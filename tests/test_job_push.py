"""Job push at the size of a deployment (``jobpush3x3.one_task_steady`` in
BENCHMARK.json, small and on the CPU): the served path under polling workers
and under ``stream_enabled`` ones held to the same plain reference, a gateway
with more open streams than handler threads, a dispatcher whose partitions do
not wait for each other, what happens to a job whose stream died or whose
activation timed out, and the two wait histograms."""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
sys.path.insert(0, str(BENCH))

import definitions as defs  # noqa: E402
import reference  # noqa: E402

from zeebe_tpu.client import JobWorker, ZeebeTpuClient  # noqa: E402
from zeebe_tpu.gateway import ClusterRuntime, Gateway  # noqa: E402
from zeebe_tpu.models.bpmn import Bpmn, to_bpmn_xml  # noqa: E402
from zeebe_tpu.protocol import RecordType, ValueType, command  # noqa: E402
from zeebe_tpu.protocol.intent import (  # noqa: E402
    JobBatchIntent,
    JobIntent,
    ProcessInstanceCreationIntent,
)
from zeebe_tpu.testing import await_deployment_distributed  # noqa: E402
from zeebe_tpu.utils.metrics import REGISTRY  # noqa: E402

LAYOUT = {"brokers": 3, "partitions": 3, "replication_factor": 3}
SPECS = [{"kind": "task_chain", "id": "one_task", "tasks": 1},
         {"kind": "task_chain", "id": "ten_tasks", "tasks": 10}]
DEFINITIONS = defs.build_definitions(SPECS)
SMALL = {"strings": 2, "string_chars": 8, "numbers": 2, "nested": 1}
SEED = 35
CREATES = 12
MODES = ("poll", "push")
HISTOGRAMS = ("stream_processor_pipeline_job_wait",
              "stream_processor_pipeline_job_push")


def observations(name: str) -> int:
    """Observations of one histogram so far, over every partition."""
    return sum(value[0] for n, kind, _labels, value in REGISTRY.snapshot()
               if kind == "histogram" and n.endswith(name))


def wait_for(condition, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not condition():
        assert time.monotonic() < deadline, what
        time.sleep(0.01)


def one_task(pid: str, job_type: str) -> str:
    return to_bpmn_xml(
        Bpmn.create_executable_process(pid)
        .start_event("s").service_task("t", job_type=job_type).end_event("e").done())


# ---------------------------------------------------------------------------
# the served path, both ways


def serve(mode: str, data_dir: Path) -> dict:
    """A dozen seeded creates of ``one_task`` and ``ten_tasks`` through the
    gateway of a 3 x 3 x RF 3 cluster, two workers a job type answering with
    the payload, polling or streaming."""
    import served

    from zeebe_tpu.observability.tracer import configure_tracing

    tracer = configure_tracing(enabled=True, seed=SEED, sample_rate=1.0,
                               capacity=1 << 18)
    payload = defs.make_payload(SMALL, SEED)
    before = {name: observations(name) for name in HISTOGRAMS}
    observed = served.Observed()
    system = served.Served(LAYOUT, data_dir, observed)
    clients = [ZeebeTpuClient(system.address)]
    completed_jobs: list = []

    def complete(_job_client, job, client) -> None:
        client.complete_job(job.key, payload)
        completed_jobs.append(job.key)

    workers = []
    try:
        clients[0].deploy_resource(*[(f"{d['id']}.bpmn", defs.to_bpmn_xml(d))
                                     for d in DEFINITIONS])
        await_deployment_distributed(system.runtime,
                                     [d["id"] for d in DEFINITIONS])
        for job_type in defs.job_types(DEFINITIONS):
            for _ in range(2):
                client = ZeebeTpuClient(system.address)
                clients.append(client)
                workers.append(JobWorker(
                    client, job_type,
                    lambda jc, job, client=client: complete(jc, job, client),
                    timeout_ms=60_000, auto_complete=False, max_backoff_s=0.05,
                    stream_enabled=mode == "push").start())
        requests = []
        for pid, variables in defs.request_plan(DEFINITIONS, CREATES, payload,
                                                SEED):
            inst = clients[0].create_instance(pid, variables=variables)
            requests.append({"key": inst.process_instance_key, "pid": pid,
                             "variables": variables})
        keys = {r["key"] for r in requests}
        wait_for(lambda: not keys - observed.completed_at.keys(), 180.0,
                 "instances did not complete")
        for w in workers:
            w.stop()
        stamps_left = sum(len(replica.processor.job_stamps)
                          for pid in range(1, system.partitions + 1)
                          for replica in system.replicas(pid)
                          if getattr(replica, "processor", None) is not None)
        activations = [said for said in observed.seen.values()
                       if said[:3] == (int(RecordType.COMMAND), int(ValueType.JOB_BATCH),
                                       int(JobBatchIntent.ACTIVATE))]
        pushes = [s.to_dict() for s in tracer.collector.snapshot()
                  if s.name == "jobstream.push"]
    finally:
        configure_tracing(enabled=False, reset=True)
        for w in workers:
            w.stop()
        for c in clients:
            c.close()
        system.stop()
    return {"requests": requests, "events": dict(observed.events),
            "payload": payload, "completed_jobs": completed_jobs,
            "stamps_left": stamps_left, "activations": len(activations),
            "pushes": pushes,
            "observed": {name: observations(name) - before[name]
                         for name in HISTOGRAMS}}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    return {mode: serve(mode, tmp_path_factory.mktemp(mode)) for mode in MODES}


def jobs_of(run: dict) -> int:
    by_id = {d["id"]: d for d in DEFINITIONS}
    return sum(defs.jobs_per_instance(by_id[r["pid"]]) for r in run["requests"])


def unkeyed(events: list) -> list:
    """An instance's records without what a run draws anew: its keys."""
    return [e[:3] if e[0] == "PI" else e[:4] for e in events]


@pytest.mark.parametrize("mode", MODES)
class TestServedBothWays:
    def test_every_instance_is_accepted_by_the_plain_reference(self, runs, mode):
        run = runs[mode]
        by_id = {d["id"]: d for d in DEFINITIONS}
        assert len(run["requests"]) == CREATES
        for r in run["requests"]:
            reference.accept(by_id[r["pid"]], r["variables"],
                             run["events"][r["key"]], run["payload"])

    def test_every_job_is_completed_exactly_once(self, runs, mode):
        run = runs[mode]
        completed = [e[4] for events in run["events"].values() for e in events
                     if e[:2] == ("JOB", "COMPLETED")]
        assert len(completed) == len(set(completed)) == jobs_of(run)
        assert sorted(run["completed_jobs"]) == sorted(completed)

    def test_one_job_wait_observation_an_activated_job(self, runs, mode):
        """Three replicas of a partition share its label: a replay that
        observed would triple the count."""
        run = runs[mode]
        assert run["observed"][HISTOGRAMS[0]] == jobs_of(run)

    def test_job_push_is_observed_for_pushed_jobs_alone(self, runs, mode):
        run = runs[mode]
        assert run["observed"][HISTOGRAMS[1]] == (
            jobs_of(run) if mode == "push" else 0)

    def test_a_pushed_job_has_its_span(self, runs, mode):
        run = runs[mode]
        if mode == "poll":
            assert run["pushes"] == []
            return
        assert len(run["pushes"]) == jobs_of(run)
        instances = {r["key"] for r in run["requests"]}
        for span in run["pushes"]:
            attrs = span["attrs"]
            assert set(attrs) == {"partition", "jobType", "jobKey", "streamId",
                                  "processInstanceKey"}
            assert attrs["processInstanceKey"] in instances
            assert attrs["jobKey"] in run["completed_jobs"]
            # at its real interval, in the trace of the command that
            # activated the job
            assert span["durUs"] > 0
            assert span["traceId"].startswith(f"{attrs['partition']}:")

    def test_no_stamp_is_left_once_the_instances_are_done(self, runs, mode):
        assert runs[mode]["stamps_left"] == 0

    def test_activation_is_a_command_in_the_log(self, runs, mode):
        """Push or poll, a job is handed out by a ``JOB_BATCH ACTIVATE``
        written through the log: a client's (one that lost a race for a job
        activates none) or the dispatcher's, which peeks first and is alone:
        none of its activations is empty."""
        activations = runs[mode]["activations"]
        assert activations >= 1
        if mode == "push":
            assert activations <= jobs_of(runs[mode])


def test_push_and_poll_write_the_same_records_an_instance(runs):
    """With the ``JOB_BATCH`` records set aside (the exporter keeps none of
    them as an instance's), the two runs' instances did the same."""
    def sequences(run: dict) -> list:
        return sorted((r["pid"], r["variables"]["x"],
                       unkeyed(run["events"][r["key"]]))
                      for r in run["requests"])

    assert sequences(runs["poll"]) == sequences(runs["push"])


# ---------------------------------------------------------------------------
# the gateway: streams take no handler thread


@pytest.fixture()
def stack():
    runtime = ClusterRuntime(broker_count=1, partition_count=2,
                             replication_factor=1)
    runtime.start()
    gateway = Gateway(runtime, max_workers=16)
    gateway.start()
    client = ZeebeTpuClient(gateway.address)
    yield client, runtime, gateway
    client.close()
    gateway.stop()
    runtime.stop()


def create_on(runtime, partition_id: int, process_id: str) -> int:
    record = runtime.submit(partition_id, command(
        ValueType.PROCESS_INSTANCE_CREATION,
        ProcessInstanceCreationIntent.CREATE,
        {"bpmnProcessId": process_id, "processDefinitionKey": -1,
         "version": -1, "variables": {}}))
    assert not record.is_rejection, record.rejection_reason
    return record.value["processInstanceKey"]


def test_twenty_open_streams_leave_the_unary_rpcs_their_threads(stack):
    client, runtime, _gateway = stack
    client.deploy_resource(("many.bpmn", one_task("many", "many_work")))
    await_deployment_distributed(runtime, ["many"])
    # the first instance compiles what it needs, outside the timed part
    client.create_instance("many")
    wait_for(lambda: (jobs := client.activate_jobs("many_work")) and
             client.complete_job(jobs[0].key, {}) is None, 60.0, "warm-up")
    streams = [client.open_job_stream("many_work", timeout_ms=60_000)
               for _ in range(20)]
    received: list = []
    for _call, jobs in streams:
        threading.Thread(target=lambda jobs=jobs: received.extend(jobs),
                         daemon=True).start()
    try:
        wait_for(lambda: len(runtime.job_streams._streams.get("many_work", ()))
                 == 20, 5.0, "20 streams did not register")
        answers: dict = {}

        def timed(name, call, *args) -> None:
            start = time.monotonic()
            answers[name] = (call(*args), time.monotonic() - start)

        for name, call, args in (
                ("create", client.create_instance, ("many",)),
                ("topology", client.topology, ())):
            t = threading.Thread(target=timed, args=(name, call, *args),
                                 daemon=True)
            t.start()
            t.join(5.0)
            assert name in answers, f"{name} did not answer in 5 s"
            assert answers[name][1] < 1.0
        wait_for(lambda: received, 5.0, "the job was not pushed")
        t = threading.Thread(target=timed, args=(
            "complete", client.complete_job, received[0].key, {}), daemon=True)
        t.start()
        t.join(5.0)
        assert "complete" in answers and answers["complete"][1] < 1.0
    finally:
        for call, _jobs in streams:
            call.cancel()
    wait_for(lambda: not runtime.job_streams.has_streams("many_work"), 5.0,
             "a cancelled stream stayed registered")


def test_a_stream_wakes_for_its_job_and_for_its_end_alone(stack):
    """No timed wake-up: the handler sleeps on its queue, and the call's end
    puts the marker that ends it."""
    client, runtime, _gateway = stack
    call, jobs = client.open_job_stream("idle_work", timeout_ms=60_000)
    threading.Thread(target=lambda: list(jobs), daemon=True).start()
    wait_for(lambda: runtime.job_streams.has_streams("idle_work"), 5.0,
             "the stream did not register")
    handle = runtime.job_streams._streams["idle_work"][0]
    time.sleep(0.6)
    assert handle.jobs.empty() and not handle.closed
    call.cancel()
    wait_for(lambda: handle.closed, 2.0, "the call's end did not wake it")


# ---------------------------------------------------------------------------
# the dispatcher: a partition's push does not wait for another's


def test_a_held_partition_does_not_delay_another_partitions_push(stack):
    client, runtime, _gateway = stack
    client.deploy_resource(("held.bpmn", one_task("held", "held_work")))
    await_deployment_distributed(runtime, ["held"])
    held, release = threading.Event(), threading.Event()
    submit = runtime.submit

    def holding(partition_id, record, **kw):
        if (partition_id == 1 and record.value_type == ValueType.JOB_BATCH
                and threading.current_thread().name.startswith("job-stream")):
            held.set()
            release.wait(30.0)
        return submit(partition_id, record, **kw)

    runtime.submit = holding
    handle = runtime.job_streams.add_stream("held_work", "w", 60_000)
    try:
        first = create_on(runtime, 1, "held")
        assert held.wait(5.0), "partition 1's activation was never submitted"
        second = create_on(runtime, 2, "held")
        key, job = handle.jobs.get(timeout=2.0)     # while partition 1 is held
        assert job["processInstanceKey"] == second
        assert handle.jobs.empty()
        release.set()
        key_1, job_1 = handle.jobs.get(timeout=5.0)
        assert job_1["processInstanceKey"] == first
        for job_key in (key, key_1):
            client.complete_job(job_key, {})
    finally:
        release.set()
        runtime.submit = submit
        runtime.job_streams.remove_stream(handle)


# ---------------------------------------------------------------------------
# what stands in for the poller a streaming worker does not keep


def job_events(runtime, partition_id: int, intent) -> list:
    """Keys of the partition's JOB events of one intent, from its log."""
    leader = runtime._leader_partition(partition_id)
    with runtime._partition_guard(partition_id):
        return [logged.record.key
                for logged in leader.stream.new_reader()
                if logged.record.value_type == ValueType.JOB
                and logged.record.is_event and logged.record.intent == intent]


def test_a_job_on_a_dead_streams_queue_goes_to_another_or_is_yielded(stack):
    client, runtime, _gateway = stack
    streams = runtime.job_streams
    client.deploy_resource(("dead.bpmn", one_task("dead", "dead_work")))
    await_deployment_distributed(runtime, ["dead"])
    first = streams.add_stream("dead_work", "w", 60_000)
    create_on(runtime, 1, "dead")
    wait_for(lambda: not first.jobs.empty(), 5.0, "the job was not pushed")
    # the stream dies with the job on its queue; another stream of the type
    # is open: the job moves there, no command written
    second = streams.add_stream("dead_work", "w", 60_000)
    streams.remove_stream(first)
    key, _job = second.jobs.get(timeout=2.0)
    assert job_events(runtime, 1, JobIntent.YIELDED) == []
    # that one dies too and no stream is left: the job is yielded...
    second.jobs.put((key, _job))
    streams.remove_stream(second)
    wait_for(lambda: job_events(runtime, 1, JobIntent.YIELDED) == [key], 5.0,
             "the job was not yielded")
    # ...and pushed again to the next stream that opens
    third = streams.add_stream("dead_work", "w", 60_000)
    try:
        again, _job = third.jobs.get(timeout=5.0)
        assert again == key
        client.complete_job(key, {})
        assert job_events(runtime, 1, JobIntent.COMPLETED) == [key]
    finally:
        streams.remove_stream(third)


def test_a_job_whose_activation_timed_out_is_pushed_again(stack):
    client, runtime, _gateway = stack
    streams = runtime.job_streams
    client.deploy_resource(("late.bpmn", one_task("late", "late_work")))
    await_deployment_distributed(runtime, ["late"])
    handle = streams.add_stream("late_work", "w", 300)   # ms: the deadline
    try:
        create_on(runtime, 2, "late")
        key, _job = handle.jobs.get(timeout=5.0)
        # nobody completes it: it times out and comes down the stream again
        again, _job = handle.jobs.get(timeout=10.0)
        assert again == key
        assert key in job_events(runtime, 2, JobIntent.TIMED_OUT)
        client.complete_job(key, {})
        assert job_events(runtime, 2, JobIntent.COMPLETED) == [key]
        stamps = runtime._leader_partition(2).processor.job_stamps
        wait_for(lambda: len(stamps) == 0, 2.0, "a stamp was left")
    finally:
        streams.remove_stream(handle)


# ---------------------------------------------------------------------------
# the dispatcher under many notifiers


class CountingRuntime:
    """The runtime surface the dispatcher uses, over a pool of jobs a
    partition: it counts the activations in flight a partition."""

    partition_count = 4

    def __init__(self, jobs_each: int) -> None:
        self.lock = threading.Lock()
        self.left = {p: list(range(p * 1_000_000, p * 1_000_000 + jobs_each))
                     for p in range(1, self.partition_count + 1)}
        self.in_flight = dict.fromkeys(self.left, 0)
        self.most_in_flight = 0

    def has_activatable_jobs(self, partition_id, job_type, tenant_ids=None):
        with self.lock:
            return bool(self.left[partition_id])

    def submit(self, partition_id, record, timeout_s: float = 10.0):
        from zeebe_tpu.protocol import Record

        with self.lock:
            self.in_flight[partition_id] += 1
            self.most_in_flight = max(self.most_in_flight,
                                      self.in_flight[partition_id])
            most = record.value["maxJobsToActivate"]
            keys, self.left[partition_id] = (self.left[partition_id][:most],
                                             self.left[partition_id][most:])
        time.sleep(0.001)       # the commit: other threads run meanwhile
        with self.lock:
            self.in_flight[partition_id] -= 1
        return Record(RecordType.EVENT, ValueType.JOB_BATCH,
                      JobBatchIntent.ACTIVATED,
                      {"jobKeys": keys, "jobs": [{"type": "t"} for _ in keys]})

    def partition_for_key(self, key: int) -> int:
        return key // 1_000_000

    def job_pushed(self, key: int):
        return None


def test_many_notifiers_push_every_job_once_one_activation_a_partition():
    from zeebe_tpu.gateway.jobstream import JobStreamDispatcher

    runtime = CountingRuntime(jobs_each=150)
    dispatcher = JobStreamDispatcher(runtime)
    dispatcher.start()
    streams = [dispatcher.add_stream("t", "w", 60_000) for _ in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def notify(partition_id: int) -> None:
            for _ in range(200):
                dispatcher.on_jobs_available(partition_id, {"t"})

        notifiers = [threading.Thread(target=notify, args=(p,), daemon=True)
                     for p in (1, 2, 3, 4) for _ in range(4)]
        for t in notifiers:
            t.start()
        for t in notifiers:
            t.join(30.0)
            assert not t.is_alive()
        wait_for(lambda: sum(s.jobs.qsize() for s in streams) == 600, 30.0,
                 "not every job was pushed")
    finally:
        sys.setswitchinterval(interval)
        dispatcher.stop()
    delivered = [s.jobs.get_nowait()[0] for s in streams
                 for _ in range(s.jobs.qsize())]
    assert len(delivered) == len(set(delivered)) == 600
    assert runtime.most_in_flight == 1
    assert not any(t.is_alive() for t, _wake in dispatcher._pushers.values())
