"""The catch path's instruments (``stream/catch_wait.py``), small and on the
CPU: upstream's ``msg_one_task`` and ``timerProcess`` (as the benchmark's
``definitions.py`` restates them) under seeded instances and a payload made
from the seed, through a 3 x 3 x RF 3 ``ClusterRuntime`` held to the plain
reference (``benchmarks/reference.py``), and through three in-process
partitions on both paths. The four histograms (``timer_lag``,
``correlate``, ``catch``, ``catch_kernel``), the count by command kind, the
due-date sweep's and the correlation's spans and annotations."""

from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
sys.path.insert(0, str(BENCH))

import definitions as defs  # noqa: E402
import reference  # noqa: E402

from tests.test_joint_flush import Recorder  # noqa: E402
from tests.test_kernel_backend import log_fingerprint  # noqa: E402
from zeebe_tpu.logstreams import LogAppendEntry  # noqa: E402
from zeebe_tpu.observability import profiler  # noqa: E402
from zeebe_tpu.protocol import ValueType  # noqa: E402
from zeebe_tpu.protocol.intent import ProcessMessageSubscriptionIntent  # noqa: E402
from zeebe_tpu.stream.catch_wait import CORRELATIONS, CatchStamps  # noqa: E402
from zeebe_tpu.testing import (  # noqa: E402
    EngineHarness,
    MultiPartitionHarness,
    await_deployment_distributed,
)
from zeebe_tpu.utils.metrics import REGISTRY  # noqa: E402

SEED = 2**31 + 41
TIMER_MS = 400
SPECS = [
    {"kind": "message_catch", "id": "msg_one_task", "message": "msg",
     "correlation_variable": "correlationKey", "job_type": "work"},
    {"kind": "timer_catch", "id": "timerProcess", "duration_ms": TIMER_MS,
     "job_type": "work"},
]
DEFINITIONS = defs.build_definitions(SPECS)
BY_ID = {d["id"]: d for d in DEFINITIONS}
SMALL = {"strings": 2, "string_chars": 8, "numbers": 2, "nested": 1}
CREATES = 12
HISTOGRAMS = ("timer_lag", "correlate", "catch", "catch_kernel")


class Tee(Recorder):
    """Records what one processor observed and passes it on."""

    def __init__(self, child) -> None:
        super().__init__()
        self.child = child

    def observe(self, value: float) -> None:
        super().observe(value)
        self.child.observe(value)


def record_catches(stamps: CatchStamps) -> dict:
    """The processor's four histograms, recorded on their way."""
    recs = {name: Tee(getattr(stamps, f"_m_{name}")) for name in HISTOGRAMS}
    for name, rec in recs.items():
        setattr(stamps, f"_m_{name}", rec)
    return recs


def observations() -> dict:
    """Observations so far of the four histograms, over every partition."""
    out = dict.fromkeys(HISTOGRAMS, 0)
    for name, kind, _labels, value in REGISTRY.snapshot():
        stage = name.rsplit("_pipeline_", 1)[-1]
        if kind == "histogram" and "_pipeline_" in name and stage in out:
            out[stage] += value[0]
    return out


def wait_for(condition, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not condition():
        assert time.monotonic() < deadline, what
        time.sleep(0.01)


def plan() -> tuple[dict, list]:
    payload = defs.make_payload(SMALL, SEED)
    return payload, defs.request_plan(DEFINITIONS, CREATES, payload, SEED)


# ---------------------------------------------------------------------------
# the served path: three brokers, three partitions, RF 3


def serve(data_dir: Path) -> dict:
    """Seeded creates of both definitions through the gateway, two pollers
    answering with the payload, every other message published before its
    create (buffered) and the rest once the subscription is open."""
    import served

    from zeebe_tpu.client import JobWorker, ZeebeTpuClient
    from zeebe_tpu.observability.tracer import configure_tracing

    CORRELATIONS.clear()
    tracer = configure_tracing(enabled=True, seed=SEED, sample_rate=1.0,
                               capacity=1 << 18)
    payload, requests_plan = plan()
    before = observations()
    observed = served.Observed()
    system = served.Served({"brokers": 3, "partitions": 3,
                            "replication_factor": 3}, data_dir, observed)
    client = ZeebeTpuClient(system.address)
    workers = []
    try:
        client.deploy_resource(*[(f"{d['id']}.bpmn", defs.to_bpmn_xml(d))
                                 for d in DEFINITIONS])
        await_deployment_distributed(system.runtime, list(BY_ID))
        recorders, stamps = [], []
        for pid in range(1, system.partitions + 1):
            for replica in system.replicas(pid):
                processor = replica.processor
                recorders.append((replica.role.name, pid,
                                  record_catches(processor.catch_stamps)))
                stamps.append(processor.catch_stamps)
        for _ in range(2):
            worker_client = ZeebeTpuClient(system.address)
            workers.append((worker_client, JobWorker(
                worker_client, "work",
                lambda _jc, job, c=worker_client: c.complete_job(job.key, payload),
                timeout_ms=60_000, auto_complete=False,
                max_backoff_s=0.05).start()))
        requests, publishes = [], {}
        for n, (pid, variables) in enumerate(requests_plan):
            key = variables.get("correlationKey")
            if key is not None and n % 2 == 0:
                publishes[key] = client.publish_message(
                    "msg", key, variables=defs.message_variables(key))
            inst = client.create_instance(pid, variables=variables)
            requests.append((inst.process_instance_key, pid, variables))
        for _instance, pid, variables in requests:
            key = variables.get("correlationKey")
            if key is None or key in publishes:
                continue
            wait_for(lambda: ("MS", "CREATED") in [
                e[:2] for e in reference.message_sides(observed.events).get(key, [])],
                60.0, "a subscription did not open")
            publishes[key] = client.publish_message(
                "msg", key, variables=defs.message_variables(key))
        keys = {r[0] for r in requests}
        wait_for(lambda: not keys - observed.completed_at.keys(), 120.0,
                 "instances did not complete")
        spans = [s.to_dict() for s in tracer.collector.snapshot()
                 if s.name in ("duedate.sweep", "message.correlate_send")]
        kinds = {}
        for b in system.backends():
            for kind, n in b.accounting.kinds.items():
                kinds[kind] = kinds.get(kind, 0) + n
        left = (len(CORRELATIONS), sum(len(s.timer_due) for s in stamps))
        counted = {name: n - before[name] for name, n in observations().items()}
    finally:
        configure_tracing(enabled=False, reset=True)
        for worker_client, worker in workers:
            worker.stop()
            worker_client.close()
        client.close()
        system.stop()
    return {"requests": requests, "events": dict(observed.events),
            "payload": payload, "publishes": publishes,
            "recorders": recorders, "spans": spans, "kinds": kinds,
            "left": left, "counted": counted}


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    return serve(tmp_path_factory.mktemp("catches"))


def of(run: dict, pid: str) -> list:
    return [r for r in run["requests"] if r[1] == pid]


def observed(run: dict, name: str, role: str | None = None) -> list:
    return [v for r, _pid, recs in run["recorders"]
            if role is None or r == role for v in recs[name].values]


class TestServed:
    def test_every_instance_is_accepted_by_the_plain_reference(self, run):
        messages = {ck: {"key": key, "variables": defs.message_variables(ck)}
                    for ck, key in run["publishes"].items()}
        assert reference.mismatches(BY_ID, run["requests"], run["events"],
                                    run["payload"], messages) == []
        assert of(run, "msg_one_task") and of(run, "timerProcess")

    def test_both_paths_of_a_message_were_taken(self, run):
        sides = reference.message_sides(run["events"])
        paths = sorted(reference.path_of(sides[ck]) for ck in run["publishes"])
        assert set(paths) == {"buffered", "open"}

    def test_one_observation_a_trigger_or_correlation_on_the_leader_alone(self, run):
        """Three replicas of a partition: a follower or a replay that
        observed would triple the counts."""
        timers = len(of(run, "timerProcess"))
        messages = len(of(run, "msg_one_task"))
        counted = run["counted"]
        assert counted["timer_lag"] == timers
        assert counted["correlate"] == messages
        assert counted["catch"] == timers + messages
        for name in HISTOGRAMS:
            assert observed(run, name, "FOLLOWER") == [], name

    def test_a_partition_observes_the_catches_of_its_own_instances(self, run):
        for role, pid, recs in run["recorders"]:
            mine = [r for r in run["requests"] if r[0] >> 51 == pid]
            if role == "LEADER" and recs["catch"].values:
                assert len(recs["catch"].values) <= len(mine)

    def test_timer_lag_is_never_negative_and_not_a_timer_long(self, run):
        lags = observed(run, "timer_lag")
        assert lags and all(0 <= lag < 10.0 for lag in lags)

    def test_correlate_covers_the_buffered_and_the_open_path(self, run):
        waits = observed(run, "correlate")
        assert run["counted"]["correlate"] == len(run["publishes"]) >= 2
        assert waits and all(0 < w < 10.0 for w in waits)

    def test_catch_kernel_is_at_most_catch(self, run):
        catches, kernel = observed(run, "catch"), observed(run, "catch_kernel")
        assert run["counted"]["catch_kernel"] <= run["counted"]["catch"]
        assert all(w >= 0 for w in catches + kernel)

    def test_no_stamp_is_left_once_the_instances_are_done(self, run):
        assert run["left"] == (0, 0)

    def test_the_count_by_kind_has_both_paths(self, run):
        kinds = run["kinds"]
        timers = len(of(run, "timerProcess"))
        messages = len(of(run, "msg_one_task"))
        assert (kinds.get(("kernel", "TIMER.TRIGGER"), 0)
                + kinds.get(("host", "TIMER.TRIGGER"), 0)) >= timers
        assert (kinds.get(("kernel", "PROCESS_MESSAGE_SUBSCRIPTION.CORRELATE"), 0)
                + kinds.get(("host", "PROCESS_MESSAGE_SUBSCRIPTION.CORRELATE"), 0)
                ) == messages
        assert kinds.get(("host", "MESSAGE.PUBLISH"), 0) == messages
        assert kinds.get(("kernel", "PROCESS_INSTANCE_CREATION.CREATE"), 0) >= 1

    def test_the_sweep_and_the_send_have_their_spans(self, run):
        sweeps = [s for s in run["spans"] if s["name"] == "duedate.sweep"]
        sends = [s for s in run["spans"] if s["name"] == "message.correlate_send"]
        assert sweeps and all(set(s["attrs"]) == {
            "partition", "timersTriggered", "messagesExpired"} for s in sweeps)
        assert sum(s["attrs"]["timersTriggered"] for s in sweeps) >= len(
            of(run, "timerProcess"))
        assert len(sends) == len(run["publishes"])
        instances = {r[0] for r in of(run, "msg_one_task")}
        for span in sends:
            attrs = span["attrs"]
            assert set(attrs) == {"partition", "receiverPartition",
                                  "processInstanceKey", "messageName"}
            assert attrs["processInstanceKey"] in instances
            assert attrs["receiverPartition"] == attrs["processInstanceKey"] >> 51
            assert attrs["messageName"] == "msg"
            assert span["traceId"].startswith(f"{attrs['partition']}:")


# ---------------------------------------------------------------------------
# three in-process partitions, the sequential engine against the kernel path


def drive(cluster: MultiPartitionHarness, payload: dict) -> None:
    cluster.deploy(*[defs.to_bpmn_xml(d) for d in DEFINITIONS])
    _payload, requests_plan = plan()
    waiting = []
    for n, (pid, variables) in enumerate(requests_plan):
        key = variables.get("correlationKey")
        if key is not None and n % 2 == 0:
            cluster.publish_message("msg", key,
                                    variables=defs.message_variables(key))
        cluster.create_instance(pid, variables)
        if key is not None and n % 2:
            waiting.append(key)
    for key in waiting:
        cluster.publish_message("msg", key, variables=defs.message_variables(key))
    cluster.advance_time(TIMER_MS + 100)
    for _ in range(4):
        for h in cluster.partitions.values():
            for job in h.activate_jobs("work", max_jobs=100):
                h.complete_job(job["key"], payload)


def in_process(use_kernel: bool) -> dict:
    CORRELATIONS.clear()
    cluster = MultiPartitionHarness(partition_count=3,
                                    use_kernel_backend=use_kernel)
    try:
        recs = {pid: record_catches(h.processor.catch_stamps)
                for pid, h in cluster.partitions.items()}
        payload, _plan = plan()
        drive(cluster, payload)
        logs = {pid: log_fingerprint(h) for pid, h in cluster.partitions.items()}
        kernel = sum(h.kernel_backend.commands_processed
                     for h in cluster.partitions.values()) if use_kernel else 0
        return {"logs": logs, "recs": recs, "kernel": kernel,
                "left": len(CORRELATIONS)}
    finally:
        cluster.close()


@pytest.fixture(scope="module")
def both():
    return {"sequential": in_process(False), "kernel": in_process(True)}


CATCH_TYPES = {"TIMER", "PROCESS_MESSAGE_SUBSCRIPTION", "MESSAGE_SUBSCRIPTION",
               "MESSAGE"}


def test_the_two_paths_write_the_same_catch_records(both):
    for pid in (1, 2, 3):
        seq = [r for r in both["sequential"]["logs"][pid] if r[5] in CATCH_TYPES]
        ker = [r for r in both["kernel"]["logs"][pid] if r[5] in CATCH_TYPES]
        assert ker == seq, pid
    assert both["kernel"]["kernel"] > 0


@pytest.mark.parametrize("path", ["sequential", "kernel"])
def test_each_catch_is_observed_once_by_its_path(both, path):
    run = both[path]
    timers = sum(1 for pid, _v in plan()[1] if pid == "timerProcess")
    messages = CREATES - timers
    total = {name: sum(len(r[name].values) for r in run["recs"].values())
             for name in HISTOGRAMS}
    assert total["timer_lag"] == timers and total["correlate"] == messages
    assert total["catch"] == timers + messages
    assert total["catch_kernel"] == (0 if path == "sequential" else total["catch"])
    # the controlled clock: every timer was created at the start and swept
    # TIMER_MS + 100 later
    lags = [v for r in run["recs"].values() for v in r["timer_lag"].values]
    assert lags == [pytest.approx(0.1)] * timers
    assert run["left"] == 0


# ---------------------------------------------------------------------------
# a correlation that never arrives: its stamp goes with its message or its
# subscription


class HeldCorrelations:
    """A sender that keeps every ``PROCESS_MESSAGE_SUBSCRIPTION CORRELATE``
    and loops everything else back into the partition's own log."""

    def __init__(self) -> None:
        self.harness = None
        self.held: list = []

    def send_command(self, _receiver: int, record) -> None:
        if (record.value_type == ValueType.PROCESS_MESSAGE_SUBSCRIPTION
                and record.intent == ProcessMessageSubscriptionIntent.CORRELATE):
            self.held.append(record)
            return
        self.harness.stream.writer.try_write([LogAppendEntry(record)])


@pytest.fixture()
def held():
    CORRELATIONS.clear()
    sender = HeldCorrelations()
    h = EngineHarness(sender=sender)
    sender.harness = h
    h.deploy(defs.to_bpmn_xml(BY_ID["msg_one_task"]))
    key = h.create_instance("msg_one_task", {"correlationKey": "held-1"})
    yield h, sender, key
    h.close()
    CORRELATIONS.clear()


def test_a_stamp_goes_when_its_message_expires(held):
    h, sender, _key = held
    h.publish_message("msg", "held-1", ttl=1_000)
    assert len(sender.held) == 1 and len(CORRELATIONS) == 1
    h.advance_time(2_000)
    assert len(CORRELATIONS) == 0


def test_a_stamp_goes_when_its_subscription_is_deleted(held):
    h, sender, key = held
    h.publish_message("msg", "held-1")
    assert len(sender.held) == 1 and len(CORRELATIONS) == 1
    h.cancel_instance(key)
    assert len(CORRELATIONS) == 0


def test_a_stamp_without_its_command_is_not_observed():
    stamps = CatchStamps("catch-test")
    recs = record_catches(stamps)
    CORRELATIONS.clear()
    CORRELATIONS.sent(7, 9)
    CORRELATIONS.drop_messages([9])
    CORRELATIONS.sent(8, 10)
    CORRELATIONS.drop_element(8)
    assert len(CORRELATIONS) == 0
    stamps.processed([], lambda _p: None, lambda: 0, kernel=True)
    assert all(r.values == [] for r in recs.values())


def test_the_two_phases_are_annotated_under_the_prefix():
    assert profiler.CATCH_PHASES == ("due_sweep", "correlate_send")
    assert not set(profiler.CATCH_PHASES) & set(
        profiler.PHASES + profiler.REQUEST_PHASES)
    for phase in profiler.CATCH_PHASES:
        annotation = profiler.phase_annotation(phase)
        assert annotation is not None
        with annotation:
            pass


def test_the_histograms_and_the_count_by_kind_are_on_metrics(both):
    names = {name for name, _kind, _labels, _value in REGISTRY.snapshot()}
    for stage in HISTOGRAMS:
        assert f"zeebe_stream_processor_pipeline_{stage}" in names
    kinds = {labels for name, _kind, labels, _value in REGISTRY.snapshot()
             if name.endswith("kernel_records_by_kind_total")}
    assert any('path="kernel"' in k and 'kind="TIMER.TRIGGER"' in k for k in kinds)
