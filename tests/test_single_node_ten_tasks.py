"""The documented single-node broker (1 broker, 1 partition, RF 1) running
upstream's ``ten_tasks`` process through the served path, small and on the
CPU: what ``single1x1.ten_tasks_steady`` (BENCHMARK.json) measures on the
chip, held here to the same comparison — the benchmark's plain reference, the
replica's Raft log read from disk, and the sequential engine as the oracle of
the kernel path's log."""

from __future__ import annotations

import json
import re
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks"
sys.path.insert(0, str(BENCH))

import definitions as defs  # noqa: E402
import reference  # noqa: E402

from zeebe_tpu.logstreams import LogAppendEntry  # noqa: E402
from zeebe_tpu.testing import EngineHarness  # noqa: E402
from zeebe_tpu.utils.metrics import REGISTRY  # noqa: E402

TRAFFIC = json.loads((BENCH / "traffic" / "ten_tasks_steady.json").read_text())
LAYOUT = json.loads((BENCH / "configs" / "zeebe-single-node-1x1.json")
                    .read_text())["layout"]
DEFINITIONS = defs.build_definitions(TRAFFIC["definitions"])
TEN_TASKS = DEFINITIONS[0]
JOB_TYPE = "work_ten_tasks"
SMALL = {"strings": 2, "string_chars": 8, "numbers": 2, "nested": 1}
SEED = 28
CREATES = 24


def family(name: str) -> dict:
    """label values -> value of one metric family; a histogram's value is
    (count, sum)."""
    return {tuple(re.findall(r'"([^"]*)"', labels)):
            value[:2] if kind == "histogram" else value
            for n, kind, labels, value in REGISTRY.snapshot()
            if n.endswith(name)}


@pytest.fixture(scope="module")
def served_run(tmp_path_factory):
    """24 seeded creates and their 240 job completions through gateway,
    partition, kernel backend and exporter of a 1 x 1 x RF 1 cluster, four
    workers answering with the payload; then everything the comparison
    reads, the Raft log from disk once the cluster is stopped."""
    import run
    import served

    from zeebe_tpu.client import JobWorker, ZeebeTpuClient
    from zeebe_tpu.observability.tracer import configure_tracing

    assert (LAYOUT["brokers"], LAYOUT["partitions"],
            LAYOUT["replication_factor"]) == (1, 1, 1)
    payload = defs.make_payload(SMALL, SEED)
    data_dir = tmp_path_factory.mktemp("single1x1")
    before = {name: family(name) for name in (
        "stream_processor_pipeline_admit_wait",
        "stream_processor_pipeline_export", "kernel_groups_by_bucket_total")}
    tracer = configure_tracing(enabled=True, seed=SEED, sample_rate=1.0,
                               capacity=1 << 18)
    observed = served.Observed()
    system = served.Served(LAYOUT, data_dir, observed)
    clients = [ZeebeTpuClient(system.address) for _ in range(5)]
    completed_jobs: list = []

    def complete(_job_client, job, client) -> None:
        client.complete_job(job.key, payload)
        completed_jobs.append(job.key)

    workers = []
    try:
        clients[0].deploy_resource(("ten_tasks.bpmn",
                                    defs.to_bpmn_xml(TEN_TASKS)))
        workers = [JobWorker(c, JOB_TYPE,
                             lambda jc, job, c=c: complete(jc, job, c),
                             timeout_ms=60_000, auto_complete=False,
                             max_backoff_s=0.05).start() for c in clients[1:]]
        requests = []
        for pid, variables in defs.request_plan(DEFINITIONS, CREATES, payload,
                                                SEED):
            inst = clients[0].create_instance(pid, variables=variables)
            requests.append({"ok": True, "key": inst.process_instance_key,
                             "pid": pid, "variables": variables})
        deadline = time.monotonic() + 180.0
        keys = {r["key"] for r in requests}
        while keys - observed.completed_at.keys():
            assert time.monotonic() < deadline, "instances did not complete"
            time.sleep(0.05)
        for w in workers:
            w.stop()
        backend = system.backends()[0]
        counts = {"groups": backend.groups_processed,
                  "commands": backend.commands_processed}
        marks = system.raft_marks()
        spans = [s.name for s in tracer.collector.snapshot()]
    finally:
        configure_tracing(enabled=False, reset=True)
        for w in workers:
            w.stop()
        for c in clients:
            c.close()
        system.stop()
    logs = served.replica_logs(data_dir, LAYOUT)
    verdict = run.compare(DEFINITIONS, requests, dict(observed.events),
                          dict(observed.completed_at), payload,
                          completed_jobs, logs, marks)
    after = {name: family(name) for name in before}
    return {"requests": requests, "observed": observed, "payload": payload,
            "completed_jobs": completed_jobs, "logs": logs, "marks": marks,
            "checks": verdict["numbers"], "examples": verdict["examples"],
            "counts": counts, "before": before, "after": after,
            "spans": spans, "correct": run.decide_correct(verdict["numbers"])}


def test_every_instance_is_accepted_by_the_reference(served_run):
    checks = served_run["checks"]
    assert checks["instances_compared"]["value"] == CREATES
    assert checks["reference_mismatches"]["value"] == 0, served_run["examples"]
    assert checks["acked_never_completed"]["value"] == 0
    assert served_run["observed"].differing == 0
    assert served_run["correct"]
    # ten service tasks an instance: ten jobs completed, each once
    assert len(set(served_run["completed_jobs"])) == 10 * CREATES


def test_every_acknowledgement_is_in_the_raft_log_on_disk(served_run):
    logs = served_run["logs"]
    assert list(logs) == [(1, "broker-0")]       # a quorum of one
    log = logs[(1, "broker-0")]
    assert {r["key"] for r in served_run["requests"]} <= log["created"]
    assert set(served_run["completed_jobs"]) <= log["jobs_completed"]
    assert served_run["checks"]["acks_missing_in_a_replica"]["value"] == 0
    assert served_run["checks"]["replica_log_entries_differing"]["value"] == 0
    assert max(log["entries"]) >= served_run["marks"][(1, "broker-0")] > 0


def test_the_kernel_path_ran_it_in_eleven_groups_an_instance(served_run):
    counts = served_run["counts"]
    # a create and ten completions an instance, each command in one group
    assert counts["commands"] == 11 * CREATES
    assert 0 < counts["groups"] <= counts["commands"]


def delta(served_run, name: str) -> dict:
    """What the run added to a family (the registry is the process's)."""
    before, after = served_run["before"][name], served_run["after"][name]
    return {labels: tuple(a - b for a, b in
                          zip(value, before.get(labels, (0, 0.0))))
            if isinstance(value, tuple) else value - before.get(labels, 0)
            for labels, value in after.items()}


def test_admit_wait_is_observed_once_a_command(served_run):
    waits = delta(served_run, "stream_processor_pipeline_admit_wait")
    count, seconds = waits[("1",)]
    assert count == served_run["counts"]["commands"]
    assert 0.0 < seconds < 60.0
    assert served_run["spans"].count("processor.stage.admit_wait") == count


def test_groups_are_counted_by_bucket_and_commands(served_run):
    groups = delta(served_run, "kernel_groups_by_bucket_total")
    assert sum(groups.values()) == served_run["counts"]["groups"]
    assert sum(int(labels[2]) * n for labels, n in groups.items()) == \
        served_run["counts"]["commands"]
    # (the registry is the process's: a family another test filled reads 0)
    for partition, bucket, _commands in (k for k, n in groups.items() if n):
        assert partition == "1" and bucket.startswith("I") and "xT" in bucket


def test_export_time_is_observed_once_a_record(served_run):
    count, seconds = delta(served_run,
                           "stream_processor_pipeline_export")[("1",)]
    # the small payload: some 180 records an instance (394 with the cell's)
    assert count >= served_run["observed"].records > 100 * CREATES
    assert 0.0 < seconds < 60.0


# ---------------------------------------------------------------------------
# controls: the acceptor refuses what the cell must never export


def one_instance(served_run):
    request = served_run["requests"][0]
    events = list(served_run["observed"].events[request["key"]])
    reference.accept(TEN_TASKS, request["variables"], events,
                     served_run["payload"])      # sound as it came
    return request, events


@pytest.mark.parametrize("fault", ["t7_completion_dropped",
                                   "t7_activated_twice"])
def test_the_acceptor_refuses(served_run, fault):
    request, events = one_instance(served_run)
    if fault == "t7_completion_dropped":
        at = next(i for i, e in enumerate(events)
                  if e[:3] == ("JOB", "COMPLETED", "t7"))
        broken = events[:at] + events[at + 1:]
    else:
        at = next(i for i, e in enumerate(events)
                  if e[:3] == ("PI", "ELEMENT_ACTIVATED", "t7"))
        broken = events[:at + 1] + [events[at]] + events[at + 1:]
    with pytest.raises(reference.Mismatch):
        reference.accept(TEN_TASKS, request["variables"], broken,
                         served_run["payload"])


# ---------------------------------------------------------------------------
# the kernel path against the sequential engine, the same commands


def fingerprint(h: EngineHarness) -> list:
    out = []
    for logged in h.stream.new_reader(1):
        rec = logged.record
        out.append((logged.position, logged.source_position, logged.processed,
                    rec.key, rec.record_type.name, rec.value_type.name,
                    int(rec.intent),
                    rec.rejection_type.name if rec.is_rejection else "",
                    dict(rec.value) if rec.value else {}))
    return out


def both_engines(scenario) -> tuple[list, list, dict]:
    logs, stats = [], {}
    for use_kernel in (False, True):
        h = EngineHarness(use_kernel_backend=use_kernel)
        try:
            scenario(h)
            logs.append(fingerprint(h))
            if use_kernel:
                stats = {"groups": h.kernel_backend.groups_processed,
                         "commands": h.kernel_backend.commands_processed}
        finally:
            h.close()
    return logs[0], logs[1], stats


def assert_same_log(seq_log: list, ker_log: list) -> None:
    for i, (a, b) in enumerate(zip(seq_log, ker_log)):
        assert a == b, f"first divergence at record {i}:\n seq={a}\n ker={b}"
    assert len(seq_log) == len(ker_log)


def test_kernel_log_equals_the_sequential_engines():
    payload = defs.make_payload(SMALL, SEED)

    def scenario(h):
        h.deploy(defs.to_bpmn_xml(TEN_TASKS))
        for pid, variables in defs.request_plan(DEFINITIONS, CREATES, payload,
                                                SEED):
            h.create_instance(pid, variables)
        # five jobs a round: the instances drift apart along the chain
        for _ in range(10 * CREATES):
            jobs = h.activate_jobs(JOB_TYPE, max_jobs=5)
            if not jobs:
                break
            for job in jobs:
                h.complete_job(job["key"], payload)
        else:
            pytest.fail("the job drive did not quiesce")

    seq_log, ker_log, stats = both_engines(scenario)
    assert_same_log(seq_log, ker_log)
    assert stats["commands"] == 11 * CREATES


def test_a_group_of_completions_parked_at_different_tasks():
    """The shape ``default3x3`` never forms: one group admits several ``JOB
    COMPLETE`` commands whose instances wait at different tasks of the
    chain."""
    from zeebe_tpu.protocol import ValueType, command
    from zeebe_tpu.protocol.intent import JobIntent

    payload = defs.make_payload(SMALL, SEED)
    ahead = (0, 3, 6, 9)      # completions each instance is given first
    parked: list = []

    def scenario(h):
        h.deploy(defs.to_bpmn_xml(TEN_TASKS))
        keys = [h.create_instance("ten_tasks", {"x": 5, **payload})
                for _ in ahead]
        current, done = {}, dict.fromkeys(keys, 0)
        while True:
            for job in h.activate_jobs(JOB_TYPE):
                current[job["processInstanceKey"]] = job
            movers = [k for k, n in zip(keys, ahead) if done[k] < n]
            if not movers:
                break
            for key in movers:
                h.complete_job(current[key]["key"], payload)
                done[key] += 1
        parked[:] = [(current[k]["elementId"], current[k]["key"])
                     for k in keys]
        before = (h.kernel_backend.groups_processed,
                  h.kernel_backend.commands_processed) \
            if h.kernel_backend else None
        # the four completions reach the log in one batch: one admission
        h.stream.writer.try_write([
            LogAppendEntry(command(ValueType.JOB, JobIntent.COMPLETE,
                                   {"variables": payload}, key=job_key))
            for _element, job_key in parked])
        h.pump()
        if before is not None:
            groups = h.kernel_backend.groups_processed - before[0]
            commands = h.kernel_backend.commands_processed - before[1]
            assert commands == len(ahead) and groups == 1, (groups, commands)

    seq_log, ker_log, _stats = both_engines(scenario)
    assert [element for element, _key in parked] == ["t0", "t3", "t6", "t9"]
    assert_same_log(seq_log, ker_log)
