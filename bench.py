"""Flagship benchmark: one-task-process workload, kernel ceiling AND end-to-end.

Two families of numbers (BASELINE.md: >= 50k process-instance state
transitions/sec/chip on the one_task workload; reference anchor:
EngineLargeStatePerformanceTest.java:138-144 at ~450 instance round trips/s):

1. **End-to-end (the headline)**: commands written to the partition log →
   stream processor → kernel backend (device step + burst-template
   materialization) → events appended to the committed log + state store
   updated. This is the real serving path behind the gateway — journal
   appends, state mutations, response side effects included; the recording
   exporter is not wired (exporters are optional, asynchronous components).
   A "transition" is one PROCESS_INSTANCE lifecycle event appended to the
   log — the same events, keys, and values the sequential engine writes
   (byte-equality enforced by tests/test_kernel_backend.py and the 120-seed
   randomized parity suite).

2. **Kernel ceiling**: the bare automaton kernel advancing 1M instances on
   device with on-device job completion (auto_jobs) — the upper bound the
   integration is converging toward.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N/50000, "extra": {...}}
with per-workload end-to-end numbers (BASELINE.json configs: one_task,
exclusive-gateway chain, parallel fork/join, mixed ragged 8-definition) and
the kernel ceiling in "extra".
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

# virtual CPU devices for the mesh-serving section (must be set before JAX
# initializes its backends; affects only the host platform — the main
# workloads still run on the default device, TPU when reachable)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import numpy as np

from zeebe_tpu.engine import Engine
from zeebe_tpu.engine.kernel_backend import KernelBackend
from zeebe_tpu.journal import SegmentedJournal
from zeebe_tpu.logstreams import LogAppendEntry, LogStream
from zeebe_tpu.models.bpmn import Bpmn, to_bpmn_xml, transform
from zeebe_tpu.ops.automaton import DeviceTables, make_state, run_to_completion
from zeebe_tpu.ops.tables import compile_tables
from zeebe_tpu.protocol import ValueType
from zeebe_tpu.protocol.intent import (
    DeploymentIntent,
    JobIntent,
    ProcessInstanceCreationIntent,
    ProcessInstanceIntent,
)
from zeebe_tpu.protocol.record import command
from zeebe_tpu.state import ZbDb
from zeebe_tpu.stream import StreamProcessor, StreamProcessorMode

NORTH_STAR = 50_000.0


# ---------------------------------------------------------------------------
# workload definitions (BASELINE.json configs)


def one_task(pid="one_task"):
    return (
        Bpmn.create_executable_process(pid)
        .start_event("start").service_task("task", job_type=f"work_{pid}")
        .end_event("end").done()
    )


def exclusive_chain(pid="excl_chain"):
    """start → 5 exclusive gateways → end (config #2: sequence-flow-only)."""
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(5):
        b = (
            b.exclusive_gateway(f"gw{i}")
            .condition_expression(f"x > {10 * i}")
            .exclusive_gateway(f"m{i}")
            .move_to_element(f"gw{i}")
            .default_flow()
            .connect_to(f"m{i}")
            .move_to_element(f"m{i}")
        )
    return b.end_event("e").done()


def fork_join(pid="fork_join"):
    """Parallel fan-out/fan-in (config #3), service tasks on both branches."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .parallel_gateway("fork")
        .service_task("a", job_type=f"a_{pid}")
        .parallel_gateway("join")
        .end_event("e")
        .move_to_element("fork")
        .service_task("b", job_type=f"b_{pid}")
        .connect_to("join")
        .done()
    )


def ten_tasks(pid="ten_tasks"):
    """10 sequential service tasks (reference fixture:
    benchmarks/project/src/main/resources/bpmn/ten_tasks.bpmn)."""
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(10):
        b = b.service_task(f"t{i}", job_type=f"work_{pid}")
    return b.end_event("e").done()


def ten_tasks_io(pid="ten_tasks_io"):
    """ten_tasks with input+output mappings on every task — the io-mapped
    elements ride the kernel (VERDICT r2 item 5) instead of host-escaping."""
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(10):
        b = (
            b.service_task(f"t{i}", job_type=f"work_{pid}")
            .zeebe_input("= base", f"local{i}")
            .zeebe_output(f"= local{i}", f"result{i}")
        )
    return b.end_event("e").done()


def subprocess_boundary(pid="sub_bnd"):
    """Embedded sub-process + timer-boundary task (kernel scope + boundary
    wait-state paths under load)."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .sub_process("sub")
        .start_event("is_")
        .service_task("inner", job_type=f"inner_{pid}")
        .boundary_timer("tb", attached_to="inner", duration="PT1H")
        .end_event("bnd_e")
        .move_to_element("inner")
        .end_event("ie")
        .sub_process_done()
        .end_event("e")
        .done()
    )


def mixed_definitions():
    """8 ragged definitions (config #5): varying task counts and routing."""
    out = [one_task("mx_one"), exclusive_chain("mx_excl"), fork_join("mx_fj")]
    for n in (2, 3, 4):
        b = Bpmn.create_executable_process(f"mx_chain{n}").start_event("s")
        for i in range(n):
            b = b.service_task(f"t{i}", job_type=f"work_mx_chain{n}")
        out.append(b.end_event("e").done())
    b = (
        Bpmn.create_executable_process("mx_route")
        .start_event("s")
        .exclusive_gateway("gw")
        .condition_expression("x > 10")
        .service_task("big", job_type="work_mx_route")
        .end_event("e1")
        .move_to_element("gw")
        .default_flow()
        .service_task("small", job_type="work_mx_route")
        .end_event("e2")
        .done()
    )
    out.append(b)
    b = (
        Bpmn.create_executable_process("mx_par3")
        .start_event("s")
        .parallel_gateway("f")
        .service_task("p0", job_type="work_mx_par3")
        .parallel_gateway("j")
        .end_event("e")
        .move_to_element("f")
        .service_task("p1", job_type="work_mx_par3")
        .connect_to("j")
        .move_to_element("f")
        .service_task("p2", job_type="work_mx_par3")
        .connect_to("j")
        .done()
    )
    out.append(b)
    return out


# ---------------------------------------------------------------------------
# end-to-end partition (log → stream processor → kernel backend → log)


class E2EPartition:
    def __init__(self, tmpdir: str, partition_id: int = 1,
                 mesh_runner=None, durable: bool = False,
                 router="shared") -> None:
        import os as _os

        self.journal = SegmentedJournal(tmpdir)
        self.clock_now = [1_700_000_000_000]
        clock = lambda: self.clock_now[0]  # noqa: E731
        self.stream = LogStream(self.journal, partition_id=partition_id,
                                clock=clock)
        if durable:
            from zeebe_tpu.state import DurableZbDb

            self.db = DurableZbDb(_os.path.join(tmpdir, "state"))
        else:
            self.db = ZbDb()
        self.engine = Engine(self.db, partition_id=partition_id,
                             clock_millis=clock)
        from zeebe_tpu.parallel.partitioning import LoopbackCommandSender

        # single-partition bench: message-subscription opens loop back into
        # the local log (sender == receiver, as in a 1-partition deployment)
        self.engine.wire_sender(LoopbackCommandSender(
            lambda rec: self.stream.writer.try_write([LogAppendEntry(rec)])
        ))
        # group sizing is LINK-dependent: where a fetch costs a fixed floor
        # big groups amortize it; on the host backend the fetch is free and
        # a big group only pays shape padding — a
        # 300-command wave padded into the 2048/8192 bucket costs ~7x the
        # device compute of the 256/1024 one (measured: mixed_8 38k -> 61k
        # transitions/s at cap 256 on the CPU host)
        self.kernel = KernelBackend(self.engine, max_group=_group_cap(),
                                    chunk_steps=8, mesh_runner=mesh_runner,
                                    router=router)
        self.processor = StreamProcessor(
            self.stream, self.db, self.engine, clock_millis=clock,
            kernel_backend=self.kernel,
        )
        self.processor.start()

    def deploy(self, models) -> None:
        resources = [
            {"resourceName": f"{m.process_id}.bpmn", "resource": to_bpmn_xml(m)}
            for m in models
        ]
        self.stream.writer.try_write([
            LogAppendEntry(command(ValueType.DEPLOYMENT, DeploymentIntent.CREATE,
                                   {"resources": resources}))
        ])
        self.processor.run_until_idle()

    def inject_creations(self, pid: str, n: int, variables: dict) -> None:
        create = command(
            ValueType.PROCESS_INSTANCE_CREATION, ProcessInstanceCreationIntent.CREATE,
            {"bpmnProcessId": pid, "version": -1, "variables": variables},
        )
        writer = self.stream.writer
        for _ in range(n):
            writer.try_write([LogAppendEntry(create)])

    def pump(self) -> None:
        while self.processor.run_until_idle():
            pass

    def pending_job_keys(self, after_position: int) -> list[tuple[str, int, int]]:
        """Worker-side job discovery over the log — a header-filtered scan
        that builds views and decodes values for JOB CREATED records only
        (LogStream.scan_filtered)."""
        from zeebe_tpu.protocol import RecordType

        jobs = []
        for view in self.stream.scan_filtered(
                after_position + 1, int(RecordType.EVENT), int(ValueType.JOB),
                int(JobIntent.CREATED)):
            value = view.value
            jobs.append((value.get("type", ""),
                         value.get("processInstanceKey", -1), view.key))
        return jobs

    def complete_in_type_waves(self, jobs: list[tuple[str, int, int]]) -> float:
        """Complete jobs one (job type, per-instance job index) wave at a
        time — the deployment reality of one worker per type completing at
        its own pace. It is also the grouping-friendly order: the batch
        admission takes one command per instance per group, so adjacent
        same-instance completes (parallel branches of one instance) would
        degenerate groups to single commands. Returns the timed seconds."""
        waves: dict[tuple[str, int], list[int]] = {}
        per_instance: dict[tuple[str, int], int] = {}
        for job_type, pi_key, key in jobs:
            idx = per_instance.get((job_type, pi_key), 0)
            per_instance[(job_type, pi_key)] = idx + 1
            waves.setdefault((job_type, idx), []).append(key)
        writer = self.stream.writer
        elapsed = 0.0
        for wave in sorted(waves):
            t0 = time.perf_counter()
            # one append batch per wave (one frame encode pass + one fsync),
            # as a real gateway's request batching would write it
            writer.try_write([
                LogAppendEntry(command(ValueType.JOB, JobIntent.COMPLETE,
                                       {"variables": {}}, key=key))
                for key in waves[wave]
            ])
            self.pump()
            elapsed += time.perf_counter() - t0
        return elapsed

    def count_transitions(self, after_position: int) -> int:
        from zeebe_tpu.protocol import RecordType

        return sum(1 for _ in self.stream.scan_filtered(
            after_position + 1, int(RecordType.EVENT),
            int(ValueType.PROCESS_INSTANCE)))


def _coverage_block(part: "E2EPartition", models, mark: dict) -> dict:
    """Per-scenario kernel-path coverage + the static-vs-observed parity
    verdict (ISSUE 13): the classifier's per-definition prediction is
    compared against the routing the measured window actually observed —
    a predicted-eligible definition host-routing for a non-runtime reason
    (or vice versa) is a gate violation that fails the bench run."""
    from zeebe_tpu.engine.eligibility import (
        classify_definition,
        parity_violations,
    )
    from zeebe_tpu.engine.kernel_backend import KernelRegistry

    delta = part.kernel.accounting.delta_since(mark)
    total = delta["kernel"] + delta["host"]
    # ONE shared registry: the prediction must see the deployment SET the
    # runtime saw (joint SlotMap clashes, max_definitions capacity) — a
    # solo prediction would blame the classifier for set-dependent declines
    reg = KernelRegistry()
    predictions = {}
    for i, m in enumerate(models):
        report = classify_definition(transform(m), definition_key=i + 1,
                                     registry=reg)
        predictions[m.process_id] = report["eligible"]
    return {
        "coverage_pct": round(100.0 * delta["kernel"] / total, 2) if total else 100.0,
        "kernel_records": delta["kernel"],
        "host_records": delta["host"],
        "per_definition": delta["perDefinition"],
        "predicted_eligible": predictions,
        "parity_violations": parity_violations(
            predictions, delta["perDefinition"]),
    }


def run_e2e_workload(models, drives, n_instances: int, variables: dict) -> dict:
    """drives: how many job-drain rounds the workload needs (0 for pure
    routing workloads). Returns transitions/instances counts and rates plus
    the burst-template hit rate."""
    with tempfile.TemporaryDirectory() as tmpdir:
        part = E2EPartition(tmpdir)
        part.deploy(models)
        # warm the compile caches (device tables + burst templates) at BOTH
        # kernel shape buckets so the measurement reflects steady state, as
        # the reference's JMH setup does: 16/def covers the small bucket and
        # per-definition templates; one max_group-sized round covers the big
        # bucket (shapes are shared across definitions of one table set)
        warm_base = part.stream.last_position
        for m in models:
            part.inject_creations(m.process_id, 16, variables)
        part.inject_creations(models[0].process_id, part.kernel.max_group, variables)
        part.pump()
        for _ in range(drives):
            jobs = part.pending_job_keys(warm_base)
            if not jobs:
                break
            warm_base = part.stream.last_position
            part.complete_in_type_waves(jobs)
        start_position = part.stream.last_position
        coverage_mark = part.kernel.accounting.mark()
        _scope_trace_to_measurement()

        elapsed = 0.0
        t0 = time.perf_counter()
        per_def = max(1, n_instances // len(models))
        for m in models:
            part.inject_creations(m.process_id, per_def, variables)
        part.pump()
        elapsed += time.perf_counter() - t0
        # drain rounds: round R completes the jobs created since the last
        # scan base (round 1 = everything the creation pump produced)
        scan_from = start_position
        for _ in range(drives):
            jobs = part.pending_job_keys(scan_from)
            if not jobs:
                break
            scan_from = part.stream.last_position
            elapsed += part.complete_in_type_waves(jobs)
        assert not part.pending_job_keys(scan_from), "workload did not drain"
        transitions = part.count_transitions(start_position)
        total_instances = per_def * len(models)
        coverage = _coverage_block(part, models, coverage_mark)
        part.journal.close()
        return {
            "transitions_per_sec": round(transitions / elapsed, 1),
            "instances_per_sec": round(total_instances / elapsed, 1),
            "transitions": transitions,
            "instances": total_instances,
            "template_hit_rate": round(
                part.kernel.template_hits
                / max(1, part.kernel.template_hits + part.kernel.template_misses
                      + part.kernel.fallbacks), 3),
            # ISSUE 13: records on the kernel path / total routed, over the
            # measured window, plus the static-vs-observed parity verdict
            "kernel_coverage": coverage,
        }


def _scope_trace_to_measurement() -> None:
    """Drop warm-phase spans so a traced scenario's critical-path artifact
    covers ONLY the measured window — the warmup's XLA compiles would
    otherwise own the scenario's p99 (ISSUE 19)."""
    from zeebe_tpu.observability import get_tracer

    tracer = get_tracer()
    if tracer.enabled:
        tracer.collector.clear()


def adversarial_gateway(pid="adv_gw"):
    """Routing on a per-instance-unique variable: every instance's condition
    input differs, so burst-template fingerprints can never collide."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .exclusive_gateway("gw")
        .condition_expression("x > 500000")
        .service_task("hi", job_type=f"hi_{pid}")
        .end_event("e1")
        .move_to_element("gw")
        .default_flow()
        .service_task("lo", job_type=f"lo_{pid}")
        .end_event("e2")
        .done()
    )


def adversarial_message(pid="adv_msg"):
    """Per-instance-unique message correlation keys — correlation state and
    subscriptions cannot share templates across instances."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .service_task("t", job_type=f"work_{pid}")
        .intermediate_catch_message("wait", "adv_pay", "=uid")
        .end_event("e")
        .done()
    )


def run_adversarial_cold(n_instances: int = 1200) -> dict:
    """VERDICT r4 item 4: the ~0% template-hit workload. Per-instance unique
    variable values feed a device condition (pinned → unique fingerprints)
    and unique message correlation keys; completions write unique result
    variables. This is the engine's honest worst case — every burst pays
    capture instead of template patching (reference baseline shape:
    EngineLargeStatePerformanceTest.java:138-144 stresses cold state)."""
    from zeebe_tpu.protocol.intent import MessageIntent

    with tempfile.TemporaryDirectory() as tmpdir:
        part = E2EPartition(tmpdir)
        part.deploy([adversarial_gateway(), adversarial_message()])
        # warm compile caches only (shapes, not templates — those can't hit)
        for pid in ("adv_gw", "adv_msg"):
            for i in range(8):
                part.inject_creations(pid, 1, {"x": 990_000 + i,
                                               "uid": f"w-{pid}-{i}"})
        part.pump()
        jobs = part.pending_job_keys(0)
        part.complete_in_type_waves(jobs)
        for i in range(8):
            part.stream.writer.try_write([LogAppendEntry(command(
                ValueType.MESSAGE, MessageIntent.PUBLISH,
                {"name": "adv_pay", "correlationKey": f"w-adv_msg-{i}",
                 "timeToLive": 60_000, "variables": {}}))])
        part.pump()
        start_position = part.stream.last_position
        part.kernel.template_hits = part.kernel.template_misses = 0
        coverage_mark = part.kernel.accounting.mark()

        per_def = n_instances // 2
        elapsed = 0.0
        t0 = time.perf_counter()
        for i in range(per_def):
            part.inject_creations("adv_gw", 1, {"x": i * 997, "uid": f"g-{i}"})
            part.inject_creations("adv_msg", 1, {"uid": f"m-{i}"})
        part.pump()
        elapsed += time.perf_counter() - t0
        # drive jobs with UNIQUE completion variables (no completion template
        # collisions either)
        scan_from = start_position
        for _ in range(3):
            jobs = part.pending_job_keys(scan_from)
            if not jobs:
                break
            scan_from = part.stream.last_position
            writer = part.stream.writer
            t0 = time.perf_counter()
            for n, (_jt, _pi, key) in enumerate(jobs):
                writer.try_write([LogAppendEntry(command(
                    ValueType.JOB, JobIntent.COMPLETE,
                    {"variables": {"result": f"r-{n}"}}, key=key))])
            part.pump()
            elapsed += time.perf_counter() - t0
        # correlate every adv_msg instance with its unique key
        t0 = time.perf_counter()
        for i in range(per_def):
            part.stream.writer.try_write([LogAppendEntry(command(
                ValueType.MESSAGE, MessageIntent.PUBLISH,
                {"name": "adv_pay", "correlationKey": f"m-{i}",
                 "timeToLive": 60_000, "variables": {"paid": i}}))])
        part.pump()
        elapsed += time.perf_counter() - t0
        transitions = part.count_transitions(start_position)
        hits, misses = part.kernel.template_hits, part.kernel.template_misses
        coverage = _coverage_block(
            part, [adversarial_gateway(), adversarial_message()],
            coverage_mark)
        part.journal.close()
        return {
            "transitions_per_sec": round(transitions / elapsed, 1),
            "instances_per_sec": round(n_instances / elapsed, 1),
            "transitions": transitions,
            "instances": n_instances,
            "template_hit_rate": round(hits / max(1, hits + misses), 3),
            "kernel_coverage": coverage,
        }


def run_one_task_warm_large_state(n_warm: int = 200_000) -> dict:
    """VERDICT r4 item 4: one_task on the DURABLE backend with ~200k
    instances of pre-existing state (≥0.5 GB serialized) — the reference's
    large-state baseline shape (EngineLargeStatePerformanceTest: 200k
    instances of pre-existing state, ~450 round trips/s). Warm state is
    seeded as realistic parked-instance entries (element instance + job +
    variables per instance), then the standard one_task flow is measured on
    top of it."""
    from zeebe_tpu.state import ColumnFamilyCode

    with tempfile.TemporaryDirectory() as tmpdir:
        part = E2EPartition(tmpdir, durable=True)
        part.deploy([one_task("one_task_warm")])
        payload = "y" * 2600  # 3 entries/instance x 200k -> >= 0.5 GB serialized
        base_key = 1 << 40  # far above the engine's key space
        for start in range(0, n_warm, 10_000):
            with part.db.transaction():
                ei = part.db.column_family(ColumnFamilyCode.ELEMENT_INSTANCE_KEY)
                jobs = part.db.column_family(ColumnFamilyCode.JOBS)
                variables = part.db.column_family(ColumnFamilyCode.VARIABLES)
                for i in range(start, start + 10_000):
                    k = base_key + i * 4
                    ei.put((k,), {"state": 4, "elementId": "warm_task",
                                  "processInstanceKey": k, "jobKey": k + 1})
                    jobs.put((k + 1,), {"type": "warm_fake", "retries": 3,
                                        "elementInstanceKey": k,
                                        "processInstanceKey": k})
                    variables.put((k, "payload"), payload)
        part.db.checkpoint()
        state_bytes = part.db.approx_bytes()

        warm_base = part.stream.last_position
        part.inject_creations("one_task_warm", 16, {})
        part.inject_creations("one_task_warm", part.kernel.max_group, {})
        part.pump()
        part.complete_in_type_waves(part.pending_job_keys(warm_base))
        start_position = part.stream.last_position

        n_instances = 3000
        elapsed = 0.0
        t0 = time.perf_counter()
        part.inject_creations("one_task_warm", n_instances, {})
        part.pump()
        elapsed += time.perf_counter() - t0
        jobs = part.pending_job_keys(start_position)
        elapsed += part.complete_in_type_waves(jobs)
        transitions = part.count_transitions(start_position)
        part.db.close()
        part.journal.close()
        return {
            "transitions_per_sec": round(transitions / elapsed, 1),
            "instances_per_sec": round(n_instances / elapsed, 1),
            "transitions": transitions,
            "instances": n_instances,
            "warm_state_entries": n_warm * 3,
            "warm_state_bytes": state_bytes,
            "template_hit_rate": round(
                part.kernel.template_hits
                / max(1, part.kernel.template_hits + part.kernel.template_misses
                      + part.kernel.fallbacks), 3),
        }


def run_one_task_on_chip(n_instances: int = 2000) -> dict:
    """one_task with the link-aware router DISABLED so every group runs on
    the default (accelerator) backend — the on-chip e2e evidence, whatever
    the router would have preferred on the measured link. Only meaningful
    when the resolved platform
    is a real accelerator; the caller gates on that."""
    with tempfile.TemporaryDirectory() as tmpdir:
        part = E2EPartition(tmpdir, router=None)
        part.deploy([one_task("one_task_chip")])
        warm_base = part.stream.last_position
        part.inject_creations("one_task_chip", 16, {})
        part.inject_creations("one_task_chip", part.kernel.max_group, {})
        part.pump()
        part.complete_in_type_waves(part.pending_job_keys(warm_base))
        start_position = part.stream.last_position
        elapsed = 0.0
        t0 = time.perf_counter()
        part.inject_creations("one_task_chip", n_instances, {})
        part.pump()
        elapsed += time.perf_counter() - t0
        elapsed += part.complete_in_type_waves(
            part.pending_job_keys(start_position))
        transitions = part.count_transitions(start_position)
        part.journal.close()
        return {
            "transitions_per_sec": round(transitions / elapsed, 1),
            "transitions": transitions,
            "instances": n_instances,
            "groups_on_default_device": part.kernel.groups_processed,
        }


#: measured load per partition for the mesh-serving modes — shared so the
#: gate's cpu-pinned baseline can never measure a different load than the
#: worker runs it is compared against
MESH_PER_PARTITION = 800


def run_mesh_serving(n_partitions: int, per_partition: int = MESH_PER_PARTITION,
                     batch_window_s: float = 0.0, workers: int = 0) -> dict:
    """Multi-partition mesh serving (SURVEY §2.13 row 1; VERDICT r3 item 2):
    ``n_partitions`` partitions, each owned by its own thread (the broker's
    per-partition ownership model), submit kernel groups to ONE shared
    MeshKernelRunner — partition = shard block of one device mesh dispatch.
    Coalescing is NATURAL (batch_window_s=0): groups pile up in the runner's
    queue while the device is busy, exactly as in serving. Reports the
    aggregate one_task transitions/s across partitions plus the runner's
    dispatch/coalescing counters.

    Devices: real ones when several are attached; otherwise the virtual
    8-device host mesh (XLA_FLAGS above) — same sharded program either way.

    ``batch_window_s``: 0 measures NATURAL coalescing. On a single-core
    host, group preparation (Python, GIL-held) far exceeds device time, so
    partition threads rarely overlap inside submit() and natural coalescing
    reads ~0 — that is a property of the 1-vCPU CI box, not the design
    (multi-core hosts overlap admission and pile onto the busy device). The
    windowed variant (a few ms) bounds the latency cost of forcing the
    overlap and PROVES the dispatch amortization: dispatches < groups.

    ``workers > 1``: the ISSUE 7 scale-out shape — partitions split across
    ``workers`` WORKER PROCESSES (one per core), each worker hosting its
    share as threads over its own shared MeshKernelRunner, so the GIL stops
    being the cluster scheduler and partition throughput adds across
    cores."""
    if workers > 1:
        return _run_mesh_serving_workers(n_partitions, per_partition, workers,
                                         batch_window_s=batch_window_s)
    from jax.sharding import Mesh

    from zeebe_tpu.parallel.mesh_runner import MeshKernelRunner

    from zeebe_tpu.utils import backend

    devices = backend.devices()
    if len(devices) < n_partitions:
        devices = jax.devices("cpu")
    if len(devices) < n_partitions:
        return {"skipped": f"{len(devices)} devices < {n_partitions}"}
    from zeebe_tpu.parallel.mesh import BATCH_AXIS

    mesh = Mesh(np.array(devices[:n_partitions]), (BATCH_AXIS,))
    runner = MeshKernelRunner(mesh=mesh, batch_window_s=batch_window_s,
                              adaptive_window=batch_window_s > 0)

    import contextlib

    with contextlib.ExitStack() as stack:
        parts = []
        for p in range(n_partitions):
            tmpdir = stack.enter_context(tempfile.TemporaryDirectory())
            part = E2EPartition(tmpdir, partition_id=p + 1, mesh_runner=runner)
            part.deploy([one_task()])
            parts.append(part)
        transitions, elapsed, reasons = _drive_mesh_partitions(
            parts, runner, per_partition)
        for p in parts:
            p.journal.close()
    out = {
        "partitions": n_partitions,
        "aggregate_transitions_per_sec": round(transitions / elapsed, 1),
        "transitions": transitions,
        "dispatches": runner.dispatches,
        "groups_dispatched": runner.groups_dispatched,
        "coalesced_dispatches": runner.coalesced_dispatches,
        "natural_coalescing_rate": round(
            runner.coalesced_dispatches / max(1, runner.dispatches), 3),
        "fallbacks": sum(p.kernel.fallbacks for p in parts),
        # why (VERDICT r4 item 5, precise since ISSUE 7):
        # head-sequential:<kind> = ordinary sequential traffic at the group
        # boundary; head-not-admittable:<kind> = an admittable command kind
        # failed admission (a regression signal); end-of-log probes count
        # nothing
        "fallback_reasons": reasons,
        "windows_slept": runner.windows_slept,
        "windows_skipped": runner.windows_skipped,
    }
    if n_partitions > 1 and _PLATFORM.startswith("cpu"):
        # every virtual mesh device shares ONE physical core here: N
        # partitions' Python AND their shards' compute serialize, so the
        # aggregate cannot exceed the single-partition rate — the curve
        # measures dispatch-coalescing overhead, not hardware scaling
        # (which needs N real chips; see __graft_entry__.dryrun_multichip
        # for the sharding-correctness evidence)
        out["note"] = "single-core host: shards serialize; not a scaling measurement"
    return out


# ---------------------------------------------------------------------------
# worker-process mesh serving (ISSUE 7): partitions across per-core processes


def _drive_mesh_partitions(parts, runner, per_partition: int,
                           wait_for_go=None) -> tuple[int, float, dict]:
    """THE mesh-serving measurement protocol, shared by the threaded and the
    worker-process modes so the two can never drift: warm every partition
    CONCURRENTLY (the sharded program compiles for the coalesced batch
    shapes the measured run will see), reset the runner's and kernels'
    counters, optionally block on a start barrier, then drive the measured
    load concurrently. Returns (transitions, elapsed_s, fallback_reasons)
    over the measured window."""
    # a thread dying would silently undercount the aggregate — collect and
    # re-raise instead
    errors: list[BaseException] = []

    def guarded(fn, *args) -> None:
        try:
            fn(*args)
        except BaseException as exc:  # noqa: BLE001 — re-raised below
            errors.append(exc)

    def warm(part: E2EPartition) -> None:
        base = part.stream.last_position
        part.inject_creations("one_task", 16, {})
        part.inject_creations("one_task", part.kernel.max_group, {})
        part.pump()
        part.complete_in_type_waves(part.pending_job_keys(base))

    threads = [threading.Thread(target=guarded, args=(warm, p))
               for p in parts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]

    start_positions = [p.stream.last_position for p in parts]
    runner.dispatches = runner.groups_dispatched = 0
    runner.coalesced_dispatches = 0
    runner.windows_slept = runner.windows_skipped = 0
    for p in parts:
        p.kernel.fallbacks = 0
        p.kernel.fallback_reasons.clear()
    if wait_for_go is not None:
        wait_for_go()

    def drive(part: E2EPartition, start_position: int) -> None:
        part.inject_creations("one_task", per_partition, {})
        part.pump()
        part.complete_in_type_waves(part.pending_job_keys(start_position))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=guarded, args=(drive, p, sp))
               for p, sp in zip(parts, start_positions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    if errors:
        raise errors[0]
    transitions = sum(
        p.count_transitions(sp) for p, sp in zip(parts, start_positions))
    reasons: dict[str, int] = {}
    for p in parts:
        for reason, count in p.kernel.fallback_reasons.items():
            reasons[reason] = reasons.get(reason, 0) + count
    return transitions, elapsed, reasons


def _split_partitions(n_partitions: int, workers: int) -> list[int]:
    base, extra = divmod(n_partitions, workers)
    return [base + (1 if i < extra else 0) for i in range(workers)]


def _run_mesh_serving_workers(n_partitions: int, per_partition: int,
                              workers: int,
                              batch_window_s: float = 0.0) -> dict:
    """Partitions split over ``workers`` worker PROCESSES, started together
    against a go-file barrier so the measured window covers genuinely
    concurrent serving. Each worker runs its share of partitions exactly as
    the threaded mode does (own journals, shared in-process
    MeshKernelRunner, natural coalescing); the aggregate is total
    transitions over the parent-measured wall window from GO to the last
    worker's result line — per-core processes are what make the aggregate
    additive (the GIL serialized the threaded mode)."""
    import shutil
    import subprocess

    workers = min(workers, n_partitions)
    sizes = [k for k in _split_partitions(n_partitions, workers) if k > 0]
    workdir = tempfile.mkdtemp(prefix="zb-mesh-workers-")
    go_file = os.path.join(workdir, "go")
    procs: list[subprocess.Popen] = []
    ready_files = []
    stderr_logs: list = []

    def stderr_tail(i: int, limit: int = 1500) -> str:
        try:
            with open(os.path.join(workdir, f"worker-{i}.stderr")) as f:
                return f.read()[-limit:]
        except OSError:
            return "<no stderr captured>"

    try:
        base = 0
        for i, k in enumerate(sizes):
            ready = os.path.join(workdir, f"ready-{i}")
            ready_files.append(ready)
            spec = {"partitions": k, "per_partition": per_partition,
                    "partition_base": base, "ready_file": ready,
                    "go_file": go_file, "batch_window_s": batch_window_s}
            base += k
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            # the worker's private virtual mesh: exactly its shard count
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if not f.startswith(
                         "--xla_force_host_platform_device_count=")]
            flags.append(f"--xla_force_host_platform_device_count={max(k, 1)}")
            env["XLA_FLAGS"] = " ".join(flags)
            # stderr to a file: a worker crashing during jax init or warm-up
            # must leave evidence (same rule as WorkerSupervisor's worker.log)
            log = open(os.path.join(workdir, f"worker-{i}.stderr"), "wb")
            stderr_logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--mesh-worker-spec", json.dumps(spec)],
                env=env, text=True,
                stdout=subprocess.PIPE, stderr=log))
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if all(os.path.exists(r) for r in ready_files):
                break
            for i, p in enumerate(procs):
                if p.poll() is not None:
                    raise RuntimeError(
                        f"mesh worker {i} died before ready "
                        f"(rc={p.returncode}); stderr tail:\n{stderr_tail(i)}")
            time.sleep(0.01)
        else:
            raise RuntimeError("mesh workers never became ready")
        t0 = time.perf_counter()
        with open(go_file, "w") as f:
            f.write("go")
        # each worker prints ONE result line right after its measured
        # section (before teardown); collect arrival-stamped lines
        results: list[dict | None] = [None] * len(procs)
        arrivals: list[float] = [0.0] * len(procs)
        errors: list[BaseException] = []

        def collect(i: int, proc: subprocess.Popen) -> None:
            try:
                line = proc.stdout.readline()
                arrivals[i] = time.perf_counter()
                results[i] = json.loads(line)
            except BaseException as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)

        threads = [threading.Thread(target=collect, args=(i, p))
                   for i, p in enumerate(procs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        if errors or any(r is None for r in results):
            tails = "\n".join(
                f"worker {i}: {stderr_tail(i)}"
                for i, r in enumerate(results) if r is None)
            raise RuntimeError(
                f"mesh worker results incomplete: {errors}\n{tails}")
        wall = max(arrivals) - t0
        transitions = sum(r["transitions"] for r in results)
        reasons: dict[str, int] = {}
        for r in results:
            for reason, count in r["fallback_reasons"].items():
                reasons[reason] = reasons.get(reason, 0) + count
        out = {
            "partitions": n_partitions,
            "workers": len(sizes),
            "partitions_per_worker": sizes,
            "mode": "worker-processes",
            # workers are PINNED to the cpu host platform (a chip belongs to
            # one process, so per-core processes cannot share it); recorded
            # so a run whose
            # other sections measured a real accelerator can't silently mix
            # backends in one comparison
            "worker_platform": "cpu",
            "aggregate_transitions_per_sec": round(transitions / wall, 1),
            "transitions": transitions,
            "wall_seconds": round(wall, 3),
            "dispatches": sum(r["dispatches"] for r in results),
            "groups_dispatched": sum(r["groups_dispatched"] for r in results),
            "coalesced_dispatches": sum(
                r["coalesced_dispatches"] for r in results),
            "natural_coalescing_rate": round(
                sum(r["coalesced_dispatches"] for r in results)
                / max(1, sum(r["dispatches"] for r in results)), 3),
            "fallbacks": sum(r["fallbacks"] for r in results),
            "fallback_reasons": reasons,
            "windows_slept": sum(r.get("windows_slept", 0) for r in results),
            "windows_skipped": sum(r.get("windows_skipped", 0)
                                   for r in results),
            **({"batch_window_s": batch_window_s} if batch_window_s else {}),
            "per_worker_transitions_per_sec": [
                r["transitions_per_sec"] for r in results],
        }
        if not _PLATFORM.startswith("cpu"):
            out["note"] = ("workers pinned to cpu: NOT comparable to this "
                           "run's accelerator-measured partition rates")
        return out
    finally:
        for log in stderr_logs:
            try:
                log.close()
            except OSError:
                pass
        for p in procs:
            if p.poll() is None:
                p.kill()
        shutil.rmtree(workdir, ignore_errors=True)


def _mesh_worker_main(spec: dict) -> None:
    """Child entry for worker-process mesh serving: host ``spec['partitions']``
    partitions as threads over one shared MeshKernelRunner, warm, signal
    ready, wait for the go file, drive the measured load, print ONE result
    JSON line on stdout."""
    import contextlib

    jax.config.update("jax_platforms", "cpu")
    from zeebe_tpu.parallel.mesh_runner import MeshKernelRunner

    k = spec["partitions"]
    base = spec.get("partition_base", 0)
    window = spec.get("batch_window_s", 0.0)
    runner = MeshKernelRunner(n_shards=min(k, len(jax.devices("cpu"))),
                              batch_window_s=window,
                              adaptive_window=window > 0)

    def wait_for_go() -> None:
        with open(spec["ready_file"], "w") as f:
            f.write("ready")
        deadline = time.monotonic() + 600
        while not os.path.exists(spec["go_file"]):
            if time.monotonic() > deadline:
                raise RuntimeError("go file never appeared")
            time.sleep(0.002)

    with contextlib.ExitStack() as stack:
        parts = []
        for p in range(k):
            tmpdir = stack.enter_context(tempfile.TemporaryDirectory())
            part = E2EPartition(tmpdir, partition_id=base + p + 1,
                                mesh_runner=runner)
            part.deploy([one_task()])
            parts.append(part)
        transitions, elapsed, reasons = _drive_mesh_partitions(
            parts, runner, spec["per_partition"], wait_for_go=wait_for_go)
        # the result line goes out BEFORE teardown so the parent's wall
        # window excludes interpreter/journal shutdown
        print(json.dumps({
            "partitions": k,
            "transitions": transitions,
            "transitions_per_sec": round(transitions / elapsed, 1),
            "elapsed": round(elapsed, 3),
            "dispatches": runner.dispatches,
            "groups_dispatched": runner.groups_dispatched,
            "coalesced_dispatches": runner.coalesced_dispatches,
            "windows_slept": runner.windows_slept,
            "windows_skipped": runner.windows_skipped,
            "fallbacks": sum(p.kernel.fallbacks for p in parts),
            "fallback_reasons": reasons,
        }), flush=True)
        for p in parts:
            p.journal.close()


def run_dmn_batch(n_contexts: int = 200_000) -> dict:
    """Batched DMN decision-table evaluation on device (ops/decision.py):
    one jitted pass matching N contexts against an 8-rule table — the
    reference evaluates one context at a time through its embedded FEEL
    engine (dmn/…/DmnDecisionEngine)."""
    from zeebe_tpu.dmn import parse_dmn_xml
    from zeebe_tpu.ops.decision import batch_evaluate, compile_decision_table

    rules = "".join(
        f'<rule id="r{i}">'
        f"<inputEntry><text>[{i * 10}..{i * 10 + 9}]</text></inputEntry>"
        f'<inputEntry><text>{"&quot;gold&quot;" if i % 2 else "-"}</text></inputEntry>'
        f"<outputEntry><text>{i}</text></outputEntry></rule>"
        for i in range(8)
    )
    xml = f"""<?xml version="1.0" encoding="UTF-8"?>
<definitions xmlns="https://www.omg.org/spec/DMN/20191111/MODEL/"
             id="b" name="b" namespace="bench">
  <decision id="band" name="band"><decisionTable hitPolicy="FIRST">
    <input id="i1"><inputExpression><text>amount</text></inputExpression></input>
    <input id="i2"><inputExpression><text>tier</text></inputExpression></input>
    <output id="o1" name="band"/>{rules}
  </decisionTable></decision>
</definitions>"""
    dec = parse_dmn_xml(xml).decisions["band"]
    table = compile_decision_table(dec)
    rng = np.random.default_rng(7)
    contexts = [
        {"amount": float(a), "tier": "gold" if g else "silver"}
        for a, g in zip(rng.uniform(0, 90, n_contexts), rng.integers(0, 2, n_contexts))
    ]
    # warm at the MEASURED shape: jit specializes on shapes, so a smaller
    # warm-up would leave the full-size compile inside the timed window
    batch_evaluate(table, contexts)
    t0 = time.perf_counter()
    out = batch_evaluate(table, contexts)
    elapsed = time.perf_counter() - t0
    matched = sum(1 for o in out if o is not None)
    return {
        "contexts": n_contexts,
        "rows_per_sec": round(n_contexts / elapsed, 1),
        "matched": matched,
    }


def run_replay_recovery(tmpdir_records: int = 4000) -> dict:
    """Restart recovery: replay a committed one_task log into a fresh state
    store (the follower/restart path — reference anchor: snapshot+replay
    recovery throughput, LargeStateControllerPerformanceTest)."""
    with tempfile.TemporaryDirectory() as tmpdir:
        part = E2EPartition(tmpdir)
        part.deploy([one_task()])
        part.inject_creations("one_task", tmpdir_records, {})
        part.pump()
        jobs = part.pending_job_keys(0)
        part.complete_in_type_waves(jobs)
        total_records = sum(1 for _ in part.stream.new_reader(1))

        db = ZbDb()
        engine = Engine(db, partition_id=1, clock_millis=lambda: 0)
        replayer = StreamProcessor(part.stream, db, engine,
                                   mode=StreamProcessorMode.REPLAY)
        t0 = time.perf_counter()
        replayer.start()
        replayer.run_until_idle()
        elapsed = time.perf_counter() - t0
        part.journal.close()
        return {
            "records_replayed": total_records,
            "records_per_sec": round(total_records / elapsed, 1),
        }


# ---------------------------------------------------------------------------
# kernel ceiling (device-only, auto jobs)


def run_kernel_ceiling(num_instances: int = 1 << 20, rounds: int = 5) -> dict:
    exe = transform(one_task())
    tables = compile_tables([exe])
    dt = DeviceTables.from_tables(tables)
    def_of = np.zeros(num_instances, np.int32)
    config = tables.kernel_config

    def fresh_state():
        return make_state(tables, num_instances, def_of, token_capacity=num_instances)

    state = fresh_state()
    final, _ = run_to_completion(dt, state, max_steps=64, config=config)
    jax.block_until_ready(final["transitions"])
    per_run = int(final["transitions"])
    assert bool(final["done"].all()) and not bool(final["overflow"])

    states = [fresh_state() for _ in range(rounds)]
    for s in states:
        jax.block_until_ready(s["elem"])
    t0 = time.perf_counter()
    totals = []
    for s in states:
        final, _ = run_to_completion(dt, s, max_steps=64, config=config)
        totals.append(final["transitions"])
    jax.block_until_ready(totals)
    elapsed = time.perf_counter() - t0
    return {"transitions_per_sec": round(rounds * per_run / elapsed, 1)}


# resolved by _ensure_backend(); "cpu" until then
_PLATFORM = "cpu"
# accelerator device count; 0 on a CPU rehearsal
_REAL_DEVICES = 0

# XLA:CPU logs a multi-kilobyte machine-feature-mismatch warning every time
# it loads a persistent-cache executable compiled under a different feature
# canonicalization ("Machine type used for XLA:CPU compilation doesn't match
# … This could lead to execution errors such as SIGILL." — tail of
# BENCH_r05.json). It can fire dozens of times per run and buries the JSON
# summary line the driver tails for.
_XLA_MISMATCH_MARKER = b"Machine type used for XLA:CPU compilation doesn't match"
_XLA_SPAM = {"machine_type_mismatch_lines": 0}


def _install_stderr_spam_filter() -> None:
    """Detect the XLA machine-type-mismatch condition ONCE, emit one concise
    warning in its place, and drop the repeats — fd-level, because the
    message comes from C++ (absl) directly on fd 2, bypassing sys.stderr.
    Everything else passes through untouched, so real errors stay visible
    and the stdout JSON summary line stays clean. An atexit hook restores
    fd 2 and joins the pump so a crashing bench run's final traceback —
    written to the pipe — still reaches the real stderr."""
    import atexit
    import threading

    saved = os.dup(2)
    rfd, wfd = os.pipe()
    os.dup2(wfd, 2)
    os.close(wfd)
    out = os.fdopen(saved, "wb", 0)

    def pump() -> None:
        buf = b""
        with os.fdopen(rfd, "rb", 0) as r:
            while True:
                chunk = r.read(65536)
                if not chunk:
                    break
                buf += chunk
                *lines, buf = buf.split(b"\n")
                for line in lines:
                    if _XLA_MISMATCH_MARKER in line:
                        _XLA_SPAM["machine_type_mismatch_lines"] += 1
                        if _XLA_SPAM["machine_type_mismatch_lines"] == 1:
                            out.write(
                                b"[bench] XLA:CPU machine-type mismatch "
                                b"detected (persistent cache compiled under "
                                b"a different CPU feature canonicalization); "
                                b"suppressing further occurrences\n")
                        continue
                    out.write(line + b"\n")
        if buf:
            out.write(buf)

    pump_thread = threading.Thread(target=pump, daemon=True,
                                   name="bench-stderr-filter")
    pump_thread.start()

    def _restore() -> None:
        try:
            # puts the real stderr back on fd 2 AND closes the pipe's only
            # write end, so the pump sees EOF, drains the tail, and exits
            os.dup2(out.fileno(), 2)
        except OSError:
            pass
        pump_thread.join(timeout=5)

    atexit.register(_restore)


def _pipeline_stage_summary() -> dict:
    """Aggregate the stream_processor_pipeline_* stage histograms (count +
    total seconds per stage across partitions) for the BENCH extra — the
    before/after breakdown of where host time goes on the batch path."""
    from zeebe_tpu.utils.metrics import REGISTRY, Histogram

    prefix = "zeebe_stream_processor_pipeline_"
    out: dict = {}
    for name, metric in REGISTRY._metrics.items():
        if not name.startswith(prefix) or not isinstance(metric, Histogram):
            continue
        stage = name[len(prefix):]
        count, total = 0, 0.0
        for child in metric._children.values():
            count += child.count
            total += child.sum
        out[stage] = {"count": count, "sum_s": round(total, 4)}
    return out


def _group_cap() -> int:
    """Kernel group cap for the resolved backend: an accelerator amortizes
    its per-fetch link latency with big groups (the broker's own cap,
    broker/partition.py); the host backend prefers tight shape buckets (see
    E2EPartition.__init__)."""
    return 256 if _PLATFORM.startswith("cpu") else 2048


#: the device this run measured on, as jax reports it — stamped into every
#: result so a number can never travel without the device it came from
_DEVICE: dict = {}


def _ensure_backend() -> str:
    """Resolve the device this run measures on (``utils/backend``), in this
    process: the benchmark owns the chip. A machine where no accelerator
    answers fails here — a CPU run is a rehearsal that has to be asked for
    by name (``ZB_BENCH_CPU=1``), and its numbers are counts of work, never
    speeds under a per-chip metric name."""
    from zeebe_tpu.utils import backend
    from zeebe_tpu.utils.xla_cache import enable_persistent_cache

    global _PLATFORM, _REAL_DEVICES
    enable_persistent_cache()
    forced_cpu = bool(os.environ.get("ZB_BENCH_CPU"))
    if forced_cpu:
        jax.config.update("jax_platforms", "cpu")
    devices = backend.devices()
    first = devices[0]
    if first.platform == "cpu" and not forced_cpu:
        raise SystemExit(
            "bench.py measures on the accelerator and none answered; set "
            "ZB_BENCH_CPU=1 for a CPU rehearsal")
    _DEVICE.update(platform=first.platform, device_kind=first.device_kind,
                   count=len(devices))
    _PLATFORM = "cpu-forced" if forced_cpu else first.platform
    _REAL_DEVICES = 0 if forced_cpu else len(devices)
    return _PLATFORM


def _router_stats() -> dict:
    from zeebe_tpu.utils.device_link import shared_router

    return shared_router().stats()


# bench tracing: 1-in-10 head sampling — enough sampled traces per scenario
# for the critical-path percentiles (ISSUE 19: ~60 traces even at quick
# one_task counts) while the span ring stays far under capacity; the
# append→ack reservoir still sees EVERY command, so the headline p50/p99
# are over the full run, not the sampled traces
TRACE_SAMPLE_RATE = 0.1


def _enable_tracing() -> None:
    from zeebe_tpu.observability import configure_tracing

    configure_tracing(enabled=True, seed=0, sample_rate=TRACE_SAMPLE_RATE,
                      capacity=1 << 16)


# --sample-metrics: the cluster metrics plane's sampler over the bench run
# (thread-driven — the bench partitions have no broker control pump). The
# acceptance bar is <1% throughput cost vs a sampler-less run.
_METRICS_SAMPLER = None


def _enable_metric_sampling() -> None:
    global _METRICS_SAMPLER
    from zeebe_tpu.observability.timeseries import (
        MetricsSampler,
        TimeSeriesStore,
    )
    from zeebe_tpu.utils.metrics import REGISTRY, install_process_metrics

    install_process_metrics()
    # retention sized to cover a full (non-quick) run so the BENCH extra
    # summarizes the whole measurement, not just the tail
    _METRICS_SAMPLER = MetricsSampler(
        REGISTRY, TimeSeriesStore(retention_ms=60 * 60 * 1000),
        interval_ms=250)
    _METRICS_SAMPLER.start()


def _timeseries_extra() -> dict:
    """Retained-series summary for the BENCH extra: store volume plus the
    latest sampled value of the headline series (append rate, processing
    rate, flush p99, process CPU/RSS)."""
    from zeebe_tpu.observability.timeseries import summarize_store

    sampler = _METRICS_SAMPLER
    sampler.stop()
    sampler.sample_once()  # final point so the tail of the run is covered
    out = summarize_store(sampler.store, headline=(
        "zeebe_journal_append_rate",
        "zeebe_stream_processor_records_total",
        "zeebe_journal_flush_duration_seconds:p99",
        "process_cpu_seconds_total",
        "process_resident_memory_bytes",
    ))
    out["intervalMs"] = sampler.interval_ms
    out["samplesTaken"] = sampler.samples_taken
    return out


# --profile: the continuous profiling plane over the bench run (always-on
# folded-stack sampler at ~19 Hz, thread-driven like --sample-metrics). The
# extra carries the top-10 hot frames plus the kernel backend's XLA compile
# telemetry (xla_compile_seconds / xla_compiles_total{cache=hit|miss}), and
# the full folded profile lands next to the BENCH json for flamegraph tools.
_PROFILER = None
_PROFILER_LEASE = None


def _enable_profiling() -> None:
    global _PROFILER, _PROFILER_LEASE
    from zeebe_tpu.observability.profiler import acquire_profiler

    # same knob as the broker plane; leasing the shared process-global
    # sampler means in-bench brokers don't stack a second daemon on top
    raw = os.environ.get("ZEEBE_BROKER_PROFILING_HZ")
    try:
        hz = float(raw) if raw else 19.0
    except ValueError:
        hz = 19.0
    if hz <= 0:
        hz = 19.0  # --profile was explicit; 0 would sample nothing
    # 360 windows (an hour at the 10s default) so a full bench run is
    # covered end to end — the broker default (~5 min) would silently
    # evict the early workloads' windows from the "full" folded profile
    _PROFILER, _PROFILER_LEASE = acquire_profiler(hz=hz, max_windows=360)


def _compile_telemetry() -> dict:
    """The compile seam's counters, read off the registry's structured
    snapshot: hit/miss split plus per-geometry-bucket compile seconds. Both
    families carry exactly one label, so its value is the second quoted
    token of the label string (``{cache="hit"}`` → ``hit``)."""
    from zeebe_tpu.utils.metrics import REGISTRY

    out: dict = {"compiles": {}, "compile_seconds": {}}
    for name, _kind, label_str, value in REGISTRY.snapshot():
        if name not in ("zeebe_xla_compiles_total",
                        "zeebe_xla_compile_seconds"):
            continue
        label = label_str.split('"')[1] if '"' in label_str else ""
        if name == "zeebe_xla_compiles_total":
            out["compiles"][label] = int(value)
        else:
            count, total, _counts, _bounds = value
            out["compile_seconds"][label] = {
                "count": count, "sum_s": round(total, 4)}
    return out


def _profiling_extra(folded_path: str) -> dict:
    from zeebe_tpu.observability.profiler import release_profiler

    prof = _PROFILER
    release_profiler(_PROFILER_LEASE)  # last lease out stops the sampler
    folded = prof.folded()
    with open(folded_path, "w") as f:
        f.write(folded + "\n" if folded else "")
    windows = prof.windows()
    return {
        "hz": prof.hz,
        "achieved_hz": prof.achieved_hz,
        # retained-window sums, the same basis as hot_frames/folded — the
        # lifetime tick count would disagree after any window eviction
        "samples": sum(w["samples"] for w in windows),
        "retained_windows": len(windows),
        "hot_frames": prof.hot_frames(top=10),
        "xla": _compile_telemetry(),
        "folded_profile": os.path.basename(folded_path),
    }


def _tracing_extra() -> dict:
    """End-to-end latency attribution for the BENCH extra: p50/p99 of the
    command append→ack latency plus span accounting (--trace only). With
    per-scenario critical-path capture on, the collector counts reflect
    only the spans since the last scenario's snapshot-and-clear — the ack
    reservoir still covers the whole run."""
    from zeebe_tpu.observability import get_tracer

    tracer = get_tracer()
    return {
        "sample_rate": tracer.sampler.rate,
        "sample_seed": tracer.sampler.seed,
        "spans_collected": len(tracer.collector),
        "spans_emitted": tracer.collector.emitted,
        **tracer.latency_percentiles(),
    }


def _critical_path_block(scenario: str) -> dict:
    """Snapshot AND CLEAR the span ring after a traced scenario: runs the
    offline critical-path extractor over the scenario's sampled traces and
    returns per-edge p50/p99 plus the conservation verdict (ISSUE 19). The
    clear is what scopes each block to its own scenario — spans are
    attributed to the workload that emitted them, never the next one."""
    from zeebe_tpu.observability import get_tracer
    from zeebe_tpu.observability.critical_path import (
        aggregate_breakdowns,
        assemble,
        breakdowns_from_spans,
        check_conservation,
    )

    tracer = get_tracer()
    spans = [s.to_dict() for s in tracer.collector.snapshot()]
    tracer.collector.clear()
    breakdowns = breakdowns_from_spans(spans)
    violations = [v for b in breakdowns for v in check_conservation(b)]
    # slow exemplars: the scenario's 3 worst traces ship their full span
    # trees (plus any group trace they reference) to the exemplar artifact
    traces = assemble(spans)
    exemplars: dict[str, list] = {}
    for b in sorted(breakdowns, key=lambda b: -b["totalUs"])[:3]:
        trace_id = b["traceId"]
        tree = traces.get(trace_id)
        if not tree:
            continue
        exemplars[trace_id] = tree
        for s in tree:
            group = (s.get("attrs") or {}).get("group")
            if group and group in traces and group not in exemplars:
                exemplars[group] = traces[group]
    return {
        "scenario": scenario,
        "spans": len(spans),
        "conservationViolationCount": len(violations),
        "conservationViolations": violations[:20],
        "_exemplars": exemplars,
        **aggregate_breakdowns(breakdowns),
    }


def run_serving_schedule(duration_s: float = 2.5, rate_per_s: float = 400.0,
                         seed: int = 7) -> dict:
    """Open-loop serving scenario (ISSUE 19): arrivals follow the serving
    gate's seeded Poisson generator against the WALL clock instead of the
    closed-loop inject-then-pump shape. Queueing delay under arrival bursts
    is real here — exactly what the critical-path extractor must attribute
    to the queue edge instead of averaging away."""
    import random as _random

    from zeebe_tpu.testing.serving import poisson_schedule

    arrivals = poisson_schedule(_random.Random(seed), duration_s,
                                lambda t: rate_per_s, rate_per_s)
    with tempfile.TemporaryDirectory() as tmpdir:
        part = E2EPartition(tmpdir)
        model = one_task("serving_sched")
        part.deploy([model])
        # warm both kernel shape buckets, as run_e2e_workload does — a
        # mid-run XLA compile would poison the p99 this scenario exists
        # to attribute
        part.inject_creations(model.process_id, 16, {})
        part.inject_creations(model.process_id, part.kernel.max_group, {})
        part.pump()
        warm_jobs = part.pending_job_keys(0)
        if warm_jobs:
            part.complete_in_type_waves(warm_jobs)
        start_position = part.stream.last_position
        _scope_trace_to_measurement()
        scan_from = start_position
        create = command(
            ValueType.PROCESS_INSTANCE_CREATION,
            ProcessInstanceCreationIntent.CREATE,
            {"bpmnProcessId": model.process_id, "version": -1,
             "variables": {}},
        )
        writer = part.stream.writer
        max_lag = 0.0
        i = 0
        t0 = time.perf_counter()
        while i < len(arrivals):
            now = time.perf_counter() - t0
            injected = 0
            while i < len(arrivals) and arrivals[i] <= now:
                writer.try_write([LogAppendEntry(create)])
                i += 1
                injected += 1
            if injected:
                max_lag = max(max_lag, now - arrivals[i - 1])
                part.processor.run_until_idle()
                jobs = part.pending_job_keys(scan_from)
                if jobs:
                    scan_from = part.stream.last_position
                    part.complete_in_type_waves(jobs)
            else:
                time.sleep(0.0002)
        part.pump()
        jobs = part.pending_job_keys(scan_from)
        while jobs:
            scan_from = part.stream.last_position
            part.complete_in_type_waves(jobs)
            part.pump()
            jobs = part.pending_job_keys(scan_from)
        elapsed = time.perf_counter() - t0
        transitions = part.count_transitions(start_position)
        part.journal.close()
        return {
            "arrivals": len(arrivals),
            "offered_rate_per_sec": rate_per_s,
            "duration_s": round(duration_s, 2),
            "elapsed_s": round(elapsed, 3),
            "transitions": transitions,
            "transitions_per_sec": round(transitions / max(elapsed, 1e-9), 1),
            # how far behind schedule the driver itself fell (host jitter —
            # large values mean the queue edge includes driver lag)
            "max_injection_lag_ms": round(max_lag * 1000.0, 2),
        }


def _latency_report(cp_blocks: dict[str, dict], quick: bool) -> list[str]:
    """ISSUE 19: write the critical-path artifact (LATENCY[_quick].json —
    CI uploads it) and return the conservation-gate violations: every
    scenario's unattributed residual at p99 must stay under 10% of that
    scenario's critical-path p99, and no per-trace breakdown may violate
    edge-sum conservation."""
    from zeebe_tpu.observability.critical_path import EDGES

    violations: list[str] = []
    exemplars = {name: block.pop("_exemplars", {})
                 for name, block in cp_blocks.items()}
    for name, block in cp_blocks.items():
        if not block.get("traces"):
            violations.append(f"{name}: no sampled traces were extracted")
            continue
        frac = block.get("unattributed", {}).get("fracOfP99")
        if frac is not None and frac >= 0.10:
            violations.append(
                f"{name}: unattributed residual is {frac:.1%} of the "
                f"critical-path p99 (gate < 10%)")
        if block.get("conservationViolationCount"):
            violations.append(
                f"{name}: {block['conservationViolationCount']} "
                f"breakdown(s) violate edge-sum conservation")
    report = {
        "quick": quick,
        "edges": list(EDGES),
        "scenarios": cp_blocks,
        "violations": violations,
    }
    name = "LATENCY_quick.json" if quick else "LATENCY.json"
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(repo_dir, name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    # slow-exemplar dump (CI artifact, not committed): the 3 worst traces
    # per scenario with full span trees — a p99 number ships its receipts
    exemplar_name = name.replace(".json", "_exemplars.json")
    with open(os.path.join(repo_dir, exemplar_name), "w") as f:
        json.dump({"quick": quick, "scenarios": exemplars}, f, indent=2)
        f.write("\n")
    for v in violations:
        print(f"latency conservation violation: {v}", file=sys.stderr)
    return violations


def _eligibility_gate(scenarios: dict[str, dict], quick: bool) -> list[str]:
    """ISSUE 13: write the per-scenario eligibility/coverage artifact
    (ELIGIBILITY[_quick].json — CI uploads it) and return every scenario's
    static-vs-observed parity violations (the caller fails the run on any).
    """
    report = {
        "quick": quick,
        "scenarios": {
            name: result["kernel_coverage"]
            for name, result in scenarios.items()
            if isinstance(result, dict) and "kernel_coverage" in result
        },
    }
    violations = [
        f"{name}: {v}"
        for name, cov in report["scenarios"].items()
        for v in cov.get("parity_violations", [])
    ]
    report["parityViolations"] = violations
    name = "ELIGIBILITY_quick.json" if quick else "ELIGIBILITY.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    for v in violations:
        print(f"eligibility parity violation: {v}", file=sys.stderr)
    return violations


def _quick_main(platform: str, trace: bool = False,
                sample_metrics: bool = False, profile: bool = False) -> None:
    """--quick: the headline workloads at small instance counts plus a
    reduced kernel ceiling — a fast smoke of the full pipeline (log →
    processor → kernel backend → log) with the same JSON summary shape.
    Writes BENCH_quick.json so a quick run never clobbers the real
    BENCH.json artifact. Since ISSUE 13 the quick run also carries the
    ROADMAP item 3 coverage baselines (e2e_mixed_8_definitions and
    adversarial_cold_templates at reduced counts) and fails on any
    static-vs-observed eligibility parity violation."""
    cp_blocks: dict[str, dict] = {}
    e2e_one_task = run_e2e_workload([one_task()], drives=1, n_instances=600,
                                    variables={})
    if trace:
        cp_blocks["one_task"] = _critical_path_block("one_task")
    e2e_ten = run_e2e_workload([ten_tasks()], drives=10, n_instances=120,
                               variables={})
    if trace:
        cp_blocks["ten_tasks"] = _critical_path_block("ten_tasks")
    e2e_mixed = run_e2e_workload(mixed_definitions(), drives=4,
                                 n_instances=480, variables={"x": 15})
    if trace:
        cp_blocks["mixed_8"] = _critical_path_block("mixed_8")
    adversarial = run_adversarial_cold(n_instances=240)
    if trace:
        cp_blocks["adversarial_cold"] = _critical_path_block(
            "adversarial_cold")
    serving_sched = None
    if trace:
        # ISSUE 19: the open-loop serving schedule only runs traced — its
        # whole point is critical-path attribution under real queueing
        serving_sched = run_serving_schedule()
        cp_blocks["serving"] = _critical_path_block("serving")
    latency_violations = (_latency_report(cp_blocks, quick=True)
                          if trace else [])
    ceiling = run_kernel_ceiling(num_instances=1 << 17, rounds=2)
    parity = _eligibility_gate({
        "e2e_one_task": e2e_one_task,
        "e2e_ten_tasks": e2e_ten,
        "e2e_mixed_8_definitions": e2e_mixed,
        "adversarial_cold_templates": adversarial,
    }, quick=True)
    # ROADMAP item 1 honesty: every quick run carries a typed multichip
    # verdict instead of silently emitting nothing (skippable for tight
    # inner loops; the probe itself never fails the bench)
    multichip = None
    if not os.environ.get("ZEEBE_SKIP_MULTICHIP_PROBE"):
        try:
            probe_out = run_multichip_probe(platform)
            multichip = {"outcome": probe_out["outcome"],
                         "verdict": probe_out["verdict"],
                         "full_results": "MULTICHIP_probe.json"}
        except Exception as exc:  # noqa: BLE001 — a probe crash is itself
            # a verdict, not a bench failure
            multichip = {"outcome": "probe-error",
                         "verdict": f"{type(exc).__name__}: {exc}"}
    value = e2e_one_task["transitions_per_sec"]
    full = {
        "metric": "e2e_process_instance_transitions_per_sec_per_chip",
        "value": value,
        "unit": "transitions/s",
        "vs_baseline": round(value / NORTH_STAR, 3),
        "extra": {
            "quick": True,
            "e2e_one_task": e2e_one_task,
            "e2e_ten_tasks": e2e_ten,
            "e2e_mixed_8_definitions": e2e_mixed,
            "adversarial_cold_templates": adversarial,
            "kernel_ceiling_transitions_per_sec": ceiling["transitions_per_sec"],
            "pipeline_stages": _pipeline_stage_summary(),
            "platform": platform,
            "device": _DEVICE,
            **({"multichip_probe": multichip} if multichip else {}),
            "xla_spam": dict(_XLA_SPAM),
            **({"tracing": _tracing_extra()} if trace else {}),
            **({"serving_schedule": serving_sched} if serving_sched else {}),
            **({"latency_critical_path": "LATENCY_quick.json"}
               if trace else {}),
            **({"timeseries": _timeseries_extra()} if sample_metrics else {}),
            **({"profiling": _profiling_extra(os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "PROFILE_quick.folded"))} if profile else {}),
        },
    }
    bench_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "BENCH_quick.json")
    with open(bench_path, "w") as f:
        json.dump(full, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "metric": full["metric"],
        "value": value,
        "unit": full["unit"],
        "vs_baseline": full["vs_baseline"],
        "platform": platform,
        "quick": True,
        "ten_tasks_transitions_per_sec": e2e_ten["transitions_per_sec"],
        "mixed_8_kernel_coverage_pct":
            e2e_mixed["kernel_coverage"]["coverage_pct"],
        "adversarial_kernel_coverage_pct":
            adversarial["kernel_coverage"]["coverage_pct"],
        "kernel_ceiling_transitions_per_sec": ceiling["transitions_per_sec"],
        "full_results": "BENCH_quick.json",
    }))
    if parity or latency_violations:
        raise SystemExit(1)


def _soak_main(quick: bool) -> None:
    """--soak: the crash-recovery endurance gate (ISSUE 6). Runs sustained
    traffic with parked instances over an aggressive snapshot cadence,
    fires seeded power-loss crash-restarts mid-flush and mid-snapshot, and
    asserts the durability invariants after every restart. Writes
    SOAK[_quick].json (violations fail the run) and lists the per-recovery
    flight dumps so CI can upload them as artifacts."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.soak import SoakConfig, run_soak

    cfg = (SoakConfig() if quick else
           SoakConfig(rounds=10, traffic_per_round=40,
                      snapshot_chain_length=6))
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-soak-")
    try:
        report = run_soak(cfg, directory=work_dir)
        # the per-recovery flight dumps are the reviewable artifacts the
        # soak exists to leave behind — copy them out of the work dir (CI
        # uploads SOAK_dumps/) before it is deleted
        report["flightDumps"] = _collect_gate_dumps(
            report["flightDumps"], "SOAK_dumps", work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    report["wallSeconds"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "SOAK_quick.json" if quick else "SOAK.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "soak": True, "quick": quick, "seed": report["seed"],
        "restarts": report["restarts"],
        "ackedCommands": report["ackedCommands"],
        "withinBudget": report["withinBudget"],
        "maxRecoveryMs": report["recoveryMs"]["max"],
        "maxChainLength": report["maxChainLength"],
        "snapshotKinds": report["snapshotKinds"],
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"soak violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def _collect_gate_dumps(dump_paths, dumps_name: str, work_dir: str) -> list:
    """Copy a chaos gate's flight dumps into ``<repo>/<dumps_name>/`` for
    CI artifact upload — shared home: zeebe_tpu/testing/evidence.py (one
    dump-preservation protocol for the soak, scale-soak, and consistency
    gates; zlint's drift-copy rule pins it there)."""
    from zeebe_tpu.testing.evidence import collect_gate_dumps

    return collect_gate_dumps(
        dump_paths, dumps_name, work_dir,
        repo_dir=os.path.dirname(os.path.abspath(__file__)))


def _consistency_main(quick: bool) -> None:
    """--consistency: the exactly-once delivery gate (ISSUE 9). Boots a
    REAL supervised multi-process worker cluster over TCP with seeded
    TCP-layer chaos (drop/dup/delay/reorder + link partitions), fires a
    kill_worker storm and a deterministic crash-between-append-and-reply,
    records the full client history + export streams, and checks the
    Jepsen-shaped invariants: no acked command lost, no duplicate
    application (per-request-id export uniqueness, byte-level), rejections
    terminal, gateway positions monotone per partition. Writes
    CONSISTENCY[_quick].json; violations fail the run."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.consistency import ConsistencyConfig, run_consistency

    cfg = (ConsistencyConfig() if quick else
           ConsistencyConfig(drive_seconds=120.0, kills=8, link_windows=5,
                             reject_every=20))
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-consistency-")
    try:
        report = run_consistency(cfg, directory=work_dir)
        # worker flight dumps are the postmortem artifacts (every kill's
        # recovery + the dedupe hits/replays land in the rings) — copy them
        # out before the work dir is deleted so CI can upload them
        from pathlib import Path as _Path

        report["flightDumps"] = _collect_gate_dumps(
            sorted(_Path(work_dir).glob("*/flight-*.json")),
            "CONSISTENCY_dumps", work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    report["wallSecondsTotal"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "CONSISTENCY_quick.json" if quick else "CONSISTENCY.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "consistency": True, "quick": quick, "seed": report["seed"],
        "requests": report["requests"],
        "ackedCommands": report["ackedCommands"],
        "kills": report["kills"],
        "linkPartitionWindows": report["linkPartitionWindows"],
        "crashSequencesVerified": report["crashSequencesVerified"],
        "dedupeProbeVerified": report.get("dedupeProbe", {}).get("verified"),
        "dedupeRepliesObserved": report["dedupeRepliesObserved"],
        "reExportedRecords": report["reExportedRecords"],
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"consistency violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def _torture_main(quick: bool) -> None:
    """--torture: the storage fault-survival gate (ISSUE 14). Real
    supervised workers serve the Jepsen-shaped workload while the disk,
    the network, and the process table all lie at once; offline checks
    prove delivery invariants held, every configured disk-fault class
    fired, every at-rest bit-rot flip was detected-or-repaired before
    wrong bytes were served, and the corrupted-follower repair probe
    re-converged CRC-identical to the leader. Writes
    TORTURE[_quick].json; violations fail the run."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.torture import TortureConfig, run_torture

    cfg = (TortureConfig() if quick else
           TortureConfig(drive_seconds=90.0, kills=3))
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-torture-")
    try:
        report = run_torture(cfg, directory=work_dir)
    finally:
        from pathlib import Path as _Path

        dumps = _collect_gate_dumps(
            sorted(_Path(work_dir).glob("*/flight-*.json")),
            "TORTURE_dumps", work_dir)
        shutil.rmtree(work_dir, ignore_errors=True)
    report["flightDumps"] = dumps
    report["wallSecondsTotal"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "TORTURE_quick.json" if quick else "TORTURE.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "torture": True, "quick": quick, "seed": report["seed"],
        "requests": report["requests"],
        "ackedCommands": report["ackedCommands"],
        "kills": report["kills"],
        "diskFaultsObserved": report["diskFaultsObserved"],
        "bitrotFlips": report["bitrotFlips"],
        "repairProbeVerified": report["repairProbe"].get("verified"),
        "scrubEvidenceEvents": report["scrubEvidenceEvents"],
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"torture violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def _device_chaos_main(quick: bool) -> None:
    """--device-chaos: the device fault-survival gate (ISSUE 15). Real
    supervised workers run the KERNEL backend while the accelerator lies
    (compile/dispatch failures, watchdogged stalls, partial-chunk
    failures, bit-flipped result rows) and a kill rides along; offline
    checks prove delivery invariants + replica CRC equality held, every
    configured device-fault class fired, every injected corruption was
    caught before commit, and at least one worker life completed the full
    SUSPECT→QUARANTINED→canary→HEALTHY cycle. Writes
    DEVICE_CHAOS[_quick].json; violations fail the run."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.device_chaos import (
        DeviceChaosConfig,
        run_device_chaos,
    )

    cfg = (DeviceChaosConfig() if quick else
           DeviceChaosConfig(drive_seconds=90.0, kills=3))
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-device-chaos-")
    try:
        report = run_device_chaos(cfg, directory=work_dir)
    finally:
        from pathlib import Path as _Path

        dumps = _collect_gate_dumps(
            sorted(_Path(work_dir).glob("*/flight-*.json")),
            "DEVICE_CHAOS_dumps", work_dir)
        shutil.rmtree(work_dir, ignore_errors=True)
    report["flightDumps"] = dumps
    report["wallSecondsTotal"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "DEVICE_CHAOS_quick.json" if quick else "DEVICE_CHAOS.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "deviceChaos": True, "quick": quick, "seed": report["seed"],
        "requests": report["requests"],
        "ackedCommands": report["ackedCommands"],
        "kills": report["kills"],
        "deviceFaultsObserved": report["deviceFaultsObserved"],
        "corruptionAccounting": report["corruptionAccounting"],
        "healthCycle": report["healthCycle"],
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"device-chaos violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def _fleetday_main(quick: bool) -> None:
    """--fleetday: the long-horizon fleet-day gate (ISSUE 20, ROADMAP
    item 4). The open-loop multi-tenant serving workload with diurnal
    ramps + tiered state + ALL THREE chaos planes at background rates +
    live definition churn + rolling worker restarts, while the fleet
    auditor watches invariants/burn-rates/leak-trends online; gated on
    the PR 9 offline checker, SLOs outside declared incident windows,
    ≥1 event per chaos plane, corruption accounting, zero leak verdicts
    on the clean fleet, auditor recall vs offline findings, and a
    leak-injection arm where the auditor MUST fire. Writes
    FLEETDAY[_quick].json; violations fail the run."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.fleetday import FULL_FLEETDAY, FleetDayConfig
    from zeebe_tpu.testing.fleetday import run_fleetday

    cfg = FleetDayConfig() if quick else FULL_FLEETDAY
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-fleetday-")
    try:
        report = run_fleetday(cfg, directory=work_dir)
    finally:
        from pathlib import Path as _Path

        dumps = _collect_gate_dumps(
            sorted(_Path(work_dir).glob("*/flight-*.json")),
            "FLEETDAY_dumps", work_dir)
        shutil.rmtree(work_dir, ignore_errors=True)
    report["flightDumps"] = dumps
    report["wallSecondsTotal"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "FLEETDAY_quick.json" if quick else "FLEETDAY.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "fleetday": True, "quick": quick, "seed": report["seed"],
        "requests": report["requests"],
        "ackedCommands": report["ackedCommands"],
        "chaosPlanes": {p: sum(c.values())
                        for p, c in report["chaosPlanes"].items()},
        "rollingRestarts": report["rollingRestarts"],
        "definitionChurn": report["definitionChurn"],
        "slo": {k: report["slo"].get(k)
                for k in ("p50Ms", "p99Ms", "ackFraction")},
        "leakVerdicts": report["leakVerdicts"],
        "leakArmFired": report["leakArm"].get("fired"),
        "auditorRecallPct": report["auditorRecall"]["recallPct"],
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"fleetday violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def _serving_main(quick: bool) -> None:
    """--serving: the open-loop SLO'd serving gate (ISSUE 11). Drives the
    real multi-process cluster with seeded Poisson arrivals from hundreds
    of concurrent client streams — per-tenant quotas with one hot tenant at
    5x its quota, a diurnal ramp, a correlation storm waking cold-parked
    instances, and a live worker kill — then gates on the well-behaved
    tenants' p50/p99 ack latency (open-loop: dispatch queueing counts),
    fairness vs the calm baseline, typed-and-fast sheds, goodput vs the
    no-chaos window, and zero acked loss against the workers' journals.
    Writes SERVING[_quick].json; violations fail the run."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.serving import FULL_CONFIG, ServingConfig, run_serving

    cfg = ServingConfig() if quick else FULL_CONFIG
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-serving-")
    try:
        report = run_serving(cfg, directory=work_dir)
    finally:
        # collect dumps BEFORE the work dir is deleted, even when the run
        # raised — a failed gate is exactly the run whose flight evidence
        # the CI artifact upload must keep
        from pathlib import Path as _Path

        dumps = _collect_gate_dumps(
            sorted(_Path(work_dir).glob("*/flight-*.json")),
            "SERVING_dumps", work_dir)
        shutil.rmtree(work_dir, ignore_errors=True)
    report["flightDumps"] = dumps
    report["wallSecondsTotal"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "SERVING_quick.json" if quick else "SERVING.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "serving": True, "quick": quick, "seed": report["seed"],
        "requests": report["requests"],
        "ackedCommands": report["ackedCommands"],
        "shedCommands": report["shedCommands"],
        "kills": report["kills"],
        "wellBehavedP99MsUnderLoad": report.get(
            "wellBehaved", {}).get("underLoad", {}).get("p99Ms"),
        "goodput": report.get("goodput"),
        "parkedColdBeforeStorm": report.get(
            "stormPool", {}).get("parkedColdBeforeStorm"),
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"serving violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def _autotune_main(quick: bool) -> None:
    """--autotune: the closed-loop control plane's A/B gate (ISSUE 12).
    Offers the SAME seeded bursty open-loop schedule to the adaptive
    broker and a panel of fixed-knob configurations (default,
    journal-aggressive, journal-conservative, small/large coalescing) at
    equal load over real supervised worker processes, then gates: the
    adaptive arm beats every fixed arm on acked p99 with goodput within
    5% of the best fixed arm, zero acked loss everywhere, every
    adjustment a control_adjust flight event, and every knob provably
    inside its declared bounds. Writes AUTOTUNE[_quick].json; violations
    fail the run."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.autotune import (
        FULL_CONFIG,
        AutotuneConfig,
        run_autotune,
    )

    cfg = AutotuneConfig() if quick else FULL_CONFIG
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-autotune-")
    try:
        report = run_autotune(cfg, work_dir)
    finally:
        # collect dumps BEFORE the work dir is deleted, even when the run
        # raised — a failed gate is exactly the run whose control audit
        # trail the CI artifact upload must keep
        from pathlib import Path as _Path

        dumps = _collect_gate_dumps(
            sorted(_Path(work_dir).glob("*/*/flight-*.json")),
            "AUTOTUNE_dumps", work_dir)
        shutil.rmtree(work_dir, ignore_errors=True)
    report["flightDumps"] = dumps
    report["wallSecondsTotal"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "AUTOTUNE_quick.json" if quick else "AUTOTUNE.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "autotune": True, "quick": quick, "seed": report["seed"],
        "offeredArrivals": report["offeredArrivals"],
        "summary": report["summary"],
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"autotune violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def _scale_soak_main(quick: bool) -> None:
    """--scale-soak: the million-instance state-tiering gate (ISSUE 8).
    Parks 1M+ instances (100k in --quick) on a tiered-state broker under
    sustained traffic with correlation storms, snapshots + compaction under
    load, and crash-restarts mid-spill and mid-snapshot; gates on bounded
    RSS, zero acked-record loss, byte-identical re-exports, recovery within
    budget, and the cold tier holding the parked majority. Writes
    SCALE_SOAK[_quick].json and copies the per-recovery flight dumps for
    CI upload."""
    import shutil
    import time as _time

    from zeebe_tpu.testing.scale_soak import (
        FULL_CONFIG,
        ScaleSoakConfig,
        run_scale_soak,
    )

    cfg = ScaleSoakConfig() if quick else FULL_CONFIG
    started = _time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix="zeebe-scale-soak-")
    try:
        report = run_scale_soak(cfg, directory=work_dir)
        report["flightDumps"] = _collect_gate_dumps(
            report["flightDumps"], "SCALE_SOAK_dumps", work_dir)
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)
    report["wallSeconds"] = round(_time.perf_counter() - started, 2)
    report["quick"] = quick
    name = "SCALE_SOAK_quick.json" if quick else "SCALE_SOAK.json"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "scaleSoak": True, "quick": quick, "seed": report["seed"],
        "created": report["created"],
        "peakSpilledInstances": report["peakSpilledInstances"],
        "peakSpilledFraction": report["peakSpilledFraction"],
        "peakRssMiB": report["rss"]["peakMiB"],
        "rssWithinBound": report["rss"]["withinBound"],
        "withinBudget": report["withinBudget"],
        "sweepProbes": report["sweepProbes"],
        "violations": len(report["violations"]),
        "full_results": name,
    }))
    if report["violations"]:
        for v in report["violations"][:20]:
            print(f"scale-soak violation: {v}", file=sys.stderr)
        raise SystemExit(1)


# ---------------------------------------------------------------------------
# interleaved A/B comparison + mesh scaling modes (ISSUE 7 satellites)


def _default_mesh_workers(n_partitions: int) -> int:
    return min(n_partitions, os.cpu_count() or 1)


def _scenario(name: str):
    """Named bench scenarios for --interleave / --mesh. ``mesh_pN`` runs the
    worker-process mode (one process per core); ``mesh_pN_threads`` forces
    the legacy single-process threaded mode for before/after comparisons."""
    import re

    m = re.fullmatch(r"mesh_p(\d+)(_threads)?", name)
    if m:
        n = int(m.group(1))
        workers = 0 if m.group(2) else _default_mesh_workers(n)
        return lambda: run_mesh_serving(n, workers=workers)
    if name == "one_task":
        return lambda: run_e2e_workload([one_task()], drives=1,
                                        n_instances=600, variables={})
    if name == "ten_tasks":
        return lambda: run_e2e_workload([ten_tasks()], drives=10,
                                        n_instances=120, variables={})
    raise SystemExit(
        f"unknown scenario {name!r}: expected one_task, ten_tasks, mesh_pN, "
        f"or mesh_pN_threads")


def _headline(result: dict) -> float:
    return float(result.get("transitions_per_sec")
                 or result.get("aggregate_transitions_per_sec") or 0.0)


def _interleave_main(spec: str, rounds: int, platform: str) -> None:
    """--interleave A,B: alternating same-box runs with paired per-round
    deltas — the box is noisy (historical one_task spread 39–84k/s), so
    cross-revision and cross-mode comparisons are only meaningful paired
    (ROADMAP: "cross-revision comparisons need interleaved runs"). Writes
    INTERLEAVE.json; the stdout summary carries the paired mean ratio."""
    names = [n.strip() for n in spec.split(",")]
    if len(names) != 2:
        raise SystemExit("--interleave expects exactly two scenarios: A,B")
    if rounds < 1:
        raise SystemExit("--rounds must be >= 1")
    a_name, b_name = names
    run_a, run_b = _scenario(a_name), _scenario(b_name)
    pairs = []
    for r in range(rounds):
        ra, rb = run_a(), run_b()
        ha, hb = _headline(ra), _headline(rb)
        # fixed "a"/"b" keys (never the scenario names): an A/A null run —
        # the natural noise calibration on this box — must keep BOTH samples
        pairs.append({
            "round": r + 1, "a": ha, "b": hb,
            "delta": round(hb - ha, 1),
            "ratio": round(hb / ha, 3) if ha else None,
            "detail": {"a": ra, "b": rb},
        })
    ratios = [p["ratio"] for p in pairs if p["ratio"]]
    deltas = [p["delta"] for p in pairs]
    summary = {
        "a": a_name, "b": b_name, "rounds": rounds,
        "mean_ratio": round(sum(ratios) / len(ratios), 3) if ratios else None,
        "min_ratio": min(ratios) if ratios else None,
        "max_ratio": max(ratios) if ratios else None,
        "mean_delta": round(sum(deltas) / len(deltas), 1),
        "a_mean": round(sum(p["a"] for p in pairs) / rounds, 1),
        "b_mean": round(sum(p["b"] for p in pairs) / rounds, 1),
    }
    out = {"interleave": summary, "pairs": pairs, "platform": platform,
           "cpu_count": os.cpu_count()}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "INTERLEAVE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps({"interleave": summary, "platform": platform,
                      "full_results": "INTERLEAVE.json"}))


def _mesh_main(counts_spec: str, gate: bool, platform: str) -> None:
    """--mesh N,M,...: the mesh-serving scaling curve at the given partition
    counts (worker-process mode above 1 partition), written to
    MESH_quick.json. --gate-scaling additionally FAILS the run when any
    multi-partition aggregate is not above the single-partition rate — the
    CI mesh-smoke gate (ISSUE 7: p4 aggregate ≤ p1 is a regression)."""
    counts = [int(c) for c in counts_spec.split(",") if c.strip()]
    results = {}
    for n in counts:
        if n > 1:
            results[f"p{n}"] = run_mesh_serving(
                n, workers=_default_mesh_workers(n))
        elif not platform.startswith("cpu"):
            # the gate's baseline must share the workers' cpu backend: an
            # accelerator-measured p1 vs cpu-pinned pN is a cross-backend
            # ratio, not a scaling measurement — run p1 as ONE cpu worker
            results[f"p{n}"] = _run_mesh_serving_workers(
                n, MESH_PER_PARTITION, 1)
        else:
            results[f"p{n}"] = run_mesh_serving(n)
    base = _headline(results[f"p{counts[0]}"])
    for n in counts[1:]:
        r = results[f"p{n}"]
        if "aggregate_transitions_per_sec" in r and base:
            r["scaling_vs_first"] = round(
                r["aggregate_transitions_per_sec"] / base, 2)
    out = {"mesh": results, "platform": platform,
           "cpu_count": os.cpu_count()}
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MESH_quick.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    headline = {f"p{n}": _headline(results[f"p{n}"]) for n in counts}
    print(json.dumps({"mesh": headline, "platform": platform,
                      "cpu_count": os.cpu_count(),
                      "full_results": "MESH_quick.json"}))
    if gate and len(counts) > 1:
        if base <= 0:
            # a skipped/failed baseline must FAIL the gate, not let every
            # positive aggregate trivially "beat" 0
            print(f"mesh scaling gate FAILED: p{counts[0]} baseline produced "
                  f"no rate ({results[f'p{counts[0]}']}) — nothing to gate "
                  f"against", file=sys.stderr)
            raise SystemExit(1)
        failures = [
            n for n in counts[1:] if _headline(results[f"p{n}"]) <= base
        ]
        if failures:
            print(f"mesh scaling gate FAILED: p{failures} aggregate <= "
                  f"p{counts[0]} ({base}/s) — partition throughput is not "
                  f"additive", file=sys.stderr)
            raise SystemExit(1)


# ---------------------------------------------------------------------------
# multichip honesty probe (ISSUE 17 satellite / ROADMAP item 1)


def _counter_total(name: str) -> float:
    from zeebe_tpu.utils.metrics import REGISTRY

    fam = REGISTRY._metrics.get(name)
    if fam is None:
        return 0.0
    return float(sum(child.value for child in fam._children.values()))


def _measure_mesh_seam_coverage() -> dict:
    """Drive a few instances through a mesh-runner-backed kernel backend with
    shadow sampling forced to 100% and MEASURE whether any mesh dispatch was
    shadow-verified. ROADMAP item 1 says the mesh runner bypasses the
    begin_group/finish_group commit seam (no shadow verification, no
    watchdog, no health ladder); this turns that claim into a counter delta
    the verdict can cite instead of an assumption."""
    from zeebe_tpu.models.bpmn import Bpmn as _Bpmn
    from zeebe_tpu.parallel.mesh import make_mesh
    from zeebe_tpu.parallel.mesh_runner import MeshKernelRunner
    from zeebe_tpu.testing import EngineHarness

    runner = MeshKernelRunner(mesh=make_mesh(1))
    h = EngineHarness(use_kernel_backend=True, mesh_runner=runner)
    cfg = h.kernel_backend.health.cfg
    saved_rate = cfg.shadow_sample_rate
    cfg.shadow_sample_rate = 1.0
    checks0 = _counter_total("zeebe_device_shadow_checks_total")
    try:
        h.deploy(
            _Bpmn.create_executable_process("mc_probe")
            .start_event("s").service_task("t", job_type="w")
            .end_event("e").done()
        )
        for _ in range(4):
            h.create_instance("mc_probe")
        for job in h.activate_jobs("w", max_jobs=8):
            h.complete_job(job["key"], None)
    finally:
        cfg.shadow_sample_rate = saved_rate
        h.close()
    shadow_delta = _counter_total("zeebe_device_shadow_checks_total") - checks0
    return {
        "mesh_dispatches": runner.dispatches,
        "shadow_checks_at_100pct_sampling": shadow_delta,
        "covered": runner.dispatches > 0 and shadow_delta > 0,
    }


def run_multichip_probe(platform: str) -> dict:
    """ROADMAP item 1 asks for "a first nonzero MULTICHIP sample … or an
    honest probe verdict explaining why not" — this is the honest probe.

    It ATTEMPTS a minimal 2-shard mesh dispatch (the ``__graft_entry__``
    re-execed child: real devices when a probed pair exists, else the
    virtual 2-device cpu mesh as sharding-correctness evidence), measures
    whether mesh dispatch is covered by the commit seam's shadow
    verification, and writes a TYPED verdict to MULTICHIP_probe.json.
    ``outcome`` is ``"ran"`` only when the sample would honestly count
    (>= 2 real non-CPU devices AND seam coverage); otherwise the precise
    why-not — never silence.
    """
    import io
    from contextlib import redirect_stdout

    import __graft_entry__ as graft

    real = 0 if platform.startswith("cpu") else _REAL_DEVICES

    dispatch = {
        "attempted": True,
        "n_shards": 2,
        "mode": "real devices" if real >= 2 else "virtual cpu mesh",
    }
    buf = io.StringIO()
    t0 = time.perf_counter()
    try:
        with redirect_stdout(buf):
            graft.dryrun_multichip(2)
        dispatch["ok"] = True
        dispatch["error"] = None
    except Exception as exc:  # noqa: BLE001 — the verdict carries it
        dispatch["ok"] = False
        dispatch["error"] = f"{type(exc).__name__}: {exc}"
    dispatch["elapsed_s"] = round(time.perf_counter() - t0, 1)
    dispatch["tail"] = buf.getvalue()[-400:]

    try:
        seam = _measure_mesh_seam_coverage()
    except Exception as exc:  # noqa: BLE001 — a broken measurement is a
        # why-not datum, not a probe crash
        seam = {"error": f"{type(exc).__name__}: {exc}", "covered": False}

    evidence = ("2-shard dispatch on the virtual cpu mesh "
                + ("completed — sharding-correctness evidence, not a "
                   "multichip sample" if dispatch["ok"]
                   else f"FAILED ({dispatch['error']})"))
    if real == 0:
        outcome = "why-not:platform"
        verdict = (f"no real accelerator answered (platform={platform}); "
                   + evidence)
    elif real < 2:
        outcome = "why-not:device-count"
        verdict = (f"only {real} real device(s) — a 2-shard mesh needs a "
                   f"pair; " + evidence)
    elif not seam.get("covered"):
        outcome = "why-not:mesh-bypasses-seam"
        verdict = (
            "a real device pair exists, but the mesh runner bypasses the "
            "begin_group/finish_group commit seam "
            f"({seam.get('shadow_checks_at_100pct_sampling', 0):.0f} shadow "
            f"checks at 100% sampling over "
            f"{seam.get('mesh_dispatches', 0)} mesh dispatches) — an "
            "unhardened sample would not honestly count (ROADMAP item 1: "
            "route mesh dispatch through the seam first)")
    elif not dispatch["ok"]:
        outcome = "why-not:dispatch-failed"
        verdict = f"2-shard real-device dispatch failed: {dispatch['error']}"
    else:
        outcome = "ran"
        verdict = ("first nonzero MULTICHIP sample: 2-shard mesh dispatch "
                   "OK with commit-seam shadow coverage")

    out = {
        "probe": "multichip-honesty",
        "platform": platform,
        "real_devices": real,
        "dispatch": dispatch,
        "seam": seam,
        "outcome": outcome,
        "verdict": verdict,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "MULTICHIP_probe.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"multichip probe: {outcome} — {verdict}", file=sys.stderr)
    return out


def main(quick: bool = False, trace: bool = False,
         sample_metrics: bool = False, profile: bool = False,
         soak: bool = False, scale_soak: bool = False,
         consistency: bool = False, serving: bool = False,
         autotune: bool = False, torture: bool = False,
         device_chaos: bool = False, multichip_probe: bool = False,
         fleetday: bool = False) -> None:
    # install the filter BEFORE any backend use: the mismatch warning fires
    # whenever a persistent-cache executable loads, including the probe's
    # subprocess (which inherits the filtered fd 2)
    _install_stderr_spam_filter()
    if consistency:
        # worker processes probe/pin their own backends; the harness itself
        # never touches a device
        _consistency_main(quick)
        return
    if serving:
        # same posture: the gateway-side harness never touches a device
        _serving_main(quick)
        return
    if autotune:
        # same posture: arms run in worker processes
        _autotune_main(quick)
        return
    if torture:
        # same posture: workers own the (faulted) disks
        _torture_main(quick)
        return
    if device_chaos:
        # same posture: workers own the (faulted) kernel dispatch path
        _device_chaos_main(quick)
        return
    if fleetday:
        # same posture: everything runs in worker processes; the gateway
        # harness + the cluster auditor never touch a device
        _fleetday_main(quick)
        return
    platform = _ensure_backend()
    if multichip_probe:
        run_multichip_probe(platform)
        return
    if soak:
        _soak_main(quick)
        return
    if scale_soak:
        _scale_soak_main(quick)
        return
    if trace:
        _enable_tracing()
    if sample_metrics:
        _enable_metric_sampling()
    if profile:
        _enable_profiling()
    if quick:
        _quick_main(platform, trace=trace, sample_metrics=sample_metrics,
                    profile=profile)
        return
    cp_blocks: dict[str, dict] = {}
    e2e_one_task = run_e2e_workload([one_task()], drives=1, n_instances=4000,
                                    variables={})
    if trace:
        cp_blocks["one_task"] = _critical_path_block("one_task")
    e2e_excl = run_e2e_workload([exclusive_chain()], drives=0, n_instances=4000,
                                variables={"x": 25})
    if trace:
        cp_blocks["exclusive_chain"] = _critical_path_block("exclusive_chain")
    e2e_fork = run_e2e_workload([fork_join()], drives=1, n_instances=2000,
                                variables={})
    if trace:
        cp_blocks["fork_join"] = _critical_path_block("fork_join")
    e2e_mixed = run_e2e_workload(mixed_definitions(), drives=4, n_instances=2400,
                                 variables={"x": 15})
    if trace:
        cp_blocks["mixed_8"] = _critical_path_block("mixed_8")
    e2e_ten = run_e2e_workload([ten_tasks()], drives=10, n_instances=800,
                               variables={})
    e2e_ten_io = run_e2e_workload([ten_tasks_io()], drives=10, n_instances=800,
                                  variables={"base": 5})
    e2e_scope = run_e2e_workload([subprocess_boundary()], drives=1,
                                 n_instances=2000, variables={})
    adversarial = run_adversarial_cold()
    serving_sched = None
    if trace:
        cp_blocks["adversarial_cold"] = _critical_path_block(
            "adversarial_cold")
        # ISSUE 19: the open-loop serving schedule runs traced-only (its
        # point is critical-path attribution under real queueing)
        serving_sched = run_serving_schedule(duration_s=6.0)
        cp_blocks["serving"] = _critical_path_block("serving")
    latency_violations = (_latency_report(cp_blocks, quick=False)
                          if trace else [])
    parity = _eligibility_gate({
        "e2e_one_task": e2e_one_task,
        "e2e_exclusive_chain": e2e_excl,
        "e2e_fork_join": e2e_fork,
        "e2e_mixed_8_definitions": e2e_mixed,
        "e2e_ten_tasks": e2e_ten,
        "e2e_ten_tasks_io_mapped": e2e_ten_io,
        "e2e_subprocess_boundary": e2e_scope,
        "adversarial_cold_templates": adversarial,
    }, quick=False)
    warm_large = run_one_task_warm_large_state()
    # on-chip e2e (router bypassed): only when a real accelerator resolved
    on_chip = (run_one_task_on_chip()
               if not platform.startswith("cpu") else None)
    recovery = run_replay_recovery()
    ceiling = run_kernel_ceiling()
    dmn = run_dmn_batch()
    # mesh serving: aggregate throughput at 1 / 3 / 8 partitions sharing one
    # device mesh (scaling curve + coalescing evidence; see run_mesh_serving
    # on natural-vs-windowed coalescing on a single-core host)
    mesh_1 = run_mesh_serving(1)
    mesh_3 = run_mesh_serving(3)
    mesh_8 = run_mesh_serving(8)
    mesh_8w = run_mesh_serving(8, batch_window_s=0.3)
    # the ISSUE 7 scale-out shape: 8 partitions over per-core worker
    # PROCESSES — the configuration whose aggregate must ADD across cores
    # (the threaded p8 serializes on the GIL)
    mesh_8p = (run_mesh_serving(8, workers=_default_mesh_workers(8))
               if (os.cpu_count() or 1) > 1 else None)
    base_rate = mesh_1.get("aggregate_transitions_per_sec", 0) or 1
    # p8_workers joins the scaling curve only when p1 also ran on cpu —
    # workers are cpu-pinned, and a cpu/accelerator ratio is not a scaling
    # measurement (the result carries its own note in that case)
    scalable = [mesh_3, mesh_8, mesh_8w]
    if mesh_8p and _PLATFORM.startswith("cpu"):
        scalable.append(mesh_8p)
    for m in scalable:
        if "aggregate_transitions_per_sec" in m:
            m["scaling_vs_1_partition"] = round(
                m["aggregate_transitions_per_sec"] / base_rate, 2)

    value = e2e_one_task["transitions_per_sec"]
    full = {
        "metric": "e2e_process_instance_transitions_per_sec_per_chip",
        "value": value,
        "unit": "transitions/s",
        "vs_baseline": round(value / NORTH_STAR, 3),
        "extra": {
            "e2e_one_task": e2e_one_task,
            "e2e_exclusive_chain": e2e_excl,
            "e2e_fork_join": e2e_fork,
            "e2e_mixed_8_definitions": e2e_mixed,
            "e2e_ten_tasks": e2e_ten,
            "e2e_ten_tasks_io_mapped": e2e_ten_io,
            "e2e_subprocess_boundary": e2e_scope,
            "adversarial_cold_templates": adversarial,
            "one_task_warm_200k_durable": warm_large,
            **({"one_task_on_chip_forced": on_chip} if on_chip else {}),
            "kernel_ceiling_transitions_per_sec": ceiling["transitions_per_sec"],
            "dmn_batch": dmn,
            "replay_recovery": recovery,
            "mesh_serving": {"p1": mesh_1, "p3": mesh_3, "p8": mesh_8,
                             "p8_windowed_300ms": mesh_8w,
                             **({"p8_workers": mesh_8p} if mesh_8p else {})},
            "platform": platform,
            "device": _DEVICE,
            # per-stage host-path breakdown of the pipelined batch loop
            # (stream_processor_pipeline_* histograms, aggregated)
            "pipeline_stages": _pipeline_stage_summary(),
            # once-detected-then-suppressed XLA:CPU machine-type stderr spam
            "xla_spam": dict(_XLA_SPAM),
            # --trace: append→ack p50/p99 + span accounting (observability)
            **({"tracing": _tracing_extra()} if trace else {}),
            **({"serving_schedule": serving_sched} if serving_sched else {}),
            **({"latency_critical_path": "LATENCY.json"} if trace else {}),
            # --sample-metrics: retained time-series summary (metrics plane)
            **({"timeseries": _timeseries_extra()} if sample_metrics else {}),
            # --profile: hot frames + XLA compile telemetry (profiling plane)
            **({"profiling": _profiling_extra(os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "PROFILE.folded"))} if profile else {}),
            # link-aware routing (utils/device_link.py): measured per-transfer
            # link cost and where groups actually ran — the e2e workloads ride
            # the accelerator only when the link amortizes (VERDICT r3 weak 3:
            # the per-transfer cost, measured, deciding the placement)
            "device_link": _router_stats(),
            "note": (
                "e2e = commands on the committed log -> stream processor -> "
                "device kernel + burst templates -> events appended + state "
                "updated; log is byte-equal to the sequential engine's "
                "(randomized parity suite)."
            ),
        },
    }
    # full result to a file; the stdout headline stays SHORT and is printed
    # last and alone, so the driver's tail capture can never truncate the
    # metric out (VERDICT r4 item 9: round 4's headline was unrecoverable)
    bench_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "BENCH.json")
    with open(bench_path, "w") as f:
        json.dump(full, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "metric": full["metric"],
        "value": value,
        "unit": full["unit"],
        "vs_baseline": full["vs_baseline"],
        "platform": platform,
        "ten_tasks_transitions_per_sec": e2e_ten["transitions_per_sec"],
        "kernel_ceiling_transitions_per_sec": ceiling["transitions_per_sec"],
        **({"one_task_on_chip_transitions_per_sec":
            on_chip["transitions_per_sec"]} if on_chip else {}),
        "full_results": "BENCH.json",
    }))
    if parity or latency_violations:
        raise SystemExit(1)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small instance counts, <60s; writes BENCH_quick.json")
    ap.add_argument("--trace", action="store_true",
                    help="enable the observability tracer (seeded sampling) "
                         "and fold append→ack p50/p99 into the BENCH extra")
    ap.add_argument("--sample-metrics", action="store_true",
                    help="run the metrics-plane sampler (250ms, thread-"
                         "driven) over the bench and fold the retained "
                         "time-series summary into the BENCH extra")
    ap.add_argument("--profile", action="store_true",
                    help="run the continuous folded-stack profiler (~19 Hz) "
                         "over the bench, fold top-10 hot frames + XLA "
                         "compile telemetry into the BENCH extra, and write "
                         "the full folded profile to PROFILE[_quick].folded")
    ap.add_argument("--soak", action="store_true",
                    help="crash-recovery soak gate: sustained traffic + "
                         "seeded power-loss crash-restarts mid-flush and "
                         "mid-snapshot; asserts no acked record lost, no "
                         "duplicate exports, replay bounded by snapshot "
                         "cadence, recovery within budget. Writes "
                         "SOAK[_quick].json; --quick bounds it to a few "
                         "minutes")
    ap.add_argument("--consistency", action="store_true",
                    help="exactly-once delivery gate (ISSUE 9): real "
                         "supervised worker processes over TCP with seeded "
                         "chaos (drop/dup/delay/reorder, link partitions, "
                         "kill storm, crash-between-append-and-reply); "
                         "checks no acked command lost, no duplicate "
                         "application, terminal rejections, monotone "
                         "positions. Writes CONSISTENCY[_quick].json")
    ap.add_argument("--scale-soak", action="store_true",
                    help="million-instance state-tiering gate: park 1M+ "
                         "instances (100k with --quick) on a tiered-state "
                         "broker with correlation storms, snapshots + "
                         "compaction under load, and crash-restarts "
                         "mid-spill/mid-snapshot; gates on bounded RSS, "
                         "zero acked-record loss, byte-identical "
                         "re-exports, and recovery within budget. Writes "
                         "SCALE_SOAK[_quick].json")
    ap.add_argument("--serving", action="store_true",
                    help="open-loop SLO'd serving gate (ISSUE 11): seeded "
                         "Poisson arrivals from hundreds of client streams "
                         "over the real multi-process cluster — per-tenant "
                         "quotas, one hot tenant at 5x quota, a diurnal "
                         "ramp, a correlation storm waking cold-parked "
                         "instances, and a live worker kill; gates on "
                         "well-behaved p50/p99 ack latency, fairness, "
                         "typed-and-fast sheds, goodput vs the no-chaos "
                         "window, and zero acked loss. Writes "
                         "SERVING[_quick].json")
    ap.add_argument("--autotune", action="store_true",
                    help="closed-loop control plane A/B gate (ISSUE 12): "
                         "the SAME seeded bursty open-loop schedule offered "
                         "to the adaptive broker and a panel of fixed-knob "
                         "configurations at equal load; gates on adaptive "
                         "beating every fixed arm's acked p99 with goodput "
                         "within 5%, zero acked loss, and a complete "
                         "control_adjust audit trail with every knob inside "
                         "its declared bounds. Writes AUTOTUNE[_quick].json")
    ap.add_argument("--interleave", metavar="A,B",
                    help="interleaved same-box A/B comparison: alternate the "
                         "two named scenarios --rounds times and report "
                         "paired deltas (INTERLEAVE.json). Scenarios: "
                         "one_task, ten_tasks, mesh_pN, mesh_pN_threads")
    ap.add_argument("--rounds", type=int, default=5,
                    help="rounds for --interleave (default 5)")
    ap.add_argument("--mesh", metavar="N,M,...",
                    help="mesh-serving scaling curve at the given partition "
                         "counts (worker-process mode above p1); writes "
                         "MESH_quick.json")
    ap.add_argument("--gate-scaling", action="store_true",
                    help="with --mesh: exit 1 unless every multi-partition "
                         "aggregate beats the first count's rate (the CI "
                         "mesh-smoke gate)")
    ap.add_argument("--torture", action="store_true",
                    help="storage fault-survival gate (ISSUE 14): the "
                         "consistency workload over real supervised worker "
                         "processes with DISK chaos (write EIO/ENOSPC, torn "
                         "writes, fsync stalls/failures, at-rest bit rot) "
                         "live simultaneously with TCP chaos and a kill "
                         "storm; gates on zero acked loss, zero duplicate "
                         "application, every configured disk-fault class "
                         "observed, every bit-rot flip detected-or-repaired "
                         "before wrong bytes served, and a deliberately "
                         "corrupted follower journal re-converging "
                         "CRC-identical to the leader's. Writes "
                         "TORTURE[_quick].json")
    ap.add_argument("--device-chaos", action="store_true",
                    help="device fault-survival gate (ISSUE 15): the "
                         "consistency workload over real supervised worker "
                         "processes with the KERNEL backend live and DEVICE "
                         "chaos (compile/dispatch failures, watchdogged "
                         "stalls, partial-chunk failures, bit-flipped "
                         "result rows) plus a worker kill; gates on zero "
                         "acked loss, zero duplicate application, replica "
                         "CRC equality, every configured device-fault "
                         "class observed, every injected corruption caught "
                         "before commit, and >=1 full SUSPECT->QUARANTINED"
                         "->canary->HEALTHY ladder cycle. Writes "
                         "DEVICE_CHAOS[_quick].json")
    ap.add_argument("--fleetday", action="store_true",
                    help="long-horizon fleet-day gate (ISSUE 20): the "
                         "open-loop multi-tenant serving workload with "
                         "diurnal ramps, tiered state, ALL THREE chaos "
                         "planes at background rates, live definition "
                         "churn, and rolling worker restarts — while the "
                         "fleet auditor watches invariants, SLO burn "
                         "rates, and resource leak trends ONLINE; gates "
                         "on the offline exactly-once checker, SLOs held "
                         "outside declared incident windows, >=1 event "
                         "per chaos plane, zero leak verdicts on the "
                         "clean fleet, 100%% auditor recall vs offline "
                         "findings, and a leak-injection arm where the "
                         "auditor MUST fire. Writes FLEETDAY[_quick].json")
    ap.add_argument("--multichip-probe", action="store_true",
                    help="multichip honesty probe (ROADMAP item 1): attempt "
                         "a minimal 2-shard mesh dispatch and write a TYPED "
                         "verdict (ran / why-not: platform, device count, "
                         "mesh-bypasses-seam) to MULTICHIP_probe.json "
                         "instead of silently emitting nothing; also runs "
                         "inside --quick unless ZEEBE_SKIP_MULTICHIP_PROBE "
                         "is set")
    ap.add_argument("--mesh-worker-spec", help=argparse.SUPPRESS)
    _args = ap.parse_args()
    if _args.mesh_worker_spec:
        _mesh_worker_main(json.loads(_args.mesh_worker_spec))
    elif _args.interleave or _args.mesh:
        _install_stderr_spam_filter()
        _platform = _ensure_backend()
        if _args.interleave:
            _interleave_main(_args.interleave, _args.rounds, _platform)
        if _args.mesh:
            _mesh_main(_args.mesh, _args.gate_scaling, _platform)
    else:
        main(quick=_args.quick, trace=_args.trace,
             sample_metrics=_args.sample_metrics, profile=_args.profile,
             soak=_args.soak, scale_soak=_args.scale_soak,
             consistency=_args.consistency, serving=_args.serving,
             autotune=_args.autotune, torture=_args.torture,
             device_chaos=_args.device_chaos,
             multichip_probe=_args.multichip_probe,
             fleetday=_args.fleetday)
