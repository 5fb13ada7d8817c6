#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on the chip.

One process, the only one that touches JAX. It builds what
``python -m zeebe_tpu.standalone --brokers 1 --partitions 3 --replication 1``
builds (``load_broker_cfg`` → ``ClusterRuntime`` → ``Gateway``, kernel backend
on as by default) on a fresh data directory, then drives it only through the
public gRPC client (``ZeebeTpuClient``, ``JobWorker``):

  1. device: fail at once unless ``jax.devices()[0].platform == "tpu"`` (or the
     CPU was asked for explicitly — a rehearsal, which never ends with a result)
  2. one_task: 10,000 instances (BASELINE.json configs[0]) created from several
     client threads, every job completed by a JobWorker
  3. mixed9: ``workloads.mixed_definitions()`` plus an embedded sub-process,
     deployed together, 250 instances each with ``x`` drawn from ``--seed``
  4. verdict: every kernel group was shadow-verified against the host (CPU)
     oracle with zero mismatches, none failed or was contained, the health
     ladder never left HEALTHY, every group ran on the TPU, and a fresh
     sequential engine replaying each partition's journal reproduces its state

``--chips 4`` replaces phases 2-3 by the path a four-chip host takes by default
and what it is compared with: (A) four partitions' logs through a
``MeshKernelRunner`` over the four chips, byte-compared with the direct
one-device path under a fixed clock; (B) 1 broker, 4 partitions, the broker's
own mesh runner, 10,000 one_task instances through the gRPC client.

The last line of stdout is the result, and exists only after a run on the chip:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
REHEARSAL_EXIT = 3  # all phases passed, but not on a chip: no result


class SmokeFailure(AssertionError):
    """A phase's check did not hold. Never caught: the run ends non-zero."""


def require(condition, what: str) -> None:
    if not condition:
        raise SmokeFailure(what)


def say(message: str) -> None:
    print(message, flush=True)


# ---------------------------------------------------------------------------
# workload


def embedded_subprocess(pid: str = "mx_sub"):
    """One task inside an embedded sub-process, one after it (the scope
    reduction's shape, as tests/test_kernel_backend.py's subprocess_task)."""
    from zeebe_tpu.models.bpmn import Bpmn

    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .sub_process("sub")
        .start_event("inner_s")
        .service_task("inner_task", job_type=f"inner_{pid}")
        .end_event("inner_e")
        .sub_process_done()
        .service_task("after", job_type=f"after_{pid}")
        .end_event("e")
        .done()
    )


def mixed_definitions() -> list:
    """The nine definitions deployed together: exclusive gateways with FEEL
    conditions (stack VM), two- and three-way fork/joins (sort under cond),
    an embedded sub-process (scope reduction), ragged task chains."""
    from zeebe_tpu.testing import workloads

    return workloads.mixed_definitions() + [embedded_subprocess()]


def job_types_of(models) -> list[str]:
    return sorted({el.job_type for m in models for el in m.elements.values()
                   if el.job_type})


# ---------------------------------------------------------------------------
# what the exporter sees (the client's only view of the instances it did not
# wait for): the standard exporter SPI, as ZEEBE_BROKER_EXPORTERS_* would load


class Tally:
    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.activated: set[int] = set()
        self.completed: set[int] = set()
        self.flows_taken: dict[str, set[str]] = {}


def _tally_exporter(tally: Tally):
    from zeebe_tpu.exporters.api import Exporter
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import ProcessInstanceIntent as PI

    class TallyExporter(Exporter):
        def export(self, logged) -> None:
            record = logged.record
            if (record.value_type == ValueType.PROCESS_INSTANCE
                    and record.is_event):
                value = record.value
                with tally.lock:
                    if record.intent == PI.SEQUENCE_FLOW_TAKEN:
                        tally.flows_taken.setdefault(
                            value["bpmnProcessId"], set()).add(value["elementId"])
                    elif value.get("bpmnElementType") == "PROCESS":
                        if record.intent == PI.ELEMENT_ACTIVATED:
                            tally.activated.add(record.key)
                        elif record.intent == PI.ELEMENT_COMPLETED:
                            tally.completed.add(record.key)
            self.controller.update_last_exported_position(logged.position)

    return TallyExporter()


# ---------------------------------------------------------------------------
# a client under backpressure: retried with backoff and counted, never dropped


class Retrying:
    def __init__(self, seed: int) -> None:
        import grpc

        self.retryable = {grpc.StatusCode.RESOURCE_EXHAUSTED,
                          grpc.StatusCode.UNAVAILABLE,
                          grpc.StatusCode.DEADLINE_EXCEEDED}
        self.not_found = grpc.StatusCode.NOT_FOUND
        self.counts: Counter = Counter()
        self._lock = threading.Lock()
        self._rng = random.Random(seed)

    def call(self, what: str, fn, *args, not_found: str = "raise",
             give_up_at: float | None = None, **kw):
        """``not_found``: "raise"; "retry" (a creation racing the
        deployment's distribution to its partition — asynchronous by design);
        "count" (a completion of a job that was delivered twice: an earlier
        attempt timed out and did land, or its activation was re-delivered
        after the job timeout) — counted, and the call returns None."""
        import grpc

        delay = 0.01
        while True:
            try:
                return fn(*args, **kw)
            except grpc.RpcError as err:
                code = err.code()
                known = code in self.retryable or (
                    code == self.not_found and not_found != "raise")
                if not known or (give_up_at is not None
                                 and time.monotonic() > give_up_at):
                    raise
                with self._lock:
                    self.counts[f"{what}:{code.name}"] += 1
                    jitter = 0.5 + self._rng.random()
                if code == self.not_found and not_found == "count":
                    return None
            time.sleep(delay * jitter)
            delay = min(delay * 2, 1.0)


# ---------------------------------------------------------------------------
# compile accounting, from jax's own monitoring events


class CompileLedger:
    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.events: Counter = Counter()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds

    def _on_event(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def report(self) -> str:
        return (f"compiles={self.compiles} compile_seconds={self.seconds:.2f} "
                f"persistent_cache_hits={self.events['cache_hits']} "
                f"persistent_cache_writes={self.events['cache_misses']}")


# ---------------------------------------------------------------------------
# the served system: standalone.py's constructors, in this process


def hold_groups_on_the_accelerator():
    """Install a process-wide BackendRouter whose rule is 'the accelerator'.
    The default rule weighs the measured link against the host XLA backend
    and sends groups host-ward when nine transfers cost over 2 ms (on the
    v5e they do); this script tests the chip, so every group goes there and
    the measured link is only printed (ROADMAP D2 decides the rule)."""
    from zeebe_tpu.utils.device_link import BackendRouter, install_shared_router

    class AcceleratorOnly(BackendRouter):
        def choose(self, bucket):
            return self.accel_device()

    router = AcceleratorOnly()
    install_shared_router(router)
    return router


class Served:
    def __init__(self, data_dir: Path, partitions: int, tally: Tally) -> None:
        from zeebe_tpu.broker.config import load_broker_cfg
        from zeebe_tpu.gateway import ClusterRuntime, Gateway

        cfg = load_broker_cfg(overrides={
            "base.partition_count": partitions,
            "base.replication_factor": 1,
        })
        require(cfg.base.kernel_backend, "kernel backend is off in the config")
        self.partitions = partitions
        self.data_dir = data_dir
        self.runtime = ClusterRuntime(
            exporters_factory=lambda: {"smoke": _tally_exporter(tally)},
            kernel_backend=cfg.base.kernel_backend,
            broker_count=1,
            partition_count=partitions,
            replication_factor=1,
            directory=data_dir,
            backpressure_algorithm=cfg.backpressure.algorithm,
            backpressure_enabled=cfg.backpressure.enabled,
            disk_min_free_bytes=(cfg.disk.min_free_bytes
                                 if cfg.disk.enable_monitoring else 0),
        )
        self.gateway = None
        self.runtime.start()
        self.gateway = Gateway(self.runtime, bind="127.0.0.1:0")
        self.gateway.start()
        self.address = self.gateway.address
        (self.broker,) = self.runtime.brokers.values()

    def stop(self) -> None:
        import shutil

        if self.gateway is not None:
            self.gateway.stop()
        self.runtime.stop()
        # tens of MB of journals and snapshots: not worth carrying home
        shutil.rmtree(self.data_dir, ignore_errors=True)

    def leaders(self) -> dict:
        return {pid: self.broker.partitions[pid]
                for pid in range(1, self.partitions + 1)}

    def backends(self) -> dict:
        out = {pid: p.processor.kernel_backend
               for pid, p in self.leaders().items()}
        require(all(b is not None for b in out.values()),
                "a partition leader runs without a kernel backend")
        return out


def run_load(served: Served, models, plan: list, tally: Tally, retry: Retrying,
             *, client_threads: int, workers_per_type: int,
             timeout_s: float) -> dict:
    """Deploy ``models``, create every (process id, variables, with_result)
    of ``plan`` from ``client_threads`` clients while JobWorkers complete
    every job, and wait until the exporter has seen them all complete."""
    from zeebe_tpu.client import JobWorker, ZeebeTpuClient
    from zeebe_tpu.models.bpmn import to_bpmn_xml

    deadline = time.monotonic() + timeout_s
    clients = [ZeebeTpuClient(served.address) for _ in range(client_threads)]
    worker_clients: list = []
    workers: list = []
    try:
        retry.call("deploy", clients[0].deploy_resource, *[
            (f"{m.process_id}.bpmn", to_bpmn_xml(m)) for m in models])

        def complete(_job_client, job, client) -> None:
            retry.call("complete", client.complete_job, job.key, {},
                       not_found="count", give_up_at=deadline)

        for job_type in job_types_of(models):
            for _ in range(workers_per_type):
                client = ZeebeTpuClient(served.address)
                worker_clients.append(client)
                workers.append(JobWorker(
                    client, job_type,
                    lambda jc, job, client=client: complete(jc, job, client),
                    # an activation whose response was lost (the gateway gave
                    # up on a stalled partition that then served it) comes
                    # back after this long, not after the 5-minute default
                    timeout_ms=60_000, auto_complete=False).start())

        with tally.lock:
            before = set(tally.completed)
        acked: list[int] = []
        with_result = 0
        errors: list[BaseException] = []
        lock = threading.Lock()

        def create(client, share) -> None:
            nonlocal with_result
            try:
                for process_id, variables, wait in share:
                    # NOT_FOUND: partition 1 distributes the deployment
                    # to the others after it answers the deploy call
                    if wait:
                        inst = retry.call(
                            "create_with_result",
                            client.create_instance_with_result, process_id,
                            variables=variables, timeout_s=300.0,
                            not_found="retry", give_up_at=deadline)
                    else:
                        inst = retry.call(
                            "create", client.create_instance, process_id,
                            variables=variables,
                            not_found="retry", give_up_at=deadline)
                    with lock:
                        acked.append(inst.process_instance_key)
                        with_result += bool(wait)
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)

        # first touches: one client, sequentially, in definition order, twice
        # round the partitions — every partition's registry then grows its
        # table set in the same order, so run to run the device programs
        # (and their compile-cache keys) are the same
        t0 = time.monotonic()
        n_first = 2 * served.partitions * len(models)
        create(clients[0], plan[:n_first])
        plan = plan[n_first:]
        threads = [threading.Thread(target=create,
                                    args=(c, plan[i::client_threads]))
                   for i, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(1.0, deadline - time.monotonic()))
        require(not any(t.is_alive() for t in threads),
                f"creation did not finish in {timeout_s:.0f}s")
        if errors:
            raise errors[0]
        created_s = time.monotonic() - t0
        want = set(acked)
        require(len(want) == n_first + len(plan),
                f"{n_first + len(plan)} creations acknowledged "
                f"{len(want)} distinct keys")
        while True:
            with tally.lock:
                settled = (want <= tally.completed
                           and tally.activated == tally.completed)
                done, seen = len(want & tally.completed), len(tally.activated)
            if settled:
                break
            require(time.monotonic() < deadline,
                    f"only {done} of {len(want)} acknowledged instances "
                    f"completed in {timeout_s:.0f}s ({seen} activated)")
            time.sleep(0.05)
        completed_s = time.monotonic() - t0
        failed_jobs = sum(w.failed_count for w in workers)
        require(failed_jobs == 0, f"{failed_jobs} job handlers failed")
        with tally.lock:
            extra = len(tally.completed - want - before)
        return {"created": len(want), "completed": len(want),
                "with_result": with_result,
                "jobs_completed": sum(w.handled_count for w in workers),
                "unacknowledged_duplicates_completed": extra,
                "create_wall_s": round(created_s, 2),
                "complete_wall_s": round(completed_s, 2)}
    finally:
        for w in workers:
            w.stop()
        for c in clients + worker_clients:
            c.close()


def one_task_plan(n: int) -> list:
    # a sample waits for its own completion through the client; the rest are
    # counted from the export stream
    return [("one_task", {"n": i}, i % 500 == 499) for i in range(n)]


def mixed_plan(models, per_definition: int, partitions: int, seed: int) -> list:
    """(process id, variables, with_result) for ``per_definition`` instances
    of each model. ``x`` is drawn (seeded) from values on both sides of every
    gateway's threshold (``x > 10*i``, i = 0..4), evenly, so a definition
    with at least six instances takes both branches of each of its gateways.
    The head of the plan is run_load's first-touch block: definition order."""
    rng = random.Random(seed)
    head, rest = [], []
    for m in models:
        xs = [(-5, 5, 15, 25, 35, 45)[i % 6] for i in range(per_definition)]
        rng.shuffle(xs)
        entries = [(m.process_id, {"x": xs[i]}, i % 125 == 124)
                   for i in range(per_definition)]
        head += entries[:2 * partitions]
        rest += entries[2 * partitions:]
    rng.shuffle(rest)
    return head + rest


def require_both_branches(models, tally: Tally) -> None:
    from zeebe_tpu.protocol.enums import BpmnElementType

    with tally.lock:
        taken = {k: set(v) for k, v in tally.flows_taken.items()}
    for model in models:
        for element in model.elements.values():
            if element.element_type != BpmnElementType.EXCLUSIVE_GATEWAY:
                continue
            flows = {f.id for f in model.outgoing(element.id)}
            if len(flows) > 1:
                missing = flows - taken.get(model.process_id, set())
                require(not missing,
                        f"{model.process_id}/{element.id}: branch(es) "
                        f"{sorted(missing)} never taken")


# ---------------------------------------------------------------------------
# the verdict on where and how the kernel groups ran


DEVICE_FAILURES = ("device-dispatch-error", "device-wedged", "device-quarantined",
                   "geometry-bounds", "no-quiesce", "token-overflow",
                   "group-error", "mesh-dispatch-error", "mesh-no-quiesce",
                   "mesh-token-overflow")


def _name(device) -> str:
    return f"{device.platform}:{device.id}"


def replay_agrees(served: Served) -> None:
    """A fresh sequential engine rebuilds each partition's state from its
    journal exactly as a restart would — from position 1, or from the latest
    snapshot once the broker has compacted the journal behind it — and must
    land on the served state (tests/test_engine_replay.py's property)."""
    from zeebe_tpu.testing.chaos import engine_state_equals, replay_state_of

    for pid, partition in served.leaders().items():
        with served.broker.partition_guard(pid):
            first = next(iter(partition.stream.new_reader(1))).position
            replayed = replay_state_of(partition)
            require(engine_state_equals(replayed, partition.db),
                    f"partition {pid}: replay of the journal by a fresh "
                    f"sequential engine does not reproduce the served state")
            say(f"  partition {pid}: journal replay agrees (positions {first}"
                f"..{partition.stream.last_position}"
                f"{'' if first == 1 else ', on top of the latest snapshot'})")


def kernel_report(served: Served) -> dict:
    from zeebe_tpu.utils.metrics import REGISTRY

    backends = served.backends()
    groups = sum(b.groups_processed for b in backends.values())
    commands = sum(b.commands_processed for b in backends.values())
    reasons: Counter = Counter()
    by_device: Counter = Counter()
    shadow_by_device: Counter = Counter()
    coverage = {}
    for pid, b in backends.items():
        reasons.update(b.fallback_reasons)
        by_device.update({_name(d): n for d, n in b.groups_by_device.items()})
        shadow_by_device.update(
            {_name(d): n for d, n in b.shadow_by_device.items()})
        coverage[pid] = {
            "kernel_records": b.accounting.kernel_records,
            "host_records": b.accounting.host_records,
            "groups": b.groups_processed,
            "commands": b.commands_processed,
            "buckets": sorted({(k[0][1], k[0][2]) for k in b._compiles_seen}),
            "template_hits": b.template_hits,
            "template_misses": b.template_misses,
            "shadow_quarantined": b.shadow_quarantined,
        }
        say(f"  partition {pid}: {coverage[pid]}")
    say(f"  kernel groups={groups} commands={commands} "
        f"mean_group={commands / max(groups, 1):.2f}")
    say(f"  host escapes / sequential heads: {dict(reasons)}")
    say(f"  groups by device: {dict(by_device)}")
    say(f"  shadow oracle runs by device: {dict(shadow_by_device)}")
    stages = {}
    for name, kind, _labels, value in REGISTRY.snapshot():
        if kind == "histogram" and "stream_processor_pipeline_" in name:
            stage = name.rsplit("_pipeline_", 1)[1]
            count, total = stages.get(stage, (0, 0.0))
            stages[stage] = (count + value[0], total + value[1])
    say("  pipeline stages, host clock (count, seconds, mean ms): " + ", ".join(
        f"{s}=({c}, {t:.2f}, {1e3 * t / max(c, 1):.2f})"
        for s, (c, t) in sorted(stages.items())))
    require(groups > 0, "no kernel group ran")
    failures = {r: n for r, n in reasons.items()
                if r.split(":")[0] in DEVICE_FAILURES}
    require(not failures, f"kernel groups failed or were contained: {failures}")
    return {"groups": groups, "by_device": by_device,
            "shadow_by_device": shadow_by_device}


def direct_path_verdict(served: Served, router, platform: str) -> None:
    from zeebe_tpu.engine.device_health import HEALTHY, shared_device_health

    report = kernel_report(served)
    health = shared_device_health()
    say(f"  router: {router.stats()}")
    say(f"  device health: {health.status()}")
    require(health.state == HEALTHY and not health.transitions,
            f"health ladder left HEALTHY: {health.transitions}")
    require(not health.faults, f"device faults: {health.faults}")
    require(health.shadow_mismatches == 0,
            f"{health.shadow_mismatches} shadow mismatches")
    dispatched = sum(report["by_device"].values())
    require(health.shadow_checks >= dispatched > 0,
            f"{dispatched} groups dispatched but only {health.shadow_checks} "
            "shadow-verified (rate must be 1)")
    on_platform = {d for d in report["by_device"] if d.startswith(platform)}
    require(set(report["by_device"]) == on_platform,
            f"groups ran off the {platform}: {dict(report['by_device'])}")
    require(all(d.startswith("cpu") for d in report["shadow_by_device"]),
            f"shadow oracle ran off the host: {dict(report['shadow_by_device'])}")
    if platform != "cpu":
        stats = router.stats()
        require(stats["host_groups"] == 0 and stats["accel_groups"] > 0,
                f"router saw host groups: {stats}")
    replay_agrees(served)


# ---------------------------------------------------------------------------
# phases


def served_one_chip(args, out_dir: Path, router, platform: str) -> None:
    tally = Tally()
    retry = Retrying(args.seed)
    served = Served(out_dir / "data", partitions=3, tally=tally)
    try:
        from zeebe_tpu.testing.workloads import one_task

        t0 = time.monotonic()
        result = run_load(served, [one_task()],
                          one_task_plan(args.instances), tally, retry,
                          client_threads=8, workers_per_type=8,
                          timeout_s=args.phase_timeout)
        say(f"phase 2 one_task: {result} wall_s={time.monotonic() - t0:.1f}")

        models = mixed_definitions()
        t0 = time.monotonic()
        result = run_load(served, models,
                          mixed_plan(models, max(6, args.instances // 40),
                                     served.partitions, args.seed),
                          tally, retry, client_threads=8, workers_per_type=2,
                          timeout_s=args.phase_timeout)
        require_both_branches(models, tally)
        say(f"phase 3 mixed9: {result} wall_s={time.monotonic() - t0:.1f}")
        say(f"client retries (backpressure, counted, none dropped): "
            f"{dict(retry.counts)}")

        say("phase 4 verdict:")
        direct_path_verdict(served, router, platform)
    finally:
        served.stop()


def mesh_parity(args, n_shards: int) -> None:
    """(A): the same seeded command sequence for four partitions through a
    MeshKernelRunner over the chips (concurrent submissions, so waves
    coalesce) and through the direct one-device path; logs byte-identical."""
    from zeebe_tpu.logstreams import LogAppendEntry
    from zeebe_tpu.parallel.mesh_runner import MeshKernelRunner
    from zeebe_tpu.protocol import ValueType, command
    from zeebe_tpu.protocol.intent import ProcessInstanceCreationIntent
    from zeebe_tpu.testing import EngineHarness

    models = mixed_definitions()
    job_types = job_types_of(models)

    def write_creations(h, partition: int) -> None:
        rng = random.Random(args.seed * 1000 + partition)
        for _ in range(24):
            rec = command(
                ValueType.PROCESS_INSTANCE_CREATION,
                ProcessInstanceCreationIntent.CREATE,
                {"bpmnProcessId": rng.choice(models).process_id, "version": -1,
                 "variables": {"x": rng.choice((-5, 5, 15, 25, 35, 45))}},
            ).replace(request_id=2, request_stream_id=0)
            h.stream.writer.try_write([LogAppendEntry(rec)])

    def prepare(h) -> None:
        # first touches in definition order: every partition's registry then
        # holds the same table set, and only equal table sets share a dispatch
        h.deploy(*models)
        for m in models:
            h.create_instance(m.process_id, variables={"x": 5})

    def drive(h, partition: int, barrier=None) -> None:
        write_creations(h, partition)
        if barrier is not None:
            barrier.wait(timeout=60)
        h.pump()  # the creations ride one kernel group
        for _ in range(12):  # the longest chain has 4 tasks; forks add waves
            jobs = [j for t in job_types for j in h.activate_jobs(t, max_jobs=64)]
            if not jobs:
                break
            for job in jobs:
                h.complete_job(job["key"], None)
        else:
            raise SmokeFailure("jobs kept appearing after 12 rounds")

    def log_bytes(h) -> list:
        return [(v.position, v.record.to_bytes(), v.processed, v.source_position)
                for v in h.stream.scan()]

    partitions = range(1, n_shards + 1)
    direct = {}
    for p in partitions:
        h = EngineHarness(use_kernel_backend=True)
        try:
            prepare(h)
            drive(h, p)
            require(h.kernel_backend.groups_processed > 0,
                    "direct path ran no kernel group")
            direct[p] = log_bytes(h)
        finally:
            h.close()

    # the window makes the barrier's concurrent submissions coalesce
    runner = MeshKernelRunner(n_shards=n_shards, batch_window_s=0.1)
    harnesses = {p: EngineHarness(use_kernel_backend=True, mesh_runner=runner)
                 for p in partitions}
    try:
        for h in harnesses.values():
            prepare(h)
        barrier = threading.Barrier(n_shards)
        errors: list[BaseException] = []

        def run(p: int) -> None:
            try:
                drive(harnesses[p], p, barrier)
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)

        threads = [threading.Thread(target=run, args=(p,)) for p in partitions]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=args.phase_timeout)
        require(not any(t.is_alive() for t in threads), "mesh parity hung")
        if errors:
            raise errors[0]
        for p, h in harnesses.items():
            require(h.kernel_backend.groups_processed > 0,
                    f"partition {p} ran no kernel group on the mesh")
            require(log_bytes(h) == direct[p],
                    f"partition {p}: mesh log differs from the direct path's")
    finally:
        for h in harnesses.values():
            h.close()
    say(f"  (A) parity: {n_shards} logs byte-identical pair by pair "
        f"({sum(len(v) for v in direct.values())} records); dispatches="
        f"{runner.dispatches} coalesced={runner.coalesced_dispatches} "
        f"shard devices={sorted(_name(d) for d in runner.shard_devices)}")
    require(runner.coalesced_dispatches >= 1, "no coalesced mesh dispatch")
    require(len(runner.shard_devices) == n_shards,
            f"shards lived on {len(runner.shard_devices)} devices, not {n_shards}")


def served_mesh(args, out_dir: Path, n_shards: int) -> None:
    """(B): 1 broker, 4 partitions — the broker builds the mesh runner itself
    from the attached devices."""
    from zeebe_tpu.testing.workloads import one_task

    tally = Tally()
    retry = Retrying(args.seed)
    served = Served(out_dir / "data", partitions=n_shards, tally=tally)
    try:
        runner = served.leaders()[1].mesh_runner
        require(runner is not None and runner.n_shards == n_shards,
                f"the broker did not build a {n_shards}-shard mesh runner")
        t0 = time.monotonic()
        result = run_load(served, [one_task()],
                          one_task_plan(args.instances), tally, retry,
                          client_threads=8, workers_per_type=8,
                          timeout_s=args.phase_timeout)
        say(f"  (B) served one_task over {n_shards} partitions: {result} "
            f"wall_s={time.monotonic() - t0:.1f}")
        say(f"  client retries: {dict(retry.counts)}")
        kernel_report(served)
        say(f"  mesh: dispatches={runner.dispatches} groups="
            f"{runner.groups_dispatched} coalesced={runner.coalesced_dispatches}"
            f" shard devices={sorted(_name(d) for d in runner.shard_devices)}")
        say("  note: the mesh path skips shadow verification (ROADMAP S7); "
            "its correctness evidence is (A) and the journal replay")
        require(runner.dispatches > 0 and runner.coalesced_dispatches > 0,
                "no (coalesced) mesh dispatch")
        require(len(runner.shard_devices) == n_shards,
                f"shards lived on {len(runner.shard_devices)} devices, "
                f"not {n_shards}")
        replay_agrees(served)
    finally:
        served.stop()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    parser.add_argument("--instances", type=int, default=10_000,
                        help="one_task instances (mixed9 gets a 40th of it "
                             "per definition)")
    parser.add_argument("--seed", type=int, default=22)
    parser.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                        help="output directory (a fresh data dir goes under it)")
    parser.add_argument("--phase-timeout", type=float, default=900.0)
    args = parser.parse_args(argv)

    t_start = time.monotonic()
    # every kernel group is re-executed by the host oracle and byte-compared
    # before commit (read once, when the process-wide ladder is built)
    os.environ["ZEEBE_BROKER_DEVICE_SHADOWSAMPLERATE"] = "1"

    from zeebe_tpu import native
    from zeebe_tpu.utils import backend
    from zeebe_tpu.utils.xla_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    ledger = CompileLedger()

    import jax
    import jaxlib

    # phase 1: the device, before anything else
    devices = backend.devices()
    first = devices[0]
    rehearsal = first.platform != "tpu"
    if rehearsal:
        require(backend.cpu_requested(),
                f"no TPU: jax found {first.platform} and the CPU was not "
                "asked for")
    require(len(devices) == args.chips,
            f"--chips {args.chips} but jax sees {len(devices)} device(s)")
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    codec = native.load_codec()
    require(codec is not None, "native codec did not build from codec.c")
    say(f"phase 1 device: platform={first.platform} kind={first.device_kind} "
        f"count={len(devices)} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu_version} device_up_s={time.monotonic() - t_start:.1f}")
    say(f"  native codec: loaded, built from codec.c in this run: "
        f"{'_zb_codec' in native.BUILT_HERE}")
    say(f"  compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries at start)")
    if rehearsal:
        say("  REHEARSAL on the host CPU: same phases, no result at the end")

    out_dir = Path(args.out) / time.strftime("%Y%m%dT%H%M%S")
    out_dir.mkdir(parents=True)
    router = hold_groups_on_the_accelerator()
    say(f"  routing: every group held on the accelerator; link "
        f"put/get measured in-process at the first group")

    if args.chips == 4:
        say("phase 2-3 replaced by the four-chip path:")
        t0 = time.monotonic()
        mesh_parity(args, n_shards=4)
        say(f"  (A) wall_s={time.monotonic() - t0:.1f}")
        served_mesh(args, out_dir, n_shards=4)
    else:
        served_one_chip(args, out_dir, router, first.platform)

    say(f"compiles: {ledger.report()}")
    memory = first.memory_stats() or {}
    say(f"peak_bytes_in_use: {memory.get('peak_bytes_in_use', 'not reported')}")
    say(f"total wall_s={time.monotonic() - t_start:.1f}")
    if rehearsal:
        say(f"rehearsal passed on {first.platform}: not a chip run, no result")
        return REHEARSAL_EXIT
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
