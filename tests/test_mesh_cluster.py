"""The mesh path as a multi-chip host takes it by default: a cluster's
partitions as the shards of one ``MeshKernelRunner``, held to the same
shadow verification as the single-device path, with the runner's own
instruments.

The oracles are the ones the single-device path is held to: a partition's
log equals the log of the same run with every group on one device, and a
mesh result that a faulty device got wrong is replaced by the host oracle's
steps before anything of it is committed."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from zeebe_tpu.client import ZeebeTpuClient
from zeebe_tpu.engine.device_health import (
    HEALTHY,
    SUSPECT,
    reset_shared_device_health,
    shared_device_health,
)
from zeebe_tpu.gateway import ClusterRuntime, Gateway
from zeebe_tpu.logstreams import LogAppendEntry
from zeebe_tpu.models.bpmn import Bpmn, to_bpmn_xml
from zeebe_tpu.observability import profiler
from zeebe_tpu.parallel import mesh_runner as mr
from zeebe_tpu.parallel.mesh_runner import MeshKernelRunner, mesh_shards
from zeebe_tpu.protocol import ValueType, command
from zeebe_tpu.protocol.intent import ProcessInstanceCreationIntent
from zeebe_tpu.testing import EngineHarness, distributing_client


@pytest.fixture(autouse=True)
def _healthy_ladder():
    """The ladder is process-wide: every test starts HEALTHY and leaves no
    posture (a latched SUSPECT, a forced sample rate) behind."""
    reset_shared_device_health()
    yield
    reset_shared_device_health()


def one_task(pid="one_task"):
    return (Bpmn.create_executable_process(pid)
            .start_event("s").service_task("t", job_type="work")
            .end_event("e").done())


def log_bytes(h: EngineHarness) -> list:
    return [(v.position, v.record.to_bytes(), v.processed, v.source_position)
            for v in h.stream.scan()]


def write_creations(h: EngineHarness, n: int, pid: str = "one_task") -> None:
    """``n`` creations written raw, so that one pump admits them as one
    group (the same ingress shape in every run compared)."""
    for i in range(n):
        rec = command(
            ValueType.PROCESS_INSTANCE_CREATION,
            ProcessInstanceCreationIntent.CREATE,
            {"bpmnProcessId": pid, "version": -1, "variables": {"i": i}},
        ).replace(request_id=2, request_stream_id=0)
        h.stream.writer.try_write([LogAppendEntry(rec)])


def drive(h: EngineHarness, n: int) -> None:
    write_creations(h, n)
    h.pump()
    for job in h.activate_jobs("work", max_jobs=n):
        h.complete_job(job["key"], None)


def solo_log(n: int, **backend) -> list:
    """The run on one device, its kernel backend's ``backend`` attributes
    set as the mesh run's."""
    h = EngineHarness(use_kernel_backend=True)
    for name, value in backend.items():
        setattr(h.kernel_backend, name, value)
    try:
        h.deploy(one_task())
        drive(h, n)
        return log_bytes(h)
    finally:
        h.close()


class FaultyOnce(MeshKernelRunner):
    """A mesh whose first fetched chunk comes back with one event row wrong:
    token 0's ``task_arrive`` flag flipped at the first step of shard 0."""

    planted = 0

    def _fetch(self, packed):
        flat = np.array(super()._fetch(packed))
        if not self.planted:
            self.planted += 1
            flat[0, 0] ^= 2
        return flat


class TestShadowOnTheMesh:
    def test_every_mesh_group_is_verified_at_rate_one(self):
        shared_device_health().cfg.shadow_sample_rate = 1.0
        base = solo_log(5)
        checks0 = shared_device_health().shadow_checks
        runner = MeshKernelRunner(n_shards=3)
        h = EngineHarness(use_kernel_backend=True, mesh_runner=runner)
        try:
            h.deploy(one_task())
            drive(h, 5)
            backend = h.kernel_backend
            assert backend.groups_processed >= 2
            assert runner.groups_dispatched == backend.groups_processed
            assert (backend.health.shadow_checks - checks0
                    == backend.groups_processed)
            assert backend.health.shadow_mismatches == 0
            assert backend.health.state == HEALTHY
            assert log_bytes(h) == base
        finally:
            h.close()

    def test_a_planted_wrong_row_is_replaced_by_the_oracles_steps(self):
        shared_device_health().cfg.shadow_sample_rate = 1.0
        base = solo_log(5)
        runner = FaultyOnce(n_shards=3)
        h = EngineHarness(use_kernel_backend=True, mesh_runner=runner)
        try:
            h.deploy(one_task())
            drive(h, 5)
            backend = h.kernel_backend
            assert runner.planted == 1
            assert backend.shadow_quarantined == 1
            assert backend.health.shadow_mismatches == 1
            # a mismatch latches the ladder, as on one chip
            assert backend.health.state == SUSPECT
            assert log_bytes(h) == base
        finally:
            h.close()

    def test_an_unsampled_mesh_group_keeps_no_rows_and_is_not_verified(self):
        shared_device_health().cfg.shadow_sample_rate = 0.0
        kept = []

        class Watching(MeshKernelRunner):
            def run_groups(self, requests):
                results = super().run_groups(requests)
                kept.extend(r.rows for r in results)
                return results

        h = EngineHarness(use_kernel_backend=True,
                          mesh_runner=Watching(n_shards=3))
        try:
            h.deploy(one_task())
            drive(h, 3)
            assert kept and all(rows is None for rows in kept)
            assert h.kernel_backend.health.shadow_checks == 0
        finally:
            h.close()

    def test_a_group_repadded_by_its_dispatch_verifies_at_that_geometry(self):
        """Two partitions coalesced in one dispatch: one group of 70
        instances (the 256 bucket) in chunks of 8 steps, one of a single
        instance (the 64 bucket) in chunks of 16. Each comes back at the
        dispatch's geometry, (256, 256, 16), and its oracle runs there, so
        padding never reads as a mismatch."""
        shared_device_health().cfg.shadow_sample_rate = 1.0
        sizes = {1: 70, 2: 1}
        settings = {1: {"max_group": 256}, 2: {"chunk_steps": 16}}
        base = {p: solo_log(n, **settings[p]) for p, n in sizes.items()}
        checks0 = shared_device_health().shadow_checks
        seen = []

        class Recording(MeshKernelRunner):
            def _dispatch(self, requests):
                seen.append(sorted((r.num_instances, r.chunk_steps)
                                   for r in requests))
                return super()._dispatch(requests)

        runner = Recording(n_shards=3, batch_window_s=0.35)
        harnesses = {p: EngineHarness(use_kernel_backend=True,
                                      mesh_runner=runner) for p in sizes}
        for p, h in harnesses.items():
            for name, value in settings[p].items():
                setattr(h.kernel_backend, name, value)
        for h in harnesses.values():
            h.deploy(one_task())
        barrier = threading.Barrier(len(sizes))
        errors: list[BaseException] = []

        def run(p: int) -> None:
            try:
                h = harnesses[p]
                write_creations(h, sizes[p])
                barrier.wait(timeout=30)
                h.pump()
                for job in h.activate_jobs("work", max_jobs=sizes[p]):
                    h.complete_job(job["key"], None)
            except BaseException as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)

        threads = [threading.Thread(target=run, args=(p,)) for p in sizes]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        try:
            assert not any(t.is_alive() for t in threads)
            assert not errors, errors
            assert [(64, 16), (256, 8)] in seen, seen
            health = shared_device_health()
            assert health.shadow_checks - checks0 == sum(
                h.kernel_backend.groups_processed for h in harnesses.values())
            assert health.shadow_mismatches == 0
            for p, h in harnesses.items():
                assert log_bytes(h) == base[p], f"partition {p} diverged"
        finally:
            for h in harnesses.values():
                h.close()


def histogram(name: str, partition: str | None = None) -> tuple:
    """(count, sum) of one family, one partition's series or all."""
    count = total = 0
    for family, kind, labels, value in mr.REGISTRY.snapshot():
        if family.endswith(name) and kind == "histogram" and (
                partition is None or labels == f'{{partition="{partition}"}}'):
            count += value[0]
            total += value[1]
    return count, total


class TestInstruments:
    def test_one_observation_a_dispatch_and_one_a_group(self):
        names = ("mesh_dispatch", "mesh_uploads", "mesh_groups")
        before = {n: histogram(f"stream_processor_pipeline_{n}") for n in names}
        wait0 = histogram("stream_processor_pipeline_mesh_wait", "1")
        runner = MeshKernelRunner(n_shards=3)
        h = EngineHarness(use_kernel_backend=True, mesh_runner=runner)
        try:
            h.deploy(one_task())
            drive(h, 4)
            groups = h.kernel_backend.groups_processed
        finally:
            h.close()
        after = {n: histogram(f"stream_processor_pipeline_{n}") for n in names}
        delta = {n: (after[n][0] - before[n][0], after[n][1] - before[n][1])
                 for n in names}
        assert groups >= 2
        for n in names:
            assert delta[n][0] == runner.dispatches
        # thirteen puts a dispatch: the eight planes the host fills, the
        # incident plane and the four per-shard counter rows
        assert delta["mesh_uploads"][1] == 13 * runner.dispatches
        assert delta["mesh_groups"][1] == runner.groups_dispatched == groups
        assert delta["mesh_dispatch"][1] > 0
        wait1 = histogram("stream_processor_pipeline_mesh_wait", "1")
        assert wait1[0] - wait0[0] == groups
        assert wait1[1] >= wait0[1]

    def test_coalesced_dispatches_observe_one_to_n_groups(self):
        count0, sum0 = histogram("stream_processor_pipeline_mesh_groups")
        runner = MeshKernelRunner(n_shards=3, batch_window_s=0.35)
        harnesses = [EngineHarness(use_kernel_backend=True, mesh_runner=runner)
                     for _ in range(3)]
        for h in harnesses:
            h.deploy(one_task())
        barrier = threading.Barrier(3)
        errors: list[BaseException] = []

        def run(h) -> None:
            try:
                write_creations(h, 2)
                barrier.wait(timeout=30)
                h.pump()
            except BaseException as exc:  # noqa: BLE001 — surfaced below
                errors.append(exc)

        threads = [threading.Thread(target=run, args=(h,)) for h in harnesses]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for h in harnesses:
            h.close()
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        count1, sum1 = histogram("stream_processor_pipeline_mesh_groups")
        assert count1 - count0 == runner.dispatches
        assert sum1 - sum0 == runner.groups_dispatched
        assert runner.coalesced_dispatches >= 1
        mean = (sum1 - sum0) / (count1 - count0)
        assert 1 <= mean <= 3

    def test_the_phases_and_the_span(self, monkeypatch):
        assert profiler.MESH_PHASES == ("mesh_put", "mesh_run", "mesh_unpack")
        assert not set(profiler.MESH_PHASES) & set(
            profiler.PHASES + profiler.REQUEST_PHASES + profiler.CATCH_PHASES)
        entered = []
        real = mr.phase_annotation

        def recording(phase):
            entered.append(phase)
            return real(phase)

        monkeypatch.setattr(mr, "phase_annotation", recording)
        from zeebe_tpu.observability.tracer import configure_tracing, get_tracer

        configure_tracing(enabled=True, sample_rate=1.0)
        try:
            runner = MeshKernelRunner(n_shards=3)
            h = EngineHarness(use_kernel_backend=True, mesh_runner=runner)
            try:
                h.deploy(one_task())
                drive(h, 2)
            finally:
                h.close()
            spans = [s for s in get_tracer().collector.snapshot()
                     if s.name == "mesh.dispatch"]
        finally:
            configure_tracing(enabled=False)
        assert entered[:2] == ["mesh_put", "mesh_run"]
        assert entered.count("mesh_put") == runner.dispatches
        assert entered.count("mesh_unpack") == entered.count("mesh_run")
        assert len(spans) == runner.dispatches
        attrs = spans[0].attrs
        assert set(attrs) == {"groups", "shards", "partitions", "I", "T",
                              "chunks"}
        assert (attrs["groups"], attrs["shards"], attrs["partitions"]) == (
            1, 3, "1")
        assert spans[0].trace_id.startswith("1:g")


class TestTheDefault:
    def test_auto_resolves_min_devices_partitions_and_none_below_two(self):
        assert mesh_shards(-1, 3, 8) == 3
        assert mesh_shards(-1, 8, 4) == 4
        assert mesh_shards(-1, 1, 8) == 0
        assert mesh_shards(-1, 3, 1) == 0
        assert mesh_shards(0, 3, 8) == 0
        assert mesh_shards(2, 3, 8) == 2

    def test_one_runner_over_three_devices_shared_by_three_brokers(
            self, tmp_path):
        rt = ClusterRuntime(broker_count=3, partition_count=3,
                            replication_factor=3, directory=tmp_path)
        try:
            runner = rt.mesh_runner
            assert runner is not None and runner.n_shards == 3
            for broker in rt.brokers.values():
                assert broker._mesh_runner() is runner
                assert broker._owned_mesh_runner is None
        finally:
            rt.stop()

    def test_zero_is_off_for_every_broker(self, tmp_path):
        rt = ClusterRuntime(broker_count=3, partition_count=3,
                            replication_factor=3, directory=tmp_path,
                            kernel_mesh_shards=0)
        try:
            assert rt.mesh_runner is None
            for broker in rt.brokers.values():
                assert broker._mesh_runner() is None
        finally:
            rt.stop()


# ---------------------------------------------------------------------------
# the deployment of the cell, end to end on the served path


def leader_log(rt: ClusterRuntime, pid: int) -> list:
    """A partition leader's log, record for record, with what the wall
    clock writes into it (each record's timestamp, the clock nonce in a
    gateway request's id, a job's deadline) set aside: two runs of one
    command sequence differ in nothing else."""

    def scrub(value):
        if isinstance(value, dict):
            return {k: 0 if k == "deadline" else scrub(v)
                    for k, v in value.items()}
        if isinstance(value, list):
            return [scrub(v) for v in value]
        return value

    return [(v.position, v.source_position,
             v.record.replace(timestamp=0, request_id=0,
                              value=scrub(v.record.value))
             .to_bytes())
            for v in rt._leader_partition(pid).stream.scan()]


def settled(rt: ClusterRuntime, pids, quiet_s: float = 0.5,
            timeout_s: float = 30.0) -> dict:
    """Every leader's log once none has grown for ``quiet_s``."""
    deadline = time.monotonic() + timeout_s
    last, since = None, time.monotonic()
    while time.monotonic() < deadline:
        now = {pid: leader_log(rt, pid) for pid in pids}
        if now != last:
            last, since = now, time.monotonic()
        elif time.monotonic() - since >= quiet_s:
            return now
        time.sleep(0.05)
    raise AssertionError("the partitions' logs did not settle")


def served_run(tmp_path, shards: int, instances: int = 6) -> tuple:
    rt = ClusterRuntime(broker_count=3, partition_count=3,
                        replication_factor=3, directory=tmp_path,
                        kernel_mesh_shards=shards)
    rt.start()
    gateway = Gateway(rt)
    gateway.start()
    client = distributing_client(ZeebeTpuClient(gateway.address), rt)
    pids = (1, 2, 3)
    try:
        client.deploy_resource(("one_task.bpmn", to_bpmn_xml(one_task())))
        # the distribution's acknowledgements are in; the order in which
        # partitions 2 and 3 acknowledge on partition 1 is a race of its own
        # in any two runs, so the comparison starts after them
        head = settled(rt, pids)
        for i in range(instances):
            client.create_instance("one_task", variables={"i": i})
        jobs = []
        deadline = time.monotonic() + 30
        while len(jobs) < instances and time.monotonic() < deadline:
            jobs += client.activate_jobs("work", max_jobs=instances,
                                         request_timeout_ms=0)
        assert len(jobs) == instances
        for job in jobs:
            client.complete_job(job.key, {"done": True})
        logs = {pid: entries[len(head[pid]):]
                for pid, entries in settled(rt, pids).items()}
        assert all(logs.values())
        leaders = [rt._leader_partition(pid) for pid in pids]
        groups = {pid: p.processor.kernel_backend.groups_processed
                  for pid, p in zip(pids, leaders)}
        completed = sum(
            1 for p in leaders for v in p.stream.scan()
            if v.record.intent.name == "ELEMENT_COMPLETED"
            and v.record.value.get("bpmnElementType") == "PROCESS")
        assert completed == instances
        dispatches = rt.mesh_runner.dispatches if rt.mesh_runner else 0
        return logs, groups, dispatches
    finally:
        client.close()
        gateway.stop()
        rt.stop()


def test_the_3x3_cluster_on_a_mesh_writes_the_one_device_logs(tmp_path):
    shared_device_health().cfg.shadow_sample_rate = 1.0
    one, one_groups, none = served_run(tmp_path / "one", shards=0)
    mesh, mesh_groups, dispatches = served_run(tmp_path / "mesh", shards=3)
    assert none == 0 and dispatches > 0
    assert mesh_groups == one_groups and all(mesh_groups.values())
    for pid in (1, 2, 3):
        assert mesh[pid] == one[pid], f"partition {pid} diverged"
    assert shared_device_health().shadow_mismatches == 0
