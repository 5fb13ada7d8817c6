"""The inside of a command's round trip (ISSUE 37): the five stamps of
``ClusterRuntime.submit`` and the histograms, annotations and spans made of
them; the long-poll peek; the collector's hook. The flush instruments of the
same issue are cases of ``test_joint_flush.py``."""

from __future__ import annotations

import gc
import sys
import threading
import time

import pytest

from tests.test_broker_cluster import create_cmd
from tests.test_joint_flush import Recorder
from zeebe_tpu.gateway import ClusterRuntime
from zeebe_tpu.gateway import broker_client
from zeebe_tpu.gateway.broker_client import (
    NoLeaderError,
    RequestTimeoutError,
    ResourceExhaustedError,
)
from zeebe_tpu.observability import profiler

PARTS = ("rpc_lock_wait", "rpc_write", "rpc_await", "rpc_wake", "rpc")
LAYOUTS = {"rf1": (1, 1, 1), "3x3": (3, 3, 3)}


def record_rpc(rt) -> dict:
    """Every partition's five histograms replaced by recorders:
    ``{partition: {part: Recorder}}``."""
    recs = {}
    for pid in rt._m_rpc:
        recs[pid] = {part: Recorder() for part in PARTS}
        rt._m_rpc[pid] = tuple(recs[pid][part] for part in PARTS)
    return recs


def start_runtime(tmp_path, layout: str = "rf1") -> ClusterRuntime:
    brokers, partitions, rf = LAYOUTS[layout]
    rt = ClusterRuntime(broker_count=brokers, partition_count=partitions,
                        replication_factor=rf, directory=tmp_path,
                        kernel_backend=False)
    rt.start()
    return rt


@pytest.fixture()
def rf1(tmp_path):
    rt = start_runtime(tmp_path)
    yield rt
    rt.stop()


def reject(rt, partition_id: int = 1, **kw):
    response = rt.submit(partition_id, create_cmd("no_such_process"), **kw)
    assert response.is_rejection
    return response


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_written_request_observes_its_four_parts_and_their_whole(
        tmp_path, layout):
    rt = start_runtime(tmp_path, layout)
    try:
        recs = record_rpc(rt)
        n = 6
        for pid in recs:
            for _ in range(n):
                reject(rt, pid)
    finally:
        rt.stop()
    for pid, rec in recs.items():
        for part in PARTS:
            assert len(rec[part].values) == n, (pid, part)
            assert all(v >= 0 for v in rec[part].values), (pid, part)
        for lock_wait, write, awaited, wake, whole in zip(
                *(rec[part].values for part in PARTS)):
            assert lock_wait + write + awaited + wake == pytest.approx(
                whole, abs=50e-6)
            assert write > 0 and awaited > 0


def test_the_registered_histograms_count_the_requests(rf1):
    from zeebe_tpu.utils.metrics import REGISTRY

    def counts() -> dict:
        return {name.rsplit("_pipeline_", 1)[1]: value[0]
                for name, kind, labels, value in REGISTRY.snapshot()
                if kind == "histogram" and "_pipeline_rpc" in name
                and labels == '{partition="1"}'}

    before = counts()
    assert set(before) == set(PARTS)
    for _ in range(5):
        reject(rf1)
    assert {part: n - before[part] for part, n in counts().items()} == \
        {part: 5 for part in PARTS}


class TestARequestThatWasNotAnswered:
    """Shed, leaderless, timed out: counted where they are today, and in
    none of the five."""

    def test_shed(self, rf1):
        from zeebe_tpu.broker.partition import BackpressureExceeded

        recs = record_rpc(rf1)
        leader = rf1._leader_partition(1)

        def refuse(_record):
            raise BackpressureExceeded("partition 1 is full")

        leader.client_write = refuse
        with pytest.raises(ResourceExhaustedError):
            rf1.submit(1, create_cmd("no_such_process"))
        assert all(rec.values == [] for rec in recs[1].values())

    def test_leaderless(self, rf1, monkeypatch):
        recs = record_rpc(rf1)
        monkeypatch.setattr(rf1, "_leader_partition", lambda pid: None)
        with pytest.raises(NoLeaderError):
            rf1.submit(1, create_cmd("no_such_process"), timeout_s=0.05)
        with pytest.raises(NoLeaderError):
            rf1.submit(9, create_cmd("no_such_process"))  # no such partition
        assert all(rec.values == [] for rec in recs[1].values())

    def test_timed_out(self, rf1, monkeypatch):
        recs = record_rpc(rf1)
        monkeypatch.setattr(rf1, "_resolve_request", lambda rid, record: None)
        with pytest.raises(RequestTimeoutError):
            rf1.submit(1, create_cmd("no_such_process"), timeout_s=0.2)
        assert all(rec.values == [] for rec in recs[1].values())
        assert not rf1._pending and not rf1._responses


def test_a_held_lock_is_lock_wait_and_not_write(rf1):
    reject(rf1)  # the partition is up and answering
    recs = record_rpc(rf1)
    done = threading.Event()

    def submit() -> None:
        reject(rf1)
        done.set()

    lock = rf1._plocks[1]
    with lock:  # as a partition thread inside a kernel group would
        thread = threading.Thread(target=submit, daemon=True)
        thread.start()
        time.sleep(0.03)
        assert not done.is_set()
    assert done.wait(10)
    thread.join(5)
    assert not thread.is_alive()
    assert recs[1]["rpc_lock_wait"].values[0] >= 0.02
    assert recs[1]["rpc_write"].values[0] < 0.02
    assert recs[1]["rpc"].values[0] >= 0.02


def test_the_resolving_thread_takes_its_own_stamp(rf1, monkeypatch):
    """What the resolver does after ``set()`` is the partition's business:
    a resolver that sleeps 5 ms there leaves ``rpc_wake`` under 5 ms, and
    the stamp is taken by the thread that resolves, not by the waiter."""
    resolvers: list = []
    real_set = broker_client._Waiter.set

    def slow_set(self) -> None:
        assert self.resolved_at > 0  # stamped before the event is set
        resolvers.append(threading.current_thread().name)
        real_set(self)
        time.sleep(0.005)

    monkeypatch.setattr(broker_client._Waiter, "set", slow_set)
    recs = record_rpc(rf1)
    for _ in range(5):
        reject(rf1)
    assert resolvers == ["partition-1"] * 5
    assert all(0 <= wake < 0.005 for wake in recs[1]["rpc_wake"].values)


class TestPeek:
    def test_one_observation_a_call_that_got_the_lock(self, tmp_path):
        rt = ClusterRuntime(directory=tmp_path, kernel_backend=False)
        try:
            rec = rt._m_peek[1] = Recorder()
            for _ in range(3):  # not started: no leader, but the lock is got
                assert rt.has_activatable_jobs(1, "work") is None
            assert len(rec.values) == 3
            assert all(0 <= v < 1 for v in rec.values)
        finally:
            rt.stop()

    def test_none_when_the_lock_is_not_got(self, tmp_path):
        class Stalled:
            def acquire(self, timeout=None) -> bool:
                return False

        rt = ClusterRuntime(directory=tmp_path, kernel_backend=False)
        try:
            rec = rt._m_peek[1] = Recorder()
            rt._plocks[1] = Stalled()
            assert rt.has_activatable_jobs(1, "work") is None
            assert rt.has_activatable_jobs(7, "work") is None  # no partition
            assert rec.values == []
        finally:
            rt.stop()

    def test_a_started_runtime_reads_the_committed_index(self, rf1):
        rec = rf1._m_peek[1] = Recorder()
        assert rf1.has_activatable_jobs(1, "work") is False
        assert len(rec.values) == 1


def test_observations_from_many_threads_are_all_counted(tmp_path):
    """Gateway threads share a partition's histograms: with more observers
    than cores under a short switch interval no observation is lost."""
    rt = ClusterRuntime(directory=tmp_path, kernel_backend=False)
    children = rt._m_rpc[1]
    before = [child.count for child in children]
    threads_n, each = 16, 400

    def observe() -> None:
        for _ in range(each):
            with rt._m_rpc_lock:
                for child in children:
                    child.observe(0.001)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=observe, daemon=True)
                   for _ in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        rt.stop()
    assert [child.count - n for child, n in zip(children, before)] == \
        [threads_n * each] * len(children)


class TestSpans:
    @pytest.fixture()
    def tracer(self):
        from zeebe_tpu.observability.tracer import configure_tracing

        tracer = configure_tracing(enabled=True, seed=7, sample_rate=1.0)
        yield tracer
        configure_tracing(enabled=False, reset=True)

    def test_lock_wait_and_reply_lie_where_they_happened(self, rf1, tracer):
        from zeebe_tpu.observability import critical_path

        reject(rf1)
        recs = record_rpc(rf1)
        tracer.collector.clear()
        lock = rf1._plocks[1]
        done = threading.Event()

        def submit() -> None:
            reject(rf1)
            done.set()

        with lock:
            threading.Thread(target=submit, daemon=True).start()
            time.sleep(0.03)
        assert done.wait(10)
        spans = [s.to_dict() for s in tracer.collector.snapshot()]
        root, = [s for s in spans if s["name"] == "gateway.request"]
        by_name = {s["name"]: s for s in spans
                   if s["traceId"] == root["traceId"]}
        lock_wait, reply = by_name["gateway.lock_wait"], by_name["gateway.reply"]
        assert lock_wait["parent"] == reply["parent"] == "gateway.request"
        rec = {part: r.values[0] for part, r in recs[1].items()}
        # the same stamps, to the microsecond a span keeps
        assert root["durUs"] == pytest.approx(rec["rpc"] * 1e6, abs=2)
        assert lock_wait["durUs"] == pytest.approx(
            rec["rpc_lock_wait"] * 1e6, abs=2)
        assert lock_wait["durUs"] >= 20_000
        assert lock_wait["startUs"] == pytest.approx(root["startUs"], abs=2)
        assert reply["durUs"] == pytest.approx(rec["rpc_wake"] * 1e6, abs=2)
        assert reply["startUs"] + reply["durUs"] == pytest.approx(
            root["startUs"] + root["durUs"], abs=3)
        breakdown, = [b for b in critical_path.breakdowns_from_spans(spans)
                      if b["traceId"] == root["traceId"]]
        assert breakdown["rootName"] == "gateway.request"
        assert breakdown["edges"]["queue"] >= lock_wait["durUs"] - 2
        assert breakdown["edges"]["reply"] >= reply["durUs"] - 2
        assert critical_path._EDGE_BY_NAME["gateway.lock_wait"] == "queue"
        assert critical_path._EDGE_BY_NAME["gateway.reply"] == "reply"

    def test_tracer_off_emits_none_and_still_observes(self, rf1):
        from zeebe_tpu.observability import get_tracer

        assert not get_tracer().enabled
        recs = record_rpc(rf1)
        reject(rf1)
        assert len(recs[1]["rpc"].values) == 1
        assert not [s for s in get_tracer().collector.snapshot()
                    if s.name.startswith("gateway.")]


class TestAnnotations:
    def test_request_phases_are_a_tuple_of_their_own(self):
        import jax

        assert profiler.REQUEST_PHASES == (
            "rpc_lock_wait", "rpc_write", "rpc_await")
        assert not set(profiler.REQUEST_PHASES) & set(profiler.PHASES)
        for phase in profiler.REQUEST_PHASES:
            with profiler.phase_annotation(phase) as annotation:
                assert isinstance(annotation, jax.profiler.TraceAnnotation)

    def test_a_capture_on_the_cpu_backend_holds_all_three(self, rf1, tmp_path):
        import jax
        from jax.profiler import ProfileData

        reject(rf1)
        trace_dir = tmp_path / "capture"
        jax.profiler.start_trace(str(trace_dir))
        try:
            for _ in range(3):
                reject(rf1)
        finally:
            jax.profiler.stop_trace()
        found = sorted(trace_dir.rglob("*.xplane.pb"))
        assert found
        names = [event.name
                 for plane in ProfileData.from_file(str(found[-1])).planes
                 for line in plane.lines for event in line.events
                 if event.name.startswith(profiler.PHASE_PREFIX)]
        for phase in profiler.REQUEST_PHASES:
            assert names.count(profiler.PHASE_PREFIX + phase) >= 3, phase


class TestCollections:
    @pytest.fixture()
    def alone(self, monkeypatch):
        """This test's leases only: runtimes that earlier tests of the same
        process never stopped keep theirs."""
        monkeypatch.setattr(profiler, "_GC_LEASES", set())
        installed = profiler._on_gc in gc.callbacks
        if installed:
            gc.callbacks.remove(profiler._on_gc)
        yield
        if profiler._on_gc in gc.callbacks:
            gc.callbacks.remove(profiler._on_gc)
        if installed:
            gc.callbacks.append(profiler._on_gc)

    def test_one_hook_a_process_gone_after_the_last_stop(self, alone, tmp_path):
        first = start_runtime(tmp_path / "a")
        second = start_runtime(tmp_path / "b")
        try:
            assert gc.callbacks.count(profiler._on_gc) == 1
            rec = Recorder()
            children = profiler._GC_PAUSE_BY_GENERATION
            profiler._GC_PAUSE_BY_GENERATION = (children[0], children[1], rec)
            try:
                gc.collect()
            finally:
                profiler._GC_PAUSE_BY_GENERATION = children
            assert len(rec.values) == 1 and 0 < rec.values[0] < 60
            first.stop()
            assert gc.callbacks.count(profiler._on_gc) == 1
        finally:
            first.stop()  # a second stop releases nothing
            assert gc.callbacks.count(profiler._on_gc) == 1
            second.stop()
        assert profiler._on_gc not in gc.callbacks
        assert not profiler._GC_LEASES

    def test_a_runtime_never_started_holds_no_lease(self, alone, tmp_path):
        rt = ClusterRuntime(directory=tmp_path, kernel_backend=False)
        rt.stop()
        assert profiler._on_gc not in gc.callbacks
