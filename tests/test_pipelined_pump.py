"""Pipelined pump (ISSUE 17): cross-wave double-buffered dispatch and the
fully async ack path.

Three contracts under test:

1. **Ack-after-covering-fsync** stays the only legal ordering with acks
   released from the journal's flush callback instead of the pump tail: a
   failed covering fsync (seeded and forced) must release NOTHING, and any
   successful covering fsync — the pump boundary's or an external barrier's
   — releases exactly the replies it covers, once.

2. **Byte parity**: the speculating pipelined pump (wave k+1 admitted and
   dispatched inside wave k's transaction) writes a log byte-identical to
   the sequential engine's, and stale speculations are discarded, never
   consumed against state their admission snapshot no longer matches.

3. **The overlap receipt is real**: the dispatch-overlap gauge commits a
   nonzero EMA when speculation runs, and the speculative-group counters
   account every stash as consumed or discarded.
"""

from __future__ import annotations

import pytest

from zeebe_tpu.journal import SegmentedJournal
from zeebe_tpu.journal.journal import FlushFailedError
from zeebe_tpu.logstreams import LogAppendEntry, LogStream
from zeebe_tpu.models.bpmn import Bpmn, to_bpmn_xml
from zeebe_tpu.protocol import ValueType, command
from zeebe_tpu.protocol.intent import (
    DeploymentIntent,
    ProcessInstanceCreationIntent,
    SignalIntent,
)
from zeebe_tpu.state import ColumnFamilyCode, ZbDb
from zeebe_tpu.stream import ProcessingResultBuilder, RecordProcessor, StreamProcessor
from zeebe_tpu.testing import EngineHarness
from zeebe_tpu.utils import storage_io
from zeebe_tpu.utils.metrics import REGISTRY


# -- metric helpers -----------------------------------------------------------

def _child_value(name: str, labels: tuple) -> float:
    fam = REGISTRY._metrics.get(name)
    if fam is None:
        return 0.0
    child = fam._children.get(labels)
    return float(child.value) if child is not None else 0.0


def _spec_counts() -> tuple[float, float]:
    return (
        _child_value("zeebe_kernel_speculative_groups", ("1", "consumed")),
        _child_value("zeebe_kernel_speculative_groups", ("1", "discarded")),
    )


def _overlap_gauge() -> float:
    return _child_value("zeebe_kernel_dispatch_overlap_ratio", ("1",))


# -- fake sequential machine for the ack tests --------------------------------

INCREMENT = SignalIntent.BROADCAST
INCREMENTED = SignalIntent.BROADCASTED


class CounterProcessor(RecordProcessor):
    def __init__(self, db: ZbDb):
        self.cf = db.column_family(ColumnFamilyCode.DEFAULT)

    def accepts(self, value_type):
        return value_type == ValueType.SIGNAL

    def process(self, logged, result):
        from zeebe_tpu.protocol import event

        amount = logged.record.value.get("amount", 1)
        ev = event(ValueType.SIGNAL, INCREMENTED, {"amount": amount})
        self.cf.put(("counter",), (self.cf.get(("counter",)) or 0) + amount)
        result.append_record(ev)
        if logged.record.request_id >= 0:
            result.with_response(ev, logged.record.request_stream_id,
                                 logged.record.request_id)

    def replay(self, logged):
        pass


def make_gated_env(tmp_path, flush_interval=3600.0):
    """Processor whose client acks are gated on the covering journal fsync
    (a huge flush_interval: the cadence check never fires on its own, so
    every release goes through an explicit covering flush)."""
    journal = SegmentedJournal(tmp_path / "log", flush_interval=flush_interval)
    stream = LogStream(journal, partition_id=1, clock=lambda: 1000)
    db = ZbDb()
    responses = []
    sp = StreamProcessor(stream, db, CounterProcessor(db),
                         response_sink=responses.append)
    sp.start()
    return journal, stream, sp, responses


def write_cmd(stream, request_id=-1, amount=1):
    return stream.writer.try_write([LogAppendEntry(
        command(ValueType.SIGNAL, INCREMENT, {"amount": amount},
                request_id=request_id, request_stream_id=9))])


class FsyncFailOnJournal:
    """Every fsync on a journal path fails; writes pass untouched."""

    def write_fault(self, path, n):
        return ("ok", 0)

    def fsync_fault(self, path):
        from zeebe_tpu.testing.chaos_disk import classify_path

        if classify_path(path) == "journal":
            raise OSError(5, f"chaos fsync failure on {path}")


# -- 1. async ack ordering ----------------------------------------------------

class TestAsyncAckOrdering:
    def test_reply_held_until_covering_fsync_then_released_by_boundary(
            self, tmp_path):
        journal, stream, sp, responses = make_gated_env(tmp_path)
        write_cmd(stream, request_id=7)
        # the step processes and commits, but the covering fsync has not run:
        # the reply must still be queued (ack-after-covering-fsync)
        assert sp.process_next()
        assert responses == []
        assert journal.last_flushed_index < journal.last_index
        # the idle boundary forces the covering fsync; the flush CALLBACK
        # (not the pump tail) releases the reply
        sp.run_until_idle()
        assert [r.request_id for r in responses] == [7]
        assert journal.last_flushed_index == journal.last_index
        journal.close()

    def test_external_covering_fsync_releases_via_flush_callback(
            self, tmp_path):
        """Anyone's successful covering fsync frees the replies it covers —
        the async path's point: release happens the moment durability is
        real, not at the next pump tail."""
        journal, stream, sp, responses = make_gated_env(tmp_path)
        write_cmd(stream, request_id=11)
        assert sp.process_next()
        assert responses == []
        journal.flush()  # an external barrier, not the pump
        assert [r.request_id for r in responses] == [11]
        journal.close()

    def test_failed_covering_fsync_releases_nothing(self, tmp_path):
        journal, stream, sp, responses = make_gated_env(tmp_path)
        write_cmd(stream, request_id=13)
        assert sp.process_next()
        assert responses == []
        storage_io.install_controller(FsyncFailOnJournal())
        try:
            with pytest.raises(FlushFailedError):
                sp.run_until_idle()  # boundary forces the covering fsync
        finally:
            storage_io.install_controller(None)
        # the fsync failed BEFORE any flush listener could fire: no reply
        # covers the unfsynced (and now rewound) prefix, and the flush
        # marker did not advance
        assert responses == []
        assert journal.last_flushed_index < sp.last_written_position
        journal.close()

    def test_seeded_fsync_failure_interleave(self, tmp_path):
        """Seeded schedule of fsync failures against flush-callback acks:
        on every failing iteration nothing is released; on every healthy
        iteration exactly the covered reply is released."""
        import random

        rng = random.Random(0xA17)
        for i in range(12):
            fail = rng.random() < 0.4
            journal, stream, sp, responses = make_gated_env(
                tmp_path / f"it{i}")
            write_cmd(stream, request_id=100 + i)
            if fail:
                storage_io.install_controller(FsyncFailOnJournal())
                try:
                    with pytest.raises(FlushFailedError):
                        sp.run_until_idle()
                finally:
                    storage_io.install_controller(None)
                assert responses == []
            else:
                sp.run_until_idle()
                assert [r.request_id for r in responses] == [100 + i]
                assert journal.last_flushed_index == journal.last_index
            journal.close()


# -- 2/3. cross-wave speculation ---------------------------------------------

def one_task(pid="one_task"):
    return (
        Bpmn.create_executable_process(pid)
        .start_event("start")
        .service_task("task", job_type="work")
        .end_event("end")
        .done()
    )


def deploy_cmd(model, name="p.bpmn"):
    return command(ValueType.DEPLOYMENT, DeploymentIntent.CREATE, {
        "resources": [{"resourceName": name, "resource": to_bpmn_xml(model)}],
    })


def create_cmd(process_id="one_task"):
    return command(
        ValueType.PROCESS_INSTANCE_CREATION, ProcessInstanceCreationIntent.CREATE,
        {"bpmnProcessId": process_id, "version": -1, "variables": {}},
    )


def drive_waves(h, n_instances=150):
    """Deploy, then ingest one big creation batch (multiple kernel waves in
    a single pump: the speculation window) and complete all jobs."""
    h.deploy(one_task())
    h.stream.writer.try_write(
        [LogAppendEntry(create_cmd()) for _ in range(n_instances)])
    h.pump()
    for _ in range(10):
        jobs = h.activate_jobs("work", max_jobs=n_instances)
        if not jobs:
            break
        for job in jobs:
            h.complete_job(job["key"])


def log_fingerprint(h):
    out = []
    for logged in h.stream.new_reader(1):
        rec = logged.record
        out.append((
            logged.position, logged.source_position, logged.processed,
            rec.key, rec.record_type.name, rec.value_type.name,
            int(rec.intent), dict(rec.value) if rec.value else {},
        ))
    return out


class TestCrossWaveSpeculation:
    def test_byte_parity_and_speculation_consumed(self):
        consumed0, _ = _spec_counts()
        h_seq = EngineHarness(use_kernel_backend=False)
        try:
            drive_waves(h_seq)
            seq_log = log_fingerprint(h_seq)
        finally:
            h_seq.close()
        h_ker = EngineHarness(use_kernel_backend=True)
        try:
            drive_waves(h_ker)
            ker_log = log_fingerprint(h_ker)
        finally:
            h_ker.close()
        assert ker_log == seq_log
        consumed1, _ = _spec_counts()
        # the parity above must have exercised the speculative path, not
        # bypassed it — the wave ingress spans multiple groups per pump
        assert consumed1 > consumed0

    def test_overlap_gauge_commits_nonzero(self):
        h = EngineHarness(use_kernel_backend=True)
        try:
            drive_waves(h, n_instances=200)
        finally:
            h.close()
        assert _overlap_gauge() > 0.0

    def test_stale_speculation_discarded_not_consumed(self):
        """A stash whose expected reader position no longer matches must be
        discarded — consuming it would process commands against state its
        admission never saw. The sentinel group would crash finish_group if
        it were ever consumed, so a green round proves the discard."""
        h = EngineHarness(use_kernel_backend=True)
        try:
            h.deploy(one_task())
            h.stream.writer.try_write(
                [LogAppendEntry(create_cmd()) for _ in range(8)])
            _, discarded0 = _spec_counts()
            sentinel = object()  # not a _PendingGroup: must never be consumed
            h.processor._spec_group = (sentinel, -999, 0, 0.0)
            h.pump()
            _, discarded1 = _spec_counts()
            assert discarded1 == discarded0 + 1
            # the round still processed the wave correctly via a fresh scan
            jobs = h.activate_jobs("work", max_jobs=8)
            assert len(jobs) == 8
        finally:
            h.close()

    def test_state_epoch_bump_discards_speculation(self):
        """A post-commit task (allowed to open its own transaction) bumps
        the state epoch; an outstanding stash from before the bump must be
        discarded even though the reader position still matches."""
        h = EngineHarness(use_kernel_backend=True)
        try:
            h.deploy(one_task())
            h.stream.writer.try_write(
                [LogAppendEntry(create_cmd()) for _ in range(8)])
            _, discarded0 = _spec_counts()
            sentinel = object()
            h.processor._spec_group = (
                sentinel, h.processor._reader_position,
                h.processor._state_epoch - 1, 0.0)
            h.pump()
            _, discarded1 = _spec_counts()
            assert discarded1 == discarded0 + 1
        finally:
            h.close()

    def test_speculation_disabled_by_knob(self, monkeypatch):
        monkeypatch.setenv("ZEEBE_BROKER_PIPELINE_SPECULATION", "0")
        consumed0, _ = _spec_counts()
        h = EngineHarness(use_kernel_backend=True)
        try:
            assert h.processor._speculation_enabled is False
            drive_waves(h, n_instances=100)
            jobs_done = log_fingerprint(h)
            assert jobs_done  # the run executed
        finally:
            h.close()
        consumed1, _ = _spec_counts()
        assert consumed1 == consumed0


# -- 4. the group's time, covered and split (ISSUE 26) ------------------------

SUB_STAGES = ("build", "device_dispatch", "device_fetch", "device_unpack")


def _stage(stage: str) -> tuple[int, float]:
    fam = REGISTRY._metrics.get(f"zeebe_stream_processor_pipeline_{stage}")
    child = fam._children.get(("1",)) if fam is not None else None
    return (child.count, child.sum) if child is not None else (0, 0.0)


class TestGroupStageSplit:
    def test_sub_stages_observed_once_a_group_and_sum_to_device(self):
        before = {s: _stage(s) for s in (*SUB_STAGES, "device")}
        h = EngineHarness(use_kernel_backend=True)
        try:
            drive_waves(h, n_instances=40)
            groups = h.kernel_backend.groups_processed
        finally:
            h.close()
        assert groups > 0
        delta = {s: (_stage(s)[0] - before[s][0], _stage(s)[1] - before[s][1])
                 for s in before}
        # single-device groups: every histogram of the split moves once a
        # group, exactly as `device` does
        for stage in SUB_STAGES:
            assert delta[stage][0] == delta["device"][0] == groups
        assert delta["build"][1] > 0
        parts = sum(delta[s][1] for s in SUB_STAGES[1:])
        # the parts share device_elapsed's clock reads: equal up to rounding
        assert parts == pytest.approx(delta["device"][1], rel=1e-6, abs=1e-7)

    def test_pending_group_parts_add_up(self):
        h = EngineHarness(use_kernel_backend=True)
        try:
            h.deploy(one_task())
            h.stream.writer.try_write(
                [LogAppendEntry(create_cmd()) for _ in range(4)])
            with h.db.transaction():
                pg = h.kernel_backend.begin_group(
                    h.processor._iter_candidate_commands())
                cmds, _ = h.kernel_backend.finish_group(
                    pg, ProcessingResultBuilder)
            assert cmds and not pg.mesh
            assert pg.t_build > 0 and pg.t_dispatch > 0 and pg.t_fetch > 0
            assert 0 < pg.t_device_get <= pg.t_fetch
            assert pg.t_unpack >= 0
            assert pg.t_dispatch + pg.t_fetch + pg.t_unpack == pytest.approx(
                pg.device_elapsed, rel=1e-9, abs=1e-9)
        finally:
            h.close()


class TestPhaseAnnotations:
    def test_helper_annotates_with_the_tracer_off(self):
        import jax

        from zeebe_tpu.observability import get_tracer
        from zeebe_tpu.observability.profiler import (
            PHASE_PREFIX,
            PHASES,
            phase_annotation,
        )

        assert not get_tracer().enabled
        assert PHASE_PREFIX == "zeebe.kernel_chunk."
        assert len(PHASES) <= 10 and len(set(PHASES)) == len(PHASES)
        for phase in PHASES:
            with phase_annotation(phase) as annotation:
                assert isinstance(annotation, jax.profiler.TraceAnnotation)
        with pytest.raises(KeyError):  # the set of names is closed
            phase_annotation("first")

    def _record_phases(self, monkeypatch) -> list:
        import contextlib

        from zeebe_tpu.engine import kernel_backend
        from zeebe_tpu.observability.profiler import PHASES
        from zeebe_tpu.stream import processor

        seen: list = []

        def recording(phase):
            assert phase in PHASES
            seen.append(phase)
            return contextlib.nullcontext()

        monkeypatch.setattr(kernel_backend, "phase_annotation", recording)
        monkeypatch.setattr(processor, "phase_annotation", recording)
        return seen

    def test_an_empty_admission_probe_emits_none(self, monkeypatch):
        h = EngineHarness(use_kernel_backend=True)
        try:
            h.deploy(one_task())
            h.pump()
            seen = self._record_phases(monkeypatch)
            for _ in range(5):
                assert h.processor.process_available_batch() == 0
            assert seen == []
        finally:
            h.close()

    def test_a_group_is_annotated_from_admit_to_side_effects(self, monkeypatch):
        h = EngineHarness(use_kernel_backend=True)
        try:
            h.deploy(one_task())
            seen = self._record_phases(monkeypatch)
            h.stream.writer.try_write(
                [LogAppendEntry(create_cmd()) for _ in range(3)])
            h.pump()
            assert h.kernel_backend.groups_processed > 0
        finally:
            h.close()
        first = [p for i, p in enumerate(seen) if p not in seen[:i]]
        assert first[:8] == ["admit", "build", "dispatch", "fetch", "unpack",
                             "materialize", "append", "flush"]
        assert "side_effects" in seen
