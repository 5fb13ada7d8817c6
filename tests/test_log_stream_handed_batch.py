"""A committed batch is served from the bytes the stream was handed.

``LogStream.append_committed_payload`` decodes the payload it is given and
seats the batch in the cache; the stream journal's file is read only on a
cache miss. These tests hold the served batch to what the file would give,
count where batches come from, and crash the partition with the stream
journal's write buffer still full. None reads a clock."""

from __future__ import annotations

import dataclasses
import json

import pytest

from zeebe_tpu.broker import InProcessCluster
from zeebe_tpu.exporters.api import Exporter
from zeebe_tpu.journal import SegmentedJournal
from zeebe_tpu.journal.journal import (
    _FRAME,
    _SEG_HEADER,
    CorruptedJournalError,
)
from zeebe_tpu.logstreams import LogAppendEntry, LogStream
from zeebe_tpu.logstreams.log_stream import (
    _BATCH_HEADER,
    _ENTRY_HEADER,
    _TS_OFFSET,
    _deserialize_batch,
    _serialize_batch,
    patch_prepatched_batch,
)
from zeebe_tpu.models.bpmn import Bpmn, to_bpmn_xml
from zeebe_tpu.protocol import Record, ValueType, command, event
from zeebe_tpu.protocol.enums import RejectionType
from zeebe_tpu.protocol.intent import (
    DeploymentIntent,
    JobIntent,
    ProcessInstanceCreationIntent,
    ProcessInstanceIntent,
    VariableIntent,
)
from zeebe_tpu.protocol.record import rejection
from zeebe_tpu.utils.metrics import REGISTRY

PARTITION = 7
TIMESTAMP = 1_700_000_000_123


def _counter(name: str, **labels) -> float:
    total = 0.0
    for fam, kind, label_str, value in REGISTRY.snapshot():
        if fam == f"zeebe_{name}" and kind == "counter" and all(
                f'{k}="{v}"' in label_str for k, v in labels.items()):
            total += value
    return total


def _commits_observed(partition: int = PARTITION) -> int:
    """Observations of the commit-to-stream histogram so far."""
    return sum(
        value[0] for fam, kind, label_str, value in REGISTRY.snapshot()
        if fam == "zeebe_stream_processor_pipeline_commit_to_stream"
        and kind == "histogram" and f'partition="{partition}"' in label_str)


def _reads(source: str, partition: int = PARTITION) -> float:
    return _counter("log_stream_batch_reads_total",
                    partition=str(partition), source=source)


def _drains() -> float:
    return _counter("journal_buffer_drain_total")


@pytest.fixture
def stream(tmp_path):
    journal = SegmentedJournal(tmp_path / "stream")
    s = LogStream(journal, partition_id=PARTITION, clock=lambda: TIMESTAMP)
    yield s
    journal.close()


def _payload(entries, first_position, source_position=-1):
    return _serialize_batch(entries, first_position, source_position, TIMESTAMP)


def _document(size: int) -> dict:
    """A variable document of about ``size`` bytes, as the benchmark's
    payload is: nested, mixed types, a list a decoder could hand back as a
    tuple."""
    doc = {"orderId": "o-1", "items": [], "nested": {"flag": True, "n": None,
                                                     "ratio": 0.25}}
    while len(json.dumps(doc)) < size:
        doc["items"].append({"sku": f"sku-{len(doc['items']):04d}",
                             "qty": len(doc["items"]), "tags": ["a", "b"]})
    return doc


def _create_command(n: int) -> Record:
    return command(
        ValueType.PROCESS_INSTANCE_CREATION,
        ProcessInstanceCreationIntent.CREATE,
        {"bpmnProcessId": "p", "version": -1, "variables": {"n": n}},
        request_stream_id=3, request_id=100 + n, operation_reference=9)


def _commands():
    return [LogAppendEntry(_create_command(0)),
            LogAppendEntry(command(ValueType.JOB, JobIntent.COMPLETE,
                                   {"variables": {}}, key=2251799813685249))]


def _events():
    return [
        LogAppendEntry.of_processed(event(
            ValueType.PROCESS_INSTANCE, ProcessInstanceIntent.ELEMENT_ACTIVATED,
            {"elementId": "task", "bpmnProcessId": "p"}, key=41,
            source_record_position=5)),
        LogAppendEntry.of_processed(event(
            ValueType.JOB, JobIntent.CREATED, {"type": "work", "retries": 3},
            key=42)),
        # a follow-up command among the events: not processed, so the command
        # scan has to find the batch
        LogAppendEntry(command(
            ValueType.PROCESS_INSTANCE, ProcessInstanceIntent.COMPLETE_ELEMENT,
            {"elementId": "task"}, key=41)),
    ]


def _oversized_rejection():
    # more than the wire's u16 of reason bytes, multi-byte codepoints at the
    # cut: Record.encode truncates, so only a decode of the bytes shows what
    # the log holds
    reason = "expected a process with id 'p' — " + "é" * 40_000
    return [LogAppendEntry.of_processed(
        rejection(_create_command(1), RejectionType.NOT_FOUND, reason))]


def _variable_documents():
    doc = _document(4096)
    return [
        LogAppendEntry(command(
            ValueType.PROCESS_INSTANCE_CREATION,
            ProcessInstanceCreationIntent.CREATE,
            {"bpmnProcessId": "p", "version": -1, "variables": doc})),
        LogAppendEntry.of_processed(event(
            ValueType.VARIABLE, VariableIntent.CREATED,
            {"name": "payload", "value": json.dumps(doc), "scopeKey": 41},
            key=43)),
    ]


SHAPES = {
    "commands": _commands,
    "events": _events,
    "oversized_rejection": _oversized_rejection,
    "variable_documents_4k": _variable_documents,
}


def _assert_batches_equal(served, from_file):
    """Field for field, types included (a list is not a tuple, an IntEnum
    is not an int)."""
    assert len(served) == len(from_file)
    for got, want in zip(served, from_file):
        for f in dataclasses.fields(want):
            if f.name == "record":
                continue
            assert getattr(got, f.name) == getattr(want, f.name), f.name
            assert type(getattr(got, f.name)) is type(getattr(want, f.name))
        for f in dataclasses.fields(Record):
            a, b = getattr(got.record, f.name), getattr(want.record, f.name)
            assert a == b, f.name
            assert type(a) is type(b), f.name
        assert repr(got.record.value) == repr(want.record.value)


def _prepatched(entries):
    """What a burst template hands the Raft writer: the batch serialized
    once with its positions and timestamps left open, and where they are."""
    buf = bytearray(_serialize_batch(entries, 0, -1, 0))
    pos_offsets, ts_offsets = [], [4 + 8]  # the batch header's timestamp
    off = _BATCH_HEADER.size
    for _ in entries:
        _processed, _pos, length = _ENTRY_HEADER.unpack_from(buf, off)
        pos_offsets.append(off + 1)
        off += _ENTRY_HEADER.size
        ts_offsets.append(off + _TS_OFFSET)
        off += length
    assert off == len(buf)
    return buf, pos_offsets, ts_offsets


# -- (a) the served batch is what the file holds ------------------------------


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_handed_batch_equals_the_files(stream, shape):
    # a first batch, so the one under test is not at the stream's start
    stream.append_committed_payload(_payload(_commands(), 1), 1)
    entries = SHAPES[shape]()
    first = stream.last_position + 1
    stream.append_committed_payload(_payload(entries, first, 1), first)
    index = stream.journal.last_index
    served = stream.read_batch_containing(first)
    from_file = _deserialize_batch(
        stream.journal.read_entry(index).data, PARTITION)
    assert [r.position for r in served] == list(
        range(first, first + len(entries)))
    assert stream.last_position == first + len(entries) - 1
    _assert_batches_equal(served, from_file)
    # and it is the batch every reader gets
    assert stream.read_at_or_after(first) is served[0]
    assert [r.position for r in stream.new_reader(first)] == [
        r.position for r in served]


@pytest.mark.parametrize("flag", [False, True, None])
def test_prepatched_burst_keeps_its_flag(stream, flag):
    entries = _events() if flag is not False else _events()[:2]
    buf, pos_offsets, ts_offsets = _prepatched(entries)
    patch_prepatched_batch(buf, pos_offsets, ts_offsets, 1, TIMESTAMP)
    stream.append_committed_payload(bytes(buf), 1, has_pending_commands=flag)
    index = stream.journal.last_index
    served = stream.read_batch_containing(1)
    from_file = _deserialize_batch(
        stream.journal.read_entry(index).data, PARTITION)
    _assert_batches_equal(served, from_file)
    assert all(r.record.timestamp == TIMESTAMP for r in served)
    # the leader's flag is kept; without one it is worked out from the
    # records, as a read of the file works it out
    has_command = any(r.record.is_command and not r.processed for r in served)
    assert stream._batch_has_commands[index] is (
        has_command if flag is None else flag)
    # the command scan skips a batch flagged empty without decoding it, and
    # otherwise hands back the batch's records for the caller to filter
    found, _hint, _scan = stream.next_command_with_hint(1, -1)
    assert (found is not None) == has_command


def test_the_flag_rule_matches_a_read_of_the_file(stream):
    """No flag given: ``append_committed_payload`` and ``_read_batch_at``
    fill ``_batch_has_commands`` alike."""
    stream.append_committed_payload(_payload(_events()[:2], 1), 1)
    stream.append_committed_payload(_payload(_commands(), 3), 3)
    at_append = dict(stream._batch_has_commands)
    assert list(at_append.values()) == [False, True]
    stream.rebuild_index()
    for position in (1, 3):
        stream.read_batch_containing(position)
    assert stream._batch_has_commands == at_append


def test_a_redelivered_commit_is_not_materialized_twice(stream):
    payload = _payload(_commands(), 1)
    stream.append_committed_payload(payload, 1)
    handed, index = _reads("handed"), stream.journal.last_index
    stream.append_committed_payload(payload, 1)
    assert stream.journal.last_index == index
    assert _reads("handed") == handed
    assert stream.last_position == 2


# -- (b) no read of the journal, no drain an entry ----------------------------


@pytest.mark.parametrize("n", [8, 64, 512])
def test_appends_read_nothing_back_and_drain_nothing(stream, n):
    journal_reads, handed, cache = (
        _reads("journal"), _reads("handed"), _reads("cache"))
    drains = _drains()
    observed = _commits_observed()
    position = 1
    for i in range(n):
        entries = [LogAppendEntry(_create_command(i))] + _events()[:2]
        stream.append_committed_payload(
            _payload(entries, position), position)
        position += len(entries)
    # every record read, as the processor, replay and the exporters read
    assert [r.position for r in stream.new_reader()] == list(
        range(1, position))
    assert _reads("journal") == journal_reads
    assert _reads("handed") == handed + n
    assert _reads("cache") >= cache + n
    # the write buffer went nowhere: n is far under max_unflushed_bytes, so
    # the count of drains does not rise with it
    assert _drains() == drains
    assert stream.journal.segments[-1]._pending_bytes > 0
    # one observation a committed entry
    assert _commits_observed() == observed + n


def test_the_histogram_is_in_the_pipeline_family(stream):
    """``benchmarks/served.py`` takes stage sums from every histogram whose
    name holds ``stream_processor_pipeline_``: the stage it will read."""
    stream.append_committed_payload(_payload(_commands(), 1), 1)
    stages = {name.rsplit("_pipeline_", 1)[1]
              for name, kind, _labels, _value in REGISTRY.snapshot()
              if kind == "histogram" and "stream_processor_pipeline_" in name}
    assert "commit_to_stream" in stages


def test_the_buffer_goes_out_at_its_limit(tmp_path):
    journal = SegmentedJournal(tmp_path / "stream", max_unflushed_bytes=4096)
    try:
        s = LogStream(journal, partition_id=PARTITION, clock=lambda: TIMESTAMP)
        drains, journal_reads = _drains(), _reads("journal")
        position = 1
        for _ in range(40):
            entries = _variable_documents()
            s.append_committed_payload(_payload(entries, position), position)
            position += len(entries)
        # a batch is ~9 KiB: each one passes the limit and goes out alone,
        # by the journal's own rule and not because a reader asked
        assert _drains() == drains + 40
        assert _reads("journal") == journal_reads
        assert journal.segments[-1]._pending_bytes == 0
    finally:
        journal.close()


# -- (c) a miss goes to the file, under its CRC -------------------------------


def _fill(stream, batches=10):
    position = 1
    for i in range(batches):
        entries = [LogAppendEntry(_create_command(i))] + _events()[:2]
        stream.append_committed_payload(_payload(entries, position), position)
        position += len(entries)
    return position - 1


@pytest.mark.parametrize("how", ["evicted", "rebuild_index", "reopened"])
def test_a_miss_is_served_from_the_file(tmp_path, how):
    journal = SegmentedJournal(tmp_path / "stream")
    s = LogStream(journal, partition_id=PARTITION, clock=lambda: TIMESTAMP)
    try:
        if how == "evicted":
            s._batch_cache_limit = 4
        last = _fill(s)
        served = s.read_batch_containing(4) if how != "evicted" else None
        if how == "evicted":
            assert s._batch_indexes[1] not in s._batch_cache
        elif how == "rebuild_index":
            s.rebuild_index()
        else:
            journal.close()
            journal = SegmentedJournal(tmp_path / "stream")
            s = LogStream(journal, partition_id=PARTITION,
                          clock=lambda: TIMESTAMP)
        assert s.last_position == last
        journal_reads, drains = _reads("journal"), _drains()
        batch = s.read_batch_containing(4)
        assert _reads("journal") == journal_reads + 1
        if how == "evicted":
            assert _drains() == drains + 1  # the reader needed the file
        assert [r.position for r in batch] == [4, 5, 6]
        assert batch[0].record.value["variables"] == {"n": 1}
        if served is not None:
            _assert_batches_equal(batch, served)
        # seated again: the next read is a hit
        assert s.read_batch_containing(4) is batch
        assert _reads("journal") == journal_reads + 1
    finally:
        journal.close()


def test_a_flipped_byte_in_the_file_still_raises(stream):
    _fill(stream)
    stream.journal.flush()
    path = stream.journal.segments[0].path
    offset = _SEG_HEADER.size + _FRAME.size + 30  # inside the first payload
    with open(path, "r+b") as f:
        f.seek(offset)
        byte = f.read(1)
        f.seek(offset)
        f.write(bytes((byte[0] ^ 0xFF,)))
    # the seated batch was decoded from the committed bytes, not the file
    assert stream.read_batch_containing(1)[0].position == 1
    stream._batch_cache.clear()
    with pytest.raises(CorruptedJournalError):
        stream.read_batch_containing(1)
    with pytest.raises(CorruptedJournalError):
        list(stream.scan(1))


def test_scans_see_handed_batches(stream):
    """``scan``/``scan_filtered`` walk the journal's file (and so drain the
    buffer) but serve a cached batch's records from the cache."""
    last = _fill(stream, batches=5)
    views = list(stream.scan(1))
    assert [v.position for v in views] == list(range(1, last + 1))
    creates = list(stream.scan_filtered(
        1, int(command(ValueType.JOB, JobIntent.COMPLETE, {}).record_type),
        int(ValueType.PROCESS_INSTANCE_CREATION)))
    assert [v.position for v in creates] == [1, 4, 7, 10, 13]
    assert stream.journal.segments[-1]._pending_bytes == 0


# -- (d) a crash with the stream journal's buffer full ------------------------


def _one_task():
    return (Bpmn.create_executable_process("p")
            .start_event("s").service_task("t", job_type="w")
            .end_event("e").done())


def _deploy(cluster):
    cluster.write_command(1, command(
        ValueType.DEPLOYMENT, DeploymentIntent.CREATE,
        {"resources": [{"resourceName": "p.bpmn",
                        "resource": to_bpmn_xml(_one_task())}]}))
    cluster.run(300)


def _create(cluster, n, tag):
    leader = cluster.leader(1)
    for i in range(n):
        leader.write_commands([command(
            ValueType.PROCESS_INSTANCE_CREATION,
            ProcessInstanceCreationIntent.CREATE,
            {"bpmnProcessId": "p", "version": -1,
             "variables": {"tag": f"{tag}-{i}"}})])
        cluster.run(100)
    cluster.run(500)


def _raft_payloads(partition):
    return [(e["asqn"], e["data"]) for e in partition.raft.committed_entries(1)
            if not e.get("init") and e.get("data")]


def _stream_payloads(partition):
    return [(r.asqn, r.data) for r in partition.stream_journal.read_from(1)]


class _Collecting(Exporter):
    """Keeps and acknowledges every record it is given."""

    def __init__(self) -> None:
        self.records = []

    def export(self, record) -> None:
        self.records.append(record)
        self.controller.update_last_exported_position(record.position)


def _lose_only_the_write_buffer(journal) -> None:
    """The process is killed: the file keeps what was written to it, the
    journal's write buffer is gone."""
    for seg in journal.segments:
        seg._pending.clear()
        seg._pending_bytes = 0
        seg.file.close()


@pytest.mark.parametrize("crash", ["power_loss", "process_killed"])
def test_crash_with_undrained_stream_bytes_rematerializes(tmp_path, crash):
    exporters: list[_Collecting] = []

    def factory():
        exporters.append(_Collecting())
        return {"rec": exporters[-1]}

    cluster = InProcessCluster(
        broker_count=1, partition_count=1, replication_factor=1,
        directory=tmp_path / "c", exporters_factory=factory,
        snapshot_period_ms=10**9)
    try:
        cluster.await_leaders()
        _deploy(cluster)
        _create(cluster, 12, "before")
        leader = cluster.leader(1)
        journal_reads = _reads("journal", partition=1)
        tail = leader.stream_journal.segments[-1]
        assert tail._pending_bytes > 0, "nothing undrained to lose"
        on_file = tail.size - tail._pending_bytes
        committed = _raft_payloads(leader)
        last_position = leader.stream.last_position
        assert committed and committed[-1][0] <= last_position
        exported_before = [r.position for r in exporters[-1].records]
        assert exported_before == list(range(1, last_position + 1))

        if crash == "process_killed":
            leader.stream_journal.simulate_power_loss = (
                lambda j=leader.stream_journal: _lose_only_the_write_buffer(j))
        cluster.hard_crash_broker("broker-0")
        assert tail.path.stat().st_size <= on_file  # the buffer never landed
        cluster.restart_broker("broker-0")
        cluster.await_leaders()
        cluster.run(1000)
        leader = cluster.leader(1)

        # the stream is the Raft log's committed prefix again, byte for byte
        assert leader.stream.last_position == last_position
        assert _stream_payloads(leader) == committed
        assert _raft_payloads(leader) == committed
        # the restarted exporter saw every record once, none missing
        exported = [r.position for r in exporters[-1].records]
        assert exported == list(range(1, last_position + 1))
        # and no command ran twice: twelve instances, twelve jobs
        _assert_each_command_processed_once(exporters[-1], instances=12)

        # it goes on from there
        _create(cluster, 3, "after")
        leader = cluster.leader(1)
        assert leader.stream.last_position > last_position
        assert _stream_payloads(leader) == _raft_payloads(leader)
        exported = [r.position for r in exporters[-1].records]
        assert exported == list(range(1, leader.stream.last_position + 1))
        _assert_each_command_processed_once(exporters[-1], instances=15)
        # recovery, replay and the exporter were all served what the stream
        # was handed: nothing went back to the stream journal's file
        assert _reads("journal", partition=1) == journal_reads
    finally:
        cluster.close()


def _assert_each_command_processed_once(exporter, instances):
    records = [r.record for r in exporter.records]
    activated = [r for r in records
                 if r.value_type == ValueType.PROCESS_INSTANCE
                 and r.is_event
                 and r.intent == ProcessInstanceIntent.ELEMENT_ACTIVATED
                 and r.value.get("bpmnElementType") == "PROCESS"]
    assert len(activated) == instances
    jobs = [r for r in records if r.value_type == ValueType.JOB
            and r.is_event and r.intent == JobIntent.CREATED]
    assert len(jobs) == instances
    # every command is the source of at most one processing step
    sources: dict[int, int] = {}
    for logged in exporter.records:
        if logged.processed and logged.source_position > 0:
            sources.setdefault(logged.source_position, logged.position)
    commands = [r for r in exporter.records
                if r.record.is_command and not r.processed]
    firsts = sorted(sources.values())
    assert len(firsts) == len(set(firsts))
    assert {c.position for c in commands} >= set(sources)


# -- (e) a follower replays the handed batches to the leader's state ----------


def test_follower_replay_yields_the_leaders_state():
    cluster = InProcessCluster(
        broker_count=3, partition_count=1, replication_factor=3)
    try:
        cluster.await_leaders()
        handed = _reads("handed", partition=1)
        journal_reads = _reads("journal", partition=1)
        _deploy(cluster)
        _create(cluster, 8, "replicated")
        cluster.run(1000)
        leader = cluster.leader(1)
        replicas = [b.partitions[1] for b in cluster.brokers.values()]
        followers = [p for p in replicas if not p.is_leader]
        assert len(followers) == 2
        entries = len(_raft_payloads(leader))
        for follower in followers:
            assert follower.stream.last_position == leader.stream.last_position
            assert follower.db.content_equals(leader.db)
            assert [(r.position, r.record) for r in follower.stream.new_reader()] \
                == [(r.position, r.record) for r in leader.stream.new_reader()]
        # once an entry a replica, and never from the file
        assert _reads("handed", partition=1) == handed + 3 * entries
        assert _reads("journal", partition=1) == journal_reads
    finally:
        cluster.close()
