"""State store tests: column families, transactions, iteration, consistency
checks, snapshot roundtrip; snapshot store lifecycle + chunked replication."""

import pytest

from zeebe_tpu.state import (
    ColumnFamilyCode,
    FileBasedSnapshotStore,
    InvalidSnapshotError,
    SnapshotId,
    ZbDb,
    ZbDbInconsistentError,
)


@pytest.fixture
def db():
    return ZbDb()


class TestTransactions:
    def test_commit_visible(self, db):
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with db.transaction():
            cf.put((1,), {"type": "a"})
        with db.transaction():
            assert cf.get((1,)) == {"type": "a"}

    def test_rollback_discards(self, db):
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with pytest.raises(RuntimeError, match="boom"):
            with db.transaction():
                cf.put((1,), "v")
                raise RuntimeError("boom")
        with db.transaction():
            assert cf.get((1,)) is None

    def test_read_your_writes(self, db):
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with db.transaction():
            cf.put((1,), "v1")
            assert cf.get((1,)) == "v1"
            cf.delete((1,))
            assert cf.get((1,)) is None

    def test_no_access_outside_transaction(self, db):
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with pytest.raises(RuntimeError):
            cf.get((1,))

    def test_nested_transactions_rejected(self, db):
        with db.transaction():
            with pytest.raises(RuntimeError):
                with db.transaction():
                    pass


class TestColumnFamilies:
    def test_families_isolated(self, db):
        jobs = db.column_family(ColumnFamilyCode.JOBS)
        timers = db.column_family(ColumnFamilyCode.TIMERS)
        with db.transaction():
            jobs.put((1,), "job")
            timers.put((1,), "timer")
        with db.transaction():
            assert jobs.get((1,)) == "job"
            assert timers.get((1,)) == "timer"
            assert len(list(jobs.items())) == 1

    def test_composite_keys_ordered_iteration(self, db):
        cf = db.column_family(ColumnFamilyCode.TIMER_DUE_DATES)
        with db.transaction():
            cf.put((300, 7), "c")
            cf.put((100, 5), "a")
            cf.put((200, 6), "b")
            cf.put((100, 9), "a2")
        with db.transaction():
            assert list(cf.values()) == ["a", "a2", "b", "c"]

    def test_negative_int_ordering(self, db):
        cf = db.column_family(ColumnFamilyCode.DEFAULT)
        with db.transaction():
            for v in (5, -3, 0, -100, 42):
                cf.put((v,), v)
        with db.transaction():
            assert list(cf.values()) == [-100, -3, 0, 5, 42]

    def test_prefix_iteration(self, db):
        cf = db.column_family(ColumnFamilyCode.ELEMENT_INSTANCE_PARENT_CHILD)
        with db.transaction():
            cf.put((1, 10), "c1")
            cf.put((1, 11), "c2")
            cf.put((2, 12), "other-parent")
        with db.transaction():
            assert list(cf.values(prefix=(1,))) == ["c1", "c2"]

    def test_string_keys(self, db):
        cf = db.column_family(ColumnFamilyCode.PROCESS_CACHE_BY_ID_AND_VERSION)
        with db.transaction():
            cf.put(("order", 1), "v1")
            cf.put(("order", 2), "v2")
            cf.put(("order-express", 1), "x1")
        with db.transaction():
            # prefix ("order",) must not match "order-express" (NUL terminator)
            assert list(cf.values(prefix=("order",))) == ["v1", "v2"]

    def test_iteration_sees_pending_writes(self, db):
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with db.transaction():
            cf.put((2,), "b")
        with db.transaction():
            cf.put((1,), "a")
            cf.put((3,), "c")
            cf.delete((2,))
            assert list(cf.values()) == ["a", "c"]


class TestConsistencyChecks:
    def test_insert_existing_rejected(self):
        db = ZbDb(consistency_checks=True)
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with db.transaction():
            cf.insert((1,), "a")
            with pytest.raises(ZbDbInconsistentError):
                cf.insert((1,), "b")

    def test_update_missing_rejected(self):
        db = ZbDb(consistency_checks=True)
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with db.transaction():
            with pytest.raises(ZbDbInconsistentError):
                cf.update((404,), "x")

    def test_delete_missing_rejected(self):
        db = ZbDb(consistency_checks=True)
        cf = db.column_family(ColumnFamilyCode.JOBS)
        with db.transaction():
            with pytest.raises(ZbDbInconsistentError):
                cf.delete((404,))

    def test_foreign_key_checker(self):
        db = ZbDb(consistency_checks=True)
        procs = db.column_family(ColumnFamilyCode.PROCESS_CACHE)

        def check_job(db_, value):
            with_cf = db_.column_family(ColumnFamilyCode.PROCESS_CACHE)
            if not with_cf.exists((value["processKey"],)):
                raise ZbDbInconsistentError("dangling processKey")

        db.register_foreign_key_check(ColumnFamilyCode.JOBS, check_job)
        jobs = db.column_family(ColumnFamilyCode.JOBS)
        with db.transaction():
            procs.put((7,), {"id": "p"})
            jobs.put((1,), {"processKey": 7})  # ok
            with pytest.raises(ZbDbInconsistentError):
                jobs.put((2,), {"processKey": 999})


class TestDbSnapshot:
    def test_roundtrip_and_equality(self, db):
        cf = db.column_family(ColumnFamilyCode.VARIABLES)
        with db.transaction():
            for i in range(50):
                cf.put((i, f"var{i}"), {"value": i})
        raw = db.to_snapshot_bytes()
        restored = ZbDb.from_snapshot_bytes(raw)
        assert restored.content_equals(db)
        with restored.transaction():
            got = restored.column_family(ColumnFamilyCode.VARIABLES).get((3, "var3"))
        assert got == {"value": 3}

    def test_corrupt_snapshot_rejected(self, db):
        with db.transaction():
            db.column_family(ColumnFamilyCode.JOBS).put((1,), "x")
        raw = bytearray(db.to_snapshot_bytes())
        raw[-1] ^= 0xFF
        with pytest.raises(ValueError, match="checksum"):
            ZbDb.from_snapshot_bytes(bytes(raw))


class TestSnapshotStore:
    def test_take_persist_latest(self, tmp_path):
        store = FileBasedSnapshotStore(tmp_path)
        t = store.new_transient_snapshot(index=10, term=1, processed_position=99, exported_position=50)
        t.write_file("state.zdb", b"statedata")
        snap = t.persist()
        assert str(snap.id) == "10-1-99-50"
        latest = store.latest_snapshot()
        assert latest is not None and latest.id == SnapshotId(10, 1, 99, 50)
        assert latest.read_file("state.zdb") == b"statedata"

    def test_older_snapshots_purged(self, tmp_path):
        store = FileBasedSnapshotStore(tmp_path)
        for idx in (5, 10, 15):
            t = store.new_transient_snapshot(idx, 1, idx * 10, 0)
            t.write_file("f", b"d%d" % idx)
            t.persist()
        snaps = store.list_snapshots()
        assert len(snaps) == 1
        assert snaps[0].id.index == 15

    def test_stale_transient_rejected(self, tmp_path):
        store = FileBasedSnapshotStore(tmp_path)
        t = store.new_transient_snapshot(10, 1, 1, 0)
        t.write_file("f", b"x")
        t.persist()
        with pytest.raises(InvalidSnapshotError):
            store.new_transient_snapshot(9, 1, 1, 0)

    def test_corrupt_snapshot_dropped_on_open(self, tmp_path):
        store = FileBasedSnapshotStore(tmp_path)
        t = store.new_transient_snapshot(10, 1, 1, 0)
        t.write_file("f", b"data")
        snap = t.persist()
        # corrupt the file after persist
        (snap.path / "f").write_bytes(b"tampered")
        store2 = FileBasedSnapshotStore(tmp_path)
        assert store2.latest_snapshot() is None

    def test_pending_leftovers_cleaned(self, tmp_path):
        store = FileBasedSnapshotStore(tmp_path)
        t = store.new_transient_snapshot(10, 1, 1, 0)
        t.write_file("f", b"x")  # never persisted
        store2 = FileBasedSnapshotStore(tmp_path)
        assert list(store2.pending_dir.iterdir()) == []

    def test_chunked_replication_roundtrip(self, tmp_path):
        src = FileBasedSnapshotStore(tmp_path / "leader")
        t = src.new_transient_snapshot(20, 2, 500, 400)
        t.write_file("state.zdb", b"S" * (3 * 1024 * 1024))  # multi-chunk
        t.write_file("meta", b"m")
        snap = t.persist()
        dst = FileBasedSnapshotStore(tmp_path / "follower")
        received = dst.receive_snapshot(src.chunk_reader(snap, chunk_size=1 << 20))
        assert received.id == snap.id
        assert received.read_file("state.zdb") == b"S" * (3 * 1024 * 1024)
        assert received.read_file("meta") == b"m"

    def test_corrupt_chunk_rejected(self, tmp_path):
        src = FileBasedSnapshotStore(tmp_path / "leader")
        t = src.new_transient_snapshot(20, 2, 500, 400)
        t.write_file("f", b"data")
        snap = t.persist()
        chunks = list(src.chunk_reader(snap))
        import dataclasses

        bad = [dataclasses.replace(chunks[0], data=b"tampered!")] + chunks[1:]
        dst = FileBasedSnapshotStore(tmp_path / "follower")
        with pytest.raises(InvalidSnapshotError):
            dst.receive_snapshot(iter(bad))


class TestIterateSnapshotNativeParity:
    """The native iterate_snapshot must match the Python merge exactly —
    ordering, overlay supersession, deleted hiding, and the defensive
    copy-and-cache of committed container values."""

    def _fill(self, db):
        from zeebe_tpu.state.db import ColumnFamilyCode as CF

        cf = db.column_family(CF.VARIABLES)
        with db.transaction():
            for i in range(6):
                cf.put((7, f"k{i}"), {"v": i})
            cf.put((8, "other"), {"v": 99})
            cf.put((7, "scalar"), 42)
            cf.put((7, "lst"), [1, 2])
        return cf

    def test_merge_matches_python_path(self):
        import zeebe_tpu.state.db as dbm
        from zeebe_tpu.state.db import ZbDb

        db = ZbDb()
        cf = self._fill(db)
        with db.transaction():
            cf.put((7, "k1"), {"v": 100})   # overlay supersedes
            cf.delete((7, "k2"))             # overlay hides
            cf.put((7, "zz"), {"v": 7})      # overlay-only key
            txn = db.require_transaction()
            native = list(txn.iterate(cf._key((7,))))
            assert db._native_iterate is dbm._iterate_snapshot
            db._native_iterate = None  # what ZEEBE_TPU_NO_NATIVE=1 leaves
            try:
                txn._reads.clear()  # fresh copy-cache for the pure path
                pure = list(txn.iterate(cf._key((7,))))
            finally:
                db._native_iterate = dbm._iterate_snapshot
            assert [k for k, _ in native] == [k for k, _ in pure]
            assert [v for _, v in native] == [v for _, v in pure]

    def test_committed_values_copy_cached(self):
        from zeebe_tpu.state.db import ZbDb

        db = ZbDb()
        cf = self._fill(db)

        class _Boom(Exception):
            pass

        try:
            with db.transaction():
                txn = db.require_transaction()
                snap = dict(txn.iterate(cf._key((7,))))
                key = cf._key((7, "k0"))
                # same transaction: get() must hand back the SAME cached copy
                # so in-place mutations stay coherent within the txn
                got = txn.get(key)
                assert got is snap[key]
                got["v"] = 1234
                raise _Boom  # roll the transaction back
        except _Boom:
            pass
        with db.transaction():
            # rollback never leaked the mutation into the committed store
            assert cf.get((7, "k0")) == {"v": 0}

    def test_all_ff_prefix_unbounded(self):
        from zeebe_tpu.state.db import ZbDb

        db = ZbDb()
        with db.transaction():
            txn = db.require_transaction()
            txn.put(b"\xff\xff\x01", 1)
            txn.put(b"\xff\xff\x02", 2)
            assert [v for _, v in txn.iterate(b"\xff\xff")] == [1, 2]


# -- the committed-key index (state/db.py BlockedKeyIndex) --------------------

_INDEX_CFS = (ColumnFamilyCode.JOBS, ColumnFamilyCode.JOB_STATES,
              ColumnFamilyCode.JOB_DEADLINES)


def _make_store(backend, tmp_path, native):
    """One store of each backend; ``native`` False strips the two C passes,
    which is all ZEEBE_TPU_NO_NATIVE=1 changes for the store (the tiered
    backend never takes them, the durable one takes the commit alone)."""
    from zeebe_tpu.state.durable import DurableZbDb
    from zeebe_tpu.state.tiering import TieredZbDb

    if backend == "plain":
        store = ZbDb()
    elif backend == "durable":
        store = DurableZbDb(tmp_path / "durable", hot_budget_bytes=256)
    else:
        store = TieredZbDb(tmp_path / "tiered")
    if not native:
        store._native_iterate = None
        store._native_commit = None
    return store


def _check_index_shape(store, load):
    maxes, blocks = store._index.lists
    assert len(maxes) == len(blocks)
    assert all(blocks), "an empty block was left in the index"
    assert max(map(len, blocks), default=0) <= 2 * load
    assert maxes == [block[-1] for block in blocks]
    flat = list(store._index)
    assert flat == sorted(store._data)
    return flat


def _border_ranges(store, rng):
    """Ranges that start, end and straddle block borders, lie wholly inside
    one block, are empty, or run to the end."""
    from zeebe_tpu.state.db import _CF_PREFIX, _prefix_successor

    _, blocks = store._index.lists
    out = [(b"", None), (b"\xff\xff", None), (b"", b"")]
    for code in _INDEX_CFS:
        out.append((_CF_PREFIX[code], _prefix_successor(_CF_PREFIX[code])))
    for i, block in enumerate(blocks):
        nxt = blocks[i + 1][0] if i + 1 < len(blocks) else None
        out += [(block[0], block[-1]), (block[0], nxt), (block[-1], nxt),
                (block[-1], block[-1]), (block[0] + b"\x00", nxt)]
        if len(block) > 2:
            out.append((block[1], block[-1]))
        if i + 2 < len(blocks):
            out.append((block[len(block) // 2], blocks[i + 2][0]))
    keys = sorted(store._data)
    for _ in range(8):
        lo, hi = sorted(rng.sample(keys, 2)) if len(keys) > 1 else (b"", None)
        out.append((lo, hi))
    return out


@pytest.mark.parametrize("native", [True, False], ids=["native", "no_native"])
@pytest.mark.parametrize("backend", ["plain", "durable", "tiered"])
class TestBlockedKeyIndex:
    """The index against a model dict + sorted(), with a small LOAD patched
    in so that blocks split, empty and vanish within a few hundred keys."""

    LOAD = 4

    def _churn(self, store, rng, model, rounds):
        from zeebe_tpu.state.db import encode_key

        class _Rollback(Exception):
            pass

        for _ in range(rounds):
            pending = dict(model)
            rollback = rng.random() < 0.15
            try:
                with store.transaction() as txn:
                    for _ in range(rng.randint(1, 12)):
                        key = encode_key(rng.choice(_INDEX_CFS),
                                         (rng.randint(0, 120),))
                        if rng.random() < 0.45:
                            txn.delete(key)
                            pending.pop(key, None)
                        else:
                            value = {"v": rng.randint(0, 9), "pad": "x" * 40}
                            txn.put(key, value)
                            pending[key] = value
                    if rollback:
                        raise _Rollback
            except _Rollback:
                pass
            else:
                model.clear()
                model.update(pending)
            yield

    def test_random_commits_match_a_sorted_model(self, backend, native,
                                                 tmp_path, monkeypatch):
        import random

        import zeebe_tpu.state.db as dbm

        monkeypatch.setattr(dbm, "LOAD", self.LOAD)
        store = _make_store(backend, tmp_path, native)
        assert (store._native_commit is not None) == (
            native and backend != "tiered" and dbm._commit_overlay is not None)
        rng = random.Random(34)
        model: dict = {}
        most_blocks = 0
        for _ in self._churn(store, rng, model, 250):
            assert _check_index_shape(store, self.LOAD) == sorted(model)
            most_blocks = max(most_blocks, store.index_block_count)
        assert most_blocks >= 12, "the sequence never grew the index"
        # drain it: every block empties and vanishes
        with store.transaction() as txn:
            for key in list(model):
                txn.delete(key)
        assert store._index.lists == ([], []) and store.key_count == 0

    def test_reads_agree_with_the_model_across_block_borders(
            self, backend, native, tmp_path, monkeypatch):
        import random

        import zeebe_tpu.state.db as dbm
        from zeebe_tpu.state.db import _CF_PREFIX, _prefix_successor

        monkeypatch.setattr(dbm, "LOAD", self.LOAD)
        store = _make_store(backend, tmp_path, native)
        rng = random.Random(3434)
        model: dict = {}
        for round_no, _ in enumerate(self._churn(store, rng, model, 60)):
            if round_no % 6:
                continue
            if backend == "tiered" and model:
                store.spill_keys(rng.sample(sorted(model), len(model) // 3))
            keys = sorted(model)
            assert store.index_block_count >= 3 or len(keys) < 3 * self.LOAD
            counts = {}
            for code in _INDEX_CFS:
                prefix = _CF_PREFIX[code]
                held = [k for k in keys if k.startswith(prefix)]
                assert store.committed_keys_of(code) == held
                if held:
                    counts[code.name] = len(held)
                    part = dbm.decode_key(held[len(held) // 2])[1]
                    assert store.committed_keys_of(code, part) == [
                        k for k in held
                        if k.startswith(dbm.encode_key(code, part))]
            assert store.key_counts_by_cf() == counts
            with store.transaction() as txn:
                # an overlay on top: a delete, an overwrite, a new key
                overlay = dict(model)
                if keys:
                    gone, changed = keys[len(keys) // 3], keys[-1]
                    txn.delete(gone)
                    overlay.pop(gone)
                    txn.put(changed, {"v": -1})
                    overlay[changed] = {"v": -1}
                new = dbm.encode_key(_INDEX_CFS[1], (500 + round_no,))
                txn.put(new, {"v": -2})
                overlay[new] = {"v": -2}
                merged = sorted(overlay)
                for lo, hi in _border_ranges(store, rng):
                    inside = [k for k in keys
                              if k >= lo and (hi is None or k < hi)]
                    assert store._keys_in_range(lo, hi) == inside
                    assert store._count_key_range(lo, hi) == len(inside)
                    assert store._first_key_at_or_after(lo, hi) == (
                        inside[0] if inside else None)
                    want = [(k, overlay[k]) for k in merged
                            if k >= lo and (hi is None or k < hi)]
                    assert list(txn.iterate_range(lo, hi)) == want
                    assert txn.first_in_range(lo, hi) == (
                        want[0] if want else None)
                    if lo and hi == _prefix_successor(lo):
                        assert list(txn.iterate(lo)) == want
                for code in _INDEX_CFS:
                    prefix = _CF_PREFIX[code]
                    assert list(txn.iterate(prefix)) == [
                        (k, overlay[k]) for k in merged
                        if k.startswith(prefix)]
                txn.rollback()
            assert sorted(store._data) == keys

    def test_no_block_outgrows_twice_load_and_none_is_left_empty(
            self, backend, native, tmp_path):
        """A structural test in place of a timing: 50,000 inserts at one
        position, then 50,000 at random, at the real LOAD."""
        import random

        from zeebe_tpu.state.db import LOAD, encode_key

        store = _make_store(backend, tmp_path, native)
        rng = random.Random(5)
        code = ColumnFamilyCode.JOB_STATES
        with store.transaction() as txn:  # neighbours on both sides
            txn.put(encode_key(ColumnFamilyCode.JOBS, (1,)), None)
            txn.put(encode_key(ColumnFamilyCode.JOB_DEADLINES, (1,)), None)
        ordered = [encode_key(code, (n,)) for n in range(50_000)]
        scattered = [encode_key(code, (rng.randrange(10**6, 10**12),))
                     for _ in range(50_000)]
        for batch in (ordered, scattered):
            for start in range(0, len(batch), 500):
                with store.transaction() as txn:
                    for key in batch[start:start + 500]:
                        txn.put(key, None)
            _check_index_shape(store, LOAD)
        assert store.key_count == 2 + len(set(ordered) | set(scattered))
        assert store.index_block_count >= store.key_count // (2 * LOAD)
        for start in range(0, len(ordered), 500):  # and out again
            with store.transaction() as txn:
                for key in ordered[start:start + 500]:
                    txn.delete(key)
        _check_index_shape(store, LOAD)

    def test_snapshot_body_is_the_same_built_by_commits_or_in_bulk(
            self, backend, native, tmp_path, monkeypatch):
        import random

        import zeebe_tpu.state.db as dbm

        monkeypatch.setattr(dbm, "LOAD", self.LOAD)
        store = _make_store(backend, tmp_path, native)
        rng = random.Random(77)
        model: dict = {}
        for _ in self._churn(store, rng, model, 80):
            pass
        bulk = ZbDb()
        bulk.bulk_apply(dict(model))
        raw = store.to_snapshot_bytes()
        assert raw == bulk.to_snapshot_bytes()
        back = ZbDb.from_snapshot_bytes(raw)
        assert back.to_snapshot_bytes() == raw
        assert _check_index_shape(back, self.LOAD) == sorted(model)
        # recovery builds the index from the sorted body: no sort, full blocks
        assert all(len(block) == self.LOAD
                   for block in back._index.lists[1][:-1])


@pytest.mark.parametrize("native", [True, False], ids=["native", "no_native"])
def test_lock_free_readers_skip_no_block_while_blocks_split_and_vanish(
        native, tmp_path, monkeypatch):
    """Readers on other threads (a gateway's long-poll peek, the metrics
    cadence) take no lock. While the writer splits and drops blocks on both
    sides of sixty keys nobody writes, a reader never raises, never sees a
    key twice or out of order, and never misses one of the inner keys (the
    index's promise: no block skipped or seen twice; at a range's two edges
    a reader may be off by the keys written between its bisect and its
    slice, as on the flat list)."""
    import sys
    import threading
    import time

    import zeebe_tpu.state.db as dbm
    from zeebe_tpu.state.db import encode_key

    monkeypatch.setattr(dbm, "LOAD", 4)
    store = _make_store("plain", tmp_path, native)
    low, cf, high = _INDEX_CFS
    settled = [encode_key(cf, (7, n)) for n in range(60)]
    with store.transaction() as txn:
        for key in settled:
            txn.put(key, None)
    inner = set(settled[10:50])  # blocks hold at most eight keys here
    stop = threading.Event()
    faults: list = []
    reads = [0]

    def read():
        try:
            while not stop.is_set():
                got = store.committed_keys_of(cf, (7,))
                one = store.committed_keys_of(cf, (7, 30))
                # a count races the writes between its two bisects: it may
                # be off by them, it may not raise
                counted = store.key_counts_by_cf()[cf.name]
                if (got != sorted(set(got)) or inner - set(got)
                        or len(got) > 70 or one != settled[30:31]
                        or counted < 1):
                    faults.append((len(got), one, counted))
                    return
                reads[0] += 1
        except Exception as error:  # noqa: BLE001 — reported by the assert
            faults.append(error)

    def write():
        try:
            n = 0
            while not stop.is_set():
                n += 1
                with store.transaction() as txn:
                    # blocks split and vanish in the families on both sides
                    # and at both edges of the settled keys' own
                    for parts in ((low,), (high,), (cf, 6), (cf, 8)):
                        for k in range(12):
                            txn.put(encode_key(parts[0],
                                               parts[1:] + (n * 12 + k,)), None)
                        if n > 4:
                            for k in range(12):
                                txn.delete(encode_key(
                                    parts[0], parts[1:] + ((n - 4) * 12 + k,)))
        except Exception as error:  # noqa: BLE001
            faults.append(error)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=read) for _ in range(3)]
    threads.append(threading.Thread(target=write))
    try:
        for thread in threads:
            thread.start()
        time.sleep(1.5)
    finally:
        stop.set()
        for thread in threads:
            thread.join(timeout=20)
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    assert not faults, faults[:3]
    assert reads[0] > 50
