"""Disk-backed partition state with O(delta) checkpoints — the large-state
backend (VERDICT r4 item 2).

Reference anchors: zb-db/src/main/java/io/camunda/zeebe/db/impl/rocksdb/
transaction/ZeebeTransaction.java:22 (RocksDB transactional store whose
checkpoints are O(delta) hard links) and broker/…/partitions/impl/perf/
LargeStateControllerPerformanceTest.java:46,69-78 (≥10 snapshot+recover ops/s
on 4 GB of state). The design here is NOT a RocksDB port — it exploits this
framework's own invariant that the replicated log is the durability source of
truth (state is always recomputable by replay), so the disk structures only
need crash-consistency, not synchronous durability:

- **Hot/cold split**: committed values start life as the Python objects the
  engine wrote (hot). A size-budgeted LRU demotes cold values to their
  msgpack bytes (``_Packed``), so resident memory tracks the SERIALIZED state
  size instead of the Python-object expansion — the 0.5–4 GB anchors fit
  where a pure object heap would not. Reads resolve cold values lazily and
  re-promote them.
- **Write-ahead delta log**: every transaction commit appends its overlay
  (the changed keys only) to the current WAL segment — O(delta) per commit,
  buffered, no fsync on the hot path.
- **Checkpoint** = flush + fsync the WAL tail and atomically publish a tiny
  manifest. Cost is O(bytes written since the last checkpoint), never
  O(total state) — the property the in-memory ``to_snapshot_bytes`` lacked.
- **Compaction**: when the WAL chain outgrows the base, the full state is
  rewritten as a new base segment (cold values are spliced as already-packed
  bytes) and the chain resets — amortized O(1) per write.
- **Recovery** maps the base segment and indexes its KEYS only; values stay
  on disk as mmap-backed cold slices resolved (and CRC-verified) on first
  read. Recover cost ≈ key-index scan, not state size — the analogue of
  RocksDB's open-from-hard-linked-checkpoint, where nothing re-reads the
  SSTs either. The WAL chain (small by construction — compaction bounds it)
  replays eagerly.
"""

from __future__ import annotations

import mmap
import os
import struct
import zlib
from collections import OrderedDict
from pathlib import Path
from typing import Any

from zeebe_tpu.native import codec_fn as _codec_fn
from zeebe_tpu.protocol import msgpack
from zeebe_tpu.state.db import BlockedKeyIndex, ZbDb, _DELETED, encode_key

_index_base_segment = _codec_fn("index_base_segment")

_FRAME = struct.Struct("<II")  # WAL frame: length, crc32
#: base-segment entry header: key len, value len, key crc. The value crc sits
#: AFTER the key, adjacent to the value bytes, so one contiguous mmap slice
#: [vcrc|value] is the whole cold representation — recovery then installs a
#: raw memoryview per entry (no per-entry Python object construction at all)
_ENTRY = struct.Struct("<HII")
_VCRC = struct.Struct("<I")
_MANIFEST = "MANIFEST"


class _Packed:
    """A cold committed value demoted in memory: its msgpack bytes."""

    __slots__ = ("b",)

    def __init__(self, b: bytes) -> None:
        self.b = b

    def resolve(self) -> Any:
        return msgpack.unpackb(self.b)


def _resolve_view(mv: memoryview) -> Any:
    """Resolve an mmap-backed cold slice ([vcrc u32][msgpack value]) with
    its crc check — the lazy analogue of RocksDB block checksums."""
    (crc,) = _VCRC.unpack_from(mv)
    body = mv[4:]
    if zlib.crc32(body) & 0xFFFFFFFF != crc:
        raise ValueError("corrupt cold state value (crc mismatch)")
    return msgpack.unpackb(body)


def _resolve_value(val: Any) -> Any:
    t = type(val)
    if t is _Packed:
        return val.resolve()
    if t is memoryview:
        return _resolve_view(val)
    return val


def _cold_size(val: Any) -> int:
    return len(val.b) if type(val) is _Packed else len(val) - 4


def _pack_value(value: Any) -> bytes:
    t = type(value)
    if t is _Packed:
        return value.b
    if t is memoryview:
        return bytes(value[4:])
    return msgpack.packb(value)


class DurableZbDb(ZbDb):
    """ZbDb with a disk-backed delta log and bounded object residency.

    Drop-in for the engine/processor: the transactional interface, column
    families, FK checks, and the full-serialization snapshot path
    (``to_snapshot_bytes`` — used by raft snapshot INSTALL to ship state to
    a lagging follower) are inherited. What changes:

    - ``checkpoint()``: O(delta) durable point; ``DurableZbDb.open()``
      recovers to the latest checkpoint.
    - cold values live as msgpack bytes under ``hot_budget_bytes`` of
      decoded-object budget.
    """

    # _data holds _Packed/memoryview cold representations: a delta snapshot
    # serialized from it would crash msgpack or round-trip wrong types (the
    # durable store has its own O(delta) story — checkpoint())
    supports_delta_snapshots = False

    #: knob defaults, shared by __init__ and open()
    DEFAULT_HOT_BUDGET_BYTES = 256 << 20
    DEFAULT_COMPACT_FACTOR = 2.0
    DEFAULT_MIN_COMPACT_BYTES = 64 << 20

    def __init__(self, directory: str | Path,
                 consistency_checks: bool = False,
                 hot_budget_bytes: int = DEFAULT_HOT_BUDGET_BYTES,
                 compact_factor: float = DEFAULT_COMPACT_FACTOR,
                 min_compact_bytes: int = DEFAULT_MIN_COMPACT_BYTES) -> None:
        super().__init__(consistency_checks)
        self._init_runtime(directory, hot_budget_bytes, compact_factor,
                           min_compact_bytes)
        self._open_wal()

    def _init_runtime(self, directory: str | Path, hot_budget_bytes: int,
                      compact_factor: float, min_compact_bytes: int) -> None:
        """Field setup shared by the constructor and ``open()`` (which
        bypasses ``__init__`` to stage recovery lazily)."""
        import threading

        # cold values need per-read resolution, which the native iterate
        # cannot do — use the (identical-semantics) Python merge path. The
        # commit is ZbDb's, native pass included: the key index is the one
        # every backend shares (state/db.py BlockedKeyIndex), and what a
        # commit writes is hot
        self._native_iterate = None
        self.directory = Path(directory)
        self.directory.mkdir(parents=True, exist_ok=True)
        self.hot_budget_bytes = hot_budget_bytes
        self.compact_factor = compact_factor
        self.min_compact_bytes = min_compact_bytes
        # LRU of hot keys → approximate packed size (budget accounting).
        # Values live in _data; this only orders/fences them.
        self._hot: OrderedDict[bytes, int] = OrderedDict()
        self._hot_bytes = 0
        self._base_file: str | None = None
        self._base_bytes = 0
        self._wal_files: list[str] = []
        # durable length per sealed/recovered segment (a recovered segment
        # may hold frames beyond its checkpointed tail — commits that were
        # reverted by a recovery and will be re-derived by log replay; they
        # must never replay from disk ahead of their re-derivation)
        self._wal_tails: dict[str, int] = {}
        self._wal = None  # current segment handle
        self._wal_seq = 0
        self._wal_bytes = 0  # total bytes across the sealed+current chain
        # live mmaps backing cold value slices; released only at close (an
        # old base's map must outlive compaction while _data still holds
        # views into it — Linux keeps unlinked-but-mapped data readable)
        self._maps: list[mmap.mmap] = []
        self._recovery_lock = threading.Lock()

    # -- wal ------------------------------------------------------------------

    def _open_wal(self) -> None:
        self._wal_seq += 1
        name = f"wal-{self._wal_seq:08d}.log"
        # "wb", not "ab": a new segment must TRUNCATE any stale file left by
        # a session that crashed before checkpointing this name into the
        # manifest — its dead frames would otherwise sit at the head and
        # replay a reverted timeline after the next checkpoint covers the
        # file (no manifest ever references a segment we are creating here:
        # manifests only list segments named by earlier, lower seqs)
        self._wal = open(self.directory / name, "wb")
        self._wal_files.append(name)

    def _pre_commit(self, writes: dict[bytes, Any]) -> None:
        if self._demote_pending:
            # demote the cold tail accumulated by earlier commits/reads;
            # safe mid-transaction — demotion only repacks COMMITTED values,
            # never overlay writes or the transaction's defensive copies
            self._maybe_demote()
        if not writes:
            return
        entries = []
        hot, data = self._hot, self._data
        for key, val in writes.items():
            if val is _DELETED:
                entries.append([key, True, b""])
                if key in hot:
                    self._hot_bytes -= hot.pop(key)
            else:
                packed = msgpack.packb(val)
                entries.append([key, False, packed])
                prev = hot.pop(key, None)
                if prev is not None:
                    self._hot_bytes -= prev
                hot[key] = len(packed)
                self._hot_bytes += len(packed)
        body = msgpack.packb(entries)
        frame = _FRAME.pack(len(body), zlib.crc32(body) & 0xFFFFFFFF) + body
        self._wal.write(frame)
        self._wal_bytes += len(frame)
        # demote over-budget cold tail AFTER the overlay applies (commit()
        # runs right after this hook) — deferring via a flag keeps ordering
        # simple because demotion only touches committed, non-overlay keys
        self._demote_pending = self._hot_bytes > self.hot_budget_bytes

    _demote_pending = False

    def _maybe_demote(self) -> None:
        if not self._demote_pending:
            return
        self._demote_pending = False
        hot, data = self._hot, self._data
        while self._hot_bytes > self.hot_budget_bytes and len(hot) > 1:
            key, size = hot.popitem(last=False)
            self._hot_bytes -= size
            val = data.get(key)
            if (val is not None and type(val) is not _Packed
                    and type(val) is not memoryview):
                data[key] = _Packed(msgpack.packb(val))

    # -- read resolution ------------------------------------------------------

    def _committed_value(self, key: bytes) -> Any:
        val = self._data.get(key)
        t = type(val)
        if t is not _Packed and t is not memoryview:
            return val
        obj = _resolve_value(val)
        # promote: the processing hot set should stay decoded
        size = _cold_size(val)
        self._data[key] = obj
        self._hot[key] = size
        self._hot_bytes += size
        if self._hot_bytes > self.hot_budget_bytes:
            self._demote_pending = True
        return obj

    def committed_get(self, code, key_parts) -> Any:
        """Cross-thread committed read: resolves cold values WITHOUT
        promoting (no LRU/object mutation from the query thread)."""
        self._ensure_recovered()

        if not isinstance(key_parts, tuple):
            key_parts = (key_parts,)
        return _resolve_value(self._data.get(encode_key(code, key_parts)))

    # -- checkpoint / recover -------------------------------------------------

    def checkpoint(self) -> dict:
        """Durable O(delta) checkpoint: fsync the WAL tail, publish the
        manifest. Returns the manifest dict (base, wal chain, tail offset)."""
        if self.in_transaction:
            raise RuntimeError("cannot checkpoint with an open transaction")
        self._ensure_recovered()
        self._maybe_demote()
        self._wal.flush()
        os.fsync(self._wal.fileno())
        self._wal_tails[self._wal_files[-1]] = self._wal.tell()
        manifest = self._manifest_doc()
        self._write_manifest(manifest)
        if self._wal_bytes > max(self._base_bytes * self.compact_factor,
                                 self.min_compact_bytes):
            manifest = self._compact()
        return manifest

    def _manifest_doc(self) -> dict:
        return {
            "base": self._base_file,
            "wals": list(self._wal_files),
            "tails": [self._wal_tails.get(name, 0) for name in self._wal_files],
        }

    def _write_manifest(self, manifest: dict) -> None:
        body = msgpack.packb(manifest)
        tmp = self.directory / (_MANIFEST + ".tmp")
        with open(tmp, "wb") as f:
            f.write(struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF) + body)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.directory / _MANIFEST)
        dir_fd = os.open(self.directory, os.O_RDONLY)
        try:
            os.fsync(dir_fd)
        finally:
            os.close(dir_fd)

    def _compact(self) -> dict:
        """Rewrite the full state as a new base segment and reset the WAL
        chain. Cold values are spliced as already-packed bytes — no decode.
        The new manifest publishes BEFORE stale files unlink, so a crash at
        any point leaves a recoverable chain."""
        seq = self._wal_seq + 1
        name = f"base-{seq:08d}.seg"
        tmp = self.directory / (name + ".tmp")
        data = self._data
        total = 0
        with open(tmp, "wb") as f:
            for key in self._index:
                val = data[key]
                kcrc = zlib.crc32(key) & 0xFFFFFFFF
                if type(val) is memoryview:
                    # cold slice already carries [vcrc|value] — splice whole
                    f.write(_ENTRY.pack(len(key), len(val) - 4, kcrc))
                    f.write(key)
                    f.write(val)
                    total += _ENTRY.size + len(key) + len(val)
                else:
                    packed = _pack_value(val)
                    f.write(_ENTRY.pack(len(key), len(packed), kcrc))
                    f.write(key)
                    f.write(_VCRC.pack(zlib.crc32(packed) & 0xFFFFFFFF))
                    f.write(packed)
                    total += _ENTRY.size + len(key) + 4 + len(packed)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.directory / name)
        old_wals, old_base = self._wal_files, self._base_file
        self._wal.close()
        self._base_file = name
        self._base_bytes = total
        self._wal_files = []
        self._wal_tails = {}
        self._wal_bytes = 0
        self._wal_seq = seq
        self._open_wal()
        self._wal.flush()
        os.fsync(self._wal.fileno())
        self._wal_tails[self._wal_files[-1]] = self._wal.tell()
        manifest = self._manifest_doc()
        self._write_manifest(manifest)
        for stale in old_wals:
            try:
                os.unlink(self.directory / stale)
            except OSError:
                pass
        if old_base:
            try:
                os.unlink(self.directory / old_base)
            except OSError:
                pass
        return manifest

    @classmethod
    def open(cls, directory: str | Path, consistency_checks: bool = False,
             **kw) -> "DurableZbDb":
        """Recover to the latest checkpoint. The base segment is mmapped and
        only its KEY index materializes; values stay on disk as cold slices
        resolved lazily — recovery cost ≈ key scan, not state size."""
        directory = Path(directory)
        manifest_path = directory / _MANIFEST
        manifest = None
        if manifest_path.exists():
            raw = manifest_path.read_bytes()
            (crc,) = struct.unpack_from("<I", raw)
            body = raw[4:]
            if zlib.crc32(body) & 0xFFFFFFFF != crc:
                raise ValueError("corrupt durable-state manifest")
            manifest = msgpack.unpackb(body)
        db = cls.__new__(cls)
        ZbDb.__init__(db, consistency_checks)
        db._init_runtime(
            directory,
            kw.get("hot_budget_bytes", cls.DEFAULT_HOT_BUDGET_BYTES),
            kw.get("compact_factor", cls.DEFAULT_COMPACT_FACTOR),
            kw.get("min_compact_bytes", cls.DEFAULT_MIN_COMPACT_BYTES),
        )
        if manifest is not None:
            base = manifest.get("base")
            if base:
                db._base_file = base
                db._base_bytes = (directory / base).stat().st_size
            wals = manifest.get("wals") or []
            tails = manifest.get("tails") or [None] * len(wals)
            for wal, tail in zip(wals, tails):
                db._wal_bytes += (tail if tail
                                  else (directory / wal).stat().st_size)
                db._wal_tails[wal] = tail or 0
            db._wal_files = list(wals)
            db._wal_seq = _max_seq(wals, db._base_file)
            # LAZY recovery: open() publishes only the manifest view — the
            # base index + WAL replay run on FIRST state access
            # (_ensure_recovered). This is what RocksDB's recover-from-
            # checkpoint costs too: opening hard links + manifest, with the
            # data itself faulted in later through the block cache.
            db._lazy_recovery = (
                directory / base if base else None,
                [(directory / wal, tail) for wal, tail in zip(wals, tails)],
            )
        db._open_wal()
        return db

    #: staged (base_path, [(wal_path, tail), …]) recovery work, or None
    _lazy_recovery = None

    def _before_transaction(self) -> None:
        self._ensure_recovered()

    def _ensure_recovered(self) -> None:
        if self._lazy_recovery is None:
            return
        with self._recovery_lock:
            if self._lazy_recovery is None:
                return  # lost the race; the winner indexed already
            base_path, wal_specs = self._lazy_recovery
            data = self._data
            base_keys = self._index_base(base_path) if base_path else []
            touched: set[bytes] = set()
            for wal_path, tail in wal_specs:
                for entries in _read_wal(wal_path, tail):
                    for key, deleted, packed in entries:
                        touched.add(key)
                        if deleted:
                            data.pop(key, None)
                        else:
                            data[key] = _Packed(packed)
            # key order: the base arrives sorted (the index builds from
            # sorted input in one O(n) pass); patch the (typically tiny)
            # WAL key-set delta in with one-block adds/discards
            keys = BlockedKeyIndex(base_keys)
            base_set = set(base_keys) if touched else None
            for key in touched:
                in_data = key in data
                if in_data and key not in base_set:
                    keys.add(key)
                elif not in_data and key in base_set:
                    keys.discard(key)
            self._index = keys
            # publish only after the view is complete (committed_get races)
            self._lazy_recovery = None

    def _index_base(self, path: Path) -> list[bytes]:
        """Scan a base segment's entry headers, verifying KEY crcs eagerly
        (cheap: keys are a sliver of the file) and installing mmap-backed
        cold slices for the values (their crc verifies at resolution). A
        torn/corrupt entry truncates the scan, like the journal. Returns the
        keys in file order (== sorted order: compaction writes sorted)."""
        size = path.stat().st_size
        if size == 0:
            return []
        with open(path, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
        self._maps.append(mm)
        view = memoryview(mm)
        data = self._data
        if _index_base_segment is not None:
            # one native pass (codec.c index_base_segment): keys + raw cold
            # slices, zero per-entry Python construction — this is what makes
            # recovery O(key index), the gate for the ≥10 snapshot+recover
            # ops/s large-state floor
            return _index_base_segment(view, data)
        keys: list[bytes] = []
        off, n = 0, size
        while off + _ENTRY.size <= n:
            klen, vlen, kcrc = _ENTRY.unpack_from(mm, off)
            kstart = off + _ENTRY.size
            vend = kstart + klen + 4 + vlen
            if vend > n:
                return keys
            key = bytes(view[kstart:kstart + klen])
            if zlib.crc32(key) & 0xFFFFFFFF != kcrc:
                return keys
            data[key] = view[kstart + klen:vend]
            keys.append(key)
            off = vend
        return keys

    def approx_bytes(self) -> int:
        """Serialized size of the committed state (cold exact, hot by the
        last packed size; hot keys never packed yet are estimated on use)."""
        self._ensure_recovered()
        total = 0
        for key, val in self._data.items():
            t = type(val)
            if t is _Packed or t is memoryview:
                total += _cold_size(val)
            else:
                total += self._hot.get(key) or len(msgpack.packb(val))
        return total

    def close(self) -> None:
        if self._wal is not None:
            self._wal.close()
            self._wal = None
        # drop cold views so the maps can release; a map with a live
        # exported view elsewhere just stays for the GC
        self._data = {}
        self._index = BlockedKeyIndex()
        for mm in self._maps:
            try:
                mm.close()
            except BufferError:
                pass
        self._maps = []

    # -- full-serialization compatibility -------------------------------------

    SNAPSHOT_MAGIC = ZbDb.SNAPSHOT_MAGIC

    def to_snapshot_bytes(self) -> bytes:
        """Full serialization (raft snapshot install ships this to lagging
        followers). Cold values decode once here — this path is rare and
        inherently O(total)."""
        if self.in_transaction:
            raise RuntimeError("cannot snapshot with an open transaction")
        self._ensure_recovered()
        body = msgpack.packb([
            [k, self._resolve(self._data[k])] for k in self._index
        ])
        crc = zlib.crc32(body) & 0xFFFFFFFF
        return self.SNAPSHOT_MAGIC + struct.pack("<I", crc) + body

    _resolve = staticmethod(_resolve_value)

    def content_equals(self, other: ZbDb) -> bool:
        self._ensure_recovered()
        if isinstance(other, DurableZbDb):
            other._ensure_recovered()
        if set(self._data) != set(other._data):
            return False
        for key, val in self._data.items():
            if self._resolve(val) != self._resolve(other._data[key]):
                return False
        return True

    def install_snapshot_bytes(self, raw: bytes) -> None:
        """Replace the whole committed state from a full snapshot (raft
        INSTALL on a lagging follower), then compact so the disk structures
        reflect it."""
        self._ensure_recovered()  # settle staged work before wholesale replace
        restored = ZbDb.from_snapshot_bytes(raw)
        self._data = restored._data
        self._index = restored._index
        self._hot.clear()
        self._hot_bytes = 0
        self._compact()  # publishes the manifest for the new state


def _read_wal(path: Path, limit: int | None):
    """Yield commit-overlay entry lists from a WAL segment up to ``limit``
    bytes (the manifest's durable tail), tolerating a torn tail beyond it."""
    with open(path, "rb") as f:
        raw = f.read()
    if limit is not None:
        raw = raw[:limit]
    off, n = 0, len(raw)
    while off + _FRAME.size <= n:
        length, crc = _FRAME.unpack_from(raw, off)
        start = off + _FRAME.size
        end = start + length
        if end > n:
            return
        body = raw[start:end]
        if zlib.crc32(body) & 0xFFFFFFFF != crc:
            return
        yield msgpack.unpackb(body)
        off = end


def _max_seq(wals: list[str], base: str | None) -> int:
    seq = 0
    for name in list(wals) + ([base] if base else []):
        stem = name.rsplit(".", 1)[0]
        try:
            seq = max(seq, int(stem.split("-", 1)[1]))
        except (IndexError, ValueError):
            pass
    return seq
