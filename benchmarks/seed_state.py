"""State that exists before the window: a deployment's ``state`` key.

    "state": {"parked": {"instances": n, "definition": {<one entry as in a
              mix's "definitions">}, "variables": {...}}}

Before the harness builds its cluster, every partition of the deployment's
data directory is made to hold ``n / partitions`` instances of that
definition, each waiting at its service task with its job created and
activatable. It gets there through the program's own doors, the way a
restart's state does:

1. a cluster of the deployment's layout is started **on the data directory**
   (the plain engine: no kernel backend, no gateway), the definition is
   deployed and a *cohort* of instances is created through the brokers' batched
   client ingress: every row is the engine's own;
2. every replica takes a full snapshot (``ZeebePartition.take_snapshot``) and
   the cluster stops: the directory now is what a stopped deployment leaves;
3. in each replica's snapshot the cohort's rows are **cloned** under keys
   shifted by a fixed stride until ``n`` instances stand (a key is shifted
   wherever it stands: in a row's key, in a value that names it), the key
   generator is moved past them, and ``state.bin`` is written back with the
   manifest the program's chain check holds it to (``manifest_bytes``).

The harness's cluster then starts on that directory and every replica
recovers: chain check, ``load_chain_db``, the bulk install, the replay of
what the log holds behind the snapshot. No row is planted in a running
partition. The program has no bulk door that makes instances
(``zeebe_tpu/backup`` restores what a cluster once held, as a restart does);
the snapshot store is the door a restart uses, so it is the one used here.

A clone is made on the packed bytes: a key above 2**51 packs to eight bytes
big-endian wherever it stands (``0x01`` + sign-flipped in a row's key,
``0xcf`` + plain in a value), so a shifted copy is the same bytes with those
fields added to. Every cohort row's fields are found once, and held against
the plain way (the row decoded, every key in it shifted, packed again by the
program's own ``encode_key`` and ``packb``) before a copy is made from them.
"""

from __future__ import annotations

import struct
import time
import zlib
from pathlib import Path

import definitions as defs

#: instances the engine itself creates on a partition; the rest are clones
#: (the engine's part of the seed is 0.4 s at 250 and 2.4 s at 2,000, and on a
#: shared host it swings in proportion: PERF.md, PR 33)
COHORT = 250
#: the control ``lose_parked``: one instance in this many is left out
LOSE_ONE_IN = 1000
SIGN = 1 << 63
_U64 = struct.Struct(">Q")


def parked_definition(state: dict) -> dict:
    return defs.build_definitions([state["parked"]["definition"]])[0]


def refuse_clash(state: dict, traffic_definitions: list) -> None:
    """The parked definition's id and job types are its own: a mix that used
    either would start instances of it, or send its workers after the parked
    jobs."""
    known = sorted(set(state) - {"parked"})
    if known or "parked" not in state:
        raise ValueError(f"state {known or sorted(state)}: known is \"parked\"")
    parked = parked_definition(state)
    if not defs.jobs_per_instance(parked):
        raise ValueError(f"state.parked: definition {parked['id']!r} runs no "
                         "job, or a number that may depend on x: its "
                         "instances would not wait")
    live = defs.build_definitions(traffic_definitions)
    if parked["id"] in {d["id"] for d in live}:
        raise ValueError(f"state.parked: the mix also uses the definition id "
                         f"{parked['id']!r}")
    if shared := sorted(set(defs.job_types([parked])) & set(defs.job_types(live))):
        raise ValueError(f"state.parked: the mix also uses the job type(s) "
                         f"{shared}")


# ---------------------------------------------------------------------------
# the cohort: the engine's own rows


def _acknowledging_exporter():
    """Stands where the harness's exporter will stand, under its id, and
    acknowledges every record: the harness's exporter then starts behind the
    seed's records, as a restarted exporter starts behind what it had."""
    from zeebe_tpu.exporters.api import Exporter

    class Acknowledging(Exporter):
        def export(self, logged) -> None:
            self.controller.update_last_exported_position(logged.position)

    return Acknowledging()


def _wait(what: str, done, timeout_s: float = 120.0) -> None:
    deadline = time.monotonic() + timeout_s
    while not done():
        if time.monotonic() > deadline:
            raise RuntimeError(f"seed: {what} did not happen in {timeout_s:.0f}s")
        time.sleep(0.005)


def _create_cohort(runtime, layout: dict, definition: dict, variables: dict,
                   cohort: int) -> dict:
    """Deploys the definition, creates ``cohort`` instances of it with those
    variables on every partition, lets every replica catch up and snapshot.
    Returns partition -> the ``first`` and ``last`` key of the cohort there,
    and the log's ``end_position``."""
    from zeebe_tpu.protocol import ValueType, command
    from zeebe_tpu.protocol.intent import (DeploymentIntent,
                                           ProcessInstanceCreationIntent)
    from zeebe_tpu.protocol.keys import encode_partition_id
    from zeebe_tpu.state import ColumnFamilyCode as CF

    partitions = range(1, int(layout["partitions"]) + 1)
    answer = runtime.submit(1, command(
        ValueType.DEPLOYMENT, DeploymentIntent.CREATE,
        {"resources": [{"resourceName": f"{definition['id']}.bpmn",
                        "resource": defs.to_bpmn_xml(definition)}]}),
        timeout_s=60.0)
    if answer.is_rejection:
        raise RuntimeError(f"seed: the deployment was rejected: {answer}")

    def replicas(pid):
        return [b.partitions[pid] for b in runtime.brokers.values()
                if pid in b.partitions]

    def counter(partition) -> int:
        return partition.db.committed_get(CF.KEY, ("next",)) or 1

    ranges = {}
    create = command(
        ValueType.PROCESS_INSTANCE_CREATION, ProcessInstanceCreationIntent.CREATE,
        {"bpmnProcessId": definition["id"], "processDefinitionKey": -1,
         "version": -1, "variables": variables})
    for pid in partitions:
        # the deployment is distributed from partition 1
        _wait(f"partition {pid} learning the definition", lambda: any(
            r.is_leader and r.db.committed_get(
                CF.PROCESS_VERSION, ("<default>", definition["id"]))
            for r in replicas(pid)))
        with runtime._partition_guard(pid):
            leader = runtime._leader_partition(pid)
            first = counter(leader)
            answers = leader.client_write_batch([create] * cohort)
        if any(status != "ok" for status, _position in answers):
            raise RuntimeError(f"seed: partition {pid} refused a create: "
                               f"{[a for a in answers if a[0] != 'ok'][:3]}")
        last_command = answers[-1][1]

        def settled(leader=leader, last_command=last_command, pid=pid) -> bool:
            if (leader.processor.last_processed_position < last_command
                    or leader.processor.last_written_position
                    > leader.stream.last_position):
                return False
            end = leader.stream.last_position
            return all(
                r.stream.last_position == end
                and r.processor.last_processed_position
                == leader.processor.last_processed_position
                and r.exporter_director.lowest_exporter_position() >= end
                for r in replicas(pid))

        _wait(f"partition {pid} settling", settled)
        ranges[pid] = {"first": encode_partition_id(pid, first),
                       "last": encode_partition_id(pid, counter(leader) - 1),
                       "end_position": leader.stream.last_position}
        for replica in replicas(pid):
            with runtime._partition_guard(pid):
                if not replica.take_snapshot(force_full=True):
                    raise RuntimeError(f"seed: partition {pid} took no snapshot")
    return ranges


# ---------------------------------------------------------------------------
# the clones


def _keys_in(obj, lo: int, hi: int, out: list) -> list:
    """Every int of ``obj`` that is one of the cohort's keys."""
    kind = type(obj)
    if kind is int:
        if lo <= obj <= hi:
            out.append(obj)
    elif kind is dict:
        for value in obj.values():
            _keys_in(value, lo, hi, out)
    elif kind is list or kind is tuple:
        for value in obj:
            _keys_in(value, lo, hi, out)
    return out


def _shifted(obj, lo: int, hi: int, by: int):
    """The plain way: ``obj`` with every one of the cohort's keys moved."""
    kind = type(obj)
    if kind is int:
        return obj + by if lo <= obj <= hi else obj
    if kind is dict:
        return {k: _shifted(v, lo, hi, by) for k, v in obj.items()}
    if kind is list or kind is tuple:
        return kind(_shifted(v, lo, hi, by) for v in obj)
    return obj


def _instance_of(value, lo: int, hi: int):
    """The instance a row's value says it belongs to, or None."""
    if type(value) is dict:
        key = value.get("processInstanceKey")
        if type(key) is int and lo <= key <= hi:
            return key
        return _instance_of(value.get("value"), lo, hi)
    return None


def clone_state(raw: bytes, first: int, last: int, instances: int,
                lose: bool = False) -> tuple[bytes, int]:
    """``raw``: a full snapshot (``state.bin``) that holds a cohort whose keys
    are ``first``..``last``. Returns the snapshot with ``instances`` instances
    in it, and its number of rows. ``lose``: the control, one instance in
    ``LOSE_ONE_IN`` is left out."""
    import numpy as np

    from zeebe_tpu.protocol import msgpack
    from zeebe_tpu.state import ColumnFamilyCode as CF
    from zeebe_tpu.state.db import ZbDb, decode_key, encode_key

    magic = ZbDb.SNAPSHOT_MAGIC
    if raw[:len(magic)] != magic:
        raise ValueError("seed: the snapshot is no full state snapshot")
    stride = last - first + 1
    next_key = encode_key(CF.KEY, ("next",))
    fixed, rows, owner = [], [], {}
    for key, value in msgpack.unpackb(raw[len(magic) + 4:]):
        cf, parts = decode_key(key)
        in_key = _keys_in(parts, first, last, [])
        in_value = _keys_in(value, first, last, [])
        if not in_key and not in_value:
            fixed.append((key, value))
            continue
        rows.append((key, cf, parts, value, in_key, set(in_key + in_value)))
        if (instance := _instance_of(value, first, last)) is not None:
            owner.update(dict.fromkeys(in_key, instance))
    by_instance: dict = {}
    for row in rows:
        owners = {owner.get(k) for k in row[4]}
        if len(owners) != 1 or None in owners:
            raise ValueError(f"seed: cannot tell whose row {row[1].name} "
                             f"{row[2]} is")
        by_instance.setdefault(owners.pop(), []).append(row)
    cohort = sorted(by_instance)
    copies = -(-instances // len(cohort))

    # every cohort row packed once, its key fields found, and the byte-wise
    # shift held against the plain one
    chunks, fields, spans, at = [], [], [], 0
    for instance in cohort:
        mine = []
        for key, cf, parts, value, _in_key, named in by_instance[instance]:
            packed = msgpack.packb([key, value])
            found = set()
            for k in named:
                for tag, field in ((b"\x01", _U64.pack(k ^ SIGN)),
                                   (b"\xcf", _U64.pack(k))):
                    i = packed.find(tag + field)
                    while i >= 0:
                        found.add(i + 1)
                        i = packed.find(tag + field, i + 1)
            moved = bytearray(packed)
            for i in found:
                _U64.pack_into(moved, i, _U64.unpack_from(packed, i)[0] + stride)
            plain = msgpack.packb([
                encode_key(cf, _shifted(parts, first, last, stride)),
                _shifted(value, first, last, stride)])
            if bytes(moved) != plain:
                raise ValueError(f"seed: row {cf.name} {parts} does not shift "
                                 "byte-wise as it does decoded")
            key_at = packed.index(key)
            mine.append((at + key_at, at + key_at + len(key), at,
                         at + len(packed)))
            fields += [at + i for i in found]
            chunks.append(packed)
            at += len(packed)
        spans.append(mine)
    template = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    where = (np.asarray(fields, dtype=np.int64)[:, None]
             + np.arange(8, dtype=np.int64))
    values = template[where].copy().view(">u8")[:, 0].astype(np.uint64)

    entries = []
    for copy in range(copies):
        buffer = template.copy()
        buffer[where] = ((values + np.uint64(copy * stride))
                         .astype(">u8").view(np.uint8).reshape(-1, 8))
        block = buffer.tobytes()
        for i, mine in enumerate(spans[:instances - copy * len(cohort)]):
            if lose and (copy * len(cohort) + i) % LOSE_ONE_IN == 0:
                continue
            for key_from, key_to, row_from, row_to in mine:
                entries.append((block[key_from:key_to], block[row_from:row_to]))
    for key, value in fixed:
        if key == next_key:     # the generator's local counter, past the clones
            value += (copies - 1) * stride
        entries.append((key, msgpack.packb([key, value])))
    entries.sort()
    body = b"".join([b"\xdd", struct.pack(">I", len(entries)),
                     *(packed for _key, packed in entries)])
    return (magic + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF) + body,
            len(entries))


def _clone_snapshots(data_dir: Path, layout: dict, ranges: dict,
                     per_partition: int, lose: bool) -> int:
    """Every replica's newest snapshot gets its clones. Returns the rows of
    the largest state written."""
    from zeebe_tpu.state.snapshot import (STATE_FILE, FileBasedSnapshotStore,
                                          manifest_bytes)

    done: dict = {}     # replicas of a partition hold the same bytes
    most = 0
    for b in range(int(layout["brokers"])):
        for pid, found in ranges.items():
            root = data_dir / f"broker-{b}" / f"partition-{pid}" / "snapshots"
            if not root.is_dir():
                continue
            store = FileBasedSnapshotStore(root)
            chain = store.latest_valid_chain()
            if chain is None or len(chain) != 1:
                raise RuntimeError(f"seed: broker-{b} partition {pid} left no "
                                   "full snapshot")
            snapshot = chain[0]
            raw = snapshot.read_file(STATE_FILE)
            if (pid, raw) not in done:
                done[(pid, raw)] = clone_state(
                    raw, found["first"], found["last"], per_partition, lose)
            state, rows = done[(pid, raw)]
            most = max(most, rows)
            (snapshot.path / STATE_FILE).write_bytes(state)
            files = {p.name: (state if p.name == STATE_FILE else p.read_bytes())
                     for p in snapshot.files()}
            (snapshot.path / "CHECKSUM.sfv").write_bytes(manifest_bytes(files))
            if store.chain_of(snapshot) is None:
                raise RuntimeError(f"seed: the program's chain check refuses "
                                   f"the seeded snapshot {snapshot.path}")
    return most


def seed(layout: dict, state: dict, data_dir: Path, exporter_id: str,
         lose: bool = False, cohort: int = COHORT) -> dict:
    """Seeds ``data_dir`` (the directory the harness's cluster is then built
    on). Returns what the comparison needs: the parked definition's ``id``,
    the ``variables`` every parked instance carries, ``instances`` (the count
    the deployment states, whatever ``lose`` left out), ``per_partition``,
    ``end_position`` (partition -> the log's last position when the seed
    stopped), and for the record ``rows`` (of the largest state written),
    ``cohort`` and ``seconds``. ``cohort``: the tests' smaller ones."""
    from zeebe_tpu.gateway import ClusterRuntime

    started = time.monotonic()
    parked = state["parked"]
    instances, partitions = int(parked["instances"]), int(layout["partitions"])
    if instances <= 0 or instances % partitions:
        raise ValueError(f"state.parked.instances {instances}: not a positive "
                         f"multiple of the {partitions} partitions")
    per_partition = instances // partitions
    cohort = min(cohort, per_partition)
    variables = dict(parked.get("variables") or {})
    runtime = ClusterRuntime(
        broker_count=int(layout["brokers"]), partition_count=partitions,
        replication_factor=int(layout["replication_factor"]),
        directory=data_dir, kernel_backend=False, backpressure_enabled=False,
        exporters_factory=lambda: {exporter_id: _acknowledging_exporter()})
    try:
        runtime.start()
        ranges = _create_cohort(runtime, layout, parked_definition(state),
                                variables, cohort)
    finally:
        runtime.stop()
    cohort_s = time.monotonic() - started
    rows = _clone_snapshots(Path(data_dir), layout, ranges, per_partition, lose)
    return {"id": parked_definition(state)["id"], "variables": variables,
            "instances": instances,
            "per_partition": per_partition, "cohort": cohort, "rows": rows,
            "end_position": {pid: r["end_position"] for pid, r in ranges.items()},
            "cohort_seconds": cohort_s,
            "seconds": time.monotonic() - started}
