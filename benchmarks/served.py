"""The system under test, built as ``python -m zeebe_tpu.standalone`` builds it
(``load_broker_cfg`` -> ``ClusterRuntime`` -> ``Gateway``), in the harness's
process — the only one that touches JAX — with the benchmark's observers on
it: an exporter that keeps what the reference needs of every record and stamps
completions, jax's compile events, and the program's own counters. Copied from
``chip_smoke.py`` (``Served``, ``Tally``, ``CompileLedger``,
``hold_groups_on_the_accelerator``, ``kernel_report``); see PERF.md."""

from __future__ import annotations

import threading
import time
from collections import Counter
from pathlib import Path

#: reasons under which a kernel group failed or was contained
DEVICE_FAILURES = ("device-dispatch-error", "device-wedged", "device-quarantined",
                   "geometry-bounds", "no-quiesce", "token-overflow",
                   "group-error", "mesh-dispatch-error", "mesh-no-quiesce",
                   "mesh-token-overflow")
#: the id the benchmark's exporter is attached under: a seeded deployment
#: (``seed_state.py``) acknowledges its own records under it, so that this
#: exporter starts behind them
EXPORTER_ID = "bench"


class Observed:
    """What the exporter saw: per process instance its records in log order,
    as the reference's plain tuples, and when each instance completed."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.events: dict = {}       # instance key -> [event tuple]
        self.completed_at: dict = {}  # instance key -> time.monotonic()
        #: instance or job key -> the log position of the record that an
        #: acknowledgement of its create or completion rests on
        self.position_of: dict = {}
        self.records = 0
        #: export is at-least-once and every replica exports: a position is
        #: seen several times. The first sighting is kept, under
        #: (partition, position), as what the record was; every later one is
        #: compared with it and counted in ``differing`` where it differs
        self.seen: dict = {}
        self.repeats = 0
        self.differing = 0
        #: set by the harness when the window opens (control and fault tests)
        self.fault: str | None = None
        #: ``lying_follower``: appends acknowledged and not stored
        self.lies = 0
        #: instance key -> its rank among the instances seen under a fault
        self.ranks: dict = {}
        #: a seeded deployment's parked definition (``state.parked``), and the
        #: exported records that name it: none may, its jobs have no worker
        self.parked_id: str | None = None
        self.parked_records = 0

    def broken(self, key: int) -> bool:
        """Under a fault one instance in ten is broken, counted in the order
        in which the instances were first seen with the fault on: the first,
        the eleventh... So a window that holds an instance holds a broken
        one, whatever keys the run drew."""
        with self.lock:
            return self.ranks.setdefault(key, len(self.ranks)) % 10 == 0


def _breakable(event: tuple, fault: str) -> bool:
    """The records a fault may break: ``double_correlate`` a correlation,
    ``early_timer`` a trigger, the others an instance's own records (not the
    message partition's, which are kept by correlation key)."""
    if fault == "double_correlate":
        return event[:2] == ("PMS", "CORRELATED")
    if fault == "early_timer":
        return event[:2] == ("TIMER", "TRIGGERED")
    return event[0] in ("PI", "JOB", "VAR", "TIMER", "PMS")


def _broken(event: tuple, fault: str):
    """The control and the fault tests: what the timed path produced, broken
    where the harness takes it (one instance in ten: ``Observed.broken``).
    ``lose_acked``: an acknowledged instance's records never arrive
    (durability broken);
    ``at_least_once``: a job's completion is applied twice (exactly-once
    broken); ``alter_record``: a token is sent down another flow;
    ``replica_export_differs``: as ``alter_record``, but in a later replica's
    export of a position, not in the first; ``double_correlate``: a message
    is correlated to its instance twice; ``early_timer``: a timer's trigger
    is stamped a millisecond before its due date."""
    if fault == "lose_acked":
        return []
    if fault == "double_correlate":
        return [event, event]
    if fault == "early_timer":
        return [event[:6] + (event[5] - 1,)]
    if fault == "at_least_once" and event[:2] == ("JOB", "COMPLETED"):
        return [event, event]
    if (fault in ("alter_record", "replica_export_differs")
            and event[:2] == ("PI", "SEQUENCE_FLOW_TAKEN")):
        other = "flow_2" if event[2] == "flow_1" else "flow_1"
        return [event[:2] + (other,) + event[3:]]
    return [event]


def names_process(record, process_id: str) -> bool:
    """Whether a record is one of an instance of that process (or a batch of
    activated jobs with one of its jobs in it)."""
    value = record.value
    return (value.get("bpmnProcessId") == process_id
            or any(job.get("bpmnProcessId") == process_id
                   for job in value.get("jobs") or ()))


def catch_event(record, value, kind: str) -> tuple:
    """``(event, key, acked)`` of a catch event's record, as the reference's
    plain tuples. On the instance's partition, in its events, by the
    instance's key:
        ("TIMER", intent, element_id, timer_key, element_instance_key,
         due_date, record_timestamp)
        ("PMS", intent, element_id, element_instance_key, message_name,
         correlation_key, message_key)
    On the message's partition, by ``("MESSAGE", correlation key)``: the
    message partition's own sequence for that key, in its log order:
        ("MS", intent, element_id, message_name, process_instance_key,
         message_key)
        ("MESSAGE", intent, message_key)
    and a batch of expiries by ``("MESSAGE_BATCH",)``:
        ("MESSAGE_BATCH", intent, (message_key, ...))
    ``acked``: the message key of a ``PUBLISHED``, which a publish's
    acknowledgement rests on; else None. ``kind``: the record's value type,
    by the first word of the tuple it makes."""
    intent = record.intent.name
    if kind == "TIMER":
        return (("TIMER", intent, value["targetElementId"], record.key,
                 value["elementInstanceKey"], value["dueDate"],
                 record.timestamp), value["processInstanceKey"], None)
    if kind == "PMS":
        return (("PMS", intent, value["targetElementId"],
                 value["elementInstanceKey"], value["messageName"],
                 value["correlationKey"], value.get("messageKey", -1)),
                value["processInstanceKey"], None)
    if kind == "MS":
        return (("MS", intent, value["targetElementId"], value["messageName"],
                 value["processInstanceKey"], value.get("messageKey", -1)),
                ("MESSAGE", value["correlationKey"]), None)
    if kind == "MESSAGE":
        return (("MESSAGE", intent, record.key),
                ("MESSAGE", value["correlationKey"]),
                record.key if intent == "PUBLISHED" else None)
    return (("MESSAGE_BATCH", intent, tuple(value["messageKeys"])),
            ("MESSAGE_BATCH",), None)


def capture_exporter(observed: Observed):
    """The standard exporter SPI, as ``ZEEBE_BROKER_EXPORTERS_*`` would load
    it. Completion is observed here, where a deployment observes it."""
    from zeebe_tpu.exporters.api import Exporter
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import (JobIntent,
                                           ProcessInstanceCreationIntent)
    from zeebe_tpu.protocol.intent import ProcessInstanceIntent as PI

    pi_type, job_type, var_type = (ValueType.PROCESS_INSTANCE, ValueType.JOB,
                                   ValueType.VARIABLE)
    creation_type = ValueType.PROCESS_INSTANCE_CREATION
    catch_types = {ValueType.TIMER: "TIMER",
                   ValueType.PROCESS_MESSAGE_SUBSCRIPTION: "PMS",
                   ValueType.MESSAGE_SUBSCRIPTION: "MS",
                   ValueType.MESSAGE: "MESSAGE",
                   ValueType.MESSAGE_BATCH: "MESSAGE_BATCH"}

    class CaptureExporter(Exporter):
        def export(self, logged) -> None:
            record = logged.record
            value_type = record.value_type
            event = key = acked = None
            if record.is_event:
                value = record.value
                if (value_type == creation_type
                        and record.intent == ProcessInstanceCreationIntent.CREATED):
                    acked = value["processInstanceKey"]
                elif (value_type == job_type
                      and record.intent == JobIntent.COMPLETED):
                    acked = record.key
                if value_type == pi_type:
                    event = ("PI", record.intent.name, value["elementId"],
                             record.key, value["flowScopeKey"])
                elif value_type == job_type:
                    event = ("JOB", record.intent.name, value["elementId"],
                             value["type"], record.key,
                             value["elementInstanceKey"])
                elif value_type == var_type:
                    event = ("VAR", record.intent.name, value["name"],
                             value["value"])
                if event is not None:
                    key = value["processInstanceKey"]
                elif value_type in catch_types:
                    # after the three above, so that their records cost
                    # what they cost without it
                    event, key, acked = catch_event(record, value,
                                                    catch_types[value_type])
            kept = [] if event is None else [event]
            fault = observed.fault
            where = (record.partition_id, logged.position)
            if (event is not None and fault is not None
                    and _breakable(event, fault) and observed.broken(key)):
                if fault != "replica_export_differs" or where in observed.seen:
                    kept = _broken(event, fault)
            said = (int(record.record_type), int(value_type),
                    int(record.intent), record.key, tuple(kept))
            parked = (observed.parked_id is not None
                      and names_process(record, observed.parked_id))
            with observed.lock:
                first = observed.seen.setdefault(where, said)
                if first is not said:
                    observed.repeats += 1
                    observed.differing += first != said
                else:
                    observed.records += 1
                    observed.parked_records += parked
                    if acked is not None:
                        observed.position_of[acked] = logged.position
                    if kept:
                        observed.events.setdefault(key, []).extend(kept)
                        if (value_type == pi_type and record.key == key
                                and record.intent == PI.ELEMENT_COMPLETED):
                            observed.completed_at[key] = time.monotonic()
            self.controller.update_last_exported_position(logged.position)

    return CaptureExporter()


class CompileLedger:
    """Compile requests and seconds, from jax's own monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.last_at = time.monotonic()
        self.events: Counter = Counter()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += seconds
            self.last_at = time.monotonic()

    def _on_event(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1

    def report(self) -> dict:
        return {"compiles": self.compiles,
                "compile_seconds": round(self.seconds, 3),
                "persistent_cache_hits": self.events["cache_hits"],
                "persistent_cache_misses": self.events["cache_misses"]}


def hold_groups_on_the_accelerator():
    """The configuration's assumed router rule: 'the accelerator'. The
    default rule weighs the measured link against the host XLA backend and
    sends groups host-ward on the v5e (PERF.md, PR 22, item 3), so a default
    deployment may not drive the chip at all; ROADMAP D2 decides the rule."""
    from zeebe_tpu.utils.device_link import BackendRouter, install_shared_router

    class AcceleratorOnly(BackendRouter):
        def choose(self, bucket):
            return self.accel_device()

    router = AcceleratorOnly()
    install_shared_router(router)
    return router


def device_name(device) -> str:
    return f"{device.platform}:{device.id}"


class Served:
    def __init__(self, layout: dict, data_dir: Path, observed: Observed) -> None:
        from zeebe_tpu.broker.config import load_broker_cfg
        from zeebe_tpu.gateway import ClusterRuntime, Gateway

        self.partitions = int(layout["partitions"])
        self.data_dir = data_dir
        cfg = load_broker_cfg(overrides={
            "base.partition_count": self.partitions,
            "base.replication_factor": int(layout["replication_factor"]),
        })
        if not cfg.base.kernel_backend:
            raise RuntimeError("kernel backend is off in the config")
        self.gateway = None
        # absent: not passed, the program's own default (every group on one
        # device); present: the mesh runner shards groups over that many chips
        mesh = ({"kernel_mesh_shards": int(layout["kernel_mesh_shards"])}
                if "kernel_mesh_shards" in layout else {})
        self.runtime = ClusterRuntime(
            exporters_factory=lambda: {EXPORTER_ID: capture_exporter(observed)},
            kernel_backend=cfg.base.kernel_backend,
            broker_count=int(layout["brokers"]),
            partition_count=self.partitions,
            replication_factor=int(layout["replication_factor"]),
            directory=data_dir,
            backpressure_algorithm=cfg.backpressure.algorithm,
            backpressure_enabled=cfg.backpressure.enabled,
            disk_min_free_bytes=(cfg.disk.min_free_bytes
                                 if cfg.disk.enable_monitoring else 0),
            **mesh,
        )
        self.runtime.start()
        self.gateway = Gateway(self.runtime, bind="127.0.0.1:0")
        self.gateway.start()
        self.address = self.gateway.address

    def stop(self) -> None:
        """Idempotent. The data directory stays: the harness reads the
        replicas' logs from it and removes it."""
        if self.gateway is not None:
            self.gateway.stop()
            self.gateway = None
        if self.runtime is not None:
            self.runtime.stop()
            self.runtime = None

    def replicas(self, partition_id: int) -> list:
        return [b.partitions[partition_id]
                for b in self.runtime.brokers.values()
                if partition_id in b.partitions]

    def backends(self) -> list:
        """Every replica's kernel backend that exists (followers only replay,
        so theirs count nothing)."""
        out = []
        for pid in range(1, self.partitions + 1):
            for replica in self.replicas(pid):
                processor = getattr(replica, "processor", None)
                backend = getattr(processor, "kernel_backend", None)
                if backend is not None:
                    out.append(backend)
        return out

    def mesh_runner(self):
        for pid in range(1, self.partitions + 1):
            for replica in self.replicas(pid):
                runner = getattr(replica, "mesh_runner", None)
                if runner is not None:
                    return runner
        return None

    def counters(self) -> dict:
        """The program's own counts and host-clock stage sums, cumulative:
        the harness reads them at the window's two ends and subtracts."""
        from zeebe_tpu.utils.metrics import REGISTRY

        out: Counter = Counter()
        by_device: Counter = Counter()
        reasons: Counter = Counter()
        for b in self.backends():
            out["groups"] += b.groups_processed
            out["commands"] += b.commands_processed
            by_device.update({device_name(d): n
                              for d, n in b.groups_by_device.items()})
            reasons.update(b.fallback_reasons)
        for name, kind, _labels, value in REGISTRY.snapshot():
            if kind == "histogram" and "stream_processor_pipeline_" in name:
                stage = name.rsplit("_pipeline_", 1)[1]
                out[f"{stage}_count"] += value[0]
                out[f"{stage}_seconds"] += value[1]
            elif kind == "histogram" and name.endswith("snapshot_duration"):
                # a saturated partition snapshots inside a run, on its pump
                # thread: how many fell in the window and what they took
                out["snapshot_count"] += value[0]
                out["snapshot_seconds"] += value[1]
            elif kind == "histogram" and name.endswith(
                    "stream_processor_batch_processing_duration"):
                # a kernel group from its admission to its flush, or one
                # command on the sequential path: the transaction's commit is
                # inside it and inside no stage (the state store's own work
                # on every key a group wrote or deleted)
                out["group_count"] += value[0]
                out["group_seconds"] += value[1]
            elif kind == "histogram" and name.endswith(
                    "stream_processor_processing_duration"):
                # the sequential path alone (ActivateJobs, a deployment)
                out["sequential_count"] += value[0]
                out["sequential_seconds"] += value[1]
        # the first histogram observes both paths, the second the sequential
        # one: their difference is the kernel groups'
        out["group_count"] -= out["sequential_count"]
        out["group_seconds"] -= out["sequential_seconds"]
        runner = self.mesh_runner()
        if runner is not None:
            out["mesh_dispatches"] = runner.dispatches
            out["mesh_coalesced"] = runner.coalesced_dispatches
            out["mesh_groups"] = runner.groups_dispatched
        return {"counts": dict(out), "groups_by_device": dict(by_device),
                "failures": {r: n for r, n in reasons.items()
                             if r.split(":")[0] in DEVICE_FAILURES},
                "shard_devices": sorted(device_name(d)
                                        for d in runner.shard_devices)
                if runner is not None else []}

    def routing(self) -> Counter:
        """How the kernel backends routed commands so far, cumulative: each
        host-path reason (a head command's names its kind, as
        ``head-not-admittable:TIMER.TRIGGER``) and, by definition,
        ``kernel:<id>`` / ``host:<id>``, the commands that rode a kernel
        group and those that took the host path."""
        out: Counter = Counter()
        for b in self.backends():
            out.update(b.fallback_reasons)
            for definition, (kernel, host, *_rest) in (
                    b.accounting.per_definition.items()):
                out[f"kernel:{definition}"] += kernel
                out[f"host:{definition}"] += host
        return out

    def state_keys(self) -> int:
        """Committed keys in the largest partition's state, over all
        replicas: what a seeded deployment starts its window with."""
        return max(replica.db.key_count
                   for pid in range(1, self.partitions + 1)
                   for replica in self.replicas(pid))

    def state_keys_by_family(self) -> dict:
        """The same state's committed keys by column family."""
        return max((replica.db for pid in range(1, self.partitions + 1)
                    for replica in self.replicas(pid)),
                   key=lambda db: db.key_count).key_counts_by_cf()

    def parked_now(self, parked: dict) -> dict:
        """(partition, broker) -> ``parked_in`` over that replica's **running**
        state, under its partition's guard: what the window left in the store
        the timed path wrote to, read before anything is stopped."""
        out = {}
        for name, broker in self.runtime.brokers.items():
            for pid, replica in broker.partitions.items():
                with self.runtime._partition_guard(pid):
                    out[(pid, name)] = parked_in(replica.db, parked["id"],
                                                 parked["variables"])
        return out

    def forget_parked(self, parked: dict) -> int:
        """The fault: partition 1's leader forgets one parked instance."""
        with self.runtime._partition_guard(1):
            return forget_parked(self.runtime._leader_partition(1).db,
                                 parked["id"])

    def replay_debt(self) -> float:
        """The program's ``snapshot_replay_debt_records``, the largest of the
        partitions': records appended since a partition's last snapshot. Its
        scheduler snapshots (and compacts the log) once that debt threatens
        the recovery budget; it sets the gauge once a second."""
        from zeebe_tpu.utils.metrics import REGISTRY

        return max((value for name, kind, _labels, value in REGISTRY.snapshot()
                    if kind == "gauge"
                    and name.endswith("snapshot_replay_debt_records")),
                   default=0.0)

    def raft_marks(self) -> dict:
        """(partition, broker) -> the replica's commit index, read while the
        cluster still runs: up to the lowest of a partition's, every replica's
        log has to hold the same bytes."""
        return {(pid, name): replica.raft.commit_index
                for name, broker in self.runtime.brokers.items()
                for pid, replica in broker.partitions.items()}

    def elections(self) -> int:
        """Raft elections started so far, over all replicas (the program's
        ``raft_elections_total``)."""
        from zeebe_tpu.utils.metrics import REGISTRY

        return int(sum(value for name, kind, _labels, value in REGISTRY.snapshot()
                       if kind == "counter" and name.endswith("raft_elections_total")))


def parked_in(db, process_id: str, variables: dict | None = None) -> tuple:
    """(held, waiting): the instances of that process which a state holds (a
    running replica's, read under its partition's guard, or one a snapshot
    loads to), and those of them that wait as they were parked, row for row:
    every active child of the instance an element with a job and the
    instance's entry for it in the parent-child index, the job there,
    activatable and in its type's index of activatable jobs, and each of
    ``variables`` on the instance's scope at its value."""
    from zeebe_tpu.engine.engine_state import JOB_ACTIVATABLE
    from zeebe_tpu.state import ColumnFamilyCode as CF

    with db.transaction():
        roots, children = {}, {}
        for _key, row in db.column_family(CF.ELEMENT_INSTANCE_KEY).items():
            value = row["value"]
            if value["bpmnProcessId"] != process_id:
                continue
            if value["flowScopeKey"] < 0:
                roots[row["key"]] = row["activeChildren"]
            elif row["jobKey"] > 0:
                children.setdefault(value["flowScopeKey"], []).append(
                    (row["key"], row["jobKey"]))
        job_cf, states = db.column_family(CF.JOBS), db.column_family(CF.JOB_STATES)
        index = db.column_family(CF.JOB_ACTIVATABLE)
        family = db.column_family(CF.ELEMENT_INSTANCE_PARENT_CHILD)
        scoped = db.column_family(CF.VARIABLES)
        wanted = list((variables or {}).items())
        waiting = 0
        for instance, active in roots.items():
            mine = children.get(instance, ())
            waiting += active > 0 and len(mine) == active and all(
                family.exists((instance, child))
                and (job := job_cf.get((key,))) is not None
                and states.get((key,)) == JOB_ACTIVATABLE
                and index.exists((job["type"], job.get("tenantId", "<default>"),
                                  key))
                for child, key in mine) and all(
                scoped.get((instance, name)) == value for name, value in wanted)
    return len(roots), waiting


def forget_parked(db, process_id: str) -> int:
    """The fault ``forget_parked``: the first parked instance in key order
    leaves a running replica's state with every row of its own, as a state
    store that dropped them would leave it; the snapshot on the disk still
    has them. Called under the partition's guard. Returns the instance's
    key."""
    from zeebe_tpu.state import ColumnFamilyCode as CF
    from zeebe_tpu.state.db import decode_key

    with db.transaction():
        instances = db.column_family(CF.ELEMENT_INSTANCE_KEY)
        root = next(row["key"] for _key, row in instances.items()
                    if row["value"]["bpmnProcessId"] == process_id
                    and row["value"]["flowScopeKey"] < 0)
        family = db.column_family(CF.ELEMENT_INSTANCE_PARENT_CHILD)
        scoped = db.column_family(CF.VARIABLES)
        for key, _none in list(family.items((root,))):
            child = decode_key(key)[1][1]
            job_key = instances.get((child,))["jobKey"]
            job = db.column_family(CF.JOBS).get((job_key,))
            listed = (job["type"], job.get("tenantId", "<default>"), job_key)
            if db.column_family(CF.JOB_ACTIVATABLE).exists(listed):
                db.column_family(CF.JOB_ACTIVATABLE).delete(listed)
            db.column_family(CF.JOB_STATES).delete((job_key,))
            db.column_family(CF.JOBS).delete((job_key,))
            instances.delete((child,))
            family.delete((root, child))
        for key, _value in list(scoped.items((root,))):
            scoped.delete(decode_key(key)[1])
        instances.delete((root,))
    return root


def replica_logs(data_dir: Path, layout: dict, parked: dict | None = None) -> dict:
    """(partition, broker) -> what that replica's Raft log holds **on disk**,
    read once the cluster is stopped and with none of its memory: ``entries``
    (raft index -> the entry's bytes), ``created`` (the instance keys whose
    creation it holds), ``jobs_completed`` (the job keys whose completion
    it holds) and ``messages_published`` (the message keys whose publication
    it holds). The durability side of an acknowledgement. A saturated
    partition snapshots within a run and compacts its log behind the
    snapshot: ``snapshot_position`` is the processed position of the newest
    snapshot on the replica's disk that the program itself would recover
    from (0: none) and stands for the entries that the log no longer holds.
    It counts only if the program's own store finds its chain valid (every
    file against the CRC in its manifest, every delta's parent present) and
    ``load_chain_db`` builds a state from it; ``snapshot_written_at`` is
    when it was persisted (``time.time()``).

    ``parked`` (a seeded deployment: what ``seed_state.seed`` returned) adds
    what the replica's disk recovers to for the parked instances (the running
    state is ``Served.parked_now``'s to read, before the stop):
    ``parked_held`` and ``parked_waiting`` (``parked_in`` over the state that
    snapshot loads to; 0 where none loads) and ``parked_in_log``, the records
    behind the seed's last position that name the parked definition: with
    none, a replay of the log leaves the parked rows as the snapshot has
    them."""
    from zeebe_tpu.journal.journal import read_only_records
    from zeebe_tpu.logstreams.log_stream import _deserialize_batch
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import (JobIntent, MessageIntent,
                                           ProcessInstanceCreationIntent)
    from zeebe_tpu.protocol.msgpack import unpackb
    from zeebe_tpu.state.snapshot import FileBasedSnapshotStore, load_chain_db

    out = {}
    for b in range(int(layout["brokers"])):
        name = f"broker-{b}"
        for pid in range(1, int(layout["partitions"]) + 1):
            log_dir = data_dir / name / f"partition-{pid}" / "raft" / "raft-log"
            if not log_dir.is_dir():
                continue
            entries, created, jobs, published, in_log = {}, set(), set(), set(), 0
            for journal_record in read_only_records(log_dir):
                entry = unpackb(journal_record.data)
                data = entry.get("data")
                if not data:
                    continue
                entries[journal_record.index] = bytes(data)
                for logged in _deserialize_batch(data, pid):
                    record = logged.record
                    if (parked is not None
                            and logged.position > parked["end_position"][pid]
                            and names_process(record, parked["id"])):
                        in_log += 1
                    if not record.is_event:
                        continue
                    if (record.value_type == ValueType.PROCESS_INSTANCE_CREATION
                            and record.intent == ProcessInstanceCreationIntent.CREATED):
                        created.add(record.value["processInstanceKey"])
                    elif (record.value_type == ValueType.JOB
                          and record.intent == JobIntent.COMPLETED):
                        jobs.add(record.key)
                    elif (record.value_type == ValueType.MESSAGE
                          and record.intent == MessageIntent.PUBLISHED):
                        published.add(record.key)
            covered, written_at, db = 0, None, None
            store = data_dir / name / f"partition-{pid}" / "snapshots"
            if store.is_dir():
                # as a restart would: the store drops what its manifest does
                # not bear out and recovery takes the newest chain that loads
                chain = FileBasedSnapshotStore(store).latest_valid_chain()
                try:
                    if (chain is not None
                            and (db := load_chain_db(chain)) is not None):
                        covered = chain[-1].id.processed_position
                        written_at = chain[-1].path.stat().st_mtime
                except Exception:  # noqa: BLE001 — it does not load: not held
                    db = None
            held, waiting = ((0, 0) if parked is None or db is None
                             else parked_in(db, parked["id"], parked["variables"]))
            del db      # a seeded state is a million rows: one at a time
            out[(pid, name)] = {"entries": entries, "created": created,
                                "jobs_completed": jobs,
                                "messages_published": published,
                                "snapshot_position": covered,
                                "snapshot_written_at": written_at}
            if parked is not None:
                out[(pid, name)].update(parked_held=held,
                                        parked_waiting=waiting,
                                        parked_in_log=in_log)
    return out


def damage_snapshots(data_dir: Path) -> int:
    """The fault ``torn_snapshot``: once the cluster has stopped, every
    snapshot on every replica's disk loses the second half of its largest
    file, as a crash between the write and the fsync leaves it. What a log
    compacted behind such a snapshot no longer holds is then held by nothing.
    Returns the number of snapshots damaged."""
    damaged = 0
    for snapshot in data_dir.glob("broker-*/partition-*/snapshots/snapshots/*"):
        files = [p for p in snapshot.iterdir()
                 if p.is_file() and p.name != "CHECKSUM.sfv"]
        if files:
            victim = max(files, key=lambda p: p.stat().st_size)
            with victim.open("r+b") as f:
                f.truncate(victim.stat().st_size // 2)
            damaged += 1
    return damaged


def plant_lying_follower(observed: Observed, victim: str = "broker-2"):
    """The control in the program's own path: from the window's start one
    broker's Raft followers acknowledge every append **without storing it**,
    so acknowledgements rest on a quorum that does not hold them and that
    replica's log ends where the window began."""
    from zeebe_tpu.cluster.raft import RaftNode as Raft

    honest = Raft._on_append_request

    def lying(self, sender, req):
        if (observed.fault != "lying_follower" or self.member_id != victim
                or not req["entries"]):
            return honest(self, sender, req)
        claimed = req["entries"][-1]["index"]
        observed.lies += 1
        honest(self, sender, {**req, "entries": [],
                              "commit": min(req["commit"], self.commit_index)})
        self._send(sender, "append-resp", {
            "term": self.current_term, "success": True,
            "lastIndex": claimed, "follower": self.member_id})

    Raft._on_append_request = lying
