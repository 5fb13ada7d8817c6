"""BrokerClient + ClusterRuntime: the gateway's view of the broker cluster.

Reference: gateway/src/main/java/io/camunda/zeebe/gateway/impl/broker/
BrokerClient / BrokerRequestManager.java:40 — request/response correlation with
retries on leader-miss, partition selection (RequestDispatchStrategy round-robin,
PartitionIdIterator), BrokerTopologyManager fed by gossip.

``ClusterRuntime`` drives an in-process broker cluster on a background thread
(the brokers' actor loop equivalent): gRPC handler threads submit commands and
block on a response future; the pump thread advances raft/processing and
resolves futures from each broker's response sink."""

from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Any

from zeebe_tpu.broker import Broker, BrokerCfg
from zeebe_tpu.broker.broker import resolve_leader_partition
from zeebe_tpu.cluster.messaging import LoopbackNetwork
from zeebe_tpu.cluster.raft import JointFlusher
from zeebe_tpu.observability.profiler import (
    acquire_gc_timer,
    phase_annotation,
    release_gc_timer,
)
from zeebe_tpu.parallel.partitioning import subscription_partition_id
from zeebe_tpu.protocol import Record
from zeebe_tpu.protocol.keys import decode_partition_id
from zeebe_tpu.utils.metrics import REGISTRY

logger = logging.getLogger("zeebe_tpu.gateway.runtime")

DEPLOYMENT_PARTITION = 1


# a command's round trip through ClusterRuntime.submit, part by part, and the
# long-poll peek beside it: always on, named into the partition pipeline's
# family, which dashboards and the benchmark read side by side (ISSUE 37)
_RPC_BUCKETS = (0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.002, 0.003, 0.005,
                0.0075, 0.01, 0.015, 0.025, 0.05, 0.1, 1.0)
_RPC_PARTS = {
    "rpc_lock_wait": "submit entered to the partition's ownership lock "
                     "acquired on the attempt that writes (retries and their "
                     "sleeps included)",
    "rpc_write": "the submitting thread holding the partition's ownership "
                 "lock: limiter, serialise, raft append and whatever a quorum "
                 "of one does inline (fsync, commit, the stream's append)",
    "rpc_await": "the write's return to the resolving thread's stamp just "
                 "before it sets the waiter's event: replicate, admission, "
                 "the group or the sequential command, the deferred effects",
    "rpc_wake": "the resolving thread's stamp to the submitting thread "
                "running again: the futex wake and the interpreter lock's "
                "hand-over",
    "rpc": "the whole of ClusterRuntime.submit; the four parts sum to it "
           "request by request",
}


_M_RPC = tuple(
    REGISTRY.histogram(
        f"stream_processor_pipeline_{part}",
        f"seconds per command written and answered: {what}",
        ("partition",), buckets=_RPC_BUCKETS)
    for part, what in _RPC_PARTS.items())
_M_PEEK = REGISTRY.histogram(
    "stream_processor_pipeline_peek",
    "seconds inside ClusterRuntime.has_activatable_jobs per call that got "
    "the partition's ownership lock: the wait for it and the committed read "
    "(a long-poll's or the push dispatcher's peek, which writes nothing)",
    ("partition",), buckets=_RPC_BUCKETS)


class RequestTimeoutError(Exception):
    pass


class DeadlineExceededError(RequestTimeoutError):
    """The overall per-request deadline expired (bounded gateway resend
    loop, ``ZEEBE_GATEWAY_REQUEST_TIMEOUT_MS``): the request is abandoned
    with a typed error instead of retrying forever against a dead
    partition. Subclasses RequestTimeoutError so existing gRPC mappings
    (DEADLINE_EXCEEDED) and retry handlers keep working."""


class NoLeaderError(Exception):
    pass


class ResourceExhaustedError(Exception):
    pass


class _Waiter(threading.Event):
    """A pending request's event. The thread that resolves the request
    stamps ``resolved_at`` (``perf_counter()``) just before it sets the
    event, so that the waiter can tell the partition's time from its own
    wake-up."""

    resolved_at = 0.0


class GatewayRuntimeBase:
    """Shared request plumbing for gateway runtimes — in-process
    (:class:`ClusterRuntime`), one-broker-per-process TCP
    (:class:`~zeebe_tpu.gateway.tcp_runtime.TcpClusterRuntime`), and
    supervised per-core workers
    (:class:`~zeebe_tpu.multiproc.runtime.MultiProcClusterRuntime`): the
    nonce'd request-id sequence, the pending/response correlation table,
    and the partition-selection helpers."""

    def _init_jobstreams(self) -> None:
        """Jobs-available hub (long-poll wakeup) + push dispatcher (job
        streams); fed by the brokers' post-commit jobs-available side effect."""
        from zeebe_tpu.gateway.jobstream import JobNotificationHub, JobStreamDispatcher

        self.jobs_hub = JobNotificationHub()
        self.job_streams = JobStreamDispatcher(self)

    def _on_jobs_available(self, partition_id: int, job_types: set) -> None:
        self.jobs_hub.notify(job_types, partition_id)
        self.job_streams.on_jobs_available(partition_id, job_types)

    def job_pushed(self, job_key: int) -> float | None:
        """The push dispatcher put the job on a live client stream: the
        seconds since the job was made activatable, where this process holds
        its wait stamp (``stream/job_wait.py``). The stamps live with the
        partition's leader, so a runtime whose brokers are other processes
        has none and observes no ``job_push``."""
        return None

    def _init_requests(self) -> None:
        self._round_robin = itertools.count()
        # request ids carry a startup nonce in the high bits: a restarted
        # gateway must never resolve a backlog command's stale request_id
        # against a fresh in-flight request
        nonce = int(time.time() * 1000) & 0x3FFFFF
        self._request_seq = itertools.count((nonce << 32) + 1)
        self._pending: dict[int, threading.Event] = {}
        self._responses: dict[int, Record] = {}

    def _register_request(self) -> tuple[int, _Waiter]:
        request_id = next(self._request_seq)
        event = _Waiter()
        self._pending[request_id] = event
        return request_id, event

    def _resolve_request(self, request_id: int, record: Record) -> None:
        event = self._pending.get(request_id)
        if event is not None:
            self._responses[request_id] = record
            # the resolving thread's own stamp, kept on the waiter beside
            # the response: what follows it is the wake-up, not the partition
            event.resolved_at = time.perf_counter()
            event.set()

    def _take_response(self, request_id: int, event: threading.Event,
                       deadline: float, partition_id: int, timeout_s: float) -> Record:
        try:
            if not event.wait(max(deadline - time.time(), 0.001)):
                raise RequestTimeoutError(
                    f"partition {partition_id} did not respond in {timeout_s}s"
                )
            return self._responses.pop(request_id)
        finally:
            self._pending.pop(request_id, None)
            self._responses.pop(request_id, None)

    def partition_for_new_instance(self) -> int:
        return next(self._round_robin) % self.partition_count + 1

    def partition_for_correlation_key(self, key: str) -> int:
        return subscription_partition_id(key, self.partition_count)

    @staticmethod
    def partition_for_key(key: int) -> int:
        return decode_partition_id(key)


class ClusterRuntime(GatewayRuntimeBase):
    """Owns N in-process brokers and the pump thread; thread-safe ingress."""

    def __init__(self, broker_count: int = 1, partition_count: int = 1,
                 replication_factor: int = 1, directory=None,
                 exporters_factory=None,
                 backpressure_algorithm: str = "vegas",
                 backpressure_enabled: bool = True,
                 disk_min_free_bytes: int = 0,
                 backup_store_directory=None,
                 backup_store=None,
                 kernel_backend: bool = True,
                 kernel_mesh_shards: int = 0) -> None:
        self.partition_count = partition_count
        self.net = LoopbackNetwork(lanes=partition_count)
        self._lock = threading.RLock()
        # per-partition ownership locks: partition p's replicas (across all
        # brokers) advance only under _plocks[p] — the single-writer
        # guarantee the reference gets from partition actors, here extended
        # so one partition's slow step (a kernel compile) no longer stalls
        # the other partitions' raft heartbeats and processing
        self._plocks = {p: threading.RLock()
                        for p in range(1, partition_count + 1)}
        # (lock_wait, write, await, wake, rpc) by partition; sixteen gateway
        # threads and the pushers observe into them, so under a lock of
        # their own (a histogram's sum and count are read-modify-write)
        self._m_rpc = {p: tuple(h.labels(str(p)) for h in _M_RPC)
                       for p in self._plocks}
        self._m_rpc_lock = threading.Lock()
        self._m_peek = {p: _M_PEEK.labels(str(p)) for p in self._plocks}
        self._gc_lease = None
        self._init_requests()
        self._init_jobstreams()
        members = [f"broker-{i}" for i in range(broker_count)]
        self.brokers: dict[str, Broker] = {}
        # one mesh per process: every in-process broker's partitions submit
        # kernel groups to the SAME runner, so the whole cluster's batch
        # coalesces onto one device mesh (partition = shard, SURVEY §2.13)
        self.mesh_runner = None
        if kernel_mesh_shards > 0 and kernel_backend:
            from zeebe_tpu.parallel.mesh_runner import MeshKernelRunner

            self.mesh_runner = MeshKernelRunner(n_shards=kernel_mesh_shards)
        from pathlib import Path

        for m in members:
            cfg = BrokerCfg(node_id=m, partition_count=partition_count,
                            replication_factor=replication_factor,
                            cluster_members=members,
                            kernel_backend=kernel_backend)
            self.brokers[m] = Broker(
                cfg, self.net.join(m),
                directory=(Path(directory) / m if directory else None),
                exporters_factory=exporters_factory,
                response_sink=self._resolve,
                backpressure_algorithm=backpressure_algorithm,
                backpressure_enabled=backpressure_enabled,
                disk_min_free_bytes=disk_min_free_bytes,
                backup_store_directory=backup_store_directory,
                backup_store=backup_store,
                mesh_runner=self.mesh_runner,
            )
            self.brokers[m].jobs_listener = self._on_jobs_available
            # topology-driven partition add/remove must hold the partition's
            # ownership lock so lifecycle never races that partition's pump
            self.brokers[m].partition_guard = self._partition_guard
        self._running = False
        self._threads: list[threading.Thread] = []

    def _partition_guard(self, partition_id: int):
        import contextlib

        lock = self._plocks.get(partition_id)
        return lock if lock is not None else contextlib.nullcontext()

    # -- pump thread -----------------------------------------------------------

    def start(self) -> None:
        self._running = True
        # one ownership thread per partition + one control thread (membership,
        # topology, gossip, observability) — the reference's partition actors,
        # as threads over the same single-writer discipline
        self._threads = [
            threading.Thread(target=self._run_partition, args=(pid,),
                             daemon=True, name=f"partition-{pid}")
            for pid in range(1, self.partition_count + 1)
        ]
        self._threads.append(
            threading.Thread(target=self._run_control, daemon=True,
                             name="cluster-control")
        )
        for t in self._threads:
            t.start()
        self._gc_lease = acquire_gc_timer()
        self.job_streams.start()
        self.await_leaders()

    def _pump_brokers(self, pump, logged: set) -> None:
        # one broker's pump failure (e.g. crashed/closed but still listed)
        # must not kill the thread that drives every other broker: keep
        # pumping the rest and retry the failed one each tick (a transient
        # cause — momentary disk pressure, a mid-transition race — recovers
        # by itself); the traceback is logged once per failure streak
        for name, broker in list(self.brokers.items()):
            try:
                pump(broker)
                logged.discard(name)
            except Exception:  # noqa: BLE001
                if name not in logged:
                    logged.add(name)
                    logger.exception("broker %s pump failed; retrying "
                                     "(logged once per streak)", name)

    def _run_partition(self, pid: int) -> None:
        logged: set[str] = set()
        # this partition's replicas share this thread, so their durability
        # barriers are taken here, together (cluster/raft.py: JointFlusher):
        # a turn settles what gateway threads appended since the last one,
        # pumps, and settles what the pump appended
        flusher = JointFlusher(f"partition-{pid}")
        try:
            while self._running:
                with self._plocks[pid]:
                    moved = self._settle(pid, flusher)
                    self._pump_brokers(lambda b: b.pump_partition(pid), logged)
                    moved += self._settle(pid, flusher)
                if moved == 0:
                    time.sleep(0.001)
        finally:
            flusher.close()

    def _settle(self, pid: int, flusher: JointFlusher) -> int:
        """Deliver the partition's messages (a follower that took entries
        appends them and holds its answer), sync every dirty Raft journal of
        the partition at once, release the acknowledgements, and deliver
        those to the leader, which commits. Returns the messages moved."""
        try:
            moved = self.net.deliver_lane(pid)
            nodes = [p.raft for b in self.brokers.values()
                     if (p := b.partitions.get(pid)) is not None]
            if flusher.flush(nodes):
                moved += self.net.deliver_lane(pid)
        except Exception:  # noqa: BLE001 — deliver_one already guards
            # handler errors; this guards queue-level corruption
            logger.exception("partition %s delivery failed", pid)
            moved = 0
        return moved

    def _run_control(self) -> None:
        logged: set[str] = set()
        while self._running:
            with self._lock:
                self._pump_brokers(lambda b: b.pump_control(), logged)
                try:
                    moved = self.net.deliver_lane(0)
                except Exception:  # noqa: BLE001
                    logger.exception("control delivery failed")
                    moved = 0
            if moved == 0:
                time.sleep(0.001)

    def stop(self) -> None:
        self.job_streams.stop()
        self._running = False
        for t in getattr(self, "_threads", []):
            t.join(timeout=5)
        release_gc_timer(self._gc_lease)
        self._gc_lease = None
        with self._lock:
            for broker in self.brokers.values():
                broker.close()

    def await_leaders(self, timeout_s: float = 30.0) -> None:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            # lock-free role reads: leadership claims are plain attributes
            # maintained by the partition threads
            ready = all(
                self._leader_partition(p) is not None
                for p in range(1, self.partition_count + 1)
            )
            if ready:
                return
            time.sleep(0.01)
        raise RuntimeError("partition leaders not elected in time")

    # -- topology --------------------------------------------------------------

    def _leader_partition(self, partition_id: int):
        return resolve_leader_partition(self.brokers.values(), partition_id)

    def topology(self) -> dict:
        with self._lock:
            return {
                "clusterSize": len(self.brokers),
                "partitionsCount": self.partition_count,
                "replicationFactor": next(iter(self.brokers.values())).cfg.replication_factor,
                "brokers": [b.health() for b in self.brokers.values()],
            }

    def cluster_status(self) -> dict:
        """Cluster-wide health/alert/rate aggregation for the management
        ``GET /cluster/status`` and ``zbctl top`` — the in-process fan-out
        over every hosted broker (reference analog: the gateway's topology
        view, widened with the metrics plane)."""
        from zeebe_tpu.broker.management import cluster_status

        # lock-free reads: broker_status only touches plain attributes and
        # the thread-safe time-series store, so a stalled partition thread
        # cannot wedge the status endpoint behind the control lock
        status = cluster_status(list(self.brokers.values()))
        status["partitionsCount"] = self.partition_count
        return status

    # -- partition selection ---------------------------------------------------

    def has_activatable_jobs(self, partition_id: int, job_type: str,
                             tenant_ids: list[str] | None = None
                             ) -> bool | None:
        """Long-poll peek: checks the leader's state without writing a
        JOB_BATCH ACTIVATE into the replicated log (reference:
        LongPollingActivateJobsHandler parks requests until jobsAvailable).
        ``tenant_ids`` keeps a tenant-filtered long-poll from flooding the log
        with empty activations when only other tenants' jobs exist. None
        where it cannot tell: an unknown partition, one without a leader, or
        one whose ownership thread is stalled."""
        entered = time.perf_counter()
        lock = self._plocks.get(partition_id)
        if lock is None or not lock.acquire(timeout=1.0):
            # long-polls and the push dispatcher both retry
            return None
        try:
            leader = self._leader_partition(partition_id)
            if leader is None or leader.db is None:
                return None
            # committed-read discipline: long-poll peeks run off the pump
            # thread — read the committed activatable index, never the
            # processing-owned transaction slot (zlint caught the old
            # `with leader.db.transaction()` here racing processing)
            from zeebe_tpu.engine.engine_state import JobState

            return JobState.any_activatable_committed(
                leader.db, job_type, tenant_ids)
        finally:
            # still under the partition's lock: one observer at a time
            self._m_peek[partition_id].observe(time.perf_counter() - entered)
            lock.release()

    def job_pushed(self, job_key: int) -> float | None:
        leader = self._leader_partition(self.partition_for_key(job_key))
        processor = getattr(leader, "processor", None)
        return None if processor is None else processor.job_stamps.pushed(job_key)

    # -- request path ----------------------------------------------------------

    def submit(self, partition_id: int, record: Record,
               timeout_s: float = 10.0) -> Record:
        """Write a command to the partition leader, await the engine response
        (retrying on leader miss — RequestRetryHandler semantics). Mints the
        trace's ROOT span: ``client_write`` returns the command's assigned
        stream position, which IS the trace id the broker-side spans
        (processing, export) key on — the gateway request joins its causal
        tree with no extra wire fields.

        The round trip is stamped five times with ``perf_counter()``: ``t0``
        (entered), ``t_locked`` (the partition's ownership lock acquired on
        the attempt that writes), ``t_written`` (``client_write`` returned a
        position; the lock goes right after), ``t_resolved`` (taken by the
        resolving thread in ``_resolve_request`` just before it sets the
        event) and ``t_woken`` (this thread runs again). A request written
        and answered observes the four intervals and their whole into the
        ``stream_processor_pipeline_rpc*`` histograms; the first three are
        also ``zeebe.kernel_chunk.rpc_*`` annotations for a device capture,
        and with the tracer on the first and the last are the spans
        ``gateway.lock_wait`` and ``gateway.reply``."""
        from zeebe_tpu.broker.partition import BackpressureExceeded
        from zeebe_tpu.observability.tracer import get_tracer

        t0 = time.perf_counter()
        request_id, event = self._register_request()
        rec = record.replace(request_id=request_id, request_stream_id=0)
        deadline = time.time() + timeout_s
        written = False
        command_position = -1
        t_locked = t_written = 0.0
        lock = self._plocks.get(partition_id)
        if lock is None:
            # a stale/crafted key can decode to a partition this cluster
            # never had — the same UNAVAILABLE surface as a leaderless one
            self._pending.pop(request_id, None)
            raise NoLeaderError(f"unknown partition {partition_id}")
        while time.time() < deadline:
            # bounded acquire: a stalled partition (held ownership lock) must
            # time this request out, not block the gRPC handler forever
            with phase_annotation("rpc_lock_wait"):
                locked = lock.acquire(timeout=0.05)
            if locked:
                t_locked = time.perf_counter()
                try:
                    leader = self._leader_partition(partition_id)
                    if leader is not None:
                        try:
                            with phase_annotation("rpc_write"):
                                position = leader.client_write(rec)
                            if position is not None:
                                t_written = time.perf_counter()
                                written = True
                                command_position = position
                        except BackpressureExceeded as exc:
                            self._pending.pop(request_id, None)
                            raise ResourceExhaustedError(str(exc)) from exc
                finally:
                    lock.release()
            if written:
                break
            with phase_annotation("rpc_lock_wait"):
                time.sleep(0.01)
        if not written:
            self._pending.pop(request_id, None)
            raise NoLeaderError(f"no leader for partition {partition_id}")
        with phase_annotation("rpc_await"):
            response = self._take_response(request_id, event, deadline,
                                           partition_id, timeout_s)
        t_woken = time.perf_counter()
        t_resolved = event.resolved_at
        lock_wait, write, awaited, wake, whole = self._m_rpc[partition_id]
        with self._m_rpc_lock:
            lock_wait.observe(t_locked - t0)
            write.observe(t_written - t_locked)
            awaited.observe(t_resolved - t_written)
            wake.observe(t_woken - t_resolved)
            whole.observe(t_woken - t0)
        tracer = get_tracer()
        if tracer.enabled:
            self._trace_request(
                tracer, partition_id, command_position, request_id, record,
                response, (t0, t_locked, t_written, t_resolved, t_woken))
        return response

    @staticmethod
    def _trace_request(tracer, partition_id: int, position: int,
                       request_id: int, record: Record, response: Record,
                       stamps: tuple) -> None:
        """Tracer on: the root span ``gateway.request`` over the whole of
        ``submit`` and, under it at their real intervals, ``gateway.lock_wait``
        and ``gateway.reply`` (``broker.backpressure_acquire`` and
        ``broker.command_append`` already cover the write; what the partition's
        own spans leave uncovered between them and the reply is nobody's)."""
        from zeebe_tpu.observability.span import now_us
        from zeebe_tpu.observability.tracer import instance_attrs

        t0, t_locked, _t_written, t_resolved, t_woken = stamps
        tracer.observe_ack("gateway", t_woken - t0)
        trace_id = f"{partition_id}:{position}"
        if not tracer.sampled(trace_id):
            return
        attrs = {"position": position, "requestId": request_id,
                 "valueType": record.value_type.name,
                 "intent": record.intent.name}
        if response.is_rejection:
            attrs["rejection"] = response.rejection_type.name
        else:
            attrs.update(instance_attrs(response.value))
        # spans carry the wall clock, the stamps perf_counter: one pair read
        # now places them all
        wall_us, perf = now_us(), time.perf_counter()

        def at(stamp: float) -> int:
            return wall_us - int((perf - stamp) * 1e6)

        tracer.emit(trace_id, "gateway.request", t_woken - t0, partition_id,
                    attrs=attrs, start_us=at(t0))
        tracer.emit(trace_id, "gateway.lock_wait", t_locked - t0,
                    partition_id, parent="gateway.request", start_us=at(t0))
        tracer.emit(trace_id, "gateway.reply", t_woken - t_resolved,
                    partition_id, parent="gateway.request",
                    start_us=at(t_resolved))

    def _resolve(self, response) -> None:
        self._resolve_request(response.request_id, response.record)


