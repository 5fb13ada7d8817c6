"""Job push + jobs-available notifications: the gateway side of job streaming.

Reference: transport/stream/impl/ (AddStream/RemoveStream/PushStream message
flow between gateway ClientStreamManager.java:24 and the broker
RemoteStreamRegistry), broker jobstream/RemoteJobStreamer.java:19 (engine
side-effect push on job CREATED via BpmnJobActivationBehavior.java:39), and
gateway impl/job/LongPollingActivateJobsHandler.java:36 (parked long-polls
woken by a "jobsAvailable" notification instead of polling).

Design (tpu-native runtime): processing emits a post-commit jobs-available
side effect (stream/processor.py on_jobs_available) that lands here. The
``JobNotificationHub`` wakes one parked ActivateJobs long-poll a
notification and tenant filter, first parked first woken; the
``JobStreamDispatcher`` owns the registered client streams and, on
notification, writes a JOB_BATCH ACTIVATE through the normal command path and
delivers the activated jobs to a registered stream — so the record log is
byte-identical to pull activation and replay/exporters see nothing special.
(The design not taken is upstream's: activation inside the step that creates
the job, no second command and no second Raft round; ROADMAP queues it.)
Jobs pushed at a stream that died before delivery are handed back with
JOB YIELD (reference: YieldingJobStreamErrorHandler).

The activation is a command that blocks for its whole round trip (Raft and
the fsync), so every partition has a pusher thread of its own, started with
the partition's first notification: a push for one partition never waits for
another partition's commit, nor for its election or its ``submit`` timeout.
A partition's pusher takes one ``(partition, job type)`` at a time, so at
most one activation is in flight a partition. A delivery that lands on a live
stream closes the job's wait stamp (``stream/job_wait.py``: histogram
``stream_processor_pipeline_job_push``) and, with the tracer on, emits the
span ``jobstream.push`` at its real interval."""

from __future__ import annotations

import collections
import itertools
import logging
import queue
import threading
import time
from dataclasses import dataclass, field

from zeebe_tpu.protocol import DEFAULT_TENANT, ValueType, command
from zeebe_tpu.protocol.intent import JobBatchIntent, JobIntent

logger = logging.getLogger("zeebe_tpu.gateway.jobstream")

PUSH_BATCH_SIZE = 32


class PollWaiter:
    """One long-poll's place in the queue of its job type and tenant filter,
    for the whole call: ``cancel`` (the call's end) takes it out of the queue
    at once, so that no notification is handed to a client that went away."""

    __slots__ = ("_hub", "job_type", "tenants", "_event", "_wake", "_queued",
                 "cancelled")

    def __init__(self, hub: "JobNotificationHub", job_type: str,
                 tenants: tuple) -> None:
        self._hub = hub
        self.job_type = job_type
        self.tenants = tenants
        self._event = threading.Event()
        self._wake: int | None = None   # the partition handed, not yet taken
        self._queued = False
        self.cancelled = False

    def cancel(self) -> None:
        self._hub._cancel(self)


class JobNotificationHub:
    """Per job type and tenant filter, the parked long-polls in arrival
    order. A jobs-available notification names a job type and a partition,
    not a tenant: it wakes the first poll of each tenant filter parked on the
    type (one poll where every poll of the type asks for the same tenants)
    and tells it which partition notified (reference:
    LongPollingActivateJobsHandler hands the notification to the type's next
    pending request). A notification is also kept, one a partition, for a
    filter that had no poll parked: a poll reads the type's version before it
    peeks and, when it parks, takes a kept notification that is newer than
    its peek and was handed to no poll of its filter, instead of sleeping."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._versions: dict[str, int] = {}
        # job type -> tenant filter -> parked polls, first parked first
        self._parked: dict[str, dict[tuple, collections.deque[PollWaiter]]] = {}
        # job type -> (partition, the filter it is for; None: every filter)
        # -> (the version it made, the filters it was handed to)
        self._kept: dict[str, dict[tuple, tuple[int, set]]] = {}

    def notify(self, job_types: set, partition_id: int) -> None:
        """``partition_id``: the partition whose step made the jobs
        activatable."""
        with self._lock:
            for job_type in job_types:
                self._hand(job_type, partition_id, None)

    def hand_on(self, job_type: str, tenants: tuple, partition_id: int) -> None:
        """A poll handed a notification that leaves the partition's jobs to
        the next poll of its filter (its room is full, or its call ended)
        passes the notification on."""
        with self._lock:
            self._hand(job_type, partition_id, tenants)

    def _hand(self, job_type: str, partition_id: int,
              tenants: tuple | None) -> None:
        version = self._versions[job_type] = self._versions.get(job_type, 0) + 1
        queues = self._parked.get(job_type, {})
        handed = set()
        for filt in (tuple(queues) if tenants is None else (tenants,)):
            if filt in queues:
                waiter = self._unqueue(queues, filt)
                waiter._wake = partition_id
                waiter._event.set()
                handed.add(filt)
        self._kept.setdefault(job_type, {})[partition_id, tenants] = (
            version, handed)

    def version(self, job_type: str) -> int:
        with self._lock:
            return self._versions.get(job_type, 0)

    def waiter(self, job_type: str, tenants: tuple) -> PollWaiter:
        return PollWaiter(self, job_type, tenants)

    def wait(self, waiter: PollWaiter, seen_version: int, timeout_s: float,
             front: bool = False) -> int | None:
        """Park until a notification of the waiter's type is handed to it,
        and return the partition that notified; None when the
        timeout passes or the waiter is cancelled first. ``seen_version``:
        the type's version read before the poll's peek, which saw every
        notification up to it. ``front``: a poll that was handed a
        notification and found nothing goes back to the head of the queue."""
        job_type, tenants = waiter.job_type, waiter.tenants
        with self._lock:
            if waiter.cancelled:
                return None
            kept = sorted(self._kept.get(job_type, {}).items(),
                          key=lambda item: item[1][0])
            for (partition_id, filt), (version, handed) in kept:
                if (version > seen_version and filt in (None, tenants)
                        and tenants not in handed):
                    handed.add(tenants)
                    return partition_id
            queues = self._parked.setdefault(job_type, {})
            parked = queues.setdefault(tenants, collections.deque())
            if front:
                parked.appendleft(waiter)
            else:
                parked.append(waiter)
            waiter._queued = True
        waiter._event.wait(timeout_s)
        with self._lock:
            waiter._event.clear()
            partition_id, waiter._wake = waiter._wake, None
            if waiter._queued:      # timed out (or cancelled) while parked
                self._unqueue(self._parked[job_type], tenants, waiter)
            return partition_id

    def _cancel(self, waiter: PollWaiter) -> None:
        with self._lock:
            waiter.cancelled = True
            if waiter._queued:
                self._unqueue(self._parked[waiter.job_type], waiter.tenants,
                              waiter)
            if waiter._wake is not None:
                # handed a notification it will not act on: the next one has it
                self._hand(waiter.job_type, waiter._wake, waiter.tenants)
                waiter._wake = None
            waiter._event.set()

    @staticmethod
    def _unqueue(queues: dict, tenants: tuple,
                 waiter: PollWaiter | None = None) -> PollWaiter:
        """Take ``waiter`` (None: the first) out of its filter's queue."""
        parked = queues[tenants]
        if waiter is None:
            waiter = parked.popleft()
        else:
            parked.remove(waiter)
        if not parked:
            del queues[tenants]
        waiter._queued = False
        return waiter


@dataclass
class ClientJobStream:
    """One StreamActivatedJobs call's registration (ClientStream equivalent)."""

    stream_id: int
    job_type: str
    worker: str
    timeout_ms: int
    #: (job key, job) as activated; None is the gateway's end-of-call marker
    jobs: "queue.Queue[tuple[int, dict] | None]" = field(default_factory=queue.Queue)
    closed: bool = False
    tenant_ids: list | None = None  # authorized-tenant filter (None = default)


from time import perf_counter as _perf_counter

from zeebe_tpu.observability.tracer import get_tracer, instance_attrs
from zeebe_tpu.utils.metrics import REGISTRY as _REG

# job-stream registry metrics (reference: transport/stream metrics — clients,
# servers, streams, aggregated_stream_clients; broker jobstream metrics —
# broker_open_job_stream_count, broker_jobs_pushed_count,
# broker_jobs_push_fail_count, push)
_M_STREAMS = _REG.gauge(
    "streams", "open job streams in the registry").labels()
_M_CLIENTS = _REG.gauge(
    "clients", "connected stream clients").labels()
_M_SERVERS = _REG.gauge(
    "servers", "stream servers (one per dispatcher)").labels()
_M_AGG_CLIENTS = _REG.gauge(
    "aggregated_stream_clients",
    "clients aggregated over logically equal streams").labels()
_M_OPEN_STREAMS = _REG.gauge(
    "broker_open_job_stream_count", "open job streams, broker view").labels()
_M_PUSHED = _REG.counter(
    "broker_jobs_pushed_count", "jobs pushed to client streams").labels()
_M_PUSH_FAIL = _REG.counter(
    "broker_jobs_push_fail_count",
    "jobs that failed delivery and were re-routed/yielded").labels()
_M_PUSH_LATENCY = _REG.histogram(
    "push", "seconds per pushed job delivery").labels()


class JobStreamDispatcher:
    """RemoteStreamRegistry + RemoteJobStreamer, runtime-side: registered
    client streams per job type and, a partition, a pusher thread turning
    notifications into JOB_BATCH ACTIVATE commands whose jobs feed the
    streams."""

    def __init__(self, runtime) -> None:
        # runtime surface used: submit, partition_for_key, partition_count,
        # has_activatable_jobs, job_pushed
        self.runtime = runtime
        self._ids = itertools.count(1)
        self._lock = threading.RLock()
        self._streams: dict[str, list[ClientJobStream]] = {}
        self._rr: dict[str, int] = {}
        self._pending: set[tuple[int, str]] = set()
        # partition -> its pusher's thread and the condition (over the
        # registry's lock) that wakes it alone
        self._pushers: dict[int, tuple[threading.Thread, threading.Condition]] = {}
        self._running = False

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        self._running = True
        _M_SERVERS.inc()
        with self._lock:    # what was armed before the start
            for partition_id in {k[0] for k in self._pending}:
                self._arm(partition_id, ())

    def stop(self) -> None:
        self._running = False
        _M_SERVERS.dec()
        with self._lock:
            pushers = list(self._pushers.values())
            self._pushers.clear()
            for _thread, wake in pushers:
                wake.notify_all()
        deadline = time.monotonic() + 5
        for thread, _wake in pushers:
            thread.join(timeout=max(0.0, deadline - time.monotonic()))

    def _arm(self, partition_id: int, job_types) -> None:
        """Under the registry lock: the partition's pusher has these types to
        push (a burst of notifications is one entry a type)."""
        self._pending.update((partition_id, t) for t in job_types)
        pusher = self._pushers.get(partition_id)
        if pusher is not None:
            pusher[1].notify()
        elif self._running:
            wake = threading.Condition(self._lock)
            thread = threading.Thread(
                target=self._run, args=(partition_id, wake), daemon=True,
                name=f"job-stream-pusher-{partition_id}")
            self._pushers[partition_id] = (thread, wake)
            thread.start()

    # -- stream registry (AddStream / RemoveStream) ----------------------------

    def add_stream(self, job_type: str, worker: str, timeout_ms: int,
                   tenant_ids: list | None = None) -> ClientJobStream:
        stream = ClientJobStream(next(self._ids), job_type, worker, timeout_ms,
                                 tenant_ids=tenant_ids)
        for g in (_M_STREAMS, _M_CLIENTS, _M_AGG_CLIENTS, _M_OPEN_STREAMS):
            g.inc()
        with self._lock:
            self._streams.setdefault(job_type, []).append(stream)
            # initial sweep: jobs that became activatable before the stream
            # existed must still be pushed (reference: broker re-notifies
            # streams on registration)
            for partition_id in range(1, self.runtime.partition_count + 1):
                self._arm(partition_id, (job_type,))
        return stream

    def remove_stream(self, stream: ClientJobStream,
                      in_flight: tuple[int, dict] | None = None) -> None:
        """Unregister; undelivered jobs (queued or the one being yielded to a
        now-dead client) go to another stream or back to the activatable
        queue via JOB YIELD. Drain happens under the registry lock, mutually
        exclusive with ``_deliver`` — a job can never land in the queue after
        the drain."""
        leftovers = [] if in_flight is None else [in_flight]
        with self._lock:
            stream.closed = True
            streams = self._streams.get(stream.job_type, [])
            if stream in streams:
                streams.remove(stream)
                for g in (_M_STREAMS, _M_CLIENTS, _M_AGG_CLIENTS,
                          _M_OPEN_STREAMS):
                    g.dec()
            if not streams:
                self._streams.pop(stream.job_type, None)
            while True:
                try:
                    left = stream.jobs.get_nowait()
                except queue.Empty:
                    break
                if left is not None:    # None: the gateway's end-of-call marker
                    leftovers.append(left)
        for key, job in leftovers:
            if not self._redeliver(stream.job_type, key, job):
                self._yield_back(key)

    def has_streams(self, job_type: str) -> bool:
        with self._lock:
            return bool(self._streams.get(job_type))

    # -- notification ingress --------------------------------------------------

    def on_jobs_available(self, partition_id: int, job_types: set) -> None:
        with self._lock:
            armed = {t for t in job_types if self._streams.get(t)}
            if armed:
                self._arm(partition_id, armed)

    # -- dispatcher ------------------------------------------------------------

    def _run(self, partition_id: int, wake: threading.Condition) -> None:
        """One partition's pusher: its pending job types one after another.
        Whatever blocks here — the activation's commit, a partition without a
        leader, a ``submit`` that times out — blocks this partition alone."""
        while self._running:
            with self._lock:
                while True:
                    if not self._running:
                        return
                    key = next((k for k in self._pending
                                if k[0] == partition_id), None)
                    if key is not None:
                        break
                    wake.wait(0.5)
                self._pending.discard(key)
            job_type = key[1]
            try:
                self._push(partition_id, job_type)
            except Exception:  # noqa: BLE001 — a failed push must not kill the loop
                logger.exception(
                    "job push failed (partition %s, type %r)", partition_id, job_type
                )
                # the jobs are still activatable and no fresh notification will
                # fire for them: re-arm and back off (CommandRedistributor-style
                # retry-forever; backpressure/no-leader conditions clear)
                with self._lock:
                    if self._streams.get(job_type):
                        self._pending.add(key)
                time.sleep(0.05)

    @staticmethod
    def _tenant_group(stream: ClientJobStream) -> tuple:
        return tuple(sorted(stream.tenant_ids or [DEFAULT_TENANT]))

    def _tenant_groups(self, job_type: str) -> list[tuple]:
        """Distinct tenant filters across the type's streams: each group is
        pushed separately so one tenant's empty activation cannot starve
        another's (streams of different tenants see different job sets)."""
        with self._lock:
            return sorted({
                self._tenant_group(s) for s in self._streams.get(job_type, ())
            })

    def _pick_stream(self, job_type: str,
                     group: tuple | None = None) -> ClientJobStream | None:
        with self._lock:
            streams = self._streams.get(job_type)
            if streams and group is not None:
                streams = [s for s in streams if self._tenant_group(s) == group]
            if not streams:
                return None
            rr_key = (job_type, group)
            idx = self._rr.get(rr_key, 0) % len(streams)
            self._rr[rr_key] = idx + 1
            return streams[idx]

    def _push(self, partition_id: int, job_type: str) -> None:
        """Activate-and-deliver, per tenant-filter group, until the partition
        has no more activatable jobs each group can see or every stream is
        gone."""
        while self._running:
            progressed = False
            for group in self._tenant_groups(job_type):
                stream = self._pick_stream(job_type, group)
                if stream is None:
                    continue
                if not self.runtime.has_activatable_jobs(
                        partition_id, job_type, list(group)):
                    continue
                record = self.runtime.submit(
                    partition_id,
                    command(ValueType.JOB_BATCH, JobBatchIntent.ACTIVATE, {
                        "type": job_type,
                        "worker": stream.worker,
                        "timeout": stream.timeout_ms,
                        "maxJobsToActivate": PUSH_BATCH_SIZE,
                        **({"tenantIds": stream.tenant_ids}
                           if stream.tenant_ids else {}),
                    }),
                )
                if record.is_rejection:
                    continue
                keys = record.value.get("jobKeys", [])
                jobs = record.value.get("jobs", [])
                activation = record.source_record_position
                for key, job in zip(keys, jobs):
                    _t0 = _perf_counter()
                    if self._deliver(stream, key, job, activation):
                        _M_PUSHED.inc()
                        _M_PUSH_LATENCY.observe(_perf_counter() - _t0)
                    else:
                        _M_PUSH_FAIL.inc()
                        if not self._redeliver(job_type, key, job, activation):
                            self._yield_back(key)
                if len(keys) >= PUSH_BATCH_SIZE:
                    progressed = True  # this group may have more to drain
            if not progressed:
                return

    def _deliver(self, stream: ClientJobStream, key: int, job: dict,
                 activation: int = -1) -> bool:
        """Enqueue under the registry lock so the closed-check and the put are
        atomic against remove_stream's drain. ``activation``: the position of
        the command that activated the job, where the caller has it."""
        with self._lock:
            if stream.closed:
                return False
            stream.jobs.put((key, job))
        # the job is on a live stream: its wait for a worker is over. A job
        # moved on from a dead stream's queue has no stamp left and reads None
        waited = self.runtime.job_pushed(key)
        if waited is not None:
            tracer = get_tracer()
            partition_id = self.runtime.partition_for_key(key)
            trace_id = f"{partition_id}:{activation}"
            if tracer.enabled and tracer.sampled(trace_id):
                tracer.emit(trace_id, "jobstream.push", waited, partition_id,
                            parent="gateway.request",
                            attrs={"partition": partition_id,
                                   "jobType": stream.job_type, "jobKey": key,
                                   "streamId": stream.stream_id,
                                   **instance_attrs(job)})
        return True

    def _redeliver(self, job_type: str, key: int, job: dict,
                   activation: int = -1) -> bool:
        """Route an undeliverable job to another live stream of the type that
        is authorized for the job's tenant (never across tenants)."""
        tenant = job.get("tenantId", DEFAULT_TENANT)
        for _ in range(8):
            stream = self._pick_stream(job_type)
            if stream is None:
                return False
            if tenant not in (stream.tenant_ids or [DEFAULT_TENANT]):
                # no eligible stream may exist at all; scan once under lock
                with self._lock:
                    eligible = [
                        s for s in self._streams.get(job_type, ())
                        if tenant in (s.tenant_ids or [DEFAULT_TENANT])
                    ]
                if not eligible:
                    return False
                stream = eligible[0]
            if self._deliver(stream, key, job, activation):
                return True
        return False

    def _yield_back(self, job_key: int) -> None:
        try:
            self.runtime.submit(
                self.runtime.partition_for_key(job_key),
                command(ValueType.JOB, JobIntent.YIELD, {}, key=job_key),
            )
        except Exception:  # noqa: BLE001 — the job times out eventually anyway
            logger.exception("job yield-back failed for key %s", job_key)
