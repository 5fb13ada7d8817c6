"""MeshKernelRunner: N partitions' admitted groups on ONE device mesh.

This is SURVEY.md §2.13 row 1 made real in the serving stack: the reference
scales horizontally by adding Raft partitions (atomix/cluster/src/main/java/
io/atomix/raft/partition/RaftPartition.java:44, gateway round-robin
RequestDispatchStrategy); the TPU-native analogue shards the batch axis of
the automaton kernel over a ``jax.sharding.Mesh`` — **partition = shard of
the device batch**. Each partition's kernel backend builds its group arrays
exactly as for the single-device path; the runner packs up to ``n_shards``
groups into one shard-block-aligned batch, runs ONE sharded chunked
run_collect program (shard_map over the mesh, per-shard event tensors
assembled on axis 1), and hands each partition back its own per-step events.

Determinism: shards never interact — a group's step events are a pure
function of its own arrays, so a partition's materialized log is
byte-identical whether its group dispatched alone or coalesced with others
(the e2e byte-equality tests assert exactly this). Quiescence/overflow tails
stay per-shard for the same reason: one partition overflowing falls back
sequentially without poisoning co-dispatched partitions.

Thread model: partition ownership threads call ``submit()``; the first
submitter becomes the dispatch leader, drains the queue (coalescing whatever
other partitions enqueued — XLA execution releases the GIL, so groups pile
up naturally while the device is busy), and wakes the waiters. A
``run_groups()`` synchronous API underneath is the deterministic seam the
tests drive directly.

Instruments (always on): a dispatch observes its wall, its host→device
transfers and the groups it carried into ``stream_processor_pipeline_mesh_*``
histograms; each result carries its group's wait behind other dispatches,
which the submitting backend observes under its partition's label. The
dispatch's three phases are ``zeebe.kernel_chunk.mesh_*`` annotations
(``profiler.MESH_PHASES``), and with the tracer on the dispatch is a
``mesh.dispatch`` span in the trace of its leading group.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from zeebe_tpu.observability.profiler import phase_annotation
from zeebe_tpu.parallel.mesh import (
    BATCH_AXIS,
    make_mesh,
    state_specs,
)
from zeebe_tpu.utils.metrics import REGISTRY

# one observation a group, by its submitting backend (label partition): the
# wait from submit() entered to the start of the dispatch that carried it
M_WAIT = REGISTRY.histogram(
    "stream_processor_pipeline_mesh_wait",
    "seconds per kernel group from MeshKernelRunner.submit() entered to the "
    "start of the sharded dispatch that carried it (the wait behind other "
    "partitions' dispatches on the one leader thread)",
    ("partition",))
# one observation a sharded dispatch: its wall, from the first device_put to
# the last shard's rows unpacked over all chunks; and two counts, not times
_M_DISPATCH = REGISTRY.histogram(
    "stream_processor_pipeline_mesh_dispatch",
    "seconds per sharded mesh dispatch, from the first host-to-device put "
    "to the last shard's event rows unpacked, over all its chunks")
_M_UPLOADS = REGISTRY.histogram(
    "stream_processor_pipeline_mesh_uploads",
    "host (numpy) arrays a sharded mesh dispatch transferred to the mesh, "
    "one host-to-device transfer each (a count, not a time)",
    buckets=(0, 1, 2, 4, 8, 16, 32))
_M_GROUPS = REGISTRY.histogram(
    "stream_processor_pipeline_mesh_groups",
    "kernel groups one sharded mesh dispatch carried (a count, not a time)",
    buckets=(1, 2, 3, 4, 8, 16))


def mesh_shards(requested: int, partitions: int, devices: int) -> int:
    """The shards a mesh runner gets: ``requested`` when positive, 0 (none)
    when 0, and for -1 (auto) ``min(devices, partitions)`` — more shards than
    partitions would be permanent padding — or none below 2, where the
    direct single-device dispatch wins."""
    if requested >= 0:
        return requested
    shards = min(devices, partitions)
    return shards if shards >= 2 else 0


@dataclass
class GroupRequest:
    """One partition's admitted group, in host (numpy) form.

    Arrays use the group's natural geometry (I, T); the runner pads to the
    dispatch's common geometry. ``tables_fingerprint`` gates coalescing:
    only groups compiled from identical table sets may share a dispatch
    (the sharded program takes ONE replicated DeviceTables argument)."""

    device_tables: Any  # DeviceTables (replicated input)
    config: Any  # KernelConfig (static)
    tables_fingerprint: Any
    arrays: dict[str, np.ndarray]  # elem/phase/inst/def_of/var_slots/join_counts/done
    num_instances: int  # I (padded bucket size)
    num_tokens: int  # T
    max_steps: int
    chunk_steps: int
    #: the submitting partition and its group's trace (tracer on): the
    #: dispatch's span joins the trace of its leading group
    partition: int = 0
    trace: str | None = None
    #: sampled for shadow verification: the result carries this request's
    #: own shard's event rows, chunk by chunk (nobody else pays for them)
    keep_rows: bool = False
    #: perf_counter when ``submit()`` was entered (0: run_groups direct)
    submitted_at: float = 0.0


@dataclass
class GroupResult:
    steps: list | None  # per-step unpacked event dicts; None → fall back
    overflow: bool = False
    quiesced: bool = True
    #: the shard's fetched event rows, chunk by chunk (``keep_rows`` only),
    #: in the dispatch's geometry: (instances, tokens, chunk steps)
    rows: list | None = None
    geometry: tuple | None = None
    #: seconds from ``submit()`` entered to the start of the dispatch that
    #: carried the group: the wait behind other partitions' dispatches
    wait_s: float = 0.0


@dataclass
class _Waiter:
    request: GroupRequest
    event: threading.Event = field(default_factory=threading.Event)
    result: GroupResult | None = None


def _pad_axis0(a: np.ndarray, n: int, fill) -> np.ndarray:
    if a.shape[0] == n:
        return a
    out = np.full((n, *a.shape[1:]), fill, a.dtype)
    out[: a.shape[0]] = a
    return out


class MeshKernelRunner:
    """Shared device-dispatch point for up to ``n_shards`` partitions."""

    def __init__(self, n_shards: int | None = None, mesh=None,
                 batch_window_s: float = 0.0, adaptive_window: bool = False) -> None:
        self.mesh = mesh if mesh is not None else make_mesh(n_shards)
        self.n_shards = self.mesh.devices.size
        # > 0: the dispatch leader waits this long before draining the queue,
        # trading a little latency for more coalescing (tests use it to make
        # multi-thread coalescing deterministic; serving leaves it 0 — groups
        # pile up naturally while the device is busy)
        self.batch_window_s = batch_window_s
        # adaptive gate (VERDICT r4 item 5): the window only pays off when
        # submitters actually overlap — sleep it only while recent drains
        # observed a backlog (dispatch queue non-empty when one finished).
        # With the gate on, an idle runner's window AUTO-DISABLES, so a
        # mis-set window cannot tax a non-contended deployment (round 4:
        # p8_windowed_300ms lost 40% throughput to an unconditional window).
        # Off by default: batch_window_s > 0 alone keeps its deterministic
        # always-sleep contract (tests coalesce concurrent submitters with
        # it; production serving opts into the adaptive gate).
        self.adaptive_window = adaptive_window
        self._recent_backlog = False
        self._lock = threading.Lock()
        self._queue: list[_Waiter] = []
        self._leader_active = False
        self._collect_cache: dict = {}
        # observability (tests assert coalescing happened)
        self.dispatches = 0
        self.groups_dispatched = 0
        self.coalesced_dispatches = 0
        self.windows_slept = 0
        self.windows_skipped = 0
        #: every device that held a shard of a dispatch's result — a mesh
        #: that quietly put all shards on one device shows up here
        self.shard_devices: set = set()

    # -- the deterministic core: one sharded dispatch per compatible batch --

    def run_groups(self, requests: list[GroupRequest]) -> list[GroupResult]:
        """Execute every request; requests sharing a tables fingerprint ride
        one sharded dispatch (up to n_shards per dispatch)."""
        results: list[GroupResult | None] = [None] * len(requests)
        by_tables: dict[Any, list[int]] = {}
        for i, req in enumerate(requests):
            by_tables.setdefault(req.tables_fingerprint, []).append(i)
        for indices in by_tables.values():
            for start in range(0, len(indices), self.n_shards):
                batch = indices[start : start + self.n_shards]
                outs = self._dispatch([requests[i] for i in batch])
                for i, out in zip(batch, outs):
                    results[i] = out
        return results  # type: ignore[return-value]

    def _dispatch(self, requests: list[GroupRequest]) -> list[GroupResult]:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from zeebe_tpu.ops.automaton import unpack_events

        t_start = time.perf_counter()
        self.dispatches += 1
        self.groups_dispatched += len(requests)
        if len(requests) > 1:
            self.coalesced_dispatches += 1
        S = self.n_shards
        # common per-shard geometry: the max bucket over the batch (every
        # request was already bucket-padded by its backend, so this re-pads
        # only when buckets differ)
        I_c = max(r.num_instances for r in requests)
        T_c = max(r.num_tokens for r in requests)
        chunk = max(r.chunk_steps for r in requests)
        max_steps = max(r.max_steps for r in requests)
        lead = requests[0]

        def shard_arrays(name, fill):
            n = T_c if name in ("elem", "phase", "inst") else I_c
            blocks = [_pad_axis0(r.arrays[name], n, fill) for r in requests]
            while len(blocks) < S:
                blocks.append(np.full_like(blocks[0], fill))
            return np.concatenate(blocks, axis=0)

        mesh = self.mesh
        specs = state_specs()
        uploads = 0

        def put(value, spec):
            nonlocal uploads
            uploads += 1
            return jax.device_put(value, NamedSharding(mesh, spec))

        with phase_annotation("mesh_put"):
            state = {
                name: put(shard_arrays(name, fill), specs[name])
                for name, fill in (("elem", -1), ("phase", 0), ("inst", 0),
                                   ("def_of", 0), ("var_slots", 0.0),
                                   ("join_counts", 0), ("mi_left", 0))
            }
            # padding instances are done upfront so they never report
            # newly_done (a backend's ``done`` plane is a 0/1 int32 view of
            # its packed buffer)
            state["done"] = put(shard_arrays("done", True).astype(np.bool_),
                                specs["done"])
            state["incident"] = put(np.zeros(S * I_c, np.bool_),
                                    specs["incident"])
            # counters/overflow are per-shard rows (NOT psum'd: a partition's
            # overflow must fall back alone)
            for name, dtype in (("transitions", np.int32),
                                ("jobs_created", np.int32),
                                ("completed", np.int32),
                                ("overflow", np.bool_)):
                state[name] = put(np.zeros(S, dtype), P(BATCH_AXIS))

        collect = self._sharded_collect(chunk, lead.config)
        # and, as the single-device path counts them, any host (numpy) leaf
        # among the sharded program's own arguments
        uploads += sum(isinstance(leaf, np.ndarray) for leaf in
                       jax.tree_util.tree_leaves(lead.device_tables))
        FO = lead.device_tables.out_target.shape[2]
        row_len = T_c * (2 + FO) + 2
        n_req = len(requests)
        steps_per: list[list] = [[] for _ in range(n_req)]
        rows_per = [[] if r.keep_rows else None for r in requests]
        quiesced = [False] * n_req
        overflow = [False] * n_req
        chunks = 0
        for _ in range(max(1, max_steps // chunk)):
            with phase_annotation("mesh_run"):
                state, packed = collect(lead.device_tables, state)
                self.shard_devices |= packed.devices()
                flat = self._fetch(packed)  # [chunk, S*row_len]
            chunks += 1
            with phase_annotation("mesh_unpack"):
                for ri in range(n_req):
                    if quiesced[ri]:
                        continue
                    block = flat[:, ri * row_len : (ri + 1) * row_len]
                    if rows_per[ri] is not None:
                        rows_per[ri].append(np.ascontiguousarray(block))
                    events = block[:, :-2].reshape(chunk, T_c, 2 + FO)
                    active = block[:, -2]
                    # overflow is cumulative in device state; run_collect's
                    # early-exit loop leaves rows past quiescence as zeros,
                    # so any written row carrying the bit is the signal
                    overflow[ri] = overflow[ri] or bool(block[:, -1].any())
                    qs = np.flatnonzero(active == 0)
                    keep = int(qs[0]) + 1 if qs.size else chunk
                    for s in range(keep):
                        steps_per[ri].append(unpack_events(events[s], I_c))
                    if qs.size:
                        quiesced[ri] = True
            if all(quiesced):
                break
        elapsed = time.perf_counter() - t_start
        _M_DISPATCH.observe(elapsed)
        _M_UPLOADS.observe(uploads)
        _M_GROUPS.observe(n_req)
        self._trace_dispatch(requests, elapsed, I_c, T_c, chunks)
        geometry = (I_c, T_c, chunk)
        return [
            GroupResult(steps=steps_per[ri], overflow=overflow[ri],
                        quiesced=quiesced[ri], rows=rows_per[ri],
                        geometry=geometry,
                        wait_s=(t_start - r.submitted_at
                                if r.submitted_at else 0.0))
            for ri, r in enumerate(requests)
        ]

    @staticmethod
    def _fetch(packed) -> np.ndarray:
        """The one device→host transfer of a chunk's event rows (a test
        substitutes a faulty device here)."""
        import jax

        return np.asarray(jax.device_get(packed))

    def _trace_dispatch(self, requests: list[GroupRequest], elapsed: float,
                        I: int, T: int, chunks: int) -> None:
        """The ``mesh.dispatch`` span (tracer on), in the trace of the
        dispatch's leading group."""
        from zeebe_tpu.observability.tracer import get_tracer

        lead = requests[0]
        tracer = get_tracer()
        if not tracer.enabled or lead.trace is None:
            return
        tracer.emit(lead.trace, "mesh.dispatch", elapsed, lead.partition,
                    parent="processor.kernel_group",
                    attrs={"groups": len(requests), "shards": self.n_shards,
                           "partitions": ",".join(str(r.partition)
                                                  for r in requests),
                           "I": I, "T": T, "chunks": chunks})

    def _sharded_collect(self, n_steps: int, config):
        key = (n_steps, config)
        fn = self._collect_cache.get(key)
        if fn is None:
            import jax
            from jax.sharding import PartitionSpec as P

            from zeebe_tpu.ops.automaton import DeviceTables, run_collect

            specs = state_specs()
            # per-shard scalar tails ride as length-S rows sharded on the
            # batch axis
            local_specs = dict(specs)
            for name in ("transitions", "jobs_created", "completed", "overflow"):
                local_specs[name] = P(BATCH_AXIS)

            # named for the kernel: the XLA module is jit_<name>, and a trace
            # reduction finds the kernel's device time by "run_collect"
            def run_collect_sharded(dt, state):
                # shard-local view: scalar counters for the kernel body
                local_state = dict(state)
                for name in ("transitions", "jobs_created", "completed",
                             "overflow"):
                    local_state[name] = state[name][0]
                new_state, packed = run_collect(dt, local_state,
                                                n_steps=n_steps, config=config)
                for name in ("transitions", "jobs_created", "completed",
                             "overflow"):
                    new_state[name] = new_state[name][None]
                return new_state, packed

            fn = jax.jit(jax.shard_map(
                run_collect_sharded,
                mesh=self.mesh,
                in_specs=(
                    DeviceTables(**{
                        name: P() for name in DeviceTables.__dataclass_fields__
                    }),
                    local_specs,
                ),
                out_specs=(local_specs, P(None, BATCH_AXIS)),
                check_vma=False,
            ))
            self._collect_cache[key] = fn
        return fn

    # -- thread-safe opportunistic batching ---------------------------------

    def submit(self, request: GroupRequest) -> GroupResult:
        """Execute one group, coalescing with other threads' concurrently
        pending groups. The first submitter leads: it drains the queue (one
        sharded dispatch per compatible batch) until empty, then hands off."""
        request.submitted_at = time.perf_counter()
        waiter = _Waiter(request)
        with self._lock:
            self._queue.append(waiter)
            if self._leader_active:
                lead = False
            else:
                self._leader_active = True
                lead = True
        if not lead:
            waiter.event.wait()
            assert waiter.result is not None
            return waiter.result
        batch: list[_Waiter] = []
        try:
            if self.batch_window_s > 0:
                if not self.adaptive_window or self._recent_backlog:
                    self.windows_slept += 1
                    time.sleep(self.batch_window_s)
                else:
                    self.windows_skipped += 1
            while True:
                with self._lock:
                    batch = self._queue
                    self._queue = []
                    if not batch:
                        self._leader_active = False
                        break
                results = self.run_groups([w.request for w in batch])
                with self._lock:
                    # device occupancy signal: others queued while we ran
                    self._recent_backlog = bool(self._queue)
                for w, res in zip(batch, results):
                    w.result = res
                    w.event.set()
        except BaseException:
            # wake EVERY waiter this leader was responsible for — the popped
            # batch and anything still queued — with a fallback result so no
            # partition thread hangs; their backends fall back sequentially
            with self._lock:
                stranded = batch + self._queue
                self._queue = []
                self._leader_active = False
            for w in stranded:
                if w.result is None:
                    w.result = GroupResult(steps=None)
                    w.event.set()
            if waiter.result is None:
                waiter.result = GroupResult(steps=None)
                waiter.event.set()
            raise
        assert waiter.result is not None
        return waiter.result
