"""The data-parallel BPMN automaton kernel.

This is the BASELINE.json north star: the reference's BpmnStreamProcessor +
per-element BpmnElementProcessor handlers (engine/…/processing/bpmn/) re-
expressed as one `jax.jit` step advancing thousands of process instances
lock-step on a TPU. Design notes:

- **SoA token pool**: a token is a (element, phase, instance) triple in flat
  int32 arrays of capacity T. No Python objects, no per-token control flow —
  the element-type dispatch (the reference's switch in BpmnElementProcessors)
  is masked vector arithmetic over the deploy-time tables (tables.py).
- **Lock-step semantics**: one kernel step advances every live token through
  one element pass. Within a step tokens are independent (per-instance state
  only); the host merges device events back into the partition's event-sourced
  log in deterministic slot order, making the batched schedule a reordering-
  equivalent of the reference's one-at-a-time processing.
- **Movement is allocation**: every taken sequence flow (including parallel
  fan-out) becomes a placement request; free token slots are assigned by
  prefix-sum, parallel-join arrivals are ranked with a stable sort so exactly
  the completing arrival proceeds — the NUMBER_OF_TAKEN_SEQUENCE_FLOWS
  counters live in a dense [instances, elements] array.
- **Conditions** run on a vectorized stack VM over per-instance order-key
  variable slots (compile_condition) — two int32 planes carrying IEEE-754
  total-order keys, bit-exact against the host float64 evaluator — so
  exclusive-gateway routing needs no host round trip.
- **TPU mapping**: everything is static-shaped, pure int32, and fuses into
  a handful of XLA kernels; gathers/scatters ride the VPU while the MXU stays
  free for future DMN/decision-table batch evaluation. Scaling over a mesh is
  data-parallel over instances (see zeebe_tpu.parallel.mesh) — the partition
  axis of the reference maps to the mesh axis here.

Job handling: ``auto_jobs=True`` emulates instant workers on-device (bench
mode, isolates engine throughput); otherwise tokens park in PHASE_WAIT and the
host completes jobs between steps (``complete_jobs``), which is how the real
job-worker path drives the kernel.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from zeebe_tpu.ops.tables import (
    K_CATCH,
    K_END,
    K_EXCLUSIVE,
    K_FORK,
    K_HOST,
    K_INCLUSIVE,
    K_JOIN,
    K_MI,
    K_NONE,
    K_PASS,
    K_SCOPE,
    K_TASK,
    MAX_PROG_LEN,
    OP_AND,
    OP_EQ,
    OP_GE,
    OP_GT,
    OP_LE,
    OP_LT,
    OP_NE,
    OP_NEG,
    OP_NOP,
    OP_NOT,
    OP_OR,
    OP_PUSH_CONST,
    OP_PUSH_VAR,
    STACK_DEPTH,
    ProcessTables,
)

# token phases
PHASE_AT = 0  # at element, executes this step
PHASE_WAIT = 1  # task activated, waiting for job completion
PHASE_DONE = 2  # job completed, finish task this step
PHASE_STALLED = 3  # incident raised; host must resolve


@dataclasses.dataclass
class DeviceTables:
    """ProcessTables moved to device arrays (a pytree via tree_flatten)."""

    kernel_op: jax.Array
    in_count: jax.Array
    job_type: jax.Array
    out_count: jax.Array
    out_target: jax.Array
    out_cond: jax.Array
    out_flow_idx: jax.Array
    default_slot: jax.Array
    start_elem: jax.Array
    scope_start: jax.Array
    in_scope: jax.Array
    cond_ops: jax.Array
    cond_args: jax.Array
    mi_sequential: jax.Array

    @classmethod
    def from_tables(cls, t: ProcessTables) -> "DeviceTables":
        return cls(
            kernel_op=jnp.asarray(t.kernel_op),
            in_count=jnp.asarray(t.in_count),
            job_type=jnp.asarray(t.job_type),
            out_count=jnp.asarray(t.out_count),
            out_target=jnp.asarray(t.out_target),
            out_cond=jnp.asarray(t.out_cond),
            out_flow_idx=jnp.asarray(t.out_flow_idx),
            default_slot=jnp.asarray(t.default_slot),
            start_elem=jnp.asarray(t.start_elem),
            scope_start=jnp.asarray(t.scope_start),
            in_scope=jnp.asarray(t.in_scope),
            cond_ops=jnp.asarray(t.cond_ops),
            cond_args=jnp.asarray(t.cond_args),
            mi_sequential=jnp.asarray(t.mi_sequential),
        )


# every jit call flattens its tables: the field names are read once, here
_TABLE_FIELDS = tuple(f.name for f in dataclasses.fields(DeviceTables))
jax.tree_util.register_pytree_node(
    DeviceTables,
    lambda t: (tuple(getattr(t, name) for name in _TABLE_FIELDS), None),
    lambda _, children: DeviceTables(*children),
)


def _coerce_slot_planes(values) -> np.ndarray:
    """Slot input → int32 (hi, lo) plane array. A 3-D INTEGER array (any
    width) is pre-packed planes — int64 Python-int inputs must coerce, not
    silently fall into the float packer, which would reinterpret plane
    integers as float *values* and mint garbage keys. Floats pack."""
    arr = np.asarray(values)
    if arr.ndim == 3:
        if arr.shape[-1] != 2:
            raise ValueError(f"pre-packed slot planes must have trailing dim 2, "
                             f"got {arr.shape}")
        if not np.issubdtype(arr.dtype, np.integer):
            raise ValueError("3-D slot input must be integer (hi, lo) planes; "
                             "pass floats as a 2-D [instances, slots] array")
        if arr.dtype != np.int32:
            out_of_range = (arr < np.iinfo(np.int32).min) | (arr > np.iinfo(np.int32).max)
            if out_of_range.any():
                raise ValueError("slot planes exceed int32 range")
            arr = arr.astype(np.int32)
        return arr
    from zeebe_tpu.ops.tables import pack_slot_values

    return pack_slot_values(arr)


def make_state(
    tables: ProcessTables,
    num_instances: int,
    definition_of_instance: np.ndarray,
    initial_slots: np.ndarray | None = None,
    token_capacity: int | None = None,
    num_shards: int = 1,
) -> dict:
    """Fresh automaton state: one token per instance, parked at the start
    event. Arrays are a plain dict pytree so jit/donation/sharding apply.

    With ``num_shards > 1`` the layout is shard-block-aligned for axis-0
    sharding over a mesh: shard s owns instance rows [s*I/n, (s+1)*I/n) and
    the token-pool block [s*T/n, (s+1)*T/n); token ``inst`` values are
    *local* to the shard block (the kernel body runs on local shapes under
    shard_map, so per-shard indices must be self-contained)."""
    I = num_instances
    T = token_capacity or (2 * I)
    if I % num_shards or T % num_shards:
        raise ValueError(f"instances ({I}) and tokens ({T}) must divide num_shards ({num_shards})")
    E = tables.max_elements
    S = tables.num_slots
    def_of = np.asarray(definition_of_instance, np.int32)
    elem = np.full(T, -1, np.int32)
    phase = np.zeros(T, np.int32)
    inst = np.zeros(T, np.int32)
    Il, Tl = I // num_shards, T // num_shards
    if Il > Tl:
        raise ValueError("token capacity per shard smaller than instances per shard")
    for s in range(num_shards):
        block = slice(s * Tl, s * Tl + Il)
        elem[block] = tables.start_elem[def_of[s * Il : (s + 1) * Il]]
        inst[block] = np.arange(Il, dtype=np.int32)
    if initial_slots is None:
        slots = np.zeros((I, S, 2), np.int32)
    else:
        slots = _coerce_slot_planes(initial_slots)
    return {
        "elem": jnp.asarray(elem),
        "phase": jnp.asarray(phase),
        "inst": jnp.asarray(inst),
        "def_of": jnp.asarray(def_of),
        "var_slots": jnp.asarray(slots),
        "join_counts": jnp.zeros((I, E), jnp.int32),
        "mi_left": jnp.zeros((I, E), jnp.int32),
        "done": jnp.zeros(I, jnp.bool_),
        "incident": jnp.zeros(I, jnp.bool_),
        "transitions": jnp.zeros((), jnp.int32),
        "jobs_created": jnp.zeros((), jnp.int32),
        "completed": jnp.zeros((), jnp.int32),
        "overflow": jnp.zeros((), jnp.bool_),
    }


# ---------------------------------------------------------------------------
# condition VM


def _eval_program(ops: jax.Array, args: jax.Array, slots: jax.Array) -> jax.Array:
    """Evaluate one condition program against one instance's slots → bool.

    Values are 64-bit order keys carried as (hi, lo) int32 planes
    (tables.f64_key_planes): comparisons are lexicographic over the planes,
    hence BIT-EXACT against the host's float64 FEEL evaluator. Booleans are
    (0|1, 0). Arithmetic never reaches the device (compile_condition
    host-escapes it), so the VM has only push/compare/bool/negate ops."""

    def body(i, carry):
        stack, sp = carry  # stack: [DEPTH, 2] int32
        op = ops[i]
        arg = args[i]  # (hi, lo)
        push_val = jnp.where(op == OP_PUSH_VAR, slots[arg[0]], arg)
        a = stack[jnp.maximum(sp - 2, 0)]
        b = stack[jnp.maximum(sp - 1, 0)]
        # lexicographic order over (hi, lo); both planes are sign-biased so
        # plain signed int32 comparison gives the unsigned half order
        lt = (a[0] < b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
        eq = (a[0] == b[0]) & (a[1] == b[1])
        bool_hi = jnp.select(
            [
                op == OP_LT, op == OP_LE, op == OP_GT, op == OP_GE,
                op == OP_EQ, op == OP_NE, op == OP_AND, op == OP_OR,
            ],
            [
                lt, lt | eq, ~(lt | eq), ~lt,
                eq, ~eq,
                (a[0] > 0) & (b[0] > 0), (a[0] > 0) | (b[0] > 0),
            ],
            default=False,
        ).astype(jnp.int32)
        bin_val = jnp.stack([bool_hi, jnp.int32(0)])
        # NOT flips a boolean; NEG negates an order key (bitwise NOT of the
        # unbiased halves = -1 - x in the sign-biased planes). Zero stays
        # zero: key(+0.0) is (0, INT32_MIN) — the sign bit of the f64 maps
        # to hi's bias and the empty mantissa to lo's — and negating it
        # would mint key(-0.0), which compares strictly below it.
        is_zero = (b[0] == 0) & (b[1] == jnp.int32(-(2**31)))
        neg_val = jnp.where(
            is_zero, b, jnp.stack([-1 - b[0], -1 - b[1]])
        )
        un_val = jnp.where(
            op == OP_NOT,
            jnp.stack([1 - jnp.minimum(b[0], 1), jnp.int32(0)]),
            neg_val,
        )
        is_push = (op == OP_PUSH_CONST) | (op == OP_PUSH_VAR)
        is_un = (op == OP_NOT) | (op == OP_NEG)
        # binary = comparisons (3..8) + AND/OR (9..10); arithmetic never
        # reaches the device (compile_condition host-escapes it), so there
        # are no opcodes above OP_OR other than the unaries
        is_bin = (op >= OP_LT) & (op <= OP_OR)
        new_top = jnp.where(is_push, push_val, jnp.where(is_bin, bin_val, un_val))
        write_pos = jnp.where(is_push, sp, jnp.where(is_bin, sp - 2, sp - 1))
        do_write = is_push | is_bin | is_un
        # NOPs write out of bounds → dropped
        write_pos = jnp.where(do_write, jnp.clip(write_pos, 0, STACK_DEPTH - 1), STACK_DEPTH)
        stack = stack.at[write_pos].set(new_top, mode="drop")
        sp = sp + jnp.where(is_push, 1, jnp.where(is_bin, -1, 0))
        return stack, sp

    stack0 = jnp.zeros((STACK_DEPTH, 2), jnp.int32)
    stack, sp = jax.lax.fori_loop(0, MAX_PROG_LEN, body, (stack0, jnp.int32(0)))
    return stack[jnp.maximum(sp - 1, 0), 0] > 0


# vmapped over (program_id per request, slots per request)
def _eval_conditions(cond_ops, cond_args, prog_ids, slot_rows):
    def one(pid, slots):
        return jax.lax.cond(
            pid >= 0,
            lambda: _eval_program(cond_ops[jnp.maximum(pid, 0)], cond_args[jnp.maximum(pid, 0)], slots),
            lambda: jnp.bool_(False),
        )
    return jax.vmap(one)(prog_ids, slot_rows)


# ---------------------------------------------------------------------------
# scope machinery


def _scope_occupancy(tables: "DeviceTables", state: dict):
    """(occ, pend): per (instance, scope element) counts of live tokens and
    unconsumed parallel-join arrivals strictly inside each scope."""
    elem = state["elem"]
    inst = state["inst"]
    I, E = state["join_counts"].shape
    live = elem >= 0
    def_of_tok = state["def_of"][inst]
    # [T, E] row t = which scopes (transitively) contain token t's element
    containing = tables.in_scope[def_of_tok, jnp.maximum(elem, 0)].astype(jnp.int32)
    occ = jnp.zeros((I, E), jnp.int32).at[inst].add(
        containing * live.astype(jnp.int32)[:, None]
    )
    pend = jnp.einsum(
        "ie,ies->is",
        state["join_counts"],
        tables.in_scope[state["def_of"]].astype(jnp.int32),
    )
    return occ, pend


def _scope_drained(tables: "DeviceTables", state: dict,
                   include_mi: bool = False, occ_pend=None) -> jax.Array:
    """Mask of parked K_SCOPE tokens whose scope holds no live token and no
    unconsumed parallel-join arrival — they complete on the next step. Used
    by ``step`` (start-of-step state) and by ``run_collect``'s active count
    (post-step state), so a drain-pending scope never reads as quiesced.
    With ``include_mi`` the mask also covers fully-spawned K_MI bodies whose
    children all drained (body completion)."""
    elem = state["elem"]
    phase = state["phase"]
    inst = state["inst"]
    live = elem >= 0
    def_of_tok = state["def_of"][inst]
    op = jnp.where(live, tables.kernel_op[def_of_tok, jnp.maximum(elem, 0)], K_NONE)
    occ, pend = occ_pend if occ_pend is not None else _scope_occupancy(tables, state)
    scope_like = op == K_SCOPE
    if include_mi:
        spawned_out = state["mi_left"][inst, jnp.maximum(elem, 0)] == 0
        scope_like = scope_like | ((op == K_MI) & spawned_out)
    return (
        live & scope_like & (phase == PHASE_WAIT)
        & (occ[inst, jnp.maximum(elem, 0)] == 0)
        & (pend[inst, jnp.maximum(elem, 0)] == 0)
    )


def _mi_spawnable(tables: "DeviceTables", state: dict,
                  occ_pend=None) -> jax.Array:
    """Mask of parked K_MI body tokens that spawn a child next step: children
    left, and (sequential bodies only) the previous child fully drained."""
    elem = state["elem"]
    phase = state["phase"]
    inst = state["inst"]
    live = elem >= 0
    def_of_tok = state["def_of"][inst]
    e = jnp.maximum(elem, 0)
    op = jnp.where(live, tables.kernel_op[def_of_tok, e], K_NONE)
    occ, pend = occ_pend if occ_pend is not None else _scope_occupancy(tables, state)
    seq = tables.mi_sequential[def_of_tok, e] > 0
    gate = ~seq | ((occ[inst, e] == 0) & (pend[inst, e] == 0))
    return (
        live & (op == K_MI) & (phase == PHASE_WAIT)
        & (state["mi_left"][inst, e] > 0) & gate
    )


# ---------------------------------------------------------------------------
# the step kernel


@partial(jax.jit, static_argnames=("auto_jobs", "emit_events", "config"))
def step(tables: DeviceTables, state: dict, auto_jobs: bool = True, emit_events: bool = False,
         config=None):
    """One lock-step advance of every live token. Returns (state', events)
    where events is None unless emit_events (parity/integration mode).
    ``config`` (static KernelConfig) prunes join/condition machinery the
    deployed process set does not use."""
    from zeebe_tpu.ops.tables import KernelConfig

    if config is None:
        config = KernelConfig()
    T = state["elem"].shape[0]
    I = state["def_of"].shape[0]
    E = tables.kernel_op.shape[1]
    FO = tables.out_target.shape[2]

    elem = state["elem"]
    phase = state["phase"]
    inst = state["inst"]
    def_of_tok = state["def_of"][inst]

    live = elem >= 0
    op = jnp.where(live, tables.kernel_op[def_of_tok, jnp.maximum(elem, 0)], K_NONE)
    stalled = phase == PHASE_STALLED

    # --- what does each token do this step? ------------------------------
    is_task = op == K_TASK
    is_wait = is_task | (op == K_CATCH)  # parks until the host resumes it
    is_scope = op == K_SCOPE  # parks until its inner tokens drain
    is_host = op == K_HOST  # parks forever: the sequential engine owns it
    is_mi = op == K_MI  # parks like a scope; spawns mi_left children
    executing = live & (phase == PHASE_AT) & ~stalled
    arriving_task = executing & is_wait
    arriving_scope = executing & is_scope
    arriving_host = executing & is_host
    arriving_mi = executing & is_mi
    pass_attempt = executing & ~is_wait & ~is_scope & ~is_host & ~is_mi
    if auto_jobs:
        waiting_done = live & is_wait & (phase == PHASE_WAIT)
    else:
        waiting_done = live & is_wait & (phase == PHASE_DONE)

    # --- scope drain detection --------------------------------------------
    # a parked scope token resumes when no live token and no unconsumed
    # parallel-join arrival remains anywhere inside it (reference: scope
    # completion requires activeChildren == 0 and activeFlows == 0); both
    # counts are start-of-step, so a resume lands one step after the last
    # inner token dies — quiesced states stay fixed points. K_MI bodies join
    # the mask once fully spawned (mi_left == 0): the body completes when
    # its children drain.
    if config.has_scopes or config.has_mi:
        occ_pend = _scope_occupancy(tables, state)
        scope_resume = _scope_drained(tables, state, include_mi=config.has_mi,
                                      occ_pend=occ_pend)
    else:
        occ_pend = None
        scope_resume = jnp.zeros(T, jnp.bool_)
    # parked MI bodies spawn one child per step (parallel: every step until
    # mi_left == 0; sequential: only when the previous child drained)
    if config.has_mi:
        mi_spawn = _mi_spawnable(tables, state, occ_pend=occ_pend)
    else:
        mi_spawn = jnp.zeros(T, jnp.bool_)

    # --- exclusive gateway condition evaluation ---------------------------
    out_count = tables.out_count[def_of_tok, jnp.maximum(elem, 0)]
    targets = tables.out_target[def_of_tok, jnp.maximum(elem, 0)]  # [T, FO]
    conds = tables.out_cond[def_of_tok, jnp.maximum(elem, 0)]  # [T, FO]
    slot_idx = jnp.arange(FO)[None, :]

    is_excl = op == K_EXCLUSIVE
    is_incl = op == K_INCLUSIVE
    need_eval = ((is_excl | is_incl) & pass_attempt)[:, None] & (conds >= 0)
    if config.has_conditions:
        # scalar-predicated skip: in steps where no executing token sits on a
        # conditional gateway (most steps of job-completion cascades), the
        # whole vectorized VM is skipped — the pred is a scalar, so lax.cond
        # stays real control flow (unlike a vmapped cond, which would lower
        # to select and evaluate both branches for every lane)
        def eval_all(_):
            prog_ids = jnp.where(need_eval, conds, -1).reshape(-1)
            slot_rows = jnp.repeat(state["var_slots"][inst], FO, axis=0)
            out = _eval_conditions(tables.cond_ops, tables.cond_args, prog_ids, slot_rows)
            return out.reshape(T, FO) & need_eval

        cond_true = jax.lax.cond(
            jnp.any(need_eval), eval_all,
            lambda _: jnp.zeros((T, FO), jnp.bool_), operand=None,
        )
    else:
        cond_true = jnp.zeros((T, FO), jnp.bool_)

    first_true = jnp.argmax(cond_true, axis=1)
    any_true = jnp.any(cond_true, axis=1)
    default = tables.default_slot[def_of_tok, jnp.maximum(elem, 0)]
    excl_choice = jnp.where(any_true, first_true, default)  # -1 if no default
    excl_no_match = (is_excl | is_incl) & pass_attempt & ~any_true & (default < 0)

    # no-match raises an incident: the token stalls instead of completing
    full_pass = pass_attempt & ~excl_no_match
    completing = full_pass | waiting_done | scope_resume  # completes & moves

    # inclusive fork: EVERY true-condition flow; the default only when none
    # hold (reference: InclusiveGatewayProcessor.findSequenceFlowsToTake)
    incl_take = cond_true | (
        (slot_idx == default[:, None]) & ~any_true[:, None]
        & (default >= 0)[:, None]
    )
    take_mask = jnp.where(
        is_excl[:, None],
        (slot_idx == excl_choice[:, None]) & (excl_choice >= 0)[:, None],
        jnp.where(is_incl[:, None], incl_take, slot_idx < out_count[:, None]),
    )
    take_mask = take_mask & completing[:, None] & (targets >= 0)

    # --- transition counting ----------------------------------------------
    # full pass = 4 lifecycle events; task arrival = 2; task completion = 2;
    # an instance finishing adds the process element's completing/completed
    flows_taken = take_mask.sum()
    per_token = (
        jnp.where(full_pass, 4, 0)
        + jnp.where(arriving_task | arriving_scope | arriving_mi, 2, 0)
        + jnp.where(waiting_done | scope_resume, 2, 0)
    )

    # --- movement: flatten taken flows into placement requests ------------
    req_target_2d = jnp.where(take_mask, targets, -1)
    spawning = arriving_scope | arriving_mi | mi_spawn
    if config.has_scopes or config.has_mi:
        # an arriving scope (or an MI body, on arrival and on each later
        # spawn step while parked) spawns its inner token; the request rides
        # the (unused) flow slot 0 of the spawner, so placement/dest
        # machinery needs no extra channel — take_mask stays false there
        # (no SEQUENCE_FLOW_TAKEN), and dest[:, 0] records the child slot
        spawn_target = jnp.where(
            spawning,
            tables.scope_start[def_of_tok, jnp.maximum(elem, 0)],
            req_target_2d[:, 0],
        )
        req_target_2d = req_target_2d.at[:, 0].set(spawn_target)
    req_target = req_target_2d.reshape(-1)  # [T*FO]
    req_inst = jnp.repeat(inst, FO)
    req_def = jnp.repeat(def_of_tok, FO)
    req_live = req_target >= 0

    if config.has_joins:
        # parallel-join arrivals: stable-rank same-(inst, target) requests so
        # exactly the arrival that fills the join proceeds
        req_op = jnp.where(
            req_live, tables.kernel_op[req_def, jnp.maximum(req_target, 0)], K_NONE
        )
        is_join_req = req_op == K_JOIN
        flat_key = jnp.where(is_join_req, req_inst * E + req_target, 0)
        arrivals_flat = jnp.zeros((I * E,), jnp.int32).at[flat_key].add(
            jnp.where(is_join_req, 1, 0)
        )

        # the stable argsort only matters when TWO arrivals hit the same
        # (instance, join) in one step; most steps have at most one, so the
        # whole ranking machinery rides a scalar-predicated cond (real
        # control flow, like the condition VM's skip)
        def ranked(_):
            join_key = jnp.where(is_join_req, req_inst * E + req_target,
                                 jnp.int32(2**30))
            order = jnp.argsort(join_key, stable=True)
            sorted_key = join_key[order]
            new_run = jnp.concatenate(
                [jnp.ones(1, jnp.bool_), sorted_key[1:] != sorted_key[:-1]])
            idxs = jnp.arange(T * FO, dtype=jnp.int32)
            run_start = jax.lax.associative_scan(
                jnp.maximum, jnp.where(new_run, idxs, 0))
            rank_sorted = idxs - run_start
            return jnp.zeros(T * FO, jnp.int32).at[order].set(rank_sorted)

        rank = jax.lax.cond(
            jnp.any(arrivals_flat > 1), ranked,
            lambda _: jnp.zeros(T * FO, jnp.int32), operand=None,
        )

        prior = state["join_counts"][req_inst, jnp.maximum(req_target, 0)]
        arity = jnp.maximum(tables.in_count[req_def, jnp.maximum(req_target, 0)], 1)
        count_after = prior + rank + 1
        join_completes = is_join_req & (count_after % arity == 0)
        proceeds = req_live & (~is_join_req | join_completes)

        consumed_flat = jnp.zeros((I * E,), jnp.int32).at[flat_key].add(
            jnp.where(join_completes, arity, 0)
        )
        join_counts = state["join_counts"] + (arrivals_flat - consumed_flat).reshape(I, E)
    else:
        proceeds = req_live
        join_counts = state["join_counts"]

    # --- token slot allocation (prefix-sum into freed slots) --------------
    elem_after_exec = jnp.where(completing, -1, elem)
    free = elem_after_exec < 0
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    # rank → slot id map (ranks are unique per free slot; non-free dropped)
    slot_of_rank = jnp.zeros(T, jnp.int32).at[
        jnp.where(free, free_rank, T)
    ].set(jnp.arange(T, dtype=jnp.int32), mode="drop")
    place_rank = jnp.cumsum(proceeds.astype(jnp.int32)) - 1
    free_count = free.sum()
    valid = proceeds & (place_rank < free_count)
    overflow = state["overflow"] | jnp.any(proceeds & ~valid)
    dest = jnp.where(valid, slot_of_rank[jnp.clip(place_rank, 0, T - 1)], T)

    new_elem = elem_after_exec.at[dest].set(req_target, mode="drop")
    new_inst = inst.at[dest].set(req_inst, mode="drop")

    new_phase = jnp.where(
        arriving_task | arriving_scope | arriving_host | arriving_mi,
        PHASE_WAIT, phase)
    new_phase = jnp.where(excl_no_match, PHASE_STALLED, new_phase)
    new_phase = new_phase.at[dest].set(PHASE_AT, mode="drop")

    if config.has_mi:
        spawned = arriving_mi | mi_spawn
        mi_left = state["mi_left"].at[inst, jnp.maximum(elem, 0)].add(
            -spawned.astype(jnp.int32)
        )
    else:
        mi_left = state["mi_left"]

    # --- instance completion ----------------------------------------------
    live_after = new_elem >= 0
    tokens_per_inst = jnp.zeros(I, jnp.int32).at[new_inst].add(live_after.astype(jnp.int32))
    was_done = state["done"]
    # a pending parallel-join arrival is an active sequence flow: the scope
    # only completes when no tokens AND no unconsumed arrivals remain
    # (reference: scope completion requires activeFlows == 0)
    pending_arrivals = join_counts.sum(axis=1)
    newly_done = ~was_done & (tokens_per_inst == 0) & (pending_arrivals == 0)
    done = was_done | newly_done
    incident = state["incident"] | jnp.zeros(I, jnp.bool_).at[inst].max(excl_no_match)

    transitions = (
        state["transitions"]
        + per_token.sum()
        + flows_taken
        + 2 * newly_done.sum()  # process element completing/completed
    )
    jobs_created = state["jobs_created"] + (arriving_task & is_task).sum()
    completed = state["completed"] + newly_done.sum()

    new_state = {
        "elem": new_elem,
        "phase": new_phase,
        "inst": new_inst,
        "def_of": state["def_of"],
        "var_slots": state["var_slots"],
        "join_counts": join_counts,
        "mi_left": mi_left,
        "done": done,
        "incident": incident,
        "transitions": transitions,
        "jobs_created": jobs_created,
        "completed": completed,
        "overflow": overflow,
    }

    events = None
    if emit_events:
        events = {
            "full_pass": full_pass,
            # scope/MI arrivals and resumes share the task bits: the host
            # decoder disambiguates by the element's kernel opcode; mid-park
            # MI spawns carry no flag at all — the decoder reads dest[:, 0]
            # of parked K_MI rows
            "task_arrive": arriving_task | arriving_scope | arriving_mi,
            "task_done": waiting_done | scope_resume,
            "elem": elem,
            "inst": inst,
            "take_mask": take_mask,
            "newly_done": newly_done,
            "no_match": excl_no_match,
            # placement slot per flattened (token, flow-slot) request; T means
            # no token was placed (join arrival merged, or dropped) — lets the
            # host decoder track slot→logical-token identity (kernel backend)
            "dest": dest.reshape(T, FO),
        }
    return new_state, events


# bit-packed event layout bounds: elem rides col 0 in 14 bits, and dest
# (with its == T "no placement" sentinel) rides 16 bits of a dest|take
# column; callers must fall back beyond these (realistic pools sit far
# below both). The active count is NOT bound — it travels as a full int32
# tail scalar.
PACK_MAX_ELEMENTS = 1 << 14
PACK_MAX_TOKENS = (1 << 16) - 1


def _pack_events(ev: dict, I: int, T: int) -> jax.Array:
    """Pack one step's event pytree into a single int32 [T, 2 + FO] tensor —
    one device buffer per chunk transfer, bit-packed to halve the bytes the
    host fetches from the device (per-buffer latency AND bandwidth both
    bound the serving path):

      col 0: flags(5b) | elem << 5 — bit0 full_pass, bit1 task_arrive,
             bit2 task_done, bit3 no_match, bit4 newly_done (row t < I =
             instance t)
      col 1: inst
      cols 2..2+FO: dest(16b) | take_mask << 16 per flow slot (dest == T
                    means no token placed)
    """
    flags = (
        ev["full_pass"].astype(jnp.int32)
        | (ev["task_arrive"].astype(jnp.int32) << 1)
        | (ev["task_done"].astype(jnp.int32) << 2)
        | (ev["no_match"].astype(jnp.int32) << 3)
    )
    newly = jnp.zeros(T, jnp.int32).at[:I].set(ev["newly_done"].astype(jnp.int32))
    flags = flags | (newly << 4) | (ev["elem"].astype(jnp.int32) << 5)
    dest_take = ev["dest"].astype(jnp.int32) | (ev["take_mask"].astype(jnp.int32) << 16)
    return jnp.concatenate(
        [flags[:, None], ev["inst"][:, None], dest_take],
        axis=1,
    )


def unpack_events(packed: np.ndarray, I: int) -> dict:
    """Host-side inverse of _pack_events for one step row ([T, 2+FO])."""
    flags = packed[:, 0]
    dest_take = packed[:, 2:]
    return {
        "full_pass": (flags & 1).astype(bool),
        "task_arrive": (flags & 2).astype(bool),
        "task_done": (flags & 4).astype(bool),
        "no_match": (flags & 8).astype(bool),
        "newly_done": (flags[:I] & 16).astype(bool),
        "elem": flags >> 5,
        "inst": packed[:, 1],
        "dest": dest_take & 0xFFFF,
        "take_mask": (dest_take >> 16).astype(bool),
    }


@partial(jax.jit, static_argnames=("n_steps", "config"))
def run_collect(tables: DeviceTables, state: dict, n_steps: int = 16, config=None):
    """Advance ``n_steps`` lock-steps in ONE device program, stacking each
    step's event tensors — the integration path's batched variant of calling
    ``step(emit_events=True)`` in a host loop. A quiesced state is a fixed
    point of ``step`` (no executing tokens → all masks false, no counters
    move), so over-running costs idle FLOPs but never wrong events.

    Returns (state', packed) where packed is ONE int32
    [n_steps, T*(2+FO) + 2] tensor — per-step rows of _pack_events flattened
    to 2-D before leaving the device (a [steps, T, C] output would be
    tile-padded on the last axis — lane size 128 — and the host fetch would
    transfer ~20x the real bytes from the device), with the post-step
    active-token count and the overflow flag appended as the final two
    scalars of each row. The host splits those off, reshapes to
    [steps, T, 2+FO], and decodes with unpack_events."""
    from zeebe_tpu.ops.tables import KernelConfig

    if config is None:
        config = KernelConfig()  # must mirror step()'s default resolution
    I = state["def_of"].shape[0]
    T = state["elem"].shape[0]

    FO = tables.out_target.shape[2]
    row_len = T * (2 + FO) + 2

    def body(carry):
        state, out, i, _ = carry
        state, ev = step(tables, state, auto_jobs=False, emit_events=True, config=config)
        active = (
            (state["elem"] >= 0)
            & ((state["phase"] == PHASE_AT) | (state["phase"] == PHASE_DONE))
        ).sum()
        if config.has_scopes or config.has_mi:
            # a parked scope whose inside just drained resumes next step —
            # it must count as active or the chunk loop would truncate the
            # decode right before the scope's completion events
            op2 = _scope_occupancy(tables, state)
            active = active + _scope_drained(
                tables, state, include_mi=config.has_mi, occ_pend=op2).sum()
            if config.has_mi:
                # a parked MI body with children left to spawn acts next step
                active = active + _mi_spawnable(tables, state,
                                                occ_pend=op2).sum()
        packed = _pack_events(ev, I, T).reshape(-1)
        # append (active, overflow) so the host needs exactly one device
        # fetch per chunk
        tail = jnp.stack([active.astype(jnp.int32),
                          state["overflow"].astype(jnp.int32)])
        out = jax.lax.dynamic_update_index_in_dim(
            out, jnp.concatenate([packed, tail]), i, 0)
        return state, out, i + 1, active > 0

    def cond(carry):
        _state, _out, i, go = carry
        return go & (i < n_steps)

    # early-exit loop (not scan): a quiesced state is a fixed point, so the
    # remaining steps of the chunk would only burn device FLOPs — short
    # cascades (a job completion advancing 2-3 steps) skip most of the chunk.
    # Unwritten rows stay zero; their active==0 tail is exactly the host's
    # truncation signal, and the host reads overflow from the LAST WRITTEN
    # row (cumulative in state), not the final buffer row.
    out0 = jnp.zeros((n_steps, row_len), jnp.int32)
    state, packed, _, _ = jax.lax.while_loop(
        cond, body, (state, out0, jnp.int32(0), jnp.bool_(True)))
    return state, packed


#: a group's state planes in the order they ride its packed int32 buffer
#: (shape letters: I instances, T tokens, S variable slots, E elements)
_PACKED_PLANES = (
    ("elem", "T"), ("phase", "T"), ("inst", "T"), ("def_of", "I"),
    ("var_slots", "IS2"), ("join_counts", "IE"), ("mi_left", "IE"),
    ("done", "I"), ("incident", "I"), ("transitions", ""),
    ("jobs_created", ""), ("completed", ""), ("overflow", ""),
)
#: bool in the kernel state, 0/1 in the buffer. A tuple: the casts are traced
#: in this order, and a set's order changes with the process's hash seed, the
#: program's text with it, and with that its key in the persistent cache
_PACKED_BOOL_PLANES = ("done", "incident", "overflow")


@lru_cache(maxsize=64)  # a deployment reaches a handful of geometries
def packed_state_layout(geometry: tuple) -> tuple:
    """((name, start, end, shape) of every plane, total int32 words) of one
    group's packed state; ``geometry`` is (instances, tokens, variable
    slots, elements)."""
    sizes = dict(zip("ITSE", geometry), **{"2": 2})
    planes, offset = [], 0
    for name, letters in _PACKED_PLANES:
        shape = tuple(sizes[letter] for letter in letters)
        end = offset + math.prod(shape)
        planes.append((name, offset, end, shape))
        offset = end
    return tuple(planes), offset


def packed_state_views(buffer, geometry: tuple) -> dict:
    """Every state plane as a named, shaped slice of the flat int32
    ``buffer``, offsets fixed by ``geometry``. On a numpy buffer the slices
    are views (the host fills a group's state through them, no copy); on a
    traced array they are static slices inside the compiled program — one
    function, so the two sides cannot disagree about an offset."""
    planes, length = packed_state_layout(geometry)
    if buffer.shape != (length,):
        raise ValueError(f"packed state of shape {buffer.shape} does not fit "
                         f"geometry {geometry} ({length} words)")
    # a 1-D plane is its slice: the reshape would be a second view a plane
    # on the host's build path, once a group
    return {name: (buffer[start:end] if len(shape) == 1
                   else buffer[start:end].reshape(shape))
            for name, start, end, shape in planes}


def unpack_state(packed: jax.Array, geometry: tuple) -> dict:
    """The kernel state dict a packed buffer holds (bool planes cast back)."""
    state = packed_state_views(packed, geometry)
    for name in _PACKED_BOOL_PLANES:
        state[name] = state[name].astype(jnp.bool_)
    return state


def pack_state(state: dict) -> jax.Array:
    """Inverse of :func:`unpack_state`: one flat int32 buffer."""
    return jnp.concatenate([state[name].astype(jnp.int32).reshape(-1)
                            for name, _ in _PACKED_PLANES])


@partial(jax.jit, static_argnames=("geometry", "n_steps", "config"))
def run_collect_packed(tables: DeviceTables, packed: jax.Array, geometry: tuple,
                       n_steps: int = 16, config=None):
    """:func:`run_collect` for the served path, with the group's whole state
    as ONE int32 buffer on both sides of the call: the first chunk's buffer
    is host-filled (one upload where the state dict's leaves were one each),
    a further chunk's is the previous call's carry, still on the device — the
    same compiled program either way, and two output buffers a call instead
    of fourteen. Returns (packed', events); events as ``run_collect``'s."""
    state, events = run_collect(tables, unpack_state(packed, geometry),
                                n_steps=n_steps, config=config)
    return pack_state(state), events


@partial(jax.jit, static_argnames=("max_steps", "auto_jobs", "config"))
def run_to_completion(tables: DeviceTables, state: dict, max_steps: int = 1000,
                      auto_jobs: bool = True, config=None):
    """Run steps until every instance is done (or max_steps) in one device
    program — no host round trips (the bench path)."""

    def cond(carry):
        state, steps = carry
        return (steps < max_steps) & jnp.any(state["elem"] >= 0)

    def body(carry):
        state, steps = carry
        state, _ = step(tables, state, auto_jobs=auto_jobs, emit_events=False, config=config)
        return state, steps + 1

    state, steps = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return state, steps


def complete_jobs(state: dict, token_slots: jax.Array, result_slots: jax.Array | None = None,
                  result_values: jax.Array | None = None) -> dict:
    """Host-side job completion (non-auto mode): move waiting tokens to
    PHASE_DONE, optionally writing job result variables into instance slots."""
    phase = state["phase"].at[token_slots].set(PHASE_DONE)
    new_state = dict(state)
    new_state["phase"] = phase
    if result_slots is not None and result_values is not None:
        vals = np.asarray(result_values)
        if vals.ndim == 2 and np.issubdtype(vals.dtype, np.integer):
            if vals.dtype != np.int32:
                # pre-packed planes in a wider dtype: coerce with the same
                # range check as _coerce_slot_planes — silent wraparound
                # would mint garbage order keys and mis-route conditions
                info = np.iinfo(np.int32)
                if ((vals < info.min) | (vals > info.max)).any():
                    raise ValueError("slot planes exceed int32 range")
                vals = vals.astype(np.int32)
        else:
            from zeebe_tpu.ops.tables import pack_slot_values

            vals = pack_slot_values(vals)  # float convenience → key planes
        inst = state["inst"][token_slots]
        new_state["var_slots"] = state["var_slots"].at[inst, result_slots].set(vals)
    return new_state
