"""Mesh sharding: partitions = shards of the instance/token axis.

The reference scales by hash-sharding process instances across Raft
partitions (SURVEY.md §2.13 data parallelism); here a partition maps to a
shard of the device mesh. Each shard owns a disjoint instance range and its
token pool, so the automaton step is embarrassingly parallel — the only
cross-shard traffic is the psum of global counters (and, later, message
correlation rides the same axis with an all_to_all). Collectives stay on ICI;
the host control plane (log, Raft, gRPC) never sees device internals.

Tables are replicated (they are small and read-only); state arrays shard on
axis 0. Works identically on a real TPU mesh and on the CPU host-device mesh
used in tests/dryrun.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from zeebe_tpu.ops.automaton import DeviceTables, step
from zeebe_tpu.utils import backend


#: the mesh's single axis: partitions = shards of the batch axis
#: (SNIPPETS.md [1]: ``NamedSharding(mesh, PartitionSpec("batch"))``)
BATCH_AXIS = "batch"


def make_mesh(n_devices: int | None = None) -> Mesh:
    devices = backend.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            # truncating silently would mismatch callers' shard-block state
            # layout (num_shards=n) and corrupt instance indexing
            raise ValueError(
                f"requested a {n_devices}-device mesh but only {len(devices)} "
                "devices are available"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (BATCH_AXIS,))


_SHARDED_KEYS = ("elem", "phase", "inst", "def_of", "var_slots", "join_counts",
                 "mi_left", "done", "incident")
_REPLICATED_KEYS = ("transitions", "jobs_created", "completed", "overflow")


def state_specs() -> dict:
    specs = {k: P(BATCH_AXIS) for k in _SHARDED_KEYS}
    specs.update({k: P() for k in _REPLICATED_KEYS})
    return specs


def shard_state(state: dict, mesh: Mesh) -> dict:
    """Place a host-built state dict onto the mesh (instances must already be
    grouped so each shard's tokens reference only its own instances — true
    for make_state's identity layout when I and T are multiples of the mesh)."""
    specs = state_specs()
    return {
        key: jax.device_put(value, NamedSharding(mesh, specs[key]))
        for key, value in state.items()
    }


def make_sharded_step(mesh: Mesh, auto_jobs: bool = True, config=None):
    """A pjit-compiled, shard_mapped step: per-shard automaton advance with
    psum'd global counters. Instances never cross shards (partition
    semantics), so the kernel body runs unchanged on local shapes."""

    specs = state_specs()

    def local_step(tables: DeviceTables, state: dict) -> dict:
        new_state, _ = step(tables, state, auto_jobs=auto_jobs, emit_events=False, config=config)
        # counters: psum the per-shard delta so the replicated value stays global
        for key in ("transitions", "jobs_created", "completed"):
            delta = new_state[key] - state[key]
            new_state[key] = state[key] + jax.lax.psum(delta, BATCH_AXIS)
        overflow_any = jax.lax.psum(
            new_state["overflow"].astype(jax.numpy.int32), BATCH_AXIS) > 0
        new_state["overflow"] = overflow_any
        return new_state

    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            DeviceTables(**{name: P() for name in DeviceTables.__dataclass_fields__}),
            specs,
        ),
        out_specs=specs,
        check_vma=False,
    )
    return jax.jit(sharded)
