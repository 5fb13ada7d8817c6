"""The served path's device programs must compile for the real chip.

Every other test runs on XLA's CPU backend, which accepts programs the TPU
compiler refuses (sort/scatter forms, 64-bit types, layouts, memory). These
cases hand the installed TPU compiler a *described* ``v5e:2x2`` — no chip
attached, ``JAX_PLATFORMS=cpu`` stays set — and compile the programs the
broker dispatches, at the shapes it dispatches them (``broker/partition.py``:
``max_group=2048``, ``chunk_steps=8``; buckets from
``KernelBackend._build_group_arrays``). Nothing runs: a pass says the chip's
compiler takes the program, not that its results are right (the byte-parity
suites and ``chip_smoke.py`` say that).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold libtpu, and every xdist worker imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

CHUNK_STEPS = 8  # broker/partition.py
SMALL_GROUP, MAX_GROUP = 64, 2048  # the two shape buckets of a partition
MESH_CHIPS = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    from jax.sharding import Mesh

    from zeebe_tpu.parallel.mesh import BATCH_AXIS

    assert len(topo.devices) == MESH_CHIPS
    return Mesh(np.array(topo.devices), (BATCH_AXIS,))


@pytest.fixture(scope="module")
def table_sets():
    """The compiled table sets exactly as a partition's registry builds
    them: deploy + first touch through an engine with the kernel backend."""
    import chip_smoke
    from zeebe_tpu.testing import EngineHarness
    from zeebe_tpu.testing.workloads import one_task

    def registry_tables(models):
        h = EngineHarness(use_kernel_backend=True)
        try:
            h.deploy(*models)
            for m in models:
                h.create_instance(m.process_id, variables={"x": 25})
            registry = h.kernel_backend.registry
            assert len(registry._infos) == len(models), registry._ineligible
            return registry.tables
        finally:
            h.close()

    mixed = registry_tables(chip_smoke.mixed_definitions())
    cfg = mixed.kernel_config
    # the point of the mixed set: stack VM, join sort and scope reduction
    assert cfg.has_conditions and cfg.has_joins and cfg.has_scopes
    return {"one_task": registry_tables([one_task()]), "mixed9": mixed}


def _abstract(tree, sharding, scalars_as=()):
    """``tree``'s arrays as ShapeDtypeStructs placed by ``sharding``."""
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape or scalars_as, a.dtype, sharding=sharding), tree)


def _group_shapes(tables, instances: int, shards: int = 1):
    """(DeviceTables, state) of one group bucket as concrete host-built
    arrays — the same constructors the kernel path uses, so dtypes and
    shapes cannot drift from the code under test."""
    from zeebe_tpu.engine.kernel_backend import KernelBackend
    from zeebe_tpu.ops.automaton import DeviceTables, make_state

    tokens = KernelBackend._pow2(tables.token_width * instances)
    state = make_state(tables, instances * shards,
                       np.zeros(instances * shards, np.int32),
                       token_capacity=tokens * shards, num_shards=shards)
    return DeviceTables.from_tables(tables), state


def _one_chip_args(tables, instances, one_chip):
    dt, state = _group_shapes(tables, instances)
    return _abstract(dt, one_chip), _abstract(state, one_chip)


def _compile_run_collect(set_name, instances, table_sets, one_chip, mesh):
    from zeebe_tpu.ops.automaton import run_collect

    tables = table_sets[set_name]
    dt, state = _one_chip_args(tables, instances, one_chip)
    return run_collect.lower(dt, state, n_steps=CHUNK_STEPS,
                             config=tables.kernel_config).compile()


def _compile_run_collect_packed(set_name, instances, table_sets, one_chip, mesh):
    """The served path's entry: the group's state as one int32 buffer, the
    geometry static (``KernelBackend._run_chunk``)."""
    import jax

    from zeebe_tpu.ops.automaton import packed_state_layout, run_collect_packed

    tables = table_sets[set_name]
    dt, state = _one_chip_args(tables, instances, one_chip)
    geometry = (instances, state["elem"].shape[0], tables.num_slots,
                tables.max_elements)
    packed = jax.ShapeDtypeStruct((packed_state_layout(geometry)[1],), np.int32,
                                  sharding=one_chip)
    return run_collect_packed.lower(dt, packed, geometry=geometry,
                                    n_steps=CHUNK_STEPS,
                                    config=tables.kernel_config).compile()


def _compile_step(set_name, instances, table_sets, one_chip, mesh):
    from zeebe_tpu.ops.automaton import step

    tables = table_sets[set_name]
    dt, state = _one_chip_args(tables, instances, one_chip)
    return step.lower(dt, state, auto_jobs=True, emit_events=False,
                      config=tables.kernel_config).compile()


def _compile_run_to_completion(set_name, instances, table_sets, one_chip, mesh):
    from zeebe_tpu.ops.automaton import run_to_completion

    tables = table_sets[set_name]
    dt, state = _one_chip_args(tables, instances, one_chip)
    return run_to_completion.lower(dt, state, max_steps=1000, auto_jobs=True,
                                   config=tables.kernel_config).compile()


def _compile_decision(_set_name, contexts, table_sets, one_chip, mesh):
    """``ops/decision._evaluate_batch`` over an eight-rule FIRST-hit table."""
    import jax

    from zeebe_tpu.dmn import parse_dmn_xml
    from zeebe_tpu.ops import decision

    rules = "".join(
        f'<rule id="r{i}">'
        f"<inputEntry><text>[{i * 10}..{i * 10 + 9}]</text></inputEntry>"
        f'<inputEntry><text>{"&quot;gold&quot;" if i % 2 else "-"}</text></inputEntry>'
        f"<outputEntry><text>{i}</text></outputEntry></rule>"
        for i in range(8))
    xml = f"""<?xml version="1.0" encoding="UTF-8"?>
<definitions xmlns="https://www.omg.org/spec/DMN/20191111/MODEL/"
             id="b" name="b" namespace="bench">
  <decision id="band" name="band"><decisionTable hitPolicy="FIRST">
    <input id="i1"><inputExpression><text>amount</text></inputExpression></input>
    <input id="i2"><inputExpression><text>tier</text></inputExpression></input>
    <output id="o1" name="band"/>{rules}
  </decisionTable></decision>
</definitions>"""
    table = decision.compile_decision_table(
        parse_dmn_xml(xml).decisions["band"])
    keys, valid = table.pack_contexts([{"amount": 1.0, "tier": "gold"}])

    def abstract(a, rows=None):
        shape = a.shape if rows is None else (rows, *a.shape[1:])
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    return decision._evaluate_batch.lower(
        abstract(table.kind), abstract(table.lo), abstract(table.hi),
        abstract(table.flags), abstract(keys, contexts),
        abstract(valid, contexts)).compile()


def _compile_mesh_collect(set_name, instances, table_sets, one_chip, mesh):
    """The sharded program of ``MeshKernelRunner._sharded_collect`` over the
    four described chips, arguments placed as ``_dispatch`` places them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from zeebe_tpu.parallel.mesh import BATCH_AXIS
    from zeebe_tpu.parallel.mesh_runner import MeshKernelRunner

    tables = table_sets[set_name]
    dt, state = _group_shapes(tables, instances, shards=MESH_CHIPS)
    # per-shard scalar tails ride as length-S rows (mesh_runner._dispatch)
    state = _abstract(state, NamedSharding(mesh, P(BATCH_AXIS)),
                      scalars_as=(MESH_CHIPS,))
    dt = _abstract(dt, NamedSharding(mesh, P()))
    collect = MeshKernelRunner(mesh=mesh)._sharded_collect(
        CHUNK_STEPS, tables.kernel_config)
    compiled = collect.lower(dt, state).compile()
    # partitions never interact: a collective here would be a sharding bug
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
        assert op not in text, f"unexpected {op} in the sharded program"
    return compiled


@pytest.mark.parametrize("build,set_name,size", [
    pytest.param(_compile_run_collect, "one_task", SMALL_GROUP,
                 id="run_collect-one_task-I64"),
    pytest.param(_compile_run_collect, "one_task", MAX_GROUP,
                 id="run_collect-one_task-I2048"),
    pytest.param(_compile_run_collect, "mixed9", SMALL_GROUP,
                 id="run_collect-mixed9-I64"),
    pytest.param(_compile_run_collect, "mixed9", MAX_GROUP,
                 id="run_collect-mixed9-I2048"),
    pytest.param(_compile_run_collect_packed, "one_task", SMALL_GROUP,
                 id="run_collect_packed-one_task-I64"),
    pytest.param(_compile_run_collect_packed, "one_task", MAX_GROUP,
                 id="run_collect_packed-one_task-I2048"),
    pytest.param(_compile_run_collect_packed, "mixed9", SMALL_GROUP,
                 id="run_collect_packed-mixed9-I64"),
    pytest.param(_compile_run_collect_packed, "mixed9", MAX_GROUP,
                 id="run_collect_packed-mixed9-I2048"),
    pytest.param(_compile_step, "one_task", MAX_GROUP,
                 id="step-one_task-I2048"),
    pytest.param(_compile_run_to_completion, "one_task", MAX_GROUP,
                 id="run_to_completion-one_task-I2048"),
    pytest.param(_compile_decision, "-", 200_000,
                 id="decision-evaluate_batch-N200000"),
    pytest.param(_compile_mesh_collect, "one_task", MAX_GROUP,
                 id="mesh_collect-one_task-4xI2048"),
    pytest.param(_compile_mesh_collect, "mixed9", SMALL_GROUP,
                 id="mesh_collect-mixed9-4xI64"),
])
def test_compiles_for_v5e(build, set_name, size, table_sets, one_chip, mesh):
    compiled = build(set_name, size, table_sets, one_chip, mesh)
    memory = compiled.memory_analysis()
    # one v5e chip holds 16 GB; a group program anywhere near that is a bug
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes < 2 << 30
