"""One packed upload a kernel group (ISSUE 29).

A single-device group's state crosses the link as ONE flat int32 buffer and
comes back from each chunk as one (``ops/automaton.run_collect_packed``).
What must hold: the compiled program unpacks exactly the planes the host
filled; the served path still writes the sequential engine's log, with the
shadow oracle agreeing on every group; a group uploads once however many
chunks it needs; and one program serves first and later chunks, so a second
chunk inside a measured window compiles nothing.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from zeebe_tpu.models.bpmn import Bpmn
from zeebe_tpu.testing import EngineHarness

HOST_FILLED = ("elem", "phase", "inst", "def_of", "var_slots", "join_counts",
               "mi_left", "done")
DEVICE_ZEROED = ("incident", "transitions", "jobs_created", "completed",
                 "overflow")


def ten_tasks(pid="ten_tasks"):
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(10):
        b = b.service_task(f"t{i}", job_type="work")
    return b.end_event("e").done()


def long_cascade(pid="long_cascade"):
    """Five pass-through elements before the first wait: with two steps a
    chunk its creation needs a second chunk where ten_tasks' needs one."""
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(5):
        b = b.manual_task(f"m{i}")
    return b.service_task("t", job_type="work").end_event("e").done()


# ---------------------------------------------------------------------------
# (i) what the program unpacks is what the host filled


def _fake_group(rng, n_real, slots, elements, token_width, resumed_share):
    """Admitted instances as admission leaves them, and a table set of the
    given widths: enough of both for ``_build_group_arrays``."""
    from zeebe_tpu.engine.kernel_backend import _Admitted, _Inst, _Token

    names = {f"v{i}": i for i in range(slots)}
    tables = SimpleNamespace(
        token_width=token_width, max_elements=elements, num_slots=slots,
        slot_map=SimpleNamespace(names=names),
        start_elem=rng.integers(0, elements, 4).astype(np.int32))
    admitted = []
    for idx in range(n_real):
        info = SimpleNamespace(index=int(rng.integers(0, 4)))
        inst = _Inst(idx=idx, info=info, new=rng.random() >= resumed_share)
        if not inst.new:
            inst.tokens = [
                _Token(slot=-1, elem_idx=int(rng.integers(0, elements)), key=7,
                       value={}, phase=int(rng.integers(0, 3)))
                for _ in range(int(rng.integers(1, token_width + 1)))]
        inst.slots = {name: (int(rng.integers(-2**31, 2**31)),
                             int(rng.integers(-2**31, 2**31)))
                      for name in names if rng.random() < 0.5}
        inst.join_counts = {int(rng.integers(0, elements)): int(rng.integers(1, 4))
                            for _ in range(2)}
        inst.mi_left = {int(rng.integers(0, elements)): int(rng.integers(1, 9))}
        admitted.append(_Admitted(cmd=None, inst=inst))
    return admitted, tables


def _reference_arrays(admitted, tables, I, T):
    """The eight arrays as the dispatch built them before they shared a
    buffer, one allocation each, ``done`` a bool plane: the loop kept as the
    reference. Runs after ``_build_group_arrays``, which assigns a new
    instance its start token."""
    S, E = tables.num_slots, tables.max_elements
    ref = {"elem": np.full(T, -1, np.int32), "phase": np.zeros(T, np.int32),
           "inst": np.zeros(T, np.int32), "def_of": np.zeros(I, np.int32),
           "var_slots": np.zeros((I, S, 2), np.int32),
           "join_counts": np.zeros((I, E), np.int32),
           "mi_left": np.zeros((I, E), np.int32),
           "done": np.zeros(I, np.bool_)}
    ref["done"][len(admitted):] = True
    slot = 0
    for adm in admitted:
        i = adm.inst
        ref["def_of"][i.idx] = i.info.index
        for name, planes in i.slots.items():
            ref["var_slots"][i.idx, tables.slot_map.names[name]] = planes
        for row, count in i.join_counts.items():
            ref["join_counts"][i.idx, row] = count
        for row, left in i.mi_left.items():
            ref["mi_left"][i.idx, row] = left
        for tok in i.tokens:
            ref["elem"][slot] = tok.elem_idx
            ref["phase"][slot] = tok.phase
            ref["inst"][slot] = i.idx
            slot += 1
    return ref


@pytest.mark.parametrize("n_real,slots,elements,token_width,resumed_share", [
    pytest.param(1, 1, 13, 1, 0.0, id="I64-one-new-instance-63-padding-rows"),
    pytest.param(64, 1, 13, 1, 1.0, id="I64-full-all-resumed"),
    pytest.param(37, 12, 96, 3, 0.5, id="I64-S12-E96-new-and-resumed"),
    pytest.param(5, 1, 4, 2, 0.5, id="I64-S1-E4-two-tokens-an-instance"),
    pytest.param(65, 1, 13, 1, 0.5, id="I2048-one-past-the-small-bucket"),
    pytest.param(2048, 6, 40, 2, 0.7, id="I2048-full-S6-E40"),
])
def test_program_unpacks_what_the_host_filled(n_real, slots, elements,
                                              token_width, resumed_share):
    import jax

    from zeebe_tpu.engine.kernel_backend import KernelBackend
    from zeebe_tpu.ops.automaton import packed_state_layout, unpack_state

    rng = np.random.default_rng(n_real * 1000 + slots)
    admitted, tables = _fake_group(rng, n_real, slots, elements, token_width,
                                   resumed_share)
    backend = KernelBackend.__new__(KernelBackend)
    backend.registry = SimpleNamespace(tables=tables)
    backend.max_group = 2048
    packed, arrays, I, T = backend._build_group_arrays(admitted)
    assert I == (64 if n_real <= 64 else 2048)
    geometry = (I, T, slots, elements)
    assert packed.dtype == np.int32
    assert packed.shape == (packed_state_layout(geometry)[1],)
    ref = _reference_arrays(admitted, tables, I, T)

    # the named views the mesh runner takes are windows on the one buffer
    for name in HOST_FILLED:
        assert np.shares_memory(arrays[name], packed), name
        assert arrays[name].shape == ref[name].shape, name
        assert np.array_equal(arrays[name], ref[name]), name

    state = jax.device_get(jax.jit(unpack_state, static_argnums=1)(packed, geometry))
    assert set(state) == set(HOST_FILLED) | set(DEVICE_ZEROED)
    for name in HOST_FILLED:
        assert state[name].dtype == ref[name].dtype, name
        assert state[name].tobytes() == ref[name].tobytes(), name
    for name in DEVICE_ZEROED:
        assert not state[name].any(), name
    assert state["incident"].dtype == np.bool_ and state["incident"].shape == (I,)
    assert state["overflow"].dtype == np.bool_ and state["overflow"].shape == ()


def test_a_buffer_of_another_geometry_is_refused():
    from zeebe_tpu.ops.automaton import packed_state_layout, packed_state_views

    buffer = np.zeros(packed_state_layout((64, 64, 1, 13))[1], np.int32)
    with pytest.raises(ValueError, match="does not fit geometry"):
        packed_state_views(buffer, (64, 64, 2, 13))


def test_packed_entry_steps_as_run_collect_does():
    """The two jitted entries share the loop: same event rows, same state,
    first chunk (host buffer) and second (device carry)."""
    import jax

    from zeebe_tpu.ops.automaton import (
        DeviceTables,
        make_state,
        pack_state,
        run_collect,
        run_collect_packed,
        unpack_state,
    )
    from zeebe_tpu.models.bpmn import transform
    from zeebe_tpu.ops.tables import compile_tables

    tables = compile_tables([transform(long_cascade())])
    I, T = 8, 16
    state = make_state(tables, I, np.zeros(I, np.int32), token_capacity=T)
    geometry = (I, T, tables.num_slots, tables.max_elements)
    dt = DeviceTables.from_tables(tables)
    packed = np.asarray(pack_state(state))
    for _chunk in range(2):
        state, rows = run_collect(dt, state, n_steps=2,
                                  config=tables.kernel_config)
        packed, packed_rows = run_collect_packed(
            dt, packed, geometry=geometry, n_steps=2,
            config=tables.kernel_config)
        assert np.array_equal(jax.device_get(rows), jax.device_get(packed_rows))
        unpacked = jax.device_get(unpack_state(packed, geometry))
        for name, plane in jax.device_get(state).items():
            assert unpacked[name].dtype == plane.dtype, name
            assert np.array_equal(unpacked[name], plane), name


# ---------------------------------------------------------------------------
# (ii) the served path: the sequential engine's log, the oracle agreeing, one
# upload a group whatever the number of chunks


def _drive_ten_tasks(h):
    h.deploy(ten_tasks())
    for request_id in range(1, 4):
        h.create_instance("ten_tasks", {"x": request_id}, request_id=request_id)
    for _task in range(10):
        for job in h.activate_jobs("work", max_jobs=10):
            h.complete_job(job["key"], {"y": 2})


def _log(h):
    from tests.test_kernel_backend import log_fingerprint

    return log_fingerprint(h)


@pytest.fixture
def shadow_every_group():
    from zeebe_tpu.engine.device_health import (
        reset_shared_device_health,
        shared_device_health,
    )

    reset_shared_device_health()
    shared_device_health().cfg.shadow_sample_rate = 1.0
    yield
    reset_shared_device_health()


@pytest.mark.parametrize("chunk_steps,chunks_a_group", [
    pytest.param(8, 1, id="one-chunk-a-group"),
    pytest.param(1, 2, id="every-group-needs-a-second-chunk"),
])
def test_served_ten_tasks_uploads_once_a_group(shadow_every_group, chunk_steps,
                                               chunks_a_group):
    sequential = EngineHarness()
    try:
        _drive_ten_tasks(sequential)
        expected = _log(sequential)
    finally:
        sequential.close()

    h = EngineHarness(use_kernel_backend=True)
    chunks = []
    backend = h.kernel_backend
    backend.chunk_steps = chunk_steps
    noted = backend.note_group_success
    backend.note_group_success = lambda pg: (chunks.append(pg.chunks_run),
                                             noted(pg))[1]
    uploads = h.processor._m_device_uploads
    before = (uploads.sum, uploads.count)
    try:
        _drive_ten_tasks(h)
        assert _log(h) == expected
        groups = uploads.count - before[1]
        assert groups == len(chunks) > 0
        assert set(chunks) == {chunks_a_group}
        assert (uploads.sum - before[0]) / groups == 1.0
        assert backend.health.shadow_checks == groups
        assert backend.health.shadow_mismatches == 0
        assert backend.accounting.kernel_records > 0
    finally:
        h.close()


# ---------------------------------------------------------------------------
# (iii) nothing is left to compile for a later second chunk


def two_lengths(pid="two_lengths"):
    """One definition, two cascades: at three steps a chunk ``x <= 10``
    reaches its task inside the first chunk, ``x > 10`` passes five manual
    tasks first and needs three. One definition, so one table set and one
    bucket: the registry compiles a new set when a definition joins it."""
    b = (Bpmn.create_executable_process(pid).start_event("s")
         .exclusive_gateway("gw").condition_expression("x > 10"))
    for i in range(5):
        b = b.manual_task(f"m{i}")
    return (b.service_task("t_long", job_type="work").end_event("e1")
            .move_to_element("gw").default_flow()
            .service_task("t_short", job_type="work").end_event("e2").done())


def test_a_later_second_chunk_compiles_nothing():
    """What ``compiles_in_window`` reads (jax.monitoring's backend-compile
    events; a persistent-cache hit counted too, should another test of this
    process have turned that cache on) does not move across a group's first
    second chunk: the carry runs the program the host buffer compiled."""
    import jax.monitoring as monitoring
    from jax._src.monitoring import (
        unregister_event_duration_listener,
        unregister_event_listener,
    )

    requests = []

    def on_duration(event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            requests.append(event)

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            requests.append(event)

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    h = EngineHarness(use_kernel_backend=True)
    chunks = []
    backend = h.kernel_backend
    backend.chunk_steps = 3
    noted = backend.note_group_success
    backend.note_group_success = lambda pg: (chunks.append(pg.chunks_run),
                                             noted(pg))[1]
    try:
        h.deploy(two_lengths())
        h.create_instance("two_lengths", {"x": 1})
        assert chunks == [1]
        after_first_dispatch = len(requests)
        assert after_first_dispatch >= 1      # the bucket's one program
        h.create_instance("two_lengths", {"x": 25})
        assert chunks == [1, 3]               # two chunks off the device carry
        assert len(requests) == after_first_dispatch
    finally:
        h.close()
        unregister_event_duration_listener(on_duration)
        unregister_event_listener(on_event)


# ---------------------------------------------------------------------------
# the program's text is the persistent compile cache's key


_LOWER_AND_DIGEST = """
import hashlib
import numpy as np
from zeebe_tpu.models.bpmn import Bpmn, transform
from zeebe_tpu.ops.automaton import (
    DeviceTables, make_state, pack_state, run_collect_packed)
from zeebe_tpu.ops.tables import compile_tables

process = (Bpmn.create_executable_process("p").start_event("s")
           .service_task("t", job_type="w").end_event("e").done())
tables = compile_tables([transform(process)])
state = make_state(tables, 64, np.zeros(64, np.int32), token_capacity=64)
lowered = run_collect_packed.lower(
    DeviceTables.from_tables(tables), np.asarray(pack_state(state)),
    geometry=(64, 64, tables.num_slots, tables.max_elements), n_steps=8,
    config=tables.kernel_config)
print("digest", hashlib.sha256(lowered.as_text().encode()).hexdigest())
"""


def test_program_text_does_not_move_with_the_hash_seed():
    """A process's string hashes are seeded at its start. Were anything
    traced in the order of a set of names, each broker start would lower
    another text, miss the persistent compile cache and pay the compile in
    its set-up (seen on the chip: 2 s of ``setup_s`` in two runs of three)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    digests = set()
    for seed in ("1", "2"):  # a set of the three bool planes' names differs
        env = {**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": str(Path(__file__).resolve().parents[1])}
        out = subprocess.run([sys.executable, "-c", _LOWER_AND_DIGEST], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        digests.add(out.stdout.split("digest ")[1].strip())
    assert len(digests) == 1
