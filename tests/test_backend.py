"""Start-up and device selection: one in-process resolver with no fallback,
a compile cache that can be placed, a codec built from the source it sits
beside, and a shadow oracle that runs on the host whatever the router says.
"""

from __future__ import annotations

import os
import shutil
from pathlib import Path

import pytest

from zeebe_tpu.utils import backend, xla_cache

REPO = Path(__file__).resolve().parent.parent


class _Device:
    def __init__(self, platform: str) -> None:
        self.platform = platform
        self.device_kind = platform


# -- the resolver --------------------------------------------------------------


def test_resolver_raises_when_jax_settled_for_a_cpu_nobody_asked_for(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Device("cpu")])
    monkeypatch.setattr(backend, "cpu_requested", lambda: False)
    with pytest.raises(RuntimeError, match="CPU was not asked for"):
        backend.devices()


def test_resolver_raises_what_the_backend_raises(monkeypatch):
    # a chip another process holds: no retry, no pinning, no CPU instead
    import jax

    def taken(*_a):
        raise RuntimeError("Unable to initialize backend 'tpu': in use")

    monkeypatch.setattr(jax, "devices", taken)
    with pytest.raises(RuntimeError, match="in use"):
        backend.devices()
    assert jax.config.jax_platforms == "cpu"  # untouched by the failure


def test_resolver_honours_an_explicit_cpu_request():
    # tests/conftest.py asked for the CPU in-process
    assert backend.cpu_requested()
    found = backend.devices()
    assert found and all(d.platform == "cpu" for d in found)
    assert backend.host_device().platform == "cpu"


def test_an_accelerator_first_platform_list_is_no_cpu_request(monkeypatch):
    # the chip machine's own setting: the CPU is listed (the shadow oracle
    # needs it) but not asked for
    import types

    import jax

    monkeypatch.setattr(jax, "config",
                        types.SimpleNamespace(jax_platforms="tpu,cpu"))
    assert not backend.cpu_requested()


def test_worker_exits_nonzero_when_its_device_is_taken(monkeypatch, capsys):
    from zeebe_tpu.multiproc import worker

    def taken():
        raise RuntimeError("Unable to initialize backend 'tpu': in use")

    monkeypatch.setattr(backend, "devices", taken)
    monkeypatch.setattr(xla_cache, "enable_persistent_cache", lambda: "")
    rc = worker.main(["--node-id", "w0", "--bind", "127.0.0.1:1",
                      "--contact", "w0=127.0.0.1:1,gw=127.0.0.1:2",
                      "--gateway", "gw"])
    assert rc != 0
    assert "no device for this worker" in capsys.readouterr().err


# -- the compile cache ---------------------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    import jax

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    return updates


def test_cache_dir_untouched_in_code_when_the_variable_is_set(
        monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert xla_cache.enable_persistent_cache() == "/x"
    assert "jax_compilation_cache_dir" not in config_updates
    assert "jax_persistent_cache_min_compile_time_secs" in config_updates


def test_cache_dir_is_the_checkouts_when_the_variable_is_not_set(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    placed = xla_cache.enable_persistent_cache()
    assert placed == str(REPO / ".xla_cache")
    assert config_updates["jax_compilation_cache_dir"] == placed
    # the same path in every process and run: nothing of this one in it
    assert str(os.getpid()) not in placed


# -- the native codec ----------------------------------------------------------


def test_codec_rebuilds_when_the_sources_hash_changes(monkeypatch, tmp_path):
    from zeebe_tpu import native

    if shutil.which(os.environ.get("CC", "gcc")) is None:
        pytest.skip("no C compiler")
    shutil.copy(Path(native._DIR) / "codec.c", tmp_path / "codec.c")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "BUILT_HERE", set())

    native._build_and_load("_zb_codec", "codec.c")
    (first,) = tmp_path.glob("_zb_codec.*.so")
    assert native.BUILT_HERE == {"_zb_codec"}

    # same source: the binary is reused, however old or new its mtime
    native.BUILT_HERE.clear()
    native._build_and_load("_zb_codec", "codec.c")
    assert native.BUILT_HERE == set()

    # a newer binary that arrived by copy, built from some other source,
    # is not trusted: only the name carrying this source's hash is loaded
    planted = tmp_path / "_zb_codec.0123456789abcdef.so"
    planted.write_bytes(b"not an ELF")
    with open(tmp_path / "codec.c", "a") as f:
        f.write("\n/* changed */\n")
    module = native._build_and_load("_zb_codec", "codec.c")
    (second,) = tmp_path.glob("_zb_codec.*.so")
    assert second != first and second != planted
    assert native.BUILT_HERE == {"_zb_codec"}
    assert callable(module.packb)


# -- the shadow oracle's device ------------------------------------------------


def test_shadow_oracle_runs_on_the_host_device_with_the_router_disabled(
        monkeypatch):
    """The oracle used to take its device from the router and, with the
    router disabled, from the process default — on a chip, the suspect
    itself. It is ``backend.host_device()`` whatever the router says."""
    import jax

    from zeebe_tpu.engine.device_health import (
        reset_shared_device_health,
        shared_device_health,
    )
    from zeebe_tpu.models.bpmn import Bpmn
    from zeebe_tpu.testing import EngineHarness

    reset_shared_device_health()
    shared_device_health().cfg.shadow_sample_rate = 1.0
    oracle_device = jax.devices()[3]  # a CPU device that is not the default
    monkeypatch.setattr(backend, "host_device", lambda: oracle_device)
    h = EngineHarness(use_kernel_backend=True)
    try:
        kernel = h.kernel_backend
        kernel.router = None  # routing disabled
        h.deploy(Bpmn.create_executable_process("p").start_event("s")
                 .service_task("t", job_type="w").end_event("e").done())
        h.create_instance("p")
        assert kernel.health.shadow_checks > 0
        assert kernel.health.shadow_mismatches == 0
        assert set(kernel.shadow_by_device) == {oracle_device}
        assert oracle_device.platform == "cpu"
        assert set(kernel.groups_by_device) == {jax.devices()[0]}
    finally:
        h.close()
        reset_shared_device_health()
