"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The tests run on the CPU: sharding/pjit paths are validated on host-platform
virtual devices (``__graft_entry__.dryrun_multichip`` dry-runs the same), and
the chip itself is exercised by ``chip_smoke.py`` through the chip tool. The
``jax.config.update`` below is the explicit CPU request ``utils/backend``
honours, whether or not the caller exported ``JAX_PLATFORMS=cpu``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# ZEEBE_SANITIZE=1: wrap ZbDb/journal/flight-recorder with single-writer and
# reentrancy assertions for this run (zeebe_tpu/testing/sanitizer.py) — CI
# runs the fast engine/state slice under it so latent cross-thread races
# fail deterministically instead of corrupting state silently
from zeebe_tpu.testing.sanitizer import maybe_install  # noqa: E402

maybe_install()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration tests")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection tests (zeebe_tpu.testing.chaos); "
        "failures print the active fault seed for reproduction",
    )


@pytest.fixture(autouse=True)
def _reset_chaos_seed(request):
    """A chaos test failing BEFORE it builds its ChaosNetwork must not report
    the previous test's seed — clear the global at setup."""
    if request.node.get_closest_marker("chaos") is not None:
        try:
            from zeebe_tpu.testing import chaos

            chaos._ACTIVE_SEED = None
        except Exception:
            pass
    yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On a chaos-test failure, print the active fault seed so the randomized
    run is reproducible: FaultPlan(seed=<printed seed>). Gated on the marker —
    a stale seed from an earlier chaos test must not decorate unrelated
    failures."""
    outcome = yield
    report = outcome.get_result()
    if (report.when == "call" and report.failed
            and item.get_closest_marker("chaos") is not None):
        try:
            from zeebe_tpu.testing.chaos import active_fault_seed

            seed = active_fault_seed()
        except Exception:
            seed = None
        if seed is not None:
            report.sections.append((
                "chaos fault seed",
                f"active fault seed: {seed} — reproduce with "
                f"FaultPlan(seed={seed})",
            ))
