"""The one module that chooses the device.

Every entry point (broker start-up, mesh construction, workers, ``bench.py``,
``chip_smoke.py``) asks here, in its own process: a chip belongs to one
process at a time, so a child that asked on the parent's behalf would be
refused the chip the parent holds. There is no fallback — a process that was
not told to use the CPU and finds no accelerator fails at start-up, where the
operator sees it, instead of serving from the host under a device's name.
"""

from __future__ import annotations


def cpu_requested() -> bool:
    """True when the CPU was asked for explicitly: ``JAX_PLATFORMS=cpu`` in
    the environment (jax reads it into ``jax_platforms``) or
    ``jax.config.update("jax_platforms", "cpu")`` already made in-process
    (``tests/conftest.py``, ``ZB_BENCH_CPU``)."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def devices() -> list:
    """``jax.devices()`` of the default backend. Raises when the backend
    cannot initialise, and when jax quietly settled for the CPU that nobody
    asked for."""
    import jax

    found = jax.devices()
    if found[0].platform == "cpu" and not cpu_requested():
        raise RuntimeError(
            "no accelerator answered and the CPU was not asked for "
            "(set JAX_PLATFORMS=cpu to run on the host)")
    return found


def host_device():
    """The host XLA device: where the shadow oracle re-executes a kernel
    group, whatever device the group itself ran on."""
    import jax

    return jax.devices("cpu")[0]
