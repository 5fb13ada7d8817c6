"""Link-aware kernel dispatch routing.

The automaton kernel is ONE XLA program; *where* a group of commands runs is
a deployment decision that depends on the host↔accelerator link as much as
on the program: every group pays a fixed number of small transfers (its
packed state up, its packed event rows back), and for a serving-sized group the
per-transfer latency floor, not the bandwidth, is what counts against the
same program compiled for the host XLA backend.

Rather than hard-coding an assumption, the router MEASURES the link once,
in-process, on the backend the entry point already brought up (a tiny
put+get round trip against the accelerator) and predicts each backend's
per-group cost: accelerator = transfers × measured link floor + an EMA of
the observed compute residue, host = EMA of observed group wall times per
shape bucket. Each group routes to the cheaper backend, with the measurement
exposed for observability instead of a silent assumption. (The reference
pins engine work to CPU threads and has no analogue of accelerator
placement.)
"""

from __future__ import annotations

import threading
import time
from typing import Any

__all__ = ["BackendRouter", "install_shared_router", "shared_router"]


class BackendRouter:
    """Chooses the execution device for one kernel group.

    ``choose(bucket)`` returns the device to run on (or None = process
    default, when routing is disabled because the default backend already IS
    the host). ``record(bucket, device, seconds)`` feeds observed group wall
    times back so the host-cost model tracks reality.
    """

    #: transfers per group on the accelerator path: the group's state goes
    #: up as one packed buffer (``run_collect_packed``) and its event rows
    #: come back in one fetch (a served group quiesces inside its first chunk)
    UPLOADS_PER_GROUP = 1
    FETCHES_PER_GROUP = 1
    #: below this predicted link cost the accelerator is effectively local
    #: and wins by default (host EMA not yet seated)
    LOCAL_LINK_S = 2e-3
    _EMA_ALPHA = 0.3

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._measured = False
        self._accel = None
        self._host = None
        self.enabled = False
        self.link_put_s: float | None = None
        self.link_get_s: float | None = None
        # host-vs-device routing threshold (ISSUE 12): the accelerator must
        # beat the host prediction by at least this margin to win a group —
        # raising it biases groups host-ward (the kernel-routing controller
        # raises it during XLA recompile storms and decays it back to 0).
        # Runtime mutation belongs to that controller's actuator.
        self.route_threshold_s = 0.0
        self._host_ema: dict[Any, float] = {}
        self._accel_ema: dict[Any, float] = {}
        self.host_groups = 0
        self.accel_groups = 0

    # -- link measurement ---------------------------------------------------

    def _measure(self) -> None:
        """Measure the accelerator link in-process: the process that routes
        groups is the one that owns the chip, so nobody else can. The floor,
        not the bandwidth, is what dominates serving-sized groups: tiny
        (8 KB) payload, best of a few round trips after one that pays the
        first-transfer set-up."""
        import numpy as np

        import jax

        from zeebe_tpu.utils import backend

        self._measured = True
        accel = backend.devices()[0]
        host = backend.host_device()
        self._accel = accel
        self._host = host
        if accel.platform == "cpu":
            return  # default backend already the host: nothing to route
        probe = np.zeros(2048, np.int32)
        puts, gets = [], []
        for _ in range(5):
            t0 = time.perf_counter()
            on_device = jax.block_until_ready(jax.device_put(probe, accel))
            puts.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.device_get(on_device)
            gets.append(time.perf_counter() - t0)
        self.link_put_s, self.link_get_s = min(puts[1:]), min(gets[1:])
        self.enabled = True

    def link_cost_s(self) -> float | None:
        """Predicted accelerator link cost for one group (None = unmeasured)."""
        if self.link_put_s is None or self.link_get_s is None:
            return None
        return (self.UPLOADS_PER_GROUP * self.link_put_s
                + self.FETCHES_PER_GROUP * self.link_get_s)

    # -- routing --------------------------------------------------------------

    def accel_device(self):
        """The measured accelerator (None when routing is disabled — the
        process default backend already is the host). Quarantine canaries
        pin their dispatch here instead of asking :meth:`choose`: while
        QUARANTINED the kernel-routing controller holds
        ``route_threshold_s`` host-ward, and a canary the router quietly
        re-routes to the host would byte-match the host oracle by
        construction — re-proving the host, not the suspect device."""
        with self._lock:
            if not self._measured:
                self._measure()
            return self._accel if self.enabled else None

    def choose(self, bucket: Any):
        """Device for this group (None = process default device)."""
        with self._lock:
            if not self._measured:
                self._measure()
            if not self.enabled:
                return None
            link = self.link_cost_s()
            host_ema = self._host_ema.get(bucket)
            accel_total = (link + self._accel_ema.get(bucket, 0.0)
                           + self.route_threshold_s)
            if host_ema is None:
                # un-seated host model: only an effectively-local accelerator
                # skips the host trial run
                return self._accel if accel_total < self.LOCAL_LINK_S else self._host
            return self._accel if accel_total < host_ema else self._host

    def record(self, bucket: Any, device, seconds: float,
               first_run: bool = False) -> None:
        """``first_run``: first execution of this (program, shape) on this
        device — the observation includes XLA compilation, which is paid once
        and must not poison the steady-state cost model."""
        with self._lock:
            if device is self._accel:
                self.accel_groups += 1
                ema = self._accel_ema
                # observed accel time includes the link; keep the compute
                # residue so repeat predictions track real runs
                link = self.link_cost_s() or 0.0
                seconds = max(0.0, seconds - link)
            else:
                self.host_groups += 1
                ema = self._host_ema
            if first_run:
                return
            prev = ema.get(bucket)
            ema[bucket] = (seconds if prev is None
                           else prev + self._EMA_ALPHA * (seconds - prev))

    def stats(self) -> dict:
        return {
            "enabled": self.enabled,
            "link_put_ms": None if self.link_put_s is None else round(1e3 * self.link_put_s, 2),
            "link_get_ms": None if self.link_get_s is None else round(1e3 * self.link_get_s, 2),
            "route_threshold_ms": round(1e3 * self.route_threshold_s, 2),
            "host_groups": self.host_groups,
            "accel_groups": self.accel_groups,
        }


_shared: BackendRouter | None = None
_shared_lock = threading.Lock()


def shared_router() -> BackendRouter:
    """Process-wide router: the link measurement is paid once, shared by
    every partition's kernel backend."""
    global _shared
    with _shared_lock:
        if _shared is None:
            _shared = BackendRouter()
        return _shared


def install_shared_router(router: BackendRouter) -> None:
    """Replace the process-wide router before any partition asks for it
    (``chip_smoke.py`` holds every group on the accelerator this way: it
    tests the chip, not the routing rule)."""
    global _shared
    with _shared_lock:
        _shared = router
