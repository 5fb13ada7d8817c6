"""Arrival schedules and latency arithmetic. Pure functions of their
arguments: nothing here reads a clock or knows how fast the system is.
(The exponential-gap arithmetic follows ``zeebe_tpu/testing/serving.py``'s
``poisson_schedule``; see PERF.md, Open questions.)"""

from __future__ import annotations

import math
import random


def poisson_offsets(rate_per_s: float, seconds: float, seed: int) -> list:
    """Due times, as offsets from the window's start, of an open loop at
    ``rate_per_s`` over ``seconds``.

    Every seed gets the same *set* of inter-arrival gaps — the
    ``n = rate * seconds`` mid-quantiles of the exponential distribution,
    scaled so that they fill the window exactly — in another order. So every
    run offers exactly ``n`` requests with Poisson-shaped bursts and lulls,
    and seeds differ in where those fall, not in how much work there is."""
    n = int(round(rate_per_s * seconds))
    if n <= 0:
        raise ValueError("the schedule holds no request")
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(gaps)
    random.Random(seed).shuffle(gaps)
    offsets, t = [], 0.0
    for gap in gaps:
        offsets.append(t)      # the first request is due at the window's start
        t += gap * scale
    return offsets


def fixed_offsets(rate_per_s: float, seconds: float) -> list:
    """Due times of a starter that fires at a fixed rate (as upstream's
    benchmark starter does, ``scheduleAtFixedRate``): ``rate * seconds``
    requests, evenly spaced, the first at the window's start."""
    n = int(round(rate_per_s * seconds))
    if n <= 0:
        raise ValueError("the schedule holds no request")
    return [i / rate_per_s for i in range(n)]


def offsets_of(loop: dict, seconds: float, seed: int) -> list:
    """The open loop's schedule by the mix's ``arrivals`` key."""
    arrivals = loop["arrivals"]
    if arrivals == "poisson":
        return poisson_offsets(float(loop["rate_per_s"]), seconds, seed)
    if arrivals == "fixed":
        return fixed_offsets(float(loop["rate_per_s"]), seconds)
    raise ValueError(f"unknown arrivals {arrivals!r}; known: fixed, poisson")


def percentile(latencies: list, q: float, attempted: int) -> float:
    """The ``q``-quantile (0 < q <= 1) over ``attempted`` requests of which
    only ``latencies`` were answered: a request that failed, was refused or
    never completed counts as missing, i.e. as slower than any answer — so
    when the rank falls among the missing the percentile is ``inf``.
    Nearest-rank: the smallest value with at least ``q`` of all attempted
    requests at or below it."""
    if attempted <= 0 or len(latencies) > attempted:
        raise ValueError("attempted must cover every latency")
    rank = max(1, math.ceil(q * attempted))
    if rank > len(latencies):
        return math.inf
    return sorted(latencies)[rank - 1]
