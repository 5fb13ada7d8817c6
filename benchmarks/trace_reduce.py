"""From the profiler's ``.xplane.pb`` to numbers: device busy time, time by
XLA module and by operation, the longest idle gaps and what the host was in.
Reads the trace with ``jax.profiler.ProfileData`` — nothing but JAX — into a
plain form (``load``), so that the arithmetic (``reduce``) can be checked on a
small recorded trace kept as JSON beside the tests."""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_ANNOTATION = "zeebe.kernel_chunk"


def short_name(name: str) -> str:
    """An XLA op's event name is its whole HLO line; keep the result's name
    and the opcode: ``%fusion.303 fusion``. Module names lose their id:
    ``jit_run_collect(1262...)`` -> ``jit_run_collect``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return head.split("(", 1)[0][:64]
    opcode = re.search(r" ([a-z][a-z\-]*)\(", rest)
    return (head + (" " + opcode.group(1) if opcode else ""))[:64]


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]}`` — device planes whole, of the host plane only
    the program's own annotations."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name), int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(HOST_ANNOTATION)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _by_name(events: list) -> dict:
    out: dict = {}
    for name, _start, duration in events:
        out[name] = out.get(name, 0.0) + duration / 1e9
    return out


def _top(table: dict, n: int = 10) -> list:
    return [[name, seconds] for name, seconds in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: dict, window_s: float) -> dict:
    """``window_s``: the traced window on the host's clock. Returns, over
    the device planes found: ``busy_s`` (the union of the intervals in which
    an operation ran, averaged over the chips) and ``busy_s_by_chip``,
    ``module_s`` (summed device seconds by XLA module, all chips),
    ``device_ops`` and ``idle_gaps`` (top ten each), ``chips``."""
    host_spans = [(start, start + duration, name)
                  for plane in trace["planes"]
                  if not plane["name"].startswith(DEVICE_PLANE)
                  for line in plane["lines"]
                  for name, start, duration in line["events"]]
    busy_by_chip, module_s, op_s, gaps = {}, {}, {}, {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = _union([(s, s + d) for _n, s, d in ops])
        busy_by_chip[plane["name"]] = sum(e - s for s, e in busy) / 1e9
        for name, seconds in _by_name(lines.get(MODULES_LINE, [])).items():
            module_s[name] = module_s.get(name, 0.0) + seconds
        for name, seconds in _by_name(lines.get(OPS_LINE, [])).items():
            op_s[name] = op_s.get(name, 0.0) + seconds
        for (_s0, end), (start, _e1) in zip(busy, busy[1:]):
            middle = (end + start) // 2
            inside = [name for s, e, name in host_spans if s <= middle < e]
            what = inside[0] if inside else "unattributed"
            gaps[what] = gaps.get(what, 0.0) + (start - end) / 1e9
    chips = len(busy_by_chip)
    # no device plane: nothing to read, so no busy time and no idle share
    busy_s = sum(busy_by_chip.values()) / chips if chips else None
    return {"chips": chips, "busy_s": busy_s, "busy_s_by_chip": busy_by_chip,
            "window_s": window_s, "module_s": module_s,
            "device_ops": _top(op_s), "idle_gaps": _top(gaps)}
