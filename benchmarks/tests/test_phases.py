"""What PR 26 added through the seams that were there: the kernel group's
phase annotations go through the unchanged reduction and name idle gaps, and
the cell finds the four metrics that split the ``device`` stage. The trace is
the first 0.45 s of a window recorded on a TPU v5e with the program's tracer
off (PR 26, cell default3x3.one_task_steady, seed 2600000011)."""

import json
from pathlib import Path

import pytest

import run
import trace_reduce

PHASES = json.loads((Path(__file__).parent / "data" / "trace_phases.json").read_text())
SPLIT = {"build_ms_per_group": "build",
         "device_dispatch_ms_per_group": "device_dispatch",
         "device_fetch_ms_per_group": "device_fetch",
         "device_unpack_ms_per_group": "device_unpack"}


def test_the_recorded_phases_are_the_programs_nine_at_most():
    names = {name for plane in PHASES["planes"]
             if not plane["name"].startswith(trace_reduce.DEVICE_PLANE)
             for line in plane["lines"] for name, _s, _d in line["events"]}
    assert names and len(names) <= 9
    assert all(n.startswith(trace_reduce.HOST_ANNOTATION + ".") for n in names)
    assert {"zeebe.kernel_chunk.dispatch", "zeebe.kernel_chunk.fetch",
            "zeebe.kernel_chunk.append"} <= names


def test_phase_names_come_out_in_the_idle_gaps():
    r = trace_reduce.reduce(PHASES, window_s=0.45)
    assert r["chips"] == 1 and 0 < r["busy_s"] < 0.001
    gaps = dict(r["idle_gaps"])
    named = {n for n in gaps if n.startswith("zeebe.kernel_chunk.")}
    assert "zeebe.kernel_chunk.append" in named
    # a gap no phase covers: no leader had a group in hand in its middle
    assert gaps["unattributed"] > 0.85 * sum(gaps.values())
    assert sum(gaps.values()) < 0.45


def test_a_gap_is_named_by_the_phase_its_middle_falls_in():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["a", 0, 1_000], ["a", 5_001_000, 1_000], ["a", 105_002_000, 1_000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["zeebe.kernel_chunk.fetch", 1_000, 1_500_000],
            ["zeebe.kernel_chunk.append", 2_000_000, 2_900_000]]}]},
    ]}
    gaps = dict(trace_reduce.reduce(trace, window_s=0.2)["idle_gaps"])
    assert gaps == {"zeebe.kernel_chunk.append": pytest.approx(0.005),
                    "unattributed": pytest.approx(0.1)}


def test_the_cell_finds_the_four_metrics_of_the_split():
    what = run.resolve_cell("default3x3.one_task_steady")
    found = {m["name"]: m for m in what["per_layer"]}
    assert set(SPLIT) <= set(found)
    counts = {"device_count": 10, "device_seconds": 0.05}
    for name, stage in SPLIT.items():
        m = found[name]
        assert (m["reader"], m["unit"], m["layer"], m["moves"]) == (
            "ratio", "ms", "kernel backend", "completion_p50_ms")
        read = run.load_reader(m["reader"])
        # a program without the histogram (the parent): nothing, not an error
        assert read({"counts": counts}, m["args"]) is None
        with_it = {**counts, f"{stage}_count": 10, f"{stage}_seconds": 0.02}
        assert read({"counts": with_it}, m["args"]) == pytest.approx(2.0)
