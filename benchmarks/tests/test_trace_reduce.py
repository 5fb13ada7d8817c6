"""The reduction from trace to numbers, on synthetic planes and on a small
trace recorded on a TPU v5e (PR 25, cell single1x3.mixed9_closed, 30 ms)."""

import json
from pathlib import Path

import pytest

import trace_reduce

SMALL = json.loads((Path(__file__).parent / "data" / "trace_small.json").read_text())


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k.replace("_", " "), "events": v}
                                    for k, v in lines.items()]}


def test_busy_is_the_union_and_gaps_go_to_the_host_span():
    trace = {"planes": [
        plane("/device:TPU:0",
              XLA_Ops=[["a", 0, 1_000_000], ["b", 500_000, 1_000_000],   # overlap
                       ["a", 4_000_000, 1_000_000]],
              XLA_Modules=[["jit_run_collect", 0, 1_500_000],
                           ["jit_run_collect", 4_000_000, 1_000_000]]),
        plane("/device:TPU:1", XLA_Ops=[["a", 0, 500_000]]),
        plane("/host:CPU", python=[["zeebe.kernel_chunk0", 2_000_000, 1_500_000]]),
    ]}
    r = trace_reduce.reduce(trace, window_s=0.01)
    assert r["chips"] == 2
    assert r["busy_s_by_chip"]["/device:TPU:0"] == pytest.approx(0.0025)
    assert r["busy_s"] == pytest.approx((0.0025 + 0.0005) / 2)
    assert r["module_s"] == {"jit_run_collect": pytest.approx(0.0025)}
    assert r["device_ops"][0] == ["a", pytest.approx(0.0025)]
    assert r["idle_gaps"] == [["zeebe.kernel_chunk0", pytest.approx(0.0025)]]


def test_no_device_plane_reads_nothing():
    r = trace_reduce.reduce({"planes": []}, window_s=1.0)
    assert r["chips"] == 0 and r["busy_s"] is None and r["module_s"] == {}


def test_the_recorded_trace():
    r = trace_reduce.reduce(SMALL, window_s=0.030)
    assert r["chips"] == 1
    # two executions of run_collect, 246,278 ns and 246,522 ns on the chip
    assert r["module_s"] == {"jit_run_collect": pytest.approx(492.8e-6)}
    # the operations run inside the modules' intervals, back to back
    assert 0.9 * 492.8e-6 < r["busy_s"] <= 492.8e-6
    assert r["device_ops"][0][0] == "%while.20 while"
    assert r["idle_gaps"][0][0] == "unattributed"
    assert sum(s for _n, s in r["idle_gaps"]) < 0.030


def test_short_names():
    assert trace_reduce.short_name("jit_run_collect(1262025845)") == "jit_run_collect"
    assert trace_reduce.short_name(
        "%while.20 = (s32[]{:T(128)}, s32[64]{0:T(128)}) while((s32[]) %t), "
        "condition=%c") == "%while.20 while"
