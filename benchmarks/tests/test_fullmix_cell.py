"""``fullmix3x3.full_mix_steady``: the cell is data files plus entries, its
deployment is the 3x3 twin's with two guarantees more, its mix is
``default_mix_steady`` at the rate of a sweep, its three metrics are read
where the catch path is, and a rehearsal of it reads ``correct``."""

import json
import os
import re
import subprocess
import sys

import run

MANIFEST = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELL = "fullmix3x3.full_mix_steady"
TWIN = "default3x3.one_task_steady"
CONFIG = "zeebe-default-3x3-full"
#: the keys benchmarks/README.md lists
CONFIG_KEYS = {"name", "source", "layout", "deployment", "assumed", "reduced",
               "guarantees"}
CATCH_METRICS = ("timer_lag_ms_per_trigger", "correlate_ms_per_message",
                 "catch_kernel_share")


def test_the_deployment_is_the_twins_with_two_guarantees_more():
    config = run.resolve_cell(CELL, MANIFEST)["config"]
    twin = run.resolve_cell(TWIN, MANIFEST)["config"]
    assert set(config) == CONFIG_KEYS
    assert config["layout"] == twin["layout"]
    assert {k: v for k, v in config["deployment"].items()
            if k != "starter_rate_per_s"} == {
        k: v for k, v in twin["deployment"].items() if k != "starter_rate_per_s"}
    assert set(config["assumed"]) >= set(twin["assumed"])
    assert set(config["guarantees"]) == set(twin["guarantees"]) | {
        "correlation", "due_dates"}
    assert {k: config["guarantees"][k] for k in twin["guarantees"]} == (
        twin["guarantees"])


def test_the_manifest_and_the_file_agree():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    config = json.loads((run.ROOT / entry["file"]).read_text())
    assert config["name"] == CONFIG
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert list(config["reduced"]) == entry["reduced"] == [
        "machines", "starter_rate_per_s"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "full_mix_steady", 1)
    assert len(cell["why"]) <= 200


def test_the_mix_is_default_mix_steady_outside_its_loop():
    mix = run.resolve_cell(CELL, MANIFEST)["traffic"]
    default = json.loads((run.HERE / "traffic" / "default_mix_steady.json")
                         .read_text())
    for own in ("name", "why", "loop"):
        assert mix.pop(own) != default.pop(own)
    assert mix == default


def test_the_rate_is_five_eighths_of_the_sweeps_knee_at_most():
    loop = run.resolve_cell(CELL, MANIFEST)["traffic"]["loop"]
    assert (loop["kind"], loop["arrivals"], loop["senders"]) == (
        "open", "fixed", 64)
    assert loop["rate_per_s"] <= 0.625 * loop["knee_per_s"] + 1e-9
    assert isinstance(loop["knee_found"], str) and loop["knee_found"]


def test_the_three_metrics_resolve_and_list_this_cell_alone():
    what = run.resolve_cell(CELL, MANIFEST)
    twin = run.resolve_cell(TWIN, MANIFEST)
    names = {m["name"] for m in what["per_layer"]}
    assert names == {m["name"] for m in twin["per_layer"]} | set(CATCH_METRICS)
    assert {m["name"] for m in what["end_to_end"]} == {
        "completed_per_s", "completion_p50_ms", "setup_s"}
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in CATCH_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        run.load_reader(next(m for m in what["per_layer"]
                             if m["name"] == name)["reader"])
    assert (by_name["catch_kernel_share"]["layer"],
            by_name["catch_kernel_share"]["moves"]) == (
        "kernel backend", "completed_per_s")


def test_a_program_without_the_histograms_leaves_the_metrics_out():
    # the parent commit has none of the three families: the readers find
    # nothing to read, and never read 0
    what = run.resolve_cell(CELL, MANIFEST)
    readers = {m["name"]: (run.load_reader(m["reader"]), m["args"])
               for m in what["per_layer"] if m["name"] in CATCH_METRICS}
    context = {"counts": {"groups": 10, "commands": 10}, "child": {}}
    for read, args in readers.values():
        assert read(context, args) is None
    context["counts"].update(timer_lag_count=4, timer_lag_seconds=0.04,
                             correlate_count=2, correlate_seconds=0.02,
                             catch_count=6, catch_seconds=0.1,
                             catch_kernel_count=3, catch_kernel_seconds=0.05)
    assert {name: round(read(context, args), 6)
            for name, (read, args) in readers.items()} == {
        "timer_lag_ms_per_trigger": 10.0, "correlate_ms_per_message": 10.0,
        "catch_kernel_share": 50.0}


def test_a_rehearsal_of_the_cell_reads_correct(tmp_path):
    """The cell walked on the host from a manifest of the test's own (about
    a minute): correct, every catch of the window observed, both ways a
    message meets its subscription taken."""
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(MANIFEST))
    cmd = [sys.executable, str(run.HERE / "run.py"), "--workload", CELL,
           "--seed", str(2**31 + 4141), "--seconds", "4", "--trace", "1",
           "--rehearse-cpu", "--manifest", str(manifest)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == run.REHEARSAL_EXIT, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""            # a rehearsal prints no result
    assert "correct=true" in proc.stderr and " failed=0" in proc.stderr
    counts = json.loads(re.search(r"counts in window: (\{.*\})",
                                  proc.stderr).group(1))
    assert counts["catch_count"] >= 1
    assert counts["catch_count"] == counts["timer_lag_count"] + counts[
        "correlate_count"]
    assert counts["catch_kernel_count"] <= counts["catch_count"]
    would_be = json.loads(re.search(r"metrics would be: (\{.*\})",
                                    proc.stderr).group(1))
    assert set(CATCH_METRICS) <= set(would_be)
