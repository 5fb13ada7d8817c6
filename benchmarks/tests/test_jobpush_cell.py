"""``jobpush3x3.one_task_steady``: the cell is data files plus entries, its mix
is its polling twin's but for the workers' delivery path, and a rehearsal of
it pushes every job."""

import json
import os
import re
import subprocess
import sys

import run
import served

MANIFEST = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELL = "jobpush3x3.one_task_steady"
TWIN = "default3x3.one_task_steady"
CONFIG = "zeebe-default-3x3-jobpush"
#: the keys benchmarks/README.md lists
CONFIG_KEYS = {"name", "source", "layout", "deployment", "assumed", "reduced",
               "guarantees"}
PUSH_METRICS = ("job_wait_ms_per_job", "job_push_ms_per_job")


def test_the_files_load_and_carry_the_documented_keys():
    what = run.resolve_cell(CELL, MANIFEST)
    config = what["config"]
    twin = run.resolve_cell(TWIN, MANIFEST)["config"]
    assert set(config) == CONFIG_KEYS
    assert config["layout"] == twin["layout"]
    assert {k: v for k, v in config["deployment"].items()
            if k not in ("job_delivery", "starter_rate_per_s")} == {
        k: v for k, v in twin["deployment"].items()
        if k != "starter_rate_per_s"}
    assert config["deployment"]["job_delivery"].startswith("push:")
    assert set(config["assumed"]) == set(twin["assumed"]) | {
        "stream_enabled", "no_poller_beside_the_stream",
        "activation_is_a_command", "streams"}
    assert set(config["guarantees"]) == set(twin["guarantees"]) | {"delivery"}
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
        CONFIG, "one_task_push_steady", 1)
    assert len(cell["why"]) <= 200


def test_the_mix_is_the_twins_but_for_the_delivery_path():
    mix = run.resolve_cell(CELL, MANIFEST)["traffic"]
    twin = run.resolve_cell(TWIN, MANIFEST)["traffic"]
    assert mix["workers"].pop("options") == {"stream_enabled": True}
    assert "options" not in twin["workers"]
    for own in ("name", "why"):
        assert mix.pop(own) != twin.pop(own)
    assert mix["loop"].pop("knee_found") != twin["loop"].pop("knee_found")
    assert mix == twin
    assert (mix["loop"]["rate_per_s"], mix["loop"]["arrivals"],
            mix["loop"]["senders"]) == (5.0, "fixed", 64)


def test_the_cell_reports_what_its_twin_reports_and_the_two_waits():
    what = run.resolve_cell(CELL, MANIFEST)
    twin = run.resolve_cell(TWIN, MANIFEST)
    assert {m["name"] for m in what["end_to_end"]} == {
        "completed_per_s", "completion_p50_ms", "setup_s"}
    names = {m["name"] for m in what["per_layer"]}
    assert names == {m["name"] for m in twin["per_layer"]} | {
        "gateway_shed_share", "job_push_ms_per_job"}
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    # the wait for a worker is one number in every cell; the push's is read
    # where jobs are pushed
    assert set(by_name["job_wait_ms_per_job"]["workloads"]) == {
        w["name"] for w in MANIFEST["workloads"]}
    assert by_name["job_push_ms_per_job"]["workloads"] == [CELL]
    for name in PUSH_METRICS:
        assert (by_name[name]["layer"], by_name[name]["moves"]) == (
            "gateway", "completion_p50_ms")


def test_a_program_without_the_histograms_leaves_the_metrics_out():
    # the parent commit has neither family, and a polling cell observes no
    # push: the reader finds nothing to read, and never reads 0
    what = run.resolve_cell(CELL, MANIFEST)
    context = {"counts": {"groups": 10, "commands": 10}, "child": {}}
    readers = {m["name"]: (run.load_reader(m["reader"]), m["args"])
               for m in what["per_layer"] if m["name"] in PUSH_METRICS}
    assert set(readers) == set(PUSH_METRICS)
    for read, args in readers.values():
        assert read(context, args) is None
    context["counts"].update(job_wait_count=4, job_wait_seconds=0.02,
                             job_push_count=0, job_push_seconds=0.0)
    assert readers["job_wait_ms_per_job"][0](
        context, readers["job_wait_ms_per_job"][1]) == 5.0
    assert readers["job_push_ms_per_job"][0](
        context, readers["job_push_ms_per_job"][1]) is None


def test_a_misspelt_stream_enabled_is_refused_before_there_is_a_cluster(monkeypatch):
    what = run.resolve_cell(CELL, MANIFEST)
    what["traffic"]["workers"]["options"] = {"streams_enabled": True}
    monkeypatch.setattr(run, "resolve_cell", lambda name, manifest=None: what)
    monkeypatch.setattr(served, "Served", None)     # building it would raise
    said = []
    monkeypatch.setattr(run, "say", said.append)
    assert run.main(["--workload", CELL, "--seed", "1",
                     "--seconds", "1"]) == run.REFUSED_EXIT
    assert "streams_enabled" in said[-1] and "one_task_push_steady" in said[-1]


def test_a_rehearsal_of_the_cell_pushes_every_job(tmp_path):
    """The cell walked on the host from a manifest of the test's own (about
    twenty seconds): every instance of the window correct, every job pushed,
    none polled for."""
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(MANIFEST))
    cmd = [sys.executable, str(run.HERE / "run.py"), "--workload", CELL,
           "--seed", str(2**31 + 35), "--seconds", "4", "--trace", "1",
           "--rehearse-cpu", "--manifest", str(manifest)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == run.REHEARSAL_EXIT, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""            # a rehearsal prints no result
    assert "correct=true" in proc.stderr and " failed=0" in proc.stderr
    counts = json.loads(re.search(r"counts in window: (\{.*\})",
                                  proc.stderr).group(1))
    assert counts["job_push_count"] >= 1
    assert counts["job_push_count"] == counts["job_wait_count"]
    # no poll: the sequential path ran the dispatcher's activations alone
    assert counts["sequential_count"] <= counts["job_wait_count"]
    would_be = json.loads(re.search(r"metrics would be: (\{.*\})",
                                    proc.stderr).group(1))
    assert set(PUSH_METRICS) <= set(would_be)
