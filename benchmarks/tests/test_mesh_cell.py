"""``mesh3x3.one_task_steady``: the 3x3 twin's deployment laid over a
four-chip host, its partitions the three shards of one mesh runner. The
deployment differs from the twin's only where it says how the host holds
it, the manifest and the file agree, its four metrics read the counters the
runner writes, and a rehearsal on four virtual devices reads ``correct``."""

import json
import os
import re
import subprocess
import sys

import run

MANIFEST = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELL = "mesh3x3.one_task_steady"
TWIN = "default3x3.one_task_steady"
CONFIG = "zeebe-default-3x3-mesh4"
MESH_METRICS = ("mesh_wait_ms_per_group", "mesh_dispatch_ms_per_dispatch",
                "mesh_uploads_per_dispatch", "mesh_groups_per_dispatch")
#: what the mesh path never observes: left out of the cell, never 0
SINGLE_DEVICE_ONLY = ("device_dispatch_ms_per_group",
                      "device_fetch_ms_per_group",
                      "device_unpack_ms_per_group", "device_uploads_per_group")


def test_the_deployment_is_the_twins_but_for_how_the_host_holds_it():
    config = run.resolve_cell(CELL, MANIFEST)["config"]
    twin = run.resolve_cell(TWIN, MANIFEST)["config"]
    assert set(config) == set(twin)
    for key in ("source", "deployment", "reduced", "guarantees"):
        assert config[key] == twin[key], key
    layout = dict(config["layout"])
    assert (layout.pop("chips"), layout.pop("kernel_mesh_shards")) == (4, 3)
    assert layout.pop("processes") != twin["layout"]["processes"]
    assert layout == {k: v for k, v in twin["layout"].items()
                      if k not in ("chips", "processes")}
    assumed = dict(config["assumed"])
    assert "chips 0-2" in assumed.pop("mesh_layout")
    assert assumed == twin["assumed"]


def test_the_manifest_and_the_file_agree():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    twin_entry = next(c for c in MANIFEST["configs"]
                      if c["name"] == "zeebe-default-3x3")
    config = json.loads((run.ROOT / entry["file"]).read_text())
    assert config["name"] == CONFIG
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["source"] != twin_entry["source"]
    assert list(config["reduced"]) == entry["reduced"] == [
        "machines", "starter_rate_per_s"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "one_task_steady", 4)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1


def test_the_cell_reports_the_twins_metrics_but_the_single_device_parts():
    what = run.resolve_cell(CELL, MANIFEST)
    twin = run.resolve_cell(TWIN, MANIFEST)
    assert ({m["name"] for m in what["end_to_end"]}
            == {m["name"] for m in twin["end_to_end"]})
    names = {m["name"] for m in what["per_layer"]}
    assert names == ({m["name"] for m in twin["per_layer"]}
                     - set(SINGLE_DEVICE_ONLY)) | set(MESH_METRICS)
    # the four are the last of per_layer, in this order, of one layer
    assert [m["name"] for m in MANIFEST["per_layer"][-4:]] == list(
        MESH_METRICS)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in MESH_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["layer"] == "mesh runner"
    assert by_name["mesh_groups_per_dispatch"]["moves"] == "completed_per_s"
    assert {by_name[n]["moves"] for n in MESH_METRICS[:3]} == {
        "completion_p50_ms"}


def test_the_four_metrics_read_the_runners_histograms():
    what = run.resolve_cell(CELL, MANIFEST)
    readers = {m["name"]: (run.load_reader(m["reader"]), m["args"])
               for m in what["per_layer"] if m["name"] in MESH_METRICS}
    # the parent commit has none of the four families: nothing to read,
    # and never 0
    context = {"counts": {"groups": 10, "mesh_dispatches": 10}, "child": {}}
    for read, args in readers.values():
        assert read(context, args) is None
    context["counts"].update(
        mesh_wait_count=10, mesh_wait_seconds=0.02,
        mesh_dispatch_count=8, mesh_dispatch_seconds=0.4,
        mesh_uploads_count=8, mesh_uploads_seconds=104.0,
        mesh_groups_count=8, mesh_groups_seconds=10.0)
    assert {name: round(read(context, args), 6)
            for name, (read, args) in readers.items()} == {
        "mesh_wait_ms_per_group": 2.0, "mesh_dispatch_ms_per_dispatch": 50.0,
        "mesh_uploads_per_dispatch": 13.0, "mesh_groups_per_dispatch": 1.25}


def test_a_rehearsal_on_four_virtual_devices_reads_correct(tmp_path):
    """The cell walked on the host over four virtual CPU devices (about
    half a minute): correct, the groups on three of them, the four metrics
    read."""
    manifest = tmp_path / "manifest.json"
    manifest.write_text(json.dumps(MANIFEST))
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": " ".join(
               flags + ["--xla_force_host_platform_device_count=4"])}
    cmd = [sys.executable, str(run.HERE / "run.py"), "--workload", CELL,
           "--seed", str(2**31 + 4545), "--seconds", "4", "--trace", "1",
           "--rehearse-cpu", "--manifest", str(manifest)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == run.REHEARSAL_EXIT, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""            # a rehearsal prints no result
    assert "correct=true" in proc.stderr and " failed=0" in proc.stderr
    assert "mesh shards: ['cpu:0', 'cpu:1', 'cpu:2']" in proc.stderr
    counts = json.loads(re.search(r"counts in window: (\{.*\})",
                                  proc.stderr).group(1))
    # one wait a group (a group in flight at either end of the window may
    # fall on the other side of it in one of the two counts)
    assert counts["groups"] > 0
    assert abs(counts["mesh_wait_count"] - counts["groups"]) <= 1
    assert counts["mesh_uploads_seconds"] == 13 * counts["mesh_uploads_count"]
    would_be = json.loads(re.search(r"metrics would be: (\{.*\})",
                                    proc.stderr).group(1))
    assert set(MESH_METRICS) <= set(would_be)
    assert not set(SINGLE_DEVICE_ONLY) & set(would_be)
