"""The control and the faults, through the whole harness: the chip look is
skipped (``--rehearse-cpu``: same path on the host, tiny window), the timed
path is broken underneath, and ``correct`` must come out false.
Slow (about half a minute a case: the engine's programs compile)."""

import json
import os
import subprocess
import sys

import pytest

import run

STEADY = "default3x3.one_task_steady"
#: not cells of the benchmark yet (PERF.md section 7): run from a manifest
#: of the test's own, the steady twin's entry with the mix of the name
CAPACITY = "default3x3.one_task_capacity"
MIX = "default3x3.default_mix_steady"
CASES = [(STEADY, None, True),
         (STEADY, "lying_follower", False),  # control, in the program's Raft
                                             # path: acks without a quorum
         (STEADY, "lose_acked", False),      # durability
         (STEADY, "at_least_once", False),   # exactly-once: an answer doubled
         (STEADY, "alter_record", False),    # a token altered
         (STEADY, "replica_export_differs", False),  # one replica exports otherwise
         # the loop closed on completions: the same comparison decides
         (CAPACITY, None, True),
         (CAPACITY, "lose_acked", False),
         # no snapshot falls inside a window this short, so tearing those on
         # the disks changes nothing (served.damage_snapshots has its own test)
         (CAPACITY, "torn_snapshot", True),
         # upstream's whole default mix: a message and a timer catch besides
         (MIX, None, True),
         (MIX, "double_correlate", False),   # a message correlated twice
         (MIX, "early_timer", False),        # a trigger before its due date
         (MIX, "lose_publish", False)]       # an acknowledged publish lost


def manifest_with(cell: str, tmp_path) -> str:
    """BENCHMARK.json with ``cell`` beside its steady twin: the same
    deployment and metrics, the mix of the cell's own name."""
    manifest = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    twin = STEADY if cell == MIX else cell.replace("_capacity", "_steady")
    entry = next(w for w in manifest["workloads"] if w["name"] == twin)
    manifest["workloads"].append({**entry, "name": cell,
                                  "traffic": cell.split(".")[1]})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if twin in metric.get("workloads", []):
            metric["workloads"].append(cell)
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    return str(path)


@pytest.mark.parametrize("cell,fault,expected", CASES)
def test_correct_under_fault(cell, fault, expected, tmp_path):
    # one instance in ten is broken, the first seen under the fault among
    # them (served.Observed.broken): a window that holds an instance holds one
    cmd = [sys.executable, str(run.HERE / "run.py"), "--workload", cell,
           "--seed", str(2**31 + 17), "--seconds", "4", "--trace", "0",
           "--rehearse-cpu"] + (["--fault", fault] if fault else [])
    if cell != STEADY:
        cmd += ["--manifest", manifest_with(cell, tmp_path)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == run.REHEARSAL_EXIT, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""            # a rehearsal prints no result
    assert f"correct={str(expected).lower()}" in proc.stderr, proc.stderr[-2000:]
