"""Smoke test for ``bench.py --quick`` (tier-2: marked slow).

Runs the quick benchmark in a subprocess exactly as the driver would and
asserts the stdout JSON summary parses with a positive headline value —
guarding both the bench entry point and the pipelined execution path it
drives end to end (log → stream processor → kernel backend → log)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent


def test_bench_quick_json_summary_parses(tmp_path):
    env = dict(os.environ)
    env["ZB_BENCH_CPU"] = "1"  # a CPU rehearsal, asked for by name
    # isolate the XLA persistent cache so the smoke run cannot be poisoned
    # by (or poison) the developer's cache
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), "--quick"],
        capture_output=True, text=True, timeout=540, cwd=str(REPO), env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # the summary is the LAST stdout line, printed alone
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert lines, "bench.py --quick printed nothing to stdout"
    summary = json.loads(lines[-1])
    assert summary["metric"] == "e2e_process_instance_transitions_per_sec_per_chip"
    assert summary["unit"] == "transitions/s"
    assert summary["quick"] is True
    assert summary["value"] > 0
    assert summary["ten_tasks_transitions_per_sec"] > 0
    assert summary["kernel_ceiling_transitions_per_sec"] > 0

    full = json.loads((REPO / "BENCH_quick.json").read_text())
    assert full["value"] == summary["value"]
    stages = full["extra"]["pipeline_stages"]
    # the pipelined batch path ran and every stage histogram is populated
    for stage in ("decode", "device", "materialize", "append", "flush",
                  "side_effects"):
        assert stages[stage]["count"] > 0, f"stage {stage} never observed"
