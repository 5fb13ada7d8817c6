"""The control and the faults, through the whole harness: the chip look is
skipped (``--rehearse-cpu``: same path on the host, tiny window), the timed
path is broken underneath, and ``correct`` must come out false.
Slow (about half a minute a case: the engine's programs compile)."""

import os
import subprocess
import sys

import pytest

import run

CELL = "default3x3.one_task_steady"
CASES = [(None, True),
         ("lying_follower", False),          # control, in the program's Raft
                                             # path: acks without a quorum
         ("lose_acked", False),              # durability
         ("at_least_once", False),           # exactly-once: an answer doubled
         ("alter_record", False),            # a token altered
         ("replica_export_differs", False)]  # one replica exports otherwise


@pytest.mark.parametrize("fault,expected", CASES)
def test_correct_under_fault(fault, expected):
    cmd = [sys.executable, str(run.HERE / "run.py"), "--workload", CELL,
           "--seed", str(2**31 + 17), "--seconds", "4", "--trace", "0",
           "--rehearse-cpu"] + (["--fault", fault] if fault else [])
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == run.REHEARSAL_EXIT, proc.stderr[-2000:]
    assert proc.stdout.strip() == ""            # a rehearsal prints no result
    assert f"correct={str(expected).lower()}" in proc.stderr, proc.stderr[-2000:]
