"""These tests are the benchmark's own (``python -m pytest benchmarks/tests -q``,
run by hand; not part of the repo's tier-1 suite)."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
