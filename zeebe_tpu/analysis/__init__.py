"""zlint — the repo's own AST-based invariant linter.

The engine's correctness story rests on replicated-state-machine determinism:
replay must rebuild byte-identical state, so appliers and state facades can
never touch wall clocks, RNGs, or iteration-order-sensitive constructs; no
code may query the jax backend outside the one module that chooses the
device; pump
hooks must never block; ingress/query threads must read through committed
accessors. Every one of those is an *architectural invariant* that reviewers
kept re-discovering by hand (the one-module-chooses-the-device rule, the ColdStore
dict-changed-size fix, the drifted `_collect_flight_dumps` copies) — zlint
machine-checks them instead.

Entry points (stdlib-only — the linter must never pull the jax stack):

- ``run_lint(root)``        → list[Finding] over the package + gates.py
- ``python -m zeebe_tpu.cli lint [--check] [--update-baseline]``
- ``python -m zeebe_tpu.cli knobs-doc [--check]`` (env-knob drift gate)

Rule catalog, suppression syntax, and how to add a rule:
docs/static-analysis.md.
"""

from zeebe_tpu.analysis.framework import (
    BASELINE_FILENAME,
    Finding,
    format_baseline,
    load_baseline,
    run_lint,
    split_findings,
)
from zeebe_tpu.analysis.knobs import render_knobs_doc, scan_knobs
from zeebe_tpu.analysis.rules import RULES

__all__ = [
    "BASELINE_FILENAME",
    "Finding",
    "RULES",
    "format_baseline",
    "load_baseline",
    "render_knobs_doc",
    "run_lint",
    "scan_knobs",
    "split_findings",
]
