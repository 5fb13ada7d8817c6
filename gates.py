"""The safety gates: ``python gates.py <gate> [--quick]``.

Eight chaos and soak gates, each a harness under ``zeebe_tpu/testing/`` that
returns a report with its ``violations``, and the eligibility parity gate.
A gate reports no speed: it writes ``<NAME>[_quick].json`` beside this file
(and its flight dumps under ``<NAME>_dumps/``; CI uploads both), prints one
summary line, and exits 1 when the report holds a violation. Speed is the
benchmark's business (``BENCHMARK.json``, ``benchmarks/run.py``).

``soak``, ``scale-soak`` and ``eligibility`` run their broker or engine in
this process, on the devices ``zeebe_tpu/utils/backend.py`` resolves
(``JAX_PLATFORMS=cpu`` asks for the host). The other six never touch a
device here: their supervised worker processes do.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, NamedTuple

from zeebe_tpu.testing import (
    autotune,
    consistency,
    device_chaos,
    fleetday,
    scale_soak,
    serving,
    soak,
    torture,
    workloads,
)
from zeebe_tpu.testing.evidence import collect_gate_dumps

REPO_DIR = os.path.dirname(os.path.abspath(__file__))


class Gate(NamedTuple):
    """One chaos/soak gate: what runs, at which two sizes, where its flight
    dumps are found, and which of the report's fields the summary line
    shows."""

    run: Callable  # run(cfg, work_dir) -> report dict with "violations"
    quick_cfg: object
    full_cfg: object
    #: flight dumps under the work dir, collected even when the run raised (a
    #: failed gate is the run whose evidence must be kept); None where the
    #: report lists its own (``flightDumps``: the in-process gates claim each
    #: recovery's dump as they go)
    dump_glob: str | None
    summary: Callable[[dict], dict]


def _pick(report: dict, *keys: str) -> dict:
    return {k: report[k] for k in keys}


GATES: dict[str, Gate] = {
    # crash-recovery endurance: sustained traffic with parked instances over
    # an aggressive snapshot cadence, seeded power-loss crash-restarts
    # mid-flush and mid-snapshot, the durability invariants after each
    "soak": Gate(
        soak.run_soak, soak.SoakConfig(),
        soak.SoakConfig(rounds=10, traffic_per_round=40,
                        snapshot_chain_length=6),
        None,
        lambda r: {**_pick(r, "restarts", "ackedCommands", "withinBudget"),
                   "maxRecoveryMs": r["recoveryMs"]["max"],
                   **_pick(r, "maxChainLength", "snapshotKinds")}),
    # exactly-once delivery: supervised worker processes over TCP under
    # seeded drop/dup/delay/reorder, link partitions, a kill storm and a
    # crash between append and reply; no acked command lost, no duplicate
    # application, rejections terminal, positions monotone
    "consistency": Gate(
        consistency.run_consistency, consistency.ConsistencyConfig(),
        consistency.ConsistencyConfig(drive_seconds=120.0, kills=8,
                                      link_windows=5, reject_every=20),
        "*/flight-*.json",
        lambda r: {**_pick(r, "requests", "ackedCommands", "kills",
                           "linkPartitionWindows", "crashSequencesVerified"),
                   "dedupeProbeVerified":
                       r.get("dedupeProbe", {}).get("verified"),
                   **_pick(r, "dedupeRepliesObserved", "reExportedRecords")}),
    # storage fault survival: the consistency workload while disk, network
    # and process table all lie; every disk-fault class fired, every bit-rot
    # flip detected or repaired before wrong bytes were served, a corrupted
    # follower re-converged CRC-identical
    "torture": Gate(
        torture.run_torture, torture.TortureConfig(),
        torture.TortureConfig(drive_seconds=90.0, kills=3),
        "*/flight-*.json",
        lambda r: {**_pick(r, "requests", "ackedCommands", "kills",
                           "diskFaultsObserved", "bitrotFlips"),
                   "repairProbeVerified": r["repairProbe"].get("verified"),
                   **_pick(r, "scrubEvidenceEvents")}),
    # device fault survival: workers on the kernel backend while the
    # accelerator lies (compile/dispatch failures, stalls, partial chunks,
    # flipped result rows); every corruption caught before commit, one full
    # SUSPECT→QUARANTINED→canary→HEALTHY cycle
    "device-chaos": Gate(
        device_chaos.run_device_chaos, device_chaos.DeviceChaosConfig(),
        device_chaos.DeviceChaosConfig(drive_seconds=90.0, kills=3),
        "*/flight-*.json",
        lambda r: _pick(r, "requests", "ackedCommands", "kills",
                        "deviceFaultsObserved", "corruptionAccounting",
                        "healthCycle")),
    # the fleet day: open-loop multi-tenant serving with diurnal ramps,
    # tiered state, all three chaos planes, definition churn and rolling
    # restarts under the online auditor; the offline checker, SLOs outside
    # incident windows, auditor recall, a leak arm that must fire
    "fleetday": Gate(
        fleetday.run_fleetday, fleetday.FleetDayConfig(),
        fleetday.FULL_FLEETDAY,
        "*/flight-*.json",
        lambda r: {**_pick(r, "requests", "ackedCommands"),
                   "chaosPlanes": {p: sum(c.values())
                                   for p, c in r["chaosPlanes"].items()},
                   **_pick(r, "rollingRestarts", "definitionChurn"),
                   "slo": {k: r["slo"].get(k)
                           for k in ("p50Ms", "p99Ms", "ackFraction")},
                   **_pick(r, "leakVerdicts"),
                   "leakArmFired": r["leakArm"].get("fired"),
                   "auditorRecallPct": r["auditorRecall"]["recallPct"]}),
    # open-loop SLO'd serving: seeded Poisson arrivals from hundreds of
    # client streams, one hot tenant at 5x quota, a correlation storm and a
    # live worker kill; well-behaved tenants' ack latency, fairness, typed
    # and fast sheds, goodput, zero acked loss
    "serving": Gate(
        serving.run_serving, serving.ServingConfig(), serving.FULL_CONFIG,
        "*/flight-*.json",
        lambda r: {**_pick(r, "requests", "ackedCommands", "shedCommands",
                           "kills"),
                   "wellBehavedP99MsUnderLoad": r.get("wellBehaved", {}).get(
                       "underLoad", {}).get("p99Ms"),
                   "goodput": r.get("goodput"),
                   "parkedColdBeforeStorm": r.get("stormPool", {}).get(
                       "parkedColdBeforeStorm")}),
    # closed-loop control plane A/B: one seeded bursty schedule offered to
    # the adaptive broker and a panel of fixed-knob arms; adaptive beats
    # every arm's acked p99 at goodput within 5 %, zero acked loss, every
    # adjustment audited and inside its bounds
    "autotune": Gate(
        autotune.run_autotune, autotune.AutotuneConfig(),
        autotune.FULL_CONFIG,
        "*/*/flight-*.json",
        lambda r: _pick(r, "offeredArrivals", "summary")),
    # million-instance state tiering (100k in --quick): parked instances
    # under traffic, correlation storms, snapshots and compaction under
    # load, crash-restarts mid-spill and mid-snapshot; bounded RSS, zero
    # acked loss, byte-identical re-exports, recovery within budget
    "scale-soak": Gate(
        scale_soak.run_scale_soak, scale_soak.ScaleSoakConfig(),
        scale_soak.FULL_CONFIG,
        None,
        lambda r: {**_pick(r, "created", "peakSpilledInstances",
                           "peakSpilledFraction"),
                   "peakRssMiB": r["rss"]["peakMiB"],
                   "rssWithinBound": r["rss"]["withinBound"],
                   **_pick(r, "withinBudget", "sweepProbes")}),
}


def _write_report(report: dict, stem: str, quick: bool) -> str:
    name = f"{stem}_quick.json" if quick else f"{stem}.json"
    with open(os.path.join(REPO_DIR, name), "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return name


def _finish(gate: str, summary: dict, violations: list, name: str) -> None:
    """The summary line, then the violations and exit 1 if there are any."""
    label = "".join(w.capitalize() if i else w
                    for i, w in enumerate(gate.split("-")))
    print(json.dumps({label: True, **summary,
                      "violations": len(violations), "full_results": name}))
    if violations:
        for v in violations[:20]:
            print(f"{gate} violation: {v}", file=sys.stderr)
        raise SystemExit(1)


def run_gate(name: str, quick: bool) -> None:
    gate = GATES[name]
    stem = name.upper().replace("-", "_")
    started = time.perf_counter()
    work_dir = tempfile.mkdtemp(prefix=f"zeebe-{name}-")
    report = None
    try:
        report = gate.run(gate.quick_cfg if quick else gate.full_cfg, work_dir)
    finally:
        # the flight dumps are what a gate leaves behind to be reviewed: copy
        # them out of the work dir before it is deleted
        if gate.dump_glob is not None:
            found = sorted(Path(work_dir).glob(gate.dump_glob))
        else:
            found = report["flightDumps"] if report else []
        dumps = collect_gate_dumps(found, f"{stem}_dumps", work_dir,
                                   repo_dir=REPO_DIR)
        shutil.rmtree(work_dir, ignore_errors=True)
    report["flightDumps"] = dumps
    # a cluster gate's report already carries its drive's own wallSeconds
    wall_key = "wallSecondsTotal" if "wallSeconds" in report else "wallSeconds"
    report[wall_key] = round(time.perf_counter() - started, 2)
    report["quick"] = quick
    _finish(name,
            {"quick": quick, "seed": report["seed"], **gate.summary(report)},
            report["violations"], _write_report(report, stem, quick))


# ---------------------------------------------------------------------------
# eligibility: the static classifier against the routing a run observed


def _drive_with(variables: dict) -> Callable:
    return functools.partial(workloads.drive, variables=variables)


#: (scenario, models, driver(harness, models, instances), instances in --quick
#: or None, instances in full)
SCENARIOS = (
    ("e2e_one_task", lambda: [workloads.one_task()],
     _drive_with({}), 600, 4000),
    ("e2e_exclusive_chain", lambda: [workloads.exclusive_chain()],
     _drive_with({"x": 25}), None, 4000),
    ("e2e_fork_join", lambda: [workloads.fork_join()],
     _drive_with({}), None, 2000),
    ("e2e_mixed_8_definitions", workloads.mixed_definitions,
     _drive_with({"x": 15}), 480, 2400),
    ("e2e_ten_tasks", lambda: [workloads.ten_tasks()],
     _drive_with({}), 120, 800),
    ("e2e_ten_tasks_io_mapped", lambda: [workloads.ten_tasks_io()],
     _drive_with({"base": 5}), None, 800),
    ("e2e_subprocess_boundary", lambda: [workloads.subprocess_boundary()],
     _drive_with({}), None, 2000),
    ("adversarial_cold_templates",
     lambda: [workloads.adversarial_gateway(),
              workloads.adversarial_message()],
     workloads.drive_adversarial_cold, 240, 1200),
)


def _coverage_block(kernel, models, mark: dict) -> dict:
    """One scenario's kernel-path coverage and the static-vs-observed parity
    verdict: the classifier's per-definition prediction against the routing
    the run observed. A predicted-eligible definition host-routing for a
    non-runtime reason (or the reverse) is a violation."""
    from zeebe_tpu.engine.eligibility import (
        classify_definition,
        parity_violations,
    )
    from zeebe_tpu.engine.kernel_backend import KernelRegistry
    from zeebe_tpu.models.bpmn import transform

    delta = kernel.accounting.delta_since(mark)
    total = delta["kernel"] + delta["host"]
    # ONE shared registry: the prediction must see the deployment SET the
    # runtime saw (joint SlotMap clashes, max_definitions capacity) — a
    # solo prediction would blame the classifier for set-dependent declines
    reg = KernelRegistry()
    predictions = {}
    for i, m in enumerate(models):
        report = classify_definition(transform(m), definition_key=i + 1,
                                     registry=reg)
        predictions[m.process_id] = report["eligible"]
    return {
        "coverage_pct": round(100.0 * delta["kernel"] / total, 2) if total else 100.0,
        "kernel_records": delta["kernel"],
        "host_records": delta["host"],
        "per_definition": delta["perDefinition"],
        "predicted_eligible": predictions,
        "parity_violations": parity_violations(
            predictions, delta["perDefinition"]),
    }


def run_eligibility(quick: bool) -> None:
    scenarios = {}
    violations = []
    for name, make_models, drive, quick_n, full_n in SCENARIOS:
        n = quick_n if quick else full_n
        if n is None:
            continue
        models = make_models()
        harness = workloads.kernel_harness()
        try:
            harness.deploy(*models)
            mark = harness.kernel_backend.accounting.mark()
            counts = drive(harness, models, n)
            if counts["completed"] != counts["instances"]:
                raise SystemExit(
                    f"{name}: {counts['completed']} of {counts['instances']} "
                    f"instances completed")
            scenarios[name] = _coverage_block(
                harness.kernel_backend, models, mark)
        finally:
            harness.close()
        violations += [f"{name}: {v}"
                       for v in scenarios[name]["parity_violations"]]
    report = {"quick": quick, "scenarios": scenarios,
              "parityViolations": violations}
    _finish("eligibility", {"quick": quick, "scenarios": len(scenarios)},
            violations, _write_report(report, "ELIGIBILITY", quick))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("gate", choices=[*GATES, "eligibility"])
    ap.add_argument("--quick", action="store_true",
                    help="the short configuration CI runs (minutes)")
    args = ap.parse_args(argv)
    # eight host devices, as tests/conftest.py gives the suite: a broker or
    # worker that owns several partitions then takes the mesh path, as it
    # would on a host with several chips. Affects the host platform only,
    # and must be in the environment before jax starts (here and in workers)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    if args.gate == "eligibility":
        run_eligibility(args.quick)
    else:
        run_gate(args.gate, args.quick)


if __name__ == "__main__":
    main()
