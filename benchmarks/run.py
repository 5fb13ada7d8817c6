#!/usr/bin/env python3
"""The benchmark's one command:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It finds the cell in ``BENCHMARK.json`` and everything that belongs to it by
name: the deployment in ``benchmarks/configs/``, the traffic mix in
``benchmarks/traffic/``, each per-layer metric in ``benchmarks/layer_metrics/``
and its reader in ``benchmarks/readers/``. This process is the only one that
imports JAX, so it holds the cell's chips: it builds the deployment's cluster
and gateway in itself, starts ``loadgen.py`` as a child (clients and job
workers, over loopback gRPC), warms the cell's own shapes, measures exactly
``--seconds`` of the cell's traffic, drains, checks what the window produced
against the plain reference, and prints one JSON line.

``--rehearse-cpu`` (with ``JAX_PLATFORMS=cpu``) walks the same path on the host
for a rehearsal: it ends with exit code 3 and prints **no** result line.
``--fault <name>`` breaks the timed path underneath the comparison — in the
program's own Raft path (``lying_follower``), on the replicas' disks once the
cluster has stopped (``torn_snapshot``), in the state a deployment is seeded
with (``lose_parked``), in the running partition's state store as the window
opens (``forget_parked``), in what the replicas' logs are read to hold
(``lose_publish``) or where the harness takes its answers (the others) —
for the controls and the fault tests (benchmarks/tests/); never used by a
measurement.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from collections import Counter
from pathlib import Path

T_PROCESS_START = time.monotonic()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))

import definitions as defs  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import schedule  # noqa: E402
import seed_state  # noqa: E402
import trace_reduce  # noqa: E402

REHEARSAL_EXIT = 3
REFUSED_EXIT = 2
FAULTS = ("lying_follower", "lose_acked", "at_least_once", "alter_record",
          "replica_export_differs", "torn_snapshot", "lose_parked",
          "forget_parked", "double_correlate", "early_timer", "lose_publish")


class Refused(Exception):
    """The run cannot be a result: no chip, an unknown name, a group off the
    TPU. Ends the process non-zero with no result line."""


def say(message: str) -> None:
    print(f"[{time.monotonic() - T_PROCESS_START:6.1f}s] {message}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, by name


def load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        shown = path.relative_to(ROOT) if path.is_relative_to(ROOT) else path
        raise Refused(f"unknown {what}: no file {shown}")
    return json.loads(path.read_text())


def resolve_cell(name: str, manifest: dict | None = None) -> dict:
    manifest = manifest or load_json(ROOT / "BENCHMARK.json", "manifest")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise Refused(f"unknown cell {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if cell["config"] not in configs:
        raise Refused(f"unknown configuration {cell['config']!r}")
    config = load_json(ROOT / configs[cell["config"]]["file"], "configuration")
    traffic_path = HERE / "traffic" / f"{cell['traffic']}.json"
    traffic = load_json(traffic_path, "traffic mix")

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in manifest["end_to_end"] if reported(m)]
    per_layer = []
    for m in manifest["per_layer"]:
        if reported(m):
            spec = load_json(HERE / "layer_metrics" / f"{m['name']}.json",
                             "per-layer metric")
            per_layer.append({**m, "reader": spec["reader"],
                              "args": spec.get("args", {})})
    return {"cell": cell, "config": config, "traffic": traffic,
            "traffic_path": traffic_path, "end_to_end": end_to_end,
            "per_layer": per_layer}


def load_reader(name: str):
    path = HERE / "readers" / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in (HERE / "readers").glob("*.py"))
        raise Refused(f"unknown metric reader {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# the child


class Child:
    def __init__(self, address: str, traffic_path: Path, partitions: int,
                 seed: int, out_dir: Path) -> None:
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)   # it never imports jax; keep it so
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py"), "--address", address,
             "--traffic", str(traffic_path), "--partitions", str(partitions),
             "--seed", str(seed), "--out-dir", str(out_dir)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
        self.answers: queue.Queue = queue.Queue()
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.answers.put(line)
        self.answers.put(None)

    def send(self, cmd: str, **kw) -> None:
        self.proc.stdin.write(json.dumps({"cmd": cmd, **kw}) + "\n")
        self.proc.stdin.flush()

    def answer(self, timeout: float) -> dict:
        try:
            line = self.answers.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError("the load generator did not answer in "
                               f"{timeout:.0f}s") from None
        if line is None:
            raise RuntimeError("the load generator died")
        answer = json.loads(line)
        if not answer.get("ok"):
            raise RuntimeError(f"load generator: {answer.get('error')}")
        return answer

    def ask(self, cmd: str, timeout: float, **kw) -> dict:
        self.send(cmd, **kw)
        return self.answer(timeout)

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# metrics arithmetic (pure: tests call these)


def window_metrics(window: list, completed_at: dict, all_completions: list,
                   t0: float, seconds: float) -> dict:
    """End-to-end numbers over **all** requests due in the window: a request
    refused, unanswered or never completed counts as missing."""
    attempted = len(window)
    acks = [r["ack"] - r["due"] for r in window if r["ok"]]
    completions = [completed_at[r["key"]] - r["due"] for r in window
                   if r["ok"] and r["key"] in completed_at]
    inside = sum(1 for t in all_completions if t0 <= t < t0 + seconds)
    backlog = sum(1 for r in window if r["ok"]
                  and completed_at.get(r["key"], math.inf) >= t0 + seconds)
    late = [r["sent"] - r["due"] for r in window]
    return {
        "attempted": attempted,
        "failed": attempted - len(completions),
        "backlog_at_close": backlog,
        "completed_per_s": inside / seconds,
        "ack_p95_ms": 1e3 * schedule.percentile(acks, 0.95, attempted),
        "completion_p50_ms": 1e3 * schedule.percentile(completions, 0.50, attempted),
        "completion_p95_ms": 1e3 * schedule.percentile(completions, 0.95, attempted),
        "generator_late_p95_ms": 1e3 * schedule.percentile(late, 0.95, attempted),
    }


def by_definition(window: list, completed_at: dict) -> dict:
    """The median completion, ms, of each definition's creates due in the
    window, a create that never completed counted as missing."""
    out = {}
    for pid in sorted({r["pid"] for r in window}):
        mine = [r for r in window if r["pid"] == pid]
        done = [completed_at[r["key"]] - r["due"] for r in mine
                if r["ok"] and r["key"] in completed_at]
        out[pid] = round(1e3 * schedule.percentile(done, 0.50, len(mine)), 3)
    return out


def decide_correct(numbers: dict) -> bool:
    """``numbers``: name -> ``{"value", "limit"}``; every value within its
    limit (a limit is an upper bound; ``min`` marks a lower one)."""
    return all(n["value"] >= n["limit"] if n.get("min") else
               n["value"] <= n["limit"] for n in numbers.values())


def compare(definitions: list, requests: list, observed_events: dict,
            completed_at: dict, returned: dict, completed_jobs: list,
            logs: dict, marks: dict, position_of: dict | None = None,
            publishes: list = ()) -> dict:
    """The comparison that decides ``correct``, over every acknowledged
    create of the run: its exported records against the plain reference, its
    completion, and — for it, for every acknowledged job completion and for
    every acknowledged publish of a message — its presence in every
    replica's log on disk; and the replicas' logs against each other, byte
    for byte, as far as all had committed. Exact: limits 0. A message catch
    is held to the acknowledged publish of its correlation key
    (``publishes``: the child's records of them).

    ``logs``: ``served.replica_logs``; ``marks``: (partition, broker) -> the
    replica's commit index while the cluster ran; ``position_of``: key -> the
    log position of the record an acknowledgement rests on, as exported. A
    replica that compacted its log is held to what it kept: an
    acknowledgement is missing unless the log on disk holds it or a snapshot
    on that replica's disk covers its position."""
    by_id = {d["id"]: d for d in definitions}
    acked = [r for r in requests if r["ok"]]
    never = [r["key"] for r in acked if r["key"] not in completed_at]
    messages = {p["correlation_key"]: {
        "key": p["key"], "variables": defs.message_variables(p["correlation_key"])}
        for p in publishes if p["ok"]}
    bad = reference.mismatches(
        by_id, [(r["key"], r["pid"], r["variables"]) for r in acked
                if r["key"] in completed_at], observed_events, returned,
        messages)
    # a key's upper bits name its partition (the protocol's layout)
    keys = {r["key"] for r in acked}
    jobs = set(completed_jobs)
    published = {m["key"] for m in messages.values()}
    missing = differing = under_a_snapshot = 0
    position_of = position_of or {}
    for (pid, _broker), log in logs.items():
        gone = ({k for k in keys if k >> 51 == pid} - log["created"]) | (
            {k for k in jobs if k >> 51 == pid} - log["jobs_completed"])
        if published:
            gone |= ({k for k in published if k >> 51 == pid}
                     - log["messages_published"])
        covered = log.get("snapshot_position", 0)
        kept = sum(1 for k in gone if 0 < position_of.get(k, math.inf) <= covered)
        under_a_snapshot += kept
        missing += len(gone) - kept
    for pid in {pid for pid, _broker in logs}:
        replicas = [log for (p, _b), log in logs.items() if p == pid]
        committed = min(mark for (p, _b), mark in marks.items() if p == pid)
        for index in set().union(*(r["entries"] for r in replicas)):
            if index <= committed:
                differing += len({r["entries"][index] for r in replicas
                                  if index in r["entries"]}) > 1
    numbers = {
        "acked_never_completed": {"value": len(never), "limit": 0},
        "reference_mismatches": {"value": len(bad), "limit": 0},
        "instances_compared": {"value": len(acked) - len(never), "limit": 1,
                               "min": True},
        "acks_missing_in_a_replica": {"value": missing, "limit": 0},
        "replica_log_entries_differing": {"value": differing, "limit": 0},
    }
    return {"numbers": numbers, "examples": bad[:3], "never": never[:3],
            "under_a_snapshot": under_a_snapshot}


def lose_a_publish(logs: dict, publishes: list) -> int:
    """The fault ``lose_publish``: the first acknowledged publish of the
    window, by its message key, is taken out of what one replica's log on
    disk is read to hold. Returns the message's key."""
    key = min(p["key"] for p in publishes if p["ok"] and p["phase"] == "window")
    replica = min(r for r in logs if r[0] == key >> 51)
    logs[replica]["messages_published"].discard(key)
    return key


def parked_checks(seeded: dict, replicas: int, running: dict, logs: dict,
                  exported: int) -> dict:
    """A seeded deployment's numbers (``state.parked``), limit 0 each, read
    twice: from every replica's **running** state once the drain is over
    (``running``: ``Served.parked_now``, the store the timed path wrote to),
    and from what every replica's **disk** recovers to after the stop
    (``logs``: ``served.replica_logs``, the newest snapshot a restart would
    load and the log behind it).
    ``parked_missing`` / ``parked_missing_on_disk``: replica by replica, how
    far the instances it holds are from the count seeded a partition, more or
    fewer (one replica's loss does not cancel against another's extra); a
    replica that is not there, or whose disk loads no state, holds none.
    ``parked_touched``: the exported records that name the parked definition
    (its job type has no worker) and the instances a running replica holds
    that no longer wait as they were parked, row for row (``parked_in``);
    ``parked_touched_on_disk``: those of a recovered state, and the records
    behind the seed in a replica's log that name the definition (a replay
    would apply them)."""
    each = seeded["per_partition"]

    def missing(held: list) -> int:
        return sum(abs(each - h) for h in held) + each * (replicas - len(held))

    return {
        "parked_missing": {
            "value": missing([held for held, _waiting in running.values()]),
            "limit": 0},
        "parked_touched": {
            "value": exported + sum(held - waiting
                                    for held, waiting in running.values()),
            "limit": 0},
        "parked_missing_on_disk": {
            "value": missing([log["parked_held"] for log in logs.values()]),
            "limit": 0},
        "parked_touched_on_disk": {
            "value": sum(log["parked_in_log"] + log["parked_held"]
                         - log["parked_waiting"] for log in logs.values()),
            "limit": 0},
    }


# ---------------------------------------------------------------------------
# the run


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest", default=None,
                        help="a manifest other than BENCHMARK.json: for a "
                             "cell that is not in the benchmark yet (sweeps, "
                             "the tests); never used by a measurement")
    parser.add_argument("--rehearse-cpu", action="store_true")
    parser.add_argument("--fault", choices=FAULTS, default=None)
    parser.add_argument("--keep-events", default=None,
                        help="write one instance's records per (definition, "
                             "x) to this JSON file (the tests' recorded data)")
    parser.add_argument("--keep-trace", default=None,
                        help="write the trace's plain form to this JSON file")
    args = parser.parse_args(argv)
    try:
        return run(args)
    except Refused as err:
        say(f"refused: {err}")
        return REFUSED_EXIT


def run(args) -> int:
    what = resolve_cell(args.workload, args.manifest and load_json(
        Path(args.manifest).resolve(), "manifest"))
    cell, config, traffic = what["cell"], what["config"], what["traffic"]
    readers = {m["name"]: load_reader(m["reader"]) for m in what["per_layer"]}
    try:
        import zeebe_tpu  # noqa: F401
    except ImportError:
        raise Refused("the program (zeebe_tpu) is not in this directory") from None

    from zeebe_tpu import native
    from zeebe_tpu.utils import backend
    from zeebe_tpu.utils.xla_cache import enable_persistent_cache

    import loadgen
    import served as srv

    try:    # what the child would refuse, before there is a cluster to start
        loadgen.worker_arguments(traffic["workers"])
        loadgen.jobs_to_wait_for(traffic)
        messages = loadgen.messages_of(traffic)
        timer_s = defs.longest_timer_ms(
            defs.build_definitions(traffic["definitions"])) / 1e3
        if timer_s and timer_s >= float(
                traffic.get("setup", {}).get("drain_max_s", 60.0)):
            raise ValueError(f"setup.drain_max_s does not exceed the longest "
                             f"timer, {timer_s:g} s")
    except ValueError as err:
        raise Refused(f"traffic mix {cell['traffic']!r}: {err}") from None
    keyed = {} if messages is None else messages[1]
    state = config.get("state")
    try:
        if state is not None:
            seed_state.refuse_clash(state, traffic["definitions"])
        elif args.fault in ("lose_parked", "forget_parked"):
            raise ValueError(f"--fault {args.fault}: no state is seeded")
    except ValueError as err:
        raise Refused(f"configuration {cell['config']!r}: {err}") from None
    cache_dir = enable_persistent_cache()
    import jax

    # programs that compile in under a second are cached too: set-up is
    # paid by every run of every later check
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ledger = srv.CompileLedger()
    devices = backend.devices()
    first = devices[0]
    rehearsal = first.platform != "tpu"
    if rehearsal and not (args.rehearse_cpu and backend.cpu_requested()):
        raise Refused(f"no TPU: jax found {first.platform}")
    if not rehearsal and args.rehearse_cpu:
        raise Refused("--rehearse-cpu on a machine with an accelerator")
    if len(devices) != cell["chips"]:
        raise Refused(f"the cell asks for {cell['chips']} chip(s), jax sees "
                      f"{len(devices)}")
    peaks = None if rehearsal else roofline.peaks_of(first.device_kind)
    if native.load_codec() is None:
        raise Refused("the native codec did not build from codec.c")
    say(f"device: platform={first.platform} kind={first.device_kind} "
        f"count={len(devices)} cache={cache_dir}")

    (ROOT / ".bench_data").mkdir(exist_ok=True)
    data_dir = Path(tempfile.mkdtemp(prefix="run-", dir=ROOT / ".bench_data"))
    layout = config["layout"]
    seconds = float(args.seconds)
    setup = traffic.get("setup", {})
    definitions = defs.build_definitions(traffic["definitions"])
    payload = defs.make_payload(traffic.get("payload"), args.seed)
    catches = any(defs.catch_of(d) is not None for d in definitions)

    from zeebe_tpu.engine.device_health import shared_device_health

    if "accelerator_router_rule" in config.get("assumed", {}):
        srv.hold_groups_on_the_accelerator()
    health = shared_device_health()
    deployed_shadow_rate = health.cfg.shadow_sample_rate
    observed = srv.Observed()
    if args.fault == "lying_follower":
        srv.plant_lying_follower(observed)
    try:
        seeded = None
        if state is not None:
            # the deployment starts with state: it is put on the directory
            # through the program's own doors, and the cluster below
            # recovers it as a restart would (seed_state.py)
            seeded = seed_state.seed(layout, state, data_dir / "data",
                                     srv.EXPORTER_ID,
                                     lose=args.fault == "lose_parked")
            observed.parked_id = seeded["id"]
            say(f"seeded {seeded['instances']} instances in "
                f"{seeded['seconds']:.2f}s (a cohort of {seeded['cohort']} a "
                f"partition created by the engine in "
                f"{seeded['cohort_seconds']:.2f}s, the rest their clones; "
                f"{seeded['rows']} rows in a partition's snapshot)")
        recover_start = time.monotonic()
        system = srv.Served(layout, data_dir / "data", observed)
    except BaseException:
        shutil.rmtree(data_dir, ignore_errors=True)
        raise
    if seeded is not None:
        say(f"the cluster recovered the seeded directory in "
            f"{time.monotonic() - recover_start:.2f}s: {system.state_keys()} "
            "keys in the largest partition's state")
    child = None
    trace_result = None
    try:
        child = Child(system.address, what["traffic_path"], system.partitions,
                      args.seed, data_dir)
        deployed = child.ask("deploy", 90.0)
        say(f"deployed: {deployed}")
        # every group of the first touches is also run by the host oracle, so
        # that the oracle's own programs are compiled before the window; then
        # back to the deployment's sample rate
        health.cfg.shadow_sample_rate = 1.0
        say("cluster up, definitions deployed, workers started")
        touched = child.ask("first_touch", 300.0)["keys"]
        wait_completed(observed, touched, 120.0, "first touches")
        say(f"first touches done: {ledger.report()}")
        if seeded is not None:
            # a replica loads its snapshot as a follower and again on
            # becoming leader, and the first copy, with the processor that
            # held it, is garbage in cycles: over a million objects that only
            # a full collection frees. Left to the allocation counts it came
            # during the warm-up, a second or three before the window, held
            # every thread for 1.6 s and opened the window on a backlog of 64
            # instances (PERF.md, PR 33). It is made here, at a fixed point
            # of set-up with no request open
            collect_start = time.monotonic()
            freed = gc.collect()
            say(f"the recovery's garbage collected: {freed} objects in "
                f"{time.monotonic() - collect_start:.2f}s")
        child.ask("warm", 30.0)
        warm_start = time.monotonic()
        time.sleep(float(setup.get("shadow_warm_s", 2.0)))
        health.cfg.shadow_sample_rate = deployed_shadow_rate
        quiet_s = float(setup.get("quiet_s", 2.0))
        # a mix that fills a partition's log within a run (the program
        # snapshots and compacts once the replay debt passes a threshold of
        # its own) says at what debt its window opens, so that the snapshot
        # falls well inside every window and not at its edge in some
        # (a rehearsal on the host never gets there: it does not wait)
        warm_debt = 0.0 if rehearsal else float(setup.get("warm_debt_records", 0))
        while True:
            now = time.monotonic()
            if (now - warm_start >= float(setup.get("warm_min_s", 5.0))
                    and now - ledger.last_at >= quiet_s
                    and (not warm_debt or system.replay_debt() >= warm_debt)):
                break
            if now - warm_start > float(setup.get("warm_max_s", 60.0)):
                say("warm-up: compiles never settled or the replay debt "
                    f"stayed at {system.replay_debt():.0f}; measuring anyway")
                break
            time.sleep(0.1)
        keys_before = system.state_keys() if seeded is not None else 0
        if args.fault == "forget_parked":
            say("forget_parked: the running state lost parked instance "
                f"{system.forget_parked(seeded)}")
        t0 = time.monotonic() + 0.3
        child.send("window", t0=t0, seconds=seconds)
        sleep_until(t0)
        observed.fault = args.fault
        counters0 = system.counters()
        routing0 = system.routing() if catches else None
        elections0 = system.elections()
        compiles0 = ledger.compiles
        cpu0, t0_wall = cpu_clocks(), time.time()
        setup_s = t0 - T_PROCESS_START
        say(f"window opens: setup_s={setup_s:.2f} {ledger.report()} "
            f"replay debt {system.replay_debt():.0f} records")
        if args.trace:
            trace_result = traced_stretch(jax, data_dir, t0, seconds,
                                          args.keep_trace)
        sleep_until(t0 + seconds)
        cpu = cpu_shares(cpu0, cpu_clocks(), seconds)
        counters1 = system.counters()
        routing1 = system.routing() if catches else None
        elections_in_window = system.elections() - elections0
        compiles_in_window = ledger.compiles - compiles0
        reply = child.answer(seconds + 90.0)
        requests = [json.loads(line) for line in
                    Path(reply["records_file"]).read_text().splitlines()]
        for r in requests:
            r["variables"] = {"x": r["x"], **payload}
            if "correlation_key" in r:
                r["variables"][keyed[r["pid"]]] = r["correlation_key"]
        publishes = ([json.loads(line) for line in Path(
            reply["publishes_file"]).read_text().splitlines()]
            if "publishes_file" in reply else [])
        acked = [r["key"] for r in requests if r["ok"]]
        drain_start = time.monotonic()
        wait_completed(observed, acked, float(setup.get("drain_max_s", 60.0)),
                       "drain", fatal=False)
        drain_s = time.monotonic() - drain_start
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        stopped = child.ask("stop", 60.0)
        completed_jobs = json.loads(Path(stopped.pop("jobs_file")).read_text())
        counters_end = system.counters()
        marks = system.raft_marks()
        shadow = {"checks": health.shadow_checks,
                  "mismatches": health.shadow_mismatches,
                  "state": str(health.state)}
        running = None
        if seeded is not None:
            # the state store the window wrote to, read while it still runs
            t_read = time.monotonic()
            running = system.parked_now(seeded)
            say(f"parked in the running state, (held, waiting) a replica: "
                f"{sorted(running.values())}, read in "
                f"{time.monotonic() - t_read:.2f}s; keys in the largest "
                f"partition's state: {keys_before} as the window opened, "
                f"{system.state_keys()} now, by family "
                f"{system.state_keys_by_family()}")
        # the program's state is freed; what its replicas hold is read from
        # their files alone
        system.stop()
        if args.fault == "torn_snapshot":
            say(f"torn_snapshot: {srv.damage_snapshots(data_dir / 'data')} "
                "snapshots damaged on the stopped replicas' disks")
        t_check = time.monotonic()
        logs = srv.replica_logs(data_dir / "data", layout, seeded)
        if args.fault == "lose_publish":
            say(f"lose_publish: the replicas' logs are read without message "
                f"{lose_a_publish(logs, publishes)}")
    finally:
        if child is not None:
            child.close()
        system.stop()
        shutil.rmtree(data_dir, ignore_errors=True)

    # ---- the window's numbers
    with observed.lock:
        events = dict(observed.events)
        completed_at = dict(observed.completed_at)
        position_of = dict(observed.position_of)
    window = [r for r in requests if r["phase"] == "window"]
    numbers = window_metrics(window, completed_at, list(completed_at.values()),
                             t0, seconds)
    delta = {k: counters1["counts"].get(k, 0) - counters0["counts"].get(k, 0)
             for k in counters1["counts"]}
    window_keys = {r["key"] for r in window if r["ok"]}
    steps = roofline.token_steps(
        [e for key, evs in events.items() if key in window_keys for e in evs])
    context = {
        "seconds": seconds, "counts": delta, "child": reply,
        "numbers": numbers, "compiles_in_window": compiles_in_window,
        "elections_in_window": elections_in_window,
        "trace": trace_result, "device_kind": first.device_kind,
        "peaks": peaks, "max_fanout": defs.max_fanout(definitions),
        "token_steps_per_s": steps / seconds,
    }
    shown = {k: round(v, 3) for k, v in numbers.items()}
    if catches:
        shown["completion_p50_ms_by_definition"] = by_definition(
            window, completed_at)
    if messages is not None:
        # how the window's messages met their subscriptions
        sides = reference.message_sides(events)
        shown["messages_met"] = dict(Counter(
            str(reference.path_of(sides.get(r["correlation_key"], [])))
            for r in window if r["ok"] and "correlation_key" in r))
    say(f"window: {json.dumps(shown)}")
    say(f"counts in window: {json.dumps(delta)}")
    if catches:
        routed = routing1 - routing0
        caught = Counter(e[:2] for key in window_keys for e in events.get(key, ())
                         if e[:2] in (("TIMER", "TRIGGERED"),
                                      ("PMS", "CORRELATED")))
        duration = {d["id"]: defs.longest_timer_ms([d]) for d in definitions}
        lags = [e[6] - (e[5] - duration[r["pid"]]) for r in window if r["ok"]
                for e in events.get(r["key"], ()) if e[:2] == ("TIMER", "CREATED")]
        say(f"catch path: the window's instances were triggered "
            f"{caught[('TIMER', 'TRIGGERED')]} times and correlated "
            f"{caught[('PMS', 'CORRELATED')]}, a timer's record stamped "
            f"{min(lags, default=0)}-{max(lags, default=0)} ms after the "
            f"clock its due date was read from; commands routed in the window "
            f"{json.dumps(dict(routed))}")
    say(f"child: window={ {k: v for k, v in reply.items() if k != 'records_file'} } "
        f"stop={stopped} drain_s={drain_s:.2f}")
    snapshots = sorted(round(log["snapshot_written_at"] - t0_wall, 1)
                       for log in logs.values() if log["snapshot_written_at"])
    say(f"cores used in the window: {json.dumps(cpu)}; the generator's "
        f"process: {reply.get('cpu_cores')}; snapshots a restart would take, "
        f"persisted at (s after the window opened): {snapshots}")
    say(f"groups by device (whole run): {counters_end['groups_by_device']} "
        f"mesh shards: {counters_end['shard_devices']} "
        f"failures: {counters_end['failures']} shadow: {shadow} "
        f"compiles: {ledger.report()} peak_bytes={peak} "
        f"elections in window: {elections_in_window} "
        f"records exported: {observed.records} repeats compared: "
        f"{observed.repeats} appends lied about: {observed.lies}")

    # ---- where the groups ran
    wanted = "cpu" if rehearsal else "tpu"
    ran_on = set(counters_end["groups_by_device"]) | set(counters_end["shard_devices"])
    off = sorted(d for d in ran_on if not d.startswith(wanted))
    if off or delta.get("groups", 0) <= 0:
        raise Refused(f"kernel groups off the {wanted} ({off}) or none in the "
                      f"window ({delta.get('groups', 0)})")

    if args.keep_events:
        kept = {}
        sides = reference.message_sides(events)
        published = {p["correlation_key"]: p["key"] for p in publishes if p["ok"]}
        for r in requests:
            name = f"{r['pid']}:{r['x']}"
            if r["ok"] and r["key"] in completed_at and name not in kept:
                kept[name] = {"pid": r["pid"], "variables": r["variables"],
                              "events": events[r["key"]]}
                if "correlation_key" in r:   # and its message's own sequence
                    kept[name].update(
                        key=r["key"],
                        message_key=published.get(r["correlation_key"]),
                        message_side=sides.get(r["correlation_key"], []))
        Path(args.keep_events).write_text(json.dumps(
            {"definitions": traffic["definitions"], "instances": kept}))

    # ---- correct
    returned = payload if traffic["workers"]["complete_with_payload"] else {}
    verdict = compare(definitions, requests, events, completed_at, returned,
                      completed_jobs, logs, marks, position_of, publishes)
    checks = verdict["numbers"]
    checks["exports_differing_at_a_position"] = {"value": observed.differing,
                                                 "limit": 0}
    checks["device_failures"] = {
        "value": sum(counters_end["failures"].values()), "limit": 0}
    checks["shadow_mismatches"] = {"value": shadow["mismatches"], "limit": 0}
    if seeded is not None:
        checks.update(parked_checks(
            seeded, system.partitions * int(layout["replication_factor"]),
            running, logs, observed.parked_records))
    correct = decide_correct(checks)
    say(f"check took {time.monotonic() - t_check:.2f}s; examples: "
        f"{verdict['examples']} never completed: {verdict['never']}; "
        f"acknowledgements held by a snapshot, their log compacted: "
        f"{verdict['under_a_snapshot']}")

    metrics = {}
    if args.trace:
        for m in what["per_layer"]:
            value = readers[m["name"]](context, m["args"])
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in what["end_to_end"]:
            value = setup_s if m["name"] == "setup_s" else numbers[m["name"]]
            metrics[m["name"]] = {
                "value": value if math.isfinite(value) else 1e12,
                "unit": m["unit"]}
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": numbers["attempted"],
              "failed": numbers["failed"], "metrics": metrics, "device": device}
    if trace_result is not None:
        device["busy_s"] = trace_result["busy_s"]
        device["window_s"] = trace_result["window_s"]
        result["breakdown"] = {"device_ops": trace_result["device_ops"],
                               "idle_gaps": trace_result["idle_gaps"]}
    result["checks"] = checks
    for name, n in checks.items():
        say(f"check {name}: value={n['value']} "
            f"{'min' if n.get('min') else 'limit'}={n['limit']}")
    say(f"correct={str(correct).lower()} attempted={numbers['attempted']} "
        f"failed={numbers['failed']}")
    if rehearsal:
        say(f"rehearsal on {first.platform}: not a chip run, no result. "
            f"metrics would be: {json.dumps(metrics)}")
        return REHEARSAL_EXIT
    print(json.dumps(result), flush=True)
    return 0


def cpu_clocks() -> dict:
    """CPU seconds so far of this process and of each of its Python threads
    (by name and id; from ``/proc``, in clock ticks), to tell at a window's
    close who had the interpreter."""
    clocks = {"process": time.process_time()}
    tick = os.sysconf("SC_CLK_TCK")
    for t in threading.enumerate():
        try:
            stat = Path(f"/proc/self/task/{t.native_id}/stat").read_text()
        except OSError:
            continue    # the thread ended
        utime, stime = stat.rsplit(")", 1)[1].split()[11:13]
        clocks[f"{t.name}#{t.native_id}"] = (int(utime) + int(stime)) / tick
    return clocks


def cpu_shares(before: dict, after: dict, seconds: float, top: int = 6) -> dict:
    """Cores used over the window: the process, and its busiest threads."""
    used = {name: (after[name] - before[name]) / seconds
            for name in after if name in before}
    threads = sorted(((share, name) for name, share in used.items()
                      if name != "process"), reverse=True)[:top]
    return {"process": round(used["process"], 3),
            "threads": {name.split("#")[0]: round(share, 3)
                        for share, name in threads}}


def sleep_until(t: float) -> None:
    while (left := t - time.monotonic()) > 0:
        time.sleep(min(left, 0.05))


def wait_completed(observed, keys: list, timeout: float, what: str,
                   fatal: bool = True) -> None:
    deadline = time.monotonic() + timeout
    want = set(keys)
    while True:
        with observed.lock:
            missing = len(want - observed.completed_at.keys())
        if not missing:
            return
        if time.monotonic() > deadline:
            if fatal:
                raise RuntimeError(f"{what}: {missing} of {len(want)} instances "
                                   f"did not complete in {timeout:.0f}s")
            say(f"{what}: {missing} of {len(want)} instances did not complete "
                f"in {timeout:.0f}s")
            return
        time.sleep(0.02)


def traced_stretch(jax, data_dir: Path, t0: float, seconds: float,
                   keep: str | None) -> dict:
    """A few seconds of the window under the profiler, then the reduction."""
    length = min(3.0, seconds / 4)
    sleep_until(t0 + min(3.0, seconds / 4))
    trace_dir = data_dir / "trace"
    # the profiler's Python tracer slows every Python thread several times
    # over; the device's own clock and XLA's host events are all that is read
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    started = time.monotonic()
    sleep_until(started + length)
    stopping = time.monotonic()
    jax.profiler.stop_trace()
    say(f"trace: {stopping - started:.2f}s traced, stop took "
        f"{time.monotonic() - stopping:.2f}s")
    trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    if keep:
        Path(keep).parent.mkdir(parents=True, exist_ok=True)
        Path(keep).write_text(json.dumps(trace))
    return trace_reduce.reduce(trace, stopping - started)


if __name__ == "__main__":
    sys.exit(main())
