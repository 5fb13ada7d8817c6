"""Schedule, percentile, roofline and metric arithmetic: pure functions."""

import json
import math
from collections import Counter
from pathlib import Path

import pytest

import definitions as defs
import roofline
import run
import schedule

DATA = Path(__file__).parent / "data"


def test_schedule_repeats_from_the_seed_and_ignores_the_system():
    a = schedule.poisson_offsets(6.4, 45, seed=2**31 + 11)
    assert a == schedule.poisson_offsets(6.4, 45, seed=2**31 + 11)
    # no clock and no feedback: it is a function of (rate, seconds, seed) alone
    assert len(a) == round(6.4 * 45) and a[0] == 0.0 and a[-1] < 45
    assert all(later >= earlier for earlier, later in zip(a, a[1:]))


def test_every_seed_gets_the_same_gaps_in_another_order():
    def gaps(seed):
        offsets = schedule.poisson_offsets(10, 30, seed) + [30.0]
        return sorted(round(b - a, 9) for a, b in zip(offsets, offsets[1:]))

    assert gaps(1) == gaps(2)
    assert schedule.poisson_offsets(10, 30, 1) != schedule.poisson_offsets(10, 30, 2)
    # exponential in shape: the median gap is ln 2 of the mean
    g = gaps(1)
    assert g[len(g) // 2] == pytest.approx(math.log(2) * 0.1, rel=0.05)


def test_percentile_counts_failed_requests_as_missing():
    answered = [0.010 * i for i in range(1, 91)]          # 90 of 100 answered
    assert schedule.percentile(answered, 0.50, 100) == pytest.approx(0.50)
    assert schedule.percentile(answered, 0.90, 100) == pytest.approx(0.90)
    assert schedule.percentile(answered, 0.95, 100) == math.inf
    assert schedule.percentile(answered, 0.95, 90) == pytest.approx(0.86)
    with pytest.raises(ValueError):
        schedule.percentile(answered, 0.5, 10)


def test_window_metrics_are_over_all_requests_and_all_the_window():
    t0, seconds = 100.0, 10.0
    window = [{"ok": True, "key": k, "due": t0 + k, "sent": t0 + k + 0.001,
               "ack": t0 + k + 0.020} for k in range(8)]
    window += [{"ok": False, "key": None, "due": t0 + 8, "sent": t0 + 8,
                "ack": t0 + 18}, {"ok": True, "key": 9, "due": t0 + 9,
                                  "sent": t0 + 9, "ack": t0 + 9.02}]
    completed_at = {k: t0 + k + 0.1 for k in range(8)}     # 9 never completes
    completed_at[77] = t0 + 5.0     # created in the warm-up, done in the window
    completed_at[78] = t0 + 10.5    # done after the window closed
    m = run.window_metrics(window, completed_at, list(completed_at.values()),
                           t0, seconds)
    assert m["attempted"] == 10 and m["failed"] == 2
    assert m["completed_per_s"] == pytest.approx(9 / 10.0)
    assert m["completion_p50_ms"] == pytest.approx(100.0)
    assert m["completion_p95_ms"] == math.inf     # the 10th of 10 is missing
    assert m["ack_p95_ms"] == math.inf
    assert m["backlog_at_close"] == 1


def test_roofline_bytes_of_a_hand_counted_group():
    recorded = json.loads((DATA / "recorded_instances.json").read_text())
    one = recorded["instances"]["mx_one:15"]["events"]
    # start event, task, end event pass once each; the task's job completes
    assert roofline.token_steps(one) == 4
    fork = recorded["instances"]["mx_fj:15"]["events"]
    # s, fork, 2 tasks, join, e = 6 activations + 2 job completions
    assert roofline.token_steps(fork) == 8
    # a token step: read 3 + write 3 (elem, phase, inst) + an event row 2 + FO
    assert roofline.least_bytes(4, max_fanout=1) == 4 * (3 + 3 + 2 + 1) * 4
    assert roofline.least_bytes(8, max_fanout=3) == 8 * 11 * 4
    assert roofline.least_seconds(8, 3, "TPU v5 lite") == pytest.approx(
        8 * 11 * 4 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks_of("TPU v9 imaginary")


def test_plans_draw_every_seed_from_the_same_set():
    d = defs.build_definitions(json.loads(
        (run.HERE / "traffic" / "mixed9_closed.json").read_text())["definitions"])
    assert len(d) == 9 and defs.max_fanout(d) == 3
    assert len(defs.job_types(d)) == 10
    a, b = (defs.request_plan(d, 540, {}, seed) for seed in (1, 2**31 + 5))
    assert a != b
    count = Counter((pid, v["x"]) for pid, v in a)
    assert count == Counter((pid, v["x"]) for pid, v in b)
    assert set(count.values()) == {10}            # 54 pairs, ten rounds
    big = defs.make_payload({"strings": 12, "string_chars": 96, "numbers": 8,
                             "nested": 2}, 7)
    assert 4000 < defs.payload_bytes(big) < 4400
    assert defs.payload_bytes(big) == pytest.approx(defs.payload_bytes(
        defs.make_payload({"strings": 12, "string_chars": 96, "numbers": 8,
                           "nested": 2}, 8)), abs=16)


def test_fixed_rate_schedule_and_unknown_arrivals():
    offsets = schedule.fixed_offsets(6.4, 45)
    assert len(offsets) == 288 and offsets[0] == 0.0
    assert offsets[1] - offsets[0] == pytest.approx(1 / 6.4)
    loop = {"rate_per_s": 6.4, "arrivals": "fixed"}
    assert schedule.offsets_of(loop, 45, seed=1) == schedule.offsets_of(loop, 45, seed=2)
    assert schedule.offsets_of({"rate_per_s": 6.4, "arrivals": "poisson"}, 45, 3) == \
        schedule.poisson_offsets(6.4, 45, 3)
    with pytest.raises(ValueError):
        schedule.offsets_of({"rate_per_s": 1, "arrivals": "bursty"}, 10, 1)
    with pytest.raises(KeyError):      # a mix has to say which: no default
        schedule.offsets_of({"rate_per_s": 1}, 10, 1)


def test_every_replica_has_to_hold_every_acknowledgement_and_the_same_bytes():
    key = (1 << 51) | 5           # partition 1 (a key's upper bits)
    job = (1 << 51) | 9
    request = {"ok": True, "key": key, "pid": "p", "variables": {}}
    log = {"entries": {1: b"a", 2: b"b", 3: b"c"}, "created": {key},
           "jobs_completed": {job}}
    logs = {(1, "broker-0"): log, (1, "broker-1"): dict(log),
            (2, "broker-0"): {"entries": {}, "created": set(),
                              "jobs_completed": set()}}
    marks = {(1, "broker-0"): 3, (1, "broker-1"): 2, (2, "broker-0"): 0}

    def numbers(logs, marks=marks):
        n = run.compare([], [request], {}, {}, {}, [job], logs, marks)["numbers"]
        return (n["acks_missing_in_a_replica"]["value"],
                n["replica_log_entries_differing"]["value"])

    assert numbers(logs) == (0, 0)
    behind = {**log, "created": set(), "jobs_completed": set()}
    assert numbers({**logs, (1, "broker-1"): behind}) == (2, 0)
    other = {**log, "entries": {1: b"a", 2: b"X", 3: b"Y"}}
    # index 3 is past what broker-1 had committed: it may still differ
    assert numbers({**logs, (1, "broker-1"): other}) == (0, 1)
    short = {**log, "entries": {1: b"a"}}
    assert numbers({**logs, (1, "broker-1"): short}) == (0, 0)
    # a replica that compacted its log behind a snapshot on its disk: what the
    # snapshot covers, by the position the record was exported at, is held;
    # a key past it, or one the exporter never saw, is missing as before
    compacted = {**behind, "snapshot_position": 40}
    for position_of, missing in (({key: 30, job: 40}, 0), ({key: 30, job: 41}, 1),
                                 ({key: 30}, 1), ({}, 2)):
        n = run.compare([], [request], {}, {}, {}, [job],
                        {**logs, (1, "broker-1"): compacted}, marks, position_of)
        assert n["numbers"]["acks_missing_in_a_replica"]["value"] == missing
    assert run.compare([], [request], {}, {}, {}, [job],
                       {**logs, (1, "broker-1"): behind}, marks, {key: 30, job: 40}
                       )["numbers"]["acks_missing_in_a_replica"]["value"] == 2


def test_a_snapshot_holds_what_its_log_compacted_only_if_a_restart_would_take_it(tmp_path):
    """``served.replica_logs`` through the program's own store: a snapshot
    counts with its manifest borne out and its state loading; torn (the
    fault ``torn_snapshot``), it covers nothing."""
    import served
    from zeebe_tpu.state.db import ZbDb
    from zeebe_tpu.state.snapshot import STATE_FILE, FileBasedSnapshotStore

    partition = tmp_path / "broker-0" / "partition-1"
    (partition / "raft" / "raft-log").mkdir(parents=True)
    layout = {"brokers": 1, "partitions": 1, "replication_factor": 1}
    assert served.replica_logs(tmp_path, layout)[(1, "broker-0")][
        "snapshot_position"] == 0
    transient = FileBasedSnapshotStore(partition / "snapshots"
                                       ).new_transient_snapshot(10, 1, 500, 480)
    transient.write_file(STATE_FILE, ZbDb().to_snapshot_bytes())
    transient.persist()
    log = served.replica_logs(tmp_path, layout)[(1, "broker-0")]
    assert log["snapshot_position"] == 500 and log["snapshot_written_at"] > 0
    assert served.damage_snapshots(tmp_path) == 1
    log = served.replica_logs(tmp_path, layout)[(1, "broker-0")]
    assert log["snapshot_position"] == 0 and log["snapshot_written_at"] is None


def test_cores_used_over_a_window_are_the_clocks_differences():
    before = {"process": 10.0, "pump#7": 4.0, "serve#8": 1.0, "gone#9": 2.0}
    after = {"process": 19.0, "pump#7": 8.5, "serve#8": 2.0, "new#10": 0.5}
    assert run.cpu_shares(before, after, 9.0, top=1) == {
        "process": 1.0, "threads": {"pump": 0.5}}
    clocks = run.cpu_clocks()       # this thread is in it, under its name
    assert clocks["process"] > 0 and any(
        name.startswith("MainThread#") for name in clocks)
