"""A deployment's ``state`` key (``seed_state.py``), on the CPU at 200
instances: what the seed leaves on a data directory, what a cluster recovers
from it, and what the comparison holds it to. The five whole-harness cases
are slow (about twenty seconds each: ``--rehearse-cpu``)."""

import json
import os
import subprocess
import sys

import pytest

import definitions as defs
import run
import seed_state
import served

LAYOUT = {"brokers": 1, "partitions": 1, "replication_factor": 1}
PARKED = {"kind": "task_chain", "id": "parked", "tasks": 1}
MANIFEST = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CONFIG = "zeebe-largestate-200k"
CELL = "largestate200k.one_task_steady"
#: the column families a parked instance has rows in
INSTANCE_ROWS = {"ELEMENT_INSTANCE_PARENT_CHILD", "ELEMENT_INSTANCE_KEY", "JOBS",
                 "JOB_STATES", "JOB_ACTIVATABLE", "VARIABLES"}


def state_of(instances: int, definition: dict = PARKED) -> dict:
    return {"parked": {"instances": instances, "definition": definition,
                       "variables": {"x": 5}}}


def cluster(directory, layout=LAYOUT, kernel_backend=False):
    from zeebe_tpu.gateway import ClusterRuntime

    runtime = ClusterRuntime(
        broker_count=layout["brokers"], partition_count=layout["partitions"],
        replication_factor=layout["replication_factor"], directory=directory,
        kernel_backend=kernel_backend, backpressure_enabled=False)
    runtime.start()
    return runtime


def submit(runtime, partition, value_type, intent, value, **kw):
    from zeebe_tpu.protocol import command

    answer = runtime.submit(partition, command(value_type, intent, value, **kw),
                            timeout_s=30.0)
    assert not answer.is_rejection, answer
    return answer.value


def activate(runtime, job_type: str, most: int) -> list:
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import JobBatchIntent

    batch = submit(runtime, 1, ValueType.JOB_BATCH, JobBatchIntent.ACTIVATE,
                   {"type": job_type, "worker": "test", "timeout": 60_000,
                    "maxJobsToActivate": most})
    return list(zip(batch["jobKeys"], batch["jobs"]))


def rows_of(db) -> dict:
    """Column family name -> [(key parts, value)] of a state, in key order."""
    from zeebe_tpu.state import ColumnFamilyCode as CF
    from zeebe_tpu.state.db import decode_key

    out = {}
    with db.transaction():
        for code in CF:
            rows = [(decode_key(key)[1], value)
                    for key, value in db.column_family(code).items()]
            if rows:
                out[code.name] = rows
    return out


# ---------------------------------------------------------------------------
# the key absent


def test_without_the_key_served_builds_its_cluster_as_before(monkeypatch, tmp_path):
    import zeebe_tpu.gateway as gateway

    built = {}

    class Runtime:
        def __init__(self, **kw):
            built.update(kw)

        def start(self):
            built["started"] = True

    class Gateway:
        address = "127.0.0.1:1"

        def __init__(self, runtime, bind):
            pass

        def start(self):
            pass

    monkeypatch.setattr(gateway, "ClusterRuntime", Runtime)
    monkeypatch.setattr(gateway, "Gateway", Gateway)
    served.Served({**LAYOUT, "partitions": 3, "brokers": 3,
                   "replication_factor": 3}, tmp_path / "data", served.Observed())
    factory = built.pop("exporters_factory")
    assert list(factory()) == [served.EXPORTER_ID] == ["bench"]
    # the arguments of the accepted cells' runs (PR 32's tree), name for name
    assert built == {
        "kernel_backend": True, "broker_count": 3, "partition_count": 3,
        "replication_factor": 3, "directory": tmp_path / "data",
        "backpressure_algorithm": built["backpressure_algorithm"],
        "backpressure_enabled": built["backpressure_enabled"],
        "disk_min_free_bytes": built["disk_min_free_bytes"], "started": True}
    # and the directory is fresh: nothing seeded it
    assert not (tmp_path / "data").exists()


def test_only_a_deployment_with_the_key_is_seeded():
    for entry in MANIFEST["configs"]:
        config = json.loads((run.ROOT / entry["file"]).read_text())
        assert ("state" in config) == (entry["name"] == CONFIG)


# ---------------------------------------------------------------------------
# the seeded partition


@pytest.fixture(scope="module")
def seeded(tmp_path_factory):
    directory = tmp_path_factory.mktemp("seeded")
    record = seed_state.seed(LAYOUT, state_of(200), directory,
                             served.EXPORTER_ID, cohort=40)
    runtime = cluster(directory)
    yield record, runtime, directory
    runtime.stop()


def test_the_seed_says_what_it_did(seeded):
    record, runtime, _directory = seeded
    assert (record["id"], record["instances"], record["per_partition"],
            record["cohort"], record["variables"]) == (
                "parked", 200, 200, 40, {"x": 5})
    # seven rows an instance, and the thirteen of an empty deployed partition
    assert record["rows"] == 200 * 7 + 13
    leader = runtime._leader_partition(1)
    assert leader.db.key_count >= record["rows"]
    assert leader.stream.last_position >= record["end_position"][1] > 0


def test_every_replica_recovers_all_of_them_waiting(seeded):
    record, runtime, _directory = seeded
    leader = runtime._leader_partition(1)
    with runtime._partition_guard(1):
        assert served.parked_in(leader.db, "parked", {"x": 5}) == (200, 200)
        # a variable at another value: held, and not as it was parked
        assert served.parked_in(leader.db, "parked", {"x": 6}) == (200, 0)
        counts = leader.db.key_counts_by_cf()
    assert counts["ELEMENT_INSTANCE_KEY"] == 400
    assert counts["JOB_ACTIVATABLE"] == counts["JOBS"] == 200
    # the harness's reading of the running state, a replica at a time
    system = served.Served.__new__(served.Served)
    system.runtime = runtime
    assert system.parked_now(record) == {(1, "broker-0"): (200, 200)}


def test_it_answers_a_create_and_a_completion_of_the_live_definition(seeded):
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import (DeploymentIntent, JobIntent,
                                           ProcessInstanceCreationIntent)

    _record, runtime, _directory = seeded
    live = defs.task_chain("one_task", 1)
    submit(runtime, 1, ValueType.DEPLOYMENT, DeploymentIntent.CREATE,
           {"resources": [{"resourceName": "one_task.bpmn",
                           "resource": defs.to_bpmn_xml(live)}]})
    created = submit(
        runtime, 1, ValueType.PROCESS_INSTANCE_CREATION,
        ProcessInstanceCreationIntent.CREATE,
        {"bpmnProcessId": "one_task", "processDefinitionKey": -1, "version": -1,
         "variables": {"x": 15}})
    instance = created["processInstanceKey"]
    # its key lies past every clone's: the generator was moved
    leader = runtime._leader_partition(1)
    with runtime._partition_guard(1):
        held = [parts[0] for parts, _ in rows_of(leader.db)["ELEMENT_INSTANCE_KEY"]]
    assert instance == max(held) - 4 and len(set(held)) == 402
    # the live type's workers get the live job and none of the parked ones
    jobs = activate(runtime, "work_one_task", 32)
    assert [job["processInstanceKey"] for _key, job in jobs] == [instance]
    submit(runtime, 1, ValueType.JOB, JobIntent.COMPLETE, {"variables": {}},
           key=jobs[0][0])
    with runtime._partition_guard(1):
        assert served.parked_in(leader.db, "one_task") == (0, 0)
        assert served.parked_in(leader.db, "parked") == (200, 200)


def test_the_parked_jobs_are_activatable_by_their_own_type(seeded):
    # last of the fixture's tests: it touches five of them
    _record, runtime, _directory = seeded
    jobs = activate(runtime, "work_parked", 5)
    assert len(jobs) == 5
    assert {job["bpmnProcessId"] for _key, job in jobs} == {"parked"}
    leader = runtime._leader_partition(1)
    with runtime._partition_guard(1):
        assert served.parked_in(leader.db, "parked") == (200, 195)


def test_a_row_gone_from_the_running_state_is_seen(seeded):
    # after the others: it breaks the fixture's state, one row at a time
    from zeebe_tpu.state import ColumnFamilyCode as CF

    record, runtime, _directory = seeded
    leader = runtime._leader_partition(1)
    system = served.Served.__new__(served.Served)
    system.runtime = runtime
    held, waiting = system.parked_now(record)[(1, "broker-0")]
    assert held == 200
    with runtime._partition_guard(1):
        with leader.db.transaction():
            roots = [row["key"] for _key, row in
                     leader.db.column_family(CF.ELEMENT_INSTANCE_KEY).items()
                     if row["value"]["bpmnProcessId"] == "parked"
                     and row["value"]["flowScopeKey"] < 0]
            # the last instance loses its variable, the one before it its
            # entry in the parent-child index: held, no longer as parked
            leader.db.column_family(CF.VARIABLES).delete((roots[-1], "x"))
            family = leader.db.column_family(CF.ELEMENT_INSTANCE_PARENT_CHILD)
            (key, _none), = list(family.items((roots[-2],)))
            family._ctx().delete(key)
    assert system.parked_now(record)[(1, "broker-0")] == (200, waiting - 2)
    # the fault forget_parked: the first instance leaves with its rows (six:
    # the test before activated its job, whose index entry went then)
    keys = leader.db.key_count
    assert system.forget_parked(record) == roots[0]
    assert leader.db.key_count == keys - 6
    assert system.parked_now(record)[(1, "broker-0")] == (199, waiting - 2)
    read = run.parked_checks(record, 1, system.parked_now(record), {}, 0)
    assert read["parked_missing"]["value"] == 1
    assert read["parked_touched"]["value"] == 199 - (waiting - 2)


# ---------------------------------------------------------------------------
# cloned rows against the served path's


def normal(rows: dict, first: int) -> dict:
    """Rows with every key at or above ``first`` replaced by its rank among
    the keys that stand in rows: the keys an instance's rows name keep their
    order, whatever the generator skipped between them."""
    keys = sorted(set(seed_state._keys_in(
        [list(rows[cf]) for cf in sorted(rows)], first, 1 << 62, [])))
    rank = {key: i for i, key in enumerate(keys)}

    def mapped(obj):
        if type(obj) is int:
            return rank.get(obj, obj)
        if type(obj) is dict:
            return {k: mapped(v) for k, v in obj.items()}
        if type(obj) in (list, tuple):
            return [mapped(v) for v in obj]
        return obj

    return {cf: mapped(rows[cf]) for cf in rows}


def test_cloned_rows_equal_the_served_paths_row_for_row(tmp_path):
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import (DeploymentIntent,
                                           ProcessInstanceCreationIntent)
    from zeebe_tpu.state.snapshot import FileBasedSnapshotStore, load_chain_db

    # fifty created one by one through the runtime the gateway submits to,
    # on the kernel backend, as the harness's cluster runs
    runtime = cluster(tmp_path / "served", kernel_backend=True)
    try:
        deployed = submit(
            runtime, 1, ValueType.DEPLOYMENT, DeploymentIntent.CREATE,
            {"resources": [{"resourceName": "parked.bpmn", "resource":
                            defs.to_bpmn_xml(defs.task_chain("parked", 1))}]})
        first = deployed["processesMetadata"][0]["processDefinitionKey"] + 1
        for _ in range(50):
            submit(runtime, 1, ValueType.PROCESS_INSTANCE_CREATION,
                   ProcessInstanceCreationIntent.CREATE,
                   {"bpmnProcessId": "parked", "processDefinitionKey": -1,
                    "version": -1, "variables": {"x": 5}})
        leader = runtime._leader_partition(1)
    finally:
        runtime.stop()
    by_served = rows_of(leader.db)

    # fifty by a cohort of ten cloned five times, as recovery loads them
    seed_state.seed(LAYOUT, state_of(50), tmp_path / "seeded",
                    served.EXPORTER_ID, cohort=10)
    store = FileBasedSnapshotStore(
        tmp_path / "seeded" / "broker-0" / "partition-1" / "snapshots")
    by_clones = rows_of(load_chain_db(store.latest_valid_chain()))

    assert INSTANCE_ROWS <= set(by_served) and INSTANCE_ROWS <= set(by_clones)
    served_rows = normal({cf: by_served[cf] for cf in INSTANCE_ROWS}, first)
    cloned_rows = normal({cf: by_clones[cf] for cf in INSTANCE_ROWS}, first)
    for cf in sorted(INSTANCE_ROWS):
        assert len(cloned_rows[cf]) == len(served_rows[cf]) == (
            100 if cf == "ELEMENT_INSTANCE_KEY" else 50), cf
        assert cloned_rows[cf] == served_rows[cf], cf
    # no other row of either state names an instance's key
    for rows in (by_served, by_clones):
        others = {cf: rows[cf] for cf in set(rows) - INSTANCE_ROWS
                  if not cf.startswith("REQUEST_DEDUPE")}   # stored replies
        assert not seed_state._keys_in(
            [list(v) for v in others.values()], first, 1 << 62, [])
    # and the generator stands past every key in a row
    highest = max(parts[0] for parts, _ in by_clones["JOBS"])
    (_, counter), = by_clones["KEY"]
    assert (1 << 51) + counter > highest


def test_three_partitions_three_replicas_hold_the_same(tmp_path):
    from zeebe_tpu.state.snapshot import FileBasedSnapshotStore, load_chain_db

    layout = {"brokers": 3, "partitions": 3, "replication_factor": 3}
    record = seed_state.seed(layout, state_of(90), tmp_path, served.EXPORTER_ID,
                             cohort=8)
    assert record["per_partition"] == 30 and sorted(record["end_position"]) == [1, 2, 3]
    for pid in (1, 2, 3):
        states = []
        for broker in range(3):
            store = FileBasedSnapshotStore(
                tmp_path / f"broker-{broker}" / f"partition-{pid}" / "snapshots")
            db = load_chain_db(store.latest_valid_chain())
            assert served.parked_in(db, "parked", {"x": 5}) == (30, 30)
            states.append(rows_of(db))
        assert states[0] == states[1] == states[2]
        keys = [parts[0] for parts, _ in states[0]["JOBS"]]
        assert {key >> 51 for key in keys} == {pid}


# ---------------------------------------------------------------------------
# what is refused, before any cluster


def test_a_mix_that_uses_the_parked_id_or_job_type_is_refused_by_name():
    state = state_of(200, {"kind": "task_chain", "id": "one_task", "tasks": 1})
    mix = [{"kind": "task_chain", "id": "one_task", "tasks": 1}]
    with pytest.raises(ValueError, match="definition id 'one_task'"):
        seed_state.refuse_clash(state, mix)
    # another id, the same job type
    state = state_of(200, {"kind": "fork_join", "id": "x", "branches": 2})
    mix = [{"kind": "fork_join", "id": "y", "branches": 2,
            "job_type_per_branch": False}]
    seed_state.refuse_clash(state, mix)     # work_x and work_y: apart
    with pytest.raises(ValueError, match="runs no job"):
        seed_state.refuse_clash(state_of(1, {"kind": "exclusive_chain",
                                             "id": "g", "gateways": 1}), mix)
    with pytest.raises(ValueError, match="known is"):
        seed_state.refuse_clash({"parkd": {}}, mix)


def manifest_at_200(tmp_path, definition=None) -> str:
    """BENCHMARK.json with the deployment's state cut to 200 instances."""
    manifest = json.loads(json.dumps(MANIFEST))
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    config = json.loads((run.ROOT / entry["file"]).read_text())
    config["state"]["parked"]["instances"] = 200    # the engine makes them all
    if definition is not None:
        config["state"]["parked"]["definition"] = definition
    (tmp_path / "config.json").write_text(json.dumps(config))
    entry["file"] = str(tmp_path / "config.json")
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    return str(tmp_path / "manifest.json")


def test_the_harness_refuses_the_clash_before_the_cluster_starts(tmp_path, capsys):
    manifest = manifest_at_200(tmp_path, {"kind": "task_chain", "id": "one_task",
                                          "tasks": 1})
    before = set((run.ROOT / ".bench_data").glob("*")) if (
        run.ROOT / ".bench_data").is_dir() else set()
    code = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                     "--manifest", manifest, "--rehearse-cpu"])
    assert code == run.REFUSED_EXIT
    assert "definition id 'one_task'" in capsys.readouterr().err
    after = set((run.ROOT / ".bench_data").glob("*")) if (
        run.ROOT / ".bench_data").is_dir() else set()
    assert after == before                 # no data directory was made
    assert run.main(["--workload", "single1x1.ten_tasks_steady", "--seed", "1",
                     "--seconds", "1", "--rehearse-cpu", "--fault",
                     "lose_parked"]) == run.REFUSED_EXIT
    assert "no state is seeded" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the two numbers


def test_the_numbers_from_what_the_replicas_hold():
    seeded = {"per_partition": 100}
    sound = {"parked_held": 100, "parked_waiting": 100, "parked_in_log": 0}
    logs = {(1, "broker-0"): dict(sound), (1, "broker-1"): dict(sound)}
    running = {(1, "broker-0"): (100, 100), (1, "broker-1"): (100, 100)}
    read = run.parked_checks(seeded, 2, running, logs, 0)
    assert read == {name: {"value": 0, "limit": 0} for name in (
        "parked_missing", "parked_touched", "parked_missing_on_disk",
        "parked_touched_on_disk")}
    assert run.decide_correct(read)
    # one replica's disk lost one; a replica that is not there holds none
    logs[(1, "broker-1")].update(parked_held=99, parked_waiting=99)
    read = run.parked_checks(seeded, 2, running, logs, 0)
    assert (read["parked_missing_on_disk"]["value"],
            read["parked_missing"]["value"]) == (1, 0)
    read = run.parked_checks(seeded, 3, running, logs, 0)
    assert (read["parked_missing_on_disk"]["value"],
            read["parked_missing"]["value"]) == (101, 100)
    # a replica's loss does not hide behind another's duplicate
    logs[(1, "broker-0")].update(parked_held=101, parked_waiting=101)
    running[(1, "broker-0")], running[(1, "broker-1")] = (98, 98), (102, 102)
    read = run.parked_checks(seeded, 2, running, logs, 0)
    assert (read["parked_missing_on_disk"]["value"],
            read["parked_missing"]["value"]) == (2, 4)
    # touched: exported or no longer waiting in a running state; named in a
    # log behind the seed or no longer waiting in a recovered one
    logs[(1, "broker-0")].update(parked_waiting=98, parked_in_log=4)
    running[(1, "broker-1")] = (102, 97)
    read = run.parked_checks(seeded, 2, running, logs, 2)
    assert read["parked_touched"]["value"] == 2 + 5
    assert read["parked_touched_on_disk"]["value"] == 4 + 3
    assert not run.decide_correct(read)


def test_the_exporter_counts_records_that_name_the_parked_definition():
    class Record:
        def __init__(self, value):
            self.value = value

    assert served.names_process(Record({"bpmnProcessId": "parked"}), "parked")
    assert not served.names_process(Record({"bpmnProcessId": "one_task"}), "parked")
    assert served.names_process(
        Record({"type": "t", "jobs": [{"bpmnProcessId": "one_task"},
                                      {"bpmnProcessId": "parked"}]}), "parked")
    assert not served.names_process(Record({"resources": []}), "parked")


@pytest.mark.parametrize("fault,running,on_disk,expected", [
    (None, 0, 0, True),
    ("lose_parked", 1, 1, False),    # the control of the guarantee "state"
    ("forget_parked", 1, 0, False),  # a row gone from the running store alone
    ("lose_acked", 0, 0, False),     # durability, as in the twin's cell
    ("alter_record", 0, 0, False)])  # an answer altered where it is produced
def test_the_whole_harness_holds_the_run_to_the_parked_state(
        fault, running, on_disk, expected, tmp_path):
    cmd = [sys.executable, str(run.HERE / "run.py"), "--workload", CELL,
           "--seed", str(2**31 + 33), "--seconds", "4", "--trace", "0",
           "--rehearse-cpu", "--manifest", manifest_at_200(tmp_path)]
    proc = subprocess.run(cmd + (["--fault", fault] if fault else []),
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == run.REHEARSAL_EXIT, proc.stderr[-2000:]
    assert "seeded 200 instances in " in proc.stderr
    assert f"check parked_missing: value={running} limit=0" in proc.stderr
    assert f"check parked_missing_on_disk: value={on_disk} limit=0" in proc.stderr
    assert "check parked_touched: value=0 limit=0" in proc.stderr
    assert "check parked_touched_on_disk: value=0 limit=0" in proc.stderr
    assert f"correct={str(expected).lower()}" in proc.stderr, proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# the deployment, the mix and the metrics as data


def test_the_deployment_and_its_mix_say_what_the_issue_asked():
    what = run.resolve_cell(CELL, MANIFEST)
    config, traffic = what["config"], what["traffic"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert config["name"] == CONFIG and config["source"].startswith(
        "camunda/zeebe engine/src/test/java/io/camunda/zeebe/engine/perf/"
        "EngineLargeStatePerformanceTest.java")
    assert len(entry["source"]) <= 200 and entry["source"] == config["source"]
    assert list(config["reduced"]) == entry["reduced"] == ["starter_rate_per_s"]
    assert config["layout"] == {**config["layout"], "brokers": 1, "partitions": 1,
                                "replication_factor": 1, "chips": 1}
    parked = config["state"]["parked"]
    assert parked["instances"] == 200_000      # the source's count, not cut
    assert parked["definition"] == {"kind": "task_chain", "tasks": 1,
                                    "id": parked["definition"]["id"]}
    assert set(config["guarantees"]) == {"ack", "processing", "export",
                                         "replication", "state"}
    assert "accelerator_router_rule" in config["assumed"]
    seed_state.refuse_clash(config["state"], traffic["definitions"])
    loop = traffic["loop"]
    assert (loop["kind"], loop["arrivals"], loop["senders"]) == ("open", "fixed", 64)
    assert 0 < loop["rate_per_s"] <= 0.625 * loop["knee_per_s"] + 1e-9
    assert loop["knee_found"]
    assert traffic["definitions"] == [{"kind": "task_chain", "id": "one_task",
                                       "tasks": 1}]
    assert traffic["payload"] is None and traffic["give_up_s"] == 10.0
    assert traffic["workers"] == {"per_job_type": 8, "max_backoff_s": 0.1,
                                  "completion_delay_ms": 0,
                                  "complete_with_payload": False}
    cell = what["cell"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "one_task_on_state", 1)
    twin = run.resolve_cell("single1x1.ten_tasks_steady", MANIFEST)
    assert {m["name"] for m in what["per_layer"]} == {
        m["name"] for m in twin["per_layer"]}
    assert {m["name"] for m in what["end_to_end"]} == {
        "completed_per_s", "completion_p50_ms", "setup_s"}


def test_the_stages_of_a_group_are_all_read_by_data_files():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    append = set(by_name["append_ms_per_group"]["workloads"])
    context = {"counts": {"materialize_seconds": 0.3, "materialize_count": 1000,
                          "flush_seconds": 0.006, "flush_count": 1000,
                          "group_seconds": 2.5, "group_count": 1000,
                          "sequential_seconds": 1.0, "sequential_count": 500}}
    expected = {"materialize_ms_per_group": 0.3, "flush_ms_per_group": 0.006,
                "group_wall_ms_per_group": 2.5, "sequential_ms_per_command": 2.0}
    for name, value in expected.items():
        entry = by_name[name]
        assert set(entry["workloads"]) == append, name
        assert (entry["layer"], entry["moves"], entry["unit"]) == (
            "stream processor", "completion_p50_ms", "ms")
        spec = json.loads((run.HERE / "layer_metrics" / f"{name}.json").read_text())
        assert spec["reader"] == "ratio"
        assert run.load_reader("ratio")(context, spec["args"]) == pytest.approx(value)
        assert run.load_reader("ratio")({"counts": {}}, spec["args"]) is None
