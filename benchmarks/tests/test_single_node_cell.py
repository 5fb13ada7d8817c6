"""``single1x1.ten_tasks_steady``: the cell is data files plus entries, and
they say the same thing wherever they say it twice."""

import json

import definitions as defs
import run

MANIFEST = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELL = "single1x1.ten_tasks_steady"
CONFIG = "zeebe-single-node-1x1"
#: the keys benchmarks/README.md lists
CONFIG_KEYS = {"source", "layout", "deployment", "assumed", "reduced",
               "guarantees"}
LAYOUT_KEYS = {"brokers", "partitions", "replication_factor", "chips",
               "processes"}
TRAFFIC_KEYS = {"loop", "definitions", "payload", "workers", "give_up_s",
                "setup"}
#: what the cell must report; later PRs add metrics and cells beside these
PER_LAYER = {
    "generator_late_p95_ms", "append_ms_per_group", "raft_elections_in_window",
    "commands_per_group", "device_stage_ms_per_group", "run_collect_roofline",
    "device_idle_share", "compiles_in_window", "build_ms_per_group",
    "device_dispatch_ms_per_group", "device_fetch_ms_per_group",
    "device_unpack_ms_per_group", "gateway_shed_share",
    "admit_wait_ms_per_command", "export_ms_per_record"}


def test_the_files_load_and_carry_every_key():
    what = run.resolve_cell(CELL, MANIFEST)
    config, traffic = what["config"], what["traffic"]
    assert CONFIG_KEYS <= set(config)
    assert LAYOUT_KEYS == set(config["layout"])
    assert TRAFFIC_KEYS <= set(traffic)
    assert config["layout"] == {**config["layout"], "brokers": 1,
                                "partitions": 1, "replication_factor": 1,
                                "chips": what["cell"]["chips"]}
    assert set(config["guarantees"]) == {"ack", "processing", "export",
                                         "replication"}
    assert "accelerator_router_rule" in config["assumed"]
    loop = traffic["loop"]
    assert (loop["kind"], loop["arrivals"], loop["senders"]) == ("open",
                                                                 "fixed", 64)
    # five eighths of the sweep's knee, as one_task_steady's rate was set
    assert 0 < loop["rate_per_s"] <= 0.625 * loop["knee_per_s"] + 1e-9
    assert loop["knee_found"]
    assert traffic["workers"] == {"per_job_type": 16, "max_backoff_s": 0.1,
                                  "completion_delay_ms": 50,
                                  "complete_with_payload": True}
    steady = json.loads((run.HERE / "traffic" / "one_task_steady.json")
                        .read_text())
    assert traffic["payload"] == steady["payload"]
    assert traffic["setup"] == steady["setup"]
    assert traffic["give_up_s"] == steady["give_up_s"]


def test_the_manifest_and_the_file_agree():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    config = json.loads((run.ROOT / entry["file"]).read_text())
    assert config["name"] == CONFIG
    assert len(config["source"]) <= 200
    assert config["source"] == entry["source"]
    assert list(config["reduced"]) == entry["reduced"] == ["starter_rate_per_s"]
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "ten_tasks_steady", 1)
    assert len(cell["why"]) <= 200


def test_every_metric_the_cell_reports_lists_it():
    what = run.resolve_cell(CELL, MANIFEST)
    assert {m["name"] for m in what["end_to_end"]} == {
        "completed_per_s", "completion_p50_ms", "setup_s"}
    assert {m["name"] for m in what["per_layer"]} >= PER_LAYER
    # a metric that reads what only other cells have (job_push_ms_per_job:
    # jobs pushed to streams) does not list this one
    for m in what["per_layer"]:
        assert CELL in m["workloads"], m["name"]
        assert m["moves"] in {"completed_per_s", "completion_p50_ms"}
    # the two that read a histogram of this PR are read in both cells
    for name in ("admit_wait_ms_per_command", "export_ms_per_record"):
        m = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert {"default3x3.one_task_steady", CELL} <= set(m["workloads"])


def test_a_program_without_the_histograms_leaves_the_metrics_out():
    # the parent commit has neither family: the reader finds nothing to read
    what = run.resolve_cell(CELL, MANIFEST)
    context = {"counts": {"groups": 10, "commands": 11}, "child": {}}
    for m in what["per_layer"]:
        if m["name"] in ("admit_wait_ms_per_command", "export_ms_per_record"):
            assert run.load_reader(m["reader"])(context, m["args"]) is None
    context["counts"].update(admit_wait_count=4, admit_wait_seconds=0.002,
                             export_count=1000, export_seconds=0.05)
    read = {m["name"]: run.load_reader(m["reader"])(context, m["args"])
            for m in what["per_layer"]}
    assert read["admit_wait_ms_per_command"] == 0.5
    assert read["export_ms_per_record"] == 0.05


def test_the_mix_is_ten_service_tasks_of_one_job_type():
    traffic = run.resolve_cell(CELL, MANIFEST)["traffic"]
    assert traffic["definitions"] == [{"kind": "task_chain", "id": "ten_tasks",
                                       "tasks": 10}]
    (ten_tasks,) = defs.build_definitions(traffic["definitions"])
    tasks = [n for n in ten_tasks["nodes"] if n["type"] == "serviceTask"]
    assert [n["id"] for n in tasks] == [f"t{i}" for i in range(10)]
    assert defs.job_types([ten_tasks]) == ["work_ten_tasks"]
    assert [n["type"] for n in ten_tasks["nodes"]] == (
        ["startEvent"] + ["serviceTask"] * 10 + ["endEvent"])
    assert len(ten_tasks["flows"]) == 11 and defs.max_fanout([ten_tasks]) == 1
