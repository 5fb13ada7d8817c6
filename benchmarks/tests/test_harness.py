"""The harness finds everything by name and refuses what it does not know;
a cell, a deployment and a metric are each added as files plus one entry."""

import json
from pathlib import Path

import pytest

import run

MANIFEST = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def test_every_name_in_the_manifest_has_its_files():
    for cell in MANIFEST["workloads"]:
        what = run.resolve_cell(cell["name"], MANIFEST)
        assert what["config"]["layout"]["chips"] == cell["chips"]
        assert {m["name"] for m in what["end_to_end"]} >= {"setup_s",
                                                           "completed_per_s"}
        for m in what["per_layer"]:
            assert callable(run.load_reader(m["reader"]))
    for metric in MANIFEST["per_layer"]:
        spec = json.loads((run.HERE / "layer_metrics" /
                           f"{metric['name']}.json").read_text())
        # the manifest says what the metric is, its file only how it is read:
        # nothing is said twice, so a new cell edits no file that is there
        assert set(spec) == {"reader", "args"}, metric["name"]
        assert "mfu" not in metric["name"]


def test_unknown_names_are_refused():
    with pytest.raises(run.Refused, match="unknown cell"):
        run.resolve_cell("no.such_cell", MANIFEST)
    broken = json.loads(json.dumps(MANIFEST))
    broken["workloads"][0]["config"] = "no-such-config"
    with pytest.raises(run.Refused, match="unknown configuration"):
        run.resolve_cell(broken["workloads"][0]["name"], broken)
    broken = json.loads(json.dumps(MANIFEST))
    broken["workloads"][0]["traffic"] = "no_such_mix"
    with pytest.raises(run.Refused, match="unknown traffic mix"):
        run.resolve_cell(broken["workloads"][0]["name"], broken)
    broken = json.loads(json.dumps(MANIFEST))
    shared = next(m for m in broken["per_layer"] if m["name"] == "commands_per_group")
    broken["per_layer"].append({**shared, "name": "no_such_metric"})
    with pytest.raises(run.Refused, match="unknown per-layer metric"):
        run.resolve_cell(broken["workloads"][0]["name"], broken)
    with pytest.raises(run.Refused, match="unknown metric reader"):
        run.load_reader("no_such_reader")


def test_a_cell_is_added_as_one_entry_over_files_that_are_there():
    # the single-node deployment under the default deployment's capacity mix:
    # files that are there, and a metric whose file is there and which no
    # cell reports yet — entries only, no file edited
    more = json.loads(json.dumps(MANIFEST))
    name = "single1x1.one_task_capacity"
    more["workloads"].append({"name": name, "config": "zeebe-single-node-1x1",
                              "traffic": "one_task_capacity", "chips": 1,
                              "why": "capacity of one partition, short instances"})
    shared = next(m for m in more["per_layer"] if m["name"] == "commands_per_group")
    shared["workloads"].append(name)
    more["per_layer"].append({**shared, "name": "mesh_coalesced_share",
                              "unit": "%", "layer": "mesh runner",
                              "workloads": [name]})
    what = run.resolve_cell(name, more)
    assert what["traffic"]["loop"] == {**what["traffic"]["loop"],
                                       "kind": "closed", "on": "completion"}
    assert [(m["name"], m["reader"]) for m in what["per_layer"]] == [
        ("commands_per_group", "ratio"), ("mesh_coalesced_share", "ratio")]
    assert {m["name"] for m in what["end_to_end"]} == {"completed_per_s", "setup_s"}


def test_readers_leave_out_what_they_cannot_read():
    ratio = run.load_reader("ratio")
    assert ratio({"counts": {"a": 3, "b": 0}}, {"numerator": "counts.a",
                                                "denominator": "counts.b"}) is None
    assert ratio({"trace": None}, {"numerator": "trace.busy_s",
                                   "denominator": "trace.window_s"}) is None
    assert ratio({"trace": {"busy_s": 1.0, "window_s": 4.0}},
                 {"numerator": "trace.busy_s", "denominator": "trace.window_s",
                  "one_minus": True, "scale": 100.0}) == pytest.approx(75.0)
    roof = run.load_reader("kernel_roofline")
    context = {"trace": {"module_s": {"jit_other": 1.0}, "window_s": 3.0},
               "token_steps_per_s": 100.0, "max_fanout": 3,
               "device_kind": "TPU v5 lite"}
    assert roof(context, {"modules": ["run_collect"]}) is None   # never 0
    context["trace"]["module_s"]["jit_run_collect"] = 0.001
    share = roof(context, {"modules": ["run_collect"]})
    assert share == pytest.approx(100 * (300 * 11 * 4 / 819e9) / 0.001)
    with pytest.raises(KeyError):
        roof({**context, "device_kind": "TPU v9"}, {"modules": ["run_collect"]})


def test_correct_is_decided_number_by_number():
    good = {"a": {"value": 0, "limit": 0}, "n": {"value": 5, "limit": 1, "min": True}}
    assert run.decide_correct(good)
    assert not run.decide_correct({**good, "a": {"value": 1, "limit": 0}})
    assert not run.decide_correct({**good, "n": {"value": 0, "limit": 1, "min": True}})
