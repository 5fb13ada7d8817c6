"""Request plans, definitions and the publisher's mix keys, as data: a mix
that names neither ``weight`` nor a catch kind gets, element for element,
the plans it got before either existed, and the two catch kinds deploy as
the program reads them."""

import hashlib
import json

import pytest

import definitions as defs
import loadgen
import run

SEEDS = (1, 2**31 + 17, 2**32 + 12345)
#: sha256 of ``[request_plan(1000 requests), [first_touch_plan(partitions)
#: for partitions 1, 3]]`` of every mix that was committed before the catch
#: kinds and weights, computed by the plans as they were then
GOLDEN = {
    "mixed9_closed:1":
        "236bfde6f2eb1bb06cd2e971c5c6e9ead27d085666af4f9b5edcc9e53495bb35",
    "mixed9_closed:2147483665":
        "04a086a39a6c448f141baa0e952ded32dcd6b8724a7dc659af83c5d5da1fb03f",
    "mixed9_closed:4294979641":
        "5dd9dc44e50cf0115942b0e03fb513a78e07b3e604fd0901b295a72ca24428c2",
    "one_task_capacity:1":
        "bb576a911bca0610d53b5ac48afe95287c2672091d3c86293840e37963b72ea6",
    "one_task_capacity:2147483665":
        "a4c4f2e23fe17353ba19d2ebd88cbc72a1b611bca17f0baa8cd82af88e361300",
    "one_task_capacity:4294979641":
        "a2f1de86f04ed909249772d00b1db867a8cc314445d39a1807339962613c8b23",
    "one_task_on_state:1":
        "f4d91a9f761b8b5b0a01558d3991e92eb90948bf692dc1d61e91c329a481f0b9",
    "one_task_on_state:2147483665":
        "6d1eb170a529f45ee84c08cb5dd46d048f8dc6d04ec6e3da89d7f01fa6c54286",
    "one_task_on_state:4294979641":
        "d9f6e56861c9020c23ce797231c37ca77bc473f10d75b4450bb33b78b560a1c8",
    "one_task_push_steady:1":
        "bb576a911bca0610d53b5ac48afe95287c2672091d3c86293840e37963b72ea6",
    "one_task_push_steady:2147483665":
        "a4c4f2e23fe17353ba19d2ebd88cbc72a1b611bca17f0baa8cd82af88e361300",
    "one_task_push_steady:4294979641":
        "a2f1de86f04ed909249772d00b1db867a8cc314445d39a1807339962613c8b23",
    "one_task_steady:1":
        "bb576a911bca0610d53b5ac48afe95287c2672091d3c86293840e37963b72ea6",
    "one_task_steady:2147483665":
        "a4c4f2e23fe17353ba19d2ebd88cbc72a1b611bca17f0baa8cd82af88e361300",
    "one_task_steady:4294979641":
        "a2f1de86f04ed909249772d00b1db867a8cc314445d39a1807339962613c8b23",
    "ten_tasks_capacity:1":
        "b606e688c65160154eb76cc169821614390ebe9a4f59276ddb292802e94f1b4e",
    "ten_tasks_capacity:2147483665":
        "7f70be196981ba1d4db30537747922a68bca0b7847336237c2f596b9f7f22dd0",
    "ten_tasks_capacity:4294979641":
        "33884a886d1bbd33a0fb0c09486c6d1035c29b2ecf0aea74b87a3e0e55bb1360",
    "ten_tasks_steady:1":
        "b606e688c65160154eb76cc169821614390ebe9a4f59276ddb292802e94f1b4e",
    "ten_tasks_steady:2147483665":
        "7f70be196981ba1d4db30537747922a68bca0b7847336237c2f596b9f7f22dd0",
    "ten_tasks_steady:4294979641":
        "33884a886d1bbd33a0fb0c09486c6d1035c29b2ecf0aea74b87a3e0e55bb1360",
}


def digest(traffic: dict, seed: int) -> str:
    definitions = defs.build_definitions(traffic["definitions"])
    payload = defs.make_payload(traffic.get("payload"), seed)
    plan = defs.request_plan(definitions, 1000, payload, seed)
    touches = [defs.first_touch_plan(definitions, p, payload) for p in (1, 3)]
    return hashlib.sha256(json.dumps([plan, touches], sort_keys=True)
                          .encode()).hexdigest()


@pytest.mark.parametrize("name_seed", sorted(GOLDEN))
def test_a_plan_without_weights_or_catches_is_the_one_it_was(name_seed):
    name, seed = name_seed.split(":")
    traffic = json.loads((run.HERE / "traffic" / f"{name}.json").read_text())
    assert digest(traffic, int(seed)) == GOLDEN[name_seed]


def test_every_committed_mix_without_catches_has_its_golden_digests():
    for path in sorted((run.HERE / "traffic").glob("*.json")):
        traffic = json.loads(path.read_text())
        plain = all(s["kind"] in ("task_chain", "exclusive_chain", "fork_join",
                                  "route", "embedded_subprocess")
                    and "weight" not in s for s in traffic["definitions"])
        assert plain == all(f"{path.stem}:{s}" in GOLDEN for s in SEEDS), path


MIX = json.loads((run.HERE / "traffic" / "default_mix_steady.json").read_text())


def test_weights_repeat_a_definition_in_every_round():
    definitions = defs.build_definitions(MIX["definitions"])
    weights = [s.get("weight", 1) for s in MIX["definitions"]]
    per_round = len(defs.X_VALUES) * sum(weights)
    for seed in SEEDS:
        plan = defs.request_plan(definitions, 5 * per_round, {}, seed)
        for d, w in zip(definitions, weights):
            assert sum(pid == d["id"] for pid, _ in plan) == 5 * w * len(defs.X_VALUES)
        # every round holds each (definition, x) pair weight times
        first = plan[:per_round]
        for d, w in zip(definitions, weights):
            for x in defs.X_VALUES:
                assert sum(v == (d["id"], x) for v in
                           ((p, v["x"]) for p, v in first)) == w


def test_a_message_request_carries_a_key_of_its_own():
    definitions = defs.build_definitions(MIX["definitions"])
    keys = []
    for seed in SEEDS:
        for s in (seed, seed ^ 0xAAAA):     # the window's plan, the warm-up's
            plan = defs.request_plan(definitions, 480, {"p": 1}, s)
            assert plan == defs.request_plan(definitions, 480, {"p": 1}, s)
            keyed = [v for pid, v in plan if pid == "msg_one_task"]
            assert len(keyed) == 60 and all(v["p"] == 1 for v in keyed)
            keys += [v["correlationKey"] for v in keyed]
            assert all("correlationKey" not in v
                       for pid, v in plan if pid != "msg_one_task")
    touches = [v["correlationKey"] for pid, v in
               defs.first_touch_plan(definitions, 3, {}) if pid == "msg_one_task"]
    assert len(touches) == 6
    assert len(set(keys + touches)) == len(keys) + len(touches)


def test_the_catch_kinds_deploy_as_the_program_reads_them():
    from zeebe_tpu.models.bpmn.xml_io import parse_bpmn_xml

    by_id = {d["id"]: d for d in defs.build_definitions(MIX["definitions"])}
    (msg,) = parse_bpmn_xml(defs.to_bpmn_xml(by_id["msg_one_task"]))
    catch = msg.elements["catch"]
    assert catch.element_type.name == "INTERMEDIATE_CATCH_EVENT"
    assert catch.event_type.name == "MESSAGE"
    assert (catch.message.name, catch.message.correlation_key) == (
        "msg", "= correlationKey")
    assert msg.elements["task"].job_type == "work_one_task"
    (timer,) = parse_bpmn_xml(defs.to_bpmn_xml(by_id["timerProcess"]))
    assert timer.elements["wait"].timer.duration == "PT10S"
    assert [defs.jobs_per_instance(d) for d in by_id.values()] == [1, 1, 1]
    assert defs.longest_timer_ms(list(by_id.values())) == 10_000
    assert defs.iso_duration(1500) == "PT1.500S"


def test_a_mix_that_cannot_run_is_refused_by_name():
    with pytest.raises(ValueError, match="weight"):
        defs.build_definitions([{"kind": "task_chain", "id": "t", "tasks": 1,
                                 "weight": 0}])
    unsent = {**MIX}
    del unsent["messages"]
    with pytest.raises(ValueError, match="no publisher sends"):
        loadgen.messages_of(unsent)
    with pytest.raises(ValueError, match="no publisher sends"):
        loadgen.messages_of({**MIX, "messages": {**MIX["messages"], "name": "other"}})
    with pytest.raises(ValueError, match="keys"):
        loadgen.messages_of({**MIX, "messages": {"name": "msg"}})
    for after in (1000, [], [0, -1]):
        with pytest.raises(ValueError, match="publish_after_ms"):
            loadgen.messages_of({**MIX, "messages": {**MIX["messages"],
                                                     "publish_after_ms": after}})
    one_task = {**MIX, "definitions": MIX["definitions"][:1]}
    with pytest.raises(ValueError, match="no definition waits"):
        loadgen.messages_of(one_task)
    del one_task["messages"]
    assert loadgen.messages_of(one_task) is None
    spec, waiting = loadgen.messages_of(MIX)
    assert spec == MIX["messages"] and waiting == {"msg_one_task": "correlationKey"}
    for kind in ("message_catch", "timer_catch"):
        closed = {**MIX, "loop": {"kind": "closed", "clients": 4,
                                  "on": "completion"},
                  "definitions": [s for s in MIX["definitions"]
                                  if s["kind"] in ("task_chain", kind)]}
        with pytest.raises(ValueError, match=kind):
            loadgen.jobs_to_wait_for(closed)


def test_the_drain_outlasts_the_longest_timer():
    assert MIX["setup"]["drain_max_s"] * 1e3 > defs.longest_timer_ms(
        defs.build_definitions(MIX["definitions"]))
