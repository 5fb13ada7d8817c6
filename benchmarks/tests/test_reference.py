"""The plain reference accepts what the engine recorded for each definition
and each side of every gateway, and refuses every way of breaking it."""

import copy
import json
from pathlib import Path

import pytest

import definitions as defs
import reference

RECORDED = json.loads(
    (Path(__file__).parent / "data" / "recorded_instances.json").read_text())
DEFINITIONS = {d["id"]: d for d in defs.build_definitions(RECORDED["definitions"])}
INSTANCES = {name: {**inst, "events": [tuple(e) for e in inst["events"]]}
             for name, inst in RECORDED["instances"].items()}


def verdict(inst, events=None, variables=None):
    try:
        reference.accept(DEFINITIONS[inst["pid"]],
                         variables or inst["variables"],
                         inst["events"] if events is None else events)
    except reference.Mismatch as err:
        return str(err)
    return None


def test_the_recording_covers_every_definition_and_both_sides():
    pids = {inst["pid"] for inst in INSTANCES.values()}
    assert pids == set(DEFINITIONS)
    xs = {inst["variables"]["x"] for inst in INSTANCES.values()}
    assert xs == set(defs.X_VALUES)


@pytest.mark.parametrize("name", sorted(INSTANCES))
def test_recorded_instance_is_accepted(name):
    assert verdict(INSTANCES[name]) is None


@pytest.mark.parametrize("name", sorted(INSTANCES))
def test_any_lost_doubled_or_swapped_record_is_refused(name):
    inst = INSTANCES[name]
    events = inst["events"]
    for i in range(len(events)):
        assert verdict(inst, events[:i] + events[i + 1:]) is not None, \
            f"record {i} {events[i]} was lost unnoticed"
        assert verdict(inst, events[:i + 1] + events[i:]) is not None, \
            f"record {i} {events[i]} doubled unnoticed"
    assert verdict(inst, events[:-1]) is not None     # never completes
    assert verdict(inst, []) is not None


def test_a_gateway_taking_the_other_branch_is_refused():
    # the records of x=15 (takes 'big') offered as the execution of x=5
    inst = INSTANCES["mx_route:15"]
    assert verdict(inst, variables={**inst["variables"], "x": 5}) is not None
    inst = INSTANCES["mx_excl:25"]
    assert verdict(inst, variables={**inst["variables"], "x": 45}) is not None
    assert verdict(inst, variables={**inst["variables"], "x": 25}) is None


def test_a_job_of_another_type_or_task_is_refused():
    inst = INSTANCES["mx_fj:15"]
    events = list(inst["events"])
    i = next(n for n, e in enumerate(events) if e[:2] == ("JOB", "CREATED"))
    wrong = copy.copy(events)
    wrong[i] = events[i][:3] + ("b1_mx_fj" if events[i][3] == "b0_mx_fj"
                                else "b0_mx_fj",) + events[i][4:]
    assert verdict(inst, wrong) is not None


def test_a_join_that_fires_early_is_refused():
    inst = INSTANCES["mx_par3:15"]
    events = list(inst["events"])
    join = next(n for n, e in enumerate(events)
                if e[:3] == ("PI", "ELEMENT_ACTIVATING", "join"))
    last_flow = max(n for n, e in enumerate(events[:join])
                    if e[1] == "SEQUENCE_FLOW_TAKEN")
    early = events[:last_flow] + events[join:join + 1] + \
        events[last_flow:join] + events[join + 1:]
    assert verdict(inst, early) is not None


def test_xml_is_what_the_definition_says():
    xml = defs.to_bpmn_xml(DEFINITIONS["mx_route"])
    assert '<bpmn:exclusiveGateway id="gw" default="flow_4" />' in xml
    assert "= x &gt; 10" in xml and 'type="work_mx_route"' in xml
    sub = defs.to_bpmn_xml(DEFINITIONS["mx_sub"])
    assert sub.index('<bpmn:subProcess id="sub">') < sub.index('id="inner_task"') \
        < sub.index("</bpmn:subProcess>") < sub.index('id="after"')


def _with_returned(inst, returned, written):
    """The instance's records with ``written`` put right behind its first
    job's COMPLETED, as the merge of a worker's returned document."""
    events = list(inst["events"])
    done = next(n for n, e in enumerate(events) if e[:2] == ("JOB", "COMPLETED"))
    events[done + 1:done + 1] = written
    try:
        reference.accept(DEFINITIONS[inst["pid"]], inst["variables"], events,
                         returned)
    except reference.Mismatch as err:
        return str(err)
    return None


def test_a_returned_document_is_merged_once_behind_its_job():
    inst = INSTANCES["mx_route:15"]
    x = inst["variables"]["x"]
    returned = {"x": x, "fresh": 7}
    created, updated = ("VAR", "CREATED", "fresh", 7), ("VAR", "UPDATED", "x", x)
    assert _with_returned(inst, returned, [updated, created]) is None
    # a name that already holds the returned value may stay unwritten ...
    assert _with_returned(inst, returned, [created]) is None
    # ... a new name or a changed value may not
    assert "never merged" in _with_returned(inst, returned, [updated])
    assert "never merged" in _with_returned(inst, {"x": x + 1}, [])
    # not twice, not another value, not the wrong intent, not without a job
    assert _with_returned(inst, returned, [created, created]) is not None
    assert _with_returned(inst, returned, [("VAR", "CREATED", "fresh", 8)]) is not None
    assert _with_returned(inst, returned, [("VAR", "UPDATED", "fresh", 7)]) is not None
    assert _with_returned(inst, {}, [updated]) is not None
    events = list(inst["events"])
    assert verdict(inst, [updated] + events) is not None


# ---------------------------------------------------------------------------
# catch events: a timer and a message between the start and the task, as the
# engine recorded them (a CPU rehearsal of traffic/default_mix_steady.json,
# the payload's variables left out), each instance's partition and, for a
# message, the message partition's sequence for its correlation key

CATCHES = json.loads(
    (Path(__file__).parent / "data" / "recorded_catches.json").read_text())
CATCH_DEFINITIONS = {d["id"]: d
                     for d in defs.build_definitions(CATCHES["definitions"])}
CATCH = {name: {**inst, "events": [tuple(e) for e in inst["events"]],
                "message_side": [tuple(e) for e in inst.get("message_side", ())]}
         for name, inst in CATCHES["instances"].items()}
TIMERS = sorted(n for n, i in CATCH.items() if i["pid"] == "timerProcess")
MESSAGES = sorted(n for n, i in CATCH.items() if "message_key" in i)


def message_of(inst, key=None):
    return {"key": inst["message_key"] if key is None else key,
            "variables": defs.message_variables(inst["variables"]["correlationKey"])}


def catch_verdict(inst, events=None, message="own"):
    message = message_of(inst) if message == "own" and "message_key" in inst \
        else (None if message == "own" else message)
    try:
        reference.accept(CATCH_DEFINITIONS[inst["pid"]], inst["variables"],
                         inst["events"] if events is None else events, None,
                         message)
    except reference.Mismatch as err:
        return str(err)
    return None


def side_verdict(inst, side=None, message="own"):
    try:
        reference.accept_message_side(
            CATCH_DEFINITIONS[inst["pid"]], inst["variables"], inst["key"],
            inst["message_side"] if side is None else side,
            message_of(inst) if message == "own" else message)
    except reference.Mismatch as err:
        return str(err)
    return None


def test_the_catch_recording_covers_both_kinds_and_both_paths():
    assert len(TIMERS) == len(MESSAGES) == len(defs.X_VALUES)
    assert {reference.path_of(CATCH[n]["message_side"]) for n in MESSAGES} == {
        "buffered", "open"}


@pytest.mark.parametrize("name", sorted(CATCH))
def test_recorded_catch_is_accepted(name):
    inst = CATCH[name]
    assert catch_verdict(inst) is None
    if name in MESSAGES:
        assert side_verdict(inst) is None


@pytest.mark.parametrize("name", sorted(CATCH))
def test_any_lost_or_doubled_catch_record_is_refused(name):
    inst = CATCH[name]
    events = inst["events"]
    for i in range(len(events)):
        assert catch_verdict(inst, events[:i] + events[i + 1:]) is not None, \
            f"record {i} {events[i]} was lost unnoticed"
        assert catch_verdict(inst, events[:i + 1] + events[i:]) is not None, \
            f"record {i} {events[i]} doubled unnoticed"


@pytest.mark.parametrize("name", TIMERS)
def test_an_early_trigger_is_refused(name):
    inst = CATCH[name]
    events = list(inst["events"])
    i = next(n for n, e in enumerate(events) if e[:2] == ("TIMER", "TRIGGERED"))
    due = events[i][5]
    assert "before its due date" in catch_verdict(
        inst, events[:i] + [events[i][:6] + (due - 1,)] + events[i + 1:])
    assert catch_verdict(
        inst, events[:i] + [events[i][:6] + (due,)] + events[i + 1:]) is None


@pytest.mark.parametrize("name", TIMERS)
def test_a_due_date_off_the_activation_is_refused(name):
    inst = CATCH[name]
    events = list(inst["events"])
    i = next(n for n, e in enumerate(events) if e[:2] == ("TIMER", "CREATED"))
    created = events[i]
    stamp, duration = created[6], CATCH_DEFINITIONS[inst["pid"]]["nodes"][1]["timer_ms"]

    def due(at):    # the timer created, and triggered, due at ``at``
        return [e[:5] + (at,) + e[6:] if e[0] == "TIMER" else e for e in events]

    assert catch_verdict(inst, due(stamp + duration)) is None
    assert catch_verdict(inst, due(stamp - reference.STAMP_LAG_MS + duration)) is None
    for off in (stamp + duration + reference.CLOCK_GRAIN_MS + 1,
                stamp - reference.STAMP_LAG_MS + duration - 1, stamp):
        assert "is not the activation" in catch_verdict(inst, due(off))


@pytest.mark.parametrize("name", MESSAGES)
def test_a_doubled_or_foreign_correlation_is_refused(name):
    inst = CATCH[name]
    events = list(inst["events"])
    i = next(n for n, e in enumerate(events) if e[:2] == ("PMS", "CORRELATED"))
    # the same correlation twice, with its variables merged each time
    assert catch_verdict(inst, events[:i + 2] + events[i:]) is not None
    # another message, or another correlation key
    foreign = events[i][:6] + (inst["message_key"] + 1,)
    assert "not the instance's own" in catch_verdict(
        inst, events[:i] + [foreign] + events[i + 1:])
    assert catch_verdict(inst, message=message_of(inst, inst["message_key"] + 1)) \
        is not None
    other_key = events[i][:5] + ("another-key",) + events[i][6:]
    assert "another message or key" in catch_verdict(
        inst, events[:i] + [other_key] + events[i + 1:])
    # on the message's partition: another message correlated, or published
    side = inst["message_side"]
    j = next(n for n, e in enumerate(side) if e[:2] == ("MS", "CORRELATING"))
    assert "not the instance's own" in side_verdict(
        inst, side[:j] + [side[j][:5] + (inst["message_key"] + 1,)] + side[j + 1:])
    assert "no publish was acknowledged" in side_verdict(
        inst, [("MESSAGE", "PUBLISHED", inst["message_key"] + 1)] + side)
    assert side_verdict(inst, side[:j + 1] + side[j:]) is not None
    assert "another instance's" in side_verdict(
        inst, [e[:4] + (e[4] + 1,) + e[5:] if e[0] == "MS" else e for e in side])


@pytest.mark.parametrize("name", sorted(CATCH))
def test_a_catch_that_completes_without_its_trigger_or_correlation_is_refused(name):
    inst = CATCH[name]
    caught = ("TIMER", "TRIGGERED") if name in TIMERS else ("PMS", "CORRELATED")
    # the catch's own record gone, and the variables a correlation merges
    events = [e for e in inst["events"] if e[:2] != caught
              and not (e[0] == "VAR" and e[2] == "published_for")]
    assert "completes without its trigger or correlation" in catch_verdict(
        inst, events)


@pytest.mark.parametrize("name", MESSAGES)
def test_a_subscription_never_opened_is_refused(name):
    inst = CATCH[name]
    # on the instance's partition
    events = [e for e in inst["events"] if e[:2] != ("PMS", "CREATING")]
    assert "out of order" in catch_verdict(inst, events)
    # on the message's partition
    side = [e for e in inst["message_side"] if e[:2] != ("MS", "CREATED")]
    assert side_verdict(inst, side) is not None
    # the answer of the message partition to the instance's may be missing
    assert side_verdict(inst, [e for e in inst["message_side"]
                               if e[:2] != ("MS", "CORRELATED")]) is None
    # a publish never acknowledged: nothing may reach the catch
    assert catch_verdict(inst, message=None) is not None
    assert side_verdict(inst, message=None) is not None


@pytest.mark.parametrize("name", MESSAGES)
def test_a_message_side_record_lost_or_doubled_is_refused(name):
    inst = CATCH[name]
    side = inst["message_side"]
    for i, event in enumerate(side):
        assert side_verdict(inst, side[:i + 1] + side[i:]) is not None, \
            f"message record {i} {event} doubled unnoticed"
        # the partition's answer and the expiry may not have come yet
        if event[:2] not in (("MS", "CORRELATED"), ("MESSAGE", "EXPIRED")):
            assert side_verdict(inst, side[:i] + side[i + 1:]) is not None, \
                f"message record {i} {event} was lost unnoticed"
    # an expiry after the correlation is lawful, before it or twice is not
    expired = ("MESSAGE", "EXPIRED", inst["message_key"])
    side = [e for e in side if e != expired]
    assert side_verdict(inst, side + [expired]) is None
    assert side_verdict(inst, side + [expired, expired]) is not None
    j = next(n for n, e in enumerate(side) if e[:2] == ("MS", "CORRELATING"))
    assert side_verdict(inst, side[:j] + [expired] + side[j:]) is not None


def test_batch_expiries_are_placed_under_their_correlation_key():
    inst = CATCH[MESSAGES[0]]
    ck = inst["variables"]["correlationKey"]
    observed = {("MESSAGE", ck): inst["message_side"],
                ("MESSAGE_BATCH",): [("MESSAGE_BATCH", "EXPIRED",
                                      (inst["message_key"], 12345))]}
    side = reference.message_sides(observed)[ck]
    assert side == inst["message_side"] + [("MESSAGE", "EXPIRED",
                                            inst["message_key"])]
    assert side_verdict(inst, side) is None


def test_mismatches_holds_an_instance_to_both_partitions():
    by_id = dict(CATCH_DEFINITIONS)
    requests, observed, messages = [], {}, {}
    for name in MESSAGES + TIMERS:
        inst = CATCH[name]
        key = inst.get("key", 10_000 + len(requests))
        requests.append((key, inst["pid"], inst["variables"]))
        observed[key] = inst["events"]
        if name in MESSAGES:
            ck = inst["variables"]["correlationKey"]
            observed[("MESSAGE", ck)] = inst["message_side"]
            messages[ck] = message_of(inst)
    assert reference.mismatches(by_id, requests, observed, None, messages) == []
    # the message side of one lost: that instance alone is refused
    lost = CATCH[MESSAGES[0]]
    del observed[("MESSAGE", lost["variables"]["correlationKey"])]
    bad = reference.mismatches(by_id, requests, observed, None, messages)
    assert [key for key, _ in bad] == [lost["key"]]
