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
