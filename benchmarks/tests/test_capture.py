"""The capture exporter: a process instance's, a job's or a variable's
record never reaches the catch branch (it costs what it cost before the
branch existed), and a catch record becomes the reference's tuple, kept in
the instance's sequence or in its message's."""

from types import SimpleNamespace

import pytest

import run  # noqa: F401 — puts the checkout, and so the program, on the path
import served


def record(value_type, intent, key, value, timestamp=1_000):
    from zeebe_tpu.protocol import Record
    from zeebe_tpu.protocol.enums import RecordType

    return Record(record_type=RecordType.EVENT, value_type=value_type,
                  intent=intent, value=value, key=key, timestamp=timestamp,
                  partition_id=1)


@pytest.fixture
def exporter():
    observed = served.Observed()
    capture = served.capture_exporter(observed)
    capture.controller = SimpleNamespace(
        update_last_exported_position=lambda position: None)
    positions = iter(range(1, 1_000))

    def export(rec):
        capture.export(SimpleNamespace(record=rec, position=next(positions)))

    return observed, export


def test_instance_job_and_variable_records_never_reach_the_catch_branch(
        exporter, monkeypatch):
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import JobIntent, ProcessInstanceIntent, VariableIntent

    def unreachable(*_args):
        raise AssertionError("a PI, JOB or VAR record reached catch_event")

    monkeypatch.setattr(served, "catch_event", unreachable)
    observed, export = exporter
    export(record(ValueType.PROCESS_INSTANCE, ProcessInstanceIntent.ELEMENT_ACTIVATED,
                  7, {"elementId": "task", "flowScopeKey": 5,
                      "processInstanceKey": 5}))
    export(record(ValueType.JOB, JobIntent.CREATED, 8,
                  {"elementId": "task", "type": "work", "elementInstanceKey": 7,
                   "processInstanceKey": 5}))
    export(record(ValueType.VARIABLE, VariableIntent.CREATED, 9,
                  {"name": "x", "value": 1, "processInstanceKey": 5}))
    assert [e[0] for e in observed.events[5]] == ["PI", "JOB", "VAR"]


def test_catch_records_go_to_the_instance_or_to_its_message(exporter):
    from zeebe_tpu.protocol import ValueType
    from zeebe_tpu.protocol.intent import (MessageBatchIntent, MessageIntent,
                                           MessageSubscriptionIntent,
                                           ProcessMessageSubscriptionIntent,
                                           TimerIntent)

    observed, export = exporter
    export(record(ValueType.TIMER, TimerIntent.CREATED, 11,
                  {"targetElementId": "wait", "elementInstanceKey": 10,
                   "dueDate": 11_000, "processInstanceKey": 5}))
    export(record(ValueType.PROCESS_MESSAGE_SUBSCRIPTION,
                  ProcessMessageSubscriptionIntent.CORRELATED, 10,
                  {"targetElementId": "catch", "elementInstanceKey": 10,
                   "messageName": "msg", "correlationKey": "k",
                   "messageKey": 21, "processInstanceKey": 5}))
    export(record(ValueType.MESSAGE, MessageIntent.PUBLISHED, 21,
                  {"name": "msg", "correlationKey": "k"}))
    export(record(ValueType.MESSAGE_SUBSCRIPTION, MessageSubscriptionIntent.CREATED,
                  22, {"targetElementId": "catch", "messageName": "msg",
                       "correlationKey": "k", "processInstanceKey": 5}))
    export(record(ValueType.MESSAGE_BATCH, MessageBatchIntent.EXPIRED, 23,
                  {"messageKeys": [21]}))
    assert observed.events[5] == [
        ("TIMER", "CREATED", "wait", 11, 10, 11_000, 1_000),
        ("PMS", "CORRELATED", "catch", 10, "msg", "k", 21)]
    assert observed.events[("MESSAGE", "k")] == [
        ("MESSAGE", "PUBLISHED", 21), ("MS", "CREATED", "catch", "msg", 5, -1)]
    assert observed.events[("MESSAGE_BATCH",)] == [
        ("MESSAGE_BATCH", "EXPIRED", (21,))]
    # a publish's acknowledgement rests on its record's position
    assert observed.position_of[21] == 3
