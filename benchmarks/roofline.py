"""The least work the kernel's contract asks for, counted from the work
itself — never from the padded bucket (I, T) or from the arrays one
implementation happens to upload — and the table of the chips' peaks.

The automaton advances *tokens*: in one step a token leaves the element it is
at. The contract (``ops/automaton.py``) keeps a token as three int32 (element,
phase, instance) and reports each step of each token as one packed event row of
``2 + FO`` int32, ``FO`` being the largest number of flows leaving one element
of the deployed definitions. A token step therefore has to read the token's
row, write it back and write one event row, whatever implements it. int32
only, no matmul: the kernel is memory-bound and its least time is bytes over
the chip's memory bandwidth.

Token steps are counted from the records of the window, not from the device:
every element instance that activated took one step to pass its element, a
service task a second one when its job completed, and a catch event a second
one when its timer triggered or its message was correlated.
"""

from __future__ import annotations

import json
from pathlib import Path

TOKEN_ROW_INT32 = 3     # elem, phase, inst
EVENT_HEAD_INT32 = 2    # flags|elem, inst; then one int32 a flow slot


def token_steps(events: list) -> int:
    """Token steps in ``events`` (the reference's tuples, any instances)."""
    steps = 0
    for event in events:
        if event[0] == "PI" and event[1] == "ELEMENT_ACTIVATED" and event[4] != -1:
            steps += 1      # an element inside the process, not the process
        elif event[0] == "JOB" and event[1] == "COMPLETED":
            steps += 1      # the task's second pass
        elif event[:2] in (("TIMER", "TRIGGERED"), ("PMS", "CORRELATED")):
            steps += 1      # the catch's second pass
    return steps


def least_bytes(steps: int, max_fanout: int) -> int:
    row = 2 * TOKEN_ROW_INT32 + EVENT_HEAD_INT32 + max_fanout
    return steps * row * 4


def peaks_of(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_seconds(steps: int, max_fanout: int, device_kind: str) -> float:
    return least_bytes(steps, max_fanout) / peaks_of(device_kind)["hbm_bytes_per_s"]
