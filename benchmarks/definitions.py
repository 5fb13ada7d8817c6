"""Process definitions, payloads and request plans, made from a traffic file's
keys and the seed. Plain data only: nothing of the program is imported here.

A definition is a dict::

    {"id": "mx_fj",
     "nodes": [{"id": "s", "type": "startEvent", "parent": None}, ...],
     "flows": [{"id": "flow_1", "source": "s", "target": "fork",
                "parent": None, "condition": None}, ...],
     "defaults": {"gw": "flow_3"}}

``type`` is the BPMN tag (``startEvent``, ``endEvent``, ``serviceTask`` with a
``job_type``, ``exclusiveGateway``, ``parallelGateway``, ``subProcess``,
``intermediateCatchEvent`` with a ``timer_ms`` or a ``message`` and its
``correlation_variable``);
``parent`` names the enclosing sub-process (None: the process itself); a
``condition`` is ``["x", ">", 10]``, the only comparison the mixes use. The
same dict is written out as BPMN XML for the deployment and walked by the
plain reference (``reference.py``).
"""

from __future__ import annotations

import json
import random
from xml.sax.saxutils import escape

BPMN_NS = "http://www.omg.org/spec/BPMN/20100524/MODEL"
ZEEBE_NS = "http://camunda.org/schema/zeebe/1.0"

#: values of ``x`` on both sides of every threshold ``x > 10*i``, i = 0..4
X_VALUES = (-5, 5, 15, 25, 35, 45)


class _Builder:
    def __init__(self, pid: str) -> None:
        self.d = {"id": pid, "nodes": [], "flows": [], "defaults": {}}

    def node(self, nid: str, tag: str, parent=None, **attrs) -> str:
        node = {"id": nid, "type": tag, "parent": parent}
        node.update((k, v) for k, v in attrs.items() if v is not None)
        self.d["nodes"].append(node)
        return nid

    def flow(self, source: str, target: str, parent=None, condition=None,
             default: bool = False) -> str:
        fid = f"flow_{len(self.d['flows']) + 1}"
        self.d["flows"].append({"id": fid, "source": source, "target": target,
                                "parent": parent, "condition": condition})
        if default:
            self.d["defaults"][source] = fid
        return fid


def task_chain(pid: str, tasks: int) -> dict:
    """start -> ``tasks`` service tasks of one job type -> end."""
    b = _Builder(pid)
    last = b.node("s", "startEvent")
    for i in range(tasks):
        here = b.node(f"t{i}", "serviceTask", job_type=f"work_{pid}")
        b.flow(last, here)
        last = here
    b.flow(last, b.node("e", "endEvent"))
    return b.d


def exclusive_chain(pid: str, gateways: int) -> dict:
    """start -> ``gateways`` exclusive splits (``x > 10*i`` or the default
    flow), each merged again -> end: sequence flows and FEEL only, no job."""
    b = _Builder(pid)
    last = b.node("s", "startEvent")
    for i in range(gateways):
        split = b.node(f"gw{i}", "exclusiveGateway")
        merge = b.node(f"m{i}", "exclusiveGateway")
        b.flow(last, split)
        b.flow(split, merge, condition=["x", ">", 10 * i])
        b.flow(split, merge, default=True)
        last = merge
    b.flow(last, b.node("e", "endEvent"))
    return b.d


def fork_join(pid: str, branches: int, job_type_per_branch: bool = False) -> dict:
    """start -> parallel fork -> one service task a branch -> join -> end."""
    b = _Builder(pid)
    b.node("s", "startEvent")
    b.node("fork", "parallelGateway")
    b.node("join", "parallelGateway")
    b.node("e", "endEvent")
    b.flow("s", "fork")
    for i in range(branches):
        job_type = f"b{i}_{pid}" if job_type_per_branch else f"work_{pid}"
        b.node(f"p{i}", "serviceTask", job_type=job_type)
        b.flow("fork", f"p{i}")
        b.flow(f"p{i}", "join")
    b.flow("join", "e")
    return b.d


def route(pid: str) -> dict:
    """start -> exclusive split: ``x > 10`` -> task -> end, else task -> end."""
    b = _Builder(pid)
    b.node("s", "startEvent")
    b.node("gw", "exclusiveGateway")
    b.flow("s", "gw")
    for name, end, condition in (("big", "e1", ["x", ">", 10]),
                                 ("small", "e2", None)):
        b.node(name, "serviceTask", job_type=f"work_{pid}")
        b.node(end, "endEvent")
        b.flow("gw", name, condition=condition, default=condition is None)
        b.flow(name, end)
    return b.d


def embedded_subprocess(pid: str) -> dict:
    """start -> sub-process(start -> task -> end) -> task -> end."""
    b = _Builder(pid)
    b.node("s", "startEvent")
    b.node("sub", "subProcess")
    b.node("inner_s", "startEvent", parent="sub")
    b.node("inner_task", "serviceTask", parent="sub", job_type=f"inner_{pid}")
    b.node("inner_e", "endEvent", parent="sub")
    b.node("after", "serviceTask", job_type=f"after_{pid}")
    b.node("e", "endEvent")
    b.flow("s", "sub")
    b.flow("inner_s", "inner_task", parent="sub")
    b.flow("inner_task", "inner_e", parent="sub")
    b.flow("sub", "after")
    b.flow("after", "e")
    return b.d


def _catch_then_task(pid: str, catch: str, job_type, **attrs) -> dict:
    """start -> intermediate catch event ``catch`` -> service task (of
    ``job_type``, by default one of its own) -> end."""
    b = _Builder(pid)
    b.node("s", "startEvent")
    b.node(catch, "intermediateCatchEvent", **attrs)
    b.node("task", "serviceTask", job_type=job_type or f"work_{pid}")
    b.node("e", "endEvent")
    for source, target in (("s", catch), (catch, "task"), ("task", "e")):
        b.flow(source, target)
    return b.d


def message_catch(pid: str, message: str, correlation_variable: str,
                  job_type: str | None = None) -> dict:
    """A catch of ``message``, correlated by the value of the instance's
    ``correlation_variable``, then a task: upstream's ``msg_one_task.bpmn``
    as restated."""
    return _catch_then_task(pid, "catch", job_type, message=message,
                            correlation_variable=correlation_variable)


def timer_catch(pid: str, duration_ms: int, job_type: str | None = None) -> dict:
    """A timer catch of ``duration_ms``, then a task: upstream's
    ``timerProcess.bpmn`` as restated."""
    return _catch_then_task(pid, "wait", job_type, timer_ms=int(duration_ms))


KINDS = {"task_chain": task_chain, "exclusive_chain": exclusive_chain,
         "fork_join": fork_join, "route": route,
         "embedded_subprocess": embedded_subprocess,
         "message_catch": message_catch, "timer_catch": timer_catch}


def build_definitions(specs: list) -> list:
    """``specs``: the traffic file's ``definitions`` — each ``{"kind": ...,
    "id": ..., <the kind's own keys>}``, and optionally ``"weight"``: how
    many times the definition's requests come in a round of the plan
    (default 1; kept on the definition only where the entry names it)."""
    out = []
    for spec in specs:
        spec = dict(spec)
        kind = spec.pop("kind")
        if kind not in KINDS:
            raise ValueError(f"unknown definition kind {kind!r}; known: "
                             f"{sorted(KINDS)}")
        weight = spec.pop("weight", None)
        d = KINDS[kind](spec.pop("id"), **spec)
        if weight is not None:
            if int(weight) != weight or weight < 1:
                raise ValueError(f"definition {d['id']!r}: weight {weight!r} "
                                 "is not a whole number of 1 or more")
            d["weight"] = int(weight)
        out.append(d)
    return out


def job_types(definitions: list) -> list:
    return sorted({n["job_type"] for d in definitions for n in d["nodes"]
                   if "job_type" in n})


def jobs_per_instance(d: dict):
    """How many jobs every instance of ``d`` runs: its service tasks. None
    where an exclusive split leaves a choice of flows in a definition with
    tasks, so that the count may depend on ``x``."""
    leaving: dict = {}
    for f in d["flows"]:
        leaving[f["source"]] = leaving.get(f["source"], 0) + 1
    tasks = sum(1 for n in d["nodes"] if n["type"] == "serviceTask")
    splits = any(n["type"] == "exclusiveGateway" and leaving.get(n["id"], 0) > 1
                 for n in d["nodes"])
    return None if tasks and splits else tasks


def catch_of(d: dict):
    """The definition's intermediate catch event (a node dict), or None."""
    return next((n for n in d["nodes"] if n["type"] == "intermediateCatchEvent"),
                None)


def longest_timer_ms(definitions: list) -> int:
    """The longest timer any instance of the mix waits on (0: none)."""
    return max((n.get("timer_ms", 0) for d in definitions for n in d["nodes"]),
               default=0)


def max_fanout(definitions: list) -> int:
    """Largest number of flows leaving one element: the ``FO`` of the kernel
    contract's event row (``2 + FO`` int32 a token step)."""
    most = 1
    for d in definitions:
        counts: dict = {}
        for f in d["flows"]:
            counts[f["source"]] = counts.get(f["source"], 0) + 1
        most = max(most, *counts.values())
    return most


# ---------------------------------------------------------------------------
# BPMN XML


def _xml_scope(d: dict, parent, indent: str) -> list:
    lines = []
    for n in d["nodes"]:
        if n["parent"] != parent:
            continue
        tag, nid = n["type"], n["id"]
        attrs = f' id="{nid}"'
        if nid in d["defaults"]:
            attrs += f' default="{d["defaults"][nid]}"'
        if tag == "subProcess":
            lines.append(f"{indent}<bpmn:subProcess{attrs}>")
            lines += _xml_scope(d, nid, indent + "  ")
            lines.append(f"{indent}</bpmn:subProcess>")
        elif tag == "serviceTask":
            lines += [
                f"{indent}<bpmn:serviceTask{attrs}>",
                f"{indent}  <bpmn:extensionElements>",
                f'{indent}    <zeebe:taskDefinition type="{n["job_type"]}" '
                f'retries="3" />',
                f"{indent}  </bpmn:extensionElements>",
                f"{indent}</bpmn:serviceTask>"]
        elif tag == "intermediateCatchEvent" and "message" in n:
            lines += [
                f"{indent}<bpmn:intermediateCatchEvent{attrs}>",
                f"{indent}  <bpmn:extensionElements>",
                f'{indent}    <zeebe:subscription correlationKey='
                f'"= {n["correlation_variable"]}" />',
                f"{indent}  </bpmn:extensionElements>",
                f'{indent}  <bpmn:messageEventDefinition '
                f'messageRef="{_message_ref(n["message"])}" />',
                f"{indent}</bpmn:intermediateCatchEvent>"]
        elif tag == "intermediateCatchEvent":
            lines += [
                f"{indent}<bpmn:intermediateCatchEvent{attrs}>",
                f"{indent}  <bpmn:timerEventDefinition>",
                f"{indent}    <bpmn:timeDuration>"
                f"{iso_duration(n['timer_ms'])}</bpmn:timeDuration>",
                f"{indent}  </bpmn:timerEventDefinition>",
                f"{indent}</bpmn:intermediateCatchEvent>"]
        else:
            lines.append(f"{indent}<bpmn:{tag}{attrs} />")
    for f in d["flows"]:
        if f["parent"] != parent:
            continue
        head = (f'{indent}<bpmn:sequenceFlow id="{f["id"]}" '
                f'sourceRef="{f["source"]}" targetRef="{f["target"]}"')
        if f["condition"] is None:
            lines.append(head + " />")
        else:
            name, op, value = f["condition"]
            lines += [head + ">",
                      f"{indent}  <bpmn:conditionExpression>"
                      f"{escape(f'= {name} {op} {value}')}"
                      f"</bpmn:conditionExpression>",
                      f"{indent}</bpmn:sequenceFlow>"]
    return lines


def iso_duration(millis: int) -> str:
    """An ISO 8601 duration of whole or fractional seconds: 10000 -> PT10S."""
    seconds, rest = divmod(int(millis), 1000)
    return f"PT{seconds}S" if not rest else f"PT{seconds}.{rest:03d}S"


def _message_ref(name: str) -> str:
    return f"message_{name}"


def to_bpmn_xml(d: dict) -> str:
    lines = [
        "<?xml version='1.0' encoding='utf-8'?>",
        f'<bpmn:definitions xmlns:bpmn="{BPMN_NS}" xmlns:zeebe="{ZEEBE_NS}" '
        f'targetNamespace="http://zeebe-tpu/bpmn">']
    # a message is declared beside the process, its catch refers to it
    lines += [f'  <bpmn:message id="{_message_ref(name)}" name="{escape(name)}" />'
              for name in sorted({n["message"] for n in d["nodes"]
                                  if "message" in n})]
    lines.append(
        f'  <bpmn:process id="{d["id"]}" name="{d["id"]}" isExecutable="true">')
    lines += _xml_scope(d, None, "    ")
    lines += ["  </bpmn:process>", "</bpmn:definitions>"]
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# payload and plans


def make_payload(spec, seed: int) -> dict:
    """The variables every create of the mix carries besides its own ``x``.
    ``spec`` None: none. Else ``{"strings": n, "string_chars": c, "numbers":
    m, "nested": k}``: a JSON object of ``n`` strings of ``c`` seeded letters,
    ``m`` numbers, and ``k`` nested objects of the same again — the same
    sizes for every seed, other letters."""
    if not spec:
        return {}
    rng = random.Random(seed ^ 0x5EED)
    letters = "abcdefghijklmnopqrstuvwxyz0123456789 "

    def level(prefix: str) -> dict:
        out = {}
        for i in range(spec.get("strings", 0)):
            out[f"{prefix}s{i}"] = "".join(
                rng.choice(letters) for _ in range(spec["string_chars"]))
        for i in range(spec.get("numbers", 0)):
            out[f"{prefix}n{i}"] = rng.randrange(1_000_000)
        return out

    payload = level("")
    for k in range(spec.get("nested", 0)):
        payload[f"o{k}"] = level(f"o{k}_")
    return payload


def payload_bytes(payload: dict) -> int:
    return len(json.dumps(payload, separators=(",", ":")))


def correlation_variable(d: dict):
    """The variable whose value correlates a message to an instance of
    ``d``, or None where its instances wait for no message."""
    catch = catch_of(d)
    return None if catch is None else catch.get("correlation_variable")


def message_variables(correlation_key: str) -> dict:
    """The document a mix's publisher sends with the message for one
    correlation key: the catch merges it into the instance, so an instance
    that holds another key's document was reached by another's message."""
    return {"published_for": correlation_key}


def first_touch_plan(definitions: list, partitions: int, payload: dict) -> list:
    """Definition order, twice round the partitions: every partition's
    registry then grows its table set in the same order run to run, so the
    device programs and their compile-cache keys repeat. A request that
    waits for a message carries a key of its own, the same in every run."""
    plan = []
    for d in definitions:
        var = correlation_variable(d)
        for i in range(2 * partitions):
            variables = {"x": X_VALUES[i % len(X_VALUES)]}
            if var is not None:
                variables[var] = f"first-touch-{len(plan)}"
            plan.append((d["id"], {**variables, **payload}))
    return plan


def request_plan(definitions: list, n: int, payload: dict, seed: int) -> list:
    """``n`` requests ``(process id, variables)``. Every seed draws from the
    same set — each definition with each ``x`` equally often, or ``weight``
    times as often — in another order: whole rounds of (definition x value)
    pairs, each round shuffled. A request of a definition that waits for a
    message carries a correlation key of its own, ``<tag>-<i>`` with ``i``
    its place in the plan and the tag drawn from the seed by a generator of
    its own, so that the shuffle draws what it drew without one."""
    rng = random.Random(seed)
    combos = [(d["id"], x) for d in definitions
              for _ in range(d.get("weight", 1)) for x in X_VALUES]
    plan: list = []
    while len(plan) < n:
        block = list(combos)
        rng.shuffle(block)
        plan += block
    keyed = {d["id"]: correlation_variable(d) for d in definitions}
    tag = f"{random.Random(seed ^ 0xC0441E).getrandbits(64):016x}"
    out = []
    for i, (pid, x) in enumerate(plan[:n]):
        variables = {"x": x}
        if keyed[pid] is not None:
            variables[keyed[pid]] = f"{tag}-{i}"
        out.append((pid, {**variables, **payload}))
    return out
