"""Process definitions, payloads and request plans, made from a traffic file's
keys and the seed. Plain data only: nothing of the program is imported here.

A definition is a dict::

    {"id": "mx_fj",
     "nodes": [{"id": "s", "type": "startEvent", "parent": None}, ...],
     "flows": [{"id": "flow_1", "source": "s", "target": "fork",
                "parent": None, "condition": None}, ...],
     "defaults": {"gw": "flow_3"}}

``type`` is the BPMN tag (``startEvent``, ``endEvent``, ``serviceTask`` with a
``job_type``, ``exclusiveGateway``, ``parallelGateway``, ``subProcess``);
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

    def node(self, nid: str, tag: str, parent=None, job_type=None) -> str:
        node = {"id": nid, "type": tag, "parent": parent}
        if job_type is not None:
            node["job_type"] = job_type
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


KINDS = {"task_chain": task_chain, "exclusive_chain": exclusive_chain,
         "fork_join": fork_join, "route": route,
         "embedded_subprocess": embedded_subprocess}


def build_definitions(specs: list) -> list:
    """``specs``: the traffic file's ``definitions`` — each ``{"kind": ...,
    "id": ..., <the kind's own keys>}``."""
    out = []
    for spec in specs:
        spec = dict(spec)
        kind = spec.pop("kind")
        if kind not in KINDS:
            raise ValueError(f"unknown definition kind {kind!r}; known: "
                             f"{sorted(KINDS)}")
        out.append(KINDS[kind](spec.pop("id"), **spec))
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


def to_bpmn_xml(d: dict) -> str:
    lines = [
        "<?xml version='1.0' encoding='utf-8'?>",
        f'<bpmn:definitions xmlns:bpmn="{BPMN_NS}" xmlns:zeebe="{ZEEBE_NS}" '
        f'targetNamespace="http://zeebe-tpu/bpmn">',
        f'  <bpmn:process id="{d["id"]}" name="{d["id"]}" isExecutable="true">']
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


def first_touch_plan(definitions: list, partitions: int, payload: dict) -> list:
    """Definition order, twice round the partitions: every partition's
    registry then grows its table set in the same order run to run, so the
    device programs and their compile-cache keys repeat."""
    return [(d["id"], {"x": X_VALUES[i % len(X_VALUES)], **payload})
            for d in definitions for i in range(2 * partitions)]


def request_plan(definitions: list, n: int, payload: dict, seed: int) -> list:
    """``n`` requests ``(process id, variables)``. Every seed draws from the
    same set — each definition with each ``x`` equally often — in another
    order: whole rounds of (definition x value) pairs, each round shuffled."""
    rng = random.Random(seed)
    combos = [(d["id"], x) for d in definitions for x in X_VALUES]
    plan: list = []
    while len(plan) < n:
        block = list(combos)
        rng.shuffle(block)
        plan += block
    return [(pid, {"x": x, **payload}) for pid, x in plan[:n]]
