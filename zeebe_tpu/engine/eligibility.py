"""Kernel-path eligibility: ONE reason catalog, the static classifier, and
the consolidated path accounting (ISSUE 13).

ROADMAP item 3 ("make host-side execution the exception") is graded on a
number nothing measured before this module existed: *which records ran on
the kernel path vs host, and why*. Three seams used to answer fragments of
that question with private state — ``check_element_eligibility`` (a bool),
``note_sequential_head`` (a bench-only Counter), and the in-dispatch
``fallback_reasons`` increments — each minting its own reason strings. This
module is their single home:

- **The reason catalog**: every reason a record can take the host path,
  typed and enumerated. Static reasons are predictable from the definition
  alone; runtime-only reasons (geometry bounds, non-quiescence, pool
  overflow, mesh errors) are not — the split is what makes the
  static-vs-observed parity gate sound. ``canonical_reason`` maps any noted
  string (including the dynamic ``head-*:<VT>.<INTENT>`` families) onto the
  catalog; an unregistered string lands on the ``unregistered`` label and
  fails a test instead of silently minting a new metric child.
- **``element_host_reason``**: the reason-returning form of the kernel
  backend's element eligibility check (the backend's boolean is derived
  from it — one logic, two views).
- **``classify_definition``**: the static eligibility report. It runs the
  REAL ``KernelRegistry`` lookup (inlining, solo compile, typed decline
  reasons) so the prediction can never drift from what admission will do,
  then explains every host-forced row through the catalog.
- **``PathAccounting``**: the runtime counter home — per-partition
  ``zeebe_kernel_records_total{path,reason}``, a per-definition
  ``zeebe_kernel_coverage_ratio`` gauge, and the per-definition reason
  split the parity gate compares against the classifier's prediction.

Honest caveats (also in docs/eligibility.md): classification is solo —
joint deployments can downgrade further via SlotMap kind clashes across
definitions; offline classification cannot resolve call activities without
the deployed process state; in-batch follow-up commands ride their head's
path and are not separately counted; coverage is per partition, not global.
"""

from __future__ import annotations

from collections import Counter
from typing import Any

from zeebe_tpu.models.bpmn.executable import ExecutableElement, ExecutableProcess
from zeebe_tpu.ops.tables import _KERNEL_OP, _MI_BODY_TYPES, K_TASK
from zeebe_tpu.protocol.enums import BpmnElementType, BpmnEventType

# ---------------------------------------------------------------------------
# the reason catalog — ONE home for every path-routing reason string

#: statically predictable, per-element: the element itself forces the host
#: path (it lowers to K_HOST, or disqualifies the whole definition)
STATIC_ELEMENT_REASONS = frozenset({
    "multi-instance",
    "io-mapping-nontask",
    "unsafe-expression",
    "output-writes-condition-var",
    "user-task",
    "called-decision",
    "script-task-shape",
    "timer-cycle-date",
    "escalation-boundary",
    "boundary-unsupported",
    "boundary-on-nontask",
    "subprocess-no-none-start",
    "subprocess-event-subprocess",
    "call-activity-unresolved",
    "event-gateway-target",
    "link-unresolved",
    "catch-unsupported",
    "unsupported-element",
    "event-type-unsupported",
    "job-type-dynamic",
    # report-only: the element is individually eligible but sits inside an
    # event sub-process whose tokens only ever enter through a host-routed
    # start event — the ROADMAP item 3 "event-sub-process children" shape
    "event-subprocess-body",
    # the solo/shared lowering downgraded the element to a host escape
    # (condition outside the device subset, SlotMap kind clash)
    "condition-not-compilable",
})

#: statically predictable, definition-level: the definition cannot ride the
#: kernel at all (KernelRegistry lookup declines with one of these;
#: table-set-full is deployment-SET-dependent — the registry's
#: max_definitions capacity, predictable only when classifying the whole
#: set against one shared registry)
DEFINITION_REASONS = frozenset({
    "no-none-start",
    "esp-start-unsupported",
    "condition-not-compilable",
    "table-set-full",
})

#: NOT statically predictable — the dispatch itself declined; the parity
#: gate must never hold these against the classifier
RUNTIME_REASONS = frozenset({
    "geometry-bounds",
    "no-quiesce",
    "token-overflow",
    "mesh-dispatch-error",
    "mesh-no-quiesce",
    "mesh-token-overflow",
    "group-error",
    # device-fault defense (ISSUE 15): containment + quarantine routing
    "device-dispatch-error",
    "device-wedged",
    "device-quarantined",
})

#: dynamic families noted as ``<family>:<VALUE_TYPE>.<INTENT>`` —
#: head-sequential is ordinary non-candidate traffic at the group boundary;
#: head-not-admittable is a candidate command that failed admission (a
#: regression signal when the definition is predicted eligible)
HEAD_FAMILIES = frozenset({"head-sequential", "head-not-admittable"})

#: the full catalog of canonical reason labels (metric label universe)
ALL_REASONS = (STATIC_ELEMENT_REASONS | DEFINITION_REASONS | RUNTIME_REASONS
               | HEAD_FAMILIES)


def canonical_reason(reason: str) -> str | None:
    """Map a noted reason string onto its catalog label: exact codes pass
    through, ``head-*:<kind>`` collapses to its family (bounded metric
    cardinality), anything else is unregistered (None)."""
    family = reason.split(":", 1)[0]
    if family in HEAD_FAMILIES:
        return family
    if reason in ALL_REASONS:
        return reason
    return None


# ---------------------------------------------------------------------------
# element-level classification (the kernel backend's eligibility logic,
# reason-returning; KernelBackend's boolean check derives from this)


def element_host_reason(exe: ExecutableProcess,
                        el: ExecutableElement) -> str | None:
    """None when the sequential engine's behavior for this element is exactly
    the kernel's opcode behavior (engine/…/processing/bpmn element processors
    vs ops/automaton masks); otherwise the catalog reason it must host-route.
    """
    from zeebe_tpu.engine.kernel_backend import (
        _condition_var_names,
        _safe_mapping_expr,
    )

    if el.multi_instance is not None:
        # only synthetic K_MI bodies (_inline_mi_bodies sets child_start on a
        # task-type element) ride the device; real loop elements host-escape
        if el.child_start_idx >= 0 and el.element_type in _MI_BODY_TYPES:
            return None
        return "multi-instance"
    if el.inputs or el.outputs:
        # io-mappings ride the kernel on job-worker tasks only, and only
        # when they cannot fail mid-burst (safe expressions) and their
        # outputs cannot invalidate prefetched device condition slots
        if _KERNEL_OP.get(el.element_type) != K_TASK:
            return "io-mapping-nontask"
        if not all(_safe_mapping_expr(e) for e, _t in el.inputs):
            return "unsafe-expression"
        if el.outputs:
            if not all(_safe_mapping_expr(e) for e, _t in el.outputs):
                return "unsafe-expression"
            if {t for _e, t in el.outputs} & _condition_var_names(exe):
                return "output-writes-condition-var"
    if el.native_user_task:
        return "user-task"
    if el.called_decision_id:
        return "called-decision"
    if el.script_expression is not None:
        # expression-flavor script tasks ride as K_PASS with the evaluation
        # and result write emitted between ACTIVATED and COMPLETING: the
        # expression must be a never-raises safe expression, and the result
        # variable must not invalidate prefetched device condition slots
        # (same discipline as io-mapping outputs)
        if (el.element_type != BpmnElementType.SCRIPT_TASK
                or el.job_type is not None
                or el.inputs or el.outputs or el.boundary_idxs):
            return "script-task-shape"
        if not _safe_mapping_expr(el.script_expression):
            return "unsafe-expression"
        if (el.script_result_variable is not None
                and el.script_result_variable in _condition_var_names(exe)):
            return "output-writes-condition-var"
        return None
    if el.element_type == BpmnElementType.BOUNDARY_EVENT:
        # triggers route sequentially (route_trigger); the kernel only needs
        # the attached wait state to be reconstructable, so the boundary's
        # subscription kind must be one _reconstruct knows how to collect
        if el.event_type == BpmnEventType.TIMER:
            if el.timer_duration is not None and el.timer_date is None:
                return None
            return "timer-cycle-date"
        if el.event_type == BpmnEventType.MESSAGE:
            return None if el.message_name is not None else "boundary-unsupported"
        if el.event_type == BpmnEventType.SIGNAL:
            # signal subscriptions count in the reconstruction integrity
            # check like timers/messages (boundary_waits third slot)
            return None if el.signal_name is not None else "boundary-unsupported"
        # error boundaries carry no wait state at all (the job THROW_ERROR
        # command routes through _find_catcher on the host). Escalation
        # boundaries only fire from a CHILD SCOPE — and scope hosts fail
        # the K_TASK host check anyway
        if el.event_type == BpmnEventType.ERROR:
            return None
        if el.event_type == BpmnEventType.ESCALATION:
            return "escalation-boundary"
        return "boundary-unsupported"
    if el.boundary_idxs:
        # boundary wait-state reconstruction is implemented for parked
        # job-worker tasks only, and every attached boundary must itself be
        # collectable (an escaped signal boundary would open a subscription
        # the reconstruction doesn't count — so the host task escapes too)
        if _KERNEL_OP.get(el.element_type) != K_TASK:
            return "boundary-on-nontask"
        if not all(element_host_reason(exe, exe.elements[b]) is None
                   for b in el.boundary_idxs):
            return "boundary-unsupported"
    if el.element_type == BpmnElementType.SUB_PROCESS:
        # embedded sub-process with a none start rides the kernel (K_SCOPE);
        # attached event sub-processes would need host-side trigger state
        # the scope reconstruction does not collect yet
        if el.child_start_idx < 0:
            return "subprocess-no-none-start"
        if exe.event_sub_processes_of(el.idx):
            return "subprocess-event-subprocess"
        return None
    if el.element_type in (BpmnElementType.CALL_ACTIVITY,
                           BpmnElementType.PROCESS):
        # only synthetic inlined rows carry a child_start here (the call
        # activity scope and its child-root placeholder); a plain call
        # activity host-escapes (_inline_call_activities decides which)
        return None if el.child_start_idx >= 0 else "call-activity-unresolved"
    if el.element_type == BpmnElementType.EVENT_BASED_GATEWAY:
        # parks on device like a catch; every succeeding catch must hold a
        # wait state the reconstruction counts — fixed-duration timers,
        # message subscriptions, and signal subscriptions all count in
        # _collect_wait_states; cycle/date timers stay host-side
        for fidx in el.outgoing:
            target = exe.elements[exe.flows[fidx].target_idx]
            if target.timer_duration is not None:
                if target.timer_cycle or target.timer_date is not None:
                    return "timer-cycle-date"
            elif target.message_name is None and target.signal_name is None:
                return "event-gateway-target"
        return None if el.outgoing else "event-gateway-target"
    if (el.element_type == BpmnElementType.INTERMEDIATE_THROW_EVENT
            and el.event_type == BpmnEventType.LINK):
        # link throw rides the kernel as a K_PASS with a synthetic edge to
        # the resolved same-scope catch (tables.compile_tables link branch)
        return None if el.link_target_idx >= 0 else "link-unresolved"
    if el.element_type in (BpmnElementType.INTERMEDIATE_CATCH_EVENT,
                           BpmnElementType.RECEIVE_TASK):
        if el.event_type == BpmnEventType.LINK:
            # catch link: plain pass-through, no wait state to reconstruct
            return None
        # timer (fixed duration), message, and signal catches park on device
        # (K_CATCH); the host resumes them via TRIGGER / CORRELATE /
        # COMPLETE_ELEMENT commands
        if el.timer_duration is not None:
            if el.timer_cycle or el.timer_date is not None:
                return "timer-cycle-date"
            if el.message_name is not None or el.signal_name is not None:
                return "catch-unsupported"
            return None
        if el.timer_cycle is not None or el.timer_date is not None:
            # cycle/date-only timer catch: no reconstructable wait state
            return "timer-cycle-date"
        if el.message_name is not None or el.signal_name is not None:
            return None
        return "catch-unsupported"
    op = _KERNEL_OP.get(el.element_type)
    if op is None:
        return "unsupported-element"
    if el.event_type not in (BpmnEventType.NONE, BpmnEventType.UNSPECIFIED):
        return "event-type-unsupported"
    if (
        el.timer_duration is not None
        or el.timer_cycle is not None
        or el.timer_date is not None
        or el.message_name is not None
        or el.signal_name is not None
    ):
        return "event-type-unsupported"
    if op == K_TASK:
        # job-worker semantics only, with deploy-time-constant type/retries
        if el.job_type is None or not el.job_type.is_static:
            return "job-type-dynamic"
        if el.job_retries is not None and not el.job_retries.is_static:
            return "job-type-dynamic"
    return None


def esp_start_host_reason(start: ExecutableElement) -> str | None:
    """Definition-level gate on a ROOT event sub-process start event: only
    subscription shapes the kernel's root-wait-state reconstruction can
    count are admissible (the BODY still host-escapes either way). Shared
    by ``KernelRegistry._build_info`` and the classifier so the two can
    never disagree."""
    if (
        start.event_type in (BpmnEventType.ERROR, BpmnEventType.ESCALATION)
        or (start.event_type == BpmnEventType.TIMER
            and start.timer_duration is not None
            and start.timer_cycle is None
            and start.timer_date is None)
        or (start.event_type == BpmnEventType.MESSAGE and start.message_name)
        or (start.event_type == BpmnEventType.SIGNAL and start.signal_name)
    ):
        return None
    # cycle/date timer starts and every other shape: sequential end to end
    return "esp-start-unsupported"


# ---------------------------------------------------------------------------
# runtime path accounting — the one counter home


class PathAccounting:
    """Per-partition kernel-vs-host record accounting. Every fallback note
    and every kernel-routed command flows through here: the legacy
    ``fallback_reasons`` Counter (full strings, BENCH back-compat), the
    ``zeebe_kernel_records_total{path,reason}`` registry counter (bounded
    canonical labels), and the per-definition split behind the
    ``zeebe_kernel_coverage_ratio{definition}`` gauge and the parity gate.

    Recording is hot-path-adjacent (one note per routed head command, one
    per kernel group member): children are resolved lazily and cached, and
    per-definition tracking is bounded (overflow folds into ``other``)."""

    MAX_DEFINITIONS = 128

    def __init__(self, partition_id: int | str = 0) -> None:
        from zeebe_tpu.utils.metrics import REGISTRY

        self.partition = str(partition_id)
        #: reason string (full, incl. ``head-*:<kind>`` suffixes) → count;
        #: cleared by bench between measurement windows
        self.reasons: Counter = Counter()
        #: reason strings that failed catalog validation (a test asserts
        #: this stays empty — new reasons register in the catalog first)
        self.unregistered: Counter = Counter()
        self.kernel_records = 0
        self.host_records = 0
        # definition (bpmnProcessId) → [kernel, host, Counter(reasons)]
        self.per_definition: dict[str, list] = {}
        self._records_total = REGISTRY.counter(
            "kernel_records_total",
            "commands routed by the stream processor, by path and "
            "(host-path) catalog reason",
            ("partition", "path", "reason"))
        self._coverage = REGISTRY.gauge(
            "kernel_coverage_ratio",
            "records on the kernel path / total routed records, per "
            "definition (cumulative over the partition's life)",
            ("partition", "definition"))
        self._children: dict = {}
        self._kernel_child = self._records_total.labels(
            self.partition, "kernel", "-")
        #: (path, ``<ValueType>.<Intent>``) → commands processed: ``kernel``
        #: in a committed kernel group, ``host`` on the sequential path
        self.kinds: Counter = Counter()
        self._by_kind = REGISTRY.counter(
            "kernel_records_by_kind_total",
            "commands processed by the stream processor, by path (kernel: "
            "in a committed kernel group; host: on the sequential path) and "
            "command kind",
            ("partition", "path", "kind"))
        self._kind_children: dict = {}

    def _def_slot(self, definition: str) -> list:
        slot = self.per_definition.get(definition)
        if slot is None:
            if len(self.per_definition) >= self.MAX_DEFINITIONS:
                definition = "other"
                slot = self.per_definition.get(definition)
                if slot is not None:
                    return slot
            # [kernel, host, host-reason Counter, cached gauge child] —
            # the child is resolved once per definition, not per note
            slot = self.per_definition[definition] = [
                0, 0, Counter(),
                self._coverage.labels(self.partition, definition)]
        return slot

    @staticmethod
    def _set_coverage(slot: list) -> None:
        total = slot[0] + slot[1]
        if total:
            slot[3].set(slot[0] / total)

    def note_kernel(self, definition: str, n: int = 1) -> None:
        """``n`` commands of ``definition`` rode the kernel path."""
        self.kernel_records += n
        self._kernel_child.inc(n)
        slot = self._def_slot(definition)
        slot[0] += n
        self._set_coverage(slot)

    def note_kind(self, path: str, record) -> None:
        """One command of ``record``'s kind was processed on ``path``."""
        key = (path, record.value_type, record.intent)
        slot = self._kind_children.get(key)
        if slot is None:
            kind = f"{record.value_type.name}.{record.intent.name}"
            slot = self._kind_children[key] = (
                (path, kind), self._by_kind.labels(self.partition, path, kind))
        self.kinds[slot[0]] += 1
        slot[1].inc()

    def note_host(self, reason: str, definition: str = "-") -> None:
        """One head command took the host path for ``reason`` (a catalog
        code or a ``head-*:<kind>`` family member)."""
        self.reasons[reason] += 1
        label = canonical_reason(reason)
        if label is None:
            self.unregistered[reason] += 1
            label = "unregistered"
        self.host_records += 1
        child = self._children.get(label)
        if child is None:
            child = self._children[label] = self._records_total.labels(
                self.partition, "host", label)
        child.inc()
        slot = self._def_slot(definition)
        slot[1] += 1
        slot[2][reason] += 1
        self._set_coverage(slot)

    def coverage_ratio(self) -> float:
        total = self.kernel_records + self.host_records
        return self.kernel_records / total if total else 1.0

    def mark(self) -> dict:
        """Snapshot for windowed measurement (bench scenarios measure
        coverage over the driven window, not the warmup)."""
        return {
            "kernel": self.kernel_records,
            "host": self.host_records,
            "reasons": dict(self.reasons),
            "per_definition": {
                d: (s[0], s[1], dict(s[2]))
                for d, s in self.per_definition.items()
            },
        }

    def delta_since(self, mark: dict) -> dict:
        """Counts accumulated since ``mark`` — the shape
        ``parity_violations`` consumes (perDefinition rows)."""
        reasons = {
            r: c - mark["reasons"].get(r, 0)
            for r, c in self.reasons.items()
            if c > mark["reasons"].get(r, 0)
        }
        per_def: dict[str, dict] = {}
        for d, s in self.per_definition.items():
            mk, mh, mr = mark["per_definition"].get(d, (0, 0, {}))
            kernel, host = s[0] - mk, s[1] - mh
            if kernel or host:
                per_def[d] = {
                    "kernel": kernel, "host": host,
                    "hostReasons": {
                        r: c - mr.get(r, 0)
                        for r, c in s[2].items() if c > mr.get(r, 0)
                    },
                }
        return {
            "kernel": self.kernel_records - mark["kernel"],
            "host": self.host_records - mark["host"],
            "reasons": reasons,
            "perDefinition": per_def,
        }

    def snapshot(self) -> dict:
        """The ``kernelCoverage`` block served on partition ``/health`` and
        ``/cluster/status`` rows (and folded into BENCH extra)."""
        top = self.reasons.most_common(8)
        return {
            "kernelRecords": self.kernel_records,
            "hostRecords": self.host_records,
            "coverageRatio": round(self.coverage_ratio(), 4),
            "perDefinition": {
                d: {"kernel": s[0], "host": s[1],
                    "coverageRatio": round(
                        s[0] / (s[0] + s[1]), 4) if (s[0] + s[1]) else 1.0,
                    "hostReasons": dict(s[2])}
                for d, s in sorted(self.per_definition.items())
            },
            "topFallbackReasons": [
                {"reason": r, "count": c} for r, c in top],
        }


# ---------------------------------------------------------------------------
# the static eligibility report


def classify_definition(exe: ExecutableProcess, processes=None,
                        definition_key: int = -1, registry=None) -> dict:
    """Static eligibility report for one definition: runs the REAL
    ``KernelRegistry`` lookup (same inlining, same solo compile, same typed
    decline reasons admission will hit), then explains every host-forced
    row through the reason catalog. ``processes`` (the deployed
    ProcessState) is needed to resolve call activities; without it they
    honestly classify ``call-activity-unresolved``.

    Pass ONE ``registry`` (with unique ``definition_key``s) to classify a
    whole deployment set jointly — the prediction then sees exactly what
    runtime admission will: cross-definition SlotMap clashes in the shared
    compile and the registry's ``max_definitions`` capacity
    (``table-set-full``). Solo classification cannot predict either (the
    honest caveat in docs/eligibility.md)."""
    from zeebe_tpu.engine.kernel_backend import KernelRegistry

    reg = registry if registry is not None else KernelRegistry()
    key = definition_key if definition_key > 0 else 1
    info = reg.lookup(key, exe, processes=processes)
    report: dict[str, Any] = {
        "bpmnProcessId": exe.process_id,
        "definitionKey": definition_key,
        "runtimeOnlyReasons": sorted(RUNTIME_REASONS),
    }
    if info is None:
        # lookup returning None WITHOUT recording a decline reason is the
        # capacity path (len(_infos) >= max_definitions)
        reason = reg.decline_reason(key) or "table-set-full"
        report["eligible"] = False
        report["definitionReasons"] = [reason]
        report["elements"] = [
            {"id": el.id, "type": el.element_type.name, "path": "host",
             **({"reason": r} if (r := element_host_reason(exe, el)) else {})}
            for el in exe.elements[1:]
        ]
        report["counts"] = {"kernel": 0, "host": len(exe.elements) - 1}
        return report

    sx = info.exe  # synthetic (call activities + MI bodies inlined)
    esp_rows = _esp_subtree_rows(sx)
    elements = []
    kernel = host = 0
    for el in sx.elements[1:]:
        if el.idx in info.host_idxs:
            # own reason first; a reason-less host row inside an event
            # sub-process is a body element (the lowering escapes the whole
            # subtree); anything else was a compile downgrade
            reason = (element_host_reason(sx, el)
                      or ("event-subprocess-body" if el.idx in esp_rows
                          else "condition-not-compilable"))
            path = "host"
        elif el.idx in esp_rows:
            # individually eligible, but tokens only enter through the
            # host-routed event-sub-process start — effectively host
            reason = "event-subprocess-body"
            path = "host"
        else:
            reason = None
            path = "kernel"
        row = {"id": el.id, "type": el.element_type.name, "path": path}
        if reason:
            row["reason"] = reason
        elements.append(row)
        kernel += path == "kernel"
        host += path == "host"
    report["eligible"] = True
    report["definitionReasons"] = []
    report["elements"] = elements
    report["counts"] = {"kernel": kernel, "host": host}
    return report


def _esp_subtree_rows(exe: ExecutableProcess) -> set[int]:
    """Rows inside any event sub-process (the container, its start, its
    body): device-unreachable even when individually eligible."""
    containers = {el.idx for el in exe.elements
                  if el.element_type == BpmnElementType.EVENT_SUB_PROCESS}
    if not containers:
        return set()
    rows: set[int] = set()
    for el in exe.elements:
        idx, seen = el.idx, []
        while idx >= 0 and idx not in rows:
            if idx in containers:
                rows.update(seen, {idx})
                break
            seen.append(idx)
            idx = exe.elements[idx].parent_idx
        else:
            if idx >= 0:  # walked into an already-classified subtree
                rows.update(seen)
    return rows


# ---------------------------------------------------------------------------
# the static-vs-observed parity gate


def parity_violations(predictions: dict[str, bool],
                      observed: dict[str, dict]) -> list[str]:
    """Compare the classifier's per-definition prediction against observed
    routing (a ``PathAccounting.snapshot()['perDefinition']`` block). A
    definition the report calls kernel-eligible whose records routed
    host-side for a NON-runtime reason — or an ineligible one that rode
    the kernel — is a violation. Runtime-only reasons and ordinary
    ``head-sequential`` traffic (non-candidate command kinds) never count
    against the prediction."""
    violations: list[str] = []
    for definition, obs in sorted(observed.items()):
        predicted = predictions.get(definition)
        if predicted is None:
            continue  # unattributed ("-"/"other") or undeclared definition
        kernel, host = obs.get("kernel", 0), obs.get("host", 0)
        reasons = obs.get("hostReasons", {})
        static_host = {
            r: c for r, c in reasons.items()
            if (canonical_reason(r) or "unregistered") not in RUNTIME_REASONS
            and not r.startswith("head-sequential")
        }
        if predicted:
            if static_host:
                violations.append(
                    f"{definition}: predicted kernel-eligible but "
                    f"{sum(static_host.values())} record(s) host-routed for "
                    f"non-runtime reason(s) {static_host} "
                    f"(kernel={kernel}, host={host})")
        elif kernel > 0:
            violations.append(
                f"{definition}: predicted host-forced but {kernel} "
                f"record(s) rode the kernel path")
    return violations
