"""Read the span system on a real run: per-RPC critical-path edges and the
account of one process instance, over a window of the run.

    python -m zeebe_tpu.observability.account \\
        [--start-marker REGEX --seconds S] <script> [args...]

runs a Python entry point in this process (``runpy``) with the tracer enabled
at a capacity that holds the run. The window opens when the script writes a
line matching ``--start-marker`` to stderr (``benchmarks/run.py`` writes
``window opens:``) and lasts ``--seconds``; the marker's moment is read on the
monotonic clock and laid on the spans' wall clock through the tracer's anchor.
When the script ends the tool prints, for the requests that *started* in the
window:

- per RPC kind (``CreateProcessInstance``, ``ActivateJobs``, ``CompleteJob``):
  count, mean and median of ``gateway.request`` and the mean of each
  critical-path edge plus ``unattributed`` (the means add up to the mean total;
  every breakdown goes through ``check_conservation``);
- the **account** of an instance, from the ``processInstanceKey`` that
  ``gateway.request`` and ``exporter.export`` spans carry: create RPC -> job
  created -> job activated -> the worker's hold -> complete RPC -> process
  completed -> its first export, mean and median of each stretch and of the
  whole. A stretch that no span covers reads ``uncovered``;
- beside the whole, the median the script itself printed (the harness's
  ``completion_p50_ms``): the difference lies outside the program's spans;
  Where the workers stream (every instance has a ``jobstream.push`` span, from
  the moment its job was made activatable to its being put on a client
  stream) the two stretches between job created and the worker's hold are
  that span's: the dispatcher's queue, then its activation and the delivery;
- ``evicted``, which must be 0: a ring that dropped spans of the window makes
  the tool refuse (:class:`SpansEvicted`) instead of reporting a part.

The reduction (:func:`window_account`) is pure: span dicts in, numbers out.
"""

from __future__ import annotations

import argparse
import io
import re
import runpy
import statistics
import sys
import time

from zeebe_tpu.observability.critical_path import (
    EDGES,
    _attr,
    breakdowns_from_spans,
    check_conservation,
)
from zeebe_tpu.observability.span import from_clock_ns

RPC_BY_COMMAND = {
    ("PROCESS_INSTANCE_CREATION", "CREATE"): "CreateProcessInstance",
    ("JOB_BATCH", "ACTIVATE"): "ActivateJobs",
    ("JOB", "COMPLETE"): "CompleteJob",
}

# the account's stretches between consecutive marks, with what covers each:
# a span's name, "outside" (the job waits for a worker's poll, or the worker
# holds it: the client's schedule, not the program's time) or "uncovered"
# (the program's time that no span covers)
STRETCHES = (
    ("create RPC", "gateway.request"),
    ("create acked -> job created at exporter", "uncovered"),
    ("job created -> activate submitted (waits for a poll)", "outside"),
    ("activate RPC", "gateway.request"),
    ("worker's hold", "outside"),
    ("complete RPC", "gateway.request"),
    ("complete acked -> process completed at exporter", "uncovered"),
    ("first export", "exporter.export"),
)


# where every instance of the window has a ``jobstream.push`` span (streaming
# workers): no poll is waited for, the dispatcher submits the activation, and
# the activation's stretch ends when the job is on its worker's stream
STRETCHES_PUSHED = {
    2: ("job created -> activate submitted (the dispatcher's queue)",
        "jobstream.push"),
    3: ("activate command, then the job onto its worker's stream",
        "jobstream.push"),
}


class SpansEvicted(RuntimeError):
    """The ring dropped spans that may belong to the window."""


def _end(span: dict) -> int:
    return span.get("startUs", 0) + max(span.get("durUs", 0), 0)


def _stats(values: list) -> dict:
    return {"mean_ms": statistics.fmean(values) / 1e3,
            "median_ms": statistics.median(values) / 1e3}


def window_account(spans: list[dict], header: dict | None,
                   start_us: int, end_us: int) -> dict:
    """``spans``: span dicts in the order the collector holds them (oldest
    first); ``header``: the dump's (``SpanCollector.header()``). Refuses when
    the ring evicted spans and the oldest one left ended inside or after the
    window: spans are added as they end, so everything dropped ended before
    it, and only then is the window known to be whole."""
    evicted = (header or {}).get("evicted", 0)
    if evicted and (not spans or _end(spans[0]) >= start_us):
        raise SpansEvicted(
            f"{evicted} spans evicted and the oldest one left ends at "
            f"{_end(spans[0]) if spans else None} us, not before the window "
            f"({start_us} us): raise the capacity")

    requests = [s for s in spans if s.get("name") == "gateway.request"]
    in_window = {(s["traceId"], _attr(s, "position")): s for s in requests
                 if start_us <= s.get("startUs", 0) < end_us}
    by_kind: dict[str, list] = {}
    violations = []
    for breakdown in breakdowns_from_spans(spans):
        root = in_window.get((breakdown["traceId"], breakdown.get("position")))
        if root is None or breakdown["rootName"] != "gateway.request":
            continue
        violations += check_conservation(breakdown)
        command = (_attr(root, "valueType"), _attr(root, "intent"))
        kind = RPC_BY_COMMAND.get(command, ".".join(map(str, command)))
        by_kind.setdefault(kind, []).append(breakdown)
    rpcs = {}
    for kind, breakdowns in sorted(by_kind.items()):
        rpcs[kind] = {
            "count": len(breakdowns),
            **_stats([b["totalUs"] for b in breakdowns]),
            "edges_mean_ms": {
                **{edge: statistics.fmean(b["edges"][edge]
                                          for b in breakdowns) / 1e3
                   for edge in EDGES},
                "unattributed": statistics.fmean(
                    b["unattributedUs"] for b in breakdowns) / 1e3},
        }
    return {"evicted": evicted, "window_us": [start_us, end_us],
            "rpcs": rpcs, "conservation_violations": violations,
            "account": _instance_account(spans, requests, start_us, end_us)}


def _instance_account(spans: list[dict], requests: list[dict],
                      start_us: int, end_us: int) -> dict:
    """Per instance whose create RPC started in the window: the marks, made
    monotone (a mark that came before its predecessor leaves a stretch of 0),
    and the stretches between them, which add up to the whole."""
    first: dict[tuple, dict] = {}

    def keep(what: str, key, span: dict) -> None:
        held = first.get((what, key))
        if held is None or span["startUs"] < held["startUs"]:
            first[(what, key)] = span

    for s in requests:
        command = (_attr(s, "valueType"), _attr(s, "intent"))
        what = RPC_BY_COMMAND.get(command)
        if what == "ActivateJobs":
            for key in _attr(s, "processInstanceKeys") or ():
                keep(what, key, s)
        elif what is not None and _attr(s, "rejection") is None:
            keep(what, _attr(s, "processInstanceKey"), s)
    for s in spans:
        if s.get("name") == "jobstream.push":
            keep("pushed", _attr(s, "processInstanceKey"), s)
        if s.get("name") != "exporter.export":
            continue
        record = (_attr(s, "valueType"), _attr(s, "intent"))
        key = _attr(s, "processInstanceKey")
        if record == ("JOB", "CREATED"):
            keep("job created", key, s)
        elif (record == ("PROCESS_INSTANCE", "ELEMENT_COMPLETED")
              and _attr(s, "key") == key):
            keep("process completed", key, s)

    rows: list[list[int]] = []
    creates = incomplete = pushed = 0
    for (what, key), create in first.items():
        if what != "CreateProcessInstance" or key is None:
            continue
        if not start_us <= create["startUs"] < end_us:
            continue
        creates += 1
        parts = [first.get((w, key)) for w in
                 ("job created", "ActivateJobs", "CompleteJob",
                  "process completed")]
        if None in parts:
            incomplete += 1
            continue
        job_created, activate, complete, completed = parts
        push = first.get(("pushed", key))
        pushed += push is not None
        handed = _end(activate) if push is None else max(_end(activate),
                                                         _end(push))
        marks = [create["startUs"], _end(create), _end(job_created),
                 activate["startUs"], handed, complete["startUs"],
                 _end(complete), completed["startUs"], _end(completed)]
        for i in range(1, len(marks)):
            marks[i] = max(marks[i], marks[i - 1])
        rows.append([b - a for a, b in zip(marks, marks[1:])])
    out = {"creates_in_window": creates, "incomplete": incomplete,
           "instances": len(rows), "pushed": pushed}
    if not rows:
        return out
    stretches = list(STRETCHES)
    if pushed == len(rows):
        for i, stretch in STRETCHES_PUSHED.items():
            stretches[i] = stretch
    wholes = [sum(row) for row in rows]
    out["whole"] = _stats(wholes)
    out["stretches"] = [
        {"name": name, "covered_by": covered, **_stats([row[i] for row in rows])}
        for i, (name, covered) in enumerate(stretches)]
    uncovered = sum(s["mean_ms"] for s in out["stretches"]
                    if s["covered_by"] == "uncovered")
    out["uncovered_mean_ms"] = uncovered
    out["uncovered_share"] = uncovered / out["whole"]["mean_ms"]
    return out


def format_report(report: dict, printed_median_ms: float | None = None) -> str:
    lines = [f"evicted: {report['evicted']}   conservation violations: "
             f"{len(report['conservation_violations'])}",
             "", "per RPC kind (ms; edges are means and add up to the mean):"]
    header = ["kind", "count", "mean", "median", *EDGES, "unattributed"]
    lines.append(" | ".join(header))
    for kind, r in report["rpcs"].items():
        lines.append(" | ".join(
            [kind, str(r["count"]), f"{r['mean_ms']:.3f}",
             f"{r['median_ms']:.3f}"]
            + [f"{r['edges_mean_ms'][e]:.3f}" for e in (*EDGES, "unattributed")]))
    create = report["rpcs"].get("CreateProcessInstance")
    if create:
        lines.append(f"ack_p50_ms (create RPC, gateway.request median): "
                     f"{create['median_ms']:.3f}")
    account = report["account"]
    lines += ["", f"account of an instance: {account['instances']} of "
                  f"{account['creates_in_window']} creates in the window "
                  f"({account['incomplete']} lack a span of the chain)"]
    if account["instances"]:
        lines.append("stretch | covered by | mean ms | median ms")
        for s in account["stretches"]:
            lines.append(f"{s['name']} | {s['covered_by']} | "
                         f"{s['mean_ms']:.3f} | {s['median_ms']:.3f}")
        whole = account["whole"]
        lines.append(f"whole (create RPC starts -> first export of process "
                     f"completed) | | {whole['mean_ms']:.3f} | "
                     f"{whole['median_ms']:.3f}")
        lines.append(f"uncovered: {account['uncovered_mean_ms']:.3f} ms of the "
                     f"mean whole ({100 * account['uncovered_share']:.1f} %)")
        if printed_median_ms is not None:
            lines.append(
                f"the script's own median: {printed_median_ms:.3f} ms; minus "
                f"the whole's median: "
                f"{printed_median_ms - whole['median_ms']:.3f} ms outside the "
                f"program's spans")
    return "\n".join(lines)


class _Watch(io.TextIOBase):
    """stderr passed through, its lines watched for the window's marker
    (first match, stamped on the monotonic clock) and the script's own
    median (last match)."""

    def __init__(self, stream, marker, printed) -> None:
        self.stream, self.marker, self.printed = stream, marker, printed
        self.marker_ns: int | None = None
        self.printed_value: float | None = None
        self._partial = ""

    def write(self, text: str) -> int:
        now = time.monotonic_ns()
        self.stream.write(text)
        *lines, self._partial = (self._partial + text).split("\n")
        for line in lines:
            if (self.marker is not None and self.marker_ns is None
                    and self.marker.search(line)):
                self.marker_ns = now
            found = self.printed.search(line)
            if found:
                self.printed_value = float(found.group(1))
        return len(text)

    def flush(self) -> None:
        self.stream.flush()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m zeebe_tpu.observability.account",
        description=__doc__.split("\n\n")[0])
    parser.add_argument("--start-marker", default=None,
                        help="regex; the window opens when the script writes "
                             "a matching line to stderr (default: the whole "
                             "run)")
    parser.add_argument("--seconds", type=float, default=None,
                        help="the window's length from the marker")
    parser.add_argument("--printed-median", default=r'"completion_p50_ms": ([0-9.]+)',
                        help="regex with one group: the median the script "
                             "prints of itself, in ms")
    parser.add_argument("--capacity", type=int, default=1 << 21,
                        help="the span ring's size for this run")
    parser.add_argument("--spans-out", default=None,
                        help="also write the span dump (JSONL) here")
    parser.add_argument("script")
    parser.add_argument("args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    if (args.start_marker is None) != (args.seconds is None):
        parser.error("--start-marker and --seconds go together")

    from zeebe_tpu.observability.tracer import configure_tracing

    tracer = configure_tracing(enabled=True, capacity=args.capacity)
    watch = _Watch(sys.stderr,
                   re.compile(args.start_marker) if args.start_marker else None,
                   re.compile(args.printed_median))
    saved_argv, saved_stderr = sys.argv, sys.stderr
    sys.argv, sys.stderr = [args.script, *args.args], watch
    code = 0
    try:
        runpy.run_path(args.script, run_name="__main__")
    except SystemExit as exit_:
        code = exit_.code if isinstance(exit_.code, int) else int(bool(exit_.code))
    finally:
        sys.argv, sys.stderr = saved_argv, saved_stderr
        tracer.disable()
    collector = tracer.collector
    if args.spans_out:
        collector.to_jsonl(args.spans_out)
    header = collector.header()
    spans = [s.to_dict() for s in collector.snapshot()]
    print(f"account: script exit code {code}; {len(spans)} spans held, "
          f"{header['emitted']} emitted", flush=True)
    if not spans:
        print("account: no spans: nothing to read")
        return code or 1
    if args.start_marker is not None:
        if watch.marker_ns is None:
            print(f"account: no stderr line matched {args.start_marker!r}")
            return code or 1
        start_us = from_clock_ns(header["anchor"], watch.marker_ns)
        end_us = start_us + int(args.seconds * 1e6)
    else:
        start_us = min(s["startUs"] for s in spans)
        end_us = max(_end(s) for s in spans) + 1
    try:
        report = window_account(spans, header, start_us, end_us)
    except SpansEvicted as err:
        print(f"account: refused: {err}")
        return code or 1
    print(format_report(report, watch.printed_value), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
