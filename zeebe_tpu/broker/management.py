"""Management HTTP server: health probes, metrics, admin operations.

Reference: dist/shared/management — actuator endpoints (startup/ready/liveness
probes wired to BrokerHealthCheckService, Prometheus servlet, /actuator/backups
trigger, pause/resume processing via BrokerAdminService).

Endpoints:
  GET  /health    → aggregated component health (liveness) + firing alerts
  GET  /ready     → 200 when every local partition has a role and a processor
  GET  /metrics   → Prometheus text exposition
  GET  /partitions → per-partition health dicts
  GET  /timeseries → retained metric history from the in-memory store
                    (?name= series or histogram base name — no name lists
                    the names; ?since= unix ms; ?step= ms downsampling)
  GET  /flight    → the flight recorder's live event rings (the same payload
                    a crash dumps to <data-dir>/flight-<ts>.json)
  GET  /alerts    → alert evaluator state (pending + firing)
  GET  /control   → closed-loop control plane state: controllers, actuator
                    bounds/values/audit counters, aggregated loops
                    (404 when ZEEBE_CONTROL_ENABLED=0 or sampling is off)
  GET  /cluster/status → topology + per-broker health/alerts/headline rates,
                    aggregated across all brokers when the server is given
                    the hosting runtime (in-process fan-out), else local
  GET  /traces    → collected tracing spans (observability subsystem);
                    ?format=chrome returns Chrome-trace-event JSON that opens
                    directly in Perfetto, ?limit=N tails the newest N spans
  GET  /profile   → one-shot sampling profiler over all runtime threads
                    (?seconds=N, capped at 30; pump/kernel/io time split;
                    ?format=folded returns flamegraph.pl/speedscope-
                    compatible collapsed stacks as text/plain)
  GET  /profile/continuous → the always-on continuous profiler's retained
                    folded-stack windows (?since= unix ms,
                    ?format=folded|json); 404 when profiling_hz=0
  POST /profile/device → single-flight jax.profiler.trace() capture into
                    <data-dir>/jax-trace-<ts>/ (?seconds=N, capped at 30);
                    202 with the trace dir, 409 while one is in flight
  POST /backups/<id> → trigger a cluster-consistent checkpoint
  GET  /backups   → backup store listing (when a store is configured)
  POST /pause | /resume → pause/resume stream processing (BrokerAdminService)
  POST /rebalance → transfer partition leadership to the highest-priority
       replicas (reference: dist/…/management/RebalancingEndpoint.java)
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from zeebe_tpu.utils.metrics import REGISTRY


class ManagementServer:
    def __init__(self, broker, bind: tuple[str, int] = ("127.0.0.1", 0),
                 registry=None, runtime=None) -> None:
        # broker=None: the gateway-process shape (multiproc workers host the
        # brokers) — /metrics, /cluster/status, and /health (aggregated from
        # the runtime) stay up; broker-local endpoints answer 404
        self.broker = broker
        self.registry = registry or REGISTRY
        # hosting ClusterRuntime (optional): enables the /cluster/status
        # all-broker fan-out for the in-process deployment shape
        self.runtime = runtime
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _send(self, code: int, body: str,
                      content_type: str = "application/json") -> None:
                data = body.encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                try:
                    outer._get(self)
                except Exception as exc:  # management must not crash the broker
                    self._send(500, json.dumps({"error": str(exc)}))

            def do_POST(self):
                try:
                    outer._post(self)
                except Exception as exc:
                    self._send(500, json.dumps({"error": str(exc)}))

        self.server = ThreadingHTTPServer(bind, Handler)
        self.port = self.server.server_address[1]
        self._thread: threading.Thread | None = None

    def _get(self, handler) -> None:
        path = handler.path.split("?", 1)[0]
        if self.broker is None:
            # no local broker (gateway process, or a broker-free test
            # server): /cluster/status, /health, /ready aggregate from the
            # runtime; broker-independent endpoints (/metrics, /traces,
            # /profile, and the getattr-guarded observability paths) fall
            # through to the shared handlers; true broker-local endpoints
            # answer 404 instead of crashing
            if path == "/health" and self.runtime is not None:
                # LIVENESS of the gateway process: always 200 while it can
                # answer — one crash-looping worker (reported in the payload)
                # must not get the gateway, and with it the supervisor and
                # every healthy worker, liveness-probed to death
                handler._send(200, json.dumps(self.runtime.cluster_status()))
                return
            if path == "/ready" and self.runtime is not None:
                # READINESS aggregates: serving needs a live leader for every
                # partition (the runtime knows; default to the health roll-up)
                ready_fn = getattr(self.runtime, "ready", None)
                status = self.runtime.cluster_status()
                ready = (bool(ready_fn()) if ready_fn is not None
                         else status.get("health") in ("HEALTHY", "DEGRADED"))
                handler._send(200 if ready else 503, json.dumps(
                    {"ready": ready, **status}))
                return
            broker_free = {"/metrics", "/traces", "/profile", "/flight",
                           "/timeseries", "/alerts", "/profile/continuous"}
            if self.runtime is not None:
                # the shared handler below serves it via the runtime fan-out
                broker_free.add("/cluster/status")
            if path not in broker_free:
                handler._send(404, json.dumps(
                    {"error": "no local broker: "
                              f"endpoint {path} unavailable"}))
                return
        if path == "/metrics":
            handler._send(200, self.registry.expose(), "text/plain; version=0.0.4")
        elif path == "/health":
            health = self.broker.health_monitor.to_dict()
            alerts = getattr(self.broker, "alerts", None)
            if alerts is not None:
                # alert details ride the health payload so one probe answers
                # both "is it up" and "is anything on fire"
                health["alerts"] = alerts.snapshot()
                health["alertsFiring"] = len(alerts.firing())
            # recovery-budget plane: the last rebuild's cost per partition
            # (duration, replay length, budget verdict) rides the same probe
            # — after a kill+restart, /health alone answers "what did the
            # recovery cost and did it fit the budget"
            recoveries = {
                str(pid): p.last_recovery
                for pid, p in self.broker.partitions.items()
                if getattr(p, "last_recovery", None) is not None
            }
            if recoveries:
                health["recoveries"] = recoveries
            code = 200 if self.broker.health_monitor.is_healthy() else 503
            handler._send(code, json.dumps(health))
        elif path == "/ready":
            ready = all(
                p.processor is not None for p in self.broker.partitions.values()
            )
            handler._send(200 if ready else 503, json.dumps({"ready": ready}))
        elif path == "/partitions":
            handler._send(200, json.dumps(
                [p.health() for p in self.broker.partitions.values()]
            ))
        elif path == "/timeseries":
            from urllib.parse import parse_qs, urlsplit

            store = getattr(self.broker, "timeseries", None)
            if store is None:
                handler._send(404, json.dumps(
                    {"error": "time-series sampling disabled "
                              "(metrics_sampling_ms=0)"}))
                return
            params = parse_qs(urlsplit(handler.path).query)
            name = params.get("name", [""])[0]
            if not name:
                stats = store.stats()
                stats.pop("series", None)  # the count would shadow the list
                handler._send(200, json.dumps({
                    "series": store.series_names(), **stats,
                    "seriesCount": len(store.series_names())}))
                return
            try:
                since = int(params.get("since", ["0"])[0])
                step = int(params.get("step", ["0"])[0])
            except ValueError:
                handler._send(400, json.dumps(
                    {"error": "since and step must be integers (ms)"}))
                return
            handler._send(200, json.dumps({
                "name": name, "since": since, "step": step,
                "series": store.query(name, since_ms=since, step_ms=step),
            }))
        elif path == "/flight":
            # broker-local recorder, or the gateway runtime's own ring
            # (worker restarts, routing-epoch changes, request re-routes)
            recorder = getattr(self.broker, "flight_recorder", None)
            if recorder is None and self.runtime is not None:
                recorder = getattr(self.runtime, "flight", None)
            if recorder is None:
                handler._send(404, json.dumps(
                    {"error": "no flight recorder"}))
                return
            handler._send(200, json.dumps(recorder.snapshot(), default=str))
        elif path == "/control":
            # closed-loop control plane (ISSUE 12): controllers, actuator
            # bounds/values/audit counters, and the aggregated loops
            # (snapshot scheduler, admission shed ladder)
            plane = getattr(self.broker, "control", None)
            if plane is None:
                handler._send(404, json.dumps(
                    {"error": "control plane disabled "
                              "(ZEEBE_CONTROL_ENABLED=0 or metrics "
                              "sampling off)"}))
                return
            handler._send(200, json.dumps(plane.snapshot(), default=str))
        elif path == "/alerts":
            alerts = getattr(self.broker, "alerts", None)
            if alerts is None:
                handler._send(404, json.dumps(
                    {"error": "alert evaluation disabled"}))
                return
            handler._send(200, json.dumps({
                "alerts": alerts.snapshot(),
                "firing": len(alerts.firing()),
                "rules": [r.describe() for r in alerts.rules],
            }))
        elif path == "/cluster/status":
            if self.runtime is not None:
                status = self.runtime.cluster_status()
            else:
                status = cluster_status([self.broker])
            handler._send(200, json.dumps(status))
        elif path == "/traces":
            from urllib.parse import parse_qs, urlsplit

            from zeebe_tpu.observability import chrome_trace, get_tracer

            params = parse_qs(urlsplit(handler.path).query)
            tracer = get_tracer()
            spans = tracer.collector.snapshot()
            try:
                limit = int(params.get("limit", ["0"])[0])
            except ValueError:
                limit = 0
            if limit > 0:
                spans = spans[-limit:]
            header = tracer.collector.header()
            if params.get("format", ["json"])[0] == "chrome":
                handler._send(200, json.dumps(chrome_trace(spans, header)))
            else:
                handler._send(200, json.dumps({
                    "enabled": tracer.enabled,
                    "sampleRate": tracer.sampler.rate,
                    "seed": tracer.sampler.seed,
                    **header,
                    "spans": [s.to_dict() for s in spans],
                }))
        elif path == "/profile":
            from urllib.parse import parse_qs, urlsplit

            params = parse_qs(urlsplit(handler.path).query)
            seconds = parse_profile_seconds(params.get("seconds", ["2.0"])[0])
            if seconds is None:
                handler._send(400, json.dumps(
                    {"error": "seconds must be a positive number"}))
                return
            folded = params.get("format", ["json"])[0] == "folded"
            result = sample_profile(seconds, fold=folded)
            if folded:
                from zeebe_tpu.observability.profiler import folded_text

                handler._send(200, folded_text(result["folded"]),
                              "text/plain; charset=utf-8")
            else:
                handler._send(200, json.dumps(result))
        elif path == "/profile/continuous":
            from urllib.parse import parse_qs, urlsplit

            profiler = getattr(self.broker, "profiler", None)
            if profiler is None:
                handler._send(404, json.dumps(
                    {"error": "continuous profiler disabled "
                              "(profiling_hz=0)"}))
                return
            params = parse_qs(urlsplit(handler.path).query)
            try:
                since = int(params.get("since", ["0"])[0])
            except ValueError:
                handler._send(400, json.dumps(
                    {"error": "since must be an integer (unix ms)"}))
                return
            if params.get("format", ["json"])[0] == "folded":
                handler._send(200, profiler.folded(since_ms=since),
                              "text/plain; charset=utf-8")
            else:
                handler._send(200, json.dumps({
                    "hz": profiler.hz,
                    "achievedHz": profiler.achieved_hz,
                    "samples": profiler.samples_taken,
                    "windowMs": profiler.window_ms,
                    "since": since,
                    "windows": profiler.windows(since_ms=since),
                }))
        elif path == "/backups":
            if self.broker.backup_store is None:
                handler._send(404, json.dumps({"error": "no backup store configured"}))
                return
            statuses = [
                {"checkpointId": s.checkpoint_id, "partitionId": s.partition_id,
                 "status": s.status.value}
                for s in self.broker.backup_store.list_backups()
            ]
            handler._send(200, json.dumps(statuses))
        else:
            handler._send(404, json.dumps({"error": f"unknown path {path}"}))

    def _post(self, handler) -> None:
        path = handler.path.split("?", 1)[0]
        if self.broker is None:
            handler._send(404, json.dumps(
                {"error": "gateway-process management: broker-local "
                          f"endpoint {path} unavailable"}))
            return
        if path.startswith("/backups/"):
            checkpoint_id = int(path.rsplit("/", 1)[-1])
            accepted = self.broker.trigger_checkpoint(checkpoint_id)
            handler._send(202, json.dumps(
                {"checkpointId": checkpoint_id, "partitions": accepted}
            ))
        elif path == "/pause":
            self.broker.pause_processing()
            handler._send(200, json.dumps({"paused": True}))
        elif path == "/resume":
            self.broker.resume_processing()
            handler._send(200, json.dumps({"paused": False}))
        elif path == "/rebalance":
            # leadership rebalancing (reference: actuator RebalancingEndpoint)
            transferred = self.broker.rebalance()
            handler._send(202, json.dumps(
                {"transferred": {str(k): v for k, v in transferred.items()}}
            ))
        elif path == "/profile/device":
            from urllib.parse import parse_qs, urlsplit

            from zeebe_tpu.observability.profiler import CaptureInFlight

            capture = getattr(self.broker, "device_capture", None)
            if capture is None:
                handler._send(404, json.dumps(
                    {"error": "no device capture (broker has no data dir)"}))
                return
            params = parse_qs(urlsplit(handler.path).query)
            seconds = parse_profile_seconds(params.get("seconds", ["3.0"])[0])
            if seconds is None:
                handler._send(400, json.dumps(
                    {"error": "seconds must be a positive number"}))
                return
            try:
                trace_dir = capture.start(seconds)
            except CaptureInFlight as exc:
                # single-flight: jax.profiler supports one trace at a time
                handler._send(409, json.dumps({"error": str(exc)}))
                return
            handler._send(202, json.dumps(
                {"traceDir": str(trace_dir), "seconds": seconds}))
        else:
            handler._send(404, json.dumps({"error": f"unknown path {path}"}))

    def start(self) -> None:
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True, name="management-server")
        self._thread.start()

    def stop(self) -> None:
        self.server.shutdown()
        if self._thread is not None:
            self._thread.join(timeout=5)


PROFILE_MAX_SECONDS = 30.0


def parse_profile_seconds(raw: str) -> float | None:
    """``?seconds=`` validation for /profile: positive finite number, capped
    at :data:`PROFILE_MAX_SECONDS` (the profiler blocks a handler thread for
    the whole window — an uncapped value is a free DoS). None = reject 400."""
    try:
        seconds = min(float(raw), PROFILE_MAX_SECONDS)
    except ValueError:
        return None
    if not 0 < seconds:  # also rejects NaN
        return None
    return seconds


# -- cluster status aggregation ------------------------------------------------

_RATE_WINDOW_MS = 10_000


def _kernel_coverage_row(partition) -> dict:
    """The compact kernelCoverage block riding a /cluster/status partition
    row: cumulative path split + ratio + the dominant host reason (the full
    per-definition report lives on the partition's /health)."""
    backend = partition.processor.kernel_backend
    acct = backend.accounting
    top = acct.reasons.most_common(1)
    # one locked snapshot, the canonical key names (the CLI renderer and
    # the /health block read the same surface)
    device = backend.health.status()
    return {
        "kernelRecords": acct.kernel_records,
        "hostRecords": acct.host_records,
        "coverageRatio": round(acct.coverage_ratio(), 4),
        **({"dominantHostReason": top[0][0]} if top else {}),
        # device-fault defense (ISSUE 15): compact ladder state — the full
        # fault/canary detail lives on the partition's /health
        "device": {k: device[k]
                   for k in ("state", "shadowChecks", "shadowMismatches")},
    }


def broker_status(broker) -> dict:
    """One broker's row in /cluster/status: health, roles, alert state, and
    headline rates read from its time-series store (appends/s from the
    counter-as-rate series, processing/s from the processed-position gauge's
    trailing-window increase, export lag from the per-container lag gauge)."""
    node = broker.cfg.node_id
    status: dict = {
        "nodeId": node,
        "health": broker.health_monitor.status().name,
        "partitions": {
            str(pid): {
                "role": p.role.value, "term": p.raft.current_term,
                "lastPosition": p.stream.last_position,
                # state tiering (ISSUE 8): parked-instance accounting when
                # the cold store is on
                **({"parkedCold": p.tiering.spilled_instances,
                    "parkCandidates": p.tiering.pending_candidates,
                    "coldBytes": p.db.tier_stats()["coldBytes"]}
                   if p.tiering is not None and p.db is not None
                   and hasattr(p.db, "tier_stats") else {}),
                # kernel-path coverage (ISSUE 13): the compact split `cli
                # top` renders (full per-definition detail on /health)
                **({"kernelCoverage": _kernel_coverage_row(p)}
                   if p.processor is not None
                   and p.processor.kernel_backend is not None else {}),
                # at-rest storage integrity (ISSUE 14): compact form — the
                # full detection/repair detail lives on /health
                **({"storageIntegrity": {
                    "status": p.scrubber.status()["status"],
                    "corruptions": len(p.scrubber.detections),
                    "repairs": len(p.scrubber.repairs),
                    "fullPasses": p.scrubber.full_passes,
                }} if p.scrubber is not None else {}),
                # latency observatory (ISSUE 19): last window's per-stage
                # critical path — what `cli top` LATENCY renders
                **({"criticalPath": cp}
                   if getattr(p, "latency_observatory", None) is not None
                   and (cp := p.latency_observatory.status()) is not None
                   else {}),
            }
            for pid, p in sorted(broker.partitions.items())
        },
    }
    alerts = getattr(broker, "alerts", None)
    if alerts is not None:
        firing = alerts.firing()
        status["alertsFiring"] = len(firing)
        status["alerts"] = firing
    control = getattr(broker, "control", None)
    if control is not None:
        # control-plane evidence rides the row: knob values, bounds, and
        # adjustment counts per controller (rendered by `cli top` CONTROL)
        status["control"] = control.snapshot()
    auditor = getattr(broker, "auditor", None)
    if auditor is not None:
        # online-audit evidence (ISSUE 20): latched invariant alerts,
        # burn-rate state, leak verdicts, and the replica-CRC checkpoints
        # the harness-side ClusterAuditor joins across workers
        status["audit"] = auditor.snapshot()
    store = getattr(broker, "timeseries", None)
    if store is not None:
        now = broker.clock_millis()
        node_label = f'node="{node}"'
        append_rate = sum(
            e["value"] for e in store.latest(
                "zeebe_log_appender_record_appended_total")
            if node_label in e["labels"])
        status["rates"] = {
            "appendPerSec": round(append_rate, 1),
            "processedPerSec": round(store.rate(
                "zeebe_stream_processor_last_processed_position",
                _RATE_WINDOW_MS, now, labels_contains=node_label), 1),
        }
        lag = [e["value"] for e in store.latest(
            "zeebe_exporter_container_lag_records")]
        if lag:
            status["rates"]["exportLagRecords"] = max(lag)
    return status


def cluster_status(brokers) -> dict:
    """Aggregate /cluster/status over a set of (in-process) brokers: the
    gossiped topology document (cluster-wide by construction — any broker's
    copy serves), per-broker status rows, and the cluster-level headline."""
    brokers = list(brokers)
    rows = [broker_status(b) for b in brokers]
    topology = brokers[0].topology.topology.summary() if brokers else {}
    partition_ids = {
        pid for member in topology.get("members", {}).values()
        for pid in member.get("partitions", {})
    }
    firing = sum(r.get("alertsFiring", 0) for r in rows)
    worst = max((r["health"] for r in rows), default="HEALTHY",
                key=lambda name: ["HEALTHY", "DEGRADED", "UNHEALTHY",
                                  "DEAD"].index(name))
    return {
        "clusterSize": len(rows),
        "partitionsCount": len(partition_ids),
        "health": worst,
        "alertsFiring": firing,
        "appendPerSec": round(sum(
            r.get("rates", {}).get("appendPerSec", 0.0) for r in rows), 1),
        "processedPerSec": round(sum(
            r.get("rates", {}).get("processedPerSec", 0.0) for r in rows), 1),
        "topology": topology,
        "brokers": rows,
    }

def sample_profile(seconds: float, hz: float = 100.0,
                   fold: bool = False) -> dict:
    """One-shot sampling profiler over every runtime thread (the management
    /profile endpoint — the reference exposes JFR/async-profiler through its
    actuator; this is the in-process equivalent): snapshots all thread
    stacks at ``hz`` for ``seconds`` and aggregates by frame, so hot
    functions and per-thread time split (pump vs kernel vs io) read
    straight off the response without attaching a debugger.

    Sampling rides the shared :mod:`zeebe_tpu.observability.profiler`
    helper, so the thread-name map refreshes every tick (threads spawned
    mid-profile report by name, not raw ident), and pacing is deadline-based
    (sleep-only pacing undershoots ``hz`` by the per-tick work — the
    response carries the *achieved* rate either way). ``fold=True``
    additionally aggregates folded stacks (the same collapsed-stack format
    the continuous profiler serves), so both endpoints feed the same
    flamegraph tooling."""
    import time as _time

    from zeebe_tpu.observability.profiler import (
        PROFILER_THREAD_NAME,
        fold_stacks,
        sample_threads,
    )

    samples = 0
    by_frame: dict[str, int] = {}
    by_thread: dict[str, int] = {}
    folded: dict[str, int] = {}
    start = _time.monotonic()
    deadline = start + seconds
    interval = 1.0 / hz
    next_tick = start + interval
    own = threading.get_ident()
    while _time.monotonic() < deadline:
        # never profile the profilers: not this handler's own stack, and
        # not the continuous sampler's wait loop (default-on — it would
        # otherwise show in ~100% of samples); names refresh inside
        # sample_threads each tick, and so does this ident set
        skip = {own} | {t.ident for t in threading.enumerate()
                        if t.name == PROFILER_THREAD_NAME}
        stacks = sample_threads(exclude_idents=skip, max_depth=40)
        for name, frames in stacks:
            by_thread[name] = by_thread.get(name, 0) + 1
            for key in set(frames):  # recursion must not inflate a frame
                by_frame[key] = by_frame.get(key, 0) + 1
        if fold:
            for key, count in fold_stacks(stacks).items():
                folded[key] = folded.get(key, 0) + count
        samples += 1
        delay = next_tick - _time.monotonic()
        if delay > 0:
            _time.sleep(delay)
            next_tick += interval
        else:
            next_tick = _time.monotonic() + interval  # overran: no burst
    elapsed = max(_time.monotonic() - start, 1e-9)
    top = sorted(by_frame.items(), key=lambda kv: -kv[1])[:50]
    total_stacks = max(sum(by_thread.values()), 1)
    out = {
        "seconds": seconds,
        "samples": samples,
        # sleep/walk overhead means the requested hz is an upper bound;
        # report what the window actually achieved so pct math is honest
        "achievedHz": round(samples / elapsed, 1),
        "threads": dict(sorted(by_thread.items(), key=lambda kv: -kv[1])),
        # pct = share of all sampled thread-stacks that contain the frame
        "hot_frames": [
            {"frame": k, "samples": v,
             "pct": round(100.0 * v / total_stacks, 1)}
            for k, v in top
        ],
    }
    if fold:
        out["folded"] = folded
    return out
