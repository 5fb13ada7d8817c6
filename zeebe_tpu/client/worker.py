"""JobWorker: poll/stream jobs, dispatch to a handler, complete or fail.

Reference: clients/java/…/worker/JobWorker + JobWorkerBuilderStep1 (poller +
streamer, exponential poll backoff, maxJobsActive flow control), and the Go
worker (clients/go/pkg/worker/jobPoller.go, jobDispatcher.go).
"""

from __future__ import annotations

import queue
import threading
import time
import traceback
from typing import Callable

from zeebe_tpu.client.client import ActivatedJob, ZeebeTpuClient

Handler = Callable[["JobClient", ActivatedJob], None]


class JobClient:
    """Handed to handlers: complete/fail/throw for the current job."""

    def __init__(self, client: ZeebeTpuClient) -> None:
        self._client = client

    def complete(self, job: ActivatedJob, variables: dict | None = None) -> None:
        self._client.complete_job(job.key, variables)

    def fail(self, job: ActivatedJob, retries: int | None = None,
             error_message: str = "", retry_back_off_ms: int = 0) -> None:
        self._client.fail_job(
            job.key, job.retries - 1 if retries is None else retries,
            error_message, retry_back_off_ms,
        )

    def throw_error(self, job: ActivatedJob, error_code: str,
                    error_message: str = "") -> None:
        self._client.throw_error(job.key, error_code, error_message)


class JobWorker:
    """Background long-polling worker.

    Every poll asks the gateway to park it for up to ``REQUEST_TIMEOUT_MS``
    until a job of the type is there; an answer that comes back empty at that
    timeout polls again at once. A refused or failed poll backs off,
    ``poll_interval_s`` doubling up to ``max_backoff_s``.

    Jobs are handled on ``HANDLER_THREADS`` threads of the worker's own
    (reference: the Go worker's default ``Concurrency``; the Java client's
    ``numJobWorkerExecutionThreads``), so a job does not wait behind the
    handler of another job of the same activation; the poller asks for as
    many jobs as ``max_jobs_active`` leaves room for and polls again at once
    when a job was finished.

    ``auto_complete``: a handler return (no exception) completes the job with
    the handler's returned dict (or {}); an exception fails it with
    retries-1 (the Java client's default error behavior).

    ``stream_enabled``: use the StreamActivatedJobs push path instead of the
    ActivateJobs poll loop (reference: JobWorkerBuilderStep1.streamEnabled —
    jobs arrive as the broker creates them, no polling)."""

    HANDLER_THREADS = 4
    #: how long a poll may park at the gateway (reference: the Java client's
    #: default request timeout and the Go worker's, both long-polling)
    REQUEST_TIMEOUT_MS = 10_000

    def __init__(
        self,
        client: ZeebeTpuClient,
        job_type: str,
        handler: Handler | Callable[[ActivatedJob], dict | None],
        worker_name: str = "python-worker",
        max_jobs_active: int = 32,
        timeout_ms: int = 300_000,
        poll_interval_s: float = 0.05,
        max_backoff_s: float = 1.0,
        auto_complete: bool = True,
        stream_enabled: bool = False,
    ) -> None:
        self.client = client
        self.job_type = job_type
        self.handler = handler
        self.worker_name = worker_name
        self.max_jobs_active = max_jobs_active
        self.timeout_ms = timeout_ms
        self.poll_interval_s = poll_interval_s
        self.max_backoff_s = max_backoff_s
        self.auto_complete = auto_complete
        self.stream_enabled = stream_enabled
        self._running = False
        self._thread: threading.Thread | None = None
        self._call = None       # the poll or stream in flight, to cancel
        self._handlers: list[threading.Thread] = []
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        # guards the three counts; notified whenever a job was finished
        self._finished = threading.Condition()
        self._active = 0        # activated for this worker, not yet finished
        self.handled_count = 0
        self.failed_count = 0

    def start(self) -> "JobWorker":
        self._running = True
        target = self._stream_loop if self.stream_enabled else self._poll_loop
        self._thread = threading.Thread(target=target, daemon=True,
                                        name=f"worker-{self.job_type}")
        self._handlers = [
            threading.Thread(target=self._handler_loop, daemon=True,
                             name=f"worker-{self.job_type}-handler-{i}")
            for i in range(self.HANDLER_THREADS)]
        for t in (self._thread, *self._handlers):
            t.start()
        return self

    def stop(self) -> None:
        """A parked poll or an open stream is cancelled; jobs a handler has
        in hand are finished (waited for up to 5 s); jobs still queued are
        left to their activation timeout."""
        with self._finished:
            self._running = False
            call = self._call
            self._finished.notify_all()
        if call is not None:
            call.cancel()
        for _ in self._handlers:
            self._jobs.put(None)
        deadline = time.monotonic() + 5
        for t in (self._thread, *self._handlers):
            if t is not None:
                t.join(timeout=max(0.0, deadline - time.monotonic()))

    def _poll_loop(self) -> None:
        backoff = self.poll_interval_s
        while self._running:
            with self._finished:
                room = self.max_jobs_active - self._active
                if room <= 0:
                    self._finished.wait(self.max_backoff_s)
                    continue
            try:
                jobs = self.client.activate_jobs(
                    self.job_type, max_jobs=room,
                    worker=self.worker_name, timeout_ms=self.timeout_ms,
                    request_timeout_ms=self.REQUEST_TIMEOUT_MS,
                    on_call=self._track,
                )
            except Exception:
                with self._finished:
                    self._finished.wait_for(lambda: not self._running, backoff)
                backoff = min(backoff * 2, self.max_backoff_s)
                continue
            backoff = self.poll_interval_s
            self._accept(jobs)

    def _track(self, call) -> None:
        """The poll's call, before it parks: ``stop`` cancels it."""
        with self._finished:
            self._call = call
            if not self._running:
                call.cancel()

    def _accept(self, jobs) -> None:
        with self._finished:
            self._active += len(jobs)
        for job in jobs:
            self._jobs.put(job)

    def _handler_loop(self) -> None:
        job_client = JobClient(self.client)
        while (job := self._jobs.get()) is not None:
            if self._running:
                self._dispatch(job_client, job)
            with self._finished:
                self._active -= 1
                self._finished.notify_all()

    def _stream_loop(self) -> None:
        while self._running:
            try:
                self._call, jobs = self.client.open_job_stream(
                    self.job_type, worker=self.worker_name,
                    timeout_ms=self.timeout_ms,
                )
                if not self._running:
                    # stop() raced the reconnect: its cancel hit the old call
                    self._call.cancel()
                    return
                for job in jobs:
                    if not self._running:
                        return
                    self._accept([job])
            except Exception:
                if not self._running:
                    return
                time.sleep(self.poll_interval_s)

    def _dispatch(self, job_client: JobClient, job: ActivatedJob) -> None:
        try:
            if self.auto_complete:
                result = self.handler(job)
                job_client.complete(job, result if isinstance(result, dict) else {})
            else:
                self.handler(job_client, job)
            with self._finished:
                self.handled_count += 1
        except Exception as exc:  # handler error → fail with retries-1
            with self._finished:
                self.failed_count += 1
            try:
                job_client.fail(job, error_message=(
                    f"{type(exc).__name__}: {exc}\n{traceback.format_exc(limit=5)}"
                ))
            except Exception:
                pass
