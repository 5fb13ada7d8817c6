"""Long-poll job activation at the gateway: parked ActivateJobs calls on a
pool of their own, one wake per jobs-available notification and tenant
filter, first parked first woken (reference: gateway
impl/job/LongPollingActivateJobsHandler.java:36 hands a notification to the
job type's next pending request)."""

from __future__ import annotations

import collections
import threading
import time

import pytest

from zeebe_tpu.client import ZeebeTpuClient
from zeebe_tpu.gateway import ClusterRuntime, Gateway
from zeebe_tpu.gateway.auth import GatewayAuthConfig, TenantAuthorizer
from zeebe_tpu.gateway.jobstream import JobNotificationHub
from zeebe_tpu.models.bpmn import Bpmn, to_bpmn_xml
from zeebe_tpu.protocol import DEFAULT_TENANT, ValueType
from zeebe_tpu.protocol.keys import decode_partition_id


def one_task(pid, job_type):
    return to_bpmn_xml(
        Bpmn.create_executable_process(pid)
        .start_event("s").service_task("t", job_type=job_type).end_event("e").done()
    )


@pytest.fixture(scope="module")
def stack():
    runtime = ClusterRuntime(broker_count=1, partition_count=3,
                             replication_factor=1)
    runtime.start()
    gateway = Gateway(runtime)
    gateway.start()
    from zeebe_tpu.testing import distributing_client

    client = distributing_client(ZeebeTpuClient(gateway.address), runtime)
    yield client, runtime
    client.close()
    gateway.stop()
    runtime.stop()


def queued(hub, job_type: str) -> int:
    return sum(map(len, hub._parked.get(job_type, {}).values()))


def parked(runtime, job_type: str) -> int:
    return queued(runtime.jobs_hub, job_type)


def wait_until(predicate, timeout_s: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


class Polls:
    """``n`` long-polls of one job type, each on a thread of its own, whose
    calls can be cancelled together."""

    def __init__(self, client, job_type: str, n: int, request_timeout_ms: int,
                 tenant_ids: list[str] | None = None):
        self.results: list = []
        self.calls: list = []
        self._lock = threading.Lock()

        def poll():
            jobs = client.activate_jobs(
                job_type, max_jobs=4, request_timeout_ms=request_timeout_ms,
                tenant_ids=tenant_ids, on_call=self._track)
            with self._lock:
                self.results.append(jobs)

        self.threads = [threading.Thread(target=poll, daemon=True)
                        for _ in range(n)]
        for t in self.threads:
            t.start()

    def _track(self, call) -> None:
        with self._lock:
            self.calls.append(call)

    def cancel(self) -> None:
        with self._lock:
            calls = list(self.calls)
        for call in calls:
            call.cancel()
        for t in self.threads:
            t.join(timeout=5)


def counting_job_batches(monkeypatch, runtime, job_type: str) -> list:
    """Every JOB_BATCH command written for the type, by partition."""
    written, real = [], runtime.submit

    def submit(partition_id, record, **kw):
        if (record.value_type == ValueType.JOB_BATCH
                and record.value.get("type") == job_type):
            written.append(partition_id)
        return real(partition_id, record, **kw)

    monkeypatch.setattr(runtime, "submit", submit)
    return written


def recording_peeks(monkeypatch, runtime, job_type: str) -> list:
    seen, real = [], runtime.has_activatable_jobs

    def peek(partition_id, jt, tenant_ids=None):
        if jt == job_type:
            seen.append(partition_id)
        return real(partition_id, jt, tenant_ids)

    monkeypatch.setattr(runtime, "has_activatable_jobs", peek)
    return seen


def test_one_job_wakes_one_of_eight_parked_polls(stack, monkeypatch):
    client, runtime = stack
    client.deploy_resource(("lp8.bpmn", one_task("lp8", "lp8_work")))
    written = counting_job_batches(monkeypatch, runtime, "lp8_work")
    polls = Polls(client, "lp8_work", 8, request_timeout_ms=30_000)
    try:
        assert wait_until(lambda: parked(runtime, "lp8_work") == 8)
        client.create_instance("lp8")
        assert wait_until(lambda: len(polls.results) == 1)
        [jobs] = polls.results
        assert len(jobs) == 1
        time.sleep(0.2)     # the seven others stay parked
        assert parked(runtime, "lp8_work") == 7
        assert written == [decode_partition_id(jobs[0].key)]
    finally:
        polls.cancel()
    client.complete_job(jobs[0].key, {})


def test_a_notification_between_the_peek_and_the_park_is_not_lost(
        stack, monkeypatch):
    client, runtime = stack
    client.deploy_resource(("lpr.bpmn", one_task("lpr", "lpr_work")))
    hub, real = runtime.jobs_hub, runtime.has_activatable_jobs
    last = runtime.partition_count
    creator = ZeebeTpuClient(client.address)
    first = [True]

    def peek(partition_id, job_type, tenant_ids=None):
        found = real(partition_id, job_type, tenant_ids)
        if job_type == "lpr_work" and partition_id == last and first[0]:
            # the sweep has peeked every partition and found nothing: the
            # job is made and notified before the poll parks
            first[0] = False
            version = hub.version(job_type)
            creator.create_instance("lpr")
            assert wait_until(lambda: hub.version(job_type) > version)
        return found

    monkeypatch.setattr(runtime, "has_activatable_jobs", peek)
    try:
        started = time.monotonic()
        jobs = client.activate_jobs("lpr_work", request_timeout_ms=30_000)
        took = time.monotonic() - started
    finally:
        creator.close()
    assert not first[0]
    assert len(jobs) == 1 and took < 15.0
    client.complete_job(jobs[0].key, {})


def test_parked_polls_leave_the_unary_rpcs_their_threads(stack):
    """More parked polls than the gateway's sixteen unary handler threads:
    a create and a completion still answer."""
    client, runtime = stack
    client.deploy_resource(("lpu.bpmn", one_task("lpu", "lpu_work")))
    polls = Polls(client, "lpu_idle", 20, request_timeout_ms=30_000)
    try:
        assert wait_until(lambda: parked(runtime, "lpu_idle") == 20)
        started = time.monotonic()
        client.create_instance("lpu")
        [job] = client.activate_jobs("lpu_work", request_timeout_ms=5_000)
        client.complete_job(job.key, {})
        assert time.monotonic() - started < 5.0
        assert parked(runtime, "lpu_idle") == 20
    finally:
        polls.cancel()
    assert polls.results == [[]] * 20
    assert wait_until(lambda: parked(runtime, "lpu_idle") == 0, 2.0)


def test_request_timeout_zero_answers_at_once(stack):
    client, runtime = stack
    started = time.monotonic()
    assert client.activate_jobs("lp0_nothing") == []
    assert time.monotonic() - started < 2.0
    assert parked(runtime, "lp0_nothing") == 0


def test_a_parked_poll_answers_empty_at_its_timeout(stack):
    client, runtime = stack
    started = time.monotonic()
    assert client.activate_jobs("lpt_nothing", request_timeout_ms=400) == []
    took = time.monotonic() - started
    assert 0.4 <= took < 5.0
    assert parked(runtime, "lpt_nothing") == 0


def test_a_woken_poll_starts_its_fan_out_at_the_partition_that_notified(
        stack, monkeypatch):
    client, runtime = stack
    client.deploy_resource(("lpf.bpmn", one_task("lpf", "lpf_work")))
    last = runtime.partition_count
    monkeypatch.setattr(runtime, "partition_for_new_instance", lambda: last)
    peeks = recording_peeks(monkeypatch, runtime, "lpf_work")
    polls = Polls(client, "lpf_work", 1, request_timeout_ms=30_000)
    try:
        assert wait_until(lambda: parked(runtime, "lpf_work") == 1)
        assert peeks == [1, 2, 3]       # the first sweep, before parking
        client.create_instance("lpf")
        assert wait_until(lambda: len(polls.results) == 1)
    finally:
        polls.cancel()
    [[job]] = polls.results
    assert decode_partition_id(job.key) == last
    # woken by the last partition, it peeked there first and stopped there
    assert peeks == [1, 2, 3, last]
    client.complete_job(job.key, {})


def test_a_notification_of_a_partition_that_cannot_be_peeked_stays_with_the_poll(
        stack, monkeypatch):
    """The partition that notified answers its first two peeks with "cannot
    tell" (no leader, or its lock stalled): the woken poll keeps the
    notification and looks again, and gets the job well before its timeout."""
    client, runtime = stack
    client.deploy_resource(("lph.bpmn", one_task("lph", "lph_work")))
    last = runtime.partition_count
    monkeypatch.setattr(runtime, "partition_for_new_instance", lambda: last)
    real, unknown, peeks = runtime.has_activatable_jobs, [0], []

    def peek(partition_id, job_type, tenant_ids=None):
        if job_type == "lph_work" and partition_id == last and unknown[0]:
            unknown[0] -= 1
            peeks.append(None)
            return None
        return real(partition_id, job_type, tenant_ids)

    monkeypatch.setattr(runtime, "has_activatable_jobs", peek)
    polls = Polls(client, "lph_work", 1, request_timeout_ms=30_000)
    try:
        assert wait_until(lambda: parked(runtime, "lph_work") == 1)
        unknown[0] = 2
        started = time.monotonic()
        client.create_instance("lph")
        assert wait_until(lambda: len(polls.results) == 1, 5.0)
        took = time.monotonic() - started
    finally:
        polls.cancel()
    [[job]] = polls.results
    assert peeks == [None, None] and took < 2.0
    client.complete_job(job.key, {})


def test_a_call_that_ends_right_after_its_wake_hands_the_wake_on(
        stack, monkeypatch):
    """The first poll is handed the notification and its call ends before
    it acts on it: the second poll gets the job, not its timeout."""
    client, runtime = stack
    client.deploy_resource(("lpe.bpmn", one_task("lpe", "lpe_work")))
    hub = runtime.jobs_hub
    real, first = hub.wait, []

    def wait(waiter, seen_version, timeout_s, front=False):
        if not first:
            first.append(waiter)
        partition_id = real(waiter, seen_version, timeout_s, front=front)
        if partition_id is not None and waiter is first[0] and len(first) == 1:
            first.append(partition_id)
            ending.cancel()
            assert wait_until(lambda: waiter.cancelled, 5.0)
        return partition_id

    monkeypatch.setattr(hub, "wait", wait)
    ending = Polls(client, "lpe_work", 1, request_timeout_ms=30_000)
    assert wait_until(lambda: parked(runtime, "lpe_work") == 1)
    other = Polls(client, "lpe_work", 1, request_timeout_ms=30_000)
    try:
        assert wait_until(lambda: parked(runtime, "lpe_work") == 2)
        [call] = ending.calls
        ending.cancel = call.cancel
        started = time.monotonic()
        client.create_instance("lpe")
        assert wait_until(lambda: len(other.results) == 1, 5.0)
        took = time.monotonic() - started
    finally:
        other.cancel()
    [[job]] = other.results
    assert len(first) == 2 and took < 2.0
    client.complete_job(job.key, {})


@pytest.fixture(scope="module")
def tenants_stack():
    runtime = ClusterRuntime(broker_count=1, partition_count=1)
    runtime.start()
    gateway = Gateway(runtime, auth=TenantAuthorizer(GatewayAuthConfig(
        multi_tenancy_enabled=True,
        token_tenants={"token-ab": ["tenant-a", "tenant-b", DEFAULT_TENANT]},
        anonymous_tenants=[DEFAULT_TENANT],
    )))
    gateway.start()
    client = ZeebeTpuClient(gateway.address, access_token="token-ab")
    yield client, runtime
    client.close()
    gateway.stop()
    runtime.stop()


def test_a_job_of_a_tenant_reaches_its_poll_behind_another_tenants(
        tenants_stack):
    """A notification names no tenant: the first parked poll of each tenant
    filter is woken, so tenant-a's poll, parked first, does not spend the
    wake that tenant-b's job needs."""
    client, runtime = tenants_stack
    client.deploy_resource(("lpt.bpmn", one_task("lpt", "lpt_work")),
                           tenant_id="tenant-b")
    first = Polls(client, "lpt_work", 1, 30_000, tenant_ids=["tenant-a"])
    assert wait_until(lambda: parked(runtime, "lpt_work") == 1)
    second = Polls(client, "lpt_work", 1, 30_000, tenant_ids=["tenant-b"])
    try:
        assert wait_until(lambda: parked(runtime, "lpt_work") == 2)
        started = time.monotonic()
        client.create_instance("lpt", tenant_id="tenant-b")
        assert wait_until(lambda: len(second.results) == 1, 5.0)
        took = time.monotonic() - started
        # tenant-a's poll found nothing and parked again
        assert wait_until(lambda: parked(runtime, "lpt_work") == 1)
        assert first.results == []
    finally:
        first.cancel()
        second.cancel()
    [[job]] = second.results
    assert took < 1.0
    client.complete_job(job.key, {})


T = (DEFAULT_TENANT,)


class TestHubOrder:
    """The hub alone: first parked, first woken; a woken poll that found
    nothing goes back to the head; a cancelled one hands its wake on."""

    @staticmethod
    def park(hub, waiter, seen, out, front=False, timeout_s=5.0):
        t = threading.Thread(target=lambda: out.append(
            (waiter, hub.wait(waiter, seen, timeout_s, front=front))))
        t.start()
        return t

    def test_first_parked_first_woken(self):
        hub, out = JobNotificationHub(), []
        a, b = hub.waiter("t", T), hub.waiter("t", T)
        ta = self.park(hub, a, hub.version("t"), out)
        assert wait_until(lambda: queued(hub, "t") == 1)
        tb = self.park(hub, b, hub.version("t"), out)
        assert wait_until(lambda: queued(hub, "t") == 2)
        hub.notify({"t"}, 3)
        ta.join(2)
        assert out == [(a, 3)]
        hub.notify({"t"}, 1)
        tb.join(2)
        assert out == [(a, 3), (b, 1)]

    def test_a_woken_poll_that_found_nothing_goes_back_to_the_head(self):
        hub, out = JobNotificationHub(), []
        a, b = hub.waiter("t", T), hub.waiter("t", T)
        tb = self.park(hub, b, hub.version("t"), out)
        assert wait_until(lambda: queued(hub, "t") == 1)
        ta = self.park(hub, a, hub.version("t"), out, front=True)
        assert wait_until(lambda: queued(hub, "t") == 2)
        hub.notify({"t"}, 2)
        ta.join(2)
        assert out == [(a, 2)]
        b.cancel()
        tb.join(2)

    def test_a_cancelled_poll_hands_its_wake_on(self):
        hub, out = JobNotificationHub(), []
        a, b = hub.waiter("t", T), hub.waiter("t", T)
        seen = hub.version("t")
        # a is handed the notification, then cancelled before it took it
        hub._parked["t"] = {T: collections.deque([a])}
        a._queued = True
        hub.notify({"t"}, 2)
        a.cancel()
        assert hub.wait(a, seen, 0.1) is None
        # the kept notification goes to the next poll to park, at once
        assert hub.wait(b, seen, 5.0) == 2

    def test_a_cancelled_parked_poll_leaves_the_queue(self):
        hub, out = JobNotificationHub(), []
        a, b = hub.waiter("t", T), hub.waiter("t", T)
        ta = self.park(hub, a, hub.version("t"), out)
        assert wait_until(lambda: queued(hub, "t") == 1)
        tb = self.park(hub, b, hub.version("t"), out)
        assert wait_until(lambda: queued(hub, "t") == 2)
        a.cancel()
        ta.join(2)
        hub.notify({"t"}, 1)
        tb.join(2)
        assert out == [(a, None), (b, 1)]

    def test_a_kept_notification_older_than_the_peek_is_dropped(self):
        hub = JobNotificationHub()
        hub.notify({"t"}, 1)
        seen = hub.version("t")     # the peek came after the notification
        assert hub.wait(hub.waiter("t", T), seen, 0.05) is None

    def test_every_notification_wakes_exactly_one_poll_under_contention(self):
        """64 polls parking again and again while 2,000 notifications, each
        of a partition of its own, arrive from four threads: each is handed
        to exactly one poll, parked or about to park, none twice."""
        import sys

        hub, woken, lock = JobNotificationHub(), [], threading.Lock()
        total, done = 2_000, threading.Event()
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            def poll():
                waiter = hub.waiter("t", T)
                while not done.is_set():
                    # seen -1: claim any kept notification
                    partition_id = hub.wait(waiter, -1, 0.05)
                    if partition_id is not None:
                        with lock:
                            woken.append(partition_id)

            def notify(first):
                for p in range(first, total + 1, 4):
                    hub.notify({"t"}, p)

            polls = [threading.Thread(target=poll) for _ in range(64)]
            notifiers = [threading.Thread(target=notify, args=(i,))
                         for i in range(1, 5)]
            for t in polls + notifiers:
                t.start()
            for t in notifiers:
                t.join(10)
            assert wait_until(lambda: len(woken) >= total, 10)
            done.set()
            for t in polls:
                t.join(5)
            assert not any(t.is_alive() for t in polls + notifiers)
        finally:
            sys.setswitchinterval(interval)
        assert sorted(woken) == list(range(1, total + 1))

    def test_a_notification_wakes_the_first_poll_of_each_tenant_filter(self):
        hub, out = JobNotificationHub(), []
        a1, a2 = hub.waiter("t", ("a",)), hub.waiter("t", ("a",))
        b = hub.waiter("t", ("b",))
        threads = []
        for waiter in (a1, a2, b):
            threads.append(self.park(hub, waiter, hub.version("t"), out))
            assert wait_until(lambda: queued(hub, "t") == len(threads))
        hub.notify({"t"}, 1)
        assert wait_until(lambda: len(out) == 2)
        assert sorted(out, key=lambda o: o[0].tenants) == [(a1, 1), (b, 1)]
        assert queued(hub, "t") == 1
        # a poll of filter a hands it on to the next of filter a alone
        hub.hand_on("t", ("a",), 1)
        assert wait_until(lambda: len(out) == 3)
        assert out[2] == (a2, 1)
        for t in threads:
            t.join(2)

    def test_a_kept_notification_is_taken_once_by_each_tenant_filter(self):
        hub = JobNotificationHub()
        seen = hub.version("t")
        hub.notify({"t"}, 2)        # no poll parked
        assert hub.wait(hub.waiter("t", ("a",)), seen, 5.0) == 2
        assert hub.wait(hub.waiter("t", ("b",)), seen, 5.0) == 2
        assert hub.wait(hub.waiter("t", ("a",)), seen, 0.05) is None
