"""The joint flush (ISSUE 36): a partition's co-located replicas sync their
Raft journals together on the ownership thread, where each took its barrier
inside its own append handler before. Every case that can runs twice, over
{inline, joint}: what a replica guarantees must not depend on who takes its
barrier. Raft-level cases drive the raw ``Cluster`` harness of
``test_raft.py`` with an owner (controlled clock, one thread); the
``ClusterRuntime`` cases run the real ownership threads."""

from __future__ import annotations

import errno
import threading
import time
from pathlib import Path

import pytest

from tests.test_broker_cluster import create_cmd
from tests.test_raft import Cluster
from zeebe_tpu.cluster import RaftNode, RaftRole
from zeebe_tpu.cluster.raft import (
    ELECTION_TIMEOUT_MS,
    HEARTBEAT_INTERVAL_MS,
    JointFlusher,
)
from zeebe_tpu.utils import storage_io
from zeebe_tpu.utils.metrics import REGISTRY

MODES = ("inline", "joint")


class Owned(Cluster):
    """The raw harness with an owner, as ``ClusterRuntime._run_partition``
    is one: a turn settles (deliver, sync the dirty journals together,
    deliver the released answers), ticks every node, and settles again.
    ``joint=False`` is the same loop with nobody registering the nodes."""

    def __init__(self, tmp_path, joint: bool, n: int = 3) -> None:
        super().__init__(tmp_path, n=n)
        self.flusher = JointFlusher("test") if joint else None

    def settle(self) -> None:
        self.net.deliver_all()
        if self.flusher is not None and self.flusher.flush(
                list(self.nodes.values())):
            self.net.deliver_all()

    def run(self, millis: int, step: int = 50) -> None:
        for _ in range(millis // step):
            self.clock.advance(step)
            self.settle()
            for node in self.nodes.values():
                node.tick()
            self.settle()

    def close(self) -> None:
        if self.flusher is not None:
            self.flusher.close()
        super().close()


@pytest.fixture(params=MODES)
def owned(request, tmp_path):
    c = Owned(tmp_path, joint=request.param == "joint")
    c.mode = request.param
    yield c
    c.close()


@pytest.fixture()
def joint(tmp_path):
    c = Owned(tmp_path, joint=True)
    yield c
    c.close()


def _member_of(path) -> str:
    """The replica a raft journal file belongs to: the directory above its
    ``raft-log`` (raw harness) or the broker's (``ClusterRuntime``)."""
    parts = Path(path).parts
    named = [p for p in parts if p.startswith(("node-", "broker-"))]
    return named[-1] if named else parts[-3]


@pytest.fixture()
def fsyncs(monkeypatch):
    """Every ``storage_io.fsync`` of a raft journal as (replica, thread
    name), in call order; ``fsyncs.fail.add(replica)`` plants one ``EIO``
    in that replica's next fsync."""
    real = storage_io.fsync

    class Calls(list):
        fail: set = set()

        def of(self, member) -> int:
            return sum(1 for m, _ in self if m == member)

    calls = Calls()

    def counted(fd, path=None):
        if path is not None and "raft-log" in str(path):
            member = _member_of(path)
            calls.append((member, threading.current_thread().name))
            if member in calls.fail:
                calls.fail.discard(member)
                raise OSError(errno.EIO, f"planted fsync failure on {path}")
        real(fd, path)

    monkeypatch.setattr(storage_io, "fsync", counted)
    return calls


def hook_durability(nodes, violations: list, sent: list) -> None:
    """The checks of ``test_raft._durability_run``: no successful
    ``append-resp`` and no leader commit ever names an index above what an
    fsync of that node covered (the journal writes its marker after the
    fsync returned). Violations are recorded, not raised: a handler's
    assertion would be swallowed by the network's guard."""
    for node in nodes:
        def checked_send(member, suffix, payload, node=node, orig=node._send):
            if suffix == "append-resp":
                sent.append((node.member_id, payload["success"],
                             payload["lastIndex"]))
                if payload["success"] and payload["lastIndex"] > max(
                        node.journal.last_flushed_index, node.snapshot_index):
                    violations.append(("ack", node.member_id, dict(payload)))
            orig(member, suffix, payload)
        node._send = checked_send

        def checked_commit(index, node=node, orig=node._set_commit):
            if (node.role == RaftRole.LEADER
                    and index > node.journal.last_flushed_index):
                violations.append(("commit", node.member_id, index))
            orig(index)
        node._set_commit = checked_commit


def log_bytes(node) -> bytes:
    return b"".join(p.read_bytes() for p in sorted(
        (node.directory / "raft-log").glob("journal-*.log")))


def pass_widths(partition: str) -> tuple[int, float]:
    child = REGISTRY.histogram(
        "stream_processor_pipeline_flush_pass", "", ("partition",)
    ).labels(partition)
    return child.count, child.sum


class Recorder:
    """Stands in for one node's histogram child: what that node observed."""

    def __init__(self) -> None:
        self.values: list[float] = []

    def observe(self, value: float) -> None:
        self.values.append(value)


# -- raft level ---------------------------------------------------------------


class TestBarrierBeforeAck:
    def test_twenty_serial_appends(self, owned, fsyncs):
        """No acknowledgement and no leader commit above the flushed index;
        twenty serial appends cost twenty fsyncs a replica; the three logs
        are byte-equal; and who took the barriers is what the mode says."""
        violations, sent = [], []
        hook_durability(owned.nodes.values(), violations, sent)
        leader = owned.elect()
        base = {m: fsyncs.of(m) for m in owned.nodes}
        passes0, width0 = pass_widths("1")
        committed = []
        for i in range(20):
            index = leader.append(b"%03d" % i * 300, asqn=i + 1,
                                  on_commit=committed.append)
            assert index is not None
            owned.run(HEARTBEAT_INTERVAL_MS)
            assert committed[-1] == index
        assert not violations, violations[:3]
        assert {m: fsyncs.of(m) - base[m] for m in owned.nodes} == \
            {m: 20 for m in owned.nodes}
        for node in owned.nodes.values():
            assert node._flushed_index == node.journal.last_index
            assert node.commit_index == leader.commit_index or \
                node.commit_index >= leader.commit_index - 1
        owned.run(HEARTBEAT_INTERVAL_MS)
        logs = [log_bytes(n) for n in owned.nodes.values()]
        assert logs[0] == logs[1] == logs[2] and len(logs[0]) > 20 * 900
        passes, width = pass_widths("1")
        if owned.mode == "joint":
            # one barrier an entry for the whole partition, three wide, two
            # of the three fsyncs off the owner's thread
            assert passes - passes0 == 20 and width - width0 == 60
            assert {t for _, t in fsyncs} >= {"MainThread", "test-sync-1",
                                              "test-sync-2"}
        else:
            assert passes - passes0 == 60 == width - width0
            assert {t for _, t in fsyncs} == {"MainThread"}

    def test_both_modes_write_the_same_bytes(self, tmp_path, fsyncs):
        logs = {}
        for mode in MODES:
            c = Owned(tmp_path / mode, joint=mode == "joint")
            try:
                leader = c.elect()
                for i in range(10):
                    leader.append(bytes([i]) * 2000, asqn=i + 1)
                    c.run(HEARTBEAT_INTERVAL_MS)
                c.run(HEARTBEAT_INTERVAL_MS)
                logs[mode] = {m: log_bytes(n) for m, n in c.nodes.items()}
            finally:
                c.close()
        assert logs["inline"] == logs["joint"]

    def test_two_appends_before_a_pass_are_both_synced_before_the_ack(
            self, joint, fsyncs):
        violations, sent = [], []
        hook_durability(joint.nodes.values(), violations, sent)
        leader = joint.elect()
        first = leader.append(b"a" * 100, asqn=1)
        second = leader.append(b"b" * 100, asqn=2)
        assert leader.commit_index < first
        assert leader._flush_dirty and leader._flushed_index < first
        joint.settle()
        assert leader.commit_index == second
        assert not violations
        for node in joint.nodes.values():
            assert node.journal.last_flushed_index == second


    def test_passes_under_a_short_switch_interval(self, joint, fsyncs):
        """The owner and its helpers hand each journal's file work over and
        back two hundred times with the interpreter switching threads as
        often as it can: every entry commits, each replica synced each
        entry once, nothing was acknowledged early, no helper is left
        holding work."""
        import sys

        violations, sent = [], []
        hook_durability(joint.nodes.values(), violations, sent)
        leader = joint.elect()
        base = {m: fsyncs.of(m) for m in joint.nodes}
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            deadline = time.time() + 60
            for i in range(200):
                index = leader.append(b"%d" % i * 50, asqn=i + 1)
                joint.settle()
                assert leader.commit_index == index
                assert time.time() < deadline
        finally:
            sys.setswitchinterval(interval)
        assert not violations
        assert {m: fsyncs.of(m) - base[m] for m in joint.nodes} == \
            {m: 200 for m in joint.nodes}
        helpers = list(joint.flusher._helpers)
        assert len(helpers) == 2
        for helper in helpers:
            assert helper.is_alive()
            assert helper.inbox.empty() and helper.done.empty()
        joint.flusher.close()
        for helper in helpers:
            helper.join(timeout=5)
            assert not helper.is_alive()


class TestWhoDefers:
    def test_a_node_nobody_registered_keeps_the_inline_barrier(self, tmp_path):
        c = Cluster(tmp_path)
        try:
            leader = c.elect()
            assert not any(n.joint_flush for n in c.nodes.values())
            index = leader.append(b"x", asqn=1)
            assert leader._flushed_index == index and not leader._flush_dirty
        finally:
            c.close()

    def test_a_quorum_of_one_syncs_and_commits_inside_append(
            self, tmp_path, fsyncs):
        c = Owned(tmp_path, joint=True, n=1)
        try:
            node = c.elect()
            assert node.joint_flush  # registered, and still never defers
            before = len(fsyncs)
            passes0, width0 = pass_widths("1")
            index = node.append(b"x" * 10, asqn=1)
            assert node.commit_index == index == node._flushed_index
            assert not node._flush_dirty
            assert fsyncs[before:] == [("node-0", "MainThread")]
            passes, width = pass_widths("1")
            assert (passes - passes0, width - width0) == (1, 1)
        finally:
            c.close()

    def test_leader_sends_before_it_syncs(self, joint, fsyncs):
        leader = joint.elect()
        before = len(fsyncs)
        index = leader.append(b"x" * 10, asqn=1)
        assert len(fsyncs) == before  # nothing synced yet
        assert any(topic.endswith("-append") and payload["entries"]
                   for _, _, topic, payload in joint.net.queue)
        assert leader._ack_index() < index  # its own vote waits

    def test_heartbeat_is_answered_at_once(self, joint):
        violations, sent = [], []
        leader = joint.elect()
        hook_durability(joint.nodes.values(), violations, sent)
        joint.clock.advance(HEARTBEAT_INTERVAL_MS)
        leader.tick()
        joint.net.deliver_all()  # no pass of the flusher
        followers = {m for m in joint.nodes if m != leader.member_id}
        assert {m for m, ok, _ in sent if ok} == followers
        assert not violations

    def test_heartbeat_on_a_dirty_follower_syncs_inline_and_answers(
            self, joint, fsyncs):
        violations, sent = [], []
        leader = joint.elect()
        hook_durability(joint.nodes.values(), violations, sent)
        index = leader.append(b"x" * 10, asqn=1)
        joint.net.deliver_all()  # followers append and hold their answers
        followers = [n for n in joint.nodes.values() if n is not leader]
        assert all(f._flush_dirty and f._held_answer for f in followers)
        assert not [s for s in sent if s[1]]
        for f in followers:  # a request with no entries, before any pass
            f._on_append_request(leader.member_id, {
                "term": leader.current_term, "leader": leader.member_id,
                "prevIndex": index, "prevTerm": leader.current_term,
                "entries": [], "commit": leader.commit_index})
        assert all(not f._flush_dirty and f._flushed_index == index
                   for f in followers)
        assert {(m, i) for m, ok, i in sent if ok} >= {
            (f.member_id, index) for f in followers}
        assert not violations

    def test_a_request_that_cuts_the_log_keeps_the_inline_barrier(
            self, joint):
        violations, sent = [], []
        hook_durability(joint.nodes.values(), violations, sent)
        cut_and_held = []
        for node in joint.nodes.values():
            def watched(sender, req, node=node, orig=node._on_append_request):
                orig(sender, req)
                if node._log_cut and (node._flush_dirty or node._held_answer):
                    cut_and_held.append(node.member_id)
            node._on_append_request = watched
            topic = f"raft-{node.partition_id}-append"
            node.messaging.subscribe(topic, watched)
        old = joint.elect()
        joint.net.isolate(old.member_id)
        for i in range(3):
            old.append(b"old-%d" % i, asqn=i + 1)  # never commits
        joint.run(4 * ELECTION_TIMEOUT_MS)
        new = next(n for n in joint.nodes.values()
                   if n.role == RaftRole.LEADER and n is not old)
        new.append(b"new", asqn=1)
        joint.run(HEARTBEAT_INTERVAL_MS)
        joint.net.heal()
        joint.run(4 * HEARTBEAT_INTERVAL_MS)
        assert old.role == RaftRole.FOLLOWER
        assert not cut_and_held and not violations
        logs = [log_bytes(n) for n in joint.nodes.values()]
        assert logs[0] == logs[1] == logs[2]

    def test_a_deposed_leader_owes_nobody_an_answer(self, joint):
        """A leader that steps down with unsynced entries still syncs them
        in the next pass, and then says nothing: its log may not be a
        prefix of the new leader's."""
        violations, sent = [], []
        leader = joint.elect()
        hook_durability(joint.nodes.values(), violations, sent)
        index = leader.append(b"x", asqn=1)
        joint.net._queues[0].clear()  # its broadcast is lost
        other = next(m for m in joint.nodes if m != leader.member_id)
        leader._on_append_request(other, {
            "term": leader.current_term + 1, "leader": other,
            "prevIndex": index + 5, "prevTerm": leader.current_term + 1,
            "entries": [], "commit": 0})
        assert leader.role == RaftRole.FOLLOWER and leader._flush_dirty
        sent.clear()
        assert joint.flusher.flush([leader]) == 1
        assert leader._flushed_index == index and not leader._flush_dirty
        assert not [s for s in sent if s[1]], sent


class TestFaultsInsideAPass:
    def test_a_followers_failed_fsync_rewinds_it_alone(self, owned, fsyncs):
        violations, sent = [], []
        leader = owned.elect()
        hook_durability(owned.nodes.values(), violations, sent)
        victim = next(n for n in owned.nodes.values() if n is not leader)
        errors = []
        victim.storage_listener = lambda kind, detail: errors.append(kind)
        before = victim.journal.last_index
        fsyncs.fail.add(victim.member_id)
        committed = []
        index = leader.append(b"x" * 500, asqn=1, on_commit=committed.append)
        owned.settle()
        assert committed == [index]  # the other two are a quorum
        assert leader.role == RaftRole.LEADER
        assert errors == ["storage_error"]
        assert victim.journal.last_index == before == victim._flushed_index
        # it never acknowledged what the failed fsync covered
        assert not [s for s in sent
                    if s[0] == victim.member_id and s[1] and s[2] >= index]
        owned.run(2 * HEARTBEAT_INTERVAL_MS)  # the leader resends
        assert victim.journal.last_index == index == victim._flushed_index
        assert not violations
        logs = [log_bytes(n) for n in owned.nodes.values()]
        assert logs[0] == logs[1] == logs[2]

    def test_a_leaders_failed_fsync_steps_it_down(self, owned, fsyncs):
        violations, sent = [], []
        leader = owned.elect()
        hook_durability(owned.nodes.values(), violations, sent)
        before = leader.journal.last_index
        fsyncs.fail.add(leader.member_id)
        committed = []
        index = leader.append(b"x" * 500, asqn=1, on_commit=committed.append)
        owned.settle()
        assert leader.role == RaftRole.FOLLOWER
        assert leader.journal.last_index == before
        assert committed == []  # never acknowledged by this leader
        if owned.mode == "inline":
            assert index is None  # failed before anything was sent
        else:
            # sent before the sync: both followers hold it, synced
            others = [n for n in owned.nodes.values() if n is not leader]
            assert all(n._flushed_index == index for n in others)
        owned.run(4 * ELECTION_TIMEOUT_MS)
        new = owned.leader()
        assert new is not None and new is not leader
        owned.run(2 * HEARTBEAT_INTERVAL_MS)
        assert not violations
        logs = [log_bytes(n) for n in owned.nodes.values()]
        assert logs[0] == logs[1] == logs[2]

    def test_a_power_loss_between_the_appends_and_the_pass(
            self, joint, tmp_path):
        """Send, then sync together, then acknowledge: what a power loss
        after the send and before the sync costs is unacknowledged entries
        only."""
        violations, sent = [], []
        leader = joint.elect()
        hook_durability(joint.nodes.values(), violations, sent)
        acked = []
        for i in range(5):
            leader.append(b"%d" % i * 100, asqn=i + 1, on_commit=acked.append)
            joint.settle()
        assert len(acked) == 5
        lost = leader.append(b"late" * 100, asqn=6, on_commit=acked.append)
        joint.net.deliver_all()  # every replica holds it, none synced it
        assert all(n.journal.last_index == lost for n in joint.nodes.values())
        assert len(acked) == 5 and leader.commit_index == acked[-1]
        assert not [s for s in sent if s[1] and s[2] >= lost]
        for node in joint.nodes.values():
            node.journal.simulate_power_loss()
        for m, old in list(joint.nodes.items()):
            node = RaftNode(joint.net.join(m), partition_id=1,
                            members=sorted(joint.nodes),
                            directory=old.directory,
                            clock_millis=joint.clock, seed=7)
            joint.nodes[m] = node
            assert node.journal.last_index == acked[-1]  # all five, no more
        assert not violations


class TestStorageFaultPlane:
    def test_with_a_plane_installed_the_journals_sync_in_member_order(
            self, joint, fsyncs):
        """A seeded plane draws its faults in call order, so under one the
        pass syncs the journals on the caller's thread, in the order the
        owner gave; the clean path is the concurrent one."""

        class Plane:
            def fsync_fault(self, path):
                return None

            def write_fault(self, path, size):
                return "ok", 0

        leader = joint.elect()
        storage_io.install_controller(Plane())
        try:
            before = len(fsyncs)
            index = leader.append(b"x" * 100, asqn=1)
            joint.settle()
            assert leader.commit_index == index
            assert fsyncs[before:] == [(m, "MainThread")
                                       for m in sorted(joint.nodes)]
        finally:
            storage_io.install_controller(None)


class TestInstruments:
    """Each histogram is observed where it says: ``replicate`` on the
    leader, one observation an entry; ``raft_fsync`` on every replica, one
    a flush; ``flush_pass`` and ``flush_duration_seconds`` once a barrier,
    with the journals it synced and the seconds it took."""

    def _record(self, nodes):
        recs = {}
        for node in nodes:
            recs[node.member_id] = r = {
                "replicate": Recorder(), "raft_fsync": Recorder(),
                "flush_pass": Recorder(), "flush_duration": Recorder()}
            node._m_replicate = r["replicate"]
            node._m_raft_fsync = r["raft_fsync"]
            node._m_flush_pass = r["flush_pass"]
            node._m_flush_duration = r["flush_duration"]
        return recs

    def test_where_each_is_observed(self, owned):
        leader = owned.elect()
        recs = self._record(owned.nodes.values())
        for i in range(4):
            leader.append(b"x" * 64, asqn=i + 1)
            owned.run(HEARTBEAT_INTERVAL_MS)
        for m, r in recs.items():
            assert len(r["replicate"].values) == (
                4 if m == leader.member_id else 0), m
            assert len(r["raft_fsync"].values) == 4, m
            assert all(0 < v < 1 for v in r["raft_fsync"].values)
        assert all(0 < v < 5 for v in recs[leader.member_id]["replicate"].values)
        widths = [v for r in recs.values() for v in r["flush_pass"].values]
        assert widths == ([3] * 4 if owned.mode == "joint" else [1] * 12)
        # the controller's duty-cycle signal: one observation a barrier,
        # not one a journal synced inside it
        barriers = [v for r in recs.values()
                    for v in r["flush_duration"].values]
        assert len(barriers) == len(widths)

    def test_nothing_is_observed_on_reopening_a_log(self, joint):
        leader = joint.elect()
        for i in range(3):
            leader.append(b"x" * 64, asqn=i + 1)
            joint.run(HEARTBEAT_INTERVAL_MS)
        for seed, (m, old) in enumerate(list(joint.nodes.items())):
            old.close()
            joint.nodes[m] = RaftNode(
                joint.net.join(m), partition_id=1, members=sorted(joint.nodes),
                directory=old.directory, clock_millis=joint.clock, seed=seed)
        recs = self._record(joint.nodes.values())
        new = joint.elect()  # reads the log back, appends its initial entry
        assert new.commit_index == new.journal.last_index == 5
        for r in recs.values():
            assert r["replicate"].values == []  # no client entry was appended
            assert len(r["raft_fsync"].values) == 1  # the initial entry's


# -- ClusterRuntime -----------------------------------------------------------


def _wait(predicate, timeout_s: float = 10.0) -> bool:
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.005)
    return predicate()


@pytest.fixture(params=MODES)
def runtime3(request, tmp_path, monkeypatch):
    """Three brokers, one partition, RF 3, the real ownership threads, the
    durability checks hooked in before anything runs. ``inline``: the same
    runtime with an owner that never takes a pass and registers nobody."""
    from zeebe_tpu.gateway import ClusterRuntime

    if request.param == "inline":
        monkeypatch.setattr(JointFlusher, "flush", lambda self, nodes: 0)
    rt = ClusterRuntime(broker_count=3, partition_count=1,
                        replication_factor=3, directory=tmp_path,
                        kernel_backend=False)
    rt.mode = request.param
    rt.violations, rt.sent = [], []
    rt.rafts = [b.partitions[1].raft for b in rt.brokers.values()]
    hook_durability(rt.rafts, rt.violations, rt.sent)
    rt.start()
    yield rt
    rt.stop()


class TestClusterRuntime:
    def test_serial_commands(self, runtime3, fsyncs):
        rt = runtime3
        leader = next(r for r in rt.rafts if r.role == RaftRole.LEADER)

        def quiet():
            last = leader.journal.last_index
            return all(r.journal.last_index == last == r._flushed_index
                       and r.commit_index == last for r in rt.rafts)

        assert _wait(quiet)
        base_index = leader.journal.last_index
        base = {r.member_id: fsyncs.of(r.member_id) for r in rt.rafts}
        passes0, width0 = pass_widths("1")
        for _ in range(20):
            response = rt.submit(1, create_cmd("no_such_process"))
            assert response.is_rejection
            assert _wait(quiet)
        entries = leader.journal.last_index - base_index
        assert entries >= 40  # a command and its follow-up, twenty times
        assert not rt.violations, rt.violations[:3]
        # one fsync an entry a replica, whoever took it
        assert {m: fsyncs.of(m) - n for m, n in base.items()} == \
            {m: entries for m in base}
        logs = [log_bytes(r) for r in rt.rafts]
        assert logs[0] == logs[1] == logs[2]
        passes, width = pass_widths("1")
        if rt.mode == "joint":
            assert width - width0 == 3 * entries
            assert (width - width0) / (passes - passes0) > 2.5
            assert {t for _, t in fsyncs} >= {
                "partition-1", "partition-1-sync-1", "partition-1-sync-2"}
        else:
            assert passes - passes0 == width - width0 == 3 * entries
            assert not any("sync" in t for _, t in fsyncs)

    def test_nodes_are_registered_by_the_runtime_alone(self, runtime3):
        expected = runtime3.mode == "joint"
        assert _wait(lambda: all(r.joint_flush == expected
                                 for r in runtime3.rafts), 2.0)
        assert all(r.flush_policy == "immediate" for r in runtime3.rafts)


def test_a_one_member_partition_commits_inside_client_write(tmp_path, fsyncs):
    from zeebe_tpu.gateway import ClusterRuntime

    rt = ClusterRuntime(broker_count=1, partition_count=1,
                        replication_factor=1, directory=tmp_path,
                        kernel_backend=False)
    rt.start()
    try:
        partition = rt.brokers["broker-0"].partitions[1]
        raft = partition.raft
        seen = []

        def watched(record, orig=partition.client_write):
            before = len(fsyncs)
            position = orig(record)
            seen.append((raft.commit_index == raft.journal.last_index
                         == raft._flushed_index, fsyncs[before:]))
            return position

        partition.client_write = watched
        passes0, width0 = pass_widths("1")
        assert rt.submit(1, create_cmd("no_such_process")).is_rejection
        committed_inside, synced = seen[0]
        assert committed_inside
        assert synced == [("broker-0", "MainThread")]  # the caller's thread
        assert _wait(lambda: raft._flushed_index == raft.journal.last_index)
        passes, width = pass_widths("1")
        assert passes - passes0 == width - width0 >= 2  # every barrier alone
    finally:
        rt.stop()
