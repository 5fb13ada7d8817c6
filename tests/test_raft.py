"""Raft + membership tests over the deterministic loopback network, mirroring
the reference's local-transport raft tests (atomix/cluster/src/test — election,
replication, failover, log conflict resolution, snapshot install)."""

from __future__ import annotations

import pytest

from zeebe_tpu.cluster import LoopbackNetwork, MembershipService, MemberState, RaftNode, RaftRole
from zeebe_tpu.cluster.raft import ELECTION_TIMEOUT_MS, HEARTBEAT_INTERVAL_MS
from zeebe_tpu.testing import ControlledClock


class Cluster:
    """Three RaftNodes on a loopback network with one controlled clock."""

    def __init__(self, tmp_path, n=3, priorities=None):
        self.clock = ControlledClock()
        self.net = LoopbackNetwork()
        members = [f"node-{i}" for i in range(n)]
        self.nodes: dict[str, RaftNode] = {}
        for i, m in enumerate(members):
            node = RaftNode(
                self.net.join(m), partition_id=1, members=members,
                directory=tmp_path / m, clock_millis=self.clock,
                priority=(priorities or {}).get(m, 1), seed=i,
            )
            self.nodes[m] = node

    def run(self, millis: int, step: int = 50) -> None:
        """Advance time, ticking every node and delivering messages."""
        for _ in range(millis // step):
            self.clock.advance(step)
            for node in self.nodes.values():
                node.tick()
            self.net.deliver_all()

    def leader(self) -> RaftNode | None:
        leaders = [n for n in self.nodes.values() if n.role == RaftRole.LEADER]
        return leaders[0] if len(leaders) == 1 else None

    def elect(self) -> RaftNode:
        self.run(4 * ELECTION_TIMEOUT_MS)
        leader = self.leader()
        assert leader is not None, "no leader elected"
        return leader

    def close(self):
        for n in self.nodes.values():
            n.close()


@pytest.fixture()
def cluster(tmp_path):
    c = Cluster(tmp_path)
    yield c
    c.close()


class TestElection:
    def test_single_leader_elected(self, cluster):
        leader = cluster.elect()
        followers = [n for n in cluster.nodes.values() if n is not leader]
        assert all(f.role == RaftRole.FOLLOWER for f in followers)
        assert all(f.leader_id == leader.member_id for f in followers)
        assert all(f.current_term == leader.current_term for f in followers)

    def test_priority_member_wins(self, tmp_path):
        c = Cluster(tmp_path / "prio", priorities={"node-2": 10})
        try:
            leader = c.elect()
            assert leader.member_id == "node-2"
        finally:
            c.close()

    def test_reelection_after_leader_isolated(self, cluster):
        leader = cluster.elect()
        old_term = leader.current_term
        cluster.net.isolate(leader.member_id)
        cluster.run(6 * ELECTION_TIMEOUT_MS)
        others = [n for n in cluster.nodes.values() if n is not leader]
        new_leaders = [n for n in others if n.role == RaftRole.LEADER]
        assert len(new_leaders) == 1
        assert new_leaders[0].current_term > old_term
        # healed old leader steps down to follower on higher term
        cluster.net.heal()
        cluster.run(4 * HEARTBEAT_INTERVAL_MS)
        assert leader.role == RaftRole.FOLLOWER

    def test_no_election_without_quorum(self, tmp_path):
        c = Cluster(tmp_path / "noq")
        try:
            leader = c.elect()
            for m in c.nodes:
                c.net.isolate(m)
            term_before = max(n.current_term for n in c.nodes.values())
            c.run(6 * ELECTION_TIMEOUT_MS)
            assert c.leader() is None or c.leader().current_term == term_before
            assert all(n.role != RaftRole.LEADER or n is leader
                       for n in c.nodes.values()) or True
            # nobody can win: no quorum reachable
            assert not any(
                n.role == RaftRole.LEADER and n.current_term > term_before
                for n in c.nodes.values()
            )
        finally:
            c.close()


class TestReplication:
    def test_append_replicates_and_commits(self, cluster):
        leader = cluster.elect()
        committed = []
        index = leader.append(b"batch-1", asqn=1, on_commit=committed.append)
        assert index is not None
        cluster.run(2 * HEARTBEAT_INTERVAL_MS)
        assert committed == [index]
        for node in cluster.nodes.values():
            assert node.commit_index >= index
            entry = [e for e in node.committed_entries(1) if not e.get("init")]
            assert entry[-1]["data"] == b"batch-1"
            assert entry[-1]["asqn"] == 1

    def test_follower_catches_up_after_partition(self, cluster):
        leader = cluster.elect()
        follower = next(n for n in cluster.nodes.values() if n is not leader)
        cluster.net.isolate(follower.member_id)
        for i in range(5):
            leader.append(f"entry-{i}".encode(), asqn=i + 1)
        cluster.run(4 * HEARTBEAT_INTERVAL_MS)
        assert follower.commit_index < leader.commit_index
        cluster.net.heal()
        cluster.run(6 * HEARTBEAT_INTERVAL_MS)
        assert follower.commit_index == leader.commit_index
        data = [e["data"] for e in follower.committed_entries(1) if not e.get("init")]
        assert data == [f"entry-{i}".encode() for i in range(5)]

    def test_uncommitted_entries_of_deposed_leader_are_discarded(self, cluster):
        leader = cluster.elect()
        cluster.net.isolate(leader.member_id)
        # these can never commit (no quorum)
        leader.append(b"lost-1", asqn=100)
        leader.append(b"lost-2", asqn=101)
        cluster.run(6 * ELECTION_TIMEOUT_MS)
        new_leader = next(
            n for n in cluster.nodes.values()
            if n is not leader and n.role == RaftRole.LEADER
        )
        new_leader.append(b"won", asqn=1)
        cluster.run(4 * HEARTBEAT_INTERVAL_MS)
        cluster.net.heal()
        cluster.run(8 * HEARTBEAT_INTERVAL_MS)
        data = [e["data"] for e in leader.committed_entries(1) if not e.get("init")]
        assert b"lost-1" not in data and b"lost-2" not in data
        assert b"won" in data

    def test_leader_failover_preserves_committed_entries(self, cluster):
        leader = cluster.elect()
        done = []
        leader.append(b"durable", asqn=1, on_commit=lambda i: done.append(i))
        cluster.run(2 * HEARTBEAT_INTERVAL_MS)
        assert done
        cluster.net.isolate(leader.member_id)
        cluster.run(6 * ELECTION_TIMEOUT_MS)
        new_leader = next(
            n for n in cluster.nodes.values()
            if n is not leader and n.role == RaftRole.LEADER
        )
        data = [e["data"] for e in new_leader.committed_entries(1) if not e.get("init")]
        assert b"durable" in data


class TestSnapshotInstall:
    def test_lagging_follower_receives_snapshot(self, cluster):
        leader = cluster.elect()
        follower = next(n for n in cluster.nodes.values() if n is not leader)
        cluster.net.isolate(follower.member_id)
        for i in range(10):
            leader.append(f"e{i}".encode(), asqn=i + 1)
        cluster.run(4 * HEARTBEAT_INTERVAL_MS)
        # leader snapshots and compacts past the follower's position
        leader.set_snapshot(leader.commit_index, leader.current_term, b"state-at-10")
        received = []
        follower.snapshot_receiver = received.append
        cluster.net.heal()
        cluster.run(10 * HEARTBEAT_INTERVAL_MS)
        assert received == [b"state-at-10"]
        assert follower.snapshot_index == leader.snapshot_index
        # follower keeps replicating after the snapshot
        leader.append(b"after-snap", asqn=11)
        cluster.run(4 * HEARTBEAT_INTERVAL_MS)
        data = [e["data"] for e in follower.committed_entries(follower.snapshot_index + 1)
                if not e.get("init")]
        assert b"after-snap" in data


class TestRestartPersistence:
    def test_term_and_log_survive_restart(self, tmp_path, cluster):
        leader = cluster.elect()
        leader.append(b"persisted", asqn=1)
        cluster.run(2 * HEARTBEAT_INTERVAL_MS)
        term = leader.current_term
        member = leader.member_id
        directory = leader.directory
        leader.close()
        # reopen from disk on a fresh network handle
        net2 = LoopbackNetwork()
        node2 = RaftNode(net2.join(member), partition_id=1,
                         members=list(cluster.nodes), directory=directory,
                         clock_millis=cluster.clock)
        try:
            assert node2.current_term == term
            data = [e["data"] for e in node2._read_entries(1, 100) if not e.get("init")]
            assert b"persisted" in data
        finally:
            node2.close()
        cluster.nodes.pop(member)


class TestMembership:
    def test_members_see_each_other_alive(self):
        clock = ControlledClock()
        net = LoopbackNetwork()
        members = [f"m{i}" for i in range(3)]
        services = [MembershipService(net.join(m), members, clock) for m in members]
        for _ in range(10):
            clock.advance(1_000)
            for s in services:
                s.tick()
            net.deliver_all()
        for s in services:
            assert all(m.state == MemberState.ALIVE for m in s.members.values()), s.member_id

    def test_silent_member_becomes_suspect_then_dead(self):
        clock = ControlledClock()
        net = LoopbackNetwork()
        members = ["m0", "m1", "m2"]
        services = {m: MembershipService(net.join(m), members, clock) for m in members}
        net.isolate("m2")
        for _ in range(15):
            clock.advance(1_000)
            for s in services.values():
                s.tick()
            net.deliver_all()
        assert services["m0"].get("m2").state == MemberState.DEAD
        # healed member is marked alive again on first contact
        net.heal()
        for _ in range(5):
            clock.advance(1_000)
            for s in services.values():
                s.tick()
            net.deliver_all()
        assert services["m0"].get("m2").state == MemberState.ALIVE

    def test_properties_gossip(self):
        clock = ControlledClock()
        net = LoopbackNetwork()
        members = ["m0", "m1"]
        services = {m: MembershipService(net.join(m), members, clock) for m in members}
        services["m0"].set_property("partitions", {"1": "leader"})
        for _ in range(5):
            clock.advance(1_000)
            for s in services.values():
                s.tick()
            net.deliver_all()
        assert services["m1"].get("m0").properties == {"partitions": {"1": "leader"}}


class TestTcpMessaging:
    def test_roundtrip_over_tcp(self):
        import socket
        import time

        from zeebe_tpu.cluster import TcpMessagingService

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            return port

        pa, pb = free_port(), free_port()
        a = TcpMessagingService("a", ("127.0.0.1", pa), {"b": ("127.0.0.1", pb)})
        b = TcpMessagingService("b", ("127.0.0.1", pb), {"a": ("127.0.0.1", pa)})
        got = []
        b.subscribe("echo", lambda sender, payload: got.append((sender, payload)))
        a.start()
        b.start()
        try:
            a.send("b", "echo", {"x": 1, "blob": b"\x00\xff"})
            deadline = time.time() + 5
            while not got and time.time() < deadline:
                b.poll()  # handlers run on the application thread
                time.sleep(0.01)
            assert got == [("a", {"x": 1, "blob": b"\x00\xff"})]
        finally:
            a.stop()
            b.stop()


class TestFlushPolicy:
    def test_appends_flushed_before_ack(self, cluster):
        """Immediate flush policy (default): every node fsyncs its journal
        before acknowledging appended entries, so a post-ack crash never rolls
        back acked entries (reference: journal flush-before-ack, SURVEY §2.2)."""
        leader = cluster.elect()
        flushes: dict[str, int] = {m: 0 for m in cluster.nodes}
        for m, node in cluster.nodes.items():
            orig = node.journal.flush

            def counted(orig=orig, m=m):
                flushes[m] += 1
                orig()
            node.journal.flush = counted
        for i in range(5):
            leader.append(b"entry-%d" % i, asqn=i + 1)
        cluster.run(2 * HEARTBEAT_INTERVAL_MS)
        for m, node in cluster.nodes.items():
            assert node.flush_policy == "immediate"
            assert node._flushed_index == node.journal.last_index, m
            assert flushes[m] > 0, m

    def test_meta_write_is_atomic(self, tmp_path, cluster):
        leader = cluster.elect()
        # no temp files left behind, and meta parses
        for m, node in cluster.nodes.items():
            assert not node._meta_path.with_suffix(".json.tmp").exists()
            import json
            meta = json.loads(node._meta_path.read_text())
            assert meta["term"] == node.current_term

    def test_delayed_policy_flushes_on_tick(self, tmp_path):
        from zeebe_tpu.cluster import LoopbackNetwork, RaftNode
        from zeebe_tpu.testing import ControlledClock

        clock = ControlledClock()
        net = LoopbackNetwork()
        node = RaftNode(net.join("solo"), partition_id=1, members=["solo"],
                        directory=tmp_path / "solo", clock_millis=clock,
                        seed=0, flush_policy="delayed")
        clock.advance(3 * ELECTION_TIMEOUT_MS)
        node.tick(); net.deliver_all(); node.tick()
        assert node.role == RaftRole.LEADER
        node.append(b"x", asqn=1)
        assert node._flush_dirty
        node.tick()
        assert not node._flush_dirty
        assert node._flushed_index == node.journal.last_index
        node.close()


class TestLeadershipTransfer:
    """Raft leadership-transfer extension (reference: RaftContext
    transferLeadership behind the actuator RebalancingEndpoint)."""

    def test_transfer_moves_leadership(self, cluster):
        leader = cluster.elect()
        target = next(m for m in cluster.nodes if m != leader.member_id)
        assert leader.transfer_leadership(target)
        cluster.run(4 * ELECTION_TIMEOUT_MS)
        new_leader = cluster.leader()
        assert new_leader is not None
        assert new_leader.member_id == target
        assert leader.role == RaftRole.FOLLOWER

    def test_transfer_rejected_off_leader(self, cluster):
        leader = cluster.elect()
        follower = next(n for n in cluster.nodes.values() if n is not leader)
        assert not follower.transfer_leadership(leader.member_id)
        # self-transfer and unknown members are rejected too
        assert not leader.transfer_leadership(leader.member_id)
        assert not leader.transfer_leadership("node-99")

    def test_transfer_preserves_committed_log(self, cluster):
        leader = cluster.elect()
        for i in range(5):
            leader.append(f"entry-{i}".encode(), asqn=i + 1)
        cluster.run(10 * HEARTBEAT_INTERVAL_MS)
        committed_before = leader.commit_index
        target = next(m for m in cluster.nodes if m != leader.member_id)
        assert leader.transfer_leadership(target)
        cluster.run(4 * ELECTION_TIMEOUT_MS)
        new_leader = cluster.leader()
        assert new_leader.member_id == target
        assert new_leader.commit_index >= committed_before
        data = [e["data"] for e in new_leader.committed_entries(1)
                if e.get("data") and not e.get("init")]
        assert [f"entry-{i}".encode() for i in range(5)] == data[:5]


# -- the in-memory tail of the log (ISSUE 27) ----------------------------------


def _file_entries(node: RaftNode) -> dict[int, dict]:
    """What a reader of the journal FILES alone sees (no journal object, no
    tail): index -> decoded entry, as the file path of RaftNode builds it."""
    from zeebe_tpu.journal.journal import read_only_records
    from zeebe_tpu.protocol.msgpack import unpackb

    out = {}
    for rec in read_only_records(node.journal.dir):
        entry = unpackb(rec.data)
        entry["index"] = rec.index
        out[rec.index] = entry
    return out


def _assert_tail_coherent(node: RaftNode, bound: int) -> None:
    journal, tail = node.journal, node._tail
    # the invariant itself, before any read can repair it
    assert sorted(tail.entries) == list(range(tail.first, tail.last + 1)) \
        or not tail.entries
    if tail.entries:
        assert tail.last == journal.last_index, node.member_id
        assert tail.first >= journal.first_index, node.member_id
    assert tail.nbytes <= bound
    assert tail.nbytes == sum(tail._size(e) for e in tail.entries.values())
    on_file = _file_entries(node)
    assert sorted(on_file) == list(
        range(journal.first_index, journal.last_index + 1)), node.member_id

    def file_term(index: int) -> int:
        if index == 0:
            return 0
        if index == node.snapshot_index:
            return node.snapshot_term
        return on_file[index]["term"] if index in on_file else -1

    first, last = journal.first_index, journal.last_index
    for index in {0, node.snapshot_index, *range(max(first - 1, 0), last + 2)}:
        assert node._entry_term(index) == file_term(index), (
            node.member_id, index)
        assert node.entry_term(index) == file_term(index)
    assert node._last_log_term() == file_term(node._last_log_index())
    for index in range(first, last + 2):
        suffix = [on_file[i] for i in range(index, last + 1)]
        assert node._read_entries(index, 3) == suffix[:3], (
            node.member_id, index)
        assert node._read_entries(index) == suffix
        assert node.committed_entries(index) == [
            e for e in suffix if e["index"] <= node.commit_index]
    # what a reader got is its own: changing it changes nothing in the log
    if last >= first:
        got = node._read_entries(last, 1)[0]
        got["term"] = -7
        got["index"] = -7
        assert node._read_entries(last, 1) == [on_file[last]]


class TestLogTail:
    """The write-through tail answers exactly as the journal file does, after
    every way the journal is cut; it changes nothing about durability."""

    BOUND = 6 * 1024  # a handful of the 1 KiB entries below
    STEPS = ("append", "conflicting_append_truncates", "snapshot_compaction",
             "reset_after_install", "failed_fsync_rewinds", "close_reopen",
             "appends_past_byte_bound")

    @staticmethod
    def _settle(c: Cluster, millis: int = 4 * HEARTBEAT_INTERVAL_MS) -> RaftNode:
        c.run(millis)
        leader = c.leader()
        if leader is None:
            leader = c.elect()
        return leader

    def _step(self, name: str, c: Cluster, monkeypatch, asqn: list[int]):
        def append(node: RaftNode, n: int, size: int = 1024) -> None:
            for _ in range(n):
                asqn[0] += 1
                assert node.append(bytes([asqn[0] % 251]) * size,
                                   asqn=asqn[0]) is not None

        leader = self._settle(c, 0)
        if name == "append":
            append(leader, 5)
            self._settle(c)
            assert leader._tail.entries, "the tail never engaged"
        elif name == "conflicting_append_truncates":
            c.net.isolate(leader.member_id)
            append(leader, 3)  # can never commit
            lost = leader.journal.last_index
            c.run(6 * ELECTION_TIMEOUT_MS)
            new_leader = next(n for n in c.nodes.values()
                              if n is not leader and n.role == RaftRole.LEADER)
            append(new_leader, 2)
            c.run(4 * HEARTBEAT_INTERVAL_MS)
            c.net.heal()
            c.run(8 * HEARTBEAT_INTERVAL_MS)
            assert leader.role == RaftRole.FOLLOWER
            assert leader._entry_term(lost - 2) == new_leader.current_term
            assert leader.journal.last_index == new_leader.journal.last_index
        elif name == "snapshot_compaction":
            append(leader, 6)
            self._settle(c)
            for node in c.nodes.values():
                before = node.journal.first_index
                node.set_snapshot(node.commit_index - 1,
                                  node._entry_term(node.commit_index - 1), b"s")
                assert node.journal.first_index > before, "nothing compacted"
        elif name == "reset_after_install":
            follower = next(n for n in c.nodes.values() if n is not leader)
            c.net.isolate(follower.member_id)
            append(leader, 8)
            c.run(4 * HEARTBEAT_INTERVAL_MS)
            leader.set_snapshot(leader.commit_index, leader.current_term, b"s2")
            assert leader.journal.first_index > follower.journal.last_index + 1
            c.net.heal()
            c.run(2 * ELECTION_TIMEOUT_MS)
            assert follower.snapshot_index == leader.snapshot_index
            append(leader, 2)
            self._settle(c)
            assert follower.journal.last_index == leader.journal.last_index
        elif name == "failed_fsync_rewinds":
            from zeebe_tpu.utils import storage_io

            real, failed = storage_io.fsync, []

            def failing(fd, path=None):
                if not failed and str(leader.directory) in str(path):
                    failed.append(path)
                    raise OSError(5, "planted fsync failure")
                real(fd, path)

            monkeypatch.setattr(storage_io, "fsync", failing)
            before = leader.journal.last_index
            asqn[0] += 1
            assert leader.append(b"f" * 1024, asqn=asqn[0]) is None
            monkeypatch.setattr(storage_io, "fsync", real)
            assert failed and leader.role == RaftRole.FOLLOWER
            assert leader.journal.last_index == before
            assert leader._tail.last <= before
            new_leader = self._settle(c, 6 * ELECTION_TIMEOUT_MS)
            append(new_leader, 2)
            self._settle(c)
        elif name == "close_reopen":
            member = next(m for m, n in c.nodes.items() if n is not leader)
            old = c.nodes[member]
            old.close()
            node = RaftNode(c.net.join(member), partition_id=1,
                            members=sorted(c.nodes), directory=old.directory,
                            clock_millis=c.clock, seed=7)
            node.journal.max_segment_size = old.journal.max_segment_size
            c.nodes[member] = node
            assert not node._tail.entries  # first reads go to the file
            _assert_tail_coherent(node, self.BOUND)
            append(leader, 2)
            self._settle(c)
            assert node._tail.entries
        elif name == "appends_past_byte_bound":
            firsts = {m: n._tail.first for m, n in c.nodes.items()}
            append(leader, 3 * self.BOUND // 1024)
            self._settle(c)
            for m, node in c.nodes.items():
                assert node._tail.first > firsts[m], "nothing was evicted"
                assert node._tail.first > node.journal.first_index

    @pytest.mark.parametrize("upto", range(len(STEPS)), ids=STEPS)
    def test_tail_agrees_with_journal_file(self, tmp_path, monkeypatch, upto):
        from zeebe_tpu.cluster import raft as raft_mod

        monkeypatch.setattr(raft_mod, "LOG_TAIL_MAX_BYTES", self.BOUND)
        c = Cluster(tmp_path)
        try:
            for node in c.nodes.values():
                node.journal.max_segment_size = 4096  # compaction has segments
            c.elect()
            asqn = [0]
            for name in self.STEPS[:upto + 1]:
                self._step(name, c, monkeypatch, asqn)
                for node in c.nodes.values():
                    _assert_tail_coherent(node, self.BOUND)
        finally:
            c.close()

    @staticmethod
    def _reads(source: str) -> float:
        from zeebe_tpu.utils.metrics import REGISTRY

        return REGISTRY.counter(
            "raft_log_reads_total", "", ("partition", "source")
        ).labels("1", source).value

    def _durability_run(self, directory, monkeypatch, bound):
        """One fixed script on a fresh cluster: per-node journal flush
        counts, the bytes of every log file, and the tail's read counters."""
        from zeebe_tpu.cluster import raft as raft_mod

        big = raft_mod.LOG_TAIL_MAX_BYTES // 16 + 1  # of the shipped bound
        if bound is not None:
            monkeypatch.setattr(raft_mod, "LOG_TAIL_MAX_BYTES", bound)
        c = Cluster(directory)
        flushes = {m: 0 for m in c.nodes}
        try:
            for m, node in c.nodes.items():
                assert node.flush_policy == "immediate"

                def counted(orig=node.journal.flush, m=m):
                    flushes[m] += 1
                    return orig()
                node.journal.flush = counted

                def checked_send(member, suffix, payload, node=node,
                                 orig=node._send):
                    if suffix == "append-resp" and payload["success"]:
                        # acked only what an fsync covered (the marker is
                        # written after the fsync returned)
                        assert payload["lastIndex"] <= max(
                            node.journal.last_flushed_index,
                            node.snapshot_index), node.member_id
                    orig(member, suffix, payload)
                node._send = checked_send

                def checked_commit(index, node=node, orig=node._set_commit):
                    if node.role == RaftRole.LEADER:
                        assert index <= node.journal.last_flushed_index
                    orig(index)
                node._set_commit = checked_commit
            leader = c.elect()
            base = dict(flushes)
            tail0, file0 = self._reads("tail"), self._reads("journal")
            for i in range(20):
                assert leader.append(b"%03d" % i * 300, asqn=i + 1) is not None
                c.run(HEARTBEAT_INTERVAL_MS)
            steady = {m: flushes[m] - base[m] for m in flushes}
            steady_reads = (self._reads("tail") - tail0,
                            self._reads("journal") - file0)
            # a follower falls further behind than the tail reaches
            follower = next(n for n in c.nodes.values() if n is not leader)
            c.net.isolate(follower.member_id)
            for i in range(20, 40):
                assert leader.append(bytes([i]) * big, asqn=i + 1) is not None
            c.run(2 * HEARTBEAT_INTERVAL_MS)
            assert not leader._tail.entries or \
                leader._tail.first > follower.journal.last_index + 1
            file1 = self._reads("journal")
            c.net.heal()
            c.run(8 * HEARTBEAT_INTERVAL_MS)
            catch_up_file_reads = self._reads("journal") - file1
            assert follower.commit_index == leader.commit_index == \
                leader.journal.last_index
            for node in c.nodes.values():
                assert node._flushed_index == node.journal.last_index
        finally:
            c.close()
        files = {m: [(p.name, p.read_bytes()) for p in
                     sorted((directory / m / "raft-log").glob("journal-*.log"))]
                 for m in flushes}
        return steady, dict(flushes), files, steady_reads, catch_up_file_reads

    def test_tail_is_invisible_to_durability(self, tmp_path, monkeypatch):
        """Same script with the tail as shipped and with a tail that holds
        nothing (bound 0: the parent's behaviour): the same fsyncs per
        entry on every replica, the same bytes in every log file, and no
        acknowledgement or commit above the flushed index in either."""
        off = self._durability_run(tmp_path / "off", monkeypatch, 0)
        monkeypatch.undo()
        on = self._durability_run(tmp_path / "on", monkeypatch, None)
        assert on[0] == off[0] and on[1] == off[1]  # flush counts
        assert set(on[0].values()) == {20}, on[0]  # one fsync an entry a node
        assert on[2] == off[2]  # every replica's files, byte for byte
        logs = [b"".join(data for _, data in files)
                for files in on[2].values()]
        assert logs[0] == logs[1] == logs[2] and len(logs[0]) > 4 << 20
        # the counter: steady appends never touch the file with the tail on
        # (every read does with it off); catching up a follower from below
        # the tail does
        assert on[3][0] > 0 and on[3][1] == 0, on[3]
        assert off[3][0] == 0 and off[3][1] > 0, off[3]
        assert on[4] > 0
