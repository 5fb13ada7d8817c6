"""The burst-template PRODUCTION path (audit off): instantiated bursts must
produce a log, responses, and final state identical to the sequential engine.

EngineHarness defaults to audit mode, where template hits shadow the slow
path — these tests are the automated guard for the code that actually runs in
production: KernelBackend._instantiate, BurstTemplate patching,
LogStreamWriter.append_prepatched, EngineState.bulk_mint, and the PreparedBurst
handling in StreamProcessor.process_available_batch.
"""

from __future__ import annotations

import pytest

from zeebe_tpu.models.bpmn import Bpmn
from zeebe_tpu.testing import EngineHarness


def one_task(pid="one_task"):
    return (
        Bpmn.create_executable_process(pid)
        .start_event("start").service_task("task", job_type="work")
        .end_event("end").done()
    )


def fork_join(pid="fj"):
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .parallel_gateway("fork")
        .service_task("a", job_type="a")
        .parallel_gateway("join")
        .end_event("e")
        .move_to_element("fork")
        .service_task("b", job_type="b")
        .connect_to("join")
        .done()
    )


def exclusive(pid="excl"):
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .exclusive_gateway("gw")
        .condition_expression("x > 10")
        .service_task("big", job_type="big")
        .end_event("e1")
        .move_to_element("gw")
        .default_flow()
        .service_task("small", job_type="small")
        .end_event("e2")
        .done()
    )


def _fingerprint(h):
    out = []
    for logged in h.stream.new_reader(1):
        rec = logged.record
        out.append((
            logged.position, logged.source_position, logged.processed,
            rec.key, rec.record_type.name, rec.value_type.name,
            int(rec.intent), rec.timestamp,
            rec.rejection_type.name if rec.is_rejection else "",
            dict(rec.value) if rec.value else {},
        ))
    return out


def _state_image(h):
    db = h.engine.state.db
    return {k: db._data[k] for k in db._index}


def _run(scenario, mode):
    """mode: 'seq' | 'fast' (templates live, audit OFF) | 'audit'"""
    h = EngineHarness(use_kernel_backend=mode != "seq")
    if mode == "fast":
        h.kernel_backend.audit_templates = False
    try:
        scenario(h)
        stats = None
        if mode == "fast":
            kb = h.kernel_backend
            stats = {"hits": kb.template_hits, "misses": kb.template_misses}
        return _fingerprint(h), [
            (r.request_id, r.record.key, int(r.record.intent)) for r in h.responses
        ], _state_image(h), stats
    finally:
        h.close()


def assert_fast_path_equivalent(scenario, min_hits=1):
    seq_log, seq_resp, seq_state, _ = _run(scenario, "seq")
    fast_log, fast_resp, fast_state, stats = _run(scenario, "fast")
    assert stats["hits"] >= min_hits, f"fast path never served: {stats}"
    assert fast_log == seq_log
    assert fast_resp == seq_resp
    assert fast_state == seq_state


def _drive(h, model, pid, job_types, instances, variables):
    h.deploy(model)
    for _ in range(instances):
        h.create_instance(pid, variables=dict(variables))
    for _ in range(16):
        worked = 0
        for jt in job_types:
            for job in h.activate_jobs(jt, max_jobs=50):
                h.complete_job(job["key"])
                worked += 1
        if not worked:
            return
    pytest.fail("jobs did not drain")


class TestFastPathEquivalence:
    def test_one_task(self):
        assert_fast_path_equivalent(
            lambda h: _drive(h, one_task(), "one_task", ["work"], 6, {"x": 1}),
            min_hits=8,
        )

    def test_fork_join(self):
        assert_fast_path_equivalent(
            lambda h: _drive(h, fork_join(), "fj", ["a", "b"], 5, {}),
            min_hits=6,
        )

    def test_exclusive_both_routes(self):
        def scenario(h):
            h.deploy(exclusive())
            for x in (20, 20, 5, 5, 20):
                h.create_instance("excl", variables={"x": x})
            for jt in ("big", "small"):
                for job in h.activate_jobs(jt, max_jobs=50):
                    h.complete_job(job["key"])

        assert_fast_path_equivalent(scenario, min_hits=4)

    def test_mixed_definitions(self):
        def scenario(h):
            h.deploy(one_task(), fork_join())
            for i in range(4):
                h.create_instance("one_task", variables={"x": 1})
                h.create_instance("fj")
            for jt in ("work", "a", "b"):
                for job in h.activate_jobs(jt, max_jobs=50):
                    h.complete_job(job["key"])

        assert_fast_path_equivalent(scenario, min_hits=6)

    def test_await_result_never_templated(self):
        # awaitResult instances touch engine.await_results (outside the
        # captured state store) and must always take the slow path
        def scenario(h):
            from zeebe_tpu.protocol import ValueType
            from zeebe_tpu.protocol.intent import ProcessInstanceCreationIntent
            from zeebe_tpu.protocol.record import command

            h.deploy(one_task())
            h.write_command(command(
                ValueType.PROCESS_INSTANCE_CREATION,
                ProcessInstanceCreationIntent.CREATE,
                {"bpmnProcessId": "one_task", "version": -1, "variables": {},
                 "awaitResult": True},
            ), request_id=77)
            for job in h.activate_jobs("work", max_jobs=5):
                h.complete_job(job["key"])

        assert_fast_path_equivalent(scenario, min_hits=0)

    def test_mixed_request_and_requestless_commands(self):
        # request presence changes the burst shape (client response or not):
        # templates captured from one must never serve the other
        def scenario(h):
            from zeebe_tpu.protocol import ValueType
            from zeebe_tpu.protocol.intent import ProcessInstanceCreationIntent
            from zeebe_tpu.protocol.record import command

            h.deploy(one_task())
            create = {"bpmnProcessId": "one_task", "version": -1, "variables": {"x": 1}}
            for i in range(6):
                cmd = command(ValueType.PROCESS_INSTANCE_CREATION,
                              ProcessInstanceCreationIntent.CREATE, create)
                if i % 2 == 0:
                    h.write_command(cmd, request_id=100 + i)
                else:
                    h.write_command(cmd)  # request-free (internal-style)
            for job in h.activate_jobs("work", max_jobs=10):
                h.complete_job(job["key"])

        seq_log, seq_resp, seq_state, _ = _run(scenario, "seq")
        fast_log, fast_resp, fast_state, stats = _run(scenario, "fast")
        assert stats["hits"] >= 2
        assert fast_log == seq_log
        assert fast_resp == seq_resp
        assert fast_state == seq_state

    def test_fingerprint_role_marker_not_forgeable(self):
        # a variable whose literal value mimics the fingerprint role marker
        # must not collide with a key-referencing context
        def scenario(h):
            h.deploy(one_task())
            h.create_instance("one_task", variables={"x": 1, "v": ["\x00r", "p"]})
            h.create_instance("one_task", variables={"x": 1, "v": ["\x00r", "p"]})
            for job in h.activate_jobs("work", max_jobs=5):
                h.complete_job(job["key"])

        assert_fast_path_equivalent(scenario, min_hits=1)

    def test_restart_replay_after_fast_path(self):
        # events written by prepatched appends must replay to identical state
        from zeebe_tpu.engine import Engine
        from zeebe_tpu.logstreams import LogStream
        from zeebe_tpu.state import ZbDb
        from zeebe_tpu.stream import StreamProcessor, StreamProcessorMode

        h = EngineHarness(use_kernel_backend=True)
        h.kernel_backend.audit_templates = False
        try:
            _drive(h, one_task(), "one_task", ["work"], 4, {"x": 1})
            assert h.kernel_backend.template_hits >= 4
            stream = LogStream(h.journal, h.stream.partition_id, clock=h.clock)
            db = ZbDb()
            engine = Engine(db, h.stream.partition_id, clock_millis=h.clock)
            sp = StreamProcessor(stream, db, engine, mode=StreamProcessorMode.REPLAY)
            sp.start()
            sp.run_until_idle()
            assert db.content_equals(h.db)
        finally:
            h.close()


class TestTemplateCache:
    def test_eviction_keeps_hot_entries(self):
        from zeebe_tpu.engine.kernel_backend import KernelBackend

        class _Eng:
            pass

        kb = KernelBackend.__new__(KernelBackend)
        kb._templates = {}
        kb._template_cache_limit = 4
        for i in range(4):
            kb._store_template(("k", i), f"t{i}")
        # touch ("k", 0) the way _materialize does on a hit
        t = kb._templates.pop(("k", 0))
        kb._templates[("k", 0)] = t
        kb._store_template(("k", 9), "t9")  # triggers eviction of oldest half
        assert ("k", 0) in kb._templates
        assert ("k", 9) in kb._templates
