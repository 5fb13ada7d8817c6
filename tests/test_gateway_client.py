"""Gateway gRPC + Python client integration tests (reference:
gateway/src/test EndpointManagerTest, clients/java client ITs). Real gRPC over
localhost against an in-process broker cluster runtime."""

from __future__ import annotations

import threading
import time

import grpc
import pytest

from zeebe_tpu.client import JobWorker, ZeebeTpuClient
from zeebe_tpu.gateway import ClusterRuntime, Gateway
from zeebe_tpu.models.bpmn import Bpmn, to_bpmn_xml


@pytest.fixture(scope="module")
def stack():
    runtime = ClusterRuntime(broker_count=1, partition_count=2,
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


def one_task(pid="p", job_type="w"):
    return to_bpmn_xml(
        Bpmn.create_executable_process(pid)
        .start_event("s").service_task("t", job_type=job_type).end_event("e").done()
    )


class TestGatewayRpcs:
    def test_topology(self, stack):
        client, _ = stack
        topo = client.topology()
        assert topo.cluster_size == 1
        assert topo.partitions_count == 2
        assert topo.gateway_version.startswith("8.4")

    def test_deploy_and_create(self, stack):
        client, _ = stack
        deployed = client.deploy_resource(("p.bpmn", one_task()))
        assert deployed["processes"][0]["bpmnProcessId"] == "p"
        assert deployed["processes"][0]["version"] == 1
        instance = client.create_instance("p", variables={"x": 1})
        assert instance.process_instance_key > 0
        assert instance.bpmn_process_id == "p"

    def test_activate_complete_roundtrip(self, stack):
        client, _ = stack
        client.deploy_resource(("rt.bpmn", one_task("rt", "rt_work")))
        client.create_instance("rt")
        jobs = client.activate_jobs("rt_work", request_timeout_ms=5_000)
        assert len(jobs) == 1
        job = jobs[0]
        assert job.type == "rt_work"
        assert job.bpmn_process_id == "rt"
        client.complete_job(job.key, {"done": True})
        # job is gone afterwards
        assert client.activate_jobs("rt_work") == []

    def test_activate_keeps_jobs_when_a_later_partition_sheds(
            self, stack, monkeypatch):
        # jobs partition 1 already activated must reach the worker even
        # when partition 2 answers RESOURCE_EXHAUSTED: aborting the call
        # would strand them, activated, until their job timeout
        from zeebe_tpu.gateway.broker_client import ResourceExhaustedError
        from zeebe_tpu.protocol import ValueType

        client, runtime = stack
        client.deploy_resource(("shed.bpmn", one_task("shed", "shed_work")))
        for _ in range(2):  # round-robin: one instance per partition
            client.create_instance("shed")
        real_submit = runtime.submit

        def shedding(partition_id, record, **kw):
            if partition_id == 2 and record.value_type == ValueType.JOB_BATCH:
                raise ResourceExhaustedError("partition 2 sheds")
            return real_submit(partition_id, record, **kw)

        monkeypatch.setattr(runtime, "submit", shedding)
        jobs = client.activate_jobs("shed_work", request_timeout_ms=5_000)
        assert len(jobs) == 1
        monkeypatch.undo()
        jobs += client.activate_jobs("shed_work", request_timeout_ms=5_000)
        assert len(jobs) == 2
        for job in jobs:
            client.complete_job(job.key, {})

    def test_create_with_result(self, stack):
        client, _ = stack
        client.deploy_resource(("wr.bpmn", one_task("wr", "wr_work")))
        worker = JobWorker(client, "wr_work",
                           lambda job: {"answer": job.variables.get("n", 0) * 2},
                           poll_interval_s=0.02).start()
        try:
            result = client.create_instance_with_result(
                "wr", variables={"n": 21}, timeout_s=10,
            )
            assert result.variables.get("answer") == 42
            assert result.variables.get("n") == 21
        finally:
            worker.stop()

    def test_rejection_maps_to_grpc_status(self, stack):
        client, _ = stack
        with pytest.raises(grpc.RpcError) as err:
            client.create_instance("does-not-exist")
        assert err.value.code() == grpc.StatusCode.NOT_FOUND

    def test_invalid_variables_rejected(self, stack):
        client, _ = stack
        with pytest.raises(grpc.RpcError) as err:
            client._create(
                __import__("zeebe_tpu.gateway.proto.gateway_pb2",
                           fromlist=["x"]).CreateProcessInstanceRequest(
                    bpmnProcessId="p", variables="[1,2]")
            )
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT

    def test_publish_message_and_signal(self, stack):
        client, _ = stack
        msg_model = to_bpmn_xml(
            Bpmn.create_executable_process("msgp")
            .start_event("s")
            .intermediate_catch_message("c", message_name="go", correlation_key="=key")
            .end_event("e").done()
        )
        client.deploy_resource(("m.bpmn", msg_model))
        instance = client.create_instance("msgp", variables={"key": "k-1"})
        assert client.publish_message("go", "k-1") > 0
        result_deadline = time.time() + 5
        # instance completes shortly after correlation
        sig_key = client.broadcast_signal("noop-signal")
        assert sig_key > 0

    def test_cancel_instance(self, stack):
        client, _ = stack
        client.deploy_resource(("cx.bpmn", one_task("cx", "cx_work")))
        instance = client.create_instance("cx")
        client.cancel_instance(instance.process_instance_key)
        assert client.activate_jobs("cx_work") == []

    def test_fail_and_retry_flow(self, stack):
        client, _ = stack
        client.deploy_resource(("fr.bpmn", one_task("fr", "fr_work")))
        client.create_instance("fr")
        [job] = client.activate_jobs("fr_work")
        client.fail_job(job.key, retries=1, error_message="transient")
        [job2] = client.activate_jobs("fr_work")
        assert job2.key == job.key
        assert job2.retries == 1
        client.complete_job(job2.key)

    def test_set_variables(self, stack):
        client, _ = stack
        client.deploy_resource(("sv.bpmn", one_task("sv", "sv_work")))
        instance = client.create_instance("sv", variables={"a": 1})
        client.set_variables(instance.process_instance_key, {"b": 2})
        [job] = client.activate_jobs("sv_work")
        assert job.variables == {"a": 1, "b": 2}
        client.complete_job(job.key)


class TestJobWorker:
    def test_worker_processes_many_jobs(self, stack):
        client, _ = stack
        client.deploy_resource(("wk.bpmn", one_task("wk", "wk_work")))
        for i in range(10):
            client.create_instance("wk", variables={"i": i})
        worker = JobWorker(client, "wk_work", lambda job: {},
                           poll_interval_s=0.02).start()
        try:
            deadline = time.time() + 15
            while worker.handled_count < 10 and time.time() < deadline:
                time.sleep(0.05)
            assert worker.handled_count == 10
        finally:
            worker.stop()

    def test_failing_handler_fails_job(self, stack):
        client, _ = stack
        client.deploy_resource(("wf.bpmn", one_task("wf", "wf_work")))
        client.create_instance("wf")

        def boom(job):
            raise RuntimeError("handler exploded")

        worker = JobWorker(client, "wf_work", boom, poll_interval_s=0.02).start()
        try:
            deadline = time.time() + 10
            while worker.failed_count < 1 and time.time() < deadline:
                time.sleep(0.05)
            assert worker.failed_count >= 1
        finally:
            worker.stop()

    @pytest.mark.parametrize("threads, side_by_side", [(1, False), (4, True)])
    def test_jobs_of_one_activation_share_the_handler_threads(
            self, stack, threads, side_by_side):
        """Four jobs that one poll activates: on four handler threads they
        are held side by side, on one thread one after another."""
        client, _ = stack
        job_type = f"wc{threads}_work"
        client.deploy_resource((f"wc{threads}.bpmn",
                                one_task(f"wc{threads}", job_type)))
        for _ in range(4):
            client.create_instance(f"wc{threads}")
        lock = threading.Lock()
        holding, most = [0], [0]

        def hold(job):
            with lock:
                holding[0] += 1
                most[0] = max(most[0], holding[0])
            time.sleep(0.2)
            with lock:
                holding[0] -= 1
            return {}

        worker = JobWorker(client, job_type, hold, poll_interval_s=0.02)
        worker.HANDLER_THREADS = threads
        worker.start()
        try:
            deadline = time.time() + 15
            while worker.handled_count < 4 and time.time() < deadline:
                time.sleep(0.02)
            assert worker.handled_count == 4
            assert (most[0] > 1) == side_by_side
            assert most[0] <= threads
        finally:
            worker.stop()

    def test_worker_holds_at_most_max_jobs_active(self, stack):
        """The poller asks for the room ``max_jobs_active`` leaves, so the
        jobs activated for the worker and not yet finished never pass it."""
        client, _ = stack
        client.deploy_resource(("wm.bpmn", one_task("wm", "wm_work")))
        for _ in range(6):
            client.create_instance("wm")
        asked, activate = [], client.activate_jobs

        def counting(job_type, max_jobs=32, **kw):
            jobs = activate(job_type, max_jobs=max_jobs, **kw)
            asked.append((max_jobs, len(jobs), worker._active))
            return jobs

        def hold(job):
            time.sleep(0.1)
            return {}

        worker = JobWorker(client, "wm_work", hold, poll_interval_s=0.02,
                           max_jobs_active=2)
        client.activate_jobs = counting
        try:
            worker.start()
            deadline = time.time() + 15
            while worker.handled_count < 6 and time.time() < deadline:
                time.sleep(0.02)
            assert worker.handled_count == 6
            assert all(1 <= room <= 2 and got <= room and got + active <= 2
                       for room, got, active in asked), asked
        finally:
            worker.stop()
            del client.activate_jobs


def parked(runtime, job_type: str) -> int:
    return sum(map(len, runtime.jobs_hub._parked.get(job_type, {}).values()))


def wait_until(predicate, timeout_s: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


class TestLongPollingWorker:
    def test_worker_sends_its_request_timeout(self, stack):
        client, _ = stack
        sent, activate = [], client.activate_jobs

        def recording(job_type, **kw):
            sent.append(kw["request_timeout_ms"])
            return activate(job_type, **kw)

        client.activate_jobs = recording
        worker = JobWorker(client, "wt_nothing", lambda job: {})
        try:
            worker.start()
            assert wait_until(lambda: sent)
        finally:
            worker.stop()
            del client.activate_jobs
        assert set(sent) == {JobWorker.REQUEST_TIMEOUT_MS} == {10_000}

    def test_a_job_made_after_the_poll_parked_needs_no_second_poll(self, stack):
        client, runtime = stack
        client.deploy_resource(("wl.bpmn", one_task("wl", "wl_work")))
        answers, activate = [], client.activate_jobs

        def counting(job_type, **kw):
            jobs = activate(job_type, **kw)
            answers.append(len(jobs))
            return jobs

        client.activate_jobs = counting
        worker = JobWorker(client, "wl_work", lambda job: {})
        try:
            worker.start()
            assert wait_until(lambda: parked(runtime, "wl_work") == 1)
            client.create_instance("wl")
            assert wait_until(lambda: worker.handled_count == 1)
        finally:
            worker.stop()
            del client.activate_jobs
        # the job came back on the worker's first poll, the one that parked
        assert answers[0] == 1

    def test_stop_cancels_a_parked_poll(self, stack):
        client, runtime = stack
        client.deploy_resource(("ws.bpmn", one_task("ws", "ws_work")))
        worker = JobWorker(client, "ws_work", lambda job: {}).start()
        assert wait_until(lambda: parked(runtime, "ws_work") == 1)
        started = time.monotonic()
        worker.stop()
        assert time.monotonic() - started < 1.0
        # the gateway took the cancelled poll out of the queue: a job made
        # now is activated by nobody
        assert wait_until(lambda: parked(runtime, "ws_work") == 0, 1.0)
        client.create_instance("ws")
        [job] = client.activate_jobs("ws_work", request_timeout_ms=5_000)
        assert worker.handled_count == 0
        client.complete_job(job.key, {})

    def test_only_a_refused_or_failed_poll_backs_off(self, stack):
        client, _ = stack
        calls = []

        def answering(job_type, **kw):
            calls.append(time.monotonic())
            if len(calls) <= 3:
                raise RuntimeError("refused")
            if len(calls) >= 9:
                time.sleep(0.05)
            return []       # empty at its request timeout

        client.activate_jobs = answering
        worker = JobWorker(client, "wb_nothing", lambda job: {},
                           poll_interval_s=0.1, max_backoff_s=0.4)
        try:
            worker.start()
            assert wait_until(lambda: len(calls) >= 9)
        finally:
            worker.stop()
            del client.activate_jobs
        gaps = [b - a for a, b in zip(calls, calls[1:])]
        # after each refusal: 0.1, 0.2, 0.4 s; after an empty answer: at once,
        # five polls in less time than one backoff
        assert all(g >= 0.95 * b for g, b in zip(gaps[:3], (0.1, 0.2, 0.4))), gaps
        assert sum(gaps[3:8]) < 0.1, gaps


class TestEvaluateDecision:
    def test_evaluate_decision_rpc(self, stack):
        import json as _json

        from zeebe_tpu.gateway.proto import gateway_pb2 as pb
        from tests.test_dmn import DISH_DMN

        client, _ = stack
        client.deploy_resource(("dish.dmn", DISH_DMN))
        stub = client.channel.unary_unary(
            "/gateway_protocol.Gateway/EvaluateDecision",
            request_serializer=pb.EvaluateDecisionRequest.SerializeToString,
            response_deserializer=pb.EvaluateDecisionResponse.FromString,
        )
        resp = stub(pb.EvaluateDecisionRequest(
            decisionId="dish",
            variables=_json.dumps({"season": "Winter", "guestCount": 12}),
        ))
        assert _json.loads(resp.decisionOutput) == "Pasta"
        assert resp.decisionId == "dish"
        [d] = resp.evaluatedDecisions
        assert d.matchedRules[0].ruleIndex == 2


class TestModificationRpcs:
    def test_modify_and_delete_resource(self, stack):
        import json as _json

        from zeebe_tpu.gateway.proto import gateway_pb2 as pb

        client, _ = stack
        deployed = client.deploy_resource(("mod.bpmn", one_task("modp", "mod_work")))
        instance = client.create_instance("modp")
        jobs = client.activate_jobs("mod_work")
        [job] = [j for j in jobs if j.process_instance_key == instance.process_instance_key]
        modify = client.channel.unary_unary(
            "/gateway_protocol.Gateway/ModifyProcessInstance",
            request_serializer=pb.ModifyProcessInstanceRequest.SerializeToString,
            response_deserializer=pb.ModifyProcessInstanceResponse.FromString,
        )
        modify(pb.ModifyProcessInstanceRequest(
            processInstanceKey=instance.process_instance_key,
            activateInstructions=[
                pb.ModifyProcessInstanceRequest.ActivateInstruction(elementId="e")],
            terminateInstructions=[
                pb.ModifyProcessInstanceRequest.TerminateInstruction(
                    elementInstanceKey=job.element_instance_key)],
        ))
        # the instance jumped to the end event and completed
        remaining = [j for j in client.activate_jobs("mod_work")
                     if j.process_instance_key == instance.process_instance_key]
        assert remaining == []
        # delete the definition: new instances are rejected
        delete = client.channel.unary_unary(
            "/gateway_protocol.Gateway/DeleteResource",
            request_serializer=pb.DeleteResourceRequest.SerializeToString,
            response_deserializer=pb.DeleteResourceResponse.FromString,
        )
        delete(pb.DeleteResourceRequest(
            resourceKey=deployed["processes"][0]["processDefinitionKey"]))
        # deletion distributes asynchronously, like deployment: wait until no
        # partition resolves the id before asserting the NOT_FOUND rejection
        from zeebe_tpu.testing import await_resource_absent

        _client, runtime = stack
        await_resource_absent(runtime, ["modp"])
        with pytest.raises(grpc.RpcError) as err:
            client.create_instance("modp")
        assert err.value.code() == grpc.StatusCode.NOT_FOUND
