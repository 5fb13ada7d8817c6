"""The process definitions the gates, the chip smoke and the tests drive, and
two drivers that run them to completion on an ``EngineHarness``.

The definitions are ``BASELINE.json``'s configurations (one_task, the
exclusive-gateway chain, the parallel fork/join, the ragged eight-definition
mix), upstream's ``ten_tasks`` fixture, and the two adversarial shapes whose
per-instance-unique inputs no burst template can share. The drivers read no
clock: what they return is a count of work.
"""

from __future__ import annotations

from zeebe_tpu.logstreams import LogAppendEntry
from zeebe_tpu.models.bpmn import Bpmn
from zeebe_tpu.protocol import RecordType, ValueType, command
from zeebe_tpu.protocol.intent import (
    JobIntent,
    MessageIntent,
    ProcessInstanceIntent,
)
from zeebe_tpu.testing import EngineHarness


def one_task(pid="one_task"):
    return (
        Bpmn.create_executable_process(pid)
        .start_event("start").service_task("task", job_type=f"work_{pid}")
        .end_event("end").done()
    )


def exclusive_chain(pid="excl_chain"):
    """start → 5 exclusive gateways → end (config #2: sequence-flow-only)."""
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(5):
        b = (
            b.exclusive_gateway(f"gw{i}")
            .condition_expression(f"x > {10 * i}")
            .exclusive_gateway(f"m{i}")
            .move_to_element(f"gw{i}")
            .default_flow()
            .connect_to(f"m{i}")
            .move_to_element(f"m{i}")
        )
    return b.end_event("e").done()


def fork_join(pid="fork_join"):
    """Parallel fan-out/fan-in (config #3), service tasks on both branches."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .parallel_gateway("fork")
        .service_task("a", job_type=f"a_{pid}")
        .parallel_gateway("join")
        .end_event("e")
        .move_to_element("fork")
        .service_task("b", job_type=f"b_{pid}")
        .connect_to("join")
        .done()
    )


def ten_tasks(pid="ten_tasks"):
    """10 sequential service tasks (reference fixture:
    benchmarks/project/src/main/resources/bpmn/ten_tasks.bpmn)."""
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(10):
        b = b.service_task(f"t{i}", job_type=f"work_{pid}")
    return b.end_event("e").done()


def ten_tasks_io(pid="ten_tasks_io"):
    """ten_tasks with input+output mappings on every task — the io-mapped
    elements ride the kernel (VERDICT r2 item 5) instead of host-escaping."""
    b = Bpmn.create_executable_process(pid).start_event("s")
    for i in range(10):
        b = (
            b.service_task(f"t{i}", job_type=f"work_{pid}")
            .zeebe_input("= base", f"local{i}")
            .zeebe_output(f"= local{i}", f"result{i}")
        )
    return b.end_event("e").done()


def subprocess_boundary(pid="sub_bnd"):
    """Embedded sub-process + timer-boundary task (kernel scope + boundary
    wait-state paths under load)."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .sub_process("sub")
        .start_event("is_")
        .service_task("inner", job_type=f"inner_{pid}")
        .boundary_timer("tb", attached_to="inner", duration="PT1H")
        .end_event("bnd_e")
        .move_to_element("inner")
        .end_event("ie")
        .sub_process_done()
        .end_event("e")
        .done()
    )


def mixed_definitions():
    """8 ragged definitions (config #5): varying task counts and routing."""
    out = [one_task("mx_one"), exclusive_chain("mx_excl"), fork_join("mx_fj")]
    for n in (2, 3, 4):
        b = Bpmn.create_executable_process(f"mx_chain{n}").start_event("s")
        for i in range(n):
            b = b.service_task(f"t{i}", job_type=f"work_mx_chain{n}")
        out.append(b.end_event("e").done())
    b = (
        Bpmn.create_executable_process("mx_route")
        .start_event("s")
        .exclusive_gateway("gw")
        .condition_expression("x > 10")
        .service_task("big", job_type="work_mx_route")
        .end_event("e1")
        .move_to_element("gw")
        .default_flow()
        .service_task("small", job_type="work_mx_route")
        .end_event("e2")
        .done()
    )
    out.append(b)
    b = (
        Bpmn.create_executable_process("mx_par3")
        .start_event("s")
        .parallel_gateway("f")
        .service_task("p0", job_type="work_mx_par3")
        .parallel_gateway("j")
        .end_event("e")
        .move_to_element("f")
        .service_task("p1", job_type="work_mx_par3")
        .connect_to("j")
        .move_to_element("f")
        .service_task("p2", job_type="work_mx_par3")
        .connect_to("j")
        .done()
    )
    out.append(b)
    return out


def adversarial_gateway(pid="adv_gw"):
    """Routing on a per-instance-unique variable: every instance's condition
    input differs, so burst-template fingerprints can never collide."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .exclusive_gateway("gw")
        .condition_expression("x > 500000")
        .service_task("hi", job_type=f"hi_{pid}")
        .end_event("e1")
        .move_to_element("gw")
        .default_flow()
        .service_task("lo", job_type=f"lo_{pid}")
        .end_event("e2")
        .done()
    )


def adversarial_message(pid="adv_msg"):
    """Per-instance-unique message correlation keys — correlation state and
    subscriptions cannot share templates across instances."""
    return (
        Bpmn.create_executable_process(pid)
        .start_event("s")
        .service_task("t", job_type=f"work_{pid}")
        .intermediate_catch_message("wait", "adv_pay", "=uid")
        .end_event("e")
        .done()
    )


# ---------------------------------------------------------------------------
# drivers: a workload run to completion on an EngineHarness, counted


def kernel_harness(**kwargs):
    """An ``EngineHarness`` on the kernel backend as a broker runs it: a
    burst-template hit is instantiated and counted, where the test suite's
    harness re-runs the slow path beside every hit to audit it."""
    harness = EngineHarness(use_kernel_backend=True, **kwargs)
    harness.kernel_backend.audit_templates = False
    return harness


def completed_instances(harness, after_position: int) -> int:
    """Process instances whose PROCESS element completed after the position."""
    return sum(
        1 for view in harness.stream.scan_filtered(
            after_position + 1, int(RecordType.EVENT),
            int(ValueType.PROCESS_INSTANCE),
            int(ProcessInstanceIntent.ELEMENT_COMPLETED))
        if view.value.get("bpmnElementType") == "PROCESS")


def _counts(harness, start_position: int, instances: int) -> dict:
    return {
        "instances": instances,
        "completed": completed_instances(harness, start_position),
        "transitions": harness.count_transitions(start_position),
    }


def drive(harness, models, n_instances: int, variables: dict) -> dict:
    """Create ``n_instances`` spread evenly over the deployed ``models`` as
    one backlog, then complete every job in type waves until none is left.
    Returns the instances created and completed and the PROCESS_INSTANCE
    events the run appended."""
    start_position = harness.stream.last_position
    per_def = max(1, n_instances // len(models))
    for m in models:
        harness.inject_creations(m.process_id, per_def, variables)
    harness.pump()
    scan_from = start_position
    while jobs := harness.pending_job_keys(scan_from):
        scan_from = harness.stream.last_position
        harness.complete_in_type_waves(jobs)
    return _counts(harness, start_position, per_def * len(models))


def drive_adversarial_cold(harness, models, n_instances: int) -> dict:
    """The ~0 % template-hit workload over the deployed ``models``, an
    ``adversarial_gateway()`` and an ``adversarial_message()``: per-instance
    unique values feed a device condition and the message correlation keys,
    and completions write unique result variables, so every burst pays
    capture instead of template patching (reference shape:
    EngineLargeStatePerformanceTest.java:138-144 stresses cold state)."""
    gateway, message = models
    writer = harness.stream.writer
    start_position = harness.stream.last_position
    per_def = n_instances // 2
    for i in range(per_def):
        harness.inject_creations(gateway.process_id, 1,
                                 {"x": i * 997, "uid": f"g-{i}"})
        harness.inject_creations(message.process_id, 1, {"uid": f"m-{i}"})
    harness.pump()
    scan_from = start_position
    while jobs := harness.pending_job_keys(scan_from):
        scan_from = harness.stream.last_position
        for n, (_type, _pi, key) in enumerate(jobs):
            writer.try_write([LogAppendEntry(command(
                ValueType.JOB, JobIntent.COMPLETE,
                {"variables": {"result": f"r-{n}"}}, key=key))])
        harness.pump()
    for i in range(per_def):
        writer.try_write([LogAppendEntry(command(
            ValueType.MESSAGE, MessageIntent.PUBLISH,
            {"name": "adv_pay", "correlationKey": f"m-{i}",
             "timeToLive": 60_000, "variables": {"paid": i}}))])
    harness.pump()
    return _counts(harness, start_position, per_def * 2)
