"""The reference workloads run to completion on the kernel backend, counted.

Each case drives one of ``BASELINE.json``'s configurations (or one of the
honest-worst-case shapes) as a backlog through the real partition path —
committed log → stream processor → kernel backend and burst templates →
events appended — and asserts what is deterministic about the run: every
instance completes, the log holds exactly the lifecycle events the
definition spells, every command went through the kernel, and the shadow
oracle agreed. No case reads a clock: on XLA's CPU backend a run is a count
of work, and speed is ``BENCHMARK.json``'s to judge, on the chip.
"""

from __future__ import annotations

import pytest

from zeebe_tpu.testing import workloads

# PROCESS_INSTANCE events one instance appends: four per element it visits
# (activating, activated, completing, completed), the process itself
# included, and one per sequence flow it takes. With x = 5 mx_excl takes
# every default flow and mx_route its "small" branch.
TRANSITIONS = {
    "one_task": 4 * 4 + 2,
    "excl_chain": 4 * 13 + 11,
    "mx_one": 4 * 4 + 2,
    "mx_excl": 4 * 13 + 11,
    "mx_fj": 4 * 7 + 6,
    "mx_chain2": 4 * 5 + 3,
    "mx_chain3": 4 * 6 + 4,
    "mx_chain4": 4 * 7 + 5,
    "mx_route": 4 * 5 + 3,
    "mx_par3": 4 * 8 + 8,
}
# job completions one instance needs; with its creation, its commands
JOBS = {"one_task": 1, "excl_chain": 0, "mx_one": 1, "mx_excl": 0,
        "mx_fj": 2, "mx_chain2": 2, "mx_chain3": 3, "mx_chain4": 4,
        "mx_route": 1, "mx_par3": 3}


@pytest.fixture
def harness():
    h = workloads.kernel_harness()
    yield h
    h.close()


def _assert_all_on_the_kernel(harness, mark: dict, commands: int) -> None:
    kernel = harness.kernel_backend
    routed = kernel.accounting.delta_since(mark)
    assert routed["host"] == 0, routed
    assert routed["kernel"] == commands, routed
    # the deployment is the one command that heads a host-sequential record
    assert set(kernel.fallback_reasons) <= {
        "head-sequential:DEPLOYMENT.CREATE"}, dict(kernel.fallback_reasons)
    assert kernel.template_hits > 0
    assert kernel.health.shadow_mismatches == 0


@pytest.mark.parametrize("models, per_definition, variables", [
    pytest.param([workloads.one_task()], 600, {}, id="one_task"),
    pytest.param([workloads.exclusive_chain()], 600, {"x": 25},
                 id="exclusive_chain"),
    pytest.param(workloads.mixed_definitions(), 40, {"x": 5}, id="mixed_8"),
])
def test_every_instance_completes_on_the_kernel(harness, models,
                                                per_definition, variables):
    harness.deploy(*models)
    mark = harness.kernel_backend.accounting.mark()
    counts = workloads.drive(harness, models, per_definition * len(models),
                             variables)
    ids = [m.process_id for m in models]
    assert counts["instances"] == per_definition * len(models)
    assert counts["completed"] == counts["instances"]
    assert counts["transitions"] == per_definition * sum(
        TRANSITIONS[i] for i in ids)
    _assert_all_on_the_kernel(
        harness, mark, per_definition * sum(1 + JOBS[i] for i in ids))


def test_adversarial_cold_completes_without_template_hits(harness):
    """Unique condition inputs, correlation keys and result variables: ~0 %
    template hits by construction, so every burst pays capture."""
    models = [workloads.adversarial_gateway(), workloads.adversarial_message()]
    harness.deploy(*models)
    counts = workloads.drive_adversarial_cold(harness, models, 600)
    assert counts["completed"] == counts["instances"] == 600
    kernel = harness.kernel_backend
    hit_rate = kernel.template_hits / max(
        1, kernel.template_hits + kernel.template_misses)
    assert hit_rate <= 0.05, (kernel.template_hits, kernel.template_misses)
    assert kernel.health.shadow_mismatches == 0


def test_one_task_completes_over_large_durable_state():
    """one_task on the durable backend over 120k parked instances' worth of
    pre-existing state (element instance + job + variables each, ~0.3 GB
    serialized) — the reference's large-state shape
    (EngineLargeStatePerformanceTest: pre-existing state, then the standard
    flow on top of it)."""
    from zeebe_tpu.state import ColumnFamilyCode

    n_warm, n_instances = 120_000, 3000
    harness = workloads.kernel_harness(durable=True, consistency_checks=False)
    try:
        db = harness.db
        harness.deploy(workloads.one_task("one_task_warm"))
        payload = "y" * 2600
        base_key = 1 << 40  # far above the engine's key space
        for start in range(0, n_warm, 10_000):
            with db.transaction():
                ei = db.column_family(ColumnFamilyCode.ELEMENT_INSTANCE_KEY)
                jobs = db.column_family(ColumnFamilyCode.JOBS)
                variables = db.column_family(ColumnFamilyCode.VARIABLES)
                for i in range(start, start + 10_000):
                    k = base_key + i * 4
                    ei.put((k,), {"state": 4, "elementId": "warm_task",
                                  "processInstanceKey": k, "jobKey": k + 1})
                    jobs.put((k + 1,), {"type": "warm_fake", "retries": 3,
                                        "elementInstanceKey": k,
                                        "processInstanceKey": k})
                    variables.put((k, "payload"), payload)
        db.checkpoint()
        assert db.approx_bytes() >= n_warm * 2600

        mark = harness.kernel_backend.accounting.mark()
        counts = workloads.drive(
            harness, [workloads.one_task("one_task_warm")], n_instances, {})
        assert counts["completed"] == counts["instances"] == n_instances
        assert counts["transitions"] == n_instances * TRANSITIONS["one_task"]
        _assert_all_on_the_kernel(harness, mark, 2 * n_instances)
    finally:
        harness.close()
