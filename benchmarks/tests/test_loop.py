"""The loop closed on completions, the two pass-throughs (``workers.options``,
``layout.kernel_mesh_shards``) and what an absent key means: exactly what the
harness did before it knew the key. The generator runs against a fake client
and fake workers: no cluster, no gRPC, no jax."""

import json
import queue
import threading
import time
from types import SimpleNamespace

import pytest

import definitions as defs
import loadgen
import run
import served

WORKERS = {"per_job_type": 2, "max_backoff_s": 0.1, "completion_delay_ms": 0,
           "complete_with_payload": False}


def mix(loop: dict, definitions=None, workers=None) -> dict:
    return {"loop": loop, "payload": None, "give_up_s": 10.0,
            "definitions": definitions or [{"kind": "task_chain", "id": "two",
                                            "tasks": 2}],
            "workers": workers or WORKERS}


class FakeBroker:
    """Instances of task chains: a create is acknowledged at once and opens
    the first job; a job's completion opens the next, ``hold_s`` later."""

    def __init__(self, tasks: int, hold_s: float) -> None:
        self.tasks, self.hold_s = tasks, hold_s
        self.lock = threading.Lock()
        self.jobs: queue.SimpleQueue = queue.SimpleQueue()
        self.left: dict = {}          # instance key -> jobs not yet completed
        self.open_of: dict = {}       # creating thread -> its last instance
        self.most_open_of_one_client = 0
        self.worker_calls: list = []

    def create(self, pid: str):
        me = threading.get_ident()
        with self.lock:
            key = len(self.left) + 1
            still_open = sum(1 for k in self.open_of.get(me, ())
                             if self.left[k] > 0)
            self.most_open_of_one_client = max(self.most_open_of_one_client,
                                               still_open + 1)
            self.open_of.setdefault(me, []).append(key)
            self.left[key] = self.tasks
        self._open_job(key, pid)
        return SimpleNamespace(process_instance_key=key)

    def _open_job(self, key: int, pid: str) -> None:
        self.jobs.put(SimpleNamespace(key=1000 * key + self.left[key],
                                      process_instance_key=key,
                                      bpmn_process_id=pid))

    def complete(self, job_key: int) -> None:
        key = job_key // 1000
        time.sleep(self.hold_s)
        with self.lock:
            self.left[key] -= 1
            more = self.left[key] > 0
        if more:
            self._open_job(key, "two")


@pytest.fixture
def fake(monkeypatch):
    import zeebe_tpu.client as client_lib

    broker = FakeBroker(tasks=2, hold_s=0.02)

    class FakeClient:
        def __init__(self, address):
            assert address == "fake:1"

        def deploy_resource(self, *resources):
            return True

        def create_instance(self, pid, variables=None):
            time.sleep(0.002)       # the acknowledgement's way back
            return broker.create(pid)

        def complete_job(self, key, variables):
            broker.complete(key)

        def close(self):
            pass

    class FakeWorker:
        def __init__(self, *args, **kw):
            broker.worker_calls.append((args, kw))
            self.client, self.job_type, self.handler = args
            self.handled_count = self.failed_count = 0
            self.running = True

        def start(self):
            threading.Thread(target=self._loop, daemon=True).start()
            return self

        def _loop(self):
            while self.running:
                try:
                    job = broker.jobs.get(timeout=0.05)
                except queue.Empty:
                    continue
                self.handler(None, job)
                self.handled_count += 1

        def stop(self):
            self.running = False

    monkeypatch.setattr(client_lib, "ZeebeTpuClient", FakeClient)
    monkeypatch.setattr(client_lib, "JobWorker", FakeWorker)
    return broker


def drive(traffic: dict, tmp_path, seconds: float = 0.6) -> list:
    gen = loadgen.LoadGen("fake:1", traffic, 1, seed=2**31 + 5,
                          out_dir=str(tmp_path))
    gen.deploy()
    gen.warm()
    time.sleep(0.2)
    reply = gen.window(time.monotonic() + 0.05, seconds)
    gen.stop()
    with open(reply["records_file"]) as f:
        return [json.loads(line) for line in f]


def test_closed_on_completion_a_client_never_has_two_instances_open(fake, tmp_path):
    records = drive(mix({"kind": "closed", "clients": 3, "on": "completion"}),
                    tmp_path)
    assert fake.most_open_of_one_client == 1
    window = [r for r in records if r["phase"] == "window"]
    # two jobs of 20 ms an instance, three clients: a few dozen, not hundreds
    assert 3 <= len(window) <= 3 * 0.6 / 0.04 + 3
    assert all(r["ok"] and r["sent"] - r["due"] < 0.05 for r in window)
    # every instance the clients were told about is done but the last of each
    assert sum(1 for n in fake.left.values() if n > 0) <= 3


def test_on_absent_sends_on_the_acknowledgement_as_before(fake, tmp_path):
    records = drive(mix({"kind": "closed", "clients": 3}), tmp_path,
                    seconds=0.2)
    # acknowledged at once, completed 40 ms later: a client has many open
    assert fake.most_open_of_one_client > 5
    assert len([r for r in records if r["phase"] == "window"]) > 30


def test_absent_keys_give_the_workers_the_arguments_they_always_got(fake, tmp_path):
    gen = loadgen.LoadGen("fake:1", mix({"kind": "closed", "clients": 1}), 1,
                          seed=1, out_dir=str(tmp_path))
    assert gen.jobs_of is None
    gen.deploy()
    gen.stop()
    assert len(fake.worker_calls) == 2          # per_job_type x one job type
    for args, kw in fake.worker_calls:
        assert len(args) == 3 and args[1] == "work_two" and callable(args[2])
        assert kw == {"timeout_ms": 60_000, "auto_complete": False,
                      "max_backoff_s": 0.1}


OWN = {"timeout_ms": 60_000, "auto_complete": False, "max_backoff_s": 0.1}


def test_worker_options_go_beside_the_harness_own():
    assert loadgen.worker_arguments(WORKERS) == OWN
    options = {"stream_enabled": True, "max_jobs_active": 8}
    assert loadgen.worker_arguments({**WORKERS, "options": options}) == {
        **OWN, **options}


@pytest.mark.parametrize("name", ["request_timeout_ms", "handler", *OWN])
def test_an_option_jobworker_lacks_or_the_harness_owns_is_refused_by_name(name):
    with pytest.raises(ValueError, match=name):
        loadgen.worker_arguments({**WORKERS, "options": {name: 1}})


def test_an_unknown_option_is_refused_before_there_is_a_cluster(monkeypatch):
    what = run.resolve_cell("single1x1.ten_tasks_steady")
    what["traffic"]["workers"]["options"] = {"no_such_option": 1}
    monkeypatch.setattr(run, "resolve_cell", lambda name, manifest=None: what)
    monkeypatch.setattr(served, "Served", None)     # building it would raise
    said = []
    monkeypatch.setattr(run, "say", said.append)
    assert run.main(["--workload", "single1x1.ten_tasks_steady", "--seed", "1",
                     "--seconds", "1"]) == run.REFUSED_EXIT
    assert "no_such_option" in said[-1] and "ten_tasks_steady" in said[-1]


def test_kinds_whose_jobs_cannot_be_counted_are_refused_by_name():
    counted = {d["id"]: defs.jobs_per_instance(d) for d in defs.build_definitions([
        {"kind": "task_chain", "id": "ten", "tasks": 10},
        {"kind": "fork_join", "id": "fj", "branches": 3},
        {"kind": "embedded_subprocess", "id": "sub"},
        {"kind": "exclusive_chain", "id": "excl", "gateways": 2},
        {"kind": "route", "id": "rt"}])}
    assert counted == {"ten": 10, "fj": 3, "sub": 2, "excl": 0, "rt": None}
    loop = {"kind": "closed", "clients": 2, "on": "completion"}
    assert loadgen.jobs_to_wait_for(mix(loop)) == {"two": 2}
    for kind in ("route", "exclusive_chain"):
        spec = {"kind": kind, "id": "x", **({"gateways": 1}
                                            if kind == "exclusive_chain" else {})}
        with pytest.raises(ValueError, match=kind):
            loadgen.jobs_to_wait_for(mix(loop, [spec]))
    with pytest.raises(ValueError, match="loop.on"):
        loadgen.jobs_to_wait_for(mix({**loop, "on": "result"}))
    with pytest.raises(ValueError, match="loop.on"):
        loadgen.jobs_to_wait_for(mix({"kind": "open", "on": "completion"}))
    assert loadgen.jobs_to_wait_for(mix({"kind": "open"})) is None


def test_the_generator_is_started_as_it_always_was(monkeypatch, tmp_path):
    started = []

    class FakePopen:
        def __init__(self, argv, **kw):
            started.append((argv, kw))
            self.stdout = iter(())

    monkeypatch.setattr(run.subprocess, "Popen", FakePopen)
    run.Child("127.0.0.1:5", tmp_path / "mix.json", 3, 7, tmp_path)
    (argv, kw), = started
    assert argv[1:] == [str(run.HERE / "loadgen.py"), "--address", "127.0.0.1:5",
                        "--traffic", str(tmp_path / "mix.json"),
                        "--partitions", "3", "--seed", "7",
                        "--out-dir", str(tmp_path)]
    assert "JAX_PLATFORMS" not in kw["env"] and kw["text"] is True


@pytest.mark.parametrize("shards", [None, 4])
def test_the_cluster_gets_the_mesh_shards_only_where_the_layout_names_them(
        monkeypatch, tmp_path, shards):
    import zeebe_tpu.gateway as gateway_lib

    built = []

    class FakeRuntime:
        def __init__(self, **kw):
            built.append(kw)

        def start(self):
            pass

        def stop(self):
            pass

    class FakeGateway:
        address = "127.0.0.1:1"

        def __init__(self, runtime, bind):
            assert bind == "127.0.0.1:0"

        def start(self):
            pass

        def stop(self):
            pass

    monkeypatch.setattr(gateway_lib, "ClusterRuntime", FakeRuntime)
    monkeypatch.setattr(gateway_lib, "Gateway", FakeGateway)
    layout = {"brokers": 3, "partitions": 3, "replication_factor": 3}
    if shards is not None:
        layout["kernel_mesh_shards"] = shards
    served.Served(layout, tmp_path, served.Observed()).stop()
    (kw,) = built
    always = {"exporters_factory", "kernel_backend", "broker_count",
              "partition_count", "replication_factor", "directory",
              "backpressure_algorithm", "backpressure_enabled",
              "disk_min_free_bytes"}
    assert set(kw) == always | ({"kernel_mesh_shards"} if shards else set())
    assert (kw["broker_count"], kw["partition_count"], kw["replication_factor"],
            kw["directory"]) == (3, 3, 3, tmp_path)
    assert kw.get("kernel_mesh_shards") == shards


def test_the_arithmetic_of_a_closed_window():
    # two clients, an instance 0.5 s long, a window of 2 s from t0 = 10: each
    # client's next create is due when its last instance was done
    t0, seconds = 10.0, 2.0
    window, completed_at = [], {}
    for client in range(2):
        for i in range(4):
            due = t0 + 0.1 * client + 0.5 * i
            key = 10 * client + i
            window.append({"ok": True, "key": key, "due": due, "sent": due + 1e-4,
                           "ack": due + 0.01})
            completed_at[key] = due + 0.5
    completed_at[99] = t0 + 0.05        # sent in the warm-up, done in the window
    del completed_at[13]                # the second client's last never ends
    m = run.window_metrics(window, completed_at, list(completed_at.values()),
                           t0, seconds)
    # done inside the window: the warm-up's one, client 0's first three (its
    # fourth ends at the close itself: outside) and client 1's first three
    assert m["completed_per_s"] == pytest.approx(7 / 2.0)
    assert (m["attempted"], m["failed"], m["backlog_at_close"]) == (8, 1, 2)
    assert m["completion_p50_ms"] == pytest.approx(500.0)
    assert m["generator_late_p95_ms"] == pytest.approx(0.1)
