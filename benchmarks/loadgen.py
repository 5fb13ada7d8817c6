#!/usr/bin/env python3
"""The load generator and the job workers: a process of its own, as clients
are in every deployment. It never imports ``jax``; it talks to the gateway
only through ``ZeebeTpuClient``/``JobWorker`` over loopback gRPC, and to the
harness through JSON lines on stdin/stdout:

    {"cmd": "deploy"}        deploy the mix's definitions, start the workers
    {"cmd": "first_touch"}   one client, sequentially, definition order
    {"cmd": "warm"}          start the mix's traffic (the warm-up stretch)
    {"cmd": "window", "t0": <monotonic>, "seconds": s}
                             the measured stretch; answers once every request
                             due in it was answered or given up
    {"cmd": "stop"}          stop the workers, answer their counts, exit

Every request is stamped with the time it was **due** (``time.monotonic()``,
which parent and child share on one machine): in the open loop the schedule's
time, in the closed loop the moment its creator was free to send it: when its
last create was acknowledged or, with ``"on": "completion"``, when the
completion of its last instance's last job was acknowledged to a worker here.
A mix with ``messages`` has a publisher besides: each create of an instance
that waits for the message is followed by one publish with its correlation
key, stamped as a create is; the window answers once those are answered too.
"""

from __future__ import annotations

import argparse
import heapq
import inspect
import json
import os
import random
import sys
import threading
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))   # the checkout: zeebe_tpu.client
sys.path.insert(0, HERE)

import definitions as defs  # noqa: E402
import schedule  # noqa: E402


class Retrying:
    """A client under backpressure: retried with backoff and counted, never
    dropped silently (copied from ``chip_smoke.Retrying``; it also counts the
    RPCs sent, for ``gateway_shed_share``)."""

    def __init__(self, seed: int) -> None:
        import grpc

        self.grpc = grpc
        self.retryable = {grpc.StatusCode.RESOURCE_EXHAUSTED,
                          grpc.StatusCode.UNAVAILABLE,
                          grpc.StatusCode.DEADLINE_EXCEEDED}
        self.not_found = grpc.StatusCode.NOT_FOUND
        self.counts: Counter = Counter()
        self.lock = threading.Lock()
        self._rng = random.Random(seed)

    def call(self, what: str, fn, *args, not_found: str = "raise",
             give_up_at: float | None = None, **kw):
        """Returns ``(result, attempts, sheds)``; ``result`` is None where a
        NOT_FOUND was only counted (a job delivered twice) or the call was
        given up (``give_up_at`` passed, or an answer that no retry cures)."""
        delay, attempts, sheds = 0.01, 0, 0
        while True:
            attempts += 1
            try:
                return fn(*args, **kw), attempts, sheds
            except self.grpc.RpcError as err:
                code = err.code()
                with self.lock:
                    self.counts[f"{what}:{code.name}"] += 1
                    jitter = 0.5 + self._rng.random()
                sheds += code == self.grpc.StatusCode.RESOURCE_EXHAUSTED
                if code == self.not_found and not_found == "count":
                    return None, attempts, sheds
                known = code in self.retryable or (
                    code == self.not_found and not_found == "retry")
                if not known or (give_up_at is not None
                                 and time.monotonic() > give_up_at):
                    with self.lock:
                        self.counts[f"{what}:given_up"] += 1
                    return None, attempts, sheds
            time.sleep(delay * jitter)
            delay = min(delay * 2, 1.0)


def worker_arguments(workers: dict) -> dict:
    """The keyword arguments of every ``JobWorker`` of a mix: the harness's
    own three, and the mix's ``workers.options`` beside them. An option that
    ``JobWorker`` does not take is refused by name, and so is one of the
    harness's own: its handler and its count of acknowledged completions
    rest on ``auto_complete`` and ``timeout_ms``, and ``max_backoff_s`` is
    the mix's own key."""
    from zeebe_tpu.client import JobWorker

    # an activation whose response was lost comes back after this long, not
    # after the five-minute default
    own = {"timeout_ms": 60_000, "auto_complete": False,
           "max_backoff_s": float(workers["max_backoff_s"])}
    options = workers.get("options", {})
    taken = set(inspect.signature(JobWorker.__init__).parameters) - {
        "self", "client", "job_type", "handler", *own}
    if unknown := sorted(set(options) - taken):
        raise ValueError(f"workers.options {unknown}: a mix may set "
                         f"{sorted(taken)} of JobWorker's; {sorted(own)} are "
                         "the harness's own")
    return {**own, **options}


def jobs_to_wait_for(traffic: dict):
    """``loop.on`` absent: None, the loop is closed on acknowledgements.
    ``"completion"``: process id -> the jobs whose acknowledged completions
    tell a client that its instance is done; a definition whose count is
    nought or may depend on ``x`` is refused by name."""
    loop = traffic["loop"]
    if "on" not in loop:
        return None
    if loop["kind"] != "closed" or loop["on"] != "completion":
        raise ValueError(f"loop.on {loop['on']!r} in a loop of kind "
                         f"{loop['kind']!r}: known is \"completion\", closed")
    jobs = {}
    for spec, d in zip(traffic["definitions"],
                       defs.build_definitions(traffic["definitions"])):
        if spec["kind"] in ("message_catch", "timer_catch"):
            raise ValueError(
                f"definition {d['id']!r} of kind {spec['kind']!r} under a loop "
                "closed on completions: its instances wait for a message or a "
                "timer, which the workers cannot see")
        jobs[d["id"]] = defs.jobs_per_instance(d)
        if not jobs[d["id"]]:
            raise ValueError(
                f"definition {d['id']!r} of kind {spec['kind']!r} under a loop "
                "closed on completions: its instances run no job, or a number "
                "that may depend on x, so the workers cannot tell when one is done")
    return jobs


#: the keys of a mix's ``messages``
MESSAGE_KEYS = {"name", "publish_after_ms", "ttl_ms", "senders"}


def messages_of(traffic: dict):
    """The mix's ``messages`` and, for it, process id -> the variable that
    correlates its instances; None where the mix has no ``messages``. A
    definition that waits for a message no publisher sends, a publisher of a
    message no definition waits for, or a key missing is refused by name."""
    waiting = {d["id"]: (catch["message"], catch["correlation_variable"])
               for d in defs.build_definitions(traffic["definitions"])
               if (catch := defs.catch_of(d)) is not None and "message" in catch}
    spec = traffic.get("messages")
    name = None if spec is None else spec.get("name")
    if unsent := sorted(pid for pid, (m, _) in waiting.items() if m != name):
        raise ValueError(f"definitions {unsent} wait for a message that no "
                         "publisher sends (the mix's \"messages\")")
    if spec is None:
        return None
    if set(spec) != MESSAGE_KEYS:
        raise ValueError(f"messages: keys {sorted(spec)}, wanted "
                         f"{sorted(MESSAGE_KEYS)}")
    after = spec["publish_after_ms"]
    if not (isinstance(after, list) and after
            and all(isinstance(a, (int, float)) and a >= 0 for a in after)):
        raise ValueError(f"messages.publish_after_ms {after!r}: a list of "
                         "delays in ms, none negative")
    if not waiting:
        raise ValueError(f"messages: no definition waits for {name!r}")
    return spec, {pid: var for pid, (_m, var) in waiting.items()}


class Publisher:
    """The mix's ``messages``: for every create of a definition that waits
    for the message, one ``PublishMessage`` with that create's correlation
    key, ``publish_after_ms`` after the create was due (the list's delays
    dealt round the creates in the order they were made), sent from a pool of
    ``senders`` of its own and retried as a create is."""

    def __init__(self, gen: "LoadGen", spec: dict, waiting: dict) -> None:
        self.after_s = [float(a) / 1e3 for a in spec["publish_after_ms"]]
        self.name, self.ttl_ms = spec["name"], int(spec["ttl_ms"])
        self.waiting = waiting
        self.gen = gen
        self.pool = ThreadPoolExecutor(max_workers=int(spec["senders"]))
        self.cond = threading.Condition()
        self.heap: list = []    # (due, order, publish record)
        self.dealt = 0
        self.open = 0           # scheduled, not yet answered
        self.closed = False
        gen._spawn(self._run)

    def schedule(self, pid: str, variables: dict, due: float,
                 phase: str) -> None:
        var = self.waiting.get(pid)
        if var is None:
            return
        with self.cond:
            after = self.after_s[self.dealt % len(self.after_s)]
            rec = {"phase": phase, "pid": pid,
                   "correlation_key": variables[var], "create_due": due,
                   "due": due + after}
            heapq.heappush(self.heap, (rec["due"], self.dealt, rec))
            self.dealt += 1
            self.open += 1
            self.cond.notify()

    def _run(self) -> None:
        with self.cond:
            while not self.closed:
                if not self.heap:
                    self.cond.wait()
                    continue
                left = self.heap[0][0] - time.monotonic()
                if left > 0:
                    self.cond.wait(left)
                    continue
                self.pool.submit(self.gen._guard, self._publish,
                                 heapq.heappop(self.heap)[2])

    def _publish(self, rec: dict) -> None:
        gen = self.gen
        client = gen.client()
        rec["sent"] = time.monotonic()
        key, attempts, sheds = gen.retry.call(
            "publish", client.publish_message, self.name,
            rec["correlation_key"],
            variables=defs.message_variables(rec["correlation_key"]),
            ttl_ms=self.ttl_ms, give_up_at=rec["sent"] + gen.give_up_s)
        rec.update(ack=time.monotonic(), ok=key is not None, key=key,
                   attempts=attempts, sheds=sheds)
        with gen.lock:
            gen.publishes.append(rec)
            gen.rpcs += attempts
            gen.sheds += sheds
        with self.cond:
            self.open -= 1
            self.cond.notify_all()

    def drain(self) -> None:
        """Until every publish scheduled so far was answered or given up."""
        with self.cond:
            while self.open and self.gen.error is None:
                self.cond.wait(0.1)

    def close(self) -> None:
        with self.cond:
            self.closed = True
            self.cond.notify_all()
        self.pool.shutdown(wait=True, cancel_futures=True)


class LoadGen:
    def __init__(self, address: str, traffic: dict, partitions: int,
                 seed: int, out_dir: str) -> None:
        from zeebe_tpu.client import ZeebeTpuClient

        self.Client = ZeebeTpuClient
        self.worker_arguments = worker_arguments(traffic["workers"])
        self.jobs_of = jobs_to_wait_for(traffic)
        self.jobs_left: dict = {}   # instance key -> jobs still to complete
        self.done: dict = {}        # instance key -> set once none is left
        self.address = address
        self.traffic = traffic
        self.partitions = partitions
        self.seed = seed
        self.out_dir = out_dir
        self.retry = Retrying(seed)
        self.definitions = defs.build_definitions(traffic["definitions"])
        self.payload = defs.make_payload(traffic.get("payload"), seed)
        self.give_up_s = float(traffic.get("give_up_s", 10.0))
        self.records: list = []
        self.publishes: list = []
        self.completed_jobs: list = []   # keys of acknowledged completions
        self.rpcs = 0
        self.sheds = 0
        self.lock = threading.Lock()
        self.local = threading.local()
        self.clients: list = []
        self.workers: list = []
        self.warm_stop = threading.Event()
        self.warm_threads: list = []
        self.error: BaseException | None = None
        self.t0: float | None = None
        self.t_end: float | None = None
        messages = messages_of(traffic)
        self.publisher = None if messages is None else Publisher(self, *messages)

    def client(self):
        if not hasattr(self.local, "client"):
            self.local.client = self.Client(self.address)
            with self.lock:
                self.clients.append(self.local.client)
        return self.local.client

    # -- one create ---------------------------------------------------------

    def create(self, pid: str, variables: dict, due: float, phase: str) -> dict:
        if self.publisher is not None:
            self.publisher.schedule(pid, variables, due, phase)
        sent = time.monotonic()
        inst, attempts, sheds = self.retry.call(
            "create", self.client().create_instance, pid, variables=variables,
            not_found="retry", give_up_at=sent + self.give_up_s)
        ack = time.monotonic()
        rec = {"phase": phase, "pid": pid, "x": variables.get("x"), "due": due,
               "sent": sent, "ack": ack, "ok": inst is not None,
               "key": None if inst is None else inst.process_instance_key,
               "attempts": attempts, "sheds": sheds}
        if self.publisher is not None and pid in self.publisher.waiting:
            rec["correlation_key"] = variables[self.publisher.waiting[pid]]
        with self.lock:
            self.records.append(rec)
            self.rpcs += attempts
            self.sheds += sheds
        return rec

    # -- phases -------------------------------------------------------------

    def deploy(self) -> dict:
        from zeebe_tpu.client import JobWorker

        resources = [(f"{d['id']}.bpmn", defs.to_bpmn_xml(d))
                     for d in self.definitions]
        result, _, _ = self.retry.call(
            "deploy", self.client().deploy_resource, *resources,
            give_up_at=time.monotonic() + 60.0)
        if result is None:
            raise RuntimeError("the deployment was refused")
        w = self.traffic["workers"]
        delay_s = float(w["completion_delay_ms"]) / 1e3
        # upstream's worker answers every job with its own payload file
        returned = self.payload if w["complete_with_payload"] else {}

        def complete(_job_client, job, client) -> None:
            if delay_s:
                time.sleep(delay_s)     # the work a job stands for

            def acknowledged() -> bool:
                client.complete_job(job.key, returned)
                return True

            done, attempts, sheds = self.retry.call(
                "complete", acknowledged, not_found="count",
                give_up_at=time.monotonic() + 60.0)
            with self.lock:
                self.rpcs += attempts
                self.sheds += sheds
                if done:        # acknowledged: it has to be durable
                    self.completed_jobs.append(job.key)
                    if self.jobs_of is not None:
                        self._job_done(job)

        for job_type in defs.job_types(self.definitions):
            for _ in range(int(w["per_job_type"])):
                client = self.Client(self.address)
                self.clients.append(client)
                self.workers.append(JobWorker(
                    client, job_type,
                    lambda jc, job, client=client: complete(jc, job, client),
                    **self.worker_arguments).start())
        return {"definitions": len(resources), "workers": len(self.workers),
                "payload_bytes": defs.payload_bytes(self.payload)}

    def first_touch(self) -> dict:
        plan = defs.first_touch_plan(self.definitions, self.partitions,
                                     self.payload)
        recs = [self.create(pid, variables, time.monotonic(), "first_touch")
                for pid, variables in plan]
        if not all(r["ok"] for r in recs):
            raise RuntimeError("a first-touch create was refused")
        return {"keys": [r["key"] for r in recs]}

    def _job_done(self, job) -> None:
        """Under ``self.lock``: one job fewer stands between the job's
        instance and its end."""
        key = job.process_instance_key
        left = self.jobs_left.get(key, self.jobs_of[job.bpmn_process_id]) - 1
        self.jobs_left[key] = left
        if left == 0:
            self.done.setdefault(key, threading.Event()).set()

    def _await_completion(self, key: int) -> None:
        """Until the instance's last job was completed, or the window closed:
        the harness waits for what is open then, the client need not."""
        with self.lock:
            done = self.done.setdefault(key, threading.Event())
        while not done.is_set():
            # the window's end is known only once the window was opened
            left = 0.25 if self.t_end is None else self.t_end - time.monotonic()
            if left <= 0:
                break
            done.wait(left)
        with self.lock:
            self.done.pop(key, None)
            self.jobs_left.pop(key, None)

    def _closed_creator(self, plans: dict) -> None:
        """One closed-loop client: the warm-up's plan until the window opens,
        then its share of the window's plan until the window closes — its
        next request goes out when the last was answered (``loop.on``
        ``"completion"``: when the instance it created was done), with no gap
        at the change-over."""
        for phase in ("warm", "window"):
            for pid, x in plans[phase]:
                variables = {**x, **self.payload}
                due = time.monotonic()
                if phase == "warm" and self.t0 is not None and due >= self.t0:
                    break
                if phase == "window" and due >= self.t_end:
                    return
                rec = self.create(pid, variables, due, phase)
                if self.jobs_of is not None and rec["ok"]:
                    self._await_completion(rec["key"])
            else:
                raise RuntimeError("a closed-loop creator ran out of plan")

    def _spawn(self, target, *args) -> threading.Thread:
        t = threading.Thread(target=self._guard, args=(target, *args),
                             daemon=True)
        t.start()
        return t

    def _guard(self, target, *args) -> None:
        try:
            target(*args)
        except BaseException as exc:  # noqa: BLE001 — reported, then re-raised
            self.error = exc
            raise

    def warm(self) -> dict:
        """The mix's own traffic, until the window takes over."""
        loop = self.traffic["loop"]
        if loop["kind"] == "closed":
            n = int(loop["clients"])
            # drawn once and dealt round the clients, without the payload: n
            # plans of n x 2000 entries, the payload in each, are tens of
            # seconds of this process's interpreter from 32 clients on
            plans = {phase: defs.request_plan(self.definitions, n * 2000, {}, seed)
                     for phase, seed in (("warm", self.seed ^ 0xAAAA),
                                         ("window", self.seed))}
            self.warm_threads = [
                self._spawn(self._closed_creator,
                            {phase: plan[i::n] for phase, plan in plans.items()})
                for i in range(n)]
        else:
            self.pool = ThreadPoolExecutor(max_workers=int(loop["senders"]))
            self.warm_threads = [self._spawn(self._open_warm, loop)]
        return {}

    def _open_warm(self, loop: dict) -> None:
        rng = random.Random(self.seed ^ 0xAAAA)
        plan = defs.request_plan(self.definitions, 100_000, self.payload,
                                 self.seed ^ 0xAAAA)
        due = time.monotonic()
        for pid, variables in plan:
            due += (1.0 / float(loop["rate_per_s"])
                    if loop["arrivals"] == "fixed"
                    else rng.expovariate(float(loop["rate_per_s"])))
            while (left := due - time.monotonic()) > 0:
                if self.warm_stop.wait(min(left, 0.05)):
                    return
            if self.warm_stop.is_set():     # running late: no gap was waited
                return
            self.pool.submit(self.create, pid, variables, due, "warm")

    def window(self, t0: float, seconds: float) -> dict:
        loop = self.traffic["loop"]
        self.t0, self.t_end = t0, t0 + seconds
        while (left := t0 - time.monotonic()) > 0:
            time.sleep(min(0.01, left))
        self.warm_stop.set()
        rpcs0, sheds0, cpu0 = self.rpcs, self.sheds, time.process_time()
        if loop["kind"] == "closed":
            for t in self.warm_threads:     # they go on into the window's plan
                t.join()
        else:
            offsets = schedule.offsets_of(loop, seconds, self.seed)
            plan = defs.request_plan(self.definitions, len(offsets),
                                     self.payload, self.seed)
            futures = []
            for offset, (pid, variables) in zip(offsets, plan):
                due = t0 + offset
                while (left := due - time.monotonic()) > 0:
                    time.sleep(min(left, 0.05))
                futures.append(self.pool.submit(self.create, pid, variables,
                                                due, "window"))
            for f in futures:
                f.result()
            for t in self.warm_threads:
                t.join()
            self.pool.shutdown(wait=True)
        if self.publisher is not None:
            self.publisher.drain()
        # this process's cores from the window's opening until its last
        # request was answered: whether the generator is what bounds a cell
        cpu_cores = (time.process_time() - cpu0) / (time.monotonic() - t0)
        if self.error is not None:
            raise self.error
        with self.lock:
            records = [r for r in self.records if r["phase"] != "first_touch"]
            window = [r for r in records if r["phase"] == "window"]
        path = os.path.join(self.out_dir, "requests.jsonl")
        with open(path, "w") as out:
            for r in records:
                out.write(json.dumps(r) + "\n")
        answer = {"records_file": path, "window_requests": len(window),
                  "rpcs": self.rpcs - rpcs0, "sheds": self.sheds - sheds0,
                  "cpu_cores": round(cpu_cores, 3)}
        if self.publisher is not None:
            with self.lock:
                publishes = [r for r in self.publishes
                             if r["phase"] != "first_touch"]
            answer["publishes_file"] = os.path.join(self.out_dir,
                                                    "publishes.jsonl")
            with open(answer["publishes_file"], "w") as out:
                for r in publishes:
                    out.write(json.dumps(r) + "\n")
            answer["window_publishes"] = sum(r["phase"] == "window"
                                             for r in publishes)
        return answer

    def stop(self) -> dict:
        if self.publisher is not None:
            self.publisher.close()
        for w in self.workers:
            w.stop()
        for c in self.clients:
            c.close()
        path = os.path.join(self.out_dir, "completed_jobs.json")
        with open(path, "w") as out:
            json.dump(self.completed_jobs, out)
        return {"jobs_handled": sum(w.handled_count for w in self.workers),
                "jobs_file": path,
                "job_handlers_failed": sum(w.failed_count for w in self.workers),
                "retries": dict(self.retry.counts),
                "rpcs": self.rpcs, "sheds": self.sheds}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--address", required=True)
    parser.add_argument("--traffic", required=True, help="the mix's data file")
    parser.add_argument("--partitions", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--out-dir", required=True)
    args = parser.parse_args(argv)
    if "jax" in sys.modules:
        raise RuntimeError("the load generator must not import jax")
    with open(args.traffic) as f:
        traffic = json.load(f)
    gen = LoadGen(args.address, traffic, args.partitions, args.seed,
                  args.out_dir)
    reply = sys.stdout
    for line in sys.stdin:
        msg = json.loads(line)
        cmd = msg.pop("cmd")
        try:
            if cmd not in ("deploy", "first_touch", "warm", "window", "stop"):
                raise ValueError(f"unknown command {cmd!r}")
            answer = {**getattr(gen, cmd)(**msg), "ok": True}
        except Exception as exc:  # noqa: BLE001 — the harness decides
            import traceback

            traceback.print_exc()
            answer = {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
        if "jax" in sys.modules:
            answer = {"ok": False, "error": "jax was imported in the load generator"}
        reply.write(json.dumps(answer) + "\n")
        reply.flush()
        if cmd == "stop" or not answer["ok"]:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
