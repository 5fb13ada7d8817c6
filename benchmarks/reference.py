"""The plain reference: BPMN token semantics over a definition dict, written
straight from the specification's rules and importing nothing of the program.

It is run as an *acceptor*. For one process instance it is given the
definition, the variables the request carried, and the records the exporter
saw for that instance in log order, and plays the token game over them: each
record must be the next lawful move (an element activates only once a flow
into it was taken, a service task completes only after its one job was
created and completed, an exclusive gateway takes the first flow whose
condition holds over the request's ``x`` and else its default, a parallel
join waits for every incoming flow, a scope completes only once everything in
it has), and at the end the instance must have completed with no token, flow
or job left over. Parallel branches may interleave in any order; everything
else is fixed, so any record altered, lost, doubled or mis-routed on the way
(kernel, materialize, log, exporter) is a mismatch.

Variables: each of the request's is created once, before anything moves. The
document a worker returns with a job's completion is merged right behind the
job's ``COMPLETED``: one record a name at most, with the document's value —
``CREATED`` for a name the instance does not hold, ``UPDATED`` for one it
holds. A name that already holds that very value may be left unwritten (the
specification's engines differ on it); any other name of the document must be
written before the next record that is not a variable's.

Catch events (an intermediate catch between a flow in and a flow out): a
timer catch waits from its activation until its due date, a message catch
until the one message published under the instance's correlation key reaches
it; the catch then completes, a message's variables merged as a job's
returned document is. A message lives on the partition its correlation key
hashes to, so its records form a sequence of their own there
(``accept_message_side``): two logs have no common order.

Events (plain tuples, log order):
    ("PI", intent, element_id, key, flow_scope_key)
    ("JOB", intent, element_id, job_type, job_key, element_instance_key)
    ("VAR", "CREATED" | "UPDATED", name, value)
    ("TIMER", intent, element_id, timer_key, element_instance_key, due_date,
     record_timestamp)
    ("PMS", intent, element_id, element_instance_key, message_name,
     correlation_key, message_key)
and on the message's partition, for one correlation key:
    ("MS", intent, element_id, message_name, process_instance_key,
     message_key)
    ("MESSAGE", "PUBLISHED" | "EXPIRED", message_key)
"""

from __future__ import annotations

from collections import Counter

CONTAINERS = ("process", "subProcess")
CATCH = "intermediateCatchEvent"
#: the clock counts milliseconds
CLOCK_GRAIN_MS = 1
#: how long before its record's stamp the step that activated a timer catch
#: may have read the clock for the due date: a batch is stamped when it is
#: appended, after the step that wrote it
STAMP_LAG_MS = 1000


class Mismatch(Exception):
    """The observed records are not a lawful execution of the definition."""


def _holds(condition, variables: dict) -> bool:
    name, op, value = condition
    if op != ">":
        raise ValueError(f"the reference knows no operator {op!r}")
    return name in variables and variables[name] > value


class _Definition:
    def __init__(self, d: dict) -> None:
        self.id = d["id"]
        self.nodes = {n["id"]: n for n in d["nodes"]}
        self.nodes[d["id"]] = {"id": d["id"], "type": "process", "parent": None}
        self.flows = {f["id"]: f for f in d["flows"]}
        self.defaults = d["defaults"]
        self.outgoing: dict = {}
        self.incoming: dict = {}
        for f in d["flows"]:
            self.outgoing.setdefault(f["source"], []).append(f)
            self.incoming.setdefault(f["target"], []).append(f)

    def start_of(self, container_id: str) -> str:
        parent = None if container_id == self.id else container_id
        starts = [n["id"] for n in self.nodes.values()
                  if n["type"] == "startEvent" and n["parent"] == parent]
        if len(starts) != 1:
            raise ValueError(f"{container_id}: {len(starts)} start events")
        return starts[0]

    def flows_taken_by(self, node_id: str, variables: dict) -> list:
        node = self.nodes[node_id]
        out = self.outgoing.get(node_id, [])
        if node["type"] != "exclusiveGateway" or len(out) <= 1:
            return [f["id"] for f in out]
        for f in out:
            if f["condition"] is not None and _holds(f["condition"], variables):
                return [f["id"]]
        if node_id not in self.defaults:
            raise Mismatch(f"{node_id}: no condition holds and no default")
        return [self.defaults[node_id]]


def accept(definition: dict, variables: dict, events: list,
           returned: dict | None = None, message: dict | None = None) -> None:
    """Raise :class:`Mismatch` unless ``events`` is one complete, lawful
    execution of ``definition`` under ``variables``, with every job completed
    with the document ``returned`` and a message catch reached by
    ``message`` (``{"key", "variables"}``: the one publish acknowledged under
    the instance's correlation key; None: none was)."""
    d = _Definition(definition)
    instances: dict = {}          # key -> {"elem", "state", "scope", "job"}
    may_activate: Counter = Counter({(d.id, -1): 1})   # (element, scope key)
    may_take: Counter = Counter()                      # (flow, scope key)
    joined: dict = {}             # (join element, scope key) -> flows taken
    jobs: dict = {}               # job key -> element instance key
    variables_left = Counter(variables.keys())
    held: dict = {}               # variable name -> the value it holds
    merging = None                # what is left of a returned document
    process_key = None

    def merge_closed(where: str) -> None:
        unmerged = [name for name, value in merging.items()
                    if name not in held or held[name] != value]
        if unmerged:
            raise Mismatch(f"{where}: returned variables never merged: "
                           f"{unmerged[:4]}")

    def children_of(scope_key):
        return [i for i in instances.values() if i["scope"] == scope_key]

    for n, event in enumerate(events):
        kind, intent = event[0], event[1]
        where = f"record {n} {event[:4]}"
        if kind == "VAR":
            _, _, name, value = event
            returns_it = (merging is not None and name in merging
                          and merging[name] == value)
            if intent == "CREATED" and name not in held and returns_it:
                del merging[name]
            elif (intent == "CREATED" and name not in held
                  and variables_left[name] > 0 and variables[name] == value):
                variables_left[name] -= 1
            elif intent == "UPDATED" and name in held and returns_it:
                del merging[name]
            else:
                raise Mismatch(f"{where}: not a variable of the request, nor "
                               "one a worker returned here")
            held[name] = value
            continue
        if merging is not None:
            merge_closed(where)
            merging = None
        if kind == "TIMER":
            _timer_event(d, instances, event, where)
        elif kind == "PMS":
            if _subscription_event(d, instances, variables, message, event,
                                   where):
                merging = dict(message["variables"])
        elif kind == "JOB":
            _, _, element_id, job_type, job_key, element_key = event
            if intent == "CREATED":
                inst = instances.get(element_key)
                if (inst is None or inst["elem"] != element_id
                        or inst["state"] != "ACTIVATED" or inst["job"] is not None
                        or d.nodes[element_id].get("job_type") != job_type):
                    raise Mismatch(f"{where}: no task waits for this job")
                inst["job"] = "CREATED"
                jobs[job_key] = element_key
            elif intent == "COMPLETED":
                inst = instances.get(jobs.get(job_key))
                if inst is None or inst["job"] != "CREATED":
                    raise Mismatch(f"{where}: job was not open")
                inst["job"] = "COMPLETED"
                merging = dict(returned or {})
            elif intent == "TIMED_OUT":
                # lawful: a worker held the job past its deadline, and the
                # job is open again for the next activation
                inst = instances.get(jobs.get(job_key))
                if inst is None or inst["job"] != "CREATED":
                    raise Mismatch(f"{where}: job was not open")
            else:
                raise Mismatch(f"{where}: unexpected job intent")
        elif kind == "PI":
            _, _, element_id, key, scope_key = event
            if intent == "SEQUENCE_FLOW_TAKEN":
                if may_take[(element_id, scope_key)] <= 0:
                    raise Mismatch(f"{where}: flow was not to be taken")
                may_take[(element_id, scope_key)] -= 1
                target = d.flows[element_id]["target"]
                incoming = d.incoming[target]
                if (d.nodes[target]["type"] == "parallelGateway"
                        and len(incoming) > 1):
                    seen = joined.setdefault((target, scope_key), set())
                    if element_id in seen:
                        raise Mismatch(f"{where}: join saw this flow twice")
                    seen.add(element_id)
                    if len(seen) < len(incoming):
                        continue
                    del joined[(target, scope_key)]
                may_activate[(target, scope_key)] += 1
                continue
            if element_id not in d.nodes:
                raise Mismatch(f"{where}: no such element")
            node_type = d.nodes[element_id]["type"]
            if intent == "ELEMENT_ACTIVATING":
                if may_activate[(element_id, scope_key)] <= 0 or key in instances:
                    raise Mismatch(f"{where}: element was not to be activated")
                may_activate[(element_id, scope_key)] -= 1
                instances[key] = {"elem": element_id, "state": "ACTIVATING",
                                  "scope": scope_key, "job": None}
                if node_type == "process":
                    process_key = key
                continue
            inst = instances.get(key)
            if inst is None or inst["elem"] != element_id:
                raise Mismatch(f"{where}: unknown element instance")
            if intent == "ELEMENT_ACTIVATED":
                if inst["state"] != "ACTIVATING":
                    raise Mismatch(f"{where}: out of lifecycle order")
                inst["state"] = "ACTIVATED"
                if node_type in CONTAINERS:
                    may_activate[(d.start_of(element_id), key)] += 1
            elif intent == "ELEMENT_COMPLETING":
                if inst["state"] != "ACTIVATED":
                    raise Mismatch(f"{where}: out of lifecycle order")
                if node_type == "serviceTask" and inst["job"] != "COMPLETED":
                    raise Mismatch(f"{where}: task completes without its job")
                if node_type == CATCH and inst.get("caught") is not True:
                    raise Mismatch(f"{where}: catch completes without its "
                                   "trigger or correlation")
                if node_type in CONTAINERS:
                    inside = children_of(key)
                    open_work = (
                        any(i["state"] != "COMPLETED" for i in inside)
                        or any(c > 0 and s == key
                               for (_e, s), c in may_activate.items())
                        or any(c > 0 and s == key
                               for (_f, s), c in may_take.items())
                        or any(s == key for (_j, s) in joined))
                    ended = any(d.nodes[i["elem"]]["type"] == "endEvent"
                                for i in inside)
                    if open_work or not ended:
                        raise Mismatch(f"{where}: scope completes with work "
                                       "left inside")
                inst["state"] = "COMPLETING"
            elif intent == "ELEMENT_COMPLETED":
                if inst["state"] != "COMPLETING":
                    raise Mismatch(f"{where}: out of lifecycle order")
                inst["state"] = "COMPLETED"
                for flow_id in d.flows_taken_by(element_id, variables):
                    may_take[(flow_id, inst["scope"])] += 1
            else:
                raise Mismatch(f"{where}: unexpected intent")
        else:
            raise Mismatch(f"{where}: unexpected record kind")

    if merging is not None:
        merge_closed("the end")
    if process_key is None or instances[process_key]["state"] != "COMPLETED":
        raise Mismatch("the process instance did not complete")
    left = ([k for k, c in may_activate.items() if c > 0]
            + [k for k, c in may_take.items() if c > 0] + list(joined)
            + [k for k, i in instances.items() if i["state"] != "COMPLETED"]
            + [k for k, c in variables_left.items() if c > 0])
    if left:
        raise Mismatch(f"work left over at the end: {left[:4]}")


def _catch_node(d: "_Definition", element_id: str, inst, what: str,
                where: str) -> dict:
    if (inst is None or inst["elem"] != element_id
            or d.nodes[element_id]["type"] != CATCH
            or what not in d.nodes[element_id]):
        raise Mismatch(f"{where}: no {what} catch waits here")
    return d.nodes[element_id]


def _timer_event(d: "_Definition", instances: dict, event: tuple,
                 where: str) -> None:
    """BPMN 2.0 section 10.5.6 (a timer's intermediate catch waits for its
    duration from its activation) and Zeebe's timer records: one ``CREATED``
    as the catch activates, due at the activation plus the duration; one
    ``TRIGGERED`` at or after its due date, which completes the catch."""
    _, intent, element_id, timer_key, element_key, due, stamp = event
    inst = instances.get(element_key)
    node = _catch_node(d, element_id, inst, "timer_ms", where)
    if intent == "CREATED":
        if inst["state"] != "ACTIVATED" or "timer" in inst:
            raise Mismatch(f"{where}: timer created twice or out of order")
        read_at = due - node["timer_ms"]
        if not stamp - STAMP_LAG_MS <= read_at <= stamp + CLOCK_GRAIN_MS:
            raise Mismatch(f"{where}: due {due} is not the activation at "
                           f"{stamp} plus {node['timer_ms']} ms")
        inst["timer"] = (timer_key, due)
    elif intent == "TRIGGERED":
        if inst.get("timer") != (timer_key, due) or inst.get("caught"):
            raise Mismatch(f"{where}: trigger of no open timer")
        if stamp < due:
            raise Mismatch(f"{where}: triggered at {stamp}, before its due "
                           f"date {due}")
        inst["caught"] = True
    else:
        raise Mismatch(f"{where}: unexpected timer intent")


def _subscription_event(d: "_Definition", instances: dict, variables: dict,
                        message, event: tuple, where: str) -> bool:
    """BPMN 2.0 section 10.5.6 (a message catch waits for its message) and
    Zeebe's correlation rules (a subscription by message name and the value
    of the catch's correlation key; a message is correlated to an instance
    once): on the instance's partition one subscription ``CREATING`` (and
    ``CREATED`` once the message partition answers) as the catch activates,
    then one ``CORRELATED``, to the instance's own message alone. Returns
    True for the correlation, whose variables merge next."""
    _, intent, element_id, element_key, name, correlation, message_key = event
    inst = instances.get(element_key)
    node = _catch_node(d, element_id, inst, "message", where)
    own = str(variables.get(node["correlation_variable"]))
    if name != node["message"] or correlation != own:
        raise Mismatch(f"{where}: subscription to another message or key")
    sub = inst.get("subscription")
    if intent == "CREATING" and sub is None and inst["state"] == "ACTIVATED":
        inst["subscription"] = "CREATING"
        return False
    if intent == "CREATED" and sub == "CREATING":
        inst["subscription"] = "CREATED"
        return False
    if intent == "CORRELATED" and sub in ("CREATING", "CREATED"):
        if message is None or message_key != message["key"]:
            raise Mismatch(f"{where}: correlated message {message_key}, not "
                           "the instance's own")
        inst["subscription"] = "CORRELATED"
        inst["caught"] = True
        return True
    raise Mismatch(f"{where}: subscription {intent} out of order")


def accept_message_side(definition: dict, variables: dict, instance_key: int,
                        events: list, message) -> str:
    """The message partition's records for the instance's correlation key,
    in that log's order: the acknowledged message ``PUBLISHED`` once (Zeebe:
    a publish is one message, buffered until its time to live ends), the
    instance's subscription ``CREATED`` once, one ``CORRELATING`` of the
    instance's own message after both, at most one ``CORRELATED`` after it
    (the instance's partition answered), the message ``EXPIRED`` at most once
    and never before its correlation. Raises :class:`Mismatch` otherwise."""
    d = _Definition(definition)
    catch = next(n for n in d.nodes.values() if "message" in n)
    if message is None:
        raise Mismatch("no publish of the instance's message was acknowledged")
    seen: dict = {}
    for n, event in enumerate(events):
        where = f"message record {n} {event}"
        kind, intent = event[0], event[1]
        if kind == "MESSAGE":
            if event[2] != message["key"]:
                raise Mismatch(f"{where}: a message no publish was "
                               "acknowledged for")
            if intent not in ("PUBLISHED", "EXPIRED") or intent in seen or (
                    intent == "EXPIRED" and "PUBLISHED" not in seen):
                raise Mismatch(f"{where}: message {intent} out of order")
        elif kind == "MS":
            _, _, element_id, name, pik, message_key = event
            if (element_id, name, pik) != (catch["id"], catch["message"],
                                           instance_key):
                raise Mismatch(f"{where}: another instance's subscription")
            ready = {"CREATED": (), "CORRELATING": ("CREATED", "PUBLISHED"),
                     "CORRELATED": ("CORRELATING",)}.get(intent)
            if (ready is None or intent in seen
                    or any(r not in seen for r in ready)
                    or (intent == "CORRELATING" and "EXPIRED" in seen)):
                raise Mismatch(f"{where}: subscription {intent} out of order")
            if intent != "CREATED" and message_key != message["key"]:
                raise Mismatch(f"{where}: correlates message {message_key}, "
                               "not the instance's own")
        else:
            raise Mismatch(f"{where}: unexpected record kind")
        seen[intent] = n
    if not {"PUBLISHED", "CREATED", "CORRELATING"} <= set(seen):
        raise Mismatch("message partition: publish, subscription or "
                       f"correlation missing (saw {sorted(seen)})")


def path_of(events: list):
    """How a message met its subscription, from the message partition's
    records for its correlation key: ``"buffered"`` (published before the
    subscription opened), ``"open"`` (to a subscription open when it was
    published), None (not both seen)."""
    kinds = [e[:2] for e in events]
    if ("MESSAGE", "PUBLISHED") not in kinds or ("MS", "CREATED") not in kinds:
        return None
    return ("buffered" if kinds.index(("MESSAGE", "PUBLISHED"))
            < kinds.index(("MS", "CREATED")) else "open")


def message_sides(observed: dict) -> dict:
    """Correlation key -> the message partition's records for it, in that
    log's order (``observed[("MESSAGE", key)]``), each batch expiry, which
    names its messages by key alone, placed last under its message's
    correlation key."""
    sides = {key[1]: list(events) for key, events in observed.items()
             if isinstance(key, tuple) and key[0] == "MESSAGE"}
    correlation_of = {e[2]: ck for ck, events in sides.items() for e in events
                      if e[:2] == ("MESSAGE", "PUBLISHED")}
    for batch in observed.get(("MESSAGE_BATCH",), ()):
        for message_key in batch[2]:
            ck = correlation_of.get(message_key)
            if ck is not None:
                sides[ck].append(("MESSAGE", batch[1], message_key))
    return sides


def correlation_variable(definition: dict):
    return next((n["correlation_variable"] for n in definition["nodes"]
                 if "correlation_variable" in n), None)


def mismatches(definitions: dict, requests: list, observed: dict,
               returned: dict | None = None, messages: dict | None = None) -> list:
    """``requests``: ``(instance key, process id, variables)`` of every
    acknowledged create; ``observed``: instance key -> its events, and the
    message partitions' records by ``("MESSAGE", correlation key)``;
    ``returned``: the document every job was completed with; ``messages``:
    correlation key -> ``{"key", "variables"}`` of the acknowledged publish.
    Returns ``(instance key, reason)`` for every instance that is not
    accepted."""
    bad = []
    sides = message_sides(observed)
    messages = messages or {}
    for key, pid, variables in requests:
        definition = definitions[pid]
        var = correlation_variable(definition)
        message = None if var is None else messages.get(str(variables[var]))
        try:
            accept(definition, variables, observed.get(key, []), returned,
                   message)
            if var is not None:
                accept_message_side(definition, variables, key,
                                    sides.get(str(variables[var]), []), message)
        except Mismatch as err:
            bad.append((key, str(err)))
    return bad
