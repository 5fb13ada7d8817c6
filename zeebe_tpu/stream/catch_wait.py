"""How long a catch waited: a timer's trigger and a message's correlation,
from host-side stamps on the leader's processing path.

- ``stream_processor_pipeline_timer_lag``: one observation per
  ``TIMER TRIGGER`` processed whose timer this partition's due-date sweep
  found due (``DueDateCheckers``): the partition clock at the end of its
  processing minus the timer's due date. It holds the sweep's lateness, the
  command's write, its replication, its admission and its group.
- ``stream_processor_pipeline_correlate``: one per ``PROCESS_MESSAGE_
  SUBSCRIPTION CORRELATE`` processed on the instance's partition, from the
  message partition's post-commit send (where ``CORRELATING`` was written,
  for a buffered message or an open subscription alike) to the end of its
  processing. The two ends are two partitions' processors, so these stamps
  are process-wide (``CORRELATIONS``), keyed by (element instance key,
  message key); a command whose message partition runs in another process
  has no stamp and is not observed. A stamp is dropped when its message
  expires or its subscription is deleted.
- ``stream_processor_pipeline_catch``: one per catch command processed
  (``CATCH_COMMANDS``), by either path: from its being readable on the
  stream to the end of its processing; ``_catch_kernel``: the same, for those
  that rode a kernel group.

Nothing here enters the replicated state or the log, and replay observes
nothing: the stamps are taken and read on the processing path alone."""

from __future__ import annotations

import threading
from time import perf_counter

from zeebe_tpu.protocol import ValueType
from zeebe_tpu.protocol.intent import ProcessMessageSubscriptionIntent, TimerIntent
from zeebe_tpu.utils import evict_oldest_half
from zeebe_tpu.utils.metrics import REGISTRY

TIMER_TRIGGER = (ValueType.TIMER, int(TimerIntent.TRIGGER))
CORRELATE = (ValueType.PROCESS_MESSAGE_SUBSCRIPTION,
             int(ProcessMessageSubscriptionIntent.CORRELATE))
#: the commands that end a catch: what the kernel's catch path admits
CATCH_COMMANDS = frozenset((TIMER_TRIGGER, CORRELATE))

# a catch whose command is never processed keeps its stamp: past this many
# the oldest half goes
_STAMP_LIMIT = 1 << 16


class CorrelationStamps:
    """Process-wide: (element instance key, message key) -> the moment the
    message partition sent the correlation."""

    def __init__(self) -> None:
        self._sent: dict[tuple[int, int], float] = {}
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._sent)

    def sent(self, element_key: int, message_key: int) -> None:
        with self._lock:
            evict_oldest_half(self._sent, _STAMP_LIMIT)
            self._sent[(element_key, message_key)] = perf_counter()

    def take(self, element_key: int, message_key: int) -> float | None:
        return self._sent.pop((element_key, message_key), None)

    def drop_messages(self, message_keys) -> None:
        """The messages expired: their correlations will not be observed."""
        gone = set(message_keys)
        with self._lock:
            for key in [k for k in self._sent if k[1] in gone]:
                del self._sent[key]

    def drop_element(self, element_key: int) -> None:
        """The element's subscription was deleted."""
        with self._lock:
            for key in [k for k in self._sent if k[0] == element_key]:
                del self._sent[key]

    def clear(self) -> None:
        with self._lock:
            self._sent.clear()


CORRELATIONS = CorrelationStamps()


class CatchStamps:
    """One partition's processor: the due dates its sweep triggered, and the
    four histograms, observed when a catch command's processing ends."""

    def __init__(self, partition_label: str) -> None:
        self.timer_due: dict[int, int] = {}     # timer key -> due date, ms

        def histogram(stage: str, text: str):
            return REGISTRY.histogram(
                f"stream_processor_pipeline_{stage}", text,
                ("partition",)).labels(partition_label)

        self._m_timer_lag = histogram(
            "timer_lag",
            "seconds per TIMER TRIGGER processed between the timer's due "
            "date and the end of its processing, on the partition clock "
            "(the leader's processing path; never replay)")
        self._m_correlate = histogram(
            "correlate",
            "seconds per PROCESS_MESSAGE_SUBSCRIPTION CORRELATE processed "
            "on the instance's partition between the message partition's "
            "post-commit send and the end of its processing (observed where "
            "both partitions share a process)")
        self._m_catch = histogram(
            "catch",
            "seconds per catch command (TIMER TRIGGER, PROCESS_MESSAGE_"
            "SUBSCRIPTION CORRELATE) processed, by either path, between its "
            "being readable on the stream and the end of its processing")
        self._m_catch_kernel = histogram(
            "catch_kernel",
            "seconds per catch command processed in a kernel group, between "
            "its being readable on the stream and the end of its processing")

    def swept(self, timer_key: int, due_ms: int) -> None:
        """The due-date sweep wrote this timer's TRIGGER."""
        evict_oldest_half(self.timer_due, _STAMP_LIMIT)
        self.timer_due[timer_key] = due_ms

    def processed(self, cmds, readable_at, clock_millis, kernel: bool) -> None:
        """The end of processing of ``cmds`` (a committed kernel group's, or
        one sequential command): each catch among them is observed."""
        now = None
        for cmd in cmds:
            record = cmd.record
            kind = (record.value_type, int(record.intent))
            if kind not in CATCH_COMMANDS:
                continue
            if now is None:
                now = perf_counter()
            readable = readable_at(cmd.position)
            if readable is not None:
                waited = max(0.0, now - readable)
                self._m_catch.observe(waited)
                if kernel:
                    self._m_catch_kernel.observe(waited)
            if kind == TIMER_TRIGGER:
                due = self.timer_due.pop(record.key, None)
                if due is not None:
                    self._m_timer_lag.observe(max(0, clock_millis() - due) / 1e3)
            else:
                value = record.value
                sent = CORRELATIONS.take(value.get("elementInstanceKey", -1),
                                         value.get("messageKey", -1))
                if sent is not None:
                    self._m_correlate.observe(now - sent)
