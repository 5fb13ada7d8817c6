"""How long a job waited for its worker, by poll or by push: host-side stamps
on the leader's processing path, keyed by job key. A job is stamped at the
post-commit moment its step made it activatable (where the jobs-available
notification fires); the ``JOB_BATCH ACTIVATED`` that hands it out observes
``stream_processor_pipeline_job_wait`` and keeps the stamp for the push
dispatcher, whose delivery to a live client stream observes
``stream_processor_pipeline_job_push`` from the same stamp. A stamp is dropped
when the job is pushed, ends (completed, canceled, failed, error thrown) or is
made activatable again. Nothing here enters the replicated state or the log,
and replay stamps nothing: a job made activatable under another leader has no
stamp and is not observed."""

from __future__ import annotations

from time import perf_counter

from zeebe_tpu.utils import evict_oldest_half
from zeebe_tpu.utils.metrics import REGISTRY

# jobs nobody works on stay stamped: past this many the oldest half goes (they
# would read in the histogram's last bucket anyway)
_STAMP_LIMIT = 1 << 17


class JobWaitStamps:
    def __init__(self, partition_label: str) -> None:
        self._waiting: dict[int, float] = {}    # activatable since
        self._activated: dict[int, float] = {}  # the same stamp, once handed out
        self._m_wait = REGISTRY.histogram(
            "stream_processor_pipeline_job_wait",
            "seconds per job between the post-commit moment its step made it "
            "activatable and the processing of the JOB_BATCH ACTIVATE that "
            "handed it to a worker, by poll or by push (the leader's "
            "processing path; never replay)",
            ("partition",)).labels(partition_label)
        self._m_push = REGISTRY.histogram(
            "stream_processor_pipeline_job_push",
            "seconds per pushed job between the post-commit moment its step "
            "made it activatable and its being put on a live client stream: "
            "the dispatcher's queue, the activation's round trip and the "
            "delivery (observed where the dispatcher shares the leader's "
            "process)",
            ("partition",)).labels(partition_label)

    def __len__(self) -> int:
        return len(self._waiting) + len(self._activated)

    def moved(self, available, activated, ended) -> None:
        """One committed step's jobs (``stream/api.py: job_moves``), at its
        post-commit effects: those it ended, handed out and made activatable."""
        for key in ended:
            self._waiting.pop(key, None)
            self._activated.pop(key, None)
        if not (available or activated):
            return
        now = perf_counter()
        for key in activated:
            since = self._waiting.pop(key, None)
            if since is not None:
                self._m_wait.observe(now - since)
                self._activated[key] = since
        for key in available:
            self._activated.pop(key, None)
            self._waiting[key] = now
        evict_oldest_half(self._waiting, _STAMP_LIMIT)
        evict_oldest_half(self._activated, _STAMP_LIMIT)

    def pushed(self, key: int) -> float | None:
        """The dispatcher's thread: the job is on a live stream. Returns the
        seconds since it was made activatable, None for a job without a stamp
        (a dict pop is atomic: no lock against the partition's thread)."""
        since = self._activated.pop(key, None)
        if since is None:
            return None
        waited = perf_counter() - since
        self._m_push.observe(waited)
        return waited
