"""Streaming stage-pipelined ingest engine: the real-data hot path.

Reference equivalent: ``dataset/image/MTLabeledBGRImgToBatch.scala:46`` —
the production ImageNet lesson that host-side batch prep must overlap both
itself (decode vs assemble) and device compute.  The synchronous
:class:`~bigdl_tpu.dataset.mt_batch.MTLabeledBGRImgToBatch` executes
read → decode → assemble serially *per batch* (``pool.map`` is a batch
barrier, assemble runs while the pool sits idle); round 5 measured that
structure at 0.56x of the decode-alone ceiling (host-side stage rates;
round-5 record, removed in PR 21).  This module removes the barriers:

    sharded seqfile readers ──► record ring ──► decode pool ──► ordered
    decoded window ──► assembler (native pack, GIL-released) ──► batch
    ring ──► consumer (── engine.BatchPrefetcher keeps N device uploads
    in flight beyond this point)

Every stage is decoupled by a bounded ring (backpressure, never unbounded
memory) and instrumented: items, busy seconds, stall seconds split into
*starve* (waiting for the upstream stage) and *backpressure* (blocked on a
full downstream ring), plus mean ring occupancy.  ``stats()`` snapshots
feed the training summary layer (``Ingest/<engine>/<stage>/*``) — the
stage with high busy and low stall is the bottleneck.

Determinism contract (the part that makes this usable for training, not
just benchmarks): crop offsets / flips draw from a CLONE of the caller's
``RandomGenerator`` stream in strict record order, and each batch carries
the post-draw RNG state; the clone's position is committed back to the
caller's stream only when the batch is CONSUMED.  Pipeline read-ahead that
gets discarded (an epoch rollover replacing the chain) therefore never
advances the user-visible stream — the pipelined engine reproduces the
synchronous path's batch sequence bit for bit at every depth setting, and
epoch rollover / reshuffle stays producer-owned exactly as before
(``engine.BatchPrefetcher``'s single-drawer contract).  With MULTIPLE
engines forked from one stream (a multi-shard ``ShardedDataSet``), only
the first fork commits; the others draw decorrelated deterministic
per-shard streams (the reference's per-partition RNG model) — sync-path
bit-parity is a single-engine contract, multi-shard runs are run-to-run
deterministic.

Self-healing contract (the part that makes this usable on dirty data at
production scale): stage failures are CLASSIFIED.  *Data* faults — a
corrupt/truncated SequenceFile record, an undecodable image, an
undersized frame — skip the one offending record into a bounded
:class:`RecordQuarantine` (``bigdl.ingest.maxBadRecords``; budget
exceeded or budget 0 → fail loudly, with a sample of offenders), counted
through the metrics registry (``Ingest/quarantined``) so silent data
loss is impossible.  *Infrastructure* faults — a transient IO blip, a
dead stage thread, a wedged ring — are retried/restarted: record reads
run behind ``utils.file_io``'s capped-backoff transient retry, a
:class:`_StageSupervisor` restarts a silently-dead reader/assembler
thread (bounded by ``bigdl.ingest.maxStageRestarts``, then escalates to
:class:`IngestInfraError`), and per-ring progress heartbeats detect a
wedged handoff (``bigdl.ingest.stallTimeoutSec``) so the run aborts with
per-stage diagnostics instead of hanging forever.  As graceful
degradation, ``bigdl.ingest.fallbackOnFailure`` lets a supervisor-
declared-dead engine finish the epoch on the synchronous path — same
drawer RNG, so the batch stream continues bit-identically (modulo
quarantined records).  All of it is provable on CPU via the chaos
injectors (``bigdl.chaos.corruptRecordAt`` / ``failDecodeAt`` /
``killStageThread`` / ``transientReads``, ``utils/chaos.py``).

Configuration (``bigdl.ingest.*``, see ``utils/config.py``):

===============================  =============================================
``bigdl.ingest.shards``          parallel seqfile reader threads
``bigdl.ingest.decodeWorkers``   decode pool size (default: host cores)
``bigdl.ingest.recordRingDepth`` reader → decode record ring depth
``bigdl.ingest.decodedRingDepth``in-flight decode window (default 2x batch)
``bigdl.ingest.batchRingDepth``  assembled batches buffered ahead
``bigdl.ingest.batchesInFlight`` device uploads in flight (BatchPrefetcher)
``bigdl.ingest.maxBadRecords``   data-error quarantine budget (0 = fail fast)
``bigdl.ingest.maxStageRestarts``dead-stage restarts before escalation
``bigdl.ingest.fallbackOnFailure`` dead engine → sync path mid-epoch
``bigdl.ingest.stallTimeoutSec`` wedged-ring detection window (0 = off)
``bigdl.ingest.deviceAugment``   pack FULL u8 frames + ride-along crop
                                 offsets/flips; crop/flip/transpose runs
                                 on device (``nn.DeviceAugment``)
``bigdl.ingest.autoscale.*``     supervisor-driven decode/assemble worker
                                 scaling (:class:`AutoscalePolicy`)
``bigdl.ingest.epochCache*``     decoded-frame cache across epochs
                                 (``dataset/epoch_cache.py``)
===============================  =============================================
"""

from __future__ import annotations

import os
import queue
import threading
import time
import weakref
from collections import deque
from concurrent import futures
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from bigdl_tpu import telemetry
from bigdl_tpu import analysis
from bigdl_tpu.dataset.transformer import Transformer
from bigdl_tpu.resources import GOVERNOR as _governor
from bigdl_tpu.resources import item_nbytes as _item_nbytes
from bigdl_tpu.utils import config

#: live engines, for the summary layer (weak: an abandoned engine must not
#: be pinned by the diagnostics that observe it)
_LIVE: "weakref.WeakSet" = weakref.WeakSet()

_END = object()          # upstream exhausted
_NO_ITEM = object()      # try_get on an empty ring

_NAME_LOCK = analysis.make_lock("ingest.name")
_NAME_SEQ = [0]          # per-process engine naming (ingest0, ingest1, …)


# ---------------------------------------------------------------------------
# error taxonomy + quarantine
# ---------------------------------------------------------------------------

class IngestDataError(Exception):
    """A fault in the DATA, not the machinery: corrupt/truncated record,
    undecodable image, undersized frame.  Quarantinable — skipping the
    one record is correct; retrying it is not (corrupt bytes stay
    corrupt)."""

    #: never absorbed by a transient-IO retry (``file_io._is_transient``)
    fatal = True


class IngestInfraError(RuntimeError):
    """The ingest MACHINERY failed beyond its self-healing budget: a
    stage thread died ``maxStageRestarts + 1`` times, or a ring wedged
    past ``stallTimeoutSec``.  Carries the engine's last per-stage
    ``stats()`` snapshot in ``diagnosis`` so the failure names the sick
    stage, not just the symptom."""

    def __init__(self, message: str, diagnosis: Optional[dict] = None):
        super().__init__(message)
        self.diagnosis = diagnosis or {}


class IngestStallError(IngestInfraError):
    """No ring made progress for ``bigdl.ingest.stallTimeoutSec`` while
    the consumer was blocked waiting — a wedged handoff (dead producer +
    blocked consumer), detected instead of hung."""


class QuarantineExceededError(IngestInfraError):
    """More data errors than ``bigdl.ingest.maxBadRecords`` allows: the
    data set is dirtier than the operator budgeted for, and silently
    skipping an unbounded stream of records would train on a different
    distribution than requested.  The message carries a sample of the
    offenders."""


def _is_data_error(e: BaseException) -> bool:
    """Data-vs-infrastructure classification shared by every stage."""
    from bigdl_tpu.dataset.seqfile import CorruptRecordError
    from bigdl_tpu.utils.chaos import CorruptRecord, UndecodableImage
    return isinstance(e, (IngestDataError, CorruptRecordError,
                          CorruptRecord, UndecodableImage))


class _StageKilledError(RuntimeError):
    """Chaos-injected silent death of a decode worker: an INFRA fault
    the assembler answers by resubmitting the decode (bounded), never by
    quarantining the record (its bytes are fine)."""


class RecordQuarantine:
    """Bounded sink for data-error records.

    ``admit(stage, index, name, error)`` either swallows the fault
    (budget remaining: count it, sample it, bump the registry counters)
    or raises — the ORIGINAL error when the budget is 0 (quarantine
    disabled: today's fail-fast contract, bit-parity with the sync
    path), a :class:`QuarantineExceededError` naming a sample of
    offenders when a nonzero budget runs out.  Thread-safe: read,
    decode, and assemble stages admit concurrently."""

    SAMPLE_MAX = 8

    def __init__(self, budget: Optional[int] = None):
        if budget is None:
            budget = config.get_int("bigdl.ingest.maxBadRecords", 0)
        self.budget = int(budget)
        self.count = 0
        self.by_stage: dict = {}
        self.samples: List[dict] = []
        self._lock = analysis.make_lock("ingest.quarantine")

    def admit(self, stage: str, index: Optional[int], name: Optional[str],
              error: BaseException) -> None:
        if self.budget <= 0:
            raise error
        with self._lock:
            self.count += 1
            self.by_stage[stage] = self.by_stage.get(stage, 0) + 1
            if len(self.samples) < self.SAMPLE_MAX:
                sample = {"stage": stage, "index": index, "name": name,
                          "error": repr(error)}
                self.samples.append(sample)
                # even this bounded diagnostic sink is accounted: the
                # host-memory governor's roll-up must see every buffer
                _governor.account("ingest_quarantine").add(
                    _item_nbytes(sample))
            over = self.count > self.budget
        telemetry.counter(
            "Ingest/quarantined", summary=True,
            help="data-error records skipped by the ingest quarantine"
        ).inc()
        telemetry.counter("Ingest/stage_errors", labels={"stage": stage},
                          help="data errors observed per ingest stage").inc()
        if over:
            raise QuarantineExceededError(
                f"ingest quarantine budget exhausted: {self.count} bad "
                f"records > bigdl.ingest.maxBadRecords={self.budget}; "
                f"offender sample: {self.samples}",
                diagnosis={"quarantine": self.summary()}) from error
        import logging
        logging.getLogger("bigdl_tpu").warning(
            "ingest quarantined record %s (%s) at stage %s: %r "
            "[%d/%d budget]", index, name, stage, error, self.count,
            self.budget)

    def summary(self) -> dict:
        with self._lock:
            return {"count": self.count, "budget": self.budget,
                    "by_stage": dict(self.by_stage),
                    "samples": list(self.samples)}


class _StageSupervisor:
    """Monitor for the engine's stage threads and ring heartbeats.

    Each restartable stage registers a thread factory and a done flag;
    the monitor polls: a thread that is dead with its done flag unset
    (it neither finished nor surfaced an error — a silent crash) is
    restarted from shared stage state, up to ``max_restarts`` times,
    then the engine is DECLARED DEAD: ``failure`` is set and ``failed``
    signaled so the blocked consumer wakes immediately.  With
    ``stall_timeout`` > 0 the monitor also watches ring progress
    heartbeats: no ring progressing while the consumer is blocked
    waiting means a wedged handoff — declared dead with the per-stage
    stats in the error instead of hanging forever."""

    POLL_S = 0.02

    def __init__(self, max_restarts: int, stall_timeout: float,
                 diagnose, rings: Sequence["_Ring"],
                 run_stats: Optional[dict] = None,
                 autoscale=None, autoscale_interval: float = 0.25):
        self.max_restarts = max(0, int(max_restarts))
        self.stall_timeout = float(stall_timeout)
        #: autoscale tick: called every ``autoscale_interval`` from the
        #: monitor loop (restart + scaling share one supervisor — the
        #: stage-lifecycle authority).  A failing tick disables itself
        #: rather than killing a working engine.
        self._autoscale = autoscale
        self._autoscale_interval = max(0.01, float(autoscale_interval))
        self._autoscale_due = time.monotonic() + self._autoscale_interval
        self._diagnose = diagnose          # () -> stats dict, for errors
        #: THIS run's StageStats (progress source for the stall check —
        #: the engine-wide diagnose merge would let a sibling shard
        #: run's progress mask this run's wedge)
        self._run_stats = run_stats or {}
        self._rings = list(rings)
        self._stages: dict = {}
        self.failure: Optional[BaseException] = None   # guarded-by: _lock
        self.failed = threading.Event()
        self.consumer_waiting_since: Optional[float] = None
        self._last_items = -1
        self._last_items_at: Optional[float] = None
        self.restarts = 0                              # guarded-by: _lock
        self._lock = analysis.make_lock("ingest.supervisor")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def register(self, name: str, factory, done_flag: List[bool]) -> None:
        """Track a stage: ``factory()`` builds AND STARTS a replacement
        thread (resuming from the stage's shared state); ``done_flag[0]``
        is set by the stage on any orderly exit — completion or error
        surfaced downstream — and gates restarts."""
        self._stages[name] = {"factory": factory, "thread": factory(),
                              "done": done_flag, "restarts": 0}

    def thread(self, name: str) -> threading.Thread:
        return self._stages[name]["thread"]

    def declare_failed(self, error: BaseException) -> None:
        with self._lock:
            if self.failure is None:
                self.failure = error
        self.failed.set()

    def count_restart(self, stage: str) -> None:
        with self._lock:
            self.restarts += 1
        telemetry.counter(
            "Ingest/stage_restarts", labels={"stage": stage},
            help="dead ingest stage workers restarted by the "
                 "supervisor").inc()

    def start(self) -> "_StageSupervisor":
        self._thread = threading.Thread(target=self._monitor, daemon=True,
                                        name="ingest-supervisor")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5)
            self._thread = None

    # -- monitor ----------------------------------------------------------

    def _monitor(self) -> None:
        import logging
        logger = logging.getLogger("bigdl_tpu")
        while not self._stop.wait(self.POLL_S):
            if self.failure is not None:
                return
            try:
                self._poll_once(logger)
            except BaseException as e:
                # the monitor must NEVER die silently: with it gone the
                # consumer would block on sup.failed forever — exactly
                # the hang this thread exists to prevent.  A failing
                # restart factory (thread exhaustion) or diagnose call
                # becomes an engine failure instead.
                self.declare_failed(IngestInfraError(
                    f"ingest supervisor failed: {e!r}"))
                return
            if self.failure is not None:
                return

    def _poll_once(self, logger) -> None:
        for name, st in self._stages.items():
            if st["done"][0] or st["thread"].is_alive():
                continue
            # dead without an orderly exit: a silent crash
            if st["restarts"] >= self.max_restarts:
                self.declare_failed(IngestInfraError(
                    f"ingest stage '{name}' died "
                    f"{st['restarts'] + 1} time(s) (restart budget "
                    f"bigdl.ingest.maxStageRestarts="
                    f"{self.max_restarts} exhausted)",
                    diagnosis=self._diagnose()))
                return
            st["restarts"] += 1
            self.count_restart(name)
            logger.warning(
                "ingest stage '%s' thread died silently — "
                "restarting from shared stage state (%d/%d)",
                name, st["restarts"], self.max_restarts)
            st["thread"] = st["factory"]()
        if self.stall_timeout > 0:
            self._check_stall()
        if self._autoscale is not None:
            now = time.monotonic()
            if now >= self._autoscale_due:
                self._autoscale_due = now + self._autoscale_interval
                try:
                    self._autoscale()
                except BaseException as e:
                    # scaling is an optimization, never a failure mode:
                    # a tick that cannot act (thread exhaustion on a
                    # spawn, …) logs once and stops trying
                    logger.warning(
                        "ingest autoscaler disabled after error: %r", e)
                    self._autoscale = None

    def _check_stall(self) -> None:
        waiting = self.consumer_waiting_since
        if waiting is None:
            return
        now = time.monotonic()
        if now - waiting < self.stall_timeout:
            return
        newest = max((r.last_progress for r in self._rings),
                     default=0.0)
        if now - newest < self.stall_timeout:
            return
        # ring silence alone is not a wedge: a slow stage working a big
        # item (a long assemble with full record ring + empty batch
        # ring) heartbeats no ring but still COMPLETES items — consult
        # THIS run's per-stage item counters before declaring death
        # (this run's own stats, not the engine-wide merge: a sibling
        # shard run's progress must not mask this run's wedge)
        items = sum(s.items for s in self._run_stats.values())
        if items != self._last_items or self._last_items_at is None:
            self._last_items = items
            self._last_items_at = now
            return
        if now - self._last_items_at < self.stall_timeout:
            return
        self.declare_failed(IngestStallError(
            f"ingest wedged: no ring progressed for "
            f"{now - newest:.1f}s and no stage completed an item for "
            f"{now - self._last_items_at:.1f}s while the consumer was "
            f"blocked (bigdl.ingest.stallTimeoutSec={self.stall_timeout});"
            " per-stage stats in .diagnosis name the stuck handoff",
            diagnosis=self._diagnose()))


class StageStats:
    """Counters for one pipeline stage.

    ``items``/``busy_s`` measure the stage's own work; ``starve_s`` is time
    blocked waiting for its upstream ring, ``backpressure_s`` time blocked
    on a full downstream ring.  A stage whose starve dominates is fed too
    slowly (look upstream); one whose backpressure dominates is faster than
    its consumer (look downstream); the bottleneck stage shows near-zero
    stall and the highest busy fraction."""

    def __init__(self, name: str):
        self.name = name
        self._lock = analysis.make_lock("ingest.stage_stats")
        self.items = 0
        self.busy_s = 0.0
        self.starve_s = 0.0
        self.backpressure_s = 0.0
        self._occ_sum = 0
        self._occ_n = 0
        self._t0 = time.monotonic()

    def add(self, items: int = 0, busy_s: float = 0.0,
            starve_s: float = 0.0, backpressure_s: float = 0.0) -> None:
        with self._lock:
            self.items += items
            self.busy_s += busy_s
            self.starve_s += starve_s
            self.backpressure_s += backpressure_s

    def sample_occupancy(self, depth: int) -> None:
        with self._lock:
            self._occ_sum += depth
            self._occ_n += 1

    def stall_seconds(self) -> Tuple[float, float]:
        """(starve_s, backpressure_s) — the autoscaler's raw signals."""
        with self._lock:
            return self.starve_s, self.backpressure_s

    def snapshot(self) -> dict:
        with self._lock:
            wall = max(time.monotonic() - self._t0, 1e-9)
            return {
                "items": self.items,
                "throughput_per_sec": round(self.items / wall, 1),
                "busy_s": round(self.busy_s, 3),
                "starve_s": round(self.starve_s, 3),
                "backpressure_s": round(self.backpressure_s, 3),
                "stall_frac": round(
                    (self.starve_s + self.backpressure_s) / wall, 3),
                "mean_queue_depth": round(self._occ_sum / self._occ_n, 2)
                if self._occ_n else 0.0,
            }


class _Ring:
    """Bounded stage-coupling queue with stall accounting.

    ``put`` charges blocked time to the producing stage's ``backpressure_s``
    (a full ring means the downstream stage is the bottleneck); ``get``
    charges the consuming stage's ``starve_s``.  Both poll a stop event so
    teardown can never deadlock a stage thread.

    ``limit`` is the DYNAMIC depth: it starts at the configured depth and
    the host-memory governor's shrinkers may halve it mid-run
    (:meth:`shrink`) — a ring at or above its limit behaves exactly like a
    full one, so the shrink flows through the existing backpressure
    accounting rather than a new mechanism.  ``account``/``sizer`` keep a
    governor byte ledger current as items enter and leave."""

    def __init__(self, depth: int, producer: Optional[StageStats] = None,
                 consumer: Optional[StageStats] = None,
                 account=None, sizer=None):
        depth = max(1, int(depth))
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        #: dynamic depth cap, <= the queue's hard maxsize; shrinks persist
        self.limit = depth
        self._producer = producer
        self._consumer = consumer
        self._account = account
        self._sizer = sizer
        #: progress heartbeat: monotonic time of the last successful
        #: put/get — the stage supervisor's wedged-handoff signal and
        #: the watchdog's stall diagnostic (ring age)
        self._hb_lock = analysis.make_lock("ingest.ring")
        self.last_progress = time.monotonic()    # guarded-by: _hb_lock

    def shrink(self) -> int:
        """Halve the dynamic depth (floor 1); returns the new limit."""
        self.limit = max(1, self.limit // 2)
        return self.limit

    def _charge(self, item, sign: int) -> None:
        if self._account is None:
            return
        try:
            n = self._sizer(item) if self._sizer is not None else 0
        except Exception:       # accounting must never break the stage
            n = 0
        if n:
            (self._account.add if sign > 0 else self._account.sub)(n)

    def put(self, item, stop: Optional[threading.Event]) -> bool:
        t0 = None
        while stop is None or not stop.is_set():
            if self.q.qsize() >= self.limit:
                # at (or shrunk below) the dynamic depth: identical to a
                # full queue — wait, charging backpressure
                if t0 is None:
                    t0 = time.monotonic()
                if stop is None:
                    time.sleep(0.05)
                else:
                    stop.wait(0.05)
                continue
            try:
                self.q.put(item, timeout=0.05)
            except queue.Full:
                if t0 is None:
                    t0 = time.monotonic()
                continue
            with self._hb_lock:
                self.last_progress = time.monotonic()
            self._charge(item, +1)
            if t0 is not None and self._producer is not None:
                # StageStats is internally locked: .add() is thread-safe
                self._producer.add(backpressure_s=time.monotonic() - t0)  # lint: allow(missing-guarded-by)
            if self._producer is not None:
                self._producer.sample_occupancy(self.q.qsize())
            return True
        if t0 is not None and self._producer is not None:
            self._producer.add(backpressure_s=time.monotonic() - t0)
        return False

    def get(self, stop: Optional[threading.Event]):
        t0 = None
        while stop is None or not stop.is_set():
            try:
                item = self.q.get(timeout=0.05)
                with self._hb_lock:
                    self.last_progress = time.monotonic()
                self._charge(item, -1)
                if t0 is not None and self._consumer is not None:
                    # StageStats is internally locked: .add() is thread-safe
                    self._consumer.add(starve_s=time.monotonic() - t0)  # lint: allow(missing-guarded-by)
                return item
            except queue.Empty:
                if t0 is None:
                    t0 = time.monotonic()
        if t0 is not None and self._consumer is not None:
            self._consumer.add(starve_s=time.monotonic() - t0)
        return _NO_ITEM

    def try_get(self):
        try:
            item = self.q.get_nowait()
        except queue.Empty:
            return _NO_ITEM
        self._charge(item, -1)
        return item

    def drain(self) -> None:
        try:
            while True:
                item = self.q.get_nowait()
                self._charge(item, -1)
        except queue.Empty:
            pass


class _DecodePool:
    """Resizable decode worker pool — the stage autoscaler's actuator.

    ``concurrent.futures.ThreadPoolExecutor`` can grow its pool but
    never shrink it; the autoscaler needs both directions.  Workers
    pull ``(future, fn, args)`` tickets from an internal queue and
    resolve real :class:`concurrent.futures.Future` objects, so every
    call site written against the executor API (``submit``,
    ``Future.result``, ``shutdown(cancel_futures=True)``) works
    unchanged — including the assembler's dead-decode-worker resubmit
    path, which observes exceptions through the future exactly as with
    the executor.  ``set_workers`` retires surplus workers
    cooperatively: each worker re-checks the target between tickets and
    exits when the pool is over target; a mid-decode worker finishes
    its ticket first, so no decode is ever abandoned by a scale-down.

    The ticket queue is unbounded by construction but bounded in
    practice: the assembler's decode window (``decoded_ring_depth``,
    governor-shrinkable) is the only submitter and never holds more
    than ``window`` tickets in flight."""

    def __init__(self, workers: int, thread_name_prefix: str = "decode"):
        self._tickets: "queue.Queue" = queue.Queue()
        self._prefix = thread_name_prefix
        self._lock = analysis.make_lock("ingest.decode_pool")
        self._target = max(1, int(workers))     # guarded-by: _lock
        self._alive = 0                         # guarded-by: _lock
        self._seq = 0                           # guarded-by: _lock
        self._shutdown = False                  # guarded-by: _lock
        for _ in range(self._target):
            self._spawn()

    def _spawn(self) -> None:
        with self._lock:
            self._alive += 1
            self._seq += 1
            name = f"{self._prefix}-{self._seq}"
        t = threading.Thread(target=self._worker, daemon=True, name=name)
        t.start()

    def _worker(self) -> None:
        while True:
            with self._lock:
                if self._shutdown or self._alive > self._target:
                    self._alive -= 1
                    return
            try:
                ticket = self._tickets.get(timeout=0.1)
            except queue.Empty:
                continue
            fut, fn, args = ticket
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args))
            except BaseException as e:
                # surfaces at Future.result() on the assembler — same
                # taxonomy routing as the executor path
                fut.set_exception(e)

    @property
    def workers(self) -> int:
        return self._target

    def set_workers(self, n: int) -> int:
        """Resize toward ``n`` (floor 1); returns the new target.
        Growth spawns immediately; shrink is cooperative (workers exit
        between tickets, never mid-decode)."""
        n = max(1, int(n))
        with self._lock:
            if self._shutdown:
                return self._target
            grow = n - self._target
            self._target = n
        for _ in range(grow):
            self._spawn()
        return n

    def submit(self, fn, *args) -> "futures.Future":
        fut: "futures.Future" = futures.Future()
        self._tickets.put((fut, fn, args))
        return fut

    def shutdown(self, wait: bool = False,
                 cancel_futures: bool = False) -> None:
        with self._lock:
            self._shutdown = True
        if cancel_futures:
            try:
                while True:
                    fut, _fn, _args = self._tickets.get_nowait()
                    fut.cancel()
            except queue.Empty:
                pass


class AutoscalePolicy:
    """Deterministic hysteresis policy for ingest stage autoscaling.

    Pure state machine — no clocks, no randomness: a fixed sequence of
    signal samples always produces the same action sequence (asserted
    by tests/test_ingest.py), so autoscaling can never make a run
    nondeterministic in anything but wall-clock.

    Per :meth:`decide` call (one per ``bigdl.ingest.autoscale.
    intervalSec`` interval), the signals are the assemble stage's
    starve and backpressure FRACTIONS over the interval just ended:
    starve = the assembler waited on decode (the scale-UP signal),
    backpressure = the batch ring was full, i.e. the consumer is the
    bottleneck and more decode workers cannot help (a scale-DOWN
    signal).  ``patience`` consecutive same-direction signals are
    required before acting; after an action the policy holds for
    ``cooldown`` intervals so the new worker count's effect is measured
    before the next decision.  The host-memory governor is the upper-
    bound authority: under pressure the policy never scales up and
    steps down toward the floor."""

    def __init__(self, min_workers: int, max_workers: int,
                 up_starve_frac: float, down_starve_frac: float,
                 patience: int, cooldown: int):
        self.min_workers = max(1, int(min_workers))
        self.max_workers = max(self.min_workers, int(max_workers))
        self.up_starve_frac = float(up_starve_frac)
        self.down_starve_frac = float(down_starve_frac)
        self.patience = max(1, int(patience))
        self.cooldown = max(0, int(cooldown))
        self._up_streak = 0
        self._down_streak = 0
        self._hold = 0

    def decide(self, starve_frac: float, backpressure_frac: float,
               workers: int, under_pressure: bool = False) -> int:
        """One interval's decision: +1 add a worker, -1 retire one, 0
        hold."""
        if self._hold > 0:
            self._hold -= 1
            return 0
        down = (workers > self.min_workers and
                (under_pressure or
                 starve_frac <= self.down_starve_frac or
                 backpressure_frac >= self.up_starve_frac))
        up = (not down and not under_pressure and
              workers < self.max_workers and
              starve_frac >= self.up_starve_frac)
        if up:
            self._up_streak += 1
            self._down_streak = 0
        elif down:
            self._down_streak += 1
            self._up_streak = 0
        else:
            self._up_streak = self._down_streak = 0
        if self._up_streak >= self.patience:
            self._up_streak = 0
            self._hold = self.cooldown
            return 1
        if self._down_streak >= self.patience:
            self._down_streak = 0
            self._hold = self.cooldown
            return -1
        return 0


class ShardedSeqFileReader:
    """Parallel SequenceFile record source preserving the global order.

    ``shards`` reader threads (``bigdl.ingest.shards``) own the ``*.seq``
    files round-robin and stream records into per-shard rings; the merge
    side drains one file at a time in sorted-walk order, so the yielded
    sequence is byte-identical to a sequential
    :func:`~bigdl_tpu.dataset.seqfile.read_image_seqfile` sweep — sharding
    is a latency detail, not an ordering change.  IO and vint/frame parsing
    for file k+1..k+shards overlap the consumer's handling of file k."""

    def __init__(self, path: str, shards: Optional[int] = None,
                 ring_depth: Optional[int] = None,
                 quarantine: Optional[RecordQuarantine] = None):
        if os.path.isdir(path):
            self.files: List[str] = []
            for root, _, files in sorted(os.walk(path)):
                for fname in sorted(files):
                    if fname.endswith(".seq"):
                        self.files.append(os.path.join(root, fname))
        else:
            self.files = [path]
        self.shards = max(1, shards if shards is not None
                          else config.get_int("bigdl.ingest.shards", 2))
        self.ring_depth = (ring_depth if ring_depth is not None
                           else config.get_int("bigdl.ingest.recordRingDepth", 256))
        self.stats = StageStats("seqfile_read")
        #: data-error sink; None = build one per sweep from
        #: ``bigdl.ingest.maxBadRecords`` (budget 0 keeps the historical
        #: fail-fast: corrupt record -> IOError on the merge side)
        self.quarantine = quarantine

    def _file_records(self, path: str,
                      quarantine: Optional[RecordQuarantine]) -> Iterator:
        """One file's (name, label, data) records, self-healing: corrupt
        records resync-skip into the quarantine (budget permitting), and
        a TRANSIENT read failure re-opens the file and resumes after the
        already-yielded prefix — the ``utils.file_io`` capped-backoff
        policy applied to a streaming read (``file_io.retrying`` itself
        wraps one call; a generator needs the resume)."""
        from bigdl_tpu.dataset.seqfile import (CorruptRecordError,
                                               read_image_seqfile,
                                               read_image_seqfile_resilient)
        from bigdl_tpu.utils import file_io

        attempts = max(1, config.get_int("bigdl.io.retryTimes", 3))
        base = config.get_float("bigdl.io.retryInterval", 0.1)
        yielded = 0
        attempt = 1
        resilient = False    # fast native path until the FIRST corruption
        # a transient failure REPLAYS the file from the top; corrupt
        # records are deterministic, so the replay re-encounters skips
        # already admitted — count events and admit only the new ones,
        # or every replay would burn quarantine budget twice
        skips = {"admitted": 0}
        while True:
            seen = 0
            pass_start = yielded
            pass_skips = [0]
            try:
                if resilient:
                    def on_skip(err, resume):
                        pass_skips[0] += 1
                        if pass_skips[0] > skips["admitted"]:
                            quarantine.admit("seqfile_read", None, path,
                                             err)
                            skips["admitted"] = pass_skips[0]
                    src = read_image_seqfile_resilient(path,
                                                       on_skip=on_skip)
                else:
                    src = read_image_seqfile(path)
                for rec in src:
                    seen += 1
                    if seen <= yielded:
                        continue     # replayed prefix after a retry
                    yield rec
                    yielded += 1
                return
            except CorruptRecordError as e:
                if (resilient or quarantine is None or
                        quarantine.budget <= 0):
                    raise            # fail-fast contract (budget 0)
                # dirty file discovered: replay through the resilient
                # Python reader, which resyncs past the damage and
                # admits each skip into the quarantine.  Clean files
                # never pay for this — they stay on the native reader.
                resilient = True
            except Exception as e:
                if yielded > pass_start:
                    # this pass made fresh progress before failing: the
                    # budget is per-blip, like file_io.retrying grants
                    # it per operation — not per lifetime of the file
                    attempt = 1
                if attempt >= attempts or not file_io._is_transient(e):
                    raise
                delay = base * (2.0 ** (attempt - 1))
                import logging
                logging.getLogger("bigdl_tpu").warning(
                    "transient seqfile read failure on %s (attempt "
                    "%d/%d, resuming after record %d in %.2fs): %r",
                    path, attempt, attempts, yielded, delay, e)
                file_io._sleep(delay)
                attempt += 1

    def __iter__(self) -> Iterator:
        from bigdl_tpu.dataset.image import LabeledImageBytes

        if not self.files:
            return
        quarantine = (self.quarantine if self.quarantine is not None
                      else RecordQuarantine())
        self.last_quarantine = quarantine   # observable after the sweep
        n = min(self.shards, len(self.files))
        stop = threading.Event()
        rings = [_Ring(max(1, self.ring_depth // n), producer=self.stats)
                 for _ in range(n)]
        file_end = object()

        def reader(si: int) -> None:
            try:
                for fi in range(si, len(self.files), n):
                    t0 = time.monotonic()
                    for name, label, data in self._file_records(
                            self.files[fi], quarantine):
                        t1 = time.monotonic()
                        self.stats.add(items=1, busy_s=t1 - t0)
                        telemetry.add_span_s("ingest/seqfile_read", t0, t1)
                        if not rings[si].put(
                                LabeledImageBytes(name, label, data), stop):
                            return
                        t0 = time.monotonic()
                    if not rings[si].put(file_end, stop):
                        return
            except BaseException as e:  # surfaced on the merge side
                rings[si].put(e, stop)

        threads = [threading.Thread(target=reader, args=(si,), daemon=True,
                                    name=f"ingest-seqread{si}")
                   for si in range(n)]
        for t in threads:
            t.start()
        try:
            for fi in range(len(self.files)):
                ring = rings[fi % n]
                while True:
                    item = ring.get(None)
                    if item is file_end:
                        break
                    if isinstance(item, BaseException):
                        raise item
                    yield item
        finally:
            stop.set()
            for ring in rings:
                ring.drain()
            for t in threads:
                t.join(timeout=5)
            for ring in rings:
                ring.drain()


class StreamingIngest(Transformer):
    """Compressed byte records → MiniBatches, stage-pipelined.

    Drop-in pipelined replacement for
    :class:`~bigdl_tpu.dataset.mt_batch.MTLabeledBGRImgToBatch` (same
    constructor surface, same output semantics — asserted bit-identical by
    ``tests/test_prefetch_determinism.py``), with the per-batch barriers
    removed:

    - a *reader* thread pulls upstream records into a bounded record ring;
    - a *decode pool* (``decode_workers`` threads; cv2/PIL JPEG decode
      releases the GIL) holds a sliding window of in-flight decodes that
      spans batch boundaries — decode of batch k+1 proceeds while batch k
      is being packed;
    - an *assembler* thread consumes decoded images in strict record
      order, draws crop/flip from the (cloned) RNG stream, and packs full
      batches with the native std::thread assembler (ctypes releases the
      GIL for the call, so packing overlaps the pool);
    - assembled MiniBatches buffer in a bounded *batch ring* the consumer
      drains, each carrying the RNG state to commit on consumption.

    Ring depths and pool width default from ``bigdl.ingest.*``; constructor
    arguments override per instance.
    """

    def __init__(self, batch_size: int, crop: Tuple[int, int] = (224, 224),
                 mean: Sequence[float] = (104.0, 117.0, 123.0),
                 std: Sequence[float] = (1.0, 1.0, 1.0),
                 random_crop: bool = True, hflip: bool = True,
                 device_normalize: bool = False,
                 device_augment: Optional[bool] = None,
                 device_jitter: bool = False,
                 decode_workers: Optional[int] = None,
                 record_ring_depth: Optional[int] = None,
                 decoded_ring_depth: Optional[int] = None,
                 batch_ring_depth: Optional[int] = None,
                 assemble_threads: Optional[int] = None,
                 name: Optional[str] = None,
                 max_bad_records: Optional[int] = None,
                 max_stage_restarts: Optional[int] = None,
                 fallback_on_failure: Optional[bool] = None,
                 stall_timeout: Optional[float] = None,
                 autoscale: Optional[bool] = None,
                 epoch_cache: Optional[bool] = None):
        if name is None:
            with _NAME_LOCK:
                name = f"ingest{_NAME_SEQ[0]}"
                _NAME_SEQ[0] += 1
        # distinguishes this engine's summary tags / log lines when more
        # than one engine is alive (train + validation pipelines, …)
        self.name = name
        self.batch_size = batch_size
        self.crop = crop
        self.mean, self.std = mean, std
        self.random_crop, self.hflip = random_crop, hflip
        self.device_normalize = device_normalize
        # device_augment: pack FULL uint8 NHWC frames plus ride-along
        # crop offsets/flips (drawn host-side from the clone-and-commit
        # stream, so parity with the host path is provable) and leave
        # crop/flip/transpose to nn.DeviceAugment inside the fused step.
        # Implies the uint8-upload layout: pair with nn.ChannelNormalize.
        self.device_augment = (
            device_augment if device_augment is not None
            else config.get_bool("bigdl.ingest.deviceAugment", False))
        # device_jitter: additionally ride along one int32 ColorJitter
        # seed per record, drawn from the same stream (breaks host-path
        # bit-parity by design — the host path has no jitter)
        self.device_jitter = bool(device_jitter)
        cores = max(1, os.cpu_count() or 1)
        self.decode_workers = (decode_workers if decode_workers is not None
                               else config.get_int("bigdl.ingest.decodeWorkers",
                                                   cores))
        self.record_ring_depth = (
            record_ring_depth if record_ring_depth is not None
            else config.get_int("bigdl.ingest.recordRingDepth", 256))
        self.decoded_ring_depth = (
            decoded_ring_depth if decoded_ring_depth is not None
            else config.get_int("bigdl.ingest.decodedRingDepth",
                                 2 * batch_size))
        self.batch_ring_depth = (
            batch_ring_depth if batch_ring_depth is not None
            else config.get_int("bigdl.ingest.batchRingDepth", 2))
        self.assemble_threads = assemble_threads or cores
        self.max_bad_records = (
            max_bad_records if max_bad_records is not None
            else config.get_int("bigdl.ingest.maxBadRecords", 0))
        self.max_stage_restarts = (
            max_stage_restarts if max_stage_restarts is not None
            else config.get_int("bigdl.ingest.maxStageRestarts", 2))
        self.fallback_on_failure = (
            fallback_on_failure if fallback_on_failure is not None
            else config.get_bool("bigdl.ingest.fallbackOnFailure", False))
        self.stall_timeout = (
            stall_timeout if stall_timeout is not None
            else config.get_float("bigdl.ingest.stallTimeoutSec", 0.0))
        self.autoscale = (
            autoscale if autoscale is not None
            else config.get_bool("bigdl.ingest.autoscale.enabled", True))
        #: live worker counts per stage (the Ingest/<stage>/workers
        #: gauges) and the autoscaler's action counters — mutated by the
        #: supervisor tick, read by summary_scalars and the driver's
        #: end-of-run decomposition log
        self.stage_workers = {"decode": self.decode_workers,
                              "assemble": self.assemble_threads}
        self.autoscale_events = {"up": 0, "down": 0}
        #: decoded-epoch cache, engine-lifetime (epoch 2 is a second run
        #: of the SAME transformer instance — the cache must outlive runs)
        use_cache = (epoch_cache if epoch_cache is not None
                     else config.get_bool("bigdl.ingest.epochCache", False))
        self.epoch_cache = None
        if use_cache:
            from bigdl_tpu.dataset.epoch_cache import DecodedEpochCache
            self.epoch_cache = DecodedEpochCache(
                name=self.name,
                cache_dir=config.get_property(
                    "bigdl.ingest.epochCacheDir"),
                budget_mb=config.get_int(
                    "bigdl.ingest.epochCacheBudgetMB", 0),
                segment_records=config.get_int(
                    "bigdl.ingest.epochCacheSegmentRecords", 256))
        # per-run stage stats: a ShardedDataSet applies ONE transformer
        # instance to every shard, so several runs can be live at once —
        # each run appends its own dict and stats() merges them
        self._active_stats: List[dict] = []
        self._last_stats: Optional[dict] = None
        #: latest run's quarantine / supervisor, for diagnostics + tests;
        #: _active_faults mirrors _active_stats (several shard runs can
        #: be live at once — monitoring must SUM them, not report the
        #: last-started run); run_history keeps a LIGHTWEIGHT summary
        #: dict per finished run ({"quarantine": ..., "stage_restarts":
        #: n}) so a multi-epoch soak can audit what epoch 1 quarantined
        #: without pinning dead threads/rings for the engine's lifetime
        self.quarantine: Optional[RecordQuarantine] = None
        self.supervisor: Optional[_StageSupervisor] = None
        self._active_faults: List[tuple] = []
        self._last_faults: Optional[tuple] = None
        self.run_history: List[dict] = []
        self.fallbacks = 0

    # ---- diagnostics ----------------------------------------------------

    def has_active_run(self) -> bool:
        """True while at least one pipeline run of this engine is live."""
        return bool(self._active_stats)

    def _fault_pairs(self) -> List[tuple]:
        """(quarantine, supervisor) of every ACTIVE run, else the last
        finished one — same merge contract as :meth:`stats`."""
        pairs = list(self._active_faults)
        if not pairs and self._last_faults is not None:
            pairs = [self._last_faults]
        return pairs

    def quarantined_count(self) -> int:
        """Data-error records skipped, summed over every active run."""
        return sum(q.count for q, _ in self._fault_pairs())

    def stage_restart_count(self) -> int:
        return sum(s.restarts for _, s in self._fault_pairs())

    def ring_ages(self) -> dict:
        """Seconds since each ring last made progress — the freshest
        (minimum) age across active runs, the wedged-handoff signal the
        supervisor and the watchdog diagnostics read; empty before the
        first run."""
        now = time.monotonic()
        out: dict = {}
        for _, sup in self._fault_pairs():
            for name, ring in zip(("record_ring", "batch_ring"),
                                  sup._rings):
                age = round(now - ring.last_progress, 3)
                out[name] = min(out.get(name, age), age)
        return out

    def fault_stats(self) -> dict:
        """Self-healing counters merged over the active runs (multi-
        shard pipelines sum, like :meth:`stats`): quarantine summary,
        stage restarts, fallbacks — the robustness sibling of
        :meth:`stats`."""
        pairs = self._fault_pairs()
        quarantine = {"count": sum(q.count for q, _ in pairs),
                      "samples": [s for q, _ in pairs
                                  for s in q.samples]}
        if len(pairs) == 1:
            quarantine = pairs[0][0].summary()
        return {
            "quarantine": quarantine if pairs else {},
            "stage_restarts": sum(s.restarts for _, s in pairs),
            "fallbacks": self.fallbacks,
            "ring_ages_s": self.ring_ages(),
        }

    def stats(self) -> dict:
        """Per-stage snapshots: the merge of every ACTIVE run (multi-shard
        pipelines sum their counters), else the last finished run."""
        runs = list(self._active_stats)
        if not runs and self._last_stats is not None:
            runs = [self._last_stats]
        if not runs:
            return {}
        if len(runs) == 1:
            return {name: s.snapshot() for name, s in runs[0].items()}
        out = {}
        for name in ("read", "decode", "assemble", "consume"):
            snaps = [r[name].snapshot() for r in runs if name in r]
            if not snaps:
                continue
            n = len(snaps)
            out[name] = {
                "items": sum(s["items"] for s in snaps),
                "throughput_per_sec": round(
                    sum(s["throughput_per_sec"] for s in snaps), 1),
                "busy_s": round(sum(s["busy_s"] for s in snaps), 3),
                "starve_s": round(sum(s["starve_s"] for s in snaps), 3),
                "backpressure_s": round(
                    sum(s["backpressure_s"] for s in snaps), 3),
                "stall_frac": round(
                    sum(s["stall_frac"] for s in snaps) / n, 3),
                "mean_queue_depth": round(
                    sum(s["mean_queue_depth"] for s in snaps) / n, 2),
            }
        return out

    # ---- the pipeline ---------------------------------------------------

    def __call__(self, it: Iterator) -> Iterator:
        import logging
        from bigdl_tpu.dataset.mt_batch import (MTLabeledBGRImgToBatch,
                                                _check_crop_fits,
                                                assemble_batch,
                                                assemble_batch_u8,
                                                crop_flip_host)
        from bigdl_tpu.dataset.sample import MiniBatch
        from bigdl_tpu.utils import chaos, file_io
        from bigdl_tpu.utils.random_generator import RandomGenerator

        logger = logging.getLogger("bigdl_tpu")
        stats = {name: StageStats(name)
                 for name in ("read", "decode", "assemble", "consume")}
        self._active_stats.append(stats)
        _LIVE.add(self)
        quarantine = RecordQuarantine(self.max_bad_records)
        self.quarantine = quarantine

        # the caller's stream is CLONED, not handed off: the assembler
        # draws from the clone in record order, and each batch carries the
        # clone's post-draw state — committed to the shared instance only
        # when the consumer takes the batch.  Read-ahead discarded at an
        # epoch rollover never advances the user-visible stream, so the
        # pipelined sequence stays bit-identical to the synchronous path
        # regardless of ring depths or how far ahead the engine ran.
        #
        # Multiple engines on ONE stream (a ShardedDataSet applies the
        # transformer per shard and the driver pulls the shard iterators
        # alternately): only the FIRST active fork is the stream's
        # committer — secondaries draw from a deterministically reseeded
        # fork (decorrelated per-shard augmentation, the reference's
        # per-partition RNG model, ``dataset/DataSet.scala:262``) and
        # never commit, so alternating consumption cannot interleave
        # incoherent positions onto the caller's stream.  Synchronous-path
        # bit-parity is therefore a SINGLE-engine contract; multi-shard
        # runs are run-to-run deterministic instead.
        shared_rng = RandomGenerator.RNG()
        active_forks = shared_rng.__dict__.setdefault("_ingest_forks", set())
        # secondary forks are numbered by how many forks are already
        # active — NOT a global counter, so re-running the same pipeline
        # derives the identical per-shard seeds
        fork_rank = len(active_forks)
        fork_token = object()
        primary = fork_rank == 0
        active_forks.add(fork_token)
        drawer = RandomGenerator(0)
        drawer.np.set_state(shared_rng.np.get_state())
        if not primary:
            # decorrelate the secondary fork: seed from the fork point +
            # the fork rank, so each shard's stream is distinct but every
            # run derives the identical sequence
            mix = int(np.asarray(shared_rng.np.get_state()[1],
                                 np.uint64).sum())
            drawer.set_seed((mix ^ (0x9E3779B1 * fork_rank)) % (2 ** 31))

        stop = threading.Event()

        # host-memory governor accounting: every bounded buffer this run
        # owns (record ring, decode in-flight window, batch ring) keeps a
        # byte ledger current, rolled up into Resources/host_bytes
        rec_acct = _governor.account("ingest_record_ring")
        bat_acct = _governor.account("ingest_batch_ring")
        dec_acct = _governor.account("ingest_decode_window")
        dec_outstanding = [0]    # this run's share, settled at teardown

        def _rec_nbytes(item):
            if isinstance(item, tuple) and len(item) == 2:
                return _item_nbytes(getattr(item[1], "bytes", None))
            return 0

        def _bat_nbytes(item):
            if isinstance(item, tuple) and len(item) == 2:
                return _item_nbytes(item[0])
            return 0

        def _dec_charge(rec, sign: int) -> None:
            n = _item_nbytes(getattr(rec, "bytes", None))
            if n:
                dec_outstanding[0] += sign * n
                (dec_acct.add if sign > 0 else dec_acct.sub)(n)

        record_ring = _Ring(self.record_ring_depth,
                            producer=stats["read"],
                            consumer=stats["assemble"],
                            account=rec_acct, sizer=_rec_nbytes)
        batch_ring = _Ring(self.batch_ring_depth,
                           producer=stats["assemble"],
                           consumer=stats["consume"],
                           account=bat_acct, sizer=_bat_nbytes)
        pool = _DecodePool(self.decode_workers,
                           thread_name_prefix="ingest-decode")
        epoch_cache = self.epoch_cache
        ch, cw = self.crop

        # shared stage state: everything a RESTARTED stage thread needs to
        # resume exactly where its dead predecessor stopped (the chaos
        # injector kills at the loop top, a consistent point) lives here,
        # never in thread-local closure variables
        rd = {"index": 0, "exhausted": False, "inhand": None}
        rd_done = [False]        # orderly exit (completion or surfaced error)
        asm = {"pending": deque(),   # (index, record, decode future) in order
               "done": False,        # upstream exhausted / error queued
               "aborted": False,     # teardown stop observed mid-wait
               "imgs": [], "recs": [], "offsets": [], "flips": [],
               "seeds": [],          # ride-along ColorJitter keys (jitter on)
               "items": 0,           # records fully handled (chaos kill key)
               "decode_restarts": 0}
        asm_done = [False]

        def reader() -> None:
            """Pull upstream records into the record ring.  The upstream
            iterator draws no host RNG (crop/flip belongs to the assembler;
            reshuffles to the training driver's producer), so running it on
            its own thread keeps the single-drawer contract intact."""
            try:
                while True:
                    if chaos.kill_stage_thread("reader", rd["index"]):
                        return          # silent death — supervisor's job
                    chaos.starve_stage("read", rd["index"])
                    t0 = time.monotonic()
                    try:
                        rec = next(it)
                    except StopIteration:
                        rd["exhausted"] = True
                        break
                    idx, rd["index"] = rd["index"], rd["index"] + 1
                    try:
                        # transient blips retry with the file_io backoff;
                        # data faults (fatal) pass straight through
                        file_io.retrying(chaos.on_record_read, idx,
                                         op="ingest record read")
                    except BaseException as e:
                        if _is_data_error(e):
                            quarantine.admit("read", idx,
                                             getattr(rec, "name", None), e)
                            continue     # one record skipped, stream lives
                        raise
                    t1 = time.monotonic()
                    stats["read"].add(items=1, busy_s=t1 - t0)
                    telemetry.add_span_s("ingest/read", t0, t1)
                    if not record_ring.put((idx, rec), stop):
                        # teardown aborted the handoff: keep the in-hand
                        # record so a fallback drain loses nothing
                        rd["inhand"] = (idx, rec)
                        rd_done[0] = True
                        return
                record_ring.put(_END, stop)
                rd_done[0] = True
            except BaseException as e:  # surface downstream
                record_ring.put(e, stop)
                rd_done[0] = True

        def timed_decode(idx: int, rec) -> np.ndarray:
            if chaos.kill_stage_thread("decode", idx):
                raise _StageKilledError(
                    f"decode worker died at record {idx}")
            chaos.starve_stage("decode", idx)
            t0 = time.monotonic()
            chaos.on_decode(idx)
            key = getattr(rec, "name", None)
            img = epoch_cache.get(key) if epoch_cache is not None else None
            if img is None:
                try:
                    img = MTLabeledBGRImgToBatch._decode(rec.bytes)
                except Exception as e:
                    # junk bytes, not junk machinery: quarantinable
                    raise IngestDataError(
                        f"undecodable image at stream position {idx}: "
                        f"{e!r}") from e
                if epoch_cache is not None:
                    epoch_cache.put(key, img)
            t1 = time.monotonic()
            stats["decode"].add(items=1, busy_s=t1 - t0)
            telemetry.add_span_s("ingest/decode", t0, t1)
            return img

        def fill(block: bool) -> None:
            """Top up the in-flight decode window.  Blocking only when
            the window is empty keeps the assembler from stalling on a
            slow upstream while it still has decoded work to pack."""
            pending = asm["pending"]
            # under host-memory pressure the read-ahead pauses: the
            # window collapses to depth 1 (progress, never deadlock)
            window = (1 if _governor.under_pressure()
                      else self.decoded_ring_depth)
            while not asm["done"] and len(pending) < window:
                item = (record_ring.get(stop) if block and not pending
                        else record_ring.try_get())
                if item is _NO_ITEM:
                    if block and not pending:
                        # stop was set mid-get: TEARDOWN, not upstream
                        # completion — the fallback drain must still see
                        # the remaining upstream records
                        asm["aborted"] = True
                    return
                if item is _END:
                    asm["done"] = True
                    return
                if isinstance(item, BaseException):
                    asm["done"] = True
                    pending.append((None, None, item))
                    return
                idx, rec = item
                _dec_charge(rec, +1)
                pending.append((idx, rec,
                                pool.submit(timed_decode, idx, rec)))

        def pack_batch() -> Tuple["MiniBatch", int, float]:
            """The ONE batch-packing path (native assemble + labels)
            over the shared lists — pipelined emit and fallback emit
            both call it, so they can never drift apart.  Returns the
            batch plus (record count, pack seconds); the CALLER accounts
            the stats once the batch is actually handed off — a pack
            discarded by a teardown-aborted ring put (the fallback
            re-packs it) must not be counted twice."""
            imgs, recs = asm["imgs"], asm["recs"]
            t0 = time.monotonic()
            offs = np.asarray(asm["offsets"], np.int32).reshape(len(imgs), 2)
            fl = np.asarray(asm["flips"], np.uint8)
            if self.device_augment:
                # ship FULL uint8 frames + the ride-along draws; the
                # per-pixel crop/flip/transpose belongs to
                # nn.DeviceAugment inside the fused step.  One np.stack
                # memcpy when the batch's source frames share a shape;
                # a mixed-shape batch pre-crops on the declared host
                # fallback (crop_flip_host) and ships identity
                # ride-alongs — same trained weights either way.
                if len({im.shape for im in imgs}) == 1:
                    frames = np.stack(imgs)
                else:
                    frames = crop_flip_host(imgs, self.crop, offs, fl)
                    offs = np.zeros_like(offs)
                    fl = np.zeros_like(fl)
                x = [frames, offs, fl]
                if self.device_jitter:
                    x.append(np.asarray(asm["seeds"], np.int32))
            elif self.device_normalize:
                x = assemble_batch_u8(imgs, self.crop, offs, fl,
                                      n_threads=self.assemble_threads)
            else:
                x = assemble_batch(imgs, self.crop, offs, fl,
                                   self.mean, self.std,
                                   n_threads=self.assemble_threads)
            y = np.asarray([r.label for r in recs], np.float32)
            t1 = time.monotonic()
            # the span records the pack that really happened (a second
            # pack after an aborted handoff is a real event on the
            # timeline); the STATS are the caller's, on handoff only
            telemetry.add_span_s("ingest/assemble", t0, t1,
                                 {"batch": len(imgs)})
            return MiniBatch(x, y), len(imgs), t1 - t0

        def admit_and_append(idx: int, rec, img) -> bool:
            """Crop-fit check (quarantinable), crop/flip draws in strict
            record order — the same draw sequence MTLabeledBGRImgToBatch
            makes — and append to the shared batch lists.  False when
            the record was quarantined (no RNG drawn: the surviving
            stream's draws equal the sync path's over the survivors).
            Shared by the assembler thread and the fallback drain."""
            try:
                _check_crop_fits(
                    [img], self.crop,
                    describe=lambda _i: (
                        f"StreamingIngest: record {len(asm['imgs'])} of "
                        f"the current batch (label {rec.label})"))
            except ValueError as e:
                quarantine.admit("assemble", idx, rec.name, e)
                return False
            h, w = img.shape[:2]
            if self.random_crop:
                oy = drawer.random_int(0, h - ch + 1)
                ox = drawer.random_int(0, w - cw + 1)
            else:
                oy, ox = (h - ch) // 2, (w - cw) // 2
            fl = int(drawer.uniform() < 0.5) if self.hflip else 0
            asm["imgs"].append(img if img.ndim == 3 else img[:, :, None])
            asm["recs"].append(rec)
            asm["offsets"].append((oy, ox))
            asm["flips"].append(fl)
            if self.device_jitter:
                # the per-record ColorJitter key rides the same clone-
                # and-commit stream: an extra draw AFTER crop/flip, so
                # it is replay-deterministic (and intentionally not
                # host-path-parity — the host path has no jitter)
                asm["seeds"].append(drawer.random_int(0, 2 ** 31 - 1))
            return True

        def emit() -> bool:
            batch, n, pack_s = pack_batch()
            # depth-1 escalation: one batch larger than the whole host
            # budget cannot be backpressured away — structured error
            _governor.check_item("ingest_batch_ring", _item_nbytes(batch))
            ok = batch_ring.put((batch, drawer.np.get_state()), stop)
            if ok:
                stats["assemble"].add(items=n, busy_s=pack_s)
                # on a teardown-aborted put the DRAWN batch stays in the
                # shared lists: the fallback drain re-emits it with its
                # already-drawn offsets/flips instead of losing it
                for key in ("imgs", "recs", "offsets", "flips", "seeds"):
                    asm[key].clear()
            return ok

        def assembler() -> None:
            pending = asm["pending"]
            imgs = asm["imgs"]
            try:
                while True:
                    if chaos.kill_stage_thread("assembler", asm["items"]):
                        return          # silent death — supervisor's job
                    chaos.starve_stage("assemble", asm["items"])
                    fill(block=True)
                    if asm["aborted"]:
                        asm_done[0] = True   # orderly teardown exit
                        return
                    if not pending:
                        break
                    idx, rec, fut = pending.popleft()
                    if rec is None:      # upstream error, in order
                        raise fut
                    _dec_charge(rec, -1)
                    try:
                        if fut.done():
                            img = fut.result()
                        else:            # wait-on-decode = assemble starve
                            t0 = time.monotonic()
                            img = fut.result()
                            stats["assemble"].add(
                                starve_s=time.monotonic() - t0)
                    except _StageKilledError as e:
                        # a dead decode WORKER is infrastructure: the
                        # record's bytes are fine — resubmit the decode,
                        # bounded like any stage restart
                        if asm["decode_restarts"] >= self.max_stage_restarts:
                            raise IngestInfraError(
                                "ingest decode worker died and the "
                                "restart budget (bigdl.ingest."
                                f"maxStageRestarts={self.max_stage_restarts}"
                                ") is exhausted",
                                diagnosis=self.stats()) from e
                        asm["decode_restarts"] += 1
                        sup.count_restart("decode")
                        logger.warning(
                            "ingest decode worker died on record %d — "
                            "resubmitting (%d/%d)", idx,
                            asm["decode_restarts"], self.max_stage_restarts)
                        _dec_charge(rec, +1)
                        pending.appendleft(
                            (idx, rec, pool.submit(timed_decode, idx, rec)))
                        continue
                    except BaseException as e:
                        if _is_data_error(e):
                            # skipped BEFORE any RNG draw: the surviving
                            # stream's draw sequence equals the sync
                            # path's over the surviving records
                            quarantine.admit("decode", idx, rec.name, e)
                            asm["items"] += 1
                            continue
                        raise
                    fill(block=False)    # decode of the NEXT batch proceeds
                    appended = admit_and_append(idx, rec, img)
                    asm["items"] += 1
                    if not appended:
                        continue
                    if len(imgs) == self.batch_size:
                        if not emit():
                            asm_done[0] = True
                            return
                if imgs:
                    if not emit():
                        asm_done[0] = True
                        return
                batch_ring.put(_END, stop)
                asm_done[0] = True
            except BaseException as e:  # surface at the consumer
                batch_ring.put(e, stop)
                asm_done[0] = True

        def _thread_factory(fn, tname):
            def factory():
                t = threading.Thread(target=fn, daemon=True, name=tname)
                t.start()
                return t
            return factory

        autoscale_tick = None
        if self.autoscale:
            cores = max(1, os.cpu_count() or 1)
            as_max = config.get_int("bigdl.ingest.autoscale.max", 0) or cores
            policy = AutoscalePolicy(
                min_workers=config.get_int("bigdl.ingest.autoscale.min", 1),
                max_workers=as_max,
                up_starve_frac=config.get_float(
                    "bigdl.ingest.autoscale.upStarveFrac", 0.2),
                down_starve_frac=config.get_float(
                    "bigdl.ingest.autoscale.downStarveFrac", 0.02),
                patience=config.get_int("bigdl.ingest.autoscale.patience",
                                        2),
                cooldown=config.get_int("bigdl.ingest.autoscale.cooldown",
                                        3))
            prev = {"starve": 0.0, "backpressure": 0.0,
                    "t": time.monotonic()}

            def autoscale_tick() -> None:
                """One supervisor-cadence decision: per-interval deltas
                of the assemble stage's stall counters become fractions
                of the interval, the pure policy decides, the pool (and
                the native assembler's thread count, in tandem) acts."""
                starve, bp = stats["assemble"].stall_seconds()
                now = time.monotonic()
                dt = max(now - prev["t"], 1e-9)
                starve_frac = (starve - prev["starve"]) / dt
                bp_frac = (bp - prev["backpressure"]) / dt
                prev.update(starve=starve, backpressure=bp, t=now)
                delta = policy.decide(starve_frac, bp_frac, pool.workers,
                                      _governor.under_pressure())
                if not delta:
                    return
                n = pool.set_workers(pool.workers + delta)
                self.assemble_threads = n
                self.stage_workers["decode"] = n
                self.stage_workers["assemble"] = n
                direction = "up" if delta > 0 else "down"
                self.autoscale_events[direction] += 1
                telemetry.counter(
                    f"Ingest/autoscale_{direction}",
                    labels={"stage": "decode"}, summary=True,
                    help="ingest worker-scaling actions taken by the "
                         "stage autoscaler").inc()
                logger.info(
                    "ingest '%s' autoscale %s: decode/assemble workers "
                    "-> %d (starve %.2f, backpressure %.2f of interval)",
                    self.name, direction, n, starve_frac, bp_frac)

        sup = _StageSupervisor(self.max_stage_restarts, self.stall_timeout,
                               diagnose=self.stats,
                               rings=[record_ring, batch_ring],
                               run_stats=stats,
                               autoscale=autoscale_tick,
                               autoscale_interval=config.get_float(
                                   "bigdl.ingest.autoscale.intervalSec",
                                   0.25))
        self.supervisor = sup
        sup.register("reader", _thread_factory(reader, "ingest-reader"),
                     rd_done)
        sup.register("assembler",
                     _thread_factory(assembler, "ingest-assembler"),
                     asm_done)
        sup.start()
        fault_pair = (quarantine, sup)
        self._active_faults.append(fault_pair)

        # run-scoped shrinker: when the governor detects host-memory
        # pressure it halves this run's ring depths and decode window —
        # the existing backpressure machinery does the rest.  Shrinks
        # persist for the engine's lifetime (self.decoded_ring_depth).
        shrink_key = f"ingest:{self.name}:{id(stop)}"

        def _shrink() -> None:
            rl = record_ring.shrink()
            bl = batch_ring.shrink()
            self.decoded_ring_depth = max(
                1, int(self.decoded_ring_depth) // 2)
            logger.warning(
                "host-memory pressure: ingest '%s' ring depths shrink to "
                "record=%d batch=%d decode-window=%d", self.name, rl, bl,
                self.decoded_ring_depth)

        _governor.register_shrinker(shrink_key, _shrink)

        def _sync_record_source() -> Iterator:
            """Leftover + remaining records for the fallback drain, in
            exact stream order: the assembler's in-flight window, then
            the record ring, then the (single-threaded, chaos-gated)
            remainder of the upstream iterator."""
            upstream_done = asm["done"] or rd["exhausted"]
            upstream_err = None
            for idx, rec, _fut in asm["pending"]:
                if rec is None:
                    upstream_err = _fut
                    upstream_done = True
                    break
                _dec_charge(rec, -1)
                yield idx, rec
            asm["pending"].clear()
            while upstream_err is None:
                item = record_ring.try_get()
                if item is _NO_ITEM:
                    break
                if item is _END:
                    upstream_done = True
                    break
                if isinstance(item, BaseException):
                    upstream_err = item
                    break
                yield item
            if upstream_err is None and rd["inhand"] is not None:
                # the record the reader held when teardown aborted its
                # ring put — after everything it already handed off
                yield rd["inhand"]
                rd["inhand"] = None
            while upstream_err is None and not upstream_done:
                try:
                    rec = next(it)
                except StopIteration:
                    break
                idx, rd["index"] = rd["index"], rd["index"] + 1
                try:
                    file_io.retrying(chaos.on_record_read, idx,
                                     op="ingest record read")
                except BaseException as e:
                    if _is_data_error(e):
                        quarantine.admit("read", idx,
                                         getattr(rec, "name", None), e)
                        continue
                    raise
                stats["read"].add(items=1)
                yield idx, rec
            if upstream_err is not None:
                raise upstream_err

        def _fallback_tail(err: BaseException) -> Iterator:
            """Finish the epoch on the synchronous path: same drawer RNG,
            same quarantine, no stage threads — the batch stream
            continues bit-identically to an uninterrupted run (modulo
            quarantined records).  Only safe once every stage thread is
            verifiably dead (a live reader still owns the upstream
            iterator); otherwise the original failure re-raises."""
            self.fallbacks += 1
            telemetry.counter(
                "Ingest/fallbacks", summary=True,
                help="mid-epoch switches to the synchronous ingest path"
            ).inc()
            logger.warning(
                "ingest engine '%s' declared dead (%s) — falling back to "
                "the synchronous path mid-epoch; per-stage stats: %s",
                self.name, err, self.stats())
            sup.stop()
            stop.set()
            for tname in ("reader", "assembler"):
                sup.thread(tname).join(timeout=5)
            if any(sup.thread(n).is_alive()
                   for n in ("reader", "assembler")):
                logger.error(
                    "ingest fallback impossible: a stage thread is still "
                    "alive and owns the upstream iterator")
                raise err
            # completed batches already in the ring are valid drawn work:
            # deliver them (committing their RNG positions) before
            # continuing from the first unassembled record
            while True:
                item = batch_ring.try_get()
                if item is _NO_ITEM or item is _END:
                    break
                if isinstance(item, BaseException):
                    raise item
                batch, rng_state = item
                if primary:
                    shared_rng.np.set_state(rng_state)
                stats["consume"].add(items=1)
                yield batch

            def emit_sync():
                # same pack as the pipelined emit(); consumption == the
                # yield itself, so the RNG position commits here
                batch, n, pack_s = pack_batch()
                stats["assemble"].add(items=n, busy_s=pack_s)
                for key in ("imgs", "recs", "offsets", "flips", "seeds"):
                    asm[key].clear()
                if primary:
                    shared_rng.np.set_state(drawer.np.get_state())
                stats["consume"].add(items=1)
                return batch

            if len(asm["imgs"]) >= self.batch_size:
                # a fully-drawn batch whose ring put was aborted by the
                # teardown: emit it before touching new records
                yield emit_sync()

            for idx, rec in _sync_record_source():
                try:
                    img = timed_decode(idx, rec)
                except BaseException as e:
                    if _is_data_error(e):
                        quarantine.admit("decode", idx, rec.name, e)
                        continue
                    raise
                if not admit_and_append(idx, rec, img):
                    continue
                if len(asm["imgs"]) == self.batch_size:
                    yield emit_sync()
            if asm["imgs"]:
                yield emit_sync()

        try:
            while True:
                # governor tick from the consumer side too: serving-only
                # processes have no optimizer loop to poll for them
                _governor.poll()
                # blocked time inside get() is charged to consume.starve_s
                # by the ring itself; the failure event doubles as the
                # stop so a supervisor escalation wakes this wait at once
                sup.consumer_waiting_since = time.monotonic()
                item = batch_ring.get(sup.failed)
                sup.consumer_waiting_since = None
                if item is _NO_ITEM:
                    # the supervisor declared the engine dead
                    err = sup.failure or IngestInfraError(
                        "ingest engine failed", diagnosis=self.stats())
                    telemetry.counter(
                        "Ingest/engine_failures", summary=True,
                        help="ingest engines declared dead by the "
                             "supervisor").inc()
                    if self.fallback_on_failure:
                        yield from _fallback_tail(err)
                        return
                    logger.error(
                        "ingest engine '%s' declared dead: %s; per-stage "
                        "stats: %s", self.name, err, self.stats())
                    raise err
                if item is _END:
                    return
                if isinstance(item, BaseException):
                    raise item
                batch, rng_state = item
                if primary:
                    # commit the drawn-through position: the caller's
                    # stream advances exactly as far as the batches it
                    # actually took
                    shared_rng.np.set_state(rng_state)
                stats["consume"].add(items=1)
                yield batch
        finally:
            _governor.unregister_shrinker(shrink_key)
            active_forks.discard(fork_token)
            for i, run in enumerate(self._active_stats):
                if run is stats:
                    del self._active_stats[i]
                    break
            self._last_stats = stats
            for i, pair in enumerate(self._active_faults):
                if pair is fault_pair:
                    del self._active_faults[i]
                    break
            self._last_faults = fault_pair
            self.run_history.append({
                "quarantine": quarantine.summary(),
                "stage_restarts": sup.restarts})
            sup.stop()          # no restarts while tearing down
            stop.set()
            # cancel queued decodes so teardown never waits on work whose
            # output nobody will read (mirrors the MT transformer fix)
            pool.shutdown(wait=False, cancel_futures=True)
            for ring in (record_ring, batch_ring):
                ring.drain()
            # a declared-dead engine's threads are dead or wedged beyond
            # recovery (that is WHY it was declared dead): don't spend
            # the full grace join on a thread that provably won't exit
            grace = 0.5 if sup.failure is not None else 5
            sup.thread("reader").join(timeout=grace)
            sup.thread("assembler").join(timeout=grace)
            # a final put can land between the first drain and the join —
            # drain again so no full batch stays pinned in the ring
            for ring in (record_ring, batch_ring):
                ring.drain()
            # settle this run's decode-window share: the account is
            # process-global (shared by concurrent engines), so only the
            # bytes THIS run still holds get released
            if dec_outstanding[0] > 0:
                dec_acct.sub(dec_outstanding[0])
            elif dec_outstanding[0] < 0:
                dec_acct.add(-dec_outstanding[0])
            dec_outstanding[0] = 0


def summary_scalars():
    """(tag, value) pairs for the training summary: per-stage throughput,
    stall fraction, and ring occupancy of every engine with an ACTIVE run
    (idle engines from finished pipelines are excluded — their stale final
    counters must not pollute a later run's series).  Tags always include
    the engine's ``name`` so the series stays stable when a second engine
    (a validation pipeline) goes live mid-run."""
    out = []
    for eng in sorted((e for e in _LIVE if e.has_active_run()),
                      key=lambda e: e.name):
        prefix = f"Ingest/{eng.name}"
        for stage, snap in eng.stats().items():
            out.append((f"{prefix}/{stage}/throughput",
                        snap["throughput_per_sec"]))
            out.append((f"{prefix}/{stage}/stall_frac", snap["stall_frac"]))
            if snap["mean_queue_depth"]:
                out.append((f"{prefix}/{stage}/queue_depth",
                            snap["mean_queue_depth"]))
        # per-stage worker gauges + autoscale action counters (ISSUE 16:
        # the driver decomposition log and charts must show what the
        # autoscaler actually did, not just its throughput effect)
        for stage, n in eng.stage_workers.items():
            out.append((f"{prefix}/{stage}/workers", n))
        for direction, n in eng.autoscale_events.items():
            if n:
                out.append((f"{prefix}/autoscale_{direction}", n))
        if eng.epoch_cache is not None:
            cache = eng.epoch_cache.stats()
            out.append((f"{prefix}/epoch_cache_hits", cache["hits"]))
            out.append((f"{prefix}/epoch_cache_misses", cache["misses"]))
        # self-healing series surface only once they are nonzero: a
        # clean run's charts stay exactly as before.  Summed over every
        # ACTIVE run — a multi-shard pipeline must not report just the
        # last-started shard's counters
        quarantined = eng.quarantined_count()
        if quarantined:
            out.append((f"{prefix}/quarantined", quarantined))
        restarts = eng.stage_restart_count()
        if restarts:
            out.append((f"{prefix}/stage_restarts", restarts))
    return out


def _stall_diagnostics() -> dict:
    """Per-engine stats + ring ages for the hung-step watchdog: when a
    driver stall traces back to a wedged data pipeline, the fire log
    names the stage instead of just the symptom."""
    return {eng.name: {"stats": eng.stats(), "faults": eng.fault_stats()}
            for eng in sorted(_LIVE, key=lambda e: e.name)
            if eng.has_active_run()}


# the engine's scalars flow through the telemetry registry's single flush
# path: the driver's one emission loop pulls this provider instead of
# special-casing the ingest module (tags unchanged — Ingest/<name>/...)
telemetry.REGISTRY.register_provider("ingest", summary_scalars)

# the hung-step watchdog reports these with every fire: "the step hung"
# arrives with "which ring is stale and which stage died" attached
from bigdl_tpu.utils import elastic as _elastic  # noqa: E402

_elastic.register_stall_diagnostic("ingest", _stall_diagnostics)
