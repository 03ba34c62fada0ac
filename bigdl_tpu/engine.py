"""Engine: process-wide topology and device management.

Reference equivalent: ``utils/Engine.scala:36`` — a singleton owning engine type
(MklBlas), node/core topology, and the two CPU thread pools (``Engine.default``
coarse task pool, ``Engine.model`` intra-layer pool).

On TPU none of the thread-pool machinery survives: XLA owns intra-op
parallelism, and the "N model replicas per node sharing one weight storage"
trick (reference ``optim/DistriOptimizer.scala:516-531``) collapses into a
single larger per-chip batch under ``jit``.  What remains is topology: which
devices exist, how they are arranged into a ``jax.sharding.Mesh``, and a
single place to configure precision and engine type.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional, Sequence

import jax
import numpy as np

from bigdl_tpu import analysis, telemetry
from bigdl_tpu.resources import GOVERNOR as _resource_governor


class DispatchPipeline:
    """Bounded queue of in-flight device results with async device→host
    copies — the dispatch-pipelining idiom shared by the training driver,
    evaluator, and predictor.

    Reading a loss/output back blocks the driver until the step that
    produced it has finished; keeping ``depth - 1`` results in flight
    and starting the host copy at dispatch keeps the device fed while
    the host reads.  ``depth`` defaults to ``bigdl.pipeline.depth``
    (1 = fully synchronous; the default of 8 is not measured on the
    current machine).

    ``drain(item, next_item_or_None)`` is called FIFO as results retire;
    ``next_item`` peeks the queue so callers can measure inter-dispatch
    intervals."""

    def __init__(self, drain, depth: Optional[int] = None):
        from bigdl_tpu.utils import config
        self.depth = max(1, depth if depth is not None
                         else config.get_int("bigdl.pipeline.depth", 8))
        self._drain = drain
        # bounded ring (the unbounded-queue-in-serving lint rule); the
        # pre-append drain in push() keeps len < depth == maxlen at
        # every append, so deque's eviction can never actually trigger
        # and silently drop an undrained item
        self._q = deque(maxlen=self.depth)

    def push(self, out_dev, *meta) -> None:
        if hasattr(out_dev, "copy_to_host_async"):
            # the intended pull's async half: on an accelerator the
            # armed host-sync guard's transfer guard counts it as an
            # implicit device→host copy (logged per step in warn mode,
            # refused in strict) unless it is allowed like host_pull's
            # device_get, which later collects this very copy
            with jax.transfer_guard_device_to_host("allow"):
                out_dev.copy_to_host_async()
        # drain BEFORE append: even if some future path breaks the
        # len <= depth-1 post-condition, append happens below capacity
        # and maxlen never evicts (an invariant guard, not a policy)
        while len(self._q) >= self.depth:
            self._pop()
        self._q.append((out_dev,) + meta)
        while len(self._q) >= self.depth:
            self._pop()

    def flush(self) -> None:
        while self._q:
            self._pop()

    def abandon(self) -> int:
        """Drop every in-flight item WITHOUT draining it — the serving
        shed path: a consumer that stopped caring must not pay a
        device→host pull per result it will discard.  Outstanding async
        copies complete (or are dropped) inside the runtime; ``drain``
        is never called for them.  Returns how many items were
        abandoned."""
        n = len(self._q)
        self._q.clear()
        return n

    def _pop(self) -> None:
        item = self._q.popleft()
        self._drain(item, self._q[0] if self._q else None)


class BatchPrefetcher:
    """Background thread running ``fetch()`` ahead of the training loop.

    The fetch path ends in a host→device transfer (``device_put`` /
    ``make_array_from_process_local_data``) — overlapping it with the
    jitted step removes it from the critical path.  Single
    producer: the loop thread only consumes, so dataset iterators are
    never touched concurrently (an epoch reset swaps the iterator
    reference the fetch closure reads — the training stream is infinite,
    so a batch prefetched across the boundary stays valid, exactly like
    the reference's pipelined RDD fetch).

    Epoch rollovers happen ON the producer (``on_batch`` hook, wired by
    the driver): the producer alone counts records and calls
    ``reset_epoch`` at the boundary, so the dataset's iterators and
    shuffled index arrays are only ever touched from one thread AND the
    batch sequence is deterministic — independent of how far ahead the
    producer happens to be, which matters for multi-host parity (every
    process must consume the identical sequence).

    Transfer-ahead stage: with ``transfer_ahead`` > 1 (default
    ``bigdl.ingest.batchesInFlight``, 2 — not measured on the current
    machine) the fetch producer and the
    ready-wait are SPLIT across two threads so up to N host→device
    uploads are in flight at once — the fetch thread issues batch k+1's
    ``device_put`` while the transfer thread is still blocking batch k
    device-resident.  When compute ≥ transfer, the consuming step then
    never waits on the link; with ``transfer_ahead`` <= 1 the producer
    fetches and blocks serially (one upload in flight — the pre-streaming
    behaviour).  Batch ORDER is unchanged either way (both hops are FIFO
    queues) and the fetch thread remains the single producer owning epoch
    rollovers and the RNG stream.

    ``depth`` defaults to ``bigdl.prefetch.depth`` (2); 0 disables (the
    call becomes a passthrough).  Exceptions in the producer re-raise at
    the consuming call site.
    """

    def __init__(self, fetch, depth: Optional[int] = None,
                 on_batch=None, transfer_ahead: Optional[int] = None,
                 guard=None):
        import queue

        from bigdl_tpu.utils import config
        #: optional host-sync guard (bigdl_tpu.analysis) armed around the
        #: user fetch callable — the guard's hooks are thread-local, so
        #: the trainer's hot-loop arming cannot see work that runs HERE
        #: on the producer thread; arming at the call site closes that
        self._guard = guard
        self.depth = (depth if depth is not None
                      else config.get_int("bigdl.prefetch.depth", 2))
        self.transfer_ahead = (
            transfer_ahead if transfer_ahead is not None
            else config.get_int("bigdl.ingest.batchesInFlight", 2))
        self._fetch = fetch
        self._on_batch = on_batch
        # transfer-stage counters: how long the pipeline spent blocking
        # uploads device-resident vs fetching — surfaced by the driver's
        # end-of-run metrics.  Written from the fetch AND
        # transfer producers AND the passthrough (depth 0) caller, so
        # they share a stats lock
        self._stats_lock = analysis.make_lock("engine.prefetch")
        self.fetch_ns = 0            # guarded-by: _stats_lock
        self.block_ns = 0            # guarded-by: _stats_lock
        self.batches = 0             # guarded-by: _stats_lock
        # transfer-ahead slot accounting: every batch sitting in the
        # prefetch rings (fetched but not yet consumed) charges its host
        # bytes to the governor — the read-ahead depth is exactly the
        # buffer the host-memory budget needs to see
        self._slot_acct = _resource_governor.account("prefetch_slots")
        # the producer owns epoch rollovers (reshuffles): it must continue
        # the CONSTRUCTING thread's RNG stream, so a user's set_seed on the
        # main thread keeps governing epoch 2+ shuffles whether or not
        # prefetch is enabled
        from bigdl_tpu.utils.random_generator import RandomGenerator
        self._rng = RandomGenerator.RNG()
        #: producer failure recovered by stop() after the consumer
        #: abandoned mid-stream (never raised at a call site) — the
        #: original error must survive the teardown, not vanish with
        #: the drained queues
        self.error: Optional[BaseException] = None   # guarded-by: _stats_lock
        if self.depth <= 0:
            return
        self._q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._transfer_thread = None
        if self.transfer_ahead > 1:
            # issued-but-not-yet-ready uploads queue here; capacity N-1
            # plus the one the transfer thread is blocking = N in flight
            self._issued_q: "queue.Queue" = queue.Queue(
                maxsize=self.transfer_ahead - 1)
            self._transfer_thread = threading.Thread(
                target=self._run_transfer, daemon=True,
                name="prefetch-transfer")
            self._transfer_thread.start()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="prefetch-fetch")
        self._thread.start()

    # batches at or above this size are blocked device-resident before
    # handoff; smaller ones stay async (see _block_ready).  The
    # threshold is not measured on the current machine.
    READY_BYTES = 4 << 20

    def _block_ready(self, batch):
        # LARGE batches are handed to the consumer DEVICE-RESIDENT, so
        # the pipeline (not the consumer's dispatch) absorbs the wait
        # for a bulk transfer, overlapped with the steps already in
        # flight.  SMALL batches do not block: a block per batch would
        # serialize the producer against a step it could overlap.
        leaves = jax.tree_util.tree_leaves(batch)
        total = sum(getattr(leaf, "nbytes", 0) for leaf in leaves)
        if total >= self.READY_BYTES:
            t0 = telemetry.clock_ns()
            for leaf in leaves:
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
            t1 = telemetry.clock_ns()
            with self._stats_lock:
                self.block_ns += t1 - t0
            telemetry.add_span("prefetch/transfer", t0, t1,
                               {"bytes": total})
        return batch

    def _fetch_once(self, block: bool = True):
        t0 = telemetry.clock_ns()
        if self._guard is not None:
            with self._guard.armed():
                batch = self._fetch()
        else:
            batch = self._fetch()
        if self._on_batch is not None:
            self._on_batch(batch)
        t1 = telemetry.clock_ns()
        with self._stats_lock:
            self.fetch_ns += t1 - t0
            self.batches += 1
        telemetry.add_span("prefetch/fetch", t0, t1)
        if block:
            self._block_ready(batch)
        return batch

    def _put(self, q, item) -> bool:
        import queue as _queue
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    @staticmethod
    def _slot_nbytes(batch) -> int:
        return int(sum(int(getattr(leaf, "nbytes", 0) or 0)
                       for leaf in jax.tree_util.tree_leaves(batch)))

    def _run(self):
        from bigdl_tpu.utils.random_generator import RandomGenerator
        RandomGenerator.adopt(self._rng)
        staged = self._transfer_thread is not None
        out_q = self._issued_q if staged else self._q
        while not self._stop.is_set():
            if _resource_governor.under_pressure():
                # host-memory pressure: pause read-ahead — batches
                # already queued keep flowing to the consumer while the
                # accounted prefetch bytes drain down
                self._stop.wait(0.05)
                continue
            try:
                # staged: hand the batch on with its upload still in
                # flight — the transfer thread blocks it ready while this
                # thread fetches (and uploads) the next one
                item = (None, self._fetch_once(block=not staged))
            except BaseException as e:  # noqa: BLE001 — re-raised at call
                item = (e, None)
            if item[0] is None:
                self._slot_acct.add(self._slot_nbytes(item[1]))
            if not self._put(out_q, item):
                self._discard(item)
                return
            if item[0] is not None:
                return

    def _run_transfer(self):
        import queue as _queue
        while not self._stop.is_set():
            try:
                item = self._issued_q.get(timeout=0.1)
            except _queue.Empty:
                continue
            err, batch = item
            if err is None:
                try:
                    self._block_ready(batch)
                except BaseException as e:  # noqa: BLE001 — re-raised
                    self._slot_acct.sub(self._slot_nbytes(batch))
                    item = (e, None)
            if not self._put(self._q, item):
                self._discard(item)
                return
            if item[0] is not None:
                return

    def _stash_error(self, item) -> None:
        """A producer stopped while holding an item it could not hand
        downstream: an ERROR item dropped here would vanish — the one
        window stop()'s post-join queue drain cannot see — so park it on
        ``self.error`` directly (threads are joined before the drain
        reads it).  First error wins — atomically, since both producer
        threads and a stopping consumer can race here."""
        with self._stats_lock:
            if item[0] is not None and self.error is None:
                self.error = item[0]

    def _discard(self, item) -> None:
        """An item dropped without ever reaching the consumer: release
        its accounted slot bytes, then preserve any error it carried."""
        if item[1] is not None:
            self._slot_acct.sub(self._slot_nbytes(item[1]))
        self._stash_error(item)

    def __call__(self):
        if self.depth <= 0:
            return self._fetch_once()
        err, batch = self._q.get()
        if err is not None:
            raise err
        self._slot_acct.sub(self._slot_nbytes(batch))
        return batch

    def stop(self):
        """Stop and JOIN the producers: a retry-from-failure restart must
        not race a still-running old producer over the same dataset
        iterators.  A consumer ABANDONING mid-stream (the serving shed
        path) calls this too — after the join, any producer error still
        parked in the rings is recovered onto ``self.error`` so the
        original failure surfaces instead of being torn down with the
        queues."""
        if self.depth <= 0:
            return
        self._stop.set()
        self._thread.join(timeout=10)
        if self._transfer_thread is not None:
            self._transfer_thread.join(timeout=10)
        import queue as _queue
        for q in (self._q, getattr(self, "_issued_q", None)):
            if q is None:
                continue
            while True:
                try:
                    err, batch = q.get(block=False)
                except _queue.Empty:
                    break
                if batch is not None:
                    self._slot_acct.sub(self._slot_nbytes(batch))
                self._stash_error((err, None))


class _EngineState:
    def __init__(self):
        self.engine_type: str = "tpu"
        self.node_number: int = 1
        self.core_number: int = 1
        self.inited: bool = False
        self.seed: int = 0
        self._mesh = None
        self._lock = analysis.make_rlock("engine.state")


_STATE = _EngineState()


class Engine:
    """Static topology singleton (mirrors reference ``utils/Engine``)."""

    MESH_AXES = ("data", "model", "seq")

    @staticmethod
    def init(node_number: Optional[int] = None,
             core_number: Optional[int] = None,
             engine_type: Optional[str] = None) -> None:
        """Initialise topology.

        ``node_number``/``core_number`` keep the reference's vocabulary
        (reference ``utils/Engine.scala:313``): here a "node" is a host
        participating in the jax distributed runtime and a "core" is a local
        accelerator device.  Defaults are discovered from JAX.
        """
        with _STATE._lock:
            _STATE.engine_type = engine_type or os.environ.get(
                "BIGDL_ENGINE_TYPE", jax.devices()[0].platform)
            _STATE.node_number = node_number or jax.process_count()
            _STATE.core_number = core_number or jax.local_device_count()
            _STATE.inited = True

    @staticmethod
    def init_distributed(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
        """Join the multi-host jax distributed runtime (the reference's
        multi-node tier: one Spark executor per node; here one host process
        per TPU host, SURVEY §7 hard-parts note).  After this,
        ``jax.devices()`` spans all hosts and ``Engine.create_mesh`` builds
        global meshes whose collectives ride ICI within a pod and DCN
        across pods.  No-op when already initialised."""
        # idempotence via jax's own distributed state: touching the backend
        # (e.g. jax.process_count()) before initialize() would pre-initialise
        # local-only XLA and break the multi-host bring-up
        if jax.distributed.is_initialized():
            return
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
        Engine.init()

    @staticmethod
    def node_number() -> int:
        Engine._ensure()
        return _STATE.node_number

    @staticmethod
    def core_number() -> int:
        Engine._ensure()
        return _STATE.core_number

    @staticmethod
    def engine_type() -> str:
        Engine._ensure()
        return _STATE.engine_type

    @staticmethod
    def device_count() -> int:
        return jax.device_count()

    @staticmethod
    def devices():
        return jax.devices()

    @staticmethod
    def set_seed(seed: int) -> None:
        _STATE.seed = seed

    @staticmethod
    def get_seed() -> int:
        return _STATE.seed

    # ---- mesh -----------------------------------------------------------

    @staticmethod
    def create_mesh(mesh_shape: Optional[Sequence[int]] = None,
                    axis_names: Optional[Sequence[str]] = None,
                    devices=None) -> "jax.sharding.Mesh":
        """Build a device mesh.

        Default: all devices on the ``data`` axis (pure data parallelism —
        the only parallelism the reference supports, SURVEY §2.12).  Pass
        ``mesh_shape``/``axis_names`` for dp x tp x sp meshes.
        """
        from jax.sharding import Mesh

        devs = np.asarray(devices if devices is not None else jax.devices())
        if mesh_shape is None:
            mesh_shape = (devs.size,)
            axis_names = axis_names or ("data",)
        axis_names = tuple(axis_names or Engine.MESH_AXES[:len(mesh_shape)])
        if int(np.prod(mesh_shape)) != devs.size:
            raise ValueError(
                f"mesh shape {tuple(mesh_shape)} does not cover {devs.size} devices")
        return Mesh(devs.reshape(mesh_shape), axis_names)

    @staticmethod
    def default_mesh() -> "jax.sharding.Mesh":
        with _STATE._lock:
            if _STATE._mesh is None:
                _STATE._mesh = Engine.create_mesh()
            return _STATE._mesh

    @staticmethod
    def set_default_mesh(mesh) -> None:
        with _STATE._lock:
            _STATE._mesh = mesh

    # ---- internal -------------------------------------------------------

    @staticmethod
    def _ensure() -> None:
        if not _STATE.inited:
            Engine.init()


def allgather_sum(rows) -> np.ndarray:
    """Sum a small per-process float array across every process (host
    collective; identity single-process).

    The multi-host reduction shared by the distributed metric kinds —
    validation partials (``optim.evaluator``) and aggregated counters
    (``optim.metrics``).  COLLECTIVE: under multi-host every process must
    call it with an array of the same shape."""
    rows = np.asarray(rows, np.float64)
    if jax.process_count() <= 1:
        return rows
    from jax.experimental import multihost_utils
    # process_allgather silently downcasts float64 wires to float32 when
    # jax_enable_x64 is off (the default), which loses integer exactness
    # above 2^24 — e.g. record counts or summed losses on very large
    # validation sets.  Ship each value as a float32 (hi, lo) pair —
    # hi = f32(x), lo = f32(x - hi) — and recombine in float64 after the
    # gather: exact for counts up to ~2^48.
    hi = rows.astype(np.float32)
    lo = (rows - hi.astype(np.float64)).astype(np.float32)
    gathered = np.asarray(
        multihost_utils.process_allgather(np.stack([hi, lo])), np.float64)
    return gathered.sum(axis=(0, 1))


def to_device(x):
    """Recursively move a nested list/tuple/dict of arrays onto the
    default backend's device (the single host→device crossing point of
    the data pipeline).  Leaves arrive uncommitted, so a step compiled
    for a mesh is free to place them."""
    import jax.numpy as jnp

    def rec(v):
        if isinstance(v, dict):
            return {k: rec(u) for k, u in v.items()}
        if isinstance(v, (list, tuple)):
            return type(v)(rec(u) for u in v)
        return jnp.asarray(v)

    return rec(x)


#: where XLA's persistent compilation cache lives when the environment
#: names no place: fixed, beside the package — the directory is part of
#: the cache key, so a path that moves (temp name, pid, time) never hits
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def init_compile_cache() -> str:
    """Place XLA's persistent compilation cache and return its directory
    — called by every driver before the first compile.  With
    ``JAX_COMPILATION_CACHE_DIR`` set nothing is set in code (JAX reads
    the variable itself); otherwise the cache goes to
    :data:`JAX_CACHE_DIR`.  Every ``lowered.compile()`` — so every
    ``CachedStep`` — goes through it.  Not the executable store of
    ``bigdl.compile.cacheDir``, which stays off by default."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
    return JAX_CACHE_DIR
