"""Resilient compilation: persistent executable cache, AOT warmup, buckets.

Compilation was the last unsupervised, unretried, uncached phase of a
training/serving run: every process start paid 15-45 s of unguarded XLA
compile, and a wedged remote compilation killed the whole run with no
diagnosis (ROADMAP item 4).  Every other expensive phase already restarts
from durable state — snapshots (PR 2), the optimizer loop (PR 6), ingest
(PR 7) — this module gives compilation the same contract, in three legs:

1. **Persistent cache** (:class:`CompileCache`).  Every fused-step
   lowering is keyed by (abstract-signature hash from the PR 4 recompile
   sentinel + the lowered StableHLO digest, topology from
   ``elastic.describe_topology``, jax/jaxlib/backend version) and the
   compiled executable is stored serialized under
   ``bigdl.compile.cacheDir`` with the PR 2 snapshot discipline: a
   per-entry JSON manifest carrying payload checksums, a ``.commit``
   marker written LAST, torn/uncommitted/corrupt/stale entries skipped
   with a structured log and a fresh compile — never a crash.  A second
   process over the same model+topology *loads* instead of compiles.
   Writers take a single-writer lock with a capped-backoff wait
   (``bigdl.compile.lockTimeoutSec``) so concurrent processes never
   corrupt each other — a process that cannot get the lock simply skips
   the write; ``bigdl.compile.keepLast`` GCs old entries commit-first.

2. **AOT warmup under a watchdog** (:class:`CachedStep`,
   :func:`compile_watchdog`).  ``tracked_jit`` wraps each fused step:
   execution always goes through an explicitly lowered-and-compiled
   executable, so the driver can warm every step up (telemetry-spanned,
   ``Compile/*`` metrics) before step 1 dispatches.  Each trace, cache
   load, and compile runs supervised by ``bigdl.compile.timeoutSec``: a
   wedged compile is aborted with a :class:`CompileTimeoutError`
   carrying the signature+topology diagnosis (cache loads additionally
   fall back to a fresh compile before failing), and the trainer's
   retry loop classifies it like divergence — restore and retry — while
   preemption still means leave.

3. **Shape bucketing** (:func:`configured_buckets`, :func:`pad_batch`).
   Variable batch inputs (validation remainder batches, ``Predictor``)
   round up to the configured ``bigdl.compile.buckets`` at the choke
   points (pad rows in, slice rows out), so post-warmup execution hits
   only pre-compiled signatures; ``CachedStep`` precompiles every
   bucket variant of a new signature family ahead of time and registers
   them with the PR 4 retrace sentinel, which in ``strict`` mode is the
   regression gate proving zero post-warmup retraces.

The abort caveat of the PR 6 watchdog applies: the injected exception
lands when the compiling thread next executes Python bytecode.  It
interrupts chaos-simulated hangs and host-side wedges; a thread parked
forever inside one native XLA call is only reachable by process-level
supervision, which the structured log and ``Compile/watchdog_fired``
counter exist to inform.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from bigdl_tpu import telemetry
from bigdl_tpu.utils import chaos as _chaos
from bigdl_tpu.visualization.crc32c import crc32c

logger = logging.getLogger("bigdl_tpu")

#: cache entry manifest schema; a manifest from a NEWER release is a
#: deliberate miss (recompile), never an unpickle crash
ENTRY_VERSION = 1

#: injectable for tests (lock backoff must not really sleep in tier-1)
_sleep = time.sleep


class CompileTimeoutError(RuntimeError):
    """A fused-step compile (or cache load) exceeded
    ``bigdl.compile.timeoutSec``.  Carries the signature+topology
    diagnosis so the log names *which* lowering wedged.  The trainer's
    retry loop treats it like divergence — restore the newest valid
    snapshot and retry — not like preemption (leave)."""

    def __init__(self, label: str = "", phase: str = "",
                 timeout: float = 0.0,
                 diagnosis: Optional[Dict[str, Any]] = None):
        # no-arg constructible: PyThreadState_SetAsyncExc instantiates
        # the bare class in the aborted thread; the catch site re-raises
        # with the full diagnosis attached
        self.label = label
        self.phase = phase
        self.timeout = timeout
        self.diagnosis = dict(diagnosis or {})
        if not label:
            super().__init__()
            return
        super().__init__(
            f"compile watchdog: {phase} of fused step {label!r} exceeded "
            f"bigdl.compile.timeoutSec={timeout:g}s — "
            f"diagnosis: "
            f"{json.dumps(self.diagnosis, sort_keys=True, default=str)}")


class _WatchState:
    __slots__ = ("fired", "detect_ms")

    def __init__(self):
        self.fired = False
        self.detect_ms = 0.0


def compile_timeout() -> float:
    from bigdl_tpu.utils import config
    return config.get_float("bigdl.compile.timeoutSec", 0.0)


class compile_watchdog:
    """Supervise one compile/load phase: if the body has not finished
    within ``timeout`` seconds, log the structured diagnosis, bump the
    ``Compile/watchdog_fired`` counter, and inject
    :class:`CompileTimeoutError` into the supervised thread (the PR 6
    ``_async_raise`` machinery).  ``timeout <= 0`` is a no-op.  The bare
    async-raised exception carries no message, so the caller re-raises a
    fully-diagnosed instance (see :meth:`CachedStep._compile_entry`)."""

    def __init__(self, label: str, phase: str,
                 timeout: Optional[float] = None,
                 diagnosis: Optional[Dict[str, Any]] = None):
        self.label = label
        self.phase = phase
        self.timeout = compile_timeout() if timeout is None else timeout
        self.diagnosis = dict(diagnosis or {})
        self.state = _WatchState()
        self._done = threading.Event()
        #: inject-vs-exit atomicity: __exit__ marks done and the monitor
        #: re-checks done IMMEDIATELY before injecting, both under this
        #: lock — a compile that completes right at the deadline (the
        #: fire diagnostics take real time) can never receive a stray
        #: async exception after leaving the supervised block, the same
        #: re-validate-under-the-lock discipline the PR 6 hung-step
        #: watchdog uses
        from bigdl_tpu import analysis
        self._lock = analysis.make_lock("compile_cache.watchdog")
        self._thread: Optional[threading.Thread] = None

    def __enter__(self) -> "_WatchState":
        if self.timeout <= 0:
            return self.state
        from bigdl_tpu.utils.elastic import _async_raise
        tid = threading.get_ident()
        t0 = time.monotonic()

        def monitor():
            if self._done.wait(self.timeout):
                return
            # past deadline: one fire per phase, then inject
            self.state.fired = True
            self.state.detect_ms = (time.monotonic() - t0 -
                                    self.timeout) * 1e3
            logger.error(
                "Compile watchdog: %s of fused step %r still running "
                "%.1fs past bigdl.compile.timeoutSec=%gs — aborting "
                "(diagnosis: %s)", self.phase, self.label,
                self.state.detect_ms / 1e3, self.timeout,
                json.dumps(self.diagnosis, sort_keys=True, default=str))
            telemetry.counter(
                "Compile/watchdog_fired",
                help="compile-watchdog aborts of wedged compiles").inc()
            telemetry.gauge("Compile/watchdog_detect_ms").set(
                self.state.detect_ms)
            telemetry.instant("compile/watchdog_fired", label=self.label,
                              phase=self.phase)
            with self._lock:
                if self._done.is_set():   # completed during diagnostics
                    return
                _async_raise(tid, CompileTimeoutError)

        self._thread = threading.Thread(target=monitor, daemon=True,
                                        name="bigdl-compile-watchdog")
        self._thread.start()
        return self.state

    def __exit__(self, *exc) -> None:
        with self._lock:
            self._done.set()
        t = self._thread
        if t is not None:
            t.join(timeout=1.0)
            self._thread = None


# ---- shape buckets --------------------------------------------------------


def configured_buckets() -> Optional[List[int]]:
    """The sorted ``bigdl.compile.buckets`` list, or None when bucketing
    is off.  Accepts a comma-separated string (``"8,16,32"``) or a
    sequence of ints."""
    from bigdl_tpu.utils import config
    v = config.get_property("bigdl.compile.buckets")
    if not v:
        return None
    if isinstance(v, (list, tuple)):
        sizes = [int(x) for x in v]
    else:
        sizes = [int(t) for t in str(v).split(",") if t.strip()]
    sizes = sorted(set(s for s in sizes if s > 0))
    return sizes or None


def bucket_size(n: int, buckets: Sequence[int]) -> int:
    """Smallest configured bucket >= ``n``; beyond the largest bucket,
    the next multiple of it (so the signature count stays bounded for
    any input size instead of growing one-per-ragged-length)."""
    for b in buckets:
        if n <= b:
            return b
    largest = buckets[-1]
    return ((n + largest - 1) // largest) * largest


def pad_batch(tree, n: int, padded_n: int):
    """Pad every leaf of a host batch from ``n`` to ``padded_n`` rows by
    repeating the last row (edge padding: always-valid values, so the
    padded rows cannot produce NaN/inf that a reduction might smear).
    Callers slice model outputs back to ``n`` rows host-side — for the
    batch-independent eval-mode graphs this feeds (conv/BN-eval/
    attention-per-row) the surviving rows equal an unpadded forward to
    rounding; the padded and unpadded batch shapes are separately
    compiled programs, which XLA does not promise to round alike."""
    import jax
    import numpy as np
    if padded_n == n:
        return tree

    def _pad(x):
        x = np.asarray(x)
        reps = np.repeat(x[-1:], padded_n - n, axis=0)
        return np.concatenate([x, reps], axis=0)

    return jax.tree_util.tree_map(_pad, tree)


def slice_rows(tree, n: int):
    """Undo :func:`pad_batch` on a pulled host output: first ``n`` rows
    of every leaf (no-op for leaves that already match)."""
    import jax
    import numpy as np

    def _cut(x):
        x = np.asarray(x)
        return x[:n] if x.ndim >= 1 and x.shape[0] > n else x

    return jax.tree_util.tree_map(_cut, tree)


# ---- persistent executable store ------------------------------------------


def backend_fingerprint() -> Dict[str, str]:
    """Versions an executable is only valid under: jax + jaxlib + the
    XLA backend platform and its version.  Any difference is a cache
    miss (recompile), never a deserialization crash."""
    import jax
    import jaxlib
    from jax.extend import backend as _xb
    b = _xb.get_backend()
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "platform": b.platform,
            "platform_version": str(b.platform_version)}


class CompileCache:
    """One persistent executable store directory (local filesystem).

    Layout per entry (``<key>`` is the hex digest of the full cache key):

    - ``<key>.bin``    — pickled ``(serialized_executable, in_tree,
      out_tree, device_ids)`` payload (``device_ids``: the executable's
      device assignment, in order — a load binds to exactly those),
    - ``<key>.json``   — manifest: entry version, label, payload checksum
      + byte count, abstract-signature hash, topology, backend
      fingerprint, creation time,
    - ``<key>.commit`` — marker written LAST; its content cross-checks
      the manifest (the atomic "entry is whole" bit).

    Reads verify commit↔manifest and payload checksum+size; any tear,
    truncation, bit-flip, schema skew, or version/topology mismatch is a
    MISS with a structured log.  Writes take the single-writer ``lock``
    file with a capped-backoff wait; a writer that cannot acquire it
    skips the write (the executable it just compiled still serves this
    process from memory)."""

    LOCK_NAME = "lock"

    def __init__(self, path: str, keep_last: Optional[int] = None):
        from bigdl_tpu.utils import config
        self.path = path
        self.keep_last = (keep_last if keep_last is not None else
                          config.get_int("bigdl.compile.keepLast", 0))
        self.lock_timeout = config.get_float(
            "bigdl.compile.lockTimeoutSec", 30.0)
        self.lock_stale = config.get_float(
            "bigdl.compile.lockStaleSec", 600.0)
        self.hits = 0
        self.misses = 0
        self.errors = 0
        self.writes = 0
        #: disk-full degradation latch: once storage is exhausted, every
        #: further store is skipped up front (the PR 8 lock-loser path —
        #: this process keeps serving executables from memory)
        self.degraded = False

    @classmethod
    def from_config(cls) -> Optional["CompileCache"]:
        from bigdl_tpu.utils import config
        path = config.get_property("bigdl.compile.cacheDir")
        if not path:
            return None
        return cls(str(path))

    # -- keying ----------------------------------------------------------

    @staticmethod
    def entry_key(label: str, signature_hash: str, hlo_digest: str,
                  topology: Optional[Dict[str, Any]],
                  fingerprint: Dict[str, str]) -> str:
        """Hex cache key.  The StableHLO digest makes the key exact (two
        models sharing parameter shapes cannot collide); signature hash,
        topology, and backend fingerprint ALSO enter the key so the same
        information that drives the miss diagnosis drives the lookup."""
        h = hashlib.sha256()
        for part in (label, signature_hash, hlo_digest,
                     json.dumps(topology or {}, sort_keys=True),
                     json.dumps(fingerprint, sort_keys=True)):
            h.update(part.encode("utf-8"))
            h.update(b"\0")
        return h.hexdigest()[:32]

    def _names(self, key: str) -> Tuple[str, str, str]:
        return (os.path.join(self.path, f"{key}.bin"),
                os.path.join(self.path, f"{key}.json"),
                os.path.join(self.path, f"{key}.commit"))

    # -- read ------------------------------------------------------------

    def load(self, key: str, expect_topology: Optional[Dict[str, Any]],
             fingerprint: Dict[str, str]) -> Optional[bytes]:
        """The verified payload bytes for ``key``, or None (a miss).
        Every rejection logs WHY — torn, corrupt, stale version, foreign
        topology — and returns None so the caller recompiles; reading
        never raises."""
        bin_p, man_p, com_p = self._names(key)
        try:
            if not os.path.exists(com_p):
                if os.path.exists(man_p) or os.path.exists(bin_p):
                    # a torn write IS a counted cache error: the metric
                    # is how an operator sees torn-write storms on a
                    # flaky store (a clean never-written key is not)
                    self._count_error()
                    logger.info(
                        "compile cache: entry %s is uncommitted (torn "
                        "write or in-flight writer) — recompiling", key)
                return None
            with open(man_p, "rb") as f:
                mbytes = f.read()
            with open(com_p, "rb") as f:
                commit = f.read().strip()
            if commit != f"{crc32c(mbytes):08x}".encode("ascii"):
                self._count_error()
                logger.warning(
                    "compile cache: entry %s commit marker does not "
                    "match its manifest — recompiling", key)
                return None
            manifest = json.loads(mbytes.decode("utf-8"))
            version = manifest.get("version", 0)
            if not isinstance(version, int) or version > ENTRY_VERSION:
                logger.warning(
                    "compile cache: entry %s has schema version %r newer "
                    "than this release (<= %d) — recompiling", key,
                    version, ENTRY_VERSION)
                return None
            if manifest.get("fingerprint") != fingerprint:
                logger.info(
                    "compile cache: entry %s was compiled under %s, this "
                    "process runs %s — version skew is a miss, "
                    "recompiling", key, manifest.get("fingerprint"),
                    fingerprint)
                return None
            if (expect_topology is not None and
                    manifest.get("topology") not in (None, expect_topology)):
                logger.info(
                    "compile cache: entry %s topology %s does not match "
                    "the resuming trainer %s — recompiling", key,
                    manifest.get("topology"), expect_topology)
                return None
            from bigdl_tpu.utils.checkpoint_manager import checksum_by_algo
            with open(bin_p, "rb") as f:
                data = f.read()
            algo = manifest.get("algo", "crc32c")
            if (len(data) != manifest.get("bytes") or
                    checksum_by_algo(algo, data) != manifest.get("checksum")):
                self._count_error()
                logger.warning(
                    "compile cache: entry %s payload fails its manifest "
                    "checksum (%d bytes) — corrupt entry skipped, "
                    "recompiling", key, len(data))
                return None
            return data
        except Exception as e:
            self._count_error()
            logger.warning(
                "compile cache: entry %s unreadable (%s: %s) — "
                "recompiling", key, type(e).__name__, e)
            return None

    def present(self, key: str) -> bool:
        """Whether any file of the entry is on disk (a miss that found
        something to read, as against a key never written)."""
        return any(os.path.exists(p) for p in self._names(key))

    def _count_error(self) -> None:
        self.errors += 1
        telemetry.counter(
            "Compile/cache_errors",
            help="corrupt/torn cache entries skipped").inc()

    # -- write -----------------------------------------------------------

    def store(self, key: str, payload: bytes, label: str,
              signature_hash: str, topology: Optional[Dict[str, Any]],
              fingerprint: Dict[str, str],
              audit: Optional[Dict[str, Any]] = None) -> bool:
        """Write one entry as a verified unit (payload → manifest →
        commit marker last) under the single-writer lock.  Returns False
        — with the executable still serving from memory — when the lock
        cannot be acquired within the backoff window or the write fails;
        a cache store must never fail a training run."""
        if self.degraded:
            return False    # disk already known full: memory-only mode
        try:
            os.makedirs(self.path, exist_ok=True)
            if not self._acquire_lock():
                logger.warning(
                    "compile cache: could not acquire the writer lock "
                    "within %.1fs — skipping the store of entry %s "
                    "(another process is writing; this process keeps "
                    "its in-memory executable)", self.lock_timeout, key)
                return False
            try:
                # C-speed payload checksum with the algo recorded (the
                # PR 2 helper: native crc32c or zlib.crc32 — the pure-
                # Python crc32c table walk would cost seconds per entry
                # against multi-MB serialized executables, on the warm
                # path this cache exists to make fast); the tiny
                # manifest↔commit cross-check below stays crc32c
                from bigdl_tpu.utils.checkpoint_manager import \
                    payload_checksum
                bin_p, man_p, com_p = self._names(key)
                algo, checksum = payload_checksum(payload)
                # chaos bit-flip AFTER the checksum: the manifest records
                # the clean payload, so only load-time verification can
                # catch the rot (the fault the injector exists to prove)
                payload = _chaos.on_compile_cache_write(key, bytes(payload))
                manifest = {
                    "version": ENTRY_VERSION,
                    "label": label,
                    "signature": signature_hash,
                    "topology": topology,
                    "fingerprint": fingerprint,
                    "algo": algo,
                    "checksum": checksum,
                    "bytes": len(payload),
                    "created": time.time(),
                }
                if audit is not None:
                    # the HLO auditor's census digest — what the offline
                    # auditor reads back; an additive key, so version 1
                    # readers without it stay loadable
                    manifest["audit"] = audit
                self._atomic_write(bin_p, payload)
                mbytes = json.dumps(manifest, sort_keys=True).encode("utf-8")
                self._atomic_write(man_p, mbytes)
                # the commit marker lands LAST: its presence is the
                # atomic "entry is whole" bit, its content cross-checks
                # the manifest
                self._atomic_write(
                    com_p, (f"{crc32c(mbytes):08x}\n").encode("ascii"))
                self.writes += 1
                self.gc()
                return True
            finally:
                self._release_lock()
        except Exception as e:
            from bigdl_tpu.resources.errors import is_storage_exhausted
            if is_storage_exhausted(e):
                # the disk is full, not flaky: latch memory-only mode so
                # every later signature skips the (pointless, multi-MB)
                # store attempt — one structured warning for the run
                self.degraded = True
                from bigdl_tpu.resources import storage as _rstorage
                _rstorage.note_degraded("compile_cache", e)
                return False
            logger.warning(
                "compile cache: store of entry %s failed (%s: %s) — "
                "continuing with the in-memory executable", key,
                type(e).__name__, e)
            return False

    @staticmethod
    def _atomic_write(path: str, data: bytes) -> None:
        # THE payload-write choke point (utils.file_io): atomic temp +
        # rename with the temp cleaned up on a failed write — a disk-full
        # mid-store must not strand multi-MB .tmp_bigdl debris per attempt
        from bigdl_tpu.utils import file_io
        file_io.write_bytes(path, data, overwrite=True)

    # -- single-writer lock ----------------------------------------------

    def _lock_path(self) -> str:
        return os.path.join(self.path, self.LOCK_NAME)

    def _acquire_lock(self) -> bool:
        """O_CREAT|O_EXCL lock file carrying pid+time, waited on with
        capped exponential backoff up to ``lockTimeoutSec``.  A lock
        older than ``lockStaleSec`` (a hard-killed writer) is stolen
        with a log line."""
        deadline = time.monotonic() + max(0.0, self.lock_timeout)
        delay = 0.05
        while True:
            try:
                fd = os.open(self._lock_path(),
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                with os.fdopen(fd, "w") as f:
                    f.write(f"{os.getpid()} {time.time()}\n")
                return True
            except FileExistsError:
                try:
                    age = time.time() - os.path.getmtime(self._lock_path())
                except OSError:
                    age = 0.0
                if age > self.lock_stale:
                    # steal by ATOMIC rename: of N waiters that all saw
                    # the lock go stale, exactly one wins the rename —
                    # the losers' renames fail and they go back to the
                    # fair O_CREAT|O_EXCL race, so a freshly re-created
                    # lock can never be unlinked by a second stealer
                    grave = f"{self._lock_path()}.stale.{os.getpid()}"
                    try:
                        os.rename(self._lock_path(), grave)
                    except OSError:
                        # lost the steal race — or rename persistently
                        # fails (read-only store): fall through to the
                        # deadline+backoff below, NEVER a bare continue
                        # (that would busy-spin unbounded with no
                        # watchdog covering the store path)
                        pass
                    else:
                        logger.warning(
                            "compile cache: stole a stale writer lock "
                            "(%.0fs old — a hard-killed writer left "
                            "it)", age)
                        try:
                            os.unlink(grave)
                        except OSError:  # pragma: no cover - gone
                            pass
                        continue
                if time.monotonic() >= deadline:
                    return False
                _sleep(min(delay, 1.0))
                delay *= 2

    def _release_lock(self) -> None:
        try:
            os.unlink(self._lock_path())
        except OSError:  # pragma: no cover - already gone
            pass

    # -- retention -------------------------------------------------------

    def entries(self) -> List[Tuple[float, str]]:
        """(created, key) for every COMMITTED entry, newest first (the
        manifest's recorded creation time orders retention; an entry
        whose manifest is unreadable sorts oldest — first to go)."""
        out: List[Tuple[float, str]] = []
        try:
            names = os.listdir(self.path)
        except OSError:
            return out
        for f in names:
            if not f.endswith(".commit"):
                continue
            key = f[:-len(".commit")]
            created = 0.0
            try:
                with open(os.path.join(self.path, f"{key}.json")) as mf:
                    created = float(json.load(mf).get("created", 0.0))
            except Exception:
                pass
            out.append((created, key))
        out.sort(reverse=True)
        return out

    def gc(self) -> None:
        """Keep the ``keep_last`` newest committed entries; drop the rest
        commit-marker FIRST (an interrupted GC leaves an uncommitted —
        ignored — entry, never a committed half-entry), manifest last."""
        if not self.keep_last or self.keep_last <= 0:
            return
        for _, key in self.entries()[self.keep_last:]:
            bin_p, man_p, com_p = self._names(key)
            for p in (com_p, bin_p, man_p):
                try:
                    os.unlink(p)
                except OSError:
                    pass


# ---- the tracked step wrapper ---------------------------------------------


def _signature_hash(args: Tuple) -> str:
    """Stable hex hash of the PR 4 abstract signature (pytree structure
    + per-leaf shape/dtype/weak-type) — the part of the cache key shared
    with the retrace sentinel's diagnosis."""
    from bigdl_tpu.analysis.retrace import abstract_signature
    treedef, sigs = abstract_signature(args)
    h = hashlib.sha256()
    h.update(repr(treedef).encode("utf-8"))
    h.update(repr(sigs).encode("utf-8"))
    return h.hexdigest()[:16]


#: the phases of one executable's way in, in order.  Each is timed by two
#: clock reads, observed into ``Compile/phase_ms{phase=...}`` (count: the
#: executables that went through it, sum: their milliseconds; always, for
#: the profiler is never on during set-up) and, while spans record, added
#: as ``compile/<phase>`` under the ``compile/<label>`` span.  ``load`` is
#: an executable taken from a persistent cache instead of compiled: the
#: store's deserialize-and-load, or a ``lowered.compile()`` that XLA's
#: persistent compilation cache answered
PHASES = ("jaxpr", "lower", "key", "read", "load", "compile", "audit",
          "store")

_PHASE_HELP = ("milliseconds of one phase of a fused step's executable "
               "(jaxpr, lower, key, read, load, compile, audit, store)")

#: what JAX reports, on the compiling thread, when its persistent
#: compilation cache answers a ``compile()`` (only then)
_XLA_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_xla = threading.local()


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    if event == _XLA_RETRIEVAL:
        _xla.retrieved_s = duration


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def _xla_cache_compile(lowered):
    """``lowered.compile()``, and the seconds XLA's persistent cache took
    to retrieve the executable, or ``None`` where the compiler ran."""
    _xla.retrieved_s = None
    exe = lowered.compile()
    return exe, _xla.retrieved_s


def _phase(name: str, t0_ns: int, t1_ns: int) -> float:
    return telemetry.observe_interval("Compile/phase_ms", {"phase": name},
                                      f"compile/{name}", t0_ns, t1_ns,
                                      help=_PHASE_HELP)


def _spec_of(x):
    """ShapeDtypeStruct mirror of one argument leaf (keeps an explicit
    sharding so AOT bucket variants lower with the placement the
    concrete batches will arrive in)."""
    import jax
    sharding = getattr(x, "sharding", None)
    if sharding is not None:
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


class CachedStep:
    """One fused step: jitted inside this module (the ONE registered
    ``jax.jit`` wrapper the ``untracked-jit`` lint rule allows),
    executed exclusively through explicitly compiled executables.

    Per distinct abstract signature the flow is: trace to a jaxpr, lower
    → key → try the persistent cache (verified read, then deserialize
    and load) → else compile → audit → store the serialized executable
    back (single-writer lock); each phase timed (:data:`PHASES`) and the
    slow ones supervised by the compile watchdog.
    After warmup every call is a dictionary lookup plus the executable
    dispatch; nothing ever re-enters jit's implicit trace-and-compile.

    ``bucket_argnums`` arms AOT bucket precompilation: on the first miss
    of a new signature family, every ``bigdl.compile.buckets`` variant
    (leaf dim 0 of the named args re-bucketed) is compiled ahead and
    registered with the attached retrace sentinel, so a bucketed
    validation/predict run hits only pre-compiled signatures.
    """

    def __init__(self, jitted, label: str,
                 topology: Optional[Dict[str, Any]] = None,
                 cache: Optional[CompileCache] = None,
                 bucket_argnums: Sequence[int] = (),
                 contract=None):
        self._jitted = jitted
        self.label = label
        self.topology = topology
        #: the StepContract the HLO auditor checks every lowered
        #: program of this step against (None = lookup by label)
        self.contract = contract
        self._cache = cache if cache is not None else CompileCache.from_config()
        self.bucket_argnums = tuple(bucket_argnums)
        self.sentinel = None          # retrace sentinel fed by precompiles
        #: called with every executable this step compiles or loads, as
        #: it is registered (the LM server reads how many bytes of the
        #: KV pools each one aliases)
        self.on_executable = None
        self._mem: Dict[Any, Any] = {}   # signature key -> loaded executable
        #: signature keys whose executable came from the persistent
        #: cache and has not dispatched yet (see :meth:`_first_dispatch`)
        self._unproven: set = set()
        #: signature families seen (bucket-arg batch dims erased): the
        #: in-plan test for batch sizes beyond the largest bucket, which
        #: round to multiples the precompiler cannot enumerate ahead
        self._families: set = set()
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        #: per-signature provenance: {signature, source: compile|cache,
        #: compile_ms and trace_ms (= jaxpr_ms + lower_ms) | load_ms,
        #: jaxpr_ms, lower_ms, key_ms, read_ms, store_ms}; a compile
        #: entry also says whether XLA's persistent cache answered
        #: (``xla_cache``)
        self.timings: List[Dict[str, Any]] = []

    # the MFU probe lowers the step for cost_analysis only — pass through
    def lower(self, *args):
        return self._jitted.lower(*args)

    def register_sentinel(self, sentinel) -> None:
        """Attach the retrace sentinel whose seen-set AOT bucket
        precompiles should pre-populate (see ``RetraceSentinel.
        register_warmup``)."""
        self.sentinel = sentinel

    # -- execution --------------------------------------------------------

    def _sig_key(self, args: Tuple):
        from bigdl_tpu.analysis.retrace import abstract_signature
        return abstract_signature(args)

    def __call__(self, *args):
        return self.call_with_signature(args, self._sig_key(args))

    def call_with_signature(self, args: Tuple, key):
        """Dispatch with a signature the caller already computed — the
        retrace sentinel observes every call with the identical
        ``abstract_signature`` walk, so its wrapper hands the key down
        instead of this step walking the argument tree a second time
        per iteration."""
        exe = self._mem.get(key)
        if exe is None:
            exe = self._compile_entry(args, key)
        # (empty in steady state: the signature is not hashed again)
        if self._unproven and key in self._unproven:
            return self._first_dispatch(exe, args, key)
        return self._dispatch(exe, args)

    def _first_dispatch(self, exe, args: Tuple, key):
        """First execution of a cache-LOADED executable.  Deserializing
        proves the bytes, not that the runtime will run them: a loaded
        executable its first dispatch rejects (argument, sharding or
        device mismatch — raised before anything executes, so the
        donated inputs are still alive) degrades to a fresh compile
        exactly as a failed deserialize does.  A classified device OOM
        is the program's fault, not the entry's, and passes through."""
        import jax
        self._unproven.discard(key)
        try:
            return self._dispatch(exe, args)
        except (jax.errors.JaxRuntimeError, ValueError, TypeError) as e:
            if any(getattr(leaf, "is_deleted", lambda: False)()
                   for leaf in jax.tree_util.tree_leaves(args)):
                raise       # inputs already consumed: nothing to re-run on
            logger.warning(
                "compile cache: the cache-loaded executable of fused step "
                "%r failed its first dispatch (%s: %s) — falling back to "
                "a fresh compile", self.label, type(e).__name__, e)
            self._cache._count_error()
        del self._mem[key]
        exe = self._compile_entry(args, key, precompile=False,
                                  use_cache=False)
        return self._dispatch(exe, args)

    def _dispatch(self, exe, args: Tuple):
        """Execute through the RESOURCE_EXHAUSTED classifier: a real XLA
        allocation failure — or the ``oomStepAt`` injector's replica,
        raised BEFORE execution so device state is untouched — surfaces
        as the structured :class:`DeviceMemoryError` the driver's
        microbatch re-plan keys on."""
        try:
            _chaos.take_oom_dispatch(self.label)
            return exe(*args)
        except Exception as e:
            from bigdl_tpu.resources.device import classify_dispatch_error
            err = classify_dispatch_error(e, self.label)
            if err is not None:
                raise err from e
            raise

    def warmup(self, *args) -> None:
        """AOT: make sure the executable for this signature exists
        (compile or cache-load) WITHOUT executing it — the driver's
        explicit warmup phase before step 1."""
        key = self._sig_key(args)
        if key not in self._mem:
            self._compile_entry(args, key)

    @property
    def warm(self) -> bool:
        return bool(self._mem)

    # -- the miss path ----------------------------------------------------

    def _compile_entry(self, args: Tuple, key, precompile: bool = True,
                       use_cache: bool = True):
        """Lower, then load-or-compile, the executable for one
        signature.  ``use_cache=False`` skips the persistent-cache LOAD
        (the fresh compile still overwrites the entry).  Anything the
        compiler itself rejects is tagged ``compile_phase`` — replaying
        it from a restored snapshot would rebuild the identical program,
        so the trainer's retry loop re-raises it at once; a watchdog
        timeout stays healable (:class:`CompileTimeoutError`)."""
        try:
            return self._compile_entry_tagged(args, key, precompile,
                                              use_cache)
        except CompileTimeoutError:
            raise
        except Exception as e:
            e.compile_phase = self.label
            raise

    def _compile_entry_tagged(self, args: Tuple, key, precompile: bool,
                              use_cache: bool):
        from jax.experimental import serialize_executable as _se

        sig_hash = _signature_hash(args)
        diagnosis = {"label": self.label, "signature": sig_hash,
                     "topology": self.topology}
        timeout = compile_timeout()

        # milliseconds of each phase this executable went through
        ms = dict.fromkeys(("jaxpr", "lower", "key", "read", "store"), 0.0)
        with telemetry.span(f"compile/{self.label}", signature=sig_hash):
            t0 = telemetry.clock_ns()
            try:
                with compile_watchdog(self.label, "trace", timeout,
                                      diagnosis):
                    traced = self._jitted.trace(*args)
                    t1 = telemetry.clock_ns()
                    lowered = traced.lower()
            except CompileTimeoutError as e:
                raise self._diagnosed(e, "trace", timeout, diagnosis)
            t2 = telemetry.clock_ns()
            ms["jaxpr"] = _phase("jaxpr", t0, t1)
            ms["lower"] = _phase("lower", t1, t2)

            from bigdl_tpu.analysis import hlo_audit
            audit_armed = hlo_audit.armed()
            fingerprint = backend_fingerprint()
            exe = None
            cache_key = None
            hlo = None
            if self._cache is not None or audit_armed:
                # the StableHLO text digest keys the entry exactly (and
                # the armed auditor scans the same text); the executable
                # is only worth serializing (tens of MB for big steps)
                # when a persistent cache will actually consume it
                t0 = telemetry.clock_ns()
                try:
                    with compile_watchdog(self.label, "trace", timeout,
                                          diagnosis):
                        hlo = lowered.as_text()
                except CompileTimeoutError as e:
                    raise self._diagnosed(e, "trace", timeout, diagnosis)
                if self._cache is not None:
                    hlo_digest = hashlib.sha256(
                        hlo.encode("utf-8")).hexdigest()
                    cache_key = CompileCache.entry_key(
                        self.label, sig_hash, hlo_digest, self.topology,
                        fingerprint)
                ms["key"] = _phase("key", t0, telemetry.clock_ns())
            if cache_key is not None and use_cache:
                exe = self._try_cache_load(cache_key, fingerprint, timeout,
                                           diagnosis, _se, ms)
            loaded = exe is not None
            if exe is None:
                if self._cache is not None:
                    self._count_miss()
                t1 = telemetry.clock_ns()
                try:
                    with compile_watchdog(self.label, "compile", timeout,
                                          diagnosis):
                        _chaos.on_compile(self.label)
                        exe, retrieved_s = _xla_cache_compile(lowered)
                except CompileTimeoutError as e:
                    raise self._diagnosed(e, "compile", timeout, diagnosis)
                xla_hit = retrieved_s is not None
                compile_ms = _phase("load" if xla_hit else "compile", t1,
                                    telemetry.clock_ns())
                if xla_hit:
                    telemetry.histogram(
                        "Compile/xla_cache_read_ms",
                        help="XLA's persistent compilation cache's own "
                             "time to retrieve an executable, within a "
                             "compile() it answered").observe(
                                 retrieved_s * 1e3)
                self.compiles += 1
                logger.info(
                    "Compiled fused step %r (signature %s): trace "
                    "%.0f ms, compile %.0f ms%s%s", self.label, sig_hash,
                    ms["jaxpr"] + ms["lower"], compile_ms,
                    " (XLA's persistent cache)" if xla_hit else "",
                    "" if self._cache is None else " — caching")
            audit_summary = None
            if audit_armed and hlo is not None:
                # audit BEFORE the store: a contract-violating program
                # must never enter the persistent cache, and the census
                # rides in the entry manifest for the offline auditor
                t0 = telemetry.clock_ns()
                report = hlo_audit.audit_step(
                    self.label, hlo, compiled=exe, contract=self.contract,
                    topology=self.topology)
                audit_summary = report.census.summary()
                _phase("audit", t0, telemetry.clock_ns())
                report.raise_or_warn()
            # HBM preflight BEFORE the first dispatch (and before the
            # store): with bigdl.resources.deviceMemBudgetMB set, a step
            # whose peak-buffer estimate cannot fit raises the structured
            # DeviceMemoryError while training state is still untouched —
            # the driver answers with a microbatch re-plan
            from bigdl_tpu.resources.device import preflight as _preflight
            _preflight(exe, self.label)
            if not loaded and cache_key is not None:
                t0 = telemetry.clock_ns()
                self._store(cache_key, exe, sig_hash, fingerprint, _se,
                            audit=audit_summary)
                ms["store"] = _phase("store", t0, telemetry.clock_ns())
            entry = {f"{p}_ms": round(v, 3) for p, v in ms.items()}
            if loaded:
                entry.update(signature=cache_key, source="cache")
            else:
                entry.update(signature=sig_hash, source="compile",
                             trace_ms=round(entry["jaxpr_ms"]
                                            + entry["lower_ms"], 3),
                             compile_ms=round(compile_ms, 3),
                             xla_cache=xla_hit)
            self.timings.append(entry)
        self._mem[key] = exe
        if self.on_executable is not None:
            self.on_executable(exe)
        if loaded:
            self._unproven.add(key)
        if self.bucket_argnums:
            self._families.add(self._family_key(args))
        if precompile and self.bucket_argnums:
            self._precompile_buckets(args)
        return exe

    @staticmethod
    def _diagnosed(e: CompileTimeoutError, phase: str, timeout: float,
                   diagnosis: Dict[str, Any]) -> CompileTimeoutError:
        """The async-raised instance is bare (no-arg constructed by
        PyThreadState_SetAsyncExc) — return a fully-diagnosed one to
        re-raise in its place; an already-diagnosed instance passes
        through."""
        if e.args:
            return e
        return CompileTimeoutError(diagnosis.get("label", "?"), phase,
                                   timeout, diagnosis)

    def _count_hit(self) -> None:
        self.cache_hits += 1
        if self._cache is not None:
            self._cache.hits += 1
        telemetry.counter("Compile/cache_hits",
                          help="fused-step executables loaded, not "
                               "compiled").inc()

    def _count_miss(self) -> None:
        self.cache_misses += 1
        if self._cache is not None:
            self._cache.misses += 1
        telemetry.counter("Compile/cache_misses",
                          help="fused-step signatures compiled fresh").inc()

    def _try_cache_load(self, cache_key: str, fingerprint: Dict[str, str],
                        timeout: float, diagnosis: Dict[str, Any], _se,
                        ms: Dict[str, float]):
        """Verified read, then deserialize and load, watchdog-supervised;
        the two phases' milliseconds go into ``ms`` (``read`` only where
        a file was there to read).  EVERY failure mode here — corrupt
        payload, unpicklable blob, a wedged deserialization aborted by
        the watchdog — degrades to a fresh compile; a cache can slow a
        start, never kill one."""
        t0 = telemetry.clock_ns()
        data = self._cache.load(cache_key, self.topology, fingerprint)
        t1 = telemetry.clock_ns()
        if data is not None or self._cache.present(cache_key):
            ms["read"] = _phase("read", t0, t1)
        if data is None:
            return None
        try:
            with compile_watchdog(self.label, "cache_load", timeout,
                                  diagnosis):
                payload, in_tree, out_tree, device_ids = pickle.loads(data)
                # bind to the devices the step was compiled for: the
                # default is every device of the backend, which turns a
                # one-device step into one expecting a shard per device
                import jax
                by_id = {d.id: d for d in jax.devices()}
                exe = _se.deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=[by_id[i] for i in device_ids])
        except Exception as e:
            logger.warning(
                "compile cache: entry %s failed to deserialize (%s: %s) "
                "— falling back to a fresh compile", cache_key,
                type(e).__name__, e)
            telemetry.counter(
                "Compile/cache_errors",
                help="corrupt/torn cache entries skipped").inc()
            return None
        load_ms = ms["load"] = _phase("load", t1, telemetry.clock_ns())
        self._count_hit()
        logger.info(
            "Warm start: fused step %r loaded from the compile cache "
            "in %.0f ms (entry %s) — no XLA compile", self.label,
            load_ms, cache_key)
        return exe

    def _store(self, cache_key: str, exe, sig_hash: str,
               fingerprint: Dict[str, str], _se,
               audit: Optional[Dict[str, Any]] = None) -> None:
        try:
            # the device assignment, in order (the attribute serialize()
            # itself reads the executable from)
            device_ids = [
                d.id for d in exe._executable._unloaded_executable.device_list]
            payload = pickle.dumps(_se.serialize(exe) + (device_ids,),
                                   protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as e:
            logger.warning(
                "compile cache: executable for %r is not serializable "
                "on this backend (%s: %s) — cache disabled for this "
                "entry", self.label, type(e).__name__, e)
            return
        self._cache.store(cache_key, payload, self.label, sig_hash,
                          self.topology, fingerprint, audit=audit)

    def _family_key(self, args: Tuple):
        """The signature with the batch dim of every bucket-arg leaf
        erased: two calls are in the same FAMILY when they differ only
        by (bucketed) batch size.  Anything else — dtype drift, a
        spatial-shape change, a different params tree — is a different
        family and stays subject to the retrace gate."""
        import jax
        from bigdl_tpu.analysis.retrace import _leaf_sig
        out = []
        for i, a in enumerate(args):
            leaves, td = jax.tree_util.tree_flatten(a)
            drop0 = i in self.bucket_argnums
            sigs = []
            for x in leaves:
                s = _leaf_sig(x)
                if drop0 and isinstance(s[0], tuple) and len(s[0]) >= 1:
                    s = (s[0][1:], s[1], s[2])
                sigs.append(s)
            out.append((repr(td), tuple(sigs)))
        return tuple(out)

    def _bucket_dim(self, args: Tuple) -> Optional[int]:
        import jax
        for arg in (args[i] for i in self.bucket_argnums
                    if i < len(args)):
            for leaf in jax.tree_util.tree_leaves(arg):
                if getattr(leaf, "ndim", 0) >= 1:
                    return int(leaf.shape[0])
        return None

    def register_if_bucketed(self, args: Tuple, key=None) -> None:
        """In-plan pre-check the sentinel wrapper runs BEFORE observing:
        a new signature whose batch dim is exactly a bucket-plan size
        (``bucket_size(n) == n`` — including the multiples of the
        largest bucket the choke points legitimately produce for
        oversize batches, which :meth:`_precompile_buckets` cannot
        enumerate ahead) AND whose family is already known is part of
        the bucket plan: it registers as a warmup compile instead of
        raising as a post-warmup retrace.  A signature differing in
        anything but the bucketed batch dim is a new family and still
        trips the gate."""
        if self.sentinel is None or not self.bucket_argnums:
            return
        buckets = configured_buckets()
        if not buckets:
            return
        if key is None:
            key = self._sig_key(args)
        if key in self._mem:
            return
        n = self._bucket_dim(args)
        if n is None or bucket_size(n, buckets) != n:
            return
        if self._family_key(args) in self._families:
            self.sentinel.register_warmup(args)

    # -- AOT bucket variants ----------------------------------------------

    def _precompile_buckets(self, args: Tuple) -> None:
        """Compile every configured bucket variant of this signature
        family ahead of time (dim 0 of the ``bucket_argnums`` args
        re-bucketed), registering each with the retrace sentinel so a
        later concrete call with that signature is a warm in-memory hit
        — never a post-warmup retrace."""
        import jax
        buckets = configured_buckets()
        if not buckets:
            return
        base = None
        for arg in (args[i] for i in self.bucket_argnums
                    if i < len(args)):
            for leaf in jax.tree_util.tree_leaves(arg):
                if getattr(leaf, "ndim", 0) >= 1:
                    base = int(leaf.shape[0])
                    break
            if base is not None:
                break
        if base is None:
            return
        for b in buckets:
            if b == base:
                continue
            spec_args = self._bucket_spec_args(args, b)
            key = self._sig_key(spec_args)
            if key in self._mem:
                continue
            try:
                self._compile_entry(spec_args, key, precompile=False)
            except CompileTimeoutError:
                raise          # a wedged precompile is still an abort
            except Exception as e:
                # a variant this step cannot lower (e.g. a bucket not
                # divisible by the eval mesh's data axis — those batches
                # run the local fallback forward anyway) is skipped, not
                # fatal; it stays OUT of the sentinel's warm set
                logger.info(
                    "compile cache: bucket-%d variant of %r not "
                    "precompilable (%s: %s) — skipped", b, self.label,
                    type(e).__name__, e)
                continue
            if self.sentinel is not None:
                self.sentinel.register_warmup(spec_args)
        # the triggering signature itself is part of the warm set
        if self.sentinel is not None:
            self.sentinel.register_warmup(args)

    def _bucket_spec_args(self, args: Tuple, bucket: int) -> Tuple:
        import jax

        def vary(x):
            if getattr(x, "ndim", 0) >= 1:
                spec = _spec_of(x)
                return jax.ShapeDtypeStruct(
                    (bucket,) + tuple(spec.shape[1:]), spec.dtype,
                    sharding=getattr(spec, "sharding", None))
            return _spec_of(x)

        def plain(x):
            # non-bucket args (params, module state) lower with their
            # sharding UNSPECIFIED: a concrete uncommitted array's
            # .sharding reads as committed-to-one-device in a spec,
            # which falsely conflicts with the mesh-sharded batch — the
            # primary concrete lowering never had that problem because
            # uncommitted arrays are free to move
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        out = []
        for i, a in enumerate(args):
            if i in self.bucket_argnums:
                out.append(jax.tree_util.tree_map(vary, a))
            else:
                out.append(jax.tree_util.tree_map(plain, a))
        return tuple(out)


def tracked_jit(fn, label: str, topology: Optional[Dict[str, Any]] = None,
                cache: Optional[CompileCache] = None,
                bucket_argnums: Sequence[int] = (),
                contract=None, **jit_kwargs) -> CachedStep:
    """``jax.jit`` + :class:`CachedStep` in one call — THE registered
    entry point for fused-step compilation (the ``untracked-jit`` lint
    rule flags any ``jax.jit``/``.lower()``/``.compile()`` outside this
    module).  ``contract`` is the step's program contract
    (:class:`~bigdl_tpu.analysis.program_contracts.StepContract`) —
    declared in the live registry and checked by the HLO auditor on
    every compile/cache-load.  ``jit_kwargs`` pass through to
    ``jax.jit`` (``donate_argnums``, ``out_shardings``, ...)."""
    import jax
    if contract is not None:
        from bigdl_tpu.analysis import program_contracts
        program_contracts.declare(contract)
    return CachedStep(jax.jit(fn, **jit_kwargs), label=label,
                      topology=topology, cache=cache,
                      bucket_argnums=bucket_argnums, contract=contract)
