"""Optimizer: training orchestration base + the single-process LocalOptimizer.

Reference equivalents: ``optim/Optimizer.scala:42,268`` (abstract base with
fluent setters + factory choosing Distri vs Local by dataset type) and
``optim/LocalOptimizer.scala:41`` (single-JVM trainer: thread-replica models
sharing one weight storage, chunked gradient sums, whole-vector optim step).

TPU-native redesign of the hot path: the reference's intra-node replica tier
(clone N models, slice the batch, sum gradients multi-threaded) collapses
into ONE jitted step — forward + loss + backward + optimizer update fused by
XLA (SURVEY §7 stage 1 note: replicas become "one params pytree, one bigger
per-chip batch").  The driver loop, triggers, checkpointing, validation, and
summary protocol are kept 1:1.
"""

from __future__ import annotations

import logging
import math
import os
import random
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import telemetry
from bigdl_tpu.telemetry import incident
from bigdl_tpu.engine import DispatchPipeline
from bigdl_tpu.engine import to_device as _to_device
from bigdl_tpu.dataset.dataset import AbstractDataSet, LocalDataSet, ShardedDataSet
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.nn.module import Container, Criterion, Module
from bigdl_tpu.optim import trigger as triggers
from bigdl_tpu.utils import chaos as _chaos
from bigdl_tpu.optim.metrics import Metrics
from bigdl_tpu.optim.optim_method import OptimMethod, SGD
from bigdl_tpu.optim.trigger import Trigger
from bigdl_tpu.optim.validation_method import ValidationMethod, ValidationResult
from bigdl_tpu.resources import (GOVERNOR as _governor, DeviceMemoryError,
                                 HostMemoryError)
from bigdl_tpu.resources import storage as _resource_storage

logger = logging.getLogger("bigdl_tpu")

#: injectable for tests (the backoff suite must not really sleep)
_sleep = time.sleep


class DivergenceError(RuntimeError):
    """Raised by the driver loop after K consecutive non-finite losses —
    caught by the retry loop, which restores the latest valid snapshot
    (graceful degradation instead of silent NaN propagation)."""


def _retry_backoff(attempt: int, base: float, cap: float,
                   rand: Optional[float] = None) -> float:
    """Capped exponential backoff with jitter for the failure-retry loop.

    Attempt ``a`` waits ``min(base * 2**(a-1), cap)`` scaled by a jitter
    factor in [0.5, 1.0] — a fleet of workers restarting off one failed
    storage backend must not stampede it in lockstep.  A cap BELOW the
    base wins (the operator asked for fast retries); a non-positive cap
    means uncapped.  ``rand`` pins the jitter for tests."""
    if base <= 0:
        return 0.0
    r = rand if rand is not None else random.random()
    interval = base * (2.0 ** (max(attempt, 1) - 1))
    if cap > 0:
        interval = min(interval, cap)
    return interval * (0.5 + 0.5 * r)


#: XLA runtime statuses that describe the PROGRAM or its arguments, not
#: the moment: the restored run would dispatch the identical call
_UNHEALABLE_STATUSES = ("INVALID_ARGUMENT", "UNIMPLEMENTED",
                        "FAILED_PRECONDITION")


def _restore_cannot_heal(e: BaseException) -> bool:
    """True for failures a snapshot restore cannot change, which the
    retry loop re-raises at once instead of backing off: anything the
    compiler rejected (``CachedStep`` tags it ``compile_phase`` — a
    Mosaic/XLA compile error, a compile-time ``RESOURCE_EXHAUSTED``),
    and runtime errors whose status blames the call itself."""
    if getattr(e, "compile_phase", None) is not None:
        return True
    return (isinstance(e, jax.errors.JaxRuntimeError) and
            str(e).split(":", 1)[0].strip() in _UNHEALABLE_STATUSES)


def is_writer_process() -> bool:
    """Single-writer discipline for externally-visible artifacts.

    In the reference, checkpoints and TensorBoard events are written exactly
    once, from the driver JVM (``optim/DistriOptimizer.scala:394-416`` and
    ``:426-456`` — executor code never writes).  The multi-controller SPMD
    rebuild runs the full driver body in EVERY process, so file-producing
    calls (checkpoint snapshots, summary events, parameter histograms) are
    gated here on process 0.  Trigger *decisions* stay ungated — every
    process must reach the same publish/validation sync points or the
    collectives inside them deadlock; only the filesystem writes are
    single-writer.  Single-process this is always True.
    """
    return jax.process_index() == 0


def cast_floats(tree, dtype):
    """Cast float leaves of a pytree (mixed-precision compute casts)."""
    def f(x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(f, tree)


def mixed_precision_forward(model: Module, params, inputs, mstate,
                            precision, training: bool, rng):
    """Forward in the compute precision, loss-side outputs back in fp32.

    bf16: parameters/inputs/state are cast down for the forward (autodiff
    casts gradients back up, so the update sees fp32 master-weight grads);
    outputs and new state return as fp32 for the loss and the carries.

    The forward runs under the ``forward`` named scope (the backward
    pass inherits it as ``transpose(jvp(forward))``) and the bf16 casts
    under ``cast_params``: metadata only, so that a device trace can say
    which part of the step an operation belongs to.
    """
    if precision == "bf16":
        with jax.named_scope("cast_params"):
            cp = cast_floats(params, jnp.bfloat16)
            cx = cast_floats(inputs, jnp.bfloat16)
        # module state (BatchNorm running statistics) stays fp32 like the
        # master weights: EMA increments below bf16 resolution must not
        # round away, and fp32 state promotes the EMA arithmetic itself
        with jax.named_scope("forward"):
            out, new_mstate = model.apply(cp, cx, mstate,
                                          training=training, rng=rng)
        out = cast_floats(out, jnp.float32)
        from bigdl_tpu.utils import config
        if (config.get_bool("bigdl.chaos.f32Upcast", False)
                and getattr(out, "ndim", 0) >= 2):
            # audit fault injection: an f32 matmul smuggled into a
            # declared-bf16 program — numerically an identity, but an
            # f32 dot_general in the lowered text, exactly the drift
            # the precision pass exists to catch
            eye = jnp.eye(jnp.shape(out)[-1], dtype=jnp.float32)
            out = out @ eye
        return out, cast_floats(new_mstate, jnp.float32)
    with jax.named_scope("forward"):
        return model.apply(params, inputs, mstate, training=training,
                           rng=rng)


def _profiler_options():
    """What the driver starts ``jax.profiler`` with: the Python tracer
    off.  On, it records thousands of host events a step and slows the
    host it looks at; the ``driver/*`` spans' twins (telemetry/tracer.py)
    are what the host plane is for."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def moe_aux_penalty(model: Module, new_mstate, weight: float):
    """MoE load-balancing term: ``weight`` x the sum of every declared
    ``aux_loss`` diagnostic in the post-forward state (Switch's balancing
    objective; without this in the loss, routing feels zero pressure and
    expert collapse is the textbook outcome).  Zero when the model has no
    MoE (the walk finds nothing at trace time, adding no ops)."""
    from bigdl_tpu.nn.module import collect_diagnostics
    aux = collect_diagnostics(model, new_mstate, "aux_loss")
    if not aux or weight == 0.0:
        return jnp.zeros(())
    return weight * sum(aux)


def all_finite(*trees) -> jnp.ndarray:
    """Scalar bool: every float leaf of every tree is finite.  The
    divergence guard's trace-time predicate — cheap relative to the step
    (one reduction per leaf, fused by XLA).  Empty and integer-only
    trees are vacuously finite and return a CONSTANT True without
    building a single device op — callers branch on the guard at trace
    time, and a float-free tree must not cost a device reduction (or a
    tracer) to say nothing."""
    ok = None
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            leaf = jnp.asarray(leaf)
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                fin = jnp.all(jnp.isfinite(leaf))
                ok = fin if ok is None else jnp.logical_and(ok, fin)
    if ok is None:
        return np.bool_(True)
    return ok


def select_tree(ok, new_tree, old_tree):
    """Per-leaf ``where(ok, new, old)`` — the divergence guard's in-step
    skip: when the step produced a non-finite loss or gradient, every
    carry keeps its pre-step value (``where(True, new, old)`` is exactly
    ``new``, so a healthy step is numerically untouched).

    Under the ``optimizer_update`` scope: XLA fuses these selects with
    the update's arithmetic and names the fused operation after them, so
    without it the update's device time reads ``jit(_where)/select_n``
    (seen on the chip, PR 24)."""
    with jax.named_scope("optimizer_update"):
        return jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new_tree, old_tree)


def regularization_penalty(module: Module, params) -> jnp.ndarray:
    """Sum per-layer regularizer penalties over the module tree
    (reference applies them in each layer's accGradParameters,
    ``optim/Regularizer.scala``; here they join the loss so autodiff
    produces the identical gradient contribution)."""
    total = jnp.zeros(())
    if isinstance(module, Container):
        for i, c in enumerate(module.children):
            total = total + regularization_penalty(c, params[i])
    else:
        wreg = getattr(module, "w_regularizer", None)
        breg = getattr(module, "b_regularizer", None)
        if wreg is not None and isinstance(params, dict):
            w = {k: v for k, v in params.items() if k != "bias"}
            total = total + wreg.penalty(w)
        if breg is not None and isinstance(params, dict) and "bias" in params:
            total = total + breg.penalty(params["bias"])
    return total


class Checkpoint:
    """model.<neval> / optimMethod.<neval> snapshot protocol
    (reference ``optim/DistriOptimizer.scala:394-416``), hardened into
    verified units by :class:`~bigdl_tpu.utils.checkpoint_manager.
    CheckpointManager`: every snapshot carries a CRC32C manifest plus a
    commit marker written last, restore scans newest→oldest skipping
    torn/uncommitted/corrupt snapshots, ``keep_last`` garbage-collects
    old committed snapshots, and ``async_write`` moves serialization+IO
    onto a background writer (errors re-raise at the next save and at
    exit).

    ``path`` may be local or any fsspec scheme (``hdfs://``, ``s3://``,
    ``memory://``, …) — the reference checkpoints to HDFS the same way
    (``File.saveToHdfs:106``); listing/joining go through
    ``utils.file_io`` so ``latest()`` resolves remotely too."""

    #: seconds a ``.tmp_bigdl`` temp must sit untouched before the sweep
    #: may reclaim it.  An atomic save holds its temp open for seconds at
    #: most; an hour-old temp is an orphan from a hard-killed writer, not
    #: another live job's in-flight write (two jobs pointed at one dir, a
    #: stalled-but-alive writer) — sweeping those would break THEIR rename.
    TEMP_SWEEP_AGE_S = 3600.0

    def __init__(self, path: str, trigger: Trigger, isOverwrite: bool = True,
                 keep_last: Optional[int] = None,
                 async_write: Optional[bool] = None):
        from bigdl_tpu.utils.checkpoint_manager import CheckpointManager
        self.path = path
        self.trigger = trigger
        self.overwrite = isOverwrite
        self.manager = CheckpointManager(path, keep_last=keep_last,
                                         async_write=async_write,
                                         overwrite=isOverwrite)
        self.manager.TEMP_SWEEP_AGE_S = self.TEMP_SWEEP_AGE_S

    def save(self, model: Module, optim: OptimMethod, neval: int,
             topology=None) -> None:
        self.manager.save(model, optim, neval, topology=topology)

    def latest(self) -> Optional[Tuple[str, str, int]]:
        """Newest snapshot that is a complete pair, committed, and
        checksum-clean (``latest_valid`` semantics: one torn write can
        never brick recovery)."""
        return self.manager.latest_valid()

    latest_valid = latest

    def join(self, raise_errors: bool = True) -> None:
        """Drain the async writer; deferred write errors re-raise here."""
        self.manager.join(raise_errors=raise_errors)


class Optimizer:
    """Abstract trainer base (reference ``optim/Optimizer.scala:42``).

    The ``Optimizer(...)`` factory (``apply``, reference ``:268``) picks
    :class:`LocalOptimizer` or the distributed trainer by dataset type.
    """

    #: when the running optimize() was entered, until step 1 dispatches
    _setup_t0: Optional[int] = None

    def __init__(self, model: Module, dataset: AbstractDataSet,
                 criterion: Criterion):
        self.model = model
        self.dataset = dataset
        self.criterion = criterion
        self.optim_method: OptimMethod = SGD()
        self.end_when: Trigger = triggers.max_iteration(100)
        self.checkpoint: Optional[Checkpoint] = None
        self.validation_trigger: Optional[Trigger] = None
        self.validation_dataset: Optional[AbstractDataSet] = None
        self.validation_methods: Optional[List[ValidationMethod]] = None
        self.train_summary = None
        self.validation_summary = None
        self.drop_percentage: float = 0.0
        self.max_drop_percentage: float = 0.0
        self.metrics = Metrics()
        self.precision: Optional[str] = None   # None = fp32; "bf16" = mixed
        self.moe_aux_weight: float = 0.01      # Switch paper's alpha
        self._step_fn = None
        self._profile_dir: Optional[str] = None
        self._profile_start: int = 10
        self._profile_n: int = 3
        #: recompile sentinel wrapped around the fused step (analysis pass 1)
        self._retrace_sentinel = None
        #: per-run step-time decomposition (bigdl_tpu.telemetry)
        self._step_account = None
        #: microbatch re-plan state (resources.microbatch): the fused
        #: step runs as k gradient-accumulation chunks after a device
        #: OOM; 1 = full-batch (the normal plan)
        self._microbatch_k: int = 1
        #: global batch size observed by the last fetch — the re-plan
        #: needs it to pick a k that divides the batch
        self._plan_batch_size: int = 0

    # -- fluent setters (reference Optimizer.scala fluent API) ------------

    def set_trace_profile(self, log_dir: str, start_iteration: int = 10,
                          n_iterations: int = 3) -> "Optimizer":
        """Capture a ``jax.profiler`` device/host trace of ``n_iterations``
        steady-state training iterations into ``log_dir`` (xplane + trace
        viewer files; open with TensorBoard's profile plugin or Perfetto).

        TPU-native counterpart of the per-module ns timing (SURVEY §5.1):
        the per-module clocks attribute time WITHIN the model graph, the
        trace shows the whole step — XLA fusions, collectives, host gaps.
        ``start_iteration`` defaults past compile+warmup so the captured
        window is the steady state the throughput logs report.  The
        session arms the program's own spans (``driver/*`` and every
        other ``telemetry.span``) for as long as it lasts and gives each a
        twin on the trace's host plane; the Python tracer stays off."""
        if n_iterations < 1:
            raise ValueError(f"n_iterations must be >= 1, got {n_iterations}")
        if start_iteration < 1:
            raise ValueError(
                f"start_iteration must be >= 1 (iteration counting is "
                f"1-based), got {start_iteration}")
        self._profile_dir = log_dir
        self._profile_start = start_iteration
        self._profile_n = n_iterations
        return self

    def set_optim_method(self, method: OptimMethod) -> "Optimizer":
        self.optim_method = method
        self._step_fn = None  # the jitted step closes over the optim method
        return self

    def set_end_when(self, trigger: Trigger) -> "Optimizer":
        self.end_when = trigger
        return self

    def set_checkpoint(self, path: str, trigger: Trigger,
                       isOverwrite: bool = True,
                       keep_last: Optional[int] = None,
                       async_write: Optional[bool] = None) -> "Optimizer":
        """``keep_last``: retain only the N newest committed snapshots
        (default ``bigdl.checkpoint.keepLast``; 0 keeps all).
        ``async_write``: serialize+write snapshots on a background thread
        so the train step never blocks on (possibly remote) IO — writer
        errors re-raise at the next save and at exit (default
        ``bigdl.checkpoint.asyncWrite``)."""
        self.checkpoint = Checkpoint(path, trigger, isOverwrite,
                                     keep_last=keep_last,
                                     async_write=async_write)
        return self

    def set_validation(self, trigger: Trigger, dataset: AbstractDataSet,
                       methods: List[ValidationMethod],
                       batch_size: Optional[int] = None) -> "Optimizer":
        self.validation_trigger = trigger
        if isinstance(dataset, (list, tuple)):
            dataset = LocalDataSet(dataset)
        if batch_size is not None and not _yields_minibatches(dataset):
            from bigdl_tpu.dataset.transformer import SampleToMiniBatch
            dataset = dataset.transform(SampleToMiniBatch(batch_size))
        self.validation_dataset = dataset
        self.validation_methods = methods
        return self

    def set_train_summary(self, summary) -> "Optimizer":
        self.train_summary = summary
        return self

    def set_validation_summary(self, summary) -> "Optimizer":
        self.validation_summary = summary
        return self

    def set_precision(self, precision: Optional[str]) -> "Optimizer":
        """Mixed-precision training: ``"bf16"`` runs forward/backward in
        bfloat16 (the MXU's native multiply format; ~1.8x ResNet-50
        throughput measured on v5e) while master weights, the loss, and the
        optimizer update stay float32.  The reference's fp16 existed only on
        the wire (``parameters/FP16CompressedTensor.scala``); on TPU reduced
        precision lives in the compute itself."""
        if precision not in (None, "bf16"):
            raise ValueError(f"unsupported precision {precision!r}")
        self.precision = precision
        self._step_fn = None
        return self

    def set_moe_aux_weight(self, weight: float) -> "Optimizer":
        """Weight of the MoE load-balancing auxiliary loss folded into the
        objective (:func:`moe_aux_penalty`).  Default 0.01 — the Switch
        Transformer paper's alpha; 0 disables the pressure (diagnostic
        stays readable in module state)."""
        self.moe_aux_weight = float(weight)
        self._step_fn = None
        return self

    def set_drop_module_percentage(self, drop_p: float,
                                   max_drop_p: float) -> "Optimizer":
        """Straggler dropping (reference ``setDropModuleProperty``).  Kept for
        API parity: synchronous XLA collectives have no intra-step stragglers
        (SURVEY §7 stage 4), so this is recorded but inert."""
        self.drop_percentage = drop_p
        self.max_drop_percentage = max_drop_p
        return self

    def optimize(self) -> Module:
        """Train with failure retry (reference
        ``optim/DistriOptimizer.scala:750-816``): on a non-argument error the
        newest VALID ``model.N``/``optimMethod.N`` snapshot is restored and
        training resumes, up to ``bigdl.failure.retryTimes`` attempts.
        Errors a restore cannot change (:func:`_restore_cannot_heal`:
        compile failures, argument/sharding runtime statuses) re-raise
        at once like the reference's ``IllegalArgumentException``.
        Waits between attempts follow capped exponential backoff with
        jitter (:func:`_retry_backoff`), and — mirroring the reference's
        ``retryNum`` reset — the attempt counter resets whenever training
        reaches NEW ground (``evalCounter`` beyond any previous
        attempt's high-water mark), so a long healthy run is never
        killed by unrelated failures hours apart, while a deterministic
        failure that replays the same stretch after every rollback still
        exhausts the budget.

        Failure taxonomy (``utils/elastic.py``): *divergence* and a
        watchdog-aborted hung step restore-and-retry here; *preemption*
        (:class:`~bigdl_tpu.utils.elastic.Preempted` — the driver already
        drained and published) commits a final verified snapshot plus a
        resumable marker within ``bigdl.elastic.gracePeriod`` and
        re-raises: the scheduler said leave, not rewind."""
        from bigdl_tpu.utils import config, elastic
        self._setup_t0 = telemetry.clock_ns()
        retry_times = config.get_int("bigdl.failure.retryTimes", 5)
        base = config.get_float("bigdl.failure.retryTimeInterval", 120.0)
        cap = config.get_float("bigdl.failure.maxRetryInterval", 900.0)
        # a fresh optimize() starts clean: a preemption flag left over
        # from a previous run in this process (or a marker from the
        # preempted lifetime we are resuming) must not instantly re-drain
        elastic.clear_preemption()
        if self.checkpoint is not None and is_writer_process():
            elastic.clear_preemption_marker(self.checkpoint.path)
        try:
            with elastic.PreemptionHandler():
                return self._optimize_with_retry(retry_times, base, cap)
        except BaseException:
            # already unwinding: drain the writer but never let a deferred
            # write error mask the original failure
            if self.checkpoint is not None:
                try:
                    self.checkpoint.join(raise_errors=False)
                except Exception:  # pragma: no cover - defensive
                    pass
            raise

    def _optimize_with_retry(self, retry_times, base, cap) -> Module:
        from bigdl_tpu.utils import elastic
        attempt = 0
        high_water = None   # furthest evalCounter any attempt reached
        while True:
            try:
                result = self._optimize()
            except (ValueError, TypeError, KeyboardInterrupt):
                # reference: IllegalArgumentException aborts immediately
                raise
            except HostMemoryError:
                # host memory exhausted even at depth 1 — no ring can
                # shrink below one item, so a retry replays the same
                # allocation; surface the structured error immediately
                raise
            except DeviceMemoryError as e:
                # RESOURCE fault, not divergence: the same program would
                # OOM forever, so retrying costs no budget and waits no
                # backoff — the answer is a microbatch re-plan (split the
                # global batch into k accumulation chunks).  next_k's
                # doubling schedule bounds the loop: once per-sample has
                # been tried the re-plan returns False and the fault is
                # fatal.
                heal_t0 = time.monotonic()
                if not self._replan_microbatch(e):
                    raise
                restored = self._restore_latest_checkpoint()
                if not restored and self._params_dead():
                    # the OOMed dispatch donated-and-deleted the carries
                    # and there is no snapshot to reload them from
                    raise
                heal_ms = (time.monotonic() - heal_t0) * 1000.0
                telemetry.gauge(
                    "Resources/oom_replan_ms",
                    help="device-OOM detection to re-planned-step "
                         "readiness (re-plan + restore)").set(heal_ms)
                incident.record("optim/oom_replan", restored=restored,
                                heal_ms=round(heal_ms, 2))
                continue
            except elastic.Preempted:
                # the driver drained and published before raising; commit
                # the grace-period snapshot and leave — preemption is an
                # eviction, not a fault, so no retry and no restore
                incident.record("optim/preempted",
                                reason=elastic.preemption_reason())
                self._commit_preemption_snapshot()
                incident.maybe_dump("preemption", reason="preemption")
                raise
            except Exception as e:
                if _restore_cannot_heal(e):
                    raise
                from bigdl_tpu.integrity import (IntegrityError,
                                                 ReplicaDesyncError)
                cur = self.optim_method.state.get("evalCounter", 0)
                if (not isinstance(e, (DivergenceError, IntegrityError))
                        and high_water is not None and cur > high_water):
                    # NEW ground — training got further than any
                    # previous attempt, so this is a fresh fault, not
                    # the same one looping (reference retryNum reset
                    # on state-version advance, :772-776).  The
                    # baseline must be the high-water mark across
                    # attempts: replayed ground after a rollback is
                    # not progress, or a deterministic failure pinned
                    # one step past the newest snapshot would reset
                    # the budget every cycle and retry forever.
                    # Divergence NEVER resets the budget: guard-
                    # skipped iterations still advance the counters
                    # (frozen params, moving evalCounter), so a
                    # persistently-NaN pipeline would otherwise creep
                    # the high-water mark every restore cycle and
                    # loop unbounded.
                    attempt = 0
                high_water = cur if high_water is None else max(
                    high_water, cur)
                attempt += 1
                if attempt >= retry_times:
                    incident.record("optim/retries_exhausted",
                                    attempt=attempt,
                                    error=type(e).__name__)
                    incident.maybe_dump("optim/retries_exhausted",
                                        reason=type(e).__name__)
                    raise
                if (isinstance(e, ReplicaDesyncError)
                        and getattr(e, "healed", False)):
                    # the trainer already re-broadcast canonical state
                    # from the agreeing majority and rewound the eval
                    # counter — a checkpoint restore would throw away
                    # the surviving replicas' newer, valid ground
                    incident.record("optim/desync_heal", attempt=attempt,
                                    error=type(e).__name__)
                    interval = _retry_backoff(attempt, base, cap)
                    logger.warning(
                        "Replica desync healed in place (attempt %d/%d); "
                        "re-entering training in %.1fs: %s", attempt,
                        retry_times, interval, e)
                    _sleep(interval)
                    continue
                heal_t0 = time.monotonic()
                restored = self._restore_latest_checkpoint()
                if restored and isinstance(e, IntegrityError):
                    telemetry.gauge(
                        "Integrity/heal_ms",
                        help="detection-to-heal latency of the last "
                             "integrity fault (restore or re-broadcast)"
                    ).set((time.monotonic() - heal_t0) * 1000.0)
                if not restored and self._params_dead():
                    # the jitted step donates its carries: without a
                    # snapshot to reload, the in-memory params are gone
                    # — retrying would fail on deleted buffers, so
                    # surface the original
                    raise
                incident.record("optim/retry_restore", attempt=attempt,
                                error=type(e).__name__, restored=restored)
                interval = _retry_backoff(attempt, base, cap)
                logger.exception(
                    "Training failed (attempt %d/%d); %s and retrying "
                    "in %.1fs", attempt, retry_times,
                    "restored latest valid checkpoint" if restored else
                    "resuming from last published state", interval)
                _sleep(interval)
                continue
            # clean exit: surface any deferred async-writer error
            # BEFORE reporting success — a "finished" run whose last
            # snapshot silently failed to land is a lie
            if self.checkpoint is not None:
                self.checkpoint.join()
            return result

    def _replan_microbatch(self, e: DeviceMemoryError) -> bool:
        """Answer a :class:`DeviceMemoryError` with the next microbatch
        plan: the global batch of B samples re-runs as k equal
        accumulation chunks (``resources.microbatch`` — Kahan-compensated
        mean gradient, ONE optimizer update, numerics allclose to the
        full-batch step).  Invalidates the built step and the retrace
        sentinel so the re-planned program compiles as a NEW signature
        with its own warmup — the re-plan must never trip the strict
        retrace gate.  Returns False when no further split exists
        (already per-sample, or no batch observed yet)."""
        from bigdl_tpu.resources import microbatch as _microbatch
        bsz = int(self._plan_batch_size or 0)
        if bsz <= 0:
            return False
        k = _microbatch.next_k(bsz, self._microbatch_k)
        if k is None:
            return False
        prev = self._microbatch_k
        self._microbatch_k = k
        self._step_fn = None           # rebuild with the k-chunk plan
        self._retrace_sentinel = None  # fresh warmup for the new program
        telemetry.counter(
            "Resources/microbatch_replans",
            help="device-OOM-driven microbatch re-plans this process").inc()
        telemetry.gauge(
            "Resources/microbatch_k",
            help="gradient-accumulation chunks per step after OOM "
                 "re-planning (1 = full batch)").set(k)
        logger.warning(
            "Device memory exhausted (%s) — re-planning the fused step: "
            "global batch %d now runs as %d accumulation chunk(s) of %d "
            "samples (was k=%d)", e, bsz, k, bsz // k, prev)
        return True

    def _commit_preemption_snapshot(self) -> None:
        """The grace-period exit: the driver already flushed its dispatch
        pipeline and published the carries before raising ``Preempted``,
        so the live model/optim shells hold the newest weights — commit
        them as a final verified snapshot, drain the async writer, and
        drop the resumable marker.  Multi-host note: preemption unwinds
        every rank (the scheduler signals the whole slice); only the
        writer process touches the store, and no barrier is added here —
        peers may already be dying, and a barrier against the dead hangs
        the grace window."""
        from bigdl_tpu.utils import elastic
        if self.checkpoint is None:
            logger.warning(
                "Preempted with no checkpoint configured — state of this "
                "run is lost (set_checkpoint enables the grace-period "
                "snapshot)")
            return
        # the grace window opened when preemption was REQUESTED: the
        # drain the driver already ran (pipeline flush + publish) spent
        # part of it, and the overshoot report must say so
        opened = elastic.preemption_requested_at()
        deadline = ((opened if opened is not None else time.monotonic())
                    + elastic.grace_period())
        neval = self.optim_method.state.get("evalCounter", 0)
        committed = True
        if is_writer_process():
            with elastic.timed("preempt_snapshot"):
                # a failed write (sync save raising, or an async write
                # surfacing at join) must neither drop the marker — a
                # marker naming a snapshot that never landed would turn
                # a botched drain into a trusted orderly preemption —
                # nor replace the Preempted unwinding this frame: the
                # run IS preempted either way, resume falls back to the
                # newest earlier valid snapshot
                try:
                    self.checkpoint.save(self.model, self.optim_method,
                                         neval,
                                         topology=self._topology_meta())
                    # the marker must only land AFTER the snapshot is
                    # committed
                    self.checkpoint.join()
                except Exception:
                    committed = False
                    logger.exception(
                        "Grace-period snapshot %d failed to commit — "
                        "NOT writing the preemption marker; resume will "
                        "fall back to the newest earlier valid snapshot",
                        neval)
            if committed:
                elastic.write_preemption_marker(self.checkpoint.path, neval)
        overshoot = time.monotonic() - deadline
        status = ("snapshot %d is committed" % neval if committed else
                  "snapshot %d FAILED to commit" % neval)
        if overshoot > 0:
            logger.warning(
                "Preemption drain exceeded bigdl.elastic.gracePeriod by "
                "%.1fs — the scheduler may have killed peers already; "
                "%s", overshoot, status)
        else:
            logger.info(
                "Preemption drain complete: %s with %.1fs of the grace "
                "period to spare", status, -overshoot)

    def _topology_meta(self) -> Optional[Dict[str, Any]]:
        """The saving topology recorded in snapshot manifests
        (``elastic.describe_topology``); distributed trainers override
        with their mesh so restores onto a different device count can
        reshard — the local trainer has no mesh to record."""
        from bigdl_tpu.utils import elastic
        return elastic.describe_topology(step="local")

    def _sync_dataset_epoch(self) -> None:
        """Cross-restart batch-stream parity, part 2: a RESUMED run
        fast-forwards the dataset's shuffle round to ``epoch - 1`` so
        its first ``reset_epoch`` draws epoch E's permutation — the one
        the interrupted run trained (and an uninterrupted run would
        train), not round 1's.  ``ShardedDataSet`` shuffles are pure in
        ``(seed, round)`` which makes the replay exact; ``LocalDataSet``
        draws from the stateful thread-local generator and has no round
        protocol (no-op here) — bit-exact resume parity is the sharded
        dataset's contract."""
        epoch = self.optim_method.state.get("epoch", 1)
        sync = getattr(self.dataset, "set_shuffle_round", None)
        if sync is not None:
            # unconditional, epoch 1 included: an in-process retry that
            # restores into epoch 1 reuses a dataset whose round already
            # advanced — without the rewind the replayed epoch would
            # draw round 2's permutation
            sync(epoch - 1)

    def _optimize(self) -> Module:
        raise NotImplementedError

    def _arm_retrace(self, step_fn, label: str):
        """Wrap a fused jitted step with the recompile sentinel
        (``bigdl.analysis.retrace``): post-warmup signature drift raises
        (strict) or logs a structured shape/dtype/weak-type diff (warn),
        surfaced as ``Analysis/retraces`` in TrainSummary.  Host-driven
        feval methods (LBFGS) are not jitted per-step, so they pass
        through unwrapped."""
        if getattr(self.optim_method, "requires_feval", False):
            return step_fn
        from bigdl_tpu.analysis.retrace import RetraceSentinel
        sentinel = RetraceSentinel.from_config(
            f"{type(self).__name__}[{label}]")
        if sentinel is None:
            return step_fn
        self._retrace_sentinel = sentinel
        return sentinel.wrap(step_fn)

    def _warmup_compiles(self, inputs, targets, hyper, rng) -> None:
        """The AOT warmup phase: compile — or warm-load from the
        persistent cache — the fused step for the first batch's
        signature BEFORE step 1 dispatches, in an explicit
        telemetry-spanned phase (``driver/compile_warmup``,
        ``Compile/warmup_ms``).  Every trace/load/compile inside runs
        under the compile watchdog (``bigdl.compile.timeoutSec``), so a
        wedged compile aborts with a diagnosed
        :class:`~bigdl_tpu.utils.compile_cache.CompileTimeoutError`
        that the retry loop treats like divergence — restore and retry
        — instead of hanging the driver.  Trainers that cannot
        reproduce their step's argument tuple (no ``_cost_args_fn``)
        simply compile at step 1 as before.  It also closes
        ``optimize()``'s set-up (``Setup/program_ms{entry=optimize}``,
        span ``driver/setup``)."""
        from bigdl_tpu.utils import compile_cache
        args_fn = getattr(self, "_cost_args_fn", None)
        step = self._step_fn
        target = getattr(step, "__wrapped__", step)
        if args_fn is not None and isinstance(target,
                                              compile_cache.CachedStep):
            was_warm = target.warm
            with telemetry.span("driver/compile_warmup"):
                t0 = telemetry.clock_ns()
                target.warmup(*args_fn(inputs, targets, hyper, rng))
                warm_ms = (telemetry.clock_ns() - t0) / 1e6
            telemetry.gauge("Compile/warmup_ms").set(warm_ms)
            if not was_warm:
                logger.info(
                    "Compile warmup complete in %.0f ms: fused step %r "
                    "ready before step 1 (%d cache hit(s), %d fresh "
                    "compile(s))", warm_ms, target.label, target.cache_hits,
                    target.compiles)
        # step 1 dispatches next: optimize()'s own set-up ends here, once
        # however many attempts it took
        t_setup, self._setup_t0 = self._setup_t0, None
        if t_setup is not None:
            telemetry.setup_done("optimize", "driver/setup", t_setup)

    def _params_dead(self) -> bool:
        """True if any live model parameter buffer was donated-and-deleted
        by a partially-completed jitted step."""
        for leaf in jax.tree_util.tree_leaves(self.model._params):
            if getattr(leaf, "is_deleted", lambda: False)():
                return True
        return False

    def _restore_latest_checkpoint(self) -> bool:
        """Reload the newest VALID model.N/optimMethod.N snapshot into the
        live model/optim shells (reference ``DistriOptimizer.scala:766-788``
        hardened): uncommitted, checksum-failing, or pair-incomplete
        snapshots are skipped, and a snapshot that fails to deserialize
        falls back to the next-older one.  Returns False when there is
        nothing to restore (no checkpoint configured, or no valid
        snapshot written yet).

        Topology-elastic: the manager compares the snapshot's recorded
        saving topology against this trainer's (``_topology_meta``) —
        same topology restores as always; a changed one either reshards
        (``bigdl.elastic.reshardOnRestore``: the canonical host trees
        restored here are re-partitioned for the new mesh when the
        trainer next places its carries) or raises a structured
        ``TopologyMismatchError``.  The whole restore is timed into
        ``Elastic/restore_ms``."""
        if self.checkpoint is None:
            return False
        # drain the async writer first: an in-flight snapshot must either
        # be fully committed or definitively absent before the scan (its
        # errors are logged, not raised — we are already recovering)
        self.checkpoint.join(raise_errors=False)
        if jax.process_count() > 1:
            # every rank must scan the same committed set: the writer's
            # drain (above) happens-before any rank lists the store.
            # Like the trigger-decision symmetry _check_symmetric_config
            # enforces, multi-host retry assumes SYMMETRIC failure —
            # every rank fails the same iteration and enters restore
            # together.  The failure classes this subsystem introduces
            # hold that invariant by construction: data/step faults
            # surface identically on all ranks, divergence works off the
            # pmean'd loss, and writer-only save errors are allgathered
            # to every rank by _run_checkpoint before anyone raises.
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("bigdl_restore_scan")
        from bigdl_tpu.utils import elastic
        with elastic.timed("restore") as timer:
            loaded = self.checkpoint.manager.load_latest(
                expected_topology=self._topology_meta())
            if loaded is None:
                # nothing restorable: the empty directory scan is not a
                # restore — don't report its duration as one
                timer.cancel()
                return False
            loaded_model, loaded_optim, n = loaded
            self.model.params = loaded_model.params
            self.model.state = loaded_model.state
            if isinstance(self.model, Container):
                self.model._adopt()
            self.optim_method.state = loaded_optim.state
            self.optim_method.set_slots(loaded_optim._slots)
        # consumed (and cleared) by the trainers' slot-placement blocks
        # via _consume_elastic_resumed — only a restore that actually
        # crossed a topology change is a reshard; a same-topology retry
        # restore re-places onto the same mesh and must not be timed
        # (or barriered) as one, keeping the gauge consistent with the
        # Elastic/reshards counter
        self._elastic_resumed = (
            self.checkpoint.manager.last_restore_mode == "reshard")
        logger.info("Restored snapshot model.%d / optimMethod.%d", n, n)
        return True

    def _consume_elastic_resumed(self) -> bool:
        """True when the live optimizer slots came from a checkpoint
        restore that CROSSED a topology change
        (``_restore_latest_checkpoint`` with ``last_restore_mode ==
        "reshard"``) — the slot placement that follows is a
        topology-elastic reshard worth timing (``elastic.place_slots``).
        A same-topology restore or a second in-process ``optimize()``
        re-placing live slots is not one, and must neither overwrite
        ``Elastic/reshard_ms`` nor pay a startup barrier.  Clears the
        flag: one placement consumes one restore."""
        resumed = (self.optim_method._slots is not None and
                   getattr(self, "_elastic_resumed", False))
        self._elastic_resumed = False
        return resumed

    # -- shared driver loop (used by Local and Distri trainers) -----------

    def _drive(self, fetch_batch, run_step, reset_epoch, publish,
               epoch_size: int, integrity=None) -> Dict[str, Any]:
        """The per-iteration driver loop both trainers share (reference
        ``optim/DistriOptimizer.scala:141-344`` / ``LocalOptimizer.scala:78``):
        fetch, step, bookkeeping/logging, epoch rollover, trigger-gated
        validation + checkpoint.

        ``fetch_batch() -> (inputs, targets, batch_size)`` and
        ``run_step(inputs, targets, hyper, rng) -> loss`` (or
        ``-> (loss, aux)`` — a device-resident diagnostics pytree rides
        the dispatch pipeline next to the loss) close over the trainer's
        device-resident carries; ``publish()`` syncs those carries back
        into the model/optim shells — called only when a trigger fires
        (the reference's getModel runs only at checkpoints, ``:818``) and
        once at the end.  ``integrity`` is the trainer's
        :class:`~bigdl_tpu.integrity.DriverIntegrity`: it names the
        first non-finite leaf in the bad-step diagnostics, and at its
        cadence classifies the step's fingerprint verdicts (raising
        ``IntegrityError`` / ``ReplicaDesyncError`` into the retry
        loop).
        """
        self._check_symmetric_config()
        state = _initial_driver_state()
        # resume: continue the counters a restored OptimMethod carries
        # (reference Train drivers pass --stateSnapshot and the optim state's
        # epoch/evalCounter pick up where the snapshot left off)
        state["neval"] = self.optim_method.state.get("evalCounter", 0) + 1
        state["epoch"] = self.optim_method.state.get("epoch", 1)
        stochastic = self.model.is_stochastic()
        rng_counter = state["neval"] - 1
        wall_start = time.time()

        from bigdl_tpu.utils import config as _config

        # -- telemetry: arm the tracer if configured, name the driver lane
        # (always: a profiler session that starts mid-run arms the spans,
        # and its readers find the lane by name), and start a fresh
        # per-run step account.  Per-run gauges from a previous optimize()
        # in this process are dropped so a run that no longer produces
        # them cannot re-chart stale values.
        telemetry.maybe_arm_from_config()
        telemetry.name_thread("driver")
        # per-run timeline: a second optimize() in this process must
        # export only its own spans (rings stay registered, events and
        # the trace epoch reset)
        telemetry.reset_tracer()
        telemetry.REGISTRY.drop_prefix("Telemetry/")
        telemetry.REGISTRY.drop_prefix("Analysis/")
        step_account = telemetry.StepAccount(
            window=_config.get_int("bigdl.telemetry.percentileWindow", 512),
            detector=telemetry.SlowStepDetector(
                _config.get_float("bigdl.telemetry.slowStepFactor", 0.0),
                warmup=_config.get_int("bigdl.telemetry.slowStepWarmup", 5),
                cooldown=_config.get_int("bigdl.telemetry.slowStepCooldown",
                                         50)))
        self._step_account = step_account
        log_every = max(1, _config.get_int("bigdl.telemetry.logEveryN", 1))
        slow_profile_dir = _config.get_property(
            "bigdl.telemetry.profileOnSlowStep")
        #: one-shot jax.profiler capture requested by the slow-step detector
        slow_req = {"due": False, "captured": False}

        # Dispatch pipeline: iteration i's loss is read (a blocking device
        # round-trip) only after up to ``bigdl.pipeline.depth`` further
        # iterations are queued, with the device→host copy started
        # asynchronously at dispatch (the depth's effect is not measured
        # on the current machine).  Every iteration still gets its
        # reference-protocol log line — it just prints up to `depth` dispatches later, and
        # always before any sync point (validation, checkpoint, end).
        # Loss-reading end triggers (min_loss) set Trigger.reads_loss, and
        # the loop flushes before evaluating them so they never see a
        # stale loss — effectively depth=1 while such a trigger is
        # installed (the user chose stop-on-loss semantics over latency
        # hiding).
        max_bad_steps = _config.get_int("bigdl.divergence.maxBadSteps", 5)

        from bigdl_tpu.analysis.hostsync import host_pull

        def drain(item, nxt):
            loss_dev, bsz, t0, epoch, recs, neval, parts, aux = item
            # the ONE intended device→host pull of the hot loop, through
            # the explicit choke point (permitted while the guard is armed)
            with telemetry.span("driver/host_wait"):
                t_pull = telemetry.clock_ns()
                loss = float(host_pull(loss_dev, what="iteration loss"))
                pull_ns = telemetry.clock_ns() - t_pull
            t_book = telemetry.clock_ns()
            # per-iteration wall time = interval to the NEXT dispatch (the
            # flush happens up to depth-1 dispatches later, so "now - t0"
            # would overstate it depth-fold)
            next_t0 = nxt[2] if nxt is not None else telemetry.clock_ns()
            dt = max(next_t0 - t0, 1)
            self.metrics.add("computing time for each node", dt)
            state["Loss"] = loss
            throughput = bsz / max(dt / 1e9, 1e-9)
            # bigdl.telemetry.logEveryN rate-limits the per-iteration log
            # line (default 1 = the reference protocol, unchanged); the
            # skipped path formats nothing
            if neval % log_every == 0:
                logger.info(
                    "[Epoch %d %d/%d][Iteration %d] Train %d in %.4f "
                    "seconds. Throughput is %.1f records/second. Loss is "
                    "%.6f.",
                    epoch, recs, epoch_size, neval, bsz, dt / 1e9,
                    throughput, loss)
            # divergence guard, host side: the in-step guard already kept
            # the params/slots/state carries at their pre-step values, so
            # a bad step costs one wasted iteration, not a poisoned model;
            # here we count consecutive bad steps and escalate to a
            # restore-from-snapshot once a transient numeric blip looks
            # like a genuinely diverged trajectory
            if not math.isfinite(loss):
                state["consecutiveBadSteps"] += 1
                # diagnosed divergence: the step recorded the index of
                # the first non-finite leaf on device; name it (the pull
                # is explicit, through the choke point, and happens only
                # on the already-slow bad-step path)
                culprit = ""
                if (integrity is not None and aux is not None
                        and "nf" in aux):
                    culprit = integrity.describe_nonfinite(
                        int(host_pull(aux["nf"],
                                      what="first non-finite leaf")))
                logger.warning(
                    "Non-finite loss/grads (%s) at iteration %d — update "
                    "skipped (%d consecutive bad step(s); restore after "
                    "%d)%s", loss, neval, state["consecutiveBadSteps"],
                    max_bad_steps, culprit)
                if 0 < max_bad_steps <= state["consecutiveBadSteps"]:
                    incident.record(
                        "optim/divergence", iteration=neval,
                        bad_steps=state["consecutiveBadSteps"])
                    raise DivergenceError(
                        f"{state['consecutiveBadSteps']} consecutive "
                        f"non-finite losses (last at iteration {neval}) — "
                        "restoring the latest valid snapshot"
                        f"{culprit}")
            else:
                state["consecutiveBadSteps"] = 0
            # training-state integrity: classify the fingerprint
            # verdicts at the configured cadence — cross-replica
            # disagreement / continuity breaks raise into the retry
            # loop, healthy verdicts feed the weight-health gates
            if (integrity is not None and aux is not None
                    and "cont" in aux and integrity.due(neval)):
                with telemetry.span("driver/integrity_check"):
                    integrity.check(aux, neval)
            # step-time decomposition: data-wait / compute / host-pull /
            # bookkeeping measured, the signed residual is unaccounted —
            # the five always sum to the wall interval exactly.  The wall
            # interval t0(i) -> t0(i+1) contains THIS iteration's dispatch
            # and bookkeeping but the NEXT iteration's fetch, so the
            # data-wait share comes from the next item's measured parts
            # (a stalled fetch lands on the same interval whose wall time
            # it inflated); the final flushed interval contains no fetch.
            data_ns = nxt[6][0] if nxt is not None else 0.0
            fired = step_account.account(
                dt, data_wait=data_ns, compute=parts[1], host_pull=pull_ns,
                bookkeeping=parts[2] + (telemetry.clock_ns() - t_book))
            if fired:
                telemetry.instant("driver/slow_step", iteration=neval,
                                  step_ms=round(dt / 1e6, 3))
                logger.warning(
                    "Slow step at iteration %d: %.1f ms (> %.1f ms = "
                    "k x EMA); %d anomaly window(s) this run", neval,
                    dt / 1e6, step_account.detector.threshold() / 1e6,
                    step_account.detector.fired)
                if slow_profile_dir:
                    # every process requests its own (process-local)
                    # profiler capture — like the scheduled window, one
                    # capture per host; only the timeline dump is a
                    # single-writer artifact
                    slow_req["due"] = True
                    if is_writer_process() and telemetry.tracing_enabled():
                        # bounded (bigdl.telemetry.maxTimelineDumps,
                        # oldest-first eviction) and disk-full-guarded: a
                        # flapping detector must not fill the disk with
                        # dump files, nor crash on one already full
                        _resource_storage.bounded_timeline_export(
                            os.path.join(
                                str(slow_profile_dir),
                                f"slowstep_{neval}_timeline.json"))
            with telemetry.span("driver/summary"):
                self._summarize_train(loss, throughput, neval)

        pipeline = DispatchPipeline(drain)
        flush_pending = pipeline.flush
        end_reads_loss = getattr(self.end_when, "reads_loss", False)

        # hung-step watchdog (bigdl.watchdog.stallFactor): a monitor
        # thread fed one heartbeat per iteration; a step whose OPEN
        # interval exceeds k x the completed-step EMA dumps the telemetry
        # timeline and aborts this thread with HungStepError so the retry
        # loop restores — instead of the job hanging forever.  Legitimate
        # long phases (publish/validation/checkpoint) run under paused().
        from contextlib import nullcontext
        from bigdl_tpu.utils import elastic as _elastic
        watchdog = _elastic.HungStepWatchdog.from_config()
        wd_pause = (watchdog.paused if watchdog is not None
                    else nullcontext)

        def should_end():
            if end_reads_loss:
                flush_pending()
            return self.end_when(state)

        # batch prefetch: fetch_batch ends in a host->device transfer —
        # run it ahead on a producer thread.  The
        # PRODUCER owns the dataset end to end: it counts records and
        # performs the epoch rollover + reshuffle at the boundary
        # (reference DistriOptimizer:333-344), so iterators and index
        # arrays are single-threaded and the batch sequence is
        # deterministic regardless of how far ahead the producer runs —
        # the consumer below tracks epochs independently for state/
        # logging from the same bsz stream, so the two stay in lockstep.
        # bigdl.prefetch.depth=0 restores synchronous fetching.
        from bigdl_tpu.engine import BatchPrefetcher
        fetched = {"records": 0}

        def on_batch(batch):
            fetched["records"] += batch[2]
            if fetched["records"] >= epoch_size:
                fetched["records"] = 0
                reset_epoch()

        # host-sync sanitizer (analysis pass 2): implicit device→host pulls
        # inside the fetch→step→dispatch region fail with their call-site
        # (strict) or log-once-and-count (warn).  The host-driven feval
        # path (LBFGS line search) pulls by design and is exempt.
        from bigdl_tpu.analysis.hostsync import NULL_GUARD, HostSyncGuard
        if getattr(self.optim_method, "requires_feval", False):
            hot_guard = NULL_GUARD
        else:
            hot_guard = HostSyncGuard.from_config()
        # bigdl.analysis.hotLoopScope: "iteration" sanitizes fetch+step,
        # "step" only the dispatch region (for exotic fetch transformers
        # that pull device values by design)
        scope = str(_config.get_property("bigdl.analysis.hotLoopScope",
                                         "iteration"))
        fetch_guard = hot_guard if scope == "iteration" else NULL_GUARD
        # per-run baseline: the global sync counter survives across runs
        # in one process; TrainSummary must chart THIS run's syncs
        if hot_guard.enabled:
            from bigdl_tpu.analysis.hostsync import STATS as _hs_stats
            self._hostsync_base = _hs_stats.snapshot()["implicit"]
        else:
            self._hostsync_base = None
        # the guard's hooks are thread-local: the producer thread runs the
        # actual fetch under bigdl.prefetch.depth > 0, so the prefetcher
        # arms the fetch guard AT the fetch call site (the in-loop arming
        # below covers only the synchronous depth=0 path and the dequeue)
        fetch = BatchPrefetcher(
            fetch_batch, on_batch=on_batch,
            guard=fetch_guard if fetch_guard.enabled else None)
        #: the AOT compile-warmup phase runs once, at the first iteration
        warmed = {"done": False}
        profiling = False
        profiled = False   # the window fires once, even across resumes

        def stop_profile():
            nonlocal profiling
            if profiling:
                profiling = False
                try:
                    # flush first so the traced iterations' device work
                    # (all dispatched asynchronously) completes inside the
                    # window...
                    flush_pending()
                finally:
                    # ...but a poisoned queue re-raising must STILL close
                    # the global profiler session, or the retry loop's
                    # next start_trace aborts on 'already running'
                    jax.profiler.stop_trace()
                logger.info("Profiler trace written to %s",
                            self._profile_dir)
                # the request is consumed: a SECOND optimize() on this
                # Optimizer must not silently re-capture into the same
                # log_dir and mix xplane artifacts — callers wanting
                # another window call set_trace_profile again
                self._profile_dir = None

        # started HERE, not at construction: everything between would-be
        # start and this try can raise, and only the finally below joins
        # the monitor — a retried setup failure must not leak a polling
        # thread per attempt
        if watchdog is not None:
            watchdog.start()
        try:
            while not should_end():
                # >= not ==: a run resumed past the start iteration still
                # captures (once) instead of silently skipping the window
                if (self._profile_dir and not profiled and
                        state["neval"] >= self._profile_start):
                    pdir = self._profile_dir
                    if jax.process_count() > 1:   # one capture per host
                        pdir = os.path.join(
                            pdir, f"process_{jax.process_index()}")
                    jax.profiler.start_trace(
                        pdir, profiler_options=_profiler_options())
                    profiling = profiled = True
                    profile_end = state["neval"] + self._profile_n
                if (slow_req["due"] and not slow_req["captured"] and
                        not profiling and not self._profile_dir):
                    # on-demand capture requested by the slow-step
                    # detector: one jax.profiler window over the next
                    # iteration (once per run; a user-scheduled
                    # set_trace_profile window always wins the session)
                    slow_req["due"] = False
                    slow_req["captured"] = True
                    pdir = os.path.join(str(slow_profile_dir),
                                        "slowstep_profile")
                    if jax.process_count() > 1:
                        pdir = os.path.join(
                            pdir, f"process_{jax.process_index()}")
                    self._profile_dir = pdir
                    jax.profiler.start_trace(
                        pdir, profiler_options=_profiler_options())
                    profiling = True
                    profile_end = state["neval"] + 1
                if watchdog is not None:
                    watchdog.heartbeat()
                # host-memory governor: one poll per iteration rolls up
                # every registered buffer account against the soft budget
                # (bigdl.resources.hostMemBudgetMB) and fires the
                # registered shrinkers edge-triggered under pressure
                _governor.poll()
                if _chaos.active():
                    # chaos harness step-level hooks: a simulated step
                    # failure raises here (the retry loop absorbs it), a
                    # preemption injection sets the elastic flag checked
                    # below, a stall blocks to exercise the watchdog, and
                    # a nan-loss injection flags this iteration's loss
                    inject_nan = _chaos.on_step(state["neval"])
                else:
                    inject_nan = False
                if _elastic.preemption_requested():
                    # graceful drain (SIGTERM/SIGINT via PreemptionHandler,
                    # or bigdl.chaos.preemptAt): finish the in-flight
                    # dispatches, publish the carries so the shells hold
                    # the newest weights, and unwind as Preempted — the
                    # retry loop commits the grace-period snapshot +
                    # resumable marker and exits instead of retrying.
                    # The counter is bumped HERE, not in the signal
                    # handler (registry locks are not signal-safe); the
                    # drain runs watchdog-paused — a long publish during
                    # the grace window is not a hung step.
                    telemetry.counter(
                        "Elastic/preemptions",
                        help="graceful-shutdown drains observed").inc()
                    with wd_pause():
                        flush_pending()
                        with telemetry.span("driver/publish"):
                            publish()
                    raise _elastic.Preempted(
                        f"preemption requested "
                        f"({_elastic.preemption_reason()}) — drained and "
                        f"published at iteration {state['neval']}")
                with fetch_guard.armed():
                    with telemetry.span("driver/fetch"):
                        t_data = telemetry.clock_ns()
                        inputs, targets, bsz = fetch()
                        data_wait_ns = telemetry.clock_ns() - t_data
                    self.metrics.add("get batch time", data_wait_ns)

                with hot_guard.armed():
                    self.optim_method.state["epoch"] = state["epoch"]
                    hyper = self.optim_method.hyper()
                    rng = (jax.random.PRNGKey(rng_counter) if stochastic else
                           jax.random.PRNGKey(0))
                    rng_counter += 1

                    if not warmed["done"]:
                        # AOT warmup: the fused step is compiled (or
                        # cache-loaded) HERE, supervised and spanned, so
                        # the dispatch below is a device step — never an
                        # unguarded 15-45 s implicit compile
                        warmed["done"] = True
                        self._warmup_compiles(inputs, targets, hyper, rng)
                    t0 = telemetry.clock_ns()
                    with telemetry.span("driver/device_step"):
                        out = run_step(inputs, targets, hyper, rng)
                        loss_dev, step_aux = (
                            out if isinstance(out, tuple) else (out, None))
                        dispatch_ns = telemetry.clock_ns() - t0
                    if inject_nan:
                        loss_dev = float("nan")
                    t_book = telemetry.clock_ns()
                    self.optim_method.step_done()
                    # decomposition parts measured at dispatch time; the
                    # drain adds its own host-pull/bookkeeping shares when
                    # the interval retires
                    parts = (data_wait_ns, dispatch_ns,
                             telemetry.clock_ns() - t_book)
                    pipeline.push(loss_dev, bsz, t0, state["epoch"],
                                  state["recordsProcessedThisEpoch"] + bsz,
                                  state["neval"], parts, step_aux)

                state["recordsProcessedThisEpoch"] += bsz

                # epoch accounting only — the rollover itself (reshuffle,
                # iterator reset) already happened on the producer at this
                # exact record boundary
                if state["recordsProcessedThisEpoch"] >= epoch_size:
                    state["epoch"] += 1
                    state["recordsProcessedThisEpoch"] = 0

                state["neval"] += 1
                if profiling and state["neval"] >= profile_end:
                    stop_profile()
                # keep the snapshot's epoch current across the rollover so
                # a resumed run continues at the right epoch
                self.optim_method.state["epoch"] = state["epoch"]

                v_due = self._validation_due(state)
                c_due = self._checkpoint_due(state)
                p_due = (self.train_summary is not None and
                         getattr(self.train_summary, "save_parameters_due",
                                 lambda s: False)(state))
                if v_due or c_due or p_due:
                    # a checkpoint or validation pass can legitimately
                    # dwarf a training step — not a stall
                    with wd_pause():
                        flush_pending()   # ordered log lines pre-validation
                        with telemetry.span("driver/publish"):
                            publish()
                        if v_due:
                            with telemetry.span("driver/validation"):
                                self._run_validation(state)
                        if c_due:
                            with telemetry.span("driver/checkpoint"):
                                self._run_checkpoint(state)
                        if p_due and is_writer_process():
                            # weight histograms (reference
                            # DistriOptimizer:426-456); the due-decision is
                            # shared (all processes publish), the write is
                            # not
                            with telemetry.span("driver/param_histograms"):
                                self.train_summary.save_parameters(
                                    self.model, state["neval"] - 1)
        finally:
            # the watchdog goes down FIRST: stop_profile()'s flush can
            # block for several EMAs of queued dispatches, and an armed
            # monitor would read that (or the post-loop flush/publish) as
            # a hung step and abort a COMPLETING run into a pointless
            # restore-and-retrain.  The trace must still close even if
            # the flush re-raises — an unterminated xplane capture is
            # unreadable — and the producer thread must stop regardless.
            try:
                if watchdog is not None:
                    watchdog.stop()
            finally:
                try:
                    stop_profile()
                finally:
                    fetch.stop()

        flush_pending()
        publish()
        # input-pipeline accounting: where the prefetch stages spent their
        # time (fetch vs blocking uploads device-resident), plus per-stage
        # ingest counters when a StreamingIngest engine fed the run — the
        # numbers that say whether a slow run was input-bound
        if fetch.batches:
            self.metrics.add("batch fetch time", fetch.fetch_ns)
            self.metrics.add("transfer block time", fetch.block_ns)
        from bigdl_tpu.dataset import ingest as _ingest
        for eng in sorted((e for e in _ingest._LIVE if e.has_active_run()),
                          key=lambda e: e.name):
            for stage, snap in eng.stats().items():
                logger.info(
                    "Ingest %s stage %s: %d items, %.1f/s, busy %.1fs, "
                    "starve %.1fs, backpressure %.1fs, workers %d",
                    eng.name, stage,
                    snap["items"], snap["throughput_per_sec"],
                    snap["busy_s"], snap["starve_s"],
                    snap["backpressure_s"],
                    eng.stage_workers.get(stage, 1))
            ups, downs = (eng.autoscale_events["up"],
                          eng.autoscale_events["down"])
            if ups or downs:
                logger.info(
                    "Ingest %s autoscaler: %d scale-up(s), %d "
                    "scale-down(s), final decode workers %d", eng.name,
                    ups, downs, eng.stage_workers["decode"])
            if eng.epoch_cache is not None:
                cache = eng.epoch_cache.stats()
                logger.info(
                    "Ingest %s epoch cache: %d hit(s), %d miss(es), "
                    "%d RAM + %d disk segment(s), %.1f MB RAM, "
                    "%d corrupt, %d evicted", eng.name, cache["hits"],
                    cache["misses"], cache["ram_segments"],
                    cache["disk_segments"], cache["ram_bytes"] / 2 ** 20,
                    cache["corrupt_segments"], cache["evicted_segments"])
        # where the step time went, one line (the full series is in the
        # Telemetry/* scalars and the telemetry.json snapshot)
        acct = step_account.summary()
        if acct.get("steps"):
            logger.info(
                "Step time decomposition over %d steps (mean %.1f ms): "
                "data-wait %.0f%%, compute %.0f%%, host-pull %.0f%%, "
                "bookkeeping %.0f%%, unaccounted %.0f%%; p50/p95/p99 "
                "%.1f/%.1f/%.1f ms; %d slow step(s)",
                acct["steps"], acct["mean_step_ms"],
                100 * acct["data_wait_frac"], 100 * acct["compute_frac"],
                100 * acct["host_pull_frac"],
                100 * acct["bookkeeping_frac"],
                100 * acct["unaccounted_frac"], acct.get("p50_ms", 0.0),
                acct.get("p95_ms", 0.0), acct.get("p99_ms", 0.0),
                acct["slow_steps"])
        self._export_telemetry(step_account)
        logger.info("Training finished in %.1f s.", time.time() - wall_start)
        return state

    def _export_telemetry(self, step_account) -> None:
        """End-of-run telemetry artifacts (writer process only): the
        Chrome trace timeline (``bigdl.telemetry.tracePath``) and the
        registry snapshot (``bigdl.telemetry.snapshotPath`` — a directory
        gets ``telemetry.json`` inside it)."""
        from bigdl_tpu.utils import config as _config
        if not is_writer_process():
            return
        # both exports run disk-full-guarded: a full disk disables the
        # artifact for the rest of the run with ONE structured warning
        # (Resources/storage_degraded) — it never fails the training run
        trace_path = _config.get_property("bigdl.telemetry.tracePath")
        if trace_path and telemetry.tracing_enabled():
            if _resource_storage.guarded_export(
                    "telemetry",
                    lambda: telemetry.export_chrome_trace(str(trace_path))):
                logger.info("Telemetry timeline written to %s", trace_path)
        snap_path = _config.get_property("bigdl.telemetry.snapshotPath")
        if snap_path:
            import json
            snap_path = str(snap_path)
            if os.path.isdir(snap_path):
                snap_path = os.path.join(snap_path, "telemetry.json")
            snap = telemetry.REGISTRY.snapshot()
            snap["step_summary"] = step_account.summary()

            def _write_snap():
                with open(snap_path, "w") as f:
                    json.dump(snap, f, indent=1, sort_keys=True)

            if _resource_storage.guarded_export("telemetry", _write_snap):
                logger.info("Telemetry snapshot written to %s", snap_path)

    def _check_symmetric_config(self) -> None:
        """Multi-host guard: the publish/validation sync points contain
        collectives, and whether they run is decided from per-process
        configuration.  A user who configures a checkpoint, summary, or
        validation on only SOME processes (a natural misreading of the
        single-writer discipline — the gating happens at write time, not
        at configuration time) would send the processes down different
        collective sequences and hang the job with no diagnostic.  Catch
        it up front with a host allgather of the configuration shape."""
        if jax.process_count() <= 1:
            return
        from jax.experimental import multihost_utils
        ts = self.train_summary
        has_param_hist = (ts is not None and
                          getattr(ts, "get_summary_trigger",
                                  lambda n: None)("Parameters") is not None)
        flags = np.array(
            [self.checkpoint is not None,
             ts is not None,
             has_param_hist,
             self.validation_trigger is not None,
             self.validation_summary is not None],
            dtype=np.int32)
        gathered = np.asarray(multihost_utils.process_allgather(flags))
        if not (gathered == flags[None, :]).all():
            raise ValueError(
                "training configuration differs across processes "
                f"(per-process [checkpoint, train_summary, param_histograms, "
                f"validation, validation_summary] flags:\n{gathered}) — "
                "every process must configure the same checkpoint/summary/"
                "validation setup; only the WRITES are limited to process 0 "
                "(bigdl_tpu.optim.optimizer.is_writer_process)")

    def _publish(self, params, slots, mstate) -> None:
        """Sync the jitted-loop carries back into the stateful shell so
        validation/checkpoint/users see current values."""
        self.model.params = params
        self.model.state = mstate
        if isinstance(self.model, Container):
            self.model._adopt()
        self.optim_method.set_slots(slots)

    def _validation_due(self, state) -> bool:
        return (self.validation_trigger is not None and
                self.validation_dataset is not None and
                self.validation_trigger(state))

    def _eval_mesh(self):
        """Mesh for sharded validation forwards; the distributed trainer
        overrides this with its training mesh."""
        return None

    def _run_validation(self, state) -> None:
        from bigdl_tpu.optim.evaluator import evaluate_dataset
        results = evaluate_dataset(self.model, self.validation_dataset,
                                   self.validation_methods,
                                   mesh=self._eval_mesh())
        for method, res in results:
            logger.info("%s is %s", method.name, res)
            state["score"] = res.final_result()
            self.optim_method.state["score"] = res.final_result()
            if self.validation_summary is not None and is_writer_process():
                self.validation_summary.add_scalar(
                    method.name, res.final_result(), state["neval"] - 1)

    def _checkpoint_due(self, state) -> bool:
        return self.checkpoint is not None and self.checkpoint.trigger(state)

    def _run_checkpoint(self, state) -> None:
        # every process reaches this point (the trigger decision is
        # shared), but only the writer touches the filesystem; the sync
        # afterwards keeps non-writers from racing ahead into a restore
        # (or a crash-retry) that would read a half-finished snapshot
        # set.  A save failure is inherently WRITER-ONLY — re-raising it
        # on rank 0 alone would send that rank into the retry loop's
        # restore barrier while its peers sit at this checkpoint sync,
        # mispairing the collectives — so the error is withheld until
        # after an allgathered failure flag lets EVERY rank raise
        # symmetrically and enter restore together.
        err: Optional[BaseException] = None
        if is_writer_process():
            try:
                self.checkpoint.save(self.model, self.optim_method,
                                     state["neval"] - 1,
                                     topology=self._topology_meta())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                err = e
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            flag = np.array([0 if err is None else 1], np.int32)
            # the allgather doubles as the checkpoint barrier
            gathered = np.asarray(multihost_utils.process_allgather(flag))
            if gathered.any():
                # EVERY rank must raise the SAME retryable class: the
                # writer re-raising the original (which may be a
                # non-retryable TypeError, e.g. an unpicklable model
                # attribute) while peers raise RuntimeError would kill
                # rank 0 instantly and hang the others at the restore
                # barrier.  A persistent save failure still dies cleanly
                # — symmetrically, once the retry budget is spent.
                raise RuntimeError(
                    "checkpoint write failed on the writer process "
                    "(rank 0) — restoring the latest valid snapshot on "
                    "every rank") from err
        if err is not None:
            raise err

    def _summarize_train(self, loss: float, throughput: float,
                         neval: int) -> None:
        if self.train_summary is None or not is_writer_process():
            return
        self.train_summary.add_scalar("Loss", loss, neval)
        self.train_summary.add_scalar("Throughput", throughput, neval)
        self.train_summary.add_scalar(
            "LearningRate", self.optim_method.get_learning_rate(), neval)
        # sanitizer counters route through the telemetry registry with
        # their historical tags: post-warmup retraces of the fused step
        # and implicit host syncs caught in the hot loop THIS RUN — a
        # healthy run charts both flat at zero.  Independent gates:
        # either pass can be off while the other still reports.
        if self._retrace_sentinel is not None:
            telemetry.gauge("Analysis/retraces", summary=True).set(
                self._retrace_sentinel.retraces)
        if getattr(self, "_hostsync_base", None) is not None:
            from bigdl_tpu.analysis.hostsync import STATS as _hs_stats
            telemetry.gauge("Analysis/implicit_host_syncs",
                            summary=True).set(
                _hs_stats.snapshot()["implicit"] - self._hostsync_base)
        # THE one emission loop: every summary-flagged registry metric
        # (Analysis/* above, the Telemetry/* decomposition gauges) plus
        # every registered provider (the streaming-ingest engine's
        # per-stage Ingest/* scalars) — one naming scheme, one flush path
        scalars = telemetry.summary_scalars()
        acct = self._step_account
        if acct is not None and acct.steps:
            scalars += acct.percentile_scalars()
        for tag, value in scalars:
            self.train_summary.add_scalar(tag, value, neval)

    # -- factory ----------------------------------------------------------

    @staticmethod
    def create(model: Module, dataset, criterion: Criterion,
               batch_size: Optional[int] = None) -> "Optimizer":
        """(reference ``Optimizer.apply:268``) — list/LocalDataSet →
        LocalOptimizer; ShardedDataSet → DistriOptimizer."""
        if isinstance(dataset, (list, tuple)):
            dataset = LocalDataSet(dataset)
        if batch_size is not None and not _yields_minibatches(dataset):
            from bigdl_tpu.dataset.transformer import SampleToMiniBatch
            pn = dataset.partition_num if isinstance(dataset, ShardedDataSet) else 1
            dataset = dataset.transform(SampleToMiniBatch(batch_size, pn))
        if isinstance(dataset, ShardedDataSet):
            try:
                from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
            except ImportError as e:
                raise NotImplementedError(
                    "the distributed trainer (bigdl_tpu.parallel."
                    "distri_optimizer) is not available in this build") from e
            return DistriOptimizer(model, dataset, criterion)
        return LocalOptimizer(model, dataset, criterion)


def _yields_minibatches(ds: AbstractDataSet) -> bool:
    from bigdl_tpu.dataset.transformer import ChainedTransformer, SampleToMiniBatch

    def has_batcher(t) -> bool:
        if isinstance(t, SampleToMiniBatch):
            return True
        if isinstance(t, ChainedTransformer):
            return any(has_batcher(s) for s in t.stages)
        return False

    ts = getattr(ds, "transformers", None)
    if ts is None and isinstance(ds, ShardedDataSet):
        # any local shard: every shard carries the same transformer chain
        ts = next(iter(ds.shards.values())).transformers
    return bool(ts) and any(has_batcher(t) for t in ts)


# shared state-key conventions (reference DistriOptimizer driverState)
def _initial_driver_state() -> Dict[str, Any]:
    return {"epoch": 1, "neval": 1, "Loss": None, "score": None,
            "recordsProcessedThisEpoch": 0, "consecutiveBadSteps": 0}


class LocalOptimizer(Optimizer):
    """Single-process trainer (reference ``optim/LocalOptimizer.scala:41``).

    One fused jitted step per iteration: forward, loss (+ regularizers),
    backward, and the optimizer's pure update all inside XLA.  Dynamic
    hyper-parameters (decayed lr, step count) enter as scalar arguments so
    the step never retraces.
    """

    def _build_step(self):
        model, criterion = self.model, self.criterion
        optim = self.optim_method
        if getattr(optim, "requires_feval", False):
            if self.precision is not None:
                raise ValueError(
                    f"{type(optim).__name__} uses the host-driven feval "
                    "path, which is fp32-only; unset set_precision")
            return self._build_feval_step()

        precision = self.precision
        aux_weight = self.moe_aux_weight
        from bigdl_tpu.utils import config
        from bigdl_tpu import integrity as _integrity
        from bigdl_tpu.resources import microbatch as _microbatch
        guard = config.get_bool("bigdl.divergence.guard", True)
        every_n = config.get_int("bigdl.integrity.everyN", 0)
        fp_seed = config.get_int("bigdl.integrity.seed",
                                 _integrity.DEFAULT_SEED)
        #: OOM re-plan: > 1 splits the batch into mb_k accumulation
        #: chunks inside ONE fused program (resources.microbatch)
        mb_k = max(1, int(self._microbatch_k))

        def _step_core(params, slots, mstate, inputs, targets, hyper, rng,
                       fpc=None, tick=None):
            def loss_fn(p):
                out, new_mstate = mixed_precision_forward(
                    model, p, inputs, mstate, precision, True, rng)
                with jax.named_scope("loss"):
                    loss = criterion.apply(out, targets)
                    loss = loss + regularization_penalty(model, p)
                    loss = loss + moe_aux_penalty(model, new_mstate,
                                                  aux_weight)
                return loss, new_mstate

            if mb_k > 1:
                # microbatch re-plan: k forward/backward passes over B/k
                # samples each, Kahan-compensated mean of (loss, grads,
                # state) — mean of equal-chunk means IS the full-batch
                # mean, so the numerics stay allclose to the full-batch
                # step while peak activation memory drops ~k-fold.  One
                # lax.scan keeps it a single fused program.
                def chunk_grads(xs):
                    cin, ctg = xs

                    def chunk_loss(p):
                        out, nm = mixed_precision_forward(
                            model, p, cin, mstate, precision, True, rng)
                        with jax.named_scope("loss"):
                            closs = criterion.apply(out, ctg)
                            closs = closs + regularization_penalty(model, p)
                            closs = closs + moe_aux_penalty(model, nm,
                                                            aux_weight)
                        return closs, nm

                    (closs, nm), cg = jax.value_and_grad(
                        chunk_loss, has_aux=True)(params)
                    return closs, cg, nm

                loss, grads, new_mstate = _microbatch.scan_mean(
                    chunk_grads, (inputs, targets), mb_k)
            else:
                (loss, new_mstate), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            with jax.named_scope("optimizer_update"):
                new_params, new_slots = optim.pure_update(grads, params,
                                                          slots, hyper)
            aux: Dict[str, Any] = {}
            ok = None
            if guard:
                # divergence guard: a non-finite loss/grad step keeps
                # every carry at its pre-step value.  The returned loss is
                # poisoned to NaN whenever the step was skipped — a
                # non-finite GRADIENT under a finite loss must still reach
                # the driver's bad-step counter, or a permanently
                # overflowing backward would freeze training silently.
                # ``nf`` names the first non-finite leaf for the driver's
                # diagnosed log line / DivergenceError.
                ok, nf = _integrity.first_nonfinite(loss, grads)
                aux["nf"] = nf
            if fpc is not None:
                # integrity: input fingerprints vs the previous step's
                # output carry — state that changed outside the fused
                # step is silent corruption; the verdict joins the
                # update-skip guard so a corrupt run FREEZES (restorable)
                # instead of training on rotten weights
                fp_p_in = _integrity.fingerprint_tree(params, fp_seed)
                fp_s_in = _integrity.fingerprint_tree(
                    slots, fp_seed + _integrity.SLOT_SEED_OFF)
                cont_ok, latch, bad_iter = _integrity.continuity_check(
                    fpc, fp_p_in, fp_s_in, tick)
                intact = latch == 0
                ok = intact if ok is None else jnp.logical_and(ok, intact)
            if ok is not None and ok is not True:
                new_params = select_tree(ok, new_params, params)
                new_slots = select_tree(ok, new_slots, slots)
                new_mstate = select_tree(ok, new_mstate, mstate)
            if guard:
                loss = jnp.where(aux["nf"] == _integrity.NF_SENTINEL,
                                 loss, jnp.nan)
            if fpc is not None:
                fp_p_out = _integrity.fingerprint_tree(new_params, fp_seed)
                fp_s_out = _integrity.fingerprint_tree(
                    new_slots, fp_seed + _integrity.SLOT_SEED_OFF)
                fp_g = _integrity.fingerprint_tree(
                    grads, fp_seed + _integrity.GRAD_SEED_OFF)
                aux.update(
                    cont=latch, bad_iter=bad_iter, fp_p=fp_p_out,
                    fp_s=fp_s_out, fp_g=fp_g,
                    pn=_integrity.sq_norm(new_params),
                    un=_integrity.sq_norm_diff(new_params, params),
                    gn=_integrity.sq_norm(grads),
                    fpc=_integrity.pack_carry(latch, bad_iter, fp_p_out,
                                              fp_s_out))
            return new_params, new_slots, new_mstate, loss, aux

        if every_n > 0:
            def step(params, slots, mstate, inputs, targets, hyper, rng,
                     fpc, tick):
                return _step_core(params, slots, mstate, inputs, targets,
                                  hyper, rng, fpc, tick)
        else:
            def step(params, slots, mstate, inputs, targets, hyper, rng):
                return _step_core(params, slots, mstate, inputs, targets,
                                  hyper, rng)

        from bigdl_tpu.analysis import program_contracts
        from bigdl_tpu.utils import compile_cache
        # the re-planned program gets its own label: same argument
        # signature, DIFFERENT traced body — it must never collide with
        # the full-batch executable in the compile cache
        label = "local" if mb_k == 1 else f"local_mb{mb_k}"
        return compile_cache.tracked_jit(
            step, label=label, topology=self._topology_meta(),
            contract=program_contracts.local_contract(precision),
            donate_argnums=(0, 1, 2))

    def _build_feval_step(self):
        """Host-driven step for multi-evaluation methods (LBFGS line
        search): one jitted loss+grad function, called repeatedly by the
        method's own inner loop.  Module state (BatchNorm statistics) is
        held fixed within a step — LBFGS is a full-batch method in the
        reference too (``optim/LBFGS.scala``)."""
        model, criterion = self.model, self.criterion
        optim = self.optim_method
        from bigdl_tpu.utils import compile_cache

        def _value_and_grad(params, mstate, inputs, targets, rng):
            def loss_fn(p):
                out, _ = model.apply(p, inputs, mstate, training=True,
                                     rng=rng)
                loss = criterion.apply(out, targets)
                return loss + regularization_penalty(model, p)
            return jax.value_and_grad(loss_fn)(params)

        from bigdl_tpu.analysis import program_contracts
        value_and_grad = compile_cache.tracked_jit(
            _value_and_grad, label="local_feval",
            topology=self._topology_meta(),
            contract=program_contracts.feval_contract())

        def step(params, slots, mstate, inputs, targets, hyper, rng):
            def feval(p):
                return value_and_grad(p, mstate, inputs, targets, rng)
            new_params, losses = optim.optimize(feval, params)
            return new_params, slots, mstate, losses[-1]

        return step

    def _optimize(self) -> Module:
        model = self.model
        model.training()
        model._ensure_init()

        carry = {"params": model.params, "mstate": model.state,
                 "slots": self.optim_method.slots(model.params)}
        self.optim_method.state.setdefault("epoch", 1)
        if self._step_fn is None:
            self._step_fn = self._arm_retrace(self._build_step(), "local")

        from bigdl_tpu.utils import config as _config
        from bigdl_tpu import integrity as _integrity
        feval = getattr(self.optim_method, "requires_feval", False)
        guard = _config.get_bool("bigdl.divergence.guard", True)
        every_n = 0 if feval else _config.get_int(
            "bigdl.integrity.everyN", 0)
        integ = None
        if not feval and (guard or every_n > 0):
            integ = _integrity.DriverIntegrity(
                "local",
                _integrity.nonfinite_names(
                    ("loss", 0.0), ("grad", carry["params"])),
                every_n=every_n,
                health=_integrity.WeightHealthMonitor(
                    _config.get_float("bigdl.integrity.healthFactor", 0.0),
                    warmup=_config.get_int(
                        "bigdl.integrity.healthWarmup", 5),
                    cooldown=_config.get_int(
                        "bigdl.integrity.healthCooldown", 50)))
        if every_n > 0:
            carry["fpc"] = jnp.asarray(_integrity.init_carry())

        it = {"data": None}

        def reset_epoch():
            self.dataset.shuffle()
            it["data"] = self.dataset.data(train=True)

        def fetch_batch():
            batch = next(it["data"])
            # the OOM re-plan picks its chunk count k against the
            # observed global batch (k must divide it)
            self._plan_batch_size = batch.size()
            return (_to_device(batch.get_input()),
                    _to_device(batch.get_target()), batch.size())

        def run_step(inputs, targets, hyper, rng):
            flip = _chaos.take_bitflip() if _chaos.active() else None
            if flip is not None:
                # injected SDC: one mantissa bit of a live parameter
                # flips between steps — all_finite cannot see it; the
                # continuity fingerprint must
                carry["params"] = _integrity.bitflip_tree(
                    carry["params"], flip)
            args = [carry["params"], carry["slots"], carry["mstate"],
                    inputs, targets, hyper, rng]
            if every_n > 0:
                tick = self.optim_method.state.get("evalCounter", 0) + 1
                args += [carry["fpc"], np.int32(tick)]
            out = self._step_fn(*args)
            if len(out) == 5:
                (carry["params"], carry["slots"], carry["mstate"],
                 loss, aux) = out
                if "fpc" in aux:
                    carry["fpc"] = aux["fpc"]
                return loss, aux
            (carry["params"], carry["slots"], carry["mstate"],
             loss) = out
            return loss

        # the fused step's full argument tuple, for the AOT compile
        # warmup (_warmup_compiles)
        def _cost_args(inputs, targets, hyper, rng):
            args = (carry["params"], carry["slots"], carry["mstate"],
                    inputs, targets, hyper, rng)
            if every_n > 0:
                args += (carry["fpc"], np.int32(1))
            return args
        self._cost_args_fn = _cost_args

        def publish():
            self._publish(carry["params"], carry["slots"], carry["mstate"])

        self._sync_dataset_epoch()
        reset_epoch()
        self._drive(fetch_batch, run_step, reset_epoch, publish,
                    epoch_size=_epoch_records(self.dataset),
                    integrity=integ)
        return model


def _epoch_records(ds: AbstractDataSet) -> int:
    """Records per epoch, before batching transformers."""
    return ds.size()


