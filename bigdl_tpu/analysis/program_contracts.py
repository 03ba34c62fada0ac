"""Per-step program contracts: what the compiled HLO is ALLOWED to do.

Every fused step family (``local`` / ``local_feval`` / ``shard_map`` /
``gspmd`` / ``pipeline`` / ``eval``) declares a :class:`StepContract` at
construction time and passes it through ``compile_cache.tracked_jit``;
the HLO auditor (:mod:`bigdl_tpu.analysis.hlo_audit`) checks every
lowered program against it at compile (or cache warm-load) time.  A
contract is the program-level counterpart of PR 4's module contracts:
instead of "this layer takes rank-4 float inputs" it says "this step
performs exactly one reduce-scatter over the gradient vector and one
all-gather over the parameter vector, computes in bf16, and nothing
else crosses the interconnect".

The collective vocabulary is the StableHLO one: ``all-reduce`` (psum /
pmean / pmin / pmax all lower here), ``all-gather``, ``reduce-scatter``
(psum_scatter), ``all-to-all`` (MoE expert dispatch), and
``collective-permute`` (ppermute rings — pipeline stages, ring
attention).  An op kind the contract does not declare, or a declared
kind whose aggregate traffic exceeds its byte budget, is a
:class:`ProgramContractViolation` naming the HLO op, its shapes, and
the owning step.

The canonical per-family builders at the bottom are what the trainers
call — each computes its byte bounds from the live model (flat
parameter bytes, module-state bytes), so the budget tightens with the
model instead of being a loose global constant.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: the StableHLO collective vocabulary the auditor extracts
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute",
                    "collective-broadcast")

#: headroom added to computed byte budgets: scalar all-reduces (loss
#: pmean, divergence-verdict pmin) and padding round-off ride under it
SCALAR_SLACK_BYTES = 4096


class ProgramContractError(ValueError):
    """A compiled step violated its program contract (strict mode).
    ``violations`` carries the structured findings."""

    def __init__(self, message: str, violations=None):
        super().__init__(message)
        self.violations = list(violations or [])


@dataclass(frozen=True)
class ProgramContractViolation:
    """One structured audit finding.

    ``step``: the owning fused-step label; ``pass_name``: which audit
    family flagged it (``collective`` / ``precision`` / ``memory``);
    ``op``: the HLO op (``stablehlo.all_gather``, ``stablehlo.
    dot_general``, ...); ``detail``: shapes, byte counts, and the
    violated bound."""

    step: str
    pass_name: str
    op: str
    detail: str

    def __str__(self):
        return (f"[audit/{self.pass_name}] step '{self.step}': {self.op} "
                f"— {self.detail}")


@dataclass(frozen=True)
class CollectiveBound:
    """Budget for one collective kind inside one step's program.

    ``max_ops``: static op-count ceiling (None = any number — e.g. a
    ppermute ring whose op count is a schedule detail); ``min_ops``:
    op-count FLOOR (None = no floor) — a program with fewer ops of the
    kind than declared is as broken as one with more: the bucketed
    ZeRO-1 schedule promises one reduce-scatter and one all-gather PER
    BUCKET, and a silently dropped bucket collective means a parameter
    range trains on unreduced gradients; ``max_bytes``: aggregate
    traffic ceiling over all ops of the kind, where one op's traffic is
    max(operand bytes, result bytes) (None = unbounded); ``reason``:
    why the step legitimately performs this collective — printed with
    violations so the reader sees what WAS declared."""

    kind: str
    max_ops: Optional[int] = None
    max_bytes: Optional[int] = None
    reason: str = ""
    min_ops: Optional[int] = None

    def __post_init__(self):
        if self.kind not in COLLECTIVE_KINDS:
            raise ValueError(
                f"unknown collective kind {self.kind!r} "
                f"(one of {COLLECTIVE_KINDS})")


@dataclass(frozen=True)
class StepContract:
    """The declared program envelope for one fused step family.

    ``collectives``: every collective kind the program may contain,
    each with its budget — a kind absent here is a violation outright.
    ``activation_dtype``: the declared compute precision (``"bf16"`` or
    None = fp32); under bf16 an f32 ``dot_general``/``convolution`` is
    precision drift.  ``max_rank4_transposes``: layout budget — rank-4
    transposes beyond it (boundary NCHW<->NHWC flips are expected, a
    growing interior census is a regressing layout) are a violation;
    None leaves the census uncapped (still exported as a metric)."""

    label: str
    collectives: Tuple[CollectiveBound, ...] = ()
    activation_dtype: Optional[str] = None
    max_rank4_transposes: Optional[int] = None

    def bound_for(self, kind: str) -> Optional[CollectiveBound]:
        for b in self.collectives:
            if b.kind == kind:
                return b
        return None


# ---- registry ---------------------------------------------------------------

#: label -> the most recently declared contract (latest wins: tests build
#: several trainers per process and the audit runs at compile time,
#: immediately after the owning declaration)
_REGISTRY: Dict[str, StepContract] = {}


def declare(contract: StepContract) -> StepContract:
    """Register ``contract`` for its label and return it (what
    ``tracked_jit(..., contract=...)`` calls)."""
    _REGISTRY[contract.label] = contract
    return contract


def lookup(label: str) -> Optional[StepContract]:
    """The live contract declared for ``label`` this process, else the
    canonical default for a known family, else None."""
    c = _REGISTRY.get(label)
    if c is not None:
        return c
    return default_contracts().get(label)


def reset() -> None:
    """Drop live declarations (test isolation)."""
    _REGISTRY.clear()


# ---- canonical per-family builders ------------------------------------------


def local_contract(precision: Optional[str] = None) -> StepContract:
    """Single-process fused train step: everything on one device, no
    interconnect traffic at all."""
    return StepContract(label="local", collectives=(),
                        activation_dtype=precision)


def feval_contract() -> StepContract:
    """Host-driven loss+grad function (LBFGS line search): local and
    fp32-only by construction."""
    return StepContract(label="local_feval", collectives=())


def shard_map_contract(precision: Optional[str], param_bytes: int,
                       state_bytes: int, *, seq_axis: bool = False,
                       expert_axis: bool = False,
                       n_buckets: int = 1,
                       integrity: bool = False) -> StepContract:
    """The ZeRO-1 data-parallel shard_map step: exactly ``n_buckets``
    reduce-scatters over the summed gradient vector, exactly
    ``n_buckets`` all-gathers reassembling the updated weights (the
    latency-hiding overlap schedule partitions the flat vector into
    contiguous buckets; the monolithic baseline is ``n_buckets=1``), and
    a small all-reduce family (loss pmean, module-state pmean per float
    leaf, the divergence-verdict pmin).  The byte budgets do NOT scale
    with ``n_buckets`` — the buckets partition the same vector, so
    aggregate wire traffic is invariant under the bucket count.  The op
    counts are exact both ways (``min_ops == max_ops``): a dropped
    bucket collective means a parameter range silently trains on
    unreduced gradients.  A ``seq``/``expert`` axis adds one full
    gradient psum per extra axis (all-reduce bytes) plus the ring /
    all-to-all exchange the wired layers perform inside the step.

    ``integrity=True`` declares the training-state integrity traffic
    (``bigdl.integrity.everyN`` > 0): exactly ONE extra all-gather — the
    cross-replica fingerprint table exchange
    (``all_reduce.gather_fingerprints``) — plus a few scalar all-reduces
    (the sharded grad-norm psum, the widened verdict pmin) that ride
    under the existing scalar slack.  Declared, not leaked: a
    fingerprint collective the contract does not cover is exactly the
    drift the auditor exists to catch."""
    extra_axes = int(seq_axis) + int(expert_axis)
    fp_gathers = 1 if integrity else 0
    bounds: List[CollectiveBound] = [
        CollectiveBound(
            "reduce-scatter", max_ops=n_buckets, min_ops=n_buckets,
            max_bytes=param_bytes,
            reason="per-bucket gradient sum + shard-scatter "
                   "(arp.reduce_scatter_gradients / "
                   "arp.reduce_scatter_bucket)"),
        CollectiveBound(
            "all-gather", max_ops=n_buckets + fp_gathers,
            min_ops=n_buckets + fp_gathers,
            max_bytes=param_bytes + (SCALAR_SLACK_BYTES if integrity
                                     else 0),
            reason="per-bucket updated-weight reassembly "
                   "(arp.all_gather_weights / arp.all_gather_bucket)"
                   + (" + integrity fingerprint table "
                      "(all_reduce.gather_fingerprints)" if integrity
                      else "")),
        CollectiveBound(
            "all-reduce", max_ops=None,
            # the mstate pmean repeats once per mesh axis the step
            # reduces over (data + each extra axis), the full-gradient
            # psum once per EXTRA axis only
            max_bytes=(state_bytes * (1 + extra_axes) + SCALAR_SLACK_BYTES +
                       param_bytes * extra_axes),
            reason="loss/module-state pmean + divergence pmin"
                   + (" + per-extra-axis gradient psum" if extra_axes
                      else "")),
    ]
    if seq_axis:
        bounds.append(CollectiveBound(
            "collective-permute", reason="ring attention k/v rotation "
                                         "over the seq axis"))
    if expert_axis:
        bounds.append(CollectiveBound(
            "all-to-all", reason="MoE expert dispatch/return over the "
                                 "expert axis"))
    return StepContract(label="shard_map", collectives=tuple(bounds),
                        activation_dtype=precision)


def gspmd_contract(precision: Optional[str] = None) -> StepContract:
    """The dp x tp GSPMD step: the traced program is collective-free —
    gradient all-reduces and tensor-parallel exchanges are inserted by
    XLA's partitioner AFTER StableHLO, so any explicit collective in the
    lowered text is a hand-written stray."""
    return StepContract(label="gspmd", collectives=(),
                        activation_dtype=precision)


def pipeline_contract() -> StepContract:
    """The GPipe step: activations (and their cotangents, in the
    backward the autodiff transpose inserts) rotate around the stage
    ring with collective-permute.  The backward ALSO carries all-reduce:
    the autodiff transpose of values replicated across the stage axis
    (the microbatch input fan-out, the scalar loss) psums their
    cotangents over the ring — empirically 2 activation-sized psums plus
    the scalar loss reduction, a schedule detail whose size tracks the
    microbatch, so the bound declares the kind without a byte cap."""
    return StepContract(label="pipeline", collectives=(
        CollectiveBound("collective-permute",
                        reason="stage-ring activation (and cotangent) "
                               "rotation"),
        CollectiveBound("all-reduce",
                        reason="autodiff-transpose psum of stage-"
                               "replicated values (microbatch cotangents, "
                               "scalar loss)"),))


def eval_contract(sharded: bool = False) -> StepContract:
    """The eval/predict forward: collective-free as traced (the sharded
    variant replicates its output through the GSPMD partitioner, not
    through explicit collectives)."""
    return StepContract(label="eval_sharded" if sharded else "eval",
                        collectives=())


def lm_prefill_contract() -> StepContract:
    """The LM serving prefill step: a single-sequence causal forward
    plus KV-pool scatter, all on one device — collective-free."""
    return StepContract(label="lm_prefill", collectives=())


def lm_decode_contract(label: str = "lm_decode") -> StepContract:
    """The LM serving decode step (``lm_decode`` full-precision /
    ``lm_decode_int8`` quantized-weight tier): one fixed-shape
    batched token step over the paged KV cache, single-device —
    collective-free.  The int8 tier computes its matmuls as
    dequantized f32 contractions (convert + dot), so the precision
    pass's f64 / f32-in-bf16 drift checks apply unchanged — this
    contract is what the quantization gate audits against."""
    return StepContract(label=label, collectives=())


def lm_full_contract() -> StepContract:
    """The LM serving full-forward step (sequential baseline + the
    decode-parity reference): one causal forward, no cache writes,
    collective-free."""
    return StepContract(label="lm_full", collectives=())


def lm_kv_scrub_contract() -> StepContract:
    """The paged KV cache's block scrub: a fixed-shape zero scatter
    into both pools, single-device — collective-free."""
    return StepContract(label="lm_kv_scrub", collectives=())


def default_contracts() -> Dict[str, StepContract]:
    """Canonical contracts for every known family — what the OFFLINE
    auditor (``python -m bigdl_tpu.analysis.hlo_audit <cacheDir>``)
    checks persisted cache entries against when no live trainer has
    declared byte bounds: kind membership is model-independent, byte
    budgets are not, so the defaults declare kinds with unbounded
    bytes."""
    unbounded = dict(max_ops=None, max_bytes=None)
    return {
        "local": local_contract(),
        "local_feval": feval_contract(),
        "shard_map": StepContract(label="shard_map", collectives=(
            CollectiveBound("reduce-scatter", **unbounded),
            CollectiveBound("all-gather", **unbounded),
            CollectiveBound("all-reduce", **unbounded),
            CollectiveBound("collective-permute", **unbounded),
            CollectiveBound("all-to-all", **unbounded),
        )),
        "gspmd": gspmd_contract(),
        "pipeline": pipeline_contract(),
        "eval": eval_contract(False),
        "eval_sharded": eval_contract(True),
        "lm_prefill": lm_prefill_contract(),
        "lm_decode": lm_decode_contract("lm_decode"),
        "lm_decode_int8": lm_decode_contract("lm_decode_int8"),
        "lm_full": lm_full_contract(),
        "lm_kv_scrub": lm_kv_scrub_contract(),
    }
