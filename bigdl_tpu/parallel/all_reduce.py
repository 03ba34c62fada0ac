"""AllReduceParameter: the TPU-native parameter-synchronization backend.

Reference equivalent: ``parameters/AllReduceParameter.scala:67`` — the model's
flattened parameter vector is sliced into ``partitionNum`` chunks; gradients
are exchanged as fp16-compressed blocks through Spark's BlockManager in a
reduce-scatter → sharded-optimizer-update → all-gather cycle.

TPU-native redesign: the whole pull-based block exchange collapses into two
XLA collectives over ICI —

- ``lax.psum_scatter(flat_grads, 'data', tiled=True)``  = reduce-scatter
  (each device ends up owning the summed gradient for its 1/N slice);
- ``lax.all_gather(new_shard, 'data', tiled=True)``     = weight all-gather.

The optimizer update between them runs on each device's shard only — the
reference's partition-sharded update (ZeRO-1, ``optim/DistriOptimizer.scala:
265-280``) expressed under ``shard_map``.  fp16 wire compression
(``parameters/FP16CompressedTensor.scala:30-90``) maps to an optional bf16
cast on the gradient just before the reduce-scatter.

This class owns the host-side geometry: ravel/unravel of the parameter
pytree, zero-padding so the flat length divides the shard count, and the
collective helpers used inside the sharded step.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree


def shard_map(f, mesh, in_specs, out_specs, check_rep=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


Params = Any


def replicate_tree(mesh):
    """One jitted identity pinned replicated over ``mesh`` — the publish-time
    regather every trainer uses to turn sharded carries back into
    host-readable arrays (the reference's getModel pull,
    ``optim/DistriOptimizer.scala:818``).  All processes must call it
    together: XLA lowers the resharding to collectives."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    # a resharding identity, not a fused step: XLA lowers it to one
    # all-gather with no compute worth caching
    return jax.jit(  # lint: allow(untracked-jit)
        lambda t: t, out_shardings=NamedSharding(mesh, P()))


def gather_to_host(tree, mesh):
    """Replicate each leaf over ``mesh`` and fetch it to host numpy ONE
    LEAF AT A TIME.

    Used for publishing optimizer slots: a whole-tree replicated gather
    would transiently materialize the complete slot set on every device —
    for Adam that is 2x the parameter bytes on top of the live sharded
    carries, exactly the allocation ZeRO-1 sharding exists to avoid.
    Per-leaf gathering bounds the transient device footprint to the
    largest single leaf, and the result lands host-side where checkpoint
    serialization (which converts to numpy anyway) wants it.  Collective:
    every process must participate."""
    import numpy as np
    gather = replicate_tree(mesh)

    def one(leaf):
        # the replicated intermediate goes out of scope immediately after
        # the host copy, so at most one leaf is replicated at a time
        return np.asarray(gather(leaf))

    return jax.tree_util.tree_map(one, tree)


class AllReduceParameter:
    """Flat-vector geometry + collectives for one parameter pytree."""

    def __init__(self, params: Params, n_shards: int,
                 compression: Optional[str] = None):
        flat, unravel = ravel_pytree(params)
        if flat.size == 0:
            raise ValueError("model has no trainable parameters")
        self.size = int(flat.size)
        self.dtype = flat.dtype
        self.n_shards = n_shards
        self.padded_size = -(-self.size // n_shards) * n_shards
        self.shard_size = self.padded_size // n_shards
        self._unravel = unravel
        if compression not in (None, "bf16"):
            raise ValueError(f"unknown compression {compression!r} "
                             "(only 'bf16' is supported on TPU)")
        self.compression = compression

    # ---- host/trace-side geometry --------------------------------------

    def flatten(self, tree: Params) -> jnp.ndarray:
        """Pytree -> zero-padded flat vector (works inside jit)."""
        flat, _ = ravel_pytree(tree)
        pad = self.padded_size - self.size
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return flat

    def unflatten(self, flat: jnp.ndarray) -> Params:
        """Padded flat vector -> pytree (works inside jit)."""
        return self._unravel(flat[:self.size])

    # ---- collectives (call inside shard_map over ``axis``) --------------

    def reduce_scatter_gradients(self, flat_grads: jnp.ndarray,
                                 axis: str) -> jnp.ndarray:
        """Sum gradients across the axis; each device keeps its own slice
        (reference ``putGradients`` + ``aggregateGradientPartition``)."""
        if self.compression == "bf16":
            flat_grads = flat_grads.astype(jnp.bfloat16)
        shard = lax.psum_scatter(flat_grads, axis, scatter_dimension=0,
                                 tiled=True)
        return shard.astype(self.dtype)

    def local_shard(self, flat: jnp.ndarray, axis: str) -> jnp.ndarray:
        """This device's slice of a replicated flat vector."""
        idx = lax.axis_index(axis)
        return lax.dynamic_slice(flat, (idx * self.shard_size,),
                                 (self.shard_size,))

    def all_gather_weights(self, shard: jnp.ndarray, axis: str) -> jnp.ndarray:
        """Reassemble the full flat vector from per-device shards
        (reference ``getWeights`` / ``sendWeightPartition``)."""
        return lax.all_gather(shard, axis, tiled=True)

    # ---- bucketed collectives (the latency-hiding overlap schedule) ------
    #
    # The padded flat vector is a logical (n_shards, shard_size) matrix:
    # row i is device i's ZeRO-1 slice.  A bucket is a contiguous COLUMN
    # range [a, b) of that matrix — so bucket k of every device's shard
    # lines up, per-bucket reduce-scatter/all-gather over the column block
    # is element-identical to the monolithic collective (same per-element
    # reduction order, same placement), and summed over buckets the wire
    # bytes are exactly the monolithic param_bytes.  N independent
    # RS->update->AG chains is what lets XLA's latency-hiding scheduler
    # overlap bucket k's collective with bucket k+1's compute.

    def bucket_edges(self, n_buckets: int):
        """~Equal contiguous [start, stop) column ranges over
        ``shard_size``.  Clamped to at most one column per bucket; the
        rounding spreads a non-divisible remainder one column at a time
        (every column appears in exactly one bucket)."""
        n = max(1, min(int(n_buckets), self.shard_size))
        edges = [round(i * self.shard_size / n) for i in range(n + 1)]
        return [(a, b) for a, b in zip(edges, edges[1:]) if b > a]

    def reduce_scatter_bucket(self, columns: jnp.ndarray,
                              axis: str) -> jnp.ndarray:
        """Reduce-scatter one column block.  ``columns``: the
        (n_shards, b-a) slice of the local gradient matrix view; returns
        this device's summed (b-a,) piece of it."""
        if self.compression == "bf16":
            columns = columns.astype(jnp.bfloat16)
        shard = lax.psum_scatter(columns, axis, scatter_dimension=0,
                                 tiled=True)
        return shard.reshape(-1).astype(self.dtype)

    def all_gather_bucket(self, bucket_shard: jnp.ndarray,
                          axis: str) -> jnp.ndarray:
        """Gather one updated column block back from every device:
        (b-a,) per device -> the (n_shards, b-a) column block of the new
        flat matrix view."""
        return lax.all_gather(bucket_shard, axis, tiled=False)


# ---- declared-contract collective helpers -----------------------------------
#
# Every collective a trainer STEP BODY performs goes through this module
# (or :class:`AllReduceParameter` above): each helper corresponds to a
# collective kind the step's program contract declares, so the HLO
# auditor's census and the source are reconcilable by grep.  The
# ``undeclared-collective`` lint rule flags raw ``lax.psum``/``pmean``/
# ``pmin``/``ppermute``/``all_gather``/``all_to_all`` calls in trainer
# step constructors — route them here instead.


def axis_sum(tree, axis: str):
    """psum over ``axis`` → one all-reduce per leaf (gradient
    contributions summed over a seq/expert axis)."""
    return lax.psum(tree, axis)


def axis_mean(tree, axis: str):
    """pmean over ``axis`` → all-reduce (loss averaging)."""
    return lax.pmean(tree, axis)


def axis_min(tree, axis: str):
    """pmin over ``axis`` → all-reduce (the global divergence verdict:
    every shard must agree to apply or skip a step)."""
    return lax.pmin(tree, axis)


def ring_permute(x, axis: str, perm):
    """ppermute over ``axis`` → collective-permute (pipeline stage ring,
    ring-attention rotation)."""
    return lax.ppermute(x, axis, perm)


def gather_fingerprints(fp, axis: str):
    """all_gather (tiled=False) over ``axis`` → every replica receives
    the full (n_replicas, k) table of per-replica integrity fingerprints
    — the cross-replica agreement verdict is then computable locally on
    each replica with no further collective."""
    return lax.all_gather(fp, axis, tiled=False)


def pmean_floats(tree, axis: str):
    """Average float leaves across the axis (keeps BatchNorm running
    stats consistent between replicas); non-float leaves pass through
    (they evolve identically on every shard)."""
    def f(x):
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            return lax.pmean(x, axis)
        return x
    return jax.tree_util.tree_map(f, tree)
