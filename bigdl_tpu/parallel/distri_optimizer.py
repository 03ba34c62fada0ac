"""DistriOptimizer: the distributed synchronous-SGD trainer.

Reference equivalent: ``optim/DistriOptimizer.scala:89-330`` — per-iteration:
weight all-gather, per-partition forward/backward, gradient scatter,
partition-sharded optimizer update, weight republish — over Spark's
BlockManager (``parameters/AllReduceParameter.scala:67-295``).

TPU-native redesign: the whole per-iteration exchange is ONE jitted
``shard_map`` over ``Engine.default_mesh()``'s ``data`` axis:

    per-shard forward/backward  (local minibatch, replicated params)
    → ``psum_scatter``          gradient reduce-scatter over ICI
    → sharded optimizer update  (each device updates its 1/N parameter slice
                                 and owns 1/N of the optimizer slots: ZeRO-1,
                                 the reference's partition-sharded update)
    → ``all_gather``            weight reassembly

There are no per-iteration host round-trips: params stay device-resident as
one replicated flat vector, slots stay sharded across the mesh, and the
driver only reads back the scalar loss.  fp16 wire compression maps to an
optional bf16 cast on the reduce-scatter (``compression='bf16'``).

Multi-host: under ``Engine.init_distributed`` every host process runs this
same driver loop (multi-controller SPMD).  Each process feeds ONLY the
data partitions its mesh positions own (:func:`local_data_partitions`;
the dataset is constructed per process with
``ShardedDataSet(..., local_partitions=...)``) and the global batch is
assembled with ``jax.make_array_from_process_local_data`` — the
reference's executor-local partition caching + locality zip
(``ZippedPartitionsWithLocalityRDD.scala:28-56``) without a driver-side
materialization.  Proven by ``tests/test_multihost.py`` (2 OS processes x
4 virtual devices == the single-process 8-device run).

Straggler mitigation (reference ``:192-216,302-330``) is structurally N/A:
XLA collectives over ICI are bulk-synchronous with no partial participation;
the API knob on :class:`Optimizer` is kept inert for parity.

Real-data ingest: feed the dataset through
:class:`~bigdl_tpu.dataset.ingest.StreamingIngest` (the stage-pipelined
decode/assemble engine) and the driver's ``Engine.BatchPrefetcher``
transfer-ahead stage keeps ``bigdl.ingest.batchesInFlight`` uploads in
flight — ``fetch_batch`` issues the ``make_array_from_process_local_data``
transfer, the transfer thread blocks it device-resident while the next
fetch's upload is already on the link, and the step consumes only
pre-transferred batches.  Epoch rollover/reshuffle stays owned by the
fetch producer and the ingest engine commits RNG draws on consumption, so
the pipelining changes latency, never the batch sequence.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu import integrity as _integrity
from bigdl_tpu.engine import Engine
from bigdl_tpu.dataset.dataset import ShardedDataSet
from bigdl_tpu.nn.module import Criterion, Module
from bigdl_tpu.optim.optimizer import (Optimizer, all_finite,
                                       mixed_precision_forward,
                                       moe_aux_penalty,
                                       regularization_penalty, select_tree)
from bigdl_tpu.parallel.all_reduce import (AllReduceParameter, axis_mean,
                                           axis_min, axis_sum,
                                           gather_fingerprints, pmean_floats)
from bigdl_tpu.utils import chaos as _chaos

logger = logging.getLogger("bigdl_tpu")


def _owned_coords_per_axis(mesh: Mesh):
    """{axis_name: sorted owned coordinates} for this process's devices,
    plus the owned-position count (for rectangularity checks)."""
    pid = jax.process_index()
    devs = np.asarray(mesh.devices)
    owned = [tuple(int(c) for c in coord)
             for coord in np.ndindex(devs.shape)
             if devs[coord].process_index == pid]
    per_axis = {a: sorted({c[i] for c in owned})
                for i, a in enumerate(mesh.axis_names)}
    return per_axis, len(owned)


def local_data_partitions(mesh: Mesh, axis: str = "data"):
    """Data-axis partition ids whose devices this process can address.

    In a multi-host job (``Engine.init_distributed``) each process owns
    ``jax.local_devices()``; the data-axis coordinate of each owned mesh
    position names a dataset partition this process must feed — the
    reference's partition→node locality (one Spark partition cached on
    the executor that trains it, ``ZippedPartitionsWithLocalityRDD.scala``
    + ``AllReduceParameter.scala:87-92`` rank-from-partition-id).
    Single-process this is simply ``range(axis_size)``."""
    return _owned_coords_per_axis(mesh)[0][axis]


def _local_axis_chunks(mesh: Mesh, axis: str):
    """Sorted owned coordinates along ``axis``, with a rectangularity
    check: per-process batch assembly slices the global batch as (owned
    data rows) x (owned seq columns), which is only well-defined when the
    owned device set is that cartesian product."""
    per_axis, n_owned = _owned_coords_per_axis(mesh)
    expect = 1
    for a in mesh.axis_names:
        expect *= len(per_axis[a])
    if n_owned != expect:
        raise ValueError(
            f"this process's mesh positions are not rectangular over axes "
            f"{mesh.axis_names} — per-process batch feeding cannot slice "
            "the global batch; arrange the mesh so each process owns a "
            "full block")
    return per_axis[axis]


def map_over_slots(optim_method, fn, slots, per_param_tree):
    """Apply ``fn(slot_leaf_tree_element, per_param_element)`` across
    every slot family (Adam's m/v, momentum's v, …): slot pytrees are
    {family: params-shaped tree}, so the per-parameter spec tree is
    zipped against each family's subtree.  Shared by the GSPMD dp x tp
    step and the pipeline trainer's ZeRO-1 slot placement."""
    outer = jax.tree_util.tree_structure(
        optim_method.init_slots(jnp.zeros(())))
    subtrees = outer.flatten_up_to(slots)
    return jax.tree_util.tree_unflatten(
        outer,
        [jax.tree_util.tree_map(fn, st, per_param_tree)
         for st in subtrees])


# the BatchNorm-state averaging helper now lives with the other declared
# collectives in all_reduce.py (pmean_floats); this alias keeps the old
# import path working
_pmean_float = pmean_floats


def _leaf_bucket_groups(params, n_buckets: int):
    """Partition the parameter leaves (flatten order) into at most
    ``n_buckets`` contiguous, size-balanced index groups — the GSPMD
    counterpart of :meth:`AllReduceParameter.bucket_edges`, operating on
    whole leaves because the partitioner owns each leaf's sharding.  A
    group closes once its leaves reach the next even-split boundary of
    the total element count."""
    leaves = jax.tree_util.tree_leaves(params)
    sizes = [int(np.prod(np.shape(x))) for x in leaves]
    total = sum(sizes)
    n = max(1, min(int(n_buckets), len(leaves)))
    groups, cur, acc = [], [], 0
    for i, s in enumerate(sizes):
        cur.append(i)
        acc += s
        if len(groups) < n - 1 and acc >= (len(groups) + 1) * total / n:
            groups.append(cur)
            cur = []
    if cur:
        groups.append(cur)
    return groups


def _bucketed_leaf_update(optim_method, groups, grads, params, slots, hyper):
    """Run the optimizer update as one independent chain per leaf group.
    Each group's gradient leaves pass through a ``lax.optimization_barrier``
    so XLA treats the group as its own scheduling unit (its
    partitioner-inserted gradient reductions can overlap other groups'
    update compute); the update itself is the same elementwise
    ``pure_update`` on the group's sub-pytree, so numerics are identical
    to the whole-tree call."""
    p_leaves, pdef = jax.tree_util.tree_flatten(params)
    g_leaves = pdef.flatten_up_to(grads)
    outer = jax.tree_util.tree_structure(
        optim_method.init_slots(jnp.zeros(())))
    fam_leaves = [pdef.flatten_up_to(f) for f in outer.flatten_up_to(slots)]
    new_p = [None] * len(p_leaves)
    new_f = [[None] * len(p_leaves) for _ in fam_leaves]
    for idxs in groups:
        gg = list(lax.optimization_barrier(
            tuple(g_leaves[i] for i in idxs)))
        sg = jax.tree_util.tree_unflatten(
            outer, [[fl[i] for i in idxs] for fl in fam_leaves])
        pp, ss = optim_method.pure_update(
            gg, [p_leaves[i] for i in idxs], sg, hyper)
        ss_f = outer.flatten_up_to(ss)
        for j, i in enumerate(idxs):
            new_p[i] = pp[j]
            for fi in range(len(fam_leaves)):
                new_f[fi][i] = ss_f[fi][j]
    new_params = jax.tree_util.tree_unflatten(pdef, new_p)
    new_slots = jax.tree_util.tree_unflatten(
        outer, [jax.tree_util.tree_unflatten(pdef, nf) for nf in new_f])
    return new_params, new_slots


class DistriOptimizer(Optimizer):
    """Data-parallel trainer over a device mesh
    (reference ``optim/DistriOptimizer.scala:689``).

    ``dataset`` must be a :class:`ShardedDataSet` whose ``partition_num``
    equals the mesh's ``data``-axis size (the reference enforces
    partition == node at ``DistriOptimizer.scala:492-494``).
    """

    def __init__(self, model: Module, dataset: ShardedDataSet,
                 criterion: Criterion, mesh: Optional[Mesh] = None,
                 compression: Optional[str] = None):
        super().__init__(model, dataset, criterion)
        self._mesh = mesh
        self.compression = compression
        self._arp: Optional[AllReduceParameter] = None

    @property
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = Engine.default_mesh()
        return self._mesh

    def set_mesh(self, mesh: Mesh) -> "DistriOptimizer":
        self._mesh = mesh
        self._step_fn = None
        return self

    def _topology_meta(self):
        """Saving topology for snapshot manifests: the mesh axes, the
        ZeRO-1 slot axis, and which fused step owns the layout — what a
        restore onto a different device count needs in order to reshard
        (or to refuse with the mismatch named)."""
        from bigdl_tpu.utils import elastic
        return elastic.describe_topology(
            self.mesh, step="gspmd" if self.model_axis else "shard_map",
            slot_axis="data")

    # ---- the fused sharded step ----------------------------------------

    @property
    def seq_axis(self) -> Optional[str]:
        """Sequence-parallel axis: present when the mesh declares a ``seq``
        dimension (the long-context dp x sp layout)."""
        return "seq" if "seq" in self.mesh.shape else None

    @property
    def expert_axis(self) -> Optional[str]:
        """Expert-parallel axis: present when the mesh declares an
        ``expert`` dimension (the dp x ep MoE layout — tokens co-shard
        over it, MixtureOfExperts layers dispatch with all_to_all)."""
        return "expert" if "expert" in self.mesh.shape else None

    @property
    def model_axis(self) -> Optional[str]:
        """Tensor-parallel axis: present when the mesh declares a
        ``model`` dimension (the dp x tp Megatron layout — handled by the
        GSPMD step, not the collective shard_map step)."""
        return "model" if "model" in self.mesh.shape else None

    def _build_step(self, arp: AllReduceParameter):
        from bigdl_tpu.parallel.all_reduce import shard_map

        model, criterion, optim = self.model, self.criterion, self.optim_method
        mesh, axis = self.mesh, "data"
        seq_axis = self.seq_axis
        expert_axis = self.expert_axis
        n = (mesh.shape[axis] *
             (mesh.shape[seq_axis] if seq_axis else 1) *
             (mesh.shape[expert_axis] if expert_axis else 1))

        precision = self.precision
        aux_weight = self.moe_aux_weight
        from bigdl_tpu.utils import config
        guard = config.get_bool("bigdl.divergence.guard", True)
        every_n = config.get_int("bigdl.integrity.everyN", 0)
        fp_seed = config.get_int("bigdl.integrity.seed",
                                 _integrity.DEFAULT_SEED)
        # chaos: in-step replica desync — at tick ``desync_at`` replica
        # ``desync_rep``'s updated parameter copy drifts AFTER the update
        # and BEFORE the output fingerprint (build-time constants; (0, 0)
        # = disarmed, and tick 0 never occurs)
        desync_at, desync_rep = _chaos.desync_replica()
        # audit fault injection: duplicate the weight all-gather so the
        # step's program breaks its declared all-gather op ceiling
        extra_ag = config.get_bool("bigdl.chaos.extraAllGather", False)
        # the latency-hiding overlap schedule: the ZeRO-1 exchange runs as
        # N independent per-bucket reduce-scatter -> update -> all-gather
        # chains (same wire bytes, element-identical numerics) so XLA's
        # scheduler can overlap bucket k's collective with bucket k±1's
        # compute; bigdl.parallel.overlap=false keeps the monolithic
        # baseline program
        overlap = config.get_bool("bigdl.parallel.overlap", True)
        edges = (arp.bucket_edges(
                     config.get_int("bigdl.parallel.overlapBuckets", 4))
                 if overlap else [(0, arp.shard_size)])
        # audit fault injection: bucket k's reduce-scatter silently
        # replaced by the device's own unreduced rows — the
        # missing-per-bucket-collective case the auditor's min_ops floor
        # exists to catch
        drop_bucket = config.get_property("bigdl.chaos.dropBucketCollective",
                                          None)
        drop_bucket = (int(drop_bucket) % len(edges)
                       if drop_bucket not in (None, "", False) else None)
        from bigdl_tpu import telemetry
        telemetry.REGISTRY.gauge(
            "Parallel/overlap_buckets", summary=True,
            help="per-step collective buckets (1 = monolithic schedule)"
        ).set(float(len(edges)))

        def shard_step(flat_params, slots, mstate, inputs, targets, hyper,
                       rng, fpc=None, tick=None):
            # distinct dropout masks per shard, like the reference's
            # independently-seeded model replicas
            rng = jax.random.fold_in(rng, lax.axis_index(axis))
            for extra in (seq_axis, expert_axis):
                if extra:
                    rng = jax.random.fold_in(rng, lax.axis_index(extra))

            def loss_fn(flat):
                p = arp.unflatten(flat)
                out, new_mstate = mixed_precision_forward(
                    model, p, inputs, mstate, precision, True, rng)
                with jax.named_scope("loss"):
                    loss = criterion.apply(out, targets)
                    loss = loss + regularization_penalty(model, p)
                    loss = loss + moe_aux_penalty(model, new_mstate,
                                                  aux_weight)
                return loss, new_mstate

            (loss, new_mstate), flat_grads = jax.value_and_grad(
                loss_fn, has_aux=True)(flat_params)

            if seq_axis:
                # sequence shards each saw a chunk of every sequence: their
                # gradient contributions sum (ring attention's backward is
                # already chunk-local)
                flat_grads = axis_sum(flat_grads, seq_axis)
            if expert_axis:
                # expert shards saw disjoint tokens AND ran disjoint expert
                # blocks: contributions sum over the axis
                flat_grads = axis_sum(flat_grads, expert_axis)
            aux = {}
            intact = None
            if fpc is not None:
                # training-state integrity, per replica: fingerprint the
                # INPUT copies — each device hashes its OWN HBM copy of
                # the replicated parameter vector and its own ZeRO-1 slot
                # shard — all-gather the parameter fingerprints into the
                # agreement table, and check continuity against this
                # replica's carry row from the previous step.  The
                # combined verdict latches and freezes the update below,
                # so a corrupted replica can never contaminate healthy
                # state: the run freezes (restorable/healable) instead of
                # training on rotten weights.
                fpc_row = fpc[0]
                fp_p_in = _integrity.fingerprint_flat(flat_params, fp_seed)
                fp_s_in = _integrity.fingerprint_tree(
                    slots, fp_seed + _integrity.SLOT_SEED_OFF)
                fps_table = gather_fingerprints(fp_p_in, axis)
                agree_ok = jnp.all(fps_table == fps_table[0])
                cont_ok, latch, bad_iter = _integrity.continuity_check(
                    fpc_row, fp_p_in, fp_s_in, tick, extra_ok=agree_ok)
                # the freeze verdict must be GLOBAL (pmin over every mesh
                # axis): one latched replica freezing alone would
                # silently fork the model
                intact = axis_min((latch == 0).astype(jnp.int32), axis)
                for extra in (seq_axis, expert_axis):
                    if extra:
                        intact = axis_min(intact, extra)
                intact = intact.astype(bool)
            if overlap:
                # bucketed schedule: the padded flat vector viewed as an
                # (n_shards, shard_size) matrix, each bucket a contiguous
                # column range — per bucket, reduce-scatter its columns,
                # update this device's piece, and all-gather it back.
                # The chains share no data flow until the divergence
                # verdict, so the scheduler is free to run bucket k's
                # collective under bucket k±1's update compute; summed
                # over buckets the wire bytes equal the monolithic
                # schedule and every element sees the same reduction
                # order (parity is exact, not approximate).
                gmat = flat_grads.reshape(arp.n_shards, arp.shard_size)
                param_row = arp.local_shard(flat_params, axis)
                grad_b, new_p, new_s = [], [], []
                for k, (a, b) in enumerate(edges):
                    if drop_bucket == k:
                        # chaos: this bucket's collective is GONE — the
                        # device's own unreduced gradient rows stand in
                        g_k = jnp.take(gmat[:, a:b], lax.axis_index(axis),
                                       axis=0).astype(arp.dtype) / n
                    else:
                        g_k = arp.reduce_scatter_bucket(gmat[:, a:b],
                                                        axis) / n
                    s_k = jax.tree_util.tree_map(lambda v: v[a:b], slots)
                    with jax.named_scope("optimizer_update"):
                        p_k, ns_k = optim.pure_update(
                            g_k, param_row[a:b], s_k, hyper)
                    grad_b.append(g_k)
                    new_p.append(p_k)
                    new_s.append(ns_k)
                okf = None
                if guard:
                    # the verdict stays GLOBAL over the whole vector: all
                    # buckets' gradients feed one pmin (the one sync point
                    # the baseline schedule has too).  The pmin is widened
                    # to a stacked [ok, nf] pair so the first-non-finite
                    # leaf index rides the same collective for the
                    # driver's diagnosed divergence line.
                    okf, nf = _integrity.first_nonfinite(loss, *grad_b)
                    verdict = axis_min(
                        jnp.stack([okf.astype(jnp.int32), nf]), axis)
                    for extra in (seq_axis, expert_axis):
                        if extra:
                            verdict = axis_min(verdict, extra)
                    okf, nf = verdict[0].astype(bool), verdict[1]
                    aux["nf"] = nf
                ok = okf
                if intact is not None:
                    ok = (intact if ok is None
                          else jnp.logical_and(ok, intact))
                if ok is not None:
                    new_p = [select_tree(ok, p_k, param_row[a:b])
                             for p_k, (a, b) in zip(new_p, edges)]
                    new_s = [select_tree(
                                 ok, s_k,
                                 jax.tree_util.tree_map(
                                     lambda v, a=a, b=b: v[a:b], slots))
                             for s_k, (a, b) in zip(new_s, edges)]
                    new_mstate = select_tree(ok, new_mstate, mstate)
                if guard:
                    # only the FINITENESS verdict poisons the loss (an
                    # integrity freeze reports through aux, not NaN)
                    loss = jnp.where(okf, loss, jnp.nan)
                if fpc is not None:
                    g_sq = _integrity.sq_norm(grad_b)
                # per-bucket gathers: each depends only on its own
                # bucket's selected shard (plus the shared verdict)
                blocks = [arp.all_gather_bucket(p_k, axis) for p_k in new_p]
                if extra_ag:
                    blocks[0] = (blocks[0] + arp.all_gather_bucket(
                        new_p[0], axis)) / 2
                new_flat = jnp.concatenate(blocks, axis=1).reshape(-1)
                new_slots = (jax.tree_util.tree_map(
                                 lambda *xs: jnp.concatenate(xs), *new_s)
                             if jax.tree_util.tree_leaves(slots)
                             else slots)
            else:
                # monolithic baseline: one reduce-scatter, one update,
                # one all-gather
                grad_shard = arp.reduce_scatter_gradients(flat_grads,
                                                          axis) / n
                # ZeRO-1: update only this device's parameter slice + slots
                param_shard = arp.local_shard(flat_params, axis)
                with jax.named_scope("optimizer_update"):
                    new_shard, new_slots = optim.pure_update(
                        grad_shard, param_shard, slots, hyper)
                okf = None
                if guard:
                    # divergence guard: non-finite loss/grad → every shard
                    # keeps its pre-step slice.  The verdict must be GLOBAL
                    # (pmin over the data axis): each device only sees 1/N
                    # of the gradient vector, and replicas applying
                    # different verdicts would silently fork the model.
                    # The pmin is widened to a stacked [ok, nf] pair so
                    # the first-non-finite leaf index rides the same
                    # collective for the diagnosed divergence line.
                    okf, nf = _integrity.first_nonfinite(loss, grad_shard)
                    verdict = axis_min(
                        jnp.stack([okf.astype(jnp.int32), nf]), axis)
                    for extra in (seq_axis, expert_axis):
                        if extra:   # seq/expert replicas must agree too
                            verdict = axis_min(verdict, extra)
                    okf, nf = verdict[0].astype(bool), verdict[1]
                    aux["nf"] = nf
                ok = okf
                if intact is not None:
                    ok = (intact if ok is None
                          else jnp.logical_and(ok, intact))
                if ok is not None:
                    new_shard = select_tree(ok, new_shard, param_shard)
                    new_slots = select_tree(ok, new_slots, slots)
                    new_mstate = select_tree(ok, new_mstate, mstate)
                if guard:
                    # a skipped step must report non-finite to the
                    # driver's bad-step counter even when only the GRADS
                    # overflowed; an integrity freeze does NOT poison the
                    # loss — its verdict reaches the driver through aux
                    loss = jnp.where(okf, loss, jnp.nan)
                if fpc is not None:
                    g_sq = _integrity.sq_norm(grad_shard)
                # all-gather the updated weights for the next forward
                new_flat = arp.all_gather_weights(new_shard, axis)
                if extra_ag:
                    # the redundant gather returns the identical vector,
                    # so (x + x) / 2 is bit-exact — but the program now
                    # carries a second all-gather for the auditor to catch
                    new_flat = (new_flat
                                + arp.all_gather_weights(new_shard,
                                                         axis)) / 2

            if fpc is not None:
                # a frozen step must keep each replica's INPUT copy
                # bit-for-bit: the all-gather above rebuilds every copy
                # from per-shard contributions, which would wash a
                # diverged copy back into agreement (or spread its
                # corrupted rows to every replica) and destroy the
                # evidence the heal's majority vote needs
                if ok is not None:
                    new_flat = select_tree(ok, new_flat, flat_params)
                if desync_at:
                    # chaos: the injected replica stays SELF-consistent
                    # (its output fingerprint hashes the drifted copy),
                    # so only the next step's agreement table can see it
                    inj = jnp.logical_and(
                        jnp.asarray(tick) == desync_at,
                        lax.axis_index(axis) == desync_rep)
                    new_flat = new_flat.at[0].add(
                        jnp.where(inj, jnp.asarray(1.0, new_flat.dtype),
                                  jnp.asarray(0.0, new_flat.dtype)))
                fp_p_out = _integrity.fingerprint_flat(new_flat, fp_seed)
                fp_s_out = _integrity.fingerprint_tree(
                    new_slots, fp_seed + _integrity.SLOT_SEED_OFF)
                accd = _integrity.acc_dtype()
                new_row = arp.local_shard(new_flat, axis)
                old_row = arp.local_shard(flat_params, axis)
                pb = jnp.stack([
                    jnp.sum(jnp.square(new_row[a:b].astype(accd)))
                    for a, b in edges])
                ub = jnp.stack([
                    jnp.sum(jnp.square(new_row[a:b].astype(accd)
                                       - old_row[a:b].astype(accd)))
                    for a, b in edges])
                # ONE psum carries every diagnostic scalar — per-bucket
                # param/update norms plus the gradient norm (the shards
                # partition the vector, so the axis sum IS the global
                # square norm)
                nb = len(edges)
                stats = axis_sum(
                    jnp.concatenate([pb, ub, g_sq[None]]), axis)
                aux.update(
                    cont=jnp.logical_not(intact).astype(jnp.int32),
                    bad_iter=-axis_min(-bad_iter, axis),
                    fps_all=fps_table,
                    pn=jnp.sum(stats[:nb]), un=jnp.sum(stats[nb:2 * nb]),
                    gn=stats[2 * nb], pb=stats[:nb],
                    ub=stats[nb:2 * nb],
                    fpc=_integrity.pack_carry(latch, bad_iter, fp_p_out,
                                              fp_s_out)[None, :])
            loss = axis_mean(loss, axis)
            new_mstate = pmean_floats(new_mstate, axis)
            for extra in (seq_axis, expert_axis):
                if extra:
                    loss = axis_mean(loss, extra)
                    new_mstate = pmean_floats(new_mstate, extra)
            if guard or every_n > 0:
                return new_flat, new_slots, new_mstate, loss, aux
            return new_flat, new_slots, new_mstate, loss

        pspec_rep = P()
        # batch over data (co-sharded with expert when present); with a
        # seq axis, time (dim 1) over seq
        dim0 = (axis, expert_axis) if expert_axis else axis
        pspec_batch = P(dim0, seq_axis) if seq_axis else P(dim0)
        # slots are sharded over the data axis only (ZeRO-1); replicated
        # across seq/expert shards
        pspec_slots = P(axis)
        in_specs = (pspec_rep,                          # flat params
                    pspec_slots,                        # slot shards
                    pspec_rep,                          # module state
                    pspec_batch, pspec_batch,           # inputs, targets
                    pspec_rep, pspec_rep)               # hyper, rng
        # the diagnostics aux rides replicated (the verdicts and the
        # gathered fingerprint table are identical on every device after
        # their reductions); the integrity carry keeps one row per data
        # replica
        aux_specs = {}
        if guard:
            aux_specs["nf"] = pspec_rep
        if every_n > 0:
            in_specs += (pspec_slots, pspec_rep)        # fpc rows, tick
            aux_specs.update(
                cont=pspec_rep, bad_iter=pspec_rep, fps_all=pspec_rep,
                pn=pspec_rep, un=pspec_rep, gn=pspec_rep, pb=pspec_rep,
                ub=pspec_rep, fpc=pspec_slots)
        out_specs = (pspec_rep, pspec_slots, pspec_rep, pspec_rep)
        if aux_specs:
            out_specs += (aux_specs,)
        sharded = shard_map(
            shard_step, mesh=mesh,
            in_specs=in_specs, out_specs=out_specs,
            check_rep=False)
        # verdict index space of the widened guard pmin, for the
        # driver's diagnosed divergence suffix
        self._nf_names = (["loss"]
                          + [f"grad:flat[{a}:{b})" for a, b in edges])
        from bigdl_tpu.analysis import program_contracts
        from bigdl_tpu.utils import compile_cache
        # byte budgets from the live model: the padded flat parameter
        # vector bounds the reduce-scatter/all-gather wire, the float
        # module-state leaves (BatchNorm stats, MoE diagnostics) bound
        # the mstate pmean all-reduces
        param_bytes = arp.padded_size * jnp.dtype(arp.dtype).itemsize
        state_bytes = sum(
            x.size * jnp.dtype(x.dtype).itemsize
            for x in map(jnp.asarray,
                         jax.tree_util.tree_leaves(model.state))
            if jnp.issubdtype(x.dtype, jnp.floating))
        contract = program_contracts.shard_map_contract(
            precision, param_bytes, state_bytes,
            seq_axis=bool(seq_axis), expert_axis=bool(expert_axis),
            n_buckets=len(edges), integrity=every_n > 0)
        return compile_cache.tracked_jit(sharded, label="shard_map",
                                         topology=self._topology_meta(),
                                         contract=contract,
                                         donate_argnums=(0, 1, 2))

    # ---- driver loop ----------------------------------------------------

    def _optimize(self) -> Module:
        model, mesh = self.model, self.mesh
        axis_size = mesh.shape["data"]
        if self.dataset.partition_num != axis_size:
            raise ValueError(
                f"dataset has {self.dataset.partition_num} partitions but the "
                f"mesh 'data' axis has {axis_size} devices — they must match "
                "(reference DistriOptimizer.scala:492)")

        model.training()
        model._ensure_init()
        if self.model_axis:
            if self.seq_axis or self.expert_axis:
                raise ValueError(
                    "the GSPMD tensor-parallel step composes with 'data' "
                    "only — a mesh mixing 'model' with 'seq'/'expert' is "
                    "not supported")
            if self.compression:
                # NOT silently ignorable: on the GSPMD path the gradient
                # all-reduces are inserted by XLA's partitioner, which
                # accumulates and reduces in f32 even for bf16 compute
                # (verified from compiled HLO: f32 all-reduce(dot) then
                # convert) — there is no program point "before the psum"
                # to cast at.  The explicit shard_map dp step is where
                # the wire dtype is controllable.
                raise ValueError(
                    "compression='bf16' controls the explicit reduce-"
                    "scatter wire of the data-parallel shard_map step; "
                    "on a tensor-parallel ('model') mesh the gradient "
                    "collectives are XLA-partitioner-inserted and their "
                    "wire dtype is not controllable — drop compression "
                    "for this mesh (set_precision('bf16') already keeps "
                    "activations/backward matmuls in bf16)")
            return self._optimize_gspmd()
        if self.seq_axis:
            self._wire_sequence_parallel(model)
        if self.expert_axis:
            self._wire_expert_parallel(model)

        arp = AllReduceParameter(model.params, axis_size, self.compression)
        self._arp = arp
        # a resumed run re-partitions the restored CANONICAL host slots
        # for THIS mesh: _flat_slots re-ravels and re-pads each family
        # for the current shard count, and the device_put places the new
        # 1/N shards — the topology-elastic reshard (timed when resuming;
        # a fresh run's zeros take the identical path untimed)
        from bigdl_tpu.utils import elastic
        slot_shards = elastic.place_slots(
            lambda: jax.device_put(self._flat_slots(arp),
                                   NamedSharding(mesh, P("data"))),
            self._consume_elastic_resumed())
        carry = {
            "flat": jax.device_put(arp.flatten(model.params),
                                   NamedSharding(mesh, P())),
            # slots live sharded across the mesh: each device owns 1/N (ZeRO-1)
            "slots": slot_shards,
            "mstate": jax.device_put(model.state, NamedSharding(mesh, P())),
        }
        self.optim_method.state.setdefault("epoch", 1)

        if self._step_fn is None:
            self._step_fn = self._arm_retrace(self._build_step(arp),
                                              "shard_map")

        from bigdl_tpu.utils import config as _config
        guard = _config.get_bool("bigdl.divergence.guard", True)
        every_n = _config.get_int("bigdl.integrity.everyN", 0)
        integ = None
        if guard or every_n > 0:
            integ = _integrity.DriverIntegrity(
                "shard_map",
                getattr(self, "_nf_names", ["loss", "grad:flat"]),
                every_n=every_n,
                health=_integrity.WeightHealthMonitor(
                    _config.get_float("bigdl.integrity.healthFactor", 0.0),
                    warmup=_config.get_int(
                        "bigdl.integrity.healthWarmup", 5),
                    cooldown=_config.get_int(
                        "bigdl.integrity.healthCooldown", 50)))
        if every_n > 0:
            # one carry row per data replica (seen/latch/bad_iter + the
            # previous step's params/slots output fingerprints)
            carry["fpc"] = jax.device_put(
                np.stack([_integrity.init_carry()] * axis_size),
                NamedSharding(mesh, P("data")))

        # batch dim co-shards over expert when present (tokens follow the
        # all_to_all dispatch axis); time (dim 1) over seq
        dim0 = ("data", "expert") if self.expert_axis else "data"
        if self.seq_axis:
            # time (dim 1) sharded over seq: per-timestep targets required
            batch_sharding = NamedSharding(mesh, P(dim0, "seq"))
            seq_size = mesh.shape["seq"]

            max_seq = getattr(self, "_max_seq_len", None)

            def _check(x):
                x = np.asarray(x)
                if x.ndim < 2 or x.shape[1] % seq_size != 0:
                    raise ValueError(
                        "sequence-parallel training needs (N, T, ...) inputs "
                        "and (N, T, ...) per-timestep targets with T "
                        f"divisible by the seq axis size {seq_size} "
                        f"(got shape {x.shape})")
                if max_seq is not None and x.shape[1] > max_seq:
                    raise ValueError(
                        f"sequence length {x.shape[1]} exceeds a module's "
                        f"position capacity {max_seq} — sharded offsets "
                        "would silently clamp; raise max_len")
                return x
        else:
            batch_sharding = NamedSharding(mesh, P(dim0))
            _check = None
        # per-process shard feeding: this process pulls ONLY the partitions
        # its mesh positions own (single-process: all of them) and the
        # global batch is assembled from every process's local block
        local_ids = local_data_partitions(mesh)
        missing = [p for p in local_ids
                   if p not in getattr(self.dataset, "local_partitions",
                                       local_ids)]
        if missing:
            raise ValueError(
                f"this process's mesh positions own data partitions "
                f"{missing} but the dataset does not hold them locally — "
                f"construct ShardedDataSet(..., local_partitions="
                f"{local_ids}) on this process")
        seq_chunks = (_local_axis_chunks(mesh, "seq") if self.seq_axis
                      else None)
        expert_chunks = (_local_axis_chunks(mesh, "expert")
                         if self.expert_axis else None)
        it = {"shards": None}

        def reset_epoch():
            self.dataset.shuffle()
            it["shards"] = {p: self.dataset.shard_data(p, train=True)
                            for p in local_ids}

        def fetch_batch():
            return _global_batch(it["shards"], batch_sharding, mesh,
                                 self.dataset.partition_num,
                                 seq_chunks=seq_chunks,
                                 expert_chunks=expert_chunks, check=_check)

        def run_step(inputs, targets, hyper, rng):
            flip = _chaos.take_bitflip() if _chaos.active() else None
            if flip is not None:
                # injected SDC: one replica's HBM copy of the replicated
                # parameter vector flips a mid-mantissa bit between steps
                # — the logical array still looks healthy and every value
                # stays finite; only fingerprint agreement can see it
                carry["flat"] = _integrity.bitflip_one_replica(
                    carry["flat"], flip)
            args = [carry["flat"], carry["slots"], carry["mstate"],
                    inputs, targets, hyper, rng]
            if every_n > 0:
                tick = self.optim_method.state.get("evalCounter", 0) + 1
                args += [carry["fpc"], np.int32(tick)]
            out = self._step_fn(*args)
            if len(out) == 5:
                (carry["flat"], carry["slots"], carry["mstate"],
                 loss, aux) = out
                if "fpc" in aux:
                    carry["fpc"] = aux["fpc"]
                return loss, aux
            (carry["flat"], carry["slots"], carry["mstate"], loss) = out
            return loss

        # the fused sharded step's argument tuple, for the AOT compile
        # warmup (Optimizer._warmup_compiles)
        def _cost_args(inputs, targets, hyper, rng):
            args = (carry["flat"], carry["slots"], carry["mstate"],
                    inputs, targets, hyper, rng)
            if every_n > 0:
                args += (carry["fpc"], np.int32(1))
            return args
        self._cost_args_fn = _cost_args

        def publish():
            # slots leave the device in the same per-parameter pytree format
            # every host-side consumer (checkpoint resume, OptimMethod.update,
            # a later LocalOptimizer) expects.  Single-process the unflatten
            # runs lazily on device (serialization fetches leaves only when a
            # checkpoint actually pickles them — no publish-time
            # transfers).  Multi-host the ZeRO shards mostly live on
            # devices this process cannot address, so each flat slot vector
            # is regathered and fetched host-side one at a time
            # (``gather_to_host`` bounds the transient device footprint to
            # one vector); every process joins the collective, only the
            # writer process later serializes.
            self._sharded_slots = carry["slots"]
            if jax.process_count() > 1:
                from bigdl_tpu.parallel.all_reduce import gather_to_host
                host_flat = gather_to_host(carry["slots"], mesh)
                unflat_slots = jax.tree_util.tree_map(
                    lambda v: jax.tree_util.tree_map(
                        np.asarray, arp.unflatten(jnp.asarray(v))),
                    host_flat)
            else:
                unflat_slots = jax.tree_util.tree_map(arp.unflatten,
                                                      carry["slots"])
            self._publish(arp.unflatten(carry["flat"]), unflat_slots,
                          carry["mstate"])

        self._sync_dataset_epoch()
        reset_epoch()
        try:
            self._drive(fetch_batch, run_step, reset_epoch, publish,
                        epoch_size=self.dataset.size(), integrity=integ)
        except _integrity.ReplicaDesyncError as e:
            # heal in place from the agreeing majority, then re-raise:
            # the retry loop sees ``healed`` and re-enters training
            # without a checkpoint restore
            self._heal_desync(e, carry, mesh)
            raise
        return model

    def _heal_desync(self, err, carry, mesh) -> None:
        """Self-heal a data-parallel replica desync: re-broadcast the
        agreeing majority's parameter copy as the canonical state,
        rewind the eval counter to just before the first frozen tick
        (the corrupted replica applied no updates — the in-step verdict
        froze every replica the moment the copies diverged, so the
        majority copy IS the last healthy state), publish, and mark the
        error healed.  The ZeRO-1 slot shards never diverged (each
        device owns disjoint rows, verified per-shard by continuity) and
        are re-placed for the mesh via ``elastic.place_slots`` on
        re-entry."""
        import time
        from bigdl_tpu import telemetry
        from bigdl_tpu.analysis.hostsync import host_pull
        t0 = time.monotonic()
        minority, _ = _integrity.replicated_shard_disagreement(
            carry["flat"], what="desync heal majority vote")
        shards = sorted(carry["flat"].addressable_shards,
                        key=lambda s: s.device.id)
        major = next(i for i in range(len(shards)) if i not in minority)
        canonical = np.asarray(host_pull(
            shards[major].data, what="desync heal canonical copy"))
        carry["flat"] = jax.device_put(canonical,
                                       NamedSharding(mesh, P()))
        self.optim_method.state["evalCounter"] = max(err.iteration - 1, 0)
        # publish the healed canonical state: re-entry rebuilds the
        # device carries (and a fresh integrity carry) from the shells
        self._publish(self._arp.unflatten(carry["flat"]),
                      jax.tree_util.tree_map(self._arp.unflatten,
                                             carry["slots"]),
                      carry["mstate"])
        # re-entry re-partitions the canonical slots for the mesh — time
        # it as the elastic reshard it is
        self._elastic_resumed = True
        telemetry.gauge(
            "Integrity/heal_ms",
            help="detection-to-heal latency of the last integrity fault "
                 "(restore or re-broadcast)").set(
            (time.monotonic() - t0) * 1000.0)
        logger.warning(
            "Healed replica desync at iteration %d: re-broadcast the "
            "majority copy over minority replica(s) %s and rewound to "
            "iteration %d", err.iteration, err.replicas,
            max(err.iteration - 1, 0))
        err.healed = True

    def _wire_expert_parallel(self, module) -> None:
        """Point every MixtureOfExperts at the mesh's ``expert`` axis
        (duck-typed like the seq wiring): inside the shard_map step each
        layer dispatches with all_to_all and runs only its expert slice;
        outside the axis the dense path serves validation/predict.
        A dp x ep mesh with no MoE layer would silently be plain dp at
        double the mesh — reject it."""
        from bigdl_tpu.nn.moe import MixtureOfExperts
        n = self.mesh.shape["expert"]
        moes = module.find_modules(MixtureOfExperts)
        if not moes:
            raise ValueError(
                "mesh declares an 'expert' axis but the model has no "
                "MixtureOfExperts layer — use a ('data',) mesh")
        for m in moes:
            m.set_expert_parallel("expert", n)

    def _optimize_gspmd(self) -> Module:
        """dp x tp trainer: the Megatron tensor-parallel step in the
        TPU-native idiom — NO hand-written collectives.  Parameters carry
        ``tp_specs`` NamedShardings over the ``model`` axis (column/row
        Linear splits, MHA head splits), the batch shards over ``data``,
        and ONE ordinary jitted step (identical in shape to
        LocalOptimizer's) lets XLA's SPMD partitioner insert the
        all-reduces: the per-pair psum on row-parallel outputs and the
        data-axis gradient reduction (the scaling-book recipe: pick a
        mesh, annotate shardings, let XLA insert collectives).  Optimizer
        slots inherit each parameter's sharding, so Adam m/v for a split
        weight are split the same way — the memory win tensor parallelism
        exists for."""
        from bigdl_tpu.parallel.tensor_parallel import (tp_shard_params,
                                                        tp_specs,
                                                        zero1_slot_specs)

        model, mesh = self.model, self.mesh
        specs = tp_specs(model, axis="model", mesh=mesh)
        rep = NamedSharding(mesh, P())
        carry = {
            "params": tp_shard_params(model.params, mesh, specs),
            "mstate": jax.device_put(model.state, rep),
        }
        # slots shard over BOTH axes: the tp split from the parameter spec
        # plus ZeRO-1 over 'data' (a dp x tp run must not pay dp-fold
        # optimizer-state memory); fresh zeros and resumed host snapshots
        # alike are placed onto the slot specs
        slot_specs = zero1_slot_specs(carry["params"], specs,
                                      mesh.shape["data"])
        resumed = self.optim_method._slots is not None
        slots0 = (self.optim_method._slots if resumed
                  else self.optim_method.init_slots(carry["params"]))
        # resumed CANONICAL host slots re-place onto the data x model slot
        # specs of THIS mesh — the GSPMD leg of the topology-elastic
        # reshard (map_over_slots is the pivot: each family's tree zips
        # against the per-parameter spec tree)
        from bigdl_tpu.utils import elastic
        carry["slots"] = elastic.place_slots(
            lambda: self._map_over_slots(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                slots0, slot_specs),
            self._consume_elastic_resumed())
        self.optim_method.set_slots(carry["slots"])
        self.optim_method.state.setdefault("epoch", 1)

        from bigdl_tpu.utils import config as _config
        guard = _config.get_bool("bigdl.divergence.guard", True)
        every_n = _config.get_int("bigdl.integrity.everyN", 0)
        integ = None
        if guard or every_n > 0:
            integ = _integrity.DriverIntegrity(
                "gspmd",
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
            carry["fpc"] = jax.device_put(
                jnp.asarray(_integrity.init_carry()), rep)

        if self._step_fn is None:
            # pin the step's output shardings: params come back in their tp
            # placement (replicated over 'data' — XLA schedules the ZeRO
            # all-gather after the update), slots stay data x model sharded
            param_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda s: isinstance(s, P))
            slot_sh = self._map_over_slots(
                lambda x, s: NamedSharding(mesh, s), carry["slots"],
                slot_specs)
            out_sh = (param_sh, slot_sh, rep, rep)
            if guard or every_n > 0:
                # the 5th output is the small aux diagnostics dict —
                # replicated (a prefix sharding covers every entry)
                out_sh += (rep,)
            self._step_fn = self._arm_retrace(
                self._build_gspmd_step(out_shardings=out_sh),
                "gspmd")

        batch_sharding = NamedSharding(mesh, P("data"))
        local_ids = local_data_partitions(mesh)
        it = {"shards": None}

        def reset_epoch():
            self.dataset.shuffle()
            it["shards"] = {p: self.dataset.shard_data(p, train=True)
                            for p in local_ids}

        def fetch_batch():
            return _global_batch(it["shards"], batch_sharding, mesh,
                                 self.dataset.partition_num)

        def run_step(inputs, targets, hyper, rng):
            flip = _chaos.take_bitflip() if _chaos.active() else None
            if flip is not None:
                # injected SDC: one mantissa bit of a live (sharded)
                # parameter leaf flips between steps — every value stays
                # finite; only the continuity fingerprint can see it
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

        # the GSPMD step's argument tuple, for the AOT compile warmup
        # (Optimizer._warmup_compiles)
        def _cost_args(inputs, targets, hyper, rng):
            args = (carry["params"], carry["slots"], carry["mstate"],
                    inputs, targets, hyper, rng)
            if every_n > 0:
                args += (carry["fpc"], np.int32(1))
            return args
        self._cost_args_fn = _cost_args

        from bigdl_tpu.parallel.all_reduce import (gather_to_host,
                                                   replicate_tree)
        gather_rep = replicate_tree(mesh)

        def publish():
            # single-process the published model keeps its Megatron split —
            # params stay physically sharded over 'model' (the memory win),
            # and host consumers can still read any shard.  Multi-host the
            # remote shards are not addressable, so params regather to
            # replicated on device (validation forwards read them) and
            # slots go per-leaf to host numpy (bounds the transient device
            # footprint; serialization wants numpy anyway).
            if jax.process_count() > 1:
                self._publish(gather_rep(carry["params"]),
                              gather_to_host(carry["slots"], mesh),
                              carry["mstate"])
            else:
                self._publish(carry["params"], carry["slots"],
                              carry["mstate"])

        self._sync_dataset_epoch()
        reset_epoch()
        self._drive(fetch_batch, run_step, reset_epoch, publish,
                    epoch_size=self.dataset.size(), integrity=integ)
        return model

    def _map_over_slots(self, fn, slots, per_param_tree):
        return map_over_slots(self.optim_method, fn, slots, per_param_tree)

    def _build_gspmd_step(self, out_shardings=None):
        model, criterion = self.model, self.criterion
        optim = self.optim_method
        precision = self.precision
        aux_weight = self.moe_aux_weight
        from bigdl_tpu.utils import config
        guard = config.get_bool("bigdl.divergence.guard", True)
        every_n = config.get_int("bigdl.integrity.everyN", 0)
        fp_seed = config.get_int("bigdl.integrity.seed",
                                 _integrity.DEFAULT_SEED)
        # GSPMD overlap: the collectives here are partitioner-inserted,
        # so bucketing means partitioning the PARAMETER LEAVES into ~N
        # contiguous size-balanced groups and running each group's
        # optimizer update as its own scheduling unit (an
        # optimization_barrier pins the group boundary) — the
        # partitioner's collective combiner then emits per-group
        # gradient reductions the scheduler can overlap with other
        # groups' update compute.  Identical elementwise numerics; the
        # traced program stays collective-free, so the gspmd contract is
        # unchanged.
        overlap = config.get_bool("bigdl.parallel.overlap", True)
        groups = (_leaf_bucket_groups(
                      model.params,
                      config.get_int("bigdl.parallel.overlapBuckets", 4))
                  if overlap else None)
        from bigdl_tpu import telemetry
        telemetry.REGISTRY.gauge(
            "Parallel/overlap_buckets", summary=True,
            help="per-step collective buckets (1 = monolithic schedule)"
        ).set(float(len(groups) if groups else 1))

        def step(params, slots, mstate, inputs, targets, hyper, rng,
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

            (loss, new_mstate), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            with jax.named_scope("optimizer_update"):
                if groups is not None and len(groups) > 1:
                    new_params, new_slots = _bucketed_leaf_update(
                        optim, groups, grads, params, slots, hyper)
                else:
                    new_params, new_slots = optim.pure_update(
                        grads, params, slots, hyper)
            aux = {}
            ok = None
            if guard:
                # divergence guard (logically-global arrays: XLA's
                # partitioner makes the finiteness verdict consistent
                # across every shard without explicit collectives); ``nf``
                # names the first non-finite leaf for the driver's
                # diagnosed divergence line
                ok, nf = _integrity.first_nonfinite(loss, grads)
                aux["nf"] = nf
            if fpc is not None:
                # training-state integrity: the fingerprints are LOGICAL
                # values — the partitioner reduces across shards without
                # explicit collectives, so the traced program stays
                # collective-free (the gspmd contract is unchanged) and
                # cross-copy agreement is verified driver-side by
                # bitwise-comparing the replicated output's device copies
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
                # a skipped step must report non-finite to the driver's
                # bad-step counter even when only the GRADS overflowed;
                # an integrity freeze does NOT poison the loss
                loss = jnp.where(aux["nf"] == _integrity.NF_SENTINEL,
                                 loss, jnp.nan)
            if fpc is not None:
                fp_p_out = _integrity.fingerprint_tree(new_params, fp_seed)
                fp_s_out = _integrity.fingerprint_tree(
                    new_slots, fp_seed + _integrity.SLOT_SEED_OFF)
                aux.update(
                    cont=latch, bad_iter=bad_iter, fp_p=fp_p_out,
                    pn=_integrity.sq_norm(new_params),
                    un=_integrity.sq_norm_diff(new_params, params),
                    gn=_integrity.sq_norm(grads),
                    fpc=_integrity.pack_carry(latch, bad_iter, fp_p_out,
                                              fp_s_out))
            if guard or every_n > 0:
                return new_params, new_slots, new_mstate, loss, aux
            return new_params, new_slots, new_mstate, loss

        from bigdl_tpu.analysis import program_contracts
        from bigdl_tpu.utils import compile_cache
        return compile_cache.tracked_jit(
            step, label="gspmd", topology=self._topology_meta(),
            contract=program_contracts.gspmd_contract(precision),
            donate_argnums=(0, 1, 2), out_shardings=out_shardings)

    def _wire_sequence_parallel(self, module) -> None:
        """Point every MultiHeadAttention at the mesh's seq axis.  The ring
        path only engages while that axis is bound (inside the shard_map
        training step), so validation/predict forwards — which run outside
        it — keep full-sequence attention.

        Other time-mixing modules have no sequence-parallel path: on a
        time-sharded input a recurrent unroll would restart its hidden
        state at every chunk edge and a temporal conv / time reverse would
        see artificial boundaries — silently wrong, so they are rejected.
        """
        import bigdl_tpu.nn as nn
        time_mixing = (nn.Recurrent, nn.BiRecurrent, nn.TemporalConvolution,
                       nn.Reverse)
        offenders = [type(m).__name__ for m in module.find_modules(time_mixing)]
        if offenders:
            raise ValueError(
                "sequence-parallel training (mesh with a 'seq' axis) shards "
                "the time dimension, but these modules mix information "
                f"across time with no ring path: {sorted(set(offenders))}; "
                "train them on a ('data',)-only mesh")
        # duck-typed: MultiHeadAttention (ring path), PositionalEncoding
        # (chunk offset), and any future seq-aware module
        self._max_seq_len = None
        for m in module.modules():
            if hasattr(m, "set_sequence_parallel"):
                m.set_sequence_parallel(self.seq_axis)
            cap = getattr(m, "max_seq_len", None)
            if cap is not None:
                self._max_seq_len = (cap if self._max_seq_len is None
                                     else min(self._max_seq_len, cap))

    def _eval_mesh(self):
        """Validation forwards run sharded over the training mesh (the
        reference evaluates inside the cluster, ``optim/Evaluator.scala``)."""
        return self.mesh

    def _flat_slots(self, arp: AllReduceParameter):
        """Optimizer slots as flat padded vectors.  Fresh runs start from
        zeros; a resumed/reused OptimMethod carries slots in the canonical
        per-parameter pytree format, which is re-flattened here."""
        cached = self.optim_method._slots
        if cached is None:
            return self.optim_method.init_slots(
                jnp.zeros((arp.padded_size,), arp.dtype))
        outer = jax.tree_util.tree_structure(
            self.optim_method.init_slots(jnp.zeros(())))
        subtrees = outer.flatten_up_to(cached)
        return jax.tree_util.tree_unflatten(
            outer, [arp.flatten(s) for s in subtrees])


def _global_batch(shard_iters, batch_sharding, mesh, partition_num,
                  seq_chunks=None, expert_chunks=None, check=None):
    """Pull one minibatch per LOCALLY-OWNED shard, concatenate host-side
    into this process's block of the global batch, and assemble the global
    sharded array with ``jax.make_array_from_process_local_data`` (each
    device gets exactly its shard's records — the reference's
    locality-preserving zip, ``ZippedPartitionsWithLocalityRDD.scala:28``,
    with per-node feeding like the reference's executor-cached
    partitions).  Single-process, where every partition is local, this
    reduces to placing the whole global batch.

    ``shard_iters``: {partition_id: iterator} for the owned partitions
    (ordered ascending when iterated).  ``seq_chunks``: owned seq-axis
    coordinates — when a seq axis exists and this process owns only some
    time chunks, the time dimension is sliced to the owned (contiguous)
    chunk range before assembly.  ``expert_chunks``: same for the
    ``expert`` axis, which co-shards the batch dim — each data
    partition's rows are sliced to the owned expert chunk range.
    ``check`` optionally validates each local leaf (sequence-parallel
    shape requirements).  Returns the GLOBAL batch record count (driver
    epoch accounting is global)."""
    batches = [next(shard_iters[p]) for p in sorted(shard_iters)]
    inputs = _cat([b.get_input() for b in batches])
    targets = _cat([b.get_target() for b in batches])
    sizes = {b.size() for b in batches}
    if len(sizes) != 1:
        # the global record count is derived as per-partition size x
        # partition_num; uneven local minibatches would silently miscount
        # epoch boundaries on both the producer rollover and the driver
        raise ValueError(
            f"locally-owned partitions yielded unequal minibatch sizes "
            f"{sorted(sizes)} — SampleToMiniBatch(batch, partition_num) "
            "must split evenly across partitions")
    bsz = sizes.pop() * partition_num
    if check is not None:
        inputs = jax.tree_util.tree_map(check, inputs)
        targets = jax.tree_util.tree_map(check, targets)
    if expert_chunks is not None:
        ep_size = mesh.shape["expert"]
        if len(expert_chunks) < ep_size:
            lo, hi = expert_chunks[0], expert_chunks[-1]
            if list(expert_chunks) != list(range(lo, hi + 1)):
                raise ValueError(
                    f"owned expert chunks {expert_chunks} are not "
                    "contiguous — cannot slice batch rows for this process")
            n_parts = len(batches)

            def _slice_rows(x):
                x = np.asarray(x)
                per = x.shape[0] // n_parts        # rows per data partition
                sub = per // ep_size               # rows per expert chunk
                blocks = x.reshape((n_parts, per) + x.shape[1:])
                return blocks[:, lo * sub:(hi + 1) * sub].reshape(
                    (-1,) + x.shape[1:])

            inputs = jax.tree_util.tree_map(_slice_rows, inputs)
            targets = jax.tree_util.tree_map(_slice_rows, targets)
    if seq_chunks is not None:
        seq_size = mesh.shape["seq"]
        if len(seq_chunks) < seq_size:
            lo, hi = seq_chunks[0], seq_chunks[-1]
            if list(seq_chunks) != list(range(lo, hi + 1)):
                raise ValueError(
                    f"owned seq chunks {seq_chunks} are not contiguous — "
                    "cannot slice the time dimension for this process")

            def _slice_t(x):
                x = np.asarray(x)
                chunk = x.shape[1] // seq_size
                return x[:, lo * chunk:(hi + 1) * chunk]

            inputs = jax.tree_util.tree_map(_slice_t, inputs)
            targets = jax.tree_util.tree_map(_slice_t, targets)

    def _assemble(x):
        return jax.make_array_from_process_local_data(
            batch_sharding, np.asarray(x))

    inputs = jax.tree_util.tree_map(_assemble, inputs)
    targets = jax.tree_util.tree_map(_assemble, targets)
    return inputs, targets, bsz


def _cat(parts):
    """Concatenate per-shard activities (arrays or nested lists of arrays)
    along the batch axis.  Single-shard (the 1-partition streaming-ingest
    case) passes through without the concatenate copy — at b128 ImageNet
    that is ~19 MB of uint8 per batch saved on the fetch thread."""
    first = parts[0]
    if isinstance(first, (list, tuple)):
        return type(first)(_cat([p[i] for p in parts])
                           for i in range(len(first)))
    if len(parts) == 1:
        return np.asarray(first)
    return np.concatenate([np.asarray(p) for p in parts], axis=0)
