"""Mixture-of-experts layer (Switch-style top-1 routing).

The reference has no MoE (its ``MixtureTable`` is a dense gated blend over
a Table of expert outputs, ``nn/MixtureTable.scala`` — every expert runs on
every sample).  This layer is the sparse, TPU-native counterpart: top-1
token routing with a capacity bound, computed as einsum dispatch/combine so
the expert FFNs stay large batched MXU matmuls; homogeneous experts are
vmapped over a stacked parameter tree.  Expert parallelism over a mesh
``expert`` axis lives in ``bigdl_tpu/parallel/expert_parallel.py``.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import init as init_methods
from bigdl_tpu.nn.module import Module


class MixtureOfExperts(Module):
    """Top-k gated mixture of ``n_experts`` homogeneous experts
    (``top_k=1``: Switch; ``top_k=2``: the GShard configuration).

    ``expert``: a template Module mapping (tokens, d_model) -> (tokens,
    d_model); its structure is replicated per expert with independent
    parameters (stacked leaf-wise under the ``"experts"`` key).

    Routing: softmax gate over experts, each token goes to its ``top_k``
    highest-gate experts with the selected gate values renormalized to
    sum to 1 per token; each expert processes at most ``capacity`` tokens
    per choice tier combined, with overflow contributions dropped to zero
    (standard Switch/GShard behavior).

    **Batch-split semantics.** Capacity-overflow dropping is a property of
    which tokens *compete* for the same expert slots, so any execution
    that splits a batch into independent forwards — GPipe microbatching
    (``parallel.pipeline``), expert-parallel token shards
    (``parallel.expert_parallel``), gradient accumulation — routes each
    split with its *own* capacity budget.  When capacity binds, the result
    therefore differs from a monolithic full-batch forward (different
    tokens drop); the two agree exactly whenever no token drops.  Pass
    ``capacity=`` to pin the per-expert, per-forward budget explicitly
    (e.g. capacity sized to the microbatch), or raise ``capacity_factor``
    to ``n_experts / top_k`` to make dropping impossible and the layer
    batch-split-invariant.  The Switch load-balancing
    diagnostic ``n_experts * sum_e(token_fraction_e * mean_gate_e)``
    (minimized at 1.0 by a uniform router) is returned in the module
    state under ``"aux_loss"``: read it from ``model.state`` after a
    TRAINING-mode forward (the stateful shell persists new state only in
    train mode) or take it from ``apply``'s returned state directly; under
    expert parallelism pass ``return_aux=True`` to
    ``expert_parallel_apply``.
    """

    def __init__(self, d_model: int, expert: Module, n_experts: int,
                 capacity_factor: float = 1.25, top_k: int = 1,
                 capacity: Optional[int] = None, name=None):
        super().__init__(name)
        if not 1 <= top_k <= n_experts:
            raise ValueError(f"top_k {top_k} must be in [1, {n_experts}]")
        if capacity is not None and capacity < 1:
            raise ValueError(f"capacity {capacity} must be >= 1")
        self.d_model = d_model
        self.expert = expert
        self.n_experts = n_experts
        self.capacity_factor = capacity_factor
        self.top_k = top_k
        self.fixed_capacity = capacity
        self.expert_parallel = None     # axis name once wired
        self._ep_shards = 1

    def _init_params(self, rng):
        ks = jax.random.split(rng, self.n_experts + 1)
        xavier = init_methods.Xavier()
        gate = xavier(ks[0], (self.d_model, self.n_experts),
                      self.d_model, self.n_experts)
        per_expert = [self.expert._init_params(k) for k in ks[1:]]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                         *per_expert)
        return {"gate": gate, "experts": stacked}

    # aux_loss is a per-forward diagnostic, not cross-step state; the
    # expert's state nests under "expert" and is owned by self.expert
    # (see module.semantic_state_leaves)
    diagnostic_state_keys = ("aux_loss",)

    @property
    def state_children(self):
        return {"expert": self.expert}

    def _init_state(self):
        # experts must be stateless: per-expert running statistics are not
        # threaded through the vmapped dispatch (guarded in expert_forward)
        from bigdl_tpu.nn.module import semantic_state_leaves
        expert_state = self.expert._init_state()
        if semantic_state_leaves(self.expert, expert_state):
            raise ValueError(
                "MixtureOfExperts experts must be stateless (no BatchNorm "
                "running statistics) — state updates cannot be threaded "
                "through the routed dispatch")
        return {"expert": expert_state,
                "aux_loss": jnp.zeros(())}

    def capacity(self, n_tokens: int) -> int:
        """Per-expert token capacity for a dispatch over ``n_tokens``:
        scales with ``top_k`` (each token makes k assignments, so a
        balanced router sends k*t/E per expert — GShard's convention).
        A ``capacity=`` constructor override pins this regardless of the
        forward's token count (stable under batch splitting — see the
        class docstring).  Under expert parallelism this applies per
        device shard (each shard routes its local tokens), so the global
        per-expert budget is n_shards * capacity(local_tokens)."""
        if self.fixed_capacity is not None:
            return self.fixed_capacity
        return max(1, math.ceil(n_tokens * self.top_k / self.n_experts
                                * self.capacity_factor))

    def route(self, params, flat):
        """(tokens, d) -> (dispatch (t, E, C), combine (t, E, C), aux).

        ``dispatch`` is the 0/1 routing tensor (token t occupies capacity
        slot c of expert e); ``combine`` additionally carries the
        (renormalized) gate probability, so ``combine @ expert_out`` is
        the weighted output; ``aux`` is the Switch load-balancing scalar.
        """
        t = flat.shape[0]
        cap = self.capacity(t)
        gates = jax.nn.softmax(flat @ params["gate"], axis=-1)   # (t, E)

        # top-k selection in one op; queue bookkeeping in int32 — a
        # low-precision activation dtype (bf16 is first-class here) cannot
        # count past 256 exactly, which would double-book capacity slots.
        # Later tiers queue AFTER all earlier tiers of the same expert
        # (GShard's ordering), via the per-expert count offset.
        top_gates, top_idx = jax.lax.top_k(gates, self.top_k)    # (t, k)
        counts = jnp.zeros((self.n_experts,), jnp.int32)
        chosen_slot, chosen_gate = [], []
        top1_oh = None                      # tier-0 assignment, for aux
        for k in range(self.top_k):
            oh = jax.nn.one_hot(top_idx[:, k], self.n_experts,
                                dtype=jnp.int32)
            pos = (jnp.cumsum(oh, axis=0) * oh - 1) + counts[None, :] * oh
            keep = (pos >= 0) & (pos < cap) & (oh > 0)
            slot = jax.nn.one_hot(jnp.where(keep, pos, -1), cap,
                                  dtype=flat.dtype)              # (t, E, C)
            if top1_oh is None:
                top1_oh = oh
            chosen_slot.append(slot * oh.astype(flat.dtype)[:, :, None])
            chosen_gate.append(top_gates[:, k])                  # (t,)
            counts = counts + jnp.sum(oh, axis=0)

        # top_k=1 (Switch) scales by the raw gate probability; top_k>1
        # renormalizes the selected gates per token (GShard)
        gate_stack = jnp.stack(chosen_gate, axis=0)              # (k, t)
        if self.top_k > 1:
            denom = jnp.maximum(jnp.sum(gate_stack, axis=0), 1e-9)
        else:
            denom = jnp.ones_like(gate_stack[0])
        dispatch = sum(chosen_slot)
        combine = sum(s * (g / denom)[:, None, None]
                      for s, g in zip(chosen_slot, gate_stack))

        # Switch load-balancing diagnostic over the TOP-1 assignment
        frac_tokens = jnp.mean(top1_oh.astype(gates.dtype), axis=0)
        mean_gate = jnp.mean(gates, axis=0)
        aux = self.n_experts * jnp.sum(frac_tokens * mean_gate)
        return dispatch, combine, aux

    # ---- the grouped execution path (bigdl.moe.impl=grouped) -------------
    #
    # Same routing decisions, different materialization: instead of the
    # O(t*E*C*d) dispatch/combine einsums over mostly-zero (t, E, C)
    # one-hot tensors, the kept (token, tier) assignments scatter
    # directly into the (E, C, d) expert batch and gather back out —
    # O(t*k*d) data movement.  The expert matmuls themselves are
    # unchanged (the same grouped (E, C, d) batch), and the kept set,
    # slot order, renormalized gates and aux diagnostic are computed by
    # the identical bookkeeping, so capacity-drop semantics are exact.

    @staticmethod
    def _impl() -> str:
        from bigdl_tpu.utils import config
        impl = str(config.get_property("bigdl.moe.impl", "einsum")
                   or "einsum").lower()
        if impl not in ("einsum", "grouped"):
            raise ValueError(f"bigdl.moe.impl={impl!r}: expected 'einsum' "
                             "or 'grouped'")
        return impl

    def route_compact(self, params, flat):
        """(tokens, d) -> (expert_id (t, k), slot (t, k), weight (t, k),
        keep (t, k), aux) — :meth:`route`'s bookkeeping in token-major
        compact form.  ``slot`` is the capacity position the assignment
        would occupy (tier k queues after all earlier tiers of the same
        expert, via the per-expert count offset — GShard's ordering);
        ``keep`` is False past capacity; ``weight`` is the (renormalized)
        gate with the keep mask already applied, so a dropped assignment
        contributes exactly zero."""
        t = flat.shape[0]
        cap = self.capacity(t)
        gates = jax.nn.softmax(flat @ params["gate"], axis=-1)   # (t, E)
        top_gates, top_idx = jax.lax.top_k(gates, self.top_k)    # (t, k)
        counts = jnp.zeros((self.n_experts,), jnp.int32)
        slots_l, keeps_l = [], []
        top1_oh = None
        for k in range(self.top_k):
            oh = jax.nn.one_hot(top_idx[:, k], self.n_experts,
                                dtype=jnp.int32)
            pos = (jnp.cumsum(oh, axis=0) * oh - 1) + counts[None, :] * oh
            # the chosen column's value IS this assignment's queue
            # position (>= 0 there by construction)
            slot_k = jnp.take_along_axis(pos, top_idx[:, k:k + 1],
                                         axis=1)[:, 0]
            if top1_oh is None:
                top1_oh = oh
            slots_l.append(slot_k)
            keeps_l.append(slot_k < cap)
            counts = counts + jnp.sum(oh, axis=0)
        slot = jnp.stack(slots_l, axis=1)                        # (t, k)
        keep = jnp.stack(keeps_l, axis=1)                        # (t, k)
        if self.top_k > 1:
            denom = jnp.maximum(jnp.sum(top_gates, axis=1, keepdims=True),
                                1e-9)
        else:
            denom = jnp.ones_like(top_gates)
        wgt = (top_gates / denom) * keep.astype(flat.dtype)
        frac_tokens = jnp.mean(top1_oh.astype(gates.dtype), axis=0)
        mean_gate = jnp.mean(gates, axis=0)
        aux = self.n_experts * jnp.sum(frac_tokens * mean_gate)
        return top_idx, slot, wgt, keep, aux

    def grouped_dispatch(self, flat, expert_id, slot, keep, cap: int):
        """Scatter kept assignments into the (E, C, d) expert batch: row
        ``expert_id * C + slot`` receives the token vector; dropped
        assignments target a discarded overflow row.  Kept rows are
        unique by construction (cumsum slot assignment), so the
        scatter-add materializes exactly what the dispatch einsum
        builds, with unfilled capacity slots staying zero."""
        t, d = flat.shape
        dump = self.n_experts * cap                 # overflow row, discarded
        rows = jnp.where(keep, expert_id * cap + slot, dump)     # (t, k)
        tok = jnp.repeat(jnp.arange(t), self.top_k)
        buf = jnp.zeros((dump + 1, d), flat.dtype)
        buf = buf.at[rows.reshape(-1)].add(flat[tok])
        return buf[:dump].reshape(self.n_experts, cap, d)

    def grouped_combine(self, expert_out, expert_id, slot, wgt, keep,
                        cap: int):
        """Gather each assignment's expert-output row and weighted-sum
        over the k tiers — the combine einsum without the (t, E, C)
        intermediate.  ``wgt`` carries the keep mask, so dropped
        assignments add zero (they gather an arbitrary row, then
        multiply by 0)."""
        d = expert_out.shape[-1]
        rows = jnp.where(keep, expert_id * cap + slot, 0)        # (t, k)
        picked = expert_out.reshape(self.n_experts * cap, d)[
            rows.reshape(-1)].reshape(rows.shape + (d,))         # (t, k, d)
        return jnp.sum(picked * wgt[:, :, None], axis=1)

    def set_expert_parallel(self, axis_name, n_shards: int
                            ) -> "MixtureOfExperts":
        """Wire the trainer's mesh ``expert`` axis (duck-typed, like
        MultiHeadAttention's ring path): while that axis is bound —
        inside the distributed trainer's shard_map step — ``apply``
        switches to the all_to_all dispatch, each device running only
        its ``n_experts / n_shards`` experts on the tokens every peer
        routed to them.  Outside the axis (validation, plain forward)
        the dense path runs unchanged."""
        if axis_name is not None and self.n_experts % n_shards != 0:
            raise ValueError(
                f"n_experts {self.n_experts} must divide by the expert "
                f"axis size {n_shards}")
        self.expert_parallel = axis_name
        self._ep_shards = n_shards if axis_name is not None else 1
        self._jit_apply = None
        return self

    def expert_forward(self, params, expert_in, state, training, rng,
                       experts=None):
        """vmapped expert application over the stacked (E, C, d) inputs.
        ``experts`` overrides the stacked tree (the expert-parallel path
        passes this device's slice)."""
        stacked = params["experts"] if experts is None else experts

        def one(p, xin):
            out, _ = self.expert.apply(p, xin, state["expert"],
                                       training=training, rng=rng)
            return out
        return jax.vmap(one)(stacked, expert_in)

    def apply(self, params, input, state, training=False, rng=None):
        from bigdl_tpu.nn.attention import _axis_bound
        flat = jnp.reshape(input, (-1, self.d_model))
        ep = self.expert_parallel
        if ep is not None and _axis_bound(ep):
            out, aux = self._apply_expert_parallel(params, flat, state,
                                                   training, rng)
        elif self._impl() == "grouped":
            eid, slot, wgt, keep, aux = self.route_compact(params, flat)
            cap = self.capacity(flat.shape[0])
            expert_in = self.grouped_dispatch(flat, eid, slot, keep, cap)
            expert_out = self.expert_forward(params, expert_in, state,
                                             training, rng)
            out = self.grouped_combine(expert_out, eid, slot, wgt, keep,
                                       cap)
        else:
            dispatch, combine, aux = self.route(params, flat)
            expert_in = jnp.einsum("tec,td->ecd", dispatch, flat)
            expert_out = self.expert_forward(params, expert_in, state,
                                             training, rng)
            out = jnp.einsum("tec,ecd->td", combine, expert_out)
        new_state = dict(state)
        new_state["aux_loss"] = aux
        return jnp.reshape(out, input.shape), new_state

    def _apply_expert_parallel(self, params, flat, state, training, rng):
        """In-axis all_to_all dispatch (tokens already sharded over the
        bound ``expert`` axis; params replicated — the trainer's ARP
        keeps one flat replicated vector).  Same exchange geometry as
        ``parallel/expert_parallel.expert_parallel_apply``: route local
        tokens against the full gate, all_to_all the per-expert queues,
        run only THIS device's expert block, all_to_all back, combine.
        The aux diagnostic is pmeant over the token shards here so the
        trainer's loss term sees the global balance."""
        from jax import lax
        ep, n = self.expert_parallel, self._ep_shards
        grouped = self._impl() == "grouped"
        if grouped:
            # grouped path: only the LOCAL dispatch/combine
            # materialization changes — the all_to_all exchange geometry
            # and per-shard capacity semantics are identical
            eid, slot, wgt, keep, aux = self.route_compact(params, flat)
            cap = self.capacity(flat.shape[0])
            expert_in = self.grouped_dispatch(flat, eid, slot, keep, cap)
        else:
            dispatch, combine, aux = self.route(params, flat)
            expert_in = jnp.einsum("tec,td->ecd", dispatch, flat)
        # (E, C, d) -> (E/n, n*C, d): every peer's tokens for my experts
        expert_in = lax.all_to_all(expert_in, ep, split_axis=0,
                                   concat_axis=1, tiled=True)
        e_per = self.n_experts // n
        start = lax.axis_index(ep) * e_per
        mine = jax.tree_util.tree_map(
            lambda a: lax.dynamic_slice_in_dim(a, start, e_per, 0),
            params["experts"])
        out = self.expert_forward(params, expert_in, state, training, rng,
                                  experts=mine)
        out = lax.all_to_all(out, ep, split_axis=1, concat_axis=0,
                             tiled=True)                     # (E, C, d)
        if grouped:
            y = self.grouped_combine(out, eid, slot, wgt, keep, cap)
        else:
            y = jnp.einsum("tec,ecd->td", combine, out)
        return y, lax.pmean(aux, ep)


# ---------------------------------------------------------------------------
# sigmoid-routed experts (DeepSeek-V3 ``noaux_tc``), held in part
# ---------------------------------------------------------------------------

#: rows of one grouped product; a step runs only the chunks that hold an
#: assignment to a held expert
_EXPERT_ROWS = 4096


def sigmoid_routed_experts(p, x, top_k: int, scale: float, held,
                           valid=None):
    """``x`` (N, d) -> ``(y (N, d) float32, counts (2,) int32)``.

    Router in float32: ``s = sigmoid(x W_g)`` over ALL ``n_routed``
    experts, the ``top_k`` highest of ``s + bias`` chosen, gates ``scale *
    s_e / sum_chosen s`` (the sum runs over all chosen, held or not).
    Of the chosen, this layer computes the experts it holds, ``held =
    (first, count)`` with weights stacked ``(count, ...)``: assignments
    are sorted by expert and go through grouped products in chunks of
    ``_EXPERT_ROWS`` rows, of which only those that hold an assignment
    run (a single chunk, a decode step, always runs).  ``lax.ragged_dot``
    leaves the rows beyond its groups undefined (on the TPU: old memory),
    so what it returns there is dropped by a select.  No capacity,
    nothing dropped, nothing stands in for an expert held elsewhere.  The
    shared expert sees every token.  ``counts``:
    assignments made by the ``valid`` tokens, and those that met a held
    expert."""
    from bigdl_tpu.nn.latent_attention import swiglu
    n, d = x.shape
    first, count = held
    dtype = p["w1"].dtype
    if valid is None:
        valid = jnp.ones((n,), bool)
    with jax.named_scope("router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            p["gate"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + p["bias"].astype(jnp.float32), top_k)
        s_chosen = jnp.take_along_axis(s, chosen, axis=1)
        gates = scale * s_chosen / jnp.sum(s_chosen, axis=1, keepdims=True)
    with jax.named_scope("dispatch"):
        local = chosen - first
        is_held = (local >= 0) & (local < count) & valid[:, None]
        rows = n * top_k
        chunk = min(rows, _EXPERT_ROWS)
        n_chunks = -(-rows // chunk)
        pad = n_chunks * chunk - rows
        key = jnp.pad(jnp.where(is_held, local, count).reshape(-1),
                      (0, pad), constant_values=count)
        order = jnp.argsort(key, stable=True)
        token = jnp.minimum(order // top_k, n - 1).reshape(n_chunks, chunk)
        gate = jnp.pad(gates.reshape(-1), (0, pad))[order]
        gate = jnp.where(key[order] < count, gate, 0.0).reshape(
            n_chunks, chunk)
        sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                        dtype=jnp.int32)
        ends = jnp.cumsum(sizes)
        n_held = ends[-1]
        counts = jnp.stack([jnp.sum(valid, dtype=jnp.int32) * top_k, n_held])
    xin = x.astype(dtype)

    def run(y, c, tok, g):
        lo = c * chunk
        here = (jnp.clip(ends, lo, lo + chunk)
                - jnp.clip(ends - sizes, lo, lo + chunk))
        xr = xin[tok]
        hid = (jax.nn.silu(jax.lax.ragged_dot(
                   xr, p["w1"], here, preferred_element_type=jnp.float32))
               * jax.lax.ragged_dot(xr, p["w3"], here,
                                    preferred_element_type=jnp.float32))
        out = jax.lax.ragged_dot(hid.astype(dtype), p["w2"], here,
                                 preferred_element_type=jnp.float32)
        # the rows of no group (gate 0) are whatever the TPU's memory
        # held, NaN among it: selected away, never multiplied by 0
        return y.at[tok].add(jnp.where((g != 0)[:, None],
                                       out * g[:, None], 0.0))

    def body(y, args):
        c, tok, g = args
        return jax.lax.cond(c * chunk < n_held, run, lambda y, *_: y,
                            y, c, tok, g), None

    with jax.named_scope("experts"):
        y = jnp.zeros((n, d), jnp.float32)
        if n_chunks == 1:
            # a decode step: always run, so that a step's time does not
            # depend on whether any of its few tokens met a held expert
            y = run(y, 0, token[0], gate[0])
        else:
            y, _ = jax.lax.scan(body, y, (jnp.arange(n_chunks), token, gate))
    with jax.named_scope("shared"):
        shared = swiglu(p["shared"], x)
    with jax.named_scope("combine"):
        y = y + shared
    return y, counts


def held_range(held, n_routed: int):
    """``held`` as ``(first, count)``, all ``n_routed`` experts for None;
    refused if it is no range of them."""
    first, count = (0, n_routed) if held is None else (int(held[0]),
                                                       int(held[1]))
    if not (0 <= first and count >= 1 and first + count <= n_routed):
        raise ValueError(f"held {(first, count)} is no range of the "
                         f"{n_routed} routed experts")
    return first, count


class SigmoidRoutedExperts(Module):
    """``n_routed`` sigmoid-scored experts, ``top_k`` a token chosen under
    a selection bias, plus one shared expert; every expert a SwiGLU of
    ``width``.  ``held = (first, count)``: the experts whose weights this
    layer holds.  The router still has ``n_routed`` outputs and chooses
    among all of them; an assignment to an expert held elsewhere adds
    nothing here (it is that holder's to add), so the sum over the shares
    ``held`` of one layer, shared expert counted once, is the whole layer
    (see :func:`sigmoid_routed_experts`).  ``out_scale`` multiplies the
    standard deviation every expert's ``w2`` is drawn at."""

    def __init__(self, d_model: int, width: int, n_routed: int, top_k: int,
                 scale: float = 1.0, held=None, out_scale: float = 1.0,
                 name=None):
        super().__init__(name)
        self.d_model, self.width = int(d_model), int(width)
        self.n_routed, self.top_k = int(n_routed), int(top_k)
        self.scale, self.out_scale = float(scale), float(out_scale)
        self.held = held_range(held, self.n_routed)
        if not 1 <= self.top_k <= self.n_routed:
            raise ValueError(f"top_k {top_k} must be in [1, {n_routed}]")

    def _init_params(self, rng):
        """Every expert's weights come from the expert's own number, so a
        layer that holds a share has exactly the uncut layer's rows.  The
        selection bias is seeded small: training moves it until the
        experts' loads are even, and under scores in (0, 1) of which the
        top few of hundreds are chosen, a bias of 0.1 makes one expert
        four times as popular as the next (a share of 16 of 256 experts
        then met 5 % of the assignments under one seed and 10 % under
        another, and a decode step's time followed; PERF.md 6)."""
        from bigdl_tpu.nn.latent_attention import SwiGLU
        kg, kb, ks, ke = jax.random.split(rng, 4)
        d = self.d_model
        one = SwiGLU(d, self.width, self.out_scale)
        first, count = self.held
        experts = jax.vmap(
            lambda e: one._init_params(jax.random.fold_in(ke, e)))(
                first + jnp.arange(count))
        return {"gate": jax.random.normal(kg, (d, self.n_routed))
                / math.sqrt(d),
                "bias": 0.01 * jax.random.normal(kb, (self.n_routed,)),
                "w1": experts["w1"], "w3": experts["w3"],
                "w2": experts["w2"],
                "shared": one._init_params(ks)}

    def apply(self, params, input, state, training=False, rng=None):
        flat = input.reshape(-1, input.shape[-1])
        y, _ = sigmoid_routed_experts(params, flat, self.top_k, self.scale,
                                      self.held)
        return y.reshape(input.shape), state
