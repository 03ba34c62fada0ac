"""The ``glm_moe_dsa`` decoder family (GLM-5.2; DeepSeek-V3's latent
attention and ``noaux_tc`` router with DeepSeek-V3.2's indexer, its
indices shared across layers as ``indexer_types`` spells out).

The family is written ONCE, as :func:`forward`, a pure function of
``(params, tokens, positions, cache)``.  The module's ``apply`` calls it
without a cache; the LM server's prefill, decode and full steps
(``serving/lm.py``) call it with the step's view of the paged pools.

Block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; the FFN
is a SwiGLU in the first ``first_k_dense`` layers and sigmoid-routed
experts with one shared expert above; final RMSNorm, untied head without
bias, log-softmax.  Token ids are 1-based.  The residual stream is
float32 whatever the weights' dtype.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import latent_attention as la
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.moe import (SigmoidRoutedExperts, held_range,
                              sigmoid_routed_experts)


class GlmMoeDsaSpec(NamedTuple):
    vocab_size: int
    attention: la.LatentAttentionSpec
    indexer_types: Tuple[str, ...]       # "full" | "shared", a layer each
    mlp_layer_types: Tuple[str, ...]     # "dense" | "sparse"
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    experts_held: Tuple[int, int]
    max_position_embeddings: int
    #: the depth of the model these layers are part of, or 0: what the
    #: projections that write to the residual stream are drawn at
    init_depth: int = 0

    @property
    def out_scale(self) -> float:
        return 1 / math.sqrt(2 * self.init_depth) if self.init_depth else 1.0

    @property
    def n_layers(self) -> int:
        return len(self.indexer_types)

    @property
    def index_layers(self) -> Tuple[int, ...]:
        """The layers that have an indexer, in order: a layer's place in
        this list is its slot in the index pool."""
        return tuple(i for i, t in enumerate(self.indexer_types)
                     if t == "full")


def forward(spec: GlmMoeDsaSpec, params, tokens, positions,
            cache: Optional[la.LatentCache] = None, valid=None, rows=None):
    """``tokens`` (B, T) 1-based at ``positions`` (B, T) -> ``(log-probs,
    cache, counts)``.

    ``cache``: None, or the step's view of the paged pools
    (:class:`~bigdl_tpu.nn.latent_attention.LatentCache`): every layer
    writes its latent rows, the ``full`` layers their indexer keys, and
    with ``cache.tables`` attention reads its context through them.
    ``valid`` (B, T): real tokens (not padding, not an idle slot).
    ``rows`` (B,): log-probs of that one position a sequence only, ``(B,
    1, vocab)``; else of every position.  ``counts``: int32 ``(routed
    assignments of valid tokens, those that met a held expert, context
    rows read)``: the first two over the expert layers; the third, in a
    step that reads its context through ``cache.tables``, the rows the
    valid tokens' attention read (``Selection.valid``), a layer's worth
    (the mean over the layers); 0 where a sequence attends itself."""
    a = spec.attention
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    with jax.named_scope("embed"):
        idx = jnp.clip(tokens.astype(jnp.int32) - 1, 0, spec.vocab_size - 1)
        x = jnp.take(params["embed"], idx, axis=0).astype(jnp.float32)
    counts = jnp.zeros((2,), jnp.int32)
    read = jnp.zeros((), jnp.int32)
    paged = cache is not None and cache.tables is not None
    sel = None
    index_slot = {l: i for i, l in enumerate(spec.index_layers)}
    for li, lyr in enumerate(params["layers"]):
        with jax.named_scope(f"layer{li}"):
            with jax.named_scope("ln1"):
                h = la.rms_norm(x, lyr["ln1"], a.eps)
            with jax.named_scope("attn"):
                out, cache, sel = la.latent_attention(
                    a, lyr["attn"], lyr.get("indexer"), h, positions, cache,
                    layer=li, index_slot=index_slot.get(li, 0), sel=sel,
                    valid=valid)
                x = x + out
                if paged:
                    read = read + jnp.sum(sel.valid, dtype=jnp.int32)
            with jax.named_scope("ln2"):
                h = la.rms_norm(x, lyr["ln2"], a.eps)
            if "moe" in lyr:
                with jax.named_scope("moe"):
                    y, c = sigmoid_routed_experts(
                        lyr["moe"], h.reshape(b * t, -1),
                        spec.num_experts_per_tok, spec.routed_scaling_factor,
                        spec.experts_held, valid.reshape(-1))
                x = x + y.reshape(b, t, -1)
                counts = counts + c
            else:
                with jax.named_scope("ffn"):
                    x = x + la.swiglu(lyr["ffn"], h)
    if rows is not None:
        # a gather, which XLA moves below the residual adds: of the last
        # layer's outputs only this row is then ever computed
        x = jnp.take_along_axis(x, rows.astype(jnp.int32)[:, None, None],
                                axis=1)
    with jax.named_scope("ln_f"):
        x = la.rms_norm(x, params["ln_f"], a.eps)
    with jax.named_scope("head"):
        logits = la.matmul(x, params["head"])
    counts = jnp.concatenate([counts, (read // spec.n_layers)[None]])
    with jax.named_scope("log_softmax"):
        return jax.nn.log_softmax(logits, axis=-1), cache, counts


class GlmMoeDsaLM(Module):
    """Token ids (B, T), 1-based -> log-probs (B, T, vocab).  One Module
    with one parameter dict: ``embed``, ``layers`` (``ln1``, ``attn``,
    ``indexer`` in the layers typed ``full``, ``ln2``, ``ffn`` or
    ``moe``), ``ln_f``, ``head``."""

    def __init__(self, spec: GlmMoeDsaSpec, name=None):
        super().__init__(name)
        self.spec = spec

    def _init_params(self, rng):
        s, a = self.spec, self.spec.attention
        d = a.d_model
        ke, kh, kf, kl = jax.random.split(rng, 4)
        layers = []
        for li in range(s.n_layers):
            k = jax.random.split(jax.random.fold_in(kl, li), 5)
            lyr = {"ln1": la.norm_weight(k[0], d),
                   "attn": la.init_attention(a, k[1], s.out_scale),
                   "ln2": la.norm_weight(k[2], d)}
            if s.indexer_types[li] == "full":
                lyr["indexer"] = la.init_indexer(a, k[3])
            if s.mlp_layer_types[li] == "dense":
                lyr["ffn"] = la.SwiGLU(
                    d, s.intermediate_size, s.out_scale)._init_params(k[4])
            else:
                lyr["moe"] = SigmoidRoutedExperts(
                    d, s.moe_intermediate_size, s.n_routed_experts,
                    s.num_experts_per_tok, s.routed_scaling_factor,
                    s.experts_held, s.out_scale)._init_params(k[4])
            layers.append(lyr)
        return {"embed": jax.random.normal(ke, (s.vocab_size, d)),
                "layers": layers,
                "ln_f": la.norm_weight(kf, d),
                "head": jax.random.normal(kh, (d, s.vocab_size))
                / math.sqrt(d)}

    def apply(self, params, input, state, training=False, rng=None):
        b, t = input.shape
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        logp, _, _ = forward(self.spec, params, input, positions)
        return logp, state


def glm_moe_dsa_lm(vocab_size: int, hidden_size: int,
                   num_attention_heads: int, q_lora_rank: int,
                   kv_lora_rank: int, qk_nope_head_dim: int,
                   qk_rope_head_dim: int, v_head_dim: int,
                   index_n_heads: int, index_head_dim: int, index_topk: int,
                   indexer_types: Sequence[str],
                   mlp_layer_types: Sequence[str], intermediate_size: int,
                   moe_intermediate_size: int, n_routed_experts: int,
                   num_experts_per_tok: int,
                   routed_scaling_factor: float = 1.0,
                   rope_theta: float = 10000.0, rms_norm_eps: float = 1e-5,
                   max_position_embeddings: int = 1 << 20,
                   experts_held: Optional[Tuple[int, int]] = None,
                   param_dtype=None,
                   init_depth: Optional[int] = None) -> GlmMoeDsaLM:
    """The ``glm_moe_dsa`` decoder at the sizes given, one layer per entry
    of ``indexer_types`` (``"full"``: the layer has an indexer; ``"shared"``:
    it reuses the selection of the nearest full layer below, so the first
    layer is ``full``) and of ``mlp_layer_types`` (``"dense"`` |
    ``"sparse"``).

    ``experts_held = (first, count)``: the routed experts this model
    holds, one chip's share of an expert-parallel deployment; the router
    keeps ``n_routed_experts`` outputs (default: all).  ``param_dtype``:
    the dtype the parameters are created at (``Module.set_param_dtype``;
    ``jnp.bfloat16`` to serve: ``model.reset(key)`` then holds
    two bytes a parameter and nothing else).  ``init_depth``: the depth
    of the model these layers are part of (the published one's, where
    only some of its layers are built).  The projections that write to
    the residual stream (attention's ``o``, every FFN's and expert's
    ``w2``) are then drawn at ``N(0, 1 / (fan_in * 2 * init_depth))``,
    the depth-scaled initialisation of GPT-2 and Megatron-LM, so that a
    layer adds to the stream what one of ``init_depth`` layers would;
    None: ``N(0, 1 / fan_in)`` like every other product."""
    indexer_types = tuple(indexer_types)
    mlp_layer_types = tuple(mlp_layer_types)
    if len(indexer_types) != len(mlp_layer_types) or not indexer_types:
        raise ValueError("indexer_types and mlp_layer_types name the same, "
                         "non-empty, list of layers")
    if indexer_types[0] != "full" or set(indexer_types) - {"full", "shared"}:
        raise ValueError("indexer_types holds 'full' | 'shared' and starts "
                         f"with 'full', got {indexer_types}")
    if set(mlp_layer_types) - {"dense", "sparse"}:
        raise ValueError(f"mlp_layer_types holds 'dense' | 'sparse', got "
                         f"{mlp_layer_types}")
    if qk_rope_head_dim % 2 or qk_rope_head_dim > index_head_dim:
        raise ValueError("qk_rope_head_dim must be even and no larger than "
                         "index_head_dim (the indexer turns its first "
                         "qk_rope_head_dim)")
    attention = la.LatentAttentionSpec(
        d_model=int(hidden_size), n_head=int(num_attention_heads),
        q_lora_rank=int(q_lora_rank), kv_lora_rank=int(kv_lora_rank),
        qk_nope_head_dim=int(qk_nope_head_dim),
        qk_rope_head_dim=int(qk_rope_head_dim), v_head_dim=int(v_head_dim),
        index_n_heads=int(index_n_heads),
        index_head_dim=int(index_head_dim), index_topk=int(index_topk),
        rope_theta=float(rope_theta), eps=float(rms_norm_eps))
    held = held_range(experts_held, int(n_routed_experts))
    model = GlmMoeDsaLM(GlmMoeDsaSpec(
        vocab_size=int(vocab_size), attention=attention,
        indexer_types=indexer_types, mlp_layer_types=mlp_layer_types,
        intermediate_size=int(intermediate_size),
        moe_intermediate_size=int(moe_intermediate_size),
        n_routed_experts=int(n_routed_experts),
        num_experts_per_tok=int(num_experts_per_tok),
        routed_scaling_factor=float(routed_scaling_factor),
        experts_held=held,
        max_position_embeddings=int(max_position_embeddings),
        init_depth=int(init_depth or 0)))
    if param_dtype is not None:
        model.set_param_dtype(param_dtype)
    return model


__all__ = ["GlmMoeDsaLM", "GlmMoeDsaSpec", "forward", "glm_moe_dsa_lm"]
