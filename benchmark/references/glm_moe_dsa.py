"""Plain reference: the ``glm_moe_dsa`` decoder (GLM-5.2) in float32.

Written from the papers and the published configuration, with none of the
program's code and no kernel: DeepSeek-V2/V3 (multi-head latent attention,
sec. 2.1; the sigmoid router with a selection bias and a shared expert,
V3 sec. 2.1.2), DeepSeek-V3.2 (the lightning indexer and top-k selection,
sec. 2.1), GLM-5.2's ``config.json`` (``indexer_types``: which layers have
an indexer and which reuse the one below).  Every product runs in float32
at ``jax.default_matmul_precision("highest")`` on the arrays it is given
(the program's own bf16 weights, widened); nothing is absorbed, nothing is
cached: one full causal forward.

    block:    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA:      c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x (nope + rope)
              [c_kv, k_r] = x W_kva;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r)
              [k_nope, v] = c_kv W_kvb;  q_r = RoPE(q_r)
              scores (q_nope . k_nope + q_r . k_r) / sqrt(nope + rope) over
              the keys s <= t that are in S_t; softmax; concat_h(p v) W_o
    indexer:  (layers typed "full") q_i = c_q W_iq -> heads x dim;
              k_i = LayerNorm(x W_ik); RoPE on the first `rope` of dim;
              w = x W_iw / sqrt(heads) / sqrt(dim);
              I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s]), s <= t;
              S_t = the keys whose I[t, s] is at or above the topk-th highest
              (every key while t + 1 <= topk).  A layer typed "shared" uses
              the S_t of the nearest "full" layer below.
    router:   s = sigmoid(x W_g); the top_k highest of s + b chosen;
              g_e = scale * s_e / sum_chosen s;  FFN(x) = sum over the chosen
              experts THAT ARE HELD of g_e SwiGLU_e(x), + SwiGLU_shared(x)
    RoPE:     interleaved (adjacent pairs), theta as given, no scaling.

Token ids are 1-based, as the program's table takes them.  Attention and
the indexer run a block of queries at a time (``lax.map``), the experts
one at a time: the arithmetic is the same, the arrays fit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_static
@dataclass(frozen=True)
class Hyper:
    """The numbers of the configuration that no array's shape gives; a
    static leaf of the weights' tree."""

    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    index_n_heads: int
    index_topk: int
    rope_theta: float
    eps: float
    top_k: int
    scale: float
    held: Tuple[int, int]           # first held expert, how many
    query_block: int = 128
    #: "" or a dtype's name: every matrix is rounded to it first, then
    #: widened (how far a forward in a narrower precision lands from this
    #: one is what a cell's margin is set against; PERF.md)
    weights_as: str = ""


def f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(w)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * f32(w) + f32(b)


def rope(x, pos, theta):
    """(..., T, D) or (..., T, H, D) with ``pos`` (T,): the pair (2i, 2i+1)
    turned by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]       # (T, D/2)
    if x.ndim == 4:
        ang = ang[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out_even = even * jnp.cos(ang) - odd * jnp.sin(ang)
    out_odd = even * jnp.sin(ang) + odd * jnp.cos(ang)
    return jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape)


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ f32(w1)) * (x @ f32(w3))) @ f32(w2)


def by_query_blocks(fn, t, size, *rows):
    """``fn`` over blocks of ``size`` rows of arrays (B, T, ...), joined."""
    n = -(-t // size)
    pad = n * size - t

    def split(a):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((a.shape[0], n, size) + a.shape[2:]),
                            1, 0)

    out = jax.lax.map(lambda blk: fn(*blk), tuple(split(a) for a in rows))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], n * size) + out.shape[3:])[:, :t]


def selected(hy: Hyper, q_i, k_i, w_i, t):
    """S_t as a mask, a block of queries at a time: the function returned
    takes the block's indexer queries (B, size, heads, dim), head weights
    and positions and gives (B, size, T) bool."""
    key_pos = jnp.arange(t)

    def mask(qi, wi, qpos):
        dots = jnp.einsum("bqhd,bsd->bqhs", qi, k_i)
        score = jnp.sum(jax.nn.relu(dots) * wi[..., None], axis=2)
        causal = key_pos[None, None, :] <= qpos[:, :, None]
        score = jnp.where(causal, score, -jnp.inf)
        if t <= hy.index_topk:
            return causal
        kth = jax.lax.top_k(score, hy.index_topk)[0][..., -1:]
        return causal & (score >= kth)

    return mask


def attention(hy: Hyper, p, x, n_head, chosen):
    """``chosen``: (q_i, k_i, w_i) of the layer whose selection applies."""
    b, t, _ = x.shape
    pos = jnp.arange(t)
    dn, dr, dv = hy.qk_nope_head_dim, hy.qk_rope_head_dim, hy.v_head_dim
    c_q = rms_norm(x @ f32(p["q_a"]), p["q_a_norm"], hy.eps)
    q = (c_q @ f32(p["q_b"])).reshape(b, t, n_head, dn + dr)
    q_nope, q_r = q[..., :dn], rope(q[..., dn:], pos, hy.rope_theta)
    kv_a = x @ f32(p["kv_a"])
    r = kv_a.shape[-1] - dr
    c_kv = rms_norm(kv_a[..., :r], p["kv_a_norm"], hy.eps)
    k_r = rope(kv_a[..., r:], pos, hy.rope_theta)
    kv = (c_kv @ f32(p["kv_b"])).reshape(b, t, n_head, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    if "index_wq" in p:
        hi = hy.index_n_heads
        q_i = (c_q @ f32(p["index_wq"])).reshape(b, t, hi, -1)
        di = q_i.shape[-1]
        k_i = layer_norm(x @ f32(p["index_wk"]), p["index_k_norm_w"],
                         p["index_k_norm_b"], hy.eps)
        q_i = jnp.concatenate([rope(q_i[..., :dr], pos, hy.rope_theta),
                               q_i[..., dr:]], axis=-1)
        k_i = jnp.concatenate([rope(k_i[..., :dr], pos, hy.rope_theta),
                               k_i[..., dr:]], axis=-1)
        w_i = x @ f32(p["index_ww"]) / math.sqrt(hi) / math.sqrt(di)
        chosen = (q_i, k_i, w_i)
    q_i, k_i, w_i = chosen
    mask_of = selected(hy, q_i, k_i, w_i, t)

    def block(qn, qr, qi, wi, qpos):
        s = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope)
             + jnp.einsum("bqhd,bkd->bhqk", qr, k_r)) / math.sqrt(dn + dr)
        s = jnp.where(mask_of(qi, wi, qpos)[:, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    qpos = jnp.broadcast_to(pos[None], (b, t))
    ctx = by_query_blocks(block, t, min(t, hy.query_block), q_nope, q_r,
                          q_i, w_i, qpos)
    return ctx.reshape(b, t, n_head * dv) @ f32(p["o"]), chosen


def experts(hy: Hyper, p, x):
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    s = jax.nn.sigmoid(flat @ f32(p["gate"]))
    _, chosen = jax.lax.top_k(s + f32(p["bias"]), hy.top_k)
    s_chosen = jnp.take_along_axis(s, chosen, axis=1)
    g = hy.scale * s_chosen / jnp.sum(s_chosen, axis=1, keepdims=True)
    out = swiglu(flat, p["shared_w1"], p["shared_w3"], p["shared_w2"])
    first, count = hy.held
    for e in range(count):
        g_e = jnp.sum(jnp.where(chosen == first + e, g, 0.0), axis=1)
        out = out + g_e[:, None] * swiglu(flat, p["w1"][e], p["w3"][e],
                                          p["w2"][e])
    return out.reshape(b, t, d)


def log_probs(weights, tokens, n_head: int):
    """(B, T) 1-based token ids -> (B, T, vocab) log-probabilities."""
    hy = weights["hyper"]
    if hy.weights_as:
        weights = jax.tree_util.tree_map(
            lambda a: a.astype(hy.weights_as).astype(a.dtype)
            if a.ndim >= 2 else a, weights)
    with jax.default_matmul_precision("highest"):
        x = f32(jnp.take(weights["embed"], tokens.astype(jnp.int32) - 1,
                         axis=0))
        chosen = None
        for p in weights["layers"]:
            a, chosen = attention(hy, p, rms_norm(x, p["ln1"], hy.eps),
                                  n_head, chosen)
            x = x + a
            h = rms_norm(x, p["ln2"], hy.eps)
            if "gate" in p:
                x = x + experts(hy, p, h)
            else:
                x = x + swiglu(h, p["w1"], p["w3"], p["w2"])
        x = rms_norm(x, weights["ln_f"], hy.eps)
        return jax.nn.log_softmax(x @ f32(weights["head"]), axis=-1)


def next_token_loss(logp, targets):
    """Mean negative log-likelihood of (B, T) 1-based targets."""
    picked = jnp.take_along_axis(
        logp, (targets.astype(jnp.int32) - 1)[..., None], axis=-1)
    return -jnp.mean(picked)
