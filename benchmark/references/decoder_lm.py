"""Plain reference: a pre-norm decoder-only transformer in float32.

Written from the papers, with none of the program's code and no kernel:
Vaswani et al. 2017 (scaled dot-product attention, sinusoidal positions,
sec. 3.2 and 3.5) in the pre-norm arrangement of OPT (Zhang et al. 2022,
``do_layer_norm_before``): x + Attn(LN(x)), then x + FFN(LN(x)) with a
ReLU FFN, a final LayerNorm and a linear head with bias.  Every product
runs at ``jax.default_matmul_precision("highest")``: on a TPU a float32
product otherwise runs in bf16 passes.

Departures from OPT, both the program's and stated in the configuration:
sinusoidal positions instead of a learned table, an untied head.
Token ids are 1-based, as the program's table takes them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sinusoid(seq_len: int, d: int, offset: int = 0):
    pos = jnp.arange(offset, offset + seq_len, dtype=jnp.float32)[:, None]
    i = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, i / d)
    pe = jnp.zeros((seq_len, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    return pe.at[:, 1::2].set(jnp.cos(angle))


def layer_norm(x, w, b, eps: float = 1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def attention(x, p, n_head: int):
    b, t, d = x.shape
    dh = d // n_head

    def heads(y):
        return y.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)

    q = heads(x @ p["wq"] + p["bq"])
    k = heads(x @ p["wk"] + p["bk"])
    v = heads(x @ p["wv"] + p["bv"])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(dh)
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", a, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
    return ctx @ p["wo"] + p["bo"]


def log_probs(weights, tokens, n_head: int):
    """(B, T) 1-based token ids -> (B, T, vocab) log-probabilities."""
    with jax.default_matmul_precision("highest"):
        d = weights["embed"].shape[1]
        x = jnp.take(weights["embed"], tokens.astype(jnp.int32) - 1, axis=0)
        x = x.astype(jnp.float32) + sinusoid(tokens.shape[1], d)[None]
        for p in weights["layers"]:
            x = x + attention(layer_norm(x, p["ln1_w"], p["ln1_b"]), p,
                              n_head)
            h = layer_norm(x, p["ln2_w"], p["ln2_b"])
            x = x + jax.nn.relu(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        x = layer_norm(x, weights["lnf_w"], weights["lnf_b"])
        logits = x @ weights["head_w"] + weights["head_b"]
        return jax.nn.log_softmax(logits, axis=-1)


def next_token_loss(logp, targets):
    """Mean negative log-likelihood of (B, T) 1-based targets."""
    picked = jnp.take_along_axis(
        logp, (targets.astype(jnp.int32) - 1)[..., None], axis=-1)
    return -jnp.mean(picked)
