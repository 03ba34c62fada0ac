"""Multi-head latent attention with a learned sparse selection (the
``glm_moe_dsa`` / DeepSeek-V3.2 block), and the small layers it stands on:
RMS norm, interleaved rotary positions, SwiGLU.

Everything here is a pure function of a parameter dict; the Modules are
thin shells that create those dicts.  One attention function serves the
module's ``apply`` (no cache), the server's prefill (the sequence attends
itself, and its latents are written into the pools) and its decode (one
token a sequence attends what the block table holds):

- **Latent attention** (DeepSeek-V2/V3 MLA).  Queries go through a
  low-rank latent ``c_q``; keys and values are up-projections of ONE
  ``kv_lora_rank`` latent per token plus one rotary key shared by all
  heads.  The cache holds that latent row (``kv_lora_rank +
  qk_rope_head_dim`` values a token a layer), not keys and values.
- **Indexer** (DeepSeek-V3.2 lightning indexer).  A layer typed ``full``
  scores every earlier key with a few light heads,
  ``I[t, s] = sum_j w[t, j] relu(q_j[t] . k[s])``, and attention runs over
  the ``index_topk`` highest.  A layer typed ``shared`` has no indexer
  and reuses the selection of the nearest ``full`` layer below
  (``Selection``).
- **Two cores.**  Over its own sequence (``apply``, prefill) keys and
  values are expanded once and attention runs in query blocks under the
  selection's mask, so no ``(T, T)`` array is ever whole.  Through the
  block table (decode) the top ``index_topk`` latent rows are gathered
  and ``kv_b`` is absorbed into the query and the output, so no key or
  value is ever expanded; only the live slots select and gather, a few
  at a time in a device loop.

Weights may be bf16: every product accumulates in float32; norm
statistics, softmax and the indexer's scores are float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Module

_NEG = -1e30        # finite: a fully masked row softmaxes to junk, not NaN
_HIGHEST = jax.lax.Precision.HIGHEST


def matmul(x, w, out_dtype=None):
    """``x @ w`` at the weight's dtype with float32 accumulation."""
    y = jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)
    return y if out_dtype is None else y.astype(out_dtype)


def rms_norm(x, weight, eps: float):
    """``x / rms(x) * weight``, statistics in float32; float32 out."""
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * weight.astype(jnp.float32)


def layer_norm(x, weight, bias, eps: float):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
            + bias.astype(jnp.float32))


def rotary_interleaved(x, positions, theta: float):
    """Rotary positions over the last axis of ``x``, adjacent pairs
    ``(x[2i], x[2i+1])`` turned by ``positions * theta**(-2i/D)``.
    ``x`` is ``(B, T, D)`` or ``(B, T, H, D)``, ``positions`` ``(B, T)``;
    float32 out."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv       # (B, T, D/2)
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


#: rows of one SwiGLU pass: a long prefill's float32 hidden activations
#: (rows x width, twice) are never whole
_FFN_ROWS = 4096


def swiglu(p, x, out_dtype=jnp.float32):
    """``w2(silu(w1 x) * w3 x)`` over the last axis, ``_FFN_ROWS`` rows
    at a time."""

    def ffn(rows):
        h = jax.nn.silu(matmul(rows, p["w1"])) * matmul(rows, p["w3"])
        return matmul(h, p["w2"], out_dtype)

    flat = x.reshape(-1, x.shape[-1])
    n = flat.shape[0]
    if n <= _FFN_ROWS:
        return ffn(x)
    chunks = -(-n // _FFN_ROWS)
    flat = jnp.pad(flat, [(0, chunks * _FFN_ROWS - n), (0, 0)])
    out = jax.lax.map(ffn, flat.reshape(chunks, _FFN_ROWS, -1))
    return out.reshape(chunks * _FFN_ROWS, -1)[:n].reshape(
        x.shape[:-1] + (out.shape[-1],))


def _normal(key, shape, std, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def norm_weight(key, n: int):
    """A norm's scale, seeded near one and not at it: a forward that left
    the scale out would not match one that applied it."""
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


class RMSNorm(Module):
    """Root-mean-square norm over the feature axis (no mean, no bias)."""

    def __init__(self, d_model: int, eps: float = 1e-5, name=None):
        super().__init__(name)
        self.d_model, self.eps = int(d_model), float(eps)

    def _init_params(self, rng):
        return {"weight": norm_weight(rng, self.d_model)}

    def apply(self, params, input, state, training=False, rng=None):
        return rms_norm(input, params["weight"], self.eps), state


class SwiGLU(Module):
    """Gated FFN ``w2(silu(w1 x) * w3 x)`` without biases.  ``out_scale``
    multiplies the standard deviation ``w2`` is drawn at (a decoder's
    depth-scaled initialisation: ``glm_moe_dsa_lm(init_depth=...)``)."""

    def __init__(self, d_model: int, width: int, out_scale: float = 1.0,
                 name=None):
        super().__init__(name)
        self.d_model, self.width = int(d_model), int(width)
        self.out_scale = float(out_scale)

    def _init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        d, f = self.d_model, self.width
        return {"w1": _normal(k1, (d, f), 1 / math.sqrt(d)),
                "w3": _normal(k3, (d, f), 1 / math.sqrt(d)),
                "w2": _normal(k2, (f, d), self.out_scale / math.sqrt(f))}

    def apply(self, params, input, state, training=False, rng=None):
        return swiglu(params, input), state


# ---------------------------------------------------------------------------
# the paged pools, as a step sees them
# ---------------------------------------------------------------------------


class LatentCache(NamedTuple):
    """The two pools of a latent-attention server inside ONE step: where
    this step's rows go, and (decode) the tables to read through.

    ``latent``: ``(layers, blocks, block_size, latent_row_width)``;
    ``index``: ``(indexer layers, blocks, block_size, index_head_dim)``;
    ``write_block`` / ``write_slot``: ``(B, T)`` address of each new
    token's row (the dump block for padding and idle slots);
    ``tables``: ``(B, max_blocks)`` block tables, or None when the
    sequence attends itself (prefill)."""

    latent: Any
    index: Any
    write_block: Any
    write_slot: Any
    tables: Optional[Any] = None

    def put(self, kind: str, layer: int, rows) -> "LatentCache":
        pool = getattr(self, kind)
        pool = pool.at[layer, self.write_block, self.write_slot].set(
            rows.astype(pool.dtype))
        return self._replace(**{kind: pool})


class Selection(NamedTuple):
    """What a ``full`` layer's indexer chose, for itself and for the
    ``shared`` layers above it.  Over a sequence that attends itself the
    choice is kept as each query's threshold (``threshold``: the sortable
    key of its ``index_topk``-th highest score) beside what the scores
    are made of (the query latents ``c_q`` with the indexer's ``wq``, its
    keys and head weights), and a layer recomputes one query block's
    scores to get that block's mask: a ``(T, T)`` mask or ``(T, topk)``
    rows are never whole.  Through the block table it is the chosen
    context positions themselves (``positions``, ``valid``)."""

    c_q: Any = None          # (B, T, q_lora_rank)
    wq: Any = None           # the indexer's query projection
    k: Any = None            # (B, T, Di)
    w: Any = None            # (B, T, Hi) float32
    threshold: Any = None    # (B, T) int32
    positions: Any = None    # (B, K) int32
    valid: Any = None        # (B, K) bool


def _sortable(scores):
    """float32 -> int32 keys in the same order (``-0.0`` below ``0.0``)."""
    i = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))


def kth_largest(keys, k: int):
    """The ``k``-th largest int32 key of each row (last axis), exactly:
    a descent over the 32 bits that counts the keys at or above each
    candidate.  Elementwise passes and sums only, so it costs 32 reads of
    the row where a sort of 16k keys costs far more on a TPU.  A row with
    fewer than ``k`` keys above the floor returns the floor."""
    u = jax.lax.bitcast_convert_type(keys, jnp.uint32) ^ jnp.uint32(1 << 31)

    def step(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(u >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, step,
                          jnp.zeros(keys.shape[:-1], jnp.uint32))
    return jax.lax.bitcast_convert_type(t ^ jnp.uint32(1 << 31), jnp.int32)


def index_scores(q, k, w):
    """``I[b, t, s] = sum_j w[b, t, j] relu(q[b, t, j] . k[b, s])`` in
    float32; ``q`` (B, Tq, Hi, Di), ``k`` (B, S, Di), ``w`` (B, Tq, Hi)."""
    dots = jnp.einsum("bqhd,bsd->bqhs", q, k,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)


def query_block(t: int) -> int:
    """Rows of one query block over a sequence of ``t`` keys: the block's
    float32 scores (rows x keys, a head) stay near 2M values."""
    if t <= 2048:
        return t
    return max(128, (1 << 21) // t)


#: a long sequence's queries go in this many groups by position, each
#: against the keys up to its own end: of the causal mask's upper half,
#: 3/8 of all scores is then never computed
_KEY_GROUPS = 4


def key_groups(t: int):
    """``[(first query, end)]``: the query ranges of a sequence of ``t``,
    each attending keys ``[0, end)``.  One range for a short sequence."""
    n = _KEY_GROUPS if t >= 4096 and t % (_KEY_GROUPS * 128) == 0 else 1
    return [(g * t // n, (g + 1) * t // n) for g in range(n)]


def query_blocks(t: int) -> int:
    """Query blocks a sequence of ``t`` attends itself in."""
    return sum(-(-(hi - lo) // query_block(hi)) for lo, hi in key_groups(t))


def _map_query_blocks(fn, qb: int, *rows):
    """``fn(block arrays...) -> pytree`` over blocks of ``qb`` rows of
    arrays whose axis 1 is the sequence; results concatenated back along
    it."""
    t = rows[0].shape[1]
    n = -(-t // qb)
    if n == 1:
        return fn(*rows)
    pad = n * qb - t

    def split(a):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((a.shape[0], n, qb) + a.shape[2:]),
                            1, 0)

    out = jax.lax.map(lambda blk: fn(*blk), tuple(split(a) for a in rows))

    def join(o):
        o = jnp.moveaxis(o, 0, 1)
        return o.reshape((o.shape[0], n * qb) + o.shape[3:])[:, :t]

    return jax.tree_util.tree_map(join, out)


def _over_key_groups(make_block, t: int, *rows):
    """``make_block(end) -> fn`` for the queries that attend keys ``[0,
    end)``; every group's query blocks, joined along the sequence."""
    parts = [_map_query_blocks(make_block(hi), query_block(hi),
                               *(r[:, lo:hi] for r in rows))
             for lo, hi in key_groups(t)]
    if len(parts) == 1:
        return parts[0]
    return jax.tree_util.tree_map(
        lambda *p: jnp.concatenate(p, axis=1), *parts)


class LatentAttentionSpec(NamedTuple):
    d_model: int
    n_head: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    rope_theta: float
    eps: float

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """Values of one cached latent row: ``latent_width`` rounded up to
        the TPU's 128 lanes.  A pool whose rows are no multiple of 128 is
        laid out by the TPU with its BLOCK axis minor (no padding that
        way), which every step would then undo and redo by two copies of
        the whole pool; the padding is what a row-major pool costs there
        anyway."""
        return -(-self.latent_width // 128) * 128


def init_attention(spec: LatentAttentionSpec, rng,
                   out_scale: float = 1.0) -> Dict[str, Any]:
    """``out_scale`` multiplies the standard deviation ``o`` is drawn at."""
    ks = jax.random.split(rng, 7)
    s, h = spec, spec.n_head
    qk = s.qk_nope_head_dim + s.qk_rope_head_dim
    return {
        "q_a": _normal(ks[0], (s.d_model, s.q_lora_rank),
                       1 / math.sqrt(s.d_model)),
        "q_a_norm": norm_weight(ks[1], s.q_lora_rank),
        "q_b": _normal(ks[2], (s.q_lora_rank, h * qk),
                       1 / math.sqrt(s.q_lora_rank)),
        "kv_a": _normal(ks[3], (s.d_model, s.latent_width),
                        1 / math.sqrt(s.d_model)),
        "kv_a_norm": norm_weight(ks[4], s.kv_lora_rank),
        "kv_b": _normal(ks[5], (s.kv_lora_rank,
                                h * (s.qk_nope_head_dim + s.v_head_dim)),
                        1 / math.sqrt(s.kv_lora_rank)),
        "o": _normal(ks[6], (h * s.v_head_dim, s.d_model),
                     out_scale / math.sqrt(h * s.v_head_dim)),
    }


def init_indexer(spec: LatentAttentionSpec, rng) -> Dict[str, Any]:
    ks = jax.random.split(rng, 5)
    s = spec
    return {
        "wq": _normal(ks[0], (s.q_lora_rank,
                              s.index_n_heads * s.index_head_dim),
                      1 / math.sqrt(s.q_lora_rank)),
        "wk": _normal(ks[1], (s.d_model, s.index_head_dim),
                      1 / math.sqrt(s.d_model)),
        "k_norm_w": norm_weight(ks[2], s.index_head_dim),
        "k_norm_b": 0.1 * jax.random.normal(ks[3], (s.index_head_dim,)),
        "ww": _normal(ks[4], (s.d_model, s.index_n_heads),
                      1 / math.sqrt(s.d_model)),
    }


def _rope_head(x, positions, spec):
    """Rotary on the first ``qk_rope_head_dim`` of the indexer's head."""
    r = spec.qk_rope_head_dim
    return jnp.concatenate(
        [rotary_interleaved(x[..., :r], positions, spec.rope_theta),
         x[..., r:].astype(jnp.float32)], axis=-1)


def _queries(spec, p, c_q, positions):
    """Query latents (B, T, q_lora_rank) -> ``(q_nope, q_rope)`` per head,
    at the weights' dtype.  The weight is split, not the product: no copy
    of a (T, H, nope + rope) array."""
    b, t = positions.shape
    h, dn, dr = spec.n_head, spec.qk_nope_head_dim, spec.qk_rope_head_dim
    dtype = p["q_b"].dtype
    w_qb = p["q_b"].reshape(-1, h, dn + dr)
    q_nope = matmul(c_q, w_qb[..., :dn].reshape(-1, h * dn),
                    dtype).reshape(b, t, h, dn)
    q_rope = rotary_interleaved(
        matmul(c_q, w_qb[..., dn:].reshape(-1, h * dr)).reshape(b, t, h, dr),
        positions, spec.rope_theta).astype(dtype)
    return q_nope, q_rope


def _index_queries(spec, wq, c_q, positions):
    b, t = positions.shape
    return _rope_head(
        matmul(c_q, wq).reshape(b, t, spec.index_n_heads,
                                spec.index_head_dim),
        positions, spec).astype(wq.dtype)


def _select_own(spec, sel: Selection, positions) -> Selection:
    """Each query's threshold over its own sequence, a query block at a
    time."""

    def make(end):
        k, kpos = sel.k[:, :end], positions[:, :end]

        def block(c_q, w, qpos):
            q = _index_queries(spec, sel.wq, c_q, qpos)
            keys = _sortable(jnp.where(
                kpos[:, None, :] <= qpos[:, :, None],
                index_scores(q, k, w), -jnp.inf))
            return kth_largest(keys, spec.index_topk)

        return block

    thr = _over_key_groups(make, positions.shape[1], sel.c_q, sel.w,
                           positions)
    return sel._replace(threshold=thr)


def _attend_own(spec, p, c_q, c_kv, k_rope, positions,
                sel: Optional[Selection]):
    """The sequence attends itself: keys and values expanded once, then a
    block of queries at a time (projected from their latents, attended
    under the causal mask and the selection's, and projected out), so
    nothing of (T, H, ...) but keys and values is ever whole."""
    b, t = positions.shape
    h, dn, dv = spec.n_head, spec.qk_nope_head_dim, spec.v_head_dim
    dtype = p["kv_b"].dtype
    with jax.named_scope("kv_expand"):
        w_kvb = p["kv_b"].reshape(-1, h, dn + dv)
        k_nope = matmul(c_kv, w_kvb[..., :dn].reshape(-1, h * dn),
                        dtype).reshape(b, t, h, dn)
        v = matmul(c_kv, w_kvb[..., dn:].reshape(-1, h * dv),
                   dtype).reshape(b, t, h, dv)
        k_rope = k_rope.astype(dtype)
    scale = 1.0 / math.sqrt(dn + spec.qk_rope_head_dim)

    def make(end):
        kn, kr, vv = k_nope[:, :end], k_rope[:, :end], v[:, :end]
        kpos = positions[:, :end]
        ki = None if sel is None else sel.k[:, :end]

        def block(c_q, qpos, *chosen):
            with jax.named_scope("q_latent"):
                qn, qr = _queries(spec, p, c_q, qpos)
            mask = kpos[:, None, :] <= qpos[:, :, None]          # (B, QB, T)
            if chosen:
                with jax.named_scope("select"):
                    sel_cq, wi, thr = chosen
                    qi = _index_queries(spec, sel.wq, sel_cq, qpos)
                    keys = _sortable(jnp.where(
                        mask, index_scores(qi, ki, wi), -jnp.inf))
                    mask = mask & (keys >= thr[..., None])
            with jax.named_scope("core"):
                s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bqhd,bkd->bhqk", qr, kr,
                                  preferred_element_type=jnp.float32))
                s = jnp.where(mask[:, None], s * scale, _NEG)
                # softmax with the division moved behind the product: the
                # (H, QB, T) scores are read twice and written once more,
                # as bf16, and never normalised as a whole
                e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                o = jnp.einsum("bhqk,bkhd->bqhd", e.astype(dtype), vv,
                               preferred_element_type=jnp.float32)
                o = o / jnp.sum(e, axis=-1).transpose(0, 2, 1)[..., None]
            with jax.named_scope("out"):
                return matmul(o.reshape(o.shape[:2] + (h * dv,)), p["o"])

        return block

    rows = (c_q, positions)
    if sel is not None:
        rows += (sel.c_q, sel.w, sel.threshold)
    return _over_key_groups(make, t, *rows)


#: live slots one trip of the decode step's latent attention reads (their
#: index keys, scores and top-k, then their selected latent rows).  The
#: smallest chunk whose step at 16 live slots is within 3 % of reading
#: every slot at once: on the v5e, the GLM-5.2 cell's decode step (16
#: slots, 2,048 of 16,384 rows, five layers) took 12.78 ms reading every
#: slot, and with chunks of 1 / 2 / 4 / 8 / 16 took 14.75 / 13.99 / 13.30
#: / 12.63 / 13.21 ms at 16 live slots and 8.10 / 8.14 / 8.62 / 8.32 /
#: 11.36 at 5 (10.93 reading every slot): a trip costs a sort and a few
#: small gathers whatever its rows (PERF.md 6, PR 36)
_SLOT_CHUNK = 8


def slot_chunk(batch: int) -> int:
    """Slots one trip of the decode step's loop over live slots reads."""
    return min(_SLOT_CHUNK, batch)


def live_trips(active, chunk: int, xp=jnp):
    """Trips of the decode step's loop over live slots: ``ceil(live /
    chunk)``, at least one.  The step calls it on its ``valid``
    (``xp=jnp``) and the scheduler on the host copy of the same
    (``xp=np``), which is how ``LM/kv_rows_fetched`` counts what the
    device read without pulling anything."""
    live = xp.sum(active, dtype=xp.int32)
    return xp.maximum((live + chunk - 1) // chunk, 1)


def _select_table(spec, q, w, pool, slot: int, tables, positions, valid):
    """``(positions, valid)``, each ``(B, K)``: the ``index_topk`` context
    positions of highest index score among those at or before each
    sequence's position (all of them while the table holds no more than
    ``index_topk``).  ``q`` (B, 1, Hi, Di) and ``w`` (B, 1, Hi) are the
    indexer's queries and head weights; its keys are ``slot`` of the
    index ``pool``, read through ``tables``."""
    b = positions.shape[0]
    bs = pool.shape[2]
    s_len = tables.shape[1] * bs
    ctx = jnp.arange(s_len)[None, :]
    live = (ctx <= positions) & valid                            # (B, S)
    if s_len <= spec.index_topk:
        return jnp.broadcast_to(ctx, (b, s_len)), live
    k_ctx = pool[slot, tables].reshape(b, s_len, -1)
    scores = jnp.where(live, index_scores(q, k_ctx, w)[:, 0], -jnp.inf)
    top, idx = jax.lax.top_k(scores, spec.index_topk)
    return idx, top > -jnp.inf


def _attend_rows(spec, pool, layer: int, tables, q_abs, q_rope, chosen,
                 valid):
    """``(B, H, kv_lora_rank)`` float32: the latent rows at the context
    positions ``chosen`` (B, K), read through ``tables``, attended by the
    absorbed queries ``q_abs`` (B, H, kv_lora_rank) and ``q_rope`` (B, H,
    rope)."""
    r, dtype = spec.kv_lora_rank, q_abs.dtype
    bs = pool.shape[2]
    with jax.named_scope("latent_gather"):
        blk = jnp.take_along_axis(tables, chosen // bs, axis=1)
        rows = pool[layer, blk, chosen % bs]                     # (B, K, row)
        c_rows = rows[..., :r]
        kr_rows = rows[..., r:r + spec.qk_rope_head_dim]
    with jax.named_scope("core"):
        s = (jnp.einsum("bhr,bkr->bhk", q_abs, c_rows,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bkd->bhk", q_rope, kr_rows,
                          preferred_element_type=jnp.float32))
        s = s / math.sqrt(spec.qk_nope_head_dim + spec.qk_rope_head_dim)
        s = jnp.where(valid[:, None, :], s, _NEG)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhk,bkr->bhr", a.astype(dtype), c_rows,
                          preferred_element_type=jnp.float32)


def _attend_table(spec, p, c_q, positions, cache: LatentCache, layer: int,
                  sel: Selection, valid, index_slot: Optional[int]):
    """One token a sequence attends the rows its selection names, read
    through the block table; ``kv_b`` absorbed into query and output.
    ``index_slot``: a layer with an indexer chooses those rows itself,
    from that slot of the index pool; None: ``sel`` names them.
    ``-> (out (B, 1, d), selection)``.

    Only the live slots (``valid``) read anything.  The projections run
    on every slot (weight reads, which rows cost nothing); the selection
    and the attention over the chosen rows run in a device loop over
    chunks of :func:`slot_chunk` slots, live ones first, for
    :func:`live_trips` trips: the rows read follow the live slots, in one
    compiled program.  A slot no trip reaches keeps an empty selection
    and an output of zeros, which the dump block and the host discard."""
    b = c_q.shape[0]
    h, dn, dv, r = (spec.n_head, spec.qk_nope_head_dim, spec.v_head_dim,
                    spec.kv_lora_rank)
    dtype = p["kv_b"].dtype
    w_kvb = p["kv_b"].reshape(r, h, dn + dv)
    with jax.named_scope("q_latent"):
        q_nope, q_rope = _queries(spec, p, c_q, positions)
    with jax.named_scope("core"):
        q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_kvb[..., :dn],
                           preferred_element_type=jnp.float32).astype(dtype)
    choose = index_slot is not None
    if choose:
        with jax.named_scope("select"):
            q_i = _index_queries(spec, sel.wq, sel.c_q, positions)
    chunk = slot_chunk(b)
    live = valid[:, 0]
    # the live slots first, in slot order; the padding (slot b) is read
    # as the last slot and written nowhere
    order = jnp.pad(jnp.argsort(~live, stable=True), (0, -b % chunk),
                    constant_values=b)

    def trip(i, carry):
        slots = jax.lax.dynamic_slice_in_dim(order, i * chunk, chunk)
        at = jnp.minimum(slots, b - 1)
        tables = cache.tables[at]
        if choose:
            with jax.named_scope("select"):
                chosen, ok = _select_table(spec, q_i[at], sel.w[at],
                                           cache.index, index_slot, tables,
                                           positions[at], valid[at])
        else:
            chosen, ok = sel.positions[at], sel.valid[at]
        o_lat = _attend_rows(spec, cache.latent, layer, tables, q_abs[at],
                             q_rope[at, 0], chosen, ok)
        done = (o_lat, chosen, ok) if choose else (o_lat,)
        return tuple(buf.at[slots].set(v, mode="drop")
                     for buf, v in zip(carry, done))

    k = min(spec.index_topk, cache.tables.shape[1] * cache.latent.shape[2])
    init = (jnp.zeros((b, h, r), jnp.float32),)
    if choose:
        init += (jnp.zeros((b, k), jnp.int32), jnp.zeros((b, k), bool))
    carry = jax.lax.fori_loop(0, live_trips(live, chunk), trip, init)
    if choose:
        sel = Selection(positions=carry[1], valid=carry[2])
    with jax.named_scope("core"):
        o = jnp.einsum("bhr,rhd->bhd", carry[0].astype(dtype),
                       w_kvb[..., dn:], preferred_element_type=jnp.float32)
    with jax.named_scope("out"):
        return matmul(o.reshape(b, 1, h * dv), p["o"]), sel


def latent_attention(spec: LatentAttentionSpec, p, indexer, x, positions,
                     cache: Optional[LatentCache] = None, layer: int = 0,
                     index_slot: int = 0, sel: Optional[Selection] = None,
                     valid=None):
    """``x`` (B, T, d) normed activations at ``positions`` (B, T) ->
    ``(out (B, T, d) float32, cache, selection)``.

    ``indexer``: this layer's indexer parameters, or None for a layer
    typed ``shared``, which attends under ``sel`` as handed up from the
    ``full`` layer below.  ``cache``: None (no cache), or the step's view
    of the pools: this layer's latent rows go to ``layer`` of the latent
    pool and its indexer keys to ``index_slot`` of the index pool; with
    ``cache.tables`` the context is read through them (T is 1).
    ``valid`` (B, T) marks real tokens (decode: active slots)."""
    s = spec
    b, t = positions.shape
    dtype = p["q_a"].dtype
    paged = cache is not None and cache.tables is not None
    if valid is None:
        valid = jnp.ones((b, t), bool)
    with jax.named_scope("q_latent"):
        c_q = rms_norm(matmul(x, p["q_a"]), p["q_a_norm"],
                       s.eps).astype(dtype)
    with jax.named_scope("kv_latent"):
        kv = matmul(x, p["kv_a"])
        c_kv = rms_norm(kv[..., :s.kv_lora_rank], p["kv_a_norm"], s.eps)
        k_rope = rotary_interleaved(kv[..., s.kv_lora_rank:], positions,
                                    s.rope_theta)
        if cache is not None:
            pad = jnp.zeros((b, t, s.latent_row_width - s.latent_width))
            cache = cache.put("latent", layer, jnp.concatenate(
                [c_kv, k_rope, pad], axis=-1))
    if indexer is not None:
        with jax.named_scope("indexer"):
            k_i = _rope_head(
                layer_norm(matmul(x, indexer["wk"]), indexer["k_norm_w"],
                           indexer["k_norm_b"], s.eps),
                positions, s).astype(dtype)
            w_i = jnp.matmul(
                x.astype(jnp.float32), indexer["ww"].astype(jnp.float32),
                precision=_HIGHEST) / math.sqrt(
                    s.index_n_heads * s.index_head_dim)
            if cache is not None:
                cache = cache.put("index", index_slot, k_i)
        sel = Selection(c_q=c_q, wq=indexer["wq"], k=k_i, w=w_i)
        if not paged:
            with jax.named_scope("select"):
                if t > s.index_topk:
                    sel = _select_own(s, sel, positions)
                else:
                    sel = None    # every earlier key is among the top
    if paged:
        if sel is None:
            raise ValueError("a layer typed shared needs the selection of "
                             "a full layer below it")
        out, sel = _attend_table(s, p, c_q, positions, cache, layer, sel,
                                 valid,
                                 None if indexer is None else index_slot)
    else:
        out = _attend_own(s, p, c_q, c_kv, k_rope, positions, sel)
    return out, cache, sel


class LatentAttention(Module):
    """Causal multi-head latent attention over ``(B, T, d_model)`` with
    its own indexer (``indexer=True``, a layer typed ``full``) or without
    (``shared``: :func:`latent_attention` is then handed a selection; the
    Module's own ``apply`` attends every earlier key)."""

    def __init__(self, spec: LatentAttentionSpec, indexer: bool = True,
                 name=None):
        super().__init__(name)
        self.spec, self.indexer = spec, bool(indexer)

    def _init_params(self, rng):
        k1, k2 = jax.random.split(rng)
        p = {"attn": init_attention(self.spec, k1)}
        if self.indexer:
            p["indexer"] = init_indexer(self.spec, k2)
        return p

    def apply(self, params, input, state, training=False, rng=None):
        b, t = input.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
        out, _, _ = latent_attention(self.spec, params["attn"],
                                     params.get("indexer"), input,
                                     positions)
        return out, state


__all__ = ["LatentAttention", "LatentAttentionSpec", "LatentCache",
           "RMSNorm", "Selection", "SwiGLU", "index_scores", "kth_largest",
           "latent_attention", "layer_norm", "live_trips", "matmul",
           "query_block", "query_blocks", "rms_norm", "rotary_interleaved",
           "slot_chunk", "swiglu"]
