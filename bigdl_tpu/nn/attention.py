"""Attention layers.

The reference has NO attention op (SURVEY §5.7: sequence handling is
``Recurrent`` unrolling only) — this module is the TPU-native long-context
extension the rebuild treats as first-class: a standard multi-head attention
whose sequence dimension can be sharded across the mesh's ``seq`` axis via
ring attention (``bigdl_tpu/parallel/ring_attention.py``).

Shapes follow (batch, time, dim); heads split the last dim.  All matmuls are
batched (B*H GEMMs) so XLA tiles them onto the MXU.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from bigdl_tpu.nn import init as init_methods
from bigdl_tpu.nn.module import Module


def _axis_bound(name: str) -> bool:
    """Trace-time check: is the named mesh axis currently bound (are we
    inside a shard_map/pmap over it)?"""
    try:
        jax.lax.axis_index(name)
        return True
    except Exception:
        return False


def scaled_dot_product_attention(q: jnp.ndarray, k: jnp.ndarray,
                                 v: jnp.ndarray,
                                 causal: bool = False,
                                 mask: Optional[jnp.ndarray] = None
                                 ) -> jnp.ndarray:
    """(B, T, H, Dh) q/k/v -> (B, T, H, Dh); softmax over the key axis."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    # finite mask value: a fully-masked row (all-padding) must softmax to
    # uniform junk rather than NaN (-inf rows give 0/0)
    neg_big = jnp.asarray(jnp.finfo(scores.dtype).min, scores.dtype)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        cm = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(cm[None, None], scores, neg_big)
    if mask is not None:
        scores = jnp.where(mask, scores, neg_big)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def paged_attention(q: jnp.ndarray, fetch, positions: jnp.ndarray,
                    active: jnp.ndarray, n_chunks, chunk: int
                    ) -> jnp.ndarray:
    """Decode-step attention over a paged-cache context, read a chunk at
    a time and only as far as the caller says.

    ``q`` is the current step's query, (B, T, H, Dh) with T=1 on the
    decode path; ``fetch(c)`` returns the (B, chunk, H, Dh) key and value
    rows of context positions ``c * chunk ... (c + 1) * chunk - 1``,
    gathered from the KV pool via each sequence's block table;
    ``positions`` (B,) is each sequence's last real position and
    ``active`` (B,) its slot's liveness, so the mask is made here and no
    (B, S) array over the table's whole capacity exists.  ``n_chunks`` is
    a traced scalar: a ``lax.fori_loop`` makes that many trips with a
    running softmax (maximum, normaliser, weighted sum), so the rows read
    follow the longest live context and not the table's capacity, in one
    compiled program.  The caller passes at least ``ceil((max live
    position + 1) / chunk)``; rows past a sequence's own position inside
    those chunks are masked as before: the finite mask value makes an
    invalid key's probability underflow to exactly 0.0, so the result is
    :func:`scaled_dot_product_attention` over the unpadded context up to
    the order of the sums: the decode-vs-full-forward parity proof leans
    on this.  A fully masked row (an inactive decode slot) softmaxes to
    uniform junk rather than NaN; its output is discarded on the host.

    Both products take their operands at the chunks' width (``q`` and
    the probabilities are cast to it) and accumulate in float32; the
    running softmax and the result are float32."""
    bsz, t, h, dh = q.shape
    neg_big = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)

    def body(c, carry):
        m, l, acc = carry
        k, v = fetch(c)
        with jax.named_scope("attn"):
            k_pos = c * chunk + jnp.arange(chunk)
            valid = (k_pos[None, :] <= positions[:, None]) & active[:, None]
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(k.dtype), k,
                           preferred_element_type=jnp.float32
                           ) / math.sqrt(dh)
            s = jnp.where(valid[:, None, None, :], s, neg_big)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = (alpha[..., None] * acc
                   + jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32))
        return m_new, l, acc

    with jax.named_scope("attn"):
        init = (jnp.full((bsz, h, t), neg_big),
                jnp.zeros((bsz, h, t), jnp.float32),
                jnp.zeros((bsz, h, t, dh), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_chunks, body, init)
    with jax.named_scope("attn"):
        return jnp.moveaxis(acc / l[..., None], 1, 2)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = False, chunk: int = 1024
                      ) -> jnp.ndarray:
    """Query-chunked attention in pure XLA: identical numerics to
    :func:`scaled_dot_product_attention`, O(T * chunk) score memory
    instead of O(T^2).

    A ``lax.scan`` walks the query blocks; each step attends its block
    against the FULL key/value (one big MXU-shaped matmul pair), and the
    step body is ``jax.checkpoint``-ed so the backward pass rematerializes
    each block's scores instead of saving all of them — without that, the
    scan VJP would stash every step's (B, H, chunk, T) probability matrix
    and reinstate the O(T^2) footprint.

    This is the single-chip fallback for shapes where the one-shot
    standard path's O(T^2) score residuals do not fit and the pallas
    kernel's constraints (head_dim % 128, TPU-only) don't hold.  For
    causal masks
    it still computes the fully-masked upper blocks (~2x the minimal
    FLOPs) — static shapes keep XLA happy; the pallas flash kernel is the
    path that skips them."""
    bsz, t, h, dh = q.shape
    tk = k.shape[1]
    if t % chunk != 0:
        raise ValueError(f"chunked attention needs T divisible by the "
                         f"chunk size: T={t}, chunk={chunk}")
    nq = t // chunk
    scale = 1.0 / math.sqrt(dh)
    neg_big = jnp.asarray(jnp.finfo(q.dtype).min, q.dtype)
    k_pos = jnp.arange(tk)
    # (nq, B, chunk, H, Dh) so scan's leading axis is the q-block index
    qb = jnp.moveaxis(q.reshape(bsz, nq, chunk, h, dh), 1, 0)

    @jax.checkpoint
    def step(_, qi):
        i, qc = qi
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc, k) * scale
        if causal:
            # bottom-right aligned like scaled_dot_product_attention's
            # tril(k=tk-tq): for Tq != Tkv, query i attends keys up to
            # i + (tk - t)
            q_pos = i * chunk + jnp.arange(chunk) + (tk - t)
            allowed = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(allowed[None, None], scores, neg_big)
        p = jax.nn.softmax(scores, axis=-1)
        return None, jnp.einsum("bhqk,bkhd->bqhd", p, v)
    _, ob = lax.scan(step, None, (jnp.arange(nq), qb))
    return jnp.moveaxis(ob, 0, 1).reshape(bsz, t, h, dh)


def _flash_block_sizes(t: int):
    """Tile sizes for the pallas flash kernel: the largest of 1024, 512,
    256 that divides the sequence length, else the kernel's stock 128.

    The ladder was chosen in round 5 on a v5e under another compiler
    (B1/H8/Dh128, fwd+bwd: 128-square tiles 32.6 ms at T8192, 512-square
    10.8 ms, 1024-square 8.4 ms — round-5 record, removed in PR 21).  On
    the current machine 1024-square fwd/dkv/dq tiles compile and run
    (``chip_smoke.py``, T2048); their speed against the other rungs is
    not measured there, and 2048-square tiles are untried."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    blk = 128
    for cand in (1024, 512, 256):
        if t % cand == 0:
            blk = cand
            break
    return BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk,
        block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)


class MultiHeadAttention(Module):
    """Self-attention over (B, T, D) input; table input (q_src, kv_src)
    gives cross-attention.

    ``flash``: opt-in TPU pallas flash-attention kernel with the tile
    sizes of :func:`_flash_block_sizes`.  In round 5 (another compiler;
    record removed in PR 21) flash won every shape tried in the full
    jitted train step, and at T16384 the one-shot standard path ran out
    of HBM on saved O(T^2) residuals beyond 2 layers (``chunk`` or
    per-block remat also recover that shape); its speed is not measured
    on the current machine.  Default (False) stays the standard path —
    it is bit-exact against the other paths, composes with the GSPMD
    head split (pallas kernels do not partition), and has no shape
    constraints; perf-critical dense training opts in (the benchmark's
    ``transformer_lm`` builder and ``chip_smoke.py`` do; on the chip the
    kernels read ``flash_roofline.train``, ``PERF.md``).  flash=True
    raises when the backend/shape constraints
    aren't met (TPU only, T % 128 == 0, head_dim % 128 == 0,
    self-attention with Tq == Tkv — the kernel's causal mask is
    top-left aligned, which diverges from the reference's
    bottom-right-aligned mask when Tq != Tkv).  Revisit per hardware
    generation.

    ``chunk=N``: the pure-XLA q-blockwise path (:func:`chunked_attention`)
    — same numerics as standard (incl. the bottom-right-aligned causal
    mask for Tq != Tkv), O(T*N) score memory; the second long-context
    path where pallas is unwanted (e.g. under the GSPMD head split,
    which pallas kernels cannot partition)."""

    # class-level defaults keep OLD pickled snapshots forward-loadable:
    # Module.__setstate__ dict-updates, so instances serialized before an
    # attribute existed fall through to these
    flash = False
    chunk: Optional[int] = None
    sequence_parallel: Optional[str] = None
    #: mesh-axis name for the EXPLICIT (shard_map) Megatron head split —
    #: the pipeline x tp composition; GSPMD meshes use tp_specs instead
    model_parallel: Optional[str] = None

    def __init__(self, hidden_size: int, n_head: int, causal: bool = False,
                 with_bias: bool = True, flash: bool = False,
                 chunk: Optional[int] = None, name=None):
        super().__init__(name)
        if hidden_size % n_head != 0:
            raise ValueError(f"hidden {hidden_size} % heads {n_head} != 0")
        if flash and chunk:
            raise ValueError("flash and chunk are alternative long-context "
                             "paths; pick one")
        self.hidden_size = hidden_size
        self.n_head = n_head
        self.head_dim = hidden_size // n_head
        self.causal = causal
        self.with_bias = with_bias
        self.flash = flash
        # chunk=N: q-blockwise scan attention (pure XLA; see
        # chunked_attention) — the second long-context path, for shapes
        # where one-shot O(T^2) breaks the backend and pallas is unwanted
        self.chunk = chunk
        # mesh-axis name for ring-attention sequence parallelism; the ring
        # path engages only while that named axis is bound (i.e. inside a
        # shard_map over the mesh's seq axis — DistriOptimizer sets this
        # for sequence-parallel training); plain forwards are unaffected
        self.sequence_parallel: Optional[str] = None

    def set_sequence_parallel(self, axis_name: Optional[str]
                              ) -> "MultiHeadAttention":
        if axis_name and self.flash:
            raise ValueError("flash kernel and ring sequence parallelism "
                             "are mutually exclusive")
        if axis_name and self.model_parallel:
            raise ValueError("pick one of model_parallel / "
                             "sequence_parallel per attention layer")
        self.sequence_parallel = axis_name
        self._jit_apply = None
        return self

    def set_model_parallel(self, axis_name: Optional[str]
                           ) -> "MultiHeadAttention":
        """Explicit Megatron head split over the named mesh axis (engages
        only while that axis is bound — the shard_map pipeline x tp step):
        wq/wk/wv are column-split so each device computes its local heads,
        wo is row-split with the pair's single psum on the output."""
        if axis_name and self.flash:
            raise ValueError("flash kernel is incompatible with the "
                             "Megatron head split (pallas kernels do not "
                             "partition)")
        if axis_name and self.sequence_parallel:
            raise ValueError("pick one of model_parallel / "
                             "sequence_parallel per attention layer")
        self.model_parallel = axis_name
        self._jit_apply = None
        return self

    def _flash_ok(self, q, k) -> bool:
        """Static (trace-time) eligibility for the pallas kernel.  Only
        explicit ``flash=True`` engages it (see class docstring)."""
        if not self.flash:
            return False
        try:
            from jax.experimental.pallas.ops.tpu import flash_attention  # noqa: F401
        except ImportError:
            ok = False
        else:
            ok = (jax.default_backend() == "tpu" and
                  q.shape[1] == k.shape[1] and
                  q.shape[1] % 128 == 0 and
                  self.head_dim % 128 == 0)
        if not ok:
            raise ValueError(
                "flash=True needs a TPU backend, equal q/kv sequence "
                "lengths divisible by 128, and head_dim divisible by 128 "
                f"(got q {q.shape}, k {k.shape}, head_dim {self.head_dim})")
        return ok

    def _init_params(self, rng):
        ks = jax.random.split(rng, 4)
        d = self.hidden_size
        xavier = init_methods.Xavier()
        p = {}
        for key, name in zip(ks, ("wq", "wk", "wv", "wo")):
            p[name] = xavier(key, (d, d), d, d)
        if self.with_bias:
            for name in ("bq", "bk", "bv", "bo"):
                p[name] = jnp.zeros((d,))
        return p

    def _project(self, params, x, w, b):
        y = x @ params[w]
        if self.with_bias:
            y = y + params[b]
        bsz, t, _ = y.shape
        # -1 heads: under the explicit Megatron split params hold only
        # the LOCAL heads' columns (head_dim never splits)
        return y.reshape(bsz, t, -1, self.head_dim)

    def apply(self, params, input, state, training=False, rng=None):
        if isinstance(input, (list, tuple)):
            q_src, kv_src = input[0], input[1]
        else:
            q_src = kv_src = input
        tp_axis = self.model_parallel
        if not (tp_axis and _axis_bound(tp_axis)):
            tp_axis = None
        with jax.named_scope("qkv"):
            q = self._project(params, q_src, "wq", "bq")
            k = self._project(params, kv_src, "wk", "bk")
            v = self._project(params, kv_src, "wv", "bv")
        # the attention core, whichever path runs it, under one scope
        # (the Pallas kernels keep their own names inside it)
        with jax.named_scope("flash"):
            if self.sequence_parallel and _axis_bound(self.sequence_parallel):
                if q_src is not kv_src:
                    raise ValueError("sequence-parallel MHA is self-attention "
                                     "only (q and kv must be the same source)")
                from bigdl_tpu.parallel.ring_attention import (
                    _ring_attention_shard)
                out = _ring_attention_shard(q, k, v,
                                            axis_name=self.sequence_parallel,
                                            causal=self.causal)
            elif self.chunk:
                out = chunked_attention(q, k, v, causal=self.causal,
                                        chunk=self.chunk)
            elif self._flash_ok(q, k):
                from jax.experimental.pallas.ops.tpu.flash_attention import (
                    flash_attention)
                # A Mosaic call is named in the device trace after the
                # innermost scope it is born in.  Through the library's
                # jit wrapper that is whatever stands before
                # ``jit(flash_attention)`` (``jvp_jit_flash_attention``
                # with no scope around the model, ``flash_attention``
                # under one), so the forward kernel gets a scope of its
                # own, beside the library's ``flash_mha_bwd_dkv`` and
                # ``flash_mha_bwd_dq``: one name whatever encloses it.
                with jax.named_scope("flash_mha_fwd"):
                    out = flash_attention.__wrapped__(
                        jnp.transpose(q, (0, 2, 1, 3)),
                        jnp.transpose(k, (0, 2, 1, 3)),
                        jnp.transpose(v, (0, 2, 1, 3)),
                        causal=self.causal,
                        sm_scale=1.0 / math.sqrt(self.head_dim),
                        block_sizes=_flash_block_sizes(q.shape[1]))
                out = jnp.transpose(out, (0, 2, 1, 3))
            else:
                out = scaled_dot_product_attention(q, k, v, causal=self.causal)
        # names the attention context for selective rematerialization:
        # nn.Remat(policy="save_attn") saves THIS tensor (O(B*T*d) per
        # block) so the VJP recomputes only projections/elementwise, never
        # the attention kernel itself.  A no-op outside jax.checkpoint.
        out = checkpoint_name(out, "attn_ctx")
        with jax.named_scope("out"):
            bsz, t = out.shape[0], out.shape[1]
            # -1: local heads * head_dim under the explicit Megatron split
            out = out.reshape(bsz, t, -1) @ params["wo"]
            if tp_axis:
                out = lax.psum(out, tp_axis)   # the head-split pair's one psum
            if self.with_bias:
                out = out + params["bo"]
        return out, state
