"""LM token serving: continuous batching over a paged KV cache.

The PR 9 serving engine batches INDEPENDENT one-shot forwards; an
autoregressive LM breaks that shape — one request is a prompt prefill
followed by a variable-length chain of single-token decode steps, and a
naive server runs each request's chain to completion while everyone
else queues.  This module serves tokens the Orca/vLLM way instead:

- **Iteration-level (continuous) batching.**  The scheduler owns
  ``maxBatch`` decode slots.  Every iteration dispatches ONE fused
  decode step over all occupied slots; sequences that finish (EOS,
  token budget, deadline) vacate their slot and free their KV blocks
  *the iteration their last token is emitted*, and waiting prompts
  prefill into the vacancy — no head-of-line blocking behind the
  longest generation.
- **One decode step in flight.**  A step chooses its tokens on the
  device, and they are the next step's input there: the scheduler
  dispatches step n+1 before it pulls and emits step n, so the host's
  part of an iteration runs while the device executes a step.  An end
  the host cannot foresee (EOS, deadline) costs one wasted row of the
  step already in flight, never a wrong token.
- **Paged KV cache** (:class:`~bigdl_tpu.serving.kv_cache.PagedKVCache`):
  one fixed device pool of ``(layer, block, block_size, head,
  head_dim)`` K/V blocks sized once at construction (gated by the HBM
  preflight budget), a host free-list, and per-sequence block tables.
  Exhaustion is a structured retriable ``Overloaded`` at admission —
  never a device OOM mid-decode.
- **Held at the width the products read.**  On a TPU at the default
  matmul precision a float32 product reads its operands as bf16, so
  ``transformer_lm``'s matrix weights and pools are held in bf16 and
  multiplied with float32 accumulation: the same precision at half the
  bytes (:func:`_held_dtype`); float32 elsewhere.
- **One decode shape.**  The decode step always runs at ``(maxBatch,
  1)`` with inactive slots masked (their scatters land in the reserved
  dump block); prefill pads to a small bucket ladder.  Both compile
  through ``compile_cache.tracked_jit`` under the strict retrace
  sentinel — zero post-warmup retraces is test- and bench-asserted,
  exactly the PR 7 contract extended to decode.
- **Streaming output.**  ``submit()`` returns a :class:`TokenStream`
  whose iterator yields tokens as the scheduler emits them; TTFT and
  inter-token latency land in exact windowed percentile histograms
  (``LM/ttft_ms``, ``LM/itl_ms``).
- **int8 weight tier** (``bigdl.lm.quantize=int8``): decode matmuls run
  against per-output-channel symmetric int8 weights dequantized in the
  contraction.  The tier only serves after passing a two-part gate at
  construction — the HLO auditor's precision-drift pass over the
  quantized program AND an fp-vs-int8 logits ``allclose`` on identical
  KV-pool inputs (:class:`QuantizationGateError` otherwise).

Failure taxonomy, admission control (queue bound, cooldown, projected
wait), deadline shedding, poison quarantine, the hung-dispatch
watchdog, drain-on-preemption, and the accounting identity
``completed + shed + rejected + quarantined == submitted`` are all the
PR 9 machinery reused verbatim — a token stream that failed after
emitting some tokens keeps them and terminates with the structured
error saying why.
"""

from __future__ import annotations

import logging
import math
import queue
import threading
import time
from collections import deque
from contextlib import nullcontext
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu import analysis, telemetry
from bigdl_tpu.resources import GOVERNOR as _resource_governor
from bigdl_tpu.resources import item_nbytes as _item_nbytes
from bigdl_tpu.telemetry import incident, request_trace
from bigdl_tpu.serving.engine import (OUTCOMES, DeadlineExceeded,
                                      HungDispatchError,
                                      HungDispatchWatchdog, Overloaded,
                                      ServingDataError, ServingInfraError,
                                      _service_ema)
from bigdl_tpu.serving.kv_cache import DUMP_BLOCK, PagedKVCache
from bigdl_tpu.utils import elastic

logger = logging.getLogger("bigdl_tpu")


class UnsupportedModelError(ValueError):
    """The served model is neither of the decoder-only families this
    engine knows how to serve (``models.transformer.transformer_lm``,
    dissected module by module; ``models.transformer.glm_moe_dsa``, served
    through the family's own forward function), or asks of its family
    what that family has not.  Structured — names the exact mismatch —
    because the silent alternative is a decode path that reads the wrong
    weights."""

    def __init__(self, what: str):
        super().__init__(
            f"LMServingEngine serves transformer_lm-shaped models "
            f"(LookupTable, PositionalEncoding, n x decoder block, "
            f"LayerNorm, Linear, LogSoftMax) and GlmMoeDsaLM; {what}")


class QuantizationGateError(ValueError):
    """The int8 decode tier failed its admission gate (auditor
    precision-drift pass, or the fp-vs-int8 logits allclose check) —
    the engine refuses to serve quantized rather than drift silently."""


# ---------------------------------------------------------------------------
# model dissection
# ---------------------------------------------------------------------------


class _LMGraph:
    """Static description of a ``transformer_lm`` model: the per-layer
    modules (weights are read through each module's adopted ``.params``
    view, never positional index math) plus the dims the decode step
    closes over."""

    family = "transformer_lm"
    #: the int8 decode tier quantizes this family's Linear layers
    quantizable = True

    def __init__(self, model):
        import bigdl_tpu.nn as nn
        from bigdl_tpu.models.transformer import (LayerNorm,
                                                  PositionalEncoding,
                                                  _Residual)
        if not isinstance(model, nn.Sequential):
            raise UnsupportedModelError(
                f"got a {type(model).__name__}, not a Sequential")
        ch = list(model.children)
        if len(ch) < 6:
            raise UnsupportedModelError(
                f"expected >= 6 children, got {len(ch)}")
        embed, pos = ch[0], ch[1]
        lnf, head, logsm = ch[-3], ch[-2], ch[-1]
        if not isinstance(embed, nn.LookupTable):
            raise UnsupportedModelError(
                f"child 0 is {type(embed).__name__}, not LookupTable")
        if embed.max_norm != float("inf"):
            raise UnsupportedModelError(
                "LookupTable max-norm renormalisation is not folded into "
                "the decode path")
        if not isinstance(pos, PositionalEncoding):
            raise UnsupportedModelError(
                f"child 1 is {type(pos).__name__}, not PositionalEncoding")
        if not isinstance(lnf, LayerNorm):
            raise UnsupportedModelError(
                f"child -3 is {type(lnf).__name__}, not the final LayerNorm")
        if not isinstance(head, nn.Linear):
            raise UnsupportedModelError(
                f"child -2 is {type(head).__name__}, not the Linear head")
        if not isinstance(logsm, nn.LogSoftMax):
            raise UnsupportedModelError(
                f"child -1 is {type(logsm).__name__}, not LogSoftMax")
        self.layers: List[Dict[str, Any]] = []
        for bi, raw in enumerate(ch[2:-3]):
            blk = raw.children[0] if isinstance(raw, nn.Remat) else raw
            if not (isinstance(blk, nn.Sequential) and
                    len(blk.children) == 2 and
                    all(isinstance(r, _Residual) for r in blk.children)):
                raise UnsupportedModelError(
                    f"block {bi} is not a pair of pre-norm residuals")
            attn_res, ffn_res = blk.children
            ln1, attn = attn_res.children
            ln2, ffn = ffn_res.children
            if not isinstance(attn, nn.MultiHeadAttention):
                raise UnsupportedModelError(
                    f"block {bi} residual 0 wraps {type(attn).__name__}, "
                    "not MultiHeadAttention")
            if not attn.causal:
                raise UnsupportedModelError(
                    f"block {bi} attention is not causal — an acausal "
                    "model has no autoregressive decode")
            if not (isinstance(ffn, nn.Sequential) and
                    len(ffn.children) == 3 and
                    isinstance(ffn.children[0], nn.Linear) and
                    isinstance(ffn.children[1], nn.ReLU) and
                    isinstance(ffn.children[2], nn.Linear)):
                raise UnsupportedModelError(
                    f"block {bi} FFN is not Linear/ReLU/Linear (MoE blocks "
                    "have no single-token decode path yet)")
            self.layers.append({"ln1": ln1, "attn": attn, "ln2": ln2,
                                "up": ffn.children[0],
                                "down": ffn.children[2]})
        if not self.layers:
            raise UnsupportedModelError("model has no decoder blocks")
        heads = {l["attn"].n_head for l in self.layers}
        if len(heads) != 1:
            raise UnsupportedModelError(
                f"heterogeneous head counts across blocks: {sorted(heads)}")
        self.model = model
        self.embed = embed
        self.pos = pos
        self.lnf = lnf
        self.head = head
        self.vocab = int(head.output_size)
        self.d_model = int(embed.n_output)
        self.n_head = int(self.layers[0]["attn"].n_head)
        self.head_dim = int(self.layers[0]["attn"].head_dim)
        self.n_layers = len(self.layers)
        self.max_seq_len = int(pos.max_seq_len)
        #: the width the steps' products read, so the width at which the
        #: matrix weights and the K and V pools are held
        self.dtype = _held_dtype(jax.default_backend(),
                                 jax.config.jax_default_matmul_precision)

    # -- what the engine asks of a family --------------------------------

    def make_cache(self, n_blocks: int, block_size: int) -> PagedKVCache:
        """K and V of every layer behind one block table, at
        :attr:`dtype`."""
        return PagedKVCache.kv(self.n_layers, self.n_head, self.head_dim,
                               n_blocks, block_size, dtype=self.dtype)

    def query_blocks(self, bucket: int) -> int:
        """Query blocks a prefill of ``bucket`` positions attends in."""
        return 1

    def params(self) -> Dict[str, Any]:
        return _extract_params(self, self.dtype)

    def rows_fetched(self, positions, active, block_size: int,
                     max_blocks: int) -> int:
        """Rows of one pool and layer the decode step's attention fetches
        for these (host) arguments: a group's tables in whole chunks, for
        every trip of the step's own loops (:func:`_group_chunks`)."""
        chunk = _chunk_blocks(block_size, max_blocks) * block_size
        group = min(_KV_GROUP, len(positions))
        _, chunks = _group_chunks(positions, active, chunk, group, np)
        return group * chunk * int(chunks.sum())

    def steps(self, block_size: int, max_blocks: int):
        """``(decode, prefill, full)`` in the shape the engine dispatches
        (:func:`_pooled`)."""
        return (_pooled(_build_decode_fn(self, block_size, max_blocks)),
                _pooled(_build_prefill_fn(self, block_size)),
                _build_full_fn(self))


class _GlmGraph:
    """A ``glm_moe_dsa`` model: the family is one function of ``(params,
    tokens, positions, cache)``, so there is nothing to dissect; the same
    six answers as :class:`_LMGraph` gives."""

    family = "glm_moe_dsa"
    quantizable = False

    def __init__(self, model):
        self.model, self.spec = model, model.spec
        self.vocab = int(model.spec.vocab_size)
        self.d_model = int(model.spec.attention.d_model)
        self.n_head = int(model.spec.attention.n_head)
        self.n_layers = int(model.spec.n_layers)
        self.max_seq_len = int(model.spec.max_position_embeddings)

    def make_cache(self, n_blocks: int, block_size: int) -> PagedKVCache:
        """A latent row for every layer and an indexer key for the layers
        that have an indexer, at the parameters' dtype, behind one block
        table."""
        a = self.spec.attention
        dtype = jax.tree_util.tree_leaves(self.model.params)[0].dtype
        return PagedKVCache(
            {"latent": (self.n_layers, (a.latent_row_width,)),
             "index": (len(self.spec.index_layers), (a.index_head_dim,))},
            n_blocks, block_size, dtype=dtype)

    def query_blocks(self, bucket: int) -> int:
        from bigdl_tpu.nn.latent_attention import query_blocks
        return query_blocks(bucket)

    def params(self):
        return self.model.params

    def rows_fetched(self, positions, active, block_size: int,
                     max_blocks: int) -> int:
        """Selected rows of one layer the decode step's attention fetches
        for these (host) arguments: ``index_topk`` (or the whole table,
        while it holds fewer) for every slot of every trip the step's
        loop over live slots makes."""
        from bigdl_tpu.nn.latent_attention import live_trips, slot_chunk
        chunk = slot_chunk(len(positions))
        return chunk * int(live_trips(active, chunk, np)) * min(
            int(self.spec.attention.index_topk), max_blocks * block_size)

    def steps(self, block_size: int, max_blocks: int):
        return _build_glm_steps(self, block_size)


def _graph_of(model):
    """The served family's graph; ``UnsupportedModelError`` for a model
    of neither."""
    from bigdl_tpu.models.transformer.glm_moe_dsa import GlmMoeDsaLM
    if isinstance(model, GlmMoeDsaLM):
        return _GlmGraph(model)
    return _LMGraph(model)


#: matmul precisions at which a float32 product on a TPU rounds both
#: operands to bf16 once and accumulates in float32, besides none set
_ONE_BF16_PASS = ("default", "bfloat16", "fastest")


def _held_dtype(backend: str, precision: Optional[str]):
    """The width at which ``transformer_lm``'s engine holds what its
    products read (the matrix weights, the K and V pools), from the
    backend and ``jax_default_matmul_precision`` in force.

    On a TPU at the default precision a float32 ``dot`` rounds both
    operands to the nearest bf16 and accumulates in float32, so matrix
    weights held in bf16 give the products the same results at half the
    bytes read from HBM: bf16.  The K and V pools are held at the same
    width; their rounding is new only where a product read them at
    float32, as the decode step's one-row attention products may (XLA can
    run those on the vector unit), and it is of the size of the default
    precision's own.  Under a wider precision (``high``, ``highest``, ``float32``,
    ``tensorfloat32``), or on a backend whose float32 product reads
    float32 (the CPU), the products read float32, and so the engine
    holds float32."""
    one_pass = precision is None or precision.lower() in _ONE_BF16_PASS
    return jnp.dtype(jnp.bfloat16 if backend == "tpu" and one_pass
                     else jnp.float32)


def _linear_entry(weight, bias, dtype) -> Dict[str, Any]:
    if dtype is not None and weight.dtype != dtype:
        weight = weight.astype(dtype)
    return {"w": weight, "b": bias}


def _extract_params(graph: _LMGraph, dtype=None) -> Dict[str, Any]:
    """Snapshot the model's weights into the decode pytree.  Root
    ``.params`` is touched first so lazy init + child adoption happen
    once; every leaf is then the module's own adopted view, except that
    the matrix weights (``wq``, ``wk``, ``wv``, ``wo``, the FFN's ``up``
    and ``down``, the head) are copies at ``dtype`` where it is given
    and differs (the module's own arrays stay as they are).  The
    embedding table (a gather), biases and LayerNorm parameters are
    always the module's own."""
    _ = graph.model.params
    layers = []
    for l in graph.layers:
        ap, wb = l["attn"].params, l["attn"].with_bias
        layers.append({
            "ln1": {"w": l["ln1"].params["weight"],
                    "b": l["ln1"].params["bias"]},
            "attn": {k: _linear_entry(ap[f"w{k[-1]}"],
                                      ap[f"b{k[-1]}"] if wb else None,
                                      dtype)
                     for k in ("wq", "wk", "wv", "wo")},
            "ln2": {"w": l["ln2"].params["weight"],
                    "b": l["ln2"].params["bias"]},
            "ffn": {"up": _linear_entry(
                        l["up"].params["weight"],
                        l["up"].params["bias"] if l["up"].with_bias
                        else None, dtype),
                    "down": _linear_entry(
                        l["down"].params["weight"],
                        l["down"].params["bias"] if l["down"].with_bias
                        else None, dtype)},
        })
    return {"embed": graph.embed.params["weight"],
            "layers": layers,
            "lnf": {"w": graph.lnf.params["weight"],
                    "b": graph.lnf.params["bias"]},
            "head": _linear_entry(
                graph.head.params["weight"],
                graph.head.params["bias"] if graph.head.with_bias
                else None, dtype)}


def _quantize_entry(entry: Dict[str, Any]) -> Dict[str, Any]:
    """Per-output-channel symmetric int8: ``s = max|w| / 127`` over the
    input axis, ``q = round(w / s)``.  Dequantization happens in the
    contraction (``(x @ q) * s``), so the auditor's precision pass sees
    an f32 dot — the tier changes storage, not accumulation dtype."""
    w = entry["w"]
    s = jnp.max(jnp.abs(w), axis=0) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s, "b": entry["b"]}


def _quantize_params(dp: Dict[str, Any]) -> Dict[str, Any]:
    """int8-quantize every decode matmul; embeddings (a gather) and the
    layer norms stay fp.  The engine hands it the module's own weights
    (:func:`_extract_params` without a ``dtype``), not the fp steps'
    copies."""
    layers = []
    for l in dp["layers"]:
        layers.append({
            "ln1": l["ln1"],
            "attn": {k: _quantize_entry(e) for k, e in l["attn"].items()},
            "ln2": l["ln2"],
            "ffn": {k: _quantize_entry(e) for k, e in l["ffn"].items()},
        })
    return {"embed": dp["embed"], "layers": layers, "lnf": dp["lnf"],
            "head": _quantize_entry(dp["head"])}


def _apply_linear(x, e):
    """One decode matmul against an fp (``w``) or int8 (``q``/``s``)
    entry — the branch is on pytree STRUCTURE, resolved at trace time,
    so fp and int8 programs compile under their own labels.  An fp entry
    takes the activation at the weight's width (bf16 where the engine
    holds bf16: :func:`_held_dtype`) and accumulates in float32."""
    if "q" in e:
        y = (x @ e["q"].astype(x.dtype)) * e["s"]
    else:
        w = e["w"]
        y = jnp.matmul(x.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)
    if e.get("b") is not None:
        y = y + e["b"]
    return y


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    return out * p["w"] + p["b"]


def _ffn(x, lyr, eps):
    """The block's second residual, ``x + down(relu(up(ln2(x))))``, under
    the ``ln2`` and ``ffn`` scopes of the layer's scope."""
    with jax.named_scope("ln2"):
        h = _layer_norm(x, lyr["ln2"], eps)
    with jax.named_scope("ffn"):
        h = jax.nn.relu(_apply_linear(h, lyr["ffn"]["up"]))
        return x + _apply_linear(h, lyr["ffn"]["down"])


def _head(x, dp, eps_f):
    """Final norm, head and log-softmax of ``(rows, d)`` activations, each
    under its scope."""
    with jax.named_scope("ln_f"):
        x = _layer_norm(x, dp["lnf"], eps_f)
    with jax.named_scope("head"):
        logits = _apply_linear(x, dp["head"])
    with jax.named_scope("log_softmax"):
        return jax.nn.log_softmax(logits, axis=-1)


# ---------------------------------------------------------------------------
# step builders (pure functions over the decode pytree + KV pools)
# ---------------------------------------------------------------------------


#: context positions one trip of the decode step's attention loop reads.
#: On the v5e at 16 slots x 32 heads of 128 a trip's two gathers (32 MB
#: each) stay in the fast memory the scores read them from; 64 measured
#: the same step time, 256 a fifth to a third slower (PERF.md 6, PR 27)
_KV_CHUNK = 128


def _chunk_blocks(block_size: int, max_blocks: int) -> int:
    """Blocks of a table one trip of the decode step's attention reads."""
    return max(1, min(max_blocks, _KV_CHUNK // block_size))


#: slots one trip of the decode step's loop over slot groups reads: the
#: smallest group whose step at 16 live slots is within 3 % of reading
#: every slot at once.  On the v5e at the chat cell's widths (16 slots,
#: every live context at 640 / 1,536) the step read every slot in 2.764 /
#: 4.133 ms at any occupancy; in groups of 2 / 4 / 8 / 16 it took 3.095 /
#: 2.897 / 2.816 / 2.776 and 4.820 / 4.415 / 4.232 / 4.135 ms at 16 live
#: (+12 / +4.8 / +1.9 / +0.4 % at 640), and 1.982 / 2.063 / 2.302 / 2.769
#: and 2.202 / 2.441 / 3.009 / 4.139 ms at one (PERF.md 6)
_KV_GROUP = 8


def _group_chunks(positions, active, chunk: int, group: int, xp=jnp):
    """``(order, chunks)``: the trips of the decode step's attention.

    ``order`` is every slot, longest context first (``pos + 1`` of an
    active slot, 0 of an idle one; ties in slot order), padded to whole
    groups of ``group`` with ``B``, the slot written nowhere.  ``chunks``
    has one entry a group: the chunks of ``chunk`` positions its trip
    reads, enough for its first, and so longest, member; at least one
    (a trip of none would leave its slots a normaliser of 0) for the
    groups up to ``ceil(live / group)`` (and the first group when none is
    live), 0 for the groups no trip reaches.  The step
    calls it on its arguments (``xp=jnp``) and the scheduler on the host
    copies of the same (``xp=np``), which is how ``LM/kv_rows_fetched``
    counts what the device read without pulling anything."""
    b = positions.shape[0]
    length = xp.where(active, positions + 1, 0)
    order = xp.argsort(-length, stable=True)
    pad = -b % group
    longest = xp.pad(length[order], (0, pad))[::group]
    live = xp.sum(active, dtype=xp.int32)
    trips = xp.maximum((live + group - 1) // group, 1)
    reached = xp.arange(longest.shape[0]) < trips
    chunks = xp.where(reached, xp.maximum((longest + chunk - 1) // chunk, 1),
                      0)
    return xp.pad(order, (0, pad), constant_values=b), chunks


def _build_decode_fn(graph: _LMGraph, block_size: int, max_blocks: int):
    """One fused decode iteration at the FIXED ``(maxBatch, 1)`` shape:
    embed + positional row, per layer scatter this step's k/v into the
    paged pool (BEFORE the gather, so the current token attends itself),
    then attention over each sequence's table context, FFN; returns
    next-token log-probs and the updated pools.

    The attention reads the live slots only, in groups of
    :data:`_KV_GROUP` slots sorted by context length, each group only as
    far as its own longest context (:func:`_group_chunks` of
    ``positions`` and ``active``, once a step): a device loop over the
    groups, each trip :func:`~bigdl_tpu.nn.attention.paged_attention`
    over its slots' tables a chunk of :data:`_KV_CHUNK` positions at a
    time, straight out of the 5-D pools (layer and block in one gather,
    no copy of a layer).  So one compiled program reads one group's 128
    rows a slot or every table's whole capacity as the traffic asks.  A
    slot no trip reaches attends nothing (zeros: finite, as the dump
    block it scatters into must stay).  The step's k/v enter the pools
    at the pools' width, and the chunks are read at it.  Inactive slots
    compute junk that scatters into the dump block and is discarded on
    the host — occupancy can never mint a new signature."""
    from bigdl_tpu.nn.attention import paged_attention
    pe = graph.pos.pe
    vocab_in = int(graph.embed.n_index)
    H, Dh = graph.n_head, graph.head_dim
    eps1 = [l["ln1"].eps for l in graph.layers]
    eps2 = [l["ln2"].eps for l in graph.layers]
    eps_f = graph.lnf.eps
    cb = _chunk_blocks(block_size, max_blocks)
    chunk = cb * block_size

    def decode(dp, pool_k, pool_v, tokens, positions, tables, active):
        B = tokens.shape[0]
        G = min(_KV_GROUP, B)
        with jax.named_scope("embed"):
            idx = jnp.clip(tokens.astype(jnp.int32) - 1, 0, vocab_in - 1)
            x = jnp.take(dp["embed"], idx, axis=0)
            x = x + jnp.take(pe, positions,
                             axis=0)[:, None, :].astype(x.dtype)
            blk = jnp.where(active, tables[jnp.arange(B),
                                           positions // block_size],
                            DUMP_BLOCK)
            slot = positions % block_size
            order, chunks = _group_chunks(positions, active, chunk, G)
            trips = jnp.sum(chunks > 0)
        for li, lyr in enumerate(dp["layers"]):
            with jax.named_scope(f"layer{li}"):
                with jax.named_scope("ln1"):
                    h = _layer_norm(x, lyr["ln1"], eps1[li])
                with jax.named_scope("qkv"):
                    q, k, v = (_apply_linear(h, lyr["attn"][w])
                               .reshape(B, 1, H, Dh)
                               for w in ("wq", "wk", "wv"))
                with jax.named_scope("kv_scatter"):
                    pool_k = pool_k.at[li, blk, slot].set(
                        k[:, 0].astype(pool_k.dtype))
                    pool_v = pool_v.at[li, blk, slot].set(
                        v[:, 0].astype(pool_v.dtype))
                with jax.named_scope("kv_gather"):
                    # the tables in whole chunks (the dump block pads
                    # the last one: every position of it is masked)
                    padded = jnp.pad(tables, ((0, 0), (0, -max_blocks % cb)),
                                     constant_values=DUMP_BLOCK)

                def trip(g, out):
                    slots = jax.lax.dynamic_slice_in_dim(order, g * G, G)
                    at = jnp.minimum(slots, B - 1)
                    rows = padded[at]

                    def fetch(c):
                        with jax.named_scope("kv_gather"):
                            part = jax.lax.dynamic_slice_in_dim(
                                rows, c * cb, cb, axis=1)
                            return (pool_k[li, part].reshape(G, chunk, H, Dh),
                                    pool_v[li, part].reshape(G, chunk, H, Dh))

                    att = paged_attention(q[at], fetch, positions[at],
                                          active[at], chunks[g], chunk)
                    return out.at[slots].set(att, mode="drop")

                with jax.named_scope("attn"):
                    att = jnp.zeros((B, 1, H, Dh), jnp.float32)
                att = jax.lax.fori_loop(0, trips, trip, att)
                with jax.named_scope("out"):
                    x = x + _apply_linear(att.reshape(B, 1, H * Dh),
                                          lyr["attn"]["wo"])
                x = _ffn(x, lyr, eps2[li])
        return _head(x[:, 0], dp, eps_f), pool_k, pool_v

    return decode


def _build_prefill_fn(graph: _LMGraph, block_size: int):
    """Bucketed prompt prefill: dense causal attention over the padded
    span (padding sits AFTER every real query, so the causal mask alone
    keeps it out of every real row), scattering each real position's
    k/v into the sequence's blocks (padded rows hit the dump block).
    Returns the last REAL position's log-probs + the updated pools."""
    pe = graph.pos.pe
    vocab_in = int(graph.embed.n_index)
    H, Dh = graph.n_head, graph.head_dim
    eps1 = [l["ln1"].eps for l in graph.layers]
    eps2 = [l["ln2"].eps for l in graph.layers]
    eps_f = graph.lnf.eps
    from bigdl_tpu.nn.attention import scaled_dot_product_attention

    def prefill(dp, pool_k, pool_v, tokens, length, table):
        T = tokens.shape[1]
        with jax.named_scope("embed"):
            idx = jnp.clip(tokens.astype(jnp.int32) - 1, 0, vocab_in - 1)
            x = jnp.take(dp["embed"], idx, axis=0)
            x = x + pe[:T][None].astype(x.dtype)
            pos = jnp.arange(T)
            blkrow = jnp.where(pos < length, table[pos // block_size],
                               DUMP_BLOCK)
            slotrow = pos % block_size
        for li, lyr in enumerate(dp["layers"]):
            with jax.named_scope(f"layer{li}"):
                with jax.named_scope("ln1"):
                    h = _layer_norm(x, lyr["ln1"], eps1[li])
                with jax.named_scope("qkv"):
                    q, k, v = (_apply_linear(h, lyr["attn"][w])
                               .reshape(1, T, H, Dh)
                               for w in ("wq", "wk", "wv"))
                with jax.named_scope("kv_scatter"):
                    pool_k = pool_k.at[li, blkrow, slotrow].set(
                        k[0].astype(pool_k.dtype))
                    pool_v = pool_v.at[li, blkrow, slotrow].set(
                        v[0].astype(pool_v.dtype))
                with jax.named_scope("attn"):
                    att = scaled_dot_product_attention(q, k, v, causal=True)
                with jax.named_scope("out"):
                    x = x + _apply_linear(att.reshape(1, T, H * Dh),
                                          lyr["attn"]["wo"])
                x = _ffn(x, lyr, eps2[li])
        logp = _head(x[0], dp, eps_f)
        return jnp.take(logp, length - 1, axis=0), pool_k, pool_v

    return prefill


def _build_full_fn(graph: _LMGraph):
    """Teacher-forced full forward over a (1, T) span -> (T, vocab)
    log-probs: the sequential-generation baseline AND the
    decode-parity reference (same closure math as prefill, no pool)."""
    pe = graph.pos.pe
    vocab_in = int(graph.embed.n_index)
    H, Dh = graph.n_head, graph.head_dim
    eps1 = [l["ln1"].eps for l in graph.layers]
    eps2 = [l["ln2"].eps for l in graph.layers]
    eps_f = graph.lnf.eps
    from bigdl_tpu.nn.attention import scaled_dot_product_attention

    def full(dp, tokens):
        T = tokens.shape[1]
        with jax.named_scope("embed"):
            idx = jnp.clip(tokens.astype(jnp.int32) - 1, 0, vocab_in - 1)
            x = jnp.take(dp["embed"], idx, axis=0)
            x = x + pe[:T][None].astype(x.dtype)
        for li, lyr in enumerate(dp["layers"]):
            with jax.named_scope(f"layer{li}"):
                with jax.named_scope("ln1"):
                    h = _layer_norm(x, lyr["ln1"], eps1[li])
                with jax.named_scope("qkv"):
                    q, k, v = (_apply_linear(h, lyr["attn"][w])
                               .reshape(1, T, H, Dh)
                               for w in ("wq", "wk", "wv"))
                with jax.named_scope("attn"):
                    att = scaled_dot_product_attention(q, k, v, causal=True)
                with jax.named_scope("out"):
                    x = x + _apply_linear(att.reshape(1, T, H * Dh),
                                          lyr["attn"]["wo"])
                x = _ffn(x, lyr, eps2[li])
        return _head(x[0], dp, eps_f)

    return full


def _pooled(fn):
    """A ``transformer_lm`` step ``(dp, pool_k, pool_v, ...) -> (lp, k, v)``
    in the shape every family's builder returns: ``(dp, *pools, ...) ->
    (lp, pools, aux)``, the cache's pools in its order (each donated),
    returned as one tuple so that one statement writes them back, and
    ``aux`` what the step counts on the device (here nothing).  The engine
    dispatches it with its token chosen (:func:`_choosing`)."""

    def step(dp, *args):
        lp, k, v = fn(dp, *args)
        return lp, (k, v), None

    return step


def _build_glm_steps(graph: "_GlmGraph", block_size: int):
    """The three steps of the ``glm_moe_dsa`` family in the shape
    :func:`_pooled` gives the other one (the engine adds the token:
    :func:`_choosing`), each the family's ONE forward function
    (``models.transformer.glm_moe_dsa.forward``) handed this step's view
    of the pools ``(latent, index)``.  ``aux`` is three int32: routed
    assignments of the real tokens, those that met a held expert, and
    (decode) the context rows the active slots' attention read."""
    from bigdl_tpu.models.transformer.glm_moe_dsa import forward
    from bigdl_tpu.nn.latent_attention import LatentCache
    spec = graph.spec

    def decode(dp, latent, index, tokens, positions, tables, active):
        b = tokens.shape[0]
        blk = jnp.where(active, tables[jnp.arange(b),
                                       positions // block_size], DUMP_BLOCK)
        cache = LatentCache(latent, index, blk[:, None],
                            (positions % block_size)[:, None], tables)
        logp, cache, counts = forward(spec, dp, tokens, positions[:, None],
                                      cache, valid=active[:, None])
        return logp[:, 0], (cache.latent, cache.index), counts

    def prefill(dp, latent, index, tokens, length, table):
        pos = jnp.arange(tokens.shape[1])
        real = pos < length
        cache = LatentCache(
            latent, index,
            jnp.where(real, table[pos // block_size], DUMP_BLOCK)[None],
            (pos % block_size)[None])
        logp, cache, counts = forward(spec, dp, tokens, pos[None], cache,
                                      valid=real[None],
                                      rows=(length - 1)[None])
        return logp[0, 0], (cache.latent, cache.index), counts

    def full(dp, tokens):
        pos = jnp.arange(tokens.shape[1])[None]
        return forward(spec, dp, tokens, pos)[0][0]

    return decode, prefill, full


def _choosing(step):
    """A family's step ``(dp, *pools, ...) -> (lp, pools, aux)`` as the
    engine dispatches it: ``-> (tok, lp, pools, aux)``, where ``tok`` is the
    greedy token of every row of ``lp`` (1-based, the first index on a tie,
    as ``np.argmax``), chosen on the device from the same float32
    log-probs.  A decode step's ``(max_batch, vocab)`` rows give a
    ``(max_batch, 1)`` column: the shape, dtype and place of the step's own
    ``tokens`` argument, so the scheduler hands step n's ``tok`` to step
    n+1 as it is, still on the device, before it has pulled it (an
    inactive row's entry is the arg-max of that row's junk: in the
    vocabulary, and written to the dump block).  A prefill's one row gives
    a scalar.  The scheduler pulls ``tok`` (and ``aux``) and nothing else;
    ``lp`` stays an output of the same program for who asks for rows
    (``generate(return_logps=True)``, the int8 gate), and costs one write
    on the device when nobody does."""

    def chosen(dp, *args):
        lp, pools, aux = step(dp, *args)
        with jax.named_scope("greedy"):
            tok = jnp.argmax(lp, axis=-1, keepdims=lp.ndim > 1)
            tok = tok.astype(jnp.int32) + 1
        return tok, lp, pools, aux

    # the device's module keeps the name a trace knows the step by
    chosen.__name__ = step.__name__
    return chosen


# ---------------------------------------------------------------------------
# streaming handle
# ---------------------------------------------------------------------------


class TokenStream:
    """One admitted generation request: a streaming token iterator plus
    a one-shot terminal state that is exactly one of :data:`OUTCOMES`
    (first-wins, like the PR 9 ``RequestHandle`` — a stream can never
    be both shed by the drain and completed by a racing decode).

    Iterating yields tokens AS THE SCHEDULER EMITS THEM; when the
    stream terminates with an error (deadline, hang, drain), iteration
    raises it after the already-streamed tokens — a partially-streamed-
    then-failed request keeps its prefix and learns why it stopped."""

    __slots__ = ("prompt", "index", "seq_id", "max_new_tokens", "eos_id",
                 "submit_ns", "deadline_ns", "first_token_ns", "finish_ns",
                 "outcome", "payload_nbytes", "_tokens", "_error",
                 "_terminal", "_cv", "trace_id")

    def __init__(self, prompt, index: int, submit_ns: int, deadline_ns: int,
                 max_new_tokens: int, eos_id: Optional[int],
                 trace_id: Optional[str] = None):
        self.prompt = prompt
        self.index = index          # admission position (chaos plans key on it)
        self.seq_id = index         # KV-cache sequence id
        self.trace_id = trace_id    # None when request tracing is disarmed
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.submit_ns = submit_ns
        self.deadline_ns = deadline_ns
        self.first_token_ns: Optional[int] = None       # guarded-by: _cv
        self.finish_ns: Optional[int] = None            # guarded-by: _cv
        self.outcome: Optional[str] = None              # guarded-by: _cv
        self.payload_nbytes = 0     # guarded-by: _cv — host bytes charged to the governor
        self._tokens: List[int] = []                    # guarded-by: _cv
        self._error: Optional[BaseException] = None     # guarded-by: _cv
        self._terminal = False                          # guarded-by: _cv
        self._cv = analysis.make_condition("lm.stream")

    # -- scheduler side ---------------------------------------------------

    def _emit(self, tok: int) -> None:
        with self._cv:
            if self._terminal:
                return
            self._tokens.append(int(tok))
            if self.first_token_ns is None:
                self.first_token_ns = telemetry.clock_ns()
            self._cv.notify_all()

    def _finish(self, outcome: str,
                error: Optional[BaseException] = None) -> bool:
        with self._cv:
            if self._terminal:
                return False
            self.outcome = outcome
            self._error = error
            self.finish_ns = telemetry.clock_ns()
            self._terminal = True
            self._cv.notify_all()
        return True

    # -- client side ------------------------------------------------------

    def __iter__(self):
        # bounded: at most max_new_tokens yields, then the terminal check
        for i in range(self.max_new_tokens + 1):
            with self._cv:
                while len(self._tokens) <= i and not self._terminal:
                    self._cv.wait(0.05)
                if i >= len(self._tokens):
                    break
                tok = self._tokens[i]
            yield tok
        if self._error is not None:
            raise self._error

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until terminal; the full token list, or raises the
        terminal error (tokens streamed before the failure stay
        readable via :meth:`tokens`)."""
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        with self._cv:
            while not self._terminal:
                if deadline is not None and time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"stream {self.index} still in flight after "
                        f"{timeout} s")
                self._cv.wait(0.05)
        if self._error is not None:
            raise self._error
        return list(self._tokens)

    def tokens(self) -> List[int]:
        """Tokens streamed so far (snapshot; no blocking)."""
        with self._cv:
            return list(self._tokens)

    def done(self) -> bool:
        return self._terminal

    def error(self) -> Optional[BaseException]:
        return self._error if self._terminal else None

    def ttft_ms(self) -> Optional[float]:
        if self.first_token_ns is None:
            return None
        return (self.first_token_ns - self.submit_ns) / 1e6

    def latency_ms(self) -> Optional[float]:
        if self.finish_ns is None:
            return None
        return (self.finish_ns - self.submit_ns) / 1e6


class _Slot:
    """One occupied decode slot: the stream plus its cursor, in two halves.
    What has been DISPATCHED: ``position`` (the pool position the next
    dispatched token writes) and ``dispatched`` (tokens counted towards
    ``max_new_tokens``, the prefill's and those of steps still in flight).
    What has been EMITTED: ``generated`` and ``last_token``.  They differ
    by the one step the scheduler keeps in flight."""

    __slots__ = ("stream", "position", "dispatched", "generated",
                 "last_token", "table_row", "last_emit_ns")

    def __init__(self, stream: TokenStream, position: int, last_token: int,
                 table_row: np.ndarray):
        self.stream = stream
        self.position = position
        self.dispatched = 1         # prefill emitted the first token
        self.generated = 1
        self.last_token = last_token
        self.table_row = table_row
        self.last_emit_ns = telemetry.clock_ns()

    def goes_on(self) -> bool:
        """Whether another step is to be dispatched for the sequence: an
        end by length is known before the step that reaches it runs."""
        return self.dispatched < self.stream.max_new_tokens

    def token_on_host(self) -> bool:
        return self.dispatched == self.generated


class _Flight(NamedTuple):
    """A decode step that was dispatched and whose tokens have not been
    pulled: the step's outputs still on the device, the host arrays it was
    built from, and WHICH STREAM each active row was dispatched for (the
    slot may be vacant, or another stream's, by the time the row is
    emitted)."""

    step: int
    t0: int
    toks: Any
    aux: Any
    rows: List[Tuple[int, TokenStream]]
    positions: np.ndarray
    active: np.ndarray


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _pulled_nbytes(tree) -> int:
    """Bytes of the arrays of ``tree``: what one pull of it brings across."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def _tree_spec(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


class LMServingEngine:
    """Continuous-batching token server over ONE decoder-only LM.

    All knobs default from ``bigdl.lm.*`` (see ``docs/configuration.md``);
    constructor arguments override per-engine.  ``submit()`` streams;
    ``generate()`` / ``generate_sequential()`` are the offline
    paged-vs-teacher-forced pair the parity proof and the bench's
    baseline lean on."""

    def __init__(self, model, max_batch: Optional[int] = None,
                 max_context: Optional[int] = None,
                 block_size: Optional[int] = None,
                 cache_blocks: Optional[int] = None,
                 max_new_tokens: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 max_queue_depth: Optional[int] = None,
                 quantize: Optional[str] = None,
                 prefill_buckets=None,
                 start: bool = False):
        from bigdl_tpu.utils import config
        t_setup = telemetry.clock_ns()
        self.graph = _graph_of(model)
        self.max_batch = int(max_batch if max_batch is not None else
                             config.get_int("bigdl.lm.maxBatch", 8))
        self.max_context = int(
            max_context if max_context is not None else
            config.get_int("bigdl.lm.maxContext", 256))
        self.block_size = int(
            block_size if block_size is not None else
            config.get_int("bigdl.lm.blockSize", 16))
        self.max_new_tokens = int(
            max_new_tokens if max_new_tokens is not None else
            config.get_int("bigdl.lm.maxNewTokens", 64))
        self.deadline_ms = float(
            deadline_ms if deadline_ms is not None else
            config.get_float("bigdl.lm.deadlineMs", 5000.0))
        self.max_queue_depth = int(
            max_queue_depth if max_queue_depth is not None else
            config.get_int("bigdl.lm.maxQueueDepth", 128))
        self.admission_factor = config.get_float(
            "bigdl.lm.admissionDeadlineFactor", 0.0)
        self.stall_factor = config.get_float("bigdl.lm.stallFactor", 0.0)
        self.warmup_steps = config.get_int("bigdl.lm.warmupSteps", 3)
        self.cooldown_steps = config.get_int("bigdl.lm.cooldownSteps", 8)
        self.grace_period = config.get_float("bigdl.lm.gracePeriod", 5.0)
        self.poll_interval = config.get_float("bigdl.lm.pollInterval", 0.01)
        self.quantize = str(
            quantize if quantize is not None else
            config.get_property("bigdl.lm.quantize", "off") or "off").lower()
        if self.quantize not in ("off", "int8"):
            raise ValueError(
                f"bigdl.lm.quantize must be 'off' or 'int8', got "
                f"{self.quantize!r}")
        if self.quantize == "int8" and not self.graph.quantizable:
            raise UnsupportedModelError(
                f"the int8 decode tier quantizes transformer_lm's Linear "
                f"layers; the {self.graph.family} family has no quantized "
                "tier")
        self.quantize_rtol = config.get_float("bigdl.lm.quantizeRtol", 0.05)
        self.quantize_atol = config.get_float("bigdl.lm.quantizeAtol", 0.05)
        if self.max_context > self.graph.max_seq_len:
            raise ValueError(
                f"bigdl.lm.maxContext {self.max_context} exceeds the "
                f"model's longest position (PositionalEncoding max_len, "
                f"max_position_embeddings) {self.graph.max_seq_len} — "
                "build the model with a larger one or lower maxContext")
        if self.max_batch < 1 or self.max_new_tokens < 1:
            raise ValueError("maxBatch and maxNewTokens must be >= 1")

        # -- KV pool: sized ONCE, preflighted against the HBM budget ------
        self._max_blocks = max(1, math.ceil(self.max_context /
                                            self.block_size))
        n_blocks = int(cache_blocks if cache_blocks is not None else
                       config.get_int("bigdl.lm.cacheBlocks", 0))
        if n_blocks <= 0:
            n_blocks = self.max_batch * self._max_blocks + 1
        self.cache = self.graph.make_cache(n_blocks, self.block_size)  # guarded-by: _lock
        self._buckets = self._bucket_plan(
            prefill_buckets if prefill_buckets is not None else
            config.get_property("bigdl.lm.prefillBuckets", None))

        # -- compiled steps + retrace sentinels ---------------------------
        self._dp = self.graph.params()
        self._dp_q = (_quantize_params(_extract_params(self.graph))
                      if self.quantize == "int8" else None)
        self._build_steps()

        # -- scheduler state (PR 9 idioms) --------------------------------
        self._q: "queue.Queue[TokenStream]" = queue.Queue(
            maxsize=self.max_queue_depth)
        self._pending: "deque[TokenStream]" = deque(   # guarded-by: _lock
            maxlen=self.max_queue_depth)
        self._slots: List[Optional[_Slot]] = [None] * self.max_batch
        # the stream currently mid-admission: the watchdog's async abort
        # (PyThreadState_SetAsyncExc) can surface anywhere in the
        # scheduler thread, so a stream popped from the queue must never
        # live only in a local — _shed_active covers this field
        self._admitting: Optional[TokenStream] = None
        # the decode step dispatched and not yet pulled (the scheduler
        # thread's alone), and when the last one was pulled
        self._inflight: Optional[_Flight] = None
        self._pulled_ns = 0
        self._lock = analysis.make_lock("lm.engine")
        self._payload_acct = _resource_governor.account("lm_admission")
        self._counts: Dict[str, int] = dict.fromkeys(OUTCOMES, 0)  # guarded-by: _lock
        self._counts["submitted"] = 0
        self._next_index = 0
        self._offline_id = 0
        self._cooldown = 0
        self._draining = False                          # guarded-by: _lock
        self._drain_deadline: Optional[float] = None    # guarded-by: _lock
        self._drain_reason = ""                         # guarded-by: _lock
        self._closed = False                            # guarded-by: _lock
        self._started = False                           # guarded-by: _lock
        self._stop_event = threading.Event()
        self._ema = _service_ema(self.warmup_steps)
        self.decode_steps = 0
        self.prefills = 0                               # guarded-by: _lock
        self.tokens_out = 0
        self.watchdog: Optional[HungDispatchWatchdog] = None
        self._thread: Optional[threading.Thread] = None
        window = config.get_int("bigdl.telemetry.percentileWindow", 512)
        self._ttft = telemetry.histogram(
            "LM/ttft_ms", window=window,
            help="submit-to-first-token latency")
        self._itl = telemetry.histogram(
            "LM/itl_ms", window=window,
            help="inter-token gap during streaming decode")
        self._latency = telemetry.histogram(
            "LM/latency_ms", window=window,
            help="per-request submit-to-terminal latency")
        self._queue_wait = telemetry.histogram(
            "LM/queue_wait_ms", window=window,
            help="submit to the scheduler's pop, per admitted request")
        self._prefill_ms = telemetry.histogram(
            "LM/prefill_ms", window=window,
            help="prefill dispatch to its token on the host, per "
                 "admitted request")
        self._h2d = telemetry.counter(
            "LM/h2d_bytes",
            help="host arrays handed to the prefill and decode steps")
        self._d2h = telemetry.counter(
            "LM/d2h_bytes",
            help="what was pulled from the prefill and decode steps: their "
                 "tokens and counts, and a row of log-probs where "
                 "generate() was asked for them")
        # tallies of this engine (stats()) and their process-wide counters
        self._tally: Dict[str, int] = {}                # guarded-by: _lock
        self._tally_counters = {
            name: telemetry.counter(f"LM/{name}", help=text)
            for name, text in (
                ("prompt_tokens", "prompt tokens of admitted requests"),
                ("attn_context_tokens",
                 "context positions of the active slots, summed over decode "
                 "steps (pos + 1 each)"),
                ("attn_selected_tokens",
                 "of those, the positions attention read: the decode "
                 "step's own count under a learned selection, else all"),
                ("kv_rows_fetched",
                 "rows of one pool and layer the decode steps' attention "
                 "fetched through the block tables: the slots of a group "
                 "x the chunks of each trip of its loop over live groups "
                 "(whole chunks up to the group's longest context), or "
                 "under a learned selection the selected rows of the "
                 "slots the step's loop over live slots read, from the "
                 "host's copy of the step's arguments"),
                ("moe_assignments",
                 "routed expert assignments of real tokens, the "
                 "scheduler's prefill and decode steps"),
                ("moe_assignments_held",
                 "of those, the assignments to an expert this model "
                 "holds"),
                ("decode_pulled_bytes",
                 "bytes the scheduler pulled from its decode steps: "
                 "max_batch int32 tokens a step, and the step's three "
                 "counts in the family that counts"),
                ("decode_steps_ahead",
                 "decode steps dispatched while the previous step's tokens "
                 "were not yet on the host (their tokens argument is that "
                 "step's output, still on the device)"),
                ("decode_rows_wasted",
                 "rows a decode step computed for a sequence that had "
                 "ended (eos, deadline, eviction) after the step was "
                 "dispatched; their tokens are dropped"))}
        telemetry.gauge(
            "LM/cache_bytes_per_token",
            help="bytes the pools hold for one token, all layers").set(
                self.cache.bytes_per_token)
        for kind in ("latent", "index"):
            if kind in self.cache.kinds:
                telemetry.gauge(f"LM/{kind}_pool_bytes").set(
                    self.cache.kind_nbytes[kind])
        # what the engine holds on the device while it serves: the
        # parameter trees its steps read (each array once) and the pools
        held = {id(x): x.nbytes for tree in (self._dp, self._dp_q)
                for x in jax.tree_util.tree_leaves(tree)}
        self.weight_bytes = int(sum(held.values()))
        telemetry.gauge(
            "LM/weight_bytes",
            help="bytes of the parameter arrays the steps read").set(
                self.weight_bytes)
        # of those, the weights the steps' products read: every array of
        # two axes or more but the embedding table, which is gathered
        dots = {id(x): x.nbytes for tree in (self._dp, self._dp_q)
                if tree is not None
                for x in jax.tree_util.tree_leaves(
                    {k: v for k, v in tree.items() if k != "embed"})
                if x.ndim >= 2}
        self.dot_weight_bytes = int(sum(dots.values()))
        telemetry.gauge(
            "LM/dot_weight_bytes",
            help="bytes of the weights the steps' products read, at the "
                 "width the engine holds them").set(self.dot_weight_bytes)
        telemetry.gauge(
            "LM/pool_bytes", help="bytes of the cache's pools, all "
            "kinds").set(self.cache.pool_nbytes)

        self.quantization_report: Optional[Dict[str, Any]] = None
        if self._dp_q is not None:
            self._quantization_gate()
        telemetry.setup_done("lm_construct", "lm/construct", t_setup)
        if start:
            self.start()

    # -- compile plan -----------------------------------------------------

    def _bucket_plan(self, spec) -> List[int]:
        """Prefill shape ladder: the ``prefill_buckets`` argument or
        ``bigdl.lm.prefillBuckets`` (``"16,32,64"`` or a sequence of
        ints), else a power-of-two ladder from blockSize up; maxContext
        is always IN the plan so the longest admissible prompt has a
        warmed signature."""
        if spec:
            parts = spec.split(",") if isinstance(spec, str) else spec
            buckets = sorted({int(b) for b in parts if str(b).strip()})
            if not buckets or buckets[0] < 1:
                raise ValueError(
                    f"bigdl.lm.prefillBuckets must be positive ints, got "
                    f"{spec!r}")
            if buckets[-1] > self.max_context:
                raise ValueError(
                    f"bigdl.lm.prefillBuckets {buckets[-1]} exceeds "
                    f"bigdl.lm.maxContext {self.max_context}")
        else:
            buckets, b = [], max(1, self.block_size)
            for _ in range(64):
                if b >= self.max_context:
                    break
                buckets.append(b)
                b *= 2
        return sorted(set(buckets + [self.max_context]))

    def _prefill_bucket(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _build_steps(self) -> None:
        from bigdl_tpu.analysis.program_contracts import (
            lm_decode_contract, lm_full_contract, lm_prefill_contract)
        from bigdl_tpu.analysis.retrace import RetraceSentinel
        from bigdl_tpu.utils.compile_cache import tracked_jit
        B, MB = self.max_batch, self._max_blocks
        pools = _tree_spec(self.cache.pools)
        dec_tail = pools + (
                    jax.ShapeDtypeStruct((B, 1), jnp.int32),
                    jax.ShapeDtypeStruct((B,), jnp.int32),
                    jax.ShapeDtypeStruct((B, MB), jnp.int32),
                    jax.ShapeDtypeStruct((B,), jnp.bool_))
        decode, prefill, full = self.graph.steps(self.block_size, MB)
        # both families, and the int8 tier over the same decode
        decode, prefill = _choosing(decode), _choosing(prefill)

        def wire(fn, label, contract, specs_list,
                 donate=tuple(range(1, 1 + len(pools)))):
            # a step that takes the pools consumes them: XLA aliases
            # each to its output and the scatters run in place
            cached = tracked_jit(fn, label, contract=contract,
                                 donate_argnums=donate)
            if donate:
                cached.on_executable = self.cache.note_executable
            sentinel = RetraceSentinel.from_config(label)
            if sentinel is not None:
                cached.register_sentinel(sentinel)
                for specs in specs_list:
                    sentinel.register_warmup(specs)
                return sentinel.wrap(cached), cached, sentinel
            return cached, cached, None

        self._decode_specs = (_tree_spec(self._dp),) + dec_tail
        self._decode, self._decode_cached, self._decode_sentinel = wire(
            decode, "lm_decode", lm_decode_contract(),
            [self._decode_specs])
        self._prefill_specs = {
            b: (_tree_spec(self._dp),) + pools + (
                jax.ShapeDtypeStruct((1, b), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((MB,), jnp.int32))
            for b in self._buckets}
        self._prefill, self._prefill_cached, self._prefill_sentinel = wire(
            prefill, "lm_prefill", lm_prefill_contract(),
            list(self._prefill_specs.values()))
        self._full_specs = {
            b: (_tree_spec(self._dp),
                jax.ShapeDtypeStruct((1, b), jnp.int32))
            for b in self._buckets}
        self._full, self._full_cached, self._full_sentinel = wire(
            full, "lm_full", lm_full_contract(),
            list(self._full_specs.values()), donate=())
        if self._dp_q is not None:
            self._decode_q_specs = (_tree_spec(self._dp_q),) + dec_tail
            (self._decode_q, self._decode_q_cached,
             self._decode_q_sentinel) = wire(
                decode, "lm_decode_int8",
                lm_decode_contract("lm_decode_int8"),
                [self._decode_q_specs])
        else:
            self._decode_q = self._decode_q_cached = None
            self._decode_q_sentinel = None

    @property
    def sentinels(self) -> Dict[str, Any]:
        """Label -> retrace sentinel for every compiled LM step (absent
        labels ran without a sentinel) — the zero-post-warmup-retrace
        proof reads ``.retraces`` off each."""
        out = {}
        for label, s in (("lm_decode", self._decode_sentinel),
                         ("lm_prefill", self._prefill_sentinel),
                         ("lm_full", self._full_sentinel),
                         ("lm_decode_int8", self._decode_q_sentinel)):
            if s is not None:
                out[label] = s
        return out

    @property
    def timings(self) -> Dict[str, List[Dict[str, Any]]]:
        """Label -> per-signature compile provenance (``CachedStep.
        timings``: source compile|cache, trace/compile/load ms) of every
        compiled LM step, the KV scrub included."""
        steps = {"lm_decode": self._decode_cached,
                 "lm_prefill": self._prefill_cached,
                 "lm_full": self._full_cached,
                 "lm_decode_int8": self._decode_q_cached,
                 "lm_kv_scrub": self.cache.scrub_step}
        return {label: list(step.timings)
                for label, step in steps.items() if step is not None}

    def warmup(self) -> None:
        """AOT-compile every signature the scheduler dispatches (decode
        at its one fixed shape, the KV scrub, each prefill bucket, the
        int8 tier when enabled) so no request ever pays a compile
        against its deadline: ``2 + len(buckets)`` cold compiles, one
        more with int8."""
        t_setup = telemetry.clock_ns()
        self._decode_cached.warmup(*self._decode_specs)
        self.cache.warmup()
        for specs in self._prefill_specs.values():
            self._prefill_cached.warmup(*specs)
        if self._decode_q_cached is not None:
            self._decode_q_cached.warmup(*self._decode_q_specs)
        if (self.cache.aliased_bytes or 0) < self.cache.pool_nbytes:
            logger.warning(
                "LM engine: the compiled steps alias %s of the KV pools' "
                "%d bytes — XLA declined the donation somewhere, and a "
                "step that does not update the pools in place copies "
                "them on every call", self.cache.aliased_bytes,
                self.cache.pool_nbytes)
        telemetry.setup_done("lm_warmup", "lm/warmup", t_setup)

    # -- int8 gate --------------------------------------------------------

    def _quantization_gate(self) -> None:
        """Admission gate for the int8 decode tier: (1) the HLO
        auditor's precision-drift pass over the quantized program, (2)
        fp-vs-int8 next-token log-probs allclose on IDENTICAL KV-pool
        inputs.  Either failing raises :class:`QuantizationGateError` —
        the engine never silently serves drifted logits.

        Both steps donate the pools, so the int8 step runs on what the
        fp step wrote back, and the inputs are still identical where a
        step reads them: the prompt's blocks hold the one prefill's
        k/v, the decode position is overwritten by each step before it
        is gathered, and the dump block (the inactive slots' junk) is
        masked out of the one row compared."""
        from bigdl_tpu.analysis import hlo_audit
        from bigdl_tpu.analysis.hostsync import host_pull
        from bigdl_tpu.analysis.program_contracts import lm_decode_contract
        # audit-only lowering — the gate inspects HLO text; serving
        # dispatch still goes through the tracked CachedStep
        lowered = self._decode_q_cached.lower(  # lint: allow(untracked-jit)
            *self._decode_q_specs)
        report = hlo_audit.audit_step(
            "lm_decode_int8", lowered.as_text(),
            contract=lm_decode_contract("lm_decode_int8"))
        B, MB = self.max_batch, self._max_blocks
        P = max(1, min(8, self.max_context - 1))
        prompt = (np.arange(P, dtype=np.int32) % self.graph.vocab) + 1
        seq_id = -1
        self.cache.allocate(seq_id, P + 1)
        try:
            tok, table_row = self._prefill_step_raw(seq_id, prompt)
            tokens = np.full((B, 1), 1, np.int32)
            positions = np.zeros((B,), np.int32)
            tables = np.full((B, MB), DUMP_BLOCK, np.int32)
            active = np.zeros((B,), bool)
            tokens[0, 0], positions[0] = tok, P
            tables[0], active[0] = table_row, True
            tail = (tokens, positions, tables, active)
            with self._lock:
                _, lp_fp, self.cache.pools, _ = self._decode(
                    self._dp, *self.cache.pools, *tail)
            with self._lock:
                _, lp_q, self.cache.pools, _ = self._decode_q(
                    self._dp_q, *self.cache.pools, *tail)
            a = np.asarray(host_pull(lp_fp, what="lm gate fp logits"))[0]
            b = np.asarray(host_pull(lp_q, what="lm gate int8 logits"))[0]
        finally:
            self.cache.free_seq(seq_id)
        close = bool(np.allclose(b, a, rtol=self.quantize_rtol,
                                 atol=self.quantize_atol))
        diff = float(np.max(np.abs(b - a)))
        self.quantization_report = {
            "audit_ok": bool(report.ok),
            "violations": [str(v) for v in report.violations],
            "allclose": close, "max_abs_diff": diff,
            "rtol": self.quantize_rtol, "atol": self.quantize_atol}
        if not report.ok:
            raise QuantizationGateError(
                "int8 decode tier failed the auditor precision gate: "
                + "; ".join(str(v) for v in report.violations))
        if not close:
            raise QuantizationGateError(
                f"int8 decode logits drifted past the gate: max |diff| "
                f"{diff:.4g} vs rtol={self.quantize_rtol} "
                f"atol={self.quantize_atol} — raise the thresholds "
                "explicitly or serve fp")

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "LMServingEngine":
        if self._closed:
            raise ServingInfraError(
                "engine is terminal: stop() is one-way — build a new "
                "engine instead of restarting this one")
        if self._started:
            return self
        with self._lock:
            self._started = True
        self._thread = threading.Thread(target=self._scheduler_loop,
                                        daemon=True, name="lm-scheduler")
        self._thread.start()
        return self

    def stop(self, grace: Optional[float] = None) -> None:
        """Graceful shutdown (idempotent + terminal, the PR 9
        contract): admission closes, queued prompts and in-flight
        sequences drain within ``grace``, leftovers are shed
        retriably."""
        if not self._started or self._closed:
            with self._lock:
                self._closed = True
            self._drain_leftovers()
            return
        with self._lock:
            if not self._draining:
                self._begin_drain_locked("stop", time.monotonic(), grace)
            elif grace is not None:
                self._drain_deadline = time.monotonic() + grace
        self._stop_event.set()
        t = self._thread
        if t is not None:
            budget = grace if grace is not None else self.grace_period
            t.join(timeout=budget + 10.0)
        self._drain_leftovers()
        with self._lock:
            self._closed = True

    def close(self) -> None:
        self.stop()

    def __enter__(self) -> "LMServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def terminal(self) -> bool:
        return self._closed

    @property
    def draining(self) -> bool:
        return self._draining

    def queue_depth(self) -> int:
        return self._q.qsize() + len(self._pending)

    def scheduler_alive(self) -> bool:
        t = self._thread
        return bool(t is not None and t.is_alive())

    # -- admission --------------------------------------------------------

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               eos_id: Optional[int] = None) -> TokenStream:
        """Admit one prompt or raise :class:`Overloaded` — fast, at the
        door.  Returns the streaming :class:`TokenStream` handle."""
        now = telemetry.clock_ns()
        deadline = float(deadline_ms if deadline_ms is not None
                         else self.deadline_ms)
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.max_new_tokens)
        payload_nbytes = _item_nbytes(prompt)
        _resource_governor.check_item("lm_admission", payload_nbytes)
        telemetry.counter("LM/submitted").inc()
        # trace id minted at the admission door — BEFORE the rejection
        # checks, so a rejected prompt still explains itself
        tid = request_trace.mint("lm", deadline_ms=deadline,
                                 max_new_tokens=max_new)
        with self._lock:
            self._counts["submitted"] += 1
            if self._closed or (self._stop_event.is_set() and
                                not self._draining):
                raise self._reject_locked("closed", trace_id=tid)
            if self._draining:
                raise self._reject_locked("draining", trace_id=tid)
            if self._cooldown > 0:
                raise self._reject_locked("cooldown", trace_id=tid)
            depth = self._q.qsize() + len(self._pending)
            if depth >= self.max_queue_depth:
                raise self._reject_locked("queue full", depth,
                                          trace_id=tid)
            n = getattr(prompt, "shape", None)
            n = (int(np.prod(n)) if n is not None
                 else len(prompt) if hasattr(prompt, "__len__") else None)
            if (n is not None and self.cache.blocks_for(n + max_new) >
                    self.cache.allocatable_blocks):
                # can NEVER be scheduled: larger than the entire pool
                raise self._reject_locked("kv blocks exhausted", depth,
                                          trace_id=tid)
            if self.admission_factor > 0:
                ema = self._ema.ema
                if ema is not None:
                    waves = math.ceil((depth + 1) / self.max_batch)
                    projected = waves * ema * max_new
                    if projected > self.admission_factor * deadline:
                        raise self._reject_locked(
                            "projected wait", depth, trace_id=tid,
                            projected_wait_ms=projected,
                            deadline_ms=deadline)
            stream = TokenStream(prompt, self._next_index, now,
                                 now + int(deadline * 1e6), max_new,
                                 eos_id, trace_id=tid)
            self._next_index += 1
        request_trace.instant(tid, "request/admit", index=stream.index,
                              depth=depth)
        # charged BEFORE the enqueue — once the stream is in the queue
        # the scheduler owns it, and a completion racing a post-enqueue
        # charge would read payload_nbytes == 0 and leak the accounting
        with stream._cv:
            stream.payload_nbytes = payload_nbytes
        self._payload_acct.add(payload_nbytes)
        try:
            self._q.put_nowait(stream)
        except queue.Full:
            with stream._cv:
                stream.payload_nbytes = 0
            self._payload_acct.sub(payload_nbytes)
            with self._lock:
                raise self._reject_locked("queue full",
                                          self.max_queue_depth,
                                          trace_id=tid)
        if self._closed:
            # scheduler exited between the admission check and the
            # enqueue (it marks _closed BEFORE its final sweep) — shed
            # it NOW rather than strand it unaccounted
            self._drain_leftovers()
        telemetry.gauge("LM/queue_depth").set(self.queue_depth())
        return stream

    def _reject_locked(self, reason: str, depth: Optional[int] = None,
                       trace_id: Optional[str] = None, **kw) -> Overloaded:
        self._counts["rejected"] += 1
        telemetry.counter("LM/rejected").inc()
        telemetry.counter("LM/rejected",
                          labels={"reason": reason.replace(" ", "_")}).inc()
        err = Overloaded(reason,
                         queue_depth=(depth if depth is not None
                                      else self.queue_depth()),
                         max_depth=self.max_queue_depth, **kw)
        request_trace.verdict(trace_id, "rejected", error=err,
                              reason=reason.replace(" ", "_"))
        return err

    def _validate(self, stream: TokenStream, chaos) -> np.ndarray:
        """Per-request prompt validation — the taxonomy choke point:
        anything wrong with the PAYLOAD raises :class:`ServingDataError`
        here, quarantining one stream instead of killing a batch."""
        if chaos.poison_prompt(stream.index):
            raise ServingDataError(
                f"chaos: poison prompt at admission position "
                f"{stream.index}")
        try:
            row = np.asarray(stream.prompt)
        except Exception as e:
            raise ServingDataError(
                f"undecodable prompt payload: {e!r}") from e
        if row.ndim != 1 or row.size == 0:
            raise ServingDataError(
                f"prompt must be a non-empty 1-D token-id sequence, got "
                f"shape {row.shape}")
        if not np.issubdtype(row.dtype, np.integer):
            raise ServingDataError(
                f"prompt token ids must be integers, got dtype "
                f"{row.dtype}")
        if row.size + stream.max_new_tokens > self.max_context:
            raise ServingDataError(
                f"prompt of {row.size} token(s) + max_new_tokens "
                f"{stream.max_new_tokens} exceeds bigdl.lm.maxContext "
                f"{self.max_context}")
        return row.astype(np.int32)

    # -- accounting -------------------------------------------------------

    def _finish_stream(self, stream: TokenStream, outcome: str,
                       error: Optional[BaseException] = None,
                       reason: Optional[str] = None) -> bool:
        if not stream._finish(outcome, error=error):
            return False
        with stream._cv:
            nbytes = stream.payload_nbytes
            stream.payload_nbytes = 0
        if nbytes:
            self._payload_acct.sub(nbytes)
        with self._lock:
            self._counts[outcome] += 1
        # the trace-recording choke point for every LM terminal verdict;
        # a completed tail stream becomes a latency-histogram exemplar
        request_trace.verdict(stream.trace_id, outcome, error=error,
                              reason=reason)
        telemetry.counter(f"LM/{outcome}").inc()
        if reason:
            telemetry.counter(f"LM/{outcome}",
                              labels={"reason": reason}).inc()
        if outcome == "completed":
            self._latency.observe(stream.latency_ms(),
                                  exemplar=stream.trace_id)
        return True

    def _count_aux(self, aux) -> Optional[int]:
        """What a step of the scheduler counted on the device, on the host
        already (it crossed in the one pull that brought the step's
        token): the expert layers' assignments into their two tallies;
        returns the context rows the step's attention read (None: the
        family counts nothing, it reads every row)."""
        if aux is None:
            return None
        made, held, read = aux
        self._count("moe_assignments", int(made))
        self._count("moe_assignments_held", int(held))
        return int(read)

    def _count(self, name: str, n: int) -> None:
        with self._lock:
            self._tally[name] = self._tally.get(name, 0) + n
        self._tally_counters[name].inc(n)

    def stats(self) -> Dict[str, Any]:
        """Outcome counters + the accounting identity residual
        (``unaccounted`` includes streams still in flight — quiesce
        first for the exact identity)."""
        with self._lock:
            out: Dict[str, Any] = dict(self._counts)
            tally = dict(self._tally)
        out["unaccounted"] = out["submitted"] - sum(out[o]
                                                    for o in OUTCOMES)
        out["decode_steps"] = self.decode_steps
        out["prefills"] = self.prefills
        out["tokens_out"] = self.tokens_out
        out["queue_depth"] = self.queue_depth()
        out["decode_ema_ms"] = self._ema.ema
        out["cooldown"] = self._cooldown
        out["draining"] = self._draining
        out["active_slots"] = sum(s is not None for s in self._slots)
        out["free_blocks"] = self.cache.free_blocks
        out["used_blocks"] = self.cache.used_blocks
        # donation: pool bytes the steps update in place (pool_nbytes
        # when engaged, 0 when XLA declined, None before a compile), and
        # dead pools replaced by zeros
        out["kv_aliased_bytes"] = self.cache.aliased_bytes
        out["kv_pool_rebuilds"] = self.cache.rebuilds
        out["cache_bytes_per_token"] = self.cache.bytes_per_token
        out["pool_bytes"] = dict(self.cache.kind_nbytes)
        out["weight_bytes"] = self.weight_bytes
        out["dot_weight_bytes"] = self.dot_weight_bytes
        for name in self._tally_counters:
            out[name] = tally.get(name, 0)
        return out

    # -- the scheduler thread --------------------------------------------

    def _any_active(self) -> bool:
        """A slot is live or a step is in flight: a decode pass has work."""
        return (self._inflight is not None or
                any(s is not None for s in self._slots))

    def _scheduler_loop(self) -> None:
        """The scheduler thread.  A pass with work is an admission pass
        (when something waits) and one :meth:`_decode_iteration`, which
        keeps ONE decode step in flight: in a run of decode steps the host
        holds the tokens of step n-1 and the device runs step n while the
        pass dispatches step n+1.  The host holds every live slot's token
        only at the first step, after an idle wait, and after an admission
        pass that prefilled.  The loop ends when nothing waits, no slot is
        live and no step is in flight; every failure sheds the slots and
        the step in flight together (:meth:`_shed_active`)."""
        telemetry.name_thread("lm-scheduler")
        wd = None
        if self.stall_factor > 0:
            wd = HungDispatchWatchdog(
                self.stall_factor, warmup=self.warmup_steps,
                cooldown=self.cooldown_steps,
                poll_interval=min(self.poll_interval, 0.05))
            wd.start()                    # driver tid = this thread
            self.watchdog = wd
        try:
            drained = False
            while not drained:
                if not self._draining:
                    if elastic.preemption_requested():
                        with self._lock:
                            self._begin_drain_locked(
                                "preemption",
                                elastic.preemption_requested_at() or
                                time.monotonic())
                    elif self._stop_event.is_set():
                        with self._lock:
                            self._begin_drain_locked("stop",
                                                     time.monotonic())
                if self._draining:
                    if time.monotonic() > self._drain_deadline:
                        self._drain_leftovers()
                        self._shed_active(ServingInfraError(
                            "engine draining: decode did not finish "
                            "within the grace period — retriable"),
                            "drained")
                        drained = True
                        continue
                    if (self._q.empty() and not self._pending and
                            not self._any_active()):
                        drained = True
                        continue
                active = True
                # one lm/iteration tree per pass that has work; a pass
                # that only polls the empty queue is lm/idle_wait alone.
                # An admission pass over nothing finds nothing, so one
                # look at the queue decides both
                try:
                    # the watchdog abort can surface during admission
                    # (validate/prefill) just as during decode — both run
                    # on the step clock, so both sit under one handler
                    n_active = sum(s is not None for s in self._slots)
                    waiting = bool(self._pending) or not self._q.empty()
                    with (telemetry.span("lm/iteration",
                                         step=self.decode_steps + 1,
                                         active=n_active)
                          if waiting or self._any_active()
                          else nullcontext()):
                        if waiting:
                            self._admit_waiting(wd)
                        active = self._any_active()
                        if active:
                            self._decode_iteration(wd)
                except HungDispatchError:
                    ema = self._ema.ema
                    baseline = (f"{ema:.1f} ms EMA" if ema is not None
                                else "unseeded EMA")
                    diag = HungDispatchError(
                        f"decode step wedged past "
                        f"{self.stall_factor:.1f}x the iteration "
                        f"baseline ({baseline}) — the hung-dispatch "
                        "watchdog aborted it")
                    self._shed_active(diag, "hung_decode", cool=True)
                    if wd is not None:
                        wd.heartbeat()
                except Exception as e:  # noqa: BLE001 — must outlive
                    self._shed_active(ServingInfraError(
                        f"decode failed: {e!r}"), "infra")
                if not active:
                    try:
                        with (wd.paused() if wd is not None
                              else nullcontext()), \
                                telemetry.span("lm/idle_wait"):
                            stream = self._q.get(
                                timeout=self.poll_interval)
                        with self._lock:
                            self._pending.append(stream)
                    except queue.Empty:
                        with self._lock:
                            if self._cooldown:
                                # backlog clear: a cooldown with no
                                # traffic would never end
                                self._cooldown = 0
        finally:
            if wd is not None:
                wd.stop()
            # _closed BEFORE the sweep: a racing submit that enqueued
            # past the drain either observes _closed (and sheds its own
            # stream) or enqueued before this sweep — exactly one
            with self._lock:
                self._closed = True
            self._drain_leftovers()
            self._shed_active(ServingInfraError(
                "scheduler exited with the sequence in flight — "
                "retriable"), "infra")

    def _begin_drain_locked(self, reason: str, started_at: float,
                            grace: Optional[float] = None) -> None:
        budget = grace if grace is not None else self.grace_period
        # deadline published BEFORE the flag (lock-free readers)
        self._drain_deadline = started_at + budget
        self._drain_reason = reason
        self._draining = True
        incident.record("lm/drain", reason=reason, grace_s=budget,
                        queued=self.queue_depth())
        logger.info("LM engine draining (%s): grace %.1f s, %d queued, "
                    "%d active", reason, budget, self.queue_depth(),
                    sum(s is not None for s in self._slots))

    def _drain_leftovers(self) -> None:
        """Shed everything still waiting (queue + block-starved pending
        holdover) — retriable by construction.  Bounded sweeps: both
        containers are capped at ``maxQueueDepth``."""
        shed = 0
        for src in ("queue", "pending"):
            for _ in range(self.max_queue_depth + 1):
                if src == "queue":
                    try:
                        stream = self._q.get_nowait()
                    except queue.Empty:
                        break
                else:
                    try:
                        with self._lock:
                            stream = self._pending.popleft()
                    except IndexError:
                        break
                err = ServingInfraError(
                    "engine draining: prompt was not scheduled within "
                    "the grace period — retriable")
                shed += self._finish_stream(stream, "shed", error=err,
                                            reason="drained")
        if shed:
            incident.record("lm/drain_shed", count=shed)
            logger.warning("LM drain shed %d queued stream(s)", shed)
        telemetry.gauge("LM/queue_depth").set(self.queue_depth())

    def _shed_active(self, error: Exception, reason: str,
                     cool: bool = False) -> None:
        """Fail every in-flight sequence with the diagnosis and free
        its blocks; the decode step in flight goes with them, unpulled
        (its tokens belong to the streams failed here).  Each victim gets
        its OWN exception instance — concurrent ``result()`` raises on a
        shared object would interleave tracebacks across client threads."""
        failed = 0
        first_trace: Optional[str] = None
        self._inflight = None
        # the failure may be a dispatch that consumed the pools and never
        # wrote them back; everything holding a block is freed below
        doomed = [s.stream.seq_id for s in self._slots if s is not None]
        if self._admitting is not None:
            doomed.append(self._admitting.seq_id)
        self.cache.ensure_live(releasing=doomed)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            self._slots[i] = None
            self.cache.free_seq(slot.stream.seq_id)
            if first_trace is None:
                first_trace = slot.stream.trace_id
            failed += self._finish_stream(
                slot.stream, "shed", error=type(error)(*error.args),
                reason=reason)
        stream = self._admitting
        if stream is not None:
            # an abort landed mid-admission: the stream was popped from
            # the queue but never reached a slot.  free_seq and
            # _finish_stream are both idempotent, so overlap with the
            # slot sweep above is harmless.
            self._admitting = None
            self.cache.free_seq(stream.seq_id)
            if first_trace is None:
                first_trace = stream.trace_id
            failed += self._finish_stream(
                stream, "shed", error=type(error)(*error.args),
                reason=reason)
        if cool:
            with self._lock:
                self._cooldown = max(self._cooldown, self.cooldown_steps)
        if failed:
            incident.record("lm/shed_active", reason=reason,
                            victims=failed, error=type(error).__name__)
            incident.maybe_dump(f"lm/{reason}", trace_id=first_trace)
            logger.error(
                "LM decode aborted (%s): %d in-flight stream(s) failed "
                "with %s%s", reason, failed, type(error).__name__,
                f"; cooling down for {self.cooldown_steps} steps"
                if cool else "")

    def _admit_waiting(self, wd) -> None:
        """Fill vacant decode slots from the pending holdover then the
        queue: expired prompts are shed, poison ones quarantined
        (neither consumes a slot); a block-starved prompt goes back to
        the FRONT of the holdover (FIFO) and admission stops until a
        finishing sequence frees blocks."""
        with telemetry.span("lm/admit") as sp:
            sp.set(admitted=self._admit_into_slots(wd))

    def _admit_into_slots(self, wd) -> int:
        """The admission pass itself; the number of prompts prefilled."""
        from bigdl_tpu.utils import chaos
        admitted = 0
        for _ in range(self.max_batch):
            slot_idx = next((i for i, s in enumerate(self._slots)
                             if s is None), None)
            if slot_idx is None:
                return admitted
            stream = None
            with self._lock:
                if self._pending:
                    stream = self._pending.popleft()
            if stream is None:
                try:
                    stream = self._q.get_nowait()
                except queue.Empty:
                    return admitted
            # published so the watchdog's async abort cannot strand a
            # stream that lives only in this local (cleared at every
            # resting point; double-finish below is a guarded no-op)
            self._admitting = stream
            now = telemetry.clock_ns()
            request_trace.record_span(stream.trace_id,
                                      "request/queue_wait",
                                      stream.submit_ns, now)
            if now > stream.deadline_ns:
                waited = (now - stream.submit_ns) / 1e6
                deadline = (stream.deadline_ns - stream.submit_ns) / 1e6
                self._finish_stream(
                    stream, "shed",
                    error=DeadlineExceeded(waited, deadline),
                    reason="expired")
                self._admitting = None
                continue
            try:
                prompt = self._validate(stream, chaos)
            except ServingDataError as e:
                incident.record("lm/quarantine", index=stream.index,
                                error=type(e).__name__)
                self._finish_stream(stream, "quarantined", error=e)
                # bundle AFTER the verdict so the trace it embeds is
                # terminal; the write stalls the scheduler for tens of
                # ms — legitimate work, not a hung decode step, so the
                # watchdog is paused or it would fire a spurious abort
                with (wd.paused() if wd is not None else nullcontext()):
                    incident.maybe_dump("lm/quarantine",
                                        trace_id=stream.trace_id)
                self._admitting = None
                continue
            if not self.cache.can_allocate(prompt.size +
                                           stream.max_new_tokens):
                with self._lock:
                    self._pending.appendleft(stream)
                self._admitting = None
                return admitted
            self.cache.allocate(stream.seq_id,
                                prompt.size + stream.max_new_tokens)
            t_pf = telemetry.clock_ns()
            try:
                with telemetry.span(
                        "lm/prefill", prompt_tokens=int(prompt.size),
                        bucket=self._prefill_bucket(int(prompt.size)),
                        query_blocks=self.graph.query_blocks(
                            self._prefill_bucket(int(prompt.size))),
                        active=sum(s is not None for s in self._slots),
                        trace_id=stream.trace_id):
                    tok, table_row = self._prefill_step_raw(
                        stream.seq_id, prompt, served=True)
            except Exception as e:  # noqa: BLE001 — fail one stream
                self.cache.free_seq(stream.seq_id)
                self._finish_stream(stream, "shed", error=ServingInfraError(
                    f"prefill failed: {e!r}"), reason="infra")
                self._admitting = None
                continue
            if wd is not None:
                wd.heartbeat()
            t_done = telemetry.clock_ns()
            request_trace.record_span(stream.trace_id, "request/prefill",
                                      t_pf, t_done,
                                      prompt_tokens=int(prompt.size))
            # observed here, not in _prefill_step_raw: generate()'s
            # offline prefills are no admissions and must not count
            admitted += 1
            self._queue_wait.observe((now - stream.submit_ns) / 1e6)
            self._prefill_ms.observe((t_done - t_pf) / 1e6)
            self._count("prompt_tokens", int(prompt.size))
            stream._emit(tok)
            request_trace.instant(stream.trace_id, "request/emit",
                                  token=int(tok), first=True)
            self._ttft.observe(stream.ttft_ms(),
                               exemplar=stream.trace_id)
            telemetry.counter("LM/tokens").inc()
            self.tokens_out += 1
            if ((stream.eos_id is not None and tok == stream.eos_id) or
                    stream.max_new_tokens <= 1):
                self.cache.free_seq(stream.seq_id)
                self._finish_stream(stream, "completed")
                self._admitting = None
                continue
            self._slots[slot_idx] = _Slot(stream, int(prompt.size), tok,
                                          table_row)
            self._admitting = None
        telemetry.gauge("LM/queue_depth").set(self.queue_depth())
        return admitted

    def _prefill_step_raw(self, seq_id: int, prompt: np.ndarray,
                          served: bool = False) -> Tuple[int, np.ndarray]:
        """Run the bucketed prefill for an ALLOCATED sequence: scatter
        the prompt's k/v into its blocks, return the first greedy token
        (1-based, the step's own choice: one int32 crosses, with the
        step's counts in the same pull when ``served``; the row of
        log-probs stays on the device) and the dump-padded table row the
        decode step gathers through."""
        from bigdl_tpu.analysis.hostsync import host_pull
        P = int(prompt.size)
        bucket = self._prefill_bucket(P)
        padded = np.ones((1, bucket), np.int32)
        padded[0, :P] = prompt
        blocks = self.cache.table(seq_id)
        table_row = np.full((self._max_blocks,), DUMP_BLOCK, np.int32)
        table_row[:len(blocks)] = blocks
        # the step consumes the pools: written back in the statement
        # that dispatches, so no line can see a deleted array
        with self._lock:
            tok, _, self.cache.pools, aux = self._prefill(
                self._dp, *self.cache.pools, padded, np.int32(P), table_row)
        # the tallies are of admitted requests, as the histograms are:
        # generate()'s offline steps neither pull nor count theirs
        pulled = (tok, aux if served else None)
        with telemetry.span("lm/prefill_wait"):
            tok, aux = host_pull(pulled, what="lm prefill token")
        self._count_aux(aux)
        self._h2d.inc(padded.nbytes + 4 + table_row.nbytes)
        self._d2h.inc(_pulled_nbytes(pulled))
        with self._lock:
            self.prefills += 1
        telemetry.counter("LM/prefills").inc()
        return int(tok), table_row

    def _decode_iteration(self, wd) -> None:
        """One pass of the continuous-batching heartbeat, with ONE decode
        step kept in flight.  In a run of decode steps the pass (1)
        dispatches step n+1, whose ``tokens`` argument is step n's ``tok``
        output as it lies on the device, (2) pulls step n's tokens (and
        counts), which waits only for what is left of step n, and (3)
        emits them and finishes, vacates and frees what ended: building,
        dispatching, emitting and the loop's own work all run while the
        device executes a step.

        Nothing is ahead when the host holds the token of a slot that goes
        on: at the first step, after an idle wait, and after an admission
        pass that prefilled (the prefill was pulled, so the step in flight
        has finished too and is retired first).  That step is built from
        the host's tokens and left in flight; the next pass dispatches
        ahead of it.  So a step dispatched ahead only ever carries
        sequences that continue, and host and device tokens never mix."""
        prev = self._inflight
        if prev is not None and any(s.token_on_host()
                                    for _, s in self._going()):
            self._inflight = None
            self._retire(prev, wd)
            prev = None
        self._inflight = self._dispatch_decode(prev)
        if prev is not None:
            self._retire(prev, wd)

    def _going(self) -> List[Tuple[int, _Slot]]:
        """The live slots whose sequence goes on, with their index."""
        return [(i, s) for i, s in enumerate(self._slots)
                if s is not None and s.goes_on()]

    def _dispatch_decode(self, prev: Optional[_Flight]) -> Optional[_Flight]:
        """Dispatch ONE fused decode step over every slot whose sequence
        goes on, and advance what those slots have dispatched; None when
        no sequence goes on.  ``prev`` is the step still in flight, whose
        ``tok`` output is this step's ``tokens`` (every slot that goes on
        has its row there), or None: the tokens are the host's."""
        from bigdl_tpu.utils import chaos
        going = self._going()
        if not going:
            return None
        self.decode_steps += 1
        step = self.decode_steps
        telemetry.counter("LM/decode_steps").inc()
        chaos.on_decode_step(step)
        if chaos.evict_block(step):
            victim = next((i for i, s in enumerate(self._slots)
                           if s is not None), None)
            if victim is not None:
                # finish-FIRST: the watchdog's async abort sweeps slots
                # and _admitting only — a stream finished before its
                # slot clears is a guarded no-op for the sweep, but a
                # slot cleared before the finish would strand the stream
                # unaccounted forever.  (Its row of the step in flight is
                # dropped at emission; the scrub runs after that step.)
                slot = self._slots[victim]
                self._finish_stream(slot.stream, "shed",
                                    error=ServingInfraError(
                                        "chaos: kv blocks evicted under "
                                        "an active sequence — retriable"),
                                    reason="evicted")
                self._slots[victim] = None
                self.cache.free_seq(slot.stream.seq_id)
                going = self._going()
                if not going:
                    return None
        t0 = telemetry.clock_ns()
        B, MB = self.max_batch, self._max_blocks
        with telemetry.span("lm/decode_build"):
            tokens = np.ones((B, 1), np.int32) if prev is None else prev.toks
            positions = np.zeros((B,), np.int32)
            tables = np.full((B, MB), DUMP_BLOCK, np.int32)
            active = np.zeros((B,), bool)
            for i, slot in going:
                if prev is None:
                    tokens[i, 0] = slot.last_token
                positions[i] = slot.position
                tables[i] = slot.table_row
                active[i] = True
        dp, fn = ((self._dp_q, self._decode_q)
                  if self._dp_q is not None else (self._dp, self._decode))
        with telemetry.span("lm/decode_dispatch"), self._lock:
            toks, _, self.cache.pools, aux = fn(
                dp, *self.cache.pools, tokens, positions, tables, active)
        for _, slot in going:
            slot.position += 1
            slot.dispatched += 1
        self._h2d.inc((tokens.nbytes if prev is None else 0)
                      + positions.nbytes + tables.nbytes + active.nbytes)
        if prev is not None:
            self._count("decode_steps_ahead", 1)
        return _Flight(step, t0, toks, aux,
                       [(i, slot.stream) for i, slot in going],
                       positions, active)

    def _retire(self, flight: _Flight, wd) -> None:
        """Pull a dispatched step's tokens, count it, emit it.  The step
        chose every slot's token on the device; ONE pull brings the
        ``max_batch`` int32 (and the step's counts, where the family
        counts) to the host, and the ``(max_batch, vocab)`` log-probs never
        cross.  The wait is the device time still to run (what is left of
        the step when the next one was dispatched ahead of it) plus one
        round trip."""
        from bigdl_tpu.analysis.hostsync import host_pull
        nbytes = _pulled_nbytes((flight.toks, flight.aux))
        with telemetry.span("lm/decode_wait", bytes=nbytes):
            toks, aux = host_pull((flight.toks, flight.aux),
                                  what="lm decode tokens")
        read = self._count_aux(aux)
        self._d2h.inc(nbytes)
        self._count("decode_pulled_bytes", nbytes)
        # every active slot's context, from the host's positions, and the
        # rows of it attention read: the device's own count where the
        # family selects, else all of them
        positions, active = flight.positions, flight.active
        context = int(positions[active].astype(np.int64).sum()
                      + active.sum())
        self._count("attn_context_tokens", context)
        self._count("attn_selected_tokens",
                    context if read is None else read)
        self._count("kv_rows_fetched", self.graph.rows_fetched(
            positions, active, self.block_size, self._max_blocks))
        now = telemetry.clock_ns()
        with telemetry.span("lm/emit", tokens=len(flight.rows)):
            wasted = self._emit_tokens(flight, toks, now)
        if wasted:
            self._count("decode_rows_wasted", wasted)
        # one iteration, pull to pull; from its own build where the step
        # was not dispatched ahead (an idle wait or a prefill lay between)
        ms = (now - max(flight.t0, self._pulled_ns)) / 1e6
        self._pulled_ns = now
        self._ema.observe(ms)
        if wd is not None:
            wd.heartbeat()
        with self._lock:
            if self._cooldown > 0:
                self._cooldown -= 1
        telemetry.gauge("LM/decode_ms").set(ms)
        telemetry.gauge("LM/slot_occupancy").set(
            sum(s is not None for s in self._slots) / max(1, self.max_batch))

    def _emit_tokens(self, flight: _Flight, toks: np.ndarray,
                     now: int) -> int:
        """The per-row half of retiring a step: each row goes to the
        stream it was dispatched for, if that stream still holds the slot;
        emit, and for a sequence that ends here finish, vacate and free.
        A row whose sequence ended after the dispatch (on its ``eos_id``,
        on its deadline, evicted) is dropped, whoever holds the slot now;
        returns how many were.  The blocks an end frees are scrubbed after
        the step already in flight, which may still write one position of
        them: freed blocks stay zero."""
        wasted = 0
        col = toks[:, 0]
        for i, stream in flight.rows:
            slot = self._slots[i]
            if slot is None or slot.stream is not stream:
                wasted += 1
                continue
            tok = int(col[i])
            slot.generated += 1
            slot.last_token = tok
            self._itl.observe((now - slot.last_emit_ns) / 1e6)
            slot.last_emit_ns = now
            stream._emit(tok)
            request_trace.record_span(stream.trace_id,
                                      "request/decode_step", flight.t0, now,
                                      step=flight.step, token=tok)
            telemetry.counter("LM/tokens").inc()
            self.tokens_out += 1
            if ((stream.eos_id is not None and tok == stream.eos_id) or
                    slot.generated >= stream.max_new_tokens):
                # finish-FIRST (same discipline as the eviction branch):
                # an async watchdog abort landing between these lines
                # must find either an occupied slot (sweep accounts it)
                # or a finished stream (sweep no-ops) — never a cleared
                # slot with an unaccounted stream
                self._finish_stream(stream, "completed")
                self._slots[i] = None
                self.cache.free_seq(stream.seq_id)
            elif now > stream.deadline_ns:
                # mid-stream expiry AFTER emitting: the streamed prefix
                # stays with the client, the terminal error says why it
                # stopped — the partially-streamed-then-failed shape
                waited = (now - stream.submit_ns) / 1e6
                deadline = (stream.deadline_ns - stream.submit_ns) / 1e6
                self._finish_stream(
                    stream, "shed",
                    error=DeadlineExceeded(waited, deadline),
                    reason="expired")
                self._slots[i] = None
                self.cache.free_seq(stream.seq_id)
        return wasted

    # -- offline generation (parity + baseline) ---------------------------

    def _offline_seq_id(self) -> int:
        # negative ids so offline allocations can never collide with a
        # stream's admission-index seq_id
        self._offline_id -= 1
        return self._offline_id

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 return_logps: bool = False):
        """Offline greedy generation through the PAGED path (prefill +
        single-token decode over the block table) — the exact compiled
        steps the scheduler dispatches, minus the scheduler, and the
        tokens taken as the scheduler takes them: the step's own choice,
        one int32 pulled a step.  ``return_logps=True`` also pulls the
        row of float32 log-probs each decode step chose from (the parity
        proofs and the benchmark's precision gate ask for it).  Refused
        while the scheduler runs (it owns the slots and pools)."""
        from bigdl_tpu.analysis.hostsync import host_pull
        if self._started:
            raise ServingInfraError(
                "generate() is the offline path — the scheduler owns the "
                "decode slots once start() has run; use submit()")
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ServingDataError(
                f"prompt must be a non-empty 1-D token-id sequence, got "
                f"shape {prompt.shape}")
        prompt = prompt.astype(np.int32)
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.max_new_tokens)
        if prompt.size + max_new > self.max_context:
            raise ServingDataError(
                f"prompt of {prompt.size} token(s) + max_new_tokens "
                f"{max_new} exceeds bigdl.lm.maxContext "
                f"{self.max_context}")
        B, MB = self.max_batch, self._max_blocks
        seq_id = self._offline_seq_id()
        self.cache.allocate(seq_id, int(prompt.size) + max_new)
        try:
            tok, table_row = self._prefill_step_raw(seq_id, prompt)
            out_tokens = [tok]
            logps: List[np.ndarray] = []
            dp, fn = ((self._dp_q, self._decode_q)
                      if self._dp_q is not None
                      else (self._dp, self._decode))
            position = int(prompt.size)
            for _ in range(max_new - 1):
                if eos_id is not None and out_tokens[-1] == eos_id:
                    break
                tokens = np.ones((B, 1), np.int32)
                positions = np.zeros((B,), np.int32)
                tables = np.full((B, MB), DUMP_BLOCK, np.int32)
                active = np.zeros((B,), bool)
                tokens[0, 0], positions[0] = out_tokens[-1], position
                tables[0], active[0] = table_row, True
                with self._lock:
                    tok, lp, self.cache.pools, _ = fn(
                        dp, *self.cache.pools, tokens, positions, tables,
                        active)
                self._h2d.inc(tokens.nbytes + positions.nbytes
                              + tables.nbytes + active.nbytes)
                pulled = (tok, lp if return_logps else None)
                self._d2h.inc(_pulled_nbytes(pulled))
                tok, lp = host_pull(pulled, what="lm offline decode token")
                out_tokens.append(int(tok[0, 0]))
                if return_logps:
                    logps.append(lp[0])
                position += 1
        finally:
            self.cache.free_seq(seq_id)
        return (out_tokens, logps) if return_logps else out_tokens

    def generate_sequential(self, prompt,
                            max_new_tokens: Optional[int] = None,
                            eos_id: Optional[int] = None,
                            return_logps: bool = False):
        """The KV-cache-free baseline the bench's speedup claim is
        measured against: one TEACHER-FORCED full forward over the
        whole growing sequence per emitted token (what serving without
        a decode cache actually costs).  Greedy tokens are bit-identical
        to :meth:`generate`; per-position log-probs agree to allclose
        (the reductions are shaped differently)."""
        from bigdl_tpu.analysis.hostsync import host_pull
        prompt = np.asarray(prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ServingDataError(
                f"prompt must be a non-empty 1-D token-id sequence, got "
                f"shape {prompt.shape}")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.max_new_tokens)
        if prompt.size + max_new > self.max_context:
            raise ServingDataError(
                f"prompt of {prompt.size} token(s) + max_new_tokens "
                f"{max_new} exceeds bigdl.lm.maxContext "
                f"{self.max_context}")
        seq = [int(t) for t in prompt]
        out_tokens: List[int] = []
        logps: List[np.ndarray] = []
        for _ in range(max_new):
            if (eos_id is not None and out_tokens and
                    out_tokens[-1] == eos_id):
                break
            t = len(seq)
            bucket = self._prefill_bucket(t)
            padded = np.ones((1, bucket), np.int32)
            padded[0, :t] = seq
            lp = self._full(self._dp, padded)
            row = np.asarray(host_pull(
                lp, what="lm sequential logits"))[t - 1]
            tok = int(np.argmax(row)) + 1
            seq.append(tok)
            out_tokens.append(tok)
            logps.append(row)
        return (out_tokens, logps) if return_logps else out_tokens


__all__ = ["LMServingEngine", "TokenStream", "PagedKVCache",
           "QuantizationGateError", "UnsupportedModelError"]
