"""Operations and bytes from shapes: the benchmark's own counts.

Everything here is arithmetic on sizes.  Nothing is read from the program
or from XLA's cost analysis (which counts recomputation and elementwise
work and sees a Mosaic kernel as opaque).  One multiply-add is two
operations.  Only matrix-multiply and convolution work counts; lookups,
normalisations, softmax and elementwise passes are left out, so a share
of peak computed from these counts is a floor on what the chip executed.
Recomputation never counts: the backward pass is the two products each
forward product requires, whatever the program chooses to recompute.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# peaks
# ---------------------------------------------------------------------------

def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"benchmark/peaks.json has no entry for device kind "
            f"{device_kind!r} (it knows {sorted(table)}): add its "
            "published peaks with their source before measuring on it")
    return table[device_kind]


# ---------------------------------------------------------------------------
# decoder-only transformer (OPT-style block)
# ---------------------------------------------------------------------------

def decoder_layer_macs_per_token(d: int, ffn: int, seq_len: int,
                                 causal: bool = True) -> float:
    """Forward multiply-adds one token costs in one layer: q, k, v and
    output projections (4 d^2), the two FFN products (2 d ffn), and
    attention's two products over the keys the token attends to.  With a
    causal mask a token at position p attends p+1 keys, (T+1)/2 on
    average; the masked half is not required work and is not counted."""
    proj = 4 * d * d + 2 * d * ffn
    keys = (seq_len + 1) / 2 if causal else seq_len
    attn = 2 * keys * d          # QK^T and PV, all heads together
    return proj + attn


def decoder_head_macs_per_token(d: int, vocab: int) -> float:
    """The output projection onto the vocabulary.  The embedding is a
    lookup and counts nothing."""
    return d * vocab


def decoder_train_flops_per_token(d: int, ffn: int, n_layers: int,
                                  vocab: int, seq_len: int) -> float:
    """Operations the forward and backward passes require per trained
    token: three products per forward product (forward, input gradient,
    weight gradient), two operations a multiply-add."""
    macs = (n_layers * decoder_layer_macs_per_token(d, ffn, seq_len)
            + decoder_head_macs_per_token(d, vocab))
    return 3 * 2 * macs


def decoder_matmul_params(d: int, ffn: int, n_layers: int,
                          vocab: int) -> int:
    """Parameters that sit in a matrix product (no table, no bias, no
    norm): what '6 N' arithmetic should use for N."""
    return n_layers * (4 * d * d + 2 * d * ffn) + d * vocab


# ---------------------------------------------------------------------------
# decoder with latent attention and routed experts (the DeepSeek-V3 block)
# ---------------------------------------------------------------------------

def causal_keys_per_token(seq_len: int, topk: Optional[int] = None) -> float:
    """Keys a token attends, averaged over the positions of a sequence:
    position p (from 0) sees p + 1 keys under the causal mask, (T+1)/2 on
    average; under a selection of ``topk`` keys it sees min(p + 1, topk)."""
    if topk is None or seq_len <= topk:
        return (seq_len + 1) / 2
    return (topk * (topk + 1) / 2 + (seq_len - topk) * topk) / seq_len


def latent_attention_macs_per_token(d: int, heads: int, kv_lora_rank: int,
                                    qk_nope: int, qk_rope: int, v_head: int,
                                    keys: float,
                                    q_lora_rank: Optional[int] = None
                                    ) -> float:
    """Forward multiply-adds one token costs in one layer of latent
    attention, keys and values expanded from the latent once (the cheaper
    form at training lengths; the absorbed form of a decode step is not
    counted here).  Queries: ``d x heads (nope + rope)``, or through a
    low-rank of ``q_lora_rank``.  Keys and values: down to ``kv_lora_rank
    + rope`` (the rotary key is shared by the heads), up to ``heads (nope
    + v)``.  Core: ``nope + rope`` a key and head for the scores, ``v`` for
    the values, over ``keys`` keys.  Output: ``heads v x d``."""
    qk = qk_nope + qk_rope
    if q_lora_rank is None:
        q = d * heads * qk
    else:
        q = d * q_lora_rank + q_lora_rank * heads * qk
    kv = d * (kv_lora_rank + qk_rope) + kv_lora_rank * heads * (qk_nope
                                                                + v_head)
    core = keys * heads * (qk + v_head)
    return q + kv + core + heads * v_head * d


def indexer_macs_per_token(d: int, index_heads: int, index_head_dim: int,
                           query_width: int, seq_len: int) -> float:
    """Forward multiply-adds of one layer's indexer for one token: its
    queries from the query latent (``query_width``; the hidden size in a
    family without a query low-rank), one key and one weight a head from
    the hidden state, and a score a head for every causal key."""
    proj = (query_width * index_heads * index_head_dim
            + d * index_head_dim + d * index_heads)
    return proj + (seq_len + 1) / 2 * index_heads * index_head_dim


def latent_moe_train_flops_per_token(
        d: int, heads: int, kv_lora_rank: int, qk_nope: int, qk_rope: int,
        v_head: int, seq_len: int, vocab: int, dense_layers: int,
        dense_ffn: int, expert_layers: int, expert_ffn: int,
        experts_per_tok: int, experts_held: int, router_width: int,
        shared_experts: int = 1, q_lora_rank: Optional[int] = None,
        index_topk: Optional[int] = None, indexer_layers: int = 0,
        index_heads: int = 0, index_head_dim: int = 0) -> float:
    """Operations the forward and backward passes require per trained
    token of a decoder whose every layer has latent attention and whose
    FFN is a SwiGLU (three products) in ``dense_layers`` layers and
    routed experts in ``expert_layers``: three products per forward
    product, two operations a multiply-add, no recomputation, as
    :func:`decoder_train_flops_per_token`.  What a family lacks it leaves
    at the default: ``q_lora_rank`` None (queries straight from the hidden
    state), ``index_topk`` None (every causal key attended),
    ``indexer_layers`` 0.

    An expert layer costs the router (``d x router_width``),
    ``shared_experts`` experts of ``expert_ffn`` for every token, and the
    routed experts held here: ``experts_per_tok x experts_held /
    router_width`` a token.  **That term is an expectation** under a
    router that spreads its choices evenly over ``router_width`` experts,
    of which this chip holds ``experts_held``; a cell whose router is far
    from even should read its held share from the program's counters
    (``experts_held_share``) and say so beside its ``train_mfu``.

    ``vocab`` is the rows of the head held here; the table is a lookup.
    A selection (``index_topk``) binds only where ``seq_len`` exceeds it;
    then attention's core runs over the selected keys and each of
    ``indexer_layers`` layers pays its indexer **forward only**: a hard
    top-k passes no gradient to the scores that chose it, so the language
    model's loss requires no backward product of the indexer (a family
    that trains its indexer by a loss of its own counts that loss's
    products in a file of its own).  Where the selection does not bind,
    nothing of the indexer is required work."""
    binds = index_topk is not None and seq_len > index_topk
    keys = causal_keys_per_token(seq_len, index_topk if binds else None)
    attn = latent_attention_macs_per_token(
        d, heads, kv_lora_rank, qk_nope, qk_rope, v_head, keys, q_lora_rank)
    expert = 3 * d * expert_ffn
    moe = (d * router_width + shared_experts * expert
           + experts_per_tok * experts_held / router_width * expert)
    macs = ((dense_layers + expert_layers) * attn
            + dense_layers * 3 * d * dense_ffn + expert_layers * moe
            + decoder_head_macs_per_token(d, vocab))
    forward_only = 0.0
    if binds:
        forward_only = indexer_layers * indexer_macs_per_token(
            d, index_heads, index_head_dim,
            d if q_lora_rank is None else q_lora_rank, seq_len)
    return 3 * 2 * macs + 2 * forward_only


# ---------------------------------------------------------------------------
# flash attention (one call of the kernel family)
# ---------------------------------------------------------------------------

def flash_attention_cost(batch: int, heads: int, seq_len: int,
                         head_dim: int, causal: bool,
                         itemsize: int = 2) -> Dict[str, Dict[str, float]]:
    """Required operations and bytes of one attention call, forward and
    backward, from (B, H, T, Dh, causal).

    Forward: S = QK^T and O = PV.  Backward: dV = P^T dO, dP = dO V^T,
    dQ = dS K, dK = dS^T Q - four products; the recomputation of S that
    a flash backward performs is not required work.  Each product is
    B H T^2 Dh multiply-adds, halved under a causal mask (the diagonal
    blocks make the true factor (T+1)/2T).  Bytes are the least traffic:
    forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO
    and writes dQ, dK, dV."""
    frac = (seq_len + 1) / (2 * seq_len) if causal else 1.0
    product = batch * heads * seq_len * seq_len * head_dim * frac
    tensor = batch * heads * seq_len * head_dim * itemsize
    return {"forward": {"flops": 2 * 2 * product, "bytes": 4 * tensor},
            "backward": {"flops": 2 * 4 * product, "bytes": 8 * tensor}}


def roofline_seconds(flops: float, nbytes: float,
                     peak: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take and which peak bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# ---------------------------------------------------------------------------
# ResNet-50 (He et al. 2015, Table 1; stride on the 3x3: "v1.5")
# ---------------------------------------------------------------------------

def _conv_out(size: int, k: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - k) // stride + 1


def resnet50_layers(image: int = 224, classes: int = 1000
                    ) -> List[Dict[str, float]]:
    """Every convolution and the classifier of ResNet-50 with its forward
    multiply-adds per image: (name, macs, first).  ``first`` marks the
    layer whose input is the image, which needs no input gradient."""
    out: List[Dict[str, float]] = []
    size = _conv_out(image, 7, 2, 3)
    out.append({"name": "conv1", "macs": 7 * 7 * 3 * 64 * size * size,
                "first": True})
    size = _conv_out(size, 3, 2, 1)            # max pool
    ch = 64
    for stage, (width, count) in enumerate(zip((64, 128, 256, 512),
                                               (3, 4, 6, 3))):
        for block in range(count):
            stride = 2 if (block == 0 and stage > 0) else 1
            o = _conv_out(size, 3, stride, 1)
            name = f"layer{stage + 1}.{block}"
            out.append({"name": name + ".conv1",
                        "macs": ch * width * size * size, "first": False})
            out.append({"name": name + ".conv2",
                        "macs": 9 * width * width * o * o, "first": False})
            out.append({"name": name + ".conv3",
                        "macs": width * 4 * width * o * o, "first": False})
            if block == 0:
                out.append({"name": name + ".downsample",
                            "macs": ch * 4 * width * o * o, "first": False})
            ch, size = 4 * width, o
    out.append({"name": "fc", "macs": ch * classes, "first": False})
    return out


def resnet50_forward_macs(image: int = 224, classes: int = 1000) -> float:
    return float(sum(l["macs"] for l in resnet50_layers(image, classes)))


def resnet50_train_flops_per_image(image: int = 224,
                                   classes: int = 1000) -> float:
    """Forward plus the two backward products of every layer, except that
    the first convolution needs no gradient for the image."""
    macs = 0.0
    for l in resnet50_layers(image, classes):
        macs += l["macs"] * (2 if l["first"] else 3)
    return 2 * macs
