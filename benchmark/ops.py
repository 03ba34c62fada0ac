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
from typing import Dict, List, Tuple

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
