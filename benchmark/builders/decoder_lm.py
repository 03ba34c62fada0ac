"""Builds the program's decoder-only LM from a configuration file, and
hands its weight arrays to the plain reference under the reference's own
names."""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import ops
from benchmark.builders.common import install_on_device, n_parameters


def _model(config: Dict[str, Any], flash: bool):
    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.transformer import transformer_lm
    d = int(config["hidden_size"])
    if int(config["ffn_dim"]) != 4 * d:
        raise ValueError("the program's block has an FFN of 4 x hidden_size; "
                         f"the configuration asks for {config['ffn_dim']}")
    if config.get("activation_function", "relu") != "relu":
        raise ValueError("the program's block is Linear/ReLU/Linear")
    model = transformer_lm(int(config["vocab_size"]), d_model=d,
                           n_head=int(config["num_attention_heads"]),
                           n_layers=int(config["num_hidden_layers"]),
                           max_len=int(config["max_position_embeddings"]))
    if flash and config.get("program", {}).get("flash"):
        for m in model.modules():
            if isinstance(m, nn.MultiHeadAttention):
                m.flash = True
    return model


def build(config: Dict[str, Any], key, on_tpu: bool = True):
    """``transformer_lm`` at the configuration's sizes, flash attention on
    where the configuration says so and a TPU runs it (the kernel refuses
    every other backend, so the CPU rehearsal runs the standard path)."""
    return install_on_device(_model(config, flash=on_tpu), key)


def shape_model(config: Dict[str, Any]):
    """The model ``build`` makes for a TPU, with no array made: what
    ``sizing.py`` compiles from shapes."""
    return _model(config, flash=True)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Required operations of forward and backward for one trained token."""
    return ops.decoder_train_flops_per_token(
        int(config["hidden_size"]), int(config["ffn_dim"]),
        int(config["num_hidden_layers"]), int(config["vocab_size"]),
        int(seq_len))


def attention_kernel(config: Dict[str, Any],
                     mix: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The shapes of the flash kernels' calls in a training step, as
    ``readers/flash_roofline.py`` takes them (it reports nothing where the
    trace holds no such kernel)."""
    heads = int(config["num_attention_heads"])
    return {"batch": int(mix["batch"]), "heads": heads,
            "seq_len": int(mix["seq_len"]),
            "head_dim": int(config["hidden_size"]) // heads,
            "layers": int(config["num_hidden_layers"])}


def reference_weights(model) -> Dict[str, Any]:
    """The same device arrays, named as ``references/decoder_lm.py`` names
    them.  The program's tree is [table, positions, block..., norm, head,
    log-softmax]; a block is [[norm, attention], [norm, [up, relu, down]]]."""
    p = model.params
    layers = []
    for blk in p[2:-3]:
        (ln1, att), (ln2, ffn) = blk
        layers.append({
            "ln1_w": ln1["weight"], "ln1_b": ln1["bias"],
            "wq": att["wq"], "bq": att["bq"], "wk": att["wk"],
            "bk": att["bk"], "wv": att["wv"], "bv": att["bv"],
            "wo": att["wo"], "bo": att["bo"],
            "ln2_w": ln2["weight"], "ln2_b": ln2["bias"],
            "w1": ffn[0]["weight"], "b1": ffn[0]["bias"],
            "w2": ffn[2]["weight"], "b2": ffn[2]["bias"]})
    return {"embed": p[0]["weight"], "layers": layers,
            "lnf_w": p[-3]["weight"], "lnf_b": p[-3]["bias"],
            "head_w": p[-2]["weight"], "head_b": p[-2]["bias"]}


def describe(config: Dict[str, Any], model) -> Dict[str, Any]:
    return {"parameters": n_parameters(model),
            "n_head": int(config["num_attention_heads"]),
            "head_dim": int(config["hidden_size"])
            // int(config["num_attention_heads"])}
