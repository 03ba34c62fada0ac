"""Builds the program's decoder-only LM from a configuration file, and
hands its weight arrays to the plain reference under the reference's own
names."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.builders.common import install_on_device, n_parameters


def build(config: Dict[str, Any], key, on_tpu: bool = True):
    """``transformer_lm`` at the configuration's sizes, flash attention on
    where the configuration says so and a TPU runs it (the kernel refuses
    every other backend, so the CPU rehearsal runs the standard path)."""
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
    if config.get("program", {}).get("flash") and on_tpu:
        for m in model.modules():
            if isinstance(m, nn.MultiHeadAttention):
                m.flash = True
    return install_on_device(model, key)


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
