"""Builds the program's ``glm_moe_dsa`` decoder from a configuration file
through the program's public holding path (``param_dtype`` at the
builder, ``reset(key)``: two bytes a parameter and nothing else), holds
what it built to the configuration's precision
(``program.served_logprob_gate``: ``benchmark/served_logprobs.py``), and
hands its weight arrays to the plain reference under the reference's own
names."""

from __future__ import annotations

from typing import Any, Dict

from benchmark import ops
from benchmark.builders.common import n_parameters


def shape_model(config: Dict[str, Any]):
    """The program's model at the configuration's sizes with no array
    made (``reset`` makes them): what ``build`` resets and what
    ``sizing.py`` compiles from shapes."""
    import jax.numpy as jnp

    from bigdl_tpu.models.transformer.glm_moe_dsa import glm_moe_dsa_lm
    prog = config["program"]
    if len(config["indexer_types"]) != int(config["num_hidden_layers"]):
        raise ValueError("indexer_types names num_hidden_layers layers")
    if int(config["n_shared_experts"]) != 1 or int(config["n_group"]) != 1:
        raise ValueError("the program's expert layer has one shared expert "
                         "and no group limit")
    if int(prog["experts_held"][1]) != int(config["n_routed_experts"]):
        raise ValueError("n_routed_experts is the number of experts held")
    return glm_moe_dsa_lm(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        index_n_heads=config["index_n_heads"],
        index_head_dim=config["index_head_dim"],
        index_topk=config["index_topk"],
        indexer_types=config["indexer_types"],
        mlp_layer_types=config["mlp_layer_types"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=prog["router_width"],
        num_experts_per_tok=config["num_experts_per_tok"],
        routed_scaling_factor=config["routed_scaling_factor"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        experts_held=tuple(prog["experts_held"]),
        param_dtype=jnp.dtype(prog["param_dtype"]),
        init_depth=prog.get("init_depth"))


def build(config: Dict[str, Any], key, on_tpu: bool = True):
    model = shape_model(config)
    model.reset(key)
    if "served_logprob_gate" in config["program"]:
        # the one limit of the cell that a narrower precision fails; the
        # role's own check sees only the tokens generate() chose
        from benchmark import served_logprobs
        served_logprobs.gate(model, config, key)
    return model


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Required operations of forward and backward for one trained token
    (``ops.latent_moe_train_flops_per_token``; the routed experts' term is
    the expectation for the experts held here under an even router)."""
    kinds = list(config["mlp_layer_types"])
    return ops.latent_moe_train_flops_per_token(
        int(config["hidden_size"]), int(config["num_attention_heads"]),
        int(config["kv_lora_rank"]), int(config["qk_nope_head_dim"]),
        int(config["qk_rope_head_dim"]), int(config["v_head_dim"]),
        int(seq_len), int(config["vocab_size"]),
        dense_layers=kinds.count("dense"),
        dense_ffn=int(config["intermediate_size"]),
        expert_layers=kinds.count("sparse"),
        expert_ffn=int(config["moe_intermediate_size"]),
        experts_per_tok=int(config["num_experts_per_tok"]),
        experts_held=int(config["program"]["experts_held"][1]),
        router_width=int(config["program"]["router_width"]),
        shared_experts=int(config["n_shared_experts"]),
        q_lora_rank=int(config["q_lora_rank"]),
        index_topk=int(config["index_topk"]),
        indexer_layers=list(config["indexer_types"]).count("full"),
        index_heads=int(config["index_n_heads"]),
        index_head_dim=int(config["index_head_dim"]))


def attention_kernel(config: Dict[str, Any], mix: Dict[str, Any]) -> None:
    """Latent attention runs no flash kernel (heads of ``qk_nope +
    qk_rope`` for the scores and ``v_head_dim`` for the values, which the
    program's kernel does not take): the cell is not listed under
    ``flash_roofline.train`` (``where_builder_gives`` in its file)."""
    return None


def reference_weights(model) -> Dict[str, Any]:
    """The same device arrays, named as ``references/glm_moe_dsa.py``
    names them, with the numbers no shape gives as a static leaf."""
    from benchmark.references.glm_moe_dsa import Hyper
    s, a, p = model.spec, model.spec.attention, model.params
    layers = []
    for lyr in p["layers"]:
        out = dict(lyr["attn"], ln1=lyr["ln1"], ln2=lyr["ln2"])
        if "indexer" in lyr:
            out.update({"index_" + k: v for k, v in lyr["indexer"].items()})
        if "moe" in lyr:
            m = lyr["moe"]
            out.update(gate=m["gate"], bias=m["bias"], w1=m["w1"],
                       w3=m["w3"], w2=m["w2"],
                       **{"shared_" + k: v for k, v in m["shared"].items()})
        else:
            out.update(lyr["ffn"])
        layers.append(out)
    hyper = Hyper(
        qk_nope_head_dim=a.qk_nope_head_dim,
        qk_rope_head_dim=a.qk_rope_head_dim, v_head_dim=a.v_head_dim,
        index_n_heads=a.index_n_heads, index_topk=a.index_topk,
        rope_theta=a.rope_theta, eps=a.eps, top_k=s.num_experts_per_tok,
        scale=s.routed_scaling_factor, held=tuple(s.experts_held))
    return {"hyper": hyper, "embed": p["embed"], "layers": layers,
            "ln_f": p["ln_f"], "head": p["head"]}


def describe(config: Dict[str, Any], model) -> Dict[str, Any]:
    return {"parameters": n_parameters(model),
            "param_dtype": str(model.param_dtype),
            "experts_held": list(model.spec.experts_held)}
