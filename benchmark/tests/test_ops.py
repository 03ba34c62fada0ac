"""``ops.py`` against counts made by hand."""

import pytest

from benchmark import ops


def test_opt_6_7b_layer():
    d, ffn, t = 4096, 16384, 2048
    # 4 d^2 for q, k, v, o and 2 d ffn for the FFN: 12 x 4096^2
    assert 4 * d * d + 2 * d * ffn == 12 * 4096 ** 2 == 201326592
    # a token attends (T+1)/2 keys on average under the causal mask, and
    # pays d multiply-adds a key twice (scores, values)
    attn = 2 * (t + 1) / 2 * d
    assert ops.decoder_layer_macs_per_token(d, ffn, t) == 201326592 + attn
    assert ops.decoder_layer_macs_per_token(d, ffn, t, causal=False) == \
        201326592 + 2 * t * d


def test_opt_cut_train_flops():
    # 2 layers and the untied head: 2 x 201.3M + 205.9M = 608.6M matmul
    # parameters, as ISSUE 23 counts them
    assert ops.decoder_matmul_params(4096, 16384, 2, 50272) == 608567296
    per_token = ops.decoder_train_flops_per_token(4096, 16384, 2, 50272,
                                                  2048)
    # 6 x (608.6M + 2 layers x 2049 x 4096 attention multiply-adds)
    assert per_token == 6 * (608567296 + 2 * 2049 * 4096)


def test_builder_count_is_the_old_count_of_the_role():
    from benchmark import cells
    cfg = cells.load_config("opt-6.7b-l2")
    got = cells.builder(cfg["builder"]).train_flops_per_token(cfg, 2048)
    assert got == ops.decoder_train_flops_per_token(4096, 16384, 2, 50272,
                                                    2048)


# one small decoder with latent attention and routed experts, by hand:
# hidden 8, 2 heads of 3 + 2 (scores) and 5 (values), key-value latent 4,
# sequences of 6, 11 rows of vocabulary, one dense layer of 16, two expert
# layers of experts of 4, 2 of 8 chosen a token, 2 held here
_SMALL = dict(d=8, heads=2, kv_lora_rank=4, qk_nope=3, qk_rope=2, v_head=5,
              seq_len=6, vocab=11, dense_layers=1, dense_ffn=16,
              expert_layers=2, expert_ffn=4, experts_per_tok=2,
              experts_held=2, router_width=8)


def test_latent_moe_with_low_rank_indexer_and_selection():
    # a selection of 4 keys binds at 6: positions see 1, 2, 3, 4, 4, 4
    assert ops.causal_keys_per_token(6, 4) == 18 / 6 == 3
    # queries through a low-rank of 6: 8 x 6 + 6 x 2 x 5 = 108; keys and
    # values 8 x (4 + 2) + 4 x 2 x (3 + 5) = 112; core 3 keys x 2 heads x
    # (5 + 5) = 60; output 2 x 5 x 8 = 80
    attn = 108 + 112 + 60 + 80
    assert ops.latent_attention_macs_per_token(8, 2, 4, 3, 2, 5, 3,
                                               q_lora_rank=6) == attn
    # an expert 3 x 8 x 4 = 96: router 8 x 8, one shared, 2 x 2/8 routed
    moe = 64 + 96 + 0.5 * 96
    macs = 3 * attn + 3 * 8 * 16 + 2 * moe + 8 * 11
    assert macs == 1968
    # an indexer of 3 heads of 4, in two layers, forward only: queries
    # 6 x 12, key 8 x 4, weights 8 x 3, and 12 a key over (6 + 1) / 2 keys
    indexer = 72 + 32 + 24 + 3.5 * 12
    assert ops.indexer_macs_per_token(8, 3, 4, 6, 6) == indexer == 170
    got = ops.latent_moe_train_flops_per_token(
        **_SMALL, shared_experts=1, q_lora_rank=6, index_topk=4,
        indexer_layers=2, index_heads=3, index_head_dim=4)
    assert got == 6 * 1968 + 2 * 2 * 170 == 12488


def test_latent_moe_with_none_of_the_three_and_two_shared_experts():
    assert ops.causal_keys_per_token(6) == 3.5
    # queries straight from the hidden state: 8 x 2 x 5 = 80; core 3.5
    # keys x 2 x 10 = 70
    attn = 80 + 112 + 70 + 80
    moe = 64 + 2 * 96 + 0.5 * 96
    macs = 3 * attn + 3 * 8 * 16 + 2 * moe + 8 * 11
    assert macs == 2106
    got = ops.latent_moe_train_flops_per_token(**_SMALL, shared_experts=2)
    assert got == 6 * 2106 == 12636
    # a selection no shorter than the sequence does not bind: nothing of
    # the indexer is required work, and every causal key is attended
    assert ops.latent_moe_train_flops_per_token(
        **_SMALL, shared_experts=2, index_topk=6, indexer_layers=2,
        index_heads=3, index_head_dim=4) == got


def test_second_family_builder_states_its_own_parts():
    """``glm-5.2-l5-e16`` through its builder: the count the function
    gives when handed the file's sizes by name, and no flash shapes."""
    from benchmark import cells
    cfg = cells.load_config("glm-5.2-l5-e16")
    b = cells.builder(cfg["builder"])
    assert b.attention_kernel(cfg, {"batch": 1, "seq_len": 8192}) is None
    want = ops.latent_moe_train_flops_per_token(
        6144, 64, 512, 192, 64, 256, 8192, 19360, dense_layers=1,
        dense_ffn=12288, expert_layers=4, expert_ffn=2048,
        experts_per_tok=8, experts_held=16, router_width=256,
        shared_experts=1, q_lora_rank=2048, index_topk=2048,
        indexer_layers=2, index_heads=32, index_head_dim=128)
    assert b.train_flops_per_token(cfg, 8192) == want
    # products with weights alone, a token: attention 165.0M a layer
    # (PERF.md 4), dense FFN 226.5M, an expert layer's router, shared
    # expert and 8 x 16/256 routed experts 58.2M, head 118.9M
    attn_w = ops.latent_attention_macs_per_token(6144, 64, 512, 192, 64,
                                                 256, 0, 2048)
    assert attn_w == 165_019_648
    assert 3 * 6144 * 2048 * 1.5 + 6144 * 256 == 58_195_968


def test_flash_cost():
    c = ops.flash_attention_cost(4, 32, 2048, 128, causal=True)
    product = 4 * 32 * 2048 * 2048 * 128 * (2049 / 4096)
    assert c["forward"]["flops"] == pytest.approx(4 * product)
    assert c["backward"]["flops"] == pytest.approx(8 * product)
    # q, k, v, o in bf16: 4 x 4 x 32 x 2048 x 128 x 2 bytes
    assert c["forward"]["bytes"] == 4 * 4 * 32 * 2048 * 128 * 2
    peak = ops.peaks("TPU v5 lite")
    t, bound = ops.roofline_seconds(c["forward"]["flops"],
                                    c["forward"]["bytes"], peak)
    assert bound == "compute" and t == pytest.approx(
        c["forward"]["flops"] / 197e12)


def test_resnet50_forward_macs():
    # the figure every model zoo quotes for ResNet-50 v1.5 at 224 x 224
    assert ops.resnet50_forward_macs() == pytest.approx(4.09e9, rel=2e-3)
    layers = {l["name"]: l["macs"] for l in ops.resnet50_layers()}
    assert layers["conv1"] == 7 * 7 * 3 * 64 * 112 * 112
    assert layers["fc"] == 2048 * 1000
    assert layers["layer2.0.conv2"] == 9 * 128 * 128 * 28 * 28   # stride here
    assert layers["layer2.0.downsample"] == 256 * 512 * 28 * 28
    assert len(layers) == 1 + 16 * 3 + 4 + 1
    # three products a layer, two for the first convolution
    assert ops.resnet50_train_flops_per_image() == pytest.approx(
        2 * (3 * ops.resnet50_forward_macs() - layers["conv1"]))


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        ops.peaks("TPU v9 imaginary")
