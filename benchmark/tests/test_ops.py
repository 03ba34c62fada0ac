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
