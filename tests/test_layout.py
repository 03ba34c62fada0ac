"""Channels-last (NHWC) compute path: layer/model parity across layouts,
the zero-interior-transpose HLO property, and inference conv+BN folding.

The contract under test (nn/layout.py): zoo models keep the Torch-style
NCHW public API but compute their conv trunk in NHWC — one boundary
transpose in, one out (or none when the exit map is 1x1 and a reshape
suffices) — and layer outputs/gradients match the NCHW path to float
rounding.
"""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import bigdl_tpu.nn as nn


RNG = np.random.RandomState(7)


def _x(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def _nhwc(x):
    return jnp.transpose(x, (0, 2, 3, 1))


def _to_nchw(y):
    return jnp.transpose(y, (0, 3, 1, 2))


def _pair(build):
    """(NCHW layer, NHWC layer) sharing identical params/state."""
    m1 = build("NCHW")
    m1._ensure_init()
    m2 = build("NHWC")
    m2._params = jax.tree_util.tree_map(lambda a: a, m1.params)
    m2._state = jax.tree_util.tree_map(lambda a: a, m1.state)
    m2._grads = jax.tree_util.tree_map(jnp.zeros_like, m1.params)
    return m1, m2


def _check_layer(build, x, train=False, tol=1e-5):
    m1, m2 = _pair(build)
    for m in (m1, m2):
        m.training() if train else m.evaluate()
    o1 = m1.forward(x)
    o2 = m2.forward(_nhwc(x))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(_to_nchw(o2)),
                               rtol=0, atol=tol)
    g = jnp.ones_like(o1)
    gi1 = m1.backward(x, g)
    gi2 = m2.backward(_nhwc(x), _nhwc(g))
    np.testing.assert_allclose(np.asarray(gi1), np.asarray(_to_nchw(gi2)),
                               rtol=0, atol=tol)
    g1 = jax.tree_util.tree_leaves(m1.grads)
    g2 = jax.tree_util.tree_leaves(m2.grads)
    for a, b in zip(g1, g2):   # kernels are HWIO in BOTH layouts
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-4)
    return m1, m2


class TestLayerParityAcrossLayouts:
    def test_conv(self):
        _check_layer(lambda f: nn.SpatialConvolution(3, 8, 3, 3, 2, 2, 1, 1,
                                                     format=f),
                     _x(2, 3, 11, 11))

    def test_conv_grouped_same_pad(self):
        _check_layer(lambda f: nn.SpatialConvolution(4, 8, 3, 3, 1, 1, -1, -1,
                                                     n_group=2, format=f),
                     _x(2, 4, 9, 9))

    def test_conv_small_taps_matmul_path(self):
        # kh*kw*cin <= 32 routes through the slice-stack matmul form,
        # which must be transpose-free in NHWC too
        _check_layer(lambda f: nn.SpatialConvolution(1, 6, 5, 5, format=f),
                     _x(2, 1, 12, 12))

    def test_dilated_conv(self):
        _check_layer(lambda f: nn.SpatialDilatedConvolution(
            3, 5, 3, 3, 1, 1, 2, 2, dilation_w=2, dilation_h=2, format=f),
            _x(2, 3, 12, 12))

    def test_full_conv_transposed(self):
        _check_layer(lambda f: nn.SpatialFullConvolution(4, 3, 3, 3, 2, 2,
                                                         1, 1, format=f),
                     _x(2, 4, 7, 7))

    def test_batchnorm_eval_and_train(self):
        x = _x(4, 6, 5, 5)
        _check_layer(lambda f: nn.SpatialBatchNormalization(6, format=f), x)
        m1, m2 = _check_layer(
            lambda f: nn.SpatialBatchNormalization(6, format=f), x,
            train=True)
        # running statistics advance identically in both layouts
        for a, b in zip(jax.tree_util.tree_leaves(m1.state),
                        jax.tree_util.tree_leaves(m2.state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-5)

    def test_max_pooling(self):
        _check_layer(lambda f: nn.SpatialMaxPooling(3, 3, 2, 2, 1, 1,
                                                    format=f).ceil(),
                     _x(2, 4, 9, 9))

    def test_avg_pooling(self):
        _check_layer(lambda f: nn.SpatialAveragePooling(
            3, 3, 2, 2, 1, 1, count_include_pad=False, format=f),
            _x(2, 4, 9, 9))

    def test_cross_map_lrn(self):
        _check_layer(lambda f: nn.SpatialCrossMapLRN(5, 1e-4, 0.75, format=f),
                     _x(2, 8, 6, 6))

    def test_within_channel_lrn(self):
        _check_layer(lambda f: nn.SpatialWithinChannelLRN(3, 1.0, 0.75,
                                                          format=f),
                     _x(2, 4, 7, 7))

    def test_channel_normalize(self):
        _check_layer(lambda f: nn.ChannelNormalize((1.0, 2.0, 3.0),
                                                   (2.0, 2.0, 2.0), format=f),
                     _x(2, 3, 5, 5))


class TestModelParityAcrossLayouts:
    @pytest.fixture(autouse=True)
    def _pin_init_stream(self):
        """Weight init draws from the thread-local RandomGenerator,
        which is NOT reset between tests — without pinning it, which
        weights these razor-thin (atol=1e-4) parity checks get depends
        on every test that ran before this file, and adding an unrelated
        test elsewhere in the suite can flip a borderline element."""
        from bigdl_tpu.utils.random_generator import RandomGenerator
        RandomGenerator.RNG().set_seed(5489)
        yield

    def _converted_clone(self, m1):
        m1._ensure_init()
        m2 = m1.clone_module()
        return nn.to_channels_last(m2)

    def test_resnet_cifar_forward_backward(self):
        from bigdl_tpu.models.resnet import resnet, DatasetType
        m1 = resnet(10, depth=20, dataset=DatasetType.CIFAR10,
                    layout="NCHW")
        m2 = self._converted_clone(m1)
        # m3 is a SAME-layout clone of m1: the m1-vs-m3 delta measures
        # this machine's run-to-run nondeterminism (XLA:CPU's threaded
        # conv reductions reassociate differently compile-to-compile,
        # and under full-suite CPU contention the jitter can exceed any
        # fixed atol — the PR 7 flake: passes solo, fails under load).
        # The cross-layout tolerance is referenced to that measured
        # noise floor, which makes the check load-immune while keeping
        # its power: a genuine layout bug corrupts m2 by O(1) without
        # moving the m1-vs-m3 floor.
        m3 = m1.clone_module()
        x = _x(2, 3, 32, 32)
        for m in (m1, m2, m3):
            m.training()
        o1, o2, o3 = m1.forward(x), m2.forward(x), m3.forward(x)
        g = jnp.ones_like(o1)
        gi1, gi2, gi3 = (m1.backward(x, g), m2.backward(x, g),
                         m3.backward(x, g))
        _, g1 = m1.get_parameters()
        _, g2 = m2.get_parameters()
        _, g3 = m3.get_parameters()
        assert g1.shape == g2.shape  # boundary modules are parameter-free

        def maxdiff(a, b):
            return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))

        for ref, other, same, base in (
                (o1, o2, o3, 1e-4),      # forward
                (gi1, gi2, gi3, 1e-3),   # input gradients
                (g1, g2, g3, 1e-3)):     # parameter gradients
            floor = maxdiff(ref, same)
            tol = max(base, 10.0 * floor)
            diff = maxdiff(ref, other)
            assert diff <= tol, (
                f"cross-layout diff {diff:.2e} exceeds tolerance "
                f"{tol:.2e} (same-layout noise floor {floor:.2e})")

    def test_resnet_shortcut_a_channel_pad_concat(self):
        # type-A shortcuts concatenate a zeroed copy along channels — the
        # Concat must follow the channel axis to the NHWC position
        from bigdl_tpu.models.resnet import resnet, DatasetType, ShortcutType
        m1 = resnet(10, depth=20, shortcut_type=ShortcutType.A,
                    dataset=DatasetType.CIFAR10, layout="NCHW")
        m2 = self._converted_clone(m1)
        x = _x(2, 3, 32, 32)
        o1 = m1.evaluate().forward(x)
        o2 = m2.evaluate().forward(x)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=0, atol=1e-5)

    @pytest.mark.slow
    def test_inception_v1_aux_heads_forward(self):
        from bigdl_tpu.models.inception import inception_v1
        m1 = inception_v1(1000, layout="NCHW")
        m2 = self._converted_clone(m1)
        x = _x(1, 3, 224, 224)
        o1 = m1.evaluate().forward(x)
        o2 = m2.evaluate().forward(x)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=0, atol=1e-4)

    @pytest.mark.slow
    def test_inception_v2_forward_backward(self):
        from bigdl_tpu.models.inception import inception_v2_no_aux_classifier
        m1 = inception_v2_no_aux_classifier(1000, layout="NCHW")
        m2 = self._converted_clone(m1)
        x = _x(1, 3, 224, 224)
        m1.training()
        m2.training()
        o1, o2 = m1.forward(x), m2.forward(x)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=0, atol=1e-4)
        g = jnp.ones_like(o1)
        gi1, gi2 = m1.backward(x, g), m2.backward(x, g)
        # input grads thread ~70 train-mode BN backward reductions whose
        # summation order differs per layout; fp32 reassociation compounds
        # to ~3e-3 on O(1e-2) gradients here
        np.testing.assert_allclose(np.asarray(gi1), np.asarray(gi2),
                                   rtol=0, atol=5e-3)

    def test_unbatched_3d_facade(self):
        # the NCHW public API accepts unbatched (C, H, W) activations; the
        # boundary transposes must handle them too
        from bigdl_tpu.models.resnet import resnet, DatasetType
        m = resnet(10, depth=20, dataset=DatasetType.CIFAR10).evaluate()
        out = m.forward(_x(3, 32, 32))
        assert out.shape == (10,)

    def test_idempotent(self):
        from bigdl_tpu.models.resnet import resnet, DatasetType
        m = resnet(10, depth=20, dataset=DatasetType.CIFAR10)
        m._ensure_init()
        x = _x(2, 3, 32, 32)
        ref = np.asarray(m.evaluate().forward(x))
        again = nn.to_channels_last(m)   # already channels-last
        assert again is m
        n_bound = len(m.find_modules(nn.NCHWToNHWC)) + \
            len(m.find_modules(nn.NHWCToNCHW))
        assert n_bound == 2   # entry + exit only, not re-inserted
        np.testing.assert_allclose(np.asarray(m.forward(x)), ref,
                                   rtol=0, atol=0)

    def test_apply_layout_rejects_unknown(self):
        with pytest.raises(ValueError, match="layout"):
            nn.apply_layout(nn.Sequential(), "NCWH")


class TestChannelsLastHLO:
    """The falsifiable artifact: the jitted channels-last ResNet-50 forward
    contains NO interior layout transposes — exactly one rank-4 transpose
    (the NCHW->NHWC entry; the exit after global pooling is a reshape) —
    and every convolution carries NHWC dimension numbers."""

    def _rank4_transposes(self, txt):
        perms = re.findall(r"transpose.*?permutation\s*=\s*dense<\[([0-9, ]+)\]",
                           txt)
        perms += re.findall(r"stablehlo\.transpose.*?dims = \[([0-9, ]+)\]",
                            txt)
        return [p for p in perms if len(p.split(",")) == 4]

    def test_resnet50_trunk_has_no_interior_transposes(self):
        from bigdl_tpu.models.resnet import resnet, DatasetType
        m = resnet(1000, depth=50, dataset=DatasetType.IMAGENET)
        m._ensure_init()

        def fwd(p, s, xb):
            out, _ = m.apply(p, xb, s, training=False)
            return out

        x = jnp.ones((2, 3, 224, 224), jnp.float32)
        txt = jax.jit(fwd).lower(m.params, m.state, x).as_text()
        r4 = self._rank4_transposes(txt)
        assert r4 == ["0, 2, 3, 1"], \
            f"expected only the boundary NCHW->NHWC transpose, got {r4}"
        conv_inputs = set(re.findall(r"dim_numbers = \[([^\]]*)\]x", txt))
        assert conv_inputs == {"b, 0, 1, f"}, conv_inputs  # all NHWC

    def test_nchw_resnet50_convs_are_channel_first(self):
        # the A/B control: the classic layout really does emit NCHW convs
        from bigdl_tpu.models.resnet import resnet, DatasetType
        m = resnet(1000, depth=50, dataset=DatasetType.IMAGENET,
                   layout="NCHW")
        m._ensure_init()

        def fwd(p, s, xb):
            out, _ = m.apply(p, xb, s, training=False)
            return out

        x = jnp.ones((1, 3, 224, 224), jnp.float32)
        txt = jax.jit(fwd).lower(m.params, m.state, x).as_text()
        assert "b, f, 0, 1" in "".join(
            re.findall(r"dim_numbers = \[([^\]]*)\]x", txt))


class TestFoldConvBN:
    def _trained_convbn_model(self):
        m = (nn.Sequential()
             .add(nn.SpatialConvolution(3, 8, 3, 3, 1, 1, 1, 1))
             .add(nn.SpatialBatchNormalization(8))
             .add(nn.ReLU())
             .add(nn.SpatialConvolution(8, 4, 3, 3, with_bias=False))
             .add(nn.SpatialBatchNormalization(4, affine=False)))
        m._ensure_init()
        m.training()
        for _ in range(3):   # make the running statistics non-trivial
            m.forward(_x(4, 3, 10, 10))
        return m.evaluate()

    def test_fold_matches_unfolded_eval(self):
        m = self._trained_convbn_model()
        x = _x(2, 3, 10, 10)
        ref = m.forward(x)
        folded = nn.fold_conv_bn(m.clone_module().evaluate())
        assert not folded.find_modules(nn.SpatialBatchNormalization)
        out = folded.forward(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=1e-5)

    def test_fold_resnet20_and_channels_last_stack(self):
        from bigdl_tpu.models.resnet import resnet, model_init, DatasetType
        m = model_init(resnet(10, depth=20, dataset=DatasetType.CIFAR10))
        m.training()
        for _ in range(2):
            m.forward(_x(4, 3, 32, 32))
        m.evaluate()
        x = _x(2, 3, 32, 32)
        ref = m.forward(x)
        folded = nn.fold_conv_bn(m.clone_module().evaluate())
        assert not folded.find_modules(nn.SpatialBatchNormalization)
        np.testing.assert_allclose(np.asarray(folded.forward(x)),
                                   np.asarray(ref), rtol=0, atol=1e-5)

    def test_predictor_fold_bn_knob(self):
        from bigdl_tpu.optim.predictor import Predictor
        from bigdl_tpu.dataset.sample import Sample
        m = self._trained_convbn_model()
        samples = [Sample(np.asarray(_x(3, 10, 10)), np.float32(1))
                   for _ in range(6)]
        plain = Predictor(m).predict(samples, batch_size=4)
        folded = Predictor(m, fold_bn=True).predict(samples, batch_size=4)
        np.testing.assert_allclose(folded, plain, rtol=0, atol=1e-5)
        # the served model was a clone: the original still has its BNs
        assert m.find_modules(nn.SpatialBatchNormalization)
