"""Inference-time graph rewrites: conv + BatchNorm folding.

At inference BatchNorm is an affine per-channel map built from FROZEN
running statistics, so it folds exactly into the preceding convolution's
weights::

    s = gamma * rsqrt(running_var + eps)
    w' = w * s          (per output channel, HWIO trailing axis)
    b' = b * s + (beta - running_mean * s)

One conv replaces a conv + BN pair — fewer kernels, less HBM traffic, and
(together with the channels-last path, ``nn/layout.py``) the shape the
Predictor/evaluator hot loop should run.  Reference BigDL has no equivalent
(its Predictor executes the module graph as built); this mirrors what every
serving stack (TensorRT, OpenVINO, tf.graph_transforms) does before deploy.

Training semantics are NOT preserved — batch statistics differ from running
statistics — so fold a clone for serving (``Predictor(model, fold_bn=True)``
does exactly that) and keep the original for training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import Container, Module, Sequential
from bigdl_tpu.nn.conv import SpatialConvolution
from bigdl_tpu.nn.normalization import SpatialBatchNormalization
from bigdl_tpu.nn.structural import Identity

__all__ = ["fold_conv_bn"]


def _bn_scale_shift(bn: SpatialBatchNormalization, params, state):
    # identical arithmetic to BatchNormalization.apply's eval path (same
    # rsqrt), so folded outputs match to float-associativity error
    inv = jax.lax.rsqrt(state["running_var"] + bn.eps)
    if bn.affine:
        scale = params["weight"] * inv
        shift = params["bias"] - state["running_mean"] * scale
    else:
        scale = inv
        shift = -state["running_mean"] * scale
    return scale, shift


def _foldable(conv: Module, bn: Module) -> bool:
    return (isinstance(conv, SpatialConvolution) and
            isinstance(bn, SpatialBatchNormalization) and
            bn.n_output == conv.n_output_plane)


def fold_conv_bn(model: Module) -> Module:
    """Fold every ``SpatialConvolution -> SpatialBatchNormalization``
    adjacency (within any ``Sequential``) into the convolution, replacing
    the BN with ``Identity``.  In place; returns ``model``.

    The rewrite uses the BN's RUNNING statistics, i.e. it freezes the
    module at its inference behaviour — only use the folded model for
    eval/serving.  Outputs match the unfolded eval forward to float
    rounding (<= 1e-5, asserted in tests/test_layout.py).
    """
    model._ensure_init()
    if isinstance(model, Container):
        # share one params/state tree across the nesting before editing in
        # place (clone_module leaves per-container copies behind)
        model._adopt()
    _fold_in(model)
    if isinstance(model, Container):
        model._adopt()
    model.clear_jit_cache()
    return model


def _fold_in(container: Module) -> None:
    if not isinstance(container, Container):
        return
    if isinstance(container, Sequential):
        for i in range(len(container.children) - 1):
            conv, bn = container.children[i], container.children[i + 1]
            if not _foldable(conv, bn):
                continue
            cp = container._params[i]
            bp = container._params[i + 1]
            bs = container._state[i + 1]
            scale, shift = _bn_scale_shift(bn, bp, bs)
            scale = scale.astype(cp["weight"].dtype)
            shift = shift.astype(cp["weight"].dtype)
            cp["weight"] = cp["weight"] * scale    # HWIO: O is trailing
            if conv.with_bias:
                cp["bias"] = cp["bias"] * scale + shift
            else:
                conv.with_bias = True
                cp["bias"] = shift
                if container._grads_tree is not None:
                    container._grads_tree[i]["bias"] = jnp.zeros_like(shift)
            ident = Identity()
            ident._ensure_init()
            container.children[i + 1] = ident
            container._params[i + 1] = ident._params
            container._state[i + 1] = ident._state
            if container._grads_tree is not None:
                container._grads_tree[i + 1] = ident._grads
    for c in container.children:
        _fold_in(c)
