"""Structural / tensor-manipulation layers.

Reference: Reshape.scala, View.scala, InferReshape.scala, Squeeze.scala,
Unsqueeze.scala, Transpose.scala, Contiguous.scala, Identity.scala, Echo.scala,
Narrow.scala, Select.scala, Index.scala, MaskedSelect.scala, Max.scala,
Min.scala, Mean.scala, Sum.scala, Replicate.scala, Padding.scala,
SpatialZeroPadding.scala, GradientReversal.scala, Scale.scala, Bottle.scala,
MM.scala, MV.scala, DotProduct.scala, Pack.scala, Reverse.scala.

Dimension arguments are 1-based (Torch convention), as in the reference.
Many layers take ``n_input_dims``: when the actual input has one more dim,
it is treated as a batch dim and the op shifts right by one.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import (Container, Module, _child_rng,
                                 child_scope)


def _axis(dim_1based: int, ndim: int, n_input_dims: int = -1) -> int:
    """Convert a 1-based (possibly batch-relative) dim to a 0-based axis."""
    d = dim_1based
    if d < 0:
        return ndim + d
    ax = d - 1
    if n_input_dims > 0 and ndim == n_input_dims + 1:
        ax += 1
    return ax


class Identity(Module):
    """Pass input through unchanged (reference ``nn/Identity.scala``)."""

    layout_role = "agnostic"

    def apply(self, params, input, state, training=False, rng=None):
        return input, state


class Echo(Module):
    """Identity that prints its input shape (debug aid, reference ``nn/Echo.scala``)."""

    layout_role = "agnostic"

    def apply(self, params, input, state, training=False, rng=None):
        jax.debug.print("Echo {name}: shape {shape}", name=self.name,
                        shape=jnp.asarray(input.shape))
        return input, state


class Contiguous(Module):
    """No-op on XLA arrays (kept for API parity, reference ``nn/Contiguous.scala``)."""

    layout_role = "agnostic"

    def apply(self, params, input, state, training=False, rng=None):
        return input, state


class Reshape(Module):
    """Reshape non-batch dims to ``size`` (reference ``nn/Reshape.scala``).

    batch_mode None (default): auto — treat first dim as batch when the
    element count of the remaining dims matches prod(size).
    """

    def __init__(self, size: Sequence[int], batch_mode: Optional[bool] = None,
                 name=None):
        super().__init__(name)
        self.size = tuple(int(s) for s in size)
        self.batch_mode = batch_mode

    def apply(self, params, input, state, training=False, rng=None):
        n = int(np.prod(self.size))
        total = int(np.prod(input.shape))
        if self.batch_mode is True or (
                self.batch_mode is None and total != n and
                input.shape and total == n * input.shape[0]):
            return jnp.reshape(input, (input.shape[0],) + self.size), state
        return jnp.reshape(input, self.size), state


class View(Module):
    """Reshape with -1 inference (reference ``nn/View.scala``)."""

    def __init__(self, *sizes, name=None):
        super().__init__(name)
        if len(sizes) == 1 and isinstance(sizes[0], (tuple, list)):
            sizes = tuple(sizes[0])
        self.sizes = tuple(int(s) for s in sizes)
        self.num_input_dims = 0

    def set_num_input_dims(self, n: int):
        self.num_input_dims = n
        return self

    def apply(self, params, input, state, training=False, rng=None):
        sizes = self.sizes
        if self.num_input_dims > 0 and input.ndim > self.num_input_dims:
            batch = input.shape[:input.ndim - self.num_input_dims]
            return jnp.reshape(input, batch + sizes), state
        n = int(np.prod([s for s in sizes if s != -1]))
        if (-1 not in sizes and input.ndim > len(sizes)
                and int(np.prod(input.shape[1:])) == n):
            return jnp.reshape(input, (input.shape[0],) + sizes), state
        return jnp.reshape(input, sizes), state


class InferReshape(Module):
    """Reshape where 0 copies the input dim and -1 is inferred
    (reference ``nn/InferReshape.scala``)."""

    def __init__(self, size: Sequence[int], batch_mode: bool = False, name=None):
        super().__init__(name)
        self.size = tuple(int(s) for s in size)
        self.batch_mode = batch_mode

    def apply(self, params, input, state, training=False, rng=None):
        in_shape = input.shape[1:] if self.batch_mode else input.shape
        out = []
        for i, s in enumerate(self.size):
            out.append(in_shape[i] if s == 0 else s)
        if self.batch_mode:
            out = [input.shape[0]] + out
        return jnp.reshape(input, tuple(out)), state


class Squeeze(Module):
    """Drop size-1 dims (1-based ``dim``, reference ``nn/Squeeze.scala``)."""

    def __init__(self, dim: Optional[int] = None, num_input_dims: int = -1,
                 name=None):
        super().__init__(name)
        self.dim = dim
        self.num_input_dims = num_input_dims

    def apply(self, params, input, state, training=False, rng=None):
        if self.dim is None:
            return jnp.squeeze(input), state
        ax = _axis(self.dim, input.ndim, self.num_input_dims)
        if input.shape[ax] != 1:
            return input, state
        return jnp.squeeze(input, axis=ax), state


class Unsqueeze(Module):
    """Insert a size-1 dim at 1-based ``pos`` (reference ``nn/Unsqueeze.scala``)."""

    def __init__(self, pos: int, num_input_dims: int = -1, name=None):
        super().__init__(name)
        self.pos = pos
        self.num_input_dims = num_input_dims

    def apply(self, params, input, state, training=False, rng=None):
        ax = self.pos - 1
        if self.num_input_dims > 0 and input.ndim == self.num_input_dims + 1:
            ax += 1
        return jnp.expand_dims(input, ax), state


class Transpose(Module):
    """Swap listed (1-based) dim pairs in order (reference ``nn/Transpose.scala``)."""

    def __init__(self, permutations: Sequence[Sequence[int]], name=None):
        super().__init__(name)
        self.permutations = [tuple(p) for p in permutations]

    def apply(self, params, input, state, training=False, rng=None):
        x = input
        for d1, d2 in self.permutations:
            x = jnp.swapaxes(x, d1 - 1, d2 - 1)
        return x, state


class Narrow(Module):
    """Slice length elements from 1-based offset along dim
    (reference ``nn/Narrow.scala``)."""

    def __init__(self, dimension: int, offset: int, length: int = 1, name=None):
        super().__init__(name)
        self.dimension = dimension
        self.offset = offset
        self.length = length

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dimension, input.ndim)
        length = self.length
        if length < 0:
            length = input.shape[ax] - self.offset + 1 + length + 1
        start = self.offset - 1
        idx = [slice(None)] * input.ndim
        idx[ax] = slice(start, start + length)
        return input[tuple(idx)], state


class Select(Module):
    """Select 1-based index along 1-based dim, dropping the dim
    (reference ``nn/Select.scala``)."""

    def __init__(self, dimension: int, index: int, name=None):
        super().__init__(name)
        self.dimension = dimension
        self.index = index

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dimension, input.ndim)
        i = self.index - 1 if self.index > 0 else input.shape[ax] + self.index
        return jnp.take(input, i, axis=ax), state


class Index(Module):
    """Table input [tensor, indices]: gather along dim (1-based indices)
    (reference ``nn/Index.scala``)."""

    def __init__(self, dimension: int, name=None):
        super().__init__(name)
        self.dimension = dimension

    def apply(self, params, input, state, training=False, rng=None):
        x, idx = input[0], input[1]
        ax = self.dimension - 1
        return jnp.take(x, idx.astype(jnp.int32) - 1, axis=ax), state


class MaskedSelect(Module):
    """Table input [tensor, mask] -> masked elements.

    XLA needs static shapes, so unlike the reference
    (``nn/MaskedSelect.scala``) the output keeps the input length with
    non-selected positions zeroed, packed to the front.
    """

    def apply(self, params, input, state, training=False, rng=None):
        x, mask = input[0], input[1]
        flat = jnp.ravel(x)
        m = jnp.ravel(mask).astype(bool)
        order = jnp.argsort(~m, stable=True)
        packed = jnp.where(m[order], flat[order], 0.0)
        return packed, state


class Max(Module):
    """Max over a 1-based dim (reference ``nn/Max.scala``)."""

    def __init__(self, dim: int = 1, num_input_dims: int = -1, name=None):
        super().__init__(name)
        self.dim = dim
        self.num_input_dims = num_input_dims

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dim, input.ndim, self.num_input_dims)
        return jnp.max(input, axis=ax), state


class Min(Module):
    """Min over a 1-based dim (reference ``nn/Min.scala``)."""

    def __init__(self, dim: int = 1, num_input_dims: int = -1, name=None):
        super().__init__(name)
        self.dim = dim
        self.num_input_dims = num_input_dims

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dim, input.ndim, self.num_input_dims)
        return jnp.min(input, axis=ax), state


class Mean(Module):
    """Mean over a 1-based dim (reference ``nn/Mean.scala``)."""

    def __init__(self, dimension: int = 1, n_input_dims: int = -1,
                 squeeze: bool = True, name=None):
        super().__init__(name)
        self.dimension = dimension
        self.n_input_dims = n_input_dims
        self.squeeze = squeeze

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dimension, input.ndim, self.n_input_dims)
        return jnp.mean(input, axis=ax, keepdims=not self.squeeze), state


class Sum(Module):
    """Sum over a 1-based dim (reference ``nn/Sum.scala``)."""

    def __init__(self, dimension: int = 1, n_input_dims: int = -1,
                 size_average: bool = False, squeeze: bool = True, name=None):
        super().__init__(name)
        self.dimension = dimension
        self.n_input_dims = n_input_dims
        self.size_average = size_average
        self.squeeze = squeeze

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dimension, input.ndim, self.n_input_dims)
        if self.size_average:
            out = jnp.mean(input, axis=ax, keepdims=not self.squeeze)
        else:
            out = jnp.sum(input, axis=ax, keepdims=not self.squeeze)
        return out, state


class Replicate(Module):
    """Insert a new dim of size n_features at 1-based dim
    (reference ``nn/Replicate.scala``)."""

    def __init__(self, n_features: int, dim: int = 1, n_dim: int = -1, name=None):
        super().__init__(name)
        self.n_features = n_features
        self.dim = dim
        self.n_dim = n_dim

    def apply(self, params, input, state, training=False, rng=None):
        ax = self.dim - 1
        if self.n_dim > 0 and input.ndim == self.n_dim + 1:
            ax += 1
        x = jnp.expand_dims(input, ax)
        reps = [1] * x.ndim
        reps[ax] = self.n_features
        return jnp.tile(x, reps), state


class Padding(Module):
    """Pad ``pad`` entries (negative -> before, positive -> after) along dim
    with ``value`` (reference ``nn/Padding.scala``)."""

    def __init__(self, dim: int, pad: int, n_input_dim: int,
                 value: float = 0.0, n_index: int = 1, name=None):
        super().__init__(name)
        self.dim = dim
        self.pad = pad
        self.n_input_dim = n_input_dim
        self.value = value

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dim, input.ndim, self.n_input_dim)
        pads = [(0, 0)] * input.ndim
        pads[ax] = (-self.pad, 0) if self.pad < 0 else (0, self.pad)
        return jnp.pad(input, pads, constant_values=self.value), state


class SpatialZeroPadding(Module):
    """Zero-pad (or crop, negative) NCHW spatial borders (reference
    ``nn/SpatialZeroPadding.scala`` — its negative pads ``narrow`` the
    input; ``lax.pad``'s negative edge config is the same operation)."""

    def __init__(self, pad_left: int, pad_right: int = None,
                 pad_top: int = None, pad_bottom: int = None, name=None):
        super().__init__(name)
        self.pl = pad_left
        self.pr = pad_right if pad_right is not None else pad_left
        self.pt = pad_top if pad_top is not None else pad_left
        self.pb = pad_bottom if pad_bottom is not None else pad_left

    def apply(self, params, input, state, training=False, rng=None):
        if (input.shape[-1] + self.pl + self.pr < 1 or
                input.shape[-2] + self.pt + self.pb < 1):
            raise ValueError("input is too small")
        cfg = ([(0, 0, 0)] * (input.ndim - 2) +
               [(self.pt, self.pb, 0), (self.pl, self.pr, 0)])
        zero = jnp.asarray(0, input.dtype)
        return jax.lax.pad(input, zero, cfg), state


class GradientReversal(Module):
    """Identity forward, -lambda * grad backward (reference
    ``nn/GradientReversal.scala``), via custom VJP."""

    layout_role = "agnostic"

    def __init__(self, the_lambda: float = 1.0, name=None):
        super().__init__(name)
        self.the_lambda = the_lambda

    def apply(self, params, input, state, training=False, rng=None):
        lam = self.the_lambda

        @jax.custom_vjp
        def rev(x):
            return x

        rev.defvjp(lambda x: (x, None), lambda _, g: (-lam * g,))
        return rev(input), state


class Scale(Module):
    """cmul + cadd with learnable size-shaped weight and bias
    (reference ``nn/Scale.scala``)."""

    def __init__(self, size: Sequence[int], init_weight=None,
                 init_bias=None, name=None):
        super().__init__(name)
        self.size = tuple(size)
        self.init_weight = init_weight
        self.init_bias = init_bias

    def _init_params(self, rng):
        w = (jnp.asarray(self.init_weight).reshape(self.size)
             if self.init_weight is not None else jnp.ones(self.size))
        b = (jnp.asarray(self.init_bias).reshape(self.size)
             if self.init_bias is not None else jnp.zeros(self.size))
        return {"weight": w, "bias": b}

    def apply(self, params, input, state, training=False, rng=None):
        w, b = params["weight"], params["bias"]
        shape = [1] * input.ndim
        # align size to dims starting at axis 1 (channel-wise for NCHW)
        for i, s in enumerate(self.size):
            shape[min(i + 1, input.ndim - 1)] = s
        return input * jnp.reshape(w, shape) + jnp.reshape(b, shape), state


class Bottle(Module):
    """Flatten leading dims, apply inner module, restore
    (reference ``nn/Bottle.scala``)."""

    def __init__(self, module: Module, n_input_dim: int = 2,
                 n_output_dim: int = 2, name=None):
        super().__init__(name)
        self.module = module
        self.n_input_dim = n_input_dim
        self.n_output_dim = n_output_dim

    def _init_params(self, rng):
        return [self.module._init_params(rng)]

    def _init_state(self):
        return [self.module._init_state()]

    def modules(self):
        return [self] + self.module.modules()

    def apply(self, params, input, state, training=False, rng=None):
        lead = input.shape[:input.ndim - self.n_input_dim + 1]
        rest = input.shape[input.ndim - self.n_input_dim + 1:]
        flat = jnp.reshape(input, (-1,) + rest)
        with child_scope(self.module, 0):
            out, s = self.module.apply(params[0], flat, state[0],
                                       training=training, rng=rng)
        out = jnp.reshape(out, lead + out.shape[1:])
        return out, [s]


class Remat(Container):
    """Activation-checkpoint (rematerialization) wrapper.

    ``jax.checkpoint`` around the wrapped module's pure ``apply``: the
    backward pass recomputes the module's internal activations from the
    module INPUT instead of storing them through the whole forward —
    trading one extra forward's FLOPs per wrapped span for O(spans)
    instead of O(all ops) activation residency.  This is the standard
    TPU lever for pushing a deep transformer stack past the HBM capacity
    wall (no reference equivalent: the reference keeps every layer's
    ``output``/``gradInput`` buffer resident by design,
    ``nn/abstractnn/AbstractModule.scala:54``).

    ``policy`` selects what intermediates MAY be saved anyway:

    - ``None`` / ``"nothing"`` — save nothing inside the span (max memory
      savings, full forward recompute in the VJP);
    - ``"dots"`` — save matmul/contraction outputs
      (``jax.checkpoint_policies.dots_with_no_batch_dims_saveable``):
      only cheap elementwise/norm ops recompute, a good default when the
      span is matmul-dominated;
    - ``"save_attn"`` — save ONLY tensors tagged ``attn_ctx``
      (:class:`~bigdl_tpu.nn.attention.MultiHeadAttention` names its
      attention context): one O(B*T*d) residual per block keeps the
      attention kernel (flash/chunked/standard) out of the VJP's
      recompute while projections/elementwise still remat — the
      middle ground where ``"dots"`` exceeds HBM but full recompute
      wastes the most expensive op;
    - any ``jax.checkpoint_policies`` callable.

    Implemented as a Container with one child so ``modules()`` walks,
    ``parallel.tp_specs``'s spec recursion, sequence-parallel wiring and
    child param adoption all see through it transparently.
    """

    def __init__(self, inner: Module, policy=None, name=None):
        super().__init__(name)
        self.add(inner)
        self.policy = policy
        self.checkpoint_policy()   # typo'd policies fail HERE, not at trace

    def add(self, module: Module) -> "Container":
        if self.children:
            raise ValueError("Remat wraps exactly one module; compose a "
                             "Sequential inside it instead")
        return super().add(module)

    def scope_name(self, index: int):
        """No scope of its own: the wrapped module's scope stands where it
        would without the wrapper (under jax's own ``checkpoint``)."""
        return None

    def checkpoint_policy(self):
        if callable(self.policy):
            return self.policy
        if self.policy in (None, "nothing"):
            return None
        if self.policy == "dots":
            return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        if self.policy == "save_attn":
            return jax.checkpoint_policies.save_only_these_names("attn_ctx")
        raise ValueError(
            f"unknown remat policy {self.policy!r}: expected None, "
            "'nothing', 'dots', 'save_attn', or a jax.checkpoint_policies "
            "callable")

    def apply(self, params, input, state, training=False, rng=None):
        inner = self.children[0]

        def fn(p, x, s, r):
            with child_scope(inner, 0):
                return inner.apply(p, x, s, training=training, rng=r)

        out, new_s = jax.checkpoint(fn, policy=self.checkpoint_policy())(
            params[0], input, state[0], _child_rng(rng, 0))
        return out, [new_s]


class MM(Module):
    """Matrix multiply of a Table [a, b] (reference ``nn/MM.scala``)."""

    def __init__(self, trans_a: bool = False, trans_b: bool = False, name=None):
        super().__init__(name)
        self.trans_a, self.trans_b = trans_a, trans_b

    def apply(self, params, input, state, training=False, rng=None):
        a, b = input[0], input[1]
        if self.trans_a:
            a = jnp.swapaxes(a, -1, -2)
        if self.trans_b:
            b = jnp.swapaxes(b, -1, -2)
        return jnp.matmul(a, b), state


class MV(Module):
    """Matrix-vector multiply of a Table [m, v] (reference ``nn/MV.scala``)."""

    def __init__(self, trans: bool = False, name=None):
        super().__init__(name)
        self.trans = trans

    def apply(self, params, input, state, training=False, rng=None):
        m, v = input[0], input[1]
        if self.trans:
            m = jnp.swapaxes(m, -1, -2)
        return jnp.einsum("...ij,...j->...i", m, v), state


class DotProduct(Module):
    """Row-wise dot product of a Table [a, b] (reference ``nn/DotProduct.scala``)."""

    def apply(self, params, input, state, training=False, rng=None):
        a, b = input[0], input[1]
        return jnp.sum(a * b, axis=-1), state


class Pack(Module):
    """Stack a Table of tensors along a new 1-based dim (reference ``nn/Pack.scala``)."""

    def __init__(self, dimension: int, name=None):
        super().__init__(name)
        self.dimension = dimension

    def apply(self, params, input, state, training=False, rng=None):
        xs = input if isinstance(input, (list, tuple)) else [input]
        return jnp.stack(list(xs), axis=self.dimension - 1), state


class Reverse(Module):
    """Reverse along a 1-based dim (reference ``nn/Reverse.scala``)."""

    def __init__(self, dimension: int = 1, name=None):
        super().__init__(name)
        self.dimension = dimension

    def apply(self, params, input, state, training=False, rng=None):
        return jnp.flip(input, axis=self.dimension - 1), state


class MulConstant(Module):
    """Multiply by a scalar constant (reference ``nn/MulConstant.scala``)."""

    layout_role = "agnostic"

    def __init__(self, constant_scalar: float, inplace: bool = False, name=None):
        super().__init__(name)
        self.constant = constant_scalar

    def apply(self, params, input, state, training=False, rng=None):
        return input * self.constant, state


class ChannelNormalize(Module):
    """Device-side per-channel input normalization for NCHW batches:
    ``(x.float() - mean[c]) / std[c]``, optionally cast to ``dtype``.

    TPU-first ingest companion to the host-side ``BGRImgNormalizer``
    (reference ``BGRImgNormalizer.scala`` always normalizes on CPU):
    putting this module first lets the data pipeline ship RAW uint8
    pixels over the host->device link — a 4x byte reduction on any
    deployment, and the deciding factor on links where bandwidth is the
    ingest wall.  The subtraction/scale fuses into the first convolution under XLA.
    ``dtype`` pins the output precision (e.g. ``"bfloat16"`` under
    mixed-precision training, where a float32 output would silently
    promote the first conv back to fp32).  ``format="NHWC"`` normalizes
    the trailing channel axis for the channels-last compute path."""

    layout_role = "spatial"

    def __init__(self, mean, std, dtype=None, format="NCHW", name=None):
        super().__init__(name)
        self.mean = tuple(float(m) for m in mean)
        self.std = tuple(float(s) for s in std)
        self.dtype = dtype
        self.format = format

    def apply(self, params, input, state, training=False, rng=None):
        c = len(self.mean)
        if self.format == "NCHW":
            shape = (1, c) + (1,) * (input.ndim - 2)
        else:
            shape = (1,) * (input.ndim - 1) + (c,)
        mean = jnp.asarray(self.mean, jnp.float32).reshape(shape)
        std = jnp.asarray(self.std, jnp.float32).reshape(shape)
        out = (input.astype(jnp.float32) - mean) / std
        if self.dtype is not None:
            out = out.astype(self.dtype)
        return out, state


class DeviceAugment(Module):
    """On-device crop/flip(/ColorJitter) head for device-augment ingest
    (ISSUE 16): consumes the ``[frames_u8_NHWC, offsets_i32, flips_u8]``
    (optionally ``+ [jitter_seeds_i32]``) input list that
    ``StreamingIngest`` packs in ``deviceAugment`` mode and emits the
    uint8 NCHW crop batch the host MT path would have produced — the
    per-pixel crop/flip/transpose work moves off the decode threads and
    into the fused step, so only raw full frames plus a few bytes of
    ride-along metadata cross the host->device link.  Place it first,
    ahead of ``ChannelNormalize``.  The crop offsets and flip flags are
    host-drawn from the clone-and-commit RNG stream, so trained weights
    are bit-identical to the host path (asserted in
    test_prefetch_determinism.py).  ``color_jitter`` is a dict of
    ``brightness``/``contrast``/``saturation`` factors; it requires the
    packer's ride-along seeds and breaks host-path parity by design."""

    layout_role = "spatial"

    def __init__(self, crop_h, crop_w, color_jitter=None, name=None):
        super().__init__(name)
        self.crop_h = int(crop_h)
        self.crop_w = int(crop_w)
        self.color_jitter = dict(color_jitter) if color_jitter else None

    def apply(self, params, input, state, training=False, rng=None):
        from bigdl_tpu.dataset import device_augment as _aug
        if not isinstance(input, (list, tuple)) or len(input) < 3:
            # Already-assembled NCHW batch (host path): pass through so
            # one model definition serves both ingest modes.
            return input, state
        frames, offsets, flips = input[0], input[1], input[2]
        out = _aug.crop_flip_transpose(frames, offsets, flips,
                                       self.crop_h, self.crop_w)
        if self.color_jitter and len(input) > 3:
            out = _aug.color_jitter(out, input[3], **self.color_jitter)
        return out, state


class AddConstant(Module):
    """Add a scalar constant (reference ``nn/AddConstant.scala``)."""

    layout_role = "agnostic"

    def __init__(self, constant_scalar: float, inplace: bool = False, name=None):
        super().__init__(name)
        self.constant = constant_scalar

    def apply(self, params, input, state, training=False, rng=None):
        return input + self.constant, state
