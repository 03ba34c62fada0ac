"""Channels-last (NHWC) compute path for convnets behind the NCHW facade.

The model zoo builds networks in Torch's NCHW convention, but the TPU's
native image layout is channels-last: convolutions/pooling/batch-norm with
``NCHW`` dimension numbers force XLA to wrap every such op in layout
transposes (and the small-taps matmul conv path in ``ops/convolution.py``
transposes explicitly).  :func:`to_channels_last` rewrites a built model so
the whole convolutional trunk computes in NHWC while the public API stays
NCHW: one :class:`NCHWToNHWC` at the network boundary, its inverse once at
the exit (or before the first interior layout-dependent module, e.g. the
``View`` flatten feeding the classifier head), and zero interior transposes
in between — a property the HLO-inspection test in ``tests/test_layout.py``
asserts on the jitted ResNet-50 forward.

The conversion walks the module tree using the layout contract every
:class:`~bigdl_tpu.nn.module.Module` declares (``layout_role`` — "opaque" /
"agnostic" / "spatial", see module.py) and the containers' structure:

- ``Sequential`` threads the current layout through its children, inserting
  the NCHW->NHWC switch right before the first spatial subtree and the
  inverse before any "opaque" (layout-dependent) child;
- ``Concat``/``ConcatTable`` fan the same layout into every branch and
  require the branches to agree on the output layout (a channel ``Concat``
  over NHWC maps is remapped from Torch dim 2 to the trailing axis);
- ``Graph`` propagates layouts along its topological order (``JoinTable``
  channel joins are remapped like ``Concat``);
- ``Remat`` is transparent.

Everything happens in place (params/state lists of already-initialised
containers are kept aligned with the inserted boundary modules), so a model
with loaded weights converts without re-initialisation: kernel storage is
HWIO in both layouts, only activations change shape.
"""

from __future__ import annotations

import jax.numpy as jnp

from bigdl_tpu.nn.module import Container, Module, Sequential
from bigdl_tpu.nn.graph import Graph
from bigdl_tpu.nn.structural import Remat
from bigdl_tpu.nn.table import Concat, ConcatTable, JoinTable

__all__ = ["NCHWToNHWC", "NHWCToNCHW", "to_channels_last", "apply_layout"]


class NCHWToNHWC(Module):
    """Boundary transpose: Torch-facade NCHW batch -> channels-last NHWC.

    Handles batched (N, C, H, W) and unbatched (C, H, W) activations.  A
    map whose spatial extent is 1x1 is moved with a reshape instead — the
    data is layout-identical, so the exit of a global-pool trunk costs
    nothing and no transpose op reaches the HLO."""

    def apply(self, params, input, state, training=False, rng=None):
        if input.ndim == 3:
            c, h, w = input.shape
            if h == 1 and w == 1:
                return jnp.reshape(input, (1, 1, c)), state
            return jnp.transpose(input, (1, 2, 0)), state
        n, c, h, w = input.shape
        if h == 1 and w == 1:
            return jnp.reshape(input, (n, 1, 1, c)), state
        return jnp.transpose(input, (0, 2, 3, 1)), state


class NHWCToNCHW(Module):
    """Boundary transpose: channels-last NHWC -> Torch-facade NCHW
    (reshape-only when the spatial extent is 1x1, see :class:`NCHWToNHWC`)."""

    def apply(self, params, input, state, training=False, rng=None):
        if input.ndim == 3:
            h, w, c = input.shape
            if h == 1 and w == 1:
                return jnp.reshape(input, (c, 1, 1)), state
            return jnp.transpose(input, (2, 0, 1)), state
        n, h, w, c = input.shape
        if h == 1 and w == 1:
            return jnp.reshape(input, (n, c, 1, 1)), state
        return jnp.transpose(input, (0, 3, 1, 2)), state


# ---------------------------------------------------------------------------
# structure editing helpers (keep params/state/grads lists aligned)
# ---------------------------------------------------------------------------

def _insert_child(seq: Container, i: int, module: Module) -> None:
    seq.children.insert(i, module)
    if seq._params is not None:
        module._ensure_init()          # boundary modules: {} params/state
        seq._params.insert(i, module._params)
        seq._state.insert(i, module._state)
        if seq._grads_tree is not None:
            seq._grads_tree.insert(i, module._grads)
    seq._jit_apply = None


def _wrapped(child: Module, before: Module = None,
             after: Module = None) -> Sequential:
    """A Sequential around ``child`` with optional boundary modules,
    inheriting ``child``'s initialised params/state so parent containers
    stay aligned after replacing the slot."""
    mods = [m for m in (before, child, after) if m is not None]
    w = Sequential()
    w.children.extend(mods)
    if child._params is not None:
        for m in mods:
            m._ensure_init()
        w._params = [m._params for m in mods]
        w._state = [m._state for m in mods]
        if child._grads_tree is not None:
            w._grads = [m._grads for m in mods]
    return w


def _replace_child(container: Container, i: int, wrapper: Module) -> None:
    container.children[i] = wrapper
    if container._params is not None:
        wrapper._ensure_init()
        container._params[i] = wrapper._params
        container._state[i] = wrapper._state
        if container._grads_tree is not None:
            container._grads_tree[i] = wrapper._grads
    container._jit_apply = None


# ---------------------------------------------------------------------------
# layout analysis
# ---------------------------------------------------------------------------

def _supported_container(m: Module) -> bool:
    return isinstance(m, (Sequential, Concat, ConcatTable, Remat, Graph))


def _contains_spatial(m: Module) -> bool:
    if m.layout_role == "spatial":
        return True
    if isinstance(m, Container):
        return any(_contains_spatial(c) for c in m.children)
    return False


def _wants_nhwc(m: Module) -> bool:
    """True if ``m``'s INPUT edge consumes image maps (so the caller should
    hand it NHWC): the first non-agnostic thing along the input path is a
    spatial module."""
    if m.layout_role == "spatial":
        return True
    if isinstance(m, Sequential):
        for c in m.children:
            if c.layout_role == "agnostic":
                continue
            return _wants_nhwc(c)
        return False
    if isinstance(m, Remat):
        return bool(m.children) and _wants_nhwc(m.children[0])
    if isinstance(m, (Concat, ConcatTable)):
        return any(_wants_nhwc(c) for c in m.children)
    if isinstance(m, Graph):
        # graphs start at Input() placeholders; fall back to containment
        return _contains_spatial(m)
    return False


def _remap_channel_concat(m, out_layout: str) -> None:
    """Torch channel concat is dim 2 (axis 1, NCHW); in NHWC the channel is
    the trailing axis.  dimension = -1 resolves to the last axis at any
    rank, so unbatched 3-D activations keep working."""
    if out_layout != "NHWC":
        return
    if m.dimension == 2:
        m.dimension = -1
    elif m.dimension != -1:   # != -1: not already converted
        raise ValueError(
            f"{m.name}: only channel concatenation (dimension=2) is "
            f"supported on the channels-last path, got dimension="
            f"{m.dimension}")


# ---------------------------------------------------------------------------
# the converter
# ---------------------------------------------------------------------------

def _convert(m: Module, fmt: str) -> str:
    """Convert ``m`` in place to consume activations in ``fmt``; returns the
    layout of its output."""
    if isinstance(m, NCHWToNHWC):
        return "NHWC"
    if isinstance(m, NHWCToNCHW):
        return "NCHW"
    if isinstance(m, Sequential):
        return _convert_sequential(m, fmt)
    if isinstance(m, Remat):
        if not m.children:
            return fmt
        c = m.children[0]
        if (fmt == "NHWC" and c.layout_role == "opaque" and
                not _supported_container(c) and
                not isinstance(c, (NCHWToNHWC, NHWCToNCHW))):
            _replace_child(m, 0, _wrapped(c, before=NHWCToNCHW()))
            return "NCHW"
        return _convert(c, fmt)
    if isinstance(m, Graph):
        return _convert_graph(m, fmt)
    if isinstance(m, (Concat, ConcatTable)):
        return _convert_branch(m, fmt)
    if m.layout_role == "agnostic":
        return fmt
    if m.layout_role == "spatial":
        m.set_format(fmt)
        return fmt
    # opaque leaf or unsupported container: the CALLER must have already
    # restored NCHW in front of it
    return "NCHW"


def _convert_sequential(seq: Sequential, fmt: str) -> str:
    cur = fmt
    i = 0
    while i < len(seq.children):
        c = seq.children[i]
        if isinstance(c, NCHWToNHWC):
            cur = "NHWC"
        elif isinstance(c, NHWCToNCHW):
            cur = "NCHW"
        elif c.layout_role == "agnostic":
            pass
        elif (c.layout_role == "spatial" or
              (_supported_container(c) and _wants_nhwc(c))):
            if cur == "NCHW":
                # the single entry switch, placed before the first spatial
                # subtree
                _insert_child(seq, i, NCHWToNHWC())
                i += 1
                cur = "NHWC"
            cur = _convert(c, cur)
        elif _supported_container(c):
            cur = _convert(c, cur)
        else:
            # layout-dependent module (View/Reshape/Linear/...): restore
            # the NCHW facade once, right before it
            if cur == "NHWC":
                _insert_child(seq, i, NHWCToNCHW())
                i += 1
                cur = "NCHW"
        i += 1
    return cur


def _convert_branch(cc, fmt: str) -> str:
    outs = []
    for i in range(len(cc.children)):
        c = cc.children[i]
        if isinstance(c, (NCHWToNHWC, NHWCToNCHW)) or c.layout_role in (
                "agnostic", "spatial") or _supported_container(c):
            outs.append(_convert(c, fmt))
        else:                      # opaque branch head needs the facade back
            if fmt == "NHWC":
                _replace_child(cc, i, _wrapped(c, before=NHWCToNCHW()))
            outs.append("NCHW")
    if len(set(outs)) > 1:
        raise ValueError(
            f"{cc.name}: branches disagree on output layout {outs}; "
            f"restructure so every branch ends in the same layout")
    out = outs[0] if outs else fmt
    if isinstance(cc, Concat):
        _remap_channel_concat(cc, out)
    return out


def _convert_graph(g: Graph, fmt: str) -> str:
    layouts = {}
    for idx, node in enumerate(g.executions):
        if not node.prev:
            in_l = fmt
        else:
            ins = {layouts[id(p)] for p in node.prev}
            if len(ins) > 1:
                raise ValueError(
                    f"{g.name}: node {node.element.name} receives mixed "
                    f"layouts {sorted(ins)}")
            (in_l,) = ins
        el = node.element
        if isinstance(el, JoinTable):
            _remap_channel_concat(el, in_l)
            out = in_l
        elif (el.layout_role in ("agnostic", "spatial") or
              _supported_container(el) or
              isinstance(el, (NCHWToNHWC, NHWCToNCHW))):
            out = _convert(el, in_l)
        else:
            if in_l == "NHWC":
                wrapper = _wrapped(el, before=NHWCToNCHW())
                node.element = wrapper
                _replace_child(g, idx, wrapper)
            out = "NCHW"
        layouts[id(node)] = out
    outl = {layouts[id(n)] for n in g.output_nodes}
    if len(outl) > 1:
        raise ValueError(f"{g.name}: output nodes disagree on layout")
    return outl.pop()


def _clear_jit(model: Module) -> None:
    model.clear_jit_cache()


def to_channels_last(model: Module) -> Module:
    """Rewrite ``model`` so its convolutional trunk computes in NHWC while
    the public API keeps consuming/producing Torch-style NCHW activations.

    In place and idempotent; safe on initialised models (loaded weights are
    untouched — kernels are stored HWIO in both layouts).  Returns the
    converted model: the SAME object for ``Sequential`` tops, a wrapping
    ``Sequential`` for other containers whose output stays a spatial map.
    """
    if not isinstance(model, Container) or not _contains_spatial(model):
        return model
    if not isinstance(model, Sequential):
        model = _wrapped(model)
    if model._params is not None:
        # re-link child param/state views to the top-level lists first: a
        # clone_module'd tree holds per-container COPIES (pickling breaks
        # the sharing), and the in-place inserts below must land in the
        # one tree apply() reads
        model._adopt()
    out = _convert_sequential(model, "NCHW")
    if out == "NHWC":
        _insert_child(model, len(model.children), NHWCToNCHW())
    if model._params is not None and isinstance(model, Container):
        model._adopt()
    _clear_jit(model)
    return model


def apply_layout(model: Module, layout: str) -> Module:
    """Zoo-builder helper: ``layout="NHWC"`` converts to the channels-last
    compute path (the default), ``"NCHW"`` keeps the classic layout."""
    if layout == "NHWC":
        return to_channels_last(model)
    if layout == "NCHW":
        return model
    raise ValueError(f"unknown layout {layout!r}: expected 'NHWC' or 'NCHW'")
