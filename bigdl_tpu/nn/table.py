"""Table (multi-input/multi-output) plumbing layers and branch containers.

Reference: ``nn/Concat.scala``, ``nn/ConcatTable.scala``, ``nn/ParallelTable.scala``,
``nn/MapTable.scala``, ``nn/JoinTable.scala``, ``nn/SplitTable.scala``,
``nn/SelectTable.scala``, ``nn/NarrowTable.scala``, ``nn/FlattenTable.scala``,
``nn/MixtureTable.scala``, ``nn/CAddTable.scala`` (+ CSub/CMul/CDiv/CMax/CMin),
``nn/PairwiseDistance.scala``, ``nn/CosineDistance.scala``.

A Table is a python list/tuple of activities (reference ``utils/Table.scala:34``).
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp

from bigdl_tpu.nn.module import (Module, Container, _child_rng,
                                 child_scope)
from bigdl_tpu.nn.structural import _axis


class Concat(Container):
    """Apply each child to the SAME input, concat outputs along 1-based dim
    (reference ``nn/Concat.scala``)."""

    def __init__(self, dimension: int, name=None):
        super().__init__(name)
        self.dimension = dimension

    def apply(self, params, input, state, training=False, rng=None):
        outs, new_states = [], []
        for i, child in enumerate(self.children):
            with child_scope(child, i):
                o, s = child.apply(params[i], input, state[i],
                                   training=training, rng=_child_rng(rng, i))
            outs.append(o)
            new_states.append(s)
        ax = _axis(self.dimension, outs[0].ndim)
        return jnp.concatenate(outs, axis=ax), new_states


class ConcatTable(Container):
    """Apply each child to the same input; output is the Table of results
    (reference ``nn/ConcatTable.scala``)."""

    def apply(self, params, input, state, training=False, rng=None):
        outs, new_states = [], []
        for i, child in enumerate(self.children):
            with child_scope(child, i):
                o, s = child.apply(params[i], input, state[i],
                                   training=training, rng=_child_rng(rng, i))
            outs.append(o)
            new_states.append(s)
        return outs, new_states


class ParallelTable(Container):
    """i-th child applied to i-th table element (reference ``nn/ParallelTable.scala``)."""

    def apply(self, params, input, state, training=False, rng=None):
        outs, new_states = [], []
        for i, child in enumerate(self.children):
            with child_scope(child, i):
                o, s = child.apply(params[i], input[i], state[i],
                                   training=training, rng=_child_rng(rng, i))
            outs.append(o)
            new_states.append(s)
        return outs, new_states


class MapTable(Container):
    """One shared child applied to every table element
    (reference ``nn/MapTable.scala``).  Parameters are shared — the single
    child's params are used for every element."""

    def __init__(self, module: Optional[Module] = None, name=None):
        super().__init__(name)
        if module is not None:
            self.add(module)

    def apply(self, params, input, state, training=False, rng=None):
        child = self.children[0]
        outs = []
        s = state[0]
        for i, x in enumerate(input):
            with child_scope(child, 0):
                o, s = child.apply(params[0], x, s, training=training,
                                   rng=_child_rng(rng, i))
            outs.append(o)
        return outs, [s]


class JoinTable(Module):
    """Concat a Table of tensors along a 1-based dim
    (reference ``nn/JoinTable.scala``)."""

    def __init__(self, dimension: int, n_input_dims: int = -1, name=None):
        super().__init__(name)
        self.dimension = dimension
        self.n_input_dims = n_input_dims

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dimension, input[0].ndim, self.n_input_dims)
        return jnp.concatenate(list(input), axis=ax), state


class SplitTable(Module):
    """Split a tensor into a Table along a 1-based dim
    (reference ``nn/SplitTable.scala``)."""

    def __init__(self, dimension: int, n_input_dims: int = -1, name=None):
        super().__init__(name)
        self.dimension = dimension
        self.n_input_dims = n_input_dims

    def apply(self, params, input, state, training=False, rng=None):
        ax = _axis(self.dimension, input.ndim, self.n_input_dims)
        n = input.shape[ax]
        outs = [jnp.take(input, i, axis=ax) for i in range(n)]
        return outs, state


class SelectTable(Module):
    """Select the i-th (1-based) element of a Table
    (reference ``nn/SelectTable.scala``)."""

    def __init__(self, index: int, name=None):
        super().__init__(name)
        self.index = index

    def apply(self, params, input, state, training=False, rng=None):
        i = self.index - 1 if self.index > 0 else len(input) + self.index
        return input[i], state


class NarrowTable(Module):
    """Slice a Table (reference ``nn/NarrowTable.scala``)."""

    def __init__(self, offset: int, length: int = 1, name=None):
        super().__init__(name)
        self.offset = offset
        self.length = length

    def apply(self, params, input, state, training=False, rng=None):
        length = self.length
        if length < 0:
            length = len(input) - self.offset + 2 + length
        return list(input)[self.offset - 1: self.offset - 1 + length], state


class FlattenTable(Module):
    """Flatten nested Tables into one flat Table (reference ``nn/FlattenTable.scala``)."""

    def apply(self, params, input, state, training=False, rng=None):
        out: List = []

        def rec(x):
            if isinstance(x, (list, tuple)):
                for v in x:
                    rec(v)
            else:
                out.append(x)

        rec(input)
        return out, state


class MixtureTable(Module):
    """Mixture-of-experts blend: input [gates (N,E), experts Table/tensor]
    (reference ``nn/MixtureTable.scala``)."""

    def __init__(self, dim: int = -1, name=None):
        super().__init__(name)
        self.dim = dim

    def apply(self, params, input, state, training=False, rng=None):
        gates, experts = input[0], input[1]
        if isinstance(experts, (list, tuple)):
            stacked = jnp.stack(list(experts), axis=1)  # (N, E, ...)
        else:
            stacked = experts
        gshape = gates.shape + (1,) * (stacked.ndim - gates.ndim)
        return jnp.sum(stacked * jnp.reshape(gates, gshape), axis=1), state


class _BinaryTableOp(Module):
    layout_role = "agnostic"   # elementwise over the table entries

    def _op(self, a, b):
        raise NotImplementedError

    def apply(self, params, input, state, training=False, rng=None):
        out = input[0]
        for x in input[1:]:
            out = self._op(out, x)
        return out, state


class CAddTable(_BinaryTableOp):
    """Elementwise sum of a Table (reference ``nn/CAddTable.scala``)."""

    def __init__(self, inplace: bool = False, name=None):
        super().__init__(name)

    def _op(self, a, b):
        return a + b


class CSubTable(_BinaryTableOp):
    """Elementwise a - b of a Table [a, b] (reference ``nn/CSubTable.scala``)."""

    def _op(self, a, b):
        return a - b


class CMulTable(_BinaryTableOp):
    """Elementwise product of all Table elements (reference ``nn/CMulTable.scala``)."""

    def _op(self, a, b):
        return a * b


class CDivTable(_BinaryTableOp):
    """Elementwise a / b of a Table [a, b] (reference ``nn/CDivTable.scala``)."""

    def _op(self, a, b):
        return a / b


class CMaxTable(_BinaryTableOp):
    """Elementwise maximum of all Table elements (reference ``nn/CMaxTable.scala``)."""

    def _op(self, a, b):
        return jnp.maximum(a, b)


class CMinTable(_BinaryTableOp):
    """Elementwise minimum of all Table elements (reference ``nn/CMinTable.scala``)."""

    def _op(self, a, b):
        return jnp.minimum(a, b)


class PairwiseDistance(Module):
    """L-p distance between table elements [a, b]
    (reference ``nn/PairwiseDistance.scala``)."""

    def __init__(self, norm: int = 2, name=None):
        super().__init__(name)
        self.norm = norm

    def apply(self, params, input, state, training=False, rng=None):
        a, b = input[0], input[1]
        d = jnp.abs(a - b) ** self.norm
        return jnp.sum(d, axis=-1) ** (1.0 / self.norm), state


class CosineDistance(Module):
    """Cosine similarity between table elements [a, b]
    (reference ``nn/CosineDistance.scala``)."""

    def apply(self, params, input, state, training=False, rng=None):
        a, b = input[0], input[1]
        an = jnp.maximum(jnp.linalg.norm(a, axis=-1), 1e-12)
        bn = jnp.maximum(jnp.linalg.norm(b, axis=-1), 1e-12)
        return jnp.sum(a * b, axis=-1) / (an * bn), state
