"""Recurrent stack: cells, Recurrent/BiRecurrent containers, TimeDistributed.

Reference equivalents: ``nn/Cell.scala:44`` (Cell hierarchy), ``nn/RNN.scala``
(RnnCell), ``nn/LSTM.scala:50``, ``nn/LSTMPeephole.scala``, ``nn/GRU.scala:54``,
``nn/ConvLSTMPeephole.scala``, ``nn/Recurrent.scala:33`` (time-dim unroll
container), ``nn/BiRecurrent.scala:33``, ``nn/TimeDistributed.scala:40``.

TPU-native redesign:

- The reference unrolls time in Scala (``nn/Recurrent.scala:203-263``), cloning
  the cell per timestep with shared parameters.  Here the unroll is a single
  ``lax.scan`` — XLA sees one compiled loop body, keeps the carried hidden
  state in registers/VMEM, and the whole scan differentiates through
  ``jax.grad`` (BPTT falls out of autodiff; no stored per-step activation
  management needed — rematerialisation is XLA's job).
- The reference's ``preTopology`` optimisation (hoist time-independent input
  projections out of the loop, ``nn/Cell.scala:50-75``) is expressed as
  :meth:`Cell.project_input`: the input-to-hidden matmul runs once over the
  whole ``(B, T, D)`` block — one large MXU matmul instead of T small ones.
  The scan body then only carries the hidden-to-hidden recurrence.
- Input layout is batch-first ``(B, T, features...)`` matching the reference's
  default ``batchNormal`` mode; the scan internally runs time-major.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.module import Container, Module, child_scope


def _uniform(rng, shape, stdv, dtype=jnp.float32):
    return jax.random.uniform(rng, shape, dtype, minval=-stdv, maxval=stdv)


# module-level named activations so cells (and models containing them)
# stay picklable for checkpoint/clone_module
def tanh(x):
    return jnp.tanh(x)


def sigmoid(x):
    return jax.nn.sigmoid(x)


class Cell(Module):
    """Base class of recurrent cells (reference ``nn/Cell.scala:44``).

    A cell defines three pure pieces:

    - :meth:`init_hidden`   — zero hidden state for a given batch size;
    - :meth:`project_input` — time-independent input projection, applied to the
      full ``(B, T, ...)`` input at once (the reference's ``preTopology``);
    - :meth:`step`          — one recurrence step on a projected timestep.

    ``apply`` gives the cell the reference's standalone Table semantics
    ``[input_t, hidden] -> [output_t, hidden']`` so a cell is usable as a
    plain module too.
    """

    hidden_is_tuple = False

    def init_hidden(self, params, batch_shape):
        raise NotImplementedError

    def project_input(self, params, x, training=False, rng=None):
        """Projection over all timesteps; default identity."""
        return x

    def step(self, params, proj_t, hidden):
        """One step: (projected input_t, hidden) -> (output_t, hidden')."""
        raise NotImplementedError

    def apply(self, params, input, state, training=False, rng=None):
        x_t, hidden = input[0], input[1]
        proj = self.project_input(params, x_t[:, None], training, rng)[:, 0]
        out, new_hidden = self.step(params, proj, hidden)
        return [out, new_hidden], state


class RnnCell(Cell):
    """Vanilla RNN cell: h' = act(x W_ih + b + h W_hh)
    (reference ``nn/RNN.scala``)."""

    def __init__(self, input_size: int, hidden_size: int,
                 activation=tanh, w_regularizer=None, u_regularizer=None,
                 b_regularizer=None, name=None):
        super().__init__(name)
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.activation = activation
        self.w_regularizer = w_regularizer
        self.u_regularizer = u_regularizer
        self.b_regularizer = b_regularizer

    def _init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        stdv = 1.0 / math.sqrt(self.hidden_size)
        return {"w_ih": _uniform(k1, (self.input_size, self.hidden_size), stdv),
                "w_hh": _uniform(k2, (self.hidden_size, self.hidden_size), stdv),
                "bias": _uniform(k3, (self.hidden_size,), stdv)}

    def init_hidden(self, params, batch_shape):
        # follow the (possibly bf16-cast) parameter dtype: an f32 hidden
        # state would promote every recurrent matmul of a bf16 forward
        return jnp.zeros(tuple(batch_shape) + (self.hidden_size,),
                         dtype=params["w_hh"].dtype)

    def project_input(self, params, x, training=False, rng=None):
        return x @ params["w_ih"] + params["bias"]

    def step(self, params, proj_t, hidden):
        h = self.activation(proj_t + hidden @ params["w_hh"])
        return h, h


class LSTM(Cell):
    """LSTM cell, gate order (i, f, g, o) (reference ``nn/LSTM.scala:50``).

    The four gate projections are one fused ``(D, 4H)`` matmul.  ``p`` is the
    reference's dropout probability on the input projections; masks for all
    timesteps are drawn up front so the scan body stays deterministic.
    """

    hidden_is_tuple = True

    def __init__(self, input_size: int, hidden_size: int, p: float = 0.0,
                 activation=tanh, inner_activation=sigmoid,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None,
                 name=None):
        super().__init__(name)
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.p = p
        self.activation = activation
        self.inner_activation = inner_activation
        self.w_regularizer = w_regularizer
        self.u_regularizer = u_regularizer
        self.b_regularizer = b_regularizer

    def is_stochastic(self):
        return self.p > 0

    def _init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        H = self.hidden_size
        stdv = 1.0 / math.sqrt(H)
        return {"w_ih": _uniform(k1, (self.input_size, 4 * H), stdv),
                "w_hh": _uniform(k2, (H, 4 * H), stdv),
                "bias": _uniform(k3, (4 * H,), stdv)}

    def init_hidden(self, params, batch_shape):
        z = jnp.zeros(tuple(batch_shape) + (self.hidden_size,),
                      dtype=params["w_hh"].dtype)
        return (z, z)

    def project_input(self, params, x, training=False, rng=None):
        if training and self.p > 0 and rng is not None:
            keep = 1.0 - self.p
            mask = jax.random.bernoulli(rng, keep, x.shape) / keep
            x = x * mask
        return x @ params["w_ih"] + params["bias"]

    def step(self, params, proj_t, hidden):
        h, c = hidden
        H = self.hidden_size
        z = proj_t + h @ params["w_hh"]
        i = self.inner_activation(z[..., 0:H])
        f = self.inner_activation(z[..., H:2 * H])
        g = self.activation(z[..., 2 * H:3 * H])
        o = self.inner_activation(z[..., 3 * H:4 * H])
        c2 = f * c + i * g
        h2 = o * self.activation(c2)
        return h2, (h2, c2)


class LSTMPeephole(LSTM):
    """LSTM with peephole connections from the cell state into the gates
    (reference ``nn/LSTMPeephole.scala``)."""

    def _init_params(self, rng):
        base = super()._init_params(rng)
        k = jax.random.fold_in(rng, 7)
        k1, k2, k3 = jax.random.split(k, 3)
        H = self.hidden_size
        stdv = 1.0 / math.sqrt(H)
        base.update({"w_ci": _uniform(k1, (H,), stdv),
                     "w_cf": _uniform(k2, (H,), stdv),
                     "w_co": _uniform(k3, (H,), stdv)})
        return base

    def step(self, params, proj_t, hidden):
        h, c = hidden
        H = self.hidden_size
        z = proj_t + h @ params["w_hh"]
        i = self.inner_activation(z[..., 0:H] + c * params["w_ci"])
        f = self.inner_activation(z[..., H:2 * H] + c * params["w_cf"])
        g = self.activation(z[..., 2 * H:3 * H])
        c2 = f * c + i * g
        o = self.inner_activation(z[..., 3 * H:4 * H] + c2 * params["w_co"])
        h2 = o * self.activation(c2)
        return h2, (h2, c2)


class GRU(Cell):
    """GRU cell, gates (r, z) + candidate n (reference ``nn/GRU.scala:54``)."""

    def __init__(self, input_size: int, hidden_size: int, p: float = 0.0,
                 w_regularizer=None, u_regularizer=None, b_regularizer=None,
                 name=None):
        super().__init__(name)
        self.input_size = input_size
        self.hidden_size = hidden_size
        self.p = p
        self.w_regularizer = w_regularizer
        self.u_regularizer = u_regularizer
        self.b_regularizer = b_regularizer

    def is_stochastic(self):
        return self.p > 0

    def _init_params(self, rng):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        H = self.hidden_size
        stdv = 1.0 / math.sqrt(H)
        return {"w_ih": _uniform(k1, (self.input_size, 3 * H), stdv),
                "w_hh": _uniform(k2, (H, 3 * H), stdv),
                "b_ih": _uniform(k3, (3 * H,), stdv),
                "b_hh": _uniform(k4, (3 * H,), stdv)}

    def init_hidden(self, params, batch_shape):
        return jnp.zeros(tuple(batch_shape) + (self.hidden_size,),
                         dtype=params["w_hh"].dtype)

    def project_input(self, params, x, training=False, rng=None):
        if training and self.p > 0 and rng is not None:
            keep = 1.0 - self.p
            mask = jax.random.bernoulli(rng, keep, x.shape) / keep
            x = x * mask
        return x @ params["w_ih"] + params["b_ih"]

    def step(self, params, proj_t, hidden):
        H = self.hidden_size
        hz = hidden @ params["w_hh"] + params["b_hh"]
        r = jax.nn.sigmoid(proj_t[..., 0:H] + hz[..., 0:H])
        z = jax.nn.sigmoid(proj_t[..., H:2 * H] + hz[..., H:2 * H])
        n = jnp.tanh(proj_t[..., 2 * H:3 * H] + r * hz[..., 2 * H:3 * H])
        h2 = (1.0 - z) * n + z * hidden
        return h2, h2


class ConvLSTMPeephole(Cell):
    """Convolutional LSTM with peepholes over NCHW maps
    (reference ``nn/ConvLSTMPeephole.scala``).

    All four gates come from one fused conv with ``4 * output_size`` output
    channels — a single large MXU convolution per step.
    """

    hidden_is_tuple = True
    _spatial_dims = 2

    def __init__(self, input_size: int, output_size: int,
                 kernel_i: int = 3, kernel_c: int = 3, stride: int = 1,
                 with_peephole: bool = True, name=None):
        super().__init__(name)
        self.input_size = input_size
        self.output_size = output_size
        self.kernel_i = kernel_i
        self.kernel_c = kernel_c
        self.stride = stride
        self.with_peephole = with_peephole
        self._spatial_shape = None  # bound at first init_hidden

    def _init_params(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        H, C = self.output_size, self.input_size
        nd = self._spatial_dims
        ki = (self.kernel_i,) * nd
        kc = (self.kernel_c,) * nd
        fan_in = C * self.kernel_i ** nd
        stdv = 1.0 / math.sqrt(fan_in)
        p = {"w_ih": _uniform(k1, (4 * H, C) + ki, stdv),
             "w_hh": _uniform(k2, (4 * H, H) + kc, stdv),
             "bias": _uniform(k3, (4 * H,), stdv)}
        if self.with_peephole:
            kk = jax.random.split(jax.random.fold_in(rng, 7), 3)
            ones = (1,) * nd
            p.update({"w_ci": _uniform(kk[0], (H,) + ones, stdv),
                      "w_cf": _uniform(kk[1], (H,) + ones, stdv),
                      "w_co": _uniform(kk[2], (H,) + ones, stdv)})
        return p

    def _dn(self, x):
        nd = self._spatial_dims
        spec = "NCHW" if nd == 2 else "NCDHW"
        kspec = "OIHW" if nd == 2 else "OIDHW"
        return lax.conv_dimension_numbers(x.shape, (1, 1) + (1,) * nd,
                                          (spec, kspec, spec))

    def _conv(self, x, w):
        nd = self._spatial_dims
        return lax.conv_general_dilated(
            x, w, window_strides=(1,) * nd, padding="SAME",
            dimension_numbers=self._dn(x))

    def init_hidden(self, params, batch_shape):
        if self._spatial_shape is None:
            raise RuntimeError("ConvLSTMPeephole hidden spatial shape unknown "
                               "before the first forward")
        shape = tuple(batch_shape) + (self.output_size,) + self._spatial_shape
        z = jnp.zeros(shape, dtype=params["w_hh"].dtype)
        return (z, z)

    def project_input(self, params, x, training=False, rng=None):
        # x: (B, T, C, *spatial) — fold T into the batch for one big conv
        B, T = x.shape[0], x.shape[1]
        self._spatial_shape = tuple(x.shape[3:])
        flat = x.reshape((B * T,) + x.shape[2:])
        nd = self._spatial_dims
        bias = params["bias"].reshape((1, -1) + (1,) * nd)
        out = self._conv(flat, params["w_ih"]) + bias
        return out.reshape((B, T) + out.shape[1:])

    def step(self, params, proj_t, hidden):
        h, c = hidden
        H = self.output_size
        z = proj_t + self._conv(h, params["w_hh"])
        zi, zf, zg, zo = (z[:, 0:H], z[:, H:2 * H],
                          z[:, 2 * H:3 * H], z[:, 3 * H:4 * H])
        if self.with_peephole:
            zi = zi + c * params["w_ci"]
            zf = zf + c * params["w_cf"]
        i = jax.nn.sigmoid(zi)
        f = jax.nn.sigmoid(zf)
        g = jnp.tanh(zg)
        c2 = f * c + i * g
        if self.with_peephole:
            zo = zo + c2 * params["w_co"]
        o = jax.nn.sigmoid(zo)
        h2 = o * jnp.tanh(c2)
        return h2, (h2, c2)


class ConvLSTMPeephole3D(ConvLSTMPeephole):
    """3-D variant (reference ``nn/ConvLSTMPeephole3D.scala``)."""

    _spatial_dims = 3


class Recurrent(Container):
    """Time-dimension unroll container (reference ``nn/Recurrent.scala:33``).

    ``add(cell)`` then forward a ``(B, T, features...)`` batch; output is the
    per-timestep cell output stacked back to ``(B, T, ...)``.  The unroll is a
    ``lax.scan`` over the time-major projected input.
    """

    def __init__(self, name=None):
        super().__init__(name)
        self._last_hidden = None
        self._init_hidden_override = None

    def add(self, module: Module) -> "Recurrent":
        if not isinstance(module, Cell):
            raise ValueError("Recurrent accepts a Cell, got "
                             f"{type(module).__name__}")
        if self.children:
            raise ValueError("Recurrent holds exactly one Cell")
        return super().add(module)

    @property
    def cell(self) -> Cell:
        return self.children[0]

    def set_hidden_state(self, hidden) -> "Recurrent":
        """(reference ``Recurrent.setHiddenState``)"""
        self._init_hidden_override = hidden
        return self

    def get_hidden_state(self):
        """(reference ``Recurrent.getHiddenState``) — hidden after the last
        forward (shell-side convenience; not part of the pure core)."""
        return self._last_hidden

    def apply(self, params, input, state, training=False, rng=None):
        cell = self.cell
        cp = params[0]
        proj = cell.project_input(cp, input, training=training, rng=rng)
        if self._init_hidden_override is not None:
            h0 = self._init_hidden_override
        else:
            h0 = cell.init_hidden(cp, (input.shape[0],))

        # time-major for the scan
        proj_tm = jnp.moveaxis(proj, 1, 0)

        def body(h, x_t):
            out, h2 = cell.step(cp, x_t, h)
            return h2, out

        h_final, outs = lax.scan(body, h0, proj_tm)
        # cache for get_hidden_state() only when not under a jit trace —
        # a leaked tracer would poison clone_module/checkpoint pickling
        if not any(isinstance(l, jax.core.Tracer)
                   for l in jax.tree_util.tree_leaves(h_final)):
            self._last_hidden = h_final
        return jnp.moveaxis(outs, 0, 1), state

    def __getstate__(self):
        d = super().__getstate__()
        d["_last_hidden"] = None
        return d


class BiRecurrent(Container):
    """Bidirectional wrapper (reference ``nn/BiRecurrent.scala:33``).

    Runs the cell forward and a clone backward over time, merging outputs
    with ``merge`` ('add', the reference's CAddTable default, or 'concat').
    """

    def __init__(self, merge: str = "add", name=None):
        super().__init__(name)
        if merge not in ("add", "concat"):
            raise ValueError(f"merge must be add|concat, got {merge}")
        self.merge = merge

    def add(self, module: Module) -> "BiRecurrent":
        if not isinstance(module, Cell):
            raise ValueError("BiRecurrent accepts a Cell")
        if len(self.children) >= 2:
            raise ValueError("BiRecurrent holds forward and reverse cells only")
        super().add(module)
        if len(self.children) == 1:
            super().add(module.clone_module())
        return self

    def apply(self, params, input, state, training=False, rng=None):
        fwd_cell, bwd_cell = self.children[0], self.children[1]

        def run(cell, cp, x, key):
            proj = cell.project_input(cp, x, training=training, rng=key)
            h0 = cell.init_hidden(cp, (x.shape[0],))
            proj_tm = jnp.moveaxis(proj, 1, 0)

            def body(h, x_t):
                out, h2 = cell.step(cp, x_t, h)
                return h2, out

            _, outs = lax.scan(body, h0, proj_tm)
            return jnp.moveaxis(outs, 0, 1)

        k1 = k2 = None
        if rng is not None:
            k1, k2 = jax.random.split(rng)
        out_f = run(fwd_cell, params[0], input, k1)
        out_b = run(bwd_cell, params[1], jnp.flip(input, axis=1), k2)
        out_b = jnp.flip(out_b, axis=1)
        if self.merge == "add":
            return out_f + out_b, state
        return jnp.concatenate([out_f, out_b], axis=-1), state


class TimeDistributed(Container):
    """Apply the wrapped layer independently at every timestep
    (reference ``nn/TimeDistributed.scala:40``): fold T into the batch so the
    inner layer sees one ``(B*T, ...)`` mega-batch — exactly the large-batch
    shape the MXU wants."""

    def __init__(self, layer: Optional[Module] = None, name=None):
        super().__init__(name)
        if layer is not None:
            self.add(layer)

    def apply(self, params, input, state, training=False, rng=None):
        B, T = input.shape[0], input.shape[1]
        flat = input.reshape((B * T,) + input.shape[2:])
        with child_scope(self.children[0], 0):
            out, new_state = self.children[0].apply(
                params[0], flat, state[0], training=training, rng=rng)
        return out.reshape((B, T) + out.shape[1:]), [new_state]


class BinaryTreeLSTM(Module):
    """Binary constituency TreeLSTM (reference ``nn/BinaryTreeLSTM.scala:36``).

    TPU-native formulation: instead of Scala-side recursion over a tree object,
    the tree is data — input is ``[embeddings, tree]`` where

    - ``embeddings``: ``(B, n_leaves, D)`` leaf word vectors;
    - ``tree``: ``(B, n_nodes, 2)`` int32 child indices in *topological order*
      (children precede parents).  Node ``i < n_leaves`` is leaf ``i``; index
      ``-1`` marks an unused child slot.  Padded trees (rows of ``-1``) are
      skipped by masking.

    The recursion becomes a ``lax.scan`` over the node list with gathers into
    the growing (h, c) buffers — compiler-friendly, fixed shapes.
    """

    def __init__(self, input_size: int, hidden_size: int, name=None):
        super().__init__(name)
        self.input_size = input_size
        self.hidden_size = hidden_size

    def _init_params(self, rng):
        ks = jax.random.split(rng, 4)
        D, H = self.input_size, self.hidden_size
        stdv = 1.0 / math.sqrt(H)
        return {
            # leaf transform
            "w_leaf": _uniform(ks[0], (D, 3 * H), stdv),   # i, o, u
            "b_leaf": _uniform(ks[1], (3 * H,), stdv),
            # composer: [h_l, h_r] -> i, f_l, f_r, o, u
            "w_comp": _uniform(ks[2], (2 * H, 5 * H), stdv),
            "b_comp": _uniform(ks[3], (5 * H,), stdv),
        }

    def apply(self, params, input, state, training=False, rng=None):
        emb, tree = input[0], input[1]
        B, L, D = emb.shape
        N = L + tree.shape[1]
        H = self.hidden_size

        # leaves: fused (B, L, 3H) projection
        z = emb @ params["w_leaf"] + params["b_leaf"]
        i = jax.nn.sigmoid(z[..., 0:H])
        o = jax.nn.sigmoid(z[..., H:2 * H])
        u = jnp.tanh(z[..., 2 * H:3 * H])
        c_leaf = i * u
        h_leaf = o * jnp.tanh(c_leaf)

        h_buf = jnp.concatenate(
            [h_leaf, jnp.zeros((B, tree.shape[1], H), dtype=h_leaf.dtype)], 1)
        c_buf = jnp.concatenate(
            [c_leaf, jnp.zeros((B, tree.shape[1], H), dtype=c_leaf.dtype)], 1)

        def body(carry, node):
            h_buf, c_buf, idx = carry
            l, r = node[:, 0], node[:, 1]
            valid = (l >= 0) & (r >= 0)
            li = jnp.maximum(l, 0)
            ri = jnp.maximum(r, 0)
            hl = jnp.take_along_axis(h_buf, li[:, None, None].repeat(H, 2), 1)[:, 0]
            hr = jnp.take_along_axis(h_buf, ri[:, None, None].repeat(H, 2), 1)[:, 0]
            cl = jnp.take_along_axis(c_buf, li[:, None, None].repeat(H, 2), 1)[:, 0]
            cr = jnp.take_along_axis(c_buf, ri[:, None, None].repeat(H, 2), 1)[:, 0]
            zc = jnp.concatenate([hl, hr], -1) @ params["w_comp"] + params["b_comp"]
            ig = jax.nn.sigmoid(zc[:, 0:H])
            fl = jax.nn.sigmoid(zc[:, H:2 * H])
            fr = jax.nn.sigmoid(zc[:, 2 * H:3 * H])
            og = jax.nn.sigmoid(zc[:, 3 * H:4 * H])
            ug = jnp.tanh(zc[:, 4 * H:5 * H])
            c_new = ig * ug + fl * cl + fr * cr
            h_new = og * jnp.tanh(c_new)
            mask = valid[:, None].astype(h_new.dtype)
            h_new = h_new * mask
            c_new = c_new * mask
            onehot = jax.nn.one_hot(idx, N, dtype=h_buf.dtype)[None, :, None]
            h_buf = h_buf * (1 - onehot) + h_new[:, None, :] * onehot
            c_buf = c_buf * (1 - onehot) + c_new[:, None, :] * onehot
            return (h_buf, c_buf, idx + 1), h_new

        (h_buf, _, _), node_h = lax.scan(
            body, (h_buf, c_buf, jnp.int32(L)), jnp.moveaxis(tree, 1, 0))
        # (B, n_internal, H) internal-node hiddens in topological order
        return jnp.moveaxis(node_h, 0, 1), state


TreeLSTM = BinaryTreeLSTM
