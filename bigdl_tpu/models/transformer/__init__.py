"""Decoder-only transformer language model.

No reference equivalent (the reference has no attention op at all,
SURVEY §5.7) — this is the flagship long-context model family: causal
MultiHeadAttention blocks with pre-norm residuals, trainable on a
``("data", "seq")`` mesh where attention runs as a ppermute ring
(``bigdl_tpu/parallel/ring_attention.py``) and optionally with
Megatron-split MLPs (``parallel/tensor_parallel.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import Container, Module, _child_rng


class PositionOutOfRange(ValueError):
    """A position past the sinusoidal table's capacity.  Structured —
    names the offending position and the limit — because the silent
    alternatives are worse: a short slice broadcasts into a confusing
    shape error and ``dynamic_slice`` silently CLAMPS, feeding wrong
    position signal with no symptom at all."""

    def __init__(self, position: int, max_len: int):
        self.position = int(position)
        self.max_len = int(max_len)
        super().__init__(
            f"position {self.position} is out of range for a "
            f"PositionalEncoding table of max_len {self.max_len} — build "
            f"the model with max_len > {self.position} or truncate the "
            "sequence")


class PositionalEncoding(Module):
    """Sinusoidal position signal added to (B, T, D) embeddings.

    Position-dependent, so under sequence parallelism each time shard must
    offset into the table by its chunk start: the trainer wires
    ``set_sequence_parallel`` (duck-typed, like MultiHeadAttention's ring
    path) and the offset engages only while the seq axis is bound.

    ``apply(..., offset=k)`` reads table rows ``k .. k+T`` instead of
    ``0 .. T`` — the decode path hands a sequence's resume position here.
    Out-of-range static positions (``T > max_len``, or ``offset + T >
    max_len``) raise :class:`PositionOutOfRange`; traced offsets (the
    sequence-parallel shard index) stay the caller's contract, as
    before."""

    def __init__(self, d_model: int, max_len: int = 4096, name=None):
        super().__init__(name)
        pos = np.arange(max_len)[:, None]
        div = np.exp(np.arange(0, d_model, 2) * (-math.log(10000.0) / d_model))
        pe = np.zeros((max_len, d_model), np.float32)
        pe[:, 0::2] = np.sin(pos * div)
        pe[:, 1::2] = np.cos(pos * div[: d_model // 2])
        # a HOST constant: every step that closes over the table embeds
        # it at lowering, and a device array would be pulled back to the
        # host for that each time (an implicit device→host transfer the
        # host-sync guard rightly reports)
        self.pe = pe
        self.sequence_parallel = None

    @property
    def max_seq_len(self) -> int:
        """Table capacity — the sp trainer validates global T against this
        (dynamic_slice would silently clamp out-of-range shard offsets)."""
        return int(self.pe.shape[0])

    def set_sequence_parallel(self, axis_name) -> "PositionalEncoding":
        self.sequence_parallel = axis_name
        self._jit_apply = None
        return self

    def rows(self, positions) -> jnp.ndarray:
        """Table rows for explicit positions — the decode step's
        per-sequence position lookup (each decode slot sits at its own
        offset).  Concrete (host) positions are range-checked; traced
        positions were validated against :attr:`max_seq_len` by the
        caller's admission path (``jnp.take`` would silently clip)."""
        if isinstance(positions, (int, np.integer, list, tuple,
                                  np.ndarray)):
            pos = np.asarray(positions)
            if pos.size and int(pos.max()) >= self.max_seq_len:
                raise PositionOutOfRange(int(pos.max()), self.max_seq_len)
        return jnp.take(self.pe, jnp.asarray(positions), axis=0)

    def apply(self, params, input, state, training=False, rng=None,
              offset: int = 0):
        from bigdl_tpu.nn.attention import _axis_bound
        t = input.shape[1]
        if self.sequence_parallel and _axis_bound(self.sequence_parallel):
            start = jax.lax.axis_index(self.sequence_parallel) * t
            if offset:
                start = start + offset
            pe = jax.lax.dynamic_slice_in_dim(self.pe, start, t, 0)
        else:
            if offset + t > self.max_seq_len:
                raise PositionOutOfRange(offset + t - 1, self.max_seq_len)
            pe = self.pe[offset:offset + t]
        return input + pe[None].astype(input.dtype), state


class LayerNorm(Module):
    """Feature-axis layer normalization (pre-norm transformer blocks;
    time-pointwise, so it composes with sequence parallelism)."""

    def __init__(self, d_model: int, eps: float = 1e-5, name=None):
        super().__init__(name)
        self.d_model = d_model
        self.eps = eps

    def _init_params(self, rng):
        return {"weight": jnp.ones((self.d_model,)),
                "bias": jnp.zeros((self.d_model,))}

    def apply(self, params, input, state, training=False, rng=None):
        mean = jnp.mean(input, axis=-1, keepdims=True)
        var = jnp.var(input, axis=-1, keepdims=True)
        out = (input - mean) * jax.lax.rsqrt(var + self.eps)
        return out * params["weight"] + params["bias"], state


class _Residual(Container):
    """x + inner(norm(x)) — pre-norm residual.

    A real Container (children = [norm, inner]) so child param views stay
    adopted: sublayer ``.forward()``, ``get_parameters_table()``, and the
    TrainSummary "Parameters" histogram walk all see the trained weights,
    and tp_specs/sequence-parallel wiring recurse naturally."""

    def __init__(self, d_model: int, inner: Module, name=None,
                 scopes=("norm", "inner")):
        super().__init__(name)
        #: the named scopes of the two children (``ln1``/``attn``,
        #: ``ln2``/``ffn`` in a decoder block).  Scopes, not module names:
        #: they repeat in every block, and names key the parameter table
        self.scopes = tuple(scopes)
        self.add(LayerNorm(d_model)).add(inner)

    def scope_name(self, index: int):
        """None: the two children's scopes stand directly under the
        block's (``layer0/ln1``, not ``layer0/0__Residual/ln1``)."""
        return None

    def apply(self, params, input, state, training=False, rng=None):
        norm, inner = self.children
        with jax.named_scope(self.scopes[0]):
            h, _ = norm.apply(params[0], input, state[0], training=training)
        with jax.named_scope(self.scopes[1]):
            h, new_inner = inner.apply(params[1], h, state[1],
                                       training=training,
                                       rng=_child_rng(rng, 1))
        return input + h, [state[0], new_inner]


def transformer_block(d_model: int, n_head: int, ff_mult: int = 4,
                      tp: bool = False,
                      moe_experts: int = 0,
                      moe_capacity_factor: float = 1.25,
                      moe_top_k: int = 1) -> nn.Sequential:
    """One pre-norm decoder block: causal MHA + MLP, both residual.

    ``tp=True`` tags the MLP pair column/row for the Megatron split
    (``parallel.tp_specs`` then shards it over the ``model`` axis; the
    MHA head split applies automatically).  ``moe_experts=E`` replaces the
    dense MLP with a Switch :class:`~bigdl_tpu.nn.MixtureOfExperts` of E
    expert MLPs (expert-parallel over an ``expert`` axis via
    ``parallel.expert_parallel``); ``moe_capacity_factor`` /
    ``moe_top_k`` pass through (capacity_factor >= E/top_k makes routing
    drop-free and thus microbatch-invariant — see the MoE class
    docstring)."""
    from bigdl_tpu.parallel.tensor_parallel import (column_parallel,
                                                    row_parallel)
    if moe_experts:
        if tp:
            raise ValueError("pick one of tp / moe_experts per block")
        expert = (nn.Sequential()
                  .add(nn.Linear(d_model, ff_mult * d_model))
                  .add(nn.ReLU())
                  .add(nn.Linear(ff_mult * d_model, d_model)))
        ffn = nn.MixtureOfExperts(d_model, expert, moe_experts,
                                  capacity_factor=moe_capacity_factor,
                                  top_k=moe_top_k)
    else:
        up = nn.Linear(d_model, ff_mult * d_model)
        down = nn.Linear(ff_mult * d_model, d_model)
        if tp:
            column_parallel(up)
            row_parallel(down)
        ffn = nn.Sequential().add(up).add(nn.ReLU()).add(down)
    return (nn.Sequential()
            .add(_Residual(d_model,
                           nn.MultiHeadAttention(d_model, n_head,
                                                 causal=True),
                           scopes=("ln1", "attn")))
            .add(_Residual(d_model, ffn, scopes=("ln2", "ffn"))))


def _default_remat(remat):
    """Resolve a builder's ``remat`` argument against the
    ``bigdl.remat.policy`` config preset: an explicit argument wins; with
    the default (``False``) the preset applies — ``"nothing"`` (save
    nothing per block), ``"dots"``, ``"save_attn"`` (:class:`nn.Remat`'s
    vocabulary, where a typo fails at construction), ``None``/``"off"``
    keeps remat off.  This is what lets the MFU bench A/B remat policies
    against collective overlap without threading a new argument through
    every model builder."""
    if remat is not False:
        return remat
    from bigdl_tpu.utils import config
    v = config.get_property("bigdl.remat.policy", None)
    if v in (None, False, ""):
        return False
    v = str(v).lower()
    if v in ("none", "off", "false"):
        return False
    if v in ("nothing", "true"):
        return True
    return v


def transformer_lm(vocab_size: int, d_model: int = 128, n_head: int = 4,
                   n_layers: int = 2, max_len: int = 4096,
                   tp: bool = False, moe_experts: int = 0,
                   moe_top_k: int = 1, remat=False) -> nn.Sequential:
    """Token ids (B, T), 1-based -> log-probs (B, T, vocab).

    ``moe_experts=E`` makes every block's FFN a MoE (train on a
    ``("data", "expert")`` mesh for expert parallelism — the driver's
    ``--expert-parallel``); ``moe_top_k`` selects the routing: 1 = Switch,
    2 = the GShard configuration (driver ``--moe-top-k``).  ``tp=True``
    tags Megatron splits (train on a ``("data", "model")`` mesh —
    ``--tensor-parallel``).  ``remat`` wraps every decoder block in
    :class:`~bigdl_tpu.nn.Remat` activation checkpointing — ``True`` saves
    nothing per block, ``"dots"`` saves matmul outputs, ``"save_attn"``
    saves only the tagged attention context (driver ``--remat``);
    identical numerics, O(layers) less activation memory.  When the
    argument is left at its default, the ``bigdl.remat.policy`` config
    preset applies (see :func:`_default_remat`)."""
    remat = _default_remat(remat)
    m = (nn.Sequential()
         .add(nn.LookupTable(vocab_size, d_model, name="embed"))
         .add(PositionalEncoding(d_model, max_len, name="pos")))
    for i in range(n_layers):
        block = transformer_block(d_model, n_head, tp=tp,
                                  moe_experts=moe_experts,
                                  moe_top_k=moe_top_k).set_name(f"layer{i}")
        if remat:
            block = nn.Remat(block,
                             policy=None if remat is True else remat)
        m.add(block)
    m.add(LayerNorm(d_model, name="ln_f"))
    m.add(nn.Linear(d_model, vocab_size, name="head"))
    m.add(nn.LogSoftMax(name="log_softmax"))
    return m


def transformer_lm_pipeline(vocab_size: int, d_model: int = 128,
                            n_head: int = 4, n_layers: int = 2,
                            max_len: int = 4096, moe_experts: int = 0,
                            moe_top_k: int = 1, remat=False,
                            tp: bool = False):
    """``(embed, blocks, head)`` for
    :class:`~bigdl_tpu.parallel.pipeline.PipelineOptimizer`: the embedding
    and LM head run replicated, the ``n_layers`` homogeneous decoder
    blocks pipeline over a ``stage`` mesh axis (one block per stage
    device — the driver's ``--pipeline``).  ``moe_experts=E`` gives every
    block a Switch-MoE FFN; the pipeline trainer folds the collected
    ``aux_loss`` into its objective (``pipeline_apply(return_aux=True)``).
    ``tp=True`` Megatron-tags each block for the 3-D
    ``('data','stage','model')`` composition (driver
    ``--pipeline --tensor-parallel``)."""
    remat = _default_remat(remat)
    embed = (nn.Sequential()
             .add(nn.LookupTable(vocab_size, d_model, name="embed"))
             .add(PositionalEncoding(d_model, max_len, name="pos")))
    blocks = [transformer_block(d_model, n_head, moe_experts=moe_experts,
                                moe_top_k=moe_top_k,
                                tp=tp).set_name(f"layer{i}")
              for i in range(n_layers)]
    if remat:
        policy = None if remat is True else remat
        blocks = [nn.Remat(b, policy=policy) for b in blocks]
    head = (nn.Sequential()
            .add(LayerNorm(d_model, name="ln_f"))
            .add(nn.Linear(d_model, vocab_size, name="head"))
            .add(nn.LogSoftMax(name="log_softmax")))
    return embed, blocks, head
