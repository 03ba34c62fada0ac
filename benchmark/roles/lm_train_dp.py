"""Role: train the decoder LM data-parallel over the cell's chips through
``DistriOptimizer`` (replicated parameters, ZeRO-1 slot shards, gradient
reduce-scatter and parameter all-gather).  The steps are ``lm_train``'s;
this file brings the optimizer and the sharded data set."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark.roles import lm_train


def make_optimizer(ctx, model, samples):
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.parallel import DistriOptimizer
    n = int(ctx.cell["chips"])
    ds = ShardedDataSet(samples, n).transform(
        SampleToMiniBatch(int(ctx.cell["mix"]["batch"]) * n, n))
    mesh = Engine.create_mesh((n,), ("data",), devices=jax.devices()[:n])
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    return DistriOptimizer(model, ds, crit, mesh=mesh), ds


def _first_of(index: np.ndarray, n: int, per_chip: int) -> np.ndarray:
    """Partition p streams the p-th contiguous slice of the one global
    index; the first global batch is the head of every slice."""
    per = len(index) // n
    return np.concatenate([index[p * per:p * per + per_chip]
                           for p in range(n)])


def first_batch(ctx, ds) -> np.ndarray:
    return _first_of(np.array(ds.index), int(ctx.cell["chips"]),
                     int(ctx.cell["mix"]["batch"]))


def predict_first_batch(ctx, n_samples: int) -> np.ndarray:
    """The sharded shuffle is a pure function of (seed, round): a probe
    data set shuffled once gives the run's first epoch."""
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.utils.random_generator import RandomGenerator
    n = int(ctx.cell["chips"])
    RandomGenerator.RNG().set_seed(ctx.seed % 2 ** 32)
    probe = ShardedDataSet(list(range(n_samples)), n)
    probe.shuffle()
    return _first_of(np.array(probe.index), n,
                     int(ctx.cell["mix"]["batch"]))


def run(ctx) -> Dict[str, Any]:
    return lm_train.run(ctx, make_optimizer, first_batch,
                        predict_first_batch)
