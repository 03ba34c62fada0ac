"""Role: train a decoder LM on one chip through
``Optimizer.create(...).optimize()``.  Set up, check, measure, report.

The role is a kind of run, not a family: the configuration's builder
answers what belongs to the model (``build``, ``describe``,
``train_flops_per_token``, ``attention_kernel``), its reference what the
model should say, and the job names the optimizer
(``training.optim_method``)."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark import cells, checks, training
from benchmark.builders.common import seed_key
from benchmark.traffic import generate


def make_optimizer(ctx, model, samples):
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
    ds = LocalDataSet(samples).transform(
        SampleToMiniBatch(int(ctx.cell["mix"]["batch"])))
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    return optim.Optimizer.create(model, ds, crit), ds


def first_batch(ctx, ds) -> np.ndarray:
    """The samples of the first batch, read off the shuffled index when the
    driver first asks the end trigger (before a second shuffle can have
    happened)."""
    return np.array(ds.index[:int(ctx.cell["mix"]["batch"])])


def predict_first_batch(ctx, n_samples: int) -> np.ndarray:
    """Seeds the program's shuffle stream from ``--seed`` and says which
    samples the first batch will hold, with the program's own shuffle on a
    probe.  Only these are run through the reference for the first-loss
    check; ``first_batch`` confirms the prediction afterwards."""
    from bigdl_tpu.dataset import LocalDataSet
    from bigdl_tpu.utils.random_generator import RandomGenerator
    seed = ctx.seed % 2 ** 32
    RandomGenerator.RNG().set_seed(seed)
    probe = LocalDataSet(list(range(n_samples)))
    probe.shuffle(RandomGenerator(seed))
    return np.array(probe.index[:int(ctx.cell["mix"]["batch"])])


def flops_per_step(ctx, global_batch: int) -> float:
    seq_len = int(ctx.cell["mix"]["seq_len"])
    per_token = cells.builder(ctx.config["builder"]).train_flops_per_token(
        ctx.config, seq_len)
    return per_token * global_batch * seq_len


def run(ctx, make_opt=make_optimizer, first_batch=first_batch,
        predict_first_batch=predict_first_batch) -> Dict[str, Any]:
    from bigdl_tpu.dataset import Sample
    cfg, cell = ctx.config, ctx.cell
    mix, job = cell["mix"], cell["job"]
    chips = int(cell["chips"])
    global_batch = int(mix["batch"]) * chips

    # -- set up ----------------------------------------------------------
    builder = cells.builder(cfg["builder"])
    model = builder.build(cfg, seed_key(ctx.seed), on_tpu=ctx.on_tpu)
    n_seq = int(mix["n_batches"]) * global_batch
    toks = generate.token_sequences(mix, int(cfg["vocab_size"]), ctx.seed,
                                    n_seq)
    samples = [Sample(t[:-1], t[1:]) for t in toks]
    ctx.say(f"model {builder.describe(cfg, model)}; {n_seq} sequences of "
            f"{mix['seq_len']} tokens")

    # -- check, before optimize() ----------------------------------------
    verdict = checks.lm_forward_against_reference(
        ctx, model, toks[:int(job.get("check_sequences", 1))])
    # the reference's loss on the batch the trainer will see first
    predicted = predict_first_batch(ctx, n_seq)
    first_ref = float(np.mean(checks.lm_reference_losses(
        ctx, model, toks[predicted])))

    # -- measure ---------------------------------------------------------
    opt, ds = make_opt(ctx, model, samples)
    first = {}
    window = training.Window(
        ctx.seconds, int(job["warm_steps"]),
        on_first_call=lambda: first.update(seen=first_batch(ctx, ds)))
    measured = training.measure(ctx, opt, window)

    # -- report ----------------------------------------------------------
    record = training.report(
        ctx, opt, window, measured, verdict, first_ref,
        flops_per_step(ctx, global_batch),
        global_batch * int(mix["seq_len"]), "tokens", chips)
    record["checks"]["first_batch_as_predicted"] = (
        "seen" in first and sorted(first["seen"]) == sorted(predicted))
    kernel = builder.attention_kernel(cfg, mix)
    if kernel is not None:
        record["flash"] = kernel
    return record
