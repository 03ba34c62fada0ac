"""Role: train an image classifier on one chip through
``Optimizer.create(...).optimize()`` from resident batches.  Set up,
check, measure, report."""

from __future__ import annotations

from typing import Any, Dict

from benchmark import cells, checks, ops, training
from benchmark.builders.common import seed_key
from benchmark.traffic import generate


def run(ctx) -> Dict[str, Any]:
    import jax

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    cfg, cell = ctx.config, ctx.cell
    mix, job = cell["mix"], cell["job"]
    batch = int(mix["batch"])

    # -- set up ----------------------------------------------------------
    builder = cells.builder(cfg["builder"])
    k_model, k_data = jax.random.split(seed_key(ctx.seed))
    model = builder.build(cfg, k_model, on_tpu=ctx.on_tpu)
    xs, ys = generate.image_batches(
        mix, int(cfg["image_size"]), int(cfg["image_channels"]),
        int(cfg["num_classes"]), k_data)
    served = []

    class Resident(MiniBatch):
        """A batch that lives on the device and notes when it is read."""

        def __init__(self, tag, x, y):
            super().__init__([x], [y])
            self.tag = tag

        def get_input(self):
            served.append(self.tag)
            return super().get_input()

    records = [Resident(i, xs[i], ys[i]) for i in range(xs.shape[0])]
    ctx.say(f"model {builder.describe(cfg, model)}; {len(records)} resident "
            f"batches of {batch}")

    # -- check, before optimize() ----------------------------------------
    verdict = checks.image_forward_against_reference(
        ctx, model, xs[0][:int(job["check_images"])])
    ref_losses = checks.image_reference_losses(ctx, model, xs, ys)

    # -- measure ---------------------------------------------------------
    opt = optim.Optimizer.create(model, LocalDataSet(records),
                                 nn.ClassNLLCriterion())
    window = training.Window(ctx.seconds, int(job["warm_steps"]))
    measured = training.measure(ctx, opt, window)

    # -- report ----------------------------------------------------------
    first_ref = float(ref_losses[served[0]]) if served else None
    flops = ops.resnet50_train_flops_per_image(
        int(cfg["image_size"]), int(cfg["num_classes"])) * batch
    return training.report(ctx, opt, window, measured, verdict, first_ref,
                           flops, batch, "images")
