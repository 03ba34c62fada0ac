"""Model-zoo performance harness.

Reference equivalents: ``models/utils/LocalOptimizerPerf.scala`` and
``DistriOptimizerPerf.scala:82-140`` — synthetic-input training-throughput
benchmarks over the zoo, reporting the driver-log ``Throughput is N
records/second`` protocol.

Run::

    python -m bigdl_tpu.models.perf -m alexnet -b 64 -i 20
    python -m bigdl_tpu.models.perf -m resnet50 --partitions 8   # mesh DP
    python -m bigdl_tpu.models.perf -m resnet50 --layout nchw    # layout A/B

The throughput it prints is the host's: what the chip reaches is measured
by ``benchmark/run.py`` (``PERF.md``).
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset import Sample
from bigdl_tpu.dataset.dataset import DataSet
from bigdl_tpu.dataset.transformer import SampleToMiniBatch
from bigdl_tpu.models import driver_utils

# model name -> (builder(layout), input CHW shape, classes)  — the reference
# harness's inputShape table (DistriOptimizerPerf.scala:100-120)
_MODELS = {
    "lenet5": (lambda layout: _logits_free("lenet", layout), (28, 28), 10),
    "alexnet": (lambda layout: _zoo("alexnet_owt", layout), (3, 224, 224), 1000),
    "vgg16": (lambda layout: _zoo("vgg16", layout), (3, 224, 224), 1000),
    "vgg19": (lambda layout: _zoo("vgg19", layout), (3, 224, 224), 1000),
    "inception_v1": (lambda layout: _zoo("inception_v1_no_aux_classifier",
                                         layout), (3, 224, 224), 1000),
    "resnet50": (lambda layout: _resnet50(layout), (3, 224, 224), 1000),
    # token LM: (T,) int features, per-timestep targets (beyond-reference)
    "transformer": (lambda layout: _transformer(), (128,), 1024),
}


def _zoo(name, layout="NHWC"):
    # zoo builders already end in LogSoftMax; only resnet emits raw logits
    import bigdl_tpu.models as models
    return getattr(models, name)(layout=layout)


def _logits_free(name, layout="NHWC"):
    from bigdl_tpu.models.lenet import lenet5
    return lenet5(10, layout=layout)


def _resnet50(layout="NHWC"):
    from bigdl_tpu.models.resnet import resnet, model_init, DatasetType
    m = model_init(resnet(1000, depth=50, dataset=DatasetType.IMAGENET,
                          layout=layout))
    m.add(nn.LogSoftMax())
    return m


def _transformer():
    from bigdl_tpu.models.transformer import transformer_lm
    return transformer_lm(1024, d_model=256, n_head=8, n_layers=4,
                          max_len=128)


def main(argv=None):
    p = argparse.ArgumentParser(description="zoo throughput harness")
    p.add_argument("-m", "--model", choices=sorted(_MODELS), default="lenet5")
    p.add_argument("-b", "--batch-size", type=int, default=64)
    p.add_argument("-i", "--iterations", type=int, default=20)
    p.add_argument("--partitions", type=int, default=1,
                   help=">1: DistriOptimizer over the device mesh")
    p.add_argument("--precision", choices=["fp32", "bf16"], default="fp32",
                   help="fused-step compute precision (fp32 matches the "
                        "reference harness; bf16 is the TPU-first mode "
                        "the benchmark's training cell runs)")
    p.add_argument("--layout", choices=["nhwc", "nchw"], default="nhwc",
                   help="convnet compute layout: nhwc = channels-last "
                        "trunk (TPU-native default), nchw = the classic "
                        "Torch layout, for before/after A-B runs")
    args = p.parse_args(argv)
    driver_utils.init_logging()

    build, shape, classes = _MODELS[args.model]
    model = build(args.layout.upper())
    rng = np.random.RandomState(0)

    n_records = max(args.batch_size * 2, args.partitions * 2)
    if args.model == "transformer":
        records = [Sample(rng.randint(1, classes + 1, shape)
                          .astype(np.float32),
                          rng.randint(1, classes + 1, shape)
                          .astype(np.float32))
                   for _ in range(n_records)]
        criterion = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                                size_average=True)
    else:
        records = [Sample(rng.uniform(-1, 1, size=shape).astype(np.float32),
                          np.float32(rng.randint(1, classes + 1)))
                   for _ in range(n_records)]
        criterion = nn.ClassNLLCriterion()
    ds = DataSet.array(records, args.partitions).transform(
        SampleToMiniBatch(args.batch_size, max(1, args.partitions)))

    opt = optim.Optimizer.create(model, ds, criterion)
    opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
    if args.precision == "bf16":
        opt.set_precision("bf16")
    # warm-up run absorbs the jit compile; the timed run is steady-state
    # (the reference harness likewise reports per-iteration throughput,
    # DistriOptimizerPerf.scala:130-140)
    import time
    opt.set_end_when(optim.max_iteration(2))
    opt.optimize()
    t0 = time.time()
    opt.set_end_when(optim.max_iteration(args.iterations + 2))
    opt.optimize()
    dt = time.time() - t0
    print(f"[{args.model}] steady-state throughput "
          f"{args.batch_size * args.iterations / dt:.1f} records/second "
          f"({dt / args.iterations * 1e3:.1f} ms/iteration, batch "
          f"{args.batch_size})")
    return opt


if __name__ == "__main__":
    main()
