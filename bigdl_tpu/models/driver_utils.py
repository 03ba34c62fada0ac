"""Shared CLI plumbing for the model-zoo Train/Test drivers.

Reference equivalent: the per-model scopt ``OptionParser`` param objects
(``models/lenet/Utils.scala``, ``models/resnet/Train.scala:35-60``) — folder,
batch size, snapshot/resume, checkpoint, learning-rate, max-epoch flags —
plus the driver bootstrap every Train main performs (LoggerFilter + Engine
init).

TPU-native additions: ``--partitions`` selects the distributed trainer over
the device mesh, ``--log-dir`` wires TensorBoard summaries, and
``--synthetic`` substitutes generated records so every driver runs (and is
testable) without the real dataset on disk.
"""

from __future__ import annotations

import argparse
import logging
import os
from typing import Callable, List, Optional

from bigdl_tpu.dataset.dataset import DataSet
from bigdl_tpu.dataset.transformer import SampleToMiniBatch


def base_parser(description: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=description)
    p.add_argument("-f", "--folder", default="./",
                   help="dataset folder (reference -f)")
    p.add_argument("-b", "--batch-size", type=int, default=None,
                   help="global mini-batch size (reference -b)")
    p.add_argument("-e", "--max-epoch", type=int, default=None,
                   help="epochs to train (reference -e)")
    p.add_argument("-i", "--max-iteration", type=int, default=None,
                   help="iterations to train (overrides --max-epoch)")
    p.add_argument("-r", "--learning-rate", type=float, default=None,
                   help="learning rate (reference --learningRate)")
    p.add_argument("--model", default=None,
                   help="model snapshot to resume from (reference --model)")
    p.add_argument("--state", default=None,
                   help="optim-method snapshot to resume from "
                        "(reference --state)")
    p.add_argument("--checkpoint", default=None,
                   help="where to write model.N/optimMethod.N snapshots")
    p.add_argument("--overwrite", action="store_true",
                   help="overwrite existing checkpoint files")
    p.add_argument("--ckpt-keep-last", type=int, default=None,
                   metavar="N",
                   help="retain only the N newest committed snapshots "
                        "(default bigdl.checkpoint.keepLast; 0 keeps all)")
    p.add_argument("--ckpt-async", action="store_true",
                   help="write snapshots on a background thread (the "
                        "train step blocks only for capture; writer "
                        "errors surface at the next save and at exit)")
    p.add_argument("--partitions", type=int, default=1,
                   help="data-parallel partitions; >1 trains with the "
                        "DistriOptimizer over the device mesh")
    p.add_argument("--log-dir", default=None,
                   help="TensorBoard summary directory")
    p.add_argument("--app-name", default=None,
                   help="TensorBoard app name (defaults to the driver name)")
    p.add_argument("--synthetic", type=int, default=0, metavar="N",
                   help="train on N synthetic records instead of --folder")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of 3 steady-state "
                        "iterations (from iteration 10) into this "
                        "directory — open with TensorBoard's profile "
                        "plugin or Perfetto")
    return p


def init_logging() -> None:
    """Driver bootstrap, called at the top of every Train main: console
    + ``bigdl.log`` via LoggerFilter (the reference calls
    ``LoggerFilter.redirectSparkInfoLogs`` there), and the placement of
    XLA's persistent compilation cache
    (:func:`bigdl_tpu.engine.init_compile_cache`).  The platform is
    JAX's to choose: an N-device CPU mesh is requested with
    ``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    from bigdl_tpu.engine import init_compile_cache
    init_compile_cache()
    from bigdl_tpu.utils.logger_filter import redirect_spark_info_logs
    redirect_spark_info_logs()


def load_snapshots(args, build_model: Callable, build_optim: Callable):
    """--model/--state resume protocol (reference Train.scala:48-60)."""
    from bigdl_tpu.utils import file_io
    from bigdl_tpu.optim.optim_method import OptimMethod

    model = file_io.load(args.model) if args.model else build_model()
    optim_method = (OptimMethod.load(args.state) if args.state
                    else build_optim())
    return model, optim_method


def make_dataset(records: List, args, batch_size: int):
    """DataSet.array sharded by --partitions + SampleToMiniBatch with the
    reference's global-batch/partition division."""
    ds = DataSet.array(records, args.partitions)
    return ds.transform(SampleToMiniBatch(batch_size, max(1, args.partitions)))


def configure(opt, args, default_epochs: int, app_name: str):
    """Apply end trigger, checkpoint, and summaries from common flags."""
    import bigdl_tpu.optim as optim

    if args.max_iteration:
        opt.set_end_when(optim.max_iteration(args.max_iteration))
    else:
        opt.set_end_when(optim.max_epoch(args.max_epoch or default_epochs))
    if args.checkpoint:
        opt.set_checkpoint(args.checkpoint, optim.every_epoch(),
                           isOverwrite=args.overwrite,
                           keep_last=getattr(args, "ckpt_keep_last", None),
                           async_write=(True if getattr(args, "ckpt_async",
                                                        False) else None))
    if args.log_dir:
        from bigdl_tpu.visualization import TrainSummary, ValidationSummary
        name = args.app_name or app_name
        opt.set_train_summary(TrainSummary(args.log_dir, name))
        opt.set_validation_summary(ValidationSummary(args.log_dir, name))
    if getattr(args, "profile_dir", None):
        opt.set_trace_profile(args.profile_dir)
    return opt
