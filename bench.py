"""Single-chip training-throughput benchmark.

Mirrors the reference's perf protocol: synthetic-input model-zoo throughput
(``models/utils/LocalOptimizerPerf.scala:82-140``) reported as the driver
log's ``Throughput is N records/second`` line
(``optim/DistriOptimizer.scala:293-297``).

Headline metric: ResNet-50/ImageNet training images/sec on one chip via the
production fused train step (forward + loss + backward + SGD update in one
jit).  Prints ONE JSON line on stdout; per-model details go to stderr.

``vs_baseline``: the reference publishes no numbers (BASELINE.json
``published: {}``), so the baseline is self-measured and pinned in
``bench_baseline.json`` at the repo root — the first measured round wrote it;
later rounds regress against it.  Without that file, vs_baseline = 1.0.
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def build_train_step(model, criterion, optim_method, hyper, module=None,
                     precision=None):
    """The production fused step — identical shape to
    LocalOptimizer._build_step: forward (at the requested precision) + loss
    (+ regularizers) + backward + the OptimMethod's pure update, one jit."""
    import jax
    from bigdl_tpu.optim.optimizer import (mixed_precision_forward,
                                           regularization_penalty)

    reg_module = module if module is not None else model

    def step(params, slots, mstate, inputs, targets):
        def loss_fn(p):
            out, new_mstate = mixed_precision_forward(
                model, p, inputs, mstate, precision, True, None)
            loss = criterion.apply(out, targets)
            loss = loss + regularization_penalty(reg_module, p)
            return loss, new_mstate

        (loss, new_mstate), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        new_params, new_slots = optim_method.pure_update(grads, params, slots,
                                                         hyper)
        return new_params, new_slots, new_mstate, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))


def bench_model(model, batch, input_shape, n_classes, steps=10, warmup=3,
                flops_per_image=None, logits=False, precision=None,
                criterion=None, make_batch=None):
    """Measure the fused-train-step throughput of ``model``.

    ``make_batch(rng, batch) -> (x, y)`` overrides the default
    image-classification batch (token LMs etc.); ``criterion`` overrides
    ClassNLL.  One measurement protocol for every benched model — the
    donated-carry sync subtleties live only here."""
    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn

    from bigdl_tpu.optim import SGD

    model.training()
    model._ensure_init()
    criterion = criterion or nn.ClassNLLCriterion()
    # momentum SGD: the reference zoo's training configuration
    method = SGD(learning_rate=0.01, momentum=0.9)
    # ClassNLLCriterion expects log-probabilities; builders that end in bare
    # Linear logits (imagenet variants) get a LogSoftMax appended in-step.
    target = _WithLogSoftMax(model, nn.LogSoftMax()) if logits else model
    step_fn = build_train_step(target, criterion, method, method.hyper(),
                               module=model, precision=precision)

    rng = np.random.RandomState(0)
    if make_batch is not None:
        x, y = make_batch(rng, batch)
        x, y = jnp.asarray(x), jnp.asarray(y)
    else:
        x = jnp.asarray(rng.uniform(-1, 1, size=(batch,) + input_shape)
                        .astype(np.float32))
        y = jnp.asarray(rng.randint(1, n_classes + 1, size=batch)
                        .astype(np.float32))

    params, mstate = model.params, model.state
    slots = method.init_slots(params)
    t_compile = time.time()
    params, slots, mstate, loss = step_fn(params, slots, mstate, x, y)
    float(loss)
    _log(f"  compile+first step: {time.time() - t_compile:.1f}s")

    for _ in range(warmup - 1):
        params, slots, mstate, loss = step_fn(params, slots, mstate, x, y)
    float(loss)

    t0 = time.time()
    for _ in range(steps):
        params, slots, mstate, loss = step_fn(params, slots, mstate, x, y)
    # a host read of the final loss forces the whole donated-carry chain
    loss_v = float(loss)
    dt = time.time() - t0

    imgs_per_sec = batch * steps / dt
    out = {"images_per_sec": imgs_per_sec, "step_ms": dt / steps * 1e3,
           "loss": loss_v}
    if flops_per_image:
        out["tflops"] = imgs_per_sec * flops_per_image / 1e12
    return out


class _WithLogSoftMax:
    """Append log-softmax to a logits model without mutating it."""

    def __init__(self, model, lsm):
        self._m, self._lsm = model, lsm

    def apply(self, p, x, s, training=False, rng=None):
        out, new_s = self._m.apply(p, x, s, training=training, rng=rng)
        out, _ = self._lsm.apply({}, out, {})
        return out, new_s


def bench_longctx(steps: int = 5):
    """Long-context attention comparison at d1024/L8, B1, bf16: tokens/s
    for (a) the default XLA attention, (b) the pallas flash kernel, (c)
    the ring-attention blockwise path on a 1-device seq axis — measured
    AT the long shapes (T8192, T16384) rather than extrapolated from
    T2048.  Returns a list of per-point records (failures recorded, not
    raised: a compile failure at T16384 is the standard path's measured
    ceiling, not an error)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.models.transformer import transformer_lm
    from bigdl_tpu.parallel.all_reduce import AllReduceParameter
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer

    v, d, nl, h, b = 16384, 1024, 8, 8, 1
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)
    rng = np.random.RandomState(0)

    def run_jit(t, mode):
        lm = transformer_lm(v, d_model=d, n_head=h, n_layers=nl, max_len=t,
                            remat=(mode == "standard_remat"))
        for m in lm.modules():
            if isinstance(m, nn.MultiHeadAttention):
                if mode == "flash":
                    m.flash = True
                elif mode == "chunked":
                    m.chunk = 1024
        r = bench_model(
            lm, b, (t,), v, steps=steps, precision="bf16",
            criterion=crit,
            make_batch=lambda rg, bsz: (
                rg.randint(1, v + 1, (bsz, t)).astype(np.float32),
                rg.randint(1, v + 1, (bsz, t)).astype(np.float32)))
        return r["images_per_sec"] * t, r["step_ms"]

    def run_ring(t):
        """The sequence-parallel shard_map step on a (data=1, seq=1) mesh:
        the ring path with one ring step — its T-chunked blockwise local
        attention + machinery overhead, isolated from multi-chip ICI."""
        lm = transformer_lm(v, d_model=d, n_head=h, n_layers=nl, max_len=t)
        lm.training()
        lm._ensure_init()
        mesh = Engine.create_mesh((1, 1), ("data", "seq"))
        o = DistriOptimizer(lm, ShardedDataSet([None], 1), crit, mesh=mesh)
        o.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
        o.set_precision("bf16")
        o._wire_sequence_parallel(lm)
        arp = AllReduceParameter(lm.params, 1)
        step = o._build_step(arp)
        flat = jax.device_put(arp.flatten(lm.params),
                              NamedSharding(mesh, P()))
        slots = jax.device_put(o._flat_slots(arp),
                               NamedSharding(mesh, P("data")))
        mstate = jax.device_put(lm.state, NamedSharding(mesh, P()))
        key = jax.random.PRNGKey(0)
        hyper = o.optim_method.hyper()
        sh = NamedSharding(mesh, P(("data",), "seq"))
        x = jax.device_put(rng.randint(1, v + 1, (b, t)).astype(np.float32),
                           sh)
        y = jax.device_put(rng.randint(1, v + 1, (b, t)).astype(np.float32),
                           sh)
        flat, slots, mstate, loss = step(flat, slots, mstate, x, y, hyper,
                                         key)
        float(loss)
        for _ in range(2):
            flat, slots, mstate, loss = step(flat, slots, mstate, x, y,
                                             hyper, key)
        float(loss)
        t0 = time.time()
        for _ in range(steps):
            flat, slots, mstate, loss = step(flat, slots, mstate, x, y,
                                             hyper, key)
        float(loss)
        dt = (time.time() - t0) / steps
        return b * t / dt, dt * 1e3

    # failure-prone one-shot standard@16k goes LAST so its failure
    # cannot shadow the measurable points.  It exhausts HBM on saved
    # O(T^2) residuals beyond 2 layers (round-5 finding) — flash, the
    # pure-XLA chunked scan, and per-block remat all recover the shape,
    # so T16384 has three working single-chip paths.
    plan = [(8192, "standard", lambda: run_jit(8192, "standard")),
            (8192, "ring_seq1", lambda: run_ring(8192)),
            (8192, "flash", lambda: run_jit(8192, "flash")),
            (8192, "chunked", lambda: run_jit(8192, "chunked")),
            (16384, "flash", lambda: run_jit(16384, "flash")),
            (16384, "chunked", lambda: run_jit(16384, "chunked")),
            (16384, "standard_remat",
             lambda: run_jit(16384, "standard_remat")),
            (32768, "flash", lambda: run_jit(32768, "flash")),
            (16384, "standard", lambda: run_jit(16384, "standard"))]
    records = []
    for t, mode, fn in plan:
        try:
            toks, ms = fn()
            _log(f"  longctx T{t} {mode}: {toks:,.0f} tokens/s "
                 f"({ms:.0f} ms/step)")
            records.append({"seq_len": t, "mode": mode,
                            "tokens_per_sec": round(toks, 0),
                            "step_ms": round(ms, 1)})
        except Exception as e:
            _log(f"  longctx T{t} {mode}: FAILED "
                 f"({type(e).__name__}: {str(e)[:120]})")
            records.append({"seq_len": t, "mode": mode,
                            "status": f"failed: {type(e).__name__}"})
    return records


def bench_inference(steps: int = 20, warmup: int = 4):
    """Serving-side throughput: the jitted eval-mode forward (the
    reference's Predictor/LocalPredictor hot path,
    ``optim/LocalPredictor.scala:37``, minus host batching — measured
    as pure device throughput with a full dispatch queue).

    Two points, both bf16 via the same ``mixed_precision_forward`` the
    trainers use: ResNet-50 b128 images/s and the 134M-param LM forward
    (B8/T2048, tuned flash) tokens/s.  Returns per-point records."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim.optimizer import mixed_precision_forward

    def run(model, x, n_items):
        model.evaluate()
        model._ensure_init()
        params, state = model.params, model.state

        @jax.jit
        def fwd(p, xb):
            out, _ = mixed_precision_forward(model, p, xb, state,
                                             "bf16", False, None)
            return out

        xb = jnp.asarray(x)
        t_c = time.time()
        fwd(params, xb).block_until_ready()
        _log(f"  compile+first forward: {time.time() - t_c:.1f}s")
        out = None
        for _ in range(warmup):
            out = fwd(params, xb)
        if out is not None:        # warmup=0: the compile call above was
            out.block_until_ready()   # the only dispatch; nothing to drain
        t0 = time.time()
        # async dispatch keeps the device queue full; block once at the
        # end — serving throughput, not per-call host latency.  Only the
        # LAST output is retained: the LM point's log-probs are ~1 GB
        # per call, so holding all `steps` of them would exhaust HBM.
        for _ in range(steps):
            out = fwd(params, xb)
        out.block_until_ready()
        dt = (time.time() - t0) / steps
        return n_items / dt, dt * 1e3

    records = []
    rng = np.random.RandomState(0)

    from bigdl_tpu.models.resnet import resnet, model_init, DatasetType
    r50 = model_init(resnet(1000, depth=50, dataset=DatasetType.IMAGENET))
    rate, ms = run(r50, rng.uniform(-1, 1, (128, 3, 224, 224))
                   .astype(np.float32), 128)
    _log(f"  inference resnet50 b128 bf16: {rate:,.0f} img/s ({ms:.1f} ms)")
    records.append({"model": "resnet50", "batch": 128,
                    "value": round(rate, 1), "unit": "images/sec",
                    "step_ms": round(ms, 2)})
    del r50

    from bigdl_tpu.models.transformer import transformer_lm
    v, t = 16384, 2048
    lm = transformer_lm(v, d_model=1024, n_head=8, n_layers=8, max_len=t)
    for m in lm.modules():
        if isinstance(m, nn.MultiHeadAttention):
            m.flash = True
    rate, ms = run(lm, rng.randint(1, v + 1, (8, t)).astype(np.float32),
                   8 * t)
    _log(f"  inference transformer-lm 134M B8/T2048 bf16 flash: "
         f"{rate:,.0f} tokens/s ({ms:.1f} ms)")
    records.append({"model": "transformer_lm_134m", "batch": 8,
                    "seq_len": t, "value": round(rate, 0),
                    "unit": "tokens/sec", "step_ms": round(ms, 2)})
    return records


def bench_checkpoint(steps: int = 12, tmp_root: str = None):
    """Checkpoint-overhead measurement: sync vs async save latency and
    the step-time impact of checkpointing every iteration.

    Three training legs over the same fused step (none / sync / async
    checkpoint per iteration) plus isolated save-call timings.  The
    number that matters for the ISSUE-2 acceptance criterion is
    ``async_save_blocking_ms`` vs ``sync_save_ms``: the async writer
    moves serialization's downstream IO (and on remote stores, the whole
    transfer) off the critical path, so the train loop blocks only for
    the host fetch + in-memory pickle."""
    import shutil
    import tempfile

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.datasets import synthetic_separable
    from bigdl_tpu.optim.optimizer import Checkpoint

    # a model big enough that serialization cost is visible (~8M params,
    # 32 MB of fp32) but cheap to compile/step
    def build():
        import jax
        m = (nn.Sequential().add(nn.Linear(256, 4096)).add(nn.Tanh())
             .add(nn.Linear(4096, 1024)).add(nn.Tanh())
             .add(nn.Linear(1024, 10)).add(nn.LogSoftMax()))
        m.reset(jax.random.PRNGKey(0))
        return m

    samples = synthetic_separable(256, 256, n_classes=10, seed=1)

    def run_leg(mode: str) -> float:
        root = tempfile.mkdtemp(dir=tmp_root, prefix=f"bench_ckpt_{mode}_")
        try:
            model = build()
            ds = LocalDataSet(samples).transform(SampleToMiniBatch(64))
            opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
            opt.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
            opt.set_end_when(optim.max_iteration(steps))
            if mode != "none":
                opt.set_checkpoint(root, optim.several_iteration(1),
                                   async_write=(mode == "async"))
            t0 = time.time()
            opt.optimize()
            return (time.time() - t0) / steps * 1e3
        finally:
            shutil.rmtree(root, ignore_errors=True)

    # each leg builds a fresh jitted closure, so jit's in-process cache
    # cannot carry over — but main() configures the PERSISTENT compile
    # cache (jax_compilation_cache_dir), and all four legs trace the
    # identical HLO: the first leg pays the real compile and populates
    # the cache, the measured legs pay only a lookup+deserialize.
    run_leg("none")                  # populate the persistent cache
    step_none = run_leg("none")      # measured leg
    step_sync = run_leg("sync")
    step_async = run_leg("async")

    # isolated save-call latency: how long the train loop BLOCKS per save
    model = build()
    model.training()
    model._ensure_init()
    method = optim.SGD(learning_rate=0.1, momentum=0.9)
    method.slots(model.params)

    def save_latency(async_write: bool, label: str, gap_s: float):
        """Mean time a save call BLOCKS the caller.  ``gap_s`` emulates
        the compute between checkpoint triggers — that is the window the
        async writer overlaps into; back-to-back saves would degenerate
        async to sync (each save joins the still-running previous
        write)."""
        root = tempfile.mkdtemp(dir=tmp_root, prefix=f"bench_ckpt_{label}_")
        try:
            ckpt = Checkpoint(root, optim.every_epoch(),
                              async_write=async_write)
            blocked = []
            for n in range(1, 7):
                t0 = time.time()
                ckpt.save(model, method, n)
                blocked.append(time.time() - t0)
                time.sleep(gap_s)
            t0 = time.time()
            ckpt.join()
            drain_ms = (time.time() - t0) * 1e3
            return float(np.mean(blocked[1:])) * 1e3, drain_ms
        finally:
            shutil.rmtree(root, ignore_errors=True)

    # gap = one measured step (the cadence of several_iteration(1)),
    # capped so a compile-inflated or huge-model step cannot hand the
    # async writer an unrealistically generous overlap window
    gap_s = min(step_none / 1e3, 0.25)

    sync_ms, _ = save_latency(False, "synlat", gap_s)
    async_block_ms, async_drain_ms = save_latency(True, "asynclat", gap_s)
    out = {
        "model_mb": round(sum(l.size * 4 for l in
                              __import__("jax").tree_util.tree_leaves(
                                  model.params)) / 1e6, 1),
        "sync_save_ms": round(sync_ms, 2),
        "async_save_blocking_ms": round(async_block_ms, 2),
        "async_final_drain_ms": round(async_drain_ms, 2),
        "async_blocking_vs_sync": round(async_block_ms / max(sync_ms, 1e-9),
                                        3),
        "step_ms_no_ckpt": round(step_none, 2),
        "step_ms_sync_ckpt": round(step_sync, 2),
        "step_ms_async_ckpt": round(step_async, 2),
        "ckpt_overhead_sync_ms": round(step_sync - step_none, 2),
        "ckpt_overhead_async_ms": round(step_async - step_none, 2),
    }
    _log(f"  checkpoint overhead: sync save {sync_ms:.1f} ms blocks the "
         f"loop, async save blocks {async_block_ms:.1f} ms "
         f"(x{out['async_blocking_vs_sync']}); per-step impact "
         f"sync +{out['ckpt_overhead_sync_ms']:.1f} ms / async "
         f"+{out['ckpt_overhead_async_ms']:.1f} ms over a "
         f"{step_none:.1f} ms step")
    return out


def _write_ckpt_artifact(ck: dict) -> dict:
    """bench_ckpt.json, shared by --ckpt-only and the full run."""
    record = {"metric": "checkpoint_overhead", "checkpoint": ck}
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_ckpt.json"), "w") as f:
        json.dump(record, f, indent=1)
    return record


def _make_bench_seqfiles(root: str, n_images: int, files: int = 10):
    """Write a synthetic-image SequenceFile set ONCE (cached across runs):
    256x256 JPEG q90 — the reference's ImageNet seqfile protocol stores
    pre-scaled JPEGs (its generator resizes before writing), so per-epoch
    work is decode + crop + flip + normalize, exactly what this set
    reproduces."""
    import io

    from PIL import Image

    from bigdl_tpu.dataset.seqfile import write_image_seqfile

    done = os.path.join(root, f".done_{n_images}")
    if os.path.exists(done):
        return
    os.makedirs(root, exist_ok=True)
    rng = np.random.RandomState(7)
    per = n_images // files
    idx = 0
    for fi in range(files):
        entries = []
        for _ in range(per):
            # smooth blobs + noise: realistic JPEG entropy (decode cost is
            # content-dependent; pure noise over-prices it, flat under-)
            base = rng.normal(128, 40, size=(256, 256, 3))
            img = np.clip(base + rng.normal(0, 20, size=base.shape),
                          0, 255).astype(np.uint8)
            buf = io.BytesIO()
            Image.fromarray(img).save(buf, "JPEG", quality=90)
            entries.append((f"img_{idx}.jpg", float(idx % 1000 + 1),
                            buf.getvalue()))
            idx += 1
        write_image_seqfile(os.path.join(root, f"part-{fi:05d}.seq"),
                            entries)
    with open(done, "w") as f:
        f.write(str(n_images))


def _ingest_stage_ceilings(records, batch: int, mt):
    """Isolated per-stage ingest ceilings, shared by ``bench_ingest`` and
    ``bench_realdata`` so the two legs can never drift apart: JPEG decode
    measured through a host-cores thread pool (the decode STAGE's shape —
    a single-threaded sweep would understate the ceiling cores-fold on
    multi-core hosts) and the native assembler (already pooled
    internally).  Returns (decoded images, decode rate, assemble rate)."""
    from concurrent.futures import ThreadPoolExecutor

    from bigdl_tpu.dataset.mt_batch import assemble_batch

    sample = [r.bytes for r in records[:2 * batch]]
    workers = max(1, os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(mt._decode, sample[:8]))     # warm codec + threads
        t0 = time.time()
        imgs = list(pool.map(mt._decode, sample))
        decode_rate = len(sample) / (time.time() - t0)
    offs = np.zeros((batch, 2), np.int32) + 16
    flips = np.zeros((batch,), np.uint8)
    args = (imgs[:batch], (224, 224), offs, flips,
            (104.0, 117.0, 123.0), (1.0, 1.0, 1.0))
    assemble_batch(*args)
    t0 = time.time()
    for _ in range(4):
        assemble_batch(*args)
    assemble_rate = 4 * batch / (time.time() - t0)
    return imgs, decode_rate, assemble_rate


def bench_ingest(batch: int = 128, out_path: str = None):
    """HOST-ONLY per-stage ingest benchmark (``--ingest-only``; no device
    work, runs anywhere): isolated stage ceilings (sharded seqfile read,
    threaded JPEG decode, native assemble), then the synchronous
    MTLabeledBGRImgToBatch transformer and the stage-pipelined
    StreamingIngest engine over the SAME records — with the engine's
    per-stage throughput / stall / ring-occupancy counters.  Writes
    ``bench_ingest.json``.

    Two ceilings bracket what any pipeline can do: the slowest single
    stage (the pipelined bound when stages run on distinct cores) and the
    CPU-bound rate ``cores / Σ(core-seconds per image per stage)`` (the
    bound when every stage shares the same cores — on a 1-core host the
    stages cannot truly overlap and this is the honest target)."""
    from bigdl_tpu.dataset.ingest import ShardedSeqFileReader, StreamingIngest
    from bigdl_tpu.dataset.mt_batch import MTLabeledBGRImgToBatch
    from bigdl_tpu.dataset.native import native_available

    n_images = batch * 10
    root = f"/tmp/bigdl_bench_seq_v1_{n_images}"
    _make_bench_seqfiles(root, n_images)

    # stage 1: seqfile record read — sequential and sharded
    t0 = time.time()
    records = list(ShardedSeqFileReader(root, shards=1))
    read_rate = len(records) / (time.time() - t0)
    t0 = time.time()
    n_sharded = sum(1 for _ in ShardedSeqFileReader(root))
    sharded_read_rate = n_sharded / (time.time() - t0)

    # stages 2-3: pooled decode + native assemble ceilings (shared helper)
    mt = MTLabeledBGRImgToBatch(batch)
    imgs, decode_rate, assemble_rate = _ingest_stage_ceilings(
        records, batch, mt)

    # stage 4: full transformers, one epoch pass each, same records
    t0 = time.time()
    n_sync = sum(b.size() for b in mt(iter(records)))
    sync_rate = n_sync / (time.time() - t0)
    eng = StreamingIngest(batch)
    t0 = time.time()
    n_stream = sum(b.size() for b in eng(iter(records)))
    stream_rate = n_stream / (time.time() - t0)
    stages = eng.stats()

    cores = os.cpu_count() or 1
    slowest = min(read_rate, decode_rate, assemble_rate)
    # core-seconds per image: read is a single-threaded sweep (1/rate);
    # decode and assemble rates are POOLED over the cores (cores/rate)
    cpu_bound = cores / (1.0 / read_rate + cores / decode_rate +
                         cores / assemble_rate)
    effective = min(slowest, cpu_bound)
    _log(f"  ingest ceilings: seqfile read {read_rate:,.0f} rec/s "
         f"(sharded {sharded_read_rate:,.0f}), decode {decode_rate:,.0f} "
         f"img/s, assemble {assemble_rate:,.0f} img/s; slowest stage "
         f"{slowest:,.0f}, cpu-bound {cpu_bound:,.0f} ({cores} core(s))")
    _log(f"  sync MT ingest {sync_rate:,.0f} img/s "
         f"({sync_rate / slowest:.2f}x slowest stage); STREAMING ingest "
         f"{stream_rate:,.0f} img/s ({stream_rate / slowest:.2f}x slowest "
         f"stage, {stream_rate / effective:.2f}x effective ceiling)")
    for name, snap in stages.items():
        _log(f"    stage {name}: {snap['items']} items, "
             f"{snap['throughput_per_sec']:,.0f}/s, busy {snap['busy_s']}s, "
             f"starve {snap['starve_s']}s, backpressure "
             f"{snap['backpressure_s']}s, mean queue "
             f"{snap['mean_queue_depth']}")
    # acceptance bar: on a multi-core host the pipelined engine must
    # sustain >= 0.8x the measured ceiling.  The ceiling is the slowest
    # stage when the cores can truly overlap the stages, and the
    # cpu-bound rate when they cannot (effective = min of the two); a
    # 1-core host has no overlap to win, so the bar is informational.
    if cores > 1:
        assert stream_rate >= 0.8 * effective, (
            f"streaming ingest {stream_rate:,.0f} img/s is below 0.8x the "
            f"effective ceiling {effective:,.0f} img/s (slowest stage "
            f"{slowest:,.0f}, cpu-bound {cpu_bound:,.0f}) on {cores} cores")

    # stage 5: decoded-epoch cache — same records with the cache enabled.
    # Epoch 1 decodes and fills the segment ring; epoch 2 skips JPEG
    # decode entirely (frames come back from RAM).  Then a governor
    # pressure excursion is injected and must shrink the cache's
    # accounted bytes (the budget authority stays in charge).
    from bigdl_tpu.resources import GOVERNOR
    from bigdl_tpu.utils import chaos, config
    config.set_property("bigdl.ingest.epochCache", True)
    try:
        eng_c = StreamingIngest(batch)
        t0 = time.time()
        n_ep1 = sum(b.size() for b in eng_c(iter(records)))
        cache_ep1 = n_ep1 / (time.time() - t0)
        t0 = time.time()
        n_ep2 = sum(b.size() for b in eng_c(iter(records)))
        cache_ep2 = n_ep2 / (time.time() - t0)
        cache_stats = eng_c.epoch_cache.stats()
        acct = f"ingest_epoch_cache:{eng_c.name}"
        cache_bytes = dict(GOVERNOR.summary_scalars()).get(
            f"Resources/host_bytes_{acct}", 0.0)
        # injected host-memory pressure -> the governor's shrinkers fire
        # -> the cache evicts RAM segments and its account drops
        config.set_property("bigdl.chaos.hostMemPressureAt", 1)
        chaos.install()
        try:
            GOVERNOR.poll()
        finally:
            chaos.uninstall()
            config.clear_property("bigdl.chaos.hostMemPressureAt")
        shrunk_bytes = dict(GOVERNOR.summary_scalars()).get(
            f"Resources/host_bytes_{acct}", 0.0)
        _log(f"  epoch cache: epoch1 {cache_ep1:,.0f} img/s (fill), epoch2 "
             f"{cache_ep2:,.0f} img/s ({cache_ep2 / cache_ep1:.2f}x), "
             f"{cache_stats['hits']} hits / {cache_stats['misses']} misses, "
             f"{cache_bytes / 1e6:,.1f} MB cached; injected pressure "
             f"shrank to {shrunk_bytes / 1e6:,.1f} MB")
        assert cache_stats["hits"] > 0, "epoch 2 never hit the epoch cache"
        assert cache_bytes > 0, "epoch cache bytes invisible to the governor"
        assert shrunk_bytes < cache_bytes, (
            "injected governor pressure did not shrink the epoch cache")
        # the 2x bar only exists where decode was actually the bottleneck
        # (the other stages must have >= 2x headroom over decode)
        if cores > 1 and decode_rate <= 0.5 * min(read_rate, assemble_rate):
            assert cache_ep2 >= 2.0 * cache_ep1, (
                f"cached epoch 2 {cache_ep2:,.0f} img/s is under 2x the "
                f"decode-bound epoch 1 {cache_ep1:,.0f} img/s")
        epoch_cache_record = {
            "epoch1_imgs_per_sec": round(cache_ep1, 1),
            "epoch2_imgs_per_sec": round(cache_ep2, 1),
            "epoch2_vs_epoch1": round(cache_ep2 / cache_ep1, 3),
            "hits": cache_stats["hits"],
            "misses": cache_stats["misses"],
            "ram_segments": cache_stats["ram_segments"],
            "governor_account": acct,
            "cache_bytes": int(cache_bytes),
            "cache_bytes_after_pressure": int(shrunk_bytes),
        }
        eng_c.epoch_cache.close()
    finally:
        config.clear_property("bigdl.ingest.epochCache")

    record = {
        "metric": "mt_ingest_imgs_per_sec",
        "value": round(stream_rate, 1),
        "unit": "images/sec",
        "pipeline": "ShardedSeqFileReader -> record ring -> decode pool -> "
                    "ordered decode window -> native assembler -> batch "
                    "ring (StreamingIngest)",
        "sync_ingest_imgs_per_sec": round(sync_rate, 1),
        "streaming_vs_sync": round(stream_rate / sync_rate, 3),
        "stage_ceilings": {
            "seqfile_read_recs_per_sec": round(read_rate, 1),
            "sharded_read_recs_per_sec": round(sharded_read_rate, 1),
            "jpeg_decode_imgs_per_sec": round(decode_rate, 1),
            "native_assemble_imgs_per_sec": round(assemble_rate, 1),
        },
        "slowest_stage_imgs_per_sec": round(slowest, 1),
        "cpu_bound_imgs_per_sec": round(cpu_bound, 1),
        "ingest_vs_slowest_stage": round(stream_rate / slowest, 3),
        "ingest_vs_cpu_bound": round(stream_rate / cpu_bound, 3),
        "ingest_vs_effective_ceiling": round(stream_rate / effective, 3),
        # the acceptance bar asserted above (>= 0.8x effective ceiling on
        # a multi-core host), recorded so regressions are diffable
        "ingest_bar": {"threshold": 0.8,
                       "asserted": cores > 1,
                       "ratio": round(stream_rate / effective, 3)},
        "epoch_cache": epoch_cache_record,
        "engine_stages": stages,
        "native_assembler": native_available(),
        "host_cores": cores,
    }
    path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_ingest.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def bench_chaos_ingest(batch: int = 128, out_path: str = None):
    """``--chaos-ingest-only`` (host-only): the self-healing ingest leg →
    ``bench_chaos.json``.

    Three measurements: (1) streaming throughput with ~0.1% injected
    corrupt records vs clean over the same record set — the quarantine
    must cost noise, not throughput (degradation asserted < 5%); (2)
    stall-detection latency: a wedged upstream must be declared dead
    within the ``stallTimeoutSec`` window plus the supervisor poll, not
    hang; (3) fallback-switch cost: the one-time pause when a declared-
    dead engine hands the epoch to the synchronous path (measured as the
    widest inter-batch gap across the switch)."""
    from bigdl_tpu.dataset.ingest import (IngestStallError,
                                          ShardedSeqFileReader,
                                          StreamingIngest)
    from bigdl_tpu.utils import chaos, config

    n_images = batch * 10
    root = f"/tmp/bigdl_bench_seq_v1_{n_images}"
    _make_bench_seqfiles(root, n_images)
    records = list(ShardedSeqFileReader(root, shards=1))

    def epoch_rate(**eng_kwargs):
        eng = StreamingIngest(batch, **eng_kwargs)
        t0 = time.time()
        n = sum(b.size() for b in eng(iter(records)))
        return n / (time.time() - t0), eng

    # throughput: clean vs 0.1% corrupt (best of 2 each — the leg
    # measures the quarantine's cost, not the host's scheduling noise)
    epoch_rate()                                   # warm codec + pools
    clean_rate = max(epoch_rate()[0] for _ in range(2))
    # ~0.1% dirt, but always at least one corrupt record — a leg run at
    # a small --batch must still exercise the quarantine
    n_corrupt = max(1, round(0.001 * len(records)))
    every = len(records) // (n_corrupt + 1)
    config.set_property("bigdl.chaos.corruptRecordEvery", every)
    chaos.install()
    try:
        dirty_rate, eng = epoch_rate(max_bad_records=len(records))
        dirty_rate = max(dirty_rate, epoch_rate(
            max_bad_records=len(records))[0])
        quarantined = eng.quarantine.count
    finally:
        chaos.uninstall()
        config.clear_property("bigdl.chaos.corruptRecordEvery")
    degradation = 1.0 - dirty_rate / clean_rate
    assert degradation < 0.05, (
        f"quarantine cost {degradation:.1%} throughput (budget 5%): "
        f"clean {clean_rate:,.0f} img/s vs dirty {dirty_rate:,.0f}")

    # stall detection: hung upstream after a prefix, engine must abort
    stall_timeout = 0.5

    def hung():
        yield from records[:2 * batch]
        time.sleep(3600)

    eng = StreamingIngest(batch, stall_timeout=stall_timeout,
                          decoded_ring_depth=batch)
    it = iter(eng(hung()))
    last_batch_t = [time.time()]
    detect_s = None
    try:
        while True:
            next(it)
            last_batch_t[0] = time.time()
    except IngestStallError:
        detect_s = time.time() - last_batch_t[0]
    assert detect_s is not None, "wedged ring was not detected"

    # fallback-switch cost: kill the assembler, no restarts, fall back
    config.set_property("bigdl.chaos.killStageThread",
                        f"assembler:{2 * batch}")
    chaos.install()
    try:
        eng = StreamingIngest(batch, max_stage_restarts=0,
                              fallback_on_failure=True)
        gaps, t_prev, n_fb = [], time.time(), 0
        for b in eng(iter(records)):
            now = time.time()
            gaps.append(now - t_prev)
            t_prev = now
            n_fb += b.size()
        assert eng.fallbacks == 1
        assert n_fb == len(records)
    finally:
        chaos.uninstall()
        config.clear_property("bigdl.chaos.killStageThread")
    switch_cost_s = max(gaps)

    _log(f"  chaos ingest: clean {clean_rate:,.0f} img/s, 0.1%-corrupt "
         f"{dirty_rate:,.0f} img/s ({degradation:+.2%} degradation, "
         f"{quarantined} quarantined); stall detected "
         f"{detect_s - stall_timeout:+.2f}s past the {stall_timeout}s "
         f"threshold; fallback switch cost {switch_cost_s * 1e3:,.0f} ms "
         f"(stream completed on the sync path)")

    record = {
        "metric": "chaos_ingest_degradation_frac",
        "value": round(degradation, 4),
        "unit": "fraction",
        "clean_imgs_per_sec": round(clean_rate, 1),
        "dirty_imgs_per_sec": round(dirty_rate, 1),
        "corrupt_rate": f"1/{every}",
        "quarantined_records": quarantined,
        "degradation_budget": 0.05,
        "stall_timeout_s": stall_timeout,
        "stall_detect_s": round(detect_s, 3),
        "stall_detect_past_threshold_s": round(detect_s - stall_timeout, 3),
        "fallback_switch_cost_ms": round(switch_cost_s * 1e3, 1),
        "host_cores": os.cpu_count() or 1,
    }
    path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_chaos.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def bench_realdata(batch: int = 128, steps: int = 20, warmup: int = 4,
                   synthetic_rate: float = None):
    """END-TO-END real-data ingest: seq_file_folder (native reader) →
    MTLabeledBGRImgToBatch (threaded decode + native assemble) →
    BatchPrefetcher → DistriOptimizer fused bf16 step — the reference's
    production ImageNet path (``dataset/DataSet.scala:500-558`` +
    ``MTLabeledBGRImgToBatch.scala:46``), measured against the
    synthetic-input headline.  Returns (imgs_per_sec, stage_rates)."""
    import logging
    import re

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.dataset.image import LabeledImageBytes
    from bigdl_tpu.dataset.mt_batch import MTLabeledBGRImgToBatch
    from bigdl_tpu.dataset.seqfile import read_image_seqfile
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.models.resnet import DatasetType, model_init, resnet
    from bigdl_tpu.parallel import DistriOptimizer

    n_images = batch * 10
    # per-size root: one shared dir with per-count .done markers would go
    # stale when a different --batch overwrites the part files
    root = f"/tmp/bigdl_bench_seq_v1_{n_images}"
    _make_bench_seqfiles(root, n_images)

    # stage 1: native seqfile record read (bytes only)
    t0 = time.time()
    records = []
    for fname in sorted(os.listdir(root)):
        if fname.endswith(".seq"):
            for name, label, data in read_image_seqfile(
                    os.path.join(root, fname)):
                records.append(LabeledImageBytes(name, label, data))
    read_rate = len(records) / (time.time() - t0)

    mt = MTLabeledBGRImgToBatch(batch)
    # stages 2-3: pooled decode + native assemble ceilings (the same
    # helper bench_ingest uses, so the two legs report one truth)
    imgs, decode_rate, assemble_rate = _ingest_stage_ceilings(
        records, batch, mt)
    # stage 4: one epoch pass each (no device) — the synchronous MT
    # transformer and the stage-pipelined streaming engine the training
    # legs below actually use
    t0 = time.time()
    n_out = sum(b.size() for b in mt(iter(records)))
    sync_ingest_rate = n_out / (time.time() - t0)
    from bigdl_tpu.dataset.ingest import StreamingIngest
    stream_probe = StreamingIngest(batch)
    t0 = time.time()
    n_out = sum(b.size() for b in stream_probe(iter(records)))
    ingest_rate = n_out / (time.time() - t0)
    stream_stages = stream_probe.stats()   # snapshot while rates are live
    _log(f"  ingest stages: seqfile read {read_rate:,.0f} rec/s, decode "
         f"{decode_rate:,.0f} img/s, native assemble {assemble_rate:,.0f} "
         f"img/s, sync MT ingest {sync_ingest_rate:,.0f} img/s, streaming "
         f"ingest {ingest_rate:,.0f} img/s ({os.cpu_count()} host core(s))")

    # stage 4.5: ISOLATED host->device upload roofline at the exact batch
    # payload, sampled AFTER a first program execution (the state the
    # training loop lives in), plus an overlap probe: what one upload
    # costs while a compute step is in flight.  Together these pin whether end-to-end
    # is transfer-bound and whether double-buffering could win it back.
    import jax
    import jax.numpy as jnp

    w = jnp.asarray(np.random.RandomState(0)
                    .normal(size=(1024, 1024)).astype(np.float32))
    float(jnp.sum(w @ w))                  # a real program: degrades the link
    u8 = np.random.RandomState(1).randint(
        0, 255, (batch, 3, 224, 224)).astype(np.uint8)
    f32 = u8.astype(np.float32)

    def upload_rate(arr, n=4):
        d = jax.device_put(arr)
        float(jnp.sum(d[0, 0, 0, :2]).astype(jnp.float32))   # settle
        t0 = time.time()
        for _ in range(n):
            d = jax.device_put(arr)
            # a tiny dependent reduce + host read forces completion; its
            # RTT (~0.1 s) is shared across the n uploads below
            float(jnp.sum(d[0, 0, 0, :2]).astype(jnp.float32))
        dt = (time.time() - t0) / n
        return arr.nbytes / dt, batch / dt

    u8_bps, u8_imgs = upload_rate(u8)
    f32_bps, f32_imgs = upload_rate(f32)
    # the link's bandwidth DRIFTS tens of percent within minutes (r4/r5
    # measurements); the roofline is re-sampled after the training runs
    # and the bound uses the mean, with the drift pinned in the artifact

    def matmul_ms(n=6):
        t0 = time.time()
        acc = w
        for _ in range(n):
            acc = acc @ w
        float(jnp.sum(acc[:1, :1]))
        return (time.time() - t0) / n * 1e3

    base_ms = matmul_ms()
    # overlap probe: dispatch compute, then start a bulk upload while it
    # is in flight
    t0 = time.time()
    acc = w
    for _ in range(6):
        acc = acc @ w
    d = jax.device_put(u8)
    float(jnp.sum(acc[:1, :1]))
    float(jnp.sum(d[0, 0, 0, :2]).astype(jnp.float32))
    overlap_s = time.time() - t0
    serial_s = 6 * base_ms / 1e3 + batch / u8_imgs
    _log(f"  upload roofline (degraded link): uint8 "
         f"{u8_bps / 1e6:,.1f} MB/s = {u8_imgs:,.1f} img/s; float32 "
         f"{f32_bps / 1e6:,.1f} MB/s = {f32_imgs:,.1f} img/s; overlap "
         f"probe {overlap_s:.2f}s vs serial {serial_s:.2f}s")

    # stage 5: end-to-end training, two upload layouts: float32, and the
    # byte-reduced TPU-first layout — raw uint8 pixels +
    # nn.ChannelNormalize on device, 4x fewer bytes.  The link's rate is
    # not measured on the current machine.
    # Wall time over whole optimize() segments (fetch, transfer, step,
    # driver) divided by images; compile excluded via a warmup segment.
    # The pipeline is the streaming engine end to end: StreamingIngest
    # (decode/assemble stage-pipelined, batch ring) feeding the driver's
    # BatchPrefetcher transfer-ahead stage (bigdl.ingest.batchesInFlight
    # uploads in flight).
    def train_rate(device_normalize: bool, n_steps: int) -> float:
        head = (nn.ChannelNormalize((104.0, 117.0, 123.0), (1.0, 1.0, 1.0),
                                    dtype="bfloat16")
                if device_normalize else nn.Identity())
        model = (nn.Sequential()
                 .add(head)
                 .add(model_init(resnet(1000, depth=50,
                                        dataset=DatasetType.IMAGENET)))
                 .add(nn.LogSoftMax()))
        ds = ShardedDataSet(records, 1).transform(
            StreamingIngest(batch, device_normalize=device_normalize))
        opt = DistriOptimizer(model, ds, nn.ClassNLLCriterion(),
                              mesh=Engine.create_mesh())
        opt.set_optim_method(optim.SGD(learning_rate=0.01, momentum=0.9))
        opt.set_precision("bf16")
        opt.set_end_when(optim.max_iteration(warmup + n_steps))

        # the driver's log protocol reports inter-dispatch intervals; their
        # sum from iteration k through the final flush equals the steady
        # wall (fetch + transfer + step + driver), excluding compile and
        # the initial param upload that precede the first dispatch
        iter_secs = []

        class Tap(logging.Handler):
            def emit(self, record):
                m = re.search(r"Train \d+ in ([0-9.]+) seconds",
                              record.getMessage())
                if m:
                    iter_secs.append(float(m.group(1)))

        lg = logging.getLogger("bigdl_tpu")
        tap = Tap()
        lg.addHandler(tap)
        level = lg.level
        lg.setLevel(logging.INFO)
        try:
            opt.optimize()
        finally:
            lg.removeHandler(tap)
            lg.setLevel(level)
        steady = iter_secs[warmup:]
        mean_rate = batch * len(steady) / sum(steady)
        # a stalled transfer can hold one iteration for many seconds;
        # the median-iteration rate is the
        # SUSTAINED throughput between stalls, reported alongside the
        # stall-inclusive mean (both honest, different questions)
        med_rate = batch / float(np.median(steady))
        return mean_rate, med_rate

    rate_f32, med_f32 = train_rate(False, max(6, steps // 2))
    _log(f"  end-to-end float32-upload: {rate_f32:,.1f} img/s "
         f"(sustained median {med_f32:,.1f})")
    rate_u8, med_u8 = train_rate(True, steps)
    _log(f"  end-to-end uint8-upload + device normalize: "
         f"{rate_u8:,.1f} img/s (sustained median {med_u8:,.1f})")
    best_med = max(med_u8, med_f32)
    # re-sample the upload roofline AFTER training (both dtypes): a
    # link whose bandwidth drifts mis-scores the runs from one sample.  The roofline is therefore a
    # RANGE [pre, post] keyed to the measured drift, and the e2e score is
    # reported against both edges.
    u8_bps2, u8_imgs2 = upload_rate(u8)
    f32_bps2, f32_imgs2 = upload_rate(f32)
    drift = u8_imgs2 / u8_imgs
    # per-sample ceiling: ingest overlaps in the producer threads (it is
    # NOT serial with the device work), while upload is taken to
    # serialize with dispatch (what the overlap probe above tests) —
    # so the
    # steady-state ceiling at an upload rate U is
    # min(ingest, 1/(1/U + 1/compute)).  The link is nonstationary, so
    # the two samples bracket the regime the training iterations saw;
    # a sustained median outside the bracket means the link moved
    # further than the samples caught.
    compute = synthetic_rate or 1834.0   # resident-input step rate

    def ceiling(upload):
        return min(ingest_rate, 1.0 / (1.0 / upload + 1.0 / compute))

    bounds = sorted([ceiling(u8_imgs), ceiling(u8_imgs2)])
    _log(f"  upload roofline re-sample: {u8_imgs2:,.1f} img/s "
         f"(drift x{drift:.2f}); transfer-bound ceiling "
         f"[{bounds[0]:,.1f}, {bounds[1]:,.1f}] img/s; uint8 e2e "
         f"sustained {med_u8:,.1f}")
    # drift flag: when the link moved more than 25% between the pre/post
    # roofline samples, the bracket no longer pins the regime the
    # training iterations saw — a mean-vs-median gap can then be the
    # LINK moving, not iteration stalls, and scoring against either
    # single sample is blind.  The flag rides next to the side-by-side
    # mean/median report so a reader (or a regression diff) can't take
    # the ceiling ratio at face value on a flagged run.
    drift_flagged = (abs(drift - 1.0) > 0.25
                     or not (bounds[0] * 0.9 <= med_u8 <= bounds[1] * 1.1))
    _log(f"  throughput report: f32 stall-inclusive mean {rate_f32:,.1f} / "
         f"sustained median {med_f32:,.1f}; u8 stall-inclusive mean "
         f"{rate_u8:,.1f} / sustained median {med_u8:,.1f} img/s; link "
         f"drift x{drift:.2f}"
         + (" [DRIFT FLAGGED: ceiling bracket unreliable]"
            if drift_flagged else ""))
    stages = {"seqfile_read_recs_per_sec": round(read_rate, 1),
              "jpeg_decode_imgs_per_sec": round(decode_rate, 1),
              "native_assemble_imgs_per_sec": round(assemble_rate, 1),
              "mt_ingest_imgs_per_sec": round(ingest_rate, 1),
              "sync_ingest_imgs_per_sec": round(sync_ingest_rate, 1),
              "ingest_engine_stages": stream_stages,
              "upload_u8_megabytes_per_sec": round(u8_bps / 1e6, 1),
              "upload_u8_imgs_per_sec": round(u8_imgs, 1),
              "upload_u8_imgs_per_sec_postrun": round(u8_imgs2, 1),
              "upload_link_drift": round(drift, 3),
              "upload_f32_megabytes_per_sec": round(f32_bps / 1e6, 1),
              "upload_f32_imgs_per_sec": round(f32_imgs, 1),
              "overlap_probe_s": round(overlap_s, 2),
              "overlap_serial_s": round(serial_s, 2),
              # the roofline is a RANGE: both dtypes sampled before AND
              # after the training legs, ceiling bracketed by the pre/post
              # samples and keyed to the measured drift
              "upload_roofline": {
                  "pre": {"u8_MBps": round(u8_bps / 1e6, 1),
                          "u8_imgs_per_sec": round(u8_imgs, 1),
                          "f32_MBps": round(f32_bps / 1e6, 1),
                          "f32_imgs_per_sec": round(f32_imgs, 1)},
                  "post": {"u8_MBps": round(u8_bps2 / 1e6, 1),
                           "u8_imgs_per_sec": round(u8_imgs2, 1),
                           "f32_MBps": round(f32_bps2 / 1e6, 1),
                           "f32_imgs_per_sec": round(f32_imgs2, 1)},
                  "drift_u8": round(drift, 3),
                  "drift_f32": round(f32_imgs2 / f32_imgs, 3)},
              "transfer_ceiling_imgs_per_sec": [round(bounds[0], 1),
                                                round(bounds[1], 1)],
              "train_f32_upload_imgs_per_sec": round(rate_f32, 1),
              "train_u8_sustained_median_imgs_per_sec": round(med_u8, 1),
              "sustained_median_imgs_per_sec": round(best_med, 1),
              # stall-inclusive mean AND sustained median, side by side
              # per upload layout, with the link-drift flag that says
              # whether the ceiling bracket can be trusted for this run
              "throughput_report": {
                  "f32": {"stall_inclusive_mean_imgs_per_sec":
                              round(rate_f32, 1),
                          "sustained_median_imgs_per_sec":
                              round(med_f32, 1)},
                  "u8": {"stall_inclusive_mean_imgs_per_sec":
                             round(rate_u8, 1),
                         "sustained_median_imgs_per_sec":
                             round(med_u8, 1)},
                  "upload_link_drift": round(drift, 3),
                  "drift_flagged": drift_flagged},
              # the uint8 leg's sustained median scored against both
              # roofline samples' ceilings: inside (or above) the
              # bracket = the framework delivers whatever the drifting
              # link allows
              "e2e_vs_ceiling_range": [round(med_u8 / bounds[1], 3),
                                       round(med_u8 / bounds[0], 3)],
              "host_cores": os.cpu_count()}
    return max(rate_u8, rate_f32), stages


def _validate_chrome_trace(doc: dict) -> dict:
    """Well-formedness check for an exported Chrome trace-event JSON:
    the structure Perfetto/chrome://tracing requires.  Returns lane and
    event counts; raises AssertionError on a malformed document."""
    assert isinstance(doc, dict), "trace must be a JSON object"
    events = doc.get("traceEvents")
    assert isinstance(events, list) and events, "traceEvents missing/empty"
    lanes = set()
    n_spans = 0
    for ev in events:
        assert ev.get("ph") in ("X", "M", "i"), f"bad phase {ev.get('ph')!r}"
        assert isinstance(ev.get("pid"), int), "pid must be an int"
        assert isinstance(ev.get("tid"), int), "tid must be an int"
        if ev["ph"] == "X":
            assert isinstance(ev.get("name"), str) and ev["name"]
            assert ev.get("ts") is not None and ev["ts"] >= 0
            assert ev.get("dur") is not None and ev["dur"] >= 0
            n_spans += 1
        elif ev["ph"] == "M" and ev["name"] == "thread_name":
            lanes.add(ev["args"]["name"])
    # the document must survive a JSON round-trip byte-exactly in meaning
    assert json.loads(json.dumps(doc)) == doc
    return {"spans": n_spans, "lanes": sorted(lanes)}


def bench_telemetry(steps: int = 25, out_path: str = None):
    """``--telemetry-only``: tracer overhead measured armed vs disarmed,
    plus a sample exported trace validated for well-formedness →
    ``bench_telemetry.json``.

    The <1% contract is checked two ways: a span-cost microbenchmark
    scaled by the driver's spans-per-step (deterministic — immune to
    run-to-run step-time noise on a shared CPU host) and the honest but
    noisy end-to-end step-time delta between a traced and an untraced
    training leg.  The ASSERTED number is the modeled fraction; the e2e
    delta is recorded alongside."""
    import tempfile

    import jax

    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.datasets import synthetic_separable

    # (1) span-cost microbenchmark: the per-span price of the context
    # manager itself, armed and disarmed
    def span_cost_ns(n: int = 50000) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            with telemetry.span("bench/probe"):
                pass
        return (time.perf_counter() - t0) / n * 1e9

    was_enabled = telemetry.tracing_enabled()
    telemetry.disarm()
    disabled_span_ns = span_cost_ns()
    telemetry.arm(ring_size=4096)
    enabled_span_ns = span_cost_ns()
    telemetry.disarm()
    telemetry.reset_tracer()

    # (2) the same small-MLP training leg with telemetry fully off, then
    # with the tracer armed + trace exported (model sized so a step is
    # milliseconds — large enough that span cost is measurable AGAINST
    # something, small enough to run anywhere)
    samples = synthetic_separable(256, 256, n_classes=10, seed=1)

    def build():
        m = (nn.Sequential().add(nn.Linear(256, 2048)).add(nn.Tanh())
             .add(nn.Linear(2048, 512)).add(nn.Tanh())
             .add(nn.Linear(512, 10)).add(nn.LogSoftMax()))
        m.reset(jax.random.PRNGKey(0))
        return m

    def run_leg(trace: bool, trace_path: str = None):
        if trace:
            telemetry.arm(ring_size=65536)
        try:
            model = build()
            ds = LocalDataSet(samples).transform(SampleToMiniBatch(64))
            opt = optim.Optimizer.create(model, ds, nn.ClassNLLCriterion())
            opt.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
            opt.set_end_when(optim.max_iteration(steps))
            t0 = time.time()
            opt.optimize()
            wall_ms = (time.time() - t0) / steps * 1e3
            doc = (telemetry.export_chrome_trace(trace_path) if trace
                   else None)
            return wall_ms, opt._step_account.summary(), doc
        finally:
            if trace:
                telemetry.disarm()
                telemetry.reset_tracer()

    run_leg(False)                        # populate the persistent cache
    off_ms, off_acct, _ = run_leg(False)
    trace_file = os.path.join(tempfile.mkdtemp(prefix="bench_tele_"),
                              "trace.json")
    on_ms, on_acct, doc = run_leg(True, trace_file)
    trace_info = _validate_chrome_trace(doc)

    # spans the driver emits per step (count them from the trace rather
    # than hard-coding the instrumentation)
    driver_spans = sum(1 for ev in doc["traceEvents"]
                       if ev["ph"] == "X" and
                       ev["name"].startswith(("driver/", "prefetch/")))
    spans_per_step = driver_spans / steps
    modeled_frac = spans_per_step * max(enabled_span_ns, 0.0) / (off_ms * 1e6)
    e2e_frac = (on_ms - off_ms) / off_ms

    record = {
        "metric": "telemetry_tracer_overhead_frac",
        "value": round(modeled_frac, 6),
        "unit": "fraction_of_step_time",
        "span_cost_ns": {"disabled": round(disabled_span_ns, 1),
                         "enabled": round(enabled_span_ns, 1)},
        "spans_per_step": round(spans_per_step, 2),
        "step_ms": {"telemetry_off": round(off_ms, 3),
                    "telemetry_on": round(on_ms, 3)},
        "e2e_overhead_frac": round(e2e_frac, 4),
        "enabled_overhead_lt_1pct": bool(modeled_frac < 0.01),
        "disabled_span_cost_lt_1us": bool(disabled_span_ns < 1000.0),
        "sample_trace": {"path": trace_file, **trace_info},
        "decomposition": {
            "closure": round(sum(off_acct[f"{p}_frac"] for p in
                                 ("data_wait", "compute", "host_pull",
                                  "bookkeeping", "unaccounted")), 6),
            "off": {k: round(v, 4) for k, v in off_acct.items()},
            "on": {k: round(v, 4) for k, v in on_acct.items()},
        },
    }
    _log(f"  telemetry: span cost {enabled_span_ns:.0f} ns armed / "
         f"{disabled_span_ns:.0f} ns disarmed; {spans_per_step:.1f} "
         f"driver spans/step over a {off_ms:.2f} ms step = "
         f"{100 * modeled_frac:.3f}% modeled overhead "
         f"(e2e delta {100 * e2e_frac:+.1f}%); trace: "
         f"{trace_info['spans']} spans on lanes {trace_info['lanes']}")
    # the artifact lands BEFORE the contract assert: a violation must
    # leave the diagnostic record behind, not destroy it
    path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_telemetry.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if was_enabled:
        telemetry.arm()
    assert modeled_frac < 0.01, \
        f"tracer overhead {100 * modeled_frac:.2f}% breaks the <1% contract"
    return record


def bench_elastic(out_path: str = None):
    """``--elastic-only``: the elastic-training leg → bench_elastic.json.

    Three numbers the autoscaling story depends on, all provable on the
    virtual CPU mesh (the leg re-runs the tests' rehearsals under a
    clock):

    - **restore + reshard latency by device-count pair** — checkpoint a
      run on N devices, resume it on M; ``Elastic/restore_ms`` times the
      manifest-verified load, ``Elastic/reshard_ms`` the re-partition +
      re-placement of the ZeRO-1 slots onto the new mesh;
    - **preemption-to-first-resumed-step** — wall time from the
      ``Preempted`` unwind (grace snapshot committed) to a new process
      image completing its first resumed step;
    - **watchdog detection latency** — how far past the stall threshold
      the open step was when the monitor fired (poll-quantized).
    """
    import jax
    from bigdl_tpu import telemetry
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.dataset.datasets import synthetic_separable
    from bigdl_tpu.parallel import DistriOptimizer
    from bigdl_tpu.utils import chaos, config, elastic

    if len(jax.devices()) < 4:
        raise SystemExit(
            "--elastic-only needs a >=4-device mesh to change topology "
            f"under (found {len(jax.devices())}). jax was initialized "
            "before the leg could force the virtual CPU mesh — run "
            "bench.py --elastic-only as its own invocation (XLA_FLAGS="
            "--xla_force_host_platform_device_count=8).")

    samples = synthetic_separable(256, 16, n_classes=4, seed=3)
    config.set_property("bigdl.failure.retryTimeInterval", 0.0)

    def trainer(parts, epochs, ckpt=None):
        m = (nn.Sequential().add(nn.Linear(16, 64)).add(nn.Tanh())
             .add(nn.Linear(64, 4)).add(nn.LogSoftMax()))
        m.reset(jax.random.PRNGKey(11))
        ds = ShardedDataSet(samples, parts).transform(
            SampleToMiniBatch(256, parts))
        mesh = Engine.create_mesh((parts,), ("data",),
                                  devices=jax.devices()[:parts])
        o = DistriOptimizer(m, ds, nn.ClassNLLCriterion(), mesh=mesh)
        o.set_optim_method(optim.Adam(learning_rate=0.01))
        o.set_end_when(optim.max_epoch(epochs))
        if ckpt:
            o.set_checkpoint(str(ckpt), optim.every_epoch())
        return o

    import tempfile

    def gauge_value(name):
        return telemetry.REGISTRY.snapshot()["gauges"].get(name)

    # -- restore + reshard latency, by (from, to) device-count pair ------
    n_dev = len(jax.devices())
    pairs = [(n, m) for n, m in ((4, 2), (2, 4), (n_dev, 2), (2, n_dev))
             if n <= n_dev and m <= n_dev and n != m]
    pair_records = []
    for n, m in dict.fromkeys(pairs):
        d = tempfile.mkdtemp(prefix=f"elastic_{n}to{m}_")
        trainer(n, 2, ckpt=d).optimize()
        o2 = trainer(m, 3, ckpt=d)
        t0 = time.perf_counter()
        assert o2._restore_latest_checkpoint()
        restore_wall_ms = (time.perf_counter() - t0) * 1e3
        o2.optimize()          # 1 resumed epoch; reshard timed inside
        pair_records.append({
            "from_devices": n, "to_devices": m,
            "restore_ms": round(gauge_value("Elastic/restore_ms"), 3),
            "restore_wall_ms": round(restore_wall_ms, 3),
            "reshard_ms": round(gauge_value("Elastic/reshard_ms"), 3),
        })
        _log(f"elastic {n}->{m}: restore "
             f"{pair_records[-1]['restore_ms']:.1f} ms, reshard "
             f"{pair_records[-1]['reshard_ms']:.2f} ms")

    # -- preemption-to-first-resumed-step --------------------------------
    d = tempfile.mkdtemp(prefix="elastic_preempt_")
    config.set_property("bigdl.chaos.preemptAt", 3)
    chaos.install()
    o = trainer(2, 6, ckpt=d)
    try:
        o.optimize()
        raise AssertionError("preemption injection did not fire")
    except elastic.Preempted:
        pass
    finally:
        chaos.uninstall()
        config.clear_property("bigdl.chaos.preemptAt")
    marker = elastic.read_preemption_marker(d)
    assert marker is not None, "grace-period drain left no marker"
    t0 = time.perf_counter()
    o2 = trainer(2, marker["neval"] + 1, ckpt=d)   # exactly 1 resumed step
    assert o2._restore_latest_checkpoint()
    o2.optimize()
    preempt_resume_ms = (time.perf_counter() - t0) * 1e3
    preemption = {
        "grace_snapshot_ms": round(
            gauge_value("Elastic/preempt_snapshot_ms"), 3),
        "to_first_resumed_step_ms": round(preempt_resume_ms, 3),
    }
    _log(f"elastic preemption: snapshot {preemption['grace_snapshot_ms']:.1f}"
         f" ms, to first resumed step {preempt_resume_ms:.1f} ms")

    # -- watchdog detection latency --------------------------------------
    fired_before = telemetry.REGISTRY.counter(
        "Elastic/watchdog_fired").value
    config.set_property("bigdl.watchdog.stallFactor", 5.0)
    config.set_property("bigdl.watchdog.warmupSteps", 2)
    config.set_property("bigdl.watchdog.pollInterval", 0.05)
    config.set_property("bigdl.chaos.stallStepAt", "6:1.0")
    chaos.install()
    try:
        trainer(2, 10, ckpt=tempfile.mkdtemp(
            prefix="elastic_wd_")).optimize()
    finally:
        chaos.uninstall()
        for k in ("bigdl.watchdog.stallFactor", "bigdl.watchdog.warmupSteps",
                  "bigdl.watchdog.pollInterval", "bigdl.chaos.stallStepAt",
                  "bigdl.failure.retryTimeInterval"):
            config.clear_property(k)
    fired = telemetry.REGISTRY.counter(
        "Elastic/watchdog_fired").value - fired_before
    assert fired == 1, f"watchdog fired {fired} times, expected exactly 1"
    watchdog = {
        "fired": int(fired),
        "detect_past_threshold_ms": round(
            gauge_value("Elastic/watchdog_detect_ms"), 3),
        "poll_interval_ms": 50.0,
    }
    _log(f"elastic watchdog: detected "
         f"{watchdog['detect_past_threshold_ms']:.1f} ms past threshold "
         f"(poll 50 ms)")

    record = {
        "pairs": pair_records,
        "preemption": preemption,
        "watchdog": watchdog,
        "devices": n_dev,
        "note": "CPU virtual-mesh rehearsal: restore/reshard are "
                "host+placement costs and transfer with model size; "
                "detection latency is poll-quantized",
    }
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_elastic.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    _log(f"elastic record -> {out_path}")
    return record


def bench_integrity(steps: int = 60, out_path: str = None):
    """``--integrity-only``: the training-state integrity leg →
    bench_integrity.json.

    Three numbers on the virtual 8-device CPU mesh (the tier-1
    configuration — absolute times are CPU times, the RATIOS transfer):

    - **measured step-time overhead** — the identical shard_map dp
      trainer with integrity off and with ``bigdl.integrity.everyN`` at
      1/10/100: p50 step time from the StepAccount window.  The armed
      step fingerprints params/slots/grads and all-gathers the agreement
      table EVERY iteration (the cadence only throttles the driver's aux
      pull), so the measured overhead is cadence-flat by design;
    - **modeled fingerprint overhead by cadence** — the jitted
      fingerprint computation timed alone, amortized over the cadence
      (``fp_ms / (n * p50_off)``): what a cadence-GATED deployment would
      pay.  Asserted < 1% at the recommended production cadence
      (everyN=100 — detection lag does not lose work: the on-device
      ``bad_iter`` records the corruption's onset and the heal rewinds
      there);
    - **detection-to-heal latency** — one injected replica bit flip
      (``bigdl.chaos.bitflipParamAt``): wall time from the desync raise
      to training resumed on re-broadcast majority state
      (``Integrity/heal_ms``), plus detection lag in iterations.
    """
    import statistics
    import tempfile
    import time

    import jax
    from bigdl_tpu import integrity, telemetry
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.dataset.datasets import synthetic_separable
    from bigdl_tpu.parallel import DistriOptimizer
    from bigdl_tpu.utils import chaos, config

    n_dev = len(jax.devices())
    if n_dev < 8:
        raise SystemExit(
            "--integrity-only needs an 8-device mesh for the dp "
            f"agreement leg (found {n_dev}). jax was initialized before "
            "the leg could force the virtual CPU mesh — run bench.py "
            "--integrity-only as its own invocation (XLA_FLAGS="
            "--xla_force_host_platform_device_count=8).")

    samples = synthetic_separable(256, 16, n_classes=4, seed=3)
    config.set_property("bigdl.failure.retryTimeInterval", 0.0)
    config.set_property("bigdl.pipeline.depth", 1)

    def mlp():
        # wide enough that the step is compute-bound on CPU — a
        # dispatch-bound toy step would make the fixed jit-call cost of
        # the fingerprint fn look like compute and inflate the ratio
        m = (nn.Sequential().add(nn.Linear(16, 1024)).add(nn.Tanh())
             .add(nn.Linear(1024, 256)).add(nn.Tanh())
             .add(nn.Linear(256, 4)).add(nn.LogSoftMax()))
        m.reset(jax.random.PRNGKey(11))
        return m

    def run(every_n, ckpt=None, iters=steps):
        if every_n:
            config.set_property("bigdl.integrity.everyN", every_n)
        try:
            m = mlp()
            ds = ShardedDataSet(samples, 8).transform(
                SampleToMiniBatch(256, 8))
            mesh = Engine.create_mesh((8,), ("data",))
            o = DistriOptimizer(m, ds, nn.ClassNLLCriterion(), mesh=mesh)
            o.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
            o.set_end_when(optim.max_iteration(iters))
            if ckpt:
                o.set_checkpoint(str(ckpt), optim.several_iteration(1))
            o.optimize()
            return o, m
        finally:
            config.clear_property("bigdl.integrity.everyN")

    # -- measured overhead, off vs everyN in {1, 10, 100} ----------------
    o, m = run(0)
    p50_off = o._step_account.summary()["p50_ms"]
    measured = {"off": round(p50_off, 3)}
    for n in (1, 10, 100):
        o, _ = run(n)
        p50 = o._step_account.summary()["p50_ms"]
        measured[f"everyN_{n}"] = round(p50, 3)
        _log(f"integrity p50 everyN={n}: {p50:.3f} ms "
             f"(off: {p50_off:.3f} ms)")

    # -- modeled fingerprint cost by cadence -----------------------------
    params = m.params
    slots = optim.SGD(learning_rate=0.1, momentum=0.9).slots(params)
    seed = integrity.DEFAULT_SEED

    @jax.jit
    def fp_fn(p, s):
        return (integrity.fingerprint_tree(p, seed),
                integrity.fingerprint_tree(s,
                                           seed + integrity.SLOT_SEED_OFF))

    jax.block_until_ready(fp_fn(params, slots))  # compile outside the clock
    reps = 50
    t0 = time.perf_counter_ns()
    for _ in range(reps):
        out = fp_fn(params, slots)
    jax.block_until_ready(out)
    fp_ms = (time.perf_counter_ns() - t0) / reps / 1e6
    modeled = {
        f"everyN_{n}": round(fp_ms / (n * p50_off) * 100, 4)
        for n in (1, 10, 100)}
    default_cadence = 100
    overhead_at_default = modeled[f"everyN_{default_cadence}"]
    _log(f"fingerprint fn: {fp_ms:.4f} ms; modeled overhead {modeled} % "
         f"(default cadence everyN={default_cadence})")
    assert overhead_at_default < 1.0, (
        f"modeled fingerprint overhead {overhead_at_default:.3f}% at "
        f"everyN={default_cadence} breaches the 1% budget")

    # -- detection-to-heal latency for one injected bit flip -------------
    config.set_property("bigdl.chaos.bitflipParamAt", "4:2")
    chaos.install()
    try:
        with tempfile.TemporaryDirectory() as tmp:
            run(1, ckpt=tmp, iters=12)
    finally:
        chaos.uninstall()
        config.clear_property("bigdl.chaos.bitflipParamAt")
    heal_ms = telemetry.gauge("Integrity/heal_ms").value
    desyncs = telemetry.counter("Integrity/desync_detected").value
    assert desyncs >= 1, "injected bit flip was never detected"
    _log(f"bitflip at iteration 4: detected {int(desyncs)} desync(s), "
         f"heal {heal_ms:.2f} ms")

    record = {
        "devices": n_dev,
        "measured_p50_step_ms": measured,
        "fingerprint_fn_ms": round(fp_ms, 4),
        "modeled_overhead_pct": modeled,
        "default_cadence": default_cadence,
        "overhead_at_default_pct": overhead_at_default,
        "heal": {"detect_iterations": 1, "heal_ms": round(heal_ms, 3),
                 "desyncs_detected": int(desyncs)},
        "note": "CPU virtual-mesh rehearsal: the armed step fingerprints "
                "every iteration (cadence throttles only the driver "
                "pull), so measured overhead is cadence-flat; the "
                "modeled row amortizes the jitted fingerprint cost over "
                "a cadence-gated deployment",
    }
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_integrity.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    _log(f"integrity record -> {out_path}")
    return record


def bench_resources(steps: int = 60, out_path: str = None):
    """``--resources-only``: the resource-exhaustion resilience leg →
    bench_resources.json.

    Four numbers on the single-device CPU rig (absolute times are CPU
    times — the RATIOS transfer):

    - **preflight overhead** — the HBM preflight runs once per compile
      (``compiled.memory_analysis()`` checked against
      ``bigdl.resources.deviceMemBudgetMB``), never per step.  Measured
      directly on a compiled probe program, charged in full against ONE
      step p50 (the worst case) and amortized over the run — both
      asserted < 1%.  The measured budget-off vs budget-armed step p50
      A/B rides along for the record (no assert: CPU noise exceeds a
      per-compile cost paid once);
    - **OOM-detect-to-replanned-step latency** — one injected dispatch
      ``RESOURCE_EXHAUSTED`` (``bigdl.chaos.oomStepAt``): wall time from
      the classified raise to the re-planned k-chunk step ready to
      dispatch (``Resources/oom_replan_ms``: re-plan + snapshot
      restore), plus the landed accumulation depth;
    - **governor accounting overhead** — the hot-loop cost of one
      ``Account.add``/``sub`` pair (every bounded-buffer put/get pays
      exactly this), expressed against step p50 at a generous
      16 ops/step;
    - **disk-full degradation throughput** — the identical checkpointed
      trainer clean vs ``bigdl.chaos.diskFullAt`` degraded (checkpoints
      fall back to in-memory snapshots): degraded step p50 must be
      within 5% of clean — full disk never slows training down.
    """
    import statistics
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    from bigdl_tpu import telemetry
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
    from bigdl_tpu.dataset.datasets import synthetic_separable
    from bigdl_tpu.resources import GOVERNOR, storage
    from bigdl_tpu.resources import device as rdevice
    from bigdl_tpu.utils import chaos, config

    samples = synthetic_separable(256, 16, n_classes=4, seed=3)
    config.set_property("bigdl.failure.retryTimeInterval", 0.0)

    def mlp():
        # wide enough that the step is compute-bound on CPU (same rationale
        # as the integrity leg): the p50 A/B deltas must not be dominated
        # by fixed dispatch cost
        m = (nn.Sequential().add(nn.Linear(16, 1024)).add(nn.Tanh())
             .add(nn.Linear(1024, 256)).add(nn.Tanh())
             .add(nn.Linear(256, 4)).add(nn.LogSoftMax()))
        m.reset(jax.random.PRNGKey(11))
        return m

    def run(iters=steps, ckpt=None, budget_mb=0):
        if budget_mb:
            config.set_property("bigdl.resources.deviceMemBudgetMB",
                                budget_mb)
        try:
            m = mlp()
            ds = LocalDataSet(samples).transform(SampleToMiniBatch(256))
            o = optim.Optimizer.create(m, ds, nn.ClassNLLCriterion())
            o.set_optim_method(optim.SGD(learning_rate=0.1, momentum=0.9))
            o.set_end_when(optim.max_iteration(iters))
            if ckpt:
                o.set_checkpoint(str(ckpt), optim.several_iteration(1))
            o.optimize()
            return o, m
        finally:
            config.clear_property("bigdl.resources.deviceMemBudgetMB")

    # -- preflight: direct cost + measured A/B ---------------------------
    o, _ = run()
    p50_off = o._step_account.summary()["p50_ms"]
    o, _ = run(budget_mb=8192)
    p50_armed = o._step_account.summary()["p50_ms"]
    peak = telemetry.gauge("Resources/device_peak_bytes",
                           labels={"step": "local"}).value

    # the preflight itself, timed on a compiled probe of comparable rank:
    # budget_bytes() + memory_analysis() + the gauge export
    lowered = jax.jit(
        lambda x: jnp.tanh(x @ x.T).sum()).lower(
            jax.ShapeDtypeStruct((1024, 1024), jnp.float32))
    probe = lowered.compile()
    config.set_property("bigdl.resources.deviceMemBudgetMB", 8192)
    try:
        rdevice.preflight(probe, "bench_probe")  # warm the import path
        reps = 200
        t0 = time.perf_counter_ns()
        for _ in range(reps):
            rdevice.preflight(probe, "bench_probe")
        preflight_ms = (time.perf_counter_ns() - t0) / reps / 1e6
    finally:
        config.clear_property("bigdl.resources.deviceMemBudgetMB")
    worst_pct = preflight_ms / p50_off * 100          # whole cost on 1 step
    amortized_pct = worst_pct / steps                 # once per compile
    _log(f"preflight: {preflight_ms:.4f} ms/compile = {worst_pct:.4f}% of "
         f"one step p50 ({p50_off:.3f} ms), {amortized_pct:.5f}% amortized "
         f"over {steps} steps; armed p50 {p50_armed:.3f} ms, peak estimate "
         f"{int(peak)} bytes")
    assert worst_pct < 1.0, (
        f"preflight {preflight_ms:.4f} ms is {worst_pct:.2f}% of step p50 "
        f"({p50_off:.3f} ms) — breaches the 1% budget even as a "
        "once-per-compile cost")

    # -- OOM detection -> re-planned step --------------------------------
    config.set_property("bigdl.chaos.oomStepAt", 2)
    chaos.install()
    try:
        with tempfile.TemporaryDirectory(suffix="_benchckpt") as tmp:
            o, _ = run(iters=8, ckpt=tmp)
        assert chaos._state.oom_fired == 1, "injected OOM never fired"
    finally:
        chaos.uninstall()
        config.clear_property("bigdl.chaos.oomStepAt")
    replan_ms = telemetry.gauge("Resources/oom_replan_ms").value
    landed_k = int(telemetry.gauge("Resources/microbatch_k").value)
    sent = o._retrace_sentinel
    assert landed_k > 1, "OOM did not land a microbatch re-plan"
    assert sent is None or sent.retraces == 0, (
        "the re-planned step tripped the post-warmup retrace gate")
    _log(f"injected OOM at dispatch 2: re-plan + restore {replan_ms:.2f} ms"
         f", landed k={landed_k}, post-warmup retraces 0")

    # -- governor accounting hot loop ------------------------------------
    acc = GOVERNOR.account("bench_probe")
    reps = 100_000
    t0 = time.perf_counter_ns()
    for _ in range(reps):
        acc.add(4096)
        acc.sub(4096)
    pair_ns = (time.perf_counter_ns() - t0) / reps
    GOVERNOR.reset()
    governor_pct = 16 * pair_ns / 1e6 / p50_off * 100
    _log(f"governor accounting: {pair_ns:.0f} ns per add/sub pair = "
         f"{governor_pct:.4f}% of step p50 at 16 ops/step")

    # -- disk-full degradation throughput --------------------------------
    storage.reset()
    with tempfile.TemporaryDirectory(suffix="_benchckpt") as tmp:
        o, _ = run(ckpt=tmp)
        p50_clean = o._step_account.summary()["p50_ms"]
    config.set_property("bigdl.chaos.diskFullAt", "1:benchckpt")
    chaos.install()
    try:
        with tempfile.TemporaryDirectory(suffix="_benchckpt") as tmp:
            o, _ = run(ckpt=tmp)
            p50_degraded = o._step_account.summary()["p50_ms"]
        assert chaos._state.disk_full_fired >= 1, "disk-full never fired"
        assert storage.is_degraded("checkpoints"), (
            "checkpoints did not degrade to memory snapshots")
    finally:
        chaos.uninstall()
        config.clear_property("bigdl.chaos.diskFullAt")
        storage.reset()
    delta_pct = (p50_degraded - p50_clean) / p50_clean * 100
    _log(f"disk-full degradation: clean p50 {p50_clean:.3f} ms, degraded "
         f"{p50_degraded:.3f} ms ({delta_pct:+.2f}%)")
    assert p50_degraded <= p50_clean * 1.05, (
        f"degraded-mode step p50 {p50_degraded:.3f} ms is more than 5% "
        f"over clean {p50_clean:.3f} ms — full disk slowed training down")

    record = {
        "metric": "resources_diskfull_p50_delta_pct",
        "value": round(delta_pct, 3),
        "unit": "%",
        "preflight": {
            "preflight_ms_per_compile": round(preflight_ms, 4),
            "worst_case_pct_of_step_p50": round(worst_pct, 4),
            "amortized_pct_over_run": round(amortized_pct, 5),
            "p50_budget_off_ms": round(p50_off, 3),
            "p50_budget_armed_ms": round(p50_armed, 3),
            "peak_estimate_bytes": int(peak),
        },
        "oom_backoff": {
            "detect_to_replanned_step_ms": round(replan_ms, 3),
            "landed_microbatch_k": landed_k,
            "post_warmup_retraces": 0,
        },
        "governor": {
            "account_pair_ns": round(pair_ns, 1),
            "pct_of_step_p50_at_16_ops": round(governor_pct, 5),
        },
        "disk_full": {
            "p50_clean_ms": round(p50_clean, 3),
            "p50_degraded_ms": round(p50_degraded, 3),
            "delta_pct": round(delta_pct, 3),
        },
        "note": "single-device CPU rig: preflight is a once-per-compile "
                "memory_analysis() check (charged worst-case against one "
                "step and amortized), the OOM leg injects a dispatch "
                "RESOURCE_EXHAUSTED and times detection to the re-planned "
                "accumulation step, disk-full compares the identical "
                "checkpointed trainer clean vs degraded-to-RAM-snapshots",
    }
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_resources.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    _log(f"resources record -> {out_path}")
    return record


def bench_overlap(steps: int = 40, out_path: str = None):
    """``--overlap-only``: the latency-hiding collective leg →
    bench_overlap.json.

    Two claims of the bucketed schedule, measured on the virtual
    8-device CPU mesh (the tier-1 configuration — absolute numbers are
    CPU numbers; the artifact's value is the A/B deltas and the
    decomposition, both of which transfer):

    - **overlap off vs on, at several bucket counts** — the identical
      transformer-LM trainer with ``bigdl.parallel.overlap=false``
      (monolithic reduce-scatter / update / all-gather) and with the
      per-bucket chains at 2/4/8 buckets: p50 step time, tokens/s, an
      MFU estimate, and the StepAccount decomposition
      (data-wait/compute/host-pull fractions).  The bucketed schedule
      is a pure reordering (weights proven bit-equal in
      tests/test_overlap.py), so any p50 regression beyond noise is a
      scheduling loss — asserted within a CPU-noise tolerance.
    - **grouped vs einsum MoE, vs dense** — the same MixtureOfExperts
      layer forwarded token-identically under ``bigdl.moe.impl=einsum``
      (the (t, E, C) one-hot dispatch/combine einsums) and ``grouped``
      (expert-sorted scatter/gather + one grouped batched matmul), with
      a dense equal-per-token-FLOPs FFN as the no-routing reference.
      The einsum path pays O(t*E*C*d) dispatch FLOPs where grouped pays
      O(t*k*d) data movement — grouped must not lose.
    """
    import statistics

    import jax
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.transformer import transformer_lm
    from bigdl_tpu.nn.moe import MixtureOfExperts
    from bigdl_tpu.utils import config

    n_dev = len(jax.devices())
    if n_dev < 4:
        raise SystemExit(
            "--overlap-only needs a multi-device mesh (found "
            f"{n_dev}). jax was initialized before the leg could force "
            "the virtual CPU mesh — run bench.py --overlap-only as its "
            "own invocation (XLA_FLAGS="
            "--xla_force_host_platform_device_count=8).")

    # -- LM step time, overlap off vs on ---------------------------------
    v, d, nl, h, t, b = 256, 64, 2, 4, 32, 64
    rng = np.random.RandomState(0)
    samples = [Sample(rng.randint(1, v + 1, t).astype(np.float32),
                      rng.randint(1, v + 1, t).astype(np.float32))
               for _ in range(b * 2)]
    crit = nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)

    def run_lm(overlap, buckets=None):
        config.set_property("bigdl.parallel.overlap",
                            "true" if overlap else "false")
        if buckets is not None:
            config.set_property("bigdl.parallel.overlapBuckets",
                                str(buckets))
        try:
            m = transformer_lm(v, d_model=d, n_head=h, n_layers=nl,
                               max_len=t)
            m.reset(jax.random.PRNGKey(3))
            ds = ShardedDataSet(samples, n_dev).transform(
                SampleToMiniBatch(b, n_dev))
            o = optim.Optimizer.create(m, ds, crit)
            o.set_optim_method(optim.Adam(learning_rate=1e-3))
            o.set_end_when(optim.max_iteration(steps))
            o.optimize()
            n_params = sum(int(np.prod(np.shape(l)))
                           for l in jax.tree_util.tree_leaves(m.params))
            return o._step_account.summary(), n_params
        finally:
            config.clear_property("bigdl.parallel.overlap")
            config.clear_property("bigdl.parallel.overlapBuckets")

    def lm_point(label, summ, n_params):
        # p50 over the run's rolling window — robust against the
        # compile-bearing first step that skews the mean
        p50 = summ["p50_ms"]
        toks = b * t / (p50 / 1e3)
        # training matmul FLOPs/token (same formula as the bench_lm leg);
        # bf16 peak of one v5e chip ~197 TFLOP/s — on THIS CPU rig the
        # number is tiny, it is recorded so the off/on DELTA is readable
        # in the same unit the TPU leg uses
        mfu = toks * (6 * n_params + 12 * nl * d * t) / 197e12
        point = {"label": label, "p50_step_ms": round(p50, 3),
                 "mean_step_ms": round(summ["mean_step_ms"], 3),
                 "tokens_per_sec": round(toks, 1),
                 "mfu_v5e_equiv": round(mfu, 8),
                 "decomposition": {
                     k: round(summ[f"{k}_frac"], 4)
                     for k in ("data_wait", "compute", "host_pull",
                               "bookkeeping", "unaccounted")
                     if f"{k}_frac" in summ}}
        _log(f"overlap {label}: p50 {p50:.2f} ms/step = {toks:,.0f} tok/s "
             f"({point['decomposition']})")
        return point

    summ, n_params = run_lm(overlap=False)
    baseline = lm_point("baseline_monolithic", summ, n_params)
    lm_points = [baseline]
    for nb in (1, 2, 4):
        summ, n_params = run_lm(overlap=True, buckets=nb)
        lm_points.append(lm_point(f"overlap_{nb}_buckets", summ, n_params))

    best = max(lm_points[1:], key=lambda p: p["tokens_per_sec"])
    # The assertable CPU claim is about the TUNED schedule: across the
    # swept bucket counts the overlap family must not lose to the
    # monolithic baseline (medians over `steps` iterations, small noise
    # tolerance).  On this rig the tuned count is 1: a virtual 8-device
    # mesh multiplexes onto the host's cores (often ONE core in CI), so
    # every extra collective is a full 8-thread rendezvous round-robin
    # with nothing concurrent to hide it under — the >1-bucket points
    # measure exactly that scheduling tax (it is proportional to step
    # time: each barrier waits out the device threads' skew).  On real
    # ICI the same sweep moves the knee up — that is the tuning story
    # the optimization guide tells.
    ratio = best["tokens_per_sec"] / baseline["tokens_per_sec"]
    assert ratio >= 0.95, (
        f"overlapped schedule (tuned over bucket counts) lost to the "
        f"monolithic baseline beyond noise: best {best['label']} at "
        f"{ratio:.3f}x")
    _log(f"overlap best: {best['label']} at {ratio:.3f}x of monolithic")

    # -- grouped vs einsum MoE, vs dense ---------------------------------
    D, E, toks_moe = 64, 8, 4096
    expert = (nn.Sequential().add(nn.Linear(D, 2 * D)).add(nn.ReLU())
              .add(nn.Linear(2 * D, D)))
    moe = MixtureOfExperts(D, expert, E, capacity_factor=1.25)
    moe.reset(jax.random.PRNGKey(7))
    dense = (nn.Sequential().add(nn.Linear(D, 2 * D)).add(nn.ReLU())
             .add(nn.Linear(2 * D, D)))
    dense.reset(jax.random.PRNGKey(7))
    x = jnp.asarray(np.random.RandomState(1)
                    .normal(size=(toks_moe, D)).astype(np.float32))

    def timed(fn, repeats=30):
        fn(x).block_until_ready()              # compile outside the clock
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fn(x).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    def moe_fwd(impl):
        config.set_property("bigdl.moe.impl", impl)
        try:
            f = jax.jit(lambda xx: moe.apply(moe.params, xx, moe.state)[0])
            return timed(f)
        finally:
            config.clear_property("bigdl.moe.impl")

    s_einsum = moe_fwd("einsum")
    s_grouped = moe_fwd("grouped")
    s_dense = timed(jax.jit(
        lambda xx: dense.apply(dense.params, xx, dense.state)[0]))
    moe_rec = {
        "tokens": toks_moe, "d_model": D, "n_experts": E,
        "capacity_factor": 1.25,
        "einsum_tokens_per_sec": round(toks_moe / s_einsum, 1),
        "grouped_tokens_per_sec": round(toks_moe / s_grouped, 1),
        "dense_ffn_tokens_per_sec": round(toks_moe / s_dense, 1),
        "grouped_vs_einsum": round(s_einsum / s_grouped, 3),
        "grouped_vs_dense": round(s_dense / s_grouped, 3),
    }
    _log(f"moe fwd ({toks_moe} tok, E{E} d{D}): einsum "
         f"{moe_rec['einsum_tokens_per_sec']:,.0f} tok/s, grouped "
         f"{moe_rec['grouped_tokens_per_sec']:,.0f} tok/s "
         f"({moe_rec['grouped_vs_einsum']:.2f}x), dense "
         f"{moe_rec['dense_ffn_tokens_per_sec']:,.0f} tok/s")
    assert moe_rec["grouped_vs_einsum"] >= 0.95, (
        "grouped MoE lost to the dispatch/combine einsums: "
        f"{moe_rec['grouped_vs_einsum']:.3f}x")

    record = {
        "metric": "overlap_best_vs_baseline",
        "value": round(ratio, 3), "unit": "x",
        "lm": {"config": {"batch": b, "seq_len": t, "d_model": d,
                          "n_layers": nl, "n_head": h, "vocab": v,
                          "devices": n_dev, "optim": "adam"},
               "points": lm_points,
               "best": best["label"]},
        "moe": moe_rec,
        "note": "virtual-CPU A/B: the schedule is weight-parity-proven "
                "(tests/test_overlap.py), so the leg's job is the cost "
                "model. The >1-bucket points price the per-collective "
                "rendezvous on a core-starved virtual mesh (all device "
                "threads must meet at every RS/AG — with one host core "
                "that is pure serialization tax, proportional to step "
                "time); the asserted claim is that the TUNED bucket "
                "count never loses to the monolithic baseline. On real "
                "ICI the per-bucket chains give XLA's latency-hiding "
                "scheduler independent RS->update->AG chains to overlap "
                "with compute and the optimum moves to several buckets "
                "of a few MiB each (see the optimization guide).",
    }
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_overlap.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    _log(f"overlap record -> {out_path}")
    return record


def bench_compile_probe(cache_dir: str, out_path: str) -> None:
    """Child process of ``--compile-only``: one full trainer+validation
    lifecycle against the given executable cache (``bigdl.compile.
    cacheDir``), reporting per-fused-step compile/load provenance.  Run
    once against an empty directory (the cold start) and once more (the
    warm start) — a REAL second process, which is exactly the claim the
    persistent cache makes: the warm process reaches its first device
    step with zero fresh compiles and bit-identical step results."""
    import jax
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.dataset.transformer import SampleToMiniBatch
    from bigdl_tpu.optim.optimizer import Optimizer
    from bigdl_tpu.optim.evaluator import evaluate_dataset
    from bigdl_tpu.optim.validation_method import Top1Accuracy
    from bigdl_tpu.utils import config
    from bigdl_tpu.utils.random_generator import RandomGenerator
    from bigdl_tpu.visualization.crc32c import crc32c

    config.set_property("bigdl.compile.cacheDir", cache_dir)
    config.set_property("bigdl.compile.buckets", "8,16")
    config.set_property("bigdl.analysis.retrace", "strict")
    RandomGenerator.RNG().set_seed(1234)
    rng = np.random.RandomState(0)
    samples = [Sample(rng.normal(size=(8,)).astype(np.float32),
                      np.int64(i % 3 + 1)) for i in range(64)]
    m = (nn.Sequential().add(nn.Linear(8, 32)).add(nn.Tanh())
         .add(nn.Linear(32, 3)).add(nn.LogSoftMax()))
    m.reset(jax.random.PRNGKey(7))
    o = Optimizer.create(m, samples, nn.ClassNLLCriterion(), batch_size=16)
    o.set_optim_method(optim.SGD(learning_rate=0.1))
    o.set_end_when(optim.max_iteration(6))
    t0 = time.perf_counter()
    o.optimize()
    train_wall_s = time.perf_counter() - t0
    train_step = getattr(o._step_fn, "__wrapped__", o._step_fn)

    # ragged validation (57 records -> 16,16,16,9) through the bucketed
    # eval forward, under the strict retrace sentinel
    t0 = time.perf_counter()
    evaluate_dataset(m, list(SampleToMiniBatch(16)(iter(samples[:57]))),
                     [Top1Accuracy()])
    eval_wall_s = time.perf_counter() - t0
    eval_fn = m._eval_jit[id(None)]
    eval_step = getattr(eval_fn, "__wrapped__", eval_fn)
    sentinel = getattr(eval_fn, "sentinel", None)

    weights = np.concatenate([np.ravel(np.asarray(x))
                              for x in jax.tree_util.tree_leaves(m.params)])
    gauges = telemetry.REGISTRY.snapshot()["gauges"]

    def leg(step):
        return {"hits": step.cache_hits, "misses": step.cache_misses,
                "compiles": step.compiles, "timings": step.timings}

    record = {
        "steps": {"train/local": leg(train_step), "eval": leg(eval_step)},
        "warmup_ms": round(gauges.get("Compile/warmup_ms", 0.0), 3),
        "train_wall_s": round(train_wall_s, 3),
        "eval_wall_s": round(eval_wall_s, 3),
        "eval_retraces": sentinel.retraces if sentinel is not None else None,
        "weights_crc": int(crc32c(weights.tobytes())),
    }
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)


def bench_compile(out_path: str = None):
    """``--compile-only``: the resilient-compilation leg →
    bench_compile.json.

    - **cold vs warm start, across real processes** — the same trainer +
      ragged bucketed validation runs in two child processes over one
      cache directory; the record keeps per-fused-step trace/compile vs
      load provenance and ASSERTS the warm-start contract: zero warm
      misses, warm hit count == cold compiled-signature count, warm
      compile-phase time < 0.5x cold, bit-identical trained weights.
    - **watchdog detection latency** — ``bigdl.chaos.hangCompileAt``
      wedges a compile; the leg records how far past
      ``bigdl.compile.timeoutSec`` the monitor fired.
    """
    import shutil
    import subprocess
    import tempfile
    here = os.path.dirname(os.path.abspath(__file__))
    cache_dir = tempfile.mkdtemp(prefix="bench_ccache_")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    def probe(tag):
        out = os.path.join(cache_dir, f"probe_{tag}.json")
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "bench.py", "--compile-probe", cache_dir, out],
            cwd=here, env=env, capture_output=True, text=True, timeout=600)
        wall = time.perf_counter() - t0
        assert proc.returncode == 0, proc.stdout + proc.stderr
        with open(out) as f:
            rec = json.load(f)
        rec["process_wall_s"] = round(wall, 2)
        return rec

    try:
        cold = probe("cold")
        warm = probe("warm")
    finally:
        # serialized executables are not small; repeated bench runs must
        # not strand a bench_ccache_* per invocation (the probe records
        # land in bench_compile.json, nothing in the dir outlives this)
        shutil.rmtree(cache_dir, ignore_errors=True)

    def total(rec, key):
        return sum(rec["steps"][s][key] for s in rec["steps"])

    def phase_ms(rec):
        return sum(t.get("trace_ms", 0) + t.get("compile_ms", 0) +
                   t.get("load_ms", 0)
                   for s in rec["steps"].values() for t in s["timings"])

    cold_misses, warm_misses = total(cold, "misses"), total(warm, "misses")
    warm_hits = total(warm, "hits")
    cold_ms, warm_ms = phase_ms(cold), phase_ms(warm)
    assert total(cold, "hits") == 0 and cold_misses >= 3
    assert warm_misses == 0 and total(warm, "compiles") == 0, \
        "warm start must skip compilation entirely"
    assert warm_hits == cold_misses, \
        "every cold-compiled fused-step signature must warm-load"
    assert warm["weights_crc"] == cold["weights_crc"], \
        "warm-start step results must be bit-identical"
    assert cold["eval_retraces"] == 0 and warm["eval_retraces"] == 0, \
        "bucketed ragged validation must stay retrace-free"
    assert warm_ms < 0.5 * cold_ms, \
        f"warm compile phase {warm_ms:.0f} ms not < 0.5x cold {cold_ms:.0f} ms"
    _log(f"compile cold: {cold_misses} compiles, {cold_ms:.0f} ms; warm: "
         f"{warm_hits} cache hits, {warm_ms:.0f} ms "
         f"({cold_ms / max(warm_ms, 1e-9):.1f}x faster)")

    # -- watchdog detection latency under a wedged compile ---------------
    from bigdl_tpu import telemetry
    from bigdl_tpu.utils import chaos, compile_cache, config
    timeout_s = 0.5
    config.set_property("bigdl.compile.timeoutSec", timeout_s)
    config.set_property("bigdl.chaos.hangCompileAt", "1:3.0")
    chaos.install()
    fired_before = telemetry.REGISTRY.counter(
        "Compile/watchdog_fired").value
    t0 = time.perf_counter()
    try:
        step = compile_cache.tracked_jit(lambda x: x * 2, label="wedge")
        try:
            step(np.ones((4,), np.float32))
            raise AssertionError("hangCompileAt did not wedge the compile")
        except compile_cache.CompileTimeoutError:
            pass
    finally:
        chaos.uninstall()
        config.clear_property("bigdl.compile.timeoutSec")
        config.clear_property("bigdl.chaos.hangCompileAt")
    abort_wall_s = time.perf_counter() - t0
    fired = telemetry.REGISTRY.counter(
        "Compile/watchdog_fired").value - fired_before
    assert fired == 1, f"compile watchdog fired {fired} times, expected 1"
    detect_ms = telemetry.REGISTRY.snapshot()["gauges"][
        "Compile/watchdog_detect_ms"]
    watchdog = {
        "timeout_s": timeout_s,
        "detect_past_threshold_ms": round(detect_ms, 3),
        "abort_wall_s": round(abort_wall_s, 3),
    }
    _log(f"compile watchdog: wedge detected {detect_ms:.0f} ms past the "
         f"{timeout_s:.1f}s timeout, aborted at {abort_wall_s:.2f}s "
         f"(wedge span 3.0s)")

    record = {
        "cold": cold,
        "warm": warm,
        "warm_start": {
            "cold_compile_signatures": cold_misses,
            "warm_cache_hits": warm_hits,
            "cold_compile_phase_ms": round(cold_ms, 1),
            "warm_load_phase_ms": round(warm_ms, 1),
            "speedup": round(cold_ms / max(warm_ms, 1e-9), 1),
            "bit_identical": True,
        },
        "watchdog": watchdog,
        "note": "two real processes over one cache dir; compile times are "
                "CPU-backend small-model floors — the ratio and the "
                "zero-miss warm contract are the transferable claims",
    }
    out_path = out_path or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_compile.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    _log(f"compile record -> {out_path}")
    return record


def bench_serving(out_path: str = None, soak: bool = False,
                  write: bool = True):
    """``--serving-only``: the overload-tolerant serving leg →
    bench_serving.json.

    - **calibrated Poisson open loop** — arrival rate pinned well under
      the measured batch-service capacity; ASSERTS p99 request latency
      ≤ ``bigdl.serving.deadlineMs`` and the accounting identity
      (completed + shed + rejected + quarantined == submitted, zero
      unaccounted).
    - **overload burst** — back-to-back arrivals against a small
      admission queue; ASSERTS rejections happen (reject-at-the-door),
      reject latency ≪ the deadline (no silent tail-latency collapse),
      and the identity again.
    - ``soak=True`` (the slow-marked test variant) runs ~10x the
      requests at the calibrated rate.
    """
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.serving import ServingEngine, run_open_loop
    from bigdl_tpu.utils import config

    deadline_ms = 250.0
    max_batch = 8
    din, dout = 16, 8
    keys = {"bigdl.compile.buckets": "2,4,8",
            "bigdl.serving.maxBatch": max_batch,
            "bigdl.serving.deadlineMs": deadline_ms}
    for k, v in keys.items():
        config.set_property(k, v)
    try:
        model = (nn.Sequential().add(nn.Linear(din, 64)).add(nn.Tanh())
                 .add(nn.Linear(64, dout)))
        model.reset(jax.random.PRNGKey(0))

        def payloads(n, seed):
            r = np.random.default_rng(seed)
            return list(r.standard_normal((n, din)).astype(np.float32))

        # -- capacity probe: warmed FULL-batch service time, measured
        # directly (the submit path would mostly dispatch sub-full
        # batches with lingerMs=0, and the warmup-minimum EMA would then
        # report small-bucket cost — overstating capacity and mis-
        # calibrating the rate below)
        eng = ServingEngine(model)
        eng.warmup(np.zeros((din,), np.float32))
        full = np.stack(payloads(max_batch, 1))
        for _ in range(3):
            eng._run_forward(full)                     # warm
        t0 = time.perf_counter()
        reps = 20
        for _ in range(reps):
            eng._run_forward(full)
        batch_ms = (time.perf_counter() - t0) / reps * 1e3
        capacity_rps = max_batch / (batch_ms / 1e3)
        rate = 0.3 * capacity_rps
        _log(f"serving capacity: {batch_ms:.2f} ms/batch of {max_batch} "
             f"≈ {capacity_rps:.0f} req/s; calibrated open-loop rate "
             f"{rate:.0f} req/s")

        # -- calibrated Poisson open loop ------------------------------
        n = 2000 if soak else 200
        cal = run_open_loop(eng, payloads(n, 2), rate_hz=rate, seed=3)
        eng.close()
        lat = np.asarray(cal["latency_ms"])
        assert cal["unaccounted"] == 0, cal
        assert cal["completed"] == n, \
            f"calibrated leg must complete everything: {cal}"
        p50, p95, p99 = (float(np.percentile(lat, q)) for q in (50, 95, 99))
        assert p99 <= deadline_ms, \
            f"p99 {p99:.1f} ms > deadline {deadline_ms} ms at the " \
            f"calibrated rate {rate:.0f} req/s"
        calibrated = {
            "requests": n, "rate_rps": round(rate, 1),
            "completed": cal["completed"],
            "p50_ms": round(p50, 3), "p95_ms": round(p95, 3),
            "p99_ms": round(p99, 3),
        }
        _log(f"serving calibrated: {n} reqs @ {rate:.0f}/s -> "
             f"p50 {p50:.2f} / p95 {p95:.2f} / p99 {p99:.2f} ms")

        # -- overload burst: reject fast at the door -------------------
        eng = ServingEngine(model, max_queue_depth=16)
        eng.warmup(np.zeros((din,), np.float32))
        m = 300
        burst = run_open_loop(eng, payloads(m, 4), rate_hz=0.0, seed=5)
        eng.close()
        assert burst["unaccounted"] == 0, burst
        assert burst["rejected"] > 0, \
            "an overload burst must produce admission rejections"
        rej = np.asarray(burst["reject_latency_ms"])
        rej_mean, rej_max = float(rej.mean()), float(rej.max())
        assert rej_mean < deadline_ms / 10, \
            f"mean reject latency {rej_mean:.2f} ms is not ≪ the " \
            f"{deadline_ms} ms deadline"
        overload = {
            "requests": m,
            "completed": burst["completed"], "shed": burst["shed"],
            "rejected": burst["rejected"],
            "quarantined": burst["quarantined"],
            "reject_latency_mean_ms": round(rej_mean, 4),
            "reject_latency_max_ms": round(rej_max, 4),
        }
        _log(f"serving overload: {m} back-to-back reqs -> "
             f"{burst['rejected']} rejected at "
             f"{rej_mean:.3f} ms mean ({burst['completed']} completed, "
             f"{burst['shed']} shed)")
    finally:
        for k in keys:
            config.clear_property(k)

    record = {
        "deadline_ms": deadline_ms,
        "max_batch": max_batch,
        "batch_service_ms": round(batch_ms, 3),
        "capacity_rps": round(capacity_rps, 1),
        "calibrated": calibrated,
        "overload": overload,
        "soak": soak,
        "note": "CPU-backend small-model floors; the transferable claims "
                "are the identity (zero unaccounted requests), p99 under "
                "deadline at the calibrated rate, and reject-at-the-door "
                "latency two orders under the deadline",
    }
    if write:
        out_path = out_path or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "bench_serving.json")
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
        _log(f"serving record -> {out_path}")
    return record


def bench_lm_serving(out_path: str = None, write: bool = True):
    """``--lm-serving-only``: the LM token-serving leg → the ``"lm"``
    section of bench_serving.json (merged, not overwritten).

    Poisson open-loop mixed-prompt load through the continuous-batching
    scheduler (paged KV cache, one fixed decode shape), against a
    sequential full-generate baseline over the SAME requests.  ASSERTS:

    - continuous batching sustains ≥ 1.5x the sequential baseline's
      tokens/s at equal load;
    - zero post-warmup retraces across prefill AND decode (strict
      sentinels — a retrace would raise, not just count);
    - the accounting identity is exact (completed + shed + rejected +
      quarantined == submitted, zero unaccounted);
    - the int8 decode tier passes the auditor precision gate with
      fp-vs-int8 logits allclose, and serves the same open loop with
      the identity intact.
    """
    import jax
    from bigdl_tpu.models.transformer import transformer_lm
    from bigdl_tpu.serving import (LMServingEngine, run_lm_open_loop,
                                   sample_lm_workload)
    from bigdl_tpu.utils import config

    # large enough that compute dominates dispatch overhead (a tiny
    # d_model makes one decode step cost one full forward and hides
    # the batching win); small enough for a CPU-backend bench
    vocab, d_model, n_head, n_layers = 64, 256, 4, 2
    max_batch, block_size, max_context = 8, 8, 64
    deadline_ms = 60000.0            # throughput leg: nothing may shed
    keys = {"bigdl.analysis.retrace": "strict"}
    for k, v in keys.items():
        config.set_property(k, v)
    try:
        model = transformer_lm(vocab, d_model=d_model, n_head=n_head,
                               n_layers=n_layers, max_len=128)
        model.reset(jax.random.PRNGKey(0))
        eng = LMServingEngine(model, max_batch=max_batch,
                              max_context=max_context,
                              block_size=block_size,
                              deadline_ms=deadline_ms)
        eng.warmup()
        eng.warmup_sequential()

        n = 48
        reqs = sample_lm_workload(n, vocab, seed=7,
                                  prompt_lens=(8, 16, 24, 32),
                                  output_lens=(16, 24, 32))
        total_new = sum(o for _, o in reqs)

        # -- sequential full-generate baseline at equal load -----------
        # one teacher-forced full forward over the growing sequence per
        # emitted token, one request at a time: what serving costs
        # without a decode cache (every bucket pre-warmed above)
        t0 = time.perf_counter()
        base_tokens = 0
        for prompt, max_new in reqs:
            base_tokens += len(eng.generate_sequential(
                prompt, max_new_tokens=max_new))
        base_s = time.perf_counter() - t0
        base_tps = base_tokens / base_s
        _log(f"lm sequential baseline: {base_tokens} tokens in "
             f"{base_s:.2f} s = {base_tps:.0f} tok/s")

        # -- Poisson open loop through the scheduler --------------------
        # arrivals offered at 4x the baseline's request-completion rate
        # so throughput is engine-limited, not arrival-limited — the
        # "equal load" is the identical request set
        rate = 4.0 * n / base_s
        eng.start()
        rec = run_lm_open_loop(eng, reqs, rate_hz=rate, seed=11)
        eng.close()
        assert rec["unaccounted"] == 0, \
            f"accounting identity broken: {rec['submitted']} submitted, " \
            f"{rec['unaccounted']} unaccounted"
        assert rec["completed"] == n, \
            f"throughput leg must complete everything: {rec['completed']}" \
            f"/{n} (shed {rec['shed']}, rejected {rec['rejected']})"
        st = eng.stats()
        assert st["unaccounted"] == 0, st
        retraces = {label: s.retraces
                    for label, s in eng.sentinels.items()}
        assert all(v == 0 for v in retraces.values()), \
            f"post-warmup retraces detected: {retraces}"
        speedup = rec["tokens_per_s"] / base_tps
        assert speedup >= 1.5, \
            f"continuous batching sustained only {speedup:.2f}x the " \
            f"sequential baseline ({rec['tokens_per_s']:.0f} vs " \
            f"{base_tps:.0f} tok/s) — the 1.5x floor is the headline"
        _log(f"lm open loop: {rec['tokens_total']} tokens at "
             f"{rec['tokens_per_s']:.0f} tok/s = {speedup:.2f}x baseline; "
             f"p99 ttft {rec['p99_ttft_ms']:.1f} ms, "
             f"p99 itl {rec['p99_itl_ms']:.2f} ms; retraces {retraces}")

        # -- int8 decode tier: gate + the same load ---------------------
        eng_q = LMServingEngine(model, max_batch=max_batch,
                                max_context=max_context,
                                block_size=block_size,
                                deadline_ms=deadline_ms,
                                quantize="int8")
        gate = dict(eng_q.quantization_report)
        assert gate["audit_ok"] and gate["allclose"], gate
        eng_q.warmup()
        eng_q.start()
        rec_q = run_lm_open_loop(eng_q, reqs[:16], rate_hz=rate, seed=13)
        eng_q.close()
        assert rec_q["unaccounted"] == 0, rec_q
        assert rec_q["completed"] == 16, rec_q
        retraces_q = {label: s.retraces
                      for label, s in eng_q.sentinels.items()}
        assert all(v == 0 for v in retraces_q.values()), retraces_q
        _log(f"lm int8 tier: gate max |logp diff| "
             f"{gate['max_abs_diff']:.3g} (rtol {gate['rtol']}, atol "
             f"{gate['atol']}); {rec_q['tokens_per_s']:.0f} tok/s over "
             f"{rec_q['completed']} requests, retraces {retraces_q}")
    finally:
        for k in keys:
            config.clear_property(k)

    record = {
        "model": {"vocab": vocab, "d_model": d_model, "n_head": n_head,
                  "n_layers": n_layers},
        "max_batch": max_batch, "block_size": block_size,
        "max_context": max_context,
        "requests": n, "tokens_requested": total_new,
        "sequential": {"tokens": base_tokens,
                       "elapsed_s": round(base_s, 3),
                       "tokens_per_s": round(base_tps, 1)},
        "open_loop": {"rate_rps": round(rate, 1),
                      "completed": rec["completed"],
                      "tokens": rec["tokens_total"],
                      "tokens_per_s": round(rec["tokens_per_s"], 1),
                      "speedup_x": round(speedup, 2),
                      "p50_ttft_ms": round(rec["p50_ttft_ms"], 3),
                      "p99_ttft_ms": round(rec["p99_ttft_ms"], 3),
                      "p50_itl_ms": round(rec["p50_itl_ms"], 3),
                      "p99_itl_ms": round(rec["p99_itl_ms"], 3)},
        "retraces": retraces,
        "int8": {"audit_ok": gate["audit_ok"],
                 "allclose": gate["allclose"],
                 "max_abs_diff": round(gate["max_abs_diff"], 6),
                 "rtol": gate["rtol"], "atol": gate["atol"],
                 "tokens_per_s": round(rec_q["tokens_per_s"], 1),
                 "retraces": retraces_q},
        "note": "CPU-backend tiny-model floors; the transferable claims "
                "are the identity (zero unaccounted streams), zero "
                "post-warmup retraces under strict sentinels across "
                "mixed prompt lengths, the >= 1.5x continuous-batching "
                "floor over sequential full-generate, and the int8 tier "
                "clearing the precision gate",
    }
    if write:
        out_path = out_path or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "bench_serving.json")
        merged = {}
        if os.path.exists(out_path):
            try:
                with open(out_path) as f:
                    merged = json.load(f)
            except (OSError, ValueError):
                merged = {}
        merged["lm"] = record
        with open(out_path, "w") as f:
            json.dump(merged, f, indent=1)
        _log(f"lm serving record -> {out_path} (\"lm\" section)")
    return record


def bench_fleet(out_path: str = None, write: bool = True):
    """``--fleet-only``: the fleet control-plane leg → bench_fleet.json.

    - **cold compile baseline** — one replica built + AOT-warmed against
      an EMPTY compile cache: the cost a version swap would pay without
      warm-loading.
    - **zero-downtime hot swap** — a 2-replica fleet rolls out an
      identical-weights candidate (bit-wise shadow parity) under an
      open-loop request stream.  ASSERTS the rollout-start→cutover swap
      time < 0.5× the cold compile, and ZERO requests lost during the
      clean rollout (nothing shed/quarantined/unaccounted — everything
      completed or was rejected retriably at the door).
    - **rollback on a corrupt candidate** — ``bigdl.chaos.
      corruptCandidateAt`` rots the candidate after fingerprint capture;
      measures rollout-start→rolled-back-report latency and ASSERTS the
      incumbent answers the next request.
    """
    import shutil
    import tempfile
    import threading

    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.fleet import Fleet
    from bigdl_tpu.serving import Overloaded, ServingEngine
    from bigdl_tpu.utils import chaos, config, elastic

    din, dout = 16, 8
    cache_dir = tempfile.mkdtemp(prefix="bench_fleet_cache_")
    keys = {"bigdl.compile.buckets": "2,4,8",
            "bigdl.compile.cacheDir": cache_dir,
            "bigdl.serving.deadlineMs": 2000.0}
    for k, v in keys.items():
        config.set_property(k, v)
    try:
        def mlp(seed=0):
            m = (nn.Sequential().add(nn.Linear(din, 64)).add(nn.Tanh())
                 .add(nn.Linear(64, dout)))
            m.reset(jax.random.PRNGKey(seed))
            return m

        warm_row = np.zeros((din,), np.float32)

        # -- cold baseline: build + AOT warmup with an empty cache -----
        t0 = time.perf_counter()
        eng = ServingEngine(mlp())
        eng.warmup(warm_row)
        cold_ms = (time.perf_counter() - t0) * 1e3
        eng.stop()
        _log(f"fleet cold baseline: build+warm {cold_ms:.1f} ms "
             f"(cache was empty: every bucket compiled fresh)")

        # -- hot swap under load (cache now warm) ----------------------
        elastic.clear_preemption()
        fleet = Fleet(poll_interval=0.02)
        fleet.add_model("svc", mlp(), replicas=2, warm_row=warm_row,
                        engine_kw={"deadline_ms": 2000.0})
        stop_load = threading.Event()
        load_errors = []

        def load():
            rng = np.random.default_rng(11)
            while not stop_load.is_set():
                try:
                    fleet.submit("svc", rng.standard_normal(
                        (din,)).astype(np.float32))
                except Overloaded:
                    pass
                except Exception as e:
                    load_errors.append(e)
                time.sleep(0.002)

        t = threading.Thread(target=load)
        t.start()
        try:
            deadline = time.monotonic() + 10.0
            while (fleet.stats("svc")["completed"] < 10 and
                   time.monotonic() < deadline):
                time.sleep(0.02)
            report = fleet.rollout("svc", mlp(seed=0), parity="bitwise")
        finally:
            stop_load.set()
            t.join(timeout=10)
        assert load_errors == [], load_errors
        assert report.promoted, report.reason
        assert fleet.quiesce(20.0), "fleet ledger failed to quiesce"
        s = fleet.stats("svc")
        lost = s["shed"] + s["quarantined"] + s["unaccounted"]
        assert lost == 0, \
            f"requests lost during a clean rollout: {s}"
        assert report.swap_ms < 0.5 * cold_ms, \
            f"warm swap {report.swap_ms:.1f} ms is not < 0.5x the cold " \
            f"compile {cold_ms:.1f} ms — the candidate did not warm-load"
        deadline = time.monotonic() + 5.0
        while (fleet.stats("svc")["last_swap_to_serve_ms"] is None and
               time.monotonic() < deadline):
            time.sleep(0.02)
        swap_to_serve_ms = fleet.stats("svc")["last_swap_to_serve_ms"]
        fleet.stop()
        hot_swap = {
            "replicas": report.replicas,
            "swap_ms": round(report.swap_ms, 2),
            "prepare_ms": round(report.prepare_ms, 2),
            "shadow_ms": round(report.shadow_ms, 2),
            "drain_ms": round(report.drain_ms, 2),
            "swap_to_first_served_ms": (
                round(swap_to_serve_ms, 2)
                if swap_to_serve_ms is not None else None),
            "parity_checked": report.parity_checked,
            "requests_submitted": s["submitted"],
            "requests_completed": s["completed"],
            "requests_rejected": s["rejected"],
            "requests_lost": lost,
        }
        _log(f"fleet hot swap: cutover in {report.swap_ms:.1f} ms "
             f"({report.swap_ms / cold_ms:.2f}x cold), first served on "
             f"new version +{hot_swap['swap_to_first_served_ms']} ms, "
             f"{lost} lost of {s['submitted']} submitted")

        # -- rollback on a corrupted candidate -------------------------
        elastic.clear_preemption()
        config.set_property("bigdl.chaos.corruptCandidateAt", 1)
        chaos.install()
        try:
            fleet2 = Fleet(poll_interval=0.02)
            fleet2.add_model("svc", mlp(), replicas=1, warm_row=warm_row,
                             engine_kw={"deadline_ms": 2000.0})
            rng = np.random.default_rng(12)
            for _ in range(4):
                fleet2.submit("svc", rng.standard_normal(
                    (din,)).astype(np.float32)).result(timeout=10.0)
            t0 = time.perf_counter()
            rb = fleet2.rollout("svc", mlp(seed=0), parity="bitwise")
            rollback_ms = (time.perf_counter() - t0) * 1e3
            assert rb.rolled_back and "fingerprint" in rb.reason, rb
            # the incumbent answers the very next request
            fleet2.submit("svc", warm_row).result(timeout=10.0)
            assert fleet2.stats("svc")["version"] == "v1"
            fleet2.stop()
        finally:
            chaos.uninstall()
            config.clear_property("bigdl.chaos.corruptCandidateAt")
        rollback = {
            "rollback_ms": round(rollback_ms, 2),
            "reason": "fingerprint",
            "incumbent_served_after": True,
        }
        _log(f"fleet rollback: corrupt candidate refused in "
             f"{rollback_ms:.1f} ms, incumbent never stopped serving")
    finally:
        for k in keys:
            config.clear_property(k)
        elastic.clear_preemption()
        shutil.rmtree(cache_dir, ignore_errors=True)

    record = {
        "cold_compile_ms": round(cold_ms, 2),
        "hot_swap": hot_swap,
        "rollback": rollback,
        "note": "CPU-backend small-model floors; the transferable claims "
                "are warm swap < 0.5x a cold compile (the candidate "
                "warm-loads through the executable cache), zero requests "
                "lost during a clean rollout, and rollback-on-corruption "
                "with the incumbent still serving",
    }
    if write:
        out_path = out_path or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "bench_fleet.json")
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
        _log(f"fleet record -> {out_path}")
    return record


def _probe_cache(cache_dir: str) -> None:
    """Populate ``cache_dir`` with one compile-probe child lifecycle
    (the same hidden ``--compile-probe`` mode the --compile-only leg
    uses).  The audit passes default to warn, so every committed entry
    lands with its program census recorded in the manifest — exactly
    what the offline auditor consumes."""
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    out = os.path.join(cache_dir, "probe.json")
    proc = subprocess.run(
        [sys.executable, "bench.py", "--compile-probe", cache_dir, out],
        cwd=here, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"compile probe failed ({proc.returncode}):\n"
            f"{proc.stdout}{proc.stderr}")


def bench_audit(out_path: str = None):
    """``--audit-only``: the HLO-audit leg → bench_audit.json.

    Populates a probe compile cache in a REAL child process, runs the
    offline auditor over the persisted entries (contract replay + the
    committed ``audit_baselines.json`` regression check), and records
    the per-step census — collective bytes by kind, transpose counts,
    peak-buffer estimates — so the bench trajectory tracks the
    communication budget over time instead of rediscovering it in an
    incident."""
    import shutil
    import tempfile
    from bigdl_tpu.analysis import hlo_audit
    here = os.path.dirname(os.path.abspath(__file__))
    cache_dir = tempfile.mkdtemp(prefix="bench_audit_")
    try:
        _probe_cache(cache_dir)
        # worst entry per fused-step label (bucket variants share a
        # label; the budget tracks the most expensive signature)
        steps = {}
        for name in sorted(os.listdir(cache_dir)):
            if not name.endswith(".commit"):
                continue
            with open(os.path.join(
                    cache_dir, name[:-len(".commit")] + ".json")) as f:
                a = json.load(f).get("audit")
            if a is None:
                continue
            prev = steps.get(a["label"])
            if prev is None or \
                    (a["collective_bytes"], a.get("peak_bytes") or 0) > \
                    (prev["collective_bytes"], prev.get("peak_bytes") or 0):
                steps[a["label"]] = a
        baselines_path = os.path.join(here, "audit_baselines.json")
        baselines = (hlo_audit.load_baselines(baselines_path)
                     if os.path.exists(baselines_path) else None)
        lines, problems = hlo_audit.audit_cache_dir(cache_dir, baselines)
        for ln in lines:
            _log(ln)
        for p in problems:
            _log(f"VIOLATION: {p}")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    record = {
        "steps": steps,
        "problems": problems,
        "baselines_checked": baselines is not None,
        "note": "per-fused-step program census from the probe cache "
                "(worst signature per label); problems non-empty means "
                "a contract or baseline regression",
    }
    out_path = out_path or os.path.join(here, "bench_audit.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    _log(f"audit record -> {out_path}")
    assert not problems, "offline HLO audit found problems:\n" + \
        "\n".join(problems)
    return record


def bench_concurrency(out_path: str = None, write: bool = True):
    """``--concurrency-only``: the lock-witness cost leg →
    bench_concurrency.json.

    - **per-acquire microbench** — plain ``threading.Lock`` vs a factory
      lock disarmed vs armed (strict), ns/acquire each.  Disarmed the
      wrapper is one mode check + delegate; armed it also bumps the
      acquisition-order bookkeeping.
    - **mini serving leg** — a small warmed ServingEngine under the
      armed witness; measures request p50 and reads the witness acquire
      counter to get locks-acquired-per-request.  ASSERTS the armed
      per-request overhead (armed-vs-plain per-acquire delta x acquires
      per request) stays under 1%% of the serving p50, and the disarmed
      delta under 0.1%% (within noise).
    - **static pass wall time** — one full
      ``analysis.concurrency.analyze`` run over the package (the
      preflight cost a CI run pays).
    """
    import threading
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu.analysis import concurrency as conc, lockwitness
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.utils import config

    here = os.path.dirname(os.path.abspath(__file__))

    # -- per-acquire microbench -----------------------------------------
    reps = 200_000

    def per_acquire_ns(lock) -> float:
        t0 = time.perf_counter_ns()
        for _ in range(reps):
            with lock:
                pass
        return (time.perf_counter_ns() - t0) / reps

    lockwitness.disarm()
    lockwitness.reset()
    factory = lockwitness.make_lock("bench.probe")
    per_acquire_ns(threading.Lock())                   # warm the loop
    plain_ns = per_acquire_ns(threading.Lock())
    disarmed_ns = per_acquire_ns(factory)
    lockwitness.arm("strict")
    try:
        armed_ns = per_acquire_ns(factory)
    finally:
        lockwitness.disarm()
        lockwitness.reset()
    _log(f"per-acquire: plain {plain_ns:.0f} ns, disarmed "
         f"{disarmed_ns:.0f} ns, armed {armed_ns:.0f} ns")

    # -- mini serving leg under the armed witness ------------------------
    din, dout = 16, 8
    config.set_property("bigdl.compile.buckets", "1,4")
    try:
        model = (nn.Sequential().add(nn.Linear(din, 64)).add(nn.Tanh())
                 .add(nn.Linear(64, dout)))
        model.reset(jax.random.PRNGKey(0))
        eng = ServingEngine(model)
        eng.warmup(np.zeros((din,), np.float32))
        payload = np.zeros((din,), np.float32)
        for _ in range(10):                            # warm the path
            eng.submit(payload).result(timeout=10.0)
        lockwitness.arm("strict")
        try:
            base = lockwitness.snapshot()["acquires"]
            lat_ms = []
            n_req = 200
            for _ in range(n_req):
                t0 = time.perf_counter_ns()
                eng.submit(payload).result(timeout=10.0)
                lat_ms.append((time.perf_counter_ns() - t0) / 1e6)
            acquires_per_req = (lockwitness.snapshot()["acquires"] -
                                base) / n_req
            violations = lockwitness.snapshot()["violations"]
        finally:
            lockwitness.disarm()
            lockwitness.reset()
        eng.stop()
    finally:
        config.clear_property("bigdl.compile.buckets")
    p50_ms = float(np.percentile(lat_ms, 50))
    # the robust overhead estimate: measured per-acquire delta x the
    # measured acquire count, against the measured p50 — two back-to-back
    # p50 measurements differ by more than 1% on a loaded CI box, the
    # microbench delta does not
    armed_pct = (armed_ns - plain_ns) * acquires_per_req / (p50_ms * 1e6) \
        * 100
    disarmed_pct = max(0.0, disarmed_ns - plain_ns) * acquires_per_req / \
        (p50_ms * 1e6) * 100
    _log(f"serving p50 {p50_ms:.3f} ms, {acquires_per_req:.1f} witnessed "
         f"acquires/request: armed overhead {armed_pct:.4f}% of p50, "
         f"disarmed {disarmed_pct:.4f}%")

    # -- static pass wall time -------------------------------------------
    pkg = os.path.join(here, "bigdl_tpu")
    t0 = time.perf_counter()
    static_findings = conc.analyze([pkg])
    static_s = time.perf_counter() - t0
    _log(f"static concurrency pass: {static_s:.2f} s, "
         f"{len(static_findings)} finding(s)")

    record = {
        "per_acquire_ns": {
            "plain": round(plain_ns, 1),
            "disarmed": round(disarmed_ns, 1),
            "armed": round(armed_ns, 1),
        },
        "serving": {
            "p50_ms": round(p50_ms, 4),
            "acquires_per_request": round(acquires_per_req, 1),
            "armed_overhead_pct_of_p50": round(armed_pct, 4),
            "disarmed_overhead_pct_of_p50": round(disarmed_pct, 4),
            "violations": violations,
        },
        "static_pass": {
            "wall_s": round(static_s, 3),
            "findings": len(static_findings),
        },
        "note": "armed overhead = (armed-plain per-acquire delta) x "
                "measured acquires/request vs measured serving p50; the "
                "witness must ride along every tier-1 test for <1% of "
                "request latency",
    }
    if write:
        out_path = out_path or os.path.join(here, "bench_concurrency.json")
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
        _log(f"concurrency record -> {out_path}")
    assert violations == 0, \
        f"lock witness saw {violations} order violation(s) in the bench leg"
    assert armed_pct < 1.0, \
        f"armed lock-witness overhead {armed_pct:.3f}% of serving p50 " \
        f"breaches the 1% rideshare budget"
    assert disarmed_pct < 0.25, \
        f"disarmed factory-lock overhead {disarmed_pct:.3f}% of serving " \
        f"p50 — the disarmed wrapper must be free within noise"
    assert not static_findings, \
        "static concurrency pass found unsuppressed findings:\n" + \
        "\n".join(str(f) for f in static_findings)
    return record


def bench_trace(out_path: str = None, write: bool = True):
    """``--trace-only``: the request-forensics cost leg →
    bench_trace.json.

    - **mini serving leg (tracing ARMED)** — a small warmed
      ServingEngine; measures request p50, reads the span count of a
      real completed trace, and verifies the exemplar round-trip: the
      latency histogram's tail exemplar resolves to a completed trace.
    - **per-request hook microbench** — the full per-request tracing
      sequence (mint + that many clock-read/record-span pairs + verdict)
      armed vs disarmed vs an empty loop, ns per request.  ASSERTS the
      armed sequence stays under 1%% of the measured serving p50 and the
      disarmed sequence under 0.25%% (every disarmed hook is one
      early-return).
    - **incident-dump latency** — flight-recorder bundle
      capture+serialize+write wall time to a scratch dir (the cost a
      terminal fault pays once per slug, under a paused watchdog).
    """
    import shutil
    import tempfile
    import jax
    import bigdl_tpu.nn as nn
    from bigdl_tpu import telemetry
    from bigdl_tpu.telemetry import clock_ns, incident, request_trace
    from bigdl_tpu.serving import ServingEngine
    from bigdl_tpu.utils import config

    here = os.path.dirname(os.path.abspath(__file__))

    # -- mini serving leg under armed tracing ----------------------------
    din, dout = 16, 8
    config.set_property("bigdl.compile.buckets", "1,4")
    request_trace.disarm()
    request_trace.reset()
    try:
        model = (nn.Sequential().add(nn.Linear(din, 64)).add(nn.Tanh())
                 .add(nn.Linear(64, dout)))
        model.reset(jax.random.PRNGKey(0))
        eng = ServingEngine(model)
        eng.warmup(np.zeros((din,), np.float32))
        payload = np.zeros((din,), np.float32)
        request_trace.arm()
        try:
            for _ in range(10):                        # warm the path
                eng.submit(payload).result(timeout=10.0)
            lat_ms = []
            n_req = 200
            for _ in range(n_req):
                t0 = time.perf_counter_ns()
                eng.submit(payload).result(timeout=10.0)
                lat_ms.append((time.perf_counter_ns() - t0) / 1e6)
            ex = telemetry.histogram("Serving/latency_ms").tail_exemplar()
            tr = request_trace.get(ex) if ex else None
            exemplar_ok = bool(tr and tr["verdict"] == "completed")
            spans_per_req = len(tr["spans"]) if tr else 3
        finally:
            request_trace.disarm()
        eng.stop()
    finally:
        config.clear_property("bigdl.compile.buckets")
    p50_ms = float(np.percentile(lat_ms, 50))
    _log(f"serving p50 {p50_ms:.3f} ms (traced), {spans_per_req} span(s) "
         f"per completed trace, exemplar round-trip "
         f"{'OK' if exemplar_ok else 'FAILED'}")

    # -- per-request hook microbench -------------------------------------
    reps = 20_000

    def per_request_ns() -> float:
        t0 = time.perf_counter_ns()
        for _ in range(reps):
            tid = request_trace.mint("bench")
            for _ in range(spans_per_req):
                a = clock_ns()
                b = clock_ns()
                request_trace.record_span(tid, "bench/span", a, b)
            request_trace.verdict(tid, "completed")
        return (time.perf_counter_ns() - t0) / reps

    t0 = time.perf_counter_ns()
    for _ in range(reps):
        pass
    plain_ns = (time.perf_counter_ns() - t0) / reps
    request_trace.reset()
    disarmed_ns = per_request_ns()                     # hooks are no-ops
    request_trace.arm()
    try:
        per_request_ns()                               # warm the path
        armed_ns = per_request_ns()
    finally:
        request_trace.disarm()
        request_trace.reset()
    armed_pct = (armed_ns - plain_ns) / (p50_ms * 1e6) * 100
    disarmed_pct = max(0.0, disarmed_ns - plain_ns) / (p50_ms * 1e6) * 100
    _log(f"per-request hooks: plain {plain_ns:.0f} ns, disarmed "
         f"{disarmed_ns:.0f} ns, armed {armed_ns:.0f} ns — armed "
         f"{armed_pct:.4f}% of p50, disarmed {disarmed_pct:.4f}%")

    # -- incident-dump latency -------------------------------------------
    tmpd = tempfile.mkdtemp(prefix="bench_incident_")
    config.set_property("bigdl.incident.dir", tmpd)
    try:
        incident.reset()
        for i in range(64):
            incident.record("bench/event", position=i)
        t0 = time.perf_counter()
        path = incident.dump("bench")
        dump_ms = (time.perf_counter() - t0) * 1e3
        bundle_bytes = os.path.getsize(path) if path else 0
    finally:
        config.clear_property("bigdl.incident.dir")
        incident.reset()
        shutil.rmtree(tmpd, ignore_errors=True)
    _log(f"incident dump: {dump_ms:.2f} ms, {bundle_bytes} bytes")

    record = {
        "per_request_ns": {
            "plain": round(plain_ns, 1),
            "disarmed": round(disarmed_ns, 1),
            "armed": round(armed_ns, 1),
        },
        "serving": {
            "p50_ms": round(p50_ms, 4),
            "spans_per_request": spans_per_req,
            "armed_overhead_pct_of_p50": round(armed_pct, 4),
            "disarmed_overhead_pct_of_p50": round(disarmed_pct, 4),
            "exemplar_roundtrip": exemplar_ok,
        },
        "incident": {
            "dump_ms": round(dump_ms, 3),
            "bundle_bytes": bundle_bytes,
        },
        "note": "armed overhead = the full per-request hook sequence "
                "(mint + clocked spans + verdict) vs the measured traced "
                "serving p50; tracing must ride along any serving run "
                "for <1% of request latency, and disarmed it must be "
                "free within noise",
    }
    if write:
        out_path = out_path or os.path.join(here, "bench_trace.json")
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
        _log(f"trace record -> {out_path}")
    assert exemplar_ok, \
        "latency-exemplar round-trip failed: the tail exemplar of " \
        "Serving/latency_ms did not resolve to a completed trace"
    assert armed_pct < 1.0, \
        f"armed request-tracing overhead {armed_pct:.3f}% of serving " \
        f"p50 breaches the 1% rideshare budget"
    assert disarmed_pct <= 0.25, \
        f"disarmed request-tracing overhead {disarmed_pct:.3f}% of " \
        f"serving p50 — every disarmed hook must be one early-return"
    assert path is not None, "incident dump wrote no bundle"
    return record


def preflight() -> int:
    """Static preflight: lint the package (host-sync/dtype/exception/lock
    rules), verify the native pipeline build, run the whole-package
    static concurrency pass (lock-order graph + guarded-by contract),
    and run the offline HLO audit over a freshly-populated probe compile
    cache — a broken tree, a missing native symbol, or a fused step
    breaking its program contract fails here, before any real device
    time is spent."""
    from bigdl_tpu.analysis.lint import DEFAULT_ALLOWLIST, lint_paths, \
        load_allowlist
    pkg = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "bigdl_tpu")
    findings = lint_paths([pkg], load_allowlist(DEFAULT_ALLOWLIST))
    for f in findings:
        _log(str(f))
    rc = 1 if findings else 0
    _log(f"preflight: lint {'FAILED' if findings else 'OK'} "
         f"({len(findings)} finding(s))")
    # whole-package static concurrency pass: lock-order inversions,
    # guarded-by contract, async-abort safety
    from bigdl_tpu.analysis import concurrency as _conc
    conc_findings = _conc.analyze([pkg])
    for f in conc_findings:
        _log(str(f))
    _log(f"preflight: concurrency {'FAILED' if conc_findings else 'OK'} "
         f"({len(conc_findings)} finding(s))")
    if conc_findings:
        rc = 1
    try:
        from bigdl_tpu.dataset import native
        native.check_build()
        _log("preflight: native build OK")
    except Exception as e:
        _log(f"preflight: native build FAILED: {e}")
        rc = 1
    # offline HLO audit over a probe cache: the child compiles the probe
    # trainer with the audit armed (warn by default), the offline pass
    # then replays every persisted census against its step contract
    import shutil
    import tempfile
    cache_dir = tempfile.mkdtemp(prefix="preflight_audit_")
    try:
        _probe_cache(cache_dir)
        from bigdl_tpu.analysis import hlo_audit
        _, problems = hlo_audit.audit_cache_dir(cache_dir)
        for p in problems:
            _log(f"VIOLATION: {p}")
        _log(f"preflight: HLO audit {'FAILED' if problems else 'OK'} "
             f"({len(problems)} problem(s))")
        if problems:
            rc = 1
    except Exception as e:
        _log(f"preflight: HLO audit FAILED: {e}")
        rc = 1
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return rc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--precision", choices=["fp32", "bf16"], default="bf16",
                    help="compute precision of the fused step (bf16 is the "
                         "TPU-first default: MXU-native, fp32 master weights)")
    ap.add_argument("--layout", choices=["nhwc", "nchw"], default="nhwc",
                    help="convnet compute layout for the headline model: "
                         "nhwc = channels-last trunk (TPU-native default), "
                         "nchw = the classic Torch layout for A/B runs")
    ap.add_argument("--quick", action="store_true",
                    help="LeNet only (CI smoke)")
    ap.add_argument("--ckpt-only", action="store_true",
                    help="checkpoint-overhead leg only (sync vs async "
                         "save latency + step-time impact)")
    ap.add_argument("--ingest-only", action="store_true",
                    help="host-only ingest leg: per-stage throughput/stall "
                         "metrics for the streaming engine vs the "
                         "synchronous MT path -> bench_ingest.json")
    ap.add_argument("--chaos-ingest-only", action="store_true",
                    help="host-only self-healing ingest leg: throughput "
                         "with 0.1%% injected corrupt records vs clean "
                         "(<5%% degradation asserted), stall-detection "
                         "latency, fallback-switch cost -> "
                         "bench_chaos.json")
    ap.add_argument("--lint-only", action="store_true",
                    help="preflight only: AST-lint bigdl_tpu/ "
                         "(bigdl_tpu.analysis.lint) + native.check_build() "
                         "+ offline HLO audit over a probe compile cache "
                         "— exit 0 iff all pass")
    ap.add_argument("--audit-only", action="store_true",
                    help="HLO-audit leg: per-fused-step program census "
                         "(collective bytes by kind, transpose counts, "
                         "peak-buffer estimates) from a probe compile "
                         "cache, contract-replayed offline and regression-"
                         "checked against audit_baselines.json -> "
                         "bench_audit.json")
    ap.add_argument("--telemetry-only", action="store_true",
                    help="telemetry leg: tracer overhead armed vs disarmed "
                         "(<1%% of step time asserted) + a validated sample "
                         "Chrome trace -> bench_telemetry.json")
    ap.add_argument("--compile-only", action="store_true",
                    help="resilient-compilation leg: cold vs warm start "
                         "across two real processes over one executable "
                         "cache (per-fused-step trace/compile vs load, "
                         "hit/miss counts, bit-identical assert) + "
                         "compile-watchdog detection latency under "
                         "hangCompileAt -> bench_compile.json")
    ap.add_argument("--compile-probe", nargs=2,
                    metavar=("CACHEDIR", "OUT"), help=argparse.SUPPRESS)
    ap.add_argument("--serving-only", action="store_true",
                    help="overload-tolerant serving leg: Poisson open-loop "
                         "latency percentiles at a calibrated admission "
                         "rate (p99 <= deadline asserted) + overload-burst "
                         "fast-rejection with exact request accounting -> "
                         "bench_serving.json")
    ap.add_argument("--serving-soak", action="store_true",
                    help="with --serving-only: ~10x the calibrated-leg "
                         "requests (the slow soak variant)")
    ap.add_argument("--lm-serving-only", action="store_true",
                    help="LM token-serving leg: Poisson open-loop "
                         "mixed-prompt load through the continuous-"
                         "batching scheduler over the paged KV cache "
                         "(>= 1.5x sequential full-generate asserted, "
                         "zero post-warmup retraces, exact stream "
                         "accounting, int8 tier precision gate) -> the "
                         "\"lm\" section of bench_serving.json")
    ap.add_argument("--fleet-only", action="store_true",
                    help="fleet control-plane leg: zero-downtime hot swap "
                         "under load (warm swap < 0.5x cold compile and "
                         "zero requests lost asserted) + rollback-on-"
                         "corrupt-candidate latency -> bench_fleet.json")
    ap.add_argument("--overlap-only", action="store_true",
                    help="latency-hiding collective leg: LM step time + "
                         "decomposition with the bucketed ZeRO-1 schedule "
                         "off/on at several bucket counts, grouped vs "
                         "einsum MoE forward throughput vs a dense FFN -> "
                         "bench_overlap.json (runs on a virtual 8-device "
                         "CPU mesh)")
    ap.add_argument("--elastic-only", action="store_true",
                    help="elastic-training leg: restore+reshard latency by "
                         "device-count pair, preemption-to-first-resumed-"
                         "step, watchdog detection latency -> "
                         "bench_elastic.json (runs on a virtual 8-device "
                         "CPU mesh)")
    ap.add_argument("--integrity-only", action="store_true",
                    help="training-state integrity leg: fingerprint + "
                         "agreement step overhead at everyN 1/10/100, "
                         "modeled cadence-amortized cost (<1%% asserted "
                         "at the default cadence), detection-to-heal "
                         "latency for one injected bit flip -> "
                         "bench_integrity.json (virtual 8-device CPU "
                         "mesh)")
    ap.add_argument("--concurrency-only", action="store_true",
                    help="lock-witness cost leg: per-acquire ns "
                         "plain/disarmed/armed, mini serving p50 under "
                         "the armed witness (<1%% overhead asserted, "
                         "disarmed within noise), static concurrency-"
                         "pass wall time -> bench_concurrency.json")
    ap.add_argument("--trace-only", action="store_true",
                    help="request-forensics cost leg: per-request hook "
                         "ns plain/disarmed/armed vs a mini traced "
                         "serving p50 (<1%% armed and <=0.25%% disarmed "
                         "asserted), latency-exemplar round-trip, "
                         "incident-bundle dump latency -> "
                         "bench_trace.json")
    ap.add_argument("--resources-only", action="store_true",
                    help="resource-exhaustion resilience leg: HBM "
                         "preflight cost (<1%% of step p50 asserted), "
                         "injected-OOM detection-to-replanned-step "
                         "latency, governor accounting overhead, "
                         "disk-full degraded-mode throughput (within 5%% "
                         "of clean asserted) -> bench_resources.json")
    args = ap.parse_args()

    if args.lint_only:
        sys.exit(preflight())

    if args.audit_only:
        # subprocess-populated cache + host-side offline audit: no
        # device work in THIS process
        rec = bench_audit()
        total = sum(s.get("collective_bytes", 0)
                    for s in rec["steps"].values())
        print(json.dumps({"metric": "audit_collective_bytes",
                          "value": total, "unit": "bytes"}))
        return

    if args.compile_probe:
        # hidden child mode of --compile-only: one trainer lifecycle
        # against the given cache dir, provenance written to OUT
        bench_compile_probe(*args.compile_probe)
        return

    if args.compile_only:
        rec = bench_compile()
        print(json.dumps({
            "metric": "compile_warm_start_speedup",
            "value": rec["warm_start"]["speedup"],
            "unit": "x"}))
        return

    if args.serving_only:
        rec = bench_serving(soak=args.serving_soak)
        print(json.dumps({"metric": "serving_p99_ms",
                          "value": rec["calibrated"]["p99_ms"],
                          "unit": "ms"}))
        return

    if args.lm_serving_only:
        rec = bench_lm_serving()
        print(json.dumps({"metric": "lm_serving_speedup",
                          "value": rec["open_loop"]["speedup_x"],
                          "unit": "x"}))
        return

    if args.fleet_only:
        rec = bench_fleet()
        print(json.dumps({"metric": "fleet_warm_swap_ms",
                          "value": rec["hot_swap"]["swap_ms"],
                          "unit": "ms"}))
        return

    if args.overlap_only:
        # like --elastic-only: force the virtual CPU mesh BEFORE jax
        # initializes its backend
        if "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=8").strip()
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        rec = bench_overlap(steps=max(args.steps, 40))
        print(json.dumps({"metric": rec["metric"], "value": rec["value"],
                          "unit": rec["unit"]}))
        return

    if args.integrity_only:
        # like --elastic-only: force the virtual CPU mesh BEFORE jax
        # initializes its backend
        if "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=8").strip()
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        rec = bench_integrity(steps=max(args.steps, 40))
        print(json.dumps({
            "metric": "integrity_overhead_at_default_pct",
            "value": rec["overhead_at_default_pct"], "unit": "%"}))
        return

    if args.elastic_only:
        # the leg needs a multi-device mesh to change topology under; a
        # virtual CPU mesh (the tier-1 configuration) must be forced
        # BEFORE jax initializes its backend
        if "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=8").strip()
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        rec = bench_elastic()
        worst = max(p["restore_ms"] + p["reshard_ms"]
                    for p in rec["pairs"])
        print(json.dumps({"metric": "elastic_restore_reshard_ms",
                          "value": round(worst, 2), "unit": "ms"}))
        return

    if args.ingest_only:
        # no device work at all — do not even init jax's backend
        print(json.dumps({
            "metric": "mt_ingest_imgs_per_sec",
            "value": bench_ingest(batch=args.batch)["value"],
            "unit": "images/sec"}))
        return

    if args.chaos_ingest_only:
        # host-only like --ingest-only: the self-healing leg
        rec = bench_chaos_ingest(batch=args.batch)
        print(json.dumps({k: rec[k]
                          for k in ("metric", "value", "unit")}))
        return

    import jax
    from bigdl_tpu.engine import init_compile_cache
    init_compile_cache()
    _log(f"devices: {jax.devices()}")

    if args.telemetry_only:
        rec = bench_telemetry(steps=max(args.steps, 25))
        print(json.dumps({k: rec[k] for k in ("metric", "value", "unit")}))
        return

    if args.concurrency_only:
        rec = bench_concurrency()
        print(json.dumps(rec["serving"]))
        return

    if args.trace_only:
        rec = bench_trace()
        print(json.dumps(rec["serving"]))
        return

    if args.resources_only:
        rec = bench_resources(steps=max(args.steps, 40))
        print(json.dumps({k: rec[k] for k in ("metric", "value", "unit")}))
        return

    from bigdl_tpu.models.resnet import resnet, model_init, DatasetType

    if args.ckpt_only:
        print(json.dumps(_write_ckpt_artifact(bench_checkpoint())))
        return

    if args.quick:
        # LeNet/MNIST (BASELINE config #1 shape) — CI smoke.  The
        # historical >11-min pathological XLA compile at batch 512 was
        # the conv WEIGHT gradient for the 1-channel 5x5 conv; the
        # small-taps slice-stack matmul path (ops/convolution.py
        # _conv2d_smallk) fixed it: full fused step now compiles in
        # ~7 s and runs ~37k img/s at batch 512.
        from bigdl_tpu.models.lenet import lenet5
        r = bench_model(lenet5(10), 512, (28, 28), 10, steps=args.steps)
        _log(f"lenet (batch 512): {r}")
        result = {"metric": "lenet_train_images_per_sec",
                  "value": round(r["images_per_sec"], 1),
                  "unit": "images/sec", "vs_baseline": 1.0}
        print(json.dumps(result))
        return

    # ResNet-50/ImageNet synthetic — the north-star protocol.
    # ~4.09 GFLOPs/image forward; training ~3x forward.  The model builds
    # channels-last by default (interior NHWC compute, NCHW facade — the
    # layout XLA:TPU wants); --layout nchw re-runs the old path for A/B.
    precision = None if args.precision == "fp32" else args.precision
    model = model_init(resnet(1000, depth=50, dataset=DatasetType.IMAGENET,
                              layout=args.layout.upper()))
    r50 = bench_model(model, args.batch, (3, 224, 224), 1000,
                      steps=args.steps, flops_per_image=3 * 4.09e9,
                      logits=True, precision=precision)
    _log(f"resnet50 (batch {args.batch}, {args.precision}, "
         f"{args.layout}): {r50}")
    if "tflops" in r50:
        # bf16 peak of one v5e chip ~197 TFLOP/s
        _log(f"  achieved {r50['tflops']:.1f} TFLOP/s "
             f"(~{r50['tflops'] / 197 * 100:.1f}% MFU of a v5e chip)")

    value = r50["images_per_sec"]
    baseline_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "bench_baseline.json")
    vs = 1.0
    if os.path.exists(baseline_path):
        with open(baseline_path) as f:
            base = json.load(f)
        # only comparable at the batch size/precision the baseline pinned
        # baselines written before the precision field existed were fp32
        if (base.get("resnet50_train_images_per_sec") and
                base.get("batch") == args.batch and
                base.get("precision", "fp32") == args.precision):
            vs = value / base["resnet50_train_images_per_sec"]

    result = {"metric": "resnet50_train_images_per_sec",
              "value": round(value, 1), "unit": "images/sec",
              "vs_baseline": round(vs, 3)}
    # emit the headline IMMEDIATELY: the experimental legs below run for
    # minutes — a hard abort in one of them must not lose the
    # already-measured number.  The enriched record is re-printed at the
    # end; consumers parse the LAST JSON line.
    print(json.dumps(result), flush=True)

    # LM flagship legs: four REALISTIC shapes through the same fused step.
    # - base: 134M params, d1024/L8/T2048/B8 (head_dim 128) — r3's point,
    #   ~107k tokens/s = ~55% MFU on one v5e.
    # - large: 537M params, d2048/L8/vocab 32k/T2048/B4 — the >= 0.5B
    #   point; without remat, B8 and L12/L16 exceed 16 GB HBM (measured
    #   r4: momentum slots + fp32 masters + B*T*d activation residuals).
    # - large_b8_remat: the SAME 537M at B8 with per-block activation
    #   checkpointing ("dots" policy: matmul outputs saved, attention +
    #   elementwise recomputed) — fits where non-remat OOMs.  Measured
    #   r5: 33.4k tok/s, 61.5% useful-FLOPs MFU; the ~7% drop vs B4
    #   non-remat (66%) IS the attention recompute (~12LdT extra
    #   FLOPs/token ~= +11%), so hardware utilization is unchanged —
    #   remat buys capacity, not speed, at this arithmetic intensity.
    # - 1b_remat: 1.04B params (d2048/L18) at B4, FULL per-block remat —
    #   the >= 1B single-chip point that cannot exist without remat
    #   (params+momentum+grads alone ~12.5GB).
    # All four legs run the TUNED pallas flash kernel
    # (_flash_block_sizes): measured r5, it beats XLA's fused standard
    # path at every one of these shapes (+17-21% on the dense legs; the
    # current numbers live in bench_lm.json — this comment stays
    # number-free so it cannot go stale against the artifact).  The r3
    # "flash loses at T2048" rejection was the stock 128-tile default.
    # Standard attention stays the MODULE default (exact numerics
    # parity, GSPMD-tp compatible); perf-critical dense paths opt in.
    # Failures here must not touch the headline metric.
    lm_configs = [
        ("transformer_lm_train_tokens_per_sec",
         16384, 1024, 8, 8, 2048, 8, False),
        ("transformer_lm_large_tokens_per_sec",
         32768, 2048, 8, 16, 2048, 4, False),
        ("transformer_lm_large_b8_remat_tokens_per_sec",
         32768, 2048, 8, 16, 2048, 8, "dots"),
        ("transformer_lm_1b_remat_tokens_per_sec",
         32768, 2048, 18, 16, 2048, 4, True),
    ]
    lm_points = []
    for metric, v, d, nl, h, t, b, remat in lm_configs:
        try:
            import jax as _jax
            import bigdl_tpu.nn as nn
            from bigdl_tpu.models.transformer import transformer_lm

            lm = transformer_lm(v, d_model=d, n_head=h, n_layers=nl,
                                max_len=t, remat=remat)
            for m in lm.modules():
                if isinstance(m, nn.MultiHeadAttention):
                    m.flash = True
            r_lm = bench_model(
                lm, b, (t,), v, steps=args.steps,
                precision="bf16",
                criterion=nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                                      size_average=True),
                make_batch=lambda rng, bsz: (
                    rng.randint(1, v + 1, (bsz, t)).astype(np.float32),
                    rng.randint(1, v + 1, (bsz, t)).astype(np.float32)))
            toks = r_lm["images_per_sec"] * t
            n_params = sum(int(np.prod(l.shape))
                           for l in _jax.tree_util.tree_leaves(lm.params))
            del lm
            # training matmul FLOPs/token: 6*params + attention 12*L*d*T;
            # bf16 peak of one v5e chip ~197 TFLOP/s
            mfu = toks * (6 * n_params + 12 * nl * d * t) / 197e12
            _log(f"transformer-lm (B{b} T{t} d{d} L{nl} vocab {v}, "
                 f"{n_params / 1e6:.0f}M params, bf16): {toks:,.0f} "
                 f"tokens/s ({r_lm['step_ms']:.1f} ms/step, "
                 f"MFU {mfu * 100:.1f}%)")
            lm_record = {"metric": metric,
                         "value": round(toks, 0), "unit": "tokens/sec",
                         "mfu": round(mfu, 3),
                         "config": {"batch": b, "seq_len": t, "d_model": d,
                                    "n_layers": nl, "n_head": h, "vocab": v,
                                    "params_m": round(n_params / 1e6, 1),
                                    "precision": "bf16",
                                    "attention": "flash_tuned",
                                    "remat": ("full" if remat is True
                                              else remat or "off")}}
            base_path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "bench_baseline.json")
            if os.path.exists(base_path):
                with open(base_path) as f:
                    pinned = json.load(f).get(metric)
                if pinned:
                    lm_record["vs_baseline"] = round(toks / pinned, 3)
                    _log(f"  vs pinned baseline: {toks / pinned:.3f}")
            lm_points.append(lm_record)
        except Exception as e:  # diagnostic only
            _log(f"transformer-lm leg {metric} skipped: {e}")
    if lm_points:
        out = dict(lm_points[0])
        out["points"] = lm_points
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_lm.json"), "w") as f:
            json.dump(out, f, indent=1)

    # Inference leg: eval-mode forward throughput (the Predictor hot
    # path) — bench_infer.json.  Failures must not touch the headline.
    try:
        # forwards are 15-45 ms; a 20-step floor costs ~1 s and amortizes
        # the dispatch-queue ramp that a 10-step window under-measures
        infer = bench_inference(steps=max(20, args.steps))
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_infer.json"), "w") as f:
            json.dump({"points": infer}, f, indent=1)
    except Exception as e:  # diagnostic only
        _log(f"inference leg skipped: {e}")

    # Long-context leg: the attention-path comparison measured AT T8192 /
    # T16384 (bench_longctx.json).  Failures must not touch the headline.
    try:
        lc = bench_longctx(steps=max(4, args.steps // 2))

        def _rate(t, mode):
            for p in lc:
                if p["seq_len"] == t and p["mode"] == mode:
                    return p.get("tokens_per_sec")
            return None

        f8, s8 = _rate(8192, "flash"), _rate(8192, "standard")
        f16 = _rate(16384, "flash")
        # the verdict is FORMATTED FROM THIS RUN'S POINTS so the artifact
        # can never contradict itself across re-runs
        ratio8 = (f"{f8 / s8:.2f}x standard at T8192"
                  if f8 and s8 else "standard@T8192 unmeasured this run")
        t16 = (f"{f16 / 1e3:.1f}k tok/s at T16384"
               if f16 else "T16384 flash unmeasured this run")
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_longctx.json"), "w") as f:
            json.dump({"config": {"d_model": 1024, "n_layers": 8,
                                  "n_head": 8, "vocab": 16384, "batch": 1,
                                  "precision": "bf16"},
                       "points": lc,
                       "verdict": "TUNED flash (1024-sq tiles, "
                                  "_flash_block_sizes) wins at every "
                                  f"measured shape: {ratio8}, {t16} "
                                  "where one-shot standard exhausts HBM "
                                  "on saved O(T^2) residuals, and wins "
                                  "at T2048 too (bench_lm.json); the "
                                  "r3/r4 flash-loses results were the "
                                  "stock 128-tile default.  chunked "
                                  "scan and per-block remat are the "
                                  "pure-XLA fallback paths"},
                      f, indent=1)
    except Exception as e:  # diagnostic only
        _log(f"long-context bench skipped: {e}")


    # Checkpoint-overhead leg: sync vs async save latency and the
    # step-time impact of per-iteration checkpointing (bench_ckpt.json +
    # the headline record).  Failures must not touch the headline.
    try:
        ck = bench_checkpoint()
        result["checkpoint"] = ck
        _write_ckpt_artifact(ck)
    except Exception as e:  # diagnostic only
        _log(f"checkpoint bench skipped: {e}")

    # Ingest-engine leg: host-only per-stage throughput/stall metrics for
    # the streaming engine (bench_ingest.json).  Failures must not touch
    # the headline metric.
    try:
        bench_ingest(batch=args.batch)
    except Exception as e:  # diagnostic only
        _log(f"ingest bench skipped: {e}")

    # Real-data ingest leg: the same ResNet-50 b128 bf16 step fed by the
    # repo's OWN production pipeline (sharded seqfile read ->
    # StreamingIngest decode/assemble -> BatchPrefetcher transfer-ahead ->
    # DistriOptimizer) instead of a resident synthetic tensor.  Failures
    # must not touch the headline metric.
    try:
        rd, stages = bench_realdata(batch=args.batch,
                                    steps=max(args.steps, 15),
                                    synthetic_rate=value)
        ratio = rd / value
        _log(f"resnet50 REAL-DATA ingest (batch {args.batch}, bf16): "
             f"{rd:,.1f} img/s = {ratio:.2f}x of synthetic {value:,.1f}")
        result["resnet50_realdata_images_per_sec"] = round(rd, 1)
        result["realdata_vs_synthetic"] = round(ratio, 3)
        rd_record = {"metric": "resnet50_realdata_images_per_sec",
                     "value": round(rd, 1), "unit": "images/sec",
                     "vs_synthetic": round(ratio, 3),
                     "stages": stages,
                     "pipeline": "ShardedSeqFileReader (native reader, "
                                 "sharded) -> StreamingIngest (record "
                                 "ring -> cv2 decode pool -> ordered "
                                 "window -> native assemble, uint8 "
                                 "layout -> batch ring) -> "
                                 "BatchPrefetcher transfer-ahead -> "
                                 "DistriOptimizer fused bf16 step with "
                                 "nn.ChannelNormalize on device"}
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "bench_realdata.json"), "w") as f:
            json.dump(rd_record, f, indent=1)
    except Exception as e:  # diagnostic only
        _log(f"real-data ingest bench skipped: {e}")

    print(json.dumps(result))


if __name__ == "__main__":
    main()
