"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the dense decoder the repo trains, at full width
(``transformer_lm(16384, d_model=1024, n_head=8, n_layers=8,
max_len=2048)``, 134M parameters, pallas flash attention), through both
normal entry points: 8 steps of ``Optimizer.optimize()`` at B8 x T2048
in bf16, then 4 requests through ``LMServingEngine.submit()``.  With
four or more chips it adds 4 data-parallel steps through
``DistriOptimizer``.  Every check raises, any raise exits non-zero.  The
last two lines of stdout are JSON objects: the summary of the run, then
the verdict ``{"ok": true, "device": {"platform", "kind", "count"}}``.

    python chip_smoke.py

There is no CPU mode: without a TPU the device gate exits non-zero
before anything runs.  The control flow is rehearsed at toy width by
``tests/test_chip_smoke.py``.  This file is not a benchmark: it prints
wall and compile seconds so a cold and a warm start can be told apart,
and no rate.
"""

import gc
import json
import logging
import math
import os
import re
import sys
import time
import traceback

#: one model the repo supports, at its full width (depth is what the
#: model has: 8 layers)
FULL_WIDTH = {
    "vocab": 16384, "d_model": 1024, "n_head": 8, "n_layers": 8,
    "max_len": 2048, "flash": True,
    # trainer.  One batch, seen every step: under plain SGD at this rate
    # its loss falls step over step, where two different batches differ
    # by more than eight steps gain
    "batch": 8, "seq_len": 2048, "steps": 8,
    # server
    "max_batch": 8, "max_context": 1024, "prefill_buckets": "128,512,1024",
    "prompt_lens": (16, 100, 300, 700), "max_new_tokens": 32,
    "deadline_ms": 120000.0, "parity_prompt": 24, "parity_tokens": 8,
    # four-chip phase
    "dp_devices": 4, "dp_batch": 32, "dp_steps": 4,
}


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# device gate
# ---------------------------------------------------------------------------

def device_gate() -> dict:
    """The device this run is about, or SystemExit: anything but a TPU
    is an error, and so is an inherited virtual-host-device request —
    neither may turn a chip check into a CPU run."""
    if "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        raise SystemExit(
            "chip_smoke: XLA_FLAGS carries "
            "--xla_force_host_platform_device_count — that asks for a "
            "virtual CPU mesh, and this script only runs on the chip")
    import jax
    devices = jax.devices()
    dev = devices[0]
    say(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)} " +
        " ".join(f"{k}={v}" for k, v in versions().items()))
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found platform {dev.platform!r}, not a TPU "
            "— there is no CPU mode (tests/test_chip_smoke.py rehearses "
            "the control flow)")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


# ---------------------------------------------------------------------------
# model and data
# ---------------------------------------------------------------------------

def build_model(cfg: dict, seed: int = 0):
    import jax

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.transformer import transformer_lm
    model = transformer_lm(cfg["vocab"], d_model=cfg["d_model"],
                           n_head=cfg["n_head"], n_layers=cfg["n_layers"],
                           max_len=cfg["max_len"])
    if cfg["flash"]:
        for m in model.modules():
            if isinstance(m, nn.MultiHeadAttention):
                m.flash = True
    model.reset(jax.random.PRNGKey(seed))
    return model


def criterion():
    import bigdl_tpu.nn as nn
    return nn.TimeDistributedCriterion(nn.ClassNLLCriterion(),
                                       size_average=True)


class _LossTap(logging.Handler):
    """The per-iteration losses, read off the driver's own log lines."""

    def __init__(self):
        super().__init__(level=logging.INFO)
        self.losses = []

    def emit(self, record):
        m = re.search(r"Loss is ([-+.\w]+?)\.( |$)", record.getMessage())
        if m:
            self.losses.append(float(m.group(1)))


def _train(opt, steps: int) -> list:
    """``optimize()`` with no retry backoff: a failure must surface as
    itself, at once.  Returns the per-iteration losses."""
    import bigdl_tpu.optim as optim
    from bigdl_tpu.utils import config
    opt.set_precision("bf16")
    opt.set_optim_method(optim.SGD(0.01, momentum=0.9))
    opt.set_end_when(optim.max_iteration(steps))
    tap = _LossTap()
    lg = logging.getLogger("bigdl_tpu")
    level = lg.level
    lg.addHandler(tap)
    lg.setLevel(logging.INFO)
    config.set_property("bigdl.failure.retryTimes", 1)
    try:
        opt.optimize()
    finally:
        config.clear_property("bigdl.failure.retryTimes")
        lg.removeHandler(tap)
        lg.setLevel(level)
    return tap.losses


def _check_losses(losses: list, steps: int) -> None:
    check(len(losses) == steps,
          f"expected {steps} iteration log lines, saw {len(losses)}")
    check(all(math.isfinite(v) for v in losses),
          f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: first {losses[0]}, last {losses[-1]}")


def _on_default_device(tree, what: str) -> None:
    import jax
    want = {jax.devices()[0]}
    for leaf in jax.tree_util.tree_leaves(tree):
        check(leaf.devices() == want,
              f"{what}: a leaf lives on {leaf.devices()}, not {want}")


# ---------------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------------

def train_phase(model, cfg: dict) -> dict:
    import jax

    import bigdl_tpu.optim as optim
    from bigdl_tpu import telemetry
    from bigdl_tpu.dataset import LocalDataSet, SampleToMiniBatch
    from bigdl_tpu.engine import to_device
    from bigdl_tpu.models.transformer.train import _synthetic
    from bigdl_tpu.utils.compile_cache import CachedStep
    t0 = time.monotonic()
    samples = _synthetic(cfg["batch"], cfg["seq_len"], vocab=cfg["vocab"])
    ds = LocalDataSet(samples).transform(SampleToMiniBatch(cfg["batch"]))
    oom0 = telemetry.counter("Resources/device_oom").value
    opt = optim.Optimizer.create(model, ds, criterion())
    losses = _train(opt, cfg["steps"])
    _check_losses(losses, cfg["steps"])

    step = getattr(opt._step_fn, "__wrapped__", opt._step_fn)
    check(isinstance(step, CachedStep),
          f"the fused step is a {type(step).__name__}, not a CachedStep")
    check(step.compiles == 1,
          f"the fused step compiled {step.compiles} times, not once")
    check(opt._retrace_sentinel is not None
          and opt._retrace_sentinel.retraces == 0,
          "the retrace sentinel is missing or saw a retrace")
    ooms = telemetry.counter("Resources/device_oom").value - oom0
    check(ooms == 0 and opt._microbatch_k == 1,
          f"{ooms} device OOM(s), microbatch k={opt._microbatch_k}: the "
          "run re-planned to a program other than the one named")
    # what enters the step, what it leaves, and what it was compiled for
    batch = next(ds.data(train=False))
    _on_default_device(to_device(batch.get_input()), "to_device(batch)")
    _on_default_device(model.params, "model.params")
    for exe in step._mem.values():
        for sh in jax.tree_util.tree_leaves(exe.input_shardings):
            check(sh.device_set == {jax.devices()[0]},
                  f"the step was compiled for {sh.device_set}")
    timing = step.timings[0]
    warmup_ms = telemetry.gauge("Compile/warmup_ms").value
    out = {
        "wall_s": round(time.monotonic() - t0, 2),
        "first_loss": losses[0], "last_loss": losses[-1],
        "compiles": step.compiles,
        "warmup_ms": round(warmup_ms, 1),
        "trace_ms": timing["trace_ms"], "compile_ms": timing["compile_ms"],
        # as_text + HLO audit + preflight: what warmup spent outside
        # tracing and compiling
        "warmup_other_ms": round(
            warmup_ms - timing["trace_ms"] - timing["compile_ms"], 1),
        "audit_peak_bytes": telemetry.gauge(
            "Audit/peak_bytes", labels={"step": "local"}).value,
    }
    say(f"train: {cfg['steps']} steps, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}, 1 compile ({timing['compile_ms']:.0f} ms), "
        f"0 retraces, 0 OOM re-plans, {out['wall_s']} s")
    return out


# ---------------------------------------------------------------------------
# phase 2: the server
# ---------------------------------------------------------------------------

def serve_phase(model, cfg: dict) -> dict:
    import numpy as np

    from bigdl_tpu.serving.lm import LMServingEngine
    from bigdl_tpu.utils import config
    t0 = time.monotonic()
    rng = np.random.RandomState(2)
    config.set_property("bigdl.lm.prefillBuckets", cfg["prefill_buckets"])
    try:
        eng = LMServingEngine(model, max_batch=cfg["max_batch"],
                              max_context=cfg["max_context"],
                              deadline_ms=cfg["deadline_ms"])
    finally:
        config.clear_property("bigdl.lm.prefillBuckets")
    with eng:
        eng.warmup()
        warm_s = time.monotonic() - t0
        # reported, not gated: how far the paged path and the
        # teacher-forced full forward agree on greedy tokens
        prompt = rng.randint(1, cfg["vocab"] + 1,
                             cfg["parity_prompt"]).astype(np.int32)
        paged = eng.generate(prompt, max_new_tokens=cfg["parity_tokens"])
        full = eng.generate_sequential(prompt,
                                       max_new_tokens=cfg["parity_tokens"])
        agree = sum(a == b for a, b in zip(paged, full))

        eng.start()
        streams = [eng.submit(rng.randint(1, cfg["vocab"] + 1, n)
                              .astype(np.int32),
                              max_new_tokens=cfg["max_new_tokens"])
                   for n in cfg["prompt_lens"]]
        results = [s.result(timeout=cfg["deadline_ms"] / 1e3 + 60.0)
                   for s in streams]
        stats = eng.stats()
        retraces = {k: s.retraces for k, s in eng.sentinels.items()}
        timings = eng.timings
    n = len(streams)
    for s, toks in zip(streams, results):
        check(s.outcome == "completed",
              f"stream {s.index} ended {s.outcome!r}: {s.error()!r}")
        check(len(toks) == cfg["max_new_tokens"],
              f"stream {s.index} produced {len(toks)} tokens, not "
              f"{cfg['max_new_tokens']}")
        check(all(1 <= t <= cfg["vocab"] for t in toks),
              f"stream {s.index} produced a token outside the vocabulary")
    check(stats["submitted"] == n and stats["completed"] == n,
          f"submitted/completed != {n}: {stats}")
    check(stats["shed"] == stats["rejected"] == stats["quarantined"] == 0
          and stats["unaccounted"] == 0, f"a request did not complete: {stats}")
    check(retraces and all(v == 0 for v in retraces.values()),
          f"post-warmup retraces: {retraces}")
    compiles = {k: sum(t["source"] == "compile" for t in v)
                for k, v in timings.items()}
    out = {
        "wall_s": round(time.monotonic() - t0, 2),
        "warmup_s": round(warm_s, 2),
        "compile_ms": round(sum(t.get("compile_ms", 0.0)
                                for v in timings.values() for t in v), 1),
        "compiles": compiles,
        "requests": n, "tokens_served": stats["tokens_out"],
        "decode_steps": stats["decode_steps"],
        "parity_agree": f"{agree}/{cfg['parity_tokens']}",
    }
    say(f"serve: {n}/{n} requests completed, {stats['tokens_out']} tokens, "
        f"0 retraces, compiles {compiles}, paged vs sequential greedy "
        f"tokens agree {out['parity_agree']}, {out['wall_s']} s")
    return out


# ---------------------------------------------------------------------------
# phase 3: four chips, data parallel
# ---------------------------------------------------------------------------

def dp_phase(cfg: dict) -> dict:
    import jax

    from bigdl_tpu.dataset import SampleToMiniBatch
    from bigdl_tpu.dataset.dataset import ShardedDataSet
    from bigdl_tpu.engine import Engine
    from bigdl_tpu.models.transformer.train import _synthetic
    from bigdl_tpu.parallel import DistriOptimizer
    n = cfg["dp_devices"]
    if jax.device_count() < n:
        say(f"dp{n}: skipped: {jax.device_count()} device")
        return {"skipped": f"{jax.device_count()} device"}
    t0 = time.monotonic()
    devices = jax.devices()[:n]
    model = build_model(cfg, seed=3)
    samples = _synthetic(cfg["dp_batch"], cfg["seq_len"], seed=5,
                         vocab=cfg["vocab"])
    ds = ShardedDataSet(samples, n).transform(
        SampleToMiniBatch(cfg["dp_batch"], n))
    mesh = Engine.create_mesh((n,), ("data",), devices=devices)
    opt = DistriOptimizer(model, ds, criterion(), mesh=mesh)
    losses = _train(opt, cfg["dp_steps"])
    _check_losses(losses, cfg["dp_steps"])
    want = set(devices)
    for what, tree in (("parameters", model.params),
                       ("optimizer slots", opt._sharded_slots)):
        for leaf in jax.tree_util.tree_leaves(tree):
            check(leaf.sharding.device_set == want,
                  f"dp{n}: {what} live on {leaf.sharding.device_set}, "
                  f"not on all of {want}")
    # (the CPU backend of the rehearsal reports no memory statistics)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    check(devices[0].platform != "tpu" or all(b and b > 0 for b in in_use),
          f"dp{n}: a device holds nothing: bytes_in_use {in_use}")
    out = {"wall_s": round(time.monotonic() - t0, 2), "devices": n,
           "first_loss": losses[0], "last_loss": losses[-1],
           "bytes_in_use": in_use}
    say(f"dp{n}: {cfg['dp_steps']} steps over {n} devices, loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}, state on all {n}, "
        f"{out['wall_s']} s")
    return out


# ---------------------------------------------------------------------------

def run(cfg: dict) -> dict:
    """Every phase, in one process; returns the summary."""
    import jax

    from bigdl_tpu.engine import init_compile_cache
    t0 = time.monotonic()
    cache_dir = init_compile_cache()
    device = device_gate()
    say(f"compile cache: {cache_dir}")
    model = build_model(cfg)
    train = train_phase(model, cfg)
    serve = serve_phase(model, cfg)
    del model
    gc.collect()
    dp = dp_phase(cfg)
    stats = jax.devices()[0].memory_stats() or {}
    return {"ok": True, "device": device,
            "versions": versions(),
            "compile_cache_dir": cache_dir,
            "wall_s": round(time.monotonic() - t0, 2),
            "train": train, "serve": serve, "dp": dp,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "claim": None}


def main(cfg: dict = FULL_WIDTH) -> int:
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s - %(message)s")
    try:
        summary = run(cfg)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 1
    except Exception:
        traceback.print_exc()
        return 1
    # the record of the run (phase seconds, compile seconds, cache
    # directory, losses, tokens, peak bytes), then the verdict: the last
    # line holds "ok" and "device" and nothing else
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
