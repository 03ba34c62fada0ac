"""Role: serve the decoder LM on one chip through
``LMServingEngine.submit()`` under an open loop of requests at a fixed
rate.  Set up, check, measure, report."""

from __future__ import annotations

import threading
import time
from typing import Any, Dict

from benchmark import cells, checks
from benchmark.builders.common import seed_key
from benchmark.traffic import client as _client
from benchmark.traffic import generate


def _sleep_until(t: float) -> None:
    while True:
        wait = t - time.perf_counter()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.05))


def _compile_timings(eng) -> list:
    return [dict(t, label=label) for label, ts in eng.timings.items()
            for t in ts]


def setup(ctx):
    """The model from the seed and a warm engine; not started yet."""
    from bigdl_tpu.serving.lm import LMServingEngine
    cfg = ctx.config
    builder = cells.builder(cfg["builder"])
    model = builder.build(cfg, seed_key(ctx.seed), on_tpu=ctx.on_tpu)
    ctx.say(f"model {builder.describe(cfg, model)}")
    # every key of job.engine is an argument of the engine; what the
    # cell does not name stays at the engine's default
    eng = LMServingEngine(model, **ctx.cell["job"]["engine"])
    try:
        eng.warmup()
    except BaseException:
        eng.stop(grace=0.0)
        raise
    ctx.say(f"warm: {sum(len(v) for v in eng.timings.values())} programs "
            f"({ {k: len(v) for k, v in eng.timings.items()} })")
    return model, eng


def offer(ctx, eng, mix: Dict[str, Any], seconds: float, seed: int,
          trace_dir=None, windows: int = 1) -> Dict[str, Any]:
    """One pass of the open loop against a started engine: lead-in, the
    window, then the drain.  Returns the client's and the server's view.
    The sweep asks for ``windows`` of ``seconds`` end to end in one pass;
    ``summaries`` has the client's view of each, ``summary`` of all."""
    job, e = ctx.cell["job"], ctx.cell["job"]["engine"]
    vocab = int(ctx.config["vocab_size"])
    lead_in = float(mix["lead_in_s"])
    each, seconds = seconds, seconds * windows
    schedule = generate.request_schedule(mix, seed, seconds, vocab)
    cl = _client.OpenLoopClient(
        lambda prompt, n: eng.submit(prompt, max_new_tokens=n), schedule)
    snaps: Dict[str, Dict[str, Any]] = {}
    tracer = None
    try:
        t0 = cl.start()
        w0, w1 = t0 + lead_in, t0 + lead_in + seconds
        if trace_dir:
            tracer = threading.Thread(
                target=_trace_slice, name="bench-trace",
                args=(trace_dir,
                      w0 + (seconds - float(job["trace_seconds"])) / 2,
                      float(job["trace_seconds"])))
            tracer.start()
        for name, at in (("open", w0), ("middle", (w0 + w1) / 2),
                         ("close", w1)):
            _sleep_until(at)
            snaps[name] = eng.stats()
        if tracer is not None:
            tracer.join()
        undone = cl.drain(w1 + float(job["drain_limit_s"]))
        snaps["end"] = eng.stats()
    except BaseException:
        cl.abandon()
        raise
    summary = _client.summarize(cl.records, w0, w1, float(e["deadline_ms"]))
    summaries = [_client.summarize(cl.records, w0 + i * each,
                                   w0 + (i + 1) * each,
                                   float(e["deadline_ms"]))
                 for i in range(windows)] if windows > 1 else [summary]
    due = [r for r in cl.records if w0 <= r.due < w1]
    s0, s1 = snaps["open"], snaps["close"]
    return {
        "client": cl, "summary": summary, "summaries": summaries,
        "snaps": snaps, "undone": undone,
        "w0": w0, "w1": w1, "offered": len(cl.records),
        "bad_length": sum(1 for r in due if r.outcome == "completed"
                          and len(r.tokens) != r.max_new),
        "bad_token": sum(1 for r in due for t in r.tokens
                         if not 1 <= t <= vocab),
        "decode_steps": s1["decode_steps"] - s0["decode_steps"],
        "decode_tokens": ((s1["tokens_out"] - s0["tokens_out"])
                          - (s1["prefills"] - s0["prefills"])),
    }


def run(ctx) -> Dict[str, Any]:
    cell = ctx.cell
    mix, e = cell["mix"], cell["job"]["engine"]
    model, eng = setup(ctx)
    passed = None
    try:
        # -- check, before start() ---------------------------------------
        verdict = checks.lm_generate_against_reference(ctx, eng, model)
        # -- measure -----------------------------------------------------
        eng.start()
        passed = offer(ctx, eng, mix, ctx.seconds, ctx.seed, ctx.trace_dir)
        retraces = {k: s.retraces for k, s in eng.sentinels.items()}
        timings = _compile_timings(eng)
    finally:
        eng.stop(grace=0.0)
        if passed is not None:
            passed["client"].close()

    # -- report ----------------------------------------------------------
    summary, snaps = passed["summary"], passed["snaps"]
    s0, s1, s_end = snaps["open"], snaps["close"], snaps["end"]
    check = dict(verdict["checks"])
    check["streams_have_their_length"] = passed["bad_length"] == 0
    check["tokens_in_vocabulary"] = passed["bad_token"] == 0
    # what is still open at the drain limit is failed, not lost
    check["nothing_unaccounted"] = (
        s_end["unaccounted"] == 0 or passed["undone"] > 0)
    check["no_retrace"] = bool(retraces) and all(
        v == 0 for v in retraces.values())
    ctx.say(describe_pass(passed))
    detail = {k: v for k, v in summary.items() if k != "ttft_ms"}
    detail.update(verdict["detail"])
    detail.update(
        rate=float(mix["rate"]), offered=passed["offered"],
        undone=passed["undone"],
        queue_depth_middle=snaps["middle"]["queue_depth"],
        queue_depth_close=s1["queue_depth"],
        active_slots_open=s0["active_slots"],
        active_slots_close=s1["active_slots"],
        server_outcomes={k: s_end[k] for k in
                         ("submitted", "completed", "shed", "rejected",
                          "quarantined", "unaccounted")},
        retraces=retraces, decode_steps_in_window=passed["decode_steps"])
    return {
        "t_window_open": passed["w0"], "t_window_close": passed["w1"],
        "attempted": summary["due_in_window"],
        "failed": summary["failed_of_due"],
        "checks": check, "detail": detail, "client": summary,
        "server": {"decode_steps_in_window": passed["decode_steps"],
                   "decode_tokens_in_window": passed["decode_tokens"],
                   "max_batch": int(e["max_batch"]),
                   "decode_ema_ms": s1["decode_ema_ms"]},
        "timings": timings,
    }


def describe_pass(passed: Dict[str, Any]) -> str:
    s, snaps = passed["summary"], passed["snaps"]

    def ms(v, digits=0):
        return "-" if v is None else f"{v:.{digits}f}"

    return (f"{s['due_in_window']} due, {s['completed_in_window']} "
            f"completed in the window, {s['failed_of_due']} failed, queue "
            f"{snaps['middle']['queue_depth']} -> "
            f"{snaps['close']['queue_depth']}, slots "
            f"{snaps['open']['active_slots']} -> "
            f"{snaps['close']['active_slots']}, "
            f"{s['serve_tok_s']:.1f} tokens/s, ttft mean/p50/p90 "
            f"{ms(s['ttft_mean_ms'], 1)}/{ms(s['ttft_p50_ms'])}/"
            f"{ms(s['ttft_p90_ms'])} ms, itl p50/p95 "
            f"{ms(s['itl_p50_ms'], 1)}/{ms(s['itl_p95_ms'], 1)} ms, late p95 "
            f"{ms(s['gen_late_p95_ms'], 2)} ms")


def _trace_slice(trace_dir: str, start_at: float, seconds: float) -> None:
    """The profiler on for a slice in the middle of the window.  The
    Python tracer stays off: it would record every call of the server's
    per-token loop and slow the thing it looks at."""
    import jax
    _sleep_until(start_at)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        _sleep_until(start_at + seconds)
    finally:
        jax.profiler.stop_trace()
