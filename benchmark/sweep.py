"""Finds a serving cell's knee: one process, one warm engine, one pass of
the open loop at each offered rate.

    python3 benchmark/sweep.py --workload opt67b-serve-chat-r80 \\
        --rates 1.6,2.0,2.4

A pass is the cell's own schedule (its trace at that rate, its lead-in)
followed for two windows of the benchmark's ``run_seconds``
end to end, so every rate is seen in two windows of the length the cell
is judged at.  The knee is the highest offered rate at which (a) the
requests completed in the windows are within 3% of the requests due in
them, (b) none failed, and (c) the engine's ``queue_depth`` is no higher
at the end of the last window than at the middle of the pass.  The
cell's rate is four fifths of it, rounded to 0.1, and is written into
the traffic file by hand with the table this prints (PERF.md keeps the
table).  Between passes the engine drains fully, so each pass starts as
the cell does: empty, then a lead-in.  Run it on the chip; it refuses
anything else unless ``--allow-cpu`` (a rehearsal).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


#: windows of ``run_seconds`` a pass follows end to end
WINDOWS = 2


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated requests a second, ascending")
    p.add_argument("--seconds", type=float, default=None,
                   help="a window's length (BENCHMARK.json's run_seconds)")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--data-dir", default=None)
    args = p.parse_args(argv)
    from benchmark import cells, run as _run
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = float(json.load(f)["run_seconds"])
    if args.data_dir:
        cells.DATA_DIRS.insert(0, os.path.abspath(args.data_dir))
    cell = cells.load_cell(args.workload)
    config = cells.load_config(cell["config"])
    from bigdl_tpu.engine import init_compile_cache
    init_compile_cache()
    device = _run.device_gate(int(cell["chips"]), args.allow_cpu)
    args.trace = 0
    ctx = _run.Context(args, cell, config, device, None)
    role = cells.role(cell["role"])
    model, eng = role.setup(ctx)
    rows = []
    try:
        eng.start()
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = dict(cell["mix"], rate=rate)
            passed = role.offer(ctx, eng, mix, args.seconds, args.seed,
                                windows=WINDOWS)
            passed["client"].close_consumers()
            s, snaps = passed["summary"], passed["snaps"]
            sustained = (
                s["completed_in_window"] >= 0.97 * s["due_in_window"]
                and s["failed_of_due"] == 0
                and snaps["close"]["queue_depth"]
                <= snaps["middle"]["queue_depth"])
            keys = ("due_in_window", "completed_in_window", "serve_tok_s",
                    "ttft_mean_ms", "ttft_p50_ms", "ttft_p90_ms",
                    "itl_p50_ms", "itl_p95_ms")
            row = {"rate": rate, "due": s["due_in_window"],
                   "completed": s["completed_in_window"],
                   "failed": s["failed_of_due"],
                   "queue_middle": snaps["middle"]["queue_depth"],
                   "queue_close": snaps["close"]["queue_depth"],
                   "slots_middle": snaps["middle"]["active_slots"],
                   "slots_close": snaps["close"]["active_slots"],
                   "decode_ema_ms": snaps["close"]["decode_ema_ms"],
                   "windows": [{k: w[k] for k in keys}
                               for w in passed["summaries"]],
                   "undone": passed["undone"], "sustained": sustained}
            rows.append(row)
            _run.say(f"rate {rate}: {role.describe_pass(passed)} -> "
                     f"{'sustained' if sustained else 'NOT sustained'}")
            for i, w in enumerate(passed["summaries"]):
                _run.say(f"  window {i + 1}: " + ", ".join(
                    f"{k} {w[k]:.1f}" if isinstance(w[k], float)
                    else f"{k} {w[k]}" for k in keys))
            # let whatever the drain limit cut short run out
            t_end = time.perf_counter() + 60.0
            while (eng.stats()["active_slots"] or eng.queue_depth()) \
                    and time.perf_counter() < t_end:
                time.sleep(0.1)
    finally:
        eng.stop(grace=0.0)
    knee = max((r["rate"] for r in rows if r["sustained"]), default=None)
    out = {"workload": args.workload, "device": device,
           "seconds": args.seconds, "windows": WINDOWS,
           "seed": args.seed, "rows": rows,
           "knee": knee,
           "four_fifths": None if knee is None else round(0.8 * knee, 1)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
