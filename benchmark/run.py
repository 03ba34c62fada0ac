"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no child.  It refuses any platform but a TPU and any
machine with fewer chips than the cell asks for, keeps XLA's persistent
compilation cache inside the checkout (``bigdl_tpu.engine.
init_compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``), warms up what the cell uses, measures for
``--seconds``, checks the program's outputs against the plain reference,
and prints one JSON object as the last line of its standard output.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, a device busy time from the
profiler's trace, and a breakdown.

Everything that belongs to one cell, configuration, traffic mix, role or
metric is a file found by name (``cells.py``); this file knows none of
them.  ``--allow-cpu`` and ``--set`` exist for the rehearsal on the CPU
and for sweeps; a run that used either says so in its line and is not a
measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # before JAX: set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_START:7.2f}s] {msg}",
          flush=True)


class Context:
    """What a role is handed."""

    def __init__(self, args, cell, config, device, trace_dir):
        self.cell, self.config = cell, config
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.trace_dir = trace_dir if self.trace else None
        self.device = device
        self.on_tpu = device["platform"] == "tpu"
        self.say = say
        #: when each backend compile ended; one inside the window fails
        #: the run
        self.compile_times = []

    def compiles_in(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.compile_times if t0 < t < t1)


def device_gate(chips: int, allow_cpu: bool) -> dict:
    if "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", "") and not allow_cpu:
        raise SystemExit("benchmark: XLA_FLAGS asks for virtual CPU "
                         "devices; this command measures on the chip only")
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not allow_cpu:
        raise SystemExit(f"benchmark: JAX found platform {dev.platform!r}, "
                         "not a TPU; nothing is measured on anything else")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:max(1, chips)]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    say(f"memory_stats of chip 0: {jax.devices()[0].memory_stats()}")
    return peak


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--allow-cpu", action="store_true",
                   help="rehearsal only: run on whatever JAX finds; the "
                        "line says so and carries no device metric")
    p.add_argument("--set", action="append", default=[],
                   metavar="key=value", help="override a value of the "
                   "cell (sweeps, rehearsals); the line says so")
    p.add_argument("--data-dir", default=None, metavar="DIR",
                   help="look for workloads/, configs/ and traffic/ under "
                        "DIR first (the rehearsal's toy files)")
    p.add_argument("--keep-trace", default=None, metavar="DIR",
                   help="keep the profiler's files under DIR")
    args = p.parse_args(argv)

    from benchmark import cells
    if args.data_dir:
        cells.DATA_DIRS.insert(0, os.path.abspath(args.data_dir))
    cell = cells.load_cell(args.workload)
    cells.apply_overrides(cell, args.set)
    config = cells.load_config(cell["config"])
    try:
        import bigdl_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        raise SystemExit(f"benchmark: the program is not here: {e}")

    from bigdl_tpu.engine import init_compile_cache
    cache_dir = init_compile_cache()
    device = device_gate(int(cell["chips"]), args.allow_cpu)
    say(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace} on {device} cache {cache_dir}")

    import jax
    ctx = None

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration" and ctx:
            ctx.compile_times.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    keep = args.keep_trace
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        trace_dir = os.path.abspath(keep) if keep else tmp
        ctx = Context(args, cell, config, device, trace_dir)
        record = cells.role(cell["role"]).run(ctx)
        record["setup_s"] = record["t_window_open"] - T_START
        record["memory_peak_bytes"] = memory_peak(int(cell["chips"]))
        if ctx.trace:
            import re

            from benchmark import trace_reduce
            kernel = cell.get("trace", {}).get("kernel_pattern")
            record["trace"] = trace_reduce.reduce_trace(
                trace_dir, kernel=re.compile(kernel) if kernel else None)
            if record["trace"] is None or not record["trace"].get("chips"):
                raise SystemExit("benchmark: the traced run left no device "
                                 "operation in its trace")

    t_close = record.get("t_window_close") or record["detail"].get("t_close")
    in_window = ctx.compiles_in(record["t_window_open"], t_close)
    record["checks"]["no_compile_in_window"] = in_window == 0
    record["device_kind"] = device["kind"]

    metrics = {}
    for m in cells.load_metrics(cell, end_to_end=not ctx.trace):
        try:
            value = cells.reader(m["reader"])(record, m)
        except KeyError as e:
            if ctx.on_tpu:
                raise
            say(f"  {m['name']}: not measured ({e})")   # no peaks for a CPU
            continue
        if value is None:
            continue        # a reader that finds nothing reports nothing
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        say(f"  {m['name'] if ctx.on_tpu else 'rehearsal value'} = {value} "
            f"{m['unit']}")

    failed_checks = sorted(k for k, v in record["checks"].items() if not v)
    if failed_checks:
        say(f"checks that failed: {failed_checks}")
    say("detail " + json.dumps(record["detail"], default=str))
    if record.get("timings"):
        say("compiled " + json.dumps(record["timings"], default=str))
    out = {
        "correct": not failed_checks,
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": {**device,
                   "memory_peak_bytes": record["memory_peak_bytes"]},
        "checks_failed": failed_checks,
        "workload": args.workload, "seed": args.seed,
        "seconds": args.seconds,
    }
    if ctx.trace:
        out["device"]["busy_s"] = record["trace"]["busy_s"]
        out["device"]["window_s"] = record["trace"]["window_s"]
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    if args.allow_cpu or args.set or args.data_dir:
        out["not_a_measurement"] = {"allow_cpu": args.allow_cpu,
                                    "set": args.set,
                                    "data_dir": args.data_dir}
    if not ctx.on_tpu:
        # a number from a CPU run is never written under the name of a
        # device metric
        out["rehearsal_values"] = out["metrics"]
        out["metrics"] = {}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        if e.code not in (None, 0):
            print(e, file=sys.stderr)
            rc = e.code if isinstance(e.code, int) else 1
        else:
            rc = 0
    except BaseException:  # noqa: BLE001 - any failure: no result line
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (prefetchers, watchdogs) must not
    # hold the process: everything of ours is joined by now
    os._exit(rc)
