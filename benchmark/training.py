"""What the training roles share: the window inside one ``optimize()``,
the checks around it, and the record the readers take.

The program has no time-based end of training, and needs none:
``optim.Trigger`` takes any callable of the driver's state.  The role
passes :class:`Window`, which is at once the end trigger and a handler
on the ``bigdl_tpu`` logger.  A step is *complete* when its loss is on
the host, which is when the driver logs it; dispatch times are never
used.  Warm-up is the first ``warm_steps`` completions of the same
``optimize()`` call (the compile is before the first of them); the
window opens at the completion of the last warm step and closes at the
last completion before ``seconds`` have passed, so it holds whole steps
only and a rate over it has no step-sized rounding in it.
"""

from __future__ import annotations

import logging
import math
import re
import statistics
import time
from typing import Any, Callable, Dict, List, Optional

_LOSS = re.compile(r"\[Iteration (\d+)\].*Loss is ([-+.\w]+?)\.( |$)")


class Window(logging.Handler):
    """End trigger and loss tap in one object (see the module's text)."""

    def __init__(self, seconds: float, warm_steps: int,
                 on_first_call: Optional[Callable[[], None]] = None):
        super().__init__(level=logging.INFO)
        self.seconds = float(seconds)
        self.warm_steps = int(warm_steps)
        self.on_first_call = on_first_call
        self.steps: List[tuple] = []       # (iteration, time, loss)
        self.t_open: Optional[float] = None
        self.calls = 0

    # the logger's side: one line per completed step
    def emit(self, record: logging.LogRecord) -> None:
        m = _LOSS.search(record.getMessage())
        if not m:
            return
        now = time.perf_counter()
        self.steps.append((int(m.group(1)), now, float(m.group(2))))
        if len(self.steps) == self.warm_steps:
            self.t_open = now

    # the driver's side: asked before every dispatch
    def __call__(self, state: Dict[str, Any]) -> bool:
        self.calls += 1
        if self.calls == 1 and self.on_first_call is not None:
            self.on_first_call()
        return (self.t_open is not None and
                time.perf_counter() >= self.t_open + self.seconds)

    # the reduction
    def measured(self) -> Dict[str, Any]:
        if self.t_open is None:
            raise RuntimeError(
                f"training ended after {len(self.steps)} completed steps, "
                f"before the {self.warm_steps} warm-up steps were done")
        t_close = self.t_open + self.seconds
        inside = [s for s in self.steps if self.t_open < s[1] <= t_close]
        if len(inside) < 2:
            raise RuntimeError(
                f"only {len(inside)} step(s) completed in a window of "
                f"{self.seconds} s: the window is too short for this cell")
        times = [self.t_open] + [s[1] for s in inside]
        gaps = [b - a for a, b in zip(times, times[1:])]
        return {"steps": len(inside),
                "seconds": inside[-1][1] - self.t_open,
                "t_open": self.t_open, "t_close": inside[-1][1],
                "step_ms_median": statistics.median(gaps) * 1e3,
                "step_ms_max": max(gaps) * 1e3}


def run_optimize(opt, window: Window) -> None:
    """``optimize()`` with the window as end trigger and log tap, and no
    retry: a failure is itself, at once (as ``chip_smoke._train``)."""
    import jax

    import bigdl_tpu.optim as optim
    from bigdl_tpu.utils import config
    opt.set_end_when(optim.Trigger(window))
    lg = logging.getLogger("bigdl_tpu")
    level = lg.level
    lg.addHandler(window)
    lg.setLevel(logging.INFO)
    config.set_property("bigdl.failure.retryTimes", 1)
    try:
        with jax.profiler.TraceAnnotation("bench/optimize"):
            opt.optimize()
    finally:
        config.clear_property("bigdl.failure.retryTimes")
        lg.removeHandler(window)
        lg.setLevel(level)


def optim_method(job: Dict[str, Any]):
    """The optimizer the job names, for the run and for ``sizing.py``
    alike, so that a plan and a run hold the same slots.  A job trains
    under SGD with its ``learning_rate`` and ``momentum``, or names
    ``job["optim_method"] = {"name": "adam", ...}`` with Adam's own
    hyper-parameters under the names ``bigdl_tpu.optim.Adam`` gives them:
    one spelling of each, and never both in one job."""
    import bigdl_tpu.optim as optim
    if "optim_method" not in job:
        return optim.SGD(float(job["learning_rate"]),
                         momentum=float(job["momentum"]))
    both = sorted({"learning_rate", "momentum"} & set(job))
    if both:
        raise SystemExit(f"benchmark: the job names optim_method and, "
                         f"beside it, {both}: which of them would train?")
    hyper = dict(job["optim_method"])
    name = hyper.pop("name")
    if name != "adam":
        raise SystemExit(f"benchmark: job.optim_method.name is {name!r}; "
                         "known: 'adam' (SGD is the job without the key)")
    return optim.Adam(**hyper)


def configure(opt, job: Dict[str, Any], trace_dir: Optional[str]) -> None:
    opt.set_precision(job.get("precision", "bf16"))
    opt.set_optim_method(optim_method(job))
    if trace_dir:
        opt.set_trace_profile(
            trace_dir,
            start_iteration=int(job["warm_steps"])
            + int(job["trace_after_steps"]),
            n_iterations=int(job["trace_steps"]))


def oom_count() -> float:
    from bigdl_tpu import telemetry
    return telemetry.counter("Resources/device_oom").value


def step_of(opt):
    step = getattr(opt, "_step_fn", None)
    return getattr(step, "__wrapped__", step)


def after_checks(opt, window: Window, oom_before: float,
                 first_loss_ref: Optional[float],
                 first_loss_tol: float) -> Dict[str, Any]:
    """Decides ``correct`` after the window; every entry is a reason."""
    losses = [s[2] for s in window.steps]
    step = step_of(opt)
    sentinel = getattr(opt, "_retrace_sentinel", None)
    k = 8
    checks: Dict[str, Any] = {}
    checks["losses_finite"] = all(math.isfinite(v) for v in losses)
    # a mean over eight completions: batches differ from each other by
    # more than a few steps gain (a cycle of eight is seen once each)
    head, tail = losses[:k], losses[-k:]
    checks["loss_fell"] = (len(losses) >= 2 * k and
                           statistics.fmean(tail) < statistics.fmean(head))
    checks["one_compile"] = getattr(step, "compiles", None) == 1
    checks["no_retrace"] = (sentinel is not None
                            and sentinel.retraces == 0)
    checks["no_oom_replan"] = (oom_count() == oom_before
                               and opt._microbatch_k == 1)
    if first_loss_ref is not None:
        checks["first_loss_close"] = (
            abs(losses[0] - first_loss_ref) <= first_loss_tol)
    return {"checks": checks,
            "first_loss": losses[0], "first_loss_reference": first_loss_ref,
            "loss_head_mean": statistics.fmean(head),
            "loss_tail_mean": statistics.fmean(tail),
            "steps_total": len(losses)}


def measure(ctx, opt, window: Window) -> Dict[str, Any]:
    """The measure step of every training role: one ``optimize()`` with
    the cell's settings, the window inside it."""
    configure(opt, ctx.cell["job"], ctx.trace_dir)
    oom_before = oom_count()
    run_optimize(opt, window)
    return {"oom_before": oom_before, **window.measured()}


def report(ctx, opt, window: Window, measured: Dict[str, Any],
           verdict: Dict[str, Any], first_loss_ref: Optional[float],
           flops_per_step: float, units_per_step: int, unit: str,
           chips: int = 1) -> Dict[str, Any]:
    """The report step of every training role: the checks after the
    window and the record the readers take.  ``units_per_step`` is
    tokens or images, for the rate said on an earlier line."""
    after = after_checks(
        opt, window, measured["oom_before"], first_loss_ref,
        float(ctx.cell["job"]["first_loss_tolerance"]))
    after["checks"].update(verdict["checks"])
    rate = measured["steps"] / measured["seconds"]
    ctx.say(f"{measured['steps']} steps in {measured['seconds']:.3f} s: "
            f"{rate * units_per_step:.1f} {unit}/s, loss "
            f"{after['loss_head_mean']:.4f} -> {after['loss_tail_mean']:.4f}")
    return {
        "t_window_open": measured["t_open"],
        "attempted": measured["steps"], "failed": 0,
        "checks": after["checks"],
        "detail": {**after, **verdict["detail"], **measured,
                   f"{unit}_per_s": rate * units_per_step,
                   "flops_per_step": flops_per_step},
        "train": {"steps_per_s": rate, "flops_per_step": flops_per_step,
                  "chips": chips,
                  "step_ms_median": measured["step_ms_median"],
                  "data_wait_frac": data_wait_frac(opt)},
        "timings": step_timings(opt),
    }


def step_timings(opt) -> List[Dict[str, Any]]:
    step = step_of(opt)
    return [dict(t, label=getattr(step, "label", "step"))
            for t in getattr(step, "timings", [])]


def data_wait_frac(opt) -> Optional[float]:
    acct = getattr(opt, "_step_account", None)
    if acct is None:
        return None
    return acct.summary().get("data_wait_frac")
