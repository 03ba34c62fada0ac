"""The open-loop client: a fixed schedule, timed from the due time.

One submitter thread hands each request to ``submit`` when it is due
(never earlier; if the thread is late the request is late, and how late
is recorded - the schedule is NOT re-anchored, so a stall shows in the
latencies of the requests behind it).  One consumer per open stream
iterates it and stamps each token as it arrives.  All times are
``time.perf_counter()`` seconds.

Accounting, as ``serving/loadgen.py`` keeps it: every request offered
ends in exactly one of completed / shed / rejected / quarantined /
undone (still open at the drain limit); their sum is the number offered.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional


class RequestRecord:
    __slots__ = ("index", "due", "submitted", "prompt_len", "max_new",
                 "stamps", "tokens", "outcome", "error", "finished")

    def __init__(self, index: int, due: float, prompt_len: int,
                 max_new: int):
        self.index = index
        self.due = due                  # absolute perf_counter time
        self.submitted: Optional[float] = None
        self.prompt_len = prompt_len
        self.max_new = max_new
        self.stamps: List[float] = []   # arrival time of each token
        self.tokens: List[int] = []
        self.outcome: Optional[str] = None
        self.error: Optional[str] = None
        self.finished: Optional[float] = None


class OpenLoopClient:
    """Offers ``schedule`` (from ``generate.request_schedule``) to
    ``submit(prompt, max_new) -> stream``.

    A stream iterates its tokens and, once exhausted, has ``outcome``
    (``completed`` or a failure); iteration raises the stream's error
    after the tokens it delivered.  A ``submit`` that raises is a request
    rejected at the door."""

    def __init__(self, submit: Callable[[Any, int], Any],
                 schedule: List[Dict[str, Any]]):
        import jax
        self._submit = submit
        self._schedule = schedule
        self._span = jax.profiler.TraceAnnotation   # names the trace's gaps
        self.records: List[RequestRecord] = []
        # a thread per open stream; far more than any cell has open
        self._pool = ThreadPoolExecutor(max_workers=256,
                                        thread_name_prefix="bench-consume")
        self._futures = []
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.t0: Optional[float] = None

    # -- submitter --------------------------------------------------------

    def start(self, t0: Optional[float] = None) -> float:
        """Begin offering; request i is due at ``t0 + due_s``."""
        self.t0 = time.perf_counter() if t0 is None else t0
        self.records = [
            RequestRecord(i, self.t0 + r["due_s"], int(len(r["prompt"])),
                          int(r["max_new"]))
            for i, r in enumerate(self._schedule)]
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-submit")
        self._thread.start()
        return self.t0

    def _run(self) -> None:
        for rec, req in zip(self.records, self._schedule):
            while True:
                wait = rec.due - time.perf_counter()
                if wait <= 0 or self._stop.is_set():
                    break
                # coarse sleep, then a short spin: sleep() alone wakes up
                # to a millisecond late
                time.sleep(wait - 0.0005 if wait > 0.001 else 0)
            if self._stop.is_set():
                rec.outcome = "not_offered"
                continue
            rec.submitted = time.perf_counter()
            try:
                with self._span("bench/submit"):
                    stream = self._submit(req["prompt"], rec.max_new)
            except Exception as e:  # rejected at the door
                rec.outcome = "rejected"
                rec.error = repr(e)[:200]
                rec.finished = time.perf_counter()
                continue
            self._futures.append(self._pool.submit(self._consume, rec,
                                                   stream))

    # -- consumers --------------------------------------------------------

    def _consume(self, rec: RequestRecord, stream) -> None:
        try:
            for tok in stream:
                rec.stamps.append(time.perf_counter())
                rec.tokens.append(int(tok))
        except Exception as e:
            rec.error = repr(e)[:200]
        rec.outcome = getattr(stream, "outcome", None) or (
            "completed" if rec.error is None else "shed")
        rec.finished = time.perf_counter()

    # -- end --------------------------------------------------------------

    def drain(self, deadline: float) -> int:
        """Wait until every offered request has ended or ``deadline``
        (absolute) passes; what is still open is marked ``undone``.
        Returns how many were undone."""
        while time.perf_counter() < deadline:
            if not self._thread.is_alive() and all(
                    r.outcome is not None for r in self.records):
                break
            time.sleep(0.02)
        self._stop.set()
        self._thread.join(5.0)
        undone = 0
        for r in self.records:
            if r.outcome is None:
                r.outcome = "undone"
                undone += 1
        return undone

    def abandon(self) -> None:
        """Stop offering at once (a failure elsewhere); ``close`` follows
        once the engine has stopped."""
        self._stop.set()

    def close(self) -> None:
        """After the engine has stopped (which ends every open stream)."""
        self._stop.set()
        self._pool.shutdown(wait=True, cancel_futures=True)
        for f in self._futures:
            if f.done() and not f.cancelled():
                f.result()

    def close_consumers(self) -> None:
        """Between passes of a sweep, the engine still running: consumers
        of streams that are still open finish on their own."""
        self._stop.set()
        self._pool.shutdown(wait=False)


# ---------------------------------------------------------------------------
# reduction of the client's records
# ---------------------------------------------------------------------------

def quantile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank quantile (the value at or above the q-th share)."""
    if not values:
        return None
    v = sorted(values)
    k = max(0, min(len(v) - 1, int(-(-q * len(v) // 1)) - 1))
    return v[k]


def summarize(records: List[RequestRecord], w0: float, w1: float,
              deadline_ms: float) -> Dict[str, Any]:
    """The client's view of the window ``[w0, w1)``."""
    due_in = [r for r in records if w0 <= r.due < w1]
    ttft = []
    for r in due_in:
        if r.outcome == "completed" and r.stamps:
            ttft.append((r.stamps[0] - r.due) * 1e3)
        else:
            ttft.append(deadline_ms)     # a failed request misses any limit
    gaps: List[float] = []
    tokens_in = 0
    for r in records:
        if r.outcome != "completed":
            continue
        for i, t in enumerate(r.stamps):
            if w0 <= t < w1:
                tokens_in += 1
                if i:
                    gaps.append((t - r.stamps[i - 1]) * 1e3)
    late = [(r.submitted - r.due) * 1e3 for r in records
            if r.submitted is not None]
    outcomes: Dict[str, int] = {}
    for r in due_in:
        outcomes[r.outcome or "open"] = outcomes.get(r.outcome or "open",
                                                     0) + 1
    completed_in = sum(1 for r in records if r.outcome == "completed"
                       and r.finished is not None and w0 <= r.finished < w1)
    return {
        "due_in_window": len(due_in),
        "completed_in_window": completed_in,
        "outcomes_of_due": outcomes,
        "failed_of_due": sum(v for k, v in outcomes.items()
                             if k != "completed"),
        "tokens_in_window": tokens_in,
        "serve_tok_s": tokens_in / (w1 - w0),
        "ttft_ms": ttft,
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "ttft_p50_ms": quantile(ttft, 0.5),
        "ttft_p90_ms": quantile(ttft, 0.9),
        "itl_gaps": len(gaps),
        "itl_p50_ms": quantile(gaps, 0.5),
        "itl_p95_ms": quantile(gaps, 0.95),
        "gen_late_p95_ms": quantile(late, 0.95),
        "gen_late_max_ms": max(late) if late else None,
    }
