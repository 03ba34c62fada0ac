"""Span tracer: thread-safe, allocation-light timeline capture.

The driver hot loop, the streaming-ingest stage threads, the prefetcher's
fetch/transfer threads, and the async checkpoint writer all mark their
work with :func:`span` context managers.  Each thread appends finished
spans to its OWN bounded ring buffer (no cross-thread locking on the hot
path — the global lock is taken once per thread, at ring registration),
and :func:`export_chrome_trace` merges every ring into one Chrome
trace-event JSON (the ``chrome://tracing`` / Perfetto format), one lane
per thread, so a single file shows whether the pipeline stages actually
overlap.

Arming.  ``span()`` records when the tracer was armed explicitly
(:func:`arm`, ``bigdl.telemetry.trace``) OR while a ``jax.profiler``
session runs (``TraceAnnotation.is_enabled()``): a profiler capture arms
the program's own spans for as long as it lasts, with no switch of its
own.  A span recorded under a session also enters a
``jax.profiler.TraceAnnotation`` of the same name and args for its
duration, so the same interval lands on the host plane of the
``.xplane.pb`` — one ``with`` block, two sinks.  The host plane's clock
is not the device plane's: they lie 0.8-2.2 ms apart, by another offset
in every trace (PERF.md section 5), so a device gap is laid against a
span only after aligning the planes on the runtime's enqueue and done
events (ROADMAP Reach B1).  :func:`add_span`, :func:`add_span_s` and
:func:`instant` write the ring only.

A span's parent is the enclosing span on the same lane (spans are
context managers, so containment is exact); :func:`self_times` gives
each span its duration less the children it covers.

Cost model (measured on the chip's host, PERF.md section 6): with no
session and no explicit arm, ``span()`` is one ``is_enabled()`` call,
one module-dict load and a shared no-op context manager — no clock
read, no allocation.  Armed, a span costs two ``monotonic_ns`` reads and
one tuple append into a ``deque(maxlen=...)``; under a session the twin
adds one ``TraceAnnotation``.  Spans never touch device values — arming
the tracer cannot introduce a host sync (the strict host-sync guard
stays armed over traced runs in the tier-1 suite to prove it).

The tracer's clock — :func:`clock_ns` — is THE timer for hot-path code:
the ``raw-clock-in-hot-path`` lint rule flags direct ``time.*`` reads in
``drain``/``run_step``/``shard_step``/``step`` functions outside this
package, so every duration in the system is measured on one monotonic
clock and two subsystems' timestamps can always be laid on one timeline.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Twin

#: True between ``jax.profiler.start_trace`` and ``stop_trace`` (about
#: 20 ns): the second way, beside :func:`arm`, in which spans are armed
_session_on = _Twin.is_enabled

#: the one hot-path clock: monotonic (immune to wall-clock steps), ns
#: resolution, same epoch as ``time.monotonic()`` (fractional seconds
#: from legacy call sites convert with a multiply).
clock_ns = time.monotonic_ns

DEFAULT_RING_SIZE = 65536

#: retained per-thread rings; beyond this the oldest DEAD thread's ring
#: is evicted (a long pytest session spawns thousands of short-lived
#: ingest/prefetch threads — their rings must not accumulate forever)
MAX_RINGS = 256

_LOCK = threading.Lock()
_TLS = threading.local()

# armed flag + ring size live in a plain dict: one dict load on the
# disarmed fast path, no attribute-protocol indirection
_STATE: Dict[str, Any] = {"enabled": False,
                          "ring_size": DEFAULT_RING_SIZE,
                          "epoch_ns": 0}

_RINGS: "List[_ThreadRing]" = []
_LANE_SEQ = [0]


class _ThreadRing:
    """One thread's span ring.  ``lane`` is a registration-ordered id
    (thread idents are recycled by the OS; lanes must stay distinct in
    the exported trace), ``events`` holds finished spans as
    ``(name, t0_ns, t1_ns, args)`` tuples — ``t1_ns is None`` marks an
    instant event."""

    __slots__ = ("lane", "name", "thread", "events")

    def __init__(self, lane: int, name: str, thread: threading.Thread,
                 maxlen: int):
        self.lane = lane
        self.name = name
        self.thread = thread
        self.events: deque = deque(maxlen=maxlen)


def _tls_ring() -> _ThreadRing:
    ring = getattr(_TLS, "ring", None)
    if ring is None:
        t = threading.current_thread()
        with _LOCK:
            _LANE_SEQ[0] += 1
            ring = _ThreadRing(_LANE_SEQ[0], t.name, t,
                               _STATE["ring_size"])
            _RINGS.append(ring)
            if len(_RINGS) > MAX_RINGS:
                for i, r in enumerate(_RINGS):
                    if not r.thread.is_alive():
                        del _RINGS[i]
                        break
                else:
                    _RINGS.pop(0)
        _TLS.ring = ring
    return ring


class _NullSpan:
    """Shared no-op context manager: the disarmed ``span()`` result."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "t0", "twin")

    def __init__(self, name: str, args: Optional[dict],
                 twin: Optional[_Twin]):
        self.name = name
        self.args = args
        self.twin = twin

    def __enter__(self):
        if self.twin is not None:
            self.twin.__enter__()
        self.t0 = clock_ns()
        return self

    def __exit__(self, *exc):
        t1 = clock_ns()
        if self.twin is not None:
            self.twin.__exit__(*exc)
        _tls_ring().events.append((self.name, self.t0, t1, self.args))
        return False

    def set(self, **args):
        """Args known only inside the block (``admitted=3``): added to
        the ring's record and to the twin's stats."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)
        if self.twin is not None:
            self.twin.set_metadata(**args)


def tracing_enabled() -> bool:
    """Armed explicitly (:func:`arm`).  A profiler session arms
    :func:`span` too, but owns no timeline export: see :func:`recording`."""
    return _STATE["enabled"]


def recording() -> bool:
    """Whether a span made now is recorded: armed explicitly, or a
    ``jax.profiler`` session is running."""
    return _STATE["enabled"] or _session_on()


def arm(ring_size: Optional[int] = None) -> None:
    """Switch span capture on.  ``ring_size`` bounds each thread's event
    ring (oldest spans fall off first); already-registered rings keep
    their size."""
    with _LOCK:
        if ring_size is not None:
            _STATE["ring_size"] = int(ring_size)
        if not _STATE["enabled"]:
            _STATE["epoch_ns"] = clock_ns()
        _STATE["enabled"] = True


def disarm() -> None:
    _STATE["enabled"] = False


def maybe_arm_from_config() -> bool:
    """Arm iff ``bigdl.telemetry.trace`` is set truthy; never disarms
    (an explicit :func:`arm` — e.g. the test suite's — wins).  Returns
    the resulting enabled state."""
    from bigdl_tpu.utils import config
    if config.get_bool("bigdl.telemetry.trace", False):
        arm(ring_size=config.get_int("bigdl.telemetry.ringSize",
                                     DEFAULT_RING_SIZE))
    return _STATE["enabled"]


def reset() -> None:
    """Drop every captured span (rings stay registered, lanes keep their
    ids).  Test isolation; also the start-of-run hook so one process's
    second training run exports only its own timeline."""
    with _LOCK:
        for ring in _RINGS:
            ring.events.clear()
        _STATE["epoch_ns"] = clock_ns()


def span(name: str, **args):
    """``with telemetry.span("optim/device_step"): ...`` — record the
    enclosed wall interval on this thread's lane, and under a profiler
    session on the profiler's host plane too.  With neither switch: one
    ``is_enabled()`` call and the shared no-op."""
    if _session_on():
        return _Span(name, args or None, _Twin(name, **args))
    if _STATE["enabled"]:
        return _Span(name, args or None, None)
    return _NULL_SPAN


def add_span(name: str, t0_ns: int, t1_ns: int,
             args: Optional[dict] = None) -> None:
    """Record an already-measured interval (both endpoints from
    :func:`clock_ns`).  For call sites that time work anyway (the ingest
    stage counters): no extra clock reads."""
    if recording():
        _tls_ring().events.append((name, t0_ns, t1_ns, args))


def add_span_s(name: str, t0_s: float, t1_s: float,
               args: Optional[dict] = None) -> None:
    """:func:`add_span` for endpoints measured with ``time.monotonic()``
    (fractional seconds, same epoch as the ns clock)."""
    if recording():
        _tls_ring().events.append((name, int(t0_s * 1e9), int(t1_s * 1e9),
                                   args))


def instant(name: str, **args) -> None:
    """A zero-duration marker on this thread's lane (slow-step flags,
    epoch rollovers)."""
    if recording():
        _tls_ring().events.append((name, clock_ns(), None, args or None))


def name_thread(name: str) -> None:
    """Name the current thread's lane in the exported trace (threads
    that were not created with a telling ``Thread(name=...)``)."""
    ring = _tls_ring()
    ring.name = name


def events() -> List[dict]:
    """Every captured span as dicts (diagnostics / tests)."""
    out = []
    with _LOCK:
        rings = [(r.lane, r.name, list(r.events)) for r in _RINGS]
    for lane, lname, evs in rings:
        for name, t0, t1, args in evs:
            out.append({"lane": lane, "thread": lname, "name": name,
                        "t0_ns": t0, "t1_ns": t1, "args": args})
    return out


def self_times(spans: List[dict]) -> List[Tuple[dict, int]]:
    """``[(span, self_ns)]`` by start time for the spans of ONE lane (as
    :func:`events` gives them; instants are skipped): a span's parent is
    the span that encloses it, and its self time is its duration less the
    children it covers.  The self times of a lane sum to what its
    top-level spans cover, so a lane whose loop is spanned end to end
    accounts for its whole slice."""
    spans = sorted((e for e in spans if e["t1_ns"] is not None),
                   key=lambda e: (e["t0_ns"], -e["t1_ns"]))
    own = [e["t1_ns"] - e["t0_ns"] for e in spans]
    stack: List[int] = []
    for i, e in enumerate(spans):
        while stack and spans[stack[-1]]["t1_ns"] < e["t1_ns"]:
            stack.pop()
        if stack:
            own[stack[-1]] -= e["t1_ns"] - e["t0_ns"]
        stack.append(i)
    return list(zip(spans, own))


def export_chrome_trace(path: Optional[str] = None) -> dict:
    """Merge every thread ring into one Chrome trace-event JSON object
    (``{"traceEvents": [...], "displayTimeUnit": "ms"}``), optionally
    written to ``path``.  Loadable by Perfetto / ``chrome://tracing``:
    ``X`` (complete) events carry ``ts``/``dur`` in microseconds relative
    to the arm time, ``M`` metadata events name the process and one lane
    per thread, ``i`` events are instants."""
    epoch = _STATE["epoch_ns"]
    trace_events: List[dict] = [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "bigdl_tpu"}},
    ]
    with _LOCK:
        rings = [(r.lane, r.name, list(r.events)) for r in _RINGS
                 if r.events]
    for lane, lname, evs in rings:
        trace_events.append({"ph": "M", "name": "thread_name", "pid": 0,
                             "tid": lane, "args": {"name": lname}})
        trace_events.append({"ph": "M", "name": "thread_sort_index",
                             "pid": 0, "tid": lane,
                             "args": {"sort_index": lane}})
        for name, t0, t1, args in evs:
            ev = {"ph": "X" if t1 is not None else "i",
                  "name": name, "cat": name.split("/", 1)[0],
                  "pid": 0, "tid": lane,
                  "ts": (t0 - epoch) / 1e3}
            if t1 is not None:
                ev["dur"] = max(t1 - t0, 0) / 1e3
            else:
                ev["s"] = "t"
            if args:
                ev["args"] = args
            trace_events.append(ev)
    # per-request lanes (telemetry/request_trace.py) merge into the same
    # timeline under their own "requests" process row; lazy import —
    # request_trace imports this module at its top
    from bigdl_tpu.telemetry import request_trace
    trace_events.extend(request_trace.chrome_events(epoch))
    doc = {"traceEvents": trace_events, "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc
