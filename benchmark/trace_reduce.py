"""From a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  A
TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds one event per executed HLO operation, and one host plane
(``/host:CPU``) with a line per thread that holds ``TraceMe`` events,
the benchmark's own ``jax.profiler.TraceAnnotation`` spans among them.
All times here are nanoseconds on the trace's clock.

The reduction:

- *slice*: from the first device operation's start to the last one's end
  over all chips.  The profiler is started and stopped by host calls
  that take time themselves, so the raw session has idle edges that
  belong to the profiler, not to the program.
- *busy*: per chip, the union of its operations' intervals inside the
  slice.  Idle share = 1 - busy / slice.
- *gaps*: the idle intervals of the busiest-idle chip, longest first,
  each labelled by the benchmark span (``bench/...``) that covers most
  of it on the host, else ``inside-program``.
- *top operations*: total duration by operation, named by
  :func:`op_label` (instruction name and result type).  XLA's running
  numbers change with any edit of the program, so a name is stable
  across runs of one program only; the result type says which it is.
- *collectives*: operations whose name says all-reduce, reduce-scatter,
  all-gather, collective-permute or all-to-all, on the operation line or
  as windows on the asynchronous line.  Exposed = the part of their
  union during which no other operation runs on that chip.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"            # one event per executed operation, in order
ASYNC_LINE = "Async XLA Ops"    # DMA and collective windows that overlap them
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all",
    re.I)
BENCH_SPAN = "bench/"


def find_xplane(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of union ``a`` not covered by union ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def read_events(path: str) -> Dict[str, Any]:
    """``{"devices": {chip: [(start, end, name)]}, "async": {chip: [...]},
    "spans": [(start, end, name)]}`` from an ``.xplane.pb``: each chip's
    operations, its asynchronous collective windows, and the host's
    ``bench/`` spans.  An operation's name is its HLO text cut down by
    :func:`op_label`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Tuple[int, int, str]]] = {}
    asyncs: Dict[int, List[Tuple[int, int, str]]] = {}
    spans: List[Tuple[int, int, str]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    into = devices.setdefault(int(m.group(1)), [])
                elif line.name == ASYNC_LINE:
                    into = asyncs.setdefault(int(m.group(1)), [])
                else:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    name = op_label(ev.name)
                    if line.name == ASYNC_LINE and not COLLECTIVE.search(
                            name):
                        continue
                    into.append((s, s + int(ev.duration_ns), name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(BENCH_SPAN):
                        s = int(ev.start_ns)
                        spans.append((s, s + int(ev.duration_ns), ev.name))
    return {"devices": {k: sorted(v) for k, v in devices.items() if v},
            "async": {k: sorted(v) for k, v in asyncs.items() if v},
            "spans": sorted(spans)}


def describe(path: str, top: int = 40) -> Dict[str, Any]:
    """What a trace holds, for looking at one by hand: planes, lines,
    event counts and the most frequent names of each line."""
    from collections import Counter

    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names = Counter()
            dur = Counter()
            n = 0
            for ev in line.events:
                n += 1
                names[ev.name] += 1
                dur[ev.name] += int(ev.duration_ns)
            lines.append({"line": line.name, "events": n,
                          "by_time": [[k, v] for k, v in
                                      dur.most_common(top)]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(text: str, limit: int = 96) -> str:
    """The trace names an operation by its whole HLO text.  Kept: the
    instruction's name and the type of its result without layouts,
    ``fusion.10 (pred[], bf16[4096,50272])`` - enough to tell the head's
    weight gradient from a block's, and a kernel by its name."""
    name, sep, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not sep:
        return name[:limit]
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            rest = rest[:i]
            break
    return f"{name} {_LAYOUT.sub('', rest)}"[:limit]


def label_gap(gap: Interval, spans: List[Tuple[int, int, str]]) -> str:
    best, best_ov = "inside-program", 0
    for s, e, name in spans:
        if s >= gap[1]:
            break
        ov = overlap(gap, (s, e))
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce_events(events: Dict[str, Any], top_ops: int = 10,
                  top_gaps: int = 5,
                  kernel: Optional[re.Pattern] = None) -> Dict[str, Any]:
    """The numbers the readers take.  ``kernel`` selects the operations
    summed under ``kernel_ns`` / ``kernel_events`` (a roofline's
    denominator)."""
    devices = events["devices"]
    if not devices:
        return {"chips": 0}
    lo = min(ops[0][0] for ops in devices.values())
    hi = max(max(e for _, e, _ in ops) for ops in devices.values())
    window = hi - lo
    per_chip = {}
    by_name: Dict[str, int] = {}
    kernel_ns = kernel_events = 0
    for chip, ops in devices.items():
        busy = union((s, e) for s, e, _ in ops)
        coll = union(
            [(s, e) for s, e, n in ops if COLLECTIVE.search(n)]
            + [(max(s, lo), min(e, hi)) for s, e, _ in
               events.get("async", {}).get(chip, [])])
        other = union((s, e) for s, e, n in ops
                      if not COLLECTIVE.search(n))
        exposed = subtract(coll, other)
        idle = subtract([(lo, hi)], busy)
        per_chip[chip] = {
            "busy_ns": total(busy), "idle_ns": total(idle),
            "collective_ns": total(coll), "exposed_ns": total(exposed),
            "gaps": sorted(idle, key=lambda g: g[0] - g[1])[:top_gaps],
            "ops": len(ops)}
        for s, e, n in ops:
            by_name[n] = by_name.get(n, 0) + (e - s)
            if kernel is not None and kernel.search(n):
                kernel_ns += e - s
                kernel_events += 1
    worst = max(per_chip, key=lambda c: per_chip[c]["idle_ns"])
    worst_exposed = max(per_chip, key=lambda c: per_chip[c]["exposed_ns"])
    n = len(per_chip)
    gaps = [[label_gap(g, events["spans"]), (g[1] - g[0]) / 1e9]
            for g in per_chip[worst]["gaps"]]
    ops_top = sorted(by_name.items(), key=lambda kv: -kv[1])[:top_ops]
    return {
        "chips": n,
        "window_s": window / 1e9,
        "busy_s": sum(c["busy_ns"] for c in per_chip.values()) / n / 1e9,
        "idle_share_worst": per_chip[worst]["idle_ns"] / window,
        "collective_exposed_share_worst":
            per_chip[worst_exposed]["exposed_ns"] / window,
        "collective_share_worst":
            max(c["collective_ns"] for c in per_chip.values()) / window,
        "device_ops": [[k, v / n / 1e9] for k, v in ops_top],
        "idle_gaps": gaps,
        "kernel_s": kernel_ns / n / 1e9,
        "kernel_events": kernel_events,
        "op_events": sum(c["ops"] for c in per_chip.values()),
    }


def reduce_trace(log_dir: str, **kw) -> Optional[Dict[str, Any]]:
    path = find_xplane(log_dir)
    if path is None:
        return None
    return reduce_events(read_events(path), **kw)
