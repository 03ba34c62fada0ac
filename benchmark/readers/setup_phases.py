"""What the set-up readers share: the program's histograms
``Compile/phase_ms{phase=...}`` (one observation an executable a phase)
and ``Setup/program_ms{entry=...}`` (one an entry point's call), read
in-process as ``counters.py`` reads the registry.  A program from before
them has neither: asking makes an empty one, and the reader returns
``None``, so the line leaves the metric out."""

from typing import Iterable, Optional

ENTRIES = ("lm_construct", "lm_warmup", "optimize")


def histogram(name: str, label: str, value: str):
    """The labelled histogram, or ``None`` where the program keeps
    another kind of metric under the name."""
    from bigdl_tpu import telemetry
    try:
        return telemetry.histogram(name, labels={label: value})
    except TypeError:
        return None


def count(name: str, label: str, values: Iterable[str]) -> int:
    return sum(h.count for h in (histogram(name, label, v) for v in values)
               if h is not None)


def total_s(name: str, label: str, values: Iterable[str]) -> float:
    return sum(h.sum for h in (histogram(name, label, v) for v in values)
               if h is not None) / 1e3


def phases_s(*phases: str) -> Optional[float]:
    """Seconds the executables spent in these phases; ``None`` where no
    executable went through any of them."""
    if not count("Compile/phase_ms", "phase", phases):
        return None
    return total_s("Compile/phase_ms", "phase", phases)
