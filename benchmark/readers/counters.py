"""What the counter readers share: the program's own registry
(``bigdl_tpu.telemetry``), read in-process as ``spans.py`` reads it.  A
program that has no such counter (it is from before the PR that brought
it) gives 0 or ``None``, and the reader leaves its metric out of the
line."""

from typing import Optional


def value(name: str, kind: str) -> float:
    """The counter's or gauge's value; 0 where the program never made it
    (asking the registry for it makes an empty one) or keeps another kind
    of metric under the name."""
    from bigdl_tpu import telemetry
    try:
        return float(getattr(telemetry, kind)(name).value)
    except TypeError:
        return 0.0


def share(part: str, whole: str) -> Optional[float]:
    """Percent of the counter ``whole`` that the counter ``part`` is."""
    total = value(whole, "counter")
    return 100.0 * value(part, "counter") / total if total else None


def histogram_sum(name: str) -> float:
    from bigdl_tpu import telemetry
    try:
        return float(telemetry.histogram(name).sum)
    except TypeError:
        return 0.0
