"""Host-sync guard: catch implicit device→host pulls in the hot loop.

An implicit device→host sync — a stray ``float(loss)``, ``np.asarray(out)``,
``if x:`` on a device value — blocks the dispatch pipeline for a full device
round-trip and serializes the driver loop against device compute.  One of
them inside the per-iteration hot loop undoes the entire
dispatch-pipelining design.

Two detection tiers, both scoped to the ARMED region on the ARMING thread:

- **JAX transfer guards** (``jax.transfer_guard_device_to_host``): on real
  accelerators every implicit device→host copy errors (strict) or logs
  (warn).  On the CPU backend arrays are host-resident so this tier never
  fires — which is why tier two exists.
- **Instrumented conversion hooks**: the array type's ``__float__`` /
  ``__int__`` / ``__bool__`` / ``__index__`` / ``item`` / ``tolist`` /
  ``__array__`` are wrapped once, process-wide; inside an armed region they
  report the offending call-site (file:line of the first frame outside jax
  and this module) before delegating.  Backend-independent, so the tier-1
  CPU test suite exercises the same contract production TPU runs enforce.

Intended pulls go through :func:`host_pull` — the explicit ``device_get``
choke point (validation outputs, the per-iteration loss read) — or an
:func:`allow_host_sync` region.  Both are counted, so a run can report
exactly how many host round-trips its hot loop performed.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import traceback
from typing import Any, Optional

logger = logging.getLogger("bigdl_tpu")

_TLS = threading.local()


def _tls():
    if not hasattr(_TLS, "armed"):
        _TLS.armed = 0
        _TLS.allow = 0
        _TLS.mode = "warn"
    return _TLS


class HostSyncError(ValueError):
    """An implicit device→host sync happened inside an armed hot-loop
    region.  Subclasses ``ValueError``: this is a programming error the
    failure-retry loop must surface, not retry around."""


class _Stats:
    def __init__(self):
        self.lock = threading.Lock()
        self.implicit = 0          # implicit syncs observed while armed
        self.explicit_pulls = 0    # host_pull calls
        self.warned_sites = set()

    def snapshot(self) -> dict:
        with self.lock:
            return {"implicit": self.implicit,
                    "explicit_pulls": self.explicit_pulls}


STATS = _Stats()

_HOOK_NAMES = ("__float__", "__int__", "__index__", "__complex__",
               "__bool__", "item", "tolist", "__array__")
_installed = {"done": False}
_INSTALL_LOCK = threading.Lock()


def _call_site() -> str:
    """file:line of the frame that triggered the conversion — the first
    frame below this module that is user/package code (jaxlib/numpy/jax
    internals are skipped so the diagnostic names the actual pull site)."""
    for frame in reversed(traceback.extract_stack()):
        f = frame.filename
        if (f.endswith("hostsync.py") or "jax/_src" in f or
                "jaxlib" in f or "numpy" in f):
            continue
        return f"{frame.filename}:{frame.lineno} in {frame.name}"
    return "<unknown call site>"


def _report(op: str, arr) -> None:
    st = _tls()
    site = _call_site()
    shape = getattr(arr, "shape", "?")
    dtype = getattr(arr, "dtype", "?")
    msg = (f"implicit device→host sync via {op} on a device value "
           f"(shape={shape}, dtype={dtype}) inside the sanitized hot loop "
           f"at {site} — route intended pulls through "
           "bigdl_tpu.analysis.host_pull(...) (explicit device_get) or an "
           "allow_host_sync() region; silence the pass with "
           "bigdl.analysis.hostSync=off")
    with STATS.lock:
        STATS.implicit += 1
        fresh = site not in STATS.warned_sites
        STATS.warned_sites.add(site)
    if st.mode == "strict":
        raise HostSyncError(msg)
    if fresh:                      # warn once per call-site, count always
        logger.warning("%s", msg)


def _make_hook(name: str, orig):
    def hook(self, *args, **kwargs):
        st = _tls()
        if st.armed > 0 and st.allow == 0:
            _report(name, self)
        return orig(self, *args, **kwargs)
    hook.__name__ = name
    hook._bigdl_hostsync_orig = orig
    return hook


def _install_hooks() -> bool:
    """Wrap the conversion dunders on the concrete jax array type, once per
    process.  The wrappers delegate untouched unless the calling thread is
    inside an armed region, so cost outside the guard is one extra Python
    call on conversions only."""
    with _INSTALL_LOCK:
        if _installed["done"]:
            return True
        try:
            import jax.numpy as jnp
            arr_t = type(jnp.zeros(()))
            for name in _HOOK_NAMES:
                orig = getattr(arr_t, name, None)
                if orig is None or hasattr(orig, "_bigdl_hostsync_orig"):
                    continue
                setattr(arr_t, name, _make_hook(name, orig))
            _installed["done"] = True
            return True
        except Exception as e:  # pragma: no cover - exotic jax builds
            logger.warning("host-sync hooks unavailable on this jax "
                           "build (%s); transfer guards only", e)
            _installed["done"] = True
            return False


@contextlib.contextmanager
def allow_host_sync():
    """Explicitly permit device→host syncs inside an armed region (the
    validation/metrics escape hatch for code that cannot batch through
    :func:`host_pull`)."""
    st = _tls()
    st.allow += 1
    try:
        yield
    finally:
        st.allow -= 1


def host_pull(x: Any, what: str = "") -> Any:
    """The explicit device→host choke point: one ``jax.device_get`` for the
    whole (possibly nested) value, permitted inside armed regions and
    counted.  Use it wherever the hot loop or a validation step genuinely
    needs host values — one batched pull instead of N implicit ones."""
    import jax
    st = _tls()
    st.allow += 1
    try:
        with jax.transfer_guard_device_to_host("allow"):
            out = jax.device_get(x)
    finally:
        st.allow -= 1
    with STATS.lock:
        STATS.explicit_pulls += 1
    return out


class HostSyncGuard:
    """Arms the host-sync pass around a hot-loop region.

    ``with guard.armed(): ...`` — inside, implicit device→host conversions
    on THIS thread raise (strict) or log-once-per-site and count (warn).
    Produced by :meth:`from_config` (``bigdl.analysis.hostSync``); a None
    guard from a disabled config is replaced by :data:`NULL_GUARD`, whose
    ``armed()`` is free."""

    def __init__(self, mode: str = "warn"):
        self.mode = mode
        self.enabled = mode in ("strict", "warn")
        if self.enabled:
            _install_hooks()

    @classmethod
    def from_config(cls) -> "HostSyncGuard":
        from bigdl_tpu.analysis import pass_mode
        mode = pass_mode("hostSync")
        if mode == "off":
            return NULL_GUARD
        return cls(mode)

    @contextlib.contextmanager
    def armed(self):
        if not self.enabled:
            yield
            return
        import jax
        st = _tls()
        prev_mode = st.mode
        st.mode = self.mode
        st.armed += 1
        try:
            # tier one: real accelerators fail implicit D2H copies in the
            # runtime itself ("disallow"); warn mode logs them.  Explicit
            # device_get stays allowed in both — that is the choke point.
            guard_level = "disallow" if self.mode == "strict" else "log"
            try:
                with jax.transfer_guard_device_to_host(guard_level):
                    yield
            except RuntimeError as e:
                # the runtime-level guard (tier one, real accelerators)
                # raises jax's own RuntimeError for pulls the conversion
                # hooks don't cover; translate it so the failure-retry
                # loop treats it as the programming error it is instead
                # of restoring a snapshot and retrying
                msg = str(e)
                if "transfer" in msg.lower() and "guard" in msg.lower():
                    raise HostSyncError(
                        f"implicit device→host transfer inside the "
                        f"sanitized hot loop (jax transfer guard): {msg} — "
                        "route intended pulls through "
                        "bigdl_tpu.analysis.host_pull(...)") from e
                raise
        finally:
            st.armed -= 1
            st.mode = prev_mode

    @property
    def implicit_syncs(self) -> int:
        return STATS.snapshot()["implicit"]


class _NullGuard(HostSyncGuard):
    def __init__(self):
        self.mode = "off"
        self.enabled = False


NULL_GUARD = _NullGuard()
