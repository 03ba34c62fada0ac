"""Overload-tolerant serving: the admission-controlled request path.

The repo's ``Predictor`` serves an *array*; production serves a *queue*.
This package is the request path built on top of ``Predictor``/
``fold_bn`` whose headline property is that it degrades gracefully
instead of falling over:

- :class:`~bigdl_tpu.serving.engine.ServingEngine` — a bounded admission
  queue with per-request deadlines feeding a continuous micro-batching
  dispatcher: requests coalesce up to ``bigdl.serving.maxBatch``, pad to
  the ``bigdl.compile.buckets`` shape plan (zero post-warmup retraces
  under arbitrary arrival patterns), execute through the tracked compile
  cache, and fan back per-request.
- Robustness is the build, not a bolt-on: admission rejects fast with a
  structured :class:`~bigdl_tpu.serving.engine.Overloaded` (reject at
  the door, never silent tail-latency collapse); expired requests are
  shed at dequeue time before wasting a device slot; a poison-request
  quarantine (:class:`~bigdl_tpu.serving.engine.ServingDataError` vs
  :class:`~bigdl_tpu.serving.engine.ServingInfraError` — the PR 7
  taxonomy) fails the one offending request and keeps the batch alive;
  a hung-dispatch watchdog aborts a wedged dispatch and cools the
  engine down; SIGTERM drains in-flight work within
  ``bigdl.serving.gracePeriod`` and rejects late arrivals retriably.
- :class:`~bigdl_tpu.serving.lm.LMServingEngine` — LM TOKEN serving:
  continuous (iteration-level) batching over a paged block-table KV
  cache (:class:`~bigdl_tpu.serving.kv_cache.PagedKVCache`, sized once
  under the HBM preflight budget), one fixed ``(maxBatch, 1)`` decode
  shape plus a bucketed prefill plan under the strict retrace-sentinel
  contract, per-request streaming :class:`~bigdl_tpu.serving.lm.
  TokenStream` output, and an optional int8 decode-weight tier gated by
  the HLO auditor's precision pass + an fp-vs-int8 logits allclose
  (``docs/optimization.md`` "LM serving").
- :mod:`~bigdl_tpu.serving.loadgen` — the Poisson open-loop load
  generator the accounting-identity and chaos proofs of
  ``tests/test_serving.py`` and ``tests/test_lm_serving.py`` drive the
  engines with (the benchmark has its own: ``benchmark/traffic/``),
  including the ``bigdl.chaos.burstArrivals`` thundering-herd injector;
  :func:`~bigdl_tpu.serving.loadgen.run_lm_open_loop` adds client-side
  TTFT / inter-token-latency percentiles over streamed tokens.

Everything is instrumented through the PR 5 metrics registry
(``Serving/*`` and ``LM/*``: latency percentiles, queue depth, outcome
counters, block/slot occupancy) with Prometheus export, and
chaos-proven by the ``bigdl.chaos.slowRequestAt`` / ``poisonRequestAt``
/ ``hangDispatchAt`` / ``burstArrivals`` injectors plus the LM trio
``poisonPromptAt`` / ``hangDecodeAt`` / ``evictBlockAt``.
"""

from bigdl_tpu.serving.engine import (HungDispatchError, Overloaded,
                                      RequestHandle, ServingDataError,
                                      ServingEngine, ServingError,
                                      ServingInfraError)
from bigdl_tpu.serving.kv_cache import PagedKVCache
from bigdl_tpu.serving.lm import (LMServingEngine, QuantizationGateError,
                                  TokenStream, UnsupportedModelError)
from bigdl_tpu.serving.loadgen import (run_lm_open_loop, run_open_loop,
                                       sample_lm_workload)

__all__ = [
    "ServingEngine", "RequestHandle", "ServingError", "Overloaded",
    "ServingDataError", "ServingInfraError", "HungDispatchError",
    "LMServingEngine", "TokenStream", "PagedKVCache",
    "QuantizationGateError", "UnsupportedModelError",
    "run_open_loop", "run_lm_open_loop", "sample_lm_workload",
]
