"""The ``bigdl.*`` configuration-property tier.

Reference equivalent: JVM system properties documented in
``docs/docs/UserGuide/configuration.md:28-41`` and read ad hoc across the
tree (``utils/Engine.scala:113-137``, ``parameters/AllReduceParameter.scala:34``,
``optim/DistriOptimizer.scala:751-752``).

TPU-native form: environment variables ``BIGDL_<DOTTED_NAME>`` (dots →
underscores, upper-cased) with programmatic overrides via :func:`set_property`.
The property names keep the reference's dotted vocabulary so its docs map 1:1.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

# name -> default; the reference's table (configuration.md:28-41) minus the
# JVM/Spark-only knobs that have no TPU analog (thread-pool sizes, nio).
_DEFAULTS: Dict[str, Any] = {
    "bigdl.engineType": "tpu",
    "bigdl.localMode": False,
    "bigdl.coreNumber": None,              # discovered from jax
    "bigdl.failure.retryTimes": 5,
    "bigdl.failure.retryTimeInterval": 120,  # base backoff seconds
    "bigdl.failure.maxRetryInterval": 900,   # backoff cap (exponential+jitter)
    "bigdl.io.retryTimes": 3,                # remote-fs transient retry budget
    "bigdl.io.retryInterval": 0.1,           # remote-fs retry base seconds
    "bigdl.checkpoint.keepLast": 0,          # snapshot retention; 0 = keep all
    "bigdl.checkpoint.asyncWrite": False,    # background checkpoint writer
    "bigdl.divergence.guard": True,          # skip non-finite updates in-step
    "bigdl.divergence.maxBadSteps": 5,       # consecutive bad steps → restore
    # chaos-injection harness (utils/chaos.py); 0/None = disarmed
    "bigdl.chaos.failWriteAt": 0,
    "bigdl.chaos.truncateWriteAt": 0,
    "bigdl.chaos.transientWrites": 0,
    "bigdl.chaos.failStepAt": 0,
    "bigdl.chaos.nanLossAt": None,
    "bigdl.chaos.preemptAt": 0,        # iteration k: simulated SIGTERM
    "bigdl.chaos.stallStepAt": None,   # "k:seconds": iteration k hangs
    "bigdl.chaos.topologyChangeAt": 0,  # iteration k: mesh goes away
    # ingest-stage fault injection (dataset/ingest.py stage threads)
    "bigdl.chaos.corruptRecordAt": None,  # "k" / "k:m": records read as corrupt
    "bigdl.chaos.corruptRecordEvery": 0,  # every Nth record reads corrupt
    "bigdl.chaos.failDecodeAt": None,     # "k" / "k:m": records fail decode
    "bigdl.chaos.transientReads": 0,      # first n record reads blip + recover
    "bigdl.chaos.killStageThread": None,  # "stage" / "stage:k": silent death
    # compile-subsystem fault injection (utils/compile_cache.py)
    "bigdl.chaos.corruptCompileCacheAt": 0,  # k: bit-flip the k-th cache entry
    "bigdl.chaos.hangCompileAt": None,    # "k" / "k:seconds": wedge k-th compile
    # serving-path fault injection (bigdl_tpu/serving)
    "bigdl.chaos.slowRequestAt": None,    # "k" / "k:seconds": k-th request handled slow
    "bigdl.chaos.poisonRequestAt": None,  # "k" / "k:m": admission positions k..m poison
    "bigdl.chaos.hangDispatchAt": None,   # "k" / "k:seconds": k-th batch dispatch wedges
    "bigdl.chaos.burstArrivals": None,    # "k" / "k:n": n extra arrivals at position k
    # LM-serving fault injection (bigdl_tpu/serving/lm.py)
    "bigdl.chaos.poisonPromptAt": None,   # "k" / "k:m": admission positions k..m poison prompts
    "bigdl.chaos.hangDecodeAt": None,     # "k" / "k:seconds": k-th decode iteration wedges
    "bigdl.chaos.evictBlockAt": 0,        # k: a KV block "evicts" at decode iteration k
    # fleet-control-plane faults (bigdl_tpu/fleet)
    "bigdl.chaos.killReplicaAt": None,    # "k" / "k:replica": async-kill a replica's
    # batcher thread at the fleet's k-th submitted request
    "bigdl.chaos.corruptCandidateAt": 0,  # k: corrupt the k-th rollout candidate's
    # weights after its fingerprint is taken (pre-cutover verify must catch it)
    "bigdl.chaos.sigtermFleetAt": 0,      # k: fleet-wide preemption (SIGTERM) at
    # the fleet's k-th submitted request
    # elastic training (utils/elastic.py): topology-elastic restore +
    # graceful preemption
    "bigdl.elastic.gracePeriod": 30.0,  # seconds for the final drain+snapshot
    "bigdl.elastic.reshardOnRestore": True,  # N->M slot reshard vs reject
    "bigdl.elastic.handleSignals": False,    # SIGTERM/SIGINT -> graceful drain
    "bigdl.elastic.globalShuffle": True,  # one global epoch permutation
    # (partition-count-invariant batch stream) vs partition-local blocks
    # (pre-elastic per-host memory footprint, same-topology replay only)
    # hung-step watchdog (utils/elastic.py): step open > k x EMA -> abort
    "bigdl.watchdog.stallFactor": 0,   # 0 disables the monitor thread
    "bigdl.watchdog.pollInterval": 0.25,  # monitor wake period, seconds
    "bigdl.watchdog.warmupSteps": 5,   # EMA warmup (compile exemption)
    "bigdl.watchdog.cooldownSteps": 50,  # heartbeats between fires
    "bigdl.watchdog.timelineDir": None,  # dump telemetry timeline here on fire
    "bigdl.check.singleton": False,
    "bigdl.summary.flushSecs": 2.0,
    # resilient compilation (utils/compile_cache.py): persistent fused-step
    # executable cache + AOT warmup watchdog + shape buckets.  XLA's own
    # compilation cache is placed by engine.init_compile_cache, not here.
    "bigdl.compile.cacheDir": None,        # executable cache dir; None = off
    "bigdl.compile.timeoutSec": 0,         # compile watchdog abort; 0 = off
    "bigdl.compile.keepLast": 0,           # cache entries retained; 0 = all
    "bigdl.compile.buckets": None,         # "8,16,32": ragged eval/predict batches pad up
    "bigdl.compile.lockTimeoutSec": 30.0,  # single-writer lock wait cap
    "bigdl.compile.lockStaleSec": 600.0,   # steal writer locks older than this
    "bigdl.pipeline.depth": 8,             # driver-loop dispatch pipeline
    # (not measured on the current machine)
    # overload-tolerant serving (bigdl_tpu/serving): admission-controlled
    # request path — bounded queue, per-request deadlines, shedding,
    # poison quarantine, hung-dispatch watchdog, graceful drain
    "bigdl.serving.maxBatch": 16,          # batcher coalesce ceiling
    "bigdl.serving.maxQueueDepth": 128,    # admission queue bound (reject past it)
    "bigdl.serving.deadlineMs": 1000.0,    # default per-request deadline
    "bigdl.serving.admissionDeadlineFactor": 1.0,  # reject when projected wait > f x deadline
    "bigdl.serving.lingerMs": 0.0,         # batcher waits this long to fill a batch
    "bigdl.serving.pollInterval": 0.05,    # batcher idle/monitor wake period, seconds
    "bigdl.serving.stallFactor": 0,        # hung-dispatch watchdog: abort > k x EMA; 0 off
    "bigdl.serving.warmupBatches": 3,      # dispatch-EMA warmup (compile exemption)
    "bigdl.serving.cooldownSteps": 8,      # batches after a watchdog fire before re-admission
    "bigdl.serving.gracePeriod": 5.0,      # drain window for SIGTERM / stop, seconds
    # LM token serving (bigdl_tpu/serving/lm.py): continuous batching over
    # a paged KV cache — ONE fixed (maxBatch, 1) decode shape, bucketed
    # prefill plan, streaming per-request token output
    "bigdl.lm.maxBatch": 8,                # concurrent decode slots (the fixed decode batch)
    "bigdl.lm.maxContext": 256,            # prompt + generated tokens ceiling per sequence
    "bigdl.lm.blockSize": 16,              # KV-cache tokens per block
    "bigdl.lm.cacheBlocks": 0,             # KV pool blocks incl. dump block; 0 = derive
    # maxBatch x blocks_per_seq(maxContext) + 1
    "bigdl.lm.prefillBuckets": None,       # "16,32,64": prompt pad-up plan; None = pow2
    # ladder from blockSize to maxContext
    "bigdl.lm.maxNewTokens": 64,           # default generation cap per request
    "bigdl.lm.deadlineMs": 5000.0,         # default end-to-end per-request deadline
    "bigdl.lm.maxQueueDepth": 128,         # admission queue bound (reject past it)
    "bigdl.lm.admissionDeadlineFactor": 0,  # reject when projected wait > f x deadline; 0 off
    "bigdl.lm.stallFactor": 0,             # hung-decode watchdog: abort > k x EMA; 0 off
    "bigdl.lm.warmupSteps": 3,             # decode-EMA warmup (compile exemption)
    "bigdl.lm.cooldownSteps": 8,           # decode iterations after a watchdog fire
    # before re-admission
    "bigdl.lm.gracePeriod": 5.0,           # drain window for SIGTERM / stop, seconds
    "bigdl.lm.pollInterval": 0.01,         # scheduler idle wake period, seconds
    "bigdl.lm.quantize": "off",            # "int8": decode matmuls on int8 weights,
    # gated by the auditor precision pass + an allclose logits check
    "bigdl.lm.quantizeRtol": 0.05,         # int8-gate allclose rtol vs full precision
    "bigdl.lm.quantizeAtol": 0.05,         # int8-gate allclose atol vs full precision
    # fleet control plane (bigdl_tpu/fleet): N models x N replicas under one
    # supervisor — zero-downtime hot swap, blue/green rollout gated on the
    # semantic checkpoint fingerprint + shadow-traffic parity, crash restarts,
    # replica autoscaling, checkpoint-to-serving promotion
    "bigdl.fleet.replicas": 1,             # replicas per service at add_model
    "bigdl.fleet.minReplicas": 1,          # autoscale floor
    "bigdl.fleet.maxReplicas": 4,          # autoscale ceiling
    "bigdl.fleet.pollInterval": 0.05,      # supervisor tick period, seconds
    "bigdl.fleet.maxReplicaRestarts": 2,   # crash restarts per replica slot
    "bigdl.fleet.gracePeriod": 5.0,        # retired-replica drain window, seconds
    "bigdl.fleet.shadowSample": 8,         # live requests mirrored per rollout
    "bigdl.fleet.parityMode": "bitwise",   # bitwise | allclose | off
    "bigdl.fleet.parityRtol": 1e-5,        # allclose rtol for shadow parity
    "bigdl.fleet.parityAtol": 1e-6,        # allclose atol for shadow parity
    "bigdl.fleet.promotionPollSec": 0.2,   # checkpoint watch_latest cadence
    "bigdl.fleet.autoscale.enabled": False,   # scale replica count per service
    "bigdl.fleet.autoscale.intervalSec": 0.25,  # decision cadence
    "bigdl.fleet.autoscale.upQueueFrac": 0.5,   # mean queue fill frac -> +1
    "bigdl.fleet.autoscale.downQueueFrac": 0.05,  # below this -> -1 toward floor
    "bigdl.fleet.autoscale.p99Factor": 0.8,  # +1 when p99 > factor x deadline
    "bigdl.fleet.autoscale.patience": 2,   # consecutive signals before acting
    "bigdl.fleet.autoscale.cooldown": 3,   # hold intervals after an action
    # streaming ingest engine (dataset/ingest.py): stage-pipelined
    # real-data path — sharded seqfile readers -> record ring -> decode
    # pool -> decoded window -> native assembler -> batch ring -> device
    # transfer-ahead (engine.BatchPrefetcher)
    "bigdl.ingest.shards": 2,              # parallel seqfile reader threads
    "bigdl.ingest.decodeWorkers": None,    # decode pool size; None = host cores
    "bigdl.ingest.recordRingDepth": 256,   # reader -> decode record ring
    "bigdl.ingest.decodedRingDepth": None, # in-flight decode window; None = 2x batch
    "bigdl.ingest.batchRingDepth": 2,      # assembled batches buffered ahead
    "bigdl.ingest.batchesInFlight": 2,     # device uploads in flight (transfer-ahead;
    # not measured on the current machine)
    "bigdl.ingest.deviceAugment": False,   # pack FULL uint8 frames + ride-along
    # crop offsets/flips; crop/flip/transpose runs on device (nn.DeviceAugment)
    # stage autoscaling (dataset/ingest.py _Autoscaler): the supervisor
    # adds/retires decode workers (and native assemble threads) from the
    # per-stage starve/backpressure signals, governor as upper bound
    "bigdl.ingest.autoscale.enabled": True,   # scale decode/assemble workers
    "bigdl.ingest.autoscale.min": 1,          # decode-worker floor
    "bigdl.ingest.autoscale.max": 0,          # worker ceiling; 0 = host cores
    "bigdl.ingest.autoscale.intervalSec": 0.25,  # decision cadence
    "bigdl.ingest.autoscale.upStarveFrac": 0.2,  # assemble starve frac -> +1
    "bigdl.ingest.autoscale.downStarveFrac": 0.02,  # below this (or
    # backpressure-bound) -> -1 toward the floor
    "bigdl.ingest.autoscale.patience": 2,     # consecutive signals before acting
    "bigdl.ingest.autoscale.cooldown": 3,     # hold intervals after an action
    # decoded-epoch cache (dataset/epoch_cache.py): repeated-epoch training
    # pays JPEG decode once; RAM segments, optional checksummed disk spill
    "bigdl.ingest.epochCache": False,      # cache decoded frames across epochs
    "bigdl.ingest.epochCacheDir": None,    # disk-spill dir; None = RAM only
    "bigdl.ingest.epochCacheBudgetMB": 0,  # cache byte cap; 0 = governor only
    "bigdl.ingest.epochCacheSegmentRecords": 256,  # records per segment
    # self-healing ingest (error taxonomy + quarantine + supervision)
    "bigdl.ingest.maxBadRecords": 0,       # data-error quarantine budget; 0 = fail fast
    "bigdl.ingest.maxStageRestarts": 2,    # dead-stage restarts before escalation
    "bigdl.ingest.fallbackOnFailure": False,  # dead engine -> sync MT path mid-epoch
    "bigdl.ingest.stallTimeoutSec": 0,     # wedged-ring detection; 0 = disabled
    # static-analysis / sanitizer passes (bigdl_tpu/analysis): each pass is
    # "strict" (raise), "warn" (log + count), or "off"
    "bigdl.analysis.retrace": "warn",      # recompile sentinel on fused steps
    "bigdl.analysis.retraceWarmupSteps": 2,  # calls treated as warmup compiles
    "bigdl.analysis.retraceBudget": 2,     # distinct signatures allowed in warmup
    "bigdl.analysis.hostSync": "warn",     # implicit device→host pulls in hot loop
    "bigdl.analysis.hotLoopScope": "iteration",  # sanitize fetch+step, or "step"
    "bigdl.analysis.contracts": "warn",    # module contract checker strictness
    "bigdl.analysis.lockWitness": "off",   # runtime lock-order witness
    # (analysis/lockwitness): strict raises LockOrderViolation on any
    # acquisition-order cycle, warn logs once per edge pair; armed
    # strict for every tier-1 test by the conftest fixture
    "bigdl.chaos.lockDelayAt": None,   # "<lockname>:k[:seconds]": the k-th
    # acquisition of the named witness lock stalls (default 0.05 s),
    # deterministically widening a racy window; once per position
    # HLO program auditor (bigdl_tpu/analysis/hlo_audit): static passes
    # over every fused step's lowered StableHLO, same strict/warn/off
    # vocabulary as bigdl.analysis.*
    "bigdl.audit.collectives": "warn",  # collective contract checker
    "bigdl.audit.precision": "warn",    # f64 / f32-in-bf16 drift pass
    "bigdl.audit.memory": "warn",       # peak-buffer + transpose budget pass
    # training-state integrity (bigdl_tpu/integrity): on-device
    # fingerprints + cross-replica agreement + weight-health gates.
    # everyN is the DRIVER pull/verify cadence — when > 0 the fused
    # steps compute fingerprints every iteration and the driver
    # classifies them every N iterations; 0 disables the whole path
    "bigdl.integrity.everyN": 0,
    "bigdl.integrity.seed": 0x51D0,        # projection-sign seed
    "bigdl.integrity.healthFactor": 0,     # weight-health gate: > k x EMA; 0 off
    "bigdl.integrity.healthWarmup": 5,     # EMA warmup observations
    "bigdl.integrity.healthCooldown": 50,  # observations between fires
    # integrity fault injection (silent-data-corruption simulators)
    "bigdl.chaos.bitflipParamAt": None,    # "k" / "k:leaf": flip one param bit
    "bigdl.chaos.desyncReplicaAt": None,   # "k" / "k:replica": one dp replica drifts
    "bigdl.chaos.corruptStateBeforeSaveAt": 0,  # k: k-th snapshot capture corrupted
    # audit fault injection: provoke the violations the auditor exists
    # to catch (step-BUILD time, unlike the runtime chaos hooks above)
    "bigdl.chaos.extraAllGather": False,  # redundant all-gather in shard_map
    "bigdl.chaos.f32Upcast": False,       # f32 matmul inside a bf16 program
    "bigdl.chaos.dropBucketCollective": None,  # k: bucket k's reduce-scatter
    # silently replaced by a local slice — MISSING-collective auditor prey
    # latency-hiding collective overlap (parallel/distri_optimizer.py):
    # the ZeRO-1 exchange runs as N independent per-bucket reduce-scatter
    # -> update -> all-gather chains so XLA's latency-hiding scheduler can
    # overlap ICI with compute; same wire bytes, same numerics
    "bigdl.parallel.overlap": True,        # False = monolithic baseline step
    "bigdl.parallel.overlapBuckets": 4,    # contiguous param buckets per step
    # MoE execution path (nn/moe.py): "einsum" = capacity-slot dispatch/
    # combine einsums (GShard reference form), "grouped" = expert-sorted
    # scatter + grouped batched matmul + gather-combine (same capacity-drop
    # and aux-loss semantics, O(t*k*d) instead of O(t*E*C*d) data movement)
    "bigdl.moe.impl": "einsum",
    # default activation-checkpoint policy for transformer_lm blocks when
    # the builder's remat arg is unset: "nothing" / "dots" / "save_attn"
    # (nn.Remat's preset vocabulary); None = no remat
    "bigdl.remat.policy": None,
    # runtime telemetry (bigdl_tpu/telemetry): span tracer + step-time
    # decomposition + metrics registry
    "bigdl.telemetry.trace": False,        # arm the span tracer
    "bigdl.telemetry.ringSize": 65536,     # per-thread span ring capacity
    "bigdl.telemetry.tracePath": None,     # export Chrome trace JSON here at run end
    "bigdl.telemetry.snapshotPath": None,  # write telemetry.json registry snapshot here
    "bigdl.telemetry.logEveryN": 1,        # throughput log line every N iterations
    "bigdl.telemetry.percentileWindow": 512,  # rolling step-latency window
    "bigdl.telemetry.slowStepFactor": 0,   # slow step = > k x EMA; 0 disables
    "bigdl.telemetry.slowStepWarmup": 5,   # EMA warmup steps before detection
    "bigdl.telemetry.slowStepCooldown": 50,  # min steps between anomaly windows
    "bigdl.telemetry.profileOnSlowStep": None,  # dir: capture jax.profiler + timeline
    "bigdl.telemetry.maxTimelineDumps": 8,  # timeline dump files per run
    # (slow-step detector + watchdog), oldest-first eviction; 0 disables
    # resource-exhaustion resilience (bigdl_tpu/resources): HBM preflight
    # + microbatch backoff, host-memory governor, disk-full degradation
    "bigdl.resources.deviceMemBudgetMB": 0,  # HBM budget per fused step;
    # preflight + dispatch-OOM -> microbatch re-plan; 0 = preflight off
    "bigdl.resources.hostMemBudgetMB": 0,  # soft host budget over all
    # accounted rings/queues; breach shrinks depths; 0 = accounting only
    # resource-exhaustion fault injection (utils/chaos.py)
    "bigdl.chaos.oomStepAt": 0,        # k: k-th step dispatch raises a
    # realistic RESOURCE_EXHAUSTED (once per plan)
    "bigdl.chaos.diskFullAt": None,    # "k"/"k:substr" (comma-separable):
    # the k-th write_bytes [matching substr] raises ENOSPC, once each
    "bigdl.chaos.hostMemPressureAt": 0,  # k: governor poll k reports
    # zero free bytes (once per plan) — shrinker/backpressure prey
    "bigdl.chaos.starveStageAt": None,   # "stage:k" / "stage:k:seconds":
    # the named ingest stage throttles from its k-th item for the window —
    # downstream stages starve; autoscaler acceptance prey
    # per-request distributed tracing (telemetry/request_trace.py)
    "bigdl.trace.requests": False,       # mint a trace id per serving/LM/fleet
    # submission; span chain + terminal verdict per request
    "bigdl.trace.maxTraces": 2048,       # retained traces (oldest evicted first)
    "bigdl.trace.maxSpansPerTrace": 512,  # per-trace span bound (then truncated flag)
    # incident flight recorder (telemetry/incident.py)
    "bigdl.incident.ringSize": 512,      # bounded structured-event ring capacity
    "bigdl.incident.maxDumps": 8,        # bundle files per run, oldest-first
    # eviction; 0 disables bundle writes entirely
    "bigdl.incident.dir": None,          # bundle directory; None = CWD
    "bigdl.incident.autoDump": True,     # write one bundle per terminal fault slug
    # driver log file (utils/logger_filter.py): size-capped rotation so a
    # long run cannot grow bigdl.log without bound
    "bigdl.utils.LoggerFilter.disable": False,      # leave logging untouched
    "bigdl.utils.LoggerFilter.enableSparkLog": True,  # redirect chatty infra logs
    "bigdl.utils.LoggerFilter.logFile": None,       # None = <CWD>/bigdl.log
    "bigdl.utils.LoggerFilter.maxBytes": 10485760,  # rotate past 10 MiB
    "bigdl.utils.LoggerFilter.backupCount": 2,      # rotated files retained
}

_OVERRIDES: Dict[str, Any] = {}


def _env_key(name: str) -> str:
    return name.replace(".", "_").upper()


def get_property(name: str, default: Optional[Any] = None) -> Any:
    """Resolution order: set_property override > env var > table default."""
    if name in _OVERRIDES:
        return _OVERRIDES[name]
    env = os.environ.get(_env_key(name))
    if env is not None:
        return env
    if name in _DEFAULTS and _DEFAULTS[name] is not None:
        return _DEFAULTS[name]
    return default


def get_int(name: str, default: int = 0) -> int:
    v = get_property(name, default)
    return int(v)


def get_float(name: str, default: float = 0.0) -> float:
    v = get_property(name, default)
    return float(v)


def get_bool(name: str, default: bool = False) -> bool:
    v = get_property(name, default)
    if isinstance(v, bool):
        return v
    return str(v).lower() in ("1", "true", "yes", "on")


def set_property(name: str, value: Any) -> None:
    _OVERRIDES[name] = value


def clear_property(name: str) -> None:
    _OVERRIDES.pop(name, None)


def known_properties() -> Dict[str, Any]:
    """The full table with current values (for diagnostics)."""
    return {k: get_property(k) for k in _DEFAULTS}


def non_default_properties() -> Dict[str, Any]:
    """Every property whose effective value differs from the table
    default — programmatic overrides, ``BIGDL_*`` environment settings,
    and override keys outside the table.  The incident bundle embeds
    exactly this (the *effective* configuration an operator must know
    to explain a run, without the 200-line full table)."""
    out: Dict[str, Any] = {}
    for name, default in _DEFAULTS.items():
        value = get_property(name)
        if value != default and not (value is None and default is None):
            out[name] = value
    for name, value in _OVERRIDES.items():
        if name not in _DEFAULTS:
            out[name] = value
    return out
