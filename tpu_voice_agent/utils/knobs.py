"""Central env-knob registry: every tunable the serving plane reads.

~90 raw ``os.environ`` reads back the plane's tuning surface; before this
module the only record of a knob's existence was its call site plus —
sometimes — a hand-kept row in one of the three docs tables. Now every
knob is declared HERE (name, default, one-line doc, and which docs table
owns its operator-facing row), and the ``env-knob`` checker in
``tools/analyze`` enforces the loop mechanically:

- an env read under ``tpu_voice_agent/`` whose name is not declared here
  fails the analyzer;
- a declared knob missing from its table's doc file fails, and a doc row
  whose name is not declared here fails (two-way sync);
- a declared knob nothing reads fails (stale declaration).

``table=None`` marks infrastructure env (JAX bootstrap, test/bench
harness plumbing) that is deliberately NOT in the operator docs — the
checker conversely rejects doc rows for those.

Declarations are literal on purpose: the analyzer parses this file with
``ast`` and never imports it, so the firewall works on a tree too broken
to import. Runtime accessors (``get``/``knob_int``/...) assert the name
is declared, making the registry load-bearing in both directions.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

RESILIENCE = "docs/RESILIENCE.md"
PERF = "docs/PERF.md"
OBSERVABILITY = "docs/OBSERVABILITY.md"


@dataclass(frozen=True)
class Knob:
    name: str
    default: str | None  # None = unset means "feature off"/"no value"
    doc: str
    table: str | None


KNOBS: dict[str, Knob] = {}


def declare(name: str, default: str | None, doc: str,
            table: str | None = None) -> Knob:
    if name in KNOBS:
        raise ValueError(f"knob {name!r} declared twice")
    k = Knob(name, default, doc, table)
    KNOBS[name] = k
    return k


# ---------------------------------------------------------------- runtime

def get(name: str, default: str | None = None) -> str | None:
    """Declared-knob env read. Undeclared names raise — code that wants a
    new knob declares it (and its doc row) first."""
    k = KNOBS.get(name)
    if k is None:
        raise KeyError(f"env knob {name!r} is not declared in utils/knobs.py")
    fallback = default if default is not None else k.default
    return os.environ.get(name, fallback)  # analyze: ok[env-knob] -- the registry's own accessor: callers must pass a declared name (enforced by the KeyError above and by the env-knob checker at their call site)


def knob_str(name: str, default: str | None = None) -> str | None:
    return get(name, default)


def knob_int(name: str, default: int | None = None) -> int:
    v = get(name, None if default is None else str(default))
    if v is None:
        raise KeyError(f"env knob {name!r} has no value and no default")
    return int(v)


def knob_float(name: str, default: float | None = None) -> float:
    v = get(name, None if default is None else str(default))
    if v is None:
        raise KeyError(f"env knob {name!r} has no value and no default")
    return float(v)


def knob_bool(name: str, default: bool | None = None) -> bool:
    """``default=None`` (the usual case) falls through to the DECLARED
    default; passing a bool here overrides it for this call only."""
    v = get(name, None if default is None else ("1" if default else "0"))
    return v is not None and str(v).lower() in ("1", "true", "yes", "on")


# ============================================================ resilience
# docs/RESILIENCE.md — fault containment, breakers, router tier, drains

declare("CHAOS_FAULTS", None, "fault spec `point:prob`/`point@kth`, comma-separated (unset = off)", table=RESILIENCE)
declare("CHAOS_SEED", "0", "per-point RNG seed — same spec+seed replays identically", table=RESILIENCE)
declare("CHAOS_STALL_S", "2.0", "how long an injected stall_step sleeps", table=RESILIENCE)
declare("CHAOS_HANG_S", "60", "how long an injected replica_hang holds /parse open", table=RESILIENCE)
declare("CHAOS_SLOW_S", "0.25", "added latency of an injected replica_slow parse", table=RESILIENCE)
declare("QUARANTINE_AFTER", "2", "poison offenses before a prompt fingerprint is refused", table=RESILIENCE)
declare("SCHED_POOL_WAIT_S", "1.0", "pool-backpressure wait before a request sheds", table=RESILIENCE)
declare("SCHED_REQUEUE_MAX", "8", "head requeues a pool-starved admission gets before rotating to the queue back (aging bound: one oversized prompt must not starve everything behind it)", table=RESILIENCE)
declare("TENANT_CLASSES", None, "tenant QoS registry `name:weight[:slots=N][:blocks=N][:rps=F][:p50=MS]`, comma-separated (unset = tenancy plane off, single-tenant paths token-identical)", table=RESILIENCE)
declare("TENANT_PREEMPT", "1", "0 disables chunk-boundary preemption of over-budget tenants (fair-share admission and rate limits stay on)", table=RESILIENCE)
declare("RADIX_PRESSURE_S", "2.0", "session-cache admission denial window after PoolExhausted", table=RESILIENCE)
declare("ENGINE_STALL_S", "30", "stalled-step threshold for the warm-restart watchdog", table=RESILIENCE)
declare("BRAIN_REPLICAS", None, "comma-separated brain replica base URLs (router tier; required)", table=RESILIENCE)
declare("ROUTER_PORT", "8095", "router listen port", table=RESILIENCE)
declare("ROUTER_PROBE_S", "0.5", "active /health probe interval", table=RESILIENCE)
declare("ROUTER_PROBE_TIMEOUT_S", "2.0", "per-probe timeout", table=RESILIENCE)
declare("ROUTER_PROBE_FAILS", "2", "consecutive probe failures before ejection", table=RESILIENCE)
declare("ROUTER_HEDGE_MS", "0", "hedge delay for idempotent parses (0 = off)", table=RESILIENCE)
declare("ROUTER_PARSE_TIMEOUT_S", "60", "default parse budget when no x-deadline-ms arrives", table=RESILIENCE)
declare("ROUTER_SESSIONS", "4096", "session-to-home LRU size", table=RESILIENCE)
declare("ROUTER_BREAKER_THRESHOLD", "3", "transport failures before a replica breaker opens", table=RESILIENCE)
declare("ROUTER_BREAKER_RESET_S", "2.0", "breaker open window before the half-open probe", table=RESILIENCE)
declare("VOICE_PARSE_TIMEOUT_S", "60", "voice-side /parse deadline", table=RESILIENCE)
declare("VOICE_EXEC_TIMEOUT_S", "120", "voice-side /execute deadline", table=RESILIENCE)
declare("VOICE_RETRY_ATTEMPTS", "3", "budgeted retry attempts per dependency call", table=RESILIENCE)
declare("VOICE_BREAKER_THRESHOLD", "3", "failures before a voice-side dependency breaker opens", table=RESILIENCE)
declare("VOICE_BREAKER_RESET_S", "2.0", "voice-side breaker open window", table=RESILIENCE)
declare("BRAIN_MAX_INFLIGHT", "32", "brain admission-controller concurrent-parse cap", table=RESILIENCE)
declare("EXECUTOR_MAX_INFLIGHT", "16", "executor admission-controller concurrent-batch cap", table=RESILIENCE)

# STT replica tier + warm-state handoff (ISSUE 13)
declare("STT_REPLICAS", "1", "STT batcher replicas behind the connection-affine tier (>1 enables it)", table=RESILIENCE)
declare("STT_REPLICA_PROBE_S", "0.25", "STT replica watchdog sweep interval", table=RESILIENCE)
declare("STT_REPLICA_STALL_S", "5.0", "frozen-tick seconds before an STT replica is warm-restarted", table=RESILIENCE)
declare("STT_SHED_PRESSURE", "0.9", "queue-occupancy fraction past which new utterances avoid an STT replica", table=RESILIENCE)
declare("HANDOFF_ENABLE", None, "1 ships warm session state (transcript + radix KV) on re-home/drain", table=RESILIENCE)
declare("HANDOFF_TIMEOUT_S", "5.0", "per-hop budget for one warm-state handoff transfer", table=RESILIENCE)
declare("HANDOFF_KV", "1", "0 ships the transcript WITHOUT KV bytes (the cold-re-home ablation baseline)", table=RESILIENCE)
declare("HANDOFF_FRAMED", "0", "1 ships warm re-home state as sequence-numbered CRC-checked frames (the disagg KV-stream wire; 0 = raw blob, byte-identical)", table=RESILIENCE)
declare("ROUTER_SHED_PRESSURE", "0.9", "pressure score past which new sessions avoid a brain replica", table=RESILIENCE)

# fleet autopilot (ISSUE 16): closed-loop elastic capacity
declare("AUTOPILOT_MIN_REPLICAS", "1", "hard floor on the per-tier replica count — the autopilot never retires below it", table=RESILIENCE)
declare("AUTOPILOT_MAX_REPLICAS", "4", "hard ceiling on the per-tier replica count — the autopilot never spawns above it", table=RESILIENCE)
declare("AUTOPILOT_INTERVAL_S", "1.0", "control-loop tick interval", table=RESILIENCE)
declare("AUTOPILOT_TARGET_UTIL", "0.6", "per-replica busy fraction the controller steers toward (capacity target = load / this)", table=RESILIENCE)
declare("AUTOPILOT_UP_WINDOWS", "2", "consecutive over-target ticks before a scale-up commits (hysteresis)", table=RESILIENCE)
declare("AUTOPILOT_DOWN_WINDOWS", "5", "consecutive under-target ticks before a scale-down commits (hysteresis; deliberately slower than up)", table=RESILIENCE)
declare("AUTOPILOT_COOLDOWN_S", "5.0", "seconds after ANY committed scale action during which no further action commits (anti-oscillation)", table=RESILIENCE)
declare("AUTOPILOT_JOIN_TIMEOUT_S", "15", "whole-join budget (spawn + pre-warm + admit); a stuck join is retired and retried, never admitted cold", table=RESILIENCE)
declare("AUTOPILOT_FORECAST_LEAD_S", "5.0", "how far ahead the load forecast extrapolates the timeseries trend", table=RESILIENCE)

# service wiring (documented in the RESILIENCE.md "Service wiring" table)
declare("VOICE_PORT", "7072", "voice service listen port", table=RESILIENCE)
declare("BRAIN_PORT", "8090", "brain service listen port", table=RESILIENCE)
declare("EXECUTOR_PORT", "7081", "executor service listen port", table=RESILIENCE)
declare("BRAIN_URL", "http://127.0.0.1:8090", "brain (or router) base URL the voice service calls", table=RESILIENCE)
declare("EXECUTOR_URL", "http://127.0.0.1:7081", "executor base URL the voice service calls", table=RESILIENCE)
declare("VOICE_STT", "null", "STT backend spec: null | whisper:<ckpt> | native:<dir>", table=RESILIENCE)
declare("VOICE_CAPACITY_SESSIONS", "0", "declared max concurrent WS sessions for the HUD headroom gauge (0 = unknown)", table=RESILIENCE)
declare("VOICE_BRAIN_HEALTH_S", "3.0", "/health brain-forward cache window", table=RESILIENCE)
declare("CDP_URL", None, "attach to an existing Chrome DevTools endpoint instead of spawning", table=RESILIENCE)
declare("CDP_PORT", "9222", "DevTools port for the spawned Chrome", table=RESILIENCE)
declare("EXECUTOR_CHROME_BIN", None, "Chrome/Chromium binary override for the executor", table=RESILIENCE)
declare("EXECUTOR_FAKE_PAGE", None, "1/true = run intents against the built-in fake page (no browser)", table=RESILIENCE)
declare("EXECUTOR_GROUNDING", None, "visual-grounding model spec `qwen2vl:<ckpt>` (unset = DOM-only)", table=RESILIENCE)
declare("EXECUTOR_SUMMARIZE", None, "page-summary model spec `llama:<ckpt>` (unset = heuristic titles)", table=RESILIENCE)
declare("ARTIFACTS_DIR", ".artifacts", "executor screenshot/DOM artifact root", table=RESILIENCE)
declare("UPLOADS_DIR", ".uploads", "executor file-upload staging dir", table=RESILIENCE)

# ================================================================== perf
# docs/PERF.md — radix KV reuse, STT batching, engine config

declare("KV_QUANT", None, "paged KV pool storage tier: int8 | int4 (unset = bf16, byte-identical path)", table=PERF)
declare("RADIX_ENABLE", None, "1 builds the radix KV session cache", table=PERF)
declare("RADIX_MAX_NODES", "4096", "radix tree size cap per dp group", table=PERF)
declare("RADIX_SESSIONS", "256", "host-side transcript LRU in the brain", table=PERF)
declare("BRAIN_POOL_BLOCKS", "0", "paged KV pool size in blocks (0 = dense worst case)", table=PERF)
declare("STT_BATCH_ENABLE", None, "1 routes voice connections through the shared STT batcher", table=PERF)
declare("STT_BATCH_SLOTS", "4", "STT decode batch width = max concurrent utterances per tick", table=PERF)

# brain engine configuration (PERF.md "Engine configuration" table)
declare("BRAIN_BACKEND", "rule", "parser backend: rule | llama | planner | pp | sp", table=PERF)
declare("BRAIN_MODEL", None, "orbax checkpoint dir for the LLM backends (unset = random init)", table=PERF)
declare("BRAIN_BATCH", "1", "continuous-batching slot count (>1 enables the scheduler)", table=PERF)
declare("BRAIN_CHUNK", "16", "decode chunk steps between host readbacks", table=PERF)
declare("BRAIN_FF", "8", "grammar fast-forward window (0 = off)", table=PERF)
declare("BRAIN_PREFIX", "1", "0 disables the shared-prefix prefill cache", table=PERF)
declare("BRAIN_PAGED", None, "1 selects the paged-KV engine", table=PERF)
declare("BRAIN_QUANT", None, "weight quantization: int8 (unset = bf16)", table=PERF)
declare("BRAIN_MOE", None, "grouped = grouped-matmul MoE FFN path", table=PERF)
declare("BRAIN_PP", "0", "pipeline-parallel stages (0 = auto: min(2, devices))", table=PERF)
declare("BRAIN_TP", "0", "tensor-parallel width (0 = auto: devices // pp)", table=PERF)
declare("BRAIN_SP", "0", "sequence-parallel width for the sp backend (0 = all devices)", table=PERF)
declare("BRAIN_PLANNER_HBM_MB", "2048", "planner session-cache HBM budget", table=PERF)
declare("BRAIN_PLANNER_PARK_MB", "4096", "planner host-RAM park budget for evicted sessions (0 = drop)", table=PERF)
declare("VOICE_SPEC_SILENCE_MS", "120", "silence before a speculative parse fires", table=PERF)
declare("VOICE_EARLY_CLOSE_MS", "240", "extra silence before the endpoint closes early on a spec hit", table=PERF)
declare("VOICE_RESPEC_AFTER", "25", "transcript-growth chars that restart an in-flight speculation", table=PERF)

# incremental streaming prefill (ISSUE 19): prefix feeds + chunked prefill
declare("PREFIX_FEED_ENABLE", None, "1 streams stabilized STT partial prefixes to the brain as prefill-only feeds (unset = off, every touched path token-identical)", table=PERF)
declare("PREFIX_FEED_STABLE_K", "3", "consecutive partials a transcript prefix must survive before it is fed", table=PERF)
declare("PREFIX_FEED_MIN_CHARS", "8", "minimum committed-prefix growth (chars) before another feed fires", table=PERF)
declare("PREFILL_CHUNK_TOKENS", None, "split prompt admissions into this many-token prefill chunks interleaved with decode chunks (unset = one-shot barrier prefill, byte-identical path)", table=PERF)

# prefill/decode disaggregation (ISSUE 20): a prefill pool streams KV
# blocks to decode replicas over the framed handoff wire
declare("ROUTER_DISAGG", None, "1 splits the brain ring into prefill/decode pools and routes long cold admissions through the KV stream (unset = off, every touched path byte-identical)", table=PERF)
declare("DISAGG_MIN_TOKENS", "256", "estimated uncached prompt tokens at/over which an admission takes the disagg prefill path", table=PERF)
declare("DISAGG_STREAM_BLOCKS", "4", "KV blocks per streamed segment — the chunk-pipelining grain (first segments ship while later chunks still prefill)", table=PERF)
declare("BRAIN_ROLE", "both", "this replica's serving role reported via /health: prefill | decode | both", table=PERF)
declare("ROUTER_PREFILL_REPLICAS", None, "comma-separated brain base URLs appended to the ring as prefill-pool members (equivalent to `url#prefill` tags in BRAIN_REPLICAS)", table=PERF)

# ========================================================= observability
# docs/OBSERVABILITY.md — SLO tracker, step ledger, sentinel, HBM ledger,
# flight recorder, trace sinks

declare("SLO_WINDOW_S", "300", "rolling SLO window", table=OBSERVABILITY)
declare("SLO_TARGET_P50_MS", "800", "p50 target (the BASELINE north star)", table=OBSERVABILITY)
declare("SLO_TARGET_P99_MS", None, "p99 target (default 4x the p50 target)", table=OBSERVABILITY)
declare("SLO_ERROR_RATE", "0.05", "error budget", table=OBSERVABILITY)
declare("SLO_AT_RISK_FRACTION", "0.8", "early-warning band fraction", table=OBSERVABILITY)
declare("SLO_MIN_SAMPLES", "5", "below this sample count the verdict stays ok", table=OBSERVABILITY)
declare("STEPLOG_ENABLE", "1", "0 disables the per-step engine ledger", table=OBSERVABILITY)
declare("STEPLOG_STEPS", "256", "step-ledger ring size", table=OBSERVABILITY)
declare("XLA_SENTINEL", "1", "0 disables the recompilation sentinel wrapping", table=OBSERVABILITY)
declare("XLA_SENTINEL_EVENTS", "128", "compile-event ring size", table=OBSERVABILITY)
declare("XLA_FENCE_QUIET_S", "120", "compile-quiet seconds that auto-arm the warmup fence (0 = never)", table=OBSERVABILITY)
declare("XLA_EXPECTED_COMPILES", None, "comma list of site prefixes allowed to compile post-fence", table=OBSERVABILITY)
declare("HBM_LEDGER_S", "1.0", "min seconds between live HBM ledger measurements", table=OBSERVABILITY)
declare("HBM_DRIFT_WARN", "0.15", "plan-vs-measured drift fraction that counts a drift event", table=OBSERVABILITY)
declare("FLIGHT_TRACES", "32", "flight-recorder trace ring size", table=OBSERVABILITY)
declare("FLIGHT_SNAPSHOTS", "120", "flight-recorder metric-snapshot ring size", table=OBSERVABILITY)
declare("FLIGHT_SNAPSHOT_S", "1.0", "metric-snapshot interval while armed", table=OBSERVABILITY)
declare("FLIGHT_SINK", None, "directory for frozen flight dumps (unset = memory only)", table=OBSERVABILITY)
declare("TRACE_SINK", None, "JSONL path for finished trace spans (unset = ring only)", table=OBSERVABILITY)

# quality observatory (ISSUE 15): online per-utterance quality signals,
# the golden-replay canary, and the quality SLO floors
declare("QUALITY_ENABLE", "1", "0 removes the quality readback lanes from the decode loops (token-identical either way)", table=OBSERVABILITY)
declare("QUALITY_WINDOW", "64", "per-signal rolling window (utterances) behind the quality gauges", table=OBSERVABILITY)
declare("QUALITY_CANARY_S", "0", "golden-replay canary cadence in seconds (0 = off)", table=OBSERVABILITY)
declare("QUALITY_CANARY_SLICE", "3", "golden cases replayed per canary round (rotating slice)", table=OBSERVABILITY)
declare("QUALITY_CANARY_OCCUPANCY", "0.5", "canary admission gate: skip the round when the replica is busier than this fraction", table=OBSERVABILITY)
declare("QUALITY_SLO_GOLDEN_MIN", "0.7", "windowed golden-replay accuracy floor (quality SLO)", table=OBSERVABILITY)
declare("QUALITY_SLO_EXEC_MIN", "0.5", "windowed executor action-success floor (quality SLO)", table=OBSERVABILITY)
declare("QUALITY_SLO_MARGIN_MIN", "0", "windowed intent masked-logit-margin floor (0 = floor off; scale is model-specific)", table=OBSERVABILITY)
declare("QUALITY_SLO_REPETITION_MAX", "0.9", "windowed STT repetition ceiling (garbled-transcript alarm)", table=OBSERVABILITY)
declare("QUALITY_SLO_MIN_SAMPLES", "5", "below this window count a quality verdict stays ok", table=OBSERVABILITY)

# fleet telemetry plane (ISSUE 14): per-service time-series rings + the
# router's peer-relative gray-failure detector
declare("TS_INTERVAL_S", "0.5", "time-series ring sample cadence per service", table=OBSERVABILITY)
declare("TS_SAMPLES", "240", "time-series ring size (samples retained per service)", table=OBSERVABILITY)
declare("TS_GAUGES", None, "comma list of gauge-name prefixes to sample (unset = all gauges)", table=OBSERVABILITY)
declare("FLEET_DETECT", "1", "0 disables the router's fleet gray-failure detector", table=OBSERVABILITY)
declare("FLEET_GRAY_MAD", "4.0", "peer-relative outlier score (MAD multiples) at/over which a window counts gray", table=OBSERVABILITY)
declare("FLEET_GRAY_WINDOWS", "3", "consecutive outlier scrape windows before a replica enters (or clean windows before it leaves) gray", table=OBSERVABILITY)
declare("FLEET_MIN_PEERS", "3", "members a signal needs before peer-relative scoring runs (a median of two cannot name the outlier)", table=OBSERVABILITY)
declare("FLEET_GRAY_HOLD_S", "300", "seconds a gray verdict survives WITHOUT scoreable evidence before expiring (demotion starves traffic-borne signals; expiry bounds the capacity loss, re-detection re-demotes)", table=OBSERVABILITY)

# cost & efficiency observatory (ISSUE 17): analytic roofline metering,
# live MFU/MBU, per-session resource attribution
declare("COST_ENABLE", "1", "0 removes the analytic cost lanes (per-request ledger + MFU/MBU gauges; token-identical either way)", table=OBSERVABILITY)
declare("COST_PEAK_TFLOPS", "0", "device peak TFLOP/s override for MFU (0 = costmodel.PEAK_TABLE by exact device_kind; an unknown TPU is an error; documented CPU proxy off-TPU)", table=OBSERVABILITY)
declare("COST_PEAK_GBPS", "0", "device peak HBM GB/s override for MBU (0 = costmodel.PEAK_TABLE by exact device_kind; an unknown TPU is an error; documented CPU proxy off-TPU)", table=OBSERVABILITY)
declare("COST_SESSIONS", "256", "per-session cost-rollup LRU size in the brain", table=OBSERVABILITY)

# ========================================================= infrastructure
# deliberately undocumented: JAX bootstrap + test/bench harness plumbing,
# not operator tuning surface (the checker rejects doc rows for these)

declare("JAX_COMPILATION_CACHE_DIR", None, "JAX's own persistent compile cache location; unset = <checkout>/.jax_cache (utils.compilecache)")
declare("JAX_TRACEBACK_IN_LOCATIONS_LIMIT", None, "JAX's own: Python frames kept in HLO locations; unset = 0, so the compile cache keys on names and not on paths and lines (utils.compilecache)")
declare("JAX_COORDINATOR_ADDRESS", None, "multihost coordinator address")
declare("JAX_NUM_PROCESSES", None, "multihost process count")
declare("JAX_PROCESS_ID", None, "multihost process index")
declare("TPU_VOICE_CACHE_DIR", None, "grammar FSM table cache dir override")
declare("CKPT_HELDOUT", None, "0 skips the held-out eval ckpt in make_tiny_ckpts")
declare("CKPT_GROUND", None, "0 skips the grounding ckpt in make_tiny_ckpts")
