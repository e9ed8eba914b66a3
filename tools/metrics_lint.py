#!/usr/bin/env python
"""Metric-name collision lint + OBSERVABILITY.md catalog sync.

One name must map to one metric type: a counter named ``x`` and a gauge
named ``x`` registered from two call sites would silently shadow each other
in the JSON snapshot and produce conflicting ``# TYPE`` lines in the
Prometheus exposition. This lint statically scans the package source for
every ``inc(...)`` / ``set_gauge(...)`` / ``observe_ms(...)`` registration
(f-string name templates are normalized: ``{expr}`` -> ``*``) and fails on
any name registered under more than one kind.

Since ISSUE 11 it is also the two-way catalog sync: every registered name
must appear in the docs/OBSERVABILITY.md metric catalog (with a matching
type where the row declares one), every catalog row must still match a
registered name, and every PINNED name must be documented — so the source,
the pin table, and the operator-facing catalog cannot drift apart. Catalog
rows may use ``<placeholder>`` segments for f-string name families
(``resilience.<dep>.breaker_state`` ↔ ``resilience.{name}.breaker_state``).

The runtime half lives in ``Metrics.collisions()`` (kind tracking at
registration time); this static half catches collisions between code paths
no single test executes together. Wired into tier-1 via
tests/test_observability.py and into ``python -m tools.analyze``
(metrics-catalog checker); also runnable standalone:

    python tools/metrics_lint.py [root_dir [catalog.md]]
"""

from __future__ import annotations

import pathlib
import re
import sys

# .inc("name"  /  .set_gauge(f"a.{x}.b"  /  .observe_ms('name'
_CALL = re.compile(
    r"\.(?P<kind>inc|set_gauge|observe_ms)\(\s*(?P<f>f?)(?P<q>['\"])(?P<name>.+?)(?P=q)")
_KIND = {"inc": "counter", "set_gauge": "gauge", "observe_ms": "histogram"}
_PLACEHOLDER = re.compile(r"\{[^{}]*\}")

# Names with an external contract (dashboards, bench artifacts, the
# OBSERVABILITY.md catalog) pinned to their kind: the lint fails if one
# disappears from the source or re-registers under another kind. The STT
# saturation gauges are AGGREGATES across live streams (max lag, summed
# buffered seconds — serve/stt.py _record_stream_gauges), not per-stream
# values; a refactor that quietly turns them back into last-writer-wins
# per-instance writes must at minimum keep the names alive here.
PINNED: dict[str, str] = {
    # radix KV reuse plane (serve/radix.py, docs/PERF.md "Session KV
    # reuse"): hit_rate/nodes are scheduler-exported gauges, the counters
    # increment at match/evict time; kv_blocks_shared is the dedup signal
    # (blocks stored once, referenced by several owners)
    "radix.hit_rate": "gauge",
    "radix.cached_tokens": "counter",
    "radix.evictions": "counter",
    "radix.nodes": "gauge",
    "paged.kv_blocks_shared": "gauge",
    "stt.feed_lag_s": "gauge",
    "stt.buffered_audio_s": "gauge",
    "stt.batch_occupancy": "gauge",
    "stt.batch_slots": "gauge",
    "stt.queue_depth": "gauge",
    "stt.partials_coalesced": "counter",
    "stt.finals_batched": "counter",
    "stt.batch_ticks": "counter",
    "stt.shed_overload": "counter",
    # capacity observatory (tools/swarm.py, benches/bench_swarm.py,
    # docs/OBSERVABILITY.md "Capacity"): the flight recorder's freeze
    # counter and ring occupancy, the aborted-utterance error accounting
    # (a WS teardown mid-utterance must burn SLO error budget, not vanish),
    # and the live-session gauge the HUD's headroom display reads. The
    # saturation gauges the swarm's attribution keys on are pinned too —
    # renaming one silently blinds the first-saturated verdict.
    "flight.freezes": "counter",
    "flight.traces_buffered": "gauge",
    "flight.snapshots_buffered": "gauge",
    "voice.utterances_aborted": "counter",
    "voice.live_sessions": "gauge",
    "scheduler.batch_occupancy": "gauge",
    "scheduler.queue_depth": "gauge",
    "paged.kv_utilization": "gauge",
    # fault containment (ISSUE 7, utils/chaos.py + serve/scheduler.py +
    # serve/colocate.py, docs/RESILIENCE.md "Fault containment"): the
    # chaos drill's injected-fault count, the quarantine/cancellation/
    # queue-expiry eviction counters bench_chaos gates on, and the
    # watchdog's warm-restart counter — renaming any of these silently
    # blinds the chaos bench's containment verdict
    "chaos.injected": "counter",
    "scheduler.slots_quarantined": "counter",
    "scheduler.cancelled": "counter",
    "scheduler.shed_expired": "counter",
    "engine.restarts": "counter",
    # tokens_per_forward is the scheduler's multi-token-step denominator
    # (forwards counts dispatches, never emitted tokens)
    "scheduler.tokens_per_forward": "gauge",
    "scheduler.forwards": "counter",
    "scheduler.forward_rows": "counter",  # ISSUE 29: forwards x the chunk's width
    # ISSUE 35: prefill calls / admissions / admissions that rode a grouped call
    "admit.calls": "counter",
    "admit.rows": "counter",
    "admit.batched_rows": "counter",
    # engine microscope (ISSUE 9, utils/steplog.py + utils/compilewatch.py
    # + utils/hbmledger.py, docs/OBSERVABILITY.md "Engine microscope"):
    # the step ledger's wall histogram + per-chunk occupancy/token gauges
    # (the per-STAGE histograms register as the f-string family
    # ``engine.step.*``), the recompilation sentinel's counters —
    # compiles_post_fence is THE alertable one (a trace after the warmup
    # fence is the silent-p99-cliff shape-churn failure, named) — and the
    # live HBM ledger's plan-vs-measured gauges benchdiff/the HUD read.
    "engine.step.wall": "histogram",
    "engine.step.occupancy": "gauge",
    "engine.step.tokens": "gauge",
    "engine.step.compile_stalls": "counter",
    "xla.compiles": "counter",
    "xla.compile_ms": "counter",
    "xla.compiles_post_fence": "counter",
    "hbm.weights_bytes": "gauge",
    "hbm.kv_pool_bytes": "gauge",
    "hbm.workspace_bytes": "gauge",
    "hbm.free_bytes": "gauge",
    "hbm.live_bytes": "gauge",
    "hbm.plan_total_bytes": "gauge",
    "hbm.plan_drift": "gauge",
    "hbm.drift_events": "counter",
    # quantized paged KV + fused decode tail (ISSUE 12, ops/kvquant.py +
    # serve/paged.py + ops/grammar_mask.py, docs/PERF.md "Quantized KV +
    # fused decode tail"): kv_quant_bits is the active-tier dial the bench
    # kv_quant rows and the HBM-plan drift check key on, kv_bytes_per_block
    # the bytes-denominated capacity unit (block counts stopped being a
    # unit of HBM when KV_QUANT halved them) — renaming either blinds the
    # bench capacity verdicts
    "paged.kv_quant_bits": "gauge",
    "paged.kv_bytes_per_block": "gauge",
    # queue wait as its own number, and one request/token count for both
    # brain backends (ISSUE 24): dashboards key on these names
    "scheduler.queue_wait": "histogram",
    "brain.parse_completed": "counter",
    "brain.parse_tokens": "counter",
    "voice.stt_feed_lag": "histogram",
    # replicated brain tier (ISSUE 10, services/router.py, docs/
    # RESILIENCE.md "Replica fault domain"): sessions_rehomed is the
    # observable failover cost (one cold re-prefill per forced move),
    # replicas_healthy is the ring-occupancy gauge the HUD badge reads,
    # hedges_fired/won are the tail-cut dials, drains counts rolling-
    # restart drills — renaming any of these blinds bench_router's gates
    "router.sessions_rehomed": "counter",
    "router.replicas_healthy": "gauge",
    "router.hedges_fired": "counter",
    "router.hedges_won": "counter",
    "router.drains": "counter",
    # replicated STT tier + warm-state handoff (ISSUE 13, serve/
    # stt_replicas.py + serve/handoff.py + services/router.py, docs/
    # RESILIENCE.md "STT replica fault domain" / "Warm-state handoff"):
    # the warm/cold split is the handoff's effectiveness dial (warm = KV
    # adopted, re-home cost ~transfer; cold = the PR 10 re-prefill),
    # shed_pressure counts gauge-driven placement redirects, the stt.*
    # names are the STT ring's restart/failover accounting bench_handoff
    # gates on — renaming any of these blinds its gates
    "router.sessions_rehomed_warm": "counter",
    "router.sessions_rehomed_cold": "counter",
    "router.shed_pressure": "counter",
    "stt.replicas_healthy": "gauge",
    "stt.replica_restarts": "counter",
    "stt.replica_failovers": "counter",
    "handoff.sessions_adopted": "counter",
    "handoff.tokens_adopted": "counter",
    # fleet telemetry plane (ISSUE 14, utils/timeseries.py + services/
    # replicaset.py + services/router.py, docs/OBSERVABILITY.md "Fleet
    # telemetry"): samples_buffered is the per-service ring occupancy,
    # gray_replicas the live demotion count the HUD/bench gates read,
    # scrapes the fleet-window cadence, outlier_score_max the worst
    # peer-relative deviation this window, gray_entered the incident
    # counter bench_fleet's detection gate keys on — renaming any of
    # these blinds the gray-failure drill's verdicts
    "ts.samples_buffered": "gauge",
    "fleet.gray_replicas": "gauge",
    "fleet.scrapes": "counter",
    "fleet.outlier_score_max": "gauge",
    "fleet.gray_entered": "counter",
    # quality observatory (ISSUE 15, utils/quality.py + utils/slo.py
    # QualityTracker, docs/OBSERVABILITY.md "Quality observatory"): the
    # online per-utterance quality signals the quality SLO floors and the
    # fleet gray detector read — golden_accuracy is the canary's headline
    # (bench_quality_online's detection drill keys on it), intent_margin
    # the decode tail's masked-logit confidence, exec_success_rate the
    # executor weak-label loop, the stt.confidence* lanes the Whisper
    # decode readbacks, prefill_remaining_at_endpoint the streaming-prefill
    # scoreboard — renaming any of these blinds the quality gates
    "quality.golden_accuracy": "gauge",
    "quality.intent_margin": "gauge",
    "quality.exec_success_rate": "gauge",
    "quality.degraded_rate": "gauge",
    "quality.canary_runs": "counter",
    "quality.intent_downgrades": "counter",
    "stt.confidence_mean": "gauge",
    "stt.confidence_min": "gauge",
    "stt.confidence_repetition": "gauge",
    "engine.prefill_remaining_at_endpoint": "gauge",
    # fleet autopilot (ISSUE 16, services/autopilot.py + services/
    # router.py, docs/RESILIENCE.md "Fleet autopilot"): the control loop's
    # decision accounting bench_autopilot gates on — joins_cold is the
    # never-admit-cold contract (the stall drill requires it stays 0),
    # join_timeouts the containment counter, sessions_shipped the
    # zero-drop scale-down's proactive warm-ship count, retired the
    # drain->ship->eject->retire completions, target/load/forecast the
    # fleetview panel's dials, replicas_added/removed the ring-churn
    # counters — renaming any of these blinds the elastic-capacity gates
    "autopilot.decisions": "counter",
    "autopilot.scale_ups": "counter",
    "autopilot.scale_downs": "counter",
    "autopilot.holds_starved": "counter",
    "autopilot.cooldown_blocks": "counter",
    "autopilot.join_timeouts": "counter",
    "autopilot.joins_prewarmed": "counter",
    "autopilot.joins_cold": "counter",
    "autopilot.sessions_shipped": "counter",
    "autopilot.retired": "counter",
    "autopilot.target_replicas": "gauge",
    "autopilot.load": "gauge",
    "autopilot.forecast_load": "gauge",
    "autopilot.stt_target_replicas": "gauge",
    "router.replicas_added": "counter",
    "router.replicas_removed": "counter",
    # cost & efficiency observatory (ISSUE 17, utils/costmodel.py +
    # serve/scheduler.py + serve/stt.py, docs/OBSERVABILITY.md "Cost &
    # efficiency observatory"): the roofline gauges bench_cost gates on
    # (engine.mfu/mbu are THE utilization headline; mfu_prefill the
    # prefill-stage split the disaggregation PR will consume) and the
    # cost.* counters the timeseries ring derives spend rates from —
    # renaming any of these blinds the efficiency gates
    "engine.mfu": "gauge",
    "engine.mbu": "gauge",
    "engine.mfu_prefill": "gauge",
    "cost.decode_flops": "counter",
    "cost.decode_bytes": "counter",
    "cost.stt_encoder_flops": "counter",
    "cost.stt_decoder_flops": "counter",
    # multi-tenant QoS plane (ISSUE 18, serve/tenancy.py + serve/
    # scheduler.py, docs/OBSERVABILITY.md "Multi-tenant QoS plane"): the
    # isolation signals bench_tenancy and the swarm drills read — throttle
    # and preemption volume are the abuse-containment evidence, and the
    # requeue-rotation counter is the aging bound's only witness
    "tenant.lanes": "gauge",
    "tenant.throttled": "counter",
    "tenant.preemptions": "counter",
    "scheduler.requeue_rotations": "counter",
    # incremental streaming prefill (ISSUE 19, serve/scheduler.py +
    # services/voice.py + services/router.py, docs/OBSERVABILITY.md
    # "Incremental streaming prefill"): the feed/chunk volume counters
    # bench_streaming_prefill gates on, plus the scoreboard gauge — the
    # prefill debt left at endpoint that the whole feature exists to
    # drive to zero. Renaming any of these blinds the warm-start gates.
    "prefill.chunked_admissions": "counter",
    "prefill.chunks": "counter",
    "prefill.feeds": "counter",
    "prefill.feeds_committed": "counter",
    "prefill.feeds_shed": "counter",
    "voice.feeds_sent": "counter",
    "voice.feeds_reaped": "counter",
    "router.feeds_discarded": "counter",
    # prefill/decode disaggregation (ISSUE 20, services/router.py +
    # serve/scheduler.py + serve/handoff.py, docs/OBSERVABILITY.md
    # "Prefill/decode disaggregation"): the admission/fallback pair is
    # bench_disagg's clean-or-cold evidence, the export/adopt volume
    # counters witness the KV stream actually moving, and the pool
    # gauges drive fleetview's per-pool roll-up and the autopilot's
    # prefill band. Renaming any of these blinds the disagg gates.
    "disagg.admissions": "counter",
    "disagg.fallbacks": "counter",
    "disagg.feeds_routed": "counter",
    "disagg.spec_routed": "counter",
    "disagg.frames_streamed": "counter",
    "disagg.tokens_prewarmed": "counter",
    "disagg.exports": "counter",
    "disagg.exports_shed": "counter",
    "disagg.blocks_streamed": "counter",
    "disagg.segments_adopted": "counter",
    "disagg.streams_aborted": "counter",
    "disagg.prefill_replicas": "gauge",
    "disagg.decode_replicas": "gauge",
    "disagg.prefill_queue": "gauge",
    "autopilot.prefill_target_replicas": "gauge",
    # what holds the batcher's thread (ISSUE 36, utils/steplog.py's event
    # ring): the collections and the watchdog's lateness behind the step
    # record's gc_* / watchdog_late_ms keys, and the wake latency the
    # benchmark's deliver_ms_mean.* divides by brain.parse_completed
    "host.gc_collections": "counter",
    "host.gc_pause": "histogram",
    "host.watchdog_late": "histogram",
    "brain.parse_deliver_ms": "counter",
    "brain.parse_completed": "counter",
}


def check_pinned(reg: dict[str, dict[str, list[str]]]) -> list[str]:
    """Pin violations: a PINNED name missing from the scan, or registered
    under a different kind than its contract says."""
    problems = []
    for name, kind in sorted(PINNED.items()):
        kinds = reg.get(name)
        if kinds is None:
            problems.append(f"pinned metric {name!r} ({kind}) not registered anywhere")
        elif list(kinds) != [kind]:
            problems.append(
                f"pinned metric {name!r} must be a {kind}, found {sorted(kinds)}")
    return problems


def _normalize(name: str, is_fstring: bool) -> str:
    return _PLACEHOLDER.sub("*", name) if is_fstring else name


# ------------------------------------------------------------- catalog sync

DEFAULT_CATALOG = pathlib.Path(__file__).resolve().parents[1] / "docs" / "OBSERVABILITY.md"

# catalog tables are recognized by a header row whose first cell starts
# with `name`; the first cell of each row carries the metric names in
# backticks (`a.b` / `c` shorthand inherits the first name's prefix,
# `→ `prom_name`` arrow targets are display-only, `<x>` placeholders are
# f-string wildcards)
_CAT_HEADER = re.compile(r"^\|\s*name\b", re.IGNORECASE)
_ARROW_TARGET = re.compile(r"(?:→|->)\s*`[^`]+`")
_CAT_TOKEN = re.compile(r"`([^`]+)`")
_ANGLE = re.compile(r"<[^<>]+>")


def iter_table_rows(text: str, header_re: re.Pattern):
    """(line_no, cells) for every data row of markdown tables whose header
    row matches ``header_re``; separator rows skipped. Shared by this
    module's catalog parser and the env-knob checker's table walker."""
    in_table = False
    for i, line in enumerate(text.splitlines(), 1):
        if header_re.match(line):
            in_table = True
            continue
        if not line.startswith("|"):
            in_table = False
            continue
        if not in_table or set(line.replace("|", "").strip()) <= {"-", ":", " "}:
            continue
        yield i, line.split("|")


def parse_catalog(text: str) -> dict[str, tuple[str | None, int]]:
    """OBSERVABILITY.md -> {normalized name pattern: (type | None, line)}.

    Only rows of tables whose header's first cell is ``name...`` count.
    The second cell, when it is exactly a metric kind, pins the type."""
    out: dict[str, tuple[str | None, int]] = {}
    for i, cells in iter_table_rows(text, _CAT_HEADER):
        if len(cells) < 3:
            continue
        first = _ARROW_TARGET.sub("", cells[1])
        kind_cell = cells[2].strip().lower()
        kind = kind_cell if kind_cell in ("counter", "gauge", "histogram") else None
        prefix = None
        for tok in _CAT_TOKEN.findall(first):
            tok = _ANGLE.sub("*", tok.strip().rstrip(".,;…"))
            if not re.fullmatch(r"[a-z0-9_*][a-z0-9_.*]*", tok):
                continue
            if "." in tok:
                prefix = tok.rsplit(".", 1)[0] + "."
            elif prefix is not None:
                tok = prefix + tok
            else:
                continue  # bare token before any dotted name: not a metric
            out.setdefault(tok, (kind, i))
    return out


def _rx(p: str) -> str:
    return "".join(".+" if c == "*" else re.escape(c) for c in p)


def _covers(pattern: str, name: str) -> bool:
    """True when a ``*``-wildcarded pattern and a (possibly wildcarded)
    registered name describe the same metric family. ``*`` on either side
    matches one or more characters."""
    return bool(pattern == name or re.fullmatch(_rx(pattern), name)
                or re.fullmatch(_rx(name), pattern))


def _pattern_covers(pattern: str, name: str) -> bool:
    """Directional: the doc pattern describes THIS registered name (not
    merely some member of a wildcard family the name denotes). Only then
    is the row's declared type binding — a generic registered family like
    the tracer's ``{service}.{span}`` histogram matches many specific
    rows without being described by them."""
    return bool(pattern == name or re.fullmatch(_rx(pattern), name))


def check_catalog(reg: dict[str, dict[str, list[str]]],
                  catalog: dict[str, tuple[str | None, int]]) -> list[str]:
    """Two-way drift: registered-but-undocumented, documented-but-gone,
    PINNED-but-undocumented, and documented-with-the-wrong-type."""
    problems: list[str] = []
    pats = list(catalog)
    for name, kinds in sorted(reg.items()):
        hits = [p for p in pats if _covers(p, name)]
        if not hits:
            sites = next(iter(kinds.values()))
            problems.append(
                f"registered metric {name!r} ({'/'.join(sorted(kinds))}, "
                f"e.g. {sites[0]}) is not in the OBSERVABILITY.md catalog")
            continue
        # specificity: an exact row beats a `<x>`-wildcard family row for
        # the type claim (`engine.step.<stage>` histogram must not bind
        # the separately-documented `engine.step.occupancy` gauge)
        exact = [p for p in hits if p == name]
        for p in exact or hits:
            want = catalog[p][0]
            if want is not None and _pattern_covers(p, name) \
                    and list(kinds) != [want]:
                problems.append(
                    f"metric {name!r} is documented as a {want} "
                    f"(catalog line {catalog[p][1]}) but registers as "
                    f"{sorted(kinds)}")
    def _witnessed(p: str, kind: str | None) -> bool:
        """A doc row is alive when a registered name vouches for it. A
        registered UNIVERSAL family (all-wildcard segments, e.g. the
        tracer's ``{service}.{span}`` → ``*.*``) matches every dotted
        string, which would make stale-row detection vacuous — so such a
        family only vouches for rows declaring its own kind (a histogram
        span row), never for typed rows of another kind or untyped ones."""
        for name, kinds in reg.items():
            if not _covers(p, name):
                continue
            if _pattern_covers(p, name) or any(
                    c.isalnum() for c in name.replace("*", "")):
                return True
            if kind is not None and list(kinds) == [kind]:
                return True
        return False

    for p, (kind, line) in sorted(catalog.items()):
        if not _witnessed(p, kind):
            problems.append(
                f"catalog entry {p!r} (OBSERVABILITY.md line {line}) matches "
                "no registered metric — stale doc row")
    for name in sorted(PINNED):
        if not any(_covers(p, name) for p in pats):
            problems.append(
                f"pinned metric {name!r} is not in the OBSERVABILITY.md "
                "catalog")
    return problems


# What a chunk program counts (``models/family.py`` ``Count``): ONE loop of the
# batcher adds each vector to the counters its record names, built from the
# modules' own tuples (``llama.moe_stat_names``, ``ops.ATTN_STATS``,
# ``mla.LATENT_STATS``, ``sambay.HYBRID_STATS``, ``olmo_hybrid.HYBRID_STATS``,
# ``lfm2.HYBRID_STATS``, ``llama.FFN_STATS``, ``llama.LOOP_STATS``, ``llama.KV_STATS``) — no
# ``inc("...")`` a name, so their families are registered where ``Count`` is defined
COUNTED = ("moe.*", "attn.*", "ssm.*", "gdn.*", "conv.*", "ffn.*", "loop.*", "kv.*")


def scan_source(root: pathlib.Path) -> dict[str, dict[str, list[str]]]:
    """name -> kind -> [file:line, ...] over every .py under root."""
    reg: dict[str, dict[str, list[str]]] = {}
    for path in sorted(root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            text = path.read_text()
        except OSError:
            continue
        for i, line in enumerate(text.splitlines(), 1):
            for m in _CALL.finditer(line):
                name = _normalize(m.group("name"), bool(m.group("f")))
                kind = _KIND[m.group("kind")]
                reg.setdefault(name, {}).setdefault(kind, []).append(
                    f"{path.relative_to(root)}:{i}")
        at = text.find("\nclass Count(")
        for name in COUNTED if at >= 0 else ():
            reg.setdefault(name, {}).setdefault("counter", []).append(
                f"{path.relative_to(root)}:{text.count(chr(10), 0, at) + 2}")
    return reg


def find_collisions(reg: dict[str, dict[str, list[str]]]) -> list[tuple[str, dict]]:
    return sorted((name, kinds) for name, kinds in reg.items() if len(kinds) > 1)


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = pathlib.Path(argv[0]) if argv else \
        pathlib.Path(__file__).resolve().parents[1] / "tpu_voice_agent"
    catalog_path = pathlib.Path(argv[1]) if len(argv) > 1 else DEFAULT_CATALOG
    reg = scan_source(root)
    collisions = find_collisions(reg)
    pin_problems = check_pinned(reg)
    catalog_problems = []
    if catalog_path.is_file():
        catalog = parse_catalog(catalog_path.read_text())
        catalog_problems = check_catalog(reg, catalog)
        print(f"[metrics-lint] catalog: {len(catalog)} documented name "
              f"patterns in {catalog_path.name}")
    print(f"[metrics-lint] {len(reg)} distinct metric names under {root}")
    if not collisions and not pin_problems and not catalog_problems:
        print("[metrics-lint] ok — no name registered under more than one type; "
              f"{len(PINNED)} pinned names present; catalog in sync")
        return 0
    for name, kinds in collisions:
        print(f"[metrics-lint] COLLISION {name!r}:")
        for kind, sites in sorted(kinds.items()):
            for site in sites:
                print(f"  {kind:<9} {site}")
    for p in pin_problems:
        print(f"[metrics-lint] PIN {p}")
    for p in catalog_problems:
        print(f"[metrics-lint] CATALOG {p}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
