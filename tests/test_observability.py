"""End-to-end utterance observability (ISSUE 2).

The executable spec for the observability plane: cross-service trace
collection (span ring + /debug/trace + traceview waterfall assembly),
Prometheus text exposition with golden-format validation, SLO state
transitions on an injected clock, runtime saturation gauges under a full
scheduler batch, and the tooling lints (traceview --self-test, metric-name
collision) wired into tier-1.
"""

import asyncio
import json
import pathlib
import re
import subprocess
import sys

import threading

import aiohttp
import numpy as np
import pytest

from tpu_voice_agent.utils import (
    FlightRecorder,
    Metrics,
    SLOTracker,
    Tracer,
    get_flight_recorder,
    get_metrics,
)
from tpu_voice_agent.utils.tracing import (
    HIST_BUCKETS_MS,
    nearest_rank,
    prometheus_exposition,
)

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tools"))

import metrics_lint  # noqa: E402
import traceview  # noqa: E402


# ------------------------------------------------------------ metrics math


def test_percentile_and_snapshot_agree_on_one_sample():
    m = Metrics()
    m.observe_ms("k", 42.0)
    snap = m.snapshot()["latency_ms"]["k"]
    assert m.percentile_ms("k", 0.5) == 42.0
    assert m.percentile_ms("k", 0.95) == 42.0
    assert snap["p50"] == snap["p95"] == snap["p99"] == snap["max"] == 42.0


def test_percentile_and_snapshot_agree_on_two_samples():
    m = Metrics()
    m.observe_ms("k", 10.0)
    m.observe_ms("k", 90.0)
    snap = m.snapshot()["latency_ms"]["k"]
    # ONE nearest-rank rule for both paths (they used to disagree on
    # index rounding): q*(n-1) rounded half-up
    assert m.percentile_ms("k", 0.5) == snap["p50"] == 90.0
    assert m.percentile_ms("k", 0.95) == snap["p95"] == 90.0
    assert m.percentile_ms("k", 0.2) == 10.0


def test_nearest_rank_rejects_empty():
    with pytest.raises(ValueError):
        nearest_rank([], 0.5)


def test_metrics_kind_collision_tracking():
    m = Metrics()
    m.inc("dup")
    m.set_gauge("dup", 1.0)
    m.observe_ms("clean", 5.0)
    assert m.collisions() == [("dup", "counter", "gauge")]


# ------------------------------------------------------------ span guard


def test_span_name_guard_rejects_cardinality_smuggling():
    t = Tracer("svc", emit=False)
    for bad in ("has space", "attr=1", "brace{x}", "tab\tname", ""):
        with pytest.raises(ValueError):
            with t.span(bad):
                pass
        with pytest.raises(ValueError):
            t.record_span(bad, "tid", 0.0, 1.0)
    with t.span("fine_name", trace_id="tid", chars=3):
        pass  # attrs are the right place for per-request values
    assert t.spans_for("tid")[0]["chars"] == 3


def test_trace_ring_bounded_and_lru():
    t = Tracer("svc", emit=False)
    t.MAX_TRACES = 4
    for i in range(10):
        with t.span("s", trace_id=f"trace{i}"):
            pass
    assert t.spans_for("trace0") == []  # evicted
    assert len(t.spans_for("trace9")) == 1


def test_trace_sink_appends_jsonl(tmp_path):
    sink = tmp_path / "spans.jsonl"
    t = Tracer("svc", emit=False, sink_path=str(sink))
    with t.span("one", trace_id="tid"):
        pass
    t.record_span("two", "tid", 0.0, 0.005)
    lines = [json.loads(ln) for ln in sink.read_text().splitlines()]
    assert [ln["span"] for ln in lines] == ["one", "two"]
    assert all(ln["svc"] == "svc" and ln["trace"] == "tid" for ln in lines)


# ------------------------------------------------------------ exposition


_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{le="[^"]+"\})? -?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?$')
_TYPE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")


def _assert_valid_exposition(text: str) -> dict:
    """Golden-format check: every line is a TYPE comment or a sample, and
    histograms are cumulative with le=+Inf == count. Returns name->value."""
    values = {}
    for line in text.strip().splitlines():
        assert _TYPE.match(line) or _SAMPLE.match(line), f"bad exposition line: {line!r}"
        if not line.startswith("#"):
            name, val = line.rsplit(" ", 1)
            values[name] = float(val)
    # histogram invariants
    for name in {n.split("_bucket{")[0] for n in values if "_bucket{" in n}:
        inf = values.get(f'{name}_bucket{{le="+Inf"}}')
        assert inf is not None, f"{name} missing the +Inf bucket"
        assert inf == values[f"{name}_count"]
        bucket_vals = [v for k, v in values.items()
                       if k.startswith(f"{name}_bucket{{")]
        assert bucket_vals == sorted(bucket_vals), f"{name} buckets not cumulative"
    return values


def test_prometheus_exposition_golden_format():
    m = Metrics()
    m.inc("svc.requests", 3)
    m.set_gauge("svc.depth", 2.5)
    for v in (0.4, 3, 70, 99999):
        m.observe_ms("svc.lat", v)
    text = prometheus_exposition(m)
    values = _assert_valid_exposition(text)
    assert values["svc_requests_total"] == 3
    assert values["svc_depth"] == 2.5
    assert values['svc_lat_ms_bucket{le="1"}'] == 1
    assert values['svc_lat_ms_bucket{le="100"}'] == 3  # cumulative
    assert values['svc_lat_ms_bucket{le="+Inf"}'] == 4  # 99999 overflows all bounds
    assert values["svc_lat_ms_count"] == 4
    assert len([k for k in values if k.startswith("svc_lat_ms_bucket")]) \
        == len(HIST_BUCKETS_MS) + 1


def test_exposition_first_registry_wins_on_collision():
    a, b = Metrics(), Metrics()
    a.set_gauge("g", 1.0)
    b.set_gauge("g", 99.0)
    assert "g 1" in prometheus_exposition(a, b).splitlines()


# ------------------------------------------------------------ SLO tracker


def test_slo_state_transitions_ok_at_risk_violated_recovered():
    clock = {"t": 0.0}
    s = SLOTracker("t", window_s=60.0, target_p50_ms=100.0, target_p99_ms=400.0,
                   error_rate_target=0.5, at_risk_fraction=0.8, min_samples=3,
                   clock=lambda: clock["t"])
    # below min_samples: always ok (warmup must not page)
    s.record(5000.0)
    s.record(5000.0)
    assert s.state() == "ok"
    clock["t"] += 61.0  # age the warmup out
    # fast traffic: ok
    for _ in range(10):
        s.record(50.0)
    assert s.state() == "ok"
    # p50 drifts past 80% of target: at_risk
    for _ in range(30):
        s.record(90.0)
    assert s.state() == "at_risk"
    # p50 blows the budget: violated
    for _ in range(60):
        s.record(300.0)
    ev = s.evaluate()
    assert ev["state"] == "violated" and ev["reasons"]
    # window slides: the slow samples age out -> recovered
    clock["t"] += 61.0
    for _ in range(10):
        s.record(50.0)
    assert s.state() == "ok"
    # error budget burn alone also violates (15 errors / 25 samples = 0.6)
    for _ in range(15):
        s.record(10.0, ok=False)
    assert s.state() == "violated"
    g = get_metrics().snapshot()["gauges"]
    assert g["slo.t.state"] == 2.0


def test_slo_p99_guard():
    clock = {"t": 0.0}
    s = SLOTracker("t99", window_s=60.0, target_p50_ms=1000.0, target_p99_ms=200.0,
                   min_samples=5, clock=lambda: clock["t"])
    for _ in range(99):
        s.record(10.0)
    assert s.state() == "ok"
    for _ in range(5):
        s.record(5000.0)  # a thin slow tail
    assert s.state() == "violated"


# ------------------------------------------------------- flight recorder


def test_flight_recorder_buffers_freezes_and_rearms():
    rec = FlightRecorder(max_traces=4, max_snapshots=8, snapshot_interval_s=999)
    for i in range(10):  # 10 traces through a 4-trace ring
        rec.observe_span({"svc": "t", "span": "s", "trace": f"tr{i}", "ms": 1.0,
                          "wall_start_s": float(i), "wall_end_s": float(i) + 0.1})
    st = rec.state("svc")
    assert st["frozen"] is False and st["traces_buffered"] == 4
    assert st["service"] == "svc"
    assert rec.trigger("slo.test.violated", detail="p50 blown") is True
    dump = rec.frozen_dump()
    assert dump["reason"] == "slo.test.violated" and dump["detail"] == "p50 blown"
    assert [t["trace_id"] for t in dump["traces"]] == ["tr6", "tr7", "tr8", "tr9"]
    assert dump["metric_snapshots"], "trigger snapshots the knee itself"
    # first freeze wins; the dump is immutable under later spans/triggers
    assert rec.trigger("breaker.x.open") is False
    rec.observe_span({"svc": "t", "span": "s", "trace": "later", "ms": 1.0})
    assert rec.frozen_dump()["reason"] == "slo.test.violated"
    assert len(rec.frozen_dump()["traces"]) == 4
    rec.rearm()
    assert rec.state()["frozen"] is False
    assert rec.trigger("second.incident") is True


def test_breaker_trip_freezes_global_flight_recorder():
    from tpu_voice_agent.utils.resilience import CircuitBreaker

    rec = get_flight_recorder()
    rec.rearm()
    try:
        b = CircuitBreaker("flighttestdep", failure_threshold=1,
                           reset_after_s=60.0)
        b.record_failure()  # threshold 1: first failure trips -> open
        dump = rec.frozen_dump()
        assert dump is not None
        assert dump["reason"] == "breaker.flighttestdep.open"
    finally:
        rec.rearm()


def test_slo_violation_freezes_global_flight_recorder():
    clock = {"t": 0.0}
    rec = get_flight_recorder()
    rec.rearm()
    try:
        s = SLOTracker("flightslo", window_s=60.0, target_p50_ms=1.0,
                       min_samples=2, clock=lambda: clock["t"])
        for _ in range(5):
            s.record(100.0)
        assert s.state() == "violated"
        dump = rec.frozen_dump()
        assert dump is not None and dump["reason"] == "slo.flightslo.violated"
        assert "p50_ms" in (dump["detail"] or "")
    finally:
        rec.rearm()


def test_passive_slo_tracker_never_mutates_the_system():
    """A measurement-side tracker (the swarm's client verdict) must score
    without side effects: no flight freeze, no slo.* gauge export."""
    rec = get_flight_recorder()
    rec.rearm()
    try:
        s = SLOTracker("passiveprobe", window_s=60.0, target_p50_ms=1.0,
                       min_samples=2, passive=True)
        for _ in range(5):
            s.record(100.0)
        assert s.state() == "violated"
        assert rec.frozen_dump() is None
        assert "slo.passiveprobe.state" not in get_metrics().snapshot()["gauges"]
    finally:
        rec.rearm()


def test_flight_sink_writes_dump_on_freeze(tmp_path, monkeypatch):
    monkeypatch.setenv("FLIGHT_SINK", str(tmp_path / "fl"))
    rec = FlightRecorder(max_traces=4, snapshot_interval_s=999)
    rec.observe_span({"svc": "t", "span": "s", "trace": "tr", "ms": 1.0})
    assert rec.trigger("slo.sink.violated")
    files = list(tmp_path.glob("fl_slo.sink.violated_*.json"))
    assert len(files) == 1
    body = json.loads(files[0].read_text())
    assert body["frozen"] and body["traces"][0]["trace_id"] == "tr"


# ------------------------------------ concurrent writers (the race hammer)


def test_slo_tracker_concurrent_record_and_eval_loses_nothing():
    """8 threads hammer record() while 2 more hammer evaluate(): no lost
    samples (the window is huge and under MAX_SAMPLES), no exceptions, and
    the percentile verdict is stable — p50 must be one of the recorded
    values, identical across back-to-back evaluations."""
    s = SLOTracker("hammer", window_s=86_400.0, target_p50_ms=10_000.0,
                   min_samples=5)
    n_threads, per_thread = 8, 400  # 3200 < MAX_SAMPLES
    stop = threading.Event()
    errors: list[Exception] = []

    def writer(t):
        try:
            for i in range(per_thread):
                s.record(1.0 + (i % 7), ok=True)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                ev = s.evaluate()
                assert ev["state"] in ("ok", "at_risk", "violated")
                if ev["p50_ms"] is not None:
                    assert 1.0 <= ev["p50_ms"] <= 8.0
        except Exception as e:  # pragma: no cover
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for th in readers + writers:
        th.start()
    for th in writers:
        th.join(timeout=60)
        assert not th.is_alive(), "writer hung"
    stop.set()
    for th in readers:
        th.join(timeout=60)
        assert not th.is_alive(), "reader hung"
    assert not errors, errors[0]
    ev1, ev2 = s.evaluate(), s.evaluate()
    assert ev1["samples"] == n_threads * per_thread, "lost SLO samples"
    assert ev1["errors"] == 0
    assert ev1["p50_ms"] == ev2["p50_ms"] and ev1["p99_ms"] == ev2["p99_ms"]


def test_trace_and_flight_rings_bounded_under_concurrent_writers():
    """Many threads complete spans with mostly-unique trace ids (the
    abandoned-trace shape: one span, never finished into an utterance):
    nothing is lost from the metrics, and neither the tracer ring nor the
    flight ring grows past its cap. A freeze racing the writers snapshots a
    consistent dump that later writes never mutate."""
    t = Tracer("hammer", emit=False)
    rec = FlightRecorder(max_traces=16, max_snapshots=8,
                         snapshot_interval_s=0.01)
    n_threads, per_thread = 8, 250  # 2000 spans < reservoir cap
    barrier = threading.Barrier(n_threads + 1)
    errors: list[Exception] = []

    def worker(w):
        try:
            barrier.wait(timeout=30)
            for i in range(per_thread):
                with t.span("s", trace_id=f"w{w}i{i}"):
                    pass
                rec.observe_span({"svc": "hammer", "span": "s",
                                  "trace": f"w{w}i{i}", "ms": 0.1})
        except Exception as e:  # pragma: no cover
            errors.append(e)

    frozen_sizes: list[int] = []

    def freezer():
        try:
            barrier.wait(timeout=30)
            rec.trigger("hammer.freeze")
            frozen_sizes.append(len(rec.frozen_dump()["traces"]))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(n_threads)]
    threads.append(threading.Thread(target=freezer))
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive(), "hammer thread hung"
    assert not errors, errors[0]
    # no lost spans: the histogram counted every completion
    assert t.metrics.snapshot()["latency_ms"]["hammer.s"]["count"] \
        == n_threads * per_thread
    # the tracer ring stayed LRU-bounded despite n_threads*per_thread ids
    assert len(t._ring) <= t.MAX_TRACES
    # the flight ring never outgrew its cap, frozen or live
    assert len(rec._traces) <= rec.max_traces
    assert frozen_sizes and frozen_sizes[0] <= rec.max_traces
    # the frozen dump did not grow after the freeze
    assert len(rec.frozen_dump()["traces"]) == frozen_sizes[0]
    assert len(rec.frozen_dump()["metric_snapshots"]) <= rec.max_snapshots


# ------------------------------------------------- scheduler saturation


def test_saturation_gauges_under_full_scheduler_batch(tiny_batch_engine):
    from tpu_voice_agent.serve.scheduler import ContinuousBatcher

    b = ContinuousBatcher(tiny_batch_engine, chunk_steps=16, max_new_tokens=64)
    prompts = ["search for laptops", "scroll down", "go back",
               "take a screenshot", "sort by price"]
    ttft_before = get_metrics().snapshot()["latency_ms"].get(
        "scheduler.ttft", {}).get("count", 0)
    for p in prompts:
        b.submit(p)
    b.step()  # admits B=3, decodes one chunk; 2 queue
    g = get_metrics().snapshot()["gauges"]
    assert g["scheduler.batch_slots"] == 3.0
    assert g["scheduler.batch_occupancy"] == 1.0  # every slot occupied
    assert g["scheduler.queue_depth"] >= 1.0
    assert g["scheduler.tokens_per_s"] > 0.0
    snap = get_metrics().snapshot()["latency_ms"]
    assert snap["scheduler.ttft"]["count"] >= ttft_before + 3
    b.run_until_done()  # drain: the shared engine goes back clean
    g = get_metrics().snapshot()["gauges"]
    assert g["scheduler.batch_occupancy"] == 0.0
    assert g["scheduler.queue_depth"] == 0.0


def test_ttft_includes_queue_wait(tiny_batch_engine):
    """TTFT is enqueue -> first token: a request that sat in the pending
    queue must not report prefill-only latency (the flat-TTFT-under-load
    failure mode)."""
    import time as _time

    from tpu_voice_agent.serve.scheduler import ContinuousBatcher

    b = ContinuousBatcher(tiny_batch_engine, chunk_steps=16, max_new_tokens=32)
    b.submit("scroll down")
    _time.sleep(0.15)  # simulated queue wait before the scheduler turns over
    b.step()
    last_ttft = get_metrics()._latencies["scheduler.ttft"][-1]
    assert last_ttft >= 150.0, last_ttft
    b.run_until_done()


def test_queue_wait_is_its_own_number_beside_ttft(tiny_batch_engine):
    """The wait for a slot, which TTFT hides inside itself: a request that
    sat in the queue reports it as ``scheduler.queue_wait`` and on its
    result, and TTFT is that wait plus the admission."""
    import time as _time

    from tpu_voice_agent.serve.scheduler import ContinuousBatcher

    b = ContinuousBatcher(tiny_batch_engine, chunk_steps=16, max_new_tokens=32)
    rid = b.submit("scroll down")
    _time.sleep(0.15)
    b.step()
    lat = get_metrics()._latencies
    wait, ttft = lat["scheduler.queue_wait"][-1], lat["scheduler.ttft"][-1]
    assert 150.0 <= wait < ttft
    b.run_until_done()
    assert b.results[rid].queue_ms == wait


def test_kv_pool_utilization_gauges():
    from tpu_voice_agent.serve.paged import BlockAllocator, record_pool_gauges

    alloc = BlockAllocator(10, n_groups=2)  # 8 usable (2 trash-reserved)
    record_pool_gauges(alloc)
    g = get_metrics().snapshot()["gauges"]
    assert g["paged.kv_blocks_total"] == 8.0
    assert g["paged.kv_utilization"] == 0.0
    held = alloc.alloc(3, group=0) + alloc.alloc(1, group=1)
    record_pool_gauges(alloc)
    g = get_metrics().snapshot()["gauges"]
    assert g["paged.kv_blocks_used"] == 4.0
    assert g["paged.kv_utilization"] == pytest.approx(0.5)
    alloc.free(held)
    record_pool_gauges(alloc)
    assert get_metrics().snapshot()["gauges"]["paged.kv_utilization"] == 0.0


# ----------------------------------------------------- cross-service e2e


PCM_SILENCE = (np.zeros(1600, dtype="<i2")).tobytes()  # 100 ms


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    """voice + brain + executor on real sockets (http_helper harness)."""
    from tests.http_helper import AppServer
    from tpu_voice_agent.serve.stt import NullSTT
    from tpu_voice_agent.services.brain import RuleBasedParser, build_app as build_brain
    from tpu_voice_agent.services.executor import SessionManager, build_app as build_executor
    from tpu_voice_agent.services.executor.page import FakePage
    from tpu_voice_agent.services.voice import VoiceConfig, build_app as build_voice

    tmp = tmp_path_factory.mktemp("obs_stack")
    brain = AppServer(build_brain(RuleBasedParser())).__enter__()
    manager = SessionManager(page_factory=FakePage.demo,
                             artifacts_root=str(tmp / "art"),
                             uploads_dir=str(tmp / "up"))
    executor = AppServer(build_executor(manager)).__enter__()
    scripted: list = []

    def stt_factory():
        return NullSTT(scripted=list(scripted))

    voice = AppServer(build_voice(VoiceConfig(
        brain_url=brain.url, executor_url=executor.url,
        stt_factory=stt_factory))).__enter__()
    yield {"voice": voice, "brain": brain, "executor": executor,
           "scripted": scripted}
    for srv in (voice, executor, brain):
        srv.__exit__(None, None, None)


def _ws_collect(voice_url, inbound, expect_types, timeout_s=30.0):
    async def run():
        events, seen = [], set()
        async with aiohttp.ClientSession() as sess:
            async with sess.ws_connect(voice_url.replace("http", "ws") + "/stream") as ws:
                for kind, payload in inbound:
                    if kind == "binary":
                        await ws.send_bytes(payload)
                    else:
                        await ws.send_json(payload)
                end = asyncio.get_event_loop().time() + timeout_s
                while asyncio.get_event_loop().time() < end:
                    try:
                        msg = await ws.receive(timeout=1.0)
                    except asyncio.TimeoutError:
                        continue
                    if msg.type != aiohttp.WSMsgType.TEXT:
                        break
                    ev = json.loads(msg.data)
                    events.append(ev)
                    seen.add(ev["type"])
                    if set(expect_types) <= seen:
                        break
        return events

    return asyncio.run(run())


def _get(url, accept=None):
    async def run():
        headers = {"Accept": accept} if accept else {}
        async with aiohttp.ClientSession() as sess:
            async with sess.get(url, headers=headers) as r:
                return r.status, r.headers.get("Content-Type", ""), await r.text()

    return asyncio.run(run())


def test_cross_service_trace_waterfall_for_real_utterance(stack):
    """The acceptance drill: one WS utterance (audio in) -> the SAME trace
    id is visible in all three services' /debug/trace, and traceview
    reassembles the complete capture -> STT -> parse -> execute waterfall."""
    stack["scripted"][:] = [("final", "search for laptops")]
    events = _ws_collect(stack["voice"].url, [("binary", PCM_SILENCE)],
                         ["latency_budget"])
    budget = next(e for e in events if e["type"] == "latency_budget")
    trace_id = budget["trace_id"]
    assert trace_id

    # the stage-split dict the web HUD renders
    st = budget["stages"]
    for key in ("audio_ingest_ms", "stt_finalize_ms", "parse_ms",
                "execute_ms", "total_ms"):
        assert key in st and st[key] >= 0.0, (key, st)
    assert st["total_ms"] == pytest.approx(
        st["stt_finalize_ms"] + st["parse_ms"] + st["execute_ms"], abs=0.01)

    # every service saw the SAME id
    urls = {n: stack[n].url for n in ("voice", "brain", "executor")}
    per_service = {}
    for name, url in urls.items():
        status, _, body = _get(f"{url}/debug/trace/{trace_id}")
        assert status == 200
        payload = json.loads(body)
        assert payload["service"] == name
        per_service[name] = payload["spans"]
        assert payload["spans"], f"{name} has no spans for {trace_id}"
        assert all(sp["trace"] == trace_id for sp in payload["spans"])

    assert {sp["span"] for sp in per_service["voice"]} >= {
        "audio_ingest", "stt_finalize", "parse_roundtrip", "execute_roundtrip"}
    assert {sp["span"] for sp in per_service["brain"]} == {"parse"}
    assert {sp["span"] for sp in per_service["executor"]} == {"execute"}

    # traceview fans out to the real endpoints and derives the stage splits
    out = traceview.waterfall(trace_id, urls)
    assert len(out["spans"]) >= 6
    stages = out["stages"]
    for stage in ("audio_ingest", "stt_finalize", "parse", "execute"):
        assert stage in stages, stages
    assert stages["parse"]["svc"] == "brain"
    assert stages["execute"]["svc"] == "executor"
    assert "queue_ms" in stages["parse"]  # the decomposition attr
    gantt = traceview.render_gantt(out["spans"])
    assert "voice.audio_ingest" in gantt and "executor.execute" in gantt


def test_each_utterance_gets_its_own_trace(stack):
    stack["scripted"][:] = [("final", "scroll down")]
    first = _ws_collect(stack["voice"].url, [("binary", PCM_SILENCE)],
                        ["latency_budget"])
    stack["scripted"][:] = [("final", "go back")]
    second = _ws_collect(stack["voice"].url, [("binary", PCM_SILENCE)],
                         ["latency_budget"])
    t1 = next(e for e in first if e["type"] == "latency_budget")["trace_id"]
    t2 = next(e for e in second if e["type"] == "latency_budget")["trace_id"]
    assert t1 != t2


def test_typed_text_path_emits_latency_budget(stack):
    events = _ws_collect(stack["voice"].url,
                         [("json", {"type": "text", "text": "take a screenshot"})],
                         ["latency_budget"])
    budget = next(e for e in events if e["type"] == "latency_budget")
    st = budget["stages"]
    assert "parse_ms" in st and "audio_ingest_ms" not in st


def test_prometheus_exposition_on_all_services(stack):
    """curl -H 'Accept: text/plain' /metrics on every service: valid 0.0.4
    exposition including the saturation + SLO gauges (the scheduler/KV
    gauges live in the process-global registry all three apps share here)."""
    values_by_service = {}
    for name in ("voice", "brain", "executor"):
        status, ctype, text = _get(stack[name].url + "/metrics",
                                   accept="text/plain")
        assert status == 200
        assert ctype.startswith("text/plain") and "version=0.0.4" in ctype
        values_by_service[name] = _assert_valid_exposition(text)

    # SLO gauges: each service exports its own verdict
    assert "slo_voice_state" in values_by_service["voice"]
    assert "slo_brain_state" in values_by_service["brain"]
    assert "slo_executor_state" in values_by_service["executor"]
    # saturation gauges (global registry; earlier tests drove the real
    # scheduler and allocator in this process)
    for vals in values_by_service.values():
        assert "scheduler_queue_depth" in vals
        assert "scheduler_batch_occupancy" in vals
        assert "paged_kv_utilization" in vals
    # breaker state + inflight ride the voice/exposed registries as gauges
    assert "resilience_brain_breaker_state" in values_by_service["voice"]
    assert "resilience_executor_inflight" in values_by_service["executor"]
    # JSON stays the default contract
    status, ctype, body = _get(stack["voice"].url + "/metrics")
    assert status == 200 and "json" in ctype
    js = json.loads(body)
    assert js["service"] == "voice" and js["slo"]["name"] == "voice"


def test_health_reports_slo_state(stack):
    for name in ("voice", "brain", "executor"):
        status, _, body = _get(stack[name].url + "/health")
        assert status == 200
        assert json.loads(body)["slo"] in ("ok", "at_risk", "violated")


# ------------------------------------------------------------ tooling/CI


def test_traceview_self_test_passes():
    proc = subprocess.run([sys.executable, str(ROOT / "tools" / "traceview.py"),
                           "--self-test"], capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "traceview self-test ok" in proc.stdout


def test_metrics_name_collision_lint_clean_on_repo():
    reg = metrics_lint.scan_source(ROOT / "tpu_voice_agent")
    assert reg, "lint found no registrations — scanner broke"
    collisions = metrics_lint.find_collisions(reg)
    assert collisions == [], f"metric name(s) registered under two types: {collisions}"
    # the multi-token-step counters are registered where the lint can see
    # them — a rename there must show up here
    for name, kind in (("scheduler.forwards", "counter"),
                       ("scheduler.tokens_per_forward", "gauge")):
        assert list(reg[name]) == [kind], name


def test_metrics_name_collision_lint_catches_mismatch(tmp_path):
    (tmp_path / "bad.py").write_text(
        'm.inc("svc.thing")\n'
        'm.set_gauge(f"svc.{dep}.state", 1)\n'
        'other.observe_ms("svc.thing", 3.0)\n')
    reg = metrics_lint.scan_source(tmp_path)
    assert reg["svc.*.state"] == {"gauge": ["bad.py:2"]}
    cols = metrics_lint.find_collisions(reg)
    assert len(cols) == 1 and cols[0][0] == "svc.thing"
    assert set(cols[0][1]) == {"counter", "histogram"}


def test_metrics_lint_pinned_stt_names_present():
    """The multi-stream STT metric names have an external contract (bench
    artifacts, OBSERVABILITY.md catalog): the lint pins name AND kind, so a
    rename or kind flip fails tier-1 here."""
    reg = metrics_lint.scan_source(ROOT / "tpu_voice_agent")
    assert metrics_lint.check_pinned(reg) == []
    for name in ("stt.feed_lag_s", "stt.buffered_audio_s",
                 "stt.batch_occupancy", "stt.partials_coalesced",
                 "stt.finals_batched"):
        assert name in metrics_lint.PINNED
    # the capacity-observatory contract: the flight recorder's metrics, the
    # aborted-utterance error accounting, the live-session gauge, and the
    # saturation gauges the swarm's attribution keys on
    for name, kind in (("flight.freezes", "counter"),
                       ("flight.traces_buffered", "gauge"),
                       ("flight.snapshots_buffered", "gauge"),
                       ("voice.utterances_aborted", "counter"),
                       ("voice.live_sessions", "gauge"),
                       ("scheduler.batch_occupancy", "gauge"),
                       ("paged.kv_utilization", "gauge")):
        assert metrics_lint.PINNED.get(name) == kind, name


def test_metrics_lint_pinned_catches_missing_and_wrong_kind():
    reg = {"stt.feed_lag_s": {"counter": ["x.py:1"]}}  # wrong kind, rest absent
    problems = metrics_lint.check_pinned(reg)
    assert any("must be a gauge" in p for p in problems)
    assert any("not registered anywhere" in p for p in problems)
