"""Fleet telemetry plane (ISSUE 14): time-series rings, peer-relative
gray-failure detection, and the fleet dashboard.

Fast-tier coverage for tpu_voice_agent/utils/timeseries.py,
services/replicaset.py's fleet detector, the router's fleet scrape, and
tools/fleetview.py:

- ring bounds + monotonic seqs + the ``?since=`` delta contract (direct
  and over HTTP against a real brain app)
- counter->rate and histogram->window-mean derivation (deterministic
  clock), counter-reset clamping, gauge-prefix filtering
- a thread-safety hammer: concurrent metric writers + ring readers
  against the live sampler thread
- MAD outlier-score units: direction awareness, deviation floors,
  min-peers gating
- the gray enter/exit drill against fake replicas: sticky sessions never
  move, new sessions avoid the gray member, recovery is symmetric, the
  flight dump carries the peer evidence
- ``replica_degrade`` e2e through the REAL router over real brain apps:
  detection, the frozen dump, and fleetview --file rendering it
- the router's clock-skew estimate + traceview's skew-corrected
  multi-service dump merge
- the swarm sampler reading /debug/timeseries deltas
- fleetview --self-test (tier-1 wiring)
"""

import json
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest
from aiohttp import web

from tests.http_helper import AppServer
from tpu_voice_agent.services.brain import RuleBasedParser
from tpu_voice_agent.services.brain import build_app as build_brain
from tpu_voice_agent.services.replicaset import (
    fleet_outlier_scores,
    reduce_window,
    signal_values,
)
from tpu_voice_agent.services.router import BrainRouter, _weight
from tpu_voice_agent.services.router import build_app as build_router
from tpu_voice_agent.utils import Metrics, TimeSeriesRing, get_metrics
from tpu_voice_agent.utils.tracing import get_flight_recorder

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
import fleetview  # noqa: E402
import traceview  # noqa: E402


def _get(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _post(url: str, body: dict, timeout: float = 20.0):
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


# ------------------------------------------------------------------- ring


def test_ring_bounds_and_since_contract():
    src = Metrics()
    clock = iter(float(i) for i in range(100))
    ring = TimeSeriesRing("t", sources=(src,), interval_s=60.0,
                          max_samples=8, clock=lambda: next(clock))
    for _ in range(20):
        ring.sample_once()
    state = ring.state()
    assert len(state["samples"]) == 8, "ring must trim to max_samples"
    seqs = [s["seq"] for s in state["samples"]]
    assert seqs == list(range(12, 20)), "seqs survive trimming, monotonic"
    assert state["next_seq"] == 20
    # the delta contract: since=N returns samples with seq >= N; a cursor
    # pointing past the end returns nothing; a trimmed-away cursor
    # returns what is still retained
    assert [s["seq"] for s in ring.since(18)] == [18, 19]
    assert ring.since(20) == []
    assert [s["seq"] for s in ring.since(0)] == seqs
    assert "now_s" in state and state["service"] == "t"


def test_rate_and_hist_derivation():
    src = Metrics()
    t = {"now": 100.0}
    ring = TimeSeriesRing("t", sources=(src,), interval_s=60.0,
                          max_samples=16, clock=lambda: t["now"])
    src.inc("c.total", 10.0)
    src.observe_ms("h.lat", 10.0)
    first = ring.sample_once()
    assert first["rates"] == {} and first["hist"] == {}, \
        "first sample has no baseline"
    # +5 counts and 3 hist events over 2 seconds
    src.inc("c.total", 5.0)
    for ms in (10.0, 20.0, 30.0):
        src.observe_ms("h.lat", ms)
    src.set_gauge("g.x", 0.7)
    t["now"] = 102.0
    s = ring.sample_once()
    assert s["dt_s"] == 2.0
    assert s["rates"]["c.total"] == pytest.approx(2.5)
    assert s["hist"]["h.lat"]["ms_per"] == pytest.approx(20.0)
    assert s["hist"]["h.lat"]["per_s"] == pytest.approx(1.5)
    assert s["gauges"]["g.x"] == 0.7
    # a counter stepping BACKWARDS (restarted registry) reads rate 0,
    # never negative
    src2 = Metrics()
    ring.sources = (src2,)
    src2.inc("c.total", 1.0)
    t["now"] = 103.0
    s2 = ring.sample_once()
    assert s2["rates"]["c.total"] == 0.0


def test_gauge_prefix_filter():
    src = Metrics()
    src.set_gauge("keep.a", 1.0)
    src.set_gauge("keep.b", 2.0)
    src.set_gauge("drop.c", 3.0)
    ring = TimeSeriesRing("t", sources=(src,), interval_s=60.0,
                          max_samples=4, gauge_prefixes=("keep.",))
    s = ring.sample_once()
    assert set(s["gauges"]) == {"keep.a", "keep.b"}


def test_source_precedence_local_wins():
    glob, local = Metrics(), Metrics()
    glob.set_gauge("x", 1.0)
    local.set_gauge("x", 2.0)
    ring = TimeSeriesRing("t", sources=(glob, local), interval_s=60.0)
    assert ring.sample_once()["gauges"]["x"] == 2.0


def test_ring_thread_hammer():
    """4 metric writers + 2 ring readers against the live sampler thread:
    no exception, bounded ring, strictly monotonic seqs."""
    src = Metrics()
    ring = TimeSeriesRing("t", sources=(src,), interval_s=0.005,
                          max_samples=16)
    stop = threading.Event()
    errors: list[BaseException] = []

    def writer(i: int) -> None:
        try:
            n = 0
            while not stop.is_set():
                src.inc(f"w{i}.count")
                src.set_gauge(f"w{i}.gauge", n)
                src.observe_ms(f"w{i}.lat", n % 50)
                n += 1
        except BaseException as e:  # pragma: no cover - diagnostics
            errors.append(e)

    def reader() -> None:
        try:
            while not stop.is_set():
                st = ring.state(since=0)
                assert len(st["samples"]) <= 16
                seqs = [s["seq"] for s in st["samples"]]
                assert seqs == sorted(set(seqs))
        except BaseException as e:  # pragma: no cover - diagnostics
            errors.append(e)

    ring.start()
    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for th in threads:
        th.start()
    # six spinning threads share the GIL with the sampler: give it until it
    # has sampled enough, not a fixed 0.4 s it only sometimes gets 10 turns in
    deadline = time.monotonic() + 10.0
    while ring.state()["next_seq"] <= 10 and time.monotonic() < deadline:
        time.sleep(0.05)
    stop.set()
    for th in threads:
        th.join(timeout=5)
    ring.stop()
    assert not errors, errors
    assert ring.state()["next_seq"] > 10


def test_since_contract_over_http(monkeypatch):
    monkeypatch.setenv("TS_INTERVAL_S", "0.05")
    with AppServer(build_brain(RuleBasedParser())) as srv:
        _post(srv.url + "/parse", {"text": "scroll down", "context": {}})
        time.sleep(0.3)
        body = _get(srv.url + "/debug/timeseries")
        assert body["service"] == "brain" and body["samples"]
        assert isinstance(body["now_s"], float)
        nxt = body["next_seq"]
        assert body["samples"][-1]["seq"] == nxt - 1
        # the cursor: nothing new yet...
        again = _get(srv.url + f"/debug/timeseries?since={nxt}")
        assert all(s["seq"] >= nxt for s in again["samples"])
        # ...until the sampler ticks again
        time.sleep(0.2)
        later = _get(srv.url + f"/debug/timeseries?since={nxt}")
        assert later["samples"] and later["samples"][0]["seq"] >= nxt


# ---------------------------------------------------------------- MAD math


def _readings(**parse_ms_by_member):
    return {m: {"parse_ms": v} for m, v in parse_ms_by_member.items()}


def test_mad_outlier_scores_units():
    # one member far above a tight fleet: huge score, peers near zero
    scores, agg = fleet_outlier_scores(
        _readings(a=10.0, b=11.0, c=10.5, d=300.0), min_peers=3)
    assert scores["d"]["score"] > 10 and scores["d"]["signal"] == "parse_ms"
    assert scores["a"]["score"] < 1 and scores["b"]["score"] < 1
    assert agg["parse_ms"]["n"] == 4
    assert agg["parse_ms"]["median"] == pytest.approx(10.75)
    # direction: parse_ms is worse HIGH — a member far BELOW the median
    # is fast, not gray
    scores, _ = fleet_outlier_scores(
        _readings(a=100.0, b=101.0, c=99.0, d=1.0), min_peers=3)
    assert scores["d"]["score"] == 0.0
    # tokens_per_forward is worse LOW
    tok = {m: {"tokens_per_forward": v}
           for m, v in dict(a=4.0, b=4.2, c=3.9, d=1.0).items()}
    scores, _ = fleet_outlier_scores(tok, min_peers=3)
    assert scores["d"]["score"] > 3 and scores["d"]["signal"] == "tokens_per_forward"
    high = {m: {"tokens_per_forward": v}
            for m, v in dict(a=4.0, b=4.2, c=3.9, d=9.0).items()}
    scores, _ = fleet_outlier_scores(high, min_peers=3)
    assert scores["d"]["score"] == 0.0, "a FASTER drafter is not gray"
    # the deviation floor: a tightly clustered fleet (MAD ~ 0) must not
    # read μs-scale noise as a catastrophic outlier
    scores, _ = fleet_outlier_scores(
        _readings(a=1.000, b=1.001, c=1.002), min_peers=3)
    assert all(v["score"] < 1 for v in scores.values())
    # min_peers: two members cannot name an outlier
    scores, agg = fleet_outlier_scores(_readings(a=1.0, b=500.0), min_peers=3)
    assert agg == {} and all(v["score"] == 0.0 for v in scores.values())


def test_signal_values_and_reduce_window():
    sample = {"gauges": {"slo.brain.p99_ms": 42.0,
                         "paged.kv_utilization": 0.5,
                         "scheduler.tokens_per_forward": 2.5},
              "rates": {"scheduler.slots_quarantined": 0.25},
              "hist": {"brain.parse": {"ms_per": 12.5, "per_s": 3.0},
                       "engine.step.decode": {"ms_per": 4.0, "per_s": 9.0}}}
    vals = signal_values(sample)
    assert vals == {"parse_ms": 12.5, "parse_p99_ms": 42.0,
                    "decode_ms": 4.0, "tokens_per_forward": 2.5,
                    "kv_utilization": 0.5, "quarantine_rate": 0.25}
    # window reduce: mean per signal over the samples that carry it
    s2 = {"gauges": {}, "rates": {},
          "hist": {"brain.parse": {"ms_per": 37.5, "per_s": 1.0}}}
    red = reduce_window([sample, s2])
    assert red["parse_ms"] == pytest.approx(25.0)
    assert red["parse_p99_ms"] == 42.0
    assert reduce_window([]) == {}


def test_gray_hold_expiry_bounds_evidence_starvation():
    """Demotion starves traffic-borne signals (no new sessions -> no
    fwd_ms): a verdict held without scoreable evidence must expire after
    gray_hold_s so the fleet does not permanently lose the replica —
    while evidence still FLOWS, the verdict holds on merit alone."""
    from tpu_voice_agent.services.replicaset import ReplicaSet

    rs = ReplicaSet(["a", "b", "c"], gray_mad=4.0, gray_windows=2,
                    gray_min_peers=3, gray_hold_s=0.05)
    slow = {"a": {"parse_ms": 300.0}, "b": {"parse_ms": 10.0},
            "c": {"parse_ms": 10.0}}
    rs.apply_fleet_window(slow)
    rs.apply_fleet_window(slow)
    ra = rs.replicas[0]
    assert ra.gray and ra.outlier_signal == "parse_ms"
    # evidence keeps flowing and keeps indicting: verdict holds, no clock
    other = {k: {"kv_utilization": 0.1} for k in ("a", "b", "c")}
    rs.apply_fleet_window(slow)
    assert ra.gray and ra.gray_held_since is None
    # now starve parse_ms fleet-wide: carried values keep it scoreable
    # for gray_windows windows (verdict still holds on merit)...
    rs.apply_fleet_window(other)
    rs.apply_fleet_window(other)
    assert ra.gray
    # ...then scoring is impossible: the hold clock arms...
    rs.apply_fleet_window(other)
    assert ra.gray and ra.gray_held_since is not None
    # ...and past gray_hold_s the verdict expires
    time.sleep(0.08)
    rs.apply_fleet_window(other)
    assert not ra.gray and ra.gray_evidence is None


# ----------------------------------------------------- gray drill (fakes)


def _fake_member(name: str, log: list, controls: dict):
    """Brain-contract stand-in with a controllable time-series surface:
    ``controls["parse_ms"]`` is the hist window mean its /debug/timeseries
    reports; ``controls["now_skew_s"]`` shifts its advertised wall clock."""
    rule = RuleBasedParser()
    seq = {"n": 0}

    async def parse(req: web.Request) -> web.Response:
        body = await req.json()
        log.append((name, body.get("session_id")))
        resp = rule.parse(body["text"], body.get("context") or {})
        return web.json_response(json.loads(resp.model_dump_json()))

    async def health(_req: web.Request) -> web.Response:
        return web.json_response({"ok": True, "service": "brain"})

    async def timeseries(req: web.Request) -> web.Response:
        # one fresh sample per scrape: deterministic windows
        s = {"seq": seq["n"], "t_s": time.time(), "dt_s": 0.1,
             "gauges": {}, "rates": {},
             "hist": {"brain.parse": {"ms_per": controls.get("parse_ms", 10.0),
                                      "per_s": 5.0}}}
        seq["n"] += 1
        return web.json_response({
            "service": "brain", "interval_s": 0.1, "max_samples": 240,
            "now_s": time.time() + controls.get("now_skew_s", 0.0),
            "next_seq": seq["n"], "samples": [s]})

    app = web.Application()
    app.router.add_post("/parse", parse)
    app.router.add_get("/health", health)
    app.router.add_get("/debug/timeseries", timeseries)
    return app


def _fleet_ring(n: int, **router_kw):
    logs = [[] for _ in range(n)]
    controls = [{"parse_ms": 10.0} for _ in range(n)]
    servers = [AppServer(_fake_member(f"r{i}", logs[i], controls[i])).__enter__()
               for i in range(n)]
    router_kw.setdefault("probe_s", 0.1)
    router_kw.setdefault("fleet_windows", 2)
    router_kw.setdefault("fleet_min_peers", 3)
    robj = BrainRouter([s.url for s in servers], **router_kw)
    router = AppServer(build_router(robj)).__enter__()
    return router, servers, logs, controls, robj


def _teardown(router, servers):
    router.__exit__(None, None, None)
    for s in servers:
        try:
            s.__exit__(None, None, None)
        except Exception:
            pass


def _sid_homed_on(robj: BrainRouter, idx: int, prefix: str) -> str:
    urls = [r.url for r in robj.replicas]
    for i in range(10_000):
        sid = f"{prefix}{i}"
        if max(range(len(urls)), key=lambda j: _weight(urls[j], sid)) == idx:
            return sid
    raise AssertionError("no session hashed onto the target replica")


def _wait(pred, timeout_s: float = 10.0, step_s: float = 0.05):
    end = time.monotonic() + timeout_s
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(step_s)
    return False


def test_gray_enter_exit_drill():
    get_flight_recorder().rearm()
    router, servers, logs, controls, robj = _fleet_ring(3)
    try:
        victim = 0
        sticky_sid = _sid_homed_on(robj, victim, "sticky")
        _post(router.url + "/parse", {"text": "scroll down",
                                      "session_id": sticky_sid, "context": {}})
        assert any(e[1] == sticky_sid for e in logs[victim])
        # healthy fleet: no gray
        assert _wait(lambda: _get(router.url + "/health")["fleet"]
                     .get("aggregates"), 5.0)
        assert _get(router.url + "/health")["replicas"]["gray"] == 0
        # the victim drifts: parse wall 30x its peers, sustained
        controls[victim]["parse_ms"] = 300.0
        assert _wait(lambda: _get(router.url + "/health")["replicas"]["gray"] == 1), \
            "victim never marked gray"
        h = _get(router.url + "/health")
        detail = {d["url"]: d for d in h["replica_detail"]}
        vurl = robj.replicas[victim].url
        assert detail[vurl]["gray"] and detail[vurl]["state"] == "up", \
            "gray is a demotion, not an eject"
        assert detail[vurl]["outlier_signal"] == "parse_ms"
        assert detail[vurl]["outlier_score"] >= 4.0
        # sticky sessions NEVER move for gray
        before = len(logs[victim])
        st, _ = _post(router.url + "/parse",
                      {"text": "go back", "session_id": sticky_sid,
                       "context": {}})
        assert st == 200 and len(logs[victim]) == before + 1, \
            "sticky session left its gray home"
        # new sessions homed on the victim are redirected off it
        moved = 0
        for i in range(4):
            sid = _sid_homed_on(robj, victim, f"fresh{i}_")
            _post(router.url + "/parse",
                  {"text": "scroll down", "session_id": sid, "context": {}})
            moved += 1
            assert not any(e[1] == sid for e in logs[victim]), \
                "a NEW session was placed on the gray replica"
        counters = get_metrics().snapshot()["counters"]
        assert counters.get("fleet.shed_gray", 0) >= moved
        assert counters.get("fleet.gray_entered", 0) >= 1
        # the flight dump carries the peer-comparison evidence
        dump = _get(router.url + "/debug/flightrecorder")
        assert dump["frozen"] and dump["reason"] == "fleet.gray"
        ev = dump["extra"]["fleet"]
        assert ev["replica"] == vurl and ev["signal"] == "parse_ms"
        assert len(ev["peers"]) == 3 and ev["score"] >= 4.0
        assert ev["fleet_median"] < ev["value"]
        # symmetric recovery: the drift clears, so does the verdict
        controls[victim]["parse_ms"] = 10.0
        assert _wait(lambda: _get(router.url + "/health")["replicas"]["gray"] == 0), \
            "gray never cleared after recovery"
        sid = _sid_homed_on(robj, victim, "postrecovery")
        _post(router.url + "/parse", {"text": "scroll down",
                                      "session_id": sid, "context": {}})
        assert any(e[1] == sid for e in logs[victim]), \
            "recovered replica still avoided"
    finally:
        _teardown(router, servers)
        get_flight_recorder().rearm()


def test_gray_needs_min_peers():
    """With only two members reporting, nobody can be named the outlier —
    detection must stay quiet instead of guessing."""
    get_flight_recorder().rearm()
    router, servers, logs, controls, robj = _fleet_ring(2)
    try:
        controls[0]["parse_ms"] = 500.0
        time.sleep(1.0)
        assert _get(router.url + "/health")["replicas"]["gray"] == 0
    finally:
        _teardown(router, servers)
        get_flight_recorder().rearm()


def test_clock_skew_estimate_and_flight_fanout():
    get_flight_recorder().rearm()
    router, servers, logs, controls, robj = _fleet_ring(3)
    try:
        controls[1]["now_skew_s"] = 5.0
        assert _wait(lambda: abs(robj.replicas[1].clock_skew_s - 5.0) < 1.0,
                     5.0)
        detail = {d["url"]: d for d in
                  _get(router.url + "/health")["replica_detail"]}
        assert abs(detail[servers[1].url]["clock_skew_s"] - 5.0) < 1.0
        assert abs(detail[servers[0].url]["clock_skew_s"]) < 1.0
        # the fan-out annotates each member dump with the estimate; fake
        # members have no /debug/flightrecorder, so bodies carry errors —
        # but the skew annotation rides regardless
        fan = _get(router.url + "/debug/replicas/flightrecorder")
        assert abs(fan["replicas"][servers[1].url]["clock_skew_s"] - 5.0) < 1.0
    finally:
        _teardown(router, servers)
        get_flight_recorder().rearm()


def test_traceview_merges_skewed_dumps(tmp_path):
    """A saved multi-service dump body merges onto one timeline with each
    member's spans shifted by its recorded skew."""
    t0 = 1_700_000_000.0

    def dump(svc, start, skew):
        return {"frozen": True, "reason": f"slo.{svc}.violated",
                "frozen_at_s": t0 + start + skew, "clock_skew_s": skew,
                "metric_snapshots": [],
                "traces": [{"trace_id": "tr1", "spans": [
                    {"svc": svc, "span": "work", "trace": "tr1", "ms": 100.0,
                     "wall_start_s": t0 + start + skew,
                     "wall_end_s": t0 + start + skew + 0.1}]}]}

    body = {"service": "router",
            "replicas": {"http://a": dump("a", 0.0, 0.0),
                         "http://b": dump("b", 0.2, 7.0)}}
    merged = traceview.merge_flight_dumps(body["replicas"])
    spans = merged["traces"][0]["spans"]
    assert len(spans) == 2
    walls = sorted(s["wall_start_s"] for s in spans)
    assert walls[1] - walls[0] == pytest.approx(0.2, abs=0.01), \
        "skew correction did not land the spans on one clock"
    # the CLI path accepts the saved fan-out shape
    p = tmp_path / "fan.json"
    p.write_text(json.dumps(body))
    assert traceview.main(["--flight", str(p), "--json"]) == 0


# ----------------------------------------------------- e2e (real services)


def test_replica_degrade_e2e_and_fleetview_dump(monkeypatch, tmp_path):
    """The canonical gray failure through the REAL stack: one of three
    real brain replicas latches persistently slow (replica_degrade chaos),
    the router's fleet scrape demotes it, the frozen dump carries the
    evidence, and fleetview renders it."""
    from tpu_voice_agent.utils import chaos as chaos_mod

    monkeypatch.setenv("TS_INTERVAL_S", "0.1")
    monkeypatch.setenv("CHAOS_SLOW_S", "0.4")
    monkeypatch.setenv("SLO_TARGET_P50_MS", "60000")  # only fleet.gray freezes
    monkeypatch.setenv("SLO_TARGET_P99_MS", "120000")
    get_flight_recorder().rearm()
    chaos_mod.configure("replica_degrade@1", seed=3)
    servers = [AppServer(build_brain(RuleBasedParser())).__enter__()
               for _ in range(3)]
    robj = BrainRouter([s.url for s in servers], probe_s=0.1,
                       fleet_windows=2, fleet_min_peers=3)
    router = AppServer(build_router(robj)).__enter__()
    try:
        # spread keyed traffic over the whole ring until detection (the
        # first parse latches its replica slow); every member needs fresh
        # parse_ms signals each window
        end = time.monotonic() + 30.0
        detected = False
        i = 0
        while time.monotonic() < end and not detected:
            for j in range(6):
                _post(router.url + "/parse",
                      {"text": "scroll down", "session_id": f"e2e{i}_{j}",
                       "context": {}})
            i += 1
            detected = _get(router.url + "/health")["replicas"]["gray"] == 1
        assert detected, "the degraded replica was never marked gray"
        h = _get(router.url + "/health")
        gray_urls = [d["url"] for d in h["replica_detail"] if d["gray"]]
        assert len(gray_urls) == 1
        dump = _get(router.url + "/debug/flightrecorder")
        assert dump["frozen"] and dump["reason"] == "fleet.gray"
        ev = dump["extra"]["fleet"]
        assert ev["replica"] == gray_urls[0]
        # a middleware-level slowdown is invisible to the replica's own
        # spans — the router-OBSERVED forward wall is what catches it
        assert ev["signal"] == "fwd_ms" and len(ev["peers"]) == 3
        assert ev["value"] > ev["fleet_median"]
        # fleetview renders the saved dump
        p = tmp_path / "gray_dump.json"
        p.write_text(json.dumps(dump))
        assert fleetview.main(["--file", str(p)]) == 0
        txt = fleetview.render_file(dump)
        assert "demoted on fwd_ms" in txt and gray_urls[0] in txt
        # the live fan-out renders too (real /debug/timeseries bodies)
        health, series, autopilot, costs = fleetview.one_frame(router.url, 32)
        # the costs fan-out answers per replica; rule-based brains carry
        # no engine meter, so every body reports the lanes off
        cost_reps = costs["replicas"]
        assert len(cost_reps) == 3
        assert all(b.get("enabled") is False for b in cost_reps.values())
        assert "[cost lanes off]" in fleetview.render_costs(costs, series)
        # no controller attached in this harness -> the panel degrades
        assert not autopilot.get("enabled")
        assert fleetview.render_autopilot(autopilot) == \
            "autopilot: not attached"
        frame = fleetview.render_fleet(health, series)
        assert "GRAY" in frame and "parse_ms" in frame
    finally:
        _teardown(router, servers)
        chaos_mod.reset()
        get_flight_recorder().rearm()


# --------------------------------------------------------------- sampler


def test_swarm_sampler_reads_timeseries(monkeypatch):
    import swarm

    monkeypatch.setenv("TS_INTERVAL_S", "0.05")
    with AppServer(build_brain(RuleBasedParser())) as srv:
        _post(srv.url + "/parse", {"text": "scroll down", "context": {}})
        sampler = swarm.MetricsSampler([srv.url], interval_s=0.05)
        with sampler:
            time.sleep(0.5)
        assert sampler.samples, "sampler collected nothing"
        assert srv.url not in sampler._legacy, \
            "sampler fell back to /metrics despite a live timeseries ring"
        merged = sampler.samples[-1]["gauges"]
        assert "ts.samples_buffered" in merged
        # the delta cursor advanced past the first poll
        assert sampler._since[srv.url] > 0


def test_sampler_primes_cursor_and_latches_only_on_404(monkeypatch):
    """The first contact with a ring must PRIME the cursor and discard
    the backlog (a prior probe's saturated gauges would otherwise stamp
    stale saturation onto this run's timeline); the legacy ?gauges=1
    fallback latches only on a definitive 404, never a transient error."""
    import swarm

    monkeypatch.setenv("TS_INTERVAL_S", "0.05")
    with AppServer(build_brain(RuleBasedParser())) as srv:
        _post(srv.url + "/parse", {"text": "scroll down", "context": {}})
        time.sleep(0.3)  # let a backlog accumulate in the ring
        backlog = _get(srv.url + "/debug/timeseries")
        assert len(backlog["samples"]) >= 3
        sampler = swarm.MetricsSampler([srv.url])
        sampler._poll_once()
        # the cursor drained the whole backlog, but at most a sliver of
        # post-construction samples may have landed on the timeline — the
        # prior history must never merge
        assert len(sampler.samples) <= 1
        assert sampler._since[srv.url] >= backlog["next_seq"]
        time.sleep(0.15)
        sampler._poll_once()
        assert sampler.samples, "post-prime deltas must merge"
        # a dead URL is a TRANSIENT failure: no legacy latch
        dead = "http://127.0.0.1:9"
        s2 = swarm.MetricsSampler([dead])
        s2._poll_once()
        assert dead not in s2._legacy
    # a service without the endpoint at all (404) latches the fallback
    from aiohttp import web as _web

    app = _web.Application()

    async def metrics(_req):
        return _web.json_response({"runtime": {"gauges": {"old.gauge": 1.0}}})

    app.router.add_get("/metrics", metrics)
    with AppServer(app) as old:
        s3 = swarm.MetricsSampler([old.url])
        s3._poll_once()
        assert old.url in s3._legacy
        assert s3.samples and s3.samples[-1]["gauges"]["old.gauge"] == 1.0


def test_fleetview_self_test():
    assert fleetview.self_test() == 0
