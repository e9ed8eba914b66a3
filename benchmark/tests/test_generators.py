"""Generators are pure functions of the seed, and an open loop times a
request from when it was due."""

import asyncio
import time

from aiohttp import web
from aiohttp.test_utils import TestServer

from benchmark.generators import parse_arrivals, parse_clients, voice_sessions
from benchmark.lib.corpus import seeded_cycle, texts

TRAFFIC = {"corpus_size": 8, "timeout_s": 10.0, "warm_requests": 1, "think_s": 0.0,
           "clients": 3, "rate_rps": 40.0}


def test_due_times_are_a_poisson_shaped_stream_drawn_from_the_seed():
    a, b = parse_arrivals.due_times(9.0, 30.0, 23), parse_arrivals.due_times(9.0, 30.0, 23)
    c = parse_arrivals.due_times(9.0, 30.0, 24)
    assert a == b and a != c
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip([0.0] + d, d))
    assert abs(len(a) - 270) <= 3 and gaps(a)[:200] == gaps(c)[:200]  # the same gaps, reordered
    assert all(0 < t < 30.0 for t in a) and a == sorted(a)
    mean = 30.0 / 270
    assert sum(g > 2 * mean for g in gaps(a)) / len(a) > 0.1  # exponential, not a metronome


def test_voice_streams_speak_the_same_lengths_in_an_order_drawn_from_the_seed():
    traffic = {"speech_s": [1.5, 1.8, 2.1, 2.4, 2.7, 3.0], "think_s": [0.5, 0.75, 1.0, 1.25, 1.5]}
    take = lambda seed, idx, n: [next(c) for c in voice_sessions.cycles(traffic, seed, idx)
                                 for _ in range(n)]
    big = 2**31 + 11
    assert take(big, 0, 12) == take(big, 0, 12)
    assert len({tuple(take(s, i, 12)) for s in (7, big) for i in (0, 1)}) == 4  # seed AND stream
    for seed in (7, big):
        speech = take(seed, 1, 12)[:12]
        assert sorted(speech[:6]) == sorted(speech[6:]) == traffic["speech_s"]  # the same work


def test_seeded_cycle_is_balanced_and_seeded():
    items = texts(8)
    take = lambda seed, n: [x for x, _ in zip(seeded_cycle(items, seed), range(n))]
    assert take(3, 40) == take(3, 40) != take(4, 40)
    assert all(take(3, 40).count(t) == 5 for t in items)
    assert texts(64)[:8] == items and len(set(texts(64))) == 64


async def _serve(delay_s: float):
    busy = asyncio.Lock()

    async def parse(req):
        await req.json()
        async with busy:  # one at a time: a queue builds, as behind a stalled engine
            await asyncio.sleep(delay_s)
        return web.json_response({"intents": []}, headers={"x-decode-ms": "5"})

    app = web.Application()
    app.router.add_post("/parse", parse)
    server = TestServer(app)
    await server.start_server()
    return server


def test_open_loop_times_from_due_and_reports_its_own_lateness():
    async def go():
        server = await _serve(0.05)  # 20/s served, 40/s offered: the queue grows
        marks = []
        try:
            url = str(server.make_url("")).rstrip("/")
            out = await parse_arrivals.run({"brain": url}, TRAFFIC, 5, 1.0,
                                           lambda ev: marks.append((ev, time.time())))
        finally:
            await server.close()
        return out, marks

    out, marks = asyncio.run(go())
    recs = out["records"]
    assert [m[0] for m in marks] == ["window_start", "window_end"]
    assert 0.95 < marks[1][1] - marks[0][1] < 1.2  # the edge is the window's, not the drain's
    assert len(recs) == len(parse_arrivals.due_times(40.0, 1.0, 5)) and len(out["lateness_ms"]) == len(recs)
    assert all(r["outcome"] == "plan" and r["ms_from_due"] >= r["ms"] - 1.0 for r in recs)
    assert max(r["ms_from_due"] for r in recs) > 400  # the backlog is charged to the waiting requests
    assert max(out["lateness_ms"]) < 100


def test_closed_loop_keeps_n_in_flight_and_marks_late_completions():
    async def go():
        server = await _serve(0.02)
        try:
            url = str(server.make_url("")).rstrip("/")
            return await parse_clients.run({"brain": url}, TRAFFIC, 5, 0.6, lambda ev: None)
        finally:
            await server.close()

    recs = asyncio.run(go())["records"]
    inside = [r for r in recs if r["in_window"]]
    assert 20 <= len(inside) <= 31 and len(recs) - len(inside) <= TRAFFIC["clients"]
    assert [r["text"] for r in recs[:3]] == [t for t, _ in zip(seeded_cycle(texts(8), 5), range(3))]
