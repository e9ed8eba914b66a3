"""Generator ``parse_arrivals``: an open loop on ``POST /parse`` at a rate
fixed in the traffic file (``rate_rps``), whatever the server does. The
gaps are a stratified sample of the exponential distribution and the texts
whole permutations of the corpus, both shuffled by ``--seed``: Poisson in
shape, the SAME set of gaps and texts for every seed, in another order. A
tail is made by where the bursts fall, so it moves with the seed: at 11.2 /s
(0.8 of the knee) over 45 s, reshuffling moved the 95th percentile by 6 %
where one order repeats to about 1 % (my chip runs, PR 23) — a cell on this
generator takes its bound from the spread across seeds. A request's latency
runs FROM WHEN IT WAS DUE, so a stall is charged to every request it
delays; how late the generator itself sent each one is reported beside it."""

from __future__ import annotations

import asyncio
import math
import random

from ._http import post_parse, warm_parse


def due_times(rate_rps: float, seconds: float, seed: int) -> list[float]:
    """Offsets (s) from the window's start at which requests are due."""
    n = max(1, round(rate_rps * seconds))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate_rps for i in range(n)]
    random.Random(seed).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g
        if t < seconds:
            out.append(t)
    return out


async def warm(urls: dict, traffic: dict, seed: int) -> dict:
    return await warm_parse(urls, traffic)


async def run(urls: dict, traffic: dict, seed: int, seconds: float, mark) -> dict:
    import aiohttp

    from ..lib.corpus import seeded_cycle, texts

    dues = due_times(traffic["rate_rps"], seconds, seed)
    order = seeded_cycle(texts(traffic["corpus_size"]), seed)
    records: list[dict] = []
    lateness: list[float] = []
    loop = asyncio.get_running_loop()
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as sess:
        mark("window_start")
        t0 = loop.time()

        async def one(due: float, text: str) -> None:
            lateness.append((loop.time() - (t0 + due)) * 1e3)
            rec = await post_parse(sess, urls["brain"], text, traffic["timeout_s"])
            rec["in_window"] = True  # every request DUE in the window counts
            rec["ms_from_due"] = (loop.time() - (t0 + due)) * 1e3
            rec["due_s"] = due
            records.append(rec)

        tasks = []
        for due in dues:
            await asyncio.sleep(max(0.0, t0 + due - loop.time()))
            tasks.append(asyncio.ensure_future(one(due, next(order))))
        await asyncio.sleep(max(0.0, t0 + seconds - loop.time()))
        mark("window_end")
        await asyncio.gather(*tasks)
    return {"records": records, "lateness_ms": lateness}
