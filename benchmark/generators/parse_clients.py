"""Generator ``parse_clients``: a closed loop of ``clients`` callers on
``POST /parse``, each sending its next request when the last one answered
(plus ``think_s``). Texts: the first ``corpus_size`` of the corpus, in whole
permutations drawn from the seed — the same set for every seed."""

from __future__ import annotations

import asyncio

from ._http import post_parse, warm_parse


async def warm(urls: dict, traffic: dict, seed: int) -> dict:
    return await warm_parse(urls, traffic)


async def run(urls: dict, traffic: dict, seed: int, seconds: float, mark) -> dict:
    import aiohttp

    from ..lib.corpus import seeded_cycle, texts

    order = seeded_cycle(texts(traffic["corpus_size"]), seed)
    records: list[dict] = []
    loop = asyncio.get_running_loop()
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as sess:
        mark("window_start")
        t_end = loop.time() + seconds

        async def client() -> None:
            while loop.time() < t_end:
                rec = await post_parse(sess, urls["brain"], next(order), traffic["timeout_s"])
                rec["in_window"] = loop.time() <= t_end  # completed inside the window
                records.append(rec)
                if traffic["think_s"] > 0 or rec["outcome"] == "failed":
                    await asyncio.sleep(max(traffic["think_s"], 0.05))  # never a hot loop of refusals

        async def edge() -> None:
            await asyncio.sleep(max(0.0, t_end - loop.time()))
            mark("window_end")

        await asyncio.gather(edge(), *(client() for _ in range(traffic["clients"])))
    return {"records": records, "lateness_ms": []}
