"""One /parse round trip as a client sees it (shared by the parse generators)."""

from __future__ import annotations

import asyncio
import time

HEADERS = ("x-prefill-ms", "x-decode-ms", "x-cached-tokens", "x-prompt-tokens")


def outcome_of(status: int, body) -> str:
    """'plan' | 'truncated' (the engine's typed ending when a random model
    never reaches EOS) | 'failed'. The parent re-validates every 'plan'
    body against the program's schema."""
    if status == 200:
        return "plan"
    if (status == 422 and isinstance(body, dict)
            and body.get("error") == "schema_validation_failed"
            and "decode truncated after" in str(body.get("detail", ""))):
        return "truncated"
    return "failed"


async def post_parse(sess, brain_url: str, text: str, timeout_s: float) -> dict:
    import aiohttp

    t_wall, t0 = time.time(), time.perf_counter()
    rec = {"t_send": t_wall, "text": text}
    try:
        async with sess.post(brain_url + "/parse", json={"text": text, "context": {}},
                             timeout=aiohttp.ClientTimeout(total=timeout_s)) as r:
            body = await r.json(content_type=None)
            rec.update(status=r.status, body=body, outcome=outcome_of(r.status, body),
                       headers={h: r.headers[h] for h in HEADERS if h in r.headers})
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        rec.update(status=0, body=None, outcome="failed", error=f"{type(e).__name__}: {e}",
                   headers={})
    rec["ms"] = (time.perf_counter() - t0) * 1e3
    return rec


async def warm_parse(urls: dict, traffic: dict) -> dict:
    import aiohttp

    from ..lib.corpus import texts

    async with aiohttp.ClientSession() as sess:
        recs = [await post_parse(sess, urls["brain"], t, traffic["timeout_s"])
                for t in texts(traffic["corpus_size"])[: traffic["warm_requests"]]]
    return {"warm": [r["outcome"] for r in recs]}
