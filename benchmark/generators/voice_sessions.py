"""Generator ``voice_sessions``: ``streams`` WebSocket sessions on voice
``/stream``, started ``stagger_s`` apart, each a closed loop: speak one
utterance (60 ms PCM16 frames at their real-time deadlines, as the web
client sends them), send ``tail_silence_s`` of silence frames, wait for the
``intent`` event, think, repeat. Speech and think lengths cycle through the
traffic file's lists in whole permutations drawn from ``--seed`` (one per
stream): every seed speaks the SAME set of lengths in another order, so the
work of a window does not depend on the seed, only how the streams'
utterances fall against each other and against the voice service's 0.5 s
incremental step. An utterance counts if its last speech frame was due
inside the window. The warm-up speaks one utterance on EVERY stream, at the
cell's stagger, so that the first time two sessions share the chip is
set-up and not the window."""

from __future__ import annotations

import asyncio
import json
import time

FRAME_S = 0.060
KEEP = ("type", "text", "stages", "message", "detail", "degraded")  # of each event


def cycles(traffic: dict, seed: int, idx: int):
    """(speech lengths, think lengths) of stream ``idx``: endless, each a
    run of whole permutations of the traffic file's list drawn from the seed."""
    from ..lib.corpus import seeded_cycle

    return (seeded_cycle(traffic["speech_s"], seed * 1009 + 2 * idx),
            seeded_cycle(traffic["think_s"], seed * 1009 + 2 * idx + 1))


async def _session(idx: int, urls: dict, traffic: dict, seed: int, t_end: float | None,
                   n_max: int | None, out: list[dict]) -> None:
    import aiohttp
    import numpy as np

    from ..lib.audio import pcm16_frames, silence_frame, synth_utterance

    loop = asyncio.get_running_loop()
    speech, think = cycles(traffic, seed, idx)
    n_tail = round(traffic["tail_silence_s"] / FRAME_S)
    sil = silence_frame()
    async with aiohttp.ClientSession() as sess:
        async with sess.ws_connect(urls["voice"].replace("http", "ws") + "/stream") as ws:
            inbox: asyncio.Queue = asyncio.Queue()

            async def reader() -> None:
                async for msg in ws:
                    if msg.type == aiohttp.WSMsgType.TEXT:
                        ev = json.loads(msg.data)
                        ev["_t"] = loop.time()
                        inbox.put_nowait(ev)

            task = asyncio.ensure_future(reader())
            try:
                done = 0
                while n_max is None or done < n_max:
                    secs = next(speech)
                    frames = pcm16_frames(synth_utterance(secs))
                    t0 = loop.time()
                    t_speech_end = t0 + (len(frames) - 1) * FRAME_S
                    if t_end is not None and t_speech_end >= t_end:
                        break
                    utt = {"stream": idx, "speech_s": secs, "t_send": time.time(), "events": [],
                           "late_ms": []}
                    for i, frame in enumerate(frames + [sil] * n_tail):
                        await asyncio.sleep(max(0.0, t0 + i * FRAME_S - loop.time()))
                        utt["late_ms"].append((loop.time() - (t0 + i * FRAME_S)) * 1e3)
                        await ws.send_bytes(frame)
                    limit = t_speech_end + traffic["timeout_s"]
                    ended = None

                    def take(ev: dict) -> None:
                        keep = {k: ev[k] for k in KEEP if k in ev}
                        keep["ms_from_speech_end"] = (ev["_t"] - t_speech_end) * 1e3
                        utt["events"].append(keep)

                    while ended is None and loop.time() < limit:
                        try:
                            ev = await asyncio.wait_for(inbox.get(), max(0.01, limit - loop.time()))
                        except asyncio.TimeoutError:
                            break
                        take(ev)
                        if ev["type"] in ("intent", "error"):
                            ended = ev["type"]
                    utt["ended"] = ended or "timeout"
                    # the speaker looks at the page; the budget event (after the
                    # executor ran) and any late events land during the pause
                    await asyncio.sleep(next(think))
                    while not inbox.empty():
                        take(inbox.get_nowait())
                    out.append(utt)
                    done += 1
            finally:
                task.cancel()


async def warm(urls: dict, traffic: dict, seed: int) -> dict:
    out: list[dict] = []
    longest = dict(traffic, speech_s=[max(traffic["speech_s"])], think_s=[0.3])

    async def start(i: int) -> None:
        await asyncio.sleep(i * traffic["stagger_s"])
        await _session(i, urls, longest, seed, None, traffic["warm_utterances"], out)

    await asyncio.gather(*(start(i) for i in range(traffic["streams"])))
    return {"warm": [u["ended"] for u in out]}


async def run(urls: dict, traffic: dict, seed: int, seconds: float, mark) -> dict:
    loop = asyncio.get_running_loop()
    out: list[dict] = []
    mark("window_start")
    t0 = loop.time()
    t_end = t0 + seconds

    async def start(i: int) -> None:
        await asyncio.sleep(i * traffic["stagger_s"])
        await _session(i, urls, traffic, seed, t_end, None, out)

    async def edge() -> None:
        await asyncio.sleep(seconds)
        mark("window_end")

    await asyncio.gather(edge(), *(start(i) for i in range(traffic["streams"])))
    lateness = [ms for u in out for ms in u.pop("late_ms")]
    return {"utterances": out, "lateness_ms": lateness}
