#!/usr/bin/env python3
"""chip_smoke.py — does voice->intent still start, and compute the right
numbers, on the chip?

ONE process (the only one that touches JAX) drives the main path through the
services' own factories, at the full width of the models ``bench.py`` names:

  K  every Pallas kernel the main path reaches, interpret=False, against its
     ``*_reference`` twin at TinyLlama-1.1B / Whisper-large-v3 head shapes
  A  full width, seeded random weights: voice + brain + fake-page executor
     served on real sockets by ``services.stack`` (whisper-large-v3 STT,
     tinyllama-1.1b int8 paged engine, 4 slots); PCM16 streamed over WS
     ``/stream`` at real-time pace while ``/parse`` requests arrive
     concurrently. Random weights owe no EOS: each parse must end in a
     schema-valid plan or the typed ``decode truncated after N tokens`` —
     and in nothing else (no degraded/rule answer, no llm_error, no engine
     restart, no 5xx)
  B  trained weights, tiny width: the committed whisper + distilled intent
     checkpoints through the same stack; the spoken text must come back as
     the transcript and as the expected intent type — numbers computed on
     the chip are the right numbers, end to end
  C  the stage-A decoder built twice, kernels="pallas" and "xla", same seed
     and prompt: prefill, T=1 decode and fast-forward block logits agree

It refuses to run unless JAX reports a TPU whose device_kind is in the
peaks table, treats a skipped stage or a caught exception as failure, and
exits non-zero on the first failure. Once past the refusal it ends with two
lines on stdout: ``[chip_smoke] REPORT {...}`` (per-stage pass, wall and
set-up seconds, errors against the references, compile-cache counts) and,
last, the driver's contract line ``{"ok": ..., "device": {"platform",
"kind", "count"}}`` with exactly those keys. A refusal prints neither.

``--four-chips`` is a second mode for a four-chip host (it runs stage M
alone): ``/parse`` through the pipeline backend (pp=2 x tp=2) and through a
batched int8 ``DecodeEngine`` on a dp=2 x tp=2 mesh — the shard_map kernels
compiled for real — printing every device's bytes in use to show weights and
KV are spread.

``--rehearse-cpu`` is the only way onto the CPU: the same stages at
test-tiny / whisper-test widths with interpret-mode kernels, every line and
the JSON labelled ``REHEARSAL platform=cpu``. It is never selected by the
environment or by a failure.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL_LABEL = "REHEARSAL platform=cpu"
WALL_LIMIT_S = 1150  # the driver allows 1200 s, compilation included

FULL = dict(stt_preset="whisper-large-v3", llm_preset="tinyllama-1.1b",
            utter_s=2.0,
            # (n_q, n_kv, head_dim, layers) of the two model families
            llm_geom=(32, 4, 64, 22), stt_geom=(20, 20, 64),
            flash_T={"whisper-encoder": 1500, "llama-prefill": 1024},
            self_S=448, cross_S=1500, cache_S=1024, block=128, max_blocks=16)
REHEARSAL = dict(stt_preset="whisper-test", llm_preset="test-tiny",
                 utter_s=1.2,
                 llm_geom=(4, 2, 32, 2), stt_geom=(4, 4, 16),
                 flash_T={"whisper-encoder": 100, "llama-prefill": 128},
                 self_S=64, cross_S=100, cache_S=128, block=16, max_blocks=8)

UTTERANCES = ["search for wireless headphones",
              "sort these by price from low to high",
              "open the second result and take a screenshot",
              "filter results under one hundred dollars"]
# what tests/test_neural_e2e.py holds the trained checkpoints to on the CPU
TRAINED = [("search for red shoes", "search"), ("scroll down", "scroll"),
           ("take a screenshot", "screenshot")]


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ stage K


def stage_kernels(size: dict, on_chip: bool, say) -> dict:
    """Each kernel vs its pure-jnp reference twin on identical bf16 inputs.

    Tolerance, attention kernels: both sides accumulate in f32 from bf16
    inputs and round their output to bf16 once; the reference additionally
    rounds the probabilities to bf16 before the PV product. Outputs are
    convex combinations of unit-normal V rows (|out| <~ 4), so two bf16
    roundings (eps 2^-8) bound the gap near 3e-2 absolute — atol = rtol =
    3e-2. The argmax kernels return integers: exact equality."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent import ops
    from tpu_voice_agent.grammar.intent_grammar import build_intent_fsm
    from tpu_voice_agent.ops.decode_attention import decode_block_attention_reference

    interpret = not on_chip
    nq, nkv, hd, L = size["llm_geom"]
    wq, wkv, whd = size["stt_geom"]
    S, bs, M = size["cache_S"], size["block"], size["max_blocks"]
    B, T = 4, 9  # four batcher slots; a fast-forward step is 1 + BRAIN_FF=8 tokens
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def rnd(*shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(jnp.bfloat16)

    results = {}

    def close(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(np.isfinite(got).all(), f"kernel {name}: non-finite output")
        err = float(np.max(np.abs(got - want)))
        check(np.allclose(got, want, atol=3e-2, rtol=3e-2),
              f"kernel {name}: max|kernel-reference| = {err:.4f} exceeds 3e-2")
        results[name] = round(err, 5)
        say(f"K {name}: max|kernel-reference| {err:.5f} (tol 3e-2) ok")

    for tag, Tq, (hq, hk, d), causal in (
            ("whisper-encoder", size["flash_T"]["whisper-encoder"], (wq, wkv, whd), False),
            ("llama-prefill", size["flash_T"]["llama-prefill"], (nq, nkv, hd), True)):
        q, k, v = rnd(1, Tq, hq, d), rnd(1, Tq, hk, d), rnd(1, Tq, hk, d)
        close(f"flash_attention[{tag} T={Tq}]",
              ops.flash_attention(q, k, v, causal=causal, interpret=interpret),
              ops.attention_reference(q, k, v, causal=causal))

    for tag, Sx in (("whisper-self", size["self_S"]), ("whisper-cross", size["cross_S"])):
        q, k, v = rnd(1, wq, whd), rnd(1, Sx, wkv, whd), rnd(1, Sx, wkv, whd)
        n = jnp.asarray([Sx - 3], jnp.int32)
        close(f"decode_attention[{tag} S={Sx}]",
              ops.decode_attention(q, k, v, n, interpret=interpret),
              ops.decode_attention_reference(q, k, v, n))

    # the llama decode kernels read ONE layer's plane of the stacked cache
    kc, vc = rnd(L, B, S, nkv, hd), rnd(L, B, S, nkv, hd)
    layer = jnp.int32(L - 1)
    kv_len = jnp.asarray([S, S // 2, 17, 1], jnp.int32)
    q1 = rnd(B, nq, hd)
    close("decode_attention_layer",
          ops.decode_attention_layer(q1, kc, vc, kv_len, layer, interpret=interpret),
          ops.decode_attention_reference(q1, kc[L - 1], vc[L - 1], kv_len))
    qT = rnd(B, T, nq, hd)
    q_pos = (kv_len - 1)[:, None] + jnp.minimum(jnp.arange(T)[None, :], S - kv_len[:, None])
    close("decode_block_attention_layer",
          ops.decode_block_attention_layer(qT, kc, vc, q_pos, layer, interpret=interpret),
          decode_block_attention_reference(qT, kc[L - 1], vc[L - 1], q_pos))

    # paged twins: non-contiguous tables into a shared pool
    N = B * M + 1
    kp, vp = rnd(L, N, bs, nkv, hd), rnd(L, N, bs, nkv, hd)
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(N - 1)[: B * M].reshape(B, M) + 1, jnp.int32)
    p_len = jnp.asarray([M * bs, M * bs // 2, bs + 1, 1], jnp.int32)
    close("paged_attention",
          ops.paged_attention(q1, kp, vp, tables, p_len, layer, interpret=interpret),
          ops.paged_attention_reference(q1, kp, vp, tables, p_len, layer))
    p_pos = (p_len - 1)[:, None] + jnp.minimum(jnp.arange(T)[None, :], M * bs - p_len[:, None])
    gathered = [x[L - 1][tables].reshape(B, M * bs, nkv, hd) for x in (kp, vp)]
    close("paged_block_attention",
          ops.paged_block_attention(qT, kp, vp, tables, p_pos, layer, interpret=interpret),
          decode_block_attention_reference(qT, *gathered, p_pos))

    # the grammar tail over the REAL intent FSM tables every engine decodes under
    _, fsm = build_intent_fsm()
    tb = fsm.device_tables()
    logits = jax.random.normal(next(keys), (B, tb.col_id.shape[0]), jnp.float32)
    states = jnp.asarray(np.random.default_rng(1).integers(
        0, tb.table.shape[0], B), jnp.int32).at[0].set(fsm.start)
    tok = ops.masked_argmax(logits, states, tb.dense_mask, interpret=interpret)
    want = ops.masked_argmax_reference(logits, states, tb.dense_mask)
    check(bool((tok == want).all()), f"kernel masked_argmax: {tok} != reference {want}")
    say(f"K masked_argmax: tokens {np.asarray(tok).tolist()} == reference ok")
    tok, nxt = ops.masked_argmax_advance(logits, states, tb.dense_mask, tb.table,
                                         tb.col_id, interpret=interpret)
    wtok, wnxt = ops.masked_argmax_advance_reference(logits, states, tb.dense_mask,
                                                     tb.table, tb.col_id)
    check(bool((tok == wtok).all() and (nxt == wnxt).all()),
          f"kernel masked_argmax_advance: ({tok}, {nxt}) != reference ({wtok}, {wnxt})")
    say(f"K masked_argmax_advance: (tok, next_state) == reference, "
        f"next {np.asarray(nxt).tolist()} ok")
    results["masked_argmax"] = results["masked_argmax_advance"] = 0.0
    return {"max_abs_err": results}


# ------------------------------------------------------- stages A and B (stack)


def pcm16_frames(audio, frame_ms: int = 60) -> list[bytes]:
    """Float audio -> 60 ms PCM16 frames, exactly like the web client."""
    import numpy as np

    pcm = (np.clip(audio, -1, 1) * 32767).astype("<i2").tobytes()
    step = 16_000 * frame_ms // 1000 * 2
    return [pcm[i:i + step] for i in range(0, len(pcm), step)]


async def speak(voice_url: str, audios: list, timeout_s: float) -> list[list[dict]]:
    """One WS session; each utterance's frames go out at their real-time
    deadlines, then events are collected until its ``intent`` or ``error``
    (or the timeout). Returns the event list per utterance."""
    import aiohttp

    out: list[list[dict]] = []
    async with aiohttp.ClientSession() as sess:
        async with sess.ws_connect(voice_url.replace("http", "ws") + "/stream") as ws:
            inbox: asyncio.Queue = asyncio.Queue()

            async def reader():
                async for msg in ws:
                    if msg.type == aiohttp.WSMsgType.TEXT:
                        inbox.put_nowait(json.loads(msg.data))

            task = asyncio.create_task(reader())
            loop = asyncio.get_running_loop()
            try:
                for audio in audios:
                    events: list[dict] = []
                    t0 = loop.time()
                    for i, frame in enumerate(pcm16_frames(audio)):
                        await asyncio.sleep(max(0.0, t0 + i * 0.060 - loop.time()))
                        await ws.send_bytes(frame)
                    end = loop.time() + timeout_s
                    while not any(e["type"] in ("intent", "error") for e in events):
                        left = end - loop.time()
                        if left <= 0:
                            break
                        with contextlib.suppress(asyncio.TimeoutError):
                            events.append(await asyncio.wait_for(inbox.get(), left))
                    out.append(events)
            finally:
                task.cancel()
    return out


def parse_outcome(status: int, body: dict) -> str:
    """'plan' | 'truncated' for the two endings a healthy engine has; raises
    on every other."""
    from tpu_voice_agent.schemas import ParseResponse

    if status == 200:
        ParseResponse.model_validate(body)  # raises if not schema-valid
        return "plan"
    detail = str(body.get("detail", ""))
    check(status == 422 and body.get("error") == "schema_validation_failed"
          and "decode truncated after" in detail,
          f"/parse ended in {status} {body}")
    return "truncated"


def utterance_outcome(events: list[dict], want_text: str | None = None) -> tuple[str, dict]:
    """The WS-side twin of ``parse_outcome`` for one utterance's events."""
    types = [e["type"] for e in events]
    brief = [(e["type"], e.get("message") or e.get("text")) for e in events]
    check(not any(e.get("degraded") for e in events),
          f"degraded / rule-parser answer: {brief}")
    finals = [e for e in events if e["type"] == "transcript_final"]
    check(len(finals) == 1, f"expected one transcript_final, got {brief}")
    if want_text is not None:
        check(finals[0]["text"] == want_text,
              f"transcript {finals[0]['text']!r} != spoken {want_text!r}")
    if "intent" in types:
        return "plan", next(e for e in events if e["type"] == "intent")
    errs = [e for e in events if e["type"] == "error"]
    check(len(errs) == 1, f"no intent and no error (timeout?): {brief}")
    check(errs[0].get("message") == "brain error 422"
          and "decode truncated after" in str(errs[0].get("detail")),
          f"utterance ended in {errs[0]}")
    return "truncated", errs[0]


def http_json(url: str, body: dict | None = None, timeout: float = 120.0):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"content-type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def counters(brain_url: str) -> dict:
    status, body = http_json(brain_url + "/metrics")
    check(status == 200, f"brain /metrics answered {status}")
    return body["runtime"]["counters"]


def serve(env: dict, say):
    """The stack from the environment, as ``python -m
    tpu_voice_agent.services.stack`` builds it; returns (stack, setup_s)."""
    from tpu_voice_agent.services.stack import serve_stack_from_env

    from tpu_voice_agent.utils.compilewatch import get_compile_watcher

    os.environ.update(env)
    t_wall, t0 = time.time(), time.perf_counter()
    stack = serve_stack_from_env(emit=False)
    setup_s = time.perf_counter() - t0
    # what the warm-up kept out of the serving loop: with a cold cache these
    # are XLA compiles, and the slowest bounds what one step could stall for
    new = [e for e in get_compile_watcher().events() if e["t_s"] >= t_wall - 1e-3]
    slow = sorted(new, key=lambda e: -e["ms"])[:3]
    say(f"stack up in {setup_s:.1f}s: {len(new)} watched programs traced in "
        f"{sum(e['ms'] for e in new) / 1e3:.1f}s before the first request, slowest "
        + ", ".join(f"{e['site']} {e['ms'] / 1e3:.1f}s" for e in slow) + f"; {stack.urls}")
    return stack, setup_s


def check_stack_health(stack, say) -> None:
    """No stall dump, no engine restart, pallas on both engines."""
    status, dump = http_json(stack.urls["brain"] + "/debug/flightrecorder")
    check(status == 200 and not (dump.get("frozen") and dump.get("reason") == "engine.stall"),
          f"engine.stall flight dump: {dump.get('reason')} {dump.get('detail')}")
    check(counters(stack.urls["brain"]).get("engine.restarts", 0) == 0,
          "engine.restarts moved")
    runtime = getattr(stack.parser, "runtime", None)
    if runtime is not None:
        check(runtime.stats.restarts == 0 and runtime.healthy(),
              f"ColocationStats.restarts == {runtime.stats.restarts}")
    llm, stt = stack.parser.engine, stack.voice_cfg.stt_factory().engine
    check(llm.kernels == "pallas" and stt.kernels == "pallas",
          f"kernels: decoder {llm.kernels!r}, speech {stt.kernels!r} (want pallas)")
    say(f"engines healthy: restarts 0, kernels pallas/pallas")


def stage_a(size: dict, say, tmp: str) -> dict:
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor

    from bench import synth_utterance

    stack, setup_s = serve({
        "VOICE_STT": f"whisper:{size['stt_preset']}",
        "BRAIN_BACKEND": f"engine:{size['llm_preset']}", "BRAIN_QUANT": "int8",
        "BRAIN_PAGED": "1", "BRAIN_BATCH": "4", "EXECUTOR_FAKE_PAGE": "1",
        "ARTIFACTS_DIR": os.path.join(tmp, "art"), "UPLOADS_DIR": os.path.join(tmp, "up"),
    }, say)
    try:
        before = counters(stack.urls["brain"])
        audio = np.concatenate([synth_utterance(size["utter_s"]),
                                np.zeros(16_000, np.float32)])  # endpoint closes in the tail
        n_ws = 3
        with ThreadPoolExecutor(len(UTTERANCES)) as pool:
            # /parse requests land WHILE the microphone streams: both engines
            # dispatch to the one chip from this one process
            posts = [pool.submit(http_json, stack.urls["brain"] + "/parse",
                                 {"text": u, "context": {}}) for u in UTTERANCES]
            per_utt = asyncio.run(speak(stack.urls["voice"], [audio] * n_ws, 180.0))
            direct = [parse_outcome(*p.result()) for p in posts]
        say(f"A /parse x{len(direct)} concurrent: {direct}")
        spoken = []
        for i, events in enumerate(per_utt):
            kind, ev = utterance_outcome(events)
            final = next(e["text"] for e in events if e["type"] == "transcript_final")
            spoken.append(kind)
            say(f"A utterance {i}: transcript_final {final[:40]!r} -> {kind}"
                + (f" ({ev['detail'][:60]})" if kind == "truncated" else ""))
        after = counters(stack.urls["brain"])
        done = after.get("scheduler.requests_completed", 0) - before.get(
            "scheduler.requests_completed", 0)
        toks = after.get("scheduler.tokens_generated", 0) - before.get(
            "scheduler.tokens_generated", 0)
        # every answered parse ran on the engine: each direct POST is one
        # request; an utterance is one (its speculative parse was reused) or
        # two (the final transcript differed from the speculation)
        lo, hi = len(direct) + n_ws, len(direct) + 2 * n_ws
        check(lo <= done <= hi, f"scheduler.requests_completed moved by {done}, "
              f"expected {lo}..{hi}: the answers did not come from the engine")
        check(done <= toks <= done * 600, f"scheduler.tokens_generated moved by {toks} "
              f"for {done} requests")
        say(f"A engine counters: requests_completed +{done:.0f}, tokens_generated +{toks:.0f}")
        check_stack_health(stack, say)
    finally:
        stack.close()
    return {"setup_s": round(setup_s, 1), "parse": direct, "utterances": spoken,
            "requests_completed": int(done), "tokens_generated": int(toks)}


def stage_b(say, tmp: str) -> dict:
    import numpy as np

    from tpu_voice_agent.train import distill

    ckpt = os.path.join(HERE, "checkpoints")
    stack, setup_s = serve({
        "VOICE_STT": f"whisper-ckpt:{os.path.join(ckpt, distill.WHISPER_CKPT)}",
        "BRAIN_BACKEND": f"distilled:{os.path.join(ckpt, distill.INTENT_CKPT)}",
        "BRAIN_BATCH": "1", "BRAIN_PAGED": "0", "BRAIN_QUANT": "",
        "EXECUTOR_FAKE_PAGE": "1",
        "ARTIFACTS_DIR": os.path.join(tmp, "art"), "UPLOADS_DIR": os.path.join(tmp, "up"),
    }, say)
    try:
        before = counters(stack.urls["brain"])
        sil = np.zeros(16_000, np.float32)
        audios = [np.concatenate([distill.render_speech(text), sil]) for text, _ in TRAINED]
        per_utt = asyncio.run(speak(stack.urls["voice"], audios, 120.0))
        got = []
        for (text, want_type), events in zip(TRAINED, per_utt):
            kind, ev = utterance_outcome(events, want_text=text)
            check(kind == "plan", f"trained parse of {text!r} ended {kind}: {ev}")
            types = [i["type"] for i in ev["data"]["intents"]]
            check(types[:1] == [want_type], f"{text!r} parsed to {types}, expected {want_type}")
            got.append(types[0])
            say(f"B {text!r}: transcript exact, intent {types[0]} ok")
        n = counters(stack.urls["brain"]).get("engine.requests", 0) - before.get(
            "engine.requests", 0)
        check(len(TRAINED) <= n <= 2 * len(TRAINED), f"engine.requests moved by {n}")
        check_stack_health(stack, say)
    finally:
        stack.close()
    return {"setup_s": round(setup_s, 1), "intents": got}


# ------------------------------------------------------------------ stage C


def stage_c(size: dict, say) -> dict:
    """Logits, not tokens. Tolerance: the two paths differ only in the
    attention op (f32-accumulated Pallas tiles vs XLA einsum+softmax, both
    over bf16 K/V), a bf16-rounding-sized difference per layer that the
    residual stream carries through the stack; 5% of the logit range
    (max|xla logit|) bounds it with room, while a wrong mask, frontier or
    head mapping moves logits by the range itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_voice_agent.models.llama import forward, init_kv_cache
    from tpu_voice_agent.serve import DecodeEngine
    from tpu_voice_agent.services.prompts import render_prompt

    t0 = time.perf_counter()
    engines = {k: DecodeEngine(preset=size["llm_preset"], max_len=1024,
                               prefill_buckets=(1024,), quant="int8", kernels=k)
               for k in ("pallas", "xla")}
    ids = engines["xla"].tokenizer.encode(
        render_prompt(UTTERANCES[0], {"last_query": None}), bos=True)
    n = len(ids)
    tokens = np.full((1, 1024), engines["xla"].pad_id, np.int32)
    tokens[0, :n] = ids
    positions = np.arange(1024, dtype=np.int32)[None, :]
    worst = {}

    def agree(name: str, got, want) -> None:
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        check(np.isfinite(got).all() and np.isfinite(want).all(), f"C {name}: non-finite logits")
        rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        check(rel <= 5e-2, f"C {name}: max|pallas-xla| is {rel:.4f} of the logit range (> 5e-2)")
        worst[name] = round(rel, 5)
        say(f"C {name}: max|pallas-xla| / max|xla| = {rel:.5f} (tol 5e-2) ok")

    caches, out = {}, {}
    for k, eng in engines.items():
        out[k], caches[k] = forward(eng.params, eng.cfg, jnp.asarray(tokens),
                                    jnp.asarray(positions), init_kv_cache(eng.cfg, 1, 1024),
                                    attn_impl=k, fresh_block=True)
    agree(f"prefill[{n} tokens]", out["pallas"][0, :n], out["xla"][0, :n])
    cur = int(jnp.argmax(out["xla"][0, n - 1]))
    pos = n
    for step in range(3):  # T=1: decode_attention_layer; teacher-forced on xla's pick
        for k, eng in engines.items():
            out[k], caches[k] = forward(eng.params, eng.cfg, jnp.full((1, 1), cur, jnp.int32),
                                        jnp.full((1, 1), pos, jnp.int32), caches[k], attn_impl=k)
        agree(f"decode step {step}", out["pallas"][0, 0], out["xla"][0, 0])
        cur, pos = int(jnp.argmax(out["xla"][0, 0])), pos + 1
    blk = jnp.asarray([[cur] + ids[1:9]], jnp.int32)  # a (1, 1+8) fast-forward step
    blk_pos = (pos + jnp.arange(9, dtype=jnp.int32))[None, :]
    for k, eng in engines.items():
        out[k], caches[k] = forward(eng.params, eng.cfg, blk, blk_pos, caches[k], attn_impl=k)
    agree("fast-forward block (T=9)", out["pallas"][0], out["xla"][0])
    return {"setup_and_run_s": round(time.perf_counter() - t0, 1), "rel_err": worst}


# ------------------------------------------------------------------ stage M


def stage_mesh(size: dict, say) -> dict:
    """Four chips, one process: the two meshed serving layouts answer
    /parse, and no device is left empty."""
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from tpu_voice_agent.parallel.mesh import make_mesh
    from tpu_voice_agent.serve import DecodeEngine
    from tpu_voice_agent.services import warm_up
    from tpu_voice_agent.services.brain import _wrap_batched, build_app, make_parser_from_env
    from tpu_voice_agent.services.stack import AppServer

    def bytes_in_use() -> list[int | None]:
        stats = [d.memory_stats() for d in jax.devices()[:4]]
        return [s["bytes_in_use"] if s else None for s in stats]  # the CPU reports none

    def pp_parser():
        os.environ.update({"BRAIN_BACKEND": f"pp:{size['llm_preset']}", "BRAIN_PP": "2",
                           "BRAIN_TP": "2", "BRAIN_BATCH": "2", "BRAIN_PAGED": "0",
                           "BRAIN_QUANT": ""})
        return make_parser_from_env()

    def mesh_parser():
        return _wrap_batched(DecodeEngine(
            preset=size["llm_preset"], mesh=make_mesh(dp=2, tp=2), quant="int8",
            batch_slots=4, fast_forward=8))

    def run_layout(name: str, build) -> dict:
        base = bytes_in_use()
        t0 = time.perf_counter()
        parser = build()
        try:
            warm_up(parser)
            setup_s = time.perf_counter() - t0
            with AppServer(build_app(parser)) as brain, ThreadPoolExecutor(4) as pool:
                posts = [pool.submit(http_json, brain.url + "/parse",
                                     {"text": u, "context": {}}, 600.0) for u in UTTERANCES]
                got = [parse_outcome(*p.result()) for p in posts]
            check(parser.runtime.stats.restarts == 0 and parser.runtime.healthy(),
                  f"M {name}: ColocationStats.restarts == {parser.runtime.stats.restarts}")
            # what THIS layout put on each device (weights, KV, tables)
            mem = [None if m is None else m - b for m, b in zip(bytes_in_use(), base)]
            check(all(m is None for m in mem) or min(mem) > 0.2 * max(mem),
                  f"M {name}: a device holds almost nothing: bytes added {mem}")
            say(f"M {name}: up in {setup_s:.1f}s, /parse x{len(got)} {got}, kernels "
                f"{parser.engine.kernels}, bytes_in_use added per device {mem}")
            return {"setup_s": round(setup_s, 1), "parse": got, "bytes_added": mem,
                    "kernels": parser.engine.kernels}
        finally:
            parser.close()

    out = {}
    for name, build in (("pp2_tp2", pp_parser), ("dp2_tp2_int8", mesh_parser)):
        out[name] = run_layout(name, build)
        gc.collect()  # the next layout's baseline must not hold this one's buffers
    return out


# --------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU at test-tiny/whisper-test widths with "
                         f"interpret-mode kernels, labelled {REHEARSAL_LABEL!r}")
    ap.add_argument("--four-chips", action="store_true",
                    help="on a four-chip host: run stage M (the pp x tp and "
                         "dp x tp serving meshes) instead of K/A/B/C")
    args = ap.parse_args()
    rehearse = args.rehearse_cpu
    label = REHEARSAL_LABEL + " " if rehearse else ""

    def say(msg: str) -> None:
        print(f"[chip_smoke] {label}{msg}", flush=True)

    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
        if args.four_chips:
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, HERE)
    os.chdir(HERE)
    t_start = time.perf_counter()

    def overrun() -> None:
        say(f"FAIL: still running after {WALL_LIMIT_S}s")
        os._exit(3)

    timer = threading.Timer(WALL_LIMIT_S, overrun)
    timer.daemon = True
    timer.start()

    import importlib.metadata as md

    import jax

    from tpu_voice_agent.utils.compilecache import place_compile_cache
    from tpu_voice_agent.utils.costmodel import PEAK_TABLE

    cache_dir = place_compile_cache()
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cache_before = cache_entries()
    say(f"platform={device['platform']} device_kind={device['kind']} count={device['count']} "
        f"jax={jax.__version__} jaxlib={md.version('jaxlib')} libtpu={md.version('libtpu')} "
        f"compile_cache={cache_dir} (entries {cache_before})")
    if rehearse:
        if device["platform"] != "cpu":
            say(f"FAIL: --rehearse-cpu but JAX runs on {device['platform']}")
            return 2
        # the rehearsal exists to walk the kernels' code paths: "auto" picks
        # pallas here too (interpret mode), as it does on the chip
        from tpu_voice_agent.ops.backend import resolve_kernels
        from tpu_voice_agent.serve import engine as _engine, stt as _stt

        _engine.resolve_kernels = _stt.resolve_kernels = (
            lambda k: resolve_kernels("pallas" if k == "auto" else k))
    elif device["platform"] != "tpu" or device["kind"] not in PEAK_TABLE:
        say(f"REFUSED: needs a TPU whose device_kind is in costmodel.PEAK_TABLE "
            f"({sorted(PEAK_TABLE)}); found {device}. No stage ran.")
        return 2
    if args.four_chips and device["count"] < 4:
        say(f"REFUSED: --four-chips needs four devices, found {device['count']}. "
            "No stage ran.")
        return 2
    size = REHEARSAL if rehearse else FULL

    from tpu_voice_agent import native

    native.rms([0.0])  # builds the C++ audio frontend with g++ (raises if the build fails)
    say(f"native audio frontend: {'built and loaded' if native.native_available() else 'no g++: numpy twins'}")

    stages: dict = {}
    result = {"ok": False, "device": device, "stages": stages}
    if rehearse:
        result["rehearsal"] = REHEARSAL_LABEL
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            plan = ((("M", lambda: stage_mesh(size, say)),) if args.four_chips else
                    (("K", lambda: stage_kernels(size, not rehearse, say)),
                     ("A", lambda: stage_a(size, say, tmp)),
                     ("B", lambda: stage_b(say, tmp)),
                     ("C", lambda: stage_c(size, say))))
            for name, run in plan:
                say(f"stage {name} ...")
                t0 = time.perf_counter()
                stages[name] = {"pass": False}
                detail = run()
                stages[name] = {"pass": True, "wall_s": round(time.perf_counter() - t0, 1),
                                **detail}
                say(f"stage {name} PASS in {stages[name]['wall_s']}s")
        result["ok"] = True
    except Exception as e:  # the first failure ends the run, loudly
        import traceback

        traceback.print_exc()
        say(f"FAIL: {type(e).__name__}: {e}")
        result["failure"] = f"{type(e).__name__}: {e}"[:500]
    result["wall_s"] = round(time.perf_counter() - t_start, 1)
    result["compile_cache"] = {"dir": cache_dir, "entries_before": cache_before,
                               "entries_after": cache_entries()}
    timer.cancel()
    # the detail (stages, walls, cache) is a labelled line; the LAST line is
    # the driver's contract and holds exactly "ok" and "device"
    say("REPORT " + json.dumps(result))
    print(json.dumps({"ok": result["ok"], "device": device}), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
