"""Cost & efficiency observatory (ISSUE 17) — FAST tier.

The conservation contract (utils/costmodel.py): every ledger quantity is
a Python int, and the scheduler folds the SAME ints into the per-request
slot ledger and the engine meter's totals — so ``sum(per-request
ledgers) == engine totals`` holds EXACTLY, including errored rows
(poisoned, cancelled: the hardware did the work, the ledger bills it).
The differential contract: the cost lanes are host arithmetic over
readbacks the chunk already pays for — token streams identical with the
lanes on or off, zero recompiles past the warmup fence with them on.

Surfaces covered here: ``GET /debug/costs`` on brain (meter + session
attribution) and voice (STT share), the flight-recorder dump's ``costs``
section, and the SessionCostLedger LRU semantics.
"""

import json
import time
import urllib.request

import pytest

from tpu_voice_agent.serve import DecodeEngine, PagedDecodeEngine
from tpu_voice_agent.serve.scheduler import ContinuousBatcher
from tpu_voice_agent.services.brain import (
    SessionTranscripts,
    install_prompt_prefix,
)
from tpu_voice_agent.services.prompts import render_prompt
from tpu_voice_agent.utils import chaos, get_metrics
from tpu_voice_agent.utils.costmodel import (
    LEDGER_KEYS,
    CostModel,
    SessionCostLedger,
    decode_flops,
    device_peak,
    llm_attn_flops_per_ctx,
    llm_token_flops,
    prefill_flops,
    whisper_decoder_flops,
    whisper_encoder_flops,
    zero_ledger,
)

BUCKETS = (128, 256, 512, 1024, 2048)
MAXTOK = 32


def _sum_costs(results) -> dict:
    out = zero_ledger()
    for r in results:
        assert r.cost is not None, f"request missing its ledger: {r.error}"
        for k in LEDGER_KEYS:
            out[k] += r.cost[k]
    return out


def _assert_conserved(batcher, results) -> None:
    summed = _sum_costs(results)
    totals = batcher.costs.totals
    for k in LEDGER_KEYS:
        assert summed[k] == totals[k], (
            f"{k}: sum(requests)={summed[k]} != engine={totals[k]} "
            f"(delta {summed[k] - totals[k]:+d})")
        assert isinstance(totals[k], int) and isinstance(summed[k], int)


# ------------------------------------------------------------- unit model


@pytest.fixture(scope="module")
def tiny_cfg():
    return DecodeEngine(preset="test-tiny", max_len=128, prefill_buckets=(64,),
                        init_weights=False).cfg


def test_zero_ledger_keys(tiny_cfg):
    z = zero_ledger()
    assert tuple(z) == LEDGER_KEYS
    assert all(v == 0 and isinstance(v, int) for v in z.values())
    assert isinstance(llm_token_flops(tiny_cfg), int)
    assert isinstance(llm_attn_flops_per_ctx(tiny_cfg), int)


def test_prefill_split_exact_partition(tiny_cfg):
    """computed + cached == the full cold-prompt cost, exactly, for any
    cache depth — the split is a partition, not an approximation."""
    model = CostModel(tiny_cfg)
    for n, c in ((100, 0), (100, 37), (100, 100), (7, 3), (1, 0)):
        computed, cached = model.prefill_split(n, c)
        assert computed + cached == prefill_flops(tiny_cfg, n, n)
        assert cached == prefill_flops(tiny_cfg, c, c)
        assert computed >= 0 and cached >= 0
    # cached beyond the prompt clamps (radix can only match the prompt)
    assert model.prefill_split(10, 99) == (0, prefill_flops(tiny_cfg, 10, 10))
    assert model.prefill_split(0, 0) == (0, 0)


def test_decode_flops(tiny_cfg):
    tok = llm_token_flops(tiny_cfg)
    att = llm_attn_flops_per_ctx(tiny_cfg)
    assert decode_flops(tiny_cfg, 3, 100) == 3 * (tok + 100 * att)
    model = CostModel(tiny_cfg)
    fl, by = model.decode_row(2, 50)
    assert fl == decode_flops(tiny_cfg, 2, 50)
    assert by == 2 * model.kv_pos_bytes * 51  # reads over ctx + the write


def test_whisper_flops_shape():
    from tpu_voice_agent.models.whisper import WhisperConfig

    cfg = WhisperConfig()
    e1 = whisper_encoder_flops(cfg, 500)
    e2 = whisper_encoder_flops(cfg, 1000)
    assert isinstance(e1, int) and e1 > 0
    assert e2 > 2 * e1  # self-attention term is quadratic in frames
    d1 = whisper_decoder_flops(cfg, 10, 250)
    assert isinstance(d1, int) and d1 > 0
    assert whisper_decoder_flops(cfg, 20, 250) == 2 * d1  # linear in tokens
    assert whisper_decoder_flops(cfg, 0, 250) == 0


def test_device_peak_knob_override(monkeypatch):
    monkeypatch.setenv("COST_PEAK_TFLOPS", "100")
    monkeypatch.setenv("COST_PEAK_GBPS", "1000")
    p = device_peak()
    assert p["flops_per_s"] == pytest.approx(100e12)
    assert p["bytes_per_s"] == pytest.approx(1000e9)
    assert p["source"] == "knob"
    monkeypatch.delenv("COST_PEAK_TFLOPS")
    monkeypatch.delenv("COST_PEAK_GBPS")
    p = device_peak()  # CPU harness: the documented proxy, finite and > 0
    assert p["flops_per_s"] > 0 and p["bytes_per_s"] > 0
    assert p["source"] == "cpu-proxy"


def test_device_peak_matches_tpu_kind_exactly(monkeypatch):
    """A TPU reads the published table by its exact device_kind; one the
    table does not know is an error, never a default."""
    import types

    import jax

    def fake(kind):
        return lambda *a: [types.SimpleNamespace(platform="tpu", device_kind=kind)]

    monkeypatch.setattr(jax, "devices", fake("TPU v5 lite"))
    p = device_peak()
    assert p == {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                 "device": "TPU v5 lite", "source": "table"}
    for unknown in ("TPU v5", "TPU v9 lite", "tpu v5 lite"):
        monkeypatch.setattr(jax, "devices", fake(unknown))
        with pytest.raises(KeyError, match="no published peaks"):
            device_peak()


# ------------------------------------------------------- dense conservation


def test_dense_conservation_exact(tiny_batch_engine):
    b = ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                          max_new_tokens=MAXTOK)
    assert b.costs is not None, "COST_ENABLE defaults on"
    prompts = [f"search for item {i} and sort by price" for i in range(5)]
    res = b.generate_many(prompts)
    assert all(r.error is None for r in res)
    _assert_conserved(b, res)
    t = b.costs.totals
    assert t["prefill_flops"] > 0 and t["decode_flops"] > 0
    assert t["decode_bytes"] > 0 and t["kv_block_us"] > 0
    assert t["prefill_cached_flops"] == 0  # dense engine, no prefix cache
    # the meter reconciled measured walls into live gauges + counters
    snap = get_metrics().snapshot()
    assert snap["gauges"]["engine.mfu"] > 0
    assert snap["gauges"]["engine.mbu"] > 0
    assert snap["gauges"]["engine.mfu_prefill"] > 0
    assert snap["counters"]["cost.decode_flops"] > 0
    assert snap["counters"]["cost.decode_bytes"] > 0
    assert b.costs.engine["chunks"] > 0
    assert b.costs.engine["weights_stream_bytes"] > 0
    assert get_metrics().collisions() == []


def test_cost_lanes_token_identity_and_quiet_sentinel(tiny_batch_engine,
                                                      monkeypatch):
    from tpu_voice_agent.utils.compilewatch import get_compile_watcher

    prompts = ["dim the bedroom lights", "what time is it"]
    on = ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                           max_new_tokens=MAXTOK).generate_many(prompts)
    monkeypatch.setenv("COST_ENABLE", "0")
    b_off = ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                              max_new_tokens=MAXTOK)
    assert b_off.costs is None
    off = b_off.generate_many(prompts)
    monkeypatch.delenv("COST_ENABLE")
    assert [r.token_ids for r in on] == [r.token_ids for r in off]
    assert all(r.cost is not None for r in on)
    assert all(r.cost is None for r in off)  # off = no ledgers at all
    # zero recompiles past the fence with the lanes ON (host arithmetic
    # only — the cost plane must never perturb the jitted decode path)
    w = get_compile_watcher()
    w.arm_fence("cost lanes warmed")
    before = w.state()["post_fence_compiles"]
    again = ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                              max_new_tokens=MAXTOK).generate_many(prompts)
    assert [r.token_ids for r in again] == [r.token_ids for r in on]
    assert w.state()["post_fence_compiles"] == before


# ------------------------------------------------------- paged mixed batch


@pytest.mark.parametrize("tier", [None, "int8", "int4"])
def test_paged_mixed_batch_conservation(tier):
    """The acceptance drill: ONE meter over a mixed workload — radix warm
    hits, a chaos-poisoned row, a mid-decode
    cancellation — reconciles exactly, errored rows still billing the
    work they spent before eviction."""
    eng = PagedDecodeEngine(
        preset="test-tiny", max_len=2048, batch_slots=2,
        prefill_buckets=BUCKETS, radix_enable=True, kv_quant=tier or "off")
    install_prompt_prefix(eng)
    b = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=MAXTOK)
    assert b.costs is not None
    tok = eng.tokenizer
    P = len(eng.prefix_ids)
    seen = []  # every result this meter's batcher produced (generate_many
    # POPS results out of batcher.results — collect as they return)

    # two session turns: turn 2 admits warm off the radix chain
    st = SessionTranscripts(tok)
    turn_res = []
    for text in ("search for wireless headphones", "open the second result"):
        prompt = st.prompt_for("sess", text, {})
        ids = (tok.encode(prompt, bos=True) if isinstance(prompt, str)
               else list(prompt))
        r = b.generate_many([ids])[0]
        assert r.error is None, r.error
        turn_res.append(r)
        seen.append(r)
        st.record("sess", ids, r.token_ids)
    assert turn_res[0].cached_tokens == P
    assert turn_res[1].cached_tokens > P  # radix warm hit
    # the warm turn's avoided work is priced, not dropped
    assert turn_res[1].cost["prefill_cached_flops"] > \
        turn_res[0].cost["prefill_cached_flops"] > 0

    # a poisoned row: 2nd admission NaN-fenced mid-decode, evicted alone
    chaos.configure("nan_logits@2")
    try:
        pois = b.generate_many([render_prompt("scroll down", {}),
                                render_prompt("go back", {})])
    finally:
        chaos.reset()
    seen += pois
    assert pois[1].error is not None and \
        pois[1].error.startswith("poisoned: non-finite"), pois[1].error
    assert pois[0].error is None
    # the evicted row rode out with the cost it spent before the fence
    assert pois[1].cost is not None
    assert pois[1].cost["kv_block_us"] > 0

    # a mid-decode cancellation: evicts at the next chunk boundary
    rid = b.submit(render_prompt("search for mechanical keyboards", {}))
    b.step()
    assert b.cancel(rid, "client gone")
    b.run_until_done()
    cancelled = b.results[rid]
    seen.append(cancelled)
    assert cancelled.error is not None and "cancel" in cancelled.error
    assert cancelled.cost is not None
    assert cancelled.cost["kv_block_us"] > 0

    # EXACT reconciliation over every request this meter ever saw
    _assert_conserved(b, seen)
    t = b.costs.totals
    # paged rows hold real block-time (owned + shared x chunk walls)
    assert t["kv_block_us"] > 0


# ------------------------------------------------------------- attribution


def test_session_cost_ledger_lru_and_top():
    led = SessionCostLedger(cap=2)
    led.fold(None, None)  # no cost -> no entry
    assert len(led) == 0
    cost_a = dict(zero_ledger(), prefill_flops=100, decode_flops=50)
    cost_b = dict(zero_ledger(), prefill_flops=10, decode_flops=5)
    led.fold("a", cost_a)
    led.fold("a", cost_a)  # accumulates, same session
    led.fold("b", cost_b)
    top = led.top()
    assert top[0]["session"] == "a"
    assert top[0]["prefill_flops"] == 200 and top[0]["utterances"] == 2
    assert top[0]["last_s"] <= time.time() + 1
    led.fold(None, cost_b)  # stateless bucket
    assert len(led) == 2  # cap=2: oldest ("a") evicted
    sessions = {e["session"] for e in led.top(8)}
    assert sessions == {"b", "_stateless"}
    assert led.top(1) and len(led.top(1)) == 1


def test_brain_debug_costs_endpoint(tiny_engine):
    # tiny_engine, not tiny_batch_engine: the rendered brain prompt is
    # ~900 tokens and needs the 1024 prefill bucket
    from tests.http_helper import AppServer
    from tpu_voice_agent.services.brain import BatchedEngineParser, build_app

    from tpu_voice_agent.services.brain import ParserError

    parser = BatchedEngineParser(tiny_engine, chunk_steps=8,
                                 max_new_tokens=300)
    try:
        for text in ("turn on the lights", "turn off the lights"):
            try:
                parser.parse(text, {}, session_id="s1")
            except ParserError:
                pass  # random-weight truncation raises AFTER the cost
                # fold — attribution covers errored requests by contract
        with AppServer(build_app(parser)) as srv:
            with urllib.request.urlopen(srv.url + "/debug/costs?top=4",
                                        timeout=10) as r:
                body = json.loads(r.read().decode())
    finally:
        parser.close()
    assert body["service"] == "brain" and body["enabled"]
    assert body["totals"]["decode_flops"] > 0
    assert set(LEDGER_KEYS) <= set(body["totals"])
    assert body["engine"]["chunks"] > 0
    assert "mfu" in body and "mbu" in body and body["peak"]["flops_per_s"] > 0
    assert body["model"]["token_flops"] > 0
    assert body["sessions"] >= 1
    top = body["top_sessions"]
    assert top and top[0]["session"] == "s1" and top[0]["utterances"] == 2


def test_voice_debug_costs_carries_stt_share():
    from tests.http_helper import AppServer
    from tpu_voice_agent.serve.stt import NullSTT
    from tpu_voice_agent.services.voice import VoiceConfig
    from tpu_voice_agent.services.voice import build_app as build_voice
    from tpu_voice_agent.utils.costmodel import (
        register_stt_engine,
        stt_cost_summary,
    )

    class _FakeSTT:
        cost_totals = {"encoder_flops": 1000, "decoder_flops": 200,
                       "encoded_frames": 300, "decoded_tokens": 12}

    fake = _FakeSTT()  # keep a strong ref: the registry is weak
    register_stt_engine(fake)
    s = stt_cost_summary()
    assert s is not None and s["encoder_flops"] >= 1000
    cfg = VoiceConfig(brain_url="http://127.0.0.1:1",
                      executor_url="http://127.0.0.1:1",
                      stt_factory=lambda: NullSTT())
    with AppServer(build_voice(cfg)) as voice:
        with urllib.request.urlopen(voice.url + "/debug/costs",
                                    timeout=10) as r:
            body = json.loads(r.read().decode())
    assert body["service"] == "voice" and body["enabled"]
    assert body["stt"]["encoder_flops"] >= 1000
    assert body["stt"]["engines"] >= 1


def test_flight_dump_carries_cost_snapshot(tiny_batch_engine):
    """The incident autopsy must carry the spend picture: a meter fed by
    a real run lands in the frozen flight dump under ``costs``."""
    from tpu_voice_agent.utils import get_flight_recorder

    b = ContinuousBatcher(tiny_batch_engine, chunk_steps=8,
                          max_new_tokens=MAXTOK)
    b.generate_many(["search for usb hubs"])
    rec = get_flight_recorder()
    rec.rearm()
    rec.trigger("test", "cost snapshot drill")
    dump = rec.frozen_dump()
    assert dump is not None
    costs = dump.get("costs")
    assert costs is not None and "llm" in costs
    assert costs["llm"]["totals"]["decode_flops"] > 0
    rec.rearm()
