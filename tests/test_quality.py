"""Quality observatory (ISSUE 15): differential token-identity of the
confidence lanes per plane, zero post-fence recompiles with the lanes on,
the quality-SLO floor/freeze contract, the golden-replay canary's
admission gating, STT confidence + the stt_garble heuristic, and the
intent_downgrade latch.

Fast tier on purpose: "enabling quality signals changes no generated
token on any plane" is the acceptance bar of the whole observatory and
must gate every tier-1 run.
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from tpu_voice_agent.serve.engine import DecodeEngine
from tpu_voice_agent.serve.paged import PagedDecodeEngine
from tpu_voice_agent.serve.scheduler import ContinuousBatcher
from tpu_voice_agent.utils import chaos as chaos_mod
from tpu_voice_agent.utils.quality import (
    GoldenCanary,
    QualityMonitor,
    conf_summary,
    repetition_score,
)
from tpu_voice_agent.utils.slo import QualityTracker
from tpu_voice_agent.utils.tracing import Metrics, get_flight_recorder

PROMPTS = ["search for usb hubs", "scroll down",
           "sort by price from high to low", "go back"]


def _dense(quality, **kw):
    return DecodeEngine(preset="test-tiny", max_len=256,
                        prefill_buckets=(64, 128, 256), batch_slots=2,
                        quality_lanes=quality, **kw)


def _paged(quality, **kw):
    return PagedDecodeEngine(preset="test-tiny", max_len=256,
                             prefill_buckets=(64, 128, 256), batch_slots=2,
                             block_size=16, pool_blocks=64,
                             quality_lanes=quality, **kw)


def _run(engine):
    return ContinuousBatcher(engine, chunk_steps=8,
                             max_new_tokens=48).generate_many(PROMPTS)


# ------------------------------------------------------------ differentials


def test_token_identity_dense_ff():
    """Dense plane + grammar fast-forward: lanes on vs off, same tokens."""
    on = _run(_dense(True, fast_forward=4))
    off = _run(_dense(False, fast_forward=4))
    assert [r.token_ids for r in on] == [r.token_ids for r in off]
    for r in on:
        assert r.error is None
        assert r.quality is not None and r.quality["decisions"] > 0
        assert r.prompt_tokens > 0
    for r in off:
        assert r.quality is None  # lanes off: no vector, not a zeroed one


def test_token_identity_paged_radix():
    """Paged+radix plane: lanes on vs off, same tokens, vector present."""
    on = _run(_paged(True, radix_enable=True, fast_forward=4))
    off = _run(_paged(False, radix_enable=True, fast_forward=4))
    assert [r.token_ids for r in on] == [r.token_ids for r in off]
    assert all(r.quality is not None for r in on)


def test_zero_postfence_recompiles_with_lanes_on():
    """The instrumented loops must not thrash the jit cache: after warmup,
    arming the sentinel fence and decoding again compiles NOTHING."""
    from tpu_voice_agent.utils.compilewatch import get_compile_watcher

    eng = _dense(True, fast_forward=4)
    batcher = ContinuousBatcher(eng, chunk_steps=8, max_new_tokens=48)
    batcher.generate_many(PROMPTS)  # warmup: every bucket/loop traced
    w = get_compile_watcher()
    before = w.state()["post_fence_compiles"]
    w.arm_fence("test_quality")
    batcher.generate_many(PROMPTS)
    assert w.state()["post_fence_compiles"] == before


# ------------------------------------------------------------ the lanes' top-2


def _sorted_conf(lg, nlegal):
    """The parent's ``_masked_conf``, kept HERE as the reference: the top-2
    by ``jax.lax.top_k``, which the TPU lowers to a sort of the whole row
    (ISSUE 33 took it out of the served program)."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.serve.engine import QUALITY_MARGIN_CAP

    top2 = jax.lax.top_k(lg, 2)[0]
    margin = jnp.where(jnp.isfinite(top2[:, 1]),
                       jnp.minimum(top2[:, 0] - top2[:, 1], QUALITY_MARGIN_CAP),
                       QUALITY_MARGIN_CAP)
    margin = jnp.where(jnp.isfinite(top2[:, 0]), margin, 0.0)
    p = jax.nn.softmax(lg, axis=-1)
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)), 0.0),
                   axis=-1)
    ent = jnp.where(jnp.isfinite(top2[:, 0]), ent, 0.0)
    return margin, ent, nlegal <= 1


def _masked_rows(case: str, V: int):
    """Seeded float32 rows as ``_conf_stats`` hands them on: -inf where the
    token is illegal. Four rows a case, legal shares 0.3 % to 30 %."""
    rng = np.random.default_rng(V * 31 + sum(map(ord, case)))
    B = 4
    raw = (rng.standard_normal((B, V)) * 3).astype(np.float32)
    legal = rng.random((B, V)) < np.array([0.003, 0.01, 0.1, 0.3])[:, None]
    a, b, c = rng.permutation(V)[:3]
    legal[:, [a, b, c]] = True
    top = np.where(legal, raw, -np.inf).max(axis=-1)
    if case == "tie_at_max":  # the maximum twice: margin 0
        raw[:, a] = raw[:, b] = top + 1.0
    elif case == "tie_below_max":  # one maximum, the runner-up twice
        raw[:, a] = top + 2.5
        raw[:, b] = raw[:, c] = top + 1.0
    elif case == "one_legal":  # the grammar forces the token
        legal[:] = False
        legal[:, a] = True
    elif case == "none_legal":  # a dead row
        legal[:] = False
    elif case == "above_cap":  # a maximum further ahead than the cap
        raw[:, a] = top + 100.0
    elif case == "nan_row":  # what the poison gate fences: the lanes stay finite
        raw[:, a] = np.nan
    else:
        assert case == "random"
    return np.where(legal, raw, -np.inf).astype(np.float32), legal.sum(axis=-1).astype(np.int32)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("V", [619, 32000, 200064])
@pytest.mark.parametrize("case", ["random", "tie_at_max", "tie_below_max", "one_legal",
                                  "none_legal", "above_cap", "nan_row"])
def test_conf_lanes_select_what_the_sort_gave(case, V):
    """ISSUE 33: ``_masked_conf`` takes the top-2 by reductions over the row;
    margin, entropy and the forced flag are, bit for bit, those of the
    parent's ``top_k`` — at the tokenizer's, Mistral's and Phi-4's vocabulary."""
    import jax

    from tpu_voice_agent.serve.engine import QUALITY_MARGIN_CAP, _masked_conf

    lg, nlegal = _masked_rows(case, V)
    got = jax.jit(_masked_conf)(lg, nlegal)
    want = jax.jit(_sorted_conf)(lg, nlegal)
    for g, w, name in zip(got, want, ("margin", "entropy", "forced")):
        assert np.array_equal(_bits(g), _bits(w)), (name, np.asarray(g), np.asarray(w))
    margin, ent, forced = (np.asarray(x) for x in got)
    assert np.all(np.isfinite(margin)) and np.all(np.isfinite(ent))
    assert forced.all() == (case in ("one_legal", "none_legal")) and forced.any() == forced.all()
    if case == "random":
        assert np.all((margin > 0) & (margin < QUALITY_MARGIN_CAP)) and np.all(ent > 0)
    elif case == "tie_below_max":
        assert np.all(margin == 1.5)
    elif case in ("one_legal", "above_cap"):
        assert np.all(margin == QUALITY_MARGIN_CAP)
    else:  # a tie at the maximum, a dead row, a poisoned row
        assert np.all(margin == 0.0) and (case == "tie_at_max" or np.all(ent == 0.0))


# ------------------------------------------------------------ quality SLO


def test_quality_tracker_floor_violation_freezes_flight():
    fr = get_flight_recorder()
    fr.rearm()
    try:
        qt = QualityTracker("quality", floors={"golden_accuracy": 0.7},
                            min_samples=3, metrics=Metrics())
        qt.record("golden_accuracy", 1.0, {"text": "warm"})
        assert qt.state() == "ok"
        for i in range(6):
            qt.record("golden_accuracy", 0.0, {"text": f"bad{i}"})
        out = qt.evaluate()
        assert out["state"] == "violated"
        dump = fr.frozen_dump()
        assert dump is not None
        assert dump["reason"] == "slo.quality.violated"
        ev = dump["extra"]["quality"]["golden_accuracy"]
        assert ev["floor"] == 0.7 and ev["mean"] < 0.7
        # the failing utterances' quality vectors ride the dump
        assert any(s.get("text", "").startswith("bad") for s in ev["recent"])
    finally:
        fr.rearm()


def test_quality_tracker_ceiling_and_disarmed_floor():
    qt = QualityTracker("quality", floors={"intent_margin": 0},
                        ceilings={"stt_repetition": 0.9},
                        min_samples=2, metrics=Metrics())
    for _ in range(4):
        qt.record("intent_margin", 0.0)  # floor 0 = disarmed
        qt.record("stt_repetition", 1.0)
    out = qt.evaluate()
    assert out["state"] == "violated"
    assert all("repetition" in r for r in out["reasons"])


# ------------------------------------------------------ monitor + canary


def test_monitor_windows_and_gauges():
    m = Metrics()
    qm = QualityMonitor("test", metrics=m,
                        tracker=QualityTracker(metrics=m))
    qm.record_stt(-0.5, -2.0, 0.1, text="hi", logp_first=-0.3)
    qm.record_intent(margin=3.0, entropy=0.5, forced_frac=0.25, text="hi")
    qm.record_exec("click", True)
    qm.record_exec("click", False)
    qm.record_golden(True, 1.0, text="case")
    g = m.gauges()
    assert g["stt.confidence_mean"] == pytest.approx(-0.5)
    assert g["quality.intent_margin"] == pytest.approx(3.0)
    assert g["quality.exec_success_rate"] == pytest.approx(0.5)
    assert g["quality.golden_accuracy"] == pytest.approx(1.0)
    st = qm.state()
    assert st["exec_by_type"]["click"] == {"ok": 1, "total": 2, "rate": 0.5}
    assert st["counts"]["quality.parses"] == 1


def test_canary_scores_rule_parser_and_respects_busy_gate():
    from tpu_voice_agent.services.brain import RuleBasedParser

    m = Metrics()
    qm = QualityMonitor("test", metrics=m,
                        tracker=QualityTracker(metrics=m))
    parser = RuleBasedParser()
    busy = {"on": True}
    canary = GoldenCanary(lambda t, c: parser.parse(t, c), qm,
                          interval_s=999, slice_n=5,
                          busy_fn=lambda: busy["on"])
    assert canary.run_once() == 0  # admission-gated: busy replica skipped
    assert qm.state()["counts"]["quality.canary_skipped_busy"] == 1
    busy["on"] = False
    scored = 0
    for _ in range(3):
        scored += canary.run_once()
    assert scored == 15
    # the rule parser IS the golden baseline: the live canary must agree
    assert m.gauges()["quality.golden_accuracy"] >= 0.8
    assert qm.state()["counts"]["quality.canary_runs"] == 3


def test_conf_summary_and_repetition():
    assert conf_summary((0.0, float("inf"), 0.0, 0, 0), 0) is None
    s = conf_summary((6.0, 1.5, 3.0, 2, 3), 4)
    assert s == {"margin_mean": 2.0, "margin_min": 1.5, "entropy_mean": 1.0,
                 "forced_frac": 0.5, "decisions": 3}
    assert repetition_score([]) == 0.0
    assert repetition_score([5, 5, 5, 5]) == 0.75
    assert repetition_score([1, 2, 3, 4]) == 0.0


# ------------------------------------------------------------ STT lanes


@pytest.fixture(scope="module")
def stt_engine():
    from tpu_voice_agent.serve.stt import SpeechEngine

    return SpeechEngine(preset="whisper-test", frame_buckets=(50, 100, 200),
                        max_new_tokens=16)


def _tone(freq, dur_s, amp=0.3, sr=16_000):
    t = np.arange(int(dur_s * sr)) / sr
    return (amp * np.sin(2 * np.pi * freq * t)).astype(np.float32)


def test_stt_confidence_lanes(stt_engine):
    res = stt_engine.transcribe(_tone(400, 0.8))
    if res.text:
        assert res.logp_mean is not None and res.logp_mean <= 0.0
        assert res.logp_min is not None and res.logp_min <= res.logp_mean
        assert res.logp_first is not None
        assert 0.0 <= res.repetition < 1.0


def test_stt_garble_chaos_flags_repetition(stt_engine):
    # seeded noise, not the 400 Hz tone: on the tone the random-init Whisper
    # loops ONE token to its budget by itself (repetition 15/16, the ceiling
    # the garble pins), and nothing can rise above that; on this noise it
    # emits five distinct tokens
    audio = (0.3 * np.random.default_rng(0).standard_normal(16_000)).astype(np.float32)
    clean = stt_engine.transcribe(audio)
    if not clean.text:
        pytest.skip("random-init whisper emitted nothing to garble")
    chaos_mod.configure("stt_garble:1", seed=3)
    try:
        garbled = stt_engine.transcribe(audio)
    finally:
        chaos_mod.reset()
    # post-decode corruption: one token looped — latency identical,
    # repetition pinned at its ceiling (what the quality SLO alarms on)
    n = len(stt_engine.tokenizer.encode(clean.text, bos=False))
    if n > 1:
        assert garbled.repetition is not None
        assert garbled.repetition > (clean.repetition or 0.0)
        assert garbled.text != clean.text


# ----------------------------------------------------- intent_downgrade


def test_intent_downgrade_latches_brain_replica():
    from tests.http_helper import AppServer
    from tpu_voice_agent.services.brain import RuleBasedParser, build_app

    chaos_mod.configure("intent_downgrade@1", seed=0)
    try:
        with AppServer(build_app(RuleBasedParser())) as srv:
            def parse(text):
                req = urllib.request.Request(
                    srv.url + "/parse",
                    data=json.dumps({"text": text, "context": {}}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    return json.loads(r.read().decode())

            first = parse("scroll down")
            second = parse("scroll down")
            # the latch: BOTH parses answer the degraded unknown plan —
            # fast, 200, wrong (the fault class only quality signals see)
            assert [i["type"] for i in first["intents"]] == ["unknown"]
            assert [i["type"] for i in second["intents"]] == ["unknown"]
            q = json.loads(urllib.request.urlopen(
                srv.url + "/debug/quality", timeout=10).read().decode())
            assert q["counts"]["quality.intent_downgrades"] >= 2
            assert q["windows"]["degraded"]["mean"] == 1.0
    finally:
        chaos_mod.reset()


def test_brain_parse_reports_quality_headers(distilled_intent):
    """An engine-backed /parse answers with the confidence headers the
    voice service folds into its gauges (x-prompt-tokens powers the
    prefill-remaining-at-endpoint measurement). The engine carries the
    in-tree DISTILLED intent checkpoint (what ``BRAIN_BACKEND=distilled``
    serves): a /parse is a 200 only if its plan reaches EOS, which random
    weights never do."""
    from tests.http_helper import AppServer
    from tpu_voice_agent.services.brain import build_app
    from tpu_voice_agent.train import distill

    with AppServer(build_app(distill.intent_engine_from(*distilled_intent))) as srv:
        req = urllib.request.Request(
            srv.url + "/parse",
            data=json.dumps({"text": "scroll down", "context": {}}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            assert r.status == 200
            assert int(float(r.headers["x-prompt-tokens"])) > 0
            assert float(r.headers["x-intent-margin"]) >= 0.0
