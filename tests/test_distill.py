"""In-tree tiny-checkpoint training (round-3 VERDICT next #2): the
train -> checkpoint -> constrained-serve loop produces REAL quality numbers
with zero external weights.

Full-budget training lives in ``python -m tpu_voice_agent.train.make_tiny_
ckpts`` (~10 min CPU) and is scored by benches/bench_quality.py; these tests
run scaled-down budgets that still prove each link of the chain.
"""

import os

import numpy as np
import pytest

from tpu_voice_agent.evals.golden import GoldenCase, score_parser
from tpu_voice_agent.evals.wer import wer
from tpu_voice_agent.train import distill


def test_synth_corpus_disjoint_from_golden():
    """Held-out means held out: no golden utterance may appear in training."""
    from tpu_voice_agent.evals.golden import GOLDEN_INTENT_CASES

    texts = {t for t, _, _ in distill.synth_intent_corpus(800, seed=3)}
    assert not texts & {c.text for c in GOLDEN_INTENT_CASES}


def test_corpus_labels_are_grammar_valid():
    """Every teacher label must be accepted by the decode grammar — a label
    the FSM cannot emit would train mass onto unreachable sequences."""
    from tpu_voice_agent.grammar.intent_grammar import build_intent_fsm

    tokenizer, fsm = build_intent_fsm()
    for text, ctx, resp_json in distill.synth_intent_corpus(60, seed=5):
        ids = tokenizer.encode(resp_json)
        assert fsm.walk(ids) >= 0, f"label left the grammar: {resp_json[:80]}"


@pytest.fixture(scope="module")
def trained_intent():
    """ONE scaled-down training run shared by the serve + ckpt tests (a
    1-core box pays ~0.35 s/step; two separate trainings doubled the
    module's wall-clock for no extra coverage)."""
    # stream=False: the fixture's job is serve/ckpt mechanics, and epoch
    # mode over a small fixed corpus memorizes quickly (reliable EOS)
    # where the same steps of streaming fresh data still truncate. The
    # round-5 corpus is richer (longer phrases, dialogs), so the fixture
    # runs more epochs over fewer examples than the old 260x1000.
    return distill.train_intent_model(steps=500, seq_len=320, batch=16,
                                      corpus_n=500, dialogs_n=40,
                                      stream=False)


def test_dialogs_disjoint_from_golden():
    """No golden utterance — single-turn case OR dialog turn — may appear
    in the training dialogs (a golden dialog's search phrase showing up in
    training would hand the copy task its answer)."""
    from tpu_voice_agent.evals.golden import GOLDEN_DIALOGS, GOLDEN_INTENT_CASES

    golden = {c.text for c in GOLDEN_INTENT_CASES}
    for d in GOLDEN_DIALOGS:
        golden.update(d.turns)
    for turns in distill.synth_intent_dialogs(150, seed=4):
        assert not {t for t, _, _ in turns} & golden


def test_dialog_batches_put_eos_target_at_mid_plan_ends():
    """The position AT a mid-dialog plan's last token must target EOS with
    loss on (that is how a served turn stops decoding) while the
    teacher-forced TRANSCRIPT continues with the next <|user|> segment —
    planner transcripts never contain EOS (serve.planner.plan_many)."""
    from tpu_voice_agent.grammar.intent_grammar import build_intent_fsm

    tok, _ = build_intent_fsm()
    dlg = distill.synth_intent_dialogs(1, seed=2)[0]
    assert len(dlg) >= 2
    toks, tgts, masks = distill.build_intent_batches(
        [], tok, 512, 1, dialogs=[dlg])
    toks, tgts, masks = toks[0, 0], tgts[0, 0], masks[0, 0]
    eos_positions = [i for i in range(len(toks))
                     if tgts[i] == tok.eos_id and masks[i] > 0]
    # one termination target per turn
    assert len(eos_positions) == len(dlg), eos_positions
    for p in eos_positions[:-1]:  # mid-dialog ends
        # the transcript itself continues (teacher-forced input is NOT eos)
        assert toks[p + 1] != tok.eos_id
        # and the next literal tokens open the next user turn
        tail = tok.decode([int(t) for t in toks[p + 1: p + 6]])
        assert tail.startswith("\n<|user|>"), repr(tail)
    # the final plan terminates in-transcript
    assert toks[eos_positions[-1] + 1] == tok.eos_id


@pytest.mark.slow
def test_intent_distillation_learns_and_serves(trained_intent):
    """A scaled-down training run must (a) collapse the loss and (b) yield
    a parser that, through the REAL grammar-constrained engine with the
    short distilled prompt, classifies utterances far above chance."""
    cfg, params, stats = trained_intent
    assert stats["final_loss"] < stats["first_loss"] * 0.1, stats
    parser = distill.intent_engine_from(cfg, params)
    # probe with held-out utterances from the easy families (chance over
    # the 19-type enum would be ~5% per intent; demand well above)
    cases = [
        GoldenCase("scroll down", ("scroll",)),
        GoldenCase("go back", ("back",)),
        GoldenCase("take a screenshot of this page", ("screenshot",)),
        GoldenCase("cancel that", ("cancel",)),
        GoldenCase("summarize this page", ("summarize",)),
        GoldenCase("open the third result", ("click",)),
    ]
    scores = score_parser(parser, cases)
    assert scores["errors"] == 0
    assert scores["type_accuracy"] >= 0.5, scores


@pytest.mark.slow
def test_distilled_weights_serve_through_planner_sessions(trained_intent):
    """The planner-distilled backend shape: distilled cfg/params behind the
    session-keyed planner with the SHORT prompt, a 2-turn session feeding
    the second turn only the transcript (context={}). Scaled-down training
    -> assert structure (valid plans, session reuse), not semantics."""
    from tpu_voice_agent.parallel.ring import sp_mesh
    from tpu_voice_agent.serve import LongSessionPlanner
    from tpu_voice_agent.services.brain import PlannerParser

    cfg, params, _ = trained_intent
    planner = LongSessionPlanner(cfg=cfg, mesh=sp_mesh(1),
                                 ctx_buckets=(512, 1024))
    planner.load_params(params)
    parser = PlannerParser(planner, render=distill.distilled_prompt)
    r1 = parser.parse("search for red shoes", {}, session_id="t")
    r2 = parser.parse("open the second result", {}, session_id="t")
    assert r1.intents and r2.intents  # grammar-valid plans both turns
    assert parser.session_count() == 1  # one session carried both turns


@pytest.mark.slow
def test_whisper_overfit_transcribes_and_roundtrips_ckpt(tmp_path):
    """Overfitting the acoustic-font pairs must push WER far below 1.0 (a
    random decoder scores ~1.0), and the checkpoint must restore through
    orbax into an engine that transcribes identically."""
    texts = distill.WHISPER_EVAL_TEXTS[:4]
    cfg, params, stats = distill.train_whisper_overfit(texts=texts, steps=220)
    assert stats["final_loss"] < stats["first_loss"] * 0.05, stats
    eng = distill.whisper_engine_from(cfg, params)
    errs = [wer(t, eng.transcribe(distill.render_speech(t)).text) for t in texts]
    assert float(np.mean(errs)) < 0.5, list(zip(texts, errs))

    from tpu_voice_agent.models.whisper import WhisperConfig

    distill.save_ckpt(str(tmp_path), distill.WHISPER_CKPT, cfg, params, stats)
    cfg2, params2 = distill.load_ckpt(str(tmp_path), distill.WHISPER_CKPT,
                                      WhisperConfig)
    assert cfg2 == cfg
    eng2 = distill.whisper_engine_from(cfg2, params2)
    for t in texts:
        a = eng.transcribe(distill.render_speech(t)).text
        b = eng2.transcribe(distill.render_speech(t)).text
        assert a == b


@pytest.mark.slow
def test_intent_ckpt_roundtrip_preserves_parses(tmp_path, trained_intent):
    """save_ckpt/load_ckpt through orbax must reproduce the parser's output
    token-for-token (the serve path the bench harness uses)."""
    cfg, params, stats = trained_intent
    from tpu_voice_agent.models.llama import LlamaConfig

    distill.save_ckpt(str(tmp_path), distill.INTENT_CKPT, cfg, params, stats)
    cfg2, params2 = distill.load_ckpt(str(tmp_path), distill.INTENT_CKPT,
                                      LlamaConfig)
    assert cfg2 == cfg
    p1 = distill.intent_engine_from(cfg, params)
    p2 = distill.intent_engine_from(cfg2, params2)
    for text in ("scroll down please", "find quiet fans"):
        r1 = p1.parse(text, {})
        r2 = p2.parse(text, {})
        assert r1.model_dump() == r2.model_dump()


def test_committed_ckpt_restores_onto_a_host_without_its_saving_device(tmp_path):
    """The committed checkpoints were saved on a CPU and record that device
    by name; orbax's bare restore() rebuilds the recorded sharding and fails
    on a host whose devices carry other names (the TPU). ``restore_params``
    places onto the restoring process's own default device instead."""
    import json
    import shutil

    import jax

    from tpu_voice_agent.ckpt.orbax_io import restore_params

    src = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "checkpoints", distill.WHISPER_CKPT)
    dst = tmp_path / "ckpt"
    shutil.copytree(src, dst)
    sharding_file = dst / "params" / "_sharding"
    recorded = json.loads(sharding_file.read_text())
    foreign = json.dumps({"sharding_type": "SingleDeviceSharding",
                          "device_str": "TPU_0(process=0,(0,0,0,0))"})
    sharding_file.write_text(json.dumps({k: foreign for k in recorded}))

    params = restore_params(str(dst))
    leaves = jax.tree.leaves(params)
    assert leaves and all(
        x.sharding.device_set == {jax.devices()[0]} for x in leaves)
    want = jax.tree.leaves(restore_params(src))
    assert all(np.array_equal(a, b) for a, b in zip(leaves, want))
