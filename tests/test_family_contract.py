"""The serving contract of a model family (ISSUE 46): what ``models.family``'s
record states is what the engine builds, compiles, counts and refuses — held
for the six configurations at test size, so that the next family is a record,
not an edit of the engine.

(The engines are ``tests/test_admit_group.py``'s models at a smaller width;
most are built without weights.)"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_admit_group import _model as _older_model
from tpu_voice_agent.models import dots3, llama, mla, olmo_hybrid, sambay
from tpu_voice_agent.models.family import FFN, family
from tpu_voice_agent.serve import ContinuousBatcher, DecodeEngine, PagedDecodeEngine

FEATURES = ("kv_quant", "radix", "mesh", "handoff", "chunked_prefill", "dense_cache",
            "ffn_pack")  # what a serving plane may ask of a family: a record refuses some, by name
MODELS = ("dense", "routed", "hybrid", "share", "latent", "sparse")
# the families that came behind ISSUE 46's six (``_model``), and what each one's refusals name
LATER = {"gdn": "(delta-rule|OlmoHybridConfig)", "looped": "pass"}
FAMILY = {"dense": "plain", "routed": "plain", "hybrid": "hybrid", "share": "plain",
          "latent": "latent", "sparse": "sparse"}
SLOTS, BS, BLOCKS = 8, 128, 24


def _model(model: str) -> dict:
    """``tests/test_admit_group.py``'s six, and the family that came behind them
    (a delta-rule state: ``models/olmo_hybrid.py``, its own test preset)."""
    if model not in ("gdn", "looped"):
        return _older_model(model)
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer

    if model == "looped":  # layers that run twice a token, under a sandwich norm and an exit gate
        return dict(cfg=llama.LlamaConfig(dim=128, n_layers=2, n_heads=4, n_kv_heads=4, ffn_dim=256,
                                          ut_steps=2, sandwich_norm=True), tokenizer=default_tokenizer())
    return dict(cfg=olmo_hybrid.PRESETS["olmo-hybrid-test"], tokenizer=default_tokenizer())


def _engine(model: str, cls=PagedDecodeEngine, **over):
    paged = dict(block_size=BS, pool_blocks=BLOCKS) if cls is PagedDecodeEngine else {}
    return cls(**{**dict(max_len=256, batch_slots=SLOTS, prefill_buckets=(128,), fast_forward=8,
                         quant=None, init_weights=False), **paged, **_model(model), **over})


def _cfg(model: str):
    kw = _model(model)
    return kw.get("cfg") or llama.PRESETS[kw["preset"]]


@pytest.mark.parametrize("model", MODELS)
def test_the_record_names_its_family_and_its_module(model):
    fam = family(_cfg(model))
    assert fam.name == FAMILY[model]
    assert fam.module is {"plain": llama, "hybrid": sambay, "latent": mla, "sparse": dots3}[fam.name]
    assert set(fam.refuses) <= set(FEATURES) and fam.cache == fam.module.cache_spec(_cfg(model))
    # what the chunk program compiles, by family: the facts the engine hands on unchanged
    eng = _engine(model)
    assert eng.family is family(eng.cfg) and eng._cache_spec is eng.family.cache
    assert (eng.hybrid, eng.latent, eng.sparse) == (
        fam.name == "hybrid", fam.name in ("latent", "sparse"), fam.name == "sparse")
    assert eng.ffn_pack_rows == fam.pack_rows == (0 if fam.name == "hybrid" else 96)
    assert fam.one_head == (model != "dense" and model != "routed")
    assert fam.scratch_prefix == (model not in ("dense", "routed"))
    assert fam.n_real == {"hybrid": "always", "sparse": "admit"}.get(fam.name, "")
    # the callers of a kernel that packs the real positions: the block kernel's
    # (``llama.forward_paged``) and the latent kernel's (``mla.forward_paged``)
    assert fam.block_real == (fam.name in ("plain", "latent"))


@pytest.mark.parametrize("model", MODELS)
def test_the_engine_builds_the_pools_the_record_states(model):
    """Structure, shapes and dtypes of both pools from the spec alone, and the
    table's state column iff the spec says so."""
    eng = _engine(model)
    spec = eng.family.cache
    for side, pool in (("k", eng.k_pool), ("v", eng.v_pool)):
        want = {n: ((p[0], BLOCKS, BS, *p[1:]), jnp.bfloat16) for n, p in spec["planes"][side].items()}
        want.update({n: ((p[0], SLOTS, *p[1:]), dt) for n, (p, dt) in spec["slot_planes"][side].items()})
        if not spec["by_name"]:
            assert not isinstance(pool, dict) and set(want) == {"kv"}
            pool = {"kv": pool}
        assert {n: (a.shape, a.dtype) for n, a in pool.items()} == want
    assert spec["state_column"] == bool(spec["slot_planes"]["k"] or spec["slot_planes"]["v"])
    assert eng.block_tables.shape == (SLOTS, eng.max_blocks + spec["state_column"])
    assert eng._table_row(3, [5, 6]).shape == (eng.max_blocks + spec["state_column"],)
    if spec["state_column"]:
        assert np.asarray(eng.block_tables)[:, -1].tolist() == list(range(SLOTS))
    # the bytes a block holds are the planes': what the pool gauges and the HBM plan read
    assert eng.kv_bytes_per_block == BS * eng.family.token_bytes
    from tpu_voice_agent.utils import hbmledger

    # the plan counts the record's BLOCK planes (since PR 57 a hybrid model's too: the layers
    # that write K/V at the heads they hold, not every layer; its slots' planes not at all:
    # ROADMAP D5, what is left) — a dense decoder's arithmetic where they are K/V by head
    by_head = model not in ("latent", "sparse")
    cfg = eng.cfg
    if model in ("dense", "routed", "share"):
        assert eng.kv_bytes_per_block == 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2 * BS
    assert eng.family.kv_by_head == by_head
    assert hbmledger.engine_hbm_plan(eng)["kv_pool_bytes"] == BLOCKS * eng.kv_bytes_per_block


def _enter(feature: str, model: str):
    """Build or enter ``feature`` with a test-size engine of ``model``: what a
    family that honours it returns, and where one that does not raises."""
    if feature == "kv_quant":
        return _engine(model, kv_quant="int8").kv_quant
    if feature == "radix":
        return _engine(model, radix_enable=True).radix
    if feature == "mesh":
        from tpu_voice_agent.parallel import make_mesh

        return _engine(model, mesh=make_mesh(dp=2, tp=1, devices=jax.devices()[:2])).dp
    if feature == "handoff":
        return _engine(model).gather_chain_kv([1])
    if feature == "chunked_prefill":
        eng = _engine(model)
        return eng.begin_chunked_prefill(eng.tokenizer.encode("go back to the start", bos=True), 0, 16)
    if feature == "dense_cache":
        return _engine(model, cls=DecodeEngine).cache
    assert feature == "ffn_pack"
    return _engine(model).ffn_pack_rows or None


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("model", MODELS + tuple(LATER))
def test_what_the_table_refuses_is_refused_by_type_and_nothing_else_is(model, feature):
    fam = family(_cfg(model))
    if feature not in fam.refuses:
        got = _enter(feature, model)
        assert got is not None  # built, or entered
        # the later two refuse all but ffn_pack: their position-wise regions run packed
        assert model not in LATER or (feature, got) == ("ffn_pack", 96)
        return
    if feature == "chunked_prefill":  # declined, not raised: the caller's one-shot fallback serves it
        assert _enter(feature, model) is None
        return
    if feature == "ffn_pack":  # the engine never enters it; the forward states it is not implemented
        assert _enter(feature, model) is None
        cfg, S = _cfg(model), jax.ShapeDtypeStruct
        with pytest.raises(NotImplementedError, match="ffn_pack: .*no packed branch"):
            llama.forward_paged(None, cfg, S((2, 9), jnp.int32), S((2, 9), jnp.int32), None, None,
                                S((2, 3), jnp.int32), ffn_pack=8)
        return
    with pytest.raises(fam.error, match=f"^{feature}: .*{LATER.get(model, '')}"):
        _enter(feature, model)
    if feature == "dense_cache":  # and the dense forward itself: not implemented there, by the same table
        with pytest.raises(NotImplementedError, match="^dense_cache: .*forward_paged"):
            llama.forward(None, _cfg(model), jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None], None)


def test_the_error_classes_stay_what_callers_catch():
    errors = {family(_cfg(m)).name: family(_cfg(m)).error for m in MODELS}
    assert errors == {"plain": NotImplementedError, "hybrid": sambay.StateNotCarried,
                      "latent": mla.LatentCacheOnly, "sparse": mla.LatentCacheOnly}
    assert issubclass(sambay.StateNotCarried, ValueError) and issubclass(mla.LatentCacheOnly, ValueError)
    # a count the record does not name is refused too, not dropped
    with pytest.raises(ValueError, match="counts"):
        llama.forward_paged(None, _cfg("dense"), None, None, None, None, None, moe_stats=True)


@pytest.fixture(scope="module")
def catalog():
    root = Path(__file__).parents[1]
    sys.path.insert(0, str(root / "tools"))
    import metrics_lint

    rows = metrics_lint.parse_catalog((root / "docs/OBSERVABILITY.md").read_text())
    return lambda name: any(metrics_lint._covers(p, name) for p in rows)


@pytest.mark.parametrize("model", MODELS)
def test_a_chunk_counts_what_the_record_names_in_its_order(model, catalog, monkeypatch):
    """One served chunk: ``counts`` holds the record's names in carry order
    (``ffn`` last from a program that packs), each vector as long as the
    counters it is added to, every counter in the operator's catalog — and the
    batcher's ONE loop adds each to its counter. The forward is looked up on
    the family's MODULE when a program is traced: one rebound there (as
    ``benchmark/tools/sparse_check.py`` plants its faults) is the one that runs."""
    from tpu_voice_agent.utils import get_metrics

    eng = _engine(model, init_weights=True)
    rebound, sound = [], eng.family.module.forward_paged
    if eng.family.module is not llama:
        from tpu_voice_agent.serve import paged

        for traced in (llama.forward_paged, paged.forward_paged_first_tokens, paged.paged_chunk_decode_loop):
            getattr(traced, "__wrapped__", traced).clear_cache()  # the three that trace a forward
        monkeypatch.setattr(eng.family.module, "forward_paged",
                            lambda *a, **kw: rebound.append(kw) or sound(*a, **kw))
    eng.ffn_pack_rows = min(eng.ffn_pack_rows, 8)  # under the compacted width's 2 x 9 positions
    fam = eng.family
    names = [c.name for c in fam.counts] + ["ffn"] * bool(eng.ffn_pack_rows)
    assert [c.keyword for c in fam.counts] == {
        "dense": ["attn_stats", "kv_stats"], "routed": ["moe_stats", "attn_stats", "kv_stats"],
        "hybrid": ["hybrid_stats", "attn_stats", "kv_stats"],
        # (this module's max_len of 256 passes the share model's rehearsal window: it BINDS,
        # and a plain model whose window binds counts what its windowed layers walk)
        "share": ["moe_stats", "attn_stats", "window_stats", "kv_stats"],
        "latent": ["moe_stats", "attn_stats", "latent_stats", "kv_stats"],
        # (its site writes planes by layer kind, not through ``llama.write_rows``: no count of them)
        "sparse": ["moe_stats", "attn_stats", "latent_stats"]}[model]
    if fam.name == "hybrid":
        assert fam.count("hybrid").metrics == sambay.HYBRID_STATS
    assert FFN.metrics == tuple(f"ffn.{n}" for n in llama.FFN_STATS)
    before = dict(get_metrics().counter_state()[0])
    bat = ContinuousBatcher(eng, chunk_steps=2, max_new_tokens=8)
    bat.submit("go back")
    res = bat.step()
    assert list(res.counts) == names
    if eng.family.module is not llama:  # a prefill, a first chunk: every family's common keywords
        assert len(rebound) >= 2 and all(
            {"attn_impl", "write_mask", "trash_idx", "fresh_block", "gather_blocks", "n_real",
             "logit_pos", "ffn_pack"} | {c.keyword for c in fam.counts} == set(kw) for kw in rebound)
    after = get_metrics().counter_state()[0]
    for name, values in res.counts.items():
        metrics = fam.count(name).metrics
        assert values.shape == (len(metrics),) and values.dtype == jnp.int32
        for metric, v in zip(metrics, np.asarray(values)):
            assert catalog(metric), metric
            assert after.get(metric, 0.0) - before.get(metric, 0.0) == float(v), metric


# ---------------------------------------------------------------- a third state-carrying family


def test_the_gdn_record_is_read_like_any_other():
    """The record of a family that came behind ISSUE 46's six (a delta-rule
    matrix state beside K/V): the engine builds, compiles and counts from it
    with no line of its own."""
    from tpu_voice_agent.ops import gated_delta

    cfg = _cfg("gdn")
    fam = family(cfg)
    assert fam.name == "gdn" and fam.module is olmo_hybrid and fam.error is sambay.StateNotCarried
    assert set(fam.refuses) <= set(FEATURES) and fam.cache == olmo_hybrid.cache_spec(cfg)
    assert [c.keyword for c in fam.counts] == ["hybrid_stats", "attn_stats", "kv_stats"]
    assert fam.count("hybrid").metrics == olmo_hybrid.HYBRID_STATS
    assert (fam.n_real, fam.one_head, fam.block_real, fam.pack_rows, fam.scratch_prefix) == (
        "always", True, False, 96, True)
    eng = _engine("gdn")
    spec = eng.family.cache
    assert eng.family is family(eng.cfg) and eng._cache_spec is spec and spec["state_column"]
    assert (eng.hybrid, eng.latent, eng.sparse) == (False, False, False) and eng.ffn_pack_rows == 96
    for side, pool in (("k", eng.k_pool), ("v", eng.v_pool)):
        want = {n: ((p[0], BLOCKS, BS, *p[1:]), jnp.bfloat16) for n, p in spec["planes"][side].items()}
        want.update({n: ((p[0], SLOTS, *p[1:]), dt) for n, (p, dt) in spec["slot_planes"][side].items()})
        assert {n: (a.shape, a.dtype) for n, a in pool.items()} == want
    c = eng.cfg
    assert eng.v_pool["gdn"].shape[2:] == gated_delta.plane_shape(c.gdn_heads, c.gdn_key_dim, c.gdn_value_dim)
    assert eng.block_tables.shape == (SLOTS, eng.max_blocks + 1)
    assert np.asarray(eng.block_tables)[:, -1].tolist() == list(range(SLOTS))
    assert eng.kv_bytes_per_block == BS * fam.token_bytes


def test_a_gdn_chunk_counts_what_the_record_names_in_its_order(catalog):
    from tpu_voice_agent.utils import get_metrics

    eng = _engine("gdn", init_weights=True)
    eng.ffn_pack_rows = 8  # under the compacted width's 2 x 9 positions
    before = dict(get_metrics().counter_state()[0])
    bat = ContinuousBatcher(eng, chunk_steps=2, max_new_tokens=8)
    bat.submit("go back")
    res = bat.step()
    assert list(res.counts) == ["hybrid", "attn", "kv", "ffn"]
    after = get_metrics().counter_state()[0]
    for count in (*eng.family.counts, FFN):
        assert res.counts[count.name].shape == (len(count.metrics),)
        for name, n in zip(count.metrics, np.asarray(res.counts[count.name]).tolist()):
            assert catalog(name), f"{name} is not in docs/OBSERVABILITY.md"
            assert after.get(name, 0.0) - before.get(name, 0.0) == n


# ---------------------------------------------------------------- layers that run several times a token


def test_the_looped_record_is_the_plain_familys_with_a_plane_for_every_pass():
    """A ``LlamaConfig`` whose layers run ``ut_steps`` times (ISSUE 57): no family of
    its own — the plain record with ``ut_steps`` x ``n_layers`` planes, one more count
    and its own refusals; the engine sizes pool, bytes and plan from the record."""
    from tpu_voice_agent.utils import hbmledger

    cfg = _cfg("looped")
    fam = family(cfg)
    assert fam.name == "plain" and fam.module is llama and fam.error is NotImplementedError
    assert set(fam.refuses) == set(FEATURES) - {"ffn_pack"} and fam.cache == llama.cache_spec(cfg)
    assert [c.keyword for c in fam.counts] == ["attn_stats", "loop_stats", "kv_stats"]
    assert fam.count("loop").metrics == tuple(f"loop.{n}" for n in llama.LOOP_STATS)
    assert (fam.n_real, fam.one_head, fam.block_real, fam.pack_rows, fam.scratch_prefix) == (
        "", True, True, 96, True)
    eng = _engine("looped")
    L = cfg.ut_steps * cfg.n_layers
    assert eng.family is family(eng.cfg) and fam.cache["planes"]["k"]["kv"][0] == L == 4
    assert eng.k_pool.shape == eng.v_pool.shape == (L, BLOCKS, BS, cfg.n_kv_heads, cfg.head_dim)
    assert eng.kv_bytes_per_block == BS * fam.token_bytes == BS * 2 * 2 * L * cfg.n_kv_heads * cfg.head_dim
    assert hbmledger.engine_hbm_plan(eng)["kv_pool_bytes"] == BLOCKS * eng.kv_bytes_per_block
    # with the fields at their defaults the record is the one it was
    plain = family(_cfg("dense"))
    assert [c.keyword for c in plain.counts] == ["attn_stats", "kv_stats"] and not plain.refuses


def test_a_looped_chunk_counts_what_the_record_names_in_its_order(catalog):
    from tpu_voice_agent.utils import get_metrics

    eng = _engine("looped", init_weights=True)
    eng.ffn_pack_rows = 8  # under the compacted width's 2 x 9 positions
    before = dict(get_metrics().counter_state()[0])
    bat = ContinuousBatcher(eng, chunk_steps=2, max_new_tokens=8)
    bat.submit("go back")
    res = bat.step()
    assert list(res.counts) == ["attn", "loop", "kv", "ffn"]
    after = get_metrics().counter_state()[0]
    for count in (*eng.family.counts, FFN):
        assert res.counts[count.name].shape == (len(count.metrics),)
        for name, n in zip(count.metrics, np.asarray(res.counts[count.name]).tolist()):
            assert catalog(name), f"{name} is not in docs/OBSERVABILITY.md"
            assert after.get(name, 0.0) - before.get(name, 0.0) == n
    assert np.asarray(res.counts["loop"])[0] == eng.cfg.ut_steps * int(res.fwds)
