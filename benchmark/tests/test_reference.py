"""The plain references against the program's own forward passes at test
widths, float32 on the CPU: written independently from the published
equations, they must give the same logits. Each is reached as the harness
reaches it: by the name a configuration gives, through the protocol's
``logits`` with the configuration's own keys (README.md "What a reference
module owes")."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import manifest as mf
from benchmark.lib import refcheck

CONFIGS = sorted(p.stem for p in (mf.BENCH_DIR / "configs").glob("*.json"))


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < tol


def _far(got, want, least):
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) > least


def test_decoder_reference_matches_llama_forward_in_float32_and_int8():
    from tpu_voice_agent.models.llama import (PRESETS, forward, init_kv_cache, init_params,
                                              quantize_params)

    conf = mf.load_json("benchmark/configs/mistral-7b-v0.1-int8.json")
    ref = mf.load_code("reference", conf["reference"])
    cfg = PRESETS["test-tiny"]
    model = {"num_hidden_layers": cfg.n_layers, "num_attention_heads": cfg.n_heads,
             "num_key_value_heads": cfg.n_kv_heads, "rms_norm_eps": cfg.norm_eps,
             "rope_theta": cfg.rope_theta, "sliding_window": 4096}
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, cfg.vocab_size)
    pos = jnp.arange(48, dtype=jnp.int32)[None]
    sample = {"tokens": [int(t) for t in toks[0]], "rows": 48}
    for tree, tol in ((params, 2e-4), (quantize_params(params), 2e-2)):
        with jax.default_matmul_precision("highest"):
            want, _ = forward(tree, cfg, toks, pos, init_kv_cache(cfg, 1, 64, dtype=jnp.float32))
        _close(ref.logits(tree, model, sample), want[0], tol)
    # the window binds when it is shorter than the context: the program has none
    _far(ref.logits(params, dict(model, sliding_window=8), sample), want[0], 1e-3)
    # and the negative control really is a different model
    _far(ref.logits(params, model, sample, control=True), want[0], 1e-2)
    assert ref.CONTROL == "int4" and ref.TOLERANCE == 0.03 and ref.SAMPLE == "paged_decoder"


def test_whisper_reference_matches_the_program_with_its_gelu_and_padding():
    from tpu_voice_agent.models import whisper as w

    conf = mf.load_json("benchmark/configs/voice-whisper-large-v3-mistral-7b.json")
    ref = mf.load_code("reference", conf["reference"])
    cfg = w.PRESETS["whisper-test"]
    assert cfg.norm_eps == ref.LAYER_NORM_EPS
    model = {"encoder_attention_heads": cfg.n_heads, "decoder_attention_heads": cfg.n_heads}
    params = w.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    mel = jax.random.normal(jax.random.PRNGKey(4), (100, cfg.n_mels), jnp.float32)
    toks = jnp.asarray([1, 7, 9, 4, 30], jnp.int32)
    with jax.default_matmul_precision("highest"):
        enc = w.encoder_forward(params, cfg, mel[None])
        kv = w.compute_cross_kv(params, cfg, enc)
        mask = jnp.arange(enc.shape[1])[None, :] < 40
        want, _ = w.decoder_forward(params, cfg, toks[None], jnp.arange(5)[None],
                                    w.init_self_cache(cfg, 1, dtype=jnp.float32), kv, mask)
    sample = {"mel": mel, "tokens": [1, 7, 9, 4, 30], "n_valid": 40, "first": 0}
    kw = dict(nh=cfg.n_heads, eps=cfg.norm_eps)
    got_enc = ref.encoder(params["encoder"], mel, **kw)
    # erf GELU (published) vs the program's tanh form: small, not zero
    _close(got_enc, enc[0], 2e-2)
    _close(ref.logits(params, model, sample), want[0], 2e-2)
    assert ref.logits(params, model, dict(sample, first=2)).shape[0] == 3
    # the float8 control is another model, and so is the published (1, 1)
    # padding of the second convolution
    _far(ref.logits(params, model, sample, control=True), want[0], 1e-2)
    _far(ref.encoder(params["encoder"], mel, conv2_pad=(1, 1), **kw), enc[0], 5e-2)
    assert ref.CONTROL == "float8" and ref.TOLERANCE == 0.03 and ref.SAMPLE == "speech"


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_references_that_keep_the_protocol(name):
    """In the manifest or held back: the builder and every reference a
    configuration names import, have what their kind owes, and a reference's
    sampler is one the comparison has."""
    conf = mf.load_json(f"benchmark/configs/{name}.json")
    if "decoder_config" in conf:
        conf["decoder"] = mf.load_json(f"benchmark/configs/{conf['decoder_config']}.json")
    assert hasattr(mf.load_code("builders", conf["builder"]), "build")
    refs = mf.references_of(conf)
    assert refs[-1] == conf["reference"] and len(refs) == 1 + ("decoder" in conf)
    for r in refs:
        mod = mf.load_code("reference", r)
        assert mod.SAMPLE in refcheck.SAMPLERS and 0 < mod.TOLERANCE < 1 and mod.CONTROL
        assert callable(mod.logits)


def test_a_module_without_the_protocol_is_refused_by_name(tmp_path, monkeypatch):
    """``load_code`` is the one door: a reference that lacks a part of the
    protocol, or a name with no file, never reaches a build."""
    import benchmark.reference as pkg

    (tmp_path / "forward_only.py").write_text("def forward(*a, **k):\n    return None\n")
    monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [str(tmp_path)])
    with pytest.raises(AttributeError, match="lacks .*TOLERANCE"):
        mf.load_code("reference", "forward_only")
    with pytest.raises(ImportError):
        mf.load_code("reference", "no_such_reference")
    with pytest.raises(ValueError):
        mf.load_code("reference", "../decoder")
