"""The plain references against the program's own forward passes at test
widths, float32 on the CPU: written independently from the published
equations, they must give the same logits."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import decoder as ref_dec
from benchmark.reference import whisper as ref_wh


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < tol


def test_decoder_reference_matches_llama_forward_in_float32_and_int8():
    from tpu_voice_agent.models.llama import (PRESETS, forward, init_kv_cache, init_params,
                                              quantize_params)

    cfg = PRESETS["test-tiny"]
    params = init_params(cfg, jax.random.PRNGKey(1), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, cfg.vocab_size)
    pos = jnp.arange(48, dtype=jnp.int32)[None]
    kw = dict(n_layers=cfg.n_layers, nq=cfg.n_heads, nkv=cfg.n_kv_heads, eps=cfg.norm_eps,
              theta=cfg.rope_theta, window=4096, last=48)
    for tree, tol in ((params, 2e-4), (quantize_params(params), 2e-2)):
        with jax.default_matmul_precision("highest"):
            want, _ = forward(tree, cfg, toks, pos, init_kv_cache(cfg, 1, 64, dtype=jnp.float32))
        _close(ref_dec.forward(tree, toks[0], **kw), want[0], tol)
    # the window binds when it is shorter than the context: the program has none
    short = ref_dec.forward(params, toks[0], **dict(kw, window=8))
    assert np.max(np.abs(np.asarray(short) - np.asarray(want[0]))) > 1e-3
    # and the negative control really is a different model
    assert np.max(np.abs(np.asarray(ref_dec.forward(params, toks[0], fake_bits=4, **kw))
                         - np.asarray(want[0]))) > 1e-2


def test_whisper_reference_matches_the_program_with_its_gelu_and_padding():
    from tpu_voice_agent.models import whisper as w

    cfg = w.PRESETS["whisper-test"]
    params = w.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    mel = jax.random.normal(jax.random.PRNGKey(4), (100, cfg.n_mels), jnp.float32)
    toks = jnp.asarray([1, 7, 9, 4, 30], jnp.int32)
    with jax.default_matmul_precision("highest"):
        enc = w.encoder_forward(params, cfg, mel[None])
        kv = w.compute_cross_kv(params, cfg, enc)
        mask = jnp.arange(enc.shape[1])[None, :] < 40
        want, _ = w.decoder_forward(params, cfg, toks[None], jnp.arange(5)[None],
                                    w.init_self_cache(cfg, 1, dtype=jnp.float32), kv, mask)
    kw = dict(nh=cfg.n_heads, eps=cfg.norm_eps)
    got_enc = ref_wh.encoder(params["encoder"], mel, **kw)
    # erf GELU (published) vs the program's tanh form: small, not zero
    _close(got_enc, enc[0], 2e-2)
    _close(ref_wh.decoder(params["decoder"], toks, got_enc, 40, **kw), want[0], 2e-2)
    # the published (1, 1) padding of the second convolution is another model
    other = ref_wh.encoder(params["encoder"], mel, conv2_pad=(1, 1), **kw)
    assert np.max(np.abs(np.asarray(other) - np.asarray(enc[0]))) > 5e-2
