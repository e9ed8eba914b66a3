"""Builder ``parse_stack``: the brain alone on a real socket — a decoder
configuration served as the repo serves any decoder: ``PagedDecodeEngine``
behind ``brain._wrap_batched`` (cached prompt prefix, continuous batcher).
Whisper is never built.

What differs between two decoders is two functions, and ``build`` /
``build_parser`` take them: ``llama_config(model, serving)`` -> the
program's ``LlamaConfig`` from the configuration's own keys, and
``make_params(cfg, seed)`` -> the served parameter tree, made on the device.
Their defaults are the dense ones below; a builder for another block type is
a file with its two functions and ``def build(config, rehearsal, say):
return parse_stack.build(config, rehearsal, say, llama_config=...,
make_params=...)``."""

from __future__ import annotations

import os
import time


def as_run(conf: dict, rehearsal: bool) -> tuple[dict, dict]:
    """(model sizes, serving parameters) as run: the file's, or its
    ``rehearsal`` widths when JAX_PLATFORMS=cpu asked for the CPU."""
    model = {k: v for k, v in conf.items() if not isinstance(v, (dict, list))}
    serving = dict(conf["serving"])
    if rehearsal:
        over = dict(conf["rehearsal"])
        serving.update(over.pop("serving", {}))
        model.update({k: v for k, v in over.items() if k != "note"})
    return model, serving


def model_dims(config: dict, rehearsal: bool) -> dict:
    model, serving = as_run(config.get("decoder", config), rehearsal)
    return {"model": model, "serving": serving}


def refuse_unserved_window(model: dict, serving: dict, cfg) -> None:
    """A published ``sliding_window`` that ``max_len`` passes BINDS (at
    ``max_len`` <= window no mask can be false: ``llama.bound_window``'s
    identity). The program serves a binding window since PR 34 — where its
    configuration carries it. So ask the program's configuration, as the
    builder's ``llama_config`` made it: one that dropped the window (the dense
    ``dense_llama_config`` passes none) would serve another model than the
    published one, and is refused here as before."""
    window = model.get("sliding_window")
    if window and serving["max_len"] > window and window not in (
            getattr(cfg, "sliding_window", None), getattr(cfg, "window", None)):
        raise ValueError(f"max_len {serving['max_len']} passes the published sliding window {window} and "
                         f"the program's configuration ({type(cfg).__name__}) does not carry it: the "
                         f"served model would not be the published one")


def apply_env(serving: dict) -> None:
    """The program's knobs as a configuration states them; run.py calls this
    before the program is imported (some are read at import)."""
    for k in serving.get("env_unset", []):
        os.environ.pop(k, None)
    os.environ.update(serving.get("env", {}))


def dense_llama_config(m: dict, s: dict):
    """The program's configuration of a dense GQA decoder from the source's
    keys ``m`` and the serving parameters ``s``."""
    from tpu_voice_agent.models.llama import LlamaConfig

    return LlamaConfig(vocab_size=m["vocab_size"], dim=m["hidden_size"],
                       n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
                       n_kv_heads=m["num_key_value_heads"], ffn_dim=m["intermediate_size"],
                       max_seq_len=s["max_len"], rope_theta=float(m["rope_theta"]),
                       norm_eps=float(m["rms_norm_eps"]))


def make_decoder_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the int8 {"q", "s"} leaves the engine serves (``quantize_leaf`` is the
    program's own): layer by layer under ``lax.map`` so that no f32 or bf16
    copy of the whole model ever exists."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.models.llama import quantize_leaf

    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    shapes = {"wq": (d, nq * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd),
              "wo": (nq * hd, d), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}

    def w(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5

    @jax.jit
    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def one_layer(k):
            ks = jax.random.split(k, len(shapes))
            return {n: quantize_leaf(w(kk, s)) for (n, s), kk in zip(shapes.items(), ks)}

        layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
        layers["attn_norm"] = jnp.ones((L, d), jnp.bfloat16)
        layers["mlp_norm"] = jnp.ones((L, d), jnp.bfloat16)
        embed = jax.random.normal(k_embed, (V, d), jnp.float32) * d ** -0.5
        return {"embed": embed.astype(jnp.bfloat16),
                "layers": layers, "final_norm": jnp.ones((d,), jnp.bfloat16),
                "lm_head": quantize_leaf(w(k_head, (d, V)))}

    # the hardware generator: threefry over 7e9 elements is seconds of set-up
    return make(jax.random.key(seed, impl="rbg"))


def build_parser(config: dict, rehearsal: bool, say, llama_config=dense_llama_config,
                 make_params=make_decoder_params):
    """The engine behind the batcher, prefix installed, runtime started."""
    import jax

    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import _wrap_batched

    dims = model_dims(config, rehearsal)
    m, s = dims["model"], dims["serving"]
    t0 = time.perf_counter()
    cfg = llama_config(m, s)
    if not rehearsal:
        refuse_unserved_window(m, s, cfg)
    engine = PagedDecodeEngine(
        cfg=cfg, tokenizer=default_tokenizer(), quant=s["quant"], batch_slots=s["batch_slots"],
        block_size=s["block_size"], pool_blocks=s["pool_blocks"], max_len=s["max_len"],
        prefill_buckets=tuple(s["prefill_buckets"]), fast_forward=s["fast_forward"],
        init_weights=False)
    t1 = time.perf_counter()
    engine.load_params(make_params(engine.cfg, s["weights_seed"]))
    jax.block_until_ready(engine.params)
    t2 = time.perf_counter()
    parser = _wrap_batched(engine)  # installs the prompt prefix, starts the serving loop
    say(f"decoder: engine+tables {t1 - t0:.1f}s, weights {t2 - t1:.1f}s, prefix "
        f"({len(engine.prefix_ids)} tokens)+batcher {time.perf_counter() - t2:.1f}s, "
        f"vocab {engine.cfg.vocab_size}, pool {s['pool_blocks']} blocks, kernels {engine.kernels}")
    return parser, dims


class Served:
    """What a builder hands the harness."""

    def __init__(self, urls, parser, dims, closers, stt_engine=None):
        self.urls, self.parser, self.dims = urls, parser, dims
        self.engine = parser.engine
        self.stt_engine = stt_engine
        self._closers = closers

    def close(self) -> None:
        for c in self._closers:
            c()


def build(config: dict, rehearsal: bool, say, **model_specific) -> Served:
    """``model_specific``: ``build_parser``'s ``llama_config`` / ``make_params``."""
    from tpu_voice_agent.services import warm_up
    from tpu_voice_agent.services.brain import build_app
    from tpu_voice_agent.services.stack import AppServer

    parser, dims = build_parser(config, rehearsal, say, **model_specific)
    t0 = time.perf_counter()
    warm_up(parser)
    say(f"decoder warm-up {time.perf_counter() - t0:.1f}s")
    brain = AppServer(build_app(parser)).__enter__()
    return Served({"brain": brain.url}, parser, dims,
                  [lambda: brain.__exit__(None, None, None), parser.close])
