"""Builder ``ouro_stack``: ``parse_stack`` with an Ouro-2.6B (``ouro``) decoder —
48 layers of weights run ``total_ut_steps`` = 4 times a token, every (pass,
layer) with K/V of its own (192 planes, 1.5 MiB a token), a norm on each
sub-layer's OUTPUT beside the one on its input, the model's final norm after
every pass and an exit gate whose published selection picks the pass the head
reads. Its two model-specific functions and the one call of ``parse_stack.build``."""

from __future__ import annotations

import dataclasses

from . import parse_stack

# what the program's LlamaConfig must know to run this configuration
NEEDS = ("ut_steps", "sandwich_norm", "exit_threshold")

# the embedding's standard deviation an element (``olmoe_stack``'s, whose head is
# untied too), the gain of the norm on every sub-layer's OUTPUT and the exit
# gate's weights over d^-0.5 (``make_params`` says why)
EMBED_STD = 3.0
MIXER_GAIN = 0.4
GATE_STD = 1.0


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys: the dense decoder's,
    with the head's published width, the passes, the sandwich norm and the
    exit threshold."""
    if not (m["hidden_act"] == "silu" and not m["tie_word_embeddings"] and m["rope_scaling"] is None
            and not m["use_sliding_window"] and m["sliding_window"] is None):
        raise ValueError("ouro_stack builds the published block alone (SwiGLU, an untied head, "
                         "plain rotary, no window)")
    return dataclasses.replace(
        parse_stack.dense_llama_config(m, s), head_size=m["head_dim"],
        ut_steps=m["total_ut_steps"], sandwich_norm=True,
        exit_threshold=float(m["early_exit_threshold"]))


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into the
    int8 {"q", "s"} leaves the engine serves (``parse_stack.make_decoder_params``'s
    way: layer by layer under ``lax.map``), with this block's four norms a layer
    and its exit gate. Matrices are normal(0, fan_in^-0.5).

    Under a norm on a sub-layer's OUTPUT that sub-layer's size beside the
    residual stream is the output norm's gain and nothing else — a matrix's
    scale divides out (``olmo_hybrid_stack``'s finding, PERF.md section 6, PR
    54) — so ``MIXER_GAIN`` is the whole of what other builders' (2 L)^-0.5 and
    ``ROUTED_GAIN`` tune. The stream starts a pass at ``EMBED_STD`` (the first:
    the embedding) or at RMS 1 (every later one: the model's final norm closed
    the pass before, its gain ones) and each of a pass's 96 sub-layers adds a
    vector of RMS ``MIXER_GAIN``. The exit gate reads a state of RMS 1: weights
    of normal(0, ``GATE_STD``^2 / d) and no bias give logits of standard
    deviation ``GATE_STD``, so lambda is neither 0 nor 1 anywhere."""
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
        k_embed, k_head, k_layers, k_gate = jax.random.split(key, 4)

        def one_layer(k):
            ks = jax.random.split(k, len(shapes))
            return {n: quantize_leaf(w(kk, s)) for (n, s), kk in zip(shapes.items(), ks)}

        layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
        ones = jnp.ones((L, d), jnp.bfloat16)
        layers.update({"attn_norm": ones, "mlp_norm": ones,
                       "attn_post_norm": ones * MIXER_GAIN, "mlp_post_norm": ones * MIXER_GAIN})
        embed = jax.random.normal(k_embed, (V, d), jnp.float32) * EMBED_STD
        gate = jax.random.normal(k_gate, (d,), jnp.float32) * (GATE_STD * d ** -0.5)
        return {"embed": embed.astype(jnp.bfloat16), "layers": layers,
                "final_norm": jnp.ones((d,), jnp.bfloat16),
                "exit_gate": {"w": gate, "b": jnp.zeros((), jnp.float32)},
                "lm_head": quantize_leaf(w(k_head, (d, V)))}

    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    from tpu_voice_agent.models.llama import LlamaConfig

    lacks = set(NEEDS) - set(LlamaConfig.__dataclass_fields__)
    if lacks:  # a program from before PR 57: say so and leave, before anything is built
        raise SystemExit(f"[benchmark] REFUSED: this program's LlamaConfig has no {sorted(lacks)}: "
                         "it cannot run an ouro configuration (layers that run several times a "
                         "token, a sandwich norm, an exit gate)")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
