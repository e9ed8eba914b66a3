"""Builder ``olmoe_stack``: ``parse_stack`` with an OLMoE decoder — routed
experts with the softmax weights of the chosen experts NOT renormalised
(``norm_topk_prob``), and an RMSNorm over the whole q and k vector
(``qk_norm``, stated in the configuration file). Its two model-specific
functions and the one call of ``parse_stack.build``."""

from __future__ import annotations

import dataclasses

from . import parse_stack


def llama_config(m: dict, s: dict):
    """The dense keys as ``parse_stack`` reads them, plus the routed ones;
    ``capacity_factor`` = E / K is the program's drop-free setting (only
    its meshed dispatch reads it)."""
    experts, top_k = m["num_experts"], m["num_experts_per_tok"]
    return dataclasses.replace(
        parse_stack.dense_llama_config(m, s), n_experts=experts, top_k=top_k,
        capacity_factor=experts / top_k, norm_topk=bool(m["norm_topk_prob"]),
        qk_norm=bool(m["qk_norm"]))


# the embedding's standard deviation an element (parse_stack: hidden_size^-0.5);
# make_params says why
EMBED_STD = 3.0


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the int8 {"q", "s"} leaves the engine serves: layer by layer under
    ``lax.map``, each expert quantised per output channel with the program's
    ``quantize_leaf`` (the stacked (E, d, f) leaf gets a scale of (E, 1, f)),
    so that no f32 or bf16 copy of the model ever exists. The router, the
    norms and the q/k norm gains stay bf16.

    One departure from ``parse_stack``'s recipe, so that the ROUTED LOAD is
    a trained, load-balanced model's — tokens that differ go to experts that
    differ: the embedding is drawn at ``EMBED_STD`` = 3 an element (there
    d^-0.5). At d^-0.5 a token's own row drowned in the attention average
    over the shared 879-token prompt prefix, which is the same for every
    row and compounds through the next layer's values: all 288 positions of
    a forward chose the same 8-10 experts (``moe_load_max_over_mean`` 8.0 =
    E / K, its ceiling) and a forward read 0.9 GB of expert weights for the
    6.4 GB a deployment streams. Experts touched a layer, of 64, and the
    served model's distance from the float32 reference (int4 control), by
    embedding scale: 1 → 10-19, 1.3-2.6 % (40 %); 2 → 44, 1.7-2.8 % (25 %);
    3 → 51, 1.3-2.1 % (13 %); 4 → 44, 1.2-1.5 % (8 %). Shrinking ``wo`` by
    (2 L)^-0.5 instead reached 57 experts but left layers so small beside
    the residual stream that one expert swapped on a near tie read 4-8 %
    against a control of 15 % (PR 27's chip runs, a refused PR; PERF.md
    section 6). PR 28 read the cell at 3 again: 58.2 experts a layer touched
    a decode forward, served 1.32-1.83 %, control 12.6-13.3 %."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.models.llama import quantize_leaf

    d, f, hd, E = cfg.dim, cfg.ffn_dim, cfg.head_dim, cfg.n_experts
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    shapes = {"wq": (d, nq * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd), "wo": (nq * hd, d),
              "moe_gate": (E, d, f), "moe_up": (E, d, f), "moe_down": (E, f, d)}

    def w(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5

    @jax.jit
    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def one_layer(k):
            ks = jax.random.split(k, len(shapes) + 1)
            layer = {n: quantize_leaf(w(kk, s)) for (n, s), kk in zip(shapes.items(), ks)}
            layer["router"] = w(ks[-1], (d, E)).astype(jnp.bfloat16)
            return layer

        layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
        for name, width in (("attn_norm", d), ("mlp_norm", d), ("q_norm", nq * hd),
                            ("k_norm", nkv * hd)):
            layers[name] = jnp.ones((L, width), jnp.bfloat16)
        embed = jax.random.normal(k_embed, (V, d), jnp.float32) * EMBED_STD
        return {"embed": embed.astype(jnp.bfloat16),
                "layers": layers, "final_norm": jnp.ones((d,), jnp.bfloat16),
                "lm_head": quantize_leaf(w(k_head, (d, V)))}

    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
