"""Builder ``cohere2moe_stack``: ``parse_stack`` with a Command A+
(``cohere2_moe``) decoder — ONE chip's share of an expert-parallel group:
parallel blocks of one LayerNorm, 128 query heads of 128 over 8 K/V heads on
a 4096-wide residual, three sliding layers (interleaved rotary pairs) and
one full layer without positions a period, a sigmoid router over ALL the
published experts with the gates renormalised over the 8 chosen, the
``num_experts`` experts HELD here beside the shared experts, a tied head.
Its two model-specific functions and the one call of ``parse_stack.build``."""

from __future__ import annotations

from . import parse_stack


def layer_types(m: dict) -> tuple[str, ...]:
    """The kind of each served layer from the scalars the harness hands over
    (``local_attn_first``: every ``layer_switch``-th layer is full); ``build``
    holds it against the file's own ``layer_types``."""
    if m.get("order_of_interleaved_layers") != "local_attn_first":
        raise ValueError("cohere2moe_stack knows the published order of layers alone")
    return tuple("full" if (i + 1) % m["layer_switch"] == 0 else "sliding"
                 for i in range(m["num_hidden_layers"]))


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys; ``num_experts`` is
    what this chip HOLDS (``reduced``), ``num_experts_published`` the router's
    width."""
    from tpu_voice_agent.models.llama import LlamaConfig

    if not (m["use_parallel_block"] and m["tie_word_embeddings"] and m["norm_topk_prob"]
            and m["position_embedding_type"] == "rope_gptj" and m["first_k_dense_replace"] == 0
            and m["shared_expert_combination_strategy"] == "average" and not m["use_qk_norm"]
            and not m["attention_bias"] and m["rotary_pct"] == 1):
        raise ValueError("cohere2moe_stack builds the published block alone")
    experts, top_k = m["num_experts_published"], m["num_experts_per_tok"]
    return LlamaConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_size=m["head_dim"], ffn_dim=m["intermediate_size"], max_seq_len=s["max_len"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["layer_norm_eps"]),
        n_experts=experts, top_k=top_k, capacity_factor=experts / top_k, norm_topk=True,
        layer_types=layer_types(m), sliding_window=m["sliding_window"], rope_interleaved=True,
        norm="layer", parallel_block=True, router_fn=m["expert_selection_fn"],
        n_shared_experts=m["num_shared_experts"],
        experts_held=m["num_experts"] if m["num_experts"] < experts else 0,
        first_expert=m["first_expert"], tie_embeddings=True, logit_scale=float(m["logit_scale"]))


# the embedding's standard deviation an element, and the gain on the query and
# key projections over fan_in^-0.5 (``make_params`` says why each)
EMBED_STD = 0.3
QK_GAIN = 1.5


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the int8 {"q", "s"} leaves the engine serves: layer by layer under
    ``lax.map``, and inside a layer expert by expert (one layer's sixteen
    held experts in float32 are 3.2 GB beside the 9 GB they become), each
    quantised per output channel with the program's ``quantize_leaf``. The
    router and the norms' gains stay bf16. Matrices are normal(0, fan_in^-0.5)
    (a shared expert's down projection at its own f^-0.5); the head is an int8
    copy of the embedding, a scale a row.

    TWO scales are this recipe's own, because the head is TIED. With x_L =
    E[t] + what the layers added, the tied logit of the input token itself is
    LN(x_L) . E[t], which grows with d where every other token's grows with
    sqrt(d): at ``olmoe_stack``'s embedding scale of 3 it stood 38 sigma over
    the rest, every request repeated its token wherever the grammar let it
    (plans of 376-463 tokens, 7 of 64 truncated at scale 1), the logit range
    was the embedding's own, and the int4 CONTROL of the comparison read 0.95 %
    — under any tolerance (my chip runs, PR 34). At 0.3 an element the self
    term is 7 sigma and the layers decide the token. But random attention
    averages ~330 of 900 positions, so nothing of a request's own text reached
    its plan (2 distinct plans of 64, all rows in step, 1.3 of 16 held experts
    a layer touched): ``QK_GAIN`` 1.5 on W_q and W_k (scores of standard
    deviation 2.25 where 1) makes attention pick positions, as a trained
    model's does. Sweep, 64 texts at once (scale, gain -> distinct plans,
    tokens a plan, truncated, held experts touched a layer, served against
    reference, int4 control): 3, 1 -> 64, 376, 0, 6.2, 0.04 %, 0.95 %; 0.3, 1 ->
    few, 53, 0, 1.4, 1.5-2.0 %, 31 %; 0.3, 1.5 -> 60, 101, 0, 6.0, 0.5-1.9 %, 32 %;
    0.3, 2 -> 64, 169, 0, 9.5, 5.6 %, 54 %; 0.1, 2 -> 64, 199, 0, 10.9, 13-16 %, 114 %;
    0.1, 4 -> 64, 152, 0, 11.2, 97-101 %, 141 %: sharper attention spreads the
    router further and makes bf16 K/V decide which key wins. PERF.md section 6."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.models.llama import quantize_leaf

    d, f, hd, E, H = cfg.dim, cfg.ffn_dim, cfg.head_dim, cfg.n_experts, cfg.n_held
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    sf = cfg.n_shared_experts * f
    shapes = {"wq": (d, nq * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd), "wo": (nq * hd, d),
              "shared_gate": (d, sf), "shared_up": (d, sf), "shared_down": (sf, d)}
    fan_in = {**{n: s[0] for n, s in shapes.items()}, "shared_down": f}
    experts = {"moe_gate": (d, f), "moe_up": (d, f), "moe_down": (f, d)}

    gain = {"wq": QK_GAIN, "wk": QK_GAIN}

    def w(key, shape, fan, name=None):
        return jax.random.normal(key, shape, jnp.float32) * (fan ** -0.5 * gain.get(name, 1.0))

    @jax.jit
    def make(key):
        k_embed, k_layers = jax.random.split(key)

        def one_expert(k):
            return {n: quantize_leaf(w(kk, s, s[0]))
                    for (n, s), kk in zip(experts.items(), jax.random.split(k, len(experts)))}

        def one_layer(k):
            ks = jax.random.split(k, len(shapes) + 2)
            layer = {n: quantize_leaf(w(kk, s, fan_in[n], n)) for (n, s), kk in zip(shapes.items(), ks)}
            layer["router"] = w(ks[-2], (d, E), d).astype(jnp.bfloat16)
            return {**layer, **jax.lax.map(one_expert, jax.random.split(ks[-1], H))}

        layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
        layers["attn_norm"] = jnp.ones((L, d), jnp.bfloat16)
        embed = (jax.random.normal(k_embed, (V, d), jnp.float32) * EMBED_STD).astype(jnp.bfloat16)
        return {"embed": embed, "layers": layers, "final_norm": jnp.ones((d,), jnp.bfloat16),
                "lm_head": quantize_leaf(embed.T)}

    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    from tpu_voice_agent.models.llama import LlamaConfig

    lacks = {"head_size", "layer_types", "experts_held", "n_shared_experts"} - set(LlamaConfig.__dataclass_fields__)
    if lacks:  # a program from before PR 34: say so and leave, before anything is built
        raise SystemExit(f"[benchmark] REFUSED: this program's LlamaConfig has no {sorted(lacks)}: "
                         "it cannot run a cohere2_moe configuration")
    m = parse_stack.as_run(config, rehearsal)[0]
    want = tuple({"sliding_attention": "sliding", "full_attention": "full"}[t]
                 for t in config["layer_types"][:m["num_hidden_layers"]])
    if layer_types(m) != want:
        raise ValueError(f"layer_switch gives {layer_types(m)}, the file's layer_types {want}")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
