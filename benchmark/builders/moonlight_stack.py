"""Builder ``moonlight_stack``: ``parse_stack`` with a Moonlight-16B-A3B
(``deepseek_v3``) decoder — latent attention behind a cache of
``kv_lora_rank`` + ``qk_rope_head_dim`` values a token a layer,
``first_k_dense_replace`` leading dense layers, then sigmoid-routed experts
chosen by score + bias beside shared experts that are ADDED, an untied head.
Its two model-specific functions and the one call of ``parse_stack.build``."""

from __future__ import annotations

from . import parse_stack

# what the program's LlamaConfig must know to run this configuration
NEEDS = ("kv_lora_rank", "qk_nope_dim", "qk_rope_dim", "v_head_dim", "latent_norm_eps",
         "first_dense_layers", "dense_ffn_dim", "router_bias", "router_scale", "shared_sum")


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys."""
    from tpu_voice_agent.models.llama import LlamaConfig

    if not (m["topk_method"] == "noaux_tc" and m["n_group"] == 1 and m["topk_group"] == 1
            and m["scoring_func"] == "sigmoid" and m["q_lora_rank"] is None
            and m["moe_layer_freq"] == 1 and not m["attention_bias"]
            and not m["tie_word_embeddings"] and m["hidden_act"] == "silu"):
        raise ValueError("moonlight_stack builds the published block alone")
    experts, top_k = m["n_routed_experts"], m["num_experts_per_tok"]
    return LlamaConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_size=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        ffn_dim=m["moe_intermediate_size"], max_seq_len=s["max_len"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        n_experts=experts, top_k=top_k, capacity_factor=experts / top_k,
        norm_topk=bool(m["norm_topk_prob"]), router_fn=m["scoring_func"], rope_interleaved=True,
        n_shared_experts=m["n_shared_experts"], shared_sum=True,
        kv_lora_rank=m["kv_lora_rank"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        latent_norm_eps=float(m["latent_norm_eps"]),
        first_dense_layers=m["first_k_dense_replace"], dense_ffn_dim=m["intermediate_size"],
        router_bias=True, router_scale=float(m["routed_scaling_factor"]))


# the embedding's standard deviation an element (``olmoe_stack``'s, whose
# head is untied too), and the standard deviation of the router's selection
# bias (``make_params`` says why each, and why the file's ``weights_seed``)
EMBED_STD = 3.0
BIAS_STD = 0.2
# a routed expert's down projection over f^-0.5
ROUTED_GAIN = 0.2


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the int8 {"q", "s"} leaves the engine serves: the leading dense layers
    and the routed ones each under ``lax.map``, and inside a routed layer
    expert by expert (a layer's 64 experts in float32 are 2.2 GB beside the
    0.55 GB they become), each quantised per output channel with the
    program's ``quantize_leaf``. The router, its bias (float32) and the
    norms' gains stay unquantised. Matrices are normal(0, fan_in^-0.5), a
    shared expert's down projection at its own f^-0.5.

    ``EMBED_STD`` 3 is ``olmoe_stack``'s, for its reason (an untied head: the
    embedding's scale decides how far tokens that differ are routed apart and
    nothing of the logits).

    ``ROUTED_GAIN`` 0.2 on a routed expert's DOWN projection is this recipe's
    own, because this model's gates do not sum to one: six sigmoid scores
    renormalised and times 2.446 put 0.41 of an expert's output on each pick
    (OLMoE's eight unrenormalised softmax weights sum to ~0.45: 0.2 here gives
    a routed layer OLMoE's size beside the residual stream). At a gain of 1 a
    sixth pick that flips on a near tie — the bf16 program's router against
    the float32 reference's, about every other row of the comparison's 13 —
    moved that row's logits by 10-40 % of their range (rows without a flip:
    1.3-1.5 %), the int4 control read 63-69 %, and 15 of the 64 corpus plans
    never ended. A checkpoint's layers are small beside its residual stream
    and a flipped pick moves it by a percent; a seeded one has to be given
    that.

    ``BIAS_STD`` 0.2: the selection bias is NONZERO — a trained model's is
    what balanced its load — at the spread of the sigmoid scores it is added
    to (logits of unit variance: scores 0.5 +- 0.2), so that the comparison
    can tell a program that chose by s alone from this one: planted at the
    served widths, that fault reads 13-14 % of the logit range at 0.1, 17-19 %
    at 0.2, 20-22 % at 0.3, while a forward touches 31-37, 19-27, 18-20 of a
    layer's 64 experts (a load-balanced deployment touches most of them): 0.2
    is the middle.

    THE SEED decides how long a plan is (``weights_seed`` in the
    configuration's file). A seeded model's plan is 0 or 8 intents where its
    own token decides the next one (33-100 or 300-450 tokens, the same for
    every text) and anything from 25 to 512 where the context does; the cell's
    traffic is plans of ~100-180 tokens (``olmoe_stack``'s read 86-183 on the
    same chip). Sweeps on the chip (``tools/recipe_check.py``, 195 recipes;
    PERF.md section 6 has the table): a sharper attention (W_q times 2-3) or
    a smaller embedding (1-2) makes plans follow their text, and makes the
    comparison useless (served 13-25 % of the range); at this recipe, 6 seeds
    of 30 (``BIAS_STD`` 0.1), 5 of 30 (0.2) and 3 of 20 (0.3) put 43 or more of
    the 64 plans inside 100-180, and under three all 64: 0.2 / 53 (115-180, 56
    distinct), **0.2 / 60 (114-174, median 126, 60 distinct plans: kept)**,
    0.3 / 53 (117-135)."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.models.llama import quantize_leaf
    from tpu_voice_agent.models.mla import attn_shapes

    d, f, E, V = cfg.dim, cfg.ffn_dim, cfg.n_experts, cfg.vocab_size
    fd, sf = cfg.dense_ffn_dim, cfg.n_shared_experts * f
    n_dense, n_routed = cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers
    attn = attn_shapes(cfg)
    dense = {**attn, "w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)}
    routed = {**attn, "shared_gate": (d, sf), "shared_up": (d, sf), "shared_down": (sf, d)}
    fan_in = {"shared_down": f}
    gain = {"moe_down": ROUTED_GAIN}
    experts = {"moe_gate": (d, f), "moe_up": (d, f), "moe_down": (f, d)}

    def w(key, shape, fan, name=None):
        return jax.random.normal(key, shape, jnp.float32) * (fan ** -0.5 * gain.get(name, 1.0))

    def norms(L):
        return {"attn_norm": jnp.ones((L, d), jnp.bfloat16), "mlp_norm": jnp.ones((L, d), jnp.bfloat16),
                "kv_norm": jnp.ones((L, cfg.kv_lora_rank), jnp.bfloat16)}

    @jax.jit
    def make(key):
        k_embed, k_head, k_dense, k_routed = jax.random.split(key, 4)

        def matrices(shapes, ks):
            return {n: quantize_leaf(w(kk, s, fan_in.get(n, s[0]), n))
                    for (n, s), kk in zip(shapes.items(), ks)}

        def one_expert(k):
            return matrices(experts, jax.random.split(k, len(experts)))

        def dense_layer(k):
            return matrices(dense, jax.random.split(k, len(dense)))

        def routed_layer(k):
            ks = jax.random.split(k, len(routed) + 3)
            layer = matrices(routed, ks)
            layer["router"] = w(ks[-3], (d, E), d).astype(jnp.bfloat16)
            layer["router_bias"] = BIAS_STD * jax.random.normal(ks[-2], (E,), jnp.float32)
            return {**layer, **jax.lax.map(one_expert, jax.random.split(ks[-1], E))}

        embed = (jax.random.normal(k_embed, (V, d), jnp.float32) * EMBED_STD).astype(jnp.bfloat16)
        out = {"embed": embed, "final_norm": jnp.ones((d,), jnp.bfloat16),
               "lm_head": quantize_leaf(w(k_head, (d, V), d)),
               "layers": {**jax.lax.map(routed_layer, jax.random.split(k_routed, n_routed)),
                          **norms(n_routed)}}
        if n_dense:
            out["dense_layers"] = {**jax.lax.map(dense_layer, jax.random.split(k_dense, n_dense)),
                                   **norms(n_dense)}
        return out

    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    from tpu_voice_agent.models.llama import LlamaConfig

    lacks = set(NEEDS) - set(LlamaConfig.__dataclass_fields__)
    if lacks:  # a program from before PR 38: say so and leave, before anything is built
        raise SystemExit(f"[benchmark] REFUSED: this program's LlamaConfig has no {sorted(lacks)}: "
                         "it cannot run a deepseek_v3 configuration (a latent cache, leading "
                         "dense layers, a router that selects by score + bias)")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
