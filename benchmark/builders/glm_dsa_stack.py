"""Builder ``glm_dsa_stack``: ``parse_stack`` with a GLM-5.2 (``glm_moe_dsa``)
decoder — ONE chip's share of an expert-parallel group of 16: latent
attention with a compressed query in every layer, attended over a SELECTION
that the layers named "full" in ``indexer_types`` make with an indexer of
their own (32 heads choosing ``index_topk`` keys) and the "shared" layers
behind them REUSE (IndexShare: no indexer weights, no index key cached), one
leading dense layer, then a sigmoid router over ALL the published experts
(chosen by score + bias, 8 a token, gates renormalised x 2.5) with the
``n_routed_experts`` experts HELD here beside one shared expert, an untied
head over this chip's rows of the vocabulary — behind ``dots3_stack``'s cached
prompt head with its SITE CONTEXT. Its two model-specific functions and the
one call of ``parse_stack.build``."""

from __future__ import annotations

from . import parse_stack
from .dots3_stack import site_context_text

# what the program's LlamaConfig must know to run this configuration
NEEDS = ("indexer_types", "index_topk", "index_n_heads", "index_head_dim", "q_lora_rank")

_KINDS = {"F": "full", "S": "shared"}


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys; ``n_routed_experts``
    is what this chip HOLDS (``reduced``), ``n_routed_experts_published`` the
    router's width; ``indexer_kinds`` (a letter a served layer, F full | S
    shared) the served slice of ``indexer_types``. Also puts the deployment's
    SITE CONTEXT into the prompt head, as ``dots3_stack.llama_config`` does."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.models.llama import LlamaConfig

    if not (m["topk_method"] == "noaux_tc" and m["scoring_func"] == "sigmoid"
            and m["moe_layer_freq"] == 1 and not m["attention_bias"] and m["norm_topk_prob"]
            and not m["tie_word_embeddings"] and m["hidden_act"] == "silu"
            and m["n_group"] == 1 and m["topk_group"] == 1 and m["n_shared_experts"] == 1
            and m["rope_interleave"] and m["indexer_rope_interleave"]
            and m["qk_head_dim"] == m["qk_nope_head_dim"] + m["qk_rope_head_dim"]):
        raise ValueError("glm_dsa_stack builds the published block alone")
    kinds = str(m["indexer_kinds"])
    if len(kinds) != m["num_hidden_layers"] or set(kinds) - set(_KINDS):
        raise ValueError(f"indexer_kinds {kinds!r}: F | S for each of {m['num_hidden_layers']} layers")
    site_context_text(default_tokenizer(), int(s.get("site_context_tokens", 0)),
                      int(s.get("site_context_seed", s["weights_seed"])))
    experts, top_k = m["n_routed_experts_published"], m["num_experts_per_tok"]
    return LlamaConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_size=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        ffn_dim=m["moe_intermediate_size"], max_seq_len=s["max_len"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        n_experts=experts, top_k=top_k, capacity_factor=experts / top_k,
        norm_topk=True, router_fn=m["scoring_func"], rope_interleaved=True,
        n_shared_experts=m["n_shared_experts"], shared_sum=True,
        experts_held=m["n_routed_experts"] if m["n_routed_experts"] < experts else 0,
        first_expert=m["first_expert"],
        kv_lora_rank=m["kv_lora_rank"], qk_nope_dim=m["qk_nope_head_dim"],
        qk_rope_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        latent_norm_eps=float(m["latent_norm_eps"]),
        first_dense_layers=m["first_k_dense_replace"], dense_ffn_dim=m["intermediate_size"],
        router_bias=True, router_scale=float(m["routed_scaling_factor"]),
        layer_types=("full",) * len(kinds), indexer_types=tuple(_KINDS[k] for k in kinds),
        index_n_heads=m["index_n_heads"], index_head_dim=m["index_head_dim"],
        index_topk=m["index_topk"], q_lora_rank=m["q_lora_rank"])


# the embedding's standard deviation an element and the router bias's
# (``moonlight_stack``'s, for its reasons: an untied head; a bias at the
# spread of the scores it is added to), a routed expert's down projection over
# f^-0.5 (``make_params`` says why), the indexer's query and key projections
# over fan_in^-0.5
EMBED_STD = 3.0
BIAS_STD = 0.2
ROUTED_GAIN = 0.4
INDEX_GAIN = 1.0


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the int8 {"q", "s"} leaves the engine serves, as ``dots3_stack.make_params``
    makes them (kind by kind, layer by layer, expert by expert; the router as
    wide as published, its bias and the norms' gains unquantised) — for THIS
    tree: ``attn_full`` the layers that run an indexer, ``attn_shared`` those
    that reuse a selection (no indexer leaves). Matrices are normal(0,
    fan_in^-0.5) (no rank is rescaled here), the shared expert's down
    projection at its own f^-0.5.

    ``ROUTED_GAIN`` 0.4 = 1 / ``routed_scaling_factor``: the eight gates sum
    to 2.5, so at a gain of 1 a token whose picks are all held here would add
    2.5 expert outputs to the residual beside the shared expert's one; at 0.4
    the routed sum of a token's picks is the size of ONE expert's output, as in
    ``dots3_stack`` (gates summing to 1 at a gain of 1), of which this chip
    holds a sixteenth."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.models import dots3
    from tpu_voice_agent.models.llama import quantize_leaf

    d, f, E, V = cfg.dim, cfg.ffn_dim, cfg.n_experts, cfg.vocab_size
    fd, sf = cfg.dense_ffn_dim, cfg.n_shared_experts * f
    n_dense, n_routed = cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers
    dense = {"w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)}
    routed = {"shared_gate": (d, sf), "shared_up": (d, sf), "shared_down": (sf, d)}
    fan_in = {"shared_down": f}
    gain = {"moe_down": ROUTED_GAIN, "w_iq": INDEX_GAIN, "w_ik": INDEX_GAIN}
    experts = {"moe_gate": (d, f), "moe_up": (d, f), "moe_down": (f, d)}
    bf16 = jnp.bfloat16
    kinds, layer_kinds = dots3.kinds(cfg), dots3.layer_kinds(cfg)

    def w(key, shape, fan, name=None):
        return jax.random.normal(key, shape, jnp.float32) * (fan ** -0.5 * gain.get(name, 1.0))

    norms = lambda L: {"attn_norm": jnp.ones((L, d), bf16), "mlp_norm": jnp.ones((L, d), bf16)}

    @jax.jit
    def make(key):
        k_embed, k_head, k_dense, k_routed, *k_kinds = jax.random.split(key, 4 + len(kinds))

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
            layer["router"] = w(ks[-3], (d, E), d).astype(bf16)
            layer["router_bias"] = BIAS_STD * jax.random.normal(ks[-2], (E,), jnp.float32)
            return {**layer, **jax.lax.map(one_expert, jax.random.split(ks[-1], cfg.n_held))}

        def attn_stack(kind, k):
            L = layer_kinds.count(kind)
            shapes = dots3.attn_shapes(cfg, kind)
            one = lambda kk: matrices(shapes, jax.random.split(kk, len(shapes)))
            return {**jax.lax.map(one, jax.random.split(k, L)), **dots3.attn_norms(cfg, kind, L)}

        embed = (jax.random.normal(k_embed, (V, d), jnp.float32) * EMBED_STD).astype(bf16)
        out = {"embed": embed, "final_norm": jnp.ones((d,), bf16),
               "lm_head": quantize_leaf(w(k_head, (d, V), d)),
               **{kinds[kind].stack: attn_stack(kind, k) for kind, k in zip(kinds, k_kinds)},
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
    if lacks:  # a program from before PR 61: say so and leave, before anything is built
        raise SystemExit(f"[benchmark] REFUSED: this program's LlamaConfig has no {sorted(lacks)}: "
                         "it cannot run a glm_moe_dsa configuration (latent attention under a "
                         "selection that one layer makes and the next layers reuse)")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
