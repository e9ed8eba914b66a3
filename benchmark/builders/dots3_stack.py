"""Builder ``dots3_stack``: ``parse_stack`` with a dots3-note-prev
(``dots3_note``) decoder — ONE chip's share of an expert-parallel group of 8:
full layers of latent attention behind a learned indexer (64 heads choosing
``index_topk`` keys), sliding layers of a second latent attention with its
own ranks under a window, a compressed query and a gate a head in both, one
leading dense layer, then a sigmoid router over ALL the published experts
(chosen by score + bias, 8 a token) with the ``n_routed_experts`` experts HELD
here beside one shared expert, an untied head over this chip's rows of the
vocabulary — behind a cached prompt head that carries a SITE CONTEXT of
``site_context_tokens`` tokens. Its two model-specific functions and the one
call of ``parse_stack.build``."""

from __future__ import annotations

from . import parse_stack

# what the program's LlamaConfig must know to run this configuration
NEEDS = ("index_topk", "index_n_heads", "index_head_dim", "q_lora_rank", "lora_rescale",
         "attn_gate", "swa_n_heads", "swa_kv_lora_rank", "swa_q_lora_rank", "swa_qk_nope_dim",
         "swa_qk_rope_dim", "swa_v_head_dim", "swa_rope_theta")

_KINDS = {"F": "full", "S": "sliding"}


def site_context_text(tokenizer, tokens: int, seed: int) -> str:
    """A site's page map and tool catalog as far as a seeded model can tell:
    words of the tokenizer's own vocabulary drawn from ``seed``, as many as
    make the cached prompt head longer by exactly ``tokens`` tokens (the head
    is located as the engine locates it: the common token prefix of two
    rendered prompts)."""
    import random

    from tpu_voice_agent.services import prompts

    def head_len(text: str) -> int:
        prompts.set_site_context(text)
        a, b = (tokenizer.encode(prompts.render_prompt(t, c), bos=True)
                for t, c in (("sample utterance alpha", {}),
                             ("a rather different beta payload", {"last_query": "gamma"})))
        n = 0
        while n < min(len(a), len(b)) and a[n] == b[n]:
            n += 1
        return n

    if tokens <= 0:
        prompts.set_site_context("")
        return ""
    bare = head_len("")
    rng = random.Random(seed)
    vocab = sorted({w for w in (tokenizer.decode([i]).strip() for i in range(tokenizer.vocab_size))
                    if w.isalpha() and len(w) >= 3})
    words: list[str] = []
    want = bare + tokens
    # whole words while they fit, then the shortest pieces that close the gap
    while head_len(" ".join(words)) < want - 8:
        words.extend(rng.choice(vocab) for _ in range(max(1, (want - head_len(" ".join(words))) // 4)))
    while head_len(" ".join(words)) > want:
        words.pop()
    fillers = [" a", " 1", ".", " b", ",", " 2"]
    text = " ".join(words)
    for _ in range(64):
        n = head_len(text)
        if n == want:
            return text
        if n > want:
            break
        text += fillers[rng.randrange(len(fillers))]
    raise ValueError(f"no site context of exactly {tokens} tokens from seed {seed} "
                     f"(the head reads {head_len(text)} of {want})")


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys; ``n_routed_experts``
    is what this chip HOLDS (``reduced``), ``n_routed_experts_published`` the
    router's width. Also puts the deployment's SITE CONTEXT into the prompt
    head (``serving.site_context_tokens``, seeded by ``site_context_seed``): every
    tool that builds this configuration's engine goes through here before it
    installs the prompt prefix."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.models.llama import LlamaConfig

    if not (m["topk_method"] == "noaux_tc" and m["scoring_func"] == "sigmoid"
            and m["moe_layer_freq"] == 1 and not m["attention_bias"] and m["norm_topk_prob"]
            and not m["tie_word_embeddings"] and m["hidden_act"] == "silu"
            and m["attention_gate_type"] == "headwise" and m["swa_attention_gate_type"] == "headwise"
            and m["apply_mla_qkv_lora_rescale"] and m["rope_scaling"] is None
            and m["n_shared_experts"] == 1):
        raise ValueError("dots3_stack builds the published block alone")
    kinds = str(m["layer_kinds"])
    if len(kinds) != m["num_hidden_layers"] or set(kinds) - set(_KINDS):
        raise ValueError(f"layer_kinds {kinds!r}: F | S for each of {m['num_hidden_layers']} layers")
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
        layer_types=tuple(_KINDS[k] for k in kinds), sliding_window=m["sliding_window_size"],
        index_n_heads=m["index_n_heads"], index_head_dim=m["index_head_dim"],
        index_topk=m["index_topk"], q_lora_rank=m["q_lora_rank"], lora_rescale=True,
        attn_gate=True, swa_n_heads=m["swa_num_attention_heads"],
        swa_kv_lora_rank=m["swa_kv_lora_rank"], swa_q_lora_rank=m["swa_q_lora_rank"],
        swa_qk_nope_dim=m["swa_qk_nope_head_dim"], swa_qk_rope_dim=m["swa_qk_rope_head_dim"],
        swa_v_head_dim=m["swa_v_head_dim"], swa_rope_theta=float(m["swa_rope_theta"]))


# the embedding's standard deviation an element and the router bias's
# (``moonlight_stack``'s, for its reasons: an untied head; a bias at the
# spread of the scores it is added to), and a routed expert's down projection
# over f^-0.5 (``make_params`` says why)
EMBED_STD = 3.0
BIAS_STD = 0.2
ROUTED_GAIN = 1.0
# the gain on the indexer's query and key projections over fan_in^-0.5
INDEX_GAIN = 1.0


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the int8 {"q", "s"} leaves the engine serves: the attention leaves of
    each KIND, the leading dense layer and the routed ones each under
    ``lax.map``, and inside a routed layer expert by expert (a layer's 32
    held experts in float32 are 3.0 GB beside the 0.75 GB they become), each
    quantised per output channel with the program's ``quantize_leaf``. The
    router (as wide as published), its bias (float32) and the norms' gains
    stay unquantised. Matrices are normal(0, fan_in^-0.5), the shared expert's
    down projection at its own f^-0.5 — and the three that read a RESCALED
    rank (W_qb and the indexer's W_qI from the compressed query, W_kvb from
    the latent) at d^-0.5: their input's mean square is d / rank, not 1, so
    rank x (d / rank) is their fan-in. Drawn at rank^-0.5 a full layer's
    attention logits have a standard deviation of ~6 (2.24 x 3.16 times a
    unit model's 0.8: PERF.md section 6, PR 43), one key in 2048 takes the
    softmax, and bf16 against float32 reads 0.38 on the chip; a trained
    model's weights have grown up under the rescale, a seeded one's have to
    be drawn under it.

    ``EMBED_STD`` and ``BIAS_STD`` are ``moonlight_stack``'s. ``ROUTED_GAIN``
    is 1 where Moonlight's is 0.2: this model's eight gates are renormalised
    to a sum of ONE (``routed_scaling_factor`` 1), and of a token's eight
    picks one in eight is held on this chip, so a pick that flips on a near
    tie moves an eighth of an eighth of the routed sum."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.models import dots3
    from tpu_voice_agent.models.llama import quantize_leaf

    d, f, E, V = cfg.dim, cfg.ffn_dim, cfg.n_experts, cfg.vocab_size
    fd, sf = cfg.dense_ffn_dim, cfg.n_shared_experts * f
    n_dense, n_routed = cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers
    dense = {"w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)}
    routed = {"shared_gate": (d, sf), "shared_up": (d, sf), "shared_down": (sf, d)}
    # a matrix that reads a RESCALED rank (the compressed query, the latent)
    # is drawn at the hidden size's fan-in: rank x (d / rank) = d
    fan_in = {"shared_down": f, **({"w_qb": d, "w_iq": d, "w_kvb": d} if cfg.lora_rescale else {})}
    gain = {"moe_down": ROUTED_GAIN, "w_iq": INDEX_GAIN, "w_ik": INDEX_GAIN}
    experts = {"moe_gate": (d, f), "moe_up": (d, f), "moe_down": (f, d)}
    bf16 = jnp.bfloat16

    def w(key, shape, fan, name=None):
        return jax.random.normal(key, shape, jnp.float32) * (fan ** -0.5 * gain.get(name, 1.0))

    norms = lambda L: {"attn_norm": jnp.ones((L, d), bf16), "mlp_norm": jnp.ones((L, d), bf16)}

    @jax.jit
    def make(key):
        k_embed, k_head, k_dense, k_routed, k_full, k_swa = jax.random.split(key, 6)

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
            L = cfg.layer_types.count(kind)
            shapes = dots3.attn_shapes(cfg, kind)
            one = lambda kk: matrices(shapes, jax.random.split(kk, len(shapes)))
            return {**jax.lax.map(one, jax.random.split(k, L)), **dots3.attn_norms(cfg, kind, L)}

        embed = (jax.random.normal(k_embed, (V, d), jnp.float32) * EMBED_STD).astype(bf16)
        out = {"embed": embed, "final_norm": jnp.ones((d,), bf16),
               "lm_head": quantize_leaf(w(k_head, (d, V), d)),
               "attn_full": attn_stack("full", k_full),
               "layers": {**jax.lax.map(routed_layer, jax.random.split(k_routed, n_routed)),
                          **norms(n_routed)}}
        if "sliding" in cfg.layer_types:
            out["attn_swa"] = attn_stack("sliding", k_swa)
        if n_dense:
            out["dense_layers"] = {**jax.lax.map(dense_layer, jax.random.split(k_dense, n_dense)),
                                   **norms(n_dense)}
        return out

    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    from tpu_voice_agent.models.llama import LlamaConfig

    lacks = set(NEEDS) - set(LlamaConfig.__dataclass_fields__)
    if lacks:  # a program from before PR 43: say so and leave, before anything is built
        raise SystemExit(f"[benchmark] REFUSED: this program's LlamaConfig has no {sorted(lacks)}: "
                         "it cannot run a dots3_note configuration (learned sparse attention over "
                         "a latent cache, windowed latent attention of its own ranks, a compressed "
                         "query, a gate a head)")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
