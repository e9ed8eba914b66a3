"""Builder ``smallthinker_stack``: ``parse_stack`` with a
SmallThinker-21BA3B-Instruct (``smallthinker``) decoder — STAGE 0 OF TWO of a
pipeline: a router that reads the layer's INPUT (the residual before any
norm; the picks are made before attention and carried across it), 64 ReGLU
experts 6 a token with the gates a softmax over the chosen logits, 28 query
heads of 128 over 4 K/V heads, one full layer WITHOUT positions among three
rotated ones behind a window of ``sliding_window_size``, an untied head over
the whole vocabulary — behind a cached prompt head that carries a SITE CONTEXT
of ``site_context_tokens`` tokens (``dots3_stack.site_context_text``: the same
text as that cell's). Its two model-specific functions and the one call of
``parse_stack.build``."""

from __future__ import annotations

from . import parse_stack
from .dots3_stack import site_context_text

# what the program's LlamaConfig must know to run this configuration
NEEDS = ("router_input", "gate_act", "layer_types", "sliding_window", "head_size")

_KINDS = {"F": "full", "S": "sliding"}


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys. Also puts the
    deployment's SITE CONTEXT into the prompt head
    (``serving.site_context_tokens``, seeded by ``site_context_seed``): every
    tool that builds this configuration's engine goes through here before it
    installs the prompt prefix."""
    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.models.llama import LlamaConfig

    if not (m["moe_primary_router_apply_softmax"] and m["norm_topk_prob"]
            and not m["tie_word_embeddings"] and m["rope_scaling"] is None):
        raise ValueError("smallthinker_stack builds the published block alone")
    kinds = str(m["layer_kinds"])
    if len(kinds) != m["num_hidden_layers"] or set(kinds) - set(_KINDS):
        raise ValueError(f"layer_kinds {kinds!r}: F | S for each of {m['num_hidden_layers']} layers")
    site_context_text(default_tokenizer(), int(s.get("site_context_tokens", 0)),
                      int(s.get("site_context_seed", s["weights_seed"])))
    experts, top_k = m["moe_num_primary_experts"], m["moe_num_active_primary_experts"]
    cfg = LlamaConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_size=m["head_dim"], ffn_dim=m["moe_ffn_hidden_size"], max_seq_len=s["max_len"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        n_experts=experts, top_k=top_k, capacity_factor=experts / top_k, norm_topk=True,
        router_fn="softmax", layer_types=tuple(_KINDS[k] for k in kinds),
        sliding_window=m["sliding_window_size"], router_input="layer", gate_act="relu")
    refuse_dropped_window(m, s, cfg)
    return cfg


def refuse_dropped_window(m: dict, s: dict, cfg) -> None:
    """``parse_stack.refuse_unserved_window`` reads the key ``sliding_window``;
    this source spells it ``sliding_window_size``. The same refusal under that
    name: a ``max_len`` past the published window is served only where the
    program's configuration carries the window AND binds it."""
    from tpu_voice_agent.models.llama import bound_window

    window = m["sliding_window_size"]
    if cfg.sliding_window != window or "sliding" not in cfg.layer_types:
        raise ValueError(f"the program's configuration dropped the published window {window}")
    if s["max_len"] > window and bound_window(cfg) != window:
        raise ValueError(f"max_len {s['max_len']} passes the published window {window} and the "
                         f"program's configuration does not bind it")


# the embedding's standard deviation an element (``olmoe_stack``'s, for its
# reason) and a routed expert's down projection over f^-0.5 (``make_params``)
EMBED_STD = 3.0
ROUTED_GAIN = 0.7


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the int8 {"q", "s"} leaves the engine serves: layer by layer under
    ``lax.map`` and inside a layer expert by expert (a layer's 64 experts in
    float32 are 1.5 GB beside the 0.38 GB they become), each quantised per
    output channel with the program's ``quantize_leaf``. The router and the
    norms' gains stay bf16. Matrices are normal(0, fan_in^-0.5).

    ``EMBED_STD`` 3 is ``olmoe_stack``'s (an untied head: the embedding's scale
    decides how far tokens that differ are routed apart and nothing of the
    logits). Here it decides MORE: the router reads the residual itself, not a
    normed copy, so its logits have the residual's scale — a standard deviation
    of ~3 — and the softmax over the six chosen is sharp (the first pick's gate
    ~0.5, the sixth's a few percent), as a trained router's is.

    ``ROUTED_GAIN`` on a routed expert's DOWN projection, as Moonlight's (0.2)
    and Nemotron's (0.1) recipes have one for their reason: the six gates sum
    to ONE where OLMoE's eight sum to ~0.45, so at a gain of 1 a pick that the
    bf16 program's router makes otherwise than the float32 reference's moves a
    row by several percent of its range. A checkpoint's layers are small
    beside its residual stream; a seeded one has to be given that. Read on the
    chip (``tools/recipe_check.py``, my chip runs, PR 50; 27 recipes, seeds
    50-67): at 0.3 every seed but one gives 1-8 distinct plans of 64 — the
    8192-token head drowns a suffix of 8-40 tokens in every attention average,
    so a plan follows its own tokens and all rows emit the same one — served
    1.1-1.6 %, control 24-28 %; at 1.0 plans differ and none ENDS; at 0.5 and
    0.7 one seed in six gives plans that differ and end (0.5 / 65: 78-139
    tokens, 49 distinct; **0.7 / 62: 77-119, all 64 distinct, kept**), served
    1.45-3.75 % over the 64 samples, control 30-31 %. A forward touches ~33 of
    a layer's 64 experts there: the router reads a residual that is mostly the
    token's own embedding, and a forward's ~35 tokens hold few distinct ones."""
    import jax
    import jax.numpy as jnp

    from tpu_voice_agent.models.llama import quantize_leaf

    d, f, hd, E = cfg.dim, cfg.ffn_dim, cfg.head_dim, cfg.n_experts
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    shapes = {"wq": (d, nq * hd), "wk": (d, nkv * hd), "wv": (d, nkv * hd), "wo": (nq * hd, d)}
    experts = {"moe_gate": (d, f), "moe_up": (d, f), "moe_down": (f, d)}
    gain = {"moe_down": ROUTED_GAIN}

    def w(key, shape, name=None):
        return jax.random.normal(key, shape, jnp.float32) * (shape[0] ** -0.5 * gain.get(name, 1.0))

    @jax.jit
    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def one_expert(k):
            return {n: quantize_leaf(w(kk, s, n))
                    for (n, s), kk in zip(experts.items(), jax.random.split(k, len(experts)))}

        def one_layer(k):
            ks = jax.random.split(k, len(shapes) + 2)
            layer = {n: quantize_leaf(w(kk, s)) for (n, s), kk in zip(shapes.items(), ks)}
            layer["router"] = w(ks[-2], (d, E)).astype(jnp.bfloat16)
            return {**layer, **jax.lax.map(one_expert, jax.random.split(ks[-1], E))}

        layers = jax.lax.map(one_layer, jax.random.split(k_layers, L))
        layers["attn_norm"] = jnp.ones((L, d), jnp.bfloat16)
        layers["mlp_norm"] = jnp.ones((L, d), jnp.bfloat16)
        embed = jax.random.normal(k_embed, (V, d), jnp.float32) * EMBED_STD
        return {"embed": embed.astype(jnp.bfloat16), "layers": layers,
                "final_norm": jnp.ones((d,), jnp.bfloat16),
                "lm_head": quantize_leaf(w(k_head, (d, V)))}

    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    from tpu_voice_agent.models.llama import LlamaConfig

    lacks = set(NEEDS) - set(LlamaConfig.__dataclass_fields__)
    if lacks:  # a program from before PR 50: say so and leave, before anything is built
        raise SystemExit(f"[benchmark] REFUSED: this program's LlamaConfig has no {sorted(lacks)}: "
                         "it cannot run a smallthinker configuration (a router that reads the "
                         "layer's input, ReGLU experts)")
    m, s = parse_stack.as_run(config, rehearsal)
    n = m["num_hidden_layers"]
    want = "".join("S" if one else "F" for one in config["sliding_window_layout"][:n])
    if (config["rope_layout"] != config["sliding_window_layout"]
            or (not rehearsal and str(m["layer_kinds"]) != want)):
        raise ValueError(f"layer_kinds {m['layer_kinds']!r} against the file's layouts {want!r} "
                         "(rope_layout and sliding_window_layout have to agree: a rotated layer "
                         "is a windowed one)")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
