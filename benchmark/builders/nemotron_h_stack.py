"""Builder ``nemotron_h_stack``: the brain alone on a real socket with a
Nemotron-H hybrid decoder (``tpu_voice_agent.models.nemotron_h``: Mamba-2
state beside two attention layers, latent relu2 experts of which this chip
holds a share) behind it, served as the repo serves any decoder —
``parse_stack.build`` with this model's two functions."""

from __future__ import annotations

# imported HERE and not where it is used: run.py asks every module a cell names
# to import before it builds anything, so a program without this model refuses
# the cell at once, exit 2
from tpu_voice_agent.models import nemotron_h

from . import parse_stack

# the embedding's standard deviation an element and the router's selection
# bias's (``olmoe_stack``'s and ``moonlight_stack``'s, whose heads are untied
# too: the layers, not the input token's own embedding, decide the next token)
EMBED_STD = 3.0
BIAS_STD = 0.1
# a routed expert's down projection over f^-0.5 (``make_params`` says why)
ROUTED_GAIN = 0.1


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys ``m`` and the
    serving parameters ``s``: the first ``num_hidden_layers`` characters of
    the published pattern, the router at its published width, the experts
    held here from ``first_expert`` on."""
    if not (m["mlp_hidden_act"] == "relu2" and m["mamba_hidden_act"] == "silu" and m["n_group"] == 1
            and m["topk_group"] == 1 and m["n_shared_experts"] == 1 and m["use_conv_bias"]
            and not (m["mamba_proj_bias"] or m["use_bias"] or m["attention_bias"] or m["mlp_bias"]
                     or m["tie_word_embeddings"])):
        raise ValueError("nemotron_h_stack builds the published block alone")
    router = m.get("n_routed_experts_published", m["n_routed_experts"])
    held = m["n_routed_experts"]
    return nemotron_h.NemotronHConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        pattern=m["hybrid_override_pattern"][:m["num_hidden_layers"]],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"], head_size=m["head_dim"],
        mamba_heads=m["mamba_num_heads"], mamba_head_dim=m["mamba_head_dim"], n_groups=m["n_groups"],
        d_state=m["ssm_state_size"], d_conv=m["conv_kernel"], n_experts=router,
        top_k=m["num_experts_per_tok"], experts_held=held if held < router else 0,
        first_expert=m.get("first_expert", 0), moe_latent=m["moe_latent_size"],
        ffn_dim=m["moe_intermediate_size"], shared_ffn_dim=m["moe_shared_expert_intermediate_size"],
        norm_topk=bool(m["norm_topk_prob"]), router_scale=float(m["routed_scaling_factor"]),
        norm_eps=float(m["norm_eps"]), group_norm_eps=float(m["layer_norm_epsilon"]),
        max_seq_len=s["max_len"])


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into the
    leaves the engine serves: the program's own ``nemotron_h.init_params``
    with ``quant`` — layer by layer and expert by expert under ``lax.map``,
    each large matrix quantised per output channel as it is drawn, so no
    float32 or bf16 copy of the model ever exists. The recipe is that
    function's (matrices normal(0, fan_in^-0.5); A, dt_bias and D by the
    published Mamba-2 initialisation; norms at gain 1) with this file's three
    scales. ``EMBED_STD`` 3 is ``olmoe_stack``'s, for its reason. ``ROUTED_GAIN``
    0.1 on a routed expert's DOWN projection is ``moonlight_stack``'s lesson at
    this model's gates: 22 renormalised sigmoid scores times 5 put FIVE experts'
    worth on every token (Moonlight's six times 2.446: 2.4; OLMoE's eight
    unrenormalised softmax weights: ~0.45), so 0.1 gives a routed layer about
    OLMoE's size beside the residual stream. At a gain of 1 a 22nd pick that
    flips on a near tie — the bf16 program's router against the float32
    reference's — cascades through the later layers' routers: rows of the
    comparison read 1-30 % of the logit range, its worst row 15-30 % over eight
    samples, the int4 control 54-62 %, and 16 of 64 corpus plans never ended; at
    0.1 every row reads 0.8-1.6 % and the control 43-51 % (my chip runs, PR 47:
    ``tools/recipe_check.py``, PERF.md section 6). A checkpoint's layers are
    small beside its residual stream; a seeded one has to be given that."""
    import jax

    make = jax.jit(lambda key: nemotron_h.init_params(cfg, key, quant=True, embed_std=EMBED_STD,
                                                      bias_std=BIAS_STD, routed_gain=ROUTED_GAIN))
    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
