"""Builder ``lfm2_stack``: the brain alone on a real socket with an LFM2
decoder with routed experts (``tpu_voice_agent.models.lfm2``: gated
short-convolution layers whose request state is a two-row tail beside six
grouped-query layers of 64-wide heads, two leading dense layers, then 32
bias-selected sigmoid experts 4 a token) behind it, served as the repo serves
any decoder — ``parse_stack.build`` with this model's two functions."""

from __future__ import annotations

# imported HERE and not where it is used: run.py asks every module a cell names
# to import before it builds anything, so a program without this model refuses
# the cell at once, exit 2
from tpu_voice_agent.models import lfm2

from . import parse_stack

# the embedding's standard deviation an element, the router's selection bias's
# and a routed expert's down projection over f^-0.5 (``make_params`` says why each)
EMBED_STD = 0.7
BIAS_STD = 0.1
ROUTED_GAIN = 0.1
# a mixer's out projection (W_out of a convolution layer, W_o of an attention layer) over fan_in^-0.5
MIXER_GAIN = 0.5

_KINDS = {"conv": "C", "full_attention": "F"}


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys ``m`` and the
    serving parameters ``s``: the first ``num_hidden_layers`` letters of
    ``layer_kinds`` (``layer_types``, a letter a layer)."""
    if not (m["model_type"] == "lfm2_moe" and m["use_expert_bias"] and not m["conv_bias"]
            and m["hidden_size"] % m["num_attention_heads"] == 0):
        raise ValueError("lfm2_stack builds the published block alone")
    return lfm2.Lfm2Config(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        pattern=m["layer_kinds"][:m["num_hidden_layers"]], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_size=m["hidden_size"] // m["num_attention_heads"],
        d_conv=m["conv_L_cache"], rope_theta=float(m["rope_theta"]), norm_eps=float(m["norm_eps"]),
        first_dense_layers=m["num_dense_layers"], dense_ffn_dim=m["intermediate_size"],
        n_experts=m["num_experts"], top_k=m["num_experts_per_tok"], ffn_dim=m["moe_intermediate_size"],
        norm_topk=bool(m["norm_topk_prob"]), router_scale=float(m["routed_scaling_factor"]),
        max_seq_len=s["max_len"])


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into the
    leaves the engine serves: the program's own ``lfm2.init_params`` with
    ``quant`` — layer by layer and expert by expert under ``lax.map``, each
    large matrix quantised per output channel as it is drawn, so no float32 or
    bf16 copy of the model ever exists; the tied head an int8 copy of the bf16
    embedding, a scale a vocabulary row. The recipe is that function's
    (matrices normal(0, fan_in^-0.5), taps normal(0, 1/3), q and k gains
    uniform in (0.5, 1.5), every other gain 1) with this file's three scales.

    ONLY THE RATIOS MATTER (every layer reads its input through a norm), and the
    ratio ``EMBED_STD`` : ``MIXER_GAIN`` decides two things at once. The head is
    TIED, so the input token's own logit grows with d where every other token's
    grows with sqrt(d): at 0.7 beside a residual stream that the 18 convolution
    mixers (an RMS of 0.5 each: B, C and u are unit normal behind the norm,
    three taps of variance 1/3) carry to ~2.3, the self term stands 45 x 0.7 /
    2.3 = 14 sigma over the rest — a plan repeats a character until the grammar
    stops it, and the texts share plans. And the mixer is CUBIC in its normed
    input (C * conv(B * u)): a mixer as large as the stream amplifies a
    rounding by the layer. Swept on the chip (my chip runs, PR 64,
    ``tools/recipe_check.py``, five ratios x three seeds, ``chiprun_out/p64/r.log``:
    ratio -> served against the float32 reference / int4 control, distinct
    plans of 64, tokens a plan): 0.3 : 1 -> 6.1-10.0 % / 83-177 %, 56-62, median
    96-168; 0.5 : 0.5 -> 3.2-3.7 % / 39-64 %, 14-62, median 42-353; 0.7 : 0.5 ->
    1.3 % / 28-36 %, 13-57, median 67-199; 0.5 : 0.3 -> 1.3 % / 20-31 %, 1-34, most
    plans never ending at one seed; 1 : 0.3 -> 0.4-0.6 % / 8-11 %, 7-56, 385-477
    tokens a plan at two seeds. THE FIRST RECIPE SERVED WAS 0.3 : 1 (weights_seed
    66: 56 distinct plans, median 117, 25.6 of 32 experts a layer touched a
    forward) and it FAILED THE CELL: two correct programs stand 3-4 % apart
    there (the block kernel's path against the XLA one from the same pools; a
    jitted forward against the same forward op by op), so a text's plan
    depends on who shares its forward, and 2-10 of ~820 requests a run ran into
    ``max_new_tokens`` 512 where all 64 plans had ended (34-402 tokens) when
    decoded together — six runs, every one ``correct``, none with 0 failed.
    0.7 : 0.5 is the calmest ratio whose plans still differ, so the cell keeps it
    and pays in plans that look alike (the file's ``weights_seed`` is the seed of
    eight with the most distinct plans among those whose 64 plans all end under
    330 tokens). No ``QK_GAIN``: q and k are normed a HEAD, so a score is unit
    normal times the two gains whatever W_q is, and the three taps of 18 layers
    carry a suffix's text 36 positions forward by themselves.

    ``ROUTED_GAIN`` 0.1 on a routed expert's DOWN projection is
    ``nemotron_h_stack``'s, ``moonlight_stack``'s lesson at this model's gates:
    four renormalised sigmoid scores put a quarter of an expert's output on
    each pick, and a fourth pick that flips on a near tie — the bf16 program's
    router against the float32 reference's — must move the stream by a percent,
    not ten. A checkpoint's layers are small beside its residual stream; a
    seeded one has to be given that.

    ``BIAS_STD`` 0.1: the selection bias is NONZERO (``nemotron_h_stack``'s:
    half the spread of a sigmoid score behind a unit-normal logit), so that
    the experts chosen by s + b and the gates made of s alone differ. At 0.2,
    the spread of the scores themselves, the four largest biases chose for
    every token: 18-20 of 32 experts a layer touched by ~180 picks a forward,
    ``moe_load_max_over_mean`` 5.6 (my chip run, PR 64)."""
    import jax

    make = jax.jit(lambda key: lfm2.init_params(cfg, key, quant=True, embed_std=EMBED_STD,
                                                bias_std=BIAS_STD, routed_gain=ROUTED_GAIN,
                                                mixer_gain=MIXER_GAIN))
    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    """``layer_kinds`` has to be ``layer_types`` (the harness hands builders and
    references the file's scalar keys: the list is stated once more as letters),
    before anything is built."""
    n = config["num_hidden_layers"]
    want = "".join(_KINDS[k] for k in config["layer_types"][:n])
    if not rehearsal and config["layer_kinds"][:n] != want:
        raise ValueError(f"layer_kinds {config['layer_kinds']!r} against the file's layer_types {want!r}")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
