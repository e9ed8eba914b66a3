"""Builder ``olmo_hybrid_stack``: the brain alone on a real socket with an OLMo
hybrid decoder (``tpu_voice_agent.models.olmo_hybrid``: Gated-DeltaNet layers
with a matrix state a head beside position-free full-attention layers, under
the reordered norm) behind it, served as the repo serves any decoder —
``parse_stack.build`` with this model's two functions."""

from __future__ import annotations

# imported HERE and not where it is used: run.py asks every module a cell names
# to import before it builds anything, so a program without this model refuses
# the cell at once, exit 2
from tpu_voice_agent.models import olmo_hybrid

from . import parse_stack

# the embedding's standard deviation an element (``olmoe_stack``'s, whose head
# is untied too: the layers, not the input token's own embedding, decide the
# next token) and the gain of the norm on every sub-layer's OUTPUT
# (``make_params`` says why)
EMBED_STD = 3.0
MIXER_GAIN = 0.3

_KINDS = {"linear_attention": "L", "full_attention": "F"}


def llama_config(m: dict, s: dict):
    """The program's configuration from the source's keys ``m`` and the
    serving parameters ``s``: the first ``num_hidden_layers`` letters of
    ``layer_kinds`` (``layer_types``, a letter a layer)."""
    if not (m["hidden_act"] == "silu" and not m["attention_bias"] and not m["tie_word_embeddings"]
            and m["linear_num_key_heads"] == m["linear_num_value_heads"]
            and m["hidden_size"] % m["num_attention_heads"] == 0):
        raise ValueError("olmo_hybrid_stack builds the published block alone")
    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        pattern=m["layer_kinds"][:m["num_hidden_layers"]], ffn_dim=m["intermediate_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_size=m["hidden_size"] // m["num_attention_heads"],
        gdn_heads=m["linear_num_value_heads"], gdn_key_dim=m["linear_key_head_dim"],
        gdn_value_dim=m["linear_value_head_dim"], d_conv=m["linear_conv_kernel_dim"],
        neg_eigval=bool(m["linear_allow_neg_eigval"]), norm_eps=float(m["rms_norm_eps"]),
        max_seq_len=s["max_len"])


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into the
    leaves the engine serves: the program's own ``olmo_hybrid.init_params``
    with ``quant`` — layer by layer under ``lax.map``, each large matrix
    quantised per output channel as it is drawn, so no float32 or bf16 copy of
    the model ever exists. The recipe is that function's (matrices normal(0,
    fan_in^-0.5); ``A_log`` and ``dt_bias`` by the published Gated-DeltaNet
    initialisation; convolutions normal(0, 1/2) without bias) with this file's
    two scales. Under the REORDERED norm a sub-layer's size beside the residual
    stream is its output norm's gain and nothing else — a matrix's scale
    divides out —, so ``MIXER_GAIN`` is the whole of what ``ROUTED_GAIN`` and
    the (2 L)^-0.5 of other builders tune: at 1 each of the 64 sub-layers adds
    a unit-RMS vector to a stream that starts at ``EMBED_STD`` 3 (``olmoe_stack``'s,
    for its reason) and ends near (9 + 64)^0.5 = 8.5."""
    import jax

    make = jax.jit(lambda key: olmo_hybrid.init_params(cfg, key, quant=True, embed_std=EMBED_STD,
                                                       mixer_gain=MIXER_GAIN))
    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    """``layer_kinds`` has to be ``layer_types`` (the harness hands builders and
    references the file's scalar keys: the list is stated once more as letters)
    and the rotary base null, before anything is built."""
    n = config["num_hidden_layers"]
    want = "".join(_KINDS[k] for k in config["layer_types"][:n])
    if not rehearsal and config["layer_kinds"][:n] != want:
        raise ValueError(f"layer_kinds {config['layer_kinds']!r} against the file's layer_types {want!r}")
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("olmo_hybrid_stack builds position-free full layers alone (rope_theta null)")
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
