"""Builder ``sambay_stack``: the brain alone on a real socket with a SambaY
decoder-hybrid-decoder (``tpu_voice_agent.models.sambay``) behind it, served
as the repo serves any decoder: ``PagedDecodeEngine`` behind
``brain._wrap_batched`` (cached prompt prefix, continuous batcher).

WHY THIS FILE DOES NOT CALL ``parse_stack.build``: until PR 42
``parse_stack.model_dims`` REFUSED a configuration whose ``sliding_window``
is at or under its ``max_len`` (written when ``models/llama.py`` had no
window); this model's 512-token window binds from the first decoded token
(the cached prefix alone is 879) and ``models/sambay.py`` implements it, so
``build`` below is ``parse_stack.build`` / ``build_parser`` again without
that check, on ``parse_stack``'s own ``as_run`` and ``Served``
(``apply_env`` is run.py's own call). Since PR 42 the refusal asks the
program's configuration (``parse_stack.refuse_unserved_window``: this
model's carries its ``window``), so this ``build`` could be a one-line call
of ``parse_stack.build``; it was left as it is so that no accepted cell's
path changed in the PR that moved the refusal.
"""

from __future__ import annotations

import time

# imported HERE and not where it is used: run.py asks every module a cell names
# to import before it builds anything (after the configuration's environment is
# set), so a program without this model refuses the cell at once, exit 2
from tpu_voice_agent.models import sambay

from .parse_stack import Served, as_run


def model_dims(config: dict, rehearsal: bool) -> dict:
    model, serving = as_run(config, rehearsal)
    return {"model": model, "serving": serving}


def sambay_config(m: dict, s: dict):
    """The program's configuration from the source's keys ``m`` (the
    state-space sizes are the file's ``ssm_*`` keys, listed there under
    ``assumed``) and the serving parameters ``s``."""
    return sambay.SambaYConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], n_layers=m["num_hidden_layers"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        ffn_dim=m["intermediate_size"], max_seq_len=s["max_len"],
        norm_eps=float(m["layer_norm_eps"]), window=m["sliding_window"],
        d_inner=m["ssm_d_inner"], d_state=m["ssm_d_state"], d_conv=m["ssm_d_conv"],
        dt_rank=m["ssm_dt_rank"])


def make_params(cfg, seed: int):
    """Seeded weights made on the device in ONE jitted call, straight into
    the leaves the engine serves: the program's own ``sambay.init_params``,
    period by period under ``lax.map``, each large projection quantised per
    output channel (``sambay.quantize_layer``) as it is drawn, so no float32
    or bf16 copy of the model ever exists. The recipe is ``sambay.init_layer``'s
    (matrices normal(0, fan_in^-0.5); A_log, dt_bias and D by the published
    state-space initialisation; lambda vectors normal(0, 0.1); norms at
    gain 1, bias 0); the head is an int8 copy of the tied bf16 embedding."""
    import jax

    from tpu_voice_agent.models.llama import quantize_leaf

    @jax.jit
    def make(key):
        params = sambay.init_params(cfg, key, each=sambay.quantize_layer)
        return {**params, "lm_head": quantize_leaf(params["embed"].T)}

    return make(jax.random.key(seed, impl="rbg"))  # the hardware generator, as parse_stack's


def build_parser(config: dict, rehearsal: bool, say):
    """``parse_stack.build_parser`` with this model's configuration and
    weights, and no window refusal."""
    import jax

    from tpu_voice_agent.grammar.intent_grammar import default_tokenizer
    from tpu_voice_agent.serve import PagedDecodeEngine
    from tpu_voice_agent.services.brain import _wrap_batched

    dims = model_dims(config, rehearsal)
    m, s = dims["model"], dims["serving"]
    t0 = time.perf_counter()
    engine = PagedDecodeEngine(
        cfg=sambay_config(m, s), tokenizer=default_tokenizer(), quant=s["quant"],
        batch_slots=s["batch_slots"], block_size=s["block_size"], pool_blocks=s["pool_blocks"],
        max_len=s["max_len"], prefill_buckets=tuple(s["prefill_buckets"]),
        fast_forward=s["fast_forward"], init_weights=False)
    t1 = time.perf_counter()
    engine.load_params(make_params(engine.cfg, s["weights_seed"]))
    jax.block_until_ready(engine.params)
    t2 = time.perf_counter()
    parser = _wrap_batched(engine)  # installs the prompt prefix, starts the serving loop
    say(f"decoder: engine+tables {t1 - t0:.1f}s, weights {t2 - t1:.1f}s, prefix "
        f"({len(engine.prefix_ids)} tokens)+batcher {time.perf_counter() - t2:.1f}s, "
        f"vocab {engine.cfg.vocab_size}, pool {s['pool_blocks']} blocks, kernels {engine.kernels}")
    return parser, dims


def build(config: dict, rehearsal: bool, say) -> Served:
    from tpu_voice_agent.services import warm_up
    from tpu_voice_agent.services.brain import build_app
    from tpu_voice_agent.services.stack import AppServer

    parser, dims = build_parser(config, rehearsal, say)
    t0 = time.perf_counter()
    warm_up(parser)
    say(f"decoder warm-up {time.perf_counter() - t0:.1f}s")
    brain = AppServer(build_app(parser)).__enter__()
    return Served({"brain": brain.url}, parser, dims,
                  [lambda: brain.__exit__(None, None, None), parser.close])
