"""Builder ``routed_stack`` (a test's fixture): ``parse_stack`` with a
routed-expert decoder — its two model-specific functions and a call."""

from __future__ import annotations

import dataclasses

from . import parse_stack


def llama_config(m: dict, s: dict):
    """The dense keys as ``parse_stack`` reads them, plus the routed ones;
    ``capacity_factor`` = E / K is the program's drop-free setting."""
    experts, top_k = m["num_local_experts"], m["num_experts_per_tok"]
    return dataclasses.replace(parse_stack.dense_llama_config(m, s), n_experts=experts,
                               top_k=top_k, capacity_factor=experts / top_k)


def make_params(cfg, seed: int):
    """Test widths: the program's own initialiser; the engine quantises the
    tree to int8 as it loads it (``load_params``)."""
    import jax

    from tpu_voice_agent.models.llama import init_params

    return init_params(cfg, jax.random.PRNGKey(seed))


def build(config: dict, rehearsal: bool, say) -> parse_stack.Served:
    return parse_stack.build(config, rehearsal, say, llama_config=llama_config,
                             make_params=make_params)
