"""Plain reference (a test's fixture): a Mixtral-style routed-expert
decoder in straightforward ``jax.numpy``, float32 at ``highest``.

Equations (Jiang et al. 2024, "Mixtral of Experts"): the dense decoder of
``reference/decoder.py`` with the feed-forward of every block replaced by

    p = softmax(Wr n2(a))                      over ALL experts
    S = the top-k experts of p, g_e = p_e / sum_{e' in S} p_e'
    h' = a + sum_{e in S} g_e . Wd_e (silu(Wg_e n2(a)) * (Wu_e n2(a)))

Every expert is computed on every token and the unchosen ones are weighted
by zero: plain, exact, and free of any capacity or dispatch order. The
attention half, the norms and the head are ``reference/decoder.py``'s."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import decoder as dense_ref

F32 = jnp.float32


@partial(jax.jit, static_argnames=("nq", "nkv", "eps", "theta", "window", "top_k", "fake_bits"))
def layer(h, pos, w, *, nq, nkv, eps, theta, window, top_k, fake_bits=None):
    with jax.default_matmul_precision("highest"):
        dense = partial(dense_ref.dense, fake_bits=fake_bits)
        a = dense_ref.attention(h, pos, w, dense, nq=nq, nkv=nkv, eps=eps, theta=theta,
                                window=window)
        x = dense_ref.rms_norm(a, w["mlp_norm"].astype(F32), eps)
        probs = jax.nn.softmax(x @ dense(w["router"]), axis=-1)  # (T, E)
        top, chosen = jax.lax.top_k(probs, top_k)
        gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], chosen].set(
            top / jnp.sum(top, axis=-1, keepdims=True))
        act = jax.nn.silu(jnp.einsum("td,edf->tef", x, dense(w["moe_gate"]))) \
            * jnp.einsum("td,edf->tef", x, dense(w["moe_up"]))
        out = jnp.einsum("tef,efd->ted", act, dense(w["moe_down"]))
        return a + jnp.einsum("te,ted->td", gates, out)


# ---- what the comparison reads (lib/refcheck.py) ----

SAMPLE = "paged_decoder"
CONTROL = "int4"
# A fixture's limit, read on the CPU at these test widths (no device number;
# tools/compare_seeds.py, 18 seeds, PR 26): the served engine 1.01-1.65 % of
# the logit range, the int4 control 67.6-91.6 %. 5 % is three times the
# sound runs' largest and a thirteenth of the control's smallest.
TOLERANCE = 0.05


def logits(params: dict, model: dict, sample: dict, control: bool = False):
    toks = sample["tokens"]
    return dense_ref.forward(params, toks, last=sample["rows"], pad_to=dense_ref.pad_len(len(toks)),
                             fake_bits=4 if control else None, block=layer,
                             top_k=int(model["num_experts_per_tok"]), **dense_ref.model_kw(model))
