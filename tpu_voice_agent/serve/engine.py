"""Grammar-constrained decode engine.

Replaces the reference's OpenAI chat.completions call (apps/brain/src/llm.ts:
19-30) with an in-tree Llama decode on the local device/mesh:

- prompt prefill at bucketed lengths (one XLA program per bucket)
- per-step fused [forward -> grammar logit mask -> sample -> FSM advance] as
  a single jitted function: the FSM mask/next-state tables live in HBM and
  are indexed by per-sequence state — no host round-trip per token
- greedy or temperature sampling; grammar constraint guarantees the output
  parses (the reference's repair loop, server.ts:110-121, becomes dead code)
"""

from __future__ import annotations

import os
import time
from typing import Any
from dataclasses import dataclass, replace, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..grammar.fsm import DeviceFSM, fsm_advance, fsm_row
from ..grammar.intent_grammar import build_fsm_for, build_intent_fsm
from ..models.family import family
from ..models.llama import LlamaConfig, PRESETS, forward, init_kv_cache, init_params
from ..ops.backend import resolve_kernels
from ..parallel.mesh import default_rules, kv_cache_shardings, param_shardings
from ..utils.compilewatch import get_compile_watcher, watch_compiles
from ..utils.steplog import (
    ALLOC_SPAN,
    FIRST_TOKEN_SPAN,
    PREFILL_CALL_SPAN,
    PREFILL_STAGE_SPAN,
    span,
)


def byte_len_table_for(tokenizer, vocab_size: int) -> jnp.ndarray:
    """(V,) int32 bytes each token id contributes to decoded output — the
    device-side table the byte-budget stop condition gathers from. Shared
    by DecodeEngine and serve.planner (one copy of the accounting)."""
    return jnp.asarray(np.array(
        [len(tokenizer.token_bytes(i)) for i in range(vocab_size)], dtype=np.int32))


@dataclass
class GenerationResult:
    text: str
    token_ids: list[int]
    prefill_ms: float
    decode_ms: float
    steps: int  # EMITTED tokens — under multi-token stepping (grammar
    # fast-forward) this counts accepted output tokens, never forward
    # dispatches (those are `forwards`)
    finished: bool  # True only if EOS was reached (truncation => False)
    error: str | None = None  # per-request failure (e.g. prompt too long)
    forwards: int = 0  # decode forward dispatches (< steps under grammar
    # fast-forward, where one forward emits several accepted tokens)
    cached_tokens: int = 0  # prompt tokens served from cached KV at
    # admission (static prefix cache or radix chain hit) — prefill_ms
    # covers only the COMPUTED suffix, so the two together describe the
    # admission honestly (conflating them was the old prefill_ms bug)
    prompt_tokens: int = 0  # prompt length in tokens — with cached_tokens
    # it yields the outstanding-prefill measurement the voice service's
    # endpoint gauge needs (ISSUE 15 satellite)
    queue_ms: float = 0.0  # submit() -> popped from the batcher's queue by
    # step(): the wait for a slot, which ``scheduler.ttft`` hides inside
    # itself (0 outside the continuous batcher)
    quality: dict | None = None  # per-request confidence vector (ISSUE 15):
    # masked-logit margin mean/min, entropy mean, grammar-forced fraction,
    # decision count — None when the quality lanes are off or no decision
    # was sampled (utils.quality.conf_summary builds it)
    cost: dict | None = None  # per-request resource ledger (ISSUE 17):
    # utils.costmodel.LEDGER_KEYS ints (prefill FLOPs split cached vs
    # computed, decode FLOPs + KV bytes, KV block-microseconds held) —
    # None when COST_ENABLE=0 or the request ran outside the continuous batcher. Errored/evicted rows still
    # carry the cost they spent before dying (the ledger conserves).

    @property
    def tokens_per_s(self) -> float:
        # zero/negative-duration guard: a fully fast-forwarded generation
        # can finish inside timer resolution — report 0 rather than raise/inf
        return self.steps / (self.decode_ms / 1e3) if self.decode_ms > 0 else 0.0


@dataclass(frozen=True)
class ChunkResult:
    """What one ``decode_chunk`` hands back, as a VALUE: a caller may hold
    one chunk's record while it dispatches the next, and nothing of a chunk
    is left on the engine. A field that is None is an empty pytree leaf, so
    ONE ``jax.device_get`` over the fields a caller wants reads them all in
    one transfer, with no placeholder for what this engine does not report."""

    # the batcher's per-slot state after the chunk, and what it emitted
    out: Any  # (B, cap) emitted token ids, pad-filled
    n: Any  # (B,) EMITTED tokens per row — never forwards
    eos: Any  # (B,) the row reached EOS (truncation leaves it False)
    cur: Any
    pos: Any
    fsm: Any
    active: Any
    nbytes: Any
    tokens_left: Any
    fwds: Any  # forward dispatches of the chunk: the denominator that keeps
    # tokens-per-forward truthful when one forward emits several tokens
    # (grammar fast-forward)
    poison: Any  # (B,) per-row fault code the quarantine evicts on:
    # 0 ok / 1 non-finite logits / 2 grammar dead state
    rows: int  # the width the chunk was dispatched at: ``batch_slots``,
    # or the paged engine's ``compact_rows``
    conf: tuple | None = None  # the ISSUE 15 per-row confidence lanes
    # (margin sum/min, entropy sum, forced, decisions); None with them off
    counts: dict = field(default_factory=dict)  # what the chunk program counted, by the names
    # of the model's record (``models.family.Family.counts``, and ``ffn`` from a
    # program whose position-wise regions may run packed): each an int32 vector
    # summed over the chunk's forwards, ONE leaf of the caller's readback; empty
    # from an engine whose loop counts nothing
    ffn_rows: int = 0  # the rows an UNPACKED forward's MLPs compute (width x
    # positions a row); 0: this engine does not say


def _mask_sample_advance(logits, fsm_state, tables: DeviceFSM, key, temperature,
                         greedy: bool, constrained: bool, kernels: str = "xla",
                         rules=None, logit_mask=None):
    """The one sampling block: grammar-mask logits, pick a token, advance the
    FSM. Shared by the fused decode step, the prefill first-token pick, and
    the device generation loop (jit-inlined at every call site).

    ``tables`` is the column-compressed DeviceFSM (grammar.fsm): the vocab
    row is recovered with two gathers XLA fuses into the masking loop, so
    the layout survives 128k-vocab checkpoints. kernels="pallas" routes the
    greedy constrained path through the fused ops.masked_argmax kernel when
    the dense (S, V) mask is small enough to exist (toy vocabs); otherwise
    the compressed XLA path runs even under kernels="pallas". On a mesh
    (rules given) the kernel runs per-shard under shard_map."""
    if constrained and greedy and kernels == "pallas" and tables.dense_mask is not None:
        from ..ops import sharded_masked_argmax_advance

        # ONE fused kernel for the whole tail (ISSUE 12): grammar mask +
        # argmax + FSM advance — the compressed transition row rides the
        # same scalar-prefetch indirection as the mask tiles, so the two
        # XLA advance gathers disappear into the kernel. For live states
        # the result is exactly masked_argmax + fsm_advance (differential-
        # tested); dead states are fenced by the poison gate either way.
        mesh = rules.mesh if rules is not None else None
        with jax.named_scope("grammar_mask_sample"):
            return sharded_masked_argmax_advance(
                mesh, logits, fsm_state, tables.dense_mask, tables.table,
                tables.col_id)
    with jax.named_scope("grammar_mask_sample"):
        if logit_mask is not None:
            # padded-vocab ids (mesh tp padding / checkpoint embed padding)
            # have real logits (zero columns -> 0.0) but no tokenizer
            # meaning: dead under the grammar, they must also be
            # unsampleable unconstrained
            logits = jnp.where(logit_mask[None, :], logits, -jnp.inf)
        if constrained:
            row = fsm_row(tables, fsm_state)  # (B, V) int32 next states; -1 dead
            logits = jnp.where(row >= 0, logits, -jnp.inf)
        if greedy:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            tok = jax.random.categorical(key, logits / jnp.maximum(temperature, 1e-4)).astype(jnp.int32)
    if constrained:
        with jax.named_scope("fsm_advance"):
            fsm_state = jnp.take_along_axis(row, tok[:, None], axis=-1)[:, 0]
    return tok, fsm_state


# margin assigned to a forced decision (one legal token: the gap is +inf;
# the cap keeps windowed means finite and comparable across grammars)
QUALITY_MARGIN_CAP = 30.0


@jax.named_scope("quality_lanes")
def _conf_stats(raw, state, tables: DeviceFSM, constrained: bool, logit_mask):
    """Masked-logit confidence of ONE sampling decision per row — the
    quality observatory's intent lanes (ISSUE 15): top1−top2 margin of the
    masked logits, entropy of the masked softmax, and the forced flag
    (grammar leaves a single legal token). THE one copy shared by the
    dense and paged chunk loops (jit-inlined at every call site). Pure
    readback arithmetic over values the loops already computed — nothing feeds back into sampling, so tokens are
    identical with the lanes on or off (tests/test_quality.py holds that
    differentially per plane)."""
    lg = raw.astype(jnp.float32)
    if logit_mask is not None:
        lg = jnp.where(logit_mask[None, :], lg, -jnp.inf)
    if constrained:
        row = fsm_row(tables, jnp.maximum(state, 0))
        legal = (row >= 0) & (state >= 0)[:, None]
        lg = jnp.where(legal, lg, -jnp.inf)
        nlegal = jnp.sum(legal, axis=-1)
    else:
        nlegal = jnp.sum(jnp.isfinite(lg), axis=-1)
    return _masked_conf(lg, nlegal)


def _masked_conf(lg, nlegal):
    """The reduction half of ``_conf_stats`` over ALREADY-masked f32
    logits.

    The margin's top-2 is SELECTED by reductions, never sorted:
    ``lax.top_k`` lowers on the TPU to a sort of the whole row (7.9 ms a
    forward at 200064 logits, for two numbers). The runner-up is the
    maximum again if it occurs twice, else the largest value under it —
    elements of ``lg`` both, so the lanes are the sort's bit for bit
    (tests/test_quality.py): a tie at the maximum gives 0, one legal token
    the cap. A row holding a NaN (fenced by the poison gate, its lanes
    never accumulated) reads margin 0 and entropy 0."""
    m1 = jnp.max(lg, axis=-1)
    m2 = jnp.where(jnp.sum(lg == m1[:, None], axis=-1) > 1, m1,
                   jnp.max(jnp.where(lg < m1[:, None], lg, -jnp.inf), axis=-1))
    margin = jnp.where(jnp.isfinite(m2),
                       jnp.minimum(m1 - m2, QUALITY_MARGIN_CAP),
                       QUALITY_MARGIN_CAP)
    # a dead row (no legal token at all) carries no signal; it is fenced
    # by the poison gate anyway — zero keeps the lane NaN-free
    margin = jnp.where(jnp.isfinite(m1), margin, 0.0)
    p = jax.nn.softmax(lg, axis=-1)
    ent = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-30)), 0.0),
                   axis=-1)
    ent = jnp.where(jnp.isfinite(m1), ent, 0.0)
    return margin, ent, nlegal <= 1


@jax.named_scope("quality_lanes")
def _conf_accumulate(conf, ok, margin, ent, forced_one, forced_extra=None):
    """Fold one decision into the per-row conf lanes ``(margin_sum,
    margin_min, entropy_sum, forced, decisions)``. ``forced_extra`` adds
    grammar-forced chain tokens (ff positions count elsewhere)."""
    msum, mmin, esum, forced, cnt = conf
    msum = msum + jnp.where(ok, margin, 0.0)
    mmin = jnp.where(ok, jnp.minimum(mmin, margin), mmin)
    esum = esum + jnp.where(ok, ent, 0.0)
    forced = forced + jnp.where(ok & forced_one, 1, 0)
    if forced_extra is not None:
        forced = forced + forced_extra
    cnt = cnt + ok.astype(jnp.int32)
    return msum, mmin, esum, forced, cnt


def _conf_init(B):
    """Fresh per-row conf lanes (margin_min starts at +inf; the host
    readback treats inf as 'no decisions')."""
    return (jnp.zeros((B,), jnp.float32),
            jnp.full((B,), jnp.inf, jnp.float32),
            jnp.zeros((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32))


def _poison_gate(raw, state, state_next, active, poison, constrained: bool):
    """THE one copy of the per-row fault check, shared by the dense AND
    paged chunk loops (plain + ff bodies — jit-inlined at every call site):
    non-finite raw logits (pre-mask — the grammar mask writes -inf on
    purpose) and dead FSM transitions (entry state or post-advance state
    below zero; only meaningful under constrained decoding). Returns
    (ok, poison): ``ok`` is active minus this step's poisoned rows —
    poisoned rows must NOT commit the faulty sample, so batch-mates'
    carries stay untouched. Poison codes: 1 = NaN/inf, 2 = dead FSM
    (sticky via max across steps)."""
    with jax.named_scope("poison_gate"):
        nanp = active & ~jnp.all(jnp.isfinite(raw), axis=-1)
        if constrained:
            deadp = active & ~nanp & ((state < 0) | (state_next < 0))
        else:
            deadp = jnp.zeros_like(active)
        poison = jnp.maximum(poison, jnp.where(nanp, 1, jnp.where(deadp, 2, 0)))
        return active & ~(nanp | deadp), poison


@watch_compiles("engine._decode_step")
@partial(jax.jit, static_argnames=("cfg", "rules", "greedy", "constrained", "kernels"))
def _decode_step(
    params,
    cfg: LlamaConfig,
    cache,
    token,  # (B,) int32 current token
    pos,  # (B,) int32 its position
    fsm_state,  # (B,) int32
    tables: DeviceFSM,
    key,
    temperature,
    rules=None,
    greedy: bool = True,
    constrained: bool = True,
    kernels: str = "xla",
    logit_mask=None,
):
    logits, cache = forward(params, cfg, token[:, None], pos[:, None], cache, rules,
                            attn_impl=kernels)
    nxt, fsm_state = _mask_sample_advance(
        logits[:, 0, :], fsm_state, tables, key, temperature, greedy,
        constrained, kernels, rules, logit_mask
    )
    return nxt, cache, fsm_state


@watch_compiles("engine._first_token")
@partial(jax.jit, static_argnames=("greedy", "constrained", "kernels", "rules"))
def _first_token(last_logits, fsm_state, tables: DeviceFSM, key, temperature,
                 greedy: bool = True, constrained: bool = True, kernels: str = "xla",
                 rules=None, logit_mask=None):
    return _mask_sample_advance(
        last_logits, fsm_state, tables, key, temperature, greedy,
        constrained, kernels, rules, logit_mask
    )


@watch_compiles("engine.prefill_row")
@partial(
    jax.jit,
    static_argnames=("cfg", "rules", "kernels", "fresh"),
    donate_argnames=("cache",),
)
def prefill_row(
    params,
    cfg: LlamaConfig,
    cache,  # full (L, B, S, nkv, hd) cache — only row `slot` is touched
    tokens,  # (1, T) int32
    positions,  # (1, T) int32
    slot,  # scalar int32 — which batch row to prefill
    rules=None,
    kernels: str = "xla",
    fresh: bool = True,  # sequence starts at position 0 (enables flash path)
):
    """Admission prefill for ONE batch slot.

    The forward runs over a (1, T) block against just that slot's cache
    line, so admission cost is independent of batch width — prefilling the
    full (B, bucket) batch to admit one row burned B× the FLOPs (the
    round-1 scheduler did exactly that). The cache is donated: XLA aliases
    the buffer and the row update happens in place.
    """
    k = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
    v = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)
    logits, row = forward(params, cfg, tokens, positions, {"k": k, "v": v},
                          rules, attn_impl=kernels, fresh_block=fresh)
    return logits, {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], row["k"], slot, axis=1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], row["v"], slot, axis=1),
    }


@watch_compiles("engine.prefill_row_with_prefix")
@partial(
    jax.jit,
    static_argnames=("cfg", "rules", "kernels"),
    donate_argnames=("cache",),
)
def prefill_row_with_prefix(
    params,
    cfg: LlamaConfig,
    cache,
    prefix_k,  # (L, 1, P, nkv, hd) — precomputed shared-prefix KV
    prefix_v,
    tokens,  # (1, T) suffix tokens (padded to a suffix bucket)
    positions,  # (1, T) absolute positions, starting at P
    slot,
    rules=None,
    kernels: str = "xla",
):
    """Admission prefill reusing a cached shared prefix (system prompt +
    few-shots). Copies the prefix KV into the slot's cache line and runs the
    forward over ONLY the user suffix — per-request prefill cost becomes
    proportional to what actually differs between requests (VERDICT round-1
    next-step #3; the reference pays its LLM vendor for the full prompt
    every call, apps/brain/src/llm.ts:19-30)."""
    k = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
    v = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)
    k = jax.lax.dynamic_update_slice(k, prefix_k, (0, 0, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(v, prefix_v, (0, 0, 0, 0, 0))
    logits, row = forward(params, cfg, tokens, positions, {"k": k, "v": v},
                          rules, attn_impl=kernels, fresh_block=False)
    return logits, {
        "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], row["k"], slot, axis=1),
        "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], row["v"], slot, axis=1),
    }


def chain_block(iw, cur, chain, k, active, pad_id, pos):
    """Block tokens/positions for a (B, 1+W) chain step: ``[cur,
    chain_0..k-1]`` with the tail duplicating the last valid (token,
    position) — duplicate (token, position) scatter writes are idempotent
    on the cache, so padding never scribbles junk over live KV. The
    grammar fast-forward loops' one copy of this construction: returns
    (step_tok, blk_tok, blk_pos)."""
    ci = jnp.clip(iw - 1, 0, jnp.maximum(k[:, None] - 1, 0))
    chain_tok = jnp.take_along_axis(chain, ci, axis=1)
    step_tok = jnp.where(active, cur, pad_id)
    blk_tok = jnp.where(iw == 0, step_tok[:, None],
                        jnp.where(k[:, None] > 0, chain_tok, step_tok[:, None]))
    write_pos = jnp.where(active, pos, 0)
    blk_pos = write_pos[:, None] + jnp.minimum(iw, k[:, None])
    return step_tok, blk_tok, blk_pos


def chain_byte_cap(k, chain, cur_tok, nbytes, byte_len_table, byte_budget):
    """Cap a chain length so its cumulative bytes still fit after
    ``cur_tok``'s: the plain path overshoots the byte budget by at most
    one token (stop is checked after the add), so chain tokens may only
    be taken while they still fit. The dense and paged ff loops share this
    contract. Returns (capped k, per-token cumulative bytes)."""
    chain_bytes = jnp.cumsum(
        jnp.where(chain >= 0, byte_len_table[jnp.maximum(chain, 0)], 0), axis=1)
    rem = (byte_budget - nbytes - byte_len_table[jnp.maximum(cur_tok, 0)])[:, None]
    return jnp.minimum(k, jnp.sum(chain_bytes <= rem, axis=1)), chain_bytes


@watch_compiles("engine.chunk_decode_loop")
@partial(
    jax.jit,
    static_argnames=("cfg", "rules", "chunk_steps", "greedy", "constrained", "kernels",
                     "eos_id", "pad_id", "unroll", "fwd", "max_len",
                     "quality_lanes"),
    donate_argnames=("cache",),
)
def chunk_decode_loop(
    params,
    cfg: LlamaConfig,
    cache,
    cur,  # (B,) current token per row
    pos,  # (B,) next write slot per row
    fsm_state,  # (B,) int32
    active,  # (B,) bool -- row is mid-generation
    nbytes,  # (B,) bytes emitted so far
    tokens_left,  # (B,) remaining token budget per row
    tables: DeviceFSM,
    byte_len_table,  # (V,) int32 bytes each token contributes
    key,
    temperature,
    byte_budget: jax.Array,  # scalar int32
    rules=None,
    logit_mask=None,  # (V,) bool; False = unsampleable (padded-vocab ids)
    nan_inject=None,  # (B,) bool or None — chaos drill: overwrite flagged
    # rows' logits with NaN so the poison guard's containment is testable.
    # None (production) keeps the traced program identical to pre-chaos.
    chunk_steps: int = 32,
    greedy: bool = True,
    constrained: bool = True,
    kernels: str = "xla",
    eos_id: int = 2,  # the serving tokenizer's ids (checkpoint-specific)
    pad_id: int = 0,
    unroll: int = 1,  # layer-scan unroll inside each decode step
    fwd=None,  # optional forward override: (params, cache, tokens,
    # positions) -> (logits, cache). The pp×tp engine injects its staged
    # pipeline forward here; None = models.llama.forward (dense cache).
    max_len: int | None = None,  # cache capacity; None = dense layout's
    # cache["k"].shape[2] (a non-dense layout MUST pass it — the staged pp
    # cache has batch at axis 2)
    quality_lanes: bool = False,  # ISSUE 15: accumulate per-row masked-
    # logit margin/entropy/forced lanes for the quality observatory. Pure
    # readback arithmetic — sampling is untouched, tokens identical either
    # way (differential-tested); False keeps the lanes as inert zeros.
):
    """THE decode loop: advance every active row by up to chunk_steps tokens
    entirely on device.

    One host dispatch per chunk -- a per-token host round trip idles the
    device between steps. Single-request generation calls this with
    B=1 and chunk_steps=max_new_tokens; the continuous batcher calls it with
    B=slots and a small chunk so new requests join at chunk boundaries. Idle
    rows park their cache writes in slot 0 of their own dead cache line —
    keeping their attention frontier (and pallas decode cost) at 1 slot.

    Grammar fast-forward: when ``tables`` carries ff chains (DeviceFSM
    ``ff_tokens``/``ff_len``) and decoding is constrained, each iteration
    appends the current token PLUS its state's forced-token chain in one
    (B, 1+W) forward — the weight read dominates a decode step's HBM
    traffic, so the chain tokens ride along nearly free and one iteration
    emits up to 1+W tokens, at ANY batch width. Under kernels="pallas" the
    small-T step runs the frontier-read block-attention kernel
    (ops.decode_block_attention: each row reads its own context, with
    intra-block causality from write positions); the XLA fallback reads
    the cache at capacity and is acceptable only off-TPU.

    Returns (emitted (B, <=chunk_steps*(1+W)), counts, eos_flags, cache,
    cur, pos, fsm_state, active, nbytes, tokens_left, fwds, poison). eos is
    True only for rows that sampled EOS (clean finish) -- budget/length
    truncation leaves it False. ``poison`` is the per-row fault code the
    scheduler's quarantine keys on: 0 healthy, 1 non-finite logits (NaN/inf
    out of the forward), 2 grammar dead state (the FSM has no legal
    continuation — unreachable under healthy constrained decoding, reached
    by corrupt state or injection). A poisoned row deactivates WITHOUT
    committing the faulty sample, so batch-mates' carries (and therefore
    their tokens) are untouched — per-request containment at the loop level.
    """
    B = cur.shape[0]
    if max_len is None:
        max_len = cache["k"].shape[2]
    use_ff = constrained and tables.ff_tokens is not None
    W = tables.ff_tokens.shape[1] if use_ff else 0
    cap = chunk_steps * (1 + W)
    # ff emission scatters through a trash column (index `cap`)
    out = jnp.full((B, cap + 1 if use_ff else cap), pad_id, dtype=jnp.int32)
    # rows already stopped before the loop: EOS right at admission
    eos0 = (~active) & (cur == eos_id)

    carry0 = (cache, cur, pos, fsm_state, active, eos0, nbytes, tokens_left, out,
              jnp.zeros((B,), jnp.int32), key, jnp.zeros((), jnp.int32),
              jnp.zeros((B,), jnp.int32), _conf_init(B))

    def cond(c):
        active, step = c[4], c[11]
        return jnp.logical_and(step < chunk_steps, jnp.any(active))

    def body(c):
        (cache, cur, pos, state, active, eos, nbytes, left, out, n, key, step,
         poison, conf) = c
        with jax.named_scope("loop_carry"):
            # record current token for active rows
            out = out.at[jnp.arange(B), jnp.minimum(n, cap - 1)].set(
                jnp.where(active, cur, out[jnp.arange(B), jnp.minimum(n, cap - 1)])
            )
            n = n + active.astype(jnp.int32)
            nbytes = nbytes + jnp.where(active, byte_len_table[cur], 0)
            left = left - active.astype(jnp.int32)

            # idle rows park their writes at slot 0 of their own (dead) line
            write_pos = jnp.where(active, pos, 0)
            step_tok = jnp.where(active, cur, pad_id)
        if fwd is not None:
            logits, cache = fwd(params, cache, step_tok[:, None], write_pos[:, None])
        else:
            logits, cache = forward(params, cfg, step_tok[:, None], write_pos[:, None],
                                    cache, rules, attn_impl=kernels, unroll=unroll)
        raw = logits[:, 0, :]
        if nan_inject is not None:
            raw = jnp.where(nan_inject[:, None] & active[:, None],
                            jnp.float32(jnp.nan), raw)
        key, k = jax.random.split(key)
        nxt, state_next = _mask_sample_advance(
            raw, state, tables, k, temperature, greedy,
            constrained, kernels, rules, logit_mask
        )
        # fault fence: a poisoned row deactivates WITHOUT committing the
        # faulty sample; healthy rows commit exactly as before (ok==active)
        ok, poison = _poison_gate(raw, state, state_next, active, poison,
                                  constrained)
        if quality_lanes:
            mg, en, f1 = _conf_stats(raw, state, tables, constrained,
                                     logit_mask)
            conf = _conf_accumulate(conf, ok, mg, en, f1)
        with jax.named_scope("loop_carry"):
            state = jnp.where(ok, state_next, state)
            cur = jnp.where(ok, nxt, cur)
            pos = jnp.where(ok, pos + 1, pos)

            eos = eos | (ok & (cur == eos_id))
            stop = (cur == eos_id) | (nbytes >= byte_budget) | (pos >= max_len - 1) | (left <= 0)
            active = ok & ~stop
        return (cache, cur, pos, state, active, eos, nbytes, left, out, n, key,
                step + 1, poison, conf)

    def ff_body(c):
        (cache, cur, pos, state, active, eos, nbytes, left, out, n, key, step,
         poison, conf) = c
        with jax.named_scope("loop_carry"):
            # dead-at-entry rows must not fast-forward: ff_tokens[state] with a
            # negative state wraps to an arbitrary chain — fence them out of
            # this step's emission entirely (their result is discarded anyway)
            dead_in = active & (state < 0)
            active = active & ~dead_in
            poison = jnp.maximum(poison, jnp.where(dead_in, 2, 0))
            iw = jnp.arange(1 + W)[None, :]  # (1, 1+W) block index
            chain = tables.ff_tokens[state]  # (B, W); -1 pads
            # chain length, capped so emission fits the token budget, the cache
            # (writes land at pos .. pos+k <= max_len-1), and the byte budget
            # (chain_byte_cap: the shared one-token-overshoot contract)
            k = jnp.minimum(jnp.minimum(tables.ff_len[state], left - 1),
                            max_len - 1 - pos)
            k, _ = chain_byte_cap(k, chain, cur, nbytes, byte_len_table,
                                  byte_budget)
            k = jnp.where(active, jnp.maximum(k, 0), 0)

            # [cur, chain_0..chain_{k-1}] with idempotent duplicate-tail padding
            step_tok, blk_tok, blk_pos = chain_block(iw, cur, chain, k, active,
                                                     pad_id, pos)

            # emit cur + chain via the trash column
            valid = (iw <= k[:, None]) & active[:, None]
            tgt = jnp.where(valid, jnp.minimum(n[:, None] + iw, cap - 1), cap)
            out = out.at[jnp.arange(B)[:, None], tgt].set(
                jnp.where(valid, blk_tok, pad_id))
            emitted = jnp.where(active, 1 + k, 0)
            n = n + emitted
            # taken chain bytes: inside chain_valid the block IS the chain
            chain_valid = (iw >= 1) & (iw <= k[:, None]) & active[:, None]
            nbytes = (nbytes + jnp.where(active, byte_len_table[cur], 0)
                      + jnp.sum(jnp.where(chain_valid,
                                          byte_len_table[jnp.maximum(blk_tok, 0)], 0),
                                axis=1))
            left = left - emitted

        with jax.named_scope("fsm_advance"):
            # FSM state after the taken chain tokens (walked stepwise so budget
            # truncation of the chain keeps the state exact)
            def cstep(s, xs):
                t, i = xs
                s2 = fsm_advance(tables, s, jnp.maximum(t, 0))
                return jnp.where(i < k, s2, s), None

            s_end, _ = jax.lax.scan(cstep, state, (chain.T, jnp.arange(W)))

        if fwd is not None:
            logits, cache = fwd(params, cache, blk_tok, blk_pos)
        else:
            logits, cache = forward(params, cfg, blk_tok, blk_pos, cache, rules,
                                    attn_impl=kernels, unroll=unroll)
        logits_k = jnp.take_along_axis(logits, k[:, None, None], axis=1)[:, 0, :]
        if nan_inject is not None:
            logits_k = jnp.where(nan_inject[:, None] & active[:, None],
                                 jnp.float32(jnp.nan), logits_k)
        key, kk = jax.random.split(key)
        nxt, state_next = _mask_sample_advance(
            logits_k, s_end, tables, kk, temperature, greedy,
            constrained, kernels, rules, logit_mask
        )
        ok, poison = _poison_gate(logits_k, s_end, state_next, active,
                                  poison, constrained)
        if quality_lanes:
            # the sampled decision at the chain's end, plus the emitted
            # chain tokens themselves counted as grammar-forced (their
            # margin is definitionally the cap; only the count matters)
            mg, en, f1 = _conf_stats(logits_k, s_end, tables, constrained,
                                     logit_mask)
            conf = _conf_accumulate(conf, ok, mg, en, f1,
                                    forced_extra=jnp.where(active, k, 0))
        with jax.named_scope("loop_carry"):
            state = jnp.where(ok, state_next, state)
            cur = jnp.where(ok, nxt, cur)
            pos = jnp.where(ok, pos + 1 + k, pos)

            eos = eos | (ok & (cur == eos_id))
            stop = (cur == eos_id) | (nbytes >= byte_budget) | (pos >= max_len - 1) | (left <= 0)
            active = ok & ~stop
        return (cache, cur, pos, state, active, eos, nbytes, left, out, n, key,
                step + 1, poison, conf)

    (cache, cur, pos, state, active, eos, nbytes, left, out, n, _, fwds, poison,
     conf) = (
        jax.lax.while_loop(cond, ff_body if use_ff else body, carry0)
    )
    return (out[:, :cap], n, eos, cache, cur, pos, state, active, nbytes, left,
            fwds, poison, conf)


class DecodeEngine:
    """Single-model decode engine over an optional device mesh."""

    # subclasses with their own KV layout (serve.paged) turn this off so
    # startup never allocates the dense worst-case batch_slots x max_len
    # cache they exist to avoid
    _alloc_dense_cache = True

    # what a KV layout MAY offer the batcher, declared once so the batcher
    # reads and calls, never probes (methods: set_slot_ns,
    # begin_chunked_prefill, reconcile_coverage, slot_block_count)
    radix = None  # per-dp-group radix trees (serve.radix), RADIX_ENABLE
    allocator = None  # the KV pool's BlockAllocator
    compact_rows = 0  # the chunk program's compacted width; 0 = it has none
    # prefill_slot's report of its last admission (its return value is the
    # logits alone: ROADMAP D13)
    _last_prefill_compute_ms = None
    _last_cached_tokens = 0
    # rows of a grouped admission call; 0: this layout admits a slot at a time
    # (``PagedDecodeEngine.admit_rows`` has the one layout that groups)
    admit_rows = 0

    def __init__(
        self,
        preset: str = "test-tiny",
        cfg: LlamaConfig | None = None,
        mesh=None,
        seed: int = 0,
        max_len: int = 2048,
        batch_slots: int = 1,
        prefill_buckets: tuple[int, ...] = (128, 256, 512, 1024, 2048),
        kernels: str = "auto",  # "auto" | "xla" | "pallas"
        quant: str | None = None,  # None | "int8" — weight-only quantization
        tokenizer=None,  # external (checkpoint) tokenizer; None = in-tree toy
        fsm=None,  # prebuilt grammar.TokenFSM over `tokenizer`
        init_weights: bool = True,  # False: caller loads a checkpoint next
        decode_unroll: int = 1,  # layer-scan unroll in the decode step
        fast_forward: int = 0,  # grammar fast-forward chain width (0 = off).
        # Applies to generate() AND the continuous batcher: a chain step is
        # a (B, 1+W) forward whose attention runs the Pallas frontier-read
        # block kernel (ops.decode_block_attention) under kernels="pallas",
        # so the chain tokens ride the weight read nearly free at any B
        quality_lanes: bool | None = None,  # ISSUE 15 confidence lanes in
        # the decode loops (margin/entropy/forced readbacks). None reads
        # QUALITY_ENABLE; tokens are identical on or off — the flag only
        # decides whether the readback arithmetic is traced at all
    ):
        # on a mesh the kernels run per-shard under shard_map (batch over
        # dp, heads over tp; ops.sharded_*), so "auto" may pick pallas both
        # off-mesh and on the dp×tp serving mesh
        self.kernels = resolve_kernels(kernels)
        base = cfg or PRESETS[preset]
        prebuilt = None
        if tokenizer is None:
            # in-tree tokenizer: its vocab IS the model vocab (random-init
            # engines for tests/latency work)
            self.tokenizer, prebuilt = build_intent_fsm()
            vocab = self.tokenizer.vocab_size
        else:
            # checkpoint tokenizer: the model vocab comes from the config
            # (embedding tables are often padded past the tokenizer) and the
            # grammar FSM is built over THAT width so gathers line up with
            # real logits. This is the round-2 fix for VERDICT missing #1.
            self.tokenizer = tokenizer
            vocab = base.vocab_size if cfg is not None else tokenizer.vocab_size
            if vocab < tokenizer.vocab_size:
                raise ValueError(
                    f"model vocab {vocab} < tokenizer vocab {tokenizer.vocab_size}"
                )
        # what this kind of model refuses of THIS engine (``models.family``), here,
        # before the grammar is built
        fam = family(base)
        if self._alloc_dense_cache:
            fam.refuse("dense_cache")
        if mesh is not None:
            fam.refuse("mesh")
        if base.n_experts > 0 and base.moe_impl == "auto":
            # THE dispatch choice of a routed model, made once, here, from
            # where the engine runs: the grouped-matmul kernel on a single
            # device (weight bytes ∝ the experts touched), the dense einsum
            # dispatch on a mesh (its experts shard over tp)
            base = replace(base, moe_impl="dense" if mesh is not None else "grouped")
        if mesh is not None:
            if getattr(base, "moe_impl", "dense") == "grouped":
                # the grouped-matmul dispatch is a bare pallas_call: under
                # GSPMD it would replicate the (E, d, f) expert weights on
                # every device, silently defeating EP — enforce the
                # documented single-device restriction at construction
                raise ValueError(
                    "moe_impl='grouped' is single-device; meshed MoE engines "
                    "use dense dispatch (EP shards experts over tp)")
            # lm_head shards the vocab over tp: pad the model vocab up to a
            # tp multiple BEFORE any FSM build (the build is multi-second —
            # it must happen once, at the final width). Padded ids are never
            # grammar-legal, so the FSM mask keeps them unsampleable.
            tp = mesh.shape.get("tp", 1)
            vocab = -(-vocab // tp) * tp
        if fsm is not None:
            if fsm.vocab_size != vocab:
                raise ValueError(
                    f"custom fsm width {fsm.vocab_size} != model vocab {vocab} "
                    f"(mesh engines pad the vocab to a tp multiple; build it "
                    f"with grammar.build_fsm_for(tokenizer, vocab_size={vocab}))")
            self.fsm = fsm
        elif prebuilt is not None and prebuilt.vocab_size == vocab:
            self.fsm = prebuilt
        else:
            self.fsm = build_fsm_for(self.tokenizer, vocab_size=vocab)
        self.cfg = replace(base, vocab_size=vocab, max_seq_len=max_len)
        # what this kind of model keeps on the device, compiles, counts and
        # refuses: the record the serving side reads in place of asking which it is
        self.family = family(self.cfg)
        self.eos_id = int(self.tokenizer.eos_id)
        self.pad_id = int(self.tokenizer.pad_id)
        self.mesh = mesh
        self.max_len = max_len
        self.batch_slots = batch_slots
        self.decode_unroll = decode_unroll
        self.prefill_buckets = tuple(b for b in prefill_buckets if b <= max_len)
        if quality_lanes is None:
            from ..utils.quality import quality_lanes_enabled

            quality_lanes = quality_lanes_enabled()
        self.quality_lanes = bool(quality_lanes)

        key = jax.random.PRNGKey(seed)
        if mesh is not None:
            dp = mesh.shape.get("dp", 1)
            if batch_slots % dp != 0:
                raise ValueError(
                    f"batch_slots ({batch_slots}) must be divisible by the mesh dp axis "
                    f"({dp}); dp>1 shards the KV-cache batch dim. Use batch_slots=dp*k "
                    "(batched decode is driven by serve.scheduler)."
                )
            self.rules = default_rules(mesh, self.cfg.n_kv_heads, self.cfg.n_heads)
            self._param_shardings = param_shardings(
                mesh, self.cfg.n_kv_heads, self.cfg.n_experts)
            self.params = jax.jit(
                partial(init_params, self.cfg), out_shardings=self._param_shardings
            )(key) if init_weights else None
            kv_sh = kv_cache_shardings(mesh, self.cfg.n_kv_heads)
            self.cache = jax.jit(
                partial(init_kv_cache, self.cfg, batch_slots, max_len), out_shardings=kv_sh
            )() if self._alloc_dense_cache else None
        else:
            self.rules = None
            self._param_shardings = None
            self.params = jax.jit(partial(init_params, self.cfg))(key) if init_weights else None
            self.cache = (init_kv_cache(self.cfg, batch_slots, max_len)
                          if self._alloc_dense_cache else None)

        if quant == "int8":
            # weight-only int8: decode is HBM-bound on weights, so halving
            # their bytes halves the per-token floor. On a mesh the
            # quantized {"q","s"} leaves get their own shardings (q keeps
            # the raw spec, per-channel scales drop the reduced axis) so
            # each tp shard reads its own int8 bytes
            if mesh is not None:
                from ..parallel.mesh import quantized_param_shardings

                self._quant_shardings = quantized_param_shardings(
                    mesh, self.cfg.n_kv_heads, self.cfg.n_experts)
            else:
                self._quant_shardings = None
            if self.params is not None:
                from ..models.llama import quantize_params

                self.params = jax.jit(
                    quantize_params, out_shardings=self._quant_shardings
                )(self.params)
        elif quant is not None:
            raise ValueError(f"unknown quant {quant!r}")
        self.quant = quant

        self.tables = self.fsm.device_tables()
        # fast-forward twin: forced-chain tables used by generate() AND the
        # batcher's decode_chunk (round-3's single-request restriction is
        # lifted: the frontier-read block kernel makes a (B, 1+W) step read
        # each row's own context, ops.decode_block_attention). _replace
        # shares the already-uploaded table/col_id/dense_mask device arrays
        # instead of re-uploading them (the dense mask alone can be tens
        # of MB)
        self.fast_forward = fast_forward
        if fast_forward > 0:
            fft, ffl = self.fsm.forced_tables(fast_forward)
            self.tables_ff = self.tables._replace(
                ff_tokens=jnp.asarray(fft), ff_len=jnp.asarray(ffl))
        else:
            self.tables_ff = None
        self.byte_len_table = byte_len_table_for(self.tokenizer, self.cfg.vocab_size)
        self._rng = jax.random.PRNGKey(seed + 1)
        # ids past the tokenizer (mesh tp padding / checkpoint embed padding)
        # decode to nothing: unsampleable even in unconstrained decode
        self.logit_mask = (
            jnp.arange(self.cfg.vocab_size) < self.tokenizer.vocab_size
            if self.cfg.vocab_size > self.tokenizer.vocab_size else None
        )
        # shared-prefix cache: token ids + their precomputed KV (L,1,P,nkv,hd)
        self.prefix_ids: list[int] = []
        self.prefix_kv: dict | None = None
        # the prompt head's text, the ids of it that no suffix can change (BOS
        # first) and the head's bytes behind them: ``encode_prompt``'s memo,
        # replaced together with the two above
        self._head: tuple[str, list[int], bytes] | None = None

    # ------------------------------------------------------------ helpers

    def load_params(self, params) -> None:
        """Install externally loaded weights (orbax / safetensors import).
        Applies the engine's quantization mode so callers can hand over raw
        bf16 checkpoint trees."""
        if self.quant == "int8" and not (
            isinstance(params.get("lm_head"), dict) and "q" in params["lm_head"]
        ):
            from ..models.llama import quantize_params

            params = jax.jit(
                quantize_params,
                out_shardings=getattr(self, "_quant_shardings", None),
            )(params)
        self.params = params

    @classmethod
    def from_hf(
        cls,
        model_dir: str,
        mesh=None,
        max_len: int = 2048,
        batch_slots: int = 1,
        prefill_buckets: tuple[int, ...] = (128, 256, 512, 1024, 2048),
        kernels: str = "auto",
        quant: str | None = None,
        dtype=jnp.bfloat16,
        fast_forward: int = 0,
        moe_impl: str | None = None,  # override cfg.moe_impl ("grouped" for
        # the single-device Pallas dispatch on MoE checkpoints)
        **engine_kw,  # subclass knobs (classmethod polymorphism: e.g.
        # PagedDecodeEngine.from_hf takes pool_blocks / block_size)
    ) -> "DecodeEngine":
        """Serve a real HF checkpoint directory: config.json decides the
        architecture, tokenizer.json supplies the real BPE vocab (the intent
        FSM is compiled over it), *.safetensors supply the weights. This is
        the path that replaces the reference's cloud LLM for real
        (apps/brain/src/llm.ts:17-30)."""
        import os

        from ..ckpt.hf_import import llama_config_from_hf, llama_from_hf_state
        from ..grammar.hf_tokenizer import load_hf_tokenizer

        cfg = llama_config_from_hf(os.path.join(model_dir, "config.json"))
        cfg = replace(cfg, max_seq_len=max_len)
        if moe_impl is not None:
            cfg = replace(cfg, moe_impl=moe_impl)
        tok = load_hf_tokenizer(model_dir)
        eng = cls(
            cfg=cfg, mesh=mesh, max_len=max_len, batch_slots=batch_slots,
            prefill_buckets=prefill_buckets, kernels=kernels, quant=quant,
            tokenizer=tok, init_weights=False, fast_forward=fast_forward,
            **engine_kw,
        )
        params = llama_from_hf_state(model_dir, cfg, dtype=dtype)
        if eng.cfg.vocab_size != cfg.vocab_size:
            # the engine padded its vocab to a tp multiple: pad the
            # checkpoint's embed rows / lm_head columns to match (pad ids
            # are never grammar-legal, so their zero logits are unsampleable
            # under constrained decode)
            pad = eng.cfg.vocab_size - cfg.vocab_size
            params["embed"] = jnp.pad(params["embed"], ((0, pad), (0, 0)))
            params["lm_head"] = jnp.pad(params["lm_head"], ((0, 0), (0, pad)))
        if mesh is not None:
            params = jax.device_put(params, eng._param_shardings)
        eng.load_params(params)
        return eng

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds max bucket {self.prefill_buckets[-1]}")

    def _suffix_bucket(self, n: int, limit: int) -> int | None:
        """Bucket for a prefix-cached suffix: finer-grained than the full
        prefill buckets (suffixes are short user payloads) and capped so
        prefix + bucket fits the cache. None = no bucket fits; the caller
        falls back to full prefill (which may still fit, since the full
        prompt buckets independently)."""
        for b in self.suffix_buckets + self.prefill_buckets:
            if n <= b <= limit:
                return b
        return None

    @property
    def suffix_buckets(self) -> tuple:
        """The buckets tried for a suffix before the full-prompt ones: every
        one is a forward executable a start-up compiles or loads."""
        return (32, 64)

    # ------------------------------------------------------------ prefix

    def set_prompt_prefix(self, *sample_prompts: str) -> int:
        """Install the shared-prefix cache from >= 2 sample prompts.

        The prefix is computed in TOKEN space as the longest common token
        prefix of the samples' encodings — robust to any tokenizer's merge
        behavior at the prefix/suffix boundary (an exact-match check at
        prefill time guarantees correctness either way). Returns the cached
        prefix length in tokens. Call once at service start with two
        rendered prompts that differ only in their user payload. The ONE
        copy of the matching logic; subclasses with their own cache layout
        override only ``_compute_prefix_kv``."""
        if len(sample_prompts) < 2:
            raise ValueError("need >= 2 sample prompts to locate the shared prefix")
        encs = [self.tokenizer.encode(p, bos=True) for p in sample_prompts]
        self._head = self._stable_head(sample_prompts, encs)
        P = 0
        shortest = min(len(e) for e in encs)
        while P < shortest and all(e[P] == encs[0][P] for e in encs):
            P += 1
        P = self._cached_prefix_len(P)
        if P == 0:
            self.prefix_ids, self.prefix_kv = [], None
            return 0
        ids = list(encs[0][:P])
        bucket = self._prefix_bucket(P)
        tokens = np.full((1, bucket), self.pad_id, dtype=np.int32)
        tokens[0, :P] = ids
        positions = np.arange(bucket, dtype=np.int32)[None, :]
        self.prefix_kv = self._compute_prefix_kv(
            jnp.asarray(tokens), jnp.asarray(positions), P, bucket)
        self.prefix_ids = ids
        return P

    def _stable_head(self, sample_prompts, encs) -> tuple | None:
        """``encode_prompt``'s memo from the samples: their common STRING
        prefix is the head's text, and what the tokenizer promises of it
        (``stable_prefix``) is kept. None where it promises nothing: every
        prompt is then encoded whole."""
        stable_prefix = getattr(self.tokenizer, "stable_prefix", None)
        if stable_prefix is None:
            return None
        head = os.path.commonprefix(sample_prompts)
        ids, n_bytes = stable_prefix(head)
        if not ids:
            return None
        ids = [self.tokenizer.bos_id] + ids
        if any(e[:len(ids)] != ids for e in encs):
            raise ValueError(
                f"{type(self.tokenizer).__name__}.stable_prefix broke its promise: "
                f"its {len(ids)} ids are not the head of a sample prompt's encoding")
        return head, ids, head.encode()[n_bytes:]

    def encode_prompt(self, prompt) -> tuple[list[int], int]:
        """A rendered prompt's ids (BOS first), and how many of them came
        from the head's memo — THE one place a prompt becomes ids. A ``str``
        that starts with the head's text takes the memo's ids and the
        tokenizer's walk from the memo's last byte on (id for id what the
        whole walk gives: ``stable_prefix``'s promise); any other ``str`` is
        encoded whole; a list of ids is itself."""
        if not isinstance(prompt, str):
            return [int(t) for t in prompt], 0
        memo = self._head  # read once: ``set_prompt_prefix`` may replace it on another thread
        if memo is not None and prompt.startswith(memo[0]):
            head, ids, tail = memo
            return ids + self.tokenizer.encode(tail + prompt[len(head):].encode()), len(ids)
        return self.tokenizer.encode(prompt, bos=True), 0

    def _cached_prefix_len(self, P: int) -> int:
        """How much of the common token prefix is cached (all of it)."""
        return P

    def _prefix_bucket(self, P: int) -> int:
        return self._bucket(P)

    def _compute_prefix_kv(self, tokens, positions, P: int, bucket: int) -> dict:
        """Prefill the prefix into a scratch cache and return its KV in
        this engine's layout (dense: (L, 1, P, nkv, hd))."""
        scratch = init_kv_cache(self.cfg, 1, bucket)
        _, kv = forward(
            self.params, self.cfg, tokens, positions,
            scratch, self.rules, attn_impl=self.kernels, fresh_block=True,
        )
        return {"k": kv["k"][:, :, :P], "v": kv["v"][:, :, :P]}

    def _split_prefix(self, ids: list[int]) -> list[int] | None:
        """Return the suffix ids when the cached prefix applies, else None.
        Exact token-prefix match: a tokenizer that merges across the
        boundary just falls back to the full prefill path."""
        P = len(self.prefix_ids)
        if self.prefix_kv is None or len(ids) <= P:
            return None
        if list(ids[:P]) != self.prefix_ids:
            return None
        return list(ids[P:])

    # ------------------------------------------------------------ generate

    def prefill_slot(self, ids: list[int], slot: int):
        """Prefill token ids into one batch slot's cache line, reusing the
        shared-prefix KV when `ids` starts with it (exact token match;
        anything else takes the full-prompt path). Returns the last real
        token's logits (1, V). THE single decision tree shared by
        single-request generate(), the continuous batcher's admission, and
        every engine layout (dense / paged / pp override only the
        ``_prefill_suffix`` / ``_prefill_full`` kernels) — the paths the
        equivalence tests hold token-identical.

        On the trace and in the step ledger ``.alloc`` is this whole method
        up to the slice of the last row (``.first_token_call``), less the
        jitted forward, which the layout kernel alone wraps as
        ``.prefill_call``. ``sched.admit.prefill`` — the ledger's prefill
        stage — is what ``prefill_ms`` times: the layout kernel's whole
        call."""
        from ..utils.chaos import ChaosError, chaos_fire

        with span(ALLOC_SPAN):
            if chaos_fire("prefill_exc"):
                # drill for the scheduler's per-request admission fence:
                # fires BEFORE any engine state is touched, like a real
                # tokenizer/shape fault at the top of admission
                raise ChaosError("chaos: injected prefill exception")
            self.release_slot(slot)  # a finished request may still own resources
            n = len(ids)
            suffix = self._split_prefix(ids)
            if suffix is not None:
                bucket = self._suffix_bucket(len(suffix), self.max_len - len(self.prefix_ids))
                if bucket is None:
                    suffix = None  # no suffix bucket fits; use full prefill below
            if suffix is not None:
                P, m = len(self.prefix_ids), len(suffix)
                tokens = np.full((1, bucket), self.pad_id, dtype=np.int32)
                tokens[0, :m] = suffix
                positions = (P + np.arange(bucket, dtype=np.int32))[None, :]
            else:
                P, m = 0, n
                bucket = self._bucket(n)
                tokens = np.full((1, bucket), self.pad_id, dtype=np.int32)
                tokens[0, :n] = ids
                positions = np.arange(bucket, dtype=np.int32)[None, :]
            with span(PREFILL_STAGE_SPAN):
                t0 = time.perf_counter()
                tokens, positions = jnp.asarray(tokens), jnp.asarray(positions)
                if suffix is not None:
                    logits = self._prefill_suffix(tokens, positions, slot, P, bucket, n)
                else:
                    logits = self._prefill_full(tokens, positions, slot, bucket, n)
                # the prefill split (scheduler/_result_to_response read it):
                # compute ms covers ONLY the layout kernel's call, a dispatch
                # (on the paged layout its block allocation too) — the cached
                # prefix contributes tokens, not compute
                self._last_prefill_compute_ms = (time.perf_counter() - t0) * 1e3
            self._last_cached_tokens = P
        with span(FIRST_TOKEN_SPAN):
            return logits[:, m - 1, :]

    def _prefill_suffix(self, tokens, positions, slot: int, P: int, bucket: int,
                        n: int):
        """Layout kernel: admit a prefix-cached suffix into ``slot``."""
        with span(PREFILL_CALL_SPAN):
            logits, self.cache = prefill_row_with_prefix(
                self.params, self.cfg, self.cache,
                self.prefix_kv["k"], self.prefix_kv["v"],
                tokens, positions, jnp.int32(slot),
                rules=self.rules, kernels=self.kernels,
            )
        return logits

    def _prefill_full(self, tokens, positions, slot: int, bucket: int, n: int):
        """Layout kernel: admit a fresh full prompt into ``slot``."""
        with span(PREFILL_CALL_SPAN):
            logits, self.cache = prefill_row(
                self.params, self.cfg, self.cache,
                tokens, positions, jnp.int32(slot),
                rules=self.rules, kernels=self.kernels, fresh=True,
            )
        return logits

    def decode_chunk(self, cur, pos, fsm, active, nbytes, tokens_left, key,
                     temperature: float, byte_budget: int, chunk_steps: int,
                     greedy: bool, live=None, nan_inject=None) -> ChunkResult:
        """Advance all slots by one decode chunk (the batcher's device-work
        entry point — the KV layout stays the engine's business, so the
        paged engine can substitute its pool/table loop). With fast_forward
        configured the chunk takes (B, 1+W) grammar-chain steps — the
        round-3 single-request restriction is lifted by the frontier-read
        block-attention kernel (each row reads its own context, not the
        cache capacity, even at batch width).

        THE CONTRACT of every layout's ``decode_chunk`` (dense, paged,
        pp): one signature, one ``ChunkResult``
        back, nothing of the chunk left on the engine. ``live`` is the
        caller's host mirror of ``active`` (a superset of it), from which a
        layout with a compacted width chooses the chunk program's width;
        this one has none and ignores it. ``nan_inject`` is the chaos
        drill's (B,) bool mask for THIS chunk (None in production, and None
        keeps the traced loop byte-identical). After consuming the record
        the caller passes the host-fetched ``pos`` to ``reconcile_coverage``:
        a layout that claims KV blocks for a chunk's worst case before its
        dispatch is only clamped back to each row's actual frontier there
        (the clamp cannot live in here: ``pos`` is a device array
        mid-async-dispatch, and a host read would stall the chain)."""
        out, n, eos, self.cache, cur, pos, fsm, active, nbytes, left, fwds, \
            pois, conf = (
                chunk_decode_loop(
                    self.params, self.cfg, self.cache,
                    cur, pos, fsm, active, nbytes, tokens_left,
                    self.tables_ff if self.tables_ff is not None else self.tables,
                    self.byte_len_table,
                    key, jnp.float32(temperature), jnp.int32(byte_budget),
                    rules=self.rules, logit_mask=self.logit_mask,
                    nan_inject=nan_inject,
                    chunk_steps=chunk_steps,
                    greedy=greedy, constrained=True, kernels=self.kernels,
                    eos_id=self.eos_id, pad_id=self.pad_id,
                    unroll=self.decode_unroll,
                    quality_lanes=self.quality_lanes,
                )
            )
        return ChunkResult(out, n, eos, cur, pos, fsm, active, nbytes, left,
                           fwds=fwds, poison=pois, rows=self.batch_slots,
                           conf=conf if self.quality_lanes else None)

    def set_slot_ns(self, slot: int, ns: str | None) -> None:
        """Tenant radix namespace of the slot's NEXT admission (the batcher
        calls this right before ``prefill_slot``). Nothing to salt without a
        radix tree."""

    def begin_chunked_prefill(self, ids: list[int], slot: int,
                              chunk_tokens: int):
        """Start a chunked admission and return its cursor, or None when
        this layout (or this prompt) cannot be chunked: the caller then
        takes the one-shot ``prefill_slot``."""
        return None

    def reconcile_coverage(self, pos_h) -> None:
        """Post-chunk hook, see ``decode_chunk``. A dense line claims
        nothing per chunk."""

    def slot_block_count(self, slot: int) -> int:
        """KV blocks the slot holds (the cost ledger's block-time): a dense
        row holds one, its whole KV line."""
        return 1

    def release_slot(self, slot: int, generated_ids: list[int] | None = None,
                     ok: bool = True) -> None:
        """A batch slot finished: dense cache rows are simply reused in
        place (the paged engine returns the slot's blocks to the pool —
        and, with radix reuse on, adopts the prompt+generated chain the
        scheduler passes via ``generated_ids`` into its tree first).
        ``ok=False`` marks an errored/cancelled request: resources are
        still freed, but layout subclasses must never cache its chain."""

    def warm_restart(self) -> None:
        """Rebuild device decode state after a wedged/corrupt step, REUSING
        the loaded weights (a cold process restart re-pays checkpoint load
        and every jit compile; the params and compiled programs are the
        expensive part and are not suspect — the mutable decode state is).
        Dense layout: a fresh KV cache; the shared-prefix KV survives (it
        lives outside the batch cache). The caller (colocate watchdog)
        owns failing inflight work and resetting the batcher."""
        if self._alloc_dense_cache:
            if self.mesh is not None:
                kv_sh = kv_cache_shardings(self.mesh, self.cfg.n_kv_heads)
                self.cache = jax.jit(
                    partial(init_kv_cache, self.cfg, self.batch_slots, self.max_len),
                    out_shardings=kv_sh)()
            else:
                self.cache = init_kv_cache(self.cfg, self.batch_slots, self.max_len)
        # re-arm the recompilation sentinel's warmup fence: the restart
        # reuses compiled programs, so any NEW trace after it means the
        # rebuilt mutable state came back with an unexpected shape — the
        # post-warm-restart retrace is exactly the p99 cliff the sentinel
        # exists to name
        get_compile_watcher().arm_fence("warm_restart")

    def _prefill(self, prompt: str):
        if self.batch_slots != 1:
            raise ValueError(
                "single-request generate() requires batch_slots=1; batched decode "
                "is driven by the continuous-batching scheduler (serve.scheduler)"
            )
        ids, _ = self.encode_prompt(prompt)
        return self.prefill_slot(ids, 0), len(ids)

    def generate(
        self,
        prompt: str,
        max_new_tokens: int = 512,
        constrained: bool = True,
        greedy: bool = True,
        temperature: float = 0.7,
        byte_budget: int = 3900,
        ignore_eos: bool = False,  # benchmarking: never stop at EOS, so a
        # fixed-step-count run exists even for checkpoints that answer short
    ) -> GenerationResult:
        """Generate a completion with the on-device whole-generation loop
        (single host dispatch). With constrained=True the result matches the
        intent grammar; byte_budget keeps generated strings inside the
        schema's 4096-char caps."""
        # SYNC DISCIPLINE: every host readback waits for the device to drain
        # and leaves it idle until the next dispatch lands — so the whole
        # generate pays exactly ONE combined device_get at the end and
        # never blocks mid-flight. prefill_ms is therefore dispatch-side
        # (enqueue) time; the total latency is what's real.
        t0 = time.perf_counter()
        last_logits, n = self._prefill(prompt)
        fsm_state = jnp.full((1,), self.fsm.start, dtype=jnp.int32)
        self._rng, k0 = jax.random.split(self._rng)
        tok0, fsm0 = _first_token(
            last_logits, fsm_state, self.tables, k0,
            jnp.float32(temperature), greedy=greedy, constrained=constrained,
            kernels=self.kernels, rules=self.rules, logit_mask=self.logit_mask,
        )
        prefill_ms = (time.perf_counter() - t0) * 1e3

        t1 = time.perf_counter()
        self._rng, key = jax.random.split(self._rng)
        tables = self.tables_ff if (constrained and self.tables_ff is not None) else self.tables
        (buf, count, eos, self.cache, _cur, _pos, _fsm, _act, _nb, _left,
         fwds, pois_d, conf) = chunk_decode_loop(
            self.params, self.cfg, self.cache,
            tok0, jnp.full((1,), n, dtype=jnp.int32), fsm0,
            tok0 != (-1 if ignore_eos else self.eos_id),  # active
            jnp.zeros((1,), jnp.int32),  # nbytes
            jnp.full((1,), max_new_tokens, dtype=jnp.int32),  # tokens_left
            tables, self.byte_len_table,
            key, jnp.float32(temperature), jnp.int32(byte_budget),
            rules=self.rules, logit_mask=self.logit_mask,
            chunk_steps=max_new_tokens,
            greedy=greedy, constrained=constrained, kernels=self.kernels,
            eos_id=-1 if ignore_eos else self.eos_id,
            pad_id=self.pad_id, unroll=self.decode_unroll,
            quality_lanes=self.quality_lanes,
        )
        buf_h, count_h_a, eos_h, fwds_h, pois_h, conf_h = jax.device_get(
            (buf, count, eos, fwds, pois_d, conf))
        count_h = int(count_h_a[0])
        out_ids = [int(t) for t in np.asarray(buf_h)[0, :count_h]]
        finished = bool(eos_h[0])
        decode_ms = (time.perf_counter() - t1) * 1e3
        pois = int(np.asarray(pois_h)[0])
        quality = None
        if self.quality_lanes:
            from ..utils.quality import conf_summary

            quality = conf_summary([np.asarray(x)[0] for x in conf_h], count_h)

        from ..utils import get_metrics

        m = get_metrics()
        m.inc("engine.requests")
        m.inc("engine.tokens_generated", count_h)
        m.observe_ms("engine.prefill", prefill_ms)
        m.observe_ms("engine.decode", decode_ms)

        return GenerationResult(
            text=self.tokenizer.decode(out_ids),
            token_ids=out_ids,
            prefill_ms=prefill_ms,
            decode_ms=decode_ms,
            steps=count_h,
            finished=finished,
            # a poisoned single-request generation surfaces the typed error
            # instead of masquerading as truncation (the batched path's
            # quarantine does the same through the scheduler)
            error=(None if pois == 0 else
                   "poisoned: " + ("non-finite logits" if pois == 1
                                   else "grammar dead state")),
            forwards=int(fwds_h),
            prompt_tokens=n,
            quality=quality,
        )

    def generate_stepwise(
        self,
        prompt: str,
        max_new_tokens: int = 512,
        constrained: bool = True,
        greedy: bool = True,
        temperature: float = 0.7,
        byte_budget: int = 3900,
    ) -> GenerationResult:
        """Host-driven per-token loop (one dispatch + readback per token);
        kept as the debugging/verification twin of `generate` (outputs must
        match under greedy decoding)."""
        t0 = time.perf_counter()
        last_logits, n = self._prefill(prompt)
        fsm_state = jnp.full((1,), self.fsm.start, dtype=jnp.int32)
        self._rng, k0 = jax.random.split(self._rng)
        tok, fsm_state = _first_token(
            last_logits, fsm_state, self.tables, k0,
            jnp.float32(temperature), greedy=greedy, constrained=constrained,
            kernels=self.kernels, rules=self.rules, logit_mask=self.logit_mask,
        )
        tok.block_until_ready()
        prefill_ms = (time.perf_counter() - t0) * 1e3

        out_ids: list[int] = []
        out_bytes = 0
        pos = n  # next write slot
        finished = False
        t1 = time.perf_counter()
        cur = tok
        steps = 0
        for _ in range(max_new_tokens):
            cur_host = int(jax.device_get(cur)[0])
            if cur_host == self.eos_id:
                finished = True
                break
            out_ids.append(cur_host)
            out_bytes += len(self.tokenizer.token_bytes(cur_host))
            if out_bytes >= byte_budget or pos >= self.max_len - 1:
                break  # truncation: finished stays False
            self._rng, k = jax.random.split(self._rng)
            cur, self.cache, fsm_state = _decode_step(
                self.params, self.cfg, self.cache,
                cur, jnp.full((1,), pos, dtype=jnp.int32), fsm_state,
                self.tables, k, jnp.float32(temperature),
                rules=self.rules, greedy=greedy, constrained=constrained,
                kernels=self.kernels, logit_mask=self.logit_mask,
            )
            pos += 1
            steps += 1
        else:
            # token budget exhausted: the final sampled-but-unemitted token
            # may be a clean EOS (parity with the device loop's eos flag)
            if int(jax.device_get(cur)[0]) == self.eos_id:
                finished = True
        decode_ms = (time.perf_counter() - t1) * 1e3

        return GenerationResult(
            text=self.tokenizer.decode(out_ids),
            token_ids=out_ids,
            prefill_ms=prefill_ms,
            decode_ms=decode_ms,
            steps=steps,
            finished=finished,
        )
