"""Grammar-aware speculative decoding: draft K tokens, verify in ONE pass.

The decode loop's unit of progress so far is one forward per emitted token
(plus the grammar fast-forward's *forced* chains). But schema-constrained
intent JSON is predictable far beyond what the grammar forces: key names,
quotes and braces follow low-entropy paths, and argument strings echo the
transcript and the prompt verbatim. Draft-and-verify multi-token stepping
(the standard streaming-LLM lever — WhisperKit-style pipelines, Medusa,
prompt lookup) converts that predictability into fewer target forwards:

- a cheap **drafter** proposes up to K continuation tokens per step
- ONE target forward over ``[cur, d_1..d_K]`` scores every draft position
  (in the memory-bound decode regime the K riding tokens are nearly free —
  the same weight read a 1-token step pays)
- the grammar FSM masks each position's logits at its *own* state, the
  longest draft prefix matching the target's masked greedy choice is
  accepted, and the target's pick at the first mismatch rides along as a
  bonus token — every verify step emits between 1 and K+1 tokens
- rejected positions roll back for free: the dense cache is indexed by
  position and attention masks slots beyond each query's position
  (models.llama._attend), so stale draft KV is either overwritten by the
  next contiguous block write or never attended

Because an accepted token is BY CONSTRUCTION the target's own masked greedy
choice, greedy speculative output is token-identical to the non-speculative
path regardless of draft quality — drafts only change how many forwards it
takes (tests/test_spec.py proves this differentially for every drafter).

Three composable drafters behind one interface:

- ``FSMDrafter``     — grammar lookahead (TokenFSM.lookahead): canonical
  tokenization of the forced byte run from the current state. Where
  fast-forward *forces* these chains (rewriting the model's tokenization),
  the drafter merely proposes them — output stays identical to plain greedy.
- ``PromptLookupDrafter`` — n-gram prompt lookup over prompt + generated
  suffix (no extra model; intent JSON echoes schema keys and the transcript).
- ``DraftModelDrafter``   — a tiny Llama checkpoint (train.make_tiny_ckpts
  builds one) greedy-drafting under the same grammar mask, with its own
  dense KV cache sharing the position-rollback property.

Env contract (read by ``spec_from_env``; services/brain.py plumbs it):
``SPEC_ENABLE=1`` turns the subsystem on, ``SPEC_K`` sets the draft width
(default 4), ``SPEC_DRAFTER`` picks a comma-chained drafter list
(``fsm,prompt`` default; ``model`` adds the draft model), and
``SPEC_DRAFT_MODEL`` points the model drafter at an orbax checkpoint dir.
With ``SPEC_ENABLE`` unset the engine never constructs a SpecDecoder and
the decode path is byte-identical to before this module existed.

Layouts: the dense DecodeEngine (rollback = position rewind in place) AND
the paged PagedDecodeEngine (ISSUE 8): draft tokens only ever land in
blocks the slot COW-owns — admission writes start past every shared/radix
block, so overwrite-before-attend holds at block granularity exactly as it
does for dense position rewind, and a rejected draft can never dirty a
cached chain. The pp staged cache has neither rollback story and refuses
``spec`` at construction. Greedy constrained decoding only (temperature
sampling needs rejection-sampling to preserve the distribution); the
batcher falls back to the plain chunk loop outside that envelope.

``SPEC_TRACE_SINK=<path>`` appends one JSONL record per cleanly released
request (prompt/generated ids + drafted/accepted counts) — the production
trace ``train.distill.train_draft_from_trace`` retrains ``draft-tiny`` on.
"""

from __future__ import annotations

import json
import threading
from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..grammar.fsm import DeviceFSM, fsm_advance, fsm_row
from ..models.llama import PRESETS, forward, forward_paged, init_kv_cache, init_params
from ..utils.compilewatch import watch_compiles
from ..utils.envcfg import env_bool, env_int, env_str
from ..utils.steplog import span
from .engine import (
    ChunkResult,
    _conf_init,
    _conf_stats,
    _masked_conf,
    chain_block,
    chain_byte_cap,
    prefill_row,
)


# ---------------------------------------------------------------- config


@dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knobs (one per engine; env-backed in services)."""

    k: int = 4  # draft width per verify step (emits 1..k+1 tokens/step)
    drafter: str = "fsm,prompt"  # comma chain: fsm | prompt | model
    draft_model: str | None = None  # orbax ckpt dir for "model"; None = random
    draft_preset: str = "draft-tiny"  # preset for a random-init draft model
    trace_sink: str | None = None  # JSONL path: per-request draft traces
    # (prompt/generated ids + drafted/accepted) for draft-model retraining


def spec_from_env() -> SpecConfig | None:
    """The SPEC_* env contract, read in ONE place. None = disabled — the
    engine keeps the exact pre-speculation decode path."""
    if not env_bool("SPEC_ENABLE"):
        return None
    return SpecConfig(
        k=max(1, env_int("SPEC_K", 4)),
        drafter=env_str("SPEC_DRAFTER", "fsm,prompt") or "fsm,prompt",
        draft_model=env_str("SPEC_DRAFT_MODEL") or None,
        trace_sink=env_str("SPEC_TRACE_SINK") or None,
    )


# ---------------------------------------------------------------- verify


def _draft_cap(draft_len, tokens_left, pos, max_pos, active):
    """Proposal length, capped so emission fits the token budget and cache
    (accepted writes land at pos .. pos+a <= max_pos-1, plus the bonus)."""
    dl = jnp.minimum(jnp.minimum(draft_len, tokens_left - 1), max_pos - 1 - pos)
    return jnp.where(active, jnp.maximum(dl, 0), 0)


def _verify_commit(logits, cur, pos, fsm_state, active, nbytes, tokens_left,
                   draft_toks, dl, step_tok, blk_tok, tables: DeviceFSM,
                   byte_len_table, byte_budget, logit_mask, K: int,
                   eos_id: int, pad_id: int, max_pos,
                   kernels: str = "xla", rules=None,
                   quality_lanes: bool = False):
    """Post-forward half of a verify step — THE one copy shared by the
    dense and paged jitted steps (jit-inlined at both call sites): FSM scan
    along the draft path, masked greedy per position, longest-prefix
    acceptance + bonus token, byte/token/cache caps, and the PR 7 poison
    gate applied per verify position (non-finite raw logits at any REAL
    block position, or a dead FSM state at entry / on the bonus advance).
    A poisoned row deactivates WITHOUT committing anything this step —
    batch-mates' carries (and tokens) are untouched, exactly the plain
    loops' containment contract. Returns (out, n_step, eos, new_cur,
    new_pos, new_state, new_active, nbytes, left, a, dl, poison)."""
    iw = jnp.arange(1 + K)[None, :]  # (1, 1+K) block index

    # FSM states along the draft path: states[i] = state after cur,d_1..d_i
    # (dead/padded transitions pin to -1; clamped only for safe gathers)
    def sstep(s, t):
        nxt = fsm_advance(tables, jnp.maximum(s, 0), jnp.maximum(t, 0))
        nxt = jnp.where((s >= 0) & (t >= 0), nxt, -1)
        return nxt, nxt

    _, states_rest = jax.lax.scan(sstep, fsm_state, draft_toks.T)  # (K, B)
    states = jnp.concatenate([fsm_state[None, :], states_rest], axis=0)

    conf_pos: list[tuple] = []  # per-position (margin, ent, forced_one)
    if kernels == "pallas" and tables.dense_mask is not None:
        # fused verify tail (ISSUE 12): every position's grammar mask +
        # argmax in ONE Pallas call (ops.masked_argmax_block folds the
        # (B, 1+K) positions into kernel rows, each streaming its own
        # state's mask tiles) instead of K+1 sequential (B, V) XLA rounds.
        # logit_mask is subsumed: padded-vocab ids are never grammar-legal.
        # Dead states clamp to 0 — their positions sit strictly past the
        # first draft mismatch (a draft token that matched the target's
        # grammar-legal pick cannot have made a dead transition), so the
        # clamped garbage can never affect acceptance, bonus, or poison.
        from ..ops import sharded_masked_argmax_block

        mesh = rules.mesh if rules is not None else None
        g = sharded_masked_argmax_block(
            mesh, logits, states.T, tables.dense_mask)  # (B, K+1)
        g = jnp.where((states.T >= 0), g, 0)
        if quality_lanes:
            # the fused kernel yields tokens, not masked logits — the conf
            # lanes re-derive them through the compressed path per position.
            # This re-pays part of the vocab work the kernel fused away,
            # but the dense_mask branch only EXISTS for toy vocabs (the
            # (S, V) mask must be small enough to materialize), so the
            # absolute cost is bounded; teaching the kernel to emit
            # top-2/entropy is the follow-up if a real-vocab fused tail
            # ever lands. QUALITY_ENABLE=0 removes it entirely.
            conf_pos = [_conf_stats(logits[:, i, :], states[i], tables,
                                    True, logit_mask)
                        for i in range(K + 1)]
    else:
        # target greedy per position under the SAME masks as the plain path
        # (logit_mask then grammar row) — identical argmax, one position at
        # a time to keep the (B, V) mask footprint of the non-spec step.
        # The conf lanes reduce the SAME masked logits (engine._masked_conf)
        # instead of re-masking per position — near-zero extra vocab work.
        gs = []
        for i in range(K + 1):
            s_i = states[i]
            lg = logits[:, i, :]
            if logit_mask is not None:
                lg = jnp.where(logit_mask[None, :], lg, -jnp.inf)
            row = fsm_row(tables, jnp.maximum(s_i, 0))
            legal = (row >= 0) & (s_i >= 0)[:, None]
            lg = jnp.where(legal, lg, -jnp.inf)
            gs.append(jnp.argmax(lg, axis=-1).astype(jnp.int32))
            if quality_lanes:
                conf_pos.append(_masked_conf(lg.astype(jnp.float32),
                                             jnp.sum(legal, axis=-1)))
        g = jnp.stack(gs, axis=1)  # (B, K+1) target greedy choices

    # accept: d_{i+1} must equal the target's pick, never be EOS (the plain
    # loop never emits EOS — it becomes the stopping cur), inside the capped
    # proposal; cumprod makes acceptance a prefix
    m = (draft_toks == g[:, :K]) & (draft_toks != eos_id) \
        & (jnp.arange(K)[None, :] < dl[:, None])
    a = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1), axis=1)  # (B,)

    # byte budget: accepted chain bytes must still fit after cur's —
    # engine.chain_byte_cap, the same one-token-overshoot contract as the
    # ff chain (truncation boundaries are part of token identity)
    a, chain_bytes = chain_byte_cap(a, draft_toks, step_tok, nbytes,
                                    byte_len_table, byte_budget)
    a = jnp.where(active, a, 0)

    # bonus: the target's choice at the first unaccepted position (its state
    # is on the accepted path, hence valid)
    g_a = jnp.take_along_axis(g, a[:, None], axis=1)[:, 0]
    s_a = jnp.take_along_axis(states.T, a[:, None], axis=1)[:, 0]
    s_next = fsm_advance(tables, jnp.maximum(s_a, 0), g_a)

    # poison gate (engine._poison_gate's verify-block twin): code 1 =
    # non-finite raw logits at any REAL position (tail duplicates repeat a
    # real position's logits, so masking them out loses nothing), code 2 =
    # dead FSM at entry or along the bonus advance. ``ok`` replaces
    # ``active`` in every commit below — on healthy rows they are equal,
    # so token identity with the pre-poison step is structural.
    real = iw <= dl[:, None]
    finite = jnp.all(jnp.isfinite(logits), axis=-1)  # (B, 1+K)
    nanp = active & jnp.any(~finite & real, axis=1)
    deadp = active & ~nanp & ((fsm_state < 0) | (s_a < 0) | (s_next < 0))
    poison = jnp.where(nanp, 1, jnp.where(deadp, 2, 0)).astype(jnp.int32)
    ok = active & ~(nanp | deadp)

    # emit cur + accepted prefix
    valid = (iw <= a[:, None]) & ok[:, None]
    out = jnp.where(valid, blk_tok, pad_id)  # (B, 1+K); slot i = token i
    n_step = jnp.where(ok, 1 + a, 0)
    acc_bytes = jnp.where(
        a > 0,
        jnp.take_along_axis(chain_bytes, jnp.maximum(a - 1, 0)[:, None],
                            axis=1)[:, 0],
        0)
    nbytes = nbytes + jnp.where(
        ok, byte_len_table[jnp.maximum(step_tok, 0)] + acc_bytes, 0)
    left = tokens_left - n_step

    new_state = jnp.where(ok, s_next, fsm_state)
    new_cur = jnp.where(ok, g_a, cur)
    new_pos = jnp.where(ok, pos + 1 + a, pos)

    eos = ok & (new_cur == eos_id)
    stop = (new_cur == eos_id) | (nbytes >= byte_budget) \
        | (new_pos >= max_pos - 1) | (left <= 0)
    new_active = ok & ~stop
    conf = _conf_init(active.shape[0])
    if quality_lanes:
        # ISSUE 15 conf lanes over the verify block: each position 0..a is
        # one verified decision (accepted drafts ARE the target's masked
        # greedy pick; position a is the bonus), scored at its own FSM
        # state — the dense/paged chunk loops and this verify path share
        # one readback contract (``ChunkResult.conf``). ``conf_pos`` was
        # computed above on the masked logits the greedy pick already
        # built; rejected positions (i > a) mask out of the fold here.
        msum, mmin, esum, forced, cnt = conf
        for i, (mg, en, f1) in enumerate(conf_pos):
            sel = ok & (i <= a)
            msum = msum + jnp.where(sel, mg, 0.0)
            mmin = jnp.where(sel, jnp.minimum(mmin, mg), mmin)
            esum = esum + jnp.where(sel, en, 0.0)
            forced = forced + jnp.where(sel & f1, 1, 0)
            cnt = cnt + sel.astype(jnp.int32)
        conf = (msum, mmin, esum, forced, cnt)
    return (out, n_step, eos, new_cur, new_pos, new_state, new_active,
            nbytes, left, a, dl, poison, conf)


@watch_compiles("spec.spec_verify_step")
@partial(
    jax.jit,
    static_argnames=("cfg", "rules", "K", "kernels", "eos_id", "pad_id",
                     "unroll", "max_len", "quality_lanes"),
    donate_argnames=("cache",),
)
def spec_verify_step(
    params,
    cfg,
    cache,
    cur,  # (B,) sampled-but-unfed token per row (the loop convention)
    pos,  # (B,) cur's write position
    fsm_state,  # (B,) grammar state AFTER cur
    active,  # (B,) bool
    nbytes,  # (B,) bytes emitted so far
    tokens_left,  # (B,) remaining token budget
    draft_toks,  # (B, K) int32 proposals; -1 pad past draft_len
    draft_len,  # (B,) int32 0..K
    tables: DeviceFSM,
    byte_len_table,  # (V,) int32
    byte_budget,  # scalar int32
    rules=None,
    logit_mask=None,
    nan_inject=None,  # (B,) bool or None — chaos drill (see engine.py twin)
    K: int = 4,
    kernels: str = "xla",
    eos_id: int = 2,
    pad_id: int = 0,
    unroll: int = 1,
    max_len: int | None = None,
    quality_lanes: bool = False,  # ISSUE 15 conf lanes (see engine twin)
):
    """ONE speculative step for every row: forward ``[cur, d_1..d_K]``,
    grammar-mask each position at its own FSM state, accept the longest
    draft prefix matching the target's greedy choice, take the target's
    pick at the first mismatch as the bonus token.

    Structurally the ff_body of chunk_decode_loop with the chain supplied
    by the host and acceptance decided by argmax-match instead of forcing:
    the block pads by duplicating the last valid (token, position) — cache
    scatter writes are idempotent — and emission goes out as ``cur`` plus
    the accepted prefix. Rollback is implicit: positions past the accepted
    frontier hold stale draft KV that the next contiguous block write
    overwrites before its queries can attend it (see _attend's causal +
    frontier masks)."""
    if max_len is None:
        max_len = cache["k"].shape[2]
    iw = jnp.arange(1 + K)[None, :]  # (1, 1+K) block index

    dl = _draft_cap(draft_len, tokens_left, pos, max_len, active)

    # block tokens [cur, d_1..d_dl, tail-duplicates]: engine.chain_block —
    # the ONE copy of the idempotent duplicate-tail construction shared
    # with the ff loop (never writes a pad/-1 over live KV)
    step_tok, blk_tok, blk_pos = chain_block(iw, cur, draft_toks, dl, active,
                                             pad_id, pos)

    logits, cache = forward(params, cfg, blk_tok, blk_pos, cache, rules,
                            attn_impl=kernels, unroll=unroll)  # (B, 1+K, V)
    if nan_inject is not None:
        logits = jnp.where(nan_inject[:, None, None] & active[:, None, None],
                           jnp.float32(jnp.nan), logits)

    (out, n_step, eos, new_cur, new_pos, new_state, new_active, nbytes, left,
     a, dl, poison, conf) = _verify_commit(
        logits, cur, pos, fsm_state, active, nbytes, tokens_left,
        draft_toks, dl, step_tok, blk_tok, tables, byte_len_table,
        byte_budget, logit_mask, K, eos_id, pad_id, max_len,
        kernels=kernels, rules=rules, quality_lanes=quality_lanes)
    return (out, n_step, eos, cache, new_cur, new_pos, new_state, new_active,
            nbytes, left, a, dl, poison, conf)


@watch_compiles("spec.paged_spec_verify_step")
@partial(
    jax.jit,
    static_argnames=("cfg", "rules", "K", "kernels", "eos_id", "pad_id",
                     "max_len", "kv_quant", "quality_lanes"),
    donate_argnames=("k_pool", "v_pool", "k_scale", "v_scale"),
)
def paged_spec_verify_step(
    params,
    cfg,
    k_pool,
    v_pool,
    block_tables,  # (B, max_blocks) int32
    cur,
    pos,
    fsm_state,
    active,
    nbytes,
    tokens_left,
    draft_toks,  # (B, K) int32 proposals; -1 pad past draft_len
    draft_len,  # (B,) int32 0..K
    tables: DeviceFSM,
    byte_len_table,
    byte_budget,
    trash_idx=None,  # (B,) int32 per-row parked-write index (dp-local trash)
    rules=None,
    logit_mask=None,
    nan_inject=None,  # (B,) bool or None — chaos drill
    k_scale=None,  # (L, N, bs, nkv) KV_QUANT scale planes (None = bf16 pool;
    # draft writes land values AND scales past the admission frontier, so
    # block-granular rollback covers the quantized tier unchanged — a
    # rejected draft's stale scale is overwritten with its stale value)
    v_scale=None,
    K: int = 4,
    kernels: str = "xla",
    eos_id: int = 2,
    pad_id: int = 0,
    max_len: int | None = None,
    kv_quant: str | None = None,
    quality_lanes: bool = False,  # ISSUE 15 conf lanes (see engine twin)
):
    """spec_verify_step's paged twin — the batched verify mode of the paged
    chunk path (ISSUE 8): per-slot ``[cur, d_1..d_K]`` columns in ONE
    (B, 1+K) forward_paged, per-row FSM-state scan, per-row accept lengths
    and per-row poison codes via ``_verify_commit``.

    Block-granular rollback contract: draft writes scatter through the
    slot's block table at positions pos..pos+dl — all past the admission
    frontier, hence in blocks the slot COW-owns (shared/radix chain blocks
    cover only positions below the first suffix write; see
    PagedDecodeEngine._prefill_chain). Rejected draft KV is therefore
    stale-but-private: the next verify block's contiguous writes overwrite
    it before any query can attend it (the paged attention paths mask by
    query position exactly like the dense _attend), and a cached radix
    chain can never contain it. Idle rows park their writes in their
    group's trash block via ``write_mask`` like the paged chunk loop."""
    max_pos = block_tables.shape[1] * k_pool.shape[2]
    if max_len is not None:
        max_pos = min(max_pos, max_len)
    iw = jnp.arange(1 + K)[None, :]

    dl = _draft_cap(draft_len, tokens_left, pos, max_pos, active)
    step_tok, blk_tok, blk_pos = chain_block(iw, cur, draft_toks, dl, active,
                                             pad_id, pos)

    logits, k_pool, v_pool, k_scale, v_scale = forward_paged(
        params, cfg, blk_tok, blk_pos, k_pool, v_pool, block_tables,
        rules=rules, attn_impl=kernels, write_mask=active,
        trash_idx=trash_idx, k_scale=k_scale, v_scale=v_scale,
        kv_quant=kv_quant)  # (B, 1+K, V)
    if nan_inject is not None:
        logits = jnp.where(nan_inject[:, None, None] & active[:, None, None],
                           jnp.float32(jnp.nan), logits)

    (out, n_step, eos, new_cur, new_pos, new_state, new_active, nbytes, left,
     a, dl, poison, conf) = _verify_commit(
        logits, cur, pos, fsm_state, active, nbytes, tokens_left,
        draft_toks, dl, step_tok, blk_tok, tables, byte_len_table,
        byte_budget, logit_mask, K, eos_id, pad_id, max_pos,
        kernels=kernels, rules=rules, quality_lanes=quality_lanes)
    return (out, n_step, eos, k_pool, v_pool, k_scale, v_scale, new_cur,
            new_pos, new_state, new_active, nbytes, left, a, dl, poison, conf)


# ---------------------------------------------------------------- drafters


class Drafter:
    """Proposal source. Stateless by default; stateful drafters (the draft
    model's KV cache) hook admission/release like the engine's slots."""

    name = "base"

    def on_admit(self, slot: int, ids: list[int]) -> None:  # pragma: no cover
        pass

    def on_release(self, slot: int) -> None:  # pragma: no cover
        pass

    def draft_one(self, ctx: list[int], state: int, k: int) -> list[int]:
        return []

    def draft_batch(self, ctxs, states, need, k: int):
        """(B, k) int32 proposals (-1 pad) + (B,) lengths. ``ctxs[b]`` is
        the FULL token context (prompt + emitted + cur) or None; ``need``
        marks rows wanting drafts (active and not already filled)."""
        B = len(ctxs)
        toks = np.full((B, k), -1, dtype=np.int32)
        lens = np.zeros((B,), dtype=np.int32)
        for b in range(B):
            if not need[b] or ctxs[b] is None:
                continue
            d = self.draft_one(ctxs[b], int(states[b]), k)[:k]
            if d:
                toks[b, : len(d)] = d
                lens[b] = len(d)
        return toks, lens


class FSMDrafter(Drafter):
    """Grammar lookahead: propose the canonical tokenization of the forced
    byte run from the current state (TokenFSM.lookahead). Free-choice
    states draft nothing."""

    name = "fsm"

    def __init__(self, fsm):
        self.fsm = fsm

    def draft_one(self, ctx, state, k):
        return self.fsm.lookahead(state, k)


class PromptLookupDrafter(Drafter):
    """N-gram prompt lookup (no model): find the longest suffix n-gram of
    the context earlier in the context and propose its continuation —
    intent JSON echoes schema keys, few-shot spans, and the transcript
    verbatim, so generated suffixes recur."""

    name = "prompt"

    def __init__(self, max_ngram: int = 4, min_ngram: int = 1):
        self.max_ngram = max_ngram
        self.min_ngram = max(1, min_ngram)

    def draft_one(self, ctx, state, k):
        L = len(ctx)
        if L < self.min_ngram + 1:
            return []
        # vectorized window match (the scan runs on EVERY verify step of
        # every row, over prompt-sized contexts — python slice compares
        # were O(max_ngram * L) allocations per step)
        arr = np.asarray(ctx, dtype=np.int64)
        for n in range(min(self.max_ngram, L - 1), self.min_ngram - 1, -1):
            key = arr[L - n:]
            hits = np.ones(L - n, dtype=bool)  # window starts 0..L-n-1
            for i in range(n):
                hits &= arr[i: i + (L - n)] == key[i]
            js = np.nonzero(hits)[0]
            if len(js):
                j = int(js[-1])  # rightmost earlier occurrence wins
                return ctx[j + n: j + n + k]
        return []


@watch_compiles("spec._draft_model_block")
@partial(
    jax.jit,
    static_argnames=("cfg", "K", "kernels"),
    donate_argnames=("cache",),
)
def _draft_model_block(params, cfg, cache, toks, poss, last_idx, state,
                       tables: DeviceFSM, logit_mask, K: int = 0,
                       kernels: str = "xla"):
    """Feed a (B, D) context block into the draft model's cache, then
    greedy-draft K tokens under the grammar mask. ``last_idx`` points at
    each row's last REAL context token inside the block (tail positions
    duplicate it — idempotent writes, and the duplicate's logits equal the
    original's because attention is position-masked). K=0 compiles the
    feed-only catch-up variant."""
    logits, cache = forward(params, cfg, toks, poss, cache, None,
                            attn_impl=kernels)
    last = jnp.take_along_axis(
        logits, last_idx[:, None, None], axis=1)[:, 0, :]  # (B, V)
    next_pos = jnp.take_along_axis(poss, last_idx[:, None], axis=1)[:, 0] + 1
    drafts = []
    s = state
    for i in range(K):
        lg = last
        if logit_mask is not None:
            lg = jnp.where(logit_mask[None, :], lg, -jnp.inf)
        row = fsm_row(tables, jnp.maximum(s, 0))
        lg = jnp.where((row >= 0) & (s >= 0)[:, None], lg, -jnp.inf)
        t = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        nxt = fsm_advance(tables, jnp.maximum(s, 0), t)
        s = jnp.where(s >= 0, nxt, s)
        drafts.append(t)
        if i < K - 1:
            logits, cache = forward(params, cfg, t[:, None],
                                    next_pos[:, None], cache, None,
                                    attn_impl=kernels)
            last = logits[:, 0, :]
            next_pos = next_pos + 1
    d = (jnp.stack(drafts, axis=1) if drafts
         else jnp.zeros((toks.shape[0], 0), jnp.int32))
    return d, cache


class DraftModelDrafter(Drafter):
    """A small Llama drafting greedily under the same grammar mask, with
    its own dense KV cache. The cache shares the target's position-rollback
    property: rejected draft KV is stale-but-masked, and each round's
    context delta is fed as a contiguous block before drafting resumes."""

    name = "model"

    def __init__(self, engine, cfg=None, params=None, preset: str = "draft-tiny",
                 seed: int = 0, feed_width: int | None = None):
        base = cfg or PRESETS[preset]
        # the draft model MUST speak the target's token ids: its vocab is
        # forced to the target width (random init) or padded up to it
        # (loaded checkpoint); a checkpoint WIDER than the target cannot
        # share ids
        self.cfg = replace(base, vocab_size=engine.cfg.vocab_size,
                           max_seq_len=engine.max_len)
        if params is None:
            params = init_params(self.cfg, jax.random.PRNGKey(seed))
        elif params["embed"].shape[0] > self.cfg.vocab_size:
            raise ValueError(
                f"draft checkpoint vocab {params['embed'].shape[0]} exceeds "
                f"target vocab {self.cfg.vocab_size}; draft and target must "
                "share token ids")
        elif params["embed"].shape[0] < self.cfg.vocab_size:
            pad = self.cfg.vocab_size - params["embed"].shape[0]
            params = dict(params)
            params["embed"] = jnp.pad(params["embed"], ((0, pad), (0, 0)))
            params["lm_head"] = jnp.pad(params["lm_head"], ((0, 0), (0, pad)))
        self.params = params
        self.engine = engine
        self.B = engine.batch_slots
        self.max_len = engine.max_len
        self.cache = init_kv_cache(self.cfg, self.B, engine.max_len)
        self.kernels = "xla"  # tiny model; the fused kernels buy nothing
        # host bookkeeping: ctx tokens already in the draft cache, and the
        # last (token, position) fed — idle/caught-up rows re-feed it
        # (idempotent) so a batched block never writes junk into live lines
        self._fed = [0] * self.B
        self._last = [(0, 0)] * self.B
        self._dead = [True] * self.B
        # feed-block width: a fully-accepting round's delta is K+1 (emitted
        # + new cur), so the width must cover SPEC_K+2 or every round pays
        # a catch-up dispatch exactly in the high-accept regime the knob is
        # tuned for; catch-up loops remain for chained drafters whose rows
        # lag several rounds
        self._dpad = max(8, feed_width or 0)

    @classmethod
    def from_checkpoint(cls, engine, path: str, feed_width: int | None = None):
        """Load an orbax draft checkpoint (train.make_tiny_ckpts writes the
        intent-tiny one) behind the drafting interface."""
        from ..models.llama import LlamaConfig
        from ..train import distill

        loaded = distill.load_ckpt_path(path, LlamaConfig)
        if loaded is None:
            raise ValueError(
                f"no draft checkpoint at {path} "
                "(run python -m tpu_voice_agent.train.make_tiny_ckpts)")
        cfg, params = loaded
        return cls(engine, cfg=cfg, params=params, feed_width=feed_width)

    def on_admit(self, slot, ids):
        n = len(ids)
        bucket = next((b for b in self.engine.prefill_buckets if n <= b), None)
        if bucket is None or n == 0:
            # prompt longer than any draft bucket (prefix-cached admissions
            # can exceed them): this slot just never drafts
            self._dead[slot] = True
            return
        toks = np.full((1, bucket), self.engine.pad_id, dtype=np.int32)
        toks[0, :n] = ids
        positions = np.arange(bucket, dtype=np.int32)[None, :]
        _, self.cache = prefill_row(
            self.params, self.cfg, self.cache,
            jnp.asarray(toks), jnp.asarray(positions), jnp.int32(slot),
            rules=None, kernels=self.kernels, fresh=True)
        self._fed[slot] = n
        self._last[slot] = (int(ids[-1]), n - 1)
        self._dead[slot] = False

    def on_release(self, slot):
        self._fed[slot] = 0
        self._last[slot] = (0, 0)
        self._dead[slot] = True

    def draft_batch(self, ctxs, states, need, k):
        B = len(ctxs)
        toks = np.full((B, k), -1, dtype=np.int32)
        lens = np.zeros((B,), dtype=np.int32)
        rows = [b for b in range(B)
                if need[b] and ctxs[b] is not None and not self._dead[b]
                and len(ctxs[b]) + k + 1 < self.max_len]
        if not rows:
            return toks, lens
        deltas = {b: ctxs[b][self._fed[b]:] for b in rows}
        while True:
            blk_t = np.zeros((B, self._dpad), dtype=np.int32)
            blk_p = np.zeros((B, self._dpad), dtype=np.int32)
            last_idx = np.zeros((B,), dtype=np.int32)
            more = False
            for b in range(B):
                t0, p0 = self._last[b]
                seq = deltas.get(b, [])[: self._dpad] if b in rows else []
                if b in rows:
                    deltas[b] = deltas[b][len(seq):]
                    more |= bool(deltas[b])
                base_p = p0 + 1
                for i in range(self._dpad):
                    if i < len(seq):
                        blk_t[b, i] = seq[i]
                        blk_p[b, i] = base_p + i
                    else:  # duplicate the last real (token, pos): idempotent
                        lt, lp = ((seq[-1], base_p + len(seq) - 1)
                                  if seq else (t0, p0))
                        blk_t[b, i] = lt
                        blk_p[b, i] = lp
                last_idx[b] = max(len(seq) - 1, 0)
                if b in rows and seq:
                    self._fed[b] += len(seq)
                    self._last[b] = (int(seq[-1]), base_p + len(seq) - 1)
            kk = 0 if more else k
            d, self.cache = _draft_model_block(
                self.params, self.cfg, self.cache,
                jnp.asarray(blk_t), jnp.asarray(blk_p),
                jnp.asarray(last_idx), jnp.asarray(states),
                self.engine.tables, self.engine.logit_mask,
                K=kk, kernels=self.kernels)
            if not more:
                break
        d_h = np.asarray(jax.device_get(d))
        for b in rows:
            toks[b] = d_h[b]
            lens[b] = k
        return toks, lens


class ChainDrafter(Drafter):
    """First non-empty proposal wins, per row — e.g. grammar lookahead for
    structural runs, prompt lookup for echoed content."""

    name = "chain"

    def __init__(self, drafters: list[Drafter]):
        if not drafters:
            raise ValueError("empty drafter chain")
        self.drafters = drafters
        self.name = "+".join(d.name for d in drafters)

    def on_admit(self, slot, ids):
        for d in self.drafters:
            d.on_admit(slot, ids)

    def on_release(self, slot):
        for d in self.drafters:
            d.on_release(slot)

    def draft_batch(self, ctxs, states, need, k):
        B = len(ctxs)
        toks = np.full((B, k), -1, dtype=np.int32)
        lens = np.zeros((B,), dtype=np.int32)
        remaining = np.array(need, dtype=bool)
        for d in self.drafters:
            if not remaining.any():
                break
            t, l = d.draft_batch(ctxs, states, remaining, k)
            fill = remaining & (l > 0)
            toks[fill] = t[fill]
            lens[fill] = l[fill]
            remaining &= ~fill
        return toks, lens


def build_drafter(cfg: SpecConfig, engine) -> Drafter:
    """SPEC_DRAFTER name(s) -> a Drafter (comma chain = first-hit-wins)."""
    out: list[Drafter] = []
    for name in (s.strip() for s in cfg.drafter.split(",")):
        if not name:
            continue
        if name == "fsm":
            out.append(FSMDrafter(engine.fsm))
        elif name == "prompt":
            out.append(PromptLookupDrafter())
        elif name == "model":
            width = cfg.k + 2
            if cfg.draft_model:
                out.append(DraftModelDrafter.from_checkpoint(
                    engine, cfg.draft_model, feed_width=width))
            else:
                out.append(DraftModelDrafter(engine, preset=cfg.draft_preset,
                                             feed_width=width))
        else:
            raise ValueError(f"unknown SPEC_DRAFTER {name!r} "
                             "(fsm | prompt | model, comma-chained)")
    if not out:
        raise ValueError(f"SPEC_DRAFTER {cfg.drafter!r} names no drafter")
    return out[0] if len(out) == 1 else ChainDrafter(out)


# ---------------------------------------------------------------- decoder


class SpecDecoder:
    """Per-engine speculative decode driver (dense AND paged layouts).

    Owns per-slot host context (prompt + emitted tokens — drafters are
    host-side) and substitutes for the on-device chunk loop behind
    ``DecodeEngine.decode_chunk``: each chunk runs up to ``chunk_steps``
    verify steps, each ONE (B, 1+K) target forward that advances every
    active row by 1..K+1 tokens. The host pays one small readback per
    verify step (drafting needs cur/state) — the trade the chunk loop
    exists to avoid, bought back K-fold in steps; where that readback
    dominates a step, prefer fast-forward or raise SPEC_K.

    On a ``PagedDecodeEngine`` the verify step goes through
    ``paged_spec_verify_step`` (writes scatter through the slot's block
    table, COW-owned blocks only) and each step first claims block
    coverage for the worst case via ``engine.spec_grow`` — a slot whose
    pool claim fails truncates alone, exactly like the plain paged chunk.
    Warm radix admissions seed the drafters with the full cached prompt
    ids (``on_admit`` fires on the radix-hit path too), so prompt-lookup
    drafting sees the whole multi-turn transcript from the first verify
    step of a warm turn.
    """

    def __init__(self, engine, cfg: SpecConfig, drafter: Drafter | None = None):
        self.paged = engine.allocator is not None
        if not engine._alloc_dense_cache and not self.paged:
            raise ValueError(
                "speculative decoding needs per-position KV rollback: the "
                "dense layout rewinds positions in place, the paged layout "
                "overwrites COW-owned draft blocks; this engine layout "
                "(staged pp cache) supports neither — serve speculation on "
                "the dense or paged engines")
        self.engine = engine
        self.cfg = cfg
        self.K = max(1, int(cfg.k))
        # ISSUE 15: the verify steps carry the same conf lanes as the
        # chunk loops (one readback contract across planes)
        self.quality_lanes = engine.quality_lanes
        self.drafter = drafter if drafter is not None else build_drafter(cfg, engine)
        self._ctx: list[list[int] | None] = [None] * engine.batch_slots
        self._prompt_len = [0] * engine.batch_slots
        # cumulative accounting behind the spec.* gauges
        self._drafted = 0
        self._accepted = 0
        self._steps = 0
        self._emitted = 0
        # per-slot accounting for the trace sink + per-request forwards
        B = engine.batch_slots
        self._slot_drafted = np.zeros((B,), np.int64)
        self._slot_accepted = np.zeros((B,), np.int64)
        self._slot_fwds = np.zeros((B,), np.int64)
        # generation fence: a warm restart (watchdog) bumps this so a
        # thread wedged INSIDE decode_chunk discards instead of committing
        # further verify steps against the restarted engine state — the
        # spec path mutates engine KV per step, so the scheduler's
        # epoch-at-commit check alone cannot contain it
        self._gen = 0
        # SPEC_TRACE_SINK: per-request JSONL draft traces for
        # train.distill.train_draft_from_trace (production retraining)
        self._trace_path = cfg.trace_sink
        self._trace_lock = threading.Lock()

    # ------------------------------------------------------------ hooks

    def on_admit(self, slot: int, ids: list[int]) -> None:
        self._ctx[slot] = list(ids)
        self._prompt_len[slot] = len(ids)
        self._slot_drafted[slot] = 0
        self._slot_accepted[slot] = 0
        self._slot_fwds[slot] = 0
        self.drafter.on_admit(slot, list(ids))

    def on_release(self, slot: int, ok: bool = True) -> None:
        ctx = self._ctx[slot]
        if (ok and self._trace_path and ctx is not None
                and len(ctx) > self._prompt_len[slot]):
            self._trace_record(slot, ctx)
        self._ctx[slot] = None
        self._prompt_len[slot] = 0
        self.drafter.on_release(slot)

    def reset(self) -> None:
        """Warm-restart hook (engine.warm_restart): drop every slot's host
        context and drafter state, and bump the generation fence so a
        decode_chunk wedged mid-flight stops dispatching verify steps
        against the restarted engine."""
        self._gen += 1
        for b in range(self.engine.batch_slots):
            if self._ctx[b] is not None:
                self._ctx[b] = None
                self._prompt_len[b] = 0
                self.drafter.on_release(b)

    def _trace_record(self, slot: int, ctx: list[int]) -> None:
        """Append one JSONL draft-trace record (cleanly released requests
        only — errored/cancelled streams are not training data)."""
        rec = {
            "plane": "paged" if self.paged else "dense",
            "drafter": self.drafter.name,
            "k": self.K,
            "prompt_ids": ctx[: self._prompt_len[slot]],
            "generated_ids": ctx[self._prompt_len[slot]:],
            "drafted": int(self._slot_drafted[slot]),
            "accepted": int(self._slot_accepted[slot]),
            "verify_steps": int(self._slot_fwds[slot]),
        }
        try:
            with self._trace_lock, open(self._trace_path, "a") as f:
                f.write(json.dumps(rec, separators=(",", ":")) + "\n")
        except OSError:  # tracing must never fail serving
            return
        from ..utils import get_metrics

        get_metrics().inc("spec.trace_records")

    # ------------------------------------------------------------ chunk

    def _verify(self, cur, pos, fsm, active, nbytes, tokens_left, dtoks,
                dlen, byte_budget: int, nan_inject):
        """One layout-dispatched verify step. Returns the step tuple with
        the engine's KV already committed back onto the engine."""
        eng = self.engine
        if self.paged:
            (out, n, eosf, eng.k_pool, eng.v_pool, eng.k_scale, eng.v_scale,
             cur, pos, fsm, active,
             nbytes, tokens_left, a, dl, pois, conf) = paged_spec_verify_step(
                eng.params, eng.cfg, eng.k_pool, eng.v_pool,
                eng.block_tables, cur, pos, fsm, active, nbytes, tokens_left,
                jnp.asarray(dtoks, jnp.int32), jnp.asarray(dlen),
                eng.tables, eng.byte_len_table, jnp.int32(byte_budget),
                trash_idx=eng._trash_idx, rules=eng.rules,
                logit_mask=eng.logit_mask, nan_inject=nan_inject,
                k_scale=eng.k_scale, v_scale=eng.v_scale,
                K=self.K, kernels=eng.kernels, eos_id=eng.eos_id,
                pad_id=eng.pad_id, max_len=eng.max_len,
                kv_quant=eng.kv_quant, quality_lanes=self.quality_lanes)
        else:
            (out, n, eosf, eng.cache, cur, pos, fsm, active, nbytes,
             tokens_left, a, dl, pois, conf) = spec_verify_step(
                eng.params, eng.cfg, eng.cache, cur, pos, fsm, active,
                nbytes, tokens_left,
                jnp.asarray(dtoks, jnp.int32), jnp.asarray(dlen),
                eng.tables, eng.byte_len_table, jnp.int32(byte_budget),
                rules=eng.rules, logit_mask=eng.logit_mask,
                nan_inject=nan_inject,
                K=self.K, kernels=eng.kernels, eos_id=eng.eos_id,
                pad_id=eng.pad_id, unroll=eng.decode_unroll,
                max_len=eng.max_len, quality_lanes=self.quality_lanes)
        return (out, n, eosf, cur, pos, fsm, active, nbytes, tokens_left,
                a, dl, pois, conf)

    def decode_chunk(self, cur, pos, fsm, active, nbytes, tokens_left, key,
                     temperature: float, byte_budget: int, chunk_steps: int,
                     greedy: bool, live=None, nan_inject=None) -> ChunkResult:
        """Drop-in for the engine's decode_chunk, under its contract and
        signature; greedy constrained only (the engine gates: ``key``,
        ``temperature`` and ``live`` go unused). In the record ``out``/
        ``n``/``eos``/``fwds``/``poison``/``conf`` are host values (the
        per-step readbacks already paid), and it widens to per-row verify
        participation, accept and draft counts (``row_fwds``,
        ``row_accepts``, ``row_drafted``) — the scheduler folds them into
        per-request ``GenerationResult.forwards`` and the cost ledger, and
        the spec gauges reflect paged-plane traffic through the same
        counters. The chaos mask injects at the chunk's first verify step,
        exactly like the plain loops' one-shot mask."""
        if not greedy:
            raise ValueError("the spec decoder verifies greedy picks only")
        eng = self.engine
        B = eng.batch_slots
        K = self.K
        gen0 = self._gen
        cur_h, fsm_h, act_h = (np.asarray(x) for x in
                               jax.device_get((cur, fsm, active)))
        eos_total = (~act_h) & (cur_h == eng.eos_id)
        outs: list[list[int]] = [[] for _ in range(B)]
        fwds = 0
        drafted = accepted = 0
        row_fwds = np.zeros((B,), np.int64)
        row_accepts = np.zeros((B,), np.int64)
        row_drafted = np.zeros((B,), np.int64)
        poison_h = np.zeros((B,), np.int32)
        # per-row conf lanes accumulated across the chunk's verify steps
        # (host arrays — each step pays its readback anyway); the fold
        # rule is THE shared one, utils.quality.conf_fold
        conf_acc = None
        for _ in range(chunk_steps):
            if not act_h.any() or self._gen != gen0:
                break
            ctxs = [
                (self._ctx[b] + [int(cur_h[b])])
                if act_h[b] and self._ctx[b] is not None else None
                for b in range(B)
            ]
            # the step ledger's draft stage: drafting is the host-side cost
            # the verify speedup pays for, so it gets its own ledger line
            with span("sched.decode.draft"):
                dtoks, dlen = self.drafter.draft_batch(ctxs, fsm_h, act_h, K)
            dlen = np.minimum(np.asarray(dlen, np.int32), K)
            if self._gen != gen0:
                # draft_batch is a host-blocking point (draft-model feeds
                # pay their own readbacks): a warm restart while it was
                # wedged must stop us BEFORE we mutate the restarted
                # engine's allocator or dispatch into its pools
                break
            if self.paged:
                # claim worst-case block coverage for this verify step
                # (cur + K drafts) — ACTIVE rows only: a slot that hit EOS
                # mid-chunk stays engine-owned until the scheduler releases
                # it post-chunk, and growing it every step would bleed the
                # pool for nothing. A slot whose claim fails truncates
                # alone at its covered frontier, like the plain paged chunk
                for b in eng.spec_grow(1 + K, active=act_h):
                    tokens_left = tokens_left.at[b].set(0)
            (out, n, eosf, cur, pos, fsm, active, nbytes, tokens_left,
             a, dl, pois, conf) = self._verify(
                cur, pos, fsm, active, nbytes, tokens_left, dtoks, dlen,
                byte_budget, nan_inject)
            nan_inject = None
            # one combined transfer per verify step: the drafters need the
            # new cur/state, the context needs the emitted tokens — and
            # ``pos`` rides along so the paged engine's growth target
            # reconciles to each row's ACTUAL frontier every step instead
            # of ratcheting by the worst case (a low-accept step advances
            # pos by 1, not 1+K; without the clamp the claims compound)
            prev_act = act_h
            (out_h, n_h, eos_h, cur_h, fsm_h, act_h, a_h, dl_h, pois_h,
             pos_h, conf_h) = (
                jax.device_get((out, n, eosf, cur, fsm, active, a, dl, pois,
                                pos, conf)))
            (out_h, n_h, eos_h, cur_h, fsm_h, act_h, a_h, dl_h, pois_h,
             pos_h) = (np.asarray(x) for x in
                       (out_h, n_h, eos_h, cur_h, fsm_h, act_h, a_h, dl_h,
                        pois_h, pos_h))
            if self._gen != gen0:
                break  # warm-restarted mid-step: discard, stop dispatching
            if self.quality_lanes:
                from ..utils.quality import conf_fold

                conf_acc = conf_fold(conf_acc, conf_h)
            if self.paged:
                eng.reconcile_coverage(pos_h)
            fwds += 1
            drafted += int(dl_h.sum())
            accepted += int(a_h.sum())
            row_fwds += prev_act.astype(np.int64)
            row_accepts += a_h.astype(np.int64)
            row_drafted += dl_h.astype(np.int64)
            poison_h = np.maximum(poison_h, pois_h)
            self._slot_fwds += prev_act.astype(np.int64)
            self._slot_drafted += dl_h.astype(np.int64)
            self._slot_accepted += a_h.astype(np.int64)
            for b in range(B):
                if n_h[b] > 0:
                    toks = [int(t) for t in out_h[b, : n_h[b]]]
                    outs[b].extend(toks)
                    if self._ctx[b] is not None:
                        self._ctx[b].extend(toks)
            eos_total = eos_total | eos_h.astype(bool)

        width = max(1, max((len(o) for o in outs), default=1))
        out_arr = np.full((B, width), eng.pad_id, dtype=np.int32)
        n_arr = np.zeros((B,), dtype=np.int32)
        for b, o in enumerate(outs):
            out_arr[b, : len(o)] = o
            n_arr[b] = len(o)

        self._steps += fwds
        self._drafted += drafted
        self._accepted += accepted
        self._emitted += int(n_arr.sum())
        if fwds:
            from ..utils import get_metrics

            m = get_metrics()
            m.inc("spec.drafted_tokens", float(drafted))
            m.inc("spec.accepted_tokens", float(accepted))
            m.inc("spec.verify_steps", float(fwds))
            if self._drafted > 0:
                m.set_gauge("spec.accept_rate", self._accepted / self._drafted)
            if self._steps > 0:
                m.set_gauge("spec.tokens_per_step", self._emitted / self._steps)
        # a chunk that ran zero verify steps reports fresh zero conf lanes
        conf = None if not self.quality_lanes else tuple(
            conf_acc if conf_acc is not None else
            (np.zeros((B,)), np.full((B,), np.inf), np.zeros((B,)),
             np.zeros((B,), np.int64), np.zeros((B,), np.int64)))
        return ChunkResult(
            out_arr, n_arr, eos_total, cur, pos, fsm, active, nbytes,
            tokens_left, fwds=fwds, poison=poison_h, rows=B, conf=conf,
            row_fwds=row_fwds, row_accepts=row_accepts,
            row_drafted=row_drafted)

    # ------------------------------------------------------------ stats

    def stats(self) -> dict:
        """Cumulative speculation counters (bench/debug surface)."""
        return {
            "drafted": self._drafted,
            "accepted": self._accepted,
            "verify_steps": self._steps,
            "emitted": self._emitted,
            "accept_rate": (self._accepted / self._drafted
                            if self._drafted else 0.0),
            "tokens_per_step": (self._emitted / self._steps
                                if self._steps else 0.0),
        }
