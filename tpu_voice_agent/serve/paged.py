"""Paged KV cache serving: block pool + allocator + paged decode engine.

SURVEY.md §7 step 2 / VERDICT round-1 missing #5. The dense engine gives
every batch slot a max_len cache line — HBM pays worst-case context per
slot, and the shared prompt prefix is COPIED into every admitted slot.
Here sequences own fixed-size blocks of one global pool via per-slot block
tables:

- HBM holds only the context each request actually has (a 40-token command
  in a 32-slot server no longer reserves 32 x max_len lines)
- the shared system-prompt+few-shot prefix is ONE set of pool blocks per
  dp group, refcounted and referenced by every slot's table — admission
  writes only the sub-block remainder tail plus the user suffix
- decode attends through ops.paged_attention (block-table indirection in
  the kernel's index map; no contiguous per-sequence cache ever exists)
- block tables grow at chunk boundaries as sequences decode, so capacity
  tracks live tokens, not budgets

``PagedDecodeEngine`` is a drop-in for ``DecodeEngine`` under the
continuous batcher (serve.scheduler) via the engine's decode_chunk /
prefill_slot / release_slot surface. On a (dp, tp) mesh the pool shards
its block axis over dp and kv heads over tp
(parallel.mesh.paged_pool_shardings): the allocator hands each slot only
blocks from its dp group's range, so paged decode attention stays
shard-local (ops.sharded_paged_attention) exactly like the dense path.
Single-request ``generate()`` stays on the dense engine.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from functools import lru_cache, partial
from math import prod
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..grammar.fsm import fsm_advance
from ..models.family import FFN, FFN_PACK_ROWS, family
from ..models.llama import forward_paged
from ..utils.compilewatch import get_compile_watcher, watch_compiles
from ..utils.steplog import (
    ALLOC_SPAN,
    FIRST_TOKEN_SPAN,
    PREFILL_CALL_SPAN,
    PREFILL_STAGE_SPAN,
    REQUEST_SPAN,
    STATE_RESTORE_SPAN,
    span,
)
from .engine import (
    ChunkResult,
    DecodeEngine,
    _conf_accumulate,
    _conf_init,
    _conf_stats,
    _mask_sample_advance,
    _poison_gate,
)
from .radix import RadixCache

# the float32 attention scores ONE chunk of a chunked prefix prefill may hold a
# layer (``PagedDecodeEngine._compute_prefix_kv``): what sizes the chunk
PREFIX_SCORE_BYTES = 512 << 20

SLOT_STATE_SPAN = REQUEST_SPAN + ".slot_state"


class PoolExhausted(RuntimeError):
    """The KV pool has no free blocks. A DEDICATED class so the scheduler
    can isolate it per request without swallowing real device faults
    (XlaRuntimeError also subclasses RuntimeError)."""


class _ChunkedPrefill:
    """Cursor of one in-flight chunked admission (ISSUE 19): host state
    between ``begin_chunked_prefill`` and the final ``chunked_prefill_step``.
    All pool blocks are already allocated and the slot's table row set —
    only the suffix forwards remain, one ``(1, C)`` dispatch per step."""

    __slots__ = ("slot", "ids", "suffix", "P", "C", "n_chunks", "j",
                 "total_ms")

    def __init__(self, slot: int, ids: list[int], suffix: list[int],
                 P: int, C: int, n_chunks: int):
        self.slot = slot
        self.ids = ids
        self.suffix = suffix
        self.P = P              # tokens served from cached KV (chain/prefix)
        self.C = C              # PREFILL_CHUNK_TOKENS
        self.n_chunks = n_chunks
        self.j = 0              # chunks completed
        self.total_ms = 0.0     # accumulated compute (prefill_ms at finish)


@dataclass(frozen=True)
class PreparedAdmission:
    """The HOST half of one static-prefix admission (ISSUE 35), from
    ``PagedDecodeEngine.prepare_admission``: the slot is released and holds
    its blocks, nothing has been launched. Whatever can fail for ONE request
    (a chaos fault, ``PoolExhausted``) has failed by now; what is left,
    ``admit_group``, is device work the requests of a step share."""

    slot: int
    n: int  # prompt tokens
    cached: int  # of them behind the static prefix (P)
    suffix: tuple  # the n - P token ids the forward computes
    bucket: int  # the suffix bucket this admission alone would run at
    blocks: tuple  # the slot's table: the prefix's shared blocks, then its own
    tail_at: int  # flat pool index where the prefix's sub-block tail lands


@dataclass(frozen=True)
class AdmissionRecord:
    """What ``admit_group`` says of one admission, as a value (the way
    ``decode_chunk`` returns ``ChunkResult``; the per-slot ``prefill_slot``
    still leaves its two ``_last_prefill_*`` attributes)."""

    slot: int
    cached_tokens: int
    compute_ms: float  # this admission's share of the call's wall
    rows: int  # admissions that rode the call
    width: int  # rows the call computed: 1, or ``admit_rows``
    bucket: int  # positions a row of the call computed


@dataclass(frozen=True)
class GroupAdmission:
    """One ``admit_group`` call. From a call of one row: ``logits`` (1, V),
    what ``prefill_slot`` returns (the first-token launch is the caller's).
    From a grouped one: ``picked``, what the caller's ``pick`` made of the
    (A, 1, V) logits inside the group's one program."""

    records: tuple
    logits: Any = None
    picked: Any = None


class BlockAllocator:
    """Host-side free-list allocator with refcounts (prefix blocks are
    shared across slots). ``n_groups`` partitions the pool into equal
    contiguous ranges (one per mesh dp group); the first block of each
    group is reserved as that group's trash block — idle batcher rows park
    their writes there — and is never handed out. Block ids are GLOBAL."""

    def __init__(self, n_blocks: int, n_groups: int = 1):
        if n_blocks % n_groups:
            raise ValueError(f"pool size {n_blocks} must divide into {n_groups} groups")
        bpg = n_blocks // n_groups
        if bpg < 2:
            raise ValueError("each group needs >= 2 blocks (block 0 is reserved)")
        self.n_blocks = n_blocks
        self.n_groups = n_groups
        self.blocks_per_group = bpg
        self._free = [
            list(range((g + 1) * bpg - 1, g * bpg, -1)) for g in range(n_groups)
        ]
        self._refs: dict[int, int] = {}

    def alloc(self, k: int, group: int = 0) -> list[int]:
        from ..utils.chaos import chaos_fire

        if chaos_fire("alloc_fail"):
            # drill for the pool-pressure degradation ladder: same type a
            # genuinely exhausted pool raises, so eviction/retry/shed paths
            # are exercised end to end
            raise PoolExhausted("chaos: injected allocation failure")
        free = self._free[group]
        if len(free) < k:
            raise PoolExhausted(
                f"KV pool exhausted: need {k} blocks, {len(free)} free of "
                f"{self.blocks_per_group} in group {group} (size the pool to "
                "the live-token working set, not per-slot budgets)")
        out = [free.pop() for _ in range(k)]
        for b in out:
            self._refs[b] = 1
        return out

    def ref(self, blocks: list[int]) -> None:
        # validate the WHOLE batch before touching any refcount: a bare
        # KeyError mid-loop would name nothing AND leave the earlier
        # blocks' counts bumped (sharing bugs — radix chains, prefix
        # blocks — need the id and an all-or-nothing failure)
        for b in blocks:
            if b not in self._refs:
                raise ValueError(
                    f"ref of untracked block {b}: not allocated, or already "
                    "fully freed (use-after-free)")
        for b in blocks:
            self._refs[b] += 1

    def free(self, blocks: list[int]) -> None:
        # all-or-nothing like ref(): account for duplicates inside one call
        # (freeing [b, b] is two decrements and must both be covered)
        need: dict[int, int] = {}
        for b in blocks:
            need[b] = need.get(b, 0) + 1
        for b, k in need.items():
            if self._refs.get(b, 0) < k:
                raise ValueError(
                    f"double free of block {b}: no live refcount (freed more "
                    "times than alloc'd + ref'd)")
        for b in blocks:
            r = self._refs[b] - 1
            if r == 0:
                del self._refs[b]
                self._free[b // self.blocks_per_group].append(b)
            else:
                self._refs[b] = r

    def reserve(self, blocks: list[int]) -> None:
        """Adopt specific block ids into a FRESH allocator as allocated
        (refcount 1): the warm-restart path rebuilds the allocator but must
        keep the static-prefix blocks — whose pool KV survives the restart —
        exactly where they are. All-or-nothing like ref()/free()."""
        for b in blocks:
            g = b // self.blocks_per_group
            if b in self._refs or b not in self._free[g]:
                raise ValueError(f"reserve of unavailable block {b}")
        for b in blocks:
            self._free[b // self.blocks_per_group].remove(b)
            self._refs[b] = 1

    def refcount(self, block: int) -> int:
        """Live refcount of one block (0 = untracked/free). Refcounts are
        the single source of truth for sharing: the radix tree's eviction
        may only free a block whose sole ref is the tree's own."""
        return self._refs.get(block, 0)

    def free_blocks(self, group: int = 0) -> int:
        """How many blocks ``alloc`` could hand out from ``group`` now."""
        return len(self._free[group])

    @property
    def blocks_in_use(self) -> int:
        return self.n_blocks - self.n_groups - sum(len(f) for f in self._free)

    @property
    def blocks_shared(self) -> int:
        """Blocks with more than one live ref — KV physically stored once
        but referenced by several owners (slots sharing a prefix chain,
        the radix tree + a live slot). The dedup the paged+radix planes
        exist to create; exported as ``paged.kv_blocks_shared``."""
        return sum(1 for r in self._refs.values() if r > 1)

    @property
    def usable_blocks(self) -> int:
        """Pool capacity net of the per-group reserved trash blocks."""
        return self.n_blocks - self.n_groups

    @property
    def utilization(self) -> float:
        """KV page utilization in [0, 1] — the saturation signal a scraper
        watches to size ``BRAIN_POOL_BLOCKS`` against the live-token
        working set (1.0 means the next admission raises PoolExhausted)."""
        u = self.usable_blocks
        return self.blocks_in_use / u if u > 0 else 0.0


def record_pool_gauges(alloc: "BlockAllocator", engine=None) -> None:
    """Export one allocator's occupancy as runtime gauges. Called by the
    continuous batcher each chunk (so the gauges track the live pool the
    scheduler actually allocates from) and directly by tests.

    With ``engine`` given the BYTES-denominated view rides along (ISSUE 12
    satellite): block counts stopped being a unit of HBM the moment
    KV_QUANT halved/quartered bytes-per-block, so capacity dashboards and
    the swarm's saturation attribution get ``paged.kv_bytes_*`` beside the
    counts. ``paged.kv_utilization`` itself needs NO re-expression — it is
    used ÷ usable of ONE pool whose blocks are uniform, so the fraction is
    invariant under any bytes-per-block (audited in docs/PERF.md)."""
    from ..utils import get_metrics

    m = get_metrics()
    m.set_gauge("paged.kv_blocks_used", float(alloc.blocks_in_use))
    m.set_gauge("paged.kv_blocks_total", float(alloc.usable_blocks))
    m.set_gauge("paged.kv_utilization", alloc.utilization)
    m.set_gauge("paged.kv_blocks_shared", float(alloc.blocks_shared))
    if engine is not None:
        bpb = engine.kv_bytes_per_block
        m.set_gauge("paged.kv_quant_bits", float(engine.kv_quant_bits))
        m.set_gauge("paged.kv_bytes_per_block", float(bpb))
        m.set_gauge("paged.kv_bytes_per_token", float(bpb // engine.block_size))
        # the K planes a token is written to, from the record's spec: a layer's —
        # or, where the layers run more than once, one for every (pass, layer)
        m.set_gauge("paged.kv_planes", float(sum(
            p[0] for p in engine.family.cache["planes"]["k"].values())))
        # what a SLOT holds beside its blocks (a recurrent state, a convolution
        # tail): the record's per-slot planes, whatever the family; 0 for K/V alone
        m.set_gauge("paged.state_bytes_per_slot", float(sum(
            prod(shape) * jnp.dtype(dtype).itemsize
            for side in engine.family.cache["slot_planes"].values() for shape, dtype in side.values())))
        m.set_gauge("paged.kv_bytes_used", float(alloc.blocks_in_use * bpb))
        m.set_gauge("paged.kv_bytes_total", float(alloc.usable_blocks * bpb))


def kv_planes(pool):
    """The (L, N, bs, nkv, hd) K or V planes of a pool. A decoder whose
    requests hold K/V alone has nothing else there; one with a recurrent
    state (``models.sambay``) keeps its per-slot planes beside them in a
    pytree, under other keys, and everything that moves BLOCKS reads and
    writes ``["kv"]``. (A pool by layer KIND, ``models.dots3``, holds the
    planes its ``cache_spec`` names — its V side no ``"kv"`` at all — and is
    moved plane by plane as a tree, never through here.)"""
    return pool["kv"] if isinstance(pool, dict) else pool


def build_pools(spec: dict, blocks: int, block_size: int, slots: int, zeros=jnp.zeros):
    """THE pool constructor: the (k_pool, v_pool) pytrees a model's cache spec
    names (``models.family`` has its shape) — a block plane (layers, blocks,
    block_size, *trailing) bfloat16, a per-slot plane (layers, slots,
    *trailing) of its own dtype; a pool is the dict of its planes by name, or
    its one plane itself. The tree's structure is part of every program's text.
    ``zeros(shape, dtype)`` makes a plane (an engine places its own; a scratch
    pool and a compile check hand theirs)."""
    def pool(side: str):
        planes = {n: zeros((p[0], blocks, block_size, *p[1:]), jnp.bfloat16)
                  for n, p in spec["planes"][side].items()}
        planes.update({n: zeros((p[0], slots, *p[1:]), dtype)
                       for n, (p, dtype) in spec["slot_planes"][side].items()})
        return planes if spec["by_name"] else planes["kv"]

    return pool("k"), pool("v")


@lru_cache(maxsize=8)
def _sharded_zeros(shape, dtype, sharding):
    """ONE program a (shape, dtype, sharding): the k and the v plane of a pool
    under a mesh are one trace, lowered once."""
    # analyze: ok[jit-sentinel] -- one-shot cache-init compile at construction time, not a serving dispatch the fence could catch
    return jax.jit(partial(jnp.zeros, shape, dtype), out_shardings=sharding)


@watch_compiles("paged._scatter_blocks")
@partial(jax.jit, donate_argnames=("k_pool", "v_pool"))
def _scatter_blocks(k_pool, v_pool, src_k, src_v, dst_idx):
    """Write (L, n, nkv, hd) rows into the flat pool at dst_idx (n,)."""
    if isinstance(src_k, dict):
        # a latent cache with planes by layer KIND (models.dots3): every plane
        # the source names, each of its own layers and width, at the same
        # (block, offset) — they ride one table, and a pool holds the planes
        # its model's ``cache_spec`` names and no other
        bs = jax.tree.leaves(k_pool)[0].shape[2]
        at = (slice(None), dst_idx // bs, dst_idx % bs)
        put = lambda pool, src: {**pool, **{n: pool[n].at[at].set(v) for n, v in src.items()}}
        return put(k_pool, src_k), put(v_pool, src_v)
    kp, vp = kv_planes(k_pool), kv_planes(v_pool)
    L, N, bs = kp.shape[0], kp.shape[1], kp.shape[2]
    shp = kp.shape
    if isinstance(k_pool, dict):
        # a hybrid model's planes are written as they are shaped, (block,
        # offset): XLA relays their flat view out around a scatter (see
        # models.sambay.forward_paged), 13 ms a call (my chip run, PR 32)
        at = (slice(None), dst_idx // bs, dst_idx % bs)
        return ({**k_pool, "kv": kp.at[at].set(src_k)}, {**v_pool, "kv": vp.at[at].set(src_v)})
    if kp.ndim == 4:
        # a latent cache's two planes, (L, N, bs, width) each of its own
        # width (models.mla): written as they are shaped, likewise
        at = (slice(None), dst_idx // bs, dst_idx % bs)
        return kp.at[at].set(src_k), vp.at[at].set(src_v)
    kf = kp.reshape(L, N * bs, *shp[3:])
    vf = vp.reshape(L, N * bs, *shp[3:])
    kf = kf.at[:, dst_idx].set(src_k)
    vf = vf.at[:, dst_idx].set(src_v)
    return kf.reshape(shp), vf.reshape(shp)


@watch_compiles("paged._restore_state")
@partial(jax.jit, donate_argnames=("k_pool", "v_pool"))
def _restore_state(k_pool, v_pool, k_slot, v_slot, slot):
    """A slot's planes <- a snapshot of them, {plane: (n_layers, ...)} a pool,
    whatever the family's record names them (``cache_spec``'s ``slot_planes``:
    a convolution tail in ``k_pool`` and a float32 state in ``v_pool`` for the
    three families that keep one)."""
    put = lambda pool, snap: {**pool, **{n: pool[n].at[:, slot].set(a) for n, a in snap.items()}}
    return put(k_pool, k_slot), put(v_pool, v_slot)


def _by_pool(k_pool, v_pool, snapshot: dict) -> tuple[dict, dict]:
    """A snapshot {plane: array} as ``_restore_state`` takes it: the planes the
    k pool holds and those the v pool holds."""
    return tuple({n: a for n, a in snapshot.items() if n in pool} for pool in (k_pool, v_pool))


@watch_compiles("paged._set_table_rows")
@partial(jax.jit, donate_argnames=("tables",))
def _set_table_rows(tables, slots, rows):
    """Several slots' table rows in one launch (a group's admissions; the
    slots a chunk's claim grew): ``rows`` (n, width) land at ``slots`` (n,);
    an entry of ``slots`` past the table (a row not used) is dropped."""
    return tables.at[slots].set(rows, mode="drop")


@watch_compiles("paged.forward_paged_first_tokens")
@partial(jax.jit,
         static_argnames=("cfg", "rules", "attn_impl", "gather_blocks", "pick", "pick_kw"),
         donate_argnames=("k_pool", "v_pool", "tables"))
def forward_paged_first_tokens(params, cfg, tokens, positions, k_pool, v_pool, tables, rows,
                               slots, ns, live, last, n_real, tail, dst, snapshot, restore_at,
                               state, pick_args, *, rules, attn_impl: str, gather_blocks: int,
                               pick, pick_kw: tuple = ()):
    """A GROUP's admission as ONE device program (ISSUE 35): the members'
    table rows into ``tables`` (an entry of ``slots`` past the table — a row
    the group does not fill — is dropped), the prefix's sub-block ``tail``
    into each member's first own block (``dst`` (A * R,) flat pool indices;
    a row not filled writes the trash block), a hybrid model's ``snapshot``
    into the members' state (``restore_at``: a row not filled names member
    0's slot again and writes the same bytes), ``forward_paged`` over the
    (A, bucket) suffixes with a write mask, a head position and, for that
    model, a count of real positions a row, and the caller's ``pick`` on the
    (A, 1, V) logits — the batcher's first tokens into its slot state.
    One program and not five: a start-up then loads ONE more executable
    (``setup_s`` may not grow a tenth), and a group is one launch.
    -> (``pick``'s result, k_pool, v_pool, tables)."""
    A = tokens.shape[0]
    tables = tables.at[slots].set(rows, mode="drop")
    if tail is not None:
        tile = lambda x: jnp.tile(x, (1, A) + (1,) * (x.ndim - 2))
        k_pool, v_pool = _scatter_blocks(k_pool, v_pool, tile(tail["k"]), tile(tail["v"]), dst)
    if snapshot is not None:
        rep = lambda x: jnp.repeat(x[:, None], A, axis=1)
        k_pool, v_pool = _restore_state(
            k_pool, v_pool, *_by_pool(k_pool, v_pool, jax.tree.map(rep, snapshot)), restore_at)
    logits, k_pool, v_pool, _, _ = forward_paged(
        params, cfg, tokens, positions, k_pool, v_pool, rows, rules=rules,
        attn_impl=attn_impl, write_mask=live, logit_pos=last, n_real=n_real,
        fresh_block=False, gather_blocks=gather_blocks)
    return pick(logits, state, slots, ns, *pick_args, **dict(pick_kw)), k_pool, v_pool, tables


@watch_compiles("paged._scatter_blocks_quant")
@partial(jax.jit, static_argnames=("kv_quant",),
         donate_argnames=("k_pool", "v_pool", "k_scale", "v_scale"))
def _scatter_blocks_quant(k_pool, v_pool, k_scale, v_scale, src_k, src_v,
                          dst_idx, kv_quant: str = "int8"):
    """_scatter_blocks' KV_QUANT twin: quantize the fp (L, n, nkv, hd)
    rows on write (ops.kvquant — the same deterministic rowwise math the
    in-forward scatter uses, so prefix-installed and decode-written KV
    stay bitwise comparable) and land values + scales at dst_idx."""
    from ..ops.kvquant import quantize_kv

    L, N, bs = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    shp, sshp = k_pool.shape, k_scale.shape
    qk, sk = quantize_kv(src_k, kv_quant)
    qv, sv = quantize_kv(src_v, kv_quant)
    kf = k_pool.reshape(L, N * bs, *shp[3:]).at[:, dst_idx].set(qk)
    vf = v_pool.reshape(L, N * bs, *shp[3:]).at[:, dst_idx].set(qv)
    ksf = k_scale.reshape(L, N * bs, sshp[3]).at[:, dst_idx].set(sk)
    vsf = v_scale.reshape(L, N * bs, sshp[3]).at[:, dst_idx].set(sv)
    return (kf.reshape(shp), vf.reshape(shp),
            ksf.reshape(sshp), vsf.reshape(sshp))


@watch_compiles("paged._scatter_scale_planes")
@partial(jax.jit, donate_argnames=("k_scale", "v_scale"))
def _scatter_scale_planes(k_scale, v_scale, src_k, src_v, dst_idx):
    """Write (L, n) bf16 scale rows into the flat (L, N*bs, nkv) planes at
    dst_idx — the scale half of a warm-handoff adoption, where the shipped
    bytes are already quantized and must land verbatim (the quantizing
    scatter would re-derive scales from values that are no longer fp)."""
    L, N, bs, nkv = k_scale.shape
    sshp = k_scale.shape
    kf = k_scale.reshape(L, N * bs, nkv).at[:, dst_idx].set(src_k)
    vf = v_scale.reshape(L, N * bs, nkv).at[:, dst_idx].set(src_v)
    return kf.reshape(sshp), vf.reshape(sshp)


@watch_compiles("paged.paged_chunk_decode_loop")
@partial(
    jax.jit,
    static_argnames=("cfg", "rules", "chunk_steps", "greedy", "constrained",
                     "kernels", "eos_id", "pad_id", "max_len", "kv_quant",
                     "quality_lanes", "ffn_pack"),
    donate_argnames=("k_pool", "v_pool", "k_scale", "v_scale"),
)
def paged_chunk_decode_loop(
    params,
    cfg,
    k_pool,
    v_pool,
    block_tables,  # (B, max_blocks) int32
    cur, pos, fsm_state, active, nbytes, tokens_left,  # (B,) device state
    tables,  # grammar DeviceFSM
    byte_len_table,
    key,
    temperature,
    byte_budget,
    trash_idx=None,  # (B,) int32 per-row parked-write index (dp-local trash)
    rules=None,
    logit_mask=None,
    nan_inject=None,  # (B,) bool or None — chaos drill (see engine.py twin)
    k_scale=None,  # (L, N, bs, nkv) bf16 KV_QUANT scale planes (None = off:
    # empty pytree leaves, the traced loop is byte-identical to pre-quant)
    v_scale=None,
    rows_idx=None,  # (R,) int32 distinct slots, or None = every slot (an empty
    # pytree leaf too: the full-width program is the one it was without it)
    chunk_steps: int = 32,
    greedy: bool = True,
    constrained: bool = True,
    kernels: str = "pallas",
    eos_id: int = 2,
    pad_id: int = 0,
    max_len: int | None = None,
    kv_quant: str | None = None,
    quality_lanes: bool = False,  # ISSUE 15 conf lanes (see the dense twin)
    ffn_pack: int = 0,  # P: the position-wise work of a fast-forward block —
    # q/k/v, the output projection, the MLPs — runs on its real positions
    # packed into P rows (``PagedDecodeEngine.ffn_pack_rows``; 0, or a block
    # of no more than P positions: the program is the one it was)
):
    """chunk_decode_loop's paged twin: forward_paged per step, idle rows'
    writes parked in their group's reserved trash block via write_mask (they
    must never scribble on another slot's — or the shared prefix's —
    blocks). Returns the dense loop's tuple shape including the per-row
    ``poison`` fault codes (0 ok / 1 non-finite logits / 2 dead FSM); a
    poisoned row deactivates without committing the faulty sample, so
    batch-mates decode token-identically to an undisturbed run. Behind them
    one more carry and output for each thing the model's record says its
    forwards count (``models.family.Family.counts``, in that order; static),
    each summed over the chunk's forwards — and LAST, from a program whose
    position-wise regions may run PACKED (ISSUES 37, 41: ``ffn_pack`` under a
    fast-forward block wider than it, off a mesh), ``family.FFN``'s.

    The COMPACTED width (ISSUE 29): with ``rows_idx`` the same loop runs over
    those R slots' rows alone — their state and block-table rows gathered on
    entry, scattered back on exit into the (B,) arrays it was handed — and
    every slot that did not ride gets what the full width gives an idle row
    (pad, 0 emitted, ``eos0``, no poison, ``_conf_init``), so the caller sees
    the full width's shapes. The pool is shared and addressed through the
    tables, so nothing of it is gathered. The engine picks the width from
    the batcher's live count (``PagedDecodeEngine.decode_chunk``)."""
    if rows_idx is not None:
        with jax.named_scope("rows_gather"):
            full = (cur, pos, fsm_state, active, nbytes, tokens_left)
            eos_full = (~active) & (cur == eos_id)
            cur, pos, fsm_state, active, nbytes, tokens_left = (
                x[rows_idx] for x in full)
            block_tables = block_tables[rows_idx]
            if trash_idx is not None:
                trash_idx = trash_idx[rows_idx]
            if nan_inject is not None:
                nan_inject = nan_inject[rows_idx]
    B = cur.shape[0]
    # the variant this kind of model compiles is its record's (``models.family``;
    # static, from the configuration): whether a table row's last column is its
    # slot's state index, not a block; whether every forward is told how many of
    # a row's positions are real; whether the head runs on the one position a
    # row reads; and what the forwards count, each one more carry and output
    fam = family(cfg)
    told = fam.n_real == "always"
    # the engine's max_len, NOT the block-rounded table capacity — with a
    # non-multiple max_len the dense loop stops at max_len-1 and the paged
    # loop must match it token for token
    max_pos = ((block_tables.shape[1] - fam.cache["state_column"])
               * kv_planes(k_pool).shape[2])
    if max_len is not None:
        max_pos = min(max_pos, max_len)
    use_ff = constrained and tables.ff_tokens is not None
    W = tables.ff_tokens.shape[1] if use_ff else 0
    cap = chunk_steps * (1 + W)
    # ff emission scatters through a trash column (index `cap`), exactly
    # like the dense loop
    out = jnp.full((B, cap + 1 if use_ff else chunk_steps), pad_id,
                   dtype=jnp.int32)
    eos0 = (~active) & (cur == eos_id)

    # a fast-forward block holds 1 + k real positions a live row and copies
    # of the last one behind them: the forward is told, and everything
    # position-wise in its layers computes the real ones packed into
    # ``ffn_pack`` rows while they fit
    packs = bool(use_ff and ffn_pack and rules is None and B * (1 + W) > ffn_pack)
    # what the forwards count and the carry sums, in the record's order (the
    # carry order is program text): a dense model's program has no carry of
    # expert rows (tests/test_olmoe.py); a packing program one more, last
    count_kw = {c.keyword: True for c in fam.counts}
    counts0 = tuple(jnp.zeros((len(c.metrics),), jnp.int32)
                    for c in fam.counts + (FFN,) * packs)

    carry0 = (k_pool, v_pool, k_scale, v_scale, cur, pos, fsm_state, active,
              eos0, nbytes,
              tokens_left, out, jnp.zeros((B,), jnp.int32), key,
              jnp.zeros((), jnp.int32), jnp.zeros((B,), jnp.int32),
              _conf_init(B), *counts0)

    def cond(c):
        active, step = c[7], c[14]
        return jnp.logical_and(step < chunk_steps, jnp.any(active))

    def body(c):
        (kp, vp, ksc, vsc, cur, pos, state, active, eos, nbytes, left, out, n,
         key, step, poison, conf, *counts) = c
        with jax.named_scope("loop_carry"):
            out = out.at[jnp.arange(B), jnp.minimum(n, chunk_steps - 1)].set(
                jnp.where(active, cur, out[jnp.arange(B), jnp.minimum(n, chunk_steps - 1)])
            )
            n = n + active.astype(jnp.int32)
            nbytes = nbytes + jnp.where(active, byte_len_table[cur], 0)
            left = left - active.astype(jnp.int32)

            step_tok = jnp.where(active, cur, pad_id)
            write_pos = jnp.where(active, pos, 0)
        logits, kp, vp, ksc, vsc, *stats = forward_paged(
            params, cfg, step_tok[:, None], write_pos[:, None], kp, vp,
            block_tables, rules=rules, attn_impl=kernels, write_mask=active,
            trash_idx=trash_idx, k_scale=ksc, v_scale=vsc, kv_quant=kv_quant,
            **count_kw, **({"n_real": active.astype(jnp.int32)} if told else {}),
        )
        raw = logits[:, 0, :]
        if nan_inject is not None:
            raw = jnp.where(nan_inject[:, None] & active[:, None],
                            jnp.float32(jnp.nan), raw)
        key, k = jax.random.split(key)
        nxt, state_next = _mask_sample_advance(
            raw, state, tables, k, temperature, greedy,
            constrained, kernels, rules, logit_mask
        )
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
            stop = (cur == eos_id) | (nbytes >= byte_budget) | (pos >= max_pos - 1) | (left <= 0)
            active = ok & ~stop
        return (kp, vp, ksc, vsc, cur, pos, state, active, eos, nbytes, left,
                out, n, key, step + 1, poison, conf,
                *(c + s for c, s in zip(counts, stats)))

    def ff_body(c):
        # the dense ff_body's paged twin: cur + its state's forced chain in
        # one (B, 1+W) forward_paged. Writes land through the block tables
        # (parked wholesale at the trash block for idle rows via
        # write_mask); attention runs the paged frontier-read block kernel
        # under kernels="pallas". Chain caps mirror the dense loop with
        # max_pos (table-covered capacity ∧ engine max_len) as the bound —
        # the engine's decode_chunk grew every live row's table to cover a
        # full ff chunk before dispatch.
        (kp, vp, ksc, vsc, cur, pos, state, active, eos, nbytes, left, out, n,
         key, step, poison, conf, *counts) = c
        with jax.named_scope("loop_carry"):
            # dead-at-entry fence (see the dense ff_body): a negative state
            # wraps the ff_tokens gather — poison it out before it emits
            dead_in = active & (state < 0)
            active = active & ~dead_in
            poison = jnp.maximum(poison, jnp.where(dead_in, 2, 0))
            iw = jnp.arange(1 + W)[None, :]
            chain = tables.ff_tokens[state]  # (B, W); -1 pads
            k = jnp.minimum(jnp.minimum(tables.ff_len[state], left - 1),
                            max_pos - 1 - pos)
            chain_bytes = jnp.cumsum(
                jnp.where(chain >= 0, byte_len_table[jnp.maximum(chain, 0)], 0), axis=1)
            rem = (byte_budget - nbytes - byte_len_table[cur])[:, None]
            k = jnp.minimum(k, jnp.sum(chain_bytes <= rem, axis=1))
            k = jnp.where(active, jnp.maximum(k, 0), 0)

            ci = jnp.clip(iw - 1, 0, jnp.maximum(k[:, None] - 1, 0))
            chain_tok = jnp.take_along_axis(chain, ci, axis=1)
            step_tok = jnp.where(active, cur, pad_id)
            blk_tok = jnp.where(iw == 0, step_tok[:, None],
                                jnp.where(k[:, None] > 0, chain_tok, step_tok[:, None]))
            # idle rows park at position 0 (writes are parked via write_mask
            # anyway): keeps their attention frontier at ONE tile instead of
            # streaming a finished row's whole covered context every layer
            write_pos = jnp.where(active, pos, 0)
            blk_pos = write_pos[:, None] + jnp.minimum(iw, k[:, None])

            valid = (iw <= k[:, None]) & active[:, None]
            tgt = jnp.where(valid, jnp.minimum(n[:, None] + iw, cap - 1), cap)
            out = out.at[jnp.arange(B)[:, None], tgt].set(
                jnp.where(valid, blk_tok, pad_id))
            emitted = jnp.where(active, 1 + k, 0)
            n = n + emitted
            chain_valid = (iw >= 1) & (iw <= k[:, None]) & active[:, None]
            nbytes = (nbytes + jnp.where(active, byte_len_table[cur], 0)
                      + jnp.sum(jnp.where(chain_valid,
                                          byte_len_table[jnp.maximum(chain_tok, 0)], 0),
                                axis=1))
            left = left - emitted

        with jax.named_scope("fsm_advance"):
            def cstep(s, xs):
                t, i = xs
                s2 = fsm_advance(tables, s, jnp.maximum(t, 0))
                return jnp.where(i < k, s2, s), None

            s_end, _ = jax.lax.scan(cstep, state, (chain.T, jnp.arange(W)))

        logits, kp, vp, ksc, vsc, *stats = forward_paged(
            params, cfg, blk_tok, blk_pos, kp, vp,
            block_tables, rules=rules, attn_impl=kernels, write_mask=active,
            trash_idx=trash_idx, k_scale=ksc, v_scale=vsc, kv_quant=kv_quant,
            **count_kw, **({"n_real": emitted} if told or packs or fam.block_real else {}),
            **({"ffn_pack": ffn_pack} if packs else {}),
            **({"logit_pos": k} if fam.one_head else {}),
        )
        logits_k = (logits[:, 0, :] if fam.one_head else
                    jnp.take_along_axis(logits, k[:, None, None], axis=1)[:, 0, :])
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
            mg, en, f1 = _conf_stats(logits_k, s_end, tables, constrained,
                                     logit_mask)
            conf = _conf_accumulate(conf, ok, mg, en, f1,
                                    forced_extra=jnp.where(active, k, 0))
        with jax.named_scope("loop_carry"):
            state = jnp.where(ok, state_next, state)
            cur = jnp.where(ok, nxt, cur)
            pos = jnp.where(ok, pos + 1 + k, pos)

            eos = eos | (ok & (cur == eos_id))
            stop = (cur == eos_id) | (nbytes >= byte_budget) | (pos >= max_pos - 1) | (left <= 0)
            active = ok & ~stop
        return (kp, vp, ksc, vsc, cur, pos, state, active, eos, nbytes, left,
                out, n, key, step + 1, poison, conf,
                *(c + s for c, s in zip(counts, stats)))

    (k_pool, v_pool, k_scale, v_scale, cur, pos, state, active, eos, nbytes,
     left, out, n, _, fwds, poison, conf, *counts) = (
        jax.lax.while_loop(cond, ff_body if use_ff else body, carry0)
    )
    out = out[:, : cap if use_ff else chunk_steps]
    if rows_idx is not None:
        with jax.named_scope("rows_scatter"):
            Bf = eos_full.shape[0]

            def put(base, rows):
                return base.at[rows_idx].set(rows)

            cur, pos, state, active, nbytes, left = (
                put(f, x) for f, x in
                zip(full, (cur, pos, state, active, nbytes, left)))
            out = put(jnp.full((Bf, out.shape[1]), pad_id, jnp.int32), out)
            n, poison = (put(jnp.zeros((Bf,), jnp.int32), x)
                         for x in (n, poison))
            eos = put(eos_full, eos)
            conf = tuple(put(c0, c) for c0, c in zip(_conf_init(Bf), conf))
    return (out, n, eos, k_pool, v_pool,
            k_scale, v_scale, cur, pos, state, active, nbytes, left, fwds,
            poison, conf, *counts)


class PagedDecodeEngine(DecodeEngine):
    """DecodeEngine with a paged KV pool instead of dense per-slot lines.

    Served through the continuous batcher (serve.scheduler), which drives
    the engine only via prefill_slot / decode_chunk / release_slot — the
    KV layout never leaks out. ``pool_blocks`` sizes HBM to the expected
    LIVE token count: pool bytes = pool_blocks * block_size * per-token KV,
    vs the dense engine's batch_slots * max_len.

    On a mesh: pool blocks shard over dp (each dp group allocates from its
    own contiguous range, so a slot's whole context is local to its dp
    shard), kv heads over tp. batch_slots must divide by dp (the parent
    engine enforces this) and so must pool_blocks.
    """

    _alloc_dense_cache = False  # startup must never peak at the dense
    # worst-case footprint this engine exists to avoid

    def __init__(self, *args, block_size: int = 128, pool_blocks: int | None = None,
                 radix_enable: bool | None = None,
                 radix_max_nodes: int | None = None,
                 kv_quant: str | None = None, **kw):
        super().__init__(*args, **kw)
        bs = block_size
        self.block_size = bs
        self.max_blocks = -(-self.max_len // bs)
        self.dp = self.mesh.shape.get("dp", 1) if self.mesh is not None else 1
        # the chunk program's compacted width (ISSUE 29): a chunk with at
        # most this many live rows computes this many, not batch_slots. One
        # width, derived here: each one is an executable every start-up
        # compiles. 0 = never: slots of different dp groups may not share a
        # program's batch axis
        R = max(1, self.batch_slots // 4)
        self.compact_rows = R if self.dp == 1 and R < self.batch_slots else 0
        # the rows a fast-forward block's projections and MLPs compute (ISSUES
        # 37, 41): a block of batch_slots x (1 + W) positions holds ~1.4 real
        # ones a row, the rest are copies. A dispatch whose block is no wider runs
        # the program it always ran, and so does a mesh (rows of different dp
        # groups may not share a packed axis)
        fam = self.family
        self.ffn_pack_rows = fam.pack_rows if self.dp == 1 else 0
        # quantized KV storage tier (ISSUE 12): KV_QUANT=int8|int4 stores
        # per-(position, head) scaled values (ops.kvquant) — half/quarter
        # the HBM bytes per block, so a fixed pool budget holds ~2x/~4x the
        # blocks. Unset keeps the bf16 pool byte-identical, differentially
        # tested like RADIX_ENABLE before it.
        if kv_quant is None:
            kv_quant = os.environ.get("KV_QUANT") or None
        if kv_quant in ("", "off"):
            kv_quant = None
        if kv_quant not in (None, "int8", "int4"):
            raise ValueError(f"KV_QUANT must be int8 or int4, got {kv_quant!r}")
        self.kv_quant = kv_quant
        if radix_enable is None:
            radix_enable = os.environ.get("RADIX_ENABLE") == "1"
        # what the model's kind refuses of a paged engine (a mesh: the
        # parent's constructor, from the same table)
        if kv_quant:
            fam.refuse("kv_quant")
        if radix_enable:
            fam.refuse("radix")
        # THE cache spec, from the model's record: the planes of the k and v
        # pools by name (a dense or routed decoder: every layer's K/V at its kv
        # heads; models.sambay: the h/2 + 1 that write K/V, at its packed heads;
        # a latent cache: a latent and ONE rotated key, two widths; models.dots3:
        # planes by layer KIND) and what a SLOT holds beside its blocks (sambay:
        # a convolution tail and a float32 state for each recurrent layer)
        self._cache_spec = spec = fam.cache
        self._state_col = int(spec["state_column"])  # a table row's last column: its slot
        # facts of the record, for whoever asks from outside (tests, tools/*_check.py)
        self.hybrid = fam.name == "hybrid"
        self.sparse = fam.name == "sparse"
        self.latent = self.sparse or fam.name == "latent"
        if pool_blocks is None:
            # default: same worst case as dense, plus each group's trash block
            pool_blocks = self.batch_slots * self.max_blocks + self.dp
        if pool_blocks % self.dp:
            raise ValueError(
                f"pool_blocks ({pool_blocks}) must divide into the mesh dp "
                f"axis ({self.dp}): each dp group owns its own block range")
        self.k_pool, self.v_pool = build_pools(spec, pool_blocks, bs, self.batch_slots,
                                               self._placed_zeros)
        self.k_scale = self.v_scale = None
        if kv_quant is not None:  # scales by (position, head), beside K/V planes by head
            L, nkv, _ = spec["planes"]["k"]["kv"]
            self.k_scale, self.v_scale = (
                self._placed_zeros((L, pool_blocks, bs, nkv), jnp.bfloat16, scale=True)
                for _ in "kv")
        self._prefix_state: dict | None = None  # a slot's own planes after the cached prefix
        self.allocator = BlockAllocator(pool_blocks, n_groups=self.dp)
        self.block_tables = self._fresh_tables()
        self._slot_shared: list[list[int]] = [[] for _ in range(self.batch_slots)]
        self._slot_owned: list[list[int]] = [[] for _ in range(self.batch_slots)]
        self._covered: list[int] = [0] * self.batch_slots  # positions with blocks
        self._next_pos: list[int] = [0] * self.batch_slots  # upper bound
        # parked writes go to the slot's OWN group's trash block so they
        # never cross dp shards (flat index = first block of the group)
        self._trash_idx = jnp.asarray(
            [self._group(b) * self.allocator.blocks_per_group * bs
             for b in range(self.batch_slots)], jnp.int32)
        # per-group shared-prefix blocks (the prefix KV must live inside
        # every dp shard that has slots attending to it)
        self._prefix_blocks: list[list[int]] = [[] for _ in range(self.dp)]
        self._prefix_tail: dict | None = None  # (L, R, nkv, hd) sub-block rest
        # radix KV reuse (serve.radix): one tree per dp group, gated by
        # RADIX_ENABLE — unset keeps the pre-radix paged path byte-identical
        # (admission never consults a tree, release never inserts)
        if radix_max_nodes is None:
            radix_max_nodes = int(os.environ.get("RADIX_MAX_NODES", "4096"))
        self.radix: list[RadixCache] | None = (
            [RadixCache(self.allocator, bs, group=g, max_nodes=radix_max_nodes)
             for g in range(self.dp)] if radix_enable else None)
        # pool-pressure gate on session-cache admission (degradation stage
        # 2): while a recent allocation actually hit PoolExhausted (genuine
        # thrash — eviction had to run or the request shed), released
        # chains are NOT adopted into the tree for RADIX_PRESSURE_S, so the
        # cache stops pinning blocks live admissions immediately need.
        # Trigger on measured thrash, not a static watermark: a full-but-
        # quiet pool is the radix cache working as intended.
        self._pressure_window_s = float(os.environ.get("RADIX_PRESSURE_S", "2.0"))
        self._pressure_until = 0.0
        # host token ids of the request occupying each slot (radix insert
        # at release needs prompt + generated ids; None when radix is off)
        self._slot_ids: list[list[int] | None] = [None] * self.batch_slots
        # tenant radix namespace per slot (ISSUE 18): the scheduler sets it
        # before admission; match/insert salt their keys with it. Empty
        # (tenancy off) keeps every radix path byte-identical.
        self._slot_ns: dict[int, str] = {}
        # slots mid-way through a chunked prefill (ISSUE 19): their owned
        # blocks exist but the slot is NOT decoding — decode_chunk's
        # worst-case growth claim and reconcile_coverage must both skip
        # them (growth would bleed the pool for a row that cannot decode
        # yet; reconcile would clamp _next_pos against the row's parked
        # device position)
        self._mid_prefill: set[int] = set()

    def _placed_zeros(self, shape, dtype, scale: bool = False):
        """A plane of zeros where this engine keeps it: under KV_QUANT a block
        plane at its stored width and dtype (``scale``: the scales beside it),
        under a mesh sharded — both the family's whose planes are K/V by head,
        every other refuses them."""
        from ..ops.kvquant import kv_store_dim, kv_store_dtype

        if self.kv_quant and not scale:
            shape = (*shape[:-1], kv_store_dim(shape[-1], self.kv_quant))
            dtype = kv_store_dtype(self.kv_quant)
        if self.mesh is None:
            return jnp.zeros(shape, dtype)
        from ..parallel.mesh import paged_pool_shardings, paged_scale_shardings

        sh = (paged_scale_shardings if scale else paged_pool_shardings)(self.mesh, shape[3])
        return _sharded_zeros(shape, dtype, sh)()

    def _fresh_tables(self):
        """(batch_slots, max_blocks) zeros; where the record says so, rows
        carry their slot's state index in one more column, which no attention
        walk reaches (``models.sambay.forward_paged`` splits it off)."""
        tables = np.zeros((self.batch_slots, self.max_blocks + self._state_col), np.int32)
        if self._state_col:
            tables[:, -1] = np.arange(self.batch_slots)
        return jnp.asarray(tables)

    def _group(self, slot: int) -> int:
        """dp group of a batch slot (slots shard over dp like the dense
        cache's batch axis: contiguous runs of batch_slots/dp)."""
        return slot // (self.batch_slots // self.dp)

    @property
    def kv_quant_bits(self) -> int:
        """Stored bits per KV element (16 bf16 / 8 / 4) — exported as the
        ``paged.kv_quant_bits`` gauge."""
        from ..ops.kvquant import kv_quant_bits

        return kv_quant_bits(self.kv_quant)

    @property
    def kv_bytes_per_block(self) -> int:
        """HBM bytes one pool block occupies under the active KV tier
        (values + scale planes; ops.kvquant.kv_block_bytes is the single
        source the HBM ledger plan and the bench capacity rows share)."""
        if self.kv_quant is None:  # every block plane of both pools, bf16
            return self.block_size * self.family.token_bytes
        from ..ops.kvquant import kv_block_bytes

        L, nkv, hd = self._cache_spec["planes"]["k"]["kv"]  # K/V by head: what KV_QUANT re-stores
        return kv_block_bytes(L, self.block_size, nkv, hd, self.kv_quant)

    def _scatter_pool(self, src_k, src_v, dst_idx) -> None:
        """Pool scatter dispatch: plain bf16 write, or quantize-on-write
        with the scales landing at the same flat indices (the ONE seam the
        prefix install and the sub-block chain-tail scatter go through)."""
        if self.kv_quant is None:
            self.k_pool, self.v_pool = _scatter_blocks(
                self.k_pool, self.v_pool, src_k, src_v, dst_idx)
        else:
            (self.k_pool, self.v_pool, self.k_scale, self.v_scale) = (
                _scatter_blocks_quant(
                    self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                    src_k, src_v, dst_idx, kv_quant=self.kv_quant))

    # ------------------------------------------------------------ prefix

    def _compute_prefix_kv(self, tokens, positions, P: int, bucket: int) -> dict:
        """The prefix of a model ``forward_paged`` alone runs (its record's
        ``scratch_prefix``): prefilled through it into a scratch pool of the
        bucket's blocks — and, where a slot holds planes of its own, ONE
        scratch slot, whose state after the P real positions is the snapshot
        every admission behind the prefix starts from (``_prefix_state``). Its
        K/V comes back in the dense layout ``set_prompt_prefix`` scatters from."""
        fam, bs = self.family, self.block_size
        if not fam.scratch_prefix:
            return super()._compute_prefix_kv(tokens, positions, P, bucket)
        nb = -(-bucket // bs)
        slot = self._cache_spec["slot_planes"]
        # the scratch planes: the pool's own, at the bucket's blocks and one
        # (block 0: parked writes), a slot's at ONE slot
        plane = lambda a, n=nb + 1: jnp.zeros((a.shape[0], n, *a.shape[2:]), a.dtype)
        scratch = lambda pool, side: (
            {n: plane(a, 1) if n in slot[side] else plane(a) for n, a in pool.items()}
            if isinstance(pool, dict) else plane(pool))
        k, v = scratch(self.k_pool, "k"), scratch(self.v_pool, "v")
        # the table's last column, where it has one: the scratch slot
        table = jnp.asarray([list(range(1, nb + 1)) + [0] * self._state_col], jnp.int32)
        kw = {"n_real": jnp.asarray([P], jnp.int32)} if fam.n_real == "always" else {}
        # a prefix longer than the largest bucket (``_prefix_in_chunks``), in
        # chunks of it through the ONE scratch pool: each chunk attends the
        # earlier ones through the model's own attention path — a layer's own
        # mask, window or none —, one program for all of them
        whole = self._prefix_in_chunks(P)
        chunk = min(bucket, self.prefill_buckets[-1]) if whole else bucket
        if whole and fam.kv_by_head:
            # K and V by head behind XLA's masks: a chunk's scores are heads x
            # chunk x keys float32 a layer, BESIDE the resident model (0.94 GB at 28
            # heads, 1024 positions, 8192 keys: a peak of 14.5 of 16 GB, my chip
            # runs, PR 50). The chunk halves until they fit ``PREFIX_SCORE_BYTES``
            while chunk > bs and self.cfg.n_heads * chunk * bucket * 4 > PREFIX_SCORE_BYTES:
                chunk //= 2
        if whole and fam.one_head and not fam.n_real:
            # nobody reads a chunk's logits: the head on ONE position, not on every
            # one of the chunk's (0.62 GB of float32 at a 151936-row vocabulary)
            kw["logit_pos"] = jnp.zeros((1,), jnp.int32)
        for at in range(0, bucket, chunk):
            part = (tokens, positions) if chunk == bucket else (
                tokens[:, at:at + chunk], positions[:, at:at + chunk])
            _, k, v, _, _ = forward_paged(self.params, self.cfg, *part, k, v, table,
                                          attn_impl=self.kernels, fresh_block=not whole, **kw)
        dense = lambda a: a[:, 1:].reshape(a.shape[0], 1, nb * bs, *a.shape[3:])[:, :, :P]
        if whole:
            # the chunks' workspace goes before the copies below are made: the host
            # runs ahead of the device, and a buffer whose free is still pending
            # counts beside every allocation made meanwhile
            jax.block_until_ready((k, v))
        if slot["k"] or slot["v"]:  # the slot's planes: the snapshot; the blocks: the K/V planes
            self._prefix_state = {n: pool[n][:, 0] for pool, side in ((k, "k"), (v, "v"))
                                  for n in slot[side]}
            k, v = k["kv"], v["kv"]
        # ONE pool at a time: its dense copy made and its scratch blocks gone before
        # the other's is — beside a resident 12.2 GB a scratch pool of 192 planes is
        # 0.9 GB a side and its copies as much again (16.43 of ~16.9 GB with both
        # alive at once: my chip run, PR 57)
        scratch = {"k": k, "v": v}
        del k, v
        return {side: jax.block_until_ready(jax.tree.map(dense, scratch.pop(side)))
                for side in ("k", "v")}

    def _restore_slot_state(self, slot: int, snapshot: dict | None) -> None:
        """Before the admission chain of a model whose slots hold planes of
        their own runs: the slot's state <- the prefix's snapshot (None: zeros,
        a prompt from position 0). One device copy; ``release_slot`` needs nothing."""
        from ..utils import get_metrics

        with span(STATE_RESTORE_SPAN):
            if snapshot is None:
                slot_planes = self._cache_spec["slot_planes"]
                snapshot = {n: jnp.zeros_like(pool[n][:, 0])
                            for pool, side in ((self.k_pool, "k"), (self.v_pool, "v"))
                            for n in slot_planes[side]}
            self.k_pool, self.v_pool = _restore_state(
                self.k_pool, self.v_pool, *_by_pool(self.k_pool, self.v_pool, snapshot), jnp.int32(slot))
        get_metrics().inc("ssm.state_restores")

    def _prefix_in_chunks(self, P: int) -> bool:
        """The common prefix is cached in WHOLE blocks and prefilled in chunks of
        the largest bucket: where the model's record says so (planes by layer
        kind: no sub-block tail to scatter plane by plane into every admission's
        first block), and for any model ``forward_paged`` alone runs whose prefix
        PASSES the largest bucket — in one fresh block its scores would be
        heads x P^2 float32 a layer. A prefix that fits a bucket is cached as it
        always was."""
        fam = self.family
        return fam.prefix_whole_blocks or (fam.scratch_prefix and P > self.prefill_buckets[-1])

    def _cached_prefix_len(self, P: int) -> int:
        """Whole blocks of the common prefix where it is cached in chunks: the
        rest of it is prefilled with the suffix."""
        return P // self.block_size * self.block_size if self._prefix_in_chunks(P) else P

    def _prefix_bucket(self, P: int) -> int:
        """Such a prefix may pass the largest bucket: whole chunks of it."""
        top = self.prefill_buckets[-1]
        return -(-P // top) * top if self._prefix_in_chunks(P) and P > top else self._bucket(P)

    def _prefill_kw(self, attn_impl: str, n_real) -> dict:
        """A prefill forward's arguments that follow the model's record: most
        take the layout kernel's attention path; one told its real positions
        (``n_real``) is told how many of the bucket's are (its states advance
        over those alone) and the engine's kernels (its forward picks the
        attention path by T, a scan by this)."""
        if not self.family.n_real:
            return {"rules": self.rules, "attn_impl": attn_impl}
        if isinstance(n_real, int):  # one row; a group hands its (A,) array
            n_real = jnp.asarray([n_real], jnp.int32)
        return {"rules": self.rules, "attn_impl": self.kernels, "n_real": n_real}

    def set_prompt_prefix(self, *sample_prompts: str) -> int:
        self._prefix_state = None
        P = super().set_prompt_prefix(*sample_prompts)
        if self.radix is not None:
            # drop the whole tree BEFORE freeing the old prefix blocks: the
            # tree holds its own ref on everything it adopted (pinned root
            # chain included), and cached chains extending the OLD prefix
            # can never match prompts rendered over the new one
            for rc in self.radix:
                rc.clear()
        for g in range(self.dp):
            if self._prefix_blocks[g]:
                self.allocator.free(self._prefix_blocks[g])
                self._prefix_blocks[g] = []
        self._prefix_tail = None
        if P == 0:
            return 0
        bs = self.block_size
        full = P // bs
        # (L, P, nkv, hd); a model with planes by layer kind: a tree of them
        pk = jax.tree.map(lambda a: a[:, 0], self.prefix_kv["k"])
        pv = jax.tree.map(lambda a: a[:, 0], self.prefix_kv["v"])
        # the dense (L, 1, P, nkv, hd) copy goes NOW, not when the blocks are in
        # the pool: beside a resident 12.5 GB an 8192-token prefix is 0.4 GB a
        # copy, and four of them were alive at the install's peak (my chip runs,
        # PR 50). ``_split_prefix`` only needs a non-None sentinel
        jax.block_until_ready((pk, pv))  # ... and is GONE before the next copy is made
        self.prefix_kv = {}
        head = lambda planes, n: jax.tree.map(lambda a: a[:, :n], planes)
        if full:
            for g in range(self.dp):
                self._prefix_blocks[g] = self.allocator.alloc(full, group=g)
                blocks = np.asarray(self._prefix_blocks[g], np.int32)
                dst = (blocks[:, None] * bs + np.arange(bs)[None, :]).reshape(-1)
                self._scatter_pool(head(pk, full * bs), head(pv, full * bs), jnp.asarray(dst))
        if P % bs:
            self._prefix_tail = {"k": pk[:, full * bs:], "v": pv[:, full * bs:]}
        if full and self.radix is not None:
            # the static prefix becomes the tree's permanently-pinned root
            # chain: session chains extend it, eviction can never take it
            for g in range(self.dp):
                self.radix[g].pin_root_chain(self.prefix_ids[: full * bs],
                                             self._prefix_blocks[g])
        # the prefix KV now lives in the pool (full blocks per dp group) +
        # self._prefix_tail (remainder)
        return P

    # ------------------------------------------------------------ admission

    def _table_row(self, slot: int, blocks) -> np.ndarray:
        row = np.zeros(self.max_blocks, np.int32)
        row[: len(blocks)] = blocks
        # empty table rows must still point INSIDE the slot's dp shard
        # (the sharded kernel localizes ids by subtracting the group base)
        row[len(blocks):] = self._group(slot) * self.allocator.blocks_per_group
        if self._state_col:
            row = np.append(row, np.int32(slot))  # the state index, never a block
        return row

    def _set_table_row(self, slot: int, blocks: list[int]) -> None:
        self.block_tables = self.block_tables.at[slot].set(
            jnp.asarray(self._table_row(slot, blocks)))

    def _alloc(self, k: int, group: int) -> list[int]:
        """allocator.alloc with radix backpressure: when the pool is out,
        evict LRU unreferenced radix leaves and retry once (degradation
        stage 1). Either way the PoolExhausted marks pool pressure, which
        gates session-cache admission (stage 2, ``_radix_may_admit``) for
        the next RADIX_PRESSURE_S. Without a tree (or with nothing
        evictable) PoolExhausted propagates — the scheduler's backpressure/
        shed ladder (stage 3) handles it."""
        try:
            return self.allocator.alloc(k, group=group)
        except PoolExhausted:
            self._pressure_until = time.monotonic() + self._pressure_window_s
            if self.radix is None:
                raise
            need = k - self.allocator.free_blocks(group)
            if self.radix[group].evict(need) < need:
                raise
            return self.allocator.alloc(k, group=group)

    def _prefill_suffix(self, tokens, positions, slot: int, P: int, bucket: int,
                        n: int):
        """Layout kernel (the decision tree lives in DecodeEngine.
        prefill_slot): the static-prefix special case of ``_prefill_chain``
        — the chain is the group's pinned prefix full blocks, the dense
        tail its sub-block remainder KV."""
        bs = self.block_size
        g = self._group(slot)
        shared = self._prefix_blocks[g][: P // bs]
        self.allocator.ref(shared)
        return self._prefill_chain(tokens, positions, slot, list(shared), P,
                                   bucket, n, tail=self._prefix_tail)

    def _prefill_chain(self, tokens, positions, slot: int, chain: list[int],
                       P: int, bucket: int, n: int, tail: dict | None = None):
        """Generalized chain admission (static prefix AND radix hits):
        ``chain`` blocks — already ref'd FOR THIS SLOT — cover positions
        [0, len(chain)*bs) read-only; ``tail`` optionally supplies dense KV
        for [len(chain)*bs, P); the (1, bucket) suffix forward computes
        [P, n). New tokens only ever land in the freshly allocated owned
        blocks (copy-on-write: suffix writes start at P >= len(chain)*bs).
        The host half (``_claim_chain``), then the launches
        (``_run_chain_row``): ``admit_group`` runs the same two, apart."""
        owned = self._claim_chain(slot, chain, P, bucket, n)
        return self._run_chain_row(tokens, positions, slot, list(chain) + owned,
                                   owned[0], P, bucket, n, tail)

    def _claim_chain(self, slot: int, chain: list[int], P: int, bucket: int,
                     n: int) -> list[int]:
        """The HOST half of a chain admission: the slot's own blocks behind
        ``chain`` for [len(chain)*bs, P + bucket), and its books. Launches
        nothing; ``PoolExhausted`` gives the chain's refs back."""
        bs = self.block_size
        full = len(chain)
        n_owned = -(-(P + bucket) // bs) - full
        try:
            owned = self._alloc(n_owned, self._group(slot))
        except PoolExhausted:
            self.allocator.free(chain)  # don't leak the chain refs
            raise
        self._slot_shared[slot], self._slot_owned[slot] = list(chain), owned
        self._covered[slot] = (full + n_owned) * bs
        self._next_pos[slot] = n
        return owned

    def _gather_bucket(self, P: int, bucket: int) -> int:
        """Table entries a mid-sequence prefill gathers a layer: the COVERED
        blocks, bucketed to a power of two so compile count stays
        log-bounded (gathering the whole table width — max_len of context —
        per layer was round-2 verdict weak #6)."""
        need = -(-(P + bucket) // self.block_size)
        gb = 1
        while gb < need:
            gb *= 2
        if self.radix is not None and gb >= 4 and need <= gb * 3 // 4:
            # half-octave refinement: the pow2 overshoot doubles the
            # per-layer gather at the worst point, and the gather is the
            # dominant shared cost of a warm radix admission (the suffix
            # itself is tiny). 3/4 of the next octave keeps the compile
            # count log-bounded (two buckets per octave) while capping
            # overshoot at 33%. Gated on radix: RADIX_ENABLE unset must
            # keep the pre-radix gather shapes (and therefore programs)
            # byte-identical.
            gb = gb * 3 // 4
        return min(gb, self.max_blocks)

    def _run_chain_row(self, tokens, positions, slot: int, blocks: list[int],
                       first_owned: int, P: int, bucket: int, n: int,
                       tail: dict | None = None):
        """The launches of ONE chain admission, behind ``_claim_chain``: the
        slot's table row, the chain's sub-block tail into its first own
        block, a hybrid model's state, the (1, bucket) forward."""
        bs = self.block_size
        self._set_table_row(slot, blocks)
        if tail is not None:
            # sub-block chain remainder goes into the slot's first
            # owned block (shared blocks stay read-only)
            R = tail["k"].shape[1]  # P less the chain's whole blocks
            dst = jnp.asarray(first_owned * bs + np.arange(R, dtype=np.int32))
            self._scatter_pool(tail["k"], tail["v"], dst)
        gb = self._gather_bucket(P, bucket)
        table_row = self.block_tables[slot][None]
        if self._state_col:
            # the chain is the static prefix (radix is refused): its K/V is the
            # shared blocks and the tail just scattered, its state the snapshot
            self._restore_slot_state(slot, self._prefix_state)
        with span(PREFILL_CALL_SPAN):
            logits, self.k_pool, self.v_pool, self.k_scale, self.v_scale = \
                forward_paged(
                    self.params, self.cfg, tokens, positions,
                    self.k_pool, self.v_pool, table_row,
                    **self._prefill_kw("xla", n - P),
                    fresh_block=False, gather_blocks=gb,
                    k_scale=self.k_scale, v_scale=self.v_scale,
                    kv_quant=self.kv_quant,
                )
        return logits

    def prefill_slot(self, ids: list[int], slot: int):
        """Radix-aware admission: consult the group's tree for the longest
        cached block chain before falling back to the static-prefix /
        full-prefill decision tree. RADIX_ENABLE unset (``self.radix is
        None``) takes the parent path untouched."""
        if self.radix is None:
            return super().prefill_slot(ids, slot)
        with span(ALLOC_SPAN):
            # capture the incoming tenant namespace across the release below
            # (release pops it — it belongs to the PREVIOUS occupant there)
            ns = self._slot_ns.get(slot)
            self.release_slot(slot)
            if ns is not None:
                self._slot_ns[slot] = ns
            ids = list(ids)
            g = self._group(slot)
            chain, matched = self.radix[g].match(ids, ns=ns)
            bucket = None
            P, tail = matched, None
            if matched:
                P0 = len(self.prefix_ids)
                if (self._prefix_tail is not None and P0 > matched
                        and len(ids) > P0
                        and chain == self._prefix_blocks[g][: len(chain)]
                        and ids[:P0] == self.prefix_ids):
                    # the match stopped exactly at the pinned root chain and
                    # the prompt extends the full static prefix: keep the
                    # sub-block tail scatter (byte-for-byte the
                    # _prefill_suffix layout) instead of recomputing the
                    # P % block_size remainder
                    P, tail = P0, self._prefix_tail
                suffix = ids[P:]
                bucket = self._suffix_bucket(len(suffix), self.max_len - P)
                if bucket is None:
                    # no suffix bucket fits: release the chain refs and take
                    # the full-prompt path (which buckets independently)
                    self.allocator.free(chain)
                    matched = 0
        if not matched:
            logits = super().prefill_slot(ids, slot)
            # the parent prefill releases the slot once more on entry, which
            # pops the namespace again — reinstate it for this occupant's
            # insert-at-release
            if ns is not None:
                self._slot_ns[slot] = ns
            self._slot_ids[slot] = ids
            return logits
        # the hit is accounted only HERE — a bucket fallback above must not
        # show up as served-from-cache in the radix gauges
        with span(ALLOC_SPAN):
            self.radix[g].record_hit(P)
            m = len(suffix)
            tokens = np.full((1, bucket), self.pad_id, dtype=np.int32)
            tokens[0, :m] = suffix
            positions = (P + np.arange(bucket, dtype=np.int32))[None, :]
            with span(PREFILL_STAGE_SPAN):
                t0 = time.perf_counter()
                tokens, positions = jnp.asarray(tokens), jnp.asarray(positions)
                logits = self._prefill_chain(tokens, positions, slot, chain, P,
                                             bucket, len(ids), tail=tail)
                self._last_prefill_compute_ms = (time.perf_counter() - t0) * 1e3
            self._last_cached_tokens = P
            self._slot_ids[slot] = ids
        with span(FIRST_TOKEN_SPAN):
            return logits[:, m - 1, :]

    # ------------------------------------------------- grouped admission

    # positions a row of a grouped call computes: the wider of the two suffix
    # buckets ``_suffix_bucket`` tries first (short payloads' own). ONE, so
    # that grouping adds one forward executable to a start-up and not two: a
    # warm start loads each one in 0.5 (a scanned dense 7B) to 3 s (unrolled
    # layers around Pallas calls), and ``setup_s`` may not grow a tenth. A
    # group whose suffixes all fit 32 pays 64 (PERF.md section 6, PR 35)
    GROUP_BUCKET = 64

    @property
    def admit_rows(self) -> int:
        """The ONE width A of a grouped admission call (ISSUE 35): requests
        that wait together behind the static prefix share one
        ``forward_paged`` at (A, ``GROUP_BUCKET``) — one read of the weights — where
        ``prefill_slot`` reads them once a request. An eighth of the slots
        (and not the quarter ``compact_rows`` takes): past ~128 rows a dense
        7B forward is compute-bound, so a call's device time grows with A
        while a group of TWO must still cost less than two one-row calls
        (PERF.md section 6, PR 35, has the chip's numbers at 4 and at 8).
        0 = never: a mesh (slots of different dp groups share no batch
        axis), radix reuse (an admission's chain is its own), ``KV_QUANT`` —
        all of which ``prefill_slot`` serves as it did."""
        A = self.batch_slots // 8
        on = (A >= 2 and self.dp == 1 and self.radix is None
              and self.kv_quant is None and bool(self.prefix_ids))
        return A if on else 0

    @property
    def suffix_buckets(self) -> tuple:
        """Where admissions are grouped, ONE suffix bucket before the
        full-prompt ones, the group's: the (1, 32) program would serve the
        odd lone short suffix alone, for half a millisecond of a 24 ms call
        at a dense 7B's width (my chip runs, PR 35), and every forward
        executable is 0.5 to 4 s of a warm start. So grouping leaves the
        number of forward programs a start-up loads where it was."""
        return (self.GROUP_BUCKET,) if self.admit_rows else super().suffix_buckets

    def prepare_admission(self, ids: list[int], slot: int) -> PreparedAdmission | None:
        """The HOST half of a static-prefix admission into ``slot``: the
        prefix match, the slot's release, its blocks and books — everything
        of ``prefill_slot`` that can fail for this request alone
        (``PoolExhausted``, a chaos fault), and no launch. None, with nothing
        touched: this prompt is ``prefill_slot``'s (no prefix match, a suffix
        past ``GROUP_BUCKET``, the grouped path off)."""
        from ..utils.chaos import ChaosError, chaos_fire

        if not self.admit_rows:
            return None
        suffix = self._split_prefix(ids)
        if suffix is None:
            return None
        P = len(self.prefix_ids)
        bucket = self._suffix_bucket(len(suffix), self.max_len - P)
        if bucket is None or bucket > self.GROUP_BUCKET:
            return None
        with span(ALLOC_SPAN):
            if chaos_fire("prefill_exc"):
                raise ChaosError("chaos: injected prefill exception")  # as prefill_slot's
            self.release_slot(slot)  # a finished request may still own resources
            bs = self.block_size
            shared = list(self._prefix_blocks[self._group(slot)][: P // bs])
            self.allocator.ref(shared)
            owned = self._claim_chain(slot, shared, P, bucket, len(ids))
            return PreparedAdmission(slot=slot, n=len(ids), cached=P, suffix=tuple(suffix),
                                     bucket=bucket, blocks=tuple(shared + owned),
                                     tail_at=owned[0] * bs)

    def admit_group(self, group: list[PreparedAdmission], pick=None, state=None,
                    pick_args: tuple = (), pick_kw: tuple = ()) -> GroupAdmission:
        """The DEVICE half of the admissions ``prepare_admission`` prepared,
        ONE program for the group (``forward_paged_first_tokens``): their
        table rows, their prefix tails, for a model with a recurrent state
        their snapshots, ONE ``forward_paged`` at (``admit_rows``,
        ``GROUP_BUCKET``) whose head runs on each row's last real position,
        and the caller's ``pick(logits, state, slots, ns, *pick_args,
        **dict(pick_kw))`` on its logits (a module-level function and a tuple
        of pairs: they are static). Rows the group does not fill park their
        writes. A group of one runs ``prefill_slot``'s launches at (1,
        bucket), the programs it always ran, and hands its logits back.
        One record an admission; nothing is left on the engine."""
        t0 = time.perf_counter()
        if len(group) == 1:
            (p,) = group
            P, m = p.cached, len(p.suffix)
            tokens = np.full((1, p.bucket), self.pad_id, dtype=np.int32)
            tokens[0, :m] = p.suffix
            positions = (P + np.arange(p.bucket, dtype=np.int32))[None, :]
            with span(ALLOC_SPAN), span(PREFILL_STAGE_SPAN):
                logits = self._run_chain_row(
                    jnp.asarray(tokens), jnp.asarray(positions), p.slot, list(p.blocks),
                    p.tail_at // self.block_size, P, p.bucket, p.n, tail=self._prefix_tail)
                ms = (time.perf_counter() - t0) * 1e3
            with span(FIRST_TOKEN_SPAN):
                logits = logits[:, m - 1, :]
            return GroupAdmission((AdmissionRecord(p.slot, P, ms, 1, 1, p.bucket),), logits=logits)
        A, n, P, bucket = self.admit_rows, len(group), group[0].cached, self.GROUP_BUCKET
        slots = np.full((A,), self.batch_slots, np.int32)  # past the table: dropped
        slots[:n] = [p.slot for p in group]
        with span(ALLOC_SPAN):
            tokens = np.full((A, bucket), self.pad_id, dtype=np.int32)
            positions = np.broadcast_to(P + np.arange(bucket, dtype=np.int32), (A, bucket))
            # a row the group does not fill: a table of trash blocks, no real
            # position, a masked write. Where a row carries a state index, its is
            # a slot OUTSIDE the group, whose state it rewrites as it found it
            # (what an idle row of the chunk program does to its own)
            rows = np.zeros((A, self.max_blocks + self._state_col), np.int32)
            if self._state_col:
                rows[n:, -1] = next(b for b in range(self.batch_slots) if b not in slots)
            m_real = np.zeros((A,), np.int32)
            R = 0 if self._prefix_tail is None else self._prefix_tail["k"].shape[1]
            dst = np.broadcast_to(np.arange(R, dtype=np.int32), (A, R)).copy()  # trash block
            for i, p in enumerate(group):
                tokens[i, : len(p.suffix)] = p.suffix
                rows[i] = self._table_row(p.slot, p.blocks)
                m_real[i] = len(p.suffix)
                dst[i] += p.tail_at
            ns = np.asarray([p.n for p in group] + [0] * (A - n), np.int32)
            restore_at = np.where(m_real > 0, slots, slots[0])
            with span(PREFILL_STAGE_SPAN):
                with span(SLOT_STATE_SPAN):  # the one host→device copy, before the launch
                    staged = jax.device_put((
                        tokens, positions, rows, slots, ns, m_real > 0,
                        np.maximum(m_real - 1, 0),
                        m_real if self.family.n_real else None,
                        dst.reshape(-1), restore_at))
                tokens, positions, rows, slots, ns, live, last, n_real, dst, restore_at = staged
                with span(PREFILL_CALL_SPAN):
                    picked, self.k_pool, self.v_pool, self.block_tables = \
                        forward_paged_first_tokens(
                            self.params, self.cfg, tokens, positions, self.k_pool, self.v_pool,
                            self.block_tables, rows, slots, ns, live, last, n_real,
                            self._prefix_tail, dst, self._prefix_state if self._state_col else None,
                            restore_at, state, pick_args, rules=self.rules,
                            attn_impl=self.kernels if self.family.n_real else "xla",
                            gather_blocks=self._gather_bucket(P, bucket),
                            pick=pick, pick_kw=pick_kw)
                ms = (time.perf_counter() - t0) * 1e3 / n
        if self._state_col:
            from ..utils import get_metrics

            get_metrics().inc("ssm.state_restores", float(n))
        return GroupAdmission(
            tuple(AdmissionRecord(p.slot, P, ms, n, A, bucket) for p in group), picked=picked)

    def _prefill_full(self, tokens, positions, slot: int, bucket: int, n: int):
        bs = self.block_size
        owned = self._alloc(-(-bucket // bs), self._group(slot))
        self._slot_shared[slot], self._slot_owned[slot] = [], owned
        self._set_table_row(slot, owned)
        self._covered[slot] = len(owned) * bs
        self._next_pos[slot] = n
        table_row = self.block_tables[slot][None]
        if self._state_col:
            self._restore_slot_state(slot, None)
        # position 0 start: block-local attention, no pool gather at all
        with span(PREFILL_CALL_SPAN):
            logits, self.k_pool, self.v_pool, self.k_scale, self.v_scale = \
                forward_paged(
                    self.params, self.cfg, tokens, positions,
                    self.k_pool, self.v_pool, table_row,
                    **self._prefill_kw(self.kernels, n),
                    fresh_block=True, gather_blocks=None,
                    k_scale=self.k_scale, v_scale=self.v_scale,
                    kv_quant=self.kv_quant,
                )
        return logits

    # ------------------------------------------------- chunked prefill

    def begin_chunked_prefill(self, ids: list[int], slot: int,
                              chunk_tokens: int) -> "_ChunkedPrefill | None":
        """Start a chunked admission (ISSUE 19): same decision tree as
        ``prefill_slot`` — radix chain match, static-prefix tail, block
        layout — but instead of one barrier ``(1, bucket)`` forward, the
        suffix is split into ``chunk_tokens``-sized pieces the scheduler
        advances one per step (``chunked_prefill_step``), interleaved with
        batch-mates' decode chunks. All blocks are allocated HERE, so the
        step calls can never raise PoolExhausted mid-admission; an evicted
        mid-prefill slot releases everything through the ordinary
        ``release_slot(ok=False)`` seam (no radix insert of a half-computed
        chain: ``_slot_ids`` is only set at the final chunk).

        Returns None when chunking cannot represent the prompt (padded
        span past max_len, or nothing left to compute) — the caller falls
        back to the one-shot ``prefill_slot`` path, which buckets (and
        errors) independently. Nor when the model's record refuses
        ``chunked_prefill`` (the cursor carries no count of real positions for
        a recurrent state): declined, not raised — the caller's fallback serves it."""
        if "chunked_prefill" in self.family.refuses:
            return None
        ns = self._slot_ns.get(slot)
        self.release_slot(slot)
        if ns is not None:
            self._slot_ns[slot] = ns
        ids = list(ids)
        g = self._group(slot)
        chain: list[int] = []
        P, tail = 0, None
        radix_hit = False
        if self.radix is not None:
            chain, matched = self.radix[g].match(ids, ns=ns)
            P = matched
            radix_hit = matched > 0
            if matched:
                P0 = len(self.prefix_ids)
                if (self._prefix_tail is not None and P0 > matched
                        and len(ids) > P0
                        and chain == self._prefix_blocks[g][: len(chain)]
                        and ids[:P0] == self.prefix_ids):
                    # same static-prefix-tail special case as prefill_slot
                    P, tail = P0, self._prefix_tail
        if not P:
            if chain:
                self.allocator.free(chain)
                chain = []
            suffix0 = self._split_prefix(ids)
            if suffix0 is not None and self.prefix_ids:
                # shared-prefix hit without a (longer) radix chain: the
                # pinned prefix full blocks + dense sub-block tail, the
                # byte-for-byte _prefill_suffix layout
                P, tail = len(self.prefix_ids), self._prefix_tail
                chain = list(self._prefix_blocks[g][: P // self.block_size])
                self.allocator.ref(chain)
        suffix = ids[P:]
        m = len(suffix)
        C = int(chunk_tokens)
        if m <= 0 or C <= 0:
            if chain:
                self.allocator.free(chain)
            return None
        n_chunks = -(-m // C)
        span = n_chunks * C
        if P + span > self.max_len:
            if chain:
                self.allocator.free(chain)
            return None
        bs = self.block_size
        full = len(chain)
        n_owned = -(-(P + span) // bs) - full
        try:
            owned = self._alloc(n_owned, g)
        except PoolExhausted:
            if chain:
                self.allocator.free(chain)
            raise
        if radix_hit:
            # committed to serving from the cached chain: account the hit
            # only now (same post-alloc commit point as prefill_slot)
            self.radix[g].record_hit(P)
        self._slot_shared[slot], self._slot_owned[slot] = list(chain), owned
        self._set_table_row(slot, list(chain) + owned)
        self._covered[slot] = (full + n_owned) * bs
        if tail is not None:
            R = P - full * bs
            dst = jnp.asarray(owned[0] * bs + np.arange(R, dtype=np.int32))
            self._scatter_pool(tail["k"], tail["v"], dst)
        self._next_pos[slot] = len(ids)
        self._mid_prefill.add(slot)
        return _ChunkedPrefill(slot=slot, ids=ids, suffix=suffix, P=P, C=C,
                               n_chunks=n_chunks)

    def chunked_prefill_step(self, cur: "_ChunkedPrefill"):
        """Run ONE ``(1, C)`` prefill chunk of an admission started by
        ``begin_chunked_prefill``. Returns the final-token logits row when
        the last chunk lands (the scheduler's ``_first_token_into_slot``
        tail takes over), else None. Earlier chunks' KV is read through the
        slot's block table with the same pow2-bucketed gather the chain
        admission uses, so compile count stays log-bounded at one token-dim
        (C)."""
        slot, C, bs = cur.slot, cur.C, self.block_size
        with span(ALLOC_SPAN):
            start = cur.j * C
            seg = cur.suffix[start:start + C]
            tokens = np.full((1, C), self.pad_id, dtype=np.int32)
            tokens[0, : len(seg)] = seg
            positions = (cur.P + start + np.arange(C, dtype=np.int32))[None, :]
            need = -(-(cur.P + start + C) // bs)
            gb = 1
            while gb < need:
                gb *= 2
            gb = min(gb, self.max_blocks)
            with span(PREFILL_STAGE_SPAN):
                t0 = time.perf_counter()
                tokens, positions = jnp.asarray(tokens), jnp.asarray(positions)
                table_row = self.block_tables[slot][None]
                with span(PREFILL_CALL_SPAN):
                    logits, self.k_pool, self.v_pool, self.k_scale, self.v_scale = \
                        forward_paged(
                            self.params, self.cfg, tokens, positions,
                            self.k_pool, self.v_pool, table_row,
                            rules=self.rules, attn_impl="xla",
                            fresh_block=False, gather_blocks=gb,
                            k_scale=self.k_scale, v_scale=self.v_scale,
                            kv_quant=self.kv_quant,
                        )
                cur.total_ms += (time.perf_counter() - t0) * 1e3
        cur.j += 1
        if cur.j < cur.n_chunks:
            return None
        self._mid_prefill.discard(slot)
        self._last_prefill_compute_ms = cur.total_ms
        self._last_cached_tokens = cur.P
        self._slot_ids[slot] = cur.ids
        r = len(cur.suffix) - start
        with span(FIRST_TOKEN_SPAN):
            return logits[:, r - 1, :]

    # ------------------------------------------------------------ decode

    def reconcile_coverage(self, pos_h) -> None:
        """Post-chunk hook (scheduler): clamp each live slot's growth
        target to its ACTUAL frontier. decode_chunk must claim the
        worst-case ff span before dispatch, but a grammar that rarely
        forces chains would otherwise compound (1+W)x per chunk until
        every table covered max_len — the dense worst-case footprint this
        engine exists to avoid."""
        for b in range(self.batch_slots):
            if self._slot_owned[b] and b not in self._mid_prefill:
                self._next_pos[b] = min(self._next_pos[b], int(pos_h[b]))

    def _grow(self, slot: int, upto: int, grown: list | None = None) -> None:
        """Extend a slot's table so positions < upto have blocks. With
        ``grown`` the slot is noted there and its table row left to the
        caller's ONE ``_put_table_rows`` (a chunk's claim: every request
        admitted this step needs a block more, and a launch a slot made the
        device wait for the chunk program behind them)."""
        bs = self.block_size
        upto = min(upto, self.max_len)
        if upto <= self._covered[slot]:
            return
        extra = self._alloc(
            -(-(upto - self._covered[slot]) // bs), self._group(slot))
        self._slot_owned[slot].extend(extra)
        self._covered[slot] += len(extra) * bs
        if grown is None:
            self._set_table_row(slot, self._slot_shared[slot] + self._slot_owned[slot])
        else:
            grown.append(slot)

    def _put_table_rows(self, slots: list[int]) -> None:
        """The table rows of ``slots`` from the host's books, in ONE launch
        at one shape (``batch_slots`` rows; the unused ones name a slot past
        the table and are dropped)."""
        B = self.batch_slots
        at = np.full((B,), B, np.int32)
        at[: len(slots)] = slots
        rows = np.zeros((B, self.max_blocks + self._state_col), np.int32)
        for i, b in enumerate(slots):
            rows[i] = self._table_row(b, self._slot_shared[b] + self._slot_owned[b])
        self.block_tables = _set_table_rows(self.block_tables, *jax.device_put((at, rows)))

    def _rows_of(self, live) -> "np.ndarray | None":
        """The (R,) slot index a compacted chunk rides, or None for the full
        width: the live slots, padded with the first idle ones so no index
        repeats (the program scatters the rows back)."""
        R = self.compact_rows
        if live is None or not R:
            return None
        live = np.asarray(live, dtype=bool)
        k = int(live.sum())
        if not 1 <= k <= R:
            return None
        return np.concatenate(
            [np.flatnonzero(live), np.flatnonzero(~live)[: R - k]]
        ).astype(np.int32)

    def decode_chunk(self, cur, pos, fsm, active, nbytes, tokens_left, key,
                     temperature: float, byte_budget: int, chunk_steps: int,
                     greedy: bool, live=None, nan_inject=None) -> ChunkResult:
        """One dispatch of up to ``chunk_steps`` constrained decode steps
        (the contract: ``DecodeEngine.decode_chunk``).

        With 1 to ``compact_rows`` slots ``live`` the greedy chunk runs at
        ``compact_rows`` rows (``paged_chunk_decode_loop``'s ``rows_idx``)
        and is token-identical; otherwise, and without ``live``, at
        ``batch_slots`` through the call this always made. A sampled
        (non-greedy) chunk keeps the full width: its per-row noise is drawn
        at the batch's shape, so a row's place would change its tokens.
        The record's ``rows`` says which width ran.

        The worst-case (1+W)x-per-step block claim below is what the
        caller's ``reconcile_coverage`` clamps back: a driver that skips it
        compounds the claim toward max_len per slot — recreating the dense
        footprint this engine exists to avoid."""
        # a fast-forward chunk can emit up to (1+W) tokens per step — the
        # table must cover the worst case BEFORE dispatch (a mid-chunk
        # write past the covered blocks would scribble on the pool). The
        # worst-case claim does NOT compound across chunks: the scheduler
        # reconciles _next_pos to each row's ACTUAL frontier after every
        # chunk (reconcile_coverage), so over-allocation stays bounded by
        # one chunk's span instead of racing every table to max_len
        W = (self.tables_ff.ff_tokens.shape[1]
             if self.tables_ff is not None else 0)
        span = chunk_steps * (1 + W)
        # slots whose claim took blocks: one launch for all of them (a slot at
        # a time under a mesh, whose tables keep their placement that way)
        grown: list[int] | None = [] if self.mesh is None else None
        for b in range(self.batch_slots):
            if b in self._mid_prefill:
                # chunked admission underway (ISSUE 19): the row is not
                # decoding — its blocks are fully allocated already and a
                # worst-case growth claim here would bleed the pool every
                # chunk with nothing to reconcile it back
                continue
            if self._slot_owned[b]:  # request in flight on this slot
                try:
                    self._grow(b, self._next_pos[b] + span + 1, grown)
                except PoolExhausted:
                    # per-request isolation at decode time too: the slot
                    # that cannot grow truncates cleanly (finished=False)
                    # at its already-covered positions; the batch lives on
                    tokens_left = tokens_left.at[b].set(0)
                    continue
                self._next_pos[b] = min(self._next_pos[b] + span, self.max_len)
        if grown:
            self._put_table_rows(grown)
        rows = self._rows_of(live) if greedy else None
        # absent at the full width, so that call is the one it always was
        compact = {} if rows is None else {"rows_idx": jnp.asarray(rows)}
        width = (self.batch_slots if rows is None else len(rows)) * (1 + W)
        # likewise absent where no block is wider than the packed rows
        packs = bool(W and self.mesh is None and width > self.ffn_pack_rows > 0)
        if packs:
            compact["ffn_pack"] = self.ffn_pack_rows
        out, n, eos, self.k_pool, self.v_pool, self.k_scale, self.v_scale, \
            cur, pos, fsm, active, nbytes, left, fwds, pois, conf, *counts = (
                paged_chunk_decode_loop(
                    self.params, self.cfg, self.k_pool, self.v_pool, self.block_tables,
                    cur, pos, fsm, active, nbytes, tokens_left,
                    self.tables_ff if self.tables_ff is not None else self.tables,
                    self.byte_len_table,
                    key, jnp.float32(temperature), jnp.int32(byte_budget),
                    trash_idx=self._trash_idx, rules=self.rules,
                    logit_mask=self.logit_mask,
                    nan_inject=nan_inject,
                    k_scale=self.k_scale, v_scale=self.v_scale,
                    chunk_steps=chunk_steps,
                    greedy=greedy, constrained=True, kernels=self.kernels,
                    eos_id=self.eos_id, pad_id=self.pad_id, max_len=self.max_len,
                    kv_quant=self.kv_quant,
                    quality_lanes=self.quality_lanes,
                    **compact,
                )
            )
        # what the program counted, under the record's names in carry order
        # (a packing program's ``ffn`` last)
        names = [c.name for c in self.family.counts] + [FFN.name] * packs
        return ChunkResult(
            out, n, eos, cur, pos, fsm, active, nbytes, left,
            fwds=fwds, poison=pois,
            rows=self.batch_slots if rows is None else len(rows),
            conf=conf if self.quality_lanes else None,
            counts=dict(zip(names, counts, strict=True)), ffn_rows=width)

    def set_slot_ns(self, slot: int, ns: str | None) -> None:
        """Install the tenant radix namespace for the slot's NEXT admission
        (the scheduler calls this right before ``prefill_slot``; the
        namespace rides until the occupant's release inserts its chain)."""
        if ns is None:
            self._slot_ns.pop(slot, None)
        else:
            self._slot_ns[slot] = ns

    def release_slot(self, slot: int, generated_ids: list[int] | None = None,
                     ok: bool = True) -> None:
        # an evicted mid-chunked-prefill slot releases through here too:
        # its half-computed chain never inserts (_slot_ids unset until the
        # final chunk), and the mid-prefill mark must not survive the slot
        self._mid_prefill.discard(slot)
        ns = self._slot_ns.pop(slot, None)
        if self._slot_owned[slot] or self._slot_shared[slot]:
            if (ok and self.radix is not None and generated_ids is not None
                    and self._slot_ids[slot] is not None
                    and self._radix_may_admit(self._group(slot))):
                # insert the finished request's prompt+generated chain back
                # into the tree BEFORE freeing the slot's refs: adopted
                # blocks gain the tree's own ref and survive the free below.
                # ok=False (errored/poisoned/cancelled request) NEVER
                # inserts: a poisoned generation must not be served to a
                # later session as a warm prefix. Under pool pressure
                # (_radix_may_admit) insertion is denied too — caching must
                # yield to live admissions before live admissions shed.
                ids = self._slot_ids[slot] + [int(t) for t in generated_ids]
                blocks = self._slot_shared[slot] + self._slot_owned[slot]
                self.radix[self._group(slot)].insert(ids, blocks, ns=ns)
            self.allocator.free(self._slot_owned[slot])
            self.allocator.free(self._slot_shared[slot])
            self._slot_owned[slot] = []
            self._slot_shared[slot] = []
            self._covered[slot] = 0
            self._next_pos[slot] = 0
        self._slot_ids[slot] = None

    def _radix_may_admit(self, group: int) -> bool:
        """Pool-pressure gate on session-cache admission (degradation stage
        2 — after cold-leaf eviction, before shedding live work): while a
        recent allocation hit PoolExhausted, released chains are dropped
        instead of adopted, so the tree stops pinning blocks the next
        admission will immediately need. Existing cached chains still
        serve hits; the cache just stops growing until pressure clears."""
        if time.monotonic() >= self._pressure_until:
            return True
        from ..utils import get_metrics

        get_metrics().inc("radix.admission_denied")
        return False

    # ------------------------------------------------------------ handoff

    def slot_block_count(self, slot: int) -> int:
        return len(self._slot_shared[slot]) + len(self._slot_owned[slot])

    def slot_chain_blocks(self, slot: int) -> list[int]:
        """The in-order pool block chain covering ``slot``'s context —
        shared (pinned prefix / radix-matched) blocks first, then owned
        blocks. Valid mid-chunked-prefill too: a block is fully WRITTEN
        only once the compute frontier has passed it, which is the
        disagg exporter's job to track (ISSUE 20 streams only blocks
        behind the frontier). Serving-loop thread only."""
        return list(self._slot_shared[slot]) + list(self._slot_owned[slot])

    def gather_chain_kv(self, blocks: list[int]):
        """Host copies of the pool KV for ``blocks``, in STORED format —
        the warm-state handoff's export payload (serve.handoff): bf16
        values (KV_QUANT off) or int8 bytes plus their bf16 scale planes
        (scales travel with the block — ops.kvquant's layout contract).
        Returns ``(k, v, k_scale | None, v_scale | None)`` shaped
        ``(L, n, bs, nkv, hd_store)`` / ``(L, n, bs, nkv)``. Serving-loop
        thread only (reads race the decode loop's pool rebinds otherwise)."""
        self.family.refuse("handoff")
        idx = jnp.asarray(blocks, jnp.int32)
        k = np.asarray(jax.device_get(self.k_pool[:, idx]))
        v = np.asarray(jax.device_get(self.v_pool[:, idx]))
        if self.kv_quant is None:
            return k, v, None, None
        ks = np.asarray(jax.device_get(self.k_scale[:, idx]))
        vs = np.asarray(jax.device_get(self.v_scale[:, idx]))
        return k, v, ks, vs

    def adopt_chain_kv(self, k, v, k_scale=None, v_scale=None,
                       group: int = 0) -> list[int]:
        """Allocate ``n`` blocks and install already-stored-format KV rows
        (the handoff's adopt half). Values land via the PLAIN scatter —
        the shipped bytes are already in this pool's storage dtype, and
        re-quantizing quantized bytes would change them — and the scale
        planes ride their own scatter. ``PoolExhausted`` propagates (after
        the radix-eviction retry in ``_alloc``): the caller counts the
        clean cold fallback. Serving-loop thread only."""
        self.family.refuse("handoff")
        n = int(k.shape[1])
        if self.kv_quant is not None and (k_scale is None or v_scale is None):
            raise ValueError("quantized pool adoption needs scale planes")
        if tuple(np.asarray(v).shape) != tuple(np.asarray(k).shape):
            raise ValueError("adopted v shape disagrees with k")
        blocks = self._alloc(n, group)
        try:
            bs = self.block_size
            arr = np.asarray(blocks, np.int32)
            dst = jnp.asarray(
                (arr[:, None] * bs
                 + np.arange(bs, dtype=np.int32)[None, :]).reshape(-1))
            L = int(k.shape[0])
            src_k = jnp.asarray(np.asarray(k)).reshape(L, n * bs, *k.shape[3:])
            src_v = jnp.asarray(np.asarray(v)).reshape(L, n * bs, *k.shape[3:])
            self.k_pool, self.v_pool = _scatter_blocks(
                self.k_pool, self.v_pool, src_k, src_v, dst)
            if self.kv_quant is not None:
                sk = jnp.asarray(np.asarray(k_scale)).reshape(L, n * bs, -1)
                sv = jnp.asarray(np.asarray(v_scale)).reshape(L, n * bs, -1)
                self.k_scale, self.v_scale = _scatter_scale_planes(
                    self.k_scale, self.v_scale, sk, sv, dst)
        except Exception:
            # a skewed/corrupt payload must not LEAK the claim: the caller
            # counts a clean cold fallback, and these blocks go back to
            # the pool instead of shrinking it forever
            self.allocator.free(blocks)
            raise
        return blocks

    def warm_restart(self) -> None:
        """Paged warm restart: throw away every slot's mutable state and the
        allocator/radix bookkeeping, KEEPING params, compiled programs, the
        pool arrays, and the static-prefix KV (its blocks are re-reserved in
        the fresh allocator and re-pinned as the radix root — the pool's
        bytes were never suspect, only the slot/table bookkeeping wedged
        with a stuck step). Inflight requests are the caller's to fail."""
        n_blocks = self.allocator.n_blocks
        self.allocator = BlockAllocator(n_blocks, n_groups=self.dp)
        for g in range(self.dp):
            if self._prefix_blocks[g]:
                self.allocator.reserve(self._prefix_blocks[g])
        if self.radix is not None:
            max_nodes = self.radix[0].max_nodes
            ns_quota = self.radix[0].ns_quota
            self.radix = [RadixCache(self.allocator, self.block_size, group=g,
                                     max_nodes=max_nodes)
                          for g in range(self.dp)]
            for rc in self.radix:
                rc.ns_quota = ns_quota  # tenant quotas survive warm restart
            full = len(self.prefix_ids) // self.block_size
            if full:
                for g in range(self.dp):
                    self.radix[g].pin_root_chain(
                        self.prefix_ids[: full * self.block_size],
                        self._prefix_blocks[g])
        self._slot_shared = [[] for _ in range(self.batch_slots)]
        self._slot_owned = [[] for _ in range(self.batch_slots)]
        self._covered = [0] * self.batch_slots
        self._next_pos = [0] * self.batch_slots
        self._slot_ids = [None] * self.batch_slots
        self._slot_ns.clear()
        self._mid_prefill.clear()
        self.block_tables = self._fresh_tables()
        self._pressure_until = 0.0
        # re-arm the recompilation sentinel (see the dense twin): the
        # rebuilt tables/allocator must come back at the old shapes — a
        # post-restart retrace is an alertable event, not background noise
        get_compile_watcher().arm_fence("warm_restart")

    # the dense single-request path doesn't exist here; the batcher is the
    # serving surface (generate_many / services with BRAIN_BATCH)
    def generate(self, *a, **kw):
        raise ValueError(
            "PagedDecodeEngine serves through the continuous batcher "
            "(serve.scheduler.ContinuousBatcher); use the dense DecodeEngine "
            "for single-request generate()")

    def generate_stepwise(self, *a, **kw):
        raise ValueError("see generate(): paged engines serve via the batcher")
