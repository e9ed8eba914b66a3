"""The serving contract of a model family: ONE record the model side owns and
the serving side (``serve.paged`` / ``engine`` / ``scheduler``, ``utils.hbm*``)
reads — what a request keeps on the device, which variant of the chunk
program the model compiles, what its forwards count, and what the engine may
not do with it. ``family`` is the only place that asks which model a
configuration is; a new family is its own module, its configuration's fields
and one more return there.

The cache, one shape for every family (each module's ``cache_spec(cfg)``):

    {"planes": {"k": {name: (layers, *trailing)}, "v": {...}},
     "slot_planes": {"k": {name: ((layers, *trailing), dtype)}, "v": {...}},
     "by_name": bool, "state_column": bool}

A block plane is (layers, N, block_size, *trailing) bfloat16 in the pool, a
per-SLOT plane (layers, slots, *trailing); a pool is the dict of its planes
``by_name``, or else its one plane ``"kv"`` itself; with ``state_column`` a
block-table row carries its slot's index in one more column.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from math import prod
from types import ModuleType
from typing import Mapping, NamedTuple

from ..ops import ATTN_STATS
from . import dots3, lfm2, llama, mla, nemotron_h, olmo_hybrid, sambay

# the rows the position-wise regions of a fast-forward block are packed into
# (ISSUE 37: the MLPs; ISSUE 41: q/k/v and the output projection with them):
# under the ridge of int8 weights on this chip (~120 rows: an MLP costs the
# same from 72 to 96 and more from 128 on), over what a chunk's forwards hold
# but its first (PERF.md section 5 item 1 has both measurements). Rows, not rows
# a slot: the ridge is the chip's, and a block no wider than this packs nothing
FFN_PACK_ROWS = 96


class Count(NamedTuple):
    """One thing a chunk program counts: a carry of ``len(metrics)`` int32
    summed over the chunk's forwards."""

    name: str  # its key in ``ChunkResult.counts``
    keyword: str  # the keyword of ``forward_paged`` that asks a forward for it
    metrics: tuple[str, ...]  # the counter each element is added to, in the forward's order


ATTN = Count("attn", "attn_stats", tuple(f"attn.{n}" for n in ATTN_STATS))
# the rows a forward's cache writes moved (``llama.write_rows``), behind a family's own counts
KV = Count("kv", "kv_stats", tuple(f"kv.{n}" for n in llama.KV_STATS))
# a program whose position-wise regions may run packed, LAST (``ffn_pack`` = the rows)
FFN = Count("ffn", "ffn_pack", tuple(f"ffn.{n}" for n in llama.FFN_STATS))


def _counts(cfg, latent: tuple[str, ...] = (), kv: bool = True) -> tuple[Count, ...]:
    """A LlamaConfig's, in the order its chunk program carries them: the
    routed layers' expert rows, the attention row-blocks, a latent cache's reads,
    the rows its cache writes moved (``kv``: its module writes through ``write_rows``)."""
    routed = (Count("moe", "moe_stats", tuple(f"moe.{n}" for n in llama.moe_stat_names(cfg))),)
    # K/V layers behind a window that BINDS at this ``max_seq_len``: what their walks read
    windowed = not cfg.kv_lora_rank and llama.bound_window(cfg) is not None
    return (routed if cfg.n_experts else ()) + (ATTN,) + (
        (Count("latent", "latent_stats", tuple(f"attn.{n}" for n in latent)),) if latent else ()) + (
        (Count("window", "window_stats", tuple(f"attn.{n}" for n in llama.WINDOW_STATS)),)
        if windowed else ()) + (
        # layers that run more than once: the passes, and where the exit gate's selection fell
        (Count("loop", "loop_stats", tuple(f"loop.{n}" for n in llama.LOOP_STATS)),)
        if cfg.ut_steps > 1 else ()) + ((KV,) if kv else ())


@dataclass(frozen=True)
class Family:
    name: str
    module: ModuleType  # ``forward_paged`` / ``init_params`` / ``quantize_params`` are looked up
    # on it at CALL time (a check plants faults by rebinding a module's forward)
    cache: Mapping  # the module's ``cache_spec(cfg)``: the shape in this module's text
    counts: tuple[Count, ...]  # what every chunk program carries, in carry order (``FFN`` behind)
    error: type  # what ``refuse`` raises
    refuses: Mapping[str, str]  # serving feature -> why this family cannot honour it
    # the program variant
    n_real: str = ""  # where a forward is told its rows' real positions: "admit" (a prefill
    # behind the prefix), "always" (the prefix and every decode forward too), "" (a packed block
    # alone). One that is told picks an admission's attention path itself, by T, from the engine's
    # kernels; for the others the caller names the layout's ("xla" behind a prefix)
    block_real: bool = False  # a 1 + W block forward is told them at EVERY width, packed or not:
    # its attention kernel multiplies the real positions alone (``ops.paged_block_attention``'s
    # common pass, both passes of ``ops.paged_latent_attention``)
    one_head: bool = False  # the head runs on the ONE position a row of a 1 + W block reads
    pack_rows: int = FFN_PACK_ROWS  # the packed width of a fast-forward block; 0: no packed branch
    scratch_prefix: bool = True  # the prompt prefix is prefilled through a scratch POOL
    # (``forward_paged`` alone runs the model), not through the dense cache
    prefix_whole_blocks: bool = False  # the cached prefix ends on a block (no sub-block tail to
    # scatter plane by plane; the rest rides every suffix) and may pass the largest bucket, in chunks

    def refuse(self, feature: str, error: type | None = None) -> None:
        """Raise where this family cannot honour ``feature``: its own class, or
        ``error`` from an entry point that states something else (a function
        that does not implement it: ``NotImplementedError``)."""
        why = self.refuses.get(feature)
        if why is not None:
            raise (error or self.error)(f"{feature}: {why}")

    def count(self, name: str) -> Count:
        return next(c for c in self.counts + (FFN,) if c.name == name)

    @property
    def kv_by_head(self) -> bool:
        """The block planes are K and V by head, (layers, heads, head_dim): what a
        dense decoder's arithmetic sizes (``utils.hbmledger``)."""
        return all(len(p) == 3 for side in self.cache["planes"].values() for p in side.values())

    @property
    def token_bytes(self) -> int:
        """Bytes a token holds in the pool's block planes (bfloat16)."""
        return 2 * sum(prod(p) for side in self.cache["planes"].values() for p in side.values())


def _state_refuses(state: str, held: str, config: str, dense_cache: str,
                   mesh: str = "a mesh shards a LlamaConfig's weights and", **more: str) -> dict[str, str]:
    """What a family whose requests hold a per-slot state beside their K/V blocks
    refuses, and why: ``state`` names it, ``held`` says what goes with the blocks,
    ``config`` whose it is; ``dense_cache`` why ``forward_paged`` alone runs the
    model; ``mesh`` what a mesh would do before it moved the blocks alone. One
    line a family: the six reasons are the same six, for another noun."""
    alone = f"K/V blocks alone, without {held} with them: not with {config}"
    return {"kv_quant": f"KV_QUANT re-stores {alone}",
            "radix": f"radix reuse hands a slot cached {alone}",
            "mesh": f"{mesh} {alone}",
            "handoff": f"a handoff ships and adopts {alone}",
            "chunked_prefill": "the cursor of a chunked admission carries no count of real positions "
                               f"for {state}: the one-shot prefill_slot serves it",
            "dense_cache": dense_cache, **more}


_HYBRID_REFUSES = _state_refuses(
    "the recurrent state", "the recurrent state that goes", "a SambaYConfig",
    "the state of a SambaYConfig's requests lives in the paged pool's per-slot planes, "
    "forward_paged's: PagedDecodeEngine alone serves it",
    mesh="a mesh shards weights of a LlamaConfig's layout and",
    ffn_pack="models.sambay's MLPs have no packed branch (ROADMAP S3 (e))")
_SSD_REFUSES = _state_refuses(
    "the Mamba-2 state", "the Mamba-2 state (4 MB a layer a request) and the convolution tail that go",
    "a NemotronHConfig",
    "a NemotronHConfig's state lives in the paged pool's per-slot planes and its layers are one "
    "block of three kinds: forward_paged's, PagedDecodeEngine alone serves it",
    mesh="a mesh shards a LlamaConfig's weights and would exchange latent rows between the chips "
         "that share an expert layer, which nothing here does; it moves")
_GDN_REFUSES = _state_refuses(
    "the delta-rule state",
    "the delta-rule state (2.2 MB a layer a request, float32) and the convolution tail that go",
    "an OlmoHybridConfig",
    "an OlmoHybridConfig's state lives in the paged pool's per-slot planes and its layers are of "
    "two kinds under a reordered norm: forward_paged's, PagedDecodeEngine alone serves it")
# a tail alone: no recurrence, nothing scanned — 8 KB a layer a request at the published sizes
_CONV_REFUSES = _state_refuses(
    "the convolution tail", "the convolution tail (two gated inputs a layer a request) that goes",
    "an Lfm2Config",
    "an Lfm2Config's tails live in the paged pool's per-slot planes and its layers are of two "
    "mixer kinds over dense and routed MLPs: forward_paged's, PagedDecodeEngine alone serves it")
_PLANES = "K and V planes by head: a latent cache has none"
_LATENT_REFUSES = {
    "kv_quant": f"KV_QUANT re-stores {_PLANES}",
    "radix": f"radix reuse hands a slot cached {_PLANES}",
    "mesh": f"a mesh shards {_PLANES}",
    "handoff": f"a handoff ships and adopts {_PLANES}",
    "dense_cache": f"a dense cache holds {_PLANES} (forward_paged alone runs it, "
                   "PagedDecodeEngine on one device serves it)",
}
_PAGED_ONLY = {"dense_cache": "layers of more than one kind, a parallel block, a tied head, a sandwich "
                              "norm and a router on the layer's input are forward_paged's: PagedDecodeEngine "
                              "serves this model, the dense cache does not"}


_LOOPED = ("a K/V plane for every (pass, layer) — ut_steps x n_layers of them — over n_layers "
           "layers of weights")
# what no CPU test drives at ut_steps > 1 is refused, not assumed: each names what it would
# have to learn of the planes
_LOOPED_REFUSES = {
    "dense_cache": f"{_LOOPED}: the dense cache and llama.forward hold one plane a layer and run "
                   "the layers once; forward_paged's loop of passes alone runs this model, "
                   "PagedDecodeEngine serves it",
    "mesh": f"a mesh shards a pool of n_layers planes by its rules and runs the layers once: {_LOOPED} "
            "is not placed by parallel.mesh",
    "kv_quant": f"KV_QUANT's scale planes and quantising scatters are untested over {_LOOPED}",
    "radix": f"radix reuse adopts and evicts chains of blocks whose cost it counts a layer: untested over {_LOOPED}",
    "handoff": f"a handoff ships and adopts blocks sized by n_layers: untested over {_LOOPED}",
    "chunked_prefill": "a chunked admission's cursor is untested over the loop of passes: the one-shot "
                       "prefill_slot serves it",
}


def tree_owner(params: dict) -> ModuleType:
    """The module whose parameter tree ``params`` is, of the families that keep
    a tree of their own (each names the key only its tree has, ``TREE_ROOT``)."""
    return next(m for m in (sambay, nemotron_h, olmo_hybrid, lfm2) if m.TREE_ROOT in params)


@lru_cache(maxsize=256)  # configurations are few, frozen and hashable; the record is read-only
def family(cfg) -> Family:
    """The record of ``cfg``'s family."""
    if isinstance(cfg, lfm2.Lfm2Config):  # a convolution's tail beside K/V, no recurrence; routed experts
        hybrid = Count("hybrid", "hybrid_stats", lfm2.HYBRID_STATS)
        routed = Count("moe", "moe_stats", tuple(f"moe.{n}" for n in llama.moe_stat_names(cfg)))
        return Family("conv", lfm2, lfm2.cache_spec(cfg), (hybrid, routed, ATTN, KV),
                      sambay.StateNotCarried, _CONV_REFUSES, n_real="always", one_head=True)
    if isinstance(cfg, olmo_hybrid.OlmoHybridConfig):  # a delta-rule matrix state beside K/V
        hybrid = Count("hybrid", "hybrid_stats", olmo_hybrid.HYBRID_STATS)
        return Family("gdn", olmo_hybrid, olmo_hybrid.cache_spec(cfg), (hybrid, ATTN, KV),
                      sambay.StateNotCarried, _GDN_REFUSES, n_real="always", one_head=True)
    if isinstance(cfg, nemotron_h.NemotronHConfig):  # a Mamba-2 state beside K/V, latent experts
        hybrid = Count("hybrid", "hybrid_stats", nemotron_h.HYBRID_STATS)
        routed = Count("moe", "moe_stats", tuple(f"moe.{n}" for n in llama.moe_stat_names(cfg)))
        return Family("ssd", nemotron_h, nemotron_h.cache_spec(cfg), (hybrid, routed, ATTN, KV),
                      sambay.StateNotCarried, _SSD_REFUSES, n_real="always", one_head=True)
    if not isinstance(cfg, llama.LlamaConfig):  # a ``sambay.SambaYConfig``: K/V and a recurrent state
        hybrid = Count("hybrid", "hybrid_stats", sambay.HYBRID_STATS)
        return Family("hybrid", sambay, sambay.cache_spec(cfg), (hybrid, ATTN, KV),
                      sambay.StateNotCarried, _HYBRID_REFUSES, n_real="always",
                      one_head=True, pack_rows=0)
    if cfg.index_topk:  # learned sparse attention over a latent cache, planes by layer kind
        # (where shared layers take a full layer's selection, what says one was carried)
        carried = dots3.CARRY_STATS if cfg.indexer_types else ()
        return Family("sparse", dots3, dots3.cache_spec(cfg),
                      _counts(cfg, mla.LATENT_STATS + dots3.SPARSE_STATS + carried, kv=False),
                      mla.LatentCacheOnly,
                      _LATENT_REFUSES, n_real="admit", one_head=True, prefix_whole_blocks=True)
    if cfg.kv_lora_rank:  # a latent and ONE rotated key a token a layer
        return Family("latent", mla, mla.cache_spec(cfg), _counts(cfg, mla.LATENT_STATS),
                      mla.LatentCacheOnly, _LATENT_REFUSES, block_real=True, one_head=True)
    looped = cfg.ut_steps > 1
    paged_only = bool(cfg.layer_types or cfg.parallel_block or cfg.tie_embeddings
                      or cfg.router_input == "layer" or looped or cfg.sandwich_norm)
    refuses = dict(_LOOPED_REFUSES if looped else _PAGED_ONLY if paged_only else {})
    if llama.bound_window(cfg) is not None:  # (the block kernel's meshed and quantised wrappers)
        refuses.update({f: "a sliding window that binds: the wrappers of the kernels that serve "
                           f"it take no window" for f in ("mesh", "kv_quant")})
    return Family("plain", llama, llama.cache_spec(cfg), _counts(cfg), NotImplementedError,
                  refuses, block_real=True,
                  one_head=bool(cfg.layer_types) or looped, scratch_prefix=paged_only)

