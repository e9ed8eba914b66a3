"""Llama-family decoder, TPU-first functional JAX.

This is the in-tree replacement for the reference's cloud LLM call
(apps/brain/src/llm.ts:19-30). Design choices for the TPU:

- params are a flat pytree with layers *stacked* on a leading axis and the
  forward pass is a ``lax.scan`` over layers: one trace regardless of depth,
  fast compiles for 70B-class configs, and remat-friendly for training
- all matmuls run in bfloat16 with float32 accumulation on the MXU
  (``preferred_element_type``); softmax/norms in float32 on the VPU
- static shapes everywhere: the KV cache is a dense ``(L, B, S, n_kv, hd)``
  ring the engine buckets by sequence length; attention uses position masks,
  never dynamic slice sizes
- grouped-query attention + RoPE, SwiGLU MLP, RMSNorm (Llama 2/3 and
  TinyLlama all instantiate from ``LlamaConfig``)
- tensor-parallel sharding is injected from the outside via
  ``parallel.ShardingRules`` constraints; the math code never mentions a mesh
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..utils.compilewatch import watch_compiles

# ---------------------------------------------------------------- config

# the activation on the gate plane of a gated MLP (``LlamaConfig.gate_act``)
GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 4096
    dim: int = 2048
    n_layers: int = 22
    n_heads: int = 32
    n_kv_heads: int = 4
    ffn_dim: int = 5632
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    # MoE (Mixtral-style): n_experts == 0 means a dense SwiGLU MLP;
    # n_experts > 0 swaps in a top-k routed expert FFN (models.moe routing)
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    # "dense": one-hot dispatch/combine einsums (jit-simple; FLOPs and weight
    # bytes ∝ E at drop-free capacity; the mesh/EP path). "grouped":
    # expert-grouped rows through the ops.grouped_matmul Pallas kernel — FLOPs
    # ∝ K, weight bytes ∝ the experts touched; single-device. "auto": the
    # engine decides ONCE, where it is built (single device -> grouped, mesh
    # -> dense: serve.engine.DecodeEngine.__init__); a bare ``forward`` call
    # reads "auto" as "dense", which is right everywhere.
    moe_impl: str = "auto"
    # two properties of the MODEL, not knobs. Mixtral divides the K chosen
    # softmax weights by their sum; OLMoE (``norm_topk_prob: false``) uses
    # them as they come out of the softmax over all experts
    norm_topk: bool = True
    # OLMoE: an RMSNorm with one learned gain over the WHOLE projected q
    # (n_heads * head_dim wide) and k vector, before the split into heads
    # and before RoPE
    qk_norm: bool = False
    # ---- sizes and rules of the MODEL a Llama-family file does not have
    # (``cohere2_moe``, Command A+). Every default is the Llama family's, and
    # with the defaults every program traces as it did before they existed.
    # A head's width where it is not dim / n_heads (128 query heads of 128 on
    # a 4096-wide residual); 0 = dim // n_heads
    head_size: int = 0
    # the kind of every layer by index, "sliding" | "full": a sliding layer
    # rotates q and k and a query sees its own and the ``sliding_window`` - 1
    # positions before it; a full layer sees every earlier position and
    # carries NO positions (no rotation). () = every layer full WITH rotation
    layer_types: tuple[str, ...] = ()
    sliding_window: int = 0
    # rotary pairs: (x[i], x[i + hd/2]) as Llama splits the head in halves,
    # or (x[2i], x[2i+1]) interleaved (``rope_gptj``)
    rope_interleaved: bool = False
    # "rms" | "layer" (a LayerNorm with a gain and no bias)
    norm: str = "rms"
    # ONE norm feeds attention and the expert layer, both added to the
    # residual: x' = x + Attn(N(x)) + FFN(N(x))
    parallel_block: bool = False
    # the router's score: "softmax" over the experts | "sigmoid" of each
    router_fn: str = "softmax"
    # experts every token passes through beside the routed ones; their MEAN is
    # added to the routed sum (``shared_expert_combination_strategy: average``)
    n_shared_experts: int = 0
    # THE CHIP'S SHARE of an expert-parallel group: the router stays
    # ``n_experts`` wide and picks ``top_k`` of them, this chip's expert
    # planes hold ``experts_held`` of them, ids ``first_expert`` onward;
    # 0 = all of them. A pick that falls elsewhere takes no row here
    experts_held: int = 0
    first_expert: int = 0
    # logits = logit_scale * N(x) embed^T (the head is the embedding)
    tie_embeddings: bool = False
    logit_scale: float = 1.0
    # ---- LATENT attention, leading dense layers and a biased router
    # (``deepseek_v3``: Moonlight; ``models.mla`` has the equations and the
    # forward). ``kv_lora_rank`` > 0: a token's cache is ONE normed latent of
    # that width and ONE rotated key of ``qk_rope_dim`` shared by every head —
    # no K and V planes — behind absorbed attention; a query head is
    # [``qk_nope_dim`` | ``qk_rope_dim``] wide (``head_size`` = their sum), a
    # value head ``v_head_dim``
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # the latent's own RMSNorm is built with its default in the published
    # implementation, not with ``rms_norm_eps``
    latent_norm_eps: float = 1e-6
    # the first layers run a dense SwiGLU of ``dense_ffn_dim`` where the rest
    # run ``n_experts`` routed experts of ``ffn_dim``
    first_dense_layers: int = 0
    dense_ffn_dim: int = 0
    # the experts are CHOSEN by score + a learned bias a expert and WEIGHTED
    # by the score alone (``topk_method: noaux_tc``); the renormalised gates
    # times ``router_scale``
    router_bias: bool = False
    router_scale: float = 1.0
    # the shared experts' outputs are ADDED to the routed sum, not averaged
    shared_sum: bool = False
    # ---- LEARNED SPARSE attention over a latent cache, beside WINDOWED latent
    # attention of its own sizes (``dots3_note``; ``models.dots3`` has the
    # equations and the forward: ``index_topk`` > 0 names that forward). With
    # ``kv_lora_rank`` the two ``layer_types`` take ANOTHER rule than a K/V
    # model's: BOTH kinds rotate, each at its own theta; a "full" layer is the
    # latent attention above behind an indexer — ``index_n_heads`` heads of
    # ``index_head_dim`` score every visible key from ONE cached index key a
    # token, and a query attends the ``index_topk`` best — and a "sliding"
    # layer is a second latent attention with the ``swa_*`` sizes under
    # ``sliding_window`` (the query's own position and the window - 1 before)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # which layers RUN an indexer (``glm_moe_dsa``'s ``indexer_types``), one of "full" | "shared"
    # for each layer: a "full" layer scores and selects as above; a "shared" one has NO indexer
    # and caches NO index key — it attends, over its OWN latents, the keys the nearest "full"
    # layer before it selected (the selection is carried from layer to layer). () = every
    # "full" entry of ``layer_types`` runs its own
    indexer_types: tuple[str, ...] = ()
    # the query is compressed too: h W_qa -> RMSNorm -> W_qb (0: one matrix)
    q_lora_rank: int = 0
    # the normed compressed query and latent are rescaled by (dim / rank)^0.5
    lora_rescale: bool = False
    # a sigmoid gate a head, from the layer's normed input, on the head's
    # output before the output projection
    attn_gate: bool = False
    swa_n_heads: int = 0
    swa_kv_lora_rank: int = 0
    swa_q_lora_rank: int = 0
    swa_qk_nope_dim: int = 0
    swa_qk_rope_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    # ---- a router that reads the layer's INPUT and a ReGLU expert
    # (``smallthinker``). Two properties of the MODEL, not knobs. What the
    # router reads: "ffn" — the tensor the experts are given, behind attention
    # (every model above) — or "layer": the residual x the layer is handed,
    # before any norm. The picks are then a function of the layer's input:
    # they are made in the FIRST position-wise region of a layer
    # (``_route_ahead``) and carried across the attention call to the experts,
    # which no longer route
    router_input: str = "ffn"
    # the activation on the gate plane of a gated (three-plane) MLP or expert:
    # "silu" (SwiGLU) | "relu" (ReGLU: down(relu(gate h) * up h))
    gate_act: str = "silu"
    # ---- LOOPED layers under a sandwich norm, behind an exit gate (``ouro``).
    # Three properties of the MODEL, not knobs. ``ut_steps`` U > 1: the SAME
    # ``n_layers`` layers run U times a token; every (pass, layer) keeps K/V of
    # its own — plane ``u * n_layers + l`` of a pool of U * ``n_layers`` planes
    # (``cache_spec``) — the model's final norm closes EVERY pass (its output is
    # the next pass's input), and an exit gate (``params["exit_gate"]``: d -> 1,
    # a sigmoid) says after each pass how much of the remaining probability
    # leaves there: the head reads the state of the first pass at which the
    # cumulated probability reaches ``exit_threshold`` (the last pass if none
    # does; at 1.0 that is the last wherever the sigmoid stays under 1). Every
    # pass is computed for every position, as the published forward does
    ut_steps: int = 1
    exit_threshold: float = 1.0
    # a norm on each sub-layer's OUTPUT beside the one on its input:
    # x + N(Attn(N(x))), then x + N(MLP(N(x))) (``attn_post_norm``, ``mlp_post_norm``)
    sandwich_norm: bool = False

    # a routed expert's form (no field: every LlamaConfig's is the gated SwiGLU of
    # three planes; ``models.nemotron_h``'s configuration names a two-plane one)
    expert_form = "swiglu"

    def __post_init__(self):
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps {self.ut_steps}: the layers run at least once")
        if (self.ut_steps > 1 or self.sandwich_norm) and (
                self.n_experts or self.kv_lora_rank or self.layer_types or self.parallel_block
                or self.tie_embeddings or self.qk_norm):
            raise NotImplementedError("looped layers and the sandwich norm: around a dense block "
                                      "of one kind with an untied head (forward_paged's scan)")
        if self.router_input not in ("ffn", "layer") or self.gate_act not in GATE_ACTS:
            raise ValueError(f"router_input 'ffn' | 'layer' and gate_act one of {sorted(GATE_ACTS)}, "
                             f"got {self.router_input!r}, {self.gate_act!r}")
        if self.router_input == "layer" and (not self.n_experts or self.kv_lora_rank
                                             or self.parallel_block):
            raise NotImplementedError("a router on the layer's input: routed experts behind "
                                      "forward_paged's own two regions (no latent forward, no "
                                      "parallel block)")
        if self.layer_types and (len(self.layer_types) != self.n_layers or
                                 set(self.layer_types) - {"sliding", "full"}):
            raise ValueError(f"layer_types: one of 'sliding' | 'full' for each of "
                             f"{self.n_layers} layers, got {self.layer_types}")
        if "sliding" in self.layer_types and self.sliding_window <= 0:
            raise ValueError("sliding layers need a sliding_window")
        if self.index_topk and not (self.kv_lora_rank and self.layer_types and self.index_n_heads
                                    and self.index_head_dim >= self.qk_rope_dim):
            raise ValueError("learned sparse attention: over a latent cache (kv_lora_rank), with "
                             "layer_types, index_n_heads and an index_head_dim that holds the "
                             "rotated width")
        if self.kv_lora_rank and self.layer_types and not self.index_topk:
            raise NotImplementedError("layer kinds inside a latent model are the selected-latent "
                                      "forward's (models.dots3): one with an indexer (index_topk)")
        if (self.q_lora_rank or self.attn_gate or self.lora_rescale) and not self.index_topk:
            raise NotImplementedError("a compressed query, a gate a head and the rank rescale "
                                      "are the selected-latent forward's (models.dots3: index_topk)")
        if self.index_topk and not self.q_lora_rank:
            raise NotImplementedError("the indexer (models.dots3) reads the compressed query: "
                                      "a q_lora_rank")
        if self.indexer_types and not (self.index_topk and len(self.indexer_types) == self.n_layers
                                       and not set(self.indexer_types) - {"full", "shared"}):
            raise ValueError(f"indexer_types: one of 'full' | 'shared' for each of {self.n_layers} "
                             f"layers behind an indexer (index_topk), got {self.indexer_types}")
        if self.indexer_types and self.indexer_types[0] != "full":
            raise ValueError("a 'shared' layer attends the keys the nearest 'full' layer before it "
                             "selected: the first layer runs an indexer")
        if self.indexer_types and set(self.layer_types) != {"full"}:
            raise NotImplementedError("a selection carried across layers: between latent layers that "
                                      "are all selected (layer_types 'full'); none carries one past "
                                      "a sliding layer")
        if self.index_topk and "sliding" in self.layer_types and not (
                self.swa_n_heads and self.swa_kv_lora_rank and self.swa_qk_nope_dim
                and self.swa_qk_rope_dim and self.swa_v_head_dim and self.swa_rope_theta):
            raise ValueError("sliding latent layers: the swa_* sizes and swa_rope_theta")
        held = self.experts_held
        if held and not self.first_expert + held <= self.n_experts:
            raise ValueError(f"experts held {self.first_expert}..{self.first_expert + held} "
                             f"of {self.n_experts}")
        if self.kv_lora_rank and not (self.qk_nope_dim and self.qk_rope_dim and self.v_head_dim
                                      and self.head_dim == self.qk_nope_dim + self.qk_rope_dim):
            raise ValueError("latent attention: qk_nope_dim, qk_rope_dim, v_head_dim and a "
                             "head_size of qk_nope_dim + qk_rope_dim")
        if self.first_dense_layers and not (self.n_experts and self.dense_ffn_dim
                                            and self.first_dense_layers <= self.n_layers):
            raise ValueError("leading dense layers: a dense_ffn_dim, before routed layers")
        if (self.first_dense_layers or self.router_bias) and not self.kv_lora_rank:
            raise NotImplementedError("leading dense layers and a biased router are "
                                      "models.mla's forward's: a latent model's alone")

    @property
    def head_dim(self) -> int:
        return self.head_size or self.dim // self.n_heads

    @property
    def n_held(self) -> int:
        """Experts whose planes this chip holds."""
        return self.experts_held or self.n_experts


# Parameter-count-faithful presets; vocab_size is overridden from the
# tokenizer at engine start.
# widest mid-sequence block the Pallas frontier-read kernel serves; wider
# blocks (suffix prefill buckets) take the exact XLA cache path. Covers
# grammar fast-forward steps (1 + chain width, default width 8).
MAX_BLOCK_DECODE_T = 16

PRESETS: dict[str, LlamaConfig] = {
    "test-tiny": LlamaConfig(dim=128, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=256, max_seq_len=256),
    "tinyllama-1.1b": LlamaConfig(dim=2048, n_layers=22, n_heads=32, n_kv_heads=4, ffn_dim=5632),
    "llama3-8b": LlamaConfig(
        dim=4096, n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336, rope_theta=500_000.0, max_seq_len=8192
    ),
    "llama3-70b": LlamaConfig(
        dim=8192, n_layers=80, n_heads=64, n_kv_heads=8, ffn_dim=28672, rope_theta=500_000.0, max_seq_len=8192
    ),
    # capacity_factor = E / K makes routing drop-free (capacity == token
    # count): inference quality never loses an expert contribution and
    # chunked prefill stays exactly consistent with per-token decode. The
    # cost is dense-dispatch FLOPs proportional to E instead of K at long
    # prefill T — a Pallas grouped-matmul is the optimization path there.
    "mixtral-test": LlamaConfig(
        dim=128, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=256, max_seq_len=256,
        n_experts=4, top_k=2, capacity_factor=2.0,
    ),
    "mixtral-8x7b": LlamaConfig(
        dim=4096, n_layers=32, n_heads=32, n_kv_heads=8, ffn_dim=14336,
        rope_theta=1_000_000.0, max_seq_len=8192, n_experts=8, top_k=2,
        capacity_factor=4.0,
    ),
}


# ---------------------------------------------------------------- params


def cache_planes(k: dict, v: dict, *, by_name: bool = False, slot_k=None, slot_v=None) -> dict:
    """A ``cache_spec`` in the one shape every family answers in (``models.family``
    has it): the block planes of the k and v pools as name -> (layers, *trailing),
    the per-slot planes beside them as name -> ((layers, *trailing), dtype)."""
    slot = {"k": slot_k or {}, "v": slot_v or {}}
    return {"planes": {"k": k, "v": v}, "slot_planes": slot, "by_name": by_name,
            "state_column": bool(slot["k"] or slot["v"])}


def cache_spec(cfg: LlamaConfig) -> dict:
    """K and V planes by head, every layer's — of every pass, where the layers
    run more than once (plane ``u * n_layers + l``)."""
    kv = {"kv": (cfg.ut_steps * cfg.n_layers, cfg.n_kv_heads, cfg.head_dim)}
    return cache_planes(kv, kv)


def gather_row_blocks(pool: jax.Array, plane, tbl: jax.Array) -> jax.Array:
    """The blocks ``tbl`` (rows, blocks) names in plane ``plane`` of a pool (planes, N, bs,
    ...), as (rows, blocks, bs, ...): ONE gather on (plane, block) in the pool as it is
    shaped. ``pool[plane][tbl]`` slices the WHOLE plane first — a ``dynamic_slice`` at a
    scan's index, a static one in an unrolled layer — and XLA writes that plane out in HBM
    before it gathers from it: 0.21 s of ``ouro_flood``'s traced stretch, 0.10 s of
    ``parse_flood``'s (ledger, PR 57), 10 of ``olmohybrid_flood``'s 51 ms call (PR 58)."""
    return pool[plane, tbl]


def init_params(cfg: LlamaConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Random init. Layer weights are stacked on a leading n_layers axis."""
    from .family import family  # the ONE hand-off to the sibling families (they import this module)

    owner = family(cfg).module
    if owner.__name__ != __name__:
        return owner.init_params(cfg, key, dtype)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    nq, nkv, L = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers

    def norm_init(*shape):
        return jnp.ones(shape, dtype=dtype)

    def w_init(key, *shape, scale=None):
        scale = scale if scale is not None else (shape[-2] ** -0.5)
        return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn_norm": norm_init(L, d),
        "wq": w_init(ks[0], L, d, nq * hd),
        "wk": w_init(ks[1], L, d, nkv * hd),
        "wv": w_init(ks[2], L, d, nkv * hd),
        "wo": w_init(ks[3], L, nq * hd, d),
    }
    if not cfg.parallel_block:  # there ``attn_norm`` feeds both halves
        layers["mlp_norm"] = norm_init(L, d)
    if cfg.qk_norm:
        layers.update({"q_norm": norm_init(L, nq * hd), "k_norm": norm_init(L, nkv * hd)})
    if cfg.sandwich_norm:
        layers.update({"attn_post_norm": norm_init(L, d), "mlp_post_norm": norm_init(L, d)})
    if cfg.n_shared_experts:
        # the shared experts side by side: ONE SwiGLU of n_shared * f columns
        # IS the sum of theirs (the mean is taken where it is added)
        ss = jax.random.split(jax.random.fold_in(k_layers, 1), 3)
        sf = cfg.n_shared_experts * f
        layers.update({"shared_gate": w_init(ss[0], L, d, sf), "shared_up": w_init(ss[1], L, d, sf),
                       "shared_down": w_init(ss[2], L, sf, d, scale=f ** -0.5)})
    if cfg.n_experts > 0:
        E, H = cfg.n_experts, cfg.n_held
        layers.update({
            # router stays small + unquantized; expert weights stack on the
            # experts HELD (all of them unless this is a chip's share)
            "router": w_init(ks[7], L, d, E),
            "moe_gate": w_init(ks[4], L, H, d, f),
            "moe_up": w_init(ks[5], L, H, d, f),
            "moe_down": w_init(ks[6], L, H, f, d),
        })
    else:
        layers.update({
            "w_gate": w_init(ks[4], L, d, f),
            "w_up": w_init(ks[5], L, d, f),
            "w_down": w_init(ks[6], L, f, d),
        })
    params = {
        "embed": w_init(k_embed, cfg.vocab_size, d, scale=d**-0.5),
        "layers": layers,
        "final_norm": norm_init(d),
    }
    if not cfg.tie_embeddings:  # tied: the head IS the embedding
        params["lm_head"] = w_init(k_head, d, cfg.vocab_size)
    if cfg.ut_steps > 1:  # float32, never quantised: d + 1 numbers
        params["exit_gate"] = {
            "w": jax.random.normal(jax.random.fold_in(k_head, 1), (d,), jnp.float32) * d ** -0.5,
            "b": jnp.zeros((), jnp.float32)}
    return params


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype=dtype), "v": jnp.zeros(shape, dtype=dtype)}


# ---------------------------------------------------------------- quantization


def _w(leaf):
    """Resolve a weight leaf to a dense array: raw array, or int8
    {"q", "s"} dequantized (materialized). Only for consumers that need a
    dense tensor — leaf-wise re-quantization (``ops.grouped_matmul`` takes
    the int8 leaf itself since PR 28: this call wrote and re-read the whole
    stacked expert tensor as bf16 before every kernel call).
    Matmul call sites must use :func:`_qe` instead: feeding a dequantized
    product into a dot makes the scale multiply the dot operand's producer
    and XLA lowers the whole matvec as a kLoop broadcast-multiply-reduce on
    the VPU (~5 f32 vector ops per weight) instead of an MXU dot — the
    round-5 on-chip HLO audit caught exactly this (bench_artifacts/
    decode_step_hlo.txt fused_computation.5; 1.69 ms/tok measured vs the
    1.18 ms/tok int8 weight-read floor)."""
    if isinstance(leaf, dict) and "q" in leaf:
        return leaf["q"].astype(jnp.bfloat16) * leaf["s"].astype(jnp.bfloat16)
    return leaf


def _qe(eq: str, x: jax.Array, leaf) -> jax.Array:
    """``einsum(eq, x, W)`` in f32 where W may be an int8 ``{"q", "s"}``
    leaf. The per-out-channel scale multiplies the OUTPUT —
    ``(x @ q) * s == x @ (q * s)`` exactly, because ``s`` (from
    ``quantize_leaf``'s axis=-2 max, shape ``(..., 1, out)``) is constant
    along the contraction axis and broadcasts against every output shape
    used here. The dot's weight operand therefore stays a bare
    ``convert(s8)->bf16``, which XLA folds into the MXU operand read; the
    scale costs O(out) work instead of O(in*out) per step."""
    if isinstance(leaf, dict) and "q" in leaf:
        out = jnp.einsum(eq, x, leaf["q"].astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        return out * leaf["s"].astype(jnp.float32)
    return jnp.einsum(eq, x, leaf, preferred_element_type=jnp.float32)


def quantize_leaf(w) -> dict:
    """One weight -> {"q": int8, "s": f32 per-out-channel}. Exposed so the
    pp engine can quantize leaf by leaf on already-sharded placements (a
    whole-tree quantize would ship 70B's full bf16 tree through one chip).
    Under jit over a GLOBAL sharded array the axis=-2 max is the global
    max (GSPMD inserts the cross-shard reduce), so per-shard quantization
    is bit-identical to whole-tree quantization."""
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 127.0
    s = jnp.where(s == 0.0, 1.0, s)
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def quantize_params(params: dict) -> dict:
    """Weight-only symmetric int8, per-output-channel scales. Norms and the
    embedding table (a gather, already cheap) stay in their original dtype;
    every matmul weight becomes {"q": int8, "s": f32} resolved by _w()."""
    if "layers" not in params:  # another family's tree: the module that owns it quantises it
        from .family import tree_owner

        return tree_owner(params).quantize_params(params)

    quant = quantize_leaf

    # matmul weights (dense w_* and stacked-expert moe_*) quantize;
    # norms and the tiny router stay full precision
    layers = lambda L: {k: (quant(v) if k.startswith(("w", "moe_", "shared_")) else v)
                        for k, v in L.items()}
    return {
        "embed": params["embed"],
        "layers": layers(params["layers"]),
        # a latent model's leading dense layers, stacked apart (models.mla)
        **({"dense_layers": layers(params["dense_layers"])} if "dense_layers" in params else {}),
        # attention leaves stacked by layer KIND (models.dots3)
        **{k: layers(params[k]) for k in ("attn_full", "attn_swa", "attn_shared") if k in params},
        "final_norm": params["final_norm"],
        **({"exit_gate": params["exit_gate"]} if "exit_gate" in params else {}),
        # a tied head: an int8 copy of the embedding, a scale a vocabulary row
        "lm_head": quant(_w(params["lm_head"]) if "lm_head" in params else params["embed"].T),
    }


# ---------------------------------------------------------------- ops


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * w


def layer_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """LayerNorm with a gain and no bias (cohere2)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _norm(x: jax.Array, w: jax.Array, cfg: "LlamaConfig") -> jax.Array:
    """The model's norm (``cfg.norm``)."""
    return (layer_norm if cfg.norm == "layer" else rms_norm)(x, w, cfg.norm_eps)


def rope_tables(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin for rotary embedding; positions (B, T) -> (B, T, hd//2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, T, H, hd); rotate pairs (split-half convention)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def apply_rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, T, H, hd); rotate the pairs (x[2i], x[2i+1]) by angle i
    (``rope_gptj``): the same tables, another pairing of the lanes. Written
    on whole 128-lane rows — each lane times its pair's cosine, plus its
    PARTNER (the neighbour lane, by two lane rotations and a select) times
    the signed sine — because a (..., hd/2, 2) view puts 2 elements in a
    128-lane tile."""
    xf = x.astype(jnp.float32)
    c = jnp.repeat(cos, 2, axis=-1)[:, :, None, :]
    s = jnp.repeat(sin, 2, axis=-1)[:, :, None, :]
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1), jnp.roll(xf, 1, axis=-1))
    return (xf * c + partner * s).astype(x.dtype)


def bound_window(cfg: "LlamaConfig") -> int | None:
    """The window a sliding layer's mask is given, or None where the mask
    can never be false: no position of a sequence of at most ``max_seq_len``
    <= ``sliding_window`` tokens is a window away from a query after it, so
    the layer IS a full causal one there (an identity, not a tolerance). The
    engine fixes ``max_seq_len`` to its ``max_len`` once, where it is built,
    and serves such a model's sliding layers through the unbounded paths —
    the block kernel's common pass among them."""
    return cfg.sliding_window if 0 < cfg.sliding_window < cfg.max_seq_len else None


def layer_kinds(cfg: "LlamaConfig") -> tuple[tuple[bool, int | None], ...]:
    """(rotates, window) of every layer by index. A Llama-family model:
    every layer rotates and sees everything."""
    kinds = {"sliding": (True, bound_window(cfg)), "full": (False, None)}
    return tuple(kinds[t] for t in cfg.layer_types) or ((True, None),) * cfg.n_layers


def _attend(q, k_cache, v_cache, q_positions, kv_len_mask, window: int | None = None):
    """GQA attention of q (B,T,nq,hd) against the full cache (B,S,nkv,hd).

    kv_len_mask: (B, S) bool — which cache slots hold valid keys.
    Causality: key_position <= query_position, tracked via positions stored
    implicitly by slot index (slot i holds the token at position i).
    ``window``: a query sees its last ``window`` positions, its own among them.
    """
    B, T, nq, hd = q.shape
    S = k_cache.shape[1]
    nkv = k_cache.shape[2]
    group = nq // nkv

    qg = q.reshape(B, T, nkv, group, hd)
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k_cache, preferred_element_type=jnp.float32)
    scores = scores * (hd**-0.5)

    slot_pos = jnp.arange(S)[None, None, :]  # (1, 1, S)
    causal = slot_pos <= q_positions[:, :, None]  # (B, T, S)
    if window is not None:
        causal = causal & (slot_pos > q_positions[:, :, None] - window)
    mask = causal & kv_len_mask[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, nq * hd).astype(q.dtype)


def _identity_cs(x, name):
    return x


def _project_qkv(p, x, cfg: LlamaConfig, cs=_identity_cs, n_heads: int | None = None,
                 n_kv_heads: int | None = None, u=None):
    """A layer's attn-norm -> q/k/v projections (-> q/k norm), each still
    (B, T, heads * head_dim): the position-wise part of the front half, which
    asks nothing of B and T (``forward_paged`` runs it on a block's real
    positions, packed). ``_layer_qkv`` has the arguments."""
    if cfg.kv_lora_rank:  # q, k and v of n_heads x head_dim: a latent model projects to a
        # latent and a shared key, for forward_paged alone
        from .family import family

        family(cfg).refuse("dense_cache")
    nq = n_heads if n_heads is not None else cfg.n_heads
    nkv = n_kv_heads if n_kv_heads is not None else cfg.n_kv_heads
    with jax.named_scope("layer/attn_qkv"):
        h = _norm(x, p["attn_norm"], cfg) if u is None else u
        h = cs(h, "act")
        q = _qe("btd,dh->bth", h, p["wq"]).astype(x.dtype)
        k = _qe("btd,dh->bth", h, p["wk"]).astype(x.dtype)
        v = _qe("btd,dh->bth", h, p["wv"]).astype(x.dtype)
        if cfg.qk_norm:
            if (nq, nkv) != (cfg.n_heads, cfg.n_kv_heads):
                raise NotImplementedError(
                    "qk_norm normalises the whole projected vector; a "
                    "tensor-parallel shard of the heads holds only part of it")
            with jax.named_scope("qk_norm"):
                q = rms_norm(q, p["q_norm"], cfg.norm_eps)
                k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _rotate_heads(q, k, v, cfg: LlamaConfig, cos, sin, cs=_identity_cs, rotate: bool = True):
    """Flat q/k/v (B, T, heads * head_dim) -> the (B, T, heads, head_dim) the
    attention calls and the K/V write keep, q and k rotated."""
    B, T = q.shape[:2]
    with jax.named_scope("layer/attn_qkv"):
        q = cs(q.reshape(B, T, -1, cfg.head_dim), "heads")
        k = cs(k.reshape(B, T, -1, cfg.head_dim), "kv_heads")
        v = cs(v.reshape(B, T, -1, cfg.head_dim), "kv_heads")
        if not rotate:
            return q, k, v
        rope = apply_rope_interleaved if cfg.rope_interleaved else apply_rope
        return rope(q, cos, sin), rope(k, cos, sin), v


def _layer_qkv(p, x, cfg: LlamaConfig, cos, sin, cs=_identity_cs,
               n_heads: int | None = None, n_kv_heads: int | None = None,
               rotate: bool = True, u=None):
    """Shared decoder-layer front half: attn-norm -> q/k/v projections ->
    head reshape -> RoPE. The ONE copy of this math for forward /
    forward_paged / pipeline / longctx (they differ only in how KV is
    written and attended, never in the projections). ``n_heads`` /
    ``n_kv_heads`` override the config's counts for tensor-parallel LOCAL
    shards inside shard_map (pipeline.pp_tp_forward_cached passes
    cfg.n_heads // tp etc; head_dim is unchanged). ``rotate`` False: a layer
    that carries no positions. ``u``: the layer's input already normed (a
    parallel block's one norm feeds the expert layer too)."""
    q, k, v = _project_qkv(p, x, cfg, cs, n_heads, n_kv_heads, u)
    return _rotate_heads(q, k, v, cfg, cos, sin, cs, rotate)


# what a routed forward counts (summed over layers by the forwards, over
# forwards by the chunk loops; ``scheduler`` publishes them as ``moe.<name>``)
MOE_STATS = ("assigned_rows", "padded_rows", "experts_touched", "load_max")
# a chip's SHARE of the experts counts one more: of the rows the router
# assigned, those that fell on an expert held here (the other four are then
# over the held experts: rows computed, held experts with a row, the busiest)
MOE_SHARE_STATS = MOE_STATS + ("local_rows",)


# what a forward of a model whose window BINDS counts beside ``ops.ATTN_STATS``
# (published as ``attn.<name>``, as ``models.sambay`` publishes its own): the
# row-blocks inside its rows' windows (what a row attends in a windowed layer,
# in a walk of its own or through the common range), those the same rows hold up
# to their frontier, and those the common range took off the rows' own walks
# (riders x the range's blocks), each summed over the windowed layers
WINDOW_STATS = ("window_blocks_walked", "window_blocks_held", "window_common_row_blocks")


def moe_stat_names(cfg) -> tuple[str, ...]:
    """What a routed forward of this model counts, in order."""
    return MOE_SHARE_STATS if cfg.experts_held else MOE_STATS


def _moe_stats(counts, computed_rows, assigned=None) -> jax.Array:
    """(4,) int32 in ``MOE_STATS`` order from one layer's per-expert row
    counts and the rows its dispatch computed (padding included); with
    ``assigned`` (a share: every row the router assigned, wherever its expert
    lives) (5,) in ``MOE_SHARE_STATS`` order."""
    local = jnp.sum(counts)
    return jnp.stack([local if assigned is None else jnp.asarray(assigned, jnp.int32),
                      jnp.asarray(computed_rows, jnp.int32), jnp.sum(counts > 0),
                      jnp.max(counts), *(() if assigned is None else (local,))]).astype(jnp.int32)


def moe_row_tile(assignments: int, n_experts: int) -> int:
    """Row tile of the grouped dispatch from the (static) assignment count
    and the ROUTER's width (an expert's mean run is the same on a chip that
    holds a share of them):
    the smallest power of two ABOVE the mean run of an expert, between one
    bf16 sublane tile (16) and the MXU's 128 rows — most experts then fill
    one tile, and the kernel pays by the tile (each converts and latches
    the whole weight plane) while every row of padding is written and read
    back. On the chip at OLMoE's widths (one layer at 288 tokens, ms;
    PERF.md section 6, PR 28): 1.16 / 0.95 / 0.76 / 0.92 at 16 / 32 / 64 / 128."""
    mean = -(-max(assignments, 1) // n_experts)
    return min(128, max(16, 1 << mean.bit_length()))


_EXPERT_LEAVES = ("moe_gate", "moe_up", "moe_down")


def _router_kw(p, cfg) -> dict:
    """What a router that selects by score + bias and scales its gates adds
    to the routing call; nothing for every other model (their programs'
    text is what it was)."""
    kw = {}
    if cfg.router_bias:
        kw["bias"] = p["router_bias"]
    if cfg.router_scale != 1.0:
        kw["scale"] = cfg.router_scale
    return kw


# the leaves a layer's MLP reads: everything under ``layer/ffn`` but its norm
_FFN_LEAVES = ("w_gate", "w_up", "w_down", "router", "shared_gate", "shared_up", "shared_down",
               *_EXPERT_LEAVES)


def _scan_and_whole(layers: dict, cfg: LlamaConfig, packed: bool = False,
                    regions: bool = False) -> tuple[dict, dict]:
    """(the stacked leaves a layer scan slices, those its body takes WHOLE).
    The grouped dispatch's kernel picks a layer's expert planes out of the
    stacked (L, E, d, f) leaves itself, by the layer index in its scalar
    prefetch: a scan's slice of them, handed to a custom call, is a copy of
    134 MB three times a layer (``ops.grouped_matmul``). Where the MLP runs
    ``packed`` every leaf it reads stays whole likewise and is sliced INSIDE
    the branch that reads it (``_ffn``): a slice made before a conditional
    is an operand of it, written out and read back, 176 MB a Mistral layer.
    Where both position-wise ``regions`` of a layer run packed
    (``forward_paged``) that is every leaf: the scan slices none."""
    grouped = cfg.n_experts > 0 and cfg.moe_impl == "grouped"
    held = tuple(k for k in (layers if regions else _FFN_LEAVES if packed else
                             _EXPERT_LEAVES if grouped else ()) if k in layers)
    return ({k: v for k, v in layers.items() if k not in held}, {k: layers[k] for k in held})


def _running_count(hot: jax.Array) -> jax.Array:
    """(A, E) bool -> (A, E) int32: how many of rows 0..i hold True, column
    by column. A prefix sum down 2304 rows is a 100 us reduce-window on the
    TPU (as much as two expert planes); in blocks of 128 rows it is one
    lower-triangular matmul on the MXU (0/1 in bf16, float32 sums: exact)
    and a prefix sum over the few block totals."""
    A, E = hot.shape
    blk = 128
    h = jnp.pad(hot, ((0, -A % blk), (0, 0))).astype(jnp.bfloat16).reshape(-1, blk, E)
    tril = jnp.tril(jnp.ones((blk, blk), jnp.bfloat16))
    within = jnp.einsum("ij,bje->bie", tril, h, preferred_element_type=jnp.float32).astype(jnp.int32)
    totals = within[:, -1, :]  # (blocks, E)
    before = jnp.cumsum(totals, axis=0) - totals
    return (within + before[:, None, :]).reshape(-1, E)[:A]


# the activation between a two-plane expert's up and down projections, by the
# configuration's ``expert_form`` ("swiglu": the gated three-plane expert)
EXPERT_ACTS = {"relu2": lambda u: jnp.square(jax.nn.relu(u)), "silu": jax.nn.silu}


def _gate_act(cfg):
    """The activation on the gate plane of ``cfg``'s gated MLPs and experts
    (another family's record that names none: SwiGLU's)."""
    return GATE_ACTS[getattr(cfg, "gate_act", "silu")]


def _route_ahead(p, x, cfg: LlamaConfig):
    """A layer's picks from its INPUT ``x`` (B, T, d), the residual before any
    norm (``router_input`` "layer") -> (eids (B, T, K) int32, gates (B, T, K)
    float32), by the model's own rule (``route_topk_flat``: the same selection
    as a router behind attention). Position-wise: a packed region runs it on
    the real positions. The expert layer is handed them (``picks``) and does
    not route. A scope of its own: ``layer/moe/route_ahead``."""
    from .moe import route_topk_flat

    B, T, d = x.shape
    with jax.named_scope("layer/moe/route_ahead"):
        eids, gates = route_topk_flat(p["router"], x.reshape(B * T, d), cfg.n_experts, cfg.top_k,
                                      cfg.norm_topk, cfg.router_fn, **_router_kw(p, cfg))
        return eids.reshape(B, T, -1), gates.reshape(B, T, -1)


def _picks_or_refuse(cfg: LlamaConfig, picks):
    """The expert layer of a model whose router reads the layer's input is
    handed its picks; one that reads its own input is handed none."""
    if (getattr(cfg, "router_input", "ffn") == "layer") != (picks is not None):
        raise NotImplementedError(
            "router_input 'layer': the caller routes ahead, on the layer's input "
            "(llama.forward_paged does), and hands the expert layer its picks")


def _moe_ffn_grouped(p, h, cfg: LlamaConfig, lat=None, n_rows=None, picks=None):
    """Grouped-matmul MoE FFN: assignments group by expert, each expert's run
    pads to a row-tile multiple, and ``ops.grouped_matmul`` streams one
    weight plane per expert that has rows — int8 as served — so FFN FLOPs
    are ∝ T·K (plus under one tile of padding per expert) and weight bytes
    ∝ the experts TOUCHED, against the dense dispatch's T·E and E. The
    single-device path (a bare pallas_call under GSPMD would replicate its
    operands); a mesh keeps the dense dispatch (``cfg.moe_impl``). ``p`` holds one layer's
    expert leaves, or — from the layer scans — the stacked ones and the
    layer's index under ``"layer"``. The configuration says what an expert
    is (``expert_form``: SwiGLU's three planes, or two with an activation of
    ``EXPERT_ACTS`` between them) and the caller what is DISPATCHED: ``lat``
    (B, T, w), one row a position, where the experts live at another width than
    the router reads (a latent; the sum comes back at that width), else ``h``
    itself; with ``picks`` (eids, gates), each (B, T, K), that the router read
    ANOTHER tensor, earlier in the layer (``_route_ahead``): nothing is routed
    here; and with ``n_rows`` () that only the first ``n_rows`` of the B * T
    rows are real (a packed region's: ``FfnPack.n_rows``): a filler row's picks
    fall on no expert — no run, no tile, no weight fetch, as a pick held
    elsewhere — and every count is the real rows' alone. -> (out,
    ``_moe_stats``: a share's five, else four, whatever ``n_rows``)."""
    from ..ops.grouped_matmul import grouped_matmul
    from .moe import route_topk_flat

    B, T, d = h.shape
    E, K = cfg.n_experts, cfg.top_k
    # the experts held: all E, or a chip's share of them — ids ``first_expert``
    # onward. An assignment to an expert held elsewhere matches no column of
    # the one-hot below: it counts in no run, pads nothing, names no tile
    # (no weight fetch), takes no row, and adds nothing in the combine. Its
    # gate stays what the router gave it (normalised over all K chosen).
    # ``absent``: some pick may match no column — a share's, a filler row's
    H, share = cfg.n_held, cfg.n_held < E
    absent = share or n_rows is not None
    Tt = B * T
    A = Tt * K
    x2 = h.reshape(Tt, d)
    if picks is not None:
        eids, gates = picks[0].reshape(Tt, K), picks[1].reshape(Tt, K)
    else:
        with jax.named_scope("router"):
            eids, gates = route_topk_flat(p["router"], x2, E, K, cfg.norm_topk,
                                          cfg.router_fn, **_router_kw(p, cfg))  # (Tt, K)
    if lat is not None:
        d = lat.shape[-1]
        x2 = lat.reshape(Tt, d)

    with jax.named_scope("dispatch"):
        # compares and running counts over an (A, E) one-hot, no sort and
        # no per-element gather: on the TPU a 2304-element gather or scatter
        # costs as much as a whole expert plane (PERF.md section 6, PR 28)
        flat_e = eids.reshape(-1)  # assignment j = t*K + k
        if share:
            flat_e = flat_e - cfg.first_expert
        if n_rows is not None:  # a filler row's picks: no column of the one-hot
            flat_e = jnp.where(jnp.arange(A, dtype=jnp.int32) // K < n_rows, flat_e, -1)
        hot = flat_e[:, None] == jnp.arange(H, dtype=jnp.int32)[None, :]  # (A, H)
        tm = moe_row_tile(A, E)
        counts = jnp.sum(hot, axis=0, dtype=jnp.int32)
        padded = ((counts + tm - 1) // tm) * tm
        ends = jnp.cumsum(padded)
        offsets = ends - padded
        # row of assignment j: its expert's padded offset plus its rank in
        # the expert's run (assignment order: expert-major, token-stable)
        rank = _running_count(hot) - 1  # (A, H)
        dest = jnp.sum(jnp.where(hot, rank + offsets[None, :], 0), axis=1)  # (A,)
        # static bound on sum(padded): under one tile of padding for each
        # expert that can hold a row; the tiles past the real ones are
        # skipped by the kernel and never gathered back
        n_static = -(-(A + min(H, A) * (tm - 1)) // tm)
        # rows move by GATHER (an int32 scatter builds the index): row r of
        # the padded layout reads token row_tok[r], padding reads a zero row
        row_tok = jnp.full((n_static * tm,), Tt, jnp.int32)
        if absent:
            local = jnp.any(hot, axis=1)  # (A,) the expert is held here, the row real
            row_tok = row_tok.at[jnp.where(local, dest, n_static * tm)].set(
                jnp.arange(A, dtype=jnp.int32) // K, mode="drop")
        else:
            row_tok = row_tok.at[dest].set(jnp.arange(A, dtype=jnp.int32) // K)
        xs = jnp.concatenate([x2, jnp.zeros((1, d), x2.dtype)])[row_tok]
        n_tiles = ends[-1] // tm
        tile_start = jnp.arange(n_static, dtype=jnp.int32) * tm
        tile_expert = jnp.minimum(
            jnp.sum(tile_start[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), H - 1)
        # skipped tiles name the last real tile's expert: no weight fetch
        last = jnp.sum(jnp.where(jnp.arange(n_static) == n_tiles - 1, tile_expert, 0))
        tile_expert = jnp.where(jnp.arange(n_static) < n_tiles, tile_expert, last)

    with jax.named_scope("experts"):
        li = p.get("layer")
        if cfg.expert_form == "swiglu":
            gate_s = grouped_matmul(xs, p["moe_gate"], tile_expert, n_tiles, li, tm=tm)
            up_s = grouped_matmul(xs, p["moe_up"], tile_expert, n_tiles, li, tm=tm)
            act = (_gate_act(cfg)(gate_s.astype(jnp.float32))
                   * up_s.astype(jnp.float32)).astype(h.dtype)
        else:
            up_s = grouped_matmul(xs, p["moe_up"], tile_expert, n_tiles, li, tm=tm)
            act = EXPERT_ACTS[cfg.expert_form](up_s.astype(jnp.float32)).astype(h.dtype)
        down = grouped_matmul(act, p["moe_down"], tile_expert, n_tiles, li, tm=tm)  # (M_pad, d)

    with jax.named_scope("combine"):
        # assignment j sits at row dest[j]; a token's K rows are gathered
        # and summed under its gates (no scatter-add of (A, d) rows)
        rows = down[dest].reshape(Tt, K, d).astype(jnp.float32)
        if absent:  # an absent pick's ``dest`` is 0: some other row, or one never written
            rows = jnp.where(local.reshape(Tt, K, 1), rows, 0.0)
        out = jnp.sum(rows * gates[:, :, None], axis=1)
    return (out.astype(h.dtype).reshape(B, T, d),
            _moe_stats(counts, ends[-1], (A if n_rows is None else n_rows * K) if share else None))


def _moe_ffn_dense(p, h, cfg: LlamaConfig, lat=None, picks=None):
    """Dense-dispatch MoE FFN (models.moe.route_topk): expert choice becomes
    one-hot einsums with static shapes. EP sharding happens declaratively:
    the stacked (E, ...) expert weights shard E over the mesh's tp axis
    (parallel.mesh.param_shardings) and XLA partitions the dispatch/combine
    einsums, inserting one psum. Every expert computes over the full
    capacity, so FLOPs and weight bytes are ∝ E whatever was routed: the
    meshed path, and the exact twin the grouped path is tested against.
    -> (out, ``_moe_stats``)."""
    from .moe import dispatch_topk, moe_capacity, route_topk

    B, T, d = h.shape
    x2 = h.reshape(B * T, d)
    # serving is drop-free by construction: cf >= E/K makes capacity cover
    # every routed token even under total routing skew, so bucketed-prefill
    # pad tokens can never crowd out real ones and chunked prefill stays
    # token-exact with per-token decode (a hand-built config with a smaller
    # cf silently dropped expert contributions — round-2 advisor finding).
    # The standalone EP layer (parallel.expert) keeps drop semantics; this
    # clamp governs the served decoder only, and says so when it fires
    # (warn runs at trace time: once per compiled shape, not per step)
    cf = max(cfg.capacity_factor, cfg.n_experts / cfg.top_k)
    if cf != cfg.capacity_factor:
        import warnings

        warnings.warn(
            f"MoE serving path clamped capacity_factor {cfg.capacity_factor} -> "
            f"{cf} (= E/K) to stay drop-free; set capacity_factor >= "
            f"{cfg.n_experts}/{cfg.top_k} in the config to silence this",
            stacklevel=2,
        )
    C = moe_capacity(B * T, cfg.n_experts, cfg.top_k, cf)
    with jax.named_scope("router"):
        if picks is not None:  # routed ahead, on the layer's input: the slots alone
            dispatch, combine = dispatch_topk(picks[0].reshape(B * T, -1),
                                              picks[1].reshape(B * T, -1), cfg.n_experts, C)
        else:
            dispatch, combine = route_topk(p["router"], x2, cfg.n_experts, cfg.top_k, C,
                                           cfg.norm_topk, cfg.router_fn, **_router_kw(p, cfg))
        if cfg.experts_held:  # a chip's share: the held experts' columns alone
            held = slice(cfg.first_expert, cfg.first_expert + cfg.experts_held)
            assigned = jnp.sum(dispatch).astype(jnp.int32)
            dispatch, combine = dispatch[:, held], combine[:, held]
    if lat is not None:
        d = lat.shape[-1]
        x2 = lat.reshape(B * T, d)
    with jax.named_scope("dispatch"):
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(h.dtype), x2)  # (E, C, d)
    with jax.named_scope("experts"):
        if cfg.expert_form == "swiglu":
            gate = _qe("ecd,edf->ecf", xe, p["moe_gate"])
            up = _qe("ecd,edf->ecf", xe, p["moe_up"])
            a = (_gate_act(cfg)(gate) * up).astype(h.dtype)
        else:
            a = EXPERT_ACTS[cfg.expert_form](_qe("ecd,edf->ecf", xe, p["moe_up"])).astype(h.dtype)
        down = _qe("ecf,efd->ecd", a, p["moe_down"]).astype(h.dtype)
    with jax.named_scope("combine"):
        out = jnp.einsum("tec,ecd->td", combine.astype(h.dtype), down).reshape(B, T, d)
    counts = jnp.sum(dispatch, axis=(0, 2)).astype(jnp.int32)
    return out, _moe_stats(counts, cfg.n_held * C, assigned if cfg.experts_held else None)


def _moe_ffn(p, h, cfg: LlamaConfig, lat=None, n_rows=None, picks=None):
    """Top-k routed expert FFN over (B, T, d) hidden states -> (out, stats).
    ``cfg.moe_impl`` names the dispatch; the engine that serves the model
    resolved "auto" where it was built (a single device takes the grouped
    kernel at every token count — PERF.md section 6, PR 28: it wins from 128
    tokens up and ties at a suffix prefill's 32-64 rows — a mesh the dense
    einsums, its experts sharded over tp)."""
    _picks_or_refuse(cfg, picks)
    if cfg.moe_impl == "grouped":
        return _moe_ffn_grouped(p, h, cfg, lat, n_rows, picks)
    return _moe_ffn_dense(p, h, cfg, lat, picks)  # (it computes every row: a filler's is unread)


def _swiglu(p, h, names, cs=_identity_cs, act="silu"):
    """down(act(gate h) * up h) over the three leaves ``names``, float32
    (``act``: the model's ``gate_act``; SwiGLU by default)."""
    gate = _qe("btd,df->btf", h, p[names[0]])
    up = _qe("btd,df->btf", h, p[names[1]])
    act = cs((GATE_ACTS[act](gate) * up).astype(h.dtype), "ffn")
    return _qe("btf,fd->btd", act, p[names[2]])


# what a forward whose position-wise work may run PACKED counts (ISSUE 37, 41;
# summed over a chunk's forwards by ``paged_chunk_decode_loop``, published as
# ``ffn.<name>``): whether it took the packed branches — one predicate decides
# every region of every layer — and the rows those regions computed
FFN_STATS = ("forwards_packed", "rows")


# what every forward that writes K/V (or a latent cache's two planes) counts, published as
# ``kv.<name>``: the rows ``write_rows`` moved into each of the two pools, summed over the
# cache layers — ``B * T`` a layer where every position is written, the walk's tiles where
# the forward is told its rows' real positions
KV_STATS = ("rows_written",)


class FfnPack(NamedTuple):
    """The real positions of a (B, T) block, packed: built ONCE a forward
    from ``n_real`` (row b's real positions are ``t < n_real[b]``), used by
    every packed region of every layer. ``idx`` (P,) names the position (into
    the B * T) each packed slot holds; ``inv`` (B, T) the slot each position
    reads back: its own if it is real, else the slot of ITS ROW's last real
    position — a padded position of a fast-forward block is a copy of that
    one and writes the same K/V index, so the values must stay equal — and
    any slot for a row with none (its writes are parked, its logits unread).
    ``fits``: all real positions have a slot; where they do not, the forward
    runs at its full width, as it always did. ``n_rows``: the real positions —
    rows in order, so where they fit the first ``n_rows`` slots hold them and
    every slot behind is filler, which a routed block sends to no expert."""

    idx: jax.Array
    inv: jax.Array
    fits: jax.Array
    n_rows: jax.Array

    @property
    def stats(self) -> jax.Array:
        """``FFN_STATS`` of this forward, (2,) int32."""
        full = self.inv.size
        return jnp.stack([self.fits.astype(jnp.int32),
                          jnp.where(self.fits, self.idx.shape[0], full).astype(jnp.int32)])

    def rows(self, block: jax.Array) -> jax.Array:
        """(B, T, ...) -> (1, P, ...): each slot's position."""
        return block.reshape(-1, *block.shape[2:])[self.idx][None]

    def block(self, rows: jax.Array) -> jax.Array:
        """(1, P, ...) -> (B, T, ...): each position's slot."""
        return rows[0][self.inv]


def ffn_pack_index(n_real: jax.Array, T: int, P: int) -> FfnPack:
    """``FfnPack`` of a (B, T) block at P slots, rows in order. No scatter and
    no prefix sum over positions: a row's slots start where the rows before
    it end, and a slot finds its row by counting the rows that end at or
    before it (B and P are small)."""
    n = jnp.clip(n_real.astype(jnp.int32), 0, T)
    B = n.shape[0]
    ends = jnp.cumsum(n)
    starts = ends - n
    t = jnp.minimum(jnp.arange(T, dtype=jnp.int32)[None, :], jnp.maximum(n[:, None] - 1, 0))
    inv = jnp.minimum(starts[:, None] + t, P - 1)
    slot = jnp.arange(P, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(ends[None, :] <= slot[:, None], axis=1, dtype=jnp.int32), B - 1)
    idx = row * T + jnp.clip(slot - starts[row], 0, T - 1)  # past the last real slot: unread
    n_rows = ends[-1]
    return FfnPack(idx, inv, n_rows <= P, n_rows)


class RowTiles(NamedTuple):
    """The real positions of a (B, T) block packed at ALL its P = B * T slots
    — they always fit: no ``fits``, no whole-width branch — and walked in
    tiles of ``tile`` rows, as many as hold real positions (``n_tiles`` >= 1;
    a fast-forward block of 32 rows: one tile of 96 in ~99 % of forwards).
    ``idx`` (P,) and ``inv`` (B, T) as ``FfnPack``'s, but a slot past the
    last real one (``last``) REPEATS it: it computes that position's values
    again and writes them to the same cache index — so whatever a walk reads
    by slot must hold the last real slot's value in every slot behind it
    (``cut`` of what a walk wrote does; of anything else read ``slots``).
    Where ``tile`` does not divide P the last tile starts at P - ``tile``
    (``cut``, ``put`` and ``slots`` clamp alike) and computes some rows
    twice: a walk reads what no tile of it writes."""

    idx: jax.Array
    inv: jax.Array
    n_tiles: jax.Array
    last: jax.Array
    tile: int

    @property
    def stats(self) -> jax.Array:
        """``FFN_STATS`` of this forward: whether ONE tile held it, and the
        rows its tiles computed."""
        return jnp.stack([(self.n_tiles == 1).astype(jnp.int32), self.n_tiles * self.tile])

    def cut(self, a: jax.Array, i) -> jax.Array:
        """Tile i of a (P, ...) array."""
        return jax.lax.dynamic_slice_in_dim(a, i * self.tile, self.tile)

    def put(self, buf: jax.Array, rows: jax.Array, i) -> jax.Array:
        return jax.lax.dynamic_update_slice_in_dim(buf, rows, i * self.tile, 0)

    def slots(self, i) -> jax.Array:
        """The slots tile i reads of a (P, ...) array that only the real
        positions' slots were written of: (tile,) int32, the last real slot
        for every slot behind it."""
        first = jnp.minimum(i * self.tile, self.idx.shape[0] - self.tile)
        return jnp.minimum(first + jnp.arange(self.tile, dtype=jnp.int32), self.last)


def row_tiles(n_real: jax.Array, T: int, tile: int) -> RowTiles:
    """``RowTiles`` of a (B, T) block whose row b's real positions are
    ``t < n_real[b]``, rows in order (``ffn_pack_index`` at P = B * T)."""
    P = n_real.shape[0] * T
    order = ffn_pack_index(n_real, T, P)
    n_pos = jnp.sum(jnp.clip(n_real.astype(jnp.int32), 0, T))
    last = jnp.maximum(n_pos - 1, 0)
    idx = order.idx[jnp.minimum(jnp.arange(P, dtype=jnp.int32), last)]
    return RowTiles(idx, order.inv, jnp.maximum(-(-n_pos // tile), 1), last, min(tile, P))


def write_tile(rows: int) -> int:
    """The rows a step of ``write_rows``' walk moves, of a block of ``rows`` = B * T
    positions: a static function of the shape. A fast-forward block holds 1.4-1.7 real
    positions of 9 a live row, so a sixth of the block (48 of 288, 16 of 72) holds a
    forward's real rows in most forwards and the chunk's first forward walks a few. A
    step costs ~1.3 us and ~0.18 us a row (a gather and a scatter, K and V, at 8 x 128)
    where the scatter of all 288 rows costs 51: one tile 9.7 us, all six 57 (my chip
    runs, PR 60; a tile that does not divide the block walks its last rows twice: 64 of
    288 costs 61 at six tiles' work in five). Where the tile is the whole block nothing
    walks."""
    return min(rows, max(16, -(-rows // 48) * 8))


def write_walk(n_real: jax.Array | None, T: int, where: tuple) -> tuple[RowTiles | None, tuple]:
    """Once a forward, for ``write_rows``: the (B, T) block's real positions (row b's
    are ``t < n_real[b]``; a row that is not live has none) in tiles, and ``where`` —
    the pool indices of every position, (B, T) each — in the tiles' packed order: a
    slot behind the last real one names that one's index again. A forward that is
    told no real positions, and a block no larger than a tile, keep the scatter:
    (None, ``where``)."""
    if n_real is None or write_tile(n_real.shape[0] * T) >= n_real.shape[0] * T:
        return None, where
    tiles = row_tiles(n_real, T, write_tile(n_real.shape[0] * T))
    return tiles, tuple(w.reshape(-1)[tiles.idx] for w in where)


def rows_written(tiles: RowTiles | None, positions: jax.Array) -> jax.Array:
    """``kv.rows_written`` of ONE cache layer: the rows ``write_rows`` moves into
    each of its two pools — the walk's tiles, or every position of the block."""
    return jnp.int32(positions.size) if tiles is None else tiles.n_tiles * tiles.tile


def write_rows(pool_a: jax.Array, pool_b: jax.Array, plane, a: jax.Array, b: jax.Array,
               where: tuple, tiles: RowTiles | None = None) -> tuple[jax.Array, jax.Array]:
    """A layer's cache write: the block's rows ``a`` / ``b`` (B, T, ...) into plane
    ``plane`` of their pools at ``where`` — the index arrays behind the plane, in the
    pool's own indexing (``(idx,)`` on a flat view, ``(block, offset)`` on a pool as
    it is shaped). Without ``tiles`` every position is written, ``where`` (B, T) each:
    ONE scatter a pool, which costs by the ROW on the chip (~0.1 us: 25-31 us at 288
    rows, 8 at 72; ledger, PR 59), whatever the row holds. With ``tiles``
    (``write_walk``: ``where`` in their packed order) the write walks the tiles that
    hold real positions and moves nothing else: a position behind a row's real ones
    only wrote the last real one's index again, a row that is not live only its
    trash slot. The two pools are the loop's carry — a ``while``'s carry aliases in
    place; a conditional around a pool copies it out."""
    if tiles is None:
        return pool_a.at[(plane, *where)].set(a), pool_b.at[(plane, *where)].set(b)
    a, b = (r.reshape(-1, *r.shape[2:]) for r in (a, b))

    def step(i, pools):
        src = tiles.cut(tiles.idx, i)
        at = (plane, *(tiles.cut(w, i) for w in where))
        return tuple(p.at[at].set(r[src]) for p, r in zip(pools, (a, b)))

    return jax.lax.fori_loop(0, tiles.n_tiles, step, (pool_a, pool_b))


def conv_window(tail: jax.Array, x: jax.Array, n_real: jax.Array, taps):
    """What a causal convolution carries between forwards, for every family that
    keeps one (``models.sambay``, ``nemotron_h``, ``olmo_hybrid``, ``lfm2``).
    ``tail`` (B, K-1, w): a row's inputs before position 0; ``x`` (B, T, w): this
    block's; ``taps``: the convolution itself, handed the padded inputs (B,
    K-1+T, w) — the filter, bias and activation are the model's. -> (what
    ``taps`` gives, the K - 1 inputs before position ``n_real`` of each row: the
    NEW tail — the old one for a row that stays, part old and part new for a row
    that advances by fewer than K - 1)."""
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = taps(xp)
    new_tail = jnp.take_along_axis(
        xp, (n_real[:, None] + jnp.arange(tail.shape[1])[None, :])[:, :, None], axis=1)
    return out, new_tail


def packed_ffn(ffn, h: jax.Array, pack: FfnPack | None):
    """``ffn(h)`` -> (y, stats) over the (B, T, d) block ``h``, or — with a
    ``pack`` — over its real positions alone where they fit: gather them to
    (1, P, d), the same ``ffn`` told how many of those rows are real
    (``n_rows``: its experts take no filler), and every position reads its slot
    back. One branch a forward (``pack.fits`` is made once, before the layers).
    A latent model's (``models.mla``), which packs its MLPs alone;
    ``forward_paged`` here packs both position-wise regions of a layer."""
    if pack is None:
        return ffn(h)

    def packed(h):
        with jax.named_scope("layer/ffn/pack"):
            hp = pack.rows(h)
        y, stats = ffn(hp, n_rows=pack.n_rows)
        with jax.named_scope("layer/ffn/unpack"):
            return pack.block(y), stats

    # the conditional's own time (its operands' copies) is the MLP's: read
    # under ``layer/ffn`` with the branches it chooses between
    with jax.named_scope("layer/ffn"):
        return jax.lax.cond(pack.fits, packed, ffn, h)


def _layer_leaves(p: dict) -> dict:
    """``p`` with the leaves ``p["stacked"]`` names — still STACKED over the
    layers, beside the layer's index — sliced: called inside whichever
    branch reads them (a slice made before a conditional is its operand)."""
    if not p.get("stacked"):
        return p
    return {**p, "stacked": (),
            **{k: jax.tree.map(lambda a: a[p["layer"]], p[k]) for k in p["stacked"]}}


def _ffn(p, h, cfg: LlamaConfig, cs=_identity_cs, picks=None, n_rows=None):
    """A layer's MLP over its normed input (B, T, d) -> (y, the layer's
    ``_moe_stats`` or None): the dense SwiGLU, or the routed experts and the
    shared ones beside them. Position-wise: it asks nothing of B and T. Opens
    ``layer/ffn`` itself: a branch of a packed region puts its own names
    before it, and the scope paths the trace is read by stay whole. ``p``
    may hold leaves still STACKED over the layers (``_layer_leaves``): sliced
    here, inside whichever branch runs. ``picks``: the layer's routing, made
    ahead on its input (``_route_ahead``). ``n_rows``: only the first ``n_rows``
    rows are real (a packed region's filler behind them): the routed experts
    take those alone; the dense and the shared MLPs compute every row."""
    with jax.named_scope("layer/ffn"):
        p = _layer_leaves(p)
        if cfg.n_experts > 0:
            y, stats = _moe_ffn(p, h, cfg, n_rows=n_rows, picks=picks)
            if cfg.n_shared_experts:
                with jax.named_scope("shared"):  # layer/ffn/shared
                    shared = _swiglu(p, h, ("shared_gate", "shared_up", "shared_down"), cs,
                                     cfg.gate_act)
                    if cfg.shared_sum:  # the stacked SwiGLU IS their sum
                        y = y + shared.astype(y.dtype)
                    else:
                        y = y + (shared * (1.0 / cfg.n_shared_experts)).astype(y.dtype)
            return y, stats
        gate = _qe("btd,df->btf", h, p["w_gate"])
        up = _qe("btd,df->btf", h, p["w_up"])
        act = (_gate_act(cfg)(gate) * up).astype(h.dtype)
        act = cs(act, "ffn")
        return _qe("btf,fd->btd", act, p["w_down"]).astype(h.dtype), None


def _layer_out(p, x, attn, cfg: LlamaConfig, cs=_identity_cs, moe_stats: bool = False, u=None,
               picks=None, n_rows=None):
    """Shared decoder-layer back half: output projection + residual, then
    the MLP (dense SwiGLU, or routed MoE when cfg.n_experts > 0) +
    residual. ``attn`` is (B, T, n_heads * head_dim). With ``moe_stats``
    (routed models only) -> (x, the layer's ``_moe_stats``). A PARALLEL
    block (``u``: the layer's one normed input, which fed q/k/v too) adds
    both halves to the same residual: x + W_o attn + FFN(u). Position-wise:
    it asks nothing of B and T (``forward_paged`` runs it on a block's real
    positions, packed, and says how many of the rows are: ``n_rows``, ``_ffn``'s).
    ``picks``: the experts of a model whose router reads the layer's input,
    chosen before attention (``_route_ahead``) on these rows."""
    with jax.named_scope("layer/attn_out"):
        attn = _qe("bth,hd->btd", attn, p["wo"]).astype(x.dtype)
        if cfg.sandwich_norm:  # the norm on the sub-layer's OUTPUT
            attn = _norm(attn, p["attn_post_norm"], cfg)
        attn = cs(attn, "act")
        if u is None:
            x = x + attn
    if cfg.n_experts == 0 and (u is not None or cfg.n_shared_experts):
        raise NotImplementedError("a parallel block or shared experts around a dense MLP")
    with jax.named_scope("layer/ffn"):
        h = _norm(x, p["mlp_norm"], cfg) if u is None else u
    y, stats = _ffn(p, h, cfg, cs, picks, n_rows)
    with jax.named_scope("layer/ffn"):
        if cfg.sandwich_norm:
            y = _norm(y, p["mlp_post_norm"], cfg)
        x = x + cs(y, "act") if u is None else x + attn + cs(y, "act")
    return (x, stats) if moe_stats else x


# ---------------------------------------------------------------- looped layers

# what a forward of a model whose layers run more than once counts (published as
# ``loop.<name>``): the passes it ran, the positions its head read (live rows'
# real ones) and, of those, the positions whose selected pass is the LAST
LOOP_STATS = ("passes", "exit_rows", "exit_last")


class LoopExit(NamedTuple):
    """The exit gate's books over the positions the head reads, carried from
    pass to pass: the selected pass's state so far, the exit probability
    cumulated, the probability that is left (the product of 1 - lambda), the
    pass selected (-1: none yet) and the passes run."""

    state: jax.Array  # (B, R, d)
    cum: jax.Array  # (B, R) float32
    left: jax.Array  # (B, R) float32
    step: jax.Array  # (B, R) int32
    passes: jax.Array  # () int32


def pass_planes(u, cfg: LlamaConfig):
    """The first K/V plane of pass ``u``: every (pass, layer) keeps K/V of its own."""
    return u * cfg.n_layers


def _close_pass(params, cfg: LlamaConfig, x, ex: LoopExit, u, read):
    """What closes pass ``u`` of a looped model: the model's final norm on every
    position — its output is the next pass's input — and, on the positions the
    head reads (``read``: (B, T, d) -> (B, R, d)), the exit gate and the published
    selection in float32: lambda_u = sigmoid(s_u . w + b); p_u = lambda_u x what is
    left (the LAST pass takes all that is left); the head reads the state of the
    first pass at which the cumulated p reaches ``exit_threshold``, the last pass's
    if none does. ``x``: (B, T, d), or a packed forward's pair (the block, the
    packed rows) — one of the two is stale and both are normed.
    -> (the next pass's input, the books)."""
    with jax.named_scope("loop/exit"):
        x = jax.tree.map(lambda a: _norm(a, params["final_norm"], cfg).astype(a.dtype), x)
        s = read(x)
        gate = params["exit_gate"]
        lam = jax.nn.sigmoid(jnp.einsum("brd,d->br", s.astype(jnp.float32),
                                        gate["w"].astype(jnp.float32),
                                        precision=jax.lax.Precision.HIGHEST)
                             + gate["b"].astype(jnp.float32))
        last = u == cfg.ut_steps - 1
        cum = ex.cum + jnp.where(last, ex.left, lam * ex.left)
        take = (ex.step < 0) & ((cum >= cfg.exit_threshold) | last)
        return x, LoopExit(jnp.where(take[..., None], s, ex.state), cum, ex.left * (1.0 - lam),
                           jnp.where(take, u, ex.step).astype(jnp.int32), ex.passes + 1)


# ---------------------------------------------------------------- forward


@watch_compiles("llama.forward")
@partial(jax.jit, static_argnames=("cfg", "rules", "remat", "attn_impl", "fresh_block", "unroll"))
def forward(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # (B, T) int32
    positions: jax.Array,  # (B, T) int32 — absolute positions of `tokens`
    kv_cache: dict,  # (L, B, S, nkv, hd)
    rules=None,  # parallel.ShardingRules | None
    remat: bool = False,  # rematerialize layer activations (training)
    attn_impl: str = "xla",  # "xla" | "pallas" (ops.flash_attention / decode_attention)
    fresh_block: bool = False,  # caller asserts this T>1 block starts a sequence at pos 0
    unroll: int = 1,  # scan unroll factor (decode: trades compile time for loop overhead)
) -> tuple[jax.Array, dict]:
    """Unified prefill/decode forward.

    Writes k/v for `tokens` into cache slots [positions], attends over the
    whole cache with causal+validity masks, returns logits (B, T, V) and the
    updated cache. T is static per bucket; prefill uses T=bucket, decode T=1.
    Padding tokens must carry position == their slot and are masked out by
    the caller via `positions` (slots beyond a sequence's length are simply
    never attended to because kv_len_mask derives from written positions).

    ``attn_impl="pallas"`` routes attention through the Pallas kernels:
    T == 1 steps use ops.decode_attention against the cache with per-row
    frontiers; T > 1 steps use ops.flash_attention over the current block's
    k/v — but ONLY when the caller passes ``fresh_block=True``, its static
    promise that the block starts a fresh sequence at position 0 (the
    engine's prefill and the scheduler's admit both do). A mid-sequence
    T > 1 block without the flag takes the exact XLA cache path instead of
    silently computing block-local attention.
    """
    from .family import family

    family(cfg).refuse("dense_cache", NotImplementedError)
    B, T = tokens.shape
    S = kv_cache["k"].shape[2]
    cs = lambda x, name: rules.constrain(x, name) if rules is not None else x

    with jax.named_scope("embed"):
        x = params["embed"][tokens]  # (B, T, D)
        x = cs(x, "act")
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)

    # validity mask: slot s valid if s <= max written position for that seq.
    # caller guarantees contiguous writes, so max(positions) is the frontier.
    frontier = jnp.max(positions, axis=1)  # (B,)
    kv_len_mask = jnp.arange(S)[None, :] <= frontier[:, None]  # (B, S)

    batch_idx = jnp.arange(B)[:, None]  # (B, 1) for scatter

    # The FULL stacked cache rides the scan CARRY and each layer updates its
    # (li,) plane in place. Passing per-layer cache planes as scan xs/ys
    # instead (round 1) forced XLA to copy every layer's whole cache line
    # per step — ~35% of the decode step's device time at tinyllama scale.
    scanned, whole = _scan_and_whole(params["layers"], cfg)

    def layer(carry, layer_in):
        x, kc, vc = carry
        p, li = layer_in
        if whole:
            p = {**p, **whole, "layer": li}
        q, k, v = _layer_qkv(p, x, cfg, cos, sin, cs)

        with jax.named_scope("layer/kv_write"):
            kc = kc.at[li, batch_idx, positions].set(k.astype(kc.dtype))
            vc = vc.at[li, batch_idx, positions].set(v.astype(vc.dtype))

        with jax.named_scope("layer/attn"):
            if attn_impl == "pallas" and T == 1:
                from ..ops import sharded_decode_attention_layer

                # per-row frontiers; idle rows park writes at slot 0 so this
                # stays proportional to real context (see chunk_decode_loop).
                # The kernel indexes the layer's plane of the STACKED cache via
                # scalar prefetch — slicing cache[li] for a per-layer kernel
                # operand would materialize a full-plane HBM copy per layer per
                # token. On a mesh it runs per-shard under shard_map.
                mesh = rules.mesh if rules is not None else None
                attn = sharded_decode_attention_layer(
                    mesh, q[:, 0], kc, vc, frontier + 1, li
                ).reshape(B, T, -1)
            elif (attn_impl == "pallas" and not fresh_block
                  and T <= MAX_BLOCK_DECODE_T):
                from ..ops import sharded_decode_block_attention_layer

                # small mid-sequence block: the grammar fast-forward step is a
                # (B, 1+W) forward, and the XLA cache fallback reads the cache
                # at CAPACITY for every row (the round-3 reason ff was
                # single-request only). This kernel reads each row's cache up
                # to its own frontier, with intra-block causality from the
                # queries' write positions — batched ff costs a T=1 step plus
                # the riding chain tokens.
                mesh = rules.mesh if rules is not None else None
                attn = sharded_decode_block_attention_layer(
                    mesh, q, kc, vc, positions, li
                ).reshape(B, T, -1)
            elif attn_impl == "pallas" and fresh_block:
                from ..ops import sharded_flash_attention

                # fresh sequence starting at position 0: attention over the
                # block's own k/v is exactly attention over the cache
                mesh = rules.mesh if rules is not None else None
                attn = sharded_flash_attention(mesh, q, k, v, causal=True).reshape(B, T, -1)
            else:
                attn = _attend(q, kc[li], vc[li], positions, kv_len_mask)
        x = _layer_out(p, x, attn, cfg, cs)
        return (x, kc, vc), None

    layer_fn = jax.checkpoint(layer) if remat else layer
    with jax.named_scope("layers"):  # names the scan's own slices of the stacked weights
        (x, new_k, new_v), _ = jax.lax.scan(
            lambda carry, inp: layer_fn(carry, inp),
            (x, kv_cache["k"], kv_cache["v"]),
            (scanned, jnp.arange(cfg.n_layers, dtype=jnp.int32)),
            unroll=unroll,
        )

    with jax.named_scope("final_norm"):
        x = _norm(x, params["final_norm"], cfg)
    with jax.named_scope("lm_head"):
        logits = _qe("btd,dv->btv", x, params["lm_head"])
        logits = cs(logits, "logits")
    return logits, {"k": new_k, "v": new_v}


@watch_compiles("llama.forward_paged")
@partial(jax.jit, static_argnames=("cfg", "rules", "attn_impl", "fresh_block",
                                   "gather_blocks", "kv_quant", "moe_stats",
                                   "attn_stats", "hybrid_stats", "ffn_pack", "latent_stats",
                                   "window_stats", "loop_stats", "kv_stats"),
         donate_argnames=("k_pool", "v_pool", "k_scale", "v_scale"))
def forward_paged(
    params: dict,
    cfg: LlamaConfig,
    tokens: jax.Array,  # (B, T) int32
    positions: jax.Array,  # (B, T) int32 — absolute positions of `tokens`
    k_pool: jax.Array,  # (L, N, bs, nkv, hd) — global paged KV pool
    # (KV_QUANT on: (L, N, bs, nkv, hdp) int8 stored values, ops.kvquant)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, max_blocks) int32 pool-block ids
    rules=None,  # parallel.ShardingRules | None — pool blocks shard over
    # dp, kv heads over tp (parallel.mesh.paged_pool_shardings)
    attn_impl: str = "pallas",  # T=1 uses ops.paged_attention; T>1 gathers
    write_mask: jax.Array | None = None,  # (B,) bool; False rows park their
    # writes in their trash block (idle continuous-batching rows must
    # never scribble on another row's — or the shared prefix's — blocks)
    trash_idx: jax.Array | None = None,  # (B,) int32 flat pool index for
    # parked writes; default 0 (block 0). On a dp mesh each dp group
    # reserves its own trash block so parked writes stay shard-local.
    fresh_block: bool = False,  # caller asserts this T>1 block starts a
    # sequence at position 0: attention runs over the block's own k/v and
    # the per-layer pool gather is SKIPPED entirely (round-2 VERDICT weak
    # #6 — prefill was gathering the row's full table capacity per layer)
    gather_blocks: int | None = None,  # T>1 non-fresh path: gather only the
    # first N table entries per row (the caller's covered-block bucket)
    # instead of the whole table width
    k_scale: jax.Array | None = None,  # (L, N, bs, nkv) bf16 per-(position,
    # head) scales when KV_QUANT is on (None keeps the bf16 path
    # byte-identical — the scale leaves are empty pytree nodes)
    v_scale: jax.Array | None = None,
    kv_quant: str | None = None,  # None | "int8" | "int4" (static)
    moe_stats: bool = False,  # routed models: also return the forward's
    # ``MOE_STATS`` summed over layers, (4,) int32 (the chunk loops carry them
    # out with their readback; no callback on the hot path)
    attn_stats: bool = False,  # also return ``ops.ATTN_STATS``, (3,) int32:
    # the row-blocks the block kernel's common pass took this forward, the
    # row-blocks live rows attend in all and the query positions that pass was
    # handed (the chunk loops carry them likewise)
    n_real: jax.Array | None = None,  # (B,) int32: row b's real positions are
    # t < n_real[b] (None: all T of a live row). A model with a RECURRENT
    # state (models.sambay) advances it over those and no others; with
    # ``ffn_pack`` a LlamaConfig's position-wise work computes those and no
    # others, and the block kernel's common pass multiplies those and no
    # others (``ops.paged_block_attention``)
    logit_pos: jax.Array | None = None,  # (B,) int32: the head runs on this
    # one position of each row, logits (B, 1, V) (that model, and one with
    # layers of more than one kind: the chunk loop's ``one_head``)
    hybrid_stats: bool = False,  # that model only: also ``sambay.HYBRID_STATS``
    ffn_pack: int = 0,  # P > 0 with ``n_real``, off a mesh, where B * T > P:
    # everything position-wise in a layer — norms, q/k/v, rotary; the output
    # projection, the residuals, the MLP — runs on the block's real positions
    # packed into P rows while they fit (``FfnPack``; a fast-forward block
    # of 1 + W positions a row holds few real ones; a latent model packs its
    # MLPs alone), and ``FFN_STATS`` (2,) int32 is returned LAST
    latent_stats: bool = False,  # a latent model only: also ``mla.LATENT_STATS``,
    # (2,) int32, after the attention row-blocks
    window_stats: bool = False,  # a model whose window BINDS only (``bound_window``): also
    # ``WINDOW_STATS``, (3,) int32, after the attention row-blocks
    loop_stats: bool = False,  # a model whose layers run more than once only (``ut_steps``):
    # also ``LOOP_STATS``, (3,) int32, behind those
    kv_stats: bool = False,  # also ``KV_STATS``, (1,) int32, behind those (before ``FFN_STATS``)
):
    """The paged twin of ``forward`` (parity-tested): sequences own
    non-contiguous pool blocks via per-row block tables (SURVEY.md §7
    step 2's paged KV cache). KV writes scatter through the table into the
    flat pool; T=1 decode attends via the ops.paged_attention kernel
    (block-table indirection in the index map — no contiguous per-sequence
    cache ever materializes); T>1 prefill gathers the row's blocks once per
    layer (a per-prefill cost, not per-token).

    KV_QUANT (ISSUE 12): with ``kv_quant`` set, writes QUANTIZE in the
    scatter (ops.kvquant: per-(position, head) bf16 scales stored
    block-major beside the int8/int4 values, at the same flat index — so
    sharing, rollback, and warm-restart reserve all travel with the block)
    and every read dequantizes in place: the T=1 / block Pallas kernels
    fold the scales into their score/probability tiles (fp KV never
    round-trips through HBM), the XLA gather and fresh-block paths attend
    ``dequantize_kv`` of exactly the stored values, so prefill logits match
    what decode later reads.

    Returns (logits, k_pool, v_pool, k_scale, v_scale) — the scale slots
    are None when ``kv_quant`` is None — then, with ``moe_stats``, the
    forward's routed-expert counts, then, with ``attn_stats``, its attention
    row-block counts."""
    from .family import family

    fam = family(cfg)
    # what the family's table refuses is refused HERE, for every family alike,
    # and a count its record does not name with it: nothing is dropped in silence
    if rules is not None:
        fam.refuse("mesh")
    if kv_quant is not None:
        fam.refuse("kv_quant")
    if ffn_pack:
        fam.refuse("ffn_pack", NotImplementedError)
    asked = {"moe_stats": moe_stats, "attn_stats": attn_stats, "hybrid_stats": hybrid_stats,
             "latent_stats": latent_stats, "window_stats": window_stats,
             "loop_stats": loop_stats, "kv_stats": kv_stats}
    counted = {c.keyword for c in fam.counts}
    if any(on and kw not in counted for kw, on in asked.items()):
        raise ValueError(f"a {fam.name} model's forward counts {sorted(counted)}: asked {asked}")
    if fam.module.__name__ != __name__:
        return fam.module.forward_paged(
            params, cfg, tokens, positions, k_pool, v_pool, block_tables,
            attn_impl=attn_impl, write_mask=write_mask, trash_idx=trash_idx,
            fresh_block=fresh_block, gather_blocks=gather_blocks, n_real=n_real,
            logit_pos=logit_pos, ffn_pack=ffn_pack, **{kw: asked[kw] for kw in counted})
    B, T = tokens.shape
    L, N, bs = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    nb = gather_blocks if gather_blocks is not None else block_tables.shape[1]
    S = nb * bs  # gathered context capacity
    cs = lambda x, name: rules.constrain(x, name) if rules is not None else x
    bits = {None: 16, "int8": 8, "int4": 4}[kv_quant]
    hdp = k_pool.shape[4]  # stored last-axis width (hd, or hd/2 packed int4)

    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        x = cs(x, "act")
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
    frontier = jnp.max(positions, axis=1)  # (B,)
    kv_len_mask = jnp.arange(S)[None, :] <= frontier[:, None]
    # pool slot for each written token: table[b, pos//bs] * bs + pos%bs
    blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)  # (B, T)
    flat_idx = blk * bs + positions % bs  # (B, T) into the (N*bs,) flat pool
    if write_mask is not None:
        park = (jnp.zeros((B,), jnp.int32) if trash_idx is None
                else trash_idx.astype(jnp.int32))
        flat_idx = jnp.where(write_mask[:, None], flat_idx, park[:, None])

    # the small mid-sequence block (a grammar fast-forward chain step)
    # attends through the paged block kernel. Which
    # leading blocks the live rows hold in common is read HERE, once a
    # forward, from the tables, the positions and the write mask: tables do
    # not move between layers. Under a mesh each dp group pins its own prefix
    # blocks, so the kernel's wrapper derives it shard-locally instead.
    mesh = rules.mesh if rules is not None else None
    # the layers' kinds (static): whether each rotates, and the window its
    # mask is given. A layer whose window binds attends through the block
    # kernel at T = 1 too (the T = 1 kernel has no window), with a split of
    # its own: behind a window no row rides the common pass
    kinds = layer_kinds(cfg)
    windows = sorted({w for _, w in kinds if w is not None})
    if windows and (mesh is not None or kv_quant is not None):
        raise NotImplementedError("a sliding window that binds, under a mesh or KV_QUANT: "
                                  "their kernels' wrappers take no window")
    block_decode = (attn_impl == "pallas" and not fresh_block
                    and (1 < T or bool(windows)) and T <= MAX_BLOCK_DECODE_T)
    split, win_split = None, {}
    if block_decode and kv_quant is None and mesh is None:
        from ..ops import common_block_split, row_group_splits

        # with ``n_real`` the kernel's common pass multiplies the riders' real
        # positions alone (where a rider's packed rows start is the split's), and
        # a padded position — a copy of its row's last real one, writing the SAME
        # K/V index — returns that one's output, as it did when it was computed
        with jax.named_scope("layer/attn/split"):
            if cfg.layer_types:
                # this model's 144 query rows a K/V head pass what the kernel
                # keeps resident for 32 rows: it walks groups of rows, each
                # with its split, all made here, once a forward
                shape = (B, T, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
                size = params["embed"].dtype.itemsize  # the activations'
                split = row_group_splits(shape, block_tables, positions, write_mask, bs,
                                         itemsize=size, n_real=n_real)
                win_split = {w: row_group_splits(shape, block_tables, positions, write_mask, bs,
                                                 window=w, itemsize=size, n_real=n_real)
                             for w in windows}
            else:
                split = common_block_split(block_tables, positions, write_mask, bs, n_real=n_real)

    # rows of different dp groups may not share a packed axis: not under a mesh
    live = None  # the real positions a row holds, told off a mesh: none of a row that is not live
    if n_real is not None and rules is None:
        live = n_real if write_mask is None else jnp.where(write_mask, n_real, 0)
    # where a position's K and V land, in the pool's indexing at the site; told the real
    # positions, the write walks tiles of them (``write_rows``): their indices, once a forward
    write_at = (flat_idx // bs, flat_idx % bs) if cfg.layer_types else (flat_idx,)
    with jax.named_scope("layer/kv_write"):
        write_tiles, write_at = write_walk(live if kv_quant is None else None, T, write_at)
    pack = None
    if ffn_pack and live is not None and B * T > ffn_pack:
        with jax.named_scope("layer/ffn/pack"):
            pack = ffn_pack_index(live, T, ffn_pack)
        # once a forward: the packed slots' angles, and the residual — which
        # then STAYS packed from layer to layer where the positions fit (the
        # block beside it is the whole branches' and goes stale meanwhile)
        with jax.named_scope("layer/attn_qkv/pack"):
            rope_packed = (pack.rows(cos), pack.rows(sin))
            x = (x, pack.rows(x))

    scanned, whole = _scan_and_whole(params["layers"], cfg, regions=pack is not None)
    # of the whole leaves, those a region slices itself, inside its branch (the
    # grouped kernel takes its planes stacked, and the layer's index)
    stacked = () if pack is None else tuple(
        k for k in whole if not (cfg.moe_impl == "grouped" and k in _EXPERT_LEAVES))

    # a router that reads the layer's input: the picks belong to the FIRST
    # position-wise region and are carried across the attention call
    ahead = cfg.router_input == "layer"

    def own_norm(p, x):
        """A parallel block's ONE normed input: q/k/v's and the MLP's."""
        if not cfg.parallel_block:
            return None
        with jax.named_scope("layer/attn_qkv"):
            return _norm(x, p["attn_norm"], cfg)

    def layer(carry, layer_in, kind=(True, None), base=None):
        x, kp, vp, ksc, vsc = carry
        p, li = layer_in
        rotate, window = kind
        # the K/V plane this layer writes and attends: its own index, or — a looped
        # model's — its index behind the pass's first plane (``base``)
        plane = li if base is None else base + li
        if whole:
            p = {**p, **whole, "layer": li, **({"stacked": stacked} if stacked else {})}

        if pack is None:
            u = own_norm(p, x)
            picks = _route_ahead(p, x, cfg) if ahead else None
            q, k, v = _layer_qkv(p, x, cfg, cos, sin, cs, rotate=rotate, u=u)
        else:
            # a layer is position-wise but for its attention call and its K/V
            # write: the region before them and the one after run on the
            # block's real positions where they fit, each its own branch of
            # one predicate — the output projection beside the MLP, not apart.
            # Only q/k/v are read back into the block, and only the attention
            # output is gathered
            # picks made ahead stay in the layout of the branch that made them
            # (the ONE predicate picks both regions' branches): a pair (the
            # block's, the packed rows'), the other half zeros nobody reads
            def no_picks(*rows):
                return (jnp.zeros((*rows, cfg.top_k), jnp.int32),
                        jnp.zeros((*rows, cfg.top_k), jnp.float32))

            def front(x, rope, hold=False):
                pl = _layer_leaves(p)
                picks = _route_ahead(pl, x, cfg) if ahead else None
                q, k, v = _project_qkv(pl, x, cfg, cs, u=own_norm(pl, x))
                if hold:
                    # buffers before they open into heads: a projection fused
                    # with that reshape wants every stacked plane transposed,
                    # and the whole branch then copies them all back, a layer
                    q, k, v = jax.lax.optimization_barrier((q, k, v))
                return _rotate_heads(q, k, v, cfg, *rope, cs, rotate), picks

            def front_rows(x, xp):
                qkv, picks = front(xp, rope_packed, hold=True)
                with jax.named_scope("layer/attn_qkv/unpack"):
                    qkv = jax.tree.map(pack.block, qkv)
                return qkv if not ahead else (qkv, (no_picks(B, T), picks))

            def front_block(x, xp):
                qkv, picks = front(x, (cos, sin))
                return qkv if not ahead else (qkv, (picks, no_picks(1, ffn_pack)))

            def back(x, attn, picks=None, n_rows=None):
                pl = _layer_leaves(p)
                out = _layer_out(pl, x, attn, cfg, cs, moe_stats=moe_stats, u=own_norm(pl, x),
                                 picks=picks, n_rows=n_rows)
                return out if moe_stats else (out, None)

            x, xp = x
            # the conditional's own time (its operands' copies) is read with
            # the branches it chooses between
            with jax.named_scope("layer/attn_qkv"):
                out = jax.lax.cond(pack.fits, front_rows, front_block, x, xp)
            (q, k, v), picks = out if ahead else (out, (None, None))

        with jax.named_scope("layer/kv_write"):
            kp_flat = kp.reshape(L, N * bs, cfg.n_kv_heads, hdp)
            vp_flat = vp.reshape(L, N * bs, cfg.n_kv_heads, hdp)
            if kv_quant is None:
                # the unrolled layers index the pool AS IT IS SHAPED, (block, offset):
                # through the flat view XLA relaid the whole pool out around a one-row
                # scatter there — a 16x padded copy, 6.25 GB at these widths (my chip
                # run, PR 34; PR 32 met the same)
                pools = (kp, vp) if cfg.layer_types else (kp_flat, vp_flat)
                pools = write_rows(*pools, plane, k.astype(kp.dtype), v.astype(vp.dtype),
                                   write_at, write_tiles)
                kp, vp = (pl.reshape(kp.shape) for pl in pools)
            else:
                from ..ops.kvquant import quantize_kv

                # quantize-on-write: one deterministic rowwise quantization at
                # the scatter, values and their scales landing at the SAME
                # flat index (a shared/rolled-back/reserved block carries its
                # scales by construction)
                qk, sk = quantize_kv(k, kv_quant)
                qv, sv = quantize_kv(v, kv_quant)
                kp = kp_flat.at[plane, flat_idx].set(qk).reshape(kp.shape)
                vp = vp_flat.at[plane, flat_idx].set(qv).reshape(vp.shape)
                ksc_flat = ksc.reshape(L, N * bs, cfg.n_kv_heads)
                vsc_flat = vsc.reshape(L, N * bs, cfg.n_kv_heads)
                ksc = ksc_flat.at[plane, flat_idx].set(sk).reshape(ksc.shape)
                vsc = vsc_flat.at[plane, flat_idx].set(sv).reshape(vsc.shape)

        # this model's layers say their kind: layer/attn/{window,full}
        with jax.named_scope("layer/attn" + ("" if not cfg.layer_types else
                                             "/full" if not rotate else "/window")):
            if attn_impl == "pallas" and T == 1 and not block_decode:
                if kv_quant is None:
                    from ..ops import sharded_paged_attention

                    attn = sharded_paged_attention(
                        mesh, q[:, 0], kp, vp, block_tables, frontier + 1, plane
                    ).reshape(B, T, -1)
                else:
                    from ..ops import sharded_paged_attention_quant

                    # fused dequant: the kernel scales score/probability tiles
                    # by the per-position scales — half (a quarter) of the KV
                    # bytes cross HBM and fp KV never materializes
                    attn = sharded_paged_attention_quant(
                        mesh, q[:, 0], kp, vp, ksc, vsc, block_tables,
                        frontier + 1, plane, bits=bits,
                    ).reshape(B, T, -1)
            elif block_decode:
                # the paged twin of the dense frontier-read block kernel — T
                # queries per row read the blocks live rows hold in common
                # once for all of them, then the row's own pool blocks up to
                # its own positions; no per-layer table gather
                if kv_quant is None:
                    from ..ops import sharded_paged_block_attention

                    attn = sharded_paged_block_attention(
                        mesh, q, kp, vp, block_tables, positions, plane, write_mask,
                        **({"split": split} if window is None else
                           {"split": win_split[window], "window": jnp.int32(window)}),
                        n_real=n_real,
                    ).reshape(B, T, -1)
                else:
                    from ..ops import sharded_paged_block_attention_quant

                    attn = sharded_paged_block_attention_quant(
                        mesh, q, kp, vp, ksc, vsc, block_tables, positions, plane,
                        bits=bits,
                    ).reshape(B, T, -1)
            elif fresh_block and T > 1 and window is None:
                # fresh sequence starting at position 0: attention over the
                # block's own k/v IS attention over the sequence — no pool
                # gather at all (the scatter above still persists the KV).
                # Under KV_QUANT the attended values are the quantize->dequant
                # roundtrip of the block — exactly what the pool stores and a
                # later decode read dequantizes, so prefill logits agree with
                # the quantized serving plane, not the fp one.
                if kv_quant is not None:
                    from ..ops.kvquant import dequantize_kv, quantize_kv

                    k_at = dequantize_kv(*quantize_kv(k, kv_quant), kv_quant)
                    v_at = dequantize_kv(*quantize_kv(v, kv_quant), kv_quant)
                else:
                    k_at = k.astype(kp.dtype)
                    v_at = v.astype(vp.dtype)
                if attn_impl == "pallas":
                    from ..ops import sharded_flash_attention

                    attn = sharded_flash_attention(mesh, q, k_at, v_at,
                                                   causal=True).reshape(B, T, -1)
                else:
                    # attend the POOL-dtype values (what the scatter persisted
                    # and decode later reads) — raw compute-dtype k/v would
                    # break prefill parity with the dense engine's bf16 cache
                    attn = _attend(q, k_at, v_at,
                                   positions, jnp.ones((B, T), dtype=bool))
            else:
                # mid-sequence prefill (prefix-cached suffix): gather the row's
                # COVERED blocks to a contiguous view once per layer
                with jax.named_scope("kv_gather"):
                    tbl = block_tables[:, :nb]

                    def covered(pool):  # values (B, S, heads, width) or scales (B, S, heads)
                        return gather_row_blocks(pool, plane, tbl).reshape(B, S, *pool.shape[3:])

                    if kv_quant is None:
                        kl, vl = covered(kp), covered(vp)
                    else:
                        from ..ops.kvquant import dequantize_kv

                        kl = dequantize_kv(covered(kp), covered(ksc), kv_quant)
                        vl = dequantize_kv(covered(vp), covered(vsc), kv_quant)
                attn = _attend(q, kl, vl, positions, kv_len_mask, window)
        if pack is None:
            out = _layer_out(p, x, attn, cfg, cs, moe_stats=moe_stats, u=u, picks=picks)
            x, stats = out if moe_stats else (out, None)
        else:
            def back_rows(x, xp, attn, *picks):  # the new residual stays packed
                with jax.named_scope("layer/ffn/pack"):
                    rows = pack.rows(attn)
                xp, st = back(xp, rows, picks[1] if ahead else None, pack.n_rows)
                return (x, xp), st

            def back_block(x, xp, attn, *picks):
                x, st = back(x, attn, picks[0] if ahead else None)
                return (x, xp), st

            # under a name no reader matches: ``layer/attn_out`` inside it
            # stays out of the MLP's time
            with jax.named_scope("layer/out"):
                x, stats = jax.lax.cond(pack.fits, back_rows, back_block, x, xp, attn,
                                        *(picks if ahead else ()))
        return (x, kp, vp, ksc, vsc), stats

    # layers of ONE kind are a scan over the stacked weights. Layers of more
    # than one kind (three sliding, one full) run UNROLLED, each one's kind
    # and index static, its weights a static slice of the stacked leaves: a
    # scan over periods of the pattern, the period's four layers unrolled in
    # its body, made XLA copy every period's slice of every leaf out — 13.6 ms
    # of a 32.6 ms forward (my chip run, PR 34)
    def unpacked(x):  # a packed forward's pair (the block, the packed rows) -> every position its slot
        return x if pack is None else jnp.where(pack.fits, pack.block(x[1]), x[0])

    with jax.named_scope("layers"):
        if cfg.ut_steps > 1:
            # the SAME scanned weights ``ut_steps`` times: a scan of passes around
            # the scan of layers — ONE layer body in the program's text, not
            # ut_steps x n_layers of them — each pass on K/V planes of its own,
            # closed by the model's norm and the exit gate
            def read(x):  # what the head reads of a pass's state
                return unpacked(x) if logit_pos is None else jnp.take_along_axis(
                    unpacked(x), logit_pos[:, None, None], axis=1)

            def one_pass(carry, u):
                (x, *pools), ex = carry
                (x, *pools), _ = jax.lax.scan(
                    partial(layer, kind=kinds[0], base=pass_planes(u, cfg)), (x, *pools),
                    (scanned, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
                x, ex = _close_pass(params, cfg, x, ex, u, read)
                return ((x, *pools), ex), None

            R = T if logit_pos is None else 1
            books = LoopExit(jnp.zeros((B, R, cfg.dim), params["embed"].dtype),
                             jnp.zeros((B, R), jnp.float32), jnp.ones((B, R), jnp.float32),
                             jnp.full((B, R), -1, jnp.int32), jnp.zeros((), jnp.int32))
            ((_, k_pool, v_pool, k_scale, v_scale), books), _ = jax.lax.scan(
                one_pass, ((x, k_pool, v_pool, k_scale, v_scale), books),
                jnp.arange(cfg.ut_steps, dtype=jnp.int32))
            stats = None
        elif len(set(kinds)) == 1:
            (x, k_pool, v_pool, k_scale, v_scale), stats = jax.lax.scan(
                partial(layer, kind=kinds[0]),
                (x, k_pool, v_pool, k_scale, v_scale),
                (scanned, jnp.arange(cfg.n_layers, dtype=jnp.int32)),
            )
        else:
            carry, per_layer = (x, k_pool, v_pool, k_scale, v_scale), []
            for i, kind in enumerate(kinds):
                carry, st = layer(carry, (jax.tree.map(lambda a: a[i], scanned), jnp.int32(i)), kind)
                per_layer.append(st)
            x, k_pool, v_pool, k_scale, v_scale = carry
            stats = jnp.stack(per_layer) if moe_stats else None

    if cfg.ut_steps > 1:
        x = books.state  # the selected pass's state, normed where its pass closed
    else:
        if pack is not None:
            with jax.named_scope("layer/out/unpack"):  # once a forward
                x = unpacked(x)
        with jax.named_scope("final_norm"):
            if logit_pos is not None:  # the head on the one position a row reads
                x = jnp.take_along_axis(x, logit_pos[:, None, None], axis=1)
            x = _norm(x, params["final_norm"], cfg)
    with jax.named_scope("lm_head"):
        if "lm_head" in params:
            logits = _qe("btd,dv->btv", x, params["lm_head"])
        else:  # a tied head, unquantised
            logits = jnp.einsum("btd,vd->btv", x, params["embed"],
                                preferred_element_type=jnp.float32)
        if cfg.logit_scale != 1.0:
            logits = logits * cfg.logit_scale
        logits = cs(logits, "logits")
    extra = (jnp.sum(stats, axis=0),) if moe_stats else ()
    if attn_stats:
        stats_of = lambda sp, **kw: _attn_stats(sp, block_decode and kv_quant is None, mesh,
                                                block_tables, positions, write_mask, bs, **kw)
        # layers behind a window that binds read other blocks: every layer's read.
        # Layers of one kind read the same ones: the row-blocks of one read (what
        # ``benchmark/lib/peaks.py`` builds its floors on), the query positions of all
        # (a windowed layer's common RANGE is ``WINDOW_STATS``', not ``common_row_blocks``)
        behind = jnp.array([0, 1, 1], jnp.int32)
        extra += (sum(stats_of(split) if w is None else behind * stats_of(win_split.get(w))
                      for _, w in kinds)
                  if windows else stats_of(split, reads=cfg.ut_steps * cfg.n_layers),)
    if window_stats:
        # what the layers behind a window attend of what their rows hold (the block
        # kernel's items, a range's block once for each of its riders; another path
        # walks no block: zeros)
        walked = held = ranged = jnp.zeros((), jnp.int32)
        for _, w in kinds:
            for sp in win_split.get(w, ()):
                walked = walked + sp.n_items - sp.n_common + sp.counts[0]
                held, ranged = held + sp.counts[1], ranged + sp.counts[0]
        extra += (jnp.stack([walked, held, ranged]).astype(jnp.int32),)
    if loop_stats:
        # the positions the head read: a live row's one (``logit_pos``), else its real ones
        valid = jnp.ones(books.step.shape, bool) if write_mask is None else write_mask[:, None]
        if logit_pos is None and n_real is not None:
            valid = valid & (jnp.arange(T, dtype=jnp.int32)[None, :] < n_real[:, None])
        extra += (jnp.stack([books.passes, jnp.sum(valid),
                             jnp.sum(valid & (books.step == cfg.ut_steps - 1))]).astype(jnp.int32),)
    if kv_stats:
        extra += (cfg.ut_steps * cfg.n_layers * rows_written(write_tiles, positions)[None],)
    if pack is not None:
        extra += (pack.stats,)
    return (logits, k_pool, v_pool, k_scale, v_scale, *extra)


def _attn_stats(split, two_pass: bool, mesh, block_tables, positions, live, bs: int,
                reads: int = 1):
    """``ops.ATTN_STATS`` of one forward, (3,) int32. Through the two-pass
    block kernel they are its split's own; under a mesh that is a split per
    dp group, as the kernel's wrapper derives it; on every other path no
    block is common and live rows attend the blocks up to their frontier.
    ``reads`` layers read alike: the query positions count every read, the
    row-blocks stay one read's."""
    from ..ops import common_block_split

    per_read = jnp.array([1, 1, reads], jnp.int32)
    if split is not None:  # one split, or one for each group of rows
        return per_read * (split.counts if hasattr(split, "counts") else sum(s.counts for s in split))
    B = positions.shape[0]
    live = jnp.ones((B,), bool) if live is None else live
    if two_pass:
        dp = mesh.shape.get("dp", 1)
        groups = lambda x: x.reshape(dp, B // dp, *x.shape[1:])
        return per_read * jnp.sum(jax.vmap(lambda t, p, l: common_block_split(t, p, l, bs).counts)(
            groups(block_tables), groups(positions), groups(live)), axis=0)
    blocks = jnp.where(live, jnp.max(positions, axis=1) // bs + 1, 0)
    none = jnp.zeros((), jnp.int32)
    return jnp.stack([none, jnp.sum(blocks).astype(jnp.int32), none])


def param_count(cfg: LlamaConfig) -> int:
    """Parameters THIS chip holds: the experts held (all, or a share), the
    shared experts, a tied head once."""
    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    per_layer = d * (cfg.n_heads * hd) + 2 * d * (cfg.n_kv_heads * hd) + (cfg.n_heads * hd) * d
    norms = (d if cfg.parallel_block else 2 * d) + (2 * d if cfg.sandwich_norm else 0)
    if cfg.n_experts > 0:
        per_layer += (cfg.n_held + cfg.n_shared_experts) * 3 * d * f + d * cfg.n_experts + norms
    else:
        per_layer += 3 * d * f + norms
    if cfg.qk_norm:
        per_layer += (cfg.n_heads + cfg.n_kv_heads) * hd
    gate = d + 1 if cfg.ut_steps > 1 else 0  # the exit gate; looped layers are held ONCE
    return cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2) + cfg.n_layers * per_layer + d + gate
