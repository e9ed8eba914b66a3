"""HBM sizing for the pp×tp flagship config (round-3 VERDICT next #6).

BASELINE config 4 wants a Llama-3-70B-class planner served with continuous
batching at 32 concurrent sessions on v5e-8. Nothing ever checked that the
weights + staged KV + replicated head tensors physically FIT — this module
is that check, and ``tests/test_70b_sizing.py`` fails the build if the
flagship config stops fitting.

Accounting mirrors serve/pp_engine.py's actual placement decisions:
- staged layer matmuls: int8 {"q","s"} (1 byte + f32 per-out-channel
  scales), layers split over pp, every matmul split over tp
- embed: replicated bf16 (a gather; quantizing it saves 1 GB/chip at a
  quality cost — kept full precision, same call as serve/engine.py)
- lm_head: int8, replicated (pp_tp_forward_cached computes logits after
  the last stage's psum; every chip holds the head)
- staged KV cache: (L/pp, slots, max_len, nkv/tp, hd) k+v bf16 per chip
- norms/rope/byte tables: noise (< 10 MB), folded into the margin
"""

from __future__ import annotations

from dataclasses import dataclass

V5E_HBM_PER_CHIP = 16 * 2**30  # bytes
# fraction of HBM usable for steady-state buffers: XLA reserves workspace
# for fusions/collectives and the compiler pads layouts; 90% is the
# conventional planning ceiling
USABLE_FRACTION = 0.90


@dataclass(frozen=True)
class HBMBreakdown:
    layer_weights: int  # per chip, bytes
    scales: int
    embed: int
    lm_head: int
    kv_cache: int
    activations: int

    @property
    def total(self) -> int:
        return (self.layer_weights + self.scales + self.embed + self.lm_head
                + self.kv_cache + self.activations)

    def fraction_of(self, hbm_per_chip: int = V5E_HBM_PER_CHIP) -> float:
        return self.total / hbm_per_chip

    def row(self) -> str:
        gb = 2**30
        return (f"weights {self.layer_weights / gb:.2f} + scales "
                f"{self.scales / gb:.2f} + embed {self.embed / gb:.2f} + "
                f"lm_head {self.lm_head / gb:.2f} + kv {self.kv_cache / gb:.2f} "
                f"+ act {self.activations / gb:.2f} = {self.total / gb:.2f} GiB/chip")


def pp_tp_hbm_per_chip(
    cfg,
    pp: int,
    tp: int,
    *,
    batch_slots: int,
    max_len: int,
    quant: str | None = "int8",
    prefill_bucket: int = 2048,
) -> HBMBreakdown:
    """Per-chip steady-state bytes for PPDecodeEngine at this config: a dense
    decoder's. A routed or hybrid model is refused by name rather than
    reported as a dense decoder of its ``ffn_dim`` (its planes are counted by
    ``costmodel.decode_step_bytes`` and, from the live tree, ``hbmledger``)."""
    from ..models.family import family

    fam = family(cfg)
    if cfg.n_experts or fam.cache["state_column"]:  # a request's per-slot state: not sized here
        raise ValueError(f"hbm_budget sizes dense decoders on the pp x tp layout; a "
                         f"{type(cfg).__name__} with {cfg.n_experts} experts "
                         f"is not one (utils.hbmledger plans from the engine's own tree)")
    d, f, hd = cfg.dim, cfg.ffn_dim, cfg.head_dim
    nq, nkv, L, V = cfg.n_heads, cfg.n_kv_heads, cfg.n_layers, cfg.vocab_size
    wbytes = 1 if quant == "int8" else 2

    per_layer_matmul = d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * f
    per_layer_out_channels = nq * hd + 2 * nkv * hd + d + 2 * f + d
    layers_per_chip = L // pp
    layer_weights = layers_per_chip * per_layer_matmul * wbytes // tp
    scales = (layers_per_chip * per_layer_out_channels * 4 // tp
              if quant == "int8" else 0)
    norms = layers_per_chip * (4 if cfg.sandwich_norm else 2) * d * 2  # bf16, replicated within stage

    embed = V * d * 2  # bf16, replicated
    lm_head = V * d * wbytes + (V * 4 if quant == "int8" else 0)  # replicated

    # the K/V planes are the family record's, not the layer count: a model whose
    # layers run more than once keeps a plane for every (pass, layer) over
    # n_layers layers of weights (a stage holds its layers' planes of every pass)
    planes_per_chip = (fam.cache["planes"]["k"]["kv"][0] if fam.kv_by_head else L) // pp
    kv_cache = 2 * planes_per_chip * batch_slots * max_len * (nkv // max(tp, 1) or 1) * hd * 2

    # activation high-water mark: the per-slot prefill block dominates
    # (B=1, T=prefill_bucket): x + q/k/v + gate/up at f32 einsum outputs
    act = prefill_bucket * max(d, f) * 4 * 4

    return HBMBreakdown(layer_weights=layer_weights + norms, scales=scales,
                        embed=embed, lm_head=lm_head, kv_cache=kv_cache,
                        activations=act)


def flagship_70b_breakdown(batch_slots: int = 32, max_len: int = 2048,
                           pp: int = 2, tp: int = 4) -> HBMBreakdown:
    """BASELINE config 4 exactly: llama3-70b at real Llama-3 vocab, int8,
    32-session continuous batching on v5e-8 (pp×tp = 8 chips)."""
    from dataclasses import replace

    from ..models.llama import PRESETS

    cfg = replace(PRESETS["llama3-70b"], vocab_size=128_256)
    return pp_tp_hbm_per_chip(cfg, pp, tp, batch_slots=batch_slots,
                              max_len=max_len, quant="int8")
