"""Pallas TPU kernels for the hot ops, with XLA reference twins.

The reference repo has no native/compute layer at all — its FLOPs live in
Deepgram/OpenAI cloud services (SURVEY.md §2 "Native components": none).
Here the hot ops of the in-tree models get hand-written Pallas kernels:

- ``flash_attention``: blockwise online-softmax attention for prefill /
  training / the Whisper encoder (never materializes the (T, S) score matrix
  in HBM)
- ``decode_attention``: single-token GQA attention against the dense KV
  cache, the per-step hot op of the decode loop
- ``masked_argmax``: fused grammar-mask + argmax over the vocab, the
  sampling half of grammar-constrained decoding
- ``selective_scan``: the state-space recurrence over per-slot float32 state
  planes, advanced in place (``models.sambay``'s recurrent layers)
- ``paged_latent_attention``: absorbed attention over a paged LATENT cache —
  one (block, kv_lora_rank) tile serves scores and values, every head a query
  row (``models.mla``)
- ``indexer_scores`` / ``sparse_latent_attention`` / ``window_latent_attention``: learned sparse
  attention over a latent cache — the indexer that scores every pool
  position of a layer's index-key plane, and absorbed attention over a key
  set GATHERED for its queries (a position's selected keys, or the blocks
  that hold a row's window; ``models.dots3``)

Every kernel has a pure-jnp reference twin (``*_reference``) that the
correctness tests and ``chip_smoke.py`` compare it against; kernels run
under ``interpret=True`` on CPU (``ops.backend.on_cpu``) so the whole suite
exercises kernel code paths without a chip, and
``tests/test_kernels_compile_tpu.py`` AOT-compiles each one for the TPU.
"""

from .backend import on_cpu, resolve_kernels
from .flash_attention import flash_attention, attention_reference, sharded_flash_attention
from .decode_attention import (
    decode_attention,
    decode_attention_layer,
    decode_attention_reference,
    decode_block_attention,
    decode_block_attention_layer,
    decode_block_attention_reference,
    sharded_decode_block_attention_layer,
    sharded_decode_attention,
    sharded_decode_attention_layer,
)
from .decode_attention import (
    decode_attention_quant,
    decode_attention_quant_reference,
)
from .grammar_mask import (
    masked_argmax,
    masked_argmax_advance,
    masked_argmax_advance_reference,
    masked_argmax_reference,
    sharded_masked_argmax,
    sharded_masked_argmax_advance,
)
from .grouped_matmul import grouped_matmul, grouped_matmul_reference
from .kvquant import (
    dequantize_kv,
    kv_block_bytes,
    kv_quant_bits,
    kv_store_dim,
    kv_store_dtype,
    quantize_kv,
)
from .selective_scan import selective_scan, selective_scan_reference
from .latent_attention import (
    latent_attention_reference,
    latent_row_splits,
    paged_latent_attention,
    paged_latent_attention_reference,
)
from .sparse_latent import (
    gathered_latent_attention_reference,
    indexer_scores,
    indexer_scores_reference,
    sparse_latent_attention,
    window_latent_attention,
)
from .paged_attention import (
    ATTN_STATS,
    BlockSplit,
    common_block_split,
    row_group_splits,
    paged_block_attention_reference,
    paged_attention,
    paged_attention_quant,
    paged_attention_quant_reference,
    paged_attention_reference,
    paged_block_attention,
    paged_block_attention_quant,
    paged_block_attention_quant_reference,
    sharded_paged_attention,
    sharded_paged_attention_quant,
    sharded_paged_block_attention,
    sharded_paged_block_attention_quant,
)

__all__ = [
    "on_cpu",
    "resolve_kernels",
    "flash_attention",
    "attention_reference",
    "sharded_flash_attention",
    "decode_attention",
    "decode_attention_layer",
    "decode_attention_reference",
    "decode_block_attention",
    "decode_block_attention_layer",
    "decode_block_attention_reference",
    "sharded_decode_block_attention_layer",
    "sharded_decode_attention",
    "sharded_decode_attention_layer",
    "grouped_matmul",
    "grouped_matmul_reference",
    "decode_attention_quant",
    "decode_attention_quant_reference",
    "masked_argmax",
    "masked_argmax_advance",
    "masked_argmax_advance_reference",
    "masked_argmax_reference",
    "sharded_masked_argmax",
    "sharded_masked_argmax_advance",
    "dequantize_kv",
    "kv_block_bytes",
    "kv_quant_bits",
    "kv_store_dim",
    "kv_store_dtype",
    "quantize_kv",
    "paged_attention",
    "paged_attention_quant",
    "paged_attention_quant_reference",
    "paged_block_attention",
    "paged_block_attention_reference",
    "common_block_split",
    "row_group_splits",
    "BlockSplit",
    "ATTN_STATS",
    "latent_attention_reference",
    "latent_row_splits",
    "paged_latent_attention",
    "paged_latent_attention_reference",
    "indexer_scores",
    "indexer_scores_reference",
    "sparse_latent_attention",
    "window_latent_attention",
    "gathered_latent_attention_reference",
    "paged_block_attention_quant",
    "paged_block_attention_quant_reference",
    "sharded_paged_block_attention",
    "sharded_paged_block_attention_quant",
    "paged_attention_reference",
    "sharded_paged_attention",
    "sharded_paged_attention_quant",
    "selective_scan",
    "selective_scan_reference",
]
