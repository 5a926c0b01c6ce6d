"""TPU-native fused ops (pallas kernels).

The reference framework has no custom kernels (its hot ops live inside
PyTorch/NCCL); on TPU the hot op of the flagship training loop is
attention, implemented here as a fused pallas flash-attention kernel so
the O(S²) score matrix never round-trips HBM. The wire-compression
kernels (quantize/dequantize/cast) move gradient-sync packing onto the
accelerator so d2h bytes scale with the wire size, not the f32 size.
"""

from .flash_attention import (
    block_scores_computed,
    flash_attention,
    flash_attention_qkv,
    flash_attention_rows,
)
from .quantize_kernels import (
    cast_bf16,
    dequantize_q8,
    quantize_q8,
    quantize_q8_ef,
)

__all__ = [
    "block_scores_computed",
    "flash_attention",
    "flash_attention_qkv",
    "flash_attention_rows",
    "cast_bf16",
    "dequantize_q8",
    "quantize_q8",
    "quantize_q8_ef",
]
