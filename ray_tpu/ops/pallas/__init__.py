"""Hand-written Pallas TPU kernels for the hot ops.

The reference (hellofinch/ray) ships no kernels of its own — GPU math is
delegated to torch/NCCL (SURVEY.md §2.4). On TPU the equivalent hot-path
ownership is these Mosaic kernels: fused RMSNorm, flash attention with
online softmax, length-aware decode attention over the slot cache,
blockwise cross-entropy, and int8 quantization.

Every kernel runs under `interpret=True` off-TPU so the full test suite
exercises kernel math on the CI CPU mesh.
"""

from ray_tpu.ops.pallas.rmsnorm import rms_norm_pallas
from ray_tpu.ops.pallas.flash_attention import flash_attention_pallas
from ray_tpu.ops.pallas.xent import softmax_cross_entropy_pallas
from ray_tpu.ops.pallas.quant import quantize_int8, dequantize_int8

__all__ = [
    "rms_norm_pallas",
    "flash_attention_pallas",
    "softmax_cross_entropy_pallas",
    "quantize_int8",
    "dequantize_int8",
]
