"""Hand-written Pallas TPU kernels for the hot ops.

The reference (hellofinch/ray) ships no kernels of its own — GPU math is
delegated to torch/NCCL (SURVEY.md §2.4). On TPU the equivalent hot-path
ownership is these Mosaic kernels: flash attention with online softmax
(`flash_attention`), length-aware decode attention over the slot cache
(`decode_attention`), the fused attention and FFN blocks of the train step
(`fused_attn`, `fused_ffn`), the AdamW update (`adamw`) and int8
quantization (`quant`).

Every kernel runs under `interpret=True` off-TPU so the full test suite
exercises kernel math on the CI CPU mesh.
"""

from ray_tpu.ops.pallas.flash_attention import flash_attention_pallas
from ray_tpu.ops.pallas.quant import quantize_int8, dequantize_int8

__all__ = [
    "flash_attention_pallas",
    "quantize_int8",
    "dequantize_int8",
]
