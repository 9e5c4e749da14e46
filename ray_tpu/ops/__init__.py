from ray_tpu.ops.layers import rms_norm, rotary_embedding, apply_rotary, swiglu
from ray_tpu.ops.attention import attention, causal_attention_reference
