"""Ulysses sequence parallelism: all-to-all head/sequence re-sharding.

Green-field relative to the reference (SURVEY §5.7: no sequence/context
parallelism exists anywhere in it). Complements ring attention
(`ops/ring_attention.py`) as the second standard SP scheme (DeepSpeed-
Ulysses, Jacobs et al.): activations arrive sequence-sharded over the `sp`
mesh axis; an all-to-all re-shards them to *head*-sharded with the full
sequence local, plain (flash) attention runs per device, and a second
all-to-all restores sequence sharding.

Trade-off vs ring: Ulysses moves activations twice over ICI
(2 x O(b*s*d/sp) per device, as all-to-alls XLA can't overlap with the
attention itself) but runs one dense attention kernel with no per-step
masking overhead; ring keeps transfers to K/V only and overlaps them with
compute, but pays the online-softmax merge per ring step. Ulysses requires
sp | local head count; ring has no head constraint. Both are exposed via
`ModelConfig.seq_parallel` and compared against dense attention in tests.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import attention


def _repeat_kv_to_multiple(t: jax.Array, sp: int) -> jax.Array:
    """Repeat KV heads (adjacently, GQA grouping order) by the minimal
    factor that makes the head count divisible by sp."""
    h = t.shape[1]
    if h % sp == 0:
        return t
    rep = sp // math.gcd(h, sp)
    b, _, s, d = t.shape
    return jnp.broadcast_to(t[:, :, None], (b, h, rep, s, d)).reshape(
        b, h * rep, s, d)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "sp", causal: bool = True,
                      sm_scale: Optional[float] = None) -> jax.Array:
    """Per-shard Ulysses attention; call inside shard_map over `axis_name`.

    Shapes are local shards [batch, heads, seq/sp, head_dim]. GQA is
    supported natively: KV heads cross the all-to-all unexpanded (repeated
    only to the minimal sp-divisible multiple), and `attention()` broadcasts
    them to the Q head count after the re-shard — so KV ICI traffic stays
    ~n_kv/n_heads of the naive pre-repeat. Q's local head count must be
    divisible by the sp axis size.
    """
    sp = jax.lax.psum(1, axis_name)
    h = q.shape[1]
    if h % sp != 0:
        raise ValueError(f"local Q head count {h} not divisible by sp={sp}")
    k = _repeat_kv_to_multiple(k, sp)
    v = _repeat_kv_to_multiple(v, sp)

    def scatter_heads(t):  # [b, h, s/sp, d] -> [b, h/sp, s, d]
        return jax.lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def gather_heads(t):   # [b, h/sp, s, d] -> [b, h, s/sp, d]
        return jax.lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return gather_heads(out)


def ulysses_attention_sharded(mesh: Mesh, q, k, v, *, causal: bool = True,
                              axis_name: str = "sp",
                              sm_scale: Optional[float] = None):
    """shard_map wrapper: [batch, heads, seq, head_dim] global arrays with
    seq sharded over `axis_name`; batch over (dp, fsdp); heads over tp."""
    spec = P(("dp", "fsdp"), "tp", axis_name, None)
    fn = functools.partial(
        ulysses_attention, axis_name=axis_name, causal=causal,
        sm_scale=sm_scale)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
