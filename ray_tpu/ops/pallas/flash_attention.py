"""Flash attention (online-softmax) Pallas kernels, forward AND backward.

Forward kernel with O(seq) memory: the [sq, sk] score matrix never hits
HBM. Grid = (batch*heads, q_blocks, k_blocks) with the k axis innermost —
sequential on TPU — so a VMEM accumulator carries the running max / sum /
weighted values across k blocks (the standard online-softmax recurrence).
The forward also emits the per-row logsumexp so the backward can recompute
attention probabilities blockwise.

Backward is the FlashAttention-2 recompute scheme as two fused kernels —
O(seq) memory, no [sq, sk] materialization:
  * dk/dv kernel: grid (bh, k_blocks, q_blocks), q innermost; for each key
    block accumulate  dv += pᵀ·dO  and  dk += dsᵀ·q  across query blocks.
  * dq kernel: grid (bh, q_blocks, k_blocks), k innermost; accumulate
    dq += ds·k  across key blocks.
with  p = exp(q·kᵀ·scale − lse)  recomputed from the saved logsumexp and
ds = p·(dO·vᵀ − Δ)·scale,  Δ = rowsum(dO ⊙ O)  precomputed outside.

This kernel pair is the training hot path (`ray_tpu.ops.attention` routes
TPU training through it). No reference-counterpart: hellofinch/ray
delegates all device math to torch (SURVEY.md §2.4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas._util import cdiv, interpret_mode

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                sq: int, sk: int, bounds_ref=None, window: int | None = None):
    """`bounds_ref` (`flash_attention_banded`): int32 scalars [q_start, k_lo,
    ...]: query row r stands at key column r + q_start (in the static
    `sk - sq`'s place), key columns below k_lo do not exist, and with
    `window` a query sees the `window` columns that end at its own."""
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(i_k == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Causal: row r attends keys <= r + (sk - sq) (diagonal offset aligns
    # the query window to the END of the key axis — the KV-cache decode
    # convention, matching _reference's tril(k=sk-sq)). Skip k blocks
    # entirely above the band.
    offset = sk - sq
    should_compute = True
    if causal:
        should_compute = (
            i_k * block_k <= i_q * block_q + block_q - 1 + offset)
    if bounds_ref is not None:
        offset, k_lo = bounds_ref[0], bounds_ref[1]
        # the band's lower edge: a key block wholly below the first query
        # row's lowest column is skipped like one above the causal edge
        should_compute = jnp.logical_and(
            i_k * block_k <= i_q * block_q + block_q - 1 + offset,
            i_k * block_k + block_k - 1 >= _band_lo(i_q * block_q + offset, k_lo,
                                                    window))

    @pl.when(should_compute)
    def _compute():
        # Matmul operands stay in the input dtype (bf16 in training): the MXU
        # runs bf16×bf16→f32 at full rate, f32×f32 at a fraction of it. All
        # accumulation and softmax state is f32.
        q = q_ref[0]                      # [bq, d]
        k = k_ref[0]                      # [bk, d]
        v = v_ref[0]                      # [bk, d]
        # zero v's padded tail rows: their p weights are 0, but 0*garbage
        # (NaN in interpret mode) would still poison the p@v accumulate
        v_rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + i_k * block_k
        v = jnp.where(v_rows < sk, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk] f32
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i_k * block_k
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i_q * block_q
            s = jnp.where(cols <= rows + offset, s, _NEG_INF)
        if bounds_ref is not None:
            s = jnp.where(cols >= _band_lo(rows + offset, k_lo, window), s, _NEG_INF)
        # mask the padded key tail of the last block (sk % block_k != 0)
        s = jnp.where(cols < sk, s, _NEG_INF)

        m_prev = m_ref[:]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                  # [bq, bk] f32
        alpha = jnp.exp(m_prev - m_new)         # rescale old state
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    @pl.when(i_k == n_k - 1)
    def _finalize():
        # Fully-masked rows (can't happen for causal self-attn) guard: l>=1e-30.
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse layout is [bh, 8, sq] (8 broadcast sublanes) so its block's
        # trailing dims satisfy Mosaic's (8,128) tiling; see _flash_fwd.
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:] + jnp.log(l))[:, 0][None, :], lse_ref.shape[1:])


def _band_lo(at, k_lo, window):
    """The lowest key column a query at column `at` sees."""
    return k_lo if window is None else jnp.maximum(k_lo, at - window + 1)


def flash_attention_banded(q, k, v, bounds, sm_scale: float,
                           window: int | None = None,
                           block_q: int = 1024, block_k: int = 1024):
    """The forward kernel for a prompt pass that walks its prompt a chunk at
    a time: causal attention of q [b H, sq, d] (a chunk's queries, every
    head) against k, v [layers, b kvh, sk, d] (a layer's rows of a cache
    that holds the chunks so far), no gradient. `bounds` int32 [3] =
    (q_start, k_lo, layer): query row r stands at key column r + q_start and
    attends columns max(k_lo, that - window + 1) .. that of layer `layer`
    (`window` None: from k_lo). Grouped heads are read through the index
    map: query head h reads key head h // (H / kvh), no copy of K or V to
    the query heads. Key blocks wholly outside the band are neither computed
    nor fetched: the index map holds the block at the band's nearest."""
    bh, sq, d = q.shape
    layers, bkv, sk, _ = k.shape
    rep = bh // bkv
    bq, bk = min(block_q, sq), min(block_k, sk)
    n_k = cdiv(sk, bk)

    def kv_block(b, i, j, bounds):
        at = i * bq + bounds[0]              # the block's first query's column
        lo = jnp.maximum(_band_lo(at, bounds[1], window), 0) // bk
        hi = jnp.minimum((at + bq - 1) // bk, n_k - 1)
        return (bounds[2], b // rep, jnp.clip(j, lo, hi), 0)

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=True,
                               block_q=bq, block_k=bk, sq=sq, sk=sk, window=window)
    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j, bounds: (b, i, 0),
                         memory_space=pltpu.VMEM)
    kvspec = pl.BlockSpec((None, 1, bk, d), kv_block, memory_space=pltpu.VMEM)
    out, _ = pl.pallas_call(
        lambda bounds_ref, *refs: kernel(*refs, bounds_ref=bounds_ref),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh, cdiv(sq, bq), n_k),
            in_specs=[qspec, kvspec, kvspec],
            out_specs=[qspec,
                       pl.BlockSpec((1, 8, bq), lambda b, i, j, bounds: (b, 0, i),
                                    memory_space=pltpu.VMEM)],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32)],
        name="flash_attention_banded",
        interpret=interpret_mode(),
    )(bounds.astype(jnp.int32), q, k, v)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k):
    """Returns (out, lse); lse is [bh, 8, sq] float32 — m + log(l) per row,
    broadcast across 8 sublanes so the (1, 8, bq) block satisfies Mosaic's
    trailing-(8, 128) tiling requirement (cf. the MIN_BLOCK padding in JAX's
    own TPU flash kernel)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    grid = (bh, cdiv(sq, bq), cdiv(sk, bk))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, sq=sq, sk=sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(q, k, v)


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *,
                    i_q, i_k, sm_scale, causal, block_q, block_k, sq, sk):
    """Shared backward-block math: recompute p [bq,bk] and ds [bq,bk]."""
    # Operands stay in the input dtype (bf16 in training) for full-rate MXU;
    # p/ds are computed f32 and cast back at the accumulating matmuls.
    q = q_ref[0]                              # [bq, d]
    k = k_ref[0]                              # [bk, d]
    v = v_ref[0]                              # [bk, d]
    do = do_ref[0]                            # [bq, d]
    lse = lse_ref[0][0, :][:, None]           # [8, bq] sublane 0 -> [bq, 1]
    delta = delta_ref[0][0, :][:, None]       # [bq, 1]
    offset = sk - sq
    # Zero every operand's padded tail rows: the contraction dims of dsᵀ·q,
    # ds·k and pᵀ·dO run over them, and although p/ds are 0 there, garbage
    # (NaN in interpret mode) still poisons the dot because 0·NaN = NaN.
    q_rows = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0) + i_q * block_q
    q = jnp.where(q_rows < sq, q, jnp.zeros_like(q))
    do = jnp.where(q_rows < sq, do, jnp.zeros_like(do))
    k_rows = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) + i_k * block_k
    k = jnp.where(k_rows < sk, k, jnp.zeros_like(k))
    v = jnp.where(k_rows < sk, v, jnp.zeros_like(v))
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale        # [bq, bk] f32
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i_q * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + i_k * block_k
    valid = (rows < sq) & (cols < sk)
    if causal:
        valid &= cols <= rows + offset
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)               # [bq, bk] f32
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [bq, bk] f32
    # where(): p==0 at invalid entries but dp can be NaN/garbage there
    # (padded v columns), and 0*NaN = NaN.
    ds = jnp.where(valid, p * (dp - delta) * sm_scale, 0.0)   # [bq, bk] f32
    return q, k, do, p, ds


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    sm_scale, causal, block_q, block_k, sq, sk):
    i_k = pl.program_id(1)
    i_q = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(i_q == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    offset = sk - sq
    should_compute = True
    if causal:  # key block entirely above the causal band contributes nothing
        should_compute = (
            i_k * block_k <= i_q * block_q + block_q - 1 + offset)

    @pl.when(should_compute)
    def _compute():
        q, k, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            i_q=i_q, i_k=i_k, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, sq=sq, sk=sk)
        # dv += pᵀ·dO ; dk += dsᵀ·q   (contract over the q dimension)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i_q == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *,
                   sm_scale, causal, block_q, block_k, sq, sk):
    i_q = pl.program_id(1)
    i_k = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(i_k == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    offset = sk - sq
    should_compute = True
    if causal:
        should_compute = (
            i_k * block_k <= i_q * block_q + block_q - 1 + offset)

    @pl.when(should_compute)
    def _compute():
        q, k, do, p, ds = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            i_q=i_q, i_k=i_k, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, sq=sq, sk=sk)
        dq_acc[:] += jnp.dot(ds.astype(k.dtype), k,
                             preferred_element_type=jnp.float32)

    @pl.when(i_k == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    n_qb = cdiv(sq, bq)
    n_kb = cdiv(sk, bk)
    # Δ = rowsum(dO ⊙ O): tiny elementwise reduce; XLA fuses it, no kernel
    # needed (FlashAttention-2 preprocess step). Same [bh, 8, sq] broadcast
    # layout as lse (Mosaic trailing-dim tiling).
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1)[:, None, :], (bh, 8, sq))

    kw = dict(sm_scale=sm_scale, causal=causal, block_q=bq, block_k=bk,
              sq=sq, sk=sk)
    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec((1, 8, bq), lambda b, i, j: (b, 0, i), memory_space=pltpu.VMEM)

    # dk/dv: key blocks in the 2nd grid dim, query blocks innermost.
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(bh, n_kb, n_qb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, jk, iq: (b, iq, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, jk, iq: (b, jk, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, jk, iq: (b, jk, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, d), lambda b, jk, iq: (b, iq, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, jk, iq: (b, 0, iq), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, bq), lambda b, jk, iq: (b, 0, iq), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, jk, iq: (b, jk, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, jk, iq: (b, jk, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(q, k, v, do, lse, delta)

    # dq: query blocks in the 2nd grid dim, key blocks innermost.
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(bh, n_qb, n_kb),
        in_specs=[
            qspec,
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), memory_space=pltpu.VMEM),
            qspec,
            rowspec,
            rowspec,
        ],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret_mode(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _reference(q, k, v, sm_scale, causal):
    logits = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           sm_scale: float | None = None, causal: bool = True,
                           block_q: int = 256, block_k: int = 256,
                           block_q_bwd: int | None = None,
                           block_k_bwd: int | None = None) -> jax.Array:
    """Flash attention over [batch*heads, seq, head_dim] tensors.

    The forward and backward kernels have different optimal tilings (the
    fwd kernel's VMEM working set is one q-block accumulator; the bwd dkv
    kernel carries two k-block accumulators), so block sizes can be given
    per direction; bwd defaults to the fwd blocks."""
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    out, _lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out


def _vjp_fwd(q, k, v, sm_scale, causal, block_q, block_k,
             block_q_bwd, block_k_bwd):
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _vjp_bwd(sm_scale, causal, block_q, block_k, block_q_bwd, block_k_bwd,
             res, g):
    q, k, v, out, lse = res
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _flash_bwd(q, k, v, out, lse, g, scale, causal,
                      block_q_bwd or block_q, block_k_bwd or block_k)


flash_attention_pallas.defvjp(_vjp_fwd, _vjp_bwd)
