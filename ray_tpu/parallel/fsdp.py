"""The weight gradients' exchange over `fsdp`, spelled by the program.

Left to the partitioner, the reduction of a layer's weight gradients over
`fsdp` is an all-reduce of the WHOLE gradient followed by a slice, and on
the TPU it runs on the compute stream: seven blocking reductions a layer,
62 ms of a 400 ms step at Mistral-7B widths on fsdp 2 x tp 2. No XLA:TPU
flag moves it. What the compiler does run in the background is a
`collective-permute`: a `-start` ... `-done` pair with the backward's other
matmuls between (PERF.md section 6, PR 31: 397 -> 338 ms a step).

So a layer's weights go into the block as `ExchangedWeight`s. `x @ w` is
then the same product, gathered and partitioned by the compiler as before
(forward and `dx` are the partitioner's program, untouched); only its `dw`
is ours. A `shard_map` manual over `fsdp` alone (`tp`, `dp` and the rest
stay the partitioner's) makes every rank's partial `dw`, of its own rows of
the batch, one shard-sized chunk at a time, and sums the chunks around a
ring of n - 1 permutes, each rank ending with its own shard. Sums are in
the gradient's dtype over the same ranks as the partitioner's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

AXIS = "fsdp"
BATCH_AXES = ("dp", AXIS)  # the batch dimension's mesh axes, outermost first


def axis_size(mesh) -> int:
    """Size of the mesh's `fsdp` axis (1 without a mesh or the axis)."""
    return 1 if mesh is None else mesh.shape.get(AXIS, 1)


def batch_split(mesh) -> list:
    """[dp, fsdp]: how many ways each of its axes splits the batch."""
    return [mesh.shape.get(a, 1) for a in BATCH_AXES]


def sharded_dim(spec: P):
    """The dimension of a leaf that `spec` shards over `fsdp`, or None."""
    return next((i for i, a in enumerate(spec) if a == AXIS), None)


class ExchangedWeight:
    """Stands where a weight [k, n] stands in `x @ w` (x [batch, ..., k],
    batch split over (dp, fsdp)); `dim` is the weight's dimension that is
    sharded over `fsdp`."""

    def __init__(self, w: jax.Array, dim: int, mesh):
        self.w, self.dim, self.mesh = w, dim, mesh

    def __rmatmul__(self, x: jax.Array) -> jax.Array:
        return _matmul(x, self.w, self.dim, self.mesh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul(x, w, dim, mesh):
    return x @ w


def _matmul_fwd(x, w, dim, mesh):
    return x @ w, (x, w)


def _matmul_bwd(dim, mesh, res, dy):
    x, w = res
    dx = jax.lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))
    # [dp, fsdp, rows, width]: each rank's own rows of the batch
    by_rank = lambda a: a.reshape(*batch_split(mesh), -1, a.shape[-1])
    dw = jax.shard_map(
        lambda x, dy: _reduce_scatter_dw(x[:, 0], dy[:, 0], dim), mesh=mesh,
        axis_names={AXIS}, in_specs=(P(None, AXIS), P(None, AXIS)),
        out_specs=P(*[None] * (1 + dim), AXIS))(by_rank(x), by_rank(dy))
    return dx, dw.sum(0).astype(w.dtype)


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


def _reduce_scatter_dw(x: jax.Array, dy: jax.Array, dim: int) -> jax.Array:
    """Sum over `fsdp` of dw = x^T dy (x [dp, rows, k], dy [dp, rows, n],
    this rank's rows), rank r keeping chunk r of dw's dimension `dim`: a
    ring of n - 1 steps. In step t rank r hands the running sum of chunk
    r - t - 1 to rank r + 1 and adds its own part of the chunk that arrives;
    at n = 2 that is one exchange of the partner's half. A chunk of dw is
    the product of a slice of x (or of dy), so no whole dw is made and cut:
    the compiler sends the first product off, and fuses the sum (and the
    write into the scan's stacked gradient) into the product of the chunk it
    keeps. (A whole dw cut in two read 374 ms a step, and a sum kept out of
    the product 358, against 338 this way.)"""
    n = jax.lax.axis_size(AXIS)
    r = jax.lax.axis_index(AXIS)
    cut = (x, dy)[dim]
    size = cut.shape[-1] // n
    ring = [(i, (i + 1) % n) for i in range(n)]

    def chunk(j):
        part = jax.lax.dynamic_slice_in_dim(cut, (j % n) * size, size, 2)
        return jnp.einsum("prk,prn->pkn", *((part, dy), (x, part))[dim])

    acc = chunk(r - 1)
    for t in range(n - 1):
        acc = jax.lax.ppermute(acc, AXIS, ring) + chunk(r - t - 2)
    return acc
