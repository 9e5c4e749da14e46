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
is ours. On a mesh with `tp` > 1 as well the products themselves are
`parallel/tp.py`'s, which reads `w` and `dim` off the same object, calls
`weight_grad` for the `dw` and `ring_products` for the products: the
weight's shards go round the same ring INSIDE the product, a rank
multiplying by its own shard while its neighbour's travels (the partitioner
gathers one weight at a time, each where the one before is first used);
where no earlier product of the loop body covers the transfer, the caller
pins that order (`own_first`).

A `shard_map` manual over `fsdp` alone (`tp`, `dp` and the rest stay the
partitioner's) makes every rank's partial `dw`, of its own rows of the
batch, one shard-sized chunk at a time, and sums the chunks around a ring of
n - 1 permutes, each rank ending with its own shard. Those sums are in the
gradient's dtype over the same ranks as the partitioner's. A product's sum
over a dimension that `fsdp` shards is taken whole in float32 and rounded
once, as the product by the gathered weight is (`ring_products`).

A layer's seven rings share ONE link in one direction, and the link serves
them in the order of their starts. The order rule, where the products are
parallel/tp.py's: the rings are taken off the link in the order they were
put on it, the backward's own (`w_down`, gate, up, `wo`, `wq`, `wk`, `wv`),
and each ring's kept product stands between its arrival and the next
ring's (`RingOrder`, `_reduce_scatter_dws`): the scheduler, which prices
each permute as if it had the link to itself, took the smallest first and
the step waited at 2 MB for the 58 MB started before it.

The two norm scales of a layer are replicated leaves, and each rank makes a
partial `dw` of its own rows: left to the partitioner that is one more
all-reduce in the backward's scan body, of 8 KB and on the compute stream,
where every chip waits for the slowest (13 ms of a 319 ms step at those
widths). Nothing in the layer needs the sum. So a scale goes into the scan
once a rank (`scale_by_rank`) and into the block as an `UnreducedScale`,
whose cotangent is each rank's own partial sum; the sum over the ranks is the
broadcast's transpose, once a step behind the scan, in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

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


class RingOrder:
    """One layer's weight-gradient rings in the order the one `fsdp` link
    carries them: `taken` is 0.0 in float32, read off the last sum a ring
    of the backward made (`weight_grads`). Every product of the layer is
    handed it and hands it on (parallel/tp.py), so in the backward it comes
    back from product to product, last product first, as a cotangent: the
    only way from one `custom_vjp`'s backward body into the next one's."""

    def __init__(self):
        self.taken = jnp.zeros((), jnp.float32)


class ExchangedWeight:
    """Stands where a weight [k, n] stands in `x @ w` (x [batch, ..., k],
    batch split over (dp, fsdp)); `dim` is the weight's dimension that is
    sharded over `fsdp`; `order` is the layer's `RingOrder` where its
    products are parallel/tp.py's."""

    def __init__(self, w: jax.Array, dim: int, mesh, order: RingOrder = None):
        self.w, self.dim, self.mesh, self.order = w, dim, mesh, order

    def __rmatmul__(self, x: jax.Array) -> jax.Array:
        return _matmul(x, self.w, self.dim, self.mesh)


def scale_by_rank(w: jax.Array, mesh, seq_axis) -> jax.Array:
    """A stacked norm scale [layers, d] -> [layers, ranks, d] in float32,
    one row for each rank of the axes that split the rows of the residual
    [batch over (dp, fsdp), seq over `seq_axis` (or whole: None), d], each
    rank holding its own. The transpose is the sum of the ranks' partial
    gradients, in float32 and rounded once to the leaf's dtype (the
    partitioner's all-reduce adds the rounded partials)."""
    axes = BATCH_AXES + ((seq_axis,) if seq_axis else ())
    ranks = math.prod(mesh.shape[a] for a in axes)
    rows = jnp.broadcast_to(w.astype(jnp.float32)[:, None],
                            (w.shape[0], ranks, w.shape[1]))
    return jax.lax.with_sharding_constraint(
        rows, NamedSharding(mesh, P(None, axes)))


class UnreducedScale:
    """Stands where a norm's scale [d] stands in `norm(x, scale)`; `rows`
    [ranks, d] is one layer's slice of `scale_by_rank`'s stack. x is seen as
    [ranks of the batch, rows, ranks of the sequence, rows, d] and each
    rank's rows are scaled by its own copy, so the cotangent of `rows` is
    each rank's partial sum over its own rows of x and no collective stands
    where the norm runs."""

    def __init__(self, rows: jax.Array, mesh, seq_axis):
        self.rows, self.mesh, self.seq_axis = rows, mesh, seq_axis

    def apply(self, norm, x: jax.Array) -> jax.Array:
        """norm(x, scale) -> [batch, seq, d], sharded as x is."""
        b, s, d = x.shape
        nb = math.prod(batch_split(self.mesh))
        ns = self.mesh.shape[self.seq_axis] if self.seq_axis else 1
        by_rank = x.reshape(nb, b // nb, ns, s // ns, d)
        return norm(by_rank, self.rows.reshape(nb, 1, ns, 1, d)).reshape(b, s, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _matmul(x, w, dim, mesh):
    return x @ w


def _matmul_fwd(x, w, dim, mesh):
    return x @ w, (x, w)


def _matmul_bwd(dim, mesh, res, dy):
    x, w = res
    dx = jax.lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))
    return dx, weight_grad(x, dy, dim, mesh).astype(w.dtype)


def weight_grad(x: jax.Array, dy: jax.Array, dim: int, mesh) -> jax.Array:
    """dw [k, n] of `x @ w` (x [batch, ..., k], dy [batch, ..., n], batch
    split over (dp, fsdp)), summed over `fsdp` by the ring below and left
    sharded over it on `dim`."""
    return weight_grads([x], [dy], dim, mesh)[0][0]


def weight_grads(xs, dys, dim: int, mesh, taken=None, alone: bool = False):
    """`weight_grad` of each pair of `xs` and `dys`, one region for all of
    them, and with `taken` (a `RingOrder`'s, as the backward body was handed
    it) what it is after the last of them: `_reduce_scatter_dws`. Called
    from inside parallel/tp.py's products (`_region`). `alone`: for a
    product outside the layers' loops (`_reduce_scatter_dws`)."""
    rows, whole = [P(None, AXIS)] * len(xs), None if taken is None else P()
    dws, taken = _region(
        lambda xs, dys, r, taken: _reduce_scatter_dws(
            [x[:, 0] for x in xs], [dy[:, 0] for dy in dys], dim, r[0], taken,
            alone),
        mesh, (rows, rows, P(AXIS), whole),
        ([P(*[None] * (1 + dim), AXIS)] * len(xs), whole))(
            [_by_rank(x, mesh) for x in xs], [_by_rank(dy, mesh) for dy in dys],
            jnp.arange(axis_size(mesh)), taken)
    return [dw.sum(0) for dw in dws], taken


_matmul.defvjp(_matmul_fwd, _matmul_bwd)


def _ring(n: int) -> list:
    """The permute's pairs: every rank hands on to the next."""
    return [(i, (i + 1) % n) for i in range(n)]


def _by_rank(a: jax.Array, mesh) -> jax.Array:
    """[batch, ..., width] -> [dp, fsdp, rows, width]: each rank's own rows."""
    return a.reshape(*batch_split(mesh), -1, a.shape[-1])


def _region(body, mesh, in_specs, out_specs):
    """`body` manual over `fsdp` alone; inside one of parallel/tp.py's
    products (manual over `tp` already) the region nests in theirs, on the
    mesh of the context. A rank's index comes in as data there: `axis_index`
    does not lower in a region that nests in another. (No result is the same
    on every rank and nothing differentiates through a region, so the check
    of what varies over `fsdp` has nothing to find; it costs a sixth of the
    four-chip step's time to trace and lower, and compiles to the same text.)"""
    nested = not jax.sharding.get_abstract_mesh().empty
    return jax.shard_map(body, mesh=None if nested else mesh, axis_names={AXIS},
                         in_specs=in_specs, out_specs=out_specs, check_vma=False)


def ring_products(groups, ws, dim: int, transposed: bool, mesh, *,
                  own_first: bool = False, keep: bool = False, kept=None):
    """For each group of operands (one a weight, [batch, ..., width], batch
    split over (dp, fsdp)): the sum over the weights of operand @ w, or of
    operand @ w^T with `transposed`, for weights [k, n] sharded over `fsdp`
    on `dim`, their shards going round the ring while the products run: a
    rank multiplies by its own shards first, its neighbour's travelling
    behind that, then by each as it arrives; one round serves every group.
    A shard that cuts the output yields its own columns of it. A shard that
    cuts the dimension a product sums over yields a partial sum, of the
    operand's matching columns: the partials are taken and added in float32
    and rounded once, as the one product by the gathered weight is.

    That order is the program's; the compiled step's is the scheduler's,
    which keeps it wherever an earlier product of the loop body stands
    between a shard's start and its done anyway. At the FIRST product of a
    layer's backward none does (the partitioner's gather of a weight can
    start only inside an earlier product of the same loop body, so there it
    runs alone on the compute stream: 29 MB for `w_down` at Mistral-7B
    widths), and there the compiler turns the order round: `acc + part` of
    two float32 partials commutes, it fuses the sum into the own shard's
    product, the fused one runs last, and the arrived shard's product stands
    straight behind the done, waiting for the transfer it was there to
    cover (6.4 ms of a 311 ms step; PERF.md section 6, PR 54). `own_first`
    pins the order for such a caller: a round's results are complete before
    the arrived shards are taken (an `optimization_barrier` over both), so
    the sum can fuse only into the arrived shard's product. The partials,
    their one rounding, the order of the ranks and every bit of the result
    stay. Not for every product that sums over the sharded dimension: the
    other shards' transfers are covered by the products before them, and
    pinned, the forward's sums come unfused (XLA's own estimate of the
    forward body at those widths: 4.445 -> 5.126 ms a layer).

    `keep`: also hands back the shards that arrived, a list a round of the
    ring (n - 1 of them) of one shard a weight -> (results, rounds). `kept`:
    such rounds, taken where this call would send the shards round again
    (a backward that follows its forward at once: the head's)."""
    n = axis_size(mesh)
    summed = 1 if transposed else 0   # the weight's dimension a product sums over
    ring = _ring(n)

    def body(groups, ws, r, kept):
        groups, r = [[x[:, 0] for x in group] for group in groups], r[0]
        size = ws[0].shape[dim]
        outs = [None] * len(groups)
        rounds = []
        for j in range(n):
            arriving = (None if j == n - 1 else kept[j] if kept
                        else [jax.lax.ppermute(w, AXIS, ring) for w in ws])
            at = ((r - j) % n) * size
            for t, group in enumerate(groups):
                if dim == summed:
                    parts = [_dot(jax.lax.dynamic_slice_in_dim(x, at, size, 2),
                                  w, summed, jnp.float32)
                             for x, w in zip(group, ws)]
                    outs[t] = parts if j == 0 else [
                        acc + part for acc, part in zip(outs[t], parts)]
                else:
                    part = sum(_dot(x, w, summed) for x, w in zip(group, ws))
                    if j == 0:
                        outs[t] = jnp.zeros((*part.shape[:2], n * size), part.dtype)
                    outs[t] = jax.lax.dynamic_update_slice_in_dim(
                        outs[t], part, at, 2)
            if own_first and arriving is not None:
                arriving, outs = jax.lax.optimization_barrier((arriving, outs))
            ws = arriving
            rounds.append(arriving)
        if dim == summed:
            outs = [sum(acc.astype(group[0].dtype) for acc in accs)
                    for accs, group in zip(outs, groups)]
        return [out[:, None] for out in outs], rounds[:-1] if keep else None

    rows, shards = P(None, AXIS), [P(*[None] * dim, AXIS)] * len(ws)
    outs, rounds = _region(
        body, mesh, ([[rows] * len(ws)] * len(groups), shards, P(AXIS),
                     kept and [shards] * (n - 1)),
        ([rows] * len(groups), [shards] * (n - 1) if keep else None))(
            [[_by_rank(x, mesh) for x in group] for group in groups], list(ws),
            jnp.arange(n), kept)
    outs = [out.reshape(*group[0].shape[:-1], out.shape[-1])
            for out, group in zip(outs, groups)]
    return (outs, rounds) if keep else outs


def _dot(x: jax.Array, w: jax.Array, summed: int, dtype=None) -> jax.Array:
    """x [dp, rows, width] times w, summing over w's dimension `summed`."""
    return jax.lax.dot_general(x, w, (((2,), (summed,)), ((), ())),
                               preferred_element_type=dtype)


def _reduce_scatter_dws(xs, dys, dim: int, r, taken=None, alone: bool = False):
    """For each pair: the sum over `fsdp` of dw = x^T dy (x [dp, rows, k],
    dy [dp, rows, n], this rank's rows), rank r keeping chunk r of dw's
    dimension `dim`: a ring of n - 1 steps. In step t rank r hands the
    running sum of chunk r - t - 1 to rank r + 1 and adds its own part of
    the chunk that arrives; at n = 2 that is one exchange of the partner's
    half. A chunk of dw is the product of a slice of x (or of dy), so no
    whole dw is made and cut: the compiler sends the first product off, and
    fuses the sum (and the write into the scan's stacked gradient) into the
    product of the chunk it keeps. (A whole dw cut in two read 374 ms a
    step, and a sum kept out of the product 358, against 338 this way.)

    With `taken`, the rings are taken off the link in the order they are
    listed, here and from one backward body of the layer to the next
    (`RingOrder`): a ring's kept product reads its slice at an offset that
    adds `taken`, and `taken` is read off the sum the ring before made
    (`_zero_read_off`). It is always 0, so no product, sum, rounding or byte
    sent changes; but the compiler cannot know, so the kept product stands
    behind the one before it, and each ring's arrival is taken straight
    before its own kept product, with the earlier rings' products between
    its start and there. Left alone, the scheduler takes the SMALLEST
    transfer's arrival first and the largest's last (it gives each permute
    the time IT needs, as if it had the link to itself), and all of a
    layer's rings share one link in one direction, which serves them in the
    order of their starts: the 2 MB of `wk` waited behind the 58 MB of
    `w_gate` and `w_up`, started before it and taken after it, with 1 ms of
    kept products that needed neither standing behind the wait (5 ms of a
    308 ms step at Mistral-7B widths on fsdp 2 x tp 2; PERF.md section 6,
    PR 57). An `optimization_barrier` does not do: it steers what fuses
    and is gone before the scheduler runs.

    What a barrier does do: a kept product that stands in that order reads
    its slice from an array of its own (`_staged`), made straight before it
    (the offset's `taken` keeps the slice, too, behind the ring before).
    Fused into the product, gate's and up's slice is of two chunks of x laid
    end to end, and the product wants BOTH whole in fast memory to read half
    their columns; the one that arrived over `tp` at the body's head the
    compiler has evicted to HBM by the tail, it fetches it back for the
    first of the twins and lets that copy die there, and the tiler halves
    the second's output window for an operand in HBM: 10.23 ms a step where
    its twin takes 7.28. A staged slice is half the bytes, born in fast
    memory and dead one instruction on, and the arrived chunk is never
    evicted: 7.12 and 7.13 (PERF.md section 6, PR 59). Every ring stages,
    not those two alone: the five other slices cost 0.95 ms a step and
    their products run 0.8 faster for an operand that is one array.

    `alone`: the ring of a product outside the layers' loops (the head's:
    67 MB, once a step), where nothing but the caller's own products is
    there to cover its way. The scheduler keeps a permute open only as long
    as ITS estimate of the transfer asks (0.9 ms for those 67 MB, which take
    1.46 on the link) and starts it no earlier; fused with the sum, the kept
    product stands behind the done, and one 0.73 ms product of the caller's
    is all that is left between. So the kept product is an array of its own
    too, the sum a pass of its own (0.3 ms), and the kept product runs
    under way with the caller's: 1.49 ms of matmul between start and done
    by XLA's estimate, and no wait on the chip (PERF.md section 6, PR 61)."""
    n = jax.lax.axis_size(AXIS)
    ring = _ring(n)

    def chunk(x, dy, j, behind=None):
        cut = (x, dy)[dim]
        size = cut.shape[-1] // n
        at = (j % n) * size
        if behind is not None:
            at = at + behind.astype(at.dtype)
        part = jax.lax.dynamic_slice_in_dim(cut, at, size, 2)
        if behind is not None:
            part = _staged(part)
        return jnp.einsum("prk,prn->pkn", *((part, dy), (x, part))[dim])

    accs = []
    for x, dy in zip(xs, dys):
        acc = chunk(x, dy, r - 1)
        for t in range(n - 1):
            own = chunk(x, dy, r - t - 2, taken)
            acc = jax.lax.ppermute(acc, AXIS, ring) + (_staged(own) if alone else own)
            if taken is not None:
                taken = _zero_read_off(acc)
        accs.append(acc)
    return accs, taken


def behind(a: jax.Array, b: jax.Array) -> jax.Array:
    """`a`, there no earlier than `b` is (a zero read off `b` is added)."""
    return a + _zero_read_off(b).astype(a.dtype)


def _staged(part: jax.Array) -> jax.Array:
    """`part` as an array of its own: the barrier keeps what makes it out of
    the fusion of what reads it."""
    return jax.lax.optimization_barrier(part)


def _zero_read_off(a: jax.Array) -> jax.Array:
    """0.0 in float32, computed from `a`'s first element in a way no
    simplification sees through (its bits with the lowest set are never 0):
    whatever uses it stands behind whatever makes `a`."""
    bits = jax.lax.bitcast_convert_type(
        a.reshape(-1)[0], jnp.dtype(f"uint{8 * a.dtype.itemsize}"))
    return ((bits | 1) == 0).astype(jnp.float32)
