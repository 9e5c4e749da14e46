"""The dense block's products over `tp`, and the head's, with the residual
stream's rows riding sharded over `tp` between them.

Left to the partitioner (the Megatron form), a row-parallel product (`wo`,
`w_down`) yields a partial sum of the WHOLE residual [batch, seq, d], and a
blocking all-reduce over `tp` runs on the compute stream behind it: two a
layer forward, two backward (behind the `dx` of the column-parallel
products), 34-36 ms of a 340 ms step at Mistral-7B widths on fsdp 2 x tp 2,
and every norm and residual add is done on each `tp` rank over the same
rows. What XLA:TPU does run in the background is a `collective-permute`
(parallel/fsdp.py, PERF.md section 6, PR 31).

So between the products the residual stream is [batch, seq over tp, d], and
the two kinds of product carry the transfer themselves, each a `custom_vjp`
whose body is a `shard_map` manual over `tp` (`dp` stays the partitioner's):

  gather_matmul   x [b, s over tp, k] @ several w [k, n over tp]: a rank
                  multiplies its own rows while they travel to its
                  neighbour, then the rows that arrived (a ring of tp - 1
                  permutes); one gather serves every weight;
  matmul_scatter  x [b, s, k over tp] @ w [k over tp, d]: the partial sums
                  of the rows another rank owns are made first and sent on,
                  the rank's own rows are made while they travel and what
                  arrives is added: [b, s over tp, d].

Each is the other's transpose, so the backward of one is the ring of the
other; the `dw` products wait for no transfer of `tp`'s. Sums over `tp` are
taken in the activations' dtype over the same ranks as the partitioner's
all-reduce.

The weights come as `fsdp.ExchangedWeight`s (the mesh has fsdp > 1 too:
`models/transformer._rows_mesh`): a `dw` is still summed over `fsdp` by that
module's ring, once a layer in exact shards, and the weights' gathers over
`fsdp` ride inside the products as well (`fsdp.ring_products`: the shards go
round fsdp's ring, a product by a rank's own shard covering its neighbour's
way; the FFN's `w_down` in the backward, the first product a layer's
backward can run, pins that order, which the compiler turns round there:
`_matmul_scatter_bwd`). Left to the partitioner they come one at a time,
each started where the one before is first used; the four all-reduces were
when they caught up, and with those gone the step waited for weights instead
(344 ms a step against the parent's 338: PERF.md section 6, PR 38).

The seven `dw` rings of a layer live in four backward bodies and share one
`fsdp` link. Every product takes the layer's `fsdp.RingOrder` beside its
operands and hands it on, so each backward body gets, as a cotangent, what
the body before it left (the backward runs the products last first) and its
rings stand behind that one's: the arrivals are taken in the order of the
starts, `w_down`'s to `wv`'s, each ring's kept product between
(`fsdp._reduce_scatter_dws`; PERF.md section 6, PR 57).

The head's product is gate's and up's by shape (rows [b, s over tp, d] by
`lm_head` [d over fsdp, vocab over tp]) but stands ALONE between the
layers' two loops, once a step (`gather_matmul_alone`). No product before
it covers its shard's way: a permute spans no loop whose body holds
permutes, so the shard starts behind the forward loop; the own shard's
product is pinned first and covers two thirds of its way, and the rows' gather over
`tp` runs inside what is left, so the product is ONE over the whole
sequence in the sequence's order and the logits need no placing by rank.
Its backward follows its forward at once: the shard that arrived is kept
and none is sent again, and `lm_head`'s gradient ring is taken before
`dx` is handed to the backward loop, behind the kept half's product and a
`dx` product. The partitioner's head gathered `lm_head` whole and
reduce-scattered its gradient on the compute stream, 1.44 and 1.81 ms of a
297 ms step (PERF.md section 6, PR 61).

`chunks`: the whole-sequence side of a product may stay a tuple of chunks,
one a rank of the ring and in each rank's OWN order (its rows first, then
the ones that arrived first, ...), which costs no placement by rank and no
copy into one array: a product writes its chunk where the backward will read
it. Right where only row-wise operations lie between a gather and the
scatter that undoes it (the FFN); the sequence's order is lost, so not for
attention.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import fsdp

AXIS = "tp"
# What a product hands on is named, for a remat policy that keeps matmul
# results (`models/transformer.maybe_remat`): it is one, whole in sequence or
# summed over `tp`, as the partitioner's product is; unnamed, the policy
# would keep the chunks' products and the backward place them, or send the
# partial sums, a second time.
SAVED = "tp_product"
_ROWS = P(None, AXIS)          # [b, s over tp, width], and a weight [k, n over tp]
_COLS = P(None, None, AXIS)    # [b, s, width over tp]
_W_ROWS = P(AXIS)              # a weight [k over tp, d]


def axis_size(mesh) -> int:
    """Size of the mesh's `tp` axis (1 without a mesh or the axis)."""
    return 1 if mesh is None else mesh.shape.get(AXIS, 1)


def shard_rows(x: jax.Array, mesh) -> jax.Array:
    """x [b, s, d] with its rows over `tp`: each rank keeps its own slice of
    the sequence (no transfer where x was replicated over `tp`)."""
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(fsdp.BATCH_AXES, AXIS, None)))


def gather_matmul(x: jax.Array, ws: Sequence, mesh, *,
                  chunks: bool = False) -> Tuple:
    """x [b, s over tp, k] @ each w [k, n over tp] (`fsdp.ExchangedWeight`s,
    sharded over fsdp on the same dimension) -> [b, s, n over tp] each, or
    with `chunks` a tuple of tp chunks [b, s / tp, n over tp] each."""
    dim, = {w.dim for w in ws}
    order, = {w.order for w in ws}
    ys, order.taken = _gather_matmul(x, tuple(w.w for w in ws), order.taken,
                                     dim, mesh, chunks)
    return ys


def gather_matmul_alone(x: jax.Array, w, mesh) -> jax.Array:
    """x [b, s over tp, k] @ w [k, n over tp] (an `fsdp.ExchangedWeight`)
    -> [b, s, n over tp], for a product that stands alone between the
    layers' loops with its backward straight behind it (the head's)."""
    return _lone_matmul(x, w.w, w.dim, mesh)


def matmul_scatter(x, w, mesh) -> jax.Array:
    """x [b, s, k over tp], or the tuple of chunks `gather_matmul` gave, @
    w [k over tp, d] (an `fsdp.ExchangedWeight`), summed over `tp` ->
    [b, s over tp, d]."""
    y, w.order.taken = _matmul_scatter(x, w.w, w.order.taken, w.dim, mesh)
    return y


# ------------------------------------------------ inside the manual region
# A rank's view: position t of the whole sequence's chunks is the one that
# reached it after t steps of the ring r -> r + 1, the rows of rank r - t.


def _ring() -> list:
    return fsdp._ring(jax.lax.axis_size(AXIS))


def _gather(x: jax.Array) -> list:
    """This rank's rows [b, s / n, w] -> every rank's, by position."""
    chunks = [x]
    for _ in range(jax.lax.axis_size(AXIS) - 1):
        chunks.append(jax.lax.ppermute(chunks[-1], AXIS, _ring()))
    return chunks


def _ring_sum(part) -> jax.Array:
    """Sum over `tp` of `part(t)` (this rank's partial sum of the rows at
    position t), each rank ending with its own rows: the partial of the
    rows one step behind goes off first, every rank adds its own to what
    arrives and hands it on, and its own rows' comes last. The barrier
    keeps the sum out of the product beside it: fused into it (as the
    compiler would), the product waits for the transfer it is there to
    cover."""
    n = jax.lax.axis_size(AXIS)
    acc = part(1 % n)
    for t in range(2, n + 1):
        arrived, own = jax.lax.optimization_barrier(
            (jax.lax.ppermute(acc, AXIS, _ring()), part(t % n)))
        acc = arrived + own
    return acc


def _offset(t: int, rows: int):
    n = jax.lax.axis_size(AXIS)
    return ((jax.lax.axis_index(AXIS) - t) % n) * rows


def _in_sequence(chunks: Sequence[jax.Array]) -> jax.Array:
    """Chunks by position -> [b, s, w] in the sequence's order."""
    b, rows, w = chunks[0].shape
    out = jnp.zeros((b, rows * len(chunks), w), chunks[0].dtype)
    for t, c in enumerate(chunks):
        out = jax.lax.dynamic_update_slice_in_dim(out, c, _offset(t, rows), 1)
    return out


def _rows_at(a, t: int) -> jax.Array:
    """The rows at position t: of a tuple of chunks, or of a whole
    [b, s, w] in the sequence's order."""
    if isinstance(a, (tuple, list)):
        return a[t]
    rows = a.shape[1] // jax.lax.axis_size(AXIS)
    return jax.lax.dynamic_slice_in_dim(a, _offset(t, rows), rows, 1)


def _like(a, chunks: Sequence[jax.Array]):
    """Chunks by position in the form `a` has: a tuple, or one array in the
    sequence's order."""
    return tuple(chunks) if isinstance(a, (tuple, list)) else _in_sequence(chunks)


def _one_array(a) -> jax.Array:
    """[b, s, w] for a `dw`: a tuple of chunks laid end to end (both of a
    `dw`'s operands then run in this rank's order of rows)."""
    return jnp.concatenate(a, axis=1) if isinstance(a, (tuple, list)) else a


def _weight_grads(x, dys, ws, dim: int, mesh, taken) -> Tuple:
    """dw of each `x @ w` from x [b, s, k] and its dy [b, s, n] over the
    whole sequence, summed over `fsdp` by fsdp.py's rings, and `taken` behind
    the last of them (`fsdp.RingOrder`)."""
    dws, taken = fsdp.weight_grads(
        [_one_array(x)] * len(dys), list(map(_one_array, dys)), dim, mesh, taken)
    return tuple(dw.astype(w.dtype) for dw, w in zip(dws, ws)), taken


def _manual(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, axis_names={AXIS}, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _cols(like):
    """The spec of a whole-sequence side: one array's, or a tuple's."""
    return (_COLS,) * len(like) if isinstance(like, (tuple, list)) else _COLS


# ------------------------------------------------------------ the products
# Every weight comes as an `fsdp.ExchangedWeight` (`dim`: its dimension over
# fsdp), and a product by it is `fsdp.ring_products`: the weight's shards go
# round fsdp's ring inside it.


def _gathered_products(x, ws, dim, mesh, chunks):
    n = axis_size(mesh)

    def body(x, ws):
        rows = _gather(x)
        each = [fsdp.ring_products([[c] for c in rows], [w], dim, False, mesh)
                for w in ws]
        return tuple(tuple(ys) if chunks else _in_sequence(ys) for ys in each)

    out = ((_COLS,) * n if chunks else _COLS,) * len(ws)
    ys = _manual(body, mesh, (_ROWS, (_ROWS,) * len(ws)), out)(x, ws)
    return jax.tree_util.tree_map(lambda y: checkpoint_name(y, SAVED), ys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gather_matmul(x, ws, taken, dim, mesh, chunks):
    return _gathered_products(x, ws, dim, mesh, chunks), taken


def _gather_matmul_fwd(x, ws, taken, dim, mesh, chunks):
    # (the products themselves, not `_gather_matmul`: a remat policy sees
    # through a `shard_map` to the dots it may keep, not through a custom_vjp)
    return (_gathered_products(x, ws, dim, mesh, chunks), taken), (x, ws)


def _gather_matmul_bwd(dim, mesh, chunks, res, cts):
    def body(x, ws, dys, taken):
        parts = fsdp.ring_products(
            [[_rows_at(dy, t) for dy in dys]
             for t in range(jax.lax.axis_size(AXIS))], ws, dim, True, mesh)
        dx = _ring_sum(lambda t: parts[t])
        rows = _like(dys[0], _gather(x))  # in flight behind the dx products
        return dx, *_weight_grads(rows, dys, ws, dim, mesh, taken)

    dys, taken = cts
    each = (_ROWS,) * len(dys)
    return _manual(body, mesh, (_ROWS, each, tuple(map(_cols, dys)), P()),
                   (_ROWS, each, P()))(*res, tuple(dys), taken)


_gather_matmul.defvjp(_gather_matmul_fwd, _gather_matmul_bwd)


def _scattered_product(x, w, dim, mesh):
    def body(x, w):
        parts = fsdp.ring_products(
            [[_rows_at(x, t)] for t in range(jax.lax.axis_size(AXIS))], [w],
            dim, False, mesh)
        return _ring_sum(lambda t: parts[t])

    return checkpoint_name(
        _manual(body, mesh, (_cols(x), _W_ROWS), _ROWS)(x, w), SAVED)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _matmul_scatter(x, w, taken, dim, mesh):
    return _scattered_product(x, w, dim, mesh), taken


def _matmul_scatter_fwd(x, w, taken, dim, mesh):
    return (_scattered_product(x, w, dim, mesh), taken), (x, w)


def _matmul_scatter_bwd(dim, mesh, res, cts):
    def body(x, w, dy, taken):
        rows = _gather(dy)
        if isinstance(x, tuple):
            # the FFN's, first in a layer's backward: dy is there from the
            # start and travels behind what remat computes again, so ONE
            # product over the whole sequence. No earlier product of the
            # body covers `w_down`'s shard on its way, so the own shard's
            # product is pinned before the arrived shard's; here alone
            # (every other shard has products before it, and a pinned sum
            # comes unfused: `fsdp.ring_products`)
            whole_dy = _one_array(rows)
            dx, = fsdp.ring_products([[whole_dy]], [w], dim, True, mesh,
                                       own_first=True)
            dx = tuple(jnp.split(dx, len(x), axis=1))
        else:
            whole_dy = _in_sequence(rows)
            dx = _in_sequence(fsdp.ring_products(
                [[c] for c in rows], [w], dim, True, mesh))
        (dw,), taken = _weight_grads(x, [whole_dy], [w], dim, mesh, taken)
        return dx, dw, taken

    x, w = res
    dy, taken = cts
    return _manual(body, mesh, (_cols(x), _W_ROWS, _ROWS, P()),
                   (_cols(x), _W_ROWS, P()))(x, w, dy, taken)


_matmul_scatter.defvjp(_matmul_scatter_fwd, _matmul_scatter_bwd)


# The product that stands alone (the head's): whole over the sequence.


def _lone_products(x, w, dim, mesh):
    """(x @ w, x whole over `tp`, the shards of w that arrived: one a round
    of fsdp's ring)."""
    def body(x, w):
        whole = _in_sequence(_gather(x))
        (y,), rounds = fsdp.ring_products([[whole]], [w], dim, False, mesh,
                                          own_first=True, keep=True)
        return y, whole, tuple(shard for shard, in rounds)

    kept = (_ROWS,) * (fsdp.axis_size(mesh) - 1)
    return _manual(body, mesh, (_ROWS, _ROWS), (_COLS, P(), kept))(x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lone_matmul(x, w, dim, mesh):
    return _lone_products(x, w, dim, mesh)[0]


def _lone_matmul_fwd(x, w, dim, mesh):
    y, whole, kept = _lone_products(x, w, dim, mesh)
    return y, (whole, w, kept)


def _lone_matmul_bwd(dim, mesh, res, dy):
    def body(whole, w, kept, dy):
        (dw,), _ = fsdp.weight_grads([whole], [dy], dim, mesh, alone=True)
        dx, = fsdp.ring_products([[dy]], [w], dim, True, mesh,
                                 kept=[[shard] for shard in kept])
        dx = _ring_sum(lambda t: _rows_at(dx, t))
        # the gradient's ring is taken before dx is handed on: what follows
        # is the layers' backward loop, and a ring left open would start
        # behind it, where the partitioner's next all-reduce waits on the
        # link for the 67 MB in flight
        return fsdp.behind(dx, dw), dw.astype(w.dtype)

    whole, w, kept = res
    return _manual(body, mesh, (P(), _ROWS, (_ROWS,) * len(kept), _COLS),
                   (_ROWS, _ROWS))(whole, w, kept, dy)


_lone_matmul.defvjp(_lone_matmul_fwd, _lone_matmul_bwd)
