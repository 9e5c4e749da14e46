"""Mixture-of-Experts block with expert parallelism.

Absent from the reference entirely (SURVEY §2.4: EP/MoE = none in-tree) —
green-field, TPU-first design: GShard-style top-2 gating with static expert
capacity, dispatch/combine einsums over stacked expert weights [E, ...].
When the "expert" logical axis is sharded over a mesh axis, XLA compiles
the dispatch/combine einsums into all-to-alls over ICI — no manual
collectives. Static capacity keeps every shape compile-time constant
(XLA-friendly; overflowing tokens are dropped, the standard trade).

Two expert layers live here, SPLIT on purpose (PR 28): `moe_ffn` /
`top2_gating` is the train path's gate (softmax, top-2, a capacity that
drops, an auxiliary loss, dense dispatch tensors that shard over a mesh
axis); `dropless_moe` is the serving path's layer (no capacity, told which experts
it holds; it takes the router's choice and weights: `route_top_k`, sigmoid
scores and top-k by score + bias, or `route_softmax_top_k`, top-k by logit
and a softmax over the chosen). They share no tensor shape and no gate, so
neither is written in terms of the other.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def top2_gating(router_logits: jax.Array, capacity: int):
    """The capacity-dropping gate of the TRAIN path (`moe_ffn`): builds
    dispatch/combine tensors [T, E, C]; a token past an expert's capacity is
    dropped. The layer that never drops, for serving, is `dropless_moe`.

    router_logits: [T, E]. Returns (dispatch [T,E,C] bool-ish float,
    combine [T,E,C] float, aux_loss scalar).
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

    # top-1 and top-2 expert per token
    idx1 = jnp.argmax(probs, axis=-1)                       # [T]
    p1 = jnp.take_along_axis(probs, idx1[:, None], axis=-1)[:, 0]
    masked = probs * (1.0 - jax.nn.one_hot(idx1, E))
    idx2 = jnp.argmax(masked, axis=-1)
    p2 = jnp.take_along_axis(masked, idx2[:, None], axis=-1)[:, 0]

    # renormalize the pair
    denom = jnp.maximum(p1 + p2, 1e-9)
    w1, w2 = p1 / denom, p2 / denom

    # position of each token within its expert's capacity (running count)
    mask1 = jax.nn.one_hot(idx1, E)                         # [T, E]
    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1        # [T, E]
    mask2 = jax.nn.one_hot(idx2, E)
    pos2 = (jnp.cumsum(mask2, axis=0) + jnp.sum(mask1, axis=0, keepdims=True)
            - 1.0) * mask2

    keep1 = (pos1 < capacity) * mask1
    keep2 = (pos2 < capacity) * mask2

    def scatter(keep, pos, w):
        # [T,E] keep/pos + [T] weight -> [T,E,C]
        pos_idx = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        onehot_c = jax.nn.one_hot(pos_idx, capacity) * keep[..., None]
        return onehot_c * w[:, None, None]

    combine = scatter(keep1, pos1, w1) + scatter(keep2, pos2, w2)
    dispatch = (combine > 0).astype(router_logits.dtype)

    # load-balancing auxiliary loss (Switch/GShard)
    density = jnp.mean(mask1, axis=0)                       # fraction routed
    density_proxy = jnp.mean(probs, axis=0)
    aux_loss = jnp.sum(density * density_proxy) * (E * E) / E
    return dispatch.astype(jnp.float32), combine.astype(jnp.float32), aux_loss


def moe_ffn(x: jax.Array, router_w: jax.Array, w_gate: jax.Array,
            w_up: jax.Array, w_down: jax.Array,
            capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """MoE SwiGLU FFN. x: [B, S, d]; router_w: [d, E];
    expert weights stacked [E, d, ff] / [E, ff, d].

    Returns (out [B,S,d], aux_loss).
    """
    B, S, d = x.shape
    E = router_w.shape[-1]
    T = B * S
    capacity = max(1, int(capacity_factor * T / E))
    xt = x.reshape(T, d)

    router_logits = (xt.astype(jnp.float32) @ router_w.astype(jnp.float32))
    dispatch, combine, aux = top2_gating(router_logits, capacity)

    # dispatch tokens to experts: [E, C, d]
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    # per-expert SwiGLU over stacked weights (sharded over the expert axis)
    gate = jnp.einsum("ecd,edf->ecf", expert_in, w_gate)
    up = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    act = jax.nn.silu(gate) * up
    expert_out = jnp.einsum("ecf,efd->ecd", act, w_down)
    # combine back: [T, d]
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return out.reshape(B, S, d), aux


# row counts a small batch's grouped product is tried at before the whole
# T k (`dropless_moe`); a batch of more rows than _TIERED_UP_TO (a prefill)
# is tried at 5/4 of the share that lands here under even routing
_ROW_TIERS = (128, 256)
_TIERED_UP_TO = 1024
# the most bytes the LAST tier may gather ([T k, d] sorted rows, and as much
# again for what the experts give back). The last tier is what keeps the
# layer dropless under ANY routing, and a program's memory is the worst of
# its branches: a prompt pass of 8191 tokens at d 7680 would reserve two
# buffers of 1 GB for the routing that sends every assignment to the 8
# experts of 256 held here (the described v5e compiler: `temp` 4.76 GB
# against 3.57, beside 11.9 GB of weights and slots). Past it the last tier
# runs each held expert over all T tokens instead (`every_expert`: as
# dropless, slower, [T, f] at a time, no gather)
_GATHERED_BYTES = 256 * 2**20


def _row_tiers(rows: int, held: int, n_experts: int):
    """The row counts `dropless_moe` runs its grouped products at, smallest
    first; the last is always all `rows`, so nothing is ever dropped."""
    if rows <= _TIERED_UP_TO:
        tiers = [r for r in _ROW_TIERS if r < rows]
    else:
        even = -(-rows * held // n_experts)
        tiers = [r for r in (-(-(even * 5 // 4 + 256) // 256) * 256,) if r < rows]
    return tiers + [rows]


def route_top_k(x: jax.Array, router_w: jax.Array, bias: jax.Array, top_k: int,
                scale: float, renormalize: bool = True):
    """Sigmoid-scored top-k routing over ALL experts: x [T, d], router_w
    [d, E], bias [E] (the per-expert correction: it chooses, it does not
    weigh). Returns (expert ids [T, k] int32, weights [T, k] float32)."""
    # float32 at full precision: the choice of the k-th expert hangs on
    # differences of a few thousandths between neighbouring scores
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                  router_w.astype(jnp.float32),
                                  precision="highest"))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * scale


def route_softmax_top_k(x: jax.Array, router_w: jax.Array, top_k: int):
    """Logit-scored top-k routing over ALL experts, weighed by a softmax
    over the CHOSEN logits (no bias, no scale: the weights of a token sum to
    1): x [T, d], router_w [d, E]. Returns (expert ids [T, k] int32, weights
    [T, k] float32), as `route_top_k` does for `dropless_moe`."""
    # float32 at full precision, as `route_top_k`: the k-th expert hangs on
    # small differences between neighbouring logits
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision="highest")
    best, idx = jax.lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(best, axis=-1)


_GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gated(gate_act: str, product, x, w_gate, w_up):
    """The gated product of a held expert, act(x W_gate) * (x W_up), before
    its down projection: `product(x, w)` is the tier's own (one grouped
    product over the sorted rows, or a plain one over all tokens), the
    gate's activation the caller's ("silu": SwiGLU; "relu": ReGLU)."""
    return _GATE_ACTS[gate_act](product(x, w_gate)) * product(x, w_up)


def dropless_moe(x: jax.Array, idx: jax.Array, w: jax.Array,
                 w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                 held: Tuple[int, ...], n_experts: int,
                 valid: Optional[jax.Array] = None,
                 layer: Optional[jax.Array] = None, gate_act: str = "silu"):
    """The held experts' part of a dropless expert layer.

    x [T, d]; `idx`, `w` [T, k] are the router's choice over ALL
    `n_experts` experts and its weights (`route_top_k`,
    `route_softmax_top_k`); `held` names the global ids of the experts whose
    gated MLPs (`gate_act`: `_gated`; all of one width f) are stacked in
    w_gate / w_up [Eh, d, f] and
    w_down [Eh, f, d] (Eh = len(held)). Every (token, expert) assignment
    that lands on a held expert is computed, however skewed the routing: the
    assignments are sorted by held expert and go through one grouped matrix
    product per projection (`jax.lax.ragged_dot`: on the TPU a native
    grouped matmul that visits only the touched groups). Assignments to
    experts held elsewhere sort to the end, belong to no group and add
    nothing: on one chip the layer runs without its exchange. `valid` [T]
    bool, if given, marks the tokens that are real (no padding, no idle
    slot): the others are routed nowhere and their rows of y are zero.

    `layer` (a traced scalar), if given, says that w_gate / w_up / w_down
    are STACKS [L, Eh, ...] of which this call's layer is `layer`: the
    grouped products then run over all L x Eh groups with every other
    layer's group empty, so the layer's weights are read IN PLACE out of the
    stack. A scanned run's expert layers call it so: a layer's slice handed
    to the product inside the tiers' conditional is written down first,
    0.68 GB a layer and decode step at 36 experts of 4096 x 768 (read on
    the chip, PR 49: 13 ms of a 44 ms step).

    Returns (y [T, d] in x's dtype, assignments that landed here (int32),
    held experts with at least one token (int32))."""
    T, d = x.shape
    top_k, Eh = idx.shape[-1], len(held)
    if layer is None:
        local = jnp.full((n_experts,), Eh, jnp.int32).at[jnp.asarray(held)].set(
            jnp.arange(Eh, dtype=jnp.int32))
    else:
        # the same table made at trace time (`held` is static): inside a
        # `lax.scan` XLA:TPU's scatter emitter fails on the scatter of
        # constants above (`operand_indices.size() == 1`); the unrolled
        # callers keep it, and with it the text they always lowered to
        table = np.full((n_experts,), Eh, np.int32)
        table[list(held)] = np.arange(Eh, dtype=np.int32)
        local = jnp.asarray(table)
    group = local[idx]                              # Eh = not held here
    if valid is not None:
        group = jnp.where(valid[:, None], group, Eh)
    group = group.reshape(T * top_k)
    order = jnp.argsort(group, stable=True)
    token = (order // top_k).astype(jnp.int32)
    sizes = jnp.bincount(group, length=Eh + 1)[:Eh].astype(jnp.int32)
    landed = jnp.sum(sizes)
    groups = sizes
    if layer is not None:
        L = w_gate.shape[0]
        stacks = (w_gate, w_up, w_down)
        w_gate, w_up, w_down = (a.reshape((L * Eh,) + a.shape[2:]) for a in stacks)
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((L * Eh,), jnp.int32), sizes, (layer * Eh,))
    # row j of token t sits at inverse[t, j] of the sorted assignments
    inverse = jnp.zeros((T * top_k,), jnp.int32).at[order].set(
        jnp.arange(T * top_k, dtype=jnp.int32)).reshape(T, top_k)

    def experts(rows: int):
        """The held experts' MLPs over the first `rows` sorted assignments
        (the landed ones come first), weighed and summed per token."""
        def run(_):
            head = x[token[:rows]]                  # [rows, d], sorted by expert
            act = _gated(gate_act, lambda a, m: jax.lax.ragged_dot(a, m, groups),
                         head, w_gate, w_up)
            out = jax.lax.ragged_dot(act.astype(x.dtype), w_down, groups)
            # back to token order by a gather; an assignment that did not
            # land here reads the zero row behind the last
            out = jnp.concatenate([out, jnp.zeros((1, d), out.dtype)])
            picked = out[jnp.where(inverse < landed, jnp.minimum(inverse, rows), rows)]
            return jnp.sum(picked.astype(jnp.float32) * w[..., None], axis=1)
        return run

    def every_expert(_):
        """The same sums for any routing at all, without gathering a row:
        each held expert's MLP over ALL T tokens, one expert after
        another, weighed by what the token gave that expert (mostly 0)."""
        chose = group.reshape(T, top_k)

        def one(y, e):
            gate, up, down, j = e
            weight = jnp.sum(jnp.where(chose == j, w, 0.0), axis=1)
            out = _gated(gate_act, jnp.matmul, x, gate, up).astype(x.dtype) @ down
            return y + out.astype(jnp.float32) * weight[:, None], None

        mine = (w_gate, w_up, w_down) if layer is None else tuple(
            jax.lax.dynamic_index_in_dim(a, layer, 0, False) for a in stacks)
        y, _ = jax.lax.scan(one, jnp.zeros((T, d), jnp.float32),
                            mine + (jnp.arange(Eh),))
        return y

    # A grouped product costs each touched expert one row TILE of work, and
    # the tile is as tall as the row count allows (measured on a v5e, 64
    # experts of 2304 x 1024, 128 landed rows: 1.07 ms at 512 rows, 0.77 at
    # 256, 0.65 at 128). A decode step offers T k rows of which about
    # held / all land here, so it runs the smallest tier that holds what
    # landed; a prefill gathers, multiplies and weighs 5/16 of its T k rows
    # instead of all. The whole row count stays the last tier: nothing is
    # dropped.
    tiers = _row_tiers(T * top_k, Eh, n_experts)
    runs = [experts(r) for r in tiers]
    if T * top_k * d * x.dtype.itemsize > _GATHERED_BYTES:
        runs[-1] = every_expert
    y = jax.lax.switch(sum((landed > r).astype(jnp.int32) for r in tiers[:-1]),
                       runs, None) if len(tiers) > 1 else runs[0](None)
    return y.astype(x.dtype), landed, jnp.sum(sizes > 0).astype(jnp.int32)
