"""The one-token update of `ops/mamba.py:selective_step` over a slot cache,
as one Pallas call that touches only the slots that serve a request.

XLA's form of the step reads the stacked state twice (once to reduce y from
it, once to rewrite it) and writes it once, for EVERY slot of the cache: at
256 slots x 26 layers x 320 KB that is 7.2 GB a step whatever is busy. This
kernel takes the WHOLE stacked state [layers, slots, d_state, d_inner]
aliased to its output, the layer as a prefetched scalar, and walks the slots
busy ones first (`live_slots`, computed once a step outside the layer
scans): a busy slot's [d_state, d_inner] block is read once, updated and
written once; the idle slots behind them repeat the last busy slot's block
index, so the pipeline issues no DMA for them and the body is skipped. An
idle slot's state stays as it was; its row of y is not written (the caller
masks it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util

KERNEL_NAME = "selective_step"


def fits(state: jax.Array) -> bool:
    """On a TPU, for a float32 state whose channels tile the lanes."""
    return (_util.on_tpu() and state.dtype == jnp.float32
            and state.shape[-1] % 128 == 0 and state.shape[-2] % 8 == 0)


def live_slots(lengths: jax.Array):
    """(`src` [B]: the slot grid step i works on or, past the busy ones,
    stays on; `n_busy` [1]), both int32. A slot is busy iff its length is
    above 0 (the engine's idle rule). Step 0 always works, so that the one
    output block an all-idle cache still writes back holds real values."""
    live = lengths > 0
    order = jnp.argsort(~live, stable=True)
    n_busy = jnp.sum(live)
    at = jnp.minimum(jnp.arange(lengths.shape[0]), jnp.maximum(n_busy - 1, 0))
    return order[at].astype(jnp.int32), jnp.reshape(n_busy, (1,)).astype(jnp.int32)


def _kernel(layer_ref, src_ref, busy_ref, dt_ref, u_ref, b_ref, c_ref, a_ref,
            h_ref, h_out_ref, y_ref):
    i = pl.program_id(0)

    @pl.when(jnp.logical_or(i == 0, i < busy_ref[0]))
    def _update():
        dt = dt_ref[...]                                      # [1, di]
        h = jnp.exp(dt * a_ref[...]) * h_ref[...] + (dt * u_ref[...]) * b_ref[...]
        h_out_ref[...] = h
        y_ref[...] = jnp.sum(h * c_ref[...], axis=0, keepdims=True)


def selective_step_pallas(state: jax.Array, layer: jax.Array, slots, u: jax.Array,
                          dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array):
    """state [L, S, n, di] float32 (aliased to the first result); `layer` a
    scalar; `slots` = `live_slots(lengths)`; u, dt [S, di]; A [n, di];
    B, C [S, n], float32 -> (state with the busy slots of `layer` advanced,
    y [S, di] without the skip term: rows of idle slots hold no value)."""
    L, S, n, di = state.shape
    src, n_busy = slots

    def per_slot(*block):
        return pl.BlockSpec((None,) + block,
                            lambda i, layer_ref, src_ref, busy_ref:
                            (src_ref[i],) + (0,) * len(block))

    whole = pl.BlockSpec((None, None, n, di),
                         lambda i, layer_ref, src_ref, busy_ref:
                         (layer_ref[0], src_ref[i], 0, 0))
    state, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S,),
            in_specs=[per_slot(1, di), per_slot(1, di), per_slot(n, 1),
                      per_slot(n, 1),
                      pl.BlockSpec((n, di), lambda i, *_: (0, 0)), whole],
            out_specs=[whole, per_slot(1, di)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, 1, di), jnp.float32)],
        # operands count the three prefetched scalars: the state is the 9th
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name=KERNEL_NAME,
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), src, n_busy,
      dt[:, None], u[:, None], B[..., None], C[..., None], A, state)
    return state, y[:, 0]
