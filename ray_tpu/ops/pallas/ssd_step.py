"""The one-token update of `ops/ssd.py:ssd_step` over a slot cache, as one
Pallas call that touches only the slots that serve a request.

`ops/pallas/selective_step.py` is the pattern (the whole stacked state
aliased to the output, the layer a prefetched scalar, the slots walked busy
ones first so that idle ones cost no DMA and skip the body); the recurrence
is Mamba-2's: the decay is a SCALAR a head, so it arrives as one row
[1, H P] (exp(dt A) of each head, repeated over the head's P channels) and
no exponential is taken per state element. The state of a slot and layer is
[N, H P] float32, 4.19 MB at the published widths: it goes through VMEM in
blocks of `_BLOCK` channels (grid: slot x channel block), each read once
and written once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util
from ray_tpu.ops.pallas.selective_step import live_slots  # noqa: F401  (the same walk)

KERNEL_NAME = "ssd_step"
_BLOCKS = (2048, 1024, 512, 256, 128)   # channels a grid step holds, widest first


def block_channels(hp: int) -> int:
    return next((b for b in _BLOCKS if hp % b == 0), 0)


def fits(state: jax.Array) -> bool:
    """On a TPU, for a float32 state whose channels tile the lanes."""
    return (_util.on_tpu() and state.dtype == jnp.float32
            and block_channels(state.shape[-1]) > 0 and state.shape[-2] % 8 == 0)


def _kernel(layer_ref, src_ref, busy_ref, decay_ref, dtx_ref, b_ref, c_ref,
            h_ref, h_out_ref, y_ref):
    i = pl.program_id(0)

    @pl.when(jnp.logical_or(i == 0, i < busy_ref[0]))
    def _update():
        h = decay_ref[...] * h_ref[...] + dtx_ref[...] * b_ref[...]
        h_out_ref[...] = h
        y_ref[...] = jnp.sum(h * c_ref[...], axis=0, keepdims=True)


def ssd_step_pallas(state: jax.Array, layer: jax.Array, slots, decay: jax.Array,
                    dtx: jax.Array, B: jax.Array, C: jax.Array):
    """state [L, S, N, HP] float32 (aliased to the first result); `layer` a
    scalar; `slots` = `live_slots(lengths)`; decay, dtx [S, HP] (exp(dt A)
    and dt x of every channel); B, C [S, N], float32 -> (state with the busy
    slots of `layer` advanced, y [S, HP] without the skip term: rows of idle
    slots hold no value)."""
    L, S, n, hp = state.shape
    src, n_busy = slots
    bd = block_channels(hp) or hp
    last = hp // bd - 1

    def chan(i, j, busy_ref):
        # an idle step stays on the block the last busy one ended on: no DMA
        return jnp.where(jnp.logical_or(i == 0, i < busy_ref[0]), j, last)

    row = pl.BlockSpec((None, 1, bd), lambda i, j, layer_ref, src_ref, busy_ref:
                       (src_ref[i], 0, chan(i, j, busy_ref)))
    col = pl.BlockSpec((None, n, 1), lambda i, j, layer_ref, src_ref, busy_ref:
                       (src_ref[i], 0, 0))
    whole = pl.BlockSpec((None, None, n, bd),
                         lambda i, j, layer_ref, src_ref, busy_ref:
                         (layer_ref[0], src_ref[i], 0, chan(i, j, busy_ref)))
    state, y = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S, hp // bd),
            in_specs=[row, row, col, col, whole],
            out_specs=[whole, row]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, 1, hp), jnp.float32)],
        # operands count the three prefetched scalars: the state is the 8th
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=KERNEL_NAME,
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), src, n_busy,
      decay[:, None], dtx[:, None], B[..., None], C[..., None], state)
    return state, y[:, 0]
