"""Pipeline parallelism: GPipe-style microbatching over a `pp` mesh axis.

The reference has no native pipeline parallelism (SURVEY §2.4: PP is
"indirect only", delegated to DeepSpeed via Accelerate) — this is the
TPU-native design, green-field:

  - the transformer's layer-stacked parameters ([L, ...] leaves, see
    `models.transformer.init_params`) are sharded over the `pp` mesh axis:
    each pipeline stage holds L/pp contiguous layers in HBM;
  - the forward runs inside a *partial-manual* `jax.shard_map` over only
    the 'pp' axis (dp/fsdp/tp stay automatic — XLA keeps inserting their
    collectives from sharding annotations as usual);
  - a `lax.scan` over n_micro + pp - 1 ticks implements the GPipe
    schedule: every tick each stage applies its layer block to its current
    microbatch activation, then `lax.ppermute` shifts activations one
    stage down the ring (compiled to ICI collective-permutes that overlap
    with the next tick's compute);
  - the backward pass is generated by autodiff — `ppermute` has a
    transpose rule, so the reverse schedule is the mirror-image pipeline,
    with per-tick `jax.checkpoint` rematerialization.

Composition: pp × dp × fsdp × tp (sequence parallelism composes with the
non-pp step instead; enforce sp == 1 here).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.transformer import (ModelConfig, _layer, init_params,
                                        maybe_remat)
from ray_tpu.ops.layers import rms_norm, rotary_embedding


def _stage_fn(cfg: ModelConfig, layers_local: Dict[str, jax.Array],
              x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply this stage's L/pp layers to one microbatch [mb, s, d]."""
    layer = maybe_remat(functools.partial(_layer, cfg, None), cfg)

    def body(h, lp):
        h, _ = layer(h, lp, cos, sin)
        return h, None

    x, _ = jax.lax.scan(body, x, layers_local)
    return x


def pipeline_blocks(cfg: ModelConfig, mesh: Mesh, layer_params: Dict[str, Any],
                    x: jax.Array, cos: jax.Array, sin: jax.Array,
                    axis_name: str = "pp") -> jax.Array:
    """Run the transformer blocks as a pp-stage pipeline.

    x: [n_micro, mb, s, d] embedded activations (microbatch leading).
    layer_params: layer-stacked tree, leading axis L sharded over `axis_name`.
    Returns activations of the same shape, replicated over pp.
    """
    n_micro = x.shape[0]

    def run(layers_local, xs, cos, sin):
        n_stages = jax.lax.psum(1, axis_name)
        stage = jax.lax.axis_index(axis_name)
        shift = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        state0 = jnp.zeros(xs.shape[1:], xs.dtype)
        # Per-tick inputs ride the scan as an OPERAND (padded with repeats
        # of the last microbatch for drain ticks): indexing a closed-over
        # xs made its backward accumulate a full-buffer gradient scatter
        # per tick — O(ticks * n_micro) traffic that inverted the bubble
        # model in measurements; scan-operand gradients are per-slice.
        xs_seq = jnp.concatenate(
            [xs, jnp.broadcast_to(xs[-1:],
                                  (n_stages - 1,) + xs.shape[1:])], axis=0)

        def tick(state, inp):
            cur = jnp.where(stage == 0, inp, state)
            # Every stage computes every tick; bubble (fill/drain) ticks
            # process values that are never selected into outputs. Skipping
            # them (lax.cond) or fusing the loss into the last stage both
            # trip XLA's SPMD partitioner when tp/fsdp collectives sit under
            # the conditional/manual mix — and since ticks are lockstep,
            # skipped stages would idle anyway: bubble compute costs energy,
            # not wall-clock. Bubble fraction = (pp-1)/(n_micro+pp-1),
            # amortized away by raising n_micro.
            y = _stage_fn(cfg, layers_local, cur, cos, sin)
            state = jax.lax.ppermute(y, axis_name, shift)
            # Each tick EMITS its stage output instead of scattering into a
            # carried [n_micro, ...] buffer: a buffer in the carry made
            # every tick copy (and backward save) the whole thing —
            # O(n_micro^2) memory traffic that swamped the bubble model.
            return state, y

        _, ys = jax.lax.scan(tick, state0, xs_seq)
        # the last stage produced microbatch m's output at tick
        # m + n_stages - 1: its real outputs are the trailing n_micro ticks
        outputs = jax.lax.dynamic_slice_in_dim(ys, n_stages - 1, n_micro, 0)
        # only the last stage holds real outputs; zero the rest and psum to
        # replicate (ppermute multicast is disallowed; one all-reduce over pp
        # of the final activations is cheap next to the layer matmuls)
        outputs = jnp.where(stage == n_stages - 1, outputs, 0)
        return jax.lax.psum(outputs, axis_name)

    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(axis_name), P(), P(), P()),
        out_specs=P(),
        axis_names={axis_name},
        check_vma=False,
    )(layer_params, x, cos, sin)


def pp_forward(params: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig,
               mesh: Mesh, n_micro: int) -> jax.Array:
    """tokens [b, s] -> logits [n_micro, mb, s, vocab] fp32 via the pipeline.

    Embedding lookup and the LM head run outside the manual region (they are
    small and stay auto-sharded over dp/fsdp/tp)."""
    b, s = tokens.shape
    assert b % n_micro == 0, f"batch {b} not divisible by n_micro {n_micro}"
    mb = b // n_micro
    x = params["embed"][tokens].astype(cfg.dtype).reshape(n_micro, mb, s, -1)
    cos, sin = rotary_embedding(jnp.arange(s), cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[None], sin[None]

    x = pipeline_blocks(cfg, mesh, params["layers"], x, cos, sin)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head.astype(cfg.dtype)).astype(jnp.float32)


def pp_loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
               cfg: ModelConfig, mesh: Mesh, n_micro: int):
    """Next-token cross entropy through the pipeline (same batch forms and
    masking as `models.transformer.loss_fn`, dense layers only)."""
    from ray_tpu.models.transformer import split_batch, token_nll

    inputs, targets, mask = split_batch(batch)
    logits = pp_forward(params, inputs, cfg, mesh, n_micro)
    b, s = inputs.shape
    micro = (n_micro, b // n_micro, s)
    loss = token_nll(logits, targets.reshape(micro),
                     None if mask is None else mask.reshape(micro))
    return loss, {"loss": loss, "ntokens": targets.size}


def make_pp_train_step(cfg: ModelConfig, mesh: Mesh,
                       optimizer: Optional[optax.GradientTransformation] = None,
                       n_micro: int = 4, donate: bool = True):
    """Pipeline-parallel analog of `train.step.make_train_step`.

    Returns (step_fn, init_fn, shardings); step_fn(state, batch) with batch
    {"inputs"/"targets": [b, s]} sharded over (dp, fsdp). The mesh must have
    pp > 1 (or 1, in which case this degenerates to the plain step) and
    sp == 1."""
    from ray_tpu.parallel.mesh import AxisRules, DEFAULT_RULES
    from ray_tpu.train.step import (TrainState, _shard_opt_like_params,
                                    default_optimizer)
    from ray_tpu.models.transformer import param_logical_axes
    from ray_tpu.parallel.mesh import logical_sharding

    if "sp" in mesh.shape and mesh.shape["sp"] != 1:
        raise ValueError("pipeline parallelism requires sp == 1 "
                         "(sequence parallelism composes with the non-pp step)")
    if cfg.n_experts > 0:
        raise NotImplementedError("pp + MoE not yet supported")
    optimizer = optimizer or default_optimizer()

    # params: like DEFAULT_RULES but the layer-stack axis shards over pp
    rules = AxisRules({**DEFAULT_RULES.rules, "layers": "pp"})
    p_axes = param_logical_axes(cfg)
    p_sh = logical_sharding(mesh, p_axes, rules)
    params_shape = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    replicated = NamedSharding(mesh, P())
    opt_sh = _shard_opt_like_params(opt_shape, params_shape, p_sh, replicated)
    sh = TrainState(params=p_sh, opt_state=opt_sh, step=replicated)
    b_sh = {"inputs": NamedSharding(mesh, P(("dp", "fsdp"))),
            "targets": NamedSharding(mesh, P(("dp", "fsdp")))}

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        (loss, aux), grads = jax.value_and_grad(pp_loss_fn, has_aux=True)(
            state.params, batch, cfg, mesh, n_micro)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "grad_norm": optax.global_norm(grads),
                   "step": state.step}
        return TrainState(new_params, new_opt, state.step + 1), metrics

    step_fn = jax.jit(
        step, in_shardings=(sh, b_sh),
        out_shardings=(sh, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else ())

    def init(rng):
        params = init_params(rng, cfg)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    return step_fn, jax.jit(init, out_shardings=sh), sh
