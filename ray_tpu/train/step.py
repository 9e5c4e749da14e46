"""Sharded training step: the pjit'd heart of the Train stack.

Where the reference's TorchTrainer wraps user loops around torch DDP/FSDP
(`python/ray/train/torch/config.py:69`, `train_loop_utils.py:92-101`), the
TPU-native step is one jitted function whose parallelism is entirely in the
in/out shardings: dp×fsdp shard the batch, fsdp shards parameters ZeRO-3
style (XLA inserts the all-gathers), tp shards heads/mlp. No collective
calls appear below — the compiler emits them over ICI/DCN from the
sharding annotations. Two the model spells itself, as
permutes that run behind matmuls where the partitioner's all-reduce blocks
the compute stream: with fsdp > 1 the dense block's weight gradients are
summed over fsdp by `parallel/fsdp.py`, and with tp > 1 its gathers and
scatters over tp ride inside the products (`parallel/tp.py`), the head's
product with the block's. A third the
model moves: there the two norm scales' gradients leave the layers' scan as
each rank's partial sums and are all-reduced once a step, where the
partitioner's reduction waits for every chip once a layer. The step's
`xla.compile` spans say which forms it has (`grad_exchanges_per_layer`,
`tp_exchanges_per_layer`, `norm_grad_reductions_in_layers`,
`ring_products_own_first`, `dw_rings_ordered`, `head_exchanged`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models.transformer import (
    ModelConfig,
    dw_rings_ordered,
    grad_exchanges_per_layer,
    head_exchanged,
    init_params,
    loss_fn,
    norm_grad_reductions_in_layers,
    param_logical_axes,
    ring_products_own_first,
    split_batch,
    tp_exchanges_per_layer,
)
from ray_tpu.parallel.mesh import AxisRules, DEFAULT_RULES, logical_sharding


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


def default_optimizer(learning_rate: float = 3e-4,
                      weight_decay: float = 0.1,
                      warmup_steps: int = 100,
                      total_steps: int = 10000) -> optax.GradientTransformation:
    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(sched, b1=0.9, b2=0.95, weight_decay=weight_decay,
                    mu_dtype=jnp.float32),
    )


def fused_adamw_optimizer(learning_rate: float = 3e-4,
                          weight_decay: float = 0.1,
                          warmup_steps: int = 100,
                          total_steps: int = 10000):
    """default_optimizer's schedule + hyperparams with the fused Pallas
    AdamW+clip apply (one memory pass over params/grads/moments)."""
    from ray_tpu.ops.pallas.adamw import FusedAdamW

    sched = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return FusedAdamW(sched, b1=0.9, b2=0.95, weight_decay=weight_decay,
                      clip_norm=1.0)


def state_shardings(cfg: ModelConfig, mesh: Mesh,
                    optimizer: optax.GradientTransformation,
                    rules: AxisRules = DEFAULT_RULES) -> TrainState:
    """Build a TrainState of NamedShardings (same tree shape as the state)."""
    p_axes = param_logical_axes(cfg)
    p_sh = logical_sharding(mesh, p_axes, rules)
    params_shape = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    replicated = NamedSharding(mesh, P())
    opt_sh = _shard_opt_like_params(opt_shape, params_shape, p_sh, replicated)
    return TrainState(params=p_sh, opt_state=opt_sh, step=replicated)


def _shard_opt_like_params(opt_shape, params_shape, p_sh, replicated):
    """Optimizer states embed param-shaped subtrees (adam mu/nu); shard those
    like the params and replicate everything else (counts, schedules)."""
    param_struct = jax.tree_util.tree_structure(params_shape)

    def recurse(node):
        try:
            struct = jax.tree_util.tree_structure(node)
        except Exception:
            struct = None
        if struct == param_struct:
            return p_sh
        if isinstance(node, (list, tuple)):
            mapped = [recurse(x) for x in node]
            return type(node)(mapped) if not hasattr(node, "_fields") else type(node)(*mapped)
        if isinstance(node, dict):
            return {k: recurse(v) for k, v in node.items()}
        if dataclasses.is_dataclass(node) and not isinstance(node, jax.ShapeDtypeStruct):
            return type(node)(**{f.name: recurse(getattr(node, f.name))
                                 for f in dataclasses.fields(node)})
        return replicated

    return recurse(opt_shape)


def batch_sharding(mesh: Mesh) -> Dict[str, NamedSharding]:
    """inputs/targets [b, s]: batch over (dp, fsdp)."""
    s = NamedSharding(mesh, P(("dp", "fsdp")))
    return {"inputs": s, "targets": s}


def make_init_fn(cfg: ModelConfig, mesh: Mesh,
                 optimizer: optax.GradientTransformation,
                 rules: AxisRules = DEFAULT_RULES) -> Callable[[jax.Array], TrainState]:
    """Jitted, sharded-out initializer: params materialize directly on the
    mesh (an 8B model never exists unsharded on any host)."""
    sh = state_shardings(cfg, mesh, optimizer, rules)

    def init(rng):
        params = init_params(rng, cfg)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    return jax.jit(init, out_shardings=sh)


def make_train_step(cfg: ModelConfig, mesh: Mesh,
                    optimizer: Optional[Any] = None,
                    rules: AxisRules = DEFAULT_RULES,
                    donate: bool = True):
    """Returns (step_fn, init_fn, shardings). step_fn(state, batch) ->
    (state, metrics); fully compiled, parameters donated.

    `optimizer` is an optax GradientTransformation, or a fused-apply
    optimizer (`ops.pallas.adamw.FusedAdamW`-style: `.apply(grads, state,
    params) -> (new_params, new_state)`) that updates params in one memory
    pass instead of returning deltas."""
    from ray_tpu.util import tracing

    tracing.record_compiles()  # `xla.compile` spans name this step
    optimizer = optimizer or default_optimizer()
    fused = hasattr(optimizer, "apply")
    sh = state_shardings(cfg, mesh, optimizer, rules)
    b_sh = batch_sharding(mesh)

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        # which form of the gradients' reduction over fsdp, of the block's
        # reductions over tp and of the norm scales' this program has, and
        # how many ring products run own shard first by a pin, whether the
        # weight gradients' rings are taken in order and whether the head's
        # product carries its exchanges, is a fact of its compile: on its
        # `xla.compile` spans
        inputs = split_batch(batch)[0]
        tracing.note_compile(
            "step", fsdp=mesh.shape.get("fsdp", 1), tp=mesh.shape.get("tp", 1),
            grad_exchanges_per_layer=grad_exchanges_per_layer(
                cfg, mesh, inputs.shape[0]),
            tp_exchanges_per_layer=tp_exchanges_per_layer(
                cfg, mesh, *inputs.shape),
            norm_grad_reductions_in_layers=norm_grad_reductions_in_layers(
                cfg, mesh, inputs.shape[0]),
            ring_products_own_first=ring_products_own_first(
                cfg, mesh, *inputs.shape),
            dw_rings_ordered=dw_rings_ordered(cfg, mesh, *inputs.shape),
            head_exchanged=head_exchanged(cfg, mesh, *inputs.shape))
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, cfg, mesh)
        # (the phases before this one are named in models/transformer.py:
        # `forward`, `head_loss`, and their transposes for the backward)
        with jax.named_scope("optimizer"):
            if fused:
                new_params, new_opt = optimizer.apply(
                    grads, state.opt_state, state.params)
            else:
                updates, new_opt = optimizer.update(grads, state.opt_state,
                                                    state.params)
                new_params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "step": state.step,
        }
        return TrainState(new_params, new_opt, state.step + 1), metrics

    step_fn = jax.jit(
        step,
        in_shardings=(sh, b_sh),
        out_shardings=(sh, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    )
    return step_fn, make_init_fn(cfg, mesh, optimizer, rules), sh
