"""Learner / LearnerGroup: the mesh-native RL update stack.

Mirrors the reference's new training stack (`rllib/core/learner/learner.py:100`
— `compute_gradients:409`, `update:773` — and `learner_group.py:52`), built
TPU-first instead of DDP-first:

* `Learner` owns one module's params + optimizer and compiles a SINGLE
  jitted update. Given a `jax.sharding.Mesh` it shards the batch over the
  mesh's `dp` axis with replicated params — GSPMD inserts the gradient
  all-reduce, so the "distributed data parallel learner" is one XLA program
  whose collectives ride ICI/DCN, not a fleet of gradient-synchronizing
  processes.
* `LearnerGroup` scales a Learner out: `backend="mesh"` (default, the
  TPU-idiomatic path) is one process driving the sharded update; and
  `backend="actors"` runs N learner actors (CPU hosts) that all-reduce
  gradients through `ray_tpu.util.collective`'s host backend — the analog
  of the reference's gloo/NCCL learner workers for envs without a mesh.

Subclass contract: implement `init_params(seed)` and
`loss(params, batch, extra, rng) -> (loss, aux_metrics_dict)` (`rng` is a
fresh PRNG key per update for stochastic losses); optionally maintain
`extra` state (e.g. a target network) via `make_extra()` and the jitted
`post_update(params, extra)` hook (polyak syncs), and override
`make_optimizer()` for per-submodule optimizers (`optax.multi_transform`,
`delayed` for TD3-style update periods).
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import ray_tpu

logger = logging.getLogger(__name__)

__all__ = ["Learner", "LearnerGroup", "broadcast_weights", "delayed"]


def broadcast_weights(weights, handles, method: str = "set_weights"):
    """Fan a weights pytree out to worker actors as ONE plasma object with
    an owner-directed push broadcast (`ray_tpu.push`, reference
    push_manager.h:29): N workers on other nodes read a pre-pushed local
    copy instead of N pulls serializing on this owner. Small (inlined)
    weights skip the push. Blocks until every worker applied them."""
    ref = ray_tpu.put(weights)
    try:
        ray_tpu.push(ref)
    except ValueError:
        pass  # inlined small object: nothing to push, args ship it inline
    except Exception:
        # push is an optimization; the pull path still works
        logging.getLogger(__name__).debug("weight push failed", exc_info=True)
    return ray_tpu.get([getattr(h, method).remote(ref) for h in handles])


def delayed(tx, period: int):
    """Wrap an optax transform so it applies only every `period`-th step,
    with its inner state FROZEN on skipped steps (true delayed updates —
    zeroing gradients instead would still decay Adam's moments). This is how
    TD3's delayed actor rides a single jitted update: compose under
    `optax.multi_transform({"actor": delayed(adam, d), ...})`."""
    import jax
    import jax.numpy as jnp
    import optax

    def init(params):
        return (tx.init(params), jnp.zeros((), jnp.int32))

    def update(grads, state, params=None):
        inner, count = state

        def run(_):
            return tx.update(grads, inner, params)

        def skip(_):
            return jax.tree_util.tree_map(jnp.zeros_like, grads), inner

        updates, inner2 = jax.lax.cond(count % period == 0, run, skip, None)
        return updates, (inner2, count + 1)

    return optax.GradientTransformation(init, update)


class Learner:
    def __init__(self, *, lr: float = 1e-3, optimizer=None, mesh=None,
                 seed: int = 0):
        import jax
        import optax

        self.mesh = mesh
        self._lr = lr
        self.optimizer = (optimizer if optimizer is not None
                          else self.make_optimizer())
        self.params = self.init_params(seed)
        self.opt_state = self.optimizer.init(self.params)
        self._rng_key = jax.random.PRNGKey(seed)
        self._build(jax, optax)

    # ------------------------------------------------------ subclass hooks
    def init_params(self, seed: int):
        raise NotImplementedError

    def loss(self, params, batch, extra, rng):
        """Return (scalar_loss, aux_metrics_dict). `rng` is a fresh PRNG key
        per update (stochastic losses: target smoothing, reparameterized
        sampling); deterministic losses just ignore it."""
        raise NotImplementedError

    def make_optimizer(self):
        """Optax transform for the whole params pytree. Override for
        per-submodule optimizers via `optax.multi_transform` (the moral
        equivalent of the reference's configure_optimizers_for_module,
        learner.py:253) — see `delayed()` for TD3-style update periods."""
        import optax

        return optax.adam(self._lr)

    def make_extra(self):
        """Extra (non-optimized) pytree threaded through the update, e.g. a
        target network. None by default."""
        return None

    def post_update(self, params, extra):
        """Jitted hook after the optimizer step: return the next `extra`
        (e.g. polyak target sync — the reference's
        additional_update_for_module). Default: unchanged."""
        return extra

    # ------------------------------------------------------------- compile
    def _build(self, jax, optax) -> None:
        def grad_fn(params, extra, rng, batch):
            (l, aux), grads = jax.value_and_grad(
                self.loss, has_aux=True)(params, batch, extra, rng)
            aux = dict(aux)
            aux["total_loss"] = l
            return grads, aux

        def update_fn(params, opt_state, extra, rng, batch):
            grads, aux = grad_fn(params, extra, rng, batch)
            updates, opt_state = self.optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            extra = self.post_update(params, extra)
            return params, opt_state, extra, aux

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self.mesh, P())
            batch_sh = NamedSharding(self.mesh, P(self.batch_shard_axis))
            self._update_fn = jax.jit(
                update_fn,
                in_shardings=(repl, repl, repl, repl, batch_sh),
                out_shardings=(repl, repl, repl, repl))
            self._grad_fn = jax.jit(
                grad_fn,
                in_shardings=(repl, repl, repl, batch_sh),
                out_shardings=(repl, repl))
        else:
            self._update_fn = jax.jit(update_fn)
            self._grad_fn = jax.jit(grad_fn)
        self.extra = self.make_extra()

    # sharded batch layout: leading axis splits over this mesh axis —
    # sample-major losses use "dp" on axis 0; sequence losses (vtrace)
    # store batches batch-major [N, T] so dp still splits SAMPLES
    batch_shard_axis = "dp"

    def _fit_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        """Meshed updates need the leading dim divisible by dp: trim the
        ragged tail (standard RL practice for remainder minibatches) rather
        than crash on GSPMD's divisibility requirement."""
        if self.mesh is None:
            return batch
        dp = self.mesh.shape.get("dp", 1)
        n = len(next(iter(batch.values())))
        r = n % dp
        if r == 0:
            return batch
        if n < dp:
            # wrap-pad tiny batches up to dp (mirrors the actor backend's
            # shard padding) — a ragged SGD tail must not crash training
            import numpy as np

            idx = np.arange(dp) % n
            return {k: v[idx] for k, v in batch.items()}
        return {k: v[:n - r] for k, v in batch.items()}

    def _next_rng(self):
        import jax

        self._rng_key, sub = jax.random.split(self._rng_key)
        return sub

    # -------------------------------------------------------------- update
    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """One optimizer step on `batch` (sharded over dp when meshed);
        returns aux metrics (reference Learner.update:773)."""
        batch = self._fit_batch(batch)
        self.params, self.opt_state, self.extra, aux = self._update_fn(
            self.params, self.opt_state, self.extra, self._next_rng(), batch)
        return aux

    def compute_gradients(self, batch: Dict[str, np.ndarray]):
        """(grads, aux) without applying (reference compute_gradients:409)."""
        return self._grad_fn(self.params, self.extra, self._next_rng(),
                             self._fit_batch(batch))

    def apply_gradients(self, grads) -> None:
        import optax

        updates, self.opt_state = self.optimizer.update(
            grads, self.opt_state, self.params)
        self.params = optax.apply_updates(self.params, updates)

    # ------------------------------------------------------------- weights
    def get_weights(self):
        """Host copy of the params pytree (any nesting, not just flat
        dicts)."""
        import jax

        return jax.tree_util.tree_map(np.asarray, jax.device_get(self.params))

    def set_weights(self, weights) -> None:
        import jax
        import jax.numpy as jnp

        self.params = jax.tree_util.tree_map(jnp.asarray, weights)
        self.opt_state = self.optimizer.init(self.params)


@ray_tpu.remote
class _LearnerActor:
    """One member of an actor-backed LearnerGroup: computes gradients
    locally and all-reduces them through the host collective backend
    (reference learner workers with gloo DDP)."""

    def __init__(self, learner_blob: bytes, kwargs: dict,
                 world_size: int, rank: int, group_name: str):
        import cloudpickle

        cls = cloudpickle.loads(learner_blob)
        self._learner: Learner = cls(**kwargs)
        self._world = world_size
        self._rank = rank
        self._group = group_name
        if world_size > 1:
            from ray_tpu.util import collective

            collective.init_collective_group(
                world_size, rank, backend="host", group_name=group_name)

    def update_shard(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        import jax

        grads, aux = self._learner.compute_gradients(batch)
        if self._world > 1:
            from ray_tpu.util import collective

            flat, tree = jax.tree_util.tree_flatten(grads)
            summed = [collective.allreduce(np.asarray(g), self._group)
                      / self._world for g in flat]
            grads = jax.tree_util.tree_unflatten(tree, summed)
        self._learner.apply_gradients(grads)
        return {k: float(v) for k, v in jax.device_get(aux).items()
                if np.ndim(v) == 0}

    def get_weights(self):
        return self._learner.get_weights()

    def set_weights(self, weights) -> bool:
        self._learner.set_weights(weights)
        return True


class LearnerGroup:
    """Scale a Learner to many devices/processes
    (reference learner_group.py:52)."""

    def __init__(self, learner_cls: Callable[..., Learner],
                 learner_kwargs: Optional[dict] = None, *,
                 backend: str = "mesh",
                 mesh=None,
                 num_learners: int = 1,
                 scheduling=None):
        self.backend = backend
        kwargs = dict(learner_kwargs or {})
        if backend == "mesh":
            if mesh is None:
                from ray_tpu.parallel import MeshConfig, make_mesh

                mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1))
            kwargs["mesh"] = mesh
            self.mesh = mesh
            self._learner = learner_cls(**kwargs)
            self._actors: List[Any] = []
        elif backend == "actors":
            import cloudpickle
            import uuid

            self.mesh = None
            self._learner = None
            blob = cloudpickle.dumps(learner_cls)
            # uuid, NOT id(self): a GC'd group's id can be reused and would
            # collide with the previous group's named rendezvous actor
            group = f"learner-group-{uuid.uuid4().hex[:12]}"
            self._group_name = group
            opts: dict = {}
            if scheduling is not None:
                opts["scheduling_strategy"] = scheduling
            actor_cls = (_LearnerActor.options(**opts)
                         if opts else _LearnerActor)
            self._actors = [
                actor_cls.remote(blob, kwargs, num_learners, rank, group)
                for rank in range(num_learners)]
            # materialize construction errors early
            ray_tpu.get([a.get_weights.remote() for a in self._actors])
        else:
            raise ValueError(f"unknown LearnerGroup backend {backend!r}")

    @property
    def num_learners(self) -> int:
        return len(self._actors) if self._actors else 1

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        """One synchronized update across the group: mesh backend shards the
        batch over dp inside jit; actor backend splits it across learners
        which all-reduce gradients."""
        if self._learner is not None:
            import jax

            aux = self._learner.update(batch)
            return {k: float(v) for k, v in jax.device_get(aux).items()
                    if np.ndim(v) == 0}
        n = len(self._actors)
        size = len(next(iter(batch.values())))
        # Wrap-pad so every sample trains and every rank gets a non-empty
        # shard (all ranks MUST participate in the all-reduce; an empty
        # shard would also mean NaN means).
        idx = np.arange(size)
        pad = (-size) % n
        if pad:
            idx = np.concatenate([idx, idx[:pad]])
        per = len(idx) // n
        shards = [{k: v[idx[i * per:(i + 1) * per]] for k, v in batch.items()}
                  for i in range(n)]
        stats = ray_tpu.get([a.update_shard.remote(s)
                             for a, s in zip(self._actors, shards)])
        return {k: float(np.mean([s[k] for s in stats]))
                for k in stats[0]} if stats else {}

    def update_minibatches(self, flat: Dict[str, np.ndarray],
                           num_epochs: int, minibatch_size: int,
                           rng: np.random.Generator) -> Dict[str, float]:
        """Epoch/shuffle/minibatch SGD driven through group update()s —
        one loop serving both backends (reference LearnerGroup.update with
        minibatching)."""
        n = len(next(iter(flat.values())))
        stats: Dict[str, float] = {}
        for _ in range(num_epochs):
            idx = rng.permutation(n)
            for start in range(0, n, minibatch_size):
                mb = {k: v[idx[start:start + minibatch_size]]
                      for k, v in flat.items()}
                stats = self.update(mb)
        return stats

    def get_weights(self) -> Dict[str, np.ndarray]:
        if self._learner is not None:
            return self._learner.get_weights()
        return ray_tpu.get(self._actors[0].get_weights.remote())

    def set_weights(self, weights: Dict[str, np.ndarray]) -> None:
        if self._learner is not None:
            self._learner.set_weights(weights)
        else:
            broadcast_weights(weights, self._actors)

    def shutdown(self) -> None:
        """Tear down learner actors + the collective rendezvous (the group
        does not auto-clean: like the reference's LearnerGroup.shutdown)."""
        if self._actors:
            if len(self._actors) > 1:
                try:
                    from ray_tpu.util import collective

                    collective.destroy_collective_group(self._group_name)
                except (ValueError, KeyError, ConnectionError) as e:
                    logger.debug("collective group already gone: %s", e)
            from ray_tpu.rllib.algorithm import Algorithm

            Algorithm._kill_workers(self._actors)
            self._actors = []
