"""Learner / LearnerGroup (reference `rllib/core/learner/learner.py:100`,
`learner_group.py:52`): the mesh backend shards batches over the virtual
8-device dp axis inside one jitted update; the actors backend all-reduces
gradients across learner actors via the host collective."""

import numpy as np
import pytest

import jax

from ray_tpu.rllib.ppo import PPOLearner, init_policy_params
from ray_tpu.rllib.dqn import DQNLearner
from ray_tpu.rllib.learner import LearnerGroup
from ray_tpu.parallel import MeshConfig, make_mesh


def _ppo_batch(n, obs_dim=4, num_actions=2, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "obs": rng.normal(size=(n, obs_dim)).astype(np.float32),
        "actions": rng.integers(0, num_actions, n),
        "logp": rng.normal(size=n).astype(np.float32) * 0.1 - 0.7,
        "advantages": rng.normal(size=n).astype(np.float32),
        "returns": rng.normal(size=n).astype(np.float32),
    }


def test_ppo_learner_mesh_matches_single_device():
    """The dp-sharded update must compute the same step as the unsharded
    one: params replicated, gradients globally averaged by GSPMD."""
    mesh = make_mesh(MeshConfig(dp=8, fsdp=1, tp=1))
    batch = _ppo_batch(64)
    plain = PPOLearner(4, 2, lr=1e-3, seed=7)
    meshed = PPOLearner(4, 2, lr=1e-3, seed=7, mesh=mesh)
    aux_plain = jax.device_get(plain.update(batch))
    aux_mesh = jax.device_get(meshed.update(batch))
    np.testing.assert_allclose(float(aux_plain["total_loss"]),
                               float(aux_mesh["total_loss"]), rtol=1e-5)
    for k in plain.params:
        np.testing.assert_allclose(np.asarray(plain.params[k]),
                                   np.asarray(meshed.params[k]),
                                   rtol=1e-4, atol=1e-5)


def test_dqn_learner_mesh_update_and_target_sync():
    mesh = make_mesh(MeshConfig(dp=8, fsdp=1, tp=1))
    learner = DQNLearner(4, 2, lr=1e-3, gamma=0.99, mesh=mesh)
    rng = np.random.default_rng(0)
    batch = {
        "obs": rng.normal(size=(32, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, 32),
        "rewards": rng.normal(size=32).astype(np.float32),
        "next_obs": rng.normal(size=(32, 4)).astype(np.float32),
        "dones": rng.integers(0, 2, 32).astype(np.float32),
    }
    loss1, td = learner.update_batch(batch)
    assert np.isfinite(loss1) and td.shape == (32,)
    learner.sync_target()
    loss2, _ = learner.update_batch(batch)
    assert np.isfinite(loss2)


def test_learner_group_mesh_backend():
    group = LearnerGroup(
        PPOLearner, {"obs_dim": 4, "num_actions": 2, "lr": 1e-3},
        backend="mesh", mesh=make_mesh(MeshConfig(dp=8, fsdp=1, tp=1)))
    stats = group.update(_ppo_batch(64))
    assert np.isfinite(stats["total_loss"])
    w = group.get_weights()
    group.set_weights(w)
    w2 = group.get_weights()
    for k in w:
        np.testing.assert_array_equal(w[k], w2[k])


def test_learner_group_actor_backend(ray_start_regular):
    """2 learner actors, host-collective gradient all-reduce: both replicas
    must hold identical params after an update (DDP invariant)."""
    group = LearnerGroup(
        PPOLearner, {"obs_dim": 4, "num_actions": 2, "lr": 1e-3, "seed": 3},
        backend="actors", num_learners=2)
    stats = group.update(_ppo_batch(64, seed=1))
    assert np.isfinite(stats["total_loss"])
    import ray_tpu

    w0, w1 = ray_tpu.get([a.get_weights.remote() for a in group._actors])
    for k in w0:
        np.testing.assert_allclose(w0[k], w1[k], rtol=1e-5, atol=1e-6,
                                   err_msg=f"replicas diverged at {k}")
    # odd-size batch: wrap-padded so every rank trains and no data is lost
    stats = group.update(_ppo_batch(65, seed=2))
    assert np.isfinite(stats["total_loss"])
    group.shutdown()


def test_ppo_algorithm_with_mesh_learner_group(ray_start_regular):
    """End-to-end: PPO's training_step drives a mesh-backed LearnerGroup
    (reference Algorithm.training_step -> LearnerGroup.update)."""
    from ray_tpu.rllib import PPOConfig

    mesh = make_mesh(MeshConfig(dp=8, fsdp=1, tp=1))
    algo = (PPOConfig()
            .rollouts(num_rollout_workers=1, num_envs_per_worker=2,
                      rollout_fragment_length=34)  # 68 samples: ragged tail
            .training(num_sgd_iter=1, sgd_minibatch_size=64)
            .learners(backend="mesh", mesh=mesh)
            .build())
    try:
        r = algo.train()
        assert np.isfinite(r["total_loss"])
        w = algo.get_weights()
        algo.set_weights(w)
    finally:
        algo.stop()


def test_ppo_algorithm_with_actor_learner_group(ray_start_regular):
    from ray_tpu.rllib import PPOConfig

    algo = (PPOConfig()
            .rollouts(num_rollout_workers=1, num_envs_per_worker=2,
                      rollout_fragment_length=32)
            .training(num_sgd_iter=1, sgd_minibatch_size=64)
            .learners(backend="actors", num_learners=2)
            .build())
    try:
        r = algo.train()
        assert np.isfinite(r["total_loss"])
    finally:
        algo.stop()


def _traj_batch(n_envs=8, t=16, obs_dim=4, num_actions=2, seed=3):
    """Rollout-layout [T, N] batch for the v-trace family."""
    rng = np.random.default_rng(seed)
    return {
        "obs": rng.normal(size=(t, n_envs, obs_dim)).astype(np.float32),
        "actions": rng.integers(0, num_actions, (t, n_envs)).astype(np.int32),
        "logp": (rng.normal(size=(t, n_envs)) * 0.1 - 0.7).astype(np.float32),
        "rewards": rng.normal(size=(t, n_envs)).astype(np.float32),
        "dones": (rng.random((t, n_envs)) < 0.05).astype(np.float32),
        "last_value": rng.normal(size=n_envs).astype(np.float32),
    }


def test_vtrace_family_mesh_matches_single_device():
    """IMPALA/APPO on the mesh backend: batches relayout batch-major so dp
    shards env trajectories; the sharded update equals the unsharded one."""
    from ray_tpu.rllib.impala import ImpalaLearner

    mesh = make_mesh(MeshConfig(dp=8, fsdp=1, tp=1))
    batch = _traj_batch()
    plain = ImpalaLearner(4, 2, lr=1e-3, gamma=0.99, vf_coeff=0.5,
                          entropy_coeff=0.01, seed=5)
    meshed = ImpalaLearner(4, 2, lr=1e-3, gamma=0.99, vf_coeff=0.5,
                           entropy_coeff=0.01, seed=5, mesh=mesh)
    s_plain = plain.update_batch(batch)
    s_mesh = meshed.update_batch(batch)
    np.testing.assert_allclose(s_plain["total_loss"], s_mesh["total_loss"],
                               rtol=1e-5)
    for k in plain.params:
        np.testing.assert_allclose(np.asarray(plain.params[k]),
                                   np.asarray(meshed.params[k]),
                                   rtol=1e-4, atol=1e-5)


def test_continuous_family_mesh_matches_single_device():
    """DDPG (continuous actor-critic family) on the mesh backend: the
    combined actor+critic loss with multi_transform optimizers and the
    jitted polyak post_update all ride the dp-sharded update."""
    from ray_tpu.rllib.ddpg import DDPGLearner

    mesh = make_mesh(MeshConfig(dp=8, fsdp=1, tp=1))
    rng = np.random.default_rng(1)
    batch = {
        "obs": rng.normal(size=(32, 3)).astype(np.float32),
        "actions": rng.uniform(-1, 1, (32, 1)).astype(np.float32),
        "rewards": rng.normal(size=32).astype(np.float32),
        "next_obs": rng.normal(size=(32, 3)).astype(np.float32),
        "dones": np.zeros(32, np.float32),
    }
    kw = dict(actor_lr=1e-3, critic_lr=1e-3, gamma=0.99, tau=0.05,
              twin_q=True, smooth_target_policy=False, target_noise=0.0,
              target_noise_clip=0.0, seed=2, policy_delay=2)
    plain = DDPGLearner(3, 1, 1.0, **kw)
    meshed = DDPGLearner(3, 1, 1.0, **kw, mesh=mesh)
    for _ in range(3):  # crosses a delayed-actor boundary (delay=2)
        s_plain = plain.update_batch(batch)
        s_mesh = meshed.update_batch(batch)
    np.testing.assert_allclose(s_plain["critic_loss"], s_mesh["critic_loss"],
                               rtol=1e-4)
    import jax as _jax

    _jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        plain.params, meshed.params)


def test_sac_learner_mesh_runs_and_polyak_targets_move():
    """SAC's stochastic loss uses the threaded rng; the mesh update runs
    and the post_update polyak actually moves the target critics."""
    from ray_tpu.rllib.sac import SACLearner

    mesh = make_mesh(MeshConfig(dp=8, fsdp=1, tp=1))
    learner = SACLearner(3, 1, 1.0, lr=3e-4, gamma=0.99, tau=0.05,
                         target_entropy=-1.0, seed=4, mesh=mesh)
    before = np.asarray(learner.extra["q1"]["w0"]).copy()
    rng = np.random.default_rng(0)
    batch = {
        "obs": rng.normal(size=(32, 3)).astype(np.float32),
        "actions": rng.uniform(-1, 1, (32, 1)).astype(np.float32),
        "rewards": rng.normal(size=32).astype(np.float32),
        "next_obs": rng.normal(size=(32, 3)).astype(np.float32),
        "dones": np.zeros(32, np.float32),
    }
    for _ in range(2):
        stats = learner.update_batch(batch)
    assert np.isfinite(stats["critic_loss"])
    assert not np.allclose(before, np.asarray(learner.extra["q1"]["w0"]))


def test_delayed_transform_freezes_inner_state():
    """`delayed(tx, k)` applies tx every k-th step with the inner state
    FROZEN between applications (true TD3 delayed updates)."""
    import optax

    from ray_tpu.rllib.learner import delayed

    tx = delayed(optax.sgd(0.1), 2)
    params = {"w": np.ones(3, np.float32)}
    state = tx.init(params)
    g = {"w": np.ones(3, np.float32)}
    up0, state = tx.update(g, state, params)   # step 0: applies
    up1, state = tx.update(g, state, params)   # step 1: skipped
    up2, state = tx.update(g, state, params)   # step 2: applies
    assert np.allclose(np.asarray(up0["w"]), -0.1)
    assert np.allclose(np.asarray(up1["w"]), 0.0)
    assert np.allclose(np.asarray(up2["w"]), -0.1)
