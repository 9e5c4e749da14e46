"""Pipeline parallelism (parallel/pipeline.py): GPipe schedule over `pp`.

Green-field vs the reference (SURVEY §2.4: PP "indirect only" via
DeepSpeed/Accelerate passthrough) — correctness is checked against the
dense, non-pipelined forward on a virtual 8-device CPU mesh."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ModelConfig
from ray_tpu.models.transformer import init_params, loss_fn
from ray_tpu.parallel import MeshConfig, make_virtual_mesh
from ray_tpu.parallel.pipeline import make_pp_train_step, pp_loss_fn


def _batch(cfg, b=4, s=64, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (b, s + 1))
    return {"inputs": jnp.array(tokens[:, :-1]),
            "targets": jnp.array(tokens[:, 1:])}


@pytest.mark.parametrize("mesh_cfg,n_layers,n_micro", [
    (MeshConfig(dp=2, pp=2, tp=2), 2, 2),
    (MeshConfig(dp=2, pp=4, tp=1), 4, 4),
    (MeshConfig(dp=1, pp=2, fsdp=2, tp=2), 4, 2),
])
def test_pp_loss_matches_dense(mesh_cfg, n_layers, n_micro):
    cfg = ModelConfig(vocab_size=512, d_model=128, n_layers=n_layers,
                      n_heads=4, n_kv_heads=2, d_ff=256, max_seq_len=256,
                      dtype=jnp.float32, remat="none")
    mesh = make_virtual_mesh(8, mesh_cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    dense, _ = loss_fn(params, batch, cfg)
    pp, _ = jax.jit(functools.partial(
        pp_loss_fn, cfg=cfg, mesh=mesh, n_micro=n_micro))(params, batch)
    np.testing.assert_allclose(float(dense), float(pp), rtol=2e-5)


def test_pp_grads_match_dense():
    cfg = ModelConfig(vocab_size=512, d_model=128, n_layers=4, n_heads=4,
                      n_kv_heads=2, d_ff=256, max_seq_len=256,
                      dtype=jnp.float32, remat="none")
    mesh = make_virtual_mesh(8, MeshConfig(dp=2, pp=4, tp=1))
    params = init_params(jax.random.PRNGKey(1), cfg)
    batch = _batch(cfg, seed=1)
    gd = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    gp = jax.jit(jax.grad(lambda p: pp_loss_fn(p, batch, cfg, mesh, 4)[0]))(params)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), gd, gp)
    assert max(jax.tree_util.tree_leaves(errs)) < 1e-4, errs


def test_pp_train_step_runs_and_learns():
    cfg = ModelConfig.tiny()
    mesh = make_virtual_mesh(8, MeshConfig(dp=2, pp=2, tp=2))
    step_fn, init_fn, _ = make_pp_train_step(cfg, mesh, n_micro=2)
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    losses = []
    for _ in range(3):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert int(state.step) == 3
    assert all(np.isfinite(l) for l in losses)


def test_pp_rejects_sp():
    cfg = ModelConfig.tiny()
    mesh = make_virtual_mesh(8, MeshConfig(dp=2, pp=2, sp=2))
    with pytest.raises(ValueError):
        make_pp_train_step(cfg, mesh)


@pytest.mark.slow
def test_perf_multichip_records_scaling_evidence(tmp_path):
    """VERDICT done-criterion: step-time scaling on the virtual 8-device
    mesh — dp/tp/sp overheads at equal work and the pp bubble fraction
    tracking the (n_micro + pp - 1)/n_micro wasted-work model."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import __graft_entry__ as graft

    out = str(tmp_path / "perf.json")
    result = graft.perf_multichip(8, out_path=out)
    assert os.path.exists(out)
    assert result["dp_overhead_vs_onedev"] > 0
    assert result["tp_overhead_vs_dp"] > 0
    rows = result["pp"]
    # bubble shrinks as n_micro grows, tracking the model's direction and
    # staying within a loose CPU-noise envelope of it
    measured = [r["measured_overhead"] for r in rows]
    model = [r["model_overhead"] for r in rows]
    assert measured[0] > measured[-1]
    for m, mod in zip(measured, model):
        assert abs(m - mod) < 0.6, (measured, model)
