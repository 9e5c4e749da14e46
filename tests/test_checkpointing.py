"""Orbax sharded checkpointing: save sharded, restore onto a DIFFERENT
mesh layout (the elastic-recovery primitive, SURVEY hard-part #7)."""

import jax
import numpy as np
import pytest

from ray_tpu.models import ModelConfig, init_params
from ray_tpu.models.transformer import param_logical_axes
from ray_tpu.parallel import MeshConfig, make_virtual_mesh
from ray_tpu.parallel.mesh import DEFAULT_RULES, logical_sharding, shard_pytree
from ray_tpu.train import abstract_like, restore_sharded, save_sharded


def _sharded_params(mesh_cfg):
    cfg = ModelConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_virtual_mesh(8, mesh_cfg)
    sh = logical_sharding(mesh, param_logical_axes(cfg), DEFAULT_RULES)
    return shard_pytree(params, sh), sh, params


def test_save_restore_same_mesh(tmp_path):
    sharded, sh, orig = _sharded_params(MeshConfig(dp=2, fsdp=2, tp=2))
    path = save_sharded(sharded, str(tmp_path / "ckpt1"))
    restored = restore_sharded(path, abstract_like(sharded))
    for a, b in zip(jax.tree_util.tree_leaves(orig),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_onto_reshaped_mesh(tmp_path):
    """Save from an 8-device dp2/fsdp2/tp2 layout, restore onto dp1/fsdp4/
    tp2 — shards re-laid-out on read, values identical."""
    sharded, _, orig = _sharded_params(MeshConfig(dp=2, fsdp=2, tp=2))
    path = save_sharded(sharded, str(tmp_path / "ckpt2"))

    cfg = ModelConfig.tiny()
    new_mesh = make_virtual_mesh(8, MeshConfig(dp=1, fsdp=4, tp=2))
    new_sh = logical_sharding(new_mesh, param_logical_axes(cfg), DEFAULT_RULES)
    restored = restore_sharded(path, abstract_like(sharded, new_sh))
    for a, b in zip(jax.tree_util.tree_leaves(orig),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the restored embed really lives on the new mesh's sharding
    assert restored["embed"].sharding.mesh.shape["fsdp"] == 4


def test_step_checkpoints_latest_and_retention(tmp_path):
    """Step-addressed checkpoints (train.checkpointing.save_checkpoint):
    latest_checkpoint resolves only COMPLETE saves, torn staging dirs and
    bare step dirs are invisible, and gc keeps the newest K."""
    import json
    import os

    from ray_tpu.train import (gc_checkpoints, latest_checkpoint,
                               load_checkpoint, save_checkpoint)

    root = str(tmp_path / "run")
    assert latest_checkpoint(root) is None  # empty / missing root
    state = {"w": np.arange(8, dtype=np.float32)}
    for step in (2, 4, 6):
        save_checkpoint(state, root, step, meta={"epoch": step * 10})
    # a torn save: staging dir left behind by a crash mid-write
    os.makedirs(os.path.join(root, ".tmp-step_8-123"))
    # an incomplete final dir (no meta.json commit marker)
    os.makedirs(os.path.join(root, "step_9", "state"))

    latest = latest_checkpoint(root)
    assert latest is not None and latest.endswith("step_6")
    restored, meta = load_checkpoint(latest, abstract_like(state))
    np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])
    assert meta["step"] == 6 and meta["epoch"] == 60

    deleted = gc_checkpoints(root, keep=2)
    kept = sorted(d for d in os.listdir(root) if d.startswith("step_"))
    # GC only reasons about COMPLETE checkpoints: step_2 (oldest complete)
    # goes, step_4/step_6 stay, the incomplete step_9 is not its business
    assert kept == ["step_4", "step_6", "step_9"]
    assert any(p.endswith("step_2") for p in deleted)
    assert not any(d.startswith(".tmp-") for d in os.listdir(root))
    # the incomplete dir still never resolves as latest
    assert latest_checkpoint(root).endswith("step_6")
    # meta survives on disk as plain json (inspectable artifacts)
    with open(os.path.join(root, "step_6", "meta.json")) as f:
        assert json.load(f)["epoch"] == 60
