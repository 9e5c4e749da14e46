"""Sharded train-step tests on the 8-device virtual CPU mesh."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ModelConfig, count_params, init_params, loss_fn
from ray_tpu.parallel import MeshConfig, make_virtual_mesh
from ray_tpu.train import make_train_step, batch_sharding
from ray_tpu.train.step import default_optimizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch(rng, cfg, batch=4, seq=64):
    tokens = jax.random.randint(rng, (batch, seq + 1), 0, cfg.vocab_size)
    return {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}


def _five_losses(cfg, mesh, batch):
    """The losses of five optimizer steps from one seeded state."""
    step_fn, init_fn, _ = make_train_step(cfg, mesh, default_optimizer(1e-3))
    state, losses = init_fn(jax.random.PRNGKey(0)), []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


def test_loss_decreases_single_device():
    cfg = ModelConfig.tiny()
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    assert count_params(params) > 0
    batch = _batch(jax.random.PRNGKey(1), cfg)
    loss0, aux = loss_fn(params, batch, cfg)
    # random init: loss should be ~ log(vocab)
    assert abs(float(loss0) - np.log(cfg.vocab_size)) < 1.0


@pytest.mark.parametrize("mesh_cfg", [
    MeshConfig(dp=2, fsdp=2, tp=2),
    MeshConfig(dp=1, fsdp=4, tp=2),
    MeshConfig(dp=8, fsdp=1, tp=1),
])
def test_train_step_sharded(mesh_cfg):
    cfg = ModelConfig.tiny()
    mesh = make_virtual_mesh(8, mesh_cfg)
    step_fn, init_fn, sh = make_train_step(cfg, mesh, default_optimizer(1e-3))
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=8, seq=64)
    batch = jax.device_put(batch, {k: batch_sharding(mesh)[k] for k in batch})
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    assert int(jax.device_get(state.step)) == 5


def _assert_norm_scale_gradients_come_whole(cfg, mesh, got, p_sh):
    """The two norm scales' gradients ride through the scan once a rank
    where the weights come exchanged (their sum over the ranks is taken
    behind it: none is left in the layers), and come back as the leaves
    are: [layers, d], replicated."""
    from ray_tpu.models import transformer

    assert transformer.norm_grad_reductions_in_layers(cfg, mesh, 8) == 0
    for k in ("attn_norm", "mlp_norm"):
        g = got["layers"][k]
        assert g.shape == (cfg.n_layers, cfg.d_model) and g.dtype == cfg.dtype
        assert g.sharding.is_equivalent_to(p_sh["layers"][k], g.ndim), k
        assert g.sharding.is_fully_replicated, k


@pytest.mark.parametrize("mesh_cfg,n", [
    (MeshConfig(dp=1, fsdp=2, tp=2), 4),
    (MeshConfig(dp=1, fsdp=4, tp=2), 8),
    (MeshConfig(dp=2, fsdp=2, tp=2), 8),
], ids=["fsdp2xtp2", "fsdp4xtp2", "dp2xfsdp2xtp2"])
def test_grad_exchange_over_fsdp_matches_one_device(mesh_cfg, n, monkeypatch):
    """On a mesh with fsdp > 1 the program sums each layer's weight
    gradients over `fsdp` itself (parallel/fsdp.py: a ring of permutes of
    exact shards). In float32 the loss and EVERY gradient leaf agree with
    one device's `value_and_grad(loss_fn)` to sums of two or four terms,
    and five optimizer steps give the losses of the partitioner's own
    reduction (the same mesh with the exchange switched off here)."""
    from ray_tpu.models import transformer
    from ray_tpu.train.step import state_shardings

    cfg = ModelConfig.tiny()
    mesh = make_virtual_mesh(n, mesh_cfg)
    assert transformer.grad_exchanges_per_layer(cfg, mesh, 8) == 7
    assert transformer.ring_products_own_first(cfg, mesh, 8, 64) == 1
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=8, seq=64)
    grad = lambda m: jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg, m)[0]))
    want_loss, want = grad(None)(params, batch)

    b_sh = batch_sharding(mesh)
    on_mesh = jax.device_put(batch, {k: b_sh[k] for k in batch})
    p_sh = state_shardings(cfg, mesh, default_optimizer()).params
    lowered = grad(mesh).lower(jax.device_put(params, p_sh), on_mesh)
    assert "collective_permute" in lowered.as_text()
    got_loss, got = lowered.compile()(jax.device_put(params, p_sh), on_mesh)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=2e-6)
    jax.tree_util.tree_map_with_path(
        lambda path, g, w: np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path)), got, want)
    assert got["layers"]["wo"].sharding.spec == p_sh["layers"]["wo"].spec
    _assert_norm_scale_gradients_come_whole(cfg, mesh, got, p_sh)

    ours = _five_losses(cfg, mesh, on_mesh)
    monkeypatch.setattr(transformer, "_exchanged_dims", lambda *a: {})
    np.testing.assert_allclose(ours, _five_losses(cfg, mesh, on_mesh), rtol=1e-5)
    assert ours[-1] < ours[0]


@pytest.mark.parametrize("mesh_cfg,n", [
    (MeshConfig(dp=1, fsdp=1, tp=2), 2),
    (MeshConfig(dp=1, fsdp=2, tp=2), 4),
    (MeshConfig(dp=1, fsdp=4, tp=2), 8),
    (MeshConfig(dp=2, fsdp=2, tp=2), 8),
    (MeshConfig(dp=1, fsdp=2, tp=4), 8),
], ids=["tp2", "fsdp2xtp2", "fsdp4xtp2", "dp2xfsdp2xtp2", "fsdp2xtp4"])
def test_tp_exchange_matches_one_device(mesh_cfg, n, monkeypatch):
    """On a mesh with tp > 1 and fsdp > 1 the residual stream rides
    sequence-sharded over `tp` between the dense block's products, and each
    gather and scatter goes as ring permutes behind its product
    (parallel/tp.py; a ring of three steps at tp 4), the weights' shards
    round fsdp's ring inside it. With fsdp 1 the weights do not come
    exchanged and the program is the partitioner's. In float32 the loss and
    EVERY gradient leaf agree with one device's `value_and_grad(loss_fn)`,
    the layers' gradient leaves come in the parameter shardings, and five
    optimizer steps give the losses of the partitioner's all-reduces (the
    same mesh with the route switched off here)."""
    import dataclasses

    from ray_tpu.models import transformer
    from ray_tpu.train.step import state_shardings

    cfg = dataclasses.replace(ModelConfig.tiny(), n_kv_heads=mesh_cfg.tp)
    mesh = make_virtual_mesh(n, mesh_cfg)
    ours = mesh_cfg.fsdp > 1
    assert transformer.tp_exchanges_per_layer(cfg, mesh, 8, 64) == 4 * ours
    assert transformer.grad_exchanges_per_layer(cfg, mesh, 8) == 7 * ours
    assert transformer.ring_products_own_first(cfg, mesh, 8, 64) == ours
    assert transformer.dw_rings_ordered(cfg, mesh, 8, 64) == ours
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=8, seq=64)
    grad = lambda m: jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, b, cfg, m)[0]))
    want_loss, want = grad(None)(params, batch)

    b_sh = batch_sharding(mesh)
    on_mesh = jax.device_put(batch, {k: b_sh[k] for k in batch})
    p_sh = state_shardings(cfg, mesh, default_optimizer()).params
    lowered = grad(mesh).lower(jax.device_put(params, p_sh), on_mesh)
    # four exchanges a layer forward, six backward, over the tp pairs
    assert (lowered.as_text().count("collective_permute") >= 10) == ours
    got_loss, got = lowered.compile()(jax.device_put(params, p_sh), on_mesh)
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=2e-6)
    jax.tree_util.tree_map_with_path(
        lambda path, g, w: np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path)), got, want)
    # (the layers' leaves: the products' own `dw`; the rest is the compiler's)
    jax.tree_util.tree_map_with_path(
        lambda path, g, h: np.testing.assert_equal(
            g.sharding.is_equivalent_to(h, g.ndim), True,
            err_msg=jax.tree_util.keystr(path)), got["layers"], p_sh["layers"])
    _assert_norm_scale_gradients_come_whole(cfg, mesh, got, p_sh)

    losses = _five_losses(cfg, mesh, on_mesh)
    monkeypatch.setattr(transformer, "_rows_mesh", lambda *a: None)
    np.testing.assert_allclose(losses, _five_losses(cfg, mesh, on_mesh), rtol=1e-5)
    assert losses[-1] < losses[0]


def _program_at_dtype(mesh_cfg, n, dtype):
    """(cfg, mesh, run) for the bit-for-bit comparisons below: `run()` lowers
    and runs `value_and_grad(loss_fn)` under the mesh, the same weights and
    batch each time -> (lowered text, loss, gradients)."""
    import dataclasses

    from ray_tpu.train.step import state_shardings

    cfg = dataclasses.replace(ModelConfig.tiny(), n_kv_heads=max(2, mesh_cfg.tp),
                              dtype=jnp.dtype(dtype))
    mesh = make_virtual_mesh(n, mesh_cfg)
    params = jax.device_put(
        init_params(jax.random.PRNGKey(0), cfg),
        state_shardings(cfg, mesh, default_optimizer()).params)
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=8, seq=64)
    batch = jax.device_put(batch, {k: batch_sharding(mesh)[k] for k in batch})

    def run():
        lowered = jax.jit(jax.value_and_grad(
            lambda p, b: loss_fn(p, b, cfg, mesh)[0])).lower(params, batch)
        return lowered.as_text(), *lowered.compile()(params, batch)

    return cfg, mesh, run


def _assert_same_bits(loss, grads, parent_loss, parent_grads):
    bits = lambda a: np.asarray(a).view(f"u{a.dtype.itemsize}")
    np.testing.assert_array_equal(bits(loss), bits(parent_loss))
    jax.tree_util.tree_map_with_path(
        lambda path, g, w: np.testing.assert_array_equal(
            bits(g), bits(w), err_msg=jax.tree_util.keystr(path)),
        grads, parent_grads)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mesh_cfg,n", [
    (MeshConfig(dp=1, fsdp=2, tp=2), 4),
    (MeshConfig(dp=1, fsdp=4, tp=2), 8),
    (MeshConfig(dp=2, fsdp=2, tp=2), 8),
], ids=["fsdp2xtp2", "fsdp4xtp2", "dp2xfsdp2xtp2"])
def test_own_shard_first_changes_no_bit(mesh_cfg, n, dtype, monkeypatch):
    """The FFN's backward asks `fsdp.ring_products` to finish the product by
    the rank's own `w_down` shard before it takes the shard that arrives
    (`own_first`: one `optimization_barrier` a round of the ring, fsdp - 1
    of them, at that one call of the layer and no other). The same float32
    partials are added, a + b for b + a, and rounded once: the loss and
    EVERY gradient leaf are bit for bit those of the program that never pins
    (the parent's form, `own_first` dropped here), in float32 and in
    bfloat16, as the four-chip cell runs it."""
    from ray_tpu.models import transformer
    from ray_tpu.parallel import fsdp

    cfg, mesh, run = _program_at_dtype(mesh_cfg, n, dtype)
    assert transformer.ring_products_own_first(cfg, mesh, 8, 64) == 1
    text, loss, grads = run()
    pinned = fsdp.ring_products
    asked = []

    def never_pinned(*args, own_first=False, **kw):
        asked.append(own_first)
        return pinned(*args, **kw)

    monkeypatch.setattr(fsdp, "ring_products", never_pinned)
    parent_text, parent_loss, parent_grads = run()
    # of a layer's ring products (forward, remat's, backward) ONE asks, and
    # the head's forward (nothing before it covers `lm_head`'s shard either)
    assert asked.count(True) == 2 and asked.count(False) >= 8, asked
    pins = lambda t: t.count("optimization_barrier")
    assert pins(text) - pins(parent_text) == 2 * (mesh_cfg.fsdp - 1)
    _assert_same_bits(loss, grads, parent_loss, parent_grads)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mesh_cfg,n", [
    (MeshConfig(dp=1, fsdp=2, tp=2), 4),
    (MeshConfig(dp=1, fsdp=4, tp=2), 8),
    (MeshConfig(dp=1, fsdp=2, tp=1), 2),
], ids=["fsdp2xtp2", "fsdp4xtp2", "fsdp2xtp1"])
def test_ordered_dw_rings_change_no_bit(mesh_cfg, n, dtype, monkeypatch):
    """Where the products are parallel/tp.py's, a layer's seven weight
    gradients' rings are taken off the `fsdp` link in the order of their
    starts: each ring's kept product reads its slice at an offset that adds
    a zero read off the sum the ring before made, in the same backward body
    or the one before (`fsdp.RingOrder`, handed from product to product and
    back as a cotangent). The zero is a zero: the loss and EVERY gradient
    leaf are bit for bit those of the program whose rings know of no order
    (the parent's form: `weight_grads` never handed `taken` here), in
    float32 and in bfloat16, at fsdp 2 and round a ring of three steps. A
    kept product that stands in the order reads its slice from an array of
    its own (`fsdp._staged`: one `optimization_barrier` for each of the
    seven rings' fsdp - 1 kept products); the slice is the slice. With
    `tp` 1 the products are the partitioner's, every ring is alone in its
    backward body, and none is handed an order at all."""
    from ray_tpu.models import transformer
    from ray_tpu.parallel import fsdp

    cfg, mesh, run = _program_at_dtype(mesh_cfg, n, dtype)
    ours = int(mesh_cfg.tp > 1)
    assert transformer.dw_rings_ordered(cfg, mesh, 8, 64) == ours
    text, loss, grads = run()
    ordered = fsdp.weight_grads
    handed = []

    def unordered(xs, dys, dim, mesh, taken=None, **alone):
        handed.append(taken is not None)
        return ordered(xs, dys, dim, mesh, **alone)[0], taken

    monkeypatch.setattr(fsdp, "weight_grads", unordered)
    parent_text, parent_loss, parent_grads = run()
    # the four backward bodies of a layer's products (and the head's ring,
    # alone in its own and handed no order), or none of the seven
    assert handed.count(True) == 4 * ours and len(handed) == (5 if ours else 7)
    assert (text != parent_text) == bool(ours)
    pins = lambda t: t.count("optimization_barrier")
    assert pins(text) - pins(parent_text) == ours * 7 * (mesh_cfg.fsdp - 1)
    _assert_same_bits(loss, grads, parent_loss, parent_grads)


@pytest.mark.parametrize("mesh_cfg,n,dtype", [
    (MeshConfig(dp=1, fsdp=2, tp=2), 4, "float32"),
    (MeshConfig(dp=1, fsdp=4, tp=2), 8, "float32"),
    (MeshConfig(dp=2, fsdp=2, tp=2), 8, "float32"),
    (MeshConfig(dp=1, fsdp=2, tp=4), 8, "float32"),
    (MeshConfig(dp=1, fsdp=2, tp=2), 4, "bfloat16"),
], ids=["fsdp2xtp2", "fsdp4xtp2", "dp2xfsdp2xtp2", "fsdp2xtp4", "fsdp2xtp2_bf16"])
def test_head_exchange_matches_the_partitioners_head(mesh_cfg, n, dtype, monkeypatch):
    """Where the layers' products carry their exchanges the head's does too
    (`tp.gather_matmul_alone`: the rows' other `tp` chunks and
    `lm_head`'s other `fsdp` shards arrive by permutes behind its own
    matmuls, the arrived shards are kept for the backward, which sends none
    again, and `lm_head`'s gradient leaves by fsdp.py's ring). In float32
    the loss and EVERY gradient leaf (`lm_head`, `final_norm`, `embed`, the
    layers') agree with the partitioner's form of the same mesh
    (`_rows_mesh` patched to None) within the tolerances the layers' ring
    tests hold against one device. In bfloat16, as the four-chip cell runs
    it, the partials of the K shards are summed in float32 and rounded once,
    as the one product is: against the float32 program (its weights the
    ones these were rounded from), no leaf is further on average than with the head ALONE left to the partitioner
    (`head_exchanged` patched to 0), to the tenth or so by which rounding
    moves single elements either way; a coarser sum or a shard left out
    would read many times further."""
    from ray_tpu.models import transformer
    from ray_tpu.train.step import state_shardings

    cfg, mesh, run = _program_at_dtype(mesh_cfg, n, dtype)
    assert transformer.head_exchanged(cfg, mesh, 8, 64) == 1
    text, loss, grads = run()
    as32 = lambda tree: jax.tree_util.tree_map(
        lambda a: np.asarray(a.astype(jnp.float32)), tree)
    if dtype == "float32":
        monkeypatch.setattr(transformer, "_rows_mesh", lambda *a: None)
    else:
        want = as32(_program_at_dtype(mesh_cfg, n, "float32")[2]()[2])
        monkeypatch.setattr(transformer, "head_exchanged", lambda *a: 0)
    assert transformer.head_exchanged(cfg, mesh, 8, 64) == 0
    parent_text, parent_loss, parent = run()
    assert text.count("collective_permute") > parent_text.count("collective_permute")
    if dtype == "float32":
        np.testing.assert_allclose(float(loss), float(parent_loss), rtol=2e-6)
        jax.tree_util.tree_map_with_path(
            lambda path, g, w: np.testing.assert_allclose(
                g, w, rtol=2e-4, atol=2e-6 * float(np.abs(w).max()),
                err_msg=jax.tree_util.keystr(path)), as32(grads), as32(parent))
    else:
        np.testing.assert_allclose(float(loss), float(parent_loss), rtol=2.0 ** -8)
        jax.tree_util.tree_map_with_path(
            lambda path, g, p, w: np.testing.assert_array_less(
                np.abs(g - w).mean(), 1.25 * np.abs(p - w).mean(),
                err_msg=jax.tree_util.keystr(path)),
            as32(grads), as32(parent), want)
    # (the ring leaves each rank its own shard: the leaf's own sharding)
    assert grads["lm_head"].sharding.is_equivalent_to(
        state_shardings(cfg, mesh, default_optimizer()).params["lm_head"], 2)


@pytest.mark.parametrize("why,want", [
    ("fsdp2xtp2", 1), ("dp2xfsdp2xtp2", 1), ("dp_only", 0), ("tp1", 0),
    ("fsdp1", 0), ("experts", 0), ("fused", 0), ("seq_not_divisible", 0),
    ("loss_chunk", 0)])
def test_head_exchanged_says_where_the_heads_product_carries_its_exchanges(why, want):
    """`head_exchanged` is 1 exactly where `_rows_mesh` hands the head its
    features with their rows over `tp` and the loss takes whole logits: 0
    on a `dp`-only mesh, with `tp` 1, with `fsdp` 1, for the expert layer and
    the fused blocks, where `tp` does not divide the sequence and under
    `loss_chunk` (the chunked loss is the partitioner's); the train step's
    `xla.compile` spans carry it, and the head's permutes over `tp` and
    `fsdp` are in the lowered step where it says 1: with the layers' scan
    taken away (`n_layers` 0) the text names permutes at 1 and none at 0."""
    import dataclasses

    from ray_tpu.models import transformer
    from ray_tpu.util import tracing

    cfg, seq = ModelConfig.tiny(), 64
    mesh_cfg = {"dp_only": MeshConfig(dp=8), "tp1": MeshConfig(dp=2, fsdp=4, tp=1),
                "fsdp1": MeshConfig(dp=4, fsdp=1, tp=2),
                "dp2xfsdp2xtp2": MeshConfig(dp=2, fsdp=2, tp=2)}.get(
                    why, MeshConfig(dp=1, fsdp=2, tp=2))
    if why == "experts":
        cfg = ModelConfig.tiny_moe()
    elif why == "fused":
        cfg = dataclasses.replace(cfg, fused_ffn=True)
    elif why == "loss_chunk":
        cfg = dataclasses.replace(cfg, loss_chunk=16)
    elif why == "seq_not_divisible":
        seq = 63
    mesh = make_virtual_mesh(mesh_cfg.dp * mesh_cfg.fsdp * mesh_cfg.tp, mesh_cfg)
    assert transformer.head_exchanged(cfg, mesh, 8, seq) == want
    if why == "fused":  # (one chip only: nothing to lower on a mesh)
        return
    cfg = dataclasses.replace(cfg, n_layers=0)
    step_fn, init_fn, _ = make_train_step(cfg, mesh, default_optimizer())
    tokens = jax.ShapeDtypeStruct((8, seq), jnp.int32)
    tracing.clear()
    lowered = step_fn.lower(jax.eval_shape(init_fn, jax.random.PRNGKey(0)),
                            {"inputs": tokens, "targets": tokens})
    assert ("collective_permute" in lowered.as_text()) == bool(want)
    spans = [e["args"] for e in tracing.get_events()
             if e["name"] == "xla.compile" and "step" in e["args"]["fun_name"]]
    assert spans and all(a["head_exchanged"] == want for a in spans), spans


@pytest.mark.parametrize("dtype", ["bf16_scales", "bf16"])
@pytest.mark.parametrize("mesh_cfg,n", [
    (MeshConfig(dp=1, fsdp=2, tp=2), 4), (MeshConfig(dp=2, fsdp=2, tp=1), 4),
], ids=["fsdp2xtp2", "dp2xfsdp2"])
def test_norm_scale_gradients_in_bf16_are_no_further_from_float32(
        mesh_cfg, n, dtype, monkeypatch):
    """The sum of the ranks' partial norm-scale gradients is no less exact
    than the partitioner's all-reduce in the layers was (the same mesh with
    the scales left as they are, switched here): it is taken in float32 and
    rounded once to the leaf's dtype, where that one adds partials already
    rounded. Against one device's float32 gradients at the same weights:
    `bf16_scales` (a float32 program whose two norm leaves alone are
    bfloat16, so the partials are exact and the sum is all that rounds):
    every element within half a bfloat16 step, and no further on average;
    `bf16` (the whole program in bfloat16, as the four-chip cell runs it:
    its own rounding, the same on both sides, is nearly all of the distance
    and moves single elements either way, so the two means tie to a tenth
    or so; a rank left out or a coarser sum would read many times further):
    within a quarter."""
    import dataclasses

    from ray_tpu.models import transformer
    from ray_tpu.train.step import state_shardings

    scales = transformer._NORM_SCALES
    cfg32 = dataclasses.replace(ModelConfig.tiny(), n_kv_heads=2)
    cfg = (cfg32 if dtype == "bf16_scales"
           else dataclasses.replace(cfg32, dtype=jnp.bfloat16))
    mesh = make_virtual_mesh(n, mesh_cfg)
    params = init_params(jax.random.PRNGKey(0), cfg)
    # scales away from one, as a trained model's are
    for k, key in zip(scales, jax.random.split(jax.random.PRNGKey(2))):
        params["layers"][k] = (1 + 0.1 * jax.random.normal(
            key, params["layers"][k].shape)).astype(jnp.bfloat16)
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=8, seq=64)
    grad = lambda m, c: jax.jit(jax.grad(lambda p, b: loss_fn(p, b, c, m)[0]))
    want = grad(None, cfg32)(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), params), batch)["layers"]

    b_sh = batch_sharding(mesh)
    on_mesh = jax.device_put(batch, {k: b_sh[k] for k in batch})
    placed = jax.device_put(
        params, state_shardings(cfg, mesh, default_optimizer()).params)
    ours = grad(mesh, cfg)(placed, on_mesh)["layers"]
    monkeypatch.setattr(transformer, "_NORM_SCALES", ())
    theirs = grad(mesh, cfg)(placed, on_mesh)["layers"]
    for k in scales:
        assert ours[k].dtype == theirs[k].dtype == jnp.bfloat16
        far = lambda got: np.abs(np.asarray(got[k].astype(jnp.float32))
                                 - np.asarray(want[k]))
        if dtype == "bf16":
            assert far(ours).mean() <= 1.25 * far(theirs).mean(), k
            continue
        half_step = 2.0 ** -8 * np.abs(np.asarray(want[k])) * 1.01 + 1e-9
        assert (far(ours) <= half_step).all(), k
        assert far(ours).mean() <= far(theirs).mean(), k


@pytest.mark.parametrize("why", ["seq_not_divisible", "experts", "fsdp1"])
def test_tp_exchange_stays_off_where_the_block_is_not_the_plain_one(why, monkeypatch):
    """Where `tp` does not divide the sequence, or the expert layer runs, or
    the mesh has fsdp 1 (the weights do not come exchanged), the program is
    the parent's to the letter: the lowered text is the one with
    `parallel/tp.py`'s products made unreachable, and it names none of them."""
    from ray_tpu.models import transformer
    from ray_tpu.parallel import tp

    cfg, seq = ModelConfig.tiny(), 64
    mesh_cfg = MeshConfig(dp=2, fsdp=2, tp=2)
    if why == "seq_not_divisible":
        seq = 63
    elif why == "experts":
        cfg = ModelConfig.tiny_moe()
    else:
        mesh_cfg = MeshConfig(dp=4, fsdp=1, tp=2)
    mesh = make_virtual_mesh(8, mesh_cfg)
    assert transformer.tp_exchanges_per_layer(cfg, mesh, 8, seq) == 0
    assert transformer.dw_rings_ordered(cfg, mesh, 8, seq) == 0
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((8, seq), jnp.int32)
    batch = {"inputs": tokens, "targets": tokens}

    def text():
        return jax.jit(jax.value_and_grad(
            lambda p, b: loss_fn(p, b, cfg, mesh)[0])).lower(params, batch).as_text()

    ours = text()

    def unreachable(*a, **k):
        raise AssertionError("the tp route was taken")

    for name in ("gather_matmul", "matmul_scatter", "shard_rows",
                 "gather_matmul_alone"):
        monkeypatch.setattr(tp, name, unreachable)
    monkeypatch.setattr(transformer, "_rows_mesh", lambda *a: None)
    assert ours == text()


@pytest.mark.parametrize("field", ["sp", "pp", "seq_parallel"])
def test_mesh_and_model_config_refuse_removed_fields(field):
    """The mesh has the axes a chip has run (dp, fsdp, tp) and the dense
    block one attention path: what PR 47 removed is an unknown field."""
    config = ModelConfig if field == "seq_parallel" else MeshConfig
    with pytest.raises(TypeError, match=field):
        config(**{field: 2})


def test_sharded_matches_unsharded():
    """The same init + batch gives the same loss on 1 device and 8."""
    cfg = ModelConfig.tiny()
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    batch = _batch(jax.random.PRNGKey(1), cfg)
    loss_1dev, _ = loss_fn(params, batch, cfg)

    mesh = make_virtual_mesh(8, MeshConfig(dp=2, fsdp=2, tp=2))
    from ray_tpu.parallel.mesh import logical_sharding, shard_pytree, DEFAULT_RULES
    from ray_tpu.models.transformer import param_logical_axes

    p_sh = logical_sharding(mesh, param_logical_axes(cfg), DEFAULT_RULES)
    sharded = shard_pytree(params, p_sh)
    loss_8dev, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg))(sharded, batch)
    np.testing.assert_allclose(float(loss_1dev), float(loss_8dev), rtol=1e-5)


@pytest.mark.slow
def test_chunked_loss_matches_dense():
    """cfg.loss_chunk computes identical loss+grads without full logits."""
    import dataclasses

    cfg = ModelConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=2, seq=32)
    cfg_c = dataclasses.replace(cfg, loss_chunk=8)

    loss_d, _ = loss_fn(params, batch, cfg)
    loss_c, _ = loss_fn(params, batch, cfg_c)
    np.testing.assert_allclose(float(loss_d), float(loss_c), rtol=2e-5)

    g_d = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(params)
    g_c = jax.grad(lambda p: loss_fn(p, batch, cfg_c)[0])(params)
    for leaf in ("final_norm", "lm_head", "embed"):
        np.testing.assert_allclose(g_d[leaf], g_c[leaf], rtol=1e-4,
                                   atol=1e-6, err_msg=leaf)

    with pytest.raises(ValueError, match="loss_chunk"):
        loss_fn(params, batch, dataclasses.replace(cfg, loss_chunk=7))


def test_chunked_nll_matches_dense_with_mask_and_odd_vocab():
    """`chunked_token_nll` (the head and the softmax a chunk at a time) is
    `token_nll` over the whole logits: value and the gradients of features
    and head, under a loss mask, at a vocabulary that is no multiple of 128."""
    from ray_tpu.models.transformer import chunked_token_nll, token_nll

    b, s, d, vocab = 2, 24, 16, 300
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (b, s, d), jnp.float32)
    head = jax.random.normal(ks[1], (d, vocab), jnp.float32) * d ** -0.5
    targets = jax.random.randint(ks[2], (b, s), 0, vocab)
    mask = jax.random.bernoulli(ks[3], 0.6, (b, s))

    def dense(x, head):
        return token_nll((x @ head).astype(jnp.float32), targets, mask)

    def chunked(x, head):
        return chunked_token_nll(x, head, targets, mask, 8)

    (l_d, g_d), (l_c, g_c) = (jax.value_and_grad(f, argnums=(0, 1))(x, head)
                              for f in (dense, chunked))
    np.testing.assert_allclose(float(l_d), float(l_c), rtol=1e-5)
    for a, bb in zip(g_d, g_c):
        np.testing.assert_allclose(a, bb, rtol=1e-4, atol=1e-6)
    # a masked position moves nothing
    assert not np.asarray(g_c[0])[~np.asarray(mask)].any()


def test_selective_remat_matches_full():
    """remat='dots' (selective checkpoint policy) is numerically identical."""
    import dataclasses

    cfg = ModelConfig.tiny()
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=2, seq=32)
    loss_ref, _ = loss_fn(params, batch, cfg)
    cfg_d = dataclasses.replace(cfg, remat="dots")
    loss_dots, _ = jax.jit(lambda p: loss_fn(p, batch, cfg_d))(params)
    np.testing.assert_allclose(float(loss_ref), float(loss_dots), rtol=2e-5)


@pytest.mark.slow
def test_hybrid_dcn_mesh_train_step():
    """2 simulated slices x 4-chip ICI mesh: dp rides the dcn axis."""
    from ray_tpu.parallel import make_hybrid_mesh

    cfg = ModelConfig.tiny()
    mesh = make_hybrid_mesh(MeshConfig(dp=1, fsdp=2, tp=2), dcn_dp=2)
    assert mesh.shape == {"dp": 2, "fsdp": 2, "tp": 2}
    step_fn, init_fn, _ = make_train_step(cfg, mesh, default_optimizer(1e-3))
    state = init_fn(jax.random.PRNGKey(0))
    batch = _batch(jax.random.PRNGKey(1), cfg, batch=8, seq=64)
    batch = jax.device_put(batch, {k: batch_sharding(mesh)[k] for k in batch})
    state, metrics = step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))

def test_llama3_8b_sharding_lowers_on_virtual_v5e64():
    """AOT shape-level proof: the full llama3_8b train step traces and
    lowers (GSPMD shardings attached) over a 64-device mesh laid out
    fsdp=16 x tp=4 — no weights materialized, subprocess so the
    64-device CPU platform doesn't leak into other tests."""
    script = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=64")
import jax
jax.config.update("jax_platforms", "cpu")
import dataclasses
import jax.numpy as jnp
from ray_tpu.models import ModelConfig
from ray_tpu.parallel import MeshConfig, make_virtual_mesh
from ray_tpu.train import make_train_step, batch_sharding
from ray_tpu.train.step import default_optimizer, state_shardings

assert len(jax.devices()) == 64, jax.devices()
cfg = dataclasses.replace(ModelConfig.llama3_8b(), max_seq_len=4096,
                          remat="dots", loss_chunk=512)
mesh = make_virtual_mesh(64, MeshConfig(dp=1, fsdp=16, tp=4))
optimizer = default_optimizer()
step_fn, init_fn, sh = make_train_step(cfg, mesh, optimizer)

# shape-level state on the real shardings — nothing materialized
state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
import numpy as np
n_params = sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(state_shape.params))
assert n_params > 8.0e9, n_params

tokens = jax.ShapeDtypeStruct((16, 4096), jnp.int32)
batch = {"inputs": tokens, "targets": tokens}
lowered = step_fn.lower(state_shape, batch)
text = lowered.as_text()
assert "sharding" in text  # GSPMD annotations attached
print("LOWERED_OK", n_params)
"""
    out = subprocess.run(
        [sys.executable, "-c", script], cwd=REPO, capture_output=True,
        text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": REPO})
    assert "LOWERED_OK" in out.stdout, (out.stdout[-2000:], out.stderr[-2000:])
