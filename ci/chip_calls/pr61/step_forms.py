"""The four-chip cell's train step with the head's product carrying its
exchanges and with the partitioner's head, in ONE process that holds the four
chips: PR 38's `step_forms.py` (the same state, the same batches, each form
compiled and timed in turn, some traced and reduced by
`ci/chip_calls/pr38/exposed.py`) with this PR's forms. A form is the program
with one name of it replaced HERE:

    parent   `models/transformer._head_logits` is the parent's head: the
             features gathered whole over `tp` by the partitioner, then
             `x @ lm_head` (the compiled text of commit bcbfb84: `temp`
             7,129,767,424 B)
    change   the program as it stands: `tp.gather_matmul_alone`
    fused    ... with the head's kept half of the gradient fused with the
             sum of what arrives, as a layer's ring has it (`weight_grads`
             without `alone`)
    loss_free  call 5's tree had `fsdp.backward_behind` (the backward's seed
             tied behind the loss's all-reduce, which in calls 1-2 stood in
             the gradient ring's window and waited 1.44 ms on the link) and
             this form switched it off: 292.25 / 292.41 against 292.25 /
             292.26, no wait either way, so it went, and the form with it

    python ci/chip_calls/pr61/step_forms.py --forms parent,change,change,parent \
        --steps 12 --trace parent,change --close --out chiprun_out/pr61/call1

`--close`: two layers at the cell's widths, one batch, the same weights: loss
and every gradient leaf of `value_and_grad(loss_fn)` under the mesh, the
exchanged head beside the partitioner's, ON THE CHIP: the largest difference
a leaf over the largest element of the parent's (the two K halves are summed
in float32 and rounded once, the partitioner's single product accumulates in
float32 inside the MXU pass: equal to the rounding of bfloat16, not bit for
bit). `--tiny`: the control flow on the CPU's virtual devices.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from ci.chip_calls.pr38 import step_forms as base  # noqa: E402


def forms():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import transformer
    from ray_tpu.parallel import fsdp

    def parents_head(params, x, cfg, mesh):
        if transformer._rows_mesh(cfg, mesh, *x.shape[:2]) is not None:
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(fsdp.BATCH_AXES, None, None)))
        return (x @ transformer.lm_head_weights(params, cfg)).astype(jnp.float32)

    @contextlib.contextmanager
    def parent():
        with base.replaced(transformer, "_head_logits", parents_head), \
                base.replaced(transformer, "head_exchanged", lambda *a: 0):
            yield

    grads = fsdp.weight_grads

    return {"parent": parent, "change": contextlib.nullcontext,
            # the head's kept half fused with the sum, as a layer's is
            "fused": lambda: base.replaced(
                fsdp, "weight_grads", lambda *a, alone=False, **k: grads(*a, **k)),
            # the loss's sum over the chips left where the scheduler stands it
            # (a form of call 5's tree, which held the loss's all-reduce
            # where the forward made it; it read the same and went)
            "loss_free": contextlib.nullcontext}


def close(tiny: bool) -> dict:
    import jax
    import numpy as np

    from perfbench.lib import model, traffic
    from ray_tpu.models import loss_fn
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train import batch_sharding
    from ray_tpu.train.step import default_optimizer, state_shardings

    conf = json.load(open("perfbench/configs/mistral-7b-v0.3.4chip.json"))
    tr = json.load(open("perfbench/traffic/pretrain-2x2048.json"))
    cfg = model.model_config(conf, n_layers=2, max_seq_len=tr["seq"],
                             remat=conf["run"]["remat"], loss_chunk=0,
                             fused_ffn=False, fused_attn=False)
    if tiny:
        cfg = dataclasses.replace(cfg, vocab_size=512, d_model=128, n_heads=4,
                                  n_kv_heads=2, d_ff=256, max_seq_len=64)
        tr = dict(tr, seq=64)
    mesh = make_mesh(MeshConfig(**conf["run"]["mesh"]), jax.devices()[:4])
    p_sh = state_shardings(cfg, mesh, default_optimizer()).params
    params = model.make_params(cfg, 97531, p_sh)
    t = traffic.token_batches(tr, 97531, cfg.vocab_size)[0]
    b_sh = batch_sharding(mesh)
    batch = {"inputs": jax.device_put(t[:, :-1], b_sh["inputs"]),
             "targets": jax.device_put(t[:, 1:], b_sh["targets"])}
    got = {}
    for form, ctx in forms().items():
        with ctx():
            loss, grads = jax.jit(jax.value_and_grad(
                lambda p, b: loss_fn(p, b, cfg, mesh)[0]))(params, batch)
        got[form] = (float(loss), jax.tree_util.tree_map(
            lambda a: np.asarray(a, np.float32), grads))
    far = jax.tree_util.tree_leaves_with_path(jax.tree_util.tree_map(
        lambda a, b: float(np.abs(a - b).max() / np.abs(a).max()),
        got["parent"][1], got["change"][1]))
    return {"close": {
        "loss": [got["parent"][0], got["change"][0]],
        "gradient_leaves": len(far),
        "max_abs_diff_over_max_abs": {
            jax.tree_util.keystr(k): round(v, 6) for k, v in far}}}


if __name__ == "__main__":
    base.forms = forms
    check = "--close" in sys.argv
    if check:
        sys.argv.remove("--close")
    if "--out" not in sys.argv:
        sys.argv += ["--out", "chiprun_out/pr61/forms"]
    base.main()
    if check:
        print(json.dumps(close("--tiny" in sys.argv)), flush=True)
