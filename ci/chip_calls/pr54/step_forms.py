"""The four-chip cell's train step with `w_down`'s ring product pinned and not
pinned, in ONE process that holds the four chips: PR 46's `step_forms.py`
(the same state, the same batches, each form compiled and timed in turn,
some traced and reduced by `ci/chip_calls/pr38/exposed.py`) with this PR's
two forms. A form is the program with one name of it replaced HERE:

    parent   `fsdp.ring_products` never pins (its `own_first` is dropped):
             the compiled step runs the arrived shard's product first (the
             text of commit 4ba736f)
    change   the program as it stands: the FFN's backward asks `own_first`

    python ci/chip_calls/pr54/step_forms.py --forms parent,change,change,parent \
        --steps 12 --trace parent,change --same-bits --out chiprun_out/pr54/call1

`--same-bits`: two layers at the cell's widths, one batch, the same weights:
loss and every gradient leaf of `value_and_grad(loss_fn)` under the mesh,
pinned beside not pinned, compared bit for bit ON THE CHIP (the CPU's
virtual devices say the same in `tests/test_train_step.py`; the chip's
fusion of the sum into a product is its own). `--tiny`: the control flow on
the CPU's virtual devices.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from ci.chip_calls.pr46 import step_forms as base  # noqa: E402


def forms():
    from ray_tpu.parallel import fsdp

    pinned = fsdp.ring_products

    def never_pinned(*args, own_first=False, **kw):
        return pinned(*args, **kw)

    return {"parent": lambda: base.replaced(fsdp, "ring_products", never_pinned),
            "change": contextlib.nullcontext}


def same_bits(tiny: bool) -> dict:
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
            fn = jax.jit(jax.value_and_grad(
                lambda p, b: loss_fn(p, b, cfg, mesh)[0]))
            text = fn.lower(params, batch).as_text()
            loss, grads = fn(params, batch)
        got[form] = (text.count("optimization_barrier"), float(loss),
                     jax.tree_util.tree_map(np.asarray, grads))
    bits = lambda a: a.view(f"u{a.dtype.itemsize}")
    leaves = jax.tree_util.tree_leaves_with_path(jax.tree_util.tree_map(
        lambda a, b: bool((bits(a) == bits(b)).all()),
        got["parent"][2], got["change"][2]))
    return {"same_bits": {
        "barriers_in_lowered_text": [got["parent"][0], got["change"][0]],
        "loss": [got["parent"][1], got["change"][1]],
        "loss_equal": got["parent"][1] == got["change"][1],
        "gradient_leaves": len(leaves),
        "leaves_that_differ": [jax.tree_util.keystr(k) for k, same in leaves
                               if not same]}}


if __name__ == "__main__":
    base.forms = forms
    check = "--same-bits" in sys.argv
    if check:
        sys.argv.remove("--same-bits")
    if "--out" not in sys.argv:
        sys.argv += ["--out", "chiprun_out/pr54/forms"]
    base.main()
    if check:
        print(json.dumps(same_bits("--tiny" in sys.argv)), flush=True)
