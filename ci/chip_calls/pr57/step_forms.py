"""The four-chip cell's train step with a layer's weight-gradient rings
ordered and not ordered, in ONE process that holds the four chips: PR 54's
`step_forms.py` (the same state, the same batches, each form compiled and
timed in turn, some traced and reduced by `ci/chip_calls/pr38/exposed.py`)
with this PR's two forms. A form is the program with one name of it
replaced HERE:

    parent   `fsdp.weight_grads` is never handed `taken`: no ring knows of
             an order and the scheduler takes the smallest transfer first
             (the text of commit c2b9b8f)
    change   the program as it stands: `fsdp.RingOrder` goes from product
             to product

    python ci/chip_calls/pr57/step_forms.py --forms parent,change,change,parent \
        --steps 12 --trace parent,change --same-bits --out chiprun_out/pr57/call1

`--same-bits`: two layers at the cell's widths, one batch, the same weights:
loss and every gradient leaf of `value_and_grad(loss_fn)` under the mesh,
ordered beside not ordered, compared bit for bit ON THE CHIP. `--tiny`: the
control flow on the CPU's virtual devices.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from ci.chip_calls.pr54 import step_forms as pr54  # noqa: E402


def forms():
    from ray_tpu.parallel import fsdp

    ordered = fsdp.weight_grads

    def unordered(xs, dys, dim, mesh, taken=None):
        return ordered(xs, dys, dim, mesh)[0], taken

    return {"parent": lambda: pr54.base.replaced(fsdp, "weight_grads", unordered),
            "change": contextlib.nullcontext}


if __name__ == "__main__":
    pr54.forms = pr54.base.forms = forms
    check = "--same-bits" in sys.argv
    if check:
        sys.argv.remove("--same-bits")
    if "--out" not in sys.argv:
        sys.argv += ["--out", "chiprun_out/pr57/forms"]
    pr54.base.main()
    if check:
        print(json.dumps(pr54.same_bits("--tiny" in sys.argv)), flush=True)
