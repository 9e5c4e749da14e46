"""The four-chip cell's train step with its kept weight-gradient products
reading a staged slice and slicing inside their own fusion, in ONE process
that holds the four chips: PR 54's `step_forms.py` (the same state, the same
batches, each form compiled and timed in turn, some traced and reduced by
`ci/chip_calls/pr38/exposed.py`) with this PR's forms. A form is the program
with one name of it replaced HERE:

    parent   `fsdp._staged` hands its slice on as it is: every kept product
             slices its operand inside its own fusion (the text of commit
             62ba75d)
    change   the program as it stands: every ring that stands in the order
             stages its kept slice (seven slices a layer)
    gate_up  only gate's and up's rings stage (the two whose `x` is a tuple
             of chunks, one of them the evicted one): the two slices that
             buy the gain, without the five that pay for themselves

    python ci/chip_calls/pr59/step_forms.py --forms parent,change,change,parent \
        --steps 12 --trace parent,change --same-bits --out chiprun_out/pr59/call1

`--same-bits`: two layers at the cell's widths, one batch, the same weights:
loss and every gradient leaf of `value_and_grad(loss_fn)` under the mesh,
staged beside not staged, compared bit for bit ON THE CHIP. `--tiny`: the
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
    from ray_tpu.parallel import fsdp, tp

    @contextlib.contextmanager
    def gate_up():
        staged, weight_grads, asked = fsdp._staged, tp._weight_grads, []

        def noting_the_caller(x, dys, ws, dim, *rest):
            asked.append(dim == 0 and isinstance(x, (tuple, list)))
            try:
                return weight_grads(x, dys, ws, dim, *rest)
            finally:
                asked.pop()

        with pr54.base.replaced(tp, "_weight_grads", noting_the_caller), \
                pr54.base.replaced(fsdp, "_staged",
                                   lambda part: staged(part) if asked[-1] else part):
            yield

    return {"parent": lambda: pr54.base.replaced(fsdp, "_staged", lambda part: part),
            "change": contextlib.nullcontext, "gate_up": gate_up}


if __name__ == "__main__":
    pr54.forms = pr54.base.forms = forms
    check = "--same-bits" in sys.argv
    if check:
        sys.argv.remove("--same-bits")
    if "--out" not in sys.argv:
        sys.argv += ["--out", "chiprun_out/pr59/forms"]
    pr54.base.main()
    if check:
        print(json.dumps(pr54.same_bits("--tiny" in sys.argv)), flush=True)
