"""Turns a checkout of this PR's tree into the form the review of PR 38 asked
to be read beside it: the partitioner's four `tp` all-reduces a layer
(`_rows_mesh` says no, `parallel/tp.py` unreachable), with `x @
ExchangedWeight` and its dx made `fsdp.ring_products`, so the weights' shards
go round fsdp's ring inside the Megatron form's products. Used by call6.sh
for the benchmark's cell if `step_forms.py` reads this form within the bound
of the whole change.

    python ci/chip_calls/pr38/rings_only_tree.py _check/rings_only
"""

import sys


def edit(path, pairs):
    text = open(path).read()
    for old, new in pairs:
        assert text.count(old) == 1, (path, old)
        text = text.replace(old, new)
    open(path, "w").write(text)


root = sys.argv[1]
edit(f"{root}/ray_tpu/models/transformer.py", [(
    "    if (tp.axis_size(mesh) == 1 or seq % tp.axis_size(mesh)\n",
    "    return None\n    if (tp.axis_size(mesh) == 1 or seq % tp.axis_size(mesh)\n")])
edit(f"{root}/ray_tpu/parallel/fsdp.py", [
    ("def _matmul(x, w, dim, mesh):\n    return x @ w\n",
     "def _matmul(x, w, dim, mesh):\n    return _ring_product(x, w, dim, mesh)\n\n\n"
     "def _ring_product(x, w, dim, mesh):\n"
     "    from jax.ad_checkpoint import checkpoint_name\n"
     "    # (named as parallel/tp.py names its products: else remat=\"dots\"\n"
     "    # keeps the float32 partial products of every layer)\n"
     "    return checkpoint_name(\n"
     "        ring_products([[x]], [w], dim, False, mesh)[0], \"tp_product\")\n"),
    ("    return x @ w, (x, w)\n",
     "    return _ring_product(x, w, dim, mesh), (x, w)\n"),
    ("    dx = jax.lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))\n",
     "    dx, = ring_products([[dy]], [w], dim, True, mesh)\n")])
