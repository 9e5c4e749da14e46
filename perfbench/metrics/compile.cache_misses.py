"""Programs the chip holder looked up in jax's persistent cache before the
window and did not find (`cache` `miss` on the backend-compile `xla.compile`
span): a warm run reads 0."""

from perfbench.lib.setup_spans import compiles


def read(run):
    c = compiles(run)
    if c is None:
        return None
    return float(sum(1 for e in c["backend"] if e["args"].get("cache") == "miss"))
