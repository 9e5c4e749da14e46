"""`lowered_hash.py <checkout>`: PR 46's `lowered_hash.py` (the lowered text,
for the described v5e, of the eleven programs the other cells run, and the
four-chip cell's step last, for contrast) with the Granite cell's decode
step and prompt pass behind them. Run on the parent's checkout and on this
one: every line but the four-chip step's must be the same. Needs no chip."""
import os
import runpy
import sys

here = os.path.dirname(os.path.abspath(__file__))
g = runpy.run_path(os.path.join(os.path.dirname(here), "pr46", "lowered_hash.py"))
from perfbench.lib import granite_model  # noqa: E402  (the checkout's: pr46's path)

g["out"].clear()
g["serving"]("granite", granite_model, "granite-4.0-h-small.1of2.json", 16384)
print(g["json"].dumps(g["out"], indent=1))
