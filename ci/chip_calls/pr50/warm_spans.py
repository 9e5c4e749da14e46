"""`warm_spans.py <checkout> kimi|pangu`: ON THE CHIP, one process: the
cell's own replica constructor (weights from a seed, the engine, every
program the traffic file warms, through the engine's cache) with the
program's `xla.compile` spans kept, then one line a program: what jax spent
tracing, lowering and compiling (or reading its cache) and, where the
program says it, `layers` and `layer_bodies_traced`. The compile cache is the
benchmark's (`<checkout>/.jax_compile_cache`), so after a run of the cell
this is a WARM replica. Works on the parent too (no `layers` there)."""
import collections
import json
import os
import sys
import time

root = os.path.abspath(sys.argv[1])
which = sys.argv[2]
sys.path.insert(0, root)
os.chdir(root)
from perfbench.lib.manifest import Manifest, prepare_env

prepare_env(root, False)
from ray_tpu.util import tracing

cell = {"kimi": "kimi-linear-serve-longgen", "pangu": "openpangu-serve-longctx"}[which]
man = Manifest(root)
c = man.cell(cell)
spec = {"config": man.load_config(c["config"]), "traffic": man.load_traffic(c["traffic"]),
        "seed": 2147483000 + os.getpid() % 1000, "rehearsal": False}
if which == "kimi":
    from perfbench.lib.hybrid_replica import HybridBenchReplica as Replica
else:
    from perfbench.lib.pangu_replica import PanguBenchReplica as Replica

tracing.record_compiles()
t0 = time.time()
rep = Replica(spec)
if rep.fatal:
    sys.exit(f"warm_spans: {rep.fatal}")
times = rep.times
rep.engine.stop_driver()
programs = collections.OrderedDict()
for e in tracing.get_events():
    if e["name"] != "xla.compile":
        continue
    a = e["args"]
    name = a["fun_name"][4:-1] if a["fun_name"].startswith("jit(") else a["fun_name"]
    # a program's three spans follow each other: a new trace opens a new line
    if a["event"] == "jaxpr_trace_duration" or not programs or \
            next(reversed(programs.values()))["program"] != name:
        programs[len(programs)] = {"program": name}
    row = next(reversed(programs.values()))
    row[a["event"].replace("_duration", "").replace("jaxpr_", "")] = round(e["dur"] / 1e6, 3)
    for k in ("layers", "layer_bodies_traced"):
        if k in a:
            row[k] = a[k]
for row in programs.values():
    if row["program"] in ("decode_step", "_prefill_first", "_write_state"):
        print(json.dumps(row), flush=True)
spans = sum(v for r in programs.values() for k, v in r.items() if isinstance(v, float))
print(json.dumps({
    "which": which, "device_to_warm_s": round(times["t_warm"] - times["t_device"], 2),
    "enter_to_device_s": round(times["t_device"] - times["t_enter"], 2),
    "compile_setup": rep.compile_setup, "xla_compile_spans_s": round(spans, 2),
    "programs_with_spans": len(programs),
    "spans_in_ring": len(tracing.get_events())}), flush=True)
os._exit(0)   # the engine's threads and the chip go with the process
