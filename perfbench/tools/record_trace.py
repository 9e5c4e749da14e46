"""Record the small trace kept beside the reduction (`lib/testdata/`):
a few jitted steps on whatever devices this process holds, under the
benchmark's span names, with pauses between them. Prints what the trace
holds, plane by plane, and the reduction of it.

    python perfbench/tools/record_trace.py <out_dir>
"""

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from perfbench.lib import xplane


def main(out_dir: str) -> None:
    devs = jax.devices()
    print("devices", [(d.platform, d.device_kind) for d in devs], flush=True)
    mesh = Mesh(devs, ("x",))
    sh = NamedSharding(mesh, P("x"))
    x = jax.device_put(jnp.ones((len(devs) * 256, 512), jnp.bfloat16), sh)

    @jax.jit
    def step(a):
        def body(c, _):
            return jnp.tanh(c @ c.T @ c) * 0.01, None
        c, _ = jax.lax.scan(body, a, None, length=3)
        return c + jnp.sum(c, axis=0, keepdims=True)  # all-reduce on a mesh

    step(x).block_until_ready()
    tmp = os.path.join(out_dir, "_trace_tmp")
    jax.profiler.start_trace(tmp)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            x = step(x)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait_input"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(tmp)
    kept = os.path.join(out_dir, f"toy_{devs[0].platform}_{len(devs)}.xplane.pb")
    shutil.copy(path, kept)
    print("kept", kept, os.path.getsize(kept), "bytes", flush=True)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            ev = list(line.events)
            print("  LINE", repr(line.name), len(ev))
            for e in ev[:4]:
                print("     ", e.name[:120], e.start_ns, e.duration_ns,
                      [(k, str(v)[:40]) for k, v in list(e.stats)[:6]])
    print(json.dumps(xplane.reduce(xplane.load(kept)), indent=1))
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/probe")
