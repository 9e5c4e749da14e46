"""The control of `correct`: over a dozen seeds, in ONE process that holds
the chip, what the comparison with the reference reads for the program and
what it reads for the control (the nearest precision below the one the
configuration states: fp8 weights in the reference's place for the bf16
training cells, the engine on weights rounded to int8 for the serving cell).
The benchmark's own runs never run it; `PERF.md` holds its readings and the
limits set from them.

    python3 perfbench/control.py --workload <name> [--seeds 12] [--control-seeds 4]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.lib.manifest import Manifest, prepare_env  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    prepare_env(ROOT, args.cpu_rehearsal)
    man = Manifest(args.root)
    cell = man.cell(args.workload)
    config, traffic = man.load_config(cell["config"]), man.load_traffic(cell["traffic"])
    how = traffic["control"]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    runs = [(s, None) for s in seeds] + [(s, how) for s in seeds[:args.control_seeds]]
    ctx = {"config": config, "traffic": traffic, "seed": seeds[0],
           "rehearsal": args.cpu_rehearsal, "control": None, "runs": runs,
           "out_dir": os.path.join(ROOT, ".perfbench_out", args.workload),
           "reference_file": man.find("references", config["reference"] + ".py"),
           "chips": cell["chips"]}

    import ray_tpu

    ray_tpu.init(num_cpus=8, resources={"TPU": cell["chips"]})
    try:
        if traffic["kind"] == "token_batches":
            from perfbench.drivers.train_steps import control_loop
            from ray_tpu.air import ScalingConfig
            from ray_tpu.train import JaxTrainer

            result = JaxTrainer(control_loop, train_loop_config=ctx,
                                scaling_config=ScalingConfig(
                                    num_workers=1, use_tpu=True,
                                    chips_per_worker=cell["chips"])).fit()
            if result.error is not None:
                raise SystemExit(f"control worker failed: {result.error}")
            readings, device = result.metrics["readings"], result.metrics["device"]
        else:
            from perfbench.drivers.open_loop_http import _call
            from perfbench.lib.replica import BenchReplica
            from ray_tpu import serve

            D = serve.deployment(BenchReplica, name="LLM", num_replicas=1,
                                 ray_actor_options={"resources": {"TPU": 1},
                                                    "num_cpus": 0})
            serve.run(D.bind(ctx))
            _, port = serve.start_http_proxy()

            async def sweep():
                info = await _call("127.0.0.1", port, "info")
                return await _call("127.0.0.1", port, "control_sweep",
                                   {"runs": runs, "seconds": 51},
                                   timeout_s=3000), info["device"]
            readings, device = asyncio.run(sweep())
            serve.shutdown()
    finally:
        ray_tpu.shutdown()
    for r in readings:
        print(json.dumps(r), flush=True)
    keys = [k for k, v in readings[0].items()
            if isinstance(v, float) and k not in ("reference_loss",)]
    summary = {"workload": args.workload, "device": device, "control": how}
    for k in keys:
        prog = [r[k] for r in readings if r["mode"] == "program"]
        ctl = [r[k] for r in readings if r["mode"] != "program"]
        summary[k] = {"program_max": max(prog), "control_min": min(ctl),
                      "ratio": min(ctl) / max(prog) if max(prog) else None,
                      "program_seeds": len(prog), "control_seeds": len(ctl)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
