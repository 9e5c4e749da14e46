"""Driver of open-loop traffic (ONE kind of request, `lib.traffic`'s own
generator) against SmallThinker-21BA3B (global and window attention layers
1 : 3, a sequential block whose router reads the attention's input, ReGLU
experts all held): `drivers.open_loop_http` with
`lib.sthink_replica.SthinkBenchReplica` in the replica's place. The path
(HTTP stream -> proxy -> router -> replica -> `ContinuousBatchingEngine`),
the load generator and the record are that driver's own (`_drive`).

A program whose window form has one block only (one whose
`ray_tpu/models/hybrid.py` knows no `route_from`) cannot run the cell: the
driver says so and exits at once, before any process of the cluster exists."""

from __future__ import annotations

import asyncio
import os
import time

from perfbench.drivers.open_loop_http import _drive
from perfbench.lib import manifest, traffic as traffic_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(ctx) -> dict:
    with open(os.path.join(ROOT, "ray_tpu", "models", "hybrid.py")) as f:
        if "route_from" not in f.read():
            raise SystemExit("perfbench: this program's ray_tpu/models/hybrid.py "
                             "has no route_from (its window form is Command A+'s "
                             "block alone): it cannot serve the SmallThinker "
                             "model of this cell")
    import ray_tpu
    from perfbench.lib.sthink_replica import SthinkBenchReplica
    from ray_tpu import serve

    cell, tr, config = ctx["cell"], ctx["traffic"], ctx["config"]
    run_cfg, seconds = config["run"], float(ctx["seconds"])
    rule = traffic_mod.slot_rule(tr, run_cfg["num_slots"])
    print(f"[traffic] rate {tr['rate_per_s']}/s; slot rule: mean busy slots "
          f"{rule['mean_busy_slots']:.2f} + 3 sigma = {rule['needs_slots']:.2f} "
          f"of {run_cfg['num_slots']} (highest rate by the rule "
          f"{rule['max_rate_per_s']:.2f}/s) {'ok' if rule['ok'] else 'BROKEN'}",
          flush=True)
    schedule = traffic_mod.open_loop(tr, ctx["seed"], seconds, config["vocab_size"])
    for r in schedule:
        r["timeout_s"] = tr["request_timeout_s"]

    manifest.lay_serve_env(ctx)
    ray_tpu.init(num_cpus=8, resources={"TPU": cell["chips"]})
    try:
        t_ask = time.time()
        D = serve.deployment(
            SthinkBenchReplica, name="LLM", num_replicas=1,
            max_concurrent_queries=run_cfg["max_concurrent_queries"],
            ray_actor_options={"resources": {"TPU": 1}, "num_cpus": 0})
        serve.run(D.bind({k: ctx[k] for k in (
            "config", "traffic", "seed", "rehearsal", "out_dir", "control",
            "reference_file")}))
        _, port = serve.start_http_proxy()
        rec = asyncio.run(_drive("127.0.0.1", port, schedule, ctx, seconds))
        # this model's third number (`lib.granite_replica.compare_with_reference`)
        rec["compared"].append(("route_margin_max", rec["check"]["route_margin_max"],
                                tr["limits"]["route_margin_max"]))
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    rec["t_ask"] = t_ask
    return rec
