"""Serve an LLM with continuous batching behind the Serve HTTP ingress.

    python examples/serve_llm.py                 # tiny model, CPU replica
    python examples/serve_llm.py --preset b1 --tpus 1   # replica owns a chip
    curl -X POST localhost:<port>/LLM \
         -d '{"prompt": [1, 17, 42], "max_new_tokens": 8}'

One process per chip: this launcher never imports jax. The replica builds
its weights from the seed in its own process (`ray_tpu.serve.llm.LLMReplica`),
and with `--tpus 1` it is a worker spawned for that chip grant.
"""

import argparse
import os
import sys
import time

try:
    import ray_tpu  # noqa: F401
except ImportError:  # running from a checkout without install
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMReplica

    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", help="ModelConfig preset")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tpus", type=int, default=0,
                    help="chips the replica owns (0: a CPU worker)")
    args = ap.parse_args()

    ray_tpu.init(num_cpus=4, resources={"TPU": args.tpus} if args.tpus else None)
    options = {"resources": {"TPU": args.tpus}} if args.tpus else {}
    D = serve.deployment(LLMReplica, name="LLM", ray_actor_options=options)
    handle = serve.run(D.bind(args.preset, seed=args.seed, num_slots=4,
                              max_len=256))
    _, port = serve.start_http_proxy()
    print(f"serving on http://127.0.0.1:{port}/LLM")

    # demo request through the handle
    out = ray_tpu.get(handle.remote(
        {"prompt": [1, 17, 42], "max_new_tokens": 8}), timeout=600)
    print("generated:", out)
    assert "jax" not in sys.modules  # the replica owns the device, not us

    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        serve.shutdown()
        ray_tpu.shutdown()


if __name__ == "__main__":
    main()
