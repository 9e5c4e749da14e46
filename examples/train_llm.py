"""Train a Llama-class LM on a sharded mesh.

Single host:   python examples/train_llm.py --steps 20
CPU smoke:     JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
                   python examples/train_llm.py --preset tiny --steps 5 --mesh dp=2,fsdp=2,tp=2
"""

import os
import sys

try:
    import ray_tpu  # noqa: F401
except ImportError:  # running from a checkout without install
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import jax
import numpy as np



def parse_mesh(spec: str):
    from ray_tpu.parallel import MeshConfig

    kw = {}
    for part in spec.split(","):
        k, v = part.split("=")
        kw[k] = int(v)
    return MeshConfig(**kw)


def main():
    from ray_tpu.models import ModelConfig, count_params
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train import batch_sharding, make_train_step
    from ray_tpu.train.step import default_optimizer

    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="b1", choices=["tiny", "b1", "llama3_8b"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--mesh", default="dp=-1")
    ap.add_argument("--tokens", default=None,
                    help="flat int32 token file (uses the native C++ loader); "
                         "random tokens when omitted")
    args = ap.parse_args()

    cfg = getattr(ModelConfig, args.preset)()
    mesh = make_mesh(parse_mesh(args.mesh), jax.devices())
    step_fn, init_fn, _ = make_train_step(cfg, mesh, default_optimizer())
    state = init_fn(jax.random.PRNGKey(0))
    print(f"model: {count_params(state.params)/1e6:.1f}M params, "
          f"mesh {dict(mesh.shape)}")

    if args.tokens:
        from ray_tpu.data.token_loader import TokenLoader

        loader = TokenLoader(args.tokens, batch=args.batch, seq_len=args.seq)
        next_batch = loader.next
    else:
        rng = np.random.default_rng(0)

        def next_batch():
            return rng.integers(0, cfg.vocab_size,
                                (args.batch, args.seq + 1)).astype(np.int32)

    b_sh = batch_sharding(mesh)
    for step in range(args.steps):
        tok = next_batch()
        batch = {"inputs": tok[:, :-1], "targets": tok[:, 1:]}
        batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        loss = float(jax.device_get(metrics["loss"]))
        print(f"step {step}: loss {loss:.4f} "
              f"({time.perf_counter() - t0:.3f}s)")


if __name__ == "__main__":
    main()
