"""Benchmark: training throughput of the flagship transformer on a TPU.

Prints ONE JSON line:
    {"metric": "train_tokens_per_sec_per_chip", "value": N, "unit": "tokens/s/chip",
     "vs_baseline": N, ...}

The reference publishes no model-throughput numbers (BASELINE.md: scalability
envelope only); the north star from BASELINE.json is >=40% MFU — so
`vs_baseline` is achieved-MFU / 0.40.

Runs in ONE process that owns every chip of the host, and refuses to run
without a TPU it knows the peak of: a number from a CPU is never printed
under a device metric's name. `chip_smoke.py` is the proof that the same
model runs through `ray_tpu.init` -> a worker.
"""

from __future__ import annotations

import json
import os
import sys
import time

def _bench_config(cfg, batch_size, seq, peak_flops_per_chip, iters):
    """Measure one model config's train step; returns (tok/s/chip, mfu, dt,
    compile_s, loss, n_params)."""
    import jax

    from ray_tpu.models import count_params
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train import make_train_step, batch_sharding
    from ray_tpu.train.step import default_optimizer

    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh(MeshConfig(dp=-1, fsdp=1, tp=1, sp=1), devices)
    step_fn, init_fn, _ = make_train_step(cfg, mesh, default_optimizer())
    state = init_fn(jax.random.PRNGKey(0))
    n_params = count_params(state.params)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch_size, seq + 1), 0, cfg.vocab_size)
    batch = {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}
    b_sh = batch_sharding(mesh)
    batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}

    t0 = time.perf_counter()
    state, metrics = step_fn(state, batch)
    jax.block_until_ready(metrics)
    compile_s = time.perf_counter() - t0

    state, metrics = step_fn(state, batch)  # warm
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step_fn(state, batch)
    jax.block_until_ready(metrics)
    dt = (time.perf_counter() - t0) / iters
    loss = float(metrics["loss"])

    tokens_per_sec = batch_size * seq / dt
    attn_flops = 6 * cfg.n_layers * cfg.d_model * seq  # 12*L*d*s * 0.5 causal
    flops_per_token = 6 * n_params + attn_flops
    mfu = tokens_per_sec * flops_per_token / (peak_flops_per_chip * n_chips)
    return tokens_per_sec / n_chips, mfu, dt, compile_s, loss, n_params


# bf16 peak FLOP/s of one chip, keyed by jax's `device_kind`. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
# A device that is not here is an error, not a default. (Lives here until
# the benchmark of ROADMAP S1 owns it.)
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12, "TPU v5e": 197e12}


def main() -> None:
    import dataclasses

    from ray_tpu.core.chips import default_compile_cache_dir

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          default_compile_cache_dir())

    import jax

    from ray_tpu.models import ModelConfig

    devices = jax.devices()
    n_chips = len(devices)
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu":
        sys.exit(f"bench.py measures the chip: jax found platform={platform!r} "
                 f"({kind}), not a TPU. Nothing was measured.")
    if kind not in PEAK_BF16_FLOPS:
        sys.exit(f"bench.py has no peak for device_kind={kind!r}; add it to "
                 f"PEAK_BF16_FLOPS with its source. Nothing was measured.")
    peak_flops_per_chip = PEAK_BF16_FLOPS[kind]
    # dots (selective) remat at batch 4 beats full remat at batch 8 by
    # ~10% MFU: matmul outputs stay resident, so the backward pass skips
    # most recompute; the smaller batch keeps activations inside HBM
    cfg = ModelConfig(
        vocab_size=32768, d_model=2048, n_layers=12, n_heads=16,
        n_kv_heads=8, d_ff=6144, max_seq_len=2048, remat="dots",
        fused_ffn=True, fused_attn=True)  # r05: custom-vjp FFN+attn
    # backward (save-don't-recompute): 301.5 -> 287.5 ms
    batch_size, seq = 4 * n_chips, 2048  # 4 per chip (dp shards batch)
    iters = 10
    tok_s_chip, mfu, dt, compile_s, loss, n_params = _bench_config(
        cfg, batch_size, seq, peak_flops_per_chip, iters)

    result = {
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 3),
        "mfu": round(mfu, 4),
        "n_params": n_params,
        "n_chips": n_chips,
        "platform": platform,
        "device_kind": kind,
        "batch": batch_size,
        "seq": seq,
        "step_time_s": round(dt, 4),
        "compile_s": round(compile_s, 1),
        "loss": round(loss, 3),
    }

    # Secondary: the ~1.2B ModelConfig.b1 (largest bench config that fits
    # one chip) — reported as b1_* fields of the same single JSON line.
    # Config retuned r04: batch 2/chip with selective (dots) remat +
    # unchunked fp32 logits beats batch 4 with full remat + chunked loss by
    # ~3 MFU points (0.605 vs 0.575). r05: fused_ffn + fused_attn
    # (custom-vjp FFN and attention blocks whose backward saves instead of
    # recomputing; BASELINE.md r05 note) take the step from 249.9 to
    # 235.1 ms (+3.6 MFU points). A failure here fails the run.
    b1 = dataclasses.replace(
        ModelConfig.b1(), max_seq_len=2048, remat="dots", loss_chunk=0,
        fused_ffn=True, fused_attn=True)
    b1_tok, b1_mfu, b1_dt, _, _, b1_params = _bench_config(
        b1, 2 * n_chips, 2048, peak_flops_per_chip, iters)
    result.update({
        "b1_tokens_per_sec_per_chip": round(b1_tok, 1),
        "b1_mfu": round(b1_mfu, 4),
        "b1_n_params": b1_params,
        "b1_step_time_s": round(b1_dt, 4),
    })

    # > the 16 MiB chunk size, so the measured path IS the pipelined chunk
    # pull (host-side: no device involved)
    result.update(_bench_transfer(512))

    print(json.dumps(result))


def _bench_transfer(size_mib: int = 512) -> dict:
    """Cross-raylet chunked object transfer throughput (reference
    release/benchmarks object-transfer envelope): an in-process 2-raylet
    cluster moves a size_mib object through the pipelined chunk pull path."""
    import numpy as np

    from ray_tpu.core.cluster import Cluster
    from ray_tpu.core.ids import ObjectID

    cluster = Cluster()
    a = cluster.add_node(num_cpus=1, object_store_memory=2 * (size_mib << 20))
    b = cluster.add_node(num_cpus=1, object_store_memory=2 * (size_mib << 20))
    try:
        oid = ObjectID.from_random()
        a.store.put_bytes(oid, np.ones(size_mib << 20, dtype=np.uint8).data)
        import ray_tpu.core.rpc as rpc

        cli = rpc.connect_with_retry(b.address, timeout=10)
        try:
            t0 = time.perf_counter()
            cli.call("pull_object", {"object_id": oid, "source": a.address},
                     timeout=300)
            dt = time.perf_counter() - t0
        finally:
            cli.close()
        return {"transfer_mib": size_mib,
                "transfer_gbps": round(size_mib / 1024 / dt * 8, 2),
                **_transfer_ceiling(size_mib)}
    finally:
        cluster.shutdown()


def _transfer_ceiling(size_mib: int) -> dict:
    """Measured SINGLE-STREAM loopback TCP baseline on THIS host, reported
    next to the transfer number so it reads against the right bar: on a
    1-core box the kernel loopback path is the limiter, not a NIC (no
    cross-host link exists in this environment). The data plane's striped
    multi-stream + copy_file_range pull can legitimately exceed this
    single-stream figure — matching or beating it is the claim."""
    import socket
    import threading

    payload = bytearray(4 << 20)
    n_chunks = (size_mib << 20) // len(payload)
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)

    def sink():
        conn, _ = srv.accept()
        with conn:
            left = n_chunks * len(payload)
            buf = memoryview(bytearray(1 << 20))
            while left:
                n = conn.recv_into(buf)
                if not n:
                    break
                left -= n

    t = threading.Thread(target=sink, daemon=True)
    t.start()
    cli = socket.create_connection(srv.getsockname())
    try:
        t0 = time.perf_counter()
        with cli:
            for _ in range(n_chunks):
                cli.sendall(payload)
        t.join(timeout=60)
        dt = time.perf_counter() - t0
        moved_mib = n_chunks * len(payload) >> 20
        return {"loopback_tcp_1stream_gbps": round(moved_mib / 1024 / dt * 8, 2)}
    finally:
        srv.close()


if __name__ == "__main__":
    main()
