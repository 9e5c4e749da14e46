"""Envelope suite smoke (scaled 1%): the full-scale run is the committed
ENVELOPE_r{N}.json artifact; this keeps the harness itself green in CI —
and pins regression floors on the core-runtime throughput numbers so the
control plane can't silently collapse between benchmark rounds. The
envelope's own floors are wall-clock rates: `test_envelope_floors` is `slow`
and `ci/run_ci.sh` runs it alone (stage 10), not beside five other xdist
workers."""

import math

import pytest

# Committed full-scale ENVELOPE_r05.json values (the pre-completion-fast-lane
# baseline). The smoke runs at 1% scale on a loaded 1-CPU CI box, so the
# floors carry a generous ~0.5x slack: they catch collapse-class regressions
# (a redundant per-completion _schedule() pass, an unbatched notify storm),
# not percent-level drift — that's what the committed artifacts track.
_R05 = {
    "submit_per_s": 582.8,
    "end_to_end_per_s": 80.8,
    "actor_call_roundtrip": 158.5,
}
_SLACK = 0.5
# Committed full-scale ENVELOPE_r06.json actor-burst time: 200 actors took
# 49.21 s to first ping on the all-cold spawn path. The warm worker pool
# (fork-template zygotes) cut the full-scale number to ~5 s; the smoke's
# 2-actor wave must never climb back into cold-collapse territory — with
# the same 0.5x slack discipline the budget is half the r06 burst time,
# still ~5x what the 2-actor wave needs even if every fork falls back to
# a cold spawn on a loaded CI box.
_R06_ACTORS_TO_FIRST_PING_S = 49.21

# Committed OBJPLANE_r14.json values (zero-copy object plane: pinned shm
# views on get(), collapsed per-object RPCs, segment recycling). The rows
# run at FULL sizes in every profile, so the floors compare like with
# like; 0.5x slack per the r05/r06 discipline — they catch the fast path
# silently dropping out (a copy sneaking back into same-node get, the
# seal turning back into a round-trip), not scheduler-noise drift.
_R14 = {
    "put_get_10mb_bytes": 7_364_988_504.1,   # bytes/s (5.63x the r10 run)
    "np_roundtrip_100mb": 13_679_092_820.0,  # bytes/s
    "arg_1mb_fanout": 302.7,                 # tasks/s through one shared ref
}
# The byte-rate rows are dominated by ONE memory pass per cycle, so the
# committed numbers encode the committing box's memory bandwidth. On a
# slower machine the binding floor is a FRACTION of that machine's own
# measured copy bandwidth instead (the effective floor takes the min):
# the pre-PR copy-per-get path ran at ~0.09x memcpy bandwidth, so these
# ratios still catch a collapse anywhere while never demanding more than
# the hardware can move.
_R14_MEMBW_RATIO = {
    "put_get_10mb_bytes": 0.30,
    "np_roundtrip_100mb": 0.45,
}

# PR 16 raw-bytes out-of-band lane: a 32 MB `bytes` roundtrip must stay on
# the zero-copy buffer plane. The floor is denominated ONLY in this
# machine's memcpy bandwidth (no committed-artifact term: the committing
# box measured oob at 0.138x membw vs 0.083x for the in-band pickle path —
# too close to discriminate under CI noise, so 0.05x is a collapse-class
# floor that catches the lane disappearing entirely, e.g. blobs copied
# through the pickle stream twice plus framing).
_R16_MEMBW_RATIO = {
    "put_get_32mb_raw_bytes": 0.05,
}

# Committed SERVEBENCH_r16.json values (serve decode fast lanes: donated
# KV caches, fused on-device sampling, lookahead pipelining, batched
# bucketed prefill). Measured on the quick profile (d_model=256 / 4-layer
# f32 model, max_len=512), which is what _servebench_quick_rows() re-runs,
# so the 0.5x-slack artifact term compares like with like.
_R16 = {
    "decode_tokens_per_s": 2301.1,   # 8-slot flagship row
    "prefill_tokens_per_s": 3015.8,  # 4 x 64-token batched admission
}
# Machine-calibration terms (the effective floor takes the min, r14
# discipline). Decode: the engine's fused step rides ONE jitted call, so
# its steps/s tracks the raw-kernel steps/s measured on the same box —
# the pre-PR loop (host argmax + 3 blocking syncs per step) ran at ~0.16x
# raw, the donated+pipelined loop at 0.9-1.1x, so 0.35x discriminates the
# collapse without flaking. Prefill: batched admission must not cost more
# per token than prefilling one prompt at a time (that IS the batching
# claim); 0.6x leaves room for scheduler noise.
_R16_DECODE_VS_RAW_KERNEL = 0.35
_R16_PREFILL_VS_SINGLE = 0.6

# TRAINSTORM_r17.json floors (PR 17, RL fleet rollout->learner loop). The
# artifact is measured UNDER CHAOS (serve replicas + named learner actor +
# object-plane hops + seeded kills/partition on however few cores CI has),
# while the re-measured quick loop below is the same sample->ingest path
# in-process — far faster. So the 0.5x-artifact term is the binding floor
# on the calibration box and the raw-probe ratios keep a slower machine
# judged against its own silicon: a loop step is one rollout (raw env-
# stepping probe) plus one PPO minibatch update (raw update probe)
# serialized, so its steps/s can't honestly fall below ~0.2x the raw
# update rate unless the path regrew per-step compiles or batch copies.
_R17_SAMPLES_VS_RAW_ENV = 0.10
_R17_STEPS_VS_RAW_UPDATE = 0.20

# STORESTORM_r18.json floors (PR 18, storage failure domain). The
# artifact's spill_restore_gbps is measured END TO END under the storm
# (ray_tpu.get over spilled objects: rpc + restore + deserialize), while
# the quick probe below drives the store's verified-restore path
# in-process — faster, so the 0.5x-artifact term binds on the committing
# box. The membw ratio keeps slower machines judged against their own
# silicon, and BOTH sides of it are measured under whatever load the
# suite is running beside, so it self-calibrates on a contended host
# (where the fixed artifact term cannot). Calibration on the committing
# box: best single 2 MB verified restore runs at ~0.018x memcpy — the
# per-restore fixed costs (spill-file open, shm segment create, attach)
# dominate at this object size, not the crc — and the same ratio holds
# within ~1.5x under a 4-way CPU hog. 0.006x is therefore 3x below the
# honest operating point but still well above a collapsed path (per-byte
# re-verification loops, a copy regrowing per restore: <= 0.002x).
_R18_RESTORE_VS_MEMBW = 0.006


def _memcpy_bytes_per_s() -> float:
    """This machine's large-copy bandwidth (the unit the byte-rate floors
    are denominated in)."""
    import time

    import numpy as np

    src = np.zeros(64 << 20, dtype=np.uint8)
    dst = np.empty_like(src)
    np.copyto(dst, src)  # warm both buffers
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        np.copyto(dst, src)
    return reps * src.nbytes / (time.perf_counter() - t0)


def test_envelope_smoke(tmp_path):
    from ray_tpu.envelope import run_envelope

    art = run_envelope(scale=0.01)
    assert art["queued_tasks"]["n_tasks"] == 200
    assert art["queued_tasks"]["end_to_end_per_s"] > 0
    actors = art["concurrent_actors"]
    assert actors["n_actors"] == 2
    assert actors["distinct_workers"] == 2
    assert actors["alive_roundtrip_calls_per_s"] > 0
    assert art["placement_groups"]["n_pgs"] == 1  # max(1, scale*30)
    assert art["placement_groups"]["create_per_s"] > 0
    assert art["broadcast"]["aggregate_gbps"] > 0
    rates = {r["benchmark"]: r["rate"] for r in art["microbenchmark"]}
    assert all(math.isfinite(v) and v > 0 for v in rates.values())
    assert "hardware" in art and art["hardware"]["cpus"] >= 1

    # the burst must ride the warm pool on fork-capable platforms: a
    # silent fall-through to all-cold spawns is a regression even when
    # it happens to fit the time budget. Leases served by ALREADY-IDLE
    # workers start nothing (warm==cold==0) — that's fine; only judge the
    # fraction when the burst actually started workers.
    import os as _os

    from ray_tpu.core.config import get_config

    started = (actors.get("warm_starts") or 0) + \
        (actors.get("cold_starts") or 0)
    if hasattr(_os, "fork") and started >= 2 \
            and get_config().worker_template_enabled:
        frac = actors.get("warm_start_fraction", 0.0)
        assert frac >= 0.5, (
            f"warm_start_fraction {frac}: most actor leases were served "
            f"by cold spawns despite a fork-capable platform")


@pytest.mark.slow
def test_envelope_floors():
    from ray_tpu.envelope import run_envelope

    art = run_envelope(scale=0.01)
    actors = art["concurrent_actors"]
    rates = {r["benchmark"]: r["rate"] for r in art["microbenchmark"]}

    # --- regression floors vs ENVELOPE_r05.json (ROADMAP item 3) ---
    q = art["queued_tasks"]
    assert q["submit_per_s"] >= _SLACK * _R05["submit_per_s"], (
        f"submit_per_s {q['submit_per_s']} fell below "
        f"{_SLACK}x the r05 envelope ({_R05['submit_per_s']})")
    assert q["end_to_end_per_s"] >= _SLACK * _R05["end_to_end_per_s"], (
        f"end_to_end_per_s {q['end_to_end_per_s']} fell below "
        f"{_SLACK}x the r05 envelope ({_R05['end_to_end_per_s']})")
    assert rates["actor_call_roundtrip"] >= \
        _SLACK * _R05["actor_call_roundtrip"], (
        f"actor_call_roundtrip {rates['actor_call_roundtrip']} fell below "
        f"{_SLACK}x the r05 envelope ({_R05['actor_call_roundtrip']})")

    # --- warm-start regression floor vs ENVELOPE_r06.json (PR 10) ---
    budget = _SLACK * _R06_ACTORS_TO_FIRST_PING_S
    assert actors["create_to_first_ping_s"] <= budget, (
        f"create_to_first_ping_s {actors['create_to_first_ping_s']} blew "
        f"the {budget:.1f}s budget ({_SLACK}x r06's "
        f"{_R06_ACTORS_TO_FIRST_PING_S}s for 100x the actors): the warm "
        f"worker pool has collapsed back to cold-spawn behavior")
    # --- object-plane regression floors vs OBJPLANE_r14.json (PR 14) ---
    membw = _memcpy_bytes_per_s()
    for row, floor_src in _R14.items():
        floor = _SLACK * floor_src
        ratio = _R14_MEMBW_RATIO.get(row)
        if ratio is not None:
            floor = min(floor, ratio * membw)
        assert rates[row] >= floor, (
            f"{row} {rates[row]} fell below the r14 object-plane floor "
            f"{floor:.3g} (min of {_SLACK}x artifact {floor_src} and "
            f"{ratio}x this machine's {membw:.3g} B/s memcpy): the "
            f"zero-copy pin path has collapsed back to copy-per-get "
            f"behavior")

    # --- raw-bytes oob lane floor (PR 16, machine-denominated only) ---
    for row, ratio in _R16_MEMBW_RATIO.items():
        floor = ratio * membw
        assert rates[row] >= floor, (
            f"{row} {rates[row]} fell below {ratio}x this machine's "
            f"{membw:.3g} B/s memcpy: the out-of-band bytes lane has "
            f"collapsed back to in-band pickling")


def _servebench_quick_rows():
    """Re-measure the two servebench floor rows at the quick profile
    (trimmed iteration counts — compile dominates the wall time anyway)."""
    from ray_tpu.models.servebench import (_bench_model, measure_decode,
                                           measure_prefill)

    params, cfg, max_len = _bench_model(True)
    decode = measure_decode(params, cfg, num_slots=8, max_len=max_len,
                            steps=20, warm_steps=8)
    prefill = measure_prefill(params, cfg, max_len=max_len, iters=4)
    return params, cfg, max_len, decode, prefill


def test_servebench_regression_floors():
    """SERVEBENCH_r16.json regression floors (PR 16). Each floor is
    min(0.5x the committed artifact, ratio x a same-box raw-kernel probe)
    so a slower CI machine is judged against its own silicon, while the
    fast-lane structure (donated in-place cache, fused sampling, one
    dispatch per step, batched admission) can't silently collapse."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.serving import decode_step_fused, prefill_kv

    params, cfg, max_len, decode, prefill = _servebench_quick_rows()

    # raw fused-kernel probe: the same jitted step the engine dispatches,
    # driven with zero host bookkeeping — this machine's device-speed
    # ceiling for an 8-slot decode step
    L, kvh, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    k = jnp.zeros((L, 8, kvh, max_len, hd), cfg.dtype)
    v = jnp.zeros((L, 8, kvh, max_len, hd), cfg.dtype)
    lengths = jnp.full((8,), 7, jnp.int32)
    tokens = jnp.arange(1, 9, dtype=jnp.int32)
    for _ in range(3):  # compile + settle
        k, v, lengths, tokens = decode_step_fused(
            params, k, v, lengths, tokens, cfg=cfg, attn_len=64)
    np.asarray(tokens)
    t0 = time.perf_counter()
    raw_steps = 20
    for _ in range(raw_steps):
        k, v, lengths, tokens = decode_step_fused(
            params, k, v, lengths, tokens, cfg=cfg, attn_len=64)
    np.asarray(tokens)
    raw_tok_per_s = raw_steps * 8 / (time.perf_counter() - t0)

    floor = min(_SLACK * _R16["decode_tokens_per_s"],
                _R16_DECODE_VS_RAW_KERNEL * raw_tok_per_s)
    assert decode["decode_tokens_per_s"] >= floor, (
        f"decode_tokens_per_s {decode['decode_tokens_per_s']} fell below "
        f"the r16 floor {floor:.1f} (min of {_SLACK}x artifact "
        f"{_R16['decode_tokens_per_s']} and {_R16_DECODE_VS_RAW_KERNEL}x "
        f"this box's raw fused-kernel rate {raw_tok_per_s:.1f} tok/s): the "
        f"decode loop is paying host-sync/reallocation costs per step again")

    # single-prompt prefill probe: batched admission must not cost more
    # per token than one-at-a-time prefill on the same box
    one = jnp.arange(1, 65, dtype=jnp.int32)[None]
    tl = jnp.asarray(64, jnp.int32)  # prefill_kv takes a scalar true_len
    logits, _, _ = prefill_kv(params, one, tl, cfg, max_len)
    np.asarray(logits)  # compile + settle
    t0 = time.perf_counter()
    for _ in range(4):
        logits, _, _ = prefill_kv(params, one, tl, cfg, max_len)
    np.asarray(logits)
    single_tok_per_s = 4 * 64 / (time.perf_counter() - t0)

    floor = min(_SLACK * _R16["prefill_tokens_per_s"],
                _R16_PREFILL_VS_SINGLE * single_tok_per_s)
    assert prefill["prefill_tokens_per_s"] >= floor, (
        f"prefill_tokens_per_s {prefill['prefill_tokens_per_s']} fell "
        f"below the r16 floor {floor:.1f} (min of {_SLACK}x artifact "
        f"{_R16['prefill_tokens_per_s']} and {_R16_PREFILL_VS_SINGLE}x "
        f"this box's single-prompt rate {single_tok_per_s:.1f} tok/s): "
        f"batched bucketed admission has collapsed")


def test_trainstorm_regression_floors():
    """TRAINSTORM_r17.json regression floors (PR 17). Re-measures the RL
    fleet's sample->ingest loop in-process at a quick profile and pins
    samples/s + learner steps/s at min(0.5x the committed under-chaos
    artifact, ratio x same-box raw probes), r14/r16 discipline."""
    import json
    import os
    import time
    from dataclasses import asdict

    from ray_tpu.rllib.fleet import FleetConfig, FleetLearnerImpl, _MlpRollouts
    from ray_tpu.rllib.ppo import PPOLearner

    art_path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "TRAINSTORM_r17.json")
    art = json.load(open(art_path))  # committed artifact IS the floor source

    cfg = FleetConfig(num_envs=2, rollout_len=32, checkpoint_every=0, seed=0)
    rolls = _MlpRollouts(cfg, seed=0)
    rolls.set_weights(PPOLearner(4, 2, lr=cfg.lr, seed=0).get_weights())
    learner = FleetLearnerImpl(asdict(cfg), "/tmp/_r17_floor_unused")

    # raw probes: this box's env-stepping and PPO-update ceilings
    rolls.sample(32)  # warm
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < 0.8:
        rolls.sample(32)
        n += 32 * cfg.num_envs
    raw_env_steps_per_s = n / (time.perf_counter() - t0)
    batch = rolls.sample(32)
    learner.ingest("warm", 0, batch)  # compile
    t0 = time.perf_counter()
    k = 0
    while time.perf_counter() - t0 < 0.8:
        learner.ingest(f"probe-{k}", 0, batch)
        k += 1
    raw_updates_per_s = k / (time.perf_counter() - t0)

    # the loop under measurement: rollout -> exactly-once ingest, serialized
    t0 = time.perf_counter()
    env_steps = steps = 0
    while time.perf_counter() - t0 < 1.2:
        b = rolls.sample(32)
        assert learner.ingest(f"loop-{steps}", 0, b)["applied"]
        env_steps += 32 * cfg.num_envs
        steps += 1
    dt = time.perf_counter() - t0
    samples_per_s = env_steps / dt
    steps_per_s = steps / dt

    floor = min(_SLACK * art["samples_per_s"],
                _R17_SAMPLES_VS_RAW_ENV * raw_env_steps_per_s)
    assert samples_per_s >= floor, (
        f"fleet samples/s {samples_per_s:.1f} fell below the r17 floor "
        f"{floor:.1f} (min of {_SLACK}x artifact {art['samples_per_s']} and "
        f"{_R17_SAMPLES_VS_RAW_ENV}x this box's raw env-stepping rate "
        f"{raw_env_steps_per_s:.1f}/s): the rollout->ingest path is paying "
        f"per-round costs the fleet loop never had")
    floor = min(_SLACK * art["learner_steps_per_s"],
                _R17_STEPS_VS_RAW_UPDATE * raw_updates_per_s)
    assert steps_per_s >= floor, (
        f"fleet learner steps/s {steps_per_s:.2f} fell below the r17 floor "
        f"{floor:.2f} (min of {_SLACK}x artifact "
        f"{art['learner_steps_per_s']} and {_R17_STEPS_VS_RAW_UPDATE}x this "
        f"box's raw update rate {raw_updates_per_s:.2f}/s): the ingest path "
        f"regrew per-step compiles or batch copies")


def test_storestorm_regression_floors(tmp_path):
    """STORESTORM_r18.json floors (PR 18). The committed storm artifact
    must certify the storage contract (zero hung gets, zero silent
    corruption under seeded ENOSPC/corruption/pin/OOM chaos), and the
    verified-restore path re-measured at a quick in-process profile must
    hold min(0.5x artifact, 0.03x membw) — the checksummed envelope can't
    silently turn restores into a per-byte crawl."""
    import json
    import os
    import time

    import numpy as np

    from ray_tpu.core.ids import ObjectID, TaskID
    from ray_tpu.core.object_store import SharedObjectStore

    art_path = os.path.join(os.path.dirname(__file__), os.pardir,
                            "STORESTORM_r18.json")
    art = json.load(open(art_path))
    # the artifact IS the acceptance record: a storm that hung a get or
    # let a corrupt payload through must never be committed
    assert art["ok"], art["violations"]
    assert art["zero_hung"] and art["zero_silent_corruption"], art
    assert art["counters"]["spill_failures"].get("enospc", 0) > 0
    assert art["counters"]["lost_spills"] > 0
    assert art["counters"]["degraded_heals"] >= 1

    # quick verified-restore probe: spill under pressure, read back cold
    store = SharedObjectStore(capacity=16 << 20, spill_dir=str(tmp_path))
    try:
        store.arena_threshold = 0
        payload = np.random.bytes(2 << 20)
        oids = [ObjectID.for_task_return(TaskID(b"e" * 16), i + 1)
                for i in range(12)]
        for oid in oids:
            store.put_bytes(oid, payload)
        spilled0 = store.stats()["restored_bytes_total"]

        # best single-restore bandwidth: each restore is timed alone and
        # the MAX over a pass is the measurement. The mean is hostage to
        # transient host load (this test runs late in a 12-minute suite)
        # and to the spill-out churn a restore triggers in a full store;
        # the best sample reflects what the path can do, and a collapsed
        # path (per-byte re-verification, a copy regrowing per restore)
        # can't produce even one fast sample. Passes repeat because the
        # 24 MB working set re-spills out of the 16 MB store each time.
        def probe_pass():
            best = 0.0
            for oid in oids:
                r0 = store.stats()["restored_bytes_total"]
                t0 = time.perf_counter()
                assert store.read_bytes(oid) is not None
                dt = time.perf_counter() - t0
                delta = store.stats()["restored_bytes_total"] - r0
                if delta > 0 and dt > 0:
                    best = max(best, delta / dt / 1e9)
            return best

        # up to 3 attempts, re-denominating against memcpy measured at
        # the SAME moment each time: a load transient slows restore and
        # memcpy together, so the ratio floor self-calibrates only if
        # both sides see the same load — a real collapse fails every
        # attempt because the ratio is load-invariant.
        for _ in range(3):
            gbps = probe_pass()
            membw_gbps = _memcpy_bytes_per_s() / 1e9
            floor = _R18_RESTORE_VS_MEMBW * membw_gbps
            if art.get("spill_restore_gbps"):
                floor = min(_SLACK * art["spill_restore_gbps"], floor)
            if gbps >= floor:
                break
            time.sleep(0.5)
        restored = store.stats()["restored_bytes_total"] - spilled0
        assert restored > 0, "pressure fill never spilled: nothing probed"
    finally:
        store.shutdown()

    assert gbps >= floor, (
        f"verified spill restore ran at {gbps:.3f} GB/s, below the r18 "
        f"floor {floor:.3f} (min of {_SLACK}x the artifact's "
        f"{art.get('spill_restore_gbps')} GB/s and "
        f"{_R18_RESTORE_VS_MEMBW}x this box's {membw_gbps:.1f} GB/s "
        f"memcpy): envelope verification has collapsed the restore path")
