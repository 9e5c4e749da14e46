#!/usr/bin/env bash
# One-command CI for ray_tpu (reference role: .buildkite/pipeline.build.yml).
#
#   ci/run_ci.sh            # native + fast + stress x20 + chaos + storm
#                           #   + burst + head-failover
#   ci/run_ci.sh --fast     # fast test tier only
#   ci/run_ci.sh --native   # native ASAN/UBSAN harness only
#   ci/run_ci.sh --stress   # actor-ordering stress x20 only
#   ci/run_ci.sh --chaos    # control-plane HA chaos suite only
#   ci/run_ci.sh --storm    # serve traffic-storm chaos only
#   ci/run_ci.sh --burst    # warm-pool elasticity burst only
#   ci/run_ci.sh --failover # standby-head kill-and-promote storm only
#   ci/run_ci.sh --node-chaos # multi-node kill storm only
#   ci/run_ci.sh --partition  # partition-heal storm only
#   ci/run_ci.sh --servebench # serving decode/prefill perf smoke only
#   ci/run_ci.sh --trainstorm # RL fleet chaos (rollout->learner loop) only
#   ci/run_ci.sh --memstorm   # store storm (storage failure domain) only
#   ci/run_ci.sh --tracing    # traced serve storm (cluster timeline) only
#   ci/run_ci.sh --jobstorm   # job storm (job failure domain) only
#
# Stages:
#   1. native      : arena + scheduler + token-loader compiled whole-program
#                    with -fsanitize=address,undefined and exercised by
#                    src/tests/sanitize_main.cpp (allocation churn, shared
#                    mappings, thread shutdown).
#   2. fast tier   : pytest tests/ (the "not slow" default tier).
#   3. stress      : the actor-ordering race test repeated 20x (the round-1
#                    ordering bug class must stay dead).
#   4. chaos       : head-replacement + fault-injection suite under its own
#                    timeout, with the injection seed printed so any failure
#                    reproduces exactly.
#   5. serve-storm : quick traffic-storm profile against a multi-replica
#                    autoscaling deployment under seeded replica-call drops
#                    + kills; prints the seed and shed/retry counters and
#                    fails on ANY unresolved (hung) request.
#   6. burst       : warm-pool elasticity chaos (quick profile): scale a
#                    loaded fleet 4 -> 40 workers with seeded worker kills;
#                    prints cold/warm start counts + the seed and fails if
#                    any lease is served by neither a warm fork nor a cold
#                    fallback (or any kill fails to recover).
#   7. failover    : standby-head kill-and-promote mid-storm (--kill-head):
#                    the active head is crash-stopped under serve load, a
#                    warm standby takes over via the lease/fencing-epoch
#                    CAS. Prints the seed, lease epochs observed and the
#                    promotion latency (lease-expiry -> first-scheduled-
#                    task); fails if promotion exceeds the budget, any
#                    request hangs, or typed errors spike past the shed
#                    baseline.
#   8. node-chaos  : multi-node kill storm (--nodes --quick): whole nodes
#                    (raylet + workers + fork templates) SIGKILLed under
#                    closed-loop load; the autoscaler reaps + relaunches,
#                    replacements onboard warm (hot-env template prewarm).
#                    Prints the seed, detection latencies vs the health
#                    bound, relaunch counts and join->first-warm-lease;
#                    fails on any undetected kill, unreplaced node, lost
#                    actor or hung call.
#   9. partition   : partition-heal storm (--partition --quick): named node
#                    groups blackholed mid-load; quarantine precedes death,
#                    actors restart on the replacement, the healed zombie
#                    is incarnation-fenced and rejoins fresh, the head-in-
#                    minority cycle starves the lease and the standby
#                    promotes. Fails on any hung call, duplicate named-
#                    actor answer, or autoscaler double replacement.
#  10. servebench  : serving perf smoke (quick profile): fused-decode
#                    tokens/s + slot sweep + w8a16 parity + batched prefill
#                    + p50/p99 under the storm load generator; fails on any
#                    missing artifact row (regression FLOORS live in
#                    tests/test_envelope.py, machine-calibrated). Then the
#                    envelope's own wall-clock floors, alone:
#                    tests/test_envelope.py::test_envelope_floors (slow).
#  11. trainstorm  : RL fleet chaos (quick profile): serve-deployed rollout
#                    replicas -> checkpointed learner actor, weight-epoch-
#                    fenced broadcasts, under composed chaos (seeded replica
#                    kills + learner crash-restart + learner|replicas
#                    partition-heal). Prints samples/s, learner steps/s and
#                    the recovery-to-first-post-restart-step time; fails on
#                    any hung future, a chaos mode that never landed, a
#                    blown recovery budget, or a missing artifact row
#                    (throughput FLOORS live in tests/test_envelope.py).
#  12. memstorm    : store storm (quick profile): the object store driven to
#                    2-4x capacity under composed storage chaos — seeded
#                    ENOSPC/EIO/torn/bitflip spill faults, a disk-full
#                    degrade->probe->heal cycle, pin-cap pressure, OOM
#                    kills composed with spilling. Exits nonzero on any
#                    hung get, any silent corruption (end-to-end checksums
#                    over every surviving ref), untyped backpressure, or
#                    failed post-heal convergence (restore-bandwidth FLOOR
#                    lives in tests/test_envelope.py).
#  13. tracing     : cluster-timeline acceptance — an untraced kill-free
#                    baseline storm, then the same profile --traced: >=99%
#                    of accepted requests must form complete correctly-
#                    parented span chains across >=3 processes, the
#                    fleet-merged chrome document must validate (monotone
#                    ts, finite durs), post-alignment clock skew < 10 ms,
#                    and the traced p50 must stay inside a loose overhead
#                    budget vs the baseline.
#  14. jobstorm    : job storm (quick profile): N concurrent driver
#                    processes (nested task trees, named + detached
#                    actors, large pinned puts), a seeded subset
#                    SIGKILLed mid-flight. Fails on any job not reaped
#                    within the bound, a dead detached actor, a hung
#                    call, an untyped cross-job get, or any leaked
#                    worker / object-table entry / shm segment.
set -euo pipefail
cd "$(dirname "$0")/.."

STAGE="${1:-all}"

run_native() {
  echo "=== [1/14] native modules under ASan/UBSan ==="
  mkdir -p build
  g++ -std=c++17 -O1 -g -fsanitize=address,undefined \
      -fno-omit-frame-pointer -o build/sanitize_native \
      src/tests/sanitize_main.cpp src/arena/arena.cpp \
      src/scheduler/cluster_scheduler.cpp src/loader/token_loader.cpp \
      -lpthread
  ASAN_OPTIONS=detect_leaks=1 UBSAN_OPTIONS=halt_on_error=1 \
      ./build/sanitize_native
}

run_fast() {
  echo "=== [2/14] fast test tier ==="
  python -m pytest tests/ -q
  # core-primitives smoke: the submission AND completion hot paths
  # (function table, event batching, batched result delivery, put/get)
  # must run end to end on CPU every CI pass, and the return-path rows
  # must be present so the completion fast lanes can't silently drop out
  mb_json="$(mktemp /tmp/ray_tpu_mb_quick.XXXXXX.json)"
  JAX_PLATFORMS=cpu python -m ray_tpu.microbenchmark --quick --json \
    | tee "$mb_json"
  MB_JSON="$mb_json" python - <<'EOF'
import json, os
rows = {r["benchmark"] for r in
        json.load(open(os.environ["MB_JSON"]))["results"]}
need = {"task_submit_p50", "task_e2e_p50", "task_completions_per_s",
        # zero-copy object plane (OBJPLANE_r14): the data-plane rows must
        # be present so the pin-protocol fast path can't silently drop out
        "put_get_10mb_bytes", "np_roundtrip_100mb", "arg_1mb_fanout",
        # raw-bytes out-of-band lane (PR 16): serve payloads/rollout blobs
        "put_get_32mb_raw_bytes"}
missing = need - rows
assert not missing, f"microbenchmark smoke missing rows: {missing}"
print("microbenchmark rows ok:", ", ".join(sorted(need)))
EOF
  rm -f "$mb_json"
}

run_stress() {
  echo "=== [3/14] actor ordering stress x20 ==="
  for i in $(seq 1 20); do
    python -m pytest tests/test_actor_ordering_stress.py -q -x \
      || { echo "ordering stress failed on iteration $i"; exit 1; }
  done
}

run_chaos() {
  echo "=== [4/14] control-plane HA chaos suite ==="
  # Deterministic fault injection: pin + print the seed so a red run
  # replays the same chaos schedule (override by exporting the variable;
  # timing-dependent counters can still drift between runs).
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "fault injection seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_head_replacement.py tests/test_head_failover.py \
    tests/test_fault_injection.py \
    tests/test_chaos.py tests/test_gcs_fault_tolerance.py \
    -q -m '' \
    || { echo "chaos suite failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
}

run_serve_storm() {
  echo "=== [5/14] serve traffic-storm chaos ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "fault injection seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --quick: ~6 s of ~4x overload with seeded serve_replica_call drops and
  # periodic replica kills. The harness prints submitted/accepted/shed/
  # timeout + retry/failover counters and exits nonzero if ANY request
  # failed to resolve (hung) — the serve plane's overload contract.
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.serve.storm \
    --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json /tmp/ray_tpu_servestorm_ci.json \
    || { echo "serve storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
}

run_burst() {
  echo "=== [6/14] warm-pool elasticity burst ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "burst seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --quick: a 4-actor fleet under closed-loop load bursts to 40 while a
  # seeded killer SIGKILLs live workers. The harness prints warm/cold
  # start counts + fork latency and exits nonzero if any lease ends up
  # served by neither a warm fork nor a cold fallback, any killed actor
  # fails to recover, or any load call never resolves.
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.core.burst \
    --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json /tmp/ray_tpu_burst_ci.json \
    || { echo "elasticity burst failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
  # cross-node composition (ROADMAP item 1): the same worker burst ACROSS
  # an autoscaler-maintained multi-raylet fleet — fails if the wave lands
  # on one node, any lease is unaccounted for, or any load call hangs.
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.core.burst \
    --nodes --target 40 --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json /tmp/ray_tpu_crossburst_ci.json \
    || { echo "cross-node burst failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
}

run_head_failover() {
  echo "=== [7/14] standby-head kill-and-promote storm ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "fault injection seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --kill-head: mid-storm the active head is crash-stopped; a warm standby
  # tails the snapshot store and promotes via the lease/fencing-epoch CAS.
  # The harness prints the lease epochs observed and the promotion latency
  # (lease-expiry -> first-scheduled-task) and exits nonzero if promotion
  # exceeds the budget, any request hangs, or typed errors spike beyond
  # the shed baseline.
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.serve.storm \
    --quick --kill-head --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json /tmp/ray_tpu_servestorm_headfail_ci.json \
    --headfail-json /tmp/ray_tpu_headfail_ci.json \
    || { echo "head-failover storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
}

run_node_chaos() {
  echo "=== [8/14] multi-node kill storm (node failure domain) ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "node storm seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --nodes --quick: a 3-node fleet (FakeNodeProvider raylets, autoscaler
  # as the recovery control loop) under closed-loop actor load takes
  # seeded WHOLE-NODE SIGKILLs — raylet + workers + fork templates die
  # together, no drain notify. The harness prints kills/detections (with
  # the health-bound detection latency), autoscaler relaunches and the
  # node-join-to-first-warm-lease of each replacement; it exits nonzero
  # if any kill goes undetected, any node stays unreplaced, any actor
  # never recovers, or any load call hangs.
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.core.burst \
    --nodes --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json /tmp/ray_tpu_nodestorm_ci.json \
    || { echo "node kill storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
}

run_partition_storm() {
  echo "=== [9/14] partition-heal storm (partition failure domain) ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "partition storm seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --partition --quick: peer-scoped partitions under closed-loop load —
  # death cycles (minority node blackholed past the death bound: must be
  # QUARANTINED first, declared dead at the bound, actors restarted on the
  # autoscaler's replacement; at heal the zombie is FENCED, kills its
  # workers and rejoins fresh; a stale handle is served by the NEW
  # instance), a quarantine-and-recover cycle (zero deaths/relaunches),
  # and a head-in-minority cycle (lease starves, PR-11 standby promotes,
  # old head self-fences). Prints the seed + fence/quarantine counters +
  # heal-to-convergence latency; exits nonzero on any hung call,
  # duplicate named-actor answer, or double replacement.
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.core.burst \
    --partition --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json /tmp/ray_tpu_partition_ci.json \
    || { echo "partition storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
}

run_servebench() {
  echo "=== [10/14] serving perf smoke (servebench quick) ==="
  # Quick profile of python -m ray_tpu.models.servebench: fused-decode
  # tokens/s + the 1/4/8 slot sweep table, w8a16 logits-parity row,
  # batched bucketed prefill, and p50/p99 request latency under the storm
  # harness's load generator against a real LLMDeployment replica. The
  # bench exits nonzero if any required artifact row is missing; the
  # throughput regression FLOORS are pinned (machine-calibrated, 0.5x
  # slack) in tests/test_envelope.py.
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m ray_tpu.models.servebench \
    --json /tmp/ray_tpu_servebench_ci.json \
    || { echo "servebench failed"; exit 1; }
  # the envelope's wall-clock rate floors (r05 / r06 / r14, the raw-bytes
  # lane): nothing else may share the machine while they are read
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest \
    tests/test_envelope.py::test_envelope_floors -q -m slow \
    || { echo "envelope floors failed"; exit 1; }
}

run_trainstorm() {
  echo "=== [11/14] RL fleet chaos (trainstorm quick) ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "trainstorm seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --quick: ~12 s rollout->learner loop (serve replicas -> named learner
  # actor over the zero-copy object plane) with seeded replica kills, one
  # learner crash-restart (resume from the latest COMPLETE checkpoint,
  # exactly-once by rollout id) and one learner|replicas partition-heal.
  # Exits nonzero if any future hangs, any chaos mode fails to land, or
  # recovery blows its budget.
  ts_json="$(mktemp /tmp/ray_tpu_trainstorm_ci.XXXXXX.json)"
  timeout -k 10 450 env JAX_PLATFORMS=cpu python -m ray_tpu.rllib.trainstorm \
    --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" --json "$ts_json" \
    || { echo "trainstorm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
  TS_JSON="$ts_json" python - <<'EOF'
import json, os
art = json.load(open(os.environ["TS_JSON"]))
need = {"samples_per_s", "learner_steps_per_s", "staleness_hist",
        "recovery_to_first_post_restart_step_s", "replica_kills",
        "learner_kills", "learner_restarts", "partition", "fenced_updates",
        "applied_batches", "duplicate_batches", "stale_batches", "zero_hung"}
missing = need - set(art)
assert not missing, f"trainstorm artifact missing rows: {missing}"
assert art["zero_hung"], "trainstorm left hung futures"
print("trainstorm artifact rows ok:", ", ".join(sorted(need)))
EOF
  rm -f "$ts_json"
}

run_memstorm() {
  echo "=== [12/14] store storm (storage failure domain, memstorm quick) ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "memstorm seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --quick: the object store driven to ~2.5x capacity by producer waves
  # while seeded fs faults land on the spill path (enospc/eio/torn/
  # bitflip), a disk-full degrade->probe->heal cycle runs, pins push past
  # the pin cap, and the memory monitor OOM-kills producers mid-spill.
  # Every surviving ref is re-read and checksummed end to end; the
  # harness exits nonzero on any hung get, silent corruption, untyped
  # backpressure, or failed post-heal convergence.
  ms_json="$(mktemp /tmp/ray_tpu_memstorm_ci.XXXXXX.json)"
  timeout -k 10 450 env JAX_PLATFORMS=cpu python -m ray_tpu.core.memstorm \
    --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" --json "$ms_json" \
    || { echo "store storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
  MS_JSON="$ms_json" python - <<'EOF'
import json, os
art = json.load(open(os.environ["MS_JSON"]))
need = {"ok", "zero_hung", "zero_silent_corruption", "spill_restore_gbps",
        "counters", "phases", "violations"}
missing = need - set(art)
assert not missing, f"memstorm artifact missing rows: {missing}"
assert art["ok"] and art["zero_hung"] and art["zero_silent_corruption"], \
    f"memstorm contract violated: {art['violations']}"
c = art["counters"]
for axis in ("spilled_bytes_total", "restored_bytes_total", "lost_spills",
             "degraded_enters", "degraded_heals", "puts_rejected_typed"):
    assert c.get(axis, 0) > 0, f"memstorm chaos axis never fired: {axis}"
print("memstorm artifact rows ok:", ", ".join(sorted(need)))
EOF
  rm -f "$ms_json"
}

run_tracing() {
  echo "=== [13/14] cluster timeline: traced serve storm ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "tracing seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # Two runs of the SAME quick kill-free storm profile: an untraced
  # baseline for the overhead bound, then --traced, where every accepted
  # request must form a complete correctly-parented span chain across >=3
  # processes (proxy/driver -> replica -> nested-task worker; the storm
  # itself exits nonzero below 99%) and the fleet-merged chrome document
  # must validate. The overhead bound is deliberately loose (2.5x + 150 ms
  # on p50): the traced run adds a nested task per request on top of the
  # span bookkeeping, and CI boxes are noisy — it exists to catch a
  # tracing hot path gone accidentally O(heavy), not to benchmark.
  base_json="$(mktemp /tmp/ray_tpu_tracing_base.XXXXXX.json)"
  traced_json="$(mktemp /tmp/ray_tpu_tracing_run.XXXXXX.json)"
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.serve.storm \
    --quick --kill-period 0 --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json "$base_json" \
    || { echo "tracing baseline storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
  timeout -k 10 300 env JAX_PLATFORMS=cpu python -m ray_tpu.serve.storm \
    --quick --traced --seed "${RAY_TPU_FAULT_INJECTION_SEED}" \
    --json "$traced_json" \
    || { echo "traced storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
  BASE_JSON="$base_json" TRACED_JSON="$traced_json" python - <<'EOF'
import json, os
from ray_tpu.util import timeline

base = json.load(open(os.environ["BASE_JSON"]))
art = json.load(open(os.environ["TRACED_JSON"]))
tr = art.get("tracing")
assert tr and tr.get("enabled"), "traced artifact has no tracing block"
assert "tracing" not in base, "baseline ran traced — overhead bound is void"
assert tr["cross3_fraction"] >= 0.99, \
    f"complete >=3-process chains: {tr['cross3_fraction']:.1%} < 99%"
assert tr["clock_sources"] >= 3, \
    f"only {tr['clock_sources']} clock sources reported"
assert tr["max_abs_clock_offset_us"] < 10_000, \
    f"post-alignment clock skew {tr['max_abs_clock_offset_us']}us >= 10ms"
# re-validate the chrome document from disk: JSON-parseable, every event
# carrying name/ph/ts/pid/tid, "X" durs finite, ts monotone in file order
doc = json.load(open(tr["chrome_path"]))
problems = timeline.validate_chrome(doc)
assert not problems, f"chrome trace invalid: {problems[:5]}"
assert len(doc["traceEvents"]) == tr["chrome_events"]
b, t = (base["latency_ms"]["p50_accepted"], art["latency_ms"]["p50_accepted"])
budget = b * 2.5 + 150.0
assert t <= budget, f"traced p50 {t}ms blows overhead budget {budget:.0f}ms " \
    f"(untraced baseline {b}ms)"
print(f"tracing stage ok: {tr['chains_3plus_processes']}/{tr['accepted_traced']} "
      f"chains across >=3 processes, {tr['clock_sources']} clock sources "
      f"(max offset {tr['max_abs_clock_offset_us']/1000:.2f}ms), "
      f"{tr['chrome_events']} chrome events, "
      f"p50 {t}ms vs untraced {b}ms (budget {budget:.0f}ms)")
EOF
  rm -f "$base_json" "$traced_json" "$traced_json.trace.json"
}

run_jobstorm() {
  echo "=== [14/14] job storm (job failure domain, jobstorm quick) ==="
  : "${RAY_TPU_FAULT_INJECTION_SEED:=20260804}"
  export RAY_TPU_FAULT_INJECTION_SEED
  echo "jobstorm seed: ${RAY_TPU_FAULT_INJECTION_SEED}"
  # --quick: 4 concurrent driver processes (nested task trees, named +
  # detached counter actors, 1 MiB pinned puts); 2 are SIGKILLed
  # mid-flight on a seeded staggered schedule. The harness exits nonzero
  # if any killed job is not DEAD + fully reaped within the bound, a
  # detached actor fails to answer a fresh driver with its pre-kill
  # state, a cross-job get of a reaped object is not the typed
  # OwnerDiedError, any survivor hangs or starves, or any worker
  # process / object-table entry / shm segment leaks.
  js_json="$(mktemp /tmp/ray_tpu_jobstorm_ci.XXXXXX.json)"
  timeout -k 10 360 env JAX_PLATFORMS=cpu python -m ray_tpu.core.jobstorm \
    --quick --seed "${RAY_TPU_FAULT_INJECTION_SEED}" --json "$js_json" \
    || { echo "job storm failed (seed ${RAY_TPU_FAULT_INJECTION_SEED})"
         exit 1; }
  JS_JSON="$js_json" python - <<'EOF'
import json, os
art = json.load(open(os.environ["JS_JSON"]))
need = {"ok", "zero_hung", "zero_leaks", "detached_survived",
        "counters", "phases", "violations"}
missing = need - set(art)
assert not missing, f"jobstorm artifact missing rows: {missing}"
assert art["ok"] and art["zero_hung"] and art["zero_leaks"] \
    and art["detached_survived"], \
    f"jobstorm contract violated: {art['violations']}"
c = art["counters"]
for axis in ("jobs_reaped", "actors_killed", "detached_spared",
             "objects_dropped", "bytes_dropped"):
    assert c.get(axis, 0) > 0, f"jobstorm reap axis never fired: {axis}"
st = art["phases"]["storm"]
assert st["leaked_workers"] == 0 and st["leaked_objects"] == 0
assert art["phases"]["teardown"]["leaked_shm_segments"] == 0
assert art["phases"]["cross_job_get"]["typed_owner_died"] > 0
print(f"jobstorm artifact rows ok: reaped={c['jobs_reaped']} "
      f"actors_killed={c['actors_killed']} "
      f"detached_spared={c['detached_spared']} "
      f"workers_killed={c['workers_killed']} "
      f"objects_dropped={c['objects_dropped']} "
      f"({c['bytes_dropped']} B) "
      f"detached_answered={art['phases']['detached']['answered']}"
      f"/{art['phases']['detached']['expected']} "
      f"leaks=0w/0o/0shm")
EOF
  rm -f "$js_json"
}

case "$STAGE" in
  --native)     run_native ;;
  --fast)       run_fast ;;
  --stress)     run_stress ;;
  --chaos)      run_chaos ;;
  --storm)      run_serve_storm ;;
  --burst)      run_burst ;;
  --failover)   run_head_failover ;;
  --node-chaos) run_node_chaos ;;
  --partition)  run_partition_storm ;;
  --servebench) run_servebench ;;
  --trainstorm) run_trainstorm ;;
  --memstorm)   run_memstorm ;;
  --tracing)    run_tracing ;;
  --jobstorm)   run_jobstorm ;;
  all)        run_native; run_fast; run_stress; run_chaos; run_serve_storm
              run_burst; run_head_failover; run_node_chaos
              run_partition_storm; run_servebench; run_trainstorm
              run_memstorm; run_tracing; run_jobstorm ;;
  *) echo "unknown stage: $STAGE" \
     "(use --native|--fast|--stress|--chaos|--storm|--burst|--failover|--node-chaos|--partition|--servebench|--trainstorm|--memstorm|--tracing|--jobstorm)" >&2
     exit 2 ;;
esac
echo "CI green"
