"""The engine's own spans (models/serving.py -> util/tracing.py): request
stages, step spans with their device waits, the request's trace carried
from the HTTP ingress into the engine with default settings, compile spans
by program name, and the named scopes of the train and serve programs."""

import json
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu.models import ModelConfig, init_params
from ray_tpu.models.serving import (ContinuousBatchingEngine, LLMDeployment,
                                    decode_step_fused, prefill_slots)
from ray_tpu.util import timeline, tracing

CFG = ModelConfig.tiny()
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
STAGES = ("engine.queue", "engine.prefill", "engine.decode")


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.clear()
    tracing.set_ctx(None)
    yield
    tracing.clear()
    tracing.set_ctx(None)


def _engine_spans():
    return [e for e in tracing.get_events() if e.get("cat") == "engine"]


def _stages_by_request(spans):
    out = {}
    for e in spans:
        if e["name"] in STAGES:
            out.setdefault(e["args"]["request_id"], {})[e["name"]] = e
    return out


def test_every_request_has_three_contiguous_stages():
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=4, max_len=64)
    asked = {eng.submit([1 + i, 2, 3], max_new_tokens=n): n
             for i, n in enumerate((5, 1, 8))}
    eng.run_until_done()
    by_req = _stages_by_request(_engine_spans())
    assert set(by_req) == set(asked)
    for rid, n in asked.items():
        q, p, d = (by_req[rid][s] for s in STAGES)
        assert q["dur"] >= 0 and p["dur"] > 0 and d["dur"] >= 0
        # in order and contiguous: each stage starts where the last ended
        assert q["ts"] + q["dur"] == pytest.approx(p["ts"], abs=1e-3)
        assert p["ts"] + p["dur"] == pytest.approx(d["ts"], abs=1e-3)
        assert d["args"]["tokens"] == n - 1   # the first came from prefill
        assert p["args"]["prompt_len"] == 3 and p["args"]["bucket"] == 8
        assert p["args"]["batch"] == 3        # one prefill admitted all three
        assert q["args"]["waited_for_slot"] is False
        # standalone use: no context on the submitting thread, no ids
        assert "trace_id" not in q


def test_one_slot_makes_two_of_three_wait_for_it():
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=1, max_len=64)
    rids = [eng.submit([7, i + 1], max_new_tokens=3) for i in range(3)]
    eng.run_until_done()
    by_req = _stages_by_request(_engine_spans())
    waited = [by_req[r]["engine.queue"]["args"]["waited_for_slot"]
              for r in rids]
    assert waited == [False, True, True]
    # who waited for a slot queued for at least the first request's service
    first_done = (by_req[rids[0]]["engine.decode"]["ts"]
                  + by_req[rids[0]]["engine.decode"]["dur"])
    for r in rids[1:]:
        q = by_req[r]["engine.queue"]
        assert q["ts"] + q["dur"] >= first_done - 1e-3
    steps = [e for e in _engine_spans() if e["name"] == "engine.step"]
    assert max(s["args"]["waiting"] for s in steps) == 2


def test_step_spans_count_the_slots_they_dispatched():
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=4, max_len=64)
    asked = [6, 3, 9, 2, 4]
    for i, n in enumerate(asked):
        eng.submit([i + 1, 5], max_new_tokens=n)
    eng.run_until_done()
    spans = _engine_spans()
    steps = [e for e in spans if e["name"] == "engine.step"]
    decode_tokens = sum(e["args"]["tokens"] for e in spans
                        if e["name"] == "engine.decode")
    assert decode_tokens == sum(asked) - len(asked)
    # `active` is the slots in each dispatched decode. With one step of
    # lookahead a retiring request is dispatched once more before the host
    # sees its last token: one junk slot-step per request on top of the
    # decode tokens produced (module docstring of models/serving.py).
    assert sum(s["args"]["active"] for s in steps) == \
        decode_tokens + len(asked)
    assert sum(s["args"]["admitted"] for s in steps) == len(asked)
    assert sum(s["args"]["prefill_batches"] for s in steps) == len(
        [e for e in spans if e["name"] == "engine.prefill_dispatch"])
    assert all(s["args"]["attn_len"] == 64 for s in steps
               if s["args"]["active"])
    # the dense cache's own argument: the rows that hold a token, which
    # the step has to read, of the slots x `attn_len` window it replaces.
    # Every prompt here has 2 tokens, so a slot's k-th dispatch holds 1 + k
    # rows: each request adds 2 + 3 + ... + (asked + 1)
    dispatched = [s["args"] for s in steps if s["args"]["active"]]
    assert all(0 < a["live_rows"] <= 4 * a["attn_len"] for a in dispatched)
    assert all(a["live_rows"] >= a["active"] for a in dispatched)
    assert sum(a["live_rows"] for a in dispatched) == sum(
        sum(range(2, n + 2)) for n in asked)
    # every device wait lies inside a step: host time = step - its waits
    waits = [e for e in spans if e["name"] == "engine.wait_device"]
    assert waits and {w["args"]["what"] for w in waits} == {"first", "decode"}
    for w in waits:
        assert any(s["ts"] <= w["ts"] and
                   w["ts"] + w["dur"] <= s["ts"] + s["dur"] + 1e-3
                   for s in steps if s["tid"] == w["tid"])


@pytest.mark.parametrize("cache", ["dense", "runs"])
def test_step_spans_count_the_slots_whose_rows_the_step_wrote(cache):
    """`active` on `engine.step` is also the slots whose block of rows the
    step's `ops.cache.write_rows` moves: the busy slots at dispatch (on the
    TPU; the loop of the CPU path visits every slot), for the dense cache
    and for the runs cache; 0, with `attn_len` 0, on a step that dispatched
    no decode (one more call of the stepper once every request is done: it
    reaps the last junk slot-step and finds nothing busy). No cache repeats
    either under a name of its own."""
    if cache == "dense":
        cfg, params = CFG, PARAMS
    else:
        from ray_tpu.models import hybrid
        cfg = hybrid.HybridConfig.tiny_runs()
        params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=64)
    for i, n in enumerate([5, 2, 4]):
        eng.submit([i + 1, 5], max_new_tokens=n)
    eng.run_until_done()
    eng.step()
    steps = [e["args"] for e in _engine_spans() if e["name"] == "engine.step"]
    assert not any({"written_slots", "window_rows"} & set(a) for a in steps)
    assert {a["active"] for a in steps} == {0, 1, 2, 3}
    assert steps[-1]["active"] == 0 and steps[-1]["attn_len"] == 0
    assert all((a["attn_len"] == 64) == (a["active"] > 0) for a in steps)
    # the other arguments of the cache stay those of a dispatched step
    own = "live_rows" if cache == "dense" else "kv_rows"
    assert all((own in a) == (a["active"] > 0) for a in steps)


def test_compiles_are_spans_named_by_program():
    # another file's test in this worker's process may have compiled the same
    # `prefill_slots` (it does not depend on the number of slots): a cache
    # hit is no compile and leaves no span
    jax.clear_caches()
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=3, max_len=32)
    eng.generate([1, 2, 3], max_new_tokens=2)   # 3 slots x 32: new shapes
    compiles = [e for e in tracing.get_events() if e["name"] == "xla.compile"]
    assert all(e["cat"] == "compile" and e["dur"] >= 0 for e in compiles)
    by_fun = {}
    for e in compiles:
        by_fun.setdefault(e["args"]["fun_name"], set()).add(e["args"]["event"])
    step = set().union(*(v for k, v in by_fun.items()
                         if "decode_step_fused" in k))
    assert {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration"} <= step, by_fun
    assert any("prefill_slots" in k for k in by_fun), sorted(by_fun)


def test_a_programs_own_trace_survives_and_the_inner_ones_do_not():
    """jax reports a trace for every jitted function it meets inside a
    program (each `jnp` call, each nested jit); `record_compiles` keeps the
    one named like the module lowered next, and a program compiles to
    exactly one trace, one lower and one compile span."""
    tracing.record_compiles()

    @jax.jit
    def inner_program(x):
        return jnp.tanh(x) * 2.0

    @jax.jit
    def outer_program(x):
        return jnp.sum(inner_program(jnp.sin(x)) + jnp.where(x > 0, x, 0.0))

    x7, x3 = jnp.ones((7,)), jnp.ones((3,))   # programs of their own: before
    tracing.clear()
    outer_program(x7).block_until_ready()
    compiles = [e["args"] for e in tracing.get_events() if e["name"] == "xla.compile"]
    traces = [a["fun_name"] for a in compiles if a["event"] == "jaxpr_trace_duration"]
    assert traces == ["outer_program"], traces
    own = [a["event"] for a in compiles if "outer_program" in a["fun_name"]]
    assert sorted(own) == ["backend_compile_duration", "jaxpr_to_mlir_module_duration",
                           "jaxpr_trace_duration"], compiles
    assert not any("inner_program" in a["fun_name"] or a["fun_name"] in ("sin", "tanh")
                   for a in compiles), compiles
    # a second, separate program after it is kept as well
    inner_program(x3).block_until_ready()
    traces = [e["args"]["fun_name"] for e in tracing.get_events()
              if e["name"] == "xla.compile"
              and e["args"]["event"] == "jaxpr_trace_duration"]
    assert traces == ["outer_program", "inner_program"], traces


_CACHE_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from ray_tpu.util import tracing
tracing.record_compiles()
x = jnp.ones((5,))   # its own small programs: before the two that count
tracing.clear()
for name in sys.argv[1:]:
    jax.jit(lambda a: jnp.tanh(a) * len(name), inline=False).lower(x)  # traced, not compiled
    fn = lambda a: jnp.cos(a) + len(name)
    fn.__name__ = name
    jax.jit(fn)(x).block_until_ready()
print(json.dumps([e["args"] for e in tracing.get_events()
                  if e["name"] == "xla.compile"
                  and e["args"]["event"] == "backend_compile_duration"]))
"""


@pytest.fixture(scope="module")
def cache_runs(tmp_path_factory):
    """Three fresh processes over one temporary persistent cache that keeps
    every program: `first_program` and `second_program` compiled back to
    back; then `first_program` and a program the cache has never seen; then
    `first_program` with no cache."""
    import subprocess
    import sys

    cache = str(tmp_path_factory.mktemp("jax_cache"))
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(sys.path))
    kept = dict(env, JAX_COMPILATION_CACHE_DIR=cache,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")

    def child(env, *programs):
        p = subprocess.run([sys.executable, "-c", _CACHE_CHILD, *programs],
                           capture_output=True, text=True, timeout=120, env=env)
        assert p.returncode == 0, p.stderr[-2000:]
        return {a["fun_name"]: a for a in json.loads(p.stdout.splitlines()[-1])}

    return {"cold": child(kept, "first_program", "second_program"),
            "warm": child(kept, "first_program", "third__program"),
            "off": child(env, "first_program")}


@pytest.mark.parametrize("run,program,cache", [
    ("cold", "first_program", "miss"), ("cold", "second_program", "miss"),
    ("warm", "first_program", "hit"), ("warm", "third__program", "miss"),
    ("off", "first_program", "off")])
def test_a_compile_span_says_how_the_persistent_cache_answered(
        cache_runs, run, program, cache):
    """`cache` on a program's backend-compile span: `miss` at its first
    compile under a persistent cache, `hit` in a fresh process with what the
    read cost, `off` without a cache; and the facts of one program never
    ride the next one's span: the miss that follows a hit back to back
    carries no `retrieval_us`."""
    spans = cache_runs[run]
    assert sorted(spans) == sorted(f"jit({p})" for p in (
        {"cold": ("first_program", "second_program"),
         "warm": ("first_program", "third__program"),
         "off": ("first_program",)}[run])), spans
    args = spans[f"jit({program})"]
    assert args["cache"] == cache, spans
    if cache == "hit":
        assert args["retrieval_us"] > 0
    else:
        assert "retrieval_us" not in args, args


def test_a_list_form_programs_spans_count_its_layer_bodies():
    """The list form's layers run through jitted bodies, one a kind
    (`models/hybrid.py`); jax reports a trace for each, and none becomes a
    span: the prompt pass's and the step's own trace, lower and compile
    spans carry `layers` and how many bodies the program traced anew
    (`layer_bodies_traced`: here each its four, nothing being traced yet)."""
    from ray_tpu.models import hybrid

    cfg = hybrid.HybridConfig.tiny_hybrid()
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    jax.clear_caches()   # a body another test traced at these shapes is a hit
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=32)
    eng.generate([1, 2, 3], max_new_tokens=2)
    compiles = [e["args"] for e in tracing.get_events() if e["name"] == "xla.compile"]
    assert not [a for a in compiles if re.search(
        r"_(kda|mla)_(seq|step)|_ffn_rows", a["fun_name"])], compiles
    for program in ("_prefill_first", "decode_step"):
        own = [a for a in compiles if a["fun_name"] in (program, f"jit({program})")]
        assert sorted(a["event"] for a in own) == [
            "backend_compile_duration", "jaxpr_to_mlir_module_duration",
            "jaxpr_trace_duration"], (program, compiles)
        assert all((a["layers"], a["layer_bodies_traced"]) == (4, 4) for a in own), own


@pytest.mark.parametrize(
    "mesh_cfg,n,seq,exchanges,tp_exchanges,norm_sums,pinned,ordered,head", [
        ({"dp": 1, "fsdp": 2, "tp": 2}, 4, 16, 7, 4, 0, 1, 1, 1),
        ({"dp": 1}, 1, 16, 0, 0, 0, 0, 0, 0),
        ({"dp": 2, "fsdp": 1, "tp": 2}, 4, 16, 0, 0, 2, 0, 0, 0),
        ({"dp": 1, "fsdp": 2, "tp": 2}, 4, 15, 7, 0, 0, 0, 0, 0),
        ({"dp": 2, "fsdp": 2, "tp": 1}, 4, 16, 7, 0, 0, 0, 0, 0)],
    ids=["fsdp2xtp2", "one_device", "dp2xtp2", "fsdp2xtp2_odd_seq", "dp2xfsdp2"])
def test_train_steps_compile_spans_say_which_reduction_ran(
        mesh_cfg, n, seq, exchanges, tp_exchanges, norm_sums, pinned, ordered,
        head):
    """Whether the program spells the weight gradients' exchange over fsdp
    itself (parallel/fsdp.py), and the block's gathers and scatters over tp
    (parallel/tp.py), is a fact of its compile: the train step's trace, lower
    and compile spans carry the mesh's `fsdp` and `tp`, how many of a layer's
    weights go the first way (7 on fsdp 2 x tp 2, else 0) and how many of its
    forward's transfers over tp the second (4 where fsdp > 1 as well and
    tp > 1 divides the sequence, else 0), and how many of its two norm
    scales' gradients are still summed across the chips inside the layers'
    backward (2 on a mesh that splits the rows, but 0 wherever the weights
    come exchanged: there the partial sums leave the scan and are summed
    once a step; 0 on one device, where nothing is summed), and how many of a
    layer's ring products run the rank's own shard first by a pin (1 where
    the products are parallel/tp.py's, the FFN's `w_down` in the backward;
    0 wherever they are the partitioner's), and whether the seven weight
    gradients' rings are taken off the `fsdp` link in the order of their
    starts (1 on the same mesh: the order goes from product to product;
    0 where the products are not parallel/tp.py's), and whether the head's
    product carries its exchanges with them (`head_exchanged`: 1 on the
    same mesh, 0 wherever the head is the partitioner's)."""
    from ray_tpu.models import ModelConfig
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import batch_sharding, make_train_step

    cfg = ModelConfig.tiny()
    mesh = make_mesh(MeshConfig(**mesh_cfg), jax.devices()[:n])
    step_fn, init_fn, _ = make_train_step(cfg, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jnp.zeros((4, seq + 1), jnp.int32)
    batch = jax.device_put({"inputs": tokens[:, :-1], "targets": tokens[:, 1:]},
                           batch_sharding(mesh))
    tracing.clear()
    lowered = step_fn.lower(state, batch)
    # the exchanges are in the program exactly where the span says they are
    assert ("collective_permute" in lowered.as_text()) == (
        exchanges + tp_exchanges > 0)
    lowered.compile()
    spans = [e["args"] for e in tracing.get_events()
             if e["name"] == "xla.compile" and "step" in e["args"]["fun_name"]]
    assert {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration"} <= {a["event"] for a in spans}, spans
    for a in spans:
        assert (a["fsdp"], a["tp"], a["grad_exchanges_per_layer"],
                a["tp_exchanges_per_layer"],
                a["norm_grad_reductions_in_layers"],
                a["ring_products_own_first"], a["dw_rings_ordered"],
                a["head_exchanged"]) == (
            mesh_cfg.get("fsdp", 1), mesh_cfg.get("tp", 1), exchanges,
            tp_exchanges, norm_sums, pinned, ordered, head), a


@pytest.mark.parametrize("name", ["engine.step", "engine.between_steps"])
def test_span_sits_on_the_profilers_host_plane(tmp_path, name):
    """One clock with the device trace: inside a profiler session the
    program's spans are TraceAnnotations of the same `.xplane.pb`; the two
    that tile the driver thread's life are `tracing.span()` blocks for that."""
    import glob

    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.start_driver()
        assert len(list(eng.generate_stream([1, 2, 3], max_new_tokens=3))) == 3
        eng.stop_driver()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert name in names


def test_a_drafting_steps_spans_say_what_it_proposed_kept_and_yielded():
    """`engine.step` of a configuration with a prediction module: the
    device's `draft_proposed` (a draft for every busy slot of the step reaped
    in that span), `draft_accepted`, the expert-layer counters and, from the
    host at dispatch, `latent_rows` (of `num_slots x attn_len`) beside
    `active`; `tokens_out` on EVERY step, and over the run it is
    exactly the tokens the requests received; `engine.prefill` carries the
    latent layers, the module's with them."""
    from ray_tpu.models import hybrid

    cfg = hybrid.HybridConfig.tiny_rotary()
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=64)
    asked = [7, 4, 9, 5, 6]
    ids = [eng.submit([i + 1, 5, 3], max_new_tokens=n) for i, n in enumerate(asked)]
    eng.run_until_done()
    eng.step()
    got = [len(eng.result(i)) - 3 for i in ids]
    assert got == asked
    spans = _engine_spans()
    steps = [e["args"] for e in spans if e["name"] == "engine.step"]
    assert all("tokens_out" in a for a in steps)
    assert sum(a["tokens_out"] for a in steps) == sum(asked)
    dispatched = [a for a in steps if a["active"]]
    for key in ("latent_rows", "attn_len", "active", "state_slots"):
        assert all(key in a for a in dispatched), key
    assert all(a["attn_len"] == 64 for a in dispatched)
    assert all(0 < a["latent_rows"] <= 4 * a["attn_len"] for a in dispatched)
    assert all(a["latent_rows"] >= a["active"] for a in dispatched)
    reaped = [a for a in steps if "draft_proposed" in a]
    for key in ("draft_accepted", "expert_assignments", "experts_touched"):
        assert all(key in a for a in reaped), key
    # a draft for every busy slot of every dispatched step, junk steps too
    assert sum(a["draft_proposed"] for a in reaped) == \
        sum(a["active"] for a in dispatched)
    assert all(0 <= a["draft_accepted"] <= a["draft_proposed"] for a in reaped)
    # a request's tokens: its first from the prompt pass, then 1 + accepted
    # a step; what the device kept is at least what the requests received
    kept = sum(a["draft_proposed"] + a["draft_accepted"] for a in reaped)
    assert kept >= sum(asked) - len(asked)
    prefills = [e["args"] for e in spans if e["name"] == "engine.prefill"]
    assert len(prefills) == len(asked)
    assert all(a["latent_layers"] == 4 and a["state_layers"] == 0 for a in prefills)


def test_every_cache_says_how_many_tokens_a_step_yields():
    """`tokens_out` is the engine's own, whatever the cache: the dense
    model's steps carry it too and it adds up to what was received."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=64)
    asked = [4, 6, 3]
    for i, n in enumerate(asked):
        eng.submit([i + 1, 5], max_new_tokens=n)
    eng.run_until_done()
    steps = [e["args"] for e in _engine_spans() if e["name"] == "engine.step"]
    assert sum(a["tokens_out"] for a in steps) == sum(asked)
    assert eng.cache.step_tokens == 1


def _scopes(lowered):
    """Path components of every op name in a lowering's debug locations."""
    names = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    return {part for n in names for part in n.split("/")}


def test_named_scopes_reach_the_lowered_programs():
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.step import default_optimizer, make_train_step

    mesh = make_mesh(MeshConfig(), jax.devices()[:1])
    step_fn, init_fn, _ = make_train_step(CFG, mesh, default_optimizer(1e-3))
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    train = _scopes(step_fn.lower(state, {"inputs": toks, "targets": toks}))
    # under value_and_grad the forward pass is the scopes' jvp and the
    # backward pass their transpose
    assert {"jvp(forward)", "jvp(head_loss)", "transpose(jvp(forward))",
            "transpose(jvp(head_loss))", "optimizer"} <= train, sorted(train)

    L, kvh, hd = CFG.n_layers, CFG.n_kv_heads, CFG.head_dim
    cache = jax.ShapeDtypeStruct((L, 4, kvh, 64, hd), CFG.dtype)
    i4 = jax.ShapeDtypeStruct((4,), jnp.int32)
    serve_scopes = {"cache_write", "attention", "mlp", "head"}
    assert serve_scopes <= _scopes(decode_step_fused.lower(
        PARAMS, cache, cache, i4, i4, CFG, 64))
    assert serve_scopes <= _scopes(prefill_slots.lower(
        PARAMS, jax.ShapeDtypeStruct((2, 16), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), CFG, 64))


def test_named_scopes_of_the_drafting_model_in_all_call_modes():
    """`mla`, `moe`, `shared_expert`, `mtp`, `state_write`, `head` name the
    phases of the forward, the prompt pass and the verify step alike."""
    from ray_tpu.models import hybrid

    cfg = hybrid.HybridConfig.tiny_rotary()
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    i2 = jax.ShapeDtypeStruct((2,), jnp.int32)
    want = {"mla", "moe", "shared_expert", "mtp", "head"}
    assert want <= _scopes(hybrid.forward.lower(params, toks, cfg, with_mtp=True))
    assert want <= _scopes(hybrid._prefill_first.lower(params, toks, i2, cfg))
    cache = cfg.make_cache(4, 64)
    i4 = jax.ShapeDtypeStruct((4,), jnp.int32)
    assert want | {"state_write"} <= _scopes(hybrid.decode_step.lower(
        params, cache.state, i4, i4, jax.ShapeDtypeStruct((4,), jnp.bool_), cfg, 64))



def _driver_spans():
    """The driver thread's `engine.step` and `engine.between_steps`, in
    time order (one engine a test: one driver thread)."""
    spans = [e for e in _engine_spans()
             if e["name"] in ("engine.step", "engine.between_steps")]
    assert len({e["tid"] for e in spans}) == 1
    return sorted(spans, key=lambda e: e["ts"])


def test_step_and_between_steps_tile_the_driver_thread():
    """Every `engine.between_steps` starts where the previous `engine.step`
    of the thread ended and ends where the next starts: no holes, no
    overlap; `slept_us` is the part of it with nothing to do."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=4, max_len=64)
    eng.start_driver()
    t_start = tracing.now_us()
    rids = [eng.submit([i + 1, 2, 3], max_new_tokens=n)
            for i, n in enumerate((6, 3, 9))]
    for rid in rids:
        eng.wait(rid, timeout=120)
    eng.stop_driver()
    t_stop = tracing.now_us()
    spans = _driver_spans()
    names = [e["name"] for e in spans]
    assert names[0] == names[-1] == "engine.between_steps"
    # strictly alternating: between, step, between, ..., between
    assert names[0::2] == ["engine.between_steps"] * len(names[0::2])
    assert names[1::2] == ["engine.step"] * len(names[1::2]) and names[1::2]
    for a, b in zip(spans, spans[1:]):
        hole = b["ts"] - (a["ts"] + a["dur"])
        assert -1.0 <= hole < 5e3, (hole, a["name"], b["name"])
    for g in spans[0::2]:
        assert 0 <= g["args"]["slept_us"] <= g["dur"], g
        assert isinstance(g["args"]["had_work"], bool)
    # the two tile the thread's life, from its start to its stop
    covered = sum(e["dur"] for e in spans)
    assert 0.9 * covered <= t_stop - t_start
    assert covered >= 0.9 * (spans[-1]["ts"] + spans[-1]["dur"] - spans[0]["ts"])
    # stepping by hand records none
    tracing.clear()
    eng.generate([1, 2, 3], max_new_tokens=3)
    assert not [e for e in _engine_spans() if e["name"] == "engine.between_steps"]


def test_an_idle_engines_between_steps_is_all_sleep():
    """With nothing to do the driver sleeps in `_cv.wait`: `slept_us` is
    within a few ms of the span (on a machine that runs other tests beside
    this one a woken thread may wait tens of ms for a processor: the best of
    the idle gaps is held to 5 ms, each to 100), `had_work` is False, and
    the work that ends the sleep is not counted as a cost of the gap."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=64)
    eng.start_driver()
    time.sleep(0.5)
    eng.generate([1, 2, 3], max_new_tokens=2)
    time.sleep(0.5)
    eng.stop_driver()
    gaps = [e for e in _driver_spans() if e["name"] == "engine.between_steps"]
    idle = [g for g in gaps if g["args"]["slept_us"] > 200e3]
    assert len(idle) == 2, [(g["dur"], g["args"]) for g in gaps]
    awake = [g["dur"] - g["args"]["slept_us"] for g in idle]
    assert all(g["args"]["had_work"] is False for g in idle)
    assert all(0 <= a <= 100e3 for a in awake) and min(awake) <= 5e3, awake


def test_step_spans_count_the_wait_for_the_lock_and_the_bookkeeping():
    """`lock_wait_us`: the summed wait to ENTER `_lock` over the step's
    acquisitions; `bookkeep_us`: the time HOLDING it while tokens are handed
    to the requests. A thread that holds `_lock` for 20 ms across a step
    shows up in the first, whole."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=64)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()
    eng.step()
    holding, calling = threading.Event(), threading.Event()

    def hold():
        with eng._lock:
            holding.set()
            calling.wait(30)
            time.sleep(0.02)

    t = threading.Thread(target=hold)
    t.start()
    assert holding.wait(30)
    tracing.clear()
    calling.set()
    eng.step()
    t.join()
    eng.run_until_done()
    held, *rest = [e for e in _engine_spans() if e["name"] == "engine.step"]
    assert held["args"]["lock_wait_us"] >= 19_000
    assert rest and all(s["args"]["lock_wait_us"] < 19_000 for s in rest)
    for s in [held] + rest:
        a = s["args"]
        assert 0 <= a["lock_wait_us"] <= s["dur"] + 1
        assert 0 <= a["bookkeep_us"] <= s["dur"] + 1
    # handing tokens out takes time: some step that reaped any counted it
    assert any(s["args"]["bookkeep_us"] > 0 for s in rest
               if s["args"]["tokens_out"])


def test_one_stream_span_per_streamed_request_from_the_consumers_thread():
    """`engine.stream`: emitted by the thread that consumes the generator,
    when it ends, under that thread's trace context; what it counted adds up
    with what the generator yielded. A generator closed early emits it too."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=64)
    eng.start_driver()
    asked, got = {0: 7, 1: 3, 2: 5}, {}

    def consume(i):
        ctx = tracing.start_trace()
        toks = list(eng.generate_stream([i + 1, 2, 3], max_new_tokens=asked[i]))
        got[i] = (ctx[0], threading.get_ident() % 100000, len(toks))

    threads = [threading.Thread(target=consume, args=(i,)) for i in asked]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    early = eng.generate_stream([9, 2, 3], max_new_tokens=20)
    assert len([next(early), next(early)]) == 2
    early.close()
    eng.stop_driver()
    streams = [e for e in _engine_spans() if e["name"] == "engine.stream"]
    assert len(streams) == 4
    by_trace = {e["trace_id"]: e for e in streams if "trace_id" in e}
    assert len(by_trace) == 3
    for i, (trace_id, tid, n) in got.items():
        e = by_trace[trace_id]
        a = e["args"]
        assert n == asked[i] == a["tokens"] and e["tid"] == tid
        # a batch's lag is under the span's length, and there is at most
        # one batch a token
        assert 1 <= a["wakes"] and 0 <= a["deliver_lag_us_sum"] <= n * e["dur"]
        assert 0 <= a["lock_us_sum"] <= e["dur"]
        # it outlasts the engine's own three stages of the same request
        decode = next(d for d in _engine_spans() if d["name"] == "engine.decode"
                      and d["args"]["request_id"] == a["request_id"])
        assert e["ts"] <= decode["ts"] and \
            e["ts"] + e["dur"] >= decode["ts"] + decode["dur"] - 1.0
    (closed,) = [e for e in streams if "trace_id" not in e]
    assert closed["args"]["tokens"] == 2 and closed["args"]["wakes"] >= 1


def _stream_over_http(port, n, rid=None):
    """POST one streamed request of `n` tokens; (its X-Request-Id, lines)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    headers = {"Content-Type": "application/json"}
    if rid:
        headers["X-Request-Id"] = rid
    conn.request("POST", "/LLMDeployment/stream?stream=1",
                 body=json.dumps({"prompt": [5, 17, 400, 3],
                                  "max_new_tokens": n}), headers=headers)
    resp = conn.getresponse()
    assert resp.status == 200
    got = resp.getheader("X-Request-Id")
    lines = [ln for ln in resp.read().splitlines() if ln]
    conn.close()
    return got, lines


def _traces_once_they_hold(trace_ids, want, settled=lambda traces: True):
    """{trace_id: spans} from the GCS once every trace holds `want`."""
    traces = {}
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        traces = timeline.group_by_trace(
            e for e in ray_tpu.timeline() if e.get("trace_id") in trace_ids)
        if all(want <= {s["name"] for s in traces.get(t, [])}
               for t in trace_ids) and settled(traces):
            break
        time.sleep(0.3)
    return traces


def test_one_trace_from_http_ingress_to_last_token(ray_start_regular):
    """Default config, no switch: one trace_id links ingress:: -> route::
    -> submit:: -> task::handle_request -> stream::handle_request ->
    engine.queue/prefill/decode/stream -> relay::, parent links resolve, and
    an incoming X-Request-Id IS the trace id. The stream method's body runs
    lazily on the worker thread: that `submit()` still sees the request's
    context is what this test is for. The three spans of the token's way out
    agree on how many tokens went by."""
    from ray_tpu import serve

    assert not tracing.enabled()
    D = serve.deployment(LLMDeployment(PARAMS, CFG, num_slots=2, max_len=64))
    try:
        serve.run(D.bind())
        _, port = serve.start_http_proxy()
        rids = ["req-0001.a_b", None]
        got_ids = []
        for rid in rids:
            got, lines = _stream_over_http(port, 5, rid)
            got_ids.append(got)
            assert len(lines) == 5
        assert got_ids[0] == rids[0] and re.fullmatch(r"[0-9a-f]{16}",
                                                      got_ids[1])

        want = {"ingress::LLMDeployment", "route::LLMDeployment",
                "submit::handle_request", "task::handle_request",
                "result::handle_request", "stream::handle_request",
                "relay::LLMDeployment", "engine.stream", *STAGES}
        traces = _traces_once_they_hold(got_ids, want)
        for t in got_ids:
            spans = {s["name"]: s for s in traces[t]}
            assert want <= set(spans), (t, sorted(spans))
            chain = timeline.validate_chain(traces[t])
            assert chain["complete"] and chain["processes"] >= 2, chain
            assert spans["ingress::LLMDeployment"]["parent_id"] == ""
            assert (spans["route::LLMDeployment"]["parent_id"]
                    == spans["ingress::LLMDeployment"]["span_id"])
            assert (spans["submit::handle_request"]["parent_id"]
                    == spans["route::LLMDeployment"]["span_id"])
            # the engine's stages hang off the replica task's context
            assert (spans["engine.queue"]["parent_id"]
                    == spans["submit::handle_request"]["span_id"])
            order = [spans[n]["ts"] for n in (
                "ingress::LLMDeployment", "route::LLMDeployment",
                "task::handle_request", *STAGES)]
            assert order == sorted(order), order
            assert spans["engine.decode"]["args"]["tokens"] == 4
            # the token's way out: one span each at the consumer, the
            # replica and the proxy, all five tokens through each
            for name in ("stream::handle_request", "relay::LLMDeployment",
                         "engine.stream"):
                assert len([s for s in traces[t] if s["name"] == name]) == 1
            consumer, replica, relay = (spans[n]["args"] for n in (
                "engine.stream", "stream::handle_request",
                "relay::LLMDeployment"))
            assert consumer["tokens"] == replica["items"] == relay["items"] == 5
            assert (spans["engine.stream"]["parent_id"]
                    == spans["stream::handle_request"]["parent_id"]
                    == spans["submit::handle_request"]["span_id"])
            assert (spans["relay::LLMDeployment"]["parent_id"]
                    == spans["ingress::LLMDeployment"]["span_id"])
            assert (replica["task_id"]
                    == spans["task::handle_request"]["args"]["task_id"])
            # `task::` ended when the method returned its generator; the
            # loop that ran it is the `stream::` span behind it
            task, stream = spans["task::handle_request"], \
                spans["stream::handle_request"]
            assert task["ts"] + task["dur"] <= stream["ts"] + 1e3
            assert 0 < replica["report_us_sum"] <= stream["dur"]
            prefill = spans["engine.prefill"]
            assert relay["first_write_ts"] >= prefill["ts"] + prefill["dur"] - 1e3
            # every item's ref carried its arrival stamp: an item is fetched
            # and written inside its lag, and lies there under the span's length
            assert relay["fetch_us_sum"] + relay["write_us_sum"] - 5 \
                <= relay["arrive_lag_us_sum"] \
                <= 5 * spans["relay::LLMDeployment"]["dur"]
            for key in ("fetch_us_sum", "write_us_sum"):
                assert 0 < relay[key] <= spans["relay::LLMDeployment"]["dur"]
        assert timeline.validate_chains(
            [s for t in got_ids for s in traces[t]], got_ids)
    finally:
        serve.shutdown()


def test_no_span_is_recorded_per_token(ray_start_regular):
    """A 64-token stream leaves as many spans under its trace id as a
    4-token one: the token's way out is counted, never spanned."""
    from ray_tpu import serve

    D = serve.deployment(LLMDeployment(PARAMS, CFG, num_slots=2, max_len=128))
    try:
        serve.run(D.bind())
        _, port = serve.start_http_proxy()
        _stream_over_http(port, 2)   # the first call's one-off spans
        (short, a), (long, b) = (_stream_over_http(port, n) for n in (4, 64))
        assert (len(a), len(b)) == (4, 64)
        want = {"ingress::LLMDeployment", "result::handle_request",
                "relay::LLMDeployment", "stream::handle_request",
                "engine.stream", *STAGES}
        traces = _traces_once_they_hold(
            [short, long], want,
            lambda tr: len(tr[short]) == len(tr[long]))
        names = {t: sorted(s["name"] for s in traces[t]) for t in (short, long)}
        assert names[short] == names[long], names
        relay = {t: next(s["args"] for s in traces[t]
                         if s["name"] == "relay::LLMDeployment")
                 for t in (short, long)}
        assert (relay[short]["items"], relay[long]["items"]) == (4, 64)
    finally:
        serve.shutdown()


def test_bare_handle_call_roots_its_own_trace(ray_start_regular):
    from ray_tpu import serve

    @serve.deployment
    def echo_ctx(_payload):
        return tracing.current_ctx()

    try:
        handle = serve.run(echo_ctx.bind())
        ctx = ray_tpu.get(handle.remote({}), timeout=60)
        assert ctx is not None
        route = next(e for e in tracing.get_events()
                     if e["name"] == "route::echo_ctx"
                     and e["trace_id"] == ctx[0])
        assert route["parent_id"] == ""   # no ingress: the route span roots it
    finally:
        serve.shutdown()


# ---- programs loaded ahead of their first call (models/programs.py)

from test_programs_ahead import cache_dir, events, life, make  # noqa: E402,F401 - fixtures


def test_spans_of_programs_loaded_ahead_say_so_and_carry_their_own_facts(cache_dir, events):
    """A replica's second life: the `xla.compile` spans of its listed
    programs come from the thread that traces and the lent main thread that
    reads, carry `ahead: true`, and each its OWN facts: the cache's answer
    and the read's cost of that program, the layers of the program that
    noted them (a prompt pass's never on the write that is lowered next).
    `programs.ahead` once a life, with its six arguments; a first life
    records neither."""
    own = ("_prefill_first", "_write_state", "decode_step")
    bare = lambda e: e["args"]["fun_name"].removeprefix("jit(").removesuffix(")")
    of = lambda name: [e for e in tracing.get_events() if e["name"] == name]
    cfg, params = make("HybridCache")
    life(cfg, params, as_actor=True)
    assert not of("programs.ahead")
    assert not [e for e in of("xla.compile") if "ahead" in e["args"]]
    callers = {e["tid"] for e in of("xla.compile") if bare(e) in own}
    assert len(callers) == 1

    life(cfg, params, as_actor=True)
    (said,) = of("programs.ahead")
    assert set(said["args"]) == {"listed", "loaded", "ready_at_first_call",
                                 "waited_us", "failed", "wall_us"}
    assert said["args"]["listed"] == said["args"]["loaded"] == 3
    assert int(said["dur"]) == said["args"]["wall_us"] > 0
    spans = [e for e in of("xla.compile") if bare(e) in own]
    assert sorted((bare(e), e["args"]["event"]) for e in spans) == sorted(
        (p, ev) for p in own for ev in ("jaxpr_trace_duration",
                                        "jaxpr_to_mlir_module_duration",
                                        "backend_compile_duration"))
    assert all(e["args"]["ahead"] is True for e in spans)
    reads = [e for e in spans if e["args"]["event"] == "backend_compile_duration"]
    tracer, reader = ({e["tid"] for e in spans} - {e["tid"] for e in reads},
                      {e["tid"] for e in reads})
    assert len(tracer) == len(reader) == 1 and tracer != reader
    assert reader == {threading.get_ident() % 100000}     # the lent main thread
    assert all(e["args"]["cache"] == "hit" and e["args"]["retrieval_us"] > 0
               for e in reads)
    for e in spans:   # a layered program's notes, on all three of its spans
        assert ("layers" in e["args"]) == (bare(e) != "_write_state"), e
    # and the main thread's own next program is not marked
    tracing.clear()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((3,)))
    mine = [e["args"] for e in of("xla.compile")]
    assert mine and not [a for a in mine if "ahead" in a or "layers" in a
                         or "retrieval_us" in a], mine
