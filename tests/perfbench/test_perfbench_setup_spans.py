"""The ten per-layer metrics that split `setup_s` from inside the program
(`perfbench/lib/setup_spans.py`): each reader on a fixed span list with the
answer worked out by hand (the chip holder told from a second worker,
another worker's compiles and those of the window left out), the rule that a
partial trace is never a number, which cells their entries list (the
entries wait in `perfbench/tools/pr58/entries.json` for a `benchmark` PR), and
a training and a serving cell rehearsed on the CPU with `--trace 1`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

T_ASK = 1_000.0           # seconds; spans carry microseconds
T_OPEN = T_ASK + 60.0
WORKER = ["worker.lease_s", "worker.spawn_s", "worker.import_s",
          "worker.chip_open_s"]
COMPILE = ["compile.programs", "compile.trace_lower_s", "compile.cache_read_s",
           "compile.cache_misses", "compile.backend_s"]
NAMES = WORKER + ["actor.create_s"] + COMPILE
RAYLET, HOLDER, OTHER = 10, 20, 30   # pids


# BENCHMARK.json's serving and training cells, as the token path's test
# tells them apart (by their traffic file's driver)
SERVING, TRAINING = load_py(os.path.join(
    ROOT, "tests", "perfbench", "test_perfbench_token_path.py"))._cells()


def _span(name, start_s, dur_s, by, **args):
    """A span `start_s` seconds after the parent asked for the worker,
    recorded by process `by`."""
    return {"name": name, "ph": "X", "ts": 1e6 * (T_ASK + start_s),
            "dur": 1e6 * dur_s, "pid": by, "tid": 1, "args": args}


def _compile(start_s, fun, trace_s, lower_s, backend_s, pid=HOLDER, **cache):
    """A program's own three spans, back to back, named as jax names them:
    the trace by the function, the module by `jit(<function>)`."""
    ev = lambda e, name, t, d, **a: _span("xla.compile", t, d, pid, event=e,
                                          fun_name=name, **a)
    return [ev("jaxpr_trace_duration", fun[4:-1], start_s, trace_s),
            ev("jaxpr_to_mlir_module_duration", fun, start_s + trace_s, lower_s),
            ev("backend_compile_duration", fun, start_s + trace_s + lower_s,
               backend_s, **cache)]


def _worker(pid, asked_s, chips, platform="tpu", lease_s=0.5, holders_us=0.0):
    """One worker's set-up: the demand arrives `asked_s` after the ask, waits
    `lease_s` for its chips, the spawn takes 2.0 s (imports 1.5), the
    constructor starts at registration and opens the chips 3 s in, for 8 s."""
    t = asked_s + lease_s
    return [
        _span("lease.tpu", asked_s, lease_s, RAYLET, pid=pid, chips=chips,
              tpu_ids=list(range(chips)), queued_us=1e6 * lease_s - holders_us,
              holders_wait_us=holders_us),
        _span("worker.spawn", t, 2.0, RAYLET, pid=pid, chips=chips),
        _span("worker.boot", t, 2.0, pid, imports_us=1.5e6),
        _span("actor.create::Replica", t + 2.0, 40.0, pid, chips=chips),
        _span("chip.open", t + 5.0, 8.0, pid, platform=platform,
              device_kind="TPU v5 lite", devices=chips, granted=chips)]


def _run(serving=True, leave_out=(), info=None, extra=(), holder_chips=1):
    """The chip holder (pid 20) among two workers: the other (pid 30) was
    leased first, opened only the CPU backend and compiled a program of its
    own. The holder's programs: two read from the cache, one missed, one
    compiled without a cache, and one compiled INSIDE the window."""
    events = _worker(OTHER, 0.2, 1, platform="cpu") + \
        _worker(HOLDER, 1.0, holder_chips, lease_s=0.5, holders_us=0.3e6)
    events += _compile(14.0, "jit(other)", 1.0, 1.0, 9.0, pid=OTHER, cache="miss")
    events += _compile(16.0, "jit(build)", 0.25, 0.25, 0.5, cache="hit",
                       retrieval_us=0.4e6)
    events += _compile(20.0, "jit(prefill)", 1.0, 0.5, 1.5, cache="hit",
                       retrieval_us=1.35e6)
    events += _compile(25.0, "jit(decode_step)", 0.5, 0.5, 7.0, cache="miss")
    events += _compile(35.0, "jit(small)", 0.125, 0.125, 0.75, cache="off")
    events += _compile(70.0, "jit(late)", 1.0, 1.0, 5.0, cache="miss")
    events = [e for e in events
              if e["name"].split("::")[0] not in leave_out or e["pid"] != HOLDER
              and e["args"].get("pid") != HOLDER] + list(extra)
    run = {"t_ask": T_ASK, "t_device": T_ASK + 16.5, "t_open": T_OPEN,
           "seconds": 10.0,
           "device": {"platform": "tpu", "kind": "TPU v5 lite",
                      "count": holder_chips, "pid": HOLDER},
           "program_spans": {"events": events, "info": info or {
               "spans_dropped": 0, "spans_evicted": 0, "spans_buffered": 40}}}
    if serving:
        run["rows"] = run["window_rows"] = []
    return run


def _read(name, run):
    return load_py(os.path.join(ROOT, "perfbench", "metrics",
                                name + ".py")).read(run)


@pytest.mark.parametrize("name,expected", [
    ("worker.lease_s", 0.5),          # the holder's, not the other worker's 0.5 at 0.2 s
    ("worker.spawn_s", 2.0),
    ("worker.import_s", 1.5),
    ("worker.chip_open_s", 8.0),
    ("actor.create_s", 40.0),
    ("compile.programs", 4.0),        # `late` began in the window, `other` is pid 30's
    ("compile.trace_lower_s", 0.5 + 1.5 + 1.0 + 0.25),
    ("compile.cache_read_s", 0.4 + 1.35),
    ("compile.cache_misses", 1.0),
    ("compile.backend_s", 7.0 + 0.75),  # the miss and the uncached one
])
def test_reader_on_a_fixed_span_list(name, expected, capsys):
    assert _read(name, _run()) == pytest.approx(expected, abs=1e-9)
    out = capsys.readouterr().out
    # how the stages tile the outside reading: 0.5 + 2.0 + 3.0 + 8.0 of 16.5;
    # 1.0 s before the demand arrived, 2.0 s behind the open
    assert "chip holder pid 20" in out and "= 13.50 of the outside 16.50" in out
    assert "remainder 3.00: ask -> the demand's arrival 1.00" in out
    assert "first device 2.00" in out and "foreign holders 0.30" in out
    assert "4 programs before the window" in out and "2 cache hits read in 1.75" in out
    assert "longest cache reads: jit(prefill) 1.35, jit(build) 0.40" in out
    assert "missed the cache: jit(decode_step) 7.00" in out
    assert "(boot 2.00, imports 1.50 of it)" in out
    assert "actor.create::Replica 40.00 s; " in out


@pytest.mark.parametrize("name", NAMES)
def test_only_the_three_events_count_and_a_training_run_has_no_constructor_metric(name):
    """An `xla.compile` span of another event than a program's trace, lower
    and backend compile is not counted (a function traced INSIDE a program
    makes no span at all: `record_compiles`, held by
    `tests/test_engine_spans.py`). A training run (no `rows`) needs no
    `actor.create::` span, and reads the other nine as the serving run
    does."""
    other = [_span("xla.compile", 20.8, 0.1, HOLDER, fun_name="sin",
                   event="some_other_event")]
    serving, training = _run(extra=other), _run(serving=False, extra=other,
                                                leave_out=("actor.create",))
    assert _read(name, serving) == _read(name, _run())
    if name == "actor.create_s":
        assert _read(name, training) is None
    else:
        assert _read(name, training) == _read(name, serving)


@pytest.mark.parametrize("how", ["older_program", "dropped", "evicted",
                                 "no_lease.tpu", "no_worker.spawn",
                                 "no_worker.boot", "no_actor.create",
                                 "two_holders", "other_chips"])
def test_a_partial_trace_is_never_a_number(how, capsys):
    """A program older than these spans, a span the GCS lost, a stage of the
    chip holder without its span, two processes that opened the run's
    platform and neither is the report's, a lease for another number of
    chips: all ten give None, and the run's output says why, once."""
    if how == "older_program":
        run = _run(leave_out=("lease.tpu", "worker.spawn", "worker.boot",
                              "actor.create", "chip.open"))
    elif how in ("dropped", "evicted"):
        run = _run(info={"spans_dropped": int(how == "dropped"),
                         "spans_evicted": int(how == "evicted")})
    elif how == "two_holders":
        run = _run(extra=_worker(40, 2.0, 1))
        run["device"]["pid"] = 99
    elif how == "other_chips":
        run = _run(holder_chips=4)
        run["device"]["count"] = 1
    else:
        run = _run(leave_out=(how[3:],))
    assert [_read(n, run) for n in NAMES] == [None] * len(NAMES)
    out = capsys.readouterr().out
    assert out.count("[setup_spans] no reading") == 1, out
    assert "chip holder pid" not in out


def test_two_openers_of_the_platform_are_told_apart_by_the_reports_pid(capsys):
    run = _run(extra=_worker(40, 2.0, 1, lease_s=0.75))
    assert _read("worker.lease_s", run) == 0.5
    run["device"]["pid"] = 40
    del run["_setup_spans"]
    assert _read("worker.lease_s", run) == 0.75


def test_a_record_that_is_no_run_of_a_cell_reads_none_without_a_fetch():
    assert [_read(n, {"seconds": 1.0}) for n in NAMES] == [None] * len(NAMES)


def _entries():
    """The ten `per_layer` entries as a `benchmark` PR appends them. They are
    NOT in BENCHMARK.json yet: two tests the benchmark already has pin the
    end of `per_layer` and one cell's exact set of metrics, and only a
    `benchmark` PR may edit those (PERF.md section 7)."""
    with open(os.path.join(ROOT, "perfbench", "tools", "pr58", "entries.json")) as f:
        return json.load(f)["per_layer"]


@pytest.mark.parametrize("name", NAMES)
def test_every_entry_lists_the_cells_it_applies_to(name, tmp_path):
    """Each entry names its cells (`workloads`): nine for all but the
    constructor's, which the serving cells list; `program_span`, `lower`,
    moves `setup_s`, a layer PERF.md names, a file of its own; and a root
    with the entries appended (`tools/pr58/root.py`) reports each in exactly
    those cells and passes the manifest's own checks."""
    m = next(m for m in _entries() if m["name"] == name)
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["source"] == "program_span" and m["better"] == "lower"
    assert m["moves"] == "setup_s"
    assert m["unit"] == ("programs" if name in ("compile.programs",
                                                "compile.cache_misses") else "s")
    assert m["layer"] == ("compile" if name in COMPILE else
                          "replica" if name == "actor.create_s" else "lease to worker")
    assert 0 < len(m["layer"]) <= 200
    want = SERVING if name == "actor.create_s" else SERVING + TRAINING
    assert sorted(m["workloads"]) == sorted(want)
    assert os.path.isfile(os.path.join(ROOT, "perfbench", "metrics", name + ".py"))
    load_py(os.path.join(ROOT, "perfbench", "tools", "pr58", "root.py")).main(str(tmp_path))
    man = Manifest(str(tmp_path))
    for cell in SERVING + TRAINING:
        listed = name in {x["name"] for x in man.metrics_for(cell, "per_layer")}
        assert listed == (cell in want)
    assert load_py(os.path.join(ROOT, "tests", "perfbench",
                                "test_perfbench_manifest.py")).check_root(str(tmp_path)) == []
    # the outside twins stay as they are (retiring them is a benchmark PR's)
    assert {"worker.spawn_to_device_s", "compile.s", "engine.compiles_in_window"} \
        <= {x["name"] for x in man.data["per_layer"]}


@pytest.mark.parametrize("cell,names", [
    ("toy-train", WORKER + COMPILE), ("toy-serve-setup", NAMES)])
def test_cell_rehearsal_reports_all_that_apply(tmp_path, cell, names):
    """The toy cells through the real command on the CPU with `--trace 1`:
    the raylet's and the worker's set-up spans cross two processes and a
    shutdown, and every reader that applies finds them."""
    cells = load_py(os.path.join(ROOT, "tests", "perfbench",
                                 "test_perfbench_cells.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = cells._throw_away_root(tmp_path, serve="toy-serve-setup")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [
        dict(m, workloads=["toy-train", "toy-serve-setup"][m["name"] == "actor.create_s":])
        for m in _entries() if m["name"] not in have]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    p = subprocess.run(
        [sys.executable, cells.RUN, "--root", root,
         "--workload", cell, "--seed", str(2**31 + 58), "--seconds", "2",
         "--trace", "1", "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = cells._would_report(p.stdout)
    assert rep["correct"] is True and rep["failed"] == 0
    assert set(names) <= set(rep["metrics"]), (sorted(rep["metrics"]),
                                               p.stdout[-2000:])
    assert ("actor.create_s" in rep["metrics"]) == (cell != "toy-train")
    m = {n: rep["metrics"][n]["value"] for n in names}
    assert all(v >= 0 for v in m.values()), m
    assert json.dumps(m)   # plain numbers
    said = [l for l in p.stdout.splitlines() if "[setup_spans] chip holder" in l]
    assert len(said) == 1 and "of the outside" in said[0]
    # the stages lie inside the outside reading, and leave little of it over
    outside = rep["metrics"]["worker.spawn_to_device_s"]["value"]
    staged = m["worker.lease_s"] + m["worker.spawn_s"] + m["worker.chip_open_s"]
    assert m["worker.import_s"] <= m["worker.spawn_s"] and staged <= outside
    assert m["compile.programs"] >= 3
    # no nested event twice: the own spans sum to no more than jax's events
    own = m["compile.trace_lower_s"] + m["compile.cache_read_s"] + m["compile.backend_s"]
    assert own <= rep["metrics"]["compile.s"]["value"] + 1e-6
    if cell != "toy-train":
        assert m["actor.create_s"] >= m["worker.chip_open_s"]
