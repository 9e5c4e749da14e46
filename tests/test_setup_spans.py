"""Set-up as spans of the program: a `TPU: 1` actor on a CPU host leaves, in
one timeline, `lease.tpu` -> `worker.spawn` > `worker.boot` ->
`actor.create::<Class>` in that order and paired by the worker's pid, and
`chip.open` inside the constructor exactly where the constructor opens the
backend: a worker whose user code leaves jax alone imports none, opens
nothing, and records none."""

import os
import sys
import time
import types

import pytest

import ray_tpu
from ray_tpu.core import chips
from ray_tpu.util import tracing

# two clocks' stamps of one instant on one host, and a reply's way back
SLACK_US = 50e3


@ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)
class Opens:
    def __init__(self):
        import jax

        self.devices = len(jax.devices())

    def facts(self):
        from jax._src import xla_bridge

        return {"pid": os.getpid(), "devices": self.devices,
                "backends": xla_bridge.backends.__qualname__,
                "finders": [type(f).__name__ for f in sys.meta_path]}


@ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)
class LeavesJaxAlone:
    def facts(self):
        bridge = sys.modules.get("jax._src.xla_bridge")
        return {"pid": os.getpid(), "jax": "jax" in sys.modules,
                "opened": bool(bridge and bridge.backends_are_initialized()),
                "finders": [type(f).__name__ for f in sys.meta_path]}


@pytest.fixture(scope="module")
def session():
    """Both actors in one session; the timeline as `shutdown()` keeps it."""
    tracing.clear()
    ray_tpu.init(num_cpus=4, resources={"TPU": 2})
    try:
        facts = ray_tpu.get([Opens.remote().facts.remote(),
                             LeavesJaxAlone.remote().facts.remote()])
        time.sleep(1.2)  # one flush of the workers' event buffers
    finally:
        ray_tpu.shutdown()
    spans = [e for e in ray_tpu.timeline() if e.get("ph") == "X"]
    return {"opens": facts[0], "alone": facts[1], "spans": spans,
            "info": ray_tpu.timeline_info()}


def _of(session, pid):
    """{stage: span} of worker `pid`: the raylet's spans name it in `args`."""
    out = {}
    for e in session["spans"]:
        key = e["name"].split("::", 1)[0]
        if key in ("lease.tpu", "worker.spawn") and e["args"]["pid"] == pid:
            out[key] = e
        elif key in ("worker.boot", "actor.create", "chip.open") and e["pid"] == pid:
            out[key] = e
    return out


@pytest.mark.parametrize("who", ["opens", "alone"])
def test_lease_spawn_boot_and_constructor_in_order_paired_by_pid(session, who):
    pid = session[who]["pid"]
    s = _of(session, pid)
    assert {"lease.tpu", "worker.spawn", "worker.boot", "actor.create"} <= set(s), s
    end = lambda e: e["ts"] + e["dur"]
    lease, spawn, boot, create = (s[k] for k in (
        "lease.tpu", "worker.spawn", "worker.boot", "actor.create"))
    # the raylet's two spans are its own process's, and tile: the lease ends
    # where the spawn begins
    assert lease["pid"] == spawn["pid"] != pid
    assert end(lease) == pytest.approx(spawn["ts"], abs=1.0)
    assert lease["args"]["chips"] == spawn["args"]["chips"] == 1
    assert len(lease["args"]["tpu_ids"]) == 1
    assert 0 <= lease["args"]["queued_us"] <= lease["dur"] + 1.0
    assert lease["args"]["holders_wait_us"] == 0.0  # a CPU host has no holder
    # the worker's boot starts at the raylet's stamp and lies inside the spawn
    assert boot["ts"] == pytest.approx(spawn["ts"], abs=1.0)
    assert end(boot) <= end(spawn) + SLACK_US
    # the interpreter and the imports are most of it; the rest is connecting
    assert boot["dur"] / 2 < boot["args"]["imports_us"] < boot["dur"]
    # the constructor runs once the worker is registered
    assert create["ts"] >= end(spawn) - SLACK_US
    assert create["name"] == "actor.create::" + \
        ("Opens" if who == "opens" else "LeavesJaxAlone")
    assert create["args"] == {"chips": 1}
    assert session["info"]["spans_dropped"] == session["info"]["spans_evicted"] == 0


def test_chip_open_lies_inside_the_constructor_that_opened_it(session):
    s = _of(session, session["opens"]["pid"])
    opened, create = s["chip.open"], s["actor.create"]
    assert opened["args"] == {"platform": "cpu", "device_kind": "cpu",
                              "devices": session["opens"]["devices"], "granted": 1}
    assert create["ts"] <= opened["ts"]
    assert opened["ts"] + opened["dur"] <= create["ts"] + create["dur"]
    assert opened["dur"] > 0
    # inert once the backend is open: jax's own function is back in its
    # module, and the finder left `sys.meta_path` when the module loaded
    assert session["opens"]["backends"] == "backends"
    assert "_BridgeFinder" not in session["opens"]["finders"]
    assert sum(e["name"] == "chip.open" for e in session["spans"]) == 1


def test_a_worker_whose_code_leaves_jax_alone_opens_nothing(session):
    alone = session["alone"]
    assert alone["jax"] is False and alone["opened"] is False
    # still armed: the finder waits for `jax._src.xla_bridge` and nothing else
    assert alone["finders"][0] == "_BridgeFinder"
    assert "chip.open" not in _of(session, alone["pid"])


def test_arming_where_the_backend_is_open_already_changes_nothing():
    """In THIS process jax is imported and its backend open (other tests):
    there is nothing left to time, and nothing is wrapped."""
    import jax
    from jax._src import xla_bridge

    jax.devices()
    before = xla_bridge.backends
    try:
        chips.time_chip_open(1)
        assert xla_bridge.backends is before
        assert not any(type(f).__name__ == "_BridgeFinder" for f in sys.meta_path)
    finally:
        chips._open_armed = False


def test_jax_has_the_names_the_timing_of_chip_open_reads():
    """`_wrap_backends` replaces `xla_bridge.backends` and reads three more
    names of that private module: a jax that renames one fails HERE, not in
    a chip worker (where the timing would stand aside and say so)."""
    from jax._src import xla_bridge

    for name in ("backends", "backends_are_initialized"):
        assert callable(getattr(xla_bridge, name)), name
    assert isinstance(xla_bridge._backends, dict)
    assert hasattr(xla_bridge, "_default_backend")


class _NoDevices:
    platform = "cpu"

    def local_devices(self):
        return []


def _bridge(**names):
    """A stand-in for `jax._src.xla_bridge` whose backends open at the
    first call of `backends()`."""
    bridge = types.SimpleNamespace(_backends={}, calls=0, **names)

    def backends():
        bridge.calls += 1
        bridge._backends["cpu"] = bridge._default_backend = _NoDevices()
        return bridge._backends

    bridge.backends = backends
    bridge.backends_are_initialized = lambda: bool(bridge._backends)
    return bridge


@pytest.mark.parametrize("fault", ["no_devices", "renamed"])
def test_a_timing_that_cannot_record_never_fails_the_opening(fault, capfd):
    """Tracing never fails what it traces: where the opened backend has no
    local device, or jax has renamed what is read, the user's call returns
    jax's own result, jax's function is back in its module, no span is
    recorded and one `[chips]` line says why."""
    bridge = _bridge()
    original = bridge.backends
    if fault == "renamed":
        del bridge.backends_are_initialized
    tracing.clear()
    chips._wrap_backends(bridge, 1)
    if fault == "no_devices":
        assert bridge.backends is not original  # armed
    assert bridge.backends() is bridge._backends and bridge.calls == 1
    assert bridge.backends is original
    assert not [e for e in tracing.get_events() if e["name"] == "chip.open"]
    err = capfd.readouterr().err
    assert err.count("[chips] `chip.open` not recorded:") == 1, err


def test_a_half_imported_bridge_is_left_alone(monkeypatch):
    """`jax._src.xla_bridge` in `sys.modules` but not executed yet (another
    thread is importing it): a finder would never fire and would stay at the
    head of `sys.meta_path` for the worker's life, so none is put there."""
    monkeypatch.setitem(sys.modules, "jax._src.xla_bridge",
                        types.ModuleType("jax._src.xla_bridge"))
    monkeypatch.setattr(chips, "_open_armed", False)
    before = list(sys.meta_path)
    chips.time_chip_open(1)
    assert sys.meta_path == before
