"""End-to-end tests for tasks, objects, and actors on a single node.

Models the reference's `python/ray/tests/test_basic.py` coverage.
"""

import time

import numpy as np
import pytest

import ray_tpu


def test_task_roundtrip(ray_start_regular):
    @ray_tpu.remote
    def f(x):
        return x + 1

    assert ray_tpu.get(f.remote(1)) == 2


def test_task_parallel_many(ray_start_regular):
    @ray_tpu.remote
    def sq(x):
        return x * x

    refs = [sq.remote(i) for i in range(20)]
    assert ray_tpu.get(refs) == [i * i for i in range(20)]


def test_task_args_refs(ray_start_regular):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    r1 = add.remote(1, 2)
    r2 = add.remote(r1, 10)  # ref as arg resolves to its value
    assert ray_tpu.get(r2) == 13


def test_task_kwargs_and_multiple_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=2)
    def divmod_(a, b=3):
        return a // b, a % b

    q, r = divmod_.remote(10)
    assert ray_tpu.get([q, r]) == [3, 1]


def test_put_get_small_and_large(ray_start_regular):
    small = {"k": 1}
    assert ray_tpu.get(ray_tpu.put(small)) == small

    big = np.random.rand(1 << 18)  # 2 MiB -> plasma path
    out = ray_tpu.get(ray_tpu.put(big))
    np.testing.assert_array_equal(out, big)


def test_large_task_arg_and_return(ray_start_regular):
    big = np.arange(1 << 18, dtype=np.float64)

    @ray_tpu.remote
    def double(x):
        return x * 2

    out = ray_tpu.get(double.remote(big))
    np.testing.assert_array_equal(out, big * 2)


def test_task_error_propagates(ray_start_regular):
    @ray_tpu.remote
    def boom():
        raise ValueError("kapow")

    with pytest.raises(ValueError, match="kapow"):
        ray_tpu.get(boom.remote())


def test_get_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        time.sleep(30)

    with pytest.raises(ray_tpu.GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_wait(ray_start_regular):
    @ray_tpu.remote
    def delay(t):
        time.sleep(t)
        return t

    ray_tpu.get([delay.remote(0), delay.remote(0)])  # warm up two workers
    fast = delay.remote(0.05)
    slow = delay.remote(5)
    ready, pending = ray_tpu.wait([fast, slow], num_returns=1, timeout=3)
    assert ready == [fast]
    assert pending == [slow]


def test_nested_tasks(ray_start_regular):
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        import ray_tpu as rt

        return rt.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(10)) == 21


def test_actor_basic(ray_start_regular):
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def incr(self, k=1):
            self.n += k
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    assert ray_tpu.get(c.incr.remote()) == 11
    assert ray_tpu.get(c.incr.remote(5)) == 16
    assert ray_tpu.get(c.value.remote()) == 16


def test_actor_ordering(ray_start_regular):
    @ray_tpu.remote
    class Log:
        def __init__(self):
            self.items = []

        def append(self, x):
            self.items.append(x)

        def get(self):
            return self.items

    log = Log.remote()
    for i in range(50):
        log.append.remote(i)
    assert ray_tpu.get(log.get.remote()) == list(range(50))


def test_actor_error(ray_start_regular):
    @ray_tpu.remote
    class A:
        def bad(self):
            raise RuntimeError("actor oops")

        def good(self):
            return "fine"

    a = A.remote()
    with pytest.raises(RuntimeError, match="actor oops"):
        ray_tpu.get(a.bad.remote())
    # actor survives method errors
    assert ray_tpu.get(a.good.remote()) == "fine"


def test_named_actor(ray_start_regular):
    @ray_tpu.remote
    class Svc:
        def ping(self):
            return "pong"

    Svc.options(name="svc1").remote()
    h = ray_tpu.get_actor("svc1")
    assert ray_tpu.get(h.ping.remote()) == "pong"


def test_kill_actor(ray_start_regular):
    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    ray_tpu.kill(a)
    time.sleep(0.5)
    with pytest.raises((ray_tpu.ActorDiedError, ray_tpu.ActorError, ray_tpu.RayTpuError)):
        ray_tpu.get(a.ping.remote(), timeout=10)


def test_actor_handle_passing(ray_start_regular):
    @ray_tpu.remote
    class Store:
        def __init__(self):
            self.v = None

        def set(self, v):
            self.v = v

        def get(self):
            return self.v

    @ray_tpu.remote
    def writer(store, v):
        import ray_tpu as rt

        rt.get(store.set.remote(v))
        return True

    s = Store.remote()
    assert ray_tpu.get(writer.remote(s, 42))
    assert ray_tpu.get(s.get.remote()) == 42


def test_cluster_resources(ray_start_regular):
    total = ray_tpu.cluster_resources()
    assert total.get("CPU") == 4.0
    assert total.get("TPU") == 8.0


def test_dropped_ref_frees_object_after_completion(ray_start_regular):
    """A counted ref GC'd while its task is still pending must still free
    the object once the result reports (the pending guard in _maybe_free
    defers, rpc_report_task_result re-checks)."""
    import time

    import numpy as np

    from ray_tpu.core.worker import current_worker

    @ray_tpu.remote
    def big():
        import time as t

        t.sleep(0.3)
        return np.ones(1 << 19)  # ~4 MiB -> plasma

    r = big.remote()
    oid = r.id
    del r  # dies while the task is pending
    w = current_worker()
    deadline = time.monotonic() + 30
    present = True
    while time.monotonic() < deadline:
        with w._obj_lock:
            present = oid in w._objects
        if not present:
            break
        time.sleep(0.1)
    assert not present, "owner table leaked an object dropped while pending"


def test_dead_borrower_releases_object(ray_start_regular):
    """Borrows are connection-scoped (reference WaitForRefRemoved liveness):
    killing a borrower actor releases its borrow, so the owner can free the
    object once its own refs are gone — a died borrower no longer pins
    objects forever."""
    import time

    import numpy as np

    from ray_tpu.core.worker import current_worker

    @ray_tpu.remote
    class Holder:
        def hold(self, wrapped):
            self.kept = wrapped  # keeps the nested ref (a borrow) alive
            return True

    big = ray_tpu.put(np.ones(1 << 17))  # ~1 MiB -> plasma, driver-owned
    oid = big.id
    h = Holder.remote()
    assert ray_tpu.get(h.hold.remote([big]), timeout=60)

    w = current_worker()
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        with w._obj_lock:
            if w._objects[oid].borrowers >= 1:
                break
        time.sleep(0.1)
    with w._obj_lock:
        assert w._objects[oid].borrowers >= 1, "borrow never registered"

    del big  # owner's local ref gone; the actor's borrow keeps it alive
    time.sleep(1.0)
    with w._obj_lock:
        assert oid in w._objects, "freed while still borrowed"

    ray_tpu.kill(h)  # borrower dies -> its connection drops -> borrow released
    deadline = time.monotonic() + 30
    present = True
    while time.monotonic() < deadline:
        with w._obj_lock:
            present = oid in w._objects
        if not present:
            break
        time.sleep(0.2)
    assert not present, "dead borrower's borrow was never released"


def test_nested_ref_survives_container_lifetime(ray_start_regular):
    """A ref nested inside a stored object must stay alive as long as the
    container does — a reader may deserialize (and only then register its
    borrow) long after every direct ref died (reference nested-ref tracking,
    reference_count.h:834; here: container pins, worker._maybe_free)."""
    from ray_tpu.core.config import get_config

    cfg = get_config()
    old = cfg.object_free_grace_period_ms
    cfg.object_free_grace_period_ms = 20
    try:
        inner = ray_tpu.put(np.arange(1 << 15, dtype=np.int64))  # plasma-sized
        container = ray_tpu.put([inner])
        inner_sum = int(np.arange(1 << 15, dtype=np.int64).sum())
        del inner  # owner's last direct local ref dies here
        # far past even the extended (10x) lineage-less grace window
        time.sleep(1.0)
        [got] = ray_tpu.get(container)
        assert int(ray_tpu.get(got).sum()) == inner_sum
    finally:
        cfg.object_free_grace_period_ms = old


def test_app_pubsub_channel(ray_start_regular):
    """Generic application pubsub: subscribe_channel + publish fan-out
    (backs Serve's push-driven handle refresh)."""
    import threading

    from ray_tpu.core.api import _global_worker

    got = []
    ev = threading.Event()

    def cb(msg):
        got.append(msg)
        ev.set()

    w = _global_worker()
    w.subscribe_channel("test_app_channel", cb)
    w.publish("test_app_channel", {"hello": 1})
    assert ev.wait(5), "pubsub push did not arrive"
    assert got[0] == {"hello": 1}
    w.unsubscribe_channel("test_app_channel", cb)


def test_returned_nested_ref_survives_container_lifetime(ray_start_regular):
    """Refs nested in a TASK RETURN get the same container protection as
    put(): the caller (container owner) holds a borrow on executor-owned
    inner objects until the container dies, so a reader deserializing the
    return long after the executor dropped its local refs still gets the
    object (reference nested-ref tracking, reference_count.h:834)."""

    @ray_tpu.remote
    class Holder:
        def make(self):
            r = ray_tpu.put(np.arange(1 << 15, dtype=np.int64))
            return [r]  # actor-owned ref escapes inside the return value

    # tiny grace on the ACTOR (inner-object owner): only the caller's
    # borrow can be keeping the inner object alive below
    h = Holder.options(runtime_env={
        "env_vars": {"RAY_TPU_OBJECT_FREE_GRACE_PERIOD_MS": "20"}}).remote()
    container = h.make.remote()
    ready, _ = ray_tpu.wait([container], num_returns=1, timeout=30)
    assert ready
    time.sleep(1.5)  # far past the actor-side (even 10x) grace window
    [inner] = ray_tpu.get(container)
    assert int(ray_tpu.get(inner).sum()) == int(
        np.arange(1 << 15, dtype=np.int64).sum())


def test_actor_concurrency_groups(ray_start_regular, tmp_path):
    """Concurrency groups (reference actor.py:65,82): a method annotated
    into a named group runs on that group's dedicated threads, so it
    completes while a default-pool call is still blocking; call-site
    .options(concurrency_group=...) overrides too."""
    import os

    flag = str(tmp_path / "unblock")

    @ray_tpu.remote(concurrency_groups={"io": 1})
    class Server:
        def blocker(self, path):
            import time as _t

            t0 = _t.time()
            while not os.path.exists(path) and _t.time() - t0 < 30:
                _t.sleep(0.05)
            return "unblocked"

        @ray_tpu.method(concurrency_group="io")
        def ping(self):
            return "pong"

        def plain(self):
            return "plain"

    s = Server.remote()
    blocked = s.blocker.remote(flag)
    time.sleep(0.3)  # let blocker occupy the single default thread
    # annotated method rides the io pool: completes despite the blocker
    assert ray_tpu.get(s.ping.remote(), timeout=10) == "pong"
    # unannotated method, call-site override onto the io pool
    assert ray_tpu.get(
        s.plain.options(concurrency_group="io").remote(), timeout=10) \
        == "plain"
    with open(flag, "w"):
        pass
    assert ray_tpu.get(blocked, timeout=30) == "unblocked"


def test_max_calls_recycles_worker(ray_start_regular):
    """A function with max_calls=2 never runs more than twice in one worker
    process (reference remote_function.py _max_calls worker recycling)."""
    import time

    @ray_tpu.remote(max_calls=2)
    def whoami():
        import os

        return os.getpid()

    pids = [ray_tpu.get(whoami.remote(), timeout=60) for _ in range(6)]
    from collections import Counter

    counts = Counter(pids)
    assert max(counts.values()) <= 2, counts
    assert len(counts) >= 3


def test_max_calls_results_survive_recycling(ray_start_regular):
    @ray_tpu.remote(max_calls=1)
    def val(i):
        return i * 10

    refs = [val.remote(i) for i in range(4)]
    assert ray_tpu.get(refs, timeout=120) == [0, 10, 20, 30]


def test_tpu_and_gpu_id_accessors(ray_start_regular):
    """get_gpu_ids() is always [] (TPU framework); get_tpu_ids() returns
    raylet-granted chip indices: DISJOINT across concurrent tasks, held
    for an actor's lifetime, a whole index even for a fractional demand
    (two processes cannot share a chip)."""
    import time

    assert ray_tpu.get_gpu_ids() == []

    @ray_tpu.remote(num_tpus=2)
    def on_tpus():
        import time as _t

        ids = ray_tpu.get_tpu_ids()
        _t.sleep(1.0)  # overlap the two tasks so grants must be disjoint
        return ids, ray_tpu.get_gpu_ids()

    r1, r2 = on_tpus.remote(), on_tpus.remote()
    (ids1, gpus), (ids2, _) = ray_tpu.get([r1, r2], timeout=120)
    assert len(ids1) == 2 and len(ids2) == 2 and gpus == []
    assert not (set(ids1) & set(ids2)), (ids1, ids2)

    @ray_tpu.remote
    def plain():
        return ray_tpu.get_tpu_ids()

    assert ray_tpu.get(plain.remote(), timeout=60) == []

    @ray_tpu.remote(num_tpus=1)
    class Holder:
        def ids(self):
            return ray_tpu.get_tpu_ids()

    h = Holder.remote()
    a = ray_tpu.get(h.ids.remote(), timeout=60)
    assert len(a) == 1 and a == ray_tpu.get(h.ids.remote(), timeout=60)
    ray_tpu.kill(h)

    @ray_tpu.remote(num_tpus=0.5)
    def frac():
        return ray_tpu.get_tpu_ids()

    assert len(ray_tpu.get(frac.remote(), timeout=60)) == 1


def _env_reporter():
    """A function (pickled by value: workers cannot import this module) that
    says how its worker was started — no jax involved."""

    def report():
        import os

        keys = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "RAY_TPU_WORKER_FORKED",
                "JAX_COMPILATION_CACHE_DIR", "TPU_PROCESS_BOUNDS")
        return {"pid": os.getpid(), "tpu_ids": ray_tpu.get_tpu_ids(),
                **{k: os.environ.get(k) for k in keys}}

    return report


def _wait_for(cond, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition not reached in time"
        time.sleep(0.05)


@pytest.mark.parametrize("operator_platform", [None, "cpu"])
def test_tpu_lease_worker_is_cold_spawned_for_its_grant(monkeypatch,
                                                        operator_platform):
    """A lease that demands TPU gets a worker STARTED for that grant: cold
    (never a template fork), the granted chips and no others visible to
    libtpu, JAX_PLATFORMS left to the operator (none set -> `tpu`, so jax
    raises without a chip instead of computing on the CPU), a fixed compile
    cache. A lease without TPU still forks warm with JAX_PLATFORMS=cpu."""
    import os

    from ray_tpu.core import api
    from ray_tpu.core.config import reset_config

    if operator_platform is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", operator_platform)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    reset_config()
    ray_tpu.init(num_cpus=4, resources={"TPU": 2})
    env_report = _env_reporter()
    try:
        pool = api._node.raylet._worker_pool

        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def report(self):
                return env_report()

        cold_before = pool.stats()["registered_cold"]
        h = Holder.remote()
        rep = ray_tpu.get(h.report.remote(), timeout=60)
        assert rep["RAY_TPU_WORKER_FORKED"] is None
        assert pool.stats()["registered_cold"] == cold_before + 1
        assert len(rep["tpu_ids"]) == 1
        assert rep["TPU_VISIBLE_CHIPS"] == str(rep["tpu_ids"][0])
        assert rep["TPU_PROCESS_BOUNDS"] == "1,1,1"  # 1 chip of the node's 2
        assert rep["JAX_PLATFORMS"] == (operator_platform or "tpu")
        assert rep["JAX_COMPILATION_CACHE_DIR"].endswith(".jax_compile_cache")

        @ray_tpu.remote
        def plain():
            return env_report()

        cpu = ray_tpu.get(plain.remote(), timeout=60)
        assert cpu["RAY_TPU_WORKER_FORKED"] == "1"
        assert cpu["JAX_PLATFORMS"] == "cpu"
        assert cpu["TPU_VISIBLE_CHIPS"] is None and cpu["tpu_ids"] == []
        ray_tpu.kill(h)
    finally:
        ray_tpu.shutdown()
        reset_config()


def test_tpu_worker_serves_one_lease_and_chip_frees_after_exit(
        ray_start_regular):
    """A TPU task's process is never handed a lease without that grant: it
    retires after its task. Chips go back to the free list only once the
    holding PROCESS is gone, and a lease that finds none waits."""
    import os
    import subprocess

    from ray_tpu.core import api
    from ray_tpu.core.raylet import WorkerHandle

    raylet = api._node.raylet
    env_report = _env_reporter()

    @ray_tpu.remote(num_tpus=1)
    def on_chip():
        return env_report()

    @ray_tpu.remote
    def plain():
        return env_report()["pid"]

    first = ray_tpu.get(on_chip.remote(), timeout=60)
    later = ray_tpu.get([plain.remote() for _ in range(8)], timeout=60)
    assert first["pid"] not in later
    _wait_for(lambda: not os.path.exists(f"/proc/{first['pid']}"))
    _wait_for(lambda: len(raylet._free_chips) == 8)

    # the grant outlives the lease until the process has exited
    with raylet._lock:
        ids = raylet._assign_tpus(8.0)
    assert ids == list(range(8))
    waiting = on_chip.remote()  # resources fit, no chip is free: it waits
    proc = subprocess.Popen(["sleep", "1.5"])
    holder = WorkerHandle(worker_id=None, conn=None, address="", pid=proc.pid,
                          proc=proc, tpu_grant=ids)
    raylet._release_chips_on_exit(holder)
    assert holder.tpu_grant is None
    time.sleep(0.5)
    assert proc.poll() is None and raylet._free_chips == []
    done, _ = ray_tpu.wait([waiting], timeout=0.1)
    assert not done
    got = ray_tpu.get(waiting, timeout=60)  # runs once the holder is gone
    assert proc.poll() is not None
    assert len(got["tpu_ids"]) == 1


class _SlowToDie:
    """A stand-in for a worker's `Popen` whose process takes `seconds` to
    go once it is told to, whatever the signal: a SIGKILLed worker with
    four chips open is still closing them when `kill()` has returned."""

    def __init__(self, seconds):
        import subprocess
        import sys

        self._proc = subprocess.Popen([sys.executable, "-c", (
            "import signal, sys, time\n"
            f"signal.signal(signal.SIGTERM, lambda *a: (time.sleep({seconds}), sys.exit(0)))\n"
            "print('up', flush=True)\ntime.sleep(60)")], stdout=subprocess.PIPE)
        assert self._proc.stdout.readline().strip() == b"up"
        self.pid = self._proc.pid
        self.poll, self.wait = self._proc.poll, self._proc.wait
        self.terminate = self._proc.terminate

    def kill(self):
        pass  # the signal is sent; the process is not gone yet

    def really_kill(self):
        self._proc.kill()
        self._proc.wait()


@pytest.mark.parametrize("holds_chips", [True, False])
def test_stop_returns_once_the_chip_holders_are_gone(holds_chips):
    """`shutdown()` returning means the chips can be opened: `stop()` waits
    for a worker that was spawned for a chip grant until its PROCESS is
    gone (here 3 s after SIGTERM, past the 2 s grace and the SIGKILL),
    within its bound. A worker without chips keeps the old rule: 2 s of
    grace, a SIGKILL, and no further wait."""
    from ray_tpu.core import api
    from ray_tpu.core.ids import WorkerID
    from ray_tpu.core.raylet import WorkerHandle

    ray_tpu.init(num_cpus=2, resources={"TPU": 8})
    raylet = api._node.raylet
    proc = _SlowToDie(3)
    try:
        wid = WorkerID.from_random()
        with raylet._lock:
            raylet._workers[wid] = WorkerHandle(
                worker_id=wid, conn=None, address="", pid=proc.pid, proc=proc,
                tpu_grant=[0] if holds_chips else None)
            if holds_chips:  # as `_launch_worker` records a lease's process
                raylet._chip_procs.append(proc)
        t0 = time.monotonic()
        ray_tpu.shutdown()
        took = time.monotonic() - t0
        if holds_chips:
            assert proc.poll() is not None, "stop() returned before the holder was gone"
            assert 2.9 < took < 10
        else:
            assert proc.poll() is None and took < 2.9
    finally:
        ray_tpu.shutdown()
        proc.really_kill()


def _hold_open(path):
    """A child process that holds `path` open until it is killed."""
    import subprocess
    import sys

    p = subprocess.Popen([sys.executable, "-c", (
        f"import time; f = open({str(path)!r}); print('up', flush=True); "
        "time.sleep(60)")], stdout=subprocess.PIPE)
    assert p.stdout.readline().strip() == b"up"
    return p


@pytest.fixture
def fake_dev(tmp_path, monkeypatch):
    """A directory standing for `/dev`, with eight accel nodes."""
    from ray_tpu.core import chips

    for i in range(8):
        (tmp_path / f"accel{i}").touch()
    monkeypatch.setattr(chips, "_DEV", str(tmp_path))
    return tmp_path


@pytest.mark.parametrize("groups,tpu_ids,want", [
    (["vfio", "0", "1", "2", "3"], [2], ["vfio/2"]),
    (["vfio", "7", "11", "9"], [0, 2], ["vfio/7", "vfio/11"]),  # i-th group, by number
    (["vfio", "0"], [0, 1], ["vfio/0"]),
    ([], [0], []),
])
def test_a_chip_maps_to_its_device_node(tmp_path, monkeypatch, groups, tpu_ids, want):
    from ray_tpu.core import chips

    if groups:
        (tmp_path / "vfio").mkdir()
    for g in groups:
        (tmp_path / "vfio" / g).touch()
    monkeypatch.setattr(chips, "_DEV", str(tmp_path))
    assert chips.chip_nodes(tpu_ids) == [str(tmp_path / w) for w in want]
    assert chips.chip_holders(tpu_ids) == {}


def test_the_probe_names_who_holds_a_chip(fake_dev):
    from ray_tpu.core import chips

    holder = _hold_open(fake_dev / "accel2")
    try:
        node = str(fake_dev / "accel2")
        assert chips.chip_holders([2]) == {node: holder.pid}
        assert chips.chip_holders([0, 1, 2, 3]) == {node: holder.pid}
        assert chips.chip_holders([0, 1, 3]) == {}           # a neighbour's chip
        assert chips.chip_holders([2], ours={holder.pid}) == {}  # our own child
        with open(node):  # this process's own descriptor is not a holder
            assert chips.chip_holders([2], ours={holder.pid}) == {}
    finally:
        holder.kill()
        holder.wait()
    assert chips.chip_holders([2]) == {}


def test_the_probe_sees_a_holder_that_is_exiting(tmp_path, monkeypatch):
    """A process that is exiting lists no descriptor and is still closing
    the device: a vfio group then refuses to be opened (EBUSY), and reads
    as held by a pid that is not known. A group some process lists is not
    opened, and an accel node never is."""
    import errno
    import os

    from ray_tpu.core import chips

    (tmp_path / "vfio").mkdir()
    for name in ("vfio/vfio", "vfio/0", "vfio/1", "vfio/2", "accel0"):
        (tmp_path / name).touch()
    monkeypatch.setattr(chips, "_DEV", str(tmp_path))
    real, opened = os.open, []

    def busy_open(path, *a, **kw):
        if str(path).startswith(str(tmp_path)):
            opened.append(os.path.relpath(path, tmp_path))
            if path.endswith("vfio/2"):
                raise OSError(errno.EBUSY, "Device or resource busy")
        return real(path, *a, **kw)

    holder = _hold_open(tmp_path / "vfio" / "1")
    try:
        monkeypatch.setattr(os, "open", busy_open)
        assert chips.chip_holders([0, 1, 2]) == {
            str(tmp_path / "vfio/1"): holder.pid, str(tmp_path / "vfio/2"): 0}
        assert opened == ["vfio/0", "vfio/2"]
    finally:
        holder.kill()
        holder.wait()


@pytest.mark.parametrize("held,starts", [("accel0", "after_the_holder"),
                                         ("accel5", "at_once"),
                                         ("accel0", "never")])
def test_a_lease_waits_for_a_foreign_holder_of_its_chips(
        ray_start_regular, fake_dev, monkeypatch, capfd, held, starts):
    """Chips that are free in the raylet's books but held open by a process
    it did not spawn (the worker of a session that has just ended): the
    lease is granted and NOT started while the holder lives, starts within
    a second of its exit and says so on stderr; a held chip outside the
    grant delays nothing; past the bound the lease fails, naming the pid."""
    from ray_tpu.core import api
    from ray_tpu.core.exceptions import WorkerCrashedError

    raylet = api._node.raylet
    if starts == "never":
        monkeypatch.setattr(raylet, "_CHIP_WAIT_S", 1.0)
    env_report = _env_reporter()

    @ray_tpu.remote(num_tpus=1, max_retries=0)
    def on_chip():
        return env_report()

    holder = _hold_open(fake_dev / held)
    try:
        ref = on_chip.remote()
        if starts == "at_once":
            assert ray_tpu.get(ref, timeout=60)["tpu_ids"] == [0]
            assert "[chips] waited" not in capfd.readouterr().err
            return
        _wait_for(lambda: raylet._tpu_leases)
        if starts == "never":
            with pytest.raises(WorkerCrashedError, match=f"pid {holder.pid} still "
                               f"holds {fake_dev / held} open"):
                ray_tpu.get(ref, timeout=30)
            _all_chips_back(raylet)
            return
        time.sleep(1.0)
        lease = raylet._tpu_leases[0]
        assert lease.tpu_ids == [0] and lease.proc is None  # granted, not started
        holder.kill()
        holder.wait()
        t0 = time.monotonic()
        _wait_for(lambda: not raylet._tpu_leases or raylet._tpu_leases[0].proc)
        assert time.monotonic() - t0 < 1.0
        assert ray_tpu.get(ref, timeout=60)["tpu_ids"] == [0]
        err = capfd.readouterr().err
        assert f"for pid {holder.pid} to release {fake_dev / held}" in err
    finally:
        holder.kill()
        holder.wait()


def _stall_tpu_spawns(monkeypatch, fail=False):
    """TPU workers spawned from here on never register (`sleep`), or do not
    spawn at all: the lease stays granted with its worker 'starting'."""
    import subprocess

    real = subprocess.Popen

    def popen(argv, *a, env=None, **kw):
        if env is not None and env.get("TPU_VISIBLE_CHIPS") is not None:
            if fail:
                raise OSError(12, "Cannot allocate memory")
            argv = ["sleep", "60"]
        return real(argv, *a, env=env, **kw)

    monkeypatch.setattr(subprocess, "Popen", popen)


def _all_chips_back(raylet):
    _wait_for(lambda: len(raylet._free_chips) == 8)
    assert raylet._tpu_leases == []
    assert raylet.resources_available["TPU"] == 8.0


@pytest.mark.parametrize("how", ["cancel", "reap_job", "kill_actor", "fence"])
def test_lease_with_a_starting_worker_can_be_taken_back(
        ray_start_regular, monkeypatch, how):
    """Between the grant and the worker's registration (~10 s on a chip) a
    TPU lease is in neither the queue nor `_workers`. A cancel, a job reap,
    an actor kill and a fence must still find it: the starting process is
    killed, the charge undone, and the chips freed once it is gone."""
    import os

    from ray_tpu.core import api
    from ray_tpu.core.exceptions import TaskCancelledError

    raylet = api._node.raylet
    _stall_tpu_spawns(monkeypatch)

    @ray_tpu.remote(num_tpus=2, max_retries=0)
    def on_chip():
        return 1

    @ray_tpu.remote(num_tpus=2)
    class Holder:
        def ok(self):
            return True

    if how == "kill_actor":
        target = Holder.remote()
    else:
        ref = on_chip.remote()
    _wait_for(lambda: any(l.proc is not None for l in raylet._tpu_leases))
    lease = raylet._tpu_leases[0]
    assert lease.tpu_ids == [0, 1] and raylet._free_chips == list(range(2, 8))
    pid = lease.proc.pid
    if how == "cancel":
        ray_tpu.cancel(ref)
        with pytest.raises(TaskCancelledError):
            ray_tpu.get(ref, timeout=30)
    elif how == "reap_job":
        out = raylet.rpc_reap_job(None, 0, {"job_id": lease.spec.job_id.binary()})
        assert out["queued_cancelled"] == 1
    elif how == "kill_actor":
        ray_tpu.kill(target)
    else:
        freed = []
        real = raylet._release_chips_after
        monkeypatch.setattr(
            raylet, "_release_chips_after", lambda proc, ids: (
                freed.append((proc, ids, list(raylet._free_chips))),
                real(proc, ids)))
        raylet._do_self_fence("test")
        # held chips were not declared free ahead of their process's exit
        assert freed == [(lease.proc, [0, 1], list(range(2, 8)))]
        with pytest.raises(Exception):  # its owner hears the worker died
            ray_tpu.get(ref, timeout=30)
    def gone():   # reaped between the two looks: gone too
        try:
            return open(f"/proc/{pid}/stat").read().split()[2] == "Z"
        except FileNotFoundError:
            return True

    _wait_for(gone)
    _all_chips_back(raylet)


def test_failed_spawn_fails_the_tpu_lease(ray_start_regular, monkeypatch):
    """The task was popped, charged and given chips before its worker is
    spawned; a spawn that raises must not lose all three and hang the
    owner."""
    from ray_tpu.core import api

    _stall_tpu_spawns(monkeypatch, fail=True)

    @ray_tpu.remote(num_tpus=1, max_retries=0)
    def on_chip():
        return 1

    with pytest.raises(Exception, match="(?i)worker|died|crash"):
        ray_tpu.get(on_chip.remote(), timeout=30)
    _all_chips_back(api._node.raylet)


def test_group_removed_under_a_live_actor_leaks_nothing(ray_start_regular):
    """A trainer kills its workers and removes their placement group in one
    breath: the bundle comes back while the actor is still charged inside
    it. What remained returns with the bundle, the charge when the worker
    is gone — it used to be dropped, and the node lost the chips for good."""
    from ray_tpu.core.placement_group import (placement_group,
                                              remove_placement_group)

    @ray_tpu.remote
    class Holder:
        def ok(self):
            return True

    before = ray_tpu.available_resources()
    pg = placement_group([{"TPU": 4.0, "CPU": 1.0}], strategy="STRICT_PACK")
    assert pg.ready(timeout=30)
    h = Holder.options(placement_group=pg, placement_group_bundle_index=0,
                       num_cpus=1, resources={"TPU": 4.0}).remote()
    assert ray_tpu.get(h.ok.remote(), timeout=60)
    ray_tpu.kill(h)
    remove_placement_group(pg)
    _wait_for(lambda: ray_tpu.available_resources().get("TPU") == before["TPU"]
              and ray_tpu.available_resources().get("CPU") == before["CPU"])
    again = placement_group([{"TPU": 8.0}], strategy="STRICT_PACK")
    assert again.ready(timeout=30)
    remove_placement_group(again)
