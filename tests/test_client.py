"""Remote-driver client mode ("ray://") against a client server subprocess.

Mirrors the reference's Ray Client tests (python/ray/tests/test_client.py):
the cluster + client server live in a separate process; this process
connects with `ray_tpu.init(address="ray://...")` and uses the normal API.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def client_server():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu", "client-server",
         "--num-cpus", "4", "--resources", '{"TPU": 8}'],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd="/tmp", start_new_session=True)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("ray://"), line
        yield line
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        # stray workers of THIS server only (its session): a `pkill -f
        # worker_main` also killed the workers of every other xdist worker's
        # cluster, which then failed with WorkerCrashedError
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass  # the whole session is gone already


@pytest.fixture
def ray_client(client_server):
    import ray_tpu

    ray_tpu.init(address=client_server)
    yield ray_tpu
    ray_tpu.shutdown()


def test_client_task_roundtrip(ray_client):
    @ray_client.remote
    def add(a, b):
        return a + b

    assert ray_client.get(add.remote(1, 2), timeout=60) == 3


def test_client_put_get_large(ray_client):
    big = np.arange(300_000, dtype=np.float32)
    ref = ray_client.put(big)
    np.testing.assert_array_equal(ray_client.get(ref, timeout=60), big)


def test_client_refs_as_args(ray_client):
    @ray_client.remote
    def double(x):
        return x * 2

    r1 = double.remote(21)
    r2 = double.remote(r1)  # ObjectRef arg crosses the wire
    assert ray_client.get(r2, timeout=60) == 84


def test_client_actor(ray_client):
    @ray_client.remote
    class Counter:
        def __init__(self, start):
            self.n = start

        def inc(self):
            self.n += 1
            return self.n

    c = Counter.remote(10)
    assert ray_client.get(c.inc.remote(), timeout=60) == 11
    assert ray_client.get(c.inc.remote(), timeout=60) == 12
    ray_client.kill(c)


def test_client_error_propagation(ray_client):
    @ray_client.remote
    def boom():
        raise ValueError("client-side boom")

    with pytest.raises(Exception, match="client-side boom"):
        ray_client.get(boom.remote(), timeout=60)


def test_client_wait_and_timeout(ray_client):
    import time as _t

    @ray_client.remote
    def slow():
        _t.sleep(30)

    @ray_client.remote
    def fast():
        return 1

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray_client.wait([f, s], num_returns=1, timeout=30)
    assert ready == [f] and not_ready == [s]

    with pytest.raises(ray_client.GetTimeoutError):
        ray_client.get(s, timeout=0.2)


def test_client_placement_group_and_cluster_info(ray_client):
    assert ray_client.cluster_resources().get("TPU") == 8.0
    pg = ray_client.util.placement_group([{"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=30)

    @ray_client.remote
    def where():
        return "ok"

    r = where.options(placement_group=pg).remote()
    assert ray_client.get(r, timeout=60) == "ok"
    ray_client.util.remove_placement_group(pg)
