"""Node failure domain: autoscaler-driven node replacement, warm
onboarding, and owner-side failover when a whole node (raylet + workers +
templates) dies.

Covers the PR-12 contract:
  - the autoscaler reconciles its launched set against the GCS live-node
    view and the provider, reaping + relaunching dead capacity;
  - provider exceptions (flaky create/terminate) never kill the update
    thread — they become backoff state with a per-type circuit breaker;
  - terminate_node is idempotent (double reap of a self-died node);
  - node-death detection latency is bounded by health_check_period_ms +
    health_check_timeout_ms (seeded heartbeat drops via FaultInjector);
  - an actor with max_restarts restarts on the REPLACEMENT node when the
    survivors have no capacity, not just on a survivor;
  - a joining node pre-spawns fork templates for the fleet's hot env keys
    (warm onboarding) without waiting for its first lease;
  - tasks spilled to a node that dies whole fail over at the owner (the
    raylet that would push task_worker_died died with the node).
"""

import os
import threading
import time

import pytest

import ray_tpu
from ray_tpu.autoscaler import FakeNodeProvider, NodeType, StandardAutoscaler
from ray_tpu.core import rpc
from ray_tpu.core.cluster import Cluster
from ray_tpu.core.config import get_config

FAULT_SEED = int(os.environ.get("RAY_TPU_FAULT_INJECTION_SEED", "20260804"))


@pytest.fixture
def fast_health():
    """Shrink the health-check clock so node-death detection is test-speed;
    must run BEFORE the cluster boots (the GCS health loop caches the
    period at start)."""
    cfg = get_config()
    saved = (cfg.health_check_period_ms, cfg.health_check_timeout_ms)
    cfg.health_check_period_ms = 200
    cfg.health_check_timeout_ms = 1500
    yield cfg
    cfg.health_check_period_ms, cfg.health_check_timeout_ms = saved


def _fleet_nodes(driver):
    return [n for n in driver.gcs.call("get_all_nodes", {}, timeout=10)
            if n.get("alive") and "fleet" in n.get("resources_total", {})]


def _make_autoscaler(cluster, provider, n, cap=2.0, **kw):
    return StandardAutoscaler(
        cluster.gcs_address, provider,
        [NodeType("fleet", {"CPU": 2.0, "fleet": cap},
                  min_workers=n, max_workers=n + 4)],
        update_interval_s=0.2, idle_timeout_s=10_000.0, **kw)


def _await_fleet(driver, provider, n=1, timeout=30.0):
    """Wait until the autoscaler's fleet is up in BOTH views: the GCS
    (raylets register from inside create_node, so this view leads) and the
    provider listing (a node is listed only once fully booted — the safe
    set to pick kill victims from)."""
    deadline = time.monotonic() + timeout
    while (len(_fleet_nodes(driver)) < n
           or len(provider.non_terminated_nodes()) < n):
        assert time.monotonic() < deadline, "fleet never formed"
        time.sleep(0.1)


def _teardown(cluster, autoscaler=None, provider=None):
    """Exception-proof teardown: an injected provider failure (or a corpse
    mid-reap) raising here must never skip cluster.shutdown() — a live
    global driver poisons every later test with 'init() called twice'."""
    if autoscaler is not None:
        try:
            autoscaler.stop()
        except Exception:
            pass
    if provider is not None:
        for pid in list(provider.non_terminated_nodes()):
            try:
                provider.terminate_node(pid)
            except Exception:
                pass
    cluster.shutdown()


def _await_stat(autoscaler, key, minimum=1, timeout=10.0):
    """Counters update a beat AFTER the provider/GCS view shows the effect
    (create_node registers the raylet before _launch records it) — poll,
    don't snapshot."""
    deadline = time.monotonic() + timeout
    while autoscaler.stats()[key] < minimum:
        assert time.monotonic() < deadline, \
            f"{key} never reached {minimum}: {autoscaler.stats()}"
        time.sleep(0.05)


def test_autoscaler_replaces_dead_node(fast_health):
    """A whole-node SIGKILL (no drain notify) is detected by the health
    loop, reaped at the provider, and relaunched to min_workers."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"head": 1})
    cluster.connect()
    provider = FakeNodeProvider(cluster.gcs_address)
    autoscaler = _make_autoscaler(cluster, provider, 1)
    try:
        autoscaler.start()
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        _await_fleet(driver, provider)
        victim = provider.non_terminated_nodes()[0]
        victim_hex = provider.raylet_for(victim).node_id.hex()
        provider.kill_node(victim)

        deadline = time.monotonic() + 30
        while True:
            fleet = _fleet_nodes(driver)
            if fleet and all(n["node_id"].hex() != victim_hex
                             for n in fleet):
                break
            assert time.monotonic() < deadline, \
                f"dead node never replaced: {autoscaler.stats()}"
            time.sleep(0.1)
        _await_stat(autoscaler, "relaunches")
        stats = autoscaler.stats()
        assert stats["deaths_by_reason"].get("health_check", 0) >= 1
        # the corpse was reaped at the provider, not left to leak
        assert victim not in provider.non_terminated_nodes()
    finally:
        _teardown(cluster, autoscaler, provider)


class _FlakyProvider(FakeNodeProvider):
    """create_node fails N times then works; terminate_node fails once."""

    def __init__(self, gcs_address, create_failures=2):
        super().__init__(gcs_address)
        self.create_calls = 0
        self.create_failures = create_failures
        self.terminate_calls = 0
        self._terminate_failed = False

    def create_node(self, node_type, resources, labels):
        self.create_calls += 1
        if self.create_calls <= self.create_failures:
            raise RuntimeError("cloud API 500 (injected)")
        return super().create_node(node_type, resources, labels)

    def terminate_node(self, provider_node_id):
        self.terminate_calls += 1
        if not self._terminate_failed:
            self._terminate_failed = True
            raise RuntimeError("cloud API timeout (injected)")
        super().terminate_node(provider_node_id)


def test_autoscaler_survives_flaky_provider(fast_health):
    """Regression (satellite): a create_node/terminate_node exception must
    not kill the update thread — the loop logs, backs off, and keeps
    reconciling until the fleet forms."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"head": 1})
    cluster.connect()
    provider = _FlakyProvider(cluster.gcs_address, create_failures=2)
    autoscaler = _make_autoscaler(cluster, provider, 1)
    try:
        autoscaler.start()
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        _await_fleet(driver, provider)
        _await_stat(autoscaler, "launch_failures", minimum=2)
        _await_stat(autoscaler, "launches")
        assert autoscaler._thread.is_alive()

        # flaky terminate: kill the node; the first terminate raises, the
        # reconcile survives it and the replacement still lands
        victim = provider.non_terminated_nodes()[0]
        provider.kill_node(victim)
        deadline = time.monotonic() + 30
        while autoscaler.stats()["relaunches"] < 1:
            assert time.monotonic() < deadline, \
                f"no relaunch after flaky terminate: {autoscaler.stats()}"
            time.sleep(0.1)
        assert autoscaler._thread.is_alive()
        assert autoscaler.stats()["terminate_failures"] >= 1
    finally:
        provider._terminate_failed = True  # disarm the injected failure
        _teardown(cluster, autoscaler, provider)


class _AlwaysFailingProvider(FakeNodeProvider):
    def __init__(self, gcs_address):
        super().__init__(gcs_address)
        self.create_calls = 0

    def create_node(self, node_type, resources, labels):
        self.create_calls += 1
        raise RuntimeError("cloud is down (injected)")


def test_launch_failure_circuit_breaker(fast_health):
    """A provider that fails every create must not be hot-looped: the
    per-type breaker opens after the threshold and launches are paced by
    full-jitter backoff, so attempts stay far below the tick count."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"head": 1})
    cluster.connect()
    provider = _AlwaysFailingProvider(cluster.gcs_address)
    autoscaler = StandardAutoscaler(
        cluster.gcs_address, provider,
        [NodeType("fleet", {"CPU": 2.0}, min_workers=1, max_workers=4)],
        update_interval_s=0.05, idle_timeout_s=10_000.0,
        launch_failure_threshold=3)
    try:
        autoscaler.start()
        time.sleep(1.5)  # ~30 ticks at 50 ms
        stats = autoscaler.stats()
        assert stats["launch_failures"] >= 3, stats
        assert stats["breakers"]["fleet"]["failures"] >= 3
        # without the breaker this would be ~30 attempts (one per tick)
        assert provider.create_calls <= 12, \
            f"breaker did not pace launches: {provider.create_calls} calls"
        assert autoscaler._thread.is_alive()
    finally:
        _teardown(cluster, autoscaler)


def test_fake_provider_terminate_idempotent():
    provider = FakeNodeProvider("127.0.0.1:1")  # never dialed
    # unknown id: no-op, no raise
    provider.terminate_node("fake-never-existed")
    provider.terminate_node("fake-never-existed")


def test_node_death_detection_latency_bounded(fast_health):
    """Seeded heartbeat drops (FaultInjector) starve a healthy node's
    heartbeats; the GCS must declare it dead within
    health_check_period_ms + health_check_timeout_ms (+ scheduling
    slack)."""
    print(f"fault injection seed: {FAULT_SEED}")
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"keep": 1})
    victim = cluster.add_node(num_cpus=2)
    cluster.connect()
    removed = {}
    evt = threading.Event()

    def on_nodes(msg):
        if msg.get("event") == "removed":
            removed[msg["node_id"].hex()] = time.monotonic()
            evt.set()

    try:
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        driver.subscribe_channel("nodes", on_nodes)
        time.sleep(0.3)  # at least one healthy heartbeat round first
        t0 = time.monotonic()
        rpc.install_fault_injector("drop:heartbeat", seed=FAULT_SEED)
        bound_s = (get_config().health_check_period_ms
                   + get_config().health_check_timeout_ms) / 1000.0
        deadline = time.monotonic() + bound_s * 3
        victim_hex = victim.node_id.hex()
        while victim_hex not in removed:
            assert time.monotonic() < deadline, \
                "starved node never declared dead"
            evt.wait(0.1)
            evt.clear()
        latency = removed[victim_hex] - t0
        # + one period of heartbeat phase + loop-tick slack
        assert latency <= bound_s * 1.5 + 0.5, \
            f"detection took {latency:.2f}s (bound {bound_s:.2f}s)"
        # the death is counted with its reason
        stats = driver.gcs.call("gcs_stats", {}, timeout=10)
        assert stats["node_failure"]["deaths_by_reason"].get(
            "health_check_failed", 0) >= 1
    finally:
        rpc.clear_fault_injector()
        cluster.shutdown()


def test_node_with_chips_is_given_time_for_a_chip_open(fast_health):
    """Opening or closing a chip stalls its whole host for seconds (8-12 s
    measured on a v5e host), heartbeats included: silence from a node that
    registered TPU is judged against three times the bound, so at defaults
    a chip open neither quarantines the node nor gets it declared dead."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"keep": 1})
    plain = cluster.add_node(num_cpus=2)
    chips = cluster.add_node(num_cpus=2, resources={"TPU": 1})
    cluster.connect()
    removed, quarantined = {}, {}

    def on_nodes(msg):
        seen = {"removed": removed, "quarantined": quarantined}.get(
            msg.get("event"))
        if seen is not None:
            seen.setdefault(msg["node_id"].hex(), time.monotonic())

    try:
        from ray_tpu.core.worker import current_worker

        current_worker().subscribe_channel("nodes", on_nodes)
        time.sleep(0.3)  # at least one healthy heartbeat round first
        t0 = time.monotonic()
        rpc.install_fault_injector("drop:heartbeat", seed=FAULT_SEED)
        timeout_s = get_config().health_check_timeout_ms / 1000.0
        deadline = time.monotonic() + 3 * timeout_s * 2 + 2
        while chips.node_id.hex() not in removed:
            assert time.monotonic() < deadline, "silent TPU node never died"
            time.sleep(0.05)
        plain_s = removed[plain.node_id.hex()] - t0
        chips_s = removed[chips.node_id.hex()] - t0
        assert plain_s <= timeout_s * 1.5 + 0.7, plain_s
        # a stall as long as the plain death bound costs it nothing ...
        assert quarantined[chips.node_id.hex()] - t0 > timeout_s
        # ... and it is declared dead at three times the bound
        assert 3 * timeout_s - 0.5 < chips_s <= 3 * timeout_s * 1.5 + 0.7, chips_s
    finally:
        rpc.clear_fault_injector()
        cluster.shutdown()


def test_actor_restarts_on_replacement_node(fast_health):
    """The actor's node dies; the only capacity for it is the autoscaler's
    REPLACEMENT node (survivors hold no 'fleet'), so the restart must land
    there — the restart path waits for capacity instead of failing."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"head": 1})
    cluster.connect()
    provider = FakeNodeProvider(cluster.gcs_address)
    autoscaler = _make_autoscaler(cluster, provider, 1)
    try:
        autoscaler.start()
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        _await_fleet(driver, provider)

        @ray_tpu.remote
        class Pinned:
            def ping(self):
                return os.getpid()

        a = Pinned.options(num_cpus=0, max_restarts=2,
                           resources={"fleet": 1.0}).remote()
        pid0 = ray_tpu.get(a.ping.remote(), timeout=30)
        victim = provider.non_terminated_nodes()[0]
        victim_id = provider.raylet_for(victim).node_id.binary()
        info = driver.get_actor_info(actor_id=a._actor_id)
        assert info["node_id"] == victim_id
        provider.kill_node(victim)

        # the actor must come back on the replacement — a different node id
        deadline = time.monotonic() + 45
        while True:
            info = driver.get_actor_info(actor_id=a._actor_id)
            if info["state"] == "ALIVE" and info["node_id"] != victim_id:
                break
            assert time.monotonic() < deadline, \
                f"actor never restarted on the replacement: {info}"
            time.sleep(0.2)
        pid1 = ray_tpu.get(a.ping.remote(), timeout=30)
        assert pid1 != pid0
        repl = [p for p in provider.non_terminated_nodes() if p != victim]
        repl_ids = {provider.raylet_for(p).node_id.binary() for p in repl
                    if provider.raylet_for(p) is not None}
        assert info["node_id"] in repl_ids
    finally:
        _teardown(cluster, autoscaler, provider)


def test_warm_onboarding_prewarms_templates(fast_health):
    """A JOINING raylet receives the fleet's hot env keys in its
    register_node reply and boots fork templates for them as part of
    onboarding — BEFORE any lease is granted on the node."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2)
    cluster.connect()
    try:
        @ray_tpu.remote
        class Hot:
            def ping(self):
                return "ok"

        # lease traffic makes the default env hot; a heartbeat ships it
        a = Hot.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=30) == "ok"
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        deadline = time.monotonic() + 10
        while True:
            stats = driver.gcs.call("gcs_stats", {}, timeout=10)
            if None in stats["node_failure"]["hot_env_keys"]:
                break
            assert time.monotonic() < deadline, \
                f"default env never became hot: {stats['node_failure']}"
            time.sleep(0.2)

        joiner = cluster.add_node(num_cpus=2)
        deadline = time.monotonic() + 15
        while True:
            tmpl = joiner._worker_pool.stats()["templates"].get("")
            if tmpl and tmpl["state"] == "ready":
                break
            assert time.monotonic() < deadline, \
                f"joiner never prewarmed its template: {tmpl}"
            time.sleep(0.1)
        # prewarm is template-only: no workers were forked for it
        s = joiner._worker_pool.stats()
        assert s["registered_warm"] == 0 and s["registered_cold"] == 0
    finally:
        cluster.shutdown()


def test_spilled_task_fails_over_on_node_death(fast_health):
    """Fast version of the chaos contract: tasks spilled to a node that
    dies WHOLE (no surviving raylet to push task_worker_died) fail over at
    the owner via the nodes-channel removal event and complete on the
    survivor within their retry budget."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"keep": 1})
    victim = cluster.add_node(num_cpus=2)
    cluster.connect()
    try:
        @ray_tpu.remote(max_retries=5)
        def work(i):
            time.sleep(0.2)
            return i * 2

        refs = [work.remote(i) for i in range(12)]
        time.sleep(0.5)  # let tasks spread (spill) to the victim
        cluster.remove_node(victim)
        out = ray_tpu.get(refs, timeout=60)
        assert out == [i * 2 for i in range(12)]
    finally:
        cluster.shutdown()


def test_actor_restart_wait_is_bounded(fast_health):
    """An actor whose restart can NEVER be placed (its resource type left
    the cluster for good) must go DEAD with a typed cause after
    actor_restart_pending_timeout_s — not park in the retry queue forever
    with every ref hung."""
    cfg = get_config()
    saved = cfg.actor_restart_pending_timeout_s
    cfg.actor_restart_pending_timeout_s = 2.0
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"head": 1})
    victim = cluster.add_node(num_cpus=2, resources={"fleet": 1.0})
    cluster.connect()
    try:
        @ray_tpu.remote
        class Pinned:
            def ping(self):
                return "ok"

        a = Pinned.options(num_cpus=0, max_restarts=4,
                           resources={"fleet": 1.0}).remote()
        assert ray_tpu.get(a.ping.remote(), timeout=30) == "ok"
        cluster.remove_node(victim)  # the only 'fleet' capacity, for good

        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        deadline = time.monotonic() + 20
        while True:
            info = driver.get_actor_info(actor_id=a._actor_id)
            if info["state"] == "DEAD":
                break
            assert time.monotonic() < deadline, \
                f"actor never expired out of the restart queue: {info}"
            time.sleep(0.2)
        assert "no feasible capacity" in info["death_cause"]
        # the queue itself drained — nothing left pending
        nf = driver.gcs.call("gcs_stats", {}, timeout=10)["node_failure"]
        assert nf["pending_actor_restarts"] == 0
    finally:
        cfg.actor_restart_pending_timeout_s = saved
        cluster.shutdown()


def test_peer_dial_does_not_serialize_other_peers(fast_health):
    """Kill-storm regression: dialing a DEAD peer address (SIGKILLed
    worker we still hold an address for) spins connect_with_retry for its
    whole timeout — that dial must not hold the peer-cache lock, or every
    submission in the process (including to healthy actors) stalls behind
    one corpse."""
    cluster = Cluster()
    head = cluster.add_node(num_cpus=2)
    cluster.connect()
    try:
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        dead_addr = "127.0.0.1:1"  # nothing listens: refused until timeout
        started = threading.Event()
        done = threading.Event()

        def dial_corpse():
            started.set()
            try:
                driver.peer(dead_addr, connect_timeout_s=5.0)
            except Exception:
                pass
            done.set()

        t = threading.Thread(target=dial_corpse, daemon=True)
        t.start()
        assert started.wait(5)
        time.sleep(0.2)  # let the dial enter its retry loop
        t0 = time.monotonic()
        driver.peer(head._server.address)  # a LIVE peer
        elapsed = time.monotonic() - t0
        assert not done.is_set(), \
            "dead dial finished too fast for the race to be exercised"
        assert elapsed < 2.0, \
            f"live peer() waited {elapsed:.2f}s behind a dead dial"
        done.wait(10)
    finally:
        cluster.shutdown()


def test_restart_dispatched_to_dying_node_recovers(fast_health):
    """Kill-storm race: an actor restart DISPATCHED to a node that dies
    before actor_creation_done comes back must not strand in RESTARTING
    forever. A successful dispatch leaves the pending-restart queue, so
    only the node-death sweep can rescue it — it must re-park the actor
    and land it on capacity that arrives later."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"head": 1})
    node_a = cluster.add_node(num_cpus=2, resources={"fleet": 1.0})
    node_b = cluster.add_node(num_cpus=2, resources={"fleet": 1.0})
    cluster.connect()
    try:
        @ray_tpu.remote
        class Pinned:
            def ping(self):
                return os.getpid()

        actor = Pinned.options(num_cpus=0, max_restarts=4,
                               resources={"fleet": 1.0}).remote()
        ray_tpu.get(actor.ping.remote(), timeout=30)
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        info = driver.get_actor_info(actor_id=actor._actor_id)
        if info["node_id"] == node_a.node_id.binary():
            first, other = node_a, node_b
        else:
            first, other = node_b, node_a
        # the restart target swallows create_actor: the dispatch succeeds
        # at the RPC layer but the creation never completes — exactly the
        # window a whole-node kill hits between dispatch and done
        other._server._handlers["create_actor"] = \
            lambda conn, req_id, payload: True
        cluster.remove_node(first)

        # the restart ends up dispatched to (and stranded on) `other`
        deadline = time.monotonic() + 20
        while True:
            info = driver.get_actor_info(actor_id=actor._actor_id)
            if info["state"] == "RESTARTING" \
                    and info["node_id"] == other.node_id.binary():
                break
            assert time.monotonic() < deadline, \
                f"restart never dispatched to the swallowing node: {info}"
            time.sleep(0.1)

        # now the dispatch target dies too; the sweep must re-park the
        # stranded restart instead of leaving it RESTARTING forever
        cluster.remove_node(other)
        node_c = cluster.add_node(num_cpus=2, resources={"fleet": 1.0})
        deadline = time.monotonic() + 30
        while True:
            info = driver.get_actor_info(actor_id=actor._actor_id)
            if info["state"] == "ALIVE" \
                    and info["node_id"] == node_c.node_id.binary():
                break
            assert time.monotonic() < deadline, \
                f"stranded restart never recovered on new capacity: {info}"
            time.sleep(0.1)
        assert ray_tpu.get(actor.ping.remote(), timeout=30)
    finally:
        cluster.shutdown()


class _FakeKubeApi:
    """Stateful fake of the Kubernetes pods API (the provider's injectable
    transport): POST creates a Running pod, GET lists by label selector,
    DELETE removes (404 on unknown). Pods can be killed behind the
    provider's back (preemption) so the autoscaler's vanished-node
    reconcile is exercised END TO END, not just per-call."""

    class _NotFound(Exception):
        status = 404

    def __init__(self, fail_creates: int = 0):
        self.pods: dict = {}
        self.create_calls = 0
        self.delete_calls = 0
        self.fail_creates = fail_creates

    def __call__(self, method, url, body=None, headers=None):
        if method == "POST":
            self.create_calls += 1
            if self.create_calls <= self.fail_creates:
                raise RuntimeError("apiserver 500 (injected)")
            name = body["metadata"]["name"]
            self.pods[name] = dict(body, status={"phase": "Running"})
            return {}
        if method == "GET":
            return {"items": [p for p in self.pods.values()
                              if p["metadata"]["labels"]
                              .get("ray-tpu-cluster") == "1"]}
        if method == "DELETE":
            self.delete_calls += 1
            name = url.rsplit("/", 1)[-1]
            if name not in self.pods:
                raise self._NotFound("pod not found")
            del self.pods[name]
            return {}
        raise AssertionError(f"unexpected {method} {url}")

    def preempt(self, name: str) -> None:
        """The node vanishes out from under the provider (spot reclaim)."""
        del self.pods[name]


def test_kubernetes_provider_reap_and_replace_loop(fast_health):
    """ROADMAP item 1 leftover: drive the autoscaler's reap-and-replace
    CONTROL LOOP through KubernetesTpuNodeProvider over its fake
    transport — minimums converge, a preempted pod is detected as
    vanished and relaunched, a transient apiserver failure becomes
    breaker/backoff state (never a dead update thread), and the 404
    double-reap stays a no-op."""
    from ray_tpu.autoscaler import KubernetesTpuNodeProvider

    cluster = Cluster()  # a real (empty) control plane for the demand polls
    cluster.add_node(num_cpus=1, resources={"head": 1})
    cluster.connect()
    api = _FakeKubeApi(fail_creates=1)
    provider = KubernetesTpuNodeProvider(
        "testns", cluster.gcs_address, request_fn=api)
    autoscaler = StandardAutoscaler(
        cluster.gcs_address, provider,
        [NodeType("tpu_pod", {"TPU": 4.0}, min_workers=2, max_workers=4)],
        update_interval_s=0.1, idle_timeout_s=10_000.0)
    try:
        autoscaler.start()
        # minimums converge THROUGH the injected create failure
        deadline = time.monotonic() + 15
        while len(provider.non_terminated_nodes()) < 2:
            assert time.monotonic() < deadline, \
                f"pod fleet never formed: {autoscaler.stats()}"
            time.sleep(0.05)
        assert autoscaler.stats()["launch_failures"] >= 1

        # spot preemption: the pod vanishes from the API; the reconcile
        # counts the death and relaunches to min_workers
        victim = provider.non_terminated_nodes()[0]
        auto0 = autoscaler.stats()
        api.preempt(victim)
        deadline = time.monotonic() + 15
        while True:
            stats = autoscaler.stats()
            if (stats["relaunches"] > auto0["relaunches"]
                    and len(provider.non_terminated_nodes()) >= 2):
                break
            assert time.monotonic() < deadline, \
                f"preempted pod never replaced: {stats}"
            time.sleep(0.05)
        assert stats["deaths_by_reason"].get("vanished", 0) >= 1
        assert autoscaler._thread.is_alive()
        # 404 double reap is a no-op at the provider (idempotent terminate)
        provider.terminate_node(victim)
        provider.terminate_node("never-existed")
        # pods the autoscaler launched carry the cluster labels + TPU
        # resource request (the manifest path actually used by the loop)
        pod = next(iter(api.pods.values()))
        assert pod["metadata"]["labels"]["ray-tpu-type"] == "tpu_pod"
        limits = pod["spec"]["containers"][0]["resources"]["limits"]
        assert limits["google.com/tpu"] == "4"
    finally:
        _teardown(cluster, autoscaler)


def test_gcs_stats_surfaces_node_failure_domain(fast_health):
    """Metrics satellite: deaths by reason, autoscaler counters and
    warm-lease joins are all readable from one gcs_stats call."""
    cluster = Cluster()
    cluster.add_node(num_cpus=2, resources={"keep": 1})
    victim = cluster.add_node(num_cpus=1)
    cluster.connect()
    try:
        from ray_tpu.core.worker import current_worker

        driver = current_worker()
        cluster.remove_node(victim)  # drain path: a SCALE-DOWN, not a death
        deadline = time.monotonic() + 10
        while True:
            nf = driver.gcs.call("gcs_stats", {}, timeout=10)["node_failure"]
            if nf["drains_total"] >= 1:
                break
            assert time.monotonic() < deadline, nf
            time.sleep(0.1)
        # graceful drains never inflate the failure counters
        assert nf["deaths_total"] == 0
        assert "autoscaler" in nf and "warm_lease_joins" in nf
        # the prometheus-side counters exist under the published names
        from ray_tpu.util.metrics import get_or_create

        assert get_or_create("counter", "ray_tpu_node_deaths_total",
                             "nodes declared dead",
                             tag_keys=("reason",)) is not None
        assert get_or_create("counter", "ray_tpu_node_relaunches_total",
                             "autoscaler replacements launched for dead "
                             "nodes") is not None
    finally:
        cluster.shutdown()
