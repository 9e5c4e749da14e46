import os

# Force an 8-device virtual CPU mesh for all tests: multi-chip sharding paths
# (dp/fsdp/tp) run in CI without TPUs, per the driver's dryrun contract.
os.environ["JAX_PLATFORMS"] = "cpu"  # force: a chip machine exports tpu
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest


@pytest.fixture
def ray_start_regular():
    """Boot a single-node runtime per test (cf. reference conftest.py:313)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, resources={"TPU": 8})
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-raylet in-process cluster (cf. reference cluster_utils.py:99)."""
    from ray_tpu.core.cluster import Cluster

    cluster = Cluster()
    yield cluster
    cluster.shutdown()
