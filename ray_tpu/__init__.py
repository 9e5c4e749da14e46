"""ray_tpu: a TPU-native distributed compute framework.

A brand-new framework with the capability surface of Ray (tasks, actors,
objects, placement groups, Data/Train/Tune/Serve/RL) designed TPU-first:
the compute path is JAX/XLA/Pallas over `jax.sharding.Mesh`es, collectives
are compiler-emitted over ICI/DCN rather than NCCL library calls, and the
scheduler treats ICI-connected TPU slices as first-class topology-aware
resources.

Public core API (mirrors the reference's `ray` module surface,
/root/reference/python/ray/_private/worker.py:1115,2391,2538,2600,2929):

    import ray_tpu as ray
    ray.init()
    @ray.remote
    def f(x): return x + 1
    ref = f.remote(1)
    ray.get(ref)
"""

from ray_tpu._version import __version__

# Core public API (lazy-bound to avoid importing jax at `import ray_tpu` time).
from ray_tpu.core.api import (
    get_gpu_ids,
    get_tpu_ids,
    init,
    shutdown,
    is_initialized,
    remote,
    get,
    put,
    push,
    wait,
    kill,
    cancel,
    get_actor,
    method,
    nodes,
    cluster_resources,
    available_resources,
    get_runtime_context,
    timeline,
    timeline_info,
)
from ray_tpu.core.object_ref import ObjectRef, ObjectRefGenerator
from ray_tpu.core.actor import ActorClass, ActorHandle
from ray_tpu.core.exceptions import (
    RayTpuError,
    TaskError,
    ActorError,
    ActorDiedError,
    OutOfMemoryError,
    WorkerCrashedError,
    ObjectLostError,
    GetTimeoutError,
    PlacementInfeasibleError,
    RequestTimeoutError,
    BackPressureError,
)

__all__ = [
    "__version__",
    "init",
    "shutdown",
    "is_initialized",
    "remote",
    "get",
    "put",
    "wait",
    "kill",
    "cancel",
    "get_actor",
    "method",
    "nodes",
    "cluster_resources",
    "available_resources",
    "get_runtime_context",
    "timeline",
    "timeline_info",
    "ObjectRef",
    "ObjectRefGenerator",
    "push",
    "ActorClass",
    "ActorHandle",
    "RayTpuError",
    "TaskError",
    "ActorError",
    "ActorDiedError",
    "OutOfMemoryError",
    "WorkerCrashedError",
    "ObjectLostError",
    "GetTimeoutError",
    "PlacementInfeasibleError",
    "RequestTimeoutError",
    "BackPressureError",
]

__all__.append("util")


def __getattr__(name):
    # `ray_tpu.util` attribute access like the reference's `ray.util`,
    # loaded lazily (PEP 562) so bare `import ray_tpu` stays light.
    if name == "util":
        import importlib

        return importlib.import_module("ray_tpu.util")
    raise AttributeError(f"module 'ray_tpu' has no attribute {name!r}")
