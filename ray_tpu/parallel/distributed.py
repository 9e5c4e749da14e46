"""Multi-host JAX initialization inside dynamically-created actors.

SURVEY hard-part #4: `jax.distributed.initialize` expects a static world at
process start, but this framework creates worker groups dynamically (Train
spawns one actor per host). This module bridges the two through the control
plane's KV store — the same place the reference rendezvouses NCCL unique
ids (`collective_group/nccl_collective_group.py`): rank 0 binds a free
coordinator port and publishes `host:port` under the group's KV key; every
rank polls the key and calls `jax.distributed.initialize(addr, world,
rank)`. After it returns, `jax.devices()` spans all processes, and a
`make_mesh` over them compiles collectives across hosts (ICI within a
slice, DCN across — or Gloo on CPU test rigs).
"""

from __future__ import annotations

import logging
import socket
import time
from typing import Optional

logger = logging.getLogger(__name__)

_KV_NS = "_jax_distributed"
_initialized_group: Optional[str] = None


def _kv():
    from ray_tpu.core.api import _global_worker

    return _global_worker().gcs


def _my_host() -> str:
    from ray_tpu.core.api import _global_worker

    addr = _global_worker().address  # "host:port" of this worker's server
    return addr.rsplit(":", 1)[0] if ":" in addr else "127.0.0.1"


def initialize_group(rank: int, world_size: int, *,
                     group_name: str = "default",
                     timeout: float = 120.0) -> None:
    """Join this process into a jax.distributed world of `world_size`
    processes. Call before any other JAX backend use in the process.
    Idempotent per group; re-initializing a different group raises.
    """
    global _initialized_group
    import jax

    if _initialized_group is not None:
        if _initialized_group == group_name:
            return
        raise RuntimeError(
            f"process already in jax.distributed group {_initialized_group!r}")
    if world_size == 1:
        _initialized_group = group_name
        return

    key = f"coordinator:{group_name}".encode()
    gcs = _kv()
    if rank == 0:
        # Hold the bound socket (SO_REUSEADDR) until just before initialize
        # to shrink the pick-port/bind race to microseconds.
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((_my_host(), 0))
        coord = f"{s.getsockname()[0]}:{s.getsockname()[1]}"
        gcs.call("kv_put", {"namespace": _KV_NS, "key": key,
                            "value": coord.encode()})
        s.close()
    else:
        # A stale key from a previous run of this group may still be in the
        # KV; only accept a coordinator that is actually listening (the old
        # process is dead -> refused -> keep polling until the new rank 0
        # overwrites the key and binds).
        deadline = time.monotonic() + timeout
        coord = None
        while time.monotonic() < deadline:
            v = gcs.call("kv_get", {"namespace": _KV_NS, "key": key})
            if v:
                host, port = v.decode().rsplit(":", 1)
                try:
                    socket.create_connection((host, int(port)),
                                             timeout=1).close()
                    coord = v.decode()
                    break
                except OSError:
                    pass
            time.sleep(0.1)
        if coord is None:
            raise TimeoutError(
                f"rank {rank}: no live coordinator for group "
                f"{group_name!r} within {timeout}s")

    logger.info("rank %d/%d joining jax.distributed at %s", rank, world_size,
                coord)
    jax.distributed.initialize(coord, num_processes=world_size,
                               process_id=rank)
    _initialized_group = group_name


def initialize_from_session(group_name: str = "default",
                            timeout: float = 120.0) -> None:
    """Inside a Train worker: rank/world come from the AIR session."""
    from ray_tpu.air import session

    initialize_group(session.get_world_rank(), session.get_world_size(),
                     group_name=group_name, timeout=timeout)


def shutdown_group(group_name: str = "default") -> None:
    global _initialized_group
    import jax

    if _initialized_group is None:
        return
    try:
        jax.distributed.shutdown()
    except (RuntimeError, ValueError):
        pass  # never initialized / already shut down
    try:
        _kv().call("kv_del", {"namespace": _KV_NS,
                              "key": f"coordinator:{group_name}".encode()})
    except (OSError, RuntimeError, TimeoutError):
        pass  # GCS already down at interpreter exit
    _initialized_group = None
