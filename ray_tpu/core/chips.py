"""What a worker that was spawned for a chip grant is told about the chip:
which chips its libtpu may open, and where its compiled programs are kept.
Imports nothing heavy — the raylet and `chip_smoke.py`'s parent
(which must stay off jax) all call it."""

from __future__ import annotations

import os
from typing import Dict, List

# libtpu's shape of n chips of one host as one process sees them
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def chip_visibility_env(tpu_ids: List[int], node_chips: int) -> Dict[str, str]:
    """Environment that makes exactly `tpu_ids` visible to the libtpu of one
    process. A grant of every chip of the node keeps libtpu's own view of
    the host (and its one-process lock); a strict subset is declared as a
    host of its own so several processes can each open their chips."""
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(i) for i in tpu_ids)}
    # a count with no single-host layout (3, 5, ...) is left to libtpu, which
    # refuses it in the worker, where the user sees why
    bounds = _PROCESS_BOUNDS.get(len(tpu_ids))
    if len(tpu_ids) < node_chips and bounds is not None:
        port = str(8476 + tpu_ids[0])  # one mesh controller per process
        env.update({
            "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "TPU_PROCESS_PORT": port,
            "CLOUD_TPU_TASK_ID": "0",
        })
    return env


def default_compile_cache_dir() -> str:
    """JAX's persistent compilation cache when `JAX_COMPILATION_CACHE_DIR`
    does not place it: one fixed directory in the checkout (git-ignored).
    The path is part of the cache key, so it never carries a temp name, a
    pid or a time."""
    import ray_tpu

    root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    return os.path.join(root, ".jax_compile_cache")
