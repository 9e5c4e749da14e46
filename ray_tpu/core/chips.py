"""What a worker that was spawned for a chip grant is told about the chip:
which chips its libtpu may open, and where its compiled programs are kept.
Imports nothing heavy — the raylet and `chip_smoke.py`'s parent
(which must stay off jax) all call it."""

from __future__ import annotations

import errno
import os
from typing import Container, Dict, List

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


# where the chips' device nodes live (a test stands a directory in for it)
_DEV = "/dev"


def chip_nodes(tpu_ids: List[int]) -> List[str]:
    """The device nodes a process opens to use chips `tpu_ids` of this host:
    `/dev/accel<i>` where the driver is the accel one, and under vfio the
    i-th of the numbered groups `/dev/vfio/<n>` in ascending order
    (`/dev/vfio/vfio`, the container every opener shares, is not a chip).
    The i-th, not group i: the one-chip v5e machines are a quarter of a
    four-chip host and hold `/dev/vfio/3` alone, which is their chip 0; a
    four-chip host holds `/dev/vfio/0..3`, libtpu opens them in that order
    (the open that failed behind a dying holder named group 2 after 0 and 1
    had opened), and a process told `TPU_VISIBLE_CHIPS=i` opens the i-th
    alone (read on the chip: `ci/chip_calls/pr45/dev_nodes.py`). No such
    node (a CPU host): an empty list."""
    try:
        groups = sorted(int(n) for n in os.listdir(os.path.join(_DEV, "vfio"))
                        if n.isdigit())
    except OSError:
        groups = []
    nodes = [os.path.join(_DEV, "vfio", str(groups[i]))
             for i in tpu_ids if i < len(groups)]
    nodes += [os.path.join(_DEV, f"accel{i}") for i in tpu_ids]
    return [n for n in nodes if os.path.exists(n)]


def _refuses_to_open(node: str) -> bool:
    """Whether opening `node` fails with EBUSY, as a vfio group does while
    another process has it (a regular file, or a node this user may not
    open, says nothing)."""
    try:
        os.close(os.open(node, os.O_RDWR))
    except OSError as e:
        return e.errno == errno.EBUSY
    return False


def chip_holders(tpu_ids: List[int], ours: Container[int] = ()) -> Dict[str, int]:
    """{device node: pid} for every node of chips `tpu_ids` that a process
    other than this one and `ours` holds: a chip belongs to one process, so
    a worker started for these chips now would die in libtpu with `Device
    or resource busy`. Two looks, because neither sees everything. One pass
    over `/proc/*/fd` names the holder while it lives. A holder that is
    EXITING has already dropped its descriptor table and is still closing
    the device (6-9 s for one v5e chip with its memory pinned, its groups
    one by one: read on the chip), so a vfio group that nobody lists is
    opened and closed once: EBUSY means held, by pid 0 (not known). Costs
    nothing where the host has no such node."""
    nodes = {os.path.realpath(n): n for n in chip_nodes(tpu_ids)}
    held: Dict[str, int] = {}
    if not nodes:
        return held
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid() or int(pid) in ours:
            continue
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue  # gone, or another user's
        for fd in fds:
            try:
                node = nodes.get(os.readlink(f"/proc/{pid}/fd/{fd}"))
            except OSError:
                continue
            if node is not None:
                held.setdefault(node, int(pid))
    for node in nodes.values():
        # an accel node is not opened: what that costs its driver is not known
        if node not in held and os.path.basename(os.path.dirname(node)) == "vfio" \
                and _refuses_to_open(node):
            held[node] = 0
    return held


def default_compile_cache_dir() -> str:
    """JAX's persistent compilation cache when `JAX_COMPILATION_CACHE_DIR`
    does not place it: one fixed directory in the checkout (git-ignored).
    The path is part of the cache key, so it never carries a temp name, a
    pid or a time."""
    import ray_tpu

    root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    return os.path.join(root, ".jax_compile_cache")
