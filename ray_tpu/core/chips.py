"""What a worker that was spawned for a chip grant is told about the chip:
which chips its libtpu may open, and where its compiled programs are kept;
and how long the backend took to open them (`time_chip_open`).
Imports nothing heavy — the raylet and `chip_smoke.py`'s parent
(which must stay off jax) all call it."""

from __future__ import annotations

import errno
import importlib.abc
import os
import sys
import threading
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


_XLA_BRIDGE = "jax._src.xla_bridge"
_open_lock = threading.Lock()
_open_armed = False


def time_chip_open(granted: int) -> None:
    """Arm, once in a worker that holds a grant of `granted` chips, the timing
    of jax's backend opening: ONE `chip.open` span (`platform`, `device_kind`,
    `devices`, `granted`) around the call of `xla_bridge.backends()` that
    initialises the backends, WHOEVER makes it (the user's `jax.devices()`, a
    `jit`, `jax.distributed.initialize` before either). The program opens
    nothing itself and imports no jax: where jax is not loaded yet, a finder
    at the head of `sys.meta_path` waits for `jax._src.xla_bridge` alone,
    wraps that module's `backends` once it has executed and takes itself off
    the path. The wrapper puts the original back as soon as a call returns
    with the backends initialised: nothing of this is on any later call's
    path. From then on the process's programs record their `xla.compile`
    spans (`tracing.record_compiles`)."""
    global _open_armed
    if _open_armed:
        return
    with _open_lock:
        if _open_armed:
            return
        _open_armed = True
    bridge = sys.modules.get(_XLA_BRIDGE)
    if bridge is None:
        sys.meta_path.insert(0, _BridgeFinder(granted))
    elif hasattr(bridge, "backends"):
        _wrap_backends(bridge, granted)
    # else another thread is half-way through importing it: a finder would
    # never fire and the module is not whole yet, so this worker records no
    # `chip.open` (its readers then give None) rather than race the import


def _wrap_backends(bridge, granted: int) -> None:
    """Tracing never fails what it traces: a jax whose `xla_bridge` lacks
    what is read here (`tests/test_setup_spans.py` pins the four names)
    is left as it is, and a span that cannot be recorded is said on stderr
    once, behind the backends' own result or error."""
    from ray_tpu.util import tracing

    try:
        original = bridge.backends
        if bridge.backends_are_initialized():
            return  # opened before the grant was known: nothing left to time
    except Exception as e:
        return _not_timed(e)

    def backends():
        start = tracing.now_us()
        try:
            return original()
        finally:
            try:
                with _open_lock:
                    mine = bridge.backends is backends and bool(bridge._backends)
                    if mine:
                        bridge.backends = original
                if mine:
                    client = bridge._default_backend
                    tracing.add_complete(
                        "chip.open", "chip", start, tracing.now_us() - start,
                        platform=client.platform,
                        device_kind=client.local_devices()[0].device_kind,
                        devices=client.device_count(), granted=granted)
                    tracing.record_compiles()
            except Exception as e:
                if bridge.backends is backends:
                    bridge.backends = original
                _not_timed(e)

    bridge.backends = backends


def _not_timed(e: Exception) -> None:
    print(f"[chips] `chip.open` not recorded: {type(e).__name__}: {e}",
          file=sys.stderr, flush=True)


class _BridgeFinder(importlib.abc.MetaPathFinder):
    """Finds nothing but `jax._src.xla_bridge`, once: the spec is the next
    finders' own, its loader's `exec_module` followed by `_wrap_backends`."""

    def __init__(self, granted: int):
        self._granted = granted

    def find_spec(self, fullname, path, target=None):
        if fullname != _XLA_BRIDGE:
            return None
        sys.meta_path.remove(self)
        for finder in sys.meta_path:
            find = getattr(finder, "find_spec", None)
            spec = find(fullname, path, target) if find else None
            if spec is not None and spec.loader is not None:
                spec.loader = _WrappingLoader(spec.loader, self._granted)
                return spec
        return None


class _WrappingLoader(importlib.abc.Loader):
    def __init__(self, loader, granted: int):
        self._loader, self._granted = loader, granted

    def create_module(self, spec):
        return self._loader.create_module(spec)

    def exec_module(self, module):
        # the module is its own loader's again before its code runs
        module.__loader__ = module.__spec__.loader = self._loader
        self._loader.exec_module(module)
        _wrap_backends(module, self._granted)


def default_compile_cache_dir() -> str:
    """JAX's persistent compilation cache when `JAX_COMPILATION_CACHE_DIR`
    does not place it: one fixed directory in the checkout (git-ignored).
    The path is part of the cache key, so it never carries a temp name, a
    pid or a time."""
    import ray_tpu

    root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    return os.path.join(root, ".jax_compile_cache")
