"""Device meshes and logical-axis shardings: the NCCL replacement.

Where the reference wires NCCL process groups through actors
(`python/ray/util/collective/collective.py:120`) and torch DDP/FSDP
(`python/ray/train/torch/config.py:69`), the TPU-native design gives every
worker group a `jax.sharding.Mesh` whose axes map onto the hardware:

    dp    — data parallel, outermost (across slices -> rides DCN)
    fsdp  — sharded data parallel (ZeRO-3 analog; within slice -> ICI)
    tp    — tensor parallel (within slice -> ICI, highest bandwidth)
    ep    — expert parallel for MoE layers (reuses fsdp axis by default)

Collectives (`psum`, `all_gather`, `ppermute`, `reduce_scatter`) are then
emitted by XLA from sharding annotations — no collective library calls in
user code. Parameters/activations carry *logical* axis names which
`AxisRules` maps to mesh axes (the flax `logical_axis_rules` idea, re-built
standalone). Two collectives the program writes out itself, in the modules
beside this one, because the TPU compiler overlaps them with compute only
as explicit permutes: `parallel/fsdp.py`, the weight gradients' sum over
`fsdp`, and, where `tp` > 1 as well, `parallel/tp.py`, the dense block's
gathers and scatters over `tp` (the residual stream rides sequence-sharded
between the products; the partitioner's form is four blocking all-reduces a
layer), with the weights' shards going round `fsdp`'s ring inside those
products.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes of each parallelism axis. -1 on `dp` means 'fill'."""

    dp: int = -1
    fsdp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        fixed = self.fsdp * self.tp
        dp = self.dp
        if dp == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fsdp*tp={fixed}")
            dp = n_devices // fixed
        if dp * fixed != n_devices:
            raise ValueError(
                f"mesh {dp}x{self.fsdp}x{self.tp} != {n_devices} devices")
        return MeshConfig(dp=dp, fsdp=self.fsdp, tp=self.tp)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.dp, self.fsdp, self.tp)


AXIS_NAMES = ("dp", "fsdp", "tp")


def make_mesh(config: MeshConfig, devices: Optional[Sequence[Any]] = None) -> Mesh:
    """Build a Mesh with (dp, fsdp, tp) axes over the given devices.

    Axis order is chosen so the innermost (fastest-varying) axes hold the
    highest-bandwidth collectives: tp innermost maps to adjacent chips on
    ICI; dp outermost maps across hosts/slices (DCN for multi-slice).
    """
    devices = list(devices if devices is not None else jax.devices())
    cfg = config.resolve(len(devices))
    arr = np.array(devices).reshape(cfg.shape)
    return Mesh(arr, AXIS_NAMES)


def make_hybrid_mesh(config: MeshConfig, *, dcn_dp: int = 1,
                     devices: Optional[Sequence[Any]] = None) -> Mesh:
    """Multi-slice mesh: `dcn_dp` data-parallel replicas across slices (DCN),
    `config` parallelism within each slice (ICI).

    Uses `mesh_utils.create_hybrid_device_mesh` so device order guarantees
    only the outermost dp axis crosses slice boundaries — tp/fsdp
    collectives stay on ICI (the scaling-book multislice recipe). Falls back
    to a plain reshape when devices carry no slice topology (CPU tests,
    single slice): semantics identical, placement guarantee vacuous.
    """
    devices = list(devices if devices is not None else jax.devices())
    if dcn_dp <= 1:
        return make_mesh(config, devices)
    if len(devices) % dcn_dp != 0:
        raise ValueError(f"{len(devices)} devices not divisible by dcn_dp={dcn_dp}")
    per_slice = config.resolve(len(devices) // dcn_dp)
    if getattr(devices[0], "slice_index", None) is not None:
        from jax.experimental import mesh_utils

        # real multislice topology: let genuine shape mismatches propagate
        arr = mesh_utils.create_hybrid_device_mesh(
            per_slice.shape, (dcn_dp, 1, 1), devices=devices)
    else:  # no slice topology (CPU tests, single slice): plain reshape
        arr = np.array(devices).reshape(
            (dcn_dp * per_slice.dp,) + per_slice.shape[1:])
    return Mesh(arr, AXIS_NAMES)


def make_virtual_mesh(n_devices: int, config: Optional[MeshConfig] = None) -> Mesh:
    """CPU-device mesh for tests/dryrun (xla_force_host_platform_device_count)."""
    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(devices)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}")
    cfg = (config or MeshConfig()).resolve(n_devices)
    return make_mesh(cfg, devices[:n_devices])


# --------------------------------------------------------------------------
# Logical axis rules


class AxisRules:
    """Maps logical axis names -> mesh axis (or None = replicated)."""

    def __init__(self, rules: Dict[str, Any]):
        self.rules = dict(rules)

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        parts = []
        used = set()
        for ax in logical_axes:
            m = self.rules.get(ax) if ax is not None else None
            if m is None:
                parts.append(None)
            else:
                # a logical axis may map to a tuple of mesh axes
                key = tuple(m) if isinstance(m, (tuple, list)) else (m,)
                free = tuple(a for a in key if a not in used)
                used.update(free)
                parts.append(free if len(free) != 1 else free[0])
                if not free:
                    parts[-1] = None
        return P(*parts)

    def sharding(self, mesh: Mesh, logical_axes: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes))


# Default rules for transformer LMs: FSDP shards the embed dim of weights,
# TP shards heads/mlp, batch shards over (dp, fsdp) [fsdp acts as extra DP
# for activations, ZeRO-style], the sequence is not sharded.
DEFAULT_RULES = AxisRules({
    "batch": ("dp", "fsdp"),
    "seq": None,
    "embed": "fsdp",
    "heads": "tp",
    "kv": None,
    "mlp": "tp",
    "vocab": "tp",
    "layers": None,
    "expert": "fsdp",
})


def logical_sharding(mesh: Mesh, axes_tree: Any, rules: AxisRules = DEFAULT_RULES):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree_util.tree_map(
        lambda axes: rules.sharding(mesh, axes),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def shard_pytree(tree: Any, shardings: Any):
    """Device-put a pytree with the given shardings (host -> sharded device)."""
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), tree, shardings)
