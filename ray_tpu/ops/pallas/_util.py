"""Shared helpers for the Pallas kernels."""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether the default device is a TPU. Device discovery that FAILS
    raises: a worker that was promised a chip must not drift onto the
    interpreter or a reference einsum because libtpu could not open it.
    Every kernel's device branch reads this one function (tests that
    compile for a described chip steer it here)."""
    return jax.devices()[0].platform == "tpu"


def interpret_mode() -> bool:
    """Kernels compile with Mosaic on TPU, interpret elsewhere (CI CPU mesh)."""
    return not on_tpu()


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
