"""The one table of chip peaks, keyed by jax's `device_kind`."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it to "
                       f"{_TABLE} with its source; an unknown device is an "
                       "error, not a default")
    return table[device_kind]
