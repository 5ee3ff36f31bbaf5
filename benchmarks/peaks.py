"""The table of peaks, keyed by `device_kind` as jax reports it. A device
that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"benchmarks/peaks.json has no peaks for device kind "
            f"{device_kind!r} (it knows {sorted(table)})"
        )
    return table[device_kind]
