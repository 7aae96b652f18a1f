"""Published peaks of a device, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

__all__ = ["peaks_for"]

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
