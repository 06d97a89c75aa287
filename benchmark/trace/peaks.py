"""Published peaks of the chips the benchmark runs on, by ``device_kind``."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks row of ``device_kind``. A device that is not in the
    table is an error, never a default."""
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a row to {_PATH}")
    return table[device_kind]
