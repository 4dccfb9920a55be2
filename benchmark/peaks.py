"""The one table of chip peaks, keyed by ``device_kind``. A device that is
not in the table is an error, never a default."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(have {sorted(table)}): add its published peaks with their "
            "source before measuring on it")
    return table[device_kind]
