"""Chip peaks keyed by ``device_kind`` (``peaks.json``).  A device that is
not in the table is an error, never a default."""
from __future__ import annotations

import json
import os
from typing import Dict

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> Dict[str, float]:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"({path}) holds {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}
