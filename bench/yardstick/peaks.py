"""Published peaks of one chip, keyed by JAX's ``device_kind``.

``peaks.json`` beside this file holds one row per kind with its source.
A kind that is not in the table is an error, not a default.
"""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    with open(TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(table)}")
    return table[device_kind]
