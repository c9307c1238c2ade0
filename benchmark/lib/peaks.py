"""The table of peaks, keyed by the `device_kind` jax reports. A device
that is not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add a row with its "
            f"source to {_TABLE.name} (known: {sorted(table)})")
    return table[device_kind]
