"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind; a kind not in the table is an
    error, never a default."""
    table = json.loads(TABLE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"device_kind {device_kind!r} is not in {TABLE.name}; "
                       f"known: {sorted(k for k in table if k != 'source')}")
    return table[device_kind]
