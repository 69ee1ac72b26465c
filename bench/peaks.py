"""The peaks table (`bench/peaks.json`), keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{TABLE.name}; known: {sorted(devices)}")
    return devices[device_kind]
