"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

The table is ``peaks.json`` beside this file, with its source. A device
that is not in it is an error, never a default: a roofline share or a
utilization against the wrong peak is a wrong number.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


class UnknownDevice(LookupError):
    pass


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    """The peaks of ``device_kind``; raises ``UnknownDevice`` if absent."""
    devices = json.loads(table.read_text())["devices"]
    if device_kind not in devices:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in "
            f"{table.name}; known: {sorted(devices)}")
    return dict(devices[device_kind])
