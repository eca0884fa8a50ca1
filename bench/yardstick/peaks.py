"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = json.loads(_TABLE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to {_TABLE.name} with its source")
    return table[device_kind]
