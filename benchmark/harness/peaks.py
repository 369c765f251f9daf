"""The one table of device peaks, keyed by the exact ``device_kind`` JAX
reports. A device that is not in the table is an error, never a default."""

from __future__ import annotations

from typing import Dict

from benchmark.harness.spec import load_json


def lookup(path: str, device_kind: str) -> Dict:
    table = load_json(path)
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {path} (it has "
            f"{sorted(table)}); add its published peaks with their source")
    return table[device_kind]
