"""Published peaks of each chip, keyed by JAX's ``device_kind``.

The numbers live in ``peaks.json`` with their source.  A device that is
not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

from .files import HERE, read_json


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    table = read_json(HERE / "peaks.json")
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; the table has "
            f"{sorted(table)}"
        )
    return table[device_kind]
