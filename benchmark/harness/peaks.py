"""Published peaks of the devices the benchmark may run on.

``peaks.json`` is keyed by JAX's ``device_kind``. A device that is not in the
table is an error, never a default: a share of an unknown peak is no number.
"""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


class UnknownDevice(LookupError):
    """The device kind has no row in ``peaks.json``."""


def load(device_kind: str) -> dict:
    """The row of ``device_kind``: bf16 FLOP/s, HBM bytes/s, ICI bits/s."""
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {TABLE.name} "
            f"(known: {sorted(table)}); add its published peaks with their "
            "source before measuring on it")
    return table[device_kind]
