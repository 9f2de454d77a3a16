"""The yardstick's own table of device peaks, keyed by ``device_kind`` as JAX
reports it.  A device that is not in ``peaks.json`` is an error, never a
default: a roofline share against a guessed peak is not a measurement."""

from __future__ import annotations

import json
import os
from typing import Dict

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(LookupError):
    pass


def peak_for(device_kind: str) -> Dict[str, float]:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(table)} (add a row with its source to peaks.json)")
    return table[device_kind]
