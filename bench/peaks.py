"""The chip peaks of ``peaks.json``, by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os
from typing import Dict

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "peaks.json")


def peaks(device_kind: str, path: str = PATH) -> Dict[str, float]:
    """The peaks of one device kind; an unknown kind is an error."""
    with open(path) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
