"""Published per-chip peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """A device kind with no published peaks in ``peaks.json``."""


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; an unknown kind raises
    rather than borrowing another chip's numbers."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r} in {PEAKS_FILE.name} (have "
                            f"{sorted(table)})")
    return dict(table[device_kind])
