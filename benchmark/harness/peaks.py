"""The table of peaks (benchmark/peaks.json), keyed by device_kind."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


class UnknownDevice(KeyError):
    """The device that ran is not in the peaks table."""


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    entry = table.get(device_kind)
    if not isinstance(entry, dict):
        known = sorted(k for k in table if not k.startswith("_"))
        raise UnknownDevice(
            f"no peaks on record for device_kind {device_kind!r} "
            f"(known: {known}); add it to benchmark/peaks.json with its "
            "source")
    return entry
