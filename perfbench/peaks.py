"""The chip's published peaks, keyed by `device_kind` (peaks.json).  A
device that is not in the table is an error, never a default."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_table():
    with open(os.path.join(_HERE, "peaks.json")) as f:
        return json.load(f)["devices"]


def peaks_for(device_kind):
    table = load_table()
    if device_kind not in table:
        raise LookupError(
            f"no peaks for device_kind {device_kind!r} (known: "
            f"{sorted(table)}); add it with its source to perfbench/peaks.json")
    return table[device_kind]
