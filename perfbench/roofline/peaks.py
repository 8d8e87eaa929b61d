"""The chip's published peaks, keyed by ``device_kind``. A device that
is not in the table is an error, never a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind):
    with open(_PATH, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in "
            f"perfbench/roofline/peaks.json"
        )
    return table[device_kind]
