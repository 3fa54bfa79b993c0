"""The table of peaks, keyed by `device_kind`. A device that is not in it
is an error, not a default."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def of(device_kind):
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in %s"
                       % (device_kind, _PATH))
    return table[device_kind]
