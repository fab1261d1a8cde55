"""The chip's published peaks, keyed by `device_kind`. A device that is
not in `peaks.json` is an error, never a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def load(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PATH}; add "
            f"the published figures with their source (known: "
            f"{sorted(table)})")
    return table[device_kind]


def least_seconds(flops: float, hbm_bytes: float, peaks: dict, *,
                  dtype: str = "bfloat16", ici_bytes: float = 0.0):
    """The least time one chip could take for this work, and which peak
    bounds it: the largest of operations over peak FLOP/s, bytes over
    HBM bandwidth, and interconnect bytes over the ICI rate."""
    terms = {
        "flops": flops / peaks["flops_per_s"][dtype],
        "hbm": hbm_bytes / peaks["hbm_bytes_per_s"],
        "ici": ici_bytes * 8.0 / peaks["ici_bits_per_s"],
    }
    bound = max(terms, key=terms.get)
    return terms[bound], bound
