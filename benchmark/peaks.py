"""The table of peaks (benchmark/peaks.json) and the least time a chip
could take for given operations and bytes: what every roofline reader
shares, whatever the family whose costs it is handed."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def device_peaks(device_kind: str) -> tuple[float, float]:
    """(bf16 FLOP/s, HBM bytes/s) of one chip from peaks.json; a device
    that is not in the table is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {known} — add the row "
                       "with its source to benchmark/peaks.json")
    return float(row["bf16_flops_per_s"]), float(row["hbm_bytes_per_s"])


def least_seconds(flops: float, bytes_: float,
                  device_kind: str) -> tuple[float, str]:
    """The least time one chip could take for this work, and which
    bound sets it."""
    peak_f, peak_b = device_peaks(device_kind)
    t_f, t_b = flops / peak_f, bytes_ / peak_b
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
