"""Least bytes that each device program needs, from its shapes alone.

These counts are the yardstick of the ``*_roofline`` metrics: a share
of the roofline is the least time (bytes over the chip's peak HBM
bandwidth, :mod:`peaks`) over the time the trace shows.  Each count is
fixed per record or key, whatever implements the program, so a later
change can move the trace time but not the count.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

# Flat device sort (one dispatch of the sort executor): per real record,
# one read of its packed 8-byte key prefix and its int32 segment id, and
# one write of its int32 position in the permutation.
FLAT_SORT_BYTES_PER_RECORD = 8 + 4 + 4

# RMI predict (one call of the serving index): per key, one read of its
# two u32 key words and one write of its int32 predicted row; per call,
# one read of the leaf table (5 f32 and 2 u32 words per leaf).
RMI_BYTES_PER_KEY = 4 + 4 + 4
RMI_BYTES_PER_LEAF = 5 * 4 + 2 * 4


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind missing from the
    table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def flat_sort_bytes(n_records: int) -> int:
    return FLAT_SORT_BYTES_PER_RECORD * int(n_records)


def rmi_call_bytes(n_keys: int, n_leaf: int) -> int:
    return RMI_BYTES_PER_KEY * int(n_keys) + RMI_BYTES_PER_LEAF * int(n_leaf)


def roofline_share(least_bytes: float, seconds: float,
                   hbm_bytes_per_s: float) -> float:
    """Percent of the memory roofline: least time over measured time."""
    return 100.0 * (least_bytes / hbm_bytes_per_s) / seconds
