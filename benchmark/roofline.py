"""What the quorum kernels need of the chip, from shapes alone.

One dispatch of ``quorum_step_impl`` (or of its dense twin
``quorum_step_dense_impl``, or of the fused K-round
``quorum_multiround_impl``) is handed the whole struct-of-arrays state of a
host's engine, donated, and hands back the next one: at the least every
leaf is read once and written once.  The staged events and the egress are
left out (they are small beside the state and vary by round), so the bytes
counted are a floor and the share of the roofline a floor with them.  The
arithmetic is a few integer compares and selects per (group, peer) cell, for
which the chip's documentation publishes no peak: the bound is memory.
"""
from __future__ import annotations

import json
import os

import numpy as np

BOUND = "memory"


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def state_bytes(leaves) -> int:
    """Bytes of a device state given (shape, dtype) of every leaf."""
    return sum(
        int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        for shape, dtype in leaves
    )


def dispatch_min_seconds(leaves, device_kind: str) -> float:
    """The least time one dispatch can take on this chip."""
    return 2 * state_bytes(leaves) / peaks(device_kind)["hbm_bytes_per_s"]
