"""Peaks of each chip, by ``device_kind`` (``peaks.json``). A chip that is
not in the table is an error, never a default."""
from __future__ import annotations

import json
import os


def peak(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, pk: dict) -> float:
    """The roofline: the least time the chip could take for the work."""
    return max(flops / pk["bf16_flop_per_s"], nbytes / pk["hbm_byte_per_s"])
