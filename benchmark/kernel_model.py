"""What the kernel ``gf_matmul_r{R}_k{K}`` has to do for one call, from its
shapes alone, and the least time a chip could take for it.

The GF(2^8) product of an R x K byte matrix with K x N bytes is computed as
one int8 matrix product of the (8R x 8K) bit matrix with the (8K x N) bit
planes of the data, reduced mod 2: 8R * 8K * N multiply-adds, two
operations each. It has to read K * N bytes, write R * N and read the bit
matrix (8R * 8K int8) once.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def gf_matmul_cost(rows: int, k: int, n: int) -> dict:
    return {
        "ops": 2 * (8 * rows) * (8 * k) * n,
        "bytes": k * n + rows * n + (8 * rows) * (8 * k),
        "input_bytes": k * n,
    }


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``. A device that is
    not in the table is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"{PEAKS_FILE} with its source"
        )
    return table[device_kind]


def roofline(cost: dict, kernel_s: float, peaks: dict) -> dict:
    """The share of its roofline a kernel reached: the least time the chip
    could take (the larger of operations over peak int8 rate and bytes over
    peak HBM rate) over the time it took, and which of the two bounds it."""
    by_ops = cost["ops"] / peaks["int8_ops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "share": 100.0 * max(by_ops, by_bytes) / kernel_s,
        "bound": "hbm" if by_bytes >= by_ops else "int8",
        "least_s": max(by_ops, by_bytes),
    }
