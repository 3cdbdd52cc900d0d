"""Peaks of the chip and the bytes the VCC problem itself defines.

A kernel's roofline share is the least time the chip could take for the
bytes its problem defines, at the published HBM bandwidth, over the
kernel's device time. The bytes are counted from shapes, so the count
is the same whatever implements the epoch.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4
# (n, H) operands of a PGD epoch: delta, eta, pi, pow_nom, lo, ub, and the
# delta it writes back
EPOCH_WIDE = 7
# per-cluster scalars: tau / 24, peak price, learning rate, softmax
# temperature, carbon price
EPOCH_SLIM = 5


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error."""
    table = json.loads((Path(__file__).with_name("peaks.json")).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json") from None


def pgd_epoch_bytes(rows: int, hours: int = 24) -> int:
    """Bytes one PGD epoch call reads and writes for ``rows`` cluster rows
    (summed over a batch): the problem's inputs and the delta output."""
    return rows * (EPOCH_WIDE * hours + EPOCH_SLIM) * F32


def roofline_pct(nbytes: float, kernel_s: float, hbm_bytes_per_s: float):
    """Share (%) of the HBM roofline, or None when there is no kernel
    time to compare with."""
    if kernel_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * (nbytes / hbm_bytes_per_s) / kernel_s
