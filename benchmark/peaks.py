"""Published peaks by ``device_kind``, and the bytes a fused step must move.

A roofline share is least time over measured time. Least time comes from
here: bytes computed from the cell's shapes, over a published peak. A
device that is not in the table is an error, not a default.
"""

from __future__ import annotations

_V5E = {
    "hbm_bytes_per_s": 819e9,
    "ici_bytes_per_s": 1600e9 / 8,   # 1,600 Gbit/s chip-to-chip
    "bf16_flops_per_s": 197e12,
    "hbm_bytes": 16e9,
    "source": "Google Cloud TPU v5e documentation",
}
# jax reports a v5e chip as "TPU v5 lite"
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(have {sorted(PEAKS)}); add a row with its source")
    return PEAKS[device_kind]


def fused_step_bytes(rows_per_chip: int, row_bytes: int, chips: int) -> dict:
    """What one fused step cannot avoid, per chip: every resident row is
    read from HBM once and written once, and with uniform keys over
    ``chips`` even ranges the share ``(chips-1)/chips`` of a chip's rows
    leaves it over ICI."""
    resident = rows_per_chip * row_bytes
    return {"hbm_bytes": 2 * resident,
            "ici_bytes": resident * (chips - 1) / chips}


def least_seconds(step_bytes: dict, peaks: dict) -> tuple:
    """``(least_s, bound_by)``: the larger of HBM time and ICI time."""
    hbm_s = step_bytes["hbm_bytes"] / peaks["hbm_bytes_per_s"]
    ici_s = step_bytes["ici_bytes"] / peaks["ici_bytes_per_s"]
    return (hbm_s, "hbm") if hbm_s >= ici_s else (ici_s, "ici")
