"""``fused_step_roofline``: least time a step could take on this chip,
over the device time it took, in percent.

Least time is the larger of HBM time (every resident row read once and
written once) and ICI time (the rows that leave the chip), from
``peaks.py``; device time is the union of device-op intervals per step,
as ``fused_step_device_s`` reads it. The result also says which of the
two bounds (``bound_by``) and the least time itself (``least_s``).
"""

from __future__ import annotations

from benchmark import peaks


def read(reading, spec):
    if reading.trace is None:
        return None
    device_s = reading.trace["busy_s"] / reading.trace["units"]
    info = reading.info
    least_s, bound_by = peaks.least_seconds(
        peaks.fused_step_bytes(info["rows_per_chip"], info["row_bytes"],
                               info["chips"]),
        peaks.peaks_for(reading.device_kind))
    return {"value": 100.0 * least_s / device_s, "bound_by": bound_by,
            "least_s": least_s}
