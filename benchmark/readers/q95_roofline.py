"""``q95_job_roofline``: least time the chip could take over the device
time a job took, in percent.

Least time is from ``q95_bytes.py`` (the problem's bytes) over
``peaks.py``'s published peaks; device time is the union of device-op
intervals per job, as ``q95_job_device_s`` reads it. The result also says
which peak bounds (``bound_by``) and the least time (``least_s``). A run
without a device trace gives ``None``.
"""

from __future__ import annotations

from benchmark import peaks, q95_bytes


def read(reading, spec):
    if reading.trace is None:
        return None
    info = reading.info
    least_s, bound_by = peaks.least_seconds(
        q95_bytes.job_bytes(info["ws_rows_per_chip"],
                            info["wr_rows_per_chip"],
                            info["survivors_per_chip"], info["chips"]),
        peaks.peaks_for(reading.device_kind))
    device_s = reading.trace["busy_s"] / reading.trace["units"]
    return {"value": 100.0 * least_s / device_s, "bound_by": bound_by,
            "least_s": least_s}
