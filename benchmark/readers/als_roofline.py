"""``als_job_roofline`` and ``als_normal_roofline``: least time the chip
could take over the device time it took, in percent.

Least time is from ``als_bytes.py`` (the problem's bytes) over
``peaks.py``'s published peaks. Device time: for ``"of": "job"`` the union
of device-op intervals per job, as ``als_job_device_s`` reads it; for
``"of": "normal"`` the seconds under the spec's ``match`` scopes (the
gather and the normal equations), as ``als_gather_s`` + ``als_normal_s``
read them. The result also says which peak bounds (``bound_by``) and the
least time (``least_s``). A run without a device trace, or a program
without the scopes, gives ``None``.
"""

from __future__ import annotations

from benchmark import als_bytes, peaks
from benchmark.readers import device_scope


def share(least_bytes: dict, device_s: float, device_kind: str) -> dict:
    least_s, bound_by = peaks.least_seconds(least_bytes,
                                            peaks.peaks_for(device_kind))
    return {"value": 100.0 * least_s / device_s, "bound_by": bound_by,
            "least_s": least_s}


def read(reading, spec):
    if reading.trace is None:
        return None
    info = reading.info
    shapes = (info["ratings_per_chip"], info["recv_rows_per_chip"],
              info["ids_per_chip"], info["rank"])
    if spec["reader"]["of"] == "job":
        return share(
            als_bytes.job_bytes(*shapes, info["chips"], info["iterations"]),
            reading.trace["busy_s"] / reading.trace["units"],
            reading.device_kind)
    device_s = device_scope.read(reading, spec)
    if device_s is None:
        return None
    return share(als_bytes.normal_bytes(*shapes, info["iterations"]),
                 device_s, reading.device_kind)
