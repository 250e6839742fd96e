"""``pagerank_job_roofline`` and ``pagerank_accumulate_roofline``: least
time the chip could take over the device time it took, in percent.

Least time is from ``pagerank_bytes.py`` (the problem's bytes) over
``peaks.py``'s published peaks. Device time: for ``"of": "job"`` the union
of device-op intervals per job, as ``pagerank_job_device_s`` reads it; for
``"of": "accumulate"`` the seconds under the spec's ``match`` scope, as
``pagerank_accumulate_s`` reads them. A chip receives, over the chips'
mean, as many records as it has edges. The result also says which peak
bounds (``bound_by``) and the least time (``least_s``). A run without a
device trace, or a program without the scope, gives ``None``.
"""

from __future__ import annotations

from benchmark import pagerank_bytes, peaks
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
    if spec["reader"]["of"] == "job":
        return share(
            pagerank_bytes.job_bytes(
                info["edges_per_chip"], info["vertices_per_chip"],
                info["chips"], info["iterations"]),
            reading.trace["busy_s"] / reading.trace["units"],
            reading.device_kind)
    device_s = device_scope.read(reading, spec)
    if device_s is None:
        return None
    return share(
        pagerank_bytes.accumulate_bytes(
            info["edges_per_chip"], info["vertices_per_chip"],
            info["iterations"]),
        device_s, reading.device_kind)
