"""Per-layer metric readers.

A metric's file under ``layer_metrics/`` carries a ``reader``. Three are
declarative and need no code: ``host_span`` (the program's ``Tracer``
spans, per unit), ``device_op`` (device seconds of the ops whose name
matches) and ``device_busy`` (the union of all device ops). A metric with
arithmetic of its own says ``{"source": "module", "module": "<name>"}``
and ``readers/<name>.py`` provides ``read(reading, spec)``.

A reader that finds nothing to read returns ``None`` and the harness
leaves the metric out of the line.
"""

from __future__ import annotations

import importlib
import re
import statistics
from dataclasses import dataclass


@dataclass
class Reading:
    """What one run gives the readers."""
    units: list         # per unit: {"start", "end", "events": [chrome X/i]}
    trace: dict | None  # xplane.reduce_trace() of the traced stretch
    info: dict          # the driver's shapes: rows_per_chip, row_bytes, chips
    device_kind: str


def _host_span(reading: Reading, reader: dict):
    where = reader.get("where", {})
    within = {"sum_per_unit": sum,
              "median_per_unit": statistics.median}[reader["reduce"]]
    per_unit = []
    for unit in reading.units:
        durs = [e["dur"] * 1e-6 for e in unit.get("events", [])
                if e.get("ph") == "X" and e["name"] == reader["match"]
                and all(k in e.get("args", {})
                        for k in where.get("args_has", []))
                and not any(k in e.get("args", {})
                            for k in where.get("args_lacks", []))]
        if durs:
            per_unit.append(within(durs))
    return statistics.median(per_unit) if per_unit else None


def _device_op(reading: Reading, reader: dict):
    if reading.trace is None:
        return None
    pat = re.compile(reader["match"])
    hit = [s for name, s in reading.trace["op_s"].items() if pat.search(name)]
    return sum(hit) / reading.trace["units"] if hit else None


def _device_busy(reading: Reading, reader: dict):
    if reading.trace is None:
        return None
    return reading.trace["busy_s"] / reading.trace["units"]


# source -> (reader, the reduces it knows)
_DECLARATIVE = {
    "host_span": (_host_span, ("sum_per_unit", "median_per_unit")),
    "device_op": (_device_op, ("sum_per_unit_mean_chips",)),
    "device_busy": (_device_busy, ("per_unit_mean_chips",)),
}


def read_metric(spec: dict, reading: Reading):
    """``{"value": ..., "unit": ...}`` (a module reader may add keys), or
    ``None`` when there was nothing to read."""
    reader = spec["reader"]
    if reader["source"] == "module":
        mod = importlib.import_module(f"benchmark.readers.{reader['module']}")
        got = mod.read(reading, spec)
    elif reader["source"] in _DECLARATIVE:
        read, reduces = _DECLARATIVE[reader["source"]]
        if reader["reduce"] not in reduces:
            raise ValueError(f"{reader['source']}: reduce "
                             f"{reader['reduce']!r} is none of {reduces}")
        got = read(reading, reader)
    else:
        raise ValueError(f"unknown reader source {reader['source']!r}")
    if got is None:
        return None
    if not isinstance(got, dict):
        got = {"value": got}
    return dict(got, unit=spec["unit"])
