"""From the profiler's ``.xplane.pb`` to busy time, idle gaps and op seconds.

Two steps, so the arithmetic can be checked on a small recorded trace
(``fixtures/trace_small.json``) without a chip: ``load_xplane`` turns the
protobuf into plain lists, ``reduce_trace`` turns those into numbers.

A trace, as plain data: ``{"planes": {plane: {line: [[name, start_ns,
duration_ns], ...]}}}``. Device planes are named ``/device:TPU:<id>``;
their ``XLA Ops`` line holds one event per executed HLO instruction. The
host plane ``/host:CPU`` holds one line per thread, with the benchmark's
own ``bench.unit`` annotation around every unit.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
UNIT_SPAN = "bench.unit"
# a span of the program or the benchmark, ``layer.thing``; the runtime's
# own events ("PjitFunction(step)", "tpu::System::Execute") are not
PROGRAM_SPAN = re.compile(r"^[a-z_][a-z_0-9]*(\.[a-z_0-9]+)+$")
SHORT_GAP_S = 10e-6
NO_SPAN = "_no_host_span_"
SHORT_GAPS = "_gaps_under_10_us_"
TOP = 10


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``; none is an error."""
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under "
                                f"{log_dir}")
    return max(found, key=os.path.getmtime)


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events)
    return {"planes": planes}


def op_name(raw: str) -> str:
    """``%fusion.2 = u32[..] fusion(..)`` -> ``fusion.2``; a name that is
    already short stays."""
    m = re.match(r"^%?([^\s=]+)\s*=", raw)
    return m.group(1) if m else raw.lstrip("%")


def _union(intervals: list) -> list:
    merged: list = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _clip(events: list, lo: float, hi: float) -> list:
    """``(name, start, end)`` in seconds of the events that touch
    ``[lo, hi]``, cut to it."""
    out = []
    for name, start_ns, dur_ns in events:
        s, e = start_ns * 1e-9, (start_ns + dur_ns) * 1e-9
        if e > lo and s < hi:
            out.append((name, max(s, lo), min(e, hi)))
    return out


def _top(totals: dict) -> list:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:TOP]]


def reduce_trace(trace: dict, device_ids=None) -> dict:
    """Busy and idle time of the devices over the traced units.

    The window runs from the start of the first ``bench.unit`` annotation
    to the end of the last. Per device: busy is the union of its ``XLA
    Ops`` intervals inside the window; every idle gap goes to the
    innermost program span that covers its middle. Everything returned is
    a mean over the devices (``device_ids``, or every device plane).
    Returns ``None`` when the trace holds no device plane or no unit.
    """
    planes = trace["planes"]
    host = planes.get(HOST_PLANE, {})
    units = sorted((s * 1e-9, (s + d) * 1e-9) for events in host.values()
                   for name, s, d in events if name == UNIT_SPAN)
    chips = {int(m.group(1)): lines for name, lines in planes.items()
             if (m := DEVICE_PLANE.match(name))}
    if device_ids is not None:
        chips = {i: chips[i] for i in device_ids if i in chips}
    if not units or not chips:
        return None
    lo, hi = units[0][0], units[-1][1]
    spans = [(s, e, name) for events in host.values()
             for name, s, e in _clip(events, lo, hi)
             if PROGRAM_SPAN.match(name)]

    busy_s = 0.0
    op_s: dict = {}
    gap_s: dict = {}
    for lines in chips.values():
        ops = _clip(lines.get(OPS_LINE, []), lo, hi)
        for name, s, e in ops:
            op_s[name] = op_s.get(name, 0.0) + (e - s)
        busy = _union([[s, e] for _, s, e in ops])
        busy_s += sum(e - s for s, e in busy)
        edges = [lo, *[t for iv in busy for t in iv], hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 <= 0:
                continue
            if g1 - g0 < SHORT_GAP_S:
                who = SHORT_GAPS
            else:
                mid = (g0 + g1) / 2
                inside = [(e - s, name) for s, e, name in spans
                          if s <= mid <= e]
                who = min(inside)[1] if inside else NO_SPAN
            gap_s[who] = gap_s.get(who, 0.0) + (g1 - g0)
    n = len(chips)
    return {
        "units": len(units),
        "window_s": hi - lo,
        "busy_s": busy_s / n,
        "chips": n,
        # keyed by the raw event name; readers match on it
        "op_s": {k: v / n for k, v in op_s.items()},
        "idle_gap_s": {k: v / n for k, v in gap_s.items()},
    }


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``: top device ops and idle gaps."""
    short: dict = {}
    for raw, s in reduced["op_s"].items():
        short[op_name(raw)] = short.get(op_name(raw), 0.0) + s
    return {"device_ops": _top(short),
            "idle_gaps": _top(reduced["idle_gap_s"])}
