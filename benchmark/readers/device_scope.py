"""Device seconds of the ops under a ``jax.named_scope``, per unit.

The fused step names its kernels with scopes (``fused.receive_sort`` /
``key_sort``, ``row_gather``); XLA's own names for them (``fusion``,
``sort.11``) change with any edit to the program. A metric's file gives a
``match``, a regular expression searched in each op's scope path.

Where the scope is: an ``XLA Ops`` event's HLO ``op_name`` is the ``tf_op``
stat of the event's *metadata* in the ``.xplane.pb``, e.g.
``jit(step)/fused.receive_sort/row_gather/jit(_take)/gather:``.
``jax.profiler.ProfileData`` gives an event's own stats only
(``device_offset_ps``, ``device_duration_ps``), not its metadata's, so the
scope is read from the ``<host>.trace.json.gz`` the profiler writes beside
the ``.xplane.pb``, whose events carry ``args.tf_op``.

Two steps, as in ``xplane.py``: ``load_scoped_ops`` turns that file into
plain lists, ``scope_seconds`` turns those into a number and is checked on
``fixtures/scoped_ops_small.json``. A program without the scopes (or a
run without a device trace) gives ``None``: nothing to read.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

from benchmark import manifest, xplane


def newest_profile() -> str:
    """The newest ``.xplane.pb`` under ``.bench_scratch/profile_*``: the
    profile this run just wrote (``Reading`` carries no path)."""
    found = glob.glob(os.path.join(
        manifest.ROOT, ".bench_scratch", "profile_*", "plugins", "profile",
        "*", "*.xplane.pb"))
    if not found:
        raise FileNotFoundError("no .xplane.pb under .bench_scratch/profile_*")
    return max(found, key=os.path.getmtime)


def load_scoped_ops(xplane_path: str) -> dict:
    """``{"units": [[start_ns, duration_ns], ...], "chips": {id: [[scope,
    start_ns, duration_ns], ...]}}`` from the trace-viewer JSON beside
    ``xplane_path``: the ``bench.unit`` annotations of the host, and per
    device plane every ``XLA Ops`` event with its scope path ('' where
    the op has none)."""
    beside = glob.glob(os.path.join(os.path.dirname(xplane_path),
                                    "*.trace.json.gz"))
    if not beside:
        raise FileNotFoundError(
            f"the profiler wrote no .trace.json.gz beside {xplane_path}")
    with gzip.open(beside[0], "rt") as f:
        events = json.load(f)["traceEvents"]
    chip_of: dict = {}    # pid -> device id
    ops_lines = set()     # (pid, tid) of the XLA Ops lines
    for e in events:
        if e.get("ph") != "M":
            continue
        name = e.get("args", {}).get("name", "")
        if e["name"] == "process_name":
            if m := xplane.DEVICE_PLANE.match(name):
                chip_of[e["pid"]] = m.group(1)
        elif e["name"] == "thread_name" and name == xplane.OPS_LINE:
            ops_lines.add((e["pid"], e["tid"]))
    units: list = []
    chips: dict = {chip: [] for chip in chip_of.values()}
    for e in events:
        if e.get("ph") != "X":
            continue
        span = [e["ts"] * 1e3, e["dur"] * 1e3]   # microseconds, as floats
        if e["name"] == xplane.UNIT_SPAN:
            units.append(span)
        elif e["pid"] in chip_of and (e["pid"], e["tid"]) in ops_lines:
            chips[chip_of[e["pid"]]].append(
                [e.get("args", {}).get("tf_op", ""), *span])
    return {"units": sorted(units), "chips": chips}


def scope_seconds(scoped: dict, pattern: str, chips: int):
    """Seconds a unit spends, a chip, in the ops whose scope matches
    ``pattern``: their durations inside the window of the units (first
    start to last end), summed over all chips, over ``chips`` (the
    cell's; a chip it does not use adds nothing) and over the units.
    ``None`` where no op in the window matches."""
    units = scoped["units"]
    lo = units[0][0] * 1e-9
    hi = (units[-1][0] + units[-1][1]) * 1e-9
    pat = re.compile(pattern)
    hit = [e - s for ops in scoped["chips"].values()
           for scope, s, e in xplane._clip(ops, lo, hi) if pat.search(scope)]
    return sum(hit) / chips / len(units) if hit else None


def read(reading, spec):
    if reading.trace is None:
        return None
    scoped = load_scoped_ops(newest_profile())
    if len(scoped["units"]) != reading.trace["units"]:
        raise RuntimeError(
            f"the newest profile holds {len(scoped['units'])} "
            f"{xplane.UNIT_SPAN} events, this run's trace "
            f"{reading.trace['units']}: it is another run's profile")
    return scope_seconds(scoped, spec["reader"]["match"],
                         reading.trace["chips"])
