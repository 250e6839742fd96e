"""What a span's thread did, per unit: one accounting arg of the program's
``Tracer`` spans (or the sum of several), summed over the spans of one
name in a unit, median over the units, as ``host_span`` reads ``dur``.

A live tracer's span carries ``cpu_user_s``, ``cpu_sys_s``, ``minflt``,
``majflt``, ``nvcsw``, ``nivcsw`` (the thread's) and ``proc_cpu_s`` (the
whole process's, meanwhile) in its ``args`` (``utils/trace.py``). A
metric's file gives ``match`` (the span's name), ``args`` (the names to
add) and, optionally, ``host_span``'s ``where``. A span without every one
of the ``args`` (a program from before the accounting, a platform without
``RUSAGE_THREAD``) is not read; where no unit has a span to read the
reader returns ``None``. So it does for a run without a device trace (a
rehearsal), as every module reader: ``tests/benchmark/test_benchmark.py``
holds a rehearsal's traced line to the ``host_span`` metrics.
"""

from __future__ import annotations

import statistics


def read(reading, spec):
    if reading.trace is None:
        return None
    reader = spec["reader"]
    where = reader.get("where", {})
    per_unit = []
    for unit in reading.units:
        spans = [e["args"] for e in unit.get("events", [])
                 if e.get("ph") == "X" and e["name"] == reader["match"]
                 and all(k in e.get("args", {})
                         for k in (*reader["args"],
                                   *where.get("args_has", [])))
                 and not any(k in e["args"]
                             for k in where.get("args_lacks", []))]
        if spans:
            per_unit.append(sum(args[k] for args in spans
                                for k in reader["args"]))
    return statistics.median(per_unit) if per_unit else None
