"""One module per *kind* of configuration (a config file's ``kind``).

A driver module provides ``Workload(config, sizes, devices, seed,
scratch)``, which sets the system up from the seed and has:

* ``unit_bytes`` — bytes one unit shuffles; ``info`` — its shapes;
* ``run_unit()`` — one whole shuffle, blocking, returning its facts;
* ``unit_problems(facts)`` — which guarantees that unit broke;
* ``verify_last()`` — the last unit's whole output against the plain
  reference, as a list of what differs;
* ``close()``.

``sizes`` is the traffic file (its ``rehearsal`` block laid over it in a
rehearsal). A new config of an existing kind is a data file only.
"""

from __future__ import annotations

import importlib


def load(kind: str):
    try:
        return importlib.import_module(f"benchmark.drivers.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.drivers.{kind}":
            raise
        raise ValueError(f"no driver for config kind {kind!r} under "
                         "benchmark/drivers/") from e
