"""The benchmark: the yardstick later PRs are measured with and may not edit.

``run.py`` runs one cell of ``BENCHMARK.json`` once. Everything that
belongs to one configuration, one traffic mix or one per-layer metric is
a data file found by its name (``configs/``, ``traffic/``,
``layer_metrics/``); ``drivers/`` holds one module per *kind* of
configuration, ``readers/`` the per-layer metric readers. From the
program the benchmark takes only the system under test, its spans, its
counters and its device-op names; traffic, reference, trace reduction,
peaks and byte counts live here.
"""
