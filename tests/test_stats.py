"""Observability tests (reference: scala/RdmaShuffleReaderStats.scala)."""

import logging

from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.utils.stats import (
    FetchHistogram,
    ShuffleReaderStats,
)


def test_histogram_bucketing():
    h = FetchHistogram(bucket_ms=100, num_buckets=3)
    for ms in (10, 99, 150, 250, 950):
        h.add(ms / 1e3)
    s = h.summary()
    assert s["count"] == 5
    buckets = list(s["buckets"].values())
    assert buckets == [2, 1, 1, 1]  # <100, <200, <300, overflow
    assert s["mean_ms"] == round((10 + 99 + 150 + 250 + 950) / 5, 3)


def test_reader_stats_per_remote():
    stats = ShuffleReaderStats(TpuShuffleConf(fetch_time_bucket_size_ms=50,
                                              fetch_time_num_buckets=4))
    stats.update(0, 0.01)
    stats.update(0, 0.02)
    stats.update(3, 0.5)
    snap = stats.snapshot()
    assert snap["global"]["count"] == 3
    assert snap["per_remote"]["0"]["count"] == 2
    assert snap["per_remote"]["3"]["count"] == 1
    stats.log_summary(logging.getLogger("test"))  # must not raise


def test_device_profile_captures_xla_trace(tmp_path):
    """utils.trace.device_profile wraps a jitted step and leaves an XLA
    profile on disk (the device-side half of the observability story),
    with a live tracer's span in it, on the profiler's clock."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from sparkrdma_tpu.utils.trace import Tracer, device_profile

    tracer = Tracer()
    with device_profile(str(tmp_path)):
        with tracer.span("exchange.round", "exchange", round=0):
            jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.ones(128)))
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert found, "no xplane profile written"
    names = [e.name for plane in ProfileData.from_file(found[0]).planes
             for line in plane.lines for e in line.events]
    assert names.count("exchange.round") == 1
    assert [e["name"] for e in tracer._events] == ["exchange.round"]


def test_device_profile_raises_when_the_profiler_cannot_start(tmp_path):
    """A second session cannot start inside a running one: that is an
    error for the caller, not a warning and an unprofiled run."""
    import pytest

    from sparkrdma_tpu.utils.trace import device_profile

    with device_profile(str(tmp_path / "outer")):
        with pytest.raises(RuntimeError, match="already"):
            with device_profile(str(tmp_path / "inner")):
                pass


def test_tracers_of_one_process_share_a_clock():
    """Two tracers made at different times stamp the same instant alike,
    so their dumps overlay (driver + executors in one process)."""
    import time

    from sparkrdma_tpu.utils.trace import Tracer

    first = Tracer()
    time.sleep(0.01)
    second = Tracer()
    a, b = first.now_us(), second.now_us()
    # one monotonic clock: with an origin per tracer the younger one
    # would read some 10 ms less
    assert b >= a
