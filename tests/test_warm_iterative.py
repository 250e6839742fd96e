"""Warm iterative reuse: superstep N>=1 over an unchanged shuffle puts
ZERO metadata RPCs on the wire (the acceptance gate of the one-sided
metadata plane), and — with ``warm_read_cache`` — zero data RPCs too.

Wire traffic is counted SERVER-side (handler invocations per received
frame at the driver and the serving peer), so the assertions hold at
the frame level, not just the client counters. Every dataplane
combination is covered; epoch bumps (re-execution overwrites) must
invalidate and force a fresh snapshot + fresh bytes.
"""

import time

import numpy as np
import pytest

from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.shuffle import dist_cache
from sparkrdma_tpu.shuffle.manager import PartitionerSpec, TpuShuffleManager
from sparkrdma_tpu.shuffle.reader import TpuShuffleReader

CONF_KW = dict(connect_timeout_ms=5000, use_cpp_runtime=False,
               pre_warm_connections=False)


def _cluster(tmp_path, n=2, **kw):
    conf = TpuShuffleConf(**dict(CONF_KW, **kw))
    driver = TpuShuffleManager(conf, is_driver=True)
    execs = [TpuShuffleManager(conf, driver_addr=driver.driver_addr,
                               executor_id=str(i),
                               spill_dir=str(tmp_path / f"e{i}"))
             for i in range(n)]
    for ex in execs:
        ex.executor.wait_for_members(n)
    return driver, execs


def _shutdown(driver, execs):
    for ex in execs:
        ex.stop()
    driver.stop()


def _write_maps(execs, handle, version=0, owner=0):
    for m in range(handle.num_maps):
        w = execs[owner].get_writer(handle, m)
        rng = np.random.default_rng(100 * version + m)
        w.write_batch(rng.integers(0, 64, 256).astype(np.uint64))
        w.close()


class _WireCounters:
    """Server-side frame counts: every received metadata/data request
    increments here, exactly once per frame on the wire."""

    def __init__(self, driver, serving_exec):
        self.counts = {"table": 0, "loc_per_map": 0, "loc_batched": 0,
                       "blocks": 0}
        drv = driver.driver
        ep = serving_exec.executor
        orig_table = drv._on_fetch_table
        orig_one, orig_many = ep._on_fetch_output, ep._on_fetch_outputs
        orig_blocks = ep._on_fetch_blocks

        def wrap(key, orig):
            def handler(*a):
                self.counts[key] += 1
                return orig(*a)
            return handler

        drv._on_fetch_table = wrap("table", orig_table)
        ep._on_fetch_output = wrap("loc_per_map", orig_one)
        ep._on_fetch_outputs = wrap("loc_batched", orig_many)
        ep._on_fetch_blocks = wrap("blocks", orig_blocks)

    @property
    def metadata(self):
        c = self.counts
        return c["table"] + c["loc_per_map"] + c["loc_batched"]

    def snapshot(self):
        return dict(self.counts)


def _superstep(execs, handle, conf):
    """One reducer pass over the whole partition range (a superstep's
    read of an unchanged parent shuffle). Returns (sorted keys, metrics)."""
    reader = TpuShuffleReader(execs[1].executor, execs[1].resolver, conf,
                              handle.shuffle_id, handle.num_maps, 0,
                              handle.num_partitions,
                              handle.row_payload_bytes)
    keys, _ = reader.read_all()
    return np.sort(keys), reader.metrics


def _native_available():
    from sparkrdma_tpu.runtime import native

    return native.available()


DATAPLANES = [
    ("coalesced_seq", dict(coalesce_reads=True, read_ahead_depth=1)),
    ("coalesced_win", dict(coalesce_reads=True, read_ahead_depth=8)),
    ("per_map_seq", dict(coalesce_reads=False, read_ahead_depth=1)),
    ("per_map_pipe", dict(coalesce_reads=False, read_ahead_depth=8)),
    # data bytes served by the native block server (metadata always
    # rides the control plane, so the zero-RPC warm contract must hold
    # identically there)
    ("native_blocks", dict(coalesce_reads=True, read_ahead_depth=8,
                           use_cpp_runtime=True)),
]


@pytest.mark.parametrize("name,kw", DATAPLANES)
def test_warm_superstep_issues_zero_location_rpcs(tmp_path, name, kw):
    """The acceptance gate: superstep N>=1 over unchanged inputs puts no
    FetchTableReq / FetchOutputReq / FetchOutputsReq frames on the wire
    — on every dataplane — and the reduce output is byte-identical to
    the cold path."""
    if kw.get("use_cpp_runtime") and not _native_available():
        pytest.skip("native runtime not built")
    driver, execs = _cluster(tmp_path, **kw)
    try:
        handle = driver.register_shuffle(1, num_maps=6, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        _write_maps(execs, handle)
        conf = TpuShuffleConf(**dict(CONF_KW, **kw))
        wire = _WireCounters(driver, execs[0])

        cold, m_cold = _superstep(execs, handle, conf)
        cold_meta = wire.metadata
        assert cold_meta > 0, "cold superstep issued no metadata RPCs?"
        assert m_cold.metadata_rpcs_per_stage == cold_meta

        for step in range(1, 4):
            warm, m_warm = _superstep(execs, handle, conf)
            np.testing.assert_array_equal(warm, cold,
                                          err_msg=f"{name} step {step}")
            assert wire.metadata == cold_meta, \
                f"{name} superstep {step} put metadata frames on the wire: " \
                f"{wire.snapshot()}"
            assert m_warm.metadata_rpcs_per_stage == 0
            assert m_warm.location_cache_hits == handle.num_maps
        # data frames still flow on the warm path (only metadata is
        # cached; warm_read_cache covers the bytes — separate test).
        # With a native block server the data reads land on ITS port,
        # invisible to the control-plane counter — which is the point.
        if not kw.get("use_cpp_runtime"):
            assert wire.counts["blocks"] > 0
    finally:
        _shutdown(driver, execs)


def test_repair_overwrite_invalidates_warm_path(tmp_path):
    """A re-execution overwrite bumps the epoch; the pushed invalidation
    forces the next superstep back to a fresh snapshot — which serves
    the NEW owner's bytes, never the cached dead location."""
    driver, execs = _cluster(tmp_path, n=3)
    try:
        handle = driver.register_shuffle(1, num_maps=4, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        _write_maps(execs, handle, version=0, owner=0)
        conf = TpuShuffleConf(**CONF_KW)
        cold, _ = _superstep(execs, handle, conf)
        warm, m = _superstep(execs, handle, conf)
        assert m.metadata_rpcs_per_stage == 0
        np.testing.assert_array_equal(warm, cold)

        # re-execute map 0 on a DIFFERENT executor with different rows
        # (version 1): the publish overwrites the entry -> epoch bump
        w = execs[2].get_writer(handle, 0)
        rng = np.random.default_rng(999)
        new_rows = rng.integers(64, 128, 256).astype(np.uint64)
        w.write_batch(new_rows)
        w.close()
        # the publish is one-sided: wait for the driver to apply + bump,
        # then for the push to land at the reducer
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and driver.driver.epoch_of(1) != 2:
            time.sleep(0.01)
        assert driver.driver.epoch_of(1) == 2
        plane = execs[1].executor.location_plane
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and plane.known_epoch(1) != 2:
            time.sleep(0.01)
        assert plane.known_epoch(1) == 2

        fresh, m2 = _superstep(execs, handle, conf)
        assert m2.metadata_rpcs_per_stage > 0, \
            "post-bump superstep served stale cached locations"
        expect = np.sort(np.concatenate(
            [new_rows] + [np.random.default_rng(100 * 0 + m2_)
                          .integers(0, 64, 256) for m2_ in range(1, 4)]
        ).astype(np.uint64))
        np.testing.assert_array_equal(fresh, expect)
    finally:
        _shutdown(driver, execs)


def test_warm_read_cache_serves_bytes_locally(tmp_path):
    """``warm_read_cache``: superstep N>=1 moves NOTHING on the wire —
    no metadata frames, no data frames — and returns identical bytes."""
    driver, execs = _cluster(tmp_path, warm_read_cache=True)
    try:
        handle = driver.register_shuffle(1, num_maps=4, num_partitions=4,
                                         partitioner=PartitionerSpec("modulo"))
        _write_maps(execs, handle)
        conf = TpuShuffleConf(**dict(CONF_KW, warm_read_cache=True))
        wire = _WireCounters(driver, execs[0])
        cold, _ = _superstep(execs, handle, conf)
        snap = wire.snapshot()
        assert snap["blocks"] > 0
        warm, m = _superstep(execs, handle, conf)
        np.testing.assert_array_equal(warm, cold)
        assert wire.snapshot() == snap, \
            f"warm superstep touched the wire: {wire.snapshot()} != {snap}"
        assert m.warm_range_hits == 1
        assert m.metadata_rpcs_per_stage == 0
        # the returned batch is a private copy: mutation can't poison
        warm[:8] = 0
        again, _ = _superstep(execs, handle, conf)
        np.testing.assert_array_equal(again, cold)
    finally:
        _shutdown(driver, execs)


def test_warm_read_cache_epoch_bump_serves_fresh_bytes(tmp_path):
    driver, execs = _cluster(tmp_path, n=3, warm_read_cache=True)
    try:
        handle = driver.register_shuffle(1, num_maps=2, num_partitions=2,
                                         partitioner=PartitionerSpec("modulo"))
        _write_maps(execs, handle, version=0, owner=0)
        conf = TpuShuffleConf(**dict(CONF_KW, warm_read_cache=True))
        cold, _ = _superstep(execs, handle, conf)
        warm, m = _superstep(execs, handle, conf)
        assert m.warm_range_hits == 1
        # re-execute map 1 elsewhere with new rows -> epoch bump
        w = execs[2].get_writer(handle, 1)
        new_rows = np.arange(1000, 1256, dtype=np.uint64)
        w.write_batch(new_rows)
        w.close()
        plane = execs[1].executor.location_plane
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and plane.known_epoch(1) != 2:
            time.sleep(0.01)
        assert plane.known_epoch(1) == 2
        fresh, m2 = _superstep(execs, handle, conf)
        assert m2.warm_range_hits == 0
        expect = np.sort(np.concatenate(
            [np.random.default_rng(0).integers(0, 64, 256),
             new_rows]).astype(np.uint64))
        np.testing.assert_array_equal(fresh, expect)
    finally:
        _shutdown(driver, execs)


# -- the iterative bench (acceptance gate) -------------------------------


def test_iterative_warm_bench_acceptance(tmp_path):
    """The bench secondary's tier-1 assertion: over a PageRank-style
    10-superstep loop, warm supersteps issue ZERO metadata RPCs, the
    bytes are identical, and the per-superstep improvement vs per-stage
    cold metadata clears 1.5x (with the fixed metadata service delay
    standing in for control-plane RTT, see shuffle/iter_bench.py)."""
    from sparkrdma_tpu.shuffle.iter_bench import run_iterative_microbench

    from sparkrdma_tpu.utils.benchgate import gated_best_of

    res = gated_best_of(
        lambda: run_iterative_microbench(str(tmp_path), supersteps=10,
                                         delay_s=0.008))
    assert res["identical"], "cold and warm supersteps diverged"
    assert res["metadata_rpcs_per_superstep"]["warm"] == 0.0, res
    assert res["metadata_rpcs_per_superstep"]["cold"] >= 2.0, res
    assert res["speedup"] >= 1.5, res


def test_dense_exchange_bench_guard():
    """The dense-exchange regression guard (bench satellite): dense and
    gather step the same rows in the same process — the recorded ratio
    cancels host noise, so a dense-specific regression is attributable
    per bench round. At micro size the ratio just has to be sane and
    both transports must actually run."""
    import bench as bench_mod
    import jax
    from jax.sharding import Mesh

    from sparkrdma_tpu.models.terasort import TeraSortConfig, generate_rows

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("shuffle",))
    cfg = TeraSortConfig(rows_per_device=512, payload_words=24,
                         out_factor=1 if len(devs) == 1 else 2)
    rows = generate_rows(cfg, len(devs), seed=1)
    detail = {}
    bench_mod._bench_dense_guard(detail, mesh, "dense", cfg, rows)
    assert "dense_exchange_guard" in detail, detail
    g = detail["dense_exchange_guard"]
    assert g["dense_step_s"] > 0 and g["gather_step_s"] > 0
    assert 0 < g["dense_vs_gather"] < 100


# -- dist_cache bounds (satellite) ---------------------------------------


def test_dist_cache_byte_budget_evicts_lru():
    dist_cache.configure(0)  # flush residue from earlier tests (the
    # cache is process-global on purpose — co-hosted managers share it)
    dist_cache.configure(10_000)
    try:
        k = np.zeros(500, dtype=np.uint64)      # 4000 B
        p = np.zeros((500, 1), dtype=np.uint8)  # 500 B
        base = dist_cache.evicted
        assert dist_cache.put_range(101, 1, 0, 4, k, p)
        assert dist_cache.put_range(102, 1, 0, 4, k.copy(), p.copy())
        assert dist_cache.get_range(101, 1, 0, 4) is not None
        # a third shuffle exceeds the budget: the LRU one (102 — 101 was
        # touched by the get above) evicts
        assert dist_cache.put_range(103, 1, 0, 4, k.copy(), p.copy())
        assert dist_cache.evicted == base + 1
        assert dist_cache.get_range(102, 1, 0, 4) is None
        assert dist_cache.get_range(101, 1, 0, 4) is not None
        assert dist_cache.get_range(103, 1, 0, 4) is not None
        stats = dist_cache.stats()
        assert stats["bytes"] <= stats["budget"]
        assert stats["evicted"] == dist_cache.evicted
    finally:
        for sid in (101, 102, 103):
            dist_cache.drop(sid)
        dist_cache.configure(256 << 20)


def test_dist_cache_oversized_entry_never_thrashes():
    dist_cache.configure(1000)
    try:
        big_k = np.zeros(1000, dtype=np.uint64)  # 8000 B > budget
        small = np.zeros(10, dtype=np.uint64)
        pay = np.zeros((10, 1), dtype=np.uint8)
        assert dist_cache.put_range(201, 1, 0, 1, small, pay)
        before = dist_cache.evicted
        assert not dist_cache.put_range(202, 1, 0, 1, big_k,
                                        np.zeros((1000, 1), np.uint8))
        # the resident small entry survived; nothing was evicted for a
        # lost cause
        assert dist_cache.evicted == before
        assert dist_cache.get_range(201, 1, 0, 1) is not None
    finally:
        dist_cache.drop(201)
        dist_cache.drop(202)
        dist_cache.configure(256 << 20)


def test_dist_cache_mesh_store_budgeted_too():
    dist_cache.configure(10_000)
    try:
        keys = np.zeros(500, dtype=np.uint64)
        payload = np.zeros((500, 1), dtype=np.uint8)
        parts = np.zeros(500, dtype=np.int64)
        base = dist_cache.evicted
        assert dist_cache.store(301, [(keys, payload, parts)]) == [0]
        assert dist_cache.store(302, [(keys, payload, parts)]) == [0]
        assert dist_cache.store(303, [(keys, payload, parts)]) == [0]
        assert dist_cache.evicted > base
        assert dist_cache.get(303, 0) is not None
        stats = dist_cache.stats()
        assert stats["bytes"] <= stats["budget"]
    finally:
        for sid in (301, 302, 303):
            dist_cache.drop(sid)
        dist_cache.configure(256 << 20)


def test_dist_cache_epoch_bump_evicts_stale_ranges():
    dist_cache.configure(1 << 20)
    try:
        k = np.arange(10, dtype=np.uint64)
        p = np.zeros((10, 1), dtype=np.uint8)
        dist_cache.put_range(401, 1, 0, 4, k, p)
        dist_cache.on_epoch(401, 2)
        assert dist_cache.get_range(401, 1, 0, 4) is None
        assert dist_cache.stats()["warm_shuffles"] == 0
        # terminal bump drops both stores
        dist_cache.put_range(401, 2, 0, 4, k, p)
        dist_cache.on_epoch(401, -1)
        assert dist_cache.get_range(401, 2, 0, 4) is None
    finally:
        dist_cache.drop(401)
        dist_cache.configure(256 << 20)
