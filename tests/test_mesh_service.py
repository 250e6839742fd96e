"""The full end-to-end slice (SURVEY.md §7): engine-facing writers commit
spills -> staged to the mesh -> the fused step redistributes over ICI and
sorts on the device. Verified against both a host-side reader and the raw
input multiset, one shot (``rows_per_round`` 0) and in bounded rounds."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.parallel import exchange as exchange_mod
from sparkrdma_tpu.shuffle.manager import PartitionerSpec, TpuShuffleManager
from sparkrdma_tpu.shuffle.mesh_service import run_mesh_reduce_fused

D = 8
CONF = TpuShuffleConf(connect_timeout_ms=5000)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:D]), ("shuffle",))


@pytest.fixture
def cluster(tmp_path):
    driver = TpuShuffleManager(CONF, is_driver=True)
    execs = [TpuShuffleManager(CONF, driver_addr=driver.driver_addr,
                               executor_id=str(i),
                               spill_dir=str(tmp_path / f"e{i}"))
             for i in range(2)]
    for ex in execs:
        ex.executor.wait_for_members(2)
    yield driver, execs
    for ex in execs:
        ex.stop()
    driver.stop()


# 10,000 rows over 8 devices: one shot, or 4 rounds of 400 rows a device
@pytest.mark.parametrize("rows_per_round", [0, 400])
def test_manager_to_mesh_reduce(cluster, mesh, rows_per_round):
    driver, execs = cluster
    num_partitions = 16
    handle = driver.register_shuffle(1, num_maps=4,
                                     num_partitions=num_partitions,
                                     partitioner=PartitionerSpec("modulo"),
                                     row_payload_bytes=8)
    rng = np.random.default_rng(0)
    truth_k, truth_p = [], []
    for m in range(4):
        keys = rng.integers(0, 100_000, 2500).astype(np.uint64)
        payload = rng.integers(0, 255, (2500, 8)).astype(np.uint8)
        w = execs[m % 2].get_writer(handle, m)
        w.write_batch(keys, payload)
        w.close()
        truth_k.append(keys)
        truth_p.append(payload)
    truth_k = np.concatenate(truth_k)
    truth_p = np.concatenate(truth_p)

    before = exchange_mod.DATA_PLANE["exchanges"]
    results = run_mesh_reduce_fused(execs, handle, mesh,
                                    rows_per_round=rows_per_round)
    assert exchange_mod.DATA_PLANE["exchanges"] - before \
        == (4 if rows_per_round else 1)

    got_k, got_p = [], []
    for d, (k, p, parts) in enumerate(results):
        # placement: every row's partition owner must be this device
        np.testing.assert_array_equal(parts % D, np.full(len(parts), d))
        # sorted within device
        assert (np.diff(k.astype(np.int64)) >= 0).all()
        got_k.append(k)
        got_p.append(p)
    got_k = np.concatenate(got_k)
    got_p = np.concatenate(got_p)
    assert len(got_k) == len(truth_k)

    def canon(k, p):
        rows = np.concatenate([k[:, None].view(np.uint8).reshape(len(k), 8), p],
                              axis=1)
        return rows[np.lexsort(rows.T[::-1])]
    np.testing.assert_array_equal(canon(got_k, got_p), canon(truth_k, truth_p))

    # cross-check one device against the host-side DCN reader path
    d0_parts = [p for p in range(num_partitions) if p % D == 0]
    host_k = []
    for p in d0_parts:
        rk, _ = execs[0].get_reader(handle, p, p + 1).read_all()
        host_k.append(rk)
    np.testing.assert_array_equal(np.sort(np.concatenate(host_k)),
                                  np.sort(results[0][0]))


@pytest.mark.parametrize("rows_per_round", [0, 128])
def test_mesh_reduce_empty_shuffle(cluster, mesh, rows_per_round):
    """No rows: the one-shot staging and the block stream both come up
    empty, and every device answers with nothing."""
    driver, execs = cluster
    handle = driver.register_shuffle(2, num_maps=1, num_partitions=4,
                                     partitioner=PartitionerSpec("modulo"))
    w = execs[0].get_writer(handle, 0)
    w.close()  # empty map output
    results = run_mesh_reduce_fused(execs, handle, mesh,
                                    rows_per_round=rows_per_round)
    assert len(results) == D
    assert all(len(k) == 0 for k, _, _ in results)


def test_spark_compat_surface(tmp_path):
    """Reference-shaped API: registerShuffle/getWriter/getReader/stop."""
    from sparkrdma_tpu.shuffle.spark_compat import (
        ShuffleDependency, SparkCompatShuffleManager)
    driver = SparkCompatShuffleManager(CONF, isDriver=True)
    ex = [SparkCompatShuffleManager(CONF, driverAddr=driver.driverAddr,
                                    executorId=str(i),
                                    spill_dir=str(tmp_path / f"sc{i}"))
          for i in range(2)]
    for e in ex:
        e.native.executor.wait_for_members(2)
    try:
        dep = ShuffleDependency(num_partitions=4, row_payload_bytes=4)
        handle = driver.registerShuffle(9, 2, dep)
        for m in range(2):
            w = ex[m].getWriter(handle, m)
            w.write([(k, np.full(4, k % 256, dtype=np.uint8))
                     for k in range(m * 50, m * 50 + 50)])
            w.stop(True)
        records = list(ex[0].getReader(handle, 0, 4).read())
        assert len(records) == 100
        for k, v in records:
            assert (v == k % 256).all()
        assert driver.unregisterShuffle(9)
        assert ex[0].shuffleBlockResolver is not None
    finally:
        for e in ex:
            e.stop()
        driver.stop()


# 4,096 rows: one shot, or 4 rounds of 128 rows a device
@pytest.mark.parametrize("rows_per_round", [0, 128])
def test_mesh_reduce_overflow_detected(cluster, mesh, rows_per_round):
    """All keys hit one partition: skew beyond out_factor must raise, not
    silently truncate."""
    driver, execs = cluster
    handle = driver.register_shuffle(3, num_maps=1, num_partitions=16,
                                     partitioner=PartitionerSpec("modulo"))
    w = execs[0].get_writer(handle, 0)
    w.write_batch(np.zeros(4096, dtype=np.uint64))  # all -> partition 0
    w.close()
    with pytest.raises(OverflowError):
        run_mesh_reduce_fused(execs, handle, mesh, out_factor=2,
                              rows_per_round=rows_per_round)


def test_compat_writer_two_record_iterable(tmp_path):
    """A 2-element tuple of records must not be mistaken for a batch."""
    from sparkrdma_tpu.shuffle.spark_compat import (
        ShuffleDependency, SparkCompatShuffleManager)
    driver = SparkCompatShuffleManager(CONF, isDriver=True)
    ex = SparkCompatShuffleManager(CONF, driverAddr=driver.driverAddr,
                                   executorId="0",
                                   spill_dir=str(tmp_path / "t"))
    ex.native.executor.wait_for_members(1)
    try:
        handle = driver.registerShuffle(5, 1, ShuffleDependency(2, row_payload_bytes=2))
        w = ex.getWriter(handle, 0)
        w.write(((1, np.array([7, 7], dtype=np.uint8)),
                 (2, np.array([9, 9], dtype=np.uint8))))
        w.stop(True)
        records = dict(ex.getReader(handle, 0, 2).read())
        assert records[1].tolist() == [7, 7] and records[2].tolist() == [9, 9]
    finally:
        ex.stop()
        driver.stop()


def test_fused_rounds_match_one_shot(cluster, mesh):
    """Bounded rounds produce the same per-device reduce as one shot
    (same keys in order, same full-row multiset), with rounds small
    enough to force many exchanges."""
    driver, execs = cluster
    handle = driver.register_shuffle(31, num_maps=4, num_partitions=16,
                                     partitioner=PartitionerSpec("modulo"),
                                     row_payload_bytes=8)
    rng = np.random.default_rng(8)
    for m in range(4):
        w = execs[m % 2].get_writer(handle, m)
        w.write_batch(rng.integers(0, 3000, 1500).astype(np.uint64),
                      rng.integers(0, 255, (1500, 8)).astype(np.uint8))
        w.close()

    one_shot = run_mesh_reduce_fused(execs, handle, mesh, expect_maps=4)
    rounds = run_mesh_reduce_fused(execs, handle, mesh, expect_maps=4,
                                   rows_per_round=128)  # 6 rounds
    for d in range(D):
        k1, p1, parts1 = one_shot[d]
        k2, p2, parts2 = rounds[d]
        np.testing.assert_array_equal(k1, k2)
        np.testing.assert_array_equal(parts1, parts2)
        # payload multiset per device (duplicate-key order may differ
        # between one sort and a merge of the rounds' runs)
        rows1 = np.concatenate([k1[:, None].astype(np.uint64),
                                p1.astype(np.uint64)], axis=1)
        rows2 = np.concatenate([k2[:, None].astype(np.uint64),
                                p2.astype(np.uint64)], axis=1)
        np.testing.assert_array_equal(rows1[np.lexsort(rows1.T[::-1])],
                                      rows2[np.lexsort(rows2.T[::-1])])
