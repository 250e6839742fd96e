"""Engine + ICI data plane unification: with a mesh configured, a DAG job's
shuffle bytes move over the collective exchange — the engine SPI and the
accelerated path are the SAME code path, matching the reference where the
reader Spark gets back does the one-sided RDMA fetch itself
(scala/RdmaShuffleManager.scala:234-261,
scala/RdmaShuffleFetcherIterator.scala:119-180). Asserted three ways:
exchange dispatch counters tick, zero TCP fetchers are constructed, and
results are exact — including across an executor loss (stage retry)."""

import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from engine_helpers import (
    make_cluster,
    make_table as _table,
    payload_u32 as _payload_u32,
    u32_payload as _u32_payload,
)
from sparkrdma_tpu.engine import DAGEngine, MapStage, ResultStage
from sparkrdma_tpu.parallel import exchange as exchange_mod
from sparkrdma_tpu.shuffle.manager import PartitionerSpec
from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency

D = 8


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:D]), ("shuffle",))


@pytest.fixture
def cluster(tmp_path):
    driver, execs = make_cluster(tmp_path)
    yield driver, execs
    for ex in execs:
        ex.stop()
    driver.stop()


def _no_tcp_fetchers(monkeypatch):
    """Arm a counter that ticks if ANY TCP fetcher gets built."""
    from sparkrdma_tpu.shuffle import fetcher as fetcher_mod

    built = {"n": 0}
    orig = fetcher_mod.ShuffleFetcher.__init__

    def spy(self, *a, **kw):
        built["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(fetcher_mod.ShuffleFetcher, "__init__", spy)
    return built


# 3-word rows at out_factor 4 (4 partitions on 8 devices): 256 rows a
# round, where the job has 525 a device
@pytest.mark.parametrize("device_hbm_budget", [0, 12 * (2 + 2 * 4) * 256])
def test_engine_job_rides_mesh(cluster, mesh, monkeypatch,
                               device_hbm_budget):
    """Sum-by-partition job: exact results, exchanges dispatched, zero TCP
    fetchers built (one-shot under the default budget, and rounds under
    one the stage does not fit)."""
    driver, execs = cluster
    P, maps, rows, key_space = 4, 6, 700, 5000

    def map_fn(ctx, writer, task_id):
        keys, vals = _table(100 + task_id, rows, key_space)
        writer.write((keys, _u32_payload(vals)))

    def reduce_fn(ctx, task_id):
        reader = ctx.read(0)
        total = 0
        n = 0
        for keys, payload in reader.readBatches():
            total += int(_payload_u32(payload).astype(np.int64).sum())
            n += len(keys)
        assert reader.metrics.remote_bytes == 0  # nothing crossed TCP
        return total, n

    built = _no_tcp_fetchers(monkeypatch)
    before = exchange_mod.DATA_PLANE["exchanges"]
    stage = MapStage(maps, ShuffleDependency(
        P, PartitionerSpec("modulo"), row_payload_bytes=4), map_fn)
    engine = DAGEngine(driver, execs, mesh=mesh,
                       device_hbm_budget=device_hbm_budget)
    out = engine.run(ResultStage(P, reduce_fn, parents=[stage]))

    # exact per-partition sums vs. host truth
    want = [0] * P
    seen = 0
    for m in range(maps):
        keys, vals = _table(100 + m, rows, key_space)
        for p in range(P):
            want[p] += int(vals[keys % P == p].astype(np.int64).sum())
        seen += rows
    assert [t for t, _ in out] == want
    assert sum(n for _, n in out) == seen
    assert exchange_mod.DATA_PLANE["exchanges"] > before, \
        "no collective exchange dispatched — bytes did not ride the mesh"
    assert built["n"] == 0, "TCP fetcher constructed in mesh mode"
    if device_hbm_budget:  # must have taken multiple rounds
        assert exchange_mod.DATA_PLANE["exchanges"] - before > 1


@pytest.mark.parametrize("dataplane", ["auto", "device"])
def test_tiny_stage_rides_the_dense_transport(cluster, mesh, dataplane):
    """A stage with fewer rows per device than the mesh has devices (4
    rows over 8) still runs on the transport it was planned with: dense
    fixed slots hold such a buffer whole (``exchange._slot_rows``), no
    other transport stands in, the stage neither fails nor degrades."""
    import chip_smoke
    from sparkrdma_tpu.utils.trace import Tracer

    driver, execs = cluster
    engine = DAGEngine(driver, execs, mesh=mesh, mesh_impl="dense",
                       dataplane=dataplane)
    engine.tracer = Tracer()
    out = engine.run(chip_smoke.build_sort_job(2, D, 2, 0))
    events = engine.tracer._events
    names = [e["name"] for e in events]
    selects = [e["args"] for e in events if e["name"] == "exchange.select"]
    assert [(s["plane"], s["impl"]) for s in selects] == [("device", "dense")]
    assert "exchange.degrade" not in names
    keys = np.concatenate([r[0] for r in out])
    want = np.sort(np.concatenate(
        [chip_smoke.map_input(0, m, 2)[0] for m in range(2)]))
    np.testing.assert_array_equal(keys, want)
    assert all(r[2] for r in out)  # every partition arrived key-sorted


def test_engine_mesh_survives_executor_loss(cluster, mesh, caplog):
    """Executor dies after the map stage: mesh staging surfaces the missing
    map as FetchFailed, the ordinary retry recomputes on survivors, the
    re-reduce is exact (scala/RdmaShuffleFetcherIterator.scala:376-381)."""
    import logging

    caplog.set_level(logging.WARNING, logger="sparkrdma_tpu.engine")
    driver, execs = cluster
    P, maps, rows, key_space = 4, 6, 500, 5000

    def map_fn(ctx, writer, task_id):
        keys, vals = _table(9100 + task_id, rows, key_space)
        writer.write((keys, _u32_payload(vals)))

    killed = {"done": False}

    def reduce_fn(ctx, task_id):
        if task_id == 0 and not killed["done"]:
            killed["done"] = True
            victim = execs[1].native
            mid = victim.executor.manager_id
            victim.executor.stop()
            driver.native.driver.remove_member(mid)
            time.sleep(0.3)
        total = 0
        for keys, payload in ctx.read(0).readBatches():
            total += int(_payload_u32(payload).astype(np.int64).sum())
        return total

    stage = MapStage(maps, ShuffleDependency(
        P, PartitionerSpec("modulo"), row_payload_bytes=4), map_fn)
    # sequential: the injection relies on task 0 killing BEFORE any other
    # task's read triggers the mesh reduce (a concurrent sibling would
    # legitimately cache the pre-kill reduce and no recovery would fire)
    engine = DAGEngine(driver, execs, mesh=mesh, max_parallel_tasks=1)
    got = sum(engine.run(ResultStage(P, reduce_fn, parents=[stage])))
    assert killed["done"], "failure injection never ran"

    want = sum(int(_table(9100 + m, rows, key_space)[1].astype(np.int64).sum())
               for m in range(maps))
    assert got == want
    assert any("recovering shuffle" in r.message for r in caplog.records)


def test_engine_mesh_two_table_join(cluster, mesh, monkeypatch):
    """Multi-parent read (equi-join) over the mesh plane: two shuffles,
    both served by collective reduces, zero TCP fetchers."""
    driver, execs = cluster
    P, maps, rows, key_space = 4, 3, 400, 64

    def writer_fn(base_seed):
        def fn(ctx, writer, task_id):
            keys, vals = _table(base_seed + task_id, rows, key_space)
            writer.write((keys, _u32_payload(vals)))
        return fn

    def join_fn(ctx, task_id):
        lk, lp = ctx.read(0)._r.read_all()
        rk, rp = ctx.read(1)._r.read_all()
        lv, rv = _payload_u32(lp), _payload_u32(rp)
        total = 0
        for k in np.unique(lk):
            total += int(lv[lk == k].astype(np.int64).sum()
                         * rv[rk == k].astype(np.int64).sum())
        return total

    built = _no_tcp_fetchers(monkeypatch)
    left = MapStage(maps, ShuffleDependency(
        P, PartitionerSpec("modulo"), row_payload_bytes=4), writer_fn(7000))
    right = MapStage(maps, ShuffleDependency(
        P, PartitionerSpec("modulo"), row_payload_bytes=4), writer_fn(8000))
    engine = DAGEngine(driver, execs, mesh=mesh)
    got = sum(engine.run(ResultStage(P, join_fn, parents=[left, right])))

    # truth: sum over keys of (sum of left vals) * (sum of right vals)
    lk = np.concatenate([_table(7000 + m, rows, key_space)[0]
                         for m in range(maps)])
    lv = np.concatenate([_table(7000 + m, rows, key_space)[1]
                         for m in range(maps)]).astype(np.int64)
    rk = np.concatenate([_table(8000 + m, rows, key_space)[0]
                         for m in range(maps)])
    rv = np.concatenate([_table(8000 + m, rows, key_space)[1]
                         for m in range(maps)]).astype(np.int64)
    want = sum(int(lv[lk == k].sum() * rv[rk == k].sum())
               for k in np.unique(lk))
    assert got == want
    assert built["n"] == 0


def test_engine_mesh_rejects_remote_executors(cluster, mesh):
    from sparkrdma_tpu.tasks import RemoteExecutor

    driver, execs = cluster
    fake = RemoteExecutor.__new__(RemoteExecutor)
    with pytest.raises(ValueError, match="in-process"):
        DAGEngine(driver, [*execs, fake], mesh=mesh)
