"""Two-level (ICI/DCN) topology: the Topology model and its detection,
the generalized cost model (hierarchical plan kind, single-slice
degenerate parity), the factored hierarchical exchange (byte parity vs
the flat device plan and a host reference across uniform / zipfian /
slice-affine inputs, empty slices, per-slice degrade), the link-cost-
aware partition layout and planner placement, bench provenance, and the
topo microbench acceptance gates. Seed swept by
``scripts/run_topo_bench.sh`` via ``TOPO_SEED``."""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from engine_helpers import u32_payload as _u32_payload
from sparkrdma_tpu.config import TpuShuffleConf
from sparkrdma_tpu.engine import DAGEngine, MapStage, ResultStage
from sparkrdma_tpu.parallel import exchange as exchange_mod
from sparkrdma_tpu.parallel import topology as topology_mod
from sparkrdma_tpu.parallel.device_plane import (
    StageProfile,
    run_fused_exchange,
    run_hierarchical_exchange,
    select_dataplane,
)
from sparkrdma_tpu.parallel.topology import Topology, detect_topology
from sparkrdma_tpu.shuffle.manager import PartitionerSpec
from sparkrdma_tpu.shuffle.planner import (
    ReducePlanner,
    SizeHistogram,
    slice_aligned_partition_map,
)
from sparkrdma_tpu.shuffle.spark_compat import (
    ShuffleDependency,
    SparkCompatShuffleManager,
)
from sparkrdma_tpu.utils.trace import Tracer

SEED = int(os.environ.get("TOPO_SEED", "0"))
D = 8
TOPO = Topology((4, 4))


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:D]), ("shuffle",))


def _canon(rows: np.ndarray) -> bytes:
    """Canonical multiset bytes of one device/partition's rows."""
    return (rows[np.lexsort(rows.T[::-1])] if len(rows) else rows).tobytes()


def _make_rows(n_rows: int, dist: str, rng) -> np.ndarray:
    """u32[N, 3] device rows with packed-u64 keys under the named key
    distribution (uniform / zipfian / affine handled by callers)."""
    if dist == "zipfian":
        ranks = rng.zipf(1.3, size=n_rows).astype(np.uint64)
        keys = ranks * 2_654_435_761 % (1 << 40)
    else:
        keys = rng.integers(0, 1 << 40, n_rows, dtype=np.uint64)
    rows = np.zeros((n_rows, 3), np.uint32)
    rows[:, :2] = keys.view(np.uint32).reshape(n_rows, 2)
    rows[:, 2] = rng.integers(0, 1 << 32, n_rows, dtype=np.uint32)
    return rows


def _host_reference(rows, dest, n):
    """The host-plane oracle: group by destination device, key-sort."""
    out = []
    for d in range(n):
        sub = rows[dest == d]
        keys = sub[:, :2].copy().view(np.uint64).reshape(-1)
        out.append(sub[np.argsort(keys, kind="stable")])
    return out


# -- the topology model --------------------------------------------------

def test_topology_model_units():
    t = Topology((2, 4, 2), ici_gbps=100.0, dcn_gbps=10.0)
    assert t.num_slices == 3 and t.num_devices == 8 and not t.is_flat
    assert [t.slice_of(i) for i in range(8)] == [0, 0, 1, 1, 1, 1, 2, 2]
    np.testing.assert_array_equal(t.device_slices(),
                                  [0, 0, 1, 1, 1, 1, 2, 2])
    assert t.slice_bounds(1) == (2, 6)
    with pytest.raises(IndexError):
        t.slice_of(8)
    # uniform inter fraction: 1 - sum((|s|/D)^2)
    assert Topology((4, 4)).uniform_inter_fraction() == pytest.approx(0.5)
    assert Topology((8,)).uniform_inter_fraction() == 0.0
    # link cost: intra rides ICI, inter rides DCN
    gb = 1 << 30
    assert t.link_seconds(gb, 0) == pytest.approx(1 / 100.0)
    assert t.link_seconds(0, gb) == pytest.approx(1 / 10.0)
    # refine returns a re-anchored copy, original untouched
    r = t.refine(dcn_gbps=25.0)
    assert r.dcn_gbps == 25.0 and r.ici_gbps == 100.0
    assert t.dcn_gbps == 10.0
    d = t.describe()
    assert d["slices"] == 3 and d["devices_per_slice"] == [2, 4, 2]
    # degenerate: single slice is flat; every slot homes there
    flat = Topology((8,))
    assert flat.is_flat
    assert all(flat.slice_of_slot(s, 3) == 0 for s in range(3))
    # slot -> slice proportional mapping on the multi-slice shape
    assert [Topology((4, 4)).slice_of_slot(s, 4) for s in range(4)] == \
        [0, 0, 1, 1]


def test_detect_topology_and_spec_parsing(mesh):
    # auto on a single-process CPU mesh: every device shares a
    # process_index -> ONE slice, the degenerate pre-topology case
    auto = detect_topology(mesh)
    assert auto.is_flat and auto.num_devices == D
    # conf-driven virtual slicing (CI/bench shape)
    two = detect_topology(mesh, conf=TpuShuffleConf(slice_topology="2"))
    assert two.slice_sizes == (4, 4)
    explicit = detect_topology(
        mesh, conf=TpuShuffleConf(slice_topology="2,6", ici_gbps=80.0,
                                  dcn_gbps=8.0))
    assert explicit.slice_sizes == (2, 6)
    assert explicit.ici_gbps == 80.0 and explicit.dcn_gbps == 8.0
    # invalid specs log-and-default to auto (config contract): a count
    # that doesn't divide, sizes that don't sum, junk text
    for bad in ("3", "5,5", "0,8", "x,y", "-2"):
        assert detect_topology(
            mesh, conf=TpuShuffleConf(slice_topology=bad)).is_flat, bad
    # no mesh at all: empty degenerate topology
    assert detect_topology(None).is_flat
    # host_topology (bench provenance) never raises and sees the devices
    host = topology_mod.host_topology()
    assert host.num_devices == len(jax.devices())


# -- the generalized cost model ------------------------------------------

def test_select_dataplane_single_slice_bit_identical(mesh):
    """The degenerate topology must reproduce the flat selector's plans
    exactly — same plane, impl, rounds, reason."""
    flat = Topology((D,))
    for profile, budget in (
            (StageProfile(est_bytes=1 << 20, row_bytes=16), 64 << 20),
            (StageProfile(est_bytes=1 << 30, row_bytes=16), 1 << 20),
            (StageProfile(est_bytes=1 << 20, row_bytes=16), 1),
            (StageProfile(est_bytes=1, row_bytes=16, resident=False),
             64 << 20)):
        base = select_dataplane(mesh, "shuffle", profile,
                                hbm_budget=budget)
        topo = select_dataplane(mesh, "shuffle", profile,
                                hbm_budget=budget, topology=flat)
        assert topo == base


def test_select_dataplane_hierarchical_scoring(mesh):
    profile = StageProfile(est_bytes=1 << 20, row_bytes=16)
    plan = select_dataplane(mesh, "shuffle", profile, topology=TOPO)
    assert plan.plane == "hierarchical"
    assert plan.topology is TOPO
    assert "two-level" in plan.reason
    # the plan carries the RAW transport ask: "auto" must re-probe per
    # sub-mesh (the opcode a cross-slice mesh rejects may compile per
    # slice), never the global mesh's resolution
    assert plan.impl == "auto" and plan.rows_per_round == 0
    # a CHUNKED device plan keeps its streamed staging discipline: the
    # hierarchical runner's whole-stage host staging is one-shot-only
    big = StageProfile(est_bytes=1 << 30, row_bytes=16)
    chunked = select_dataplane(mesh, "shuffle", big, hbm_budget=1 << 20,
                               topology=TOPO)
    assert chunked.plane == "device" and chunked.rows_per_round > 0
    # no ICI:DCN gap -> the hierarchical plan buys nothing -> flat device
    even = Topology((4, 4), ici_gbps=10.0, dcn_gbps=10.0)
    assert select_dataplane(mesh, "shuffle", profile,
                            topology=even).plane == "device"
    # an explicit per-link byte decomposition overrides the uniform
    # estimate: zero inter bytes still beats all-DCN flat pricing
    skewed = StageProfile(est_bytes=1 << 20, row_bytes=16,
                          intra_bytes=1 << 20, inter_bytes=0)
    assert select_dataplane(mesh, "shuffle", skewed,
                            topology=TOPO).plane == "hierarchical"
    # overrides and non-device outcomes are untouched by topology
    assert select_dataplane(mesh, "shuffle", profile, override="host",
                            topology=TOPO).plane == "host"
    assert select_dataplane(None, "shuffle", profile,
                            topology=TOPO).plane == "host"
    assert select_dataplane(mesh, "shuffle", profile, hbm_budget=1,
                            topology=TOPO).plane == "host"


# -- the factored hierarchical exchange ----------------------------------

@pytest.mark.parametrize("dist", ["uniform", "zipfian"])
@pytest.mark.parametrize("sizes", [(4, 4), (2, 6)])
def test_hierarchical_vs_flat_vs_host_byte_parity(mesh, dist, sizes):
    """The parity matrix: hierarchical, flat-device, and host plans must
    serve byte-identical per-device results across input shapes and
    slice layouts."""
    topo = Topology(sizes)
    rng = np.random.default_rng(1000 * SEED + hash((dist, sizes)) % 997)
    rows = _make_rows(4000, dist, rng)
    keys = rows[:, :2].copy().view(np.uint64).reshape(-1)
    dest = (keys % D).astype(np.int32)
    home = rng.integers(0, topo.num_slices, len(rows)).astype(np.int32)

    before = topology_mod.cross_slice_snapshot()["bytes"]
    hier, _ = run_hierarchical_exchange(
        mesh, "shuffle", topo, rows, dest, home, key_words=2,
        out_factor=8, impl="gather")
    moved = topology_mod.cross_slice_snapshot()["bytes"] - before
    dev_slice = topo.device_slices()
    want_cross = int((dev_slice[dest] != home).sum()) * rows.shape[1] * 4
    assert moved == want_cross, "cross-slice tally != actual residue"

    flat, _ = run_fused_exchange(mesh, "shuffle", rows, dest, key_words=2,
                                 out_factor=8, impl="gather")
    host = _host_reference(rows, dest, D)
    for d in range(D):
        assert _canon(hier[d]) == _canon(flat[d]) == _canon(host[d]), \
            f"device {d} diverged under {dist}/{sizes}"
        # the per-device sort contract holds on the hierarchical plan
        k = hier[d][:, :2].copy().view(np.uint64).reshape(-1)
        assert (k[:-1] <= k[1:]).all()


def test_hierarchical_empty_slice_and_empty_input(mesh):
    """A slice that produces and receives nothing is simply idle — and
    the degenerate empty stage returns empty devices."""
    rng = np.random.default_rng(SEED + 3)
    rows = _make_rows(800, "uniform", rng)
    keys = rows[:, :2].copy().view(np.uint64).reshape(-1)
    dest = (keys % 4).astype(np.int32)  # devices 0-3 only: slice 1 idle
    home = np.zeros(len(rows), np.int32)
    before = topology_mod.cross_slice_snapshot()
    hier, _ = run_hierarchical_exchange(
        mesh, "shuffle", TOPO, rows, dest, home, key_words=2,
        out_factor=8, impl="gather")
    after = topology_mod.cross_slice_snapshot()
    assert after["bytes"] == before["bytes"], \
        "slice-local stage moved bytes across the seam"
    host = _host_reference(rows, dest, D)
    for d in range(D):
        assert _canon(hier[d]) == _canon(host[d])
    assert all(len(hier[d]) == 0 for d in range(4, 8))
    # fully empty input
    empty, rounds = run_hierarchical_exchange(
        mesh, "shuffle", TOPO, np.zeros((0, 3), np.uint32),
        np.zeros(0, np.int32), np.zeros(0, np.int32), impl="gather")
    assert rounds == 0 and all(len(e) == 0 for e in empty)


def test_slice_overflow_degrades_only_that_slice(mesh):
    """Skew that overflows ONE slice's receive headroom degrades only
    that slice's rows to host serving — byte-identically — while the
    other slice stays on the ICI collective."""
    rng = np.random.default_rng(SEED + 11)
    # slice 0: balanced intra traffic; slice 1: every row lands on
    # device 4 (4x the balanced share — past out_factor=2 headroom)
    r0 = _make_rows(2000, "uniform", rng)
    k0 = r0[:, :2].copy().view(np.uint64).reshape(-1)
    d0 = (k0 % 4).astype(np.int32)
    r1 = _make_rows(2000, "uniform", rng)
    d1 = np.full(len(r1), 4, np.int32)
    rows = np.concatenate([r0, r1])
    dest = np.concatenate([d0, d1])
    home = np.concatenate([np.zeros(len(r0), np.int32),
                           np.ones(len(r1), np.int32)])
    tracer = Tracer()
    before = exchange_mod.DATA_PLANE["exchanges"]
    hier, _ = run_hierarchical_exchange(
        mesh, "shuffle", TOPO, rows, dest, home, key_words=2,
        out_factor=2, impl="gather", tracer=tracer)
    assert exchange_mod.DATA_PLANE["exchanges"] - before >= 1, \
        "the healthy slice left the ICI collective too"
    degrades = [e for e in tracer._events
                if e["name"] == "exchange.degrade"]
    assert [e["args"]["slice"] for e in degrades] == [1]
    assert all(e["args"]["scope"] == "slice" for e in degrades)
    host = _host_reference(rows, dest, D)
    for d in range(D):
        assert _canon(hier[d]) == _canon(host[d]), f"device {d} diverged"


def test_hierarchical_budget_rounds_parity(mesh):
    """``rows_per_round`` bounds the per-slice ICI rounds (the budget
    auto-sizing's knob) without changing a byte."""
    rng = np.random.default_rng(SEED + 21)
    rows = _make_rows(3000, "uniform", rng)
    keys = rows[:, :2].copy().view(np.uint64).reshape(-1)
    dest = (keys % D).astype(np.int32)
    home = rng.integers(0, 2, len(rows)).astype(np.int32)
    one_shot, r1 = run_hierarchical_exchange(
        mesh, "shuffle", TOPO, rows, dest, home, key_words=2,
        out_factor=8, impl="gather")
    tracer = Tracer()
    rounds, rn = run_hierarchical_exchange(
        mesh, "shuffle", TOPO, rows, dest, home, key_words=2,
        out_factor=8, impl="gather", rows_per_round=128, tracer=tracer)
    assert rn > r1
    for d in range(D):
        assert _canon(one_shot[d]) == _canon(rounds[d])
    # every device merged several runs: each row written exactly once
    (merge,) = [e["args"] for e in tracer._events
                if e["name"] == "exchange.merge"]
    assert merge["runs"] > 1 and merge["rows"] == len(rows)
    assert merge["bytes"] == rows.nbytes


# -- engine end-to-end: the three planes agree ---------------------------

def _topo_cluster(tmp_path, **conf_kw):
    conf = TpuShuffleConf(connect_timeout_ms=1000,
                          max_connection_attempts=2, **conf_kw)
    driver = SparkCompatShuffleManager(conf, isDriver=True)
    execs = [SparkCompatShuffleManager(
        conf, driverAddr=driver.driverAddr, executorId=str(i),
        spill_dir=str(tmp_path / f"e{i}")) for i in range(3)]
    for ex in execs:
        ex.native.executor.wait_for_members(3)
    return driver, execs


def _engine_job(num_partitions, maps, rows, base_seed):
    def table(seed):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, 40000, size=rows).astype(np.uint64)
        vals = rng.integers(0, 1000, size=rows).astype(np.uint32)
        return keys, vals

    def map_fn(ctx, writer, task_id):
        keys, vals = table(base_seed + task_id)
        writer.write((keys, _u32_payload(vals)))

    def reduce_fn(ctx, task_id):
        keys, payload = ctx.read(0)._r.read_all()
        assert ((keys % num_partitions) == task_id).all()
        rows8 = np.concatenate(
            [keys.view(np.uint8).reshape(len(keys), 8), payload], axis=1)
        return _canon(rows8)

    stage = MapStage(maps, ShuffleDependency(
        num_partitions, PartitionerSpec("modulo"), row_payload_bytes=4),
        map_fn)
    return stage, reduce_fn


def test_engine_hierarchical_plane_end_to_end(tmp_path, mesh):
    """With a multi-slice ``slice_topology`` conf the cost model selects
    the HIERARCHICAL plan; its results are byte-identical to the forced
    flat-device and host planes, and the run actually crossed the seam
    (cross_slice_bytes) and rode ICI (collective tally)."""
    P, maps, rows = 4, 4, 500
    outs = {}
    for label, conf_kw, engine_kw in (
            ("hier", dict(slice_topology="2"), dict(mesh_impl="gather")),
            ("device", dict(hierarchical_exchange=False),
             dict(dataplane="device", mesh_impl="gather")),
            ("host", {}, dict(dataplane="host"))):
        driver, execs = _topo_cluster(tmp_path / label, **conf_kw)
        try:
            stage, reduce_fn = _engine_job(P, maps, rows, 9000 + SEED)
            cross0 = topology_mod.cross_slice_snapshot()["bytes"]
            moved0 = exchange_mod.DATA_PLANE["exchanges"]
            engine = DAGEngine(driver, execs, mesh=mesh, **engine_kw)
            outs[label] = engine.run(
                ResultStage(P, reduce_fn, parents=[stage]))
            cross = topology_mod.cross_slice_snapshot()["bytes"] - cross0
            moved = exchange_mod.DATA_PLANE["exchanges"] - moved0
            if label == "hier":
                assert cross > 0, "hierarchical run crossed no seam"
                assert moved > 0, "hierarchical run rode no collective"
            else:
                assert cross == 0, f"{label} plane tallied cross-slice"
        finally:
            for ex in execs:
                ex.stop()
            driver.stop()
    assert outs["hier"] == outs["device"] == outs["host"]


# -- link-cost-aware layout ----------------------------------------------

def test_slice_aligned_partition_map():
    # flat topology reproduces p % D bit-for-bit
    flat = slice_aligned_partition_map(np.zeros((1, 6), np.int64),
                                       Topology((4,)), 4)
    np.testing.assert_array_equal(flat, np.arange(6) % 4)
    # slice-affine histogram: every partition lands in its producing
    # slice, devices balanced within it
    topo = Topology((4, 4))
    hist = np.zeros((2, 16), np.int64)
    hist[0, :8] = 100
    hist[1, 8:] = 100
    pmap = slice_aligned_partition_map(hist, topo, 8)
    assert (pmap[:8] < 4).all() and (pmap[8:] >= 4).all()
    assert np.bincount(pmap, minlength=8).max() == 2  # balanced
    # one slice produced EVERYTHING: the balance cap forces a spill so
    # neither slice is starved (locality never recreates the straggler)
    solo = np.zeros((2, 16), np.int64)
    solo[0] = 100
    smap = slice_aligned_partition_map(solo, topo, 8)
    assert (smap < 4).any() and (smap >= 4).any()
    # determinism
    np.testing.assert_array_equal(
        pmap, slice_aligned_partition_map(hist, topo, 8))


def test_planner_link_cost_placement():
    """Multi-slice slot topology: placement minimizes the two-level
    link bill (consolidating same-slice bytes beats raw locality); the
    flat spec reproduces the byte-locality placement."""
    kw = dict(adaptive_plan=True, coalesce_target_bytes=0,
              split_threshold_bytes=1 << 30, locality_placement=True)
    hist = SizeHistogram(num_maps=3, num_partitions=1)
    hist.add(0, [40])
    hist.add(1, [30])
    hist.add(2, [30])
    owners = {0: 0, 1: 2, 2: 3}  # 40B on slot 0; 30B each on slots 2, 3
    live = [0, 1, 2, 3]
    flat_plan = ReducePlanner(TpuShuffleConf(**kw)).plan(
        1, hist, owners, live)
    # byte locality: slot 0 holds the single largest share
    assert flat_plan.tasks[0].placement == 0
    topo_plan = ReducePlanner(TpuShuffleConf(
        slice_topology="2", ici_gbps=100.0, dcn_gbps=10.0, **kw)).plan(
        1, hist, owners, live)
    # link cost: slots 2+3 share a slice — 60B at ICI beats 40B at ICI
    # with 60B crossing DCN, so the task consolidates into slice 1
    assert topo_plan.tasks[0].placement == 2
    # replan of an orphaned task follows the same link-cost scoring
    lost = ReducePlanner(TpuShuffleConf(
        slice_topology="2", ici_gbps=100.0, dcn_gbps=10.0, **kw)).replan(
        topo_plan, hist, owners, [0, 1, 3], completed_task_ids=[])
    assert lost.tasks[0].placement == 3  # same slice, next-best link bill


# -- satellites ----------------------------------------------------------

def test_bench_round_provenance_records_topology():
    import bench as bench_mod

    detail = bench_mod._round_provenance({})
    assert len(detail["host_load_avg"]) == 3
    topo = detail["topology"]
    assert topo["slices"] >= 1
    assert sum(topo["devices_per_slice"]) == len(jax.devices())
    assert topo["ici_gbps"] > topo["dcn_gbps"] > 0


def test_topo_microbench_acceptance(mesh):
    """The ISSUE's acceptance gate: >= 1.5x vs the flat plan on a
    2-slice virtual cluster under the 10:1 ICI:DCN cost shim, byte-
    identical output, strictly fewer cross-slice bytes."""
    from sparkrdma_tpu.shuffle.topo_bench import run_topo_microbench
    from sparkrdma_tpu.utils.benchgate import gated_best_of

    res = gated_best_of(lambda: run_topo_microbench(seed=SEED))
    assert res["identical"], "plans exchanged different bytes"
    assert res["slices"] == 2
    assert res["cross_slice_bytes"]["hier"] < \
        res["cross_slice_bytes"]["flat"]
    assert res["speedup"] >= 1.5, res
