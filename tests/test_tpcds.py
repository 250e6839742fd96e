"""TPC-DS workloads: the generic star join plus the ACTUAL q64 and q95
plan shapes (models/tpcds_queries.py), each run on-mesh (chained
collective exchanges) and as an engine stage DAG, against numpy
oracles."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from sparkrdma_tpu.models.tpcds import (
    TpcdsConfig,
    build_tpcds_job,
    generate_star,
    numpy_tpcds,
    run_tpcds,
)

CFG = TpcdsConfig(fact_rows_per_device=512, dim1_size=200, dim2_size=300,
                  num_groups=64, out_factor=4)


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:8]), ("shuffle",))


def test_on_mesh_matches_oracle(mesh):
    counts, sums = run_tpcds(mesh, CFG, seed=3)
    fact, dim1, dim2 = generate_star(CFG, 8, seed=3)
    want_c, want_s = numpy_tpcds(fact, dim1, dim2, CFG.num_groups)
    np.testing.assert_array_equal(counts, want_c)
    np.testing.assert_array_equal(sums, want_s)
    assert counts.sum() > 0, "degenerate query: nothing joined"


def test_heavy_skew_still_exact(mesh):
    """zipf_a -> 1.05 piles most fact rows on few keys; headroom + flags
    must keep results exact (BASELINE config #5-style skew stress)."""
    cfg = TpcdsConfig(fact_rows_per_device=256, dim1_size=50, dim2_size=80,
                      num_groups=32, zipf_a=1.05, out_factor=8)
    counts, sums = run_tpcds(mesh, cfg, seed=11)
    fact, dim1, dim2 = generate_star(cfg, 8, seed=11)
    want_c, want_s = numpy_tpcds(fact, dim1, dim2, cfg.num_groups)
    np.testing.assert_array_equal(counts, want_c)
    np.testing.assert_array_equal(sums, want_s)


def test_overflow_flag_on_insufficient_headroom(mesh):
    cfg = TpcdsConfig(fact_rows_per_device=256, dim1_size=8, dim2_size=50,
                      num_groups=16, zipf_a=1.01, out_factor=1)
    with pytest.raises(OverflowError):
        run_tpcds(mesh, cfg, seed=1)


def test_engine_plan_matches_oracle(tmp_path):
    from sparkrdma_tpu.config import TpuShuffleConf
    from sparkrdma_tpu.engine import DAGEngine
    from sparkrdma_tpu.shuffle.spark_compat import SparkCompatShuffleManager

    conf = TpuShuffleConf(connect_timeout_ms=1000, max_connection_attempts=2)
    driver = SparkCompatShuffleManager(conf, isDriver=True)
    execs = [SparkCompatShuffleManager(
        conf, driverAddr=driver.driverAddr, executorId=str(i),
        spill_dir=str(tmp_path / f"e{i}")) for i in range(3)]
    try:
        for ex in execs:
            ex.native.executor.wait_for_members(3)
        cfg = TpcdsConfig(fact_rows_per_device=2048, dim1_size=150,
                          dim2_size=200, num_groups=48)
        job, finish = build_tpcds_job(cfg, num_maps=3, num_partitions=4,
                                      seed=5)
        counts, sums = finish(DAGEngine(driver, execs).run(job))
        fact, dim1, dim2 = generate_star(cfg, 1, seed=5)
        want_c, want_s = numpy_tpcds(fact, dim1, dim2, cfg.num_groups)
        np.testing.assert_array_equal(counts, want_c)
        np.testing.assert_array_equal(sums, want_s)
        assert counts.sum() > 0
    finally:
        for ex in execs:
            ex.stop()
        driver.stop()


# ===========================================================================
# actual q95 / q64 plan shapes (models/tpcds_queries.py)
# ===========================================================================

import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference_q95  # noqa: E402
from sparkrdma_tpu.models.tpcds_queries import (  # noqa: E402
    Q64Config,
    Q95Config,
    Q95Job,
    build_q64_job,
    build_q95_job,
    generate_q64,
    generate_q95,
    make_q95_step,
    numpy_q64,
    place_q95,
    q95_totals,
    run_q64,
    run_q95,
)
from sparkrdma_tpu.parallel import exchange  # noqa: E402
from sparkrdma_tpu.utils.trace import ACCOUNTING_ARGS, Tracer  # noqa: E402


def _q95_cfg(devices, **changes):
    """6,144 line items of 512 orders over ``devices``, a tenth returned,
    and dimensions small enough that the three predicates leave rows."""
    sizes = dict(
        ws_rows_per_device=6144 // devices, wr_rows_per_device=608 // devices,
        num_orders=512, survivor_capacity=2048, window_days=1200,
        num_addresses=300, num_states=3, target_state=1, num_sites=12,
        num_companies=2, num_warehouses=3, out_factor=3)
    return Q95Config(**dict(sizes, **changes))


def _q95_params(cfg):
    return {k: getattr(cfg, k) for k in (
        "window_start", "window_days", "target_state", "target_company")}


def _q95_want(tables, cfg):
    return reference_q95.reference_q95(tables, _q95_params(cfg))


def _q95_mesh(devices):
    return Mesh(np.array(jax.devices()[:devices]), ("shuffle",))


Q95_CFG = _q95_cfg(8)
Q64_CFG = Q64Config(ss_rows_per_device=640, cs_rows_per_device=512,
                    num_items=300, out_factor=4)


def test_q95_on_mesh_matches_oracle(mesh):
    got = run_q95(mesh, Q95_CFG, seed=9)
    tables = generate_q95(Q95_CFG, 8, seed=9)
    want = _q95_want(tables, Q95_CFG)
    assert got._asdict() == want
    assert want["orders"] > 0, "degenerate q95: no qualifying orders"
    # the returns semi-join must bite: some rows pass all dimension
    # filters yet fall to the order-level predicate
    loose = _q95_want(tables._replace(wr_order=np.unique(tables.ws_order)),
                      Q95_CFG)
    assert loose["orders"] > want["orders"], \
        "returns semi-join filtered nothing"


def test_q95_dense_transport_matches(mesh):
    got = run_q95(mesh, Q95_CFG, seed=9, impl="dense")
    assert got._asdict() == _q95_want(generate_q95(Q95_CFG, 8, seed=9),
                                      Q95_CFG)


# -- the generator: dsdgen's order structure ---------------------------------

def test_generate_q95_is_deterministic_and_keeps_the_order_structure():
    cfg = _q95_cfg(4, order_base=2**33 + 5)
    a, b = generate_q95(cfg, 4, seed=2**31 + 3), generate_q95(
        cfg, 4, seed=2**31 + 3)
    c = generate_q95(cfg, 4, seed=2**31 + 4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.ws_warehouse, c.ws_warehouse)
    assert len(a.ws_order) == 6144 and len(a.wr_order) == 608
    assert a.ws_order.dtype == np.int64 and a.wr_order.dtype == np.int64
    # order by order: an order's items are consecutive, 8 to 16 of them,
    # the numbers count up from order_base
    orders, first, items = np.unique(a.ws_order, return_index=True,
                                     return_counts=True)
    np.testing.assert_array_equal(orders, np.arange(512) + 2**33 + 5)
    assert (np.diff(a.ws_order) >= 0).all()
    assert items.min() >= 8 and items.max() <= 16 and items.sum() == 6144
    assert len(set(items.tolist())) > 4
    # ship address and web site are an order's; warehouse, ship date,
    # cost and profit an item's
    for per_order in (a.ws_ship_addr, a.ws_web_site):
        np.testing.assert_array_equal(per_order,
                                      np.repeat(per_order[first], items))
    for per_item in (a.ws_warehouse, a.ws_ship_date, a.ws_ext_ship_cost,
                     a.ws_net_profit):
        assert not np.array_equal(per_item,
                                  np.repeat(per_item[first], items))
    assert a.ws_warehouse.min() >= 0 and a.ws_warehouse.max() < 3
    assert 0 <= a.ws_ship_date.min() and a.ws_ship_date.max() < 73_049
    assert a.ws_net_profit.min() < 0 < a.ws_net_profit.max()
    # web_returns: a tenth of the items, in the items' order
    assert (np.diff(a.wr_order) >= 0).all()
    assert np.isin(a.wr_order, a.ws_order).all()
    assert a.d_date.shape == (73_049,) and a.ca_state.shape == (300,)
    assert a.web_company.tolist() == [i % 2 for i in range(12)]


def test_generate_q95_refuses_rows_that_are_no_whole_orders():
    with pytest.raises(ValueError, match="8 to 16"):
        generate_q95(_q95_cfg(1, num_orders=100), 1)


# -- the job against the benchmark's reference --------------------------------

def _plain(tables):
    return tables


def _orders_above_2_32(tables):
    """Pairs of orders whose numbers differ in the HIGH word only: a join
    on the low word alone merges them."""
    lift = (tables.ws_order % 2 == 0) * np.int64(2**32)
    wr_lift = (tables.wr_order % 2 == 0) * np.int64(2**32)
    twins = tables._replace(ws_order=tables.ws_order // 2 * 2 + lift,
                            wr_order=tables.wr_order // 2 * 2 + wr_lift)
    assert len(np.unique(twins.ws_order)) == 512
    assert len(np.unique(twins.ws_order & 0xFFFFFFFF)) == 257
    return twins


def _negative_profit(tables):
    return tables._replace(
        ws_net_profit=-np.abs(tables.ws_net_profit) - 1)


def _sums_past_2_31(tables):
    """decimal(7,2)'s largest value on every row: a few hundred survivors
    pass 2^31 cents, and the profits -2^31."""
    n = len(tables.ws_order)
    return tables._replace(
        ws_ext_ship_cost=np.full(n, 9_999_999, np.int32),
        ws_net_profit=np.full(n, -9_999_999, np.int32))


@pytest.mark.parametrize("change", [
    _plain, _orders_above_2_32, _negative_profit, _sums_past_2_31],
    ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("devices", [1, 4])
def test_q95_job_equals_the_reference_exactly(devices, change):
    """One device (the cell's cut) and four (the deployment's form) give
    the reference's six integers: same seed, same whole tables."""
    cfg = _q95_cfg(devices)
    tables = generate_q95(cfg, devices, seed=2**31 + 17)
    # a fifth of the orders ship from one warehouse: ws_wh has work to do
    one = tables.ws_order % 5 == 0
    tables = change(tables._replace(
        ws_warehouse=np.where(one, 1, tables.ws_warehouse).astype(np.int32)))
    mesh = _q95_mesh(devices)
    answers = Q95Job(mesh, "shuffle", cfg)(place_q95(mesh, "shuffle",
                                                     tables))
    assert all(isinstance(a, jax.Array) for a in answers)
    got, want = q95_totals(answers)._asdict(), _q95_want(tables, cfg)
    assert got == want
    assert want["orders"] > 15 and want["orders_seen"] == 512
    assert want["multi_warehouse_orders"] < 450 < want["orders_seen"]
    assert 0 < want["returned_orders"] < 512
    if change is _negative_profit:
        assert want["net_profit"] < 0
    if change is _sums_past_2_31:
        assert want["ship_cost"] > 2**31 and want["net_profit"] < -2**31


def test_q95_overflows_are_named():
    cfg = _q95_cfg(4, survivor_capacity=4)
    tables = generate_q95(cfg, 4, seed=3)
    mesh = _q95_mesh(4)
    with pytest.raises(OverflowError, match=r"\['filter'\]"):
        Q95Job(mesh, "shuffle", cfg)(place_q95(mesh, "shuffle", tables))
    # every row of one order: one owner receives four devices' rows
    cfg = _q95_cfg(4)
    crowd = tables._replace(ws_order=np.full_like(tables.ws_order, 77))
    with pytest.raises(OverflowError, match=r"\['pairs'\]"):
        Q95Job(mesh, "shuffle", cfg)(place_q95(mesh, "shuffle", crowd))


def test_q95_padding_rows_are_no_rows():
    cfg = _q95_cfg(4)
    tables = generate_q95(cfg, 4, seed=5)
    ws_order, wr_order = tables.ws_order.copy(), tables.wr_order.copy()
    ws_order[-40:] = -1
    wr_order[-7:] = -1
    tables = tables._replace(ws_order=ws_order, wr_order=wr_order)
    mesh = _q95_mesh(4)
    resident = place_q95(mesh, "shuffle", tables)
    assert (resident.ws_rows, resident.wr_rows) == (6144 - 40, 608 - 7)
    assert resident.orders == len(np.unique(ws_order)) - 1
    got = q95_totals(Q95Job(mesh, "shuffle", cfg)(resident))
    assert got._asdict() == _q95_want(tables, cfg)


def test_q95_job_spans_and_counters(tmp_path):
    cfg = _q95_cfg(4)
    tables = generate_q95(cfg, 4, seed=4)
    mesh = _q95_mesh(4)
    tracer = Tracer()
    Q95Job(mesh, "shuffle", cfg, tracer=tracer)(
        place_q95(mesh, "shuffle", tables))
    path = str(tmp_path / "trace.json")
    tracer.dump(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert set(spans) == {"q95.job", "q95.dispatch", "q95.wait"}
    survivors = int(reference_q95.survivor_mask(
        tables, _q95_params(cfg)).sum())
    job = spans["q95.job"]
    # what the caller gave; the tracer adds its accounting beside it
    own = {k: v for k, v in job["args"].items()
           if k not in ACCOUNTING_ARGS}
    assert own == {"ws_rows": 6144, "wr_rows": 608, "orders": 512,
                   "received": [6144, 608, survivors],
                   "survivors": survivors,
                   # 2- to 4-word rows: jnp.take on any platform
                   "row_move": "sort"}
    assert survivors > 20
    for inner in ("q95.dispatch", "q95.wait"):
        assert job["ts"] <= spans[inner]["ts"]
        assert (spans[inner]["ts"] + spans[inner]["dur"]
                <= job["ts"] + job["dur"])
    counters = {e["name"]: e["args"]["value"] for e in events
                if e.get("ph") == "C"}
    assert counters["q95.survivors"] == survivors
    # most records a device received in an exchange over that exchange's
    # capacity: the pairs fall evenly, a quarter each of 3 x 1536
    assert 0.25 / 3 < counters["q95.recv_fill"] <= 1.0


def test_q95_scopes_name_the_steps_ops():
    cfg = _q95_cfg(4)
    mesh = _q95_mesh(4)
    resident = place_q95(mesh, "shuffle", generate_q95(cfg, 4, seed=1))
    step = make_q95_step(mesh, "shuffle", cfg)
    text = step.lower(resident.web_sales, resident.web_returns,
                      resident.dimensions).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("q95.filter", "q95.exchange", "q95.join",
                  "q95.exchange/row_sort"):
        assert any(f"/{scope}/" in n for n in names), scope
    assert not any("row_gather" in n for n in names)
    for scope, kernel in (("q95.filter", "gather"),
                          ("q95.exchange/row_sort", "sort"),
                          ("q95.join", "sort"),
                          ("q95.join", "scatter")):
        assert any(f"/{scope}/" in n and n.endswith(kernel)
                   for n in names), (scope, kernel)
    assert step.row_moves == ["sort"] * 3


def test_q95_and_pagerank_ride_one_packer(monkeypatch):
    """Both steps call ``exchange.pack_exchange_shard``, here as on the
    chip: there is no second."""
    from sparkrdma_tpu.models.pagerank import (
        PageRankConfig,
        make_pagerank_step,
        powerlaw_graph,
    )

    calls = []
    packer = exchange.pack_exchange_shard

    def counted(rows, *args, **kwargs):
        calls.append(rows.shape[1])
        return packer(rows, *args, **kwargs)

    monkeypatch.setattr(exchange, "pack_exchange_shard", counted)
    mesh = _q95_mesh(4)
    cfg = _q95_cfg(4)
    tables = generate_q95(cfg, 4, seed=6)
    got = q95_totals(Q95Job(mesh, "shuffle", cfg)(
        place_q95(mesh, "shuffle", tables)))
    assert calls == [3, 2, 4]
    assert got._asdict() == _q95_want(tables, cfg)
    pcfg = PageRankConfig(num_vertices=256, edges_per_device=1024)
    edges, ranks, out_deg = powerlaw_graph(pcfg, 4, seed=1)
    make_pagerank_step(mesh, "shuffle", pcfg)(edges, ranks, out_deg)
    assert calls == [3, 2, 4, 2]


def test_q64_on_mesh_matches_oracle(mesh):
    got = run_q64(mesh, Q64_CFG, seed=13)
    want = numpy_q64(*generate_q64(Q64_CFG, 8, seed=13), Q64_CFG)
    assert got == want
    assert want[0] > 0, "degenerate q64: no qualifying items"


def test_q64_having_predicate_bites(mesh):
    """cs_ui's HAVING sum(sale) > 2*sum(refund) must exclude items (the
    returns-heavy items), not pass everything."""
    ss, sr, cs, cr, date = generate_q64(Q64_CFG, 8, seed=13)
    items_with_sales = len(set(cs[:, 0].tolist()))
    no_refunds = numpy_q64(ss, sr, cs, cr[:0], date, Q64_CFG)
    with_refunds = numpy_q64(ss, sr, cs, cr, date, Q64_CFG)
    assert with_refunds[0] < no_refunds[0], \
        f"HAVING filtered nothing ({items_with_sales} items)"


from engine_helpers import make_cluster as _cluster  # noqa: E402


def test_q95_engine_plan_matches_oracle(tmp_path):
    from sparkrdma_tpu.engine import DAGEngine

    driver, execs = _cluster(tmp_path)
    try:
        cfg = _q95_cfg(1, order_base=2**32 - 100)   # both key words vary
        job, finish = build_q95_job(cfg, num_maps=3, num_partitions=4,
                                    seed=9)
        got = finish(DAGEngine(driver, execs).run(job))
        want = _q95_want(generate_q95(cfg, 1, seed=9), cfg)
        assert got == (want["orders"], want["ship_cost"],
                       want["net_profit"])
        assert got[0] > 0
    finally:
        for ex in execs:
            ex.stop()
        driver.stop()


def test_q64_engine_plan_matches_oracle(tmp_path):
    from sparkrdma_tpu.engine import DAGEngine

    driver, execs = _cluster(tmp_path)
    try:
        job, finish = build_q64_job(Q64_CFG, num_maps=3, num_partitions=4,
                                    seed=13, data_scale=8)
        got = finish(DAGEngine(driver, execs).run(job))
        want = numpy_q64(*generate_q64(Q64_CFG, 8, seed=13), Q64_CFG)
        assert got == want
        assert got[0] > 0
    finally:
        for ex in execs:
            ex.stop()
        driver.stop()
