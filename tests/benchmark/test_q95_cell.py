"""The ``tpcds_q95`` configuration's own pieces of the yardstick, and the
kept cell ``pagerank_4chip``'s: the int64 reference finds what is wrong (a
dropped return, a one-warehouse order counted as multi, a wrapped sum) and
passes what is right, the bytes and the roofline reader match hand-worked
fixtures, the scope and span metrics pick their own events, and the driver
holds a unit to the configuration's guarantees. Nothing here yields a
device number."""

import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    manifest,
    peaks,
    q95_bytes,
    readers,
    reference_q95,
    xplane,
)
from benchmark.readers import device_scope  # noqa: E402

MANIFEST = manifest.load_manifest()
CELL = "q95_1chip"
PARAMS = {"window_start": 100, "window_days": 60, "target_state": 2,
          "target_company": 1}
Q95_METRICS = ["q95_job_device_s", "q95_filter_s", "q95_exchange_s",
               "q95_join_s", "q95_dispatch_s", "q95_job_roofline"]


def _spec(name):
    with open(manifest.layer_metric_path(name)) as f:
        return json.load(f)


def _fixture(name):
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", name)) as f:
        return json.load(f)


# -- the reference ------------------------------------------------------------

def _tables(**columns):
    """Six line items of three orders, written out by hand. Order 10: two
    warehouses, returned, both items pass the three predicates. Order 11:
    one warehouse (twice), returned. Order 12: two warehouses, never
    returned. Dimensions: d_date is the key itself, three states, two
    companies."""
    base = dict(
        ws_order=np.array([10, 10, 11, 11, 12, 12], np.int64),
        ws_warehouse=np.array([0, 1, 3, 3, 0, 2], np.int32),
        ws_ship_date=np.array([100, 160, 120, 120, 130, 130], np.int32),
        ws_ship_addr=np.array([5, 5, 5, 5, 5, 5], np.int32),
        ws_web_site=np.array([1, 1, 1, 1, 1, 1], np.int32),
        ws_ext_ship_cost=np.array([700, 50, 1, 1, 1, 1], np.int32),
        ws_net_profit=np.array([-900, 100, 1, 1, 1, 1], np.int32),
        wr_order=np.array([10, 11, 11], np.int64),
        d_date=np.arange(400, dtype=np.int32),
        ca_state=np.array([0, 1, 2, 0, 1, 2], np.int32),
        web_company=np.array([0, 1], np.int32))
    return SimpleNamespace(**dict(base, **columns))


def test_reference_is_the_query():
    want = reference_q95.reference_q95(_tables(), PARAMS)
    assert want == {"orders": 1, "ship_cost": 750, "net_profit": -800,
                    "orders_seen": 3, "multi_warehouse_orders": 2,
                    "returned_orders": 2}
    assert all(type(v) is int for v in want.values())
    assert reference_q95.q95_problems(want, _tables(), PARAMS) == []
    # "between" takes both ends: day 100 and day 160 are in, 161 is out
    late = _tables(ws_ship_date=np.array([100, 161, 120, 120, 130, 130],
                                         np.int32))
    assert reference_q95.reference_q95(late, PARAMS)["ship_cost"] == 700
    # each predicate bites
    for column, value in (("ca_state", np.zeros(6, np.int32)),
                          ("web_company", np.zeros(2, np.int32))):
        assert reference_q95.reference_q95(
            _tables(**{column: value}), PARAMS)["orders"] == 0
    # padding rows (order number -1) are no rows
    padded = _tables(
        ws_order=np.array([10, 10, 11, 11, 12, 12, -1], np.int64),
        **{c: np.append(getattr(_tables(), c), 0).astype(np.int32)
           for c in ("ws_warehouse", "ws_ship_date", "ws_ship_addr",
                     "ws_web_site", "ws_ext_ship_cost", "ws_net_profit")},
        wr_order=np.array([10, 11, 11, -1], np.int64))
    assert reference_q95.reference_q95(padded, PARAMS) == want


@pytest.mark.parametrize("wrong, names", [
    # order 10's return never arrived
    ({"orders": 0, "ship_cost": 0, "net_profit": 0, "returned_orders": 1},
     ["orders", "ship_cost", "net_profit", "returned_orders"]),
    # order 11 (one warehouse, two rows) counted into ws_wh
    ({"multi_warehouse_orders": 3}, ["multi_warehouse_orders"]),
    # a row of the pairs lost on the way: its order never seen
    ({"orders_seen": 2}, ["orders_seen"]),
], ids=["a_dropped_return", "a_one_warehouse_order_as_multi",
        "a_lost_pair"])
def test_q95_problems_finds_what_is_wrong(wrong, names):
    got = dict(reference_q95.reference_q95(_tables(), PARAMS), **wrong)
    problems = reference_q95.q95_problems(got, _tables(), PARAMS)
    assert [p.split(" is ")[0] for p in problems] == names
    assert all("the reference's" in p for p in problems)


def test_q95_problems_finds_a_wrapped_sum():
    """300 survivors of decimal(7,2)'s largest cost pass 2^31 cents: an
    int32 sum wraps, the reference's int64 does not."""
    n = 300
    big = _tables(
        ws_order=np.repeat(np.arange(n // 2, dtype=np.int64), 2),
        ws_warehouse=np.tile(np.array([0, 1], np.int32), n // 2),
        ws_ship_date=np.full(n, 120, np.int32),
        ws_ship_addr=np.full(n, 5, np.int32),
        ws_web_site=np.full(n, 1, np.int32),
        ws_ext_ship_cost=np.full(n, 9_999_999, np.int32),
        ws_net_profit=np.full(n, -9_999_999, np.int32),
        wr_order=np.arange(n // 2, dtype=np.int64))
    want = reference_q95.reference_q95(big, PARAMS)
    assert want["ship_cost"] == 2_999_999_700 > 2**31
    assert want["net_profit"] == -2_999_999_700
    wrapped = dict(want, ship_cost=int(np.int32(want["ship_cost"]
                                                - 2**32)))
    assert wrapped["ship_cost"] < 0
    assert reference_q95.q95_problems(wrapped, big, PARAMS) == [
        f"ship_cost is {wrapped['ship_cost']}, the reference's 2999999700"]


# -- the bytes and the roofline reader ----------------------------------------

def test_q95_bytes():
    # 1,000 web_sales rows, 100 web_returns rows, 10 survivors
    assert q95_bytes.shuffled_bytes(1000, 100, 10) == 12_000 + 800 + 160
    one = q95_bytes.job_bytes(1000, 100, 10, 1)
    assert one == {"hbm_bytes": 32_000 + 2 * 12_960, "ici_bytes": 0}
    four = q95_bytes.job_bytes(1000, 100, 10, 4)
    assert four == {"hbm_bytes": 57_920, "ici_bytes": 9_720}
    v5e = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds(one, v5e) == (
        pytest.approx(57_920 / 819e9), "hbm")
    # the cell's share: 45,000,024 rows, 4,499,845 returns, ~4,900 left
    share = q95_bytes.job_bytes(45_000_024, 4_499_845, 4_900, 1)
    assert share["hbm_bytes"] == (32 * 45_000_024
                                  + 2 * (540_000_288 + 35_998_760 + 78_400))
    assert peaks.least_seconds(share, v5e)[0] == pytest.approx(
        2_592_155_664 / 819e9)


def test_job_roofline_on_the_trace_fixture():
    reduced = xplane.reduce_trace(_fixture("trace_small.json"), [0, 1])
    spec = _spec("q95_job_roofline")
    info = {"ws_rows_per_chip": 1000, "wr_rows_per_chip": 100,
            "survivors_per_chip": 10, "chips": 2}
    got = readers.read_metric(
        spec, readers.Reading([], reduced, info, "TPU v5 lite"))
    # HBM 57,920 / 819e9 = 7.07e-8 s; ICI 12,960 / 2 / 200e9 = 3.24e-8 s,
    # so HBM bounds; the device was busy 8.32375 ms a unit
    assert got.pop("unit") == "%"
    assert got == pytest.approx({"value": 100 * (57_920 / 819e9) / 8.32375e-3,
                                 "bound_by": "hbm",
                                 "least_s": 57_920 / 819e9})
    assert readers.read_metric(
        spec, readers.Reading([], None, info, "TPU v5 lite")) is None


@pytest.fixture(scope="module")
def scoped():
    return _fixture("scoped_ops_q95.json")


@pytest.mark.parametrize("name, want", [
    # gathers of 1 ms in each job and a cumsum of 0.25 ms in the first;
    # the 0.5 ms gather before the first unit is outside the window
    ("q95_filter_s", 2.25e-3 / 2),
    # a sort of 0.5 ms, row gathers of 1.5 and 2.5 ms
    ("q95_exchange_s", 4.5e-3 / 2),
    # sorts of 2 and 3 ms, a cummax of 0.75 ms, a scatter of 0.25 ms
    ("q95_join_s", 6e-3 / 2)])
def test_scope_metrics_on_the_q95_fixture(scoped, name, want):
    pattern = _spec(name)["reader"]["match"]
    assert device_scope.scope_seconds(scoped, pattern, chips=1) == (
        pytest.approx(want))
    # the three scopes tile the job: only the unscoped copy lies outside
    total = sum(device_scope.scope_seconds(
        scoped, _spec(n)["reader"]["match"], chips=1)
        for n in ("q95_filter_s", "q95_exchange_s", "q95_join_s"))
    assert total == pytest.approx((2.25 + 4.5 + 6) * 1e-3 / 2)
    # a program without the scopes: nothing to read, and no error
    assert device_scope.scope_seconds(
        _fixture("scoped_ops_pagerank.json"), pattern, chips=1) is None


def test_dispatch_metric_reads_its_span():
    def job(seconds):
        return {"events": [
            {"name": "q95.job", "ph": "X", "ts": 0, "dur": 5e6,
             "args": {"ws_rows": 6}},
            {"name": "q95.dispatch", "ph": "X", "ts": 0,
             "dur": seconds * 1e6, "args": {}},
            {"name": "q95.wait", "ph": "X", "ts": 0, "dur": 4e6,
             "args": {}}]}
    got = readers.read_metric(
        _spec("q95_dispatch_s"),
        readers.Reading([job(0.002), job(0.003), job(0.009)], None, {}, "cpu"))
    assert got == {"value": pytest.approx(0.003), "unit": "s"}
    # a program without the span (the parent): nothing, and no error
    assert readers.read_metric(
        _spec("q95_dispatch_s"),
        readers.Reading([{"events": []}], None, {}, "cpu")) is None


def test_the_cell_names_its_six_metrics():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "job_makespan_s", "shuffle_gbps_per_chip", "setup_s"}
    assert [m["name"] for m, _ in cell.per_layer] == Q95_METRICS
    assert cell.config["kind"] == "q95" and cell.chips == 1
    assert cell.config["reduced"] == ["table_share"]
    assert set(cell.config["record"]) >= {"pairs", "returns", "survivors"}
    t = cell.traffic
    # the halving rule keeps 12 items an order and 10 % returns, and never
    # goes under a quarter of the share
    assert t["ws_rows_per_chip"] == 45_000_024 >> t["halved"] >= 11_250_006
    assert t["orders_per_chip"] == 3_750_002 >> t["halved"]
    assert t["wr_rows_per_chip"] == 4_499_845 >> t["halved"]
    assert (t["customer_address"], t["date_dim"], t["web_site"],
            t["warehouse"]) == (6_000_000, 73_049, 54, 20)
    # the job time measured at each size tried, the share first
    assert len(t["job_s_by_halving"]) == t["halved"] + 1


def test_the_kept_cell_pagerank_4chip_names_its_eight_metrics():
    cell = manifest.load_cell(MANIFEST, "pagerank_4chip")
    one = manifest.load_cell(MANIFEST, "pagerank_1chip")
    assert cell.chips == 4 and cell.config == one.config
    assert [m["name"] for m, _ in cell.per_layer] == [
        *(m["name"] for m, _ in one.per_layer), "pagerank_collective_s"]
    assert {m["name"] for m in cell.end_to_end} == {
        "job_makespan_s", "shuffle_gbps_per_chip", "setup_s"}
    # pagerank_1chip's size on every chip of the host
    for key in ("edges_per_chip", "vertices_per_chip", "iterations",
                "zipf_s", "damping"):
        assert cell.traffic[key] == one.traffic[key]
    # the collective's metric reads the ragged all-to-all ops of a trace
    reduced = xplane.reduce_trace(_fixture("trace_small.json"), [0, 1])
    got = readers.read_metric(
        _spec("pagerank_collective_s"),
        readers.Reading([], reduced, {}, "TPU v5 lite"))
    assert got == {"value": pytest.approx(0.675e-3), "unit": "s"}
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four == 2 and len(MANIFEST["workloads"]) == 6


# -- the driver ----------------------------------------------------------------

def test_q95_driver_holds_a_unit_to_the_guarantees(tmp_path):
    """The driver on four of conftest's virtual CPU devices, in process:
    the four-chip shape no cell drives yet."""
    import jax

    from benchmark.drivers import q95

    cell = manifest.load_cell(MANIFEST, CELL)
    sizes = dict(cell.traffic, **cell.traffic["rehearsal"])
    work = q95.Workload(cell.config, sizes, jax.devices()[:4], 2**31 + 9,
                        str(tmp_path))
    try:
        # the warm unit is held to the job's own count of survivors: the
        # reference's count is not made inside set-up
        warm = work.run_unit()
        assert warm["warm"] and work.unit_problems(warm) == []
        assert "survivors" not in vars(work)
        facts = work.run_unit()
        assert facts["end"] > facts["start"] and not facts["warm"]
        assert work.unit_problems(facts) == []
        assert work.verify_last() == []
        assert work.info == {
            "ws_rows_per_chip": sizes["ws_rows_per_chip"],
            "wr_rows_per_chip": sizes["wr_rows_per_chip"],
            "survivors_per_chip": work.survivors / 4, "chips": 4,
            "exchange_impl": "gather"}
        assert work.survivors > 0
        assert work.unit_bytes == (
            12 * 4 * sizes["ws_rows_per_chip"]
            + 8 * 4 * sizes["wr_rows_per_chip"] + 16 * work.survivors)
        assert {e["name"] for e in facts["events"]} == {
            "q95.job", "q95.dispatch", "q95.wait", "q95.recv_fill",
            "q95.survivors"}
        # a survivor the filter lost shows against the reference's count
        lost = json.loads(json.dumps(facts))
        for e in lost["events"]:
            if e["name"] == "q95.job":
                e["args"]["received"][2] -= 1
                e["args"]["survivors"] -= 1
        assert len(work.unit_problems(lost)) == 1
        # a return short, a buffer past its capacity, answers pulled to
        # the host: each is named
        short = json.loads(json.dumps(facts))
        for e in short["events"]:
            if e["name"] == "q95.job":
                e["args"]["received"][1] -= 1
            if e["name"] == "q95.recv_fill":
                e["args"]["value"] = 1.5
        short["on_device"] = False
        broken = work.unit_problems(short)
        assert len(broken) == 3
        assert "records received" in broken[0]
        assert "recv_fill" in broken[1] and "jax.Array" in broken[2]
        # the last job's answers against broken tables: an order's
        # returns dropped from what the reference is fed show as a
        # difference
        kept = work.tables.wr_order != work.tables.wr_order[0]
        work.tables = work.tables._replace(
            wr_order=work.tables.wr_order[kept])
        assert any("returned_orders" in p for p in work.verify_last())
    finally:
        work.close()
