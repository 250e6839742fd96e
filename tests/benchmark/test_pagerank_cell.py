"""The ``pagerank_powerlaw`` configuration's own pieces of the yardstick:
the float64 reference finds what is wrong (a dropped edge, a wrong damping,
contributions rounded to bfloat16) and passes what is right, the roofline
reader's arithmetic matches hand-worked fixtures, the scope and span
metrics pick their own events, and the driver holds a unit to the
configuration's guarantees. Nothing here yields a device number."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import (  # noqa: E402
    manifest,
    pagerank_bytes,
    peaks,
    readers,
    reference_pagerank,
    xplane,
)
from benchmark.readers import device_scope  # noqa: E402

MANIFEST = manifest.load_manifest()
CELL = "pagerank_1chip"
DAMPING, ITERATIONS, NUM_V = 0.85, 3, 2048


def _spec(name):
    with open(manifest.layer_metric_path(name)) as f:
        return json.load(f)


def _fixture(name):
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", name)) as f:
        return json.load(f)


# -- the reference ------------------------------------------------------------

@pytest.fixture(scope="module")
def graph():
    """A seeded graph with Zipf in-links, made here in numpy alone: the
    reference's tests lean on nothing of the program's."""
    rng = np.random.default_rng(2**31 + 5)
    weights = np.arange(1, NUM_V + 1, dtype=np.float64) ** -0.9
    dst = rng.permutation(NUM_V)[np.searchsorted(
        np.cumsum(weights) / weights.sum(), rng.random(60_000))]
    src = rng.integers(0, NUM_V, len(dst))
    return np.stack([src, dst], axis=1).astype(np.int32)


def _float32_job(edges, damping=DAMPING, contribution_dtype=np.float32):
    """The job as the program computes it, but in numpy: float32 ranks
    and contributions (or a narrower type's), float32 sums."""
    src, dst = edges[:, 0], edges[:, 1]
    out_deg = np.maximum(np.bincount(src, minlength=NUM_V), 1).astype(
        np.float32)
    ranks = np.full(NUM_V, 1.0 / NUM_V, dtype=np.float32)
    for _ in range(ITERATIONS):
        contrib = (ranks[src] / out_deg[src]).astype(
            contribution_dtype).astype(np.float32)
        sums = np.zeros(NUM_V, dtype=np.float32)
        np.add.at(sums, dst, contrib)
        ranks = (np.float32((1.0 - damping) / NUM_V)
                 + np.float32(damping) * sums)
    return ranks


def test_reference_is_pagerank_and_passes_a_float32_job(graph):
    want, bound, in_degree = reference_pagerank.reference_pagerank(
        graph, NUM_V, DAMPING, ITERATIONS)
    assert want.dtype == np.float64 and want.sum() == pytest.approx(1.0)
    assert in_degree.sum() == len(graph) and (bound > 0).all()
    # the bound is tight where sums are short and wide on the hubs
    low = in_degree <= 4
    assert low.any() and np.median((bound / want)[low]) < 3e-6
    assert (bound / want).max() > 10 * np.median((bound / want)[low])
    problems, readings = reference_pagerank.pagerank_report(
        _float32_job(graph), graph, NUM_V, DAMPING, ITERATIONS)
    assert problems == []
    assert 0 < readings["bound_share"] < 0.5
    assert readings["max_in_degree"] == in_degree.max()
    # padding rows (src < 0) are no edges
    padded = np.concatenate([graph, np.full((7, 2), -1, np.int32)])
    again, _, _ = reference_pagerank.reference_pagerank(
        padded, NUM_V, DAMPING, ITERATIONS)
    np.testing.assert_array_equal(again, want)


def _dropped_edge(edges):
    in_degree = np.bincount(edges[:, 1], minlength=NUM_V)
    at = int(np.nonzero(in_degree[edges[:, 1]] <= 30)[0][0])
    return _float32_job(np.delete(edges, at, axis=0))


def _bfloat16_contributions(edges):
    import ml_dtypes

    return _float32_job(edges, contribution_dtype=ml_dtypes.bfloat16)


@pytest.mark.parametrize("wrong", [
    _dropped_edge,
    lambda edges: _float32_job(edges, damping=0.8),
    _bfloat16_contributions,
], ids=["a_dropped_edge", "a_wrong_damping", "bfloat16_contributions"])
def test_pagerank_problems_finds_what_is_wrong(graph, wrong):
    problems, readings = reference_pagerank.pagerank_report(
        wrong(graph), graph, NUM_V, DAMPING, ITERATIONS)
    assert len(problems) == 1 and "float32" in problems[0]
    # not by a whisker: the limit has room above a float32 job's reading
    assert readings["bound_share"] > 50 * reference_pagerank.BOUND_SHARE


def test_pagerank_problems_on_ranks_that_cannot_be_compared(graph):
    assert "shape" in reference_pagerank.pagerank_problems(
        np.zeros(NUM_V - 1), graph, NUM_V, DAMPING, ITERATIONS)[0]
    ranks = _float32_job(graph)
    ranks[3] = np.nan
    assert reference_pagerank.pagerank_problems(
        ranks, graph, NUM_V, DAMPING, ITERATIONS) == ["a rank is not finite"]


# -- the bytes and the roofline reader ----------------------------------------

def test_pagerank_bytes():
    one = pagerank_bytes.job_bytes(67_108_864, 1_875_000, 1, 3)
    assert one == {"hbm_bytes": 3 * (8 * 67_108_864 + 12 * 1_875_000),
                   "ici_bytes": 0}
    v5e = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds(one, v5e) == (
        pytest.approx(3 * 559_370_912 / 819e9), "hbm")
    four = pagerank_bytes.job_bytes(67_108_864, 1_875_000, 4, 3)
    assert four["ici_bytes"] == 3 * 8 * 67_108_864 * 3 / 4
    assert peaks.least_seconds(four, v5e)[1] == "ici"
    assert pagerank_bytes.accumulate_bytes(1000, 10, 3) == {
        "hbm_bytes": 3 * (8000 + 40), "ici_bytes": 0}


def test_job_roofline_on_the_trace_fixture():
    reduced = xplane.reduce_trace(_fixture("trace_small.json"), [0, 1])
    spec = _spec("pagerank_job_roofline")
    info = {"edges_per_chip": 1000, "vertices_per_chip": 100, "chips": 2,
            "iterations": 3}
    got = readers.read_metric(
        spec, readers.Reading([], reduced, info, "TPU v5 lite"))
    # HBM 3 x (8000 + 1200) / 819e9 = 3.37e-8 s; ICI 3 x 8000 / 2 / 200e9
    # = 6e-8 s, so ICI bounds; the device was busy 8.32375 ms a unit
    assert got.pop("unit") == "%"
    assert got == pytest.approx({"value": 100 * 6e-8 / 8.32375e-3,
                                 "bound_by": "ici", "least_s": 6e-8})
    assert readers.read_metric(
        spec, readers.Reading([], None, info, "TPU v5 lite")) is None


@pytest.fixture(scope="module")
def scoped():
    return _fixture("scoped_ops_pagerank.json")


@pytest.mark.parametrize("name, want", [
    # two jobs on one chip, a gather of 1 ms in each
    ("pagerank_contrib_s", 2e-3 / 2),
    # a sort of 0.5 ms and a row gather of 1.5 ms in each
    ("pagerank_exchange_s", 4e-3 / 2),
    # scatter-adds of 2 and 3 ms and the damping's 0.25 ms; the 0.5 ms
    # scatter-add before the first unit is outside the window
    ("pagerank_accumulate_s", 5.25e-3 / 2)])
def test_scope_metrics_on_the_pagerank_fixture(scoped, name, want):
    pattern = _spec(name)["reader"]["match"]
    assert device_scope.scope_seconds(scoped, pattern, chips=1) == (
        pytest.approx(want))
    # the three scopes tile the step: only the reset lies under none
    total = sum(device_scope.scope_seconds(
        scoped, _spec(n)["reader"]["match"], chips=1)
        for n in ("pagerank_contrib_s", "pagerank_exchange_s",
                  "pagerank_accumulate_s"))
    assert total == pytest.approx((2 + 4 + 5.25) * 1e-3 / 2)


def test_accumulate_roofline_on_the_pagerank_fixture(scoped, monkeypatch):
    monkeypatch.setattr(device_scope, "newest_profile", lambda: "x.pb")
    monkeypatch.setattr(device_scope, "load_scoped_ops", lambda path: scoped)
    spec = _spec("pagerank_accumulate_roofline")
    info = {"edges_per_chip": 1_000_000, "vertices_per_chip": 10_000,
            "chips": 1, "iterations": 3}
    trace = {"units": 2, "chips": 1, "busy_s": 0.02}
    got = readers.read_metric(
        spec, readers.Reading([], trace, info, "TPU v5 lite"))
    least = 3 * (8e6 + 4e4) / 819e9
    assert got.pop("unit") == "%"
    assert got == pytest.approx({"value": 100 * least / 2.625e-3,
                                 "bound_by": "hbm", "least_s": least})
    # a program without the scope: nothing to read, and no error
    monkeypatch.setattr(
        device_scope, "load_scoped_ops",
        lambda path: _fixture("scoped_ops_small.json"))
    assert readers.read_metric(spec, readers.Reading(
        [], dict(trace, chips=2), info, "TPU v5 lite")) is None
    assert readers.read_metric(
        spec, readers.Reading([], None, info, "TPU v5 lite")) is None


def test_dispatch_metric_reads_its_span():
    def job(seconds):
        return {"events": [
            {"name": "pagerank.job", "ph": "X", "ts": 0, "dur": 5e6,
             "args": {"iterations": 3}},
            {"name": "pagerank.dispatch", "ph": "X", "ts": 0,
             "dur": seconds * 1e6, "args": {}},
            {"name": "pagerank.wait", "ph": "X", "ts": 0, "dur": 4e6,
             "args": {}}]}
    got = readers.read_metric(
        _spec("pagerank_dispatch_s"),
        readers.Reading([job(0.002), job(0.003), job(0.009)], None, {}, "cpu"))
    assert got == {"value": pytest.approx(0.003), "unit": "s"}


def test_the_cell_names_its_seven_metrics():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "job_makespan_s", "shuffle_gbps_per_chip", "setup_s"}
    assert [m["name"] for m, _ in cell.per_layer] == [
        "pagerank_job_device_s", "pagerank_contrib_s", "pagerank_exchange_s",
        "pagerank_accumulate_s", "pagerank_dispatch_s",
        "pagerank_job_roofline", "pagerank_accumulate_roofline"]
    assert cell.config["kind"] == "pagerank" and cell.chips == 1
    assert cell.config["record"]["bytes"] == 8
    assert cell.traffic["iterations"] == 3 and cell.traffic["zipf_s"] == 0.9
    # the halving rule keeps the degree and never goes under 2^24 edges
    assert cell.traffic["edges_per_chip"] == 67_108_864 >> cell.traffic[
        "halved"] >= 16_777_216
    assert cell.traffic["vertices_per_chip"] == 1_875_000 >> cell.traffic[
        "halved"]


# -- the driver ----------------------------------------------------------------

def test_pagerank_driver_holds_a_unit_to_the_guarantees(tmp_path):
    """The driver on four of conftest's virtual CPU devices, in process:
    the four-chip shape no cell drives yet."""
    import jax

    from benchmark.drivers import pagerank

    cell = manifest.load_cell(MANIFEST, CELL)
    sizes = dict(cell.traffic, **cell.traffic["rehearsal"])
    work = pagerank.Workload(cell.config, sizes, jax.devices()[:4],
                             2**31 + 9, str(tmp_path))
    try:
        facts = work.run_unit()
        assert facts["end"] > facts["start"]
        assert work.unit_problems(facts) == []
        assert work.verify_last() == []
        assert work.info == {
            "edges_per_chip": sizes["edges_per_chip"],
            "vertices_per_chip": sizes["vertices_per_chip"],
            "iterations": 3, "chips": 4, "exchange_impl": "gather"}
        assert work.unit_bytes == 3 * 4 * sizes["edges_per_chip"] * 8
        assert {e["name"] for e in facts["events"]} == {
            "pagerank.job", "pagerank.dispatch", "pagerank.wait",
            "pagerank.recv_fill", "pagerank.max_in_degree"}
        # a contribution short, a buffer past its capacity, ranks pulled
        # to the host: each is named
        short = json.loads(json.dumps(facts))
        for e in short["events"]:
            if e["name"] == "pagerank.job":
                e["args"]["received"][1] -= 1
            if e["name"] == "pagerank.recv_fill":
                e["args"]["value"] = 1.5
        short["on_device"] = False
        broken = work.unit_problems(short)
        assert len(broken) == 3
        assert "contributions received" in broken[0]
        assert "recv_fill" in broken[1] and "jax.Array" in broken[2]
    finally:
        work.close()
