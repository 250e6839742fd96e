"""The ``als_netflix`` configuration's own pieces of the yardstick, and the
kept cell ``q95_4chip``'s: the manifest is well-formed with both new cells in
it and the four-chip cells within their cap, both new cells rehearse, the bytes and the roofline reader match
hand-worked fixtures, the scope, span and collective metrics pick their own
events, and the driver holds a unit to the configuration's guarantees,
faulted one by one. The float64 reference's own tests are in
``tests/test_als.py``. Nothing here yields a device number."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import als_bytes, manifest, peaks, readers, xplane  # noqa: E402
from benchmark.readers import device_scope  # noqa: E402

MANIFEST = manifest.load_manifest()
CELL = "als_4chip"
ALS_METRICS = ["als_job_device_s", "als_dispatch_s", "als_exchange_s",
               "als_gather_s", "als_normal_s", "als_solve_s",
               "als_collective_s", "als_job_roofline", "als_normal_roofline"]
INFO = {"ratings_per_chip": 1_000_000.0,
        "recv_rows_per_chip": {"item": 20_000.0, "user": 1_000.0},
        "ids_per_chip": {"item": 250, "user": 5_000},
        "rank": 10, "iterations": 3, "chips": 4}


def _spec(name):
    with open(manifest.layer_metric_path(name)) as f:
        return json.load(f)


def _fixture(name):
    with open(os.path.join(manifest.BENCH_DIR, "fixtures", name)) as f:
        return json.load(f)


# -- the manifest --------------------------------------------------------------

def test_the_manifest_holds_both_new_cells_within_the_four_chip_cap():
    assert manifest.problems(MANIFEST) == []
    assert "als_netflix" in {c["name"] for c in MANIFEST["configs"]}
    cells = MANIFEST["workloads"]
    by_name = {w["name"]: w for w in cells}
    assert by_name["als_4chip"]["chips"] == by_name["q95_4chip"]["chips"] == 4
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    # the cap: a benchmark with more than half its cells on four chips
    # is refused
    more = json.loads(json.dumps(MANIFEST))
    more["workloads"] += [
        dict(by_name["als_4chip"], name=f"als_4chip_again{i}",
             traffic=f"jobs_again{i}") for i in range(len(cells))]
    assert any("ask for 4 chips" in p for p in manifest.problems(more))


def test_the_cell_names_its_nine_metrics():
    cell = manifest.load_cell(MANIFEST, CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "job_makespan_s", "shuffle_gbps_per_chip", "setup_s"}
    assert [m["name"] for m, _ in cell.per_layer] == ALS_METRICS
    assert cell.chips == 4
    config, t = cell.config, cell.traffic
    assert config["kind"] == "als" and config["architecture"] is None
    assert config["params"] == {"rank": 10, "reg": 0.1, "out_factor": 2,
                                "impl": "auto"}
    # users, items, ratings, rank and regParam are the source's; the
    # number of sweeps is cut only where the sizing rule left maxIter 10
    assert config["reduced"] == ([] if t["iterations"] == 10
                                 else ["iterations"])
    assert (t["ratings"], t["users"], t["items"]) == (
        100_480_507, 480_189, 17_770)
    assert t["item_top_share"] == pytest.approx(232_944 / 100_480_507,
                                                rel=1e-3)
    assert t["user_top_share"] == pytest.approx(17_653 / 100_480_507,
                                                rel=2e-3)
    assert t["iterations"] in (10, 5, 3, 2)
    # the job time measured at each step of the sizing rule, 10 first
    steps = [int(k) for k in t["job_s_by_iterations"]]
    assert steps == [10, 5, 3, 2][:len(steps)] and steps[-1] == t["iterations"]
    assert set(config["assumed"]) >= {"ratings", "users", "items",
                                      "item_top_share", "user_top_share",
                                      "zipf_exponents", "out_factor"}
    assert set(config["departures"]) >= {"precision", "blocks", "data"}


def test_the_kept_cell_q95_4chip_is_q95_1chip_on_every_chip():
    cell = manifest.load_cell(MANIFEST, "q95_4chip")
    one = manifest.load_cell(MANIFEST, "q95_1chip")
    assert cell.chips == 4 and cell.config == one.config
    assert [m["name"] for m, _ in cell.per_layer] == [
        *(m["name"] for m, _ in one.per_layer), "q95_collective_s"]
    assert {m["name"] for m in cell.end_to_end} == {
        "job_makespan_s", "shuffle_gbps_per_chip", "setup_s"}
    for key in ("ws_rows_per_chip", "orders_per_chip", "wr_rows_per_chip",
                "customer_address", "date_dim", "web_site", "warehouse",
                "rehearsal"):
        assert cell.traffic[key] == one.traffic[key], key
    t = cell.traffic
    assert t["ws_rows_per_chip"] == 45_000_024 >> t["halved"] >= 11_250_006
    assert len(t["job_s_by_halving"]) == t["halved"] + 1
    # pagerank_4chip is as PR 33 left it
    kept = manifest.load_cell(MANIFEST, "pagerank_4chip")
    assert [m["name"] for m, _ in kept.per_layer][-1] == (
        "pagerank_collective_s")


@pytest.mark.parametrize("cell", ["als_4chip", "q95_4chip"])
def test_the_new_cells_rehearse(cell):
    """The cell's own command at the traffic file's toy size on four
    virtual CPU devices: ``correct``, no failed unit, its metrics named
    ``rehearsal.<name>``."""
    lines = []
    for trace in ("0", "1"):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", cell,
             "--seed", str(2**31 + 35), "--seconds", "0.3", "--trace", trace,
             "--rehearsal"], cwd=REPO, capture_output=True, text=True,
            timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    for line in lines:
        assert line["correct"] and line["failed"] == 0
        assert line["device"]["count"] == 4
    assert set(lines[0]["metrics"]) == {
        "rehearsal.job_makespan_s", "rehearsal.shuffle_gbps_per_chip",
        "rehearsal.setup_s"}
    span = "rehearsal.%s_dispatch_s" % cell.split("_")[0]
    assert lines[1]["metrics"][span]["value"] > 0


# -- the bytes and the roofline reader ----------------------------------------

def test_als_bytes():
    half = als_bytes.half_step_bytes(1000, 200, 50, 10, 4)
    assert half == {"hbm_bytes": 8_000 + 2 * 40 * 200 + 40 * 50,
                    "ici_bytes": 40 * 200 * 3 / 4}
    job = als_bytes.job_bytes(*(INFO[k] for k in (
        "ratings_per_chip", "recv_rows_per_chip", "ids_per_chip", "rank")),
        4, 3)
    assert job == {
        "hbm_bytes": 3 * (2 * 8e6 + 80 * 21_000 + 40 * 5_250),
        "ici_bytes": 3 * 40 * 21_000 * 3 / 4}
    normal = als_bytes.normal_bytes(1e6, INFO["recv_rows_per_chip"],
                                    INFO["ids_per_chip"], 10, 3)
    assert normal == {"hbm_bytes": 3 * (2 * 8e6 + 40 * 21_000 + 260 * 5_250),
                      "ici_bytes": 0}
    # the cell's half-steps: a chip's 25,120,127 ratings read once is
    # 0.245 ms of HBM, the 480,188 rows an items' half-step receives
    # 0.072 ms of ICI: HBM bounds
    v5e = peaks.peaks_for("TPU v5 lite")
    cell = als_bytes.half_step_bytes(25_120_127, 480_188, 4_443, 10, 4)
    assert peaks.least_seconds(cell, v5e) == (
        pytest.approx((8 * 25_120_127 + 80 * 480_188 + 40 * 4_443) / 819e9),
        "hbm")


def test_job_roofline_on_the_trace_fixture():
    reduced = xplane.reduce_trace(_fixture("trace_small.json"), [0, 1])
    got = readers.read_metric(
        _spec("als_job_roofline"),
        readers.Reading([], reduced, INFO, "TPU v5 lite"))
    least = 3 * (2 * 8e6 + 80 * 21_000 + 40 * 5_250) / 819e9
    assert got.pop("unit") == "%"
    assert got == pytest.approx({"value": 100 * least / 8.32375e-3,
                                 "bound_by": "hbm", "least_s": least})
    assert readers.read_metric(
        _spec("als_job_roofline"),
        readers.Reading([], None, INFO, "TPU v5 lite")) is None


@pytest.fixture(scope="module")
def scoped():
    return _fixture("scoped_ops_als.json")


@pytest.mark.parametrize("name, want", [
    # two gathers and a sort of 0.25 + 0.25 + 0.5 ms and a collective of
    # 0.5 ms in the first job, a collective of 1 ms in the second
    ("als_exchange_s", 2.5e-3 / 2),
    # 3 and 5 ms; the 0.4 ms gather before the first unit is outside
    ("als_gather_s", 8e-3 / 2),
    # tile sums of 1 and 1.5 ms, a segmented sum of 0.5 ms
    ("als_normal_s", 3e-3 / 2),
    ("als_solve_s", 1e-3 / 2)])
def test_scope_metrics_on_the_als_fixture(scoped, name, want):
    pattern = _spec(name)["reader"]["match"]
    assert device_scope.scope_seconds(scoped, pattern, chips=1) == (
        pytest.approx(want))
    # the four scopes tile the job: only the unscoped copy lies outside
    total = sum(device_scope.scope_seconds(
        scoped, _spec(n)["reader"]["match"], chips=1)
        for n in ("als_exchange_s", "als_gather_s", "als_normal_s",
                  "als_solve_s"))
    assert total == pytest.approx(14.5e-3 / 2)
    # a program without the scopes (the parent): nothing, and no error
    assert device_scope.scope_seconds(
        _fixture("scoped_ops_q95.json"), pattern, chips=1) is None


def test_normal_roofline_on_the_als_fixture(scoped, monkeypatch):
    monkeypatch.setattr(device_scope, "newest_profile", lambda: "x.pb")
    monkeypatch.setattr(device_scope, "load_scoped_ops", lambda path: scoped)
    spec = _spec("als_normal_roofline")
    trace = {"units": 2, "chips": 1, "busy_s": 0.02}
    got = readers.read_metric(
        spec, readers.Reading([], trace, INFO, "TPU v5 lite"))
    least = 3 * (2 * 8e6 + 40 * 21_000 + 260 * 5_250) / 819e9
    assert got.pop("unit") == "%"
    # the gather's and the normal equations' 11 ms over two jobs
    assert got == pytest.approx({"value": 100 * least / 5.5e-3,
                                 "bound_by": "hbm", "least_s": least})
    monkeypatch.setattr(
        device_scope, "load_scoped_ops",
        lambda path: _fixture("scoped_ops_q95.json"))
    assert readers.read_metric(
        spec, readers.Reading([], trace, INFO, "TPU v5 lite")) is None
    assert readers.read_metric(
        spec, readers.Reading([], None, INFO, "TPU v5 lite")) is None


@pytest.mark.parametrize("name", ["als_collective_s", "q95_collective_s"])
def test_collective_metrics_read_the_ragged_all_to_all(name):
    reduced = xplane.reduce_trace(_fixture("trace_small.json"), [0, 1])
    got = readers.read_metric(
        _spec(name), readers.Reading([], reduced, {}, "TPU v5 lite"))
    assert got == {"value": pytest.approx(0.675e-3), "unit": "s"}
    assert readers.read_metric(
        _spec(name), readers.Reading([], None, {}, "TPU v5 lite")) is None


def test_dispatch_metric_reads_its_span():
    def job(seconds):
        return {"events": [
            {"name": "als.job", "ph": "X", "ts": 0, "dur": 5e6,
             "args": {"iterations": 3}},
            {"name": "als.dispatch", "ph": "X", "ts": 0,
             "dur": seconds * 1e6, "args": {}},
            {"name": "als.wait", "ph": "X", "ts": 0, "dur": 4e6,
             "args": {}}]}
    got = readers.read_metric(
        _spec("als_dispatch_s"),
        readers.Reading([job(0.002), job(0.003), job(0.009)], None, {}, "cpu"))
    assert got == {"value": pytest.approx(0.003), "unit": "s"}
    # a program without the span (the parent): nothing, and no error
    assert readers.read_metric(
        _spec("als_dispatch_s"),
        readers.Reading([{"events": []}], None, {}, "cpu")) is None


# -- the driver ----------------------------------------------------------------

def test_als_driver_holds_a_unit_to_the_guarantees(tmp_path):
    """The driver on four of conftest's virtual CPU devices, in process."""
    import jax

    from benchmark.drivers import als

    cell = manifest.load_cell(MANIFEST, CELL)
    sizes = dict(cell.traffic, **cell.traffic["rehearsal"])
    work = als.Workload(cell.config, sizes, jax.devices()[:4], 2**31 + 9,
                        str(tmp_path))
    try:
        facts = work.run_unit()
        assert facts["end"] > facts["start"]
        assert work.unit_problems(facts) == []
        assert work.verify_last() == []
        links = work.out_links
        assert work.unit_bytes == sizes["iterations"] * 40 * (
            links["item"] + links["user"])
        assert work.info == {
            "ratings_per_chip": sizes["ratings"] / 4,
            "recv_rows_per_chip": {"item": links["item"] / 4,
                                   "user": links["user"] / 4},
            "ids_per_chip": {"item": -(-sizes["items"] // 4),
                             "user": -(-sizes["users"] // 4)},
            "rank": 10, "iterations": sizes["iterations"], "chips": 4,
            "exchange_impl": "gather"}
        assert {e["name"] for e in facts["events"]} == {
            "als.job", "als.dispatch", "als.wait", "als.recv_fill",
            "als.max_segment", "als.out_links"}
        # a factor row short in one half-step, a buffer past its capacity,
        # factors pulled to the host: each is named
        short = json.loads(json.dumps(facts))
        for e in short["events"]:
            if e["name"] == "als.job":
                e["args"]["received"][1] -= 1
            if e["name"] == "als.recv_fill":
                e["args"]["value"] = 1.5
        short["on_device"] = False
        broken = work.unit_problems(short)
        assert len(broken) == 3
        assert "factor rows received" in broken[0]
        assert "recv_fill" in broken[1] and "jax.Array" in broken[2]
        # the last job's factors against broken ratings: one rating's
        # value changed in what the reference is fed shows as a vector
        # outside float32's bound
        work.ratings.rating[0] = 6.0 - work.ratings.rating[0] + 0.5
        assert any("times what float32" in p for p in work.verify_last())
        # and factors that are not the last job's are not vouched for by
        # the replay
        work.last = (work.last[0] * 2, work.last[1])
        assert any("bit for bit" in p for p in work.verify_last())
    finally:
        work.close()


@pytest.mark.parametrize("iterations", [1, 3])
def test_als_driver_checks_the_last_sweep_from_its_own_inputs(
        tmp_path, monkeypatch, iterations):
    """What decides ``correct``: the last sweep's two half-steps, every
    item's and every user's final vector, solved by the reference from the
    users the sweep started from (the seeded ones in a job of one sweep)."""
    import jax
    import numpy as np

    from benchmark import reference_als
    from benchmark.drivers import als

    cell = manifest.load_cell(MANIFEST, CELL)
    sizes = {**cell.traffic, **cell.traffic["rehearsal"],
             "iterations": iterations}
    work = als.Workload(cell.config, sizes, jax.devices()[:4], 2**31 + 11,
                        str(tmp_path))
    seen = {}
    report = reference_als.als_report

    def spy(steps, user, item, rating, start, reg, first_sweep=0):
        seen.update(steps=steps, start=start, first_sweep=first_sweep)
        return report(steps, user, item, rating, start, reg, first_sweep)

    monkeypatch.setattr(reference_als, "als_report", spy)
    try:
        work.run_unit()
        assert work.verify_last() == []
        assert len(seen["steps"]) == 1
        assert seen["first_sweep"] == iterations - 1
        seeded = work.job.initial_user_factors()
        assert np.array_equal(seen["start"], seeded) == (iterations == 1)
        # a last sweep started from other users than the job's is not the
        # reference's: the comparison does read the sweep's own inputs
        problems, _ = report(seen["steps"], *work.ratings,
                             np.roll(seen["start"], 1, axis=0),
                             work.cfg.reg)
        assert any("item" in p for p in problems)
    finally:
        work.close()
