"""The benchmark's own checks: the manifest resolves to files, each cell
rehearses end to end at toy size with the contract's last line, no TPU
means no result, and the trace arithmetic matches a fixture worked out by
hand. Nothing here yields a device number: rehearsals run on virtual CPU
devices and name their metrics ``rehearsal.<name>``."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, peaks, readers, reference, xplane  # noqa: E402

MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(args, cwd=REPO, **env):
    full = dict(os.environ, **env)
    return subprocess.run([sys.executable, "benchmark/run.py", *args],
                          cwd=cwd, env=full, capture_output=True, text=True,
                          timeout=300)


# -- the manifest -----------------------------------------------------------

def test_manifest_is_well_formed():
    assert manifest.problems(MANIFEST) == []
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"][-1] == "benchmark/run.py"
    assert set(MANIFEST["paths"]) == {"benchmark", "tests/benchmark"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("breakage, says", [
    (lambda m: m["workloads"][0].update(traffic="no_such_mix"), "no_such_mix"),
    (lambda m: m["per_layer"][0].update(moves="job_makespan_s"), "moves"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "4 chips"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="nofile")),
     "nofile"),
])
def test_manifest_problems_are_found(breakage, says):
    broken = json.loads(json.dumps(MANIFEST))
    breakage(broken)
    found = manifest.problems(broken)
    assert any(says in p for p in found), found


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files_of_its_own(cell):
    got = manifest.load_cell(MANIFEST, cell)
    listed = {c["name"]: c for c in MANIFEST["configs"]}[got.config["name"]]
    assert got.config["source"] == listed["source"]
    assert got.config["reduced"] == listed["reduced"]
    assert got.config["assumed"] and got.config["guarantees"]
    assert os.path.isfile(os.path.join(
        manifest.BENCH_DIR, "drivers", got.config["kind"] + ".py"))
    assert got.traffic["rehearsal"]
    assert "setup_s" in {m["name"] for m in got.end_to_end}
    for m, spec in got.per_layer:
        assert spec["name"] == m["name"] and "reader" in spec


def test_an_unknown_cell_fails_loudly():
    with pytest.raises(manifest.ManifestError, match="nonesuch"):
        manifest.load_cell(MANIFEST, "nonesuch")


# -- one run, end to end ----------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell, trace):
    got = manifest.load_cell(MANIFEST, cell)
    proc = _run(["--workload", cell, "--seed", str(2**31 + 351), "--seconds",
                 "0.5", "--trace", str(trace), "--rehearsal"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS    # no device plane here, so no breakdown
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == got.chips
    if trace:
        # what the host's spans give; device metrics have nothing to read
        want = {m["name"] for m, spec in got.per_layer
                if spec["reader"]["source"] == "host_span"}
    else:
        want = {m["name"] for m in got.end_to_end}
    assert set(line["metrics"]) == {"rehearsal." + n for n in want}
    for value in line["metrics"].values():
        assert value["value"] > 0 and value["unit"]


def test_fused_driver_on_four_devices(tmp_path):
    """The four-chip shape of the fused driver (traffic
    ``rounds_512mib_per_chip``), which no cell of the manifest drives
    yet: here on four of conftest's virtual CPU devices, in process."""
    import jax

    from benchmark.drivers import fused

    with open(os.path.join(manifest.BENCH_DIR, "configs",
                           "terasort_fused.json")) as f:
        config = json.load(f)
    with open(manifest.traffic_path("rounds_512mib_per_chip")) as f:
        traffic = json.load(f)
    sizes = dict(traffic, **traffic["rehearsal"])
    work = fused.Workload(config, sizes, jax.devices()[:4], 2**31 + 7,
                          str(tmp_path))
    try:
        facts = work.run_unit()
        assert facts["end"] > facts["start"]
        assert work.unit_problems(facts) == []
        assert work.verify_last() == []
        assert work.info["chips"] == 4
        assert work.unit_bytes == 4 * sizes["rows_per_chip"] * 100
    finally:
        work.close()


def test_without_a_tpu_there_is_no_result():
    proc = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0"], JAX_PLATFORMS="cpu")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in MANIFEST["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path)
    proc = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                 "--trace", "0", "--rehearsal"], cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "sparkrdma_tpu" in proc.stderr


# -- the trace arithmetic, against the fixture's hand-worked numbers --------

@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(manifest.BENCH_DIR, "fixtures",
                           "trace_small.json")) as f:
        return xplane.reduce_trace(json.load(f), [0, 1])


def test_trace_reduction_busy_idle_ops_and_gaps(reduced):
    assert reduced["units"] == 2 and reduced["chips"] == 2
    assert reduced["window_s"] == pytest.approx(20.5e-3)
    assert reduced["busy_s"] == pytest.approx(16.6475e-3)
    assert 1 - reduced["busy_s"] / reduced["window_s"] == pytest.approx(
        0.187927, abs=1e-6)
    got = xplane.breakdown(reduced)
    assert [n for n, _ in got["device_ops"]] == [
        "fusion", "sort.11", "ragged_all_to_all.20", "copy-start.1"]
    assert dict(got["device_ops"]) == pytest.approx(
        {"fusion": 12e-3, "sort.11": 3.2975e-3,
         "ragged_all_to_all.20": 1.35e-3, "copy-start.1": 0.5e-3})
    # one gap lies under the program's engine.stage span, the innermost
    # one over its middle; the rest under the benchmark's own unit span
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"bench.unit": 2.65e-3, "engine.stage": 1.2e-3,
         xplane.SHORT_GAPS: 2.5e-6})
    assert (reduced["busy_s"] + sum(reduced["idle_gap_s"].values())
            == pytest.approx(reduced["window_s"]))


def test_a_trace_without_devices_or_units_reduces_to_nothing():
    assert xplane.reduce_trace({"planes": {"/host:CPU": {"python3": [
        ["bench.unit", 0.0, 5.0]]}}}) is None
    assert xplane.reduce_trace({"planes": {"/device:TPU:0": {"XLA Ops": [
        ["fusion", 0.0, 5.0]]}}}) is None


@pytest.mark.parametrize("name, want", [
    ("fused_step_device_s", {"value": 8.32375e-3}),
    ("exchange_collective_s", {"value": 0.675e-3}),
    # 100 KB a chip on 2 chips: HBM 2 x 1e5 / 819e9 = 2.442e-7 s, ICI
    # 1e5 / 2 / 200e9 = 2.5e-7 s, so ICI bounds; 2.5e-7 / 8.32375e-3
    ("fused_step_roofline", {"value": 100 * 2.5e-7 / 8.32375e-3,
                             "bound_by": "ici", "least_s": 2.5e-7}),
])
def test_device_readers_on_the_fixture(reduced, name, want):
    with open(manifest.layer_metric_path(name)) as f:
        spec = json.load(f)
    info = {"rows_per_chip": 1000, "row_bytes": 100, "chips": 2}
    reading = readers.Reading([], reduced, info, "TPU v5 lite")
    got = readers.read_metric(spec, reading)
    assert got.pop("unit") == spec["unit"]
    assert got == pytest.approx(want)
    assert readers.read_metric(
        spec, readers.Reading([], None, info, "TPU v5 lite")) is None


@pytest.mark.parametrize("name, want", [
    ("engine_map_stage_s", 0.25), ("engine_result_stage_s", 3.0),
    ("exchange_round_s", 0.5)])
def test_span_readers_pick_their_spans(name, want):
    def job(scale):
        us = 1e6 * scale
        return {"events": [
            {"name": "engine.stage", "ph": "X", "ts": 0, "dur": 0.25 * us,
             "args": {"stage": 1, "shuffle": 7, "tasks": 16}},
            {"name": "engine.stage", "ph": "X", "ts": 0, "dur": 3.0 * us,
             "args": {"stage": 2, "tasks": 16}},
            {"name": "exchange.round", "ph": "X", "ts": 0, "dur": 0.2 * us,
             "args": {"round": 0}},
            {"name": "exchange.round", "ph": "X", "ts": 0, "dur": 0.3 * us,
             "args": {"round": 1}},
            {"name": "exchange.select", "ph": "i", "ts": 0,
             "args": {"plane": "device"}}]}
    with open(manifest.layer_metric_path(name)) as f:
        spec = json.load(f)
    units = [job(0.5), job(1.0), job(4.0)]   # the median job is the middle
    got = readers.read_metric(spec, readers.Reading(units, None, {}, "cpu"))
    assert got == {"value": pytest.approx(want), "unit": "s"}
    assert readers.read_metric(
        spec, readers.Reading([{"events": []}], None, {}, "cpu")) is None


def test_peaks_and_step_bytes():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bytes_per_s"] == 200e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    one = peaks.fused_step_bytes(10_737_418, 100, 1)
    assert one == {"hbm_bytes": 2 * 1_073_741_800, "ici_bytes": 0}
    assert peaks.least_seconds(one, v5e) == (
        pytest.approx(2 * 1_073_741_800 / 819e9), "hbm")
    four = peaks.fused_step_bytes(5_368_709, 100, 4)
    assert four["ici_bytes"] == 536_870_900 * 3 / 4
    assert peaks.least_seconds(four, v5e) == (
        pytest.approx(536_870_900 * 0.75 / 200e9), "ici")


# -- the plain references ---------------------------------------------------

def test_terasort_reference_finds_what_is_wrong():
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 2**32, size=(4096, 5), dtype=np.uint32)
    want = reference.numpy_terasort(rows, 4)
    # the spelled-out pipeline IS the global stable sort, for any split
    stable = rows[np.argsort(rows[:, 0], kind="stable")]
    assert np.array_equal(want, stable)
    assert np.array_equal(reference.numpy_terasort(rows, 1), stable)
    edges = [(i << 32) // 4 for i in range(1, 4)]
    cuts = np.searchsorted(want[:, 0].astype(np.uint64),
                           np.array(edges, dtype=np.uint64))
    good = np.split(want, cuts)
    assert reference.terasort_problems(good, rows) == []
    swapped = [g.copy() for g in good]
    swapped[1][[0, 1], 1:] = swapped[1][[1, 0], 1:]   # payloads change keys
    assert any("stable sort" in p
               for p in reference.terasort_problems(swapped, rows))
    detached = [g.copy() for g in good]
    detached[2][0, 3] ^= 1
    assert any("multiset" in p
               for p in reference.terasort_problems(detached, rows))
    assert any("rows delivered" in p
               for p in reference.terasort_problems(
                   [good[0][:-1], *good[1:]], rows))
    unsorted = [g.copy() for g in good]
    unsorted[0][[0, -1]] = unsorted[0][[-1, 0]]
    assert any("not sorted" in p
               for p in reference.terasort_problems(unsorted, rows))
    moved = [good[0][:-1], np.concatenate([good[0][-1:], good[1]]), *good[2:]]
    assert reference.terasort_problems(moved, rows) == [
        "device 1 holds keys outside its range"]


def test_spi_reference_is_the_global_sort():
    parts = [(np.array([5, 1], np.uint64), np.array([[50], [10]], np.uint8)),
             (np.array([3], np.uint64), np.array([[30]], np.uint8))]
    keys, payload = reference.sorted_records(parts)
    assert keys.tolist() == [1, 3, 5] and payload.ravel().tolist() == [
        10, 30, 50]
