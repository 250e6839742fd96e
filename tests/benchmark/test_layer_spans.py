"""The per-layer metrics that read the result stage's spans and the fused
step's kernel scopes: each span metric picks its own spans out of a job's
events (hand-worked numbers), the scope reader's arithmetic matches a
hand-made fixture, and a rehearsal of the SPI cell reports every span
metric. Nothing here yields a device number."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, readers  # noqa: E402
from benchmark.readers import device_scope  # noqa: E402

MANIFEST = manifest.load_manifest()
SPAN_METRICS = {
    "engine_mesh_reduce_s": "engine.mesh_reduce",
    "exchange_stage_s": "exchange.stage",
    "exchange_collect_s": "exchange.collect",
    "exchange_merge_s": "exchange.merge",
    "exchange_unpack_s": "exchange.unpack",
    "exchange_split_s": "exchange.split",
}
SCOPE_METRICS = ("fused_row_gather_s", "fused_key_sort_s")


def _spec(name):
    with open(manifest.layer_metric_path(name)) as f:
        return json.load(f)


def test_manifest_with_the_new_metrics_is_well_formed():
    assert manifest.problems(MANIFEST) == []
    spi = {m["name"]: spec for m, spec in manifest.load_cell(
        MANIFEST, "spi_device_1chip").per_layer}
    for name, span in SPAN_METRICS.items():
        assert spi[name]["reader"] == {"source": "host_span", "match": span,
                                       "reduce": "sum_per_unit"}
    fused = {m["name"]: spec for m, spec in manifest.load_cell(
        MANIFEST, "fused_1chip").per_layer}
    for name in SCOPE_METRICS:
        assert fused[name]["reader"]["module"] == "device_scope"
    for m in MANIFEST["per_layer"]:
        if m["name"] in {*SPAN_METRICS, *SCOPE_METRICS}:
            assert m["source"] == ("program_span" if m["name"]
                                   in SPAN_METRICS else "device_trace")


def _job(scale):
    """One job's events: a mesh reduce of two rounds inside the result
    stage, every duration ``scale`` times the seconds written here."""
    def span(name, seconds, **args):
        return {"name": name, "ph": "X", "ts": 0, "dur": seconds * 1e6 * scale,
                "args": args}
    return {"events": [
        span("engine.stage", 0.25, stage=1, shuffle=7, tasks=16),
        span("engine.stage", 3.0, stage=2, tasks=16),
        span("engine.task", 2.9, stage=2, task=0, remote=False),
        span("engine.mesh_reduce", 2.5, shuffle=7),
        span("exchange.stage", 0.3, round=0, rows=100, bytes=10400),
        span("exchange.round", 0.05, round=0, rows=100, bytes=13312),
        span("exchange.stage", 0.2, round=1, rows=60, bytes=6240),
        span("exchange.round", 0.05, round=1, rows=60, bytes=13312),
        span("exchange.collect", 0.4, round=0, rows=100, bytes=25700),
        span("exchange.stage", 0.0005, round=2, rows=0, bytes=0),
        span("exchange.collect", 0.35, round=1, rows=60, bytes=25700),
        span("exchange.merge", 0.6, runs=2, rows=160),
        span("exchange.unpack", 0.125, rows=160),
        span("exchange.split", 0.375, partitions=16, rows=160),
        {"name": "exchange.select", "ph": "i", "ts": 0,
         "args": {"plane": "device"}},
        {"name": "exchange.overlap", "ph": "i", "ts": 0,
         "args": {"dispatched": 1, "collecting": 0}}]}


@pytest.mark.parametrize("name, want", [
    ("engine_mesh_reduce_s", 2.5),
    ("exchange_stage_s", 0.3 + 0.2 + 0.0005),
    ("exchange_collect_s", 0.4 + 0.35),
    ("exchange_merge_s", 0.6),
    ("exchange_unpack_s", 0.125),
    ("exchange_split_s", 0.375)])
def test_span_metric_reads_its_own_spans(name, want):
    units = [_job(0.5), _job(1.0), _job(4.0)]   # the median job: scale 1
    got = readers.read_metric(
        _spec(name), readers.Reading(units, None, {}, "cpu"))
    assert got == {"value": pytest.approx(want), "unit": "s"}
    assert readers.read_metric(
        _spec(name), readers.Reading([{"events": []}], None, {},
                                     "cpu")) is None


def test_the_children_tile_the_mesh_reduce_in_the_synthetic_job():
    """The acceptance arithmetic, on the job above: the five new
    exchange_*_s and exchange_round_s against engine_mesh_reduce_s."""
    reading = readers.Reading([_job(1.0)], None, {}, "cpu")
    value = {n: readers.read_metric(_spec(n), reading)["value"]
             for n in [*SPAN_METRICS, "exchange_round_s"]}
    children = sum(v for n, v in value.items() if n.startswith("exchange_"))
    assert children == pytest.approx(2.4505)
    assert children / value["engine_mesh_reduce_s"] == pytest.approx(
        0.9802)


# -- the scope reader's arithmetic, on its fixture ----------------------------

@pytest.fixture(scope="module")
def scoped():
    with open(os.path.join(manifest.BENCH_DIR, "fixtures",
                           "scoped_ops_small.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, want", [
    # chip 0: 6 + 6 ms of gather and 0.25 ms of write-back in the window
    # (the 0.5 ms gather before the first unit is outside it); chip 1:
    # 6 + 5 ms, the second cut at the window's end from 6: 23.25 ms over
    # 2 chips and 2 units
    ("fused_row_gather_s", 23.25e-3 / 4),
    # chip 0: 2 + 1.5 ms; chip 1: 2 + 1 ms, and the partition phase's
    # 0.5 ms key sort counts too: 7 ms over 2 chips and 2 units
    ("fused_key_sort_s", 7e-3 / 4)])
def test_scope_seconds_on_the_fixture(scoped, name, want):
    pattern = _spec(name)["reader"]["match"]
    assert device_scope.scope_seconds(scoped, pattern, chips=2) == (
        pytest.approx(want))


def test_scope_seconds_finds_nothing_where_no_scope_matches(scoped):
    assert device_scope.scope_seconds(scoped, "(^|/)no_such(/|$)",
                                      chips=2) is None
    # a part of the path matches whole or not at all
    assert device_scope.scope_seconds(scoped, "(^|/)key(/|$)",
                                      chips=2) is None


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_reader_without_a_device_trace_reads_nothing(name):
    assert readers.read_metric(
        _spec(name), readers.Reading([], None, {}, "cpu")) is None


def test_scope_reader_refuses_a_profile_of_another_run(scoped, monkeypatch):
    monkeypatch.setattr(device_scope, "newest_profile", lambda: "x.pb")
    monkeypatch.setattr(device_scope, "load_scoped_ops", lambda path: scoped)
    spec = _spec("fused_row_gather_s")
    trace = {"units": 2, "chips": 2}
    got = readers.read_metric(
        spec, readers.Reading([], trace, {}, "TPU v5 lite"))
    assert got == {"value": pytest.approx(23.25e-3 / 4), "unit": "s"}
    with pytest.raises(RuntimeError, match="bench.unit"):
        readers.read_metric(spec, readers.Reading(
            [], dict(trace, units=3), {}, "TPU v5 lite"))


# -- the cell, rehearsed ------------------------------------------------------

def test_rehearsal_reports_every_span_metric():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "spi_device_1chip", "--seed", str(2**31 + 977), "--seconds", "0.5",
         "--trace", "1", "--rehearsal"], cwd=REPO, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = json.loads(proc.stdout.strip().splitlines()[-1])["metrics"]
    for name in SPAN_METRICS:
        assert metrics["rehearsal." + name]["value"] > 0, name
    reduce_s = metrics["rehearsal.engine_mesh_reduce_s"]["value"]
    assert reduce_s < metrics["rehearsal.engine_result_stage_s"]["value"]
    children = sum(metrics[f"rehearsal.exchange_{n}_s"]["value"] for n in (
        "stage", "round", "collect", "merge", "unpack", "split"))
    assert children <= reduce_s   # they lie inside it, one after another
