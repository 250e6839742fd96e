"""The per-layer metrics that read a span's accounting args (``span_cpu``:
CPU seconds of the thread, of the process) and the children of
``exchange.stage``, and ``als_4chip``'s two half-step scopes: the reader's
arithmetic on hand-made events, the manifest with the new entries, and a
toy job of the SPI driver that reports every one of them. Nothing here
yields a device number."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import manifest, readers  # noqa: E402
from benchmark.readers import device_scope, span_cpu  # noqa: E402

MANIFEST = manifest.load_manifest()
CHILDREN = ("stage", "round", "collect", "merge", "unpack", "split")
CPU_METRICS = ["engine_map_stage_cpu_s", "engine_result_stage_cpu_s",
               "engine_mesh_reduce_cpu_s", "engine_mesh_reduce_sys_s",
               *(f"exchange_{c}_cpu_s" for c in CHILDREN)]
STAGE_METRICS = ["exchange_stage_read_s", "exchange_stage_pack_s",
                 "exchange_stage_route_s", "exchange_stage_cut_s"]
ALS_STEP_METRICS = ["als_item_step_s", "als_user_step_s"]
TRACED = {"units": 1}   # stands for a traced window: the reader wants one


def _spec(name):
    with open(manifest.layer_metric_path(name)) as f:
        return json.load(f)


# -- the manifest --------------------------------------------------------------

def test_the_manifest_with_the_new_entries_is_well_formed():
    assert manifest.problems(MANIFEST) == []
    names = [m["name"] for m in MANIFEST["per_layer"]]
    new = [*CPU_METRICS, *STAGE_METRICS, *ALS_STEP_METRICS]
    assert names[-len(new):] == new   # appended, in the issue's order
    spi = [m["name"] for m, _ in manifest.load_cell(
        MANIFEST, "spi_device_1chip").per_layer]
    assert spi[-14:] == [*CPU_METRICS, *STAGE_METRICS]
    als = [m["name"] for m, _ in manifest.load_cell(
        MANIFEST, "als_4chip").per_layer]
    assert als[-2:] == ALS_STEP_METRICS


@pytest.mark.parametrize("name, source, moves, cell", [
    *((n, "program_counter", "host_cpu_s_per_gb", "spi_device_1chip")
      for n in CPU_METRICS),
    *((n, "program_span", "job_makespan_s", "spi_device_1chip")
      for n in STAGE_METRICS),
    *((n, "device_trace", "job_makespan_s", "als_4chip")
      for n in ALS_STEP_METRICS)])
def test_a_new_metrics_file_agrees_with_its_entry(name, source, moves, cell):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    spec = _spec(name)
    assert entry["source"] == source and entry["workloads"] == [cell]
    assert entry["better"] == "lower" and entry["moves"] == moves
    for key in ("name", "unit", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["unit"] == ("CPU-s" if name in CPU_METRICS else "s")
    reader = spec["reader"]
    if name in CPU_METRICS:
        assert (reader["source"], reader["module"]) == ("module", "span_cpu")
    elif name in STAGE_METRICS:
        assert reader == {"source": "host_span", "reduce": "sum_per_unit",
                          "match": "exchange." + name[len("exchange_"):-2]}
    else:
        assert reader["module"] == "device_scope"


# -- the reader's arithmetic, on hand-made events ------------------------------

def _span(name, user, sys_, proc, **args):
    return {"name": name, "ph": "X", "ts": 0, "dur": 1e6,
            "args": dict(args, cpu_user_s=user, cpu_sys_s=sys_,
                         proc_cpu_s=proc, minflt=0, majflt=0, nvcsw=0,
                         nivcsw=0)}


def _job(scale):
    """One job's events, every CPU second ``scale`` times what is written
    here (binary fractions: the sums are exact)."""
    def span(name, user, sys_, proc, **args):
        return _span(name, user * scale, sys_ * scale, proc * scale, **args)
    return {"events": [
        span("engine.stage", 0.0625, 0.0, 0.75, stage=1, shuffle=7),
        span("engine.stage", 0.125, 0.0, 3.5, stage=2),
        span("engine.mesh_reduce", 1.5, 0.5, 3.0, shuffle=7),
        span("exchange.stage", 0.25, 0.0625, 0.5, round=0),
        span("exchange.stage", 0.125, 0.0625, 0.25, round=1),
        span("exchange.merge", 0.25, 0.25, 0.5, runs=2),
        {"name": "exchange.merge", "ph": "i", "ts": 0, "args": {}},
        {"name": "exchange.select", "ph": "i", "ts": 0,
         "args": {"plane": "device"}}]}


@pytest.mark.parametrize("name, want", [
    ("engine_map_stage_cpu_s", 0.75),          # proc_cpu_s, has `shuffle`
    ("engine_result_stage_cpu_s", 3.5),        # proc_cpu_s, lacks it
    ("engine_mesh_reduce_cpu_s", 1.5 + 0.5),   # user + kernel
    ("engine_mesh_reduce_sys_s", 0.5),
    ("exchange_stage_cpu_s", 0.25 + 0.0625 + 0.125 + 0.0625),
    ("exchange_merge_cpu_s", 0.5)])
def test_span_cpu_sums_its_args_over_a_units_spans(name, want):
    units = [_job(0.5), _job(4.0), _job(1.0)]   # the median job: scale 1
    got = readers.read_metric(
        _spec(name), readers.Reading(units, TRACED, {}, "cpu"))
    assert got == {"value": want, "unit": "CPU-s"}


def test_span_cpu_reads_nothing_where_there_is_nothing_to_read():
    spec = _spec("exchange_merge_cpu_s")
    # the parent's program: the span is there, its accounting is not
    bare = {"events": [{"name": "exchange.merge", "ph": "X", "ts": 0,
                        "dur": 5e5, "args": {"runs": 2, "rows": 9}}]}
    assert span_cpu.read(readers.Reading([bare], TRACED, {}, "cpu"),
                         spec) is None
    assert span_cpu.read(readers.Reading([{"events": []}], TRACED, {},
                                         "cpu"), spec) is None
    # a span of another name, and a unit that has none beside one that has
    other = {"events": [_span("exchange.split", 9.0, 9.0, 9.0)]}
    assert span_cpu.read(readers.Reading([other, _job(1.0)], TRACED, {},
                                         "cpu"), spec) == 0.5
    # a run without a device trace (a rehearsal): as every module reader
    assert span_cpu.read(readers.Reading([_job(1.0)], None, {}, "cpu"),
                         spec) is None


# -- als_4chip by half-step ----------------------------------------------------

def test_the_half_step_scopes_tell_the_two_programs_apart():
    """Ops named as the compiled half-steps name them (a described v5e:
    ``jit(step)/shard_map/als.item_step/closed_call/als.gather/...``): a
    half-step's metric takes its own program's ops whole, the unscoped
    copy between two scopes included, and the inner scopes still match."""
    def path(side, rest):
        return f"jit(step)/shard_map/als.{side}_step/{rest}"
    ms = 1e6   # the fixture's clock is nanoseconds
    scoped = {"units": [[0.0, 100 * ms]], "chips": {"0": [
        [path("item", "als.exchange/jit(_take)/gather"), 0 * ms, 2 * ms],
        [path("item", "closed_call/als.gather/jit(_take)/gather"),
         2 * ms, 8 * ms],
        [path("item", "copy"), 10 * ms, 1 * ms],
        [path("user", "closed_call/als.gather/jit(_take)/gather"),
         20 * ms, 4 * ms],
        [path("user", "als.solve/div"), 24 * ms, 1 * ms],
        ["jit(reset)/normalize", 30 * ms, 16 * ms]]}}
    seconds = {n: device_scope.scope_seconds(
        scoped, _spec(n)["reader"]["match"], chips=1)
        for n in [*ALS_STEP_METRICS, "als_gather_s", "als_exchange_s",
                  "als_solve_s"]}
    assert seconds == pytest.approx({
        "als_item_step_s": 11e-3, "als_user_step_s": 5e-3,
        "als_gather_s": 12e-3, "als_exchange_s": 2e-3, "als_solve_s": 1e-3})
    # the parent's program has no such scope: nothing, and no error
    with open(os.path.join(manifest.BENCH_DIR, "fixtures",
                           "scoped_ops_als.json")) as f:
        parent = json.load(f)
    for name in ALS_STEP_METRICS:
        assert device_scope.scope_seconds(
            parent, _spec(name)["reader"]["match"], chips=1) is None


# -- the cell's own driver, at toy size ----------------------------------------

def test_a_toy_job_of_the_spi_driver_reports_every_new_metric(tmp_path):
    """One job through ``benchmark/drivers/spi.py`` at the traffic file's
    rehearsal size, in process on one of conftest's CPU devices: every
    new metric of the cell reads a value, and what lies inside a span
    reads no more than the span. One job, so each metric IS that job's
    sum and the comparisons are exact (a thread's clocks and counters
    never run backwards): no medians over jobs, no thresholds."""
    import jax

    from benchmark.drivers import spi

    cell = manifest.load_cell(MANIFEST, "spi_device_1chip")
    sizes = dict(cell.traffic, **cell.traffic["rehearsal"])
    work = spi.Workload(cell.config, sizes, jax.devices()[:1], 2**31 + 38,
                        str(tmp_path))
    try:
        unit = work.run_unit()
        assert work.unit_problems(unit) == []
    finally:
        work.close()
    reading = readers.Reading([unit], TRACED, work.info, "cpu")
    value = {m["name"]: readers.read_metric(spec, reading)["value"]
             for m, spec in cell.per_layer
             if m["name"] in {*CPU_METRICS, *STAGE_METRICS}
             or spec["reader"]["source"] == "host_span"}
    assert set(value) >= {*CPU_METRICS, *STAGE_METRICS}
    assert all(v >= 0 for v in value.values())
    reduce_cpu = value["engine_mesh_reduce_cpu_s"]
    assert reduce_cpu > 0 and value["engine_result_stage_cpu_s"] > 0
    assert value["engine_mesh_reduce_sys_s"] <= reduce_cpu
    # one thread, one span after another inside the mesh reduce
    assert sum(value[f"exchange_{c}_cpu_s"] for c in CHILDREN) <= reduce_cpu
    for child in CHILDREN:
        assert value[f"exchange_{child}_cpu_s"] <= reduce_cpu
    # the process's clock covers the thread's
    assert reduce_cpu <= value["engine_result_stage_cpu_s"]
    # the four children lie inside the exchange.stage spans
    assert 0 < sum(value[n] for n in STAGE_METRICS) <= (
        value["exchange_stage_s"])
    staged = {name: sum(e["args"]["rows"] for e in unit["events"]
                        if e["name"] == "exchange.stage_" + name)
              for name in ("read", "pack", "route", "cut")}
    assert staged == dict.fromkeys(staged, work.records)
