"""``exchange.stage`` opened (``shuffle/mesh_service.py``): whichever plan
carries a device-plane job (bounded rounds, one shot, hierarchical), its
staging records ``exchange.stage_read`` / ``stage_pack`` / ``stage_route``
spans, once a committed spill, on the mesh reduce's thread inside an
``exchange.stage`` span, with ``rows`` that sum to the job's records;
bounded rounds also ``exchange.stage_cut``, once a round."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from engine_helpers import (
    make_cluster,
    make_table,
    payload_u32,
    u32_payload,
)
from sparkrdma_tpu.engine import DAGEngine, MapStage, ResultStage
from sparkrdma_tpu.shuffle.manager import PartitionerSpec
from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency
from sparkrdma_tpu.utils.trace import ACCOUNTING_ARGS, Tracer

D, P, MAPS, ROWS = 8, 4, 6, 700
CHILDREN = ("exchange.stage_read", "exchange.stage_pack",
            "exchange.stage_route")
# 3-word rows at out_factor 4: 256 rows a device a round, of 525
ROUNDS_BUDGET = 12 * (2 + 2 * 4) * 256


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:D]), ("shuffle",))


def _job():
    def map_fn(ctx, writer, task_id):
        keys, vals = make_table(300 + task_id, ROWS, 5000)
        writer.write((keys, u32_payload(vals)))

    def reduce_fn(ctx, task_id):
        keys, payload = ctx.read(0).readAll()
        return len(keys), int(payload_u32(payload).astype(np.int64).sum())

    stage = MapStage(MAPS, ShuffleDependency(
        P, PartitionerSpec("modulo"), row_payload_bytes=4), map_fn)
    return ResultStage(P, reduce_fn, parents=[stage])


@pytest.mark.parametrize("plane, conf_kw, engine_kw", [
    ("device", {}, dict(dataplane="device",
                        device_hbm_budget=ROUNDS_BUDGET)),
    ("device", {}, dict(dataplane="device")),
    ("hierarchical", dict(slice_topology="2"), dict(mesh_impl="gather")),
], ids=["rounds", "one_shot", "hierarchical"])
def test_stage_children_lie_inside_the_stage_spans(tmp_path, mesh, plane,
                                                   conf_kw, engine_kw):
    driver, execs = make_cluster(tmp_path, **conf_kw)
    try:
        engine = DAGEngine(driver, execs, mesh=mesh, **engine_kw)
        engine.tracer = Tracer()
        out = engine.run(_job())
    finally:
        for ex in execs:
            ex.stop()
        driver.stop()
    records = MAPS * ROWS
    assert sum(n for n, _ in out) == records
    events = engine.tracer._events
    assert [e["args"]["plane"] for e in events
            if e["name"] == "exchange.select"] == [plane]
    spans = [e for e in events if e["ph"] == "X"]
    (reduce_span,) = [e for e in spans if e["name"] == "engine.mesh_reduce"]
    stages = [e for e in spans if e["name"] == "exchange.stage"]
    for name in CHILDREN:
        mine = [e for e in spans if e["name"] == name]
        # one a committed spill, and the read's last pull that finds the
        # end; the one-shot plan packs and routes what it read at once
        reads = MAPS + 1 if name == "exchange.stage_read" else MAPS
        assert len(mine) in (reads, 1), name
        assert sum(e["args"]["rows"] for e in mine) == records, name
        for e in mine:
            assert e["tid"] == reduce_span["tid"]
            assert set(ACCOUNTING_ARGS) <= set(e["args"])
            assert any(s["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= s["ts"] + s["dur"]
                       for s in stages), name
    cuts = [e for e in spans if e["name"] == "exchange.stage_cut"]
    if "device_hbm_budget" in engine_kw:
        # one a round: the stream's last pull cuts nothing
        assert len(cuts) == len(stages) - 1 >= 3
        assert sum(e["args"]["rows"] for e in cuts) == records
        assert sum(e["args"]["bytes"] for e in cuts) == records * (12 + 4)
        assert all(any(s["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= s["ts"] + s["dur"]
                       for s in stages) for e in cuts)
    else:
        assert cuts == []   # staged whole: nothing is cut on the way
    by_rows = {name: [e["args"] for e in spans if e["name"] == name]
               for name in CHILDREN}
    # 12-byte device rows out of 8-byte keys and 4-byte payloads
    assert sum(a["bytes"] for a in by_rows["exchange.stage_read"]) == (
        records * 12)
    assert sum(a["bytes"] for a in by_rows["exchange.stage_pack"]) == (
        records * 12)
    # a child's CPU is inside its parent's: the stage spans' covers theirs
    for key in ("cpu_user_s", "cpu_sys_s", "minflt"):
        assert sum(e["args"][key] for e in spans
                   if e["name"] in (*CHILDREN, "exchange.stage_cut")) <= sum(
            s["args"][key] for s in stages), key
