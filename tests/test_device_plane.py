"""The unified exchange dataplane: fused device-plane parity against the
host dataplane across every exchange transport, cost-model selection,
the overflow -> host degrade, round auto-sizing/overlap traces, and the
two exchange satellites (topology-warning dedupe, chunked-quota pow2
bucketing). Seed swept by ``scripts/run_device_bench.sh`` via
``DEVICE_SEED``."""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from engine_helpers import make_cluster, u32_payload as _u32_payload
from sparkrdma_tpu.engine import DAGEngine, MapStage, ResultStage
from sparkrdma_tpu.parallel import exchange as exchange_mod
from sparkrdma_tpu.parallel.device_plane import (
    StageProfile,
    auto_rows_per_round,
    run_fused_exchange,
    run_fused_exchange_rounds,
    select_dataplane,
)
from sparkrdma_tpu.shuffle.manager import PartitionerSpec
from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency
from sparkrdma_tpu.utils.trace import ACCOUNTING_ARGS, Tracer

SEED = int(os.environ.get("DEVICE_SEED", "0"))
D = 8


def _own(args):
    """A span's args less the tracer's accounting: what the caller gave."""
    return {k: v for k, v in args.items() if k not in ACCOUNTING_ARGS}


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


def _canon(keys: np.ndarray, payload: np.ndarray) -> bytes:
    """Canonical partition bytes: rows sorted by (key, payload) so
    equal-key payload order (unspecified on both planes) can't fail an
    exact-bytes comparison."""
    rows = np.concatenate(
        [keys.view(np.uint8).reshape(len(keys), 8),
         np.ascontiguousarray(payload)], axis=1)
    return rows[np.lexsort(rows.T[::-1])].tobytes()


def _job(num_partitions, maps, rows, key_space, base_seed, skip_partition=None):
    """A MapStage writing deterministic tables + the canonical-bytes
    reduce; returns (stage, reduce_fn)."""

    def table(seed):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, key_space, size=rows).astype(np.uint64)
        if skip_partition is not None:
            keys = keys[keys % num_partitions != skip_partition]
        vals = rng.integers(0, 1000, size=len(keys)).astype(np.uint32)
        return keys, vals

    def map_fn(ctx, writer, task_id):
        keys, vals = table(base_seed + task_id)
        writer.write((keys, _u32_payload(vals)))

    def reduce_fn(ctx, task_id):
        keys, payload = ctx.read(0)._r.read_all()
        assert ((keys % num_partitions) == task_id).all()
        return _canon(keys, payload)

    stage = MapStage(maps, ShuffleDependency(
        num_partitions, PartitionerSpec("modulo"), row_payload_bytes=4),
        map_fn)
    return stage, reduce_fn


def _fetcher_spy(monkeypatch):
    from sparkrdma_tpu.shuffle import fetcher as fetcher_mod

    built = {"n": 0}
    orig = fetcher_mod.ShuffleFetcher.__init__

    def spy(self, *a, **kw):
        built["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(fetcher_mod.ShuffleFetcher, "__init__", spy)
    return built


# -- fused-step vs host-dataplane parity, all four transports ------------

@pytest.mark.parametrize("impl", ["native", "dense", "gather",
                                  "ring_interpret"])
@pytest.mark.parametrize("skip_partition", [None, 2])
def test_device_vs_host_dataplane_byte_parity(tmp_path, mesh, impl,
                                              skip_partition):
    """The same job through the fused device plane and the host
    dataplane must produce byte-identical partitions — including a
    stage with an entirely empty partition."""
    if impl == "native":
        resolved = exchange_mod.resolve_impl(mesh, "auto", "shuffle")
        if resolved != "native":
            pytest.skip("ragged-all-to-all opcode unavailable on this "
                        f"mesh (probe resolved {resolved!r})")
    P, maps, rows, key_space = 4, 5, 600, 4000
    outs = {}
    for plane in ("device", "host"):
        driver, execs = make_cluster(tmp_path / f"{impl}_{plane}")
        try:
            stage, reduce_fn = _job(P, maps, rows, key_space,
                                    1000 * SEED + 17,
                                    skip_partition=skip_partition)
            before = exchange_mod.DATA_PLANE["exchanges"]
            engine = DAGEngine(driver, execs, mesh=mesh, mesh_impl=impl,
                               dataplane=plane)
            outs[plane] = engine.run(
                ResultStage(P, reduce_fn, parents=[stage]))
            moved = exchange_mod.DATA_PLANE["exchanges"] - before
            if plane == "device":
                assert moved > 0, "device plane dispatched no collective"
            else:
                assert moved == 0, "host plane dispatched a collective"
        finally:
            for ex in execs:
                ex.stop()
            driver.stop()
    assert outs["device"] == outs["host"]


def test_empty_shuffle_on_device_plane(cluster, mesh):
    """Maps that write nothing: the fused plane serves every partition
    empty without tripping staging or the exchange."""
    driver, execs = cluster
    P = 4

    def map_fn(ctx, writer, task_id):
        writer.write((np.zeros(0, np.uint64), np.zeros((0, 4), np.uint8)))

    def reduce_fn(ctx, task_id):
        keys, payload = ctx.read(0)._r.read_all()
        return len(keys) + len(payload)

    stage = MapStage(3, ShuffleDependency(
        P, PartitionerSpec("modulo"), row_payload_bytes=4), map_fn)
    engine = DAGEngine(driver, execs, mesh=mesh, dataplane="device")
    assert engine.run(ResultStage(P, reduce_fn, parents=[stage])) == [0] * P


# -- overflow -> host degrade --------------------------------------------

def test_overflow_degrades_stage_to_host_dataplane(cluster, mesh,
                                                   monkeypatch, caplog):
    """Every key lands in ONE partition: the receive overflows the
    out_factor headroom, and the stage — not the job — degrades to the
    host dataplane with byte-identical results."""
    import logging

    caplog.set_level(logging.WARNING, logger="sparkrdma_tpu.engine")
    driver, execs = cluster
    P, maps, rows = 4, 4, 500

    def map_fn(ctx, writer, task_id):
        rng = np.random.default_rng(300 + SEED + task_id)
        keys = (rng.integers(0, 1000, rows).astype(np.uint64) * P)  # all p0
        writer.write((keys, _u32_payload(
            rng.integers(0, 1000, rows).astype(np.uint32))))

    degraded = {}

    def reduce_fn(ctx, task_id):
        keys, payload = ctx.read(0)._r.read_all()
        # observe the degrade while the stage is alive (teardown pops
        # the memo when run() returns)
        degraded.update(holder["engine"]._mesh_degraded)
        return _canon(keys, payload)

    built = _fetcher_spy(monkeypatch)
    stage = MapStage(maps, ShuffleDependency(
        P, PartitionerSpec("modulo"), row_payload_bytes=4), map_fn)
    holder = {"engine": None}
    engine = holder["engine"] = DAGEngine(driver, execs, mesh=mesh,
                                          dataplane="device")
    out = engine.run(ResultStage(P, reduce_fn, parents=[stage]))

    assert list(degraded.values()) == ["receive overflow"]
    assert not engine._mesh_degraded, "teardown leaked the degrade memo"
    assert built["n"] > 0, "degrade never reached the host dataplane"
    assert any("host dataplane" in r.message for r in caplog.records)
    # truth: all rows in partition 0, others empty
    all_k, all_v = [], []
    for m in range(maps):
        rng = np.random.default_rng(300 + SEED + m)
        all_k.append(rng.integers(0, 1000, rows).astype(np.uint64) * P)
        all_v.append(rng.integers(0, 1000, rows).astype(np.uint32))
    want0 = _canon(np.concatenate(all_k),
                   _u32_payload(np.concatenate(all_v)))
    empty = _canon(np.zeros(0, np.uint64), np.zeros((0, 4), np.uint8))
    assert out == [want0, empty, empty, empty]


# -- cost model ----------------------------------------------------------

def test_cost_model_selection(mesh):
    profile = StageProfile(est_bytes=1 << 20, row_bytes=16, out_factor=2)
    # overrides win
    assert select_dataplane(mesh, "shuffle", profile,
                            override="host").plane == "host"
    forced = select_dataplane(mesh, "shuffle", profile, override="device",
                              hbm_budget=1)  # budget below one row
    assert forced.plane == "device" and forced.rows_per_round == 1
    # auto: fits one round -> one-shot device
    fits = select_dataplane(mesh, "shuffle", profile,
                            hbm_budget=64 << 20)
    assert fits.plane == "device" and fits.rows_per_round == 0
    assert fits.impl in ("native", "dense", "gather")
    # auto: bigger than a round -> chunked device with auto-sized rounds
    big = StageProfile(est_bytes=1 << 30, row_bytes=16, out_factor=2)
    chunked = select_dataplane(mesh, "shuffle", big, hbm_budget=1 << 20)
    assert chunked.plane == "device"
    assert chunked.rows_per_round == auto_rows_per_round(16, 1 << 20, 2)
    assert 0 < chunked.rows_per_round < (1 << 30) // 16 // D
    # auto: budget below one row -> host
    tiny = select_dataplane(mesh, "shuffle", profile, hbm_budget=1)
    assert tiny.plane == "host"
    # no mesh / non-resident stages can't ride the device plane
    assert select_dataplane(None, "shuffle", profile).plane == "host"
    off_mesh = StageProfile(est_bytes=1, row_bytes=16, resident=False)
    assert select_dataplane(mesh, "shuffle", off_mesh).plane == "host"
    # forcing the device plane where it declared itself unable is loud
    with pytest.raises(ValueError, match="no mesh configured"):
        select_dataplane(None, "shuffle", profile, override="device")
    with pytest.raises(ValueError, match="not resident"):
        select_dataplane(mesh, "shuffle", off_mesh, override="device")
    # the host plane carries what the device plane cannot, and says so
    assert select_dataplane(None, "shuffle", profile).reason \
        == "host dataplane"


def test_auto_rows_per_round_footprint():
    # budget / (row_bytes * (2 + 2*out_factor)): 1 MiB at 16B rows,
    # out_factor 2 -> 1 MiB / 96
    assert auto_rows_per_round(16, 1 << 20, 2) == (1 << 20) // 96
    assert auto_rows_per_round(16, 0, 2) == 0
    assert auto_rows_per_round(16, 95, 2) == 0


def test_engine_auto_budget_streams_rounds(tmp_path, mesh):
    """A tiny device_hbm_budget auto-sizes multi-round streaming:
    several exchanges dispatch, exact results."""
    driver, execs = make_cluster(tmp_path)
    try:
        P, maps, rows, key_space = 4, 4, 400, 1000
        stage, reduce_fn = _job(P, maps, rows, key_space, 7000 + SEED)
        before = exchange_mod.DATA_PLANE["exchanges"]
        row_bytes = 4 * 3  # 2 key words + 1 payload word
        budget = row_bytes * (2 + 2 * 4) * 128  # 128 rows/round (of=4)
        engine = DAGEngine(driver, execs, mesh=mesh, dataplane="device",
                           device_hbm_budget=budget)
        out_dev = engine.run(ResultStage(P, reduce_fn, parents=[stage]))
        assert exchange_mod.DATA_PLANE["exchanges"] - before > 1, \
            "budget did not stream multiple rounds"

        stage2, reduce2 = _job(P, maps, rows, key_space, 7000 + SEED)
        engine2 = DAGEngine(driver, execs, mesh=mesh, dataplane="host")
        assert engine2.run(ResultStage(P, reduce2,
                                       parents=[stage2])) == out_dev
    finally:
        for ex in execs:
            ex.stop()
        driver.stop()


def test_cost_model_rejects_unknown_override(mesh):
    """A typo'd device_plane escape hatch must fail loudly, not
    silently ride the cost model."""
    profile = StageProfile(est_bytes=1 << 20, row_bytes=16)
    with pytest.raises(ValueError, match="unknown dataplane override"):
        select_dataplane(mesh, "shuffle", profile, override="hsot")


@pytest.mark.parametrize("devices", [1, D])
def test_fused_u64_key_order(devices):
    """The packed-u64 (key_words=2) layout through the local sort, as
    the whole step on one device and behind the exchange on eight: the
    two key words order as one u64."""
    mesh = Mesh(np.array(jax.devices()[:devices]), ("shuffle",))
    rng = np.random.default_rng(SEED + 9)
    N = 3000
    # low 32 bits collide often so multi-word ordering actually matters
    keys = (rng.integers(0, 2**31, N, dtype=np.uint64) << 32) \
        | rng.integers(0, 4, N, dtype=np.uint64)
    rows = np.zeros((N, 3), np.uint32)
    rows[:, :2] = keys.view(np.uint32).reshape(N, 2)
    rows[:, 2] = rng.integers(0, 2**32, N, dtype=np.uint32)
    dest = (keys % devices).astype(np.int32)
    res, _ = run_fused_exchange(mesh, "shuffle", rows, dest, key_words=2,
                                impl="gather", out_factor=4)
    got = []
    for d, r in enumerate(res):
        k = r[:, :2].copy().view(np.uint64).reshape(-1)
        assert (k % devices == d).all()
        assert (k[:-1] <= k[1:]).all(), "not u64-sorted"
        got.append(k)
    np.testing.assert_array_equal(np.sort(np.concatenate(got)),
                                  np.sort(keys))


# -- overlap traces ------------------------------------------------------

def test_round_overlap_traces(mesh):
    """Double-buffered rounds: round k+1's collective dispatches before
    round k is collected — one exchange.round span per round and an
    exchange.overlap instant per overlapped pair prove it."""
    rng = np.random.default_rng(SEED)
    N = 4000
    keys = rng.integers(0, 2**63, N, dtype=np.uint64)
    rows = np.zeros((N, 3), np.uint32)
    rows[:, :2] = keys.view(np.uint32).reshape(N, 2)
    rows[:, 2] = rng.integers(0, 2**32, N, dtype=np.uint32)
    dest = (keys % D).astype(np.int32)

    def run(pipeline):
        tracer = Tracer()
        res, rounds = run_fused_exchange(
            mesh, "shuffle", rows, dest, key_words=2, impl="gather",
            out_factor=4, rows_per_round=128, tracer=tracer,
            pipeline_rounds=pipeline)
        spans = [e for e in tracer._events if e["name"] == "exchange.round"]
        overlaps = [e for e in tracer._events
                    if e["name"] == "exchange.overlap"]
        return res, rounds, spans, overlaps

    res_p, rounds, spans, overlaps = run(True)
    assert rounds == -(-N // (128 * D)) and rounds >= 3
    assert len(spans) == rounds
    assert len(overlaps) == rounds - 1, \
        "rounds did not overlap (no double buffering)"
    # every round says which forms its step's rows followed their order in
    # (what the step's trace chose: the 3-word rows rode the grouping's
    # sort, and on a CPU mesh the receive sort's rows followed jnp.take)
    assert {e["args"]["row_move"] for e in spans} == {"sort+take"}
    # sequential mode: same bytes, zero overlap instants
    res_s, _, spans_s, overlaps_s = run(False)
    assert len(spans_s) == rounds and not overlaps_s
    for a, b in zip(res_p, res_s):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("want_rounds", [4, 1])
def test_merge_span_counts_bytes_written(mesh, want_rounds):
    """``exchange.merge``'s ``bytes``: the merge of several rounds' runs
    writes every row exactly once (``rows * row_words * 4``, where the
    pairwise tournament wrote each once a level); one round's single run
    passes through and nothing is written."""
    rng = np.random.default_rng(SEED + 27)
    N, W = 128 * D * 4, 5
    per_round = N // want_rounds
    rows = rng.integers(0, 2**32, (N, W), dtype=np.uint32)
    dest = (rows[:, 0] % D).astype(np.int32)
    blocks = ((rows[i:i + per_round], dest[i:i + per_round])
              for i in range(0, N, per_round))
    tracer = Tracer()
    res, rounds = run_fused_exchange_rounds(
        mesh, "shuffle", blocks, W, per_round // D, key_words=2,
        impl="gather", out_factor=4, tracer=tracer)
    assert rounds == want_rounds
    (merge,) = [_own(e["args"]) for e in tracer._events
                if e["name"] == "exchange.merge"]
    assert merge == {"runs": rounds, "rows": N,
                     "bytes": N * W * 4 if rounds > 1 else 0}
    for d, r in enumerate(res):
        assert (r[:, 0] % D == d).all()
        k = r[:, :2].copy().view(np.uint64).reshape(-1)
        assert (k[:-1] <= k[1:]).all()
    assert sum(len(r) for r in res) == N


# -- satellite: topology-warning dedupe ----------------------------------

def test_topology_warning_dedupes_per_mesh_axis(mesh, caplog):
    import logging

    caplog.set_level(logging.WARNING,
                     logger="sparkrdma_tpu.parallel.exchange")
    exchange_mod._topology_warned.discard((mesh, "shuffle"))
    for _ in range(3):
        exchange_mod._warn_topology_once(mesh, "shuffle", "probe says no")
    hits = [r for r in caplog.records if "rejects ragged" in r.message]
    assert len(hits) == 1, "warning not deduped per (mesh, axis)"


# -- satellite: chunked-quota pow2 bucketing -----------------------------

def test_bucket_quota_values():
    from sparkrdma_tpu.parallel.exchange import bucket_quota

    assert [bucket_quota(q) for q in (1, 2, 3, 5, 8, 9, 127, 128)] == \
        [1, 2, 4, 8, 8, 16, 128, 128]


def test_chunked_exchange_quota_bucketing_parity(mesh):
    """Drifting quotas bucket to one compiled round_fn; results are
    unchanged for every quota in the bucket."""
    from sparkrdma_tpu.parallel.exchange import (
        chunked_exchange,
        make_chunked_exchange,
    )

    assert make_chunked_exchange(mesh, "shuffle", 5) is \
        make_chunked_exchange(mesh, "shuffle", 8)
    assert make_chunked_exchange(mesh, "shuffle", 9) is not \
        make_chunked_exchange(mesh, "shuffle", 8)

    rng = np.random.default_rng(SEED + 4)
    per_dev = 48
    rows = np.zeros((D * per_dev, 2), dtype=np.uint32)
    counts = np.zeros((D, D), dtype=np.int32)
    for d in range(D):
        dest = np.sort(rng.integers(0, D, size=per_dev))
        rows[d * per_dev:(d + 1) * per_dev, 0] = dest
        rows[d * per_dev:(d + 1) * per_dev, 1] = rng.integers(
            0, 2**31, per_dev, dtype=np.uint32)
        counts[d] = np.bincount(dest, minlength=D)
    base, _ = chunked_exchange(mesh, "shuffle", rows, counts, quota=16)
    for quota in (7, 8, 13):  # 7/8 share a bucket; 13 buckets to 16
        got, _ = chunked_exchange(mesh, "shuffle", rows, counts,
                                  quota=quota)
        for d in range(D):
            np.testing.assert_array_equal(got[d], base[d])


# -- bench acceptance + round-JSON provenance ----------------------------

def test_fused_exchange_microbench_acceptance(tmp_path):
    """The ISSUE's acceptance gate: fused vs host-staged same-process
    A/B >= 1.5x, byte-identical."""
    from sparkrdma_tpu.shuffle.device_bench import run_device_microbench
    from sparkrdma_tpu.utils.benchgate import gated_best_of

    res = gated_best_of(lambda: run_device_microbench(str(tmp_path)))
    assert res["identical"], "dataplanes reduced different bytes"
    assert res["speedup"] >= 1.5, res


def test_bench_round_json_provenance():
    """Every bench round must record host_load_avg (the BENCH_r05
    host-contention lesson) and, on dense rounds, dense_exchange_guard;
    the fused secondary rides _secondary_workloads."""
    import inspect

    import bench as bench_mod

    detail = bench_mod._round_provenance({})
    assert len(detail["host_load_avg"]) == 3
    assert "captured_at" in detail
    main_src = inspect.getsource(bench_mod.main)
    assert "_round_provenance" in main_src
    assert "_bench_dense_guard" in main_src
    sec_src = inspect.getsource(bench_mod._secondary_workloads)
    assert "_bench_fused_exchange" in sec_src


# -- the result stage's spans, on the Tracer's clock and the profiler's ---

D4 = 4
STAGE_SPANS = ("exchange.stage", "exchange.round", "exchange.collect")
ONCE_SPANS = ("exchange.merge", "exchange.unpack", "exchange.split")


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.array(jax.devices()[:D4]), ("shuffle",))


def _traced_job(cluster, mesh4, tracer, budget):
    """One device-plane shuffle (4 partitions, 4 maps x 400 rows) with
    ``tracer`` as the engine's; returns its X events and row count."""
    driver, execs = cluster
    P, maps, rows = 4, 4, 400
    stage, reduce_fn = _job(P, maps, rows, 1000, 8100 + SEED)
    engine = DAGEngine(driver, execs, mesh=mesh4, dataplane="device",
                       device_hbm_budget=budget)
    if tracer is not None:
        engine.tracer = tracer
    engine.run(ResultStage(P, reduce_fn, parents=[stage]))
    return maps * rows


# 12-byte rows, out_factor 2 on 4 devices with 4 partitions: 64 rows a
# device a round -> 1,600 rows in 7 rounds; the default budget: one shot
MULTI_ROUND_BUDGET = 12 * (2 + 2 * 2) * 64


@pytest.mark.parametrize("budget, multi_round", [
    (MULTI_ROUND_BUDGET, True), (64 << 20, False)])
def test_result_stage_spans_tile_the_mesh_reduce(cluster, mesh4, budget,
                                                 multi_round):
    tracer = Tracer()
    records = _traced_job(cluster, mesh4, tracer, budget)
    spans = [e for e in tracer._events if e["ph"] == "X"]
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    (reduce_span,) = by_name["engine.mesh_reduce"]
    assert set(reduce_span["args"]) == {"shuffle", *ACCOUNTING_ARGS}
    lo, hi = reduce_span["ts"], reduce_span["ts"] + reduce_span["dur"]
    for name in STAGE_SPANS + ONCE_SPANS:
        assert by_name.get(name), f"no {name} span"
        for e in by_name[name]:
            assert e["tid"] == reduce_span["tid"]
            assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi, name
    for name in ONCE_SPANS:
        assert len(by_name[name]) == 1, name
    rounds = len(by_name["exchange.round"])
    merge, unpack, split = (_own(by_name[n][0]["args"]) for n in ONCE_SPANS)
    # the merge wrote every 12-byte row once, or nothing: a single run
    # passes through
    assert merge == {"runs": rounds, "rows": records,
                     "bytes": records * 12 if multi_round else 0}
    assert unpack == {"rows": records}
    assert split == {"partitions": 4, "rows": records}
    assert len(by_name["exchange.collect"]) == rounds
    for name in ("exchange.round", "exchange.collect"):
        assert sum(e["args"]["rows"] for e in by_name[name]) == records
        assert sorted(e["args"]["round"] for e in by_name[name]) == list(
            range(rounds))
    for e in by_name["exchange.collect"]:
        # the whole padded receive buffer comes back, not the useful rows
        assert e["args"]["bytes"] > e["args"]["rows"] * 12
    staged = [_own(e["args"]) for e in by_name["exchange.stage"]]
    assert all(set(a) == {"round", "rows", "bytes"} for a in staged)
    if multi_round:
        assert rounds == -(-records // (64 * D4)) and rounds >= 3
        # one span a round, and the stream's last pull that finds its end
        assert [a["rows"] > 0 for a in staged] == [True] * rounds + [False]
        assert sum(a["rows"] for a in staged) == records
    else:
        assert rounds == 1 and merge["runs"] == 1
        # the real staging, then the driver's slicing of what it made
        assert staged[0]["rows"] == records


def test_noop_tracer_enters_no_annotation(cluster, mesh4, monkeypatch):
    from sparkrdma_tpu.utils import trace as trace_mod

    entered = []
    real = trace_mod._annotation
    monkeypatch.setattr(trace_mod, "_annotation",
                        lambda name: entered.append(name) or real(name))
    _traced_job(cluster, mesh4, None, MULTI_ROUND_BUDGET)
    assert entered == [] and trace_mod.NULL._events == []
    # the counter does count: the same job with a live tracer
    tracer = Tracer()
    _traced_job(cluster, mesh4, tracer, MULTI_ROUND_BUDGET)
    assert len(entered) == sum(e["ph"] == "X" for e in tracer._events) > 0


def test_live_tracer_spans_are_in_the_profile(cluster, mesh4, tmp_path):
    """Every span of a live tracer is a TraceAnnotation of the same name:
    a running profiler session holds as many host-plane events of each
    name as the Tracer recorded."""
    from collections import Counter

    from benchmark import xplane

    tracer = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        _traced_job(cluster, mesh4, tracer, MULTI_ROUND_BUDGET)
    recorded = Counter(e["name"] for e in tracer._events if e["ph"] == "X")
    assert {"engine.mesh_reduce", "engine.stage", "engine.task",
            *STAGE_SPANS, *ONCE_SPANS} <= set(recorded)
    host = xplane.load_xplane(xplane.find_xplane(str(tmp_path)))[
        "planes"][xplane.HOST_PLANE]
    profiled = Counter(name for events in host.values()
                       for name, _, _ in events if name in recorded)
    assert profiled == recorded


@pytest.mark.parametrize("partition", ["range", "dest"])
@pytest.mark.parametrize("devices", [1, 4])
def test_fused_step_hlo_carries_the_kernel_scopes(devices, partition):
    from sparkrdma_tpu.parallel.device_plane import make_fused_step

    mesh_n = Mesh(np.array(jax.devices()[:devices]), ("shuffle",))
    step = make_fused_step(mesh_n, "shuffle", 4, impl="gather",
                           partition=partition)
    args = [jax.ShapeDtypeStruct((devices * 256, 4), np.uint32)]
    if partition == "dest":
        args.append(jax.ShapeDtypeStruct((devices * 256,), np.int32))
    hlo = step.lower(*args).compile().as_text()
    assert "fused.receive_sort/row_gather" in hlo
    assert "fused.receive_sort/key_sort" in hlo
    multi = ("fused.exchange", "fused.partition")
    assert all((name in hlo) == (devices > 1) for name in multi)
    # the names are metadata: a cached executable of a build without them
    # must not be served for this program
    assert jax.config.jax_compilation_cache_include_metadata_in_key


@pytest.mark.parametrize("devices,partition,key_words", [
    (1, "range", 1), (4, "range", 1), (1, "dest", 2), (4, "dest", 2)])
def test_fused_step_packed_form_is_byte_identical(monkeypatch, devices,
                                                  partition, key_words):
    """``make_fused_step`` with the packed row permute forced (its kernels
    interpreted) returns what the ``take`` form returns, byte for byte:
    sorted rows with their padding rows, counts, overflow flags."""
    from sparkrdma_tpu.ops import row_permute
    from sparkrdma_tpu.parallel.device_plane import make_fused_step

    mesh_n = Mesh(np.array(jax.devices()[:devices]), ("shuffle",))
    rng = np.random.default_rng(SEED + 29)
    n = devices * 700
    rows = rng.integers(0, 2**32, (n, 25), dtype=np.uint32)
    rows[::7, 0] = rows[3, 0]           # duplicate keys: ties by position
    args = [rows]
    if partition == "dest":
        dest = rng.integers(0, devices, n).astype(np.int32)
        dest[::11] = -1                 # padding rows, not sent
        args.append(dest)

    def run():
        make_fused_step.cache_clear()   # the form is chosen while tracing
        # a ring transport: interpreted kernels need check_vma off
        step = make_fused_step(mesh_n, "shuffle", 25, impl="ring_interpret",
                               partition=partition, key_words=key_words)
        assert step.row_moves == []     # filled by the trace
        return [np.asarray(x) for x in step(*args)], step

    # one move on one device (the single sort); across devices one before
    # the exchange (the key sort's, or the grouping's) and the receive sort's
    moves = 1 if devices == 1 else 2
    try:
        want, step = run()
        assert step.row_moves == ["take"] * moves
        monkeypatch.setattr(row_permute, "row_move_form",
                            lambda n_rows, row_words, platform: "packed")
        got, step = run()
        assert step.row_moves == ["packed"] * moves
        lowered = step.lower(*args)
        assert step.row_moves == ["packed"] * moves     # the same trace
    finally:
        make_fused_step.cache_clear()
    hlo = lowered.as_text(debug_info=True)
    for part in ("pack", "permute", "unpack"):
        assert f"row_gather/{part}" in hlo
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
