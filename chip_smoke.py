"""Chip smoke: the shuffle's main path, once, on the TPU this host has.

    python chip_smoke.py [--seed N]

Runs BASELINE config #1 ("TeraSort 1 GB", 100-byte records) two ways, in
this one process, on every chip ``jax.devices()`` returns:

* **Job A — the fused step.** ``models.terasort.run_terasort`` (the
  device plane's ``make_fused_step`` in range mode) over 1 GiB of rows in
  one round, a warm and a timed step; the WHOLE output of the last step
  is compared row for row with ``numpy_terasort`` on the same input and
  passed through ``verify_terasort``.
* **Job B — the same sort through the SPI** (deployment shape 2 of
  ``docs/DEPLOY.md``): a driver and four executor roles with the default
  ``TpuShuffleConf()``, a ``DAGEngine`` over the mesh, 16 map tasks writing
  u64 key + 92-byte payload through ``getWriter``, a range partitioner,
  reduce tasks reading through ``ctx.read``. Run on the device plane (the
  cost model's choice: a chunked plan under the default 64 MiB
  ``device_hbm_budget``) and again on the host plane; both are compared
  record for record with a numpy sort of the same input, and with each
  other byte for byte.

* **Job C — PageRank** (BASELINE config #3 at the size of the benchmark's
  cell ``pagerank_1chip``: 16,777,216 edges and 468,750 vertices a chip,
  Zipf in-links): ``models.pagerank.PageRankJob`` over a resident
  ``powerlaw_graph``, a warm job and a timed one of three supersteps;
  the ranks of the last are held to ``benchmark/reference_pagerank.py``.

One process drives all the chips of the host: a chip belongs to one
process at a time, so this script starts no other process.

It exits 0 only if jax's backend is a TPU and every check passed. It
writes two lines to standard output. The first is the report, one JSON
object with the facts of both jobs that ends ``"claim": null}``; seconds
in it are set-up facts of this run (compile included where it says so),
never metrics. The LAST line is the verdict, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
with the device as jax reports it, and nothing else: the driver's check
reads that line and refuses any other key. Without a TPU it prints
neither line and exits non-zero. ``--rehearsal`` runs the same logic at
toy size on four virtual CPU devices for the sandbox; its report says
``"rehearsal": true`` and no line of it has an ``ok`` key.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

AXIS = "shuffle"
ROW_BYTES = 100          # the gensort record: 25 u32 words on the device
PAYLOAD_BYTES = 92       # Job B: u64 key + 92 bytes = the same 25-word row
FULL_BYTES = 1 << 30     # BASELINE config #1
# Job C: a chip's graph in the benchmark's cell pagerank_1chip
PAGERANK_EDGES = 16_777_216
PAGERANK_VERTICES = 468_750
PAGERANK_ITERATIONS = 3
PAGERANK_ZIPF_S = 0.9
NO_EXCHANGE = "none: one device, the step is a local sort"


class CompileLog:
    """Backend-compile seconds and persistent-cache hits/misses, from
    jax's own monitoring events (a cache hit's retrieval time counts as
    its compile time — which is what a warm run should show)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> tuple:
        with self._lock:
            return self.seconds, self.hits, self.misses

    def since(self, snap: tuple) -> dict:
        s, h, m = self.snapshot()
        return {"compile_s": round(s - snap[0], 2),
                "compile_cache_hits": h - snap[1],
                "compile_cache_misses": m - snap[2]}


def peak_hbm(devices) -> list:
    """Per device ``peak_bytes_in_use`` since process start (None where
    the backend keeps no such counter, as XLA:CPU does)."""
    stats = [d.memory_stats() for d in devices]
    return [s.get("peak_bytes_in_use") if s else None for s in stats]


# ---------------------------------------------------------------------------
# Job A: the fused step
# ---------------------------------------------------------------------------

def run_job_a(mesh, total_bytes: int, seed: int, compile_log: CompileLog):
    """1 GiB-class TeraSort round through ``run_terasort`` (a warm and a
    timed call of ``make_terasort_step``). Returns ``(record, failures)``.
    On a TPU mesh of more than one chip the transport must be ``native``
    and the compiled HLO must hold the opcode; a CPU mesh (rehearsal,
    tests) rides the gather oracle. A receive overflow raises out of
    ``run_terasort`` and fails the job at ``main``'s boundary."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.models.terasort import (
        TeraSortConfig,
        generate_rows,
        make_terasort_step,
        numpy_terasort,
        run_terasort,
        verify_terasort,
    )
    from sparkrdma_tpu.ops.row_permute import forms_label
    from sparkrdma_tpu.parallel import exchange as exchange_mod

    failures: list = []
    n = mesh.shape[AXIS]
    cfg = TeraSortConfig(rows_per_device=total_bytes // ROW_BYTES // n,
                         payload_words=24, out_factor=2)
    rows = generate_rows(cfg, n, seed=seed)
    impl = exchange_mod.resolve_impl(mesh, "auto", AXIS)
    require_native = n > 1 and mesh.devices.flat[0].platform == "tpu"
    if require_native and impl != "native":
        failures.append(f"job_a: resolve_impl(mesh) = {impl!r}, not 'native'")

    # the step run_terasort is about to call (the builder is memoized),
    # compiled ahead so its HLO can be read; the call itself then finds
    # the executable in the compile cache
    snap = compile_log.snapshot()
    step = make_terasort_step(mesh, AXIS, cfg, impl="auto")
    t0 = time.perf_counter()
    compiled = step.lower(jax.ShapeDtypeStruct(
        rows.shape, rows.dtype,
        sharding=NamedSharding(mesh, P(AXIS)))).compile()
    lower_compile_s = time.perf_counter() - t0
    ragged_ops = sum("ragged-all-to-all" in ln
                     for ln in compiled.as_text().splitlines())
    if require_native and not ragged_ops:
        failures.append("job_a: no ragged-all-to-all in the compiled HLO")

    before = exchange_mod.DATA_PLANE["exchanges"]
    t0 = time.perf_counter()
    out_np, counts_np, step_s = run_terasort(mesh, cfg, AXIS, impl="auto",
                                             rows=rows)
    run_s = time.perf_counter() - t0
    dispatched = exchange_mod.DATA_PLANE["exchanges"] - before
    compile_facts = compile_log.since(snap)
    if dispatched < 1:
        failures.append("job_a: DATA_PLANE['exchanges'] did not advance")

    # the whole output of a step that ran at this size, against the plain
    # reference on the full input
    t0 = time.perf_counter()
    verified = False
    try:
        if counts_np.shape != (n, n):
            raise AssertionError(f"counts shape {counts_np.shape}, "
                                 f"expected {(n, n)}")
        verify_terasort(out_np, counts_np, rows, n)
        per_dev = out_np.reshape(n, -1, out_np.shape[-1])
        got = np.concatenate([per_dev[d][:int(counts_np[d].sum())]
                              for d in range(n)])
        if not np.array_equal(got, numpy_terasort(rows, n)):
            raise AssertionError("output differs from numpy_terasort")
        verified = True
    except AssertionError as e:
        failures.append(f"job_a: {e}")
    verify_s = time.perf_counter() - t0

    record = {
        "rows": int(len(rows)), "bytes": int(rows.nbytes),
        "rows_per_device": cfg.rows_per_device, "out_factor": cfg.out_factor,
        "exchange_impl": impl if n > 1 else NO_EXCHANGE,
        "hlo_ragged_all_to_all_ops": ragged_ops,
        # the form the rows followed their order in, as the step's trace
        # chose it (ops/row_permute.py); job B's is its rounds' spans',
        # job C's its job span's
        "row_move": forms_label(step.row_moves),
        "plan": {"plane": "device", "rows_per_round": cfg.rows_per_device,
                 "rounds": 1},
        # DATA_PLANE counts dispatched fused steps; on one device a step
        # holds no collective
        "fused_steps_dispatched": dispatched,
        "collective_exchanges": dispatched if n > 1 else 0,
        "verified": verified,
        "setup_facts": dict(compile_facts,
                            lower_compile_s=round(lower_compile_s, 2),
                            run_terasort_wall_s=round(run_s, 2),
                            step_wall_s=round(step_s, 4),
                            verify_s=round(verify_s, 2)),
        "peak_hbm_bytes": peak_hbm(mesh.devices.flat),
    }
    return record, failures


# ---------------------------------------------------------------------------
# Job B: the same sort through the SPI
# ---------------------------------------------------------------------------

def map_input(seed: int, task: int, rows_per_map: int):
    """Map task ``task``'s records, made from the seed alone so the
    reference can regenerate them: 40 random high bits spread the keys
    over the whole u64 range, the low 24 bits are the global row index —
    keys are unique, so "record for record" has exactly one right answer."""
    rng = np.random.default_rng([seed, task])
    high = rng.integers(0, 1 << 40, rows_per_map, dtype=np.uint64)
    index = np.arange(task * rows_per_map, (task + 1) * rows_per_map,
                      dtype=np.uint64)
    payload = rng.integers(0, 256, (rows_per_map, PAYLOAD_BYTES),
                           dtype=np.uint8)
    return (high << np.uint64(24)) | index, payload


def build_sort_job(maps: int, partitions: int, rows_per_map: int,
                   seed: int):
    """The TeraSort DAG: ``maps`` map tasks write their records through
    ``getWriter``, a range partitioner splits the u64 key space evenly,
    reduce task ``t`` reads partition ``t`` through ``ctx.read`` and
    returns it key-sorted (plus whether it arrived sorted, and the bytes
    its reader fetched remotely)."""
    from sparkrdma_tpu.engine import MapStage, ResultStage
    from sparkrdma_tpu.shuffle.manager import PartitionerSpec
    from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency

    if maps * rows_per_map > 1 << 24:
        raise ValueError("map_input indexes rows in 24 bits")
    splitters = tuple((i << 64) // partitions for i in range(1, partitions))

    def map_fn(ctx, writer, task_id):
        writer.write(map_input(seed, task_id, rows_per_map))

    def reduce_fn(ctx, task_id):
        reader = ctx.read(0)
        keys, payload = reader.readAll()
        arrived_sorted = bool((keys[1:] > keys[:-1]).all())
        order = np.argsort(keys, kind="stable")
        return (keys[order], payload[order], arrived_sorted,
                int(reader.metrics.remote_bytes))

    stage = MapStage(maps, ShuffleDependency(
        partitions, PartitionerSpec("range", splitters),
        row_payload_bytes=PAYLOAD_BYTES), map_fn)
    return ResultStage(partitions, reduce_fn, parents=[stage])


@contextlib.contextmanager
def count_tcp_fetchers():
    """Counts every TCP fetcher built while the block runs (the spy of
    tests/test_engine_mesh.py): a device-plane job must build none."""
    from sparkrdma_tpu.shuffle import fetcher as fetcher_mod

    built = {"n": 0}
    orig = fetcher_mod.ShuffleFetcher.__init__

    def spy(self, *a, **kw):
        built["n"] += 1
        return orig(self, *a, **kw)

    fetcher_mod.ShuffleFetcher.__init__ = spy
    try:
        yield built
    finally:
        fetcher_mod.ShuffleFetcher.__init__ = orig


def run_plane(driver, execs, mesh, job, dataplane: str, trace_dir: str):
    """One engine run of ``job`` on the named dataplane (``"auto"`` asks
    the cost model). Returns ``(results, facts)``; ``facts`` holds what
    the engine's own trace and counters say about how the bytes moved."""
    from sparkrdma_tpu.engine import DAGEngine
    from sparkrdma_tpu.parallel import exchange as exchange_mod
    from sparkrdma_tpu.utils.trace import Tracer

    engine = DAGEngine(driver, execs, mesh=mesh, dataplane=dataplane)
    # the default conf has no trace_file, so the managers carry the no-op
    # tracer; the engine's own spans and instants are what is read here
    engine.tracer = Tracer()
    before = exchange_mod.DATA_PLANE["exchanges"]
    with count_tcp_fetchers() as built:
        t0 = time.perf_counter()
        results = engine.run(job)
        wall_s = time.perf_counter() - t0
    path = os.path.join(trace_dir, f"trace_{dataplane}.json")
    engine.tracer.dump(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    selects = [e["args"] for e in events if e["name"] == "exchange.select"]
    facts = {
        "selects": selects,
        "degrades": sum(e["name"] == "exchange.degrade" for e in events),
        "rounds": sum(e["name"] == "exchange.round" for e in events),
        "row_move": sorted({e["args"]["row_move"] for e in events
                            if e["name"] == "exchange.round"}),
        "dispatches": exchange_mod.DATA_PLANE["exchanges"] - before,
        "tcp_fetchers_built": built["n"],
        "remote_bytes": sum(r[3] for r in results),
        "arrived_sorted": all(r[2] for r in results),
        "job_wall_s": round(wall_s, 2),
    }
    return results, facts


def run_job_b(mesh, total_bytes: int, seed: int, compile_log: CompileLog,
              maps: int = 16, executors: int = 4):
    """The sort through driver + executor roles and the DAG engine, on
    the device plane and on the host plane. Returns ``(record, failures)``."""
    from sparkrdma_tpu.config import TpuShuffleConf
    from sparkrdma_tpu.parallel import topology as topology_mod
    from sparkrdma_tpu.shuffle.spark_compat import SparkCompatShuffleManager

    failures: list = []
    n = mesh.shape[AXIS]
    partitions = max(16, 4 * n)
    rows_per_map = total_bytes // ROW_BYTES // maps
    conf = TpuShuffleConf()
    topo = topology_mod.detect_topology(mesh, AXIS, conf)
    if not topo.is_flat:
        failures.append(f"job_b: topology not flat ({topo.num_slices} "
                        "slices): a plan was ranked by the guessed link "
                        "coefficients")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        driver = SparkCompatShuffleManager(conf, isDriver=True)
        execs = []
        try:
            execs = [SparkCompatShuffleManager(
                conf, driverAddr=driver.driverAddr, executorId=str(i),
                spill_dir=os.path.join(tmp, f"e{i}"))
                for i in range(executors)]
            for ex in execs:
                ex.native.executor.wait_for_members(executors)
            snap = compile_log.snapshot()
            dev_out, dev = run_plane(
                driver, execs, mesh,
                build_sort_job(maps, partitions, rows_per_map, seed),
                "auto", tmp)
            compile_facts = compile_log.since(snap)
            hbm = peak_hbm(mesh.devices.flat)
            host_out, host = run_plane(
                driver, execs, mesh,
                build_sort_job(maps, partitions, rows_per_map, seed),
                "host", tmp)
        finally:
            for ex in execs:
                ex.stop()
            driver.stop()

    select = dev["selects"][0] if dev["selects"] else {}
    if [s.get("plane") for s in dev["selects"]] != ["device"]:
        failures.append(f"job_b: exchange.select instants {dev['selects']}, "
                        "expected exactly one with plane 'device'")
    if dev["degrades"]:
        failures.append(f"job_b: {dev['degrades']} exchange.degrade instants")
    if dev["tcp_fetchers_built"] or dev["remote_bytes"]:
        failures.append(
            f"job_b: device-plane run built {dev['tcp_fetchers_built']} TCP "
            f"fetchers and fetched {dev['remote_bytes']} remote bytes")
    if dev["dispatches"] < 1:
        failures.append("job_b: DATA_PLANE['exchanges'] did not advance")
    if not dev["arrived_sorted"]:
        failures.append("job_b: a partition left the device plane unsorted")
    if [s.get("plane") for s in host["selects"]] != ["host"] \
            or not host["tcp_fetchers_built"]:
        failures.append("job_b: the host-plane run did not ride the fetcher "
                        f"path ({host['selects']}, "
                        f"{host['tcp_fetchers_built']} fetchers)")

    # record for record against a numpy sort of the same input; range
    # partitions in order ARE the global sort
    t0 = time.perf_counter()
    parts = [map_input(seed, m, rows_per_map) for m in range(maps)]
    keys = np.concatenate([k for k, _ in parts])
    payload = np.concatenate([p for _, p in parts])
    del parts
    order = np.argsort(keys, kind="stable")
    want_keys, want_payload = keys[order], payload[order]
    got_keys = np.concatenate([r[0] for r in dev_out])
    got_payload = np.concatenate([r[1] for r in dev_out])
    verified = (np.array_equal(got_keys, want_keys)
                and np.array_equal(got_payload, want_payload))
    if not verified:
        failures.append("job_b: device-plane result differs from the numpy "
                        "sort of the same input")
    identical = len(dev_out) == len(host_out) and all(
        np.array_equal(d[0], h[0]) and np.array_equal(d[1], h[1])
        for d, h in zip(dev_out, host_out))
    if not identical:
        failures.append("job_b: device- and host-plane results differ")
    verify_s = time.perf_counter() - t0

    record = {
        "rows": maps * rows_per_map,
        "bytes": maps * rows_per_map * ROW_BYTES,
        "maps": maps, "partitions": partitions, "executors": executors,
        "exchange_impl": select.get("impl") if n > 1 else NO_EXCHANGE,
        "plan": {"plane": select.get("plane"),
                 "rows_per_round": select.get("rows_per_round"),
                 "rounds": dev["rounds"], "reason": select.get("reason")},
        "row_move": dev["row_move"],
        # DATA_PLANE counts dispatched fused rounds; on one device a
        # round holds no collective
        "fused_rounds_dispatched": dev["dispatches"],
        "collective_exchanges": dev["dispatches"] if n > 1 else 0,
        "degrade_instants": dev["degrades"],
        "tcp_fetchers_built": dev["tcp_fetchers_built"],
        "arrived_sorted": dev["arrived_sorted"],
        "verified": verified,
        "identical_to_host_plane": identical,
        "host_plane": {"tcp_fetchers_built": host["tcp_fetchers_built"],
                       "remote_bytes": host["remote_bytes"]},
        "topology_flat": topo.is_flat,
        "setup_facts": dict(compile_facts,
                            device_job_wall_s=dev["job_wall_s"],
                            host_job_wall_s=host["job_wall_s"],
                            verify_s=round(verify_s, 2)),
        "peak_hbm_bytes": hbm,
    }
    return record, failures


# ---------------------------------------------------------------------------
# Job C: PageRank over a resident power-law graph
# ---------------------------------------------------------------------------

def run_job_c(mesh, edges_per_chip: int, seed: int, compile_log: CompileLog):
    """One PageRank job (after a warm one) of ``PAGERANK_ITERATIONS``
    supersteps over ``edges_per_chip`` edges a chip at the cell's degree,
    its ranks against the float64 reference. Returns ``(record,
    failures)``."""
    import jax

    from benchmark import reference_pagerank
    from sparkrdma_tpu.models.pagerank import (
        PageRankConfig,
        PageRankJob,
        place_graph,
        powerlaw_graph,
    )
    from sparkrdma_tpu.parallel import exchange as exchange_mod
    from sparkrdma_tpu.utils.trace import Tracer

    failures: list = []
    n = mesh.shape[AXIS]
    cfg = PageRankConfig(
        num_vertices=n * (edges_per_chip * PAGERANK_VERTICES
                          // PAGERANK_EDGES),
        edges_per_device=edges_per_chip, out_factor=2)
    impl = exchange_mod.resolve_impl(mesh, "auto", AXIS)
    if (n > 1 and mesh.devices.flat[0].platform == "tpu"
            and impl != "native"):
        failures.append(f"job_c: resolve_impl(mesh) = {impl!r}, not 'native'")
    edges, _, out_deg = powerlaw_graph(cfg, n, seed, PAGERANK_ZIPF_S)
    snap = compile_log.snapshot()
    graph = place_graph(mesh, AXIS, edges, out_deg)
    job = PageRankJob(mesh, AXIS, cfg, PAGERANK_ITERATIONS)
    t0 = time.perf_counter()
    job(graph)
    warm_job_s = time.perf_counter() - t0
    compile_facts = compile_log.since(snap)
    job.tracer = Tracer()
    t0 = time.perf_counter()
    ranks = job(graph)
    job_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        job.tracer.dump(os.path.join(tmp, "job_c.json"))
        with open(os.path.join(tmp, "job_c.json")) as f:
            events = json.load(f)["traceEvents"]
    job_args = next(e["args"] for e in events if e["name"] == "pagerank.job")
    received = job_args["received"]
    counters = {e["name"]: e["args"]["value"] for e in events
                if e.get("ph") == "C"}
    if received != [graph.num_edges] * PAGERANK_ITERATIONS:
        failures.append(f"job_c: contributions received {received}, valid "
                        f"edges in {graph.num_edges}")
    t0 = time.perf_counter()
    problems, readings = reference_pagerank.pagerank_report(
        np.asarray(ranks), edges, cfg.num_vertices, cfg.damping,
        PAGERANK_ITERATIONS)
    failures += [f"job_c: {p}" for p in problems]
    record = {
        "edges": graph.num_edges, "vertices": cfg.num_vertices,
        "iterations": PAGERANK_ITERATIONS, "record_bytes": 8,
        "exchange_impl": impl,
        "row_move": job_args["row_move"],
        "contributions_received": received,
        "recv_fill": counters.get("pagerank.recv_fill"),
        "max_in_degree": counters.get("pagerank.max_in_degree"),
        "ranks_on_device": isinstance(ranks, jax.Array),
        "verified": not problems,
        "against_reference": readings,
        "setup_facts": dict(compile_facts,
                            warm_job_s=round(warm_job_s, 2),
                            job_s=round(job_s, 2),
                            verify_s=round(time.perf_counter() - t0, 2)),
        "peak_hbm_bytes": peak_hbm(mesh.devices.flat),
    }
    return record, failures


# ---------------------------------------------------------------------------

def verdict(failures: list, devs) -> dict:
    """The last line of standard output: these keys and no others."""
    return {"ok": not failures,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy size on 4 virtual CPU devices; never 'ok'")
    args = ap.parse_args(argv)

    if args.rehearsal:
        from __graft_entry__ import _pin_virtual_cpu

        _pin_virtual_cpu(4)
    import jax
    from jax.sharding import Mesh

    from sparkrdma_tpu.runtime import native
    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    if not args.rehearsal and devs[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU: jax's default backend is "
              f"{devs[0].platform!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}); nothing ran",
              file=sys.stderr)
        return 1

    compile_log = CompileLog()
    compile_log.install()
    mesh = Mesh(np.array(devs), (AXIS,))
    n = len(devs)
    bytes_a = bytes_b = FULL_BYTES
    edges_c = PAGERANK_EDGES
    if args.rehearsal:
        bytes_a, bytes_b, edges_c = 4 << 20, 64 << 20, 1 << 16
    failures: list = []
    if not native.available():
        failures.append("native runtime not loaded (make -C csrc)")

    jobs = {}
    for name, fn, size in (("job_a", run_job_a, bytes_a),
                           ("job_b", run_job_b, bytes_b),
                           ("job_c", run_job_c, edges_c)):
        t0 = time.perf_counter()
        try:
            jobs[name], job_failures = fn(mesh, size, args.seed, compile_log)
            failures += job_failures
        except Exception as e:  # the boundary: report, then fail the run
            traceback.print_exc()
            jobs[name] = None
            failures.append(f"{name} raised {type(e).__name__}: {e}")
        print(f"chip_smoke.py: {name} done in "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    last = verdict(failures, devs)
    report = {"rehearsal": True} if args.rehearsal else {"ok": last["ok"]}
    report.update({
        "device": last["device"],
        "seed": args.seed,
        "exchange": (NO_EXCHANGE if n == 1 else
                     f"{(jobs['job_a'] or {}).get('exchange_impl')} "
                     f"over {n} devices"),
        "job_a": jobs.get("job_a"),
        "job_b": jobs.get("job_b"),
        "job_c": jobs.get("job_c"),
        "failures": failures,
        "reduced": [],
        "native_runtime_loaded": native.available(),
        "compile_cache": {
            "dir": cache_dir,
            "placed_by_env": bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR"))},
        "note": "seconds are set-up facts of this run, not metrics",
        "claim": None,
    })
    print(json.dumps(report))
    if not args.rehearsal:
        print(json.dumps(last), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
