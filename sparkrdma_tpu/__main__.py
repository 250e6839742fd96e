"""CLI: ``python -m sparkrdma_tpu {info | config | selftest | demo}``.

The reference's operational entry point is one Spark config line
(README.md:69-71); a standalone framework needs its own front door for
quick inspection and smoke-testing a deployment.
"""

import json
import sys


def _info() -> int:
    import sparkrdma_tpu
    from sparkrdma_tpu.runtime import native

    print(f"sparkrdma_tpu {sparkrdma_tpu.__version__}")
    print(f"native runtime: {'built' if native.available() else 'pure-Python fallback'}")
    # a backend that fails to initialize is an error, not "unavailable"
    import jax
    devs = jax.devices()
    print(f"devices: {len(devs)} x {devs[0].device_kind} "
          f"({devs[0].platform})")
    return 0


def _config() -> int:
    from sparkrdma_tpu.config import TpuShuffleConf, _KEYS

    defaults = TpuShuffleConf().to_dict()
    for k in _KEYS:
        print(f"{k.name:40s} {str(defaults[k.name]):>12s}  {k.doc}")
    return 0


def _selftest() -> int:
    """In-process smoke test: 2-executor shuffle cycle + pool + staging."""
    import tempfile

    import numpy as np

    from sparkrdma_tpu.config import TpuShuffleConf
    from sparkrdma_tpu.shuffle.manager import PartitionerSpec, TpuShuffleManager

    conf = TpuShuffleConf()
    driver = TpuShuffleManager(conf, is_driver=True)
    execs = [TpuShuffleManager(conf, driver_addr=driver.driver_addr,
                               executor_id=str(i),
                               spill_dir=tempfile.mkdtemp())
             for i in range(2)]
    try:
        for e in execs:
            e.executor.wait_for_members(2)
        handle = driver.register_shuffle(1, 2, 4, PartitionerSpec("hash"),
                                         row_payload_bytes=8)
        rng = np.random.default_rng(0)
        n = 0
        for m in range(2):
            w = execs[m].get_writer(handle, m)
            keys = rng.integers(0, 10_000, 5000).astype(np.uint64)
            w.write_batch(keys, rng.integers(0, 255, (5000, 8)).astype(np.uint8))
            w.close()
            n += len(keys)
        k, _ = execs[0].get_reader(handle, 0, 4).read_all()
        k2, _ = execs[1].get_reader(handle, 0, 4).read_all()
        assert len(k) == n and len(k2) == n, "row count mismatch"
        print(json.dumps({"selftest": "ok", "rows": n,
                          "native_server": execs[0].block_server is not None}))
        return 0
    finally:
        for e in execs:
            e.stop()
        driver.stop()


def _demo() -> int:
    """On-mesh TeraSort demo on jax's default backend — a toy-size
    correctness demo, not a measurement path, so it may run on the CPU;
    the record names the platform it ran on."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from sparkrdma_tpu.models.terasort import (
        TeraSortConfig, generate_rows, run_terasort, verify_terasort)
    from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("shuffle",))
    cfg = TeraSortConfig(rows_per_device=100_000, payload_words=4,
                         out_factor=1 if len(devs) == 1 else 2)
    rows = generate_rows(cfg, len(devs))
    out, counts, dt = run_terasort(mesh, cfg, rows=rows)
    verify_terasort(out, counts, rows, len(devs))
    print(json.dumps({"demo": "terasort", "rows": len(rows),
                      "devices": len(devs), "platform": devs[0].platform,
                      "device_kind": devs[0].device_kind,
                      "step_s": round(dt, 4), "verified": True}))
    return 0


def _engine_demo(use_mesh: bool = False) -> int:
    """Multi-stage TPC-DS star job through the DAG engine (drop-in SPI).
    With ``use_mesh``, reduce-side reads ride the ICI collective data
    plane (engine mesh mode) instead of the TCP fetcher — verified by the
    exchange dispatch counter."""
    import tempfile

    from sparkrdma_tpu.config import TpuShuffleConf
    from sparkrdma_tpu.engine import DAGEngine
    from sparkrdma_tpu.models.tpcds import (
        TpcdsConfig, build_tpcds_job, generate_star, numpy_tpcds)
    from sparkrdma_tpu.shuffle.spark_compat import SparkCompatShuffleManager

    conf = TpuShuffleConf()
    driver = SparkCompatShuffleManager(conf, isDriver=True)
    execs = [SparkCompatShuffleManager(
        conf, driverAddr=driver.driverAddr, executorId=str(i),
        spill_dir=tempfile.mkdtemp()) for i in range(2)]
    mesh = None
    exchanges = 0
    if use_mesh:
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from sparkrdma_tpu.parallel import exchange as exchange_mod
        from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        mesh = Mesh(np.array(jax.devices()), ("shuffle",))
        exchanges = exchange_mod.DATA_PLANE["exchanges"]
    try:
        for e in execs:
            e.native.executor.wait_for_members(2)
        cfg = TpcdsConfig(fact_rows_per_device=4096, dim1_size=256,
                          dim2_size=256, num_groups=64)
        job, finish = build_tpcds_job(cfg, num_maps=3, num_partitions=4,
                                      seed=1)
        engine = DAGEngine(driver, execs, mesh=mesh)
        counts, sums = finish(engine.run(job))
        fact, d1, d2 = generate_star(cfg, 1, seed=1)
        want_c, want_s = numpy_tpcds(fact, d1, d2, cfg.num_groups)
        ok = (counts == want_c).all() and (sums == want_s).all()
        record = {"demo": "tpcds-engine", "joined_rows": int(counts.sum()),
                  "groups": cfg.num_groups, "oracle_exact": bool(ok)}
        if use_mesh:
            from sparkrdma_tpu.parallel import exchange as exchange_mod

            record["data_plane"] = "mesh"
            record["platform"] = mesh.devices.flat[0].platform
            record["device_kind"] = mesh.devices.flat[0].device_kind
            record["collective_exchanges"] = (
                exchange_mod.DATA_PLANE["exchanges"] - exchanges)
            ok = ok and record["collective_exchanges"] > 0
        print(json.dumps(record))
        return 0 if ok else 1
    finally:
        for e in execs:
            e.stop()
        driver.stop()


def _shuffle_service() -> int:
    """Standalone shuffle service: adopt a dead executor's spill
    directory and serve its COMMITTED map outputs so reducers finish
    without recomputation — the role Spark's external shuffle service
    plays (which the reference notably does not support: its MR
    registrations die with the executor JVM). Here committed spills are
    plain files + sidecar indexes, so any process can re-register them.

    Usage:
      python -m sparkrdma_tpu shuffle-service DRIVER_HOST:PORT SPILL_DIR \
          [SERVICE_ID]
    """
    import signal
    import threading

    from sparkrdma_tpu.config import TpuShuffleConf
    from sparkrdma_tpu.shuffle.manager import TpuShuffleManager

    if len(sys.argv) < 4 or ":" not in sys.argv[2] \
            or not sys.argv[2].rsplit(":", 1)[1].isdigit():
        print(_shuffle_service.__doc__)
        return 2
    host, port = sys.argv[2].rsplit(":", 1)
    spill_dir = sys.argv[3]
    service_id = sys.argv[4] if len(sys.argv) > 4 else "shuffle-svc"
    mgr = TpuShuffleManager(TpuShuffleConf(), driver_addr=(host, int(port)),
                            executor_id=service_id, spill_dir=spill_dir)
    recovered = mgr.recover_and_republish()
    n_maps = sum(len(v) for v in recovered.values())
    print(f"shuffle-service {service_id}: serving {n_maps} recovered map "
          f"outputs across {len(recovered)} shuffles from {spill_dir}",
          flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    mgr.stop()
    return 0


def _rdd_demo() -> int:
    """Word-count + global sort through the RDD API (the pyspark-shaped
    front half) over a 3-executor in-process cluster: textFile ->
    flatMap -> reduceByKey (map-side combine) -> sortByKey ->
    saveAsTextFile, every shuffle through the full SPI underneath."""
    import tempfile
    import os

    from sparkrdma_tpu.config import TpuShuffleConf
    from sparkrdma_tpu.engine import DAGEngine
    from sparkrdma_tpu.rdd import EngineContext
    from sparkrdma_tpu.shuffle.spark_compat import SparkCompatShuffleManager

    conf = TpuShuffleConf()
    driver = SparkCompatShuffleManager(conf, isDriver=True)
    execs = [SparkCompatShuffleManager(
        conf, driverAddr=driver.driverAddr, executorId=str(i),
        spill_dir=tempfile.mkdtemp()) for i in range(3)]
    try:
        for e in execs:
            e.native.executor.wait_for_members(3)
        workdir = tempfile.mkdtemp()
        src = os.path.join(workdir, "input.txt")
        vocab = ["shuffle", "exchange", "mesh", "ici", "spill", "stage"]
        with open(src, "w") as f:
            for i in range(5000):
                f.write(vocab[i * 7 % len(vocab)] + " "
                        + vocab[i * 3 % len(vocab)] + "\n")
        ctx = EngineContext(DAGEngine(driver, execs))
        out = os.path.join(workdir, "counts")
        (ctx.text_file(src, 6)
            .flat_map(str.split)
            .map(lambda w: (w, 1))
            .reduce_by_key(lambda a, b: a + b, 4)
            .sort_by_key(2)
            .map(lambda kv: f"{kv[0]}\t{kv[1]}")
            .save_as_text_file(out))
        lines = []
        for part in sorted(os.listdir(out)):
            if part.startswith("part-"):
                lines += open(os.path.join(out, part)).read().splitlines()
        total = sum(int(ln.split("\t")[1]) for ln in lines)
        print(json.dumps({"demo": "rdd-wordcount", "distinct_words":
                          len(lines), "total_words": total,
                          "sorted": lines == sorted(lines),
                          "verified": total == 10000
                          and len(lines) == len(vocab)}))
        return 0
    finally:
        for e in execs:
            e.stop()
        driver.stop()


def main() -> int:
    cmd = sys.argv[1] if len(sys.argv) > 1 else "info"
    handlers = {"info": _info, "config": _config,
                "selftest": _selftest, "demo": _demo,
                "engine-demo": _engine_demo,
                "engine-mesh-demo": lambda: _engine_demo(use_mesh=True),
                "rdd-demo": _rdd_demo,
                "shuffle-service": _shuffle_service}
    if cmd not in handlers:
        print(f"usage: python -m sparkrdma_tpu {{{' | '.join(handlers)}}}")
        return 2
    return handlers[cmd]()


if __name__ == "__main__":
    sys.exit(main())
