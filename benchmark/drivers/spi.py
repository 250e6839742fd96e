"""Kind ``spi``: the same sort through the Spark-shaped SPI.

Deployment shape 2 of ``docs/DEPLOY.md`` in one process: a driver and
executor roles with the default ``TpuShuffleConf()``, one ``DAGEngine``
over the mesh. A unit is one ``engine.run(job)``: map tasks write u64 key
+ payload through ``getWriter``, a range partitioner splits the key
space evenly, reduce tasks read through ``ctx.read`` and return their
partition. This is ``chip_smoke.py``'s ``build_sort_job`` / ``run_plane``
copied, with one change: the map inputs are made once in set-up from the
seed, and ``map_fn`` only writes them.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import reference

AXIS = "shuffle"


def map_input(seed: int, task: int, rows_per_map: int, payload_bytes: int):
    """Map task ``task``'s records: 40 random high bits spread the keys
    over the whole u64 range, the low 24 bits are the global row index,
    so keys are unique and the sort has exactly one right answer."""
    rng = np.random.default_rng([seed, task])
    high = rng.integers(0, 1 << 40, rows_per_map, dtype=np.uint64)
    index = np.arange(task * rows_per_map, (task + 1) * rows_per_map,
                      dtype=np.uint64)
    payload = rng.integers(0, 256, (rows_per_map, payload_bytes),
                           dtype=np.uint8)
    return (high << np.uint64(24)) | index, payload


class Workload:
    def __init__(self, config: dict, sizes: dict, devices: list, seed: int,
                 scratch: str):
        from jax.sharding import Mesh

        from sparkrdma_tpu.config import TpuShuffleConf
        from sparkrdma_tpu.engine import DAGEngine
        from sparkrdma_tpu.runtime import native
        from sparkrdma_tpu.shuffle import fetcher as fetcher_mod
        from sparkrdma_tpu.shuffle.spark_compat import (
            SparkCompatShuffleManager,
        )

        if not native.available():
            raise RuntimeError("native runtime not loaded (make -C csrc): "
                               "the cell would time the pure-Python twin")
        p = config["params"]
        self.p = p
        self.maps, self.partitions = p["maps"], p["partitions"]
        self.rows_per_map = sizes["rows_per_map"]
        if self.maps * self.rows_per_map > 1 << 24:
            raise ValueError("map_input indexes rows in 24 bits")
        self.records = self.maps * self.rows_per_map
        row_bytes = 8 + p["payload_bytes"]
        self.unit_bytes = self.records * row_bytes
        self.info = {"rows_per_chip": self.records // len(devices),
                     "row_bytes": row_bytes, "chips": len(devices)}
        self.parts = [map_input(seed, m, self.rows_per_map,
                                p["payload_bytes"])
                      for m in range(self.maps)]

        # every TCP fetcher built from here on is counted (the spy of
        # tests/test_engine_mesh.py): a device-plane job builds none
        self._fetcher_cls = fetcher_mod.ShuffleFetcher
        self._fetcher_init = self._fetcher_cls.__init__
        self.fetchers_built = 0

        def spy(fetcher, *a, **kw):
            self.fetchers_built += 1
            return self._fetcher_init(fetcher, *a, **kw)

        self._fetcher_cls.__init__ = spy

        self.tmp = tempfile.mkdtemp(prefix="spi_", dir=scratch)
        self.trace_path = os.path.join(self.tmp, "unit_trace.json")
        conf = TpuShuffleConf()   # the default is what users run
        self.driver = SparkCompatShuffleManager(conf, isDriver=True)
        self.execs = []
        try:
            self.execs = [SparkCompatShuffleManager(
                conf, driverAddr=self.driver.driverAddr, executorId=str(i),
                spill_dir=os.path.join(self.tmp, f"e{i}"))
                for i in range(p["executors"])]
            for ex in self.execs:
                ex.native.executor.wait_for_members(p["executors"])
            self.engine = DAGEngine(self.driver, self.execs,
                                    mesh=Mesh(np.array(devices), (AXIS,)),
                                    dataplane=p["dataplane"])
        except BaseException:
            self.close()
            raise
        self.last = None

    def _job(self):
        from sparkrdma_tpu.engine import MapStage, ResultStage
        from sparkrdma_tpu.shuffle.manager import PartitionerSpec
        from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency

        parts = self.parts
        splitters = tuple((i << 64) // self.partitions
                          for i in range(1, self.partitions))

        def map_fn(ctx, writer, task_id):
            writer.write(parts[task_id])

        def reduce_fn(ctx, task_id):
            reader = ctx.read(0)
            keys, payload = reader.readAll()
            arrived_sorted = bool((keys[1:] > keys[:-1]).all())
            order = np.argsort(keys, kind="stable")
            return (keys[order], payload[order], arrived_sorted,
                    int(reader.metrics.remote_bytes))

        stage = MapStage(self.maps, ShuffleDependency(
            self.partitions, PartitionerSpec("range", splitters),
            row_payload_bytes=self.p["payload_bytes"]), map_fn)
        return ResultStage(self.partitions, reduce_fn, parents=[stage])

    def run_unit(self) -> dict:
        from sparkrdma_tpu.parallel import exchange
        from sparkrdma_tpu.utils.trace import Tracer

        self.last = None
        job = self._job()
        # the default conf has no trace_file, so the managers carry the
        # no-op tracer; the engine's own spans and instants are read here
        self.engine.tracer = Tracer()
        built = self.fetchers_built
        dispatched = exchange.DATA_PLANE["exchanges"]
        t0 = time.perf_counter()
        results = self.engine.run(job)
        t1 = time.perf_counter()
        self.engine.tracer.dump(self.trace_path)
        with open(self.trace_path) as f:
            events = [e for e in json.load(f)["traceEvents"]
                      if e.get("ph") in ("X", "i")]
        self.last = results
        return {
            "start": t0, "end": t1, "events": events,
            "dispatches": exchange.DATA_PLANE["exchanges"] - dispatched,
            "tcp_fetchers_built": self.fetchers_built - built,
            "remote_bytes": sum(r[3] for r in results),
            "arrived_sorted": all(r[2] for r in results),
            "records": sum(len(r[0]) for r in results),
        }

    def unit_problems(self, facts: dict) -> list:
        out = []
        planes = [e["args"].get("plane") for e in facts["events"]
                  if e["name"] == "exchange.select"]
        if planes != [self.p["expect_plane"]]:
            out.append(f"exchange.select planes {planes}, expected exactly "
                       f"one {self.p['expect_plane']!r}")
        degrades = sum(e["name"] == "exchange.degrade"
                       for e in facts["events"])
        if degrades:
            out.append(f"{degrades} exchange.degrade instants")
        if self.p["expect_plane"] == "device":
            if facts["tcp_fetchers_built"] or facts["remote_bytes"]:
                out.append(f"{facts['tcp_fetchers_built']} TCP fetchers "
                           f"built, {facts['remote_bytes']} remote bytes")
            if facts["dispatches"] < 1:
                out.append("DATA_PLANE['exchanges'] did not advance")
        if not facts["arrived_sorted"]:
            out.append("a partition arrived unsorted")
        if facts["records"] != self.records:
            out.append(f"{facts['records']} records arrived of "
                       f"{self.records}")
        return out

    def verify_last(self) -> list:
        want_keys, want_payload = reference.sorted_records(self.parts)
        got_keys = np.concatenate([r[0] for r in self.last])
        got_payload = np.concatenate([r[1] for r in self.last])
        if (np.array_equal(got_keys, want_keys)
                and np.array_equal(got_payload, want_payload)):
            return []
        return ["result differs from the numpy sort of the same input"]

    def close(self) -> None:
        self.last = None
        self._fetcher_cls.__init__ = self._fetcher_init
        for ex in self.execs:
            ex.stop()
        self.driver.stop()
        shutil.rmtree(self.tmp, ignore_errors=True)
