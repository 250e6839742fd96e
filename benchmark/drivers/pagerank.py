"""Kind ``pagerank``: PageRank jobs over a resident power-law graph.

A unit is one job of ``models.pagerank.PageRankJob``: the ranks are reset
on the device, ``iterations`` supersteps are dispatched back to back —
each shuffles one 8-byte contribution per edge to the owner of its target
and sums them there — and the caller blocks once. The graph is made once
from the seed (``powerlaw_graph``) and stays in HBM; the ranks of a job
stay there too.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmark import reference_pagerank

AXIS = "shuffle"
RECORD_BYTES = 8   # u32 dst + f32 contribution


class Workload:
    def __init__(self, config: dict, sizes: dict, devices: list, seed: int,
                 scratch: str):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from sparkrdma_tpu.models.pagerank import (
            PageRankConfig,
            PageRankJob,
            place_graph,
            powerlaw_graph,
        )
        from sparkrdma_tpu.parallel import exchange

        self._jax = jax
        p = config["params"]
        n = len(devices)
        self.iterations = sizes["iterations"]
        self.cfg = PageRankConfig(
            num_vertices=n * sizes["vertices_per_chip"],
            edges_per_device=sizes["edges_per_chip"],
            damping=sizes["damping"], out_factor=p["out_factor"])
        mesh = Mesh(np.array(devices), (AXIS,))
        self.unit_bytes = (self.iterations * n * self.cfg.edges_per_device
                           * RECORD_BYTES)
        self.info = {"edges_per_chip": self.cfg.edges_per_device,
                     "vertices_per_chip": sizes["vertices_per_chip"],
                     "iterations": self.iterations, "chips": n,
                     "exchange_impl": exchange.resolve_impl(
                         mesh, p["impl"], AXIS)}
        self.edges, _, out_deg = powerlaw_graph(self.cfg, n, seed,
                                                sizes["zipf_s"])
        self.graph = place_graph(mesh, AXIS, self.edges, out_deg)
        self.job = PageRankJob(mesh, AXIS, self.cfg, self.iterations,
                               impl=p["impl"])
        self.trace_path = os.path.join(scratch, f"pagerank_{os.getpid()}.json")
        self.last = None

    def run_unit(self) -> dict:
        from sparkrdma_tpu.utils.trace import Tracer

        self.last = None
        self.job.tracer = Tracer()
        t0 = time.perf_counter()
        ranks = self.job(self.graph)
        t1 = time.perf_counter()
        self.job.tracer.dump(self.trace_path)
        with open(self.trace_path) as f:
            events = [e for e in json.load(f)["traceEvents"]
                      if e.get("ph") in ("X", "C")]
        self.last = ranks
        return {"start": t0, "end": t1, "events": events,
                "on_device": isinstance(ranks, self._jax.Array)}

    def unit_problems(self, facts: dict) -> list:
        out = []
        jobs = [e["args"] for e in facts["events"]
                if e["name"] == "pagerank.job"]
        want = [self.graph.num_edges] * self.iterations
        if len(jobs) != 1 or jobs[0].get("received") != want:
            out.append(f"contributions received a superstep "
                       f"{[j.get('received') for j in jobs]}, valid edges "
                       f"in {want}")
        fill = [e["args"]["value"] for e in facts["events"]
                if e["name"] == "pagerank.recv_fill"]
        if not fill or max(fill) > 1.0:
            out.append(f"pagerank.recv_fill {fill}: a receive buffer was "
                       "past its capacity")
        if not facts["on_device"]:
            out.append("the job did not return its ranks as a jax.Array: "
                       "they left the device")
        return out

    def verify_last(self) -> list:
        import numpy as np

        problems, readings = reference_pagerank.pagerank_report(
            np.asarray(self.last), self.edges, self.cfg.num_vertices,
            self.cfg.damping, self.iterations)
        print(f"benchmark/drivers/pagerank.py: against the reference "
              f"{json.dumps(readings)}", file=sys.stderr)
        return problems

    def close(self) -> None:
        self.last = None
        if os.path.exists(self.trace_path):
            os.remove(self.trace_path)
