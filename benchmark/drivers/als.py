"""Kind ``als``: MLlib ALS jobs over a resident blocked data set.

A unit is one job of ``models.als.ALSJob``: the user factors are reset on
the device from the seed, ``iterations`` sweeps are dispatched back to
back (each half-step shuffles one factor row of 40 bytes to every block
that rates it, gathers a row a rating out of the receive buffer, sums the
normal equations a destination id and solves them) and the caller blocks
once. The ratings are made once from the seed (``netflix_like_ratings``),
blocked once on the host (``block_ratings``: MLlib's In/OutBlocks) and
stay in HBM; a job's factors stay there too.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmark import reference_als

AXIS = "shuffle"
ROW_BYTES = 40   # a factor row: rank 10 x float32


class Workload:
    def __init__(self, config: dict, sizes: dict, devices: list, seed: int,
                 scratch: str):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from sparkrdma_tpu.models.als import (
            ALSConfig,
            ALSJob,
            block_ratings,
            ids_per_block,
            netflix_like_ratings,
            place_als,
            zipf_exponent,
        )
        from sparkrdma_tpu.parallel import exchange

        self._jax = jax
        p = config["params"]
        n = len(devices)
        self.iterations = sizes["iterations"]
        self.cfg = ALSConfig(num_users=sizes["users"],
                             num_items=sizes["items"], rank=p["rank"],
                             reg=p["reg"], out_factor=p["out_factor"])
        mesh = Mesh(np.array(devices), (AXIS,))
        self.ratings = netflix_like_ratings(
            self.cfg, sizes["ratings"], seed, sizes["item_top_share"],
            sizes["user_top_share"])
        user_side, item_side = block_ratings(self.cfg, self.ratings, n)
        self.out_links = {"item": item_side.out_links,
                          "user": user_side.out_links}
        self.unit_bytes = (self.iterations * sum(self.out_links.values())
                           * ROW_BYTES)
        self.info = {
            "ratings_per_chip": sizes["ratings"] / n,
            "recv_rows_per_chip": {
                "item": item_side.out_links / n,
                "user": user_side.out_links / n},
            "ids_per_chip": {
                "item": ids_per_block(self.cfg.num_items, n),
                "user": ids_per_block(self.cfg.num_users, n)},
            "rank": self.cfg.rank, "iterations": self.iterations,
            "chips": n,
            "exchange_impl": exchange.resolve_impl(mesh, p["impl"], AXIS)}
        data = {"zipf_s_item": zipf_exponent(self.cfg.num_items,
                                             sizes["item_top_share"]),
                "zipf_s_user": zipf_exponent(self.cfg.num_users,
                                             sizes["user_top_share"]),
                "max_segment": {"item": item_side.max_segment,
                                "user": user_side.max_segment},
                "out_links": self.out_links,
                "rating_slots_per_chip": {
                    "item": int(item_side.src_pos[0].size),
                    "user": int(user_side.src_pos[0].size)}}
        print(f"benchmark/drivers/als.py: the data {json.dumps(data)}",
              file=sys.stderr)
        self.resident = place_als(mesh, AXIS, (user_side, item_side))
        del user_side, item_side
        self.job = ALSJob(mesh, AXIS, self.cfg, self.iterations, seed,
                          impl=p["impl"])
        self.trace_path = os.path.join(scratch, f"als_{os.getpid()}.json")
        self.last = None

    def run_unit(self) -> dict:
        from sparkrdma_tpu.utils.trace import Tracer

        self.last = None
        self.job.tracer = Tracer()
        t0 = time.perf_counter()
        factors = self.job(self.resident)
        t1 = time.perf_counter()
        self.job.tracer.dump(self.trace_path)
        with open(self.trace_path) as f:
            events = [e for e in json.load(f)["traceEvents"]
                      if e.get("ph") in ("X", "C")]
        self.last = factors
        return {"start": t0, "end": t1, "events": events,
                "on_device": all(isinstance(f, self._jax.Array)
                                 for f in factors)}

    def unit_problems(self, facts: dict) -> list:
        out = []
        jobs = [e["args"] for e in facts["events"] if e["name"] == "als.job"]
        want = [self.out_links["item"],
                self.out_links["user"]] * self.iterations
        if len(jobs) != 1 or jobs[0].get("received") != want:
            out.append(f"factor rows received a half-step "
                       f"{[j.get('received') for j in jobs]}, the "
                       f"OutBlocks' links {want}")
        fill = [e["args"]["value"] for e in facts["events"]
                if e["name"] == "als.recv_fill"]
        if not fill or max(fill) > 1.0:
            out.append(f"als.recv_fill {fill}: a receive buffer was past "
                       "its capacity")
        if not facts["on_device"]:
            out.append("the job did not return its factors as jax.Arrays: "
                       "they left the device")
        return out

    def verify_last(self) -> list:
        import numpy as np

        from sparkrdma_tpu.models.als import factors_by_id

        n, cfg = self.info["chips"], self.cfg

        def by_id(pair):
            items, users = pair
            return (factors_by_id(items, cfg.num_items, n),
                    factors_by_id(users, cfg.num_users, n))

        # the job's programs again, keeping what each sweep returned; the
        # last sweep is what the reference is held against
        sweeps = self.job.trajectory(self.resident)
        step = by_id(sweeps[-1])
        if not all(np.array_equal(a, b)
                   for a, b in zip(step, by_id(self.last[::-1]))):
            return ["the replayed job's factors are not bit for bit the "
                    "last timed job's: the programs are not deterministic, "
                    "or the resident blocks changed"]
        start = (by_id(sweeps[-2])[1] if len(sweeps) > 1
                 else self.job.initial_user_factors())
        del sweeps
        t0 = time.perf_counter()
        problems, readings = reference_als.als_report(
            [step], *self.ratings, start, cfg.reg,
            first_sweep=self.iterations - 1)
        readings["reference_s"] = time.perf_counter() - t0
        print(f"benchmark/drivers/als.py: against the reference "
              f"{json.dumps(readings)}", file=sys.stderr)
        return problems

    def close(self) -> None:
        self.last = None
        if os.path.exists(self.trace_path):
            os.remove(self.trace_path)
