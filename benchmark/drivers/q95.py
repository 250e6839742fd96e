"""Kind ``q95``: TPC-DS q95 jobs over resident tables.

A unit is one job of ``models.tpcds_queries.Q95Job``: one program filters
``web_sales`` against the three dimensions, shuffles ``(order,
warehouse)`` of every ``web_sales`` row, ``(order)`` of every
``web_returns`` row and the survivors to the order's owner, joins them
there, and the caller blocks once. The tables are made once from the seed
(``generate_q95``) and stay in HBM; a job's answers stay there too.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

from benchmark import q95_bytes, reference_q95

AXIS = "shuffle"
PARAMETERS = ("window_start", "window_days", "target_state",
              "target_company")


class Workload:
    def __init__(self, config: dict, sizes: dict, devices: list, seed: int,
                 scratch: str):
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from sparkrdma_tpu.models.tpcds_queries import (
            Q95Config,
            Q95Job,
            generate_q95,
            place_q95,
        )
        from sparkrdma_tpu.parallel import exchange

        self._jax = jax
        p = config["params"]
        n = len(devices)
        # the schema's ranges are the configuration's. Only a rehearsal
        # (off the TPU: run.py lets nothing else be) shrinks them, from
        # the traffic file's ``rehearsal`` block and from nowhere else, so
        # that its few rows still pass the filter
        toy = sizes.get("rehearsal", {}) if devices[0].platform != "tpu" \
            else {}
        states = toy.get("states", p["states"])
        self.cfg = Q95Config(
            ws_rows_per_device=sizes["ws_rows_per_chip"],
            wr_rows_per_device=sizes["wr_rows_per_chip"],
            num_orders=n * sizes["orders_per_chip"],
            survivor_capacity=toy.get("survivor_capacity",
                                      p["survivor_capacity"]),
            order_base=p["order_base"], items_lo=p["items_lo"],
            items_hi=p["items_hi"], num_warehouses=sizes["warehouse"],
            num_dates=sizes["date_dim"], window_start=p["window_start"],
            window_days=p["window_days"], max_ship_lag=p["max_ship_lag"],
            num_addresses=sizes["customer_address"],
            num_states=states, target_state=p["target_state"] % states,
            num_sites=sizes["web_site"],
            num_companies=toy.get("companies", p["companies"]),
            target_company=p["target_company"], out_factor=p["out_factor"])
        self.params = {k: getattr(self.cfg, k) for k in PARAMETERS}
        mesh = Mesh(np.array(devices), (AXIS,))
        self.tables = generate_q95(self.cfg, n, seed)
        self.ws_rows = len(self.tables.ws_order)
        self.wr_rows = len(self.tables.wr_order)
        self._shapes = {"ws_rows_per_chip": self.cfg.ws_rows_per_device,
                        "wr_rows_per_chip": self.cfg.wr_rows_per_device,
                        "chips": n,
                        "exchange_impl": exchange.resolve_impl(
                            mesh, p["impl"], AXIS)}
        # units still to run before the window: run.py's warm-up
        self._warm_left = sizes.get("warm_units", 1)
        self.resident = place_q95(mesh, AXIS, self.tables)
        self.job = Q95Job(mesh, AXIS, self.cfg, impl=p["impl"])
        self.trace_path = os.path.join(scratch, f"q95_{os.getpid()}.json")
        self.last = None

    @functools.cached_property
    def survivors(self) -> int:
        """The reference's count of the rows the filter passes. Made on
        first use, which is after the window (a warm unit is held to the
        job's own count), so the reference's seconds are not set-up's."""
        return int(reference_q95.survivor_mask(self.tables,
                                               self.params).sum())

    @property
    def unit_bytes(self) -> int:
        return q95_bytes.shuffled_bytes(self.ws_rows, self.wr_rows,
                                        self.survivors)

    @property
    def info(self) -> dict:
        return dict(self._shapes, survivors_per_chip=(
            self.survivors / self._shapes["chips"]))

    def run_unit(self) -> dict:
        from sparkrdma_tpu.utils.trace import Tracer

        warm = self._warm_left > 0
        self._warm_left -= warm
        self.last = None
        self.job.tracer = Tracer()
        t0 = time.perf_counter()
        answers = self.job(self.resident)
        t1 = time.perf_counter()
        self.job.tracer.dump(self.trace_path)
        with open(self.trace_path) as f:
            events = [e for e in json.load(f)["traceEvents"]
                      if e.get("ph") in ("X", "C")]
        self.last = answers
        return {"start": t0, "end": t1, "events": events, "warm": warm,
                "on_device": all(isinstance(a, self._jax.Array)
                                 for a in answers)}

    def unit_problems(self, facts: dict) -> list:
        out = []
        jobs = [e["args"] for e in facts["events"] if e["name"] == "q95.job"]
        survivors = (jobs[0].get("survivors") if facts.get("warm") and jobs
                     else self.survivors)
        want = [self.ws_rows, self.wr_rows, survivors]
        if len(jobs) != 1 or jobs[0].get("received") != want:
            out.append(f"records received in the three exchanges "
                       f"{[j.get('received') for j in jobs]}, rows sent "
                       f"{want}")
        fill = [e["args"]["value"] for e in facts["events"]
                if e["name"] == "q95.recv_fill"]
        if not fill or max(fill) > 1.0:
            out.append(f"q95.recv_fill {fill}: a receive buffer was past "
                       "its capacity")
        if not facts["on_device"]:
            out.append("the job did not return its answers as jax.Arrays: "
                       "they left the device")
        return out

    def verify_last(self) -> list:
        from sparkrdma_tpu.models.tpcds_queries import q95_totals

        got = q95_totals(self.last)._asdict()
        print(f"benchmark/drivers/q95.py: the job's six integers "
              f"{json.dumps(got)}", file=sys.stderr)
        return reference_q95.q95_problems(got, self.tables, self.params)

    def close(self) -> None:
        self.last = None
        if os.path.exists(self.trace_path):
            os.remove(self.trace_path)
