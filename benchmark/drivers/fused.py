"""Kind ``fused``: TeraSort rounds through the device plane's fused step.

A unit is one blocked call of ``models.terasort.make_terasort_step`` on
rows that are already resident in HBM: map outputs ready -> every chip
holds its key range, sorted. The rows are made once from the seed; every
unit sorts the same resident round, as the rounds of a larger job would.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import reference

AXIS = "shuffle"


class Workload:
    def __init__(self, config: dict, sizes: dict, devices: list, seed: int,
                 scratch: str):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from sparkrdma_tpu.models.terasort import (
            TeraSortConfig,
            make_terasort_step,
        )
        from sparkrdma_tpu.parallel import exchange

        self._jax = jax
        p = config["params"]
        n = len(devices)
        words = 1 + p["payload_words"]
        cfg = TeraSortConfig(rows_per_device=sizes["rows_per_chip"],
                             payload_words=p["payload_words"],
                             out_factor=p["out_factor"],
                             sort_mode=p["sort_mode"])
        mesh = Mesh(np.array(devices), (AXIS,))
        self.impl = exchange.resolve_impl(mesh, p["impl"], AXIS)
        if n > 1 and devices[0].platform == "tpu" and self.impl != "native":
            raise RuntimeError(f"transport resolved to {self.impl!r}, not "
                               "'native': the bytes would not cross ICI "
                               "through ragged_all_to_all")
        self.n = n
        self.total_rows = n * cfg.rows_per_device
        self.unit_bytes = self.total_rows * 4 * words
        self.info = {"rows_per_chip": cfg.rows_per_device,
                     "row_bytes": 4 * words, "chips": n,
                     "exchange_impl": self.impl if n > 1 else "none"}
        self.rows = np.random.default_rng(seed).integers(
            0, 2**32, size=(self.total_rows, words), dtype=np.uint32)
        self.rows_d = jax.device_put(self.rows, NamedSharding(mesh, P(AXIS)))
        self.step = make_terasort_step(mesh, AXIS, cfg, impl=p["impl"])
        self.last = None

    def run_unit(self) -> dict:
        self.last = None  # the previous output leaves HBM before the step
        t0 = time.perf_counter()
        out, counts, overflowed = self._jax.block_until_ready(
            self.step(self.rows_d))
        t1 = time.perf_counter()
        self.last = (out, counts)
        return {"start": t0, "end": t1, "counts": counts,
                "overflowed": overflowed}

    def unit_problems(self, facts: dict) -> list:
        out = []
        counts = np.asarray(facts["counts"])
        flagged = np.nonzero(np.asarray(facts["overflowed"]).ravel())[0]
        if len(flagged):
            out.append(f"receive overflow on devices {flagged.tolist()}")
        if counts.shape != (self.n, self.n):
            out.append(f"counts shape {counts.shape}")
        elif int(counts.sum()) != self.total_rows:
            out.append(f"{int(counts.sum())} rows delivered of "
                       f"{self.total_rows}")
        return out

    def verify_last(self) -> list:
        out, counts = self.last
        counts = np.asarray(counts)
        shards = sorted(out.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        per_device = [np.asarray(s.data)[:int(counts[d].sum())]
                      for d, s in enumerate(shards)]
        return reference.terasort_problems(per_device, self.rows)

    def close(self) -> None:
        self.last = None
