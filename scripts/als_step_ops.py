"""Per-op device seconds of ``als_4chip``'s two half-steps, each traced alone.

    python scripts/als_step_ops.py <seed> [out_dir]

The benchmark's ``breakdown`` adds device ops by name over the window, and
the two half-step programs share most names (both have a ``fusion.27``):
its top ops are sums of an items' and a users' op (``PERF.md`` section
7.15). This draws and blocks the cell's data (100,480,507 ratings, ~45 s of
host work), runs one job of one sweep, then profiles each half-step's
program three times on its own and writes ``<out_dir>/ops.json`` (default
``chiprun_out/als_step_ops``): a step's device seconds, its seventy largest
ops and its seconds by kind of op, on device 0. On the cell's four chips it
is the cell (about two minutes from the compile cache); on fewer it keeps a
chip's share a chip (a quarter of the users and of the ratings on one chip,
every item: both tables are then gathered as rows, and a minute of one chip
costs a quarter of the four's). Exits non-zero without a TPU.
"""

import json
import os
import shutil
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
from jax.sharding import Mesh

from benchmark import xplane
from sparkrdma_tpu.models import als
from sparkrdma_tpu.utils.compile_cache import enable_compile_cache

AXIS = "shuffle"
USERS, ITEMS, RATINGS, CELL_CHIPS = 480_189, 17_770, 100_480_507, 4
TOP_SHARES = {"item_top_share": 0.00232, "user_top_share": 0.000176}
REPS, TOP_OPS, TOP_KINDS = 3, 70, 25


def step_ops(step, args, log_dir):
    """Mean device seconds an op of ``step(*args)`` on device 0, by the
    profiler's raw event name, over ``REPS`` calls traced alone."""
    with jax.profiler.trace(log_dir):
        for _ in range(REPS):
            out = step(*args)
        jax.block_until_ready(out)
    trace = xplane.load_xplane(xplane.find_xplane(log_dir))
    shutil.rmtree(log_dir, ignore_errors=True)
    ops: dict = {}
    for raw, _, dur_ns in trace["planes"]["/device:TPU:0"].get(
            xplane.OPS_LINE, []):
        ops[raw] = ops.get(raw, 0.0) + dur_ns * 1e-9 / REPS
    return ops


def main():
    if len(sys.argv) < 2:
        sys.exit(__doc__)
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"als_step_ops.py: needs a TPU, found "
                 f"{jax.devices()[0].platform!r}: a CPU's ops are another "
                 "program's")
    seed = int(sys.argv[1])
    out_dir = sys.argv[2] if len(sys.argv) > 2 else "chiprun_out/als_step_ops"
    os.makedirs(out_dir, exist_ok=True)
    enable_compile_cache()
    t0 = time.perf_counter()
    devices = jax.devices()
    share = min(len(devices), CELL_CHIPS) / CELL_CHIPS
    cfg = als.ALSConfig(num_users=int(USERS * share), num_items=ITEMS)
    mesh = Mesh(np.array(devices), (AXIS,))
    ratings = als.netflix_like_ratings(cfg, int(RATINGS * share), seed,
                                       **TOP_SHARES)
    blocks = als.block_ratings(cfg, ratings, len(devices))
    print(f"host {time.perf_counter() - t0:.1f} s", flush=True)
    resident = als.place_als(mesh, AXIS, blocks)
    del blocks, ratings
    job = als.ALSJob(mesh, AXIS, cfg, 1, seed)
    users, items = job(resident)       # compiles or loads both programs
    report = {"device": devices[0].device_kind, "chips": len(devices),
              "seed": seed}
    for side, source, blocks_of in (("item", users, resident.item_side),
                                    ("user", items, resident.user_side)):
        ops = step_ops(job._steps[side], (source, *blocks_of.arrays),
                       os.path.join(out_dir, f"trace_{side}"))
        kinds: dict = {}
        for raw, seconds in ops.items():
            # an op's kind: its name less its number
            kind = xplane.op_name(raw).rstrip("0123456789").rstrip(".")
            kinds[kind] = kinds.get(kind, 0.0) + seconds
        by_seconds = lambda kv: -kv[1]  # noqa: E731
        report[side] = {
            "step_device_s": sum(ops.values()),
            "kinds": sorted(kinds.items(), key=by_seconds)[:TOP_KINDS],
            "ops": [[raw[:160], seconds] for raw, seconds
                    in sorted(ops.items(), key=by_seconds)[:TOP_OPS]]}
        print(side, f"{report[side]['step_device_s']:.5f} s",
              report[side]["kinds"][:6], flush=True)
    with open(os.path.join(out_dir, "ops.json"), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
