"""Incremental hardware probe for the TeraSort step: times each stage
(device_put, compile, steps) separately per size/mode so a slow transfer
or a pathological compile is attributable, unlike the all-or-nothing
bench watchdog. Usage:

    python scripts/tpu_probe_bench.py [size_mb] [mode] [reps]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def main():
    size_mb = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    mode = sys.argv[2] if len(sys.argv) > 2 else "gather"
    reps = int(sys.argv[3]) if len(sys.argv) > 3 else 3

    t0 = time.perf_counter()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.models.terasort import (
        TeraSortConfig, generate_rows, make_terasort_step)

    devs = jax.devices()
    log(f"devices={devs} ({time.perf_counter() - t0:.1f}s)")
    n = len(devs)
    mesh = Mesh(np.array(devs), ("shuffle",))
    rows_per_device = (size_mb << 20) // 100 // n
    cfg = TeraSortConfig(rows_per_device=rows_per_device, payload_words=24,
                         out_factor=1 if n == 1 else 2, sort_mode=mode)

    t0 = time.perf_counter()
    rows = generate_rows(cfg, n, seed=0)
    log(f"generated {rows.nbytes >> 20} MiB ({time.perf_counter() - t0:.1f}s)")

    t0 = time.perf_counter()
    rows_d = jax.device_put(rows, NamedSharding(mesh, P("shuffle")))
    jax.block_until_ready(rows_d)
    dt = time.perf_counter() - t0
    log(f"device_put done ({dt:.1f}s, {rows.nbytes / dt / 1e6:.0f} MB/s)")

    step = make_terasort_step(mesh, "shuffle", cfg)
    t0 = time.perf_counter()
    lowered = jax.jit(step).lower(rows_d) if not hasattr(step, "lower") \
        else step.lower(rows_d)
    log(f"lowered ({time.perf_counter() - t0:.1f}s)")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    log(f"compiled mode={mode} ({time.perf_counter() - t0:.1f}s)")

    for i in range(2):
        t0 = time.perf_counter()
        out = compiled(rows_d)
        np.asarray(out[1])
        log(f"warmup {i}: {time.perf_counter() - t0:.2f}s")
    times = []
    for i in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(rows_d))
        times.append(time.perf_counter() - t0)
        log(f"step {i}: {times[-1]:.3f}s")
    best = min(times)
    gbps = rows.nbytes / best / 1e9 / n
    log(f"RESULT size_mb={size_mb} mode={mode} best={best:.3f}s "
        f"-> {gbps:.3f} GB/s/chip")


if __name__ == "__main__":
    main()
