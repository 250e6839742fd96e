"""Micro-benchmarks for the TeraSort local-sort bottleneck on hardware.

Times the two phases of sort_rows_by_key separately across row widths:
the (key, iota) sort and the row gather — plus narrow-payload multisort
scaling, so layout/strategy decisions are measured, not guessed.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def timeit(fn, *args, reps=5):
    fn_j = jax.jit(fn)
    jax.block_until_ready(fn_j(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_j(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def log(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def main():
    n_rows = int(sys.argv[1]) if len(sys.argv) > 1 else 10_700_000
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 2**32, n_rows, dtype=np.uint32))
    order_np = rng.permutation(n_rows).astype(np.int32)
    order = jnp.asarray(order_np)
    log(f"n={n_rows} on {jax.devices()[0].device_kind}")

    # dispatch+fetch round-trip floor (subtract from small timings)
    t0 = time.perf_counter()
    for _ in range(5):
        np.asarray(keys[:1])
    log(f"sync RTT floor: {(time.perf_counter()-t0)/5*1e3:.1f} ms")

    dt = timeit(lambda k: jax.lax.sort(
        (k, jnp.arange(k.shape[0], dtype=jnp.int32)), num_keys=1), keys)
    log(f"sort(key,iota): {dt*1e3:.1f} ms ({dt/n_rows*1e9:.2f} ns/row)")

    for width in (8, 16, 25, 32):
        rows = jnp.asarray(
            rng.integers(0, 2**32, (n_rows, width), dtype=np.uint32))
        dt = timeit(lambda r, o: jnp.take(r, o, axis=0), rows, order)
        bw = rows.nbytes * 2 / dt / 1e9
        log(f"gather width={width:3d}: {dt*1e3:7.1f} ms "
            f"({dt/n_rows*1e9:6.2f} ns/row, {bw:5.1f} GB/s r+w)")
        del rows

    # multisort scaling in payload operand count (compile can explode at
    # high operand counts: bound each with an alarm)
    for width in (2, 4, 8):
        rows = jnp.asarray(
            rng.integers(0, 2**32, (n_rows, width), dtype=np.uint32))

        def ms(k, r):
            cols = tuple(r[:, j] for j in range(r.shape[1]))
            out = jax.lax.sort((k,) + cols, num_keys=1)
            return jnp.stack(out[1:], axis=1)

        t0 = time.perf_counter()
        try:
            dt = timeit(ms, keys, rows)
            log(f"multisort width={width}: {dt*1e3:.1f} ms "
                f"(compile+warm {time.perf_counter()-t0:.0f}s)")
        except Exception as e:  # noqa: BLE001
            log(f"multisort width={width}: failed {e}")
        del rows


if __name__ == "__main__":
    main()
