"""Micro-benchmarks for the TeraSort local-sort bottleneck on hardware.

Two modes, both for a TPU: they time the device. ``rowmove`` exits
non-zero anywhere else; ``sort`` runs anywhere, and off the chip its
numbers mean nothing.

``python scripts/tpu_micro.py [sort] [n_rows]``
    the two phases of the local sort apart across row widths: the
    (key, iota) sort and the row gather.

``python scripts/tpu_micro.py rowmove [out.json]``
    the row move's sweep, N x W x form (``PERF.md`` section 6, PR 29):
    ``jnp.take`` against ``ops.row_permute``'s packed form with its three
    parts (pack, permute, unpack) timed apart, ns a row. One JSON object a
    line on stdout, and the whole table in ``out.json`` (default
    ``chiprun_out/rowmove.json``). No benchmark cell runs it.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

ROWMOVE_N = (1 << 17, 1 << 18, 1 << 20, 1 << 22, 10_737_418)
ROWMOVE_W = (2, 8, 16, 25, 32)


def timeit(fn, *args, reps=5):
    fn_j = jax.jit(fn)
    jax.block_until_ready(fn_j(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_j(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def time_queued(fn, *args, reps=5, batches=2):
    """Seconds a call of jitted ``fn``: ``reps`` calls queued back to back
    and one block at their end (so a sub-millisecond kernel is not timed
    by the host's round trip), the least of ``batches`` such means."""
    fn_j = jax.jit(fn)
    jax.block_until_ready(fn_j(*args))
    best = float("inf")
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn_j(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def log(msg):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def sort_main(n_rows):
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


def rowmove_point(n, w, seed=0):
    """One point of the sweep: ns a row of ``jnp.take`` and of the packed
    form, whole and by part, each a jitted program of its own."""
    from sparkrdma_tpu.ops import row_permute as rp

    key = jax.random.key(seed * 1_000_003 + n * 131 + w)
    k_rows, k_order = jax.random.split(key)
    rows = jax.random.bits(k_rows, (n, w), jnp.uint32)
    order = jax.random.permutation(k_order, n).astype(jnp.int32)
    want = jax.jit(lambda r, o: jnp.take(r, o, axis=0))(rows, order)

    slots = rp._slots(w)
    q = rp._packed_rows(n, slots)

    def pack(r):
        return rp.pack_rows(r)

    def permute(p, o):
        return rp.permute_packed(p, jnp.pad(o, (0, slots * q - n)), slots)

    def unpack(p):
        return rp.unpack_rows(p, n, w)

    def packed_whole(r, o):
        return unpack(permute(pack(r), o))

    got = jax.jit(packed_whole)(rows, order)
    point = {"n_rows": n, "row_words": w,
             "form": rp.row_move_form(n, w, "tpu"),
             "equal": bool(jnp.array_equal(got, want))}
    del got, want
    packed = jax.jit(pack)(rows)
    seconds = {
        "take": time_queued(lambda r, o: jnp.take(r, o, axis=0),
                            rows, order),
        "packed": time_queued(packed_whole, rows, order),
        "pack": time_queued(pack, rows),
        "permute": time_queued(permute, packed, order),
        "unpack": time_queued(unpack, packed),
    }
    point.update({f"{name}_ns_row": s / n * 1e9
                  for name, s in seconds.items()})
    return point


def rowmove_main(out_path):
    device = jax.devices()[0]
    if device.platform != "tpu":
        # the kernels would have to be interpreted, and an interpreter's
        # times must not stand under the names of the chip's
        sys.exit(f"tpu_micro.py rowmove: needs a TPU, found "
                 f"{device.platform!r}; tests/test_row_permute.py runs the "
                 "kernels interpreted")
    table = {"device": {"platform": device.platform,
                        "kind": device.device_kind}, "points": []}
    for w in ROWMOVE_W:
        for n in ROWMOVE_N:
            point = rowmove_point(n, w)
            table["points"].append(point)
            print(json.dumps(point), flush=True)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(table, f, indent=1)


def main():
    args = sys.argv[1:]
    if args and args[0] == "rowmove":
        rowmove_main(args[1] if len(args) > 1 else "chiprun_out/rowmove.json")
        return
    if args and args[0] == "sort":
        args = args[1:]
    sort_main(int(args[0]) if args else 10_700_000)


if __name__ == "__main__":
    main()
