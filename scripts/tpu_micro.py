"""Micro-benchmarks of single kernels on hardware: the TeraSort local sort,
the row move, PageRank's per-edge gather, the grouping of narrow rows.

Five modes, all for a TPU: they time the device. ``rowmove``, ``gather``
and ``groupsort`` exit non-zero anywhere else; ``sort`` runs anywhere, and
off the chip its numbers mean nothing.

``python scripts/tpu_micro.py [sort] [n_rows]``
    the two phases of the local sort apart across row widths: the
    (key, iota) sort and the row gather.

``python scripts/tpu_micro.py rowmove [out.json]``
    the row move's sweep, N x W x form (``PERF.md`` section 6, PR 29):
    ``jnp.take`` against ``ops.row_permute``'s packed form with its three
    parts (pack, permute, unpack) timed apart, ns a row. One JSON object a
    line on stdout, and the whole table in ``out.json`` (default
    ``chiprun_out/rowmove.json``). No benchmark cell runs it.

``python scripts/tpu_micro.py rowmove mn [out.json]``
    the row move where the order is not the operand's length (``PERF.md``
    section 6, PR 35 and 37): ALS's per-rating gather, M = 25,120,127
    indices, repeated, into N rows of 10 words (N = 960,376 and 35,540,
    the two receive buffers at ``out_factor`` 2; 480,189 and 17,770, the
    rows that can arrive; 240,095 and 120,048, a half and a quarter of the
    users), by ``jnp.take`` in chunks of 2^20 indices as the half-step's
    scan takes them, ns an index; and the packed form at
    N = M, which bounds what a packed gather of M out of N would cost
    (its operand would be smaller). Default ``chiprun_out/rowmove_mn.json``.

``python scripts/tpu_micro.py gather [out.json]``
    PageRank's contribution phase alone at ``pagerank_1chip``'s shape
    (16,777,280 indices into ``f32[468750]``; ``PERF.md`` section 6,
    PR 32): one gather of a table against the two gathers and the per-edge
    divide, and the table built in the program as the superstep builds
    it; random indices against sorted ones, ns an index. Output as
    ``rowmove``'s (default ``chiprun_out/gather.json``).

``python scripts/tpu_micro.py groupsort [out.json]``
    ``exchange.group_by_destination``'s two carriers at 10,737,418 rows of
    2 to 8 words (``PERF.md`` section 6, PR 34): one stable sort with the
    row's words as value operands and the counts read off the sorted
    destinations, against the stable argsort, ``jnp.take`` and
    ``jnp.bincount`` it replaced under 8 words, ns a row. The argsort and
    the bincount do not depend on the width and are timed once. Output as
    ``rowmove``'s (default ``chiprun_out/groupsort.json``).
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
ROWMOVE_MN_N, ROWMOVE_MN_M, ROWMOVE_MN_W = (960_376, 480_189, 240_095, 120_048,
                                            35_540, 17_770), 25_120_127, 10
GATHER_INDICES, GATHER_TABLE = 16_777_280, 468_750
GROUPSORT_N, GROUPSORT_W, GROUPSORT_PARTS = 10_737_418, range(2, 9), 4


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


def _tpu_table(mode, why):
    """The output table's head; exits where the device is no TPU: a time
    taken anywhere else must not stand under the names of the chip's."""
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"tpu_micro.py {mode}: needs a TPU, found "
                 f"{device.platform!r}; {why}")
    return {"device": {"platform": device.platform,
                       "kind": device.device_kind}, "points": []}


def _write_table(table, out_path):
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(table, f, indent=1)


def rowmove_main(out_path):
    table = _tpu_table("rowmove", "tests/test_row_permute.py runs the "
                       "kernels interpreted")
    for w in ROWMOVE_W:
        for n in ROWMOVE_N:
            point = rowmove_point(n, w)
            table["points"].append(point)
            print(json.dumps(point), flush=True)
    _write_table(table, out_path)


def rowmove_mn_main(out_path):
    """``jnp.take`` of M indices out of N rows, M >> N, chunk by chunk."""
    from sparkrdma_tpu.ops import row_permute as rp

    table = _tpu_table("rowmove mn", "the CPU's gather is another program")
    w, chunk = ROWMOVE_MN_W, 1 << 20
    chunks = -(-ROWMOVE_MN_M // chunk)

    def chunked_take(rows, order):
        # every chunk's rows are summed so that none is dropped as dead;
        # straight-line code, as the half-step's chunk loop is (as a
        # ``while`` the v5e read the small tables wrongly, and 7x slower)
        def body(acc, idx):
            return acc + jnp.take(rows, idx, axis=0).T.sum(axis=1), None
        return jax.lax.scan(body, jnp.zeros(w, jnp.uint32), order,
                            unroll=True)[0]

    for n in ROWMOVE_MN_N:
        k_rows, k_order = jax.random.split(jax.random.key(n))
        rows = jax.random.bits(k_rows, (n, w), jnp.uint32)
        order = jax.random.randint(k_order, (chunks, chunk), 0, n, jnp.int32)
        point = {"n_rows": n, "n_indices": chunks * chunk, "row_words": w,
                 "form_by_rule": rp.row_move_form(n, w, "tpu"),
                 "take_ns_index": time_queued(chunked_take, rows, order,
                                              reps=2)
                 / (chunks * chunk) * 1e9}
        table["points"].append(point)
        print(json.dumps(point), flush=True)
    _write_table(table, out_path)
    point = rowmove_point(ROWMOVE_MN_M, w)
    point["note"] = "N = M: the packed form's cost a row, operand and all"
    table["points"].append(point)
    print(json.dumps(point), flush=True)
    _write_table(table, out_path)


# the contribution phase's three forms: what the superstep computed an edge
# before PR 32, what it computes now, and the gather alone
GATHER_FORMS = {
    "two_gathers": lambda ranks, deg, idx:
        ranks[idx] / jnp.maximum(deg[idx], 1.0),
    "table_then_gather": lambda ranks, deg, idx:
        (ranks / jnp.maximum(deg, 1.0))[idx],
    "one_gather": lambda ranks, deg, idx: ranks[idx],
}


def gather_points(n_indices, n_table, seed=0):
    """ns an index of each form, for uniform random indices (the graph's
    sources as ``powerlaw_graph`` draws them) and for the same indices
    sorted (edges ordered by source at placement)."""
    k_idx, k_ranks, k_deg = jax.random.split(jax.random.key(seed), 3)
    ranks = jax.random.uniform(k_ranks, (n_table,), jnp.float32)
    deg = jax.random.randint(k_deg, (n_table,), 0, 72).astype(jnp.float32)
    random = jax.random.randint(k_idx, (n_indices,), 0, n_table, jnp.int32)
    want = jax.jit(GATHER_FORMS["two_gathers"])(ranks, deg, random)
    got = jax.jit(GATHER_FORMS["table_then_gather"])(ranks, deg, random)
    equal = bool(jnp.array_equal(got, want))   # the same float32 quotient
    del got, want
    for order, idx in (("random", random), ("sorted", jnp.sort(random))):
        for form, fn in GATHER_FORMS.items():
            yield {"n_indices": n_indices, "n_table": n_table,
                   "indices": order, "form": form,
                   "table_equals_two_gathers": equal,
                   "ns_index": time_queued(fn, ranks, deg, idx)
                   / n_indices * 1e9}


def gather_main(out_path):
    table = _tpu_table("gather", "the CPU's gather is another program")
    for point in gather_points(GATHER_INDICES, GATHER_TABLE):
        table["points"].append(point)
        print(json.dumps(point), flush=True)
    _write_table(table, out_path)


def groupsort_points(n, widths, parts, seed=0):
    """ns a row of each part of the grouping. ``sort`` is the whole of the
    narrow form (sort, stack, binary searches: ``group_by_destination``'s
    own lines, repeated here because the sweep runs them past the rule's
    edge too); ``argsort`` + ``take`` + ``bincount`` the whole of the
    other, a program each."""
    from jax import lax

    k_dest, k_rows = jax.random.split(jax.random.key(seed))
    dest = jax.random.randint(k_dest, (n,), 0, parts + 1, jnp.int32)
    order = jnp.argsort(dest, stable=True)

    def sort_form(rows, dest):
        sorted_dest, *columns = lax.sort(
            (dest, *(rows[:, k] for k in range(rows.shape[1]))),
            num_keys=1, is_stable=True)
        bounds = jnp.searchsorted(
            sorted_dest, jnp.arange(parts + 1, dtype=jnp.int32), side="left")
        return jnp.stack(columns, axis=1), jnp.diff(bounds)

    shared = {
        "argsort": time_queued(lambda d: jnp.argsort(d, stable=True), dest),
        "bincount": time_queued(
            lambda d: jnp.bincount(d, length=parts + 1)[:parts], dest),
    }
    for w in widths:
        rows = jax.random.bits(jax.random.fold_in(k_rows, w), (n, w),
                               jnp.uint32)
        got, counts = jax.jit(sort_form)(rows, dest)
        equal = bool(jnp.array_equal(got, jnp.take(rows, order, axis=0))
                     and jnp.array_equal(
                         counts, jnp.bincount(dest, length=parts + 1)[:parts]))
        del got
        seconds = dict(shared, sort=time_queued(sort_form, rows, dest),
                       take=time_queued(lambda r, o: jnp.take(r, o, axis=0),
                                        rows, order))
        point = {"n_rows": n, "row_words": w, "partitions": parts,
                 "equal": equal}
        point.update({f"{name}_ns_row": sec / n * 1e9
                      for name, sec in seconds.items()})
        point["argsort_take_ns_row"] = (point["argsort_ns_row"]
                                        + point["take_ns_row"])
        yield point


def groupsort_main(out_path):
    table = _tpu_table("groupsort", "tests/test_packed_exchange.py holds "
                       "the two forms equal on the CPU")
    for point in groupsort_points(GROUPSORT_N, GROUPSORT_W, GROUPSORT_PARTS):
        table["points"].append(point)
        print(json.dumps(point), flush=True)
    _write_table(table, out_path)


def main():
    args = sys.argv[1:]
    if args[:2] == ["rowmove", "mn"]:
        rowmove_mn_main(args[2] if len(args) > 2
                        else "chiprun_out/rowmove_mn.json")
        return
    if args and args[0] == "rowmove":
        rowmove_main(args[1] if len(args) > 1 else "chiprun_out/rowmove.json")
        return
    if args and args[0] == "gather":
        gather_main(args[1] if len(args) > 1 else "chiprun_out/gather.json")
        return
    if args and args[0] == "groupsort":
        groupsort_main(args[1] if len(args) > 1
                       else "chiprun_out/groupsort.json")
        return
    if args and args[0] == "sort":
        args = args[1:]
    sort_main(int(args[0]) if args else 10_700_000)


if __name__ == "__main__":
    main()
