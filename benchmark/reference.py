"""The plain references the cells' outputs are held to, in numpy alone.

``numpy_terasort`` and the row fingerprints are copies from
``sparkrdma_tpu.models.terasort`` (``verify_terasort``'s checks are in
``terasort_problems``), ``sorted_records`` is ``chip_smoke.py``'s numpy
sort; kept here so that no later change to the program can move the
yardstick. Nothing in this file imports the program or jax.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_CHUNK = 1 << 19   # rows a comparison task takes


def numpy_terasort(rows: np.ndarray, num_partitions: int) -> np.ndarray:
    """The same partition / shuffle / sort pipeline on the host: rows
    ``u32[N, W]`` keyed on word 0, split into ``num_partitions`` even
    u32 key ranges, each range stably sorted by key."""
    keys = rows[:, 0]
    edges = np.array([(i * (1 << 32)) // num_partitions
                      for i in range(1, num_partitions)], dtype=np.uint64)
    dest = np.searchsorted(edges, keys.astype(np.uint64), side="right")
    order = np.argsort(dest, kind="stable")
    grouped = rows[order]
    counts = np.bincount(dest, minlength=num_partitions)
    out = np.empty_like(grouped)
    start = 0
    for c in counts:
        seg = grouped[start:start + c]
        out[start:start + c] = seg[np.argsort(seg[:, 0], kind="stable")]
        start += c
    return out


def _row_fingerprints(rows: np.ndarray) -> np.ndarray:
    """One u64 per row: every word times a fixed odd per-column
    multiplier, summed mod 2^64; equal fingerprint multisets mean equal
    whole-row multisets up to a 2^-64 collision. Chunked, so a GiB of
    rows never doubles in memory."""
    mult = np.random.default_rng(0x7E5A).integers(
        0, 2**63, size=rows.shape[1], dtype=np.uint64) * 2 + 1
    out = np.empty(len(rows), dtype=np.uint64)
    chunk = 1 << 20
    for lo in range(0, len(rows), chunk):
        out[lo:lo + chunk] = (rows[lo:lo + chunk].astype(np.uint64)
                              * mult).sum(axis=1, dtype=np.uint64)
    return out


def terasort_problems(per_device: list, input_rows: np.ndarray) -> list:
    """The fused configuration's guarantees on one whole output, and the
    record-for-record comparison with the reference. ``per_device`` holds
    each device's valid rows (padding stripped), in device order. Returns
    what is wrong, as sentences.

    The reference is the global stable sort of the input by key: even key
    ranges taken in order, each stably sorted, ARE that sort, whatever the
    number of ranges (``numpy_terasort`` spells the pipeline out, and the
    tests hold the two equal). Compared in chunks on a few threads — numpy
    releases the interpreter lock in the gather — because one run in
    every check pays for it."""
    out: list = []
    n = len(per_device)
    edges = [(i << 32) // n for i in range(n + 1)]
    for d, rows in enumerate(per_device):
        keys = rows[:, 0]
        if len(keys) and (keys[1:] < keys[:-1]).any():
            out.append(f"device {d} is not sorted by key")
        if len(keys) and not (edges[d] <= int(keys.min())
                              and int(keys.max()) < edges[d + 1]):
            out.append(f"device {d} holds keys outside its range")
    delivered = sum(len(r) for r in per_device)
    if delivered != len(input_rows):
        out.append(f"{delivered} rows delivered of {len(input_rows)}")
        return out
    order = np.argsort(input_rows[:, 0], kind="stable")
    chunks = []
    base = 0
    for rows in per_device:
        chunks += [(rows[lo:lo + _CHUNK], base + lo)
                   for lo in range(0, len(rows), _CHUNK)]
        base += len(rows)

    def same(chunk) -> bool:
        got, at = chunk
        return np.array_equal(got, input_rows[order[at:at + len(got)]])

    with ThreadPoolExecutor(max_workers=8) as pool:
        equal = all(pool.map(same, chunks))
    if not equal:
        out.append("output differs from the stable sort of the input")
        got_fp = np.concatenate([_row_fingerprints(r) for r in per_device])
        if not np.array_equal(np.sort(got_fp),
                              np.sort(_row_fingerprints(input_rows))):
            out.append("row multiset differs: a row was lost, doubled or "
                       "a payload left its key")
    return out


def sorted_records(parts: list):
    """The SPI job's reference: every map's ``(keys u64[n], payload
    u8[n, P])`` concatenated and stably sorted by key. Range partitions
    read in order ARE this global sort."""
    keys = np.concatenate([k for k, _ in parts])
    payload = np.concatenate([p for _, p in parts])
    order = np.argsort(keys, kind="stable")
    return keys[order], payload[order]
