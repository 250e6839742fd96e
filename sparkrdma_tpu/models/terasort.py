"""TeraSort: the flagship workload.

The reference's headline benchmark is TeraSort-320GB, 2.63× faster than
Spark's TCP shuffle on InfiniBand FDR (README.md:11-17; BASELINE.md). It is
the canonical shuffle stress: every byte crosses the network exactly once.

TPU-native design — the whole map/shuffle/reduce cycle of a round is ONE
jitted SPMD step, the device plane's fused step in its range-partition
mode (``parallel.device_plane.make_fused_step``). Rows are ``[N, 1+P]``
uint32 matrices (key word + P payload words):

1. **partition**: a local sort by key. The uniform u32 key-range split
   is monotonic in key, so key-sorted rows are already grouped by
   destination device; the per-destination counts are D-1 binary
   searches on the sorted keys.
2. **exchange**: the grouped rows cross ICI as one dense buffer through
   ``parallel.exchange.ragged_exchange_shard`` (size pre-exchange +
   ragged all-to-all).
3. **local sort**: the received rows are sorted by key (padded rows sort
   to the end via the key-max sentinel).

Each local sort is a key sort of ``(key, iota)`` whose order the rows then
follow (``ops.row_permute``). On one device the step is the one sort.

The result is globally sorted by (device order, local order) — the same
contract as TeraSort's output files. A numpy reference pipeline provides the
CPU baseline (the "stock local sort-shuffle" stand-in, BASELINE.json
config #1).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class TeraSortConfig:
    rows_per_device: int
    payload_words: int = 24  # 4B key word + 24*4B payload ≈ the classic 100B row
    out_factor: int = 2      # receive headroom (uniform keys -> mild skew)
    # The device plane has one local sort. The field stays, accepting
    # only "gather", because the benchmark's driver still passes it
    # (ROADMAP "Named debts" (a)); it goes once that driver stops.
    sort_mode: str = "gather"

    def __post_init__(self):
        if self.sort_mode != "gather":
            raise ValueError(f"unknown sort_mode {self.sort_mode!r}: the "
                             "device plane has one local sort, 'gather'")

    @property
    def row_bytes(self) -> int:
        return 4 * (1 + self.payload_words)


def make_terasort_step(mesh: Mesh, axis_name: str, cfg: TeraSortConfig,
                       impl: str = "auto"):
    """Build the jitted one-round TeraSort step over ``mesh``.

    Takes ``rows: u32[D*rows_per_device, 1+P]`` sharded on the leading axis
    (column 0 is the key); returns ``(sorted_rows, recv_counts[D, D],
    overflowed[D])`` with rows per device sorted by key, padding
    (key=0xFFFFFFFF) at the end. ``overflowed[d]`` flags that device d's
    receive buffer was too small for the skew (results there are truncated
    and must not be trusted — raise ``out_factor`` or chunk the round).

    The step IS the device plane's fused op (``parallel.device_plane.
    make_fused_step``) in its range-partition mode: TeraSort's uniform
    u32 key-range split makes ONE key sort double as the destination
    grouping. The mesh shuffle service rides the same op in its
    caller-computed-destination mode.
    """
    from sparkrdma_tpu.parallel.device_plane import make_fused_step

    return make_fused_step(mesh, axis_name, 1 + cfg.payload_words,
                           out_factor=cfg.out_factor, impl=impl,
                           key_words=1, partition="range")


def generate_rows(cfg: TeraSortConfig, num_devices: int,
                  seed: int = 0) -> np.ndarray:
    """Uniform random TeraSort input: u32 keys + incompressible payload."""
    rng = np.random.default_rng(seed)
    n = num_devices * cfg.rows_per_device
    rows = rng.integers(0, 2**32, size=(n, 1 + cfg.payload_words),
                        dtype=np.uint32)
    return rows


def numpy_terasort(rows: np.ndarray, num_partitions: int) -> np.ndarray:
    """CPU baseline: the identical partition/shuffle/sort pipeline in numpy
    (the single-host stock sort-shuffle stand-in, BASELINE.json config #1)."""
    keys = rows[:, 0]
    edges = np.array([(i * (1 << 32)) // num_partitions
                      for i in range(1, num_partitions)], dtype=np.uint64)
    dest = np.searchsorted(edges, keys.astype(np.uint64), side="right")
    # "shuffle": group rows by destination partition (the data movement)
    order = np.argsort(dest, kind="stable")
    grouped = rows[order]
    counts = np.bincount(dest, minlength=num_partitions)
    # per-partition local sort
    out = np.empty_like(grouped)
    start = 0
    for c in counts:
        seg = grouped[start:start + c]
        out[start:start + c] = seg[np.argsort(seg[:, 0], kind="stable")]
        start += c
    return out


def run_terasort(mesh: Mesh, cfg: TeraSortConfig, axis_name: str = "shuffle",
                 impl: str = "auto", seed: int = 0,
                 rows: Optional[np.ndarray] = None,
                 ) -> Tuple[np.ndarray, np.ndarray, float]:
    """Host driver: generate, run one jitted round, return
    (sorted_rows_by_device, counts, step_seconds). Compile excluded.
    Each dispatched step is tallied in ``exchange.DATA_PLANE``."""
    from sparkrdma_tpu.parallel.exchange import record_exchange

    n = mesh.shape[axis_name]
    if rows is None:
        rows = generate_rows(cfg, n, seed)
    step = make_terasort_step(mesh, axis_name, cfg, impl)
    sharding = NamedSharding(mesh, P(axis_name))
    rows_d = jax.device_put(rows, sharding)
    # compile + warm
    out, counts, overflowed = jax.block_until_ready(step(rows_d))
    record_exchange(len(rows))
    t0 = time.perf_counter()
    out, counts, overflowed = jax.block_until_ready(step(rows_d))
    dt = time.perf_counter() - t0
    record_exchange(len(rows))
    if np.asarray(overflowed).any():
        raise OverflowError(
            "receive buffer overflow: key skew exceeds out_factor headroom "
            f"(devices {np.nonzero(np.asarray(overflowed).ravel())[0].tolist()}); "
            "raise TeraSortConfig.out_factor or chunk the round")
    return np.asarray(out), np.asarray(counts), dt


def run_terasort_streamed(mesh: Mesh, cfg: TeraSortConfig, rows: np.ndarray,
                          axis_name: str = "shuffle", impl: str = "auto",
                          pipeline_rounds: bool = True,
                          phase_times: Optional[dict] = None,
                          ) -> Tuple[list, int]:
    """TeraSort a dataset LARGER than one round's device capacity.

    The 320 GB-class configuration (BASELINE.md config #2): per-device HBM
    holds only a fraction of the data, so the job runs as R rounds of the
    jitted partition/exchange/sort step — each round bounded to
    ``rows_per_device`` rows per device — and each device merges its R
    key-sorted runs host-side. Per-round memory is static; total data is
    not (the chunked-transfer discipline of the reference's grouped
    fetches, scala/RdmaShuffleFetcherIterator.scala:240-276, applied to
    the whole job).

    ``pipeline_rounds`` (default) double-buffers: round r+1's staging +
    device step overlap round r's host-side collection, at the cost of up
    to TWO rounds of device footprint resident at once. Pass False for
    the strict one-round footprint when a round is sized near HBM.

    ``phase_times``, when a dict is passed, is filled with wall seconds per
    phase — ``stage_s`` (host chunk prep + device_put + async dispatch),
    ``collect_s`` (blocking device wait + host-side run splitting) and
    ``merge_s`` (final per-device merge of the rounds' runs) — the
    per-phase view BASELINE config #2 rehearsals report (with pipelining
    on, stage and collect overlap, so their sum can exceed end-to-end
    wall time).

    Returns ``(per_device_sorted_rows: [D] list of u32[*, 1+P], rounds)``.
    """
    from sparkrdma_tpu.parallel.exchange import record_exchange

    n = mesh.shape[axis_name]
    if len(rows) == 0:
        return [np.zeros((0, rows.shape[1]), rows.dtype)
                for _ in range(n)], 0
    per_round = n * cfg.rows_per_device
    num_rounds = -(-len(rows) // per_round)
    step = make_terasort_step(mesh, axis_name, cfg, impl)
    sharding = NamedSharding(mesh, P(axis_name))
    # Tail-round padding: pad j is addressed to device j % n with that
    # device's range-maximum key, spreading the extra receive load evenly
    # (all-max-key padding would pile onto the last device and overflow its
    # headroom on perfectly valid input). Pads are appended LAST, so the
    # stable sort puts each device's pads at the very end of its run; the
    # strip is an exact per-device row count.
    range_max = np.array([((d + 1) << 32) // n - 1 for d in range(n)],
                         dtype=np.uint32)

    # Tail rounds reuse the SAME full-size step (one compile, static round
    # memory — the function's whole point): the tail is padded up to a full
    # round with the spread pads. With pads spread evenly, a device receives
    # at most ~rows_per_device real rows (uniform keys) + ~rows_per_device
    # pads, which fits the out_factor>=2 receive budget; genuine key skew is
    # caught by the overflow flag like any other round.
    if n > 1 and cfg.out_factor < 2 and len(rows) % per_round:
        raise ValueError("streamed terasort with a partial tail round needs "
                         "out_factor >= 2 (pad headroom)")

    runs: list = [[] for _ in range(n)]
    times = {"stage_s": 0.0, "collect_s": 0.0, "merge_s": 0.0}

    def dispatch(r: int):
        """Stage + launch round r; returns (pads_for, async device results)."""
        t0 = time.perf_counter()
        chunk = rows[r * per_round:(r + 1) * per_round]
        pads_for = np.zeros(n, dtype=np.int64)
        tail_pad = per_round - len(chunk)
        if tail_pad:
            pad = np.zeros((tail_pad, rows.shape[1]), rows.dtype)
            dests = np.arange(tail_pad) % n
            pad[:, 0] = range_max[dests]
            np.add.at(pads_for, dests, 1)
            chunk = np.concatenate([chunk, pad])
        result = pads_for, step(jax.device_put(chunk, sharding))
        record_exchange(len(chunk) - tail_pad)
        times["stage_s"] += time.perf_counter() - t0
        return result

    def collect(pads_for, results):
        t0 = time.perf_counter()
        out, counts, overflowed = results
        if np.asarray(overflowed).any():
            raise OverflowError("streamed round receive overflow; raise "
                                "out_factor or shrink rows_per_device")
        out = np.asarray(out).reshape(n, -1, rows.shape[1])
        counts = np.asarray(counts)
        for d in range(n):
            total = int(counts[d].sum())
            # .copy(): a view would pin the whole padded round buffer on the
            # host across all R rounds (~out_factor x dataset RSS)
            runs[d].append(out[d][:total - int(pads_for[d])].copy())
        times["collect_s"] += time.perf_counter() - t0

    # Double-buffered rounds: round r+1's device work is dispatched (jax
    # dispatch is async) before round r's host-side collection, so staging
    # + host processing overlap the device step — the inter-round pipeline
    # the reference gets from its async fetch window
    # (scala/RdmaShuffleFetcherIterator.scala:264-276).
    if pipeline_rounds:
        pending = None
        for r in range(num_rounds):
            nxt = dispatch(r)
            if pending is not None:
                collect(*pending)
            pending = nxt
        collect(*pending)
    else:
        for r in range(num_rounds):
            collect(*dispatch(r))

    from sparkrdma_tpu.shuffle.external import merge_runs

    t0 = time.perf_counter()
    merged = []
    for d in range(n):
        if not runs[d]:
            merged.append(np.zeros((0, rows.shape[1]), rows.dtype))
            continue
        # R key-sorted runs -> one sorted output in merge_runs' single
        # pass, each row written once (keys are a zero-copy view of
        # column 0; earlier rounds win ties, matching the former stable
        # re-sort's order exactly)
        _, out = merge_runs([(r[:, 0], r) for r in runs[d]])
        merged.append(out)
    times["merge_s"] = time.perf_counter() - t0
    if phase_times is not None:
        phase_times.update(times, rounds=num_rounds)
    return merged, num_rounds


def _row_fingerprints(rows: np.ndarray) -> np.ndarray:
    """One u64 per row: every word times a fixed odd per-column
    multiplier, summed mod 2^64. Two row sets with equal fingerprint
    multisets hold the same whole rows (key AND payload) up to a 2^-64
    collision — chunked so a 1 GiB input never doubles in memory."""
    mult = np.random.default_rng(0x7E5A).integers(
        0, 2**63, size=rows.shape[1], dtype=np.uint64) * 2 + 1
    out = np.empty(len(rows), dtype=np.uint64)
    chunk = 1 << 20
    for lo in range(0, len(rows), chunk):
        out[lo:lo + chunk] = (rows[lo:lo + chunk].astype(np.uint64)
                              * mult).sum(axis=1, dtype=np.uint64)
    return out


def verify_terasort(sorted_rows: np.ndarray, counts: np.ndarray,
                    input_rows: np.ndarray, num_devices: int) -> None:
    """Check the global sort contract against the input: each device
    locally sorted, device ranges ordered, the key multiset preserved,
    and every payload still attached to the key it came with (whole-row
    multiset equality, via ``_row_fingerprints``)."""
    per_dev = sorted_rows.reshape(num_devices, -1, sorted_rows.shape[-1])
    got_keys = []
    got_fps = []
    prev_max = -1
    for d in range(num_devices):
        total = int(counts[d].sum())
        keys = per_dev[d][:total, 0].astype(np.int64)
        if len(keys):
            assert (np.diff(keys) >= 0).all(), f"device {d} not locally sorted"
            assert keys[0] >= prev_max, f"device {d} overlaps previous range"
            prev_max = keys[-1]
        got_keys.append(keys)
        got_fps.append(_row_fingerprints(per_dev[d][:total]))
    got = np.concatenate(got_keys)
    assert len(got) == len(input_rows), "row count mismatch"
    np.testing.assert_array_equal(np.sort(got),
                                  np.sort(input_rows[:, 0].astype(np.int64)))
    assert np.array_equal(np.sort(np.concatenate(got_fps)),
                          np.sort(_row_fingerprints(input_rows))), \
        "payload detached from its key (row multiset differs)"
