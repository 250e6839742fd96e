"""Mesh shuffle service: the bridge from the engine-facing API to the ICI
data plane.

This closes the loop the reference closes with its NIC: committed map
outputs (host spill files, ``shuffle/resolver.py``) are staged into device
HBM, the device plane's fused step (``parallel/device_plane.py``) groups
every row by its reduce partition's owner device, moves it there in one
ragged all-to-all and key-sorts it where it lands, and the sorted rows
come back once per round. The host's only data-plane job is streaming
sequential spill bytes up — the per-(map, reduce) scatter the reference
does with one-sided READs (scala/RdmaShuffleFetcherIterator.scala:119-180)
happens **on the mesh**, where it is a collective.

There is one reduce driver per plan kind, and the engine picks by the
plan ``device_plane.select_dataplane`` returns: ``run_mesh_reduce_fused``
for flat plans (one shot, or rounds sized from ``device_hbm_budget`` with
spills streamed into round blocks), ``run_mesh_reduce_hier`` for
multi-slice plans.

Partition → device placement on flat plans: partition ``p`` is owned by
device ``p % D`` (the same modulo placement the driver-table scheme uses
for executors).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from sparkrdma_tpu.shuffle.fetcher import ReadMetrics
from sparkrdma_tpu.shuffle.manager import ShuffleHandle, TpuShuffleManager
from sparkrdma_tpu.utils import trace as trace_mod


def device_row_words(payload_bytes: int) -> int:
    """u32 words per device row for a given payload width: key lo, key
    hi, then the padded payload words — THE row-layout formula, shared
    by the packers, the streamed reducers, and the engine's cost model
    (a layout change must move them all together)."""
    return 2 + (payload_bytes + 3) // 4


def _rows_to_u32(keys: np.ndarray, payload: np.ndarray) -> np.ndarray:
    """Pack (u64 keys, u8 payload) into the device row format:
    ``u32[N, 2 + ceil(W/4)]`` = key lo, key hi, payload words."""
    n = len(keys)
    pw = (payload.shape[1] + 3) // 4
    rows = np.zeros((n, 2 + pw), dtype=np.uint32)
    # ascontiguousarray: decode_rows hands out zero-copy strided key views
    # (free when already contiguous, which concatenated batches are)
    rows[:, :2] = np.ascontiguousarray(keys).view(np.uint32).reshape(n, 2)
    if payload.shape[1]:
        padded = np.zeros((n, pw * 4), dtype=np.uint8)
        padded[:, :payload.shape[1]] = payload
        rows[:, 2:] = padded.view(np.uint32).reshape(n, pw)
    return rows


def _u32_to_rows(rows: np.ndarray, payload_bytes: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    if len(rows) == 0:
        return (np.zeros(0, dtype=np.uint64),
                np.zeros((0, payload_bytes), dtype=np.uint8))
    keys = rows[:, :2].copy().view(np.uint64).reshape(-1)
    payload = rows[:, 2:].copy().view(np.uint8).reshape(
        len(rows), -1)[:, :payload_bytes]
    return keys, payload


def run_mesh_reduce_fused(managers: Sequence[TpuShuffleManager],
                          handle: ShuffleHandle, mesh,
                          axis_name: str = "shuffle", impl: str = "auto",
                          rows_per_round: int = 0, out_factor: int = 2,
                          expect_maps: Optional[int] = None,
                          tracer=None,
                          ) -> List[Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]]:
    """Reduce every partition of ``handle`` on the mesh through the
    fused device plane: one ``shard_map``-fused partition+exchange+
    local-sort step per round (``parallel.device_plane``), so between a
    round's staging upload and its result download partitions never
    leave HBM, and rounds are double-buffered (round k+1's collective
    dispatches while round k's on-device sort runs;
    ``exchange.round``/``exchange.overlap`` trace the overlap).

    ``managers``: the executor managers whose resolvers hold the
    committed map outputs (single-host deployment: one process, many
    executor roles, one mesh).

    ``rows_per_round`` bounds each round's per-device rows (0 = one
    shot) — the engine takes it from the plan, which sizes it from the
    HBM byte budget (``device_plane.auto_rows_per_round``). With rounds
    bounded, host staging is bounded too: spills stream straight into
    round blocks (one round resident, plus the in-flight one).

    ``out_factor``: receive headroom per device relative to the balanced
    share. Raises ``OverflowError`` when skew beats it; the engine
    degrades exactly this stage to the host dataplane.

    Returns, per device ``d``: ``(keys u64[*], payload u8[*, W],
    partition_ids i64[*])`` for the partitions ``{p : p % D == d}``,
    rows key-sorted within the device.
    """
    from sparkrdma_tpu.parallel.device_plane import (
        run_fused_exchange,
        run_fused_exchange_rounds,
    )

    tracer = tracer if tracer is not None else trace_mod.NULL
    n_dev = mesh.shape[axis_name]
    partitioner = handle.partitioner.build(handle.num_partitions)
    pw = device_row_words(handle.row_payload_bytes)

    if rows_per_round > 0:
        def round_block(pieces_r, pieces_d):
            with tracer.span("exchange.stage_cut", "exchange",
                             rows=sum(map(len, pieces_r))) as args:
                block = np.concatenate(pieces_r), np.concatenate(pieces_d)
                args["bytes"] = block[0].nbytes + block[1].nbytes
            return block

        # bounded rounds: stream spills straight into round blocks
        def round_blocks():
            pending_r: List[np.ndarray] = []
            pending_d: List[np.ndarray] = []
            pending = 0
            per_round = rows_per_round * n_dev
            delivered: set = set()
            for k, p in _read_batches(tracer, _iter_committed_batches(
                    managers, handle, delivered)):
                rows = _pack_batch(tracer, k, p)
                with tracer.span("exchange.stage_route", "exchange",
                                 rows=len(k)):
                    dest = (np.asarray(partitioner(k), dtype=np.int32)
                            % n_dev)
                while len(rows):
                    take = min(len(rows), per_round - pending)
                    pending_r.append(rows[:take])
                    pending_d.append(dest[:take])
                    pending += take
                    rows, dest = rows[take:], dest[take:]
                    if pending == per_round:
                        yield round_block(pending_r, pending_d)
                        pending_r, pending_d, pending = [], [], 0
            _check_staging_complete(delivered, expect_maps,
                                    handle.shuffle_id)
            if pending:
                yield round_block(pending_r, pending_d)

        per_device, _rounds = run_fused_exchange_rounds(
            mesh, axis_name, round_blocks(), pw, rows_per_round,
            key_words=2, out_factor=out_factor, impl=impl, tracer=tracer)
    else:
        # one shot: the cost model only picks this when the stage fits
        # the budget, so whole-stage staging is within contract. The
        # staging is here; the driver's own exchange.stage spans only
        # slice what this one made
        with tracer.span("exchange.stage", "exchange", round=0) as args:
            keys, payload = _stage_all(managers, handle, expect_maps,
                                       tracer)
            rows = _pack_batch(tracer, keys, payload)
            with tracer.span("exchange.stage_route", "exchange",
                             rows=len(keys)):
                dest = (np.asarray(partitioner(keys), dtype=np.int32)
                        % n_dev)
            args["rows"] = len(rows)
            args["bytes"] = rows.nbytes + dest.nbytes
        per_device, _rounds = run_fused_exchange(
            mesh, axis_name, rows, dest, key_words=2,
            out_factor=out_factor, impl=impl, tracer=tracer)
    return _unpack_devices(per_device, handle, partitioner, tracer)


def run_mesh_reduce_hier(managers: Sequence[TpuShuffleManager],
                         handle: ShuffleHandle, mesh, topology,
                         axis_name: str = "shuffle", impl: str = "auto",
                         rows_per_round: int = 0, out_factor: int = 2,
                         expect_maps: Optional[int] = None, tracer=None,
                         partition_map: Optional[np.ndarray] = None,
                         ) -> List[Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]]:
    """``run_mesh_reduce_fused`` over a MULTI-SLICE topology: the fused
    ICI step runs per slice over its sub-mesh (the bulk bytes), and only
    the slice-crossing residue rides the host/DCN channel, composed as
    the factored two-phase redistribution
    (``device_plane.run_hierarchical_exchange``).

    Each staged batch's HOME slice is its staging manager's slot mapped
    through ``Topology.slice_of_slot`` (co-hosted executors and their
    slice's devices agree on a home — the same contiguous-range
    convention the shard map uses). ``partition_map`` is the
    link-cost-aware partition->device layout (``i32[P]``); None derives
    the slice-aligned map from the staged per-slice byte histogram
    (``planner.slice_aligned_partition_map``) so cross-slice bytes are
    minimized by construction, in place of the flat reduce's ``p % D``
    placement. Same result contract as ``run_mesh_reduce_fused``
    (per-device key-sorted rows; a different partition layout only moves
    WHICH device serves a partition, never its bytes).

    Staging is WHOLE-STAGE (the one-shot fused path's contract): the
    cost model only emits a hierarchical plan when the stage fits the
    one-shot budget, so host staging stays within the same bound —
    chunked-size stages keep the flat device plan's streamed rounds.
    ``rows_per_round`` still bounds the per-slice DEVICE rounds.
    """
    from sparkrdma_tpu.parallel.device_plane import (
        run_hierarchical_exchange,
    )
    from sparkrdma_tpu.shuffle.planner import slice_aligned_partition_map

    tracer = tracer if tracer is not None else trace_mod.NULL
    n_dev = mesh.shape[axis_name]
    partitioner = handle.partitioner.build(handle.num_partitions)
    row_bytes = 4 * device_row_words(handle.row_payload_bytes)
    num_mgrs = max(1, len(managers))

    with tracer.span("exchange.stage", "exchange", round=0) as args:
        all_rows, all_parts, all_home = [], [], []
        part_bytes = np.zeros((topology.num_slices, handle.num_partitions),
                              dtype=np.int64)
        delivered: set = set()
        for i, k, p in _read_batches(tracer, _iter_committed_batches_indexed(
                managers, handle, delivered)):
            home = topology.slice_of_slot(i, num_mgrs)
            with tracer.span("exchange.stage_route", "exchange",
                             rows=len(k)):
                parts = np.asarray(partitioner(k), dtype=np.int64)
            np.add.at(part_bytes[home], parts, row_bytes)
            all_rows.append(_pack_batch(tracer, k, p))
            all_parts.append(parts)
            all_home.append(np.full(len(k), home, dtype=np.int32))
        _check_staging_complete(delivered, expect_maps, handle.shuffle_id)
        if not all_rows:
            rows = np.zeros(
                (0, device_row_words(handle.row_payload_bytes)), np.uint32)
            parts = np.zeros(0, np.int64)
            home = np.zeros(0, np.int32)
        else:
            rows = np.concatenate(all_rows)
            parts = np.concatenate(all_parts)
            home = np.concatenate(all_home)

        if partition_map is None:
            partition_map = slice_aligned_partition_map(part_bytes,
                                                        topology, n_dev)
        dest = partition_map[parts].astype(np.int32) if len(parts) else \
            np.zeros(0, np.int32)
        args["rows"] = len(rows)
        args["bytes"] = rows.nbytes + dest.nbytes

    per_device, _rounds = run_hierarchical_exchange(
        mesh, axis_name, topology, rows, dest, home, key_words=2,
        rows_per_round=rows_per_round, out_factor=out_factor, impl=impl,
        tracer=tracer)

    return _unpack_devices(per_device, handle, partitioner, tracer)


def _unpack_devices(per_device, handle, partitioner, tracer):
    """Per-device sorted u32 rows -> ``(keys, payload, partition ids)``
    per device: the unpacking and the partitioner's second pass over
    every key (rows arrive key-sorted per device already)."""
    with tracer.span("exchange.unpack", "exchange",
                     rows=sum(len(r) for r in per_device)):
        results = []
        for rows in per_device:
            k, p = _u32_to_rows(rows, handle.row_payload_bytes)
            results.append(
                (k, p, np.asarray(partitioner(k), dtype=np.int64)))
    return results


def _read_batches(tracer, batches):
    """``batches`` (``_iter_committed_batches*``), each pull from it under
    an ``exchange.stage_read`` span: the resolver's read of one committed
    spill and its decode, with the ``rows`` and ``bytes`` it gave. The
    last pull, which finds the end, records ``rows=0``."""
    while True:
        with tracer.span("exchange.stage_read", "exchange", rows=0,
                         bytes=0) as args:
            batch = next(batches, None)
            if batch is not None:
                *_, keys, payload = batch
                args.update(rows=len(keys),
                            bytes=keys.nbytes + payload.nbytes)
        if batch is None:
            return
        yield batch


def _pack_batch(tracer, keys: np.ndarray, payload: np.ndarray) -> np.ndarray:
    """``_rows_to_u32`` under an ``exchange.stage_pack`` span."""
    with tracer.span("exchange.stage_pack", "exchange",
                     rows=len(keys)) as args:
        rows = _rows_to_u32(keys, payload)
        args["bytes"] = rows.nbytes
    return rows


def _stage_all(managers, handle, expect_maps: Optional[int], tracer
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Stage every committed local spill into one (keys, payload) pair:
    streamed sequentially (no host scatter) through the resolver's
    locked serving API (safe vs. concurrent re-commit/unregister
    disposal), with the completeness check. The one-shot reduce's
    staging; bounded rounds stream instead."""
    all_keys, all_payloads = [], []
    delivered: set = set()
    for k, p in _read_batches(tracer, _iter_committed_batches(
            managers, handle, delivered)):
        all_keys.append(k)
        all_payloads.append(p)
    _check_staging_complete(delivered, expect_maps, handle.shuffle_id)
    keys = (np.concatenate(all_keys) if all_keys
            else np.zeros(0, dtype=np.uint64))
    payload = (np.concatenate(all_payloads) if all_payloads
               else np.zeros((0, handle.row_payload_bytes), dtype=np.uint8))
    return keys, payload


def _iter_committed_batches(managers, handle, delivered: Optional[set] = None):
    """Decoded (keys, payload) batches of every committed local spill —
    ``_iter_committed_batches_indexed`` minus the staging-manager index
    (the flat reduce doesn't care which executor held a map; the
    hierarchical reduce does — the index names the home slice)."""
    for _, k, p in _iter_committed_batches_indexed(managers, handle,
                                                   delivered):
        yield k, p


def _iter_committed_batches_indexed(managers, handle,
                                    delivered: Optional[set] = None):
    """Decoded (manager_index, keys, payload) batches of every committed
    local spill — THE staging hook: both mesh reduce drivers (fused,
    hierarchical) stage through this one generator, so a shim or chaos
    injection wrapped around it covers them both.

    Each map id is taken from the FIRST resolver holding it: stage retry
    and speculation can leave identical copies of one map output on two
    live executors (deterministic tasks, idempotent positional publishes —
    the same invariant the driver table's overwrite relies on), and a
    reduce must consume exactly one. ``delivered`` (when given) records
    the map ids actually read, so callers can detect outputs disposed
    mid-staging instead of silently reducing a partial dataset.
    """
    from sparkrdma_tpu.shuffle.writer import decode_rows

    seen: set = set()
    for i, mgr in enumerate(managers):
        if mgr.resolver is None:
            continue
        for m in mgr.resolver.map_ids(handle.shuffle_id):
            if m in seen:
                continue
            from sparkrdma_tpu.utils.integrity import CorruptOutputError
            try:
                raw = mgr.resolver.local_blocks(handle.shuffle_id, m, 0,
                                                handle.num_partitions)
            except (CorruptOutputError, OSError):
                raw = None  # corrupt/unreadable: same as disposed below
            if raw is None:
                continue  # disposed between map_ids() and the read;
                # another manager may still hold a copy — completeness is
                # the caller's expect_maps check
            seen.add(m)
            if delivered is not None:
                delivered.add(m)
            yield (i,) + decode_rows(raw, handle.row_payload_bytes)


def _check_staging_complete(delivered: set, expect_maps: Optional[int],
                            shuffle_id: int) -> None:
    """Raise FetchFailedError for the first map output that went missing
    during staging (disposed under a dying executor) — the mesh-mode
    analogue of a failed remote fetch; the engine's stage retry recomputes
    it (scala/RdmaShuffleFetcherIterator.scala:376-381)."""
    if expect_maps is None:
        return
    missing = sorted(set(range(expect_maps)) - delivered)
    if missing:
        from sparkrdma_tpu.shuffle.fetcher import FetchFailedError

        raise FetchFailedError(
            shuffle_id, missing[0], -1,
            "map output disposed during mesh staging")


def split_by_partition(results, num_partitions: int, row_payload_bytes: int
                       ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Re-index a mesh reduce's per-DEVICE results as per-PARTITION
    ``(keys, payload)`` — the unit the engine's reduce tasks consume
    (task ``t`` reads partition ``t``). Within-partition key order is
    preserved from the device results (sorted when the reduce sorted)."""
    per: List[Optional[Tuple[np.ndarray, np.ndarray]]] = [None] * num_partitions
    for k, p, parts in results:
        for pid in np.unique(parts):
            m = parts == pid
            per[int(pid)] = (k[m], p[m])
    empty = (np.zeros(0, dtype=np.uint64),
             np.zeros((0, row_payload_bytes), dtype=np.uint8))
    return [e if e is not None else empty for e in per]


class CachedPartitionReader:
    """Reader over a partition range served from mesh-reduce results.

    This is what the engine hands a task in mesh mode: the same surface as
    ``TpuShuffleReader`` (``read`` yields batches; ``read_all`` /
    ``read_sorted`` / ``read_sorted_spilled``; ``metrics``), but every byte
    arrived over the ICI collective — the ``metrics`` show local serving
    only, never remote fetches. Mirrors the reference property that the
    engine-facing reader IS the accelerated path
    (scala/RdmaShuffleManager.scala:234-261).
    """

    def __init__(self, per_partition: Sequence[Tuple[np.ndarray, np.ndarray]],
                 start_partition: int, end_partition: int,
                 row_payload_bytes: int):
        self._parts = per_partition
        self._range = range(start_partition, end_partition)
        self.row_payload_bytes = row_payload_bytes
        self.metrics = ReadMetrics()

    def read(self):
        for p in self._range:
            keys, payload = self._parts[p]
            if len(keys):
                self.metrics.record_local(
                    len(keys) * (8 + self.row_payload_bytes))
                yield keys, payload

    def read_all(self) -> Tuple[np.ndarray, np.ndarray]:
        ks, ps = [], []
        for k, p in self.read():
            ks.append(k)
            ps.append(p)
        if not ks:
            return (np.zeros(0, dtype=np.uint64),
                    np.zeros((0, self.row_payload_bytes), dtype=np.uint8))
        return np.concatenate(ks), np.concatenate(ps)

    def read_sorted(self) -> Tuple[np.ndarray, np.ndarray]:
        keys, payload = self.read_all()
        order = np.argsort(keys, kind="stable")
        return keys[order], payload[order]

    def read_aggregated(self, combine) -> Tuple[np.ndarray, np.ndarray]:
        """Vectorized sorted-run reduction (TpuShuffleReader parity).
        Combiners never see zero rows — the writer-side contract
        (shuffle/writer.py skips empty inputs) holds on the read side."""
        keys, payload = self.read_sorted()
        if not len(keys):
            return keys, payload
        return combine(keys, payload)

    def read_sorted_spilled(self, memory_budget_bytes: int = 64 << 20,
                            spill_dir: Optional[str] = None):
        # data is already resident (mesh results live on the driver); the
        # bounded-memory contract is about FETCH buffering, which the
        # collective already did — serve the sorted view in one batch
        keys, payload = self.read_sorted()
        if len(keys):
            yield keys, payload
