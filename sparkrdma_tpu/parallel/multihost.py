"""Multi-host deployment: the exchange over a global (cross-process) mesh.

The reference scales multi-node by giving every executor a verbs endpoint
and letting the NICs carry the M×R traffic (java/RdmaNode.java;
README.md:11-31 — 5-7 worker clusters). The TPU-native equivalent is a
**global ``jax.sharding.Mesh`` spanning hosts**: ``jax.distributed``
bootstraps the process group, XLA routes collectives over ICI within a
slice and DCN between hosts, and the same jitted exchange step from
``parallel.exchange`` runs unchanged — SPMD does not care where shards
live.

Division of labor (mirrors the reference exactly):
* **data plane**: the ragged all-to-all over the global mesh (XLA-routed,
  host CPUs idle — the remote-CPU-bypass invariant);
* **control plane**: ``parallel.endpoints`` hello/announce + driver tables
  over TCP (DCN) — in the reference these are the only two RPCs too.

For the driver's multi-chip dry runs and CI, the same code path is
exercised with multiple *processes of CPU devices* on one machine
(``tests/test_multihost.py`` spawns a 2-process × 4-device cluster) —
the process-boundary behavior (global array assembly, cross-process
collectives) is identical to a real multi-host TPU pod.
"""

from __future__ import annotations

import logging
from typing import Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger(__name__)


def init_multihost(coordinator_address: str, num_processes: int,
                   process_id: int,
                   local_device_count: Optional[int] = None,
                   platform: Optional[str] = None) -> None:
    """Join the distributed runtime. Call before any jax computation.

    On a real TPU pod each process owns its host's chips and
    ``local_device_count``/``platform`` stay None; CI passes
    ``local_device_count=K, platform='cpu'`` to emulate hosts with virtual
    devices.
    """
    import os

    if local_device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{local_device_count}").strip()
    import jax

    if platform is not None:
        jax.config.update("jax_platforms", platform)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(axis_name: str = "shuffle"):
    """One-axis mesh over every device in the cluster."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()), (axis_name,))


def shard_local_rows(mesh, axis_name: str, local_rows: np.ndarray,
                     global_rows: int):
    """Assemble this process's rows into the global sharded array."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis_name))
    return jax.make_array_from_process_local_data(
        sharding, local_rows, (global_rows,) + local_rows.shape[1:])


def run_multihost_mesh_reduce(managers: Sequence, handle, mesh,
                              axis_name: str = "shuffle",
                              impl: str = "auto", out_factor: int = 2,
                              sort_by_key: bool = True,
                              rows_per_round: int = 0):
    """Cross-process mesh reduce: committed spills on N hosts -> ONE
    global-mesh exchange — the reference's whole multi-node pipeline
    (README.md:11-31: map outputs on every node's disks, NICs carry the
    MxR redistribution) with the global collective as the data plane.

    Each process stages the spills its LOCAL executors own according to
    the driver table (so a map recomputed or speculated onto another host
    stages exactly once, table-owner-wins — the same single-owner contract
    the TCP fetch path reads by), assembles the global sharded arrays with
    ``make_array_from_process_local_data``, and the same jitted exchange
    step every other path uses redistributes rows to their partition's
    owner device. SPMD: every process must call this collectively.

    ``managers``: this process's executor-role ``TpuShuffleManager`` s.
    Returns this process's ADDRESSABLE results: a list of
    ``(keys u64[*], payload u8[*, W], partition_ids i64[*])`` per local
    mesh device (remote shards belong to their own processes).

    ``rows_per_round > 0`` bounds DEVICE memory: the exchange runs in R
    rounds of at most ``rows_per_round`` rows per device per round (R is
    agreed group-wide from the same metadata allgather, so every process
    enters the same number of collectives; one compile serves all
    rounds). Host staging is unchanged — what the rounds bound is the
    device-resident working set.
    """
    import jax
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.parallel import exchange as exchange_mod
    from sparkrdma_tpu.parallel.exchange import make_shuffle_exchange
    from sparkrdma_tpu.shuffle.mesh_service import (
        _rows_to_u32,
        _u32_to_rows,
        device_row_words,
    )
    from sparkrdma_tpu.shuffle.writer import decode_rows

    n_global = mesh.devices.size
    local_mesh_devices = [d for d in mesh.devices.flat
                          if d.process_index == jax.process_index()]
    n_local = len(local_mesh_devices)
    if n_local == 0:
        raise ValueError("this process owns no devices of the mesh")
    partitioner = handle.partitioner.build(handle.num_partitions)

    # 1. the driver table names each map's owner slot; stage local ones
    endpoint_mgr = next((m for m in managers if m.executor is not None),
                        None)
    if endpoint_mgr is None:
        # failing BEFORE the collective: a silent StopIteration here would
        # leave every peer hung in the allgather
        raise ValueError("managers must include at least one executor role")
    table = endpoint_mgr.executor.get_driver_table(
        handle.shuffle_id, expect_published=handle.num_maps)
    # exec_index with a wait budget: the hello/announce is async, and a
    # KeyError here would kill this process before the collective and
    # strand every peer in the allgather
    by_slot = {m.executor.exec_index(timeout=5): m for m in managers
               if m.executor is not None and m.resolver is not None}
    all_keys, all_payloads = [], []
    staged = np.zeros(handle.num_maps, dtype=np.int64)
    for m in range(handle.num_maps):
        entry = table.entry(m)
        if entry is None:
            raise RuntimeError(f"map {m} unpublished in driver table")
        owner = by_slot.get(entry[1])
        if owner is None:
            continue  # another process's map (checked globally below)
        from sparkrdma_tpu.utils.integrity import CorruptOutputError
        try:
            raw = owner.resolver.local_blocks(handle.shuffle_id, m, 0,
                                              handle.num_partitions)
        except (CorruptOutputError, OSError) as e:
            # corrupt/unreadable at staging time: same treatment as a
            # disposed output — unstaged, so the consistent completeness
            # check below owns the failure on every process
            raw = None
            log.warning("map %d unreadable at staging time (%s); leaving "
                        "unstaged", m, e)
        if raw is None:
            # disposed mid-staging (dying executor): leave it unstaged —
            # the POST-allgather completeness check raises the retryable
            # FetchFailedError on EVERY process consistently; raising here
            # would strand the peers in the collective
            continue
        k, p = decode_rows(raw, handle.row_payload_bytes)
        staged[m] = 1
        all_keys.append(k)
        all_payloads.append(p)
    keys = (np.concatenate(all_keys) if all_keys
            else np.zeros(0, dtype=np.uint64))
    payload = (np.concatenate(all_payloads) if all_payloads
               else np.zeros((0, handle.row_payload_bytes), dtype=np.uint8))
    rows = _rows_to_u32(keys, payload)
    dest = np.asarray(partitioner(keys), dtype=np.int32) % n_global

    # cross-slice accounting: the per-host seams ARE the topology's DCN
    # links (parallel/topology.py) — tally the bytes this process sends
    # across them so multi-host rounds report cross_slice_bytes the same
    # way the in-process hierarchical exchange does
    from sparkrdma_tpu.parallel import topology as topology_mod

    topo = topology_mod.detect_topology(mesh)
    if not topo.is_flat and len(dest):
        dev_slice = topo.device_slices()
        my_pos = next(i for i, d in enumerate(mesh.devices.flat)
                      if d.process_index == jax.process_index())
        crossing = int((dev_slice[dest] != dev_slice[my_pos]).sum())
        if crossing:
            topology_mod.record_cross_slice(crossing * rows.shape[1] * 4)

    # 2. one tiny host-side allgather carries ALL the cross-host metadata:
    # per-process (row total, mesh-device count) for capacity agreement,
    # plus the staged-map bitmap for global completeness
    meta = multihost_utils.process_allgather(np.concatenate(
        [np.array([len(rows), n_local], dtype=np.int64), staged]))
    meta = meta.reshape(-1, 2 + handle.num_maps)
    # processes may own different device counts: everyone takes the max of
    # per-process ceil(rows_i / n_local_i) so the global shape agrees
    cap = max(1, int(max(-(-int(r) // max(1, int(nl)))
                         for r, nl in meta[:, :2])))
    rounds = 1
    round_order = None
    if rows_per_round > 0 and cap > rows_per_round:
        # bounded device rounds: same derivation on every process from
        # the shared metadata, so the group agrees on R with no extra
        # collective
        rounds = -(-cap // rows_per_round)
        # staged rows are key-sorted per map (the writer's spill order),
        # so CONTIGUOUS slices concentrate each round on few destination
        # devices and overflow the per-round receive budget. Assign each
        # destination's rows evenly across rounds instead — monotone
        # within a destination (round = floor(j*R/m_d)), so per-dest
        # order is preserved — and pad cap by the ±1-per-dest rounding.
        counts_d = np.bincount(dest, minlength=n_global) \
            if len(dest) else np.zeros(n_global, np.int64)
        grouped = np.argsort(dest, kind="stable") if len(dest) else \
            np.zeros(0, np.int64)
        starts = np.r_[0, np.cumsum(counts_d)[:-1]]
        within = (np.arange(len(grouped), dtype=np.int64)
                  - np.repeat(starts, counts_d))
        m_rep = np.repeat(np.maximum(counts_d, 1), counts_d)
        round_of = (within * rounds) // m_rep
        round_order = [grouped[round_of == r] for r in range(rounds)]
        # pad slack for the ±1-per-destination rounding: derived from the
        # ALLGATHERED device counts — every process must compute the same
        # global array shape, and local n_local values differ
        min_nl = max(1, int(meta[:, 1].min()))
        cap = rows_per_round + -(-n_global // min_nl)
    staged_global = meta[:, 2:].sum(axis=0)
    unstaged = np.flatnonzero(staged_global == 0)
    if len(unstaged):
        from sparkrdma_tpu.shuffle.fetcher import FetchFailedError

        m = int(unstaged[0])
        entry = table.entry(m)
        raise FetchFailedError(
            handle.shuffle_id, m, entry[1] if entry else -1,
            "map output staged by no process (owner died, spill disposed "
            "mid-staging, or its managers not passed in) — raised on all "
            "processes; recompute and re-enter collectively")

    width = device_row_words(handle.row_payload_bytes)
    sharding = NamedSharding(mesh, P(axis_name))
    # 3. the shared jitted exchange over the GLOBAL mesh — one compile
    # serves every round (shapes are identical by construction)
    exchange = make_shuffle_exchange(mesh, axis_name, impl=impl,
                                     out_factor=out_factor)
    per_round = n_local * cap
    got_rows: list = [[] for _ in range(n_local)]
    for r in range(rounds):
        if round_order is not None:
            idx = round_order[r]
            if len(idx) > per_round:  # ±1-per-dest rounding blew the pad
                raise OverflowError(
                    f"round {r} holds {len(idx)} rows > send budget "
                    f"{per_round}; raise rows_per_round")
            chunk, cdest = rows[idx], dest[idx]
        else:
            chunk = rows[r * per_round:(r + 1) * per_round]
            cdest = dest[r * per_round:(r + 1) * per_round]
        rows_p = np.zeros((per_round, width), dtype=np.uint32)
        rows_p[:len(chunk)] = chunk
        dest_p = np.full(per_round, -1, dtype=np.int32)
        dest_p[:len(chunk)] = cdest
        rows_g = jax.make_array_from_process_local_data(
            sharding, rows_p, (n_global * cap, width))
        dest_g = jax.make_array_from_process_local_data(
            sharding, dest_p, (n_global * cap,))
        received, counts, _, overflowed = jax.block_until_ready(
            exchange(rows_g, dest_g))
        recv_by_dev = {s.device: np.asarray(s.data)
                       for s in received.addressable_shards}
        counts_by_dev = {s.device: np.asarray(s.data)
                         for s in counts.addressable_shards}
        of_by_dev = {s.device: np.asarray(s.data)
                     for s in overflowed.addressable_shards}
        for i, dev in enumerate(local_mesh_devices):
            got = recv_by_dev[dev].reshape(-1, width)
            cnt = counts_by_dev[dev].reshape(-1)
            total = int(cnt.sum())
            if of_by_dev[dev].any():
                raise OverflowError(
                    "multihost mesh reduce receive overflow; raise "
                    "out_factor or lower rows_per_round skew exposure")
            got_rows[i].append(got[:total].copy())
    exchange_mod.record_exchange(int(meta[:, 0].sum()))

    # 4. assemble this process's addressable results across rounds
    results = []
    for segs in got_rows:
        allrows = (np.concatenate(segs) if segs
                   else np.zeros((0, width), np.uint32))
        k, p = _u32_to_rows(allrows, handle.row_payload_bytes)
        parts = np.asarray(partitioner(k), dtype=np.int64)
        if sort_by_key:
            order = np.argsort(k, kind="stable")
            k, p, parts = k[order], p[order], parts[order]
        results.append((k, p, parts))
    return results


def run_multihost_terasort(mesh, axis_name: str, rows_per_device: int,
                           payload_words: int = 4, seed: int = 0,
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """One TeraSort round over the global mesh; returns this process's
    local sorted shards + counts (addressable output only — remote shards
    belong to other processes)."""
    import jax

    from sparkrdma_tpu.models.terasort import TeraSortConfig, generate_rows, make_terasort_step

    n_global = mesh.devices.size
    n_local = len(jax.local_devices())
    process_id = jax.process_index()
    cfg = TeraSortConfig(rows_per_device=rows_per_device,
                         payload_words=payload_words, out_factor=2)
    # each process generates ONLY its slice (O(local) memory/time) with a
    # process-disjoint deterministic seed
    local_slice = generate_rows(cfg, n_local,
                                seed=seed * 100_003 + process_id)
    rows_global = shard_local_rows(mesh, axis_name, local_slice,
                                   n_global * rows_per_device)
    step = make_terasort_step(mesh, axis_name, cfg)
    out, counts, overflowed = jax.block_until_ready(step(rows_global))
    local_out = np.concatenate(
        [np.asarray(s.data) for s in out.addressable_shards])
    local_counts = np.concatenate(
        [np.asarray(s.data) for s in counts.addressable_shards])
    if any(bool(np.asarray(s.data).any()) for s in overflowed.addressable_shards):
        raise OverflowError("terasort receive overflow on this host")
    return local_out, local_counts
