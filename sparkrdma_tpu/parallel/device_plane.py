"""The device dataplane: which plane carries a stage, the fused step,
and the drivers that feed it rounds.

A shuffle stage rides one of two planes. The HOST plane is the writer ->
resolver -> fetcher path over the control plane (`shuffle/fetcher.py`);
it carries any stage. The DEVICE plane is this module: rows are staged
into HBM, grouped by destination device, exchanged over ICI and
key-sorted where they land, so partitions never leave HBM between the
map output and the sorted reduce input.

* ``select_dataplane`` is the per-stage cost model. The engine asks it
  for an ``ExchangePlan`` (plane, transport, round size, and the reason,
  which the ``exchange.select`` trace instant carries) from what it can
  observe: whether a mesh is configured, whether the stage's inputs are
  resident to this process, the stage's bytes against
  ``device_hbm_budget``, and the mesh's slice topology.
* ``make_fused_step`` builds the ``shard_map``-fused partition +
  exchange + local-sort program: one pass, no materialized
  intermediates (the redistribution-plan recipe of "Memory-efficient
  array redistribution through portable collective communication",
  PAPERS.md). The local sort is one key sort of ``(keys..., iota)``
  whose order the rows then follow (``ops/row_permute.py``); the
  transport is `parallel/exchange.py`'s.
* ``run_fused_exchange_rounds`` is the host driver: rounds sized by
  ``auto_rows_per_round`` from the HBM byte budget, double-buffered so
  round ``k+1``'s collective is dispatched while round ``k``'s device
  sort runs and its results drain (``exchange.round`` spans and
  ``exchange.overlap`` instants show the overlap in the trace).

Overflow (per-pair skew past the dense slot, or a receive past the
capacity headroom) raises ``OverflowError``; the engine degrades exactly
the overflowing stage to the host plane instead of failing the job
(`engine.py` catches it and re-serves the stage through the fetcher).

On a multi-slice topology (``parallel/topology.py``) there is a third
plan kind, **hierarchical**: the fused step runs per slice over its
sub-mesh (bulk bytes stay on ICI), and only the slice-crossing residue
moves over the host/DCN channel, re-homed into its destination slice's
next round (local regroup -> cross-slice move -> local regroup, the
factored redistribution of the same paper).
``run_hierarchical_exchange`` drives it. ``select_dataplane`` scores it
against the flat plan by the two-level link cost
``intra_bytes/ici_bw + inter_bytes/dcn_bw``; a single-slice topology
gives the flat selector's answer bit for bit. One slice's overflow (or
a collective failure under a lost device) degrades only that slice's
residue to host-side serving, byte-identically; the other slices stay
on ICI.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from sparkrdma_tpu.parallel import topology as topology_mod
from sparkrdma_tpu.utils import trace as trace_mod

DEVICE_PLANE = "device"
HOST_PLANE = "host"
HIERARCHICAL_PLANE = "hierarchical"


def stage_to_device(arr: np.ndarray, sharding):
    """One staged round's host->device upload, donation-friendly: the
    host staging buffer — a BufferPool lease the native fetch engine
    already landed wire bytes in, or the round's freshly-padded block,
    never touched again after dispatch — may back the device array
    directly instead of being copied. Backends that can't alias transfer
    exactly as before; results are identical either way."""
    import jax

    return jax.device_put(arr, sharding, may_alias=True)


# conservative per-device HBM footprint of one fused round, in row
# multiples: the input buffer + its destination-grouped copy (2 x cap)
# plus the receive buffer + its sorted copy (2 x out_factor x cap). The
# cost model sizes rounds so this fits the configured budget.
def _footprint_rows(row_bytes: int, out_factor: int) -> int:
    return row_bytes * (2 + 2 * out_factor)


@dataclass(frozen=True)
class StageProfile:
    """What the cost model knows about one stage's exchange.

    ``est_bytes``: committed map-output bytes across the stage (the
    driver/resolvers know this exactly at stage boundary — the same
    size column the adaptive planner consumes). ``row_bytes``: the
    device row stride. ``resident``: whether the stage's inputs can be
    staged straight into this process's HBM (in-process executors; a
    remote-only stage can't ride the local mesh). ``out_factor``:
    receive headroom the runner will allocate.

    ``intra_bytes`` / ``inter_bytes`` decompose ``est_bytes`` BY LINK
    for multi-slice topologies: bytes whose destination stays in the
    producing map's home slice vs. bytes that must cross the DCN seam.
    ``-1`` = unknown — the cost model falls back to the topology's
    uniform-destination estimate; flat topologies never look at them.
    """

    est_bytes: int
    row_bytes: int
    resident: bool = True
    out_factor: int = 2
    intra_bytes: int = -1
    inter_bytes: int = -1


@dataclass(frozen=True)
class ExchangePlan:
    """One stage's dataplane decision: which plane, which transport,
    and (device plane) the auto-sized round bound. ``rows_per_round``
    0 = one shot; ``reason`` is the cost model's audit trail (surfaced
    on the ``exchange.select`` trace instant). ``topology`` rides along
    on HIERARCHICAL plans — the runner needs the slice bounds the plan
    was scored against (None on flat plans); hierarchical ``impl`` is
    the RAW transport ask (``"auto"`` re-probes per sub-mesh — the
    opcode a cross-slice mesh rejects may compile per slice)."""

    plane: str
    impl: str = ""
    rows_per_round: int = 0
    reason: str = ""
    topology: Optional[topology_mod.Topology] = None


def auto_rows_per_round(row_bytes: int, hbm_budget: int,
                        out_factor: int = 2) -> int:
    """Rows per device per fused round that keep the round's footprint
    (input + grouped copy + receive + sorted copy) inside
    ``hbm_budget``."""
    return max(0, int(hbm_budget) // _footprint_rows(max(1, row_bytes),
                                                     max(1, out_factor)))


_NO_ROW_FITS = "device_hbm_budget below one row a device"


def _device_plan(mesh, axis_name: str, profile: StageProfile, impl: str,
                 hbm_budget: int) -> Tuple[Optional[ExchangePlan], str]:
    """The flat device plan for one stage, or ``None`` and why the
    device plane cannot carry it: no mesh, inputs not resident to this
    process, or a budget that cannot hold one row a device."""
    from sparkrdma_tpu.parallel.exchange import resolve_transport

    if mesh is None:
        return None, "no mesh configured"
    if not profile.resident:
        return None, "stage inputs not resident to this process"
    resolved = resolve_transport(mesh, impl, axis_name)
    rows_cap = auto_rows_per_round(profile.row_bytes, hbm_budget,
                                   profile.out_factor)
    if rows_cap < 1:
        return None, _NO_ROW_FITS
    n = mesh.shape[axis_name]
    per_dev_rows = -(-max(0, profile.est_bytes)
                     // max(1, profile.row_bytes) // n) or 1
    if per_dev_rows <= rows_cap:
        return ExchangePlan(
            DEVICE_PLANE, resolved, 0,
            f"fits budget one-shot ({per_dev_rows} rows/dev <= "
            f"{rows_cap} cap)"), ""
    return ExchangePlan(
        DEVICE_PLANE, resolved, rows_cap,
        f"chunked: {per_dev_rows} rows/dev over {rows_cap}-row "
        "budget rounds"), ""


def select_dataplane(mesh, axis_name: str, profile: StageProfile, *,
                     impl: str = "auto", hbm_budget: int = 64 << 20,
                     override: str = "auto",
                     topology: Optional[topology_mod.Topology] = None,
                     ) -> ExchangePlan:
    """The per-stage cost model: device plane when the stage is mesh-
    resident and the HBM budget holds at least one row a device (one
    shot when the stage fits the budget, budget-sized rounds when it
    does not), host plane otherwise: the host plane (writer -> resolver
    -> fetcher, served through the ordinary ``getReader`` path with its
    retry/CRC machinery) carries any stage. ``override`` short-circuits:
    ``"device"`` / ``"host"`` force a plane; ``"auto"`` asks the cost
    model.

    ``topology``: the mesh's two-level description. On a MULTI-slice
    topology a stage that would ride the device plane is scored by the
    two-level link cost instead of a residency boolean: the flat
    collective routes EVERY byte through the DCN-priced inter-slice
    fabric (a cross-slice all-to-all is lock-stepped on its slowest
    links, and the native ragged opcode doesn't span slices at all),
    while the hierarchical plan keeps the intra-slice bulk on ICI and
    pays DCN only for the slice-crossing residue —
    ``intra/ici_bw + inter/dcn_bw``. None or a single-slice topology
    reproduces the flat selector bit-for-bit."""
    if override not in ("auto", DEVICE_PLANE, HOST_PLANE):
        # a typo'd escape hatch must not silently ride the cost model
        raise ValueError(f"unknown dataplane override {override!r} "
                         "(expected 'auto', 'device' or 'host')")
    if override == HOST_PLANE:
        return ExchangePlan(HOST_PLANE, "", 0, "forced by override")
    dev, why = _device_plan(mesh, axis_name, profile, impl, hbm_budget)
    if override == DEVICE_PLANE:
        if dev is not None:
            return dev
        if why != _NO_ROW_FITS:
            # forcing a plane that cannot carry the stage (no mesh,
            # non-resident inputs) is a caller error — silently running
            # host under a "device" ask would be worse
            raise ValueError(f"dataplane override 'device': {why}")
        # the budget can't hold a row: run minimum rounds rather than
        # silently switching planes under an explicit ask
        from sparkrdma_tpu.parallel.exchange import resolve_transport

        return ExchangePlan(
            DEVICE_PLANE, resolve_transport(mesh, impl, axis_name), 1,
            "forced by override (budget below one row)")
    if dev is None:
        return ExchangePlan(HOST_PLANE, "", 0, "host dataplane")
    if (topology is not None and not topology.is_flat
            and dev.rows_per_round == 0):
        # one-shot plans only: the hierarchical runner stages the whole
        # stage host-side before factoring it (the same whole-stage
        # contract the one-shot fused path has); a CHUNKED plan means
        # the stage outgrew that contract, and the flat chunked device
        # plan keeps its streamed bounded-staging discipline
        est = max(0, profile.est_bytes)
        intra, inter = profile.intra_bytes, profile.inter_bytes
        if intra < 0 or inter < 0:
            # no per-link byte decomposition published for this stage:
            # fall back to the uniform-destination estimate
            inter = int(est * topology.uniform_inter_fraction())
            intra = est - inter
        hier_s = topology.link_seconds(intra, inter)
        flat_s = topology.link_seconds(0, intra + inter)
        if hier_s < flat_s:
            # the plan carries the RAW transport ask, not the global
            # mesh's resolution: the native ragged opcode that a
            # cross-slice mesh rejects may well compile on each
            # single-slice sub-mesh, so "auto" must re-probe per
            # sub-mesh inside the runner (make_fused_step)
            return ExchangePlan(
                HIERARCHICAL_PLANE, impl, 0,
                f"two-level: {topology.num_slices} slices, "
                f"{intra >> 20}MiB intra@{topology.ici_gbps:g}GB/s + "
                f"{inter >> 20}MiB inter@{topology.dcn_gbps:g}GB/s = "
                f"{hier_s:.4f}s vs flat {flat_s:.4f}s",
                topology=topology)
    return dev


# ---------------------------------------------------------------------------
# the fused step: partition + exchange + local sort, one shard_map program
# ---------------------------------------------------------------------------

def _local_sort(rows, keys, write_back_keys: bool, move):
    """The local sort of full rows by (pre-masked) keys: one key sort of
    ``(keys..., iota)``, then the rows follow the order through
    ``move(rows, order)``: ``ops.row_permute.permute_rows`` bound to the
    platform the step compiles for, which picks its data path from that
    and the shape.

    ``keys`` is a TUPLE of u32 key vectors, most significant first —
    one entry for single-word keys (TeraSort), two for the u64 packed
    ``[lo, hi]`` row layout the mesh shuffle service moves (x64 is
    disabled in this runtime, so multi-word keys sort as multiple u32
    operands instead of one u64). ``write_back_keys`` overwrites
    column 0 with the sorted key (single-word layouts only — padding
    rows get their sentinel visible in the key column, the terasort
    contract)."""
    import jax
    import jax.numpy as jnp

    def written_back(sorted_rows, sorted_keys):
        # the key column already equals sorted_keys for valid rows;
        # only padding rows (sentinel keys) need the overwrite
        if write_back_keys:
            return sorted_rows.at[:, 0].set(sorted_keys)
        return sorted_rows

    # the leaf scopes name the kernels in a device profile, whatever XLA
    # calls its fusions: ``key_sort`` is the sort proper, ``row_gather``
    # the row move
    with jax.named_scope("key_sort"):
        iota = jnp.arange(rows.shape[0], dtype=jnp.int32)
        # iota as a FINAL KEY makes the order total: duplicate keys
        # order by original position with no reliance on sort
        # stability (a value-operand iota under an unstable sort
        # could permute ties arbitrarily)
        out = jax.lax.sort(keys + (iota,), num_keys=len(keys) + 1)
        sorted_keys, order = out[0], out[-1]
    with jax.named_scope("row_gather"):
        sorted_rows = written_back(move(rows, order), sorted_keys)
    return sorted_rows, sorted_keys


def _row_keys(rows, key_words: int):
    """The per-row sort key vectors, most significant first: column 0
    for single-word u32 keys, ``(hi=col 1, lo=col 0)`` for the
    little-endian packed u64 layout ``shuffle/mesh_service.
    _rows_to_u32`` produces."""
    if key_words == 1:
        return (rows[:, 0],)
    return (rows[:, 1], rows[:, 0])


@functools.lru_cache(maxsize=64)
def make_fused_step(mesh, axis_name: str, row_words: int, *,
                    out_factor: int = 2, impl: str = "auto",
                    key_words: int = 1, partition: str = "range"):
    """Build the jitted fused partition+exchange+local-sort step.
    Memoized per full signature so per-job callers compile once.

    ``partition`` selects how rows find their destination device:

    * ``"range"`` — uniform u32 key-range split (TeraSort): ONE key
      sort doubles as the destination grouping (range partition is
      monotonic in key), per-destination counts fall out of D-1 binary
      searches. ``step(rows)`` with ``rows: u32[D*cap, row_words]``
      sharded on the leading axis, key = column 0.
    * ``"dest"`` — caller-computed destinations (any partitioner):
      ``step(rows, dest)`` with ``dest: i32[D*cap]``; ``dest < 0``
      marks padding rows (not sent). Rows group by destination, ride
      the exchange, and key-sort on the receiving device
      (``key_words`` 1 = u32 column 0, 2 = u64 packed columns [0,1]).

    Returns ``(sorted_rows, recv_counts[D, D], overflowed[D])`` with
    each device's rows key-sorted, padding at the end (strip with
    ``recv_counts[d].sum()``). ``overflowed[d]`` flags a receive past
    the ``out_factor`` headroom or a dense-slot pair overflow — results
    there are truncated and MUST not be trusted (the engine's remedy:
    degrade the stage to the host dataplane).

    ``step.row_moves`` lists the form each of the step's row moves took
    (``ops.row_permute``: ``"packed"`` / ``"take"`` / ``"sort"``), in order. It
    is filled while the step is traced (its first call or ``lower``).
    """
    import jax
    import jax.numpy as jnp

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from sparkrdma_tpu.ops.partition import uniform_splitters
    from sparkrdma_tpu.parallel.exchange import (
        group_by_destination,
        ragged_exchange_shard,
        resolve_transport,
        row_mover,
    )

    if partition not in ("range", "dest"):
        raise ValueError(f"unknown partition {partition!r} "
                         "(expected 'range' or 'dest')")
    if partition == "range" and key_words != 1:
        raise ValueError("range partitioning is defined on single-word "
                         "u32 keys")
    # The kernels' names below are op metadata, and jax leaves metadata
    # out of the persistent compile cache's key by default: an executable
    # cached by a build with other scopes, or none, would be loaded in
    # place of this one and its device profile would carry that build's
    # names. Process-wide, and a matter of cache keys only.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    n = mesh.shape[axis_name]
    impl = resolve_transport(mesh, impl, axis_name)
    # how the step's rows follow an order, and the form each such move took
    # ("packed" / "take" / "sort"), filled while it is traced: step.row_moves
    row_moves: list = []
    move = row_mover(mesh, row_moves)
    spec = P(axis_name)
    sentinel = jnp.uint32(0xFFFFFFFF)
    write_back = key_words == 1
    splitters = uniform_splitters(n, jnp.uint32) if partition == "range" \
        else None

    def exchange_and_sort(grouped, counts):
        """The multi-device tail both partition modes share: the
        destination-grouped rows cross the mesh (``fused.exchange``),
        then the received rows are key-sorted (``fused.receive_sort``)
        with pads (index >= total) masked to the sentinel on every key
        word so they sort last; stable order within equal keys is
        arrival (source-major) order."""
        with jax.named_scope("fused.exchange"):
            output = jnp.zeros((grouped.shape[0] * out_factor, row_words),
                               dtype=grouped.dtype)
            received, recv_counts, _, overflowed = ragged_exchange_shard(
                grouped, counts, axis_name, output=output, impl=impl)
        with jax.named_scope("fused.receive_sort"):
            total = recv_counts.sum()
            idx = jnp.arange(received.shape[0], dtype=jnp.int32)
            keys = tuple(jnp.where(idx < total, k, sentinel)
                         for k in _row_keys(received, key_words))
            sorted_rows = _local_sort(received, keys, write_back, move)[0]
        return sorted_rows, recv_counts[None], overflowed[None]

    # pallas interpret-mode outputs confuse the vma checker when mixed
    # with collectives; disable it ONLY for the ring transports (same
    # rule as make_chunked_exchange / make_shuffle_exchange)
    in_specs = (spec,) if partition == "range" else (spec, spec)
    shard_kwargs = dict(mesh=mesh, in_specs=in_specs,
                        out_specs=(spec, spec, spec))
    if impl in ("ring", "ring_interpret"):
        shard_kwargs["check_vma"] = False

    if partition == "range":

        @jax.jit
        @functools.partial(shard_map, **shard_kwargs)
        def step(rows):
            keys = (rows[:, 0],)
            if n == 1:
                # single-device: no exchange, one sort is the whole job
                with jax.named_scope("fused.receive_sort"):
                    sorted_rows, _ = _local_sort(rows, keys, write_back,
                                                 move)
                counts = jnp.array([[rows.shape[0]]], dtype=jnp.int32)
                return sorted_rows, counts, jnp.zeros((1,), bool)

            with jax.named_scope("fused.partition"):
                # Local sort by KEY once: range partition is monotonic
                # in key, so key-sorted rows are destination-grouped for
                # free — this replaces the separate argsort-by-
                # destination + gather entirely.
                grouped, sorted_keys = _local_sort(rows, keys, write_back,
                                                   move)
                # per-destination counts: D-1 binary searches on sorted
                # keys
                bounds = jnp.searchsorted(sorted_keys, splitters,
                                          side="left")
                bounds = jnp.concatenate([
                    jnp.zeros(1, bounds.dtype), bounds,
                    jnp.array([rows.shape[0]], bounds.dtype)])
                counts = jnp.diff(bounds).astype(jnp.int32)
            return exchange_and_sort(grouped, counts)

    else:

        @jax.jit
        @functools.partial(shard_map, **shard_kwargs)
        def step(rows, dest):
            dest = dest.reshape(-1)
            if n == 1:
                valid = dest >= 0
                with jax.named_scope("fused.receive_sort"):
                    idx_keys = tuple(jnp.where(valid, k, sentinel)
                                     for k in _row_keys(rows, key_words))
                    sorted_rows, _ = _local_sort(rows, idx_keys, write_back,
                                                 move)
                counts = jnp.sum(valid).astype(jnp.int32).reshape(1, 1)
                return sorted_rows, counts, jnp.zeros((1,), bool)
            with jax.named_scope("fused.partition"):
                grouped, counts = group_by_destination(rows, dest, n, move)
            return exchange_and_sort(grouped, counts)

    step.row_moves = row_moves
    return step


# ---------------------------------------------------------------------------
# the overlapped host driver
# ---------------------------------------------------------------------------

def run_fused_exchange(mesh, axis_name: str, rows: np.ndarray,
                       dest: np.ndarray, *, key_words: int = 2,
                       rows_per_round: int = 0, out_factor: int = 2,
                       impl: str = "auto", tracer=None,
                       pipeline_rounds: bool = True,
                       ) -> Tuple[List[np.ndarray], int]:
    """Drive the fused step over fully-materialized arrays: bounded
    rounds of ``rows_per_round`` rows per device (0 = one shot) through
    ``run_fused_exchange_rounds``. ``rows: u32[N, W]`` (unpadded),
    ``dest: i32[N]`` destination device per row. Callers whose data
    streams off disk should feed ``run_fused_exchange_rounds`` a block
    generator instead, so host staging holds one round."""
    n = mesh.shape[axis_name]
    row_words = rows.shape[1]
    if len(rows) == 0:
        return [np.zeros((0, row_words), np.uint32) for _ in range(n)], 0
    cap = rows_per_round if rows_per_round > 0 else -(-len(rows) // n)
    per_round = cap * n

    def blocks():
        for start in range(0, len(rows), per_round):
            yield (rows[start:start + per_round],
                   dest[start:start + per_round])

    return run_fused_exchange_rounds(
        mesh, axis_name, blocks(), row_words, cap, key_words=key_words,
        out_factor=out_factor, impl=impl, tracer=tracer,
        pipeline_rounds=pipeline_rounds)


def run_fused_exchange_rounds(mesh, axis_name: str, blocks,
                              row_words: int, rows_per_round: int, *,
                              key_words: int = 2, out_factor: int = 2,
                              impl: str = "auto", tracer=None,
                              pipeline_rounds: bool = True,
                              ) -> Tuple[List[np.ndarray], int]:
    """Drive the fused step over a stream of round blocks: ``blocks``
    yields ``(rows u32[<= rows_per_round * D, row_words], dest i32)``
    per round, so HOST staging holds one round (plus the in-flight one
    when pipelined) no matter how large the stage. Rounds are
    DOUBLE-BUFFERED: round ``k+1``'s collective is dispatched while
    round ``k``'s on-device sort runs and its results drain
    (``exchange.round`` spans per round, ``exchange.overlap`` instants
    when a dispatch preceded the previous round's collection).

    Returns ``(per_device_sorted_rows, rounds)``: device d's rows
    key-sorted (u64 packed keys when ``key_words == 2``), the rounds'
    runs merged in one pass (``exchange.merge``). Raises
    ``OverflowError`` on any round's receive overflow — the caller
    (engine) degrades the stage to the host dataplane.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.ops.row_permute import forms_label
    from sparkrdma_tpu.parallel.exchange import record_exchange

    tracer = tracer if tracer is not None else trace_mod.NULL
    n = mesh.shape[axis_name]
    per_round = max(1, rows_per_round) * n
    step = make_fused_step(mesh, axis_name, row_words,
                           out_factor=out_factor, impl=impl,
                           key_words=key_words, partition="dest")
    sharding = NamedSharding(mesh, P(axis_name))
    runs: List[list] = [[] for _ in range(n)]

    def staged(r: int):
        """Round ``r``'s block from the caller's stream, or None at its
        end. The stream's work is the round's staging (spills -> u32
        rows and destinations); its last pull, which finds the end,
        records ``rows=0``."""
        with tracer.span("exchange.stage", "exchange", round=r) as args:
            block = next(blocks, None)
            args["rows"] = len(block[0]) if block is not None else 0
            args["bytes"] = (block[0].nbytes + block[1].nbytes
                             if block is not None else 0)
        return block

    def dispatch(r: int, chunk: np.ndarray, dchunk: np.ndarray):
        """Stage one round (pad to the static shape) and launch its
        collective; jax dispatch is async — no blocking here."""
        with tracer.span("exchange.round", "exchange", round=r,
                         rows=len(chunk),
                         bytes=per_round * (row_words + 1) * 4) as args:
            rows_p = np.zeros((per_round, row_words), np.uint32)
            rows_p[:len(chunk)] = chunk
            dest_p = np.full(per_round, -1, np.int32)
            dest_p[:len(chunk)] = dchunk
            out = step(stage_to_device(rows_p, sharding),
                       stage_to_device(dest_p, sharding))
            # what the step's trace chose, known once it has been called
            args["row_move"] = forms_label(step.row_moves)
        record_exchange(len(chunk))
        return r, out

    def collect(launched) -> None:
        r, results = launched
        with tracer.span("exchange.collect", "exchange", round=r) as args:
            got = _pull_runs(results, n, row_words)
            args["rows"] = sum(len(g) for g in got)
            args["bytes"] = sum(int(a.nbytes) for a in results)
        for d in range(n):
            runs[d].append(got[d])

    blocks = iter(blocks)
    rounds = 0
    in_flight = None
    while (block := staged(rounds)) is not None:
        nxt = dispatch(rounds, *block)
        if not pipeline_rounds:
            collect(nxt)
        else:
            if in_flight is not None:
                tracer.instant("exchange.overlap", "exchange",
                               dispatched=rounds, collecting=rounds - 1)
                collect(in_flight)
            in_flight = nxt
        rounds += 1
    if in_flight is not None:
        collect(in_flight)

    if rounds == 0:
        return [np.zeros((0, row_words), np.uint32) for _ in range(n)], 0
    return _merge_all_devices(tracer, runs, row_words, key_words), rounds


def _pull_runs(results, n: int, row_words: int) -> List[np.ndarray]:
    """Wait for one launched step and bring each device's received rows
    to the host: ``np.asarray`` blocks on the device (exchange + sort)
    and pulls the whole ``out_factor``-padded receive buffer."""
    out, counts, overflowed = results
    if np.asarray(overflowed).any():
        raise OverflowError(
            "fused exchange receive overflow: skew exceeds the "
            "out_factor headroom for this round size — the engine "
            "degrades the stage to the host dataplane")
    out = np.asarray(out).reshape(n, -1, row_words)
    counts = np.asarray(counts)
    # .copy(): a view would pin the padded round buffer across all rounds
    return [out[d][:int(counts[d].sum())].copy() for d in range(n)]


def _merge_all_devices(tracer, runs: List[list], row_words: int,
                       key_words: int) -> List[np.ndarray]:
    """The ``exchange.merge`` span of both drivers: every device's runs
    -> one sorted run each. ``bytes`` is what the merge wrote: each row
    once (``rows * row_words * 4``), and 0 for a device whose single
    run passed through."""
    with tracer.span("exchange.merge", "exchange",
                     runs=max(len(rs) for rs in runs),
                     rows=sum(len(r) for rs in runs for r in rs)) as args:
        merged = [_merge_device_runs(rs, row_words, key_words)
                  for rs in runs]
        args["bytes"] = sum(int(m.nbytes) for m, rs in zip(merged, runs)
                            if all(m is not r for r in rs))
    return merged


def _merge_device_runs(device_runs: list, row_words: int,
                       key_words: int) -> np.ndarray:
    """One device's key-sorted runs (one per round) as one sorted run:
    ``merge_runs``' single pass; a single run passes through."""
    if not device_runs:
        return np.zeros((0, row_words), np.uint32)
    if len(device_runs) == 1:
        return device_runs[0]
    from sparkrdma_tpu.shuffle.external import merge_runs

    return merge_runs([(_run_keys(r, key_words), r)
                       for r in device_runs])[1]


# ---------------------------------------------------------------------------
# the hierarchical (two-level) driver: per-slice ICI + DCN residue
# ---------------------------------------------------------------------------

def _run_keys(r: np.ndarray, key_words: int) -> np.ndarray:
    """Sort/merge keys of device-row runs: packed u64 for the 2-word
    layout, column 0 otherwise (shared by the flat and hierarchical
    drivers' merges and the host-side degrade sort)."""
    if key_words == 2:
        return r[:, :2].copy().view(np.uint64).reshape(-1)
    return r[:, 0]


def run_hierarchical_exchange(mesh, axis_name: str,
                              topology: topology_mod.Topology,
                              rows: np.ndarray, dest: np.ndarray,
                              home_slice: np.ndarray, *,
                              key_words: int = 2, rows_per_round: int = 0,
                              out_factor: int = 2, impl: str = "auto",
                              tracer=None,
                              ) -> Tuple[List[np.ndarray], int]:
    """Drive the FACTORED two-phase redistribution over a multi-slice
    topology: local regroup -> cross-slice move -> local regroup, per
    "Memory-efficient array redistribution through portable collective
    communication" (PAPERS.md) — no full intermediate is ever
    materialized.

    * **Phase 1 (intra)**: every row whose destination device lives in
      its home slice rides that slice's fused partition+exchange+sort
      step over the slice sub-mesh (``topology.slice_mesh``) — the bulk
      bytes, on ICI, in budget-bounded rounds exactly like the flat
      driver.
    * **DCN move**: the slice-crossing residue is tallied and charged
      (``topology.record_cross_slice`` + the installed shim) WHILE the
      phase-1 collectives are in flight — the DCN phase overlaps the ICI
      phase (``exchange.overlap``), the two-level analogue of the flat
      driver's double buffering.
    * **Phase 2 (regroup at destination)**: arrived residue rows run the
      destination slice's fused step — the second local regroup.

    ``home_slice: i32[N]`` names each row's producing slice (executor
    slots map to slices via ``Topology.slice_of_slot``); ``dest`` is the
    GLOBAL destination device per row. Returns the flat drivers'
    contract: per-device key-sorted rows (runs merged across phases and
    rounds), plus the total ICI round count.

    Per-slice degrade: a slice whose receive overflows (or whose
    collective fails under a lost device) falls back to host-side
    serving for ITS rows only — byte-identically, the other slices stay
    on ICI (``exchange.degrade`` instant with ``scope="slice"``).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sparkrdma_tpu.parallel.exchange import record_exchange

    tracer = tracer if tracer is not None else trace_mod.NULL
    n = mesh.shape[axis_name]
    row_words = rows.shape[1]
    if topology is None or topology.is_flat:
        # degenerate single-slice topology: the flat driver IS the plan
        return run_fused_exchange(
            mesh, axis_name, rows, dest, key_words=key_words,
            rows_per_round=rows_per_round, out_factor=out_factor,
            impl=impl, tracer=tracer)
    dest = np.asarray(dest, dtype=np.int32)
    home = np.asarray(home_slice, dtype=np.int32)
    dev_slice = topology.device_slices()
    dest_slice = dev_slice[dest] if len(dest) else dest
    runs: List[list] = [[] for _ in range(n)]
    degraded: set = set()
    rounds = 0
    row_bytes = row_words * 4

    def host_fallback(s: int, chunk: np.ndarray, dchunk: np.ndarray):
        """Serve one slice-chunk host-side, byte-identically: group by
        destination device, key-sort each group (the receiving device's
        sort), append as ordinary runs."""
        lo, hi = topology.slice_bounds(s)
        for d in range(lo, hi):
            sub = chunk[dchunk == d]
            if not len(sub):
                continue
            order = np.argsort(_run_keys(sub, key_words), kind="stable")
            runs[d].append(np.ascontiguousarray(sub[order]))

    def collect(s: int, lo: int, ns: int, r: int, result) -> None:
        with tracer.span("exchange.collect", "exchange", round=r,
                         slice=s) as args:
            got = _pull_runs(result, ns, row_words)
            args["rows"] = sum(len(g) for g in got)
            args["bytes"] = sum(int(a.nbytes) for a in result)
        for i in range(ns):
            runs[lo + i].append(got[i])

    def run_phase(per_slice: Dict[int, Tuple[np.ndarray, np.ndarray]],
                  phase: str, dcn_moves=None) -> None:
        """Dispatch every slice's budget-bounded rounds; charge the DCN
        residue move while round 0's collectives are in flight; collect
        with per-slice degrade."""
        nonlocal rounds
        sched = []
        for s in sorted(per_slice):
            rs, ds = per_slice[s]
            if not len(rs):
                continue
            lo, hi = topology.slice_bounds(s)
            ns = hi - lo
            cap = rows_per_round if rows_per_round > 0 else -(-len(rs) // ns)
            per_round = max(1, cap) * ns
            submesh = topology_mod.slice_mesh(mesh, axis_name, topology, s)
            step = make_fused_step(submesh, axis_name, row_words,
                                   out_factor=out_factor, impl=impl,
                                   key_words=key_words, partition="dest")
            sharding = NamedSharding(submesh, P(axis_name))
            chunks = [(rs[o:o + per_round], ds[o:o + per_round])
                      for o in range(0, len(rs), per_round)]
            sched.append((s, lo, ns, per_round, step, sharding, chunks))

        charged = dcn_moves is None

        def charge():
            nonlocal charged
            if charged:
                return
            charged = True
            for (src, dst) in sorted(dcn_moves):
                topology_mod.record_cross_slice(dcn_moves[(src, dst)])

        for r in range(max((len(c[6]) for c in sched), default=0)):
            batch = []
            for s, lo, ns, per_round, step, sharding, chunks in sched:
                if r >= len(chunks):
                    continue
                chunk, dchunk = chunks[r]
                if s in degraded:
                    host_fallback(s, chunk, dchunk)
                    continue
                with tracer.span("exchange.round", "exchange",
                                 round=rounds, phase=phase, slice=s,
                                 rows=len(chunk),
                                 bytes=per_round * (row_words + 1) * 4):
                    rows_p = np.zeros((per_round, row_words), np.uint32)
                    rows_p[:len(chunk)] = chunk
                    dest_p = np.full(per_round, -1, np.int32)
                    dest_p[:len(chunk)] = dchunk - lo  # slice-local device
                    out = step(stage_to_device(rows_p, sharding),
                               stage_to_device(dest_p, sharding))
                record_exchange(len(chunk))
                batch.append((s, lo, ns, chunk, dchunk, out))
            if batch and not charged:
                # jax dispatch is async: the residue crosses DCN while
                # the ICI collectives above are in flight
                tracer.instant("exchange.overlap", "exchange",
                               dispatched=rounds, collecting=-1,
                               phase=phase)
            charge()
            for s, lo, ns, chunk, dchunk, out in batch:
                try:
                    collect(s, lo, ns, rounds, out)
                except OverflowError:
                    # degrade ONLY this slice's residue to host serving;
                    # the other slices stay on ICI
                    degraded.add(s)
                    tracer.instant("exchange.degrade", "exchange",
                                   scope="slice", slice=s,
                                   reason="overflow")
                    host_fallback(s, chunk, dchunk)
            if batch:
                rounds += 1
        charge()  # a phase with no ICI rounds still pays its DCN move

    if len(rows):
        intra = dest_slice == home
        phase1 = {}
        phase2 = {}
        dcn_moves: Dict[Tuple[int, int], int] = {}
        for s in range(topology.num_slices):
            m = intra & (home == s)
            phase1[s] = (rows[m], dest[m])
        inter_rows = 0
        for t in range(topology.num_slices):
            segs_r, segs_d = [], []
            for s in range(topology.num_slices):
                if s == t:
                    continue
                m = (home == s) & (dest_slice == t)
                cnt = int(m.sum())
                if not cnt:
                    continue
                dcn_moves[(s, t)] = cnt * row_bytes
                inter_rows += cnt
                segs_r.append(rows[m])
                segs_d.append(dest[m])
            if segs_r:
                phase2[t] = (np.concatenate(segs_r),
                             np.concatenate(segs_d))
        run_phase(phase1, "intra", dcn_moves=dcn_moves)
        run_phase(phase2, "residue")
        tracer.instant("exchange.hierarchical", "exchange",
                       slices=topology.num_slices,
                       intra_rows=int(intra.sum()), inter_rows=inter_rows,
                       cross_slice_bytes=inter_rows * row_bytes,
                       degraded_slices=sorted(degraded))

    return _merge_all_devices(tracer, runs, row_words, key_words), rounds
