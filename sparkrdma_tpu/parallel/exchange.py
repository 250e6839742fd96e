"""The ICI data plane: size-exchange + ragged all-to-all.

This is the TPU-native replacement for the reference's entire one-sided READ
data path (scala/RdmaShuffleFetcherIterator.scala:119-180 — the M×R matrix of
scatter RDMA READs), and for its metadata location reads (293-315): on a TPU
mesh the exchange is a *collective*, so the "remote CPU bypass" property the
reference buys with RDMA verbs comes for free from the ICI fabric — no host
is involved once the step is launched.

Scheme (per device, inside ``shard_map`` over the shuffle axis):

1. **Size exchange** — ``all_gather`` of each device's ``send_counts`` row
   builds the D×D count matrix (the analogue of reading every map's
   ``RdmaMapTaskOutput`` table: it tells everyone where everything goes).
   O(D²) int32s — negligible next to the payload, like the reference's
   16-byte entries.
2. **Data exchange** — ``lax.ragged_all_to_all`` moves the ragged
   destination-grouped rows over ICI. Receiver-side landing offsets are
   column-wise exclusive prefix sums of the count matrix, so the result is
   densely packed, grouped by source — the same layout a reducer sees after
   the reference's grouped fetches.

Everything is static-shape: ``data`` and ``output`` are fixed-capacity
buffers; raggedness lives in the offset/size vectors, which is what keeps
XLA happy (no dynamic shapes under jit).

Narrow rows. Rows under ``ops.row_permute.MIN_PACKED_WORDS`` words are
narrow, and the one constant decides two things at trace time, from the
row's width alone, on every platform, so the CPU tests run the program the
chip runs. They are grouped by riding the sort that orders their
destinations, as its value operands (``grouping_form``,
``group_by_destination``: no order vector, no gather, the counts read off
the sorted destinations). And since the TPU's ragged all-to-all moves
every row as 128 lanes, they travel ``wire_records`` to a wire row
(``wire_form``, ``pack_exchange_shard``, ``shuffle_records_shard``): the
one packer in the tree. PageRank's 2-word records and q95's 3-, 2- and
4-word rows take both; ``models/join.py``, ``models/tpcds.py`` and q64
keep the unpacked ``shuffle_shard`` by name (toy sizes, no fill record of
their own), and their narrow rows ride the sort too.

Transports (``impl``). ``"auto"``, what every caller passes by default,
resolves per mesh (``resolve_impl``): ``native`` on a TPU mesh, ``dense``
where the compiler rejects the probe compile of the ragged opcode,
``gather`` off the TPU. The ring runs only on an explicit ask.

* ``"native"`` — ``lax.ragged_all_to_all`` (TPU; switch-routed ICI). The
  v5e compiler accepts it up to 16 chips; larger slices have limited ICI
  routing and reject the opcode, which is why ``resolve_impl``
  probe-compiles per mesh.
* ``"dense"`` — ``lax.all_to_all`` over fixed per-pair slots (supported
  at every scale): each (source, dest) pair gets ``out_capacity / D``
  slot rows; skew past a slot raises the callers' overflow flag exactly
  like a capacity overflow. Bandwidth = the padded capacity, i.e. an
  ``out_factor``-bounded overhead instead of gather's D×.
* ``"gather"`` — decomposed ``all_gather`` + mask-compaction, D×
  bandwidth: what XLA:CPU meshes run (XLA:CPU has no ragged opcode),
  and the oracle the other transports are tested against.
* ``"ring"`` / ``"ring_interpret"`` — the hand-scheduled Pallas ring kernel
  (``ops.ring_exchange``): explicit chip-to-chip async remote DMAs, the
  closest structural analogue of the reference's one-sided verbs engine.
  ``auto`` never picks it.
"""

from __future__ import annotations

import functools
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.ops.row_permute import MIN_PACKED_WORDS, RowMover

# Host-side dispatch tally for the ICI data plane. Callers that launch a
# collective exchange (mesh_service, models) record here so tests and the
# engine can assert that a job's shuffle bytes actually crossed the mesh
# rather than the TCP fetch path (the reference's equivalent evidence is
# its verbs counters vs. socket counters).
DATA_PLANE = {"exchanges": 0, "rows": 0}
_DATA_PLANE_LOCK = threading.Lock()


def record_exchange(rows: int) -> None:
    """Tally one dispatched collective exchange moving ``rows`` rows."""
    with _DATA_PLANE_LOCK:
        DATA_PLANE["exchanges"] += 1
        DATA_PLANE["rows"] += int(rows)


def _exclusive_cumsum(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    return jnp.cumsum(x, axis=axis) - x


def _slot_fill(data: jnp.ndarray, starts: jnp.ndarray, counts: jnp.ndarray,
               n: int, q: int):
    """Fill fixed per-destination slots: result ``[n*q, ...]`` where slot
    (j, k) holds row ``starts[j] + k`` of ``data`` when ``k < counts[j]``
    and zeros otherwise. Shared by the dense transport and the
    chunked-ring round (their block shape IS this slot layout)."""
    cap = data.shape[0]
    slot = jnp.arange(n * q, dtype=jnp.int32)
    dest_of_slot = jnp.minimum(slot // q, n - 1)
    within = slot - dest_of_slot * q
    src_idx = starts[dest_of_slot] + within
    valid = within < counts[dest_of_slot]
    picked = jnp.take(
        data, jnp.where(valid, jnp.minimum(src_idx, cap - 1), 0), axis=0)
    vmask = valid.reshape((-1,) + (1,) * (data.ndim - 1))
    return jnp.where(vmask, picked, 0), valid, dest_of_slot, within


def _slot_rows(out_cap: int, n: int) -> int:
    """Rows each (src, dst) pair owns in the fixed-slot transports: an
    even split of the receive capacity. Below one row per device an even
    split is zero, so a slot is the whole capacity instead — no pair can
    land more than that, so a pair overflows only when the buffer does
    and results match gather/native exactly (send side: < D*D rows)."""
    return out_cap // n or out_cap


def _pack_by_source(blocks: jnp.ndarray, recv_counts: jnp.ndarray,
                    base: jnp.ndarray) -> jnp.ndarray:
    """Compact per-source slot blocks ``[n, q, ...]`` into ``base``-shaped
    packed rows grouped by source (``recv_counts[j] <= q`` rows from
    source j, in slot order); ``base`` supplies rows past the total."""
    n, q = blocks.shape[0], blocks.shape[1]
    out_len = base.shape[0]
    off = _exclusive_cumsum(recv_counts)
    cum = jnp.cumsum(recv_counts)
    pos = jnp.arange(out_len, dtype=jnp.int32)
    src_of_pos = jnp.minimum(
        jnp.sum(pos[:, None] >= cum[None, :], axis=1), n - 1)
    flat_idx = src_of_pos * q + jnp.minimum(pos - off[src_of_pos], q - 1)
    packed = jnp.take(blocks.reshape((n * q,) + blocks.shape[2:]),
                      flat_idx, axis=0)
    mask = (pos < cum[-1]).reshape((-1,) + (1,) * (base.ndim - 1))
    return jnp.where(mask, packed, base)


def ragged_exchange_shard(data: jnp.ndarray, send_counts: jnp.ndarray,
                          axis_name: str,
                          output: Optional[jnp.ndarray] = None,
                          impl: str = "native",
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                     jnp.ndarray]:
    """Per-shard ragged all-to-all. Call inside ``shard_map``.

    Args:
      data: ``[capacity, ...]`` local rows, grouped by destination device in
        axis order (rows for device 0 first, then device 1, ...). Rows beyond
        ``send_counts.sum()`` are padding and are not sent.
      send_counts: ``i32[D]`` — rows destined for each device.
      axis_name: mesh axis to exchange over.
      output: optional ``[out_capacity, ...]`` buffer to receive into
        (defaults to a zeroed buffer shaped like ``data``).
      impl: ``"native"`` uses ``lax.ragged_all_to_all`` (TPU: rides ICI
        with no padding overhead); ``"dense"`` is fixed per-pair slots
        over ``lax.all_to_all`` (every topology; padding bounded by
        out_factor; pair skew past a slot trips the overflow flag);
        ``"gather"`` is the ``all_gather`` + mask-compaction oracle
        (D× bandwidth; XLA:CPU validation meshes). Identical results
        whenever dense's slots fit.

    Returns:
      ``(received, recv_counts, recv_offsets, overflowed)`` where
      ``received`` is packed grouped-by-source, ``recv_counts[j]`` is rows
      received from device j, ``recv_offsets`` is their exclusive prefix
      (start of each source's segment in ``received``), and ``overflowed``
      is a bool scalar: True when this shard's receive exceeded
      ``out_capacity`` OR (dense transport) some pair exceeded its fixed
      slot. When it is set, ``received`` is truncated — counts/offsets stay
      real, but callers MUST check the flag before trusting the rows
      (remedy: raise ``out_factor`` / chunk into rounds).
    """
    send_counts = send_counts.astype(jnp.int32)
    # 1. size exchange: full D x D count matrix; mat[j, i] = j sends to i.
    mat = lax.all_gather(send_counts, axis_name, axis=0, tiled=False)
    my = lax.axis_index(axis_name)

    input_offsets = _exclusive_cumsum(send_counts)
    send_sizes = send_counts
    # Landing offset of MY slice on receiver i = sum of what devices before
    # me send to i (column-wise exclusive prefix, my row).
    output_offsets = _exclusive_cumsum(mat, axis=0)[my]
    recv_sizes = mat[:, my]

    if output is None:
        output = jnp.zeros_like(data)
    # 2. data exchange over ICI.
    pair_overflow = jnp.bool_(False)
    if impl == "native":
        received = lax.ragged_all_to_all(
            data, output, input_offsets, send_sizes, output_offsets, recv_sizes,
            axis_name=axis_name)
    elif impl == "dense":
        received, recv_sizes, pair_overflow = _dense_exchange(
            data, mat, my, output, axis_name)
    elif impl in ("ring", "ring_interpret"):
        received, recv_sizes, pair_overflow = _ring_exchange(
            data, mat, my, output, axis_name,
            interpret=(impl == "ring_interpret"))
    elif impl == "gather":
        received = _gather_exchange(data, mat, my, output, axis_name)
    else:
        raise ValueError(f"unknown exchange impl {impl!r}")
    overflowed = pair_overflow | (jnp.sum(recv_sizes) > output.shape[0])
    return received, recv_sizes, _exclusive_cumsum(recv_sizes), overflowed


def _dense_exchange(data: jnp.ndarray, mat: jnp.ndarray, my: jnp.ndarray,
                    output: jnp.ndarray, axis_name: str):
    """Fixed-slot ``lax.all_to_all`` exchange: every (src, dst) pair owns
    ``Q = out_capacity // D`` slot rows (any ``out_capacity % D``
    remainder rows are unused headroom; ``_slot_rows`` covers
    ``out_capacity < D``).

    Exact (bit-identical to native/gather) whenever no pair exceeds its
    slot; a pair overflow is reported as an explicit bool (third return
    value) that ``ragged_exchange_shard`` folds into its ``overflowed``
    flag — receive counts are always the TRUE per-source counts (remedy
    for an overflow is the same as for capacity: raise ``out_factor``,
    which grows Q). Unlike ragged-all-to-all this lowers on every
    topology (plain all-to-all) and on XLA:CPU, so the path is
    executable in CI.
    """
    n = mat.shape[0]
    q = _slot_rows(output.shape[0], n)
    counts = mat[my]                      # what I send to each dest
    send, _, _, _ = _slot_fill(data, _exclusive_cumsum(counts), counts, n, q)
    got = lax.all_to_all(send.reshape((n, q) + data.shape[1:]), axis_name,
                         split_axis=0, concat_axis=0)

    recv_true = mat[:, my]
    received = _pack_by_source(got, jnp.minimum(recv_true, q), output)
    # pair overflow (anyone sent me more than a slot): explicit flag;
    # counts stay true so offsets derived from them are never garbage
    return received, recv_true, (recv_true > q).any()


def _ring_move_blocks(blocks: jnp.ndarray, axis_name: str, n: int,
                      interpret: bool) -> jnp.ndarray:
    """Move per-destination blocks ``[n, ...]`` (row j -> device j) with
    the Pallas ring kernel; returns the per-source received blocks, same
    shape. Mosaic remote-DMA slices need the lane (last) dim 128-aligned,
    so each block travels as flat words reshaped to [*, 128] lanes
    (padded by <128 words when the block size isn't a lane multiple) and
    is unflattened on arrival."""
    from sparkrdma_tpu.ops.ring_exchange import ring_all_to_all_shard

    words = int(np.prod(blocks.shape[1:]))
    lanes = -(-words // 128) * 128
    flat = blocks.reshape(n, words)
    if lanes != words:
        flat = jnp.pad(flat, ((0, 0), (0, lanes - words)))
    got = ring_all_to_all_shard(flat.reshape(n, lanes // 128, 128),
                                axis_name, n, interpret=interpret)
    return got.reshape(n, lanes)[:, :words].reshape(blocks.shape)


def _ring_exchange(data: jnp.ndarray, mat: jnp.ndarray, my: jnp.ndarray,
                   output: jnp.ndarray, axis_name: str,
                   interpret: bool = False):
    """Fixed-slot exchange with the SAME slot layout and overflow
    semantics as ``_dense_exchange``, moved by the hand-scheduled Pallas
    ring (``ops.ring_exchange``) instead of ``lax.all_to_all``: explicit
    chip-to-chip async remote DMAs, neighbor-hop traffic only — the
    production transport for slices whose compiler rejects
    ragged-all-to-all and whose topology favors ring traffic
    (O(D/2) blocks per link) over switch routing. Bit-identical to
    dense/native/gather whenever no pair exceeds its slot."""
    n = mat.shape[0]
    q = _slot_rows(output.shape[0], n)
    counts = mat[my]
    send, _, _, _ = _slot_fill(data, _exclusive_cumsum(counts), counts, n, q)
    got = _ring_move_blocks(send.reshape((n, q) + data.shape[1:]),
                            axis_name, n, interpret)
    recv_true = mat[:, my]
    received = _pack_by_source(got, jnp.minimum(recv_true, q), output)
    return received, recv_true, (recv_true > q).any()


def _gather_exchange(data: jnp.ndarray, mat: jnp.ndarray, my: jnp.ndarray,
                     output: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Decomposed ragged exchange: all_gather everything, keep what's mine.

    Bandwidth is D× the native path (every row visits every device), which is
    fine for validation meshes; results are bit-identical to the native path:
    rows packed grouped-by-source, stable within source.
    """
    num_dev, capacity = mat.shape[0], data.shape[0]
    rows_all = lax.all_gather(data, axis_name, axis=0, tiled=False)  # [D, cap, ...]
    # Reconstruct each row's destination from the count matrix (rows are
    # destination-grouped per sender): row i of sender j targets the bucket
    # whose cumulative count straddles i; i >= total(j) is padding (-> D).
    bounds = jnp.cumsum(mat, axis=1)  # [D, D] inclusive per-sender
    row_idx = jnp.arange(capacity, dtype=jnp.int32)
    dest_all = jnp.sum(row_idx[None, :, None] >= bounds[:, None, :],
                       axis=-1)  # [D, cap] in [0, D]
    keep = dest_all == my
    order = (jnp.arange(num_dev, dtype=jnp.int32)[:, None] * capacity
             + row_idx[None, :])
    key = jnp.where(keep, order, jnp.int32(num_dev * capacity)).reshape(-1)
    perm = jnp.argsort(key, stable=True)
    flat = rows_all.reshape((num_dev * capacity,) + rows_all.shape[2:])
    # output capacity may exceed D*capacity (generous receive headroom);
    # pad the permutation with index 0 — those slots are masked off below
    # (total received rows can never exceed D*capacity)
    out_cap = output.shape[0]
    k = min(out_cap, num_dev * capacity)
    sel = jnp.zeros(out_cap, dtype=perm.dtype).at[:k].set(perm[:k])
    packed = jnp.take(flat, sel, axis=0)
    total = jnp.sum(mat[:, my])
    mask = jnp.arange(out_cap) < total
    mask = mask.reshape((-1,) + (1,) * (output.ndim - 1))
    return jnp.where(mask, packed, output)


# The grouping's edge is ``MIN_PACKED_WORDS`` because the sort that carries
# the rows wins at every width under it. ``scripts/tpu_micro.py groupsort``,
# 10,737,418 rows to 4 destinations and padding, ns a row (my chip run,
# PR 34; PERF.md section 6): the whole narrow form (one stable sort of
# dest + W operands, the binary searches) | stable argsort 2.50 +
# ``jnp.take``, and ``jnp.bincount`` (8.77 at any width) on top of that:
#   W   2     3      4      5      6      7      8
#   4.36  4.86   6.31   6.97   8.77   9.19   10.83
#   8.62  17.84  17.84  19.74  19.76  21.19  21.19
# about 2.3 + 1.05 W against 2.5 + ~15-19 + 8.8. At 8 words the rows
# follow their order packed (2.50 + 5.90: ``ops/row_permute.py``), under
# the sort's 10.83 by less than the bincount that path still pays; no cell
# runs rows that wide through ``group_by_destination``, so it stays as it is.
def grouping_form(row_words: int) -> str:
    """``"sort"`` or ``"order"``: how ``group_by_destination`` brings rows
    of ``row_words`` 32-bit words into destination order. The edge is
    ``ops.row_permute.row_move_form``'s and ``wire_form``'s, one constant:
    a row under ``MIN_PACKED_WORDS`` words is narrow, and narrow rows ride
    the sort that orders the destinations, as its value operands; wider
    rows follow an order vector (``ops.row_permute.permute_rows``). Pure;
    decided at trace time, on any platform."""
    return "sort" if row_words < MIN_PACKED_WORDS else "order"


def group_by_destination(data: jnp.ndarray, dest: jnp.ndarray,
                         num_partitions: int, move: RowMover = RowMover(),
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable local grouping of rows by destination partition.

    The local analogue of the reference writer's sort-by-partition spill
    (its wrapped SortShuffleWriter produces partition-contiguous files,
    writer/wrapper/RdmaWrapperShuffleWriter.scala:83-99). Rows with
    ``dest >= num_partitions`` or ``dest < 0`` are treated as padding: they
    sort to the end and don't count.

    One algorithm, order by destination, and two carriers for the rows,
    chosen by ``grouping_form`` from the row's width alone. Narrow rows
    (``u32[N, W]`` or another 4-byte type, ``W < MIN_PACKED_WORDS``) are
    the value operands of ONE stable ``lax.sort`` keyed on the destination
    (no order vector, no gather), and the counts are ``num_partitions + 1``
    binary searches on the sorted destinations; ``move.note`` records the
    form, ``"sort"``. Any other rows follow a stable argsort through
    ``move(rows, order)``: ``ops.row_permute.permute_rows``, which a caller
    that knows its mesh binds to the mesh's platform (``row_mover``);
    unbound it is ``jnp.take``. Both give the same rows and counts,
    element for element.

    Returns ``(grouped_rows, counts)`` with ``counts: i32[num_partitions]``.
    """
    dest = jnp.where((dest < 0) | (dest >= num_partitions),
                     num_partitions, dest.astype(jnp.int32))
    if (data.ndim == 2 and data.dtype.itemsize == 4
            and grouping_form(data.shape[1]) == "sort"):
        move.note("sort")
        with jax.named_scope("row_sort"):  # a device profile's kernel name
            sorted_dest, *columns = lax.sort(
                (dest, *(data[:, k] for k in range(data.shape[1]))),
                num_keys=1, is_stable=True)
        bounds = jnp.searchsorted(
            sorted_dest, jnp.arange(num_partitions + 1, dtype=jnp.int32),
            side="left")
        return (jnp.stack(columns, axis=1),
                jnp.diff(bounds).astype(jnp.int32))
    order = jnp.argsort(dest, stable=True)
    with jax.named_scope("row_gather"):   # a device profile's kernel name
        grouped = move(data, order)
    counts = jnp.bincount(dest, length=num_partitions + 1)[:num_partitions]
    return grouped, counts.astype(jnp.int32)


def shuffle_shard(data: jnp.ndarray, dest: jnp.ndarray, axis_name: str,
                  num_devices: int,
                  output: Optional[jnp.ndarray] = None,
                  impl: str = "native", move: RowMover = RowMover()):
    """Full per-shard shuffle step: group locally by destination device
    (``move``: see ``group_by_destination``), then ragged-exchange.
    Returns (received, recv_counts, recv_offsets, overflowed) — see
    ``ragged_exchange_shard``."""
    grouped, counts = group_by_destination(data, dest, num_devices, move)
    return ragged_exchange_shard(grouped, counts, axis_name, output, impl)


# --- narrow rows: records to a wire row ---------------------------------
# The TPU's ragged all-to-all moves a row as 128 32-bit lanes (512 bytes)
# whatever its width: an 8-byte record sent as a row of its own is padded
# 64-fold, in the send buffer and in the receive buffer (1,536 bytes of HBM
# a record at ``out_factor`` 2: a chip could not hold 10^7 of them; PERF.md
# section 6, PR 28).
WIRE_LANES = 128


def wire_records(row_words: int) -> int:
    """Records of ``row_words`` 32-bit words that one wire row carries."""
    return WIRE_LANES // row_words


def wire_form(row_words: int) -> str:
    """``"packed"`` or ``"rows"``: how ``shuffle_records_shard`` sends rows
    of ``row_words`` words. The edge is ``ops.row_permute.row_move_form``'s,
    one constant and not a second: a row under ``MIN_PACKED_WORDS`` words
    is narrow, and narrow rows travel ``wire_records`` to a wire row, on
    any platform (the 128-lane padding is the TPU's; one program
    everywhere is the tests'). Pure; decided at trace time."""
    return "packed" if row_words < MIN_PACKED_WORDS else "rows"


def wire_rows(n_rows: int, row_words: int, num_devices: int) -> int:
    """Wire rows a device sends at most in the packed form: its
    ``n_rows`` records in whole rows, and one more for each destination's
    last, partly filled row."""
    return -(-n_rows // wire_records(row_words)) + num_devices


def record_capacity(n_rows: int, row_words: int, num_devices: int,
                    out_factor: int) -> int:
    """Records the receive buffer of ``shuffle_records_shard`` holds for a
    device that sends ``n_rows``: ``out_factor`` times what it sends at
    most, which in the packed form is ``wire_rows`` whole wire rows."""
    if wire_form(row_words) == "packed":
        return (out_factor * wire_rows(n_rows, row_words, num_devices)
                * wire_records(row_words))
    return out_factor * n_rows


def pack_exchange_shard(rows: jnp.ndarray, dest: jnp.ndarray,
                        fill: jnp.ndarray, axis_name: str, num_devices: int,
                        out_factor: int = 1, impl: str = "native",
                        move: RowMover = RowMover()):
    """The shuffle of narrow rows, ``wire_records`` records to a wire row.
    Call inside ``shard_map``; plain ``jax.numpy``, any platform, any
    transport. ``shuffle_records_shard`` picks it by ``wire_form``.

    ``rows u32[N, W]`` go to ``dest i32[N]`` (outside ``[0,
    num_devices)``: padding, not sent). Each destination's group is
    filled up to whole wire rows with copies of ``fill[d] u32[W]``, the
    CALLER's record for destination ``d`` that does no harm where it
    lands (PageRank: ``(d's first vertex, 0.0)``; a join: its dead key).
    The fill rows are appended before the grouping, so one
    ``group_by_destination`` orders records and fill alike and the wire
    rows are a reshape of its result: lanes ``[k * R, (k + 1) * R)`` of a
    wire row hold word ``k`` of its ``R = wire_records(W)`` records, and
    the lanes past ``W * R`` are zero.

    Returns ``(records, recv_counts, delivered, overflowed)``:
    ``records u32[record_capacity, W]``, grouped by source as
    ``ragged_exchange_shard`` delivers them, each source's records in
    their sender's stable order and followed by at most ``R - 1`` copies
    of ``fill[me]``; ``recv_counts i32[D]`` in records, FILL INCLUDED (a
    multiple of ``R`` each): the records at positions under
    ``recv_counts.sum()`` are senders' records or fill, and the receiver
    does not tell them apart: that is what the harmless fill is for;
    ``delivered``, the senders' own records this device was sent, fill
    excluded; ``overflowed`` as ``ragged_exchange_shard`` states it, in
    wire rows."""
    n = num_devices
    n_rows, words = rows.shape
    per_row = wire_records(words)
    rows_out = wire_rows(n_rows, words, n)
    slack = rows_out * per_row - n_rows - n * per_row
    devices = jnp.arange(n, dtype=jnp.int32)
    # a compare and a sum: a bincount is a scatter-add of N rows
    counts = jnp.sum(dest[:, None] == devices[None, :], axis=0,
                     dtype=jnp.int32)
    # fill records: the first ``(-count) % R`` of each destination's R are
    # sent to it, the others to nobody
    lane = jnp.arange(per_row, dtype=jnp.int32)
    fill_dest = jnp.where(lane[None, :] < (-counts % per_row)[:, None],
                          devices[:, None], -1).reshape(-1)
    # a column at a time, not the matrix at once: the same values, but the
    # chip's compiler then keeps the result of PageRank's per-edge gather,
    # which feeds a column, in fast memory (PERF.md section 6, PR 33)
    fill = fill.astype(rows.dtype)
    rows = jnp.stack([jnp.concatenate(
        [rows[:, k], jnp.repeat(fill[:, k], per_row),
         jnp.zeros(slack, rows.dtype)]) for k in range(words)], axis=1)
    dest = jnp.concatenate(
        [dest.astype(jnp.int32), fill_dest, jnp.full(slack, -1, jnp.int32)])
    grouped, sent = group_by_destination(rows, dest, n, move)
    lanes = [grouped[:, k].reshape(rows_out, per_row) for k in range(words)]
    if words * per_row < WIRE_LANES:
        lanes.append(jnp.zeros((rows_out, WIRE_LANES - words * per_row),
                               rows.dtype))
    wire = jnp.concatenate(lanes, axis=1)
    output = jnp.zeros((rows_out * out_factor, WIRE_LANES), rows.dtype)
    received, recv_rows, _, overflowed = ragged_exchange_shard(
        wire, sent // per_row, axis_name, output=output, impl=impl)
    records = jnp.stack(
        [received[:, k * per_row:(k + 1) * per_row].reshape(-1)
         for k in range(words)], axis=1)
    me = lax.axis_index(axis_name)
    delivered = lax.all_gather(counts, axis_name)[:, me].sum()
    return records, recv_rows * per_row, delivered, overflowed


def shuffle_records_shard(rows: jnp.ndarray, dest: jnp.ndarray,
                          fill: jnp.ndarray, axis_name: str,
                          num_devices: int, out_factor: int = 1,
                          impl: str = "native", move: RowMover = RowMover()):
    """``shuffle_shard`` for records that may be narrow: group by
    destination device, exchange, and hand back records. The form is
    ``wire_form(W)``'s: ``pack_exchange_shard`` for rows under
    ``MIN_PACKED_WORDS`` words, else ``shuffle_shard`` into a buffer of
    ``record_capacity`` rows (``fill`` then goes unused). No option and
    no platform selects the form.

    Returns ``pack_exchange_shard``'s four, and a caller that keeps to
    them is right at any width: it treats every position under
    ``recv_counts.sum()`` as a record, gives a ``fill`` that does no harm
    there, and counts what it was sent by ``delivered``."""
    if wire_form(rows.shape[1]) == "packed":
        return pack_exchange_shard(rows, dest, fill, axis_name, num_devices,
                                   out_factor, impl, move)
    output = jnp.zeros((out_factor * rows.shape[0], rows.shape[1]),
                       rows.dtype)
    received, recv_counts, _, overflowed = shuffle_shard(
        rows, dest, axis_name, num_devices, output=output, impl=impl,
        move=move)
    return received, recv_counts, recv_counts.sum(), overflowed


# the one compile-time rejection that selects the dense transport (see
# _native_compiles; tests/test_tpu_aot.py pins the compiler's text)
_LIMITED_ICI_ROUTING = "not supported in limited ICI routing"


@functools.lru_cache(maxsize=32)
def _native_compiles(mesh: Mesh, axis_name: str) -> Tuple[bool, str]:
    """(supported, reason): whether THIS mesh's TPU compiler accepts
    ragged-all-to-all over ``axis_name``.

    Not every topology does: v5e slices above 16 chips have limited ICI
    routing and the opcode is rejected at compile time ("Ragged
    all-to-all is currently not supported in limited ICI routing
    settings"). One tiny throwaway compile per (mesh, axis), cached.
    ONLY that rejection answers "unsupported"; any other compile failure
    is not a topology limit and propagates with the compiler's message —
    a broken toolchain must not quietly run the job on another
    transport.
    """
    n = mesh.shape[axis_name]
    spec = P(axis_name)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,) * 4,
                       out_specs=spec)
    def probe(op, out, iof, sz):
        return lax.ragged_all_to_all(op[0], out[0], iof[0], sz[0], iof[0],
                                     sz[0], axis_name=axis_name)[None]

    sh = jax.sharding.NamedSharding(mesh, spec)
    arg = jax.ShapeDtypeStruct((n, n * 8), jnp.int32, sharding=sh)
    idx = jax.ShapeDtypeStruct((n, n), jnp.int32, sharding=sh)
    try:
        probe.lower(arg, arg, idx, idx).compile()
    except Exception as e:  # the compiler's error type is not public API
        if _LIMITED_ICI_ROUTING in str(e):
            return False, f"{type(e).__name__}: {e}"
        raise
    return True, ""


def mesh_platform(mesh: Mesh) -> str:
    """The platform a program on ``mesh`` compiles for (a described
    topology's devices say "tpu" in a process whose backend is the CPU)."""
    return next(iter(mesh.devices.flat)).platform


def row_mover(mesh: Mesh, chosen: Optional[list] = None) -> RowMover:
    """``ops.row_permute.RowMover`` for a step compiled for ``mesh``:
    ``move(rows, order)``. ``chosen`` gains the form of each move traced
    through it (``"packed"`` / ``"take"``) or noted on it (``"sort"``)."""
    return RowMover(mesh_platform(mesh), chosen)


def resolve_impl(mesh: Mesh, impl: str = "auto",
                 axis_name: Optional[str] = None) -> str:
    """``auto`` -> native on TPU meshes whose compiler supports the
    ragged-all-to-all opcode over the exchange axis; the dense fixed-slot
    transport on the large v5e slices that reject it for limited ICI
    routing (and only there — any other probe failure raises); the
    gather oracle on non-TPU meshes (XLA:CPU has no opcode at all).
    ``axis_name`` defaults to the last mesh axis (the convention
    everywhere in this package)."""
    if impl != "auto":
        return impl
    if mesh_platform(mesh) != "tpu":
        return "gather"
    axis = axis_name or mesh.axis_names[-1]
    ok, reason = _native_compiles(mesh, axis)
    if ok:
        return "native"
    _warn_topology_once(mesh, axis, reason)
    return "dense"


def resolve_transport(mesh: Mesh, impl: str,
                      axis_name: Optional[str] = None) -> str:
    """The transport resolution every plan/build site shares: ring
    transports pass through verbatim (they are explicit asks, never
    probed), everything else goes through ``resolve_impl``'s per-mesh
    probe. One helper so the step builders and the cost model's plan
    sites can't drift apart."""
    return (impl if impl in ("ring", "ring_interpret")
            else resolve_impl(mesh, impl, axis_name))


# (mesh, axis) pairs whose topology-rejection warning already fired:
# only _native_compiles is cached, so without this memo EVERY
# resolve_impl call re-logged the same rejection — iterative stages
# (ALS supersteps, per-stage cost-model probes) flooded the log.
_topology_warned: set = set()
_TOPOLOGY_WARN_LOCK = threading.Lock()


def _warn_topology_once(mesh: Mesh, axis_name: str, reason: str) -> None:
    """Log the "topology rejects ragged-all-to-all" warning once per
    (mesh, axis); later resolutions of the same pair stay silent."""
    key = (mesh, axis_name)
    with _TOPOLOGY_WARN_LOCK:
        if key in _topology_warned:
            return
        _topology_warned.add(key)
    import logging

    logging.getLogger(__name__).warning(
        "this TPU topology rejects ragged-all-to-all; using the dense "
        "fixed-slot all-to-all transport (out_factor-bounded padding "
        "overhead; the chunked ring is the neighbor-traffic "
        "alternative). Compiler said: %s", reason[:300])


def bucket_quota(quota: int) -> int:
    """Round ``quota`` up to the next power of two — the memoization
    bucket for the chunked-exchange builders. Iterative stages derive
    per-round quotas from drifting byte budgets; memoizing per EXACT
    quota recompiled every superstep, while pow2 bucketing caps the
    compile count at log2(max quota) with identical results (quota only
    bounds per-round chunking, never the data moved). Rounding UP means
    a round may buffer up to 2x the requested quota — callers sizing
    quota against a hard memory bound should pass the pow2 at or below
    their budget."""
    return 1 << max(0, int(quota) - 1).bit_length()


def make_chunked_exchange(mesh: Mesh, axis_name: str, quota: int,
                          impl: str = "auto"):
    """Bounded-round ragged exchange for arbitrary skew; ``quota`` is
    bucketed to the next power of two (``bucket_quota``) before the
    memoized build, so drifting quotas share compiles. The returned
    ``round_fn``'s shapes are sized by the BUCKETED quota — drive the
    round loop with ``bucket_quota(quota)`` (``chunked_exchange`` does).
    See ``_make_chunked_exchange``."""
    return _make_chunked_exchange(mesh, axis_name, bucket_quota(quota),
                                  impl)


@functools.lru_cache(maxsize=128)
def _make_chunked_exchange(mesh: Mesh, axis_name: str, quota: int,
                           impl: str = "auto"):
    """Bounded-round ragged exchange for arbitrary skew. Memoized per
    (mesh, axis, quota, impl) so iterative callers (ALS) compile once.

    One round moves at most ``quota`` rows per (source, destination) pair,
    so a receiver never nets more than ``D * quota`` rows per round no
    matter how skewed the traffic — the collective analogue of the
    reference's bounded in-flight window + grouped fetches
    (scala/RdmaShuffleFetcherIterator.scala:240-276): total transfer is
    unbounded, per-round memory is not.

    Returns ``round_fn(grouped, counts, round_idx) -> (received[D*quota,...],
    recv_counts[D])`` to be driven by a host loop over
    ``ceil(max_pair_count / quota)`` rounds (the host knows counts — it
    computed them or fetched the size exchange). ``grouped`` must be
    destination-grouped rows with per-destination ``counts`` (as produced by
    ``group_by_destination``).
    """
    n = mesh.shape[axis_name]
    impl_resolved = resolve_transport(mesh, impl, axis_name)
    spec = P(axis_name)

    # pallas interpret-mode outputs confuse the vma checker when mixed
    # with collectives; disable it ONLY for the ring transports so the
    # static varying-axes check still guards the collective paths
    shard_kwargs = dict(mesh=mesh, in_specs=(spec, spec, None),
                        out_specs=(spec, spec))
    if impl_resolved in ("ring", "ring_interpret"):
        shard_kwargs["check_vma"] = False

    @jax.jit
    @functools.partial(shard_map, **shard_kwargs)
    def round_fn(grouped, counts, round_idx):
        received, recv_counts = _chunked_round_shard(
            grouped, counts, round_idx, axis_name, n, quota, impl_resolved)
        return received, recv_counts[None]

    return round_fn


def _chunked_round_shard(grouped, counts, round_idx, axis_name: str, n: int,
                         quota: int, impl_resolved: str):
    """One chunked round, inside shard_map: returns this round's received
    rows packed grouped-by-source plus per-source counts."""
    counts = counts.reshape(-1).astype(jnp.int32)
    seg_starts = _exclusive_cumsum(counts)
    # This round's slice of each destination segment:
    # [start + r*quota, start + min((r+1)*quota, count))
    lo = jnp.minimum(round_idx * quota, counts)
    hi = jnp.minimum(lo + quota, counts)
    send_counts = hi - lo
    # per-destination slot layout, shared with the dense transport
    filled, valid, dest_of_slot, within = _slot_fill(
        grouped, seg_starts + lo, send_counts, n, quota)

    if impl_resolved in ("ring", "ring_interpret"):
        # Hand-scheduled ICI transport (ops/ring_exchange.py): send rows
        # stay in natural [D, quota] block layout — no compaction needed
        # on the send side; the ring's fixed block shape IS the quota.
        got = _ring_move_blocks(
            filled.reshape((n, quota) + grouped.shape[1:]), axis_name, n,
            interpret=(impl_resolved == "ring_interpret"))
        mat = lax.all_gather(send_counts, axis_name, axis=0, tiled=False)
        my = lax.axis_index(axis_name)
        recv_counts = mat[:, my]
        # compact [D, quota] -> packed grouped-by-source (recv_counts
        # <= quota by construction)
        received = _pack_by_source(
            got, recv_counts,
            jnp.zeros((n * quota,) + grouped.shape[1:], grouped.dtype))
        return received, recv_counts

    # Collective transport: compact send buffer, destination-grouped.
    send_off = _exclusive_cumsum(send_counts)
    compact_idx = jnp.where(valid,
                            send_off[dest_of_slot] + within,
                            n * quota - 1)
    send_buf = jnp.zeros((n * quota,) + grouped.shape[1:], grouped.dtype)
    # scatter picked rows to their compact position (invalid rows all
    # collide harmlessly on the last slot, then get overwritten only by
    # at most one valid row — counts guarantee compact positions unique)
    send_buf = send_buf.at[compact_idx].set(filled)
    # overflow is impossible by construction here: per-pair send_counts
    # <= quota and the output capacity is exactly n * quota (= dense's
    # slot size), so the flag is statically dead — dropped
    received, recv_counts, _, _ = ragged_exchange_shard(
        send_buf, send_counts, axis_name, impl=impl_resolved)
    return received, recv_counts


def make_chunked_exchange_acc(mesh: Mesh, axis_name: str, quota: int,
                              impl: str = "auto"):
    """``make_chunked_exchange_acc`` with the same pow2 quota bucketing
    as ``make_chunked_exchange`` (see ``bucket_quota``)."""
    return _make_chunked_exchange_acc(mesh, axis_name,
                                      bucket_quota(quota), impl)


@functools.lru_cache(maxsize=128)
def _make_chunked_exchange_acc(mesh: Mesh, axis_name: str, quota: int,
                               impl: str = "auto"):
    """``make_chunked_exchange`` with a DEVICE-RESIDENT accumulator: each
    round scatters its received rows straight into a per-device output
    buffer at their final source-major position, so the host loop touches
    no data at all — per-round host work is the loop counter, and the
    whole result crosses to the host (if ever) exactly once.

    Landing offsets need no device->host sync: every shard re-derives the
    full DxD count matrix with one O(D^2)-int ``all_gather`` per round and
    computes ``base[src] + already_sent[src] + within`` locally — the same
    trick the one-shot exchange uses for its receive offsets.

    Returns ``round_acc(grouped, counts, round_idx, acc) -> acc`` where
    ``acc`` is ``[D * cap_out, ...]`` sharded on the leading axis (its
    shape IS the capacity — jit re-specializes per shape); rows a device
    nets beyond ``cap_out`` are the CALLER's sizing error (cap_out must be
    ``max_d sum_s counts[s, d]``, which the caller knows — it has the
    count matrix).
    """
    n = mesh.shape[axis_name]
    impl_resolved = resolve_transport(mesh, impl, axis_name)
    spec = P(axis_name)
    shard_kwargs = dict(mesh=mesh, in_specs=(spec, spec, None, spec),
                        out_specs=spec)
    if impl_resolved in ("ring", "ring_interpret"):
        shard_kwargs["check_vma"] = False

    @functools.partial(jax.jit, donate_argnums=(3,))
    @functools.partial(shard_map, **shard_kwargs)
    def round_acc(grouped, counts, round_idx, acc):
        counts = counts.reshape(-1).astype(jnp.int32)
        received, _ = _chunked_round_shard(
            grouped, counts, round_idx, axis_name, n, quota, impl_resolved)
        # full count matrix -> my column = total rows each source sends me
        mat = lax.all_gather(counts, axis_name, axis=0, tiled=False)
        my = lax.axis_index(axis_name)
        to_me = mat[:, my]
        base = _exclusive_cumsum(to_me)          # source-major layout
        lo = jnp.minimum(round_idx * quota, to_me)
        hi = jnp.minimum(lo + quota, to_me)
        rcnt = hi - lo                           # received per source now
        off = _exclusive_cumsum(rcnt)            # packed positions
        src = jnp.repeat(jnp.arange(n), quota)
        w = jnp.tile(jnp.arange(quota), n)
        valid = w < rcnt[src]
        rows = received[jnp.where(valid, off[src] + w, 0)]
        # invalid slots aim past the buffer and drop
        dst = jnp.where(valid, base[src] + lo[src] + w, acc.shape[0])
        return acc.at[dst].set(rows, mode="drop")

    return round_acc


def chunked_exchange(mesh: Mesh, axis_name: str, grouped: np.ndarray,
                     counts: np.ndarray, quota: int, impl: str = "auto"):
    """Host driver for the chunked exchange: runs all rounds with the
    device-resident accumulator, returns (received_rows_per_device,
    total_rounds). Each device's rows are grouped by source device, in the
    source's original within-destination order (same contract as
    ``ragged_exchange_shard``). ``grouped``/``counts`` are global arrays
    sharded on axis 0.

    ``quota`` is bucketed UP to the next power of two (``bucket_quota``)
    to share compiles across drifting quotas — a round may buffer up to
    2x the requested per-pair bound, so callers sizing quota against a
    hard memory budget should pass the pow2 at or below it.

    Host cost model: O(1) work per round (the loop index), one
    device->host transfer at the end. The previous per-round
    ``np.asarray`` + O(D^2) Python segment slicing made the HOST the
    bottleneck at ALS/skew scale — the round loop now leaves data in HBM
    (the reference's analogous property: fetched blocks land in
    registered memory and stay there,
    scala/RdmaShuffleFetcherIterator.scala:240-276)."""
    n = mesh.shape[axis_name]
    quota = bucket_quota(quota)  # match the builders' memoization bucket
    counts_host = np.asarray(counts).reshape(n, n)
    num_rounds = max(1, int(-(-counts_host.max() // quota)))
    recv_totals = counts_host.sum(axis=0)        # rows landing per device
    cap_out = max(1, int(recv_totals.max()))
    round_acc = make_chunked_exchange_acc(mesh, axis_name, quota, impl)
    sharding = NamedSharding(mesh, P(axis_name))
    grouped_d = jax.device_put(grouped, sharding)
    counts_d = jax.device_put(counts_host.reshape(-1), sharding)
    # host-side zeros: device_put then ships each device ONLY its shard —
    # a jnp.zeros here would transiently commit the whole global buffer to
    # the default device before resharding (D-fold HBM spike)
    acc = jax.device_put(
        np.zeros((n * cap_out,) + grouped.shape[1:], grouped.dtype),
        sharding)
    # Bound dispatch run-ahead. On XLA:CPU a collective BLOCKS its worker
    # thread inside the rendezvous (InProcessCommunicator); unbounded
    # async dispatch lets fast device threads queue rounds ahead and fill
    # the shared pool with executions parked at future-round rendezvous,
    # starving some device of a thread for the CURRENT round — after 40s
    # the rendezvous aborts the process ("Expected 8 ... only 7 arrived").
    # Reproduced deterministically on a 1-core host at rehearsal scale:
    # synchronized rounds run at ~0.1s/round, the first unsynchronized
    # batch of rounds SIGABRTs. On TPU collectives run device-side (the
    # host thread is not parked), so a deeper pipeline is safe and keeps
    # dispatch off the critical path.
    sync_every = 1 if mesh_platform(mesh) == "cpu" else 8
    for r in range(num_rounds):
        acc = round_acc(grouped_d, counts_d, r, acc)
        if (r + 1) % sync_every == 0:
            jax.block_until_ready(acc)
    record_exchange(int(counts_host.sum()))
    # Epilogue peak control: pull ONE device's shard to the host at a
    # time and free buffers as we go. Materializing the whole padded
    # accumulator host-side while the device copy is still alive doubles
    # the padded footprint (up to D x the real data under skew) — at
    # rehearsal scale that is the difference between fitting the memory
    # contract and an honest MemoryError under RLIMIT_AS.
    del grouped_d, counts_d
    shards = {s.index[0].start or 0: s for s in acc.addressable_shards}
    results: list = []
    if len(shards) == n:
        for d in range(n):
            host = np.asarray(shards[d * cap_out].data)
            # copies, not views: under skew the padded shard is up to D x
            # the real rows, and callers (ALS) hold results across solves
            results.append(host[:int(recv_totals[d])].copy())
            del host
    else:  # multi-process mesh: only local shards are addressable —
        # assemble the global array (callers at that scale stream)
        out = np.asarray(acc).reshape(n, cap_out, *grouped.shape[1:])
        del acc
        results = [out[d][:int(recv_totals[d])].copy() for d in range(n)]
    return results, num_rounds


@functools.lru_cache(maxsize=64)
def make_shuffle_exchange(mesh: Mesh, axis_name: str, impl: str = "auto",
                          out_factor: int = 1):
    """Build a jitted all-device shuffle-exchange over ``mesh``. Memoized
    per (mesh, axis, impl, out_factor) like ``make_chunked_exchange`` so
    per-job callers (mesh_service) compile once.

    The returned callable takes globally-sharded arrays
    ``(data[D*capacity, ...], dest[D*capacity])`` (sharded on the leading
    axis) and returns ``(received, recv_counts[D, D], recv_offsets[D, D],
    overflowed[D])`` with the same leading-axis sharding; ``overflowed[d]``
    is device d's explicit receive-overflow flag (capacity or dense pair
    slot) — check it before trusting ``received``.

    ``out_factor`` scales each device's receive capacity relative to its send
    capacity: a receiver may legitimately net-gain rows (skew). Callers bound
    worst-case skew or chunk into rounds (the reference's analogous knob is
    the grouped-fetch ceiling ``shuffleReadBlockSize``,
    scala/RdmaShuffleFetcherIterator.scala:240-263).
    """
    spec = P(axis_name)
    n = mesh.shape[axis_name]
    impl = resolve_transport(mesh, impl, axis_name)
    move = row_mover(mesh)

    # pallas interpret-mode outputs confuse the vma checker when mixed
    # with collectives; disable it ONLY for the ring transports so the
    # static varying-axes check still guards the collective paths
    shard_kwargs = dict(mesh=mesh, in_specs=(spec, spec),
                        out_specs=(spec, spec, spec, spec))
    if impl in ("ring", "ring_interpret"):
        shard_kwargs["check_vma"] = False

    @jax.jit
    @functools.partial(shard_map, **shard_kwargs)
    def exchange(data, dest):
        output = jnp.zeros((data.shape[0] * out_factor,) + data.shape[1:],
                           dtype=data.dtype)
        received, recv_counts, recv_offsets, overflowed = shuffle_shard(
            data, dest, axis_name, n, output=output, impl=impl, move=move)
        return received, recv_counts[None], recv_offsets[None], \
            overflowed[None]

    return exchange
