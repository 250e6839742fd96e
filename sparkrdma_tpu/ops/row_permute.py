"""Packed row permute: rows follow an order as contiguous 128-lane records.

``permute_rows(rows, order)`` is ``jnp.take(rows, order, axis=0)`` — the
row move after a ``(key, iota)`` sort (``parallel.device_plane.
_local_sort``) and after the argsort by destination (``parallel.exchange.
group_by_destination``, whose narrow rows ride their sort and follow no
order): the one place in the tree where rows follow an order.

Why a second data path. On the chip XLA keeps ``u32[N, W]`` with narrow
``W`` column-major (``{0,1:T(8,128)}``, ``W`` padded to a multiple of 8
sublanes): one 100-byte row is 25 words in 25 different 512-byte sublane
rows, and past the size where operand and result fit VMEM its gather
moves them a word at a time (37.6 ns a row at ``10,737,418 x 25``, ledger,
PR 28). The packed form makes a row one contiguous piece first:

1. ``pack``     ``u32[N, W]`` -> ``u32[Q, 128]`` row-major, ``S = 128 / Wp``
   records to a 128-lane row (``Wp`` = W rounded up to 32 or 64).
   **The record-to-slot map is slab-major**: record ``i`` sits in row
   ``i % Q``, lanes ``[(i // Q) * Wp, (i // Q) * Wp + W)``. The packed
   array is then the 2-D transpose of the ``S`` slabs ``rows.T[:, g*Q:
   (g+1)*Q]`` stacked along sublanes, which a kernel streams through VMEM
   in blocks with one XLU transpose each and no re-tiling in HBM.
2. ``permute``  one asynchronous copy of one 512-byte packed row a record,
   HBM -> VMEM, a block's copies issued before the block before it is
   waited for (once, for all its copies); in VMEM each record's lane group
   is rolled to the slot its output position has (same slab-major map)
   and the block written out.
3. ``unpack``   the inverse transpose, back to ``u32[N, W]``.

All three are Pallas TPU kernels: left to XLA, layout assignment turns the
transposes into bitcasts and moves the physical re-tiling onto the
128-lane-padded input (a 5.5 GB copy of a 1.4 GB operand; compiled for a
described v5e, PR 29).

``row_move_form`` picks the path from what the code can see (shape and
platform); there is no option for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# jax.experimental.pallas and its tpu module, imported by the packed form's
# first use: they take a second to import, and a step that stays with
# jnp.take (the SPI's rounds, PageRank) never needs them
pl = pltpu = None


def _import_pallas() -> None:
    global pl, pltpu
    if pl is None:
        from jax.experimental import pallas
        from jax.experimental.pallas import tpu

        pl, pltpu = pallas, tpu


LANES = 128

# --- the selection rule's three constants ---------------------------------
# Rows narrower than this stay with jnp.take. A packed record costs one
# 512-byte copy whatever its width (6.5 ns a row all told at 10,737,418
# rows); XLA's gather there took 6.1 ns a row at 2 words, 14.6 at 8, 22.3
# at 16, 37.6 at 25 and 32 (my chip run, PR 29; PERF.md section 6).
MIN_PACKED_WORDS = 8
# Rows wider than this stay with jnp.take too, because XLA already moves
# them as 128-lane records: from 64 words on its gather makes row-major
# copies of operand and result at 128 lanes (2 x 512 bytes a row of
# temporaries, what one record to a packed row would cost here), and at
# 128 words the rows are such records as they stand. At 2,097,155 rows,
# ns a row, jnp.take | packed: 65 words 7.99 | 7.49, 100 words 8.40 | 8.22,
# 128 words 5.58 | 11.82; at 33 words 29.06 | 6.67 (my chip run, PR 29).
# So a packed row holds four records or two, never one.
MAX_PACKED_WORDS = 64
# At or under this many bytes of operand as the compiler holds it
# (n_rows * row_words rounded up to 8 sublanes * 4) XLA's gather works out
# of VMEM and there is nothing to gain: 3.0-4.3 ns a row while its result
# is row-major, 3.5-10.5 while it is column-major in VMEM, 37 past that
# (25 words; my chip run, PR 29). The edge is the compiler's: jnp.take
# compiled for a described v5e at a ladder of N, the gather fusion's
# result layout read from the text. 25 and 32 words: {1,0:T(8,128)S(1)}
# (row-major, VMEM) up to 114,144 rows; {1,0:T(8,128)} (row-major, HBM)
# up to 349,336; {0,1:T(8,128)S(1)} (column-major, VMEM) up to 458,712;
# {0,1:T(8,128)} (column-major, HBM: the slow variant) from 459,400 on.
# The last VMEM size at other widths: 1,834,272 rows of 8 words, 916,960
# of 9, 916,672 of 16, 611,464 of 24: 58.67-58.72 MB of sublane-padded
# operand every time. The constant is the least of them.
TAKE_VMEM_EDGE_BYTES = 916_672 * 16 * 4

# --- the kernels' constants (tuned on the chip, PR 29) --------------------
# The permute at 10,737,418 x 25, ns a row: 5.57 at 256 rows a step and 8
# a trip, 5.47 at 512 and 8, 5.35 at 512 and 16 (one kernel form earlier
# 128 rows a step read 6 % over 256); the transposes do not care (1024 or
# 2048 rows a step: 0.43 and 0.79 ns a row). 1024 rows a step do not fit
# the scoped VMEM.
_PERMUTE_ROWS = 512     # packed rows a grid step of the permute
_ISSUE_UNROLL = 16      # packed rows a trip of the copy-issue loop
_TRANSPOSE_ROWS = 1024  # packed rows a grid step of pack / unpack


def row_move_form(n_rows: int, row_words: int, platform: str | None) -> str:
    """``"packed"`` or ``"take"``: which data path moves ``n_rows`` rows of
    ``row_words`` 32-bit words on ``platform`` (None: not known, so
    ``take``). Pure; decided at trace time, once a compiled step."""
    if platform != "tpu":
        return "take"
    if not MIN_PACKED_WORDS <= row_words <= MAX_PACKED_WORDS:
        return "take"
    if n_rows * -(-row_words // 8) * 8 * 4 <= TAKE_VMEM_EDGE_BYTES:
        return "take"
    return "packed"


def forms_label(chosen) -> str:
    """One word for the forms a step's row moves took (``RowMover``'s
    ``chosen``): ``"packed"``, ``"take"``, ``"sort"``, ``"packed+take"``
    where they differ, ``"none"`` where no rows followed an order."""
    return "+".join(sorted(set(chosen))) or "none"


def _slots(row_words: int) -> int:
    """Records to a 128-lane packed row: four or two. (With one, a grid
    step's 512 indices would be half of the 1,024-word tile XLA gives a
    1-D SMEM operand, and the permute does not compile for the chip.)"""
    assert row_words <= MAX_PACKED_WORDS, row_words
    return 4 if row_words <= 32 else 2


def _packed_rows(n_rows: int, slots: int) -> int:
    """Q: packed rows for ``n_rows`` records, a whole number of both
    kernels' blocks (one block size divides the other)."""
    block = max(_PERMUTE_ROWS, _TRANSPOSE_ROWS)
    return -(-n_rows // (slots * block)) * block


def _out_struct(shape, like):
    """A pallas_call result typed as varying over the mesh axes its
    operand varies over (``shard_map``'s ``check_vma``)."""
    return jax.ShapeDtypeStruct(shape, like.dtype, vma=jax.typeof(like).vma)


# ---------------------------------------------------------------------------
# pack / unpack: the slab-major transposes
# ---------------------------------------------------------------------------

def _pack_kernel(slots, words, *refs):
    slabs, out_ref, stacked = refs[:slots], refs[slots], refs[slots + 1]
    wp = LANES // slots

    @pl.when(pl.program_id(0) == 0)
    def _():     # the lanes past a record's words: zero, once
        stacked[...] = jnp.zeros_like(stacked)

    for g in range(slots):
        stacked[g * wp:g * wp + words, :] = slabs[g][...]
    out_ref[...] = stacked[...].T


def pack_rows(rows, interpret=False):
    """``u32[N, W]`` -> ``u32[Q, 128]``, record ``i`` in row ``i % Q``,
    lane group ``i // Q`` (slab-major). Records past ``N`` hold copies of
    other records and are never read back."""
    _import_pallas()
    n, words = rows.shape
    slots = _slots(words)
    q = _packed_rows(n, slots)
    cols = _TRANSPOSE_ROWS
    steps = q // cols
    last = (n - 1) // cols      # the last block of rows.T that holds a row

    def slab(g, j):
        return 0, jnp.minimum(g * steps + j, last)

    rows_t = rows.T     # [W, N]: a bitcast of the chip's column-major rows
    return pl.pallas_call(
        functools.partial(_pack_kernel, slots, words),
        out_shape=_out_struct((q, LANES), rows),
        grid=(steps,),
        in_specs=[pl.BlockSpec((words, cols), functools.partial(slab, g))
                  for g in range(slots)],
        out_specs=pl.BlockSpec((cols, LANES), lambda j: (j, 0)),
        scratch_shapes=[pltpu.VMEM((LANES, cols), rows.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="row_pack",
    )(*([rows_t] * slots))


def _unpack_kernel(slots, words, in_ref, out_ref, stacked):
    wp = LANES // slots
    slab = pl.program_id(1)

    @pl.when(slab == 0)
    def _():     # one transpose a block, kept for its other slabs
        stacked[...] = in_ref[...].T

    for g in range(slots):
        @pl.when(slab == g)
        def _(g=g):
            out_ref[...] = stacked[g * wp:g * wp + words, :]


def unpack_rows(packed, n_rows: int, row_words: int, interpret=False):
    """The inverse of ``pack_rows``: ``u32[Q, 128]`` -> ``u32[N, W]``. The
    grid's inner axis walks a block's slabs, so the block is fetched and
    transposed once and its slabs land in one ``[W, S * Q]`` array (as
    separate results XLA joined them in a pass of its own)."""
    _import_pallas()
    q = packed.shape[0]
    slots = _slots(row_words)
    cols = _TRANSPOSE_ROWS
    steps = q // cols
    rows_t = pl.pallas_call(
        functools.partial(_unpack_kernel, slots, row_words),
        out_shape=_out_struct((row_words, slots * q), packed),
        grid=(steps, slots),
        in_specs=[pl.BlockSpec((cols, LANES), lambda j, g: (j, 0))],
        out_specs=pl.BlockSpec((row_words, cols),
                               lambda j, g: (0, g * steps + j)),
        scratch_shapes=[pltpu.VMEM((LANES, cols), packed.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="row_unpack",
    )(packed)
    return rows_t[:, :n_rows].T


# ---------------------------------------------------------------------------
# permute: one 512-byte copy a record, select in VMEM
# ---------------------------------------------------------------------------

def _permute_kernel(slots, src_ref, group_ref, packed, out_ref, landing,
                    sems):
    """Grid step ``j`` starts block ``j``'s copies, then waits for block
    ``j - 1``'s and selects it: a block's copies fly while the block before
    it is selected, and the kernel holds the copies' code once (its
    lowering is a step's set-up time: PERF.md section 6). The grid has one
    step more than there are blocks."""
    block = _PERMUTE_ROWS
    step = pl.program_id(0)
    buf = lax.rem(step, 2)

    @pl.when(step + 1 < pl.num_programs(0))
    def _():    # record (g, t) of block ``step`` -> landing[buf, g, t]
        def trip(i, _):
            def row(u, _):      # unrolled when lowered: traced once
                t = i * _ISSUE_UNROLL + u
                for g in range(slots):
                    pltpu.make_async_copy(
                        packed.at[pl.ds(src_ref[g * block + t], 1)],
                        landing.at[buf, g, pl.ds(t, 1)],
                        sems.at[buf]).start()
                return 0
            return lax.fori_loop(0, _ISSUE_UNROLL, row, 0, unroll=True)
        lax.fori_loop(0, block // _ISSUE_UNROLL, trip, 0)

    @pl.when(step > 0)
    def _():
        done = 1 - buf
        # one wait for the block's copies together: a DMA semaphore counts
        # what arrived, and this descriptor is as large as all of them
        pltpu.make_async_copy(landing.at[done], landing.at[done],
                              sems.at[done]).wait()
        wp = LANES // slots
        lane_group = lax.broadcasted_iota(jnp.int32, (block, LANES), 1) // wp
        src_group = group_ref[...].T    # [block, 8]: a record's source group
        out = jnp.zeros((block, LANES), out_ref.dtype)
        for g in range(slots):
            got = landing[done, g]
            # roll the record from its source lane group to lane group g
            shift = lax.rem(g - src_group[:, g:g + 1] + slots, slots)
            moved = got
            for k in range(1, slots):
                moved = jnp.where(shift == k, pltpu.roll(got, k * wp, 1),
                                  moved)
            out = jnp.where(lane_group == g, moved, out)
        out_ref[...] = out


def permute_packed(packed, order, slots: int, interpret=False):
    """``out[p] = packed record order[p]`` over slab-major packed arrays:
    ``packed: u32[Q, 128]``, ``order: i32[slots * Q]`` with every index in
    ``[0, slots * Q)``."""
    _import_pallas()
    q = packed.shape[0]
    block = _PERMUTE_ROWS
    steps = q // block
    # a grid step's indices contiguous in SMEM, lane group by lane group
    by_step = order.reshape(slots, steps, block).transpose(1, 0, 2)
    src_row = lax.rem(by_step, q).reshape(-1)
    # a record's source lane group; 8 sublanes, so the kernel's transpose
    # is of whole tiles
    src_group = jnp.pad(lax.div(by_step, q), ((0, 0), (0, 8 - slots), (0, 0)))

    def done(j):    # the block grid step j finishes (step 0: none yet)
        return jnp.maximum(j - 1, 0)

    return pl.pallas_call(
        functools.partial(_permute_kernel, slots),
        out_shape=_out_struct(packed.shape, packed),
        grid=(steps + 1,),
        in_specs=[pl.BlockSpec((slots * block,),
                               lambda j: (jnp.minimum(j, steps - 1),),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, 8, block), lambda j: (done(j), 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, LANES), lambda j: (done(j), 0)),
        scratch_shapes=[pltpu.VMEM((2, slots, block, LANES), packed.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        # Mosaic's per-copy bounds checks are 12 of the 17 bundles a copy
        # costs to issue, and the copies' issue is the kernel's time; every
        # index is in range by construction (permute_rows clips)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=interpret,
        name="row_permute",
    )(src_row, src_group, packed)


# ---------------------------------------------------------------------------
# the one entry point
# ---------------------------------------------------------------------------

def permute_rows(rows, order, platform: str | None = None,
                 chosen: list | None = None):
    """``jnp.take(rows, order, axis=0)`` for an ``order`` of ANY length
    with indices in ``[0, N)``: a permutation as ``_local_sort`` and
    ``group_by_destination`` send it, or M indices into N rows, repeated
    (ALS reads a factor row once a rating). An index outside the range is
    clipped into it, where ``jnp.take`` would fill. The packed kernels
    shape their result as their operand, so only an order of the operand's
    length takes them; any other goes to ``take``: 25 M indices of 10 words
    read 4.62 / 2.54 ns each out of 480,189 / 17,770 rows, the packed permute
    alone 5.19 at M = N (``tpu_micro.py rowmove mn``, PR 35; PERF.md 6).

    ``platform`` is the platform the caller compiles for (a mesh's
    devices', ``parallel.exchange.mesh_platform``); without one the move
    is ``jnp.take``: the process's backend is not asked, since a CPU mesh
    in a TPU-backed process must not be handed a Mosaic call. Off the TPU
    only a test that forces the form sees the packed one, interpreted
    (its unvarying scratch fails ``shard_map``'s ``check_vma``: such a
    test runs the step with a ring transport, which turns the check off).
    ``chosen``, where given, gains the form this move took (``"packed"``
    / ``"take"``) while the caller's step is traced (``step.row_moves``).
    """
    packable = (rows.ndim == 2 and rows.dtype.itemsize == 4 and len(order)
                == len(rows) and rows.shape[1] <= MAX_PACKED_WORDS)
    form = row_move_form(*rows.shape, platform) if packable else "take"
    if chosen is not None:
        chosen.append(form)
    if form == "take":
        return jnp.take(rows, order, axis=0)
    interpret = platform != "tpu"
    n, words = rows.shape
    slots = _slots(words)
    with jax.named_scope("pack"):
        packed = pack_rows(rows, interpret)
        order = jnp.clip(order.astype(jnp.int32), 0, n - 1)
        order = jnp.pad(order, (0, slots * packed.shape[0] - n))
    with jax.named_scope("permute"):
        moved = permute_packed(packed, order, slots, interpret)
    with jax.named_scope("unpack"):
        return unpack_rows(moved, n, words, interpret)


class RowMover(functools.partial):
    """``move(rows, order)``: ``permute_rows`` for one step, bound to the
    platform the step compiles for and to ``chosen``, the list that gains
    the form of each of the step's row moves while it is traced
    (``parallel.exchange.row_mover`` makes one for a mesh). ``note`` adds
    the form of a move that followed no order vector:
    ``group_by_destination``'s ``"sort"``. Unbound (``RowMover()``) it is
    ``jnp.take`` and keeps no list.

    A ``functools.partial`` and not a class with a ``__call__`` of its own,
    so that a call adds no Python frame: a Mosaic kernel's lowered text
    holds its call stack, and the fused steps' programs stay byte for byte
    what they were (for the same reason nothing above this line moves)."""

    def __new__(cls, platform: str | None = None,
                chosen: list | None = None):
        return super().__new__(cls, permute_rows, platform=platform,
                               chosen=chosen)

    def note(self, form: str) -> None:
        chosen = self.keywords["chosen"]
        if chosen is not None:
            chosen.append(form)
