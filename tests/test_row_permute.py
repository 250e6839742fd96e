"""``ops.row_permute``: the packed row permute against ``rows[order]``.

The kernels run in Pallas interpret mode on the CPU (the form is forced:
off the TPU ``row_move_form`` always answers ``take``); what the chip's
compiler makes of them is ``tests/test_tpu_aot.py``'s to say, what they
cost ``scripts/tpu_micro.py rowmove``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkrdma_tpu.ops import row_permute as rp

# 100 and 128 words: over MAX_PACKED_WORDS, so jnp.take even when forced
WIDTHS = (1, 2, 8, 25, 26, 32, 33, 64, 100, 128)
# Every width at 4,099 rows: past one block of either kernel at four
# records to a packed row, and no multiple of the slot count, the tile or
# a block. The sizes under one block (1 and 7: under one packed row too)
# pad to the same kernels whatever the width, so one width of each slot
# class (4 and 2 records to a row) runs them. A shape costs a compile of
# the interpreted kernels (~2 s); its three orders share it.
SHAPES = ([(w, 4_099) for w in WIDTHS]
          + [(w, n) for n in (1, 7, 1_000) for w in (25, 64)])
ORDERS = ("identity", "reversed", "random")


@pytest.fixture(scope="module")
def forced_permute():
    """``permute_rows`` with the packed form forced, jitted once for the
    module so the three orders of a shape share one compile."""
    patch = pytest.MonkeyPatch()
    patch.setattr(rp, "row_move_form", lambda n, w, platform: "packed")
    # a function of its own: jit caches traces by function, and the trace
    # is where the form is chosen
    yield jax.jit(lambda rows, order: rp.permute_rows(rows, order))
    patch.undo()


def _rows(n, w):
    rng = np.random.default_rng(n * 131 + w)
    return rng.integers(0, 2**32, (n, w), dtype=np.uint32)


def _order(kind, n):
    if kind == "identity":
        return np.arange(n, dtype=np.int32)
    if kind == "reversed":
        return np.arange(n, dtype=np.int32)[::-1].copy()
    return np.random.default_rng(n).permutation(n).astype(np.int32)


@pytest.mark.parametrize("kind", ORDERS)
@pytest.mark.parametrize("w,n", SHAPES)
def test_permute_rows_equals_take(forced_permute, w, n, kind):
    rows, order = _rows(n, w), _order(kind, n)
    got = np.asarray(forced_permute(rows, order))
    np.testing.assert_array_equal(got, rows[order])


@pytest.mark.parametrize("n", (7, 4_099))
@pytest.mark.parametrize("w", (2, 25, 33, 64))
def test_pack_then_unpack_is_the_identity(w, n):
    rows = _rows(n, w)
    packed = rp.pack_rows(jnp.asarray(rows), interpret=True)
    slots = rp._slots(w)
    assert packed.shape == (rp._packed_rows(n, slots), rp.LANES)
    # slab-major: record i in row i % Q, lane group i // Q
    q, wp = packed.shape[0], rp.LANES // slots
    i = n - 1
    np.testing.assert_array_equal(
        np.asarray(packed)[i % q, (i // q) * wp:(i // q) * wp + w], rows[i])
    back = rp.unpack_rows(packed, n, w, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), rows)


def test_repeated_indices_are_honoured_and_strays_clipped(forced_permute):
    rows = _rows(300, 25)
    order = np.random.default_rng(3).integers(0, 300, 300).astype(np.int32)
    assert len(np.unique(order)) < 300
    np.testing.assert_array_equal(
        np.asarray(forced_permute(rows, order)), rows[order])
    stray = order.copy()
    stray[:2] = (-5, 10_000)
    np.testing.assert_array_equal(
        np.asarray(forced_permute(rows, stray)),
        rows[np.clip(stray, 0, 299)])


@pytest.mark.parametrize("forced", (True, False), ids=("forced", "rule"))
@pytest.mark.parametrize("m", (1_000, 4_099, 9_000),
                         ids=("M<N", "M=N", "M>N"))
def test_an_order_of_any_length_is_take(monkeypatch, m, forced):
    """``permute_rows`` is ``jnp.take`` for M indices into N rows, in every
    form the rule can choose: the packed kernels shape their result as
    their operand, so only M = N can take them (forced here, as on the
    chip), and any other length goes to ``take``. ALS's per-rating gather
    is M = 26 N, repeated."""
    # (the module's ``forced_permute`` may have the rule patched already)
    monkeypatch.setattr(rp, "row_move_form", (
        lambda n, w, p: "packed") if forced else ROW_MOVE_FORM)
    n, w = 4_099, 10
    rows = _rows(n, w)
    order = np.random.default_rng(m).integers(0, n, m).astype(np.int32)
    assert len(np.unique(order)) < m
    chosen = []
    move = jax.jit(lambda rows, order: rp.permute_rows(rows, order,
                                                       chosen=chosen))
    got = np.asarray(move(rows, order))
    assert chosen == ["packed" if forced and m == n else "take"]
    assert got.shape == (m, w)
    np.testing.assert_array_equal(got, rows[order])
    stray = order.copy()
    stray[:2] = (-5, 10_000)
    want = (rows[np.clip(stray, 0, n - 1)] if chosen == ["packed"]
            else np.asarray(jnp.take(rows, stray, axis=0)))
    np.testing.assert_array_equal(np.asarray(move(rows, stray)), want)


@pytest.mark.parametrize("dtype", (np.int32, np.float32))
def test_any_32_bit_rows_ride_the_packed_form(forced_permute, dtype):
    rows = _rows(500, 9).view(np.int32).astype(dtype)
    order = _order("random", 500)
    np.testing.assert_array_equal(
        np.asarray(forced_permute(rows, order)), rows[order])


@pytest.mark.parametrize("rows", (
    np.arange(40, dtype=np.uint32),                      # not 2-D
    np.arange(80, dtype=np.uint16).reshape(40, 2),       # not 32-bit
    np.arange(320, dtype=np.uint32).reshape(40, 4, 2),   # not 2-D
    np.arange(2600, dtype=np.uint32).reshape(40, 65),    # no slot that wide
))
def test_other_operands_stay_with_take(forced_permute, rows):
    order = _order("random", 40)
    np.testing.assert_array_equal(
        np.asarray(forced_permute(rows, order)), rows[order])


ROW_MOVE_FORM = rp.row_move_form     # the module's own, whatever is forced
EDGE_ROWS = rp.TAKE_VMEM_EDGE_BYTES // (32 * 4)    # at 25 to 32 words


@pytest.mark.parametrize("n,w,platform,want", [
    (10_737_418, 25, "cpu", "take"),        # platform
    (10_737_418, 25, "gpu", "take"),
    (10_737_418, 25, "tpu", "packed"),
    (10_737_418, rp.MIN_PACKED_WORDS - 1, "tpu", "take"),   # width under
    (10_737_418, rp.MIN_PACKED_WORDS, "tpu", "packed"),     # W_min itself
    (16_777_280, 2, "tpu", "take"),         # PageRank's rows
    (10_737_418, rp.MAX_PACKED_WORDS, "tpu", "packed"),     # W_max itself
    (10_737_418, rp.MAX_PACKED_WORDS + 1, "tpu", "take"),   # width over
    (10_737_418, 100, "tpu", "take"),       # XLA's own 128-lane records
    (10_737_418, 128, "tpu", "take"),
    (10_737_418, 129, "tpu", "take"),
    (EDGE_ROWS, 25, "tpu", "take"),         # N at the edge
    (EDGE_ROWS + 1, 25, "tpu", "packed"),   # and past it
    (2 * EDGE_ROWS, 16, "tpu", "take"),     # the edge is in operand bytes
    (2 * EDGE_ROWS + 1, 16, "tpu", "packed"),
    (111_848, 25, "tpu", "take"),           # the SPI's round
    (5_368_709, 25, "tpu", "packed"),       # fused_4chip's first gather
    (10_737_418, 25, None, "take"),         # platform not known
])
def test_row_move_form_table(n, w, platform, want):
    assert ROW_MOVE_FORM(n, w, platform) == want


@pytest.mark.parametrize("platform", (None, "cpu"))
def test_permute_rows_off_the_tpu_is_take(monkeypatch, platform):
    """Unforced, a caller that compiles for the CPU, or does not say what
    for, never sees a kernel: the program is the one gather. The process's
    backend is not asked (a CPU mesh in a TPU-backed process)."""
    monkeypatch.setattr(rp, "row_move_form", ROW_MOVE_FORM)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, order = _rows(4_099, 25), _order("random", 4_099)
    chosen = []
    unforced = jax.jit(
        lambda rows, order: rp.permute_rows(rows, order, platform, chosen))
    text = unforced.lower(rows, order).as_text()
    assert "gather" in text and "while" not in text
    assert chosen == ["take"]
    np.testing.assert_array_equal(
        np.asarray(rp.permute_rows(rows, order, platform)), rows[order])


def test_chosen_gains_the_form_of_each_traced_move(forced_permute):
    """``chosen`` is filled while tracing, one entry a move, and an
    operand the packed form cannot carry says ``take`` whatever is
    forced."""
    chosen = []

    @jax.jit
    def two_moves(rows, halves, order):
        return (rp.permute_rows(rows, order, chosen=chosen),
                rp.permute_rows(halves, order, chosen=chosen))

    rows, order = _rows(40, 9), _order("random", 40)
    halves = np.arange(80, dtype=np.uint16).reshape(40, 2)
    got = two_moves(rows, halves, order)
    assert chosen == ["packed", "take"]
    two_moves(rows, halves, order)      # served from the trace cache
    assert chosen == ["packed", "take"]
    np.testing.assert_array_equal(np.asarray(got[0]), rows[order])
    np.testing.assert_array_equal(np.asarray(got[1]), halves[order])


@pytest.mark.parametrize("chosen,label", [
    ([], "none"), (["take"], "take"), (["packed", "packed"], "packed"),
    (["take", "packed", "take"], "packed+take")])
def test_forms_label(chosen, label):
    assert rp.forms_label(chosen) == label
