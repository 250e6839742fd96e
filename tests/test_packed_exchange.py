"""The exchange's way with narrow rows (``parallel/exchange.py``): the
rule that picks it, the grouping whose rows ride the sort against the
argsort + take + bincount formulation, the packer against the unpacked
shuffle on one and on four virtual devices over the ``gather`` transport,
and the rows the rule leaves alone. Grouping and packer are plain
``jax.numpy`` and the rule reads the row's width alone, so the CPU runs
the form the chip runs."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from sparkrdma_tpu.ops.row_permute import MIN_PACKED_WORDS, RowMover
from sparkrdma_tpu.parallel import exchange

AXIS = "shuffle"
FILL = 0xFFFFFFFF     # every word of the tests' fill record; the data's
#                       words stay under 2^31


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), (AXIS,))


def _plain(rows, dest, n, out_factor):
    """``shuffle_shard`` with ``shuffle_records_shard``'s four results."""
    output = jnp.zeros((out_factor * rows.shape[0], rows.shape[1]),
                       rows.dtype)
    received, counts, _, overflowed = exchange.shuffle_shard(
        rows, dest, AXIS, n, output=output, impl="gather")
    return received, counts, counts.sum(), overflowed


def _shuffle(form, n, rows, dest, out_factor):
    """``rows u32[n * N, W]`` to ``dest``: per device ``(records,
    recv_counts, delivered, overflowed)`` on the host. ``form``
    ``"packed"`` calls the packer itself, ``"rule"`` the front that picks
    by ``wire_form``, ``"plain"`` the unpacked ``shuffle_shard``."""
    words = rows.shape[1]

    @jax.jit
    @functools.partial(shard_map, mesh=_mesh(n), in_specs=(P(AXIS), P(AXIS)),
                       out_specs=(P(AXIS),) * 4)
    def run(rows, dest):
        fill = jnp.full((n, words), FILL, jnp.uint32)
        if form == "packed":
            got = exchange.pack_exchange_shard(
                rows, dest, fill, AXIS, n, out_factor, "gather")
        elif form == "rule":
            got = exchange.shuffle_records_shard(
                rows, dest, fill, AXIS, n, out_factor, "gather")
        else:
            got = _plain(rows, dest, n, out_factor)
        return tuple(x[None] for x in got)

    return [np.asarray(x) for x in run(rows, dest)]


def _traffic(n, per_device, words, seed, skew=False):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 2**31, (n * per_device, words), dtype=np.uint32)
    dest = rng.integers(0, n, n * per_device).astype(np.int32)
    if skew:
        dest[:] = 0
    dest[rng.random(len(dest)) < 0.05] = -1      # padding rows: not sent
    dest[rng.random(len(dest)) < 0.02] = n       # past the mesh: not sent
    return rows, dest


def test_the_rule_draws_the_row_moves_edge():
    assert [exchange.wire_form(w) for w in (1, 2, 3, 4, 7)] == [
        "packed"] * 5
    assert exchange.wire_form(MIN_PACKED_WORDS - 1) == "packed"
    assert exchange.wire_form(MIN_PACKED_WORDS) == "rows"
    assert exchange.wire_form(25) == "rows"
    # the grouping's carrier turns on the same edge: narrow rows ride the
    # sort, the others follow an order vector
    assert [exchange.grouping_form(w) for w in (1, 2, 3, 4, 7)] == [
        "sort"] * 5
    assert exchange.grouping_form(MIN_PACKED_WORDS - 1) == "sort"
    assert exchange.grouping_form(MIN_PACKED_WORDS) == "order"
    assert exchange.grouping_form(25) == "order"
    assert [exchange.wire_records(w) for w in (1, 2, 3, 4, 7)] == [
        128, 64, 42, 32, 18]
    # whole wire rows, and one more a destination
    assert exchange.wire_rows(1000, 2, 4) == 16 + 4
    assert exchange.wire_rows(1000, 3, 4) == 24 + 4
    assert exchange.record_capacity(1000, 3, 4, 2) == 2 * 28 * 42
    assert exchange.record_capacity(1000, 8, 4, 2) == 2000
    assert exchange.record_capacity(1000, 25, 4, 2) == 2000


def _group_plain(data, dest, num_partitions):
    """``group_by_destination`` as it was at every width: a stable argsort,
    ``jnp.take`` and ``jnp.bincount``. The plain reference."""
    dest = jnp.where((dest < 0) | (dest >= num_partitions), num_partitions,
                     dest.astype(jnp.int32))
    grouped = jnp.take(data, jnp.argsort(dest, stable=True), axis=0)
    counts = jnp.bincount(dest, length=num_partitions + 1)[:num_partitions]
    return grouped, counts.astype(jnp.int32)


def _group_traffic(shape, num_partitions, seed, dtype=np.uint32):
    """Rows whose destinations repeat (613 rows over at most 5
    destinations), with padding of both kinds (``dest`` < 0 and >= P) and,
    past one partition, a destination nobody sends to."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 2**16, shape).astype(dtype)
    dest = rng.integers(0, num_partitions, shape[0]).astype(np.int32)
    if num_partitions > 1:
        dest[dest == num_partitions - 2] = 0        # an empty destination
    dest[rng.random(shape[0]) < 0.05] = -1
    dest[rng.random(shape[0]) < 0.03] = -7
    dest[rng.random(shape[0]) < 0.05] = num_partitions
    dest[rng.random(shape[0]) < 0.03] = num_partitions + 9
    return data, dest


@pytest.mark.parametrize("words", [1, 2, 3, 4, 5, 6, 7, 8, 25])
@pytest.mark.parametrize("num_partitions", [1, 5])
def test_grouping_equals_argsort_take_bincount(num_partitions, words):
    """Rows and counts element for element, padding rows included: the
    sort that carries narrow rows is stable, so every sender's records
    keep their order, as under the argsort."""
    data, dest = _group_traffic((613, words), num_partitions, seed=words)
    chosen = []
    grouped, counts = jax.jit(
        lambda d, t: exchange.group_by_destination(
            d, t, num_partitions, RowMover(None, chosen)))(data, dest)
    want_rows, want_counts = _group_plain(data, dest, num_partitions)
    np.testing.assert_array_equal(grouped, want_rows)
    np.testing.assert_array_equal(counts, want_counts)
    assert counts.dtype == jnp.int32 and grouped.dtype == jnp.uint32
    live = (dest >= 0) & (dest < num_partitions)
    assert counts.sum() == live.sum() and 0 < live.sum() < len(dest)
    if num_partitions > 1:
        assert counts[num_partitions - 2] == 0
    assert chosen == ["sort" if words < MIN_PACKED_WORDS else "take"]


@pytest.mark.parametrize("shape,dtype", [
    ((613,), np.uint32),            # no rows of words at all
    ((613, 2), np.uint16),          # narrow, but not 4-byte words
    ((613, 2, 2), np.uint32),       # rows that are no vector of words
    ((613, 3), np.float32),         # 4-byte words of another type: sorted
])
def test_grouping_of_rows_that_are_no_narrow_word_rows(shape, dtype):
    data, dest = _group_traffic(shape, 3, seed=11, dtype=dtype)
    chosen = []
    grouped, counts = exchange.group_by_destination(
        data, dest, 3, RowMover(None, chosen))
    want_rows, want_counts = _group_plain(data, dest, 3)
    np.testing.assert_array_equal(grouped, want_rows)
    np.testing.assert_array_equal(counts, want_counts)
    assert grouped.dtype == dtype
    assert chosen == ["sort" if dtype == np.float32 else "take"]


@pytest.mark.parametrize("words", [1, 2, 3, 4, 7])
@pytest.mark.parametrize("n", [1, 4])
def test_packed_exchange_delivers_what_the_unpacked_one_does(n, words):
    per_device = 1000        # no whole number of wire rows at any width
    rows, dest = _traffic(n, per_device, words, seed=2**31 + words)
    per_row = exchange.wire_records(words)
    packed = _shuffle("packed", n, rows, dest, out_factor=2)
    plain = _shuffle("plain", n, rows, dest, out_factor=2)
    # the rule picks the packer at these widths, on this platform too
    for ruled, direct in zip(_shuffle("rule", n, rows, dest, out_factor=2),
                             packed):
        np.testing.assert_array_equal(ruled, direct)
    assert packed[0].shape == (n, exchange.record_capacity(
        per_device, words, n, 2), words)
    assert plain[0].shape == (n, 2 * per_device, words)
    for d in range(n):
        p_rec, p_counts, p_delivered, p_over = (x[d] for x in packed)
        u_rec, u_counts, u_delivered, u_over = (x[d] for x in plain)
        assert not p_over and not u_over
        # recv_counts: in records, fill included, whole wire rows a source
        assert (p_counts % per_row == 0).all()
        assert ((p_counts - u_counts >= 0)
                & (p_counts - u_counts < per_row)).all()
        want = [rows[s * per_device:(s + 1) * per_device][
            dest[s * per_device:(s + 1) * per_device] == d]
            for s in range(n)]
        assert p_delivered == u_delivered == sum(len(w) for w in want)
        assert u_counts.tolist() == [len(w) for w in want]
        # grouped by source, a source's records in their sender's order,
        # then the fill that makes its rows whole
        at = 0
        for s in range(n):
            got = p_rec[at:at + p_counts[s]]
            np.testing.assert_array_equal(got[:len(want[s])], want[s])
            assert (got[len(want[s]):] == FILL).all()
            at += p_counts[s]
        np.testing.assert_array_equal(u_rec[:u_counts.sum()],
                                      np.concatenate(want))
        # the same multiset of records a destination, the fill apart
        live = p_rec[:p_counts.sum()]
        live = live[(live != FILL).any(axis=1)]
        np.testing.assert_array_equal(
            np.sort(live.view(f"V{4 * words}").ravel()),
            np.sort(u_rec[:u_counts.sum()].copy().view(
                f"V{4 * words}").ravel()))


@pytest.mark.parametrize("words", [2, 3])
def test_packed_exchange_flags_a_receive_past_its_buffer(words):
    n, per_device = 4, 600
    rows, dest = _traffic(n, per_device, words, seed=7, skew=True)
    for form in ("rule", "plain"):
        _, counts, delivered, over = _shuffle(form, n, rows, dest,
                                              out_factor=2)
        assert over.tolist() == [True, False, False, False], form
        # counts stay real when the buffer does not hold them
        assert delivered[0] == (dest == 0).sum() > 2 * per_device
        assert counts[0].sum() >= delivered[0]
    _, _, _, over = _shuffle("packed", n, rows, dest, out_factor=4)
    assert not over.any()


@pytest.mark.parametrize("words", [8, 25])
def test_rows_of_eight_words_or_more_are_untouched(words):
    """The rule leaves them to ``shuffle_shard``: the traced program is
    the one ``shuffle_shard`` traces, into a buffer of ``out_factor``
    times the rows sent."""
    n, per_device = 4, 64
    rows, dest = _traffic(n, per_device, words, seed=words)
    mesh = _mesh(n)

    def traced(ruled):
        @functools.partial(shard_map, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
                           out_specs=P(AXIS))
        def run(rows, dest):
            fill = jnp.zeros((n, words), jnp.uint32)   # unused at 8 words up
            if ruled:
                return exchange.shuffle_records_shard(
                    rows, dest, fill, AXIS, n, 2, "gather")[0]
            return _plain(rows, dest, n, 2)[0]
        return str(jax.make_jaxpr(run)(rows, dest))

    assert traced(True) == traced(False)
    got = _shuffle("rule", n, rows, dest, out_factor=2)
    assert got[0].shape == (n, 2 * per_device, words)
    for d in range(n):
        want = np.concatenate([
            rows[s * per_device:(s + 1) * per_device][
                dest[s * per_device:(s + 1) * per_device] == d]
            for s in range(n)])
        np.testing.assert_array_equal(got[0][d][:got[1][d].sum()], want)
        assert got[2][d] == len(want)
