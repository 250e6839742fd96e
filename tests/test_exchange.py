"""Data-plane tests on the 8-device virtual CPU mesh.

This is the multi-device integration tier the reference never had
(SURVEY.md §4): the ragged all-to-all exchange is checked against a numpy
oracle for balanced, ragged, skewed, and empty traffic patterns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from sparkrdma_tpu.ops.partition import (
    hash_partition,
    partition_and_count,
    range_partition,
    sample_splitters,
    uniform_splitters,
)
from sparkrdma_tpu.ops.sort import sort_kv, sort_segments
from sparkrdma_tpu.parallel.exchange import make_shuffle_exchange

D = 8


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= D, "conftest must provide 8 virtual devices"
    return Mesh(np.array(devs[:D]), ("shuffle",))


def _numpy_oracle(data: np.ndarray, dest: np.ndarray, capacity: int):
    """Expected per-device received rows, grouped by source device, in local
    row order — the exchange's contract."""
    n_dev = D
    per_dev = data.reshape(n_dev, capacity, *data.shape[1:])
    per_dest = dest.reshape(n_dev, capacity)
    out = []
    for i in range(n_dev):
        rows = [per_dev[j][per_dest[j] == i] for j in range(n_dev)]
        out.append(np.concatenate(rows) if rows else np.zeros((0,)))
    return out


def _run_exchange(mesh, data, dest, capacity, out_factor=1):
    exchange = make_shuffle_exchange(mesh, "shuffle", out_factor=out_factor)
    sharding = jax.NamedSharding(mesh, P("shuffle"))
    data_d = jax.device_put(data, sharding)
    dest_d = jax.device_put(dest, sharding)
    received, counts, offsets, overflowed = jax.block_until_ready(
        exchange(data_d, dest_d))
    return (np.asarray(received).reshape(D, capacity * out_factor, *data.shape[1:]),
            np.asarray(counts), np.asarray(offsets), np.asarray(overflowed))


def _check(mesh, data, dest, capacity, out_factor=1):
    received, counts, offsets, overflowed = _run_exchange(
        mesh, data, dest, capacity, out_factor)
    assert not overflowed.any(), "unexpected overflow flag"
    expect = _numpy_oracle(data, dest, capacity)
    for i in range(D):
        total = counts[i].sum()
        assert total == len(expect[i]), f"device {i}: count mismatch"
        np.testing.assert_array_equal(received[i][:total], expect[i])
        np.testing.assert_array_equal(offsets[i], np.cumsum(counts[i]) - counts[i])
    return received, counts


def test_balanced_exchange(mesh):
    capacity = 64
    rng = np.random.default_rng(0)
    data = rng.integers(0, 2**31, size=D * capacity, dtype=np.int32)
    dest = np.tile(np.repeat(np.arange(D, dtype=np.int32), capacity // D), D)
    _check(mesh, data, dest, capacity)


def test_ragged_random_exchange(mesh):
    capacity = 128
    rng = np.random.default_rng(1)
    data = rng.integers(0, 2**31, size=D * capacity, dtype=np.int32)
    dest = rng.integers(0, D, size=D * capacity).astype(np.int32)
    # random loads can exceed send capacity on some receiver -> 2x headroom
    _check(mesh, data, dest, capacity, out_factor=2)


def test_skewed_exchange(mesh):
    """ALS-style skew: ~90% of all rows target device 3 (receiver needs
    8x headroom — the pattern that motivates multi-round chunking)."""
    capacity = 64
    rng = np.random.default_rng(2)
    data = rng.integers(0, 2**31, size=D * capacity, dtype=np.int32)
    dest = np.where(rng.random(D * capacity) < 0.9, 3,
                    rng.integers(0, D, size=D * capacity)).astype(np.int32)
    _check(mesh, data, dest, capacity, out_factor=D)


def test_empty_senders(mesh):
    """Devices 1..7 send nothing; device 0 broadcasts evenly."""
    capacity = 32
    data = np.arange(D * capacity, dtype=np.int32)
    dest = np.full(D * capacity, -1, dtype=np.int32)  # -1 = padding
    dest[:capacity] = np.repeat(np.arange(D, dtype=np.int32), capacity // D)
    received, counts, _, _ = _run_exchange(mesh, data, dest, capacity)
    for i in range(D):
        assert counts[i].sum() == capacity // D
        # all received rows come from device 0
        assert counts[i][0] == capacity // D
        np.testing.assert_array_equal(
            received[i][:capacity // D],
            np.arange(i * (capacity // D), (i + 1) * (capacity // D)))


def test_all_traffic_to_one_device(mesh):
    """Every device sends capacity//D rows, all to device 0 (fits exactly)."""
    capacity = 16
    data = np.arange(D * capacity, dtype=np.int32)
    dest = np.full(D * capacity, -1, dtype=np.int32)
    for j in range(D):
        dest[j * capacity: j * capacity + capacity // D] = 0
    received, counts, _, _ = _run_exchange(mesh, data, dest, capacity)
    assert counts[0].sum() == capacity  # exactly fills device 0's buffer
    for i in range(1, D):
        assert counts[i].sum() == 0
    expect = np.concatenate([np.arange(j * capacity, j * capacity + capacity // D)
                             for j in range(D)])
    np.testing.assert_array_equal(received[0], expect)


def test_multicolumn_rows(mesh):
    """Rows with payload columns ride along."""
    capacity = 32
    rng = np.random.default_rng(3)
    data = rng.integers(0, 255, size=(D * capacity, 4), dtype=np.int32)
    dest = rng.integers(0, D, size=D * capacity).astype(np.int32)
    _check(mesh, data, dest, capacity, out_factor=2)


# ---- partition/sort op tests (single device) ----

def test_hash_partition_range_and_determinism():
    keys = jnp.arange(10_000, dtype=jnp.uint32)
    p1 = hash_partition(keys, 16)
    p2 = hash_partition(keys, 16)
    assert p1.min() >= 0 and p1.max() < 16
    np.testing.assert_array_equal(p1, p2)
    # roughly balanced
    counts = np.bincount(np.asarray(p1), minlength=16)
    assert counts.min() > 10_000 / 16 * 0.7


def test_range_partition_matches_numpy():
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 2**32, size=5000, dtype=np.uint32)
    splitters = sample_splitters(keys[:500], 8)
    dest = np.asarray(range_partition(jnp.array(keys), jnp.array(splitters)))
    expect = np.searchsorted(splitters, keys, side="right")
    np.testing.assert_array_equal(dest, expect)
    assert dest.max() < 8


def test_uniform_splitters_balanced():
    keys = jnp.array(np.random.default_rng(5).integers(
        0, 2**32, size=50_000, dtype=np.uint32))
    spl = uniform_splitters(8, jnp.uint32)
    dest, counts = partition_and_count(keys, spl, 8)
    c = np.asarray(counts)
    assert c.sum() == 50_000
    assert c.min() > 50_000 / 8 * 0.8


def test_sort_kv():
    rng = np.random.default_rng(6)
    keys = jnp.array(rng.integers(0, 2**31, 1000, dtype=np.int32))
    vals = jnp.arange(1000, dtype=jnp.int32)
    sk, sv = sort_kv(keys, vals)
    np.testing.assert_array_equal(np.asarray(sk), np.sort(np.asarray(keys)))
    # values follow their keys
    np.testing.assert_array_equal(np.asarray(keys)[np.asarray(sv)], np.asarray(sk))


def test_sort_kv_multicolumn():
    rng = np.random.default_rng(7)
    keys = jnp.array(rng.integers(0, 1000, 256, dtype=np.int32))
    vals = jnp.array(rng.integers(0, 255, size=(256, 3), dtype=np.int32))
    sk, sv = sort_kv(keys, vals)
    order = np.argsort(np.asarray(keys), kind="stable")
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(vals)[order])


def test_sort_segments_padding():
    keys = jnp.array([5, 3, 9, 7, 0, 0], dtype=jnp.uint32)
    valid = jnp.array([True, True, True, True, False, False])
    sk, _ = sort_segments(keys, valid)
    np.testing.assert_array_equal(np.asarray(sk)[:4], [3, 5, 7, 9])
    assert (np.asarray(sk)[4:] == np.iinfo(np.uint32).max).all()


# -- dense fixed-slot transport (the 32+ chip fallback; executable on CPU) --


def _run_impl(mesh, data, dest, capacity, out_factor, impl):
    exchange = make_shuffle_exchange(mesh, "shuffle", impl=impl,
                                     out_factor=out_factor)
    sharding = jax.NamedSharding(mesh, P("shuffle"))
    received, counts, _, overflowed = jax.block_until_ready(
        exchange(jax.device_put(data, sharding),
                 jax.device_put(dest, sharding)))
    return (np.asarray(received).reshape(D, capacity * out_factor,
                                         *data.shape[1:]),
            np.asarray(counts), np.asarray(overflowed))


def test_dense_bit_identical_to_gather(mesh):
    """No pair over its slot: dense == gather == oracle, bit for bit."""
    capacity = 64
    rng = np.random.default_rng(7)
    data = rng.integers(0, 2**31, size=(D * capacity, 3), dtype=np.int32)
    dest = rng.integers(0, D, size=D * capacity).astype(np.int32)
    dr, dc, dof = _run_impl(mesh, data, dest, capacity, 2, "dense")
    gr, gc, gof = _run_impl(mesh, data, dest, capacity, 2, "gather")
    np.testing.assert_array_equal(dc, gc)
    np.testing.assert_array_equal(dr, gr)
    assert not dof.any() and not gof.any()
    expect = _numpy_oracle(data, dest, capacity)
    for i in range(D):
        np.testing.assert_array_equal(dr[i][:dc[i].sum()], expect[i])


def test_dense_empty_and_one_hot(mesh):
    capacity = 32
    data = np.arange(D * capacity, dtype=np.int32)
    # nobody sends anything
    dest = np.full(D * capacity, -1, np.int32)
    dr, dc, dof = _run_impl(mesh, data, dest, capacity, 2, "dense")
    assert dc.sum() == 0 and not dof.any()
    # everyone sends everything to device 5; per-pair cap rows need
    # out_factor >= D for the slots to fit
    dest = np.full(D * capacity, 5, np.int32)
    dr, dc, dof = _run_impl(mesh, data, dest, capacity, D, "dense")
    assert dc[5].sum() == D * capacity
    assert not dof.any()
    np.testing.assert_array_equal(
        np.sort(dr[5][:D * capacity].ravel()), data)
    assert all(dc[i].sum() == 0 for i in range(D) if i != 5)


def test_dense_pair_overflow_sets_flag_counts_stay_true(mesh):
    """A single (src, dst) pair exceeding its slot must set the explicit
    overflow flag for the receiver ONLY, while reported counts stay the
    TRUE per-source counts (no poisoning — offsets derived from them
    remain meaningful)."""
    capacity, out_factor = 64, 2
    q = capacity * out_factor // D  # 16 per pair
    data = np.arange(D * capacity, dtype=np.int32)
    dest = np.full(D * capacity, -1, np.int32)
    # device 3 sends q+4 rows to device 0 (pair overflow); total to 0 is
    # far under out_cap
    dest[3 * capacity: 3 * capacity + q + 4] = 0
    dr, dc, dof = _run_impl(mesh, data, dest, capacity, out_factor, "dense")
    assert dof[0], "pair overflow flag not set on receiver"
    assert not dof[1:].any(), "overflow flag leaked to clean receivers"
    # counts are the true sent totals, not a poisoned sentinel
    assert dc[0].sum() == q + 4
    assert dc[0][3] == q + 4
    # unaffected devices stay exact (nothing was sent to them)
    assert all(dc[i].sum() == 0 for i in range(1, D))


@pytest.mark.parametrize("impl", ["dense", "ring_interpret"])
def test_slot_transports_below_one_row_per_device(mesh, impl):
    """Receive capacity < D (an even per-pair split would be zero rows):
    the slot transports still run under their own name and agree with
    gather bit for bit — rows, counts and flags — both when everything
    fits (one pair carries the whole buffer) and when a receiver
    overflows."""
    capacity = 2
    data = np.arange(1, D * capacity + 1, dtype=np.int32)
    dest = np.full(D * capacity, -1, np.int32)
    dest[6 * capacity: 7 * capacity] = 1      # device 6 -> 1: both rows
    dest[0] = 4                               # device 0 -> 4: one row
    got = _run_impl(mesh, data, dest, capacity, 1, impl)
    want = _run_impl(mesh, data, dest, capacity, 1, "gather")
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert not got[2].any()
    np.testing.assert_array_equal(got[0][1], data[12:14])
    dest[3 * capacity] = 1                    # a third row for device 1
    got = _run_impl(mesh, data, dest, capacity, 1, impl)
    want = _run_impl(mesh, data, dest, capacity, 1, "gather")
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    assert got[2][1] and got[2].sum() == 1


def test_capacity_overflow_sets_flag(mesh):
    """Aggregate receive past out_capacity sets the flag on native/gather
    paths too (here gather on CPU): every device sends its full buffer to
    device 0 with out_factor 1."""
    capacity = 16
    data = np.arange(D * capacity, dtype=np.int32)
    dest = np.zeros(D * capacity, np.int32)
    _r, dc, dof = _run_impl(mesh, data, dest, capacity, 1, "gather")
    assert dof[0], "capacity overflow flag not set"
    assert not dof[1:].any()
    assert dc[0].sum() == D * capacity  # true counts still reported


# -- ring transport in the oracle matrix (ADVICE r5) ---------------------
#
# test_ring_exchange.py proves ring == gather/dense; these check the
# ring transport against the NUMPY ORACLE directly, through the same
# traffic-pattern matrix the other impls face, so a regression that
# broke ring and dense in lockstep would still be caught.


def _check_impl(mesh, data, dest, capacity, impl, out_factor=1):
    exchange = make_shuffle_exchange(mesh, "shuffle", impl=impl,
                                     out_factor=out_factor)
    sharding = jax.NamedSharding(mesh, P("shuffle"))
    received, counts, offsets, overflowed = jax.block_until_ready(
        exchange(jax.device_put(data, sharding),
                 jax.device_put(dest, sharding)))
    received = np.asarray(received).reshape(D, capacity * out_factor,
                                            *data.shape[1:])
    counts, offsets = np.asarray(counts), np.asarray(offsets)
    assert not np.asarray(overflowed).any(), "unexpected overflow flag"
    expect = _numpy_oracle(data, dest, capacity)
    for i in range(D):
        total = counts[i].sum()
        assert total == len(expect[i]), f"device {i}: count mismatch"
        np.testing.assert_array_equal(received[i][:total], expect[i])
        np.testing.assert_array_equal(offsets[i],
                                      np.cumsum(counts[i]) - counts[i])


@pytest.mark.parametrize("impl", ["ring_interpret", "dense", "gather"])
def test_impl_matrix_balanced_vs_oracle(mesh, impl):
    capacity = 32
    rng = np.random.default_rng(21)
    data = rng.integers(0, 2**31, size=D * capacity, dtype=np.int32)
    dest = np.tile(np.repeat(np.arange(D, dtype=np.int32),
                             capacity // D), D)
    _check_impl(mesh, data, dest, capacity, impl)


@pytest.mark.parametrize("impl", ["ring_interpret", "dense", "gather"])
def test_impl_matrix_ragged_vs_oracle(mesh, impl):
    capacity = 32
    rng = np.random.default_rng(22)
    data = rng.integers(0, 2**31, size=(D * capacity, 2), dtype=np.int32)
    dest = rng.integers(0, D, size=D * capacity).astype(np.int32)
    # out_factor 4: the fixed-slot transports (dense/ring) cap each
    # (src, dst) PAIR at capacity*out_factor/D rows — random raggedness
    # needs pair headroom, not just aggregate headroom
    _check_impl(mesh, data, dest, capacity, impl, out_factor=4)


@pytest.mark.parametrize("impl", ["ring_interpret", "dense", "gather"])
def test_impl_matrix_empty_senders_vs_oracle(mesh, impl):
    capacity = 16
    data = np.arange(D * capacity, dtype=np.int32)
    dest = np.full(D * capacity, -1, dtype=np.int32)  # -1 = padding
    dest[:capacity] = np.repeat(np.arange(D, dtype=np.int32),
                                capacity // D)
    _check_impl(mesh, data, dest, capacity, impl)


def test_terasort_ring_interpret_matches_numpy_baseline(mesh):
    """End-to-end terasort over the ring transport against the NUMPY
    baseline (test_ring_exchange.py checks ring == gather; this pins
    the ring path to the ground-truth sort itself: full verification
    plus the exact per-partition key sequence — payload order under
    equal keys is the one legitimate divergence from the stable CPU
    sort, so keys compare exactly and rows verify structurally)."""
    from sparkrdma_tpu.models.terasort import (
        TeraSortConfig, generate_rows, numpy_terasort, run_terasort,
        verify_terasort)
    cfg = TeraSortConfig(rows_per_device=128, payload_words=2,
                         out_factor=2)
    rows = generate_rows(cfg, D, seed=23)
    out, counts, _ = run_terasort(mesh, cfg, impl="ring_interpret",
                                  rows=rows)
    verify_terasort(out, counts, rows, D)
    want = numpy_terasort(rows, D)
    per_dev = out.reshape(D, -1, out.shape[-1])
    got = np.concatenate([per_dev[i][:counts[i].sum()] for i in range(D)])
    np.testing.assert_array_equal(got[:, 0], want[:, 0])
