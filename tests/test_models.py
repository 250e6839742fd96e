"""Model workload tests on the 8-device virtual mesh: PageRank (iterative),
ALS (the blocked factor shuffle), shuffle join — BASELINE.md configs
#3/#4/#5 at test scale, all oracle-verified."""

import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference_als import reference_als  # noqa: E402
from sparkrdma_tpu.models.als import (  # noqa: E402
    ALSConfig,
    ALSJob,
    block_ratings,
    factors_by_id,
    netflix_like_ratings,
    place_als,
    run_als,
)
from sparkrdma_tpu.models.join import (  # noqa: E402
    JoinConfig,
    generate_tables,
    numpy_join,
    run_join,
)
from sparkrdma_tpu.models.pagerank import (  # noqa: E402
    PageRankConfig,
    numpy_pagerank,
    random_graph,
    run_pagerank,
)
from sparkrdma_tpu.parallel.exchange import chunked_exchange  # noqa: E402

D = 8


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:D]), ("shuffle",))


# ---- chunked exchange (the skew machinery) ----

def test_chunked_exchange_extreme_skew(mesh):
    """All rows from all devices target device 0; quota bounds each round."""
    per_dev = 64
    rows = np.arange(D * per_dev, dtype=np.uint32).reshape(-1, 1)
    counts = np.zeros((D, D), dtype=np.int32)
    counts[:, 0] = per_dev  # everything -> device 0, already "grouped"
    received, rounds = chunked_exchange(mesh, "shuffle", rows, counts, quota=16)
    assert rounds == 4  # 64 / 16
    assert len(received[0]) == D * per_dev
    for d in range(1, D):
        assert len(received[d]) == 0
    # every row arrives exactly once
    np.testing.assert_array_equal(np.sort(received[0].ravel()),
                                  np.arange(D * per_dev, dtype=np.uint32))


def test_chunked_exchange_mixed_traffic(mesh):
    rng = np.random.default_rng(0)
    per_dev = 50
    rows = np.zeros((D * per_dev, 2), dtype=np.uint32)
    counts = np.zeros((D, D), dtype=np.int32)
    expect = [[] for _ in range(D)]
    for d in range(D):
        dest = np.sort(rng.integers(0, D, size=per_dev))
        seg = np.stack([dest.astype(np.uint32),
                        rng.integers(0, 2**31, per_dev, dtype=np.uint32)], 1)
        rows[d * per_dev:(d + 1) * per_dev] = seg
        counts[d] = np.bincount(dest, minlength=D)
        for i in range(D):
            expect[i].append(seg[dest == i])
    received, rounds = chunked_exchange(mesh, "shuffle", rows, counts, quota=7)
    assert rounds > 1
    for i in range(D):
        # exact source-grouped order: same contract as the one-shot exchange
        np.testing.assert_array_equal(received[i], np.concatenate(expect[i]))


# ---- PageRank ----

def test_pagerank_matches_oracle(mesh):
    cfg = PageRankConfig(num_vertices=64, edges_per_device=96, out_factor=D)
    edges, _, _ = random_graph(cfg, D, seed=3)
    ranks = run_pagerank(mesh, cfg, iterations=5, seed=3)
    expect = numpy_pagerank(edges, cfg.num_vertices, cfg.damping, 5)
    np.testing.assert_allclose(ranks, expect, rtol=1e-4)
    assert abs(ranks.sum() - 1.0) < 0.2  # probability-ish mass


def test_pagerank_converges(mesh):
    cfg = PageRankConfig(num_vertices=32, edges_per_device=64, out_factor=D)
    r5 = run_pagerank(mesh, cfg, iterations=5, seed=1)
    r20 = run_pagerank(mesh, cfg, iterations=20, seed=1)
    r21 = run_pagerank(mesh, cfg, iterations=21, seed=1)
    assert np.abs(r21 - r20).max() < np.abs(r5 - r20).max()


# ---- ALS ----

def _als_job(mesh, cfg, ratings, iterations, seed):
    resident = place_als(mesh, "shuffle", block_ratings(cfg, ratings, D))
    job = ALSJob(mesh, "shuffle", cfg, iterations, seed)
    return job, resident


def test_als_skewed_half_step_matches_oracle(mesh):
    """Items from the seeded user factors over 8 blocks a side, a hub item
    with a tenth of the ratings: the first half-step of a job against the
    float64 reference (``benchmark/reference_als.py``)."""
    cfg = ALSConfig(num_users=64, num_items=16, rank=4)
    ratings = netflix_like_ratings(cfg, 640, seed=5, item_top_share=0.1,
                                   user_top_share=0.03)
    job, resident = _als_job(mesh, cfg, ratings, 1, seed=5)
    items, _ = job.trajectory(resident)[0]
    _, expect = reference_als(*ratings, job.initial_user_factors(),
                              cfg.num_items, cfg.reg, 1)
    np.testing.assert_allclose(factors_by_id(items, cfg.num_items, D),
                               expect, rtol=1e-4, atol=1e-5)
    assert resident.item_side.max_segment == np.bincount(ratings.item).max()


def test_als_user_half_step_matches_oracle(mesh):
    """The user-side half-step is another program (its In/OutBlocks, its
    receive buffer): users from the items the job itself solved."""
    cfg = ALSConfig(num_users=64, num_items=16, rank=4)
    ratings = netflix_like_ratings(cfg, 640, seed=6, item_top_share=0.1,
                                   user_top_share=0.03)
    job, resident = _als_job(mesh, cfg, ratings, 1, seed=6)
    users, items = job(resident)
    expect, expect_items = reference_als(
        *ratings, job.initial_user_factors(), cfg.num_items, cfg.reg, 1)
    np.testing.assert_allclose(factors_by_id(items, cfg.num_items, D),
                               expect_items, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(factors_by_id(users, cfg.num_users, D),
                               expect, rtol=1e-4, atol=1e-5)


def test_als_full_alternating_loop_converges(mesh):
    """The full users<->items loop must actually FIT the ratings: the
    train RMSE falls sweep after sweep (config #5's workload semantics,
    not just its shuffle shape)."""
    cfg = ALSConfig(num_users=96, num_items=24, rank=6)
    ratings = netflix_like_ratings(cfg, 1_280, seed=8, item_top_share=0.1,
                                   user_top_share=0.03)
    _uf, _if, history = run_als(mesh, cfg, ratings, iterations=4, seed=8)
    # monotone improvement every sweep; unstructured uniform ratings
    # floor near their intrinsic noise
    assert all(b <= a for a, b in zip(history, history[1:])), history
    # a rank-6 fit of uniform 1..5 ratings (sigma 1.41) beats the mean
    assert history[-1] < 1.2, f"did not fit: {history}"


# ---- join ----

def test_join_matches_oracle(mesh):
    cfg = JoinConfig(rows_per_device_left=128, rows_per_device_right=96,
                     key_space=256, out_factor=4)
    left, right = generate_tables(cfg, D, seed=7)
    matches, pair_sum = run_join(mesh, cfg, seed=7)
    exp_matches, exp_sum = numpy_join(left, right)
    assert matches == exp_matches
    assert pair_sum == exp_sum


def test_join_no_matches(mesh):
    cfg = JoinConfig(rows_per_device_left=32, rows_per_device_right=32,
                     key_space=4, out_factor=D)
    left, right = generate_tables(cfg, D, seed=9)
    left[:, 0] = 0
    right[:, 0] = 1

    from jax.sharding import NamedSharding, PartitionSpec as P
    from sparkrdma_tpu.models.join import make_join_step
    step = make_join_step(mesh, "shuffle", cfg)
    shard = NamedSharding(mesh, P("shuffle"))
    counts, sums, _ = step(jax.device_put(left, shard),
                           jax.device_put(right, shard))
    assert int(np.asarray(counts).sum()) == 0
    assert int(np.asarray(sums).sum()) == 0


def test_chunked_exchange_device_resident_at_als_scale(mesh):
    """VERDICT r2 item 3: >=64 rounds on the 8-device mesh with the round
    loop doing no per-round host data work — outputs accumulate in device
    buffers and cross to the host once. Asserts exactness, bounded host
    allocations during the loop, and logs the legacy-hostloop A/B time."""
    import time
    import tracemalloc

    from sparkrdma_tpu.parallel.exchange import (
        NamedSharding,
        P,
        jax as jax_mod,
        make_chunked_exchange,
        make_chunked_exchange_acc,
    )

    quota = 32
    heavy = 64 * quota  # pair (s, 0) traffic -> exactly 64 rounds
    light = 40
    width = 8
    rng = np.random.default_rng(5)
    counts = np.full((D, D), light, dtype=np.int32)
    counts[:, 0] = heavy
    total = int(counts.sum())
    rows = np.zeros((D, heavy + (D - 1) * light, width), dtype=np.uint32)
    expect = [[] for _ in range(D)]
    for s in range(D):
        segs = []
        for d in range(D):  # destination-grouped layout per source
            seg = rng.integers(0, 2**31, (counts[s, d], width),
                               dtype=np.uint32)
            segs.append(seg)
            expect[d].append(seg)
        rows[s] = np.concatenate(segs)
    rows = rows.reshape(D * rows.shape[1], width)

    chunked_exchange(mesh, "shuffle", rows, counts, quota=quota)  # warm

    tracemalloc.start()
    t0 = time.monotonic()
    received, rounds = chunked_exchange(mesh, "shuffle", rows, counts,
                                        quota=quota)
    new_time = time.monotonic() - t0
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()

    assert rounds == 64
    for d in range(D):
        # exact source-major contract straight out of the device buffer
        np.testing.assert_array_equal(received[d], np.concatenate(expect[d]))
    # the loop must not have staged the dataset on the host per round:
    # peak python/numpy allocations stay near the ONE final transfer of
    # the padded device buffer (D*cap_out rows; skew pads it), far under
    # 64 rounds x per-round staging
    final_bytes = total * width * 4
    cap_out = int(counts.sum(axis=0).max())
    padded_bytes = D * cap_out * width * 4
    assert peak < padded_bytes + 2 * final_bytes + (1 << 20), \
        f"host peak {peak} suggests per-round host staging"

    # legacy host-loop A/B (the pre-rework driver, reconstructed): pulls
    # every round's full mesh output to the host and slices O(D^2) segments
    round_fn = make_chunked_exchange(mesh, "shuffle", quota)
    sharding = NamedSharding(mesh, P("shuffle"))
    grouped_d = jax_mod.device_put(rows, sharding)
    counts_d = jax_mod.device_put(counts.reshape(-1), sharding)
    round_fn(grouped_d, counts_d, 0)  # warm (compile) before timing
    t0 = time.monotonic()
    per_source = [[[] for _ in range(D)] for _ in range(D)]
    for r in range(rounds):
        out, rc = round_fn(grouped_d, counts_d, r)
        out = np.asarray(out).reshape(D, quota * D, width)
        rc = np.asarray(rc)
        for d in range(D):
            start = 0
            for j in range(D):
                c = int(rc[d][j])
                if c:
                    per_source[d][j].append(out[d][start:start + c])
                start += c
    legacy = [np.concatenate([seg for j in range(D)
                              for seg in per_source[d][j]])
              for d in range(D)]
    legacy_time = time.monotonic() - t0
    for d in range(D):
        np.testing.assert_array_equal(received[d], legacy[d])
    print(f"\nchunked 64 rounds: device-resident {new_time:.3f}s vs "
          f"legacy host-loop {legacy_time:.3f}s "
          f"(host peak {peak / 1e6:.1f} MB, moved {final_bytes / 1e6:.1f} MB)")
