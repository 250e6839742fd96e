"""``models.als``: MLlib's blocked ALS on the device plane, at toy size on
the virtual CPU mesh. The program is the chip's (no option selects a
form); the float64 reference is ``benchmark/reference_als.py``, which
imports nothing of the program."""

import json
import os
import sys

import jax
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import Mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import reference_als  # noqa: E402
from sparkrdma_tpu.models import als  # noqa: E402
from sparkrdma_tpu.models.als import (  # noqa: E402
    ALSConfig,
    ALSJob,
    Ratings,
    block_ratings,
    factors_by_id,
    netflix_like_ratings,
    place_als,
)
from sparkrdma_tpu.parallel import exchange  # noqa: E402
from sparkrdma_tpu.utils.trace import Tracer  # noqa: E402

AXIS = "shuffle"
CFG = ALSConfig(num_users=403, num_items=61)   # neither a multiple of 4


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), (AXIS,))


def _ratings(cfg=CFG, num=20_000, seed=3):
    return netflix_like_ratings(cfg, num, seed, item_top_share=0.06,
                                user_top_share=0.01)


def _run(cfg, ratings, n, iterations=2, seed=7, tracer=None):
    """A job over ``n`` devices: its factors after each sweep in id order,
    the factors it started from, and the resident blocks."""
    mesh = _mesh(n)
    resident = place_als(mesh, AXIS, block_ratings(cfg, ratings, n))
    job = ALSJob(mesh, AXIS, cfg, iterations, seed)
    if tracer is not None:
        job.tracer = tracer
    users, items = job(resident)
    steps = [(factors_by_id(i, cfg.num_items, n),
              factors_by_id(u, cfg.num_users, n))
             for i, u in job.trajectory(resident)]
    np.testing.assert_array_equal(steps[-1][1],
                                  factors_by_id(users, cfg.num_users, n))
    np.testing.assert_array_equal(steps[-1][0],
                                  factors_by_id(items, cfg.num_items, n))
    return steps, job.initial_user_factors(), resident, job


@pytest.fixture(scope="module")
def ratings():
    return _ratings()


@pytest.fixture(scope="module")
def one_device(ratings):
    return _run(CFG, ratings, 1)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_job_is_the_reference_whatever_the_blocking(ratings, one_device, n):
    """The routing's test: the same factors over 1, 2 and 4 blocks a side,
    every half-step within float32's bound of the reference's."""
    steps, start, _, _ = one_device if n == 1 else _run(CFG, ratings, n)
    np.testing.assert_array_equal(start, one_device[1])
    problems, readings = reference_als.als_report(
        steps, *ratings, start, CFG.reg)
    assert problems == [] and readings["bound_share"] < 0.05
    # against the whole float64 job too, and against one block a side
    want_users, want_items = reference_als.reference_als(
        *ratings, start, CFG.num_items, CFG.reg, 2)
    np.testing.assert_allclose(steps[-1][1], want_users, atol=2e-4)
    np.testing.assert_allclose(steps[-1][0], want_items, atol=2e-4)
    np.testing.assert_allclose(steps[-1][1], one_device[0][-1][1], atol=5e-5)


def test_out_blocks_send_a_vector_once_to_each_block_that_needs_it(ratings):
    n = 4
    user_side, item_side = block_ratings(CFG, ratings, n)
    for side, dst, src in ((item_side, ratings.item, ratings.user),
                           (user_side, ratings.user, ratings.item)):
        needed = set(zip(src.tolist(), (dst % n).tolist()))
        sent = [(int(local) * n + s, int(d))
                for s in range(n)
                for local, d in zip(side.out_idx[s], side.out_dest[s])
                if d < n]
        assert len(sent) == len(set(sent)) == side.out_links
        assert set(sent) == needed
        # grouped by destination, ascending, as the exchange wants them
        for s in range(n):
            assert (np.diff(side.out_dest[s]) >= 0).all()
        assert side.count.sum() == len(dst)


def test_received_is_the_out_links_in_every_half_step(ratings, tmp_path):
    tracer = Tracer()
    _, _, resident, job = _run(CFG, ratings, 4, iterations=2, tracer=tracer)
    tracer.dump(str(tmp_path / "trace.json"))
    with open(tmp_path / "trace.json") as f:
        events = {e["name"]: e for e in json.load(f)["traceEvents"]}
    links = [resident.item_side.out_links, resident.user_side.out_links]
    assert events["als.job"]["args"]["received"] == links * 2
    assert events["als.job"]["args"]["row_move"] == "take"
    assert 0 < events["als.recv_fill"]["args"]["value"] <= 1.0
    assert events["als.out_links"]["args"]["value"] == sum(links)
    assert events["als.max_segment"]["args"]["value"] == max(
        np.bincount(ratings.item).max(), np.bincount(ratings.user).max())
    assert {"als.dispatch", "als.wait"} <= set(events)


def test_ten_word_rows_take_the_order_path_and_cross_as_rows(one_device):
    assert exchange.grouping_form(CFG.rank) == "order"
    assert exchange.wire_form(CFG.rank) == "rows"
    job = one_device[3]
    for side in als.SIDES:
        # the grouping's move and the per-rating gather, both recorded
        assert job._steps[side].row_moves == ["take", "take"]


def test_a_hub_item_and_an_id_nobody_rates():
    """A third of all ratings on one item, a user and an item with no
    rating at all: the hub's segment is long, the empty ids get zeros."""
    cfg = ALSConfig(num_users=200, num_items=40)
    rng = np.random.default_rng(11)
    num = 6_000
    user = rng.integers(0, cfg.num_users - 1, num).astype(np.int32)
    item = rng.integers(0, cfg.num_items - 1, num).astype(np.int32)
    item[rng.random(num) < 1 / 3] = 5
    data = Ratings(user, item, rng.integers(1, 6, num).astype(np.float32))
    steps, start, resident, _ = _run(cfg, data, 4)
    assert resident.item_side.max_segment == np.bincount(item).max() > num / 3
    assert reference_als.als_problems(steps, *data, start, cfg.reg) == []
    for items, users in steps:
        assert not items[cfg.num_items - 1].any()
        assert not users[cfg.num_users - 1].any()
        assert items[5].any()


def test_repeated_pairs_are_ratings_of_their_own():
    rng = np.random.default_rng(12)
    cfg = ALSConfig(num_users=50, num_items=12)
    user = rng.integers(0, 50, 900).astype(np.int32)
    item = rng.integers(0, 12, 900).astype(np.int32)
    rating = rng.integers(1, 6, 900).astype(np.float32)
    # every rating thrice: the pair (user, item) repeats with other values
    data = Ratings(np.tile(user, 3), np.tile(item, 3),
                   np.concatenate([rating, rating[::-1], rating]))
    steps, start, resident, _ = _run(cfg, data, 2)
    assert resident.num_ratings == 2_700
    assert reference_als.als_problems(steps, *data, start, cfg.reg) == []
    once = Ratings(user, item, rating)
    assert reference_als.als_problems(steps, *once, start, cfg.reg) != []


def test_the_ridge_is_reg_times_the_count(ratings, one_device):
    """MLlib's ``numExplicits * regParam``: a job is the reference's at
    ``reg * n`` on the diagonal, and a plain ``reg`` is far outside the
    bound."""
    steps, start = one_device[0], one_device[1]
    by_item = reference_als.Side(ratings.item, ratings.user, ratings.rating,
                                 CFG.num_items)
    want, bound = by_item.half_step(start, CFG.reg)
    share, _, _ = reference_als._share(steps[0][0], want, bound)
    assert share < reference_als.BOUND_SHARE
    # the same solve with reg alone on the diagonal, for the first item
    d = int(np.flatnonzero(by_item.count)[0])
    y = np.asarray(start, np.float64)[ratings.user[ratings.item == d]]
    r = ratings.rating[ratings.item == d].astype(np.float64)
    plain = np.linalg.solve(y.T @ y + CFG.reg * np.eye(CFG.rank), y.T @ r)
    assert np.abs(plain - steps[0][0][d]).max() > 100 * bound[d]


def test_overflow_raises_and_names_the_half_step():
    """Every rating on items of block 0: each user block sends all its
    users there, four times the longest OutBlock, past ``out_factor`` 2."""
    cfg = ALSConfig(num_users=80, num_items=16)
    rng = np.random.default_rng(13)
    data = Ratings(np.repeat(np.arange(80, dtype=np.int32), 5),
                   (4 * rng.integers(0, 4, 400)).astype(np.int32),
                   rng.integers(1, 6, 400).astype(np.float32))
    mesh = _mesh(4)
    resident = place_als(mesh, AXIS, block_ratings(cfg, data, 4))
    with pytest.raises(OverflowError, match=r"\['0:item', '1:item'\]"):
        ALSJob(mesh, AXIS, cfg, 2)(resident)
    roomy = ALSConfig(num_users=80, num_items=16, out_factor=4)
    resident = place_als(mesh, AXIS, block_ratings(roomy, data, 4))
    users, _ = ALSJob(mesh, AXIS, roomy, 1)(resident)
    assert np.isfinite(np.asarray(users)).all()


def test_run_als_returns_the_history(ratings):
    users, items, history = als.run_als(_mesh(4), CFG, ratings, 3, seed=7)
    assert users.shape == (CFG.num_users, CFG.rank)
    assert items.shape == (CFG.num_items, CFG.rank)
    assert history[-1] == pytest.approx(als.rmse(ratings, users, items))
    assert history[0] > history[1] > history[2]


def _ratings_of_the_layout(side, n):
    """``(dst id, src id, rating)`` of every valid slot of ``side``'s
    InBlocks, read back through the OutBlocks; asserts on the way that a
    slot's rank is under the length of its source's OutBlock for the
    device, and that a device's tiles ascend by destination id."""
    out = []
    for d in range(n):
        _, _, nt = side.src_pos[d].shape
        c, j, t = np.nonzero(side.src_pos[d] >= 0)
        pos = side.src_pos[d][c, j, t]
        rank, device = pos // n, pos % n
        src = np.empty_like(pos)
        for s in range(n):
            # s's OutBlock for d
            sent = side.out_idx[s][side.out_dest[s] == d]
            of_s = device == s
            assert (rank[of_s] < len(sent)).all()
            src[of_s] = sent[rank[of_s]] * n + s
        assert (np.diff(side.tile_dst[d]) >= 0).all()
        dst = side.tile_dst[d][c * nt + t] * n + d
        out.append(np.stack([dst, src, side.rating[d][c, j, t]], axis=1))
    return np.concatenate(out)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_every_rating_sits_in_exactly_one_slot(ratings, n):
    user_side, item_side = block_ratings(CFG, ratings, n)
    for side, dst, src in ((item_side, ratings.item, ratings.user),
                           (user_side, ratings.user, ratings.item)):
        got = _ratings_of_the_layout(side, n)
        want = np.stack([dst, src, ratings.rating], axis=1)
        assert len(got) == len(want)
        np.testing.assert_array_equal(got[np.lexsort(got.T)],
                                      want[np.lexsort(want.T)])
        np.testing.assert_array_equal(
            side.count.T.reshape(-1)[:dst.max() + 1], np.bincount(dst))


def test_shapes_do_not_follow_the_seed(monkeypatch):
    """Sixteen seeds, sixteen sets of block lengths, ONE set of shapes:
    the draws enter through a maximum rounded up to whole chunks, so a
    compile cache keyed on the program's text is warm under a new seed
    (a program's shapes may follow the traffic file, never the seed)."""
    # over these seeds the fullest device of the items' half-step has
    # 1,734-2,115 tiles and that of the users' 1,629-1,758: two chunks of
    # 1,100 each
    monkeypatch.setattr(als, "_CHUNK_TILES", 1100)
    shapes, lengths = set(), set()
    for seed in range(16):
        sides = block_ratings(CFG, _ratings(num=200_000, seed=seed), 4)
        shapes.add(tuple(a.shape for side in sides for a in side[:6]))
        lengths.add(tuple(int((side.tile_dst[d] < side.count.shape[1]).sum())
                          for side in sides for d in range(4)))
    assert len(shapes) == 1 and len(lengths) == 16
    # the rounding is what holds the shape
    assert next(iter(shapes))[2] == next(iter(shapes))[8] == (
        4, 2, als.TILE, 1100)


@pytest.mark.parametrize("side", als.SIDES)
def test_a_half_step_lowers_to_one_text_with_no_select_over_gathered_rows(
        ratings, side):
    """The compile cache's key is the program's text: two builds of one
    shape lower to the same text. And the gather is clipped, not filled:
    no select runs over a chunk's rows as they were gathered (on the v5e
    they are padded to 128 lanes; ``PERF.md`` section 6, PR 37); the
    padding slots are masked as columns."""
    mesh = _mesh(4)
    resident = place_als(mesh, AXIS, block_ratings(CFG, ratings, 4))
    blocks = resident.item_side if side == "item" else resident.user_side
    factors = np.zeros(
        (4 * als.ids_per_block(CFG.num_users if side == "item"
                               else CFG.num_items, 4), CFG.rank), np.float32)
    texts = {als.make_als_half_step(mesh, AXIS, CFG, side)
             .lower(factors, *blocks.arrays).as_text() for _ in range(2)}
    assert len(texts) == 1
    _, tile, nt = blocks.src_pos.shape     # [D * chunks, TILE, NT]
    rows = f"tensor<{tile * nt}x{CFG.rank}xf32>"
    lines = next(iter(texts)).splitlines()
    assert any("stablehlo.gather" in line and rows in line for line in lines)
    assert not any("stablehlo.select" in line and rows in line
                   for line in lines)
    assert any("stablehlo.select" in line
               and f"tensor<{CFG.rank}x{tile}x{nt}xf32>" in line
               for line in lines)


@pytest.mark.parametrize("side", als.SIDES)
def test_a_half_step_is_one_scope_and_its_names_are_in_the_cache_key(
        ratings, side):
    """Every op of a half-step lies under ``als.<side>_step`` (a job runs
    two programs whose ops share names: a profile tells them apart by
    it), the four phases inside it. The names are metadata: a cached
    executable of a build without them must not be served for this
    program (on the chip the parent's was, and the profile had no
    ``als.item_step``: ``PERF.md`` section 6, PR 38)."""
    import re

    mesh = _mesh(2)
    resident = place_als(mesh, AXIS, block_ratings(CFG, ratings, 2))
    blocks = resident.item_side if side == "item" else resident.user_side
    factors = np.zeros(
        (2 * als.ids_per_block(CFG.num_users if side == "item"
                               else CFG.num_items, 2), CFG.rank), np.float32)
    hlo = als.make_als_half_step(mesh, AXIS, CFG, side).lower(
        factors, *blocks.arrays).compile().as_text()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    named = re.findall(r'op_name="(jit\(step\)/shard_map/[^"]*)"', hlo)
    # (the compiler's own hoisted broadcasts carry no scope at all)
    assert named and all(f"/als.{side}_step/" in path
                         for path in named if "/als." in path)
    for phase in ("exchange", "gather", "normal", "solve"):
        assert any(f"/als.{phase}/" in path for path in named), phase


# -- the generator ------------------------------------------------------------

def test_netflix_like_ratings_are_seeded_with_the_two_top_shares():
    cfg = ALSConfig(num_users=5_000, num_items=300)
    a = netflix_like_ratings(cfg, 600_000, 5, item_top_share=0.03,
                             user_top_share=0.004)
    b = netflix_like_ratings(cfg, 600_000, 5, item_top_share=0.03,
                             user_top_share=0.004)
    c = netflix_like_ratings(cfg, 600_000, 6, item_top_share=0.03,
                             user_top_share=0.004)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.item, c.item)
    assert np.bincount(a.item).max() / 600_000 == pytest.approx(0.03,
                                                                rel=0.05)
    assert np.bincount(a.user).max() / 600_000 == pytest.approx(0.004,
                                                                rel=0.05)
    assert set(np.unique(a.rating)) == {1.0, 2.0, 3.0, 4.0, 5.0}
    assert a.user.dtype == a.item.dtype == np.int32
    assert a.rating.dtype == np.float32
    assert a.user.max() < cfg.num_users and a.item.max() < cfg.num_items
    # Netflix's own shares give the exponents the configuration records
    assert als.zipf_exponent(17_770, 0.00232) == pytest.approx(0.45, abs=0.02)
    assert als.zipf_exponent(480_189, 0.000176) == pytest.approx(0.37,
                                                                 abs=0.02)


def test_rating_chunks_do_not_depend_on_the_thread_count(monkeypatch):
    cfg = ALSConfig(num_users=300, num_items=40)
    monkeypatch.setattr(als, "_RATING_CHUNK", 1 << 10)
    many = netflix_like_ratings(cfg, 10_000, 9, 0.08, 0.02)

    class OneThread(als.ThreadPoolExecutor):
        def __init__(self, max_workers=None):
            super().__init__(max_workers=1)

    monkeypatch.setattr(als, "ThreadPoolExecutor", OneThread)
    one = netflix_like_ratings(cfg, 10_000, 9, 0.08, 0.02)
    for x, y in zip(many, one):
        np.testing.assert_array_equal(x, y)


# -- the limit ----------------------------------------------------------------

def test_bfloat16_factors_fail_the_bound_by_the_stated_factor():
    """Netflix's density on the user side (~200 ratings a user): float32
    reads a few thousandths of the bound, source factors rounded to
    bfloat16 several times the bound, so the limit of 0.1 has an order
    of magnitude on both sides. Through ``als_report``, which is what
    decides a run's ``correct``."""
    cfg = ALSConfig(num_users=2_000, num_items=80)
    data = netflix_like_ratings(cfg, 400_000, 1, item_top_share=0.05,
                                user_top_share=0.002)
    steps, start, _, _ = _run(cfg, data, 4, iterations=1)
    items = steps[0][0]
    problems, float32 = reference_als.als_report(steps, *data, start,
                                                 cfg.reg)
    assert problems == []
    assert float32["bound_share"] < 0.01 < reference_als.BOUND_SHARE
    # the users' half-step as a bfloat16 wire or a matrix unit's default
    # precision would leave it: the items' vectors rounded on the way
    by_user = reference_als.Side(data.user, data.item, data.rating,
                                 cfg.num_users)
    rounded, _ = by_user.half_step(items, cfg.reg,
                                   factor_dtype=ml_dtypes.bfloat16)
    problems, bfloat16 = reference_als.als_report(
        [(items, rounded.astype(np.float32))], *data, start, cfg.reg)
    assert any("user" in p and "times what float32" in p for p in problems)
    assert all(p.startswith("sweep 0: user") or "RMSE" in p
               for p in problems)
    assert bfloat16["bound_share"] > 20 * reference_als.BOUND_SHARE
    assert bfloat16["bound_share"] > 500 * float32["bound_share"]
    # and a lost factor row: one item's vector zeroed on the way
    lost = items.copy()
    lost[np.bincount(data.item).argmax()] = 0
    moved, _ = by_user.half_step(lost, cfg.reg)
    problems, readings = reference_als.als_report(
        [(items, moved.astype(np.float32))], *data, start, cfg.reg)
    assert problems and readings["bound_share"] > 100
