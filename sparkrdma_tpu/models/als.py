"""ALS (alternating least squares) as MLlib runs it: the blocked factor
shuffle.

BASELINE.md config #5: ``org.apache.spark.ml.recommendation.ALS``,
explicit feedback, over 100 M ratings. MLlib never shuffles the ratings.
Users hash to user blocks and items to item blocks; once a job's data is
loaded each side builds, and caches, an **InBlock** per destination block
(its ratings grouped by destination id, each with the place of its source's
factor vector and the rating) and an **OutBlock** per source block (for
each destination block, the source ids whose vectors that block needs).
A half-step then shuffles FACTOR VECTORS: every source block sends each
destination block the vectors its OutBlock lists for it, a vector to every
block that rates it and to no other (a multicast, not a partition), and
each destination id ``d`` with ratings ``r_ds`` over its sources ``S_d``
solves ``(sum y_s y_s^T + reg * |S_d| * I) x_d = sum r_ds * y_s`` by
Cholesky: the regulariser is scaled by the number of ratings (MLlib's
``numExplicits * regParam``).

TPU-native design: a block of each side a device (user ``u`` in block
``u % D``, item ``i`` in block ``i % D``), blocked ONCE on the host
(``block_ratings``: the system's "map output registered once") and placed
in HBM (``place_als``). One half-step is one jitted SPMD program of four
scopes (``make_als_half_step``):

1. ``als.exchange``: gather the source factors the OutBlock lists and
   send them through ``exchange.shuffle_shard``, the generic path: rows of
   ``rank`` 4-byte words, grouped by an order vector, one wire row a row;
2. ``als.gather``: every rating reads its source's vector out of the
   receive buffer by one clipped ``take`` a chunk (every index is in the
   table by construction: ``take``'s default, a fill of what lies outside,
   is a select over all that was gathered); the InBlock holds the source's
   place as ``(source device, rank in that device's OutBlock for me)``, so
   the step adds ``recv_offsets[source device]`` and nothing is scattered
   on arrival;
3. ``als.normal``: the ``k(k+1)/2 + k`` products a rating and their sums a
   destination id, in float32 on the vector unit (no ``dot``: nothing for
   the matrix unit's bfloat16 passes to round);
4. ``als.solve``: ``reg * count`` on the diagonal, Cholesky, two
   triangular solves.

How the segments are summed (the builder's choice; PERF.md section 6,
PR 35). A destination id's ratings are laid out by ``block_ratings`` in
*tiles* of ``TILE`` ratings of ONE id (the last tile of an id padded), and
the tiles in chunks of ``[TILE, chunk_tiles]``: rating ``j`` of a chunk's
tile ``t`` at ``[j, t]``. Per-rating values are then COLUMNS, with the
tiles along the lanes: a tile's sum is ``TILE - 1`` adds of full vectors,
the ``[R, 65]`` array of products is never whole (nor padded to 128 lanes,
which would make 12.8 GB of 25 M rows of 10 words), and what is left for a
segmented sum is one row a tile (``jax.ops.segment_sum`` over sorted tile
ids), 1/32 of the ratings. The price is the padding: half a tile an id on
average, 7.6 % more gathers for 120,047 users of ~209 ratings each.

``ALSJob`` queues ``2 x iterations`` programs and blocks once; factors
never leave HBM.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.ops.row_permute import forms_label
from sparkrdma_tpu.parallel.exchange import (
    record_exchange,
    resolve_impl,
    row_mover,
    shuffle_shard,
)
from sparkrdma_tpu.utils import trace

SIDES = ("item", "user")   # a sweep's half-steps, in order: the side SOLVED

TILE = 32                  # ratings of one destination id a tile
_CHUNK_TILES = 1 << 15     # tiles a chunk at most: 2^20 ratings a chunk
_RATING_CHUNK = 1 << 20    # ratings a generator task draws; part of the seeding


@dataclass(frozen=True)
class ALSConfig:
    num_users: int
    num_items: int
    rank: int = 10
    reg: float = 0.1
    out_factor: int = 2


class Ratings(NamedTuple):
    """``(user, item, rating)`` triples; a repeated pair is a rating of
    its own, as MLlib treats it."""
    user: np.ndarray      # i32[R]
    item: np.ndarray      # i32[R]
    rating: np.ndarray    # f32[R]


def ids_per_block(num_ids: int, num_devices: int) -> int:
    """Ids a block holds (id ``e`` is local index ``e // D`` of block
    ``e % D``); the last blocks' spare places are ids nobody rates."""
    return -(-num_ids // num_devices)


def factors_by_id(factors, num_ids: int, num_devices: int) -> np.ndarray:
    """A job's block-ordered factors ``[D * ids_per_block, k]`` on the
    host in id order ``[num_ids, k]``."""
    f = np.asarray(factors)
    k = f.shape[1]
    return (f.reshape(num_devices, -1, k).transpose(1, 0, 2)
            .reshape(-1, k)[:num_ids])


# ---------------------------------------------------------------------------
# the data: Netflix's shape from a seed
# ---------------------------------------------------------------------------

def zipf_exponent(num_ids: int, top_share: float) -> float:
    """The exponent ``s`` of a Zipf bounded to ``num_ids`` ids whose most
    drawn id has ``top_share`` of the draws: ``1 / sum(k^-s) = top_share``,
    by bisection (the share rises with ``s``)."""
    k = np.arange(1, num_ids + 1, dtype=np.float64)
    lo, hi = 0.0, 4.0
    for _ in range(60):
        mid = (lo + hi) / 2
        if 1.0 / np.sum(k ** -mid) < top_share:
            lo = mid
        else:
            hi = mid
    return (lo + hi) / 2


def netflix_like_ratings(cfg: ALSConfig, num_ratings: int, seed: int = 0,
                         item_top_share: float = 0.00232,
                         user_top_share: float = 0.000176) -> Ratings:
    """Seeded ratings in the Netflix Prize data set's shape.

    Users and items are drawn independently, each from a Zipf bounded to
    its id range (``models.pagerank.powerlaw_graph``'s sampler: the inverse
    CDF of ``k^-s``), the exponents found by ``zipf_exponent`` so that the
    most rated item has ``item_top_share`` of the ratings and the busiest
    user ``user_top_share``; a seeded permutation a side spreads the hubs
    over the ids, and so over the blocks. Ratings are uniform integers
    1..5 as float32. In chunks of ``_RATING_CHUNK`` on a few threads, each
    chunk with a generator of its own seeded by ``(seed, chunk)``: the
    data do not depend on the number of threads."""
    def sampler(n_ids, top_share, tag):
        s = zipf_exponent(n_ids, top_share)
        cdf = np.cumsum(np.arange(1, n_ids + 1, dtype=np.float64) ** -s)
        cdf /= cdf[-1]
        perm = np.random.default_rng([seed, tag, n_ids]).permutation(
            n_ids).astype(np.int32)
        return cdf, perm

    user_cdf, user_perm = sampler(cfg.num_users, user_top_share, 1)
    item_cdf, item_perm = sampler(cfg.num_items, item_top_share, 2)
    out = Ratings(np.empty(num_ratings, np.int32),
                  np.empty(num_ratings, np.int32),
                  np.empty(num_ratings, np.float32))

    def draw(lo: int) -> None:
        hi = min(lo + _RATING_CHUNK, num_ratings)
        rng = np.random.default_rng([seed, lo // _RATING_CHUNK])
        for cdf, perm, ids in ((user_cdf, user_perm, out.user),
                               (item_cdf, item_perm, out.item)):
            zipf_rank = np.searchsorted(cdf, rng.random(hi - lo),
                                        side="right")
            ids[lo:hi] = perm[np.minimum(zipf_rank, len(perm) - 1)]
        out.rating[lo:hi] = rng.integers(1, 6, size=hi - lo)

    tasks = range(0, num_ratings, _RATING_CHUNK)
    with ThreadPoolExecutor(max_workers=max(1, min(16, len(tasks)))) as pool:
        list(pool.map(draw, tasks))
    return out


# ---------------------------------------------------------------------------
# blocking, once, on the host
# ---------------------------------------------------------------------------

class SideBlocks(NamedTuple):
    """One side's blocks on the host, a leading axis over the devices:
    the OutBlocks of its SOURCE blocks and the InBlocks of its destination
    blocks (side ``"item"``: items solved from users)."""
    out_idx: np.ndarray    # i32[D, L]: local index of the vector to send
    out_dest: np.ndarray   # i32[D, L]: its destination, grouped; padding D
    src_pos: np.ndarray    # i32[D, chunks, TILE, NT]: rank * D + source
    #                        device of a rating's source vector; padding -1
    rating: np.ndarray     # f32[D, chunks, TILE, NT]
    tile_dst: np.ndarray   # i32[D, chunks * NT]: a tile's local destination
    #                        id, ascending; padding tiles = ids_per_block
    count: np.ndarray      # i32[D, ids_per_block]: ratings of each id
    out_links: int         # factor rows a half-step sends, all devices
    max_segment: int       # most ratings of one destination id


def _stable_order(key: np.ndarray) -> np.ndarray:
    """``np.argsort(key, kind="stable")`` for non-negative int32 keys, by
    16 bits a pass: numpy sorts 16-bit keys by radix, wider ones by
    merging, three to four times slower at 10^8 keys."""
    order = np.argsort((key & 0xFFFF).astype(np.uint16), kind="stable")
    if int(key.max(initial=0)) >> 16:
        high = (key >> 16).astype(np.uint16)[order]
        order = order[np.argsort(high, kind="stable")]
    return order


def _block_side(dst: np.ndarray, src: np.ndarray, rating: np.ndarray,
                num_dst: int, num_src: int, n: int) -> SideBlocks:
    dst_dev, dst_loc = dst % n, dst // n
    dst_ids, src_ids = ids_per_block(num_dst, n), ids_per_block(num_src, n)
    # OutBlocks: source e's vector goes to block d iff e rates an id of d
    needs = np.zeros((src_ids * n, n), dtype=bool)
    needs[src, dst_dev] = True
    by_device = needs.reshape(src_ids, n, n)        # [local, source dev, dest]
    # a vector's rank among those its device sends to d, by local index
    rank = np.cumsum(by_device, axis=0, dtype=np.int32) - 1
    links = by_device.sum(axis=0)                   # [source dev, dest]
    length = max(1, int(links.sum(axis=1).max()))
    out_idx = np.zeros((n, length), np.int32)
    out_dest = np.full((n, length), n, np.int32)
    for s in range(n):
        local, dest = np.nonzero(by_device[:, s, :].T)[::-1]
        out_idx[s, :len(local)] = local
        out_dest[s, :len(local)] = dest
    pos = rank.reshape(-1, n)[src, dst_dev] * n + (src % n).astype(np.int32)

    # InBlocks: a device's ratings by destination id (stable), an id's in
    # tiles of TILE
    def in_block(d):
        mine = np.flatnonzero(dst_dev == d)
        local = dst_loc[mine]
        order = mine[_stable_order(local)]
        local = dst_loc[order]
        count = np.bincount(local, minlength=dst_ids).astype(np.int32)
        first = np.cumsum(count) - count
        within = np.arange(len(order), dtype=np.int64) - first[local]
        tiles_of = -(-count // TILE)
        tile = (np.cumsum(tiles_of) - tiles_of)[local] + within // TILE
        return (order, tile, within % TILE, count,
                np.repeat(np.arange(dst_ids, dtype=np.int32), tiles_of))

    with ThreadPoolExecutor(max_workers=min(n, 8)) as pool:
        blocks = list(pool.map(in_block, range(n)))
    most = max(1, max(len(b[4]) for b in blocks))
    nt = min(_CHUNK_TILES, -(-most // 128) * 128)
    chunks = -(-most // nt)
    src_pos = np.full((n, chunks * TILE * nt), -1, np.int32)
    values = np.zeros((n, chunks * TILE * nt), np.float32)
    tile_dst = np.full((n, chunks * nt), dst_ids, np.int32)
    for d, (order, tile, row, _, tiles) in enumerate(blocks):
        flat = ((tile // nt) * TILE + row) * nt + tile % nt
        src_pos[d, flat] = pos[order]
        values[d, flat] = rating[order]
        tile_dst[d, :len(tiles)] = tiles
    count = np.stack([b[3] for b in blocks])
    return SideBlocks(out_idx, out_dest,
                      src_pos.reshape(n, chunks, TILE, nt),
                      values.reshape(n, chunks, TILE, nt), tile_dst, count,
                      int(links.sum()), int(count.max(initial=0)))


def block_ratings(cfg: ALSConfig, ratings: Ratings, num_devices: int,
                  ) -> Tuple[SideBlocks, SideBlocks]:
    """``(user_side, item_side)``: MLlib's In/OutBlocks for ``num_devices``
    blocks a side, built once a data set. ``user_side`` solves users from
    items (its InBlocks group the ratings by user, its OutBlocks list the
    item vectors each user block needs); ``item_side`` the other way."""
    user, item, rating = ratings
    # the two sides at once: half the host's time at 10^8 ratings
    with ThreadPoolExecutor(max_workers=2) as pool:
        sides = [pool.submit(_block_side, user, item, rating, cfg.num_users,
                             cfg.num_items, num_devices),
                 pool.submit(_block_side, item, user, rating, cfg.num_items,
                             cfg.num_users, num_devices)]
        return sides[0].result(), sides[1].result()


class ResidentSide(NamedTuple):
    """``SideBlocks`` on the devices, the device axis folded into the
    leading one and sharded over the shuffle axis."""
    out_idx: jax.Array
    out_dest: jax.Array
    src_pos: jax.Array
    rating: jax.Array
    tile_dst: jax.Array
    count: jax.Array
    out_links: int
    max_segment: int

    @property
    def arrays(self) -> tuple:
        return self[:6]


class ResidentRatings(NamedTuple):
    """A blocked data set on the devices, as a job takes it."""
    user_side: ResidentSide
    item_side: ResidentSide
    num_ratings: int


def place_als(mesh: Mesh, axis_name: str,
              blocks: Tuple[SideBlocks, SideBlocks]) -> ResidentRatings:
    """Put ``block_ratings``' blocks on the mesh, once, for any number of
    jobs."""
    shard = NamedSharding(mesh, P(axis_name))

    def place(side: SideBlocks) -> ResidentSide:
        arrays = [jax.device_put(a.reshape((-1,) + a.shape[2:]), shard)
                  for a in side[:6]]
        return ResidentSide(*arrays, *side[6:])

    return ResidentRatings(place(blocks[0]), place(blocks[1]),
                           int(blocks[0].count.sum()))


# ---------------------------------------------------------------------------
# one half-step, one program
# ---------------------------------------------------------------------------

def _cholesky_solve(a: dict, b: list, k: int) -> list:
    """``x`` of ``A x = b`` for a batch of symmetric positive definite
    ``k x k`` systems held as COLUMNS: ``a[i, j]`` (``i >= j``) and
    ``b[i]`` are vectors over the batch. Cholesky and the two triangular
    solves, unrolled over ``k``: a few hundred multiply-adds of full
    vectors, where ``[batch, k, k]`` arrays would be padded to 8 x 128
    tiles (0.98 GB for 120,047 systems of 10 x 10)."""
    low: dict = {}
    for j in range(k):
        s = a[j, j]
        for p in range(j):
            s = s - low[j, p] * low[j, p]
        low[j, j] = jnp.sqrt(s)
        for i in range(j + 1, k):
            s = a[i, j]
            for p in range(j):
                s = s - low[i, p] * low[j, p]
            low[i, j] = s / low[j, j]
    z: list = []
    for i in range(k):
        s = b[i]
        for p in range(i):
            s = s - low[i, p] * z[p]
        z.append(s / low[i, i])
    x: list = [None] * k
    for i in reversed(range(k)):
        s = z[i]
        for p in range(i + 1, k):
            s = s - low[p, i] * x[p]
        x[i] = s / low[i, i]
    return x


def make_als_half_step(mesh: Mesh, axis_name: str, cfg: ALSConfig,
                       side: str, impl: str = "auto"):
    """One jitted ALS half-step: ``side`` (``"item"`` or ``"user"``) is
    solved from the other side's factors.

    ``step(src_factors, *resident_side.arrays)``; per-device inputs
    (leading axis sharded over ``axis_name``): ``src_factors f32[D *
    ids_per_block(source), k]`` and the six arrays of ``ResidentSide``.

    Returns ``(factors, received[D, 2], overflowed[D])`` as
    ``make_pagerank_step`` does: ``factors f32[D * ids_per_block(side),
    k]`` (an id with no rating gets zeros); ``received[d]`` is the factor
    rows device d was sent, twice (rows of ``rank`` words travel one to a
    wire row, so the exchange adds no fill); ``overflowed[d]`` flags a
    receive buffer too small for that fan-in (results invalid: raise
    ``out_factor``).

    A device profile names the four phases by scope (``als.exchange``,
    ``als.gather``, ``als.normal``, ``als.solve``: the module's
    docstring), all under ``als.item_step`` or ``als.user_step``.
    ``step.row_moves`` lists the forms the step's row moves took (the
    grouping's and the gather's), once the step has been traced. No
    option selects a form: the CPU tests run the program the chip runs.
    """
    if side not in SIDES:
        raise ValueError(f"side {side!r} is none of {SIDES}")
    # The scopes below are op metadata, which jax leaves out of the
    # persistent compile cache's key by default: an executable cached by a
    # build with other scopes would be loaded in place of this one and a
    # profile would carry that build's names (``make_fused_step`` sets the
    # same, for the same reason). Process-wide, a matter of cache keys only.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    k = cfg.rank
    num_dst, num_src = ((cfg.num_items, cfg.num_users) if side == "item"
                        else (cfg.num_users, cfg.num_items))
    dst_ids = ids_per_block(num_dst, n)
    pairs = [(i, j) for i in range(k) for j in range(i + 1)]
    spec = P(axis_name)
    row_moves: list = []
    move = row_mover(mesh, row_moves)

    def half_step(src_factors, out_idx, out_dest, src_pos, rating, tile_dst,
                  count):
        with jax.named_scope("als.exchange"):
            rows = jax.lax.bitcast_convert_type(
                jnp.take(src_factors, out_idx, axis=0), jnp.uint32)
            output = jnp.zeros((cfg.out_factor * rows.shape[0], k),
                               jnp.uint32)
            received, recv_counts, recv_offsets, overflowed = shuffle_shard(
                rows, out_dest, axis_name, n, output, impl, move)
            total = recv_counts.sum()
        # a block needs no vector twice, so no more rows than there are
        # source ids can have arrived: the gather's operand is that long
        with jax.named_scope("als.gather"):
            # as float32 here, not after the gather: a bit-cast of what is
            # gathered is a pass over a chunk of 128-lane rows
            table = jax.lax.bitcast_convert_type(
                received[:min(received.shape[0], num_src)], jnp.float32)

        def chunk_sums(_, chunk):
            pos, r = chunk                           # [TILE, NT] each
            with jax.named_scope("als.gather"):
                valid = pos >= 0
                place = jnp.maximum(pos, 0)
                # the source device's offset by compares: a lookup in a
                # table of D entries would be a gather an index
                device = jax.lax.rem(place, n)
                offset = sum(jnp.where(device == s, recv_offsets[s], 0)
                             for s in range(n))
                at = jnp.where(valid, jax.lax.div(place, n) + offset, 0)
                # clipped, not filled: ``jnp.take``'s default masks what is
                # gathered against the table's bounds, and out of a table
                # small enough to be gathered as rows that select runs over
                # 2^20 rows padded to 128 lanes, 512 MB a chunk: 1.63 ms
                # of a 4.3 ms chunk on the v5e (PERF.md section 6, PR 37).
                # The padding slots' rows are masked below, as columns.
                move.note("take")
                y = jnp.take(table, at.reshape(-1), axis=0, mode="clip")
            with jax.named_scope("als.normal"):
                # columns: y[a] is f32[TILE, NT], the tiles along the lanes
                y = jnp.where(valid[None], y.T.reshape((k,) + pos.shape), 0.0)
                sums = [(y[i] * y[j]).sum(axis=0) for i, j in pairs]
                sums += [(r * y[i]).sum(axis=0) for i in range(k)]
                return None, jnp.stack(sums)          # [55 + k, NT]

        # chunk after chunk in straight-line code, not a ``while``: on the
        # v5e the loop's gather read a loop-carried 17,770-row table wrongly
        # (every user off; the same chunks one call each were right:
        # PERF.md section 6, PR 35)
        _, tiles = jax.lax.scan(chunk_sums, None, (src_pos, rating),
                                unroll=True)
        with jax.named_scope("als.normal"):
            sums = jax.ops.segment_sum(
                tiles.transpose(0, 2, 1).reshape(-1, tiles.shape[1]),
                tile_dst, num_segments=dst_ids + 1, indices_are_sorted=True)
            cols = sums[:dst_ids].T                   # [55 + k, ids]
        with jax.named_scope("als.solve"):
            # MLlib's numExplicits * regParam; an id nobody rates solves
            # reg * x = 0
            ridge = cfg.reg * jnp.maximum(count, 1).astype(jnp.float32)
            a = {(i, j): cols[p] + ridge if i == j else cols[p]
                 for p, (i, j) in enumerate(pairs)}
            b = [cols[len(pairs) + i] for i in range(k)]
            factors = jnp.stack(_cholesky_solve(a, b, k), axis=1)
        return (factors,
                jnp.stack([total, total]).astype(jnp.int32)[None],
                overflowed[None])

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,) * 7,
                       out_specs=(spec, spec, spec))
    def step(src_factors, out_idx, out_dest, src_pos, rating, tile_dst,
             count):
        # the half-step whole under one scope: a job runs two programs
        # whose ops share names, and a profile tells them apart by this
        with jax.named_scope(f"als.{side}_step"):
            return half_step(src_factors, out_idx, out_dest, src_pos,
                             rating, tile_dst, count)

    step.row_moves = row_moves
    return step


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

class ALSJob:
    """``job(resident) -> (user_factors, item_factors)``: one ALS job over
    a resident blocked data set.

    A job resets the user factors on the devices from ``seed`` (gaussian,
    unit norm a row, the same whatever the number of blocks:
    ``initial_user_factors()`` gives them in id order for a reference),
    dispatches ``iterations`` sweeps back to back (items from users, then
    users from items: ``2 x iterations`` programs; the factors never
    leave HBM and the host does not wait between them), blocks once, and
    only then reads every half-step's ``overflowed`` flag: any one set
    raises ``OverflowError`` naming the half-steps. Returns the factors as
    sharded ``jax.Array``s in block order (``factors_by_id`` puts them in
    id order on the host). ``job(resident, user_factors)`` starts from
    those factors instead (block order). The programs are built here,
    once, for any number of jobs.

    Spans, on ``self.tracer``: ``als.job`` (``iterations``, ``ratings``,
    ``users``, ``items``, ``rank``; at its end ``received``, the factor
    rows delivered in each half-step, and ``row_move``) around
    ``als.dispatch`` and ``als.wait``. Counters, per job:
    ``als.recv_fill`` (most rows a device received in a half-step over
    that half-step's receive capacity), ``als.max_segment`` (most ratings
    of one id) and ``als.out_links`` (factor rows a sweep sends).
    """

    def __init__(self, mesh: Mesh, axis_name: str, cfg: ALSConfig,
                 iterations: int, seed: int = 0, impl: str = "auto",
                 tracer=trace.NULL):
        self.cfg = cfg
        self.iterations = iterations
        self.tracer = tracer
        self.num_devices = n = mesh.shape[axis_name]
        self._steps = {side: make_als_half_step(mesh, axis_name, cfg, side,
                                                impl) for side in SIDES}
        local = ids_per_block(cfg.num_users, n)

        def reset():
            # drawn in id order, so the factors do not depend on the
            # blocking; then each id to its block
            f = jax.random.normal(jax.random.key(seed),
                                  (local * n, cfg.rank), jnp.float32)
            f = f / jnp.sqrt(jnp.sum(f * f, axis=1, keepdims=True))
            return (f.reshape(local, n, cfg.rank).transpose(1, 0, 2)
                    .reshape(n * local, cfg.rank))

        self._reset = jax.jit(
            reset, out_shardings=NamedSharding(mesh, P(axis_name)))

    def initial_user_factors(self) -> np.ndarray:
        """The factors a job starts from, ``f32[num_users, k]`` in id
        order on the host."""
        return factors_by_id(self._reset(), self.cfg.num_users,
                             self.num_devices)

    def _sweeps(self, resident: ResidentRatings, users, facts: list):
        """Dispatch the job's half-steps; yields ``(items, users)`` after
        each sweep, without waiting for either."""
        sides = {"item": resident.item_side, "user": resident.user_side}
        for _ in range(self.iterations):
            for side in SIDES:
                source = users if side == "item" else items
                solved, received, overflowed = self._steps[side](
                    source, *sides[side].arrays)
                facts.append((side, received, overflowed))
                record_exchange(sides[side].out_links)
                if side == "item":
                    items = solved
                else:
                    users = solved
            yield items, users

    def trajectory(self, resident: ResidentRatings) -> list:
        """``[(item_factors, user_factors), ...]`` after each sweep of a
        job: the same programs on the same inputs, so the last pair is
        bit for bit what ``job(resident)`` returns. What a verification
        reads: each half-step can be held to its own inputs."""
        return list(self._sweeps(resident, self._reset(), []))

    def __call__(self, resident: ResidentRatings,
                 user_factors: Optional[jax.Array] = None,
                 ) -> Tuple[jax.Array, jax.Array]:
        tracer, cfg = self.tracer, self.cfg
        sides = {"item": resident.item_side, "user": resident.user_side}
        with tracer.span("als.job", "als", iterations=self.iterations,
                         ratings=resident.num_ratings, users=cfg.num_users,
                         items=cfg.num_items, rank=cfg.rank) as args:
            with tracer.span("als.dispatch", "als"):
                users = (self._reset() if user_factors is None
                         else user_factors)
                facts: list = []
                for items, users in self._sweeps(resident, users, facts):
                    pass
            with tracer.span("als.wait", "als"):
                jax.block_until_ready((users, items))
            received = [np.asarray(r)[:, 0] for _, r, _ in facts]
            args["received"] = [int(r.sum()) for r in received]
            args["row_move"] = forms_label(
                [m for step in self._steps.values() for m in step.row_moves])
            # a half-step's receive buffer: out_factor x its OutBlock's length
            capacity = {side: cfg.out_factor * sides[side].out_idx.shape[0]
                        // self.num_devices for side in SIDES}
            tracer.counter(
                "als.recv_fill",
                max(float(r.max()) / capacity[side]
                    for (side, _, _), r in zip(facts, received)), "als")
            tracer.counter("als.max_segment",
                           max(s.max_segment for s in sides.values()), "als")
            tracer.counter("als.out_links",
                           sum(s.out_links for s in sides.values()), "als")
            late = [f"{i // 2}:{side}" for i, (side, _, o) in enumerate(facts)
                    if np.asarray(o).any()]
            if late:
                raise OverflowError(
                    f"als receive buffer overflow in half-steps {late} "
                    "(sweep:side solved): a block needs more factor rows "
                    "than out_factor x the longest OutBlock; raise "
                    "ALSConfig.out_factor")
        return users, items


def rmse(ratings: Ratings, user_factors: np.ndarray,
         item_factors: np.ndarray) -> float:
    """Root-mean-square error of ``user . item`` over the ratings, in
    float64 on the host (factors in id order)."""
    pred = np.einsum("rk,rk->r",
                     np.asarray(user_factors, np.float64)[ratings.user],
                     np.asarray(item_factors, np.float64)[ratings.item])
    return float(np.sqrt(np.mean((pred - ratings.rating) ** 2)))


def run_als(mesh: Mesh, cfg: ALSConfig, ratings: Ratings, iterations: int,
            axis_name: str = "shuffle", seed: int = 0, impl: str = "auto",
            ) -> Tuple[np.ndarray, np.ndarray, list]:
    """``iterations`` sweeps over ``ratings``; returns ``(user_factors,
    item_factors, rmse_history)`` on the host in id order,
    ``rmse_history[i]`` the train RMSE after sweep ``i + 1``. The
    small-data convenience over ``block_ratings`` + ``place_als`` +
    ``ALSJob``: one sweep a job, each started from the last one's user
    factors, so that the history can be read between them."""
    n = mesh.shape[axis_name]
    resident = place_als(mesh, axis_name, block_ratings(cfg, ratings, n))
    job = ALSJob(mesh, axis_name, cfg, 1, seed, impl)
    users, history = None, []
    for _ in range(iterations):
        users, items = job(resident, users)
        by_id = (factors_by_id(users, cfg.num_users, n),
                 factors_by_id(items, cfg.num_items, n))
        history.append(rmse(ratings, *by_id))
    return (*by_id, history)
