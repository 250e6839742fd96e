"""The plain reference of the ``als_netflix`` configuration, in float64
numpy alone: nothing here imports the program or jax.

ALS as MLlib runs it (``org.apache.spark.ml.recommendation.ALS``, explicit
feedback), without blocks and without an exchange: a sweep solves every
item from the users' factors, then every user from the items'. An id ``d``
with ratings ``r_ds`` over its sources ``S_d``, ``n = |S_d|``, solves

    (sum_s y_s y_s^T + reg * n * I) x_d = sum_s r_ds * y_s

(the regulariser scaled by the number of ratings: MLlib's ``numExplicits *
regParam``); an id nobody rates gets zeros. From the raw ``(user, item,
rating)`` triples: the ratings are sorted by destination id once a side,
the normal equations are ``np.add.reduceat`` over blocks of ``_BLOCK``
ratings on a few threads, and the solves are batched.

The limit of the comparison, and why. The configuration states float32
products and float32 sums. Beside ``x_d`` the reference gives float32's
a-priori error bound for it, and ``BOUND_SHARE`` is the share of that bound
an error may reach: 0.1. The bound is the worst case over the signs of n
roundings, which do not line up: float32 reads 0.004 of it at ~200 ratings
an id and 0.0045 to 0.0053 on the v5e in the cell itself (the last sweep;
0.0095 over all four half-steps of a two-sweep job at a chip's share),
source factors rounded to bfloat16 3.8 to 4.7 times it, so the limit
stands an order of magnitude from either (PERF.md section 6, PR 35). With ``M = A + reg n I``, ``A =
sum y y^T``, ``b = sum r y`` and ``EPS`` = 2^-24 (Higham, *Accuracy and
Stability of Numerical Algorithms*, 2nd ed., sections 4.2, 7.1, 10.1):

* each entry of ``A`` and ``b`` is a sum of ``n`` rounded products, added
  in an order of the device's choosing: off by at most ``(n + 2) EPS`` of
  the sum of the products' magnitudes. Over a whole matrix, ``||dA||_F <=
  (n + 2) EPS q`` with ``q = sum ||y_s||^2`` (``|| |y| |y|^T ||_F =
  ||y||^2``), and ``||db||_2 <= (n + 2) EPS p`` with ``p = sum |r| ||y_s||``;
* Cholesky and the two triangular solves are backward stable: they solve
  ``(M + dM) x = b`` with ``|dM| <= gamma_(3k+1) |R^T| |R|``, so ``||dM||_F
  <= (3k + 1) EPS trace(M) = (3k + 1) EPS (q + k reg n)``; ``SOLVE_ULPS``
  doubles it for the chip's divide and square root, which are not
  correctly rounded;
* ``x`` moves by ``M^-1 (db - (dA + dM) x)``, and ``||M^-1||_2 <= 1 /
  (reg n)`` since ``A`` is positive semi-definite: the condition number
  that ``reg * n`` on the diagonal bounds.

So every element of ``x_d`` is within

    bound(d) = EPS * ((n + 2) (p + q ||x||_2)
                      + SOLVE_ULPS (3k + 1) (q + k reg n) ||x||_2) / (reg n)

of the exact solve from the same source factors. That is a bound for ONE
half-step from given inputs, and the comparison holds a half-step to it
from the job's OWN inputs to that half-step (``ALSJob.trajectory`` replays
the job's programs and keeps what each sweep returned; the last pair is,
bit for bit, what the timed job returned). ``als_report`` does so for
every sweep it is given: the tests give it all of a toy job's, the
benchmark's driver the LAST sweep of the last timed job, which is every
item's and every user's final vector, each solved over all its ratings
(two half-steps of 100 M ratings take the reference under a minute on the
chip's host, twenty would take six; the earlier sweeps run the same two
programs on the same resident blocks). A bound carried through the
sweeps instead says nothing: an error ``e`` in the sources may move ``x``
by ``sqrt(k) (2 ||x|| ||y|| + |r|) e / reg``, two hundred-fold a half-step
in the worst case, where the real factors move by about as much as they
were moved (the errors do not line up). What it catches: a factor row that
was lost, sent to the wrong block or read from the wrong place puts a
whole ``y_s y_s^T`` into ``dA``, hundreds of times the bound; source
factors rounded to bfloat16 (a bfloat16 wire, or a matrix unit's default
precision) are off by ``2^-9`` each, 32,768 times float32's ``EPS``
(``factor_dtype``, for the test that the limit catches it). After the
vectors, the train RMSE of the last pair against the reference's, within
``RMSE_TOLERANCE``.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

EPS = 2.0 ** -24          # float32's unit roundoff
SOLVE_ULPS = 2            # the chip's divide and sqrt over correct rounding
BOUND_SHARE = 0.1
RMSE_TOLERANCE = 1e-6     # |job's RMSE - the reference's|, absolute
_BLOCK = 1 << 18          # ratings a task (threads scale best here: smaller tasks fight over the interpreter lock)
_THREADS = min(32, os.cpu_count() or 16)


def _stable_order(key: np.ndarray) -> np.ndarray:
    """A stable argsort of non-negative int32 keys, 16 bits a pass (numpy
    sorts 16-bit keys by radix)."""
    order = np.argsort((key & 0xFFFF).astype(np.uint16), kind="stable")
    if int(key.max(initial=0)) >> 16:
        order = order[np.argsort((key >> 16).astype(np.uint16)[order],
                                 kind="stable")]
    return order


def _map(fn, tasks):
    with ThreadPoolExecutor(max_workers=_THREADS) as pool:
        return list(pool.map(fn, tasks))


class Side:
    """The ratings sorted by one side's ids: what its half-steps read."""

    def __init__(self, dst: np.ndarray, src: np.ndarray, rating: np.ndarray,
                 num_dst: int):
        order = _stable_order(np.asarray(dst, np.int32))
        self.dst = np.asarray(dst)[order]
        self.src = np.asarray(src)[order]
        self.rating = np.asarray(rating, np.float64)[order]
        self.num_dst = num_dst
        self.count = np.bincount(self.dst, minlength=num_dst)

    def half_step(self, src_factors: np.ndarray, reg: float,
                  factor_dtype=None):
        """``(x f64[num_dst, k], bound f64[num_dst])``: every id solved
        from ``src_factors`` and how far float32 arithmetic in any order
        of summation may end from it (the module's docstring).
        ``factor_dtype`` rounds the source factors through that type
        first: what a lower precision on the wire or in the products
        would give."""
        y_all = np.asarray(src_factors, np.float64)
        if factor_dtype is not None:
            y_all = y_all.astype(factor_dtype).astype(np.float64)
        k = y_all.shape[1]
        iu, ju = np.triu_indices(k)
        pairs = len(iu)
        norm = np.sqrt((y_all * y_all).sum(axis=1))
        columns = np.ascontiguousarray(y_all.T)     # [k, sources]

        def block(lo):
            # a rating's values as COLUMNS: each product and each sum is
            # then over one contiguous vector that fits the cache
            hi = min(lo + _BLOCK, len(self.dst))
            ids = self.dst[lo:hi]
            starts = np.concatenate(([0], np.flatnonzero(np.diff(ids)) + 1))
            src, r = self.src[lo:hi], self.rating[lo:hi]
            y = columns[:, src]
            out = np.empty((pairs + k + 2, len(starts)))
            for p in range(pairs):
                np.add.reduceat(y[iu[p]] * y[ju[p]], starts, out=out[p])
            for i in range(k):
                np.add.reduceat(y[i] * r, starts, out=out[pairs + i])
            np.add.reduceat(norm[src] ** 2, starts, out=out[-2])
            np.add.reduceat(np.abs(r) * norm[src], starts, out=out[-1])
            return ids[starts], out.T

        sums = np.zeros((self.num_dst, pairs + k + 2))
        for ids, part in _map(block, range(0, len(self.dst), _BLOCK)):
            sums[ids] += part      # an id split over two blocks adds twice
        n = self.count.astype(np.float64)
        rated = n > 0
        m = np.zeros((self.num_dst, k, k))
        m[:, iu, ju] = sums[:, :pairs]
        m[:, ju, iu] = sums[:, :pairs]
        m[:, np.arange(k), np.arange(k)] += np.where(rated, reg * n,
                                                     1.0)[:, None]
        b = sums[:, pairs:pairs + k]

        def solve(lo):
            return np.linalg.solve(m[lo:lo + _BLOCK],
                                   b[lo:lo + _BLOCK, :, None])[..., 0]

        x = np.concatenate(_map(solve, range(0, self.num_dst, _BLOCK)))
        q, p = sums[:, -2], sums[:, -1]
        x_norm = np.sqrt((x * x).sum(axis=1))
        bound = np.where(
            rated,
            EPS * ((n + 2) * (p + q * x_norm)
                   + SOLVE_ULPS * (3 * k + 1) * (q + k * reg * n) * x_norm)
            / np.where(rated, reg * n, 1.0), 0.0)
        return x, bound


def train_rmse(user: np.ndarray, item: np.ndarray, rating: np.ndarray,
               user_factors: np.ndarray, item_factors: np.ndarray) -> float:
    """Root-mean-square error of ``user . item`` over all ratings."""
    uf = np.asarray(user_factors, np.float64)
    vf = np.asarray(item_factors, np.float64)

    def block(lo):
        hi = lo + _BLOCK
        pred = np.einsum("rk,rk->r", uf[user[lo:hi]], vf[item[lo:hi]])
        return float(((pred - rating[lo:hi]) ** 2).sum())

    return float(np.sqrt(sum(_map(block, range(0, len(user), _BLOCK)))
                         / len(user)))


def reference_als(user: np.ndarray, item: np.ndarray, rating: np.ndarray,
                  user_factors: np.ndarray, num_items: int, reg: float,
                  iterations: int, factor_dtype=None):
    """``(user_factors, item_factors)`` in float64 after ``iterations``
    sweeps from ``user_factors f[num_users, k]``: the whole job, every
    half-step from the reference's own factors."""
    users = np.asarray(user_factors, np.float64)
    by_item = Side(item, user, rating, num_items)
    by_user = Side(user, item, rating, len(users))
    for _ in range(iterations):
        items, _ = by_item.half_step(users, reg, factor_dtype)
        users, _ = by_user.half_step(items, reg, factor_dtype)
    return users, items


def _share(got: np.ndarray, want: np.ndarray, bound: np.ndarray):
    """The largest error over its id's bound, and that id. An id whose
    bound is 0 (nobody rates it) must be exactly the reference's zeros."""
    err = np.abs(np.asarray(got, np.float64) - want).max(axis=1)
    share = np.where(err > 0, err / np.where(bound > 0, bound, 1.0), 0.0)
    share = np.where((err > 0) & (bound == 0), np.inf, share)
    worst = int(np.argmax(share))
    return float(share[worst]), worst, float(err[worst])


def als_report(steps: list, user: np.ndarray, item: np.ndarray,
               rating: np.ndarray, user_factors: np.ndarray, reg: float,
               first_sweep: int = 0) -> tuple:
    """``(problems, readings)``: what is wrong with a job's half-steps, as
    sentences (empty when every vector of every half-step is the
    reference's within float32's bound and the train RMSE agrees), and the
    comparison's numbers. ``steps`` is ``[(item_factors, user_factors),
    ...]`` after each of some consecutive sweeps of a job, in id order,
    the first of them sweep ``first_sweep`` (for the sentences);
    ``user_factors`` what that sweep started from. Half-step by half-step the reference solves from the
    JOB's inputs to it. ``readings``: ``bound_share`` (the largest error
    over its id's bound, all half-steps: the number the limit holds),
    ``rmse`` and ``rmse_reference`` (the last pair's and the reference's
    last pair's), ``max_segment``; empty where the factors could not be
    compared at all."""
    num_users, num_items = len(user_factors), len(steps[-1][0])
    for items, users in steps:
        if (np.shape(items) != (num_items, np.shape(user_factors)[1])
                or np.shape(users) != np.shape(user_factors)):
            return [f"factors have shapes {np.shape(users)} and "
                    f"{np.shape(items)}"], {}
        if not (np.isfinite(items).all() and np.isfinite(users).all()):
            return ["a factor is not finite"], {}
    by_item = Side(item, user, rating, num_items)
    by_user = Side(user, item, rating, num_users)
    out, shares, want = [], [], {}
    users = user_factors
    for sweep, (got_items, got_users) in enumerate(steps, first_sweep):
        for name, side, got, source in (("item", by_item, got_items, users),
                                        ("user", by_user, got_users,
                                         got_items)):
            want[name], bound = side.half_step(source, reg)
            share, worst, err = _share(got, want[name], bound)
            shares.append(share)
            if share > BOUND_SHARE:
                out.append(
                    f"sweep {sweep}: {name} {worst} "
                    f"({int(side.count[worst])} ratings) is off by "
                    f"{err:.3e}, {share:.3g} times what float32 "
                    f"arithmetic can account for (limit {BOUND_SHARE:g}): "
                    "a factor row was lost, misplaced or rounded below "
                    "float32")
        users = got_users
    readings = {
        "bound_share": max(shares),
        "rmse": train_rmse(user, item, rating, steps[-1][1], steps[-1][0]),
        # the reference's last pair: each solved from the job's inputs
        "rmse_reference": train_rmse(user, item, rating, want["user"],
                                     want["item"]),
        "max_segment": int(max(by_item.count.max(), by_user.count.max()))}
    gap = abs(readings["rmse"] - readings["rmse_reference"])
    if not gap <= RMSE_TOLERANCE:
        out.append(f"train RMSE {readings['rmse']:.7f} against the "
                   f"reference's {readings['rmse_reference']:.7f}: "
                   f"{gap:.3e} apart (limit {RMSE_TOLERANCE:g})")
    return out, readings


def als_problems(steps: list, user: np.ndarray, item: np.ndarray,
                 rating: np.ndarray, user_factors: np.ndarray,
                 reg: float) -> list:
    """``als_report``'s sentences alone."""
    return als_report(steps, user, item, rating, user_factors, reg)[0]
