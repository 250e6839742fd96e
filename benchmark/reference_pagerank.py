"""The plain reference of the ``pagerank_powerlaw`` configuration, in
float64 numpy alone: nothing here imports the program or jax.

PageRank as Spark runs it, in the repo's normalised form: every superstep
each edge ``(src, dst)`` sends ``rank[src] / outdeg[src]`` to ``dst``, the
contributions are summed per vertex, and ``rank = (1 - d) / V + d * sum``.
Ranks start at ``1 / V``.

The limit of the comparison, and why (readings in ``PERF.md`` section 6,
PR 28). The configuration states float32 contributions and float32 sums. A
hub's sum has up to millions of terms, added on the device in an order of
its choosing: on the v5e the largest hub (2,036,714 in-links) read 2.1e-3
off the float64 sum, and every vertex that hub links to inherits that
error whole, a vertex of in-degree 1 among them. So no one relative
tolerance separates float32 from a lower precision: bfloat16 contributions
(2^-9 a term) put 2e-3 on every low-degree vertex. What separates them is
where the error may be. Beside the ranks the reference carries float32's
a-priori error bound per vertex (Higham's running bound, with ``EPS`` =
2^-24): ``CONTRIBUTION_ULPS * EPS`` of each contribution for the divide
(the v5e's reads 2.2 EPS) and its rounding, ``n * EPS`` of a sum of ``n``
terms in any order, ``RANK_ULPS * EPS`` of the new rank for the damping's
multiply and add, and what the sources' own bounds pass on. A hub and its
out-neighbours get the room that float32 sums need; a vertex of in-degree
<= 4 that no hub feeds gets about 1e-6 of its rank, and bfloat16
contributions are off by two thousand times that. ``BOUND_SHARE`` is the
share of the bound an error may reach: 1, the bound itself.
"""

from __future__ import annotations

import numpy as np

EPS = 2.0 ** -24          # float32's unit roundoff
CONTRIBUTION_ULPS = 8     # rank / outdeg on the device, and its rounding
RANK_ULPS = 4             # (1 - d) / V + d * sum: a constant, a multiply, an add
BOUND_SHARE = 1.0


def reference_pagerank(edges: np.ndarray, num_vertices: int, damping: float,
                       iterations: int, contribution_dtype=None):
    """``(ranks f64[V], bound f64[V], in_degree i64[V])`` of the graph
    ``edges i32[E, 2]`` (rows with src < 0 are padding): the ranks, how
    far float32 arithmetic in any order of summation may end from them,
    and the in-degrees. ``contribution_dtype`` rounds each contribution
    through that type first: what a lower precision on the wire would
    give, for the test that the limit catches it."""
    keep = edges[:, 0] >= 0
    src, dst = edges[keep, 0], edges[keep, 1]
    share = 1.0 / np.maximum(np.bincount(src, minlength=num_vertices), 1)
    in_degree = np.bincount(dst, minlength=num_vertices)
    ranks = np.full(num_vertices, 1.0 / num_vertices)
    bound = EPS * ranks
    for _ in range(iterations):
        contrib = (ranks * share)[src]
        may_differ = (bound * share)[src] + CONTRIBUTION_ULPS * EPS * contrib
        if contribution_dtype is not None:
            contrib = contrib.astype(contribution_dtype).astype(np.float64)
        sums = np.bincount(dst, weights=contrib, minlength=num_vertices)
        ranks = (1.0 - damping) / num_vertices + damping * sums
        bound = (damping * (np.bincount(dst, weights=may_differ,
                                        minlength=num_vertices)
                            + in_degree * EPS * sums)
                 + RANK_ULPS * EPS * ranks)
    return ranks, bound, in_degree


def pagerank_report(ranks: np.ndarray, edges: np.ndarray,
                    num_vertices: int, damping: float,
                    iterations: int) -> tuple:
    """``(problems, readings)``: what is wrong with ``ranks`` as the
    result of ``iterations`` supersteps over ``edges``, as sentences
    (empty when they are the reference's within float32's bound), and the
    comparison's numbers: ``bound_share`` (the largest error over its
    vertex's bound: the number the limit holds), ``relative_error`` (the
    largest over all vertices) and ``max_in_degree``; empty where the
    ranks could not be compared at all."""
    ranks = np.asarray(ranks)
    if ranks.shape != (num_vertices,):
        return [f"ranks have shape {ranks.shape}, not ({num_vertices},)"], {}
    if not np.isfinite(ranks).all():
        return ["a rank is not finite"], {}
    want, bound, in_degree = reference_pagerank(edges, num_vertices,
                                                damping, iterations)
    err = np.abs(ranks.astype(np.float64) - want)
    worst = int(np.argmax(err / bound))
    readings = {"bound_share": float(err[worst] / bound[worst]),
                "relative_error": float((err / want).max()),
                "max_in_degree": int(in_degree.max())}
    out = []
    if readings["bound_share"] > BOUND_SHARE:
        out.append(
            f"vertex {worst} (in-degree {int(in_degree[worst])}) is off by "
            f"{err[worst] / want[worst]:.3e} of its rank, "
            f"{readings['bound_share']:.3g} times what float32 arithmetic "
            f"can account for (limit {BOUND_SHARE:g}): a contribution was "
            "lost, changed or rounded below float32")
    return out, readings


def pagerank_problems(ranks: np.ndarray, edges: np.ndarray,
                      num_vertices: int, damping: float,
                      iterations: int) -> list:
    """``pagerank_report``'s sentences alone."""
    return pagerank_report(ranks, edges, num_vertices, damping,
                           iterations)[0]
