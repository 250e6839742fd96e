"""The bytes a PageRank job cannot avoid, per chip: the problem's, whatever
implements it (``peaks.py`` has the peaks they are divided by).

``E`` edges and ``V`` vertices a chip, ``n`` chips. A superstep reads each
edge once (8 bytes: two i32 ids), reads the rank and the out-degree of
its vertices and writes the new rank (12 bytes a vertex); a contribution
need never touch HBM on its own chip. The share ``(n-1)/n`` of the
contributions (8 bytes each) leaves the chip over ICI when targets fall
evenly over the chips.
"""

from __future__ import annotations

EDGE_BYTES = 8      # (i32 src, i32 dst)
RECORD_BYTES = 8    # (u32 dst, f32 contribution)
RANK_BYTES = 4


def job_bytes(edges_per_chip: int, vertices_per_chip: int, chips: int,
              iterations: int) -> dict:
    """``{"hbm_bytes", "ici_bytes"}`` of one job of ``iterations``
    supersteps, in the form ``peaks.least_seconds`` takes."""
    return {"hbm_bytes": iterations * (EDGE_BYTES * edges_per_chip
                                       + 3 * RANK_BYTES * vertices_per_chip),
            "ici_bytes": iterations * RECORD_BYTES * edges_per_chip
            * (chips - 1) / chips}


def accumulate_bytes(rows_received: int, vertices_per_chip: int,
                     iterations: int) -> dict:
    """The receive side alone: every received record read once, every
    local rank written once; nothing of it crosses ICI."""
    return {"hbm_bytes": iterations * (RECORD_BYTES * rows_received
                                       + RANK_BYTES * vertices_per_chip),
            "ici_bytes": 0}
