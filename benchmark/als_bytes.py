"""The bytes an ALS job cannot avoid, per chip: the problem's, whatever
implements it (``peaks.py`` has the peaks they are divided by).

A chip holds ``ratings`` ratings twice (once by user, once by item) and
one block of each side. A half-step reads each of its ratings once (8
bytes: the source's place and the rating), writes and reads each factor
row it receives once (``2 x 40`` bytes: a row lands in HBM and the
ratings that need it read it from fast memory thereafter) and writes each
solved vector (40 bytes). The share ``(n-1)/n`` of the rows received
crossed ICI when the blocks that need a vector fall evenly over ``n``
chips.
"""

from __future__ import annotations

RATING_BYTES = 8    # (i32 source place, f32 rating)
WORD_BYTES = 4


def half_step_bytes(ratings: float, rows_received: float, ids: int,
                    rank: int, chips: int) -> dict:
    """``{"hbm_bytes", "ici_bytes"}`` of one half-step, a chip."""
    row = rank * WORD_BYTES
    return {"hbm_bytes": (RATING_BYTES * ratings + 2 * row * rows_received
                          + row * ids),
            "ici_bytes": row * rows_received * (chips - 1) / chips}


def job_bytes(ratings: float, rows_received: dict, ids: dict, rank: int,
              chips: int, iterations: int) -> dict:
    """One job of ``iterations`` sweeps, in the form
    ``peaks.least_seconds`` takes; ``rows_received`` and ``ids`` by side
    (``"item"``, ``"user"``), a chip."""
    halves = [half_step_bytes(ratings, rows_received[side], ids[side], rank,
                              chips) for side in ("item", "user")]
    return {key: iterations * sum(h[key] for h in halves)
            for key in ("hbm_bytes", "ici_bytes")}


def normal_bytes(ratings: float, rows_received: dict, ids: dict, rank: int,
                 iterations: int) -> dict:
    """The gather and the normal equations alone: every rating read once,
    every received row read once, ``k (k + 1) / 2 + k`` sums an id
    written; nothing of it crosses ICI."""
    row = rank * WORD_BYTES
    sums = (rank * (rank + 1) // 2 + rank) * WORD_BYTES
    return {"hbm_bytes": iterations * sum(
        RATING_BYTES * ratings + row * rows_received[side] + sums * ids[side]
        for side in ("item", "user")), "ici_bytes": 0}
