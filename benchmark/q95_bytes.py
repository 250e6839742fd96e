"""The bytes a q95 job cannot avoid, per chip: the problem's, whatever
implements it (``peaks.py`` has the peaks they are divided by).

A chip holds ``ws_rows`` rows of ``web_sales`` and ``wr_rows`` of
``web_returns``; its filter passes ``survivors``. The job reads the seven
resident ``web_sales`` columns the query touches once (the bigint order
number and six 4-byte columns: 32 bytes a row) and shuffles three row
sets: 12 bytes a ``web_sales`` row, 8 a ``web_returns`` row, 16 a
survivor. A shuffled row is written once where it lands and read once by
the join. The share ``(n-1)/n`` of the shuffled bytes leaves the chip
over ICI when the owners fall evenly over ``n`` chips.
"""

from __future__ import annotations

WS_ROW_BYTES = 32     # bigint order + warehouse, ship date, ship address,
#                       web site, cost, profit
PAIR_BYTES = 12       # (bigint order, int warehouse)
RETURN_BYTES = 8      # (bigint order)
SURVIVOR_BYTES = 16   # (bigint order, cost, profit)


def shuffled_bytes(ws_rows: int, wr_rows: int, survivors: int) -> int:
    """What the three exchanges of one job move: a driver's ``unit_bytes``."""
    return (PAIR_BYTES * ws_rows + RETURN_BYTES * wr_rows
            + SURVIVOR_BYTES * survivors)


def job_bytes(ws_rows: int, wr_rows: int, survivors: int, chips: int) -> dict:
    """``{"hbm_bytes", "ici_bytes"}`` of one job, a chip, in the form
    ``peaks.least_seconds`` takes."""
    shuffled = shuffled_bytes(ws_rows, wr_rows, survivors)
    return {"hbm_bytes": WS_ROW_BYTES * ws_rows + 2 * shuffled,
            "ici_bytes": shuffled * (chips - 1) / chips}
