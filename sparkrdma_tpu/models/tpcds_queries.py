"""Actual TPC-DS q64 / q95 plan shapes (BASELINE.md config #4).

The reference's workload class is shuffle-heavy Spark jobs (its README
publishes TeraSort and PageRank results, /root/reference/README.md:7-31);
BASELINE.md config #4 names Spark SQL TPC-DS q64/q95 as the
multi-join shuffle stress for this build — q64 and q95 are the
standard shuffle-heavy picks in TPC-DS benchmarking literature. The
generic star in ``models/tpcds.py`` covers the *class*; this module
expresses the two *named* plans:

**q95** — web-sales shipping analysis:
  - ``ws_wh`` self-semi-join: orders shipped from MORE THAN ONE warehouse
    (web_sales ⋈ web_sales on order_number, warehouse_sk <> warehouse_sk)
  - semi-join against web_returns on order_number (returned orders only)
  - dimension filters: date_dim (60-day ship window), customer_address
    (state), web_site (company)
  - output: count(distinct order_number), sum(ext_ship_cost),
    sum(net_profit)

**q64** — cross-channel sales with both returns tables:
  - ``cs_ui``: catalog_sales ⋈ catalog_returns on (item, order), grouped
    by item, HAVING sum(sales) > 2 * sum(refund)
  - store_sales ⋈ store_returns on (item, ticket)  [inner: sold AND
    returned]
  - ⋈ date_dim on sold_date (two consecutive years)
  - semi-join against cs_ui on item
  - per (item, year) aggregation, then the aggregated CTE SELF-JOINED
    across years: items where cnt(year+1) <= cnt(year)
  - output: count(qualifying items), sum(both years' price sums)

q95 runs as Spark SQL plans it (``make_q95_step``, ``Q95Job`` over
``place_q95``'s resident tables): the dimensions are broadcast, so the
three predicates are local lookups, and what crosses the shuffle is
narrow: ``(order, warehouse)`` of every ``web_sales`` row, ``(order)`` of
every ``web_returns`` row and the few rows the filters leave, all three
hash-partitioned on the bigint order number to one owner and
sort-merge-joined there. Its oracle is the benchmark's
``benchmark/reference_q95.py``. q64 (``make_q64_step``) still chains
every join, the dimensions' too, as collective exchanges in one jitted
step, heavier than Spark's broadcast joins, over 16-bit key spaces
(``_pairkey``) and against ``numpy_q64``; it keeps the unpacked
``shuffle_shard`` by name.

``build_q95_job`` / ``build_q64_job``: the same logical plans as stage
DAGs for ``engine.DAGEngine.run`` — source stages, join MapStages,
aggregating ResultStage — driving the drop-in shuffle SPI the way Spark
SQL's stage graph drives the reference, on the native u64 key lane.
PAD = 0xFFFFFFFF marks q64's dead rows.
"""

from __future__ import annotations

import functools
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.ops.partition import hash_partition
from sparkrdma_tpu.ops.row_permute import forms_label
from sparkrdma_tpu.parallel.exchange import (
    record_capacity,
    record_exchange,
    resolve_impl,
    row_mover,
    shuffle_records_shard,
    shuffle_shard,
)
from sparkrdma_tpu.utils import trace

PAD = np.uint32(0xFFFFFFFF)
_KEY_BITS = 16  # item/order/ticket key spaces (see module docstring)


def _pairkey(a, b):
    """Exact u32 composite of two 16-bit keys (same in numpy and jnp)."""
    return a * np.uint32(1 << _KEY_BITS) + b


# ---------------------------------------------------------------------------
# shared shard-side helpers (inside shard_map)
# ---------------------------------------------------------------------------


def _exchange(rows, dest, axis_name, n, capacity, impl):
    """One collective shuffle of ``rows`` to ``dest`` with a fixed receive
    capacity; returns (received, valid_mask, overflowed)."""
    output = jnp.zeros((capacity,) + rows.shape[1:], rows.dtype)
    received, recv_counts, _, overflowed = shuffle_shard(
        rows, dest, axis_name, n, output=output, impl=impl)
    total = recv_counts.sum()
    valid = jnp.arange(capacity, dtype=jnp.int32) < total
    return received, valid, overflowed


def _lookup(dim_keys, dim_valid, dim_attr, probes):
    """Sorted unique-key lookup: returns (attr, found) per probe."""
    dk = jnp.where(dim_valid, dim_keys, PAD)
    order = jnp.argsort(dk)
    ks = jnp.take(dk, order)
    at = jnp.take(dim_attr, order)
    idx = jnp.clip(jnp.searchsorted(ks, probes), 0, ks.shape[0] - 1)
    found = (jnp.take(ks, idx) == probes) & (probes != PAD)
    return jnp.take(at, idx), found


def _route(keys, valid, n):
    return jnp.where(valid, hash_partition(keys, n), -1)


def _dim_cap(rows_per_shard: int, n: int) -> int:
    """Receive capacity for a small broadcast-class table: ``rows * n``.

    One device receiving EVERYTHING fits, and under the dense transport
    each (src, dst) pair's fixed slot is ``cap // n = rows`` — a source
    only HAS ``rows`` rows, so pair overflow is impossible too. Dim
    tables are small by definition; anything where rows*n hurts should
    ride the fact-table path with an out_factor instead."""
    return rows_per_shard * n


# ===========================================================================
# q95
# ===========================================================================

# days since 1900-01-02, date_dim's first row (d_date_sk 2415022): a
# d_date_sk less 2415022 indexes the table, and d_date is that ordinal
_D_1999_02_01 = 36189
_D_1998_01_02 = 35794      # the first day web_sales are sold on
_SOLD_DAYS = 1826          # 1998-01-02 .. 2003-01-01, five years of sales
DEAD = np.uint32(0xFFFFFFFF)   # both words of the dead order number
_Q95_CHUNK = 1 << 18       # orders a generator task draws; part of the seeding
_SUM_BLOCK = 8192          # rows a block partial of the limb sums covers


@dataclass(frozen=True)
class Q95Config:
    """A q95 deployment: sizes a device, the schema's ranges, the
    qualification parameters. The defaults are TPC-DS's at scale factor
    1000 (``benchmark/configs/tpcds_q95.json`` says which are assumed)."""
    ws_rows_per_device: int       # web_sales line items a device holds
    wr_rows_per_device: int       # web_returns rows a device holds
    num_orders: int               # orders of all devices' web_sales
    survivor_capacity: int        # rows a device's filter may pass (static)
    order_base: int = 1           # the first ws_order_number (a bigint)
    items_lo: int = 8             # line items an order: uniform lo..hi
    items_hi: int = 16
    num_warehouses: int = 20
    num_dates: int = 73_049       # date_dim
    window_start: int = _D_1999_02_01   # d_date between start and
    window_days: int = 60               # start + 60 days, both included
    max_ship_lag: int = 120       # ship date = sold date + 1..120 days
    num_addresses: int = 6_000_000      # customer_address
    num_states: int = 51
    target_state: int = 14        # 'IL'
    num_sites: int = 54           # web_site
    num_companies: int = 6
    target_company: int = 0       # 'pri'
    out_factor: int = 2


class Q95Tables(NamedTuple):
    """The five tables on the host, a column an array. ``web_sales`` and
    ``web_returns`` are written order by order, as ``dsdgen`` writes
    them; a ``web_sales`` row whose order number is -1 is padding."""
    ws_order: np.ndarray          # i64[N]  ws_order_number (bigint)
    ws_warehouse: np.ndarray      # i32[N]  ws_warehouse_sk
    ws_ship_date: np.ndarray      # i32[N]  ws_ship_date_sk - 2415022
    ws_ship_addr: np.ndarray      # i32[N]  ws_ship_addr_sk
    ws_web_site: np.ndarray       # i32[N]  ws_web_site_sk
    ws_ext_ship_cost: np.ndarray  # i32[N]  decimal(7,2), in cents
    ws_net_profit: np.ndarray     # i32[N]  decimal(7,2), in cents, signed
    wr_order: np.ndarray          # i64[R]  wr_order_number; -1 is padding
    d_date: np.ndarray            # i32[num_dates]      d_date by d_date_sk
    ca_state: np.ndarray          # i32[num_addresses]  ca_state's code
    web_company: np.ndarray       # i32[num_sites]  web_company_name's code


def generate_q95(cfg: Q95Config, num_devices: int, seed: int = 0
                 ) -> Q95Tables:
    """Seeded tables with ``dsdgen``'s order structure: ``num_orders``
    orders of ``items_lo..items_hi`` line items (uniform; the counts are
    then nudged by one, order by order at random, until they add up to the
    devices' rows exactly), an order's items consecutive. Order number
    (``order_base`` + its index), ship address and web site are an
    order's; warehouse, ship date (the order's sold date + 1..
    ``max_ship_lag`` days), cost and profit an item's. ``web_returns``
    holds exactly ``num_devices * wr_rows_per_device`` items drawn without
    replacement, in the items' order. Chunked by order and threaded as
    ``powerlaw_graph`` is: each chunk has a generator of its own, seeded
    by ``(seed, chunk)``, so the tables do not depend on the threads."""
    n_rows = cfg.ws_rows_per_device * num_devices
    n_ret = cfg.wr_rows_per_device * num_devices
    orders = cfg.num_orders
    if not cfg.items_lo * orders <= n_rows <= cfg.items_hi * orders:
        raise ValueError(
            f"{n_rows} web_sales rows cannot be {orders} orders of "
            f"{cfg.items_lo} to {cfg.items_hi} line items")
    rng = np.random.default_rng([seed, orders])
    items = rng.integers(cfg.items_lo, cfg.items_hi + 1, orders)
    while (short := n_rows - int(items.sum())) != 0:
        step = 1 if short > 0 else -1
        room = np.flatnonzero(items < cfg.items_hi if short > 0
                              else items > cfg.items_lo)
        items[rng.choice(room, min(abs(short), len(room)),
                         replace=False)] += step
    first = np.concatenate([[0], np.cumsum(items)])

    cols = {name: np.empty(n_rows, np.int32) for name in (
        "ws_warehouse", "ws_ship_date", "ws_ship_addr", "ws_web_site",
        "ws_ext_ship_cost", "ws_net_profit")}
    ws_order = np.empty(n_rows, np.int64)
    returned = np.zeros(n_rows, bool)
    chunks = range(0, orders, _Q95_CHUNK)
    # a chunk returns its share of the rows, to the row
    ret_edge = [n_ret * first[min(lo + _Q95_CHUNK, orders)] // n_rows
                for lo in chunks]

    def draw(task) -> None:
        c, lo = task
        hi = min(lo + _Q95_CHUNK, orders)
        r = np.random.default_rng([seed, c])
        count = items[lo:hi]
        rows = slice(first[lo], first[hi])
        n = first[hi] - first[lo]
        ws_order[rows] = np.repeat(
            np.arange(lo, hi, dtype=np.int64) + cfg.order_base, count)
        cols["ws_ship_addr"][rows] = np.repeat(
            r.integers(0, cfg.num_addresses, hi - lo, dtype=np.int32), count)
        cols["ws_web_site"][rows] = np.repeat(
            r.integers(0, cfg.num_sites, hi - lo, dtype=np.int32), count)
        sold = np.repeat(r.integers(_D_1998_01_02, _D_1998_01_02 + _SOLD_DAYS,
                                    hi - lo, dtype=np.int32), count)
        cols["ws_ship_date"][rows] = np.minimum(
            sold + r.integers(1, cfg.max_ship_lag + 1, n, dtype=np.int32),
            cfg.num_dates - 1)
        cols["ws_warehouse"][rows] = r.integers(
            0, cfg.num_warehouses, n, dtype=np.int32)
        cols["ws_ext_ship_cost"][rows] = r.integers(
            0, 1_000_000, n, dtype=np.int32)
        cols["ws_net_profit"][rows] = r.integers(
            -1_000_000, 1_000_000, n, dtype=np.int32)
        want = ret_edge[c] - (ret_edge[c - 1] if c else 0)
        returned[first[lo] + r.choice(n, want, replace=False)] = True

    tasks = list(enumerate(chunks))
    with ThreadPoolExecutor(max_workers=min(8, len(tasks))) as pool:
        list(pool.map(draw, tasks))
    return Q95Tables(
        ws_order=ws_order, **cols, wr_order=ws_order[returned],
        d_date=np.arange(cfg.num_dates, dtype=np.int32),
        ca_state=np.random.default_rng([seed, orders, 1]).integers(
            0, cfg.num_states, cfg.num_addresses, dtype=np.int32),
        web_company=(np.arange(cfg.num_sites, dtype=np.int32)
                     % cfg.num_companies))


def _words(order: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """A bigint column as its two words ``(low, high)``; -1 is the dead
    order number, both words ``DEAD``."""
    bits = np.ascontiguousarray(order, np.int64).view(np.uint64)
    return ((bits & np.uint64(0xFFFFFFFF)).astype(np.uint32),
            (bits >> np.uint64(32)).astype(np.uint32))


class ResidentQ95(NamedTuple):
    """The tables on the devices, as a job takes them: the fact tables'
    columns sharded over the shuffle axis in the rows' order, the
    dimensions whole on every device, as a broadcast leaves them."""
    web_sales: Tuple[jax.Array, ...]    # order low, order high, warehouse,
    #   ship date, ship address, web site, cost, profit: u32/i32[D * N]
    web_returns: Tuple[jax.Array, ...]  # order low, order high: u32[D * R]
    dimensions: Tuple[jax.Array, ...]   # d_date, ca_state, web_company
    ws_rows: int                        # rows that are no padding
    wr_rows: int
    orders: int      # runs of equal order numbers in web_sales: the
    #                  orders, for a table written order by order


def place_q95(mesh: Mesh, axis_name: str, tables: Q95Tables) -> ResidentQ95:
    """Put host tables on the mesh, once, for any number of jobs."""
    shard = NamedSharding(mesh, P(axis_name))
    whole = NamedSharding(mesh, P())
    t = tables
    live = t.ws_order[t.ws_order >= 0]
    return ResidentQ95(
        tuple(jax.device_put(c, shard) for c in (
            *_words(t.ws_order), t.ws_warehouse, t.ws_ship_date,
            t.ws_ship_addr, t.ws_web_site, t.ws_ext_ship_cost,
            t.ws_net_profit)),
        tuple(jax.device_put(c, shard) for c in _words(t.wr_order)),
        tuple(jax.device_put(c, whole) for c in (
            t.d_date, t.ca_state, t.web_company)),
        ws_rows=len(live), wr_rows=int((t.wr_order >= 0).sum()),
        orders=int(np.count_nonzero(np.diff(live)) + 1) if len(live) else 0)


def _owner(low, high, valid, n):
    """The owner of an order number: a hash over both its words."""
    mix = (high ^ (high >> 16)) * jnp.uint32(0x9E3779B1)
    return jnp.where(valid, hash_partition(low ^ mix, n), -1)


def _is_dead(low, high):
    return (low == DEAD) & (high == DEAD)


def _key_edges(high, low):
    """``(first, last)``: where a run of equal two-word keys starts and
    ends in a sorted sequence."""
    differs = (high[1:] != high[:-1]) | (low[1:] != low[:-1])
    edge = jnp.ones(1, bool)
    return (jnp.concatenate([edge, differs]),
            jnp.concatenate([differs, edge]))


def _search(high, low, q_high, q_low, upper: bool):
    """Binary search of two-word queries in a sequence sorted on
    ``(high, low)``: the first position whose key is greater than the
    query (``upper``) or not less than it."""
    size = high.shape[0]

    def halve(_, bounds):
        lo, hi = bounds
        mid = jnp.minimum((lo + hi) // 2, size - 1)
        m_high, m_low = high[mid], low[mid]
        right = (m_high < q_high) | ((m_high == q_high) & (
            (m_low <= q_low) if upper else (m_low < q_low)))
        open_ = lo < hi
        return (jnp.where(open_ & right, mid + 1, lo),
                jnp.where(open_ & ~right, mid, hi))

    lo, _ = jax.lax.fori_loop(
        0, size.bit_length(), halve,
        (jnp.zeros_like(q_high, jnp.int32),
         jnp.full_like(q_high, size, jnp.int32)))
    return lo


def _limb_sums(values, keep):
    """Block partials of a sum of int32 that cannot wrap: ``i32[blocks,
    2]``, the sums of the high and of the low 16 bits of ``values`` where
    ``keep``, over blocks of ``_SUM_BLOCK`` rows (under 2^29 in size
    each). The whole sum is ``sum(high) * 65536 + sum(low)`` in int64."""
    v = jnp.where(keep, values, 0).astype(jnp.int32)
    v = jnp.pad(v, (0, -v.shape[0] % _SUM_BLOCK)).reshape(-1, _SUM_BLOCK)
    return jnp.stack([(v >> 16).sum(axis=1), (v & 0xFFFF).sum(axis=1)],
                     axis=1)


Q95_EXCHANGES = ("pairs", "returns", "survivors")
_ROW_WORDS = (3, 2, 4)     # an exchange's row, in 32-bit words


class Q95Answers(NamedTuple):
    """What a job hands back, on the devices; ``q95_totals`` adds it up."""
    counts: jax.Array       # i32[D, 4]: qualifying orders, orders seen,
    #                         multi-warehouse orders, returned orders
    cost: jax.Array         # i32[D, blocks, 2]: ``_limb_sums``
    profit: jax.Array
    received: jax.Array     # i32[D, 3, 2]: an exchange's records delivered
    #                         (the senders' own) and received (fill too)
    survivors: jax.Array    # i32[D]: rows the filter passed
    overflowed: jax.Array   # bool[D, 4]: the filter, then the exchanges


class Q95Totals(NamedTuple):
    """The query's three answers, and three counts over every row."""
    orders: int             # count(distinct ws_order_number)
    ship_cost: int          # sum(ws_ext_ship_cost), cents
    net_profit: int         # sum(ws_net_profit), cents
    orders_seen: int        # distinct order numbers in web_sales
    multi_warehouse_orders: int
    returned_orders: int    # distinct order numbers in web_returns


def q95_totals(answers: Q95Answers) -> Q95Totals:
    """The devices' partials summed on the host, in int64: every order
    lives on one device, so counts add up."""
    counts = np.asarray(answers.counts).astype(np.int64).sum(axis=0)

    def whole(limbs) -> int:
        high, low = np.asarray(limbs).astype(np.int64).reshape(-1, 2).sum(0)
        return int(high) * 65536 + int(low)

    return Q95Totals(int(counts[0]), whole(answers.cost),
                     whole(answers.profit), *(int(c) for c in counts[1:]))


def make_q95_step(mesh: Mesh, axis_name: str, cfg: Q95Config,
                  impl: str = "auto"):
    """q95 as Spark SQL plans it, a device's part in one jitted program.

    ``step(web_sales, web_returns, dimensions)`` over ``ResidentQ95``'s
    arrays returns ``Q95Answers``. Three phases, named by scope:

    ``q95.filter`` — the three dimension predicates as lookups into the
    tables every device holds whole (``d_date`` of ``ws_ship_date_sk`` in
    the window, ``ca_state`` of ``ws_ship_addr_sk``, ``web_company_name``
    of ``ws_web_site_sk``); the survivors keep ``(order, cost, profit)``
    and are compacted to ``survivor_capacity`` rows, with an overflow flag
    of the filter's own.

    ``q95.exchange`` — three row sets to ``hash(order) % devices``, the
    hash over both words of the bigint, each through
    ``exchange.shuffle_records_shard`` (``group_by_destination`` with its
    ``row_sort``, ``ragged_exchange_shard``; packed):
    pairs ``(order, warehouse)`` of EVERY ``web_sales`` row, 3 words;
    ``(order)`` of every ``web_returns`` row, 2 words; the survivors'
    ``(order, cost, profit)``, 4 words. The fill record is the dead row,
    order number ``0xFFFFFFFF_FFFFFFFF``, which sorts last and joins
    nothing.

    ``q95.join`` — at the owner the pairs and the returns are sorted on
    the two-word key (a multi-operand ``lax.sort``, ``num_keys=2``, not
    stable: a stable one compiles three times as long). An order is in
    ``ws_wh`` when it has more than one distinct warehouse: that is what
    the self-join means, and the one departure from the query's text —
    its 132 rows an order are never built. In the sorted pairs an order
    has two warehouses where two neighbours of one order differ; a
    running maximum over the positions of order starts and of such
    differences tells, at an order's last row, whether one lay inside
    it. A survivor finds its order's last pair and its return by binary
    search. Then ``count(distinct order)`` (an order is its last pair's
    position), ``sum(cost)``, ``sum(profit)`` (signed) over the survivors
    of orders with both, the sums as ``_limb_sums`` so that none can
    wrap, and the counts of orders seen, multi-warehouse orders and
    returned orders, over every row.

    ``step.row_moves`` lists the forms the groupings' row moves took."""
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    spec, whole = P(axis_name), P()
    row_moves: list = []
    move = row_mover(mesh, row_moves)
    rows_in = cfg.ws_rows_per_device
    cap = cfg.survivor_capacity
    first_day = np.int32(cfg.window_start)
    last_day = np.int32(cfg.window_start + cfg.window_days)

    def exchange(rows, dest):
        fill = jnp.full((n, rows.shape[1]), DEAD, jnp.uint32)
        records, counts, delivered, overflowed = shuffle_records_shard(
            rows, dest, fill, axis_name, n, cfg.out_factor, impl, move)
        total = counts.sum()
        arrived = jnp.arange(records.shape[0], dtype=jnp.int32) < total
        # the records' columns; past what arrived the key is dead too
        columns = (jnp.where(arrived, records[:, 0], DEAD),
                   jnp.where(arrived, records[:, 1], DEAD),
                   *(records[:, k] for k in range(2, records.shape[1])))
        return columns, jnp.stack([delivered, total]), overflowed

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=((spec,) * 8, (spec,) * 2, (whole,) * 3),
        out_specs=Q95Answers(*(spec,) * 6))
    def step(web_sales, web_returns, dimensions):
        (ws_low, ws_high, warehouse, ship_date, ship_addr, web_site,
         cost, profit) = web_sales
        wr_low, wr_high = web_returns
        d_date, ca_state, web_company = dimensions
        ws_live = ~_is_dead(ws_low, ws_high)
        with jax.named_scope("q95.filter"):
            day = d_date[ship_date]
            passes = (ws_live & (day >= first_day) & (day <= last_day)
                      & (ca_state[ship_addr] == cfg.target_state)
                      & (web_company[web_site] == cfg.target_company))
            passed = passes.sum(dtype=jnp.int32)
            # the survivors' rows, first come first; ``rows_in`` where
            # none: the first row at which the running count reaches k
            at = jnp.searchsorted(
                jnp.cumsum(passes, dtype=jnp.int32),
                jnp.arange(1, cap + 1, dtype=jnp.int32)).astype(jnp.int32)
            kept = at < rows_in
            at = jnp.minimum(at, rows_in - 1)
            survivors = jnp.stack([
                jnp.where(kept, ws_low[at], DEAD),
                jnp.where(kept, ws_high[at], DEAD),
                cost[at].astype(jnp.uint32),
                profit[at].astype(jnp.uint32)], axis=1)
        with jax.named_scope("q95.exchange"):
            pairs, pairs_got, pairs_over = exchange(
                jnp.stack([ws_low, ws_high, warehouse.astype(jnp.uint32)],
                          axis=1),
                _owner(ws_low, ws_high, ws_live, n))
            returns, returns_got, returns_over = exchange(
                jnp.stack([wr_low, wr_high], axis=1),
                _owner(wr_low, wr_high, ~_is_dead(wr_low, wr_high), n))
            picked, picked_got, picked_over = exchange(
                survivors, _owner(survivors[:, 0], survivors[:, 1], kept, n))
        with jax.named_scope("q95.join"):
            # ws_wh: orders with more than one distinct warehouse. Within
            # an order the warehouses come in any order: where they are
            # not all one, two neighbours differ
            p_low, p_high, p_wh = pairs
            p_high, p_low, p_wh = jax.lax.sort(
                (p_high, p_low, p_wh), num_keys=2, is_stable=False)
            p_live = ~_is_dead(p_low, p_high)
            p_first, p_last = _key_edges(p_high, p_low)
            other = ~p_last & jnp.concatenate(
                [p_wh[1:] != p_wh[:-1], jnp.zeros(1, bool)])
            slots = p_high.shape[0]
            spot = jnp.arange(slots, dtype=jnp.int32)
            # odd where the latest event up to here is a second warehouse,
            # even where it is an order's first row
            latest = jax.lax.cummax(jnp.maximum(
                jnp.where(p_first, 2 * spot, -1),
                jnp.where(other, 2 * spot + 1, -1)))
            multi = (latest & 1) == 1
            # web_returns' order numbers
            r_low, r_high = returns
            r_high, r_low = jax.lax.sort((r_high, r_low), num_keys=2,
                                         is_stable=False)
            r_first, _ = _key_edges(r_high, r_low)
            # a survivor finds its order's last pair, and its return
            s_low, s_high, s_cost, s_profit = picked
            pair = jnp.maximum(
                _search(p_high, p_low, s_high, s_low, upper=True) - 1, 0)
            ret = jnp.minimum(
                _search(r_high, r_low, s_high, s_low, upper=False),
                r_high.shape[0] - 1)
            qualifies = (~_is_dead(s_low, s_high)
                         & (p_high[pair] == s_high) & (p_low[pair] == s_low)
                         & multi[pair]
                         & (r_high[ret] == s_high) & (r_low[ret] == s_low))
            # count(distinct order): an order is its last pair's position
            orders = jnp.zeros(slots, jnp.int32).at[
                jnp.where(qualifies, pair, slots)].set(1, mode="drop")
            counts = jnp.stack([
                orders.sum(dtype=jnp.int32),
                (p_live & p_first).sum(dtype=jnp.int32),
                (p_live & p_last & multi).sum(dtype=jnp.int32),
                (~_is_dead(r_low, r_high) & r_first).sum(dtype=jnp.int32)])
            as_i32 = functools.partial(jax.lax.bitcast_convert_type,
                                       new_dtype=jnp.int32)
            answers = Q95Answers(
                counts[None],
                _limb_sums(as_i32(s_cost), qualifies)[None],
                _limb_sums(as_i32(s_profit), qualifies)[None],
                jnp.stack([pairs_got, returns_got, picked_got])[None],
                passed[None],
                jnp.stack([passed > cap, pairs_over, returns_over,
                           picked_over])[None])
        return answers

    step.row_moves = row_moves
    return step


class Q95Job:
    """``job(resident) -> Q95Answers``: one q95 over resident tables.

    One dispatch of ``make_q95_step``'s program, one block, the answers
    left on the devices as ``jax.Array``s; only then are the filter's and
    every exchange's ``overflowed`` flags read, and one set raises
    ``OverflowError`` naming it. The program is built here, once, for any
    number of jobs.

    Spans, on ``self.tracer`` (a caller may set one per job): ``q95.job``
    (``ws_rows``, ``wr_rows``, ``orders``; at its end ``received``, the
    records delivered in each of the three exchanges, ``survivors`` and
    ``row_move``) around ``q95.dispatch`` and ``q95.wait``. Counters, per
    job: ``q95.recv_fill`` (most records any device received in an
    exchange, the exchange's fill among them, over that exchange's
    receive capacity) and ``q95.survivors``."""

    def __init__(self, mesh: Mesh, axis_name: str, cfg: Q95Config,
                 impl: str = "auto", tracer=trace.NULL):
        self.cfg = cfg
        self.tracer = tracer
        self._step = make_q95_step(mesh, axis_name, cfg, impl)
        n = mesh.shape[axis_name]
        sent = (cfg.ws_rows_per_device, cfg.wr_rows_per_device,
                cfg.survivor_capacity)
        self._capacity = np.array([
            record_capacity(rows, words, n, cfg.out_factor)
            for rows, words in zip(sent, _ROW_WORDS)])

    def __call__(self, resident: ResidentQ95) -> Q95Answers:
        tracer = self.tracer
        with tracer.span("q95.job", "q95", ws_rows=resident.ws_rows,
                         wr_rows=resident.wr_rows,
                         orders=resident.orders) as args:
            with tracer.span("q95.dispatch", "q95"):
                answers = self._step(resident.web_sales,
                                     resident.web_returns,
                                     resident.dimensions)
            with tracer.span("q95.wait", "q95"):
                jax.block_until_ready(answers)
            received = np.asarray(answers.received)
            survivors = int(np.asarray(answers.survivors).sum())
            args["received"] = received[:, :, 0].sum(axis=0).tolist()
            for rows in args["received"]:
                record_exchange(rows)
            args["survivors"] = survivors
            args["row_move"] = forms_label(self._step.row_moves)
            tracer.counter(
                "q95.recv_fill",
                float((received[:, :, 1].max(axis=0) / self._capacity).max()),
                "q95")
            tracer.counter("q95.survivors", survivors, "q95")
            late = np.asarray(answers.overflowed).any(axis=0)
            if late.any():
                names = [x for x, o in zip(("filter", *Q95_EXCHANGES), late)
                         if o]
                raise OverflowError(
                    f"q95 overflow in {names}: the filter passes more rows "
                    "than Q95Config.survivor_capacity, or an exchange's "
                    "fan-in exceeds out_factor's headroom")
        return answers


def run_q95(mesh: Mesh, cfg: Q95Config, axis_name: str = "shuffle",
            seed: int = 0, impl: str = "auto") -> Q95Totals:
    """One job over ``generate_q95(cfg, seed)``; returns the totals on the
    host. The small-size convenience over ``place_q95`` + ``Q95Job``."""
    tables = generate_q95(cfg, mesh.shape[axis_name], seed)
    job = Q95Job(mesh, axis_name, cfg, impl)
    return q95_totals(job(place_q95(mesh, axis_name, tables)))


# ===========================================================================
# q64
# ===========================================================================


@dataclass(frozen=True)
class Q64Config:
    ss_rows_per_device: int
    cs_rows_per_device: int
    num_items: int             # < 2**16
    num_dates: int = 365
    first_year_mod: int = 0    # dates with (date % 3) == mod are year Y
    sr_fraction: float = 0.5   # store returns coverage of store sales
    cr_fraction: float = 0.5   # catalog returns coverage
    zipf_a: float = 1.3        # item popularity skew
    out_factor: int = 4


def _zipf_items(rng, num_items, size, a):
    z = rng.zipf(a, size=size * 2)
    z = z[z <= num_items][:size]
    while len(z) < size:
        more = rng.zipf(a, size=size)
        z = np.concatenate([z, more[more <= num_items]])[:size]
    return (z - 1).astype(np.uint32)


def generate_q64(cfg: Q64Config, num_devices: int, seed: int = 0):
    """(ss[N,4], sr[R,2], cs[M,3], cr[Q,3], date[D,2]) as u32.

    ss: item, ticket, sold_date, price.  sr: item, ticket.
    cs: item, order, price.              cr: item, order, refund.
    date: date_sk, year (0 = Y, 1 = Y+1, 2 = other -> filtered).
    Tickets/orders are globally unique (row index), so (item, key) pairs
    are unique — the join-on-pair contract of the real tables."""
    assert cfg.num_items < (1 << _KEY_BITS)
    rng = np.random.default_rng(seed)
    n_ss = cfg.ss_rows_per_device * num_devices
    n_cs = cfg.cs_rows_per_device * num_devices
    assert max(n_ss, n_cs) < (1 << _KEY_BITS)
    ss = np.stack([
        _zipf_items(rng, cfg.num_items, n_ss, cfg.zipf_a),
        np.arange(n_ss, dtype=np.uint32),
        rng.integers(0, cfg.num_dates, n_ss).astype(np.uint32),
        rng.integers(0, 1000, n_ss).astype(np.uint32),
    ], axis=1)
    sr_rows = rng.permutation(n_ss)[: int(n_ss * cfg.sr_fraction)]
    sr = ss[np.sort(sr_rows)][:, :2].copy()
    cs = np.stack([
        _zipf_items(rng, cfg.num_items, n_cs, cfg.zipf_a),
        np.arange(n_cs, dtype=np.uint32),
        rng.integers(0, 1000, n_cs).astype(np.uint32),
    ], axis=1)
    cr_rows = rng.permutation(n_cs)[: int(n_cs * cfg.cr_fraction)]
    cr = np.concatenate(
        [cs[np.sort(cr_rows)][:, :2],
         rng.integers(0, 1000, len(cr_rows)).astype(np.uint32)
         .reshape(-1, 1)], axis=1)
    date = np.stack([
        np.arange(cfg.num_dates, dtype=np.uint32),
        ((np.arange(cfg.num_dates) + cfg.first_year_mod) % 3)
        .astype(np.uint32),
    ], axis=1)
    return ss, sr, cs, cr, date


def numpy_q64(ss, sr, cs, cr, date, cfg: Q64Config) -> Tuple[int, int]:
    """Oracle: (qualifying item count, sum of both years' price sums)."""
    year = dict(zip(date[:, 0].tolist(), date[:, 1].tolist()))
    # cs_ui: join cr on (item, order), group by item, HAVING
    refund_by_pair = {(i, o): r for i, o, r in cr.tolist()}
    sale: dict = {}
    refund: dict = {}
    for i, o, p in cs.tolist():
        sale[i] = sale.get(i, 0) + p
        refund[i] = refund.get(i, 0) + refund_by_pair.get((i, o), 0)
    ui = {i for i in sale if sale[i] > 2 * refund[i]}
    # store_sales ⋈ store_returns (inner) ⋈ date ⋈ cs_ui (semi)
    returned_pairs = {(i, t) for i, t in sr.tolist()}
    cnt = {}
    psum = {}
    for i, t, d, p in ss.tolist():
        if (i, t) not in returned_pairs or i not in ui:
            continue
        y = year.get(d)
        if y not in (0, 1):
            continue
        cnt[(i, y)] = cnt.get((i, y), 0) + 1
        psum[(i, y)] = psum.get((i, y), 0) + p
    # CTE self-join across years: cnt(Y+1) <= cnt(Y)
    items = 0
    total = 0
    for i in ui:
        c0, c1 = cnt.get((i, 0), 0), cnt.get((i, 1), 0)
        if c0 > 0 and c1 > 0 and c1 <= c0:
            items += 1
            total += psum.get((i, 0), 0) + psum.get((i, 1), 0)
    return items, total


def make_q64_step(mesh: Mesh, axis_name: str, cfg: Q64Config,
                  impl: str = "auto"):
    """q64 as FIVE chained exchange rounds in one jitted SPMD step.

    1. catalog_sales + catalog_returns by hash(item, order): pair join.
    2. joined rows by hash(item): per-item sale/refund sums -> cs_ui.
    3. store_sales + store_returns by hash(item, ticket): inner pair join.
    4. survivors + date_dim by hash(sold_date): year lookup + filter.
    5. survivors by hash(item): per-(item, year) aggregation, cs_ui
       semi-join, and the across-years CTE self-join (items co-located).
    Returns per-device ``(i32[D, 2], overflowed[D])`` partials."""
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    spec = P(axis_name)
    cap_ss = cfg.ss_rows_per_device * cfg.out_factor
    cap_cs = cfg.cs_rows_per_device * cfg.out_factor

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec,) * 5, out_specs=(spec, spec))
    def step(ss, sr, cs, cr, date):
        all_valid = jnp.ones  # shorthand

        # -- round 1: catalog pair join ---------------------------------
        cs_pk = _pairkey(cs[:, 0], cs[:, 1])
        cs_r, cs_v, o1 = _exchange(
            jnp.concatenate([cs, cs_pk[:, None]], axis=1),
            _route(cs_pk, all_valid(cs.shape[0], bool), n),
            axis_name, n, cap_cs, impl)
        cr_pk = _pairkey(cr[:, 0], cr[:, 1])
        cr_r, cr_v, o2 = _exchange(
            jnp.concatenate([cr, cr_pk[:, None]], axis=1),
            _route(cr_pk, all_valid(cr.shape[0], bool), n),
            axis_name, n, cap_cs, impl)
        refund, _found = _lookup(cr_r[:, 3], cr_v, cr_r[:, 2],
                                 jnp.where(cs_v, cs_r[:, 3], PAD))
        refund = jnp.where(_found, refund, jnp.uint32(0))

        # -- round 2: group catalog by item -> cs_ui --------------------
        joined = jnp.stack([cs_r[:, 0], cs_r[:, 2], refund], axis=1)
        j_r, j_v, o3 = _exchange(
            joined, _route(cs_r[:, 0], cs_v, n), axis_name, n,
            cap_cs, impl)
        ik = jnp.where(j_v, j_r[:, 0], PAD)
        perm = jnp.argsort(ik)
        ik_s = jnp.take(ik, perm)
        j_s = jnp.take(j_r, perm, axis=0)
        Ncs = ik_s.shape[0]
        new_seg = jnp.concatenate([jnp.ones(1, bool),
                                   ik_s[1:] != ik_s[:-1]])
        si = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
        live = ik_s != PAD
        sale_sum = jax.ops.segment_sum(
            jnp.where(live, j_s[:, 1], 0).astype(jnp.int32), si,
            num_segments=Ncs)
        refund_sum = jax.ops.segment_sum(
            jnp.where(live, j_s[:, 2], 0).astype(jnp.int32), si,
            num_segments=Ncs)
        seg_item = jax.ops.segment_max(ik_s, si, num_segments=Ncs)
        ui_flag = sale_sum > 2 * refund_sum
        # representative row per segment -> local (item, ui) table
        ui_item = jnp.where(ui_flag & (seg_item != PAD), seg_item, PAD)

        # -- round 3: store pair join (inner) ---------------------------
        ss_pk = _pairkey(ss[:, 0], ss[:, 1])
        ss_r, ss_v, o4 = _exchange(
            jnp.concatenate([ss, ss_pk[:, None]], axis=1),
            _route(ss_pk, all_valid(ss.shape[0], bool), n),
            axis_name, n, cap_ss, impl)
        sr_pk = _pairkey(sr[:, 0], sr[:, 1])
        sr_r, sr_v, o5 = _exchange(
            jnp.concatenate([sr, sr_pk[:, None]], axis=1),
            _route(sr_pk, all_valid(sr.shape[0], bool), n),
            axis_name, n, cap_ss, impl)
        _, ret_found = _lookup(sr_r[:, 2], sr_v, sr_r[:, 2],
                               jnp.where(ss_v, ss_r[:, 4], PAD))
        surv_v = ss_v & ret_found

        # -- round 4: date join on survivors ----------------------------
        d_r, d_v, o6 = _exchange(
            date, _route(date[:, 0], all_valid(date.shape[0], bool), n),
            axis_name, n, _dim_cap(date.shape[0], n), impl)
        s2, s2_v, o7 = _exchange(
            ss_r[:, :4], _route(ss_r[:, 2], surv_v, n),
            axis_name, n, cap_ss, impl)
        year, y_found = _lookup(d_r[:, 0], d_v, d_r[:, 1],
                                jnp.where(s2_v, s2[:, 2], PAD))
        in_years = y_found & (year <= 1)
        s2_v = s2_v & in_years

        # -- round 5: group by item; semi-join cs_ui; CTE self-join -----
        rows5 = jnp.stack([s2[:, 0], year, s2[:, 3]], axis=1)
        r5, v5, o8 = _exchange(rows5, _route(s2[:, 0], s2_v, n),
                               axis_name, n, cap_ss, impl)
        ik5 = jnp.where(v5, r5[:, 0], PAD)
        perm5 = jnp.argsort(ik5)
        ik5_s = jnp.take(ik5, perm5)
        r5_s = jnp.take(r5, perm5, axis=0)
        N5 = ik5_s.shape[0]
        ns5 = jnp.concatenate([jnp.ones(1, bool), ik5_s[1:] != ik5_s[:-1]])
        si5 = jnp.cumsum(ns5.astype(jnp.int32)) - 1
        live5 = ik5_s != PAD
        y1 = live5 & (r5_s[:, 1] == 1)
        y0 = live5 & (r5_s[:, 1] == 0)
        cnt0 = jax.ops.segment_sum(y0.astype(jnp.int32), si5,
                                   num_segments=N5)
        cnt1 = jax.ops.segment_sum(y1.astype(jnp.int32), si5,
                                   num_segments=N5)
        sum01 = jax.ops.segment_sum(
            jnp.where(live5, r5_s[:, 2], 0).astype(jnp.int32), si5,
            num_segments=N5)
        item5 = jax.ops.segment_max(ik5_s, si5, num_segments=N5)
        # semi-join against this device's cs_ui slice: items were routed
        # by the SAME hash in rounds 2 and 5, so the lookup is local
        _, is_ui = _lookup(ui_item, ui_item != PAD, ui_item, item5)
        qual = is_ui & (item5 != PAD) & (cnt0 > 0) & (cnt1 > 0) \
            & (cnt1 <= cnt0)
        items = qual.astype(jnp.int32).sum()
        total = jnp.where(qual, sum01, 0).sum()
        overflowed = o1 | o2 | o3 | o4 | o5 | o6 | o7 | o8
        return jnp.stack([items, total])[None], overflowed[None]

    return step


def run_q64(mesh: Mesh, cfg: Q64Config, axis_name: str = "shuffle",
            seed: int = 0, impl: str = "auto") -> Tuple[int, int]:
    """Host driver: returns the exact global q64 answer."""
    n = mesh.shape[axis_name]
    ss, sr, cs, cr, date = generate_q64(cfg, n, seed)
    step = make_q64_step(mesh, axis_name, cfg, impl)
    shard = NamedSharding(mesh, P(axis_name))
    args = [jax.device_put(pad_rows_to_devices(t, n), shard)
            for t in (ss, sr, cs, cr, date)]
    partial, overflowed = jax.block_until_ready(step(*args))
    if np.asarray(overflowed).any():
        raise OverflowError("q64 exchange overflowed; raise out_factor")
    totals = np.asarray(partial).sum(axis=0).astype(np.int64)
    return int(totals[0]), int(totals[1])


def pad_rows_to_devices(table: np.ndarray, n: int) -> np.ndarray:
    """Pad a global table to a device multiple with PAD rows (dead keys
    never match a lookup and never route anywhere)."""
    rem = (-len(table)) % n
    if rem == 0:
        return table
    padding = np.full((rem, table.shape[1]), PAD, dtype=table.dtype)
    return np.concatenate([table, padding])


# ===========================================================================
# engine-DAG variants (the drop-in SPI path)
# ===========================================================================


def _engine_dep(num_partitions: int, width: int):
    from sparkrdma_tpu.shuffle.manager import PartitionerSpec
    from sparkrdma_tpu.shuffle.spark_compat import ShuffleDependency

    return ShuffleDependency(num_partitions, PartitionerSpec("modulo"),
                             row_payload_bytes=4 * width)


def _engine_src(table: np.ndarray, keyfn, num_maps: int):
    """Source-stage task fn: stripe ``table`` across map tasks, write
    u32 rows keyed by ``keyfn(rows) -> u64``."""
    width = table.shape[1] * 4

    def fn(ctx, writer, task, _t=table, _w=width):
        rows = _t[task::num_maps]
        writer.write((keyfn(rows), np.ascontiguousarray(rows, "<u4")
                      .view(np.uint8).reshape(len(rows), _w)))
    return fn


def _read_u32(ctx, parent: int, width: int):
    """Drain one parent shuffle into (keys u64[N], cols u32[N, width])."""
    ks, vs = [], []
    for keys, payload in ctx.read(parent).readBatches():
        ks.append(keys)
        vs.append(np.ascontiguousarray(payload).view("<u4")
                  .reshape(len(keys), -1))
    if not ks:
        return np.zeros(0, np.uint64), np.zeros((0, width), np.uint32)
    return np.concatenate(ks), np.concatenate(vs)


def _np_lookup(dkeys, dattr, probes):
    """Vectorized unique-key join: (attr[N] u32, found[N] bool)."""
    if len(dkeys) == 0:
        return (np.zeros(len(probes), np.uint32),
                np.zeros(len(probes), bool))
    order = np.argsort(dkeys)
    ks, at = dkeys[order], dattr[order]
    idx = np.clip(np.searchsorted(ks, probes), 0, len(ks) - 1)
    return at[idx].astype(np.uint32), ks[idx] == probes


def build_q95_job(cfg: Q95Config, num_maps: int, num_partitions: int,
                  seed: int = 0, data_scale: int = 1):
    """q95 as a stage DAG for ``engine.DAGEngine.run``: five sources,
    three dimension shuffle-join MapStages, a final by-order ResultStage
    — seven shuffles through the SPI, every one keyed on the u64 lane
    (the order number whole). The dimensions are shuffle-joined here, not
    broadcast: the engine form exercises the SPI, ``make_q95_step`` is
    the plan Spark runs. Over ``generate_q95(cfg, data_scale, seed)``.
    Returns (result_stage, finish); ``finish`` gives the query's three
    answers."""
    from sparkrdma_tpu.engine import MapStage, ResultStage

    t = generate_q95(cfg, data_scale, seed)

    def dep(width):
        return _engine_dep(num_partitions, width)

    def col(key_col):
        return lambda rows, _k=key_col: rows[:, _k].astype(np.uint64)

    def order_key(rows):
        return rows[:, 0].astype(np.uint64) | (
            rows[:, 1].astype(np.uint64) << np.uint64(32))

    def dim(attr):
        return np.stack([np.arange(len(attr)), attr], axis=1).astype(
            np.uint32)

    # working rows: order low, order high, warehouse, ship date, ship
    # address, web site, cost, profit (its bits), flags
    ws9 = np.stack([
        *_words(t.ws_order), *(c.astype(np.uint32) for c in t[1:7]),
        np.zeros(len(t.ws_order), np.uint32)], axis=1)
    wr = np.stack(_words(t.wr_order), axis=1)
    ws_st = MapStage(num_maps, dep(9),
                     _engine_src(ws9, col(3), num_maps))   # by ship_date
    date_st = MapStage(num_maps, dep(2),
                       _engine_src(dim(t.d_date), col(0), num_maps))
    addr_st = MapStage(num_maps, dep(2),
                       _engine_src(dim(t.ca_state), col(0), num_maps))
    site_st = MapStage(num_maps, dep(2),
                       _engine_src(dim(t.web_company), col(0), num_maps))
    wr_st = MapStage(num_maps, dep(2),
                     _engine_src(wr, order_key, num_maps))  # by order

    lo, hi = cfg.window_start, cfg.window_start + cfg.window_days

    def join_stage(key_col, next_key, flag_bit, pred):
        def fn(ctx, writer, task, _k=key_col, _nk=next_key,
               _b=flag_bit, _p=pred):
            _, rows = _read_u32(ctx, 0, 9)
            dkeys, dcols = _read_u32(ctx, 1, 2)
            attr, found = _np_lookup(dkeys, dcols[:, 1],
                                     rows[:, _k].astype(np.uint64))
            ok = found & _p(attr)
            rows = rows.copy()
            rows[:, 8] |= np.where(ok, np.uint32(_b), np.uint32(0))
            writer.write((_nk(rows),
                          np.ascontiguousarray(rows, "<u4").view(np.uint8)
                          .reshape(len(rows), 36)))
            del task
        return fn

    j1 = MapStage(num_partitions, dep(9),
                  join_stage(3, col(4), 1, lambda d: (d >= lo) & (d <= hi)),
                  parents=[ws_st, date_st])
    j2 = MapStage(num_partitions, dep(9),
                  join_stage(4, col(5), 2, lambda s: s == cfg.target_state),
                  parents=[j1, addr_st])
    j3 = MapStage(num_partitions, dep(9),
                  join_stage(5, order_key, 4,
                             lambda c: c == cfg.target_company),
                  parents=[j2, site_st])

    def final_fn(ctx, task):
        keys, rows = _read_u32(ctx, 0, 9)
        wr_keys, _wr_rows = _read_u32(ctx, 1, 2)
        returned = set(wr_keys.tolist())
        wh_by_order: dict = {}
        for o, w in zip(keys.tolist(), rows[:, 2].tolist()):
            wh_by_order.setdefault(o, set()).add(w)
        multi = {o for o, s in wh_by_order.items() if len(s) > 1}
        signed = rows[:, 6:8].view(np.int32).tolist()
        orders = set()
        cost = profit = 0
        for o, flags, (c, p) in zip(keys.tolist(), rows[:, 8].tolist(),
                                    signed):
            if flags == 7 and o in multi and o in returned:
                orders.add(o)
                cost += c
                profit += p
        del task
        return len(orders), cost, profit

    result = ResultStage(num_partitions, final_fn, parents=[j3, wr_st])

    def finish(results):
        return (sum(r[0] for r in results), sum(r[1] for r in results),
                sum(r[2] for r in results))

    return result, finish


def build_q64_job(cfg: Q64Config, num_maps: int, num_partitions: int,
                  seed: int = 0, data_scale: int = 1):
    """q64 as a stage DAG: five sources, catalog pair-join, catalog
    group-by(item) -> cs_ui, store pair-join, date join, final by-item
    ResultStage with the across-years CTE self-join — eight shuffles
    through the SPI. Returns (result_stage, finish)."""
    from sparkrdma_tpu.engine import MapStage, ResultStage

    ss, sr, cs, cr, date = generate_q64(cfg, data_scale, seed)

    def dep(width):
        return _engine_dep(num_partitions, width)

    def pair_u64(rows):
        return (rows[:, 0].astype(np.uint64) << _KEY_BITS) | \
            rows[:, 1].astype(np.uint64)

    def col0_u64(rows):
        return rows[:, 0].astype(np.uint64)

    cs_st = MapStage(num_maps, dep(3), _engine_src(cs, pair_u64, num_maps))
    cr_st = MapStage(num_maps, dep(3), _engine_src(cr, pair_u64, num_maps))
    ss_st = MapStage(num_maps, dep(4), _engine_src(ss, pair_u64, num_maps))
    sr_st = MapStage(num_maps, dep(2), _engine_src(sr, pair_u64, num_maps))
    date_st = MapStage(num_maps, dep(2),
                       _engine_src(date, col0_u64, num_maps))

    def cat_join_fn(ctx, writer, task):
        cs_keys, cs_rows = _read_u32(ctx, 0, 3)
        cr_keys, cr_rows = _read_u32(ctx, 1, 3)
        refund_by_pair = dict(zip(cr_keys.tolist(),
                                  cr_rows[:, 2].tolist()))
        refunds = np.array([refund_by_pair.get(k, 0)
                            for k in cs_keys.tolist()], np.uint32)
        out = np.stack([cs_rows[:, 0], cs_rows[:, 2], refunds], axis=1)
        writer.write((cs_rows[:, 0].astype(np.uint64),
                      np.ascontiguousarray(out, "<u4").view(np.uint8)
                      .reshape(len(out), 12)))
        del task

    cat_join = MapStage(num_partitions, dep(3), cat_join_fn,
                        parents=[cs_st, cr_st])

    def ui_fn(ctx, writer, task):
        _, rows = _read_u32(ctx, 0, 3)
        sale: dict = {}
        refund: dict = {}
        for i, p, r in rows.tolist():
            sale[i] = sale.get(i, 0) + p
            refund[i] = refund.get(i, 0) + r
        ui = np.array([i for i in sale if sale[i] > 2 * refund[i]],
                      np.uint32).reshape(-1, 1)
        writer.write((ui[:, 0].astype(np.uint64),
                      np.ascontiguousarray(ui, "<u4").view(np.uint8)
                      .reshape(len(ui), 4)))
        del task

    ui_st = MapStage(num_partitions, dep(1), ui_fn, parents=[cat_join])

    def store_join_fn(ctx, writer, task):
        ss_keys, ss_rows = _read_u32(ctx, 0, 4)
        sr_keys, _ = _read_u32(ctx, 1, 2)
        returned = set(sr_keys.tolist())
        keep = np.array([k in returned for k in ss_keys.tolist()], bool)
        rows = ss_rows[keep]
        writer.write((rows[:, 2].astype(np.uint64),   # by sold_date
                      np.ascontiguousarray(rows, "<u4").view(np.uint8)
                      .reshape(len(rows), 16)))
        del task

    store_join = MapStage(num_partitions, dep(4), store_join_fn,
                          parents=[ss_st, sr_st])

    def date_join_fn(ctx, writer, task):
        _, rows = _read_u32(ctx, 0, 4)
        dkeys, dcols = _read_u32(ctx, 1, 2)
        year = dict(zip(dkeys.tolist(), dcols[:, 1].tolist()))
        ys = np.array([year.get(d, 99) for d in rows[:, 2].tolist()],
                      np.uint32)
        keep = ys <= 1
        out = np.stack([rows[:, 0][keep], ys[keep], rows[:, 3][keep]],
                       axis=1)
        writer.write((out[:, 0].astype(np.uint64),    # by item
                      np.ascontiguousarray(out, "<u4").view(np.uint8)
                      .reshape(len(out), 12)))
        del task

    date_join = MapStage(num_partitions, dep(3), date_join_fn,
                         parents=[store_join, date_st])

    def final_fn(ctx, task):
        _, rows = _read_u32(ctx, 0, 3)
        ui_keys, _ = _read_u32(ctx, 1, 1)
        ui = set(ui_keys.tolist())
        cnt: dict = {}
        psum: dict = {}
        for i, y, p in rows.tolist():
            if i not in ui:
                continue
            cnt[(i, y)] = cnt.get((i, y), 0) + 1
            psum[(i, y)] = psum.get((i, y), 0) + p
        items = total = 0
        for i in {i for i, _y in cnt}:
            c0, c1 = cnt.get((i, 0), 0), cnt.get((i, 1), 0)
            if c0 > 0 and c1 > 0 and c1 <= c0:
                items += 1
                total += psum.get((i, 0), 0) + psum.get((i, 1), 0)
        del task
        return items, total

    result = ResultStage(num_partitions, final_fn,
                         parents=[date_join, ui_st])

    def finish(results):
        return (sum(r[0] for r in results), sum(r[1] for r in results))

    return result, finish
