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

Both run two ways against ONE numpy oracle each:
  - ``make_q95_step`` / ``make_q64_step``: every shuffle is a collective
    ragged exchange chained inside ONE jitted shard_map step (dimension
    joins are expressed as shuffle joins — heavier than Spark's broadcast
    hash joins on purpose: the exchange is the thing under test).
    Static shapes throughout: selectivity travels as flag bits on the
    rows, never as data-dependent row counts.
  - ``build_q95_job`` / ``build_q64_job``: the same logical plan as a
    stage DAG for ``engine.DAGEngine.run`` — source stages, join
    MapStages, aggregating ResultStage — driving the drop-in shuffle SPI
    exactly the way Spark SQL's stage graph drives the reference.

Key-space convention: item/order/ticket keys fit 16 bits so an exact
(item, order) pair key fits one u32 lane (pairkey = item << 16 | order);
the engine path uses the native u64 key lane instead. PAD = 0xFFFFFFFF
marks dead rows.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.ops.partition import hash_partition
from sparkrdma_tpu.parallel.exchange import resolve_impl, shuffle_shard

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


@dataclass(frozen=True)
class Q95Config:
    ws_rows_per_device: int
    num_orders: int            # < 2**16
    num_warehouses: int = 8
    num_dates: int = 365
    window_start: int = 40     # d_date in [start, start + 60)
    num_states: int = 16
    target_state: int = 3
    num_sites: int = 12
    num_companies: int = 4
    target_company: int = 1
    return_fraction: float = 0.4
    out_factor: int = 3


def generate_q95(cfg: Q95Config, num_devices: int, seed: int = 0):
    """(ws[N,7], wr[R,1], date[D,2], addr[A,2], site[S,2]) as u32.

    ws columns: order, warehouse, ship_date, ship_addr, site, cost,
    profit. Orders are zipf-ish popular (several line items per order —
    the self-semi-join needs real multi-row orders)."""
    assert cfg.num_orders < (1 << _KEY_BITS)
    rng = np.random.default_rng(seed)
    n_rows = cfg.ws_rows_per_device * num_devices
    order = rng.integers(0, cfg.num_orders, n_rows)
    ws = np.stack([
        order,
        rng.integers(0, cfg.num_warehouses, n_rows),
        rng.integers(0, cfg.num_dates, n_rows),
        rng.integers(0, cfg.num_states * 50, n_rows),
        rng.integers(0, cfg.num_sites, n_rows),
        rng.integers(0, 1000, n_rows),
        rng.integers(0, 1000, n_rows),
    ], axis=1).astype(np.uint32)
    returned = rng.permutation(cfg.num_orders)[
        : int(cfg.num_orders * cfg.return_fraction)]
    wr = np.sort(returned).astype(np.uint32).reshape(-1, 1)
    date = np.stack([np.arange(cfg.num_dates),
                     np.arange(cfg.num_dates)], axis=1).astype(np.uint32)
    addr = np.stack([np.arange(cfg.num_states * 50),
                     np.arange(cfg.num_states * 50) % cfg.num_states],
                    axis=1).astype(np.uint32)
    site = np.stack([np.arange(cfg.num_sites),
                     np.arange(cfg.num_sites) % cfg.num_companies],
                    axis=1).astype(np.uint32)
    return ws, wr, date, addr, site


def numpy_q95(ws, wr, date, addr, site, cfg: Q95Config
              ) -> Tuple[int, int, int]:
    """Oracle: (distinct qualifying orders, sum cost, sum profit)."""
    d_date = dict(zip(date[:, 0].tolist(), date[:, 1].tolist()))
    a_state = dict(zip(addr[:, 0].tolist(), addr[:, 1].tolist()))
    s_comp = dict(zip(site[:, 0].tolist(), site[:, 1].tolist()))
    returned = set(wr[:, 0].tolist())
    wh_by_order: dict = {}
    for o, w in zip(ws[:, 0].tolist(), ws[:, 1].tolist()):
        wh_by_order.setdefault(o, set()).add(w)
    multi = {o for o, whs in wh_by_order.items() if len(whs) > 1}
    lo, hi = cfg.window_start, cfg.window_start + 60
    orders = set()
    cost = profit = 0
    for o, _w, dt, ad, st, c, p in ws.tolist():
        dd = d_date.get(dt)
        if dd is None or not (lo <= dd < hi):
            continue
        if a_state.get(ad) != cfg.target_state:
            continue
        if s_comp.get(st) != cfg.target_company:
            continue
        if o not in multi or o not in returned:
            continue
        orders.add(o)
        cost += c
        profit += p
    return len(orders), cost, profit


def make_q95_step(mesh: Mesh, axis_name: str, cfg: Q95Config,
                  impl: str = "auto"):
    """q95 as FOUR chained exchange rounds in one jitted SPMD step.

    Rounds 1-3 shuffle-join the three dimensions (date/addr/site),
    accumulating pass/fail as flag bits on the moving rows; round 4
    co-locates web_sales and web_returns by order_number, where the
    multi-warehouse self-semi-join and the returns semi-join become
    per-order segment reductions. Returns per-device partials
    ``(i32[D, 3], overflowed[D])``: host-sums give the exact answer
    (each order lives on exactly one device)."""
    n = mesh.shape[axis_name]
    impl = resolve_impl(mesh, impl, axis_name)
    spec = P(axis_name)
    F = cfg.ws_rows_per_device
    cap = F * cfg.out_factor
    lo = np.uint32(cfg.window_start)
    hi = np.uint32(cfg.window_start + 60)

    def dim_round(rows, valid, key_col, dim, flag_bit, pred):
        """Shuffle-join one dimension; OR ``pred(attr) & found`` into the
        flags column (col 7); returns (rows, valid, overflow)."""
        d_recv, d_valid, of_d = _exchange(
            dim, _route(dim[:, 0], jnp.ones(dim.shape[0], bool), n),
            axis_name, n, _dim_cap(dim.shape[0], n), impl)
        keys = rows[:, key_col]
        f_recv, f_valid, of_f = _exchange(
            rows, _route(keys, valid, n), axis_name, n, cap, impl)
        attr, found = _lookup(d_recv[:, 0], d_valid, d_recv[:, 1],
                              jnp.where(f_valid, f_recv[:, key_col], PAD))
        ok = found & pred(attr)
        flags = f_recv[:, 7] | jnp.where(ok, jnp.uint32(flag_bit),
                                         jnp.uint32(0))
        return (f_recv.at[:, 7].set(flags), f_valid, of_d | of_f)

    @jax.jit
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec,) * 5, out_specs=(spec, spec))
    def step(ws, wr, date, addr, site):
        # working rows: [order, wh, date, addr, site, cost, profit, flags]
        rows = jnp.concatenate(
            [ws, jnp.zeros((ws.shape[0], 1), jnp.uint32)], axis=1)
        valid = jnp.ones(rows.shape[0], bool)
        rows, valid, of1 = dim_round(
            rows, valid, 2, date, 1, lambda d: (d >= lo) & (d < hi))
        rows, valid, of2 = dim_round(
            rows, valid, 3, addr, 2,
            lambda s: s == np.uint32(cfg.target_state))
        rows, valid, of3 = dim_round(
            rows, valid, 4, site, 4,
            lambda c: c == np.uint32(cfg.target_company))
        # round 4: co-locate by order_number (fact AND returns)
        rows, valid, of4 = _exchange(
            rows, _route(rows[:, 0], valid, n), axis_name, n, cap, impl)
        wr_recv, wr_valid, of5 = _exchange(
            wr, _route(wr[:, 0], jnp.ones(wr.shape[0], bool), n),
            axis_name, n, _dim_cap(wr.shape[0], n), impl)

        # per-order segment reductions over order-sorted rows
        o = jnp.where(valid, rows[:, 0], PAD)
        perm = jnp.argsort(o)
        o_s = jnp.take(o, perm)
        r_s = jnp.take(rows, perm, axis=0)
        N = o_s.shape[0]
        new_seg = jnp.concatenate(
            [jnp.ones(1, bool), o_s[1:] != o_s[:-1]])
        si = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
        live = o_s != PAD
        wh = r_s[:, 1]
        min_wh = jax.ops.segment_min(
            jnp.where(live, wh, PAD), si, num_segments=N)
        max_wh = jax.ops.segment_max(
            jnp.where(live, wh, jnp.uint32(0)), si, num_segments=N)
        multi = min_wh != max_wh          # ws_wh: >1 distinct warehouse
        _, has_ret = _lookup(wr_recv[:, 0], wr_valid,
                             wr_recv[:, 0], o_s)
        qual = (live & (r_s[:, 7] == 7) & has_ret
                & jnp.take(multi, si))
        # distinct via segment_sum (identity 0 — segment_max's int32
        # identity is INT32_MIN on unoccupied segments)
        distinct = (jax.ops.segment_sum(
            qual.astype(jnp.int32), si, num_segments=N) > 0).sum()
        cost = jnp.where(qual, r_s[:, 5], 0).astype(jnp.int32).sum()
        profit = jnp.where(qual, r_s[:, 6], 0).astype(jnp.int32).sum()
        overflowed = of1 | of2 | of3 | of4 | of5
        return (jnp.stack([distinct, cost, profit])[None],
                overflowed[None])

    return step


def run_q95(mesh: Mesh, cfg: Q95Config, axis_name: str = "shuffle",
            seed: int = 0, impl: str = "auto") -> Tuple[int, int, int]:
    """Host driver: returns the exact global q95 answer."""
    n = mesh.shape[axis_name]
    ws, wr, date, addr, site = generate_q95(cfg, n, seed)
    step = make_q95_step(mesh, axis_name, cfg, impl)
    shard = NamedSharding(mesh, P(axis_name))
    args = [jax.device_put(pad_rows_to_devices(t, n), shard)
            for t in (ws, wr, date, addr, site)]
    partial, overflowed = jax.block_until_ready(step(*args))
    if np.asarray(overflowed).any():
        raise OverflowError("q95 exchange overflowed; raise out_factor")
    totals = np.asarray(partial).sum(axis=0).astype(np.int64)
    return int(totals[0]), int(totals[1]), int(totals[2])


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
    — seven shuffles through the SPI. Returns (result_stage, finish)."""
    from sparkrdma_tpu.engine import MapStage, ResultStage

    ws, wr, date, addr, site = generate_q95(cfg, data_scale, seed)

    def dep(width):
        return _engine_dep(num_partitions, width)

    def col(key_col):
        return lambda rows, _k=key_col: rows[:, _k].astype(np.uint64)

    # working rows carry an extra flags column (col 7)
    ws8 = np.concatenate(
        [ws, np.zeros((len(ws), 1), np.uint32)], axis=1)
    ws_st = MapStage(num_maps, dep(8),
                     _engine_src(ws8, col(2), num_maps))   # by ship_date
    date_st = MapStage(num_maps, dep(2), _engine_src(date, col(0), num_maps))
    addr_st = MapStage(num_maps, dep(2), _engine_src(addr, col(0), num_maps))
    site_st = MapStage(num_maps, dep(2), _engine_src(site, col(0), num_maps))
    wr_st = MapStage(num_maps, dep(1),
                     _engine_src(wr, col(0), num_maps))    # by order

    lo, hi = cfg.window_start, cfg.window_start + 60

    def join_stage(key_col, next_key_col, flag_bit, pred):
        def fn(ctx, writer, task, _k=key_col, _nk=next_key_col,
               _b=flag_bit, _p=pred):
            _, rows = _read_u32(ctx, 0, 8)
            dkeys, dcols = _read_u32(ctx, 1, 2)
            attr, found = _np_lookup(dkeys, dcols[:, 1],
                                     rows[:, _k].astype(np.uint64))
            ok = found & _p(attr)
            rows = rows.copy()
            rows[:, 7] |= np.where(ok, np.uint32(_b), np.uint32(0))
            writer.write((rows[:, _nk].astype(np.uint64),
                          np.ascontiguousarray(rows, "<u4").view(np.uint8)
                          .reshape(len(rows), 32)))
            del task
        return fn

    j1 = MapStage(num_partitions, dep(8),
                  join_stage(2, 3, 1, lambda d: (d >= lo) & (d < hi)),
                  parents=[ws_st, date_st])
    j2 = MapStage(num_partitions, dep(8),
                  join_stage(3, 4, 2, lambda s: s == cfg.target_state),
                  parents=[j1, addr_st])
    j3 = MapStage(num_partitions, dep(8),
                  join_stage(4, 0, 4, lambda c: c == cfg.target_company),
                  parents=[j2, site_st])

    def final_fn(ctx, task):
        _, rows = _read_u32(ctx, 0, 8)
        wr_keys, _wr_rows = _read_u32(ctx, 1, 1)
        returned = set(wr_keys.tolist())
        wh_by_order: dict = {}
        for o, w in zip(rows[:, 0].tolist(), rows[:, 1].tolist()):
            wh_by_order.setdefault(o, set()).add(w)
        multi = {o for o, s in wh_by_order.items() if len(s) > 1}
        orders = set()
        cost = profit = 0
        for r in rows.tolist():
            o = r[0]
            if r[7] == 7 and o in multi and o in returned:
                orders.add(o)
                cost += r[5]
                profit += r[6]
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
