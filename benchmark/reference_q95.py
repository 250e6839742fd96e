"""The plain reference of the ``tpcds_q95`` configuration, in int64 numpy
alone: nothing here imports the program or jax. Written from the query's
text (TPC-DS ``query95.tpl``), not from ``sparkrdma_tpu/models``::

    with ws_wh as
     (select ws1.ws_order_number, ws1.ws_warehouse_sk wh1,
             ws2.ws_warehouse_sk wh2
      from web_sales ws1, web_sales ws2
      where ws1.ws_order_number = ws2.ws_order_number
        and ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
    select count(distinct ws_order_number), sum(ws_ext_ship_cost),
           sum(ws_net_profit)
    from web_sales ws1, date_dim, customer_address, web_site
    where d_date between '1999-2-01' and
              (cast('1999-2-01' as date) + 60 days)
      and ws1.ws_ship_date_sk = d_date_sk
      and ws1.ws_ship_addr_sk = ca_address_sk and ca_state = 'IL'
      and ws1.ws_web_site_sk = web_site_sk and web_company_name = 'pri'
      and ws1.ws_order_number in (select ws_order_number from ws_wh)
      and ws1.ws_order_number in
          (select wr_order_number from web_returns, ws_wh
           where wr_order_number = ws_wh.ws_order_number)

The tables come as the generator's columns (any object with these
attributes): ``ws_order``, ``ws_warehouse``, ``ws_ship_date``,
``ws_ship_addr``, ``ws_web_site``, ``ws_ext_ship_cost``,
``ws_net_profit``, ``wr_order``, and the dimensions as arrays indexed by
their surrogate key: ``d_date``, ``ca_state``, ``web_company``. A row
whose order number is negative is padding. Money is in cents. The
parameters (``window_start``, ``window_days``, ``target_state``,
``target_company``) are the configuration's.

The comparison is exact: six integers, no tolerance. Beside the query's
three answers stand three counts over EVERY row (distinct orders in
``web_sales``, those among them in ``ws_wh``, distinct orders in
``web_returns``), because the answers rest on the few thousand rows the
filters leave and a shuffle that lost one row in ten thousand elsewhere
would pass them.
"""

from __future__ import annotations

import numpy as np

NAMES = ("orders", "ship_cost", "net_profit", "orders_seen",
         "multi_warehouse_orders", "returned_orders")


def survivor_mask(tables, params: dict) -> np.ndarray:
    """The ``web_sales`` rows the three dimension predicates pass."""
    t = tables
    day = np.asarray(t.d_date)[t.ws_ship_date]
    first = params["window_start"]
    return ((np.asarray(t.ws_order) >= 0)
            & (day >= first) & (day <= first + params["window_days"])
            & (np.asarray(t.ca_state)[t.ws_ship_addr]
               == params["target_state"])
            & (np.asarray(t.web_company)[t.ws_web_site]
               == params["target_company"]))


def reference_q95(tables, params: dict) -> dict:
    """The six integers, by ``NAMES``, as Python ints."""
    t = tables
    order = np.asarray(t.ws_order, np.int64)
    live = order >= 0
    # ws_wh: an order joins itself on two rows of different warehouses
    # exactly when it has more than one distinct warehouse
    span = int(np.max(t.ws_warehouse)) + 1 if len(order) else 1
    pairs = np.unique(order[live] * span
                      + np.asarray(t.ws_warehouse, np.int64)[live])
    seen, warehouses = np.unique(pairs // span, return_counts=True)
    ws_wh = seen[warehouses > 1]
    wr_order = np.asarray(t.wr_order, np.int64)
    returned = np.unique(wr_order[wr_order >= 0])
    returned_in_ws_wh = returned[np.isin(returned, ws_wh)]
    keep = (survivor_mask(t, params) & np.isin(order, ws_wh)
            & np.isin(order, returned_in_ws_wh))
    return dict(zip(NAMES, (
        len(np.unique(order[keep])),
        int(np.asarray(t.ws_ext_ship_cost, np.int64)[keep].sum()),
        int(np.asarray(t.ws_net_profit, np.int64)[keep].sum()),
        len(seen), len(ws_wh), len(returned))))


def q95_problems(got: dict, tables, params: dict) -> list:
    """What differs between ``got`` (the six integers by ``NAMES``) and
    the reference over ``tables``, as sentences; empty when all six are
    equal."""
    want = reference_q95(tables, params)
    return [f"{name} is {got.get(name)}, the reference's {want[name]}"
            for name in NAMES if got.get(name) != want[name]]
