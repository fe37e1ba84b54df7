"""The population `presto-tpu --serve` ships, in plain numpy: the
benchmark's own copy of what `presto_tpu/connectors/tpch.py` generates
on the host (TPC-H clauses 4.2.3 / 4.2.5 as that connector ports them:
1..7 lines an order, a third of the customers without orders, dense
order keys). It imports nothing of the program.

That generator draws each table's columns from one
`np.random.default_rng` stream in a fixed order, so a column's values
depend on every draw made before it. This file makes every draw in the
same order, also for columns it then drops, up to the last column a
reference reads (Q1/Q3/Q6's: the table below). It is the yardstick: if
the program's generator changes, the cell turns `correct: false`.

Units as in datagen.py: money in cents, rates in hundredths, dates in
days since 1970-01-01, dictionary columns in indexes of datagen.py's
pools (`SEGMENTS`, `RETURNFLAGS`, `LINESTATUSES`: the connector's pools,
in the same order).
"""

import threading

import numpy as np

from datagen import CURRENTDATE, ENDDATE, SEGMENTS, STARTDATE

COLUMNS = {
    "customer": ("c_custkey", "c_mktsegment"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "lineitem": (
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate",
    ),
}


def _retail_cents(partkey):
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _customer(sf: float) -> dict:
    n = int(150_000 * sf)
    rng = np.random.default_rng(5001)
    rng.integers(-99999, 999999, n)  # c_acctbal
    return {
        "c_custkey": np.arange(1, n + 1, dtype=np.int64),
        "c_mktsegment": rng.integers(0, len(SEGMENTS), n).astype(np.int32),
    }


def _orders_and_lineitem(sf: float):
    n_orders = int(1_500_000 * sf)
    n_cust = int(150_000 * sf)
    n_part = int(200_000 * sf)
    rng = np.random.default_rng(6001)

    orderkey = np.arange(1, n_orders + 1, dtype=np.int64)
    # no customer whose key is a multiple of 3 orders: such a draw goes
    # to the next key, and one past the last customer three keys back
    custkey = rng.integers(1, max(n_cust, 2), n_orders).astype(np.int64)
    custkey += custkey % 3 == 0
    custkey = np.where(custkey > n_cust, np.maximum(custkey - 3, 1), custkey)
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders)

    lines = rng.integers(1, 8, n_orders)  # 1..7 lines an order
    n = int(lines.sum())
    partkey = rng.integers(1, n_part + 1, n).astype(np.int64)
    rng.integers(0, 4, n)  # which of the part's four suppliers
    qty = rng.integers(1, 51, n).astype(np.int64)
    discount = rng.integers(0, 11, n).astype(np.int64)
    tax = rng.integers(0, 9, n).astype(np.int64)
    shipdate = (
        np.repeat(orderdate, lines).astype(np.int64) + rng.integers(1, 122, n)
    ).astype(np.int32)
    rng.integers(30, 91, n)  # l_commitdate
    receiptdate = (shipdate + rng.integers(1, 31, n)).astype(np.int32)
    accepted = rng.random(n) < 0.5  # A or R, for a line received by now
    returnflag = np.where(
        receiptdate <= CURRENTDATE, np.where(accepted, 0, 2), 1
    )
    orders = {
        "o_orderkey": orderkey,
        "o_custkey": custkey,
        "o_orderdate": orderdate.astype(np.int32),
        "o_shippriority": np.zeros(n_orders, np.int64),
    }
    lineitem = {
        "l_orderkey": np.repeat(orderkey, lines),
        "l_quantity": qty * 100,
        "l_extendedprice": qty * _retail_cents(partkey),
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": returnflag.astype(np.int32),
        "l_linestatus": (shipdate > CURRENTDATE).astype(np.int32),
        "l_shipdate": shipdate,
    }
    return orders, lineitem


_made = {}
_lock = threading.Lock()


def tables(sf: float) -> dict:
    """{table: {column: numpy array}} of `COLUMNS` at scale factor `sf`,
    made once a process (the population has no seed, as dbgen's): under
    a lock, because run.py answers parameter sets on four threads."""
    with _lock:
        if sf not in _made:
            orders, lineitem = _orders_and_lineitem(sf)
            _made[sf] = {
                "customer": _customer(sf), "orders": orders,
                "lineitem": lineitem,
            }
        return _made[sf]
