"""The data set, in plain numpy: the benchmark's own copy of the population.

The program generates its tables on the device from counters
(`presto_tpu/benchmark/benchgen.py`); this file defines the same
population for the reference and imports nothing of the program. Every
column is a pure function of the row index through a splitmix64 counter
stream, so there is no seed: like dbgen's, the population of a scale
factor is fixed. Departures from dbgen (stated in each config's
`assumed`): every order has exactly 4 lines, quantities are 1..50, and
only the columns below exist.

Money is in cents, rates in hundredths, dates in days since 1970-01-01,
dictionary columns in pool indexes: the integers the SQL's decimals,
dates and strings stand for.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

STARTDATE = 8035  # 1992-01-01
CURRENTDATE = 9298  # 1995-06-17
ENDDATE = 10591  # 1998-12-31
LINES_PER_ORDER = 4
RETURNFLAGS = ("A", "N", "R")
LINESTATUSES = ("F", "O")
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")

CHUNK_ROWS = 1 << 20


def sizes(sf: float) -> dict:
    n_orders = max(int(1_500_000 * sf), 8)
    return {
        "orders": n_orders,
        "lineitem": n_orders * LINES_PER_ORDER,
        "customer": max(int(150_000 * sf), 4),
        "part": max(int(200_000 * sf), 4),
        "supplier": max(int(10_000 * sf), 2),
    }


def _u64(stream: int, i):
    base = (stream * 0xA0761D6478BD642F) & 0xFFFFFFFFFFFFFFFF
    z = (i + np.uint64(base)) * np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _uni(stream: int, i, lo: int, hi: int):
    """Uniform int64 in [lo, hi)."""
    return (_u64(stream, i) % np.uint64(hi - lo)).astype(np.int64) + lo


def _retail_cents(partkey):
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _lineitem(s, i, columns):
    order = i // np.uint64(LINES_PER_ORDER)
    out = {}
    if "l_orderkey" in columns:
        out["l_orderkey"] = order.astype(np.int64) + 1
    qty = _uni(4, i, 1, 51)
    if "l_quantity" in columns:
        out["l_quantity"] = qty * 100
    if "l_extendedprice" in columns:
        out["l_extendedprice"] = qty * _retail_cents(
            _uni(3, i, 1, s["part"] + 1)
        )
    if "l_discount" in columns:
        out["l_discount"] = _uni(5, i, 0, 11)
    if "l_tax" in columns:
        out["l_tax"] = _uni(6, i, 0, 9)
    ship = _uni(7, order, STARTDATE, ENDDATE - 151 + 1) + _uni(8, i, 1, 122)
    if "l_shipdate" in columns:
        out["l_shipdate"] = ship.astype(np.int32)
    if "l_linestatus" in columns:
        out["l_linestatus"] = (ship > CURRENTDATE).astype(np.int32)
    if "l_returnflag" in columns:
        receipt = ship + _uni(9, i, 1, 31)
        out["l_returnflag"] = np.where(
            receipt <= CURRENTDATE,
            np.where(_u64(10, i) % np.uint64(2) == 0, 0, 2),
            1,
        ).astype(np.int32)
    return out


def _orders(s, o, columns):
    out = {}
    if "o_orderkey" in columns:
        out["o_orderkey"] = o.astype(np.int64) + 1
    if "o_custkey" in columns:
        out["o_custkey"] = _uni(11, o, 1, s["customer"] + 1)
    if "o_orderdate" in columns:
        out["o_orderdate"] = _uni(
            7, o, STARTDATE, ENDDATE - 151 + 1
        ).astype(np.int32)
    if "o_shippriority" in columns:
        out["o_shippriority"] = np.zeros(o.shape, np.int64)
    return out


def _customer(s, i, columns):
    out = {}
    if "c_custkey" in columns:
        out["c_custkey"] = i.astype(np.int64) + 1
    if "c_mktsegment" in columns:
        out["c_mktsegment"] = (
            _u64(23, i) % np.uint64(len(SEGMENTS))
        ).astype(np.int32)
    return out


_GENERATORS = {"lineitem": _lineitem, "orders": _orders, "customer": _customer}


def columns(table: str, sf: float, names, threads: int = 8) -> dict:
    """{column: numpy array} for the whole table, made in row blocks on a
    few threads (numpy releases the interpreter lock in its loops)."""
    s = sizes(sf)
    n = s[table]
    names = tuple(names)
    gen = _GENERATORS[table]
    unknown = set(names) - set(gen(s, np.arange(1, dtype=np.uint64), names))
    if unknown:
        raise KeyError(f"{table} has no column(s) {sorted(unknown)}")
    starts = list(range(0, n, CHUNK_ROWS))

    def block(start):
        idx = np.arange(start, min(start + CHUNK_ROWS, n), dtype=np.uint64)
        return gen(s, idx, names)

    with ThreadPoolExecutor(max_workers=threads) as pool:
        blocks = list(pool.map(block, starts))
    return {c: np.concatenate([b[c] for b in blocks]) for c in names}
