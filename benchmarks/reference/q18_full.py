"""TPC-H Q18 (large volume customer, clause 2.4.18) on the population
`presto-tpu --serve` ships (datagen_full.py), plain numpy. `TABLES` is
empty, as in q3_full.py: this module brings its tables itself, at the
parameter set's constant `sf` (stated in the mix; the SQL text has no
placeholder for it).

`o_totalprice` is no column of datagen_full.py: it is DERIVED here from
the copy's own lineitem columns by the connector's rule (per line
`ext * (100 - disc) // 100`, then `* (100 + tax) // 100`, summed per
order), and `c_name` is `Customer#` + the key zero-padded to 9. `acc`
as in q1: the control carries the sums and the prices in a float type.

The statement orders by (o_totalprice desc, o_orderdate) only, so which
of two orders tied on both makes the LIMIT is open: a parameter set
with such a tie at row 100 is refused here, loudly, as in q3.py."""

import numpy as np

import datagen_full
from refutil import dec, iso

TABLES = {}
LIMIT = 100


def ORDER_BY(row):  # order by o_totalprice desc, o_orderdate (ISO text)
    return -row[4], row[3]


def customer_name(custkey) -> str:
    return f"Customer#{int(custkey):09d}"


def _floor_div(x, d):
    if np.issubdtype(x.dtype, np.integer):
        return x // d
    return np.floor(x / x.dtype.type(d))


def line_gross(ext, disc, tax, acc=np.int64):
    """A line's part of its order's o_totalprice, in cents: rounded down
    after the discount and again after the tax, as the connector does."""
    net = _floor_div(ext.astype(acc) * (100 - disc.astype(acc)), 100)
    return _floor_div(net * (100 + tax.astype(acc)), 100)


def order_runs(lkey):
    """(keys, starts) of the runs of equal l_orderkey: the population
    stores a table's lines order by order."""
    if len(lkey) and np.any(lkey[1:] < lkey[:-1]):
        raise ValueError("q18_full: lineitem is not stored by l_orderkey")
    starts = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]])
    return lkey[starts], starts


def answer(_tables, p, acc=np.int64):
    t = datagen_full.tables(float(p["sf"]))
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    keys, starts = order_runs(li["l_orderkey"])
    if len(keys) == 0:
        return []
    qty = np.add.reduceat(li["l_quantity"].astype(acc), starts)
    # l_quantity is held in hundredths: the text's whole number x 100
    big = np.flatnonzero(qty > acc(int(p["quantity"]) * 100))
    if len(big) == 0:
        return []
    # only those orders' lines, each order's still together
    ends = np.r_[starts[1:], len(li["l_orderkey"])]
    lines = np.concatenate([np.arange(starts[g], ends[g]) for g in big])
    sub_starts = np.r_[0, np.cumsum(ends[big] - starts[big])[:-1]]
    total = np.add.reduceat(
        line_gross(
            li["l_extendedprice"][lines], li["l_discount"][lines],
            li["l_tax"][lines], acc,
        ),
        sub_starts,
    )
    # orders x customer: an order whose customer is missing drops out
    by_okey = np.argsort(o["o_orderkey"], kind="stable")
    pos = by_okey[np.searchsorted(o["o_orderkey"][by_okey], keys[big])]
    if not np.array_equal(o["o_orderkey"][pos], keys[big]):
        raise ValueError("q18_full: a line's order is not in orders")
    cust, odate = o["o_custkey"][pos], o["o_orderdate"][pos]
    has_customer = np.isin(cust, c["c_custkey"])
    rows = np.flatnonzero(has_customer)
    by = rows[np.lexsort((odate[rows], -total[rows]))[:LIMIT + 1]]
    top = by[:LIMIT]
    if len(by) > LIMIT and (
        total[by[LIMIT]] == total[top[-1]] and odate[by[LIMIT]] == odate[top[-1]]
    ):
        raise ValueError(
            f"q18_full {p}: rows {LIMIT} and {LIMIT + 1} tie on the ORDER BY"
        )
    return [
        (
            customer_name(cust[i]), int(cust[i]), int(keys[big][i]),
            iso(odate[i]), dec(total[i], 2), dec(qty[big][i], 2),
        )
        for i in top
    ]
