"""The statistics the served planner reads (connectors/system.py).

`CoordinatorServer` plans every statement through `SystemCatalog`. A
catalog that keeps statistics of its own (`TpchCatalog`,
`DeviceTpchCatalog`, the TPC-DS generator, the file connectors) must be
asked for them there too: `Connector.column_stats`, the SPI's 2^18-row
sample, reads a quarter of a sorted key's values and does not scale
them, and Q3's join was sized 25 to 100 times too large for it.
Planning only: nothing here runs a statement.
"""

import inspect
import os

import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.connectors.spi import Connector
from presto_tpu.connectors.system import METRICS, SystemCatalog
from presto_tpu.connectors.tpch import TpchCatalog
from presto_tpu.connectors.tpch_device import DeviceTpchCatalog
from presto_tpu.page import Page
from presto_tpu.plan import nodes as N
from presto_tpu.plan.stats import ColumnStats, derive
from presto_tpu.session import Session

BENCH_SQL = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "sql",
)
FIXED = ColumnStats(ndv=1234.0, min=-5.0, max=77.0)


class Bare:
    """A catalog by duck type, with no statistics of its own."""

    name = "bare"

    def __init__(self):
        self._page = Page.from_dict(
            {"k": (np.arange(100, dtype=np.int64) // 4, T.BIGINT)}
        )

    def table_names(self):
        return ["t"]

    def schema(self, table):
        return {"k": T.BIGINT}

    def row_count(self, table):
        return 100

    exact_row_count = row_count

    def unique_columns(self, table):
        return []

    def page(self, table):
        return self._page

    def scan(self, table, start, stop, pad_to=None, columns=None,
             predicate=None):
        return Connector.scan(
            self, table, start, stop, pad_to=pad_to, columns=columns
        )


class Stub(Bare):
    """...and one whose statistics are fixed answers."""

    def __init__(self):
        super().__init__()
        self.asked = []

    def column_stats(self, table, column):
        self.asked.append((table, column))
        return FIXED


# -- (a) who answers ---------------------------------------------------------


def test_a_user_table_is_answered_by_the_wrapped_catalog():
    stub = Stub()
    assert SystemCatalog(stub).column_stats("t", "k") is FIXED
    assert stub.asked == [("t", "k")]


def test_a_system_table_keeps_the_spi_sample():
    stub = Stub()
    got = SystemCatalog(stub).column_stats(METRICS, "value")
    assert isinstance(got, ColumnStats) and got is not FIXED
    assert stub.asked == []


def test_a_catalog_without_statistics_keeps_the_spi_sample():
    syscat = SystemCatalog(Bare())
    got = syscat.column_stats("t", "k")
    assert (got.ndv, got.min, got.max) == (25.0, 0.0, 24.0)
    assert syscat.column_stats("t", "k") is got  # cached, as the SPI caches


# -- (b) no default of the SPI is shadowed in silence ------------------------


def test_system_catalog_defines_every_method_the_spi_gives_a_body():
    """`SystemCatalog.__getattr__` delegates only what normal lookup
    does not find, and lookup finds every method `Connector` or
    `Catalog` defines. So a default added there answers for the wrapped
    catalog, unasked, until SystemCatalog routes it: it must do so by
    name."""
    own = vars(SystemCatalog)
    inherited = [
        name
        for base in SystemCatalog.__mro__[1:-1]
        for name, member in vars(base).items()
        if not name.startswith("_") and inspect.isfunction(member)
    ]
    assert "column_stats" in inherited and "scan" in inherited
    assert [name for name in inherited if name not in own] == []


# -- (c) the served plan is the catalog's plan -------------------------------


def q3() -> str:
    with open(os.path.join(BENCH_SQL, "q3.sql")) as f:
        return f.read().format(segment="BUILDING", date="1995-03-15")


def estimates(plan, catalog, pos="0"):
    """{position: (class name, estimated rows)} of a plan."""
    out = {pos: (type(plan).__name__, derive(plan, catalog).rows)}
    for i, child in enumerate(plan.children):
        out.update(estimates(child, catalog, f"{pos}.{i}"))
    return out


def test_q3_is_estimated_alike_served_and_direct():
    # SF0.1: lineitem is 600k rows, the smallest scale at which the
    # SPI's sample is not the whole table
    cat = TpchCatalog(sf=0.1)
    assert cat.exact_row_count("lineitem") > Connector.STATS_SAMPLE_ROWS
    syscat = SystemCatalog(cat)
    plan = Session(syscat).plan(q3())
    served = estimates(plan, syscat)
    assert served == estimates(Session(cat).plan(q3()), cat)
    joins = [p for p, (name, _rows) in served.items() if name == "Join"]
    top = min(joins, key=len)
    # far under `Executor._dyn_worthwhile`'s gate of 0.7: the lineitem
    # dynamic filter is derived (the SPI's sample read 0.93 at SF1)
    assert served[top][1] < 0.7 * served[top + ".0"][1]
    node = plan
    for i in top.split(".")[1:]:
        node = node.children[int(i)]
    assert isinstance(node, N.Join) and not node.unique_build


# -- the device catalog's own statistics over its sampling cap ---------------


@pytest.fixture(scope="module")
def half():
    # SF0.5: lineitem is 3M rows, the smallest over the 2M-row cap;
    # the numpy twin only, nothing is made on a device
    cat = DeviceTpchCatalog(sf=0.5)
    assert cat.row_count("lineitem") > cat.STATS_SAMPLE_ROWS
    return cat


@pytest.mark.parametrize(
    "column, lo, hi, ndv_low, ndv_high",
    [
        # stored sorted, four lines an order: the whole table's range,
        # and a distinct count that grows with the table
        ("l_orderkey", 1.0, 750_000.0, 375_000, 1_500_000),
        # stationary columns read what a prefix read
        ("l_quantity", 1.0, 50.0, 50, 50),
        ("l_discount", 0.0, 0.10, 11, 11),
        ("l_returnflag", None, None, 3, 3),
    ],
)
def test_device_statistics_over_the_cap(
    half, column, lo, hi, ndv_low, ndv_high
):
    got = half.column_stats("lineitem", column)
    assert (got.min, got.max) == (lo, hi)
    assert ndv_low <= got.ndv <= ndv_high
    assert SystemCatalog(half).column_stats("lineitem", column) is got
