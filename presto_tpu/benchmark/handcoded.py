"""Hand-composed TPC-H Q1 / Q6 kernel pipelines.

The analog of the reference's hand-coded operator benchmarks
(presto-benchmark/.../HandTpchQuery1.java, HandTpchQuery6.java): the query is
expressed directly against the kernel library, bypassing the SQL frontend.
These are the flagship single-chip and multi-chip execution paths until the
planner takes over; bench.py and __graft_entry__.py drive them.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .. import types as T
from ..connectors import tpch
from ..expr import ir
from ..expr.ir import col, comparison, lit
from ..ops.aggregate import AggSpec, grouped_aggregate_direct
from ..ops.filter import filter_page
from ..ops.sort import SortKey, sort_page
from ..page import Block, Page

DEC12_2 = T.DecimalType(12, 2)
DEC4_2 = T.DecimalType(4, 2)

Q1_COLUMNS = (
    "l_quantity",
    "l_extendedprice",
    "l_discount",
    "l_tax",
    "l_returnflag",
    "l_linestatus",
    "l_shipdate",
)

Q6_COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_shipdate")


def _table_page(name: str, sf: float, columns, pad_to: Optional[int] = None) -> Page:
    """Benchmark pages are GENERATED ON DEVICE via benchgen whenever it
    covers the requested columns, so set-up is a few scalars instead
    of a bulk host->device upload (see benchgen docstring). Unsupported
    columns fall back to the host tpch connector (transfer)."""
    from . import benchgen

    if benchgen.supports(name, columns):
        return benchgen.device_page(name, sf, tuple(columns), pad_to=pad_to)
    t = tpch.table(name, sf)
    data = {}
    for c in columns:
        colm = t.columns[c]
        data[c] = Block.from_numpy(colm.data, colm.type, dictionary=colm.dictionary)
    return Page.from_dict(data, pad_to=pad_to)


def lineitem_q1_page(sf: float, pad_to: Optional[int] = None) -> Page:
    return _table_page("lineitem", sf, Q1_COLUMNS, pad_to)


def lineitem_q6_page(sf: float, pad_to: Optional[int] = None) -> Page:
    return _table_page("lineitem", sf, Q6_COLUMNS, pad_to)


# ---------------------------------------------------------------------------
# Q1: pricing summary report
# ---------------------------------------------------------------------------

Q1_PREDICATE = comparison(
    "le", col("l_shipdate", T.DATE), ir.Literal("1998-09-02", T.DATE)
)

Q1_GROUPS = (col("l_returnflag", T.VARCHAR), col("l_linestatus", T.VARCHAR))
Q1_GROUP_NAMES = ("l_returnflag", "l_linestatus")
Q1_DOMAINS = (3, 2)  # returnflag in {A,N,R}, linestatus in {F,O}


def q1_aggs():
    qty = col("l_quantity", DEC12_2)
    price = col("l_extendedprice", DEC12_2)
    disc = col("l_discount", DEC4_2)
    tax = col("l_tax", DEC4_2)
    one_minus_disc = ir.binary("subtract", lit(1), disc)
    disc_price = ir.binary("multiply", price, one_minus_disc)
    one_plus_tax = ir.binary("add", lit(1), tax)
    charge = ir.binary("multiply", disc_price, one_plus_tax)

    def agg(func, inp, name):
        in_t = None if inp is None else inp.type
        return AggSpec(func, inp, name, AggSpec.infer_output_type(func, in_t))

    return (
        agg("sum", qty, "sum_qty"),
        agg("sum", price, "sum_base_price"),
        agg("sum", disc_price, "sum_disc_price"),
        agg("sum", charge, "sum_charge"),
        agg("avg", qty, "avg_qty"),
        agg("avg", price, "avg_price"),
        agg("avg", disc, "avg_disc"),
        agg("count_star", None, "count_order"),
    )


def q1_local(page: Page) -> Page:
    """Single-chip Q1: filter fused as an aggregation mask (no compaction —
    masked reductions run at memory bandwidth; compaction would cost a full
    sort+gather of the table) → direct grouped aggregation → sort.
    Jittable end-to-end (Pages are pytrees)."""
    out = grouped_aggregate_direct(
        page, Q1_GROUPS, Q1_GROUP_NAMES, q1_aggs(), Q1_DOMAINS,
        pre_mask=Q1_PREDICATE,
    )
    return sort_page(
        out,
        (
            SortKey(col("l_returnflag", T.VARCHAR)),
            SortKey(col("l_linestatus", T.VARCHAR)),
        ),
    )


def _q1_prelude(page: Page) -> Page:
    """Module-level (stable identity) so the compiled SPMD step caches."""
    return filter_page(page, Q1_PREDICATE)


def q1_distributed(mesh, page: Page, axis: str = "workers", max_groups: int = 16):
    """Multi-chip Q1: shard lineitem over the mesh (≈ split-parallel leaf
    stage), filter + partial-aggregate locally, all_to_all repartition partial
    rows by group hash (≈ FIXED_HASH exchange), final-aggregate, merge.

    For a SQL MPP engine the parallelism axes are data-parallel splits and
    hash repartition (SURVEY.md §2.6) — this exercises both collectively."""
    from ..parallel.distributed import dist_grouped_aggregate

    out = dist_grouped_aggregate(
        mesh,
        axis,
        page,
        Q1_GROUPS,
        Q1_GROUP_NAMES,
        q1_aggs(),
        max_groups=max_groups,
        part_capacity=max(2 * max_groups, 32),
        prelude=_q1_prelude,
    )
    return sort_page(
        out,
        (
            SortKey(col("l_returnflag", T.VARCHAR)),
            SortKey(col("l_linestatus", T.VARCHAR)),
        ),
    )


# ---------------------------------------------------------------------------
# Q6: forecasting revenue change
# ---------------------------------------------------------------------------

Q6_PREDICATE = ir.and_(
    comparison("ge", col("l_shipdate", T.DATE), ir.Literal("1994-01-01", T.DATE)),
    comparison("lt", col("l_shipdate", T.DATE), ir.Literal("1995-01-01", T.DATE)),
    ir.between(
        col("l_discount", DEC4_2),
        ir.Literal(0.05, DEC4_2),
        ir.Literal(0.07, DEC4_2),
    ),
    comparison("lt", col("l_quantity", DEC12_2), lit(24)),
)


def q6_local(page: Page) -> Page:
    from ..ops.aggregate import global_aggregate

    revenue = ir.binary(
        "multiply", col("l_extendedprice", DEC12_2), col("l_discount", DEC4_2)
    )
    return global_aggregate(
        page,
        (
            AggSpec(
                "sum",
                revenue,
                "revenue",
                AggSpec.infer_output_type("sum", revenue.type),
            ),
        ),
        pre_mask=Q6_PREDICATE,
    )


def q1_local_pallas(page: Page) -> Page:
    """Q1 via the hand-written single-pass Pallas kernel
    (ops/pallas_agg.py) — the custom-kernel analog of the reference's
    hand-coded benchmarks. Produces the same Page as q1_local; group ids
    are emitted in (returnflag, linestatus) order so no final sort is
    needed."""
    import jax
    import jax.numpy as jnp

    from ..expr import datetime_kernels as dtk
    from ..ops import decimal128 as d128
    from ..ops.aggregate import avg_from_sum_count
    from ..ops.filter import compact
    from ..ops.pallas_agg import BLK_ROWS, combine, q1_partial_sums

    def col32(name):
        return page.block(name).data.astype(jnp.int32)

    n = page.capacity
    padded = -(-n // BLK_ROWS) * BLK_ROWS

    def pad(x):
        return jnp.pad(x, (0, padded - n)) if padded != n else x

    cutoff = jnp.int32(dtk.parse_date_literal("1998-09-02"))
    partials = q1_partial_sums(
        pad(col32("l_quantity")),
        pad(col32("l_extendedprice")),
        pad(col32("l_discount")),
        pad(col32("l_tax")),
        pad(col32("l_returnflag")),
        pad(col32("l_linestatus")),
        pad(col32("l_shipdate")),
        page.count.astype(jnp.int32),
        cutoff,
    )
    s = combine(partials)

    rf_b = page.block("l_returnflag")
    ls_b = page.block("l_linestatus")
    cnt = s["count"]
    DEC38_2 = T.DecimalType(38, 2)
    DEC38_4 = T.DecimalType(38, 4)
    DEC38_6 = T.DecimalType(38, 6)
    blocks = [
        Block(jnp.asarray([0, 0, 1, 1, 2, 2], jnp.int32), T.VARCHAR,
              None, rf_b.dict_id),
        Block(jnp.asarray([0, 1, 0, 1, 0, 1], jnp.int32), T.VARCHAR,
              None, ls_b.dict_id),
        Block(d128.from_int64(s["sum_qty"]), DEC38_2, cnt > 0),
        Block(d128.from_int64(s["sum_price"]), DEC38_2, cnt > 0),
        Block(s["sum_disc_price"], DEC38_4, cnt > 0),
        Block(s["sum_charge"], DEC38_6, cnt > 0),
        Block(
            avg_from_sum_count(
                d128.from_int64(s["sum_qty"]), cnt, DEC12_2, DEC12_2
            ),
            DEC12_2, cnt > 0,
        ),
        Block(
            avg_from_sum_count(
                d128.from_int64(s["sum_price"]), cnt, DEC12_2, DEC12_2
            ),
            DEC12_2, cnt > 0,
        ),
        Block(
            avg_from_sum_count(
                d128.from_int64(s["sum_disc"]), cnt, DEC4_2, DEC4_2
            ),
            DEC4_2, cnt > 0,
        ),
        Block(cnt, T.BIGINT, None),
    ]
    names = (
        "l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
        "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
        "avg_disc", "count_order",
    )
    out = Page.from_blocks(blocks, names, count=6)
    return compact(out, cnt > 0)
