"""TPC-DS queries vs the SQLite oracle (same pattern as
test_tpch_queries.py; reference: presto-tpcds + the benchto TPC-DS suite,
presto-benchto-benchmarks/.../tpcds.yaml).

Sharded by FILE on purpose: tier-1 runs under `--dist loadfile`, which gives
a whole file to one worker, and as one file these cases were 1,432 s of the
run's 1,470 s clock. Do not merge the shard files back."""

from presto_tpu.benchmark.tpcds_sql import QUERIES
from presto_tpu.testing.oracle import assert_same_results

SF = 0.02
# Thirteen, so that a shard has fewer cases (8) than test_scale_sf10.py (9)
# and test_streaming.py (12): xdist hands files out by their count of cases,
# largest first, and those two long files of few cases must start before the
# shards, which then fill the end of the run in pieces of ~100 s.
N_SHARDS = 13


def shard(k):
    """The query ids of `test_tpcds_queries_<k>.py`: every N_SHARDS-th,
    which interleaves the heavy ones and places a new query by itself."""
    return sorted(QUERIES)[k::N_SHARDS]


def _expand_rollup(aggs_sql, rollup_cols, body, order_limit, grouping_alias=None):
    """SQLite has no ROLLUP: build the equivalent UNION ALL of per-level
    grouped selects (the oracle still computes every aggregate itself)."""
    n = len(rollup_cols)
    parts = []
    for k in range(n, -1, -1):
        cols = []
        for i, c in enumerate(rollup_cols):
            name = c.split(".")[-1]
            cols.append(c if i < k else f"null as {name}")
        g = ""
        if grouping_alias is not None:
            val = sum(1 << (n - 1 - i) for i in range(k, n))
            g = f", {val} as {grouping_alias}"
        gb = f" group by {', '.join(rollup_cols[:k])}" if k else ""
        parts.append(f"select {', '.join(cols)}{g}, {aggs_sql} {body}{gb}")
    return f"select * from ({' union all '.join(parts)}) {order_limit}"


_Q18_BODY = QUERIES[18].split("from", 1)[1].split("group by")[0]
_Q22_BODY = QUERIES[22].split("from", 1)[1].split("group by")[0]
_Q27_BODY = QUERIES[27].split("from", 1)[1].split("group by")[0]


def _rollup_level_union(aggs_sql, cols, body, level_alias):
    """ROLLUP expansion where `level_alias` carries the SUM of grouping
    bits (grouping(a)+grouping(b) = number of rolled-up columns), the form
    Q36/Q70/Q86 partition their windows by."""
    n = len(cols)
    parts = []
    for k in range(n, -1, -1):
        sel_cols = [
            (c if i < k else f"null as {c.split('.')[-1]}")
            for i, c in enumerate(cols)
        ]
        gb = f" group by {', '.join(cols[:k])}" if k else ""
        parts.append(
            f"select {aggs_sql}, {', '.join(sel_cols)}, "
            f"{n - k} as {level_alias} {body}{gb}"
        )
    return " union all ".join(parts)

def _rollup_channel_oracle(qid):
    """Q5/Q77/Q80 shape: WITH ctes + `select channel, id, sums group by
    rollup(channel, id)` — rebuild the final select as the UNION ALL of
    rollup levels for SQLite."""
    txt = QUERIES[qid]
    head, tail = txt.rsplit("select channel, id,", 1)
    body = tail[tail.index("from (") : tail.rindex(") x") + 3]
    return head + _expand_rollup(
        "sum(sales) as sales, sum(returns1) as returns1,"
        " sum(profit) as profit",
        ["channel", "id"],
        body,
        "order by channel nulls last, id nulls last limit 100",
    )


ORACLE_SQL = {
    # SQLite gives cast(... as decimal) INTEGER affinity, making the spec's
    # ratio an integer division — force real division in the oracle
    75: QUERIES[75].replace("as decimal(17,2))", "as real)"),
    49: QUERIES[49].replace("as decimal(15,4))", "as real)"),
    # engine casts decimal->int with HALF_UP; SQLite cast truncates
    54: QUERIES[54].replace(
        "cast((revenue / 50) as integer)",
        "cast(round(revenue / 50.0) as integer)",
    ),
    # SQLite refuses the spec's ambiguous output-alias ORDER BY
    58: QUERIES[58].replace(
        "order by item_id, ss_item_rev",
        "order by ss_items.item_id, ss_item_rev",
    ),
    5: _rollup_channel_oracle(5),
    77: _rollup_channel_oracle(77),
    80: _rollup_channel_oracle(80),
    # SQLite rejects parenthesized members of a compound SELECT
    8: QUERIES[8]
    .replace("from ((select substr", "from (select substr")
    .replace(
        "'00559'))\n            intersect\n            (select ca_zip",
        "'00559')\n            intersect\n            select ca_zip",
    )
    .replace("> 10) a1)) a2) v1", "> 10) a1) a2) v1"),
    # SQLite can't add an interval to a date COLUMN (the transpiler only
    # folds literal date arithmetic); d_date is stored as ISO text
    72: QUERIES[72].replace(
        "d3.d_date > d1.d_date + interval '5' day",
        "d3.d_date > date(d1.d_date, '+5 day')",
    ),
    18: _expand_rollup(
        "avg(cast(cs_quantity as double)) agg1,"
        " avg(cast(cs_list_price as double)) agg2,"
        " avg(cast(cs_coupon_amt as double)) agg3,"
        " avg(cast(cs_sales_price as double)) agg4,"
        " avg(cast(cs_net_profit as double)) agg5,"
        " avg(cast(c_birth_year as double)) agg6,"
        " avg(cast(cd1.cd_dep_count as double)) agg7",
        ["i_item_id", "ca_country", "ca_state", "ca_county"],
        "from" + _Q18_BODY,
        # NULLS LAST: match the engine's (and the reference's) ASC default;
        # sqlite defaults to nulls-first, which changes WHICH rows LIMIT keeps
        "order by ca_country nulls last, ca_state nulls last,"
        " ca_county nulls last, i_item_id nulls last limit 100",
    ),
    22: _expand_rollup(
        "avg(inv_quantity_on_hand) qoh",
        ["i_product_name", "i_brand", "i_class", "i_category"],
        "from" + _Q22_BODY,
        "order by qoh nulls last, i_product_name nulls last,"
        " i_brand nulls last, i_class nulls last, i_category nulls last"
        " limit 100",
    ),
    27: _expand_rollup(
        "avg(ss_quantity) agg1, avg(ss_list_price) agg2,"
        " avg(ss_coupon_amt) agg3, avg(ss_sales_price) agg4",
        ["i_item_id", "s_state"],
        "from" + _Q27_BODY,
        "order by i_item_id nulls last, s_state nulls last limit 100",
        grouping_alias="g_state",
    ),
}

_Q36_BODY = (
    "from store_sales, date_dim d1, item, store "
    "where d1.d_year = 2001 and d1.d_date_sk = ss_sold_date_sk "
    "and i_item_sk = ss_item_sk and s_store_sk = ss_store_sk "
    "and s_state = 'TN'"
)
_Q70_BODY = (
    "from store_sales, date_dim d1, store "
    "where d1.d_month_seq between 1200 and 1211 "
    "and d1.d_date_sk = ss_sold_date_sk and s_store_sk = ss_store_sk "
    "and s_state in (select s_state from "
    " (select s_state as s_state, rank() over (partition by s_state "
    "  order by sum(ss_net_profit) desc) as ranking "
    "  from store_sales, store, date_dim "
    "  where d_month_seq between 1200 and 1211 "
    "    and d_date_sk = ss_sold_date_sk and s_store_sk = ss_store_sk "
    "  group by s_state) tmp1 where ranking <= 5)"
)
_Q86_BODY = (
    "from web_sales, date_dim d1, item "
    "where d1.d_month_seq between 1200 and 1211 "
    "and d1.d_date_sk = ws_sold_date_sk and i_item_sk = ws_item_sk"
)

ORACLE_SQL[36] = f"""
select gross_margin, i_category, i_class, lochierarchy,
       rank() over (partition by lochierarchy,
                    case when lochierarchy = 0 then i_category end
                    order by gross_margin asc) rank_within_parent
from ({_rollup_level_union(
        "cast(sum(ss_net_profit) as real) / cast(sum(ss_ext_sales_price) as real)"
        " as gross_margin",
        ["i_category", "i_class"], _Q36_BODY, "lochierarchy")}) t
order by lochierarchy desc,
         case when lochierarchy = 0 then i_category end nulls last,
         rank_within_parent
limit 100
"""
ORACLE_SQL[70] = f"""
select total_sum, s_state, s_county, lochierarchy,
       rank() over (partition by lochierarchy,
                    case when lochierarchy = 0 then s_state end
                    order by total_sum desc) rank_within_parent
from ({_rollup_level_union(
        "sum(ss_net_profit) as total_sum",
        ["s_state", "s_county"], _Q70_BODY, "lochierarchy")}) t
order by lochierarchy desc,
         case when lochierarchy = 0 then s_state end nulls last,
         rank_within_parent
limit 100
"""
ORACLE_SQL[86] = f"""
select total_sum, i_category, i_class, lochierarchy,
       rank() over (partition by lochierarchy,
                    case when lochierarchy = 0 then i_category end
                    order by total_sum desc) rank_within_parent
from ({_rollup_level_union(
        "sum(ws_net_paid) as total_sum",
        ["i_category", "i_class"], _Q86_BODY, "lochierarchy")}) t
order by lochierarchy desc,
         case when lochierarchy = 0 then i_category end nulls last,
         rank_within_parent
limit 100
"""


_q14_head, _q14_tail = QUERIES[14].rsplit(
    "select channel, i_brand_id, i_class_id, i_category_id,", 1
)
_q14_body = _q14_tail[_q14_tail.index("from (") : _q14_tail.rindex(") y") + 3]
ORACLE_SQL[14] = _q14_head + _expand_rollup(
    "sum(sales) as sum_sales, sum(number_sales) as number_sales",
    ["channel", "i_brand_id", "i_class_id", "i_category_id"],
    _q14_body,
    "order by channel nulls last, i_brand_id nulls last,"
    " i_class_id nulls last, i_category_id nulls last limit 100",
)

_Q67_COLS = [
    "i_category", "i_class", "i_brand", "i_product_name", "d_year",
    "d_qoy", "d_moy", "s_store_id",
]
_Q67_BODY = (
    "from store_sales, date_dim, store, item "
    "where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk "
    "and ss_store_sk = s_store_sk and d_month_seq between 1200 and 1211"
)
_q67_parts = []
for _k in range(len(_Q67_COLS), -1, -1):
    _sel = [
        (c if i < _k else f"null as {c}") for i, c in enumerate(_Q67_COLS)
    ]
    _gb = f" group by {', '.join(_Q67_COLS[:_k])}" if _k else ""
    _q67_parts.append(
        f"select {', '.join(_sel)}, "
        f"sum(coalesce(ss_sales_price * ss_quantity, 0)) sumsales "
        f"{_Q67_BODY}{_gb}"
    )
ORACLE_SQL[67] = f"""
select * from
 (select i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
         d_moy, s_store_id, sumsales,
         rank() over (partition by i_category order by sumsales desc) rk
  from ({' union all '.join(_q67_parts)}) dw1) dw2
where rk <= 100
order by i_category nulls last, i_class nulls last, i_brand nulls last,
         i_product_name nulls last, d_year nulls last, d_qoy nulls last,
         d_moy nulls last, s_store_id nulls last, sumsales, rk
limit 100
"""


def check(session, oracle, qid):
    sql = QUERIES[qid]
    ours = session.query(sql)
    expected = oracle.query(ORACLE_SQL.get(qid, sql))
    types = [b.type for b in ours.page.blocks]
    assert_same_results(ours.rows(), expected, types)
