"""The configuration `tpch-sf10-full` and its cell `sf10f.q18`
(benchmarks/): TPC-H Q18 as clause 2.4.18 writes it, served by
`CoordinatorServer` over the host-fed `TpchCatalog` as `presto-tpu
--serve` starts it, against `benchmarks/reference/q18_full.py`, at
SF0.01 on the CPU.

(a) the reference's derived `o_totalprice` and `c_name` equal
    `connectors.tpch.table`'s, row by row;
(b) Q18 served over HTTP equals the reference under the comparison that
    decides `correct`. qgen's QUANTITY 312..315 returns nothing at
    15,000 orders (the largest order here sums to 302), so the cases that
    return rows are 250 / 270 / 290 (57 / 14 / 1 orders) and 315 is the
    case of the empty answer on both sides; one altered digit fails;
(c) the spans of the statement: `groups` / `max_groups` on the hash-sort
    `Aggregate`, the counts on the `SemiJoin`, the runtime filters on
    both scans, and the group-by that outgrows its first guess learning
    its capacity;
and what the cell's files promise each other.
"""

import importlib.util
import json
import os
import sys
from decimal import Decimal

import numpy as np
import pytest

from presto_tpu.connectors import tpch
from presto_tpu.exec import executor as executor_module
from presto_tpu.obs.span import TRACES
from presto_tpu.server import Client, CoordinatorServer
from presto_tpu.session import Session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (os.path.join(BENCH, "reference"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare  # noqa: E402
import datagen_full  # noqa: E402
import q18_full  # noqa: E402

SF = 0.01
WITH_ROWS = (250, 270, 290)
EMPTY = 315
THRESHOLDS = WITH_ROWS + (EMPTY,)


def bench_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def sql_text(quantity: int) -> str:
    with open(os.path.join(BENCH, "sql", "q18_full.sql")) as f:
        return f.read().format(quantity=quantity)


def reference(quantity: int):
    return q18_full.answer({}, {"quantity": quantity, "sf": SF})


def orders_over(quantity: int):
    """(orders whose lines sum over `quantity`, those orders' lines) on
    the benchmark's copy."""
    li = datagen_full.tables(SF)["lineitem"]
    _keys, starts = q18_full.order_runs(li["l_orderkey"])
    qty = np.add.reduceat(li["l_quantity"], starts)
    lines = np.diff(np.r_[starts, len(li["l_orderkey"])])
    big = qty > quantity * 100
    return int(big.sum()), int(lines[big].sum())


# -- (a) what the reference derives --

def test_derived_totalprice_equals_the_connectors_row_by_row():
    li = datagen_full.tables(SF)["lineitem"]
    keys, starts = q18_full.order_runs(li["l_orderkey"])
    total = np.add.reduceat(
        q18_full.line_gross(
            li["l_extendedprice"], li["l_discount"], li["l_tax"]
        ),
        starts,
    )
    orders = tpch.table("orders", SF).columns
    assert np.array_equal(keys, orders["o_orderkey"].data)
    assert np.array_equal(total, orders["o_totalprice"].data)
    # the float control's arithmetic is the same rule while it is exact
    assert np.array_equal(
        q18_full.line_gross(
            li["l_extendedprice"], li["l_discount"], li["l_tax"], np.float64
        ),
        q18_full.line_gross(
            li["l_extendedprice"], li["l_discount"], li["l_tax"]
        ),
    )


def test_derived_name_equals_the_connectors_row_by_row():
    c_name = tpch.table("customer", SF).columns["c_name"]
    keys = datagen_full.tables(SF)["customer"]["c_custkey"]
    assert len(keys) == len(c_name.data)
    assert all(
        q18_full.customer_name(k) == c_name.dictionary[int(code)]
        for k, code in zip(keys, c_name.data)
    )


def test_a_tie_at_the_limit_is_refused(monkeypatch):
    monkeypatch.setattr(q18_full, "LIMIT", 1)
    t = datagen_full.tables(SF)
    li, o = t["lineitem"], t["orders"]
    tied = {
        "customer": t["customer"],
        # two orders, the same lines, the same date
        "orders": {k: v[:2].copy() for k, v in o.items()},
        "lineitem": {
            k: np.concatenate([v[:1], v[:1]]) for k, v in li.items()
        },
    }
    tied["orders"]["o_orderdate"][:] = o["o_orderdate"][0]
    tied["lineitem"]["l_orderkey"] = o["o_orderkey"][:2].copy()
    monkeypatch.setattr(datagen_full, "tables", lambda sf: tied)
    with pytest.raises(ValueError, match="tie on the ORDER BY"):
        q18_full.answer({}, {"quantity": 0, "sf": SF})


# -- (b) served answers against the reference --

@pytest.fixture(scope="module")
def served():
    server = CoordinatorServer(
        Session(tpch.TpchCatalog(sf=SF), result_cache=False), port=0
    ).start()
    try:
        yield Client(server.uri, timeout=600.0)
    finally:
        server.stop()


@pytest.fixture(scope="module")
def answers(served):
    """{quantity: (served rows, the statement's spans)}, each served once."""
    out = {}

    def get(quantity):
        if quantity not in out:
            TRACES.reset()
            cols, rows = served.execute(sql_text(quantity))
            (trace,) = TRACES.recent()
            out[quantity] = (compare.canonical(cols, rows), trace.spans())
        return out[quantity]

    return get


@pytest.mark.parametrize("quantity", THRESHOLDS)
def test_served_q18_equals_the_reference(answers, quantity):
    got, want = answers(quantity)[0], reference(quantity)
    assert bool(want) == (quantity in WITH_ROWS)
    assert len(want) == min(orders_over(quantity)[0], q18_full.LIMIT)
    correct, checks = compare.verdict(
        [(quantity, got)], {quantity: (want, q18_full.ORDER_BY)}, 0
    )
    assert correct, checks
    assert all(
        c["value"] == 0 for n, c in checks.items() if n != "answers_compared"
    )


@pytest.mark.parametrize("quantity", WITH_ROWS)
def test_one_altered_digit_is_not_correct(answers, quantity):
    got, want = answers(quantity)[0], reference(quantity)
    row = list(want[-1])
    i = next(i for i, v in enumerate(row) if isinstance(v, Decimal))
    row[i] += Decimal(1).scaleb(row[i].as_tuple().exponent)
    correct, checks = compare.verdict(
        [(quantity, got)],
        {quantity: (want[:-1] + [tuple(row)], q18_full.ORDER_BY)}, 0,
    )
    assert not correct
    assert checks["mismatched_cells"]["value"] == 1


# -- (c) the statement's spans --

def spans_of(session, sql):
    TRACES.reset()
    rows = session.query(sql).rows()
    (trace,) = TRACES.recent()
    return rows, {
        (s.name, s.attrs.get("pos")): s.attrs for s in trace.spans()
    }


@pytest.fixture()
def no_hash_slot(monkeypatch):
    """The chip's strategy for a group-by of 15,000 groups is `hash-sort`;
    on the CPU the hash-slot group-by's host twin takes up to 64k groups
    first, so the test switches that attempt off."""
    monkeypatch.setenv("PRESTO_TPU_PALLAS_GROUPBY_HASH", "off")


@pytest.fixture()
def counts_held(monkeypatch):
    """An accelerator keeps the host's copy of a count on the array once
    a `_shrink` has read it; the CPU backend keeps none, so `held` finds
    nothing there. The test stands in for the copy by reading."""
    monkeypatch.setattr(executor_module, "held", lambda x: np.asarray(x))


@pytest.mark.parametrize("quantity", WITH_ROWS)
def test_spans_carry_what_the_reference_counts(
    no_hash_slot, counts_held, quantity
):
    session = Session(tpch.TpchCatalog(sf=SF), result_cache=False)
    rows, spans = spans_of(session, sql_text(quantity))
    orders, lines = orders_over(quantity)
    assert len(rows) == orders
    n_orders = len(datagen_full.tables(SF)["orders"]["o_orderkey"])
    n_lines = len(datagen_full.tables(SF)["lineitem"]["l_orderkey"])
    aggregates = [
        a for (name, _pos), a in spans.items()
        if name == "Aggregate" and a.get("strategy") == "hash-sort"
    ]
    # one group per order under the HAVING, one per returned row on top
    by_groups = {a["groups"]: a for a in aggregates}
    assert set(by_groups) == {n_orders, orders}
    sub = by_groups[n_orders]
    assert sub["max_groups"] >= n_orders
    assert sub["max_groups"] & (sub["max_groups"] - 1) == 0
    assert "retries" not in sub  # 15,000 groups are under the first guess
    (semi,) = [a for (name, _pos), a in spans.items() if name == "SemiJoin"]
    assert (semi["probe_rows"], semi["build_rows"], semi["out_rows"]) == (
        lines, orders, lines
    )
    # the semi-join's keys prune the orders scan, and what is left of
    # orders prunes lineitem: the join's estimate follows its build side
    scans = {
        a["dyn_strategy"].split(":")[0]: a["dyn_pruned"]
        for (name, _pos), a in spans.items()
        if name == "TableScan" and "dyn_pruned" in a
    }
    assert scans == {"df2": n_orders - orders, "df1": n_lines - lines}
    (expand,) = [
        a for (name, _pos), a in spans.items()
        if name == "Join" and "out_capacity" in a
    ]
    assert expand["est_rows"] < n_lines and expand["out_capacity"] < n_lines


def test_a_group_by_learns_the_capacity_it_outgrew(no_hash_slot):
    """A hash-sort group-by whose groups pass the 65,536 of its first
    guess runs twice the first time and once from then on."""
    session = Session(tpch.TpchCatalog(sf=0.02), result_cache=False)
    sql = (
        "select l_orderkey, l_linenumber, sum(l_quantity) from lineitem "
        "group by l_orderkey, l_linenumber"
    )
    n_lines = tpch.table("lineitem", 0.02).num_rows
    assert n_lines > 1 << 16
    seen = []
    for _ in range(2):
        rows, spans = spans_of(session, sql)
        assert len(rows) == n_lines
        (agg,) = [a for (name, _p), a in spans.items() if name == "Aggregate"]
        assert agg["strategy"] == "hash-sort"
        assert (agg["groups"], agg["max_groups"]) == (n_lines, 1 << 17)
        seen.append(agg.get("retries", 0))
    assert seen == [1, 0]


@pytest.fixture()
def large_pages(monkeypatch):
    """SF10's pages are over the row counts from which the group-by takes
    its run-sum form and a dynamic filter its compare-all mask
    (`RUNS_MIN_ROWS`, `LARGE_PAGE_ROWS`); SF0.01's are not, so the test
    lowers both."""
    from presto_tpu.ops import aggregate, filter as filter_ops

    from presto_tpu.exec import dynfilter

    monkeypatch.setattr(aggregate, "RUNS_MIN_ROWS", 1 << 10)
    monkeypatch.setattr(filter_ops, "LARGE_PAGE_ROWS", 1 << 10)
    taken = []
    for module, name in (
        (aggregate, "_grouped_aggregate_runs"), (dynfilter, "_inlist_mask")
    ):
        real = getattr(module, name)

        def spy(*args, _real=real, _name=name, **kwargs):
            taken.append(_name)
            return _real(*args, **kwargs)

        monkeypatch.setattr(module, name, spy)
    return taken


@pytest.mark.parametrize("quantity", THRESHOLDS)
def test_large_page_forms_give_the_reference(
    served, no_hash_slot, large_pages, quantity
):
    """Q18 through the forms SF10 takes: equal to the reference, the
    subquery's group-by starts from the planner's estimate (no retry), and
    its rows, which the connector stores by `l_orderkey`, skip the run-sum
    form's first sort."""
    TRACES.reset()
    cols, rows = served.execute(sql_text(quantity) + " -- large pages")
    (trace,) = TRACES.recent()
    correct, checks = compare.verdict(
        [(quantity, compare.canonical(cols, rows))],
        {quantity: (reference(quantity), q18_full.ORDER_BY)}, 0,
    )
    assert correct, checks
    n_orders = len(datagen_full.tables(SF)["orders"]["o_orderkey"])
    (sub,) = [
        s.attrs for s in trace.spans()
        if s.name == "Aggregate" and s.attrs.get("groups") == n_orders
    ]
    assert sub["strategy"] == "hash-sort" and "retries" not in sub
    assert sub["presorted"] is True
    # the group-by is traced once a process, the masks run every time
    # there is something to look for
    if quantity == THRESHOLDS[0]:
        assert "_grouped_aggregate_runs" in large_pages
    if quantity in WITH_ROWS:
        assert large_pages.count("_inlist_mask") == 2


def test_a_selective_filter_of_a_large_page_gathers_what_it_keeps(
    no_hash_slot, large_pages, monkeypatch
):
    """Q18's HAVING alone, at SF0.02 (30,000 slots: a page `_shrink`
    would read the count of): the count is read before the compaction
    and `compact_few` gathers the few rows kept; the rows are those of
    the full-capacity compaction."""
    from presto_tpu.ops import filter as filter_ops

    sql = (
        "select l_orderkey, sum(l_quantity) from lineitem "
        "group by l_orderkey having sum(l_quantity) > 290 order by 1"
    )
    few = []
    real = filter_ops.compact_few

    def spy(page, keep, cap):
        few.append((page.capacity, cap))
        return real(page, keep, cap=cap)

    monkeypatch.setattr(filter_ops, "compact_few", spy)
    got = Session(tpch.TpchCatalog(sf=0.02), result_cache=False).query(sql).rows()
    assert few and all(cap * 16 <= capacity for capacity, cap in few)
    monkeypatch.setattr(filter_ops, "LARGE_PAGE_ROWS", 1 << 40)
    want = Session(tpch.TpchCatalog(sf=0.02), result_cache=False).query(
        sql + " -- full-capacity compaction"
    ).rows()
    assert got == want and 0 < len(got) < 100


@pytest.mark.parametrize("page_is_large", [False, True])
def test_a_learned_group_count_skips_the_hash_slot_attempt(
    monkeypatch, request, page_is_large
):
    """A node that needed more slots than the hash-slot group-by has does
    not read its keys and inputs to the host again to find that out:
    whether a retry taught it the count (the capped first guess) or the
    first guess held (a large page starts from the estimate)."""
    if page_is_large:
        request.getfixturevalue("large_pages")
    session = Session(tpch.TpchCatalog(sf=0.02), result_cache=False)
    sql = (
        "select l_orderkey, l_linenumber, sum(l_quantity) from lineitem "
        "group by l_orderkey, l_linenumber"
    )
    calls = []
    real = executor_module.Executor._try_hash_groupby

    def counted(self, node, page):
        calls.append(node)
        return real(self, node, page)

    monkeypatch.setattr(executor_module.Executor, "_try_hash_groupby", counted)
    tag = f" -- {page_is_large}"  # a plan, and so a node, of its own
    session.query(sql + tag).rows()
    session.query(sql + tag).rows()
    assert len(calls) == 1


# -- what the new files promise each other --

def test_the_cells_files_agree():
    bench = bench_json("..", "BENCHMARK.json")
    names = {kind: [e["name"] for e in bench[kind]] for kind in (
        "configs", "workloads", "per_layer")}
    for cell_name in ("sf10.scan_agg", "sf10f.q18"):
        cell = bench_json("workloads", cell_name + ".json")
        config = bench_json("configs", cell["config"] + ".json")
        mix = bench_json("traffic", cell["traffic"] + ".json")
        entry = next(w for w in bench["workloads"] if w["name"] == cell_name)
        assert (entry["config"], entry["traffic"], entry["chips"]) == (
            cell["config"], cell["traffic"], config["chips"]
        )
        assert entry["why"] == cell["why"] and len(cell["why"]) <= 200
        assert cell["config"] in names["configs"]
        for st in mix["statements"]:
            for kind, ext in (("sql", ".sql"), ("reference", ".py")):
                assert os.path.exists(os.path.join(BENCH, kind, st["id"] + ext))
    config = bench_json("configs", "tpch-sf10-full.json")
    listed = next(c for c in bench["configs"] if c["name"] == "tpch-sf10-full")
    assert os.path.join(ROOT, listed["file"]) == os.path.join(
        BENCH, "configs", "tpch-sf10-full.json"
    )
    sf1 = bench_json("configs", "tpch-sf1-full.json")
    # the connector's departures from dbgen, exactly as tpch-sf1-full's;
    # no width among them, and the scale factor is not cut
    assert listed["reduced"] == config["reduced"] == sf1["reduced"]
    assert set(config["reduced"]) <= set(config["reduced_from"])
    assert config["sf"] == 10.0
    for same in ("catalog", "session", "guarantees", "chips", "serve",
                 "lineitem_columns", "orders_columns", "customer_columns",
                 "lines_per_order", "tables"):
        assert config[same] == sf1[same], same
    (st,) = bench_json("traffic", "q18_full.json")["statements"]
    # qgen's whole range, and the scale factor the reference is told
    assert [s["quantity"] for s in st["sets"]] == [312, 313, 314, 315]
    assert all(s["sf"] == config["sf"] for s in st["sets"])
    assert bench_json("traffic", "q18_full.json")["clients"] == 1
    # the four readers exist and are asked of this cell only
    for metric in ("aggregate_ms", "agg_retries_per_stmt", "semijoin_ms",
                   "hbm_roofline_share_full"):
        entry = next(m for m in bench["per_layer"] if m["name"] == metric)
        assert entry["workloads"] == ["sf10f.q18"]
        assert entry["moves"] == "stmt_ms"
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", metric + ".py")
        )


def test_bytes_model_full_counts_each_table_once():
    import bytes_model_full

    assert bytes_model_full.named_columns(sql_text(312)) == {
        "lineitem": ["l_orderkey", "l_quantity"],
        "orders": ["o_orderkey", "o_custkey", "o_totalprice", "o_orderdate"],
        "customer": ["c_custkey", "c_name"],
    }
    # 16 B of a lineitem row, 28 B of an order, 12 B of a customer
    assert bytes_model_full.statement_bytes("q18_full", 10.0) == (
        60_000_000 * 16 + 15_000_000 * 28 + 1_500_000 * 12
    )
    widths = {
        t: sum(spec["columns"].values())
        for t, spec in bytes_model_full.SCHEMA.items()
    }
    assert widths == {"lineitem": 96, "orders": 52, "customer": 44}
    for t, spec in bytes_model_full.SCHEMA.items():
        host = tpch.table(t, SF)
        assert list(spec["columns"]) == list(host.columns)
        assert widths[t] * host.num_rows == host.nbytes


def test_the_span_readers_read_the_statement(served, monkeypatch):
    """The three readers over the program's store: a served Q18 has
    `Aggregate` and `SemiJoin` spans; a statement without them gives
    None, never 0."""
    import time
    from types import SimpleNamespace

    readers = {}
    for name in ("aggregate_ms", "semijoin_ms", "agg_retries_per_stmt"):
        path = os.path.join(BENCH, "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("lm_" + name, path)
        readers[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(readers[name])
    TRACES.reset()
    run = SimpleNamespace(records=[{"epoch_ns": time.time_ns()}])
    served.execute(sql_text(270))
    assert readers["aggregate_ms"].compute(run) > 0
    assert readers["semijoin_ms"].compute(run) > 0
    assert readers["agg_retries_per_stmt"].compute(run) == 0
    TRACES.reset()
    run = SimpleNamespace(records=[{"epoch_ns": time.time_ns()}])
    served.execute("select count(*) from nation")
    assert readers["semijoin_ms"].compute(run) is None
    assert readers["agg_retries_per_stmt"].compute(run) is not None  # a global Aggregate
    TRACES.reset()
    served.execute("select n_name from nation")
    run.records = [{"epoch_ns": run.records[0]["epoch_ns"]}]
    assert readers["aggregate_ms"].compute(run) is None
    assert readers["agg_retries_per_stmt"].compute(run) is None
