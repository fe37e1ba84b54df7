"""The configuration `tpch-sf1-full` (benchmarks/configs/): the host-fed
`TpchCatalog` served by `CoordinatorServer` as `presto-tpu --serve`
starts it, against the benchmark's own copy of its population, at
SF0.01 on the CPU.

(a) `benchmarks/datagen_full.py` equals `connectors.tpch.table` column
    by column for every column the references read;
(b) Q3 (both parameter sets of the cell), Q1 and Q6 served over HTTP
    equal the plain references on that copy under the comparison that
    decides `correct`, and one altered digit fails it;
(c) the statement that loads a table carries `table_load` spans, the
    next one none, and the `table_load_s` metric reads them;
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
from presto_tpu.obs import span as obs_span
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

SF = 0.01
STATEMENTS = {
    "q3_full-BUILDING": (
        "q3_full", {"segment": "BUILDING", "date": "1995-03-15", "sf": SF}),
    "q3_full-MACHINERY": (
        "q3_full", {"segment": "MACHINERY", "date": "1995-03-20", "sf": SF}),
    "q1": ("q1", {"delta": 90}),
    "q6": ("q6", {"year": 1994, "discount": 6, "quantity": 24}),
}


def load(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def start(catalog):
    return CoordinatorServer(
        Session(catalog, result_cache=False), port=0
    ).start()


# -- (a) the copy of the population --

@pytest.mark.parametrize(
    "table,column",
    [(t, c) for t, cols in datagen_full.COLUMNS.items() for c in cols],
)
def test_copy_equals_the_connectors_column(table, column):
    mine = datagen_full.tables(SF)[table][column]
    theirs = tpch.table(table, SF).columns[column].data
    assert mine.dtype == theirs.dtype
    assert mine.shape == theirs.shape
    assert np.array_equal(mine, theirs)


def test_copy_covers_what_the_references_read_and_has_the_shape():
    for qid in ("q1", "q3", "q6"):
        for table, cols in load("reference", qid).TABLES.items():
            assert set(cols) <= set(datagen_full.COLUMNS[table]), qid
    t = datagen_full.tables(SF)
    lines = np.bincount(np.bincount(t["lineitem"]["l_orderkey"])[1:])
    assert lines[0] == 0 and len(lines) == 8 and lines[1:].min() > 0
    ordering = np.unique(t["orders"]["o_custkey"])
    assert not (ordering % 3 == 0).any()
    assert len(ordering) < len(t["customer"]["c_custkey"]) * 0.7
    # the pools the references decode with are the connector's, in order
    import datagen

    assert tuple(tpch.SEGMENTS) == datagen.SEGMENTS
    lineitem = tpch.table("lineitem", SF).columns
    assert lineitem["l_returnflag"].dictionary == datagen.RETURNFLAGS
    assert lineitem["l_linestatus"].dictionary == datagen.LINESTATUSES


# -- (b) served answers against the references on the copy --

@pytest.fixture(scope="module")
def served():
    server = start(tpch.TpchCatalog(sf=SF))
    try:
        yield Client(server.uri, timeout=600.0)
    finally:
        server.stop()


@pytest.fixture(scope="module")
def answers(served):
    """{case: (served rows, reference rows, ORDER BY)}, each statement
    served once."""
    out = {}

    def get(case):
        if case not in out:
            qid, params = STATEMENTS[case]
            with open(os.path.join(BENCH, "sql", qid + ".sql")) as f:
                cols, rows = served.execute(f.read().format(**params))
            ref = load("reference", qid)
            tables = {} if not ref.TABLES else datagen_full.tables(SF)
            out[case] = (
                compare.canonical(cols, rows), ref.answer(tables, params),
                ref.ORDER_BY,
            )
        return out[case]

    return get


@pytest.mark.parametrize("case", list(STATEMENTS))
def test_served_answer_equals_the_reference_on_the_copy(answers, case):
    got, want, order_by = answers(case)
    assert want and got, "an empty answer proves nothing"
    correct, checks = compare.verdict(
        [(case, got)], {case: (want, order_by)}, 0
    )
    assert correct, checks
    assert all(
        c["value"] == 0 for n, c in checks.items() if n != "answers_compared"
    )


@pytest.mark.parametrize("case", list(STATEMENTS))
def test_one_altered_digit_is_not_correct(answers, case):
    got, want, order_by = answers(case)
    row = list(want[-1])
    i = next(i for i, v in enumerate(row) if isinstance(v, Decimal))
    row[i] += Decimal(1).scaleb(row[i].as_tuple().exponent)
    altered = want[:-1] + [tuple(row)]
    correct, checks = compare.verdict(
        [(case, got)], {case: (altered, order_by)}, 0
    )
    assert not correct
    assert checks["mismatched_cells"]["value"] == 1


def filter_spans(trace):
    """{"df0" | "df1": attrs} of the `Filter` spans that applied Q3's
    runtime filters: `df0` prunes orders, `df1` lineitem."""
    return {
        s.attrs["dyn_strategy"].split(":")[0]: s.attrs
        for s in trace.spans()
        if s.name == "Filter" and "dyn_strategy" in s.attrs
    }


@pytest.mark.parametrize("case", ["q3_full-BUILDING", "q3_full-MACHINERY"])
def test_q3s_dynamic_filters_say_which_compaction_ran(served, case, request):
    """Q3's lineitem `Filter` keeps under a hundredth of its page behind
    the join's runtime filter and gathers the survivors' capacity
    (`compact: few`); orders' keeps a tenth and runs `compact` (`sort`).
    Served once as tier-1 compacts (on the host), once with the
    executor's device branch forced: the same rows and `dyn_pruned`."""
    qid, params = STATEMENTS[case]
    with open(os.path.join(BENCH, "sql", qid + ".sql")) as f:
        sql = f.read().format(**params)
    runs = []
    for fixture in (None, "device_branch"):
        if fixture:
            request.getfixturevalue(fixture)
        TRACES.reset()
        cols, rows = served.execute(sql)
        (trace,) = TRACES.recent()
        runs.append((compare.canonical(cols, rows), filter_spans(trace)))
    (want, on_host), (got, on_device) = runs
    assert got == want and len(got) == 10
    assert {f["compact"] for f in on_host.values()} == {"host"}
    orders, lineitem = on_device["df0"], on_device["df1"]
    n_lineitem = tpch.table("lineitem", SF).num_rows
    assert lineitem["compact"] == "few"
    assert lineitem["compact_capacity"] * 16 <= n_lineitem
    assert 0 < lineitem["dyn_pruned"] == on_host["df1"]["dyn_pruned"]
    assert orders["compact"] == "sort"
    assert orders["compact_capacity"] * 16 > tpch.table("orders", SF).num_rows
    assert 0 < orders["dyn_pruned"] == on_host["df0"]["dyn_pruned"]
    for f in ("df0", "df1"):
        assert (
            on_device[f]["compact_capacity"] == on_host[f]["compact_capacity"]
        )


# -- (c) the load's spans and the metric that reads them --

def loads_of(trace):
    return [s for s in trace.spans() if s.name == "table_load"]


@pytest.mark.parametrize("table", ["customer", "orders", "lineitem"])
def test_table_load_spans_on_the_first_statement_only(table):
    metric = load("layer_metrics", "table_load_s")
    server = start(tpch.TpchCatalog(sf=SF))  # a fresh catalog: nothing resident
    try:
        client = Client(server.uri, timeout=600.0)
        sql = f"select count(*) from {table}"
        TRACES.reset()
        _cols, first_rows = client.execute(sql)
        (first,) = TRACES.recent()
        host = tpch.table(table, SF)
        assert first_rows == [[host.num_rows]]
        loads = loads_of(first)
        uploads = [s for s in loads if "bytes" in s.attrs]
        assert len(uploads) == 1
        up = uploads[0]
        by_id = {s.span_id: s for s in first.spans()}
        assert by_id[up.parent_id].name == "TableScan"
        assert up.attrs["table"] == table
        assert up.attrs["rows"] == host.num_rows
        assert up.attrs["columns"] == len(host.columns)
        assert up.attrs["bytes"] == sum(
            c.data.nbytes for c in host.columns.values()
        )
        assert up.attrs["upload_s"] > 0 and "generate_s" not in up.attrs
        # generation is a span of its own under whatever asked first
        made = [s for s in loads if s is not up]
        assert made and all("generate_s" in s.attrs for s in made)
        # folded upward, like host_reads
        root = first.root()
        assert root.attrs["upload_s"] == pytest.approx(up.attrs["upload_s"])
        assert metric.compute(None) == pytest.approx(
            sum(s.wall_s for s in loads)
        )
        TRACES.reset()
        assert client.execute(sql)[1] == first_rows
        (second,) = TRACES.recent()
        assert loads_of(second) == []
        assert "upload_s" not in second.root().attrs
        assert metric.compute(None) is None
    finally:
        server.stop()


def test_child_without_an_open_span_just_runs_the_body():
    assert obs_span.current() is None
    ran = []
    with obs_span.child("table_load", wall_as="upload_s", table="t"):
        ran.append(obs_span.current())
    assert ran == [None]


def test_held_never_reads_the_device():
    import jax.numpy as jnp

    assert obs_span.held(7) == 7
    assert obs_span.held(np.int32(7)) == 7
    x = jnp.asarray(7, jnp.int32) + 1
    held = obs_span.held(x)
    assert held is None or int(held) == 8  # None wherever no copy is kept


# -- what the cell's files promise each other --

def test_the_cells_files_agree():
    bench = bench_json("..", "BENCHMARK.json")
    cell = bench_json("workloads", "sf1f.join.json")
    config = bench_json("configs", cell["config"] + ".json")
    mix = bench_json("traffic", cell["traffic"] + ".json")
    entry = next(w for w in bench["workloads"] if w["name"] == "sf1f.join")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        cell["config"], cell["traffic"], config["chips"]
    )
    assert entry["why"] == cell["why"]
    listed = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert listed["reduced"] == config["reduced"]
    assert set(config["reduced"]) <= set(config["reduced_from"])
    assert set(config["reduced"]) <= set(config)
    assert config["catalog"] == {
        "module": "presto_tpu.connectors.tpch", "class": "TpchCatalog"
    }
    sf1 = bench_json("configs", "tpch-sf1.json")
    assert config["guarantees"] == sf1["guarantees"]
    # the same statement and pool as sf1.join's; `sf` states the scale
    (st,) = mix["statements"]
    (old,) = bench_json("traffic", "join.json")["statements"]
    assert [
        {k: v for k, v in s.items() if k != "sf"} for s in st["sets"]
    ] == old["sets"]
    assert all(s["sf"] == config["sf"] for s in st["sets"])
    for name in ("loop", "clients", "warmup_passes", "trace"):
        assert mix[name] == bench_json("traffic", "join.json")[name]
    with open(os.path.join(BENCH, "sql", "q3.sql")) as a, \
            open(os.path.join(BENCH, "sql", st["id"] + ".sql")) as b:
        assert a.read() == b.read()
    metric = next(m for m in bench["per_layer"] if m["name"] == "table_load_s")
    assert metric["workloads"] == ["sf1f.join"] and metric["moves"] == "setup_s"
