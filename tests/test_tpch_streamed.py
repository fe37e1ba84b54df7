"""The configuration `tpch-sf10-stream` (benchmarks/configs/): the
host-fed `TpchCatalog` behind a STREAMING session (`exec/stream.py`:
the table stays on the host, every statement scans it in batches under
a memory budget), served by `CoordinatorServer`, at SF0.01 on the CPU
in batches of 4,096 rows (15 batches, the last one short).

(a) `q1_full` and `q6_full` served streamed equal the plain references
    on the benchmark's copy of the population, cell by cell, and the
    resident session's rows;
(b) nothing of lineitem is resident afterwards (the configuration's
    `residency` guarantee);
(c) the statement's tree has the shape a resident one has: one span a
    plan node, the scan's per-batch work folded into its node as
    counters, every host read of the driver loop booked;
(d) the three per-layer metrics that read them, and what the two new
    cells' files promise each other.
"""

import importlib.util
import json
import math
import os
import sys
import time

import jax
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

import bytes_model_full  # noqa: E402
import compare  # noqa: E402

SF = 0.01
BATCH_ROWS = 4096
BUDGET = 64 << 20
STATEMENTS = {
    "q1_full-90": ("q1_full", {"delta": 90, "sf": SF}),
    "q1_full-63": ("q1_full", {"delta": 63, "sf": SF}),
    "q1_full-120": ("q1_full", {"delta": 120, "sf": SF}),
    "q6_full-1994": (
        "q6_full", {"year": 1994, "discount": 6, "quantity": 24, "sf": SF}),
    "q6_full-1993": (
        "q6_full", {"year": 1993, "discount": 2, "quantity": 25, "sf": SF}),
    "q6_full-1995": (
        "q6_full", {"year": 1995, "discount": 9, "quantity": 24, "sf": SF}),
    "q6_full-1996": (
        "q6_full", {"year": 1996, "discount": 4, "quantity": 25, "sf": SF}),
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


def sql_for(qid, params):
    with open(os.path.join(BENCH, "sql", qid + ".sql")) as f:
        return f.read().format(**params)


def sql_of(case):
    return sql_for(*STATEMENTS[case])


def n_lineitem():
    return tpch.table("lineitem", SF).num_rows


def n_batches():
    return math.ceil(n_lineitem() / BATCH_ROWS)


class Served:
    def __init__(self, **session):
        self.catalog = tpch.TpchCatalog(sf=SF)
        self.session = Session(self.catalog, result_cache=False, **session)
        self.server = CoordinatorServer(self.session, port=0).start()
        self.client = Client(self.server.uri, timeout=600.0)

    def trace_of(self, sql):
        """(canonical answer, the statement's trace), served once."""
        TRACES.reset()
        cols, rows = self.client.execute(sql)
        (trace,) = TRACES.recent()
        return compare.canonical(cols, rows), trace


@pytest.fixture(scope="module")
def streamed():
    s = Served(streaming=True, batch_rows=BATCH_ROWS, memory_budget=BUDGET)
    try:
        yield s
    finally:
        s.server.stop()


@pytest.fixture(scope="module")
def resident():
    s = Served()
    try:
        yield s
    finally:
        s.server.stop()


@pytest.fixture(scope="module")
def answers(streamed):
    """{case: (served answer, its trace)}, each statement served once."""
    out = {}

    def get(case):
        if case not in out:
            out[case] = streamed.trace_of(sql_of(case))
        return out[case]

    return get


def spans_named(trace, name):
    return [s for s in trace.spans() if s.name == name]


def own_host_reads(trace, name):
    """`host_reads` booked on the span itself, without its children's."""
    (own,) = [n for s, n in trace.exclusive("host_reads") if s.name == name]
    return own


# -- (a) streamed answers: the references', and the resident session's --

@pytest.mark.parametrize("case", list(STATEMENTS))
def test_streamed_answer_equals_the_reference_on_the_copy(answers, case):
    assert n_lineitem() % BATCH_ROWS, "the last batch has to be short"
    got, _trace = answers(case)
    qid, params = STATEMENTS[case]
    ref = load("reference", qid)
    assert ref.TABLES == {}  # it brings datagen_full's tables itself
    want = ref.answer({}, params)
    assert want and got, "an empty answer proves nothing"
    correct, checks = compare.verdict(
        [(case, got)], {case: (want, ref.ORDER_BY)}, 0
    )
    assert correct, checks
    assert all(
        c["value"] == 0 for n, c in checks.items() if n != "answers_compared"
    )


@pytest.mark.parametrize("case", ["q1_full-90", "q6_full-1994"])
def test_streamed_rows_equal_the_resident_sessions(answers, resident, case):
    got, _trace = answers(case)
    want, resident_trace = resident.trace_of(sql_of(case))
    assert got == want
    # the resident tree has the same operators and no streamed counters
    (scan,) = spans_named(resident_trace, "TableScan")
    assert "batches" not in scan.attrs and "scan_s" not in scan.attrs
    assert "lineitem" in resident.catalog._pages


@pytest.fixture(scope="module")
def streamed_pallas():
    """The sink as the TPU runs it: the dense Pallas group-by first
    (default on there; here forced, in interpret mode)."""
    s = Served(
        streaming=True, batch_rows=BATCH_ROWS, memory_budget=BUDGET,
        pallas_groupby=True,
    )
    try:
        yield s
    finally:
        s.server.stop()


@pytest.mark.parametrize("case", ["q1_full-90", "q1_full-63", "q1_full-120"])
def test_streamed_q1_through_the_pallas_sink_equals_the_reference(
    streamed_pallas, answers, case
):
    """Each batch's partial aggregation as ONE program
    (`_pallas_agg_attempt`), merged by the sink: the reference's cells
    and the hash-slot sink's rows; a DELTA the session has not seen
    compiles no new group-by (the literal is an operand)."""
    got, trace = streamed_pallas.trace_of(sql_of(case))
    qid, params = STATEMENTS[case]
    ref = load("reference", qid)
    correct, checks = compare.verdict(
        [(case, got)], {case: (ref.answer({}, params), ref.ORDER_BY)}, 0
    )
    assert correct, checks
    assert got == answers(case)[0]
    (agg,) = spans_named(trace, "Aggregate")
    (scan,) = spans_named(trace, "TableScan")
    assert agg.attrs["partial_strategy"] == "pallas"
    # the batches' program and the end of the stream's (`merge_partials`)
    assert agg.attrs["strategy"] == "pallas" and agg.attrs["programs"] == 2
    assert agg.attrs["merges"] == 1
    assert agg.attrs["bound_literals"] == 1
    assert agg.attrs["agg_hash_batches"] == 0
    assert scan.attrs["batches"] == n_batches()
    # a count a scanned batch: no read of a partial page, whose 64 slots
    # bound its count, and none at the merge, whose slots hold them all
    assert agg.attrs["host_reads"] <= n_batches() + 4
    assert agg.attrs["partial_reads"] == 0
    assert own_host_reads(trace, "Aggregate") == 0
    assert "lineitem" not in streamed_pallas.catalog._pages


# -- the sink's step for a batch: one cached program and no read --

@pytest.fixture(scope="module")
def streamed_wide():
    """Four batches of 16,384 rows, the last one short."""
    s = Served(streaming=True, batch_rows=1 << 14, memory_budget=BUDGET)
    try:
        yield s
    finally:
        s.server.stop()


@pytest.mark.parametrize("batch_rows", [BATCH_ROWS, 1 << 14])
def test_streamed_q6_is_one_program_a_batch_and_no_read(
    streamed, streamed_wide, resident, batch_rows
):
    """No GROUP BY: a batch's partial is the resident path's
    `jit_global_aggregate`, the end of the stream one program over the
    partial pages, and the sink reads nothing, however many batches."""
    served = streamed if batch_rows == BATCH_ROWS else streamed_wide
    batches = math.ceil(n_lineitem() / batch_rows)
    assert n_lineitem() % batch_rows, "the last batch has to be short"
    case = "q6_full-1994"
    got, trace = served.trace_of(sql_of(case))
    qid, params = STATEMENTS[case]
    ref = load("reference", qid)
    correct, checks = compare.verdict(
        [(case, got)], {case: (ref.answer({}, params), ref.ORDER_BY)}, 0
    )
    assert correct, checks
    assert got == resident.trace_of(sql_of(case))[0]
    (agg,) = spans_named(trace, "Aggregate")
    (scan,) = spans_named(trace, "TableScan")
    assert scan.attrs["batches"] == batches
    assert agg.attrs["partial_strategy"] == "global"
    assert agg.attrs["programs"] == 2
    assert agg.attrs["partial_reads"] == 0
    assert scan.attrs["host_reads"] >= batches
    assert own_host_reads(trace, "Aggregate") == 0
    assert "lineitem" not in served.catalog._pages


def test_second_streamed_q6_compiles_nothing_and_caches_two_programs(
    streamed
):
    """The sink's programs live in `KERNEL_CACHE` like every resident
    kernel's: a set of literals the process has not seen adds the
    batches' program (the node holds its literals) and nothing a batch;
    the end of the stream's program has no literal and is there already;
    the same statement again compiles nothing."""
    from presto_tpu.exec.qcache import KERNEL_CACHE

    streamed.trace_of(sql_of("q6_full-1994"))  # the final program's shape
    sql = sql_for(
        "q6_full", {"year": 1997, "discount": 3, "quantity": 24, "sf": SF}
    )
    before = KERNEL_CACHE.snapshot()
    _got, first = streamed.trace_of(sql)
    after = KERNEL_CACHE.snapshot()
    (agg,) = spans_named(first, "Aggregate")
    assert agg.attrs["compiles"] >= 1
    assert 1 <= after["misses"] - before["misses"] <= 2
    assert after["hits"] - before["hits"] >= n_batches()
    _got, again = streamed.trace_of(sql)
    assert all("compiles" not in s.attrs for s in again.spans())
    assert KERNEL_CACHE.snapshot()["misses"] == after["misses"]


def test_large_sort_partials_keep_their_read_and_merge_at_merge_rows(
    monkeypatch,
):
    """A partial page of more than 2^14 slots (the sort strategy over a
    high-NDV key) is read for its count as before, one read a batch, and
    the sink merges whenever half a batch of groups is pending."""
    monkeypatch.setenv("PRESTO_TPU_PALLAS_GROUPBY_HASH", "off")
    batch_rows = 1 << 15
    batches = math.ceil(n_lineitem() / batch_rows)
    sql = (
        "select l_orderkey, l_linenumber, sum(l_quantity) as q "
        "from lineitem group by l_orderkey, l_linenumber"
    )
    s = Served(streaming=True, batch_rows=batch_rows, memory_budget=BUDGET)
    try:
        got, trace = s.trace_of(sql)
    finally:
        s.server.stop()
    assert len(got) == n_lineitem()  # a group a row
    (agg,) = spans_named(trace, "Aggregate")
    assert agg.attrs["partial_strategy"] == "sort"
    assert agg.attrs["partial_reads"] == batches == 2
    # every batch brings more than merge_rows (2^14) groups: a merge a
    # batch, and the end of the stream's
    assert agg.attrs["merges"] == batches + 1


# -- (b) residency --

@pytest.mark.parametrize("case", ["q1_full-90", "q6_full-1994"])
def test_nothing_of_lineitem_is_resident_after_a_streamed_statement(
    streamed, answers, case
):
    answers(case)
    assert "lineitem" not in streamed.catalog._pages
    assert streamed.catalog._pages == {}
    assert "lineitem" in streamed.catalog._tables  # it stays on the host
    assert streamed.session.executor.pool.reserved == 0


# -- (c) the statement's tree --

@pytest.mark.parametrize("case", ["q1_full-90", "q6_full-1994"])
def test_tree_has_one_span_a_plan_node_and_the_scans_counters(answers, case):
    _got, trace = answers(case)
    assert not trace.orphans()
    assert all(s.end is not None for s in trace.spans())
    by_id = {s.span_id: s for s in trace.spans()}
    (execute,) = spans_named(trace, "execute")
    (agg,) = spans_named(trace, "Aggregate")
    (scan,) = spans_named(trace, "TableScan")
    assert by_id[scan.parent_id] is agg
    # positions as Executor._run numbers them: child indices from the root
    chain, s = [], scan
    while s is not execute:
        chain.append(s)
        s = by_id[s.parent_id]
    assert [c.attrs["pos"] for c in reversed(chain)] == [
        ".".join("0" * (i + 1)) for i in range(len(chain))
    ]
    assert chain[-1].name == "Output"
    qid, _params = STATEMENTS[case]
    named = bytes_model_full.named_columns(sql_of(case))["lineitem"]
    width = sum(bytes_model_full.SCHEMA["lineitem"]["columns"][c] for c in named)
    assert width == {"q1_full": 44, "q6_full": 28}[qid]
    assert scan.attrs["batches"] == n_batches() == 15
    assert scan.attrs["rows"] == n_lineitem()
    assert scan.attrs["upload_bytes"] == n_lineitem() * width
    assert 0 < scan.attrs["scan_s"] <= scan.wall_s
    # folded upward, as host_reads is; `rows` is no counter (`execute`
    # carries the answer's row count under that name)
    for span in (agg, execute, trace.root()):
        assert span.attrs["batches"] == n_batches()
        assert span.attrs["upload_bytes"] == scan.attrs["upload_bytes"]
        assert span.attrs["scan_s"] == pytest.approx(scan.attrs["scan_s"])
    assert execute.attrs["rows"] == {"q1_full": 4, "q6_full": 1}[qid]
    # a span's wall is the time inside it: the sink's self time is left
    own = dict(
        (s.span_id, w) for s, w in trace.exclusive_walls()
    )
    assert scan.wall_s < agg.wall_s <= execute.wall_s
    assert own[agg.span_id] == pytest.approx(agg.wall_s - scan.wall_s)
    # what the sink did, from values the host holds
    assert agg.attrs["merges"] >= 1
    assert agg.attrs["pool_peak_bytes"] <= BUDGET
    if qid == "q1_full":
        assert agg.attrs["partial_strategy"] in ("hash", "sort", "hash+sort")
        assert agg.attrs["spilled"] is False
        assert 0 <= agg.attrs["agg_hash_batches"] <= n_batches() + agg.attrs["merges"]
    else:
        assert agg.attrs["partial_strategy"] == "global"


@pytest.mark.parametrize("case", ["q1_full-63", "q6_full-1993"])
def test_booked_host_reads_equal_the_reads_made(streamed, case, monkeypatch):
    """Every blocking read a streamed statement makes goes through
    `obs.span.host_read`: the root's `host_reads` equals a count taken
    at the read site (`np.asarray` of a device array, inside
    obs/span.py, on a thread with an open span), and no code of
    exec/stream.py converts a device value on its own."""
    import numpy as np
    from jax._src import array as jax_array

    made = []

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **k):
            if isinstance(x, jax.Array) and obs_span.current() is not None:
                made.append(1)
            return np.asarray(x, *a, **k)

    stream_py = os.path.join("presto_tpu", "exec", "stream.py")
    direct = []

    def watched(method):
        original = getattr(jax_array.ArrayImpl, method)

        def convert(self):
            caller = sys._getframe(1).f_code.co_filename
            if caller.endswith(stream_py):
                direct.append((method, sys._getframe(1).f_lineno))
            return original(self)

        return convert

    for method in ("__int__", "__bool__", "__index__", "__float__"):
        monkeypatch.setattr(jax_array.ArrayImpl, method, watched(method))
    monkeypatch.setattr(obs_span, "np", CountingNumpy())
    _got, trace = streamed.trace_of(sql_of(case))
    monkeypatch.undo()
    assert direct == []
    (execute,) = spans_named(trace, "execute")
    (scan,) = spans_named(trace, "TableScan")
    assert scan.attrs["host_reads"] >= n_batches()  # a count a batch
    assert execute.attrs["host_reads"] >= scan.attrs["host_reads"]
    assert trace.root().attrs["host_reads"] == len(made)
    assert trace.root().attrs["host_read_wait_s"] > 0


def test_streamed_filter_project_limit_have_spans_and_stop_early(streamed):
    """Nodes that stream batches on (`Filter`, `Project`) get ONE span
    each for all their batches, timed over their own pieces; a LIMIT
    that stops pulling closes them `ok` with the batches it took."""
    got, trace = streamed.trace_of(
        "select l_orderkey, l_quantity * 2 as q from lineitem "
        "where l_quantity < 3 limit 5"
    )
    assert len(got) == 5
    assert not trace.orphans()
    spans = {s.name: s for s in trace.spans()}
    for name in ("Output", "Limit", "TableScan"):
        assert spans[name].end is not None and spans[name].status == "ok"
    assert {"Filter", "Project"} & set(spans)
    scan = spans["TableScan"]
    assert 1 <= scan.attrs["batches"] < n_batches()  # it stopped early
    assert scan.attrs["rows"] == scan.attrs["batches"] * BATCH_ROWS
    (execute,) = spans_named(trace, "execute")
    own = sum(
        w for s, w in trace.exclusive_walls()
        if s.name in ("Output", "Limit", "Filter", "Project", "TableScan")
    )
    assert own <= execute.wall_s * 1.001
    assert obs_span.current() is None


# -- obs.span.Pulled and count, alone --

def test_pulled_span_is_timed_over_its_pieces_and_folds_its_counters():
    trace = obs_span.Trace()
    outer = trace.enter("sink")
    try:
        pulled = obs_span.Pulled.open("source", pos="0.0")
        for _ in range(3):
            with pulled as span:
                assert obs_span.current() == (trace, span)
                obs_span.count(batches=1, scan_s=0.25)
                time.sleep(0.01)
            assert obs_span.current() == (trace, outer)
            time.sleep(0.02)  # the consumer's time: not the source's
        closed = pulled.close()
    finally:
        trace.leave(outer)
    assert closed.attrs == {"pos": "0.0", "batches": 3, "scan_s": 0.75}
    assert 0.03 <= closed.wall_s < 0.06
    assert outer.attrs["batches"] == 3 and outer.attrs["scan_s"] == 0.75
    own = {s.name: w for s, w in trace.exclusive_walls()}
    assert own["sink"] == pytest.approx(outer.wall_s - closed.wall_s)
    assert own["sink"] >= 0.06


def test_pulled_and_count_without_an_open_span_do_nothing():
    assert obs_span.current() is None
    assert obs_span.Pulled.open("source") is None
    obs_span.count(batches=1)  # nothing to book on: no error


def test_streaming_executor_without_a_trace_opens_no_span():
    from presto_tpu.exec.stream import StreamingExecutor

    catalog = tpch.TpchCatalog(sf=SF)
    session = Session(catalog, result_cache=False)
    ex = StreamingExecutor(catalog, batch_rows=BATCH_ROWS, memory_budget=BUDGET)
    TRACES.reset()
    page = ex.run(session.plan("select count(*) from lineitem"))
    assert page.to_pylist() == [(n_lineitem(),)]
    assert TRACES.recent() == [] and ex._pos == {}


# -- (d) the metrics that read the spans --

class _Run:
    def __init__(self, epoch_ns):
        self.records = [{"epoch_ns": epoch_ns}]


@pytest.mark.parametrize(
    "metric", ["stream_batches_per_stmt", "stream_scan_ms", "stream_sink_ms"]
)
def test_stream_metrics_read_the_streamed_statements(
    streamed, resident, metric
):
    reader = load("layer_metrics", metric)
    TRACES.reset()
    t0 = time.time_ns()
    assert reader.compute(_Run(t0)) is None  # no statement at all
    resident.client.execute(sql_of("q1_full-90"))
    assert reader.compute(_Run(t0)) is None  # none that streamed
    TRACES.reset()
    t0 = time.time_ns()
    for case in ("q1_full-90", "q6_full-1994"):
        streamed.client.execute(sql_of(case))
    traces = TRACES.recent()
    assert len(traces) == 2
    value = reader.compute(_Run(t0))
    scans = [s for t in traces for s in spans_named(t, "TableScan")]
    if metric == "stream_batches_per_stmt":
        assert value == n_batches()
    elif metric == "stream_scan_ms":
        assert value == pytest.approx(
            sum(s.attrs["scan_s"] for s in scans) / 2 * 1e3
        )
    else:
        sinks = [
            w for t in traces for s, w in t.exclusive_walls()
            if s.name == "Aggregate"
        ]
        assert value == pytest.approx(sum(sinks) / 2 * 1e3)
        assert value == pytest.approx(
            load("layer_metrics", "aggregate_ms").compute(_Run(t0))
        )


# -- the cell's deployment: coordinator.py's, held to two things --

def small_stream_config():
    config = bench_json("configs", "tpch-sf10-stream.json")
    config["sf"] = SF
    config["session"] = dict(
        config["session"], batch_rows=BATCH_ROWS, memory_budget=BUDGET
    )
    return config


class _Device:
    def __init__(self, held):
        self.held = held

    def memory_stats(self):
        return None if self.held is None else {"bytes_in_use": self.held}


@pytest.mark.parametrize("held,kept", [
    (None, True), (BUDGET, True), (BUDGET + 1, False),
])
def test_streamed_deployment_holds_the_device_to_the_budget(
    held, kept, monkeypatch
):
    deployment = load("deployments", "coordinator_streamed").start(
        small_stream_config()
    )
    try:
        cols, rows = deployment.client().execute(sql_of("q6_full-1994"))
        ref = load("reference", "q6_full")
        want = ref.answer({}, STATEMENTS["q6_full-1994"][1])
        correct, checks = compare.verdict(
            [("q6", compare.canonical(cols, rows))],
            {"q6": (want, ref.ORDER_BY)}, 0,
        )
        assert correct, checks
    finally:
        monkeypatch.setattr(jax, "devices", lambda: [_Device(held)])
        if kept:
            deployment.stop()
        else:
            with pytest.raises(SystemExit, match="residency"):
                deployment.stop()


def test_streamed_deployment_refuses_a_program_without_streamed_spans(
    monkeypatch
):
    monkeypatch.delattr(obs_span, "Pulled")
    with pytest.raises(SystemExit, match="cannot run a streamed cell"):
        load("deployments", "coordinator_streamed").start(
            small_stream_config()
        )


# -- what the cells' files promise each other --

def test_the_stream_cells_files_agree():
    bench = bench_json("..", "BENCHMARK.json")
    cell = bench_json("workloads", "sf10s.scan_agg.json")
    config = bench_json("configs", cell["config"] + ".json")
    full = bench_json("configs", "tpch-sf10-full.json")
    mix = bench_json("traffic", cell["traffic"] + ".json")
    entry = next(w for w in bench["workloads"] if w["name"] == "sf10s.scan_agg")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "tpch-sf10-stream", "scan_agg_full", config["chips"]
    )
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    listed = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert listed["reduced"] == config["reduced"] == full["reduced"]
    assert listed["source"] == config["source"] and len(listed["source"]) <= 200
    # tpch-sf10-full's population and catalog; what differs is the session
    assert config["catalog"] == full["catalog"]
    for key in (
        "sf", "chips", "tables", "lineitem_columns",
        "lineitem_rows", "lines_per_order", "order_keys", "random_streams",
    ):
        assert config[key] == full[key], key
    # coordinator.py's deployment, held to what a streamed cell rests on
    assert (full["serve"], config["serve"]) == (
        "coordinator", "coordinator_streamed"
    )
    assert config["session"] == {
        "result_cache": False, "streaming": True,
        "batch_rows": 1 << 20, "memory_budget": 512 << 20,
    }
    for key, text in full["guarantees"].items():
        assert config["guarantees"][key] == text
    assert "residency" in config["guarantees"]
    assert config["stream"]["batches_per_statement"] == math.ceil(
        config["lineitem_rows"] / config["session"]["batch_rows"]
    ) == 58
    # the statements: q1.sql / q6.sql's texts, scan_agg.json's parameters
    q1, q6 = mix["statements"]
    old_q1, old_q6 = bench_json("traffic", "scan_agg.json")["statements"]
    assert (q1["id"], q6["id"]) == ("q1_full", "q6_full")
    assert q1["params"]["delta"] == old_q1["params"]["delta"]
    assert q1["params"]["sf"] == {"range": [10, 10]}
    assert q1["distinct_per_run"] == 4
    assert [
        {k: v for k, v in s.items() if k != "sf"} for s in q6["sets"]
    ] == old_q6["sets"][:4]
    assert all(s["sf"] == config["sf"] for s in q6["sets"])
    assert (mix["loop"], mix["clients"], mix["warmup_passes"]) == (
        "closed", 1, 1
    )
    for new, old in (("q1_full", "q1"), ("q6_full", "q6")):
        with open(os.path.join(BENCH, "sql", old + ".sql")) as a, \
                open(os.path.join(BENCH, "sql", new + ".sql")) as b:
            assert a.read() == b.read()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("stream_batches_per_stmt", "stream_scan_ms",
                 "stream_sink_ms"):
        m = by_name[name]
        assert m["workloads"] == ["sf10s.scan_agg"] and m["moves"] == "stmt_ms"


def test_the_sf10_join_cells_files_agree():
    bench = bench_json("..", "BENCHMARK.json")
    cell = bench_json("workloads", "sf10.join.json")
    entry = next(w for w in bench["workloads"] if w["name"] == "sf10.join")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "tpch-sf10-full", "join_full_sf10", 1
    ) == (cell["config"], cell["traffic"], 1)
    assert entry["why"] == cell["why"] and len(entry["why"]) <= 200
    config = bench_json("configs", "tpch-sf10-full.json")
    (st,) = bench_json("traffic", "join_full_sf10.json")["statements"]
    (old,) = bench_json("traffic", "join_full.json")["statements"]
    assert st["id"] == old["id"] == "q3_full"
    assert [dict(s, sf=config["sf"]) for s in old["sets"]] == st["sets"]
    # the listed metrics that name the two cells: the streamed scan's
    # (PR 35) and the ready stamps' (PR 37), and no end-to-end one
    naming = {
        cell: {
            m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if cell in m.get("workloads", [])
        }
        for cell in ("sf10.join", "sf10s.scan_agg")
    }
    assert naming == {
        "sf10.join": {
            "join_device_ms", "filter_device_ms", "aggregate_device_ms",
        },
        "sf10s.scan_agg": {
            "stream_batches_per_stmt", "stream_scan_ms", "stream_sink_ms",
            "stream_upload_ms", "stream_link_idle_ms",
            "stream_inflight_peak_mb",
        },
    }
