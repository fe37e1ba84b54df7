"""One span tree per served statement (docs/observability.md): the
lifecycle spans `QueryManager` opens, the operator spans `Executor._run`
hangs under `execute`, the counters booked on them (`host_reads`,
`host_read_wait_s`, `compiles`, `compile_s`), the profiler annotations
that put the same tree on a trace's clock, the names of the device
programs, and the benchmark's six metric files that read the tree.
"""

import ast
import glob
import importlib.util
import json
import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from presto_tpu.connectors.tpch_device import DeviceTpchCatalog
from presto_tpu.obs import span as obs_span
from presto_tpu.obs.span import TRACES, Trace, TraceStore
from presto_tpu.server import Client, CoordinatorServer
from presto_tpu.session import Session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SF = 0.01
PARAMS = {
    "q1": {"delta": 90},
    "q6": {"year": 1994, "discount": 6, "quantity": 24},
    "q3": {"segment": "BUILDING", "date": "1995-03-15"},
}
LIFECYCLE = ["submit", "queued", "query", "rows"]


def sql_of(qid: str, **params) -> str:
    with open(os.path.join(BENCH, "sql", qid + ".sql")) as f:
        return f.read().format(**(params or PARAMS[qid]))


@pytest.fixture(scope="module")
def served():
    session = Session(DeviceTpchCatalog(sf=SF), result_cache=False)
    server = CoordinatorServer(session, port=0).start()
    try:
        yield session, server, Client(server.uri, timeout=600.0)
    finally:
        server.stop()


def serve(served, sql):
    """(rows, the trace the statement left, its query id); the statement
    must leave exactly one."""
    _session, server, client = served
    before = {t.trace_id for t in TRACES.recent()}
    _cols, rows = client.execute(sql)
    new = [t for t in TRACES.recent() if t.trace_id not in before]
    assert len(new) == 1
    qid = server.manager.list_queries()[-1].query_id
    return rows, new[0], qid


def span_named(trace, name):
    return next(s for s in trace.spans() if s.name == name)


def plan_positions(node, pos="0"):
    """{position: class name} of a plan, as `Executor._run` numbers it."""
    out = {pos: type(node).__name__}
    for i, child in enumerate(node.children):
        out.update(plan_positions(child, f"{pos}.{i}"))
    return out


# -- (a) the tree ------------------------------------------------------------


@pytest.mark.parametrize("qid", ["q1", "q6", "q3"])
def test_served_statement_leaves_one_tree(served, qid):
    session = served[0]
    sql = sql_of(qid)
    _rows, trace, query_id = serve(served, sql)
    assert TRACES.by_query_id(query_id) is trace
    root = trace.root()
    assert root.name == "statement" and root.attrs["query_id"] == query_id
    assert [s.name for s in trace.children(root.span_id)] == LIFECYCLE
    query = span_named(trace, "query")
    assert [s.name for s in trace.children(query.span_id)] == [
        "plan", "execute",
    ]
    assert trace.orphans() == []
    by_id = {s.span_id: s for s in trace.spans()}
    for s in by_id.values():
        assert s.end is not None and s.status == "ok"
        if s.parent_id is not None:
            parent = by_id[s.parent_id]
            assert parent.start <= s.start and s.end <= parent.end, s.name
    # the operators: the plan's node classes, nested as the plan is
    lifecycle = set(LIFECYCLE) | {"statement", "plan", "execute"}
    operators = {
        s.attrs["pos"]: s for s in by_id.values() if s.name not in lifecycle
    }
    assert {p: s.name for p, s in operators.items()} == plan_positions(
        session.plan(sql)
    )
    execute = span_named(trace, "execute")
    for pos, s in operators.items():
        parent = operators[pos.rsplit(".", 1)[0]] if "." in pos else execute
        assert s.parent_id == parent.span_id
    # the lifecycle phases ride the completed event's phase_ms
    info = served[1].manager.get(query_id)
    assert info.trace_id == trace.trace_id
    assert set(info.phase_ms) == {
        "submit", "queued", "plan", "execute", "rows",
    }


def test_metrics_fold_lifecycle_phases(served):
    from presto_tpu.obs.metrics import METRICS

    serve(served, sql_of("q6"))
    text = METRICS.render()
    for phase in ("submit", "queued", "plan", "execute", "rows"):
        assert f"presto_query_phase_{phase}_seconds_count" in text


def test_join_and_filter_spans_say_what_the_estimate_did(served):
    """Q3 through the coordinator's SystemCatalog: the expand join's
    span carries the CBO's estimate and the capacity `join_expand` ran
    at, the span that applied a dynamic filter what it pruned."""
    _rows, trace, _ = serve(served, sql_of("q3"))
    spans = trace.spans()
    # lineitem x (orders x customer); orders x customer is n:1
    (expand,) = [
        s for s in spans if s.name == "Join" and "out_capacity" in s.attrs
    ]
    attrs = expand.attrs
    cap = attrs["out_capacity"]
    assert cap & (cap - 1) == 0 and cap >= attrs["est_rows"] > 0
    assert "retries" not in attrs  # the estimate held: no overflow, no re-run
    pruned = [s for s in spans if "dyn_pruned" in s.attrs]
    assert any(s.name == "Filter" for s in pruned)
    assert {s.name for s in pruned} <= {"Filter", "TableScan", "Join"}
    for s in pruned:
        assert s.attrs["dyn_pruned"] >= 0 and ":" in s.attrs["dyn_strategy"]


# -- (b) host reads ----------------------------------------------------------


@pytest.mark.parametrize("qid", ["q1", "q6", "q3"])
def test_host_reads_repeat_and_fold(served, qid):
    sql = sql_of(qid)
    serve(served, sql)  # a first run reads what later ones find cached
    readings = []
    for _ in range(2):
        _rows, trace, _ = serve(served, sql)
        own = {s.span_id: n for s, n in trace.exclusive("host_reads")}
        spans = trace.spans()
        readings.append(
            sorted((s.attrs.get("pos", s.name), own[s.span_id]) for s in spans)
        )
        execute = span_named(trace, "execute")
        under = {execute.span_id}
        for s in spans:  # begin order: a parent comes before its children
            if s.parent_id in under:
                under.add(s.span_id)
        assert execute.attrs["host_reads"] >= 1  # the result's row count
        assert execute.attrs["host_reads"] == sum(own[i] for i in under)
        assert trace.root().attrs["host_reads"] == sum(own.values())
        for s in spans:
            assert s.attrs.get("host_read_wait_s", 0.0) <= s.wall_s + 1e-9
    assert readings[0] == readings[1]


def test_host_read_counts_one_copy_per_array():
    trace = Trace()
    root = trace.enter("reader")
    x = jnp.arange(4) + 1
    assert obs_span.host_read(x).tolist() == [1, 2, 3, 4]
    # an accelerator's array now holds its host copy and is not read
    # again; the CPU backend reads in place and keeps none
    again = x._npy_value is None
    obs_span.host_read(x)
    obs_span.host_read(7)  # not a device value
    trace.leave(root)
    assert root.attrs["host_reads"] == 1 + again
    assert 0.0 <= root.attrs["host_read_wait_s"] <= root.wall_s


# -- (c) compiles ------------------------------------------------------------


def test_new_literal_books_compiles_on_its_operator(served):
    sql = sql_of("q6", year=1996, discount=3, quantity=17)
    _rows, first, _ = serve(served, sql)
    own = {s.name: n for s, n in first.exclusive("compiles") if n}
    assert own.get("Aggregate", 0) >= 1  # Q6's one fused program
    agg = span_named(first, "Aggregate")
    assert 0.0 < agg.attrs["compile_s"] <= agg.wall_s
    assert first.root().attrs["compiles"] == sum(own.values())
    _rows, again, _ = serve(served, sql)
    assert all("compiles" not in s.attrs for s in again.spans())


def test_fresh_q1_delta_hits_the_fused_program(served):
    """Q1's DELTA is an operand of ONE `grouped_aggregate_pallas` program,
    not part of its key: a DELTA the process has not seen compiles
    nothing, hits the kernel cache, and still answers for ITS date (a
    stale literal is the bug this is here for). Counts, so they repeat."""
    from presto_tpu.exec.qcache import KERNEL_CACHE

    session = Session(
        DeviceTpchCatalog(sf=SF), result_cache=False, pallas_groupby=True
    )
    server = CoordinatorServer(session, port=0).start()
    try:
        pallas = session, server, Client(server.uri, timeout=600.0)
        answers = []
        for i, delta in enumerate((90, 91, 117)):
            before = KERNEL_CACHE.snapshot()
            rows, trace, _ = serve(pallas, sql_of("q1", delta=delta))
            after = KERNEL_CACHE.snapshot()
            agg = span_named(trace, "Aggregate")
            assert agg.attrs["strategy"] == "pallas"
            assert agg.attrs["programs"] == 1
            assert agg.attrs["bound_literals"] == 1
            if i:
                assert all("compiles" not in s.attrs for s in trace.spans())
                assert after["misses"] == before["misses"]
                assert after["hits"] > before["hits"]
            else:
                assert agg.attrs["compiles"] >= 1
            # the XLA composition, pallas off, is the reference
            assert rows == serve(served, sql_of("q1", delta=delta))[0]
            answers.append(rows)
    finally:
        server.stop()
    assert len({repr(rows) for rows in answers}) == 3


# -- (d) program names -------------------------------------------------------


# where each way into the kernel cache takes the program's name
NAME_ARGUMENT = {"_kernel": 0, "_kernel_guarded": 1, "_run_packed": 2}


def test_every_kernel_site_names_its_program():
    path = os.path.join(ROOT, "presto_tpu", "exec", "executor.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    sites = 0
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in NAME_ARGUMENT
        ):
            continue
        arg = node.args[NAME_ARGUMENT[node.func.attr]]
        named = isinstance(arg, ast.Constant) and isinstance(arg.value, str)
        # _kernel_guarded and _run_packed hand their own parameter on
        assert named or isinstance(arg, ast.Name), ast.dump(node)
        sites += named
    assert sites >= 18


def test_kernel_module_is_named_for_its_site():
    from presto_tpu.connectors.memory import MemoryCatalog
    from presto_tpu.exec.executor import Executor

    fn = Executor(MemoryCatalog({}))._build_kernel(
        "filter", lambda: lambda x: x + 1
    )
    fn = getattr(fn, "fn", fn)  # the compile/execute profiler's shim
    assert "module @jit_filter" in fn.lower(jnp.ones(4)).as_text()


def test_no_program_of_q1_q3_q6_is_a_lambda(served, caplog):
    """Every program the three statements compile (fresh literals, so
    their kernels are traced anew) has a name; XLA calls a jitted lambda
    `jit__lambda_`."""
    fresh = {
        "q1": {"delta": 61},
        "q6": {"year": 1997, "discount": 8, "quantity": 19},
        "q3": {"segment": "HOUSEHOLD", "date": "1995-03-07"},
    }
    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING, logger="jax"):
            for qid, params in fresh.items():
                serve(served, sql_of(qid, **params))
    finally:
        jax.config.update("jax_log_compiles", False)
    compiled = [
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("Compiling ")
    ]
    assert len(compiled) >= 3
    assert not [m for m in compiled if "lambda" in m.split(" with ")[0]]


# -- (e) PRESTO_TPU_TRACE=0 --------------------------------------------------


def test_trace_off_leaves_no_trace_and_the_same_rows(served, monkeypatch):
    sql = sql_of("q3")
    rows_on, _trace, _ = serve(served, sql)
    monkeypatch.setenv("PRESTO_TPU_TRACE", "0")
    before = [t.trace_id for t in TRACES.recent()]
    _cols, rows_off = served[2].execute(sql)
    assert [t.trace_id for t in TRACES.recent()] == before
    assert rows_off == rows_on
    info = served[1].manager.list_queries()[-1]
    assert info.trace_id is None and info.phase_ms is None
    assert obs_span.current() is None
    # nothing to book on: the helper is a plain np.asarray
    assert obs_span.host_read(jnp.arange(3)).tolist() == [0, 1, 2]


# -- (f) the profiler's clock -----------------------------------------------


def profiled_annotations(tmp_path, run):
    """[(name, start ns, end ns)] of the `presto.*` events on each line
    of a profile taken around `run()`."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    assert paths
    return [
        [
            (e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events if e.name.startswith("presto.")
        ]
        for plane in ProfileData.from_file(paths[-1]).planes
        for line in plane.lines
    ]


def nested(events, inner, outer):
    """Events called `inner` inside an event called `outer`."""
    return sum(
        n == inner and a <= c and d <= b
        for name, a, b in events if name == outer
        for n, c, d in events
    )


def test_spans_are_annotations_in_a_profile(served, tmp_path):
    sql = sql_of("q6")
    serve(served, sql)  # warm: the profile holds a steady statement
    lines = profiled_annotations(tmp_path, lambda: serve(served, sql))
    names = {name for events in lines for name, _a, _b in events}
    # the worker thread's line: the operator inside execute
    assert sum(
        nested(ev, "presto.Aggregate", "presto.execute") for ev in lines
    ) == 1
    assert {"presto.submit", "presto.query", "presto.plan",
            "presto.rows", "presto.TableScan"} <= names
    assert "presto.queued" not in names  # crosses threads: no annotation


def test_a_streamed_statements_pieces_are_annotations(tmp_path):
    """`obs.span.Pulled`: a node that hands batches on has ONE span and
    an annotation a piece, inside its sink's."""
    from presto_tpu.connectors import tpch

    session = Session(
        tpch.TpchCatalog(sf=SF), result_cache=False, streaming=True,
        batch_rows=4096, memory_budget=64 << 20,
    )
    sql = sql_of("q6")
    session.query(sql).rows()
    lines = profiled_annotations(
        tmp_path, lambda: session.query(sql).rows()
    )
    trace = TRACES.recent()[-1]
    scan = span_named(trace, "TableScan")
    assert scan.attrs["batches"] == 15
    # a piece a batch and the one that finds the stream at its end
    assert sum(
        nested(ev, "presto.TableScan", "presto.Aggregate") for ev in lines
    ) == scan.attrs["batches"] + 1
    assert sum(
        nested(ev, "presto.Aggregate", "presto.execute") for ev in lines
    ) == 1
    assert len([s for s in trace.spans() if s.name == "TableScan"]) == 1


# -- (g) the benchmark's metric files ----------------------------------------

NEW_METRICS = (
    "submit_ms", "queued_ms", "rows_ms", "host_reads_per_stmt",
    "host_read_wait_ms", "dispatch_ms",
)


def metric_module(name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        f"layer_metrics.{name}",
        os.path.join(BENCH, "layer_metrics", name + ".py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class HandRun:
    """What a metric reader takes of `benchmarks/run.py`'s Run."""

    def __init__(self, opened_s):
        self.records = [{"epoch_ns": int(opened_s * 1e9)}]


def hand_statement(store, at, execute_s, wait_s, reads, root="statement"):
    """A statement's tree on a clock of the test's own: submit 1 ms,
    queued 2 ms, execute as given, rows 4 ms."""
    trace = store.new_trace(query_id="q_1")
    top = trace.begin(root, start=at)

    def span(name, parent, start, wall, **counters):
        s = trace.begin(name, parent=parent, start=start)
        s.counters.update(counters)
        s.end = start + wall
        return trace.finish(s)

    span("submit", top, at, 0.001)
    span("queued", top, at + 0.001, 0.002)
    query = trace.begin("query", parent=top, start=at + 0.003)
    span("execute", query, at + 0.003, execute_s,
         host_reads=reads, host_read_wait_s=wait_s)
    query.end = at + 0.003 + execute_s
    trace.finish(query)
    span("rows", top, query.end, 0.004)
    top.end = query.end + 0.004
    trace.finish(top)


def test_metric_files_on_a_hand_built_store(monkeypatch):
    store = TraceStore()
    monkeypatch.setattr(obs_span, "TRACES", store)
    hand_statement(store, 50.0, 9.0, 9.0, 99)  # before the window opened
    hand_statement(store, 100.0, 0.030, 0.020, 3)
    hand_statement(store, 101.0, 0.050, 0.040, 1)
    # a tree of a program without the `statement` span is not read
    hand_statement(store, 102.0, 9.0, 9.0, 99, root="query")
    got = {n: metric_module(n).compute(HandRun(100.0)) for n in NEW_METRICS}
    assert got == {
        "submit_ms": pytest.approx(1.0), "queued_ms": pytest.approx(2.0),
        "rows_ms": pytest.approx(4.0),
        "host_reads_per_stmt": pytest.approx(2.0),
        "host_read_wait_ms": pytest.approx(30.0),
        "dispatch_ms": pytest.approx(10.0),
    }
    # nothing to read (no statement since the window opened): no metric
    late = HandRun(200.0)
    assert [metric_module(n).compute(late) for n in NEW_METRICS] == [None] * 6


def test_rehearsal_traced_run_prints_the_six():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="0")
    env.pop("PRESTO_TPU_TRACE", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "rehearsal.scan_agg", "--seed", "3000000019", "--seconds", "3",
         "--trace", "1"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(metrics)
    # the parts lie inside what times the same layer from outside
    assert (
        metrics["submit_ms"] + metrics["queued_ms"] + metrics["rows_ms"]
        <= metrics["http_ms"]
    )
    assert metrics["host_reads_per_stmt"] >= 1
    assert metrics["host_read_wait_ms"] >= 0 and metrics["dispatch_ms"] > 0
