"""The unified observability plane (docs/observability.md).

Covers the obs/ package end to end: span trees (begin/finish, remote
merge idempotence, retry-sibling semantics, exclusive-wall critical
path), the MetricsRegistry (counters/gauges/histograms, Prometheus
exposition, scrape-time producers, failure isolation), the
single-process Session trace + EXPLAIN ANALYZE footers, system.runtime.metrics / system.runtime.tasks,
the query_completed event's trace fields, NodeStats cumulative output
accounting, and the coordinator's /v1/metrics endpoint.
"""

import urllib.request

import pytest

from presto_tpu.connectors.tpch import TpchCatalog
from presto_tpu.obs.metrics import METRICS, MetricsRegistry
from presto_tpu.obs.span import TRACES, Trace, render_critical_path
from presto_tpu.session import Session

SF = 0.002


# -- span trees ---------------------------------------------------------------


def test_span_tree_basics():
    tr = Trace()
    root = tr.begin("query", sql="select 1")
    child = tr.begin("plan", parent=root)
    tr.finish(child)
    tr.finish(root, rows=1)
    assert root.parent_id is None
    assert child.parent_id == root.span_id
    assert tr.root() is root
    assert tr.children(root.span_id) == [child]
    assert tr.orphans() == []
    assert root.wall_s >= child.wall_s >= 0
    assert root.attrs["rows"] == 1


def test_remote_merge_is_idempotent_and_upgrades():
    coord = Trace("abc123")
    worker = Trace("abc123")
    anchor = coord.begin("dispatch t_1")
    span = worker.begin("task t_1", parent_id=anchor.span_id)
    # mid-flight poll: unfinished span (end=None) merges...
    assert coord.add_remote(worker.to_dicts()) == 1
    merged = {s.span_id: s for s in coord.spans()}[span.span_id]
    assert merged.end is None
    # ...and the final poll upgrades it in place, no duplicate
    worker.finish(span, rows=7)
    assert coord.add_remote(worker.to_dicts()) == 1
    assert len(coord.spans()) == 2
    merged = {s.span_id: s for s in coord.spans()}[span.span_id]
    assert merged.end is not None and merged.attrs["rows"] == 7
    # malformed dicts are skipped, not fatal
    assert coord.add_remote([{"name": "no-id"}, None]) == 0


def test_retry_attempts_are_siblings():
    tr = Trace()
    stage = tr.begin("stage hash:Aggregate")
    d1 = tr.begin("dispatch t_1", parent=stage, worker="w1")
    tr.finish(d1, "error", error="injected fault")
    d2 = tr.begin("dispatch t_2", parent=stage, worker="w2")
    tr.finish(d2)
    kids = tr.children(stage.span_id)
    assert [k.status for k in kids] == ["error", "ok"]
    assert "!" + d1.name in render_critical_path(tr, topk=10)


def test_exclusive_wall_and_critical_path(monkeypatch):
    # begin/finish on a clock the test steps: query [0, 1] holds
    # plan [0, 0.05] and execute [0.1, 1.0]
    from presto_tpu.obs import span as obs_span

    clock = iter([0.0, 0.0, 0.05, 0.1, 1.0, 1.0])
    monkeypatch.setattr(obs_span.time, "time", lambda: next(clock))
    tr = Trace()
    root = tr.begin("query")
    tr.finish(tr.begin("plan", parent=root))
    inner = tr.finish(tr.begin("execute", parent=root))
    tr.finish(root)
    monkeypatch.undo()
    excl = {s.name: e for s, e in tr.exclusive_walls()}
    assert excl["query"] == pytest.approx(0.05, abs=1e-6)
    assert excl["execute"] == pytest.approx(0.9, abs=1e-6)
    top = tr.critical_path(topk=1)
    assert top[0][0] is inner


def test_trace_store_bounded(monkeypatch):
    from presto_tpu.obs.span import TraceStore

    # a private store: evicting from the process-global TRACES would
    # couple this test to every other test that reads TRACES.recent()
    monkeypatch.setenv("PRESTO_TPU_TRACE_KEEP", "3")
    store = TraceStore()
    ids = [store.new_trace().trace_id for _ in range(5)]
    assert store.get(ids[0]) is None  # FIFO-evicted
    assert store.get(ids[-1]) is not None


# -- metrics registry ---------------------------------------------------------


def test_registry_counter_gauge_histogram_render():
    reg = MetricsRegistry()
    reg.counter("t_hits_total", 2, {"cache": "plan"}, help="hits")
    reg.counter("t_hits_total", 1, {"cache": "plan"})
    reg.gauge("t_bytes", 42.0)
    reg.observe("t_seconds", 0.001)
    reg.observe("t_seconds", 0.002)
    text = reg.render()
    assert '# TYPE t_hits_total counter' in text
    assert 't_hits_total{cache="plan"} 3' in text
    assert "t_bytes 42" in text
    # cumulative buckets: each observation lands in exactly one bucket
    # and bucket counts are monotone, never exceeding _count
    assert 't_seconds_bucket{le="0.001"} 1' in text
    assert 't_seconds_bucket{le="0.002"} 2' in text
    assert 't_seconds_bucket{le="0.004"} 2' in text
    assert 't_seconds_bucket{le="+Inf"} 2' in text
    assert "t_seconds_count 2" in text
    assert text.endswith("\n")


def test_registry_producer_runs_at_scrape_and_is_isolated():
    reg = MetricsRegistry()
    reg.register_producer(
        "good", lambda: [("t_pull", "gauge", (), 1.0)]
    )
    reg.register_producer("bad", lambda: 1 / 0)
    text = reg.render()
    assert "t_pull 1" in text
    # the failing producer is counted, not fatal
    assert "presto_scrape_errors_total 1" in text


def test_label_escaping():
    reg = MetricsRegistry()
    reg.counter("t_esc_total", 1, {"q": 'a"b\\c\nd'})
    text = reg.render()
    assert '{q="a\\"b\\\\c\\nd"}' in text


# -- single-process session ---------------------------------------------------


@pytest.fixture(scope="module")
def sess():
    return Session(TpchCatalog(sf=SF))


def test_session_query_carries_trace(sess):
    res = sess.query("select count(*) from region")
    assert res.trace_id is not None
    assert set(res.phase_ms) == {"plan", "execute"}
    tr = TRACES.get(res.trace_id)
    assert tr is not None
    root = tr.root()
    kids = tr.children(root.span_id)
    assert sorted(k.name for k in kids) == ["execute", "plan"]
    # phase exclusive walls account for the query wall
    assert abs(sum(k.wall_s for k in kids) - root.wall_s) \
        <= max(0.01, 0.1 * root.wall_s)


def test_session_trace_disabled(monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_TRACE", "0")
    s = Session(TpchCatalog(sf=SF))
    res = s.query("select count(*) from nation")
    assert res.trace_id is None and res.phase_ms is None


def test_explain_analyze_trace_and_kernel_footers(sess):
    out = sess.query(
        "explain analyze select r_name, count(*) from region group by r_name"
    )
    text = "\n".join(r[0] for r in out.rows())
    assert "-- trace: trace " in text
    assert "top exclusive:" in text
    # the first run of the text compiled, and every node was stamped
    assert "-- kernels: compile +" in text and " device-side " in text
    # per-node synthetic spans graft into the same tree shape
    assert "TableScan" in text.split("-- trace:")[1] or "Aggregate" in text


def test_query_error_traced(sess):
    with pytest.raises(Exception):
        sess.query("select no_such_column from region")
    # the most recent trace carries the error status on its root
    spans = [s for tr in TRACES.recent() for s in tr.spans()]
    assert any(s.status == "error" for s in spans)


# -- system tables ------------------------------------------------------------


def test_system_runtime_metrics_and_tasks():
    from presto_tpu.connectors.system import SystemCatalog

    s = Session(SystemCatalog(TpchCatalog(sf=SF)))
    s.query("select count(*) from nation")
    rows = s.query(
        "select name, value from system.runtime.metrics "
        "where name = 'presto_queries_total'"
    ).rows()
    assert rows and all(v >= 1 for _, v in rows)
    names = {r[0] for r in s.query(
        "select name from system.runtime.metrics"
    ).rows()}
    assert "presto_qcache_hits_total" in names
    assert "presto_kernel_compiles_total" in names
    task_rows = s.query(
        "select trace_id, name, status, wall_ms "
        "from system.runtime.tasks"
    ).rows()
    assert any(name == "query" for _, name, _, _ in task_rows)
    assert all(status in ("ok", "error") for _, _, status, _ in task_rows)


# -- event bus ----------------------------------------------------------------


def test_query_completed_event_carries_trace_and_phases():
    from presto_tpu.server.coordinator import CoordinatorServer
    from presto_tpu.server.client import Client
    from presto_tpu.server.events import EventListener

    class Capture(EventListener):
        def __init__(self):
            self.events = []

        def query_completed(self, event):
            self.events.append(event)

    cap = Capture()
    srv = CoordinatorServer(
        Session(TpchCatalog(sf=SF)), listeners=[cap]
    ).start()
    try:
        Client(srv.uri).execute("select count(*) from region")
        ev = cap.events[-1]
        assert ev.state == "FINISHED"
        assert ev.trace_id is not None
        assert ev.phase_ms and "execute" in ev.phase_ms
        assert TRACES.get(ev.trace_id) is not None
        # the coordinator role serves the same metrics plane
        with urllib.request.urlopen(srv.uri + "/v1/metrics") as r:
            assert "text/plain" in r.headers.get("Content-Type", "")
            text = r.read().decode()
        for needle in (
            "presto_queries_total", "presto_qcache_hits_total",
            "presto_breakers_open_count", "presto_kernel_compiles_total",
            "presto_resource_group_running",
        ):
            assert needle in text
    finally:
        srv.stop()


# -- NodeStats cumulative output accounting ------------------------------


def test_node_stats_tracks_cumulative_and_peak_bytes():
    from presto_tpu.exec.stats import NodeStats, StatsCollector

    coll = StatsCollector(sync_counts=True)
    node = object()
    coll.record(node, 0.01, 1, 1, out_bytes=100)
    coll.record(node, 0.01, 1, 1, out_bytes=300)
    coll.record(node, 0.01, 1, 1, out_bytes=50)
    s = coll.lookup(node)
    assert s.out_bytes == 50  # last call: the live-footprint input
    assert s.out_bytes_total == 450
    assert s.out_bytes_peak == 300
    line = s.line()
    assert "Σ" in line and "peak" in line
    # single-dispatch nodes keep the terse rendering
    assert "Σ" not in NodeStats(calls=1, out_bytes=10,
                                out_bytes_total=10).line()
