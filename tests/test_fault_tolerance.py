"""Fault-tolerant distributed execution (docs/fault-tolerance.md).

Three layers under test:
* Kernel — KernelCircuitBreaker (exec/breaker.py): a faulting kernel
  degrades to its XLA fallback with correct results, the breaker opens,
  the faulting kernel is not re-attempted until the recovery window, and
  a successful half-open probe closes it again.
* Worker — structured retryable-vs-fatal failure classification
  (server/worker.py), 503 {"retry": true} handling in the REST client.
* Coordinator — per-task retry onto alternate workers, blacklisting with
  recovery re-admission and worker up/down events, and an end-to-end
  TPC-H subset against fault_rate=0.3 workers completing with
  oracle-correct results.
"""

import json
import threading
import time

import pytest

from presto_tpu.connectors.tpch import TpchCatalog
from presto_tpu.exec.breaker import (
    BREAKERS,
    CLOSED,
    HALF_OPEN,
    OPEN,
    KernelCircuitBreaker,
)
from presto_tpu.server.cluster import (
    HttpClusterSession,
    HttpScheduler,
    NodeManager,
    TaskFailure,
)
from presto_tpu.server.worker import WorkerServer, _classify_failure
from presto_tpu.session import Session

SF = 0.002


@pytest.fixture(autouse=True)
def _reset_breakers():
    BREAKERS.reset()
    yield
    BREAKERS.reset()


# -- kernel circuit breaker state machine ------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_breaker_opens_blocks_and_recovers():
    clock = FakeClock()
    br = KernelCircuitBreaker(
        "k", failure_threshold=2, recovery_timeout=60.0, clock=clock
    )
    assert br.state == CLOSED and br.allow()
    br.record_failure("boom 1")
    assert br.state == CLOSED and br.allow()  # below threshold
    br.record_failure("boom 2")
    assert br.state == OPEN and not br.allow()  # threshold reached
    clock.t += 30
    assert not br.allow()  # still inside the recovery window
    clock.t += 31
    assert br.state == HALF_OPEN and br.allow()  # probe admitted
    br.record_failure("probe failed")
    assert br.state == OPEN and not br.allow()  # re-armed window
    clock.t += 61
    assert br.allow()
    br.record_success()
    assert br.state == CLOSED and br.consecutive_failures == 0


def test_breaker_success_resets_streak():
    br = KernelCircuitBreaker("k", failure_threshold=3)
    br.record_failure("a")
    br.record_failure("b")
    br.record_success()
    br.record_failure("c")
    assert br.state == CLOSED  # streak broken: 2 + 1 non-consecutive


def test_registry_snapshot_and_env_threshold(monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_BREAKER_THRESHOLD", "1")
    BREAKERS.reset()
    assert BREAKERS.allow("pallas_groupby")
    BREAKERS.record_failure("pallas_groupby", "Mosaic lowering failed")
    snap = BREAKERS.snapshot()["pallas_groupby"]
    assert snap["state"] == "open" and snap["total_failures"] == 1
    assert "Mosaic" in snap["last_error"]
    assert not BREAKERS.allow("pallas_groupby")
    monkeypatch.setenv("PRESTO_TPU_BREAKER_DISABLE", "1")
    assert BREAKERS.allow("pallas_groupby")  # kill switch


# -- pallas group-by: fault -> fallback correct -> breaker open --------------


@pytest.mark.parametrize(
    "where", ["maybe_grouped_aggregate", "_pallas_partials"]
)
def test_pallas_fault_degrades_to_xla_fallback(monkeypatch, where):
    """Acceptance: with a forced kernel fault in the Pallas group-by
    path, an aggregation query completes via the XLA fallback with the
    breaker reported open in the exec/stats.py surface — and the
    faulting kernel is NOT re-attempted while the breaker is open. The
    fault is the whole path's, or the kernel call's deep inside the
    fused program: either way it comes out of the program's TRACE."""
    from presto_tpu.ops import pallas_groupby as pg

    calls = []

    def faulting(*args, **kwargs):
        calls.append(1)
        raise RuntimeError("Mosaic lowering failed (injected fault)")

    monkeypatch.setattr(pg, where, faulting)
    sess = Session(TpchCatalog(sf=SF), pallas_groupby=True)
    sql = (
        "select o_orderpriority, count(*) c, sum(o_totalprice) s "
        "from orders group by o_orderpriority order by o_orderpriority"
    )
    got = sess.query(sql).rows()
    want = Session(TpchCatalog(sf=SF)).query(sql).rows()
    assert got == want  # fallback produced the oracle answer
    assert len(calls) == 1

    from presto_tpu.exec.stats import (
        kernel_breaker_lines,
        kernel_breaker_snapshot,
    )

    snap = kernel_breaker_snapshot()["pallas_groupby"]
    assert snap["state"] == "open" and snap["total_failures"] == 1
    assert any("pallas_groupby: open" in ln for ln in kernel_breaker_lines())

    # open breaker: the faulting kernel is not re-attempted
    got2 = sess.query(sql).rows()
    assert got2 == want and len(calls) == 1

    # EXPLAIN ANALYZE surfaces the degraded path
    report = sess.explain_analyze(sql)
    assert "breaker pallas_groupby: open" in report


def test_join_and_sort_breakers_degrade_without_wrong_results():
    """Open join_probe / fused_sort breakers force the searchsorted probe
    and the argsort composition — results must stay oracle-correct."""
    sql = (
        "select c_custkey, count(o_orderkey) n from customer, orders "
        "where c_custkey = o_custkey group by c_custkey "
        "order by n desc, c_custkey limit 5"
    )
    want = Session(TpchCatalog(sf=SF)).query(sql).rows()
    for name in ("join_probe", "fused_sort"):
        BREAKERS.get(name).record_failure("forced open")
    assert not BREAKERS.allow("join_probe")
    got = Session(TpchCatalog(sf=SF)).query(sql).rows()
    assert got == want


def test_kernel_guard_falls_back_per_call_even_when_breaker_cannot_open(
    monkeypatch,
):
    """A fault on the experimental path must degrade THIS call to the
    fallback even when the breaker is prevented from opening
    (PRESTO_TPU_BREAKER_DISABLE=1) — not fail the query."""
    from presto_tpu.connectors.memory import MemoryCatalog
    from presto_tpu.exec.executor import Executor

    monkeypatch.setenv("PRESTO_TPU_BREAKER_DISABLE", "1")
    ex = Executor(MemoryCatalog({}), jit=False)

    def make_fn():
        def fn():
            if BREAKERS.allow("guard_test"):  # trace-time path choice
                raise RuntimeError("Mosaic fault (injected)")
            return "fallback result"

        return fn

    assert ex._kernel_guarded("guard_test", "guard", "k", make_fn) == "fallback result"
    # disabled registry never opens, yet the call degraded per-call
    assert BREAKERS.allow("guard_test")


def test_blacklist_not_laundered_through_probe_failure():
    """BLACKLISTED -> (probes fail) must NOT become FAILED and then get
    re-admitted by the next healthy probe before the recovery window."""
    w = WorkerServer(TpchCatalog(sf=SF)).start()
    nodes = NodeManager(
        [w.uri], interval=3600, failure_threshold=1,
        task_failure_threshold=1, blacklist_recovery=60.0,
    )
    nodes.record_task_failure(w.uri, "boom")
    assert nodes.workers[w.uri]["state"] == "BLACKLISTED"
    w.stop()  # heartbeats now fail
    nodes.probe_all()
    assert nodes.workers[w.uri]["state"] == "BLACKLISTED"  # not FAILED
    # a healthy probe before the recovery window keeps it drained
    w2 = WorkerServer(TpchCatalog(sf=SF)).start()
    try:
        nodes.workers[w2.uri] = dict(
            nodes.workers[w.uri], blacklisted_at=time.time()
        )
        del nodes.workers[w.uri]
        nodes.probe_all()
        assert nodes.workers[w2.uri]["state"] == "BLACKLISTED"
    finally:
        w2.stop()


# -- worker failure classification -------------------------------------------


def test_classify_failure_retryable_vs_fatal():
    from presto_tpu.server.worker import QueryKilledError

    assert _classify_failure(RuntimeError("injected fault on worker x"))[
        "retryable"
    ]
    kernel = _classify_failure(
        RuntimeError("Mosaic lowering failed: INTERNAL: bad vreg")
    )
    assert kernel["retryable"] and kernel["kernelFault"]
    assert not _classify_failure(
        QueryKilledError("Query killed: the cluster ran out of memory")
    )["retryable"]
    assert not _classify_failure(MemoryError("worker memory exhausted"))[
        "retryable"
    ]


# -- REST client: 503 retry + transient connection retry ---------------------


class _FlakyHandler:
    """Tiny HTTP server: first N requests answer 503 {"retry": true}
    (or drop the connection), then 200 with a terminal payload."""

    def __init__(self, fail_times, mode="503"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.requests = 0
        outer = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                outer.requests += 1
                if outer.requests <= fail_times:
                    if mode == "drop":
                        self.connection.close()
                        return
                    body = json.dumps({"retry": True}).encode()
                    self.send_response(503)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                body = json.dumps({"ok": True}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.uri = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_client_retries_503_retry_true():
    from presto_tpu.server.client import Client

    srv = _FlakyHandler(fail_times=2, mode="503")
    try:
        c = Client(srv.uri, backoff_base=0.01)
        assert c._request("GET", f"{srv.uri}/x") == {"ok": True}
        assert srv.requests == 3
    finally:
        srv.stop()


def test_client_503_retries_are_bounded():
    from presto_tpu.server.client import Client, QueryError

    srv = _FlakyHandler(fail_times=10_000, mode="503")
    try:
        c = Client(srv.uri, max_retries=3, backoff_base=0.01)
        with pytest.raises(QueryError, match="503"):
            c._request("GET", f"{srv.uri}/x")
        assert srv.requests == 4  # initial + 3 retries
    finally:
        srv.stop()


def test_client_retries_transient_disconnect_once():
    from presto_tpu.server.client import Client, QueryError

    srv = _FlakyHandler(fail_times=1, mode="drop")
    try:
        c = Client(srv.uri, backoff_base=0.01)
        assert c._request("GET", f"{srv.uri}/x") == {"ok": True}
    finally:
        srv.stop()
    # a dead server (connection refused) fails after the single retry
    c = Client(srv.uri, backoff_base=0.01)
    with pytest.raises(QueryError, match="connection failed"):
        c._request("GET", f"{srv.uri}/x")


# -- node manager: blacklist + recovery + events -----------------------------


def test_blacklist_drains_and_readmits_with_events():
    from presto_tpu.server.events import EventBus, EventListener

    seen = []

    class Recorder(EventListener):
        def worker_state_changed(self, ev):
            seen.append((ev.uri, ev.state))

    w = WorkerServer(TpchCatalog(sf=SF)).start()
    try:
        nodes = NodeManager(
            [w.uri], interval=3600, task_failure_threshold=2,
            blacklist_recovery=0.05, event_bus=EventBus([Recorder()]),
        )
        nodes.record_task_failure(w.uri, "injected fault")
        assert nodes.active_workers() == [w.uri]  # below threshold
        nodes.record_task_failure(w.uri, "injected fault")
        assert nodes.active_workers() == []
        assert nodes.workers[w.uri]["state"] == "BLACKLISTED"
        assert (w.uri, "BLACKLISTED") in seen
        # a success in between resets the streak
        nodes2 = NodeManager([w.uri], interval=3600, task_failure_threshold=2)
        nodes2.record_task_failure(w.uri)
        nodes2.record_task_success(w.uri)
        nodes2.record_task_failure(w.uri)
        assert nodes2.active_workers() == [w.uri]
        # recovery: healthy probe after the penalty window re-admits
        time.sleep(0.06)
        nodes.probe_all()
        assert nodes.active_workers() == [w.uri]
        assert (w.uri, "ACTIVE") in seen
    finally:
        w.stop()


def test_task_status_deadline_names_worker_task_attempt():
    nodes = NodeManager(["http://127.0.0.1:1"], interval=3600)
    sched = HttpScheduler(
        TpchCatalog(sf=SF), nodes, status_deadline=0.3, status_timeout=0.2
    )
    with pytest.raises(TaskFailure) as exc_info:
        sched._task_status("http://127.0.0.1:1", "t_9", attempt=2)
    msg = str(exc_info.value)
    assert "t_9" in msg and "127.0.0.1:1" in msg and "attempt 2" in msg
    assert exc_info.value.retryable


# -- end-to-end: TPC-H subset survives fault_rate=0.3 ------------------------


# the TPC-H subset: IDENTICAL SQL + scale factor to test_server.py's
# CLUSTER_QUERIES / cluster fixture, so tier-1 (one pytest process, one
# XLA compile cache) compiles each fragment pipeline once across the
# two modules instead of twice
E2E_SF = 0.01
FT_QUERIES = [
    # two-stage aggregation over a repartition exchange
    "select l_returnflag, l_linestatus, sum(l_quantity) q, "
    "avg(l_extendedprice) a, count(*) n from lineitem "
    "where l_shipdate <= date '1998-09-02' "
    "group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus",
    # broadcast join + aggregation + topN (TPC-H Q3 shape — the round-5
    # wedge was this query)
    "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as rev "
    "from customer, orders, lineitem "
    "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey "
    "and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' "
    "group by l_orderkey order by rev desc limit 10",
    # global aggregate
    "select count(*), sum(o_totalprice) from orders",
    # distinct + sort
    "select distinct o_orderpriority from orders order by o_orderpriority",
]


def test_fault_trace_merged_span_tree():
    """Observability acceptance (docs/observability.md): under injected
    faults the coordinator's merged span tree contains the FAILED
    attempt (status="error", recorded on the worker before the fault
    fired), the retry as a SIBLING span, and no orphans; phase walls
    account for the query wall; the worker serves /v1/metrics."""
    import urllib.request

    from presto_tpu.obs.span import TRACES

    workers = [
        WorkerServer(TpchCatalog(sf=E2E_SF), fault_rate=0.3).start()
        for _ in range(2)
    ]
    nodes = NodeManager(
        [w.uri for w in workers], interval=3600,
        task_failure_threshold=50,
    )
    sess = HttpClusterSession(
        TpchCatalog(sf=E2E_SF), nodes,
        scheduler_opts={
            "backoff_base": 0.01, "backoff_cap": 0.1,
            "max_task_retries": 4, "max_query_retries": 4,
        },
    )
    try:
        trace = None
        for i in range(12):  # 30% fault rate: a faulted-but-recovered
            # run is statistically certain within the bound. The
            # predicate is vacuously true but textually distinct per
            # iteration, so the coordinator result cache (which keys on
            # the SQL) cannot short-circuit the dispatch we need to
            # fault.
            res = sess.query(
                "select count(*), sum(o_totalprice) from orders "
                f"where o_orderkey > -{i + 1}"
            )
            assert res.trace_id is not None
            tr = TRACES.get(res.trace_id)
            assert tr is not None
            if any(s.status == "error" for s in tr.spans()):
                trace = tr
                break
        assert trace is not None, "no faulted query observed"
        spans = trace.spans()
        by_id = {s.span_id: s for s in spans}
        # one tree: every span (coordinator AND worker) shares the id,
        # worker task spans actually merged, nothing dangling
        assert all(s.trace_id == trace.trace_id for s in spans)
        assert any(s.name.startswith("task ") for s in spans)
        assert trace.orphans() == []
        errors = [s for s in spans if s.status == "error"]
        assert errors
        # the retry rides as a sibling subtree: for a failed worker task
        # its dispatch span has a later-posted ok sibling under the same
        # stage; a failed attempt/dispatch has an ok sibling directly
        def _has_retry_sibling(e):
            node = by_id.get(e.parent_id) if e.name.startswith("task ") else e
            if node is None:
                return False
            return any(
                s.parent_id == node.parent_id
                and s.span_id != node.span_id
                and s.status == "ok" and s.start >= node.start
                for s in spans
            )
        assert any(_has_retry_sibling(e) for e in errors)
        # phase spans (root's direct children) account for the wall
        root = trace.root()
        assert root is not None and root.wall_s > 0
        kid_sum = sum(k.wall_s for k in trace.children(root.span_id))
        assert abs(kid_sum - root.wall_s) <= 0.1 * root.wall_s
        # the worker role serves the unified metrics plane
        with urllib.request.urlopen(workers[0].uri + "/v1/metrics") as r:
            assert "text/plain" in r.headers.get("Content-Type", "")
            text = r.read().decode()
        for needle in (
            "presto_qcache_hits_total", "presto_breakers_open_count",
            "presto_exchange_pages_total", "presto_kernel_compiles_total",
            "presto_worker_tasks_total",
        ):
            assert needle in text
    finally:
        for w in workers:
            w.stop()


def test_cluster_survives_fault_rate():
    """Acceptance: with fault_rate=0.3 on EVERY worker, the TPC-H subset
    completes with oracle-correct results, and the retries that made that
    possible are observable in scheduler stats."""
    workers = [
        WorkerServer(TpchCatalog(sf=E2E_SF), fault_rate=0.3).start()
        for _ in range(2)
    ]
    nodes = NodeManager(
        [w.uri for w in workers], interval=3600,
        # faults are random, not worker-specific: keep the cluster whole
        task_failure_threshold=50,
    )
    sess = HttpClusterSession(
        TpchCatalog(sf=E2E_SF), nodes,
        scheduler_opts={
            "backoff_base": 0.01, "backoff_cap": 0.1,
            "max_task_retries": 4, "max_query_retries": 4,
        },
    )
    oracle = Session(TpchCatalog(sf=E2E_SF))
    try:
        for sql in FT_QUERIES:
            assert sess.query(sql).rows() == oracle.query(sql).rows()
        stats = sess.scheduler.stats
        # 30% fault rate over dozens of tasks: statistically certain to
        # have needed retries; run singles until observed, bounded
        for _ in range(10):
            if stats.task_retries + stats.query_retries > 0:
                break
            assert sess.query(FT_QUERIES[2]).rows() == oracle.query(
                FT_QUERIES[2]
            ).rows()
        assert stats.task_retries + stats.query_retries > 0
        assert stats.tasks_failed > 0
        assert "injected fault" in stats.last_error
    finally:
        for w in workers:
            w.stop()
