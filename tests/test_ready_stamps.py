"""Ready stamps (obs/span.py `sent`, docs/observability.md): a span
learns WHEN what it sent was done, from a watcher thread a queue, without
the served thread ever blocking.

(a) the mechanism, on hand-made "arrays" whose `block_until_ready` waits
    until the test lets go: order within a queue, the two queues apart,
    the streamed scan's `upload_s` / `link_idle_s` / `inflight_peak_bytes`,
    a raising array, nothing held past readiness, many threads at once;
(b) `Trace.device_spans` on a hand-built tree;
(c) a served Q3 (every operator stamped, in execution order, the
    device-side spans tile the statement) and a streamed Q6 (an entry a
    batch); EXPLAIN ANALYZE prints the device-side span a node;
(d) `PRESTO_TPU_TRACE=0`: no watcher thread, no stamp, the same rows;
(e) the six per-layer metrics that read the stamps, on a hand-built
    store; `obs/kernelprof.py` is gone.
"""

import gc
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import weakref

import pytest

from presto_tpu.connectors import tpch
from presto_tpu.connectors.tpch_device import DeviceTpchCatalog
from presto_tpu.obs import span as obs_span
from presto_tpu.obs.span import TRACES, Trace, TraceStore
from presto_tpu.server import Client, CoordinatorServer
from presto_tpu.session import Session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SF = 0.01
Q6 = {"year": 1994, "discount": 6, "quantity": 24}
Q3 = {"segment": "BUILDING", "date": "1995-03-15"}
LIFECYCLE = {"statement", "submit", "queued", "query", "plan", "execute",
             "rows"}


def sql_of(qid: str, params: dict) -> str:
    with open(os.path.join(BENCH, "sql", qid + ".sql")) as f:
        return f.read().format(**params)


class Gated:
    """An "array" that is ready when the test says so."""

    def __init__(self, ready=False, error=None):
        self.gate = threading.Event()
        self.error = error
        if ready:
            self.gate.set()

    def block_until_ready(self):
        assert self.gate.wait(30.0), "the test never let go"
        if self.error is not None:
            raise self.error
        return self


def stamped(span, key="ready_at", timeout=10.0):
    """Wait for the watcher to write `key` onto `span`."""
    deadline = time.monotonic() + timeout
    while key not in span.attrs:
        assert time.monotonic() < deadline, (key, span.attrs)
        time.sleep(0.001)
    return span.attrs[key]


@pytest.fixture
def trace():
    assert obs_span.settle()  # nothing of an earlier test in flight
    assert obs_span.current() is None
    t = Trace()
    yield t
    obs_span.release()


# -- (a) the mechanism -------------------------------------------------------


def test_stamps_keep_the_order_of_their_queue(trace):
    spans, arrays = [], []
    for i in range(4):
        span = trace.enter(f"op{i}")
        arrays.append(Gated())
        t_before = time.time()
        obs_span.sent([arrays[-1]], "device")
        assert time.time() - t_before < 0.5  # never blocks
        trace.leave(span)
        spans.append(span)
    assert all("ready_at" not in s.attrs for s in spans)
    # let go in the WRONG order: entries still finish first-in first-out
    for a in reversed(arrays[1:]):
        a.gate.set()
    time.sleep(0.05)
    assert all("ready_at" not in s.attrs for s in spans)
    arrays[0].gate.set()
    assert obs_span.settle()
    ready = [s.attrs["ready_at"] for s in spans]
    assert ready == sorted(ready)
    for s, earlier in zip(spans[1:], spans):
        assert s.attrs["ready_after"] == earlier.attrs["ready_at"]
        assert s.attrs["handed_at"] <= s.attrs["ready_at"]
        assert s.attrs["ready_queue"] == "device"
    # a span had closed by the time its stamp came
    assert all(s.end <= s.attrs["ready_at"] for s in spans)


def test_the_two_queues_do_not_wait_for_each_other(trace):
    op = trace.enter("Join")
    compute = Gated()
    obs_span.sent([compute], "device")
    trace.leave(op)
    scan = trace.enter("TableScan")
    obs_span.sent([Gated(ready=True)], "link", nbytes=10)
    trace.leave(scan)
    copied = stamped(scan)  # while the device's entry still waits
    assert "ready_at" not in op.attrs
    assert scan.attrs["ready_queue"] == "link"
    compute.gate.set()
    assert obs_span.settle()
    assert op.attrs["ready_at"] >= copied


@pytest.mark.parametrize("ahead,peak", [(0, 100), (2, 300)])
def test_link_stamps_split_the_stream_between_link_and_host(
    trace, ahead, peak
):
    """A host that hands batch k over while batches k-1 .. k-`ahead`
    are still on the link."""
    scan = trace.enter("TableScan")
    batches = [Gated() for _ in range(6)]
    for k, batch in enumerate(batches):
        obs_span.sent([batch], "link", nbytes=100)
        if k >= ahead:
            time.sleep(0.01)  # the link's time for batch k - ahead
            batches[k - ahead].gate.set()
            assert stamped(scan, "uploads") >= 1
            while scan.attrs["uploads"] < k - ahead + 1:
                time.sleep(0.001)
            time.sleep(0.005)  # the host's time before the next batch
    trace.leave(scan)
    for batch in batches:
        batch.gate.set()
    assert obs_span.settle()
    a = scan.attrs
    assert a["uploads"] == 6
    assert a["inflight_peak_bytes"] == peak
    assert a["upload_s"] + a["link_idle_s"] == pytest.approx(
        a["ready_at"] - a["handed_at"], abs=1e-6
    )
    assert a["upload_s"] >= 6 * 0.01 * 0.9 if ahead == 0 else a["upload_s"] > 0
    if ahead == 0:
        # the link had nothing to copy while the host slept between
        assert a["link_idle_s"] >= 5 * 0.005 * 0.9
    else:
        # batch k was handed over before batch k-1 was ready
        assert a["link_idle_s"] == pytest.approx(0.0, abs=2e-3)
    assert obs_span._WATCHERS["link"]._inflight == 0


def test_a_raising_array_stamps_ready_error_and_the_watcher_lives(trace):
    bad = trace.enter("Filter")
    obs_span.sent(
        [Gated(ready=True, error=RuntimeError("Array has been deleted"))],
        "device",
    )
    trace.leave(bad)
    good = trace.enter("Project")
    obs_span.sent([Gated(ready=True)], "device")
    trace.leave(good)
    assert obs_span.settle()
    assert bad.attrs["ready_error"] == "RuntimeError: Array has been deleted"
    assert "ready_at" not in bad.attrs
    assert "ready_at" in good.attrs
    # the failed entry gives no device-side span and breaks no reader
    assert [s.name for s, _ in trace.device_spans()] == ["Project"]


def test_nothing_is_held_past_readiness(trace):
    span = trace.enter("Aggregate")
    array = Gated(ready=True)
    ref = weakref.ref(array)
    obs_span.sent([array], "device")
    trace.leave(span)
    del array
    assert obs_span.settle()
    gc.collect()
    assert ref() is None


def test_no_open_span_no_entry():
    assert obs_span.settle()
    assert obs_span.current() is None
    array = Gated()  # would hang a watcher for 30 s if it were taken
    ref = weakref.ref(array)
    obs_span.sent([array], "device")
    obs_span.sent([array], "link", nbytes=1 << 30)
    del array
    gc.collect()
    assert ref() is None
    assert obs_span.settle(1.0)


def test_many_threads_hand_over_at_once():
    """More threads than cores, a short switch interval: every span is
    stamped once, in-flight bytes return to zero."""
    assert obs_span.settle()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    traces = [Trace() for _ in range(16)]
    spans = [[] for _ in traces]

    def work(i):
        t = traces[i]
        for k in range(50):
            span = t.enter("TableScan")
            obs_span.sent([Gated(ready=True)], "link", nbytes=7)
            obs_span.sent([Gated(ready=True)], "link", nbytes=7)
            t.leave(span)
            spans[i].append(span)
            op = t.enter("Join")
            obs_span.sent([Gated(ready=True)], "device")
            t.leave(op)
            spans[i].append(op)
        obs_span.release()

    try:
        threads = [
            threading.Thread(target=work, args=(i,)) for i in range(16)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60.0)
        assert not any(th.is_alive() for th in threads)
        assert obs_span.settle(30.0)
    finally:
        sys.setswitchinterval(interval)
    for mine in spans:
        assert len(mine) == 100
        for s in mine:
            assert "ready_at" in s.attrs and "ready_error" not in s.attrs
            if s.name == "TableScan":
                assert s.attrs["uploads"] == 2
                # nothing holds a thread back: at most all that was sent
                assert 7 <= s.attrs["inflight_peak_bytes"] <= 7 * 1600
    assert obs_span._WATCHERS["link"]._inflight == 0
    watchers = [
        t.name for t in threading.enumerate()
        if t.name.startswith("presto-ready-")
    ]
    assert sorted(watchers) == ["presto-ready-device", "presto-ready-link"]


# -- (b) device-side spans on a hand-built tree ------------------------------


def hand_operator(trace, name, parent, start, end, ready=None, after=None,
                  **attrs):
    span = trace.begin(name, parent=parent, start=start, **attrs)
    span.end = end
    trace.finish(span)
    if ready is not None:
        span.attrs.update(
            ready_queue="device", handed_at=end, ready_at=ready
        )
        if after is not None:
            span.attrs["ready_after"] = after
    return span


def test_device_spans_on_a_hand_built_tree():
    """Join(build, probe) with the build side first, a dynamic filter
    published between the two, the device a little behind the host."""
    t = Trace()
    execute = hand_operator(t, "execute", None, 10.0, 19.5)
    join = hand_operator(t, "Join", execute, 10.0, 19.0, ready=20.0,
                         after=17.0)
    build = hand_operator(t, "Filter", join, 10.0, 12.0, ready=13.0,
                          after=11.5)
    scan = hand_operator(t, "TableScan", build, 10.0, 11.0, ready=11.5,
                         after=3.0)  # the statement before this one
    load = hand_operator(t, "table_load", scan, 10.0, 10.75)
    probe = hand_operator(t, "TableScan", join, 15.0, 16.0, ready=17.0,
                          after=13.0)
    failed = hand_operator(t, "Project", execute, 19.0, 19.5)
    failed.attrs.update(ready_queue="device", ready_error="boom")
    got = {s.span_id: d for s, d in t.device_spans()}
    assert got == {
        # ready minus its blocking child's leave (not the old statement)
        scan.span_id: pytest.approx(11.5 - 10.75),
        build.span_id: pytest.approx(13.0 - 11.5),
        # a leaf that began after the device was free: from its start
        probe.span_id: pytest.approx(17.0 - 15.0),
        # its own stretch, and the one between its children
        join.span_id: pytest.approx((20.0 - 17.0) + (15.0 - 13.0)),
    }
    assert load.span_id not in got and failed.span_id not in got
    # they tile the queue from the first node's work to the last stamp
    assert sum(got.values()) == pytest.approx(20.0 - 10.75)
    # in the order handed over
    assert [s.span_id for s, _ in t.device_spans()] == [
        scan.span_id, build.span_id, probe.span_id, join.span_id,
    ]


# -- (c) statements -----------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    session = Session(DeviceTpchCatalog(sf=SF), result_cache=False)
    server = CoordinatorServer(session, port=0).start()
    try:
        yield session, Client(server.uri, timeout=600.0)
    finally:
        server.stop()


def test_served_q3_every_operator_is_stamped_in_execution_order(served):
    _session, client = served
    sql = sql_of("q3", Q3)
    client.execute(sql)  # warm: the second run compiles nothing
    TRACES.reset()
    client.execute(sql)
    assert obs_span.settle()
    (trace,) = TRACES.recent()
    operators = [s for s in trace.spans() if s.name not in LIFECYCLE]
    assert {s.name for s in operators} >= {
        "Join", "Filter", "Aggregate", "TableScan", "TopN",
    }
    for s in operators:
        assert s.attrs["ready_queue"] == "device", s.name
        assert "ready_error" not in s.attrs
        # handed over where the span was left, ready no earlier
        assert 0.0 <= s.end - s.attrs["handed_at"] < 0.05
        assert s.attrs["handed_at"] <= s.attrs["ready_at"]
    # the stamps rise in execution order: the order the spans were left
    by_leave = sorted(operators, key=lambda s: s.end)
    ready = [s.attrs["ready_at"] for s in by_leave]
    assert ready == sorted(ready)
    device = trace.device_spans()
    assert [s.span_id for s, _ in device] == [s.span_id for s in by_leave]
    assert all(d >= 0.0 for _, d in device)
    execute = next(s for s in trace.spans() if s.name == "execute")
    total = sum(d for _, d in device)
    # they do not overlap: together no more than the statement's stretch
    # of the queue, which ends when the watcher saw the last output
    assert total <= ready[-1] - execute.start + 1e-9
    assert total <= execute.wall_s + 0.25
    assert total > 0.0


def test_explain_analyze_prints_a_device_side_span_a_node(served):
    session, _client = served
    text = session.explain_analyze(sql_of("q6", Q6))
    lines = text.splitlines()
    nodes = [ln for ln in lines if ln.lstrip().startswith("- ")]
    assert nodes and all("B, device-side " in ln for ln in nodes)
    footer = next(ln for ln in lines if ln.startswith("-- kernels:"))
    assert "compile +" in footer
    assert f"over {len(nodes)} operators" in footer


def test_streamed_q6_has_an_entry_a_batch():
    catalog = tpch.TpchCatalog(sf=SF)
    session = Session(
        catalog, result_cache=False, streaming=True, batch_rows=4096,
        memory_budget=64 << 20,
    )
    res = session.query(sql_of("q6", Q6))
    rows = res.rows()
    assert obs_span.settle()
    trace = TRACES.get(res.trace_id)
    (scan,) = [s for s in trace.spans() if s.name == "TableScan"]
    a = scan.attrs
    assert a["batches"] == 15
    assert a["uploads"] == a["batches"]
    assert a["ready_queue"] == "link" and "ready_error" not in a
    assert a["upload_s"] + a["link_idle_s"] == pytest.approx(
        a["ready_at"] - a["handed_at"], abs=1e-6
    )
    batch_bytes = 4096 * (8 + 8 + 8 + 4)  # Q6 names 28 B of a row
    assert batch_bytes <= a["inflight_peak_bytes"] <= 15 * batch_bytes
    # the streamed driver's spans have no device-queue stamp
    assert trace.device_spans() == []
    resident = Session(catalog, result_cache=False).query(sql_of("q6", Q6))
    assert rows == resident.rows()


# -- (d) PRESTO_TPU_TRACE=0 ---------------------------------------------------

_OFF = """
import json, sys, threading
from presto_tpu.connectors import tpch
from presto_tpu.obs import span as obs_span
from presto_tpu.session import Session
sql = sys.argv[1]
catalog = tpch.TpchCatalog(sf=0.01)
rows = []
for kwargs in ({}, dict(streaming=True, batch_rows=4096,
                        memory_budget=64 << 20)):
    res = Session(catalog, result_cache=False, **kwargs).query(sql)
    assert res.trace_id is None
    rows.append([[str(v) for v in r] for r in res.rows()])
print(json.dumps({
    "rows": rows, "watchers": sorted(obs_span._WATCHERS),
    "threads": [t.name for t in threading.enumerate()],
    "traces": len(obs_span.TRACES.recent()),
}))
"""


def test_trace_off_starts_no_thread_and_leaves_the_same_rows():
    sql = sql_of("q6", Q6)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PRESTO_TPU_TRACE="0",
               JAX_ENABLE_COMPILATION_CACHE="0")
    out = subprocess.run(
        [sys.executable, "-c", _OFF, sql], env=env, cwd=ROOT,
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    off = json.loads(out.stdout.strip().splitlines()[-1])
    assert off["watchers"] == [] and off["traces"] == 0
    assert not [n for n in off["threads"] if n.startswith("presto-ready")]
    on = Session(tpch.TpchCatalog(sf=SF), result_cache=False).query(sql)
    want = [[str(v) for v in r] for r in on.rows()]
    assert off["rows"] == [want, want]


# -- (e) the benchmark's readers ---------------------------------------------

READERS = (
    "join_device_ms", "filter_device_ms", "aggregate_device_ms",
    "stream_upload_ms", "stream_link_idle_ms", "stream_inflight_peak_mb",
)


def reader(name):
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
    def __init__(self, opened_s):
        self.records = [{"epoch_ns": int(opened_s * 1e9)}]


def hand_statement(store, at, scale=1.0, streamed=None, stamps=True):
    """A statement's tree on the test's own clock: Aggregate over
    Join(Filter(TableScan), TableScan), every node's output ready
    `scale` x 10 ms after the one before; `streamed` = the link stamps
    of its first scan."""
    trace = store.new_trace(query_id="q_1")
    top = hand_operator(trace, "statement", None, at, at + 1.0)
    execute = hand_operator(trace, "execute", top, at, at + 0.9)
    step = 0.010 * scale

    def op(name, parent, k, **attrs):
        ready = at + k * step if stamps else None
        return hand_operator(
            trace, name, parent, at, at + k * step - 0.001, ready=ready,
            after=at + (k - 1) * step if k > 1 else None, **attrs
        )

    agg = op("Aggregate", execute, 5)
    join = op("Join", agg, 4)
    filt = op("Filter", join, 2)
    scan = op("TableScan", filt, 1)
    op("TableScan", join, 3)
    if streamed is not None:
        scan.attrs.update(streamed)
    return trace


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_hand_built_store(monkeypatch, name):
    store = TraceStore()
    monkeypatch.setattr(obs_span, "TRACES", store)
    link = dict(uploads=58, upload_s=0.150, link_idle_s=0.120,
                inflight_peak_bytes=46_137_344)
    hand_statement(store, 50.0, 9.0, dict(link, upload_s=9.0))  # too early
    hand_statement(store, 100.0, 1.0, link)
    hand_statement(store, 102.0, 3.0, dict(
        link, upload_s=0.170, link_idle_s=0.260,
        inflight_peak_bytes=92_274_688,
    ))
    # a resident statement: its scan has a table_load's `upload_s`
    # folded in, but no batch was stamped
    hand_statement(store, 104.0, 1.0, dict(upload_s=5.0))
    want = {
        # every node's stretch is one step: 10 ms and 30 ms, twice 10
        "join_device_ms": (10.0 + 30.0 + 10.0) / 3,
        "filter_device_ms": (10.0 + 30.0 + 10.0) / 3,
        "aggregate_device_ms": (10.0 + 30.0 + 10.0) / 3,
        "stream_upload_ms": (150.0 + 170.0) / 2,
        "stream_link_idle_ms": (120.0 + 260.0) / 2,
        "stream_inflight_peak_mb": 92.274688,
    }[name]
    assert reader(name).compute(HandRun(100.0)) == pytest.approx(want)
    # nothing since the window opened, or a program that stamps nothing
    assert reader(name).compute(HandRun(200.0)) is None
    bare = TraceStore()
    monkeypatch.setattr(obs_span, "TRACES", bare)
    hand_statement(bare, 100.0, stamps=False)
    assert reader(name).compute(HandRun(100.0)) is None
    if name.endswith("_device_ms"):
        # a program from before the stamps: its Trace has no reader
        monkeypatch.delattr(Trace, "device_spans")
        assert reader(name).compute(HandRun(100.0)) is None


def test_benchmark_json_names_the_six_with_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["moves"] == "stmt_ms" and m["better"] == "lower"
        assert set(m["workloads"]) <= cells
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py")
        )
    assert by_name["stream_upload_ms"]["workloads"] == ["sf10s.scan_agg"]
    assert "sf10.join" in by_name["filter_device_ms"]["workloads"]


def test_kernelprof_is_gone_and_compiles_come_from_the_listener():
    assert importlib.util.find_spec("presto_tpu.obs.kernelprof") is None
    mentions = subprocess.run(
        ["grep", "-rl", "kernelprof\\|KERNEL_PROFILE", "--include=*.py",
         os.path.join(ROOT, "presto_tpu")],
        capture_output=True, text=True,
    ).stdout.split()
    assert mentions == []
    import jax
    import jax.numpy as jnp

    from presto_tpu.obs.export import _metrics_kernel_producer

    t = TRACES.new_trace()  # the listener is on from the first trace
    before = obs_span.compile_totals()
    span = t.enter("execute")
    jax.jit(lambda x: x * 3 + before[0])(jnp.arange(5)).block_until_ready()
    t.leave(span)
    after = obs_span.compile_totals()
    assert after[0] - before[0] == span.attrs["compiles"] >= 1
    assert after[1] - before[1] == pytest.approx(span.attrs["compile_s"])
    samples = {name: value for name, _k, _l, value in
               _metrics_kernel_producer()}
    assert samples["presto_kernel_compiles_total"] >= after[0]
    assert set(samples) == {
        "presto_kernel_compiles_total",
        "presto_kernel_compile_seconds_total",
    }
