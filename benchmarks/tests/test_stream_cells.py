"""The cells PR 35 added, `sf10s.scan_agg` and `sf10.join`: what the
one traffic generator makes of their mixes, and the three readers of
the streamed scan's spans on a hand-made trace.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`.
"""

import importlib.util
import os
import time

import pytest

import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("stream_batches_per_stmt", "stream_scan_ms", "stream_sink_ms")


def reader(name: str):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"layer_metrics_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scan_agg_full_is_four_and_four_sets_at_sf10():
    a = traffic.Mix("scan_agg_full", 2147483659)
    b = traffic.Mix("scan_agg_full", 2147483659)
    c = traffic.Mix("scan_agg_full", 7)
    sent = [(st.id, st.sql(i)) for st, i in (next(a) for _ in range(16))]
    assert sent == [(st.id, st.sql(i)) for st, i in (next(b) for _ in range(16))]
    assert [s for s, _ in sent[:4]] == ["q1_full", "q6_full"] * 2
    q1a, q6a = a.statements
    q1c, q6c = c.statements
    assert q1a.param_sets != q1c.param_sets  # the seed draws the DELTAs
    for q1 in (q1a, q1c):
        assert len(q1.param_sets) == 4
        assert len({p["delta"] for p in q1.param_sets}) == 4
        assert all(60 <= p["delta"] <= 120 for p in q1.param_sets)
    assert q6a.param_sets == q6c.param_sets  # a fixed pool
    old = next(
        st for st in traffic.Mix("scan_agg", 7).statements if st.id == "q6"
    )
    assert [
        {k: v for k, v in p.items() if k != "sf"} for p in q6a.param_sets
    ] == old.param_sets[:4]
    assert len(list(a.every())) == 8
    assert all(
        p["sf"] == 10 for st in a.statements for p in st.param_sets
    )
    # `sf` tells the reference the scale factor: the SQL is unchanged by it
    assert q1a.sql(0) == traffic.sql_template("q1").format(
        delta=q1a.param_sets[0]["delta"]
    )
    assert q6a.sql(0) == next(
        st for st in traffic.Mix("scan_agg", 7).statements if st.id == "q6"
    ).sql(0)
    assert "10" not in q1a.sql(0).replace(str(q1a.param_sets[0]["delta"]), "") \
        .replace("1998-12-01", "")
    assert a.spec["warmup_passes"] == 1
    assert a.spec["trace"] == {"seconds": 3.0, "min_statements": 2}


def test_join_full_sf10_is_join_fulls_two_sets_at_sf10():
    mix = traffic.Mix("join_full_sf10", 5)
    (st,) = mix.statements
    (old,) = traffic.Mix("join_full", 5).statements
    assert st.id == old.id == "q3_full"
    assert st.param_sets == [dict(p, sf=10.0) for p in old.param_sets]
    assert len(list(mix.every())) == 2
    assert [st.sql(i) for i in range(2)] == [old.sql(i) for i in range(2)]
    assert mix.spec["warmup_passes"] == 1
    assert mix.spec["trace"] == {"seconds": 3.0, "min_statements": 1}


class Run:
    def __init__(self, epoch_ns):
        self.records = [{"epoch_ns": epoch_ns}]


def hand_statement(store, t0, scans, agg_wall, scan_wall):
    """A served statement's tree as `exec/stream.py` leaves it: an
    `Aggregate` of `agg_wall` s over one `TableScan` span per entry of
    `scans` ({counter: value}; {} = a resident table's scan) of
    `scan_wall` s each."""
    trace = store.new_trace("q_hand")

    def closed(name, parent, start, wall, **attrs):
        span = trace.begin(name, parent=parent, start=start, **attrs)
        span.end = start + wall
        return trace.finish(span)

    root = closed("statement", None, t0, agg_wall + 0.004)
    query = closed("query", root, t0 + 0.001, agg_wall + 0.002)
    execute = closed("execute", query, t0 + 0.002, agg_wall + 0.001)
    agg = closed("Aggregate", execute, t0 + 0.002, agg_wall)
    for counters in scans:
        closed("TableScan", agg, t0 + 0.002, scan_wall, **counters)
    return trace


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_streamed_spans(name):
    from presto_tpu.obs import span as obs_span

    obs_span.TRACES.reset()
    t0 = time.time()
    assert reader(name).compute(Run(int(t0 * 1e9))) is None
    hand_statement(obs_span.TRACES, t0 + 1, [{}], 0.5, 0.1)
    assert reader(name).compute(Run(int(t0 * 1e9))) is None
    obs_span.TRACES.reset()


@pytest.mark.parametrize("name,want", [
    ("stream_batches_per_stmt", (58 + 58 + 3) / 2),
    ("stream_scan_ms", (700.0 + 400.0 + 10.0) / 2),
    # Aggregate self walls: 6.0 - 1.5 and 2.0 - (0.75 + 0.75)
    ("stream_sink_ms", (4500.0 + 500.0) / 2),
])
def test_readers_on_a_hand_made_trace(name, want):
    from presto_tpu.obs import span as obs_span

    obs_span.TRACES.reset()
    t0 = time.time()
    # before the window: left out
    hand_statement(
        obs_span.TRACES, t0 - 100, [{"batches": 9, "scan_s": 9.0}], 9.0, 1.0
    )
    hand_statement(
        obs_span.TRACES, t0 + 1, [{"batches": 58, "scan_s": 0.7}], 6.0, 1.5
    )
    hand_statement(
        obs_span.TRACES, t0 + 10,
        [{"batches": 58, "scan_s": 0.4}, {"batches": 3, "scan_s": 0.01}],
        2.0, 0.75,
    )
    got = reader(name).compute(Run(int(t0 * 1e9)))
    assert got == pytest.approx(want)
    obs_span.TRACES.reset()
