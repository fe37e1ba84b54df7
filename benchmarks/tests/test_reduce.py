"""The yardstick's arithmetic: trace reduction, bytes, percentile, geomean.

Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`.
"""

import gzip
import json
import os

import pytest

import bytes_model
import stats
import tracered
import traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_trace():
    """Two statements of 100 us each, 50 us apart, on a trace clock that
    starts 1 ms after the wall clock's 0. The device runs three ops in
    two programs: 10+20 us in the first statement's execute span, 30 us
    in the second's."""
    trace = {
        "device": {"/device:TPU:0": {
            "ops": [["fusion.1", 1_020_000.0, 10_000.0],
                    ["sort.2", 1_040_000.0, 20_000.0],
                    ["fusion.1", 1_190_000.0, 30_000.0]],
            "modules": [["jit_a(123)", 1_020_000.0, 40_000.0],
                        ["jit_b(456)", 1_190_000.0, 30_000.0]],
        }},
        "marks": [["bench.stmt.q1", 1_000_000.0, 100_000.0],
                  ["bench.stmt.q6", 1_150_000.0, 100_000.0]],
    }
    records = []
    for sid, at in (("q1", 0.0), ("q6", 150e-6)):
        records.append({
            "id": sid, "epoch_ns": at * 1e9,
            "spans": {"query": (at + 10e-6, at + 90e-6),
                      "plan": (at + 10e-6, at + 20e-6),
                      "execute": (at + 20e-6, at + 90e-6)},
        })
    return trace, records


def test_reduce_hand_trace():
    out = tracered.reduce(*hand_trace())
    assert out["statements"] == 2 and out["traced_ids"] == ["q1", "q6"]
    assert out["window_s"] == pytest.approx(250e-6)
    assert out["busy_s"] == pytest.approx(60e-6)
    assert out["launches"] == 2
    assert out["device_ops"] == [["jit_a", pytest.approx(40e-6)],
                                 ["jit_b", pytest.approx(30e-6)]]
    gaps = dict(out["idle_gaps"])
    # execute spans: 2 x 70 us, 60 us of them busy
    assert gaps["execute"] == pytest.approx(80e-6)
    assert gaps["plan"] == pytest.approx(20e-6)
    assert gaps["http"] == pytest.approx(40e-6)
    assert gaps["client"] == pytest.approx(50e-6)
    assert sum(gaps.values()) == pytest.approx(250e-6 - 60e-6)


def test_reduce_overlapping_ops_count_once():
    trace, records = hand_trace()
    trace["device"]["/device:TPU:0"]["ops"].append(
        ["copy.3", 1_045_000.0, 10_000.0]  # inside sort.2
    )
    assert tracered.reduce(trace, records)["busy_s"] == pytest.approx(60e-6)


def test_reduce_without_device_plane_gives_none_not_zero():
    trace, records = hand_trace()
    trace["device"] = {}
    out = tracered.reduce(trace, records)
    assert out["busy_s"] is None and out["launches"] is None
    assert out["window_s"] == pytest.approx(250e-6)


def test_recorded_trace():
    """A trace recorded on the chip (TPU v5 lite, sf1.scan_agg, the first
    statements of a window; PR 26) and the numbers read from it then."""
    with gzip.open(os.path.join(DATA, "trace_sf1_scan_agg.json.gz"), "rt") as f:
        rec = json.load(f)
    out = tracered.reduce(rec["trace"], rec["records"])
    want = rec["expected"]
    assert out["statements"] == want["statements"]
    assert out["launches"] == want["launches"]
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"][0][0] == want["top_op"]


def test_bytes_model_sf1():
    assert bytes_model.statement_bytes("q1", 1.0) == 264_000_000
    assert bytes_model.statement_bytes("q6", 1.0) == 168_000_000
    assert bytes_model.statement_bytes("q1", 10.0) == 2_640_000_000
    # q3 names 2 customer, 4 orders and 4 lineitem columns
    assert bytes_model.statement_bytes("q3", 1.0) == (
        150_000 * 12 + 1_500_000 * 28 + 6_000_000 * 28
    )


def test_percentile_and_geomean():
    sample = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(sample, 50) == 30.0
    assert stats.percentile(sample, 95) == pytest.approx(48.0)
    assert stats.percentile(sample, 100) == 50.0
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.geomean([2.0, 8.0]) == pytest.approx(4.0)
    assert stats.geomean([170.0, 12.0]) == pytest.approx(45.1663592)
    recs = [{"id": "a", "wall_ms": 1.0}, {"id": "a", "wall_ms": 3.0},
            {"id": "b", "wall_ms": 8.0}]
    assert stats.class_means(recs) == {"a": 2.0, "b": 8.0}
    with pytest.raises(ValueError):
        stats.geomean([])


def test_mix_is_a_function_of_the_seed_and_keeps_the_work_alike():
    a, b = traffic.Mix("scan_agg", 2147483659), traffic.Mix("scan_agg", 2147483659)
    sent_a = [(st.id, st.sql(i)) for st, i in (next(a) for _ in range(40))]
    sent_b = [(st.id, st.sql(i)) for st, i in (next(b) for _ in range(40))]
    assert sent_a == sent_b
    assert [s for s, _ in sent_a[:4]] == ["q1", "q6", "q1", "q6"]
    c = traffic.Mix("scan_agg", 7)
    q1a, q6a = a.statements
    q1c, q6c = c.statements
    # another seed: other DELTAs of qgen's range, as many; the same pool
    # of Q6 sets, warmed in the file's order, sent in another order
    assert q1c.param_sets != q1a.param_sets
    for q1 in (q1a, q1c):
        deltas = [p["delta"] for p in q1.param_sets]
        assert len(set(deltas)) == 8 and all(60 <= d <= 120 for d in deltas)
    assert q6c.param_sets == q6a.param_sets and len(q6a.param_sets) == 8
    sent_c = [st.sql(i) for st, i in (next(c) for _ in range(32)) if st.id == "q6"]
    sent_a6 = [sql for sid, sql in sent_a[:32] if sid == "q6"]
    assert sent_c != sent_a6 and sorted(sent_c) == sorted(sent_a6)
    assert all(p["quantity"] in (24, 25) and 2 <= p["discount"] <= 9
               and 1993 <= p["year"] <= 1997 for p in q6a.param_sets)
    assert len(list(traffic.Mix("join", 5).every())) == 2


def test_mixes_share_a_pool_by_name():
    scan = traffic.Mix("scan", 3)
    q6 = next(st for st in traffic.Mix("scan_agg", 3).statements if st.id == "q6")
    assert [st.id for st in scan.statements] == ["q6"]
    assert scan.statements[0].param_sets == q6.param_sets
