"""The per-operator microbenchmark suite must stay runnable (the JMH-analog
of presto-benchmark BenchmarkSuite.java:32) — every entry executes and
reports sane rows/s on the test mesh backend."""

import time

from presto_tpu.benchmark.micro import DEVICE_BENCHES, run_suite

_SMALL = {"sf": 0.005, "runs": 1}


def _settle_soak(table):
    """Run `mixed_soak_qps` again while it loses its own race: the bench
    starts its writer thread and reads at once, and its 40 cached reads take
    ~12 ms, so on a busy machine all of them can be served before the first
    append lands; it then raises "zero patched reads" (2 of 12 runs under
    `-n 6`). A broken patch verdict loses EVERY attempt, so poll to a
    generous deadline, as conftest's spill guard does."""
    deadline = time.monotonic() + 120.0
    while (
        "zero patched reads" in table["errors"].get("mixed_soak_qps", "")
        and time.monotonic() < deadline
    ):
        again = run_suite(only=["mixed_soak_qps"], **_SMALL)
        del table["errors"]["mixed_soak_qps"]
        table["errors"].update(again["errors"])
        table["results"].extend(again["results"])


def test_suite_runs_every_operator():
    table = run_suite(**_SMALL)
    _settle_soak(table)
    assert table["backend"] == "cpu"
    names = {r["name"] for r in table["results"]}
    # every device bench + the host serde bench must produce a row;
    # the exchange benches run on the 8-device test mesh (never
    # "skipped" here — the multichip gate pins that on single-device)
    expected = set(DEVICE_BENCHES) | {
        "serde_lz4", "exchange_all_to_all", "exchange_hier",
    }
    assert expected <= names, (
        f"missing: {expected - names}; errors: {table['errors']}"
    )
    assert not table["errors"], table["errors"]
    for r in table["results"]:
        assert r["rows_per_s"] > 0, r
        assert r["ms"] > 0, r
    hier = next(r for r in table["results"] if r["name"] == "exchange_hier")
    assert hier["speedup_vs_flat"] > 0 and hier["wire_bytes"] > 0, hier
    a2a = next(
        r for r in table["results"] if r["name"] == "exchange_all_to_all"
    )
    assert a2a["wire_bytes"] > 0, a2a


def test_single_bench_selection():
    table = run_suite(only=["filter_compact"], **_SMALL)
    assert [r["name"] for r in table["results"]] == ["filter_compact"]
