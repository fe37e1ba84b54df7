"""Bench regression gate: fail when a hot kernel regresses vs BASELINE.json.

Runs the per-operator micro suite (presto_tpu.benchmark.micro) for the
order-sensitive kernels the keypack work targets and compares rows/s
against the values recorded under BASELINE.json `micro_gate`. Exits
non-zero when any gated kernel falls more than `--tolerance` (default
10%) below its recorded value, so CI catches a perf regression the same
way it catches a correctness one.

The recorded values are backend+scale specific (BENCH_r05 ran cpu at
sf=0.1); when the live backend or scale differs the gate SKIPS (exit 0)
rather than comparing apples to TPUs.

Usage:
    python tools/bench_gate.py [--sf 0.1] [--runs 3] [--tolerance 0.10]

Wired into the test suite as a `slow`-marked test
(tests/test_bench_gate.py) so tier-1 stays fast.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

GATED = (
    "sort_2key", "top_n_100", "distinct_2key", "window_rank_runsum",
    # dynamic-filter probe path (PR 3): the filtered probe must stay
    # ahead of the legacy unfiltered join_probe_n1 floor, and the bloom
    # build+query kernel must not regress
    "join_probe_filtered", "bloom_build_query",
    # vectorized exchange (PR 4): light-weight encodings + striped
    # parallel compression + the pipelined pull client. serde_lz4 also
    # carries a serialize_MBps floor (acceptance: >= 2x the BENCH_r05
    # 208 MB/s) checked via the mbps_floors table below
    "serde_lz4", "serde_encoded", "serde_parallel_stripes",
    "exchange_pull_pipelined",
    # memory-arbitration degradation path (PR 7): the partitioned hybrid
    # hash join and the external sort must stay fast even when forced
    # through the CRC-checked disk spill tier — a regression here is an
    # overload-behavior regression even if in-memory paths stay green
    "hybrid_join_spill", "external_sort_disk",
    # serving fast path (PR 8): warm EXECUTE through the plan-skeleton +
    # result caches (exec/qcache.py); the micro RAISES when the warm
    # path misses either cache, so the gate catches a broken fast path
    # as well as a slow one
    "plan_cache_hit",
    # hash-relational kernels: join_build/join_probe_n1 measure the
    # sorted-hash join (the one join engine, floors at the BENCH_r05
    # rates); pallas_groupby_hash pins the hash-slot group-by (PR 11)
    # so a default-path change can't silently shelve it
    "join_build", "join_probe_n1", "pallas_groupby_hash",
    # streaming ingest + incremental matviews (PR 14): delta refresh
    # must scale with the delta, not the base (the micro RAISES when
    # the refresh falls off the delta path, and its speedup_vs_full
    # ratio carries the >=5x acceptance floor via ratio_floors);
    # mixed_soak_qps RAISES when zero reads were served by the qcache
    # patch verdict, so a broken patch path fails the gate outright
    "matview_refresh_delta", "ingest_append", "mixed_soak_qps",
    # observability plane (PR 15): one Prometheus scrape of the unified
    # registry (producers + render) must stay cheap enough that a 15s
    # scraper is never a serving-latency event
    "metrics_scrape",
    # history-based adaptive execution (PR 16): the warm history-driven
    # plan must beat the cold static misordered plan (speedup_vs_full
    # carries the >=1.5x acceptance floor via ratio_floors; the micro
    # RAISES when warm runs never consult the store), and the store's
    # fingerprint+lookup path must stay cheap enough that consulting
    # history never becomes a planning-latency event
    "feedback_replan", "feedback_lookup",
)
_HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BASELINE = os.path.join(_HERE, os.pardir, "BASELINE.json")


def run_lint_gate() -> list:
    """prestolint findings count via `python -m presto_tpu.analysis
    --check --json`: the static-analysis burndown gates CI next to the
    perf floors — a new unbaselined finding fails the gate the same way
    a kernel regression does. Returns failure strings ([] = clean)."""
    import subprocess

    repo_root = os.path.abspath(os.path.join(_HERE, os.pardir))
    proc = subprocess.run(
        [sys.executable, "-m", "presto_tpu.analysis", "--check", "--json"],
        capture_output=True, text=True, cwd=repo_root,
    )
    try:
        payload = json.loads(proc.stdout)
    except ValueError:
        return [
            "prestolint: unparseable --json output "
            f"(exit {proc.returncode}): {proc.stderr.strip()[:200]}"
        ]
    print(
        f"prestolint: {len(payload['new'])} new finding(s), "
        f"{payload['baselined']} baselined, {len(payload['passes'])} "
        f"passes in {payload['elapsed_s']}s"
    )
    if not payload["ok"]:
        by_rule = ", ".join(
            f"{r}={n}"
            for r, n in sorted(payload["new_by_rule"].items())
        ) or f"{payload['expired']} expired baseline entries"
        return [f"prestolint: gate not clean ({by_rule})"]
    return []


def run_gate(sf: float = 0.1, runs: int = 3, tolerance: float = 0.10,
             baseline_path: str = DEFAULT_BASELINE) -> int:
    # the lint gate is backend/scale independent: it runs (and can fail
    # the build) even when the perf comparison below has to skip
    lint_failures = run_lint_gate()
    for f_ in lint_failures:
        print(f"  {f_}")
    with open(baseline_path) as f:
        gate = json.load(f).get("micro_gate")
    if not gate or not gate.get("values"):
        print("bench_gate: no micro_gate baseline recorded — skipping")
        return 1 if lint_failures else 0
    if abs(float(gate.get("sf", sf)) - sf) > 1e-9:
        print(
            f"bench_gate: baseline recorded at sf={gate.get('sf')}, "
            f"run requested sf={sf} — skipping"
        )
        return 1 if lint_failures else 0

    repo_root = os.path.abspath(os.path.join(_HERE, os.pardir))
    if repo_root not in sys.path:  # `python tools/bench_gate.py` puts only
        sys.path.insert(0, repo_root)  # tools/ on sys.path
    from presto_tpu.benchmark.micro import run_suite

    table = run_suite(sf=sf, runs=runs, only=list(GATED))
    if table["backend"] != gate.get("backend"):
        print(
            f"bench_gate: baseline backend {gate.get('backend')!r} != live "
            f"backend {table['backend']!r} — skipping"
        )
        return 1 if lint_failures else 0
    got = {r["name"]: r for r in table["results"]}
    failures = list(lint_failures)
    for name in GATED:
        base = gate["values"].get(name)
        if base is None:
            continue
        r = got.get(name)
        if r is None:
            failures.append(
                f"{name}: missing from fresh run "
                f"({table['errors'].get(name, 'no result')})"
            )
            continue
        cur = r["rows_per_s"]
        ratio = cur / base
        note = f" [{r['note']}]" if r.get("note") else ""
        line = f"{name}: {cur:,} rows/s vs baseline {base:,} ({ratio:.2f}x){note}"
        print(line)
        if ratio < 1.0 - tolerance:
            failures.append(line)
        mbps_floor = (gate.get("mbps_floors") or {}).get(name)
        if mbps_floor and r.get("serialize_MBps"):
            mline = (
                f"{name}: serialize {r['serialize_MBps']} MB/s vs floor "
                f"{mbps_floor} MB/s"
            )
            print(mline)
            if r["serialize_MBps"] < mbps_floor * (1.0 - tolerance):
                failures.append(mline)
        # acceptance-ratio floors (e.g. matview delta refresh >= 5x a
        # full recompute at 1% delta) — absolute ratios, no tolerance:
        # the ratio is self-normalizing across machines
        ratio_floor = (gate.get("ratio_floors") or {}).get(name)
        if ratio_floor:
            ratio_val = r.get("speedup_vs_full")
            rline = (
                f"{name}: speedup_vs_full {ratio_val} vs floor "
                f"{ratio_floor}x"
            )
            print(rline)
            if ratio_val is None or ratio_val < ratio_floor:
                failures.append(rline)
    failures += run_multichip_gate(runs, tolerance, baseline_path)
    failures += run_qps_gate(tolerance, baseline_path)
    failures += run_tracing_overhead_gate(baseline_path)
    if failures:
        print(f"\nbench_gate: FAIL — {len(failures)} check(s) regressed "
              f">{tolerance:.0%} vs {os.path.basename(baseline_path)}:")
        for f_ in failures:
            print(f"  {f_}")
        return 1
    print("bench_gate: OK")
    return 0


def run_multichip_gate(runs: int, tolerance: float,
                       baseline_path: str = DEFAULT_BASELINE):
    """Multi-device exchange floors (BASELINE.json `multichip_gate`):
    the exchange micros need >=2 devices, which the in-process suite
    above cannot provide once jax has initialized single-chip — so this
    gate re-runs them in a SUBPROCESS with `--virtual-devices N`. A
    gated bench that comes back missing/skipped is a FAILURE, not a
    skip: the all_to_all micro regressed to 'skipped: single device'
    for ten PRs before this gate existed. Floors: rows/s per bench
    (tolerance applies) and the hier-vs-flat `speedup_vs_flat` ratio
    (absolute — self-normalizing across machines).
    Returns failure strings ([] = green/skipped)."""
    import subprocess
    import tempfile

    with open(baseline_path) as f:
        gate = json.load(f).get("multichip_gate")
    if not gate or not gate.get("values"):
        return []
    if gate.get("backend") != "cpu":
        # recorded on real multi-chip hardware: only comparable there
        import jax

        if jax.default_backend() != gate.get("backend"):
            print(
                f"multichip_gate: baseline backend {gate.get('backend')!r}"
                f" != live {jax.default_backend()!r} — skipping"
            )
            return []
    n_dev = int(gate.get("virtual_devices", 2))
    sf = float(gate.get("sf", 0.1))
    names = list(gate["values"])
    repo_root = os.path.abspath(os.path.join(_HERE, os.pardir))
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        out_path = tf.name
    try:
        proc = subprocess.run(
            [
                sys.executable, "-m", "presto_tpu.benchmark.micro",
                "--virtual-devices", str(n_dev), "--sf", str(sf),
                "--runs", str(runs), "--out", out_path, "--only", *names,
            ],
            capture_output=True, text=True, cwd=repo_root, timeout=1200,
        )
        if proc.returncode != 0:
            return [
                "multichip_gate: micro subprocess failed "
                f"(exit {proc.returncode}): {proc.stderr.strip()[-300:]}"
            ]
        with open(out_path) as f:
            table = json.load(f)
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass
    got = {r["name"]: r for r in table["results"]}
    failures = []
    for name in names:
        base = gate["values"][name]
        r = got.get(name)
        if r is None:
            failures.append(
                f"{name}: missing from {n_dev}-device run "
                f"({table['errors'].get(name, 'no result')})"
            )
            continue
        cur = r["rows_per_s"]
        ratio = cur / base
        note = f" [{r['note']}]" if r.get("note") else ""
        line = (
            f"{name}: {cur:,} rows/s vs baseline {base:,} "
            f"({ratio:.2f}x){note}"
        )
        print(line)
        if ratio < 1.0 - tolerance:
            failures.append(line)
        ratio_floor = (gate.get("ratio_floors") or {}).get(name)
        if ratio_floor:
            ratio_val = r.get("speedup_vs_flat")
            rline = (
                f"{name}: speedup_vs_flat {ratio_val} vs floor "
                f"{ratio_floor}x"
            )
            print(rline)
            if ratio_val is None or ratio_val < ratio_floor:
                failures.append(rline)
    return failures


def run_qps_gate(tolerance: float, baseline_path: str = DEFAULT_BASELINE):
    """Serving-benchmark floors (BASELINE.json `qps_gate`): run the
    northstar_qps driver at the recorded config and enforce the QPS
    floor, the warm-p50 ceiling, and the >=Nx warm-vs-cold p50 speedup
    acceptance line. Returns failure strings ([] = green/skipped)."""
    import jax

    with open(baseline_path) as f:
        gate = json.load(f).get("qps_gate")
    if not gate:
        return []
    if jax.default_backend() != gate.get("backend"):
        print(
            f"qps_gate: baseline backend {gate.get('backend')!r} != live "
            f"{jax.default_backend()!r} — skipping"
        )
        return []
    if jax.default_backend() == "cpu" and len(jax.devices()) < 2:
        # the single-device CPU runtime has a known pre-existing
        # host-callback deadlock on ORDER BY >= ~14k rows (ROADMAP
        # "Known issues") that the workload's top_orders statement would
        # hit; the backend is already initialized here, so the device
        # count cannot be forced anymore — skip rather than convert the
        # wedge into a 10-minute spurious failure (the test harness and
        # northstar_qps --cpu both run >=2 virtual devices)
        print("qps_gate: single-device CPU runtime — skipping "
              "(set --xla_force_host_platform_device_count=2)")
        return []
    from presto_tpu.benchmark.northstar_qps import run

    # wall-clock guard: a wedged query must FAIL the gate, not hang CI
    # forever (the driver also bounds its own client-thread joins; this
    # alarm additionally covers the single-threaded cold/warm phases).
    # SIGALRM only works on the main thread — elsewhere (the pytest slow
    # test) the conftest alarm guard plays this role.
    import signal
    import threading

    budget_s = int(gate.get("budget_s", 600))
    armed = threading.current_thread() is threading.main_thread()
    if armed:
        def _on_alarm(signum, frame):
            raise TimeoutError(f"northstar_qps exceeded {budget_s}s")

        prev_handler = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(budget_s)
    try:
        out = run(
            sf=float(gate.get("sf", 0.01)),
            clients=int(gate.get("clients", 4)),
            iters=int(gate.get("iters", 10)),
            join_timeout_s=max(budget_s - 60, 60),
        )
    except TimeoutError as e:
        return [f"northstar_qps: WEDGED — {e}"]
    finally:
        if armed:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, prev_handler)
    failures = []
    line = (
        f"northstar_qps: {out['qps']} qps, warm p50 {out['warm_p50_ms']}ms "
        f"(cold {out['cold_p50_ms']}ms, {out['speedup_p50']}x), "
        f"plan hit {out['caches']['plan']['hit_rate']}, "
        f"result hit {out['caches']['result']['hit_rate']}, "
        f"{out['errors']} errors"
    )
    print(line)
    if out["errors"]:
        failures.append(f"northstar_qps: {out['errors']} request errors")
    if out["qps"] is not None and out["qps"] < gate["min_qps"] * (1 - tolerance):
        failures.append(
            f"northstar_qps: {out['qps']} qps below floor {gate['min_qps']}"
        )
    if out["warm_p50_ms"] > gate["max_warm_p50_ms"] * (1 + tolerance):
        failures.append(
            f"northstar_qps: warm p50 {out['warm_p50_ms']}ms above ceiling "
            f"{gate['max_warm_p50_ms']}ms"
        )
    if out["speedup_p50"] is not None and (
        out["speedup_p50"] < gate.get("min_speedup_p50", 5.0)
    ):
        failures.append(
            f"northstar_qps: warm/cold p50 speedup {out['speedup_p50']}x "
            f"below the {gate.get('min_speedup_p50', 5.0)}x acceptance line"
        )
    return failures


def run_tracing_overhead_gate(baseline_path: str = DEFAULT_BASELINE):
    """Tracing-overhead floor (BASELINE.json `tracing_overhead_gate`):
    warm northstar p50 with PRESTO_TPU_TRACE=1 must stay within
    `max_overhead_frac` (default 5%) of the p50 with tracing off, plus
    `abs_slack_ms` of absolute slack — at sub-millisecond warm p50 a
    pure percentage is below box noise. The default-on observability
    plane earns its place HERE: regress the hot path and CI says no.
    Returns failure strings ([] = green/skipped)."""
    import jax

    with open(baseline_path) as f:
        gate = json.load(f).get("tracing_overhead_gate")
    if not gate:
        return []
    if jax.default_backend() != gate.get("backend"):
        print(
            f"tracing_overhead_gate: baseline backend "
            f"{gate.get('backend')!r} != live {jax.default_backend()!r} "
            f"— skipping"
        )
        return []
    if jax.default_backend() == "cpu" and len(jax.devices()) < 2:
        # same single-device ORDER BY wedge run_qps_gate documents
        print("tracing_overhead_gate: single-device CPU runtime — "
              "skipping (set --xla_force_host_platform_device_count=2)")
        return []
    from presto_tpu.benchmark.northstar_qps import run

    sf = float(gate.get("sf", 0.01))
    clients = int(gate.get("clients", 1))
    iters = int(gate.get("iters", 10))

    def _warm_p50(trace: str) -> float:
        prev = os.environ.get("PRESTO_TPU_TRACE")
        os.environ["PRESTO_TPU_TRACE"] = trace
        try:
            out = run(sf=sf, clients=clients, iters=iters,
                      join_timeout_s=120)
        finally:
            if prev is None:
                os.environ.pop("PRESTO_TPU_TRACE", None)
            else:
                os.environ["PRESTO_TPU_TRACE"] = prev
        if out["errors"]:
            raise RuntimeError(
                f"{out['errors']} request errors with trace={trace}"
            )
        return float(out["warm_p50_ms"])

    try:
        # off first, on second: any cache warm-up penalty lands on the
        # traced run, so the comparison can only overstate the overhead
        p50_off = _warm_p50("0")
        p50_on = _warm_p50("1")
    except Exception as e:  # noqa: BLE001 — a wedged/erroring driver is
        # a gate failure, not a crash
        return [f"tracing_overhead: driver failed — {e!r}"]
    frac = float(gate.get("max_overhead_frac", 0.05))
    slack = float(gate.get("abs_slack_ms", 0.2))
    ceiling = p50_off * (1.0 + frac) + slack
    overhead = (p50_on / p50_off - 1.0) if p50_off > 0 else 0.0
    line = (
        f"tracing_overhead: warm p50 {p50_on}ms traced vs {p50_off}ms "
        f"untraced ({overhead:+.1%}, ceiling {ceiling:.3f}ms)"
    )
    print(line)
    if p50_on > ceiling:
        return [line]
    return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sf", type=float, default=0.1)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--tolerance", type=float, default=0.10)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    args = ap.parse_args(argv)
    return run_gate(args.sf, args.runs, args.tolerance, args.baseline)


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # skip native teardown (see bench.py)
