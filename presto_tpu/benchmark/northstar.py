"""North-star queries (BASELINE.json: TPC-H Q3/Q5/Q17/Q18) on the
DEVICE-RESIDENT catalog.

The chunked SF100 runner (`benchmark/scale.py`) generates on HOST and
uploads every batch. This runner instead drives the same north-star
shapes through `DeviceTpchCatalog`: every scan batch is generated ON
DEVICE from splitmix64 counter streams, so host->device traffic is
scalars only and the run measures the engine, not the upload, at any SF
that fits HBM.

Reference protocol: presto-benchto-benchmarks tpch.yaml (runs + prewarm
per query); targets from BASELINE.json north_star (Q3/Q5/Q17/Q18
wall-clock, rows/sec/chip tracked per query).

    python -m presto_tpu.benchmark.northstar --sf 1 --runs 3

Prints ONE JSON line: per-query wall ms (best + mean), lineitem rows/s,
backend/device, and the SF.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from .scale import Q3, Q5, Q17, Q18

QUERIES = {"q3": Q3, "q5": Q5, "q17": Q17, "q18": Q18}


def run(sf: float, runs: int = 3, prewarm: int = 1, queries=None):
    import jax

    from ..connectors.tpch_device import DeviceTpchCatalog
    from ..session import Session

    dev = jax.devices()[0]
    cat = DeviceTpchCatalog(sf=sf)
    # result_cache off: this driver times EXECUTION — serving repeats
    # from the result cache would time a dictionary lookup instead (the
    # serving fast path has its own driver, northstar_qps)
    sess = Session(cat, result_cache=False)
    li_rows = cat.exact_row_count("lineitem")
    out = {
        "suite": "northstar_device_sql",
        "backend": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "sf": sf,
        "lineitem_rows": li_rows,
        "runs": runs,
        "results": [],
    }
    from ..exec import spillspace
    from ..server.serde import GLOBAL_WIRE_STATS

    for name in queries or QUERIES:
        sql = QUERIES[name]
        try:
            for _ in range(prewarm):
                rows = sess.query(sql).rows()  # compile + caches
            samples = []
            wire0 = GLOBAL_WIRE_STATS.snapshot()
            spilled0 = spillspace.total_written()
            rev0 = getattr(
                getattr(sess.executor, "pool", None), "revocations", 0
            )
            for _ in range(runs):
                t0 = time.perf_counter()
                rows = sess.query(sql).rows()
                samples.append((time.perf_counter() - t0) * 1e3)
            # per-query wire traffic (serde.GLOBAL_WIRE_STATS delta):
            # zero on the single-process ICI path, the real exchange
            # bytes + compression ratio when the query crossed workers
            wire1 = GLOBAL_WIRE_STATS.snapshot()
            wire_bytes = (wire1["wire_bytes"] - wire0["wire_bytes"]) // runs
            raw_b = (wire1["raw_bytes"] - wire0["raw_bytes"]) // runs
            best = min(samples)
            # dynamic-filter pruning observability (exec/dynfilter.py):
            # rows the runtime filters dropped before probe kernels, per
            # query, alongside wall-clock
            dyn = getattr(sess.executor, "dyn_ctx", None)
            snap = dyn.snapshot() if dyn is not None else {}
            out["results"].append(
                {
                    "name": name,
                    "ms": round(best, 1),
                    "mean_ms": round(sum(samples) / len(samples), 1),
                    "lineitem_rows_per_s": round(li_rows / (best / 1e3)),
                    "out_rows": len(rows),
                    "rows_pruned": (
                        sum(snap.get("scan_pruned", {}).values())
                        + sum(snap.get("preprobe_pruned", {}).values())
                    ),
                    "dyn_filters": snap.get("filters") or {},
                    "wire_bytes": wire_bytes,
                    "wire_ratio": (
                        round(raw_b / wire_bytes, 2) if wire_bytes else None
                    ),
                    # degradation-path observability (exec/spillspace.py +
                    # exec/memory.py): disk bytes the query spilled and
                    # revocation cycles it absorbed — a regression here
                    # (suddenly spilling, or revoking every run) is a perf
                    # bug even when wall-clock still looks fine
                    "spilled_bytes": (
                        (spillspace.total_written() - spilled0) // runs
                    ),
                    "revocations": getattr(
                        getattr(sess.executor, "pool", None),
                        "revocations", 0,
                    ) - rev0,
                }
            )
        except Exception as e:  # noqa: BLE001 — record, keep going
            out["results"].append({"name": name, "error": repr(e)[:300]})
        print(f"# {name}: {out['results'][-1]}", file=sys.stderr, flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--queries", type=str, default="")
    args = ap.parse_args()
    qs = [q for q in args.queries.split(",") if q] or None
    print(json.dumps(run(args.sf, runs=args.runs, queries=qs)), flush=True)


if __name__ == "__main__":
    main()
