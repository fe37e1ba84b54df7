"""On-chip join-stage decomposition: where do join_probe_n1's ms go?

Times each stage of the FK->PK probe independently with the chained-
dependency protocol (bench.py `_chained_device_time` rationale): probe-key
hashing, candidate-range lookup (bucket directory vs the searchsorted it
replaced), collision scan, payload gather, and the full join_n1 — so a
TPU regression or win is attributable to a stage, not guessed.

    python -m presto_tpu.benchmark.profile_join --sf 0.1 --runs 5

Reference analog: BenchmarkHashBuildAndJoinOperators breaks build/probe
phases apart for the same reason.
"""

from __future__ import annotations

import argparse
import json
import time


def _chained(fn, n_runs=5, reps=3):
    import jax
    import jax.numpy as jnp

    f = jax.jit(fn)
    s = f(jnp.int64(0))
    int(s)  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        s = jnp.int64(0)
        for _ in range(n_runs):
            s = f(s)
        int(s)
        best = min(best, (time.perf_counter() - t0) / n_runs)
    return best


def main(sf: float = 0.1, runs: int = 5):
    import jax.numpy as jnp

    from .. import types as T
    from ..expr.compiler import evaluate
    from ..expr.ir import col
    from ..ops import join as J
    from ..ops.hashing import hash_rows
    from .handcoded import _table_page
    from .micro import _orders_keys_page

    probe = _table_page("lineitem", sf, ("l_orderkey", "l_extendedprice"))
    bpage = _orders_keys_page(sf)
    kexpr = (col("o_orderkey", T.BIGINT),)
    pkexpr = (col("l_orderkey", T.BIGINT),)
    bs = J.build_sorted(bpage, kexpr)
    pkeys = [evaluate(e, probe) for e in pkexpr]
    h = hash_rows(pkeys)
    n = int(probe.count)
    out = {"sf": sf, "probe_rows": n, "build_rows": int(bpage.count)}

    def dep(acc):
        # zero-valued dependency folded into the probe hash input
        return [type(v)(v.data + (acc * 0).astype(v.data.dtype), v.valid,
                        v.type, v.dict_id) for v in pkeys]

    def t_hash(acc):
        return jnp.sum(hash_rows(dep(acc)).astype(jnp.int64))

    def t_ranges(acc):
        _, lo, hi = J._probe_ranges(bs, dep(acc), probe.capacity)
        return jnp.sum(lo.astype(jnp.int64)) + jnp.sum(hi.astype(jnp.int64))

    def t_ranges_searchsorted(acc):
        hh = hash_rows(dep(acc))
        lo = jnp.searchsorted(bs.sorted_hash, hh, side="left")
        hi = jnp.searchsorted(bs.sorted_hash, hh, side="right")
        return jnp.sum(lo.astype(jnp.int64)) + jnp.sum(hi.astype(jnp.int64))

    def t_scan(acc):
        ks = dep(acc)
        _, lo, hi = J._probe_ranges(bs, ks, probe.capacity)
        m, br = J._collision_scan(bs, ks, lo, hi)
        return jnp.sum(br.astype(jnp.int64)) + jnp.sum(m.astype(jnp.int64))

    def t_full(acc):
        from ..page import Block, Page

        b0 = probe.blocks[0]
        blocks = (Block(b0.data + (acc * 0).astype(b0.data.dtype), b0.type,
                        b0.valid, b0.dict_id),) + probe.blocks[1:]
        p = Page(blocks, probe.names, probe.count)
        o = J.join_n1(p, bs, pkexpr, ("o_custkey", "o_totalprice"),
                      ("o_custkey", "o_totalprice"))
        acc2 = jnp.int64(0)
        for b in o.blocks:
            acc2 = acc2 + jnp.sum(b.data[0].astype(jnp.int64))
        return acc2

    for name, fn in (
        ("hash_ms", t_hash),
        ("ranges_bucket_ms", t_ranges),
        ("ranges_searchsorted_ms", t_ranges_searchsorted),
        ("scan_ms", t_scan),
        ("join_full_ms", t_full),
    ):
        try:
            out[name] = round(_chained(fn, runs) * 1e3, 3)
        except Exception as e:  # noqa: BLE001 - the error IS the
            # recorded measurement for this row
            out[name] = f"error: {repr(e)[:120]}"
    import jax

    out["backend"] = jax.default_backend()
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.1)
    ap.add_argument("--runs", type=int, default=5)
    a = ap.parse_args()
    main(a.sf, a.runs)
