"""On-chip join-stage decomposition: where do join_probe_n1's ms go?

Times each stage of the FK->PK probe independently with the chained-
dependency protocol (bench.py `_chained_device_time` rationale): probe-key
hashing, candidate-range lookup (bucket directory vs the searchsorted it
replaced), collision scan, payload gather, and the full join_n1 — so a
TPU regression or win is attributable to a stage, not guessed.

    python -m presto_tpu.benchmark.profile_join --sf 0.1 --runs 5

`--ranks` times instead how the join programs rank SORTED queries in a
sorted array (ops/join.py `sorted_rank`), each shape three ways:
`jnp.searchsorted`'s default gather rounds (`search`), the merge through
sort (`merge`) and `jnp.searchsorted(method="sort")`, whose rank step is a
scatter (`sort`); the shapes are the bucket directories and expansion maps
of the join cells and one map with far fewer queries than values. The
values are an argument of each timed program, and the compiled program's
`sort` and `while` ops are counted beside its time:

    python -m presto_tpu.benchmark.profile_join --ranks --runs 5

Reference analog: BenchmarkHashBuildAndJoinOperators breaks build/probe
phases apart for the same reason.
"""

from __future__ import annotations

import argparse
import json
import time


def _chained(fn, n_runs=5, reps=3, args=()):
    import jax
    import jax.numpy as jnp

    f = jax.jit(fn)
    s = f(*args, jnp.int64(0))
    int(s)  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        s = jnp.int64(0)
        for _ in range(n_runs):
            s = f(*args, s)
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


# (label, values n, queries nq, side): the directory of a build page of
# n slots (2^bits + 1 queries, bits capped at 22) and join_expand's map of
# nq output slots over a probe page of n slots: the SF10 cells', a map far
# below its probe (where a search would beat the merge), the SF1 cells'
RANK_SHAPES = (
    ("directory", 2_097_152, 4_194_305, "left"),
    ("expand_map", 4_194_304, 4_194_304, "right"),
    ("expand_map", 4_194_304, 16_384, "right"),
    ("directory", 262_144, 524_289, "left"),
    ("expand_map", 65_536, 131_072, "right"),
)


def _rank_values(label: str, n: int, nq: int):
    """Sorted int32 values of a shape: bucket ids of 2^22-capped bits with
    a dead tail in the last bucket, or the cumulative counts of a probe
    whose rows have 0-1 candidates."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.PRNGKey(n ^ nq)
    if label == "directory":
        nb = nq - 1
        b = jax.random.randint(key, (n,), 0, nb, dtype=jnp.int32)
        b = jnp.where(jnp.arange(n) < n - n // 8, b, nb - 1)
        return jnp.sort(b)
    counts = jax.random.randint(key, (n,), 0, 2, dtype=jnp.int32)
    return jnp.cumsum(counts * np.int32(max(1, nq // max(n, 1))))


def main_ranks(runs: int = 5):
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import join as J

    out = {"backend": jax.default_backend()}
    for label, n, nq, side in RANK_SHAPES:
        a = _rank_values(label, n, nq)
        q = jnp.arange(nq, dtype=jnp.int32)
        forms = {
            "search": lambda v: jnp.searchsorted(v, q, side=side),
            "merge": lambda v: J.sorted_rank(v, nq, side),
            "sort": lambda v: jnp.searchsorted(v, q, side=side, method="sort"),
        }
        want = np.searchsorted(np.asarray(a), np.arange(nq), side=side)
        row = {"n": n, "nq": nq, "rounds": int(np.ceil(np.log2(n + 1)))}
        for form, f in forms.items():
            got = np.asarray(jax.jit(f)(a))
            if not np.array_equal(got, want):
                row[f"{form}_ms"] = "error: differs from np.searchsorted"
                continue

            def timed(v, acc, f=f):
                # the ranks are never negative, so this adds 0, and only
                # the device knows it: each run waits for the last
                r = f(v + (acc < 0).astype(v.dtype))
                return jnp.sum(r.astype(jnp.int64))

            hlo = jax.jit(timed).lower(a, jnp.int64(0)).compile().as_text()
            row[f"{form}_hlo"] = {
                op: len(re.findall(rf"\b{op}\(", hlo))
                for op in ("sort", "while")
            }
            ms = _chained(timed, runs, args=(a,)) * 1e3
            row[f"{form}_ms"] = round(ms, 3)
        if isinstance(row["search_ms"], float):
            row["search_ns_per_index"] = round(
                row["search_ms"] * 1e6 / (nq * row["rounds"]), 3
            )
        if isinstance(row["merge_ms"], float):
            row["merge_ns_per_element"] = round(
                row["merge_ms"] * 1e6 / (n + nq), 3
            )
        out[f"{label}_{nq}x{n}"] = row
        print(json.dumps({f"{label}_{nq}x{n}": row}), flush=True)
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.1)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--ranks", action="store_true",
                    help="time the three ways to rank sorted queries")
    a = ap.parse_args()
    if a.ranks:
        main_ranks(a.runs)
    else:
        main(a.sf, a.runs)
