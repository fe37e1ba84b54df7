import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.page import Block, Page
from presto_tpu.expr import col, lit, comparison, binary
from presto_tpu.ops import (
    AggSpec,
    SortKey,
    build_sorted,
    compact,
    distinct_page,
    filter_page,
    global_aggregate,
    grouped_aggregate_direct,
    grouped_aggregate_sorted,
    join_expand,
    join_n1,
    limit_page,
    sort_page,
    top_n,
)


def test_compact():
    p = Page.from_dict({"a": np.arange(8, dtype=np.int64)}, pad_to=8)
    keep = jnp.asarray([True, False, True, False, True, False, False, True])
    out = compact(p, keep)
    assert out.to_pylist() == [(0,), (2,), (4,), (7,)]
    assert int(out.count) == 4


@pytest.mark.parametrize("n", [1, 7, 128, 5000])
def test_sort_forms_match_the_stable_argsorts(n):
    """The compile-cheap sort forms (distinct keys, unstable sort) give
    exactly the permutations of the stable argsorts they replace."""
    from presto_tpu.ops.filter import kept_first_permutation
    from presto_tpu.ops.hashing import argsort_hashes

    rng = np.random.default_rng(n)
    keep = jnp.asarray(rng.random(n) < 0.3)
    np.testing.assert_array_equal(
        kept_first_permutation(keep), jnp.argsort(~keep, stable=True)
    )
    # few distinct hashes -> long tie runs; then a dead-row sentinel run
    h = jnp.asarray(rng.integers(0, 50, n).astype(np.uint64) << np.uint64(58))
    for hashes in (h, h.at[: n // 2].set(np.uint64(0xFFFFFFFFFFFFFFFF))):
        np.testing.assert_array_equal(
            argsort_hashes(hashes), jnp.argsort(hashes, stable=True)
        )


def test_filter_page():
    p = Page.from_dict({"a": np.arange(10, dtype=np.int64)}, pad_to=16)
    out = filter_page(p, comparison("ge", col("a", T.BIGINT), lit(7)))
    assert out.to_pylist() == [(7,), (8,), (9,)]


def test_global_aggregate_with_nulls():
    blk = Block.from_numpy(
        np.array([1, 2, 3, 4], np.int64),
        T.BIGINT,
        valid=np.array([True, False, True, True]),
    )
    p = Page.from_blocks([blk], ["x"])
    out = global_aggregate(
        p,
        [
            AggSpec("sum", col("x", T.BIGINT), "s", T.BIGINT),
            AggSpec("count", col("x", T.BIGINT), "c", T.BIGINT),
            AggSpec("count_star", None, "cs", T.BIGINT),
            AggSpec("min", col("x", T.BIGINT), "mn", T.BIGINT),
            AggSpec("max", col("x", T.BIGINT), "mx", T.BIGINT),
            AggSpec("avg", col("x", T.BIGINT), "av", T.DOUBLE),
        ],
    )
    assert out.to_pylist() == [(8, 3, 4, 1, 4, 8 / 3)]


def test_global_aggregate_empty_input():
    p = Page.from_dict({"x": np.array([], np.int64)}, pad_to=4)
    out = global_aggregate(
        p,
        [
            AggSpec("sum", col("x", T.BIGINT), "s", T.BIGINT),
            AggSpec("count", col("x", T.BIGINT), "c", T.BIGINT),
        ],
    )
    # SQL: sum over empty = NULL, count = 0
    assert out.to_pylist() == [(None, 0)]


def test_grouped_direct():
    p = Page.from_dict(
        {
            "g": Block.from_strings(["b", "a", "b", "a", "c"]),
            "x": np.array([10, 1, 20, 2, 100], np.int64),
        },
        pad_to=8,
    )
    g = p.block("g")
    out = grouped_aggregate_direct(
        p,
        [col("g", T.VARCHAR)],
        ["g"],
        [AggSpec("sum", col("x", T.BIGINT), "s", T.BIGINT)],
        domains=[3],
    )
    assert sorted(out.to_pylist()) == [("a", 3), ("b", 30), ("c", 100)]


def test_grouped_sorted_general():
    rng = np.random.default_rng(7)
    n = 1000
    g = rng.integers(0, 37, n)
    x = rng.integers(0, 100, n)
    p = Page.from_dict(
        {"g": g.astype(np.int64), "x": x.astype(np.int64)}, pad_to=1024
    )
    out = grouped_aggregate_sorted(
        p,
        [col("g", T.BIGINT)],
        ["g"],
        [
            AggSpec("sum", col("x", T.BIGINT), "s", T.BIGINT),
            AggSpec("count_star", None, "c", T.BIGINT),
        ],
        max_groups=64,
    )
    got = {r[0]: (r[1], r[2]) for r in out.to_pylist()}
    want = {}
    for gi in np.unique(g):
        want[gi] = (int(x[g == gi].sum()), int((g == gi).sum()))
    assert got == want


def test_grouped_sorted_multikey_with_nulls():
    k1 = Block.from_numpy(
        np.array([1, 1, 2, 1, 2, 1], np.int64),
        T.BIGINT,
        valid=np.array([True, True, True, False, True, False]),
    )
    k2 = Block.from_strings(["x", "y", "x", "x", "x", "x"])
    x = Block.from_numpy(np.array([1, 2, 4, 8, 16, 32], np.int64), T.BIGINT)
    p = Page.from_blocks([k1, k2, x], ["k1", "k2", "x"])
    out = grouped_aggregate_sorted(
        p,
        [col("k1", T.BIGINT), col("k2", T.VARCHAR)],
        ["k1", "k2"],
        [AggSpec("sum", col("x", T.BIGINT), "s", T.BIGINT)],
        max_groups=16,
    )
    got = sorted(out.to_pylist(), key=lambda r: (r[0] is None, r[0], r[1]))
    # groups: (1,x)=1, (1,y)=2, (2,x)=4+16=20, (NULL,x)=8+32=40
    assert got == [(1, "x", 1), (1, "y", 2), (2, "x", 20), (None, "x", 40)]


def test_join_n1_inner_left_semi_anti():
    build_page = Page.from_dict(
        {
            "k": np.array([1, 2, 3, 5], np.int64),
            "name": ["one", "two", "three", "five"],
        },
        pad_to=8,
    )
    probe = Page.from_dict(
        {"k": np.array([3, 1, 4, 1, 5], np.int64), "v": np.array([30, 10, 40, 11, 50], np.int64)},
        pad_to=8,
    )
    bs = build_sorted(build_page, [col("k", T.BIGINT)])

    out = join_n1(probe, bs, [col("k", T.BIGINT)], ["name"], ["name"], kind="inner")
    assert out.to_pylist() == [
        (3, 30, "three"),
        (1, 10, "one"),
        (1, 11, "one"),
        (5, 50, "five"),
    ]

    out = join_n1(probe, bs, [col("k", T.BIGINT)], ["name"], ["name"], kind="left")
    assert out.to_pylist() == [
        (3, 30, "three"),
        (1, 10, "one"),
        (4, 40, None),
        (1, 11, "one"),
        (5, 50, "five"),
    ]

    out = join_n1(probe, bs, [col("k", T.BIGINT)], [], [], kind="semi")
    assert [r[0] for r in out.to_pylist()] == [3, 1, 1, 5]
    out = join_n1(probe, bs, [col("k", T.BIGINT)], [], [], kind="anti")
    assert [r[0] for r in out.to_pylist()] == [4]


def test_join_n1_null_keys_never_match():
    bk = Block.from_numpy(
        np.array([1, 2], np.int64), T.BIGINT, valid=np.array([True, False])
    )
    build_page = Page.from_blocks([bk], ["k"])
    pk = Block.from_numpy(
        np.array([1, 2, 3], np.int64), T.BIGINT, valid=np.array([True, False, True])
    )
    probe = Page.from_blocks([pk], ["k"])
    bs = build_sorted(build_page, [col("k", T.BIGINT)])
    out = join_n1(probe, bs, [col("k", T.BIGINT)], [], [], kind="semi")
    assert out.to_pylist() == [(1,)]


def test_join_expand_1n():
    build_page = Page.from_dict(
        {"k": np.array([1, 1, 2, 3, 3, 3], np.int64), "w": np.array([10, 11, 20, 30, 31, 32], np.int64)},
        pad_to=8,
    )
    probe = Page.from_dict(
        {"k": np.array([3, 1, 9], np.int64), "v": np.array([300, 100, 900], np.int64)},
        pad_to=4,
    )
    bs = build_sorted(build_page, [col("k", T.BIGINT)])
    out, overflow = join_expand(
        probe,
        bs,
        [col("k", T.BIGINT)],
        ["k", "v"],
        [("w", "w")],
        out_capacity=16,
        kind="inner",
    )
    assert int(overflow) == 0
    rows = sorted(out.to_pylist())
    assert rows == [(1, 100, 10), (1, 100, 11), (3, 300, 30), (3, 300, 31), (3, 300, 32)]

    out, overflow = join_expand(
        probe,
        bs,
        [col("k", T.BIGINT)],
        ["k", "v"],
        [("w", "w")],
        out_capacity=16,
        kind="left",
    )
    assert int(overflow) == 0
    rows = sorted(out.to_pylist(), key=lambda r: (r[0], r[2] is None, r[2] or 0))
    assert (9, 900, None) in rows
    assert len(rows) == 6


def test_sort_multikey_desc_nulls():
    a = Block.from_numpy(
        np.array([2, 1, 2, 1, 3], np.int64),
        T.BIGINT,
        valid=np.array([True, True, True, True, False]),
    )
    b = Block.from_numpy(np.array([5.0, 7.0, 3.0, 9.0, 1.0]), T.DOUBLE)
    p = Page.from_blocks([a, b], ["a", "b"])
    out = sort_page(
        p,
        [SortKey(col("a", T.BIGINT), ascending=True), SortKey(col("b", T.DOUBLE), ascending=False)],
    )
    # default: ASC => NULLS LAST
    assert out.to_pylist() == [
        (1, 9.0),
        (1, 7.0),
        (2, 5.0),
        (2, 3.0),
        (None, 1.0),
    ]


def test_top_n_and_limit():
    p = Page.from_dict({"x": np.array([5, 3, 9, 1, 7], np.int64)}, pad_to=8)
    out = top_n(p, [SortKey(col("x", T.BIGINT), ascending=False)], 3)
    assert out.to_pylist() == [(9,), (7,), (5,)]
    assert out.capacity == 3
    out = limit_page(p, 2)
    assert out.to_pylist() == [(5,), (3,)]


def test_distinct():
    p = Page.from_dict({"x": np.array([3, 1, 3, 2, 1, 3], np.int64)}, pad_to=8)
    out = distinct_page(p, max_groups=8)
    assert sorted(out.to_pylist()) == [(1,), (2,), (3,)]


def test_kernels_are_jittable():
    @jax.jit
    def pipeline(p: Page) -> Page:
        f = filter_page(p, comparison("gt", col("x", T.BIGINT), lit(2)))
        return global_aggregate(
            f, [AggSpec("sum", col("x", T.BIGINT), "s", T.BIGINT)]
        )

    p = Page.from_dict({"x": np.array([1, 2, 3, 4, 5], np.int64)}, pad_to=8)
    out = pipeline(p)
    assert out.to_pylist() == [(12,)]


def test_join_bucket_directory_stress():
    """The bucket-start directory (O(1) probe ranges) vs a brute-force
    oracle: many probes, duplicate build keys, dead build rows beyond
    count, and a composite key — bucket candidates that differ in hash
    or sit in the dead tail must never match."""
    import os

    if os.environ.get("PRESTO_TPU_JOIN_PROBE", "directory") != "directory":
        pytest.skip("directory probe gated off via PRESTO_TPU_JOIN_PROBE")
    rng = np.random.default_rng(7)
    nb, npr = 5000, 20000
    bk = rng.integers(0, 3000, nb)  # duplicates guaranteed
    bw = rng.integers(0, 1 << 40, nb)
    build_page = Page.from_dict(
        {"k": bk.astype(np.int64), "w": bw.astype(np.int64)},
        pad_to=8192,  # dead tail after nb rows
    )
    pk = rng.integers(0, 4000, npr)  # some keys miss entirely
    probe = Page.from_dict({"k": pk.astype(np.int64)}, pad_to=1 << 15)
    # this test pins the sorted layout's bucket directory
    bs = build_sorted(build_page, [col("k", T.BIGINT)])
    assert bs.bucket_start is not None and bs.bucket_bits > 0

    out = join_n1(probe, bs, [col("k", T.BIGINT)], [], [], kind="semi")
    got = sorted(r[0] for r in out.to_pylist())
    want = sorted(int(k) for k in pk if k in set(bk.tolist()))
    assert got == want

    # 1:N expansion counts through bucket (superset) candidate ranges
    sub = Page.from_dict({"k": pk[:50].astype(np.int64)}, pad_to=64)
    out, overflow = join_expand(
        sub, bs, [col("k", T.BIGINT)], ["k"], [("w", "w")],
        out_capacity=4096, kind="inner",
    )
    assert int(overflow) == 0
    got = sorted(out.to_pylist())
    import collections

    bw_by_k = collections.defaultdict(list)
    for k, w in zip(bk.tolist(), bw.tolist()):
        bw_by_k[k].append(w)
    want = sorted(
        (int(k), w) for k in pk[:50].tolist() for w in bw_by_k.get(k, [])
    )
    assert got == want


def test_sort_float_signs_nans_negzero():
    """Fused-sort float key regression (TPC-DS 47/57/89 round-5): keys
    are compared SIGNED, so negatives must map below positives; NaNs
    sort last in BOTH directions (jnp.argsort parity); -0.0 ties +0.0
    (stable: original order preserved among the tie)."""
    from presto_tpu.ops.sort import SortKey, sort_page

    vals = np.array(
        [21.2, -73.85, float("nan"), 0.0, -0.0, float("inf"),
         -float("inf"), 1e-300, -1e-300], np.float64
    )
    tag = np.arange(len(vals), dtype=np.int64)
    page = Page.from_dict({"v": vals, "t": tag})
    asc = sort_page(page, (SortKey(col("v", T.DOUBLE)),)).to_pylist()
    got = [r[1] for r in asc]
    # -inf, -73.85, -1e-300, 0.0(idx3), -0.0(idx4), 1e-300, 21.2, inf, nan
    assert got == [6, 1, 8, 3, 4, 7, 0, 5, 2]
    desc = sort_page(
        page, (SortKey(col("v", T.DOUBLE), ascending=False),)
    ).to_pylist()
    got_d = [r[1] for r in desc]
    assert got_d[-1] == 2  # NaN still last under DESC
    assert got_d[:3] == [5, 0, 7]  # inf, 21.2, 1e-300


def test_block_topn_matches_full_sort():
    """Round-5 block-wise TopN selection: per-block candidate sorts +
    final candidate sort + n-row gather must match the full stable sort
    exactly — heavy ties, descending float key, nulls."""
    import os

    from presto_tpu.expr.ir import col
    from presto_tpu.ops.sort import SortKey, top_n

    rng = np.random.default_rng(1)
    n = 1 << 17
    b = rng.standard_normal(n)
    bv = rng.random(n) > 0.01  # some NULLs
    pg = Page.from_dict(
        {
            "a": rng.integers(0, 50, n).astype(np.int64),
            "b": Block.from_numpy(b, T.DOUBLE, valid=bv),
            "c": np.arange(n, dtype=np.int64),
        }
    )
    keys = (
        SortKey(col("a", T.BIGINT)),
        SortKey(col("b", T.DOUBLE), ascending=False),
    )
    fast = top_n(pg, keys, 100)
    os.environ["PRESTO_TPU_BLOCK_TOPN"] = "0"
    try:
        slow = top_n(pg, keys, 100)
    finally:
        os.environ.pop("PRESTO_TPU_BLOCK_TOPN")
    assert fast.to_pylist() == slow.to_pylist()
