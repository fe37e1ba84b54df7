"""The sorted-hash join (ops/join.py) against a pure-python oracle, the
hash-slot group-by against the sort composition, the ragged paged
partition layout (ops/ragged.py), and the engine wiring (executor
strategy notes, a star query against the SQLite oracle)."""

import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu import types as T
from presto_tpu.connectors.memory import MemoryCatalog
from presto_tpu.exec.breaker import BREAKERS
from presto_tpu.expr.ir import col
from presto_tpu.ops import ragged
from presto_tpu.ops import join as J
from presto_tpu.ops.join import (
    build_sorted,
    join_expand,
    join_n1,
    semi_match_mask,
    sorted_rank,
)
from presto_tpu.page import Block, Page, round_capacity
from presto_tpu.session import Session


@pytest.fixture(autouse=True)
def _fresh_breakers():
    BREAKERS.reset()
    yield
    BREAKERS.reset()


def _page(cols, count=None):
    blocks, names = [], []
    n = None
    for name, (data, typ, valid) in cols.items():
        blocks.append(
            Block(
                jnp.asarray(data), typ,
                None if valid is None else jnp.asarray(valid),
            )
        )
        names.append(name)
        n = len(data)
    return Page(tuple(blocks), tuple(names), jnp.int32(count if count is not None else n))


def _rows(out, names):
    n = int(out.count)
    cols = []
    for nm in names:
        b = out.block(nm)
        data = np.asarray(b.data)[:n]
        if b.valid is not None:
            valid = np.asarray(b.valid)[:n]
            cols.append([None if not v else d.item() for d, v in zip(data, valid)])
        else:
            cols.append([d.item() for d in data])
    def one(x):
        if x is None:
            return (2, 0)
        if isinstance(x, float) and x != x:  # NaN: orderable sentinel
            return (1, 0)
        return (0, x)

    return sorted(zip(*cols), key=lambda t: tuple(one(x) for x in t)) if cols else []


# ---------------------------------------------------------------------------
# property suite: sorted-hash join == oracle across dtypes x NULLs x skew x
# empty x build-larger-than-probe
# ---------------------------------------------------------------------------


def _key_data(rng, dtype, n, domain, skew):
    if dtype == "int64":
        k = rng.integers(0, domain, n).astype(np.int64) * 7919 - 1000
    elif dtype == "int32":
        k = rng.integers(0, domain, n).astype(np.int32)
    elif dtype == "float64":
        k = (rng.integers(0, domain, n) * 0.5).astype(np.float64)
    else:
        raise AssertionError(dtype)
    if skew:
        heavy = rng.random(n) < 0.7  # one key takes 70% of rows
        k = np.where(heavy, k.flat[0], k)
    return k

_TYPES = {"int64": T.BIGINT, "int32": T.INTEGER, "float64": T.DOUBLE}


@pytest.mark.parametrize("dtype", ["int64", "int32", "float64"])
@pytest.mark.parametrize("nulls", [False, True])
@pytest.mark.parametrize("skew", [False, True])
def test_join_property_suite(dtype, nulls, skew):
    rng = np.random.default_rng(hash((dtype, nulls, skew)) % (2**32))
    # build larger than probe in half the shapes; also exercise dead-tail
    # capacity padding (count < capacity)
    nb, np_ = (3000, 900) if skew else (700, 2500)
    bk = _key_data(rng, dtype, nb, max(nb // 2, 2), skew)
    pk = _key_data(rng, dtype, np_, max(nb // 2, 2) + 5, False)
    bkv = (rng.random(nb) > 0.2) if nulls else None
    pkv = (rng.random(np_) > 0.2) if nulls else None
    kt = _TYPES[dtype]
    b = _page({"k": (bk, kt, bkv), "v": (np.arange(nb), T.BIGINT, None)},
              count=nb - 17)
    p = _page({"k": (pk, kt, pkv), "w": (np.arange(np_), T.BIGINT, None)},
              count=np_ - 5)
    keys = (col("k", kt),)

    bs = build_sorted(b, keys)

    # oracle pair multiset over live, non-null rows
    blive = [i for i in range(nb - 17) if bkv is None or bkv[i]]
    plive = [i for i in range(np_ - 5) if pkv is None or pkv[i]]
    by_key = {}
    for i in blive:
        by_key.setdefault(bk[i].item(), []).append(i)

    # -- expand (all matches) --
    from collections import Counter

    for kind in ("inner", "left"):
        cap = 1 << 13
        while True:
            out, ov = join_expand(
                p, bs, keys, ("w",), [("v", "bv")], cap, kind=kind
            )
            if int(ov) == 0:
                break
            cap = round_capacity(cap + int(ov))

        want = []
        for i in range(np_ - 5):
            ms = by_key.get(pk[i].item(), []) if (pkv is None or pkv[i]) else []
            if ms:
                want += [(i, m) for m in ms]
            elif kind == "left" and i < np_ - 5:
                want.append((i, None))
        want_pairs = Counter(want)

        got = Counter(_rows(out, ("w", "bv")))
        assert got == want_pairs, (kind, dtype, nulls, skew)

    # -- semi / anti / mark --
    want_semi = sorted(i for i in plive if pk[i].item() in by_key)
    got_s = _rows(join_n1(p, bs, keys, (), (), kind="semi"), ("w",))
    assert got_s == [(i,) for i in want_semi]
    got_a = _rows(join_n1(p, bs, keys, (), (), kind="anti"), ("w",))
    semi = set(want_semi)
    assert got_a == [(i,) for i in range(np_ - 5) if i not in semi]
    mask_s = np.asarray(semi_match_mask(p, bs, keys))
    assert np.flatnonzero(mask_s).tolist() == want_semi


def test_empty_build_and_empty_probe():
    keys = (col("k", T.BIGINT),)
    b = _page({"k": (np.zeros(8, np.int64), T.BIGINT, None),
               "v": (np.arange(8), T.BIGINT, None)}, count=0)
    p = _page({"k": (np.arange(64, dtype=np.int64), T.BIGINT, None),
               "w": (np.arange(64), T.BIGINT, None)})
    bs = build_sorted(b, keys)
    out = join_n1(p, bs, keys, ("v",), ("bv",))
    assert int(out.count) == 0
    out = join_n1(p, bs, keys, ("v",), ("bv",), kind="anti")
    assert int(out.count) == 64
    # empty probe partition
    p0 = _page({"k": (np.arange(16, dtype=np.int64), T.BIGINT, None),
                "w": (np.arange(16), T.BIGINT, None)}, count=0)
    out = join_n1(p0, bs, keys, ("v",), ("bv",))
    assert int(out.count) == 0


def test_varchar_cross_dictionary_join():
    """Different dictionaries on the two sides: value hashing + unified
    code verification must find every match."""
    b = Page.from_dict(
        {"k": [f"s{i:03d}" for i in range(200)],
         "v": np.arange(200, dtype=np.int64)}
    )
    rng = np.random.default_rng(11)
    pk = [f"s{i:03d}" for i in rng.integers(0, 260, 700)]
    p = Page.from_dict({"k": pk, "w": np.arange(700, dtype=np.int64)})
    kt = b.block("k").type
    keys = (col("k", kt),)
    assert b.block("k").dict_id != p.block("k").dict_id
    bs = build_sorted(b, keys)
    assert bs.value_hashed
    got = _rows(join_n1(p, bs, keys, ("v",), ("bv",)), ("w", "bv"))
    # python oracle over VALUES: a code-hash join drops cross-dictionary
    # matches; the eager, value-hashed build must find every one
    from presto_tpu.page import dictionary_by_id

    bd = dictionary_by_id(b.block("k").dict_id)
    pd_ = dictionary_by_id(p.block("k").dict_id)
    bcodes = np.asarray(b.block("k").data)
    pcodes = np.asarray(p.block("k").data)
    by_val = {bd[int(c)]: i for i, c in enumerate(bcodes)}
    oracle = sorted(
        (w, by_val[pd_[int(c)]])
        for w, c in enumerate(pcodes)
        if pd_[int(c)] in by_val
    )
    assert got == oracle and len(got) > 0


@pytest.mark.parametrize("shape", ["n1_jit", "join_sql", "semi_sql"])
def test_varchar_cross_dictionary_traced_join(shape):
    """The same under a trace, where the executor runs its joins: the
    two sides are two tables with a dictionary each, so a traced build
    must hash values too (PR 31: code-hashed, it met 87 of 541 pairs)."""
    import jax

    b = Page.from_dict(
        {"k": [f"s{i:03d}" for i in range(200)],
         "v": np.arange(200, dtype=np.int64)}
    )
    rng = np.random.default_rng(11)
    pk = [f"s{i:03d}" for i in rng.integers(0, 260, 700)]
    p = Page.from_dict({"pk": pk, "w": np.arange(700, dtype=np.int64)})
    assert b.block("k").dict_id != p.block("pk").dict_id
    want = sorted((w, int(x[1:])) for w, x in enumerate(pk) if int(x[1:]) < 200)
    if shape == "n1_jit":
        kt = b.block("k").type
        fn = jax.jit(
            lambda p_, b_: join_n1(
                p_, build_sorted(b_, (col("k", kt),)), (col("pk", kt),),
                ("v",), ("bv",),
            )
        )
        assert _rows(fn(p, b), ("w", "bv")) == want
        return
    s = Session(MemoryCatalog({"b": b, "p": p}))
    if shape == "join_sql":
        got = s.query("select w, v from p join b on pk = k").rows()
        assert sorted(got) == want
    else:
        got = s.query("select w from p where pk in (select k from b)").rows()
        assert sorted(got) == [(w,) for w, _ in want]


# ---------------------------------------------------------------------------
# ranking sorted queries: the bucket directory, join_expand's slot map
# ---------------------------------------------------------------------------


def _rank_case(case, rng):
    """(sorted values, nq) of one shape the join programs rank."""
    if case == "empty":
        return np.zeros(0, np.int32), 9
    if case == "one":
        return np.array([3], np.int32), 7
    if case == "no_queries":
        return np.array([0, 2, 2], np.int32), 0
    if case == "duplicates":  # long runs of equal values
        return np.sort(rng.integers(0, 6, 500)).astype(np.int32), 8
    if case == "empty_ranges":  # most queries fall between two values
        return np.sort(rng.choice([0, 40, 41, 97], 300)).astype(np.int32), 128
    if case == "past_the_end":  # values at and far beyond the last query
        return np.sort(rng.integers(0, 3 * 64, 400)).astype(np.int32), 64
    if case == "all_dead":  # an empty build: every row in the last bucket
        nb = 1 << 9
        return np.full(256, nb - 1, np.int32), nb + 1
    if case == "clamped_offsets":  # int64 offsets whose total passes nq
        counts = rng.integers(0, 5, 300)
        return np.cumsum(counts).astype(np.int64), 256
    raise AssertionError(case)


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize(
    "case",
    ["empty", "one", "no_queries", "duplicates", "empty_ranges",
     "past_the_end", "all_dead", "clamped_offsets"],
)
def test_sorted_rank_is_searchsorted(case, side):
    """The merge gives np.searchsorted's ranks exactly, as int32."""
    a, nq = _rank_case(case, np.random.default_rng(len(case)))
    want = np.searchsorted(a, np.arange(nq), side=side)
    got = sorted_rank(jnp.asarray(a), nq, side)
    assert got.dtype == jnp.int32
    assert np.asarray(got).tolist() == want.tolist(), (case, side)


@pytest.mark.parametrize("capacity,count", [(1000, 1000), (4096, 3000), (300, 0)])
def test_directory_equals_the_searched_one(capacity, count):
    """build_sorted's bucket_start is the directory jnp.searchsorted
    built, dead rows (MAX_HASH, the last bucket) included."""
    rng = np.random.default_rng(capacity)
    k = rng.integers(0, capacity // 3 + 1, capacity).astype(np.int64)
    bs = build_sorted(_page({"k": (k, T.BIGINT, None)}, count=count),
                      (col("k", T.BIGINT),))
    bits = bs.bucket_bits
    bucket = (bs.sorted_hash >> np.uint64(64 - bits)).astype(jnp.int32)
    want = jnp.searchsorted(
        bucket, jnp.arange((1 << bits) + 1, dtype=jnp.int32), side="left"
    )
    assert np.asarray(bs.bucket_start).tolist() == np.asarray(want).tolist()


def test_expand_overflow_equals_the_search_form(monkeypatch):
    """At an out_capacity below the candidates' total, join_expand's
    overflow and rows are the search form's, slot for slot."""
    rng = np.random.default_rng(7)
    bk = rng.integers(0, 40, 600).astype(np.int64)
    pk = rng.integers(0, 50, 900).astype(np.int64)
    b = _page({"k": (bk, T.BIGINT, None), "v": (np.arange(600), T.BIGINT, None)})
    p = _page({"k": (pk, T.BIGINT, None), "w": (np.arange(900), T.BIGINT, None)},
              count=850)
    keys = (col("k", T.BIGINT),)

    def run():
        out, ov = join_expand(p, build_sorted(b, keys), keys, ("w",),
                              [("v", "bv")], 4096)
        return int(ov), int(out.count), [
            np.asarray(out.block(n).data).tolist() for n in ("w", "bv")
        ]

    merged = run()
    monkeypatch.setattr(J, "sorted_rank", lambda a, nq, side: jnp.searchsorted(
        a, jnp.arange(nq, dtype=jnp.int32), side=side).astype(jnp.int32))
    searched = run()
    assert merged[0] > 0  # the total passed the capacity
    assert merged == searched


# ---------------------------------------------------------------------------
# hash-slot group-by
# ---------------------------------------------------------------------------


def _agg_oracle_compare(page, gexprs, names, aggs, out):
    from presto_tpu.ops.aggregate import grouped_aggregate_sorted

    want = grouped_aggregate_sorted(page, gexprs, names, aggs, 1 << 12, None)
    all_names = list(names) + [a.name for a in aggs]
    got_rows = _rows(out, all_names)
    want_rows = _rows(want, all_names)
    assert len(got_rows) == len(want_rows)
    for g, w in zip(got_rows, want_rows):
        for a, b in zip(g, w):
            if isinstance(a, float) and isinstance(b, float):
                if a != a and b != b:
                    continue  # NaN group keys compare equal (grouping)
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9)
            else:
                assert a == b


@pytest.mark.parametrize("nulls", [False, True])
def test_hash_groupby_property(nulls):
    from presto_tpu.ops.aggregate import AggSpec
    from presto_tpu.ops.pallas_groupby import maybe_grouped_aggregate_hash

    rng = np.random.default_rng(13 + nulls)
    n = 20_000
    keys = (rng.integers(0, 300, n) * 104729 - 7).astype(np.int64)
    vals = rng.integers(-(10**9), 10**9, n)
    fv = rng.normal(size=n) * 1e3
    kv = (rng.random(n) > 0.1) if nulls else None
    vv = (rng.random(n) > 0.15) if nulls else None
    page = _page({
        "k": (keys, T.BIGINT, kv),
        "v": (vals, T.BIGINT, vv),
        "f": (fv, T.DOUBLE, None),
    })
    gexprs = (col("k", T.BIGINT),)
    aggs = (
        AggSpec("count_star", None, "c", T.BIGINT),
        AggSpec("count", col("v", T.BIGINT), "cv", T.BIGINT),
        AggSpec("sum", col("v", T.BIGINT), "s",
                AggSpec.infer_output_type("sum", T.BIGINT)),
        AggSpec("avg", col("f", T.DOUBLE), "af",
                AggSpec.infer_output_type("avg", T.DOUBLE)),
        AggSpec("min", col("v", T.BIGINT), "mn", T.BIGINT),
        AggSpec("max", col("v", T.BIGINT), "mx", T.BIGINT),
    )
    out = maybe_grouped_aggregate_hash(page, gexprs, ("k",), aggs, None)
    assert out is not None
    _agg_oracle_compare(page, gexprs, ("k",), aggs, out)


def test_hash_groupby_nan_and_composite_keys():
    from presto_tpu.ops.aggregate import AggSpec
    from presto_tpu.ops.pallas_groupby import maybe_grouped_aggregate_hash

    rng = np.random.default_rng(21)
    n = 5000
    k1 = np.where(rng.random(n) < 0.1, np.nan, rng.integers(0, 20, n) * 1.0)
    k2 = rng.integers(0, 7, n).astype(np.int64)
    page = _page({
        "a": (k1, T.DOUBLE, None),
        "b": (k2, T.BIGINT, None),
        "v": (rng.integers(0, 1000, n), T.BIGINT, None),
    })
    gexprs = (col("a", T.DOUBLE), col("b", T.BIGINT))
    aggs = (AggSpec("sum", col("v", T.BIGINT), "s",
                    AggSpec.infer_output_type("sum", T.BIGINT)),
            AggSpec("count_star", None, "c", T.BIGINT))
    out = maybe_grouped_aggregate_hash(page, gexprs, ("a", "b"), aggs, None)
    assert out is not None
    # all NaN keys form ONE group per b value (doubleToLongBits grouping)
    _agg_oracle_compare(page, gexprs, ("a", "b"), aggs, out)


def test_hash_groupby_high_ndv_falls_back():
    from presto_tpu.ops.aggregate import AggSpec
    from presto_tpu.ops.pallas_groupby import (
        HASH_MAX_GROUPS_HOST,
        maybe_grouped_aggregate_hash,
    )

    n = 4 * HASH_MAX_GROUPS_HOST
    page = _page({
        "k": (np.arange(n, dtype=np.int64), T.BIGINT, None),
        "v": (np.ones(n, np.int64), T.BIGINT, None),
    })
    aggs = (AggSpec("count_star", None, "c", T.BIGINT),)
    assert maybe_grouped_aggregate_hash(
        page, (col("k", T.BIGINT),), ("k",), aggs, None
    ) is None


def test_hash_groupby_breaker(monkeypatch):
    from presto_tpu.connectors.tpch import TpchCatalog

    cat = TpchCatalog(sf=0.01)
    sql = ("select o_custkey, count(*) c, sum(o_totalprice) s "
           "from orders group by o_custkey")
    want = sorted(Session(cat).query(sql).rows())
    br = BREAKERS.get("pallas_groupby_hash")
    for _ in range(br.failure_threshold):
        br.record_failure("injected")
    assert sorted(Session(cat).query(sql).rows()) == want


# ---------------------------------------------------------------------------
# ragged paged layout
# ---------------------------------------------------------------------------


def test_ragged_layout_invariants():
    rng = np.random.default_rng(5)
    parts = [
        rng.permutation(100)[:n].astype(np.int64)
        for n in (0, 1, 5, 700, 64, 0, 33)
    ]
    # give partitions disjoint global row ids
    base = 0
    gparts = []
    for p in parts:
        gparts.append(p + base)
        base += 1000
    rp = ragged.from_partitions(gparts, page_rows=64)
    assert rp.num_parts == len(parts)
    assert rp.total_rows == sum(len(p) for p in parts)
    for i, p in enumerate(gparts):
        got = rp.part_rows(i)
        assert got.tolist() == p.tolist()
        assert rp.part_num_rows(i) == len(p)
    # only the last page of a partition may be partial
    for pid in range(rp.num_parts):
        lo, hi = int(rp.page_start[pid]), int(rp.page_start[pid + 1])
        pages = rp.page_ids[lo:hi]
        for g in pages[:-1]:
            assert rp.rows_in_page[g] == rp.page_rows
    assert 0 < rp.occupancy() <= 1.0
    # pad-to-max would over-allocate vs the ragged pages on this skew
    assert rp.padded_waste_ratio() > 1.0
    # lane gather: dead slots get the fill value
    col_ = np.arange(base, dtype=np.int64) * 3
    lane = rp.lane(col_, fill=-1)
    assert lane.shape == (rp.num_pages, 64)
    for pid in (2, 3, 6):
        rows = rp.part_rows(pid)
        lo = int(rp.page_start[pid])
        flat = lane[rp.page_ids[lo : int(rp.page_start[pid + 1])]].reshape(-1)
        assert flat[: len(rows)].tolist() == (rows * 3).tolist()
        assert (flat[len(rows):] == -1).all()


def test_ragged_empty():
    rp = ragged.from_partitions([], page_rows=32)
    assert rp.num_pages == 0 and rp.occupancy() == 1.0


def test_hybrid_join_ragged_recursion_tiny_budget(monkeypatch):
    """Recursion-into-ragged-pages at a tiny memory budget (the
    tests/test_memory_pressure.py harness shape): oracle-equal, with the
    ragged layout stats populated and surfaced in EXPLAIN ANALYZE."""
    monkeypatch.setenv("PRESTO_TPU_HOST_SPILL_BYTES", "0")
    monkeypatch.setenv("PRESTO_TPU_HYBRID_JOIN_PARTS", "4")
    rng = np.random.default_rng(3)
    n_build, n_probe = 4_000, 8_000
    # skewed build: partition sizes differ wildly, so pad-to-max would
    # burn memory exactly where the budget is tightest
    bk = np.where(
        rng.random(n_build) < 0.5, 7, np.arange(n_build)
    ).astype(np.int64)
    b = Page.from_dict(
        {"bk": bk, "bv": rng.integers(0, 1000, n_build).astype(np.int64)}
    )
    p = Page.from_dict({
        "pk": rng.integers(0, n_build, n_probe).astype(np.int64),
        "pv": rng.integers(0, 1000, n_probe).astype(np.int64),
    })
    cat = MemoryCatalog({"b": b, "p": p})
    sql = "select count(*) c, sum(bv + pv) s from p join b on pk = bk"
    want = Session(cat).query(sql).rows()
    s = Session(
        cat, streaming=True, batch_rows=2048,
        memory_budget=(n_build * 16) // 16,
    )
    assert s.query(sql).rows() == want
    st = s.executor.spill_stats
    assert "hybrid_hash_join" in s.executor.spill_events
    assert st["ragged_pages"] > 0, st
    assert 0 < st["ragged_occupancy_pct"] <= 100
    txt = s.explain_analyze(sql)
    assert "ragged pages=" in txt and "occ=" in txt


# ---------------------------------------------------------------------------
# engine wiring: strategy notes, a star query
# ---------------------------------------------------------------------------


def test_explain_analyze_join_strategy_note():
    from presto_tpu.connectors.tpch import TpchCatalog

    s = Session(TpchCatalog(sf=0.01))
    txt = s.explain_analyze(
        "select count(*) from lineitem, orders where l_orderkey = o_orderkey"
    )
    assert "strategy=sorted-hash(directory)" in txt, txt


def test_multiway_star_fusion_oracle():
    """Two stacked n1 joins with both keys on the fact side (the planner
    knows the build keys are unique, so TPC-H PK joins are the shape)
    run as two plain joins, one after the other, on every backend: the
    answer is the SQLite oracle's and no node is fused."""
    from presto_tpu.connectors.tpch import TpchCatalog
    from presto_tpu.testing.oracle import SqliteOracle, assert_same_results

    sql = (
        "select count(*) c, "
        "sum(l_extendedprice + o_totalprice + s_acctbal) v from lineitem "
        "join orders on l_orderkey = o_orderkey "
        "join supplier on l_suppkey = s_suppkey"
    )
    s = Session(TpchCatalog(sf=0.01), result_cache=False)
    result = s.query(sql)
    assert_same_results(
        result.rows(), SqliteOracle(sf=0.01).query(sql),
        [b.type for b in result.page.blocks], ordered=False,
    )
    txt = s.explain_analyze(sql)
    assert "multiway" not in txt, txt
    assert txt.count("strategy=sorted-hash(directory)") == 2, txt
