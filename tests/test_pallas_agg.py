"""Hand-written Pallas Q1 kernel vs the XLA composition (exact match).

Runs in interpret mode on the CPU test mesh; on a TPU backend the same
kernel compiles under Mosaic (tests/test_tpu_compile.py compiles it for
a described v5e) and bench.py times it. Correctness of the limb decomposition and
per-block combine is fully exercised either way."""

import pytest

from presto_tpu.benchmark.handcoded import (
    lineitem_q1_page,
    q1_local,
    q1_local_pallas,
)


def _pallas_rows(cat, sql):
    """Rows through Session(pallas_groupby=True), asserting that the
    Pallas path really produced them: an import or trace error used to
    degrade to the XLA composition behind the breaker, and the test then
    compared the fallback with itself."""
    from presto_tpu.exec.breaker import BREAKERS
    from presto_tpu.session import Session

    BREAKERS.reset()
    sess = Session(cat, pallas_groupby=True)
    rows = sess.query(sql).rows()
    snap = BREAKERS.snapshot().get("pallas_groupby")
    assert snap and snap["total_successes"] >= 1, snap
    assert snap["total_failures"] == 0 and snap["state"] == "closed", snap
    assert "strategy=pallas" in sess.explain_analyze(sql)
    return rows


def test_pallas_q1_matches_xla():
    page = lineitem_q1_page(0.01)
    want = q1_local(page).to_pylist()
    got = q1_local_pallas(page).to_pylist()
    assert len(want) == 4
    assert got == want


def test_pallas_q1_partial_batch_boundary():
    # capacity not a multiple of the block size exercises padding + the
    # count-based liveness mask
    page = lineitem_q1_page(0.003)
    assert page.capacity % 16384 != 0
    want = q1_local(page).to_pylist()
    got = q1_local_pallas(page).to_pylist()
    assert got == want


# -- generalized pallas_groupby: float64 sum/avg + count_if + auto-default


def test_pallas_groupby_float_and_countif():
    """float64 sum/avg ride the hi/lo f32 channel path (tolerance is
    ~f32 ulps of sum(|x|) — the documented contract); count_if and
    integer sums stay bit-exact."""
    import numpy as np

    from presto_tpu.connectors.memory import MemoryCatalog
    from presto_tpu.page import Page
    from presto_tpu.session import Session

    rng = np.random.default_rng(5)
    n = 40000
    pool = ("A", "N", "R")
    flag = np.array([pool[i] for i in rng.integers(0, 3, n)])
    d = rng.random(n) * 1e6 - 5e5
    v = rng.integers(-1000, 1000, n)
    cat = MemoryCatalog(
        {"t": Page.from_dict({"f": list(flag), "d": d, "v": v})}
    )
    sql = (
        "select f, sum(d) sd, avg(d) ad, count_if(v > 0) ci, sum(v) sv "
        "from t group by f order by f"
    )
    ref = Session(cat, pallas_groupby=False).query(sql).rows()
    pal = _pallas_rows(cat, sql)
    assert len(ref) == 3
    for r, p in zip(ref, pal):
        mag = np.abs(d[flag == r[0]]).sum()
        assert (r[0], r[3], r[4]) == (p[0], p[3], p[4])
        assert abs(r[1] - p[1]) < mag * 1e-6
        assert abs(r[2] - p[2]) < mag * 1e-6


def test_pallas_groupby_min_max_and_empty_group():
    """min/max channels combine across blocks AND lanes by min/max (the
    imax/imin in-kernel fill values must survive the per-lane partial
    layout); a key value absent from the data exercises empty-group
    compaction."""
    import numpy as np

    from presto_tpu.connectors.memory import MemoryCatalog
    from presto_tpu.page import Page
    from presto_tpu.session import Session

    rng = np.random.default_rng(11)
    n = 50000  # spans multiple 16384-row kernel blocks
    pool = ("A", "N", "R", "Z")  # "Z" never drawn -> empty group
    flag = np.array([pool[i] for i in rng.integers(0, 3, n)])
    v = rng.integers(-(10**9), 10**9, n)
    cat = MemoryCatalog(
        {"t": Page.from_dict({"f": list(flag), "v": v})}
    )
    sql = (
        "select f, min(v) mn, max(v) mx, sum(v) sv, count(*) c "
        "from t group by f order by f"
    )
    ref = Session(cat, pallas_groupby=False).query(sql).rows()
    pal = _pallas_rows(cat, sql)
    assert len(ref) == 3
    assert pal == ref


def test_pallas_groupby_null_key_group():
    """A NULL group key forms its own group (SQL GROUP BY) — the kernel
    path must not silently drop those rows (round-5 regression: `live`
    used to AND away null keys)."""
    import numpy as np

    from presto_tpu import types as T
    from presto_tpu.connectors.memory import MemoryCatalog
    from presto_tpu.page import Block, Page
    from presto_tpu.session import Session

    fb = Block.from_numpy(
        np.array([0, 1, 0, 1, 0], np.int32), T.VARCHAR,
        valid=np.array([True, True, False, True, True]),
        dictionary=("A", "B"),
    )
    vb = Block.from_numpy(np.array([1, 2, 4, 8, 16], np.int64), T.BIGINT)
    cat = MemoryCatalog({"t": Page.from_blocks([fb, vb], ["f", "v"])})
    sql = "select f, sum(v) s, count(*) c from t group by f"
    ref = sorted(
        Session(cat, pallas_groupby=False).query(sql).rows(), key=str
    )
    pal = sorted(
        _pallas_rows(cat, sql), key=str
    )
    assert ref == pal
    assert (None, 4, 1) in pal


def test_pallas_groupby_auto_default_off_on_cpu():
    """pallas_groupby=None resolves to the backend default at first
    aggregation: False on CPU (interpret would crawl), True on TPU."""
    import numpy as np

    from presto_tpu.connectors.memory import MemoryCatalog
    from presto_tpu.page import Page
    from presto_tpu.session import Session

    cat = MemoryCatalog(
        {"t": Page.from_dict({"v": np.array([1, 2], dtype=np.int64)})}
    )
    s = Session(cat)
    assert s.executor.pallas_groupby is None  # unresolved until used
    s.query("select count(*) c from t group by v")
    assert s.executor.pallas_groupby is False  # CPU backend in tests


def test_pallas_groupby_g63_matches_sort_strategy():
    """Round-5 G-cap raise (32 -> 64): a 63-way dictionary group-by is
    pallas-eligible and matches the hash-sort strategy exactly."""
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu import types as T
    from presto_tpu.expr.ir import col
    from presto_tpu.ops.aggregate import AggSpec, grouped_aggregate_sorted
    from presto_tpu.ops.pallas_groupby import maybe_grouped_aggregate
    from presto_tpu.page import Block, Page, intern_dictionary

    rng = np.random.default_rng(0)
    n = 50000
    codes = rng.integers(0, 63, n).astype(np.int32)
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    d = intern_dictionary(tuple(f"k{i:02d}" for i in range(63)))
    pg = Page(
        (
            Block(jnp.asarray(codes), T.VARCHAR, None, d),
            Block(jnp.asarray(vals), T.BIGINT),
        ),
        ("g", "v"),
        jnp.asarray(n, jnp.int32),
    )
    aggs = (
        AggSpec("sum", col("v", T.BIGINT), "s", T.BIGINT),
        AggSpec("count_star", None, "c", T.BIGINT),
    )
    out = maybe_grouped_aggregate(pg, (col("g", T.VARCHAR),), ("g",), aggs, None)
    assert out is not None
    want = grouped_aggregate_sorted(
        pg, (col("g", T.VARCHAR),), ("g",), aggs, 128
    )
    assert sorted(out.to_pylist()) == sorted(want.to_pylist())


# -- the fused program: one trace per plan shape, mask literals as operands


def _fused_case(name):
    """(page, group_exprs, group_names, aggs, mask, expected groups)."""
    import jax.numpy as jnp
    import numpy as np

    from presto_tpu import types as T
    from presto_tpu.benchmark.handcoded import (
        Q1_GROUP_NAMES,
        Q1_GROUPS,
        Q1_PREDICATE,
        q1_aggs,
    )
    from presto_tpu.expr import ir
    from presto_tpu.expr.ir import col
    from presto_tpu.ops.aggregate import AggSpec
    from presto_tpu.page import Block, Page, intern_dictionary

    if name == "q1":
        return (
            lineitem_q1_page(0.003), Q1_GROUPS, Q1_GROUP_NAMES, q1_aggs(),
            Q1_PREDICATE, 4,
        )
    rng = np.random.default_rng(3)
    n = 20000
    d = intern_dictionary(("A", "N", "R"))
    blocks = (
        Block(
            jnp.asarray(rng.integers(0, 3, n).astype(np.int32)), T.VARCHAR,
            jnp.asarray(rng.random(n) > 0.1) if name == "null_key" else None,
            d,
        ),
        Block(jnp.asarray(rng.integers(-(10**9), 10**9, n)), T.BIGINT),
        Block(jnp.asarray(rng.random(n) * 1e6 - 5e5), T.DOUBLE),
    )
    page = Page(blocks, ("g", "v", "x"), jnp.asarray(n - 7, jnp.int32))
    v, x = col("v", T.BIGINT), col("x", T.DOUBLE)
    aggs = {
        "null_key": (
            AggSpec("sum", v, "s", T.BIGINT),
            AggSpec("count_star", None, "c", T.BIGINT),
        ),
        "float": (
            AggSpec("sum", x, "sx", T.DOUBLE),
            AggSpec("avg", x, "ax", T.DOUBLE),
            AggSpec("count", v, "c", T.BIGINT),
        ),
        "min_max": (
            AggSpec("min", v, "mn", T.BIGINT),
            AggSpec("max", v, "mx", T.BIGINT),
        ),
        "all_filtered": (
            AggSpec("sum", v, "s", T.BIGINT),
            AggSpec("avg", v, "a", T.DOUBLE),
        ),
    }[name]
    # a bigint bound no row passes empties the page; else about half pass
    bound = 2 * 10**9 if name == "all_filtered" else 0
    mask = ir.comparison("ge", v, ir.Literal(bound, T.BIGINT))
    groups = {"null_key": 4, "all_filtered": 0}.get(name, 3)
    return page, (col("g", T.VARCHAR),), ("g",), aggs, mask, groups


@pytest.mark.parametrize(
    "case", ["q1", "null_key", "float", "min_max", "all_filtered"]
)
def test_fused_program_matches_sort_strategy(case):
    """The dense group-by as the executor launches it (ONE jitted program,
    the mask's literal an operand) against `grouped_aggregate_sorted`:
    exact for integers and decimals, f32-ulp close for float sums."""
    from presto_tpu.connectors.memory import MemoryCatalog
    from presto_tpu.exec.breaker import BREAKERS
    from presto_tpu.exec.executor import Executor
    from presto_tpu.ops.aggregate import grouped_aggregate_sorted
    from presto_tpu.plan import nodes as N

    page, group_exprs, group_names, aggs, mask, groups = _fused_case(case)
    BREAKERS.reset()
    node = N.Aggregate(None, group_exprs, group_names, tuple(aggs), mask)
    out = Executor(MemoryCatalog({}))._try_pallas_groupby(node, page)
    assert out is not None
    assert BREAKERS.snapshot()["pallas_groupby"]["total_failures"] == 0
    want = grouped_aggregate_sorted(
        page, group_exprs, group_names, aggs, 64, mask
    )
    key = lambda row: str(row[: len(group_names)])  # noqa: E731
    got, want = sorted(out.to_pylist(), key=key), sorted(
        want.to_pylist(), key=key
    )
    assert len(got) == len(want) == groups
    for g, w in zip(got, want):
        assert g == pytest.approx(w, rel=1e-6) if case == "float" else g == w
