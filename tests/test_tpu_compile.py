"""Ahead-of-time compiles for a described (not attached) TPU v5e of the
kernels the TPU-default path of TPC-H Q1/Q6/Q3/Q18 reaches, at SF1
widths. Nothing runs here — a pass says the chip's compiler accepts the
program and it fits device memory, not that its results are right (the
interpret-mode and oracle tests do that) and not how fast it is.

The topology is described inside a module-scoped fixture (never at
import): only one process may hold the TPU library, and under xdist
every worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

LINEITEM_SF1 = 8_388_608  # round_capacity(6,000,000)
ORDERS_SF1 = 2_097_152  # round_capacity(1,500,000)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compile cache
    off (an entry written for a described device cannot be read back
    without a chip and would warn on every later run)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture()
def as_tpu(monkeypatch):
    """The kernels pick interpret mode from jax.default_backend(), which
    is `cpu` during such a compile: steer it here, not through an option
    of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_q1_partial_sums_sf1(one_chip, as_tpu):
    from presto_tpu.ops.pallas_agg import q1_partial_sums

    col = _spec((LINEITEM_SF1,), jnp.int32, one_chip)
    sc = _spec((), jnp.int32, one_chip)
    c = _compile(q1_partial_sums, *([col] * 7), sc, sc)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize(
    "groups,nch,dtype,kinds",
    [
        (6, 23, jnp.int32, None),  # Q1 through SQL: 8 aggs as limb channels
        (63, 7, jnp.int32, ("add",) * 5 + ("min", "max")),
        (6, 8, jnp.float32, None),  # hi/lo-split f64 sums
        (63, 8, jnp.float32, None),
        (512, 2, jnp.int32, None),  # hash-slot gids (_pallas_accumulate)
    ],
    ids=["g6-int", "g63-int-minmax", "g6-f32", "g63-f32", "g512-hash"],
)
def test_pallas_groupby_partials_sf1(one_chip, as_tpu, groups, nch, dtype, kinds):
    from presto_tpu.ops.pallas_groupby import _pallas_partials

    kinds = kinds or ("add",) * nch
    vec = lambda dt: _spec((LINEITEM_SF1,), dt, one_chip)

    def fn(gid, live, count, *channels):
        return _pallas_partials(
            gid, live, list(channels), count, groups, kinds, dtype=dtype
        )

    c = _compile(
        fn, vec(jnp.int32), vec(jnp.bool_), _spec((), jnp.int32, one_chip),
        *[vec(dtype)] * nch,
    )
    assert "tpu_custom_call" in c.as_text()


def test_fused_q1_groupby_sf1(one_chip, as_tpu):
    """Q1's whole `Aggregate` as the executor launches it: the decimal
    arithmetic, the limb split and the kernel in one program, DELTA an
    operand. (At 2**26 rows, SF10's width, the same compile reports 2.95
    GB of arguments and 5.10 GB of temporaries.)"""
    from presto_tpu.benchmark.handcoded import (
        Q1_GROUP_NAMES,
        Q1_GROUPS,
        Q1_PREDICATE,
        lineitem_q1_page,
        q1_aggs,
    )
    from presto_tpu.exec.qcache import lift_literals, rebind_plan
    from presto_tpu.ops.pallas_groupby import maybe_grouped_aggregate

    mask, operands = lift_literals(Q1_PREDICATE)
    assert len(operands) == 1

    def fn(page, ops):
        return maybe_grouped_aggregate(
            page, Q1_GROUPS, Q1_GROUP_NAMES, q1_aggs(),
            rebind_plan(mask, ops),
        )

    page = jax.tree_util.tree_map(
        lambda x: _spec((LINEITEM_SF1,) * x.ndim, x.dtype, one_chip),
        lineitem_q1_page(0.001),
    )
    c = _compile(fn, page, (_spec((), operands[0].dtype, one_chip),))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


def test_streamed_q1_partial_groupby_batch(one_chip, as_tpu):
    """The streaming sink's per-batch partial aggregation of Q1
    (`StreamingExecutor._pallas_agg_attempt`, the cell `sf10s.scan_agg`):
    `decompose_partial`'s sums and counts over one 2**20-row batch as
    ONE program, DELTA an operand."""
    from presto_tpu.benchmark.handcoded import (
        Q1_GROUP_NAMES,
        Q1_GROUPS,
        Q1_PREDICATE,
        lineitem_q1_page,
        q1_aggs,
    )
    from presto_tpu.exec.qcache import lift_literals, rebind_plan
    from presto_tpu.ops.aggregate import decompose_partial
    from presto_tpu.ops.pallas_groupby import maybe_grouped_aggregate

    partial, _final, _post = decompose_partial(q1_aggs())
    mask, operands = lift_literals(Q1_PREDICATE)

    def fn(page, ops):
        return maybe_grouped_aggregate(
            page, Q1_GROUPS, Q1_GROUP_NAMES, tuple(partial),
            rebind_plan(mask, ops),
        )

    page = jax.tree_util.tree_map(
        lambda x: _spec((1 << 20,) * x.ndim, x.dtype, one_chip),
        lineitem_q1_page(0.001),
    )
    c = _compile(fn, page, (_spec((), operands[0].dtype, one_chip),))
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 256 << 20


def test_matmul_agg_g4096_sf1(one_chip):
    from presto_tpu.ops.matmul_agg import grouped_matmul_partials

    nch = 17  # count + one sign-split 8-limb int64 sum

    def fn(gid, *channels):
        return grouped_matmul_partials(gid, list(channels), 4096)

    _compile(
        fn, _spec((LINEITEM_SF1,), jnp.int32, one_chip),
        *[_spec((LINEITEM_SF1,), jnp.bfloat16, one_chip)] * nch,
    )


def test_compaction_sort_sf1(one_chip):
    """Every filter compiles one of these per page capacity; the
    single-operand form is what keeps that to seconds."""
    from presto_tpu.ops.filter import kept_first_permutation

    _compile(
        kept_first_permutation, _spec((LINEITEM_SF1,), jnp.bool_, one_chip)
    )


def test_wide_segment_sum_sf10(one_chip):
    """A decimal sum per group over SF10's lineitem (Q18's subquery at
    the sort strategy's first guess of 65,536 groups): as ONE scatter of
    (rows, 2) lane pairs the compiler held the updates as u32[rows, 2]
    tiled (8, 128), 30.7 GB, and the statement died with
    RESOURCE_EXHAUSTED (chip run, PR 33); a scatter per lane fits."""
    from presto_tpu.ops import decimal128 as d128

    rows, groups = 59_994_841, (1 << 16) + 1
    compiled = _compile(
        lambda x, gid: d128.segment_sum_wide(
            d128.from_int64(x), gid, groups
        ),
        _spec((rows,), jnp.int64, one_chip),
        _spec((rows,), jnp.int32, one_chip),
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


LINEITEM_SF10_FULL = 59_994_841  # TpchCatalog(sf=10)'s lineitem


def test_selective_compaction_sf10(one_chip):
    """What a dynamic filter runs over SF10's lineitem (PR 33): the
    compare-all IN-list mask and `compact_few`, neither with a
    full-capacity gather or a 60M-row sort to compile."""
    from presto_tpu import types as T
    from presto_tpu.exec.dynfilter import _inlist_mask
    from presto_tpu.ops.filter import compact_few

    page = _page_specs(
        [(jnp.int64, T.BIGINT), (jnp.int64, T.DecimalType(12, 2))],
        LINEITEM_SF10_FULL, one_chip,
    )
    keep = _spec((LINEITEM_SF10_FULL,), jnp.bool_, one_chip)
    c = compact_few.lower(page, keep, cap=1024).compile()
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
    c = _inlist_mask.lower(
        _spec((128,), jnp.int64, one_chip),
        _spec((LINEITEM_SF10_FULL,), jnp.int64, one_chip),
    ).compile()
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


LINEITEM_SF1_FULL = 6_000_859  # TpchCatalog(sf=1)'s lineitem, as stored


def test_selective_compaction_q3_sf1(one_chip):
    """What Q3's lineitem `Filter` runs behind its runtime filter since
    PR 34: `compact_few` over the four columns it carries, ~51,000 rows
    kept (the 65,536 bucket), in place of a 6M-row sort and four 6M-row
    gathers. No `lax.sort` in it (one at this size took 15-50 s on the
    chip machine): 8-9 s to compile in this sandbox (PR 34)."""
    from presto_tpu import types as T
    from presto_tpu.ops.filter import compact_few

    dec = T.DecimalType(12, 2)
    page = _page_specs(
        [
            (jnp.int64, T.BIGINT), (jnp.int64, dec), (jnp.int64, dec),
            (jnp.int32, T.DATE),
        ],
        LINEITEM_SF1_FULL, one_chip,
    )
    keep = _spec((LINEITEM_SF1_FULL,), jnp.bool_, one_chip)
    c = compact_few.lower(page, keep, cap=1 << 16).compile()
    assert " sort(" not in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 256 << 20


@pytest.mark.slow
def test_run_sum_group_by_sf10(one_chip):
    """Q18's subquery at SF10 in the run-sum form: two payload-carrying
    60M-row sorts (150 s to compile in this sandbox), 2.6 GB of
    temporaries beside the 6.6 GB resident."""
    from presto_tpu import types as T
    from presto_tpu.expr.ir import col
    from presto_tpu.ops.aggregate import AggSpec, grouped_aggregate_sorted

    dec = T.DecimalType(12, 2)
    page = _page_specs(
        [(jnp.int64, T.BIGINT), (jnp.int64, dec)], LINEITEM_SF10_FULL, one_chip
    )
    compiled = _compile(
        lambda p: grouped_aggregate_sorted(
            p, [col("c0", T.BIGINT)], ["k"],
            [AggSpec("sum", col("c1", dec), "s", T.DecimalType(38, 2))],
            1 << 24, runs=True,
        ),
        page,
    )
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def _page_specs(cols, capacity, sharding):
    """A Page of ShapeDtypeStructs (jit lowers pytrees of them)."""
    from presto_tpu.page import Block, Page

    blocks = tuple(
        Block(_spec((capacity,), dt, sharding), typ, None)
        for dt, typ in cols
    )
    names = tuple(f"c{i}" for i in range(len(cols)))
    return Page(blocks, names, _spec((), jnp.int32, sharding))


# The two XLA programs below sort: the TPU compiler spends minutes on a
# lax.sort in this sandbox (PR 22: ~150 s for the join, ~400 s for the
# sort + top_n pair, one core), so they stay out of tier-1.


@pytest.mark.slow
def test_sorted_join_directory_probe_sf1(one_chip):
    """orders (build) x lineitem (probe) through the sorted-hash build
    and the bucket-directory probe, one jit — memory, not lowering."""
    from presto_tpu import types as T
    from presto_tpu.expr.ir import ColumnRef
    from presto_tpu.ops import join as J

    build = _page_specs(
        [(jnp.int64, T.BIGINT), (jnp.int32, T.DATE), (jnp.int32, T.INTEGER)],
        ORDERS_SF1, one_chip,
    )
    probe = _page_specs(
        [(jnp.int64, T.BIGINT), (jnp.int64, T.BIGINT), (jnp.int64, T.BIGINT)],
        LINEITEM_SF1, one_chip,
    )
    key = (ColumnRef("c0", T.BIGINT),)

    def fn(b, p):
        bs = J.build_sorted(b, key)
        assert bs.bucket_start is not None  # the directory is the default
        return J.join_n1(p, bs, key, ["c1", "c2"], ["o_date", "o_prio"])

    _compile(fn, build, probe)


@pytest.mark.slow
def test_fused_sort_and_topn_sf1(one_chip):
    from presto_tpu import types as T
    from presto_tpu.expr.ir import ColumnRef
    from presto_tpu.ops.sort import SortKey, sort_page, top_n

    page = _page_specs(
        [(jnp.int64, T.BIGINT), (jnp.int32, T.DATE), (jnp.int64, T.BIGINT)],
        LINEITEM_SF1, one_chip,
    )
    keys = (
        SortKey(ColumnRef("c0", T.BIGINT), ascending=False),
        SortKey(ColumnRef("c1", T.DATE), ascending=True),
    )
    _compile(lambda p: sort_page(p, keys), page)
    _compile(lambda p: top_n(p, keys, 10), page)
