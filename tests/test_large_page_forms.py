"""The forms a page of 2^23 rows or more takes (SF10's lineitem and
orders; PR 33), each against the form it replaces there, at small sizes:

- `grouped_aggregate_sorted`'s run-sum form (ops/aggregate.py,
  `_grouped_aggregate_runs`: two payload-carrying sorts and prefix sums, no
  gather, no scatter): the same groups, sums, counts and NULLs, row for row;
- `compact_few` (ops/filter.py): `compact` for a mask that keeps few rows;
- `_inlist_mask` (exec/dynfilter.py): IN-list membership by comparison.
"""

import jax
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.expr.ir import col
from presto_tpu.ops import aggregate as agg
from presto_tpu.ops.aggregate import AggSpec, grouped_aggregate_sorted
from presto_tpu.page import Block, Page

DEC = T.DecimalType(12, 2)


def _page(seed, n, pad, ndv, null_keys, null_inputs):
    rng = np.random.default_rng(seed)
    k1 = rng.integers(-ndv, ndv, n).astype(np.int64)
    k2 = rng.integers(0, 3, n).astype(np.int32)
    x = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    d = rng.integers(-(1 << 62), 1 << 62, n).astype(np.int64)
    blocks = [
        Block.from_numpy(
            k1, T.BIGINT,
            valid=(rng.random(n) < 0.9) if null_keys else None,
        ),
        Block.from_numpy(k2, T.INTEGER),
        Block.from_numpy(
            x, T.BIGINT,
            valid=(rng.random(n) < 0.7) if null_inputs else None,
        ),
        Block.from_numpy(
            d, DEC, valid=(rng.random(n) < 0.5) if null_inputs else None
        ),
    ]
    page = Page.from_blocks(blocks, ["k1", "k2", "x", "d"])
    if pad != n:
        page = Page.from_dict(
            dict(zip(page.names, page.blocks)), pad_to=pad
        )
    return page


AGGS = [
    AggSpec("sum", col("x", T.BIGINT), "sx", T.BIGINT),
    AggSpec("sum", col("d", DEC), "sd", T.DecimalType(38, 2)),
    AggSpec("avg", col("d", DEC), "ad", DEC),
    AggSpec("avg", col("x", T.BIGINT), "ax", T.DOUBLE),
    AggSpec("count", col("x", T.BIGINT), "cx", T.BIGINT),
    AggSpec("count_star", None, "c", T.BIGINT),
]
KEYS = ([col("k1", T.BIGINT), col("k2", T.INTEGER)], ["k1", "k2"])


def _rows(page):
    return sorted(
        page.to_pylist(),
        key=lambda r: tuple((v is None, v) for v in r[:2]),
    )


@pytest.mark.parametrize(
    "n,pad,ndv,null_keys,null_inputs,max_groups",
    [
        (1000, 1024, 20, True, True, 256),
        (1000, 1000, 400, False, False, 4096),  # max_groups over capacity
        (777, 2048, 5, True, False, 64),
        (512, 512, 1, False, True, 8),
        (1, 16, 3, False, False, 4),
    ],
)
def test_runs_equal_scatter_form(n, pad, ndv, null_keys, null_inputs, max_groups):
    page = _page(n, n, pad, ndv, null_keys, null_inputs)
    want = grouped_aggregate_sorted(
        page, *KEYS, AGGS, max_groups, runs=False
    )
    got = jax.jit(
        lambda p: grouped_aggregate_sorted(
            p, *KEYS, AGGS, max_groups, runs=True
        )
    )(page)
    assert int(got.count) == int(want.count)
    assert got.names == want.names
    assert _rows(got) == _rows(want)


def test_runs_overflow_reports_true_count():
    """More groups than slots: the count is still the true one (the
    executor's retry reads it), as in the scatter form."""
    page = _page(3, 1000, 1024, 400, False, False)
    got = grouped_aggregate_sorted(page, *KEYS, AGGS, 16, runs=True)
    want = grouped_aggregate_sorted(page, *KEYS, AGGS, 16, runs=False)
    assert int(got.count) == int(want.count) > 16


def test_runs_mask_and_dead_rows():
    page = _page(5, 900, 1024, 30, True, True)
    mask = col("k2", T.INTEGER)  # not boolean: build a comparison instead
    from presto_tpu.expr.ir import Call, Literal

    mask = Call("gt", (col("k2", T.INTEGER), Literal(0, T.INTEGER)), T.BOOLEAN)
    want = grouped_aggregate_sorted(page, *KEYS, AGGS, 256, mask, runs=False)
    got = grouped_aggregate_sorted(page, *KEYS, AGGS, 256, mask, runs=True)
    assert _rows(got) == _rows(want) and int(got.count) == int(want.count)


@pytest.mark.parametrize(
    "aggs,keys",
    [
        ([AggSpec("min", col("x", T.BIGINT), "m", T.BIGINT)], KEYS),
        (
            [AggSpec("sum", col("f", T.DOUBLE), "s", T.DOUBLE)],
            KEYS,
        ),
        (
            [AggSpec("count_star", None, "c", T.BIGINT)],
            ([col("f", T.DOUBLE)], ["f"]),
        ),
    ],
)
def test_ineligible_shapes_keep_the_scatter_form(aggs, keys, monkeypatch):
    """min / max, float sums and float keys are not what a prefix sum
    reproduces bit for bit: `runs=True` leaves them where they were."""
    page = _page(9, 200, 256, 10, False, False)
    f = Block.from_numpy(
        np.random.default_rng(1).integers(0, 4, 256).astype(np.float64),
        T.DOUBLE,
    )
    page = Page.from_blocks(
        list(page.blocks) + [f], list(page.names) + ["f"], count=page.count
    )

    def boom(*a, **k):
        raise AssertionError("run-sum form taken")

    monkeypatch.setattr(agg, "_grouped_aggregate_runs", boom)
    out = grouped_aggregate_sorted(page, *keys, aggs, 64, runs=True)
    assert int(out.count) > 0


def test_size_gate():
    """By default the form follows the page's capacity."""
    assert agg.RUNS_MIN_ROWS == 1 << 23


# -- compact_few and the compare-all IN-list mask --

@pytest.mark.parametrize("kept,cap", [(0, 16), (1, 16), (40, 64), (64, 64)])
def test_compact_few_equals_compact(kept, cap):
    from presto_tpu.ops.filter import compact, compact_few

    rng = np.random.default_rng(kept)
    n, capacity = 5000, 8192
    page = Page.from_dict(
        {
            "a": rng.integers(0, 1000, n).astype(np.int64),
            "s": [str(i % 7) for i in range(n)],
        },
        pad_to=capacity,
    )
    keep = np.zeros(capacity, np.bool_)
    keep[rng.choice(n, kept, replace=False)] = True
    keep[n:] = True  # dead rows stay dead whatever the mask says
    want = compact(page, jax.numpy.asarray(keep))
    got = compact_few(page, jax.numpy.asarray(keep), cap=cap)
    assert got.capacity == cap and int(got.count) == int(want.count) == kept
    assert got.to_pylist() == want.to_pylist()


@pytest.mark.parametrize("k", [1, 7, 8, 111, 256])
def test_inlist_mask_equals_isin(k):
    from presto_tpu.exec import dynfilter

    rng = np.random.default_rng(k)
    data = rng.integers(-500, 500, 4096).astype(np.int64)
    values = np.unique(rng.integers(-500, 500, 4 * k))[:k]
    df = dynfilter.DynamicFilter(
        "inlist", T.BIGINT, len(values),
        values=jax.numpy.asarray(values), values_host=values,
    )
    block = Block.from_numpy(data, T.BIGINT)
    want = np.isin(data, values)
    assert (np.asarray(df.mask(block)) == want).all()  # searchsorted
    calls = []
    real = dynfilter._inlist_mask
    try:
        dynfilter._inlist_mask = lambda v, d: calls.append(v.shape) or real(v, d)
        from presto_tpu.ops import filter as filter_ops

        prev, filter_ops.LARGE_PAGE_ROWS = filter_ops.LARGE_PAGE_ROWS, 1024
        assert (np.asarray(df.mask(block)) == want).all()
    finally:
        dynfilter._inlist_mask = real
        filter_ops.LARGE_PAGE_ROWS = prev
    # one program a power of two, eight values a pass at the least
    assert calls == [(max(8, 1 << (len(values) - 1).bit_length()),)]
