"""The forms a page of 2^23 rows or more takes (SF10's lineitem and
orders; PR 33), each against the form it replaces there, at small sizes:

- `grouped_aggregate_sorted`'s run-sum form (ops/aggregate.py,
  `_grouped_aggregate_runs`: two payload-carrying sorts and prefix sums, no
  gather, no scatter): the same groups, sums, counts and NULLs, row for row;
- `compact_few` (ops/filter.py): `compact` for a mask that keeps few rows;
- `_inlist_mask` (exec/dynfilter.py): IN-list membership by comparison.

`compact_few` is no longer a large page's alone (PR 34): behind a
dynamic filter's mask `Executor._dyn_compact` takes it at any capacity
once the survivors fit a sixteenth of the page; its device branch is run
here on the CPU backend.
"""

import jax
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.expr.ir import Call, Literal, col
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


def _ordered_page(order, null_keys, n=600, capacity=1024):
    """`n` live rows laid out by `order` ("sorted": by the whole key,
    NULLs last, as the run-sum form's sort puts them; "first_key": by
    `k1` alone; "shuffled"), then a dead tail whose storage is garbage."""
    rng = np.random.default_rng(n + null_keys)
    k1 = rng.integers(-50, 50, capacity).astype(np.int64)
    k2 = rng.integers(0, 3, capacity).astype(np.int32)
    k1_valid = rng.random(capacity) < 0.8 if null_keys else None
    if order != "shuffled":
        null = np.zeros(n, bool) if k1_valid is None else ~k1_valid[:n]
        k1_value = np.where(null, 0, k1[:n])
        by = (k1_value, null) if order == "first_key" else (
            k2[:n], k1_value, null
        )
        rows = np.lexsort(by)
        k1[:n], k2[:n] = k1[rows], k2[rows]
        if k1_valid is not None:
            k1_valid[:n] = k1_valid[rows]
    blocks = [
        Block.from_numpy(k1, T.BIGINT, valid=k1_valid),
        Block.from_numpy(k2, T.INTEGER),
        Block.from_numpy(
            rng.integers(-(1 << 40), 1 << 40, capacity).astype(np.int64),
            T.BIGINT, valid=rng.random(capacity) < 0.7,
        ),
        Block.from_numpy(
            rng.integers(-(1 << 62), 1 << 62, capacity).astype(np.int64), DEC
        ),
    ]
    return Page.from_blocks(blocks, ["k1", "k2", "x", "d"], count=n)


K2_POSITIVE = Call("gt", (col("k2", T.INTEGER), Literal(0, T.INTEGER)), T.BOOLEAN)


@pytest.mark.parametrize(
    "order,null_keys,mask,presorted",
    [
        ("sorted", False, None, 1),  # the dead tail's garbage is not read
        ("shuffled", False, None, 0),
        ("sorted", False, K2_POSITIVE, 0),  # dead rows in the middle
        ("sorted", True, None, 1),  # the NULL flag is part of the key
        ("first_key", False, None, 0),  # `k2` out of order inside a run
    ],
)
def test_runs_skip_the_sort_of_rows_in_key_order(
    order, null_keys, mask, presorted, monkeypatch
):
    """The run-sum form tests its rows' order on the device and skips its
    first sort where that sort would move nothing: the same answer as the
    scatter form either way, and the status says which branch ran."""
    page = _ordered_page(order, null_keys)
    want = grouped_aggregate_sorted(page, *KEYS, AGGS, 1024, mask, runs=False)
    got, status = jax.jit(
        lambda p: grouped_aggregate_sorted(
            p, *KEYS, AGGS, 1024, mask, runs=True, status=True
        )
    )(page)
    assert np.asarray(status).tolist() == [int(want.count), presorted]
    assert int(got.count) == int(want.count)
    assert _rows(got) == _rows(want)
    # without jit `lax.cond` runs the one branch it picks: count the sorts
    sorts = []
    real = jax.lax.sort
    monkeypatch.setattr(
        jax.lax, "sort", lambda *a, **k: sorts.append(1) or real(*a, **k)
    )
    with jax.disable_jit():
        grouped_aggregate_sorted(page, *KEYS, AGGS, 1024, mask, runs=True)
    assert len(sorts) == 2 - presorted


def test_runs_overflow_reports_true_count():
    """More groups than slots: the count is still the true one (the
    executor's retry reads it), as in the scatter form."""
    page = _page(3, 1000, 1024, 400, False, False)
    got = grouped_aggregate_sorted(page, *KEYS, AGGS, 16, runs=True)
    want = grouped_aggregate_sorted(page, *KEYS, AGGS, 16, runs=False)
    assert int(got.count) == int(want.count) > 16


def test_runs_mask_and_dead_rows():
    page = _page(5, 900, 1024, 30, True, True)
    mask = K2_POSITIVE
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

def _masked_page(n, capacity, kept):
    """A page of `n` live rows in `capacity` slots and a mask that keeps
    `kept` of them, and says True for every dead slot."""
    rng = np.random.default_rng(capacity + kept)
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
    return page, jax.numpy.asarray(keep)


@pytest.mark.parametrize(
    "kept,cap,n,capacity",
    [
        (0, 16, 5000, 8192), (1, 16, 5000, 8192), (40, 64, 5000, 8192),
        (64, 64, 5000, 8192),
        (51_000, 65_536, 1_000_000, 1 << 20),  # Q3's bucket
    ],
)
def test_compact_few_equals_compact(kept, cap, n, capacity):
    from presto_tpu.ops.filter import compact, compact_few

    page, keep = _masked_page(n, capacity, kept)
    want = compact(page, keep)
    got = compact_few(page, keep, cap=cap)
    assert got.capacity == cap and int(got.count) == int(want.count) == kept
    assert got.to_pylist() == want.to_pylist()


# kept rows at the edges of both rules: a bucket's (`round_capacity`)
# and the sixteenth's (1,024 of 16,384 slots; 375 of 6,000)
DYN_COMPACT_CASES = [
    (1 << 14, kept) for kept in (0, 1, 1023, 1024, 1025, 1 << 13)
] + [(6000, kept) for kept in (0, 1, 256, 257, 374, 375, 376, 3000)]


@pytest.mark.parametrize("capacity,kept", DYN_COMPACT_CASES)
def test_dyn_compact_counts_first_at_any_capacity(
    capacity, kept, device_branch, monkeypatch
):
    """Under 2^23 rows too: one read (the count), `compact_few` exactly
    when the count's bucket fits a sixteenth of the page, `compact`'s
    sort and the bucket's rows of its permutation gathered otherwise;
    `compact`'s rows in `compact`'s order either way."""
    from presto_tpu.exec.executor import Executor
    from presto_tpu.ops import filter as filter_ops
    from presto_tpu.page import round_capacity

    page, keep = _masked_page(capacity - 100, capacity, kept)
    want = filter_ops.compact(page, keep)
    taken = []
    for name in ("kept_first_permutation", "compact_few"):
        real = getattr(filter_ops, name)
        monkeypatch.setattr(
            filter_ops, name,
            lambda *a, _real=real, _name=name, **k: (
                taken.append(_name) or _real(*a, **k)
            ),
        )
    out, count = Executor(None)._dyn_compact(page, keep)
    cap = round_capacity(max(kept, 1))
    assert count == int(out.count) == kept
    assert out.capacity == cap < capacity
    assert out.to_pylist() == want.to_pylist()
    assert taken == [
        "compact_few" if cap * 16 <= capacity else "kept_first_permutation"
    ]
    assert len(device_branch) == 1


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
