"""Filter + compaction kernels.

Equivalent of the reference's FilterAndProjectOperator /
ScanFilterAndProjectOperator (presto-main/.../operator/
ScanFilterAndProjectOperator.java:55) with codegen'd PageProcessors. On TPU a
filter has two parts: evaluating the predicate (fused elementwise — see
expr/compiler.py) and *compaction* — moving surviving rows to the front so the
page keeps its "live rows in [0, count)" invariant, the XLA answer to
dynamic row counts under static shapes. Two programs do it, both keeping
the survivors in their original order: `compact` (one single-operand sort
of the row ids, kept rows first, then every column gathered at page
capacity; a scatter serializes on the TPU) for a caller that does not know
how many rows survive, and `compact_few` (a running count of kept rows,
`cap` binary searches over it, `cap` rows of each column gathered; no
sort) for one that has read the count and found it small. A gather costs
the v5e ~7.5 ns an index whatever it gathers from and a 6M-row sort 8 ms
(chip runs, PR 34), so what a compaction costs is what it gathers: a
caller that holds the count (`Executor._dyn_compact`) never gathers a
column at page capacity."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..expr.compiler import evaluate
from ..page import Block, Page


def kept_first_permutation(keep: jnp.ndarray) -> jnp.ndarray:
    """Row permutation with kept rows first, each half in its original
    order — exactly `jnp.argsort(~keep, stable=True)` — as ONE
    single-operand sort: the key is the row id, plus `cap` for dropped
    rows, so all keys are distinct and the sort need not be stable.

    The TPU compiler's time for a sort grows with its operands and with
    stability: compiled for a described v5e at 2M rows the stable
    two-operand argsort took 63 s and this form 4.5 s (sandbox compile,
    PR 22) — and every filter compiles one per page capacity."""
    cap = keep.shape[0]
    dtype = jnp.int32 if cap < (1 << 30) else jnp.int64
    idx = jnp.arange(cap, dtype=dtype)
    key = jax.lax.sort(jnp.where(keep, idx, idx + cap), is_stable=False)
    return jnp.where(key >= cap, key - cap, key)


@jax.named_scope("compact")
def compact(page: Page, keep: jnp.ndarray) -> Page:
    """Keep rows where `keep & live`, moved to the front, count updated.

    TPU note: implemented as a sort + gathers. Scatter (the obvious
    cumsum+scatter formulation) serializes on TPU and measured ~6x slower
    than sort+gather at 6M rows; XLA's sort is the fastest reorder
    primitive available."""
    keep = keep & page.live_mask()
    # int32 count invariant (page.py): x64 mode would promote the sum
    count = jnp.sum(keep.astype(jnp.int32)).astype(jnp.int32)
    perm = kept_first_permutation(keep)
    blocks = [b.take_rows(perm) for b in page.blocks]
    return Page(tuple(blocks), page.names, count)


# From this many rows up two call sites take the forms that need no
# full-capacity gather; under it they stay the programs every smaller page
# has compiled (PR 33 had to hold the accepted cells' programs still). The
# two left: the plain `Filter` (`Executor._exec_filter`: `compact_few`
# behind a count read first, where `_shrink` would have read it) and a
# small IN-list's compare-all mask (exec/dynfilter._inlist_mask). Neither
# choice depends on a page's size; each gate goes with a measurement of
# its own on the chip, as the dynamic filter's compaction did in PR 34
# (`Executor._dyn_compact` picks by the kept share alone, at any size).
LARGE_PAGE_ROWS = 1 << 23


@functools.partial(jax.jit, static_argnames=("cap",))
def compact_few(page: Page, keep: jnp.ndarray, cap: int) -> Page:
    """`compact` then a slice to `cap` rows, for a mask known to keep at
    most `cap` of them: the j-th kept row is where the running count of
    kept rows first reaches j + 1, found by `cap` binary searches, so
    only `cap` rows of each column are gathered. Rows past the count
    repeat the last row (they are dead either way)."""
    keep = keep & page.live_mask()
    running = jnp.cumsum(keep.astype(jnp.int32))
    idx = jnp.searchsorted(
        running, jnp.arange(1, cap + 1, dtype=jnp.int32), side="left"
    )
    idx = jnp.minimum(idx, page.capacity - 1)
    blocks = [b.take_rows(idx) for b in page.blocks]
    return Page(tuple(blocks), page.names, running[-1].astype(jnp.int32))


def keep_mask(page: Page, predicate):
    """(live rows the predicate selects, how many): `filter_page`'s mask,
    for a caller that reads the count before it compacts."""
    v = evaluate(predicate, page)
    keep = v.data
    if v.valid is not None:
        keep = keep & v.valid  # NULL predicate == not selected
    keep = keep & page.live_mask()
    return keep, jnp.sum(keep.astype(jnp.int32)).astype(jnp.int32)


def filter_page(page: Page, predicate) -> Page:
    """Evaluate a predicate RowExpression and compact survivors."""
    v = evaluate(predicate, page)
    keep = v.data
    if v.valid is not None:
        keep = keep & v.valid  # NULL predicate == not selected
    return compact(page, keep)


def filter_project_page(page: Page, predicate, exprs, names) -> Page:
    """Fused filter+project: project all expressions, then compact once.

    Matches the reference's PageProcessor structure (filter first, then
    projections on selected positions) — here XLA fuses both passes."""
    from ..expr.compiler import project_page

    projected = project_page(page, exprs, names)
    if predicate is None:
        return projected
    v = evaluate(predicate, page)
    keep = v.data
    if v.valid is not None:
        keep = keep & v.valid
    return compact(projected, keep)


def sample_page(page: Page, fraction: float, seed: int, offset=0) -> Page:
    """TABLESAMPLE BERNOULLI(p): keep each live row independently with
    probability `fraction`, decided by a splitmix64 hash of (global row
    position, seed) — deterministic within one plan (the seed is drawn
    at plan time), stateless across batches (reference SampleNode +
    bernoulli_sample filter rewrite).

    `offset` is the GLOBAL position of this page's row 0 — a running
    row offset plus a per-worker/per-shard salt threaded by the
    executors. Without it the same positional mask would repeat across
    every batch and worker (systematic sampling, not Bernoulli —
    variance inflated and results biased whenever row order correlates
    with values; ADVICE round-5). Traced, so one compiled kernel serves
    every batch."""
    idx = jnp.arange(page.capacity, dtype=jnp.uint64) + jnp.asarray(
        offset
    ).astype(jnp.uint64)
    z = (idx + jnp.uint64(seed & 0xFFFFFFFFFFFFFFFF)) * jnp.uint64(
        0x9E3779B97F4A7C15
    )
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    z = z ^ (z >> jnp.uint64(31))
    u = (z >> jnp.uint64(11)).astype(jnp.float64) * (1.0 / (1 << 53))
    keep = (u < fraction) & page.live_mask()
    return compact(page, keep)
