"""Sort / TopN / Limit / Distinct kernels.

Equivalents of the reference's OrderByOperator (PagesIndex sort),
TopNOperator, LimitOperator and DistinctLimitOperator/MarkDistinctOperator
(presto-main/.../operator/). TPU redesign: XLA's sort is the workhorse —
multi-key ORDER BY is iterated stable argsort (last key first), NULLS
FIRST/LAST is a validity-aware key transform, and TopN is sort + static
truncation (lax.top_k only handles single keys)."""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax.numpy as jnp

from .. import types as T
from ..expr.compiler import evaluate
from ..obs.span import host_read
from ..page import Block, Page


@dataclasses.dataclass(frozen=True)
class SortKey:
    expr: object  # RowExpression
    ascending: bool = True
    nulls_first: Optional[bool] = None  # SQL default: NULLS LAST for ASC, FIRST for DESC

    @property
    def effective_nulls_first(self) -> bool:
        if self.nulls_first is not None:
            return self.nulls_first
        return not self.ascending


def asc_normalized_scalar_key(data, ascending: bool):
    """Normalize one 1-D key array so ascending numeric order equals the
    requested order (bool widened, negated for DESC). Shared by the local
    sort and the distributed rank-merge so the two can never disagree on
    key order. Returns None for multi-lane (long-decimal) data, which has
    no single mergeable scalar."""
    if data.ndim == 2:
        return None
    if jnp.issubdtype(data.dtype, jnp.bool_):
        data = data.astype(jnp.int32)
    if not ascending:
        if jnp.issubdtype(data.dtype, jnp.floating):
            data = -data
        else:
            # bitwise NOT is strictly order-reversing on ints and, unlike
            # negation, cannot overflow on INT64_MIN
            data = ~data.astype(jnp.int64)
    return data


def _float_total_order(x):
    """Total-order integer key for a float array matching jnp.argsort's
    semantics exactly (the pre-fused-sort behavior): -0.0 ties +0.0 and
    NaNs compare ABOVE +inf (so they land last in ascending order; the
    caller re-forces them last after any descending flip)."""
    import jax

    wide = x.dtype == jnp.float64
    it = jnp.int64 if wide else jnp.int32
    x = jnp.where(x == 0, jnp.zeros((), x.dtype), x)  # -0.0 ties +0.0
    bits = jax.lax.bitcast_convert_type(x, it)
    top = it(-(1 << 63)) if wide else it(-(1 << 31))  # INT_MIN bit pattern
    # SIGNED-comparison total order (lax.sort compares keys as signed):
    # positive floats keep their bit pattern (already ascending, >= 0);
    # negative floats map to ~bits ^ top = -1 - magnitude (< 0, ascending
    # with the float value). The unsigned-classic `bits ^ (sign | top)`
    # would invert the two sign classes under signed comparison.
    key = jnp.where(bits < 0, (~bits) ^ top, bits)
    # pin ALL NaNs (either sign) above every real value
    return jnp.where(jnp.isnan(x), it(jnp.iinfo(it).max), key)


def _sort_operands(page: Page, keys: Sequence[SortKey]):
    """The variadic lax.sort key operands for a page: (dead-flag,
    [null-flag_i, key_i...]) — shared by the full sort and the block-wise
    top-N selection so the two can never disagree on order."""
    cap = page.capacity
    ops = _key_operands(page, keys)
    # dead rows last: most-significant operand
    ops.insert(0, (~page.live_mask()).astype(jnp.int8))
    return ops


def sort_permutation(page: Page, keys: Sequence[SortKey]) -> jnp.ndarray:
    """Permutation that orders live rows by the sort keys; dead rows last.

    ONE variadic `lax.sort` over (dead-flag, [null-flag_i, key_i...])
    operands — XLA fuses the whole lexicographic comparison into a single
    sort network, where the per-key stable-argsort composition it
    replaces paid k+2 full sorts plus a permutation gather between each
    (measured 3x the passes on the TPU micro suite for 2-key sorts)."""
    import jax

    cap = page.capacity
    ops = _sort_operands(page, keys)
    fused = os.environ.get("PRESTO_TPU_FUSED_SORT", "1") != "0"
    if fused:
        # kernel-fault circuit breaker (exec/breaker.py): a faulting
        # fused sort degrades to the argsort composition process-wide
        from ..exec.breaker import BREAKERS

        fused = BREAKERS.allow("fused_sort")
    if not fused:
        # chip-diagnosis escape hatch / open breaker: the pre-fused
        # composition — iterated stable argsort, least-significant first
        perm = jnp.arange(cap, dtype=jnp.int32)
        for op in reversed(ops):
            perm = perm[jnp.argsort(op[perm], stable=True)]
        return perm
    idx = jnp.arange(cap, dtype=jnp.int32)
    out = jax.lax.sort(
        tuple(ops) + (idx,), num_keys=len(ops), is_stable=True
    )
    return out[-1]


def _key_operands(page: Page, keys: Sequence[SortKey]):
    ops = []
    for k in keys:
        v = evaluate(k.expr, page)
        if isinstance(v.type, T.VarcharType):
            from ..expr.functions import require_sorted_dict

            require_sorted_dict(v, "ORDER BY")
        data = v.data
        if v.valid is not None:
            # nulls to the requested end: leading per-key flag operand
            flag = v.valid if k.effective_nulls_first else ~v.valid
            ops.append(flag.astype(jnp.int8))
            # canonicalize NULL slots: their storage is garbage and must
            # not order null-tied rows ahead of the NEXT sort key (the
            # window sort does the same; SQL ties on NULL break by the
            # remaining keys)
            mask = v.valid if data.ndim == 1 else v.valid[:, None]
            data = jnp.where(mask, data, jnp.zeros_like(data))
        if data.ndim == 2:
            # long-decimal lanes: (hi, lo) lexicographic == numeric
            # (lo >= 0); bitwise NOT reverses order without overflow
            hi, lo = data[:, 0], data[:, 1]
            if not k.ascending:
                hi, lo = ~hi, ~lo
            ops.extend([hi, lo])
            continue
        if jnp.issubdtype(data.dtype, jnp.floating):
            raw = data
            data = _float_total_order(raw)
            if not k.ascending:
                data = ~data
            # jnp.argsort parity: NaNs sort LAST in both directions
            data = jnp.where(
                jnp.isnan(raw), jnp.iinfo(data.dtype).max, data
            )
            ops.append(data)
            continue
        if jnp.issubdtype(data.dtype, jnp.bool_):
            data = data.astype(jnp.int8)
        if not k.ascending:
            data = ~data.astype(data.dtype)
        ops.append(data)
    return ops


def apply_permutation(page: Page, perm: jnp.ndarray) -> Page:
    return Page(
        tuple(b.take_rows(perm) for b in page.blocks),
        page.names,
        page.count,
    )


def sort_page(page: Page, keys: Sequence[SortKey]) -> Page:
    return apply_permutation(page, sort_permutation(page, keys))


_TOPN_BLK = 1 << 13  # selection block; also the fast path's N ceiling


def top_n(page: Page, keys: Sequence[SortKey], n: int) -> Page:
    """ORDER BY + LIMIT n with static output capacity n (TopNOperator).

    TPU-first selection instead of the reference's bounded heap
    (operator/TopNOperator.java GroupedTopNBuilder): for small n over a
    big page, per-BLOCK variadic sorts keep each block's first n
    candidates (any global top-n row is in its block's top-n), one small
    sort over the B*n candidates picks the winners, and only THEN are
    the payload columns gathered — n rows instead of the whole page.
    The full sort + full-page gather only remains for big n. Ties break
    by original row id in both paths (stable), so the two agree
    exactly."""
    import jax

    cap = min(n, page.capacity)
    if (
        n <= _TOPN_BLK // 4
        and page.capacity >= 4 * _TOPN_BLK
        and os.environ.get("PRESTO_TPU_BLOCK_TOPN", "1") != "0"
    ):
        ops = _sort_operands(page, keys)
        idx = jnp.arange(page.capacity, dtype=jnp.int32)
        blk = _TOPN_BLK
        pad = (-page.capacity) % blk
        if pad:
            # padding rows carry dead-flag 2 > any real flag: sort last
            ops = [
                jnp.concatenate(
                    [o, jnp.full((pad,), 2 if i == 0 else 0, o.dtype)]
                )
                for i, o in enumerate(ops)
            ]
            idx = jnp.concatenate(
                [idx, jnp.zeros((pad,), jnp.int32)]
            )
        B = (page.capacity + pad) // blk
        blocked = [o.reshape(B, blk) for o in ops] + [idx.reshape(B, blk)]
        out = jax.lax.sort(
            tuple(blocked),
            dimension=1,
            num_keys=len(ops),
            is_stable=True,
        )
        cands = [o[:, :n].reshape(-1) for o in out]
        final = jax.lax.sort(
            tuple(cands),
            num_keys=len(ops) + 1,  # idx as last key: exact stable ties
            is_stable=True,
        )
        perm = final[-1][:cap]
        blocks = []
        for b in page.blocks:
            nb = b.take_rows(perm)
            blocks.append(nb)
        count = jnp.minimum(page.count, cap).astype(jnp.int32)
        return Page(tuple(blocks), page.names, count)
    s = sort_page(page, keys)
    # take_rows keeps collection companions (lengths/elem_valid/key_block)
    blocks = [b.take_rows(slice(0, cap)) for b in s.blocks]
    count = jnp.minimum(s.count, cap).astype(jnp.int32)
    return Page(tuple(blocks), s.names, count)


def limit_page(page: Page, n: int) -> Page:
    """LIMIT without ORDER BY: keep the first n live rows."""
    return Page(page.blocks, page.names, jnp.minimum(page.count, n).astype(jnp.int32))


# ---------------------------------------------------------------------------
# packed composite-key paths (ops/keypack.py): ONE sort on ONE key
# ---------------------------------------------------------------------------


def _packed_key_vals(page: Page, keys: Sequence[SortKey]):
    return [evaluate(k.expr, page) for k in keys]


def _host_argsort(*lanes):
    """numpy stable argsort of the packed lane(s) (lexicographic across
    lanes). ~8M rows/s vs ~2M for XLA's CPU comparison sort. Operands
    arrive as jax ArrayImpls — materialize to real numpy buffers first
    or numpy's sort runs ~3x slower through the buffer protocol."""
    import numpy as np

    lanes = [host_read(l) for l in lanes]
    if len(lanes) == 1:
        return np.argsort(lanes[0], kind="stable").astype(np.int32)
    return np.lexsort(tuple(reversed(lanes))).astype(np.int32)


def _host_topn(n: int):
    """numpy n-smallest row selection: argpartition + a stable sort of
    the <=n-ish candidates, ties broken by lower row index (the legacy
    stable order)."""
    import numpy as np

    def select(k):
        k = host_read(k)
        part = np.argpartition(k, n - 1)[:n]
        thresh = k[part].max()
        cand = np.flatnonzero(k <= thresh)
        return cand[np.argsort(k[cand], kind="stable")][:n].astype(np.int32)

    return select


def _concrete(*arrays) -> bool:
    """True when every operand is a real array (not a jit/vmap tracer) —
    the host route can then run numpy DIRECTLY instead of through
    `jax.pure_callback`. The callback path wedges forever on the
    single-device CPU runtime (the main thread blocks synchronizing the
    kernel while the callback thread starves — the PR 2 deadlock), so
    the executor routes host-sort plans around jit and this guard keeps
    the op layer honest about which world it is in."""
    import jax

    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def packed_sort_perm(lanes, plan, cap: int) -> jnp.ndarray:
    """Stable permutation sorting the packed lane(s) ascending — ONE
    device sort, or one numpy argsort on the host when the plan was made
    for the CPU backend (plan.host_sort). The host route runs numpy
    directly on concrete operands (the executor executes host-sort plans
    eagerly, outside jit); `jax.pure_callback` remains only as the
    under-trace fallback and is unsafe on single-device CPU."""
    import jax

    if plan.host_sort:
        if _concrete(*lanes):
            return jnp.asarray(_host_argsort(*lanes))
        # prestolint: allow(tracing-host-callback) -- under-trace
        # fallback only; executor routes host_sort plans around jit
        return jax.pure_callback(
            _host_argsort,
            jax.ShapeDtypeStruct((cap,), jnp.int32),
            *lanes,
        )
    idx = jnp.arange(cap, dtype=jnp.int32)
    out = jax.lax.sort(
        tuple(lanes) + (idx,), num_keys=len(lanes), is_stable=True
    )
    return out[-1]


def sort_page_packed(page: Page, keys: Sequence[SortKey], plan):
    """Multi-key ORDER BY as ONE argsort on the packed composite key
    (instead of a K-operand variadic sort / K iterated stable argsorts).

    Returns (sorted page, ok): `ok` is None unless the plan packs through
    sampled CBO bounds, in which case a False `ok` means some key fell
    outside the planned range and the caller must rerun the legacy path."""
    from .keypack import pack_keys

    vals = _packed_key_vals(page, keys)
    lanes, ok = pack_keys(vals, plan, page.live_mask())
    perm = packed_sort_perm(lanes, plan, page.capacity)
    return apply_permutation(page, perm), ok


def top_n_packed(page: Page, keys: Sequence[SortKey], n: int, plan):
    """TopN on the single-lane packed key: `lax.top_k` of the negated key
    (a selection network over ONE int64 array instead of any full sort)
    or a numpy argpartition under plan.host_sort. Both break ties in
    favor of the lower index, matching the legacy stable order exactly.
    Returns (page, ok) like sort_page_packed."""
    import jax

    from .keypack import pack_keys

    if not plan.single_lane:
        out, ok = sort_page_packed(page, keys, plan)
        cap = min(n, page.capacity)
        # take_rows keeps collection companions (lengths/elem_valid/...)
        blocks = [b.take_rows(slice(0, cap)) for b in out.blocks]
        count = jnp.minimum(out.count, cap).astype(jnp.int32)
        return Page(tuple(blocks), out.names, count), ok
    vals = _packed_key_vals(page, keys)
    lanes, ok = pack_keys(vals, plan, page.live_mask())
    cap = min(n, page.capacity)
    if plan.host_sort and cap < page.capacity:
        if _concrete(lanes[0]):
            perm = jnp.asarray(_host_topn(cap)(lanes[0]))
        else:
            # prestolint: allow(tracing-host-callback) -- under-trace
            # fallback only; executor routes host_sort plans around jit
            perm = jax.pure_callback(
                _host_topn(cap),
                jax.ShapeDtypeStruct((cap,), jnp.int32),
                lanes[0],
            )
    else:
        # packed keys are < 2**62 (dead rows INT64_MAX): negation is safe
        # and turns "n smallest" into top_k's "n largest"
        _, perm = jax.lax.top_k(-lanes[0], cap)
    blocks = [b.take_rows(perm) for b in page.blocks]
    count = jnp.minimum(page.count, cap).astype(jnp.int32)
    return Page(tuple(blocks), page.names, count), ok


def _host_distinct_sel(count, *lanes):
    """numpy distinct: one representative row index per distinct packed
    key among the first `count` (live) rows. Returns (selection indices
    padded to capacity, distinct count)."""
    import numpy as np

    n = int(host_read(count))
    cap = lanes[0].shape[0]
    ls = [host_read(l)[:n] for l in lanes]
    if n == 0:
        return np.zeros(cap, np.int32), np.int32(0)
    if len(ls) == 1:
        order = np.argsort(ls[0])  # unstable: any representative works
    else:
        order = np.lexsort(tuple(reversed(ls)))
    flag = np.zeros(n, bool)
    flag[0] = True
    for l in ls:
        s = l[order]
        flag[1:] |= s[1:] != s[:-1]
    sel = order[flag]
    out = np.zeros(cap, np.int32)
    out[: sel.size] = sel
    return out, np.int32(sel.size)


def _adjacent_run_starts(lanes_sorted, live_s):
    """First-of-run flags over sorted lane arrays (leading row True)."""
    from .aggregate import _neq_adjacent

    boundary = jnp.zeros(live_s.shape, jnp.bool_).at[0].set(True)
    for lane in lanes_sorted:
        boundary = boundary | _neq_adjacent(lane)
    return boundary & live_s


def distinct_packed(page: Page, plan):
    """SELECT DISTINCT as sorted-adjacent-unique on the packed key.

    bitpack/two_lane plans are exact (distinct packed keys == distinct
    rows); the hashed plan compares the raw key columns across every
    adjacent equal-hash pair and flips `ok` on a collision so the caller
    degrades to the legacy grouped-aggregation path."""
    import jax

    from .filter import compact
    from .keypack import pack_keys

    live = page.live_mask()
    idx = jnp.arange(page.capacity, dtype=jnp.int32)
    if plan.strategy == "hashed":
        from .hashing import hash_rows

        h = hash_rows(page.blocks)
        h = jnp.where(live, h, jnp.uint64(0xFFFFFFFFFFFFFFFF))
        out = jax.lax.sort((h, idx), num_keys=1, is_stable=True)
        h_s, perm = out
        live_s = live[perm]
        from .aggregate import _neq_adjacent

        boundary = (
            jnp.zeros(page.capacity, jnp.bool_).at[0].set(True)
            | _neq_adjacent(h_s)
        ) & live_s
        # post-hoc collision check: an adjacent pair with EQUAL hash but
        # UNEQUAL key values means 64 bits were not enough for this batch
        same_hash = (~_neq_adjacent(h_s)) & live_s
        differs = jnp.zeros(page.capacity, jnp.bool_)
        for b in page.blocks:
            from .aggregate import _neq_adjacent_nullaware

            differs = differs | _neq_adjacent_nullaware(
                b.data[perm], None if b.valid is None else b.valid[perm]
            )
        ok = ~jnp.any(same_hash & differs)
        sorted_page = apply_permutation(page, perm)
        return compact(sorted_page, boundary), ok
    lanes, ok = pack_keys(page.blocks, plan, live)
    if plan.host_sort:
        # numpy first-of-run selection over the live prefix (live rows
        # occupy [0, count) by the Page contract); equal packed keys are
        # identical rows, so representative choice is free and the
        # unstable (faster) numpy sort kinds are safe
        if _concrete(page.count, *lanes):
            sel, cnt = _host_distinct_sel(page.count, *lanes)
            sel, cnt = jnp.asarray(sel), jnp.asarray(cnt)
        else:
            # prestolint: allow(tracing-host-callback) -- under-trace
            # fallback only; executor routes host_sort plans around jit
            sel, cnt = jax.pure_callback(
                _host_distinct_sel,
                (
                    jax.ShapeDtypeStruct((page.capacity,), jnp.int32),
                    jax.ShapeDtypeStruct((), jnp.int32),
                ),
                page.count,
                *lanes,
            )
        blocks = [b.take_rows(sel) for b in page.blocks]
        return Page(tuple(blocks), page.names, cnt), ok
    out = jax.lax.sort(
        tuple(lanes) + (idx,), num_keys=len(lanes), is_stable=True
    )
    perm = out[-1]
    live_s = live[perm]
    boundary = _adjacent_run_starts(out[:-1], live_s)
    sorted_page = apply_permutation(page, perm)
    return compact(sorted_page, boundary), ok


def distinct_page(page: Page, max_groups: int) -> Page:
    """SELECT DISTINCT via the grouped-aggregation machinery (reference
    MarkDistinctOperator uses the same GroupByHash)."""
    from ..expr.ir import ColumnRef
    from .aggregate import grouped_aggregate_sorted

    exprs = [ColumnRef(n, b.type) for n, b in zip(page.names, page.blocks)]
    return grouped_aggregate_sorted(page, exprs, page.names, (), max_groups)
