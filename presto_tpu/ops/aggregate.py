"""Grouped aggregation kernels.

Re-designed equivalent of the reference's aggregation stack:
HashAggregationOperator + MultiChannelGroupByHash (presto-main/.../operator/
MultiChannelGroupByHash.java:54 — open-addressing hash + BigArrays) and the
compiled Accumulators (operator/aggregation/AccumulatorCompiler.java).

TPU-first redesign: no pointer-chasing hash table. Two strategies, chosen at
plan time like the reference chooses between hash/streaming aggregation:

1. DIRECT — all group keys are small-domain codes (dictionary codes, bools,
   tiny int ranges known from metadata). Group id = mixed-radix combination of
   codes; aggregation is ONE jax.ops.segment_sum (scatter-add) per aggregate.
   This covers TPC-H Q1-style group-bys (returnflag × linestatus = 6 groups).

2. SORT — general path: hash group keys, sort rows by hash (XLA's optimized
   sort), detect run boundaries by comparing *actual* keys of adjacent rows
   (so hash collisions stay distinct groups), dense group ids via cumsum, then
   segment reductions. The sorted layout is the analog of the reference's
   GroupByHash dense groupIds, with O(n log n) sort replacing probing.

Both paths are static-shape: output capacity = max_groups (a planner-provided
bound), live group count is a device scalar.

Aggregate functions: count/count_star/sum/min/max/avg with SQL null semantics
(nulls don't contribute; empty-group sum/min/max = NULL, count = 0).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..expr.compiler import evaluate
from ..expr.functions import Val
from ..page import Block, Page
from .hashing import argsort_hashes, hash_rows

SUPPORTED = (
    "count", "count_star", "sum", "min", "max", "avg", "checksum",
    "min_by", "max_by", "percentile",
    "array_agg", "map_agg", "histogram",
    "approx_distinct", "hll_registers", "hll_merge",
    "qsketch", "qsketch_merge",
    "linreg", "linreg_acc", "linreg_merge",
    "cmoments", "cmoments_merge",
    "map_union", "multimap_agg", "num_hist",
)


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate: func(input_expr [, key_expr]) AS name. `input2` is
    the ordering key of min_by/max_by (reference
    operator/aggregation/MinMaxByAggregations)."""

    func: str  # one of SUPPORTED
    input: Optional[object]  # RowExpression; None for count_star
    name: str
    output_type: T.Type
    input2: Optional[object] = None

    @staticmethod
    def infer_output_type(func: str, input_type: Optional[T.Type]) -> T.Type:
        if func in ("count", "count_star", "checksum", "approx_distinct"):
            return T.BIGINT
        if func == "array_agg":
            return T.ArrayType(input_type)
        if func == "histogram":
            return T.MapType(input_type, T.BIGINT)
        if func in ("min", "max", "min_by", "max_by"):
            return input_type
        if func == "sum":
            if isinstance(input_type, T.DecimalType):
                # long decimal result (reference: sum(decimal) -> decimal(38,s),
                # DecimalSumAggregation) — two int64 lanes, ops/decimal128.py
                return T.DecimalType(38, input_type.scale)
            if T.is_floating(input_type):
                return T.DOUBLE
            return T.BIGINT
        if func == "avg":
            if isinstance(input_type, T.DecimalType):
                return input_type  # reference: avg(decimal) keeps the scale
            return T.DOUBLE
        raise KeyError(f"unsupported aggregate {func!r}")


def _min_identity(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    if jnp.issubdtype(dtype, jnp.bool_):
        return jnp.asarray(True, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _max_identity(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    if jnp.issubdtype(dtype, jnp.bool_):
        return jnp.asarray(False, dtype)
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


@jax.named_scope("agg.reduce")
def _segment_reduce(func, data, valid, gid, num_segments, wide: bool = False):
    """One aggregate over dense group ids; returns (values, group_has_value).

    wide=True accumulates sums in two int64 lanes (ops/decimal128.py) —
    exact beyond int64, the reference's decimal(38) sum path. Lane-shaped
    inputs (data.ndim == 2, partial sums being re-aggregated) stay wide."""
    from . import decimal128 as d128

    contributes = valid
    if func in ("count", "count_star"):
        ones = contributes.astype(jnp.int64)
        return jax.ops.segment_sum(ones, gid, num_segments), None
    if func == "checksum":
        # order-independent wrapping sum of row hashes (reference
        # ChecksumAggregationFunction uses XOR; a mod-2^64 sum has the same
        # order/partition invariance and segments natively). Inputs arrive
        # pre-hashed by _eval_inputs; NULL rows contribute the null hash.
        x = jnp.where(contributes, data, jnp.zeros_like(data))
        return jax.ops.segment_sum(x, gid, num_segments), None
    masked_count = jax.ops.segment_sum(
        contributes.astype(jnp.int64), gid, num_segments
    )
    has = masked_count > 0
    lanes_in = data.ndim == 2
    if func in ("sum", "avg"):
        if lanes_in or (wide and jnp.issubdtype(data.dtype, jnp.integer)):
            lanes = data if lanes_in else d128.from_int64(data)
            lanes = jnp.where(contributes[:, None], lanes, 0)
            s = d128.segment_sum_wide(lanes, gid, num_segments)
        else:
            contrib = jnp.where(contributes, data, jnp.zeros_like(data))
            s = jax.ops.segment_sum(contrib, gid, num_segments)
        if func == "sum":
            return s, has
        return (s, masked_count), has
    if lanes_in:  # min/max over long decimal lanes: lexicographic two-pass
        ident_hi = (
            _min_identity(data.dtype) if func == "min" else _max_identity(data.dtype)
        )
        hi = jnp.where(contributes, data[:, 0], ident_hi)
        lo = jnp.where(contributes, data[:, 1], ident_hi)
        seg = jax.ops.segment_min if func == "min" else jax.ops.segment_max
        best_hi = seg(hi, gid, num_segments)
        on_best = contributes & (data[:, 0] == best_hi[gid])
        lo2 = jnp.where(on_best, lo, ident_hi)
        best_lo = seg(lo2, gid, num_segments)
        return jnp.stack([best_hi, best_lo], axis=-1), has
    if func == "min":
        contrib = jnp.where(contributes, data, _min_identity(data.dtype))
        return jax.ops.segment_min(contrib, gid, num_segments), has
    if func == "max":
        contrib = jnp.where(contributes, data, _max_identity(data.dtype))
        return jax.ops.segment_max(contrib, gid, num_segments), has
    raise KeyError(func)


def avg_from_sum_count(s, cnt, output_type: T.Type, input_type: Optional[T.Type]):
    """Finalize avg from (sum, count): decimal HALF_UP in scaled units, else
    double division (descaling decimal inputs). Shared by the single-node
    finalizer and the distributed post-exchange step so semantics can never
    diverge between them. Wide (two-lane) sums divide exactly via
    ops/decimal128.py (counts < 2^31, the per-chip row bound)."""
    from . import decimal128 as d128

    safe = jnp.maximum(cnt, 1)
    if s.ndim == 2:  # exact long-decimal intermediate
        if isinstance(output_type, T.DecimalType) and output_type.is_long:
            q = d128.ddiv_int64_half_up(s, safe)
            return d128.from_int64(q)
        if isinstance(output_type, T.DecimalType):
            return d128.ddiv_int64_half_up(s, safe).astype(
                output_type.storage_dtype
            )
        sd = d128.to_float64(s)
        if input_type is not None and isinstance(input_type, T.DecimalType):
            sd = sd / (10**input_type.scale)
        return (sd / safe).astype(output_type.storage_dtype)
    if isinstance(output_type, T.DecimalType):
        data = jnp.sign(s) * ((2 * jnp.abs(s) + safe) // (2 * safe))
    else:
        sd = s.astype(jnp.float64)
        if input_type is not None and isinstance(input_type, T.DecimalType):
            sd = sd / (10**input_type.scale)
        data = sd / safe
    return data.astype(output_type.storage_dtype)


def _finalize(
    spec: AggSpec, raw, has, input_type: Optional[T.Type], dict_id=None
) -> Block:
    if spec.func == "avg":
        s, cnt = raw
        data = avg_from_sum_count(s, cnt, spec.output_type, input_type)
        return Block(data, spec.output_type, has)
    if spec.func in ("count", "count_star"):
        return Block(raw.astype(jnp.int64), spec.output_type, None)
    # min/max over varchar operate on sorted-dictionary codes; keep the dict
    return Block(
        raw.astype(spec.output_type.storage_dtype), spec.output_type, has, dict_id
    )


@jax.named_scope("agg.inputs")
def _eval_inputs(page: Page, group_exprs, aggs):
    keys = [evaluate(e, page) for e in group_exprs]
    ins = []
    for a in aggs:
        if a.input is None:
            ins.append(None)
        else:
            v = evaluate(a.input, page)
            if a.func in ("min", "max") and isinstance(v.type, T.VarcharType):
                from ..expr.functions import require_sorted_dict

                require_sorted_dict(v, f"{a.func} aggregate")
            if a.func == "checksum":
                # pre-hash: checksum aggregates row hashes, nulls included.
                # Varchar hashes the STRING VALUES (host-hashed dictionary
                # table), not codes — equal data must checksum equal under
                # any dictionary (reference ChecksumAggregationFunction
                # hashes the value bytes).
                from .hashing import hash_column

                if isinstance(v.type, T.VarcharType):
                    import hashlib

                    import numpy as np

                    d = v.dictionary or ()
                    table = jnp.asarray(
                        np.array(
                            [
                                int.from_bytes(
                                    hashlib.blake2b(
                                        s.encode(), digest_size=8
                                    ).digest(),
                                    "little",
                                )
                                for s in d
                            ],
                            np.uint64,
                        ).view(np.int64)
                    )
                    hv = table[v.data]
                    if v.valid is not None:
                        hv = jnp.where(v.valid, hv, jnp.int64(0x9AE16A3B))
                    v = Val(hv, None, T.BIGINT)
                else:
                    h = hash_column(v.data, v.valid).view(jnp.int64)
                    v = Val(h, None, T.BIGINT)
            ins.append(v)
    return keys, ins


def _eval_by_keys(page: Page, aggs):
    """Ordering keys for min_by/max_by (AggSpec.input2), aligned with aggs."""
    out = []
    for a in aggs:
        if a.input2 is None or a.func == "percentile":
            # percentile's input2 is a literal fraction parameter, not an
            # ordering-key column — nothing to evaluate per batch
            out.append(None)
            continue
        k = evaluate(a.input2, page)
        if isinstance(k.type, T.VarcharType):
            from ..expr.functions import require_sorted_dict

            require_sorted_dict(k, f"{a.func} ordering key")
        if k.data.ndim == 2:
            raise NotImplementedError(
                f"{a.func} over a long-decimal ordering key"
            )
        out.append(k)
    return out


def _reduce_percentile(
    fraction: float, value: Val, contributes, gid, num_groups: int
):
    """Exact percentile by selection: one composite sort by (group, value)
    with non-contributing rows pushed to each group's end, then a gather
    at first + round(p * (n-1)) per group. Satisfies approx_percentile's
    contract exactly (the reference uses a qdigest estimate,
    operator/aggregation/ApproximateLongPercentileAggregations)."""
    from .sort import asc_normalized_scalar_key

    data = value.data
    vc = contributes if value.valid is None else (contributes & value.valid)
    if data.ndim == 2:
        # long-decimal lanes: lexicographic (hi, lo) via two stable
        # passes (canonical lo is non-negative, ops/decimal128.py)
        order = jnp.argsort(data[:, 1], stable=True)
        order = order[jnp.argsort(data[order, 0], stable=True)]
    else:
        norm = asc_normalized_scalar_key(data, True)
        if jnp.issubdtype(norm.dtype, jnp.floating):
            vc = vc & ~jnp.isnan(norm)
        # stable three-pass composite sort: by value, then contributing
        # rows first, then by group id — no sentinel values, so genuine
        # extremes (inf / INT64_MAX) can never collide with excluded rows
        order = jnp.argsort(norm, stable=True)
    order = order[jnp.argsort((~vc)[order], stable=True)]
    order = order[jnp.argsort(gid[order], stable=True)]
    n = data.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    gid_o = gid[order]
    vc_o = vc[order]
    # contributing rows sit at each group's FRONT, so the group start is
    # the first contributing position
    first = (
        jnp.full((num_groups,), n, jnp.int32)
        .at[gid_o]
        .min(jnp.where(vc_o, pos, n), mode="drop")
    )
    cnt = (
        jnp.zeros((num_groups,), jnp.int32)
        .at[gid_o]
        .add(vc_o.astype(jnp.int32), mode="drop")
    )
    has = cnt > 0
    last = jnp.maximum(cnt - 1, 0)
    # clamp to the group's last contributing row: float32 rounding of
    # fraction*(cnt-1) can land one past it for cnt > 2^24
    off = jnp.minimum(
        jnp.round(fraction * last).astype(jnp.int32), last
    )
    target = jnp.minimum(first + off, n - 1)
    picked = order[target]
    return data[picked], has


def positional_reduce(spec: "AggSpec", value, by_key, contributes, gid,
                      num_groups: int):
    """Dispatch for positional aggregates (min_by/max_by/percentile) —
    the one place all three aggregation strategies call into."""
    if spec.func == "percentile":
        return _reduce_percentile(
            float(spec.input2.value), value, contributes, gid, num_groups
        )
    return _reduce_by(spec.func, value, by_key, contributes, gid, num_groups)


def _reduce_by(func, value: Val, key: Val, contributes, gid, num_groups: int):
    """min_by/max_by: per group, the value at the extreme ordering key.

    Two reductions + a representative-row gather — no scatter beyond the
    engine's .at[].min index trick: (1) best key per group, (2) first row
    index attaining it, then gather the value column at those rows."""
    n = key.data.shape[0]
    kc = contributes if key.valid is None else (contributes & key.valid)
    if jnp.issubdtype(key.data.dtype, jnp.floating):
        # NaN keys poison the scatter-min/max (NaN != NaN breaks the
        # candidate match below); treat them like NULL keys
        kc = kc & ~jnp.isnan(key.data)
    ident = (
        _min_identity(key.data.dtype)
        if func == "min_by"
        else _max_identity(key.data.dtype)
    )
    kdat = jnp.where(kc, key.data, ident)
    best = (
        jnp.full((num_groups,), ident, kdat.dtype)
        .at[gid]
        .min(kdat, mode="drop")
        if func == "min_by"
        else jnp.full((num_groups,), ident, kdat.dtype)
        .at[gid]
        .max(kdat, mode="drop")
    )
    has = (
        jnp.zeros((num_groups,), jnp.int32)
        .at[gid]
        .max(kc.astype(jnp.int32), mode="drop")
        > 0
    )
    candidate = kc & (kdat == best[jnp.minimum(gid, num_groups - 1)])
    ridx = jnp.where(candidate, jnp.arange(n, dtype=jnp.int32), n)
    first = (
        jnp.full((num_groups,), n, jnp.int32).at[gid].min(ridx, mode="drop")
    )
    first = jnp.minimum(first, n - 1)
    vdat = value.data[first]
    vval = has if value.valid is None else (has & value.valid[first])
    return vdat, vval


@jax.named_scope("agg.mask")
def _masked_live(page: Page, pre_mask) -> jnp.ndarray:
    """Liveness restricted by a fused selection mask (Aggregate.mask)."""
    live = page.live_mask()
    if pre_mask is None:
        return live
    mv = evaluate(pre_mask, page)
    m = mv.data if mv.valid is None else (mv.data & mv.valid)
    return live & m


def _agg_contributes(v: Optional[Val], live):
    if v is None:  # count(*)
        return live
    if v.valid is None:
        return live
    return live & v.valid


def _wide_for(spec: AggSpec, v: Optional[Val]) -> bool:
    """Exact two-lane accumulation for decimal sums/averages (the decimal(38)
    path); float sums stay float, bigint sums keep int64 + its SQL overflow."""
    return (
        v is not None
        and isinstance(v.type, T.DecimalType)
        and spec.func in ("sum", "avg")
    )


def _neq_adjacent(d):
    """Adjacent-row inequality with a leading True; lane columns (n, 2)
    differ if any lane differs."""
    neq = d[1:] != d[:-1]
    if neq.ndim == 2:
        neq = neq.any(axis=-1)
    return jnp.concatenate([jnp.ones((1,), jnp.bool_), neq])


def _canon_cmp(d):
    """Canonical EQUALITY key for run/boundary detection: float columns
    map through the total-order transform so ±0.0 tie and ALL NaNs
    compare equal — the reference's doubleToLongBits canonicalization
    (GROUP BY / DISTINCT / window peers treat NaN as one value). A raw
    `!=` on float storage would make every NaN row its own group."""
    if jnp.issubdtype(d.dtype, jnp.floating):
        from .sort import _float_total_order

        return _float_total_order(d)
    return d


def _neq_adjacent_nullaware(data, valid):
    """Adjacent-row inequality under SQL grouping semantics: float values
    compare canonically (_canon_cmp), a NULL differs from any non-NULL,
    and two adjacent NULLs are EQUAL regardless of their garbage storage.
    Leading element True. `valid` may be None (no nulls)."""
    neq = _neq_adjacent(_canon_cmp(data))
    if valid is None:
        return neq
    vneq = jnp.concatenate(
        [jnp.zeros((1,), jnp.bool_), valid[1:] != valid[:-1]]
    )
    both_null = jnp.concatenate(
        [jnp.zeros((1,), jnp.bool_), (~valid[1:]) & (~valid[:-1])]
    )
    return (neq & ~both_null) | vneq


@jax.named_scope("agg.reduce")
def _mask_reduce(func, data, contributes, gid, num_groups: int, wide=False):
    """_segment_reduce over a SMALL static group count via per-group masked
    full reductions — no scatter. On TPU, scatter-add (what segment_sum
    lowers to) serializes on colliding indices (~70x slower measured at 6M
    rows, G=6); G fused elementwise-masked tree-reductions run at memory
    bandwidth. Same return contract as _segment_reduce."""
    from . import decimal128 as d128

    masks = [contributes & (gid == k) for k in range(num_groups)]
    if func in ("count", "count_star"):
        cnt = jnp.stack([jnp.sum(m, dtype=jnp.int64) for m in masks])
        return cnt, None
    if func == "checksum":
        s = jnp.stack(
            [jnp.sum(jnp.where(m, data, 0), dtype=jnp.int64) for m in masks]
        )
        return s, None
    cnt = jnp.stack([jnp.sum(m, dtype=jnp.int64) for m in masks])
    has = cnt > 0
    lanes_in = data.ndim == 2
    if func in ("sum", "avg"):
        if lanes_in or (wide and jnp.issubdtype(data.dtype, jnp.integer)):
            lanes = data if lanes_in else d128.from_int64(data)
            sums = []
            for m in masks:
                x = jnp.where(m[:, None], lanes, 0)
                hi, lo = d128.dnorm(jnp.sum(x[:, 0]), jnp.sum(x[:, 1]))
                sums.append(jnp.stack([hi, lo]))
            s = jnp.stack(sums)
        else:
            s = jnp.stack(
                [jnp.sum(jnp.where(m, data, jnp.zeros_like(data))) for m in masks]
            )
        if func == "sum":
            return s, has
        return (s, cnt), has
    ident = _min_identity(data.dtype) if func == "min" else _max_identity(data.dtype)
    red = jnp.min if func == "min" else jnp.max
    if lanes_in:  # long decimal: lexicographic (hi, then lo among best-hi)
        outs = []
        for m in masks:
            hi, lo = data[:, 0], data[:, 1]
            best_hi = red(jnp.where(m, hi, ident))
            on_best = m & (hi == best_hi)
            best_lo = red(jnp.where(on_best, lo, ident))
            outs.append(jnp.stack([best_hi, best_lo]))
        return jnp.stack(outs), has
    s = jnp.stack([red(jnp.where(m, data, ident)) for m in masks])
    return s, has


# ---------------------------------------------------------------------------
# DIRECT strategy (small-domain keys)
# ---------------------------------------------------------------------------


def direct_group_ids(keys: Sequence[Val], domains: Sequence[int], live):
    """Mixed-radix group id from small-int codes. NULL gets its own slot per
    key (domain+1 values each)."""
    gid = jnp.zeros(live.shape, jnp.int32)
    for v, dom in zip(keys, domains):
        code = v.data.astype(jnp.int32)
        if v.valid is not None:
            code = jnp.where(v.valid, code, dom)  # null bucket
            dom = dom + 1
        gid = gid * jnp.int32(dom) + code
    return gid


def direct_num_groups(keys: Sequence[Val], domains: Sequence[int]) -> int:
    n = 1
    for v, dom in zip(keys, domains):
        n *= dom + (0 if v.valid is None else 1)
    return n


def grouped_aggregate_direct(
    page: Page,
    group_exprs,
    group_names,
    aggs: Sequence[AggSpec],
    domains: Sequence[int],
    pre_mask=None,
) -> Page:
    """Aggregation when every key is a code in [0, domain). Output rows are
    exactly the occupied combinations, compacted."""
    live = _masked_live(page, pre_mask)
    keys, ins = _eval_inputs(page, group_exprs, aggs)
    num_groups = direct_num_groups(keys, domains)
    gid_all = direct_group_ids(keys, domains, live)
    gid = jnp.where(live, gid_all, num_groups)  # dead rows -> overflow slot

    # mask-reduce beats scatter for small G (measured 70x at G=6); its cost
    # grows linearly in G (G full passes + G-way unrolled graph), so hand
    # larger domains back to segment_sum well before the crossover
    small = num_groups <= 32
    if small:
        occupied = jnp.stack(
            [jnp.any(live & (gid_all == k)) for k in range(num_groups)]
        )
    else:
        occupied = jax.ops.segment_sum(
            live.astype(jnp.int32), gid, num_groups + 1
        )[:num_groups] > 0

    blocks = []
    names = []
    # group key columns: reconstruct codes from the group id (mixed radix)
    radixes = []
    for v, dom in zip(keys, domains):
        radixes.append(dom + (0 if v.valid is None else 1))
    rem = jnp.arange(num_groups, dtype=jnp.int32)
    codes = []
    for r in reversed(radixes):
        codes.append(rem % r)
        rem = rem // r
    codes = list(reversed(codes))
    for v, name, dom, code in zip(keys, group_names, domains, codes):
        if v.valid is not None:
            kvalid = code != dom
            kdata = jnp.where(kvalid, code, 0)
        else:
            kvalid = None
            kdata = code
        blocks.append(Block(kdata.astype(v.data.dtype), v.type, kvalid, v.dict_id))
        names.append(name)

    by_keys = _eval_by_keys(page, aggs)
    for spec, v, bk in zip(aggs, ins, by_keys):
        if spec.func in COLLECTION_AGGS or spec.func in (
            "approx_distinct", "hll_registers", "hll_merge",
            "qsketch", "qsketch_merge",
            "linreg", "linreg_acc", "linreg_merge",
            "cmoments", "cmoments_merge",
        ):
            raise NotImplementedError(
                f"{spec.func} runs through the SORT aggregation strategy"
            )
        if spec.func in ("min_by", "max_by", "percentile"):
            vdat, vval = positional_reduce(
                spec, v, bk, live, gid, num_groups + 1
            )
            blocks.append(
                Block(
                    vdat[:num_groups].astype(spec.output_type.storage_dtype),
                    spec.output_type,
                    vval[:num_groups],
                    v.dict_id,
                )
            )
            names.append(spec.name)
            continue
        contributes = _agg_contributes(v, live)
        data = None if v is None else v.data
        if data is None:
            data = jnp.zeros(live.shape, jnp.int64)
        if small:
            raw, has = _mask_reduce(
                spec.func, data, contributes, gid_all, num_groups,
                wide=_wide_for(spec, v),
            )
        else:
            raw, has = _segment_reduce(
                spec.func, data, contributes, gid, num_groups + 1,
                wide=_wide_for(spec, v),
            )
            raw = jax.tree_util.tree_map(lambda x: x[:num_groups], raw)
            has = None if has is None else has[:num_groups]
        in_t = None if v is None else v.type
        did = None if v is None else v.dict_id
        blocks.append(_finalize(spec, raw, has, in_t, did))
        names.append(spec.name)

    out = Page.from_blocks(blocks, names, count=num_groups)
    from .filter import compact

    return compact(out, occupied)


# ---------------------------------------------------------------------------
# SORT strategy (general keys)
# ---------------------------------------------------------------------------


# `grouped_aggregate_sorted` takes the run-sum form below from this many
# rows up. Under it the scatter form stays: its programs are the ones every
# smaller page already has compiled, and a multi-operand sort compiles
# slowly (hashing.argsort_hashes). On the v5e a 60M-row single-operand
# sort is tens of milliseconds and a gather or scatter ~15-30 ns an
# element (PERF.md section 5, PR 33), so at size a sort that carries its
# payload replaces every `x[order]` and every `segment_sum`.
RUNS_MIN_ROWS = 1 << 23

_RUN_FUNCS = ("sum", "avg", "count", "count_star")


def _runs_eligible(keys, ins, aggs) -> bool:
    """Integer keys and integer sum / avg / count: what a prefix sum over
    rows in key order reproduces bit for bit (a float sum would round
    differently, a float key has -0.0 and NaNs to canonicalize, min / max
    have no inverse to subtract)."""

    def int1d(v):
        return v.data.ndim == 1 and jnp.issubdtype(v.data.dtype, jnp.integer)

    return (
        all(int1d(v) for v in keys)
        and all(a.func in _RUN_FUNCS for a in aggs)
        and all(v is None or int1d(v) for v in ins)
    )


def _exclusive_diff(c):
    """c[g] - c[g-1], with c[-1] = 0."""
    return c - jnp.concatenate([jnp.zeros((1,), c.dtype), c[:-1]])


def _in_key_order(sort_keys) -> jnp.ndarray:
    """Whether `lax.sort` by `sort_keys` (the dead flag, then each key's
    operands) would leave every row where it is, up to ties: no live row
    after a dead one, and each live row's key tuple no less than the one
    before it. A dead row's keys are never compared: a padding tail holds
    whatever its storage held."""
    dead = sort_keys[0]
    keys_ok = jnp.ones(dead.shape[0] - 1, jnp.bool_)
    for k in reversed(sort_keys[1:]):
        keys_ok = (k[:-1] < k[1:]) | ((k[:-1] == k[1:]) & keys_ok)
    return jnp.all((dead[:-1] <= dead[1:]) & ((dead[1:] != 0) | keys_ok))


def _grouped_aggregate_runs(
    page: Page, live, keys, ins, group_names, aggs, max_groups: int
):
    """`grouped_aggregate_sorted` with no gather and no scatter: ONE sort
    by the key columns themselves carries each aggregate's contribution,
    prefix sums run over the rows in key order, and a second sort moves
    each run's LAST row to the front, where a group's sum is the
    difference of two neighbouring prefix sums (exact: int64 wraps as
    `segment_sum` does, and decimal sums go as 32-bit halves in int64
    prefix sums, good for 2^31 rows, into the same two lanes).

    Rows that arrive in key order (a table stored by its key, as
    lineitem by `l_orderkey`) skip the first sort: the program tests the
    order on the device and branches on it (`lax.cond`, so one branch
    runs). A sum over a run does not depend on the order inside it, so
    both branches give the same page. Returns (page, in_order)."""
    from . import decimal128 as d128

    cap = page.capacity
    idx_t = jnp.int32 if cap < (1 << 30) else jnp.int64
    # sort keys: dead rows last, then per key (NULL flag, value)
    operands = [(~live).astype(jnp.int32)]
    for v in keys:
        if v.valid is not None:
            operands.append((~v.valid).astype(jnp.int32))
            operands.append(jnp.where(v.valid, v.data, jnp.zeros_like(v.data)))
        else:
            operands.append(v.data)
    num_keys = len(operands)
    # payload: per aggregate its zeroed contribution, and the flag of
    # what contributes where a NULL input makes that differ from `live`
    slot_of = {}

    def carry(arr, tag):
        if tag not in slot_of:
            slot_of[tag] = len(operands)
            operands.append(arr)
        return slot_of[tag]

    plan = []
    for spec, v in zip(aggs, ins):
        flag = None
        if v is not None and v.valid is not None:
            flag = carry(
                (live & v.valid).astype(jnp.int32), ("c", id(v.valid))
            )
        val = None
        if spec.func in ("sum", "avg"):
            contributes = live if v.valid is None else (live & v.valid)
            val = carry(
                jnp.where(contributes, v.data, jnp.zeros_like(v.data)),
                ("x", id(v.data), id(v.valid)),
            )
        plan.append((flag, val))
    in_order = _in_key_order(operands[:num_keys])
    srt = jax.lax.cond(
        in_order,
        lambda ops: ops,
        lambda ops: jax.lax.sort(ops, num_keys=num_keys, is_stable=False),
        tuple(operands),
    )

    with jax.named_scope("group.runs"):
        live_s = srt[0] == 0
        boundary = jnp.zeros(cap, jnp.bool_).at[0].set(True)
        for k in srt[1:num_keys]:
            boundary = boundary | jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), k[1:] != k[:-1]]
            )
        boundary = boundary & live_s
        num_live_groups = jnp.sum(boundary.astype(jnp.int32))
        # a run's last row: live, and the next row starts a run or is dead
        continues = jnp.concatenate(
            [live_s[1:] & ~boundary[1:], jnp.zeros((1,), jnp.bool_)]
        )
        is_last = live_s & ~continues
        idx = jnp.arange(cap, dtype=idx_t)
        ends_key = jnp.where(is_last, idx, idx + cap)

    with jax.named_scope("group.prefix"):
        prefix = {}  # operand slot -> tuple of inclusive prefix sums
        for flag, val in plan:
            if flag is not None and flag not in prefix:
                prefix[flag] = (jnp.cumsum(srt[flag]),)
        for (flag, val), spec, v in zip(plan, aggs, ins):
            if val is None or val in prefix:
                continue
            x = srt[val]
            if _wide_for(spec, v):
                prefix[val] = (
                    jnp.cumsum(x >> d128.RADIX_BITS),
                    jnp.cumsum(x & d128.MASK32),
                )
            else:
                prefix[val] = (jnp.cumsum(x),)

    with jax.named_scope("group.ends"):
        moved = list(srt[1:num_keys])
        where = {}
        for slot, arrs in prefix.items():
            where[slot] = (len(moved), len(arrs))
            moved.extend(arrs)
        out = jax.lax.sort(
            (ends_key, *moved), num_keys=1, is_stable=False
        )

        def fit(a):
            # the first `max_groups` rows (max_groups may pass capacity)
            if max_groups <= cap:
                return a[:max_groups]
            return jnp.pad(a, (0, max_groups - cap))

        in_range = jnp.arange(max_groups, dtype=jnp.int32) < num_live_groups
        ends = fit(out[0]).astype(jnp.int64)
        # rows of a run that are live: its end position less the last one's
        live_count = jnp.where(
            in_range,
            ends - jnp.concatenate([jnp.full((1,), -1, jnp.int64), ends[:-1]]),
            0,
        )
        gkeys = [fit(a) for a in out[1:num_keys]]
        sums = {
            slot: tuple(
                jnp.where(in_range, _exclusive_diff(fit(out[1 + at + j])), 0)
                for j in range(n)
            )
            for slot, (at, n) in where.items()
        }

    blocks, names = [], []
    ki = 0
    for v, name in zip(keys, group_names):
        kvalid = None
        if v.valid is not None:
            kvalid = gkeys[ki] == 0
            ki += 1
        blocks.append(
            Block(gkeys[ki].astype(v.data.dtype), v.type, kvalid, v.dict_id)
        )
        ki += 1
        names.append(name)
    for (flag, val), spec, v in zip(plan, aggs, ins):
        masked_count = (
            live_count if flag is None else sums[flag][0].astype(jnp.int64)
        )
        if spec.func in ("count", "count_star"):
            blocks.append(_finalize(spec, masked_count, None, None))
            names.append(spec.name)
            continue
        parts = sums[val]
        if len(parts) == 2:
            s = jnp.stack(d128.dnorm(parts[0], parts[1]), axis=-1)
        else:
            s = parts[0]
        raw = s if spec.func == "sum" else (s, masked_count)
        blocks.append(
            _finalize(spec, raw, masked_count > 0, v.type, v.dict_id)
        )
        names.append(spec.name)
    return Page.from_blocks(blocks, names, count=num_live_groups), in_order


def grouped_aggregate_sorted(
    page: Page,
    group_exprs,
    group_names,
    aggs: Sequence[AggSpec],
    max_groups: int,
    pre_mask=None,
    max_elems: int = 128,
    runs: Optional[bool] = None,
    status: bool = False,
):
    """General grouped aggregation via hash-sort + run detection.

    max_groups is the static output capacity (planner-chosen; overflow beyond
    it is a query error the host checks via the returned count). `runs`
    picks the run-sum form (`_grouped_aggregate_runs`) where the shape is
    eligible; None = by the page's capacity (RUNS_MIN_ROWS). With `status`
    the result is (page, status): an int32[2] of the live group count and
    whether the run-sum form found its rows in key order (1: it skipped
    its first sort; 0: it sorted; -1: the form did not run), one array
    so that the host reads both at once."""
    live = _masked_live(page, pre_mask)
    keys, ins = _eval_inputs(page, group_exprs, aggs)
    if runs is None:
        runs = page.capacity >= RUNS_MIN_ROWS
    if runs and page.capacity > 1 and _runs_eligible(keys, ins, aggs):
        out, in_order = _grouped_aggregate_runs(
            page, live, keys, ins, group_names, aggs, max_groups
        )
        return _with_status(out, in_order.astype(jnp.int32), status)

    with jax.named_scope("group.hash_sort"):
        h = hash_rows(keys)
        # dead rows sort to the end: flip to max sentinel
        h = jnp.where(live, h, jnp.uint64(0xFFFFFFFFFFFFFFFF))
        order = argsort_hashes(h)

        live_s = live[order]
        keys_s = [
            Val(
                v.data[order], None if v.valid is None else v.valid[order],
                v.type, v.dict_id,
            )
            for v in keys
        ]

    with jax.named_scope("group.runs"):
        # run boundaries on actual key values (collision-proof)
        boundary = jnp.zeros(page.capacity, jnp.bool_).at[0].set(True)
        for v in keys_s:
            boundary = boundary | _neq_adjacent_nullaware(v.data, v.valid)

        boundary = boundary & live_s
        gid_s = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        num_live_groups = (
            jnp.maximum(gid_s[-1] + 1, 0) if page.capacity else 0
        )
        gid_s = jnp.where(live_s, gid_s, max_groups)

    with jax.named_scope("group.keys"):
        # representative (first) row index per group, for key gather
        first_idx = (
            jnp.full((max_groups + 1,), page.capacity, jnp.int32)
            .at[gid_s]
            .min(jnp.arange(page.capacity, dtype=jnp.int32), mode="drop")
        )
        first_idx = jnp.minimum(first_idx, page.capacity - 1)[:max_groups]

        blocks, names = [], []
        for v, name in zip(keys_s, group_names):
            kdata = v.data[first_idx]
            kvalid = None if v.valid is None else v.valid[first_idx]
            blocks.append(Block(kdata, v.type, kvalid, v.dict_id))
            names.append(name)

    by_keys = _eval_by_keys(page, aggs)
    collect_need = None
    for spec, v, bk in zip(aggs, ins, by_keys):
        if spec.func in COLLECTION_AGGS:
            v_sorted = Val(
                v.data[order],
                None if v.valid is None else v.valid[order],
                v.type,
                v.dict_id,
            )
            if spec.func == "array_agg":
                blk, need = collect_array_agg(
                    v_sorted, live_s, gid_s, max_groups, max_elems
                )
            else:
                bk_sorted = None
                if spec.func == "map_agg":
                    bk_sorted = Val(
                        bk.data[order],
                        None if bk.valid is None else bk.valid[order],
                        bk.type,
                        bk.dict_id,
                    )
                    blk, need = collect_map_agg(
                        spec, v_sorted, bk_sorted, live_s, gid_s,
                        max_groups, max_elems,
                    )
                elif spec.func == "map_union":
                    # rebuild the map Val (keys are lost by the plain
                    # data[order] copy above)
                    m_sorted = Val(
                        v.data[order],
                        None if v.valid is None else v.valid[order],
                        v.type, v.dict_id,
                        lengths=None if v.lengths is None
                        else v.lengths[order],
                        elem_valid=None if v.elem_valid is None
                        else v.elem_valid[order],
                        keys=Val(
                            v.keys.data[order], None, v.keys.type,
                            v.keys.dict_id,
                        ),
                    )
                    blk, need = collect_map_union(
                        spec, m_sorted, live_s, gid_s, max_groups,
                        max_elems,
                    )
                elif spec.func == "multimap_agg":
                    bk_sorted = Val(
                        bk.data[order],
                        None if bk.valid is None else bk.valid[order],
                        bk.type,
                        bk.dict_id,
                    )
                    blk, need = collect_multimap_agg(
                        spec, v_sorted, bk_sorted, live_s, gid_s,
                        max_groups, max_elems,
                    )
                elif spec.func == "num_hist":
                    contributes = live_s if v.valid is None else (
                        live_s & v.valid[order]
                    )
                    blk = numeric_histogram_agg(
                        spec, v_sorted, contributes, gid_s, max_groups + 1
                    )
                    blk = Block(
                        blk.data[:max_groups], blk.type, None,
                        lengths=blk.lengths[:max_groups],
                        elem_valid=blk.elem_valid[:max_groups],
                        key_block=Block(
                            blk.key_block.data[:max_groups],
                            blk.key_block.type, None,
                            lengths=blk.key_block.lengths[:max_groups],
                            elem_valid=blk.key_block.elem_valid[:max_groups],
                        ),
                    )
                    need = jnp.int32(0)
                else:  # histogram
                    blk, need = collect_map_agg(
                        spec, v_sorted, None, live_s, gid_s,
                        max_groups, max_elems,
                    )
            blocks.append(blk)
            names.append(spec.name)
            collect_need = (
                need if collect_need is None
                else jnp.maximum(collect_need, need)
            )
            continue
        if spec.func in ("approx_distinct", "hll_registers"):
            v_sorted_data = v.data[order]
            contributes = live_s if v.valid is None else (
                live_s & v.valid[order]
            )
            vv = Val(v_sorted_data, None, v.type, v.dict_id)
            regs = hll_group_registers(vv, contributes, gid_s, max_groups + 1)
            regs = regs[:max_groups]
            if spec.func == "approx_distinct":
                blocks.append(Block(hll_estimate(regs), T.BIGINT, None))
            else:
                blocks.append(
                    Block(regs, spec.output_type, None)
                )
            names.append(spec.name)
            continue
        if spec.func == "hll_merge":
            data_s = v.data[order]
            contributes = live_s
            regs = hll_merge_registers(
                data_s, contributes, gid_s, max_groups + 1
            )[:max_groups]
            blocks.append(Block(regs, spec.output_type, None))
            names.append(spec.name)
            continue
        if spec.func in ("qsketch", "qsketch_merge"):
            from . import qsketch as qs

            data_s = v.data[order]
            contributes = live_s if v.valid is None else (
                live_s & v.valid[order]
            )
            if spec.func == "qsketch":
                sk = qs.group_sketch(
                    data_s, contributes, gid_s, max_groups + 1
                )[:max_groups]
            else:
                sk = qs.merge_sketches(
                    data_s, contributes, gid_s, max_groups + 1
                )[:max_groups]
            blocks.append(Block(sk, T.ArrayType(T.BIGINT), None))
            names.append(spec.name)
            continue
        if spec.func in ("cmoments", "cmoments_merge"):
            from . import moments as mo

            contributes = live_s if v.valid is None else (
                live_s & v.valid[order]
            )
            if spec.func == "cmoments":
                acc = mo.group_moments(
                    v.data[order], contributes, gid_s, max_groups + 1
                )[:max_groups]
            else:
                acc = mo.merge_moments(
                    v.data[order], contributes, gid_s, max_groups + 1
                )[:max_groups]
            blocks.append(
                Block(
                    acc, T.ArrayType(T.DOUBLE), None,
                    lengths=jnp.full(acc.shape[0], mo.ACC_WIDTH, jnp.int32),
                )
            )
            names.append(spec.name)
            continue
        if spec.func in ("linreg", "linreg_acc", "linreg_merge"):
            from . import mlreg

            contributes = live_s if v.valid is None else (
                live_s & v.valid[order]
            )
            if spec.func == "linreg_merge":
                acc = mlreg.merge_accumulators(
                    v.data[order], contributes, gid_s, max_groups + 1
                )[:max_groups]
            else:
                lab = bk
                lab_data = mlreg.logical_values(lab.data, lab.type)[order]
                if lab.valid is not None:
                    contributes = contributes & lab.valid[order]
                lens = (
                    v.lengths[order]
                    if getattr(v, "lengths", None) is not None
                    else jnp.full(
                        v.data.shape[0], v.data.shape[1], jnp.int32
                    )
                )
                acc = mlreg.group_accumulate(
                    mlreg.logical_values(v.data, v.type)[order], lens,
                    lab_data, contributes, gid_s, max_groups + 1,
                )[:max_groups]
            valid_g = None
            if spec.func == "linreg":
                acc, has = mlreg.solve_weights(acc)
                valid_g = has  # empty group -> NULL model
            blocks.append(
                Block(
                    acc, T.ArrayType(T.DOUBLE), valid_g,
                    lengths=jnp.full(acc.shape[0], acc.shape[1], jnp.int32),
                )
            )
            names.append(spec.name)
            continue
        if spec.func in ("min_by", "max_by", "percentile"):
            v_sorted = Val(
                v.data[order],
                None if v.valid is None else v.valid[order],
                v.type,
                v.dict_id,
            )
            k_sorted = None
            if bk is not None:
                k_sorted = Val(
                    bk.data[order],
                    None if bk.valid is None else bk.valid[order],
                    bk.type,
                    bk.dict_id,
                )
            vdat, vval = positional_reduce(
                spec, v_sorted, k_sorted, live_s, gid_s, max_groups + 1
            )
            blocks.append(
                Block(
                    vdat[:max_groups].astype(spec.output_type.storage_dtype),
                    spec.output_type,
                    vval[:max_groups],
                    v.dict_id,
                )
            )
            names.append(spec.name)
            continue
        if v is None:
            v_s = None
            data_s = jnp.zeros(page.capacity, jnp.int64)
            contributes = live_s
            in_t = None
        else:
            with jax.named_scope("group.gather"):
                data_s = v.data[order]
                valid_s = None if v.valid is None else v.valid[order]
            contributes = live_s if valid_s is None else (live_s & valid_s)
            in_t = v.type
        raw, has = _segment_reduce(
            spec.func, data_s, contributes, gid_s, max_groups + 1,
            wide=_wide_for(spec, v),
        )
        raw = jax.tree_util.tree_map(lambda x: x[:max_groups], raw)
        has = None if has is None else has[:max_groups]
        did = None if v is None else v.dict_id
        blocks.append(_finalize(spec, raw, has, in_t, did))
        names.append(spec.name)

    if collect_need is not None:
        # adaptive-width protocol: the executor reads this hidden block,
        # retries with a larger max_elems when any group overflowed, and
        # drops it from the result (same pattern as the max_groups retry)
        blocks.append(
            Block(
                jnp.full(
                    (max_groups,), 0, jnp.int32
                ).at[0].set(collect_need.astype(jnp.int32)),
                T.INTEGER,
                None,
            )
        )
        names.append("$collect_need")
    out = Page.from_blocks(blocks, names, count=num_live_groups)
    return _with_status(out, jnp.int32(-1), status)


def _with_status(out: Page, presorted, status: bool):
    """`grouped_aggregate_sorted`'s result: the page, or with `status`
    (page, [live group count, presorted])."""
    if not status:
        return out
    return out, jnp.stack([out.count, presorted])


# ---------------------------------------------------------------------------
# partial/final decomposition (distributed aggregation)
# ---------------------------------------------------------------------------
#
# The reference splits aggregations into PARTIAL (pre-exchange) and FINAL
# (post-exchange) steps (sql/planner/optimizations/AddExchanges + Step in
# AggregationNode). Here the same decomposition feeds the all_to_all exchange:
# every worker partially aggregates its shard, partial rows are repartitioned
# by group-key hash, and finals combine. `avg` decomposes into (sum, count).


@dataclasses.dataclass(frozen=True)
class AvgPost:
    """Post-exchange step: name = sum_col / cnt_col with avg typing."""

    name: str
    sum_col: str
    cnt_col: str
    output_type: T.Type
    input_type: T.Type


@dataclasses.dataclass(frozen=True)
class HllPost:
    """Post-exchange step: name = HLL estimate of merged registers."""

    name: str
    reg_col: str

    # mirror AvgPost's helper-column protocol
    @property
    def sum_col(self):
        return self.reg_col

    @property
    def cnt_col(self):
        return self.reg_col


@dataclasses.dataclass(frozen=True)
class LinRegPost:
    """Post-exchange step: solve merged normal equations into weights."""

    name: str
    acc_col: str

    @property
    def sum_col(self):
        return self.acc_col

    @property
    def cnt_col(self):
        return self.acc_col


@dataclasses.dataclass(frozen=True)
class QSketchPost:
    """Post-exchange step: name = percentile read off the merged quantile
    sketch (ops/qsketch.py — the mergeable approx_percentile path)."""

    name: str
    sketch_col: str
    fraction: float
    output_type: T.Type

    @property
    def sum_col(self):
        return self.sketch_col

    @property
    def cnt_col(self):
        return self.sketch_col


def decompose_partial(aggs: Sequence[AggSpec]):
    """Returns (partial_specs, final_specs, post_steps, final_keep_names).

    partial_specs run on each shard before the exchange; final_specs run on
    repartitioned partial rows; post_steps derive remaining columns (avg)."""
    from ..expr.ir import ColumnRef

    partial, final, post = [], [], []
    for a in aggs:
        if a.func in ("count", "count_star", "checksum"):
            partial.append(a)
            final.append(AggSpec("sum", ColumnRef(a.name, T.BIGINT), a.name, T.BIGINT))
        elif a.func in ("sum", "min", "max"):
            partial.append(a)
            final.append(
                AggSpec(a.func, ColumnRef(a.name, a.output_type), a.name, a.output_type)
            )
        elif a.func == "avg":
            in_t = a.input.type
            sum_t = AggSpec.infer_output_type("sum", in_t)
            s_name, c_name = f"{a.name}$sum", f"{a.name}$cnt"
            partial.append(AggSpec("sum", a.input, s_name, sum_t))
            partial.append(AggSpec("count", a.input, c_name, T.BIGINT))
            final.append(AggSpec("sum", ColumnRef(s_name, sum_t), s_name, sum_t))
            final.append(AggSpec("sum", ColumnRef(c_name, T.BIGINT), c_name, T.BIGINT))
            post.append(AvgPost(a.name, s_name, c_name, a.output_type, in_t))
        elif a.func == "approx_distinct":
            reg_t = T.ArrayType(T.TINYINT)
            r_name = f"{a.name}$hll"
            partial.append(AggSpec("hll_registers", a.input, r_name, reg_t))
            final.append(
                AggSpec("hll_merge", ColumnRef(r_name, reg_t), r_name, reg_t)
            )
            post.append(HllPost(a.name, r_name))
        elif a.func == "percentile":
            # distributed approx_percentile goes through the MERGEABLE
            # log-histogram sketch (ops/qsketch.py) instead of exact
            # per-node selection — the qdigest role (reference
            # ApproximateLongPercentileAggregations + QuantileDigest).
            # Long-decimal lanes have no scalar sketch key: gather-path
            # fallback (KeyError contract, same as collection aggs)
            if (
                a.input is not None
                and isinstance(a.input.type, T.DecimalType)
                and a.input.type.is_long
            ):
                raise KeyError(
                    "cannot decompose percentile over long decimals"
                )
            sk_t = T.ArrayType(T.BIGINT)
            s_name = f"{a.name}$qsk"
            frac = float(a.input2.value)
            partial.append(AggSpec("qsketch", a.input, s_name, sk_t))
            final.append(
                AggSpec("qsketch_merge", ColumnRef(s_name, sk_t), s_name, sk_t)
            )
            post.append(QSketchPost(a.name, s_name, frac, a.output_type))
        elif a.func in ("hll_registers", "hll_merge"):
            # bare sketch aggregates (approx_set / merge): partials merge
            # by register-max
            partial.append(a)
            final.append(
                AggSpec("hll_merge", ColumnRef(a.name, a.output_type),
                        a.name, a.output_type)
            )
        elif a.func in ("qsketch", "qsketch_merge"):
            partial.append(a)
            final.append(
                AggSpec("qsketch_merge", ColumnRef(a.name, a.output_type),
                        a.name, a.output_type)
            )
        elif a.func == "cmoments":
            # mergeable central-moment accumulators (ops/moments.py):
            # partial rows re-center on the merged mean at final time
            acc_t = T.ArrayType(T.DOUBLE)
            partial.append(a)
            final.append(
                AggSpec("cmoments_merge", ColumnRef(a.name, acc_t), a.name,
                        acc_t)
            )
        elif a.func == "linreg":
            # mergeable normal-equation accumulators (ops/mlreg.py)
            acc_t = T.ArrayType(T.DOUBLE)
            m_name = f"{a.name}$lr"
            partial.append(
                AggSpec("linreg_acc", a.input, m_name, acc_t,
                        input2=a.input2)
            )
            final.append(
                AggSpec("linreg_merge", ColumnRef(m_name, acc_t), m_name,
                        acc_t)
            )
            post.append(LinRegPost(a.name, m_name))
        else:
            raise KeyError(f"cannot decompose aggregate {a.func!r}")
    return tuple(partial), tuple(final), tuple(post)


def apply_avg_post(page: Page, aggs: Sequence[AggSpec], post: Sequence[AvgPost]) -> Page:
    """Produce the user-visible columns (group keys + aggregates in `aggs`
    order) from a final-aggregated page containing decomposed columns."""
    by_name = {p.name: p for p in post}
    helper_cols = {x for p in post for x in (p.sum_col, p.cnt_col)}
    agg_names = {a.name for a in aggs}
    blocks, names = [], []
    # group keys pass through in page order
    for name, b in zip(page.names, page.blocks):
        if name not in helper_cols and name not in agg_names:
            blocks.append(b)
            names.append(name)
    # aggregates in spec order
    for a in aggs:
        p = by_name.get(a.name)
        if p is None:
            blocks.append(page.block(a.name))
            names.append(a.name)
            continue
        if isinstance(p, HllPost):
            regs = page.block(p.reg_col).data
            blocks.append(Block(hll_estimate(regs), T.BIGINT, None))
            names.append(a.name)
            continue
        if isinstance(p, LinRegPost):
            from . import mlreg

            acc = page.block(p.acc_col).data
            w, has = mlreg.solve_weights(acc)
            blocks.append(
                Block(
                    w, T.ArrayType(T.DOUBLE), has,
                    lengths=jnp.full(w.shape[0], w.shape[1], jnp.int32),
                )
            )
            names.append(a.name)
            continue
        if isinstance(p, QSketchPost):
            from . import qsketch as qs

            sk = page.block(p.sketch_col).data
            vals = qs.percentile_value(sk, p.fraction)
            valid = jnp.sum(sk, axis=1) > 0
            out_t = p.output_type
            if T.is_floating(out_t):
                data = vals.astype(out_t.storage_dtype)
            else:
                data = jnp.round(vals).astype(out_t.storage_dtype)
            blocks.append(Block(data, out_t, valid))
            names.append(a.name)
            continue
        s = page.block(p.sum_col).data
        cnt = page.block(p.cnt_col).data
        data = avg_from_sum_count(s, cnt, p.output_type, p.input_type)
        blocks.append(Block(data, p.output_type, cnt > 0))
        names.append(a.name)
    return Page(tuple(blocks), tuple(names), page.count)


def global_aggregate(page: Page, aggs: Sequence[AggSpec], pre_mask=None) -> Page:
    """Aggregation with no GROUP BY — one output row (reference
    AggregationOperator)."""
    live = _masked_live(page, pre_mask)
    _, ins = _eval_inputs(page, (), aggs)
    by_keys = _eval_by_keys(page, aggs)
    blocks, names = [], []
    gid = jnp.zeros(page.capacity, jnp.int32)
    for spec, v, bk in zip(aggs, ins, by_keys):
        if spec.func in ("min_by", "max_by", "percentile"):
            vdat, vval = positional_reduce(spec, v, bk, live, gid, 1)
            blocks.append(
                Block(
                    vdat.astype(spec.output_type.storage_dtype),
                    spec.output_type,
                    vval,
                    v.dict_id,
                )
            )
            names.append(spec.name)
            continue
        if spec.func in COLLECTION_AGGS or spec.func in (
            "approx_distinct", "hll_registers", "hll_merge",
            "qsketch", "qsketch_merge",
            "linreg", "linreg_acc", "linreg_merge",
            "cmoments", "cmoments_merge",
        ):
            gid0 = jnp.zeros(page.capacity, jnp.int32)
            live0 = live
            order0 = jnp.argsort(~live0, stable=True)  # live rows first
            gid_s0 = jnp.where(live0[order0], 0, 1)
            v_s = Val(
                v.data[order0],
                None if v.valid is None else v.valid[order0],
                v.type,
                v.dict_id,
            )
            if spec.func == "array_agg":
                blk, _need = collect_array_agg(
                    v_s, live0[order0], gid_s0, 1, page.capacity
                )
            elif spec.func in ("map_agg", "histogram"):
                bk2 = None
                if spec.func == "map_agg":
                    bk2 = _eval_by_keys(page, [spec])[0]
                    bk2 = Val(
                        bk2.data[order0],
                        None if bk2.valid is None else bk2.valid[order0],
                        bk2.type,
                        bk2.dict_id,
                    )
                blk, _need = collect_map_agg(
                    spec, v_s, bk2, live0[order0], gid_s0, 1, page.capacity
                )
            elif spec.func == "map_union":
                m_s = Val(
                    v.data[order0],
                    None if v.valid is None else v.valid[order0],
                    v.type, v.dict_id,
                    lengths=None if v.lengths is None
                    else v.lengths[order0],
                    elem_valid=None if v.elem_valid is None
                    else v.elem_valid[order0],
                    keys=Val(
                        v.keys.data[order0], None, v.keys.type,
                        v.keys.dict_id,
                    ),
                )
                blk, _need = collect_map_union(
                    spec, m_s, live0[order0], gid_s0, 1, page.capacity
                )
            elif spec.func == "multimap_agg":
                bk3 = _eval_by_keys(page, [spec])[0]
                bk3 = Val(
                    bk3.data[order0],
                    None if bk3.valid is None else bk3.valid[order0],
                    bk3.type,
                    bk3.dict_id,
                )
                blk, _need = collect_multimap_agg(
                    spec, v_s, bk3, live0[order0], gid_s0, 1,
                    page.capacity,
                )
            elif spec.func == "num_hist":
                contributes0 = live0[order0] if v.valid is None else (
                    live0[order0] & v_s.valid_mask()
                )
                blk = numeric_histogram_agg(
                    spec, v_s, contributes0, gid_s0, 2
                )
                blk = Block(
                    blk.data[:1], blk.type, None,
                    lengths=blk.lengths[:1],
                    elem_valid=blk.elem_valid[:1],
                    key_block=Block(
                        blk.key_block.data[:1], blk.key_block.type, None,
                        lengths=blk.key_block.lengths[:1],
                        elem_valid=blk.key_block.elem_valid[:1],
                    ),
                )
            elif spec.func == "hll_merge":
                regs = hll_merge_registers(v_s.data, live0[order0], gid_s0, 2)[:1]
                blk = Block(regs, spec.output_type, None)
            elif spec.func in ("qsketch", "qsketch_merge"):
                from . import qsketch as qs

                contributes0 = live0[order0] if v.valid is None else (
                    live0[order0] & v_s.valid_mask()
                )
                if spec.func == "qsketch":
                    sk = qs.group_sketch(
                        v_s.data, contributes0, gid_s0, 2
                    )[:1]
                else:
                    sk = qs.merge_sketches(
                        v_s.data, contributes0, gid_s0, 2
                    )[:1]
                blk = Block(sk, T.ArrayType(T.BIGINT), None)
            elif spec.func in ("cmoments", "cmoments_merge"):
                from . import moments as mo

                contributes0 = live0[order0] if v.valid is None else (
                    live0[order0] & v_s.valid_mask()
                )
                if spec.func == "cmoments":
                    acc = mo.group_moments(
                        v_s.data, contributes0, gid_s0, 2
                    )[:1]
                else:
                    acc = mo.merge_moments(
                        v_s.data, contributes0, gid_s0, 2
                    )[:1]
                blk = Block(
                    acc, T.ArrayType(T.DOUBLE), None,
                    lengths=jnp.full(acc.shape[0], mo.ACC_WIDTH, jnp.int32),
                )
            elif spec.func in ("linreg", "linreg_acc", "linreg_merge"):
                from . import mlreg

                contributes0 = live0[order0] if v.valid is None else (
                    live0[order0] & v_s.valid_mask()
                )
                if spec.func == "linreg_merge":
                    acc = mlreg.merge_accumulators(
                        v_s.data, contributes0, gid_s0, 2
                    )[:1]
                else:
                    lab0 = bk
                    lab_d = mlreg.logical_values(lab0.data, lab0.type)[order0]
                    if lab0.valid is not None:
                        contributes0 = contributes0 & lab0.valid[order0]
                    lens0 = (
                        v.lengths[order0]
                        if getattr(v, "lengths", None) is not None
                        else jnp.full(
                            v_s.data.shape[0], v_s.data.shape[1], jnp.int32
                        )
                    )
                    acc = mlreg.group_accumulate(
                        mlreg.logical_values(v_s.data, v.type), lens0, lab_d,
                        contributes0, gid_s0, 2,
                    )[:1]
                valid_g0 = None
                if spec.func == "linreg":
                    acc, has0 = mlreg.solve_weights(acc)
                    valid_g0 = has0
                blk = Block(
                    acc, T.ArrayType(T.DOUBLE), valid_g0,
                    lengths=jnp.full(acc.shape[0], acc.shape[1], jnp.int32),
                )
            else:
                contributes0 = live0[order0] if v.valid is None else (
                    live0[order0] & v_s.valid_mask()
                )
                vv0 = Val(v_s.data, None, v.type, v.dict_id)
                regs = hll_group_registers(vv0, contributes0, gid_s0, 2)[:1]
                if spec.func == "approx_distinct":
                    blk = Block(hll_estimate(regs), T.BIGINT, None)
                else:
                    blk = Block(regs, spec.output_type, None)
            blocks.append(blk)
            names.append(spec.name)
            continue
        contributes = _agg_contributes(v, live)
        data = jnp.zeros(page.capacity, jnp.int64) if v is None else v.data
        # mask-reduce: a single-segment segment_sum is the worst-case
        # all-colliding scatter on TPU; a plain masked reduction is free
        raw, has = _mask_reduce(
            spec.func, data, contributes, gid, 1, wide=_wide_for(spec, v)
        )
        in_t = None if v is None else v.type
        did = None if v is None else v.dict_id
        blocks.append(_finalize(spec, raw, has, in_t, did))
        names.append(spec.name)
    return Page.from_blocks(blocks, names, count=1)


# ---------------------------------------------------------------------------
# collection aggregates + HyperLogLog (reference: aggregation/
# ArrayAggregationFunction, MapAggregationFunction, HistogramAggregation,
# ApproximateCountDistinctAggregations + airlift HyperLogLog)
# ---------------------------------------------------------------------------

COLLECTION_AGGS = (
    "array_agg", "map_agg", "histogram",
    "map_union", "multimap_agg", "num_hist",
)


def collect_map_union(spec, mv, live_s, gid_s, max_groups: int,
                      max_elems: int):
    """map_union over sorted rows: explode each row's map entries into
    (key, value) pseudo-rows and run the map_agg pair machinery — the
    merged map keeps the first value seen per key (reference
    MapUnionAggregation keeps an arbitrary one)."""
    cap, width = mv.data.shape[0], mv.data.shape[1]
    keys = mv.keys
    lens = (
        mv.lengths if mv.lengths is not None
        else jnp.full(cap, width, jnp.int32)
    )
    inb = jnp.arange(width)[None, :] < lens[:, None]
    live_x = (jnp.repeat(live_s, width) & inb.reshape(-1))
    if mv.valid is not None:
        live_x = live_x & jnp.repeat(mv.valid, width)
    gid_x = jnp.repeat(gid_s, width)
    kv = Val(keys.data.reshape(-1), None, mv.type.key, keys.dict_id)
    ev = None
    if mv.elem_valid is not None:
        ev = mv.elem_valid.reshape(-1)
    vv = Val(mv.data.reshape(-1), ev, mv.type.value, mv.dict_id)
    return collect_map_agg(
        AggSpec("map_agg", None, spec.name, spec.output_type),
        kv, vv, live_x, gid_x, max_groups, max_elems,
    )


def collect_multimap_agg(spec, kv, vv, live_s, gid_s, max_groups: int,
                         max_elems: int):
    """multimap_agg(k, v): map k -> ARRAY of every v seen with k
    (reference MultimapAggregationFunction). Values ride a 3-D
    (group, key, occurrence) block; occurrences of a (group, key) pair
    are contiguous in the pair-sorted row order."""
    cap = gid_s.shape[0]
    contributes = live_s if kv.valid is None else (live_s & kv.valid)
    key_norm = hash_rows([kv])
    perm, pair_gid, first_pos, pair_count = _pair_runs(
        gid_s, key_norm, contributes, max_groups
    )
    grange = jnp.arange(max_groups, dtype=jnp.int32)
    pstart = jnp.searchsorted(pair_gid, grange, side="left").astype(jnp.int32)
    pend = jnp.searchsorted(pair_gid, grange, side="right").astype(jnp.int32)
    pcounts = pend - pstart
    j = jnp.arange(max_elems, dtype=jnp.int32)
    ppos = jnp.clip(pstart[:, None] + j[None, :], 0, cap - 1)
    inb = j[None, :] < jnp.minimum(pcounts[:, None], max_elems)
    first_row = perm[first_pos]
    keys_mat = kv.data[first_row][ppos]
    kblk = Block(
        keys_mat, T.ArrayType(kv.type), None, kv.dict_id,
        lengths=jnp.minimum(pcounts, max_elems), elem_valid=inb,
    )
    e = jnp.arange(max_elems, dtype=jnp.int32)
    vsorted = vv.data[perm]
    vpos = jnp.clip(
        first_pos[ppos][:, :, None] + e[None, None, :], 0, cap - 1
    )
    vcnt = pair_count[ppos]
    data3 = vsorted[vpos]
    ev3 = inb[:, :, None] & (
        e[None, None, :] < jnp.minimum(vcnt, max_elems)[:, :, None]
    )
    if vv.valid is not None:
        ev3 = ev3 & vv.valid[perm][vpos]
    blk = Block(
        data3, spec.output_type, None, vv.dict_id,
        lengths=jnp.minimum(pcounts, max_elems), elem_valid=ev3,
        key_block=kblk,
    )
    # mask vcnt to live windows: clipped gathers past the pair count
    # read garbage rows whose counts must not inflate the retry target
    need = jnp.maximum(
        jnp.max(pcounts), jnp.max(jnp.where(inb, vcnt, 0))
    )
    return blk, need


def numeric_histogram_agg(spec, v, contributes, gid, num_groups: int):
    """numeric_histogram(b, x): equal-width histogram over each group's
    [min, max] range, computed in one two-pass aggregate — bucket key =
    mean of its members, value = member count. The reference's
    NumericHistogramAggregation adapts bucket boundaries while
    streaming; the fixed-shape equivalent is the equi-width split of the
    exact per-group range (same bucket COUNT contract, deterministic
    boundaries)."""
    from ..expr.ir import Literal

    b = spec.input2
    buckets = int(b.value if isinstance(b, Literal) else b)
    x = v.data.astype(jnp.float64)
    if isinstance(v.type, T.DecimalType) and not v.type.is_long:
        x = x / (10 ** v.type.scale)
    big = jnp.float64(jnp.inf)
    mn = jax.ops.segment_min(
        jnp.where(contributes, x, big), gid, num_segments=num_groups
    )
    mx = jax.ops.segment_max(
        jnp.where(contributes, x, -big), gid, num_segments=num_groups
    )
    w = jnp.maximum((mx - mn) / buckets, 1e-300)
    bi = jnp.clip(
        jnp.floor((x - mn[gid]) / w[gid]).astype(jnp.int32), 0, buckets - 1
    )
    flat = gid * buckets + bi
    total = num_groups * buckets
    cnt = jax.ops.segment_sum(
        contributes.astype(jnp.float64), flat, num_segments=total
    ).reshape(num_groups, buckets)
    sx = jax.ops.segment_sum(
        jnp.where(contributes, x, 0.0), flat, num_segments=total
    ).reshape(num_groups, buckets)
    centers = sx / jnp.maximum(cnt, 1.0)
    # compact non-empty buckets to the front (empty buckets are absent
    # from the result map, like the reference)
    occupied = cnt > 0
    order = jnp.argsort(~occupied, axis=1, stable=True)
    centers = jnp.take_along_axis(centers, order, axis=1)
    weights = jnp.take_along_axis(cnt, order, axis=1)
    lens = jnp.sum(occupied, axis=1).astype(jnp.int32)
    inb = jnp.arange(buckets)[None, :] < lens[:, None]
    kblk = Block(
        centers, T.ArrayType(T.DOUBLE), None, None,
        lengths=lens, elem_valid=inb,
    )
    return Block(
        weights, T.MapType(T.DOUBLE, T.DOUBLE), None, None,
        lengths=lens, elem_valid=inb, key_block=kblk,
    )

HLL_P = 10  # 2^10 = 1024 registers; standard error 1.04/sqrt(m) ~ 3.25%
HLL_M = 1 << HLL_P


def _clz64(x):
    """Count leading zeros of a uint64 (exact, branch-free binary search:
    at each step, if the TOP `shift` bits are zero, skip them)."""
    x = x.astype(jnp.uint64)
    is_zero = x == 0
    n = jnp.zeros(x.shape, jnp.int32)
    for shift in (32, 16, 8, 4, 2, 1):
        top_zero = (x >> jnp.uint64(64 - shift)) == 0
        n = n + jnp.where(top_zero, shift, 0)
        x = jnp.where(top_zero, x << jnp.uint64(shift), x)
    return jnp.where(is_zero, 64, n)


def hll_row_registers(value, contributes):
    """(register index, rank) per row: the HLL insert decomposition."""
    from .hashing import hash_column

    h = hash_column(value.data, None)
    reg = (h >> jnp.uint64(64 - HLL_P)).astype(jnp.int32)
    rank = (_clz64(h << jnp.uint64(HLL_P)) + 1).astype(jnp.int32)
    rank = jnp.minimum(rank, 64 - HLL_P + 1)
    return jnp.where(contributes, reg, -1), rank


def hll_group_registers(value, contributes, gid, num_groups: int):
    """Per-group register arrays (num_groups, HLL_M) int8: scatter-max of
    row ranks — the mergeable HLL partial state."""
    reg, rank = hll_row_registers(value, contributes)
    flat_idx = jnp.where(
        reg >= 0, gid * HLL_M + reg, num_groups * HLL_M
    )
    flat = (
        jnp.zeros((num_groups * HLL_M + 1,), jnp.int8)
        .at[flat_idx]
        .max(rank.astype(jnp.int8), mode="drop")
    )
    return flat[:-1].reshape(num_groups, HLL_M)


def hll_merge_registers(data_s, contributes, gid, num_groups: int):
    """Elementwise max-merge of register-array rows per group."""
    masked = jnp.where(
        contributes[:, None], data_s, jnp.zeros((), data_s.dtype)
    )
    return (
        jnp.zeros((num_groups, HLL_M), data_s.dtype)
        .at[gid]
        .max(masked, mode="drop")
    )


def hll_estimate(registers):
    """(num_groups, HLL_M) registers -> int64 estimates (HLL with the
    linear-counting small-range correction)."""
    m = float(HLL_M)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    r = registers.astype(jnp.float64)
    raw = alpha * m * m / jnp.sum(jnp.exp2(-r), axis=1)
    zeros = jnp.sum(registers == 0, axis=1).astype(jnp.float64)
    linear = m * (jnp.log(m) - jnp.log(jnp.maximum(zeros, 1.0)))
    est = jnp.where((raw <= 2.5 * m) & (zeros > 0), linear, raw)
    return jnp.round(est).astype(jnp.int64)


def _run_bounds(gid_s, max_groups: int):
    """Per-group [start, count) of the contiguous runs in sorted order."""
    grange = jnp.arange(max_groups, dtype=gid_s.dtype)
    start = jnp.searchsorted(gid_s, grange, side="left").astype(jnp.int32)
    end = jnp.searchsorted(gid_s, grange, side="right").astype(jnp.int32)
    return start, end - start


def collect_array_agg(v, live_s, gid_s, max_groups: int, max_elems: int):
    """array_agg over sorted group runs: gather each run into a
    (max_groups, max_elems) matrix. Returns (block, needed_elems)."""
    start, counts = _run_bounds(gid_s, max_groups)
    j = jnp.arange(max_elems, dtype=jnp.int32)
    pos = start[:, None] + j[None, :]
    safe = jnp.clip(pos, 0, gid_s.shape[0] - 1)
    inb = j[None, :] < jnp.minimum(counts[:, None], max_elems)
    data = v.data[safe]
    ev = inb if v.valid is None else (inb & v.valid[safe])
    lengths = jnp.minimum(counts, max_elems)
    blk = Block(
        data, T.ArrayType(v.type), None, v.dict_id,
        lengths=lengths, elem_valid=ev,
    )
    return blk, jnp.max(counts)


def _pair_runs(gid_s, key_norm, contributes, max_groups: int):
    """Sort rows by (group, key) and detect distinct (group, key) runs.
    Returns (perm, pair_gid, pair_first_pos, pair_count, pair_id) where
    pair arrays have capacity length (garbage past the pair count is
    masked by pair_gid == max_groups)."""
    cap = gid_s.shape[0]
    gidc = jnp.where(contributes, gid_s, max_groups)
    o1 = jnp.argsort(key_norm, stable=True)
    o2 = jnp.argsort(gidc[o1], stable=True)
    perm = o1[o2]
    g2 = gidc[perm]
    k2 = key_norm[perm]
    boundary = jnp.ones(cap, jnp.bool_).at[1:].set(
        (g2[1:] != g2[:-1]) | (k2[1:] != k2[:-1])
    )
    pair_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    first_pos = (
        jnp.full((cap,), cap, jnp.int32)
        .at[pair_id]
        .min(jnp.arange(cap, dtype=jnp.int32))
    )
    pair_count = jnp.zeros((cap,), jnp.int32).at[pair_id].add(1)
    pair_gid = jnp.full((cap,), max_groups, jnp.int32).at[pair_id].set(
        g2.astype(jnp.int32)
    )
    return perm, pair_gid, jnp.minimum(first_pos, cap - 1), pair_count


def collect_map_agg(
    spec, kv, vv, live_s, gid_s, max_groups: int, max_elems: int
):
    """histogram / map_agg over sorted rows: distinct keys per group via a
    second (group, key) sort; values are counts (histogram) or the first
    row's value (map_agg). Returns (block, needed_elems)."""
    cap = gid_s.shape[0]
    contributes = live_s if kv.valid is None else (live_s & kv.valid)
    key_norm = hash_rows([kv])
    perm, pair_gid, first_pos, pair_count = _pair_runs(
        gid_s, key_norm, contributes, max_groups
    )
    # per-group range over the pair axis (pairs are sorted by group)
    grange = jnp.arange(max_groups, dtype=jnp.int32)
    pstart = jnp.searchsorted(pair_gid, grange, side="left").astype(jnp.int32)
    pend = jnp.searchsorted(pair_gid, grange, side="right").astype(jnp.int32)
    pcounts = pend - pstart
    j = jnp.arange(max_elems, dtype=jnp.int32)
    ppos = jnp.clip(pstart[:, None] + j[None, :], 0, cap - 1)
    inb = j[None, :] < jnp.minimum(pcounts[:, None], max_elems)
    first_row = perm[first_pos]  # pair -> original sorted-row index
    keys_mat = kv.data[first_row][ppos]
    kblk = Block(
        keys_mat, T.ArrayType(kv.type), None, kv.dict_id,
        lengths=jnp.minimum(pcounts, max_elems), elem_valid=inb,
    )
    if spec.func == "histogram":
        vals_mat = pair_count[ppos].astype(jnp.int64)
        vtype = T.BIGINT
        vdict = None
        ev = inb
    else:  # map_agg: value at the pair's first row
        vals_mat = vv.data[first_row][ppos]
        vtype = vv.type
        vdict = vv.dict_id
        ev = inb if vv.valid is None else (inb & vv.valid[first_row][ppos])
    blk = Block(
        vals_mat, T.MapType(kv.type, vtype), None, vdict,
        lengths=jnp.minimum(pcounts, max_elems), elem_valid=ev,
        key_block=kblk,
    )
    return blk, jnp.max(pcounts)
