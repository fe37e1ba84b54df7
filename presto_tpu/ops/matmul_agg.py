"""Grouped aggregation as one-hot matmuls on the MXU.

The high-NDV middle ground between the small-G Pallas kernel
(ops/pallas_groupby.py, G <= 32) and the general hash-sort strategy
(ops/aggregate.grouped_aggregate_sorted): for dense group ids up to
G = 4096, grouped count/sum/avg is literally a matrix product —

    partials[g, c] = sum_rows onehot[row, g] * channel[row, c]
                   = (onehot^T @ channels)[g, c]

which is exactly what the MXU does at hundreds of TFLOP/s, vs the sort
strategy whose cost is dominated by an O(n log^2 n) XLA sort. The
reference's analog is the dense array-addressed group-by fast path for
small integer keys (presto-main/.../operator/aggregation/
BigintGroupByHash.java:52 — when keys fit a dense range it indexes an
array instead of hashing); the MXU formulation is the TPU-native
equivalent of that dense addressing.

Exactness (this path is EXACT, not approximate): integer inputs are
decomposed into SIGN-SPLIT 7-bit limbs (8 limbs cover |x| < 2^56; the
per-type sum contract sum|x| < 2^63 is the same one the other
strategies rely on). Each limb value (0..127) is exact in bfloat16;
one-hot entries are 0/1; per-chunk dot products accumulate in f32 where
partial sums stay below 127 * CHUNK_ROWS = 2.6e5 << 2^24, so every f32
partial is integral and exact; chunk partials accumulate in int64
outside the dot. Float inputs are NOT eligible (the Pallas or sort
strategies take those).

Group keys: dictionary varchar / boolean (like the Pallas path) plus
dense-range INTEGER keys — the executor host-syncs the key's min/max
(it already syncs per-aggregation for adaptive capacity) and any key
whose value range fits the group budget gets dense codes. NULL keys
form their own group (SQL semantics), encoded as an extra slot per key.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..expr.compiler import evaluate
from ..obs.span import host_read
from ..page import Block, Page
from .aggregate import AggSpec, avg_from_sum_count

MATMUL_MAX_GROUPS = 4096
CHUNK_ROWS = 2048
LIMB_BITS = 7
N_LIMBS = 8  # covers |x| < 2^56
MAX_CHANNELS = 512
_SUPPORTED = {"count", "count_star", "sum", "avg"}


def _limb_channels(x, mask):
    """Sign-split 7-bit limb channels of int64 `x` under `mask`:
    2 * N_LIMBS bf16 columns (positive limbs, then negated-negative)."""
    pos = jnp.where(mask & (x >= 0), x, 0)
    neg = jnp.where(mask & (x < 0), -x, 0)
    cols = []
    for src in (pos, neg):
        for k in range(N_LIMBS):
            cols.append(
                ((src >> (LIMB_BITS * k)) & 0x7F).astype(jnp.bfloat16)
            )
    return cols


def _recombine(s, base):
    """int64 limb sums (G, nch) at channel offset base -> (G,) int64."""
    total = s[:, base]
    for k in range(1, N_LIMBS):
        total = total + (s[:, base + k] << (LIMB_BITS * k))
    return total


def grouped_matmul_partials(gid, channels, G: int):
    """(G, nch) int64 exact channel sums via chunked one-hot matmuls.

    gid: int32 (n,) in [0, G) (dead rows must carry all-zero channels);
    channels: list of (n,) bf16 columns."""
    n = gid.shape[0]
    nch = len(channels)
    pad = -n % CHUNK_ROWS
    if pad:
        gid = jnp.pad(gid, (0, pad))
        channels = [jnp.pad(c, (0, pad)) for c in channels]
        n += pad
    chunks = n // CHUNK_ROWS
    gidm = gid.reshape(chunks, CHUNK_ROWS)
    chm = jnp.stack(channels, axis=-1).reshape(chunks, CHUNK_ROWS, nch)
    garange = jnp.arange(G, dtype=jnp.int32)

    def step(carry, inputs):
        g, ch = inputs
        onehot = (g[:, None] == garange[None, :]).astype(jnp.bfloat16)
        # (G, CHUNK) @ (CHUNK, nch) on the MXU, f32 accumulation
        part = jax.lax.dot_general(
            onehot.T, ch,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry + part.astype(jnp.int64), None

    init = jnp.zeros((G, nch), jnp.int64)
    out, _ = jax.lax.scan(step, init, (gidm, chm))
    return out


def plan_matmul_grouped_aggregate(page: Page, group_exprs, aggs, pre_mask):
    """HOST side of eligibility: decide dense domains/bases, syncing key
    min/max where needed. Must run EAGERLY (outside jit) — the resulting
    plan (all python ints) is static, so `_apply` below is traceable.

    Plan = (domains, bases): `bases[i]` is the rebase value for integer
    keys (None otherwise); NULL adds one extra slot per nullable key."""
    if not group_exprs:
        return None
    if any(a.func not in _SUPPORTED for a in aggs):
        return None
    from .aggregate import _masked_live

    live = _masked_live(page, pre_mask)
    domains, bases = [], []
    for e in group_exprs:
        v = evaluate(e, page)
        base = None
        if isinstance(v.type, T.VarcharType) and v.dictionary is not None:
            d = max(len(v.dictionary), 1)
        elif isinstance(v.type, T.BooleanType):
            d = 2
        elif v.data.ndim == 1 and jnp.issubdtype(v.data.dtype, jnp.integer):
            ok = live if v.valid is None else (live & v.valid)
            if not bool(host_read(jnp.any(ok))):
                d = 1
            else:
                big = jnp.iinfo(jnp.int64)
                data = v.data.astype(jnp.int64)
                mn = int(host_read(jnp.min(jnp.where(ok, data, big.max))))
                mx = int(host_read(jnp.max(jnp.where(ok, data, big.min))))
                span = mx - mn + 1
                if span > MATMUL_MAX_GROUPS:
                    return None
                d = int(span)
                base = mn
        else:
            return None
        if v.valid is not None:  # NULL keys get their own group slot
            d += 1
        if d > MATMUL_MAX_GROUPS:
            return None
        domains.append(d)
        bases.append(base)
    total = 1
    for d in domains:
        total *= d
    if not 0 < total <= MATMUL_MAX_GROUPS:
        return None
    return tuple(domains), tuple(bases)


def _key_codes(page: Page, group_exprs, plan):
    """Traceable re-evaluation of keys -> dense codes under a static plan."""
    domains, bases = plan
    keys, codes = [], []
    for e, d, base in zip(group_exprs, domains, bases):
        v = evaluate(e, page)
        d_data = d - (1 if v.valid is not None else 0)  # non-null slots
        if base is not None:
            code = (v.data.astype(jnp.int64) - base).astype(jnp.int32)
        else:
            code = v.data.astype(jnp.int32)
        code = jnp.clip(code, 0, max(d_data - 1, 0))
        if v.valid is not None:
            code = jnp.where(v.valid, code, d - 1)  # null slot = last
        keys.append(v)
        codes.append(code)
    return keys, codes


def maybe_matmul_grouped_aggregate(
    page: Page, group_exprs, group_names, aggs: Sequence[AggSpec], pre_mask,
    plan=None,
) -> Optional[Page]:
    """Route an eligible aggregation through the MXU path; None when not
    eligible (caller falls back to the sort strategy). Pass a
    pre-computed `plan` (plan_matmul_grouped_aggregate) to make this
    call fully traceable under jit."""
    if plan is None:
        plan = plan_matmul_grouped_aggregate(
            page, group_exprs, aggs, pre_mask
        )
    if plan is None:
        return None
    from .aggregate import _masked_live

    live = _masked_live(page, pre_mask)
    keys, codes = _key_codes(page, group_exprs, plan)
    domains, bases = plan
    ins = []
    for a in aggs:
        if a.input is None:
            ins.append(None)
            continue
        v = evaluate(a.input, page)
        if v.data.ndim != 1:
            return None
        if not (
            jnp.issubdtype(v.data.dtype, jnp.integer)
            or isinstance(v.type, T.BooleanType)
        ):
            return None  # floats ride the Pallas / sort strategies
        ins.append(v)

    gid = jnp.zeros(page.capacity, jnp.int32)
    for code, d in zip(codes, domains):
        gid = gid * d + code
    G = 1
    for d in domains:
        G *= d
    gid = jnp.where(live, gid, 0)  # dead rows: gid 0 with zero channels

    # channel plan: (agg idx, role, base channel index)
    channels: List = []
    plan: List[Tuple[int, str, int]] = []
    for ai, (a, v) in enumerate(zip(aggs, ins)):
        m = live if (v is None or v.valid is None) else (live & v.valid)
        if a.func in ("count", "count_star", "avg"):
            plan.append((ai, "count", len(channels)))
            channels.append(m.astype(jnp.bfloat16))
        if a.func in ("sum", "avg"):
            plan.append((ai, "sum", len(channels)))
            channels.extend(_limb_channels(v.data.astype(jnp.int64), m))
    if len(channels) > MAX_CHANNELS:
        return None

    if channels:
        s = grouped_matmul_partials(gid, channels, G)
    else:  # pure GROUP BY / DISTINCT: occupancy only, no dot needed
        s = jnp.zeros((G, 0), jnp.int64)

    def sum_of(base):
        return _recombine(s, base) - _recombine(s, base + N_LIMBS)

    by_agg: dict = {}
    for ai, role, base in plan:
        by_agg.setdefault(ai, {})[role] = base

    # group key columns decoded from the dense gid (mixed radix)
    grange = jnp.arange(G, dtype=jnp.int32)
    rem = grange
    key_codes = []
    for d in reversed(domains):
        key_codes.append(rem % d)
        rem = rem // d
    key_codes = list(reversed(key_codes))
    out_blocks: List[Block] = []
    out_names: List[str] = []
    for v, nm, code, d, base in zip(
        keys, group_names, key_codes, domains, bases
    ):
        valid = None
        if v.valid is not None:  # last slot of this key's radix = NULL
            valid = code < (d - 1)
        if base is not None:
            data = (code.astype(jnp.int64) + base).astype(v.data.dtype)
        else:
            data = code
        out_blocks.append(Block(data, v.type, valid, v.dict_id))
        out_names.append(nm)

    # rows-per-group for empty-group compaction
    group_rows = None
    for ai, a in enumerate(aggs):
        base = by_agg.get(ai, {}).get("count")
        if base is not None:
            group_rows = s[:, base]
            break
    if group_rows is None:
        occ = (
            jnp.zeros(G + 1, jnp.int32)
            .at[jnp.where(live, gid, G)]
            .add(1, mode="drop")
        )
        group_rows = occ[:G].astype(jnp.int64)

    from . import decimal128 as d128

    for ai, a in enumerate(aggs):
        has = group_rows > 0
        roles = by_agg[ai]
        if a.func in ("count", "count_star"):
            out_blocks.append(Block(s[:, roles["count"]], T.BIGINT, None))
        elif a.func == "sum":
            total = sum_of(roles["sum"])
            if isinstance(a.output_type, T.DecimalType) and a.output_type.is_long:
                out_blocks.append(
                    Block(d128.from_int64(total), a.output_type, has)
                )
            else:
                out_blocks.append(
                    Block(
                        total.astype(a.output_type.storage_dtype),
                        a.output_type,
                        has,
                    )
                )
        else:  # avg over ints
            cnt = s[:, roles["count"]]
            data = avg_from_sum_count(
                sum_of(roles["sum"]), cnt, a.output_type, a.input.type
            )
            out_blocks.append(Block(data, a.output_type, cnt > 0))
        out_names.append(a.name)

    out = Page.from_blocks(out_blocks, out_names, count=G)
    from .filter import compact

    return compact(out, group_rows > 0)
