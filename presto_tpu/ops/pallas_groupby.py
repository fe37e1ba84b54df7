"""Parameterized Pallas TPU kernel: small-G grouped aggregation.

Generalizes the hand-written Q1 kernel (ops/pallas_agg.py) into a
substrate the SQL path can route through (reference analog:
MultiChannelGroupByHash.java:54's specialized small-group loops): any
aggregate list of count / count_star / sum / avg / min / max over
integral-storage columns, grouped by up to PALLAS_MAX_GROUPS dense group
ids, compiles to ONE streaming pass — where the XLA composition runs
G x A masked reductions.

Exactness without int64 (Pallas TPU has no 64-bit reductions): sum
inputs are decomposed OUTSIDE the kernel into 16-bit limb channels
(l0, l1 unsigned, l2 = x >> 32 signed); each 16384-row block sums
channels in int32 (bound 2^16 * 2^14 = 2^30), per-block tiles combine
outside in int64 — exact for |x| < 2^45, asserted against the input
types' value bounds. min/max ride int32 channels directly (their
storage is int32-safe for the eligible types).

Eligibility (plan_grouped_aggregate, from types and dictionary lengths
alone; None otherwise): every group key is a small-domain dictionary
column or a boolean, G <= PALLAS_MAX_GROUPS, every aggregate is
count/count_star/sum/avg/min/max over integral storage (float64 for
sum/avg). The body around the kernel (mask, group id, limb channels, lane
fold, recomposition) has no host read and no data-dependent branch: the
executor traces it with the kernel as ONE program per plan shape.

DEPLOYMENT: on a `tpu` backend the kernel is compiled by Mosaic and is
the engine default for eligible aggregations (Executor._exec_aggregate);
tests/test_tpu_compile.py compiles it for a described v5e at SF1 widths.
CPU CI validates it in interpret mode against the XLA path, switched on
per query with the `pallas_groupby` session property
(Session(pallas_groupby=True) or X-Presto-Session).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..expr.compiler import evaluate
from ..obs.span import host_read
from ..page import Block, Page, dictionary_by_id
from .aggregate import AggSpec, avg_from_sum_count

BLK_ROWS = 16384  # 128 x 128 rows per grid step
# G cap: the per-block output tile gate (rows_pad <= 1024 rows) is the
# real bound — at G=64 a 16-channel plan exactly fills the 512KB tile.
# Single-pass wins GROW with G vs the XLA fallback (one data read vs
# G x A masked column reads), so eligible mid-size domains route here.
PALLAS_MAX_GROUPS = 64
MAX_CHANNELS = 128  # one output lane per channel
_SUM_BOUND = 1 << 45  # |sum input| bound keeping block limb sums in int32


def _rows_pad(num_groups: int, num_channels: int) -> int:
    """Output tile rows: one row per (group, channel), padded to the
    int32 sublane multiple (8)."""
    return -(-(num_groups * num_channels) // 8) * 8


def _kernel_factory(num_groups: int, num_channels: int, reduce_kinds,
                    dtype=jnp.int32):
    """Build the grid kernel for a (G, channels) plan. reduce_kinds[k] in
    {'add', 'min', 'max'} selects the per-channel block reduction.
    dtype is the tile/channel element type: int32 for the exact limb
    path, float32 for the hi/lo-split float64 path.

    Only SUBLANE (axis 0) reductions happen in-kernel — the generic
    lax.reduce primitive has no Mosaic lowering, and cross-lane scalar
    reduction is what the VPU is worst at. Row g*num_channels+k of the
    output tile holds channel k of group g as 128 per-lane partials; the
    lane fold happens outside the kernel in XLA int64/f64."""

    rpad = _rows_pad(num_groups, num_channels)

    def kernel(cnt_ref, *refs):
        from jax.experimental import pallas as pl

        gid_ref, live_ref = refs[0], refs[1]
        chan_refs = refs[2:-1]
        out_ref = refs[-1]
        i = pl.program_id(0)
        gid = gid_ref[:]
        base = i * BLK_ROWS
        rows = jax.lax.broadcasted_iota(jnp.int32, gid.shape, 0) * 128
        lanes = jax.lax.broadcasted_iota(jnp.int32, gid.shape, 1)
        live = ((base + rows + lanes) < cnt_ref[0]) & (live_ref[:] != 0)

        if dtype == jnp.int32:
            zero = jnp.int32(0)
            imax = jnp.int32(np.iinfo(np.int32).max)
            imin = jnp.int32(np.iinfo(np.int32).min)
        else:
            zero = dtype(0)
            imax = dtype(np.inf)
            imin = dtype(-np.inf)
        rows_out: List = []
        for g in range(num_groups):
            sel = live & (gid == g)
            for k, ref in enumerate(chan_refs):
                ch = ref[:]
                kind = reduce_kinds[k]
                if kind == "add":
                    rows_out.append(
                        jnp.sum(jnp.where(sel, ch, zero), axis=0,
                                dtype=dtype)
                    )
                elif kind == "min":
                    rows_out.append(
                        jnp.min(jnp.where(sel, ch, imax), axis=0)
                    )
                else:
                    rows_out.append(
                        jnp.max(jnp.where(sel, ch, imin), axis=0)
                    )
        rows_out.extend(
            [jnp.full((128,), zero, dtype)] * (rpad - len(rows_out))
        )
        out_ref[:] = jnp.stack(rows_out)[None]

    return kernel


def _pallas_partials(gid, live, channels, count, num_groups, reduce_kinds,
                     dtype=jnp.int32):
    """(blocks, rows_pad, 128) per-block per-lane partials in `dtype`;
    row g*len(channels)+k = channel k of group g (see _kernel_factory).

    An eager pallas_call of a fresh kernel closure compiles on every
    call (PR 22 chip run: one compile per repeat of Q1), so the call goes
    through one jit keyed on the static plan; inside a traced caller (the
    dense path's fused program) it is inlined."""
    return _pallas_partials_jit(
        gid, live, tuple(channels), count, num_groups, tuple(reduce_kinds),
        dtype, jax.default_backend() != "tpu",
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_groups", "reduce_kinds", "dtype", "interpret"),
)
def _pallas_partials_jit(gid, live, channels, count, num_groups,
                         reduce_kinds, dtype, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = gid.shape[0]
    pad = -n % BLK_ROWS
    if pad:
        with jax.named_scope("partials.pad"):
            gid = jnp.pad(gid, (0, pad))
            live = jnp.pad(live, (0, pad))
            channels = [jnp.pad(c, (0, pad)) for c in channels]
        n += pad
    blocks = n // BLK_ROWS
    view = lambda x: x.reshape(n // 128, 128)

    col_spec = pl.BlockSpec(
        (128, 128), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    kernel = _kernel_factory(
        num_groups, len(channels), tuple(reduce_kinds), dtype
    )
    rpad = _rows_pad(num_groups, len(channels))
    with jax.named_scope("partials.layout"):
        ins = (
            count.reshape(1).astype(jnp.int32),
            view(gid.astype(jnp.int32)),
            view(live.astype(jnp.int32)),
            *[view(c.astype(dtype)) for c in channels],
        )
    # trace with x64 OFF: under global x64 the BlockSpec index maps trace
    # to i64 functions, which Mosaic fails to legalize ("func.return
    # (i64)"); the kernel is explicit int32/float32 throughout.
    with jax.enable_x64(False), jax.named_scope("partials.kernel"):
        return pl.pallas_call(
            kernel,
            grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [col_spec] * (2 + len(channels)),
            out_specs=pl.BlockSpec(
                (1, rpad, 128),
                lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            out_shape=jax.ShapeDtypeStruct(
                (blocks, rpad, 128), dtype
            ),
            interpret=interpret,
        )(*ins)


_SUPPORTED = {"count", "count_star", "sum", "avg", "min", "max"}


def _eligible_keys(page: Page, group_exprs):
    """((domain, nullable) per key, G) when every key is small-domain,
    read off the page's blocks and the expressions' types: a dictionary
    column's domain is its dictionary's length, a boolean's is 2. NULL
    keys form their OWN group (SQL GROUP BY semantics), so a nullable key
    gets one slot more; a computed boolean key is taken as nullable,
    since only evaluating it would say. None when a key is anything else
    or the groups pass PALLAS_MAX_GROUPS."""
    from ..expr.ir import ColumnRef

    keys = []
    G = 1
    for e in group_exprs:
        blk = page.block(e.name) if isinstance(e, ColumnRef) else None
        if isinstance(e.type, T.BooleanType):
            d = 2
        elif (
            isinstance(e.type, T.VarcharType)
            and blk is not None
            and blk.dict_id is not None
        ):
            d = max(len(dictionary_by_id(blk.dict_id)), 1)
        else:
            return None
        nullable = blk is None or blk.valid is not None
        keys.append((d, nullable))
        G *= d + nullable
    if G > PALLAS_MAX_GROUPS:
        return None
    return tuple(keys), G


def _input_kind(t: T.Type) -> Optional[str]:
    """'int' / 'float' for a 1-D integral- or float-storage input type,
    else None (two-lane decimals, collections)."""
    if isinstance(t, (T.ArrayType, T.MapType)) or (
        isinstance(t, T.DecimalType) and t.is_long
    ):
        return None
    if isinstance(t, T.BooleanType) or jnp.issubdtype(
        t.storage_dtype, jnp.integer
    ):
        return "int"
    if jnp.issubdtype(t.storage_dtype, jnp.floating):
        return "float"
    return None


def plan_grouped_aggregate(page: Page, group_exprs, aggs: Sequence[AggSpec]):
    """The STATIC half of the dense small-G group-by: whether the shape is
    eligible, decided from aggregate names, key and input types, dictionary
    lengths, the channel count and the output tile bound. It touches no
    device array, so a caller can ask before anything is traced or
    launched. Returns the key plan of `_eligible_keys`, or None."""
    if not group_exprs:
        return None
    if any(a.func not in _SUPPORTED for a in aggs):
        return None
    keys = _eligible_keys(page, group_exprs)
    if keys is None:
        return None
    ch = fch = 0
    for a in aggs:
        if a.func in ("count", "count_star", "avg"):
            ch += 1
        if a.input is None:
            continue
        kind = _input_kind(a.input.type)
        # float64 rides the hi/lo-split f32 channel path, sum/avg only
        # (min/max would need 64-bit compares the kernel does not have)
        if kind is None or (kind == "float" and a.func in ("min", "max")):
            return None
        if a.func in ("sum", "avg"):
            if kind == "float":
                fch += 2
            else:
                ch += 3
        elif a.func in ("min", "max"):
            ch += 1
    if ch > MAX_CHANNELS or fch > MAX_CHANNELS:
        return None
    # bound the per-block output tile (rows x 128 lanes) to 512KB VMEM
    if max(_rows_pad(keys[1], ch), _rows_pad(keys[1], fch)) > 1024:
        return None
    return keys


def maybe_grouped_aggregate(
    page: Page, group_exprs, group_names, aggs: Sequence[AggSpec], pre_mask
) -> Optional[Page]:
    """Route an eligible aggregation through the Pallas kernel; None when
    the shape is not eligible (caller falls back to the XLA path). The
    whole of it: the static plan step, then the body. The body has no host
    read and no data-dependent branch, so it traces: the executor runs it
    as ONE program (`Executor._exec_aggregate`, "grouped_aggregate_pallas"),
    and run as it stands it is that program's operations one by one."""
    key_plan = plan_grouped_aggregate(page, group_exprs, aggs)
    if key_plan is None:
        return None
    return _grouped_aggregate_body(
        page, group_exprs, group_names, aggs, pre_mask, key_plan
    )


def _grouped_aggregate_body(
    page: Page, group_exprs, group_names, aggs, pre_mask, key_plan
) -> Optional[Page]:
    """Mask, dense group id, limb channels, the partials kernel, the lane
    fold, the recomposition and the compaction of empty groups. None when
    an evaluated input is not what its type promised the plan step."""
    slots, G = key_plan
    keys = [evaluate(e, page) for e in group_exprs]
    ins = []
    for a in aggs:
        if a.input is None:
            ins.append(None)
            continue
        v = evaluate(a.input, page)
        floating = jnp.issubdtype(v.data.dtype, jnp.floating)
        if v.data.ndim != 1 or floating != (
            _input_kind(a.input.type) == "float"
        ):
            return None
        ins.append(v)

    # dense mixed-radix group id; a nullable key's NULL slot is its last
    from .aggregate import _masked_live

    live = _masked_live(page, pre_mask)
    gid = jnp.zeros(page.capacity, jnp.int32)
    domains = [d for d, _ in slots]
    eff_domains = [d + nullable for d, nullable in slots]
    for v, d, eff in zip(keys, domains, eff_domains):
        code = jnp.clip(v.data.astype(jnp.int32), 0, d - 1)
        if v.valid is not None:
            code = jnp.where(v.valid, code, d)
        gid = gid * eff + code

    # channel plan: (agg index, role, limb index, reduce kind)
    channels: List = []
    plan: List[Tuple[int, str]] = []
    kinds: List[str] = []
    fchannels: List = []  # float32 hi/lo channels (their own kernel/tile)
    fplan: List[Tuple[int, str]] = []

    def add_channel(arr, tag, kind="add"):
        channels.append(arr)
        plan.append(tag)
        kinds.append(kind)

    def add_fchannel(arr, tag):
        fchannels.append(arr)
        fplan.append(tag)

    ones = jnp.ones(page.capacity, jnp.int32)
    for ai, (a, v) in enumerate(zip(aggs, ins)):
        contrib = live if v is None or v.valid is None else (live & v.valid)
        cmask = contrib.astype(jnp.int32)
        if a.func in ("count", "count_star", "avg"):
            add_channel(ones * cmask, (ai, "count", 0))
        if a.func in ("sum", "avg") and jnp.issubdtype(
            v.data.dtype, jnp.floating
        ):
            # hi/lo split: hi = f32(x), lo = f32(x - hi) represents the
            # f64 value to ~48 mantissa bits; block partials sum in f32,
            # blocks combine in f64 outside (documented tolerance — the
            # XLA f64 path is the exact-comparison oracle in tests)
            xf = v.data.astype(jnp.float64)
            hi = xf.astype(jnp.float32)
            lo = (xf - hi.astype(jnp.float64)).astype(jnp.float32)
            fm = cmask.astype(jnp.float32)
            add_fchannel(hi * fm, (ai, "fsum", 0))
            add_fchannel(lo * fm, (ai, "fsum", 1))
            continue
        if a.func in ("sum", "avg"):
            x = v.data.astype(jnp.int64)
            add_channel(
                (x & 0xFFFF).astype(jnp.int32) * cmask, (ai, "sum", 0)
            )
            add_channel(
                ((x >> 16) & 0xFFFF).astype(jnp.int32) * cmask,
                (ai, "sum", 1),
            )
            add_channel(
                (x >> 32).astype(jnp.int32) * cmask, (ai, "sum", 2)
            )
        if a.func in ("min", "max"):
            x = v.data.astype(jnp.int32)
            add_channel(
                x, (ai, a.func, 0), kind=a.func
            )  # masking happens in-kernel via `sel`
    CH = len(channels)
    if CH:
        partials = _pallas_partials(
            gid, live, channels, page.count, G, kinds
        )
        pv = (
            partials[:, : G * CH, :]
            .reshape(-1, G, CH, 128)
            .astype(jnp.int64)
        )
        s = jnp.sum(pv, axis=(0, 3))  # (G, CH)
        # min/max channels combine across blocks AND lanes by min/max
        # (their in-kernel fill values imax/imin survive empty groups)
        pmin = jnp.min(pv, axis=(0, 3))
        pmax = jnp.max(pv, axis=(0, 3))
    else:
        s = pmin = pmax = jnp.zeros((G, 0), jnp.int64)
    fs = None
    if fchannels:
        CHF = len(fchannels)
        fpartials = _pallas_partials(
            gid, live, fchannels, page.count, G,
            ["add"] * CHF, dtype=jnp.float32,
        )
        fs = jnp.sum(
            fpartials[:, : G * CHF, :]
            .reshape(-1, G, CHF, 128)
            .astype(jnp.float64),
            axis=(0, 3),
        )

    # per-agg recomposition
    by_agg: dict = {}
    for k, tag in enumerate(plan):
        by_agg.setdefault(tag[0], {})[(tag[1], tag[2])] = k
    by_agg_f: dict = {}
    for k, tag in enumerate(fplan):
        by_agg_f.setdefault(tag[0], {})[(tag[1], tag[2])] = k

    def fsum_of(ai):
        chs = by_agg_f[ai]
        return fs[:, chs[("fsum", 0)]] + fs[:, chs[("fsum", 1)]]

    counts_live = None
    out_blocks: List[Block] = []
    out_names: List[str] = []
    # group key columns from the dense gid (mixed radix decode over the
    # EFFECTIVE domains; a nullable key's last slot decodes to NULL)
    grange = jnp.arange(G, dtype=jnp.int32)
    rem = grange
    key_codes = []
    for d in reversed(eff_domains):
        key_codes.append(rem % d)
        rem = rem // d
    key_codes = list(reversed(key_codes))
    for v, nm, code, d, eff in zip(
        keys, group_names, key_codes, domains, eff_domains
    ):
        valid = (code < d) if eff != d else None
        out_blocks.append(
            Block(jnp.clip(code, 0, d - 1), v.type, valid, v.dict_id)
        )
        out_names.append(nm)

    # rows-per-group (for empty-group compaction): any count channel, else
    # compute from a dedicated pass? count channels exist for count/avg;
    # guarantee one by construction below
    group_rows = None
    for ai, a in enumerate(aggs):
        ch = by_agg.get(ai, {}).get(("count", 0))
        if ch is not None:
            group_rows = s[:, ch]
            break
    if group_rows is None:
        # no counting aggregate requested: derive occupancy with one tiny
        # XLA reduction (still one pass over gid, not per-agg)
        occ = (
            jnp.zeros(G + 1, jnp.int32)
            .at[jnp.where(live, gid, G)]
            .add(1, mode="drop")
        )
        group_rows = occ[:G].astype(jnp.int64)

    from . import decimal128 as d128

    def sum_of(ai):
        chs = by_agg[ai]
        l0 = s[:, chs[("sum", 0)]]
        l1 = s[:, chs[("sum", 1)]]
        l2 = s[:, chs[("sum", 2)]]
        return l0 + (l1 << 16) + (l2 << 32)

    for ai, a in enumerate(aggs):
        has = group_rows > 0
        if a.func in ("count", "count_star"):
            out_blocks.append(
                Block(s[:, by_agg[ai][("count", 0)]], T.BIGINT, None)
            )
        elif a.func == "sum" and ai in by_agg_f:
            out_blocks.append(
                Block(
                    fsum_of(ai).astype(a.output_type.storage_dtype),
                    a.output_type,
                    has,
                )
            )
        elif a.func == "avg" and ai in by_agg_f:
            cnt = s[:, by_agg[ai][("count", 0)]]
            data = avg_from_sum_count(
                fsum_of(ai), cnt, a.output_type, a.input.type
            )
            out_blocks.append(Block(data, a.output_type, cnt > 0))
        elif a.func == "sum":
            total = sum_of(ai)
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
        elif a.func == "avg":
            cnt = s[:, by_agg[ai][("count", 0)]]
            data = avg_from_sum_count(
                sum_of(ai), cnt, a.output_type, a.input.type
            )
            out_blocks.append(Block(data, a.output_type, cnt > 0))
        else:  # min / max
            ch = by_agg[ai][(a.func, 0)]
            col = pmin[:, ch] if a.func == "min" else pmax[:, ch]
            out_blocks.append(
                Block(
                    col.astype(a.output_type.storage_dtype),
                    a.output_type,
                    has,
                )
            )
        out_names.append(a.name)

    out = Page.from_blocks(out_blocks, out_names, count=G)
    from .filter import compact

    return compact(out, group_rows > 0)


def pallas_available() -> bool:
    return True  # interpret mode always works; TPU uses Mosaic


# -- hash-slot grouped aggregation (PR 11) -----------------------------------
#
# The dense path above needs every key to be a SMALL-DOMAIN dictionary /
# boolean column (mixed-radix gid over the domain product, G <= 64). The
# hash-slot path below lifts that ceiling: ARBITRARY-valued keys (int64
# order keys, composite keys, floats, NULLs) map to dense group ids
# through a linear-probe slot table —
# a distinct-insert pass assigns each row the slot of its key's first
# occurrence (true key equality verified against the slot's
# representative row, so 32-bit tag collisions re-probe instead of
# merging groups), occupied slots rank-compact to gid 0..G-1, and the
# accumulation runs over gids:
#
# * tpu / interp — the SAME _pallas_partials streaming kernel as the
#   dense path (gid is just no longer a radix code), eligible while the
#   output tile fits: rows_pad(G, channels) <= 1024, i.e. G up to 512
#   with a sum+count plan — an 8x group ceiling lift with identical
#   exactness (16-bit limb channels).
# * cpu (engine default for this path) — numpy bincount per limb
#   channel: one C pass per channel, exact (limb partial sums stay
#   below 2^53 for any page under 2^37 rows), beating the jitted
#   sort-compose fallback on high-NDV shapes.
#
# Behind the pallas_groupby_hash breaker; ineligible/overflow shapes
# return None and the caller falls through to the MXU one-hot matmul or
# the sort strategy exactly as before.

HASH_MAX_GROUPS_HOST = 1 << 16
_HASH_START_BITS = 13
_HASH_ROUNDS = 96  # distinct-insert advance bound before resizing


def _concrete(*arrays) -> bool:
    """Eager-only guard (the ops/sort.py idiom): the slot assignment is
    host work; traced callers keep the XLA compositions."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _keys_match(keys_np, rows_a: np.ndarray, rows_b: np.ndarray):
    """GROUP BY equality of key tuples at rows_a vs rows_b: NULL == NULL,
    NaN == NaN, -0.0 == 0.0 (reference doubleToLongBits grouping)."""
    ok = np.ones(len(rows_a), bool)
    for data, valid in keys_np:
        a, b = data[rows_a], data[rows_b]
        part = a == b
        if np.issubdtype(data.dtype, np.floating):
            part = part | (np.isnan(a) & np.isnan(b))
        if part.ndim == 2:
            part = part.all(axis=-1)
        if valid is not None:
            va, vb = valid[rows_a], valid[rows_b]
            part = (part & va & vb) | (~va & ~vb)
        ok &= part
    return ok


def _assign_slots(tag: np.ndarray, keys_np, live: np.ndarray, bits: int):
    """Distinct-insert: every live row ends at the slot of its key's
    first occurrence. Returns (slot_of_row, slot_rep, occupied) or None
    when displacement exhausts _HASH_ROUNDS (caller retries with a
    bigger table)."""
    size = (1 << bits) + _HASH_ROUNDS + 2
    limit = size - 2
    slot_rep = np.full(size, -1, np.int64)  # representative row per slot
    slot_tag = np.full(size, np.uint32(0xFFFFFFFF), np.uint32)
    desired = (tag >> np.uint32(32 - bits)).astype(np.int64)
    n = len(tag)
    slot_of = np.full(n, -1, np.int64)
    pend = np.flatnonzero(live)
    off = np.zeros(n, np.int64)
    for _ in range(2 * _HASH_ROUNDS):
        if not len(pend):
            break
        cand = np.minimum(desired[pend] + off[pend], limit)
        occ = slot_rep[cand] >= 0
        done = np.zeros(len(pend), bool)
        # (a) occupied: join when tag AND true keys match the
        # representative; otherwise advance (collision / other group)
        if occ.any():
            same = occ & (slot_tag[cand] == tag[pend])
            if same.any():
                si = np.flatnonzero(same)
                km = _keys_match(
                    keys_np, pend[si], slot_rep[cand[si]]
                )
                joined = si[km]
                slot_of[pend[joined]] = cand[joined]
                done[joined] = True
                off[pend[si[~km]]] += 1
            off[pend[occ & ~same]] += 1
        # (b) vacant: race-insert; winners become representatives,
        # losers retry the SAME slot next round (it is occupied now)
        vac = ~occ
        if vac.any():
            vi = np.flatnonzero(vac)
            vc = pend[vi]
            c = cand[vi]
            slot_rep[c] = vc  # last writer wins
            won = slot_rep[c] == vc
            slot_tag[c[won]] = tag[vc[won]]
            slot_of[vc[won]] = c[won]
            done[vi[won]] = True
        if len(pend) and off[pend].max(initial=0) >= _HASH_ROUNDS:
            return None
        pend = pend[~done]
    if len(pend):
        return None
    occupied = np.flatnonzero(slot_rep >= 0)
    return slot_of, slot_rep, occupied


_HASH_SUPPORTED = _SUPPORTED  # count / count_star / sum / avg / min / max


def _estimate_ndv(tag: np.ndarray, live: np.ndarray, sample: int = 8192) -> int:
    """Cheap NDV estimate from distinct tags in a strided sample: when
    the sample is mostly repeats the domain is about the distinct count;
    when it is mostly unique, scale up linearly (over-estimating is the
    safe direction — it only skips the hash path)."""
    rows = np.flatnonzero(live)
    n = len(rows)
    if n == 0:
        return 0
    if n > sample:
        rows = rows[:: max(n // sample, 1)][:sample]
    u = len(np.unique(tag[rows]))
    s = len(rows)
    if u < s // 2:
        return max(int(u * 1.25), 1)
    return max(int(n * (u / max(s, 1))), 1)


# prestolint: host-function -- eager host orchestration: device key eval,
# host slot assignment, backend-dispatched accumulation
def maybe_grouped_aggregate_hash(
    page: Page, group_exprs, group_names, aggs: Sequence[AggSpec], pre_mask
) -> Optional[Page]:
    """Hash-slot grouped aggregation; None when ineligible (caller falls
    through to the matmul / sort strategies)."""
    if not group_exprs:
        return None
    if any(a.func not in _HASH_SUPPORTED for a in aggs):
        return None
    from .aggregate import _masked_live
    from .hashing import hash_rows

    keys = [evaluate(e, page) for e in group_exprs]
    probe_arrays = [k.data for k in keys] + [page.count]
    if not _concrete(*probe_arrays):
        return None
    mode = _hash_groupby_mode()
    if mode == "off":
        return None
    ins = []
    for a in aggs:
        if a.input is None:
            ins.append(None)
            continue
        v = evaluate(a.input, page)
        if v.data.ndim != 1 or not _concrete(v.data):
            return None
        integral = jnp.issubdtype(v.data.dtype, jnp.integer) or isinstance(
            v.type, T.BooleanType
        )
        floating = jnp.issubdtype(v.data.dtype, jnp.floating)
        if not integral and not floating:
            return None
        if floating and a.func in ("min", "max") and mode != "host":
            return None  # float compares don't ride the int32 channels
        if (
            mode != "host"
            and a.func in ("min", "max")
            and v.data.dtype.itemsize > 4
        ):
            return None  # 64-bit min/max needs the host path
        if a.func in ("sum", "avg") and not jnp.issubdtype(
            v.data.dtype, jnp.floating
        ):
            amax = int(np.abs(host_read(v.data)).max(initial=0))
            if isinstance(a.input.type, T.DecimalType):
                # decimal sums must stay EXACT: this path totals in
                # int64 limbs (the sort strategy carries two-lane d128),
                # so bail when |sum| could pass 2^61 — avg's HALF_UP
                # rounding computes 2*|sum|+cnt, which must also fit
                if amax and amax * page.capacity >= (1 << 61):
                    return None
            if mode != "host" and amax >= _SUM_BOUND:
                # the pallas limb kernel's high-limb block partials sum
                # in int32 (module header bound: exact for |x| < 2^45);
                # the host bincount path chunks exactly, so only the
                # kernel modes bail
                return None
        ins.append(v)

    live = host_read(_masked_live(page, pre_mask))
    h = host_read(hash_rows(keys))
    tag = np.minimum(
        (h >> np.uint64(32)).astype(np.uint32), np.uint32(0xFFFFFFFE)
    )
    keys_np = [
        (
            host_read(k.data),
            None if k.valid is None else host_read(k.valid),
        )
        for k in keys
    ]
    cap = HASH_MAX_GROUPS_HOST if mode == "host" else 1 << 10
    # size the table from a sampled NDV estimate: a table sized for the
    # wrong order of magnitude costs a full doomed insert pass before the
    # resize loop can react (measured 5x worse than the sort fallback at
    # NDV 30k), and an estimate far above the cap means the sort/matmul
    # strategies win anyway — bail before paying anything
    est = _estimate_ndv(tag, live)
    if est > 2 * cap:
        return None
    # table size is independent of the group cap: start at the estimate
    # (4x headroom) and grow on displacement overflow / hot load, up to
    # 2x cap slots (a table larger than the cap only means a cooler load)
    max_bits = max(
        _HASH_START_BITS, int(np.ceil(np.log2(max(cap * 2, 2))))
    )
    bits = min(
        max(int(np.ceil(np.log2(max(est * 4, 16)))), 8), max_bits
    )
    assigned = None
    while assigned is None and bits <= max_bits:
        assigned = _assign_slots(tag, keys_np, live, bits)
        if assigned is not None and bits < max_bits:
            # resize when the table ran hot (load > 1/2): scans stay short
            if len(assigned[2]) * 2 > (1 << bits):
                assigned = None
        if assigned is None:
            bits += 2
    if assigned is None:
        return None
    slot_of, slot_rep, occupied = assigned
    G = len(occupied)
    if G == 0 or G > cap:
        return None
    rank = np.zeros(len(slot_rep), np.int64)
    rank[occupied] = np.arange(G)
    gid = np.where(live, rank[np.maximum(slot_of, 0)], 0)
    reps = slot_rep[occupied]

    if mode == "host":
        agg_blocks = _host_accumulate(gid, live, aggs, ins, G)
    else:
        agg_blocks = _pallas_accumulate(gid, live, aggs, ins, G, page)
    if agg_blocks is None:
        return None

    out_blocks: List[Block] = []
    out_names: List[str] = []
    for v, nm in zip(keys, group_names):
        data, valid = host_read(v.data), v.valid
        out_blocks.append(
            Block(
                jnp.asarray(data[reps]),
                v.type,
                None if valid is None else jnp.asarray(
                    host_read(valid)[reps]
                ),
                v.dict_id,
            )
        )
        out_names.append(nm)
    for b, a in zip(agg_blocks, aggs):
        out_blocks.append(b)
        out_names.append(a.name)
    return Page.from_blocks(out_blocks, out_names, count=G)


def _hash_groupby_mode() -> str:
    import os

    forced = os.environ.get("PRESTO_TPU_PALLAS_GROUPBY_HASH", "")
    if forced in ("0", "off"):
        return "off"
    if forced == "interp":
        return "interp"
    return "pallas" if jax.default_backend() == "tpu" else "host"


def _contrib_mask(live, v) -> np.ndarray:
    if v is None or v.valid is None:
        return live
    return live & host_read(v.valid)


def _host_accumulate(gid, live, aggs, ins, G) -> Optional[List[Block]]:
    """numpy bincount accumulation: one C pass per limb channel, exact
    (16-bit limbs keep partial sums below 2^53)."""
    from . import decimal128 as d128

    out: List[Block] = []
    counts_cache = {}

    def counts_for(ai, v):
        c = counts_cache.get(ai)
        if c is None:
            m = _contrib_mask(live, v)
            c = np.bincount(gid[m], minlength=G).astype(np.int64)
            counts_cache[ai] = c
        return c

    def exact_sum(x: np.ndarray, m: np.ndarray) -> np.ndarray:
        g = gid[m]
        x = x[m].astype(np.int64)
        # bincount accumulates in f64: 16-bit limbs stay exact to 2^37
        # rows, but the signed high limb can reach 2^31 — chunk it so no
        # partial passes 2^53 regardless of value distribution
        total = np.zeros(G, np.int64)
        step = 1 << 21
        for s0 in range(0, len(x), step):
            xs, gs = x[s0 : s0 + step], g[s0 : s0 + step]
            l0 = np.bincount(gs, weights=(xs & 0xFFFF).astype(np.float64),
                             minlength=G).astype(np.int64)
            l1 = np.bincount(
                gs, weights=((xs >> 16) & 0xFFFF).astype(np.float64),
                minlength=G,
            ).astype(np.int64)
            l2 = np.bincount(gs, weights=(xs >> 32).astype(np.float64),
                             minlength=G).astype(np.int64)
            total += l0 + (l1 << 16) + (l2 << 32)
        return total

    for ai, (a, v) in enumerate(zip(aggs, ins)):
        if a.func in ("count", "count_star"):
            out.append(
                Block(jnp.asarray(counts_for(ai, v)), T.BIGINT, None)
            )
            continue
        m = _contrib_mask(live, v)
        data = host_read(v.data)
        has = counts_for(ai, v) > 0
        if a.func in ("sum", "avg"):
            if np.issubdtype(data.dtype, np.floating):
                total = np.bincount(
                    gid[m], weights=data[m].astype(np.float64), minlength=G
                )
            else:
                total = exact_sum(data, m)
            if a.func == "avg":
                cnt = counts_for(ai, v)
                res = avg_from_sum_count(
                    jnp.asarray(total), jnp.asarray(cnt), a.output_type,
                    a.input.type,
                )
                out.append(Block(res, a.output_type, jnp.asarray(has)))
            elif isinstance(a.output_type, T.DecimalType) and (
                a.output_type.is_long
            ):
                out.append(
                    Block(
                        d128.from_int64(jnp.asarray(total)), a.output_type,
                        jnp.asarray(has),
                    )
                )
            else:
                res = jnp.asarray(total).astype(a.output_type.storage_dtype)
                out.append(Block(res, a.output_type, jnp.asarray(has)))
            continue
        # min / max via ufunc.at (correct for any width; the tpu path
        # restricts to int32-safe storage instead)
        if np.issubdtype(data.dtype, np.floating):
            init = np.inf if a.func == "min" else -np.inf
            acc = np.full(G, init, np.float64)
            red = np.minimum if a.func == "min" else np.maximum
            red.at(acc, gid[m], data[m].astype(np.float64))
        else:
            info = np.iinfo(np.int64)
            init = info.max if a.func == "min" else info.min
            acc = np.full(G, init, np.int64)
            red = np.minimum if a.func == "min" else np.maximum
            red.at(acc, gid[m], data[m].astype(np.int64))
        res = jnp.asarray(acc).astype(a.output_type.storage_dtype)
        out.append(Block(res, a.output_type, jnp.asarray(has)))
    return out


# prestolint: host-function -- eager host orchestration around the
# partials kernel (concrete gid/live; occupancy bincount runs on host)
def _pallas_accumulate(gid, live, aggs, ins, G, page) -> Optional[List[Block]]:
    """Accumulate over hash gids with the SAME streaming kernel as the
    dense path (_pallas_partials): limb channels, per-block partials,
    int64/f64 combine outside. None when the output tile gate
    (rows_pad <= 1024) rejects this (G, channels) plan."""
    channels: List = []
    kinds: List[str] = []
    plan: List[Tuple[int, str, int]] = []
    fchannels: List = []
    fplan: List[Tuple[int, str, int]] = []
    livej = jnp.asarray(live)
    ones = jnp.ones(len(gid), jnp.int32)

    for ai, (a, v) in enumerate(zip(aggs, ins)):
        contrib = (
            livej
            if v is None or v.valid is None
            else (livej & jnp.asarray(v.valid))
        )
        cmask = contrib.astype(jnp.int32)
        if a.func in ("count", "count_star", "avg"):
            channels.append(ones * cmask)
            plan.append((ai, "count", 0))
            kinds.append("add")
        if a.func in ("sum", "avg") and jnp.issubdtype(
            v.data.dtype, jnp.floating
        ):
            xf = v.data.astype(jnp.float64)
            hi = xf.astype(jnp.float32)
            lo = (xf - hi.astype(jnp.float64)).astype(jnp.float32)
            fm = cmask.astype(jnp.float32)
            fchannels.append(hi * fm)
            fplan.append((ai, "fsum", 0))
            fchannels.append(lo * fm)
            fplan.append((ai, "fsum", 1))
            continue
        if a.func in ("sum", "avg"):
            x = v.data.astype(jnp.int64)
            for li, limb in enumerate(
                ((x & 0xFFFF), ((x >> 16) & 0xFFFF), (x >> 32))
            ):
                channels.append(limb.astype(jnp.int32) * cmask)
                plan.append((ai, "sum", li))
                kinds.append("add")
        if a.func in ("min", "max"):
            # pre-mask NULL inputs with the fold identity: the kernel's
            # row mask is group-level liveness only
            fill = jnp.int32(
                np.iinfo(np.int32).max if a.func == "min"
                else np.iinfo(np.int32).min
            )
            channels.append(
                jnp.where(contrib, v.data.astype(jnp.int32), fill)
            )
            plan.append((ai, a.func, 0))
            kinds.append(a.func)
    if len(channels) > MAX_CHANNELS or len(fchannels) > MAX_CHANNELS:
        return None
    if max(
        _rows_pad(G, len(channels)), _rows_pad(G, len(fchannels)), 8
    ) > 1024:
        return None

    gidj = jnp.asarray(gid.astype(np.int32))
    count = jnp.asarray(np.int32(len(gid)))  # liveness rides the mask
    CH = len(channels)
    if CH:
        partials = _pallas_partials(gidj, livej, channels, count, G, kinds)
        pv = partials[:, : G * CH, :].reshape(-1, G, CH, 128).astype(
            jnp.int64
        )
        s = jnp.sum(pv, axis=(0, 3))
        pmin = jnp.min(pv, axis=(0, 3))
        pmax = jnp.max(pv, axis=(0, 3))
    else:
        s = pmin = pmax = jnp.zeros((G, 0), jnp.int64)
    fs = None
    if fchannels:
        CHF = len(fchannels)
        fpartials = _pallas_partials(
            gidj, livej, fchannels, count, G, ["add"] * CHF,
            dtype=jnp.float32,
        )
        fs = jnp.sum(
            fpartials[:, : G * CHF, :].reshape(-1, G, CHF, 128).astype(
                jnp.float64
            ),
            axis=(0, 3),
        )

    by_agg: dict = {}
    for k, (ai, role, li) in enumerate(plan):
        by_agg.setdefault(ai, {})[(role, li)] = k
    by_agg_f: dict = {}
    for k, (ai, role, li) in enumerate(fplan):
        by_agg_f.setdefault(ai, {})[(role, li)] = k

    from . import decimal128 as d128

    out: List[Block] = []
    for ai, (a, v) in enumerate(zip(aggs, ins)):
        if a.func in ("count", "count_star"):
            out.append(Block(s[:, by_agg[ai][("count", 0)]], T.BIGINT, None))
            continue
        if ai in by_agg and ("count", 0) in by_agg[ai]:
            cnt = s[:, by_agg[ai][("count", 0)]]
        else:
            m = _contrib_mask(live, v)
            cnt = jnp.asarray(
                np.bincount(gid[m], minlength=G).astype(np.int64)
            )
        has = cnt > 0
        if ai in by_agg_f:
            chs = by_agg_f[ai]
            total = fs[:, chs[("fsum", 0)]] + fs[:, chs[("fsum", 1)]]
            if a.func == "avg":
                out.append(
                    Block(
                        avg_from_sum_count(
                            total, cnt, a.output_type, a.input.type
                        ),
                        a.output_type, has,
                    )
                )
            else:
                out.append(
                    Block(
                        total.astype(a.output_type.storage_dtype),
                        a.output_type, has,
                    )
                )
            continue
        if a.func in ("sum", "avg"):
            chs = by_agg[ai]
            total = (
                s[:, chs[("sum", 0)]]
                + (s[:, chs[("sum", 1)]] << 16)
                + (s[:, chs[("sum", 2)]] << 32)
            )
            if a.func == "avg":
                out.append(
                    Block(
                        avg_from_sum_count(
                            total, cnt, a.output_type, a.input.type
                        ),
                        a.output_type, has,
                    )
                )
            elif isinstance(a.output_type, T.DecimalType) and (
                a.output_type.is_long
            ):
                out.append(
                    Block(d128.from_int64(total), a.output_type, has)
                )
            else:
                out.append(
                    Block(
                        total.astype(a.output_type.storage_dtype),
                        a.output_type, has,
                    )
                )
            continue
        ch = by_agg[ai][(a.func, 0)]
        col = pmin[:, ch] if a.func == "min" else pmax[:, ch]
        out.append(
            Block(col.astype(a.output_type.storage_dtype), a.output_type, has)
        )
    return out
