"""Composite-key packing: an ordered tuple of sort/partition keys mapped
to ONE lexicographically-ordered fixed-width integer key.

The reference engine's hot core (PagesIndex / OrderByOperator,
presto-main/.../operator/) compares multi-key rows field-by-field per
position; our pre-packing kernels paid the same tax in array form — a
variadic `lax.sort` moves and compares one operand array per key plus one
per null flag. BENCH_r05 showed the arithmetic of combining keys is free
(`hash_rows_2key` 3.0B rows/s) while every order-sensitive operator ran at
1-3M rows/s, so the win is collapsing K keys into a single device key and
sorting ONCE ("Accelerating Presto with GPUs" makes the same argument for
GPU sort-based operators).

Three strategies, chosen per plan node on the host (widths must be static
under jit):

* ``bitpack`` — every key's (null bit + payload rank) bit-packed into one
  int64 lane, most-significant key first. Payload widths come from exact
  type ranges (bools, small ints, dates, REAL via the float total-order
  transform, dict-encoded strings by dictionary size, short decimals by
  precision) or, for 64-bit keys, from CBO min/max stats
  (plan/stats.ColumnStats). Stats-derived lanes carry a runtime range
  check: connector stats are SAMPLED, so a value outside [lo, hi] flips
  the `ok` flag and the caller degrades to the legacy kernel.
* ``two_lane`` — the same field stream split across two int64 lanes
  (split only at field boundaries), sorted with one fused two-key pass.
* ``hashed`` — the equality-only consumer (DISTINCT) gets a 64-bit row
  hash when its keys don't bit-pack; a post-hoc adjacent-collision check
  degrades to the legacy path on the (rare) colliding batch. (Windows
  can't use it: their order keys need true ordering, so an unpackable
  window spec runs the legacy kernel.)

`PRESTO_TPU_KEYPACK=0` disables packing engine-wide; the executor also
runs every packed kernel behind a `keypack_*` circuit breaker
(exec/breaker.py) whose fallback is the legacy iterated path.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..obs.span import host_read

# Per-lane payload budget: values stay < 2**62, strictly below the
# INT64_MAX dead-row sentinel, and negation for the `lax.top_k` TopN path
# can never overflow.
LANE_BITS = 62
_I64_MAX = np.int64(np.iinfo(np.int64).max)


def keypack_enabled() -> bool:
    return os.environ.get("PRESTO_TPU_KEYPACK", "1") != "0"


@dataclasses.dataclass(frozen=True)
class KeyInfo:
    """Host-side facts about one key column, gathered BEFORE tracing
    (executor: from the input page's blocks + CBO column stats; benches
    and tests: from exact device min/max via `plan_from_page`)."""

    type: T.Type
    nullable: bool = True
    dict_len: Optional[int] = None
    dict_sorted: bool = True
    # exact-or-conservative STORAGE bounds (scaled decimal units, epoch
    # days, raw int64); None = unknown
    lo: Optional[int] = None
    hi: Optional[int] = None
    # bounds are exact (device-computed min/max) rather than sampled CBO
    # estimates: exact bounds need no runtime range check
    exact_bounds: bool = False


@dataclasses.dataclass(frozen=True)
class FieldSpec:
    """One bit field in the packed stream. Fields appear most-significant
    first; a key contributes an optional 1-bit null flag field followed by
    its payload field. A 'native' field is a full-width 64-bit payload
    (raw int64 / float total-order key) that occupies a whole lane by
    itself — legal only after at least one packed lane, whose sub-2**62
    values keep the INT64_MAX dead-row sentinel unambiguous."""

    key_index: int
    kind: str  # 'null'|'bool'|'int'|'dict'|'f32'|'range'|'frange'|'native'
    bits: int
    lo: int = 0  # bias for 'range'/'frange' (storage / total-order units)
    hi: int = 0
    desc: bool = False
    nulls_first: bool = False  # 'null' fields only
    checked: bool = False  # stats-derived: needs the runtime range check


@dataclasses.dataclass(frozen=True)
class KeyPackPlan:
    strategy: str  # 'bitpack' | 'two_lane' | 'hashed'
    lanes: Tuple[Tuple[FieldSpec, ...], ...]  # () for 'hashed'
    needs_check: bool
    # window use (single-lane bitpack): number of LOW bits in the lane
    # occupied by the order-key fields — partition identity is the packed
    # key shifted right by this amount
    order_bits: int = 0
    # CPU backend: run the packed-key argsort/top-n through numpy via
    # jax.pure_callback. XLA's CPU comparison sort runs ~2M rows/s
    # single-threaded while numpy's sorts run 8-70M rows/s on the same
    # key array; packing makes the handoff ONE int64 column, so the
    # callback is cheap. Resolved at PLAN time from the live backend —
    # never set for TPU plans, where a host round trip per sort would be
    # catastrophic and lax.sort/top_k are the right primitives.
    host_sort: bool = False

    @property
    def single_lane(self) -> bool:
        return self.strategy == "bitpack" and len(self.lanes) == 1


def _default_host_sort() -> bool:
    import jax

    if os.environ.get("PRESTO_TPU_KEYPACK_HOST_SORT", "") == "0":
        return False
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# planning (host side)
# ---------------------------------------------------------------------------


def _float_total_order_host(x: float, wide: bool) -> int:
    """Host replica of ops/sort._float_total_order for ONE finite float —
    used to transform stats min/max into total-order-key bounds."""
    dt = np.float64 if wide else np.float32
    it = np.int64 if wide else np.int32
    v = dt(x)
    if v == 0:
        v = dt(0.0)
    bits = int(np.asarray(v).view(it))
    top = int(np.iinfo(it).min)
    if bits < 0:
        return (~bits) ^ top
    return bits


def _payload_field(i: int, info: KeyInfo, desc: bool, use_stats: bool,
                   use_native: bool,
                   equality_only: bool) -> Optional[FieldSpec]:
    """The payload FieldSpec for one key, or None if unpackable under the
    given (stats, native-lane) policy."""
    t = info.type
    if isinstance(t, T.BooleanType):
        return FieldSpec(i, "bool", 1, desc=desc)
    if isinstance(t, T.VarcharType):
        if info.dict_len is None:
            return None
        if not equality_only and not info.dict_sorted:
            return None  # codes do not order like strings
        n = max(int(info.dict_len), 1)
        return FieldSpec(i, "dict", max((n - 1).bit_length(), 1), desc=desc)
    if isinstance(t, T.DecimalType) and t.is_long:
        return None  # two-lane storage per row: not a scalar key
    dtype = np.dtype(t.storage_dtype)
    if dtype == np.bool_:
        return FieldSpec(i, "bool", 1, desc=desc)
    if dtype.kind == "f":
        if dtype.itemsize == 4:
            return FieldSpec(i, "f32", 32, desc=desc)
        # float64: packable through stats-transformed total-order bounds
        # (NaN maps above the bound and trips the range check), else a
        # native full-width total-order lane
        if use_stats and info.lo is not None and info.hi is not None:
            klo = _float_total_order_host(float(info.lo), True)
            khi = _float_total_order_host(float(info.hi), True)
            if khi >= klo:
                # one slot above khi stays reserved so NaN sorts STRICTLY
                # after every finite value (legacy jnp.argsort parity)
                bits = max((khi - klo + 1).bit_length(), 1)
                if bits <= LANE_BITS:
                    return FieldSpec(
                        i, "frange", bits, lo=klo, hi=khi, desc=desc,
                        checked=not info.exact_bounds,
                    )
        if use_native:
            return FieldSpec(i, "native", 64, desc=desc)
        return None
    if dtype.kind != "i":
        return None
    if dtype.itemsize <= 4:
        return FieldSpec(i, "int", 8 * dtype.itemsize, desc=desc)
    # int64 family (BIGINT, TIMESTAMP, short DECIMAL): exact width by
    # decimal precision when it fits, else CBO/stats bounds, else a
    # native full-width lane
    if isinstance(t, T.DecimalType):
        mag = 10 ** t.precision - 1
        bits = (2 * mag).bit_length()
        if bits <= LANE_BITS:
            return FieldSpec(i, "range", bits, lo=-mag, hi=mag, desc=desc)
    if use_stats and info.lo is not None and info.hi is not None:
        lo, hi = int(info.lo), int(info.hi)
        if hi >= lo:
            bits = max((hi - lo).bit_length(), 1)
            if bits <= LANE_BITS:
                return FieldSpec(i, "range", bits, lo=lo, hi=hi, desc=desc,
                                 checked=not info.exact_bounds)
    if use_native:
        return FieldSpec(i, "native", 64, desc=desc)
    return None


def _fields_for(keys, infos: Sequence[KeyInfo], use_stats: bool,
                use_native: bool,
                equality_only: bool) -> Optional[List[FieldSpec]]:
    fields: List[FieldSpec] = []
    for i, (k, info) in enumerate(zip(keys, infos)):
        desc = not getattr(k, "ascending", True)
        payload = _payload_field(
            i, info, desc, use_stats, use_native, equality_only
        )
        if payload is None:
            return None
        if info.nullable:
            nf = bool(getattr(k, "effective_nulls_first", False))
            fields.append(FieldSpec(i, "null", 1, nulls_first=nf))
        fields.append(payload)
    return fields


def _pack_lanes(fields: List[FieldSpec],
                max_lanes: int) -> Optional[Tuple[Tuple[FieldSpec, ...], ...]]:
    """Greedy split of the field stream across <= max_lanes lanes of
    LANE_BITS each; splitting is only legal BETWEEN fields (lexicographic
    lane order then equals lexicographic field order). A 'native' field
    takes a whole lane and may not lead the stream (the first lane's
    sub-2**62 values carry the dead-row sentinel)."""
    lanes: List[List[FieldSpec]] = []
    cur: List[FieldSpec] = []
    used = 0
    for f in fields:
        if f.kind == "native":
            if cur:
                lanes.append(cur)
                cur, used = [], 0
            elif not lanes:
                return None  # native cannot occupy the first lane
            lanes.append([f])
            continue
        if f.bits > LANE_BITS:
            return None
        if used + f.bits > LANE_BITS:
            lanes.append(cur)
            cur, used = [], 0
        cur.append(f)
        used += f.bits
    if cur:
        lanes.append(cur)
    if not lanes or len(lanes) > max_lanes:
        return None
    return tuple(tuple(l) for l in lanes)


def plan_keypack(
    keys,
    infos: Sequence[KeyInfo],
    equality_only: bool = False,
    allow_hashed: bool = False,
    single_lane: bool = False,
    n_order_keys: int = 0,
    host_sort: Optional[bool] = None,
) -> Optional[KeyPackPlan]:
    """Choose a packing strategy for an ordered key tuple, or None (legacy).

    `keys` are SortKey-likes (ascending / effective_nulls_first read via
    getattr, so plain expressions work for equality-only consumers).
    `n_order_keys` marks the TRAILING keys as window order keys, recorded
    as `order_bits` for partition-boundary extraction (requires the
    single-lane form). `host_sort=None` resolves from the live backend
    (numpy sorts on CPU, device sorts elsewhere)."""
    if not keys or len(keys) != len(infos):
        return None
    if host_sort is None:
        host_sort = _default_host_sort()
    max_lanes = 1 if single_lane else 2
    # evaluate the (stats?, native-lane?) policy grid and keep the best
    # packing: fewest lanes, then no-runtime-check, then no native lane
    best = None
    for use_stats in (False, True):
        for use_native in (False, True):
            fields = _fields_for(
                keys, infos, use_stats, use_native, equality_only
            )
            if fields is None:
                continue
            lanes = _pack_lanes(fields, max_lanes)
            if lanes is None:
                continue
            flat = [f for lane in lanes for f in lane]
            score = (
                len(lanes),
                any(f.checked for f in flat),
                any(f.kind == "native" for f in flat),
            )
            if best is None or score < best[0]:
                best = (score, lanes)
    chosen = None if best is None else best[1]
    if chosen is not None:
        needs_check = any(f.checked for lane in chosen for f in lane)
        order_bits = 0
        if n_order_keys:
            if len(chosen) != 1:
                return None
            first_order = len(keys) - n_order_keys
            order_bits = sum(
                f.bits for f in chosen[0] if f.key_index >= first_order
            )
        return KeyPackPlan(
            strategy="bitpack" if len(chosen) == 1 else "two_lane",
            lanes=chosen,
            needs_check=needs_check,
            order_bits=order_bits,
            host_sort=bool(host_sort),
        )
    if equality_only and allow_hashed:
        # hashed plans keep the device sort: the collision check needs the
        # raw key columns adjacent in sorted order
        return KeyPackPlan(strategy="hashed", lanes=(), needs_check=True)
    return None


# ---------------------------------------------------------------------------
# packing (trace time)
# ---------------------------------------------------------------------------


def _encode_payload(f: FieldSpec, v) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Non-negative int64 rank in [0, 2**f.bits) whose ascending numeric
    order equals the requested key order; plus an optional per-row
    in-range mask ('range'/'frange' with sampled bounds)."""
    from .sort import _float_total_order

    data = v.data
    in_range = None
    if f.kind == "native":
        # a full-width lane of its own: raw int64 order (or the float
        # total-order key), DESC via bitwise NOT (order-reversing and,
        # unlike negation, safe on INT64_MIN)
        if jnp.issubdtype(data.dtype, jnp.floating):
            r = _float_total_order(data)
            if f.desc:
                r = ~r
            return (
                jnp.where(jnp.isnan(data), jnp.int64(_I64_MAX), r),
                None,
            )
        r = data.astype(jnp.int64)
        if f.desc:
            r = ~r
        return r, None
    if f.kind == "bool":
        r = data.astype(jnp.int64)
    elif f.kind == "int":
        lo = int(np.iinfo(np.dtype(data.dtype)).min)
        r = data.astype(jnp.int64) - jnp.int64(lo)
    elif f.kind == "dict":
        r = data.astype(jnp.int64)
    elif f.kind == "f32":
        key = _float_total_order(data)  # int32; NaN already at int32 max
        r = key.astype(jnp.int64) - jnp.int64(np.iinfo(np.int32).min)
    elif f.kind == "frange":
        key = _float_total_order(data)  # int64 total-order key
        if f.checked:
            in_range = (key >= f.lo) & (key <= f.hi)
        r = jnp.clip(key, f.lo, f.hi) - jnp.int64(f.lo)
    else:  # 'range'
        x = data.astype(jnp.int64)
        if f.checked:
            in_range = (x >= f.lo) & (x <= f.hi)
        r = jnp.clip(x, f.lo, f.hi) - jnp.int64(f.lo)
    if f.desc:
        r = jnp.int64((1 << f.bits) - 1) - r
    if f.kind in ("f32", "frange"):
        # jnp.argsort parity (legacy _key_operands): NaNs sort LAST among
        # non-null values in BOTH directions
        r = jnp.where(jnp.isnan(data), jnp.int64((1 << f.bits) - 1), r)
    return r, in_range


def pack_keys(vals, plan: KeyPackPlan, live):
    """Encode evaluated key columns into packed int64 lane(s).

    Returns (lanes, ok): `lanes` is a list of int64 arrays (dead rows =
    INT64_MAX so they sort last in every lane); `ok` is a device bool
    scalar when the plan carries a runtime range check, else None (static
    — no host sync needed)."""
    checks = []
    lanes = []
    for lane in plan.lanes:
        acc = jnp.zeros(live.shape, jnp.int64)
        for f in lane:
            v = vals[f.key_index]
            if f.kind == "null":
                if v.valid is None:
                    bit = jnp.ones(live.shape, jnp.int64) if f.nulls_first \
                        else jnp.zeros(live.shape, jnp.int64)
                else:
                    flag = v.valid if f.nulls_first else ~v.valid
                    bit = flag.astype(jnp.int64)
                acc = (acc << 1) | bit
                continue
            r, in_range = _encode_payload(f, v)
            if v.valid is not None:
                # NULL storage is garbage: canonicalize so equal-null rows
                # pack equal (the null flag field carries the ordering)
                r = jnp.where(v.valid, r, jnp.int64(0))
                if in_range is not None:
                    in_range = in_range | ~v.valid
            if in_range is not None:
                checks.append(jnp.all(in_range | ~live))
            if f.kind == "native":
                acc = r  # whole lane; a 64-bit shift would be undefined
            else:
                acc = (acc << f.bits) | r
        lanes.append(jnp.where(live, acc, _I64_MAX))
    ok = None
    if plan.needs_check:
        ok = jnp.all(jnp.stack(checks)) if checks else jnp.bool_(True)
    return lanes, ok


# ---------------------------------------------------------------------------
# exact-bounds planning helper (benches / tests / adaptive executors)
# ---------------------------------------------------------------------------


def key_info_from_block(block, lo: Optional[int] = None,
                        hi: Optional[int] = None,
                        exact: bool = False) -> KeyInfo:
    d = block.dictionary
    return KeyInfo(
        type=block.type,
        nullable=block.valid is not None,
        dict_len=None if d is None else len(d),
        dict_sorted=getattr(d, "is_sorted", True) if d is not None else True,
        lo=lo,
        hi=hi,
        exact_bounds=exact,
    )


# prestolint: host-function -- setup-time planning with a deliberate
# one-off host sync per key; never reachable from jitted code
def plan_from_page(
    page,
    keys,
    equality_only: bool = False,
    allow_hashed: bool = False,
    single_lane: bool = False,
    n_order_keys: int = 0,
    host_sort: Optional[bool] = None,
) -> Optional[KeyPackPlan]:
    """Plan packing for ColumnRef keys of a MATERIALIZED page, computing
    exact storage min/max on device (one small host sync per 64-bit key;
    setup-time only — benches and tests call this once, the SQL executor
    plans from CBO stats instead)."""
    from ..expr import ir

    infos = []
    for k in keys:
        e = getattr(k, "expr", k)
        if not isinstance(e, ir.ColumnRef) or e.name not in page.names:
            return None
        b = page.block(e.name)
        lo = hi = None
        dtype = np.dtype(b.data.dtype)
        if b.data.ndim == 1 and dtype.kind in "if" and dtype.itemsize == 8:
            n = int(host_read(page.count))
            if n == 0:
                lo, hi = 0, 0
            else:
                data = b.data[:n]
                if b.valid is not None:
                    v = b.valid[:n]
                    if dtype.kind == "f":
                        data = jnp.where(v, data, jnp.nan)
                    else:
                        data = jnp.where(v, data, data[0])
                if dtype.kind == "f":
                    flo = float(host_read(jnp.nanmin(data)))
                    fhi = float(host_read(jnp.nanmax(data)))
                    if np.isfinite(flo) and np.isfinite(fhi):
                        lo, hi = flo, fhi
                else:
                    lo = int(host_read(jnp.min(data)))
                    hi = int(host_read(jnp.max(data)))
        infos.append(key_info_from_block(b, lo=lo, hi=hi, exact=True))
    return plan_keypack(
        keys,
        infos,
        equality_only=equality_only,
        allow_hashed=allow_hashed,
        single_lane=single_lane,
        n_order_keys=n_order_keys,
        host_sort=host_sort,
    )
