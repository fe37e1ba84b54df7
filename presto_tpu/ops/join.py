"""Join kernels.

Re-designed equivalent of the reference's join stack: HashBuilderOperator →
PagesIndex → JoinCompiler-generated PagesHash + PositionLinks, probed by
LookupJoinOperator/JoinProbe (presto-main/.../operator/JoinHash.java:28,
getJoinPosition :82-89; LookupJoinOperator.java).

TPU-first redesign: the "hash table" is the build side *sorted by key hash* —
a layout XLA produces with one optimized sort, instead of pointer-chasing
collision chains. Duplicate build keys are contiguous runs, the analog of
PositionLinks chains:

  build:  sort by (hash, ...), keep permutation; a bucket directory over
          the top hash bits gives each bucket its range of sorted positions
  probe:  lo, hi = directory[bucket], directory[bucket + 1] -> candidates
  1:N expansion: static-capacity output; row r of the output maps back to
  probe row by its rank in the cumulative match counts (cumsum trick), the
  static-shape answer to dynamic join fan-out.

Both the directory and the expansion's slot-to-probe map rank SORTED
queries (an arange) in a sorted array: `sorted_rank` merges the two
through sort instead of searchsorted's log2(n) rounds of gathers.

Hash collisions are resolved by verifying actual key equality after gather.
Composite keys hash-combine then verify each part.

Supported: inner, left (probe-outer), semi, anti — the shapes TPC-H needs.
Right/full outer come with the planner's join-side swap in a later round.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..expr.compiler import evaluate
from ..expr.functions import Val, and_valid
from ..page import Block, Page
from .hashing import (
    argsort_hashes,
    hash_rows,
    hash_rows_values,
    value_hashable,
)


def _want_value_hash(keys) -> bool:
    """Varchar keys whose dictionaries admit the one-time value pass ->
    hash by VALUE so cross-dictionary equi-joins meet (see
    BuildSide.value_hashed)."""
    return any(
        getattr(k, "dict_id", None) is not None for k in keys
    ) and value_hashable(keys)


# numpy scalar (not a device array) so importing this module does no device work
MAX_HASH = np.uint64(0xFFFFFFFFFFFFFFFF)


@dataclasses.dataclass
class BuildSide:
    """Sorted build-side 'lookup source' (reference LookupSourceFactory
    output). All arrays have the build page's capacity."""

    sorted_hash: jnp.ndarray  # uint64, live rows first by hash, dead at end
    order: jnp.ndarray  # permutation: sorted position -> original row
    page: Page  # original build page (payload gathers go through `order`)
    key_vals: Tuple[Val, ...]  # UNsorted key values (original order)
    count: jnp.ndarray  # live build rows
    # O(1) probe directory: sorted positions of bucket b (the top
    # `bucket_bits` of the hash) span [bucket_start[b], bucket_start[b+1])
    bucket_start: Optional[jnp.ndarray] = None  # int32, (2^bits + 1,)
    bucket_bits: int = 0  # static per build shape
    # True when varchar keys were hashed by dictionary VALUE
    # (hash_rows_values): probes MUST hash the same way or equal strings
    # with different codes never meet. Eager and traced builds alike:
    # the two sides of a jitted join are two tables, each with its own
    # dictionary (PR 31: a code-hashed traced join dropped those matches).
    value_hashed: bool = False


def _pick_bucket_bits(capacity: int) -> int:
    """Directory of ~2x build capacity: expected bucket occupancy <= 0.5,
    so the unrolled 4-slot collision scan covers nearly every probe."""
    bits = max(1, int(np.ceil(np.log2(max(capacity, 1) * 2))))
    return min(bits, 22)  # cap the directory at 4M entries


def sorted_rank(a: jnp.ndarray, nq: int, side: str = "left") -> jnp.ndarray:
    """`jnp.searchsorted(a, arange(nq), side)` as int32, exactly, for a
    non-decreasing, non-negative integer `a`, by a merge through sort.

    Each value and each query becomes ONE integer key
    with a tag bit below it (query 2q against element 2a+1 for 'left',
    so an equal element sorts after the query; 2q+1 against 2a for
    'right'); one sort merges them, a cumsum of the element tags counts
    the elements before every query, and a second sort of
    (tag << shift) | count brings the queries' counts to the front in
    query order (equal keys are equal values, so neither sort need be
    stable). Single-operand sorts only: no gather rounds, no scatter."""
    n = a.shape[0]
    # a value at or past nq ranks every query alike: clamped to nq, the
    # keys (<= 2nq + 1) and the counts (<= n) fit int32 below 2^30
    dt, shift = (
        (jnp.int32, 30) if max(n, nq) < 1 << 30 else (jnp.int64, 62)
    )
    v = jnp.minimum(a, nq).astype(dt)
    q = jnp.arange(nq, dtype=dt)
    if side == "left":
        keys = jnp.concatenate([2 * q, 2 * v + 1])
    else:
        keys = jnp.concatenate([2 * q + 1, 2 * v])
    merged = jax.lax.sort(keys)
    elem = merged & 1 if side == "left" else 1 - (merged & 1)
    before = jnp.cumsum(elem, dtype=dt)  # a query's own tag adds nothing
    ranks = jax.lax.sort((elem << shift) | before)[:nq]
    return (ranks & ((1 << shift) - 1)).astype(jnp.int32)


def sorted_probe_layout() -> str:
    """Which probe layout build_sorted produces now: 'directory' unless
    PRESTO_TPU_JOIN_PROBE says otherwise or the join_probe breaker is
    open (exec/breaker.py: a faulting directory build degrades every
    join in the process to 'searchsorted' until the recovery window
    elapses)."""
    if os.environ.get("PRESTO_TPU_JOIN_PROBE", "directory") != "directory":
        return "searchsorted"
    from ..exec.breaker import BREAKERS

    return "directory" if BREAKERS.allow("join_probe") else "searchsorted"


@jax.named_scope("join.build")
def build_sorted(page: Page, key_exprs) -> BuildSide:
    """Sort the build side by key hash (HashBuilderOperator.finish analog).
    Empty key_exprs = all rows in one bucket (cross join support).

    TPU-first probe layout: alongside the sorted hashes we histogram the
    top `bucket_bits` hash bits into a bucket-start directory. Probing is
    then TWO gathers (bucket_start[b], bucket_start[b+1]) instead of
    jnp.searchsorted's ~log2(n) serial gather rounds — binary search is
    the worst memory-access shape for the TPU; a directory lookup is a
    plain vectorized gather. The directory itself is the rank of every
    bucket id in the sorted bucket ids, built by a merge through sort
    (`sorted_rank`), not by a search. Candidates inside a bucket that
    carry a different hash are rejected by the existing true-key-equality
    check."""
    with jax.named_scope("hash"):
        keys = [evaluate(e, page) for e in key_exprs]
        live = page.live_mask()
        value_hashed = _want_value_hash(keys)
        if not keys:
            h = jnp.zeros(page.capacity, jnp.uint64)
        elif value_hashed:
            h = hash_rows_values(keys)
        else:
            h = hash_rows(keys)
        h = jnp.where(live, h, MAX_HASH)  # dead rows cluster at the end
    with jax.named_scope("sort"):
        order = argsort_hashes(h)
        sh = h[order]
    if sorted_probe_layout() != "directory":
        # chip-diagnosis escape hatch / open breaker: searchsorted probe
        return BuildSide(
            sh, order, page, tuple(keys), page.count,
            value_hashed=value_hashed,
        )
    bits = _pick_bucket_bits(page.capacity)
    nb = 1 << bits
    bucket = (sh >> np.uint64(64 - bits)).astype(jnp.int32)
    # directory from the SORTED bucket ids by a merge with the sorted
    # bucket range. (A bincount/scatter-add builds the same counts but
    # XLA:TPU lowers large scatters to a serial loop; a binary search
    # is ~log2(n) gather rounds over 2^bits + 1 queries.) Dead rows
    # (MAX_HASH) land in bucket nb - 1; _probe_ranges clamps them off.
    with jax.named_scope("directory"):
        starts = sorted_rank(bucket, nb + 1, "left")
    return BuildSide(
        sh, order, page, tuple(keys), page.count, starts, bits,
        value_hashed=value_hashed,
    )


@jax.named_scope("join.probe_ranges")
def _probe_ranges(bs: BuildSide, probe_keys: Sequence[Val], capacity: int):
    """For each probe row: [lo, hi) candidate range in the sorted build.

    Via the bucket directory when present (O(1), two gathers); candidate
    ranges then cover the whole hash-prefix bucket — a superset of the
    exact hash run — which downstream consumers must treat as CANDIDATES
    (true key equality + liveness decide membership)."""
    if not probe_keys:  # cross join: every live build row is a candidate
        lo = jnp.zeros(capacity, jnp.int32)
        hi = jnp.broadcast_to(bs.count.astype(jnp.int32), (capacity,))
        return None, lo, hi
    h = (
        hash_rows_values(probe_keys)
        if bs.value_hashed
        else hash_rows(probe_keys)
    )
    if bs.bucket_start is not None:
        b = (h >> np.uint64(64 - bs.bucket_bits)).astype(jnp.int32)
        cnt = bs.count.astype(jnp.int32)
        # live rows occupy sorted positions [0, count): clamping excludes
        # the dead-padding tail from the last bucket (dead rows sort to
        # MAX_HASH), keeping candidates live and the tail bucket short
        lo = jnp.minimum(bs.bucket_start[b], cnt)
        hi = jnp.minimum(bs.bucket_start[b + 1], cnt)
        return h, lo, hi
    lo = jnp.searchsorted(bs.sorted_hash, h, side="left")
    hi = jnp.searchsorted(bs.sorted_hash, h, side="right")
    return h, lo.astype(jnp.int32), hi.astype(jnp.int32)


def _keys_equal(bs: BuildSide, probe_keys: Sequence[Val], build_rows):
    """Verify actual key equality probe[i] == build[build_rows[i]].
    SQL join semantics: NULL keys never match."""
    if not probe_keys:
        return jnp.ones(build_rows.shape, jnp.bool_)
    eq = None
    for pv, bv in zip(probe_keys, bs.key_vals):
        bd = bv.data[build_rows]
        if isinstance(pv.type, T.VarcharType) and pv.dict_id != bv.dict_id:
            from ..expr.functions import unify_dictionaries

            pd_, bd2, _ = unify_dictionaries(
                pv, Val(bd, None, bv.type, bv.dict_id)
            )
            part = pd_ == bd2
        else:
            part = pv.data == bd
            if part.ndim == 2:  # long-decimal lanes: all lanes must match
                part = part.all(axis=-1)
        if pv.valid is not None:
            part = part & pv.valid
        if bv.valid is not None:
            part = part & bv.valid[build_rows]
        eq = part if eq is None else (eq & part)
    return eq


@jax.named_scope("join.probe_loop")
def _collision_scan(bs: BuildSide, probe_keys, lo, hi, max_scan: int = 4):
    """Resolve hash collisions: the first max_scan candidate slots are
    UNROLLED (64-bit hashes make >1 essentially impossible, so this is
    the entire cost in practice), then a lax.while_loop keeps scanning
    for pathological longer runs — a >max_scan-deep run of colliding,
    key-unequal candidates can no longer silently drop matches (round-4
    verdict weak#8). Returns (matched, build_row)."""
    matched = jnp.zeros(lo.shape, jnp.bool_)
    build_row = jnp.zeros(lo.shape, jnp.int32)
    limit = bs.sorted_hash.shape[0] - 1

    def probe_slot(k, matched, build_row):
        cand = lo + k
        in_range = cand < hi
        rows = bs.order[jnp.minimum(cand, limit)].astype(jnp.int32)
        ok = in_range & _keys_equal(bs, probe_keys, rows) & ~matched
        return matched | ok, jnp.where(ok, rows, build_row)

    for k in range(max_scan):
        matched, build_row = probe_slot(k, matched, build_row)

    def cond(state):
        k, m, _ = state
        return jnp.any(~m & (lo + k < hi))

    def body(state):
        k, m, br = state
        m, br = probe_slot(k, m, br)
        return k + 1, m, br

    _, matched, build_row = jax.lax.while_loop(
        cond, body, (jnp.int32(max_scan), matched, build_row)
    )
    return matched, build_row


def join_n1(
    probe: Page,
    bs: BuildSide,
    probe_key_exprs,
    build_names: Sequence[str],
    out_build_names: Sequence[str],
    kind: str = "inner",
) -> Page:
    """Join where each probe row matches at most ONE build row (FK->PK joins;
    also semi/anti). kind: inner | left | semi | anti.

    Output capacity == probe capacity; probe columns pass through, build
    payload columns are gathered (null where unmatched, for `left`)."""
    probe_keys = [evaluate(e, probe) for e in probe_key_exprs]
    live = probe.live_mask()
    _, lo, hi = _probe_ranges(bs, probe_keys, probe.capacity)
    matched, build_row = _collision_scan(bs, probe_keys, lo, hi)
    matched = matched & live

    from .filter import compact

    if kind == "semi":
        return compact(probe, matched)
    if kind == "anti":
        return compact(probe, ~matched & live)

    blocks = list(probe.blocks)
    names = list(probe.names)
    with jax.named_scope("join.gather"):
        for bname, oname in zip(build_names, out_build_names):
            b = bs.page.block(bname)
            data = b.data[build_row]
            valid = (
                matched if b.valid is None else (matched & b.valid[build_row])
            )
            blocks.append(Block(data, b.type, valid, b.dict_id))
            names.append(oname)
    out = Page(tuple(blocks), tuple(names), probe.count)
    if kind == "inner":
        return compact(out, matched)
    if kind == "left":
        return out  # unmatched rows keep probe columns, build columns NULL
    raise ValueError(f"unknown join kind {kind!r}")


def semi_match_mask(
    probe: Page, bs: BuildSide, probe_key_exprs
) -> jnp.ndarray:
    """Boolean per-probe-row match membership (the mark-join kernel:
    reference HashSemiJoinOperator's semiJoinOutput channel)."""
    probe_keys = [evaluate(e, probe) for e in probe_key_exprs]
    live = probe.live_mask()
    _, lo, hi = _probe_ranges(bs, probe_keys, probe.capacity)
    matched, _ = _collision_scan(bs, probe_keys, lo, hi)
    return matched & live


def join_expand(
    probe: Page,
    bs: BuildSide,
    probe_key_exprs,
    probe_out: Sequence[str],
    build_out: Sequence[Tuple[str, str]],  # (build col, output name)
    out_capacity: int,
    kind: str = "inner",
) -> Tuple[Page, jnp.ndarray]:
    """General 1:N inner/left join with static output capacity.

    out_capacity bounds total hash-range *candidates* (planner-estimated, like
    the reference sizes lookup join output pages). Returns (page, overflow):
    overflow is the number of candidate rows beyond out_capacity — the host
    must check it is 0 and retry with a larger capacity otherwise (candidates
    that merely fail true key equality are dropped exactly, not counted)."""
    probe_keys = [evaluate(e, probe) for e in probe_key_exprs]
    live = probe.live_mask()
    _, lo, hi = _probe_ranges(bs, probe_keys, probe.capacity)

    # counts per probe row: number of hash-range candidates. Candidates that
    # fail true key equality are dropped at emission (conservative capacity,
    # exact rows). For LEFT joins a probe row with candidates but no TRUE
    # match (NULL keys, hash collisions) must still emit one null row, so
    # we detect real matches with the n1 scan first.
    counts = jnp.where(live, hi - lo, 0)
    if kind == "left":
        has_match, _ = _collision_scan(bs, probe_keys, lo, hi)
        no_match = live & ~has_match
        counts = jnp.where(no_match, 1, counts)  # emit exactly one null row
    with jax.named_scope("join.expand"):
        offsets = jnp.cumsum(counts)
        total = offsets[-1] if probe.capacity else jnp.asarray(0, jnp.int32)
        starts = offsets - counts

        out_i = jnp.arange(out_capacity, dtype=jnp.int32)
        # output slot -> probe row: the slot's rank in the offsets
        src = sorted_rank(offsets, out_capacity, "right")
        src = jnp.minimum(src, probe.capacity - 1)
        within = out_i - starts[src]
        in_bounds = out_i < total

        sorted_pos = lo[src] + within
        sorted_pos = jnp.minimum(sorted_pos, bs.sorted_hash.shape[0] - 1)
        build_row = bs.order[sorted_pos].astype(jnp.int32)

    # verify true key equality for emitted pairs
    probe_keys_g = [
        Val(
            v.data[src],
            None if v.valid is None else v.valid[src],
            v.type,
            v.dict_id,
        )
        for v in probe_keys
    ]
    eq = _keys_equal(bs, probe_keys_g, build_row)
    if kind == "left":
        synthetic = no_match[src]  # left-outer null row for match-less probes
        keep = in_bounds & (eq | synthetic)
        build_valid_base = ~synthetic
    else:
        keep = in_bounds & eq
        build_valid_base = jnp.ones(out_capacity, jnp.bool_)

    blocks, names = [], []
    with jax.named_scope("join.gather"):
        for name in probe_out:
            b = probe.block(name)
            data = b.data[src]
            valid = None if b.valid is None else b.valid[src]
            blocks.append(Block(data, b.type, valid, b.dict_id))
            names.append(name)
        for bname, oname in build_out:
            b = bs.page.block(bname)
            data = b.data[build_row]
            valid = build_valid_base if b.valid is None else (
                build_valid_base & b.valid[build_row]
            )
            blocks.append(Block(data, b.type, valid, b.dict_id))
            names.append(oname)

    out = Page.from_blocks(blocks, names, count=out_capacity)
    from .filter import compact

    overflow = jnp.maximum(total.astype(jnp.int64) - out_capacity, 0)
    return compact(out, keep), overflow
