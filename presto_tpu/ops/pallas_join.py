"""Pallas-native hash join kernels on a linear-probe slot table.

The reference engine closes the hash-relational perf gap with runtime
bytecode generation (presto-main sql/gen: JoinCompiler emits a
PagesHash + PositionLinks per key signature). The TPU-native equivalent
is a custom kernel family over an explicit HASH TABLE layout
(arXiv:1905.13376's build/probe/multiway design), replacing the
sorted-hash + bucket-directory BuildSide of ops/join.py on backends
where it wins:

* BUILD — bulk parallel linear-probing insert into a power-of-two slot
  array: every pending row scatters its id at (desired slot + round k),
  a gather confirms the winner (the CAS-free formulation of the paper's
  atomic insert; any race winner yields the same probe results), losers
  advance to round k+1. Rows still unplaced after R_MAX rounds (heavy
  single-key skew: duplicates place one per round) move to a dense
  tag-sorted OVERFLOW region probed by binary search — the table never
  degrades quadratically and never wraps (a guaranteed-empty sentinel
  slot terminates every scan).
* PROBE — per probe row: scan slots from the key's desired slot until
  the first EMPTY slot, collecting 32-bit tag matches; true key
  equality (dictionary-unified for varchar) decides membership, so tag
  collisions only cost a re-check. First-match (n1 / semi / anti mark)
  and count-then-emit (1:N expand, statically sized output) variants.
* MULTIWAY — one pass over the probe batch chains two or more build
  tables (star-shaped joins): each fact batch resolves every dimension
  before any intermediate page is materialized or compacted.

Backend dispatch (all behind the pallas_join_build / pallas_join_probe
circuit breakers in exec/breaker.py, with ops/join.py's sorted-hash
composition as the fallback):

* cpu  — the numpy host path below IS the engine default: scans are
  cache-resident C loops and beat both XLA's comparison sort (build)
  and its gather cascades (probe) by 3-10x. Callers route these joins
  AROUND jit (the ops/sort.py host-sort idiom); everything here
  requires concrete operands.
* tpu  — OFF: joins take ops/join.py's device-resident sorted-hash
  composition directly. The same scan is written below as Pallas kernels
  (slot arrays resident in VMEM, probe rows blocked over a grid), but
  the Mosaic compiler of the installed JAX refuses both (PR 22, compiled
  for a described v5e: the probe's `jnp.take` on a 1-D VMEM array gives
  "Only 2D gather is supported", the insert's vector scatter does not
  get through lowering), so they are not a TPU default.
  PRESTO_TPU_PALLAS_JOIN=interp runs the kernels in interpret mode on
  any backend, which is how CI covers them.

Partition-bounded inputs: exec/stream.py's hybrid join hands partitions
through the ragged paged layout (ops/ragged.py), which bounds every
build side a kernel sees — that is what keeps slot arrays VMEM-sized on
TPU and keeps R_MAX displacement bounds honest under skew.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..expr.compiler import evaluate
from ..expr.functions import Val
from ..page import Block, Page
from .hashing import np_hash_rows_values, value_hashable

EMPTY_TAG = np.uint32(0xFFFFFFFF)  # slot sentinel; real tags clamp below it
R_MAX = 64  # bounded insert rounds; leftovers go to the overflow region
TABLE_MAX_BUILD = 1 << 22  # larger builds keep the sorted-hash layout
_MAX_BITS = 23


def _concrete(*arrays) -> bool:
    """True when every operand is a real array (not a jit/vmap tracer) —
    the table path runs eagerly by design (host numpy on cpu, eager
    pallas on tpu); under a trace callers use the sorted-hash path."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def pallas_join_mode() -> str:
    """'host' (numpy, the cpu default), 'interp' (pallas interpret mode
    — CI validation of the kernels on any backend), or 'off' (the
    sorted-hash composition of ops/join.py; the default on every other
    backend, see the module header). Resolved per call so tests can flip
    the env."""
    forced = os.environ.get("PRESTO_TPU_PALLAS_JOIN", "")
    if forced in ("0", "off"):
        return "off"
    if forced == "interp":
        return "interp"
    return "host" if jax.default_backend() == "cpu" else "off"


@dataclasses.dataclass
class JoinTable:
    """Linear-probe hash table over one build page (the JoinCompiler
    PagesHash analog). slot arrays have nslots + R_MAX + 1 entries; the
    final entry is permanently EMPTY so scans terminate without wrap.
    Overflow rows (unplaced after R_MAX rounds) sit tag-sorted in
    of_tag/of_row."""

    slot_tag: np.ndarray  # uint32; EMPTY_TAG = vacant
    slot_row: np.ndarray  # int32 build row id; -1 = vacant
    bits: int  # desired slot = tag >> (32 - bits)
    of_tag: np.ndarray  # uint32, sorted ascending (may be empty)
    of_row: np.ndarray  # int32
    page: Page  # build page (payload gathers)
    key_vals: Tuple[Val, ...]  # evaluated build keys (original order)
    key_exprs: tuple  # for the sorted-path rebuild on kernel fault
    count: int  # live build rows
    inserted: int  # rows in the slot array (count - null-key - overflow)

    def occupancy(self) -> float:
        """Live fraction of the power-of-two slot array — the EXPLAIN
        ANALYZE page-table/occupancy metric for this build."""
        return self.inserted / max(1 << self.bits, 1)


def _tag_desired(h: np.ndarray, bits: int):
    """(uint32 tag, int64 desired slot) from 64-bit row hashes. The tag
    keeps the TOP hash bits (desired is derived from the tag alone, so
    kernels carry one array), clamped below the EMPTY sentinel."""
    t = (np.asarray(h) >> np.uint64(32)).astype(np.uint32)
    t = np.minimum(t, np.uint32(0xFFFFFFFE))
    d = (t >> np.uint32(32 - bits)).astype(np.int64)
    return t, d


def _np_live(page: Page) -> np.ndarray:
    """Concrete live mask without an eager device op."""
    return np.arange(page.capacity) < int(page.count)


def _pick_bits(n: int) -> int:
    bits = max(4, int(np.ceil(np.log2(max(n, 1) * 2))))
    return min(bits, _MAX_BITS)


# -- build -------------------------------------------------------------------


def _host_insert(tag: np.ndarray, rows: np.ndarray, bits: int):
    """Parallel linear-probing insert (host twin of the Pallas kernel):
    round k scatters pending rows at desired+k (last writer wins the
    slot), a gather confirms placement, losers continue. Returns the
    slot arrays plus the row ids that overflowed R_MAX rounds."""
    nslots = 1 << bits
    size = nslots + R_MAX + 1
    slot_tag = np.full(size, EMPTY_TAG, np.uint32)
    slot_row = np.full(size, -1, np.int32)
    desired = (tag >> np.uint32(32 - bits)).astype(np.int64)
    limit = size - 2  # last slot stays EMPTY forever
    # round 0 on FULL vectors (every live row is pending; the index
    # indirection below only pays once the pending set has shrunk)
    live = rows >= 0
    cand0 = np.minimum(desired, limit)
    slot_row[np.where(live, cand0, size - 1)] = np.where(live, rows, -1)
    slot_row[size - 1] = -1
    won0 = live & (slot_row[cand0] == rows)
    slot_tag[cand0[won0]] = tag[won0]
    pend = np.flatnonzero(live & ~won0)
    for k in range(1, R_MAX):
        if not len(pend):
            break
        cand = np.minimum(desired[pend] + k, limit)
        vacant = slot_row[cand] == -1
        trial = pend[vacant]
        if len(trial):
            tc = cand[vacant]
            slot_row[tc] = rows[trial]  # races: last writer wins
            won = slot_row[tc] == rows[trial]
            tw = tc[won]
            slot_tag[tw] = tag[trial[won]]
            placed = np.zeros(len(pend), bool)
            placed[np.flatnonzero(vacant)[won]] = True
            pend = pend[~placed]
        # occupied slots (incl. freshly won) simply advance to k+1
    return slot_tag, slot_row, pend


def _host_build(
    tag: np.ndarray, live_rows: np.ndarray, bits: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    slot_tag, slot_row, left = _host_insert(tag, live_rows, bits)
    if len(left):
        of_order = np.argsort(tag[left], kind="stable")
        of_tag = tag[left][of_order]
        of_row = live_rows[left][of_order].astype(np.int32)
    else:
        of_tag = np.empty(0, np.uint32)
        of_row = np.empty(0, np.int32)
    inserted = int((slot_row >= 0).sum())
    return slot_tag, slot_row, of_tag, of_row, inserted


def _pallas_insert_kernel(nrows: int, size: int, rounds: int):
    """Pallas build kernel: the same scatter/confirm rounds with the slot
    arrays resident in VMEM (one grid step — partition-bounded builds).
    Races between lanes scattering into one slot resolve to SOME lane
    (matching the host path's last-writer semantics); the confirming
    gather makes every resolution yield identical join results."""
    from jax.experimental import pallas as pl  # noqa: F401 (kernel ctx)

    def kernel(tag_ref, row_ref, desired_ref, st_ref, sr_ref, pend_ref):
        st_ref[:] = jnp.full((size,), EMPTY_TAG, jnp.uint32)
        sr_ref[:] = jnp.full((size,), -1, jnp.int32)
        limit = size - 2
        tag = tag_ref[:]
        row = row_ref[:]
        desired = desired_ref[:]
        pending = row >= 0

        def one_round(k, state):
            st, sr, pending = state
            cand = jnp.minimum(desired + k, limit)
            vacant = pending & (sr[cand] == -1)
            tc = jnp.where(vacant, cand, size - 1)
            sr = sr.at[tc].set(jnp.where(vacant, row, -1))
            sr = sr.at[size - 1].set(-1)
            won = vacant & (sr[tc] == row)
            st = st.at[jnp.where(won, tc, size - 1)].set(
                jnp.where(won, tag, EMPTY_TAG)
            )
            st = st.at[size - 1].set(EMPTY_TAG)
            return st, sr, pending & ~won

        st, sr, pending = jax.lax.fori_loop(
            0, rounds, one_round,
            (st_ref[:], sr_ref[:], pending),
        )
        st_ref[:] = st
        sr_ref[:] = sr
        pend_ref[:] = pending.astype(jnp.int32)

    return kernel


# prestolint: host-function -- eager host orchestration around the
# insert kernel (concrete arrays in, overflow sort on the host)
def _pallas_build(tag, live_rows, bits: int, interpret: bool):
    """Run the insert kernel; overflow handling (tag sort of the rare
    leftovers) stays outside the kernel — sorting has no Mosaic lowering
    (ops/pallas_groupby.py has the same split)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nslots = 1 << bits
    size = nslots + R_MAX + 1
    n = len(live_rows)
    tag = jnp.asarray(tag)
    rowsj = jnp.asarray(live_rows, dtype=jnp.int32)
    desired = (tag >> jnp.uint32(32 - bits)).astype(jnp.int32)
    kernel = _pallas_insert_kernel(n, size, R_MAX)
    fn = _cached_pallas(
        ("pallas_join_build", n, size, R_MAX, interpret),
        lambda: pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
            out_specs=(
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((size,), jnp.uint32),
                jax.ShapeDtypeStruct((size,), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32),
            ),
            interpret=interpret,
        ),
    )
    st, sr, pend = fn(tag, rowsj, desired)
    st, sr = np.asarray(st), np.asarray(sr)
    left = np.flatnonzero(np.asarray(pend))
    tag_np = np.asarray(tag)
    if len(left):
        rows_np = np.asarray(live_rows)
        of_order = np.argsort(tag_np[left], kind="stable")
        of_tag = tag_np[left][of_order]
        of_row = rows_np[left][of_order].astype(np.int32)
    else:
        of_tag = np.empty(0, np.uint32)
        of_row = np.empty(0, np.int32)
    return st, sr, of_tag, of_row, int((sr >= 0).sum())


def _cached_pallas(key, make_fn):
    """Compiled pallas_call reuse through the process-wide kernel cache
    (exec/qcache.KERNEL_CACHE) — cross-query compile amortization, same
    contract as Executor._kernel."""
    from ..exec.qcache import KERNEL_CACHE

    gkey = (jax.default_backend(), "pallas_join", key)
    fn = KERNEL_CACHE.get(gkey)
    if fn is None:
        fn = make_fn()
        KERNEL_CACHE.put(gkey, fn)
    return fn


# prestolint: host-function -- eager host orchestration: evaluates keys on
# device, then builds the host-resident slot arrays
def build_table(page: Page, key_exprs) -> Optional[JoinTable]:
    """Build the linear-probe JoinTable for a build page, or None when
    the shape is ineligible (caller falls back to the sorted-hash
    BuildSide): traced operands, empty key list (cross join), oversized
    build, huge-dictionary varchar keys, or a disabled mode."""
    mode = pallas_join_mode()
    if mode == "off" or not key_exprs:
        return None
    if page.capacity > TABLE_MAX_BUILD:
        return None
    keys = [evaluate(e, page) for e in key_exprs]
    datas = [k.data for k in keys] + [
        k.valid for k in keys if k.valid is not None
    ]
    if not _concrete(page.count, *datas):
        return None
    if not value_hashable(keys):
        return None
    h = np_hash_rows_values(keys)
    n = page.capacity
    cnt = int(page.count)
    bits = _pick_bits(cnt)
    tag_np, _ = _tag_desired(h, bits)
    # insert only live rows with fully NON-NULL keys: SQL equi-joins
    # never match NULL, and skew-heavy NULL columns would otherwise
    # pile into one chain
    live = _np_live(page)
    for k in keys:
        if k.valid is not None:
            live = live & np.asarray(k.valid)
    rows = np.where(live, np.arange(n, dtype=np.int32), -1).astype(np.int32)
    if mode == "interp":
        st, sr, of_tag, of_row, inserted = _pallas_build(
            tag_np, rows, bits, interpret=True
        )
    else:
        st, sr, of_tag, of_row, inserted = _host_build(tag_np, rows, bits)
    return JoinTable(
        st, sr, bits, of_tag, of_row, page, tuple(keys),
        tuple(key_exprs), cnt, inserted,
    )


# -- key verification --------------------------------------------------------


def _comparable_pair(pv: Val, bv: Val):
    """(probe array, build array) made directly comparable: varchar
    columns with differing dictionaries translate through one unified
    dictionary (ops/join._keys_equal does the same per-gather; here it
    happens ONCE per batch so the scan loop compares plain ints)."""
    if (
        isinstance(pv.type, T.VarcharType)
        and pv.dict_id is not None
        and bv.dict_id is not None
        and pv.dict_id != bv.dict_id
    ):
        from ..expr.functions import unify_dictionaries

        pd_, bd_, _ = unify_dictionaries(pv, bv)
        return np.asarray(pd_), np.asarray(bd_)
    return np.asarray(pv.data), np.asarray(bv.data)


def _host_prepare_keys(jt: JoinTable, probe_keys: Sequence[Val]):
    """Per-key comparable numpy arrays + validity, prepared once per
    probe batch for the in-scan verifier."""
    prep = []
    for pv, bv in zip(probe_keys, jt.key_vals):
        pd_, bd_ = _comparable_pair(pv, bv)
        if jnp.issubdtype(jnp.asarray(pd_).dtype, jnp.floating):
            # canonicalize NaN payloads like ops/hashing: all NaN compare
            # unequal anyway (SQL equi-join), -0.0 == 0.0 holds in numpy
            pass
        prep.append(
            (
                pd_,
                bd_,
                None if pv.valid is None else np.asarray(pv.valid),
                None if bv.valid is None else np.asarray(bv.valid),
            )
        )
    return prep


def _host_verify(prep, probe_idx: np.ndarray, build_rows: np.ndarray):
    """True key equality probe[i] == build[row]; NULL never matches."""
    ok = np.ones(len(probe_idx), bool)
    for pd_, bd_, pvld, bvld in prep:
        a = pd_[probe_idx]
        b = bd_[build_rows]
        part = a == b
        if part.ndim == 2:  # long-decimal lanes
            part = part.all(axis=-1)
        if pvld is not None:
            part = part & pvld[probe_idx]
        if bvld is not None:
            part = part & bvld[build_rows]
        ok &= part
    return ok


# -- probe: first verified match (n1 / semi / anti / mark) -------------------


def _host_probe_n1(jt: JoinTable, ptag, pdesired, live, prep):
    """First VERIFIED match per probe row: scan from the desired slot
    until the first EMPTY slot; tag matches verify true key equality
    in-scan (collisions continue scanning). Returns (matched, build_row)."""
    m = len(ptag)
    matched = np.zeros(m, bool)
    brow = np.zeros(m, np.int32)
    limit = len(jt.slot_tag) - 1
    # step 0 on FULL vectors: at load <= 1/2 nearly every probe resolves
    # at its desired slot, so the first step skips the active-index
    # indirection entirely (measured ~30% of host probe wall)
    cand = np.minimum(pdesired, limit)
    t = jt.slot_tag[cand]
    hit = (t == ptag) & live
    if hit.any():
        hidx = np.flatnonzero(hit)
        rows_c = jt.slot_row[cand[hidx]]
        ok = _host_verify(prep, hidx, rows_c)
        matched[hidx[ok]] = True
        brow[hidx[ok]] = rows_c[ok]
    active = np.flatnonzero(live & (t != EMPTY_TAG) & ~matched)
    k = 1
    while len(active) and k <= limit:
        cand = np.minimum(pdesired[active] + k, limit)
        t = jt.slot_tag[cand]
        hit = t == ptag[active]
        if hit.any():
            hidx = active[hit]
            rows_c = jt.slot_row[cand[hit]]
            ok = _host_verify(prep, hidx, rows_c)
            matched[hidx[ok]] = True
            brow[hidx[ok]] = rows_c[ok]
            cont = t != EMPTY_TAG
            cont[hit] &= ~ok
        else:
            cont = t != EMPTY_TAG
        active = active[cont]
        k += 1
    if len(jt.of_tag):
        pend = np.flatnonzero(live & ~matched)
        if len(pend):
            m2, b2 = _host_probe_overflow(jt, ptag, prep, pend)
            matched[m2] = True
            brow[m2] = b2
    return matched, brow


def _pallas_probe_kernel(size: int, blk: int, max_scan: int):
    """Pallas probe kernel: table arrays whole in VMEM, probe rows
    blocked (blk x 128) over the grid. Emits the first TAG-match
    position per row plus a needs-more flag for rows whose scan ran past
    max_scan without hitting EMPTY — the eager caller resolves those
    (and any tag match that fails true key equality) with the bounded
    continuation scan, so max_scan caps VMEM work, not correctness."""

    def kernel(st_ref, sr_ref, tag_ref, des_ref, start_ref, out_pos,
               out_row, out_more):
        st = st_ref[:]
        sr = sr_ref[:]
        ptag = tag_ref[:]
        des = des_ref[:]
        start = start_ref[:]
        limit = size - 1
        found = jnp.zeros(ptag.shape, jnp.bool_)
        pos = jnp.full(ptag.shape, -1, jnp.int32)
        row = jnp.full(ptag.shape, -1, jnp.int32)
        ended = jnp.zeros(ptag.shape, jnp.bool_)
        for k in range(max_scan):
            cand = jnp.minimum(des + start + k, limit)
            t = jnp.take(st, cand)
            hit = (~found) & (~ended) & (t == ptag)
            pos = jnp.where(hit, cand, pos)
            row = jnp.where(hit, jnp.take(sr, cand), row)
            found = found | hit
            ended = ended | (t == EMPTY_TAG)
        out_pos[:] = pos
        out_row[:] = row
        out_more[:] = ((~found) & (~ended)).astype(jnp.int32)

    return kernel


# prestolint: host-function -- eager host orchestration around the
# probe kernel (pads/blocks concrete probe arrays for the grid)
def _pallas_probe_first(jt: JoinTable, ptag, pdesired, start, interpret,
                        max_scan: int = 16):
    """One kernel launch: first tag-match pos/row per probe row from
    scan offset `start`, plus the needs-deeper-scan flag."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m = len(ptag)
    pad = -m % 128
    size = len(jt.slot_tag)

    def pad1(x, fill):
        x = jnp.asarray(x)
        return jnp.pad(x, (0, pad), constant_values=fill) if pad else x

    view = lambda x: x.reshape(-1, 128)
    rows2 = (m + pad) // 128
    fn = _cached_pallas(
        ("pallas_join_probe", size, rows2, max_scan, interpret),
        lambda: pl.pallas_call(
            _pallas_probe_kernel(size, rows2, max_scan),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
            out_specs=(
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((rows2, 128), jnp.int32),
                jax.ShapeDtypeStruct((rows2, 128), jnp.int32),
                jax.ShapeDtypeStruct((rows2, 128), jnp.int32),
            ),
            interpret=interpret,
        ),
    )
    pos, row, more = fn(
        jnp.asarray(jt.slot_tag),
        jnp.asarray(jt.slot_row),
        view(pad1(ptag, 0)),
        view(pad1(pdesired.astype(np.int32), 0)),
        view(pad1(start.astype(np.int32), 0)),
    )
    flat = lambda x: np.asarray(x).reshape(-1)[:m]
    return flat(pos), flat(row), flat(more).astype(bool)


def _probe_n1(jt: JoinTable, ptag, pdesired, live, prep, mode: str):
    """Backend-dispatched first-verified-match probe."""
    if mode != "interp":
        return _host_probe_n1(jt, ptag, pdesired, live, prep)
    m = len(ptag)
    matched = np.zeros(m, bool)
    brow = np.zeros(m, np.int32)
    start = np.zeros(m, np.int32)
    pend = np.flatnonzero(live)
    rounds = 0
    limit = len(jt.slot_tag) - 1
    while len(pend) and rounds <= limit:
        pos, row, more = _pallas_probe_first(
            jt, ptag[pend], pdesired[pend], start[pend],
            interpret=True,
        )
        got = pos >= 0
        ok = np.zeros(len(pend), bool)
        if got.any():
            ok[got] = _host_verify(prep, pend[got], row[got])
            matched[pend[ok]] = True
            brow[pend[ok]] = row[ok]
        # continue: verified-failed tag matches scan past their match
        # position; truncated scans (more) resume where the kernel left
        cont = (got & ~ok) | more
        start[pend[got & ~ok]] = (
            pos[got & ~ok] - pdesired[pend[got & ~ok]] + 1
        )
        start[pend[more & ~got]] += 16
        pend = pend[cont]
        rounds += 1
    if len(jt.of_tag):
        rest = np.flatnonzero(live & ~matched)
        if len(rest):
            m2, b2 = _host_probe_overflow(jt, ptag, prep, rest)
            matched[m2] = True
            brow[m2] = b2
    return matched, brow


def _host_probe_overflow(jt: JoinTable, ptag, prep, pend):
    """First verified match within the tag-sorted overflow region."""
    lo = np.searchsorted(jt.of_tag, ptag[pend], side="left")
    hi = np.searchsorted(jt.of_tag, ptag[pend], side="right")
    sel = lo < hi
    act, lo, hi = pend[sel], lo[sel], hi[sel]
    out_idx: List[np.ndarray] = []
    out_row: List[np.ndarray] = []
    while len(act):
        rows_c = jt.of_row[lo]
        ok = _host_verify(prep, act, rows_c)
        out_idx.append(act[ok])
        out_row.append(rows_c[ok])
        lo = lo + 1
        keep = (~ok) & (lo < hi)
        act, lo, hi = act[keep], lo[keep], hi[keep]
    if out_idx:
        return np.concatenate(out_idx), np.concatenate(out_row)
    return np.empty(0, np.int64), np.empty(0, np.int32)


# -- probe: all matches (1:N expand, count-then-emit) ------------------------


def _host_probe_all(jt: JoinTable, ptag, pdesired, live, prep):
    """EVERY verified match as (probe row, build row) pair arrays —
    the count-then-emit shape: callers size output from len(pairs)."""
    limit = len(jt.slot_tag) - 1
    pi: List[np.ndarray] = []
    bi: List[np.ndarray] = []
    # step 0 on full vectors (see _host_probe_n1)
    cand = np.minimum(pdesired, limit)
    t = jt.slot_tag[cand]
    hit = (t == ptag) & live
    if hit.any():
        hidx = np.flatnonzero(hit)
        rows_c = jt.slot_row[cand[hidx]]
        ok = _host_verify(prep, hidx, rows_c)
        pi.append(hidx[ok])
        bi.append(rows_c[ok])
    active = np.flatnonzero(live & (t != EMPTY_TAG))
    k = 1
    while len(active) and k <= limit:
        cand = np.minimum(pdesired[active] + k, limit)
        t = jt.slot_tag[cand]
        hit = t == ptag[active]
        if hit.any():
            hidx = active[hit]
            rows_c = jt.slot_row[cand[hit]]
            ok = _host_verify(prep, hidx, rows_c)
            pi.append(hidx[ok])
            bi.append(rows_c[ok])
        active = active[t != EMPTY_TAG]
        k += 1
    if len(jt.of_tag):
        pend = np.flatnonzero(live)
        lo = np.searchsorted(jt.of_tag, ptag[pend], side="left")
        hi = np.searchsorted(jt.of_tag, ptag[pend], side="right")
        sel = lo < hi
        act, lo, hi = pend[sel], lo[sel], hi[sel]
        while len(act):
            rows_c = jt.of_row[lo]
            ok = _host_verify(prep, act, rows_c)
            pi.append(act[ok])
            bi.append(rows_c[ok])
            lo = lo + 1
            keep = lo < hi
            act, lo, hi = act[keep], lo[keep], hi[keep]
    if pi:
        probe_idx = np.concatenate(pi)
        build_idx = np.concatenate(bi)
        # probe-row-major pair order (stable by scan step within a row)
        order = np.argsort(probe_idx, kind="stable")
        return probe_idx[order], build_idx[order]
    return np.empty(0, np.int64), np.empty(0, np.int32)


# -- page emission (host) ----------------------------------------------------


def _np_block(b: Block):
    return (
        np.asarray(b.data),
        None if b.valid is None else np.asarray(b.valid),
    )


def _emit_gather(b: Block, idx: np.ndarray, capacity: int,
                 extra_valid: Optional[np.ndarray] = None) -> Block:
    """Gather block rows by host indices into a capacity-padded Block
    (tail rows are dead by the page count invariant, so np.empty tails
    cost nothing)."""
    data, valid = _np_block(b)
    n = len(idx)
    out = np.empty((capacity,) + data.shape[1:], data.dtype)
    out[:n] = data[idx]
    # rows beyond n stay uninitialized: the page contract masks them out
    # (live rows occupy [0, count)), and skipping the tail fill saves a
    # full write pass per column
    v = None
    if valid is not None or extra_valid is not None:
        v = np.zeros(capacity, bool)
        vv = np.ones(n, bool) if valid is None else valid[idx]
        if extra_valid is not None:
            vv = vv & extra_valid
        v[:n] = vv
    return Block(
        jnp.asarray(out), b.type,
        None if v is None else jnp.asarray(v), b.dict_id,
    )


def _host_compact_page(page: Page, keep: np.ndarray) -> Page:
    """compact() twin for concrete pages: ONE flatnonzero + gathers
    instead of a full-capacity sort (ops/filter.py documents why the
    device path sorts; on the host the C gather wins)."""
    idx = np.flatnonzero(keep)
    blocks = tuple(
        _emit_gather(b, idx, page.capacity) for b in page.blocks
    )
    return Page(blocks, page.names, jnp.int32(len(idx)))


# -- public: the kernel-side join API ----------------------------------------


# prestolint: host-function -- eager host orchestration around the kernels
def table_join_n1(
    probe: Page,
    jt: JoinTable,
    probe_key_exprs,
    build_names: Sequence[str],
    out_build_names: Sequence[str],
    kind: str = "inner",
) -> Page:
    """join_n1 over the hash table (inner | left | semi | anti)."""
    probe_keys = [evaluate(e, probe) for e in probe_key_exprs]
    live = _np_live(probe)
    h = np_hash_rows_values(probe_keys)
    ptag, pdesired = _tag_desired(h, jt.bits)
    prep = _host_prepare_keys(jt, probe_keys)
    matched, brow = _probe_n1(
        jt, ptag, pdesired, live, prep, pallas_join_mode()
    )
    if kind == "semi":
        return _host_compact_page(probe, matched & live)
    if kind == "anti":
        return _host_compact_page(probe, ~matched & live)
    if kind == "inner":
        idx = np.flatnonzero(matched & live)
        blocks = [
            _emit_gather(b, idx, probe.capacity) for b in probe.blocks
        ]
        names = list(probe.names)
        bidx = brow[idx]
        for bname, oname in zip(build_names, out_build_names):
            b = jt.page.block(bname)
            blocks.append(_emit_gather(b, bidx, probe.capacity))
            names.append(oname)
        return Page(tuple(blocks), tuple(names), jnp.int32(len(idx)))
    if kind == "left":
        blocks = list(probe.blocks)
        names = list(probe.names)
        srow = np.where(matched, brow, 0)
        for bname, oname in zip(build_names, out_build_names):
            b = jt.page.block(bname)
            data, valid = _np_block(b)
            out = data[srow]
            v = matched if valid is None else (matched & valid[srow])
            blocks.append(
                Block(jnp.asarray(out), b.type, jnp.asarray(v), b.dict_id)
            )
            names.append(oname)
        return Page(tuple(blocks), tuple(names), probe.count)
    raise ValueError(f"unknown join kind {kind!r}")


# prestolint: host-function -- eager host orchestration around the kernels
def table_semi_mask(probe: Page, jt: JoinTable, probe_key_exprs):
    """semi_match_mask over the hash table (mark-join kernel)."""
    probe_keys = [evaluate(e, probe) for e in probe_key_exprs]
    live = _np_live(probe)
    h = np_hash_rows_values(probe_keys)
    ptag, pdesired = _tag_desired(h, jt.bits)
    prep = _host_prepare_keys(jt, probe_keys)
    matched, _ = _probe_n1(
        jt, ptag, pdesired, live, prep, pallas_join_mode()
    )
    return jnp.asarray(matched & live)


# prestolint: host-function -- eager host orchestration around the kernels
def table_join_expand(
    probe: Page,
    jt: JoinTable,
    probe_key_exprs,
    probe_out: Sequence[str],
    build_out: Sequence[Tuple[str, str]],
    out_capacity: int,
    kind: str = "inner",
) -> Tuple[Page, jnp.ndarray]:
    """join_expand over the hash table: count-then-emit, exact rows.

    Pairs are VERIFIED matches (not hash-range candidates), so overflow
    reports exactly total_matches - out_capacity and one retry always
    suffices."""
    probe_keys = [evaluate(e, probe) for e in probe_key_exprs]
    live = _np_live(probe)
    h = np_hash_rows_values(probe_keys)
    ptag, pdesired = _tag_desired(h, jt.bits)
    prep = _host_prepare_keys(jt, probe_keys)
    probe_idx, build_idx = _host_probe_all(
        jt, ptag, pdesired, live, prep
    )
    if kind == "left":
        # one NULL-extended row for every live probe row with no match
        has = np.zeros(probe.capacity, bool)
        has[probe_idx] = True
        synth = np.flatnonzero(live & ~has)
        probe_idx = np.concatenate([probe_idx, synth])
        build_idx = np.concatenate(
            [build_idx.astype(np.int64), np.full(len(synth), -1, np.int64)]
        )
        order = np.argsort(probe_idx, kind="stable")
        probe_idx, build_idx = probe_idx[order], build_idx[order]
    total = len(probe_idx)
    emit = min(total, out_capacity)
    pidx = probe_idx[:emit]
    bidx = np.maximum(build_idx[:emit], 0)
    bvalid = build_idx[:emit] >= 0
    blocks, names = [], []
    for name in probe_out:
        blocks.append(
            _emit_gather(probe.block(name), pidx, out_capacity)
        )
        names.append(name)
    for bname, oname in build_out:
        blocks.append(
            _emit_gather(
                jt.page.block(bname), bidx, out_capacity,
                extra_valid=bvalid,
            )
        )
        names.append(oname)
    out = Page(tuple(blocks), tuple(names), jnp.int32(emit))
    overflow = jnp.asarray(max(total - out_capacity, 0), jnp.int64)
    return out, overflow


# prestolint: host-function -- eager host orchestration around the kernels
def table_multiway_n1(
    probe: Page,
    specs: Sequence[Tuple[JoinTable, tuple, Sequence[str], Sequence[str]]],
) -> Page:
    """Multiway probe: chain TWO (or more) build tables through ONE pass
    over the probe batch (arXiv:1905.13376's multiway variant — the
    star-join shape where every key lives on the fact side). INNER
    semantics with at-most-one match per side: the batch survives all
    sides' probes before any output page is materialized, replacing
    len(specs) joins' worth of intermediate pages and compactions with
    one emit."""
    keep = _np_live(probe)
    gathered: List[Tuple[np.ndarray, JoinTable, Sequence[str],
                         Sequence[str]]] = []
    mode = pallas_join_mode()
    for jt, key_exprs, build_names, out_names in specs:
        probe_keys = [evaluate(e, probe) for e in key_exprs]
        h = np_hash_rows_values(probe_keys)
        ptag, pdesired = _tag_desired(h, jt.bits)
        prep = _host_prepare_keys(jt, probe_keys)
        matched, brow = _probe_n1(
            jt, ptag, pdesired, keep, prep, mode
        )
        keep &= matched
        gathered.append((brow, jt, build_names, out_names))
    idx = np.flatnonzero(keep)
    blocks = [_emit_gather(b, idx, probe.capacity) for b in probe.blocks]
    names = list(probe.names)
    for brow, jt, build_names, out_names in gathered:
        bidx = brow[idx]
        for bname, oname in zip(build_names, out_names):
            blocks.append(
                _emit_gather(jt.page.block(bname), bidx, probe.capacity)
            )
            names.append(oname)
    return Page(tuple(blocks), tuple(names), jnp.int32(len(idx)))
