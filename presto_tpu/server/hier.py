"""Hierarchical exchange producer: device collectives intra-host, ragged
paged partitions on the PTP2 wire inter-host.

The engine used to run two disconnected shuffle worlds: the shard_map
mesh path (`parallel/exchange.py`) repartitions with ONE `lax.all_to_all`
collective, while the HTTP cluster's partitioned task output
(`server/worker.py:_hash_partition`) looped `compact(page, part == p)`
once PER PARTITION — nparts separate device dispatches and full-page
scans per output batch. This module unifies them into a hierarchy:

* **intra-host** — rows regroup by destination partition in ONE device
  step. On a multi-device host the step is the shard_map
  `lax.all_to_all` collective itself (`shuffle_write_parts` routes each
  row to device `part % d`, the collective swaps buffers over ICI, and
  each device sorts its received rows by partition); on a single chip a
  fused jitted grouping kernel (argsort + searchsorted boundaries + one
  gather per column) does the same in one dispatch. Either way the cost
  is O(rows log rows) once, not O(nparts * rows).

* **inter-host** — the grouped rows ship over the PR 4 PTP2 wire as
  RAGGED paged partitions (ops/ragged.py): each partition's rows cut
  into chunks of at most `PRESTO_TPU_RAGGED_PAGE_ROWS` live rows, the
  last chunk partial. A dense collective output buffer pads every
  partition to the largest one — at 100:1 skew that pads ~99% of the
  wire; the ragged unit ships live rows only, and `wire_padding`
  accounts for exactly how much the skew would have cost.

The producer path is capability-negotiated (`serde.local_capabilities`
advertises ``"hier"``; `negotiate` intersects it fleet-wide) and gated
by the `hier_exchange` circuit breaker — any fault degrades the task to
the flat per-partition loop, monotonically, with oracle-equal output.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import types as T
from ..expr.compiler import evaluate
from ..ops.hashing import hash_rows
from ..ops.ragged import page_rows_default, wire_padding
from ..page import Block, Page
from . import knobs
from .serde import serialize_page

_PART_COL = "$hier_part"


class HierExchangeStats:
    """Thread-safe accounting for one task's hierarchical exchange
    output (the producer half; the consumer-side overlap lives in
    ExchangeStats). Shipped in the task status payload under
    ``exchangeStats["hier"]``, folded by the coordinator, exported to
    /v1/metrics via obs/export.export_hier_stats."""

    def __init__(self):
        self._lock = threading.Lock()
        self.exchanges = 0  # output batches regrouped hierarchically
        self.collective_exchanges = 0  # of those, via the all_to_all path
        self.rows = 0
        self.collective_s = 0.0  # intra-host regroup wall (device step
        # dispatch + host readback), the "collective wall" of the footer
        self.wire_pages = 0  # ragged pages put on the wire
        self.ragged_pad_rows = 0  # pad the ragged paged layout carries
        self.fixed_pad_rows = 0  # pad a pad-to-max wire unit would carry
        self.fallbacks = 0  # batches that fell back to the flat loop

    def record_batch(self, rows: int, seconds: float, collective: bool,
                     pages: int, pad: dict) -> None:
        with self._lock:
            self.exchanges += 1
            if collective:
                self.collective_exchanges += 1
            self.rows += int(rows)
            self.collective_s += float(seconds)
            self.wire_pages += int(pages)
            self.ragged_pad_rows += int(pad.get("ragged_pad_rows", 0))
            self.fixed_pad_rows += int(pad.get("fixed_pad_rows", 0))

    def record_fallback(self) -> None:
        with self._lock:
            self.fallbacks += 1

    def merge_snapshot(self, snap: Optional[dict]) -> None:
        """Fold a remote snapshot (task status payload) into this
        accumulator — the coordinator sums its producers' hier stats."""
        if not snap:
            return
        with self._lock:
            self.exchanges += int(snap.get("exchanges", 0))
            self.collective_exchanges += int(
                snap.get("collective_exchanges", 0)
            )
            self.rows += int(snap.get("rows", 0))
            self.collective_s += (snap.get("collective_ms") or 0) / 1e3
            self.wire_pages += int(snap.get("wire_pages", 0))
            self.ragged_pad_rows += int(snap.get("ragged_pad_rows", 0))
            self.fixed_pad_rows += int(snap.get("fixed_pad_rows", 0))
            self.fallbacks += int(snap.get("fallbacks", 0))

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "exchanges": self.exchanges,
                "collective_exchanges": self.collective_exchanges,
                "rows": self.rows,
                "collective_ms": round(self.collective_s * 1e3, 2),
                "wire_pages": self.wire_pages,
                "ragged_pad_rows": self.ragged_pad_rows,
                "fixed_pad_rows": self.fixed_pad_rows,
                "pad_saved_rows": max(
                    self.fixed_pad_rows - self.ragged_pad_rows, 0
                ),
                "fallbacks": self.fallbacks,
            }


def hier_negotiated(caps: Optional[dict]) -> bool:
    """Did the fleet-wide wire negotiation keep the hierarchical
    capability? A spec without the advert (old coordinator, or any
    worker that did not advertise it) degrades to the flat loop."""
    return bool(isinstance(caps, dict) and (caps.get("hier") or {}).get(
        "ragged"
    ))


# ---------------------------------------------------------------------------
# intra-host regroup: one device step, not one dispatch per partition
# ---------------------------------------------------------------------------

_FUSED_JIT = None  # lazily-built jitted regroup (one per process)
_COLLECTIVE_CACHE: dict = {}  # (n_devices, nparts) -> shard_map'd fn


def _fused_regroup_fn():
    global _FUSED_JIT
    if _FUSED_JIT is None:
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("nparts",))
        def fused(page, part, nparts):
            # dead rows carry the nparts sentinel: stable argsort puts
            # them LAST, searchsorted boundaries never include them
            order = jnp.argsort(part, stable=True)
            ps = part[order]
            bins = jnp.arange(nparts, dtype=ps.dtype)
            starts = jnp.searchsorted(ps, bins, side="left")
            ends = jnp.searchsorted(ps, bins, side="right")
            blocks = tuple(b.take_rows(order) for b in page.blocks)
            return blocks, starts.astype(jnp.int32), ends.astype(jnp.int32)

        _FUSED_JIT = fused
    return _FUSED_JIT


def _collective_regroup_fn(n_dev: int, nparts: int, names: Tuple[str, ...]):
    """Build (and cache) the shard_map'd collective regroup for this
    (device count, partition count) topology: each device scatters its
    rows toward owner device ``part % n_dev`` (`shuffle_write_parts`),
    ONE `lax.all_to_all` swaps the buffers over ICI, and the receiver
    sorts its rows by destination partition so the host can slice each
    owned partition's contiguous range."""
    key = (n_dev, nparts, names)
    fn = _COLLECTIVE_CACHE.get(key)
    if fn is not None:
        return fn
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.exchange import all_to_all_page, shuffle_write_parts
    from ..parallel.mesh import default_mesh

    mesh = default_mesh(n_dev)
    axis = mesh.axis_names[0]
    ppd = -(-nparts // n_dev)  # partitions owned per device

    def shard_fn(blocks, part):
        cap = part.shape[0]  # per-device shard rows R
        carrying = blocks + (Block(part, T.INTEGER),)
        page_l = Page(carrying, names + (_PART_COL,),
                      jnp.asarray(cap, jnp.int32))
        # destination device owns partitions congruent to it mod n_dev;
        # the sentinel (part >= nparts: dead/pad rows) drops in the
        # scatter. part_capacity == R is overflow-free by construction
        # (a shard holds at most R rows, however skewed).
        dest = jnp.where(part < nparts, part % n_dev, n_dev)
        buf, counts, _dropped = shuffle_write_parts(
            page_l, dest, n_dev, cap
        )
        recv = all_to_all_page(buf, counts, axis, cap)
        pcol = recv.blocks[-1].data
        pcol = jnp.where(recv.live_mask(), pcol, nparts + n_dev)
        order = jnp.argsort(pcol, stable=True)
        ps = pcol[order]
        j = jax.lax.axis_index(axis)
        bins = j + jnp.arange(ppd, dtype=ps.dtype) * n_dev
        starts = jnp.searchsorted(ps, bins, side="left")
        ends = jnp.searchsorted(ps, bins, side="right")
        out = tuple(b.take_rows(order) for b in recv.blocks[:-1])
        return out, starts.astype(jnp.int32), ends.astype(jnp.int32)

    kw = dict(
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    smapped = jax.shard_map(shard_fn, check_vma=False, **kw)
    fn = jax.jit(smapped)
    _COLLECTIVE_CACHE[key] = fn
    return fn


def _pad_rows(arr, rows: int):
    import jax.numpy as jnp

    if arr.shape[0] >= rows:
        return arr
    pad = [(0, rows - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad)


def _pad_block_rows(b: Block, rows: int) -> Block:
    return Block(
        _pad_rows(b.data, rows), b.type,
        None if b.valid is None else _pad_rows(b.valid, rows),
        b.dict_id,
    )


def _collective_eligible(page: Page, n_dev: int, rows: int) -> bool:
    if n_dev < knobs.hier_exchange_min_devices():
        return False
    if rows < knobs.hier_exchange_min_rows():
        return False
    # the collective swaps data/valid arrays only: collection blocks
    # (lengths/elem_valid/key_block companions) take the fused kernel
    return all(
        b.lengths is None and b.elem_valid is None and b.key_block is None
        for b in page.blocks
    )


def _host_block(b: Block) -> Block:
    return Block(
        np.asarray(b.data), b.type,
        None if b.valid is None else np.asarray(b.valid),
        b.dict_id,
        lengths=None if b.lengths is None else np.asarray(b.lengths),
        elem_valid=(
            None if b.elem_valid is None else np.asarray(b.elem_valid)
        ),
        key_block=None if b.key_block is None else _host_block(b.key_block),
    )


def hier_partition(
    page: Page,
    key_exprs,
    nparts: int,
    caps: Optional[dict] = None,
    stats=None,
    hier: Optional[HierExchangeStats] = None,
    page_rows: Optional[int] = None,
) -> Dict[int, List[bytes]]:
    """Partition live rows by key hash into serialized RAGGED wire pages
    — the hierarchical replacement for the flat `_hash_partition` loop.
    Output contract matches flat exactly: every partition gets at least
    one page (possibly empty), and the union of decoded rows per
    partition equals the flat path's."""
    import jax

    pr = page_rows or page_rows_default()
    t0 = time.perf_counter()
    n = int(page.count)
    keys = [evaluate(e, page) for e in key_exprs]
    import jax.numpy as jnp

    h = hash_rows(keys)
    part = (h % jnp.uint64(nparts)).astype(jnp.int32)
    part = jnp.where(page.live_mask(), part, nparts)

    n_dev = len(jax.devices())
    collective = _collective_eligible(page, n_dev, n)
    if collective:
        # shard the batch over the local mesh (rows padded to a multiple
        # of the device count; pad rows carry the drop sentinel)
        cap = -(-page.capacity // n_dev) * n_dev
        blocks = tuple(_pad_block_rows(b, cap) for b in page.blocks)
        part_in = jnp.pad(
            part, (0, cap - page.capacity), constant_values=nparts
        )
        fn = _collective_regroup_fn(n_dev, nparts, page.names)
        out_blocks, starts, ends = fn(blocks, part_in)
        local_cap = cap  # each device receives up to n_dev * (cap/n_dev)
        ppd = -(-nparts // n_dev)
        starts = np.asarray(starts).reshape(n_dev, ppd)
        ends = np.asarray(ends).reshape(n_dev, ppd)
        host = [_host_block(b) for b in out_blocks]
        regions: Dict[int, List[Tuple[int, int]]] = {}
        for j in range(n_dev):
            base = j * local_cap
            for i in range(ppd):
                p = j + i * n_dev
                if p >= nparts:
                    break
                lo, hi = base + int(starts[j, i]), base + int(ends[j, i])
                if hi > lo:
                    regions.setdefault(p, []).append((lo, hi))
    else:
        fn = _fused_regroup_fn()
        out_blocks, starts, ends = fn(page, part, nparts)
        starts = np.asarray(starts)
        ends = np.asarray(ends)
        host = [_host_block(b) for b in out_blocks]
        regions = {
            p: [(int(starts[p]), int(ends[p]))]
            for p in range(nparts)
            if int(ends[p]) > int(starts[p])
        }
    regroup_s = time.perf_counter() - t0

    out: Dict[int, List[bytes]] = {}
    counts: List[int] = []
    pages_emitted = 0
    for p in range(nparts):
        rows_p = sum(hi - lo for lo, hi in regions.get(p, ()))
        counts.append(rows_p)
        datas: List[bytes] = []
        for lo, hi in regions.get(p, ()):
            # ragged wire unit: chunks of at most page_rows LIVE rows,
            # last chunk partial — skew never pads the wire
            for start in range(lo, hi, pr):
                stop = min(start + pr, hi)
                sl = slice(start, stop)
                chunk = Page(
                    tuple(b.take_rows(sl) for b in host),
                    page.names,
                    stop - start,
                )
                datas.append(serialize_page(chunk, caps=caps, stats=stats))
        if not datas:
            # contract parity with the flat loop: an empty partition
            # still ships one (empty) page, so consumers that require at
            # least one page per source see identical streams
            empty = Page(
                tuple(b.take_rows(slice(0, 0)) for b in host),
                page.names, 0,
            )
            datas.append(serialize_page(empty, caps=caps, stats=stats))
        pages_emitted += len(datas)
        out[p] = datas
    if hier is not None:
        hier.record_batch(
            n, regroup_s, collective, pages_emitted,
            wire_padding(counts, pr),
        )
    return out
