"""Streaming (paged) plan execution with a device-memory budget.

Re-designed equivalent of the reference's worker streaming pipeline — the
Driver hot loop pulling pages operator-to-operator
(presto-main/.../operator/Driver.java:347-430), split/morsel scans
(SourcePartitionedScheduler + ConnectorPageSource), and the revocable-
memory/spill machinery (memory/MemoryPool.java:43,
operator/HashBuilderOperator.java:155-180 SPILLING_INPUT states,
spiller/). TPU-first redesign:

* A "page" is a fixed-capacity device batch (static shapes -> one compiled
  kernel chain reused for every batch); the host driver loop streams leaf
  batches through stateless kernels into accumulating sinks.
* Aggregations accumulate PARTIAL states on device and merge periodically —
  the same partial/final decomposition the distributed path uses
  (ops/aggregate.decompose_partial), so a base table is never resident.
* Join build sides materialize on device under a MemoryPool budget; when
  the budget would be exceeded they *offload to host RAM* (the disk-spill
  analog, SURVEY §5 "long-context analog") and INNER joins run
  chunk-by-chunk against re-streamed probes — the reference's grouped /
  bucket-wise execution (Lifespan + PipelineExecutionStrategy.GROUPED).
* Sinks short-circuit where the reference would (LIMIT stops the scan).

Everything falls back to the materializing Executor for node shapes that
need whole inputs (windows, full-outer composition, sorts beyond budget).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..expr import ir
from ..ops.aggregate import (
    apply_avg_post,
    decompose_partial,
    global_aggregate,
    grouped_aggregate_sorted,
)
from ..ops.filter import filter_page
from ..ops.join import build_sorted, join_expand, join_n1
from ..ops.sort import distinct_page, limit_page, sort_page, top_n
from ..obs import span as obs_span
from ..obs.span import host_read
from ..ops.union import concat_pages
from ..page import Block, Page, round_capacity
from ..plan import nodes as N
from .executor import SMALL_PAGE_ROWS, ExecutionError, Executor
from .memory import MemoryExceededError, MemoryPool
from .stats import page_device_bytes


def coalesce_pages(
    pages: Iterator[Page], target_rows: int
) -> Iterator[Page]:
    """Merge consecutive small pages into ~target_rows batches.

    The hierarchical exchange (server/hier.py) ships RAGGED paged
    partitions — wire pages of at most PRESTO_TPU_RAGGED_PAGE_ROWS live
    rows, so skew never pads the wire. The flip side is many small
    pages per batch; feeding them one-by-one into the streaming sinks
    would dispatch a device kernel per sliver. This coalescer restores
    batch efficiency on the consumer: accumulate until target_rows,
    concat once, hand the sinks full batches. A stream of only empty
    pages coalesces to ONE empty page, so schema survives; a truly
    empty iterator stays empty."""
    held: List[Page] = []
    held_rows = 0
    for page in pages:
        n = int(host_read(page.count))
        if n >= target_rows and not held:
            yield page
            continue
        held.append(page)
        held_rows += n
        if held_rows >= target_rows:
            yield held[0] if len(held) == 1 else concat_pages(held)
            held, held_rows = [], 0
    if held:
        yield held[0] if len(held) == 1 else concat_pages(held)


@dataclasses.dataclass
class HostTable:
    """Host-RAM offloaded rows (the spill-file analog): numpy columns +
    schema, uploadable chunk-by-chunk."""

    names: Tuple[str, ...]
    types: tuple
    dict_ids: tuple
    columns: List[np.ndarray]
    valids: List[Optional[np.ndarray]]

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @property
    def row_bytes(self) -> int:
        if not self.num_rows:
            return 0
        per = sum(c.dtype.itemsize * (c.size // len(c)) for c in self.columns)
        per += sum(1 for v in self.valids if v is not None)
        return per

    def slice_page(self, start: int, stop: int, pad_to=None) -> Page:
        blocks = []
        for c, v, t2, d in zip(self.columns, self.valids, self.types, self.dict_ids):
            data = jnp.asarray(c[start:stop])
            valid = None if v is None else jnp.asarray(v[start:stop])
            blk = Block(data, t2, valid, d)
            if pad_to is not None and pad_to > stop - start:
                from ..page import _pad_block

                blk = _pad_block(blk, pad_to)
            blocks.append(blk)
        return Page.from_blocks(blocks, self.names, count=stop - start)

    @staticmethod
    def from_pages(pages: List[Page]) -> "HostTable":
        from ..ops.union import unify_block_dictionaries

        first = pages[0]
        cols: List[np.ndarray] = []
        valids: List[Optional[np.ndarray]] = []
        dict_ids: List[Optional[int]] = []
        for i in range(len(first.blocks)):
            # unify per-batch dictionaries BEFORE concatenating codes (same
            # invariant as concat_pages — codes are meaningless across
            # different dictionaries)
            blocks, did = unify_block_dictionaries([p.blocks[i] for p in pages])
            dict_ids.append(did)
            parts = []
            vparts = []
            any_valid = any(b.valid is not None for b in blocks)
            for p, b in zip(pages, blocks):
                n = int(host_read(p.count))
                parts.append(host_read(b.data[:n]))
                if any_valid:
                    vparts.append(
                        host_read(b.valid[:n])
                        if b.valid is not None
                        else np.ones((n,), np.bool_)
                    )
            cols.append(np.concatenate(parts) if parts else np.empty((0,)))
            valids.append(np.concatenate(vparts) if any_valid else None)
        return HostTable(
            first.names,
            tuple(b.type for b in first.blocks),
            tuple(dict_ids),
            cols,
            valids,
        )

    def append_page(self, page: Page) -> None:
        self.append_host(HostTable.from_pages([page]))

    def append_host(self, other: "HostTable") -> None:
        """Concatenate another host table's rows onto this one, unifying
        per-table string dictionaries."""
        from ..page import dictionary_by_id, intern_dictionary

        dict_ids = list(self.dict_ids)
        for i in range(len(self.columns)):
            a_id, b_id = dict_ids[i], other.dict_ids[i]
            b_col = other.columns[i]
            if a_id != b_id:
                # host-side dictionary unification: remap BOTH code arrays
                # onto the merged sorted dictionary
                da = dictionary_by_id(a_id) if a_id is not None else ()
                db = dictionary_by_id(b_id) if b_id is not None else ()
                merged = tuple(sorted(set(da) | set(db)))
                index = {s: j for j, s in enumerate(merged)}
                map_a = np.array([index[s] for s in da], np.int32)
                map_b = np.array([index[s] for s in db], np.int32)
                if len(da):
                    self.columns[i] = map_a[self.columns[i]]
                if len(db):
                    b_col = map_b[b_col]
                dict_ids[i] = intern_dictionary(merged)
            self.columns[i] = np.concatenate([self.columns[i], b_col])
            a, b = self.valids[i], other.valids[i]
            if a is None and b is None:
                continue
            if a is None:
                a = np.ones((len(self.columns[i]) - len(b_col),), np.bool_)
            if b is None:
                b = np.ones((other.num_rows,), np.bool_)
            self.valids[i] = np.concatenate([a, b])
        self.dict_ids = tuple(dict_ids)


def _pushdown_hints(predicate, scan_node: N.TableScan):
    """Extract (source_column, op, python_value) pruning hints from simple
    conjuncts over scanned columns (the TupleDomain-lite of the SPI)."""
    import datetime as pydt
    import decimal as pydec

    to_source = {ch: col for ch, col, _ in scan_node.columns}
    types = {ch: typ for ch, _, typ in scan_node.columns}
    conjuncts: List = []

    def split(e):
        if isinstance(e, ir.Call) and e.name == "and":
            for a in e.args:
                split(a)
        else:
            conjuncts.append(e)

    split(predicate)
    flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq"}
    hints = []

    def value_for(ch, lit):
        typ = types.get(ch)
        v = lit.value
        if v is None:
            return None
        if isinstance(typ, T.DateType):
            if isinstance(v, str):
                return pydt.date.fromisoformat(v)
            return pydt.date(1970, 1, 1) + pydt.timedelta(days=int(v))
        if isinstance(typ, T.DecimalType):
            # literal carries the LOGICAL value (planner _number_literal);
            # Decimal statistics compare fine against float in Python
            return float(v) if not isinstance(v, pydec.Decimal) else v
        if isinstance(typ, T.VarcharType):
            return v if isinstance(v, str) else None
        return v

    def in_values(e):
        """(channel, values) for `col IN (lit...)` and OR-of-equals over
        ONE column — both become the SPI 'in' hint (reference
        TupleDomain's discrete value sets)."""
        if e.name == "in" and isinstance(e.args[0], ir.ColumnRef):
            col, opts = e.args[0], e.args[1:]
            if all(isinstance(o, ir.Literal) for o in opts):
                return col.name, opts
            return None
        if e.name == "or":
            col = None
            opts = []
            for part in e.args:
                if not (
                    isinstance(part, ir.Call)
                    and part.name == "eq"
                    and len(part.args) == 2
                ):
                    return None
                a, b = part.args
                if isinstance(b, ir.ColumnRef) and isinstance(a, ir.Literal):
                    a, b = b, a
                if not (
                    isinstance(a, ir.ColumnRef) and isinstance(b, ir.Literal)
                ):
                    return None
                if col is None:
                    col = a.name
                elif col != a.name:
                    return None
                opts.append(b)
            return (col, tuple(opts)) if col is not None else None
        return None

    for e in conjuncts:
        if not isinstance(e, ir.Call):
            continue
        iv = in_values(e)
        if iv is not None:
            col_name, opts = iv
            if col_name in to_source:
                vals = tuple(value_for(col_name, o) for o in opts)
                if all(v is not None for v in vals):
                    hints.append((to_source[col_name], "in", vals))
            continue
        if e.name == "between" and isinstance(e.args[0], ir.ColumnRef):
            col, lo, hi = e.args
            if col.name in to_source and isinstance(lo, ir.Literal) and isinstance(hi, ir.Literal):
                vlo, vhi = value_for(col.name, lo), value_for(col.name, hi)
                if vlo is not None:
                    hints.append((to_source[col.name], "ge", vlo))
                if vhi is not None:
                    hints.append((to_source[col.name], "le", vhi))
            continue
        if e.name not in flip:
            continue
        a, b = e.args
        if isinstance(a, ir.ColumnRef) and isinstance(b, ir.Literal):
            col, lit, op = a, b, e.name
        elif isinstance(b, ir.ColumnRef) and isinstance(a, ir.Literal):
            col, lit, op = b, a, flip[e.name]
        else:
            continue
        if col.name not in to_source:
            continue
        v = value_for(col.name, lit)
        if v is not None:
            hints.append((to_source[col.name], op, v))
    return hints or None


# nodes `_run` executes whole, through a budget-aware sink
_SINKS = (N.Aggregate, N.Distinct, N.TopN, N.Limit, N.Sort)
_END = object()  # a stream has no batch left


class StreamingExecutor:
    """Host driver loop over device page batches (reference Driver +
    TaskExecutor collapsed: one Python loop, kernels stay on device)."""

    def __init__(
        self,
        catalog,
        batch_rows: int = 1 << 20,
        memory_budget: Optional[int] = None,
        collector=None,
        query_id: str = "",
        worker_pool=None,
        spill_space=None,
    ):
        self.catalog = catalog
        self.batch_rows = batch_rows
        self.query_id = query_id or f"local-{id(self):x}"
        # parent mirroring: on a worker, executor-held bytes show up in
        # the WorkerMemoryPool's execution ledger (/v1/memory)
        self.pool = MemoryPool(
            memory_budget, name=self.query_id, parent=worker_pool,
            query_id=self.query_id,
        )
        self.local = Executor(catalog, collector=collector)
        self.collector = collector
        # dynamic filters are shared with the delegate executor: joins
        # publish there, and scans/filters running through exec_node
        # consume the same registry (exec/dynfilter.py)
        self.dyn_ctx = self.local.dyn_ctx
        # which operators offloaded to host this query (tests/EXPLAIN assert
        # the spill path actually fired; reference: OperatorStats spill
        # counters)
        self.spill_events: List[str] = []
        # degradation-ladder observability (EXPLAIN ANALYZE memory line):
        # disk bytes written, hybrid-join partition count / recursion
        # depth, and chunk-loop fallbacks (all-ties / depth exhausted)
        self.spill_stats: Dict[str, int] = {
            "disk_bytes": 0,
            "hybrid_parts": 0,
            "hybrid_depth": 0,
            "chunk_fallbacks": 0,
            # ragged paged partition layout (ops/ragged.py): pages
            # allocated and live-slot occupancy percent for the last
            # hybrid join's build partitions
            "ragged_pages": 0,
            "ragged_occupancy_pct": 0,
            # batches the aggregate sink routed through the hash-slot
            # group-by instead of the sort composition
            "agg_hash_batches": 0,
        }
        self._spill_space = spill_space
        self._owns_spill = spill_space is None
        self._pos: Dict[int, str] = {}  # span positions of the running plan

    def _spill(self):
        """Lazily opened spill space (exec/spillspace.py): disk-tier
        quota accounting + guaranteed file cleanup at run() end (owned
        spaces) or task end (worker-provided spaces)."""
        if self._spill_space is None:
            from .spillspace import SPILL_MANAGER

            self._spill_space = SPILL_MANAGER.open(self.query_id)
            self._owns_spill = True
        return self._spill_space

    def _spill_share(self) -> int:
        """Device bytes one offloaded operator may hold at a time: half the
        budget remaining after resident reservations."""
        budget = self.pool.max_bytes or (1 << 62)
        return max((budget - self.pool.reserved) // 2, 1)

    def _collect_or_spill(self, child: N.PlanNode, tag: str):
        """Accumulate a child stream on device while the budget allows;
        past it — or when a revoke is pending — migrate everything to a
        SpilledRows store (host RAM, then the disk tier). Returns
        (first_batch, device_batches, held_bytes, spilled_or_None)."""
        from .spill import SpilledRows

        batches: List[Page] = []
        held = 0
        spilled = None
        first: Optional[Page] = None
        for b in self.stream(child):
            if first is None:
                first = b  # schema carrier for the all-empty case
            if int(host_read(b.count)) == 0:
                continue
            nb = page_device_bytes(b)
            if spilled is None and self.pool.can_accumulate(held + nb):
                batches.append(b)
                held += nb
                self.pool.accumulated = held
                continue
            if spilled is None:
                self.spill_events.append(tag)
                spilled = SpilledRows(space=self._spill(), tag=tag)
                for p in batches:
                    spilled.append(p)
                batches = []
                self.pool.note_revoked(held)
                held = 0
                self.pool.accumulated = 0
            spilled.append(b)
        self.pool.accumulated = 0
        return first, batches, held, spilled

    # -- public --

    def run(self, node: N.PlanNode) -> Page:
        self.dyn_ctx.reset()  # filters are per-query state
        self._pos = (
            N.plan_positions(node) if obs_span.current() is not None else {}
        )
        try:
            return self._run(node)
        finally:
            self.release_spill()

    def release_spill(self) -> None:
        """Guaranteed spill cleanup: fold disk-tier counters into the
        stats and unlink this query's spill files. Worker-provided spaces
        are released by the task's own finally (server/worker.py)."""
        if self._spill_space is not None:
            self.spill_stats["disk_bytes"] += self._spill_space.written
            self._spill_space.written = 0
            if self._owns_spill:
                self._spill_space.release()
                self._spill_space = None

    def rows(self, node: N.PlanNode) -> List[tuple]:
        return self.run(node).to_pylist()

    # -- top-level dispatch: sinks consume streams --

    def _run(self, node: N.PlanNode) -> Page:
        """One sink node, whole. Where a trace is open on this thread
        (obs/span.py) it runs inside a span named by its class with its
        position in the plan, as `Executor._run` opens one: the sink's
        self time is its span minus its children's."""
        cur = obs_span.current()
        if cur is None:
            return self._run_sink(node)
        span = cur[0].enter(type(node).__name__, pos=self._node_pos(node))
        try:
            out = self._run_sink(node)
        except BaseException:
            cur[0].leave(span, "error")
            raise
        cur[0].leave(span)
        return out

    def _node_pos(self, node: N.PlanNode) -> str:
        """`0.1.0`: child indices from the plan's root (`run` maps the
        plan it was given; `?` for a node that is not of it)."""
        return self._pos.get(id(node), "?")

    def _run_sink(self, node: N.PlanNode) -> Page:
        if isinstance(node, N.Output):
            return self.local.exec_node(node, self._run(node.child))
        if isinstance(node, N.Aggregate):
            return self._sink_aggregate(node)
        if isinstance(node, N.Distinct):
            return self._sink_distinct(node)
        if isinstance(node, N.TopN):
            return self._sink_topn(node)
        if isinstance(node, N.Limit):
            return self._sink_limit(node)
        if isinstance(node, N.Sort):
            return self._sink_sort(node)
        # everything else: materialize the stream
        return self._materialize(node)

    def _materialize(self, node: N.PlanNode) -> Page:
        pages: List[Page] = []
        first: Optional[Page] = None
        for p in self.stream(node):
            if first is None:
                first = p  # schema carrier for the all-empty case
            if int(host_read(p.count)) > 0:
                pages.append(p)
        if not pages:
            return first
        if len(pages) == 1:
            return pages[0]
        return concat_pages(pages)

    # -- streaming core: generator of batches per node -----------------------

    def stream(self, node: N.PlanNode) -> Iterator[Page]:
        """A node's batches; a sink reached mid-tree has `_run`'s span,
        every other node `_spanned`'s."""
        if isinstance(node, _SINKS):
            return self._stream_node(node)
        return self._spanned(node, self._stream_node(node))

    def _spanned(
        self, node: N.PlanNode, batches: Iterator[Page]
    ) -> Iterator[Page]:
        """`batches`, the stream of `node`, under ONE span for all of
        them where a trace is open on this thread (`obs.span.Pulled`:
        the thread's innermost while the node's own code runs, closed
        at the sum of those pieces), not one a batch."""
        pulled = obs_span.Pulled.open(
            type(node).__name__, pos=self._node_pos(node)
        )
        if pulled is None:
            yield from batches
            return
        status = "error"
        try:
            while True:
                with pulled:
                    batch = next(batches, _END)
                if batch is _END:
                    break
                yield batch
            status = "ok"
        except GeneratorExit:  # the consumer stopped pulling (LIMIT)
            status = "ok"
            raise
        finally:
            batches.close()
            pulled.close(status)

    def _stream_node(self, node: N.PlanNode) -> Iterator[Page]:
        if isinstance(node, N.TableScan):
            yield from self._stream_scan(node)
        elif isinstance(node, N.Filter) and isinstance(node.child, N.TableScan):
            # predicate pushdown hint: simple conjuncts prune row groups /
            # partitions at the connector (reference TupleDomain pushdown);
            # the real filter kernel still runs on every delivered batch
            hints = _pushdown_hints(node.predicate, node.child)
            for batch in self._spanned(
                node.child, self._stream_scan(node.child, predicate=hints)
            ):
                yield self.local.exec_node(node, batch)
        elif isinstance(node, (N.Filter, N.Project, N.Unnest, N.Sample)):
            # all row-local and stateless: apply per batch (Unnest expands
            # within the batch, keeping the device-memory budget honest)
            for batch in self.stream(node.child):
                yield self.local.exec_node(node, batch)
        elif isinstance(node, N.Join) and node.kind in ("inner", "left") and not (
            node.kind == "left" and node.residual is not None
        ):
            yield from self._stream_join(node)
        elif isinstance(node, N.SemiJoin) and node.residual is None:
            yield from self._stream_semijoin(node)
        elif isinstance(node, N.ScalarApply):
            sub = self._run(node.subquery)
            for batch in self.stream(node.child):
                yield self.local.exec_node(node, batch, sub)
        elif isinstance(node, N.Union) and not node.distinct:
            first_names = None
            for child in node.children:
                for batch in self.stream(child):
                    if first_names is None:
                        first_names = batch.names
                    yield Page(batch.blocks, first_names, batch.count)
        elif isinstance(node, N.Window) and node.partition_exprs:
            yield from self._stream_window(node)
        elif isinstance(node, _SINKS):
            # sink nodes reached mid-tree (e.g. Sort under the Project that
            # drops a hidden order channel) still go through their
            # budget-aware sinks, not the materializing fallback
            yield self._run(node)
        else:
            # window / outer compositions / distinct-union / exchanges:
            # materialize the subtree with the classic executor (its inputs
            # still stream where they can, via _run recursion)
            yield self._exec_fallback(node)

    def _exec_fallback(self, node: N.PlanNode) -> Page:
        pages = [self._run(c) for c in node.children]
        return self.local.exec_node(node, *pages)

    def _dyn_scan_hints(self, node: N.TableScan):
        """SPI pruning conjuncts from published dynamic filters (the
        scan-side half of dynamic filtering: connectors prune row groups /
        stripes before decode + upload)."""
        hints = []
        types = {ch: typ for ch, _col, typ in node.columns}
        for fid, ch, src_col, _apply in node.dynamic_filters:
            df = self.local.dyn_ctx.get(fid)
            if df is not None:
                try:
                    # the scan knows the channel's type — authoritative
                    # for wire-reconstructed (typeless) filters
                    hints.extend(df.spi_conjuncts(src_col, typ=types.get(ch)))
                except Exception:  # noqa: BLE001 — hints are best-effort
                    continue
        return hints

    def _scan_out(self, node: N.TableScan, page: Page) -> Page:
        """Post-scan dynamic mask for scans with no Filter above (the
        annotation's apply_mask entries); fused-into-Filter entries are
        applied by exec_node(Filter) downstream."""
        if node.dynamic_filters:
            return self.local._apply_scan_masks(node, page)
        return page

    def _stream_scan(self, node: N.TableScan, predicate=None) -> Iterator[Page]:
        # row_count is a planner ESTIMATE (statistics); drive the scan off
        # the actual batches until a short batch marks the end of the table
        est = self.catalog.row_count(node.table)
        B = self.batch_rows
        if node.dynamic_filters:
            dyn = self._dyn_scan_hints(node)
            if dyn:
                predicate = list(predicate or []) + dyn
        scan = getattr(self.catalog, "scan", None)
        if scan is None:
            yield self._scan_out(
                node, self._rename_scan(node, self.catalog.page(node.table))
            )
            return
        if est <= B // 2 and not predicate:
            try:
                src = self.catalog.page(node.table)
            except MemoryError:
                pass  # chunked catalogs refuse to materialize; stream below
            else:
                yield self._scan_out(node, self._rename_scan(node, src))
                return
        cols = [col for _, col, _ in node.columns]
        exact = getattr(self.catalog, "exact_row_count", None)
        total = exact(node.table) if exact is not None else None
        if total is None:
            # without an exact row count the short-batch heuristic is the
            # only end-of-table signal, and pruning may shorten any batch —
            # drop the (optional) hint rather than risk dropped rows
            predicate = None
        start = 0
        read_total = skipped_total = rows_total = 0
        while True:
            t0 = time.perf_counter()
            src = scan(
                node.table, start, start + B, pad_to=B,
                columns=cols, predicate=predicate,
            )
            scan_s = time.perf_counter() - t0
            # every column is with the runtime: when the link has copied
            # them goes onto the scan's span from a watcher thread
            # (`upload_s`, `link_idle_s`, `inflight_peak_bytes`)
            src_bytes = page_device_bytes(src)
            obs_span.sent(src, "link", src_bytes)
            # connector pruning counters are per scan CALL; take the max
            # across batches — exact for partition pruning (every call sees
            # the full file set) and a per-batch high-water for stripe
            # pruning (each call only sees its range)
            skipped_total = max(
                skipped_total,
                getattr(self.catalog, "last_scan_files_skipped", 0) or 0,
            )
            read_total = max(
                read_total,
                getattr(self.catalog, "last_scan_files_read", 0) or 0,
            )
            n = int(host_read(src.count))
            # the scan's per-batch work, folded into the node's ONE span
            # (docs/observability.md): `scan_s` is the wall inside the
            # connector (slice, pad, hand the columns to the runtime);
            # `upload_bytes` the live rows' share of the padded page
            # (stored widths: dictionary codes, a mask a byte a row)
            rows_total += n
            obs_span.count(
                batches=1, scan_s=scan_s,
                upload_bytes=src_bytes // max(src.capacity, 1) * n,
            )
            self.local._span_note(rows=rows_total)
            if n > 0 or start == 0:
                yield self._scan_out(node, self._rename_scan(node, src))
            start += B
            done = (start >= total) if total is not None else (n < B)
            # n < B only marks table end without pruning (predicate hints
            # can legally shorten any batch)
            if done:
                # surface connector pruning in EXPLAIN ANALYZE (reference:
                # the hive split source reports skipped partitions)
                if skipped_total and self.collector is not None:
                    self.collector.stats_for(node).detail = (
                        f"files: {read_total} read, "
                        f"{skipped_total} pruned"
                    )
                return

    @staticmethod
    def _rename_scan(node: N.TableScan, src: Page) -> Page:
        blocks, names = [], []
        for ch, col, _typ in node.columns:
            blocks.append(src.block(col))
            names.append(ch)
        return Page(tuple(blocks), tuple(names), src.count)

    # -- joins ----------------------------------------------------------------

    def _collect_side(self, node: N.PlanNode):
        """Materialize a build side on device within budget; offload to a
        SpilledRows store (host RAM -> disk tier) when the budget runs
        out or a revoke is pending (HashBuilderOperator's
        revoke-to-spill)."""
        from .spill import SpilledRows

        batches: List[Page] = []
        held = 0
        spilled: Optional[SpilledRows] = None
        first: Optional[Page] = None
        for b in self.stream(node):
            if first is None:
                first = b
            if int(host_read(b.count)) == 0:
                continue
            nb = page_device_bytes(b)
            if spilled is None and self.pool.can_accumulate(nb + held):
                batches.append(b)
                held += nb
                self.pool.accumulated = held
            else:
                if spilled is None:
                    self.spill_events.append("join_build")
                    spilled = SpilledRows(
                        space=self._spill(), tag="join_build"
                    )
                    for p in batches:
                        spilled.append(p)
                    batches = []
                    self.pool.note_revoked(held)
                    held = 0
                    self.pool.accumulated = 0
                spilled.append(b)
        self.pool.accumulated = 0
        if spilled is not None:
            return "spilled", spilled
        if not batches and first is not None:
            batches.append(first)  # keep schema carrier
        self.pool.reserve(held, "join build side")
        page = batches[0] if len(batches) == 1 else concat_pages(batches)
        return "device", (page, held)

    def _bucket_side_info(self, side: N.PlanNode):
        """(scan_node, wrappers, (bucket_cols, count)) when `side` is a
        Filter/Project chain over a TableScan of a BUCKETED table
        (reference: bucketed table detection feeding
        GROUPED_EXECUTION/Lifespan scheduling)."""
        wrappers = []
        n = side
        while isinstance(n, (N.Filter, N.Project)):
            wrappers.append(n)
            n = n.child
        if not isinstance(n, N.TableScan):
            return None
        bucketing = getattr(self.catalog, "bucketing", None)
        if bucketing is None:
            return None
        spec = bucketing(n.table)
        if spec is None:
            return None
        return n, tuple(reversed(wrappers)), spec

    def _grouped_join_spec(self, node: N.Join):
        """Detect a co-located bucket join: both sides bucketed with the
        same bucket count, and the equi-join keys are exactly the bucket
        columns (single-column buckets — the common spec)."""
        li = self._bucket_side_info(node.left)
        ri = self._bucket_side_info(node.right)
        if li is None or ri is None:
            return None
        (lscan, lwrap, (lcols, lcount)) = li
        (rscan, rwrap, (rcols, rcount)) = ri
        if lcount != rcount or len(lcols) != 1 or len(rcols) != 1:
            return None

        lsrc = {ch: col for ch, col, _ in lscan.columns}
        rsrc = {ch: col for ch, col, _ in rscan.columns}
        # the two bucket columns must be PAIRED at the same equi-key index:
        # checking each side independently would co-locate rows by
        # DIFFERENT keys (round-4 advisor: a crossed multi-key join — left
        # bucketed by k2, right by j1, on k1=j1 and k2=j2 — put matching
        # rows in different buckets and silently dropped them)
        paired = any(
            isinstance(lk, ir.ColumnRef)
            and isinstance(rk, ir.ColumnRef)
            and lsrc.get(lk.name) == lcols[0]
            and rsrc.get(rk.name) == rcols[0]
            for lk, rk in zip(node.left_keys, node.right_keys)
        )
        if not paired:
            return None
        return (lscan, lwrap), (rscan, rwrap), lcount

    def _stream_side_bucket(
        self, scan_node: N.TableScan, wrappers, bucket: int
    ) -> Iterator[Page]:
        """Batches of ONE bucket of a side, with its Filter/Project chain
        re-applied per batch."""
        cols = [col for _, col, _ in scan_node.columns]
        for lo, hi in self.catalog.bucket_row_ranges(scan_node.table, bucket):
            for s in range(lo, hi, self.batch_rows):
                src = self.catalog.scan(
                    scan_node.table, s, min(s + self.batch_rows, hi),
                    columns=cols,
                )
                page = self._rename_scan(scan_node, src)
                for w in wrappers:
                    page = self.local.exec_node(w, page)
                yield page

    def _grouped_bucket_join(self, node: N.Join, spec) -> Iterator[Page]:
        """Bucket-at-a-time execution (reference Lifespan.driverGroup +
        PipelineExecutionStrategy.GROUPED_EXECUTION): bucket i's build and
        probe run end-to-end before bucket i+1, bounding resident HBM to
        one bucket's build side."""
        (lscan, lwrap), (rscan, rwrap), count = spec
        right_names = tuple(n for n, _ in node.right.fields)
        for b in range(count):
            build_batches = [
                p
                for p in self._stream_side_bucket(rscan, rwrap, b)
                if int(host_read(p.count)) > 0
            ]
            if not build_batches:
                continue  # inner join: an empty build bucket matches nothing
            # a skewed bucket can still exceed the budget: probe it in
            # build sub-chunks (inner joins distribute over build chunks —
            # the same contract as the host-offload path)
            chunks: List[List[Page]] = [[]]
            held = 0
            for p in build_batches:
                nb = page_device_bytes(p)
                if chunks[-1] and not self.pool.can_reserve(held + nb):
                    chunks.append([])
                    held = 0
                chunks[-1].append(p)
                held += nb
            for chunk in chunks:
                build_page = (
                    chunk[0] if len(chunk) == 1 else concat_pages(chunk)
                )
                nb = page_device_bytes(build_page)
                self.pool.reserve(nb, f"bucket {b} build side")
                try:
                    yield from self._probe_stream(
                        node,
                        build_page,
                        right_names,
                        probe=self._stream_side_bucket(lscan, lwrap, b),
                    )
                finally:
                    self.pool.free(nb)

    def _index_join_spec(self, node: N.Join):
        """Index join (reference operator/index/ IndexLoader +
        IndexJoinOptimizer): when the build side is a bare TableScan of a
        connector that can serve point lookups on the single equi-key,
        fetch ONLY the build rows matching each probe batch's keys instead
        of scanning the build table."""
        if not isinstance(node.right, N.TableScan):
            return None
        if len(node.right_keys) != 1 or len(node.left_keys) != 1:
            return None
        rkey, lkey = node.right_keys[0], node.left_keys[0]
        if not isinstance(rkey, ir.ColumnRef) or not isinstance(
            lkey, ir.ColumnRef
        ):
            return None
        # block values are ENCODED (varchar = dictionary codes, date = day
        # offsets) — only integral keys survive the trip to remote SQL
        if not (T.is_integral(rkey.type) and T.is_integral(lkey.type)):
            return None
        scan = node.right
        src = {ch: col for ch, col, _ in scan.columns}
        col = src.get(rkey.name)
        supports = getattr(self.catalog, "supports_index", None)
        if col is None or supports is None or not supports(scan.table, col):
            return None
        # cost gate (reference IndexJoinOptimizer): point lookups beat a
        # build-side scan only when the build table is large relative to a
        # probe batch's worth of keys
        if self.catalog.row_count(scan.table) < 4 * self.batch_rows:
            return None
        return scan, col, lkey.name

    def _stream_index_join(self, node: N.Join, spec) -> Iterator[Page]:
        scan, index_col, probe_ch = spec
        right_names = tuple(n for n, _ in node.right.fields)
        cols = [col for _, col, _ in scan.columns]
        for batch in self.stream(node.left):
            blk = batch.block(probe_ch)
            m = int(host_read(batch.count))
            keys = host_read(blk.data[:m])
            if blk.valid is not None:
                keys = keys[host_read(blk.valid[:m])]
            keys = np.unique(keys)
            rows = self.catalog.index_lookup(
                scan.table, index_col, keys.tolist(), cols
            )
            build_page = self._rename_scan(scan, rows)
            yield from self._probe_stream(
                node, build_page, right_names, probe=iter([batch])
            )

    def _stream_join(self, node: N.Join) -> Iterator[Page]:
        if node.kind == "inner":
            idx = self._index_join_spec(node)
            if idx is not None:
                self.spill_events.append("index_join")
                yield from self._stream_index_join(node, idx)
                return
        # grouped execution covers INNER joins (a LEFT join with an empty
        # build bucket would need schema-only null extension)
        grouped = (
            self._grouped_join_spec(node) if node.kind == "inner" else None
        )
        if grouped is not None:
            self.spill_events.append("grouped_bucket_join")
            yield from self._grouped_bucket_join(node, grouped)
            return
        kind, side = self._collect_side(node.right)
        right_names = tuple(n for n, _ in node.right.fields)
        if kind == "device":
            right_page, held = side
            if getattr(node, "dynamic_filters", ()):
                # the build side is complete: derive + publish BEFORE the
                # probe stream's scan generators start pulling batches
                self.local._publish_dynamic_filters(node, right_page)
            try:
                yield from self._probe_stream(node, right_page, right_names)
            finally:
                self.pool.free(held)
            return
        # offloaded build: partitioned hybrid hash join — INNER only
        if node.kind != "inner":
            raise MemoryExceededError(
                "outer join build side exceeds the device budget "
                "(chunked execution covers inner joins)"
            )
        spilled = side
        if getattr(node, "dynamic_filters", ()):
            self._publish_host_filters(node, spilled)
        from .breaker import BREAKERS

        if BREAKERS.allow("hybrid_join") and not self._hybrid_unsafe_keys(
            node, spilled
        ):
            try:
                # partitioning + resident-build SETUP runs before the
                # probe stream is touched: a fault here falls back
                # CLEANLY to the chunked path (no probe page consumed or
                # acked, no row emitted). Spill-tier errors stay fatal —
                # retrying cannot outrun a quota or a corrupt file.
                setup = self._hybrid_setup(node, spilled)
            except MemoryExceededError:
                raise
            except Exception as exc:  # noqa: BLE001 - degrade, don't fail
                from .spillspace import SpillError

                if isinstance(exc, SpillError):
                    raise
                BREAKERS.record_failure("hybrid_join", repr(exc))
            else:
                # once the probe pass starts its pages may be consumed
                # (and exchange-acked): no silent fallback — a fault
                # propagates, the breaker records it, and the NEXT
                # attempt takes the chunked path
                try:
                    yield from self._hybrid_hash_join(
                        node, spilled, right_names, setup
                    )
                except (MemoryExceededError, GeneratorExit):
                    raise
                except Exception as exc:  # noqa: BLE001
                    from .spillspace import SpillError

                    if not isinstance(exc, SpillError):
                        BREAKERS.record_failure("hybrid_join", repr(exc))
                    raise
                BREAKERS.record_success("hybrid_join")
                return
        self.spill_stats["chunk_fallbacks"] += 1
        yield from self._chunked_host_join(node, spilled, right_names)

    def _hybrid_unsafe_keys(self, node: N.Join, spilled) -> bool:
        """Hash partitioning requires build/probe key hashes to agree for
        equal VALUES. Varchar keys used to be routed to the chunked path
        categorically (dictionary codes hash per-table); PR 11 rehashes
        them by dictionary VALUE (ops/hashing.hash_rows_values), so
        varchar equi-joins take the partitioned/kernel path whenever the
        build-side dictionaries admit the one-time value-hash pass. Only
        a dictionary beyond PRESTO_TPU_VALUE_HASH_MAX_DICT (or one we
        cannot inspect) still forces the chunked path.

        Scope: only BUILD-side dictionaries are inspectable before the
        probe stream starts. A probe batch arriving later with an
        over-cap dictionary still hashes CORRECTLY (hash_rows_values
        computes whatever value table it needs, cached per dict_id) —
        the cap bounds predictable cost, it is not a correctness gate."""
        if not any(
            isinstance(getattr(k, "type", None), T.VarcharType)
            for k in tuple(node.left_keys) + tuple(node.right_keys)
        ):
            return False
        from ..expr.compiler import evaluate
        from ..ops.hashing import value_hashable

        try:
            sample = spilled.take_page(
                np.arange(min(spilled.num_rows, 1))
            )
            keys = [evaluate(e, sample) for e in node.right_keys]
        except Exception as exc:  # noqa: BLE001 — uninspectable: chunked
            self.spill_events.append(f"hybrid_varchar_probe_failed:{exc!r}")
            return True
        return not value_hashable(keys)

    def _chunked_host_join(self, node: N.Join, spilled, right_names):
        """Legacy offloaded-build execution (the hybrid join's circuit-
        breaker fallback): upload budget-sized build chunks, re-stream the
        whole probe against each (inner joins distribute over build
        chunks)."""
        share = self._spill_share()
        rows_per_chunk = max(int(share // max(spilled.row_bytes, 1)), 1)
        n = spilled.num_rows
        for start in range(0, max(n, 1), rows_per_chunk):
            stop = min(start + rows_per_chunk, n)
            chunk = spilled.take_page(np.arange(start, max(stop, start)))
            nb = page_device_bytes(chunk)
            self.pool.reserve(nb, "join build chunk")
            try:
                yield from self._probe_stream(node, chunk, right_names)
            finally:
                self.pool.free(nb)

    def _hybrid_partition_count(self, total_bytes: int, share: int,
                                cap: int = 64, node=None) -> int:
        import os

        env = int(os.environ.get("PRESTO_TPU_HYBRID_JOIN_PARTS", "0"))
        if env > 0:
            return env  # manual override beats both heuristics
        # 2x headroom per partition (arXiv:2112.02480: over-partitioning
        # is cheap, under-partitioning forces recursion)
        P = min(max(-(-total_bytes * 2 // max(share, 1)), 2), cap)
        if node is not None:
            P = self._hybrid_history_parts(node, P, cap)
        return P

    def _hybrid_history_parts(self, node: N.Join, P: int, cap: int) -> int:
        """History-based sizing (plan/history.py): a join frame that
        previously recursed with P0 partitions wants ~P0 * 2^depth up
        front — recursion repartitions the SAME rows on fresh hash bits,
        so pre-scaling buys the one-pass layout the byte estimate
        undersized. Never shrinks below the byte-derived count."""
        try:
            from ..plan.history import HISTORY, feedback_on, fingerprint

            if not feedback_on():
                return P
            ent = HISTORY.lookup(fingerprint(node), self.catalog)
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            from .breaker import BREAKERS

            BREAKERS.record_failure("adaptive_plan", repr(exc))
            return P
        if ent is None or not ent.hybrid_parts:
            return P
        want = ent.hybrid_parts << max(int(ent.hybrid_depth), 0)
        return min(max(P, want), cap)

    def _hybrid_setup(self, node: N.Join, spilled) -> dict:
        """Eager setup phase of the hybrid hash join: hash-partition the
        build side, choose the resident set, and upload + build it. Runs
        BEFORE the probe stream is touched, so a fault here can fall back
        to the chunked path cleanly (nothing consumed, nothing acked,
        nothing emitted)."""
        import os

        from .spill import hash_partition_indices

        share = self._spill_share()
        row_b = max(spilled.row_bytes, 1)
        total_bytes = spilled.num_rows * row_b
        P = self._hybrid_partition_count(total_bytes, share, node=node)
        chunk_rows = max(share // (2 * row_b), 1 << 10)
        parts = hash_partition_indices(
            spilled, node.right_keys, P, chunk_rows, salt=0,
            value_safe=True,
        )
        # resident set: smallest partitions first, up to half the share
        # (the other half belongs to probe batches / output pages)
        resident: List[int] = []
        acc = 0
        for p in sorted(range(P), key=lambda q: len(parts[q])):
            nb = len(parts[p]) * row_b
            if len(parts[p]) and acc + nb <= share // 2:
                resident.append(p)
                acc += nb
        resident_set = frozenset(resident)
        deferred = [
            p for p in range(P)
            if p not in resident_set and len(parts[p])
        ]
        # ragged paged layout over the DEFERRED partitions (ops/ragged.py
        # — the ones handed to kernels later): skewed partitions allocate
        # unequal page counts instead of padding to the max, and the
        # occupancy lands in EXPLAIN ANALYZE's memory line. The layout
        # TAKES OVER the deferred row-id arrays (their `parts` entries
        # are dropped) so the memory-pressure path holds one copy, not
        # two; resident partitions never need pages.
        from ..ops import ragged as _ragged

        deferred_set = frozenset(deferred)
        rp = _ragged.from_partitions(
            [
                parts[p] if p in deferred_set else np.empty(0, np.int64)
                for p in range(P)
            ]
        )
        for p in deferred:
            parts[p] = None  # owned by the ragged layout now
        self.spill_stats["ragged_pages"] += rp.num_pages
        if rp.num_pages:
            self.spill_stats["ragged_occupancy_pct"] = int(
                rp.occupancy() * 100
            )
        bs_mem = None
        mem_held = 0
        if resident:
            idx = np.concatenate([parts[p] for p in sorted(resident)])
            mem_page = spilled.take_page(idx)
            mem_held = page_device_bytes(mem_page)
            self.pool.reserve(mem_held, "hybrid join resident build")
            try:
                bs_mem = build_sorted(mem_page, node.right_keys)
            except BaseException:
                self.pool.free(mem_held)
                raise
        res_np = np.zeros(P, np.bool_)
        res_np[resident] = True
        return {
            "P": P,
            "chunk_rows": chunk_rows,
            "parts": parts,
            "ragged": rp,
            "deferred": deferred,
            "bs_mem": bs_mem,
            "mem_held": mem_held,
            "res_np": res_np,
            "max_depth": int(
                os.environ.get("PRESTO_TPU_HYBRID_JOIN_MAX_DEPTH", "3")
            ),
        }

    def _hybrid_hash_join(self, node: N.Join, spilled, right_names, setup):
        """Partitioned hybrid hash join over an offloaded build side
        (reference HashBuilderOperator SPILLING_INPUT +
        GenericPartitioningSpiller; design trade-offs per
        arXiv:2112.02480): hash-partition build AND probe, keep the
        partitions that fit on device and probe them in ONE pass over the
        probe stream, spill the rest of the probe, then join each
        deferred (build, probe) partition pair — recursively
        repartitioning oversized partitions on fresh hash bits up to
        PRESTO_TPU_HYBRID_JOIN_MAX_DEPTH, after which an all-ties
        partition degrades to the chunked build loop."""
        from ..expr.compiler import evaluate
        from ..ops.filter import compact
        from ..ops.hashing import hash_rows_values
        from .spill import SpilledRows, hash_partition_indices, to_host_page

        P = setup["P"]
        chunk_rows = setup["chunk_rows"]
        parts = setup["parts"]
        deferred = setup["deferred"]
        bs_mem = setup["bs_mem"]
        mem_held = setup["mem_held"]
        max_depth = setup["max_depth"]
        self.spill_events.append("hybrid_hash_join")
        self.spill_stats["hybrid_parts"] = max(
            self.spill_stats["hybrid_parts"], P
        )
        depth_before = self.spill_stats["hybrid_depth"]
        res_lut = jnp.asarray(setup["res_np"])
        probe_spill = (
            SpilledRows(space=self._spill(), tag="hybrid_probe")
            if deferred else None
        )
        preprobe = getattr(node, "dynamic_filters", ()) and any(
            not consumed for _f, _i, consumed in node.dynamic_filters
        )
        first_probe: Optional[Page] = None
        yielded = False
        try:
            # ONE pass over the probe: resident partitions join now,
            # deferred partitions' rows spill alongside the build
            for batch in self.stream(node.left):
                if preprobe:
                    batch = self.local._apply_preprobe(node, batch)
                if first_probe is None:
                    first_probe = batch
                keys = [evaluate(e, batch) for e in node.left_keys]
                # value-safe: must agree with the build-side partitioning
                # for equal VALUES (varchar dictionaries differ per side)
                h = hash_rows_values(keys)
                part = (h % jnp.uint64(P)).astype(jnp.int32)
                live = batch.live_mask()
                if bs_mem is not None:
                    mem_batch = compact(batch, res_lut[part] & live)
                    if int(host_read(mem_batch.count)) > 0:
                        for out in self._probe_with(
                            node, bs_mem, right_names, iter([mem_batch])
                        ):
                            yielded = True
                            yield out
                if probe_spill is not None:
                    d_batch = compact(batch, (~res_lut[part]) & live)
                    if int(host_read(d_batch.count)) > 0:
                        probe_spill.append(to_host_page(d_batch))
        finally:
            if mem_held:
                self.pool.free(mem_held)
        bs_mem = None
        if probe_spill is not None and probe_spill.num_rows:
            pparts = hash_partition_indices(
                probe_spill, node.left_keys, P, chunk_rows, salt=0,
                value_safe=True,
            )
            ragged = setup["ragged"]
            for p in deferred:
                if not len(pparts[p]):
                    continue
                for out in self._join_partition(
                    node, spilled.subset(ragged.part_rows(p)),
                    probe_spill.subset(pparts[p]), right_names, 0,
                    chunk_rows, max_depth,
                ):
                    yielded = True
                    yield out
        if not yielded and first_probe is not None:
            # schema carrier: join one probe batch against an empty build
            # so downstream sinks always see the output schema. A probe
            # stream that yielded NOTHING (possible for an exchange source
            # whose producer finished empty) has no carrier to offer —
            # and nothing downstream to feed either.
            empty = spilled.take_page(np.empty(0, np.int64))
            yield from self._probe_with(
                node, build_sorted(empty, node.right_keys), right_names,
                iter([first_probe]),
            )
        self._record_hybrid_outcome(node, P, depth_before)

    def _record_hybrid_outcome(self, node: N.Join, P: int,
                               depth_before: int) -> None:
        """Remember how this join frame actually partitioned (the
        feedback half of _hybrid_history_parts). spill_stats tracks the
        query-wide max depth, so only depth growth since THIS join
        started is attributable to it."""
        try:
            from ..plan.history import HISTORY, feedback_on, fingerprint
            from .qcache import plan_tables

            if not feedback_on():
                return
            d = self.spill_stats["hybrid_depth"]
            HISTORY.record(
                fingerprint(node), catalog=self.catalog,
                tables=plan_tables(node),
                hybrid=(P, d - depth_before if d > depth_before else 0),
                kind="Join",
            )
        except Exception as exc:  # noqa: BLE001 — bookkeeping only
            from .breaker import BREAKERS

            BREAKERS.record_failure("adaptive_plan", repr(exc))

    def _join_partition(self, node: N.Join, build_sub, probe_sub,
                        right_names, depth: int, chunk_rows: int,
                        max_depth: int):
        """Join one deferred (build, probe) partition pair: upload the
        build whole when it fits, recursively repartition on fresh hash
        bits when it doesn't, and fall back to the chunked build loop
        when partitioning stops making progress (all-ties keys) or the
        depth bound is hit."""
        from .spill import hash_partition_indices

        share = self._spill_share()
        row_b = max(build_sub.row_bytes, 1)
        bbytes = build_sub.num_rows * row_b
        if bbytes * 2 <= share or build_sub.num_rows <= 1:
            page = build_sub.take_page(np.arange(build_sub.num_rows))
            nb = page_device_bytes(page)
            self.pool.reserve(nb, "hybrid join partition build")
            try:
                bs = build_sorted(page, node.right_keys)
                yield from self._probe_with(
                    node, bs, right_names,
                    self._spilled_pages(probe_sub, chunk_rows),
                )
            finally:
                self.pool.free(nb)
            return
        if depth < max_depth:
            P2 = self._hybrid_partition_count(bbytes, share, cap=16)
            salt = 7 * (depth + 1)  # fresh hash bits each level
            bparts = hash_partition_indices(
                build_sub, node.right_keys, P2, chunk_rows, salt=salt,
                value_safe=True,
            )
            if max(len(i) for i in bparts) < build_sub.num_rows:
                # made progress: recurse on each co-partition pair
                self.spill_stats["hybrid_depth"] = max(
                    self.spill_stats["hybrid_depth"], depth + 1
                )
                pparts = hash_partition_indices(
                    probe_sub, node.left_keys, P2, chunk_rows, salt=salt,
                    value_safe=True,
                )
                for p in range(P2):
                    if len(bparts[p]) and len(pparts[p]):
                        yield from self._join_partition(
                            node, build_sub.subset(bparts[p]),
                            probe_sub.subset(pparts[p]), right_names,
                            depth + 1, chunk_rows, max_depth,
                        )
                return
        # all-ties partition (one key value defeats every hash) or depth
        # exhausted: inner joins distribute over build chunks
        self.spill_stats["chunk_fallbacks"] += 1
        rows_per = max(int((share // 2) // row_b), 1)
        n = build_sub.num_rows
        for s in range(0, n, rows_per):
            page = build_sub.take_page(np.arange(s, min(s + rows_per, n)))
            nb = page_device_bytes(page)
            self.pool.reserve(nb, "hybrid join build chunk")
            try:
                bs = build_sorted(page, node.right_keys)
                yield from self._probe_with(
                    node, bs, right_names,
                    self._spilled_pages(probe_sub, chunk_rows),
                )
            finally:
                self.pool.free(nb)

    @staticmethod
    def _spilled_pages(spilled, chunk_rows: int):
        """Device pages of a spilled store, chunk-by-chunk."""
        n = spilled.num_rows
        step = max(chunk_rows, 1)
        for start in range(0, n, step):
            yield spilled.take_page(np.arange(start, min(start + step, n)))

    def _publish_host_filters(self, node: N.Join, spilled) -> None:
        """Derive filters from an offloaded build side (numpy columns,
        host or disk tier; the spilled-build analog of
        _publish_dynamic_filters)."""
        from ..expr import ir as _ir
        from .breaker import BREAKERS
        from .dynfilter import HostFilterAccumulator, filter_from_summary

        if not self.local._dyn_enabled() or not self.local._dyn_worthwhile(
            node
        ):
            return
        for fid, i, _c in node.dynamic_filters:
            key = node.right_keys[i]
            df = None
            try:
                acc = HostFilterAccumulator(key.name)
                key_type = None
                for chunk in spilled.iter_host_chunks():
                    if not isinstance(key, _ir.ColumnRef) or (
                        key.name not in chunk.names
                    ):
                        acc = None
                        break
                    idx = chunk.names.index(key.name)
                    key_type = chunk.types[idx]
                    acc.add_numpy(
                        chunk.columns[idx], chunk.valids[idx], key_type
                    )
                if acc is None:
                    continue
                df = filter_from_summary(acc.summary(), key_type)
            except Exception as exc:  # noqa: BLE001 — degrade, don't fail
                BREAKERS.record_failure("dynamic_filter", repr(exc))
                return
            if df is not None:
                BREAKERS.record_success("dynamic_filter")
                self.local.dyn_ctx.publish(fid, df)

    def _probe_stream(
        self, node: N.Join, right_page: Page, right_names, probe=None
    ) -> Iterator[Page]:
        bs = build_sorted(right_page, node.right_keys)
        preprobe = getattr(node, "dynamic_filters", ()) and any(
            not consumed for _f, _i, consumed in node.dynamic_filters
        )

        def batches():
            for batch in (
                probe if probe is not None else self.stream(node.left)
            ):
                if preprobe:
                    yield self.local._apply_preprobe(node, batch)
                else:
                    yield batch

        yield from self._probe_with(node, bs, right_names, batches())

    def _probe_with(
        self, node: N.Join, bs, right_names, batches
    ) -> Iterator[Page]:
        """Probe pre-filtered batches against a prepared BuildSide (the
        shared probe loop of the device, chunked, and hybrid join paths)."""
        for batch in batches:
            if node.unique_build:
                out = join_n1(
                    batch, bs, node.left_keys, right_names, right_names,
                    kind=node.kind,
                )
            else:
                cap = round_capacity(max(int(host_read(batch.count)), 1))
                while True:
                    out, overflow = join_expand(
                        batch,
                        bs,
                        node.left_keys,
                        batch.names,
                        [(nm, nm) for nm in right_names],
                        out_capacity=cap,
                        kind=node.kind,
                    )
                    if int(host_read(overflow)) == 0:
                        break
                    cap = round_capacity(cap + int(host_read(overflow)))
            if node.residual is not None:
                out = filter_page(out, node.residual)
            yield self.local._shrink(out)

    def _stream_window(self, node: N.Window) -> Iterator[Page]:
        """Partitioned window under the budget: if the input fits, one
        device window kernel; otherwise partition-chunked execution — rows
        hash-bucketed on the PARTITION BY keys (a window function never
        reads across partitions), one device window kernel per bucket
        (reference: grouped execution via Lifespan + the spilling
        WindowOperator). Output keeps within-bucket (partition, order)
        ordering; bucket order is a hash order, which the SQL contract
        allows (a Sort node above imposes any required final order)."""
        from .spill import hash_partition_indices

        first, batches, held, spilled = self._collect_or_spill(
            node.child, "window"
        )
        if spilled is None:
            if not batches:
                yield self.local.exec_node(node, first)
                return
            self.pool.reserve(held, "window input")
            try:
                acc = batches[0] if len(batches) == 1 else concat_pages(batches)
                yield self.local.exec_node(node, acc)
            finally:
                self.pool.free(held)
            return
        chunk_rows = max(self._spill_share() // spilled.row_bytes, 1 << 10)
        num_parts = max(-(-spilled.num_rows // chunk_rows), 2)
        for idx in hash_partition_indices(
            spilled, node.partition_exprs, num_parts, chunk_rows
        ):
            if not len(idx):
                continue
            page = spilled.take_page(idx)
            nb = page_device_bytes(page)
            self.pool.reserve(nb, "window partition bucket")
            try:
                yield self.local.exec_node(node, page)
            finally:
                self.pool.free(nb)

    def _stream_semijoin(self, node: N.SemiJoin) -> Iterator[Page]:
        source = self._run(node.source)
        if getattr(node, "dynamic_filters", ()):
            self.local._publish_dynamic_filters(node, source)
        preprobe = getattr(node, "dynamic_filters", ()) and any(
            not consumed for _f, _i, consumed in node.dynamic_filters
        )
        held = self.pool.reserve(page_device_bytes(source), "semijoin source")
        try:
            bs = build_sorted(source, node.source_keys)
            for batch in self.stream(node.child):
                if preprobe:
                    batch = self.local._apply_preprobe(node, batch)
                if node.mark is not None:
                    from ..ops.join import semi_match_mask

                    mask = semi_match_mask(batch, bs, node.probe_keys)
                    yield self.local._attach_mark(batch, mask, node.mark)
                    continue
                out = join_n1(
                    batch, bs, node.probe_keys, [], [],
                    kind="anti" if node.anti else "semi",
                )
                yield self.local._shrink(out)
        finally:
            self.pool.free(held)

    # -- sinks ----------------------------------------------------------------

    def _hash_agg_attempt(
        self, page: Page, group_exprs, group_names, aggs, mask
    ) -> Optional[Page]:
        """Hash-slot grouped aggregation attempt for the streaming sink's
        partial/merge passes (ops/pallas_groupby.maybe_grouped_aggregate_hash
        behind the pallas_groupby_hash breaker); None falls back to the
        sort composition. Output schema matches grouped_aggregate_sorted,
        so partial pages from both strategies merge freely."""
        from ..ops.pallas_groupby import maybe_grouped_aggregate_hash
        from .breaker import BREAKERS

        if not BREAKERS.allow("pallas_groupby_hash"):
            return None
        try:
            out = maybe_grouped_aggregate_hash(
                page, group_exprs, group_names, aggs, mask
            )
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            BREAKERS.record_failure("pallas_groupby_hash", repr(exc))
            return None
        if out is not None:
            BREAKERS.record_success("pallas_groupby_hash")
            self.spill_stats["agg_hash_batches"] += 1
        return out

    def _pallas_agg_attempt(
        self, partial_node: N.Aggregate, batch: Page
    ) -> Optional[Page]:
        """A batch's partial aggregation as ONE program: the dense
        small-G Pallas group-by, the resident path's first strategy
        (`Executor._try_pallas_groupby`: behind the pallas_groupby
        breaker, default on for the TPU, the mask's literals operands of
        the program). None where it is off or the shape is ineligible;
        the hash-slot attempt and the sort composition follow. Without
        it a streamed Q1 at SF10 read 73 MB of every 2^20-row batch back
        to the host for the hash-slot path and took 24-27 s (PERF.md
        section 6, PR 35)."""
        if not self.local._pallas_groupby_on():
            return None
        return self.local._try_pallas_groupby(partial_node, batch)

    def _agg_input_stream(self, node: N.Aggregate) -> Iterator[Page]:
        """Child batches for a (possibly filter-fused) aggregation; a fused
        mask over a direct table scan still pushes pruning hints down."""
        if node.mask is not None and isinstance(node.child, N.TableScan):
            return self._spanned(node.child, self._stream_scan(
                node.child, predicate=_pushdown_hints(node.mask, node.child)
            ))
        return self.stream(node.child)

    def _sink_aggregate(self, node: N.Aggregate) -> Page:
        try:
            partial, final, post = decompose_partial(node.aggs)
        except KeyError:
            # non-decomposable (min_by/max_by): aggregate the materialized
            # input in one pass (same choice the fragmenter makes)
            return self._exec_fallback(node)
        # the node as the per-batch partial aggregation runs it
        partial_node = dataclasses.replace(node, aggs=tuple(partial))
        final_aggs, posts = tuple(final), tuple(post)
        if not node.group_exprs:
            # a batch's step is ONE cached program, enqueued, and no
            # read: the resident path's `jit_global_aggregate`, one shape
            # for every batch (the scan pads the last). Called unjitted
            # this was ~60 eager launches a batch, and a streamed Q6 at
            # SF10 was slower than the Q1 that moves 1.6x its bytes
            # (PERF.md section 6, PR 36). The scan's count read keeps the
            # host one batch ahead of its uploads at most: a sink that
            # loses that read has to bound the run-ahead itself
            partials = [
                self.local._exec_aggregate(partial_node, batch)
                for batch in self._agg_input_stream(node)
            ]
            finish = self.local._kernel(
                "global_aggregate_final",
                ("global_aggregate_final", final_aggs, node.aggs, posts),
                lambda: lambda parts: apply_avg_post(
                    global_aggregate(concat_pages(parts), final_aggs),
                    node.aggs, posts,
                ),
            )
            out = finish(partials)
            self.local._span_note(
                partial_strategy="global", merges=1, partial_reads=0,
                pool_peak_bytes=self.pool.peak,
                **({"programs": 2} if self.local.jit else {}),
            )
            return out

        group_refs = tuple(
            ir.ColumnRef(nm, e.type)
            for nm, e in zip(node.group_names, node.group_exprs)
        )
        state: Optional[Page] = None
        state_held = 0
        merge_rows = max(self.batch_rows // 2, 1 << 14)
        pending: List[Page] = []
        pending_rows = pending_bytes = 0
        spilled = None  # SpilledRows of partial-state pages
        # what the sink did, for its span: host-held values only
        hash_before = self.spill_stats["agg_hash_batches"]
        strategies = set()
        merges = 0
        partial_reads = 0  # blocking reads of a partial page's count
        programs = set()  # the cached programs the sink dispatched

        def merge(parts: List[Page], last: bool = False) -> Page:
            """`final` over the accumulated partial pages; the answer's
            columns (`apply_avg_post`) too where it is the `last`."""
            nonlocal merges
            merges += 1
            slots = sum(p.capacity for p in parts)
            if slots <= SMALL_PAGE_ROWS:
                # few slots (the 58 partials of a streamed Q1 at SF10
                # hold 348): ONE cached program, at slots its groups
                # cannot outgrow, so no retry and no read. Dispatched
                # step by step this was ~1,700 launches of that Q1's 1,775
                mg = round_capacity(slots)

                def merged(pages: List[Page]) -> Page:
                    out = grouped_aggregate_sorted(
                        concat_pages(pages), group_refs, node.group_names,
                        final_aggs, mg,
                    )
                    return apply_avg_post(out, node.aggs, posts) if last else out

                fn = self.local._kernel(
                    "merge_partials",
                    ("merge_partials", group_refs, node.group_names,
                     final_aggs, mg, (node.aggs, posts) if last else None),
                    lambda: merged,
                )
                programs.add(("merge_partials", last))
                return fn(parts)
            acc = parts[0] if len(parts) == 1 else concat_pages(parts)
            out = self._hash_agg_attempt(
                acc, group_refs, node.group_names, final, None
            )
            if out is None:
                bound = pending_rows + (
                    int(host_read(state.count)) if state is not None else 0
                )
                mg = round_capacity(min(max(bound, 1), 1 << 22))
                while True:
                    out = grouped_aggregate_sorted(
                        acc, group_refs, node.group_names, final, mg
                    )
                    true_groups = int(host_read(out.count))
                    if true_groups <= mg:
                        break
                    mg = round_capacity(true_groups)
            out = self.local._shrink(out)
            return apply_avg_post(out, node.aggs, post) if last else out

        def spill_all(pages: List[Page]) -> None:
            """Move partial-state pages to the spill store (re-finalizable:
            `final` over partial columns is idempotent, so spilled merged
            state and raw partials share one schema)."""
            nonlocal spilled
            from .spill import SpilledRows

            if spilled is None:
                self.spill_events.append("aggregate")
                spilled = SpilledRows(space=self._spill(), tag="aggregate")
            for p in pages:
                if int(host_read(p.count)) > 0 or spilled.num_rows == 0:
                    spilled.append(p)

        # state_held rotates through the loop; the finally releases
        # whatever is still reserved when a kernel faults or a
        # MemoryExceededError fires mid-stream (found by prestolint
        # memory-accounting: a leaked reservation here permanently
        # shrinks the worker's admission budget until task cleanup).
        # Normal paths zero state_held as they free so the finally is a
        # no-op for them.
        try:
            for batch in self._agg_input_stream(node):
                part = self._pallas_agg_attempt(partial_node, batch)
                if part is not None:
                    strategies.add("pallas")
                    programs.add("grouped_aggregate_pallas")
                else:
                    part = self._hash_agg_attempt(
                        batch, node.group_exprs, node.group_names, partial,
                        node.mask,
                    )
                    strategies.add("sort" if part is None else "hash")
                # the partial's rows as far as the host holds them: the
                # count, where the sort strategy's retry loop has read it
                part_rows = None
                if part is None:
                    mg = round_capacity(
                        min(max(int(host_read(batch.count)), 1), 1 << 16)
                    )
                    while True:
                        part = grouped_aggregate_sorted(
                            batch, node.group_exprs, node.group_names,
                            partial, mg, node.mask,
                        )
                        part_rows = int(host_read(part.count))
                        partial_reads += 1
                        if part_rows <= mg:
                            break
                        mg = round_capacity(part_rows)
                part = self.local._shrink(part)
                if spilled is not None:
                    spill_all([part])
                    continue
                pending.append(part)
                if part_rows is None and part.capacity > SMALL_PAGE_ROWS:
                    part_rows = int(host_read(part.count))
                    partial_reads += 1
                elif part_rows is None:
                    # `pending_rows` only says WHEN to merge and bounds
                    # the merge's slots, and a page's capacity bounds its
                    # count: no read for a page `_shrink` would not read
                    # either. The dense kernel's partial has at most 64
                    # slots, and its read held the host until the batch
                    # had crossed the link and the kernel had run
                    part_rows = part.capacity
                pending_rows += part_rows
                pending_bytes += page_device_bytes(part)
                self.pool.accumulated = pending_bytes
                if pending_rows >= merge_rows or not self.pool.can_accumulate(
                    pending_bytes
                ):
                    parts = ([state] if state is not None else []) + pending
                    new_state = merge(parts)
                    self.pool.free(state_held)
                    state_held = 0
                    nb = page_device_bytes(new_state)
                    if self.pool.can_accumulate(nb):
                        state_held = self.pool.reserve(nb, "aggregation state")
                        state = new_state
                    else:
                        # group state outgrew the budget (or a revoke asked
                        # for it back): switch to spilling
                        # (SpillableHashAggregationBuilder.spillToDisk)
                        spill_all([new_state])
                        self.pool.note_revoked(nb)
                        state = None
                    pending = []
                    pending_rows = pending_bytes = 0
                    self.pool.accumulated = 0
            self.pool.accumulated = 0
            if spilled is not None:
                spill_all(pending)
                return self._finalize_spilled_agg(
                    node, spilled, group_refs, final, post
                )
            # stream() always yields at least one batch: parts is non-empty
            parts = ([state] if state is not None else []) + pending
            est = sum(page_device_bytes(p) for p in parts)
            if not self.pool.can_reserve(est - state_held):
                # the final merged state itself would not fit: finish on
                # the spill path, which emits a host-backed result
                spill_all(parts)
                self.pool.free(state_held)
                state_held = 0
                return self._finalize_spilled_agg(
                    node, spilled, group_refs, final, post
                )
            out = merge(parts, last=True)
            self.pool.free(state_held)
            state_held = 0
            return out
        finally:
            self.local._span_note(
                partial_strategy="+".join(sorted(strategies)),
                agg_hash_batches=(
                    self.spill_stats["agg_hash_batches"] - hash_before
                ),
                merges=merges, spilled=spilled is not None,
                partial_reads=partial_reads,
                pool_peak_bytes=self.pool.peak,
                **(
                    {"programs": len(programs)}
                    if programs and self.local.jit else {}
                ),
            )
            if state_held:
                self.pool.free(state_held)
            # pending partials are dropped with the exception — without
            # this the pool keeps reporting their bytes as revocable and
            # the revoking scheduler keeps picking a dead query whose
            # revoke can never complete
            self.pool.accumulated = 0

    def _finalize_spilled_agg(
        self, node: N.Aggregate, spilled, group_refs, final, post
    ) -> Page:
        """Final aggregation over host-spilled partial states: hash-
        partition rows by group key (equal keys share a partition), run the
        device final aggregation per partition, concatenate on the host.
        Skewed partitions re-partition recursively on fresh hash bits."""
        from .spill import (
            hash_partition_indices,
            host_concat_pages,
            to_host_page,
        )

        outs: List[Page] = []
        chunk_rows = max(self._spill_share() // spilled.row_bytes, 1 << 10)

        def finalize(sub, depth: int) -> None:
            n = sub.num_rows
            if n > chunk_rows and depth < 4:
                parts = max(-(-n // chunk_rows), 2)
                for idx in hash_partition_indices(
                    sub, group_refs, parts, chunk_rows, salt=13 * (depth + 1)
                ):
                    if len(idx):
                        finalize(sub.subset(idx), depth + 1)
                return
            # one partition's groups fit (or hashing cannot split further:
            # upload regardless and let the pool fail honestly)
            page = sub.take_page(np.arange(n))
            nb = page_device_bytes(page)
            self.pool.reserve(nb, "final aggregation partition")
            try:
                mg = round_capacity(max(int(host_read(page.count)), 1))
                while True:
                    out = grouped_aggregate_sorted(
                        page, group_refs, node.group_names, final, mg
                    )
                    if int(host_read(out.count)) <= mg:
                        break
                    mg = round_capacity(int(host_read(out.count)))
                out = apply_avg_post(out, node.aggs, post)
                outs.append(to_host_page(out))
            finally:
                self.pool.free(nb)

        finalize(spilled, 0)
        return host_concat_pages(outs)

    def _sink_distinct(self, node: N.Distinct) -> Page:
        state: Optional[Page] = None
        for batch in self.stream(node.child):
            d = distinct_page(batch, batch.capacity)
            if state is None:
                state = d
            else:
                merged = concat_pages([state, d])
                state = distinct_page(merged, merged.capacity)
            state = self.local._shrink(state)
        return state if state is not None else next(self.stream(node.child))

    def _sink_topn(self, node: N.TopN) -> Page:
        state: Optional[Page] = None
        for batch in self.stream(node.child):
            t = top_n(batch, node.keys, node.count)
            if state is None:
                state = t
            else:
                state = top_n(concat_pages([state, t]), node.keys, node.count)
        return state if state is not None else next(self.stream(node.child))

    def _sink_limit(self, node: N.Limit) -> Page:
        got: List[Page] = []
        rows = 0
        for batch in self.stream(node.child):
            got.append(batch)
            rows += int(host_read(batch.count))
            if rows >= node.count:
                break  # short-circuit: stop pulling the scan
        if not got:
            got = [next(self.stream(node.child))]
        acc = got[0] if len(got) == 1 else concat_pages(got)
        return self.local._shrink(limit_page(acc, node.count))

    def _sink_sort(self, node: N.Sort) -> Page:
        """Full-table sort; beyond the budget it goes external: offload to
        host, range-partition on the first key, device-sort each range
        (spill.external_sort_chunks — the OrderByOperator-spill analog)."""
        from .spill import external_sort_chunks, host_concat_pages

        first, batches, held, spilled = self._collect_or_spill(
            node.child, "sort"
        )
        if spilled is None:
            if not batches:
                return sort_page(first, node.keys)
            self.pool.reserve(held, "sort input")
            try:
                acc = batches[0] if len(batches) == 1 else concat_pages(batches)
                return sort_page(acc, node.keys)
            finally:
                self.pool.free(held)
        chunk_rows = max(self._spill_share() // spilled.row_bytes, 1 << 10)
        chunks = external_sort_chunks(spilled, node.keys, chunk_rows, self.pool)
        return host_concat_pages(chunks)
