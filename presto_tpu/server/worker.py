"""Worker server: task execution + pull-based output buffers over HTTP.

Re-designed equivalent of the reference's worker surface (SURVEY L6 + L8):
TaskResource (`POST /v1/task/{id}`, server/TaskResource.java:120),
SqlTaskExecution running a PlanFragment, partitioned output buffers
(execution/buffer/PartitionedOutputBuffer) and the pull protocol
`GET /v1/task/{id}/results/{bufferId}/{token}` (TaskResource.java:239).

This is the DCN path of the communication backend (SURVEY §2.7): pages
move between processes as serde bytes over HTTP; the in-process shard_map
path (exec/dist.py) remains the ICI path within one slice. A task's
fragment is a pickled plan subtree whose exchange inputs appear as
RemoteSource placeholders resolved by pulling upstream buffers.
"""

from __future__ import annotations

import base64
import dataclasses
import json
import pickle
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import types as T
from ..exec.executor import Executor
from ..exec.stream import StreamingExecutor
from ..ops.union import concat_pages
from ..page import Block, Page
from ..plan import nodes as N
from . import knobs
from .serde import serialize_page


@dataclasses.dataclass(frozen=True)
class RemoteSource(N.PlanNode):
    """Placeholder for an exchange input materialized by pulling upstream
    task buffers (reference RemoteSourceNode)."""

    source_id: str
    schema: Tuple[Tuple[str, object], ...]  # (channel, Type)

    @property
    def fields(self):
        return self.schema


class QueryKilledError(RuntimeError):
    """Raised into blocked tasks when the cluster memory manager kills
    their query (reference: ExceededMemoryLimitException from
    LowMemoryKiller)."""


class WorkerMemoryPool:
    """Worker-wide memory accounting (reference: worker MemoryPool polled
    by ClusterMemoryManager.process, memory/ClusterMemoryManager.java:89).

    Two ledgers share one limit:
    * OUTPUT buffers (`reserve`/`free`): reservations past the limit
      BLOCK (the reference's blocking futures) until space frees, a
      revocation frees executor state, or the cluster memory manager
      kills a query.
    * EXECUTION state (`reserve_execution`/`free_execution`): build
      tables, accumulator state and spilled-pending bytes mirrored from
      each task's exec MemoryPool (exec/memory.py parent mirroring) —
      accounting-only (the executor enforces its own device budget), but
      counted against the limit/watermark so `/v1/memory` and the killer
      see REAL usage.

    Crossing the revocation watermark asks running executors to revoke
    (offload -> disk spill) in largest-revocable-first order — the
    MemoryRevokingScheduler analog (MemoryRevokingScheduler.java:46) —
    BEFORE anything blocks long enough for the killer to fire."""

    def __init__(self, limit: Optional[int] = None,
                 revoke_watermark: Optional[float] = None):
        import os

        self.limit = limit
        self.revoke_watermark = (
            knobs.revoke_watermark()
            if revoke_watermark is None else revoke_watermark
        )
        self.reserved = 0  # output-buffer bytes
        self.by_query: Dict[str, int] = {}
        self.exec_reserved = 0  # executor-held bytes (mirrored)
        self.exec_by_query: Dict[str, int] = {}
        self.blocked: set = set()  # query ids currently waiting
        # double-free observability (never silently clamp)
        self.over_frees = 0
        self.over_freed_bytes = 0
        # leaked exec reservations force-released at task unregister —
        # nonzero means a driver leak (the chaos suite asserts zero)
        self.leaked_exec_bytes = 0
        self.revocations_requested = 0
        self.watermark_breaches = 0
        self._revocations_base = 0  # completed, from unregistered pools
        self._exec_pools: Dict[int, object] = {}  # id -> exec MemoryPool
        # attached serving caches (exec/qcache.py ResultCache): bytes are
        # counted toward the watermark and the caches are revoked FIRST —
        # cached results are the cheapest memory on the node to give back
        self._caches: Dict[str, object] = {}
        self._cond = threading.Condition()

    # -- attached serving caches --

    def attach_cache(self, cache) -> None:
        with self._cond:
            self._caches[getattr(cache, "name", "cache")] = cache

    def detach_cache(self, cache) -> None:
        with self._cond:
            self._caches.pop(getattr(cache, "name", "cache"), None)

    def _cache_bytes_locked(self) -> int:
        return sum(c.stats.bytes for c in self._caches.values())

    # -- execution ledger (exec/memory.MemoryPool parent mirroring) --

    def register_exec_pool(self, pool) -> None:
        with self._cond:
            self._exec_pools[id(pool)] = pool

    def unregister_exec_pool(self, pool) -> None:
        """Detach a finished task's pool; any bytes it still holds are a
        driver leak — force-release them so the worker stays healthy, but
        COUNT them (tests assert zero)."""
        with self._cond:
            self._exec_pools.pop(id(pool), None)
            self._revocations_base += pool.revocations
        leaked = pool.reserved
        if leaked:
            with self._cond:
                self.leaked_exec_bytes += leaked
            self.free_execution(pool.query_id, leaked)

    def reserve_execution(self, query_id: str, nbytes: int) -> None:
        maybe_revoke = False
        with self._cond:
            self.exec_reserved += nbytes
            self.exec_by_query[query_id] = (
                self.exec_by_query.get(query_id, 0) + nbytes
            )
            maybe_revoke = (
                self.limit is not None
                and self.reserved + self.exec_reserved
                + self._cache_bytes_locked()
                > self.revoke_watermark * self.limit
            )
            if maybe_revoke:
                self._request_revocations_locked(0)

    def free_execution(self, query_id: str, nbytes: int) -> None:
        from ..exec.memory import GLOBAL_ACCOUNTING

        with self._cond:
            if nbytes > self.exec_reserved:
                self.over_frees += 1
                self.over_freed_bytes += nbytes - self.exec_reserved
                GLOBAL_ACCOUNTING["over_frees"] += 1
                GLOBAL_ACCOUNTING["over_freed_bytes"] += (
                    nbytes - self.exec_reserved
                )
                nbytes = self.exec_reserved
            self.exec_reserved -= nbytes
            left = self.exec_by_query.get(query_id, 0) - nbytes
            if left > 0:
                self.exec_by_query[query_id] = left
            else:
                self.exec_by_query.pop(query_id, None)
            self._cond.notify_all()

    def total_reserved(self) -> int:
        with self._cond:
            return self.reserved + self.exec_reserved

    # -- revocation (the rung between "blocked" and "killed") --

    def _request_revocations_locked(self, need: int) -> None:
        """Ask executors to revoke until the projected freeing covers the
        excess over the watermark, largest-revocable-first (reference
        MemoryRevokingScheduler.requestMemoryRevoking)."""
        if self.limit is None:
            return
        floor = int(self.revoke_watermark * self.limit)
        excess = (
            self.reserved + self.exec_reserved
            + self._cache_bytes_locked() + need - floor
        )
        if excess <= 0:
            return
        self.watermark_breaches += 1
        # serving caches revoke FIRST: evicting a cached result is free
        # (the entry re-materializes on the next miss) while revoking an
        # executor forces a spill — only the remaining excess reaches the
        # spill ladder
        for cache in self._caches.values():
            if excess <= 0:
                return
            excess -= cache.revoke(excess)
        if excess <= 0:
            return
        pools = sorted(
            self._exec_pools.values(),
            key=lambda p: -p.revocable_bytes(),
        )
        for pool in pools:
            if excess <= 0:
                break
            if pool.request_revoke():
                self.revocations_requested += 1
            # even a pool with nothing revocable RIGHT NOW is asked: its
            # next accumulation window observes the pending revoke and
            # offloads instead of growing
            excess -= max(pool.revocable_bytes(), 1)

    def revocations_completed(self) -> int:
        with self._cond:
            return self._revocations_base + sum(
                p.revocations for p in self._exec_pools.values()
            )

    # -- output-buffer ledger --

    def reserve(self, query_id: str, nbytes: int, abort: threading.Event,
                timeout: float = 600.0) -> None:
        if self.limit is None:
            with self._cond:
                self.reserved += nbytes
                self.by_query[query_id] = self.by_query.get(query_id, 0) + nbytes
            return
        deadline = time.time() + timeout
        with self._cond:
            while self.reserved + self.exec_reserved + nbytes > self.limit:
                if abort.is_set():
                    self.blocked.discard(query_id)
                    raise QueryKilledError(
                        "Query killed: the cluster ran out of memory "
                        "(TotalReservation low-memory killer)"
                    )
                if time.time() > deadline:
                    self.blocked.discard(query_id)
                    raise MemoryError(
                        f"worker memory exhausted: {nbytes:,}B requested, "
                        f"{self.reserved:,}B of {self.limit:,}B reserved"
                    )
                # revoke-before-kill: ask executors to free revocable
                # state instead of waiting for the low-memory killer
                self._request_revocations_locked(nbytes)
                self.blocked.add(query_id)
                self._cond.wait(timeout=0.05)
            self.blocked.discard(query_id)
            self.reserved += nbytes
            self.by_query[query_id] = self.by_query.get(query_id, 0) + nbytes
            # the watermark can be crossed by buffer growth alone: ask
            # for revocations BEFORE anything blocks, not only after
            self._request_revocations_locked(0)

    def free(self, query_id: str, nbytes: int) -> None:
        from ..exec.memory import GLOBAL_ACCOUNTING

        with self._cond:
            if nbytes > self.reserved:
                self.over_frees += 1
                self.over_freed_bytes += nbytes - self.reserved
                GLOBAL_ACCOUNTING["over_frees"] += 1
                GLOBAL_ACCOUNTING["over_freed_bytes"] += (
                    nbytes - self.reserved
                )
                nbytes = self.reserved
            self.reserved -= nbytes
            left = self.by_query.get(query_id, 0) - nbytes
            if left > 0:
                self.by_query[query_id] = left
            else:
                self.by_query.pop(query_id, None)
            self._cond.notify_all()

    def wake(self) -> None:
        with self._cond:
            self._cond.notify_all()

    def snapshot(self) -> dict:
        with self._cond:
            queries: Dict[str, int] = dict(self.by_query)
            for qid, nbytes in self.exec_by_query.items():
                queries[qid] = queries.get(qid, 0) + nbytes
            revoke_pending = any(
                p.revoke_pending for p in self._exec_pools.values()
            )
            return {
                "limit": self.limit,
                # total usage: buffers + executor-held bytes, so the
                # cluster memory manager kills on REAL reservation
                "reserved": self.reserved + self.exec_reserved,
                "buffer_reserved": self.reserved,
                "exec_reserved": self.exec_reserved,
                "queries": queries,
                "buffers": dict(self.by_query),
                "execution": dict(self.exec_by_query),
                "blocked": sorted(self.blocked),
                "over_frees": self.over_frees,
                "over_freed_bytes": self.over_freed_bytes,
                "leaked_exec_bytes": self.leaked_exec_bytes,
                "revocations": {
                    "watermark_breaches": self.watermark_breaches,
                    "requested": self.revocations_requested,
                    "completed": self._revocations_base + sum(
                        p.revocations for p in self._exec_pools.values()
                    ),
                    "pending": revoke_pending,
                },
                "watermark": self.revoke_watermark,
                # attached serving caches (exec/qcache.py): bytes held +
                # bytes given back under pressure, per cache
                "cache_reserved": self._cache_bytes_locked(),
                "caches": {
                    name: {
                        "bytes": c.stats.bytes,
                        "entries": len(c),
                        "revoked_bytes": c.stats.revoked_bytes,
                        "evictions": c.stats.evictions,
                    }
                    for name, c in self._caches.items()
                },
            }


class OutputBuffers:
    """Bounded, ack-consumed task output buffers (reference
    PartitionedOutputBuffer + OutputBufferMemoryManager,
    execution/buffer/): producers append page-at-a-time and BLOCK while
    unacknowledged bytes exceed the bound (backpressure); consumers pull
    by token and acknowledge, which frees producer budget. Bytes are also
    accounted in the worker memory pool so the cluster memory manager
    sees them."""

    def __init__(self, pool: "WorkerMemoryPool", query_id: str,
                 abort: threading.Event, bound: Optional[int] = None):
        self.pool = pool
        self.query_id = query_id
        self.abort = abort
        self.bound = bound
        self._pages: Dict[int, List[Optional[bytes]]] = {}
        self._unacked = 0
        self._finished = False
        self._drained = False
        self._cond = threading.Condition()

    def put(self, buffer_id: int, data: bytes,
            timeout: float = 600.0) -> None:
        deadline = time.time() + timeout
        with self._cond:
            while self.bound is not None and self._unacked + len(data) > max(
                self.bound, len(data)
            ):
                if self.abort.is_set():
                    raise QueryKilledError(
                        "Query killed: the cluster ran out of memory "
                        "(TotalReservation low-memory killer)"
                    )
                if time.time() > deadline:
                    raise MemoryError(
                        "output buffer consumer stalled past the bound"
                    )
                self._cond.wait(timeout=0.05)
            if self._drained:
                raise QueryKilledError("task deleted while producing")
            # claim the bound bytes under the SAME lock acquisition as
            # the check: concurrent producers can no longer all pass the
            # check and overshoot the bound while one of them sits in
            # pool.reserve below
            self._unacked += len(data)
        try:
            # prestolint: allow(memory-reserve-no-finally) -- both
            # failure paths DO undo: this except hands back _unacked,
            # and the drained branch below frees the pool bytes
            self.pool.reserve(self.query_id, len(data), self.abort)
        except BaseException:
            with self._cond:
                if not self._drained:  # drain() already zeroed _unacked
                    self._unacked -= len(data)
                self._cond.notify_all()
            raise
        with self._cond:
            if self._drained:
                # task was deleted while this producer was mid-stream:
                # hand the bytes straight back, never strand them
                # (drain() zeroed _unacked, so only the pool needs undo)
                self.pool.free(self.query_id, len(data))
                raise QueryKilledError("task deleted while producing")
            self._pages.setdefault(buffer_id, []).append(data)
            self._cond.notify_all()

    def finish(self) -> None:
        with self._cond:
            self._finished = True
            self._cond.notify_all()

    def get(self, buffer_id: int, token: int,
            timeout: float = 60.0):
        """(serialized page | None, complete, ready): ready=False means
        long-poll again (the page is not produced yet)."""
        with self._cond:
            deadline = time.time() + timeout
            while True:
                pages = self._pages.get(buffer_id, [])
                if token < len(pages):
                    if pages[token] is None:
                        raise RuntimeError(
                            f"buffer {buffer_id} token {token} was already "
                            "acknowledged (exchange protocol violation)"
                        )
                    return pages[token], False, True
                if self._finished:
                    return None, True, True
                if time.time() > deadline:
                    return None, False, False
                self._cond.wait(timeout=0.1)

    def get_many(self, buffer_id: int, token: int, max_bytes: int,
                 timeout: float = 60.0):
        """([serialized pages], complete, ready): as many consecutive
        already-produced pages from `token` as fit the `max_bytes`
        response budget (the reference's `exchange.max-response-size`
        batching, TaskResource.java:239). At least one page is always
        returned when one exists; `complete` is True when the returned
        batch drains a finished buffer, saving the final round trip."""
        first, complete, ready = self.get(buffer_id, token, timeout=timeout)
        if not ready or first is None:
            return [], complete, ready
        out = [first]
        total = len(first)
        with self._cond:
            pages = self._pages.get(buffer_id, [])
            t = token + 1
            while t < len(pages) and total < max_bytes:
                p = pages[t]
                if p is None:
                    raise RuntimeError(
                        f"buffer {buffer_id} token {t} was already "
                        "acknowledged (exchange protocol violation)"
                    )
                out.append(p)
                total += len(p)
                t += 1
            complete = self._finished and t >= len(pages)
        return out, complete, True

    def ack(self, buffer_id: int, upto_token: int) -> None:
        """Acknowledge pages [0, upto_token): their bytes free the bound
        and the worker pool (reference: acknowledge + delete results)."""
        with self._cond:
            pages = self._pages.get(buffer_id, [])
            freed = 0
            for i in range(min(upto_token, len(pages))):
                if pages[i] is not None:
                    freed += len(pages[i])
                    pages[i] = None
            if freed:
                self._unacked -= freed
                self._cond.notify_all()
        if freed:
            self.pool.free(self.query_id, freed)

    def drain(self) -> None:
        """Free everything still held (task deleted); later puts are
        rejected so a mid-stream producer cannot leak reservations."""
        with self._cond:
            self._drained = True
            freed = sum(
                len(p)
                for pages in self._pages.values()
                for p in pages
                if p is not None
            )
            self._pages.clear()
            self._unacked = 0
            self._cond.notify_all()
        if freed:
            self.pool.free(self.query_id, freed)


class TaskState:
    def __init__(self, query_id: str = ""):
        self.state = "RUNNING"
        self.error: Optional[str] = None
        # structured failure cause the coordinator classifies as
        # retryable vs. fatal (see _classify_failure)
        self.error_info: Optional[dict] = None
        self.buffers: Optional[OutputBuffers] = None
        self.done = threading.Event()
        self.query_id = query_id
        self.abort = threading.Event()  # set by the low-memory killer
        # dynamic-filter summaries accumulated over this task's output
        # (spec dyn_filter_produce; exec/dynfilter.HostFilterAccumulator),
        # exposed to the coordinator through the status endpoint
        self.dyn_filters: dict = {}
        # wire observability: encode stats for this task's serialized
        # output + pull stats for its upstream exchange clients, exposed
        # through the status endpoint as "exchangeStats" (the substrate
        # of EXPLAIN ANALYZE's per-exchange wire numbers)
        from .serde import WireStats

        self.wire_stats = WireStats()
        self.pull_stats = None  # ExchangeStats, set when sources exist
        self.hier_stats = None  # HierExchangeStats, set when this task
        # partitions output through the hierarchical exchange plane
        # memory-arbitration observability, filled at task end: the exec
        # pool snapshot (peak/revocations/over-frees) and spill stats
        # (events, disk bytes, hybrid join partition/recursion counters)
        self.executor = None
        self.spill_space = None
        self.memory_stats: Optional[dict] = None
        self.spill_stats: Optional[dict] = None
        # serialized span dicts for this task (obs/span.py), shipped in
        # the status payload and merged into the coordinator's trace —
        # the worker NEVER registers its trace globally, so the HTTP
        # merge path is exercised even by in-process workers
        self.spans: list = []


# message fragments marking failures that would recur identically on any
# worker — retrying them only wastes the retry budget
_FATAL_MARKERS = (
    "Query killed",  # low-memory killer chose this query
    "memory exhausted",  # worker pool limit: the retry would also exceed it
    "protocol violation",
    "not yet supported",
    # disk spill tier (exec/spillspace.py): a retry on another worker
    # would hit the same quota; a corrupt spill file must fail the query
    # with its structured error, never be retried into wrong rows
    "spill quota exceeded",
    "spill file corrupt",
)

# exception-type / message fragments identifying accelerator kernel
# faults (XLA / Mosaic): retryable, because the kernel circuit breaker
# (exec/breaker.py) degrades the faulting kernel to its XLA fallback on
# the retry attempt
_KERNEL_FAULT_MARKERS = (
    "XlaRuntimeError", "Mosaic", "INTERNAL:", "mosaic", "pallas",
)


def _classify_failure(exc: BaseException) -> dict:
    """Serialize an exception into the structured error the coordinator's
    retry policy consumes (reference: ExecutionFailureInfo + ErrorCode
    retryability, spi/StandardErrorCode.java)."""
    text = f"{type(exc).__name__}: {exc}"
    kernel_fault = any(m in text for m in _KERNEL_FAULT_MARKERS)
    retryable = not any(m in text for m in _FATAL_MARKERS)
    if isinstance(exc, (QueryKilledError, MemoryError)):
        retryable = False
    return {
        "type": type(exc).__name__,
        "message": str(exc)[:500],
        "retryable": retryable,
        "kernelFault": kernel_fault,
    }


class FragmentExecutor(Executor):
    """Executes a fragment subtree; scans are split-limited, RemoteSources
    read pulled pages (reference SqlTaskExecution + LocalExecutionPlanner)."""

    def __init__(self, catalog, splits, sources):
        super().__init__(catalog)
        self.splits = splits or {}
        self.sources = sources or {}
        self.sample_salt = _split_salt(self.splits)

    def _exec_tablescan(self, node: N.TableScan) -> Page:
        rng = self.splits.get(node.table)
        if rng is None:
            return super()._exec_tablescan(node)
        start, stop = rng
        scan = getattr(self.catalog, "scan", None)
        cols = [c for _, c, _ in node.columns]
        src = scan(node.table, start, stop, columns=cols)
        blocks, names = [], []
        for ch, colname, _t in node.columns:
            blocks.append(src.block(colname))
            names.append(ch)
        return Page(tuple(blocks), tuple(names), src.count)

    def _exec_remotesource(self, node: RemoteSource) -> Page:
        pages = self.sources[node.source_id]
        if not pages:
            raise RuntimeError(f"no pages for source {node.source_id}")
        return pages[0] if len(pages) == 1 else concat_pages(pages)


class StreamingFragmentExecutor(StreamingExecutor):
    """Streaming task execution (reference Driver pipeline fed by
    ExchangeOperator): scans honor split ranges batch-by-batch, and
    RemoteSource inputs arrive PAGE-AT-A-TIME from the pull clients —
    never materialize-then-concat. Budget-aware sinks (aggregation state
    merging, join build offload, external sort) compose unchanged, so an
    upstream stage larger than this worker's memory flows through in
    bounded pieces."""

    def __init__(self, catalog, splits, source_streams,
                 batch_rows: int = 1 << 18,
                 memory_budget: Optional[int] = None,
                 query_id: str = "",
                 worker_pool=None,
                 spill_space=None,
                 coalesce_remote: bool = False):
        super().__init__(
            catalog, batch_rows=batch_rows, memory_budget=memory_budget,
            query_id=query_id, worker_pool=worker_pool,
            spill_space=spill_space,
        )
        self.splits = splits or {}
        self.source_streams = source_streams or {}
        self.coalesce_remote = coalesce_remote
        # TABLESAMPLE: distinct per-worker hash salt derived from this
        # task's split assignment, so workers sampling disjoint row
        # ranges never reuse one positional mask (ops/filter.sample_page)
        self.local.sample_salt = _split_salt(self.splits)

    def stream(self, node: N.PlanNode):
        if isinstance(node, RemoteSource):
            if self.coalesce_remote:
                # the hierarchical exchange ships ragged wire pages
                # (small, skew-proportional); coalesce them back into
                # full batches so the sinks dispatch one kernel per
                # batch_rows, not one per wire sliver
                # (exec/stream.coalesce_pages). Flat-path exchanges
                # stream straight through — buffering full-size pages
                # would only stall the pull pipeline.
                from ..exec.stream import coalesce_pages
                from ..ops.ragged import page_rows_default

                target = min(self.batch_rows, 4 * page_rows_default())
                yield from coalesce_pages(
                    self.source_streams[node.source_id](), target
                )
                return
            yield from self.source_streams[node.source_id]()
            return
        yield from super().stream(node)

    def _stream_scan(self, node: N.TableScan, predicate=None):
        rng = self.splits.get(node.table)
        if rng is None:
            yield from super()._stream_scan(node, predicate)
            return
        if node.dynamic_filters:
            # dynamic-filter SPI hints (coordinator-shipped or published
            # by an in-fragment join) prune connector units before decode
            dyn = self._dyn_scan_hints(node)
            if dyn:
                predicate = list(predicate or []) + dyn
        start, stop = rng
        B = self.batch_rows
        pos = start
        first = True
        while pos < stop or first:
            # split bounds are exact, so connector pruning hints stay safe
            # (a pruned short batch cannot be mistaken for end-of-table)
            src = self.catalog.scan(
                node.table, pos, min(pos + B, stop), pad_to=B,
                columns=[c for _, c, _ in node.columns],
                predicate=predicate,
            )
            yield self._scan_out(node, self._rename_scan(node, src))
            first = False
            pos += B


class WorkerServer:
    """One worker process/port: executes tasks against its own catalog
    instance (catalogs must be deterministic across nodes — the TPC-H
    generator and parquet files are)."""

    def __init__(self, catalog, host: str = "127.0.0.1", port: int = 0,
                 memory_limit: Optional[int] = None,
                 buffer_bound: Optional[int] = 32 << 20,
                 task_concurrency: int = 2,
                 fault_rate: float = 0.0,
                 task_timeout: Optional[float] = None,
                 wire_caps: Optional[dict] = None,
                 exec_budget: Optional[int] = None,
                 revoke_watermark: Optional[float] = None,
                 spill_dir: Optional[str] = None,
                 spill_node_quota: Optional[int] = None,
                 spill_query_quota: Optional[int] = None,
                 account_result_cache: bool = False):
        from ..exec.spillspace import SPILL_MANAGER, SpillSpaceManager
        from ..exec.taskqueue import MultilevelScheduler

        self.catalog = catalog
        # per-task streaming-executor device budget: past it, operator
        # state offloads to host RAM and then the disk spill tier
        self.exec_budget = exec_budget
        # disk spill tier (exec/spillspace.py): workers with explicit
        # quotas/dirs get their own manager; otherwise the process-global
        # one (both register in the suite-wide leak oracle)
        if spill_dir or spill_node_quota or spill_query_quota:
            self.spill = SpillSpaceManager(
                directory=spill_dir, node_quota=spill_node_quota,
                query_quota=spill_query_quota,
            )
        else:
            self.spill = SPILL_MANAGER
        # capability-advertisement override (tests: simulate an old node
        # or one without the zstandard wheel in an in-process fleet)
        self.wire_caps = wire_caps
        # fault injection knob: probability a task fails at start
        self.fault_rate = float(fault_rate)
        # wall-clock ceiling per task, checked between batches: a wedged
        # kernel cannot hold a task RUNNING forever (the coordinator's
        # per-task deadline is the outer guard; this one frees the
        # worker's own slot)
        self.task_timeout = task_timeout
        self.tasks: Dict[str, TaskState] = {}
        self.pool = WorkerMemoryPool(
            memory_limit, revoke_watermark=revoke_watermark
        )
        # opt-in: account the process-wide result cache (exec/qcache.py)
        # in THIS worker's pool — its bytes then show in /v1/memory,
        # count toward the revocation watermark, and are revoked first.
        # Opt-in because one process can host several in-process workers
        # (tests) and the cache can only be charged to one of them.
        self._accounted_cache = None
        if account_result_cache:
            from ..exec.qcache import RESULT_CACHE

            self.pool.attach_cache(RESULT_CACHE)
            self._accounted_cache = RESULT_CACHE
        self.buffer_bound = buffer_bound
        # multilevel feedback gate over per-batch quanta (reference
        # TaskExecutor + MultilevelSplitQueue)
        self.scheduler = MultilevelScheduler(task_concurrency)
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, code, payload):
                body = (
                    payload
                    if isinstance(payload, bytes)
                    else json.dumps(payload).encode()
                )
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                parts = [p for p in self.path.split("/") if p]
                if parts[:2] == ["v1", "task"] and len(parts) == 3:
                    # containment: a malformed spec must 500 with a
                    # structured error, never tear down the connection
                    # (the round-5 failure mode: one bad task wedged the
                    # serving loop)
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                        spec = json.loads(self.rfile.read(n))
                        outer._start_task(parts[2], spec)
                    except Exception as exc:  # noqa: BLE001
                        self._send(500, {
                            "error": traceback.format_exc(limit=10),
                            "errorInfo": _classify_failure(exc),
                        })
                        return
                    self._send(200, {"taskId": parts[2], "state": "RUNNING"})
                    return
                self._send(404, {"error": "not found"})

            def do_GET(self):
                try:
                    self._do_get()
                except (BrokenPipeError, ConnectionResetError):
                    raise
                except Exception:  # noqa: BLE001 - surface handler bugs
                    self._send(
                        500, {"error": traceback.format_exc(limit=10)}
                    )

            def _do_get(self):
                path, _, query = self.path.partition("?")
                parts = [p for p in path.split("/") if p]
                if parts == ["v1", "status"]:
                    # capability handshake: the coordinator intersects
                    # every member's advertised wire caps and ships the
                    # result in task specs, so a mixed fleet (one node
                    # without the zstandard wheel, or still on wire v1)
                    # agrees on a format instead of failing deserialize
                    from .serde import local_capabilities
                    from ..exec import qcache

                    self._send(200, {
                        "state": "ACTIVE",
                        "wire": outer.wire_caps or local_capabilities(),
                        "caches": qcache.snapshot_all(),
                    })
                    return
                if parts == ["v1", "metrics"]:
                    # Prometheus text exposition — same registry the
                    # coordinator scrapes (process-global), so an
                    # in-process fleet shares one plane and a real
                    # remote worker exposes its own
                    from ..obs.metrics import METRICS

                    body = METRICS.render().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8",
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if parts == ["v1", "memory"]:
                    # reference MemoryResource polled by the coordinator's
                    # ClusterMemoryManager: buffer + execution ledgers,
                    # revocation counters, and the disk spill tier
                    snap = outer.pool.snapshot()
                    snap["spill"] = outer.spill.snapshot()
                    self._send(200, snap)
                    return
                if parts[:2] == ["v1", "task"] and len(parts) == 3:
                    t = outer.tasks.get(parts[2])
                    if t is None:
                        self._send(404, {"error": "unknown task"})
                        return
                    t.done.wait(timeout=0.5)  # short-poll: consumers
                    # pipeline against RUNNING producers; failures also
                    # surface as 500s on the results pull
                    ex_stats = t.wire_stats.snapshot()
                    if t.pull_stats is not None:
                        ex_stats["pull"] = t.pull_stats.snapshot()
                    if t.hier_stats is not None:
                        ex_stats["hier"] = t.hier_stats.snapshot()
                    self._send(200, {
                        "state": t.state, "error": t.error,
                        "errorInfo": t.error_info,
                        "dynFilters": t.dyn_filters or None,
                        "exchangeStats": ex_stats,
                        "memoryStats": t.memory_stats,
                        "spillStats": t.spill_stats,
                        # serialized span dicts the coordinator merges
                        # (Trace.add_remote) into the query's one tree
                        "spans": t.spans or None,
                    })
                    return
                if (
                    parts[:2] == ["v1", "task"]
                    and len(parts) == 6
                    and parts[3] == "results"
                ):
                    tid, buffer_id, token = parts[2], int(parts[4]), int(parts[5])
                    t = outer.tasks.get(tid)
                    if t is None:
                        self._send(404, {"error": "unknown task"})
                        return
                    if t.state == "FAILED":
                        self._send(500, {"error": t.error,
                                         "errorInfo": t.error_info})
                        return
                    if t.buffers is None:  # task thread not started yet
                        self._send(503, {"retry": True, "state": t.state})
                        return
                    max_bytes = 0
                    for kv in query.split("&"):
                        if kv.startswith("max_bytes="):
                            try:
                                max_bytes = int(kv.split("=", 1)[1])
                            except ValueError:
                                pass
                    if max_bytes > 0:
                        # multi-page response bounded by the client's
                        # max_response_bytes budget (the
                        # exchange.max-response-size analog); "page"
                        # stays populated so old pullers interoperate
                        datas, complete, ready = t.buffers.get_many(
                            buffer_id, token, max_bytes, timeout=50
                        )
                    else:
                        data, complete, ready = t.buffers.get(
                            buffer_id, token, timeout=50
                        )
                        datas = [] if data is None else [data]
                    if t.state == "FAILED":
                        # finish() fires in the task's finally, so a failed
                        # producer must never look like a complete stream
                        self._send(500, {"error": t.error,
                                         "errorInfo": t.error_info})
                        return
                    if not ready:
                        self._send(503, {"retry": True, "state": t.state})
                        return
                    encoded = [
                        base64.b64encode(d).decode() for d in datas
                    ]
                    self._send(
                        200,
                        {
                            "page": encoded[0] if encoded else None,
                            "pages": encoded,
                            "complete": complete,
                        },
                    )
                    return
                self._send(404, {"error": "not found"})

            def do_DELETE(self):
                parts = [p for p in self.path.split("/") if p]
                if (
                    parts[:2] == ["v1", "task"]
                    and len(parts) == 6
                    and parts[3] == "results"
                ):
                    # acknowledge pages [0, token): frees producer budget
                    t = outer.tasks.get(parts[2])
                    if t is not None and t.buffers is not None:
                        t.buffers.ack(int(parts[4]), int(parts[5]))
                    self._send(200, {"acknowledged": True})
                    return
                if parts[:2] == ["v1", "task"] and len(parts) == 3:
                    t = outer.tasks.pop(parts[2], None)
                    if t is not None:
                        t.abort.set()
                        if t.buffers is not None:
                            t.buffers.drain()
                    self._send(200, {"deleted": True})
                    return
                if parts[:2] == ["v1", "query"] and len(parts) == 3:
                    # low-memory kill: abort every task of this query;
                    # blocked reservations raise QueryKilledError
                    outer.kill_query(parts[2])
                    self._send(200, {"killed": parts[2]})
                    return
                self._send(404, {"error": "not found"})

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._httpd.server_address
        self.node_id = f"{self.host}:{self.port}"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )

    # -- task execution --

    def _start_task(self, task_id: str, spec: dict):
        state = TaskState(query_id=spec.get("query_id") or task_id)
        self.tasks[task_id] = state
        threading.Thread(
            target=self._run_task, args=(task_id, spec, state), daemon=True
        ).start()

    def _run_task(self, task_id: str, spec: dict, state: TaskState):
        # broadcast consumers never ack (pages are shared; freed at task
        # DELETE), so a bounded buffer would deadlock its producer
        stream_iter = None  # closed in the finally for deterministic
        # generator teardown (reservations return before unregister)
        bound = None if spec.get("buffer_unbounded") else self.buffer_bound
        buffers = OutputBuffers(
            self.pool, state.query_id, state.abort, bound=bound
        )
        state.buffers = buffers
        # task span opened BEFORE fault injection: a failed attempt must
        # still ship an error-status span in its FAILED status payload so
        # the coordinator's merged tree shows the attempt (retry =
        # sibling spans, never an overwrite). The Trace is standalone —
        # never registered in the global TRACES store — so the only way
        # home is the status payload, same as a real remote worker.
        from ..obs.span import Trace, enabled as _trace_enabled

        tctx = spec.get("trace") or {}
        task_trace = task_span = None
        if tctx.get("trace_id") and _trace_enabled():
            task_trace = Trace(str(tctx["trace_id"]))
            task_span = task_trace.begin(
                f"task {task_id}", parent_id=tctx.get("parent"),
                worker=self.node_id,
            )
        try:
            if self.fault_rate > 0:
                # fault injection (reference: test-only task failures,
                # e.g. TestEventListener's failing connector; here a
                # worker-level knob so cluster tests can exercise the
                # failure-propagation path deterministically)
                import random

                if random.random() < self.fault_rate:
                    raise RuntimeError(
                        f"injected fault on worker {self.node_id} "
                        f"(fault_rate={self.fault_rate})"
                    )
            fragment = pickle.loads(base64.b64decode(spec["fragment"]))
            splits = {
                t: tuple(rng) for t, rng in (spec.get("splits") or {}).items()
            }
            # fleet-negotiated wire capabilities (coordinator handshake):
            # this task's output must only use codecs/encodings every
            # consumer can decode. A spec WITHOUT the field came from a
            # coordinator that does not negotiate (an old build) — its
            # decoder is unknown, so degrade to the universal baseline
            # rather than assuming this process's own capabilities.
            from .serde import baseline_capabilities

            wire_caps = spec.get("wire") or baseline_capabilities()
            if spec.get("sources"):
                from .exchange import ExchangeStats

                state.pull_stats = ExchangeStats()

            def make_stream(locations, exclusive):
                def gen():
                    # pipelined concurrent pull: one puller per producer
                    # task, multi-page responses, deserialize overlapped
                    # with in-flight requests (server/exchange.py). Acks
                    # free producer pages — only safe when this task is
                    # the buffer's sole consumer (replicated buffers are
                    # pulled by every consumer and freed on task DELETE)
                    from .exchange import ExchangeClient

                    client = ExchangeClient(
                        [(u, t, b) for u, t, b in locations],
                        ack=exclusive,
                        stats=state.pull_stats,
                    )
                    for page in client.pages():
                        yield _min_capacity(page)
                return gen

            streams = {
                sid: make_stream(
                    src["locations"], bool(src.get("exclusive", True))
                )
                for sid, src in (spec.get("sources") or {}).items()
            }
            # per-task spill space: quota-accounted under the QUERY id,
            # released in this thread's finally — kills, failures and
            # clean finishes all delete their spill files
            spill_space = self.spill.open(state.query_id)
            state.spill_space = spill_space
            # incoming ragged slivers are possible only when the fleet
            # negotiated the hierarchical exchange AND the knob is on
            # (upstream producers share this negotiation); otherwise
            # stream remote pages through untouched
            from .hier import hier_negotiated as _hier_neg

            coalesce_remote = (
                bool(spec.get("sources"))
                and knobs.hier_exchange_enabled()
                and _hier_neg(wire_caps)
            )
            ex = StreamingFragmentExecutor(
                self.catalog, splits, streams,
                memory_budget=self.exec_budget,
                query_id=state.query_id,
                worker_pool=self.pool,
                spill_space=spill_space,
                coalesce_remote=coalesce_remote,
            )
            state.executor = ex
            # executor-held bytes join the worker ledger + the revoking
            # scheduler's candidate set (revoke-before-kill)
            self.pool.register_exec_pool(ex.pool)
            # cross-task dynamic filters shipped by the coordinator: seed
            # the executor registry so annotated scans in this fragment
            # prune (exec/dynfilter.py). Missing/late filters simply stay
            # unpublished — the scan runs unfiltered (proceed-without).
            for fid, summary in (spec.get("dyn_filters") or {}).items():
                try:
                    from ..exec.dynfilter import filter_from_summary

                    df = filter_from_summary(summary, None)
                    if df is not None:
                        ex.dyn_ctx.publish(fid, df)
                except Exception:  # noqa: BLE001 — filters are best-effort
                    pass
            # summaries to accumulate over THIS task's output pages
            # (the build side of some downstream dynamic-filter join)
            from ..exec.dynfilter import HostFilterAccumulator

            dyn_accs = {
                fid: HostFilterAccumulator(channel)
                for fid, channel in (spec.get("dyn_filter_produce") or [])
            }
            part_keys = spec.get("partition_keys")
            nparts = int(spec.get("num_partitions", 1))
            keys = (
                pickle.loads(base64.b64decode(part_keys))
                if part_keys and nparts > 1
                else None
            )
            # hierarchical exchange (server/hier.py): regroup partitioned
            # output with ONE device step + ragged wire pages, when the
            # fleet negotiated the capability, the knob is on, and the
            # breaker is closed. Any fault mid-task trips the breaker
            # and degrades the REST of this task (and, once open, every
            # later task) to the flat per-partition loop — monotonic.
            use_hier = False
            if keys is not None:
                from ..exec.breaker import BREAKERS
                from .hier import HierExchangeStats, hier_negotiated, \
                    hier_partition

                use_hier = (
                    knobs.hier_exchange_enabled()
                    and hier_negotiated(wire_caps)
                    and BREAKERS.allow("hier_exchange")
                )
                if use_hier:
                    state.hier_stats = HierExchangeStats()
            # page-at-a-time into the bounded buffers: put() applies
            # backpressure when the consumer lags past the bound; pages
            # bigger than the bound split into row slices first
            # (reference PageSplitterUtil). Each batch passes through the
            # multilevel scheduler gate (exec/taskqueue.py) so a fresh
            # query's quanta preempt a long-running one BETWEEN batches;
            # buffer emission stays outside the quantum — blocking on a
            # slow consumer must not hold an execution slot.
            stream_iter = iter(ex.stream(fragment))
            deadline = (
                time.time() + self.task_timeout
                if self.task_timeout else None
            )
            while True:
                # crash containment checkpoints between batches: an
                # aborted (killed/deleted) task stops producing, and a
                # task past its deadline FAILS instead of holding its
                # slot forever (the round-5 wedge)
                if state.abort.is_set():
                    raise QueryKilledError("task aborted")
                if deadline is not None and time.time() > deadline:
                    raise TimeoutError(
                        f"task {task_id} exceeded task_timeout="
                        f"{self.task_timeout}s on worker {self.node_id}"
                    )
                with self.scheduler.quantum(state.query_id):
                    page = next(stream_iter, None)
                if page is None:
                    break
                for acc in dyn_accs.values():
                    try:
                        acc.add_page(page)
                    except Exception:  # noqa: BLE001 — best-effort
                        acc.unsupported = True
                for piece in _split_to_bound(page, bound):
                    if keys is not None:
                        if use_hier:
                            try:
                                parts = hier_partition(
                                    piece, keys, nparts, caps=wire_caps,
                                    stats=state.wire_stats,
                                    hier=state.hier_stats,
                                )
                                BREAKERS.record_success("hier_exchange")
                            except Exception as e:  # noqa: BLE001 — any
                                # hier fault degrades to the flat loop;
                                # output correctness must not depend on
                                # the optimized path
                                BREAKERS.record_failure(
                                    "hier_exchange", repr(e)
                                )
                                state.hier_stats.record_fallback()
                                use_hier = False
                                parts = _hash_partition(
                                    piece, keys, nparts, caps=wire_caps,
                                    stats=state.wire_stats,
                                )
                        else:
                            parts = _hash_partition(
                                piece, keys, nparts, caps=wire_caps,
                                stats=state.wire_stats,
                            )
                        for p, data in parts.items():
                            for d in data:
                                buffers.put(p, d)
                    else:
                        buffers.put(0, serialize_page(
                            piece, caps=wire_caps, stats=state.wire_stats,
                        ))
            if dyn_accs:
                state.dyn_filters = {
                    fid: s
                    for fid, acc in dyn_accs.items()
                    if (s := acc.summary()) is not None
                }
            state.state = "FINISHED"
        except BaseException as exc:  # noqa: BLE001 - kernel faults
            # (XLA/Mosaic aborts surface as various exception types)
            # must transition the task to FAILED with a structured cause
            # the coordinator can classify — never tear down the thread
            # silently or wedge the HTTP serving side
            state.error = traceback.format_exc(limit=20)
            state.error_info = _classify_failure(exc)
            state.state = "FAILED"
        finally:
            # deterministic teardown (not GC): closing the stream runs
            # every suspended generator's finally, returning executor
            # reservations before the pool unregisters
            if stream_iter is not None:
                try:
                    stream_iter.close()
                except Exception:  # noqa: BLE001 - teardown best-effort
                    pass
            ex_obj = getattr(state, "executor", None)
            if ex_obj is not None:
                release_error = None
                try:
                    ex_obj.release_spill()  # fold disk counters
                except Exception as exc:  # noqa: BLE001 — teardown must
                    # finish; the failure is recorded into spill_stats
                    # below instead of vanishing (prestolint burndown)
                    release_error = repr(exc)
                state.memory_stats = ex_obj.pool.snapshot()
                state.spill_stats = dict(ex_obj.spill_stats)
                if release_error is not None:
                    state.spill_stats["release_error"] = release_error
                state.spill_stats["events"] = sorted(
                    set(ex_obj.spill_events)
                )
                self.pool.unregister_exec_pool(ex_obj.pool)
            space = getattr(state, "spill_space", None)
            if space is not None:
                # guaranteed spill cleanup on finish, failure AND kill
                space.release()
            buffers.finish()
            try:
                self._finish_observability(task_id, state, task_trace,
                                           task_span)
            except Exception:  # noqa: BLE001 — observability must never
                # change task outcome or wedge teardown
                pass
            state.done.set()

    def _finish_observability(self, task_id: str, state: TaskState,
                              task_trace, task_span) -> None:
        """Close the task span (rows/bytes attrs from the wire stats,
        error status for FAILED) into state.spans, and fold this task's
        serde/pull accounting + outcome counter into the metrics plane."""
        from ..obs.export import (
            METRICS, export_exchange_stats, export_wire_stats,
        )

        wire_snap = state.wire_stats.snapshot()
        if task_trace is not None and task_span is not None:
            status = "error" if state.state == "FAILED" else "ok"
            attrs = {
                "pages": wire_snap.get("pages", 0),
                "bytes": wire_snap.get("wire_bytes", 0),
            }
            if state.error_info:
                attrs["error"] = state.error_info.get("message", "")[:200]
            if state.hier_stats is not None:
                hs = state.hier_stats.snapshot()
                if hs.get("exchanges"):
                    attrs["hier_collective_ms"] = hs["collective_ms"]
                    attrs["hier_wire_pages"] = hs["wire_pages"]
            if state.pull_stats is not None:
                # the span's overlap proof: wire wall the pullers spent
                # vs the fraction the consumer's device compute hid
                ps = state.pull_stats.snapshot()
                if ps.get("pull_ms"):
                    attrs["wire_ms"] = ps["pull_ms"]
                    attrs["wire_hidden_ms"] = ps["hidden_ms"]
            task_trace.finish(task_span, status=status, **attrs)
            state.spans = task_trace.to_dicts()
        METRICS.counter(
            "presto_worker_tasks_total", 1, {"state": state.state},
            help="Worker tasks run",
        )
        export_wire_stats("task_encode", state.wire_stats)
        if state.pull_stats is not None:
            export_exchange_stats(state.pull_stats)
        if state.hier_stats is not None:
            from ..obs.export import export_hier_stats

            export_hier_stats(state.hier_stats)

    def start(self) -> "WorkerServer":
        self._thread.start()
        return self

    def kill_query(self, query_id: str) -> None:
        for t in list(self.tasks.values()):
            if t.query_id == query_id:
                t.abort.set()
        self.pool.wake()

    def stop(self):
        if self._accounted_cache is not None:
            self.pool.detach_cache(self._accounted_cache)
            self._accounted_cache = None
        self._httpd.shutdown()
        self._httpd.server_close()

    @property
    def uri(self) -> str:
        return f"http://{self.host}:{self.port}"


def _split_salt(splits: Dict[str, Tuple[int, int]]) -> int:
    """Deterministic per-task sample salt from the split assignment: the
    summed range starts are distinct across workers of one stage (their
    row ranges are disjoint), so TABLESAMPLE's positional hash never
    reuses a mask across workers."""
    return sum(int(start) for start, _stop in splits.values())


def _split_to_bound(page: Page, bound: Optional[int]):
    """Split a page into row slices whose RAW bytes fit the output-buffer
    bound (serialized bytes are smaller), so one page never blows through
    the backpressure budget (reference PageSplitterUtil.splitPage)."""
    n = int(page.count)
    if bound is None or n == 0:
        yield page
        return
    row_bytes = max(
        sum(
            b.data.dtype.itemsize * (b.data.size // max(b.data.shape[0], 1))
            + (1 if b.valid is not None else 0)
            for b in page.blocks
        ),
        1,
    )
    max_rows = max(bound // (2 * row_bytes), 256)
    if n <= max_rows:
        yield page
        return
    for start in range(0, n, max_rows):
        stop = min(start + max_rows, n)
        idx = slice(start, stop)
        blocks = tuple(b.take_rows(idx) for b in page.blocks)
        yield Page(blocks, page.names, stop - start)


def _min_capacity(page: Page, minimum: int = 16) -> Page:
    """Empty wire pages deserialize with ZERO capacity; the streaming
    sinks' static-shape kernels need at least one slot — pad up."""
    if not page.blocks or page.blocks[0].data.shape[0] >= minimum:
        return page
    from ..page import _pad_block

    return Page(
        tuple(_pad_block(b, minimum) for b in page.blocks),
        page.names,
        page.count,
    )


def _hash_partition(page: Page, key_exprs, nparts: int,
                    caps: Optional[dict] = None,
                    stats=None) -> Dict[int, List[bytes]]:
    """Partition live rows by key hash -> serialized per-partition pages
    (reference PartitionedOutputOperator.partitionPage + PagesSerde)."""
    import jax.numpy as jnp

    from ..ops.filter import compact
    from ..ops.hashing import hash_rows
    from ..expr.compiler import evaluate

    keys = [evaluate(e, page) for e in key_exprs]
    h = hash_rows(keys)
    part = (h % jnp.uint64(nparts)).astype(jnp.int32)
    out: Dict[int, List[bytes]] = {}
    for p in range(nparts):
        sub = compact(page, part == p)
        out[p] = [serialize_page(sub, caps=caps, stats=stats)]
    return out


def _pull_buffer(uri: str, task_id: str, buffer_id: int, ack: bool = True,
                 deadline: Optional[float] = None,
                 max_bytes: Optional[int] = None):
    """Generator of serialized pages from ONE upstream buffer, batched
    long-polls + acks (reference HttpPageBufferClient pull/ack/delete
    loop). The multi-producer pipelined path is server/exchange.py's
    ExchangeClient; this sequential form remains for single-location
    pulls and as the oracle the concurrent client is tested against.

    `deadline` caps the wall time between PAGES (a progress deadline): a
    wedged producer (RUNNING forever, producing nothing) must fail the
    pull — retryably — instead of hanging its consumer forever. None
    reads PRESTO_TPU_TASK_DEADLINE_S
    (default 600)."""
    from .exchange import ack_pages, fetch_pages

    if deadline is None:
        deadline = knobs.task_deadline_s()
    give_up = time.time() + deadline

    token = 0
    while True:
        pages, complete, ready = fetch_pages(
            uri, task_id, buffer_id, token, max_bytes=max_bytes
        )
        if pages:
            token += len(pages)
            for data in pages:
                yield data
            give_up = time.time() + deadline  # progress resets the clock
            if ack:
                ack_pages(uri, task_id, buffer_id, token)
            if complete:
                return
            continue
        if complete:
            return
        if not ready and time.time() >= give_up:
            raise RuntimeError(
                f"upstream task {task_id} on {uri} produced no "
                f"page within the {deadline:.0f}s task deadline "
                "(wedged worker?)"
            ) from None
