"""Single-node plan executor.

The analog of the reference's LocalExecutionPlanner + Driver (SURVEY.md
§3.3): walks the PlanNode tree bottom-up, executing each node as one or a
few fused device kernels over capacity-padded Pages.

Design points (TPU-first):
* Static shapes with adaptive retry — joins whose candidate count exceeds
  the planned output capacity are re-run with doubled capacity (the
  reference instead grows pages dynamically; XLA needs detect-and-retry).
* Capacities are bucketed to powers of two (`round_capacity`) and pages are
  shrunk after selective operators, so recompilation is bounded
  (the reference's adaptive batch sizing in PageFunctionCompiler).
* The executor is host-driven and *adaptive*: it sees real row counts
  between kernels, picks build/probe strategies accordingly — the eager
  analog of Presto's cost-based decisions with perfect cardinalities.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..expr import ir
from ..ops.aggregate import global_aggregate, grouped_aggregate_sorted
from ..ops.filter import compact, filter_page
from ..ops.join import (
    build_sorted,
    join_expand,
    join_n1,
    sorted_probe_layout,
)
from ..ops.sort import distinct_page, limit_page, sort_page, top_n
from ..expr.compiler import project_page
from ..obs.span import current as current_span
from ..obs.span import held, host_read, sent
from ..page import Block, Page, round_capacity
from ..plan import nodes as N

# a page of at most this many slots is never read back for its count: a
# blocking read cannot pay there (`_shrink_reads`; the streaming sink's
# partial pages, exec/stream.py)
SMALL_PAGE_ROWS = 1 << 14


class ExecutionError(RuntimeError):
    pass


def _last_written(page: Page) -> list:
    """What to wait for to know a node's output is whole, never the
    whole page: its count and the last array of its last column. One
    program writes them all; the eager forms (`_dyn_compact`, `_shrink`)
    count FIRST and gather the columns in order, and the device runs
    what it is sent in order."""
    arrays = [page.count]
    if page.blocks:
        arrays.append(jax.tree_util.tree_leaves(page.blocks[-1])[-1])
    return arrays


class Executor:
    def __init__(self, catalog, shrink: bool = True, jit: bool = True,
                 collector=None, pallas_groupby=None,
                 matmul_groupby=None):
        self.catalog = catalog
        self.shrink = shrink
        self.jit = jit
        # route eligible small-G aggregations through the Pallas kernel
        # (ops/pallas_groupby.py). None = auto: DEFAULT ON for TPU
        # backends — the reference's hot loop is its specialized group-by
        # (MultiChannelGroupByHash.java:54) and ours must be the Mosaic
        # path, not an opt-in — and OFF on CPU, where interpret mode
        # would crawl. The `pallas_groupby` session property forces
        # either way (resolved lazily so importing the executor never
        # initializes a backend).
        self.pallas_groupby = pallas_groupby
        # route eligible dense-key aggregations (G <= 4096) through the
        # one-hot-matmul MXU path (ops/matmul_agg.py) before falling back
        # to the sort strategy; same auto semantics as pallas_groupby
        self.matmul_groupby = matmul_groupby
        # (plan node, static params) -> jitted kernel. Backed by the
        # PROCESS-WIDE LRU (exec/qcache.KERNEL_CACHE) keyed additionally
        # on (backend, jit flag): back-to-back queries from different
        # sessions reuse traced executables — the analog of the reference
        # caching compiled PageProcessors per plan (LocalExecutionPlanner
        # compiles once, Drivers reuse), promoted cross-query.
        # JAX's persistent compilation cache (qcache) additionally keeps
        # XLA executables on disk so restarts warm-start.
        self._backend = None  # resolved lazily (never init jax at import)
        # kernels over time-/context-dependent expressions (now(), ...)
        # bake the value at TRACE time and must not outlive this
        # executor: they stay in a per-executor dict (the pre-PR-8
        # compile-once scope) instead of the process-wide cache
        self._local_kernels: Dict = {}
        self._det_keys: Dict = {}  # kernel key -> is-deterministic verdict
        # EXPLAIN ANALYZE support (exec/stats.py); None = no accounting
        self.collector = collector
        self._retries = 0  # adaptive-capacity re-runs since last snapshot
        # runtime dynamic filters (exec/dynfilter.py): per-query registry
        # of build-side summaries consumed by probe-side scans/filters
        from .dynfilter import DynamicFilterContext

        self.dyn_ctx = DynamicFilterContext()
        # session override (the `dynamic_filtering` session property);
        # PRESTO_TPU_DYNFILTER=0 disables engine-wide
        self.dynamic_filtering = True
        # TABLESAMPLE determinism: per-Sample-node running row offset
        # (streaming batches) + a per-worker/per-split salt set by the
        # fragment executors, mixed into the sample hash so positional
        # masks never repeat across batches/workers (ops/filter.py)
        self._sample_pos: Dict[int, int] = {}
        self.sample_salt = 0
        # hash-sort group-bys that outgrew their first guess: plan node
        # -> the capacity its groups needed (_exec_aggregate)
        from .qcache import LRUCache

        self._agg_groups = LRUCache(max_entries=4096, name="agg_groups")
        self._inputs_wall_s = 0.0  # _run: walls of the inputs run so far

    def _kernel(self, name, key, make_fn):
        """Compile-once cache for per-node kernels. jax.jit retraces per
        input shape bucket automatically; `key` carries the static config
        (the node itself plus capacity-like ints); `name` is the call
        site's (XLA calls the program `jit_<name>`, and a profile lists
        device time under it). The store is the
        process-wide bounded LRU in exec/qcache.py, keyed additionally on
        (backend, jit) — kernels close over plan-node config only, never
        the catalog, so cross-executor reuse is sound."""
        from .qcache import (
            KERNEL_CACHE,
            enable_persistent_compile_cache,
            plan_is_deterministic,
        )

        if self._backend is None:
            enable_persistent_compile_cache()
            self._backend = jax.default_backend()
        # determinism is static per key: memoize so the per-batch hot
        # path pays one dict probe, not a plan-subtree walk per call
        det = self._det_keys.get(key)
        if det is None:
            det = self._det_keys[key] = plan_is_deterministic(key)
        if not det:
            # now()/current_date/... are CONSTANTS baked at trace time:
            # sharing such a kernel across sessions would serve the
            # first trace's clock forever. Per-executor scope matches
            # the pre-cache behavior (one session reuses its own trace).
            fn = self._local_kernels.get(key)
            if fn is None:
                fn = self._build_kernel(name, make_fn)
                self._local_kernels[key] = fn
            return fn
        gkey = (self._backend, self.jit, key)
        fn = KERNEL_CACHE.get(gkey)
        if fn is None:
            fn = self._build_kernel(name, make_fn)
            KERNEL_CACHE.put(gkey, fn)
        return fn

    def _build_kernel(self, name, make_fn):
        """Cache-fill: name the function for its call site and jit
        (compilation itself is lazy, paid at the first call, and booked
        by obs/span.py's compile listener on the span open then)."""
        fn = make_fn()
        fn.__name__ = fn.__qualname__ = name
        if self.jit:
            fn = jax.jit(fn)
        return fn

    def _kernel_guarded(self, breaker_name, name, key, make_fn, *args):
        """Run a jitted kernel (`name`, `key`, `make_fn`: as `_kernel`)
        under a kernel-fault circuit breaker
        (exec/breaker.py). The op layer consults `BREAKERS.allow(name)`
        at TRACE time to pick the experimental path vs. the safe XLA
        composition, so the breaker decision is part of the cache key.
        A fault records a failure and retries ONCE with the fallback
        FORCED — even when the breaker hasn't opened yet (streak below
        threshold, or PRESTO_TPU_BREAKER_DISABLE=1), the call that just
        faulted must still degrade rather than fail the query."""
        import contextlib

        from .breaker import BREAKERS

        for attempt in (0, 1):
            if attempt == 0:
                allowed = BREAKERS.allow(breaker_name)
                ctx = contextlib.nullcontext()
            else:
                allowed = False
                ctx = BREAKERS.forced_fallback(breaker_name)
            with ctx:
                try:
                    fn = self._kernel(
                        name, (key, breaker_name, allowed), make_fn
                    )
                    out = fn(*args)
                except Exception as exc:
                    if attempt == 0 and allowed:
                        # the experimental path faulted: count it and
                        # retry on the forced fallback
                        BREAKERS.record_failure(breaker_name, repr(exc))
                        continue
                    if attempt:
                        # the FALLBACK failed right after the experimental
                        # path did: a semantic / user error, not a kernel
                        # fault — neutralize the breaker hit so a bad
                        # query can't degrade the kernel for the process
                        BREAKERS.record_success(breaker_name)
                    raise
            if allowed:
                BREAKERS.record_success(breaker_name)
            return out

    # -- public --
    def run(self, node: N.PlanNode) -> Page:
        self.dyn_ctx.reset()  # filters are per-query state
        page = self._run(node)
        return page

    def rows(self, node: N.PlanNode) -> List[tuple]:
        return self.run(node).to_pylist()

    # -- dispatch --
    def _run_children(self, node: N.PlanNode, pos=None) -> List[Page]:
        """Execute a node's children — BUILD SIDE FIRST for dynamic-filter
        joins, so the derived filter is published before the probe side's
        scans run (the single-process analog of the reference's
        LocalDynamicFiltersCollector ordering). `pos` is the node's
        position in the plan where spans are on (`_run`), else None."""
        if (
            isinstance(node, (N.Join, N.SemiJoin))
            and getattr(node, "dynamic_filters", ())
        ):
            build = self._run(node.children[1], pos and pos + ".1")
            self._publish_dynamic_filters(node, build)
            probe = self._run(node.children[0], pos and pos + ".0")
            return [probe, build]
        return [
            self._run(c, pos and f"{pos}.{i}")
            for i, c in enumerate(node.children)
        ]

    def _run(self, node: N.PlanNode, pos=None) -> Page:
        """One plan node: its inputs, then the node. Where a trace is
        open on this thread (obs/span.py; never under PRESTO_TPU_TRACE=0)
        the node runs inside a span named by its class, nested as the
        plan is, with its position in the plan (`0.1.0`: child indices
        from the root) and its strategy note as attributes; what the
        node books (`host_read`, compiles) lands on it. The span's two
        clock readings are the node's one timing site: with a collector
        (EXPLAIN ANALYZE) they give its wall too."""
        cur = current_span()
        collector = self.collector
        if cur is None and collector is None:
            return self._run_node(node, None)[0]
        import time

        span = t0 = None
        if cur is not None:
            pos = pos or "0"
            span = cur[0].enter(type(node).__name__, pos=pos)
        else:
            t0 = time.perf_counter()
        if collector is not None:
            # the inputs' walls, to take off this node's: one executor
            # serves one EXPLAIN ANALYZE (session._collector_executor)
            below, self._inputs_wall_s = self._inputs_wall_s, 0.0
        try:
            out, pages, retries = self._run_node(node, pos)
            if collector is not None:
                rows_in, rows_out = self._row_counts(pages, out)
        except BaseException:
            if span is not None:
                cur[0].leave(span, "error")
            raise
        if span is not None:
            if isinstance(node, (N.Join, N.SemiJoin)) and len(pages) == 2:
                # rows in and out, where the host holds them already
                # (a `_shrink` below or here read them): never a read
                for name, page in zip(
                    ("probe_rows", "build_rows", "out_rows"), (*pages, out)
                ):
                    n = held(page.count)
                    if n is not None:
                        span.attrs[name] = int(n)
            if retries:
                span.attrs["retries"] = retries
            # when the node's output is READY goes onto its span later,
            # from a watcher thread: `Trace.device_spans`
            sent(_last_written(out), "device")
            wall = cur[0].leave(span).wall_s
        else:
            wall = time.perf_counter() - t0
        if collector is not None:
            from .stats import page_device_bytes

            collector.record(
                node, max(0.0, wall - self._inputs_wall_s), rows_in,
                rows_out, page_device_bytes(out), retries,
            )
            self._inputs_wall_s = below + wall
        return out

    def _run_node(self, node: N.PlanNode, pos):
        """(output, input pages, adaptive re-runs of this node)."""
        pages = self._run_children(node, pos)
        retries_before = self._retries
        out = self.exec_node(node, *pages)
        return out, pages, self._retries - retries_before

    def _row_counts(self, pages, out: Page):
        """A node's rows in and out for the collector."""
        if getattr(self.collector, "sync_counts", True):
            # blocks until the kernel finishes
            return (
                sum(int(host_read(p.count)) for p in pages),
                int(host_read(out.count)),
            )
        # keep row counts as device scalars — each int() here is a
        # blocking host round trip per plan node (the cost PR-1's
        # _shrink already avoids); the collector resolves
        # them in one batch at query end. Wall then measures dispatch
        # + any syncs the node itself performs.
        return [p.count for p in pages], out.count

    def exec_node(self, node: N.PlanNode, *pages: Page) -> Page:
        """Apply one plan node to already-materialized input pages — the
        unit the distributed executor and the streaming driver both reuse."""
        method = getattr(self, f"_exec_{type(node).__name__.lower()}")
        return method(node, *pages)

    def _shrink(
        self, page: Page, node: "N.PlanNode" = None, n: Optional[int] = None
    ) -> Page:
        """Slice page capacity down to the live row count's bucket.

        Reading the count is a BLOCKING host sync: the host waits for
        everything queued on the device and then for a transfer, and the
        device idles until the next dispatch. So the sync is only paid
        when shrinking can plausibly win: the page is big AND the CBO
        expects the live count to be well under capacity. `n`: the count,
        where the caller read it already."""
        if not self._shrink_reads(page, node):
            return page
        if n is None:
            n = int(host_read(page.count))
        cap = round_capacity(max(n, 1))
        if cap >= page.capacity:
            return page
        idx = slice(0, cap)
        blocks = [b.take_rows(idx) for b in page.blocks]
        return Page(tuple(blocks), page.names, page.count)

    def _shrink_reads(self, page: Page, node: "N.PlanNode" = None) -> bool:
        """Whether `_shrink` pays its sync for this page (see there)."""
        if not self.shrink:
            return False
        if page.capacity <= SMALL_PAGE_ROWS:
            return False  # too small for shrinking to pay for a sync
        if node is not None:
            est = self._est_rows(node)
            if est is not None and est >= 0.5 * page.capacity:
                return False  # expected near-full: skip the sync
        return True

    def _node_plan_stats(self, node):
        """Memoized full CBO PlanStats for a node (column min/max/NDV —
        the keypack planner's input). Same keying/bounding rules as
        _est_rows."""
        cache = getattr(self, "_ps_cache", None)
        if cache is None:
            from .qcache import LRUCache

            # bounded LRU, not clear-on-threshold: a long session crossing
            # the old wholesale clear() triggered a recompute storm over
            # every live plan's stats
            cache = self._ps_cache = LRUCache(
                max_entries=1024, name="plan_stats"
            )
        key = (node,) + self._est_env()
        hit = cache.get(key, count=False)
        if hit is not None:
            return hit[0]
        try:
            from ..plan.stats import derive

            ps = derive(node, self.catalog)
        except Exception:  # noqa: BLE001 — estimation is best-effort
            ps = None
        cache.put(key, (ps,))
        return ps

    def _est_env(self) -> tuple:
        """Environment half of the estimate-cache keys: the feedback
        store's generation (a history record/invalidation must never let
        a live executor keep serving estimates derived from superseded
        observations) plus the mesh width (a DistributedExecutor shares
        this object as its local delegate; per-shard sizing decisions
        must not alias across mesh shapes)."""
        from ..plan.history import plan_env_token

        return plan_env_token(), getattr(self, "mesh_n", 1)

    # -- composite-key packing (ops/keypack.py) --
    def _keypack_plan(self, node, keys, page: Page, equality_only=False,
                      allow_hashed=False, single_lane=False,
                      n_order_keys=0):
        """Choose a packing strategy for one order-sensitive node from the
        input page's blocks (types, nullability, dictionaries) plus the
        child's CBO column stats (sampled min/max tightens 64-bit keys;
        sampled lanes carry a runtime range check). Returns None when the
        keys don't pack — the node runs its legacy kernel."""
        from ..ops.keypack import (
            KeyInfo,
            key_info_from_block,
            keypack_enabled,
            plan_keypack,
        )
        from ..plan.stats import storage_bounds

        if not keypack_enabled():
            return None
        ps = self._node_plan_stats(node.children[0])
        infos = []
        for k in keys:
            e = getattr(k, "expr", k)
            typ = getattr(e, "type", None)
            if typ is None:
                return None
            if isinstance(e, ir.ColumnRef) and e.name in page.names:
                b = page.block(e.name)
                lo = hi = None
                if ps is not None:
                    bounds = storage_bounds(ps.column(e.name), b.type)
                    if bounds is not None:
                        lo, hi = bounds
                infos.append(key_info_from_block(b, lo=lo, hi=hi))
            else:
                infos.append(KeyInfo(type=typ, nullable=True))
        try:
            return plan_keypack(
                keys,
                infos,
                equality_only=equality_only,
                allow_hashed=allow_hashed,
                single_lane=single_lane,
                n_order_keys=n_order_keys,
            )
        except Exception:  # noqa: BLE001 — planning is best-effort
            return None

    def _run_packed(self, node, breaker_name: str, label: str, make_fn,
                    page: Page, plan, key=None):
        """Attempt one packed kernel behind its circuit breaker. Returns
        the output page, or None when the caller must run the legacy
        kernel (breaker open, kernel fault, or the plan's runtime range
        check tripped — sampled CBO bounds missed / a hash collided,
        which is expected adaptivity rather than a kernel fault). `key`
        is what the kernel closes over besides the plan, where that is
        less than the node: a statement that differs only in a literal
        of the node's subtree then hits the cached program."""
        key = node if key is None else key
        from .breaker import BREAKERS

        if not BREAKERS.allow(breaker_name):
            return None
        if plan.host_sort:
            # host-routed plans run numpy on the host. Commit
            # mesh-sharded pages (gathered from the distributed
            # executor) to one device first — cheap on the CPU backend,
            # and host-sort plans only exist there.
            page = self._commit_single_device(page)
        try:
            if plan.host_sort:
                # EAGER, never jitted: under jit the host step becomes a
                # jax.pure_callback, which deadlocks on the single-device
                # CPU runtime (main thread blocks synchronizing the
                # kernel while the callback thread starves — the PR 2
                # ORDER BY >= 14k wedge). Eagerly, ops/sort.py calls
                # numpy directly and there is nothing to deadlock; the
                # sort dominates the cost, so losing jit fusion of the
                # cheap pack arithmetic is noise.
                fn = make_fn()
            else:
                fn = self._kernel(label, (key, label, plan), make_fn)
            out, ok = fn(page)
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            BREAKERS.record_failure(breaker_name, repr(exc))
            return None
        if ok is not None and not bool(host_read(ok)):
            self._strategy_note(
                node, f"keypack={plan.strategy}->legacy(range)"
            )
            return None
        BREAKERS.record_success(breaker_name)
        self._strategy_note(node, f"keypack={plan.strategy}")
        return out

    @staticmethod
    def _commit_single_device(page: Page) -> Page:
        """Move a page's arrays onto ONE device when any block is
        mesh-sharded. No-op for already-single-device pages."""
        try:
            multi = any(
                len(b.data.devices()) > 1 for b in page.blocks
            )
        except Exception:  # noqa: BLE001 — non-Array leaves: leave as-is
            return page
        if not multi:
            return page
        dev = jax.devices()[0]
        blocks = tuple(
            Block(
                jax.device_put(b.data, dev),
                b.type,
                None if b.valid is None else jax.device_put(b.valid, dev),
                b.dict_id,
            )
            for b in page.blocks
        )
        count = page.count
        if hasattr(count, "devices"):
            count = jax.device_put(count, dev)
        return Page(blocks, page.names, count)

    def _est_rows(self, node):
        """CBO row estimate for a node's output (cached per plan node).

        Keyed by the node OBJECT (kept referenced by the cache, so ids
        cannot be recycled mid-flight) and bounded by LRU eviction: a
        long-lived server session executes unboundedly many plans, and
        the old clear-everything-at-threshold caused recompute storms."""
        cache = getattr(self, "_est_cache", None)
        if cache is None:
            from .qcache import LRUCache

            cache = self._est_cache = LRUCache(
                max_entries=4096, name="row_est"
            )
        key = (node,) + self._est_env()
        hit = cache.get(key, count=False)
        if hit is not None:
            return hit[0]
        try:
            from ..plan.stats import derive

            est = float(derive(node, self.catalog).rows)
        except Exception:  # noqa: BLE001 — estimation is best-effort
            est = None
        cache.put(key, (est,))
        return est

    # -- dynamic filters (exec/dynfilter.py) --

    def _dyn_enabled(self) -> bool:
        from .breaker import BREAKERS
        from .dynfilter import dynamic_filtering_enabled

        return (
            self.dynamic_filtering
            and dynamic_filtering_enabled()
            and BREAKERS.allow("dynamic_filter")
        )

    def _est_join_rows(self, node, build: Optional[Page]):
        """The CBO's estimate of a join's output, held against the build
        side that arrived: a page of `capacity` slots holds at most that
        many rows, and where that is under the build's own estimate (a
        runtime filter below it pruned what the CBO cannot see: Q18's
        orders, cut to the semi-join's few hundred keys) the output is
        taken to shrink with it. A build at or over its estimate, or one
        that is no page (spilled, sharded), leaves the figure as it is.
        None where there are no statistics."""
        est = self._est_rows(node)
        if est is None or build is None:
            return est
        build_est = self._est_rows(node.children[1])
        if not build_est or build.capacity >= build_est:
            return est
        return est * build.capacity / build_est

    def _dyn_worthwhile(self, node, build: Optional[Page] = None) -> bool:
        """CBO benefit gate: deriving costs a build-side pass plus a probe
        mask, so skip when the join barely filters (est output close to
        the probe input — e.g. an unfiltered FK->PK join keeps every
        row). Stats-less plans derive anyway (best-effort)."""
        import os

        if os.environ.get("PRESTO_TPU_DYNFILTER_FORCE") == "1":
            return True
        max_sel = float(
            os.environ.get("PRESTO_TPU_DYNFILTER_MAX_SEL", "0.7")
        )
        out_est = self._est_join_rows(node, build)
        probe_est = self._est_rows(node.children[0])
        if out_est is None or probe_est is None or probe_est <= 0:
            return True
        return out_est < max_sel * probe_est

    def _publish_dynamic_filters(self, node, build_page: Page) -> None:
        """Derive per-key summaries from a materialized build side and
        publish them under the planner-assigned ids. Behind the
        `dynamic_filter` breaker: a faulting derivation degrades the whole
        path to legacy no-filter execution, never fails the query."""
        import time

        from .breaker import BREAKERS
        from ..expr.compiler import evaluate
        from .dynfilter import derive_filter

        if not self._dyn_enabled() or not self._dyn_worthwhile(
            node, build_page
        ):
            return
        keys = (
            node.right_keys
            if isinstance(node, N.Join)
            else node.source_keys
        )
        notes = []
        t0 = time.perf_counter()
        live = build_page.live_mask()
        for fid, i, _consumed in node.dynamic_filters:
            try:
                val = evaluate(keys[i], build_page)
                df = derive_filter(val, live)
            except Exception as exc:  # noqa: BLE001 — degrade, don't fail
                BREAKERS.record_failure("dynamic_filter", repr(exc))
                return
            if df is None:
                continue
            BREAKERS.record_success("dynamic_filter")
            self.dyn_ctx.publish(fid, df)
            notes.append(f"{fid}={df.describe()}")
        if notes and self.collector is not None:
            ms = (time.perf_counter() - t0) * 1e3
            self._append_detail(
                node, f"df[{', '.join(notes)}, derive {ms:.1f}ms]"
            )

    def _append_detail(self, node, txt: str) -> None:
        if self.collector is None:
            return
        s = self.collector.stats_for(node)
        if txt not in s.detail:
            s.detail = f"{s.detail}; {txt}" if s.detail else txt

    def _dyn_compact(self, page: Page, keep) -> Tuple[Page, int]:
        """Compact + shrink for dynamic-filter masks, which are typically
        VERY selective, and whose survivors `_shrink`'s CBO gate (it knows
        nothing about runtime filters) would leave at page capacity. The
        survivor count is read FIRST (pruned-row accounting needs it
        anyway; the one read made here), so no column is gathered at page
        capacity: a gather costs the TPU ~7.5 ns an index whatever it
        gathers from, a 6M-row sort 8 ms. A mask that keeps a sixteenth of
        the page or less runs `compact_few` (no sort at all); any other
        sorts the kept rows first as `compact` does and gathers the
        count's bucket of that permutation. The node's span says which
        (`compact`, `compact_capacity`). On the CPU backend the whole
        compaction routes through ONE host `np.flatnonzero` pass + a small
        gather instead of XLA's comparison sort (the keypack host-sort
        pattern, ops/keypack.py). Returns (page, survivor count)."""
        import numpy as np

        from ..ops.filter import compact_few, kept_first_permutation

        keep = keep & page.live_mask()
        if jax.default_backend() == "cpu":
            nz = np.flatnonzero(host_read(keep))
            n = int(nz.size)
            cap = round_capacity(max(n, 1))
            idx = np.zeros(cap, np.int64)
            idx[:n] = nz
            form, perm = "host", jnp.asarray(idx)
            count = jnp.asarray(n, dtype=jnp.int32)
        else:
            # the page leaves with THIS count object: the host's copy
            # stays on it (`obs.span.held`), so nothing above reads the
            # count again
            count = jnp.sum(keep, dtype=jnp.int32)
            n = int(host_read(count))
            cap = min(round_capacity(max(n, 1)), page.capacity)
            # a sixteenth: the searches gather cap * log2(rows) indices
            # where the sort costs what the page's rows do (on a 6M-row
            # page the two cross near 1/128: PERF.md section 6, PR 34)
            if cap * 16 <= page.capacity:
                form, perm = "few", None
            else:
                form, perm = "sort", kept_first_permutation(keep)[:cap]
        self._span_note(compact=form, compact_capacity=cap)
        if perm is None:
            blocks = compact_few(page, keep, cap=cap).blocks
        else:
            blocks = tuple(b.take_rows(perm) for b in page.blocks)
        return Page(blocks, page.names, count), n

    def _dyn_mask_page(self, node, page: Page, entries, where: str) -> Page:
        """AND every available dynamic-filter mask over `page` and compact.
        `entries` is [(fid, value_source)] where value_source is a channel
        name or a key RowExpression. No-ops when nothing is published."""
        from .breaker import BREAKERS
        from ..expr.compiler import evaluate

        picked = []
        for fid, src in entries:
            df = self.dyn_ctx.get(fid)
            if df is not None:
                picked.append((fid, src, df))
        if not picked or not self._dyn_enabled():
            return page
        try:
            keep = None
            for fid, src, df in picked:
                val = (
                    page.block(src)
                    if isinstance(src, str)
                    else evaluate(src, page)
                )
                m = df.mask(val)
                keep = m if keep is None else (keep & m)
            before = int(host_read(page.count))
            out, n = self._dyn_compact(page, keep)
            pruned = before - n
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            BREAKERS.record_failure("dynamic_filter", repr(exc))
            return page
        BREAKERS.record_success("dynamic_filter")
        self._note_dyn_pruned(
            node, picked[0][0], pruned, where,
            ",".join(f"{fid}:{df.strategy}" for fid, _s, df in picked),
        )
        return out

    def _note_dyn_pruned(
        self, node, lead_fid: str, pruned: int, where: str, descs: str
    ) -> None:
        """Book pruned rows (combined mask attributed once, to the lead
        filter id) and refresh the node's EXPLAIN ANALYZE tag with the
        accumulated total (streaming overwrites it per batch)."""
        import re

        self.dyn_ctx.note_pruned(lead_fid, pruned, where)
        cur = current_span()
        if cur is not None:
            # summed: the streaming driver applies the mask per batch
            attrs = cur[1].attrs
            attrs["dyn_pruned"] = attrs.get("dyn_pruned", 0) + pruned
            attrs["dyn_strategy"] = descs
        if self.collector is None:
            return
        book = self.dyn_ctx.scan_pruned if where == "scan" else (
            self.dyn_ctx.preprobe_pruned
        )
        total = book.get(lead_fid, pruned)
        s = self.collector.stats_for(node)
        tag = f"dyn_pruned={total:,} ({descs})"
        s.detail = (
            re.sub(r"dyn_pruned=[^;]*", tag, s.detail)
            if "dyn_pruned=" in s.detail
            else (f"{s.detail}; {tag}" if s.detail else tag)
        )

    def _apply_scan_masks(
        self, node: N.TableScan, page: Page, hint_entries: bool = False
    ) -> Page:
        """Scan-level dynamic pruning. Default: entries marked apply_mask
        (no Filter above fuses them). With `hint_entries`, ONLY the
        hint-only entries — the distributed executor applies those at the
        scan because its SPMD filter stages run pre-compiled kernels that
        cannot see runtime filters (apply-marked entries already ran in
        _exec_tablescan; re-applying them would pay a second compaction)."""
        entries = [
            (fid, ch)
            for fid, ch, _src, apply in node.dynamic_filters
            if apply != hint_entries
        ]
        if not entries:
            return page
        return self._dyn_mask_page(node, page, entries, "scan")

    def _apply_preprobe(self, node, probe: Page) -> Page:
        """On-device pre-probe filter for produced ids with NO scan
        consumer — join_n1/semi_match_mask then see only surviving rows."""
        keys = (
            node.left_keys if isinstance(node, N.Join) else node.probe_keys
        )
        entries = [
            (fid, keys[i])
            for fid, i, consumed in getattr(node, "dynamic_filters", ())
            if not consumed
        ]
        if not entries:
            return probe
        return self._dyn_mask_page(node, probe, entries, "preprobe")

    # -- physical nodes (fragmented plans executed single-node) --
    def _exec_exchange(self, node, page: Page) -> Page:
        return page  # all exchange kinds are identities on a single worker

    def _exec_aggfinalize(self, node, page: Page) -> Page:
        from ..ops.aggregate import apply_avg_post

        return apply_avg_post(page, node.aggs, node.post)

    # -- leaf --
    def _exec_singlerow(self, node: N.SingleRow) -> Page:
        import numpy as np

        blk = Block.from_numpy(np.zeros(1, dtype=np.int64), T.BIGINT)
        return Page((blk,), (node.channel,), 1)

    def _exec_tablescan(self, node: N.TableScan) -> Page:
        src = self.catalog.page(node.table)
        blocks = []
        names = []
        for ch, col, _typ in node.columns:
            blocks.append(src.block(col))
            names.append(ch)
        page = Page(tuple(blocks), tuple(names), src.count)
        if node.dynamic_filters:
            page = self._apply_scan_masks(node, page)
        return page

    # -- stateless row ops --
    def _exec_unnest(self, node: N.Unnest, page: Page) -> Page:
        from ..ops.unnest import unnest_page

        fn = self._kernel(
            "unnest", node,
            lambda: lambda p: unnest_page(
                p, node.array_exprs, node.elem_channels,
                node.ordinality_channel,
            ),
        )
        return self._shrink(fn(page), node)

    def _exec_sample(self, node: N.Sample, page: Page) -> Page:
        from ..ops.filter import sample_page

        # global row position of this batch: per-node running offset
        # (advanced by CAPACITY, not count, so it needs no host sync) +
        # the per-worker/per-split salt — the same positional mask must
        # never repeat across batches or workers (Bernoulli, not
        # systematic sampling). Offset is a traced argument, so the
        # compiled kernel is shared across batches.
        pos = self._sample_pos.get(id(node), 0)
        self._sample_pos[id(node)] = pos + page.capacity
        offset = jnp.asarray(
            (self.sample_salt + pos) & 0xFFFFFFFFFFFFFFFF, jnp.uint64
        )
        fn = self._kernel(
            "sample", node,
            lambda: lambda p, off: sample_page(
                p, node.fraction, node.seed, off
            ),
        )
        return self._shrink(fn(page, offset), node)

    def _exec_filter(self, node: N.Filter, page: Page) -> Page:
        if node.dynamic_filters and any(
            self.dyn_ctx.get(fid) is not None
            for fid, _ch in node.dynamic_filters
        ):
            return self._exec_filter_dyn(node, page)
        from ..ops.filter import LARGE_PAGE_ROWS, compact_few, keep_mask

        if page.capacity >= LARGE_PAGE_ROWS and self._shrink_reads(page, node):
            # the count `_shrink` would read, read BEFORE the compaction:
            # a predicate that keeps a sixteenth or less of a large page
            # (Q18's HAVING: ~100 of 2^24 slots) gathers only what it keeps
            mask = self._kernel(
                "filter_mask", ("filter_mask", node),
                lambda: lambda p: keep_mask(p, node.predicate),
            )
            keep, count = mask(page)
            cap = round_capacity(max(int(host_read(count)), 1))
            if cap * 16 <= page.capacity:
                return compact_few(page, keep, cap=cap)
        fn = self._kernel(
            "filter", node, lambda: lambda p: filter_page(p, node.predicate)
        )
        return self._shrink(fn(page), node)

    def _exec_filter_dyn(self, node: N.Filter, page: Page) -> Page:
        """Filter with fused dynamic-filter masks: ONE compaction pass for
        the predicate AND every published runtime filter (the fusion that
        makes dynamic pruning free of extra compactions). Runs eagerly —
        filter arrays are per-query runtime values, not plan constants."""
        from .breaker import BREAKERS
        from ..expr.compiler import evaluate
        from ..ops.filter import compact

        v = evaluate(node.predicate, page)
        keep = v.data
        if v.valid is not None:
            keep = keep & v.valid
        try:
            dmask = None
            picked = []
            for fid, ch in node.dynamic_filters:
                df = self.dyn_ctx.get(fid)
                if df is None:
                    continue
                m = df.mask(page.block(ch))
                dmask = m if dmask is None else (dmask & m)
                picked.append((fid, df))
            if dmask is None:
                return self._shrink(compact(page, keep), node)
            live_keep = keep & page.live_mask()
            would_keep = jnp.sum(live_keep.astype(jnp.int32))
            out, n = self._dyn_compact(page, live_keep & dmask)
            pruned = int(host_read(would_keep)) - n
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            BREAKERS.record_failure("dynamic_filter", repr(exc))
            fn = self._kernel(
                "filter", node,
                lambda: lambda p: filter_page(p, node.predicate),
            )
            return self._shrink(fn(page), node)
        BREAKERS.record_success("dynamic_filter")
        self._note_dyn_pruned(
            node, picked[0][0], pruned, "scan",
            ",".join(f"{fid}:{df.strategy}" for fid, df in picked),
        )
        return out

    def _exec_project(self, node: N.Project, page: Page) -> Page:
        fn = self._kernel(
            "project", node,
            lambda: lambda p: project_page(p, node.exprs, node.names),
        )
        out = fn(page)
        # the same live count by construction: keep the input's count
        # object, whose host copy (where a `_shrink` below read it) the
        # jitted program's output lacks (`obs.span.held`)
        return Page(out.blocks, out.names, page.count)

    def _exec_output(self, node: N.Output, page: Page) -> Page:
        blocks = tuple(page.block(c) for c in node.channels)
        return Page(blocks, tuple(node.titles), page.count)

    def _strategy_note(self, node, name: str) -> None:
        """Record which aggregation strategy ran (EXPLAIN ANALYZE
        surfaces it — the 4-strategy choice is the engine's hottest
        decision and should be observable, not guessed): on the node's
        span, which is this thread's innermost while the node executes,
        and in the collector's stats."""
        self._span_note(strategy=name)
        if self.collector is not None:
            self.collector.stats_for(node).detail = f"strategy={name}"

    @staticmethod
    def _span_note(**attrs) -> None:
        """Attributes for the executing node's span (this thread's
        innermost while the node runs): values the host already holds,
        never a read."""
        cur = current_span()
        if cur is not None:
            cur[1].attrs.update(attrs)

    # -- aggregation --
    def _exec_aggregate(self, node: N.Aggregate, page: Page) -> Page:
        if not node.group_exprs:
            fn = self._kernel(
                "global_aggregate", node,
                lambda: lambda p: global_aggregate(p, node.aggs, node.mask),
            )
            return fn(page)
        # the slots this node's groups needed the last time it ran the
        # sort strategy: past the hash-slot cap that attempt would read
        # the keys and inputs to the host (1.5 GB for Q18's subquery at
        # SF10) only to give up again
        from ..ops.pallas_groupby import HASH_MAX_GROUPS_HOST

        learned = self._agg_groups.get(node, count=False)
        small = learned is None or learned <= HASH_MAX_GROUPS_HOST
        if small and self._pallas_groupby_on():
            out = self._try_pallas_groupby(node, page)
            if out is not None:
                return out
        out = self._try_hash_groupby(node, page) if small else None
        if out is not None:
            return out
        if self.matmul_groupby is None:
            import jax

            self.matmul_groupby = jax.default_backend() == "tpu"
        if self.matmul_groupby:
            from ..ops.matmul_agg import maybe_matmul_grouped_aggregate

            # plain XLA, no breaker: ineligible shapes return None, and
            # an exception here is a bug that must fail the query
            out = maybe_matmul_grouped_aggregate(
                page, node.group_exprs, node.group_names, node.aggs,
                node.mask,
            )
            if out is not None:
                self._strategy_note(node, "mxu-matmul")
                return self._shrink(out, node)
        self._strategy_note(node, "hash-sort")
        # groups <= live rows; guess low and retry with the true group count
        # (returned regardless of the bound) on overflow — the adaptive-
        # capacity pattern used by all static-shape operators here. The
        # initial guess comes from the CBO's NDV estimate (free) instead
        # of a blocking count sync; page.capacity bounds it above.
        est = self._est_rows(node)
        guess = int(est) if est is not None else page.capacity
        # a large page takes the run-sum form, whose cost does not grow
        # with its slots: there the estimate stands, and Q18's 15M groups
        # run once, not as a 65,536-slot program first that is thrown away
        from ..ops.aggregate import RUNS_MIN_ROWS

        runs = page.capacity >= RUNS_MIN_ROWS
        first_cap = page.capacity if runs else 1 << 16
        max_groups = round_capacity(
            min(max(guess, 1), page.capacity, first_cap)
        )
        # the capacity this node outgrew its guess to the last time it
        # ran (a repeated statement's plan is the same object): start
        # there, or every execution runs the whole program twice and
        # throws the first away (Q18: 60M rows into 15M groups)
        if learned is not None:
            max_groups = min(learned, round_capacity(page.capacity))
        max_elems = 128  # collection-aggregate width (adaptive, like mg)
        while True:
            mg, me = max_groups, max_elems
            fn = self._kernel(
                "grouped_aggregate_sorted", (node, mg, me, runs),
                lambda: lambda p: grouped_aggregate_sorted(
                    p, node.group_exprs, node.group_names, node.aggs, mg,
                    node.mask, max_elems=me, runs=runs, status=runs,
                ),
            )
            out = fn(page)
            if runs:
                # the group count and the run-sum form's order test in
                # ONE read
                out, status = out
                true_groups, presorted = (int(x) for x in host_read(status))
            else:
                true_groups, presorted = int(host_read(out.count)), -1
            if true_groups > max_groups:
                max_groups = round_capacity(true_groups)
                self._agg_groups.put(node, max_groups)
                self._retries += 1
                continue
            if "$collect_need" in out.names:
                need = int(host_read(out.block("$collect_need").data[0]))
                if need > max_elems:
                    max_elems = round_capacity(need)
                    self._retries += 1
                    continue
                keep = [
                    (n, b)
                    for n, b in zip(out.names, out.blocks)
                    if n != "$collect_need"
                ]
                out = Page(
                    tuple(b for _, b in keep),
                    tuple(n for n, _ in keep),
                    out.count,
                )
            break
        # the count the loop read anyway, and the slots it last ran with
        self._span_note(groups=true_groups, max_groups=max_groups)
        if presorted >= 0:  # the run-sum form ran: did it skip its sort
            self._span_note(presorted=presorted == 1)
            self._append_detail(
                node, f"presorted={'true' if presorted else 'false'}"
            )
        if true_groups > HASH_MAX_GROUPS_HOST and learned is None:
            # no retry taught it (the first guess held): still past the
            # hash-slot cap, so the next execution skips that attempt
            self._agg_groups.put(node, max_groups)
        return self._shrink(out, node, true_groups)

    def _pallas_groupby_on(self) -> bool:
        """The `pallas_groupby` knob, resolved at first use (None = auto:
        on for the TPU backend)."""
        if self.pallas_groupby is None:
            import jax

            self.pallas_groupby = jax.default_backend() == "tpu"
        return self.pallas_groupby

    def _try_pallas_groupby(self, node: N.Aggregate, page: Page) -> Optional[Page]:
        """Dense small-G group-by (ops/pallas_groupby.py) as ONE program
        per plan shape, behind the pallas_groupby breaker. The static plan
        step refuses an ineligible shape before anything is traced or
        launched. The mask's scalar literals go in as operands and the
        kernel's key is what the body closes over with their values
        erased (qcache.lift_literals), so Q1 with a DELTA the process has
        not seen is a kernel-cache hit and compiles nothing. None =
        ineligible or faulted; the caller takes the next strategy."""
        from ..ops import pallas_groupby as pg
        from .breaker import BREAKERS, PROGRAMMING_ERRORS
        from .qcache import lift_literals, rebind_plan

        if not BREAKERS.allow("pallas_groupby"):
            return None
        if pg.plan_grouped_aggregate(page, node.group_exprs, node.aggs) is None:
            return None
        mask, operands = lift_literals(node.mask)
        # the cached program keeps what it is keyed on, not the node
        shape = (node.group_exprs, node.group_names, node.aggs)
        try:
            fn = self._kernel(
                "grouped_aggregate_pallas",
                ("grouped_aggregate_pallas", shape, mask),
                lambda: lambda p, ops: pg.maybe_grouped_aggregate(
                    p, *shape, rebind_plan(mask, ops)
                ),
            )
            out = fn(page, operands)
        except PROGRAMMING_ERRORS:
            raise
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            # a Mosaic lowering/compile failure (or any fault in trace or
            # run of the fused program) must degrade to the next
            # strategy, not fail the query; the breaker keeps the
            # faulting kernel from being re-attempted until its recovery
            # window, and its snapshot is where a run shows it faulted
            BREAKERS.record_failure("pallas_groupby", repr(exc))
            return None
        if out is None:
            return None
        BREAKERS.record_success("pallas_groupby")
        self._strategy_note(node, "pallas")
        cur = current_span()
        if cur is not None:
            if self.jit:
                cur[1].attrs["programs"] = 1
            cur[1].attrs["bound_literals"] = len(operands)
        return self._shrink(out, node)

    def _try_hash_groupby(self, node: N.Aggregate, page: Page) -> Optional[Page]:
        """Hash-slot grouped aggregation attempt (the PR 11 ceiling lift
        over the dense pallas path: arbitrary-valued keys, G to 512 on
        the kernel / 64k on the host twin) behind the pallas_groupby_hash
        breaker. None = ineligible or faulted; the caller falls through
        to the matmul / sort strategies unchanged."""
        from ..ops.pallas_groupby import maybe_grouped_aggregate_hash
        from .breaker import BREAKERS, PROGRAMMING_ERRORS

        if not BREAKERS.allow("pallas_groupby_hash"):
            return None
        try:
            out = maybe_grouped_aggregate_hash(
                page, node.group_exprs, node.group_names, node.aggs,
                node.mask,
            )
        except PROGRAMMING_ERRORS:
            raise
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            BREAKERS.record_failure("pallas_groupby_hash", repr(exc))
            return None
        if out is None:
            return None
        BREAKERS.record_success("pallas_groupby_hash")
        self._strategy_note(node, "hash-slot")
        return self._shrink(out, node)

    def _exec_distinct(self, node: N.Distinct, page: Page) -> Page:
        from ..expr.ir import ColumnRef

        key_exprs = tuple(
            ColumnRef(n, b.type) for n, b in zip(page.names, page.blocks)
        )
        # collection columns carry equality in companion arrays
        # (lengths/elem_valid/key_block) the packed key cannot see
        packable = all(
            b.lengths is None and b.key_block is None for b in page.blocks
        )
        plan = self._keypack_plan(
            node, key_exprs, page, equality_only=True, allow_hashed=True
        ) if packable else None
        if plan is not None:
            from ..ops.sort import distinct_packed

            out = self._run_packed(
                node, "keypack_distinct", "distinct_packed",
                lambda: lambda p: distinct_packed(p, plan),
                page, plan,
            )
            if out is not None:
                return self._shrink(out, node)
        if self.matmul_groupby is None:
            self.matmul_groupby = jax.default_backend() == "tpu"
        if self.matmul_groupby:
            # DISTINCT over dense keys = the MXU strategy's occupancy-only
            # shape (no channels, no dot) — skips the full hash-sort
            from ..expr.ir import ColumnRef
            from ..ops.matmul_agg import maybe_matmul_grouped_aggregate

            exprs = tuple(
                ColumnRef(n, b.type)
                for n, b in zip(page.names, page.blocks)
            )
            try:
                out = maybe_matmul_grouped_aggregate(
                    page, exprs, page.names, (), None
                )
            except Exception:  # noqa: BLE001 — shape-specific matmul
                # fallback, same contract as _exec_aggregate's
                out = None
            if out is not None:
                self._strategy_note(node, "mxu-occupancy")
                return self._shrink(out, node)
        self._strategy_note(node, "hash-sort")
        fn = self._kernel(
            "distinct", node, lambda: lambda p: distinct_page(p, p.capacity)
        )
        return self._shrink(fn(page), node)

    # -- joins --
    def _exec_join(self, node: N.Join, left: Page, right: Page) -> Page:
        if node.kind == "full" or (
            node.kind != "inner" and node.residual is not None
        ):
            return self._exec_outer_join(node, left, right)
        if node.dynamic_filters:
            left = self._apply_preprobe(node, left)
        right_names = right.names
        self._strategy_note(node, f"sorted-hash({sorted_probe_layout()})")
        if node.unique_build:
            out = self._kernel_guarded(
                "join_probe",
                "join_n1",
                (node, "n1"),
                lambda: lambda l, r: join_n1(
                    l,
                    build_sorted(r, node.right_keys),
                    node.left_keys,
                    right_names,
                    right_names,
                    kind=node.kind,
                ),
                left, right,
            )
            if node.residual is not None:
                if node.kind != "inner":
                    raise ExecutionError(
                        "residual on outer join not yet supported"
                    )
                out = filter_page(out, node.residual)
            return self._shrink(out, node)
        # general 1:N expansion with adaptive capacity retry; initial
        # guess = probe capacity vs CBO join-output estimate, held
        # against the build that arrived (no blocking count sync)
        est = self._est_join_rows(node, right)
        cap = round_capacity(
            max(left.capacity, int(est) if est is not None else 1, 1)
        )
        while True:
            c = cap
            out, overflow = self._kernel_guarded(
                "join_probe",
                "join_expand",
                (node, "expand", c),
                lambda: lambda l, r: join_expand(
                    l,
                    build_sorted(r, node.right_keys),
                    node.left_keys,
                    l.names,
                    [(n, n) for n in right_names],
                    out_capacity=c,
                    kind=node.kind,
                ),
                left, right,
            )
            over = int(host_read(overflow))
            if over == 0:
                break
            cap = round_capacity(cap + over)
            self._retries += 1
        self._note_expand(est, cap)
        if node.residual is not None:
            if node.kind != "inner":
                raise ExecutionError("residual on outer join not yet supported")
            out = filter_page(out, node.residual)
        return self._shrink(out, node)

    def _note_expand(self, est, cap: int) -> None:
        """On the join's span: the CBO's output estimate and the capacity
        `join_expand` last ran at (its cost follows the slots, not the
        rows: a capacity far over `out_rows` is an estimate far out)."""
        self._span_note(
            est_rows=None if est is None else int(est), out_capacity=cap
        )

    def _exec_outer_join(self, node: N.Join, left: Page, right: Page) -> Page:
        """LEFT join with a residual ON filter, and FULL OUTER join.

        Composition (reference handles these inside LookupJoinOperator +
        OuterLookupSource; here they compose from the same primitive
        kernels): inner-expand on the equi keys, apply the residual, then
        null-extend the probe rows (and for FULL the build rows) whose row
        id has no surviving match."""
        from ..ops.union import concat_pages, extend_with_nulls

        full = node.kind == "full"
        taken = set(left.names) | set(right.names)
        i = 0
        while f"$ridL{i}" in taken or f"$ridR{i}" in taken:
            i += 1
        rid_l, rid_r = f"$ridL{i}", f"$ridR{i}"
        left2 = self._with_row_id(left, rid_l)
        right2 = self._with_row_id(right, rid_r)
        rid_t = T.BIGINT

        bs = build_sorted(right2, node.right_keys)
        probe_out = list(left.names) + [rid_l]
        build_out = [(n, n) for n in right.names] + [(rid_r, rid_r)]
        est = self._est_rows(node)
        cap = round_capacity(
            max(left.capacity, int(est) if est is not None else 1, 1)
        )
        while True:
            expanded, overflow = join_expand(
                left2,
                bs,
                node.left_keys,
                probe_out,
                build_out,
                out_capacity=cap,
                kind="inner",
            )
            over = int(host_read(overflow))
            if over == 0:
                break
            cap = round_capacity(cap + over)
            self._retries += 1
        self._note_expand(est, cap)
        matched = (
            filter_page(expanded, node.residual)
            if node.residual is not None
            else expanded
        )
        matched = self._shrink(matched, node)

        def drop(page: Page, names) -> Page:
            keep = [
                (b, n)
                for b, n in zip(page.blocks, page.names)
                if n not in names
            ]
            return Page(
                tuple(b for b, _ in keep), tuple(n for _, n in keep), page.count
            )

        parts = [drop(matched, {rid_l, rid_r})]

        # probe rows with no surviving match -> null build columns
        bs_l = build_sorted(matched, (ir.ColumnRef(rid_l, rid_t),))
        left_un = join_n1(
            left2, bs_l, (ir.ColumnRef(rid_l, rid_t),), [], [], kind="anti"
        )
        parts.append(
            extend_with_nulls(
                drop(left_un, {rid_l}),
                right.names,
                [b.type for b in right.blocks],
                [b.dict_id for b in right.blocks],
            )
        )
        if full:
            bs_r = build_sorted(matched, (ir.ColumnRef(rid_r, rid_t),))
            right_un = join_n1(
                right2, bs_r, (ir.ColumnRef(rid_r, rid_t),), [], [], kind="anti"
            )
            parts.append(
                extend_with_nulls(
                    drop(right_un, {rid_r}),
                    left.names,
                    [b.type for b in left.blocks],
                    [b.dict_id for b in left.blocks],
                    prepend=True,
                )
            )
        return self._shrink(concat_pages(parts), node)

    @staticmethod
    def _attach_mark(probe: Page, mask, name: str) -> Page:
        return Page(
            probe.blocks + (Block(mask, T.BOOLEAN, None),),
            probe.names + (name,),
            probe.count,
        )

    def _exec_semijoin(self, node: N.SemiJoin, probe: Page, source: Page) -> Page:
        if node.dynamic_filters:
            probe = self._apply_preprobe(node, probe)
        self._strategy_note(node, f"sorted-hash({sorted_probe_layout()})")
        if node.residual is None:
            from ..ops.join import semi_match_mask

            def probe_fn(p, s):
                bs = build_sorted(s, node.source_keys)
                if node.mark is not None:
                    return semi_match_mask(p, bs, node.probe_keys)
                return join_n1(
                    p, bs, node.probe_keys, [], [],
                    kind="anti" if node.anti else "semi",
                )

            # ONE jitted sorted-hash kernel, as _exec_join does. Run
            # eagerly, the collision scan's while_loop re-traces and
            # compiles on every execution of the statement.
            out = self._kernel_guarded(
                "join_probe", "semi_join", (node, "semi"),
                lambda: probe_fn, probe, source,
            )
            if node.mark is not None:
                return self._attach_mark(probe, out, node.mark)
            return self._shrink(out, node)
        # residual EXISTS: expand probe x source on equi keys, filter the
        # residual, then keep probe rows whose row-id survived
        rid = self._row_id_channel(probe)
        probe2 = self._with_row_id(probe, rid)
        bs = build_sorted(source, node.source_keys)
        needed = self._residual_channels(node.residual)
        probe_out = [rid] + [n for n in probe.names if n in needed]
        build_out = [(n, n) for n in source.names if n in needed]
        cap = round_capacity(max(probe.capacity, 1))  # no count sync
        while True:
            expanded, overflow = join_expand(
                probe2,
                bs,
                node.probe_keys,
                probe_out,
                build_out,
                out_capacity=cap,
                kind="inner",
            )
            over = int(host_read(overflow))
            if over == 0:
                break
            cap = round_capacity(cap + over)
            self._retries += 1
        matched = filter_page(expanded, node.residual)
        matched = self._shrink(matched, node)
        rid_type = T.BIGINT
        bs2 = build_sorted(matched, (ir.ColumnRef(rid, rid_type),))
        if node.mark is not None:
            from ..ops.join import semi_match_mask

            mask = semi_match_mask(
                probe2, bs2, (ir.ColumnRef(rid, rid_type),)
            )
            return self._attach_mark(probe, mask, node.mark)
        out = join_n1(
            probe2,
            bs2,
            (ir.ColumnRef(rid, rid_type),),
            [],
            [],
            kind="anti" if node.anti else "semi",
        )
        # drop the row-id column
        blocks = tuple(
            b for b, n in zip(out.blocks, out.names) if n != rid
        )
        names = tuple(n for n in out.names if n != rid)
        return self._shrink(Page(blocks, names, out.count), node)

    def _row_id_channel(self, page: Page) -> str:
        i = 0
        while f"$rid{i}" in page.names:
            i += 1
        return f"$rid{i}"

    def _with_row_id(self, page: Page, name: str) -> Page:
        rid = Block(
            jnp.arange(page.capacity, dtype=jnp.int64), T.BIGINT, None, None
        )
        return Page(page.blocks + (rid,), page.names + (name,), page.count)

    def _residual_channels(self, e: ir.RowExpression) -> set:
        out: set = set()

        def walk(x):
            if isinstance(x, ir.ColumnRef):
                out.add(x.name)
            elif isinstance(x, ir.Call):
                for a in x.args:
                    walk(a)

        walk(e)
        return out

    def _exec_scalarapply(self, node: N.ScalarApply, page: Page, sub: Page) -> Page:
        n = int(host_read(sub.count))
        if n > 1:
            raise ExecutionError("scalar subquery returned more than one row")
        cap = page.capacity
        blocks = list(page.blocks)
        names = list(page.names)
        for b, (fname, ftype) in zip(sub.blocks, node.subquery.fields):
            if n == 0:
                data = jnp.zeros((cap,) + b.data.shape[1:], b.data.dtype)
                valid = jnp.zeros((cap,), jnp.bool_)
            else:
                data = jnp.broadcast_to(b.data[0], (cap,) + b.data.shape[1:])
                if b.valid is None:
                    valid = None
                else:
                    valid = jnp.broadcast_to(b.valid[0], (cap,))
            blocks.append(Block(data, b.type, valid, b.dict_id))
            names.append(fname)
        return Page(tuple(blocks), tuple(names), page.count)

    def _exec_window(self, node: N.Window, page: Page) -> Page:
        from ..ops.sort import SortKey
        from ..ops.window import window_op

        specs = tuple(SortKey(e) for e in node.partition_exprs) + tuple(
            node.order_keys
        )
        plan = self._keypack_plan(
            node, specs, page, single_lane=True,
            n_order_keys=len(node.order_keys),
        ) if specs else None
        if plan is not None:
            from ..ops.window import window_op_packed

            out = self._run_packed(
                node, "keypack_window", "window_packed",
                lambda: lambda p: window_op_packed(
                    p, node.partition_exprs, node.order_keys, node.funcs,
                    plan,
                ),
                page, plan,
            )
            if out is not None:
                return out
        fn = self._kernel(
            "window", node,
            lambda: lambda p: window_op(
                p, node.partition_exprs, node.order_keys, node.funcs
            ),
        )
        return fn(page)

    # -- ordering / limits --
    def _exec_sort(self, node: N.Sort, page: Page) -> Page:
        plan = self._keypack_plan(node, node.keys, page)
        if plan is not None:
            from ..ops.sort import sort_page_packed

            out = self._run_packed(
                node, "keypack_sort", "sort_packed",
                lambda: lambda p: sort_page_packed(p, node.keys, plan),
                page, plan, key=node.keys,
            )
            if out is not None:
                return out
        return self._kernel_guarded(
            "fused_sort",
            "sort",
            (node, "sort"),
            lambda: lambda p: sort_page(p, node.keys),
            page,
        )

    def _exec_topn(self, node: N.TopN, page: Page) -> Page:
        plan = self._keypack_plan(node, node.keys, page)
        if plan is not None:
            from ..ops.sort import top_n_packed

            out = self._run_packed(
                node, "keypack_topn", "top_n_packed",
                lambda: lambda p: top_n_packed(
                    p, node.keys, node.count, plan
                ),
                page, plan,
            )
            if out is not None:
                return out
        fn = self._kernel(
            "top_n", node, lambda: lambda p: top_n(p, node.keys, node.count)
        )
        return fn(page)

    def _exec_limit(self, node: N.Limit, page: Page) -> Page:
        return self._shrink(limit_page(page, node.count), node)

    def _exec_union(self, node: N.Union, *pages: Page) -> Page:
        from ..ops.union import concat_pages

        # positional union: output schema/names follow the first branch
        return self._shrink(concat_pages(pages, distinct=node.distinct), node)
