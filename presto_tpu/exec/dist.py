"""Distributed plan executor: fragmented plans over the device mesh.

Re-designed equivalent of the reference's distributed execution stack —
SqlQueryScheduler wiring stages to remote tasks (execution/scheduler/
SqlQueryScheduler.java:112), exchange producers/consumers (execution/buffer/,
operator/ExchangeClient.java) — collapsed TPU-first:

* A "stage" is a shard_map'd SPMD program over the worker mesh axis; every
  worker runs the same static-shape kernel on its shard of each Page.
* Exchanges are collectives: `repartition` = shuffle_write + lax.all_to_all
  (rides ICI), `gather`/`replicate` = device-global compaction (XLA inserts
  the all_gathers) — no serde, no HTTP, pages never leave HBM.
* The host drives adaptive capacity retry BETWEEN stages using per-shard
  live counts/overflow scalars — the static-shape replacement for the
  reference's grow-as-you-go pages and output-buffer backpressure.

The executor walks ONE physical tree (plan/fragment.py) and keeps every
subtree either sharded (SPage) or single/replicated (plain Page). All
relational kernels are the same ones the single-node Executor runs — a
sharded stage is literally the local kernel wrapped in shard_map.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import types as T
from ..expr import ir
from ..ops.aggregate import (
    apply_avg_post,
    global_aggregate,
    grouped_aggregate_sorted,
)
from ..ops.filter import compact, filter_page
from ..ops.join import build_sorted, join_expand, join_n1
from ..ops.sort import distinct_page, limit_page, top_n
from ..expr.compiler import project_page
from ..page import Block, Page, round_capacity
from ..parallel.exchange import exchange_by_hash
from ..parallel.mesh import (
    WORKER_AXIS,
    page_from_arrays,
    page_schema,
    page_to_arrays,
    shard_rows,
)
from ..plan import nodes as N
from ..plan.fragment import AggFinalize, Exchange
from .executor import ExecutionError, Executor


def _shard_map(step, mesh, in_specs, out_specs):
    return jax.shard_map(
        step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


@dataclasses.dataclass
class SPage:
    """Host handle to a mesh-sharded page: global arrays whose leading dim is
    n_shards * shard_capacity (shard i owns the contiguous chunk
    [i*cap, (i+1)*cap)), plus per-shard live counts. The device-resident
    analog of a stage's partitioned output buffers."""

    leaves: Tuple[jax.Array, ...]
    schema: tuple  # parallel.mesh.Schema
    counts: jax.Array  # (n_shards,) int32
    n_shards: int

    @property
    def shard_capacity(self) -> int:
        return self.leaves[0].shape[0] // self.n_shards

    def max_count(self) -> int:
        return int(jnp.max(self.counts))

    def total_count(self) -> int:
        return int(jnp.sum(self.counts))


class DistributedExecutor:
    """Executes a fragmented plan over `mesh`'s worker axis. Single/\
replicated subtrees delegate to the single-node Executor."""

    def __init__(self, catalog, mesh, axis: str = WORKER_AXIS,
                 collector=None, exchange_budget: Optional[int] = None):
        self.catalog = catalog
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.local = Executor(catalog, collector=collector)
        # estimate caches in the delegate key on mesh width (_est_env):
        # per-shard sizing derived at one width must not serve another
        self.local.mesh_n = self.n
        self._steps: Dict = {}
        self.collector = collector
        # per-shard byte budget for exchanged join intermediates: when an
        # exchange+join would materialize more than this, the hash space
        # is split into buckets processed one at a time (SURVEY §7
        # chunked ICI exchange; reference OutputBufferMemoryManager's
        # backpressure role). None = materialize whole intermediates.
        self.exchange_budget = exchange_budget
        self.exchange_events: List[dict] = []
        # ids of the devices that held a sharded stage's output: what a
        # run on real chips checks against the mesh width
        self.shard_devices: set = set()
        # dynamic filters shared with the local delegate: sharded joins
        # publish, and scans (which run through local.exec_node before
        # sharding) consume (exec/dynfilter.py)
        self.dyn_ctx = self.local.dyn_ctx

    # -- public --

    def run(self, root: N.PlanNode) -> Page:
        self.dyn_ctx.reset()  # filters are per-query state
        # per-query subtree memo: a node instance executes at most once
        # (the grouped-join probe may walk children the fallback path
        # revisits; without the memo that would double-execute stages)
        self._node_memo: Dict[int, object] = {}
        try:
            out = self._run(root)
        finally:
            self._node_memo = {}
        if isinstance(out, SPage):  # fragmenter gathers, but be safe
            out = self.to_single(out)
        return out

    # -- sharded step machinery --

    def _compile_step(self, cache_key, make_local, spages: Sequence[SPage],
                      rep_pages: Sequence[Page], n_extra: int):
        """Compile (or fetch) a shard_map'd stage.

        make_local(*local_pages, *rep_pages) -> Page | (Page, *extra_scalars).
        Returns (compiled_fn, out_schema). compiled_fn(leaves_tuples,
        counts_tuple, rep_pages) -> (out_leaves, out_counts, extra_vectors).
        """
        in_schemas = [sp.schema for sp in spages]
        rep_key = tuple((page_schema(rp), rp.capacity) for rp in rep_pages)
        key = (
            cache_key,
            tuple(in_schemas),
            tuple(sp.shard_capacity for sp in spages),
            rep_key,
            n_extra,
        )
        hit = self._steps.get(key)
        if hit is not None:
            return hit

        schema_box = {}

        def step(leaves_tuples, counts, reps):
            locals_ = [
                page_from_arrays(lv, sch, cnt[0])
                for lv, sch, cnt in zip(leaves_tuples, in_schemas, counts)
            ]
            out = make_local(*locals_, *reps)
            extras = ()
            if isinstance(out, tuple):
                out, *extras = out
            schema_box["out"] = page_schema(out)
            return (
                page_to_arrays(out),
                out.count.reshape(1),
                tuple(jnp.asarray(e).reshape(1) for e in extras),
            )

        smapped = _shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis), P()),
            out_specs=P(self.axis),
        )
        fn = jax.jit(smapped)

        # one abstract trace to learn the output schema without running
        # (global shapes — shard_map needs the mesh context for collectives)
        leaf_structs = tuple(
            tuple(
                jax.ShapeDtypeStruct(l.shape, l.dtype) for l in sp.leaves
            )
            for sp in spages
        )
        count_structs = tuple(
            jax.ShapeDtypeStruct((self.n,), jnp.int32) for _ in in_schemas
        )
        jax.eval_shape(fn, leaf_structs, count_structs, tuple(rep_pages))
        out_schema = schema_box["out"]

        self._steps[key] = (fn, out_schema)
        return fn, out_schema

    def _apply(self, cache_key, make_local, spages: Sequence[SPage],
               rep_pages: Sequence[Page] = (), n_extra: int = 0):
        """Run a local kernel as one SPMD stage over the mesh.

        Returns (SPage, extra_vectors) where each extra is an (n_shards,)
        array of per-shard scalars (overflow counts etc.)."""
        fn, out_schema = self._compile_step(
            cache_key, make_local, spages, rep_pages, n_extra
        )
        out_leaves, out_counts, extras = fn(
            tuple(sp.leaves for sp in spages),
            tuple(sp.counts for sp in spages),
            tuple(rep_pages),
        )
        self.shard_devices.update(d.id for d in out_counts.devices())
        sp = SPage(tuple(out_leaves), out_schema, out_counts, self.n)
        return sp, tuple(extras)

    # -- SPage <-> Page --

    def from_page(self, page: Page) -> SPage:
        """Contiguous row shards (leaf split assignment)."""
        padded, counts = shard_rows(page, self.n)
        return SPage(
            page_to_arrays(padded), page_schema(padded), counts, self.n
        )

    def to_single(self, sp: SPage) -> Page:
        """Collect all shards' live rows into one compacted Page (the root
        stage output buffer; XLA inserts the cross-device gathers)."""
        cap = sp.shard_capacity
        key = ("to_single", sp.schema, cap, self.n)
        fn = self._steps.get(key)
        if fn is None:

            def collect(leaves, counts):
                # count = full capacity: every position participates, and the
                # occupancy mask alone decides liveness (compact intersects
                # with live_mask, so a smaller count would drop real rows)
                page = page_from_arrays(
                    leaves, sp.schema, self.n * cap
                )
                occ = (
                    jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]
                ).reshape(-1)
                return compact(page, occ)

            fn = jax.jit(collect)
            self._steps[key] = fn
        out = fn(sp.leaves, sp.counts)
        return self.local._shrink(out)

    def _shrink_sp(self, sp: SPage) -> SPage:
        """Slice every shard down to the live-count bucket (bounded
        recompilation, like Executor._shrink but uniform across shards)."""
        cap = sp.shard_capacity
        new_cap = round_capacity(max(sp.max_count(), 1))
        if new_cap >= cap:
            return sp
        key = ("shrink", sp.schema, cap, new_cap, self.n)
        fn = self._steps.get(key)
        if fn is None:

            def shrink(leaves):
                return tuple(
                    l.reshape((self.n, cap) + l.shape[1:])[:, :new_cap]
                    .reshape((self.n * new_cap,) + l.shape[1:])
                    for l in leaves
                )

            fn = jax.jit(shrink)
            self._steps[key] = fn
        return SPage(fn(sp.leaves), sp.schema, sp.counts, self.n)

    # -- dispatch --

    def _run(self, node: N.PlanNode):
        memo = getattr(self, "_node_memo", None)
        if memo is not None and id(node) in memo:
            return memo[id(node)]
        out = self._run_timed(node)
        if memo is not None:
            memo[id(node)] = out
        return out

    def _run_timed(self, node: N.PlanNode):
        if self.collector is None:
            return self._run_inner(node)
        import time

        from .stats import page_device_bytes

        t0 = time.perf_counter()
        out = self._run_inner(node)
        if isinstance(out, SPage):
            rows = out.total_count()  # blocks until shards finish
            nbytes = sum(l.size * l.dtype.itemsize for l in out.leaves)
        else:
            rows = int(out.count)
            nbytes = page_device_bytes(out)
        wall = time.perf_counter() - t0
        # child time is recorded by the recursive call; subtract it so each
        # node's number is self time (the single-node path measures the same
        # way because exec_node receives materialized inputs)
        child_wall = sum(
            (self.collector.lookup(c) or type("S", (), {"wall_s": 0})).wall_s
            for c in node.children
        )
        self.collector.record(
            node, max(wall - child_wall, 0.0), 0, rows, nbytes
        )
        return out

    def _run_inner(self, node: N.PlanNode):
        m = getattr(self, f"_d_{type(node).__name__.lower()}", None)
        if m is not None:
            return m(node)
        # nodes without a distributed handler run single-node
        pages = []
        for c in node.children:
            v = self._run(c)
            if isinstance(v, SPage):
                raise ExecutionError(
                    f"{type(node).__name__} got sharded input but has no "
                    "distributed handler (fragmenter should have gathered)"
                )
            pages.append(v)
        return self.local.exec_node(node, *pages)

    # -- exchanges --

    def _d_exchange(self, node: Exchange):
        child = self._run(node.child)
        if node.kind in ("gather", "replicate"):
            return self.to_single(child) if isinstance(child, SPage) else child
        if node.kind == "repartition":
            if not isinstance(child, SPage):
                return child  # single data is trivially co-located
            return self._repartition(child, node.keys)
        raise ExecutionError(f"unknown exchange kind {node.kind!r}")

    def _repartition(self, sp: SPage, keys) -> SPage:
        import time

        cap = sp.shard_capacity
        n = self.n
        axis = self.axis

        def local(p: Page):
            # part_capacity = sender shard capacity -> overflow-free by
            # construction (a sender cannot emit more rows than it holds)
            recv, dropped = exchange_by_hash(p, keys, axis, n, cap)
            return recv, dropped

        t0 = time.perf_counter()
        out, (dropped,) = self._apply(
            ("repartition", tuple(keys)), local, [sp], n_extra=1
        )
        total_dropped = int(jnp.sum(dropped))  # host sync: collective done
        self.exchange_events.append({
            "kind": "repartition",
            "shards": n,
            "rows": out.total_count(),
            "collective_ms": round((time.perf_counter() - t0) * 1e3, 3),
        })
        if total_dropped != 0:  # cannot happen; fail loudly if it does
            raise ExecutionError("exchange dropped rows")
        return self._shrink_sp(out)

    # -- leaves --

    def _d_tablescan(self, node: N.TableScan):
        page = self.local.exec_node(node)  # applies apply_mask entries
        if node.dynamic_filters:
            # ALSO apply the hint-only entries: the SPMD Filter stages
            # above run pre-compiled shard_map kernels that cannot see
            # runtime filters, so the scan is this path's prune point
            page = self.local._apply_scan_masks(node, page, hint_entries=True)
        return self.from_page(page)

    # -- dynamic filters over sharded build sides --

    def _publish_dyn_filters_any(self, node, side) -> None:
        """Publish build-side filters from either a plain Page or an
        SPage (global leaves with per-shard live prefixes)."""
        from ..expr.compiler import evaluate as _ev
        from .breaker import BREAKERS
        from .dynfilter import derive_filter

        if isinstance(side, Page):
            self.local._publish_dynamic_filters(node, side)
            return
        if not self.local._dyn_enabled() or not self.local._dyn_worthwhile(
            node
        ):
            return
        sp: SPage = side
        cap = sp.shard_capacity
        page = page_from_arrays(
            sp.leaves, sp.schema, jnp.asarray(self.n * cap, jnp.int32)
        )
        # per-shard live prefix (NOT a global prefix): shard i's live rows
        # occupy [i*cap, i*cap + counts[i])
        occ = (
            jnp.arange(cap, dtype=jnp.int32)[None, :] < sp.counts[:, None]
        ).reshape(-1)
        keys = (
            node.right_keys if isinstance(node, N.Join) else node.source_keys
        )
        for fid, i, _c in node.dynamic_filters:
            try:
                val = _ev(keys[i], page)
                df = derive_filter(val, occ)
            except Exception as exc:  # noqa: BLE001 — degrade, don't fail
                BREAKERS.record_failure("dynamic_filter", repr(exc))
                return
            if df is not None:
                BREAKERS.record_success("dynamic_filter")
                self.dyn_ctx.publish(fid, df)

    # -- stateless row ops --

    def _unary(self, node, key, local_fn, shrink: bool = False):
        """Common unary-node shape: sharded input -> one SPMD stage;
        single input -> delegate to the single-node executor."""
        c = self._run(node.child)
        if not isinstance(c, SPage):
            return self.local.exec_node(node, c)
        out, _ = self._apply(key, local_fn, [c])
        return self._shrink_sp(out) if shrink else out

    def _d_unnest(self, node: N.Unnest):
        from ..ops.unnest import unnest_page

        return self._unary(
            node,
            ("unnest", node),
            lambda p: unnest_page(
                p, node.array_exprs, node.elem_channels,
                node.ordinality_channel,
            ),
            shrink=True,
        )

    def _d_sample(self, node):
        from ..ops.filter import sample_page

        axis = self.axis

        def fn(p):
            # per-shard component of the global row position: shard i's
            # rows occupy [i*capacity, i*capacity + count) — without it
            # every shard would reuse the identical positional mask
            # (systematic, not Bernoulli sampling)
            off = jax.lax.axis_index(axis).astype(jnp.uint64) * jnp.uint64(
                p.capacity
            )
            return sample_page(p, node.fraction, node.seed, off)

        return self._unary(node, ("sample", node), fn, shrink=True)

    def _d_filter(self, node: N.Filter):
        return self._unary(
            node,
            ("filter", node),
            lambda p: filter_page(p, node.predicate),
            shrink=True,
        )

    def _d_project(self, node: N.Project):
        return self._unary(
            node,
            ("project", node),
            lambda p: project_page(p, node.exprs, node.names),
        )

    # -- aggregation --

    def _d_aggregate(self, node: N.Aggregate):
        c = self._run(node.child)
        if not isinstance(c, SPage):
            return self.local.exec_node(node, c)
        if not node.group_exprs:
            out, _ = self._apply(
                ("gagg", node),
                lambda p: global_aggregate(p, node.aggs, node.mask),
                [c],
            )
            return out
        # collection aggregates (array_agg/map_agg/histogram) are not
        # decomposable, so the fragmenter always gathers them to the
        # local-executor path above (which owns the adaptive-width retry);
        # only scalar + HLL-register specs run on sharded inputs
        from ..ops.aggregate import COLLECTION_AGGS

        if any(a.func in COLLECTION_AGGS for a in node.aggs):
            raise ExecutionError(
                "collection aggregates must be gathered before the "
                "sharded aggregation path"
            )
        max_groups = round_capacity(min(max(c.max_count(), 1), 1 << 16))
        while True:
            mg = max_groups
            out, _ = self._apply(
                ("agg", node, mg),
                lambda p: grouped_aggregate_sorted(
                    p, node.group_exprs, node.group_names, node.aggs, mg,
                    node.mask,
                ),
                [c],
            )
            true_groups = out.max_count()
            if true_groups <= max_groups:
                break
            max_groups = round_capacity(true_groups)
        return self._shrink_sp(out)

    def _d_aggfinalize(self, node: AggFinalize):
        return self._unary(
            node,
            ("aggfin", node),
            lambda p: apply_avg_post(p, node.aggs, node.post),
        )

    def _d_distinct(self, node: N.Distinct):
        return self._unary(
            node,
            ("distinct", node),
            lambda p: distinct_page(p, p.capacity),
            shrink=True,
        )

    # -- joins --

    @staticmethod
    def _row_bytes(sp: "SPage") -> int:
        return sum(
            int(jnp.dtype(lf.dtype).itemsize)
            * (int(lf.shape[-1]) if lf.ndim > 2 else 1)
            for lf in sp.leaves
        )

    def _maybe_grouped_join(self, node: N.Join):
        """Grouped-execution exchange join (chunked ICI exchange): when
        repartitioning both sides would materialize more than
        exchange_budget bytes per shard, split the hash space into B
        buckets and run filter -> all_to_all -> build -> join ONE BUCKET
        at a time inside a single SPMD step each — the exchanged
        intermediate never exceeds ~1/B of the materializing path, and
        jax's async dispatch overlaps bucket b's compute with b+1's
        enqueue (the double-buffering the reference gets from paged
        OutputBuffers + ExchangeClient prefetch)."""
        if self.exchange_budget is None or node.unique_build:
            return None
        if node.kind not in ("inner", "left"):
            return None
        if not (
            isinstance(node.left, Exchange)
            and node.left.kind == "repartition"
            and isinstance(node.right, Exchange)
            and node.right.kind == "repartition"
        ):
            return None
        left = self._run(node.left.child)
        right = self._run(node.right.child)
        if not isinstance(left, SPage) or not isinstance(right, SPage):
            return None
        lcap, rcap = left.shard_capacity, right.shard_capacity
        est = self.n * (
            lcap * self._row_bytes(left) + rcap * self._row_bytes(right)
        )
        B = 1
        while B < 64 and est // B > self.exchange_budget:
            B *= 2
        if B == 1:
            return None  # fits the budget: the normal path materializes
        right_names = tuple(nm for nm, _ in node.right.fields)
        axis, n = self.axis, self.n
        # per-bucket capacities start at cap/B (hash buckets are balanced
        # in expectation); skew retries with doubled capacity on drops
        bl = max(round_capacity(-(-lcap // B)), 64)
        br = max(round_capacity(-(-rcap // B)), 64)
        out_cap = max(round_capacity(-(-lcap // B)), 64)
        parts: List[SPage] = []
        peak = 0
        from ..expr.compiler import evaluate as _ev
        from ..ops.hashing import hash_rows

        def bucket_filter(p: Page, keys, b):
            vals = [_ev(k, p) for k in keys]
            h = hash_rows(vals)
            live = jnp.arange(p.capacity) < p.count
            keep = live & (((h // n) % B) == b)
            return compact(p, keep)

        import numpy as _np

        b = 0
        while b < B:
            cbl, cbr, cout = bl, br, out_cap

            def step(l: Page, r: Page, bpage: Page, _cbl=cbl, _cbr=cbr,
                     _cout=cout) -> Page:
                # the bucket id arrives as a TRACED replicated scalar, so
                # ONE compiled step (keyed on capacities) serves every
                # bucket instead of B recompiles
                _b = bpage.blocks[0].data[0]
                lb = bucket_filter(l, node.left.keys, _b)
                rb = bucket_filter(r, node.right.keys, _b)
                lx, ldrop = exchange_by_hash(
                    lb, node.left.keys, axis, n, _cbl
                )
                rx, rdrop = exchange_by_hash(
                    rb, node.right.keys, axis, n, _cbr
                )
                out, overflow = join_expand(
                    lx,
                    build_sorted(rx, node.right_keys),
                    node.left_keys,
                    lx.names,
                    [(nm, nm) for nm in right_names],
                    out_capacity=_cout,
                    kind=node.kind,
                )
                return out, ldrop + rdrop, overflow

            bpage = Page.from_dict({"b": _np.asarray([b], _np.int32)})
            out, (dropped, overflow) = self._apply(
                (node, "gx", B, cbl, cbr, cout), step, [left, right],
                rep_pages=[bpage], n_extra=2,
            )
            if int(jnp.max(dropped)) > 0:
                bl, br = bl * 2, br * 2
                continue  # retry the same bucket with bigger exchange caps
            ov = int(jnp.max(overflow))
            if ov > 0:
                out_cap = round_capacity(out_cap + ov)
                continue
            peak = max(
                peak, n * (bl * self._row_bytes(left)
                           + br * self._row_bytes(right))
            )
            parts.append(self._shrink_sp(out))
            b += 1
        self.exchange_events.append(
            {"buckets": B, "per_shard_bytes": peak, "estimate": est}
        )
        if len(parts) == 1:
            out = parts[0]
        else:
            from ..ops.union import concat_pages

            out, _ = self._apply(
                (node, "gx-concat", B, tuple(p.shard_capacity for p in parts)),
                lambda *pages: concat_pages(pages),
                parts,
            )
            out = self._shrink_sp(out)
        if node.residual is not None:
            if node.kind != "inner":
                raise ExecutionError(
                    "residual on outer join not yet supported"
                )
            out, _ = self._apply(
                (node, "gx-resid"),
                lambda p: filter_page(p, node.residual),
                [out],
            )
            out = self._shrink_sp(out)
        return out

    def _d_join(self, node: N.Join):
        grouped = self._maybe_grouped_join(node)
        if grouped is not None:
            return grouped
        if node.dynamic_filters:
            # build side first: probe-side scans then see the filters
            right = self._run(node.right)
            self._publish_dyn_filters_any(node, right)
            left = self._run(node.left)
        else:
            left = self._run(node.left)
            right = self._run(node.right)
        if not isinstance(left, SPage):
            if isinstance(right, SPage):
                right = self.to_single(right)
            return self.local.exec_node(node, left, right)

        right_sp: Optional[SPage] = right if isinstance(right, SPage) else None
        right_names = tuple(n for n, _ in node.right.fields)

        def make_n1(l: Page, r: Page) -> Page:
            return join_n1(
                l,
                build_sorted(r, node.right_keys),
                node.left_keys,
                right_names,
                right_names,
                kind=node.kind,
            )

        if node.unique_build:
            ins, reps = self._join_inputs(left, right_sp, right)
            out, _ = self._apply((node, "n1"), make_n1, ins, reps)
            if node.residual is not None:
                if node.kind != "inner":
                    raise ExecutionError("residual on outer join not yet supported")
                out, _ = self._apply(
                    (node, "resid"),
                    lambda p: filter_page(p, node.residual),
                    [out],
                )
            return self._shrink_sp(out)

        cap = round_capacity(max(left.max_count(), 1))
        while True:
            c = cap

            def make_expand(l: Page, r: Page):
                return join_expand(
                    l,
                    build_sorted(r, node.right_keys),
                    node.left_keys,
                    l.names,
                    [(nm, nm) for nm in right_names],
                    out_capacity=c,
                    kind=node.kind,
                )

            ins, reps = self._join_inputs(left, right_sp, right)
            out, (overflow,) = self._apply(
                (node, "expand", c), make_expand, ins, reps, n_extra=1
            )
            ov = int(jnp.max(overflow))
            if ov == 0:
                break
            cap = round_capacity(cap + ov)
        if node.residual is not None:
            if node.kind != "inner":
                raise ExecutionError("residual on outer join not yet supported")
            out, _ = self._apply(
                (node, "resid2"),
                lambda p: filter_page(p, node.residual),
                [out],
            )
        return self._shrink_sp(out)

    @staticmethod
    def _join_inputs(left: SPage, right_sp: Optional[SPage], right):
        if right_sp is not None:
            return [left, right_sp], []
        return [left], [right]

    def _d_semijoin(self, node: N.SemiJoin):
        if node.dynamic_filters:
            source = self._run(node.source)
            self._publish_dyn_filters_any(node, source)
            probe = self._run(node.child)
        else:
            probe = self._run(node.child)
            source = self._run(node.source)
        if not isinstance(probe, SPage):
            if isinstance(source, SPage):
                source = self.to_single(source)
            return self.local.exec_node(node, probe, source)
        source_sp = source if isinstance(source, SPage) else None

        if node.residual is None:

            def local(p: Page, s: Page) -> Page:
                bs = build_sorted(s, node.source_keys)
                return join_n1(
                    p,
                    bs,
                    node.probe_keys,
                    [],
                    [],
                    kind="anti" if node.anti else "semi",
                )

            ins, reps = self._join_inputs(probe, source_sp, source)
            out, _ = self._apply((node, "semi"), local, ins, reps)
            return self._shrink_sp(out)

        # residual EXISTS: expand on equi keys, filter residual, keep probe
        # rows whose (per-shard) row id survived — all local to one shard
        # because the source side is replicated.
        if source_sp is not None:
            source = self.to_single(source_sp)
            source_sp = None
        rid = "$rid_d"
        rid_t = T.BIGINT
        needed = self.local._residual_channels(node.residual)
        cap = round_capacity(max(probe.max_count(), 1))
        while True:
            c = cap

            def local(p: Page, s: Page):
                p2 = self.local._with_row_id(p, rid)
                bs = build_sorted(s, node.source_keys)
                probe_out = [rid] + [nm for nm in p.names if nm in needed]
                build_out = [(nm, nm) for nm in s.names if nm in needed]
                expanded, overflow = join_expand(
                    p2,
                    bs,
                    node.probe_keys,
                    probe_out,
                    build_out,
                    out_capacity=c,
                    kind="inner",
                )
                matched = filter_page(expanded, node.residual)
                bs2 = build_sorted(matched, (ir.ColumnRef(rid, rid_t),))
                out = join_n1(
                    p2,
                    bs2,
                    (ir.ColumnRef(rid, rid_t),),
                    [],
                    [],
                    kind="anti" if node.anti else "semi",
                )
                blocks = tuple(
                    b for b, nm in zip(out.blocks, out.names) if nm != rid
                )
                names = tuple(nm for nm in out.names if nm != rid)
                return Page(blocks, names, out.count), overflow

            out, (overflow,) = self._apply(
                (node, "semiresid", c), local, [probe], [source], n_extra=1
            )
            ov = int(jnp.max(overflow))
            if ov == 0:
                break
            cap = round_capacity(cap + ov)
        return self._shrink_sp(out)

    def _d_scalarapply(self, node: N.ScalarApply):
        child = self._run(node.child)
        sub = self._run(node.subquery)
        if isinstance(sub, SPage):
            sub = self.to_single(sub)
        if not isinstance(child, SPage):
            return self.local.exec_node(node, child, sub)
        n_sub = int(sub.count)  # host-side check; the broadcast is pure
        if n_sub > 1:
            raise ExecutionError("scalar subquery returned more than one row")

        def local(p: Page, s: Page) -> Page:
            cap = p.capacity
            blocks = list(p.blocks)
            names = list(p.names)
            for b, (fname, _ftype) in zip(s.blocks, node.subquery.fields):
                if n_sub == 0:
                    data = jnp.zeros((cap,) + b.data.shape[1:], b.data.dtype)
                    valid = jnp.zeros((cap,), jnp.bool_)
                else:
                    data = jnp.broadcast_to(
                        b.data[0], (cap,) + b.data.shape[1:]
                    )
                    valid = (
                        None
                        if b.valid is None
                        else jnp.broadcast_to(b.valid[0], (cap,))
                    )
                blocks.append(Block(data, b.type, valid, b.dict_id))
                names.append(fname)
            return Page(tuple(blocks), tuple(names), p.count)

        out, _ = self._apply((node, "sapply", n_sub == 0), local, [child], [sub])
        return out

    # -- windows / ordering --

    def _d_window(self, node: N.Window):
        from ..ops.window import window_op

        return self._unary(
            node,
            ("window", node),
            lambda p: window_op(
                p, node.partition_exprs, node.order_keys, node.funcs
            ),
        )

    def _d_sort(self, node: N.Sort):
        """Distributed sort (reference admin/dist-sort.rst: per-task partial
        sort + single-node MergeOperator k-way merge). Stage 1 sorts every
        shard in parallel on the mesh; stage 2 merges on the root.

        Merge fast path (single non-null key): each row's global position is
        its in-run position plus, per other run, how many of that run's keys
        precede it (vmapped searchsorted over the sorted runs, ties broken
        by run index for stability) — one argsort over int32 ranks instead
        of re-running the full multi-pass key sort. Nullable or multi-key
        sorts fall back to sorting the gathered page."""
        import jax.numpy as jnp

        from ..expr.compiler import evaluate
        from ..ops.sort import sort_page
        from ..page import Block

        # the fragmenter plans ORDER BY as Sort(Exchange(gather, child));
        # run the gather's sharded input through the merge path instead of
        # materializing it unsorted on the root
        ch = node.child
        if isinstance(ch, Exchange) and ch.kind == "gather":
            if self.collector is not None:
                import time as _time

                t0 = _time.perf_counter()
                c = self._run(ch.child)
                below = _time.perf_counter() - t0
                sub = self.collector.lookup(ch.child)
                # keep the Exchange visible to EXPLAIN ANALYZE even though
                # the merge path absorbed it (Sort's self-time subtraction
                # reads its direct child)
                self.collector.record(
                    ch,
                    max(below - (sub.wall_s if sub else 0.0), 0.0),
                    0,
                    c.total_count() if isinstance(c, SPage) else int(c.count),
                    0,
                )
            else:
                c = self._run(ch.child)
        else:
            c = self._run(ch)
        if not isinstance(c, SPage):
            return self.local.exec_node(node, c)

        keys = node.keys
        single_key = len(keys) == 1 and not isinstance(
            keys[0].expr.type, T.VarcharType
        )
        if not single_key:
            # multi-key/varchar sorts gain nothing from per-shard sorting
            # (XLA's root sort cost is data-independent) — gather raw
            return self.local.exec_node(node, self.to_single(c))

        def local(p: Page):
            from ..ops.sort import asc_normalized_scalar_key

            s = sort_page(p, keys)
            v = evaluate(keys[0].expr, s)
            key_col = asc_normalized_scalar_key(v.data, keys[0].ascending)
            if key_col is None:  # long decimal: not merge-friendly
                has_nulls = jnp.ones((), jnp.int32)
                key_col = jnp.zeros(p.capacity, jnp.int64)
            else:
                live = s.live_mask()
                # only LIVE rows count — shard padding carries a zeroed
                # validity mask that is not a real NULL. NaN keys also
                # break searchsorted's ordering assumption: fall back.
                bad = jnp.zeros_like(live)
                if v.valid is not None:
                    bad = bad | ~v.valid
                if jnp.issubdtype(key_col.dtype, jnp.floating):
                    bad = bad | jnp.isnan(key_col)
                has_nulls = jnp.any(bad & live).astype(jnp.int32)
            kb = Block(
                key_col,
                T.DOUBLE
                if jnp.issubdtype(key_col.dtype, jnp.floating)
                else T.BIGINT,
            )
            return (
                Page(s.blocks + (kb,), s.names + ("__sortkey__",), s.count),
                has_nulls,
            )

        sorted_sp, (has_nulls,) = self._apply(
            ("dsort", keys, single_key), local, [c], n_extra=1
        )
        if single_key and int(jnp.sum(has_nulls)) == 0:
            return self._merge_sorted_runs(sorted_sp)
        page = self.to_single(sorted_sp)
        if single_key:  # drop the helper key column before the fallback
            page = Page(page.blocks[:-1], page.names[:-1], page.count)
        return self.local.exec_node(node, page)

    def _merge_sorted_runs(self, sp: SPage) -> Page:
        """Rank-merge n sorted runs whose last column is the asc-normalized
        merge key; returns the single merged Page without that column."""
        import jax.numpy as jnp

        cap = sp.shard_capacity
        n = self.n
        key = ("merge_runs", sp.schema, cap, n)
        fn = self._steps.get(key)
        if fn is None:

            def merge(leaves, counts):
                K = leaves[-1].reshape(n, cap)
                sentinel = (
                    jnp.inf
                    if jnp.issubdtype(K.dtype, jnp.floating)
                    else jnp.iinfo(K.dtype).max
                )
                pos = jnp.arange(cap, dtype=jnp.int32)[None, :]
                live = pos < counts[:, None]
                Kp = jnp.where(live, K, sentinel)
                flat = Kp.reshape(-1)
                ss_l = jax.vmap(
                    lambda a: jnp.searchsorted(a, flat, side="left")
                )(Kp)  # (n, n*cap)
                ss_r = jax.vmap(
                    lambda a: jnp.searchsorted(a, flat, side="right")
                )(Kp)
                cnt = counts.astype(jnp.int32)[:, None]
                ss_l = jnp.minimum(ss_l, cnt)
                ss_r = jnp.minimum(ss_r, cnt)
                run_of = jnp.repeat(
                    jnp.arange(n, dtype=jnp.int32), cap
                )  # (n*cap,)
                other = jnp.arange(n, dtype=jnp.int32)[:, None]
                before = jnp.where(other < run_of[None, :], ss_r, ss_l)
                contrib = jnp.where(
                    other == run_of[None, :], 0, before
                ).sum(axis=0)
                in_run = jnp.tile(pos[0], n)
                total = jnp.sum(counts).astype(jnp.int32)
                gidx = jnp.arange(n * cap, dtype=jnp.int32)
                rank = jnp.where(
                    live.reshape(-1),
                    in_run + contrib.astype(jnp.int32),
                    total + gidx,  # dead rows strictly after all live rows
                )
                perm = jnp.argsort(rank)
                # every leaf's leading dim is n*cap (SPage layout)
                merged = tuple(leaf[perm] for leaf in leaves)
                return merged, total

            fn = jax.jit(merge)
            self._steps[key] = fn
        merged, total = fn(sp.leaves, sp.counts)
        page = page_from_arrays(merged, sp.schema, total)
        # drop the __sortkey__ helper column
        page = Page(page.blocks[:-1], page.names[:-1], page.count)
        return self.local._shrink(page)

    def _d_topn(self, node: N.TopN):
        return self._unary(
            node,
            ("topn", node),
            lambda p: top_n(p, node.keys, node.count),
            shrink=True,
        )

    def _d_limit(self, node: N.Limit):
        return self._unary(
            node,
            ("limit", node),
            lambda p: limit_page(p, node.count),
            shrink=True,
        )
