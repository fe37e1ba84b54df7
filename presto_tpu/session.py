"""Session: SQL in, rows out.

The single-process equivalent of the reference's LocalQueryRunner
(presto-main/.../testing/LocalQueryRunner.java:204 — full
parse->plan->execute in one process, no HTTP), and the embedding API the
CLI/server layers build on.
"""

from __future__ import annotations

from typing import List, Optional

from .exec.executor import Executor
from .plan import nodes as N
from .sql import tree as t
from .sql.parser import parse
from .sql.planner import Planner


def _opt_f64(values):
    """Optional-float column: (data, valid-aware) numpy for Page.from_dict."""
    import numpy as np

    from .page import Block
    from . import types as T

    data = np.array(
        [0.0 if v is None else float(v) for v in values], np.float64
    )
    valid = np.array([v is not None for v in values], bool)
    return Block.from_numpy(
        data, T.DOUBLE, valid=None if valid.all() else valid
    )


class QueryResult:
    def __init__(self, page, titles):
        self.page = page
        self.titles = list(titles)
        # observability plane (docs/observability.md): set by the traced
        # dispatch paths; None when tracing is off or N/A (DDL, EXPLAIN)
        self.trace_id: Optional[str] = None
        self.phase_ms: Optional[dict] = None

    def rows(self) -> List[tuple]:
        return self.page.to_pylist()

    def row_count(self) -> int:
        from .obs.span import host_read

        return int(host_read(self.page.count))


# system session properties: per-query engine overrides (reference
# SystemSessionProperties — 49+ properties; these are the ones this
# engine's executors actually read). Each entry: parser from string.
def _parse_bool(v: str) -> bool:
    if str(v).lower() in ("true", "1", "yes"):
        return True
    if str(v).lower() in ("false", "0", "no"):
        return False
    raise ValueError(f"invalid boolean {v!r}")


SESSION_PROPERTIES = {
    "broadcast_threshold": int,   # join build-side broadcast cutover (rows)
    "streaming": _parse_bool,     # paged scans through the streaming driver
    "batch_rows": int,            # streaming scan batch size
    "memory_budget": int,         # device-memory budget (bytes)
    "query_priority": int,        # resource-group query_priority policy
    "pallas_groupby": _parse_bool,  # small-G aggregation via the Pallas kernel
    "matmul_groupby": _parse_bool,  # dense-key aggregation via MXU matmuls
    "dynamic_filtering": _parse_bool,  # build-side runtime filters on probes
    "plan_cache": _parse_bool,    # serve plans from exec/qcache.PLAN_CACHE
    "result_cache": _parse_bool,  # serve results from exec/qcache.RESULT_CACHE
}


def parse_session_properties(text: str) -> dict:
    """Parse 'k=v,k=v' (the X-Presto-Session header format,
    presto-client/.../PrestoHeaders.java) with type checking."""
    props = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"invalid session property {part!r}")
        k, v = part.split("=", 1)
        k = k.strip().lower()
        parser = SESSION_PROPERTIES.get(k)
        if parser is None:
            raise ValueError(f"unknown session property {k!r}")
        props[k] = parser(v.strip())
    return props


class Session:
    """mesh=None runs single-device; passing a jax.sharding.Mesh fragments
    every plan (plan/fragment.py) and executes it distributed over the
    mesh's worker axis (exec/dist.py) — the analog of LocalQueryRunner vs
    DistributedQueryRunner (presto-tests/.../DistributedQueryRunner.java:75)."""

    def __init__(
        self,
        catalog,
        mesh=None,
        broadcast_threshold=None,  # None = cost-based distribution
        streaming: bool = False,
        batch_rows: int = 1 << 20,
        memory_budget=None,
        access_control=None,
        user: str = "user",
        pallas_groupby=None,  # None = auto (ON on TPU, OFF on CPU)
        matmul_groupby=None,  # None = auto (ON on TPU, OFF on CPU)
        exchange_budget=None,  # per-shard bytes for exchanged joins
        dynamic_filtering: bool = True,  # build-side runtime join filters
        plan_cache: bool = True,    # plan/skeleton reuse (exec/qcache.py)
        result_cache: bool = True,  # snapshot-validated result reuse
    ):
        self.access_control = access_control
        self.user = user
        self.catalog = catalog
        self.mesh = mesh
        self.broadcast_threshold = broadcast_threshold
        self.exchange_budget = exchange_budget
        if mesh is not None:
            from .exec.dist import DistributedExecutor

            self.executor = DistributedExecutor(
                catalog, mesh, exchange_budget=exchange_budget
            )
        elif streaming:
            from .exec.stream import StreamingExecutor

            self.executor = StreamingExecutor(
                catalog, batch_rows=batch_rows, memory_budget=memory_budget
            )
        else:
            self.executor = Executor(catalog)
        self.streaming = streaming
        self.batch_rows = batch_rows
        self.memory_budget = memory_budget
        self.pallas_groupby = pallas_groupby
        self.matmul_groupby = matmul_groupby
        self.dynamic_filtering = dynamic_filtering
        self.plan_cache = plan_cache
        self.result_cache = result_cache
        local = getattr(self.executor, "local", self.executor)
        if pallas_groupby is not None and hasattr(local, "pallas_groupby"):
            local.pallas_groupby = pallas_groupby
        if matmul_groupby is not None and hasattr(local, "matmul_groupby"):
            local.matmul_groupby = matmul_groupby
        if hasattr(local, "dynamic_filtering"):
            local.dynamic_filtering = dynamic_filtering
        # statement-layer state (shared BY REFERENCE with derived
        # property-override sessions, see with_properties)
        self.views: dict = {}  # name -> view query SQL
        self.prepared: dict = {}  # name -> prepared statement SQL
        self.schemas = {"default"}
        self._session_overrides: dict = {}  # SET SESSION k = v
        from .matview.manager import MatViewManager

        self.matviews_mgr = MatViewManager(self)
        self._attach_matviews()

    def _attach_matviews(self) -> None:
        """Point the routing SystemCatalog (if any, connectors/system.py)
        at this session's MV registry so system.runtime.materialized_views
        serves live rows. Walks the .wrapped chain; only a catalog that
        DECLARES the slot (SystemCatalog sets it to None in __init__)
        gets it — __getattr__ delegators must not be tricked by hasattr."""
        probe = self.catalog
        while probe is not None:
            if "matview_manager" in getattr(probe, "__dict__", {}):
                probe.matview_manager = self.matviews_mgr
                return
            probe = getattr(probe, "wrapped", None)

    def _swap_catalog(self, catalog) -> None:
        """Point the session AND its executors at a different catalog
        (transaction overlay enter/exit)."""
        self.catalog = catalog
        self.executor.catalog = catalog
        local = getattr(self.executor, "local", None)
        if local is not None:
            local.catalog = catalog

    def with_properties(self, props: dict) -> "Session":
        """A sibling session with per-query property overrides applied
        (reference: Session.withSystemProperty). Non-engine properties
        (query_priority) are admission-control metadata and ignored here.
        Derived sessions are cached per property set so repeat clients
        reuse compiled kernels instead of rebuilding executors."""
        engine = {k: v for k, v in props.items() if k != "query_priority"}
        if not engine:
            return self
        key = tuple(sorted(engine.items()))
        cache = getattr(self, "_prop_sessions", None)
        if cache is None:
            cache = self._prop_sessions = {}
        derived = cache.get(key)
        if derived is not None and derived.catalog is not self.catalog:
            # the base session's catalog moved (transaction overlay
            # enter/exit) after this derived session was cached — repoint
            # it or reads would miss the transaction's own writes
            derived._swap_catalog(self.catalog)
        if derived is None:
            if len(cache) >= 16:  # bound server memory: FIFO-evict
                cache.pop(next(iter(cache)))
            derived = Session(
                self.catalog,
                mesh=self.mesh,
                broadcast_threshold=engine.get(
                    "broadcast_threshold", self.broadcast_threshold
                ),
                streaming=engine.get("streaming", self.streaming),
                batch_rows=engine.get("batch_rows", self.batch_rows),
                memory_budget=engine.get("memory_budget", self.memory_budget),
                access_control=self.access_control,
                user=self.user,
                pallas_groupby=engine.get(
                    "pallas_groupby", self.pallas_groupby
                ),
                matmul_groupby=engine.get(
                    "matmul_groupby", self.matmul_groupby
                ),
                dynamic_filtering=engine.get(
                    "dynamic_filtering", self.dynamic_filtering
                ),
                exchange_budget=self.exchange_budget,
                plan_cache=engine.get("plan_cache", self.plan_cache),
                result_cache=engine.get("result_cache", self.result_cache),
            )
            # statement-layer state is session-wide, not per-override
            derived.views = self.views
            derived.prepared = self.prepared
            derived.schemas = self.schemas
            derived.matviews_mgr = self.matviews_mgr
            # derived's __init__ attached its own (now orphaned) manager
            # to the shared SystemCatalog — re-attach the session-wide one
            self._attach_matviews()
            cache[key] = derived
        return derived

    def plan(self, sql: str) -> N.PlanNode:
        ast = parse(sql)
        if isinstance(ast, t.Explain):
            ast = ast.query
        if not isinstance(ast, t.Query):
            raise ValueError("only SELECT queries supported here")
        return self._plan_query_cached(ast)

    # -- plan cache (exec/qcache.py) --

    def _plan_env_key(self):
        """Planning-relevant session state: plans keyed by the same AST
        are only interchangeable within one catalog object, view set,
        join-distribution config, mesh width, and feedback-store
        generation (plan/history.py: a recorded observation or an
        invalidation must re-plan, never reuse a plan built on
        superseded history)."""
        from .plan.history import plan_env_token

        mesh_n = self.mesh.devices.size if self.mesh is not None else 0
        views_fp = tuple(sorted(self.views.items())) if self.views else ()
        return (id(self.catalog), mesh_n, self.broadcast_threshold,
                views_fp, plan_env_token())

    def _engine_env_key(self):
        """Execution-engine identity, part of the RESULT cache key: two
        sessions only share materialized pages when they would execute
        the same way. Results are oracle-equal across engines, but what
        an execution PRODUCES also includes observability (spill events,
        dynamic-filter stats, breaker counters) and A/B harnesses rely
        on differently-configured sessions actually executing."""
        return (
            type(self.executor).__name__,
            self.streaming,
            self.batch_rows,
            self.memory_budget,
            self.exchange_budget,
            self.pallas_groupby,
            self.matmul_groupby,
            self.dynamic_filtering,
        )

    def _plan_query_uncached(self, ast: t.Query) -> N.PlanNode:
        planner = Planner(self.catalog, views=self.views)
        rp = planner.plan_query(ast, outer=None, ctes={})
        scope = rp.scope
        channels = tuple(f.channel for f in scope.fields)
        titles = tuple(f.name for f in scope.fields)
        from .plan.optimizer import optimize

        node = optimize(N.Output(rp.node, channels, titles))
        if self.mesh is not None:
            from .plan.fragment import fragment_plan

            node = fragment_plan(
                node, self.catalog, self.broadcast_threshold,
                num_workers=self.mesh.devices.size,
            )
        return node

    def _plan_query_cached(self, ast: t.Query) -> N.PlanNode:
        """Plan via the process-wide plan cache. Entries are validated
        against the catalog object AND every referenced table's connector
        snapshot version, so a write (which can change schemas and the
        CBO stats planning depends on) replans; unversioned connectors
        are never cached."""
        from .exec import qcache

        if not self.plan_cache:
            return self._plan_query_uncached(ast)
        key = ("q", ast, self._plan_env_key())
        ent = qcache.PLAN_CACHE.lookup(key, self.catalog)
        if ent is not None:
            return ent.plan
        node = self._plan_query_uncached(ast)
        qcache.PLAN_CACHE.store(key, node, self.catalog)
        return node

    def explain(self, sql: str) -> str:
        from .plan.stats import StatsDeriver

        return N.plan_tree_str(
            self.plan(sql), stats_of=StatsDeriver(self.catalog).stats
        )

    def query(self, sql: str, user: Optional[str] = None) -> QueryResult:
        ast = parse(sql)
        # explicit empty-string identity must NOT fall back to the
        # (possibly privileged) session default
        effective = self.user if user is None else user
        if self.access_control is not None:
            from .security import enforce

            enforce(self.access_control, effective, ast, views=self.views)
        if isinstance(
            ast,
            (t.CreateTable, t.DropTable, t.Insert, t.Delete, t.ShowTables,
             t.ShowColumns, t.StartTransaction, t.Commit, t.Rollback,
             t.CreateView, t.DropView, t.ShowCreateView, t.CreateSchema,
             t.DropSchema, t.ShowSchemas, t.Prepare, t.ExecutePrepared,
             t.Deallocate, t.DescribeInput, t.DescribeOutput, t.SetSession,
             t.ResetSession, t.ShowSession, t.RenameTable, t.RenameColumn,
             t.AddColumn, t.DropColumn, t.Grant, t.Revoke,
             t.ShowFunctions, t.ShowCatalogs, t.ShowCreateTable,
             t.ShowStats, t.Use, t.Analyze, t.ShowGrants,
             t.CreateMaterializedView, t.RefreshMaterializedView,
             t.DropMaterializedView),
        ):
            # the user travels as an argument: the Session is shared across
            # QueryManager worker threads, so instance state would race
            return self._execute_statement(ast, effective)
        if self._session_overrides:
            # SET SESSION overrides route plain queries through the
            # derived-session cache (reference: Session.withSystemProperty)
            return self.with_properties(dict(self._session_overrides))._dispatch_query(
                sql, ast, effective
            )
        return self._dispatch_query(sql, ast, effective)

    def _dispatch_query(self, sql, ast, effective):
        if not isinstance(ast, t.Explain):
            # plain SELECT: the result-cache fast path, under plan /
            # execute phase spans when the observability plane is on
            return self._run_select_traced(sql)
        node = self.plan(sql)
        from .page import Page

        etype = getattr(ast, "etype", "logical")
        if ast.analyze:
            lines = self.explain_analyze_plan(node).split("\n")
        elif etype == "validate":
            # reference ExplainTask TYPE VALIDATE: analysis+planning
            # succeeded if we got here
            pg = Page.from_dict({"Valid": [True]})
            return QueryResult(pg, ("Valid",))
        elif etype == "io":
            # reference IOPlanPrinter: the tables/columns the plan reads
            scans = []

            def walk(n):
                if isinstance(n, N.TableScan):
                    cols = ", ".join(c for _, c, _ in n.columns)
                    scans.append(f"{n.table} [{cols}]")
                for c in n.children:
                    walk(c)

            walk(node)
            pg = Page.from_dict({"Table": scans or [None]})
            if not scans:
                pg = Page(pg.blocks, pg.names, 0)
            return QueryResult(pg, ("Table",))
        elif etype == "distributed":
            # reference PlanPrinter.textDistributedPlan over fragments
            from .plan.fragment import fragment_plan

            workers = (
                self.mesh.devices.size if self.mesh is not None else 2
            )
            froot = fragment_plan(
                node, self.catalog, self.broadcast_threshold,
                num_workers=workers,
            )
            lines = N.plan_tree_str(froot).split("\n")
        else:
            lines = N.plan_tree_str(node).split("\n")
        pg = Page.from_dict({"Query Plan": lines})
        return QueryResult(pg, ("Query Plan",))

    def _run_select_traced(self, sql: str) -> QueryResult:
        """Plan + execute with per-phase spans. Under a served statement
        the spans go into the tree `QueryManager.submit` opened (the
        calling thread's current trace) and the manager exports it;
        called directly, the trace is this query's own: it lands in the
        process TraceStore (system.runtime.tasks), the phase timings on
        the QueryResult, and the completion counters in the metrics
        registry."""
        from .obs import span as obs_span

        if not obs_span.enabled():
            return self._execute_plan_cached(self.plan(sql))
        cur = obs_span.current()
        trace = cur[0] if cur is not None else obs_span.TRACES.new_trace()
        root = trace.enter("query", sql=sql[:200])
        status = "ok"
        phase_ms: dict = {}
        try:
            span = trace.enter("plan")
            try:
                node = self.plan(sql)
            finally:
                trace.leave(span)
            phase_ms["plan"] = round(span.wall_s * 1e3, 3)
            span = trace.enter("execute")
            try:
                res = self._execute_plan_cached(node)
                span.attrs["rows"] = res.row_count()
            finally:
                trace.leave(span)
            phase_ms["execute"] = round(span.wall_s * 1e3, 3)
            res.trace_id = trace.trace_id
            res.phase_ms = phase_ms
            return res
        except Exception:
            status = "error"
            raise
        finally:
            trace.leave(root, status)
            if cur is None:
                from .obs.export import export_query

                export_query(status, root.wall_s, phase_ms)

    def _execute_plan_cached(self, node) -> QueryResult:
        """Execute a planned query through the result cache: a hit serves
        the materialized page without touching the executor; a miss
        executes and stores under the snapshot versions read BEFORE
        execution (a concurrent writer can only waste the entry, never
        stale it). Plans over unversioned connectors, TABLESAMPLE, or
        nondeterministic functions bypass the cache entirely."""
        from .exec import qcache

        if not self.result_cache:
            return QueryResult(self.executor.run(node), node.titles)
        key = ("r", node, self._plan_env_key(), self._engine_env_key())
        hit = qcache.RESULT_CACHE.lookup(key, self.catalog)
        if hit is not None:
            return QueryResult(hit.page, hit.titles)
        pre = qcache.RESULT_CACHE.preversions(node, self.catalog)
        page = self._run_observed(node)
        if pre is not None and qcache.plan_is_deterministic(node):
            qcache.RESULT_CACHE.store(
                key, page, node.titles, self.catalog, pre
            )
        return QueryResult(page, node.titles)

    def _run_observed(self, node):
        """Observe-once execution hook for history-based feedback
        (plan/history.py): when the plane is on AND the store lacks a
        live entry for some frame of this plan, run through a fresh
        collector-attached executor (the explain_analyze construction —
        the shared session executor can't have a collector swapped in
        per query under the server's concurrency) and fold the observed
        cardinalities in at completion. Plans whose frames are all
        remembered take the plain path: the warm cost is one store walk,
        not an instrumented run."""
        try:
            from .plan import history as H

            observe = H.feedback_on() and H.HISTORY.wants_observation(
                node, self.catalog
            )
        except Exception as exc:  # noqa: BLE001 — degrade, don't fail
            from .exec.breaker import BREAKERS

            BREAKERS.record_failure("adaptive_plan", repr(exc))
            observe = False
        if not observe:
            return self.executor.run(node)
        from .exec.stats import StatsCollector

        collector = StatsCollector()
        ex = self._collector_executor(collector)
        page = ex.run(node)
        try:
            H.HISTORY.record_plan(node, collector, self.catalog)
        except Exception as exc:  # noqa: BLE001 — bookkeeping only
            from .exec.breaker import BREAKERS

            BREAKERS.record_failure("adaptive_plan", repr(exc))
        return page

    # -- DDL / DML tasks (reference execution/CreateTableTask.java,
    # CreateTableAsSelect via TableWriter/TableFinish operators,
    # operator/TableWriterOperator.java, operator/DeleteOperator.java;
    # re-designed: the coordinator task runs the source plan through the
    # session's executor and hands final pages to the writable connector) --

    def _writable(self):
        from .connectors.spi import WritableConnector, WriteError

        # unwrap routing catalogs (connectors/system.py SystemCatalog)
        cat = self.catalog
        probe = cat
        while probe is not None and not isinstance(probe, WritableConnector):
            probe = getattr(probe, "wrapped", None)
        if probe is None:
            raise WriteError(
                f"catalog {getattr(cat, 'name', '?')!r} is read-only"
            )
        return cat

    def _run_query_ast(self, ast: t.Query):
        """Plan + execute a Query AST; returns (page, titles, scope).
        Plans come from the snapshot-validated plan cache; results are
        NOT result-cached here (DML sources execute fresh)."""
        node = self._plan_query_cached(ast)
        return self.executor.run(node), node.titles, None

    def _table_schema(self, cat, name: str):
        if name not in cat.table_names():
            raise ValueError(f"table {name!r} does not exist")
        return cat.schema(name)

    @staticmethod
    def _row_count_result(n: int) -> QueryResult:
        import numpy as np

        from .page import Page

        pg = Page.from_dict({"rows": np.array([n], dtype=np.int64)})
        return QueryResult(pg, ("rows",))

    @staticmethod
    def _like_filter(names, pat):
        """SQL LIKE pattern over a name list (SHOW ... LIKE 'x%')."""
        if pat is None:
            return names
        import re

        rx = re.compile(
            "^" + re.escape(pat).replace("%", ".*").replace("_", ".") + "$",
            re.IGNORECASE,
        )
        return [n for n in names if rx.match(n)]

    def _execute_statement(self, ast, user: Optional[str] = None) -> QueryResult:
        from .page import Page

        if user is None:
            user = self.user

        if isinstance(ast, t.Use):
            # reference UseTask: switch the session default catalog/schema.
            # With a CatalogStore the used catalog becomes the FIRST
            # bare-name resolver (per-session copy, no global mutation).
            from .server.catalog_store import CatalogStore

            cat_name, schema = ast.catalog, ast.schema
            if cat_name is None and isinstance(self.catalog, CatalogStore) \
                    and schema in self.catalog.catalogs:
                cat_name, schema = schema, "default"
            if cat_name is not None:
                if not isinstance(self.catalog, CatalogStore) or \
                        cat_name not in self.catalog.catalogs:
                    raise ValueError(f"catalog {cat_name!r} does not exist")
                ordered = {cat_name: self.catalog.catalogs[cat_name]}
                ordered.update(self.catalog.catalogs)
                self._swap_catalog(CatalogStore(ordered))
            elif schema not in self.schemas:
                raise ValueError(f"schema {schema!r} does not exist")
            self.current_schema = schema
            return self._row_count_result(0)

        if isinstance(ast, t.Analyze):
            # reference AnalyzeTask: collect and materialize table stats
            # (here: force column-stat derivation through the CBO path and
            # report the analyzed row count)
            name = ast.table.lower()
            schema = self._table_schema(self.catalog, name)
            get = getattr(self.catalog, "column_stats", None)
            if get is not None:
                for c in schema:
                    get(name, c)  # populates the connector's stats cache
            return self._row_count_result(
                int(self.catalog.row_count(name))
            )

        if isinstance(ast, t.ShowTables):
            # views list alongside tables (reference ShowQueriesRewrite:
            # information_schema.tables carries both)
            names = sorted(set(self.catalog.table_names()) | set(self.views))
            names = self._like_filter(names, ast.like)
            if self.access_control is not None:
                # filter out tables the user cannot read (reference
                # SystemAccessControl.filterTables)
                from .security import AccessDeniedError

                visible = []
                for n in names:
                    try:
                        self.access_control.check_can_select_from_table(
                            user, n
                        )
                        visible.append(n)
                    except AccessDeniedError:
                        pass
                names = visible
            pg = Page.from_dict({"Table": list(names) or [None]})
            if not names:
                pg = Page(pg.blocks, pg.names, 0)
            return QueryResult(pg, ("Table",))
        if isinstance(ast, t.ShowColumns):
            schema = self._table_schema(self.catalog, ast.table.lower())
            pg = Page.from_dict(
                {
                    "Column": list(schema),
                    "Type": [str(ty) for ty in schema.values()],
                }
            )
            return QueryResult(pg, ("Column", "Type"))
        if isinstance(ast, t.StartTransaction):
            if getattr(self, "_txn", None) is not None:
                raise ValueError("transaction already in progress")
            from .exec.transaction import TransactionCatalog

            self._txn_base = self.catalog
            self._txn = TransactionCatalog(self._writable())
            self._swap_catalog(self._txn)
            return self._row_count_result(0)
        if isinstance(ast, (t.Commit, t.Rollback)):
            txn = getattr(self, "_txn", None)
            if txn is None:
                raise ValueError("no transaction in progress")
            try:
                if isinstance(ast, t.Commit):
                    txn.commit()
                else:
                    txn.rollback()
            finally:
                self._swap_catalog(self._txn_base)
                self._txn = None
            return self._row_count_result(0)
        if isinstance(ast, t.CreateTable):
            return self._create_table(ast)
        if isinstance(ast, t.DropTable):
            cat = self._writable()
            name = ast.name.lower()
            if name in self.matviews_mgr.views:
                raise ValueError(
                    f"{name!r} is a materialized view; "
                    "use DROP MATERIALIZED VIEW"
                )
            if name not in cat.table_names():
                if ast.if_exists:
                    return self._row_count_result(0)
                raise ValueError(f"table {ast.name!r} does not exist")
            cat.drop_table(name)
            return self._row_count_result(0)
        if isinstance(ast, t.Insert):
            return self._insert(ast)
        if isinstance(ast, t.Delete):
            return self._delete(ast)

        # -- views (reference execution/CreateViewTask.java,
        # DropViewTask.java; expansion happens in the planner) --
        if isinstance(ast, t.CreateView):
            name = ast.name.lower()
            if name in self.matviews_mgr.views:
                raise ValueError(
                    f"materialized view {name!r} already exists"
                )
            if name in self.catalog.table_names():
                raise ValueError(f"table {name!r} already exists")
            if name in self.views and not ast.or_replace:
                raise ValueError(f"view {name!r} already exists")
            # validate now: the view text must parse AND plan — against
            # the NEW binding (name excluded), so OR REPLACE cannot store
            # a self-reference that only fails at first use
            from .sql.parser import parse as _parse

            vast = _parse(ast.query_sql)
            if not isinstance(vast, t.Query):
                raise ValueError("CREATE VIEW requires a SELECT query")
            probe = {k: v for k, v in self.views.items() if k != name}
            Planner(self.catalog, views=probe).plan_query(
                vast, outer=None, ctes={}
            )
            self.views[name] = ast.query_sql
            return self._row_count_result(0)
        if isinstance(ast, t.DropView):
            name = ast.name.lower()
            if name not in self.views:
                if ast.if_exists:
                    return self._row_count_result(0)
                raise ValueError(f"view {name!r} does not exist")
            del self.views[name]
            return self._row_count_result(0)
        if isinstance(ast, t.ShowCreateView):
            name = ast.name.lower()
            if name not in self.views:
                raise ValueError(f"view {name!r} does not exist")
            txt = f"CREATE VIEW {name} AS {self.views[name]}"
            pg = Page.from_dict({"Create View": [txt]})
            return QueryResult(pg, ("Create View",))

        # -- materialized views (matview/manager.py; reference
        # execution/CreateMaterializedViewTask.java) --
        if isinstance(ast, t.CreateMaterializedView):
            self.matviews_mgr.create(
                ast.name, ast.query_sql, ast.if_not_exists
            )
            return self._row_count_result(0)
        if isinstance(ast, t.RefreshMaterializedView):
            self.matviews_mgr.refresh(ast.name, full=ast.full)
            return self._row_count_result(0)
        if isinstance(ast, t.DropMaterializedView):
            self.matviews_mgr.drop(ast.name, ast.if_exists)
            return self._row_count_result(0)

        # -- schemas (reference CreateSchemaTask.java, DropSchemaTask) --
        if isinstance(ast, t.CreateSchema):
            name = ast.name.lower()
            if name in self.schemas:
                if ast.if_not_exists:
                    return self._row_count_result(0)
                raise ValueError(f"schema {name!r} already exists")
            self.schemas.add(name)
            return self._row_count_result(0)
        if isinstance(ast, t.DropSchema):
            name = ast.name.lower()
            if name == "default":
                raise ValueError("cannot drop the default schema")
            if name not in self.schemas:
                if ast.if_exists:
                    return self._row_count_result(0)
                raise ValueError(f"schema {name!r} does not exist")
            held = [
                tn for tn in self.catalog.table_names()
                if tn.lower().startswith(name + ".")
            ]
            if held:
                raise ValueError(f"schema {name!r} is not empty: {held}")
            self.schemas.discard(name)
            return self._row_count_result(0)
        if isinstance(ast, t.ShowFunctions):
            # reference ShowQueriesRewrite SHOW FUNCTIONS over the
            # registry; kind mirrors FunctionKind
            from .sql.planner import AGG_FUNCS, LAMBDA_FUNCS, REWRITE_AGG_FUNCS
            from .expr.functions import FUNCTIONS
            from .ops.window import AGGREGATE, OFFSET, RANKING, VALUE

            # one row per name; precedence aggregate > scalar > lambda >
            # window (sum/avg/min/max/count exist both as aggregates and
            # window reducers — Presto lists them once, as aggregates)
            kind_of = {}
            for n in RANKING | OFFSET | VALUE | AGGREGATE:
                kind_of[n] = "window"
            for n in LAMBDA_FUNCS:
                kind_of[n] = "lambda"
            for n in FUNCTIONS:
                kind_of[n] = "scalar"
            for n in AGG_FUNCS | REWRITE_AGG_FUNCS:
                kind_of[n] = "aggregate"
            rows = sorted(
                (n, k)
                for n, k in kind_of.items()
                if n in set(self._like_filter(list(kind_of), ast.like))
            )
            pg = Page.from_dict(
                {
                    "Function": [r[0] for r in rows],
                    "Kind": [r[1] for r in rows],
                }
            )
            return QueryResult(pg, ("Function", "Kind"))
        if isinstance(ast, t.ShowGrants):
            # surface the active rule set (reference: SHOW GRANTS reads
            # information_schema.table_privileges); filtered to rules
            # whose table pattern covers the named table
            rules = getattr(self.access_control, "rules", []) or []
            rows = [(r.user, r.table, r.privileges) for r in rules]
            if ast.table is not None:
                import re as _re

                rows = [
                    (u, tp, p) for (u, tp, p) in rows
                    if _re.fullmatch(tp, ast.table.lower())
                ]
            pg = Page.from_dict(
                {
                    "Grantee": [r[0] for r in rows] or [None],
                    "Table": [r[1] for r in rows] or [None],
                    "Privilege": [r[2] for r in rows] or [None],
                }
            )
            if not rows:
                pg = Page(pg.blocks, pg.names, 0)
            return QueryResult(pg, ("Grantee", "Table", "Privilege"))
        if isinstance(ast, t.ShowCatalogs):
            pg = Page.from_dict(
                {"Catalog": [str(getattr(self.catalog, "name", "default"))]}
            )
            return QueryResult(pg, ("Catalog",))
        if isinstance(ast, t.ShowCreateTable):
            name = ast.name.lower()
            if name in self.views:
                raise ValueError(
                    f"{name!r} is a view; use SHOW CREATE VIEW"
                )
            schema = self._table_schema(self.catalog, name)
            cols = ",\n   ".join(f"{c} {ty}" for c, ty in schema.items())
            txt = f"CREATE TABLE {name} (\n   {cols}\n)"
            pg = Page.from_dict({"Create Table": [txt]})
            return QueryResult(pg, ("Create Table",))
        if isinstance(ast, t.ShowStats):
            # reference ShowStatsRewrite: per-column CBO statistics —
            # NDV, null fraction, logical min/max + a summary row with
            # the table row count
            name = ast.name.lower()
            schema = self._table_schema(self.catalog, name)
            stats_fn = getattr(self.catalog, "column_stats", None)
            rows_total = None
            erc = getattr(self.catalog, "exact_row_count", None)
            if erc is not None:
                try:
                    rows_total = float(erc(name))
                except Exception:  # noqa: BLE001 - summary is advisory
                    rows_total = None
            cols, ndvs, nfs, lows, highs = [], [], [], [], []
            for c in schema:
                st = None
                if stats_fn is not None:
                    try:
                        st = stats_fn(name, c)
                    except Exception:  # noqa: BLE001 - per-column stats
                        # are advisory, same contract as rows_total above
                        st = None
                cols.append(c)
                ndvs.append(None if st is None else st.ndv)
                nfs.append(None if st is None else st.null_fraction)
                lows.append(None if st is None or st.min is None
                            else str(st.min))
                highs.append(None if st is None or st.max is None
                             else str(st.max))
            # summary row (column_name NULL, row_count set) — the
            # reference's layout
            cols.append(None)
            ndvs.append(None)
            nfs.append(None)
            lows.append(None)
            highs.append(None)
            rc = [None] * (len(cols) - 1) + [rows_total]
            pg = Page.from_dict(
                {
                    "column_name": cols,
                    "distinct_values_count": _opt_f64(ndvs),
                    "nulls_fraction": _opt_f64(nfs),
                    "row_count": _opt_f64(rc),
                    "low_value": lows,
                    "high_value": highs,
                }
            )
            return QueryResult(
                pg,
                ("column_name", "distinct_values_count", "nulls_fraction",
                 "row_count", "low_value", "high_value"),
            )
        if isinstance(ast, t.ShowSchemas):
            names = sorted(self.schemas)
            pg = Page.from_dict({"Schema": names})
            return QueryResult(pg, ("Schema",))

        # -- prepared statements (reference execution/PrepareTask.java,
        # DeallocateTask.java; DESCRIBE INPUT/OUTPUT statements) --
        if isinstance(ast, t.Prepare):
            from .sql.parser import parse as _parse

            _parse(ast.statement_sql)  # must at least parse
            self.prepared[ast.name.lower()] = ast.statement_sql
            return self._row_count_result(0)
        if isinstance(ast, t.Deallocate):
            if self.prepared.pop(ast.name.lower(), None) is None:
                raise ValueError(f"prepared statement {ast.name!r} not found")
            return self._row_count_result(0)
        if isinstance(ast, t.ExecutePrepared):
            return self._execute_prepared(ast, user)
        if isinstance(ast, t.DescribeInput):
            sql2 = self._prepared_sql(ast.name)
            from .sql.parser import parse as _parse

            n_params = t.count_parameters(_parse(sql2))
            import numpy as np

            pg = Page.from_dict(
                {
                    "Position": np.arange(max(n_params, 1), dtype=np.int64),
                    "Type": ["unknown"] * max(n_params, 1),
                }
            )
            if n_params == 0:
                pg = Page(pg.blocks, pg.names, 0)
            return QueryResult(pg, ("Position", "Type"))
        if isinstance(ast, t.DescribeOutput):
            sql2 = self._prepared_sql(ast.name)
            from .sql.parser import parse as _parse

            past = _parse(sql2)
            n_params = t.count_parameters(past)
            past = t.substitute_parameters(
                past, tuple(t.NullLiteral() for _ in range(n_params))
            )
            if not isinstance(past, t.Query):
                pg = Page.from_dict({"Column": [None], "Type": [None]})
                return QueryResult(
                    Page(pg.blocks, pg.names, 0), ("Column", "Type")
                )
            # column names/types are metadata: same privilege as reading
            # (SHOW COLUMNS enforces this; DESCRIBE OUTPUT must too)
            if self.access_control is not None:
                from .security import enforce

                enforce(self.access_control, user, past, views=self.views)
            planner = Planner(self.catalog, views=self.views)
            rp = planner.plan_query(past, outer=None, ctes={})
            pg = Page.from_dict(
                {
                    "Column": [f.name for f in rp.scope.fields],
                    "Type": [str(f.type) for f in rp.scope.fields],
                }
            )
            return QueryResult(pg, ("Column", "Type"))

        # -- session properties (reference SetSessionTask.java,
        # ResetSessionTask.java) --
        if isinstance(ast, t.SetSession):
            key = ast.name.lower()
            if key not in SESSION_PROPERTIES:
                raise ValueError(f"unknown session property {key!r}")
            self._session_overrides[key] = SESSION_PROPERTIES[key](
                str(self._literal_value(ast.value))
            )
            return self._row_count_result(0)
        if isinstance(ast, t.ResetSession):
            self._session_overrides.pop(ast.name.lower(), None)
            return self._row_count_result(0)
        if isinstance(ast, t.ShowSession):
            rows = sorted(SESSION_PROPERTIES)
            vals = [
                str(self._session_overrides.get(k, "")) for k in rows
            ]
            pg = Page.from_dict({"Name": rows, "Value": vals})
            return QueryResult(pg, ("Name", "Value"))

        # -- ALTER TABLE (reference RenameTableTask.java,
        # RenameColumnTask.java, AddColumnTask.java, DropColumnTask) --
        if isinstance(ast, (t.RenameTable, t.RenameColumn, t.AddColumn,
                            t.DropColumn)):
            return self._alter_table(ast)

        # -- GRANT / REVOKE wired into security.py (reference
        # GrantTask.java, RevokeTask.java) --
        if isinstance(ast, (t.Grant, t.Revoke)):
            ac = self.access_control
            if ac is None or not hasattr(ac, "grant"):
                raise ValueError(
                    "GRANT/REVOKE requires a mutable access control "
                    "(security.RuleBasedAccessControl)"
                )
            table = ast.table.lower()
            if isinstance(ast, t.Grant):
                ac.grant(ast.grantee, table, ast.privilege)
            else:
                ac.revoke(ast.grantee, table, ast.privilege)
            return self._row_count_result(0)
        raise ValueError(f"unsupported statement {type(ast).__name__}")

    def _prepared_sql(self, name: str) -> str:
        sql = self.prepared.get(name.lower())
        if sql is None:
            raise ValueError(f"prepared statement {name!r} not found")
        return sql

    # -- EXECUTE fast path (exec/qcache.py plan skeletons) --

    def _execute_prepared(self, ast: t.ExecutePrepared, user) -> QueryResult:
        """EXECUTE binds USING values as TYPED CONSTANTS into a cached
        plan skeleton: N executions of one dashboard statement parse and
        plan once, and identical (statement, values, snapshot) executions
        serve straight from the result cache. There is no text
        substitution anywhere on this path — a string parameter is a
        varchar constant, never SQL."""
        sql2 = self._prepared_sql(ast.name)
        from .sql.parser import parse as _parse

        past = _parse(sql2)
        n_params = t.count_parameters(past)
        if len(ast.params) != n_params:
            raise ValueError(
                f"prepared statement {ast.name!r} expects {n_params} "
                f"parameters, got {len(ast.params)}"
            )
        bound = t.substitute_parameters(past, ast.params)
        # the prepared text was an opaque string to the PREPARE-time
        # check: the BOUND statement must pass the same enforcement a
        # direct query would (EXECUTE is not a privilege bypass)
        if self.access_control is not None:
            from .security import enforce

            enforce(self.access_control, user, bound, views=self.views)
        if not isinstance(bound, t.Query):
            return self._execute_statement(bound, user)
        # SET SESSION overrides apply to prepared executions the same as
        # to direct queries
        target = (
            self.with_properties(dict(self._session_overrides))
            if self._session_overrides
            else self
        )
        node = target._plan_prepared(past, ast.params, bound)
        return target._execute_plan_cached(node)

    def _plan_prepared(
        self, past, params, bound: t.Query
    ) -> N.PlanNode:
        """Plan an EXECUTE through the skeleton cache: parameters become
        param-tagged typed literals, the optimized plan is cached once
        per (statement, parameter-type signature, planning env), and new
        values REBIND the cached tree instead of re-planning. Guards, in
        order: (1) the skeleton is only kept when every parameter index
        survives into the plan (a value consumed at plan time — LIMIT ?,
        a folded negation — disqualifies it), (2) the first rebind to new
        values is verified against one direct re-plan, then trusted,
        (3) anything non-rebindable falls back to the ordinary per-value
        plan cache."""
        from .exec import qcache

        if not self.plan_cache or not params:
            return self._plan_query_cached(bound)
        lits = [self._param_literal(p) for p in params]
        if any(lv is None for lv in lits):
            # non-literal USING expressions: per-value plan cache only
            return self._plan_query_cached(bound)
        values = tuple(lv.value for lv in lits)
        sig = tuple(str(lv.type) for lv in lits)
        key = ("x", past, sig, self._plan_env_key())
        ent = qcache.PLAN_CACHE.lookup(key, self.catalog)
        if ent is not None and ent.rebindable:
            if values == ent.values0:
                return ent.plan
            plan = qcache.rebind_plan(ent.plan, values)
            if not ent.verified:
                direct = self._plan_query_uncached(bound)
                if qcache.strip_params(plan) == direct:
                    ent.verified = True
                else:
                    ent.rebindable = False
                    return direct
            return plan
        if ent is not None:  # known-non-rebindable statement shape
            return self._plan_query_cached(bound)
        wrapped = t.substitute_parameters(
            past,
            tuple(t.BoundParameter(i, p) for i, p in enumerate(params)),
        )
        try:
            skel = self._plan_query_uncached(wrapped)
        except Exception:  # noqa: BLE001 — param in a literal-only spot
            skel = None
        rebindable = skel is not None and (
            qcache.collect_param_indices(skel) == set(range(len(params)))
        )
        if not rebindable:
            fallback = self._plan_query_cached(bound)
            qcache.PLAN_CACHE.store(
                key, fallback, self.catalog,
                rebindable=False, values0=values,
            )
            return fallback
        qcache.PLAN_CACHE.store(
            key, skel, self.catalog,
            rebindable=True, verified=False, values0=values,
        )
        return skel

    @staticmethod
    def _param_literal(node):
        """Plan one USING argument as a typed ir constant (mirrors the
        planner's literal cases), or None when it is not a plain literal."""
        from .expr import ir
        from . import types as T
        from .sql.planner import _number_literal, _parse_timestamp_literal

        if isinstance(node, t.UnaryOp) and node.op == "-" and isinstance(
            node.operand, t.NumberLiteral
        ):
            lit = _number_literal(node.operand.text)
            if not isinstance(lit.value, (int, float)):
                return None  # Decimal lanes stay symbolic (planner parity)
            return ir.Literal(-lit.value, lit.type)
        if isinstance(node, t.NumberLiteral):
            return _number_literal(node.text)
        if isinstance(node, t.StringLiteral):
            return ir.Literal(node.value, T.VARCHAR)
        if isinstance(node, t.BooleanLiteral):
            return ir.Literal(node.value, T.BOOLEAN)
        if isinstance(node, t.NullLiteral):
            return ir.Literal(None, T.UNKNOWN)
        if isinstance(node, t.DateLiteral):
            return ir.Literal(node.value, T.DATE)
        if isinstance(node, t.TimestampLiteral):
            return ir.Literal(
                _parse_timestamp_literal(node.value), T.TIMESTAMP
            )
        if isinstance(node, t.IntervalLiteral):
            n = int(node.value) * (-1 if node.negative else 1)
            if node.unit in ("year", "month"):
                months = n * (12 if node.unit == "year" else 1)
                return ir.Literal(months, T.INTERVAL_YEAR_MONTH)
            if node.unit == "day":
                return ir.Literal(n, T.INTERVAL_DAY)
        return None

    @staticmethod
    def _literal_value(node):
        if isinstance(node, t.StringLiteral):
            return node.value
        if isinstance(node, t.NumberLiteral):
            return node.text
        if isinstance(node, t.BooleanLiteral):
            return node.value
        raise ValueError("SET SESSION requires a literal value")

    def _alter_table(self, ast) -> QueryResult:
        """ALTER TABLE against a writable connector: metadata-only ops are
        implemented as a page rewrite + replace (the in-memory connectors
        have no separate metadata store)."""
        import numpy as np

        from . import types as T
        from .page import Block, Page

        cat = self._writable()
        name = (ast.name if isinstance(ast, t.RenameTable) else ast.table).lower()
        if name not in cat.table_names():
            raise ValueError(f"table {name!r} does not exist")
        page = cat.page(name)
        if isinstance(ast, t.RenameTable):
            new = ast.new_name.lower()
            if new in cat.table_names() or new in self.views:
                raise ValueError(f"table {new!r} already exists")
            cat.create_table_from_page(new, page)
            cat.drop_table(name)
            return self._row_count_result(0)
        cols = list(page.names)
        blocks = list(page.blocks)
        if isinstance(ast, t.RenameColumn):
            old = ast.name.lower()
            new = ast.new_name.lower()
            if old not in cols:
                raise ValueError(f"column {old!r} does not exist")
            if new in cols:
                raise ValueError(f"column {new!r} already exists")
            cols[cols.index(old)] = new
        elif isinstance(ast, t.AddColumn):
            cname = ast.column.name.lower()
            if cname in cols:
                raise ValueError(f"column {cname!r} already exists")
            typ = T.parse_type(ast.column.type_name)
            import jax.numpy as jnp

            data = jnp.zeros(page.capacity, typ.storage_dtype)
            valid = jnp.zeros(page.capacity, bool)  # all NULL
            cols.append(cname)
            blocks.append(Block(data, typ, valid))
        elif isinstance(ast, t.DropColumn):
            cname = ast.name.lower()
            if cname not in cols:
                raise ValueError(f"column {cname!r} does not exist")
            if len(cols) == 1:
                raise ValueError("cannot drop the only column")
            i = cols.index(cname)
            del cols[i]
            del blocks[i]
        cat.replace(name, Page(tuple(blocks), tuple(cols), page.count))
        return self._row_count_result(0)

    def _create_table(self, ast: t.CreateTable) -> QueryResult:
        from . import types as T
        from .page import Page

        cat = self._writable()
        name = ast.name.lower()
        if name in self.views:
            # the planner resolves views first, so a same-named table
            # would be permanently shadowed — reject the collision both
            # ways (CREATE VIEW already checks tables)
            raise ValueError(f"view {name!r} already exists")
        if name in self.matviews_mgr.views:
            raise ValueError(f"materialized view {name!r} already exists")
        if name in cat.table_names():
            if ast.if_not_exists:
                return self._row_count_result(0)
            raise ValueError(f"table {name!r} already exists")
        if ast.query is None:
            schema = {}
            for col in ast.columns:
                cname = col.name.lower()
                if cname in schema:
                    raise ValueError(f"duplicate column {cname!r}")
                schema[cname] = T.parse_type(col.type_name)
            cat.create_table(name, schema)
            return self._row_count_result(0)
        page, titles, _scope = self._run_query_ast(ast.query)
        lowered = tuple(tl.lower() for tl in titles)
        if len(set(lowered)) != len(lowered):
            raise ValueError("CREATE TABLE AS requires unique column names")
        for tl, blk in zip(lowered, page.blocks):
            if isinstance(blk.type, T.UnknownType):
                raise ValueError(
                    f"CREATE TABLE AS column {tl!r} has unknown type "
                    "(all-NULL); cast it to a concrete type"
                )
        cat.create_table_from_page(name, Page(page.blocks, lowered, page.count))
        return self._row_count_result(int(page.count))

    def _insert(self, ast: t.Insert) -> QueryResult:
        from . import types as T
        from .expr import ir
        from .expr.compiler import project_page
        from .ops.union import null_block
        from .page import Page

        cat = self._writable()
        name = ast.table.lower()
        schema = self._table_schema(cat, name)
        targets = (
            tuple(c.lower() for c in ast.columns)
            if ast.columns
            else tuple(schema)
        )
        if len(set(targets)) != len(targets):
            raise ValueError("duplicate column in INSERT target list")
        for c in targets:
            if c not in schema:
                raise ValueError(f"column {c!r} not in table {name!r}")
        page, _titles, _scope = self._run_query_ast(
            ast.query if isinstance(ast.query, t.Query) else t.Query(ast.query)
        )
        if page.num_columns != len(targets):
            raise ValueError(
                f"INSERT has {page.num_columns} columns, expected {len(targets)}"
            )
        # positional channels, then cast each source column to the target type
        chans = tuple(f"c{i}" for i in range(page.num_columns))
        page = Page(page.blocks, chans, page.count)
        exprs = []
        for ch, blk, col in zip(chans, page.blocks, targets):
            ref = ir.ColumnRef(ch, blk.type)
            want = schema[col]
            exprs.append(ref if blk.type == want else ir.cast(ref, want))
        cast_pg = project_page(page, tuple(exprs), targets)
        # assemble full-width page in table column order; unmentioned
        # columns are NULL
        by_name = dict(zip(targets, cast_pg.blocks))
        cap = cast_pg.capacity if cast_pg.blocks else 1
        blocks = []
        for col, ty in schema.items():
            if col in by_name:
                blocks.append(by_name[col])
            else:
                did = None
                if isinstance(ty, T.VarcharType):
                    from .page import intern_dictionary

                    did = intern_dictionary(())
                blocks.append(null_block(ty, cap, did))
        cat.append(name, Page(tuple(blocks), tuple(schema), page.count))
        return self._row_count_result(int(page.count))

    def _delete(self, ast: t.Delete) -> QueryResult:
        cat = self._writable()
        name = ast.table.lower()
        schema = self._table_schema(cat, name)
        before = int(cat.page(name).count)
        if ast.where is None:
            from .ops.union import empty_page

            cat.replace(name, empty_page(schema))
            return self._row_count_result(before)
        # keep rows where the predicate is NOT TRUE (false or null)
        keep = t.Case(
            None,
            ((ast.where, t.BooleanLiteral(False)),),
            t.BooleanLiteral(True),
        )
        sel = t.Select(
            items=(t.Star(),),
            from_=t.Table(name),
            where=keep,
            group_by=(),
            having=None,
            distinct=False,
        )
        page, titles, _scope = self._run_query_ast(t.Query(sel))
        from .page import Page

        cat.replace(name, Page(page.blocks, tuple(tl.lower() for tl in titles), page.count))
        return self._row_count_result(before - int(page.count))

    def _collector_executor(self, collector):
        """Fresh executor with a per-query stats collector, matching the
        engine the session actually runs (mesh / streaming / local plus
        the session's strategy overrides). Used by EXPLAIN ANALYZE and
        by the feedback plane's observe-once runs: the shared executor
        can't have a collector swapped in per query under concurrency."""
        if self.mesh is not None:
            from .exec.dist import DistributedExecutor

            ex = DistributedExecutor(self.catalog, self.mesh, collector=collector)
        elif self.streaming:
            # profile the SAME engine the session runs: streamed batches
            # under the session's memory budget (per-node stats cover the
            # kernels the streaming driver delegates to the local executor)
            from .exec.stream import StreamingExecutor

            ex = StreamingExecutor(
                self.catalog,
                batch_rows=self.batch_rows,
                memory_budget=self.memory_budget,
                collector=collector,
            )
        else:
            ex = Executor(self.catalog, collector=collector)
        # profile with the session's strategy overrides (pallas/matmul
        # group-by), matching the executor the session actually runs
        local = getattr(ex, "local", ex)
        if self.pallas_groupby is not None and hasattr(local, "pallas_groupby"):
            local.pallas_groupby = self.pallas_groupby
        if self.matmul_groupby is not None and hasattr(local, "matmul_groupby"):
            local.matmul_groupby = self.matmul_groupby
        if hasattr(local, "dynamic_filtering"):
            local.dynamic_filtering = self.dynamic_filtering
        return ex

    def explain_analyze_plan(self, node: N.PlanNode) -> str:
        """Execute the plan with per-operator accounting and render the
        annotated tree (reference EXPLAIN ANALYZE via ExplainAnalyzeOperator,
        presto-main/.../execution/ExplainAnalyzeContext.java)."""
        from .exec.stats import StatsCollector

        collector = StatsCollector()
        ex = self._collector_executor(collector)
        from .obs import span as obs_span

        traced = obs_span.enabled()
        trace = root = exec_span = None
        if traced:
            # a trace of its own, so that the `-- trace:` footer ranks
            # this run alone; `Executor._run` hangs the live operator
            # spans under `execute`, the units the cluster path ships
            trace = obs_span.TRACES.new_trace()
            root = trace.enter("query")
            exec_span = trace.enter("execute")
        try:
            ex.run(node)
        finally:
            if traced:
                trace.leave(exec_span)
                trace.leave(root)
        # fold parked device row-count scalars in one batch (the lazy
        # collector avoids a blocking host sync per plan node)
        collector.resolve()
        device_s = {}
        if traced:
            # each node's stretch of the device's queue, from the ready
            # stamps its spans got (the resident executor's; the watcher
            # may be a moment behind the reads above)
            obs_span.settle()
            for span, secs in trace.device_spans():
                pos = span.attrs.get("pos")
                device_s[pos] = device_s.get(pos, 0.0) + secs
            for node_id, pos in N.plan_positions(node).items():
                stats = collector.by_node.get(node_id)
                if stats is not None and pos in device_s:
                    stats.device_s = device_s[pos]
        tree = N.plan_tree_str(node, collector=collector)
        total_ms = collector.total_wall_s() * 1e3
        peak = collector.peak_bytes / (1024 * 1024)
        from .exec.stats import kernel_breaker_lines

        breakers = kernel_breaker_lines()
        breaker_txt = "".join(f"\n-- {line}" for line in breakers)
        dyn_txt = ""
        dyn_ctx = getattr(
            ex, "dyn_ctx", getattr(getattr(ex, "local", None), "dyn_ctx", None)
        )
        if dyn_ctx is not None and dyn_ctx.snapshot()["filters"]:
            snap = dyn_ctx.snapshot()
            filters = ", ".join(
                f"{fid}={d}" for fid, d in sorted(snap["filters"].items())
            )
            scan_p = sum(snap["scan_pruned"].values())
            pre_p = sum(snap["preprobe_pruned"].values())
            dyn_txt = (
                f"\n-- dynamic filters: {filters}; rows_pruned="
                f"{scan_p + pre_p:,} (scan {scan_p:,}, pre-probe {pre_p:,})"
            )
            if snap["wait_s"]:
                dyn_txt += f", wait {snap['wait_s']:.2f}s"
        # memory-arbitration line: every rung of the degradation ladder
        # the query touched (offload events, disk tier, hybrid-join
        # partitioning/recursion, revocations) + over-free accounting
        mem_txt = ""
        spill_ev = getattr(ex, "spill_events", None)
        if spill_ev is not None:
            st = getattr(ex, "spill_stats", {}) or {}
            pool = getattr(ex, "pool", None)
            revs = getattr(pool, "revocations", 0) if pool else 0
            overs = getattr(pool, "over_frees", 0) if pool else 0
            if spill_ev or revs or overs or st.get("disk_bytes"):
                parts = []
                if spill_ev:
                    parts.append("spill " + ",".join(sorted(set(spill_ev))))
                if st.get("disk_bytes"):
                    parts.append(f"disk {st['disk_bytes']:,}B")
                if st.get("hybrid_parts"):
                    parts.append(
                        f"hybrid parts={st['hybrid_parts']} "
                        f"depth={st.get('hybrid_depth', 0)}"
                    )
                if st.get("ragged_pages"):
                    # ragged paged partition layout (ops/ragged.py):
                    # pages allocated for the hybrid build partitions and
                    # their live-slot occupancy (pad-to-max would be 100%
                    # only under zero skew)
                    parts.append(
                        f"ragged pages={st['ragged_pages']} "
                        f"occ={st.get('ragged_occupancy_pct', 0)}%"
                    )
                if st.get("agg_hash_batches"):
                    parts.append(
                        f"agg_hash_batches={st['agg_hash_batches']}"
                    )
                if st.get("chunk_fallbacks"):
                    parts.append(f"chunk_fallbacks={st['chunk_fallbacks']}")
                if revs:
                    parts.append(f"revocations={revs}")
                if overs:
                    parts.append(f"over_frees={overs}")
                mem_txt = "\n-- memory: " + ", ".join(parts)
        # mesh-exchange line: repartition collectives (ICI all_to_all
        # wall, measured to host sync) and grouped-join chunked exchanges
        exch_txt = ""
        ex_ev = getattr(ex, "exchange_events", None)
        if ex_ev:
            reparts = [e for e in ex_ev if e.get("kind") == "repartition"]
            grouped = [e for e in ex_ev if "buckets" in e]
            parts = []
            if reparts:
                coll_ms = sum(e["collective_ms"] for e in reparts)
                rows = sum(e["rows"] for e in reparts)
                parts.append(
                    f"{len(reparts)} repartition collectives over "
                    f"{reparts[0]['shards']} shards, {rows:,} rows, "
                    f"device {coll_ms:,.1f}ms"
                )
            for e in grouped:
                parts.append(
                    f"grouped join buckets={e['buckets']} "
                    f"peak {e['per_shard_bytes']:,}B/shard"
                )
            exch_txt = "\n-- exchange: " + "; ".join(parts)
        # serving-cache observability (exec/qcache.py): process-wide
        # hits/misses/evictions/bytes for the plan, result and kernel
        # caches — EXPLAIN ANALYZE itself always re-executes, so these
        # are the counters the profiled query runs alongside
        from .exec import qcache

        cache_txt = "\n-- caches: " + qcache.format_summary(
            qcache.snapshot_all()
        )
        # adaptive-execution feedback (plan/history.py): fold this run's
        # observed cardinalities into the history store, then surface the
        # plane's counters — lookup hits, estimate-vs-observed relative
        # error, and mid-query replans — so a profiled query shows both
        # what history it consumed and what it contributed
        feedback_txt = ""
        from .plan import history as _H

        if _H.feedback_on():
            try:
                _H.HISTORY.record_plan(node, collector, self.catalog)
            except Exception as exc:  # noqa: BLE001 — bookkeeping only
                from .exec.breaker import BREAKERS

                BREAKERS.record_failure("adaptive_plan", repr(exc))
            fs = _H.HISTORY.stats.snapshot()
            err = fs["mean_abs_rel_err"]
            feedback_txt = (
                f"\n-- feedback: hits={fs['hits']} misses={fs['misses']}"
                f" records={fs['records']} est-err="
                f"{'n/a' if err is None else f'{err:.2f}'}"
                f" replans={fs['replans']}"
            )
        # materialized-view freshness (matview/manager.py): which views
        # exist, delta vs recompute maintenance, and how stale each is
        matview_txt = ""
        mgr = getattr(self, "matviews_mgr", None)
        if mgr is not None and mgr.views:
            matview_txt = "\n-- matview: " + mgr.format_summary()
        # observability footers (docs/observability.md): the critical
        # path from the SAME span-tree renderer the cluster path uses;
        # what this run compiled (the spans' listener booked it on the
        # span that paid) and the device-side spans printed a node above
        trace_txt = kernel_txt = ""
        if traced:
            from .server import knobs as _knobs

            trace_txt = "\n-- trace: " + obs_span.render_critical_path(
                trace, _knobs.trace_topk()
            )
            compiles = root.attrs.get("compiles", 0)
            if compiles or device_s:
                kernel_txt = (
                    f"\n-- kernels: compile +{compiles}"
                    f" ({root.attrs.get('compile_s', 0.0) * 1e3:,.1f}ms),"
                    f" device-side {sum(device_s.values()) * 1e3:,.1f}ms"
                    f" over {len(device_s)} operators"
                )
        return (
            f"{tree}{dyn_txt}{breaker_txt}{mem_txt}{exch_txt}{cache_txt}"
            f"{feedback_txt}{matview_txt}{trace_txt}{kernel_txt}\n"
            f"-- total {total_ms:,.1f}ms, peak live output {peak:,.2f}MB"
        )

    def explain_analyze(self, sql: str) -> str:
        return self.explain_analyze_plan(self.plan(sql))
