"""HTTP cluster execution: node discovery, failure detection, and the
stage scheduler that runs fragmented plans across worker processes.

Re-designed equivalents (SURVEY L3 + L11 + §2.7):
* NodeManager — DiscoveryNodeManager + HeartbeatFailureDetector
  (failureDetector/HeartbeatFailureDetector.java:77): periodic /v1/status
  probes, consecutive-failure threshold marks a worker FAILED and excludes
  it from scheduling. Consecutive TASK failures additionally BLACKLIST a
  worker (drained from scheduling even though its /v1/status is healthy —
  the round-5 failure mode was exactly a live-but-faulting worker); after
  `blacklist_recovery` seconds a healthy probe re-admits it. State
  transitions emit worker-up/down events through server/events.py.
* HttpScheduler — SqlQueryScheduler + SqlStageExecution + HttpRemoteTask
  (execution/scheduler/SqlQueryScheduler.java:112): cuts the fragmented
  plan (plan/fragment.py Exchange tree) at exchange boundaries into
  stages, runs leaf stages as one task per worker over row-range splits,
  links consumer tasks to producer output buffers (worker w pulls hash
  partition w from every producer — the pull-based FIXED_HASH shuffle),
  and executes the root single-distribution fragment on the coordinator.

Fault tolerance (docs/fault-tolerance.md): unlike the reference (a worker
loss fails the whole query, SURVEY §5), tasks that fail to START — POST
refused, or FAILED at the eager status check with a retryable cause — are
retried with exponential backoff + jitter onto an alternate healthy
worker, up to `max_task_retries` alternates. Failures past that point
(mid-stream faults surfacing on the results pull) trigger a bounded
QUERY-level re-execution against a fresh worker snapshot. Fatal causes
(low-memory kill, memory exhaustion, protocol violations) are never
retried. Sibling tasks of an unrecoverable failure are canceled eagerly.

This is the DCN/multi-host data path; exec/dist.py's shard_map collectives
remain the intra-slice ICI path."""

from __future__ import annotations

import base64
import dataclasses
import itertools
import json
import os
import pickle
import random
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

from ..plan import nodes as N
from ..plan.fragment import Exchange
from . import knobs
from .exchange import ExchangeClient, ExchangeError, ExchangeStats
from .serde import WireStats, negotiate
from .worker import (
    _FATAL_MARKERS,
    FragmentExecutor,
    RemoteSource,
)


def _retryable_message(msg: str) -> bool:
    """Classify an unstructured failure message: fatal causes would recur
    identically on any worker / attempt (see worker._classify_failure)."""
    return not any(m in msg for m in _FATAL_MARKERS)


def _http_error_details(e: "urllib.error.HTTPError") -> Tuple[str, bool]:
    """(detail, retryable) from a worker's structured error response —
    a POST 500 carries errorInfo.retryable, which must not be blindly
    retried away when it says false."""
    try:
        payload = json.loads(e.read())
    except Exception:  # noqa: BLE001 — unparseable error body: fall back
        # to classifying the HTTPError's own message below
        payload = {}
    if not isinstance(payload, dict):
        payload = {}
    detail = payload.get("error") or str(e)
    info = payload.get("errorInfo") or {}
    return detail, bool(info.get("retryable", _retryable_message(detail)))


class NodeManager:
    """Tracks worker liveness via heartbeats; failed nodes are excluded
    from scheduling until they respond again. Consecutive task failures
    blacklist (drain) a worker with timed re-admission."""

    def __init__(self, worker_uris: List[str], interval: float = 5.0,
                 failure_threshold: int = 3,
                 task_failure_threshold: int = 3,
                 blacklist_recovery: float = 30.0,
                 event_bus=None):
        self.workers = {
            u: {"state": "ACTIVE", "failures": 0, "task_failures": 0,
                "blacklisted_at": None, "wire": None}
            for u in worker_uris
        }
        self.interval = interval
        self.failure_threshold = failure_threshold
        self.task_failure_threshold = task_failure_threshold
        self.blacklist_recovery = blacklist_recovery
        self.event_bus = event_bus
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self) -> "NodeManager":
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()

    def active_workers(self) -> List[str]:
        with self._lock:
            return [
                u for u, s in self.workers.items() if s["state"] == "ACTIVE"
            ]

    def all_workers(self) -> List[str]:
        with self._lock:
            return list(self.workers)

    # -- state transitions (events fire outside the lock) --

    def _set_state(self, uri: str, state: str, reason: str) -> None:
        with self._lock:
            st = self.workers[uri]
            if st["state"] == state:
                return
            st["state"] = state
            if state == "BLACKLISTED":
                st["blacklisted_at"] = time.time()
            elif state == "ACTIVE":
                st["failures"] = 0
                st["task_failures"] = 0
                st["blacklisted_at"] = None
        if self.event_bus is not None:
            self.event_bus.fire_worker_state(uri, state, reason)

    def record_task_failure(self, uri: str, reason: str = "") -> None:
        """A task on this worker failed to start/run. N consecutive
        failures drain the worker (reference analog: the coordinator
        operator manually shutting down a flaky node)."""
        with self._lock:
            st = self.workers.get(uri)
            if st is None:
                return
            st["task_failures"] += 1
            drain = (
                st["state"] == "ACTIVE"
                and st["task_failures"] >= self.task_failure_threshold
            )
        if drain:
            self._set_state(
                uri, "BLACKLISTED",
                f"{self.task_failure_threshold} consecutive task failures"
                + (f": {reason[:120]}" if reason else ""),
            )

    def record_task_success(self, uri: str) -> None:
        with self._lock:
            st = self.workers.get(uri)
            if st is not None:
                st["task_failures"] = 0

    def wire_caps(self, uri: str) -> Optional[dict]:
        """Cached wire capabilities a worker advertised through its
        status handshake; fetched once on demand when the heartbeat loop
        has not probed yet. None = unknown (negotiation degrades to the
        baseline wire format for the whole fleet). A failed probe is
        negatively cached for one heartbeat interval so an unreachable
        worker costs ONE query a 2s stall, not every query."""
        with self._lock:
            st = self.workers.get(uri)
            if st is None:
                return None
            cached = st.get("wire")
            failed_at = st.get("wire_probe_failed_at")
        if cached is not None:
            return cached
        if failed_at is not None and time.time() - failed_at < self.interval:
            return None
        caps = None
        try:
            with urllib.request.urlopen(f"{uri}/v1/status", timeout=2) as r:
                caps = json.loads(r.read()).get("wire")
        except Exception:  # noqa: BLE001 - unknown peer stays baseline
            caps = None
        with self._lock:
            st = self.workers.get(uri)
            if st is not None:
                if isinstance(caps, dict):
                    st["wire"] = caps
                    st.pop("wire_probe_failed_at", None)
                else:
                    st["wire_probe_failed_at"] = time.time()
        return caps if isinstance(caps, dict) else None

    def wire_caps_all(self, uris: List[str]) -> List[Optional[dict]]:
        """wire_caps for a worker snapshot, fetching the uncached ones
        CONCURRENTLY — query submit must not pay a serial 2s-per-worker
        stall while the heartbeat cache warms up. A probe that misses
        the join window reports None (baseline degradation) instead of
        being re-issued serially; the daemon thread still warms the
        cache for the next query."""
        results: Dict[str, Optional[dict]] = {}
        with self._lock:
            for u in uris:
                st = self.workers.get(u)
                if st is not None and st.get("wire") is not None:
                    results[u] = st["wire"]
        missing = [u for u in uris if u not in results]
        if len(missing) == 1:
            results[missing[0]] = self.wire_caps(missing[0])
        elif missing:
            def probe(u):
                results[u] = self.wire_caps(u)

            threads = [
                threading.Thread(target=probe, args=(u,), daemon=True)
                for u in missing
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=3)
        return [results.get(u) for u in uris]

    def probe_all(self):
        for uri in self.all_workers():
            try:
                with urllib.request.urlopen(f"{uri}/v1/status", timeout=2) as r:
                    payload = json.loads(r.read())
                    ok = payload.get("state") == "ACTIVE"
                    # cache what the worker advertises NOW — including
                    # clearing a stale entry when a rolled-back build at
                    # the same URI stops advertising caps (else peers
                    # would keep sending it undecodable v2 pages)
                    caps = payload.get("wire")
                    with self._lock:
                        st = self.workers.get(uri)
                        if st is not None:
                            st["wire"] = (
                                caps if isinstance(caps, dict) else None
                            )
            except Exception:  # noqa: BLE001 - network failure IS the signal
                ok = False
            with self._lock:
                st = self.workers[uri]
                state = st["state"]
                if ok:
                    st["failures"] = 0
                else:
                    st["failures"] += 1
                # only an ACTIVE worker degrades to FAILED: a BLACKLISTED
                # worker keeps serving its drain penalty (otherwise a
                # restart would launder BLACKLISTED -> FAILED -> ACTIVE
                # and skip the recovery window)
                probe_failed = (
                    not ok
                    and state == "ACTIVE"
                    and st["failures"] >= self.failure_threshold
                )
                blacklist_done = (
                    ok
                    and state == "BLACKLISTED"
                    and st["blacklisted_at"] is not None
                    and time.time() - st["blacklisted_at"]
                    >= self.blacklist_recovery
                )
            if probe_failed:
                self._set_state(uri, "FAILED", "heartbeat probes exhausted")
            elif ok and state == "FAILED":
                self._set_state(uri, "ACTIVE", "heartbeat recovered")
            elif blacklist_done:
                # drained worker served its penalty and probes healthy:
                # re-admit (half-open — the next task failure streak
                # drains it again)
                self._set_state(uri, "ACTIVE", "blacklist recovery elapsed")

    def _loop(self):
        while not self._stop.wait(self.interval):
            self.probe_all()


def _has_remote_source(node) -> bool:
    """True when a producer subtree pulls from a deeper exchange — its
    static estimate would bottom out at the RemoteSource default, so
    observed-vs-estimated comparisons there are meaningless."""
    if isinstance(node, RemoteSource):
        return True
    return any(_has_remote_source(c) for c in node.children)


class TaskFailure(RuntimeError):
    """A task (or its stage) failed. Carries the worker URI, task id,
    attempt number, and whether the cause is retryable on another
    worker / query attempt."""

    def __init__(self, message: str, uri: str = "", task_id: str = "",
                 attempt: int = 1, retryable: bool = True):
        super().__init__(message)
        self.uri = uri
        self.task_id = task_id
        self.attempt = attempt
        self.retryable = retryable


@dataclasses.dataclass
class SchedulerStats:
    """Observable retry accounting (acceptance: retries must be visible,
    not inferred from timing)."""

    task_retries: int = 0
    query_retries: int = 0
    tasks_failed: int = 0
    worker_failures: Dict[str, int] = dataclasses.field(default_factory=dict)
    last_error: str = ""
    # cross-task dynamic filtering (exec/dynfilter.py): filters shipped
    # from build stages into probe-stage task specs, seconds spent in the
    # bounded wait, and waits that expired (proceed-without-filter)
    dynfilters_shipped: int = 0
    dynfilter_wait_s: float = 0.0
    dynfilter_timeouts: int = 0
    # mid-query adaptive replans (plan/history.py): attempts abandoned
    # at an exchange boundary because the observed stage output
    # contradicted the estimate grossly enough to re-plan downstream
    adaptive_replans: int = 0
    # pipelined exchange observability (server/exchange.py): per-source
    # pull stats of the LAST query attempt (coordinator-side gathers) +
    # best-effort producer-side encode stats polled from task statuses,
    # and the wire capability set the attempt negotiated
    exchange: Dict[str, dict] = dataclasses.field(default_factory=dict)
    wire_caps: Optional[dict] = None
    # memory-arbitration rollup polled from task statuses (worker-side
    # memoryStats/spillStats): disk bytes spilled, revocations absorbed,
    # spill events seen — the cluster half of EXPLAIN ANALYZE's memory line
    memory: Dict[str, object] = dataclasses.field(default_factory=dict)
    # hierarchical-exchange rollup (server/hier.py) of the LAST query:
    # mid-tree repartition producers are never pulled by the coordinator
    # (their consumers are other workers), so their hier snapshots are
    # folded query-wide by the final status sweep (_collect_task_obs)
    hier: Dict[str, object] = dataclasses.field(default_factory=dict)
    # serving-cache counters (exec/qcache.py snapshot_all) refreshed after
    # every cluster query — plan/result hits the coordinator served plus
    # the process-wide kernel cache
    caches: Optional[dict] = None

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


class HttpScheduler:
    """Executes a fragmented plan over HTTP workers; the coordinator runs
    the root fragment locally (its catalog serves coordinator-side scans
    of single-distribution subtrees, e.g. tiny dimension tables)."""

    def __init__(self, catalog, nodes: NodeManager,
                 max_task_retries: Optional[int] = None,
                 max_query_retries: Optional[int] = None,
                 task_deadline: Optional[float] = None,
                 status_deadline: float = 10.0,
                 status_timeout: float = 15.0,
                 backoff_base: float = 0.2,
                 backoff_cap: float = 5.0):
        self.catalog = catalog
        self.nodes = nodes
        self._task_ids = itertools.count(1)
        env = os.environ.get
        self.max_task_retries = (
            int(env("PRESTO_TPU_TASK_RETRIES", "3"))
            if max_task_retries is None else max_task_retries
        )
        self.max_query_retries = (
            int(env("PRESTO_TPU_QUERY_RETRIES", "2"))
            if max_query_retries is None else max_query_retries
        )
        # wall ceiling on any single task's results stream: a wedged
        # worker (RUNNING forever, producing nothing) fails the pull
        # instead of hanging the coordinator
        self.task_deadline = (
            knobs.task_deadline_s()
            if task_deadline is None else task_deadline
        )
        self.status_deadline = status_deadline
        self.status_timeout = status_timeout
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        # bounded wait for a build stage to publish dynamic-filter
        # summaries before the probe stage launches; expiry degrades to
        # proceed-without-filter (reference: dynamic filtering's
        # collection timeout). 0 disables cross-task shipping.
        self.dynfilter_wait = float(env("PRESTO_TPU_DYNFILTER_WAIT_S", "10"))
        self.stats = SchedulerStats()
        self._lock = threading.Lock()

    # -- public --

    def record_caches(self, snapshot: dict) -> None:
        """Publish serving-cache counters into stats. Sessions call this
        after every query, concurrent with worker status polls mutating
        stats under _lock — the write must take the same lock."""
        with self._lock:
            self.stats.caches = snapshot
            from ..obs.export import export_scheduler_stats

            # republish the cumulative scheduler counters as gauges once
            # per query (idempotent; the registry takes its own lock
            # inside ours, never the reverse)
            export_scheduler_stats(self.stats)

    def stats_snapshot(self) -> dict:
        """Point-in-time copy of SchedulerStats for EXPLAIN ANALYZE and
        the stats surfaces; reading fields off the live object would
        race the pollers mid-update."""
        with self._lock:
            return self.stats.snapshot()

    def run(self, root: N.PlanNode, query_id: Optional[str] = None,
            trace_ctx: Optional[tuple] = None, adapt: bool = True):
        """Execute with bounded query-level re-execution: a retryable
        failure that escaped per-task retry (e.g. a mid-stream worker
        loss) re-runs the whole plan against a fresh worker snapshot.

        `trace_ctx` is the observability plane's (Trace, parent span_id)
        pair (docs/observability.md): each query-level attempt gets its
        own child span, so a retried query shows up as SIBLING attempt
        subtrees, never an overwrite."""
        if query_id is None:
            import uuid

            # unique across sessions sharing these workers: per-query
            # memory accounting must never merge two queries
            query_id = f"q_{uuid.uuid4().hex[:12]}"
        trace = trace_ctx[0] if trace_ctx else None
        for attempt in range(self.max_query_retries + 1):
            # distinct per-attempt query id: a prior attempt's dying
            # tasks must not share memory accounting with the re-run
            qid = query_id if attempt == 0 else f"{query_id}.r{attempt}"
            aspan = None
            if trace is not None:
                aspan = trace.begin(
                    f"attempt {attempt}", parent_id=trace_ctx[1],
                    query_id=qid,
                )
            try:
                result = self._run_attempt(
                    root, qid,
                    tctx=(trace, aspan.span_id) if trace else None,
                    adapt=adapt,
                )
                if trace is not None:
                    trace.finish(aspan)
                return result
            except RuntimeError as exc:
                if trace is not None:
                    trace.finish(aspan, "error", error=str(exc)[:200])
                retryable = getattr(exc, "retryable", None)
                if retryable is None:
                    retryable = _retryable_message(str(exc))
                if not retryable or attempt >= self.max_query_retries:
                    raise
                # a MID-STREAM failure attributed to a worker counts
                # toward its blacklist streak too — a live-but-faulting
                # worker must drain even when its tasks start cleanly
                uri = getattr(exc, "uri", "")
                if uri:
                    self._note_task_failure(uri, str(exc))
                with self._lock:
                    self.stats.query_retries += 1
                    self.stats.last_error = str(exc)[:300]
                time.sleep(self._backoff(attempt))
                if not self.nodes.active_workers():
                    raise

    def _run_attempt(self, root: N.PlanNode, query_id: str,
                     tctx: Optional[tuple] = None, adapt: bool = True):
        # snapshot membership for the whole attempt (threaded explicitly
        # so concurrent queries can't clobber each other): producer
        # partition counts must match consumer task counts even if a node
        # fails mid-query (per-task retry then re-posts the SAME spec to
        # an alternate member of the snapshot)
        workers = self.nodes.active_workers()
        if not workers:
            raise TaskFailure("no active workers", retryable=False)
        # wire-format handshake: intersect the snapshot's advertised
        # capabilities (+ the coordinator's own) once per attempt and
        # ship the result in every task spec — a mixed fleet agrees on
        # codecs/encodings instead of failing on deserialize
        wire_caps = negotiate(self.nodes.wire_caps_all(workers))
        with self._lock:
            self.stats.wire_caps = wire_caps
            self.stats.exchange = {}
            self.stats.memory = {}
            self.stats.hier = {}
        all_tasks: List[Tuple[str, str, bool]] = []
        try:
            fragment, specs = self._cut(root)
            sources = self._resolve_sources(
                specs, False, workers, all_tasks, query_id,
                dyn_links=self._dyn_links(fragment, specs),
                dyn_values={},
                wire_caps=wire_caps,
                tctx=tctx,
                adapt=adapt,
            )
            rspan = (
                tctx[0].begin("root-fragment", parent_id=tctx[1])
                if tctx else None
            )
            ex = FragmentExecutor(self.catalog, {}, sources)
            try:
                result = ex.run(fragment)
            except Exception:
                if rspan is not None:
                    tctx[0].finish(rspan, "error")
                raise
            if rspan is not None:
                tctx[0].finish(rspan)
            return result
        finally:
            # sweep final worker span + hier payloads into the merged
            # accounting BEFORE cancellation deletes task state
            self._collect_task_obs(all_tasks, tctx)
            # free worker-side output buffers (reference: task results are
            # acknowledged and deleted after consumption); on failure this
            # doubles as sibling-task cancellation
            self._cancel_tasks(all_tasks)

    def _collect_task_obs(self, tasks: List[Tuple[str, str, bool]],
                          tctx: Optional[tuple]) -> None:
        """Final merge sweep: pull task status once and fold its span
        payload into the query trace plus its hierarchical-exchange
        snapshot into the query rollup. Mid-tree producer stages are
        never status-polled on the happy path (their consumers are other
        workers), so without this sweep their spans AND their hier stats
        would be lost. With tracing off, only partitioned-output
        producers are polled (the sole carriers of hier stats) — the
        common untraced single-stage query pays zero extra round-trips.
        Tasks from failed POSTs 404 here — best effort by design."""
        trace = tctx[0] if tctx is not None else None
        if trace is None:
            tasks = [t for t in tasks if t[2]]
        if not tasks:
            return
        from ..obs.export import export_hier_stats
        from .hier import HierExchangeStats

        hier = HierExchangeStats()
        for uri, task_id, _partitioned in tasks:
            try:
                st = self._task_status(uri, task_id)
            except Exception:  # noqa: BLE001 — observability, best effort
                continue
            if trace is not None:
                trace.add_remote(st.get("spans") or ())
            hier.merge_snapshot(
                (st.get("exchangeStats") or {}).get("hier")
            )
        snap = hier.snapshot()
        if snap.get("exchanges") or snap.get("fallbacks"):
            with self._lock:
                self.stats.hier = snap
            export_hier_stats(hier, role="gather")

    def _cancel_tasks(self, tasks: List[Tuple[str, str, bool]]) -> None:
        for uri, task_id, _partitioned in tasks:
            try:
                req = urllib.request.Request(
                    f"{uri}/v1/task/{task_id}", method="DELETE"
                )
                urllib.request.urlopen(req, timeout=5).read()
            except Exception:  # noqa: BLE001 - cleanup is best-effort
                pass

    def _backoff(self, attempt: int) -> float:
        """Exponential backoff with full jitter (attempt counts from 0)."""
        ceiling = min(self.backoff_base * (2 ** attempt), self.backoff_cap)
        return random.uniform(0, ceiling)

    # -- plan cutting --

    def _cut(self, node: N.PlanNode):
        """Replace each Exchange child with a RemoteSource; returns
        (fragment, {source_id: Exchange})."""
        specs: Dict[str, Exchange] = {}

        def walk(n):
            import dataclasses as dc

            if isinstance(n, Exchange):
                sid = f"s{len(specs)}"
                specs[sid] = n
                return RemoteSource(sid, tuple(n.fields))
            replace = {}
            for f in dc.fields(n):
                v = getattr(n, f.name)
                if isinstance(v, N.PlanNode):
                    nv = walk(v)
                    if nv is not v:
                        replace[f.name] = nv
                elif isinstance(v, tuple) and v and isinstance(v[0], N.PlanNode):
                    nv = tuple(walk(c) for c in v)
                    if nv != v:
                        replace[f.name] = nv
            return dc.replace(n, **replace) if replace else n

        return walk(node), specs

    @staticmethod
    def _has_scan(node: N.PlanNode) -> bool:
        if isinstance(node, N.TableScan):
            return True
        return any(HttpScheduler._has_scan(c) for c in node.children)

    # -- cross-task dynamic filters (exec/dynfilter.py) --

    @staticmethod
    def _dyn_links(fragment: N.PlanNode, specs: Dict[str, Exchange]):
        """(produce, consume) stage links for dynamic filters crossing
        task boundaries. produce: source_id -> [(filter_id, channel)] for
        joins in `fragment` whose BUILD side is directly a RemoteSource —
        that producer stage's output IS the build rows, so its tasks can
        summarize the key channel. consume: source_id -> {filter_id} for
        producer subtrees containing annotated probe scans."""
        from ..expr import ir

        produce: Dict[str, list] = {}

        def walk(n):
            if isinstance(n, (N.Join, N.SemiJoin)) and getattr(
                n, "dynamic_filters", ()
            ):
                build = n.children[1]
                keys = (
                    n.right_keys
                    if isinstance(n, N.Join)
                    else n.source_keys
                )
                if isinstance(build, RemoteSource):
                    fields = {f for f, _ in build.fields}
                    for fid, i, _c in n.dynamic_filters:
                        k = keys[i]
                        if isinstance(k, ir.ColumnRef) and k.name in fields:
                            produce.setdefault(build.source_id, []).append(
                                (fid, k.name)
                            )
            for c in n.children:
                walk(c)

        walk(fragment)

        consume: Dict[str, set] = {}

        def scan_fids(n, acc: set):
            if isinstance(n, N.TableScan):
                for fid, *_rest in n.dynamic_filters:
                    acc.add(fid)
            for c in n.children:
                scan_fids(c, acc)

        for sid, ex in specs.items():
            acc: set = set()
            scan_fids(ex.child, acc)
            if acc:
                consume[sid] = acc
        return produce, consume

    def _await_dyn_filters(self, handles, entries, dyn_values: dict) -> None:
        """Bounded wait for a build stage's tasks to FINISH, then merge
        their per-task summaries into `dyn_values`. Expiry or a failed
        task drops the filter (proceed-without-filter) — dynamic filters
        are an optimization, never a correctness dependency."""
        from ..exec.dynfilter import merge_summaries

        deadline = time.time() + self.dynfilter_wait
        t0 = time.perf_counter()
        per_task: List[Optional[dict]] = []
        timed_out = False
        for uri, task in handles:
            status = None
            while time.time() < deadline:
                try:
                    status = self._task_status(uri, task)
                except TaskFailure:
                    status = None
                    break
                if status.get("state") in ("FINISHED", "FAILED"):
                    break
                time.sleep(0.05)
            else:
                timed_out = True
            if status is None or status.get("state") != "FINISHED":
                per_task.append(None)
            else:
                per_task.append(status.get("dynFilters") or {})
        with self._lock:
            self.stats.dynfilter_wait_s += time.perf_counter() - t0
            if timed_out:
                self.stats.dynfilter_timeouts += 1
        if any(p is None for p in per_task):
            return  # a task failed/timed out: filter untrusted
        for fid, _channel in entries:
            merged = merge_summaries([p.get(fid) for p in per_task])
            if merged is not None:
                dyn_values[fid] = merged
                with self._lock:
                    self.stats.dynfilters_shipped += 1

    # -- stage execution --

    def _resolve_sources(self, specs, sharded_consumer: bool,
                         workers: List[str], all_tasks,
                         query_id: Optional[str] = None,
                         dyn_links=None, dyn_values: Optional[dict] = None,
                         wire_caps: Optional[dict] = None,
                         tctx: Optional[tuple] = None,
                         adapt: bool = False):
        """Run producer stages for each exchange; returns either
        {sid: (kind, handles)} (sharded consumer) or {sid: [pages]}
        (coordinator consumer).

        Dynamic-filter link scheduling: a stage producing a filter some
        sibling stage's scans consume launches FIRST; the coordinator then
        waits (bounded) for its summaries and ships the merged filter in
        the later stages' task specs — the cross-task half of dynamic
        filtering (exec/dynfilter.py)."""
        produce, consume = dyn_links if dyn_links else ({}, {})
        if dyn_values is None:
            dyn_values = {}
        wanted: set = set()
        for fids in consume.values():
            wanted |= fids
        if self.dynfilter_wait <= 0:
            produce, consume, wanted = {}, {}, set()

        def is_producer(sid):
            return any(f in wanted for f, _ in produce.get(sid, ()))

        order = sorted(specs, key=lambda sid: (not is_producer(sid),))
        resolved = {}
        for sid in order:
            ex = specs[sid]
            entries = [
                (f, ch) for f, ch in produce.get(sid, ()) if f in wanted
            ]
            if ex.kind == "repartition" and sharded_consumer:
                handles = self._run_sharded_stage(
                    ex.child, ("hash", ex.keys), workers, all_tasks,
                    query_id, dyn_produce=entries, dyn_values=dyn_values,
                    wire_caps=wire_caps, tctx=tctx,
                )
                resolved[sid] = ("repartition", handles)
            else:
                # gather / replicate — and repartition consumed by the
                # coordinator itself, which reads everything anyway (hash
                # partitioning there would just drop partitions != 0).
                # Replicated outputs are pulled by EVERY consumer without
                # acks, so their producer buffers must be unbounded.
                handles = self._run_sharded_stage(
                    ex.child, ("single",), workers, all_tasks, query_id,
                    unbounded_output=(
                        sharded_consumer and ex.kind == "replicate"
                    ),
                    dyn_produce=entries, dyn_values=dyn_values,
                    wire_caps=wire_caps, tctx=tctx,
                )
                resolved[sid] = ("gather", handles)
            if entries and any(
                other != sid
                and (consume.get(other, set()) & {f for f, _ in entries})
                for other in specs
            ):
                self._await_dyn_filters(handles, entries, dyn_values)
        if sharded_consumer:
            return resolved
        # coordinator-side: materialize every source into Pages through
        # the PIPELINED exchange client — one puller per producer task,
        # multi-page responses, deserialization overlapped with in-flight
        # pulls (replaces the round-5 sequential one-thread drain)
        out = {}
        for sid, (kind, handles) in resolved.items():
            ex_stats = ExchangeStats()
            client = ExchangeClient(
                [(uri, task, 0) for uri, task in handles],
                ack=True,
                deadline=self.task_deadline,
                stats=ex_stats,
            )
            gspan = (
                tctx[0].begin(f"exchange {sid}", parent_id=tctx[1])
                if tctx else None
            )
            pages = []
            try:
                for page in client.pages():
                    pages.append(page)
            except ExchangeError as e:
                # attribute the mid-stream failure to its worker so
                # query-level retry can feed the blacklist. Pull stats
                # only — polling still-RUNNING producers' statuses here
                # would add ~0.5s of server-side wait per producer to
                # every retry attempt
                self._record_exchange(sid, ex_stats, ())
                if gspan is not None:
                    tctx[0].finish(gspan, "error", error=str(e)[:200])
                raise TaskFailure(
                    str(e), uri=e.uri, task_id=e.task_id,
                    retryable=_retryable_message(str(e)),
                ) from None
            self._record_exchange(sid, ex_stats, handles)
            if gspan is not None:
                snap = ex_stats.snapshot()
                tctx[0].finish(
                    gspan, pages=snap["pages"], bytes=snap["wire_bytes"],
                    wire_ms=snap["pull_ms"],
                    hidden_ms=snap["hidden_ms"],
                    overlap=snap["overlap_frac"],
                )
            if adapt:
                self._maybe_adaptive_replan(specs[sid], pages)
            out[sid] = pages
        return out

    def _maybe_adaptive_replan(self, ex, pages) -> None:
        """Mid-query adaptation (plan/history.py): the coordinator just
        materialized a producer stage, so its TRUE cardinality is known
        while the downstream fragments are still unexecuted. When the
        observation contradicts the estimate grossly enough
        (PRESTO_TPU_FEEDBACK_REPLAN_FACTOR) the observation is recorded
        and AdaptiveReplan raised; the session layer re-plans the
        downstream fragments against the now-updated history and
        re-runs through the same retry machinery worker failures use
        (it re-runs with adapt=False, so one replan per query)."""
        from ..plan import history as H
        from . import knobs

        try:
            if not H.feedback_on():
                return
            child = ex.child
            if _has_remote_source(child) or not self._has_scan(child):
                return  # nested-exchange estimates are not comparable
            observed = float(sum(int(p.count) for p in pages))
            if observed < knobs.feedback_replan_min_rows():
                return
            from ..plan.stats import derive

            est = float(derive(child, self.catalog).rows)
            if observed < knobs.feedback_replan_factor() * max(est, 1.0):
                return
            from ..exec.qcache import plan_tables

            recorded = H.HISTORY.record(
                H.fingerprint(child), catalog=self.catalog,
                tables=plan_tables(child), rows=observed, est_rows=est,
                kind=type(child).__name__,
            )
            if not recorded:
                return  # unversioned tables: a re-plan would not differ
        except Exception as exc:  # noqa: BLE001 — adaptation must never
            from ..exec.breaker import BREAKERS  # fail a healthy query

            BREAKERS.record_failure("adaptive_plan", repr(exc))
            return
        with H.HISTORY.stats._lock:
            H.HISTORY.stats.replans += 1
        with self._lock:
            self.stats.adaptive_replans += 1
        raise H.AdaptiveReplan(
            f"stage output {observed:,.0f} rows vs estimate {est:,.0f}: "
            "re-planning downstream fragments on observed cardinality"
        )

    def _record_exchange(self, sid: str, ex_stats: "ExchangeStats",
                         handles) -> None:
        """Fold one gather's pull stats + best-effort producer encode
        stats (task status exchangeStats — the producers are FINISHED
        here, so each poll answers immediately; still queryable until
        query cleanup) into the scheduler's observable accounting."""
        entry = ex_stats.snapshot()
        encode = WireStats()
        from .hier import HierExchangeStats

        hier = HierExchangeStats()
        mem_events: set = set()
        spilled = revocations = 0
        for uri, task in handles:
            try:
                st = self._task_status(uri, task)
            except Exception:  # noqa: BLE001 — observability, best effort
                continue
            ex = st.get("exchangeStats") or {}
            encode.merge_snapshot(ex)
            hier.merge_snapshot(ex.get("hier"))
            sp = st.get("spillStats") or {}
            spilled += int(sp.get("disk_bytes") or 0)
            mem_events.update(sp.get("events") or ())
            ms = st.get("memoryStats") or {}
            revocations += int(ms.get("revocations") or 0)
        entry["producer"] = encode.snapshot()
        hier_snap = hier.snapshot()
        if hier_snap.get("exchanges") or hier_snap.get("fallbacks"):
            entry["hier"] = hier_snap
        # unified metrics plane: one fold per gather (each ExchangeStats
        # and producer-encode accumulator lives for exactly one gather).
        # hier stats are NOT exported here — the final status sweep
        # (_collect_task_obs) covers every task exactly once, including
        # these gather producers
        from ..obs.export import export_exchange_stats, export_wire_stats

        export_exchange_stats(ex_stats)
        export_wire_stats("producer_encode", encode)
        with self._lock:
            self.stats.exchange[sid] = entry
            if spilled or revocations or mem_events:
                m = self.stats.memory
                m["spilled_bytes"] = (
                    int(m.get("spilled_bytes") or 0) + spilled
                )
                m["revocations"] = (
                    int(m.get("revocations") or 0) + revocations
                )
                m["events"] = sorted(
                    set(m.get("events") or ()) | mem_events
                )

    def _run_sharded_stage(self, node: N.PlanNode, output,
                           all_workers: List[str], all_tasks,
                           query_id: Optional[str] = None,
                           unbounded_output: bool = False,
                           dyn_produce=None,
                           dyn_values: Optional[dict] = None,
                           wire_caps: Optional[dict] = None,
                           tctx: Optional[tuple] = None) -> List[Tuple[str, str]]:
        """One task per worker for sharded stages (splits/repartition
        inputs); scan-less single-distribution stages run as ONE task so
        rows are never duplicated. Returns [(worker_uri, task_id)]."""
        nw = len(all_workers)
        fragment, specs = self._cut(node)
        sharded = self._has_scan(fragment) or any(
            ex.kind == "repartition" for ex in specs.values()
        )
        workers = all_workers if sharded else all_workers[:1]
        sspan = None
        if tctx is not None:
            sspan = tctx[0].begin(
                f"stage {output[0]}:{type(fragment).__name__}",
                parent_id=tctx[1], tasks=len(workers),
            )
            tctx = (tctx[0], sspan.span_id)
        child_resolved = self._resolve_sources(
            specs, True, all_workers, all_tasks, query_id,
            dyn_links=self._dyn_links(fragment, specs),
            dyn_values=dyn_values,
            wire_caps=wire_caps,
            tctx=tctx,
        )

        # row-range splits per scanned table
        tables = self._scan_tables(fragment)
        ranges = {}
        for t in tables:
            total = self.catalog.row_count(t)
            exact = getattr(self.catalog, "exact_row_count", None)
            if exact is not None:
                total = exact(t)
            per = -(-total // nw)
            ranges[t] = [
                (w * per, min((w + 1) * per, total)) for w in range(nw)
            ]

        frag_b64 = base64.b64encode(pickle.dumps(fragment)).decode()
        part_keys_b64 = None
        nparts = 1
        if output[0] == "hash":
            part_keys_b64 = base64.b64encode(pickle.dumps(output[1])).decode()
            nparts = nw

        launched = []  # (uri, task_id, spec) — spec kept for retries
        for w, uri in enumerate(workers):
            sources = {}
            for sid, (kind, child_handles) in child_resolved.items():
                if kind == "repartition":
                    # partition w has exactly ONE consumer: acks may free
                    # producer pages as this task consumes them
                    locs = [(u, t, w) for (u, t) in child_handles]
                    exclusive = True
                else:  # gather/replicate: every consumer pulls buffer 0
                    locs = [(u, t, 0) for (u, t) in child_handles]
                    exclusive = len(workers) == 1
                sources[sid] = {"locations": locs, "exclusive": exclusive}
            spec = {
                "fragment": frag_b64,
                "splits": {t: list(ranges[t][w]) for t in tables},
                "sources": sources,
                "partition_keys": part_keys_b64,
                "num_partitions": nparts,
                "query_id": query_id,
                "buffer_unbounded": unbounded_output,
                # cross-task dynamic filters: summaries this stage must
                # PRODUCE over its output, and resolved filter values its
                # scans may CONSUME (a snapshot — stages launched before a
                # build stage finished simply run unfiltered)
                "dyn_filter_produce": list(dyn_produce or ()) or None,
                "dyn_filters": dict(dyn_values) if dyn_values else None,
                # fleet-negotiated wire capabilities: this task's output
                # serializer must stay within them
                "wire": wire_caps,
            }
            launched.append(
                self._post_with_retry(uri, spec, all_workers, all_tasks,
                                      tctx=tctx)
            )
        # surface start failures eagerly, retrying each failed task onto
        # an alternate healthy worker (catalogs are deterministic across
        # nodes, so the same spec — splits, sources, partitioning — is
        # valid anywhere in the snapshot)
        handles = []
        for uri, task_id, spec, _post_attempts in launched:
            # fresh attempt budget: POST retries (connection-level) and
            # start-failure retries (task-level) are separate concerns
            handles.append(
                self._ensure_started(uri, task_id, spec, all_workers,
                                     all_tasks, tctx=tctx)
            )
        if sspan is not None:
            # the stage span covers launch (dispatch + start confirmation);
            # its children — per-attempt dispatch spans and the workers'
            # remote task spans — carry the execution wall
            tctx[0].finish(sspan)
        return handles

    # -- task start + retry --

    def _post_with_retry(self, uri: str, spec: dict,
                         snapshot: List[str], all_tasks,
                         tctx: Optional[tuple] = None):
        """POST a task, retrying a refused connection onto alternates.
        Returns (uri, task_id, spec, attempts_used)."""
        attempt = 1
        while True:
            task_id = f"t_{next(self._task_ids)}"
            failed = self._try_post(uri, task_id, spec, all_tasks,
                                    tctx=tctx)
            if failed is None:
                return uri, task_id, spec, attempt
            error = failed["error"]
            retryable = bool(failed["errorInfo"]["retryable"])
            self._note_task_failure(uri, error)
            if not retryable or attempt > self.max_task_retries:
                raise TaskFailure(
                    f"task {task_id} could not be started "
                    f"(last worker {uri}, attempt {attempt}, "
                    f"retryable={retryable}): {error}",
                    uri=uri, task_id=task_id, attempt=attempt,
                    retryable=retryable,
                )
            time.sleep(self._backoff(attempt - 1))
            uri = self._pick_alternate(uri, snapshot)
            attempt += 1
            with self._lock:
                self.stats.task_retries += 1

    def _try_post(self, uri: str, task_id: str, spec: dict,
                  all_tasks, tctx: Optional[tuple] = None) -> Optional[dict]:
        """POST a task; returns None on success, else a synthesized
        FAILED status dict (never raises for transport errors). The task
        id is registered for cleanup BEFORE posting: if the POST response
        is lost after the worker already accepted the task, query cleanup
        still deletes it (DELETE of an unknown task is a no-op).

        This is the single choke point every task POST goes through, so
        the per-ATTEMPT dispatch span lives here: each (re)post gets its
        own span under the stage, and the spec carries (trace_id, that
        span's id) so the worker parents its task span to this exact
        attempt — a retry is a sibling subtree, never an overwrite."""
        # partitioned-output producers are the only tasks the final
        # observability sweep must poll when tracing is off (their
        # exchangeStats["hier"] is unreachable any other way — their
        # consumers are other workers, not the coordinator)
        all_tasks.append((uri, task_id, bool(spec.get("partition_keys"))))
        dspan = None
        if tctx is not None:
            dspan = tctx[0].begin(
                f"dispatch {task_id}", parent_id=tctx[1], worker=uri,
            )
            spec["trace"] = {
                "trace_id": tctx[0].trace_id, "parent": dspan.span_id,
            }
        try:
            self._post_task(uri, task_id, spec)
            if dspan is not None:
                tctx[0].finish(dspan)
            return None
        except urllib.error.HTTPError as e:
            # the worker answered: honor its structured verdict
            detail, retryable = _http_error_details(e)
            if dspan is not None:
                tctx[0].finish(dspan, "error", error=detail[:200])
            return {
                "state": "FAILED",
                "error": detail,
                "errorInfo": {"retryable": retryable},
            }
        except (urllib.error.URLError, ConnectionError, OSError) as e:
            if dspan is not None:
                tctx[0].finish(dspan, "error", error=str(e)[:200])
            return {
                "state": "FAILED",
                "error": f"POST to {uri} refused: {e}",
                "errorInfo": {"retryable": True},
            }

    def _ensure_started(self, uri: str, task_id: str, spec: dict,
                        snapshot: List[str], all_tasks,
                        attempt: int = 1,
                        tctx: Optional[tuple] = None) -> Tuple[str, str]:
        """Eager failure surfacing with bounded retry: a task FAILED at
        the status check is re-posted (same spec) to an alternate worker
        after backoff + jitter; unrecoverable failures cancel the
        query's sibling tasks and raise."""
        status: Optional[dict] = None  # None = POST itself failed
        posted = True
        while True:
            if posted:
                try:
                    status = self._task_status(uri, task_id, attempt=attempt)
                except TaskFailure as tf:
                    status = {
                        "state": "FAILED",
                        "error": str(tf),
                        "errorInfo": {"retryable": tf.retryable},
                    }
            if tctx is not None:
                # merge whatever spans the worker reported — a FAILED
                # attempt's task span (status="error") lands in the tree
                # HERE, before its replacement is even posted
                tctx[0].add_remote(status.get("spans") or ())
            if status.get("state") != "FAILED":
                # started (RUNNING or FINISHED): reset the consecutive-
                # failure streak feeding the blacklist
                self.nodes.record_task_success(uri)
                return uri, task_id
            error = status.get("error") or "unknown"
            info = status.get("errorInfo") or {}
            retryable = bool(
                info.get("retryable", _retryable_message(error))
            )
            self._note_task_failure(uri, error)
            if not retryable or attempt > self.max_task_retries:
                self._cancel_tasks(list(all_tasks))
                raise TaskFailure(
                    f"task {task_id} on worker {uri} failed "
                    f"(attempt {attempt}/{self.max_task_retries + 1}, "
                    f"retryable={retryable}):\n{error}",
                    uri=uri, task_id=task_id, attempt=attempt,
                    retryable=retryable,
                )
            time.sleep(self._backoff(attempt - 1))
            uri = self._pick_alternate(uri, snapshot)
            task_id = f"t_{next(self._task_ids)}"
            failed = self._try_post(uri, task_id, spec, all_tasks,
                                    tctx=tctx)
            posted = failed is None
            if not posted:
                status = failed  # skip the status poll: classify directly
            attempt += 1
            with self._lock:
                self.stats.task_retries += 1

    def _pick_alternate(self, failed_uri: str, snapshot: List[str]) -> str:
        """Prefer a currently-active snapshot member that is not the
        failed worker; fall back to any snapshot member (single-worker
        clusters still get in-place retries)."""
        active = set(self.nodes.active_workers())
        candidates = [
            u for u in snapshot if u != failed_uri and u in active
        ] or [u for u in snapshot if u != failed_uri] or [failed_uri]
        return random.choice(candidates)

    def _note_task_failure(self, uri: str, error: str) -> None:
        with self._lock:
            self.stats.tasks_failed += 1
            self.stats.worker_failures[uri] = (
                self.stats.worker_failures.get(uri, 0) + 1
            )
            self.stats.last_error = error[:300]
        self.nodes.record_task_failure(uri, error)

    @staticmethod
    def _scan_tables(node: N.PlanNode) -> List[str]:
        out = []

        def walk(n):
            if isinstance(n, N.TableScan):
                out.append(n.table)
            for c in n.children:
                walk(c)

        walk(node)
        return sorted(set(out))

    # -- HTTP --

    @staticmethod
    def _post_task(uri: str, task_id: str, spec: dict):
        body = json.dumps(spec).encode()
        req = urllib.request.Request(
            f"{uri}/v1/task/{task_id}", data=body, method="POST"
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    def _task_status(self, uri: str, task_id: str,
                     attempt: int = 1) -> dict:
        """Short-poll the task status endpoint under a configurable
        deadline (replaces the raw 300 s blocking urlopen): the worker
        answers within ~0.5 s, so looping only happens across transient
        network errors; exhausting the deadline raises a TaskFailure
        naming the worker, task, and attempt."""
        deadline = time.time() + self.status_deadline
        last = None
        while True:
            try:
                with urllib.request.urlopen(
                    f"{uri}/v1/task/{task_id}", timeout=self.status_timeout
                ) as resp:
                    return json.loads(resp.read())
            except urllib.error.HTTPError as e:
                # the worker answered with an error status (404 unknown
                # task after a restart, 500 handler bug): definitive —
                # not worth polling out the deadline
                try:
                    detail = json.loads(e.read()).get("error") or str(e)
                except Exception:  # noqa: BLE001 — body parse is
                    # best-effort detail; the TaskFailure below still
                    # carries the HTTP error either way
                    detail = str(e)
                raise TaskFailure(
                    f"status of task {task_id} on worker {uri} "
                    f"(attempt {attempt}): HTTP {e.code}: {detail}",
                    uri=uri, task_id=task_id, attempt=attempt,
                ) from None
            except Exception as e:  # noqa: BLE001 - poll again until deadline
                last = e
            if time.time() >= deadline:
                raise TaskFailure(
                    f"status poll for task {task_id} on worker {uri} "
                    f"(attempt {attempt}) exceeded "
                    f"{self.status_deadline:.0f}s deadline: {last}",
                    uri=uri, task_id=task_id, attempt=attempt,
                ) from None
            time.sleep(0.1)


class ClusterMemoryManager:
    """Coordinator-side cluster memory management (reference
    memory/ClusterMemoryManager.java:89,210 + LowMemoryKiller.java:26):
    polls every worker's /v1/memory, aggregates per-query reservation
    across the cluster, and when any worker is memory-blocked kills the
    query with the LARGEST total reservation (the TotalReservation
    strategy) by aborting its tasks on every worker."""

    def __init__(self, nodes: NodeManager, interval: float = 0.25,
                 on_kill=None, grace_polls: int = 4,
                 revoke_watermark: Optional[float] = None):
        self.nodes = nodes
        self.interval = interval
        self.on_kill = on_kill
        self.grace_polls = grace_polls  # sustained blockage before a kill
        self.revoke_watermark = (
            knobs.revoke_watermark()
            if revoke_watermark is None else revoke_watermark
        )
        self._blocked_streak = 0
        self.killed: List[str] = []
        # memory-manager blindness observability: per-worker poll
        # failures are counted and surfaced, never silently skipped
        self.poll_failures: Dict[str, int] = {}
        self._unpollable: set = set()
        self.loop_errors = 0
        self.last_loop_error = ""
        self.last_snapshot: Dict[str, dict] = {}
        self._pressure = False
        # PER-WORKER last-seen revocation counters: a flapping worker's
        # counter dropping out of (and back into) a summed total would
        # oscillate the progress signal and indefinitely defer the killer
        self._last_rev_by_worker: Dict[str, int] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self) -> "ClusterMemoryManager":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.poll_once()
            except Exception as exc:  # noqa: BLE001 - keep polling, but
                # COUNT the blindness instead of swallowing it bare
                self.loop_errors += 1
                self.last_loop_error = repr(exc)[:300]

    def above_watermark(self) -> bool:
        """Is any worker above the revocation watermark (or blocked)?
        Resource-group admission refuses to start new queries while True
        (server/resource_groups.py cluster_pressure)."""
        return self._pressure

    def _note_poll_failure(self, uri: str, exc: Exception) -> None:
        self.poll_failures[uri] = self.poll_failures.get(uri, 0) + 1
        if uri not in self._unpollable:
            self._unpollable.add(uri)
            bus = getattr(self.nodes, "event_bus", None)
            if bus is not None:
                # memory-manager blindness is an observable worker event,
                # not an invisible `continue`
                bus.fire_worker_state(
                    uri, "MEMORY_UNPOLLABLE",
                    f"/v1/memory poll failed: {exc!r}"[:200],
                )

    def poll_once(self) -> Optional[str]:
        """One manager cycle; returns the killed query id, if any."""
        states = []
        snapshot: Dict[str, dict] = {}
        for uri in self.nodes.active_workers():
            try:
                with urllib.request.urlopen(
                    f"{uri}/v1/memory", timeout=5
                ) as resp:
                    states.append((uri, json.loads(resp.read())))
            except Exception as exc:  # noqa: BLE001 - count + surface;
                # liveness demotion stays the failure detector's job
                self._note_poll_failure(uri, exc)
                snapshot[uri] = {
                    "unreachable": True,
                    "poll_failures": self.poll_failures[uri],
                }
                continue
            if uri in self._unpollable:
                self._unpollable.discard(uri)
                bus = getattr(self.nodes, "event_bus", None)
                if bus is not None:
                    bus.fire_worker_state(
                        uri, "MEMORY_POLLABLE", "memory polls recovered"
                    )
        # live gauge snapshot for system.jmx.memory
        progress = False
        pressure = False
        for uri, st in states:
            reserved = int(st.get("reserved") or 0)
            limit = st.get("limit") or 0
            rev = st.get("revocations") or {}
            completed = int(rev.get("completed") or 0)
            # progress is judged PER WORKER against its own last-seen
            # counter (only updated when the worker answers), so an
            # unpollable worker neither fakes nor hides progress
            if completed > self._last_rev_by_worker.get(uri, completed):
                progress = True
            self._last_rev_by_worker[uri] = completed
            if st.get("blocked") or (
                limit and reserved >= self.revoke_watermark * limit
            ):
                pressure = True
            snapshot[uri] = {
                "reserved": reserved,
                "limit": limit,
                "blocked": len(st.get("blocked") or ()),
                "exec_reserved": int(st.get("exec_reserved") or 0),
                "revocations": rev,
                "over_frees": int(st.get("over_frees") or 0),
                "spilled_bytes": int(
                    (st.get("spill") or {}).get("total_written") or 0
                ),
                "poll_failures": self.poll_failures.get(uri, 0),
            }
        self.last_snapshot = snapshot
        self._pressure = pressure
        blocked = any(st.get("blocked") for _, st in states)
        if not blocked:
            self._blocked_streak = 0
            return None
        # revoke-before-kill: while executors keep completing revocations
        # (freeing state into the spill tier), the blockage is being
        # WORKED ON — the killer only fires after revocation fails to
        # free enough for `grace_polls` consecutive polls
        if progress:
            self._blocked_streak = 0
            return None
        # transient blocking is normal flow control (acks free bytes
        # continuously); only SUSTAINED exhaustion triggers the killer
        self._blocked_streak += 1
        if self._blocked_streak < self.grace_polls:
            return None
        self._blocked_streak = 0
        victim = self.choose_victim(states)
        if victim is None:
            return None
        self.kill(victim)
        return victim

    @staticmethod
    def choose_victim(states) -> Optional[str]:
        """TotalReservation: the query holding the most bytes cluster-wide
        (blocked-but-unreserved queries are victims of last resort)."""
        totals: Dict[str, int] = {}
        for _uri, st in states:
            for qid, nbytes in (st.get("queries") or {}).items():
                totals[qid] = totals.get(qid, 0) + int(nbytes)
            for qid in st.get("blocked") or ():
                totals.setdefault(qid, 0)
        if not totals:
            return None
        return max(totals, key=lambda q: (totals[q], q))

    def kill(self, query_id: str) -> None:
        # kill on EVERY known worker — a blacklisted (drained) worker
        # can still hold tasks of the victim query
        for uri in self.nodes.all_workers():
            try:
                req = urllib.request.Request(
                    f"{uri}/v1/query/{query_id}", method="DELETE"
                )
                urllib.request.urlopen(req, timeout=5).read()
            except Exception:  # noqa: BLE001 - best effort per worker
                pass
        self.killed.append(query_id)
        if self.on_kill is not None:
            self.on_kill(query_id)


class HttpClusterSession:
    """Session facade executing SQL over an HTTP worker cluster — the
    DistributedQueryRunner analog for the DCN path."""

    def __init__(self, catalog, nodes: NodeManager,
                 broadcast_threshold=None,  # None = cost-based
                 memory_manager: bool = False,
                 scheduler_opts: Optional[dict] = None):
        from ..session import Session

        self._planner = Session(catalog)  # reuse parse/plan/fragment
        self._planner.mesh = None
        self.catalog = catalog
        self.broadcast_threshold = broadcast_threshold
        self.scheduler = HttpScheduler(
            catalog, nodes, **(scheduler_opts or {})
        )
        self._query_ids = itertools.count(1)
        self.memory_manager = (
            ClusterMemoryManager(nodes).start() if memory_manager else None
        )

    def _run_fragmented(self, sql: str, use_result_cache: bool = True):
        """The one plan -> fragment -> schedule pipeline both query()
        and explain_analyze() go through; returns (fragmented node,
        result page, trace_or_None, phase_ms). Both serving caches
        (exec/qcache.py) sit in front of the scheduler: the fragmented
        plan is cached per (sql, worker count, broadcast config) and
        validated against connector snapshot versions, and a
        snapshot-identical repeat serves its page without touching the
        fleet at all. Worker-count changes (blacklist, re-admission)
        change the plan key, so failover replans instead of reusing a
        stale fragmentation.

        Tracing (docs/observability.md): the coordinator opens the query
        root + plan/execute phase spans; the scheduler hangs per-attempt
        / per-stage / per-dispatch spans under the execute span and
        merges the workers' remote spans into the same tree."""
        from ..exec import qcache
        from ..obs import span as obs_span
        from ..obs.export import export_query
        from ..plan.fragment import fragment_plan

        # under a served statement (server/state.py) the tree is the one
        # its `statement` span roots, and the manager exports it
        served = obs_span.current()
        if served is not None:
            trace = served[0]
        else:
            trace = obs_span.TRACES.new_trace() if obs_span.enabled() else None
        root = (
            trace.begin(
                "query", parent=served[1] if served else None, sql=sql[:200]
            )
            if trace is not None else None
        )
        status = "ok"
        phase_ms: dict = {}
        try:
            pspan = (
                trace.begin("plan", parent=root)
                if trace is not None else None
            )
            from ..plan import history as H

            n_workers = max(len(self.scheduler.nodes.active_workers()), 2)

            def plan_fresh():
                # pkey carries the feedback generation: a history record
                # or invalidation must re-plan, never reuse a fragmented
                # plan built on superseded observations
                key = ("c", sql, self.broadcast_threshold, n_workers,
                       id(self.catalog), H.plan_env_token())
                ent = qcache.PLAN_CACHE.lookup(key, self.catalog)
                if ent is not None:
                    return ent.plan
                planned = self._planner.plan(sql)
                planned = fragment_plan(planned, self.catalog,
                                        self.broadcast_threshold,
                                        num_workers=n_workers)
                qcache.PLAN_CACHE.store(key, planned, self.catalog)
                return planned

            node = plan_fresh()
            if trace is not None:
                trace.finish(pspan)
                phase_ms["plan"] = round(pspan.wall_s * 1e3, 3)
            rkey = ("cr", sql, self.broadcast_threshold, n_workers,
                    id(self.catalog))
            pre = None
            if use_result_cache:
                hit = qcache.RESULT_CACHE.lookup(rkey, self.catalog)
                if hit is not None:
                    self.scheduler.record_caches(qcache.snapshot_all())
                    return node, hit.page, trace, phase_ms
                pre = qcache.RESULT_CACHE.preversions(node, self.catalog)
            espan = (
                trace.begin("execute", parent=root)
                if trace is not None else None
            )
            try:
                try:
                    page = self.scheduler.run(
                        node, query_id=f"q_{next(self._query_ids)}",
                        trace_ctx=(
                            (trace, espan.span_id) if trace is not None
                            else None
                        ),
                    )
                except H.AdaptiveReplan:
                    # mid-query adaptation: the scheduler recorded the
                    # contradicting observation before raising, so a
                    # fresh plan (new generation -> new pkey) reorders /
                    # re-distributes downstream fragments on measured
                    # rows. The re-run has adaptation off: one replan
                    # per query, and a second misprediction just runs.
                    node = plan_fresh()
                    page = self.scheduler.run(
                        node, query_id=f"q_{next(self._query_ids)}",
                        trace_ctx=(
                            (trace, espan.span_id) if trace is not None
                            else None
                        ),
                        adapt=False,
                    )
                    from ..exec.breaker import BREAKERS

                    BREAKERS.record_success("adaptive_plan")
            except Exception:
                if trace is not None:
                    trace.finish(espan, "error")
                raise
            if trace is not None:
                trace.finish(espan, rows=int(page.count))
                phase_ms["execute"] = round(espan.wall_s * 1e3, 3)
            if pre is not None and qcache.plan_is_deterministic(node):
                qcache.RESULT_CACHE.store(
                    rkey, page, getattr(node, "titles", ()), self.catalog,
                    pre,
                )
            self.scheduler.record_caches(qcache.snapshot_all())
            return node, page, trace, phase_ms
        except Exception:
            status = "error"
            raise
        finally:
            if trace is not None:
                trace.finish(root, status)
                if served is None:
                    export_query(status, root.wall_s, phase_ms)

    def query(self, sql: str):
        from ..session import QueryResult

        node, page, trace, phase_ms = self._run_fragmented(sql)
        res = QueryResult(page, node.titles)
        if trace is not None:
            res.trace_id = trace.trace_id
            res.phase_ms = phase_ms
        return res

    def explain_analyze(self, sql: str) -> str:
        """Run the query over the cluster and render the fragmented plan
        with per-exchange WIRE stats: pages, wire vs raw bytes and the
        compression ratio, encode/decode wall, and pull concurrency —
        the distributed half of EXPLAIN ANALYZE (the single-process half
        lives in Session.explain_analyze_plan)."""
        # bypass the result cache: EXPLAIN ANALYZE must actually execute
        # to have wire/memory stats worth reporting
        node, _page, trace, _phase_ms = self._run_fragmented(
            sql, use_result_cache=False
        )
        tree = N.plan_tree_str(node)
        lines = [tree]
        st = self.scheduler.stats_snapshot()
        if st["wire_caps"]:
            lines.append(
                "-- wire: v%s, codecs %s"
                % (st["wire_caps"].get("version"),
                   "/".join(st["wire_caps"].get("codecs") or ()))
            )
        for sid, ex in sorted(st["exchange"].items()):
            prod = ex.get("producer") or {}
            ratio = prod.get("compression_ratio")
            lines.append(
                f"-- exchange {sid}: {ex['pages']} pages from "
                f"{ex['sources']} producers, wire "
                f"{ex['wire_bytes']:,}B"
                + (
                    f" (raw {prod['raw_bytes']:,}B, {ratio}x)"
                    if prod.get("raw_bytes") and ratio
                    else ""
                )
                + f", encode {prod.get('encode_ms', 0)}ms, decode "
                f"{ex['decode_ms']}ms, pull peak {ex['peak_concurrent']} "
                f"concurrent"
            )
            if ex.get("pull_ms") is not None:
                # overlap proof: wire wall vs what the consumer actually
                # waited for — the difference was hidden behind compute
                lines.append(
                    f"-- exchange {sid} overlap: wire "
                    f"{ex['pull_ms']}ms, consumer wait "
                    f"{ex.get('consumer_wait_ms', 0)}ms, hidden "
                    f"{ex.get('hidden_ms', 0)}ms "
                    f"({round(100 * ex.get('overlap_frac', 0.0))}%)"
                )
            hier = ex.get("hier")
            if hier:
                lines.append(
                    f"-- exchange {sid} hier: "
                    f"{hier['collective_exchanges']}/{hier['exchanges']} "
                    f"collective, device {hier['collective_ms']}ms, "
                    f"{hier['wire_pages']} ragged pages, pad "
                    f"{hier['ragged_pad_rows']} rows (fixed would be "
                    f"{hier['fixed_pad_rows']}), "
                    f"fallbacks {hier['fallbacks']}"
                )
        if st.get("hier"):
            # query-wide rollup from the final task sweep: mid-tree
            # repartition producers' hierarchical regroup accounting
            h = st["hier"]
            lines.append(
                f"-- hier: {h['collective_exchanges']}/{h['exchanges']} "
                f"batches collective, device {h['collective_ms']}ms, "
                f"{h['wire_pages']} ragged pages, pad "
                f"{h['ragged_pad_rows']} rows (fixed would be "
                f"{h['fixed_pad_rows']}), fallbacks {h['fallbacks']}"
            )
        if st["memory"]:
            m = st["memory"]
            lines.append(
                "-- memory: spill "
                + ",".join(m.get("events") or ("none",))
                + f", disk {m.get('spilled_bytes', 0):,}B, "
                f"revocations {m.get('revocations', 0)}"
            )
        if st["caches"]:
            from ..exec import qcache

            lines.append("-- caches: " + qcache.format_summary(st["caches"]))
        if trace is not None:
            # same renderer as Session.explain_analyze_plan — one source
            # of truth for the single-process and cluster critical path
            from ..obs.span import render_critical_path

            lines.append(
                "-- trace: "
                + render_critical_path(trace, knobs.trace_topk())
            )
        return "\n".join(lines)

    def close(self):
        if self.memory_manager is not None:
            self.memory_manager.stop()
