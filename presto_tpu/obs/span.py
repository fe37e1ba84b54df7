"""Span trees: one query end-to-end across the fleet.

Re-designed equivalent of the reference's query-wide stats tree
(QueryStats → StageStats → TaskStats → OperatorStats assembled by the
coordinator from task status updates) expressed as a trace: a query
gets a `trace_id`; the coordinator opens phase spans (plan / execute),
per-stage and per-dispatch spans; the trace context (trace_id + parent
span_id) rides the HTTP task spec; workers record their own task spans
against that parent and return them in the task-status payload; the
coordinator merges the fleet's spans into ONE tree.

Retry semantics: every dispatch attempt gets its OWN span under the
same parent — a retried task appears as sibling spans (the failed
attempt with status="error", the retry with status="ok"), never an
overwrite. Merging is idempotent by span_id, last write wins, so a
status polled mid-flight (end=None) is upgraded by the final poll.

Timebase is time.time() so coordinator and worker spans align on the
wall clock; durations of remote spans are computed remotely, so clock
skew shifts placement, not length.

The served single-process path (docs/observability.md) keeps, per
thread, the innermost open span: `Trace.enter` / `Trace.leave` open and
close a span under it, hold a `jax.profiler.TraceAnnotation` named
`presto.<span name>` for as long (so a profiler session has the same
tree on the device trace's clock), and are where the counters live:
`host_read` and the compile listener add to the thread's innermost
span, `leave` folds a span's counters into its parent, so every span
carries its subtree's totals.
`begin` / `finish` stay the thread-free primitives the cluster path and
the cross-thread `statement` / `queued` spans use.

A span's two clock readings say when the host finished ASKING. When what
it asked for was DONE is a ready stamp (`sent`): the thread that has
just enqueued work hands the arrays it will write to a watcher thread,
which waits for them and writes the time onto the span, so the served
thread never blocks for a stamp. `Trace.device_spans` turns the stamps
into each operator's stretch of the device's queue; the streamed scan's
batches (queue `link`) leave the link's busy and idle time on the
`TableScan` span.
"""

from __future__ import annotations

import contextlib
import itertools
import queue as queue_mod
import threading
import time
import uuid
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation


# ids: this process's random prefix and a counter. Unique across the
# fleet like the uuid4 slices they replace (workers' spans merge into the
# coordinator's tree by id), without a read of the kernel's random pool
# for every span of every statement.
_ID_PREFIX = uuid.uuid4().hex[:8]
_ids = itertools.count(1)


def _new_id() -> str:
    return f"{_ID_PREFIX}{next(_ids):08x}"


class Span:
    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "start", "end",
        "status", "attrs", "counters", "_outer", "_annotation",
    )

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], start: float):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = start
        self.end: Optional[float] = None
        self.status = "ok"
        self.attrs: Dict[str, object] = {}
        # own bookings plus the folded totals of the children that have
        # closed; `finish` copies them into `attrs`
        self.counters: Dict[str, float] = {}
        # enter(): the (Trace, Span) that was the thread's innermost
        self._outer: Optional[tuple] = None
        self._annotation = None

    @property
    def wall_s(self) -> float:
        if self.end is None:
            return 0.0
        return max(0.0, self.end - self.start)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "end": self.end,
            "status": self.status,
            "attrs": dict(self.attrs),
        }


class Trace:
    """One query's span tree. All span mutation happens through the
    trace's lock (begin/finish/add_remote), so status-poll merges from
    puller threads and the coordinator's own phase spans never race."""

    def __init__(self, trace_id: Optional[str] = None,
                 query_id: Optional[str] = None):
        self.trace_id = trace_id or _new_id()
        # the served statement's id (server/state.py); TraceStore finds a
        # trace by it and the profiler annotations carry it
        self.query_id = query_id
        self._mark = {} if query_id is None else {"query_id": query_id}
        self._lock = threading.Lock()
        self._spans: "OrderedDict[str, Span]" = OrderedDict()

    # -- recording --

    def begin(self, name: str, parent: Optional[Span] = None,
              parent_id: Optional[str] = None,
              start: Optional[float] = None, **attrs) -> Span:
        span = Span(
            name, self.trace_id, _new_id(),
            parent.span_id if parent is not None else parent_id,
            time.time() if start is None else start,
        )
        if attrs:
            span.attrs.update(attrs)
        with self._lock:
            self._spans[span.span_id] = span
        return span

    def finish(self, span: Span, status: str = "ok", **attrs) -> Span:
        with self._lock:
            if span.end is None:
                span.end = time.time()
            span.status = status
            if span.counters:
                span.attrs.update(span.counters)
            if attrs:
                span.attrs.update(attrs)
        return span

    def enter(self, name: str, **attrs) -> Span:
        """`begin` under the calling thread's innermost open span, which
        the new span then is until `leave`, on the SAME thread, gives
        the place back. Held inside a profiler annotation
        `presto.<name>`: no cost beyond a flag test while no profiler
        session runs."""
        outer = getattr(_OPEN, "cur", None)
        parent = outer[1] if outer is not None and outer[0] is self else None
        span = self.begin(name, parent=parent, **attrs)
        span._outer = outer
        span._annotation = TraceAnnotation("presto." + name, **self._mark)
        span._annotation.__enter__()
        _OPEN.cur = (self, span)
        return span

    def leave(self, span: Span, status: str = "ok", **attrs) -> Span:
        """Close a span `enter` opened and fold its counters into the
        span it was opened under."""
        span._annotation.__exit__(None, None, None)
        span._annotation = None
        self.finish(span, status, **attrs)
        outer, span._outer = span._outer, None
        _OPEN.cur = outer
        if span.counters and outer is not None and outer[0] is self:
            with self._lock:
                into = outer[1].counters
                for k, v in span.counters.items():
                    into[k] = into.get(k, 0) + v
        return span

    def add_remote(self, span_dicts: Iterable[dict]) -> int:
        """Merge spans shipped from a worker (task-status payload).
        Idempotent by span_id — re-polling a task upgrades the entry in
        place instead of duplicating it. Returns spans merged."""
        n = 0
        with self._lock:
            for d in span_dicts or ():
                try:
                    sid = d["span_id"]
                    span = Span(
                        str(d.get("name", "?")), self.trace_id, sid,
                        d.get("parent_id"), float(d.get("start", 0.0)),
                    )
                    end = d.get("end")
                    span.end = float(end) if end is not None else None
                    span.status = str(d.get("status", "ok"))
                    span.attrs = dict(d.get("attrs") or {})
                except (KeyError, TypeError, ValueError):
                    continue
                self._spans[sid] = span
                n += 1
        return n

    # -- reading --

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans.values())

    def to_dicts(self) -> List[dict]:
        return [s.to_dict() for s in self.spans()]

    def root(self) -> Optional[Span]:
        for s in self.spans():
            if s.parent_id is None:
                return s
        return None

    def children(self, span_id: Optional[str]) -> List[Span]:
        return [s for s in self.spans() if s.parent_id == span_id]

    def orphans(self) -> List[Span]:
        """Spans whose parent never arrived — a merge bug or a lost
        status payload; the fault-tolerance test asserts none."""
        with self._lock:
            ids = set(self._spans)
            return [
                s for s in self._spans.values()
                if s.parent_id is not None and s.parent_id not in ids
            ]

    def exclusive_walls(self) -> List[Tuple[Span, float]]:
        """(span, wall minus children's wall) — the time a span spent
        NOT delegated further down the tree, the critical-path unit."""
        return self._exclusive(lambda s: s.wall_s)

    def exclusive(self, counter: str) -> List[Tuple[Span, float]]:
        """(span, its own bookings of `counter`): a closed span's
        `attrs[counter]` is its subtree's total, as its wall is."""
        return self._exclusive(lambda s: s.attrs.get(counter, 0))

    def _exclusive(self, value) -> List[Tuple[Span, float]]:
        spans = self.spans()
        child_sum: Dict[str, float] = {}
        for s in spans:
            if s.parent_id is not None:
                child_sum[s.parent_id] = (
                    child_sum.get(s.parent_id, 0) + value(s)
                )
        return [
            (s, max(0, value(s) - child_sum.get(s.span_id, 0)))
            for s in spans
        ]

    def device_spans(self) -> List[Tuple[Span, float]]:
        """(span, seconds) for every span that carries a ready stamp of
        the `device` queue (`sent`; `Executor._run` hands over each
        node's output): its DEVICE-SIDE span, `ready_at` minus the later
        of `ready_after` (when the entry before it on the queue was
        ready: the device was not this node's before that) and the
        moment the node's own work began (its last child's leave, or its
        start). A node that works BETWEEN its children (a join publishes
        its dynamic filter after the build side) also gets the stretch
        from the one child's `ready_at` to the next child's first start.
        That is the part of the device's queue that belongs to the node:
        its programs AND the gaps between them (the host's reads and
        dispatch inside the node), so it is the node's device time only
        where the device is busy throughout. A stamp is never earlier
        than its hand-over, so a node's host work after its last program
        counts too. The stretches of one statement do not overlap."""
        spans = self.spans()
        children_left: Dict[str, float] = {}
        for s in spans:
            if s.parent_id is not None and s.end is not None:
                children_left[s.parent_id] = max(
                    children_left.get(s.parent_id, 0.0), s.end
                )
        stamped = sorted(
            (
                s for s in spans
                if s.attrs.get("ready_queue") == "device"
                and "ready_at" in s.attrs
            ),
            key=lambda s: (s.attrs["ready_at"], s.attrs["handed_at"]),
        )  # the order handed over
        own = {s.span_id: 0.0 for s in stamped}
        before = None
        for s in stamped:
            after = s.attrs.get("ready_after", 0.0)
            began = max(s.start, children_left.get(s.span_id, 0.0), after)
            own[s.span_id] += max(0.0, s.attrs["ready_at"] - began)
            if before is not None and before.parent_id in own:
                # the host came here from `before`, a child of a node
                # above: what lies between is that node's own work
                own[before.parent_id] += max(
                    0.0, s.start - max(after, before.end or after)
                )
            before = s
        return [(s, own[s.span_id]) for s in stamped]

    def critical_path(self, topk: int = 5) -> List[Tuple[Span, float]]:
        ranked = sorted(
            self.exclusive_walls(), key=lambda p: p[1], reverse=True
        )
        return ranked[:max(1, topk)]


def render_critical_path(trace: Trace, topk: int = 5) -> str:
    """The `-- trace:` EXPLAIN ANALYZE footer — ONE renderer for the
    single-process and cluster paths (acceptance: one source of truth)."""
    root = trace.root()
    total = root.wall_s if root is not None else 0.0
    parts = []
    for span, excl in trace.critical_path(topk):
        pct = f" ({excl / total * 100:.0f}%)" if total > 0 else ""
        flag = "!" if span.status != "ok" else ""
        parts.append(f"{flag}{span.name} {excl * 1e3:.1f}ms{pct}")
    head = f"trace {trace.trace_id} wall {total * 1e3:.1f}ms"
    if not parts:
        return head
    return head + "; top exclusive: " + ", ".join(parts)


class TraceStore:
    """Bounded keep-last-N registry of traces for system.runtime.tasks
    and coordinator-side merging. Workers do NOT register their
    per-task traces here — theirs travel in the status payload so the
    merge path is the same in-process and across real processes."""

    def __init__(self):
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, Trace]" = OrderedDict()

    def _keep(self) -> int:
        from ..server import knobs

        return knobs.trace_keep()

    def new_trace(self, query_id: Optional[str] = None) -> Trace:
        _listen_for_compiles()
        trace = Trace(query_id=query_id)
        keep = self._keep()
        with self._lock:
            self._traces[trace.trace_id] = trace
            while len(self._traces) > max(1, keep):
                self._traces.popitem(last=False)
        return trace

    def get(self, trace_id: str) -> Optional[Trace]:
        with self._lock:
            return self._traces.get(trace_id)

    def by_query_id(self, query_id: str) -> Optional[Trace]:
        """The newest kept trace of a served statement (query ids are
        one manager's: `q_7` of a second server in the process is
        another statement)."""
        with self._lock:
            for trace in reversed(self._traces.values()):
                if trace.query_id == query_id:
                    return trace
        return None

    def recent(self) -> List[Trace]:
        with self._lock:
            return list(self._traces.values())

    def reset(self) -> None:
        with self._lock:
            self._traces.clear()


def enabled() -> bool:
    from ..server import knobs

    return knobs.trace_enabled()


# -- the calling thread's innermost open span, and what is booked on it --

_OPEN = threading.local()  # .cur = (Trace, Span) while a span is open here


def current() -> Optional[Tuple[Trace, Span]]:
    """(trace, innermost open span) of the calling thread; None while it
    has none, which is always the case under PRESTO_TPU_TRACE=0."""
    return getattr(_OPEN, "cur", None)


def adopt(trace: Trace, span: Span) -> None:
    """Make `span`, opened on another thread (or by `begin`), the calling
    thread's innermost: what `enter`s next on this thread is its child."""
    _OPEN.cur = (trace, span)


def release() -> None:
    """Undo `adopt`: the calling thread has no open span again."""
    _OPEN.cur = None


def host_read(x):
    """`np.asarray(x)`, the one call through which the served path reads
    a device value on the host (`int(host_read(page.count))`). For a
    device array whose value the host does not hold yet, the innermost
    open span gets `host_reads` += 1 and `host_read_wait_s` += the time
    the host stood here: waiting for the programs the value depends on,
    then for the copy. An array keeps the copy an accelerator made, so a
    second read of the same array object waits for nothing and is not
    counted (the CPU backend reads in place, keeps none, and counts)."""
    cur = getattr(_OPEN, "cur", None)
    if (
        cur is None
        or not isinstance(x, jax.Array)
        or getattr(x, "_npy_value", None) is not None
    ):
        return np.asarray(x)
    t0 = time.perf_counter()
    out = np.asarray(x)
    waited = time.perf_counter() - t0
    counters = cur[1].counters
    counters["host_reads"] = counters.get("host_reads", 0) + 1
    counters["host_read_wait_s"] = (
        counters.get("host_read_wait_s", 0.0) + waited
    )
    return out


def held(x):
    """The host's copy of `x` where it holds one already, else None:
    never a wait and never a counted read. A Python or numpy value is
    held; a device array is once `host_read` (or anything else) has
    fetched it, which on an accelerator leaves the copy on the array
    (the CPU backend keeps none, so there this is None until the value
    is a plain one)."""
    if isinstance(x, jax.Array):
        return getattr(x, "_npy_value", None)
    return x


@contextlib.contextmanager
def child(name: str, wall_as: Optional[str] = None, **attrs):
    """A span `name` under the calling thread's innermost open one, for
    code below the executor (a connector) that has no trace in hand;
    where the thread has no open span the body just runs. `wall_as`
    names a counter that gets the body's seconds, folded upward as
    `host_read_wait_s` is: `host_read`'s counterpart for what the host
    MAKES or SENDS. A body that sends ends with `jax.block_until_ready`
    on what it sent, so the transfer's wait is booked here, once, and
    not in whichever read comes next."""
    cur = getattr(_OPEN, "cur", None)
    if cur is None:
        yield
        return
    span = cur[0].enter(name, **attrs)
    t0 = time.perf_counter()
    status = "ok"
    try:
        yield
    except BaseException:
        status = "error"
        raise
    finally:
        if wall_as is not None:
            span.counters[wall_as] = (
                span.counters.get(wall_as, 0.0) + time.perf_counter() - t0
            )
        cur[0].leave(span, status)


def count(**amounts) -> None:
    """Add to counters of the calling thread's innermost open span
    (folded upward when it closes, as `host_reads` is): for values the
    host holds already, never a read. Nothing on a thread with no open
    span."""
    cur = getattr(_OPEN, "cur", None)
    if cur is not None:
        counters = cur[1].counters
        for name, amount in amounts.items():
            counters[name] = counters.get(name, 0) + amount


# -- ready stamps: when what a span sent was done ---------------------------


def sent(arrays, queue: str = "device", nbytes: int = 0) -> None:
    """Note on the calling thread's innermost open span when `arrays`,
    which this thread has just enqueued the writing of, are READY. Never
    blocks and dispatches nothing: the host time goes down as
    `handed_at` and the arrays go to the queue's watcher, a daemon thread
    (started at first use, one a queue) that takes entries first-in
    first-out, `block_until_ready`s each and writes `ready_at` onto the
    entry's span under the trace's lock, on the spans' clock. The span
    may have closed by then: stamps are plain attributes, not counters
    `leave` folds. A failed or deleted array stamps `ready_error` and
    nothing else. `queue` says which of the runtime's queues the work is
    on, `device` (the compute stream) or `link` (host-to-device copies):
    entries of one finish in the order sent, entries of different ones
    do not, so neither's stamps wait behind the other's. `nbytes` is
    what the entry holds of the device until it is ready. Nothing on a
    thread with no open span (always so under PRESTO_TPU_TRACE=0): no
    thread is started."""
    cur = getattr(_OPEN, "cur", None)
    if cur is not None:
        _watcher(queue).hand(cur[0], cur[1], arrays, nbytes)


def settle(timeout: float = 10.0) -> bool:
    """Wait until every entry handed over so far is stamped: for a
    reader that runs right behind the statement (EXPLAIN ANALYZE, a
    test), never for the served path. False if `timeout` s passed
    first."""
    markers = []
    for watcher in list(_WATCHERS.values()):
        marker = threading.Event()
        watcher.entries.put(marker)
        markers.append(marker)
    deadline = time.monotonic() + timeout
    return all(
        m.wait(max(0.0, deadline - time.monotonic())) for m in markers
    )


def _stamp_operator(attrs, handed_at, ready_at, queue_ready_at, _inflight):
    """`device`: one entry a span, its node's output. `ready_after` is
    when the entry before it on the queue was ready (absent on the
    queue's first): `Trace.device_spans` reads the three."""
    attrs["handed_at"] = handed_at
    attrs["ready_at"] = ready_at
    if queue_ready_at is not None:
        attrs["ready_after"] = queue_ready_at


def _stamp_batch(attrs, handed_at, ready_at, _queue_ready_at, inflight):
    """`link`: one entry a batch of a streamed scan, all on the scan's
    ONE span. Batch k adds to `upload_s` its `ready_k - max(ready_{k-1},
    handed_k)`: the link's time that nothing on the host covered; and to
    `link_idle_s` `max(0, handed_k - ready_{k-1})`: the time the link had
    nothing of this scan to copy because the host had not handed over
    the next batch. The two sum to the last `ready_at` minus the first
    `handed_at`. `inflight_peak_bytes`: the most bytes handed to the
    queue and not yet ready, as a hand-over of this scan found it."""
    before = attrs.get("ready_at")
    attrs.setdefault("handed_at", handed_at)
    attrs["ready_at"] = ready_at
    attrs["uploads"] = attrs.get("uploads", 0) + 1
    began = handed_at if before is None else max(before, handed_at)
    attrs["upload_s"] = attrs.get("upload_s", 0.0) + max(
        0.0, ready_at - began
    )
    attrs["link_idle_s"] = attrs.get("link_idle_s", 0.0) + (
        0.0 if before is None else max(0.0, handed_at - before)
    )
    attrs["inflight_peak_bytes"] = max(
        attrs.get("inflight_peak_bytes", 0), inflight
    )


_STAMPS = {"device": _stamp_operator, "link": _stamp_batch}
_WATCHERS: Dict[str, "_Watcher"] = {}
_watchers_lock = threading.Lock()


def _watcher(queue: str) -> "_Watcher":
    watcher = _WATCHERS.get(queue)
    if watcher is None:
        with _watchers_lock:
            watcher = _WATCHERS.get(queue)
            if watcher is None:
                watcher = _WATCHERS[queue] = _Watcher(queue, _STAMPS[queue])
    return watcher


class _Watcher:
    """One queue's entries, stamped in the order handed over."""

    def __init__(self, name: str, stamp):
        self.name = name
        self.stamp = stamp
        self.entries: "queue_mod.SimpleQueue" = queue_mod.SimpleQueue()
        self._lock = threading.Lock()  # `_inflight`: two threads write it
        self._inflight = 0
        self._ready_at: Optional[float] = None  # of the newest entry
        threading.Thread(
            target=self._watch, name=f"presto-ready-{name}", daemon=True
        ).start()

    def hand(self, trace: Trace, span: Span, arrays, nbytes: int) -> None:
        handed_at = time.time()
        with self._lock:
            self._inflight += nbytes
            inflight = self._inflight
        self.entries.put([trace, span, arrays, nbytes, handed_at, inflight])

    def _watch(self) -> None:
        while True:
            entry = self.entries.get()
            if isinstance(entry, threading.Event):  # `settle`'s marker
                entry.set()
            else:
                self._wait(entry)
            del entry  # nor the trace, while the queue is empty

    def _wait(self, entry: list) -> None:
        trace, span, arrays, nbytes, handed_at, inflight = entry
        error = None
        try:
            jax.block_until_ready(arrays)
        except Exception as e:  # noqa: BLE001 — a stamp never raises
            error = f"{type(e).__name__}: {e}"[:200]
        ready_at = time.time()
        entry[2] = arrays = None  # nothing is held past readiness
        with self._lock:
            self._inflight -= nbytes
        with trace._lock:
            span.attrs["ready_queue"] = self.name
            if error is not None:
                span.attrs["ready_error"] = error
                return
            self.stamp(
                span.attrs, handed_at, ready_at, self._ready_at, inflight
            )
        self._ready_at = ready_at


class Pulled:
    """A span for a body that runs in pieces on one thread: a generator
    that yields batches to a consumer between them (exec/stream.py's
    per-node streams). `with pulled:` around each piece makes the span
    the thread's innermost for as long, so what the piece books
    (`host_read`, `count`, compiles) and the spans it opens land under
    it, and the consumer's work between pieces does not. `close` ends
    the span at its start plus the seconds spent INSIDE the pieces: its
    wall is the body's own time, children's included, as an `enter`ed
    span's is, so `Trace.exclusive_walls` still gives every span its
    self time; only its right edge on the timeline is not where the last
    piece ended. Each piece is held inside a profiler annotation
    `presto.<name>`, as an `enter`ed span is (no cost beyond a flag test
    while no profiler session runs): a profile of a streamed statement
    has the node's pieces on the host's timeline. `open` returns None on
    a thread with no open span."""

    __slots__ = (
        "trace", "span", "parent", "inside_s", "_outer", "_t0",
        "_annotation",
    )

    @classmethod
    def open(cls, name: str, **attrs) -> Optional["Pulled"]:
        cur = getattr(_OPEN, "cur", None)
        if cur is None:
            return None
        self = cls()
        self.trace, self.parent = cur
        self.span = self.trace.begin(name, parent=self.parent, **attrs)
        self.inside_s = 0.0
        return self

    def __enter__(self):
        self._outer = getattr(_OPEN, "cur", None)
        _OPEN.cur = (self.trace, self.span)
        self._annotation = TraceAnnotation(
            "presto." + self.span.name, **self.trace._mark
        )
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self.span

    def __exit__(self, *_exc):
        self.inside_s += time.perf_counter() - self._t0
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        _OPEN.cur = self._outer
        return False

    def close(self, status: str = "ok") -> Span:
        span = self.span
        span.end = span.start + self.inside_s
        self.trace.finish(span, status)
        if span.counters:  # folded upward, as `Trace.leave` folds
            with self.trace._lock:
                into = self.parent.counters
                for k, v in span.counters.items():
                    into[k] = into.get(k, 0) + v
        return span


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_listening = False
_listening_lock = threading.Lock()
_compile_totals = [0, 0.0]  # this process's, since the listener began


def _listen_for_compiles() -> None:
    """Register, once a process, the listener that books `compiles` and
    `compile_s` on the span open on the compiling thread. JAX fires the
    event on that thread for every backend compile; a load from the
    persistent cache counts (the program was not in this process yet),
    a jit cache hit fires nothing."""
    global _listening
    with _listening_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _on_duration(event: str, secs: float, **_) -> None:
    if event != _COMPILE_EVENT:
        return
    with _listening_lock:
        _compile_totals[0] += 1
        _compile_totals[1] += secs
    cur = getattr(_OPEN, "cur", None)
    if cur is not None:
        counters = cur[1].counters
        counters["compiles"] = counters.get("compiles", 0) + 1
        counters["compile_s"] = counters.get("compile_s", 0.0) + secs


def compile_totals() -> Tuple[int, float]:
    """(backend compiles, their seconds) of this process since its first
    trace: what the spans' `compiles` / `compile_s` add up to, and the
    compiles on threads with no open span."""
    with _listening_lock:
        return _compile_totals[0], _compile_totals[1]


# process-global: the coordinator's (or single-process session's) view
TRACES = TraceStore()
