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
"""

from __future__ import annotations

import contextlib
import itertools
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
    piece ended. It holds no profiler annotation (one a piece would be
    one a batch). `open` returns None on a thread with no open span."""

    __slots__ = ("trace", "span", "parent", "inside_s", "_outer", "_t0")

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
        self._t0 = time.perf_counter()
        return self.span

    def __exit__(self, *_exc):
        self.inside_s += time.perf_counter() - self._t0
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
    cur = getattr(_OPEN, "cur", None)
    if cur is not None:
        counters = cur[1].counters
        counters["compiles"] = counters.get("compiles", 0) + 1
        counters["compile_s"] = counters.get("compile_s", 0.0) + secs


# process-global: the coordinator's (or single-process session's) view
TRACES = TraceStore()
