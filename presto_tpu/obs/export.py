"""Bridges from the existing stats silos into the MetricsRegistry.

Every `*Stats` surface the engine already maintains (NodeStats,
ExchangeStats, SchedulerStats, WireStats, GroupStats, CacheStats via
qcache snapshots, breaker stats, the spans' compile totals) exports here —
prestolint's `stats-not-exported` rule enforces that a surfaced Stats
class also reaches this module, so a new silo can't silently stay
invisible to `/v1/metrics`.

Naming scheme (docs/observability.md): `presto_<subsystem>_<what>` with
`_total` for counters and `_seconds`/`_bytes` units spelled out; labels
are low-cardinality only (cache name, breaker kernel, group name,
outcome) — never query ids or SQL.
"""

from __future__ import annotations

import threading
from typing import TYPE_CHECKING, Dict, Iterable, List, Optional

from .metrics import METRICS, Sample

if TYPE_CHECKING:  # annotations only — avoids exec/server import cycles
    from ..exec.qcache import CacheStats
    from ..exec.stats import NodeStats
    from ..plan.history import FeedbackStats
    from ..server.cluster import SchedulerStats
    from ..server.exchange import ExchangeStats
    from ..server.hier import HierExchangeStats
    from ..server.resource_groups import GroupStats
    from ..server.serde import WireStats

_defaults_lock = threading.Lock()
_defaults_done = False


def reset_defaults() -> None:
    global _defaults_done
    with _defaults_lock:
        _defaults_done = False


def ensure_default_exports() -> None:
    """Idempotent: declare the core series (stable scrape schema before
    the first increment) and register the process-global snapshot
    producers. Called by every scrape/collect."""
    global _defaults_done
    with _defaults_lock:
        if _defaults_done:
            return
        _defaults_done = True
    METRICS.declare_counter(
        "presto_queries_total", "Queries executed", {"outcome": "ok"}
    )
    METRICS.declare_counter(
        "presto_queries_total", labels={"outcome": "error"}
    )
    METRICS.declare_counter(
        "presto_exchange_pages_total", "Exchange pages pulled"
    )
    METRICS.declare_counter(
        "presto_exchange_wire_bytes_total", "Exchange bytes off the wire"
    )
    METRICS.declare_counter(
        "presto_exchange_hidden_seconds_total",
        "Exchange wire wall hidden behind device compute",
    )
    METRICS.declare_counter(
        "presto_hier_exchanges_total",
        "Output batches regrouped by the hierarchical exchange",
        {"role": "task"},
    )
    METRICS.declare_counter(
        "presto_wire_encode_seconds_total", "Page serialization wall"
    )
    METRICS.declare_counter(
        "presto_wire_decode_seconds_total", "Page deserialization wall"
    )
    METRICS.declare_counter(
        "presto_worker_tasks_total", "Worker tasks run", {"state": "FINISHED"}
    )
    METRICS.declare_counter(
        "presto_worker_tasks_total", labels={"state": "FAILED"}
    )
    METRICS.register_producer("qcache", _metrics_qcache_producer)
    METRICS.register_producer("breakers", _metrics_breaker_producer)
    METRICS.register_producer("compiles", _metrics_kernel_producer)
    METRICS.register_producer("feedback", _metrics_feedback_producer)


# ---------------------------------------------------------------------------
# pull producers: process-global snapshot owners, evaluated at scrape
# ---------------------------------------------------------------------------


def export_cache_stats(cache: str, stats: "CacheStats") -> List[Sample]:
    """One qcache LRU's CacheStats as counter/gauge samples."""
    snap = stats.snapshot()
    label = (("cache", cache),)
    out: List[Sample] = []
    for field in ("hits", "misses", "stores", "evictions",
                  "invalidations", "patches"):
        out.append((
            f"presto_qcache_{field}_total", "counter", label,
            float(snap[field]),
        ))
    out.append((
        "presto_qcache_bytes", "gauge", label, float(snap["bytes"])
    ))
    return out


def _metrics_qcache_producer() -> List[Sample]:
    from ..exec.qcache import (
        HISTORY_CACHE, KERNEL_CACHE, PLAN_CACHE, RESULT_CACHE,
    )

    out: List[Sample] = []
    for name, cache in (
        ("plan", PLAN_CACHE), ("result", RESULT_CACHE),
        ("kernel", KERNEL_CACHE), ("history", HISTORY_CACHE),
    ):
        out.extend(export_cache_stats(name, cache.stats))
    return out


def export_feedback_stats(stats: "FeedbackStats") -> List[Sample]:
    """The adaptive-execution plane's FeedbackStats (plan/history.py) as
    `presto_feedback_*` samples: store traffic, estimate quality, and
    mid-query replans."""
    snap = stats.snapshot()
    out: List[Sample] = []
    for field in ("hits", "misses", "records", "invalidations",
                  "decays", "mispredictions", "replans"):
        out.append((
            f"presto_feedback_{field}_total", "counter", (),
            float(snap[field]),
        ))
    err = snap.get("mean_abs_rel_err")
    if err is not None:
        out.append((
            "presto_feedback_estimate_rel_error", "gauge", (), float(err)
        ))
    return out


def _metrics_feedback_producer() -> List[Sample]:
    from ..plan.history import HISTORY

    return export_feedback_stats(HISTORY.stats)


def _metrics_breaker_producer() -> List[Sample]:
    from ..exec.breaker import BREAKERS

    snap = BREAKERS.snapshot()
    open_count = 0
    out: List[Sample] = []
    for kernel, s in sorted(snap.items()):
        is_open = 1.0 if s.get("state") == "open" else 0.0
        open_count += int(is_open)
        label = (("kernel", kernel),)
        out.append(("presto_breaker_open", "gauge", label, is_open))
        out.append((
            "presto_breaker_failures_total", "counter", label,
            float(s.get("total_failures", 0)),
        ))
        out.append((
            "presto_breaker_successes_total", "counter", label,
            float(s.get("total_successes", 0)),
        ))
    # summary gauge is ALWAYS present so scrapers see the breaker plane
    # even before any kernel has tripped
    out.append((
        "presto_breakers_open_count", "gauge", (), float(open_count)
    ))
    return out


def _metrics_kernel_producer() -> List[Sample]:
    """Backend compiles of this process, as the spans' listener counts
    them (obs/span.py: `compiles` / `compile_s` on the span that paid)."""
    from .span import compile_totals

    compiles, seconds = compile_totals()
    return [
        ("presto_kernel_compiles_total", "counter", (), float(compiles)),
        ("presto_kernel_compile_seconds_total", "counter", (), seconds),
    ]


def export_group_stats(groups: Iterable["GroupStats"]) -> List[Sample]:
    out: List[Sample] = []
    for g in groups:
        label = (("group", g.name),)
        out.append((
            "presto_resource_group_running", "gauge", label,
            float(g.running),
        ))
        out.append((
            "presto_resource_group_queued", "gauge", label, float(g.queued)
        ))
        out.append((
            "presto_resource_group_cpu_used_seconds", "gauge", label,
            float(g.cpu_used_s),
        ))
    return out


def register_resource_groups(manager) -> None:
    """Scrape-time producer over the coordinator's resource-group tree
    (fixed key: a re-created QueryManager replaces, never accumulates)."""
    METRICS.register_producer(
        "resource_groups", lambda: export_group_stats(manager.stats())
    )


# ---------------------------------------------------------------------------
# push exporters: per-query / per-task folds at the silo's own fold point
# ---------------------------------------------------------------------------


def export_node_stats(by_node: Dict[int, "NodeStats"]) -> None:
    """Fold one resolved StatsCollector (EXPLAIN ANALYZE run) into the
    exec series."""
    calls = wall = rows = out_bytes = 0
    for s in by_node.values():
        calls += s.calls
        wall += s.wall_s
        rows += max(0, s.rows_out)
        out_bytes += s.out_bytes_total
    METRICS.counter("presto_exec_node_calls_total", calls,
                    help="Plan-node dispatches (EXPLAIN ANALYZE runs)")
    METRICS.counter("presto_exec_node_wall_seconds_total", wall)
    METRICS.counter("presto_exec_rows_total", rows)
    METRICS.counter("presto_exec_output_bytes_total", out_bytes)


def export_exchange_stats(pull: "ExchangeStats") -> None:
    """Fold one gather's pull-side accounting (each ExchangeStats lives
    for one gather and is folded exactly once, at _record_exchange)."""
    snap = pull.snapshot()
    METRICS.counter("presto_exchange_pages_total", snap.get("pages", 0))
    METRICS.counter(
        "presto_exchange_wire_bytes_total", snap.get("wire_bytes", 0)
    )
    METRICS.counter(
        "presto_exchange_responses_total", snap.get("responses", 0)
    )
    METRICS.counter(
        "presto_exchange_pull_seconds_total",
        (snap.get("pull_ms") or 0) / 1e3,
    )
    METRICS.counter(
        "presto_exchange_decode_seconds_total",
        (snap.get("decode_ms") or 0) / 1e3,
    )
    # overlap plane (hierarchical exchange): wire wall split into the
    # part the consumer actually waited for vs the part its device
    # compute hid behind prefetching pullers
    METRICS.counter(
        "presto_exchange_consumer_wait_seconds_total",
        (snap.get("consumer_wait_ms") or 0) / 1e3,
    )
    METRICS.counter(
        "presto_exchange_hidden_seconds_total",
        (snap.get("hidden_ms") or 0) / 1e3,
    )


def export_hier_stats(stats: "HierExchangeStats",
                      role: str = "task") -> None:
    """Fold one endpoint's hierarchical-exchange accounting into the
    metrics plane — called once when the endpoint retires. `role`
    labels the fold point ("task" = a worker's own producer regroup,
    "gather" = the coordinator's per-exchange fold over its producers'
    status payloads) so an in-process fleet sharing one registry never
    double-counts one series."""
    snap = stats.snapshot()
    label = {"role": role}
    METRICS.counter(
        "presto_hier_exchanges_total", snap.get("exchanges", 0), label,
        help="Output batches regrouped by the hierarchical exchange",
    )
    METRICS.counter(
        "presto_hier_collective_exchanges_total",
        snap.get("collective_exchanges", 0), label,
    )
    METRICS.counter("presto_hier_rows_total", snap.get("rows", 0), label)
    METRICS.counter(
        "presto_hier_collective_seconds_total",
        (snap.get("collective_ms") or 0) / 1e3, label,
    )
    METRICS.counter(
        "presto_hier_wire_pages_total", snap.get("wire_pages", 0), label
    )
    METRICS.counter(
        "presto_hier_ragged_pad_rows_total",
        snap.get("ragged_pad_rows", 0), label,
    )
    METRICS.counter(
        "presto_hier_fixed_pad_rows_total",
        snap.get("fixed_pad_rows", 0), label,
    )
    METRICS.counter(
        "presto_hier_fallbacks_total", snap.get("fallbacks", 0), label
    )


def export_wire_stats(role: str, stats: "WireStats") -> None:
    """Fold one endpoint's serde accounting (a task's output serializer
    or a pull client's decoder) — called once when the endpoint retires."""
    snap = stats.snapshot() if hasattr(stats, "snapshot") else {}
    label = {"role": role}
    METRICS.counter(
        "presto_wire_pages_total", snap.get("pages", 0), label
    )
    METRICS.counter(
        "presto_wire_bytes_total", snap.get("wire_bytes", 0), label
    )
    METRICS.counter(
        "presto_wire_encode_seconds_total",
        (snap.get("encode_ms") or 0) / 1e3, label,
    )
    METRICS.counter(
        "presto_wire_decode_seconds_total",
        (snap.get("decode_ms") or 0) / 1e3, label,
    )


def export_scheduler_stats(stats: "SchedulerStats") -> None:
    """Publish the scheduler's cumulative counters as gauges (the
    SchedulerStats object is itself cumulative; re-publishing is
    idempotent). Caller holds the scheduler lock."""
    import dataclasses

    for field, value in dataclasses.asdict(stats).items():
        if isinstance(value, (int, float)):
            METRICS.gauge(f"presto_scheduler_{field}", float(value))


def export_query(outcome: str, wall_s: float,
                 phase_ms: Optional[Dict[str, float]] = None) -> None:
    """One query completion (single-process or cluster execution layer)."""
    METRICS.counter(
        "presto_queries_total", 1, {"outcome": outcome},
        help="Queries executed",
    )
    METRICS.observe(
        "presto_query_seconds", wall_s, help="Query wall time"
    )
    for phase, ms in (phase_ms or {}).items():
        METRICS.observe(f"presto_query_phase_{phase}_seconds", ms / 1e3)
