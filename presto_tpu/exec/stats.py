"""Per-operator execution statistics — the EXPLAIN ANALYZE substrate.

Re-designed equivalent of the reference's operator stats tree
(presto-main/.../operator/OperatorStats.java, DriverStats, TaskStats rolled
into QueryStats) and ExplainAnalyzeContext
(presto-main/.../execution/ExplainAnalyzeContext.java). TPU-first
differences: the unit of accounting is a plan-node *kernel dispatch* (one
jitted XLA program) rather than a Java operator's addInput/getOutput calls,
and the memory number is the device-resident bytes of the node's output
page — the HBM footprint XLA must hold live between stages.

Wall time per node includes host sync (`block_until_ready` on the output
count), so the first call also includes XLA compile time; `calls` lets the
reader separate warm-up from steady state, and `retries` counts adaptive
capacity re-executions (the static-shape analog of the reference's page
growth, which its stats never see). The wall is the HOST's time in the
node; `device_s` is the node's stretch of the device's queue, from the
ready stamps of its spans (obs/span.py).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class NodeStats:
    calls: int = 0
    wall_s: float = 0.0
    rows_in: int = 0
    rows_out: int = 0
    retries: int = 0
    # device bytes of the node's output page. `out_bytes` is the LAST
    # call's page (the node's live footprint — the collector's
    # peak_bytes high-water sums these); multi-dispatch nodes report
    # honestly through the cumulative total and per-dispatch peak.
    out_bytes: int = 0
    out_bytes_total: int = 0  # cumulative across all dispatches
    out_bytes_peak: int = 0  # largest single dispatch
    detail: str = ""  # connector-provided annotation (e.g. file pruning)
    # the node's stretch of the device's queue, from its spans' ready
    # stamps (obs/span.py `Trace.device_spans`: its programs and the
    # gaps between them); None where the run left no stamp
    device_s: Optional[float] = None

    def line(self) -> str:
        ms = self.wall_s * 1e3
        parts = [
            f"{ms:,.1f}ms",
            f"in {self.rows_in:,} rows",
            f"out {self.rows_out:,} rows",
            f"{_fmt_bytes(self.out_bytes)}",
        ]
        if self.device_s is not None:
            parts.append(f"device-side {self.device_s * 1e3:,.1f}ms")
        if self.calls != 1:
            parts.append(f"{self.calls} calls")
            if self.out_bytes_total != self.out_bytes:
                parts.append(
                    f"Σ{_fmt_bytes(self.out_bytes_total)}"
                    f" (peak {_fmt_bytes(self.out_bytes_peak)})"
                )
        if self.retries:
            parts.append(f"{self.retries} retries")
        if self.detail:
            parts.append(self.detail)
        return "[" + ", ".join(parts) + "]"


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "kB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:,.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"


def page_device_bytes(page) -> int:
    """Device-resident bytes of a Page's blocks (data + validity masks)."""
    total = 0
    for b in page.blocks:
        total += b.data.size * b.data.dtype.itemsize
        if b.valid is not None:
            total += b.valid.size * b.valid.dtype.itemsize
    return total


class StatsCollector:
    """Collects per-node stats keyed by plan-node identity (two structurally
    equal nodes at different tree positions stay distinct).

    Row counts are collected LAZILY by default: `record` accepts device
    int32 scalars (or lists of them) for rows_in/rows_out and parks them
    unresolved — reading a device scalar is a blocking host sync, and one
    per plan node was the dominant term in on-chip SQL wall time
    (2026-08-01 chip session: ~5 syncs ≈ 2.5 s around a 14 ms
    aggregation).
    `resolve()` drains them in one batch at query end, which is when the
    EXPLAIN ANALYZE renderer needs integers anyway. Pass
    `sync_counts=True` to restore the old per-node blocking reads (then
    per-node wall time includes kernel completion, not just dispatch)."""

    def __init__(self, sync_counts: bool = False):
        self.by_node: Dict[int, NodeStats] = {}
        self.peak_bytes: int = 0  # high-water of summed live output bytes
        self.sync_counts = sync_counts
        self._pending: list = []  # (NodeStats, rows_in, rows_out) scalars

    def stats_for(self, node) -> NodeStats:
        s = self.by_node.get(id(node))
        if s is None:
            s = NodeStats()
            self.by_node[id(node)] = s
        return s

    @staticmethod
    def _count(x) -> int:
        from ..obs.span import host_read

        if isinstance(x, (list, tuple)):
            return sum(int(host_read(v)) for v in x)
        return int(host_read(x))

    def record(self, node, wall_s: float, rows_in, rows_out,
               out_bytes: int, retries: int = 0) -> None:
        s = self.stats_for(node)
        s.calls += 1
        s.wall_s += wall_s
        s.retries += retries
        s.out_bytes = out_bytes
        s.out_bytes_total += out_bytes
        s.out_bytes_peak = max(s.out_bytes_peak, out_bytes)
        if self.sync_counts:
            s.rows_in += self._count(rows_in)
            s.rows_out += self._count(rows_out)
        else:
            # keep the device scalars; resolved once at query end
            self._pending.append((s, rows_in, rows_out))
        live = sum(st.out_bytes for st in self.by_node.values())
        self.peak_bytes = max(self.peak_bytes, live)

    def resolve(self) -> None:
        """Fold all parked device row-count scalars into the integer
        stats — ONE sync point at query end instead of one per node."""
        pending, self._pending = self._pending, []
        for s, rows_in, rows_out in pending:
            s.rows_in += self._count(rows_in)
            s.rows_out += self._count(rows_out)
        from ..obs.export import export_node_stats

        export_node_stats(self.by_node)

    def lookup(self, node) -> Optional[NodeStats]:
        return self.by_node.get(id(node))

    def total_wall_s(self) -> float:
        return sum(s.wall_s for s in self.by_node.values())


def kernel_breaker_snapshot() -> Dict[str, dict]:
    """State of every kernel circuit breaker (exec/breaker.py) — part of
    the stats surface so EXPLAIN ANALYZE and operators can report that a
    kernel path is degraded, not silently slower."""
    from .breaker import BREAKERS

    return BREAKERS.snapshot()


def kernel_breaker_lines() -> List[str]:
    """Formatted one-per-breaker report lines for non-closed breakers."""
    lines = []
    for name, snap in sorted(kernel_breaker_snapshot().items()):
        if snap["state"] == "closed" and not snap["total_failures"]:
            continue
        parts = [f"breaker {name}: {snap['state']}"]
        if snap["total_failures"]:
            parts.append(f"{snap['total_failures']} failures")
        if snap.get("retry_in_s") is not None:
            parts.append(f"retry in {snap['retry_in_s']:.0f}s")
        if snap["last_error"]:
            parts.append(snap["last_error"].splitlines()[0][:80])
        lines.append(", ".join(parts))
    return lines
