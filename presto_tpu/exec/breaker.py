"""Kernel-fault circuit breakers: reusable degrade-to-XLA machinery.

Generalizes the ad-hoc `self.pallas_groupby = False` kill switch that
round-5 added after a Mosaic fault took down a whole SQL stage (see
exec/executor.py aggregation dispatch). Each experimental kernel path —
the Pallas group-by, the bucket-directory join probe (ops/join.py) and
the fused variadic sort (ops/sort.py) — now runs behind a named breaker
with the classic three states:

* CLOSED     — kernel allowed; consecutive failures are counted.
* OPEN       — kernel skipped (the safe XLA composition runs instead)
  until `recovery_timeout` elapses.
* HALF_OPEN  — after the timeout probe attempts are admitted again;
  success closes the breaker, failure re-opens it with a fresh timeout.

`allow()` is deliberately non-mutating (HALF_OPEN is derived from the
clock, transitions happen only in record_success / record_failure): the
executor consults the breaker when picking a kernel-cache key and the op
layer consults it again at trace time, and both must see one answer.

The registry is process-global (module singleton `BREAKERS`) because a
kernel that faults does so for every executor in the process — the
failure is a property of the (kernel, backend, libtpu) combination, not
of one query. Stats surface through exec/stats.py and EXPLAIN ANALYZE.

Env knobs:
* PRESTO_TPU_BREAKER_THRESHOLD   consecutive failures to open (default 1
  — matching the old behavior where a single Mosaic failure disabled the
  Pallas path for the process).
* PRESTO_TPU_BREAKER_RECOVERY_S  seconds an open breaker waits before a
  half-open probe (default 300).
* PRESTO_TPU_BREAKER_DISABLE=1   breakers never open (kernel faults
  still fall back per call, but every call re-attempts the kernel).
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Callable, Dict, Optional

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

# What a bug in this repo raises (a name the installed JAX no longer has,
# a typo) — not a kernel the compiler or the device refused. Dispatch
# sites re-raise these instead of degrading: a fallback would compare the
# XLA path with itself and nothing would say the kernel never ran.
PROGRAMMING_ERRORS = (ImportError, AttributeError, NameError)


class KernelCircuitBreaker:
    """One kernel's failure state machine. Thread-safe: executors on
    worker task threads share the process-global registry."""

    def __init__(self, name: str, failure_threshold: int = 1,
                 recovery_timeout: float = 300.0,
                 clock: Callable[[], float] = time.monotonic):
        self.name = name
        self.failure_threshold = max(1, int(failure_threshold))
        self.recovery_timeout = float(recovery_timeout)
        self.clock = clock
        self.consecutive_failures = 0
        self.total_failures = 0
        self.total_successes = 0
        self.opened_at: Optional[float] = None  # None = closed
        self.last_error: str = ""
        self._lock = threading.RLock()

    @property
    def state(self) -> str:
        with self._lock:
            if self.opened_at is None:
                return CLOSED
            if self.clock() - self.opened_at >= self.recovery_timeout:
                return HALF_OPEN
            return OPEN

    def allow(self) -> bool:
        """May the kernel be attempted right now? Non-mutating: an open
        breaker past its recovery timeout admits half-open probes."""
        return self.state != OPEN

    def record_success(self) -> None:
        with self._lock:
            self.total_successes += 1
            self.consecutive_failures = 0
            self.opened_at = None  # a half-open probe succeeded: close

    def record_failure(self, error: str = "") -> None:
        with self._lock:
            self.total_failures += 1
            self.consecutive_failures += 1
            self.last_error = error[:300]
            if self.opened_at is not None:
                # half-open probe failed (or repeat fault while open):
                # re-arm a fresh recovery window
                self.opened_at = self.clock()
            elif self.consecutive_failures >= self.failure_threshold:
                self.opened_at = self.clock()

    def snapshot(self) -> dict:
        with self._lock:
            state = self.state
            wait = None
            if state == OPEN and self.opened_at is not None:
                wait = max(
                    0.0,
                    self.recovery_timeout - (self.clock() - self.opened_at),
                )
            return {
                "state": state,
                "consecutive_failures": self.consecutive_failures,
                "total_failures": self.total_failures,
                "total_successes": self.total_successes,
                "retry_in_s": wait,
                "last_error": self.last_error,
            }


class BreakerRegistry:
    """Lazily-created breakers keyed by kernel name."""

    def __init__(self, failure_threshold: Optional[int] = None,
                 recovery_timeout: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self._breakers: Dict[str, KernelCircuitBreaker] = {}
        self._lock = threading.Lock()
        self._tls = threading.local()  # per-thread forced-fallback names
        self.clock = clock
        self.failure_threshold = failure_threshold
        self.recovery_timeout = recovery_timeout

    @contextlib.contextmanager
    def forced_fallback(self, name: str):
        """Force `allow(name)` to False on THIS thread for the duration —
        the executor's per-call fallback retry after a fault, regardless
        of breaker state (a below-threshold streak or
        PRESTO_TPU_BREAKER_DISABLE=1 must still fall back for the call
        that just faulted). Thread-local because the kernel's trace runs
        on the caller's thread."""
        prev = getattr(self._tls, "forced", frozenset())
        self._tls.forced = prev | {name}
        try:
            yield
        finally:
            self._tls.forced = prev

    def _config(self):
        threshold = self.failure_threshold
        if threshold is None:
            threshold = int(
                os.environ.get("PRESTO_TPU_BREAKER_THRESHOLD", "1")
            )
        recovery = self.recovery_timeout
        if recovery is None:
            recovery = float(
                os.environ.get("PRESTO_TPU_BREAKER_RECOVERY_S", "300")
            )
        return threshold, recovery

    def get(self, name: str) -> KernelCircuitBreaker:
        with self._lock:
            br = self._breakers.get(name)
            if br is None:
                threshold, recovery = self._config()
                br = KernelCircuitBreaker(
                    name, failure_threshold=threshold,
                    recovery_timeout=recovery, clock=self.clock,
                )
                self._breakers[name] = br
            return br

    def allow(self, name: str) -> bool:
        if name in getattr(self._tls, "forced", ()):
            return False
        if os.environ.get("PRESTO_TPU_BREAKER_DISABLE") == "1":
            return True
        return self.get(name).allow()

    def record_success(self, name: str) -> None:
        self.get(name).record_success()

    def record_failure(self, name: str, error: str = "") -> None:
        self.get(name).record_failure(error)

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            items = list(self._breakers.items())
        return {name: br.snapshot() for name, br in items}

    def reset(self) -> None:
        """Forget all breaker state (tests)."""
        with self._lock:
            self._breakers.clear()


# process-global registry: kernel health is per (backend, libtpu), not
# per executor instance
BREAKERS = BreakerRegistry()
