"""Test harness: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's DistributedQueryRunner idea (presto-tests/.../
DistributedQueryRunner.java:75 — N workers in one JVM): we test all
multi-chip sharding logic on N virtual CPU devices in one process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
import pathlib
import re

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

# Tests run on the CPU (eight virtual devices), whatever is attached.
jax.config.update("jax_platforms", "cpu")
# No persistent compile cache under test: six xdist workers would share
# <checkout>/.jax_cache, and XLA:CPU logs a machine-feature mismatch
# error for every entry it reloads.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


class _JaxAs:
    """`jax` for ONE module, answering `default_backend()` with a name
    of the test's and everything else as `jax` does."""

    def __init__(self, backend):
        self._backend = backend

    def default_backend(self):
        return self._backend

    def __getattr__(self, name):
        return getattr(jax, name)


@pytest.fixture()
def device_branch(monkeypatch):
    """`Executor._dyn_compact` takes `np.flatnonzero` on the CPU backend;
    here the executor module (and no other) sees an accelerator, so its
    device branch runs. Returns the list its host reads are added to."""
    from presto_tpu.exec import executor

    reads = []
    real = executor.host_read
    monkeypatch.setattr(executor, "jax", _JaxAs("accelerator"))
    monkeypatch.setattr(
        executor, "host_read", lambda x: reads.append(1) or real(x)
    )
    return reads


# -- memory/spill accounting guard (every test) ------------------------------
#
# After EVERY test: no spill file may be left on disk and no spill bytes
# may still be charged against any quota (a leaked reservation in one test
# silently shrinks the budget of every later query on a shared node), and
# no MemoryPool anywhere may have recorded an over-free (a double-free
# accounting bug masks real leaks). Worker task threads are daemons and may
# still be mid-teardown when the test body returns, so the spill check
# polls briefly before declaring a leak.


@pytest.fixture(autouse=True)
def _query_cache_isolation():
    """Drop plan/result cache ENTRIES before each test: the caches are
    process-wide and keyed partly by catalog object identity, so a
    module-scoped catalog fixture would otherwise let one test serve a
    result another test expected to EXECUTE (fault-injection and
    observability tests monkeypatch internals and assert side effects).
    The kernel (compile) cache is intentionally left warm — cross-test
    compiled-kernel reuse is exactly its production behavior and only
    speeds the suite up. Within-test cache behavior is unaffected."""
    from presto_tpu.exec import qcache

    qcache.PLAN_CACHE.clear()
    qcache.RESULT_CACHE.clear()
    yield


@pytest.fixture(autouse=True)
def _memory_accounting_guard():
    from presto_tpu.exec import spillspace
    from presto_tpu.exec.memory import GLOBAL_ACCOUNTING

    over0 = GLOBAL_ACCOUNTING["over_frees"]
    yield
    import time as _time

    deadline = _time.monotonic() + 5.0
    while _time.monotonic() < deadline:
        if spillspace.all_active_bytes() == 0 and (
            spillspace.all_active_files() == 0
        ):
            break
        _time.sleep(0.05)
    assert spillspace.all_active_bytes() == 0, (
        f"leaked spill bytes: {spillspace.all_active_bytes()} "
        "(a query finished/was killed without releasing its spill space)"
    )
    assert spillspace.all_active_files() == 0, (
        f"leaked spill files: {spillspace.all_active_files()}"
    )
    over = GLOBAL_ACCOUNTING["over_frees"] - over0
    assert over == 0, (
        f"{over} memory over-free(s) recorded during this test — a "
        "double-free accounting bug (exec/memory.py MemoryPool.free)"
    )


# -- per-test wall-clock guard (no pytest-timeout in the image) --------------
#
# The distributed/cluster modules talk to real HTTP worker threads; a wedged
# worker once stalled a whole tier-1 run. An alarm-based guard
# fails the TEST instead of hanging the RUN. Only modules that spin up
# workers/servers get a default; any test can override with
# @pytest.mark.timeout(seconds).

_MODULE_TIMEOUTS = {
    "test_server.py": 240,
    "test_cluster_memory.py": 240,
    "test_streaming_exchange.py": 240,
    "test_fault_tolerance.py": 240,
    "test_taskqueue.py": 240,
    "test_tpch_distributed.py": 300,
    "test_distributed_sort.py": 300,
    "test_grouped_exchange.py": 300,
    "test_parallel.py": 300,
    "test_jdbc.py": 240,
    "test_auth_tls.py": 240,
    "test_memory_pressure.py": 300,
    "test_overload_chaos.py": 300,
    "test_query_cache.py": 240,
    "test_matview_chaos.py": 300,
    "test_feedback.py": 240,
}
# Every shard of the TPC-DS suite, however many there are (the limit is per
# case: Q14, the longest, took 109 s in a whole run).
_MODULE_TIMEOUTS.update(
    (p.name, 300)
    for p in (pathlib.Path(__file__).parent / "tpcds").glob("test_*.py")
)

_SLOW_CANDIDATE_S = 30.0
_slow_candidates = []


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1 (-m 'not slow')"
    )
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test wall-clock guard override"
    )


def _alarm_guard(item, phase):
    """Context manager arming SIGALRM for one runtest phase — setup and
    teardown too: a cluster fixture wedging while starting/stopping
    workers is the same hazard as a wedged test body."""
    import contextlib
    import signal
    import threading

    marker = item.get_closest_marker("timeout")
    limit = (
        float(marker.args[0]) if marker and marker.args
        else _MODULE_TIMEOUTS.get(item.path.name)
    )
    usable = (
        limit
        and hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
    )

    @contextlib.contextmanager
    def guard():
        if not usable:
            yield
            return

        def _on_timeout(signum, frame):
            raise TimeoutError(
                f"{item.nodeid} [{phase}] exceeded the {limit:.0f}s "
                "wall-clock guard (wedged worker?)"
            )

        old = signal.signal(signal.SIGALRM, _on_timeout)
        signal.setitimer(signal.ITIMER_REAL, limit)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)

    return guard()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    with _alarm_guard(item, "setup"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item):
    with _alarm_guard(item, "teardown"):
        yield


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    import time as _time

    start = _time.monotonic()
    with _alarm_guard(item, "call"):
        yield
    wall = _time.monotonic() - start
    if wall > _SLOW_CANDIDATE_S and not item.get_closest_marker("slow"):
        _slow_candidates.append((item.nodeid, wall))


def pytest_terminal_summary(terminalreporter):
    if _slow_candidates:
        terminalreporter.write_sep(
            "-", "slow-test candidates (>30s; consider @pytest.mark.slow)"
        )
        for nodeid, wall in sorted(_slow_candidates, key=lambda x: -x[1]):
            terminalreporter.write_line(f"  {wall:6.1f}s  {nodeid}")


_EXIT_STATUS = [0]


def pytest_sessionfinish(session, exitstatus):
    _EXIT_STATUS[0] = int(exitstatus)


def pytest_unconfigure(config):
    """Bypass interpreter teardown: XLA/plugin native destructors can abort
    (SIGABRT, 'FATAL: exception not rethrown') AFTER a fully green run,
    turning exit 0 into 134. unconfigure runs after the terminal reporter
    has printed failures and the summary — flush and exit directly."""
    import sys

    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_EXIT_STATUS[0])
