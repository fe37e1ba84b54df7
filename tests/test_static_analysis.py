"""prestolint (presto_tpu/analysis): seeded true positives and
false-positive guards for every pass, suppression/baseline round-trips,
and the tier-1 gate that keeps the REAL tree clean."""

import gc
import json
import textwrap
import time
from pathlib import Path

import pytest

from presto_tpu.analysis import (
    load_project,
    run_check,
    run_passes,
)
from presto_tpu.analysis.core import (
    evaluate_against_baseline,
    load_baseline,
    save_baseline,
)
from presto_tpu.analysis.passes import (
    PASSES_BY_NAME,
    coverage as p_cov,
    exceptions as p_exc,
    exhaustive as p_exh,
    knobs as p_knobs,
    locks as p_locks,
    memory as p_mem,
    races as p_races,
    tracing as p_trace,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


def make_project(tmp_path, files):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return load_project(tmp_path)


def rules(findings):
    return sorted(f.rule for f in findings)


# -- tracing-safety ---------------------------------------------------------


def test_tracing_flags_unguarded_callback(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/ops/bad.py": """
            import jax
            import jax.numpy as jnp

            def kernel(lanes, cap):
                return jax.pure_callback(_host, None, *lanes)
        """,
    })
    found = run_passes(proj, [p_trace.PASS])
    assert "tracing-host-callback" in rules(found)


def test_tracing_guarded_callback_is_clean(tmp_path):
    # the ops/sort.py idiom: eager bypass when concrete, callback only
    # as the under-trace fallback
    proj = make_project(tmp_path, {
        "presto_tpu/ops/good.py": """
            import jax
            import jax.numpy as jnp

            def kernel(lanes, cap):
                if not isinstance(lanes[0], jax.core.Tracer):
                    return _host_argsort(*lanes)
                return jax.pure_callback(_host_argsort, None, *lanes)
        """,
    })
    assert run_passes(proj, [p_trace.PASS]) == []


def test_tracing_guard_is_scoped_not_function_wide(tmp_path):
    # a guard somewhere in the function must not silence an UNRELATED
    # callback: only callbacks inside a guard-conditional's subtree, or
    # after a guard whose body early-returns, count as guarded
    proj = make_project(tmp_path, {
        "presto_tpu/ops/scoped.py": """
            import jax
            import jax.numpy as jnp

            def kernel(lanes, extra):
                # unguarded callback BEFORE the guard: still flagged
                pre = jax.pure_callback(_host_prep, None, extra)
                if _concrete(*lanes):
                    return _host_argsort(*lanes)
                return jax.pure_callback(_host_argsort, None, *lanes)

            def sibling(lanes, mode):
                if _concrete(*lanes):
                    out = _host_argsort(*lanes)
                # guard body does NOT return: the later callback is on
                # an unrelated path and must be flagged
                return jax.pure_callback(_host_argsort, None, *lanes)
        """,
    })
    found = run_passes(proj, [p_trace.PASS])
    assert rules(found) == ["tracing-host-callback"] * 2
    assert sorted(f.context for f in found) == ["kernel", "sibling"]


def test_tracing_flags_tracer_truthiness(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/ops/bad.py": """
            import jax.numpy as jnp

            def kernel(x):
                if jnp.any(x > 0):
                    return jnp.sum(x)
                return x
        """,
    })
    assert "tracing-tracer-bool" in rules(run_passes(proj, [p_trace.PASS]))


def test_tracing_flags_numpy_consumer_on_device(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/ops/bad.py": """
            import numpy as np
            import jax.numpy as jnp

            def kernel(x):
                y = jnp.abs(x)
                return np.argsort(y)
        """,
    })
    assert "tracing-numpy-on-device" in rules(
        run_passes(proj, [p_trace.PASS])
    )


def test_tracing_false_positive_guards(tmp_path):
    proj = make_project(tmp_path, {
        # _host_ prefix, callback targets, np CONSTRUCTORS over host
        # data, the host-function marker, and code outside ops//expr/
        # must all stay clean
        "presto_tpu/ops/good.py": """
            import jax
            import numpy as np
            import jax.numpy as jnp

            def _host_select(k):
                return np.argsort(k)

            def entry_table(vals):
                # constructors over host data: the dictionary idiom
                table = np.zeros(len(vals) + 1, np.int64)
                return jnp.asarray(table)

            # prestolint: host-function -- eager orchestration; jnp only
            # touches concrete arrays here
            def orchestrate(px):
                cells = np.clip(px, 0, 8)
                return jnp.asarray(cells)

            def jitted(lanes):
                return jax.pure_callback(_host_select, None, lanes[0])
        """,
        "presto_tpu/exec/mixed.py": """
            import numpy as np
            import jax.numpy as jnp

            def eager_compact(keep):
                # exec/ mixes worlds legally (eager executor code)
                return np.flatnonzero(np.asarray(keep))
        """,
    })
    found = run_passes(proj, [p_trace.PASS])
    # the pure_callback in `jitted` targets _host_select which IS a
    # callback target; but `jitted` itself has no guard -> still flagged
    assert rules(found) == ["tracing-host-callback"]


def test_tracing_nested_defs_have_own_context(tmp_path):
    # nested defs are analyzed with their OWN host/guard flags: a
    # _host_ helper nested inside a compound statement stays clean, and
    # a guard inside a nested helper does NOT un-flag an unguarded
    # callback in the outer body
    proj = make_project(tmp_path, {
        "presto_tpu/ops/nested.py": """
            import jax
            import numpy as np
            import jax.numpy as jnp

            def kernel(lanes, mode):
                if mode:
                    def _host_pick(k):
                        # host helper defined inline: its numpy is legal
                        return np.argsort(k)
                else:
                    def _host_pick(k):
                        return np.lexsort(k)
                return jnp.take(lanes[0], jnp.asarray(_host_pick(lanes)))

            def outer(lanes):
                def guarded_helper(x):
                    if isinstance(x, jax.core.Tracer):
                        return None
                    return x
                # the helper's guard must not mark `outer` guarded
                return jax.pure_callback(guarded_helper, None, lanes[0])
        """,
    })
    found = run_passes(proj, [p_trace.PASS])
    assert rules(found) == ["tracing-host-callback"]
    assert found[0].context == "outer"


def test_passes_see_defs_inside_module_level_try(tmp_path):
    # serde.py defines its zstd helpers inside a module-level try — a
    # def wrapped in try/if at module or class level must still be
    # analyzed by every pass
    proj = make_project(tmp_path, {
        "presto_tpu/ops/trywrap.py": """
            import jax

            try:
                import zstandard

                def compressed_kernel(lanes):
                    return jax.pure_callback(_host, None, lanes[0])
            except ImportError:
                zstandard = None
        """,
        "presto_tpu/exec/trymem.py": """
            try:
                def reserve_path(pool, n):
                    held = pool.reserve(n)
                    return held
            except RuntimeError:
                pass
        """,
    })
    found = run_passes(proj, [p_trace.PASS, p_mem.PASS])
    rs = rules(found)
    assert "tracing-host-callback" in rs
    assert "memory-reserve-unpaired" in rs


# -- lock-discipline --------------------------------------------------------


def test_lock_flags_blocking_and_inversion(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/server/bad.py": """
            import queue
            import threading
            import time

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._out = threading.Lock()
                    self._q = queue.Queue()

                def a(self):
                    with self._lock:
                        time.sleep(0.5)
                        with self._out:
                            pass

                def b(self):
                    with self._out:
                        with self._lock:
                            pass

                def c(self):
                    with self._lock:
                        return self._q.get()
        """,
    })
    found = run_passes(proj, [p_locks.PASS])
    rs = rules(found)
    assert rs.count("lock-blocking-call") == 2  # sleep + queue.get
    assert "lock-order-inversion" in rs


def test_lock_inversion_multi_item_with(tmp_path):
    # `with a, b:` acquires left-to-right — the a->b edge must be
    # recorded exactly as in the nested form, or an opposite-order
    # nested acquisition elsewhere ships a real ABBA deadlock through
    # the gate
    proj = make_project(tmp_path, {
        "presto_tpu/server/multi.py": """
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a, self._b:
                        pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """,
    })
    found = run_passes(proj, [p_locks.PASS])
    assert rules(found) == ["lock-order-inversion"]


def test_lock_multi_item_with_consistent_order_is_clean(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/server/multi_ok.py": """
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a, self._b:
                        pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
        """,
    })
    assert run_passes(proj, [p_locks.PASS]) == []


def test_lock_cross_class_inversion_via_call_graph(tmp_path):
    # Buffers.put: _lock -> (call) Pool._cv; Killer (a Pool subclass,
    # so self._cv IS Pool._cv): _cv -> (call) Buffers._lock. The two
    # edges only exist through one level of calls + inheritance-resolved
    # lock identity — exactly the worker-pool/output-buffer shape.
    proj = make_project(tmp_path, {
        "presto_tpu/server/pools.py": """
            import threading

            class Buffers:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.pool = Pool()

                def drop(self):
                    with self._lock:
                        pass

                def put(self, data):
                    with self._lock:
                        self.pool.reserve(len(data))

            class Pool:
                def __init__(self):
                    self._cv = threading.Condition()

                def reserve(self, n):
                    with self._cv:
                        return n

            class Killer(Pool):
                def __init__(self):
                    super().__init__()
                    self.buffers = Buffers()

                def kill(self):
                    with self._cv:
                        self.buffers.drop()
        """,
    })
    found = run_passes(proj, [p_locks.PASS])
    assert "lock-order-inversion" in rules(found)


def test_lock_false_positive_guards(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/server/good.py": """
            import queue
            import threading
            import time

            class S:
                def __init__(self):
                    self._cond = threading.Condition()
                    self._q = queue.Queue()

                def waiter(self):
                    with self._cond:
                        # waiting on the HELD condition is the cv idiom
                        self._cond.wait(timeout=0.1)

                def timed_get(self):
                    with self._cond:
                        return self._q.get(timeout=1.0)

                def unlocked(self):
                    time.sleep(0.01)
                    return self._q.get()
        """,
    })
    assert run_passes(proj, [p_locks.PASS]) == []


def test_lock_deferred_callbacks_not_attributed_to_held_set(tmp_path):
    # a lambda or nested def BUILT under a lock runs later, without it:
    # neither its blocking calls nor phase-B propagation may attribute
    # them to the held set
    proj = make_project(tmp_path, {
        "presto_tpu/server/deferred.py": """
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = threading.Lock()
                    import queue
                    self._jobs = queue.Queue()

                def register(self):
                    with self._lock:
                        cb = lambda: self._jobs.get()
                        return cb

                def helper(self):
                    def drain():
                        return self._jobs.get()
                    return drain

                def caller(self):
                    with self._lock:
                        return self.helper()

                def control(self):
                    # same call made DIRECTLY under the lock: still bad
                    with self._lock:
                        return self._jobs.get()
        """,
    })
    found = run_passes(proj, [p_locks.PASS])
    assert rules(found) == ["lock-blocking-call"]
    assert found[0].context == "S.control"


def test_lock_blocking_inside_closure_is_flagged(tmp_path):
    # a nested def is deferred — but its OWN body is analyzed with a
    # fresh held set: a thread-target closure that blocks while holding
    # a lock is exactly the deadlock class this pass exists for
    proj = make_project(tmp_path, {
        "presto_tpu/server/closure.py": """
            import threading
            import urllib.request

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                def spawn(self):
                    def probe(u):
                        with self._lock:
                            return urllib.request.urlopen(u)
                    return threading.Thread(target=probe, args=("x",))
        """,
    })
    found = run_passes(proj, [p_locks.PASS])
    assert rules(found) == ["lock-blocking-call"]
    assert found[0].context == "S.spawn.probe"


def test_lock_queue_get_block_true_is_flagged(tmp_path):
    # block=True is the indefinite wait — only a literal block=False
    # (or a timeout) makes queue.get non-blocking
    proj = make_project(tmp_path, {
        "presto_tpu/server/blockkw.py": """
            import queue
            import threading

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def bad(self):
                    with self._lock:
                        return self._q.get(block=True)

                def ok(self):
                    with self._lock:
                        return self._q.get(block=False)
        """,
    })
    found = run_passes(proj, [p_locks.PASS])
    assert rules(found) == ["lock-blocking-call"]
    assert found[0].context == "S.bad"


def test_lock_result_needs_future_evidence(tmp_path):
    # .result() is only blocking on a FUTURE: a builder/parser method
    # that happens to be named result() must not fail the gate, while
    # submit()-sourced futures (attr, local, or chained) must
    proj = make_project(tmp_path, {
        "presto_tpu/server/futures.py": """
            import threading
            from concurrent.futures import ThreadPoolExecutor

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._pool = ThreadPoolExecutor(2)
                    self._fut = self._pool.submit(print)

                def attr_future(self):
                    with self._lock:
                        return self._fut.result()

                def local_future(self):
                    f = self._pool.submit(print)
                    with self._lock:
                        return f.result()

                def chained(self):
                    with self._lock:
                        return self._pool.submit(print).result()

                def not_a_future(self, builder):
                    with self._lock:
                        return builder.result()
        """,
    })
    found = run_passes(proj, [p_locks.PASS])
    assert rules(found) == ["lock-blocking-call"] * 3
    assert sorted(f.context for f in found) == [
        "S.attr_future", "S.chained", "S.local_future",
    ]


def test_lock_duplicate_class_names_resolve_same_file_first(tmp_path):
    # two files both define class Worker with a .reserve() method; only
    # one blocks. A caller in the blocking file must propagate into ITS
    # Worker; a caller in a THIRD file (ambiguous target) must stay
    # silent rather than pick whichever parsed first
    blocking = """
        import threading
        import queue

        class Worker:
            def __init__(self):
                self._q = queue.Queue()

            def reserve(self):
                return self._q.get()

        class Caller:
            def __init__(self):
                self._lock = threading.Lock()
                self.w = Worker()

            def go(self):
                with self._lock:
                    return self.w.reserve()
    """
    benign = """
        class Worker:
            def __init__(self):
                self.n = 0

            def reserve(self):
                return self.n
    """
    third = """
        import threading

        class Worker:
            def __init__(self):
                self.n = 1

            def reserve(self):
                return self.n

        class Other:
            def __init__(self):
                self._lock = threading.Lock()
                self.w = Worker()

            def go(self):
                with self._lock:
                    return self.w.reserve()
    """
    proj = make_project(tmp_path, {
        "presto_tpu/server/a_block.py": blocking,
        "presto_tpu/server/b_benign.py": benign,
        "presto_tpu/server/c_third.py": third,
    })
    found = run_passes(proj, [p_locks.PASS])
    # exactly one finding: a_block.Caller.go -> its own Worker.reserve.
    # c_third.Other.go resolves to the SAME-FILE benign Worker, clean.
    assert rules(found) == ["lock-blocking-call"]
    assert found[0].file == "presto_tpu/server/a_block.py"
    assert found[0].context == "Caller.go"


# -- exception-hygiene ------------------------------------------------------


def test_exception_swallow_and_silent(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/server/bad.py": """
            def swallow():
                try:
                    work()
                except Exception:
                    pass

            def silent():
                try:
                    return work()
                except Exception:
                    return 42
        """,
    })
    rs = rules(run_passes(proj, [p_exc.PASS]))
    assert rs == ["broad-except-silent", "broad-except-swallow"]


def test_exception_false_positive_guards(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/server/good.py": """
            def reraises():
                try:
                    work()
                except Exception as e:
                    raise RuntimeError("wrapped") from e

            def records(stats):
                try:
                    work()
                except Exception as e:
                    stats.record_failure(repr(e))

            def narrow():
                try:
                    work()
                except (ValueError, KeyError):
                    pass

            def reasoned():
                try:
                    work()
                except Exception:  # noqa: BLE001 — probing optional dep
                    return None

            def allowed():
                try:
                    work()
                # prestolint: allow(broad-except-swallow) -- dropping is
                # the documented contract here
                except Exception:
                    return None
        """,
    })
    assert run_passes(proj, [p_exc.PASS]) == []


# -- plan-exhaustiveness ----------------------------------------------------

_EXH_FILES = {
    "presto_tpu/plan/nodes.py": """
        import dataclasses

        @dataclasses.dataclass(frozen=True)
        class PlanNode:
            pass

        class Alpha(PlanNode):
            pass

        class Beta(PlanNode):
            pass

        def plan_tree_str(node):
            if isinstance(node, Alpha):
                return "alpha"
            {beta_branch}
            return ""
    """,
    "presto_tpu/plan/fragment.py": """
        class Fragmenter:
            def _v_alpha(self, n):
                return n

            def _v_beta(self, n):
                return n
    """,
    "presto_tpu/exec/executor.py": """
        class Executor:
            def _exec_alpha(self, n):
                return n
            {exec_beta}
    """,
    "presto_tpu/expr/ir.py": """
        class RowExpression:
            pass

        class Leaf(RowExpression):
            pass
    """,
    "presto_tpu/expr/compiler.py": """
        def evaluate(expr, page):
            if isinstance(expr, Leaf):
                return page
            raise TypeError(expr)
    """,
}


def _exh_project(tmp_path, *, beta_branch, exec_beta):
    files = {
        rel: text.replace("{beta_branch}", beta_branch).replace(
            "{exec_beta}", exec_beta
        )
        for rel, text in _EXH_FILES.items()
    }
    return make_project(tmp_path, files)


def test_exhaustive_flags_missing_dispatch(tmp_path):
    proj = _exh_project(tmp_path, beta_branch="", exec_beta="")
    found = run_passes(proj, [p_exh.PASS])
    msgs = [f.message for f in found]
    assert rules(found) == ["plan-dispatch-missing"] * 2
    assert any("_exec_beta" in m for m in msgs)
    assert any("plan_tree_str never mentions Beta" in m for m in msgs)


def test_exhaustive_clean_when_all_handled(tmp_path):
    proj = _exh_project(
        tmp_path,
        beta_branch="if isinstance(node, Beta):\n                return 'beta'",
        exec_beta="""
            def _exec_beta(self, n):
                return n
        """,
    )
    assert run_passes(proj, [p_exh.PASS]) == []


def test_exhaustive_real_tree_surfaces_are_complete():
    """The real executor/fragmenter/EXPLAIN/evaluate surfaces cover every
    node class — a NEW node class without handlers must fail this."""
    proj = load_project(REPO_ROOT)
    assert run_passes(proj, [p_exh.PASS]) == []


# -- memory-accounting ------------------------------------------------------


def test_memory_unpaired_and_no_finally(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/bad.py": """
            class A:
                def leak(self):
                    self.pool.reserve(100, "x")
                    return work()

            class B:
                def racy(self):
                    nb = 10
                    self.pool.reserve(nb, "x")
                    work()
                    self.pool.free(nb)
        """,
    })
    rs = rules(run_passes(proj, [p_mem.PASS]))
    assert rs == ["memory-reserve-no-finally", "memory-reserve-unpaired"]


def test_memory_false_positive_guards(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/good.py": """
            class Guarded:
                def ok(self):
                    nb = 10
                    self.pool.reserve(nb, "x")
                    try:
                        return work()
                    finally:
                        self.pool.free(nb)

            class Transfer:
                def build(self):
                    held = self.pool.reserve(100, "build")
                    return held  # ownership moves to the consumer

                def consume(self, held):
                    try:
                        work()
                    finally:
                        self.pool.free(held)

            class NotAPool:
                def other(self):
                    self.slots.reserve(3)
        """,
    })
    assert run_passes(proj, [p_mem.PASS]) == []


# -- guarded-fields (race inference) ----------------------------------------


def test_races_flags_mutation_call_and_publication(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/bad.py": """
            import threading

            class Pool:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []
                    self.count = 0

                def add(self, x):
                    with self._lock:
                        self.items.append(x)
                        self.count += 1

                def drain(self):
                    with self._lock:
                        out = list(self.items)
                        self.items.clear()
                        self.count = 0
                    return out

                def racy_assign(self):
                    self.count = 99

                def racy_call(self, x):
                    self.items.append(x)

                def racy_publish(self, pool):
                    pool.submit(work, self.items)

                def racy_deferred(self):
                    with self._lock:
                        def cb():
                            self.items.pop()
                    return cb
        """,
    })
    found = run_passes(proj, [p_races.PASS])
    assert rules(found) == ["race-unguarded-mutation"] * 4
    assert sorted(f.context for f in found) == [
        "Pool.racy_assign", "Pool.racy_call",
        "Pool.racy_deferred.cb", "Pool.racy_publish",
    ]


def test_races_false_positive_guards(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/good.py": """
            import threading

            class Clean:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []     # __init__ is happens-before
                    self.hits = 0

                def add(self, x):
                    with self._lock:
                        self.items.append(x)
                        self.hits += 1

                def drain(self):
                    with self._lock:
                        self.items.clear()
                        self.hits += 1

                def read_only(self):
                    return len(self.items)   # torn read: not flagged

                def flush(self):
                    with self._lock:
                        self._flush_locked()

                def compact(self):
                    with self._lock:
                        self._flush_locked()

                def _flush_locked(self):
                    # every in-class call site holds _lock: assumed held
                    self.items.pop()

                def reset_for_tests(self):
                    # prestolint: unguarded(items) -- single-threaded test hook
                    self.items.clear()

            class Ambiguous:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()
                    self.x = 0

                def m1(self):
                    with self._a:
                        with self._b:
                            self.x += 1

                def m2(self):
                    with self._a:
                        with self._b:
                            self.x += 1

                def m3(self):
                    self.x = 5   # tie between _a and _b: refuse to infer
        """,
    })
    assert run_passes(proj, [p_races.PASS]) == []


def test_races_escaped_helper_disables_propagation(tmp_path):
    # handing `self.m` to a thread voids the all-call-sites-hold-L proof
    proj = make_project(tmp_path, {
        "presto_tpu/exec/esc.py": """
            import threading

            class Esc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def a(self):
                    with self._lock:
                        self.items.append(1)
                        self._bump()

                def b(self):
                    with self._lock:
                        self.items.append(2)
                        self._bump()

                def spawn(self, ex):
                    ex.submit(self._bump)

                def _bump(self):
                    self.items.pop()
        """,
    })
    found = run_passes(proj, [p_races.PASS])
    assert rules(found) == ["race-unguarded-mutation"]
    assert found[0].context == "Esc._bump"


def test_races_cross_object_write_needs_owners_lock(tmp_path):
    # the cluster.py bug shape: another class writes owner.stats.<field>
    # without taking the owner's lock — holding it the chained way
    # (`with self.owner._lock:`) is clean
    proj = make_project(tmp_path, {
        "presto_tpu/exec/owner.py": """
            import threading

            class Stats:
                pass

            class Owner:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = Stats()

                def poll(self):
                    with self._lock:
                        self.stats.polls = 1

                def fail(self):
                    with self._lock:
                        self.stats.failures = 1

            class GoodUser:
                def __init__(self):
                    self.owner = Owner()

                def publish(self, snap):
                    with self.owner._lock:
                        self.owner.stats.caches = snap

            class BadUser:
                def __init__(self):
                    self.owner = Owner()

                def publish(self, snap):
                    self.owner.stats.caches = snap

                def ok_method_call(self):
                    self.owner.poll()   # method synchronizes internally
        """,
    })
    found = run_passes(proj, [p_races.PASS])
    assert rules(found) == ["race-unguarded-mutation"]
    assert found[0].context == "BadUser.publish"
    assert "Owner._lock" in found[0].message


def test_races_real_tree_is_clean():
    """The burndown acceptance: zero unguarded mutations on the real
    tree (cluster.py's scheduler.stats.caches write now goes through
    HttpScheduler.record_caches, which takes the lock)."""
    proj = load_project(REPO_ROOT)
    assert run_passes(proj, [p_races.PASS]) == []


# -- knob-consistency -------------------------------------------------------


def test_knobs_multi_parse_undocumented_and_stale(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/a.py": """
            import os
            A = float(os.environ.get("PRESTO_TPU_KNOB_A", "1"))
            B = os.environ.get("PRESTO_TPU_KNOB_OTHER", "x")
        """,
        "presto_tpu/b.py": """
            import os
            A2 = float(os.environ.get("PRESTO_TPU_KNOB_A", "2"))
        """,
        "docs/tuning.md": """
            `PRESTO_TPU_KNOB_A` (default 1) does things.
            `PRESTO_TPU_KNOB_GONE` was removed long ago.
        """,
    })
    found = run_passes(proj, [p_knobs.PASS])
    assert rules(found) == [
        "knob-multi-parse", "knob-stale-doc", "knob-undocumented",
    ]
    by_rule = {f.rule: f for f in found}
    assert "PRESTO_TPU_KNOB_A" in by_rule["knob-multi-parse"].message
    assert "PRESTO_TPU_KNOB_OTHER" in by_rule["knob-undocumented"].message
    assert "PRESTO_TPU_KNOB_GONE" in by_rule["knob-stale-doc"].message


def test_knobs_near_miss_both_directions(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/a.py": """
            import os
            # one edit from the documented PRESTO_TPU_STRIDE
            X = os.environ.get("PRESTO_TPU_STRIDES", "1")
            Y = os.environ.get("PRESTO_TPU_WIDTH", "2")
        """,
        "docs/tuning.md": """
            `PRESTO_TPU_STRIDE` picks the stride.
            `PRESTO_TPU_WIDTHS` picks the widths.
        """,
    })
    found = run_passes(proj, [p_knobs.PASS])
    assert rules(found) == ["knob-near-miss"] * 2


def test_knobs_false_positive_guards(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/a.py": """
            import os

            # the single parse site, documented: clean
            TUNED = int(os.environ.get("PRESTO_TPU_TUNED", "4"))

            def save_restore():
                # probes (no default) and writes are NOT parse sites
                prev = os.environ.get("PRESTO_TPU_TUNED")
                os.environ["PRESTO_TPU_TUNED"] = "8"
                if "PRESTO_TPU_TUNED" in os.environ:
                    os.environ.pop("PRESTO_TPU_TUNED", None)
        """,
        "docs/tuning.md": """
            `PRESTO_TPU_TUNED` (default 4).
            The `PRESTO_TPU_FAMILY_*` knobs share a prefix (wildcard —
            not a knob name, must not count as documented-but-unread).
        """,
    })
    assert run_passes(proj, [p_knobs.PASS]) == []


def test_knobs_env_helper_counts_as_parse_site(tmp_path):
    # parsing through a module-level helper is still one parse site per
    # knob — two helper calls for the SAME knob is multi-parse
    proj = make_project(tmp_path, {
        "presto_tpu/a.py": """
            import os

            def _env_int(name, default):
                return int(os.environ.get(name, "") or default)

            A = _env_int("PRESTO_TPU_HELPER_KNOB", 4)
        """,
        "presto_tpu/b.py": """
            from .a import _env_int

            B = _env_int("PRESTO_TPU_HELPER_KNOB", 8)
        """,
        "docs/tuning.md": """
            `PRESTO_TPU_HELPER_KNOB` (default 4).
        """,
    })
    found = run_passes(proj, [p_knobs.PASS])
    assert rules(found) == ["knob-multi-parse"]


# -- observability-coverage -------------------------------------------------


def test_coverage_breaker_without_fallback_or_doc(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/k.py": """
            from .breaker import BREAKERS

            def run(x):
                BREAKERS.allow("dark_kernel")   # decision ignored
                out = kernel(x)
                BREAKERS.record_success("dark_kernel")
                return out

            def run2(x):
                # record_* only, never even asks allow()
                BREAKERS.record_failure("log_only", "boom")
                return kernel(x)
        """,
        "docs/fault-tolerance.md": """
            | breaker | fallback |
            |---|---|
            (neither name is here)
        """,
    })
    found = run_passes(proj, [p_cov.PASS])
    assert rules(found) == [
        "breaker-no-fallback", "breaker-no-fallback",
        "breaker-undocumented", "breaker-undocumented",
    ]


def test_coverage_breaker_false_positive_guards(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/k.py": """
            from .breaker import BREAKERS

            def gated(x):
                if BREAKERS.allow("good_kernel"):
                    return kernel(x)
                return fallback(x)

            def assigned(x):
                ok = BREAKERS.allow("assigned_kernel")
                return kernel(x) if ok else fallback(x)

            def wrapped(x):
                return _kernel_guarded("wrapped_kernel", kernel, fallback, x)
        """,
        "docs/fault-tolerance.md": """
            | breaker | fallback |
            |---|---|
            | `good_kernel` | XLA composition |
            | `assigned_kernel` | XLA composition |
            | `wrapped_kernel` | legacy kernel |
        """,
    })
    assert run_passes(proj, [p_cov.PASS]) == []


def test_coverage_stats_class_must_reach_a_surface(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/m.py": """
            class DarkStats:
                def __init__(self):
                    self.hits = 0

                def snapshot(self):
                    return {"hits": self.hits}

            class LitStats:
                def __init__(self):
                    self.hits = 0

                def snapshot(self):
                    return {"hits": self.hits}

            LIT = LitStats()

            def snapshot_all():
                return {"lit": LIT.snapshot()}

            def export_lit(stats: "LitStats"):
                pass
        """,
    })
    found = run_passes(proj, [p_cov.PASS])
    assert rules(found) == ["stats-not-snapshotted"]
    assert found[0].context == "DarkStats"


def test_coverage_snapshotted_stats_must_also_export(tmp_path):
    """TP: a Stats class that reaches a snapshot surface but never an
    export/metrics-named function ships dark on /v1/metrics."""
    proj = make_project(tmp_path, {
        "presto_tpu/exec/m.py": """
            class SiloStats:
                def snapshot(self):
                    return {}

            SILO = SiloStats()

            def snapshot_all():
                return {"silo": SILO.snapshot()}
        """,
    })
    found = run_passes(proj, [p_cov.PASS])
    assert rules(found) == ["stats-not-exported"]
    assert found[0].context == "SiloStats"


def test_coverage_exported_stats_clean(tmp_path):
    """FP guard: a quoted parameter annotation or a bare class reference
    inside an export/metrics-named function counts as metrics reach."""
    proj = make_project(tmp_path, {
        "presto_tpu/exec/m.py": """
            class AnnStats:
                def snapshot(self):
                    return {}

            class RefStats:
                def snapshot(self):
                    return {}

            ANN = AnnStats()
            REF = RefStats()

            def snapshot_all():
                return {"a": ANN.snapshot(), "r": REF.snapshot()}

            def export_ann_stats(stats: "AnnStats"):
                pass

            def _metrics_ref_producer():
                return RefStats
        """,
    })
    assert run_passes(proj, [p_cov.PASS]) == []


def test_coverage_docstring_mention_is_not_an_export(tmp_path):
    """TP guard: a Stats class named only in an export-named function's
    docstring (or any non-annotation str constant) has NOT reached the
    metrics plane — only annotation positions count for str constants."""
    proj = make_project(tmp_path, {
        "presto_tpu/exec/m.py": """
            class DocStats:
                def snapshot(self):
                    return {}

            DOC = DocStats()

            def snapshot_all():
                return {"d": DOC.snapshot()}

            def export_other_things():
                '''Folds counters; see DocStats for the snapshot shape.'''
                help = "unrelated to DocStats"
                return help
        """,
    })
    found = run_passes(proj, [p_cov.PASS])
    assert rules(found) == ["stats-not-exported"]
    assert found[0].context == "DocStats"


def test_coverage_qcache_global_must_be_in_snapshot_all(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/exec/qcache.py": """
            class LRUCache:
                def snapshot(self):
                    return {}

            SEEN_CACHE = LRUCache()
            DARK_CACHE = LRUCache()

            def snapshot_all():
                return {"seen": SEEN_CACHE.snapshot()}
        """,
    })
    found = run_passes(proj, [p_cov.PASS])
    assert rules(found) == ["cache-not-snapshotted"]
    assert "DARK_CACHE" in found[0].message


def test_coverage_and_knobs_real_tree_clean():
    """Burndown acceptance for the doc/observability rules: every knob
    documented with one parse site, every breaker gated + cataloged,
    every Stats/Cache wired to a snapshot surface."""
    proj = load_project(REPO_ROOT)
    assert run_passes(proj, [p_knobs.PASS, p_cov.PASS]) == []


# -- suppression + baseline -------------------------------------------------


def test_allow_comment_suppresses(tmp_path):
    proj = make_project(tmp_path, {
        "presto_tpu/server/s.py": """
            def swallow():
                try:
                    work()
                # prestolint: allow(broad-except-swallow) -- reason here
                except Exception:
                    pass
        """,
    })
    assert run_passes(proj, [p_exc.PASS]) == []


def test_baseline_round_trip(tmp_path):
    files = {
        "presto_tpu/server/old.py": """
            def old_swallow():
                try:
                    work()
                except Exception:
                    pass
        """,
    }
    proj = make_project(tmp_path, files)
    findings = run_passes(proj, [p_exc.PASS])
    assert len(findings) == 1

    bl_path = tmp_path / "baseline.json"
    save_baseline(bl_path, findings)
    baseline = load_baseline(bl_path)
    assert len(baseline) == 1

    # baselined -> check passes
    res = evaluate_against_baseline(findings, baseline)
    assert res.ok and len(res.baselined) == 1 and not res.expired

    # NEW finding in another file -> only IT fails
    (tmp_path / "presto_tpu/server/new.py").write_text(
        textwrap.dedent("""
            def new_swallow():
                try:
                    work()
                except Exception:
                    pass
        """)
    )
    proj2 = load_project(tmp_path)
    f2 = run_passes(proj2, [p_exc.PASS])
    res2 = evaluate_against_baseline(f2, load_baseline(bl_path))
    assert not res2.ok
    assert [f.file for f in res2.new] == ["presto_tpu/server/new.py"]
    assert [f.file for f in res2.baselined] == ["presto_tpu/server/old.py"]

    # fix the OLD file -> its entry expires; update prunes it
    (tmp_path / "presto_tpu/server/old.py").write_text("def old():\n    pass\n")
    proj3 = load_project(tmp_path)
    f3 = run_passes(proj3, [p_exc.PASS])
    res3 = evaluate_against_baseline(f3, load_baseline(bl_path))
    assert len(res3.expired) == 1
    save_baseline(bl_path, f3)
    assert len(load_baseline(bl_path)) == 1  # only new.py's finding


def test_baseline_fingerprints_survive_line_drift(tmp_path):
    files = {
        "presto_tpu/server/s.py": """
            def f():
                try:
                    work()
                except Exception:
                    pass
        """,
    }
    proj = make_project(tmp_path, files)
    findings = run_passes(proj, [p_exc.PASS])
    bl_path = tmp_path / "baseline.json"
    save_baseline(bl_path, findings)

    # prepend unrelated code: lines shift, fingerprint must not
    src = (tmp_path / "presto_tpu/server/s.py").read_text()
    (tmp_path / "presto_tpu/server/s.py").write_text(
        "import os\n\nCONST = 1\n\n" + src
    )
    proj2 = load_project(tmp_path)
    res = evaluate_against_baseline(
        run_passes(proj2, [p_exc.PASS]), load_baseline(bl_path)
    )
    assert res.ok and not res.expired


# -- the tier-1 gate --------------------------------------------------------


def _cpu_seconds(fn):
    """(CPU seconds of this process, result) of one call, garbage of
    earlier tests collected first."""
    gc.collect()
    t0 = time.process_time()
    out = fn()
    return time.process_time() - t0, out


def test_repo_is_clean_and_fast():
    """THE gate: zero un-baselined findings on the real tree, in well
    under the 10s budget. A new finding means: fix it, allow() it with a
    reason, or (for pre-existing classes) re-baseline deliberately.
    The budget is CPU seconds of the linter, which is single-threaded and
    starts no process. A busy machine stretches those as it does wall
    seconds (6.4 s idle, 10.5-11.1 s beside five tier-1 workers that keep
    every hardware thread busy, 10.8-12.4 s alone on a slow day of the
    host), so a check over 10 s is held to the cost of parsing the same
    tree just then: it takes 6.5-7.5 parses on an idle and on a loaded
    machine alike, and 11 parses is the headroom 10 s leaves over 6.4."""
    dt, result = _cpu_seconds(lambda: run_check(REPO_ROOT))
    assert result.ok, "NEW prestolint findings:\n" + "\n".join(
        f.render() for f in result.new
    )
    if dt >= 10.0:
        parse, _ = _cpu_seconds(lambda: load_project(REPO_ROOT))
        assert dt < 11.0 * parse, (
            f"prestolint took {dt:.1f}s of CPU (budget 10s) and "
            f"{dt / parse:.1f} parses of the tree (budget 11)"
        )


def test_all_eight_passes_registered():
    assert set(PASSES_BY_NAME) == {
        "tracing-safety", "lock-discipline", "guarded-fields",
        "exception-hygiene", "plan-exhaustiveness", "memory-accounting",
        "knob-consistency", "observability-coverage",
    }
