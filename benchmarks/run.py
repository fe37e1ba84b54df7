"""presto-tpu benchmark: one cell, one run.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it starts the configuration's deployment
(`deployments/<serve>.py`: catalog, session, server over HTTP) and the
traffic mix's loop (`loops/<loop>.py`) sends the mix's statements.
Set-up (imports, catalog, server, warming every statement the window can
send) is timed apart; nothing compiles inside the window. Once the window has
closed, every answer it returned is compared with the plain numpy
reference. The last line of stdout is the result object BENCHMARK.json's
contract describes; a failed look for the chip prints none and exits
non-zero.

Nothing about a cell, a statement or a metric is in this file: see
README.md for the files each lives in.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(HERE, "reference"), HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare  # noqa: E402
import datagen  # noqa: E402
import stats  # noqa: E402
import tracered  # noqa: E402
from traffic import Mix, load_json  # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    """`<kind>/<name>.py` under this directory, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileCounter:
    """XLA backend compiles (a load from the persistent cache counts too:
    either way a program was not yet in this process) through JAX's
    monitoring hooks. An in-memory jit hit fires none."""

    def __init__(self):
        import jax

        self.compiles = self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Run:
    """What one run measured; the metric readers' only argument."""

    def __init__(self, cell, config, bench):
        self.cell = cell
        self.config = config
        self.bench = bench
        self.records = []          # one dict per statement of the window
        self.window_s = None       # first send to last answer
        self.setup_s = None
        self.window_compiles = None
        self.trace = None          # tracered.reduce()'s result, traced runs
        self.device_kind = None
        self.platform = None

    def metric_names(self, group: str):
        """The metrics of `group` BENCHMARK.json asks of this cell."""
        return [
            m["name"] for m in self.bench[group]
            if "workloads" not in m or self.cell["name"] in m["workloads"]
        ]


def newest_spans():
    """{name: (start, end)} of the statement that just returned: with
    one client it is the newest trace in the program's store."""
    from presto_tpu.obs import span as obs_span

    traces = obs_span.TRACES.recent()
    if not traces:
        return {}
    return {
        s.name: (s.start, s.end)
        for s in traces[-1].spans()
        if s.end is not None and s.name in ("query", "plan", "execute")
    }


def send(client, st, i, annotate):
    """One statement through the client; the record the metrics read."""
    sql = st.sql(i)
    rec = {"id": st.id, "set": i, "ok": False, "epoch_ns": time.time_ns()}
    t0 = time.perf_counter()
    try:
        with annotate(f"bench.stmt.{st.id}"):
            cols, rows = client.execute(sql)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 — a failed statement is counted
        text = f"{type(e).__name__}: {e}"
        # a server-side traceback says what failed in its last lines
        rec["error"] = text if len(text) <= 1500 else "... " + text[-1500:]
        cols, rows = [], []
    rec["t0"], rec["t1"] = t0, time.perf_counter()
    rec["wall_ms"] = (rec["t1"] - t0) * 1e3
    rec["spans"] = newest_spans() if rec["ok"] else {}
    rec["answer"] = compare.canonical(cols, rows) if rec["ok"] else None
    return rec


def warm_up(mix, client, counter, passes: int):
    """Every statement the window can send, `passes` times over and on
    until a whole pass compiles nothing (an adaptive choice may move
    between a statement's first runs: PERF.md)."""
    done = 0
    while True:
        c0 = counter.compiles
        for st, i in mix.every():
            rec = send(client, st, i, no_annotation)
            if not rec["ok"]:
                raise SystemExit(f"warm-up of {st.id} failed: {rec['error']}")
        done += 1
        new = counter.compiles - c0
        say(f"warm-up pass {done}: {new} programs compiled or loaded")
        if done >= passes and new == 0:
            return
        if done >= passes + 3:
            say("warm-up: still compiling after 3 extra passes; going on")
            return


def no_annotation(_name):
    return nullcontext()


class Tracer:
    """The profiler over the window's first statements: on from the
    start of a traced run until `spec["seconds"]` have passed and
    `spec["min_statements"]` are in, which the loop reports through
    `due`. An untraced run's tracer does nothing."""

    def __init__(self, spec, trace_dir):
        self.spec, self.dir = spec, trace_dir
        self.on = False
        self.traced = 0  # statements sent while the trace ran

    annotate = staticmethod(no_annotation)

    def start(self):
        if self.dir is None:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.on, self.annotate = True, jax.profiler.TraceAnnotation

    def due(self, elapsed: float, statements: int):
        if self.on and (
            elapsed >= self.spec["seconds"]
            and statements >= self.spec["min_statements"]
        ):
            self.stop(statements)

    def stop(self, statements: int):
        if self.on:
            import jax

            jax.profiler.stop_trace()
            self.on, self.annotate = False, no_annotation
            self.traced = statements


def window_notes(run) -> dict:
    """What a reader of one odd run wants beside the metrics: how the
    window's two halves compare, each class's mean wall, and the longest
    statements with the second of the window they began in."""
    recs = run.records
    t_open = recs[0]["t0"]
    mid = t_open + run.window_s / 2
    halves = [
        [r for r in recs if r["t1"] <= mid], [r for r in recs if r["t1"] > mid]
    ]
    return {
        "seconds": run.window_s, "statements": len(recs),
        "compiles": run.window_compiles, "setup_s": run.setup_s,
        "half_stmt_ms": [
            (h[-1]["t1"] - h[0]["t0"]) * 1e3 / len(h) if h else None
            for h in halves
        ],
        "class_mean_ms": stats.class_means(recs),
        "longest": [
            [r["id"], r["wall_ms"], r["t0"] - t_open]
            for r in sorted(recs, key=lambda r: -r["wall_ms"])[:3]
        ],
    }


def reference_modules(mix) -> dict:
    return {st.id: load_module("reference", st.id) for st in mix.statements}


def reference_tables(mods, sf: float) -> dict:
    """The columns the references name, each table made once, whole."""
    need = {}
    for mod in mods.values():
        for table, cols in mod.TABLES.items():
            need.setdefault(table, set()).update(cols)
    return {t: datagen.columns(t, sf, sorted(cols)) for t, cols in need.items()}


def references(mix, config, used):
    """{(id, set): (rows, order_by)} from the plain references, for the
    parameter sets the window used."""
    mods = reference_modules(mix)
    tables = reference_tables(mods, config["sf"])
    jobs = [
        (st, i) for st in mix.statements
        for i in range(len(st.param_sets)) if (st.id, i) in used
    ]

    def answer(job):
        st, i = job
        return mods[st.id].answer(tables, st.param_sets[i]), mods[st.id].ORDER_BY

    # a few at a time: numpy releases the interpreter lock, and each
    # answer holds several arrays as long as the table
    with ThreadPoolExecutor(max_workers=4) as pool:
        return {
            (st.id, i): ref for (st, i), ref in zip(jobs, pool.map(answer, jobs))
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_json("workloads", args.workload)
    cell["name"] = args.workload
    config = load_json("configs", cell["config"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not config.get("rehearsal"):
        say(
            f"{cell['config']} needs a TPU; JAX found "
            f"{devices[0].platform!r} (only a config marked rehearsal "
            "runs elsewhere)"
        )
        return 3
    if len(devices) < int(config["chips"]):
        say(f"{cell['config']} needs {config['chips']} chip(s), JAX sees "
            f"{len(devices)}")
        return 3

    import presto_tpu  # noqa: F401  (x64 on; compile cache at
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache)
    from presto_tpu.exec.qcache import enable_persistent_compile_cache

    counter = CompileCounter()
    say(f"device {devices[0].device_kind} x{len(devices)}; compile cache at "
        f"{enable_persistent_compile_cache()}")

    run = Run(cell, config, bench)
    run.platform, run.device_kind = devices[0].platform, devices[0].device_kind
    mix = Mix(cell["traffic"], args.seed)
    deployment = load_module("deployments", config["serve"]).start(config)
    trace_dir = None
    try:
        warm_up(mix, deployment.client(), counter,
                int(mix.spec.get("warmup_passes", 1)))
        say(f"set-up: {counter.compiles} programs, {counter.compile_s:.1f} s "
            f"compiling or loading; persistent cache hits {counter.hits} "
            f"misses {counter.misses}")
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="presto_bench_trace_")
        tracer = Tracer(mix.spec.get("trace"), trace_dir)
        c0 = counter.compiles
        run.setup_s = time.perf_counter() - T_PROCESS_START
        tracer.start()
        try:
            run.records = load_module("loops", mix.spec["loop"]).drive(
                mix, deployment.client, send, args.seconds, tracer
            )
        finally:
            tracer.stop(len(run.records))
        run.window_s = run.records[-1]["t1"] - run.records[0]["t0"]
        run.window_compiles = counter.compiles - c0
        stats = devices[0].memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        if trace_dir is not None:
            run.trace = tracered.reduce(
                tracered.load(trace_dir), run.records[:tracer.traced]
            )
    finally:
        deployment.stop()
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # correctness: every answer of the window against the plain reference
    ok_records = [r for r in run.records if r["ok"]]
    t_ref = time.perf_counter()
    refs = references(mix, config, {(r["id"], r["set"]) for r in ok_records})
    correct, checks = compare.verdict(
        [((r["id"], r["set"]), r["answer"]) for r in ok_records],
        refs,
        len(run.records) - len(ok_records),
    )
    say(f"reference: {len(refs)} answers in "
        f"{time.perf_counter() - t_ref:.1f} s")
    group = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in bench[group]}
    metrics = {}
    for name in run.metric_names(group):
        value = load_module(
            "layer_metrics" if args.trace else "end_to_end", name
        ).compute(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}

    device = {
        "platform": run.platform, "kind": run.device_kind,
        "count": len(devices), "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": len(run.records),
        "failed": sum(
            checks[k]["value"]
            for k in ("failed_statements", "wrong_answers", "unanswered_refs")
        ),
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None and run.trace.get("busy_s") is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"][:10],
            "idle_gaps": run.trace["idle_gaps"][:10],
        }
    result["window"] = window_notes(run)
    result["checks"] = checks
    for r in run.records:
        if not r["ok"]:
            say(f"failed statement {r['id']}[{r['set']}]: {r['error']}")
    for name, c in checks.items():
        say(f"check {name}: " + " ".join(f"{k}={v}" for k, v in c.items()))
    say(f"correct={correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
