"""A cell's statements by operator: PERF.md section 5's tables.

    python3 tools/operator_table.py --workload <cell> [--seed N]
        [--repeats 2] [--profile DIR]

Starts the cell's deployment as `benchmarks/run.py` does (the same
configuration, catalog, session and server), warms every statement of
the mix, then serves every parameter set `--repeats` times OUTSIDE the
harness's loop and prints, for each statement class, a markdown table of
its operator spans (means over the statements served): the span's SELF
wall (its wall minus its children's), its own `host_reads` and the ms
the host stood in them, its DEVICE-SIDE span (`Trace.device_spans`, from
the ready stamps: docs/observability.md) and the attributes worth a
column. A streamed scan's row carries the link's stamps. The figures
are this process's, on whatever JAX runs on: the header names it, and
only a TPU's are device numbers.

`--profile DIR` also serves every parameter set once more, each inside
a `jax.profiler` session of its own, and holds the stamps against the
profile: each
`device` stamp against the end of the last program launched under the
operator's `presto.<name>` annotation (linked through the launch's
`run_id`, else by order), each `link` stamp (the scan's last batch's)
against the end of the last host-to-device copy done by then. What it finds goes to stdout; the events it
read go to DIR/events.<class>.<set>.json.gz so that the arithmetic can be
done again without the chip (`--events FILE`).
"""

import argparse
import glob
import gzip
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (os.path.join(BENCH, "reference"), BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import tracered  # noqa: E402  (benchmarks/: a program's name in a profile)

NOTED = (
    "strategy", "partial_strategy", "programs", "compact",
    "compact_capacity", "dyn_pruned", "dyn_strategy", "est_rows",
    "out_capacity", "probe_rows", "build_rows", "out_rows", "groups",
    "max_groups", "presorted", "retries", "batches", "uploads", "upload_s",
    "link_idle_s", "inflight_peak_bytes", "scan_s", "ready_error",
)
FOLDED = ("batches", "scan_s")


def say(msg):
    print(msg, file=sys.stderr, flush=True)


def serve(client, sql):
    """(client wall s, the trace the statement left)."""
    from presto_tpu.obs import span as obs_span

    t0 = time.perf_counter()
    client.execute(sql)
    wall = time.perf_counter() - t0
    return wall, obs_span.TRACES.recent()[-1]


def rows_of(trace):
    """{(name, pos): {column: value}} of one statement's tree."""
    self_s = {s.span_id: v for s, v in trace.exclusive_walls()}
    reads = {s.span_id: v for s, v in trace.exclusive("host_reads")}
    waits = {s.span_id: v for s, v in trace.exclusive("host_read_wait_s")}
    device = {s.span_id: v for s, v in trace.device_spans()}
    out = {}
    for s in trace.spans():
        key = (s.name, s.attrs.get("pos", ""))
        row = out.setdefault(key, {
            "self_ms": 0.0, "reads": 0, "wait_ms": 0.0, "device_ms": None,
            "notes": {},
        })
        row["self_ms"] += self_s[s.span_id] * 1e3
        row["reads"] += reads[s.span_id]
        row["wait_ms"] += waits[s.span_id] * 1e3
        if s.span_id in device:
            row["device_ms"] = (row["device_ms"] or 0.0) + (
                device[s.span_id] * 1e3
            )
        row["notes"].update({
            k: s.attrs[k] for k in NOTED if k in s.attrs
            # a scan's counters fold upward: shown where they were booked
            and (k not in FOLDED or s.name == "TableScan")
        })
    return out


def fmt(values, digits=1):
    """Mean of the values that are there; a range where they differ."""
    values = [
        str(v).lower() if isinstance(v, bool) else v
        for v in values if v is not None
    ]
    if not values:
        return ""
    if all(isinstance(v, str) for v in values):
        return " / ".join(sorted(set(values)))
    lo, hi = min(values), max(values)
    if lo == hi:
        return f"{lo:,.{digits}f}" if isinstance(lo, float) else f"{lo:,}"
    mean = sum(values) / len(values)
    return f"{mean:,.{digits}f} [{lo:,.{digits}f}-{hi:,.{digits}f}]"


def table(cls, served):
    """The class's markdown table from [(wall s, trace)]."""
    per = [rows_of(trace) for _wall, trace in served]
    keys = sorted(
        {k for rows in per for k in rows},
        key=lambda k: -statistics.mean(
            rows[k]["self_ms"] for rows in per if k in rows
        ),
    )
    lines = [
        f"**{cls}**: {len(served)} statements, client wall "
        f"{fmt([w * 1e3 for w, _ in served])} ms; device-side spans sum "
        f"{fmt([sum(v * 1e3 for _s, v in t.device_spans()) for _w, t in served])}"
        " ms",
        "",
        "| span (`pos`) | self ms | reads (wait ms) | device-side ms "
        "| attributes |",
        "| --- | --- | --- | --- | --- |",
    ]
    for key in keys:
        rows = [r[key] for r in per if key in r]
        notes = {}
        for r in rows:
            for k, v in r["notes"].items():
                notes.setdefault(k, []).append(v)
        name, pos = key
        lines.append(
            f"| `{name}`" + (f" (`{pos}`)" if pos else "")
            + f" | {fmt([r['self_ms'] for r in rows])}"
            + f" | {fmt([r['reads'] for r in rows])}"
            + f" ({fmt([r['wait_ms'] for r in rows])})"
            + f" | {fmt([r['device_ms'] for r in rows])}"
            + " | " + ", ".join(
                f"`{k}` {fmt(v, 4 if k.endswith('_s') else 1)}"
                for k, v in notes.items()
            ) + " |"
        )
    return "\n".join(lines)


# -- the stamps against a profile --------------------------------------------


def is_upload(name: str) -> bool:
    """The END of a host-to-device copy, by the name the TPU runtime
    gives it (one event a column of a batch, on the worker that hears
    of the copy's completion)."""
    return name.startswith("tpu::System::TransferToDevice=>IssueEvent=>Done")


def read_events(trace_dir):
    """What the comparison needs of the newest profile under
    `trace_dir`: {"host": [[line, name, start_ns, dur_ns, run_id]],
    "device": [[line, name, start_ns, dur_ns, run_id]], "lines":
    {plane/line: {name prefix: count}}} (the last for a reader of a
    profile whose names differ)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))[-1]
    out = {"host": [], "device": [], "lines": {}}
    for plane in ProfileData.from_file(path).planes:
        side = "device" if plane.name.startswith("/device:") else "host"
        for line in plane.lines:
            where = f"{plane.name}/{line.name}"
            counts = out["lines"].setdefault(where, {})
            for e in line.events:
                prefix = e.name.split("(", 1)[0][:60]
                counts[prefix] = counts.get(prefix, 0) + 1
                run_id = dict(e.stats).get("run_id")
                keep = (
                    run_id is not None or is_upload(e.name)
                    or e.name.startswith(("presto.", "PjitFunction("))
                    or (side == "device" and line.name == "XLA Modules")
                )
                if keep:
                    out[side].append([
                        where, e.name[:80], float(e.start_ns),
                        float(e.duration_ns), run_id,
                    ])
    return out


def device_ends(host, device):
    """[(host time of a launch, the device's end of that program)]: by
    the `run_id` both sides carry, else by order (a thread's k-th
    `PjitFunction(<name>)` is the device's k-th `jit_<name>` module)."""
    ends = {}
    for _w, _n, start, dur, run_id in device:
        if run_id is not None:
            ends[run_id] = max(ends.get(run_id, 0.0), start + dur)
    linked = [(e[2], ends[e[4]]) for e in host if e[4] in ends]
    if linked:
        return linked
    calls = []
    for e in host:
        inside = calls and e[2] < calls[-1][2] + calls[-1][3]
        if e[1].startswith("PjitFunction(") and not inside:
            calls.append(e)
    modules = sorted(
        (e for e in device if e[0].endswith("/XLA Modules")),
        key=lambda e: e[2],
    )
    names = [tracered.program_name(e[1]) for e in modules]
    wanted = ["jit_" + e[1][len("PjitFunction("):-1] for e in calls]
    if names != wanted:
        say(f"launches and modules do not pair by order: {len(calls)} "
            f"calls, {len(modules)} modules")
        return []
    return [(c[2], m[2] + m[3]) for c, m in zip(calls, modules)]


def stamp_errors(events, trace):
    """{"device": [(span name, pos, error ms)], "link": [...]}: how long
    after the profile's own end of the work each stamp was written. The
    spans' wall clock is moved onto the profile's by each span's own
    `presto.<name>` annotation (entered as the span began)."""
    host = sorted(events["host"], key=lambda e: e[2])
    notes = [e for e in host if e[1].startswith("presto.")]
    spans = sorted(
        (s for s in trace.spans() if "ready_at" in s.attrs),
        key=lambda s: s.start,
    )
    launches = device_ends(host, events["device"])
    transfers = [
        e for side in ("host", "device") for e in events[side]
        if is_upload(e[1])
    ]
    out = {"device": [], "link": []}
    used = set()
    for s in spans:
        mark = next(
            (i for i, e in enumerate(notes)
             if i not in used and e[1] == "presto." + s.name), None,
        )
        if mark is None:
            continue
        used.add(mark)
        _w, _n, a0, dur, _r = notes[mark]
        shift = a0 - s.start * 1e9  # wall clock -> profile clock
        ready = s.attrs["ready_at"] * 1e9 + shift
        if s.attrs.get("ready_queue") == "device":
            mine = [
                end for at, end in launches if a0 <= at <= a0 + dur
            ]
            if mine:
                # a stamp cannot come before its hand-over
                done = max(max(mine), s.attrs["handed_at"] * 1e9 + shift)
                out["device"].append(
                    (s.name, s.attrs.get("pos", ""), (ready - done) / 1e6)
                )
        elif transfers:
            # the scan's stamp is its LAST batch's: against the end of
            # the last copy that was done by then (a Pulled span has an
            # annotation a piece; the first one gave the shift)
            done = [e[2] + e[3] for e in transfers if e[2] + e[3] <= ready]
            if done:
                out["link"].append(
                    (s.name, s.attrs.get("pos", ""), (ready - max(done)) / 1e6)
                )
    return out


def report_errors(cls, errors):
    for queue, rows in errors.items():
        if not rows:
            print(f"{cls}: no `{queue}` stamp could be held against the "
                  "profile (no linked event found)")
            continue
        ms = sorted(r[2] for r in rows)
        worst = max(rows, key=lambda r: r[2])
        print(
            f"{cls}: `{queue}` stamps behind the profile's end of the "
            f"work, ms: median {statistics.median(ms):.3f}, worst "
            f"{ms[-1]:.3f} (`{worst[0]}` `{worst[1]}`), over {len(ms)} "
            f"stamps: {[round(m, 3) for m in ms]}"
        )


def profiled(client, sql, cls, out_dir):
    """One statement in a profiler session of its own."""
    import jax

    from presto_tpu.obs import span as obs_span

    tmp = tempfile.mkdtemp(prefix="presto_optable_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            _wall, trace = serve(client, sql)
            obs_span.settle()
        finally:
            jax.profiler.stop_trace()
        events = read_events(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    events["spans"] = trace.to_dicts()
    os.makedirs(out_dir, exist_ok=True)
    with gzip.open(
        os.path.join(out_dir, f"events.{cls}.json.gz"), "wt"
    ) as f:
        json.dump(events, f)
    return stamp_errors(events, trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--profile", metavar="DIR")
    ap.add_argument("--events", metavar="FILE",
                    help="redo the stamps-against-profile arithmetic on "
                    "a saved events file")
    args = ap.parse_args(argv)

    from presto_tpu.obs import span as obs_span

    if args.events:
        with gzip.open(args.events, "rt") as f:
            events = json.load(f)
        trace = obs_span.Trace()
        trace.add_remote(events["spans"])
        report_errors(os.path.basename(args.events),
                      stamp_errors(events, trace))
        return 0

    import jax
    from traffic import Mix, load_json

    import run as bench_run

    cell = load_json("workloads", args.workload)
    config = load_json("configs", cell["config"])
    device = jax.devices()[0]
    if device.platform != "tpu" and not config.get("rehearsal"):
        say(f"{cell['config']} needs a TPU; JAX found {device.platform!r}")
        return 3
    mix = Mix(cell["traffic"], args.seed)
    deployment = bench_run.load_module(
        "deployments", config["serve"]
    ).start(config)
    try:
        client = deployment.client()
        for _ in range(int(mix.spec.get("warmup_passes", 1)) + 1):
            for st, i in mix.every():
                client.execute(st.sql(i))
        served = {}
        for _ in range(args.repeats):
            for st, i in mix.every():
                served.setdefault(st.id, []).append(
                    serve(client, st.sql(i))
                )
        obs_span.settle()
        print(f"## {args.workload} by operator: {device.device_kind} "
              f"({device.platform}), seed {args.seed}, outside the "
              "harness's loop\n")
        for cls, runs in served.items():
            print(table(cls, runs) + "\n")
        if args.profile:
            for st in mix.statements:
                errors = {"device": [], "link": []}
                for i in range(len(st.param_sets)):
                    found = profiled(
                        client, st.sql(i), f"{st.id}.{i}", args.profile
                    )
                    for queue, rows in found.items():
                        errors[queue] += rows
                report_errors(st.id, errors)
    finally:
        deployment.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
