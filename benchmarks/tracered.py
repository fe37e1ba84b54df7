"""From a profiler trace to numbers: device busy time, launches, the
operations that took most time, and where the device's idle time fell.

`load` reads the `.xplane.pb` that `jax.profiler` wrote (with
`jax.profiler.ProfileData`, nothing else) into plain lists; `reduce`
does the arithmetic on those lists, so it can be checked against the
small recorded trace under `tests/data/`.

On a TPU each chip is a plane `/device:TPU:<n>`; its line `XLA Ops` has
one event per operation that ran on the chip and `XLA Modules` one per
program executed (a launch). Busy time is the union of the `XLA Ops`
intervals (`Async XLA Ops`, whose events span from a copy's start to its
end, are left out); the breakdown's operations are the programs of
`XLA Modules`, by the jit or Pallas name XLA gives them. The client's statements are on the host's
planes as `bench.stmt.<id>` annotations, on the same clock; the
program's spans carry wall-clock stamps and are moved onto it by the
offset between a statement's annotation and the wall-clock time taken
as it was sent.
"""

import glob
import os

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench.stmt."


def load(trace_dir: str) -> dict:
    """{"device": {plane: {"ops": [[name, start_ns, dur_ns]], "modules":
    [...]}}, "marks": [[name, start_ns, dur_ns]]} from the newest trace
    under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"device": {}, "marks": []}
    for plane in data.planes:
        for line in plane.lines:
            events = [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events
            ]
            if plane.name.startswith(DEVICE_PLANE):
                kind = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if kind is not None:
                    out["device"].setdefault(
                        plane.name, {"ops": [], "modules": []}
                    )[kind].extend(events)
            else:
                out["marks"].extend(
                    e for e in events if e[0].startswith(MARK)
                )
    out["marks"].sort(key=lambda e: e[1])
    return out


def program_name(name: str) -> str:
    """`jit_multiply(17561562841670141911)` -> `jit_multiply`: the name
    XLA gives a program, without its fingerprint."""
    return name.split("(", 1)[0][:120]


def union(intervals):
    """Merged, sorted (starts, ends) arrays of [start, end) intervals."""
    if len(intervals) == 0:
        return np.zeros(0), np.zeros(0)
    iv = np.asarray(sorted(intervals), dtype=np.float64)
    starts, ends = [iv[0, 0]], [iv[0, 1]]
    for s, e in iv[1:]:
        if s <= ends[-1]:
            if e > ends[-1]:
                ends[-1] = e
        else:
            starts.append(s)
            ends.append(e)
    return np.asarray(starts), np.asarray(ends)


def overlap(merged, a: float, b: float) -> float:
    """Length of [a, b) covered by a merged interval set."""
    starts, ends = merged
    if len(starts) == 0 or b <= a:
        return 0.0
    return float(
        np.clip(np.minimum(ends, b) - np.maximum(starts, a), 0.0, None).sum()
    )


def _minus(outer, inner):
    """Intervals of `outer` not covered by the `inner` ones (both lists
    of (start, end)); inner ones lie inside outer."""
    out = []
    for a, b in outer:
        at = a
        for s, e in sorted(i for i in inner if i[1] > a and i[0] < b):
            if s > at:
                out.append((at, s))
            at = max(at, e)
        if b > at:
            out.append((at, b))
    return out


def reduce(trace: dict, records) -> dict:
    """The numbers the per-layer readers take. `records` are the traced
    statements' records in the order sent: `epoch_ns` (wall clock as the
    statement was sent) and `spans` {name: (start_s, end_s)} on the wall
    clock. Seconds throughout. No device plane, or nothing on it: the
    device keys are None (a CPU rehearsal), never 0."""
    marks = trace["marks"][: len(records)]
    out = {
        "statements": len(marks), "busy_s": None, "window_s": None,
        "launches": None, "device_ops": [], "idle_gaps": [],
        "traced_ids": [r["id"] for r in records[: len(marks)]],
    }
    if not marks:
        return out
    w0 = marks[0][1]
    w1 = max(m[1] + m[2] for m in marks)
    out["window_s"] = (w1 - w0) / 1e9
    planes = [p for p in trace["device"].values() if p["ops"] or p["modules"]]
    if not planes:
        return out

    busy, launches, by_op = [], [], {}
    merged_all = []
    for p in planes:
        events = p["ops"] or p["modules"]
        merged = union([(s, s + d) for _, s, d in events])
        merged_all.append(merged)
        busy.append(overlap(merged, w0, w1))
        launches.append(sum(1 for _, s, _d in p["modules"] if w0 <= s < w1))
        for name, s, d in p["modules"] or p["ops"]:
            if w0 <= s < w1:
                name = program_name(name)
                by_op[name] = by_op.get(name, 0.0) + d
    n = len(planes)
    out["busy_s"] = sum(busy) / n / 1e9
    out["launches"] = sum(launches) / n
    out["device_ops"] = [
        [name, d / n / 1e9]
        for name, d in sorted(by_op.items(), key=lambda kv: -kv[1])
    ]

    # where the idle time fell, by what the host was doing
    labels = {k: [] for k in ("plan", "execute", "query_other", "http")}
    stmts = []
    for (_, m0, md), r in zip(marks, records):
        stmts.append((m0, m0 + md))
        shift = m0 - r["epoch_ns"]  # wall clock -> trace clock
        sp = {
            k: (v[0] * 1e9 + shift, v[1] * 1e9 + shift)
            for k, v in r.get("spans", {}).items()
        }
        inner = [sp[k] for k in ("plan", "execute") if k in sp]
        for k in ("plan", "execute"):
            if k in sp:
                labels[k].append(sp[k])
        if "query" in sp:
            labels["query_other"] += _minus([sp["query"]], inner)
            labels["http"] += _minus([stmts[-1]], [sp["query"]])
        else:
            labels["http"].append(stmts[-1])
    labels["client"] = _minus([(w0, w1)], stmts)
    gaps = []
    for label, ivs in labels.items():
        idle = 0.0
        for a, b in ivs:
            a, b = max(a, w0), min(b, w1)
            if b > a:
                idle += (b - a) - sum(
                    overlap(m, a, b) for m in merged_all
                ) / n
        if idle > 0:
            gaps.append([label, idle / 1e9])
    out["idle_gaps"] = sorted(gaps, key=lambda g: -g[1])
    return out
