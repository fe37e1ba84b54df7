"""Chip smoke: the served SQL path, end to end, on the attached TPU.

    python chip_smoke.py             # one chip: served SF1 Q1/Q6/Q3 + host-fed
    python chip_smoke.py --full      # ... + Q18, SF0.1 vs SQLite, cluster
    python chip_smoke.py --chips 4   # four chips: the mesh path, only

One process owns the chip(s). Exits non-zero — and prints no result line
— when JAX finds no TPU, when any phase raises, when a query's rows
differ from the independent reference, when a kernel breaker recorded a
failure, or when Q1/Q3 did not take the paths the code declares default
on `tpu`. The last line of stdout is
`{"ok": true, "device": {"platform", "kind", "count"}}`; everything else
is printed before it. Times printed here are set-up information (a cold
run compiles), not a benchmark.
"""

import argparse
import json
import re
import sys
import time

import numpy as np


def _q18() -> str:
    """TPC-H Q18 as benchmark/scale.py adapts it (no c_name), with the
    HAVING threshold moved from 300 to 180: the device generator gives
    every order exactly 4 lines of quantity <= 50, so no order passes
    the spec's 300 and the join half would run on nothing."""
    from presto_tpu.benchmark import scale

    _require("> 300" in scale.Q18, "scale.Q18 no longer has its threshold")
    return scale.Q18.replace("> 300", "> 180")


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits/misses
    through JAX's monitoring hooks (an in-memory jit hit fires none)."""

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

    def line(self) -> str:
        return (
            f"compiles={self.compiles} ({self.compile_s:.1f}s) "
            f"persistent-cache hits={self.hits} misses={self.misses}"
        )


def _say(msg: str) -> None:
    print(msg, flush=True)


def _require(ok, msg: str) -> None:
    """A check that survives `python -O`."""
    if not ok:
        raise AssertionError(msg)


def _wire_rows(cols, rows):
    """Rows as the HTTP client returns them -> comparable values + the
    column types (decimals travel as strings)."""
    from presto_tpu import types as T

    types = [T.parse_type(c["type"]) for c in cols]
    out = [
        tuple(
            float(v)
            if v is not None and isinstance(t, T.DecimalType)
            else v
            for v, t in zip(r, types)
        )
        for r in rows
    ]
    return out, types


def _same(name, ours, want, types, ordered):
    from presto_tpu.testing.oracle import assert_same_results

    assert_same_results(ours, want, types, ordered=ordered)
    _say(f"  {name}: {len(ours)} rows equal the reference")


# -- numpy references on the device generator's host twin (SF1) -------------


def _numpy_q1(cols):
    cutoff = (
        np.datetime64("1998-09-02") - np.datetime64("1970-01-01")
    ).astype(int)
    m = cols["l_shipdate"] <= cutoff
    rf, ls = cols["l_returnflag"][m], cols["l_linestatus"][m]
    qty, price, disc, tax = (
        cols[c][m].astype(np.int64)
        for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
    )
    gid = rf.astype(np.int64) * 2 + ls
    disc_price = price * (100 - disc)  # scale 4
    charge = disc_price * (100 + tax)  # scale 6
    rows = []
    for g in np.unique(gid):
        s = gid == g
        n = int(s.sum())
        rows.append((
            "ANR"[g // 2], "FO"[g % 2],
            int(qty[s].sum()) / 1e2, int(price[s].sum()) / 1e2,
            int(disc_price[s].sum()) / 1e4, int(charge[s].sum()) / 1e6,
            qty[s].sum() / n / 1e2, price[s].sum() / n / 1e2,
            disc[s].sum() / n / 1e2, n,
        ))
    return rows


def _numpy_q6(cols):
    d0 = (np.datetime64("1994-01-01") - np.datetime64("1970-01-01")).astype(int)
    d1 = (np.datetime64("1995-01-01") - np.datetime64("1970-01-01")).astype(int)
    m = (
        (cols["l_shipdate"] >= d0) & (cols["l_shipdate"] < d1)
        & (cols["l_discount"] >= 5) & (cols["l_discount"] <= 7)
        & (cols["l_quantity"] < 2400)
    )
    total = int(
        (
            cols["l_extendedprice"][m].astype(np.int64)
            * cols["l_discount"][m]
        ).sum()
    )
    return [(total / 1e4,)]


def _days(iso: str) -> int:
    return int(
        (np.datetime64(iso) - np.datetime64("1970-01-01")).astype(int)
    )


def _iso(days) -> str:
    return str(np.datetime64(int(days), "D"))


def _numpy_q3(t):
    from presto_tpu.benchmark.benchgen import _SEG_POOL

    c, o, li = t["customer"], t["orders"], t["lineitem"]
    cutoff = _days("1995-03-15")
    building = c["c_custkey"][
        c["c_mktsegment"] == _SEG_POOL.index("BUILDING")
    ]
    om = (o["o_orderdate"] < cutoff) & np.isin(o["o_custkey"], building)
    by_key = np.argsort(o["o_orderkey"][om])
    okey, odate, oprio = (
        o[col][om][by_key]
        for col in ("o_orderkey", "o_orderdate", "o_shippriority")
    )
    lm = li["l_shipdate"] > cutoff
    lkey = li["l_orderkey"][lm]
    rev = li["l_extendedprice"][lm].astype(np.int64) * (
        100 - li["l_discount"][lm].astype(np.int64)
    )
    pos = np.minimum(np.searchsorted(okey, lkey), len(okey) - 1)
    hit = okey[pos] == lkey
    total = np.zeros(len(okey), np.int64)
    np.add.at(total, pos[hit], rev[hit])
    g = np.flatnonzero(np.bincount(pos[hit], minlength=len(okey)))
    top = g[np.lexsort((odate[g], -total[g]))[:10]]
    return [
        (int(okey[i]), total[i] / 1e4, _iso(odate[i]), int(oprio[i]))
        for i in top
    ]


def _numpy_q18(t):
    o, li = t["orders"], t["lineitem"]
    # scale-2 quantities; exact in float64 far below 2^53
    qty = np.bincount(li["l_orderkey"], weights=li["l_quantity"])
    # every o_custkey names a customer, so the customer join keeps all
    big = np.flatnonzero(qty[o["o_orderkey"]] > 180 * 100)
    top = big[np.lexsort((o["o_orderdate"][big], -o["o_totalprice"][big]))[:100]]
    return [
        (
            int(o["o_custkey"][i]), int(o["o_orderkey"][i]),
            _iso(o["o_orderdate"][i]), o["o_totalprice"][i] / 1e2,
            qty[o["o_orderkey"][i]] / 1e2,
        )
        for i in top
    ]


_TWIN_COLUMNS = {
    "lineitem": (
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_tax", "l_returnflag", "l_linestatus", "l_shipdate",
    ),
    "orders": (
        "o_orderkey", "o_custkey", "o_totalprice", "o_orderdate",
        "o_shippriority",
    ),
    "customer": ("c_custkey", "c_mktsegment"),
}


def numpy_reference(sf: float):
    """reference(qname, sql) from plain numpy over the device generator's
    host twin: what SF1 is checked against, where loading SQLite would
    take longer than the smoke may."""
    from presto_tpu.benchmark import benchgen

    twin = {
        name: benchgen.numpy_columns(name, sf, cols)
        for name, cols in _TWIN_COLUMNS.items()
    }
    refs = {
        "q1": lambda: _numpy_q1(twin["lineitem"]),
        "q6": lambda: _numpy_q6(twin["lineitem"]),
        "q3": lambda: _numpy_q3(twin),
        "q18": lambda: _numpy_q18(twin),
    }
    return lambda qname, _sql: refs[qname]()


def sqlite_reference(sf: float):
    """reference(qname, sql) from the SQLite oracle on the same twin."""
    from presto_tpu.connectors import tpch_device
    from presto_tpu.testing.oracle import SqliteOracle

    oracle = SqliteOracle(
        sf, tables=("customer", "orders", "lineitem"), source=tpch_device
    )
    return lambda _qname, sql: oracle.query(sql)


# -- phases -----------------------------------------------------------------


def _serve(session):
    """Start the coordinator as `python -m presto_tpu.cli --serve` does
    and return (server, client)."""
    from presto_tpu.server import Client, CoordinatorServer

    server = CoordinatorServer(session, port=0).start()
    return server, Client(server.uri)


def _twice(name, run, counter):
    """Run a statement cold, then again: same rows and no new XLA
    compile. An adaptive choice may move once between the first two runs
    (PERF.md, open questions), so a repeat that did compile gets one
    more run, and that one must not."""
    walls, compiles, outs = [], [], []
    for _ in range(3):
        c0 = counter.compiles
        t0 = time.perf_counter()
        outs.append(run())
        walls.append((time.perf_counter() - t0) * 1e3)
        compiles.append(counter.compiles - c0)
        if len(outs) >= 2 and compiles[-1] == 0:
            break
    _say(
        f"  {name}: cold {walls[0]:.0f} ms ({compiles[0]} compiles), warm "
        + ", ".join(
            f"{w:.0f} ms ({c} new compiles)"
            for w, c in zip(walls[1:], compiles[1:])
        )
        + f", {len(outs[-1][1])} rows"
    )
    _require(all(o[1] == outs[0][1] for o in outs), f"{name}: rows changed")
    _require(
        compiles[-1] == 0,
        f"{name}: the statement still compiled {compiles[-1]} program(s) "
        f"on run {len(outs)}",
    )
    return outs[-1]


def phase_served(sf: float, counter, reference, with_q18: bool) -> None:
    """SQL over HTTP against a device-generated catalog. `reference(qname,
    sql)` returns the rows the query must produce."""
    from presto_tpu.benchmark.tpch_sql import QUERIES
    from presto_tpu.connectors.tpch_device import DeviceTpchCatalog
    from presto_tpu.session import Session

    tag = f"served sf{sf:g}"
    _say(f"== {tag}: DeviceTpchCatalog behind CoordinatorServer")
    # result cache off so the second run executes on the device again
    session = Session(DeviceTpchCatalog(sf=sf), result_cache=False)
    server, client = _serve(session)
    try:
        statements = [
            ("q1", QUERIES[1], True),
            ("q6", QUERIES[6], True),
            ("q3", QUERIES[3], False),
        ]
        if with_q18:
            statements.append(("q18", _q18(), False))
        for qname, sql, ordered in statements:
            cols, rows = _twice(
                f"{tag} {qname}", lambda: client.execute(sql), counter
            )
            ours, types = _wire_rows(cols, rows)
            _same(f"{tag} {qname}", ours, reference(qname, sql), types, ordered)
        _check_strategies(session, QUERIES[1], QUERIES[3])
    finally:
        server.stop()


def _check_strategies(session, q1_sql, q3_sql) -> None:
    """EXPLAIN ANALYZE must name the paths the code declares default on
    tpu: the Pallas single-pass group-by for Q1, and for Q3 the
    device-resident sorted-hash join with the bucket-directory probe."""
    from presto_tpu.ops.join import sorted_probe_layout

    notes1 = re.findall(r"strategy=([^\],]*)", session.explain_analyze(q1_sql))
    _require("pallas" in notes1, f"Q1 strategies {notes1}: no Pallas group-by")
    notes3 = re.findall(r"strategy=([^\],]*)", session.explain_analyze(q3_sql))
    joins = [n for n in notes3 if n.startswith("sorted-hash")]
    want = f"sorted-hash({sorted_probe_layout()})"
    _require(
        joins and all(j == want for j in joins),
        f"Q3 join strategies {joins}, declared default {want}",
    )
    _require(sorted_probe_layout() == "directory", sorted_probe_layout())
    _say(f"  strategies: q1 {notes1}, q3 {notes3}")


def phase_host_fed(sf: float, counter, queries) -> None:
    """Host-generated tables uploaded to the device — the path every
    file connector (Parquet/ORC/Hive) takes."""
    from presto_tpu.benchmark.tpch_sql import QUERIES
    from presto_tpu.connectors import tpch
    from presto_tpu.session import Session
    from presto_tpu.testing.oracle import SqliteOracle

    tag = f"host-fed sf{sf:g}"
    _say(f"== {tag}: TpchCatalog through Session.query")
    session = Session(tpch.TpchCatalog(sf=sf), result_cache=False)
    oracle = SqliteOracle(
        sf, tables=("customer", "orders", "lineitem"), source=tpch
    )

    def run(sql):
        res = session.query(sql)
        return [b.type for b in res.page.blocks], res.rows()

    for q in queries:
        types, rows = _twice(f"{tag} q{q}", lambda: run(QUERIES[q]), counter)
        _same(f"{tag} q{q}", rows, oracle.query(QUERIES[q]), types, q == 1)


def phase_cluster(sf: float) -> None:
    """Two workers + NodeManager + HttpClusterSession behind the
    coordinator (docs/deployment.md), all threads of this process:
    fragments, the PTP2 wire and server/hier.py's single-chip regroup."""
    from presto_tpu import native
    from presto_tpu.benchmark.tpch_sql import QUERIES
    from presto_tpu.connectors import tpch
    from presto_tpu.server import serde
    from presto_tpu.server.cluster import HttpClusterSession, NodeManager
    from presto_tpu.server.worker import WorkerServer
    from presto_tpu.testing.oracle import SqliteOracle

    tag = f"cluster sf{sf:g}"
    _say(f"== {tag}: 2 WorkerServers + HttpClusterSession + coordinator")
    catalog = tpch.TpchCatalog(sf=sf)
    workers = [WorkerServer(catalog).start() for _ in range(2)]
    nodes = NodeManager([w.uri for w in workers])
    session = HttpClusterSession(catalog, nodes)
    server, client = _serve(session)
    try:
        _say(
            f"  {tag} wire codec: {serde._pick_codec(None)} (native lz4 "
            + ("built" if native.available() else
               f"absent: {native.build_error()}")
            + ")"
        )
        t0 = time.perf_counter()
        cols, rows = client.execute(QUERIES[3])
        _say(
            f"  {tag} q3: {(time.perf_counter() - t0) * 1e3:.0f} ms, "
            f"{len(rows)} rows"
        )
        ours, types = _wire_rows(cols, rows)
        oracle = SqliteOracle(
            sf, tables=("customer", "orders", "lineitem"), source=tpch
        )
        _same(f"{tag} q3", ours, oracle.query(QUERIES[3]), types, False)
        wire = session.scheduler.stats_snapshot()
        _require(wire["wire_caps"], "no wire negotiation happened")
        _say(f"  {tag} wire caps: {wire['wire_caps']}")
    finally:
        server.stop()
        session.close()
        nodes.stop()
        for w in workers:
            w.stop()


def phase_mesh(counter) -> None:
    """Four chips, one process: Session(mesh=...) with shard_map +
    all_to_all against a single-device Session on the same data."""
    from presto_tpu.benchmark.tpch_sql import QUERIES
    from presto_tpu.connectors.tpch import TpchCatalog
    from presto_tpu.parallel.mesh import default_mesh
    from presto_tpu.session import Session

    # small tables: this phase is about sharding and collectives, and
    # every second of it is charged four times
    sf = 0.01
    _say(f"== mesh: Session(mesh=default_mesh(4)), TpchCatalog(sf={sf:g})")
    mesh = default_mesh(4)
    catalog = TpchCatalog(sf=sf)
    single = Session(catalog, result_cache=False)
    sort_sql = (
        "select o_orderkey, o_totalprice, o_orderdate from orders "
        "order by o_totalprice desc, o_orderkey limit 5000"
    )
    cases = [
        ("q1", QUERIES[1], None, True),
        ("q3 broadcast", QUERIES[3], 1 << 40, False),
        ("q3 partitioned", QUERIES[3], 0, False),
        ("distributed sort", sort_sql, None, True),
    ]
    want = {}
    for name, sql, _, _ in cases:
        if sql not in want:
            res = single.query(sql)
            want[sql] = (res.rows(), [b.type for b in res.page.blocks])
    for name, sql, threshold, ordered in cases:
        kw = {} if threshold is None else {"broadcast_threshold": threshold}
        dist = Session(catalog, mesh=mesh, result_cache=False, **kw)
        t0 = time.perf_counter()
        rows = dist.query(sql).rows()
        _say(f"  mesh {name}: {(time.perf_counter() - t0) * 1e3:.0f} ms")
        _same(f"mesh {name}", rows, want[sql][0], want[sql][1], ordered)
        placed = sorted(dist.executor.shard_devices)
        _require(
            len(placed) == 4,
            f"mesh {name}: sharded pages lived on devices {placed}, "
            "expected four",
        )
    _say(f"  sharded stage outputs lived on devices {placed}")


def _check_breakers() -> None:
    from presto_tpu.exec.breaker import BREAKERS

    snap = BREAKERS.snapshot()
    bad = {
        name: s for name, s in snap.items()
        if s["total_failures"] or s["state"] != "closed"
    }
    _require(not bad, f"kernel breakers recorded failures: {bad}")
    _say(f"  breakers: {len(snap)} registered, 0 failures, all closed")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--full", action="store_true",
        help="one chip: also Q18, served SF0.1 against the SQLite oracle, "
        "host-fed Q3 and the two-worker cluster (a cold run of all of it "
        "compiles for longer than the default run may take)",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke.py needs a TPU; JAX found {devices[0].platform!r}"
        )
    if len(devices) < args.chips:
        sys.exit(f"--chips {args.chips} asked, JAX sees {len(devices)}")

    import presto_tpu  # noqa: F401  (x64 on, compile cache configured)
    from presto_tpu.exec.qcache import enable_persistent_compile_cache

    counter = CompileCounter()
    _say(
        f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"at {enable_persistent_compile_cache()}"
    )
    t_start = time.perf_counter()
    if args.chips == 4:
        phase_mesh(counter)
    else:
        phase_served(1.0, counter, numpy_reference(1.0), args.full)
        phase_host_fed(0.1, counter, (1, 3) if args.full else (1,))
        if args.full:
            phase_served(0.1, counter, sqlite_reference(0.1), True)
            phase_cluster(0.1)
    _say("== checks")
    _check_breakers()
    _say(f"  {counter.line()}")
    _say(f"  total {time.perf_counter() - t_start:.0f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }), flush=True)


if __name__ == "__main__":
    main()
