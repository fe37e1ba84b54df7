"""Verifier (presto-verifier analog), DB-API client (presto-jdbc analog),
and the coordinator web UI."""

import datetime

import pytest

from presto_tpu.connectors.memory import MemoryCatalog
from presto_tpu.connectors.tpch import TpchCatalog
from presto_tpu.server.coordinator import CoordinatorServer
from presto_tpu.session import Session


@pytest.fixture(scope="module")
def server():
    srv = CoordinatorServer(Session(TpchCatalog(sf=0.002)), max_concurrent=2)
    srv.start()
    yield srv
    srv.stop()


# -- verifier ---------------------------------------------------------------


def test_verifier_match_and_mismatch():
    from presto_tpu.verifier import SessionTarget, verify_suite

    control = SessionTarget(Session(TpchCatalog(sf=0.002)))
    test = SessionTarget(Session(TpchCatalog(sf=0.002)))
    results = verify_suite(
        control, test,
        [
            "select count(*) from orders",
            "select o_orderpriority, count(*) c from orders group by 1",
        ],
    )
    assert all(r.status == "MATCH" for r in results)

    # different SF -> detected mismatch
    test2 = SessionTarget(Session(TpchCatalog(sf=0.004)))
    bad = verify_suite(control, test2, ["select count(*) from orders"])
    assert bad[0].status == "MISMATCH"
    assert "row count" in bad[0].detail or "checksum" in bad[0].detail


def test_verifier_order_insensitive_digest():
    from presto_tpu.verifier import row_digest

    n1, d1 = row_digest([(1, "a"), (2, "b")])
    n2, d2 = row_digest([(2, "b"), (1, "a")])
    assert (n1, d1) == (n2, d2)
    n3, d3 = row_digest([(1, "a"), (2, "x")])
    assert d3 != d1


def test_verifier_reports_failures():
    from presto_tpu.verifier import SessionTarget, verify_query

    control = SessionTarget(Session(TpchCatalog(sf=0.002)))
    test = SessionTarget(Session(MemoryCatalog({})))
    r = verify_query(control, test, "select count(*) from orders")
    assert r.status == "TEST_FAILED"


def test_verifier_rest_targets(server):
    from presto_tpu.verifier import RestTarget, verify_suite

    a = RestTarget(server.uri)
    b = RestTarget(server.uri)
    results = verify_suite(a, b, ["select count(*) from lineitem"])
    assert results[0].status == "MATCH"


# -- DB-API -----------------------------------------------------------------


def test_dbapi_roundtrip(server):
    import presto_tpu.dbapi as dbapi

    with dbapi.connect(server.uri) as conn:
        cur = conn.cursor()
        cur.execute("select count(*) c from orders")
        assert cur.description[0][0] == "c"
        assert cur.fetchone()[0] > 0
        assert cur.fetchone() is None

        cur.execute(
            "select o_orderkey, o_orderpriority from orders"
            " where o_orderkey <= ? order by 1 limit ?",
            (10, 3),
        )
        rows = cur.fetchall()
        assert len(rows) <= 3
        assert cur.rowcount == len(rows)


def test_dbapi_param_binding():
    from presto_tpu.dbapi import ProgrammingError, _substitute

    assert _substitute("select ?", (5,)) == "select 5"
    assert _substitute("select '?', ?", ("a'b",)) == "select '?', 'a''b'"
    assert (
        _substitute("select ?", (datetime.date(2020, 2, 2),))
        == "select date '2020-02-02'"
    )
    assert _substitute("select ?, ?", (None, True)) == "select null, true"
    with pytest.raises(ProgrammingError):
        _substitute("select ?", ())
    with pytest.raises(ProgrammingError):
        _substitute("select ?", (1, 2))


def test_dbapi_error_wrapping(server):
    import presto_tpu.dbapi as dbapi

    conn = dbapi.connect(server.uri)
    cur = conn.cursor()
    with pytest.raises(dbapi.DatabaseError):
        cur.execute("select bogus_column from orders")
    conn.close()
    with pytest.raises(dbapi.InterfaceError):
        cur.execute("select 1")


# -- web UI -----------------------------------------------------------------


def test_web_ui_renders(server):
    import urllib.request

    import presto_tpu.dbapi as dbapi

    dbapi.connect(server.uri).cursor().execute("select count(*) from nation")
    html = urllib.request.urlopen(server.uri + "/").read().decode()
    assert "presto-tpu coordinator" in html
    assert "Resource groups" in html
    assert "select count(*) from nation" in html


def test_digest_no_even_multiplicity_cancellation():
    from presto_tpu.verifier import row_digest

    a = row_digest([(1, "a"), (1, "a")])
    b = row_digest([(2, "b"), (2, "b")])
    assert a != b


def test_dbapi_placeholders_in_comments_and_quotes():
    from presto_tpu.dbapi import _substitute

    assert (
        _substitute("select x from t where y = ? -- why?", (5,))
        == "select x from t where y = 5 -- why?"
    )
    assert (
        _substitute('select "a?b" from t /* ?? */ where z = ?', (1,))
        == 'select "a?b" from t /* ?? */ where z = 1'
    )


def test_benchmark_driver(server, tmp_path):
    import json

    from presto_tpu.benchmark.driver import main, render, run_suite
    from presto_tpu.verifier import RestTarget

    benches = run_suite(
        RestTarget(server.uri),
        {"counts": "select count(*) from orders",
         "bad": "select nope from orders"},
        runs=2, warmup=0,
    )
    by_name = {b.name: b for b in benches}
    assert len(by_name["counts"].runs_ms) == 2
    assert by_name["counts"].rows == 1
    assert by_name["bad"].error
    text = render(benches)
    assert "counts" in text and "FAILED" in text

    suite = tmp_path / "suite.json"
    suite.write_text(json.dumps(
        {"runs": 1, "warmup": 0,
         "queries": {"n": "select count(*) from nation"}}
    ))
    assert main(["--server", server.uri, str(suite)]) == 0


def test_benchmark_suites_definitions_and_run():
    """benchto-benchmarks analog (ref tpch.yaml protocol: 6 runs + 2
    prewarms, weekly): suite definitions carry the reference protocol and
    execute in-process."""
    from presto_tpu.benchmark.suites import SUITES, run

    assert SUITES["tpch"]["runs"] == 6 and SUITES["tpch"]["prewarms"] == 2
    assert SUITES["tpch"]["frequency_days"] == 7
    assert len(SUITES["tpcds"]["queries"]) >= 99
    out = run("tpch", sf=0.005, queries=[1, 6], runs=1)
    assert set(out["queries"]) == {"1", "6"}
    for q in out["queries"].values():
        assert q["p50_ms"] > 0 and q["rows"] > 0 and not q["error"]
    out2 = run("distributed_sort", sf=0.005, queries=["sort_1col"], runs=1)
    assert out2["queries"]["sort_1col"]["rows"] == 10


def test_cli_split_statements():
    from presto_tpu.cli import split_statements

    assert split_statements("select 1; select 2;") == [
        "select 1",
        "select 2",
    ]
    # semicolons inside string literals are not separators
    assert split_statements("select 'a;b'; select ';'") == [
        "select 'a;b'",
        "select ';'",
    ]
    assert split_statements("select 'it''s; fine'") == [
        "select 'it''s; fine'"
    ]


# -- prestolint CLI (presto_tpu/analysis/__main__.py) ------------------------
#
# The static-analysis suite's tooling contract: --check exits nonzero on
# any un-baselined finding (how tier-1 and the verify recipe invoke it),
# --baseline-update regenerates the suppression file. Pass logic itself
# is covered in tests/test_static_analysis.py.


def _lint_main(argv):
    from presto_tpu.analysis.__main__ import main

    return main(argv)


def _bad_tree(tmp_path):
    pkg = tmp_path / "presto_tpu" / "server"
    pkg.mkdir(parents=True)
    (pkg / "bad.py").write_text(
        "def f():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    return tmp_path


def test_lint_check_fails_then_baseline_then_passes(tmp_path, capsys):
    root = _bad_tree(tmp_path)
    bl = str(tmp_path / "baseline.json")

    # un-baselined finding -> nonzero
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl]) == 1
    out = capsys.readouterr().out
    assert "broad-except-swallow" in out and "FAILED" in out

    # --baseline-update writes the suppression file -> check passes
    assert _lint_main(["--baseline-update", "--root", str(root),
                       "--baseline", bl]) == 0
    import json

    entries = json.load(open(bl))["findings"]
    assert len(entries) == 1 and entries[0]["rule"] == "broad-except-swallow"
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl]) == 0

    # NEW finding on top of the baseline -> nonzero again
    (root / "presto_tpu" / "server" / "worse.py").write_text(
        "def g():\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl]) == 1
    out = capsys.readouterr().out
    assert "worse.py" in out


def test_lint_stale_baseline_reports_expired(tmp_path, capsys):
    root = _bad_tree(tmp_path)
    bl = str(tmp_path / "baseline.json")
    assert _lint_main(["--baseline-update", "--root", str(root),
                       "--baseline", bl]) == 0
    # fix the finding: entry goes stale but check still passes
    (root / "presto_tpu" / "server" / "bad.py").write_text("X = 1\n")
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl]) == 0
    out = capsys.readouterr().out
    assert "stale" in out
    # prune
    assert _lint_main(["--baseline-update", "--root", str(root),
                       "--baseline", bl]) == 0
    import json

    assert json.load(open(bl))["findings"] == []


def test_lint_pass_filter_and_listing(tmp_path, capsys):
    root = _bad_tree(tmp_path)
    bl = str(tmp_path / "nope.json")
    # a different pass doesn't see the exception finding
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl,
                       "--pass", "memory-accounting"]) == 0
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl,
                       "--pass", "no-such-pass"]) == 2
    assert _lint_main(["--list-passes"]) == 0
    out = capsys.readouterr().out
    assert "tracing-safety" in out and "lock-discipline" in out


def test_lint_baseline_update_scoped_to_pass(tmp_path, capsys):
    """`--baseline-update --pass X` regenerates only X's rules; other
    passes' baseline entries are preserved verbatim and their OPEN
    findings are never silently suppressed."""
    import json

    root = _bad_tree(tmp_path)  # broad-except-swallow (exception-hygiene)
    ops = root / "presto_tpu" / "ops"
    ops.mkdir()
    (ops / "bad.py").write_text(
        "import jax\n\n"
        "def kernel(lanes, cap):\n"
        "    return jax.pure_callback(_host, None, *lanes)\n"
    )  # tracing-host-callback (tracing-safety)
    bl = str(tmp_path / "baseline.json")

    # scoped update must NOT baseline the other pass's open finding
    assert _lint_main(["--baseline-update", "--root", str(root),
                       "--baseline", bl, "--pass", "tracing-safety"]) == 0
    entries = json.load(open(bl))["findings"]
    assert [e["rule"] for e in entries] == ["tracing-host-callback"]
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl]) == 1
    out = capsys.readouterr().out
    assert "broad-except-swallow" in out

    # full update baselines both; a later scoped update keeps the other
    # pass's entry verbatim
    assert _lint_main(["--baseline-update", "--root", str(root),
                       "--baseline", bl]) == 0
    assert len(json.load(open(bl))["findings"]) == 2
    # a scoped --check must not mislabel the OTHER pass's still-valid
    # baseline entries as stale
    capsys.readouterr()
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl,
                       "--pass", "tracing-safety"]) == 0
    assert "stale" not in capsys.readouterr().out
    (ops / "bad.py").write_text("X = 1\n")  # fix the tracing finding
    assert _lint_main(["--baseline-update", "--root", str(root),
                       "--baseline", bl, "--pass", "tracing-safety"]) == 0
    entries = json.load(open(bl))["findings"]
    assert [e["rule"] for e in entries] == ["broad-except-swallow"]
    assert _lint_main(["--check", "--root", str(root), "--baseline", bl]) == 0


def test_lint_json_report(tmp_path, capsys):
    """`--check --json` emits exactly one machine-readable object on
    stdout — the contract tools/bench_gate.py's lint gate parses."""
    import json

    root = _bad_tree(tmp_path)
    bl = str(tmp_path / "baseline.json")
    assert _lint_main(["--check", "--json", "--root", str(root),
                       "--baseline", bl]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is False
    assert payload["new_by_rule"] == {"broad-except-swallow": 1}
    assert payload["new"][0]["file"].endswith("bad.py")
    assert len(payload["passes"]) == 8

    assert _lint_main(["--baseline-update", "--root", str(root),
                       "--baseline", bl]) == 0
    capsys.readouterr()
    assert _lint_main(["--check", "--json", "--root", str(root),
                       "--baseline", bl]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["ok"] is True and payload["baselined"] == 1


def test_lint_module_entrypoint_real_tree():
    """`python -m presto_tpu.analysis --check` — exactly the tier-1 /
    verify-recipe invocation — exits 0 on the committed tree."""
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    proc = subprocess.run(
        [sys.executable, "-m", "presto_tpu.analysis", "--check"],
        cwd=str(root), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


# -- the TPC-DS suite's sharding (tests/tpcds/) -------------------------------


def test_tpcds_shards_partition_the_queries():
    """Every TPC-DS query is in exactly one shard, and the shard files on
    disk are exactly k = 0..N_SHARDS-1, each asking for its own k: a file
    lost in a merge would silently drop its share of the oracle suite."""
    import importlib.util
    from pathlib import Path

    from presto_tpu.benchmark.tpcds_sql import QUERIES

    here = Path(__file__).resolve().parent / "tpcds"
    spec = importlib.util.spec_from_file_location(
        "tpcds_cases", here / "cases.py"
    )
    cases = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cases)

    dealt = [q for k in range(cases.N_SHARDS) for q in cases.shard(k)]
    assert sorted(dealt) == sorted(QUERIES)
    assert {p.name for p in here.glob("test_*.py")} == {
        f"test_tpcds_queries_{k}.py" for k in range(cases.N_SHARDS)
    }
    for k in range(cases.N_SHARDS):
        text = (here / f"test_tpcds_queries_{k}.py").read_text()
        assert f'parametrize("qid", shard({k}))' in text, k
