"""TPC-H Q1-Q22 end-to-end vs the SQLite oracle.

The reference's AbstractTestQueries pattern (presto-tests/.../
AbstractTestQueries.java — same SQL on the engine and on H2, diff results)
instantiated for the embedded tpch catalog at SF 0.01."""

import pytest

from presto_tpu.benchmark.tpch_sql import QUERIES
from presto_tpu.connectors.tpch import TpchCatalog
from presto_tpu.session import Session
from presto_tpu.testing.oracle import SqliteOracle, assert_same_results

SF = 0.01


@pytest.fixture(scope="module")
def session():
    return Session(TpchCatalog(sf=SF))


@pytest.fixture(scope="module")
def oracle():
    return SqliteOracle(sf=SF)


def run_query(session, oracle, qid):
    sql = QUERIES[qid]
    result = session.query(sql)
    expected = oracle.query(sql)
    types = [b.type for b in result.page.blocks]
    assert_same_results(result.rows(), expected, types, ordered=False)
    assert result.row_count() > 0 or len(expected) == 0


@pytest.mark.parametrize("qid", sorted(QUERIES))
def test_tpch_query(session, oracle, qid):
    run_query(session, oracle, qid)


@pytest.mark.parametrize("qid", [3, 5, 7, 9, 10, 18])
def test_tpch_joins_run_the_sorted_hash_programs(session, monkeypatch, qid):
    """The CPU backend runs the join the chip runs: every Join and
    SemiJoin reports the sorted-hash strategy, a unique-build join is the
    program `join_n1`, any other `join_expand`, a semi join `semi_join`
    (PERF.md section 5 lists them for Q3 on the chip)."""
    from presto_tpu.exec.executor import Executor
    from presto_tpu.plan import nodes as N

    launched = []
    guarded = Executor._kernel_guarded

    def spy(self, breaker, name, key, make_fn, *args):
        if breaker == "join_probe":
            launched.append((name, key[0]))
        return guarded(self, breaker, name, key, make_fn, *args)

    monkeypatch.setattr(Executor, "_kernel_guarded", spy)
    lines = [
        ln.strip()
        for ln in session.explain_analyze(QUERIES[qid]).splitlines()
        if ln.strip().startswith(("- Join", "- SemiJoin"))
    ]
    assert lines and all("strategy=sorted-hash(" in ln for ln in lines), lines
    assert len({id(node) for _, node in launched}) == len(lines)
    for name, node in launched:
        if isinstance(node, N.SemiJoin):
            assert name == "semi_join"
        else:
            assert name == ("join_n1" if node.unique_build else "join_expand")
    if qid == 3:
        assert sorted({name for name, _ in launched}) == [
            "join_expand", "join_n1",
        ]
