"""The comparison that decides `correct` has been shown to fail.

Two kinds of test, both run by hand on the CPU
(`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`):

- the control: the plain reference put in the program's place and carried
  in a precision the configurations do not allow (they state exact
  decimals) must come out as not correct: float64 at the cell's own size
  (SF1 Q1: the sum of charges passes 2**53), float32 at any size;
- the faults: a whole run of the harness on the rehearsal configuration
  (the only one that skips the look for a chip), with the timed path
  broken underneath, must print `correct: false`.
"""

import importlib
import json
from decimal import Decimal

import numpy as np
import pytest

import compare
import datagen

CASES = {
    "q1": {"delta": 90},
    "q6": {"year": 1994, "discount": 6, "quantity": 24},
    "q3": {"segment": "BUILDING", "date": "1995-03-15"},
}


def control_verdict(statement, sf, acc):
    ref = importlib.import_module(statement)
    tables = {
        t: datagen.columns(t, sf, cols) for t, cols in ref.TABLES.items()
    }
    p = CASES[statement]
    want = {("s", 0): (ref.answer(tables, p), ref.ORDER_BY)}
    served = [(("s", 0), ref.answer(tables, p, acc))]
    return compare.verdict(served, want, 0)


@pytest.mark.parametrize("statement", sorted(CASES))
def test_reference_against_itself_is_correct(statement):
    correct, checks = control_verdict(statement, 0.01, np.int64)
    assert correct and checks["mismatched_cells"]["value"] == 0


@pytest.mark.parametrize("statement", sorted(CASES))
def test_float32_control_is_not_correct(statement):
    correct, checks = control_verdict(statement, 0.01, np.float32)
    assert not correct
    assert checks["mismatched_cells"]["value"] >= 1


def test_float64_control_is_not_correct_at_sf1():
    correct, checks = control_verdict("q1", 1.0, np.float64)
    assert not correct
    assert checks["mismatched_cells"]["value"] >= 1


def _run_harness(monkeypatch, capsys, break_execute):
    """One whole rehearsal run with Client.execute wrapped by
    `break_execute(n, cols, rows)`, n counting the statements sent."""
    import run
    from presto_tpu.server import client as client_mod

    real = client_mod.Client.execute
    sent = [0]

    def execute(self, sql):
        cols, rows = real(self, sql)
        sent[0] += 1
        return break_execute(sent[0], cols, rows)

    monkeypatch.setattr(client_mod.Client, "execute", execute)
    rc = run.main([
        "--workload", "rehearsal.scan_agg", "--seed", "2147483777",
        "--seconds", "2", "--trace", "0",
    ])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(monkeypatch, capsys):
    out = _run_harness(monkeypatch, capsys, lambda n, c, r: (c, r))
    assert out["correct"] is True and out["failed"] == 0
    assert out["checks"]["answers_compared"]["value"] == out["attempted"]


def test_altered_answer_is_not_correct(monkeypatch, capsys):
    """One digit of one cell of every 7th answer altered where the client
    hands it over."""

    def alter(n, cols, rows):
        if n % 7 == 0 and rows:
            rows = [list(r) for r in rows]
            v = rows[0][-1]
            rows[0][-1] = v + 1 if isinstance(v, int) else v[:-1] + (
                "1" if v[-1] != "1" else "2"
            )
        return cols, rows

    out = _run_harness(monkeypatch, capsys, alter)
    assert out["correct"] is False and out["failed"] >= 1
    assert out["checks"]["mismatched_cells"]["value"] >= 1


def test_stale_answer_is_not_correct(monkeypatch, capsys):
    """Each statement class answered from the first answer it ever gave:
    a result cache keyed without the parameters, which breaks the
    configuration's 'every statement executes'."""
    first = {}

    def stale(n, cols, rows):
        key = tuple(c["name"] for c in cols)
        return first.setdefault(key, (cols, rows))

    out = _run_harness(monkeypatch, capsys, stale)
    assert out["correct"] is False
    assert out["checks"]["mismatched_cells"]["value"] >= 1


def test_dropped_rows_are_not_correct(monkeypatch, capsys):
    """The last row of every answer with more than one row left out."""

    def drop(n, cols, rows):
        return cols, rows[:-1] if len(rows) > 1 else rows

    out = _run_harness(monkeypatch, capsys, drop)
    assert out["correct"] is False
    assert out["checks"]["wrong_row_count"]["value"] >= 1


def test_misordered_rows_are_not_correct(monkeypatch, capsys):
    """Every answer's rows handed over last row first: the rows are the
    reference's, their order breaks the statement's ORDER BY."""
    out = _run_harness(monkeypatch, capsys, lambda n, c, rows: (c, rows[::-1]))
    assert out["correct"] is False
    assert out["checks"]["misordered_rows"]["value"] >= 1
    assert out["checks"]["mismatched_cells"]["value"] == 0


def test_order_is_judged_by_the_order_by_key_and_ties_are_open():
    q3 = importlib.import_module("q3")
    a = (1, Decimal("9.5"), "1995-03-01", 0)
    b = (2, Decimal("9.5"), "1995-03-01", 0)   # ties with a on both keys
    c = (3, Decimal("9.5"), "1995-03-02", 0)
    d = (4, Decimal("7.0"), "1995-01-01", 0)
    assert compare.misordered([a, b, c, d], q3.ORDER_BY) == 0
    assert compare.misordered([b, a, c, d], q3.ORDER_BY) == 0
    assert compare.misordered([a, c, b, d], q3.ORDER_BY) == 1  # date order
    assert compare.misordered([d, a, b, c], q3.ORDER_BY) == 1  # revenue desc
    assert compare.misordered([d, a], None) == 0
    want = {("s", 0): ([a, b, c, d], q3.ORDER_BY)}
    correct, checks = compare.verdict([(("s", 0), [d, c, b, a])], want, 0)
    assert not correct and checks["misordered_rows"]["value"] == 2
