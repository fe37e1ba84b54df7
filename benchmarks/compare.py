"""The comparison that decides `correct`: served rows against the plain
reference, cell by cell and exactly (decimals by value, so the limit on
every number below is 0), and the served order against the statement's
ORDER BY."""

import re
from decimal import Decimal

LIMITS = {
    "mismatched_cells": 0,   # cells that differ from the reference's
    "wrong_row_count": 0,    # answers with more or fewer rows than it
    "misordered_rows": 0,    # rows that break the statement's ORDER BY
    "wrong_answers": 0,      # answers with either fault
    "failed_statements": 0,  # statements that raised or never answered
    "unanswered_refs": 0,    # answers the reference could not judge
}

_DECIMAL = re.compile(r"decimal\(")


def canonical(columns, rows):
    """Rows as they came off the wire -> tuples of comparable values:
    decimals (strings on the wire) become Decimal, the rest stay."""
    is_dec = [bool(_DECIMAL.match(c["type"])) for c in columns]
    return [
        tuple(
            Decimal(v) if d and v is not None else v
            for v, d in zip(row, is_dec)
        )
        for row in rows
    ]


def _key(row):
    return tuple((v is None, str(type(v)), v) for v in row)


def misordered(served, order_by) -> int:
    """Served rows that sort before their predecessor under the
    statement's ORDER BY (`order_by(row)` is its key, None where it has
    none). Rows that tie on the key may come in any order: the statement
    leaves that open, so only true ties are tolerated."""
    if order_by is None:
        return 0
    try:
        keys = [order_by(r) for r in served]
        return sum(1 for a, b in zip(keys, keys[1:]) if b < a)
    except (TypeError, IndexError, ArithmeticError):  # a cell of the wrong kind
        return len(served)


def diff(served, want):
    """(mismatched cells, 1 if the row counts differ else 0) between one
    served answer and the reference's, both lists of tuples, as sets of
    rows: `misordered` judges the order."""
    served, want = sorted(served, key=_key), sorted(want, key=_key)
    cells = 0
    for a, b in zip(served, want):
        cells += sum(1 for x, y in zip(a, b) if not _same(x, y))
        cells += abs(len(a) - len(b))
    return cells, int(len(served) != len(want))


def _same(x, y) -> bool:
    """Equal by value (a Decimal equals the int of its value); a string
    equals only a string; None only None."""
    if isinstance(x, str) != isinstance(y, str):
        return False
    return x == y


def verdict(answers, references, failed_statements: int):
    """`answers`: [(key, rows)] as served in the window; `references`:
    {key: (rows, order_by)} or a missing key where the reference gave
    none. Returns (correct, {name: {"value", "limit"}})."""
    got = dict.fromkeys(LIMITS, 0)
    got["failed_statements"] = failed_statements
    for key, rows in answers:
        ref = references.get(key)
        if ref is None:
            got["unanswered_refs"] += 1
            continue
        cells, count = diff(rows, ref[0])
        swaps = misordered(rows, ref[1])
        got["mismatched_cells"] += cells
        got["wrong_row_count"] += count
        got["misordered_rows"] += swaps
        got["wrong_answers"] += int(cells > 0 or count > 0 or swaps > 0)
    checks = {
        name: {"value": got[name], "limit": LIMITS[name]} for name in LIMITS
    }
    checks["answers_compared"] = {
        "value": len(answers) - got["unanswered_refs"], "limit_min": 1,
    }
    correct = all(got[n] <= LIMITS[n] for n in LIMITS) and (
        checks["answers_compared"]["value"] >= 1
    )
    return correct, checks
