"""The bytes a statement must read: rows x stored width of the columns
its text names. `schema.json` is the benchmark's own copy of the stored
widths (presto_tpu/benchmark/benchgen.py SCHEMAS: bigint and decimal 8
bytes, date and dictionary code 4) and of the rows per unit of scale."""

import json
import os
import re

from traffic import sql_template

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "schema.json")) as _f:
    SCHEMA = json.load(_f)


def named_columns(sql: str) -> dict:
    """{table: [columns]} the text names."""
    words = set(re.findall(r"[a-z_][a-z0-9_]*", sql.lower()))
    out = {}
    for table, spec in SCHEMA.items():
        cols = [c for c in spec["columns"] if c in words]
        if cols:
            out[table] = cols
    return out


def rows(table: str, sf: float) -> int:
    spec = SCHEMA[table]
    return max(int(spec["rows_per_sf"] * sf), spec["min_rows"])


def sql_bytes(sql: str, sf: float) -> int:
    return sum(
        rows(t, sf) * sum(SCHEMA[t]["columns"][c] for c in cols)
        for t, cols in named_columns(sql).items()
    )


def statement_bytes(statement_id: str, sf: float) -> int:
    return sql_bytes(sql_template(statement_id), sf)
