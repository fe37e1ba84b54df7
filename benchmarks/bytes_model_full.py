"""bytes_model.py's count for the population `presto-tpu --serve` ships:
the bytes a statement must read are rows x stored width of the columns
its text names, each table once however often the text names it.
`schema_full.json` is the benchmark's own copy of the stored widths of
the host-fed `TpchCatalog`'s three large tables (bigint and decimal 8
bytes; date and dictionary code 4: lineitem 96 B a row, orders 52,
customer 44) and of the rows per unit of scale. Lineitem's is the
nominal 6,000,000 (1..7 lines an order, mean 4): the generated table
has 59,994,841 rows at SF10, 0.009 % fewer."""

import json
import os
import re

from traffic import sql_template

HERE = os.path.dirname(os.path.abspath(__file__))

with open(os.path.join(HERE, "schema_full.json")) as _f:
    SCHEMA = json.load(_f)


def named_columns(sql: str) -> dict:
    """{table: [columns]} the text names."""
    words = set(re.findall(r"[a-z_][a-z0-9_]*", sql.lower()))
    named = {
        table: [c for c in spec["columns"] if c in words]
        for table, spec in SCHEMA.items()
    }
    return {table: cols for table, cols in named.items() if cols}


def statement_bytes(statement_id: str, sf: float) -> int:
    return sum(
        int(SCHEMA[t]["rows_per_sf"] * sf)
        * sum(SCHEMA[t]["columns"][c] for c in cols)
        for t, cols in named_columns(sql_template(statement_id)).items()
    )
