"""Device-resident TPC-H catalog: SQL scans GENERATE their batches on
device.

The host-fed `TpchCatalog` generates table data with numpy and uploads
it to the chip, which makes set-up, not the engine, the cost of a run at
real scale. The reference's equivalent design point is worker-side
generation —
presto-tpch/src/main/java/com/facebook/presto/tpch/TpchRecordSet.java
materializes rows inside the worker from the split alone, so table data
never crosses the coordinator link. Here the same contract holds against
the HOST-DEVICE link: `scan(table, start, stop)` ships ONE scalar (the
range start) and the splitmix64 column generators (benchmark/benchgen.py)
produce the batch on device under a cached jit.

The numpy twin of the same generators backs the SQLite oracle
(`table(name, sf)` below feeds testing/oracle.SqliteOracle), so every
query over this catalog is oracle-verifiable bit-for-bit; and it backs
`column_stats`, so the CBO sees statistics of exactly the data the device
will generate. nation/region (25/5 rows) stay host-generated — their
upload is a few hundred bytes.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import numpy as np

from .. import types as T
from ..benchmark import benchgen
from ..page import Block, Page, intern_dictionary
from . import tpch as tpch_host
from .tpch import Column, Table, TpchCatalog

TABLE_NAMES = sorted(list(benchgen.SCHEMAS) + ["nation", "region"])

_HOST_SMALL = {"nation": tpch_host.gen_nation, "region": tpch_host.gen_region}


def table(name: str, sf: float = 1.0) -> Table:
    """Host-twin Table (numpy, bit-identical to the device data) — the
    SqliteOracle source-module protocol."""
    if name in _HOST_SMALL:
        return _HOST_SMALL[name]()
    schema = benchgen.SCHEMAS[name]
    cols = benchgen.numpy_columns(name, sf, tuple(schema))
    out: Dict[str, Column] = {}
    for c, (typ, pool) in schema.items():
        data = cols[c]
        if pool is not None:
            out[c] = Column(data.astype(np.int32), typ, tuple(pool))
        else:
            out[c] = Column(data.astype(typ.storage_dtype), typ)
    return Table(name, out)


class DeviceTpchCatalog(TpchCatalog):
    """TpchCatalog whose scan path generates batches ON DEVICE."""

    name = "tpch"

    def table_names(self):
        return list(TABLE_NAMES)

    def schema(self, tname: str):
        if tname in _HOST_SMALL:
            return {
                c: col.type for c, col in self.host_table(tname).columns.items()
            }
        return {c: t for c, (t, _pool) in benchgen.SCHEMAS[tname].items()}

    def row_count(self, tname: str) -> int:
        if tname in _HOST_SMALL:
            return self.host_table(tname).num_rows
        return benchgen._sizes(self.sf)[tname]

    def exact_row_count(self, tname: str) -> int:
        return self.row_count(tname)

    def host_table(self, tname: str) -> Table:
        tb = self._tables.get(tname)
        if tb is None:
            tb = table(tname, self.sf)
            self._tables[tname] = tb
        return tb

    # a table over this many rows is sampled for its statistics, in
    # ranges spread over the whole table (as Connector's sample is)
    STATS_SAMPLE_ROWS = 2_000_000
    STATS_SAMPLE_RANGES = 8

    def column_stats(self, tname: str, column: str):
        """CBO statistics from the numpy twin. A table over
        STATS_SAMPLE_ROWS is sampled, to bound host memory at high SF, in
        ranges spread from its first row to its last: the keys are
        stored sorted, so only such ranges see the table's min and max.
        Where the ranges share no value (a clustered key: every range
        brings values of its own, however often each repeats inside it)
        the distinct count grows with the table and is scaled by the
        table's size over the sample's; `stats_from_column` scales only
        a sample that is over half distinct."""
        from ..plan.stats import stats_from_column

        cache = getattr(self, "_stats_cache", None)
        if cache is None:
            cache = self._stats_cache = {}
        key = (tname, column)
        if key not in cache:
            n = self.row_count(tname)
            cap = self.STATS_SAMPLE_ROWS
            pieces = None
            if tname in _HOST_SMALL or n <= cap:
                col = self.host_table(tname).columns[column]
                data, dic = col.data, col.dictionary
                valid = getattr(col, "valid", None)
            else:
                typ, dic = benchgen.SCHEMAS[tname][column]
                span = cap // self.STATS_SAMPLE_RANGES
                pieces = [
                    benchgen.numpy_columns_range(
                        tname, self.sf, (column,), int(start), span
                    )[column].astype(typ.storage_dtype)
                    for start in np.linspace(
                        0, n - span, self.STATS_SAMPLE_RANGES
                    ).astype(np.int64)
                ]
                data, valid = np.concatenate(pieces), None
            stats = stats_from_column(
                data, valid, self.schema(tname)[column], dic, n
            )
            if pieces is not None and dic is None and (
                stats.ndv <= 0.5 * len(data)  # not scaled already
            ):
                apart = sum(len(np.unique(p)) for p in pieces)
                if stats.ndv >= 0.9 * apart:
                    stats = dataclasses.replace(
                        stats, ndv=stats.ndv * (n / len(data))
                    )
            cache[key] = stats
        return cache[key]

    def page(self, tname: str) -> Page:
        pg = self._pages.get(tname)
        if pg is None:
            if tname in _HOST_SMALL:
                pg = self.host_table(tname).to_page()
            else:
                pg = benchgen.device_page(
                    tname, self.sf, tuple(benchgen.SCHEMAS[tname])
                )
            self._pages[tname] = pg
        return pg

    def scan(self, tname: str, start: int, stop: int, pad_to=None,
             columns=None, predicate=None) -> Page:
        if tname in _HOST_SMALL:
            return super().scan(
                tname, start, stop, pad_to=pad_to, columns=columns,
                predicate=predicate,
            )
        schema = benchgen.SCHEMAS[tname]
        cols = tuple(columns) if columns is not None else tuple(schema)
        # the streaming driver over-requests the last batch and expects
        # the connector to clamp at table end (exec/stream.py scan loop)
        stop = min(stop, self.row_count(tname))
        start = min(start, stop)
        arrays = benchgen.device_range(
            tname, self.sf, cols, start, stop - start
        )
        blocks = {}
        for c, arr in zip(cols, arrays):
            typ, pool = schema[c]
            did = intern_dictionary(tuple(pool)) if pool is not None else None
            blocks[c] = Block(arr, typ, None, did)
        return Page.from_dict(blocks, pad_to=pad_to)
