"""Runtime dynamic filters: build-side summaries pushed into probe scans.

Re-designed equivalent of the reference's dynamic filtering stack
(presto-main/.../operator/DynamicFilterSourceOperator collecting build-side
values, sql/planner/optimizations/ PredicatePushdown's dynamic filter
placeholders, LocalDynamicFiltersCollector waiting probe-side scans) —
the signature optimization of the Presto lineage for selective joins.

TPU-first reduction: after a join's build side materializes, the executor
derives ONE per-key summary on device and publishes it under a planner-
assigned filter id (plan/rules.annotate_dynamic_filters). Strategy picked
from the build side's real cardinality (the executors are host-driven and
adaptive, so this is a perfect-information choice, not an estimate):

  minmax   exact min/max of the build keys — always derived for ordered
           storage (ints, dates, short decimals, floats); doubles as the
           SPI pruning hint (ge/le conjuncts).
  inlist   exact sorted distinct values when build NDV <= in_limit —
           membership by vectorized binary search; zero false positives;
           exported as the SPI `in` hint so connectors prune row groups.
  bloom    blocked bloom filter over the engine row hash
           (ops/bloomfilter.py) otherwise — no false negatives, ~1-2%
           false positives, queried fully vectorized on device.

Application is fused into the probe side's existing Filter/TableScan
kernels (exec/executor.py, exec/stream.py): the dynamic mask ANDs into the
scan filter's keep mask so pruning costs no extra compaction pass. Probe
rows with NULL keys are pruned too (SQL equi-join semantics: NULL never
matches) — only INNER joins and plain semi joins are annotated, where
dropping non-matching probe rows early is an identity on the result.

Cross-task (server/cluster.py): build-stage workers accumulate HOST
summaries over their output pages (HostFilterAccumulator), the coordinator
merges per-task summaries with a bounded wait and ships them in probe-stage
task specs; a slow or failed build stage degrades to proceed-without-filter.
Everything runs behind the `dynamic_filter` kernel circuit breaker
(exec/breaker.py) with the legacy no-filter path as fallback.
"""

from __future__ import annotations

import base64
import dataclasses
import os
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..obs.span import host_read
from ..ops.bloomfilter import (
    bloom_build,
    bloom_build_host,
    bloom_merge_host,
    bloom_query,
    choose_log2_bits,
)
from ..ops.hashing import hash_column


def dynamic_filtering_enabled() -> bool:
    return os.environ.get("PRESTO_TPU_DYNFILTER", "1") != "0"


def in_list_limit() -> int:
    """Build NDV at or under this derives the exact IN-list filter."""
    return int(os.environ.get("PRESTO_TPU_DYNFILTER_IN_LIMIT", "8192"))


# IN-lists longer than this are not exported as SPI hints (a connector
# comparing thousands of values per row group beats nobody)
SPI_IN_LIMIT = 256


# An IN-list of at most this many values is tested on a large page
# (ops/filter.LARGE_PAGE_ROWS) by comparing every row with every value:
# `searchsorted` is a gather per row and step, ~15 ns each on the v5e,
# 7.7 s for 75M rows against 111 values (PR 33), where the compares are
# a few passes over the column.
SMALL_INLIST = 256


@jax.jit
def _inlist_mask(values, data):
    """`data` IN `values` by comparison, eight values a pass. `values` is
    padded (by repeating a member) to a multiple of eight."""

    def body(i, m):
        v = jax.lax.dynamic_slice(values, (i * 8,), (8,))
        hit = data == v[0]
        for j in range(1, 8):
            hit = hit | (data == v[j])
        return m | hit

    return jax.lax.fori_loop(
        0, values.shape[0] // 8, body, jnp.zeros(data.shape, jnp.bool_)
    )


def _is_ordered_storage(typ) -> bool:
    """Types whose 1-D storage ints/floats order like the logical value."""
    return isinstance(
        typ,
        (
            T.BigintType, T.IntegerType, T.SmallintType, T.TinyintType,
            T.DateType, T.TimestampType, T.DoubleType, T.RealType,
        ),
    ) or (isinstance(typ, T.DecimalType) and not typ.is_long)


def _storage_to_logical(typ, v):
    """One STORAGE scalar -> the logical Python value the SPI expects
    (datetime.date for DATE, Decimal for decimals — matching what
    file-format statistics expose)."""
    import datetime as pydt
    import decimal as pydec

    if isinstance(typ, T.DateType):
        return pydt.date(1970, 1, 1) + pydt.timedelta(days=int(v))
    if isinstance(typ, T.DecimalType):
        return pydec.Decimal(int(v)).scaleb(-typ.scale)
    if isinstance(typ, (T.DoubleType, T.RealType)):
        return float(v)
    return int(v)


@dataclasses.dataclass
class DynamicFilter:
    """One derived build-side summary, queryable on device.

    `lo`/`hi`/`values`/`bloom_words` are device arrays (or None); the
    *_host twins are numpy/python values used for SPI hints and wire
    serialization. A filter always carries minmax when the key type is
    ordered; exactly one of values/bloom_words when membership is on."""

    strategy: str  # 'minmax' | 'inlist' | 'bloom'
    key_type: T.Type
    build_rows: int
    lo: Optional[jnp.ndarray] = None  # storage-unit scalars
    hi: Optional[jnp.ndarray] = None
    values: Optional[jnp.ndarray] = None  # sorted distinct storage values
    bloom_words: Optional[jnp.ndarray] = None
    log2_bits: int = 0
    lo_host: Optional[object] = None  # storage-unit numpy scalars
    hi_host: Optional[object] = None
    values_host: Optional[np.ndarray] = None
    str_values: Optional[Tuple[str, ...]] = None  # varchar IN-list (logical)
    empty_build: bool = False  # no live build rows: probe matches nothing

    # -- device application --

    def mask(self, val) -> jnp.ndarray:
        """Per-row keep mask over a probe key Val/Block: False rows can
        NEVER match the build side (no false negatives by construction)."""
        data = val.data
        if self.empty_build:
            return jnp.zeros(data.shape[:1], jnp.bool_)
        if self.str_values is not None:
            keep = self._varchar_mask(val)
        else:
            keep = jnp.ones(data.shape[:1], jnp.bool_)
            if self.lo is not None and data.ndim == 1:
                keep = (data >= self.lo) & (data <= self.hi)
            if self.values is not None and data.ndim == 1:
                from ..ops.filter import LARGE_PAGE_ROWS

                k = self.values.shape[0]
                if data.shape[0] >= LARGE_PAGE_ROWS and k <= SMALL_INLIST:
                    # padded to a power of two: one program a bucket
                    padded = jnp.pad(
                        self.values,
                        (0, max(8, 1 << (k - 1).bit_length()) - k),
                        mode="edge",
                    )
                    keep = keep & _inlist_mask(padded, data)
                else:
                    pos = jnp.searchsorted(self.values, data)
                    pos = jnp.minimum(pos, k - 1)
                    keep = keep & (self.values[pos] == data)
            elif self.bloom_words is not None:
                h = hash_column(data)
                keep = keep & bloom_query(self.bloom_words, h, self.log2_bits)
        if val.valid is not None:
            keep = keep & val.valid  # NULL keys never equi-match
        return keep

    def _varchar_mask(self, val) -> jnp.ndarray:
        """Varchar membership via the probe DICTIONARY: a host lookup table
        over the (small) dictionary, gathered by code — O(dict) host work,
        O(rows) device gather. Codes are dictionary-local, so comparing
        them against build codes directly would be wrong; logical strings
        are the cross-dictionary currency."""
        from ..page import dictionary_by_id

        if val.dict_id is None:
            return jnp.ones(val.data.shape[:1], jnp.bool_)
        entries = dictionary_by_id(val.dict_id)
        members = frozenset(self.str_values)
        lut = np.fromiter(
            (s in members for s in entries), np.bool_, count=len(entries)
        )
        if not len(lut):
            return jnp.zeros(val.data.shape[:1], jnp.bool_)
        codes = jnp.clip(val.data, 0, len(lut) - 1)
        return jnp.asarray(lut)[codes]

    # -- SPI hints --

    def spi_conjuncts(self, source_col: str, typ=None) -> List[tuple]:
        """(column, op, logical value) pruning hints for connector scans
        (connectors/spi.py Predicate). Bloom filters export only their
        min/max envelope — a connector cannot evaluate the bit array.

        `typ` overrides the stored key type — wire-reconstructed filters
        (cluster cross-task) carry no type, and emitting raw STORAGE ints
        as logical values would wrongly refute units for decimal/date
        keys; with no type from either source, no hints are emitted."""
        t = typ if typ is not None else self.key_type
        if self.str_values is None and t is None:
            return []
        out: List[tuple] = []
        if self.str_values is not None and len(self.str_values) <= SPI_IN_LIMIT:
            out.append((source_col, "in", tuple(self.str_values)))
            return out
        if self.values_host is not None and len(self.values_host) <= SPI_IN_LIMIT:
            out.append(
                (
                    source_col,
                    "in",
                    tuple(_storage_to_logical(t, v) for v in self.values_host),
                )
            )
        if self.lo_host is not None:
            out.append((source_col, "ge", _storage_to_logical(t, self.lo_host)))
            out.append((source_col, "le", _storage_to_logical(t, self.hi_host)))
        return out

    def describe(self) -> str:
        if self.empty_build:
            return "empty"
        if self.strategy == "bloom":
            return f"bloom(n={self.build_rows},bits=2^{self.log2_bits})"
        if self.strategy == "inlist":
            n = (
                len(self.str_values)
                if self.str_values is not None
                else int(self.values.shape[0])
            )
            return f"inlist({n})"
        return f"minmax(n={self.build_rows})"


# ---------------------------------------------------------------------------
# derivation (device)
# ---------------------------------------------------------------------------


def _key_stats(data, valid):
    """(n, ndv, sorted_with_sentinel) in one device program. `data` must be
    1-D; the sort sends invalid rows to the dtype max sentinel so live
    distinct values occupy a prefix."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        sentinel = jnp.asarray(jnp.inf, data.dtype)
    else:
        sentinel = jnp.asarray(jnp.iinfo(data.dtype).max, data.dtype)
    # one operand: stability changes nothing but the TPU compile time
    s = jnp.sort(jnp.where(valid, data, sentinel), stable=False)
    n = jnp.sum(valid.astype(jnp.int64))
    cap = data.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int64)
    boundary = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), s[1:] != s[:-1]]
    )
    ndv = jnp.sum((boundary & (idx < n)).astype(jnp.int64))
    return n, ndv, s


def derive_filter(val, live: jnp.ndarray) -> Optional[DynamicFilter]:
    """Summarize one build-side key column (a Val/Block) into a
    DynamicFilter, or None when the type has no cheap summary.

    Host syncs: ONE batched fetch of 4 scalars to pick the strategy (the
    build side is already materialized, so this races nothing), plus the
    strategy's own payload. The caller runs this behind the
    `dynamic_filter` circuit breaker."""
    data = val.data
    valid = live if val.valid is None else (live & val.valid)
    typ = val.type

    if isinstance(typ, T.VarcharType):
        return _derive_varchar(val, valid)
    if data.ndim != 1 or data.dtype == jnp.bool_:
        return None  # long-decimal lanes / booleans: not worth a filter
    if not _is_ordered_storage(typ):
        return None
    if jnp.issubdtype(data.dtype, jnp.floating):
        # NaN build keys never equi-match (IEEE NaN != NaN, which is also
        # the engine's join compare) — and a NaN min/max would prune every
        # probe row. Treat them as absent from the build side.
        valid = valid & ~jnp.isnan(data)

    n_d, ndv_d, s = _key_stats(data, valid)
    n, ndv = (int(host_read(x)) for x in (n_d, ndv_d))
    if n == 0:
        return DynamicFilter(
            "minmax", typ, 0, empty_build=True
        )
    lo = s[0]
    hi = jnp.max(jnp.where(valid, data, s[0]))
    lo_h, hi_h = host_read(lo), host_read(hi)
    if ndv <= in_list_limit():
        boundary = jnp.concatenate([jnp.ones(1, jnp.bool_), s[1:] != s[:-1]])
        pos = jnp.nonzero(boundary, size=ndv, fill_value=0)[0]
        values = s[pos]
        return DynamicFilter(
            "inlist", typ, n, lo=lo, hi=hi, values=values,
            lo_host=lo_h, hi_host=hi_h,
            values_host=host_read(values),
        )
    log2_bits = choose_log2_bits(ndv)
    words = bloom_build(hash_column(data), valid, log2_bits)
    return DynamicFilter(
        "bloom", typ, n, lo=lo, hi=hi, bloom_words=words,
        log2_bits=log2_bits, lo_host=lo_h, hi_host=hi_h,
    )


def _derive_varchar(val, valid) -> Optional[DynamicFilter]:
    """Varchar keys: dictionary codes are dictionary-LOCAL, so the only
    safe cross-column summary is the logical string set. Distinct codes
    among live rows map through the build dictionary; NDV above the limit
    means no filter (a bloom over codes would be wrong across dicts)."""
    from ..page import dictionary_by_id

    if val.dict_id is None:
        return None
    entries = dictionary_by_id(val.dict_id)
    if len(entries) > in_list_limit():
        return None
    nbits = max(len(entries), 1)
    seen = (
        jnp.zeros(nbits + 1, jnp.bool_)
        .at[jnp.where(valid, jnp.clip(val.data, 0, nbits - 1), nbits)]
        .set(True)
    )
    seen_h = host_read(seen[:nbits])
    n = int(seen_h.sum())
    if n == 0:
        return DynamicFilter("minmax", val.type, 0, empty_build=True)
    strs = tuple(s for s, flag in zip(entries, seen_h) if flag)
    return DynamicFilter("inlist", val.type, n, str_values=strs)


# ---------------------------------------------------------------------------
# context: publish / consume across one query
# ---------------------------------------------------------------------------


class DynamicFilterContext:
    """Per-query registry of derived filters. Single-process executors
    publish synchronously (the build side always completes before the
    probe side streams), so `get` never blocks; the bounded wait lives in
    the cluster coordinator, which resolves summaries between stages."""

    def __init__(self):
        self._filters: Dict[str, DynamicFilter] = {}
        self._lock = threading.Lock()
        # ids applied at a scan/filter (so joins skip the pre-probe pass)
        self.consumed: set = set()
        # observability: fid -> rows pruned at scan/filter vs pre-probe
        self.scan_pruned: Dict[str, int] = {}
        self.preprobe_pruned: Dict[str, int] = {}
        self.wait_s: float = 0.0  # cross-task filter wait (cluster path)

    def publish(self, fid: str, df: DynamicFilter) -> None:
        with self._lock:
            self._filters[fid] = df

    def get(self, fid: str) -> Optional[DynamicFilter]:
        with self._lock:
            return self._filters.get(fid)

    def note_pruned(self, fid: str, n: int, where: str = "scan") -> None:
        with self._lock:
            book = self.scan_pruned if where == "scan" else self.preprobe_pruned
            book[fid] = book.get(fid, 0) + int(n)

    def total_pruned(self) -> int:
        with self._lock:
            return sum(self.scan_pruned.values()) + sum(
                self.preprobe_pruned.values()
            )

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "filters": {
                    fid: df.describe() for fid, df in self._filters.items()
                },
                "scan_pruned": dict(self.scan_pruned),
                "preprobe_pruned": dict(self.preprobe_pruned),
                "wait_s": self.wait_s,
            }

    def reset(self) -> None:
        with self._lock:
            self._filters.clear()
            self.consumed.clear()
            self.scan_pruned.clear()
            self.preprobe_pruned.clear()
            self.wait_s = 0.0


# ---------------------------------------------------------------------------
# host accumulation + wire summaries (cluster cross-task shipping)
# ---------------------------------------------------------------------------


class HostFilterAccumulator:
    """Accumulates a filter summary on the HOST over pages as a worker task
    produces them (server/worker.py) — numpy only, no device work on the
    output path. Varchar and long-decimal channels are skipped (dictionary
    codes are process-local; 2-lane storage has no scalar summary)."""

    def __init__(self, channel: str):
        self.channel = channel
        self.typ = None
        self.count = 0
        self.lo = None
        self.hi = None
        self.values: Optional[set] = set()  # None once overflowed
        self.words: Optional[np.ndarray] = None
        self.log2_bits = choose_log2_bits(in_list_limit() * 4)
        self.unsupported = False

    def add_page(self, page) -> None:
        if self.unsupported or self.channel not in page.names:
            if self.channel not in page.names:
                self.unsupported = True
            return
        b = page.block(self.channel)
        n = int(host_read(page.count))
        data = host_read(b.data[:n])
        valid = None if b.valid is None else host_read(b.valid[:n])
        self.add_numpy(data, valid, b.type)

    def add_numpy(self, data: np.ndarray, valid, typ) -> None:
        """Accumulate raw host columns (HostTable spill stores and worker
        output pages share this path)."""
        if self.unsupported:
            return
        if data.ndim != 1 or isinstance(typ, T.VarcharType) or not (
            _is_ordered_storage(typ)
        ):
            self.unsupported = True
            return
        self.typ = typ
        if valid is not None:
            data = data[valid]
        if np.issubdtype(data.dtype, np.floating):
            data = data[~np.isnan(data)]  # NaN never equi-matches
        if not len(data):
            return
        self.count += len(data)
        lo, hi = data.min(), data.max()
        self.lo = lo if self.lo is None else min(self.lo, lo)
        self.hi = hi if self.hi is None else max(self.hi, hi)
        if self.values is not None:
            self.values.update(np.unique(data).tolist())
            if len(self.values) > in_list_limit():
                self.values = None  # overflow: bloom only from here on
        self.words = bloom_build_host(
            _host_hash(data), self.log2_bits, self.words
        )

    def summary(self) -> Optional[dict]:
        """JSON-able wire summary, or None when nothing useful accrued."""
        if self.unsupported or self.typ is None:
            return None
        out = {
            "count": self.count,
            "type": repr(self.typ),
            "lo": _json_scalar(self.lo),
            "hi": _json_scalar(self.hi),
            "float": isinstance(self.typ, (T.DoubleType, T.RealType)),
            # REAL keys hash by their float32 bit pattern: a values->bloom
            # conversion must re-hash at the same width
            "real": isinstance(self.typ, T.RealType),
        }
        if self.count == 0:
            out["empty"] = True
            return out
        if self.values is not None:
            out["values"] = [_json_scalar(v) for v in sorted(self.values)]
        else:
            out["bloom_b64"] = base64.b64encode(
                self.words.tobytes()
            ).decode()
            out["log2_bits"] = self.log2_bits
        return out


def _host_hash(data: np.ndarray) -> np.ndarray:
    """Host replica of ops/hashing.hash_column for 1-D numeric storage —
    bit-identical so host-built blooms answer device-hashed queries."""
    if np.issubdtype(data.dtype, np.floating):
        data = np.where(data == 0, np.zeros_like(data), data)
        data = np.where(np.isnan(data), np.full_like(data, np.nan), data)
        width = data.dtype.itemsize
        bits = data.view({4: np.uint32, 8: np.uint64}[width]).astype(np.uint64)
    else:
        bits = data.astype(np.uint64)
    with np.errstate(over="ignore"):
        x = bits
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _json_scalar(v):
    if v is None:
        return None
    if isinstance(v, (np.floating, float)):
        return float(v)
    return int(v)


def merge_summaries(parts: List[dict]) -> Optional[dict]:
    """Merge per-task wire summaries (min of lo, max of hi, union of value
    sets, OR of blooms — a part that fell back to bloom forces bloom). Any
    missing part means a task's keys are unaccounted for and the filter
    cannot be trusted — return None (no false negatives, ever)."""
    if not parts or any(not p for p in parts):
        return None
    out = dict(parts[0])
    for p in parts[1:]:
        if p.get("type") != out.get("type"):
            return None
        out["count"] = out.get("count", 0) + p.get("count", 0)
        for k, pick in (("lo", min), ("hi", max)):
            a, b = out.get(k), p.get(k)
            out[k] = pick(a, b) if a is not None and b is not None else (
                a if b is None else b
            )
        if "values" in out and "values" in p:
            merged = sorted(set(out["values"]) | set(p["values"]))
            if len(merged) <= in_list_limit():
                out["values"] = merged
                continue
        # membership degrades to an OR of blooms (values parts convert by
        # re-hashing — BEFORE dropping them from the summaries)
        wa = _words_of(out)
        wb = _words_of(p)
        out.pop("values", None)
        if wa is None or wb is None or len(wa) != len(wb):
            out.pop("bloom_b64", None)
            out.pop("log2_bits", None)
        else:
            out["bloom_b64"] = base64.b64encode(
                bloom_merge_host(wa, wb).tobytes()
            ).decode()
            out["log2_bits"] = (
                out.get("log2_bits")
                or p.get("log2_bits")
                or choose_log2_bits(in_list_limit() * 4)
            )
    out["empty"] = out.get("count", 0) == 0
    if (
        not out.get("empty")
        and "values" not in out
        and "bloom_b64" not in out
        and out.get("lo") is None
    ):
        return None
    return out


def _words_of(summary: dict) -> Optional[np.ndarray]:
    b64 = summary.get("bloom_b64")
    if b64 is None:
        # a pure value-set part converts to a bloom for OR-merging; the
        # hash must use the key's STORAGE width (REAL keys hash their
        # float32 bit pattern — re-hashing as float64 would insert
        # different bits than the device probe queries)
        vals = summary.get("values")
        if vals is None:
            return None
        lb = summary.get("log2_bits") or choose_log2_bits(
            in_list_limit() * 4
        )
        if summary.get("real"):
            dt = np.float32
        elif summary.get("float"):
            dt = np.float64
        else:
            dt = np.int64
        return bloom_build_host(_host_hash(np.asarray(vals, dt)), lb)
    return np.frombuffer(
        base64.b64decode(b64), np.uint32
    ).copy()


def filter_from_summary(summary: dict, key_type) -> Optional[DynamicFilter]:
    """Reconstruct a device-queryable DynamicFilter from a wire summary on
    the probe-side worker."""
    if summary is None:
        return None
    if summary.get("empty"):
        return DynamicFilter("minmax", key_type, 0, empty_build=True)
    dt = np.float64 if summary.get("float") else np.int64
    lo_h, hi_h = summary.get("lo"), summary.get("hi")
    lo = hi = None
    if lo_h is not None:
        lo = jnp.asarray(dt(lo_h))
        hi = jnp.asarray(dt(hi_h))
    if "values" in summary:
        values = np.asarray(summary["values"], dt)
        return DynamicFilter(
            "inlist", key_type, summary.get("count", len(values)),
            lo=lo, hi=hi, values=jnp.asarray(values),
            lo_host=lo_h, hi_host=hi_h, values_host=values,
        )
    if "bloom_b64" in summary:
        words = np.frombuffer(
            base64.b64decode(summary["bloom_b64"]), np.uint32
        )
        return DynamicFilter(
            "bloom", key_type, summary.get("count", 0), lo=lo, hi=hi,
            bloom_words=jnp.asarray(words),
            log2_bits=int(summary["log2_bits"]),
            lo_host=lo_h, hi_host=hi_h,
        )
    if lo is None:
        return None
    return DynamicFilter(
        "minmax", key_type, summary.get("count", 0), lo=lo, hi=hi,
        lo_host=lo_h, hi_host=hi_h,
    )
