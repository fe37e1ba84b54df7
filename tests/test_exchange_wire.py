"""Vectorized exchange: wire-format v2 (light-weight encodings + striped
parallel compression), codec/encoding capability negotiation, and the
pipelined concurrent exchange client (server/serde.py + server/exchange.py).

Covers the acceptance surface of the exchange rework: round-trip property
tests across types x NULLs x encoding paths x codec fallbacks, mixed-fleet
negotiation (zstd/v2 absent on one side), concurrent-pull ordering + ack,
corrupt-stripe-header rejection under MAX_PAGE_BYTES, and a multi-worker
cluster test asserting the client pulls from >= 2 producers CONCURRENTLY
(via exchange stats, not timing) with oracle-equal results."""

import threading
import time

import numpy as np
import pytest

import presto_tpu  # noqa: F401  (enables x64)
from presto_tpu import types as T
from presto_tpu.connectors.tpch import TpchCatalog
from presto_tpu.page import Block, Page
from presto_tpu.server import serde
from presto_tpu.server.exchange import ExchangeClient, ExchangeStats
from presto_tpu.server.serde import (
    deserialize_page,
    local_capabilities,
    negotiate,
    serialize_page,
)
from presto_tpu.server.worker import (
    OutputBuffers,
    WorkerMemoryPool,
    WorkerServer,
)

SF = 0.01


# -- round-trip property tests ----------------------------------------------


def _rng():
    return np.random.default_rng(7)


def _typed_pages():
    """Pages exercising every encoding path x types x NULLs."""
    rng = _rng()
    n = 3000
    # delta (sorted keys), dict (low NDV), off (bounded range), rle
    # (runs), const, bits (bools + null bitmaps), raw (random wide)
    base = Page.from_dict(
        {
            "sorted_key": np.cumsum(rng.integers(0, 50, n)).astype(np.int64),
            "low_ndv": rng.choice(
                np.array([3, 7, 60000], np.int64), n
            ),
            "bounded": rng.integers(-500, 500, n, np.int64),
            "runs": np.repeat(
                rng.integers(0, 9, n // 100 + 1), 100
            )[:n].astype(np.int64),
            "const_col": np.full(n, -17, np.int64),
            "wide": rng.integers(-(2**62), 2**62, n, np.int64),
            "flags": rng.random(n) < 0.3,
            "doubles": rng.standard_normal(n),
            "const_f": np.full(n, 2.5),
            "small_int": rng.integers(0, 100, n).astype(np.int32),
        }
    )
    # nulls on several columns
    valid = rng.random(n) > 0.2
    blocks = []
    for i, (name, b) in enumerate(zip(base.names, base.blocks)):
        if name in ("bounded", "doubles", "low_ndv"):
            import jax.numpy as jnp

            b = Block(b.data, b.type, jnp.asarray(valid), b.dict_id)
        blocks.append(b)
    pages = [Page(tuple(blocks), base.names, base.count)]
    # strings (dictionary), NaN, decimal two-lane, empty page
    import jax.numpy as jnp

    lanes = jnp.stack(
        [
            jnp.asarray(rng.integers(0, 10**6, 64), dtype=jnp.int64),
            jnp.asarray(np.zeros(64, np.int64)),
        ],
        axis=-1,
    )
    p2 = Page.from_dict(
        {
            "s": [None if i % 5 == 0 else f"v{i % 11}" for i in range(64)],
            "f": np.where(np.arange(64) % 7 == 0, np.nan, 1.25),
        }
    )
    pages.append(
        Page(
            p2.blocks + (Block(lanes, T.DecimalType(38, 2)),),
            p2.names + ("dec",),
            p2.count,
        )
    )
    pages.append(Page.from_dict({"x": np.zeros(0, np.int64)}))
    return pages


def _rows_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert len(ra) == len(rb)
        for va, vb in zip(ra, rb):
            if (
                isinstance(va, float)
                and isinstance(vb, float)
                and np.isnan(va)
                and np.isnan(vb)
            ):
                continue
            assert va == vb or str(va) == str(vb), (va, vb)


@pytest.mark.parametrize("caps_codecs", [None, ["zlib", "raw"], ["raw"]])
def test_roundtrip_types_nulls_encodings_codecs(caps_codecs):
    caps = None
    if caps_codecs is not None:
        caps = {"version": 2, "codecs": caps_codecs}
    for page in _typed_pages():
        wire = serialize_page(page, caps=caps)
        assert wire[:4] == b"PTP2"
        out = deserialize_page(wire)
        _rows_equal(out.to_pylist(), page.to_pylist())


def test_roundtrip_v1_peer_gets_v1_frame():
    for page in _typed_pages():
        wire = serialize_page(
            page, caps={"version": 1, "codecs": ["lz4", "zlib", "raw"]}
        )
        assert wire[:4] == b"PTP1"
        out = deserialize_page(wire)
        _rows_equal(out.to_pylist(), page.to_pylist())


def test_roundtrip_without_native_lz4(monkeypatch):
    """Codec fallback: no zstd wheel (this image) AND no native codec ->
    zlib; the frame still round-trips."""
    from presto_tpu import native

    monkeypatch.setattr(native, "available", lambda: False)
    page = _typed_pages()[0]
    wire = serialize_page(page, caps={"version": 2, "codecs": ["zstd", "lz4", "zlib", "raw"]})
    _rows_equal(deserialize_page(wire).to_pylist(), page.to_pylist())


def test_encodings_shrink_wire_bytes():
    page = _typed_pages()[0]
    v2 = serialize_page(page)
    v1 = serialize_page(
        page, caps={"version": 1, "codecs": ["lz4", "zlib", "raw"]}
    )
    assert len(v2) < len(v1), (len(v2), len(v1))


def test_wire_stats_record_ratio():
    st = serde.WireStats()
    page = _typed_pages()[0]
    wire = serialize_page(page, stats=st)
    deserialize_page(wire, stats=st)
    snap = st.snapshot()
    assert snap["raw_bytes"] > snap["wire_bytes"] > 0
    assert snap["compression_ratio"] and snap["compression_ratio"] > 1
    assert snap["encodings"]  # at least one light-weight encoding fired


# -- negotiation -------------------------------------------------------------


def test_negotiate_intersects_codecs_and_version():
    me = local_capabilities()
    out = negotiate([{"version": 2, "codecs": ["lz4", "raw"]}])
    assert out["version"] == min(2, me["version"])
    assert "zstd" not in out["codecs"] and "zlib" not in out["codecs"]
    # a peer advertising nothing degrades the fleet to v1 + baseline
    out = negotiate([None])
    assert out["version"] == 1
    assert set(out["codecs"]) <= {"lz4", "zlib", "raw"}
    # raw is always the floor
    out = negotiate([{"version": 2, "codecs": []}])
    assert out["codecs"] == ["raw"]


def test_serialize_honors_negotiated_codecs():
    """zstd must never hit the wire unless every peer advertised it."""
    page = Page.from_dict(
        {"a": np.tile(_rng().integers(0, 2**62, 2048, np.int64), 2)}
    )
    wire = serialize_page(page, caps={"version": 2, "codecs": ["zlib", "raw"]})
    assert wire[4] in (0, 1)  # zlib or raw, never zstd(3)/lz4(2)
    assert deserialize_page(wire).to_pylist() == page.to_pylist()


def test_mixed_fleet_cluster_negotiates_down():
    """One worker advertises wire v1 without zstd (an old build / missing
    wheel): the coordinator must negotiate the WHOLE fleet down so every
    page stays decodable, and results stay oracle-equal."""
    from presto_tpu.server.cluster import HttpClusterSession, NodeManager
    from presto_tpu.session import Session

    old_caps = {"version": 1, "codecs": ["lz4", "zlib", "raw"]}
    workers = [
        WorkerServer(TpchCatalog(sf=SF)).start(),
        WorkerServer(TpchCatalog(sf=SF), wire_caps=old_caps).start(),
    ]
    try:
        nodes = NodeManager([w.uri for w in workers], interval=3600)
        sess = HttpClusterSession(TpchCatalog(sf=SF), nodes)
        sql = (
            "select o_orderpriority, count(*) c from orders "
            "group by o_orderpriority order by o_orderpriority"
        )
        got = [tuple(r) for r in sess.query(sql).rows()]
        want = [tuple(r) for r in Session(TpchCatalog(sf=SF)).query(sql).rows()]
        assert got == want
        caps = sess.scheduler.stats.wire_caps
        assert caps["version"] == 1
        assert "zstd" not in caps["codecs"]
    finally:
        for w in workers:
            w.stop()


# -- striped frame: corrupt-header rejection --------------------------------


def _stripe_frame(codec, stripes):
    out = serde._MAGIC2 + bytes([codec]) + len(stripes).to_bytes(4, "little")
    for orig, blob in stripes:
        out += orig.to_bytes(4, "little") + len(blob).to_bytes(4, "little")
    for _orig, blob in stripes:
        out += blob
    return out


def test_corrupt_stripe_headers_rejected():
    # stripe count bomb
    evil = serde._MAGIC2 + b"\x02" + (1 << 31).to_bytes(4, "little")
    with pytest.raises(ValueError, match="stripe count"):
        deserialize_page(evil)
    # declared size past MAX_PAGE_BYTES
    big = serde.MAX_PAGE_BYTES + 1
    evil = _stripe_frame(0, [(big, b"\x00" * 16)])
    with pytest.raises(ValueError, match="page cap"):
        deserialize_page(evil)
    # many stripes summing past the cap under a small test bound (raw
    # codec: the per-stripe inflation bound does not apply, so the SUM
    # check is what rejects it)
    old = serde.MAX_PAGE_BYTES
    serde.MAX_PAGE_BYTES = 1 << 16
    try:
        stripes = [((1 << 14), b"\x00" * 8)] * 8
        with pytest.raises(ValueError, match="page cap"):
            deserialize_page(_stripe_frame(0, stripes))
    finally:
        serde.MAX_PAGE_BYTES = old
    # implausible per-stripe inflation (lz4 bound)
    evil = _stripe_frame(2, [((1 << 25), b"\x00" * 64)])
    with pytest.raises(ValueError, match="implausible"):
        deserialize_page(evil)
    # raw stripe shorter than its declared original size
    evil = _stripe_frame(0, [(32, b"\x00" * 8)])
    with pytest.raises(ValueError, match="unexpected size"):
        deserialize_page(evil)
    # payload bytes missing vs the declared compressed lengths
    evil = _stripe_frame(0, [(8, b"\x00" * 8)])[:-4]
    with pytest.raises(ValueError, match="length mismatch"):
        deserialize_page(evil)
    # truncated stripe table
    evil = serde._MAGIC2 + b"\x00" + (4).to_bytes(4, "little") + b"\x00" * 8
    with pytest.raises(ValueError, match="truncated stripe header"):
        deserialize_page(evil)
    # unknown codec id
    evil = _stripe_frame(9, [(8, b"\x00" * 8)])
    with pytest.raises(ValueError, match="unknown page codec"):
        deserialize_page(evil)


def test_corrupt_header_decode_amplification_rejected():
    """A tiny frame whose JSON header declares a huge column shape with
    an expanding encoding (const) must be rejected BEFORE materializing
    — per column and cumulatively across many columns."""
    import json as _json

    def body_frame(header: dict, bufs):
        h = _json.dumps(header).encode()
        raw = len(h).to_bytes(4, "little") + h
        for b in bufs:
            raw += len(b).to_bytes(8, "little") + b
        return (
            serde._MAGIC2 + b"\x00" + (1).to_bytes(4, "little")
            + len(raw).to_bytes(4, "little") + len(raw).to_bytes(4, "little")
            + raw
        )

    col = {
        "name": "a", "type": "bigint", "dtype": "<i8",
        "shape": [1 << 40], "valid": False, "dict_id": None,
        "lengths": False, "elem_valid": False, "enc": [{"k": "const"}],
    }
    evil = body_frame(
        {"count": 8, "columns": [col], "dictionaries": {}}, [b"\x00" * 8]
    )
    with pytest.raises(ValueError, match="page cap"):
        deserialize_page(evil)
    # cumulative: per-column-legal shapes that sum past the cap
    old = serde.MAX_PAGE_BYTES
    serde.MAX_PAGE_BYTES = 1 << 20
    try:
        ncols = 20
        cols = []
        for i in range(ncols):
            cols.append({
                "name": f"c{i}", "type": "bigint", "dtype": "<i8",
                "shape": [(1 << 20) // 8 - 8], "valid": False,
                "dict_id": None, "lengths": False, "elem_valid": False,
                "enc": [{"k": "const"}],
            })
        evil = body_frame(
            {"count": 8, "columns": cols, "dictionaries": {}},
            [b"\x00" * 8] * ncols,
        )
        with pytest.raises(ValueError, match="page cap"):
            deserialize_page(evil)
    finally:
        serde.MAX_PAGE_BYTES = old


def test_multi_stripe_roundtrip(monkeypatch):
    """A body larger than the stripe size splits into several stripes
    that decompress (concurrently) back to the identical page."""
    monkeypatch.setattr(serde, "_STRIPE_BYTES", 64 << 10)
    rng = _rng()
    # repeat period (8KB) well inside LZ4's 64KB match window, so every
    # stripe compresses even though the values defeat the encodings
    piece = rng.integers(0, 2**62, 1024, np.int64)
    page = Page.from_dict({"a": np.tile(piece, 80)})
    wire = serialize_page(page)
    # the codec byte is whichever codec this installation selects
    # (zstd where the module is present, else native lz4, else zlib)
    assert wire[:4] == b"PTP2"
    assert wire[4] == serde._CODEC_IDS[serde._pick_codec(None)] != 0
    nstripes = int.from_bytes(wire[5:9], "little")
    assert nstripes > 1, "expected a multi-stripe frame"
    assert deserialize_page(wire).to_pylist() == page.to_pylist()


# -- concurrent pull: ordering, acks, stats ---------------------------------


def _buffer_worker(pages_by_buffer):
    """A WorkerServer with a hand-built task exposing pre-serialized
    pages (no fragment execution), like test_streaming_exchange does."""
    from presto_tpu.server.worker import TaskState

    w = WorkerServer(TpchCatalog(sf=0.002))
    t = TaskState(query_id="qx")
    t.buffers = OutputBuffers(w.pool, "qx", threading.Event(), bound=None)
    for buf_id, datas in pages_by_buffer.items():
        for d in datas:
            t.buffers.put(buf_id, d)
    t.buffers.finish()
    t.state = "FINISHED"
    t.done.set()
    w.tasks["tx"] = t
    return w.start()


def _tag_page(producer: int, seq: int) -> bytes:
    return serialize_page(
        Page.from_dict(
            {
                "producer": np.full(8, producer, np.int64),
                "seq": np.full(8, seq, np.int64),
            }
        )
    )


def test_concurrent_pull_preserves_per_producer_order_and_acks():
    n_pages = 12
    workers = [
        _buffer_worker({0: [_tag_page(i, s) for s in range(n_pages)]})
        for i in range(3)
    ]
    try:
        stats = ExchangeStats()
        client = ExchangeClient(
            [(w.uri, "tx", 0) for w in workers],
            ack=True,
            max_response_bytes=1 << 12,  # force several responses each
            stats=stats,
        )
        seen = {i: [] for i in range(3)}
        for page in client.pages():
            rows = page.to_pylist()
            seen[rows[0][0]].append(rows[0][1])
        # every page arrived exactly once, per-producer token order intact
        for i in range(3):
            assert seen[i] == list(range(n_pages)), seen[i]
        snap = stats.snapshot()
        assert snap["pages"] == 3 * n_pages
        assert snap["sources"] == 3
        assert snap["peak_concurrent"] >= 2  # genuinely concurrent pullers
        assert snap["responses"] >= 3
        # acks drained every producer buffer
        deadline = time.time() + 5
        for w in workers:
            while time.time() < deadline and w.tasks["tx"].buffers._unacked:
                time.sleep(0.01)
            assert w.tasks["tx"].buffers._unacked == 0
    finally:
        for w in workers:
            w.stop()


def test_pull_failure_attributed_to_location():
    from presto_tpu.server.exchange import ExchangeError

    w = _buffer_worker({0: [_tag_page(0, 0)]})
    bad_uri = "http://127.0.0.1:1"  # nothing listens
    try:
        client = ExchangeClient(
            [(w.uri, "tx", 0), (bad_uri, "t_dead", 0)], ack=True
        )
        with pytest.raises(ExchangeError, match="t_dead"):
            for _ in client.pages():
                pass
    finally:
        w.stop()


def test_multi_page_response_batching():
    """max_bytes batching: one HTTP response carries several pages."""
    w = _buffer_worker({0: [_tag_page(0, s) for s in range(10)]})
    try:
        from presto_tpu.server.exchange import fetch_pages

        pages, complete, ready = fetch_pages(
            w.uri, "tx", 0, 0, max_bytes=1 << 20
        )
        assert ready and complete and len(pages) == 10
        # an un-budgeted (legacy) request still gets exactly one page
        pages, complete, ready = fetch_pages(w.uri, "tx", 0, 0)
        assert ready and len(pages) == 1 and not complete
    finally:
        w.stop()


# -- acceptance: pipelined client over a live cluster ------------------------


def test_cluster_pipelined_pull_concurrent_and_oracle_equal():
    """The pipelined exchange client must pull from >= 2 producers
    concurrently (asserted via exchange stats, not timing) and produce
    results oracle-equal to single-node execution."""
    from presto_tpu.server.cluster import HttpClusterSession, NodeManager
    from presto_tpu.session import Session

    workers = [
        WorkerServer(TpchCatalog(sf=SF), buffer_bound=64 << 10).start()
        for _ in range(2)
    ]
    try:
        nodes = NodeManager([w.uri for w in workers], interval=3600)
        sess = HttpClusterSession(TpchCatalog(sf=SF), nodes)
        sql = (
            "select l_returnflag, l_linestatus, count(*) c, "
            "sum(l_quantity) q from lineitem "
            "group by l_returnflag, l_linestatus "
            "order by l_returnflag, l_linestatus"
        )
        got = [tuple(r) for r in sess.query(sql).rows()]
        want = [
            tuple(r) for r in Session(TpchCatalog(sf=SF)).query(sql).rows()
        ]
        assert got == want
        ex = sess.scheduler.stats.exchange
        assert ex, "no exchange stats recorded"
        gather = max(ex.values(), key=lambda e: e["sources"])
        assert gather["sources"] >= 2
        assert gather["peak_concurrent"] >= 2, gather
        assert gather["pages"] >= 2 and gather["wire_bytes"] > 0
        # producer-side encode stats polled from task statuses
        assert gather["producer"]["wire_bytes"] > 0
        assert sess.scheduler.stats.wire_caps["version"] >= 1
    finally:
        for w in workers:
            w.stop()
