"""Hand-written Pallas TPU kernel: single-pass Q1-shaped grouped aggregation.

The flagship custom kernel (the role the reference gives hand-tuned paths
like HandTpchQuery1.java + MultiChannelGroupByHash.java): ONE pass over the
raw int32 columns computes every TPC-H Q1 aggregate for all 6 groups —
where the XLA composition (ops/aggregate.grouped_aggregate_direct) makes
G x A masked passes.

Exactness without int64 (Pallas TPU has no 64-bit reductions): every
per-row contribution is decomposed into 16-bit limb channels, each block
of 16384 rows sums channels in int32 (bound 2^16 * 2^14 = 2^30 < int32
max), and per-block partial tiles are combined OUTSIDE the kernel in
int64/two-lane arithmetic — so decimal(38) sums stay exact at any scale
factor.

Layout: each (n,) column is viewed as (n/128, 128); the grid walks row
blocks of (128, 128) = 16384 rows; the kernel emits a (128, 128) partial
tile per block: row g*16+k holds the PER-LANE partial sums of limb
channel k masked to group g (6 live groups x 14 live channels, padded to
128 rows). Only sublane (axis 0) reductions happen in-kernel — Mosaic
lowers `jnp.sum(axis=0)` natively, and cross-lane reduction is exactly
what the VPU is worst at; the final 128-lane fold runs in XLA int64
outside the kernel (combine()).

DEPLOYMENT: CPU CI validates in interpret mode (exact match against the
XLA composition, tests/test_pallas_agg.py) and tests/test_tpu_compile.py
compiles the kernel for a described v5e at SF1 width; on a TPU backend
bench.py times it compiled (`q1_pallas_ms`), where it is expected to
collapse the G x A masked passes of the XLA path into one streaming
pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLK_ROWS = 16384  # 128 x 128 rows per grid step
_G = 6  # returnflag {A,N,R} x linestatus {F,O}
_CH = 14  # limb channels, see combine()


def _kernel(cut_ref, cnt_ref, qty_ref, price_ref, disc_ref, tax_ref,
            rf_ref, ls_ref, ship_ref, out_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    qty = qty_ref[:]
    price = price_ref[:]
    disc = disc_ref[:]
    tax = tax_ref[:]
    rf = rf_ref[:]
    ls = ls_ref[:]
    ship = ship_ref[:]

    # liveness: global row index < count, and the fused Q1 filter
    base = i * BLK_ROWS
    rows = jax.lax.broadcasted_iota(jnp.int32, qty.shape, 0) * 128
    lanes = jax.lax.broadcasted_iota(jnp.int32, qty.shape, 1)
    gidx = base + rows + lanes
    live = (gidx < cnt_ref[0]) & (ship <= cut_ref[0])

    gid = rf * 2 + ls  # direct mixed-radix group id

    m = 100 - disc  # (1 - l_discount) in scale-2 units
    t = 100 + tax  # (1 + l_tax) in scale-2 units
    p0 = price & 0xFFFF
    p1 = price >> 16
    a = p0 * m  # < 2^23
    b = p1 * m  # < 2^21, weight 2^16
    at = a * t  # < 2^30
    bt = b * t  # < 2^28, weight 2^16

    channels = (
        jnp.ones_like(qty),  # 0: count
        qty & 0xFFFF,  # 1
        qty >> 16,  # 2
        p0,  # 3
        p1,  # 4
        disc,  # 5
        a & 0xFFFF,  # 6: disc_price limbs
        a >> 16,  # 7  (weight 2^16)
        b & 0xFFFF,  # 8  (weight 2^16)
        b >> 16,  # 9  (weight 2^32)
        at & 0xFFFF,  # 10: charge limbs
        at >> 16,  # 11 (weight 2^16)
        bt & 0xFFFF,  # 12 (weight 2^16)
        bt >> 16,  # 13 (weight 2^32)
    )

    zero = jnp.int32(0)
    # sublane-only reductions: each (group, channel) pair fills row g*16+k
    # with per-lane sums (int32 is safe: 128 rows x <2^16 limbs < 2^23).
    # The generic lax.reduce primitive has no Mosaic lowering; jnp.sum
    # with an explicit int32 dtype lowers to the supported reduce_sum.
    rows_out = []
    for g in range(_G):
        sel = live & (gid == g)
        for ch in channels:
            rows_out.append(
                jnp.sum(jnp.where(sel, ch, zero), axis=0, dtype=jnp.int32)
            )
        rows_out.extend([jnp.zeros((128,), jnp.int32)] * (16 - len(channels)))
    rows_out.extend(
        [jnp.zeros((128,), jnp.int32)] * (128 - _G * 16)
    )
    out_ref[:] = jnp.stack(rows_out)[None]


def q1_partial_sums(qty, price, disc, tax, rf, ls, ship, count, cutoff):
    """Per-block limb-channel partial sums: (num_blocks, 8, 128) int32.

    All column inputs are int32 arrays of one capacity n (a multiple of
    BLK_ROWS); count/cutoff are int32 scalars."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = qty.shape[0]
    assert n % BLK_ROWS == 0, n
    blocks = n // BLK_ROWS
    view = lambda x: x.reshape(n // 128, 128)
    interpret = jax.default_backend() != "tpu"  # CPU tests run interpreted

    # index_map returns BLOCK coordinates (units of block_shape)
    col_spec = pl.BlockSpec(
        (128, 128), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    # trace with x64 OFF: under the repo's global x64 mode the BlockSpec
    # index maps trace to i64 functions, which Mosaic fails to legalize
    # ("func.return (i64)") — every value in this kernel is explicit
    # int32, so 32-bit tracing is semantics-preserving.
    with jax.enable_x64(False):
        return pl.pallas_call(
            _kernel,
            grid=(blocks,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ]
            + [col_spec] * 7,
            out_specs=pl.BlockSpec(
                (1, 128, 128), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
            out_shape=jax.ShapeDtypeStruct((blocks, 128, 128), jnp.int32),
            interpret=interpret,
        )(
            cutoff.reshape(1),
            count.reshape(1),
            view(qty),
            view(price),
            view(disc),
            view(tax),
            view(rf),
            view(ls),
            view(ship),
        )


def combine(partials):
    """(blocks, 128, 128) int32 limb partials -> per-group int64 sums.

    Row g*16+k of each block tile holds channel k of group g as 128
    per-lane partials; fold blocks + lanes in int64 here (outside the
    kernel), then decode limb channels. Returns dict of (6,)-shaped
    arrays: count, sum_qty, sum_price, sum_disc (int64) and
    disc_price/charge as (6, 2) two-lane values (ops/decimal128
    layout) — exact at any row count."""
    from . import decimal128 as d128

    folded = jnp.sum(partials.astype(jnp.int64), axis=(0, 2))  # (128,)
    s = folded.reshape(8, 16)[: _G, : _CH]  # (6, 14)
    ch = [s[:, k] for k in range(_CH)]

    def lanes(lo16, mid, hi32):
        # value = lo16 + 2^16 * mid + 2^32 * hi32, all int64, exact
        lo = lo16 + ((mid & 0xFFFF) << 16)
        hi = (mid >> 16) + hi32
        hi, lo = d128.dnorm(hi, lo)
        return jnp.stack([hi, lo], axis=-1)

    return {
        "count": ch[0],
        "sum_qty": ch[1] + (ch[2] << 16),
        "sum_price": ch[3] + (ch[4] << 16),
        "sum_disc": ch[5],
        "sum_disc_price": lanes(ch[6], ch[7] + ch[8], ch[9]),
        "sum_charge": lanes(ch[10], ch[11] + ch[12], ch[13]),
    }
