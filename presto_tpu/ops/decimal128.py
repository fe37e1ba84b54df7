"""Long-decimal (wide) arithmetic as two int64 lanes.

Re-designed equivalent of the reference's 128-bit unscaled decimal
(presto-spi/.../type/UnscaledDecimal128Arithmetic.java, Decimals.java):
DECIMAL(p>18) values are stored as TWO int64 lanes per row —
``value = hi * 2**32 + lo`` with canonical ``lo in [0, 2**32)`` and signed
``hi`` — i.e. radix-2^32 limbs chosen so every add/merge stays exact in
int64 (no __int128, no uint64 carries in the hot path; TPU emulates 64-bit
integers, so fewer wide ops = faster).

Block layout: ``data.shape == (capacity, 2)``, lane 0 = hi, lane 1 = lo.
Representable magnitude ~2^95 (≈ 4e28) — the SQL type is decimal(38, s)
for parity with the reference; values beyond 2^95 are out of range the
same way the reference overflows beyond 10^38. TPC-H SF100 sums peak
around 1e20, five orders inside the range.

Canonicalization (`dnorm`) uses arithmetic shifts, so it is correct for
negative intermediate lo lanes produced by subtraction.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

MASK32 = np.int64(0xFFFFFFFF)
RADIX_BITS = 32


def is_long_decimal(t) -> bool:
    from .. import types as T

    return isinstance(t, T.DecimalType) and t.precision > 18


def dnorm(hi, lo):
    """Canonicalize lanes: fold lo's overflow (or borrow) into hi."""
    carry = lo >> RADIX_BITS  # arithmetic shift = floor(lo / 2^32)
    return hi + carry, lo & MASK32


def from_int64(x):
    """Widen an int64 column to lanes, shape (..., 2)."""
    return jnp.stack([x >> RADIX_BITS, x & MASK32], axis=-1)


def to_int64(lanes):
    """Narrow lanes to int64. Exact when |value| < 2^63; wraps beyond
    (callers narrow only where magnitudes are known to fit — the same
    contract as the reference's checked casts, minus the runtime throw,
    which a jitted TPU kernel cannot raise data-dependently)."""
    return lanes[..., 0] * (MASK32 + 1) + lanes[..., 1]


def dneg(lanes):
    hi, lo = lanes[..., 0], lanes[..., 1]
    return jnp.stack(dnorm(-hi, -lo), axis=-1)


def dadd(a, b):
    hi, lo = dnorm(a[..., 0] + b[..., 0], a[..., 1] + b[..., 1])
    return jnp.stack([hi, lo], axis=-1)


def dsub(a, b):
    hi, lo = dnorm(a[..., 0] - b[..., 0], a[..., 1] - b[..., 1])
    return jnp.stack([hi, lo], axis=-1)


def dcmp_lt(a, b):
    ah, al, bh, bl = a[..., 0], a[..., 1], b[..., 0], b[..., 1]
    return (ah < bh) | ((ah == bh) & (al < bl))


def dcmp_eq(a, b):
    return (a[..., 0] == b[..., 0]) & (a[..., 1] == b[..., 1])


def dsign(lanes):
    hi, lo = lanes[..., 0], lanes[..., 1]
    neg = hi < 0
    zero = (hi == 0) & (lo == 0)
    return jnp.where(zero, 0, jnp.where(neg, -1, 1)).astype(jnp.int64)


def dabs(lanes):
    return jnp.where((lanes[..., 0] < 0)[..., None], dneg(lanes), lanes)


def dmul_int64(lanes, c):
    """Exact lanes * int64 (|result| must stay < 2^95; beyond that the top
    limb is dropped, mirroring unchecked overflow of the narrow path).

    Schoolbook 32-bit limb multiply: value = v2*2^64 + v1*2^32 + v0 times
    c = c1*2^32 + c0. Every partial product is split into 32-bit halves
    before accumulation so all arithmetic stays exact in int64."""
    sign = dsign(lanes) * jnp.sign(jnp.where(c == 0, 1, c))
    a = dabs(lanes)
    cmag = jnp.abs(c)
    v0 = a[..., 1]
    v1 = a[..., 0] & MASK32
    v2 = (a[..., 0] >> RADIX_BITS) & MASK32
    c0 = cmag & MASK32
    c1 = (cmag >> RADIX_BITS) & MASK32

    def halves(x, y):
        # x, y < 2^32 -> x*y < 2^64: compute exactly via 16-bit splits of x
        xl = x & np.int64(0xFFFF)
        xh = x >> 16
        lo_p = xl * y  # < 2^48
        hi_p = xh * y  # < 2^48, weight 2^16
        lo = (lo_p + ((hi_p & np.int64(0xFFFF)) << 16)) & MASK32
        carry = (lo_p + ((hi_p & np.int64(0xFFFF)) << 16)) >> RADIX_BITS
        hi = (hi_p >> 16) + carry
        return hi, lo  # x*y == hi*2^32 + lo, both < 2^32 (hi < 2^32)

    r0 = jnp.zeros_like(v0)
    r1 = jnp.zeros_like(v0)
    r2 = jnp.zeros_like(v0)
    for vi, shift in ((v0, 0), (v1, 1), (v2, 2)):
        for cj, cshift in ((c0, 0), (c1, 1)):
            ph, pl = halves(vi, cj)
            k = shift + cshift
            if k == 0:
                r0 = r0 + pl
                r1 = r1 + ph
            elif k == 1:
                r1 = r1 + pl
                r2 = r2 + ph
            elif k == 2:
                r2 = r2 + pl
            # k >= 3 exceeds 2^96: dropped (out of supported range)
    # carry-propagate (each r accumulates <= 4 terms < 2^34 + carries)
    r1 = r1 + (r0 >> RADIX_BITS)
    r0 = r0 & MASK32
    r2 = r2 + (r1 >> RADIX_BITS)
    r1 = r1 & MASK32
    hi = (r2 << RADIX_BITS) | r1
    mag = jnp.stack([hi, r0], axis=-1)
    return jnp.where((sign < 0)[..., None], dneg(mag), mag)


def _divmod_nonneg(lanes_nonneg, d):
    """(quotient lanes, remainder int64) for non-negative lanes, 0<d<2^31.

    Exact: the remainder-times-radix step stays below 2^63 when d < 2^31.
    Quotient limbs are canonical (q2 < 2^32) so the result is valid lanes
    even when the quotient itself exceeds int64."""
    ahi, alo = lanes_nonneg[..., 0], lanes_nonneg[..., 1]
    q1 = ahi // d
    r1 = ahi - q1 * d
    num2 = (r1 << RADIX_BITS) + alo  # < d*2^32 + 2^32 <= 2^63 for d < 2^31
    q2 = num2 // d
    r2 = num2 - q2 * d
    return jnp.stack([q1, q2], axis=-1), r2


def ddiv_lanes_half_up(lanes, d):
    """lanes / d as lanes, HALF_UP (away from zero); 0 < d < 2^31."""
    sign_neg = lanes[..., 0] < 0
    q, r2 = _divmod_nonneg(dabs(lanes), d)
    bump = (2 * r2 >= d).astype(jnp.int64)
    hi, lo = dnorm(q[..., 0], q[..., 1] + bump)
    q = jnp.stack([hi, lo], axis=-1)
    return jnp.where(sign_neg[..., None], dneg(q), q)


def ddiv_int64_half_up(lanes, d):
    """lanes / d narrowed to int64, HALF_UP; 0 < d < 2^31. Exact when the
    quotient fits int64 (avg-by-count, small rescales)."""
    return to_int64(ddiv_lanes_half_up(lanes, d))


def rescale(lanes, pow10: int):
    """Multiply lanes by 10**pow10. Negative pow10 divides with HALF_UP
    rounding (SQL rescale semantics, reference Decimals.java)."""
    out = lanes
    p = pow10
    while p > 0:
        step = min(p, 18)
        out = dmul_int64(out, jnp.int64(10**step))
        p -= step
    while p < 0:
        # divisor steps < 2^31 stay exact; all but the last step truncate
        # toward zero, the last rounds HALF_UP (one-shot-equivalent to < 1
        # final ulp, matching reference rescale behavior in practice)
        step = min(-p, 9)
        d = jnp.int64(10**step)
        if -p > 9:  # intermediate step: truncate toward zero
            neg = out[..., 0] < 0
            q, _ = _divmod_nonneg(dabs(out), d)
            out = jnp.where(neg[..., None], dneg(q), q)
        else:
            out = ddiv_lanes_half_up(out, d)
        p += step
    return out


def ddiv_wide(lanes, d):
    """lanes / d for arbitrary int64 divisors (|d| up to ~2^62), HALF_UP.

    Float64 quotient estimate + exact lane-space remainder correction;
    exact for |quotient| < 2^53 (beyond that float64 cannot index integers
    — far outside decimal(18)-result range anyway). Returns int64."""
    sign = dsign(lanes) * jnp.sign(jnp.where(d == 0, 1, d))
    a = dabs(lanes)
    dm = jnp.abs(jnp.where(d == 0, 1, d))
    q = (to_float64(a) / dm.astype(jnp.float64)).astype(jnp.int64)
    q = jnp.maximum(q, 0)
    for _ in range(2):
        # exact remainder in lane space, then float-refine the quotient;
        # after one pass |r| <= a few * dm, so the next to_int64 is exact
        r = dsub(a, dmul_int64(from_int64(q), dm))
        adj = jnp.floor(to_float64(r) / dm.astype(jnp.float64)).astype(jnp.int64)
        q = q + adj
    rem = to_int64(dsub(a, dmul_int64(from_int64(q), dm)))
    # one exact fix each way (float refinement leaves |error| <= 1)
    fix_dn = rem < 0
    q = q - fix_dn.astype(jnp.int64)
    rem = rem + jnp.where(fix_dn, dm, 0)
    fix_up = rem >= dm
    q = q + fix_up.astype(jnp.int64)
    rem = rem - jnp.where(fix_up, dm, 0)
    q = q + (2 * rem >= dm).astype(jnp.int64)  # HALF_UP on the magnitude
    return sign * q


def segment_sum_wide(x_lanes, segment_ids, num_segments):
    """Exact segmented sum of lane pairs: per-lane segment_sum, then one
    normalization. Safe for < 2^31 contributing rows per call (lo lanes are
    canonical < 2^32, so their int64 partial sums cannot overflow)."""
    import jax

    # a scatter per lane: one scatter of (rows, 2) updates makes the TPU
    # compiler hold them as u32[rows, 2] tiled (8, 128), 512 B a row
    # (30.7 GB for 60M rows: RESOURCE_EXHAUSTED, PR 33)
    hi, lo = dnorm(
        jax.ops.segment_sum(x_lanes[..., 0], segment_ids, num_segments),
        jax.ops.segment_sum(x_lanes[..., 1], segment_ids, num_segments),
    )
    return jnp.stack([hi, lo], axis=-1)


def cumsum_wide(x_lanes):
    """Exact prefix sums of lane pairs (same < 2^31 row bound)."""
    hi = jnp.cumsum(x_lanes[..., 0])
    lo = jnp.cumsum(x_lanes[..., 1])
    hi, lo = dnorm(hi, lo)
    return jnp.stack([hi, lo], axis=-1)


def to_float64(lanes):
    return lanes[..., 0].astype(jnp.float64) * float(2**32) + lanes[
        ..., 1
    ].astype(jnp.float64)
