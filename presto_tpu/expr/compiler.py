"""Expression compiler: typed RowExpression -> fused jax computation.

The TPU-native equivalent of the reference's runtime bytecode generation
(presto-main/.../sql/gen/ExpressionCompiler.java:93 compilePageProcessor and
BytecodeGenerator visitors). Tracing with jax *is* the codegen: `evaluate`
walks the tree once inside a jit trace and XLA fuses the result into the
surrounding kernel, exactly where the reference emits JVM bytecode.

Special forms implemented here (the reference's special BytecodeGenerators,
sql/gen/AndCodeGenerator.java etc.):
  and / or      — SQL three-valued (Kleene) logic
  not, is_null, is_not_null
  if / case     — searched CASE via nested jnp.where
  coalesce, nullif
  in            — OR of equalities (dictionary fast path via functions.eq)
  between       — lo <= v AND v <= hi
  cast          — numeric/decimal/date conversions

Everything else dispatches to the scalar registry (expr/functions.py).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..page import Block, Page, intern_dictionary
from . import datetime_kernels as dt
from .functions import Val, and_valid, apply_function
from .ir import Call, ColumnRef, Lambda, Literal, RowExpression

LAMBDA_FORMS = {
    "transform",
    "filter",
    "reduce",
    "zip_with",
    "map_zip_with",
    "any_match",
    "all_match",
    "none_match",
    "map_filter",
    "transform_values",
    "transform_keys",
}

SPECIAL_FORMS = {
    "and",
    "or",
    "not",
    "is_null",
    "is_not_null",
    "if",
    "case",
    "coalesce",
    "nullif",
    "in",
    "between",
    "cast",
    "try_cast",
}


def evaluate(expr: RowExpression, page: Page, n: Optional[int] = None) -> Val:
    """Trace `expr` against the page's blocks. Returns a capacity-length Val."""
    cap = page.capacity

    if isinstance(expr, ColumnRef):
        blk = page.block(expr.name)
        keys_val = None
        if blk.key_block is not None:
            kb = blk.key_block
            keys_val = Val(
                kb.data, None, T.ArrayType(blk.type.key), kb.dict_id,
                lengths=kb.lengths, elem_valid=kb.elem_valid,
            )
        return Val(
            blk.data, blk.valid, blk.type, blk.dict_id,
            lengths=blk.lengths, elem_valid=blk.elem_valid, keys=keys_val,
        )

    if isinstance(expr, Literal):
        return _literal_val(expr, cap)

    if isinstance(expr, Lambda):
        # exhaustive over the IR: a Lambda is only meaningful as an
        # argument of a lambda-form Call (transform/filter/reduce...),
        # where _eval_lambda_form binds its parameters. Reaching one
        # bare means the planner emitted it in a value position.
        raise TypeError(
            f"bare Lambda {expr} outside a lambda-form call — planner bug"
        )

    assert isinstance(expr, Call), expr
    name = expr.name

    if name in LAMBDA_FORMS:
        return _eval_lambda_form(expr, page)

    if name == "and":
        return _kleene_and([evaluate(a, page) for a in expr.args])
    if name == "or":
        return _kleene_or([evaluate(a, page) for a in expr.args])
    if name == "not":
        v = evaluate(expr.args[0], page)
        return Val(~v.data, v.valid, T.BOOLEAN)
    if name == "is_null":
        v = evaluate(expr.args[0], page)
        data = jnp.zeros(cap, jnp.bool_) if v.valid is None else ~v.valid
        return Val(data, None, T.BOOLEAN)
    if name == "is_not_null":
        v = evaluate(expr.args[0], page)
        data = jnp.ones(cap, jnp.bool_) if v.valid is None else v.valid
        return Val(data, None, T.BOOLEAN)
    if name == "if":
        cond, then, els = (evaluate(a, page) for a in expr.args)
        return _if_val(cond, then, els, expr.type)
    if name == "case":
        # args = [cond1, val1, cond2, val2, ..., else]
        args = [evaluate(a, page) for a in expr.args]
        *pairs, els = args
        out = els
        for i in range(len(pairs) - 2, -1, -2):
            out = _if_val(pairs[i], pairs[i + 1], out, expr.type)
        return out
    if name == "coalesce":
        vals = [evaluate(a, page) for a in expr.args]
        out = vals[-1]
        for v in vals[-2::-1]:
            out = _if_val(
                Val(v.valid_mask(), None, T.BOOLEAN), v, out, expr.type
            )
        return out
    if name == "nullif":
        a, b = (evaluate(x, page) for x in expr.args)
        eq = apply_function("eq", [a, b], T.BOOLEAN)
        new_valid = and_valid(a.valid, ~(eq.data & eq.valid_mask()))
        return Val(a.data, new_valid, expr.type, a.dict_id)
    if name == "in":
        v = evaluate(expr.args[0], page)
        hits = [
            apply_function("eq", [v, evaluate(o, page)], T.BOOLEAN)
            for o in expr.args[1:]
        ]
        return _kleene_or(hits)
    if name == "between":
        v, lo, hi = (evaluate(a, page) for a in expr.args)
        ge = apply_function("ge", [v, lo], T.BOOLEAN)
        le = apply_function("le", [v, hi], T.BOOLEAN)
        return _kleene_and([ge, le])
    if name == "cast":
        v = evaluate(expr.args[0], page)
        return _cast_val(v, expr.type)
    if name == "try_cast":
        v = evaluate(expr.args[0], page)
        return _cast_val(v, expr.type, null_on_failure=True)

    vals = [evaluate(a, page) for a in expr.args]
    return apply_function(name, vals, expr.type)


# ---------------------------------------------------------------------------


def literal_scalar(expr: Literal):
    """The host scalar a numeric/date/boolean literal stands for, in its
    type's storage units (days for a date, the scaled integer for a short
    decimal). What `_literal_val` broadcasts, and what goes to a program
    as an operand when the literal is bound (exec/qcache.lift_literals)."""
    t = expr.type
    if isinstance(t, T.DateType) and isinstance(expr.value, str):
        return dt.parse_date_literal(expr.value)
    if isinstance(t, T.DecimalType):
        # any numeric literal -> scaled int in the decimal's units
        from decimal import Decimal

        return int(
            (Decimal(str(expr.value)) * (10**t.scale)).to_integral_value()
        )
    return expr.value


def _literal_val(expr: Literal, cap: int) -> Val:
    t = expr.type
    if expr.value is None:
        return Val(
            jnp.zeros(cap, t.storage_dtype), jnp.zeros(cap, jnp.bool_), t
        )
    if isinstance(expr.value, (jax.Array, np.ndarray)):
        # bound as an operand of the program (qcache.rebind_plan over a
        # lifted skeleton): already in storage units, and no `literal`,
        # so the value is not part of what gets traced
        return Val(jnp.full(cap, expr.value, t.storage_dtype), None, t)
    if isinstance(t, T.VarcharType):
        did = intern_dictionary((expr.value,))
        return Val(jnp.zeros(cap, jnp.int32), None, t, did, literal=expr.value)
    scalar = literal_scalar(expr)
    if isinstance(t, T.DateType) and isinstance(expr.value, str):
        return Val(jnp.full(cap, scalar, jnp.int32), None, t, literal=scalar)
    if isinstance(t, T.DecimalType) and t.is_long:
        # beyond int64: (hi, lo) radix-2^32 lanes (ops/decimal128.py)
        if abs(scalar) >= (1 << 95):
            raise ValueError(
                f"decimal literal {expr.value} exceeds the two-lane "
                "range (~2^95)"
            )
        lanes = np.array([[scalar >> 32, scalar & 0xFFFFFFFF]], np.int64)
        data = jnp.broadcast_to(jnp.asarray(lanes), (cap, 2))
        return Val(data, None, t, literal=expr.value)
    return Val(
        jnp.full(cap, scalar, t.storage_dtype), None, t, literal=expr.value
    )


def _kleene_and(vals: Sequence[Val]) -> Val:
    data, valid = vals[0].data, vals[0].valid
    for v in vals[1:]:
        new_data = data & v.data
        if valid is None and v.valid is None:
            valid = None
        else:
            av = jnp.ones_like(data) if valid is None else valid
            bv = v.valid_mask()
            # result valid if: both valid, or either side is a valid FALSE
            valid = (av & bv) | (av & ~data) | (bv & ~v.data)
        data = new_data
    return Val(data, valid, T.BOOLEAN)


def _kleene_or(vals: Sequence[Val]) -> Val:
    data, valid = vals[0].data, vals[0].valid
    for v in vals[1:]:
        new_data = data | v.data
        if valid is None and v.valid is None:
            valid = None
        else:
            av = jnp.ones_like(data) if valid is None else valid
            bv = v.valid_mask()
            # result valid if: both valid, or either side is a valid TRUE
            valid = (av & bv) | (av & data) | (bv & v.data)
        data = new_data
    return Val(data, valid, T.BOOLEAN)


def _if_val(cond: Val, then: Val, els: Val, out_type: T.Type) -> Val:
    c = cond.data & cond.valid_mask()
    a, b = _align_pair(then, els, out_type)  # same dict_id after alignment
    da, db = a.data, b.data
    if da.ndim != db.ndim:
        # one branch is long-decimal lanes, the other a scalar column
        # (e.g. a NULL/int literal): widen the scalar side exactly
        from ..ops import decimal128 as d128

        if da.ndim == 1:
            da = d128.from_int64(da.astype(jnp.int64))
        else:
            db = d128.from_int64(db.astype(jnp.int64))
    cw = c[:, None] if da.ndim == 2 else c
    data = jnp.where(cw, da, db)
    if a.valid is None and b.valid is None:
        valid = None
    else:
        valid = jnp.where(c, a.valid_mask(), b.valid_mask())
    return Val(data, valid, out_type, a.dict_id)


def _align_pair(a: Val, b: Val, out_type: T.Type):
    """Bring two Vals into the same representation for jnp.where."""
    if isinstance(out_type, T.VarcharType):
        if a.dict_id == b.dict_id:
            return a, b
        from .functions import unify_dictionaries

        xa, xb, did = unify_dictionaries(a, b)
        return Val(xa, a.valid, out_type, did), Val(xb, b.valid, out_type, did)
    ca = _cast_val(a, out_type)
    cb = _cast_val(b, out_type)
    return ca, cb


def _cast_val(v: Val, to: T.Type, null_on_failure: bool = False) -> Val:
    frm = v.type
    if frm == to:
        return v
    if isinstance(frm, T.UnknownType):
        return Val(jnp.zeros(v.data.shape, to.storage_dtype), jnp.zeros(v.data.shape, jnp.bool_), to)
    if isinstance(frm, T.VarcharType) and not isinstance(
        to, (T.VarcharType, T.DateType)
    ):
        # varchar -> numeric/boolean: parse once per DICTIONARY entry on
        # host (the date-cast model below). CAST raises on any
        # unparseable entry; TRY_CAST maps those entries to NULL.
        return _cast_varchar_entries(v, to, null_on_failure)
    if isinstance(to, T.VarcharType):
        if isinstance(frm, T.VarcharType):
            return Val(v.data, v.valid, to, v.dict_id)
        raise NotImplementedError(f"cast {frm} -> varchar")
    frm_long = isinstance(frm, T.DecimalType) and frm.is_long
    if isinstance(to, T.DoubleType) or isinstance(to, T.RealType):
        s = frm.scale if isinstance(frm, T.DecimalType) else 0
        if frm_long:
            from ..ops import decimal128 as d128

            d = d128.to_float64(v.data).astype(to.storage_dtype)
        else:
            d = v.data.astype(to.storage_dtype)
        return Val(d / (10**s) if s else d, v.valid, to)
    if isinstance(to, T.DecimalType):
        if to.is_long:
            from .functions import _to_lanes

            if T.is_floating(frm):
                from ..ops import decimal128 as d128
                from .functions import _round_half_away

                d = _round_half_away(v.data * (10**to.scale)).astype(jnp.int64)
                return Val(d128.from_int64(d), v.valid, to)
            return Val(_to_lanes(v, to.scale), v.valid, to)
        if frm_long:
            from ..ops import decimal128 as d128

            lanes = d128.rescale(v.data, to.scale - frm.scale)
            return Val(d128.to_int64(lanes), v.valid, to)
        if isinstance(frm, T.DecimalType):
            return Val(
                _rescale_int(v.data, frm.scale, to.scale), v.valid, to
            )
        if T.is_floating(frm):
            from .functions import _round_half_away

            d = _round_half_away(v.data * (10**to.scale)).astype(jnp.int64)
            return Val(d, v.valid, to)
        return Val(v.data.astype(jnp.int64) * (10**to.scale), v.valid, to)
    if T.is_integral(to):
        if frm_long:
            from ..ops import decimal128 as d128

            lanes = d128.rescale(v.data, -frm.scale)
            return Val(d128.to_int64(lanes).astype(to.storage_dtype), v.valid, to)
        if isinstance(frm, T.DecimalType):
            d = _rescale_int(v.data, frm.scale, 0)
            return Val(d.astype(to.storage_dtype), v.valid, to)
        if T.is_floating(frm):
            from .functions import _round_half_away

            return Val(_round_half_away(v.data).astype(to.storage_dtype), v.valid, to)
        return Val(v.data.astype(to.storage_dtype), v.valid, to)
    if isinstance(to, T.BooleanType):
        return Val(v.data != 0, v.valid, to)
    if isinstance(to, T.DateType) and isinstance(frm, T.VarcharType):
        d = v.dictionary or ()
        table = jnp.asarray(
            np.array([dt.parse_date_literal(s) for s in d], np.int32)
        )
        return Val(table[v.data], v.valid, to)
    raise NotImplementedError(f"cast {frm} -> {to}")


def _cast_varchar_entries(v: Val, to: T.Type, null_on_failure: bool) -> Val:
    import decimal as _dec

    d = v.dictionary or ()

    def parse(s: str):
        s2 = s.strip()
        try:
            if isinstance(to, T.BooleanType):
                low = s2.lower()
                if low in ("true", "t", "1"):
                    return 1, True
                if low in ("false", "f", "0"):
                    return 0, True
                return 0, False
            if T.is_integral(to):
                return int(s2), True
            if T.is_floating(to):
                return float(s2), True
            if isinstance(to, T.DecimalType):
                q = _dec.Decimal(s2).scaleb(to.scale).to_integral_value(
                    rounding=_dec.ROUND_HALF_UP
                )
                x = int(q)
                # two-int64-lane representation bound (ops/decimal128.py)
                if to.is_long and abs(x) >= (1 << 95):
                    return 0, False
                if not to.is_long and abs(x) >= (1 << 63):
                    return 0, False
                return x, True
        except (ValueError, _dec.InvalidOperation, ArithmeticError):
            return 0, False
        return 0, False

    parsed = [parse(s) for s in d]
    bad = [s for s, (_, ok) in zip(d, parsed) if not ok]
    if bad and not null_on_failure:
        raise ValueError(
            f"Cannot cast {bad[0]!r} to {to.display()} (CAST; use "
            "TRY_CAST for NULL-on-failure)"
        )
    if isinstance(to, T.DecimalType) and to.is_long:
        # long decimals: build (hi, lo) 32-bit lanes from python ints
        lanes = np.zeros((max(len(parsed), 1), 2), np.int64)
        for i, (x, _ok) in enumerate(parsed):
            lanes[i, 0] = x >> 32
            lanes[i, 1] = x & 0xFFFFFFFF
        table = jnp.asarray(lanes)
        data = table[v.data]
    else:
        if isinstance(to, T.BooleanType):
            npdt = np.bool_
        elif T.is_floating(to):
            npdt = np.float64 if isinstance(to, T.DoubleType) else np.float32
        else:
            npdt = np.int64
        table = jnp.asarray(
            np.array([x for x, _ in parsed] or [0], npdt).astype(
                to.storage_dtype
            )
        )
        data = table[v.data]
    okt = jnp.asarray(np.array([ok for _, ok in parsed] or [True], bool))
    ok = okt[v.data]
    valid = ok if v.valid is None else (v.valid & ok)
    if not bad:
        valid = v.valid  # all entries parse: keep original nullability
    return Val(data, valid, to)


def _rescale_int(data, from_scale: int, to_scale: int):
    from .functions import _rescale

    return _rescale(data.astype(jnp.int64), from_scale, to_scale)


# ---------------------------------------------------------------------------
# page-level entry points (the PageProcessor analog,
# reference operator/project/PageProcessor.java)
# ---------------------------------------------------------------------------


def project_page(
    page: Page, exprs: Sequence[RowExpression], names: Sequence[str]
) -> Page:
    """Evaluate projections; returns a new page with the same live count."""
    blocks = []
    for e in exprs:
        v = evaluate(e, page)
        kb = None
        if v.keys is not None:
            k = v.keys
            kb = Block(
                k.data, v.type.key, None, k.dict_id,
                lengths=k.lengths, elem_valid=k.elem_valid,
            )
        blocks.append(
            Block(
                v.data, v.type, v.valid, v.dict_id,
                lengths=v.lengths, elem_valid=v.elem_valid, key_block=kb,
            )
        )
    return Page(tuple(blocks), tuple(names), page.count)


def compile_projection(exprs, names) -> Callable[[Page], Page]:
    exprs = tuple(exprs)
    names = tuple(names)

    @jax.jit
    def run(page: Page) -> Page:
        return project_page(page, exprs, names)

    return run


# ---------------------------------------------------------------------------
# higher-order (lambda) functions over arrays
# ---------------------------------------------------------------------------
# Strategy (reference ArrayTransformFunction & friends, re-designed for
# XLA): flatten the (capacity, width) element matrix to one (capacity *
# width) column, append every outer column row-repeated `width` times, and
# evaluate the lambda BODY as an ordinary scalar expression over that flat
# page — every scalar kernel is reused unchanged, and XLA fuses the whole
# thing. Results reshape back to (capacity, width).


def _flat_page_for(page: Page, width: int, params) -> Page:
    """Outer columns row-repeated `width` times + lambda-param blocks."""
    blocks, names = [], []
    for nm, b in zip(page.names, page.blocks):
        data = jnp.repeat(b.data, width, axis=0)
        valid = None if b.valid is None else jnp.repeat(b.valid, width)
        blocks.append(Block(data, b.type, valid, b.dict_id))
        names.append(nm)
    for nm, v in params:
        blocks.append(Block(v.data, v.type, v.valid, v.dict_id))
        names.append(nm)
    cap = page.capacity * width
    return Page(tuple(blocks), tuple(names), jnp.asarray(cap, jnp.int32))


def _elements_val(arr: Val, elem_t: T.Type) -> Val:
    """Flatten an array Val's elements to a (capacity*width,) Val."""
    width = arr.data.shape[1]
    data = arr.data.reshape((arr.data.shape[0] * width,) + arr.data.shape[2:])
    valid = (
        None if arr.elem_valid is None else arr.elem_valid.reshape(-1)
    )
    return Val(data, valid, elem_t, arr.dict_id)


def _in_bounds(arr: Val) -> jnp.ndarray:
    """(capacity, width) mask of slots inside each row's length."""
    cap, width = arr.data.shape[0], arr.data.shape[1]
    lens = (
        arr.lengths
        if arr.lengths is not None
        else jnp.full(cap, width, jnp.int32)
    )
    return jnp.arange(width, dtype=jnp.int32)[None, :] < lens[:, None]


def _eval_lambda_form(expr: Call, page: Page) -> Val:
    name = expr.name
    out_type = expr.type
    if name == "zip_with":
        return _eval_zip_with(expr, page)
    if name == "map_zip_with":
        return _eval_map_zip_with(expr, page)
    if name == "reduce":
        return _eval_reduce(expr, page)
    if name in ("map_filter", "transform_values", "transform_keys"):
        return _eval_map_lambda(expr, page)
    arr = evaluate(expr.args[0], page)
    lam: Lambda = expr.args[1]
    if arr.data.ndim != 2:
        raise TypeError(f"{name} expects an array value")
    cap, width = arr.data.shape[0], arr.data.shape[1]
    elems = _elements_val(arr, lam.param_types[0])
    flat = _flat_page_for(page, width, [(lam.params[0], elems)])
    body = evaluate(lam.body, flat)
    inb = _in_bounds(arr)

    if name == "transform":
        data = body.data.reshape((cap, width) + body.data.shape[1:])
        evalid = (
            None
            if body.valid is None
            else body.valid.reshape(cap, width)
        )
        return Val(
            data, arr.valid, out_type, body.dict_id,
            lengths=arr.lengths
            if arr.lengths is not None
            else jnp.full(cap, width, jnp.int32),
            elem_valid=evalid,
        )
    if name == "filter":
        keep = (body.data & body.valid_mask()).reshape(cap, width) & inb
        # stable left-compaction per row: kept slots first, order preserved
        order = jnp.argsort(~keep, axis=1, stable=True)
        data = jnp.take_along_axis(
            arr.data, order.reshape(order.shape + (1,) * (arr.data.ndim - 2)),
            axis=1,
        ) if arr.data.ndim > 2 else jnp.take_along_axis(arr.data, order, axis=1)
        evalid = (
            None
            if arr.elem_valid is None
            else jnp.take_along_axis(arr.elem_valid, order, axis=1)
        )
        lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
        return Val(
            data, arr.valid, out_type, arr.dict_id,
            lengths=lengths, elem_valid=evalid,
        )
    # any/all/none_match over in-bounds elements (SQL semantics: NULL
    # lambda results participate in three-valued logic; the engine takes
    # the two-valued reduction like the reference's simplified matchers)
    truthy = (body.data & body.valid_mask()).reshape(cap, width)
    if name == "any_match":
        agg = jnp.any(truthy & inb, axis=1)
    elif name == "all_match":
        agg = jnp.all(truthy | ~inb, axis=1)
    else:  # none_match
        agg = ~jnp.any(truthy & inb, axis=1)
    return Val(agg, arr.valid, T.BOOLEAN)


def _eval_map_zip_with(expr: Call, page: Page) -> Val:
    """map_zip_with(m1, m2, (k, v1, v2) -> ...) — reference
    MapZipWithFunction: output keys are the UNION of the two key sets;
    a side's value is NULL where its map lacks the key.

    TPU shape: concat the two key lanes, one per-row sort clusters
    duplicates, a shifted-compare marks first occurrences, and a stable
    compaction left-packs the union; each side's value is then a masked
    equality-join of the union keys against that side's (short) key lane
    — O(W^2) per row on lanes that are all collection-width bounded."""
    m1 = evaluate(expr.args[0], page)
    m2 = evaluate(expr.args[1], page)
    lam: Lambda = expr.args[2]
    if m1.keys is None or m2.keys is None:
        raise TypeError("map_zip_with expects two map values")
    k1, k2 = m1.keys, m2.keys
    kd1, kd2, kdict = k1.data, k2.data, k1.dict_id
    # the keys companion is typed array(varchar) — gate on dict ids
    if (k1.dict_id is not None or k2.dict_id is not None) and (
        k1.dict_id != k2.dict_id
    ):
        from .functions import unify_dictionaries

        kd1, kd2, kdict = unify_dictionaries(k1, k2)
    if kd1.dtype != kd2.dtype:
        wide = jnp.promote_types(kd1.dtype, kd2.dtype)
        kd1, kd2 = kd1.astype(wide), kd2.astype(wide)
    cap, w1 = m1.data.shape[0], m1.data.shape[1]
    w2 = m2.data.shape[1]
    W = w1 + w2
    inb1, inb2 = _in_bounds(m1), _in_bounds(m2)
    allk = jnp.concatenate([kd1, kd2], axis=1)
    inb = jnp.concatenate([inb1, inb2], axis=1)
    # sort on the explicit (out_of_bounds, key) composite — the dead-flag
    # approach of ops/sort.py — instead of overloading dtype-max/+inf as
    # padding: a REAL key equal to the sentinel would otherwise be
    # indistinguishable from padding and silently dropped/mis-joined.
    # Out-of-bounds lanes sort last; in-bounds duplicates stay adjacent.
    oob = (~inb).astype(jnp.int8)
    sort_oob, sk = jax.lax.sort(
        (oob, allk), dimension=1, num_keys=2, is_stable=True
    )
    sinb = sort_oob == 0
    first = jnp.concatenate(
        [jnp.ones((cap, 1), jnp.bool_), sk[:, 1:] != sk[:, :-1]], axis=1
    )
    uniq = sinb & first
    pack = jnp.argsort(~uniq, axis=1, stable=True)
    ukeys = jnp.take_along_axis(sk, pack, axis=1)
    ulen = uniq.sum(axis=1).astype(jnp.int32)

    def lookup(m: Val, kd, inbm):
        eq = (ukeys[:, :, None] == kd[:, None, :]) & inbm[:, None, :]
        found = jnp.any(eq, axis=2)
        idx = jnp.argmax(eq, axis=2).astype(jnp.int32)
        vdat = jnp.take_along_axis(m.data, idx, axis=1)
        ev = found
        if m.elem_valid is not None:
            ev = ev & jnp.take_along_axis(m.elem_valid, idx, axis=1)
        return vdat, ev

    v1, ev1 = lookup(m1, kd1, inb1)
    v2, ev2 = lookup(m2, kd2, inb2)
    kelems = Val(ukeys.reshape(-1), None, lam.param_types[0], kdict)
    v1e = Val(v1.reshape(-1), ev1.reshape(-1), lam.param_types[1], m1.dict_id)
    v2e = Val(v2.reshape(-1), ev2.reshape(-1), lam.param_types[2], m2.dict_id)
    flat = _flat_page_for(
        page,
        W,
        [
            (lam.params[0], kelems),
            (lam.params[1], v1e),
            (lam.params[2], v2e),
        ],
    )
    body = evaluate(lam.body, flat)
    bdata = body.data.reshape(cap, W)
    bvalid = None if body.valid is None else body.valid.reshape(cap, W)
    out_type = expr.type
    new_keys = Val(ukeys, None, out_type.key, kdict, lengths=ulen)
    return Val(
        bdata,
        and_valid(m1.valid, m2.valid),
        out_type,
        body.dict_id,
        lengths=ulen,
        elem_valid=bvalid,
        keys=new_keys,
    )


def _eval_map_lambda(expr: Call, page: Page) -> Val:
    """map_filter / transform_values / transform_keys: the lambda body
    evaluates over flattened (key, value) element pairs (reference
    MapFilterFunction + MapTransform*Function)."""
    name = expr.name
    out_type = expr.type
    m = evaluate(expr.args[0], page)
    lam: Lambda = expr.args[1]
    if m.keys is None or m.data.ndim != 2:
        raise TypeError(f"{name} expects a map value")
    keys = m.keys
    cap, width = m.data.shape[0], m.data.shape[1]
    kelems = _elements_val(keys, lam.param_types[0])
    velems = _elements_val(m, lam.param_types[1])
    flat = _flat_page_for(
        page, width, [(lam.params[0], kelems), (lam.params[1], velems)]
    )
    body = evaluate(lam.body, flat)
    inb = _in_bounds(m)

    if name == "map_filter":
        keep = (body.data & body.valid_mask()).reshape(cap, width) & inb
        order = jnp.argsort(~keep, axis=1, stable=True)
        vdata = jnp.take_along_axis(m.data, order, axis=1)
        kdata = jnp.take_along_axis(keys.data, order, axis=1)
        ev = m.elem_valid
        if ev is not None:
            ev = jnp.take_along_axis(ev, order, axis=1)
        lengths = jnp.sum(keep, axis=1).astype(jnp.int32)
        new_keys = Val(
            kdata, None, keys.type, keys.dict_id, lengths=lengths
        )
        return Val(
            vdata, m.valid, out_type, m.dict_id, lengths=lengths,
            elem_valid=ev, keys=new_keys,
        )
    bdata = body.data.reshape(cap, width)
    bvalid = (
        None if body.valid is None else body.valid.reshape(cap, width)
    )
    if name == "transform_values":
        # the body's OWN validity is the only per-entry nullability: a
        # lambda ignoring v yields non-null even for null input values
        # (its valid mask already folds in elem_valid when it reads v)
        return Val(
            bdata, m.valid, out_type, body.dict_id, lengths=m.lengths,
            elem_valid=bvalid, keys=keys,
        )
    # transform_keys: values unchanged; keys replaced by the body
    new_keys = Val(
        bdata, None, out_type.key, body.dict_id, lengths=m.lengths
    )
    return Val(
        m.data, m.valid, out_type, m.dict_id, lengths=m.lengths,
        elem_valid=m.elem_valid, keys=new_keys,
    )


def _eval_zip_with(expr: Call, page: Page) -> Val:
    a = evaluate(expr.args[0], page)
    b = evaluate(expr.args[1], page)
    lam: Lambda = expr.args[2]
    cap = a.data.shape[0]
    wa, wb = a.data.shape[1], b.data.shape[1]
    width = max(wa, wb)

    def widen(v: Val, w: int) -> Val:
        if v.data.shape[1] == w:
            return v
        pad = w - v.data.shape[1]
        data = jnp.pad(v.data, ((0, 0), (0, pad)) + ((0, 0),) * (v.data.ndim - 2))
        ev = v.elem_valid
        ev = (
            jnp.pad(ev, ((0, 0), (0, pad)))
            if ev is not None
            else jnp.ones((cap, v.data.shape[1]), jnp.bool_)
        )
        if ev.shape[1] != w:
            ev = jnp.pad(ev, ((0, 0), (0, w - ev.shape[1])))
        return Val(data, v.valid, v.type, v.dict_id,
                   lengths=v.lengths, elem_valid=ev)

    a2, b2 = widen(a, width), widen(b, width)
    la = a.lengths if a.lengths is not None else jnp.full(cap, wa, jnp.int32)
    lb = b.lengths if b.lengths is not None else jnp.full(cap, wb, jnp.int32)
    out_len = jnp.maximum(la, lb)
    # shorter array's missing elements are NULL (Presto zip_with)
    ev_a = (
        a2.elem_valid
        if a2.elem_valid is not None
        else jnp.ones((cap, width), jnp.bool_)
    ) & (jnp.arange(width, dtype=jnp.int32)[None, :] < la[:, None])
    ev_b = (
        b2.elem_valid
        if b2.elem_valid is not None
        else jnp.ones((cap, width), jnp.bool_)
    ) & (jnp.arange(width, dtype=jnp.int32)[None, :] < lb[:, None])
    ea = Val(
        a2.data.reshape((cap * width,) + a2.data.shape[2:]),
        ev_a.reshape(-1), lam.param_types[0], a.dict_id,
    )
    eb = Val(
        b2.data.reshape((cap * width,) + b2.data.shape[2:]),
        ev_b.reshape(-1), lam.param_types[1], b.dict_id,
    )
    flat = _flat_page_for(
        page, width, [(lam.params[0], ea), (lam.params[1], eb)]
    )
    body = evaluate(lam.body, flat)
    data = body.data.reshape((cap, width) + body.data.shape[1:])
    evalid = (
        body.valid.reshape(cap, width)
        if body.valid is not None
        else None
    )
    valid = and_valid(a.valid, b.valid)
    return Val(
        data, valid, expr.type, body.dict_id,
        lengths=out_len, elem_valid=evalid,
    )


def _eval_reduce(expr: Call, page: Page) -> Val:
    """reduce(array, init, (s, x) -> s', s -> r): the state folds over a
    STATIC-width python loop (widths are trace constants), masked past
    each row's length — XLA unrolls and fuses the chain."""
    arr = evaluate(expr.args[0], page)
    init = evaluate(expr.args[1], page)
    input_fn: Lambda = expr.args[2]
    output_fn: Lambda = expr.args[3]
    cap, width = arr.data.shape[0], arr.data.shape[1]
    inb = _in_bounds(arr)
    state = init
    if state.type != input_fn.param_types[0]:
        state = _cast_val(state, input_fn.param_types[0])
    for j in range(width):
        edata = arr.data[:, j]
        evalid = None if arr.elem_valid is None else arr.elem_valid[:, j]
        ev = Val(edata, evalid, input_fn.param_types[1], arr.dict_id)
        flat = _flat_page_for(
            page, 1, [(input_fn.params[0], state), (input_fn.params[1], ev)]
        )
        nxt = evaluate(input_fn.body, flat)
        live = inb[:, j]
        data = jnp.where(_bcast(live, nxt.data), nxt.data, state.data)
        if state.valid is None and nxt.valid is None:
            valid = None
        else:
            valid = jnp.where(live, nxt.valid_mask(), state.valid_mask())
        state = Val(data, valid, nxt.type, nxt.dict_id)
    flat = _flat_page_for(page, 1, [(output_fn.params[0], state)])
    out = evaluate(output_fn.body, flat)
    return Val(out.data, and_valid(out.valid, arr.valid), expr.type, out.dict_id)


def _bcast(mask, data):
    """Broadcast a row mask over trailing lanes (long-decimal data)."""
    return mask.reshape(mask.shape + (1,) * (data.ndim - 1))
