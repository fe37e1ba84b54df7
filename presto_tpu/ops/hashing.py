"""Row hashing for group-by / join / repartitioning.

The TPU-native equivalent of the reference's compiled hash strategies
(presto-main/.../sql/gen/JoinCompiler.java hash generation and
operator/InterpretedHashGenerator.java): combine per-column 64-bit hashes into
one row hash with splitmix64-style mixing, fully vectorized. NULLs hash to a
fixed constant and compare equal (SQL GROUP BY/join-on-null semantics are
handled by callers via validity comparison)."""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# splitmix64 constants; arithmetic in uint64 wraps mod 2^64.
# numpy scalars, NOT jnp arrays: creating a device array at module import
# would force JAX backend initialization during `import presto_tpu`, which
# wedges driver entry points before they can select a platform.
_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_NULL_HASH = np.uint64(0x9AE16A3B2F90404F)


def mix64(x):
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * _C1
    x = (x ^ (x >> 27)) * _C2
    return x ^ (x >> 31)


def hash_column(data, valid: Optional[jnp.ndarray] = None):
    """64-bit hash of one column's storage values (any int/float/bool dtype).
    Multi-lane columns (long decimal, (n, 2) lanes) hash-combine per lane."""
    if data.ndim == 2:
        hs = [hash_column(data[:, i]) for i in range(data.shape[1])]
        h = combine_hashes(hs)
        if valid is not None:
            h = jnp.where(valid, h, _NULL_HASH)
        return h
    if jnp.issubdtype(data.dtype, jnp.floating):
        # canonicalize -0.0 == 0.0 and ALL NaN payloads to one quiet NaN
        # before bitcasting (reference doubleToLongBits semantics: every
        # NaN hashes and groups as the same value)
        data = jnp.where(data == 0, jnp.zeros_like(data), data)
        data = jnp.where(jnp.isnan(data), jnp.full_like(data, jnp.nan), data)
        width = data.dtype.itemsize
        idtype = {4: jnp.uint32, 8: jnp.uint64}[width]
        bits = jnp.asarray(data).view(idtype).astype(jnp.uint64)
    else:
        bits = data.astype(jnp.uint64)
    h = mix64(bits)
    if valid is not None:
        h = jnp.where(valid, h, _NULL_HASH)
    return h


def combine_hashes(hashes: Sequence[jnp.ndarray]):
    """Order-dependent combination (reference CombineHashFunction semantics)."""
    out = jnp.zeros_like(hashes[0])
    for h in hashes:
        out = (out * jnp.uint64(31)) + h
        out = mix64(out + _GOLDEN)
    return out


def argsort_hashes(h: jnp.ndarray) -> jnp.ndarray:
    """`jnp.argsort(h)` (stable) for uint64 row hashes, with the row id as
    a second sort key instead of stability: the keys are then distinct,
    the permutation is the same, and the TPU compiler takes about half
    the time (described v5e, 2M rows: 107 s stable, 53 s this form;
    sandbox compile, PR 22)."""
    idx = jnp.arange(h.shape[0], dtype=jnp.int32)
    return jax.lax.sort((h, idx), num_keys=2, is_stable=False)[1]


def hash_rows(columns) -> jnp.ndarray:
    """Hash a sequence of Blocks/Vals (anything with .data/.valid)."""
    hs = [hash_column(c.data, c.valid) for c in columns]
    return combine_hashes(hs) if len(hs) > 1 else hs[0]


# -- dictionary-VALUE hashing (table-independent varchar keys) ---------------
#
# Dictionary codes are per-table: the same string can carry different codes
# on the two sides of a join, so hashing codes (hash_column above) is only
# safe within one table. For join partitioning / hash ordering the two
# sides must agree for equal VALUES, so varchar columns rehash through a
# per-dictionary value-hash lookup table: vh[code] = crc-seeded splitmix64
# of the string bytes, computed ONCE per interned dictionary and cached.
# 32-bit crc collisions only create false candidates — true key equality
# (dictionary-unified code compare) always decides matches.
#
# The lookup table is a host array, so a traced kernel carries it as a
# per-dictionary constant: a block's dict_id is static pytree data
# (page.py), so a new dictionary is a new trace with or without it.

_VALUE_HASH_BY_DICT: dict = {}

# dictionaries beyond this size skip value hashing (the one-time host pass
# over every entry would dominate the join); callers fall back to their
# code-hash-unsafe routing for such keys. PRESTO_TPU_VALUE_HASH_MAX_DICT
# overrides (docs/tuning.md).
_VALUE_HASH_MAX_DICT_DEFAULT = 1 << 22


def value_hash_max_dict() -> int:
    import os

    try:
        v = int(os.environ.get("PRESTO_TPU_VALUE_HASH_MAX_DICT", "0"))
    except ValueError:
        v = 0
    return v if v > 0 else _VALUE_HASH_MAX_DICT_DEFAULT


# prestolint: host-function -- one-time host pass over an interned
# dictionary; jnp only finishes the mix on the host-built array
def dict_value_hashes(dict_id: int) -> jnp.ndarray:
    """(len(dictionary),) uint64 value hashes for an interned dictionary,
    cached on the device per dict_id (dictionaries are immutable once
    interned)."""
    vh = _VALUE_HASH_BY_DICT.get(dict_id)
    if vh is None:
        import zlib

        from ..page import dictionary_by_id

        entries = dictionary_by_id(dict_id)
        raw = np.empty(max(len(entries), 1), np.uint64)
        for i, s in enumerate(entries):
            b = s.encode("utf-8", "surrogatepass")
            raw[i] = np.uint64(zlib.crc32(b)) | (
                np.uint64(len(b) & 0xFFFFFFFF) << np.uint64(32)
            )
        with jax.ensure_compile_time_eval():  # the caller may be a trace
            vh = mix64(jnp.asarray(raw))[: len(entries)]
        _VALUE_HASH_BY_DICT[dict_id] = vh
    return vh


def value_hashable(columns) -> bool:
    """True when every varchar column's dictionary is small enough for the
    one-time value-hash pass (non-varchar columns are always fine)."""
    cap = value_hash_max_dict()
    for c in columns:
        if getattr(c, "dict_id", None) is not None:
            d = c.dictionary
            if d is None or len(d) > cap:
                return False
    return True


def hash_rows_values(columns) -> jnp.ndarray:
    """hash_rows with table-independent varchar hashing: dictionary
    columns hash their VALUES via dict_value_hashes, so build and probe
    sides of a join partition/tag identically for equal strings.
    Callers gate on value_hashable()."""
    hs = []
    for c in columns:
        if getattr(c, "dict_id", None) is not None:
            vh = dict_value_hashes(c.dict_id)
            if len(vh):
                h = vh[jnp.clip(c.data.astype(jnp.int32), 0, len(vh) - 1)]
            else:
                h = jnp.full(c.data.shape, _NULL_HASH)
            if c.valid is not None:
                h = jnp.where(c.valid, h, _NULL_HASH)
            hs.append(h)
        else:
            hs.append(hash_column(c.data, c.valid))
    return combine_hashes(hs) if len(hs) > 1 else hs[0]
