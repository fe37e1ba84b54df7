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
# safe within one table. For join partitioning / hash-table tags the two
# sides must agree for equal VALUES, so varchar columns rehash through a
# per-dictionary value-hash lookup table: vh[code] = crc-seeded splitmix64
# of the string bytes, computed ONCE per interned dictionary and cached.
# 32-bit crc collisions only create false candidates — true key equality
# (dictionary-unified code compare) always decides matches.
#
# Eager/host contexts only: the lookup table is a host array; embedding it
# in a traced kernel would bake a per-dictionary constant into the
# executable (one recompile per dictionary). Callers (ops/pallas_join.py,
# exec/spill.hash_partition_indices) run eagerly by design.

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
def dict_value_hashes(dict_id: int) -> np.ndarray:
    """(len(dictionary),) uint64 value hashes for an interned dictionary,
    cached per dict_id (dictionaries are immutable once interned)."""
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
        vh = np.asarray(mix64(jnp.asarray(raw)))
        if not len(entries):
            vh = vh[:0]
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


def _np_mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer, numpy twin of mix64 (uint64 wraps mod 2^64;
    numpy wraps silently for unsigned dtypes)."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * _C1
    x = (x ^ (x >> np.uint64(27))) * _C2
    return x ^ (x >> np.uint64(31))


def _np_hash_column(data: np.ndarray, valid) -> np.ndarray:
    """hash_column's numpy twin — bit-identical results (the host join
    path hashes probe batches every call; eager jnp dispatch overhead
    was ~40% of the whole probe)."""
    if data.ndim == 2:
        hs = [_np_hash_column(data[:, i], None) for i in range(data.shape[1])]
        h = np_combine_hashes(hs)
        if valid is not None:
            h = np.where(valid, h, _NULL_HASH)
        return h
    if np.issubdtype(data.dtype, np.floating):
        data = np.where(data == 0, np.zeros_like(data), data)
        data = np.where(np.isnan(data), np.full_like(data, np.nan), data)
        idtype = {4: np.uint32, 8: np.uint64}[data.dtype.itemsize]
        bits = data.view(idtype).astype(np.uint64)
    else:
        bits = data.astype(np.uint64)
    h = _np_mix64(bits)
    if valid is not None:
        h = np.where(valid, h, _NULL_HASH)
    return h


def np_combine_hashes(hashes) -> np.ndarray:
    out = np.zeros_like(hashes[0])
    for h in hashes:
        out = (out * np.uint64(31)) + h
        out = _np_mix64(out + _GOLDEN)
    return out


# prestolint: host-function -- host twin of hash_rows_values for the
# eager join/group-by kernels (np.asarray on CPU jax arrays is zero-copy)
def np_hash_rows_values(columns) -> np.ndarray:
    """hash_rows_values computed entirely in numpy — bit-identical to
    the jnp version (both are splitmix64 over the same canonicalized
    bits), for the host kernel paths where per-op jax dispatch dominates."""
    hs = []
    for c in columns:
        valid = None if c.valid is None else np.asarray(c.valid)
        if getattr(c, "dict_id", None) is not None:
            vh = dict_value_hashes(c.dict_id)
            codes = np.asarray(c.data).astype(np.int64)
            np.clip(codes, 0, max(len(vh) - 1, 0), out=codes)
            h = (
                vh[codes]
                if len(vh)
                else np.full(codes.shape, _NULL_HASH)
            )
            if valid is not None:
                h = np.where(valid, h, _NULL_HASH)
        else:
            h = _np_hash_column(np.asarray(c.data), valid)
        hs.append(h)
    return np_combine_hashes(hs) if len(hs) > 1 else hs[0]


# prestolint: host-function -- eager-only by contract (module note):
# gathers host value-hash tables by concrete dictionary codes
def hash_rows_values(columns) -> jnp.ndarray:
    """hash_rows with table-independent varchar hashing: dictionary
    columns hash their VALUES via dict_value_hashes, so build and probe
    sides of a join partition/tag identically for equal strings. Eager
    contexts only (see module note); callers gate on value_hashable()."""
    hs = []
    for c in columns:
        if getattr(c, "dict_id", None) is not None:
            vh = dict_value_hashes(c.dict_id)
            codes = np.asarray(c.data).astype(np.int64)
            np.clip(codes, 0, max(len(vh) - 1, 0), out=codes)
            h = jnp.asarray(
                vh[codes] if len(vh) else np.full(codes.shape, _NULL_HASH)
            )
            if c.valid is not None:
                h = jnp.where(c.valid, h, _NULL_HASH)
            hs.append(h)
        else:
            hs.append(hash_column(c.data, c.valid))
    return combine_hashes(hs) if len(hs) > 1 else hs[0]
