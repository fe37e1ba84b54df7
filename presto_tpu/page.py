"""Columnar Page/Block core as device-resident JAX arrays.

Re-designed equivalent of the reference data-plane representation
(presto-spi/src/main/java/com/facebook/presto/spi/Page.java:34 — "A Page is a
Block[]" — and the ~25 Block implementations under presto-spi/.../spi/block/).
TPU-first differences:

* A Block is a fixed-capacity device array plus a validity (non-null) mask,
  instead of variable-size heap memory. Static shapes keep everything
  jit-compilable; live row count is a *device scalar* on the Page.
* Rows in [0, capacity) beyond the live set are garbage and masked out by
  `Page.live_mask()`. This replaces the reference's dynamic page sizes and is
  the engine-wide convention all kernels in ops/ follow (capacity-padded pages
  + valid counts — the XLA answer to data-dependent shapes).
* Strings are dictionary codes (int32) over a host-side sorted tuple — the
  reference's DictionaryBlock (spi/block/DictionaryBlock.java) promoted to the
  *only* string representation on device.
* Block and Page are registered pytrees, so whole pages flow through jit /
  shard_map / all_to_all without manual flattening.

The reference's LazyBlock/RunLengthEncodedBlock have no device analog yet;
RLE-style constant blocks are represented by broadcasting at trace time.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import types as T


# Host-side dictionary interning: blocks carry a small int id instead of the
# string tuple, so (a) jit cache keys stay tiny, (b) equal dictionaries share
# one id and never force recompilation. Dictionaries are expected to be
# table-global per column (the tpch connector guarantees this), mirroring how
# the reference shares one DictionaryBlock dictionary across a whole segment.
_DICT_INTERN: dict = {}
_DICT_BY_ID: list = []


class LazyDict:
    """A dictionary whose entries are computed on demand — for huge formatted
    string domains (c_name = 'Customer#%09d', phones, …) where materializing
    tuples of millions of python strings would defeat the point of dictionary
    encoding. Subclasses must be hashable value objects and implement
    __len__/__getitem__; `is_sorted` declares whether entry order equals
    lexicographic order (required for <,>,ORDER BY on codes)."""

    is_sorted: bool = True

    def __len__(self) -> int:
        raise NotImplementedError

    def __getitem__(self, i: int) -> str:
        raise NotImplementedError

    def __iter__(self):
        return (self[i] for i in range(len(self)))


def intern_dictionary(d) -> int:
    key = d if isinstance(d, LazyDict) else tuple(d)
    did = _DICT_INTERN.get(key)
    if did is None:
        did = len(_DICT_BY_ID)
        _DICT_INTERN[key] = did
        _DICT_BY_ID.append(key)
    return did


def dictionary_by_id(did: int) -> Tuple[str, ...]:
    return _DICT_BY_ID[did]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Block:
    """One column: `data[capacity]` storage + `valid[capacity]` non-null mask.

    `valid is None` means "no nulls" (common fast path — skips mask math).
    `dict_id` identifies a host-side sorted tuple of strings for VARCHAR
    blocks (see intern_dictionary; static pytree aux data).
    """

    data: jax.Array
    type: T.Type
    valid: Optional[jax.Array] = None
    dict_id: Optional[int] = None
    # collection blocks only (ArrayType / MapType results, e.g. array_agg):
    # data is (capacity, width), `lengths` the per-row element counts,
    # `elem_valid` an optional per-element null mask, and `key_block` the
    # companion keys column of a MAP (reference ArrayBlock/MapBlock)
    lengths: Optional[jax.Array] = None
    elem_valid: Optional[jax.Array] = None
    key_block: Optional["Block"] = None

    @property
    def dictionary(self) -> Optional[Tuple[str, ...]]:
        return None if self.dict_id is None else dictionary_by_id(self.dict_id)

    # -- pytree protocol --
    def tree_flatten(self):
        children = [self.data]
        mask = 0
        if self.valid is not None:
            children.append(self.valid)
            mask |= 1
        if self.lengths is not None:
            children.append(self.lengths)
            mask |= 2
        if self.elem_valid is not None:
            children.append(self.elem_valid)
            mask |= 4
        if self.key_block is not None:
            children.append(self.key_block)
            mask |= 8
        return tuple(children), (self.type, self.dict_id, mask)

    @classmethod
    def tree_unflatten(cls, aux, children):
        typ, dict_id, mask = aux
        it = iter(children)
        data = next(it)
        valid = next(it) if mask & 1 else None
        lengths = next(it) if mask & 2 else None
        elem_valid = next(it) if mask & 4 else None
        key_block = next(it) if mask & 8 else None
        return cls(
            data=data, type=typ, valid=valid, dict_id=dict_id,
            lengths=lengths, elem_valid=elem_valid, key_block=key_block,
        )

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    def take_rows(self, idx) -> "Block":
        """Reindex every row-aligned array (gather/slice/permutation),
        preserving collection companions (lengths/elem_valid/key_block)."""
        return Block(
            self.data[idx],
            self.type,
            None if self.valid is None else self.valid[idx],
            self.dict_id,
            lengths=None if self.lengths is None else self.lengths[idx],
            elem_valid=(
                None if self.elem_valid is None else self.elem_valid[idx]
            ),
            key_block=(
                None if self.key_block is None else self.key_block.take_rows(idx)
            ),
        )

    def valid_mask(self) -> jax.Array:
        if self.valid is None:
            return jnp.ones(self.data.shape[0], dtype=jnp.bool_)
        return self.valid

    def with_dictionary(self, dictionary: Sequence[str]) -> "Block":
        return Block(self.data, self.type, self.valid, intern_dictionary(dictionary))

    # -- host-side constructors --
    @staticmethod
    def from_numpy(
        arr: np.ndarray,
        typ: T.Type,
        valid: Optional[np.ndarray] = None,
        dictionary: Optional[Sequence[str]] = None,
    ) -> "Block":
        data = jnp.asarray(arr, dtype=typ.storage_dtype)
        v = None if valid is None else jnp.asarray(valid, dtype=jnp.bool_)
        did = intern_dictionary(dictionary) if dictionary is not None else None
        return Block(data, typ, v, did)

    @staticmethod
    def from_strings(
        values: Sequence[Optional[str]],
        dictionary: Optional[Sequence[str]] = None,
    ) -> "Block":
        """Dictionary-encode python strings into a sorted-dictionary block.

        Pass a shared, pre-sorted `dictionary` whenever encoding repeated
        batches of one logical column — per-call derived dictionaries grow the
        intern table and force fresh jit compilations (see intern_dictionary).
        """
        present = [v for v in values if v is not None]
        if dictionary is None:
            dictionary = tuple(sorted(set(present)))
        else:
            dictionary = tuple(dictionary)
        index = {s: i for i, s in enumerate(dictionary)}
        codes = np.array([index[v] if v is not None else 0 for v in values], np.int32)
        valid = (
            None
            if len(present) == len(values)
            else np.array([v is not None for v in values], np.bool_)
        )
        return Block.from_numpy(codes, T.VARCHAR, valid, dictionary)

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.data)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Page:
    """A batch of rows: positional blocks + column names + live row count.

    `count` is a device int32 scalar — the number of live rows. Live rows
    always occupy positions [0, count); kernels that produce scattered
    liveness (filters) compact or mask via `live_mask()`.
    """

    blocks: Tuple[Block, ...]
    names: Tuple[str, ...]
    count: jax.Array  # int32 scalar

    def tree_flatten(self):
        return (tuple(self.blocks), self.count), (self.names,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        blocks, count = children
        (names,) = aux
        return cls(blocks=tuple(blocks), names=names, count=count)

    # -- shape info --
    @property
    def capacity(self) -> int:
        return self.blocks[0].capacity if self.blocks else 0

    @property
    def num_columns(self) -> int:
        return len(self.blocks)

    def live_mask(self) -> jax.Array:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.count

    def block(self, name: str) -> Block:
        return self.blocks[self.names.index(name)]

    def channel(self, i: int) -> Block:
        return self.blocks[i]

    def types(self) -> Tuple[T.Type, ...]:
        return tuple(b.type for b in self.blocks)

    def with_columns(self, blocks: Sequence[Block], names: Sequence[str]) -> "Page":
        return Page(tuple(blocks), tuple(names), self.count)

    def select(self, names: Sequence[str]) -> "Page":
        return Page(tuple(self.block(n) for n in names), tuple(names), self.count)

    # -- construction --
    @staticmethod
    def from_blocks(blocks: Sequence[Block], names: Sequence[str], count=None) -> "Page":
        blocks = tuple(blocks)
        if count is None:
            count = blocks[0].capacity if blocks else 0
        return Page(blocks, tuple(names), jnp.asarray(count, jnp.int32))

    @staticmethod
    def from_dict(columns: dict, pad_to: Optional[int] = None) -> "Page":
        """Build a device page from {name: numpy array | (array, Type) | Block |
        list-of-strings}. Pads every column to `pad_to` capacity if given."""
        blocks = []
        names = []
        n = None
        for name, value in columns.items():
            blk = _to_block(value)
            if n is None:
                n = blk.capacity
            elif blk.capacity != n:
                raise ValueError(
                    f"column {name!r} has {blk.capacity} rows, expected {n}"
                )
            blocks.append(blk)
            names.append(name)
        if n is None:
            n = 0
        if pad_to is not None and pad_to != n:
            if pad_to < n:
                raise ValueError("pad_to smaller than data")
            blocks = [_pad_block(b, pad_to) for b in blocks]
        return Page.from_blocks(blocks, names, count=n)

    # -- host materialization --
    def to_pylist(self) -> list:
        """Materialize live rows as python tuples (decoding dictionaries;
        collection blocks decode to lists / dicts)."""
        from .obs.span import host_read

        n = int(host_read(self.count))
        cols = []
        for b in self.blocks:
            data = host_read(b.data[:n])
            valid = None if b.valid is None else host_read(b.valid[:n])
            if b.lengths is not None:
                cols.append(_collection_pylist(b, data, valid, n))
                continue
            col = []
            for i in range(n):
                if valid is not None and not valid[i]:
                    col.append(None)
                else:
                    col.append(b.type.to_python(data[i], b.dictionary))
            cols.append(col)
        return [tuple(row) for row in zip(*cols)] if cols else []

    def to_dict_of_numpy(self) -> dict:
        n = int(self.count)
        return {name: np.asarray(b.data[:n]) for name, b in zip(self.names, self.blocks)}


def _collection_pylist(b: Block, data, valid, n: int) -> list:
    """Decode an ArrayType / MapType block's rows to lists / dicts."""
    lens = np.asarray(b.lengths[:n])
    ev = None if b.elem_valid is None else np.asarray(b.elem_valid[:n])
    if isinstance(b.type, T.MapType):
        kb = b.key_block
        kdata = np.asarray(kb.data[:n])
        kt, vt = b.type.key, b.type.value
        col = []
        if data.ndim == 3:
            # array-valued map (multimap_agg): values per key ride the
            # third axis, liveness in the 3-D elem_valid
            et = vt.element
            for i in range(n):
                if valid is not None and not valid[i]:
                    col.append(None)
                    continue
                row = {}
                for j in range(int(lens[i])):
                    k = kt.to_python(kdata[i, j], kb.dictionary)
                    row[k] = [
                        et.to_python(data[i, j, e], b.dictionary)
                        for e in range(data.shape[2])
                        if ev is None or ev[i, j, e]
                    ]
                col.append(row)
            return col
        for i in range(n):
            if valid is not None and not valid[i]:
                col.append(None)
                continue
            row = {}
            for j in range(int(lens[i])):
                k = kt.to_python(kdata[i, j], kb.dictionary)
                if ev is not None and not ev[i, j]:
                    row[k] = None
                else:
                    row[k] = vt.to_python(data[i, j], b.dictionary)
            col.append(row)
        return col
    et = b.type.element
    col = []
    for i in range(n):
        if valid is not None and not valid[i]:
            col.append(None)
            continue
        col.append(
            [
                None
                if ev is not None and not ev[i, j]
                else et.to_python(data[i, j], b.dictionary)
                for j in range(int(lens[i]))
            ]
        )
    return col


def _to_block(value) -> Block:
    if isinstance(value, Block):
        return value
    if isinstance(value, tuple) and len(value) == 2 and isinstance(value[1], T.Type):
        arr, typ = value
        return Block.from_numpy(np.asarray(arr), typ)
    if isinstance(value, (list,)) and value and isinstance(value[0], (str, type(None))):
        return Block.from_strings(value)
    arr = np.asarray(value)
    typ = _infer_type(arr)
    return Block.from_numpy(arr, typ)


def _infer_type(arr: np.ndarray) -> T.Type:
    if arr.dtype == np.bool_:
        return T.BOOLEAN
    if np.issubdtype(arr.dtype, np.integer):
        return T.BIGINT if arr.dtype.itemsize > 4 else T.INTEGER
    if np.issubdtype(arr.dtype, np.floating):
        return T.DOUBLE
    raise TypeError(f"cannot infer SQL type for dtype {arr.dtype}")


def _pad_block(b: Block, capacity: int) -> Block:
    n = b.capacity
    pad = capacity - n

    def padarr(x, fill_bool=False):
        if x is None:
            return None
        z = (
            jnp.zeros((pad,) + x.shape[1:], x.dtype)
            if not fill_bool
            else jnp.zeros((pad,) + x.shape[1:], jnp.bool_)
        )
        return jnp.concatenate([x, z])

    return Block(
        padarr(b.data),
        b.type,
        padarr(b.valid, True),
        b.dict_id,
        lengths=padarr(b.lengths),
        elem_valid=padarr(b.elem_valid, True),
        key_block=None if b.key_block is None else _pad_block(b.key_block, capacity),
    )


def round_capacity(n: int, minimum: int = 16) -> int:
    """Bucket a row count to the next power of two (bounded recompilation —
    the analog of the reference's adaptive batch sizing in
    presto-main/.../sql/gen/PageFunctionCompiler)."""
    cap = minimum
    while cap < n:
        cap *= 2
    return cap
