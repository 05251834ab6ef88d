"""Columnar chunks: the device-resident unit of table data.

TPU-native analog of the reference's columnar chunk format
(yt/yt/ytlib/columnar_chunk_format — "format version 3" scan-oriented reader,
segment_readers.h) re-designed for XLA rather than translated:

  * A chunk is a struct-of-arrays: one fixed-width device plane per column plus
    a validity plane, padded to a static capacity (multiple of 128 lanes) so
    every kernel sees static shapes.  `row_count` may be smaller than capacity;
    rows beyond it are masked out by `row_valid`.
  * Strings are order-preserving dictionary-encoded per chunk: the device plane
    holds int32 ranks into a host-side sorted vocabulary.  Rank order == byte
    order, so ORDER BY / range predicates / GROUP BY on strings are pure integer
    ops on device.  Cross-chunk operations unify vocabularies host-side and
    remap codes with one device gather (see `unify_dictionaries`).
  * `any`-typed payloads stay host-side (list of YSON values); they ride along
    for projection but are opaque to device compute, like the reference's
    "any" columns are opaque blobs to its codegen.
"""

from __future__ import annotations

import functools
import hashlib
import weakref
from dataclasses import dataclass, replace
from typing import Any, Iterable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.utils.invariants import check as _invariant_check
from ytsaurus_tpu.utils.tracing import child_span
from ytsaurus_tpu.schema import (
    EValueType,
    TableSchema,
    VectorType,
    device_dtype,
)

LANE = 128  # last-dim tiling unit on TPU; capacities are multiples of this


def next_pow2(n: int, floor: int = 1) -> int:
    """THE pow2 bucketing primitive: smallest power-of-two multiple of
    `floor` that is >= n (floor itself for n <= floor).  Every bucketed
    shape in the tree — chunk capacities, lookup-probe needle arrays,
    vocabulary-table paddings, IN-list bindings, LIMIT fingerprint
    buckets — derives from this one implementation, so the compile-cache
    key spectrum is O(log max) everywhere by construction."""
    cap = max(floor, 1)
    while cap < n:
        cap *= 2
    return cap


def pad_capacity(n: int) -> int:
    """Round a row count up to a static capacity bucket.

    Buckets are powers of two (times LANE) so distinct data sizes collapse onto
    few compiled shapes — the XLA analog of the reference's LLVM code cache
    keyed by query fingerprint only (engine_api/cg_cache.h): we additionally
    key by capacity bucket, so bucketing bounds the number of recompiles.
    """
    return next_pow2(n, floor=LANE)


def _encode_strings(values: Sequence[Optional[bytes]]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Order-preserving dictionary encode. Returns (codes, valid, vocab).

    Vectorized for high-cardinality columns (the round-1 "string cliff"):
    a fixed-width bytes array + ONE np.unique(return_inverse) replaces the
    per-value Python dict lookups — C-speed for ~1M-distinct columns (the
    sortedness of np.unique keeps code order == byte order, which the
    range/comparison lowering relies on)."""
    valid = np.array([v is not None for v in values], dtype=bool)
    if not valid.any():
        return (np.zeros(len(values), dtype=np.int32), valid,
                np.array([], dtype=object))
    # Object dtype (NOT numpy "S": fixed-width strips trailing NULs and
    # would corrupt arbitrary binary strings).
    packed = np.empty(len(values), dtype=object)
    packed[:] = [v if v is not None else b"" for v in values]
    vocab, codes = np.unique(packed, return_inverse=True)
    codes = codes.astype(np.int32)
    # b"" padding for nulls may introduce a phantom vocab entry; keep it
    # only if a VALID row actually holds the empty string.
    if len(vocab) and vocab[0] == b"" and not (
            valid & (codes == 0)).any():
        vocab = vocab[1:]
        codes = np.maximum(codes - 1, 0)
    return codes, valid, np.asarray(vocab, dtype=object)


def _to_bytes(v) -> bytes:
    if isinstance(v, bytes):
        return v
    if isinstance(v, str):
        return v.encode("utf-8")
    raise YtError(f"Expected string value, got {type(v).__name__}")


@dataclass(frozen=True)
class Column:
    """One column plane: device data + validity + optional host vocabulary."""

    type: EValueType
    data: jax.Array                      # (capacity,) device_dtype(type)
    valid: jax.Array                     # (capacity,) bool
    dictionary: Optional[np.ndarray] = None   # host vocab for string columns
    host_values: Optional[list] = None        # payloads for `any` columns

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def fetched_planes(self) -> tuple:
        """The device planes `decode` reads, validity first: none of a
        `null` column, no placeholder data plane of an `any` column."""
        if self.type is EValueType.null:
            return ()
        if self.type is EValueType.any:
            return (self.valid,)
        return (self.valid, self.data)

    def decode(self, row_count: int,
               host: Optional[Sequence[np.ndarray]] = None) -> list:
        """Materialize host values for the first `row_count` rows.
        `host` is `fetched_planes()` already on the host (a chunk
        fetches all its columns' at once); without it the column
        fetches its own."""
        if host is None:
            host = fetch_prefix(self.fetched_planes(), row_count)[0]
        if self.type is EValueType.null:
            return [None] * row_count
        valid, *rest = host
        data = rest[0] if rest else None
        out: list = []
        for i in range(row_count):
            if not valid[i]:
                out.append(None)
            elif isinstance(self.type, VectorType):
                out.append([float(x) for x in data[i]])
            elif self.type is EValueType.string:
                out.append(bytes(self.dictionary[int(data[i])]))
            elif self.type is EValueType.any:
                out.append(self.host_values[i])
            elif self.type is EValueType.boolean:
                out.append(bool(data[i]))
            elif self.type is EValueType.double:
                out.append(float(data[i]))
            else:
                out.append(int(data[i]))
        return out


# Planes that total at most this cross to the host whole, with no program
# dispatched; above it the live prefix is cut on the device first.  From a
# reading on one v5e (PERF.md §6, PR 28): twenty planes cross whole in
# 1.8 ms at 11 KB, 2.4 ms at 1.5 MB and 2.9 ms at 2.9 MB, where the cut to
# a 128-row bucket and its fetch take 3.0 ms whatever the planes' size.
WHOLE_FETCH_BYTES = 2 << 20


@functools.partial(jax.jit, static_argnums=1)
def _cut_planes(planes: tuple, bucket: int) -> tuple:
    return tuple(plane[:bucket] for plane in planes)


def fetch_prefix(planes: Sequence[jax.Array], row_count: int
                 ) -> tuple[list[np.ndarray], str, int]:
    """The first `row_count` rows of every plane, on the host, by ONE
    device-to-host fetch (`jax.device_get` starts every copy before it
    waits on any).  Returns (host planes, "whole" | "prefix", bytes that
    crossed).  Small planes cross whole and are cut in numpy; large ones
    are cut on the device to `pad_capacity(row_count)` (a bucket: few
    shapes) by one program over all of them, so a LIMIT 10 out of a
    million-slot plane never moves the plane."""
    bucket = pad_capacity(row_count)
    fetch = "whole"
    if sum(plane.nbytes for plane in planes) > WHOLE_FETCH_BYTES and \
            any(plane.shape[0] > bucket for plane in planes):
        fetch = "prefix"
        planes = _cut_planes(tuple(planes), bucket)
    host = jax.device_get(list(planes))
    return ([plane[:row_count] for plane in host], fetch,
            sum(plane.nbytes for plane in host))


@dataclass(frozen=True)
class ColumnarChunk:
    """An immutable columnar rowset with static device capacity."""

    schema: TableSchema
    row_count: int
    columns: dict[str, Column]
    # Sealed physical row order (ISSUE 19): column names whose ascending,
    # null-first, YT-comparator order the rows are already in (a prefix
    # guarantee: rows sorted by sorted_by[0], ties by sorted_by[1], ...).
    # Sealed at tablet flush/snapshot time where the MVCC merge emits key
    # order; ORDER BY lowering skips the packed-key sort when its spec is
    # covered.  Row-order-preserving transforms propagate it; anything
    # that reorders or merges rows must drop it (the default).
    sorted_by: tuple = ()

    @property
    def capacity(self) -> int:
        if not self.columns:
            return pad_capacity(max(self.row_count, 1))
        return next(iter(self.columns.values())).capacity

    @property
    def row_valid(self) -> jax.Array:
        cap = self.capacity
        return jnp.arange(cap) < self.row_count

    @functools.cached_property
    def nbytes(self) -> int:
        """Resident bytes of the column planes (capacity-padded) — the
        bytes-scanned unit per-tenant accounting charges.  `.nbytes` on
        a device array is metadata; nothing transfers.  Computed once
        per (immutable) chunk: every select reads it for its statistics
        and its `query.stage` span, and 32 plane lookups cost more than
        a span."""
        total = 0
        for col in self.columns.values():
            total += int(getattr(col.data, "nbytes", 0))
            if col.valid is not None:
                total += int(getattr(col.valid, "nbytes", 0))
        return total

    def column(self, name: str) -> Column:
        col = self.columns.get(name)
        if col is None:
            raise YtError(f"No such column {name!r} in chunk",
                          code=EErrorCode.QueryTypeError)
        return col

    # --- construction ---------------------------------------------------------

    @staticmethod
    def from_rows(schema: TableSchema, rows: Sequence[Mapping[str, Any] | Sequence[Any]],
                  capacity: Optional[int] = None) -> "ColumnarChunk":
        n = len(rows)
        cap = capacity or pad_capacity(max(n, 1))
        if cap < n:
            raise YtError(f"Capacity {cap} < row count {n}")
        names = schema.column_names
        # Normalize to per-column host lists.
        name_set = set(names)
        per_col: dict[str, list] = {name: [] for name in names}
        for row in rows:
            if isinstance(row, Mapping):
                if schema.strict:
                    unknown = set(row) - name_set
                    if unknown:
                        raise YtError(
                            f"Unknown columns {sorted(unknown)} for strict schema",
                            code=EErrorCode.QueryTypeError)
                for name in names:
                    per_col[name].append(row.get(name))
            else:
                if len(row) != len(names):
                    raise YtError(
                        f"Row width {len(row)} != schema width {len(names)}")
                for name, v in zip(names, row):
                    per_col[name].append(v)
        columns: dict[str, Column] = {}
        for col_schema in schema:
            name = col_schema.name
            ty = col_schema.type
            values = per_col[name]
            if col_schema.required:
                for i, v in enumerate(values):
                    if v is None:
                        raise YtError(
                            f"Required column {name!r} is null in row {i}",
                            code=EErrorCode.QueryTypeError)
            columns[name] = _build_column(ty, values, cap, name=name)
        chunk = ColumnarChunk(schema=schema, row_count=n, columns=columns)
        _invariant_check("chunks", chunk)
        return chunk

    @staticmethod
    def from_arrays(schema: TableSchema, arrays: Mapping[str, np.ndarray],
                    row_count: Optional[int] = None,
                    valids: Optional[Mapping[str, np.ndarray]] = None,
                    dictionaries: Optional[Mapping[str, np.ndarray]] = None,
                    capacity: Optional[int] = None) -> "ColumnarChunk":
        """Fast path from numpy arrays (no per-value python loop)."""
        names = schema.column_names
        n = row_count if row_count is not None else len(next(iter(arrays.values())))
        cap = capacity or pad_capacity(max(n, 1))
        columns: dict[str, Column] = {}
        for col_schema in schema:
            name = col_schema.name
            ty = col_schema.type
            if ty is EValueType.any:
                raise YtError("from_arrays does not support `any` columns; "
                              "use from_rows", code=EErrorCode.QueryUnsupported)
            arr = np.asarray(arrays[name])
            if len(arr) != n:
                raise YtError(f"Column {name!r} length {len(arr)} != {n}")
            if isinstance(ty, VectorType):
                if arr.ndim != 2 or arr.shape[1] != ty.dim:
                    raise YtError(
                        f"Vector column {name!r} needs a (rows, {ty.dim}) "
                        f"array, got shape {arr.shape}",
                        code=EErrorCode.QueryTypeError)
                if not np.isfinite(arr).all():
                    raise YtError(
                        f"Non-finite component in vector column {name!r}",
                        code=EErrorCode.QueryTypeError)
                data = np.zeros((cap, ty.dim), dtype=np.float32)
                data[:n] = arr.astype(np.float32)
                valid = np.zeros(cap, dtype=bool)
                if valids is not None and name in valids:
                    valid[:n] = np.asarray(valids[name], dtype=bool)
                else:
                    valid[:n] = True
                columns[name] = Column(type=ty, data=jnp.asarray(data),
                                       valid=jnp.asarray(valid))
                continue
            vocab = None
            if ty is EValueType.string:
                if dictionaries is not None and name in dictionaries:
                    vocab = np.asarray(dictionaries[name], dtype=object)
                else:
                    # Raw string array: vectorized dictionary encode (the
                    # high-cardinality path; ONE np.unique, no per-value
                    # Python lookups).  "S"/"U" inputs are fixed-width
                    # already (numpy cannot represent trailing NULs there);
                    # object arrays unique losslessly over arbitrary bytes.
                    raw = arr
                    if raw.dtype.kind == "U":
                        raw = np.char.encode(raw, "utf-8")
                    if raw.dtype.kind == "O":
                        # None entries mark nulls; replace with b"" so
                        # np.unique can compare, masked out via validity.
                        none_mask = np.array(
                            [v is None for v in raw], dtype=bool)
                        if none_mask.any():
                            raw = raw.copy()
                            raw[none_mask] = b""
                            if valids is None or name not in valids:
                                v0 = np.ones(n, dtype=bool)
                                v0[none_mask] = False
                                valids = dict(valids or {})
                                valids[name] = v0
                    if raw.dtype.kind in ("S", "O"):
                        vocab_s, codes = np.unique(raw, return_inverse=True)
                        vocab = np.empty(len(vocab_s), dtype=object)
                        vocab[:] = [bytes(v) for v in vocab_s]
                        arr = codes.astype(np.int32)
                    else:
                        raise YtError(
                            f"String column {name!r} needs a dictionary "
                            "or a string-typed array")
            dt = device_dtype(ty)
            data = np.zeros(cap, dtype=dt)
            data[:n] = arr.astype(dt)
            valid = np.zeros(cap, dtype=bool)
            if valids is not None and name in valids:
                valid[:n] = np.asarray(valids[name], dtype=bool)
            else:
                valid[:n] = True
            columns[name] = Column(type=ty, data=jnp.asarray(data),
                                   valid=jnp.asarray(valid), dictionary=vocab)
        chunk = ColumnarChunk(schema=schema, row_count=n, columns=columns)
        _invariant_check("chunks", chunk)
        return chunk

    # --- materialization ------------------------------------------------------

    def _decode_columns(self, tag=None) -> list[list]:
        """Host values of every schema column, in schema order, after
        ONE fetch of all their planes."""
        cols = [self.columns[name] for name in self.schema.column_names]
        per_col = [col.fetched_planes() for col in cols]
        host, fetch, nbytes = fetch_prefix(
            [plane for planes in per_col for plane in planes],
            self.row_count)
        if tag is not None:
            tag("fetch", fetch)
            tag("bytes", nbytes)
        host = iter(host)
        return [col.decode(self.row_count, [next(host) for _ in planes])
                for col, planes in zip(cols, per_col)]

    def to_rows(self, tag=None) -> list[dict[str, Any]]:
        """The rows as dicts.  `tag(key, value)`, where given, is told
        what crossed to the host: `fetch` ("whole" | "prefix") and
        `bytes` (a span's `add_tag`)."""
        names = self.schema.column_names
        return [dict(zip(names, row)) for row in self.to_tuples(tag)]

    def to_tuples(self, tag=None) -> list[tuple]:
        decoded = self._decode_columns(tag)
        return [tuple(col[i] for col in decoded)
                for i in range(self.row_count)]

    # --- transforms -----------------------------------------------------------

    def with_capacity(self, capacity: int) -> "ColumnarChunk":
        """Repad all planes to a new (>= row_count) capacity."""
        if capacity == self.capacity:
            return self
        if capacity < self.row_count:
            raise YtError("Cannot shrink chunk below its row count")
        columns = {}
        m = min(capacity, self.capacity)
        for name, col in self.columns.items():
            # (capacity,) + trailing dims: vector planes repad along axis 0.
            data = jnp.zeros((capacity,) + col.data.shape[1:],
                             dtype=col.data.dtype).at[:m].set(col.data[:m])
            valid = jnp.zeros(capacity, dtype=bool).at[:m].set(col.valid[:m])
            columns[name] = replace(col, data=data, valid=valid)
        return ColumnarChunk(schema=self.schema, row_count=self.row_count,
                             columns=columns, sorted_by=self.sorted_by)

    def slice_rows(self, start: int, end: int) -> "ColumnarChunk":
        start = max(0, start)
        end = min(self.row_count, end)
        n = max(0, end - start)
        cap = pad_capacity(max(n, 1))
        columns = {}
        for name, col in self.columns.items():
            trailing = col.data.shape[1:]
            data = jnp.zeros((cap,) + trailing, dtype=col.data.dtype).at[:n].set(
                jax.lax.dynamic_slice_in_dim(col.data, start, n) if n else
                jnp.zeros((0,) + trailing, dtype=col.data.dtype))
            valid = jnp.zeros(cap, dtype=bool).at[:n].set(
                jax.lax.dynamic_slice_in_dim(col.valid, start, n) if n else
                jnp.zeros(0, dtype=bool))
            host_values = None
            if col.host_values is not None:
                host_values = col.host_values[start:end]
            columns[name] = replace(col, data=data, valid=valid,
                                    host_values=host_values)
        return ColumnarChunk(schema=self.schema, row_count=n, columns=columns,
                             sorted_by=self.sorted_by)


def _plane_dtype(ty: EValueType) -> np.dtype:
    # `any` columns carry host payloads; their device plane is a placeholder.
    if ty is EValueType.any:
        return np.dtype(np.int8)
    return device_dtype(ty)


def _build_vector_plane(ty: VectorType, values: Sequence[Any],
                        cap: int, name: str = "") -> tuple[np.ndarray,
                                                           np.ndarray]:
    """Host rows → contiguous (cap, dim) float32 plane + validity.

    The WRITE-path hardening gate: ragged rows, wrong-dim rows and
    non-finite components are rejected loudly here — a NaN that slipped
    into a stored plane would silently poison every distance it ever
    participates in, so it must never seal."""
    dim = ty.dim
    n = len(values)
    data_np = np.zeros((cap, dim), dtype=np.float32)
    valid_np = np.zeros(cap, dtype=bool)
    label = f" in column {name!r}" if name else ""
    for i, v in enumerate(values):
        if v is None:
            continue
        try:
            arr = np.asarray(v, dtype=np.float32)
        except (TypeError, ValueError) as e:
            raise YtError(f"Bad vector value{label} at row {i}: {e}",
                          code=EErrorCode.QueryTypeError)
        if arr.ndim != 1:
            raise YtError(
                f"Ragged vector value{label} at row {i}: expected a flat "
                f"{dim}-component vector, got shape {arr.shape}",
                code=EErrorCode.QueryTypeError)
        if arr.shape[0] != dim:
            raise YtError(
                f"Vector dim mismatch{label} at row {i}: expected {dim} "
                f"components, got {arr.shape[0]}",
                code=EErrorCode.QueryTypeError)
        if not np.isfinite(arr).all():
            raise YtError(
                f"Non-finite vector component{label} at row {i}",
                code=EErrorCode.QueryTypeError)
        data_np[i] = arr
        valid_np[i] = True
    return data_np, valid_np


def _build_column(ty: EValueType, values: Sequence[Any], cap: int,
                  name: str = "") -> Column:
    n = len(values)
    if isinstance(ty, VectorType):
        data_np, valid_np = _build_vector_plane(ty, values, cap, name)
        return Column(type=ty, data=jnp.asarray(data_np),
                      valid=jnp.asarray(valid_np))
    dt = _plane_dtype(ty)
    valid_np = np.zeros(cap, dtype=bool)
    data_np = np.zeros(cap, dtype=dt)
    vocab = None
    host_values = None
    if ty is EValueType.string:
        encoded = [None if v is None else _to_bytes(v) for v in values]
        codes, valid, vocab = _encode_strings(encoded)
        data_np[:n] = codes
        valid_np[:n] = valid
    elif ty is EValueType.any:
        host_values = list(values) + [None] * (cap - n)
        valid_np[:n] = [v is not None for v in values]
    elif ty is EValueType.null:
        pass
    else:
        for i, v in enumerate(values):
            if v is None:
                continue
            valid_np[i] = True
            if ty is EValueType.boolean:
                data_np[i] = bool(v)
            elif ty is EValueType.double:
                data_np[i] = float(v)
            elif ty is EValueType.uint64:
                data_np[i] = np.uint64(v)
            else:
                data_np[i] = np.int64(v)
    return Column(type=ty, data=jnp.asarray(data_np), valid=jnp.asarray(valid_np),
                  dictionary=vocab, host_values=host_values)


# id(vocab) -> (weakref, digest).  Vocab arrays are immutable by
# convention (built sorted once at encode time, shared thereafter), so a
# content digest can be memoized per array identity; the weakref guards
# against id() reuse after collection (the _chunk_memo idiom).
_VOCAB_DIGEST_MEMO: dict = {}


def vocab_digest(vocab: np.ndarray) -> str:
    """Stable content digest of a sorted string vocabulary.  O(|vocab|)
    once per array, O(1) after — the identity check that lets
    `unify_dictionaries` and code-space predicate bindings recognize
    already-shared vocabs without a merge."""
    key = id(vocab)
    hit = _VOCAB_DIGEST_MEMO.get(key)
    if hit is not None and hit[0]() is vocab:
        return hit[1]
    h = hashlib.blake2b(digest_size=16)
    for v in vocab:
        b = v if isinstance(v, bytes) else _to_bytes(v)
        h.update(len(b).to_bytes(4, "little"))
        h.update(b)
    digest = h.hexdigest()
    if len(_VOCAB_DIGEST_MEMO) > 4096:
        for k in [k for k, (ref, _) in _VOCAB_DIGEST_MEMO.items()
                  if ref() is None]:
            del _VOCAB_DIGEST_MEMO[k]
    _VOCAB_DIGEST_MEMO[key] = (weakref.ref(vocab), digest)
    return digest


def unify_dictionaries(columns: Sequence[Column]) -> tuple[list[Column], np.ndarray]:
    """Re-encode string columns onto a shared sorted vocabulary.

    Returns the remapped columns and the unified vocab.  The remap is a single
    device gather per column (codes -> new codes), keeping order preservation.

    Fast path: when every string column already carries the SAME vocab
    (by identity, else by length + content digest) — the common
    post-compaction case — the columns return untouched: no host merge,
    no device gathers.
    """
    string_cols = [c for c in columns if c.type is EValueType.string]
    if string_cols and all(c.dictionary is not None for c in string_cols):
        first = string_cols[0].dictionary
        rest = [c.dictionary for c in string_cols[1:]]
        identical = all(v is first for v in rest)
        if not identical and all(len(v) == len(first) for v in rest):
            d0 = vocab_digest(first)
            identical = all(vocab_digest(v) == d0 for v in rest)
        if identical:
            return list(columns), np.asarray(first, dtype=object)
    vocabs = [c.dictionary for c in columns if c.dictionary is not None]
    # Vectorized union + remap (np.unique / searchsorted over object
    # arrays — lossless for arbitrary bytes): high-cardinality vocab
    # merges were the round-1 host cliff.
    if vocabs:
        merged = np.unique(np.concatenate(
            [np.asarray(v, dtype=object) for v in vocabs]))
    else:
        merged = np.array([], dtype=object)
    merged = np.asarray(merged, dtype=object)
    out = []
    for col in columns:
        if col.type is not EValueType.string:
            out.append(col)
            continue
        old_vocab = col.dictionary if col.dictionary is not None else np.array([], dtype=object)
        remap_np = np.searchsorted(
            merged, np.asarray(old_vocab, dtype=object)).astype(np.int32) \
            if len(old_vocab) else np.array([], dtype=np.int32)
        if len(remap_np) == 0:
            remap_np = np.zeros(1, dtype=np.int32)
        table = np.zeros(next_pow2(len(remap_np)), dtype=np.int32)
        table[:len(remap_np)] = remap_np
        new_codes = _remap_codes(jnp.asarray(table), col.data,
                                 np.int32(len(remap_np) - 1))
        out.append(replace(col, data=new_codes, dictionary=merged))
    return out, merged


@jax.jit
def _remap_codes(table: jax.Array, codes: jax.Array,
                 last: jax.Array) -> jax.Array:
    """codes -> table[codes], each code clipped into the table's first
    `last + 1` entries.  The table is padded to a power of two, so the
    program is keyed on that bucket and on the plane's capacity, not on
    the vocabulary's exact length."""
    return table[jnp.clip(codes, 0, last)].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("capacity", "dtype"))
def _concat_planes(datas: tuple, valids: tuple, offsets: jax.Array,
                   total: jax.Array, capacity: int, dtype) -> tuple:
    """Planes of `capacity` rows holding each part's first rows from
    offsets[i] on, in one program keyed on the parts' capacities and not
    on their row counts.  Each part is written whole, padding included,
    in order: its padding lands where the next part is written after it,
    or at or past `total`, where the output is zero and invalid."""
    trailing = datas[0].shape[1:]
    spare = max(d.shape[0] for d in datas)
    data = jnp.zeros((capacity + spare,) + trailing, dtype=dtype)
    valid = jnp.zeros(capacity + spare, dtype=bool)
    rest = (jnp.zeros((), offsets.dtype),) * len(trailing)
    for i, (part, part_valid) in enumerate(zip(datas, valids)):
        data = jax.lax.dynamic_update_slice(
            data, part.astype(dtype), (offsets[i],) + rest)
        valid = jax.lax.dynamic_update_slice(
            valid, part_valid.astype(bool), (offsets[i],))
    live = jnp.arange(capacity) < total
    data = jnp.where(live.reshape((capacity,) + (1,) * len(trailing)),
                     data[:capacity], jnp.zeros((), dtype=dtype))
    return data, valid[:capacity] & live


# Bound on string min/max stat values stored in chunk meta.  chunk_may_match
# treats a None bound as unprunable, so widening/dropping bounds is always
# safe — it only costs pruning power on pathological columns.
_STAT_STRING_CAP = 64

# --- distinct-count sketch ----------------------------------------------------
#
# A fixed 64-register hash-max sketch (the HLL register layout) per
# column, sealed into chunk meta next to min/max/has_null: the cost-based
# join planner (query/planner.py) reads NDV off chunk metadata instead of
# decoding data, and sketches MERGE across chunks by elementwise register
# max — so a table-level NDV is a fold over per-chunk meta, never a scan.
# 64 one-byte registers keep the meta payload bounded (the PR 5 hunk-
# externalization lesson: stats must never re-inline data-sized payloads).

NDV_SKETCH_SLOTS = 64
_NDV_SLOT_BITS = 6
_NDV_MAX_RANK = 58              # 64 - slot bits: ranks fit one byte


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized splitmix64 over a uint64 array (wrapping arithmetic)."""
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _ndv_sketch_from_hashes(hashes: np.ndarray) -> bytes:
    """Fold uniform uint64 hashes into the 64-register sketch: low bits
    pick the register, the rank is 1 + trailing-zero count of the rest
    (the classic stochastic-averaging split)."""
    regs = np.zeros(NDV_SKETCH_SLOTS, dtype=np.uint8)
    if len(hashes):
        h = hashes.astype(np.uint64)
        slots = (h & np.uint64(NDV_SKETCH_SLOTS - 1)).astype(np.int64)
        rest = h >> np.uint64(_NDV_SLOT_BITS)
        with np.errstate(over="ignore"):
            lsb = rest & (~rest + np.uint64(1))
        # log2 of an exact power of two is exact in float64 up to 2^58.
        rank = np.where(rest == 0, _NDV_MAX_RANK,
                        1 + np.log2(np.maximum(lsb, 1).astype(np.float64))
                        ).astype(np.uint8)
        np.maximum.at(regs, slots, rank)
    return regs.tobytes()


def _hash_string_vocab(vocab: np.ndarray) -> np.ndarray:
    """Deterministic (cross-process stable) uint64 content hash per
    vocab entry, vectorized: one concatenated byte buffer, a wrapping
    polynomial fold per segment (`np.add.reduceat` over byte·p^pos),
    the length folded in, then splitmix.  Entries that are hunk refs
    hash their id (the same identity the store dedups by).  This runs
    on the chunk SEAL path — a per-entry digest loop would be
    O(distinct) interpreter-speed work on exactly the high-NDV columns
    the sketch exists for."""
    from ytsaurus_tpu.chunks.hunks import HunkRef
    n = len(vocab)
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    entries = [v.hunk_id.encode() if isinstance(v, HunkRef) else bytes(v)
               for v in vocab]
    lengths = np.fromiter((len(e) for e in entries), count=n,
                          dtype=np.int64)
    # One leading sentinel byte per entry keeps every reduceat segment
    # non-empty (reduceat over an empty segment would leak a neighbor's
    # byte) and distinguishes b"" from absent.
    data = np.frombuffer(b"\x01" + b"\x01".join(entries),
                         dtype=np.uint8).astype(np.uint64)
    seg_lengths = lengths + 1
    starts = np.zeros(n, dtype=np.int64)
    np.cumsum(seg_lengths[:-1], out=starts[1:])
    p = np.uint64(0x9E3779B97F4A7C15 | 1)
    with np.errstate(over="ignore"):
        powers = np.empty(int(seg_lengths.max()), dtype=np.uint64)
        powers[0] = 1
        np.cumprod(np.full(len(powers) - 1, p, dtype=np.uint64),
                   out=powers[1:])
        pos = np.arange(len(data), dtype=np.int64) - \
            np.repeat(starts, seg_lengths)
        h = np.add.reduceat(data * powers[pos], starts)
        h = h ^ (lengths.astype(np.uint64) *
                 np.uint64(0xBF58476D1CE4E5B9))
    return _splitmix64(h)


def column_ndv_sketch(col: Column, row_count: int) -> "bytes | None":
    """The column's distinct-count sketch over its valid values, or None
    for types with no meaningful NDV (any/null)."""
    if col.type in (EValueType.any, EValueType.null) or \
            isinstance(col.type, VectorType):
        return None
    n = row_count
    valid = np.asarray(col.valid[:n]) if n else np.zeros(0, dtype=bool)
    if not n or not valid.any():
        return _ndv_sketch_from_hashes(np.zeros(0, dtype=np.uint64))
    data = np.asarray(col.data[:n])[valid]
    if col.type is EValueType.string:
        vocab = col.dictionary if col.dictionary is not None \
            else np.array([], dtype=object)
        entry_hashes = _hash_string_vocab(vocab)
        if len(entry_hashes) == 0:
            hashes = np.zeros(0, dtype=np.uint64)
        else:
            hashes = entry_hashes[
                np.clip(data.astype(np.int64), 0, len(entry_hashes) - 1)]
    elif col.type is EValueType.double:
        canon = np.where(data == 0.0, 0.0, data)   # -0.0 == +0.0
        hashes = _splitmix64(canon.view(np.uint64))
    else:
        hashes = _splitmix64(data.astype(np.int64).view(np.uint64)
                             if col.type is not EValueType.uint64
                             else data.astype(np.uint64))
    return _ndv_sketch_from_hashes(hashes)


def _sketch_regs(sketch) -> "np.ndarray | None":
    """Registers from a sketch payload.  Binary YSON round-trips bytes
    that happen to be valid utf-8 as str — re-encoding restores the
    exact original bytes, so both spellings parse."""
    if sketch is None:
        return None
    if isinstance(sketch, str):
        sketch = sketch.encode("utf-8")
    regs = np.frombuffer(bytes(sketch), dtype=np.uint8)
    if len(regs) != NDV_SKETCH_SLOTS:
        return None                    # corrupt payload: unusable, not fatal
    return regs


def merge_ndv_sketches(sketches: "Iterable[bytes]") -> "bytes | None":
    """Elementwise register max — the sketch of the UNION of the inputs."""
    merged = None
    for s in sketches:
        regs = _sketch_regs(s)
        if regs is None:
            continue
        merged = regs.copy() if merged is None else np.maximum(merged, regs)
    return None if merged is None else merged.tobytes()


def ndv_estimate(sketch: "bytes | None") -> int:
    """Distinct-count estimate off the registers (HLL harmonic mean with
    the linear-counting small-range correction).  >= 1 for a non-empty
    sketch so selectivity divisions are always safe; 0 for no data."""
    regs = _sketch_regs(sketch)
    if regs is None:
        return 0
    regs = regs.astype(np.float64)
    if not regs.any():
        return 0
    m = float(NDV_SKETCH_SLOTS)
    est = 0.709 * m * m / np.sum(np.exp2(-regs))
    zeros = int((regs == 0).sum())
    if est <= 2.5 * m and zeros:
        est = m * np.log(m / zeros)
    return max(int(round(est)), 1)


def merge_column_stats(stats_list: "Sequence[dict]") -> dict:
    """Fold per-chunk column stats into table-level stats: min of mins,
    max of maxes (None = unbounded wins), has_null ORs, `$row_count`
    sums, sketches merge.  The planner's one-stop table cardinality
    view over chunk metadata."""
    def bound(v):
        # Binary YSON round-trips utf-8-clean bytes as str; normalize so
        # bounds from sealed meta and fresh host stats compare.
        return v.encode("utf-8") if isinstance(v, str) else v

    out: dict = {"$row_count": 0}
    for stats in stats_list:
        for name, entry in stats.items():
            if name == "$row_count":
                out["$row_count"] += int(entry)
                continue
            if not isinstance(entry, dict):
                continue
            if "vector_dim" in entry:
                # Vector columns fold exactly: counts and centroid SUMS
                # add, norm bounds min/max (None = no valid rows, the
                # other side wins), has_null ORs.
                cur = out.get(name)
                if cur is None:
                    out[name] = {**entry, "centroid_sum":
                                 list(entry.get("centroid_sum") or [])}
                    continue
                cur["has_null"] = bool(cur.get("has_null")) or \
                    bool(entry.get("has_null"))
                cur["count"] = int(cur.get("count", 0)) + \
                    int(entry.get("count", 0))
                a = cur.get("centroid_sum") or []
                b = entry.get("centroid_sum") or []
                cur["centroid_sum"] = [float(x) + float(y)
                                       for x, y in zip(a, b)] \
                    if a and b else list(a or b)
                for key, pick in (("norm_min", min), ("norm_max", max)):
                    x, y = cur.get(key), entry.get(key)
                    cur[key] = y if x is None else \
                        (x if y is None else pick(x, y))
                continue
            entry = {**entry, "min": bound(entry.get("min")),
                     "max": bound(entry.get("max"))}
            cur = out.get(name)
            if cur is None:
                cur = {"min": entry.get("min"), "max": entry.get("max"),
                       "has_null": bool(entry.get("has_null")),
                       "ndv_sketch": entry.get("ndv_sketch"),
                       "_empty": entry.get("min") is None
                       and entry.get("max") is None}
                out[name] = cur
                continue
            # A chunk with no valid rows (min AND max None) contributes
            # nothing to the bounds; a lone None bound (the string-cap
            # overflow) is genuinely unbounded and must win the merge.
            entry_empty = entry.get("min") is None and \
                entry.get("max") is None
            if not entry_empty:
                if cur.pop("_empty", False):
                    cur["min"], cur["max"] = entry.get("min"), \
                        entry.get("max")
                else:
                    for key, pick in (("min", min), ("max", max)):
                        a, b = cur.get(key), entry.get(key)
                        cur[key] = None if a is None or b is None \
                            else pick(a, b)
                cur["_empty"] = False
            cur["has_null"] = cur["has_null"] or bool(entry.get("has_null"))
            cur["ndv_sketch"] = merge_ndv_sketches(
                [cur.get("ndv_sketch"), entry.get("ndv_sketch")])
    for entry in out.values():
        if isinstance(entry, dict):
            entry.pop("_empty", None)
    return out


def _string_stat_upper(value: bytes) -> "bytes | None":
    """An upper bound for `value` no longer than the cap: the value itself
    when short, else the successor of its cap-length prefix (strictly
    greater than EVERY string starting with that prefix).  None when no
    bounded successor exists (prefix is all 0xFF)."""
    if len(value) <= _STAT_STRING_CAP:
        return value
    prefix = value[:_STAT_STRING_CAP].rstrip(b"\xff")
    if not prefix:
        return None
    return prefix[:-1] + bytes([prefix[-1] + 1])


def vector_column_stats(col: Column, row_count: int) -> dict:
    """Centroid + L2-norm stats for a vector column, sealed into chunk
    meta at flush time (the NDV-sketch pattern; the later ANN-pruning
    hook).  `centroid_sum` is the elementwise SUM over valid rows (not
    the mean) so the cross-chunk merge fold is an exact addition —
    readers divide by `count`.  `norm_min`/`norm_max` bracket the L2
    norms of valid rows: with a query norm they bound any chunk's best
    possible dot/cosine/L2 score via the triangle inequality."""
    n = row_count
    valid = np.asarray(col.valid[:n]) if n else np.zeros(0, dtype=bool)
    entry: dict = {"has_null": bool((~valid).any()) if n else True,
                   "vector_dim": int(col.type.dim), "count": 0,
                   "centroid_sum": [0.0] * int(col.type.dim),
                   "norm_min": None, "norm_max": None,
                   "ndv_sketch": None}
    if n and valid.any():
        data = np.asarray(col.data[:n])[valid].astype(np.float64)
        norms = np.sqrt((data * data).sum(axis=1))
        entry["count"] = int(valid.sum())
        entry["centroid_sum"] = [float(x) for x in data.sum(axis=0)]
        entry["norm_min"] = float(norms.min())
        entry["norm_max"] = float(norms.max())
    return entry


def chunk_column_stats(chunk: ColumnarChunk) -> dict:
    """Per-column min/max/has_null pruning statistics (+ `$row_count`).

    THE single implementation: embedded into chunk meta at serialize
    time (`chunks/encoding.py`), surfaced by `FsChunkStore.read_stats`,
    and re-exported as `query/pruning.compute_column_stats` for the
    host-side backfill of chunks written before stats persisted."""
    out: dict[str, dict] = {}
    n = chunk.row_count
    for name, col in chunk.columns.items():
        if col.type in (EValueType.any, EValueType.null):
            continue
        if isinstance(col.type, VectorType):
            out[name] = vector_column_stats(col, n)
            continue
        valid = np.asarray(col.valid[:n])
        entry: dict = {"has_null": bool((~valid).any()) if n else True,
                       "min": None, "max": None}
        if n and valid.any():
            data = np.asarray(col.data[:n])[valid]
            if col.type is EValueType.string:
                codes = data
                # Long payloads (hunk-bound blobs) must not ride into the
                # meta verbatim — a 2KB value would re-inline what the
                # hunk store just externalized.  min truncates to a prefix
                # (a prefix is ≤ the value, still a lower bound); max
                # needs a prefix SUCCESSOR to stay an upper bound.
                entry["min"] = bytes(
                    col.dictionary[int(codes.min())])[:_STAT_STRING_CAP]
                entry["max"] = _string_stat_upper(
                    bytes(col.dictionary[int(codes.max())]))
            elif col.type is EValueType.boolean:
                entry["min"] = bool(data.min())
                entry["max"] = bool(data.max())
            elif col.type is EValueType.double:
                entry["min"] = float(data.min())
                entry["max"] = float(data.max())
            else:
                entry["min"] = int(data.min())
                entry["max"] = int(data.max())
        # Bounded 64-byte distinct-count sketch (cost-based join
        # planning reads NDV off metadata; merges across chunks by
        # register max — merge_column_stats).
        entry["ndv_sketch"] = column_ndv_sketch(col, n)
        out[name] = entry
    # Not a column: per-chunk row count rides the stats so metadata-only
    # consumers (chunk merger sizing) never decode the chunk.  "$" can
    # never collide with a column name, and chunk_may_match looks
    # columns up by name so it skips this key.
    out["$row_count"] = n
    return out


def project_chunk(chunk: ColumnarChunk, schema: TableSchema) -> ColumnarChunk:
    """View of `chunk` under `schema` (subset/reorder of columns)."""
    columns = {}
    for col_schema in schema:
        col = chunk.columns.get(col_schema.name)
        if col is None:
            raise YtError(f"Chunk is missing column {col_schema.name!r}",
                          code=EErrorCode.QueryExecutionError)
        columns[col_schema.name] = col
    # Column projection keeps row order; the sealed sort order survives
    # for the longest key prefix whose columns are still present (rows
    # sorted by (a, b) are NOT sorted by b alone once a is dropped).
    sorted_by = []
    for name in chunk.sorted_by:
        if name not in columns:
            break
        sorted_by.append(name)
    return ColumnarChunk(schema=schema, row_count=chunk.row_count,
                         columns=columns, sorted_by=tuple(sorted_by))


def concat_chunks(chunks: Sequence[ColumnarChunk]) -> ColumnarChunk:
    """Concatenate chunks of identical schema into one (device concat + repad).
    The device work is compiled per capacity bucket of the parts and of the
    result, whatever their row counts: chunks of new row totals at known
    capacities compile nothing."""
    if not chunks:
        raise YtError("concat_chunks: empty input")
    if len(chunks) == 1:
        return chunks[0]
    schema = chunks[0].schema
    for c in chunks[1:]:
        if c.schema != schema:
            raise YtError("concat_chunks: schema mismatch",
                          code=EErrorCode.ChunkFormatError)
    total = sum(c.row_count for c in chunks)
    cap = pad_capacity(max(total, 1))
    offsets = np.cumsum([0] + [c.row_count for c in chunks[:-1]],
                        dtype=np.int32)
    # Two spans a concatenation, not one a column: every string column's
    # dictionary union first, then every column's plane dispatch.
    unified: dict[str, tuple[list[Column], np.ndarray]] = {}
    with child_span("chunk.concat.unify") as span:
        for col_schema in schema:
            if col_schema.type is EValueType.string:
                unified[col_schema.name] = unify_dictionaries(
                    [c.column(col_schema.name) for c in chunks])
        if span.sampled:
            span.add_tag("columns", len(unified))
            span.add_tag("vocab_entries",
                         sum(len(vocab) for _, vocab in unified.values()))
    columns: dict[str, Column] = {}
    with child_span("chunk.concat.planes", columns=len(schema),
                    capacity=cap):
        for col_schema in schema:
            name = col_schema.name
            cols, vocab = unified.get(name) or (
                [c.column(name) for c in chunks], None)
            data, valid = _concat_planes(
                tuple(col.data for col in cols),
                tuple(col.valid for col in cols),
                offsets, np.int32(total), capacity=cap,
                dtype=_plane_dtype(col_schema.type))
            host_values = None
            if col_schema.type is EValueType.any:
                host_values = []
                for chunk, col in zip(chunks, cols):
                    host_values.extend(
                        (col.host_values or [])[: chunk.row_count])
                host_values += [None] * (cap - total)
            columns[name] = Column(type=col_schema.type, data=data,
                                   valid=valid, dictionary=vocab,
                                   host_values=host_values)
    return ColumnarChunk(schema=schema, row_count=total, columns=columns)
