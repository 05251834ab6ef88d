"""Vectorized MVCC: columnar version resolution on the XLA backbone.

Ref: versioned_row_merger.h / versioned_chunk_reader — the reference
resolves visibility with a per-row k-way heap merge + per-column JIT'd
loops.  Here the whole versioned read path is ONE compiled pipeline over
static-capacity planes, the same discipline the query engine already
follows (SURVEY §7 / the compiled-query-pipeline argument in PAPERS.md):

  1. Every source (versioned snapshot chunk, dynamic store ingested to
     planes once per mutation generation) concatenates on device.
  2. One packed u32 sort orders versions by (key asc, timestamp desc) —
     the primitives are `ops/segments.py`'s packed key encoding + stable
     radix/network argsort shared with the window subsystem.
  3. Visibility is segmented-scan algebra over the sorted planes:
     timestamp filtering is a compare, tombstone bounding is a segmented
     running-OR, per-column newest-written fill is a segmented index-min
     + gather.  No Python touches a row.

Three entry points share the machinery (compiled once per
(versioned-schema, capacity-bucket), cached process-wide):

  visible_chunk      read_snapshot: versions → the select-input chunk
  sorted_versioned_chunk  flush: stores → one (key, -ts)-ordered chunk
  retained_chunk     major compaction: versions ≤ retention collapse to
                     one consolidated per-column base version per key

The Python merge loops in tablet/tablet.py (`_mvcc_select`,
`_drop_superseded`) remain as the reference oracles: property tests
assert bit-exact row parity between the two implementations.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.chunks.columnar import Column, ColumnarChunk, pad_capacity
from ytsaurus_tpu.ops.segments import (
    compact_mask,
    pack_key_planes_bits,
    segment_end_index,
    segment_scan,
    stable_argsort_u32,
)
from ytsaurus_tpu.schema import EValueType, TableSchema
from ytsaurus_tpu.utils.tracing import child_span

# (kind, versioned-schema key, capacity) → compiled program.  Capacity
# buckets are powers of two (chunks/columnar.pad_capacity), so the cache
# stays bounded the same way the evaluator's compile cache does.
_PROGRAMS: dict = {}


def _schema_key(schema: TableSchema) -> tuple:
    return tuple(
        (c.name, c.type.value,
         c.sort_order.value if c.sort_order is not None else None)
        for c in schema)


def supports(schema: TableSchema) -> bool:
    """`any`-typed payloads live host-side (opaque to device compute);
    tablets carrying them keep the Python reference merge."""
    return not any(c.type is EValueType.any for c in schema)


def _comparable(data: jax.Array, valid: jax.Array) -> jax.Array:
    """Plane canonicalized for ordering/equality: invalid rows zeroed
    (null == null regardless of plane garbage) and -0.0 folded into +0.0
    so keys the host comparator calls equal land in one segment."""
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    if jnp.issubdtype(data.dtype, jnp.floating):
        data = data + 0.0
    return jnp.where(valid, data, jnp.zeros_like(data))


def _version_order(planes: dict, key_names: tuple, mask: jax.Array
                   ) -> jax.Array:
    """Stable permutation sorting versions by (key asc — nulls first —
    then timestamp desc), masked rows last.  Stability preserves the
    source concatenation order among duplicate (key, ts) versions, which
    is exactly the tie-break the Python reference's stable list sort
    applies."""
    items = [((~mask), jnp.ones_like(mask), False, 1)]
    for name in key_names:
        data, valid = planes[name]
        items.append((_comparable(data, valid), valid & mask, False, 64))
    ts_data, ts_valid = planes["$timestamp"]
    items.append((ts_data, ts_valid & mask, True, 64))
    words, bits = pack_key_planes_bits(items)
    return stable_argsort_u32(words, word_bits=bits)


def _key_starts(sorted_key_planes, s_mask: jax.Array) -> jax.Array:
    """Segment-start flags: row 0, any key change, masked transition."""
    change = s_mask != jnp.roll(s_mask, 1)
    for data, valid in sorted_key_planes:
        dz = _comparable(data, valid)
        change = change | (dz != jnp.roll(dz, 1)) | \
            (valid != jnp.roll(valid, 1))
    return change.at[0].set(True)


def _written_plane(s: dict, name: str) -> jax.Array:
    """Did each version STATE this column?  Mirrors tablet._written:
    an absent/null $w: flag means a whole-row write (legacy layout),
    only an explicit False means unwritten."""
    w_data, w_valid = s["$w:" + name]
    return jnp.where(w_valid, w_data, jnp.ones_like(w_data))


def _newest_written(s: dict, name: str, eligible: jax.Array,
                    starts: jax.Array, seg_end: jax.Array,
                    iota: jax.Array):
    """Per row: (data, valid) of its key's newest eligible version that
    wrote `name` — a segmented index-min over candidate rows + gather.
    Rows of one segment all read the same answer."""
    cap = iota.shape[0]
    data, valid = s[name]
    cand = eligible & _written_plane(s, name)
    cand_idx = jnp.where(cand, iota, jnp.full(cap, cap, dtype=jnp.int32))
    first_idx = segment_scan("min", cand_idx, starts)[seg_end]
    has = first_idx < cap
    idx = jnp.clip(first_idx, 0, cap - 1)
    return data[idx], has & valid[idx], has


def _build_visible(key_names: tuple, value_names: tuple, capacity: int):
    """read_snapshot program: versioned planes → visible-row planes (in
    key order, compacted to the front) + row count."""

    def run(planes, row_count, read_ts):
        iota = jnp.arange(capacity, dtype=jnp.int32)
        mask = iota < row_count
        perm = _version_order(planes, key_names, mask)
        s = {name: (d[perm], v[perm]) for name, (d, v) in planes.items()}
        s_mask = mask[perm]
        starts = _key_starts([s[k] for k in key_names], s_mask)
        seg_end = segment_end_index(starts)

        ts_data, _ = s["$timestamp"]
        tomb_data, tomb_valid = s["$tombstone"]
        tomb = tomb_data & tomb_valid
        eligible = s_mask & (ts_data <= read_ts)
        # Newest tombstone ≤ read_ts bounds the merge: a segmented
        # running-OR marks every version at/after (older than) it dead.
        dead = segment_scan(
            "max", (eligible & tomb).astype(jnp.int8), starts) > 0
        in_merge = eligible & ~dead
        # One output row per key with surviving writes; its planes are
        # gathered at the key's NEWEST surviving write (the leader).
        seen = segment_scan("sum", in_merge.astype(jnp.int32), starts)
        leader = in_merge & (seen == 1)

        out = {name: s[name] for name in key_names}
        for name in value_names:
            data, valid, _ = _newest_written(s, name, in_merge, starts,
                                             seg_end, iota)
            out[name] = (data, valid)
        order, count = compact_mask(leader)
        emitted = jnp.arange(capacity, dtype=jnp.int64) < count
        out = {name: (d[order], v[order] & emitted)
               for name, (d, v) in out.items()}
        return out, count

    return run


def _build_sorted(key_names: tuple, capacity: int):
    """flush program: one stable (key, -ts) sort, planes gathered."""

    def run(planes, row_count):
        iota = jnp.arange(capacity, dtype=jnp.int32)
        mask = iota < row_count
        perm = _version_order(planes, key_names, mask)
        return {name: (d[perm], v[perm])
                for name, (d, v) in planes.items()}

    return run


def _build_retained(key_names: tuple, value_names: tuple, capacity: int):
    """Major-compaction program (`_drop_superseded` semantics): versions
    newer than the retention timestamp pass through; versions at/below
    it collapse into ONE consolidated base version per key (per-column
    merged visible state at the retention cut), or nothing when that
    state is a delete."""

    def run(planes, row_count, retention_ts):
        iota = jnp.arange(capacity, dtype=jnp.int32)
        mask = iota < row_count
        perm = _version_order(planes, key_names, mask)
        s = {name: (d[perm], v[perm]) for name, (d, v) in planes.items()}
        s_mask = mask[perm]
        starts = _key_starts([s[k] for k in key_names], s_mask)
        seg_end = segment_end_index(starts)

        ts_data, ts_valid = s["$timestamp"]
        tomb_data, tomb_valid = s["$tombstone"]
        tomb = tomb_data & tomb_valid
        is_base = s_mask & (ts_data <= retention_ts)
        kept = s_mask & ~is_base
        dead = segment_scan(
            "max", (is_base & tomb).astype(jnp.int8), starts) > 0
        in_base = is_base & ~dead
        # The base versions sort after every kept version of their key
        # (lower timestamps), so the leader row — the newest surviving
        # base write — is where the consolidated version lands, already
        # in (key, -ts) output order.
        seen = segment_scan("sum", in_base.astype(jnp.int32), starts)
        leader = in_base & (seen == 1)

        out = {name: s[name] for name in key_names}
        out["$timestamp"] = (ts_data, ts_valid)   # leader keeps base_ts
        out["$tombstone"] = (jnp.where(leader, False, tomb_data),
                             tomb_valid | leader)
        for name in value_names:
            data, valid = s[name]
            base_d, base_v, _ = _newest_written(s, name, in_base, starts,
                                                seg_end, iota)
            out[name] = (jnp.where(leader, base_d, data),
                         jnp.where(leader, base_v, valid))
            w_data, w_valid = s["$w:" + name]
            # Consolidated versions STATE every column explicitly.
            out["$w:" + name] = (w_data | leader, w_valid | leader)
        emit = kept | leader
        order, count = compact_mask(emit)
        emitted = jnp.arange(capacity, dtype=jnp.int64) < count
        out = {name: (d[order], v[order] & emitted)
               for name, (d, v) in out.items()}
        return out, count

    return run


def _builder(kind: str, capacity: int, key_names: tuple,
             value_names: tuple):
    if kind == "visible":
        return _build_visible(key_names, value_names, capacity)
    if kind == "sorted":
        return _build_sorted(key_names, capacity)
    return _build_retained(key_names, value_names, capacity)


def _run_program(kind: str, merged: ColumnarChunk, key_names: tuple,
                 value_names: tuple, scalars: tuple, stats=None):
    """Run the (kind, schema, capacity) program over `merged`.  An
    entry's first call compiles it ahead of time, apart from its
    execution: the seconds land in a `tablet.mvcc_compile` span and,
    where the caller threads a QueryStatistics down, in its
    `compile_time` / `compile_count` (these programs are jitted outside
    the evaluator, whose counters would otherwise miss them)."""
    key = (kind, _schema_key(merged.schema), merged.capacity)
    args = (_planes(merged),) + scalars
    fn = _PROGRAMS.get(key)
    if fn is None:
        jitted = jax.jit(_builder(kind, merged.capacity, key_names,
                                  value_names))
        with child_span("tablet.mvcc_compile", kind=kind,
                        capacity=merged.capacity):
            t0 = time.perf_counter()
            try:
                fn = jitted.lower(*args).compile()
            except Exception:   # noqa: BLE001 — AOT is how the compile
                # is timed apart; what it cannot lower runs as the jit
                # wrapper (the first call then compiles, untimed).
                fn = jitted
            seconds = time.perf_counter() - t0
        _PROGRAMS[key] = fn
        if stats is not None:
            stats.compile_count += 1
            stats.compile_time += seconds
    try:
        return fn(*args)
    except (TypeError, ValueError):
        if hasattr(fn, "lower"):
            raise               # the jit wrapper: a genuine error
        # An AOT executable rejects an aval drift the key did not
        # capture: serve this entry through the tolerant jit wrapper.
        fn = _PROGRAMS[key] = jax.jit(_builder(
            kind, merged.capacity, key_names, value_names))
        return fn(*args)


def _planes(chunk: ColumnarChunk) -> dict:
    return {name: (col.data, col.valid)
            for name, col in chunk.columns.items()}


def _emit_chunk(schema: TableSchema, out_planes: dict, n: int,
                source: ColumnarChunk) -> ColumnarChunk:
    """Wrap program output planes into a chunk, shrunk to the tightest
    capacity bucket so downstream compile caches key on output size, not
    on how many superseded versions fed the merge."""
    columns = {}
    for c in schema:
        data, valid = out_planes[c.name]
        columns[c.name] = Column(
            type=c.type, data=data, valid=valid,
            dictionary=source.columns[c.name].dictionary)
    chunk = ColumnarChunk(schema=schema, row_count=n, columns=columns)
    tight = pad_capacity(max(n, 1))
    if tight < chunk.capacity:
        chunk = chunk.with_capacity(tight)
    return chunk


def visible_chunk(merged: ColumnarChunk, table_schema: TableSchema,
                  timestamp: int, stats=None) -> ColumnarChunk:
    """MVCC merge at `timestamp` over a concatenated versioned chunk →
    the select-input ColumnarChunk (plain table schema, key order)."""
    key_names = tuple(table_schema.key_column_names)
    value_names = tuple(c.name for c in table_schema
                        if c.sort_order is None)
    out, count = _run_program(
        "visible", merged, key_names, value_names,
        (np.int64(merged.row_count), np.int64(timestamp)), stats)
    chunk = _emit_chunk(table_schema.to_unsorted(), out, int(count), merged)
    # The merge emits key order — seal it so ORDER BY <key prefix> over a
    # tablet snapshot skips the packed-key sort (ISSUE 19 layout sealing).
    return dataclasses.replace(chunk, sorted_by=key_names)


def sorted_versioned_chunk(merged: ColumnarChunk,
                           table_schema: TableSchema) -> ColumnarChunk:
    """Stable (key asc, ts desc) ordering of a versioned chunk — the
    flush sort, without materializing rows."""
    key_names = tuple(table_schema.key_column_names)
    out = _run_program("sorted", merged, key_names, (),
                       (np.int64(merged.row_count),))
    return _emit_chunk(merged.schema, out, merged.row_count, merged)


def retained_chunk(merged: ColumnarChunk, table_schema: TableSchema,
                   retention_timestamp: int) -> ColumnarChunk:
    """Major compaction over a concatenated versioned chunk: row-exact
    `_drop_superseded` on device.  row_count == 0 means every version
    was superseded by a delete — the caller drops the chunk."""
    key_names = tuple(table_schema.key_column_names)
    value_names = tuple(c.name for c in table_schema
                        if c.sort_order is None)
    out, count = _run_program(
        "retained", merged, key_names, value_names,
        (np.int64(merged.row_count), np.int64(retention_timestamp)))
    return _emit_chunk(merged.schema, out, int(count), merged)
