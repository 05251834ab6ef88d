"""Plan lowering: typed IR → a staged XLA pipeline over columnar planes.

The reference JIT-compiles a per-row push pipeline (scan→filter→group→order→
project, cg_fragment_compiler.cpp).  Here each clause becomes a batch
transformation over static-capacity planes:

  filter   = predicate mask (no data movement)
  group    = lexsort by key planes → segment boundaries → segment reductions
  order    = lexsort by order keys → gather
  project  = elementwise expression evaluation
  limit    = compaction (stable sort by ~mask) + static slice

`prepare()` runs per chunk on the host (binding vocabularies etc. — see
expr.py); the returned `run` callable is pure and jit-traceable, and is cached
by (plan fingerprint, capacity, binding shapes) in the evaluator.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.ops.segments import (
    compact_mask,
    hash_group_order,
    lexsort_indices,
    packed_sort_indices,
    segment_aggregate,
    segment_boundaries,
    segment_arg_by,
    segment_distinct_count,
    sort_key_planes,
)
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.engine.expr import (
    BindContext,
    BoundExpr,
    ColumnBinding,
    EmitContext,
    ExprBinder,
)
from ytsaurus_tpu.schema import EValueType, TableSchema, device_dtype


@dataclass
class OutputColumn:
    name: str
    type: EValueType
    vocab: Optional[np.ndarray]


@dataclass
class PreparedQuery:
    """Host-bound execution plan for one chunk shape."""
    run: callable                  # (columns, row_valid, bindings) -> (planes, count)
    bindings: list
    output: list[OutputColumn]
    capacity: int                  # input capacity
    out_capacity: int = 0          # output plane length (≠ input for fast group)
    structure_key: tuple = ()      # host decisions that shape the program

    def binding_shapes(self) -> tuple:
        return (tuple((tuple(b.shape), str(b.dtype)) for b in self.bindings),
                self.structure_key)


import weakref

_MINMAX_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _column_min_max(col, ty: EValueType) -> tuple[int, int]:
    """Min/max of an integer column's valid values, memoized per device
    plane (two tiny reductions + host reads otherwise repeat on every
    execution of a cached plan)."""
    try:
        cached = _MINMAX_CACHE.get(col.data)
    except TypeError:
        cached = None
    if cached is not None:
        return cached
    info = np.iinfo(np.int64 if ty is EValueType.int64 else np.uint64)
    top = jnp.array(info.max, dtype=col.data.dtype)
    bot = jnp.array(info.min, dtype=col.data.dtype)
    # Both reductions cross device→host as ONE stacked transfer (the
    # `yt analyze` jax pass flagged the original two `int(jnp.min)` /
    # `int(jnp.max)` reads — two blocking syncs where one suffices).
    # analyze: allow(host-sync): the memoized min/max IS this path's one sanctioned sync
    lo_hi = np.asarray(jnp.stack(
        [jnp.min(jnp.where(col.valid, col.data, top)),
         jnp.max(jnp.where(col.valid, col.data, bot))]))
    # analyze: allow(host-sync): lo_hi is host numpy (the one stacked transfer above)
    lo, hi = int(lo_hi[0]), int(lo_hi[1])
    if hi < lo:               # no valid values at all
        lo, hi = 0, 0
    try:
        _MINMAX_CACHE[col.data] = (lo, hi)
    except TypeError:
        pass
    return lo, hi


def _column_bindings(schema: TableSchema, chunk) -> dict[str, ColumnBinding]:
    out = {}
    for col_schema in schema:
        col = chunk.columns.get(col_schema.name)
        if col is None:
            raise YtError(f"Chunk is missing column {col_schema.name!r}",
                          code=EErrorCode.QueryExecutionError)
        out[col_schema.name] = ColumnBinding(type=col_schema.type,
                                             vocab=col.dictionary)
    return out


def prepare(plan: "ir.Query | ir.FrontQuery", chunk) -> PreparedQuery:
    """Bind a plan against one chunk's vocabularies/capacity."""
    capacity = chunk.capacity
    bind_ctx = BindContext(columns=_column_bindings(plan.schema, chunk))
    binder = ExprBinder(bind_ctx)

    where_b: Optional[BoundExpr] = None
    if isinstance(plan, ir.Query) and plan.where is not None:
        where_b = binder.bind(plan.where)

    group = plan.group
    group_key_b: list[tuple[str, BoundExpr]] = []
    agg_arg_b: list[tuple[ir.AggregateItem, Optional[BoundExpr]]] = []
    post_binder: Optional[ExprBinder] = None
    having_b = None
    if group is not None:
        for item in group.group_items:
            group_key_b.append((item.name, binder.bind(item.expr)))
        for agg in group.aggregate_items:
            arg = binder.bind(agg.argument) if agg.argument is not None else None
            by_arg = binder.bind(agg.by_argument) \
                if agg.by_argument is not None else None
            agg_arg_b.append((agg, arg, by_arg))
        # Post-group namespace: keys + aggregate slots.
        post_columns: dict[str, ColumnBinding] = {}
        for (name, bound), item in zip(group_key_b, group.group_items):
            post_columns[name] = ColumnBinding(type=bound.type, vocab=bound.vocab)
        for agg, arg, _ in agg_arg_b:
            vocab = arg.vocab if (arg is not None and
                                  agg.type is EValueType.string) else None
            post_columns[agg.name] = ColumnBinding(type=agg.type, vocab=vocab)
        post_binder = ExprBinder(BindContext(columns=post_columns,
                                             bindings=bind_ctx.bindings,
                                             structure=bind_ctx.structure))
        if plan.having is not None:
            having_b = post_binder.bind(plan.having)
    final_binder = post_binder if post_binder is not None else binder

    # Window stage: binds partition/order/item expressions and registers
    # the slot columns so ORDER BY / projection can reference them.
    window = plan.window
    win_stage = None
    if window is not None:
        if group is not None:
            raise YtError("Window functions cannot combine with GROUP BY",
                          code=EErrorCode.QueryUnsupported)
        from ytsaurus_tpu.query.engine.window import WindowStage
        win_stage = WindowStage(window, binder)
        bind_ctx.columns.update(win_stage.slot_bindings())

    order_b: list[tuple[BoundExpr, bool]] = []
    if plan.order is not None:
        for item in plan.order.items:
            order_b.append((final_binder.bind(item.expr), item.descending))

    project_b: list[tuple[str, BoundExpr]] = []
    if plan.project is not None:
        for item in plan.project.items:
            project_b.append((item.name, final_binder.bind(item.expr)))
    else:
        # Identity projection over the stage's namespace.
        if group is not None:
            for (name, bound) in group_key_b:
                project_b.append((name, _post_ref(name, bound)))
            for agg, arg, _ in agg_arg_b:
                vocab = arg.vocab if (arg is not None and
                                      agg.type is EValueType.string) else None
                project_b.append((agg.name, _post_ref_t(agg.name, agg.type, vocab)))
        else:
            for col_schema in plan.schema:
                project_b.append(
                    (col_schema.name,
                     final_binder.bind(ir.TReference(type=col_schema.type,
                                                     name=col_schema.name))))
            if window is not None:
                # Identity projection carries the window slots (the
                # bottom stage of a distributed window plan).
                for item in window.items:
                    project_b.append(
                        (item.name,
                         final_binder.bind(ir.TReference(type=item.type,
                                                         name=item.name))))

    output = [OutputColumn(name=name, type=b.type, vocab=b.vocab)
              for name, b in project_b]
    offset = plan.offset
    limit = plan.limit

    # Packed-key bit widths per ORDER BY item bake into the sort
    # program (vocab-length-derived: a trace constant binding shapes
    # cannot see) — computed once here and noted into the structure key.
    order_bits = [_order_key_bits(bound) for bound, _desc in order_b]
    if order_bits:
        bind_ctx.note("obits", *order_bits)

    # Presorted-layout sort skip (ISSUE 19): tablet snapshots seal their
    # key order into chunk.sorted_by (ascending, null-first — the same
    # comparator pack_key_planes_bits encodes).  When every ORDER BY item
    # is a plain ascending column reference forming a prefix of that
    # sealed order, and no stage upstream of ORDER BY reorders rows
    # (filter only masks lanes; GROUP BY and window slots change the
    # namespace), the packed sort is the identity on valid rows: the
    # stable compact downstream yields bit-identical output without it.
    # The decision is chunk-layout-derived, so it is noted into the
    # structure key — a sealed and an unsealed chunk of the same capacity
    # must not share a compiled program.
    presorted_skip = False
    if order_b and group is None and window is None and \
            plan.order is not None and getattr(chunk, "sorted_by", ()):
        names: "list[str] | None" = []
        for item in plan.order.items:
            if isinstance(item.expr, ir.TReference) and not item.descending:
                names.append(item.expr.name)
            else:
                names = None
                break
        if names is not None and \
                tuple(names) == tuple(chunk.sorted_by)[:len(names)]:
            presorted_skip = True
            bind_ctx.note("presorted", len(names))

    # --- direct-aggregation fast path ----------------------------------------
    # When every group key has a small known value domain (dictionary codes,
    # booleans), segment ids are computed arithmetically — no sort.  This is
    # the TPU answer to the reference's open hash table in GroupOpHelper
    # (cg_routines/registry.cpp:1230): for low-cardinality keys the "hash
    # table" becomes a dense segment_sum over dict-code strides.
    fast_group = None
    if group is not None:
        # Per key: (size, offset).  Dictionary codes and booleans have known
        # domains; integer REFERENCE columns get a device min/max probe (one
        # tiny reduction, host-read) — XLA sorts collapse beyond ~4M rows on
        # TPU, so avoiding the sort is worth a probe per (chunk, plan).
        sizes_offsets: "list[tuple[int, int]] | None" = []
        for item, (_, bound) in zip(group.group_items, group_key_b):
            if bound.type is EValueType.string and bound.vocab is not None:
                sizes_offsets.append((len(bound.vocab), 0))
            elif bound.type is EValueType.boolean:
                sizes_offsets.append((2, 0))
            elif bound.type in (EValueType.int64, EValueType.uint64) and \
                    isinstance(item.expr, ir.TReference):
                col = chunk.columns.get(item.expr.name) \
                    if hasattr(chunk, "columns") else None
                data = getattr(col, "data", None)
                if data is None:          # rep chunks carry no planes
                    sizes_offsets = None
                    break
                lo, hi = _column_min_max(col, bound.type)
                if hi - lo + 1 > 65536:
                    sizes_offsets = None
                    break
                sizes_offsets.append((hi - lo + 1, lo))
            else:
                sizes_offsets = None
                break
        if sizes_offsets is not None:
            dims = 1
            for s, _ in sizes_offsets:
                dims *= s + 1          # +1 slot per key for NULL
            if 0 < dims <= 65536:
                strides = []
                acc = 1
                for s, _ in reversed(sizes_offsets):
                    strides.append(acc)
                    acc *= s + 1
                strides.reverse()
                from ytsaurus_tpu.chunks.columnar import pad_capacity
                fast_group = (tuple(sizes_offsets), tuple(strides), dims,
                              pad_capacity(dims + 1))

    # Plan auto-parameterization (ISSUE 10): OFFSET/LIMIT are static
    # residue that BUCKETS instead of hoisting — the top-k candidate
    # count must be a trace constant, so static decisions use the pow2
    # bucket (>= the actual value) while the exact offset/limit ride as
    # runtime bindings.  One program then serves every LIMIT within a
    # bucket, matching the parameterized fingerprint
    # (ir.fingerprint(omit_values=True) buckets limits the same way).
    from ytsaurus_tpu.chunks.columnar import next_pow2
    from ytsaurus_tpu.config import compile_config
    parameterized = compile_config().parameterize
    if parameterized:
        k_static = ((next_pow2(offset) if offset > 0 else 0)
                    + next_pow2(max(limit, 1))) if limit is not None \
            else None
    else:
        k_static = (offset + limit) if limit is not None else None

    # Single-key ORDER BY ... LIMIT k fast path decision (static): full
    # sorts collapse on TPU beyond a few million rows, so select ~2k
    # candidates with lax.top_k and only sort those.
    k_limit = k_static
    group_stage_cap = fast_group[3] if fast_group else capacity
    use_topk = (len(order_b) == 1 and k_limit is not None
                and 0 < k_limit <= 1024 and group_stage_cap > 4 * k_limit
                and not presorted_skip)
    topk_cand_cap = 3 * k_limit if use_topk else None

    offset_slot = limit_slot = None
    if parameterized:
        offset_slot = bind_ctx.add(jnp.asarray(np.int64(offset)))
        if limit is not None:
            limit_slot = bind_ctx.add(jnp.asarray(np.int64(limit)))

    def run(columns: dict, row_valid: jax.Array, bindings: tuple):
        ctx = EmitContext(columns=columns, bindings=bindings, capacity=capacity)
        stage_cap = capacity
        mask = row_valid
        # Stage names for the device trace (trace-time metadata: an HLO
        # op's name carries the scope it was emitted under).
        with jax.named_scope("ql.filter"):
            if where_b is not None:
                d, v = where_b.emit(ctx)
                mask = mask & v & d.astype(bool)

        with jax.named_scope("ql.group"):
            if group is not None and fast_group is not None:
                sizes_offsets, strides, dims, seg_cap = fast_group
                nseg = dims + 1                    # +1 garbage slot for masked rows

                def _pad(plane):
                    return jnp.zeros(seg_cap, dtype=plane.dtype).at[:nseg].set(plane)

                key_planes = [b.emit(ctx) for _, b in group_key_b]
                seg = jnp.zeros(capacity, dtype=jnp.int32)
                for (data, valid), (size, key_offset), stride in zip(
                        key_planes, sizes_offsets, strides):
                    if jnp.issubdtype(data.dtype, jnp.integer):
                        # Modular uint64 subtraction: correct for int64 offsets
                        # near the type bounds and uint64 keys >= 2^63.
                        off = np.uint64(key_offset % (1 << 64))
                        shifted = (data.astype(jnp.uint64) - off).astype(jnp.int32)
                    else:
                        shifted = (data.astype(jnp.int64)
                                   - key_offset).astype(jnp.int32)
                    code = jnp.where(valid, shifted, size)
                    seg = seg + code * stride
                seg = jnp.where(mask, seg, dims)   # masked-out rows → garbage slot
                # Above the dense-reduce limit the reduction needs segment-
                # sorted rows (scatter-adds serialize on TPU) — ONE u32 sort
                # here is shared by every aggregate below.
                from ytsaurus_tpu.ops.segments import presort_segments
                grp_order = presort_segments(seg, nseg)
                presorted = grp_order is not None
                if presorted:
                    seg = seg[grp_order]
                    gmask = mask[grp_order]
                else:
                    gmask = mask

                def _r(plane):
                    return plane if grp_order is None else plane[grp_order]

                present_counts, _ = segment_aggregate(
                    "count", gmask, gmask, seg, nseg, EValueType.int64,
                    assume_sorted=presorted)
                present = _pad((jnp.arange(nseg) < dims) & (present_counts > 0))
                new_columns: dict[str, tuple[jax.Array, jax.Array]] = {}
                slot = jnp.arange(seg_cap)
                for (name, bound), (size, key_offset), stride in zip(
                        group_key_b, sizes_offsets, strides):
                    code = (slot // stride) % (size + 1)
                    key_valid = code < size
                    data = jnp.clip(code, 0, max(size - 1, 0))
                    if bound.type is EValueType.boolean:
                        data = data.astype(jnp.bool_)
                    elif bound.type in (EValueType.int64, EValueType.uint64):
                        dt = device_dtype(bound.type)
                        data = data.astype(dt) + jnp.array(key_offset, dtype=dt)
                    else:
                        data = data.astype(jnp.int32)
                    new_columns[name] = (data, key_valid)
                for agg, arg, by_arg in agg_arg_b:
                    if agg.function == "avg":
                        data, valid = arg.emit(ctx)
                        data = _r(data).astype(jnp.float64)
                        valid = _r(valid) & gmask
                        s, sv = segment_aggregate("sum", data, valid, seg,
                                                  nseg, EValueType.double,
                                                  assume_sorted=presorted)
                        c, _ = segment_aggregate("count", data, valid, seg,
                                                 nseg, EValueType.int64,
                                                 assume_sorted=presorted)
                        new_columns[agg.name] = (_pad(s / jnp.maximum(c, 1)),
                                                 _pad(sv))
                    elif agg.function == "cardinality":
                        data, valid = arg.emit(ctx)
                        d, dv = segment_distinct_count(
                            _r(data), _r(valid) & gmask, seg, nseg)
                        new_columns[agg.name] = (_pad(d), _pad(dv))
                    elif agg.function in ("argmin", "argmax"):
                        vd, vv = arg.emit(ctx)
                        bd, bv = by_arg.emit(ctx)
                        out_d, out_v = segment_arg_by(
                            _r(vd), _r(vv), _r(bd), _r(bv) & gmask, seg, nseg,
                            take_max=(agg.function == "argmax"),
                            assume_sorted=presorted)
                        new_columns[agg.name] = (_pad(out_d), _pad(out_v))
                    else:
                        data, valid = arg.emit(ctx)
                        valid = _r(valid) & gmask
                        out, out_v = segment_aggregate(
                            agg.function, _r(data), valid, seg, nseg, agg.type,
                            assume_sorted=presorted)
                        new_columns[agg.name] = (_pad(out), _pad(out_v))
                mask = present
                stage_cap = seg_cap
                ctx = EmitContext(columns=new_columns, bindings=bindings,
                                  capacity=seg_cap)
                if having_b is not None:
                    d, v = having_b.emit(ctx)
                    mask = mask & v & d.astype(bool)
            elif group is not None:
                key_planes = [b.emit(ctx) for _, b in group_key_b]
                # Exact grouping order: equal key tuples made adjacent via
                # the order-preserving key encoding (segments.py), masked
                # rows last; large/wide keys dispatch to the tiled radix
                # engine (ops/radix.py) instead of the one-pass network.
                order_idx = hash_group_order(key_planes, mask)
                sorted_mask = mask[order_idx]
                sorted_keys = [(d[order_idx], v[order_idx]) for d, v in key_planes]
                seg_ids, num_groups = segment_boundaries(sorted_keys, sorted_mask)
                new_columns: dict[str, tuple[jax.Array, jax.Array]] = {}
                for (name, _), (data, valid) in zip(group_key_b, sorted_keys):
                    out_d, _ = segment_aggregate("first", data, sorted_mask,
                                                 seg_ids, capacity,
                                                 EValueType.null,
                                                 assume_sorted=True)
                    out_v, _ = segment_aggregate(
                        "first", valid.astype(jnp.int8), sorted_mask, seg_ids,
                        capacity, EValueType.null, assume_sorted=True)
                    new_columns[name] = (out_d, out_v.astype(bool))
                for agg, arg, by_arg in agg_arg_b:
                    if agg.function == "avg":
                        data, valid = arg.emit(ctx)
                        data = data[order_idx].astype(jnp.float64)
                        valid = valid[order_idx] & sorted_mask
                        s, sv = segment_aggregate("sum", data, valid, seg_ids,
                                                  capacity, EValueType.double,
                                                  assume_sorted=True)
                        c, _ = segment_aggregate("count", data, valid, seg_ids,
                                                 capacity, EValueType.int64,
                                                 assume_sorted=True)
                        cnt = jnp.maximum(c, 1)
                        new_columns[agg.name] = (s / cnt, sv)
                    elif agg.function == "cardinality":
                        data, valid = arg.emit(ctx)
                        d, dv = segment_distinct_count(
                            data[order_idx], valid[order_idx] & sorted_mask,
                            seg_ids, capacity)
                        new_columns[agg.name] = (d, dv)
                    elif agg.function in ("argmin", "argmax"):
                        vd, vv = arg.emit(ctx)
                        bd, bv = by_arg.emit(ctx)
                        out_d, out_v = segment_arg_by(
                            vd[order_idx], vv[order_idx],
                            bd[order_idx], bv[order_idx] & sorted_mask,
                            seg_ids, capacity,
                            take_max=(agg.function == "argmax"),
                            assume_sorted=True)
                        new_columns[agg.name] = (out_d, out_v)
                    else:
                        data, valid = arg.emit(ctx)
                        data = data[order_idx]
                        valid = valid[order_idx] & sorted_mask
                        out, out_v = segment_aggregate(
                            agg.function, data, valid, seg_ids, capacity,
                            agg.type, assume_sorted=True)
                        new_columns[agg.name] = (out, out_v)
                mask = jnp.arange(capacity) < num_groups
                ctx = EmitContext(columns=new_columns, bindings=bindings,
                                  capacity=capacity)
                if having_b is not None:
                    d, v = having_b.emit(ctx)
                    mask = mask & v & d.astype(bool)

        with jax.named_scope("ql.window"):
            if win_stage is not None:
                # Window columns join the namespace; no rows move.
                win_columns = win_stage.emit(ctx, mask)
                ctx = EmitContext(columns={**ctx.columns, **win_columns},
                                  bindings=bindings, capacity=stage_cap)

        with jax.named_scope("ql.order"):
            if order_b and not presorted_skip:
                # Candidates = top-k by value (masked excluded) ∪ up-to-k null
                # rows (null ordering differs by direction; the tiny exact sort
                # below settles it).
                if use_topk:
                    bound, descending = order_b[0]
                    data, valid = bound.emit(ctx)
                    value, null_key = sort_key_planes(data, valid, descending)
                    # Invert the value so top_k picks the query's front.  Valid
                    # rows compete by value; null rows are all equal (their
                    # position relative to values is settled by the tiny exact
                    # sort below), so an indicator pass covers them; a third
                    # indicator pass covers valid rows whose inverted value
                    # aliases the exclusion sentinel (single value class).
                    if jnp.issubdtype(value.dtype, jnp.unsignedinteger):
                        inv = ~value
                    elif jnp.issubdtype(value.dtype, jnp.integer) or \
                            value.dtype == jnp.bool_:
                        inv = ~value.astype(jnp.int64)
                    else:
                        inv = -value.astype(jnp.float64)
                    if jnp.issubdtype(inv.dtype, jnp.integer):
                        bottom = jnp.array(jnp.iinfo(inv.dtype).min, inv.dtype)
                    else:
                        bottom = jnp.array(-jnp.inf, inv.dtype)
                    include = mask & valid
                    ranked = jnp.where(include, inv, bottom)
                    _, idx1 = jax.lax.top_k(ranked, k_limit)
                    nulls = (mask & ~valid).astype(jnp.int32)
                    _, idx2 = jax.lax.top_k(nulls, k_limit)
                    aliased = (include & (inv == bottom)).astype(jnp.int32)
                    _, idx3 = jax.lax.top_k(aliased, k_limit)
                    cand = jnp.concatenate([idx1, idx2, idx3])
                    # Dedupe candidates (overlap would duplicate rows).
                    cand_sorted = jnp.sort(cand)
                    dup = jnp.concatenate([
                        jnp.zeros(1, dtype=bool),
                        cand_sorted[1:] == cand_sorted[:-1]])
                    cand_cap = cand.shape[0]
                    ctx = EmitContext(
                        columns={name: (d[cand_sorted], v[cand_sorted])
                                 for name, (d, v) in ctx.columns.items()},
                        bindings=bindings, capacity=cand_cap)
                    mask = mask[cand_sorted] & ~dup
                    stage_cap = cand_cap
                # Packed composite sort key: masked-last bit + every ORDER BY
                # item (null bit + order-preserving value bits) packed into as
                # few u64 words as possible — minimum operands through the
                # device sort network (payload columns are gathered after).
                items = [((~mask), jnp.ones_like(mask), False, 1)]
                for (bound, descending), bits in zip(order_b, order_bits):
                    data, valid = bound.emit(ctx)
                    items.append((data, valid, descending, bits))
                order_idx = packed_sort_indices(items)
                ctx = EmitContext(
                    columns={name: (d[order_idx], v[order_idx])
                             for name, (d, v) in ctx.columns.items()},
                    bindings=bindings, capacity=stage_cap)
                mask = mask[order_idx]

        with jax.named_scope("ql.compact"):
            planes = []
            for name, bound in project_b:
                d, v = bound.emit(ctx)
                planes.append((d, v))

            # Compact valid rows to the front (stable → preserves sort order).
            comp_idx, total = compact_mask(mask)
            if offset_slot is not None:
                # Dynamic offset/limit (read from bindings): clamped to the
                # stage capacity so the downstream int32 arithmetic is safe.
                off = jnp.minimum(bindings[offset_slot],
                                  stage_cap).astype(total.dtype)
            else:
                off = offset
            count = total - off
            if limit is not None:
                lim = jnp.minimum(bindings[limit_slot],
                                  stage_cap).astype(total.dtype) \
                    if limit_slot is not None else limit
                count = jnp.minimum(count, lim)
            count = jnp.maximum(count, 0)
            out_planes = []
            shift = jnp.clip(jnp.arange(stage_cap) + off, 0, stage_cap - 1)
            for d, v in planes:
                d = d[comp_idx][shift]
                v = v[comp_idx][shift] & (jnp.arange(stage_cap) < count)
                out_planes.append((d, v))
        return out_planes, count

    return PreparedQuery(
        run=run, bindings=bind_ctx.bindings, output=output, capacity=capacity,
        out_capacity=topk_cand_cap if use_topk else group_stage_cap,
        structure_key=((("fastgrp",) + fast_group[0] if fast_group else ())
                       + (("topk", k_limit) if use_topk else ())
                       + (("param", k_static) if parameterized else ())
                       + tuple(bind_ctx.structure)))


def _order_key_bits(bound: BoundExpr) -> int:
    """Packed-key width for one ORDER BY item: dictionary codes and bools
    need few bits; everything else is full-width."""
    if bound.type is EValueType.boolean:
        return 1
    if bound.type is EValueType.string and bound.vocab is not None:
        return max(len(bound.vocab) - 1, 1).bit_length()
    return 64


def _post_ref(name: str, bound: BoundExpr) -> BoundExpr:
    return _post_ref_t(name, bound.type, bound.vocab)


def _post_ref_t(name: str, ty: EValueType, vocab) -> BoundExpr:
    def emit(ctx: EmitContext):
        return ctx.columns[name]
    return BoundExpr(type=ty, vocab=vocab, emit=emit)
