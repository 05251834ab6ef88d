"""Equi-join execution: device sort-merge over columnar planes.

TPU-first redesign of the reference's MultiJoinOpHelper (cg_routines/
registry.cpp:599 — batched hash lookups into foreign tables): the foreign
side is lex-sorted by join key once, each self row finds its match range via
a vectorized lexicographic binary search, and the (self, foreign) index pairs
are materialized with a static output capacity computed host-side between the
two jitted phases (shape buckets keep recompiles bounded).

Both phases are jit-compiled and cached by (join fingerprint, capacities,
binding shapes); only the total match count crosses to the host between
them.
"""

from __future__ import annotations

import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.chunks.columnar import Column, ColumnarChunk, pad_capacity
from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.ops.segments import lexsort_indices
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.engine.expr import (
    BindContext,
    ColumnBinding,
    EmitContext,
    ExprBinder,
    _merge_vocabs,
    _pad_np,
    _remap_table,
    _vocab_bucket,
)
from ytsaurus_tpu.schema import TableSchema
from ytsaurus_tpu.utils.tracing import NULL_SPAN, child_span


def _bind_keys(chunk: ColumnarChunk, schema: TableSchema,
               equations: tuple[ir.TExpr, ...], shared_bindings: list,
               structure: "list | None" = None):
    """Host phase: bind join-key expressions against a chunk's vocabularies.
    All slots index into ONE shared bindings list so both sides' emit
    closures can run under the same traced tuple.  `structure` (when
    given) collects the bind-phase structure notebook — baked host
    constants like concat's pair width — which the CALLER must fold
    into its program-cache key (ISSUE 10 sharing contract)."""
    bind_ctx = BindContext(columns={
        c.name: ColumnBinding(type=c.type, vocab=chunk.columns[c.name].dictionary)
        for c in schema}, bindings=shared_bindings,
        structure=structure if structure is not None else [])
    binder = ExprBinder(bind_ctx)
    return [binder.bind(e) for e in equations]


def _emit_encoded_keys(bound, remap_slots, ctx: EmitContext):
    """Trace phase: emit key planes encoded as (null_rank, value) pairs with
    string codes remapped onto the shared vocabulary."""
    out = []
    for b, slot in zip(bound, remap_slots):
        data, valid = b.emit(ctx)
        if slot is not None:
            table = ctx.bindings[slot]
            data = table[jnp.clip(data, 0, table.shape[0] - 1)]
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int8)
        data = jnp.where(valid, data, jnp.zeros_like(data))
        out.append((valid.astype(jnp.int8), data))
    return out


def _lex_less(a_planes, b_planes, a_idx, b_idx, or_equal: bool):
    """Lexicographic a[a_idx] < b[b_idx] (or <= when or_equal) over encoded
    (null_rank, value) key plane pairs; null sorts before any value."""
    result = jnp.full(a_idx.shape, or_equal, dtype=bool)
    for (av, ad), (bv, bd) in reversed(list(zip(a_planes, b_planes))):
        a_v, a_d = av[a_idx], ad[a_idx]
        b_v, b_d = bv[b_idx], bd[b_idx]
        lt = (a_v < b_v) | ((a_v == b_v) & (a_d < b_d))
        eq = (a_v == b_v) & (a_d == b_d)
        result = lt | (eq & result)
    return result


def _lex_searchsorted(sorted_planes, n_sorted, max_n: int, query_planes,
                      side: str):
    """For each query row, binary-search the sorted key planes.
    side='left' → first index whose key >= query; 'right' → first > query.
    `n_sorted` is a traced scalar (live row count); `max_n` is the static
    capacity bound driving the iteration count so the compiled program is
    row-count independent."""
    cap_q = query_planes[0][0].shape[0]
    lo = jnp.zeros(cap_q, dtype=jnp.int64)
    hi = jnp.full(cap_q, n_sorted, dtype=jnp.int64)
    iters = max(1, int(np.ceil(np.log2(max(max_n, 2)))) + 1)
    q_idx = jnp.arange(cap_q)

    def body(_, carry):
        lo, hi = carry
        active = lo < hi
        mid = (lo + hi) // 2
        mid_c = jnp.clip(mid, 0, max(max_n - 1, 0))
        go_right = _lex_less(sorted_planes, query_planes, mid_c, q_idx,
                             or_equal=(side == "right"))
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def sort_foreign_keys(f_keys, f_valid):
    """Sort encoded foreign key planes (masked rows last); returns
    (f_order, f_sorted).  THE foreign-side ordering used by both the host
    join phases and the SPMD broadcast join."""
    sort_keys = []
    for v, d in reversed(f_keys):
        sort_keys.extend([d, v])
    sort_keys.append((~f_valid).astype(jnp.int8))
    f_order = lexsort_indices(sort_keys)
    return f_order, [(v[f_order], d[f_order]) for v, d in f_keys]


def null_key_mask(self_keys):
    """Rows whose join key has ANY null component (match nothing — SQL
    semantics)."""
    cap = self_keys[0][0].shape[0]
    s_null = jnp.zeros(cap, dtype=bool)
    for v, _ in self_keys:
        s_null = s_null | (v == 0)
    return s_null


def probe_replicated(sl, n_keys: int, f_cap: int, self_keys, mask,
                     is_left: bool):
    """THE broadcast-join probe body, shared by the stitched SPMD join
    (distributed.py) and the fused whole-plan join (whole_plan.py).

    `sl` is one join's replicated arg slice, laid out as
    [v_0, d_0, … v_{k-1}, d_{k-1},  pulled (data, valid) pairs …,
    n_foreign]: lex-search the sorted foreign key planes for each self
    row, gather every pulled plane at the (unique-key) match row masked
    to matched, and narrow the row mask for INNER joins.  Returns
    (pulled_planes, new_mask)."""
    f_sorted = [(sl[2 * i], sl[2 * i + 1]) for i in range(n_keys)]
    n_foreign = sl[-1]
    lo = _lex_searchsorted(f_sorted, n_foreign, f_cap, self_keys, "left")
    hi = _lex_searchsorted(f_sorted, n_foreign, f_cap, self_keys,
                           "right")
    matched = mask & ~null_key_mask(self_keys) & (hi > lo)
    pos = jnp.clip(lo, 0, f_cap - 1)
    base = 2 * n_keys
    pulled = [(sl[base + 2 * i][pos], sl[base + 2 * i + 1][pos] & matched)
              for i in range((len(sl) - base - 1) // 2)]
    return pulled, (mask if is_left else matched)


def _join_fingerprint(join: ir.JoinClause) -> str:
    # The full JoinClause serialized (equations, alias, is_left, pulled
    # columns) as a SHAPE fingerprint (ISSUE 10): the phase programs
    # read equation literals from the shared bindings tuple per call,
    # and the cache key already carries binding shapes + exact vocab
    # structure, so one program serves every equation constant.
    from ytsaurus_tpu.query.parameterize import plan_fingerprint
    return plan_fingerprint(ir.Query(
        schema=join.foreign_schema, source=join.foreign_table,
        joins=(join,)))


def execute_join(chunk: ColumnarChunk, combined_schema: TableSchema,
                 join: ir.JoinClause, foreign_chunk: ColumnarChunk,
                 cache: dict, stats=None, span=NULL_SPAN) -> ColumnarChunk:
    """Materialize `chunk ⋈ foreign_chunk` into a wider columnar chunk.

    `combined_schema` is the namespace *after* this join (flat names):
    the columns of `chunk` it names and `join.foreign_columns` are the
    ones phase 2 expands (`ir.join_cascade` leaves out what nothing
    reads afterwards, the stage's own key among them);
    `cache` holds the compiled phase programs (owned by the Evaluator so
    lifetime/clearing follow the plan cache).  `stats` (a
    QueryStatistics) counts the host sync between the phases and its
    seconds; `span` (the caller's `evaluator.join`) is tagged with how
    the program lookup ended and with the output capacity.
    """
    self_schema = chunk.schema
    self_names = [c.name for c in self_schema if c.name in combined_schema]
    all_bindings: list = []
    bind_structure: list = []
    self_bound = _bind_keys(chunk, self_schema, join.self_equations,
                            all_bindings, structure=bind_structure)
    f_bound = _bind_keys(foreign_chunk, join.foreign_schema,
                         join.foreign_equations, all_bindings,
                         structure=bind_structure)
    # String keys: remap both sides onto merged vocabularies (host).
    self_slots: list = []
    foreign_slots: list = []

    def add_binding(value):
        all_bindings.append(value)
        return len(all_bindings) - 1

    for sb, fb in zip(self_bound, f_bound):
        if sb.vocab is not None or fb.vocab is not None:
            merged = _merge_vocabs(sb.vocab, fb.vocab)
            s_vocab = sb.vocab if sb.vocab is not None else \
                np.array([], dtype=object)
            f_vocab = fb.vocab if fb.vocab is not None else \
                np.array([], dtype=object)
            s_table = _remap_table(s_vocab, merged)
            f_table = _remap_table(f_vocab, merged)
            self_slots.append(add_binding(jnp.asarray(
                _pad_np(s_table, _vocab_bucket(len(s_table)), 0))))
            foreign_slots.append(add_binding(jnp.asarray(
                _pad_np(f_table, _vocab_bucket(len(f_table)), 0))))
        else:
            self_slots.append(None)
            foreign_slots.append(None)

    n_foreign = foreign_chunk.row_count
    # Exact vocab lengths of every key expr: bound-vocab-derived Python
    # constants (e.g. concat's pair-table width) bake into the traced
    # program, and bucket-padded binding shapes alone cannot distinguish
    # them.
    vocab_structure = tuple(
        (len(b.vocab) if b.vocab is not None else -1)
        for b in list(self_bound) + list(f_bound))
    cache_key = (_join_fingerprint(join), chunk.capacity,
                 foreign_chunk.capacity,
                 tuple(c.name for c in self_schema), tuple(self_names),
                 vocab_structure,
                 # Bind-phase structure notebook (ISSUE 10): host
                 # constants the equation binds BAKE (concat's nb
                 # multiplier) that neither vocab lengths nor padded
                 # binding shapes can distinguish.
                 tuple(bind_structure),
                 tuple((tuple(b.shape), str(b.dtype)) for b in all_bindings))
    entry = cache.get(cache_key)
    span.add_tag("cache", "miss" if entry is None else "hit")
    if entry is None:
        entry = _build_join_programs(
            self_bound, f_bound, self_slots, foreign_slots,
            chunk.capacity, foreign_chunk.capacity, join.is_left,
            self_names, list(join.foreign_columns))
        cache[cache_key] = entry
    phase1, make_phase2 = entry

    self_columns = {c.name: (chunk.columns[c.name].data,
                             chunk.columns[c.name].valid)
                    for c in self_schema}
    foreign_columns = {name: (foreign_chunk.columns[name].data,
                              foreign_chunk.columns[name].valid)
                       for name in set(list(join.foreign_columns) +
                                       list(join.foreign_schema.column_names))}
    args = (self_columns, foreign_columns, chunk.row_valid,
            foreign_chunk.row_valid, tuple(all_bindings),
            jnp.asarray(n_foreign, dtype=jnp.int64))
    lo, counts, f_order, total = phase1(*args)
    # The one host sync between the phases: the match count sizes phase
    # 2's static output.  The wait is phase 1's device time as the host
    # sees it.
    t_sync = time.perf_counter()
    with child_span("join.count_sync"):
        total = int(total)
    if stats is not None:
        stats.join_host_syncs += 1
        stats.join_sync_time += time.perf_counter() - t_sync
    out_cap = pad_capacity(max(total, 1))
    span.add_tag("out_capacity", out_cap)
    phase2 = make_phase2(out_cap)
    out_planes, self_row, foreign_row = phase2(*args, lo, counts, f_order)

    columns: dict[str, Column] = {}
    self_row_np = None
    for name in self_names:
        col = chunk.columns[name]
        data, valid = out_planes["self"][name]
        host_values = None
        if col.host_values is not None:
            if self_row_np is None:
                # analyze: allow(host-sync): string/any columns live on host — the gather index must cross once
                self_row_np = np.asarray(self_row)
            host_values = _gather_host(col, self_row_np, out_cap)
        columns[name] = replace(col, data=data, valid=valid,
                                host_values=host_values)
    foreign_row_np = None
    for fname in join.foreign_columns:
        fcol = foreign_chunk.columns[fname]
        flat = f"{join.alias}.{fname}" if join.alias else fname
        data, valid = out_planes["foreign"][fname]
        host_values = None
        if fcol.host_values is not None:
            if foreign_row_np is None:
                # analyze: allow(host-sync): string/any columns live on host — the gather index must cross once
                foreign_row_np = np.asarray(foreign_row)
            host_values = _gather_host(fcol, foreign_row_np, out_cap)
        columns[flat] = replace(fcol, data=data, valid=valid,
                                host_values=host_values)
    out_columns = {}
    for col_schema in combined_schema:
        if col_schema.name not in columns:
            raise YtError(f"Join produced no column {col_schema.name!r}",
                          code=EErrorCode.QueryExecutionError)
        out_columns[col_schema.name] = columns[col_schema.name]
    return ColumnarChunk(schema=combined_schema, row_count=total,
                         columns=out_columns)


def _build_join_programs(self_bound, f_bound, self_slots, foreign_slots,
                         self_cap, foreign_cap,
                         is_left, self_names, foreign_names):
    def phase1(self_columns, foreign_columns, s_valid, f_valid, bindings,
               n_foreign):
        s_ctx = EmitContext(columns=self_columns, bindings=bindings,
                            capacity=self_cap)
        f_ctx = EmitContext(columns=foreign_columns, bindings=bindings,
                            capacity=foreign_cap)
        self_keys = _emit_encoded_keys(self_bound, self_slots, s_ctx)
        foreign_keys = _emit_encoded_keys(f_bound, foreign_slots, f_ctx)
        # Sort foreign side (first key most significant; masked rows last).
        with jax.named_scope("ql.join.sort"):
            f_order, f_sorted = sort_foreign_keys(foreign_keys, f_valid)
        with jax.named_scope("ql.join.probe"):
            lo = _lex_searchsorted(f_sorted, n_foreign, foreign_cap,
                                   self_keys, "left")
            hi = _lex_searchsorted(f_sorted, n_foreign, foreign_cap,
                                   self_keys, "right")
        s_null = null_key_mask(self_keys)
        counts = jnp.where(s_valid & ~s_null, hi - lo, 0)
        if is_left:
            per_row = jnp.where(s_valid, jnp.maximum(counts, 1), 0)
        else:
            per_row = counts
        total = jnp.sum(per_row)
        return lo, counts, f_order, total

    phase2_cache: dict[int, callable] = {}

    def make_phase2(out_cap: int):
        fn = phase2_cache.get(out_cap)
        if fn is not None:
            return fn

        @jax.named_scope("ql.join.expand")
        def phase2(self_columns, foreign_columns, s_valid, f_valid, bindings,
                   n_foreign, lo, counts, f_order):
            if is_left:
                per_row = jnp.where(s_valid, jnp.maximum(counts, 1), 0)
            else:
                per_row = counts
            offsets = jnp.cumsum(per_row)
            total = offsets[-1]
            starts = jnp.concatenate(
                [jnp.zeros(1, dtype=offsets.dtype), offsets[:-1]])
            out_idx = jnp.arange(out_cap)
            self_row = jnp.searchsorted(offsets, out_idx, side="right")
            self_row = jnp.clip(self_row, 0, self_cap - 1)
            within = out_idx - starts[self_row]
            matched = counts[self_row] > 0
            foreign_pos = jnp.clip(lo[self_row] + within, 0, foreign_cap - 1)
            foreign_row = f_order[foreign_pos]
            out_valid_row = out_idx < total
            out = {"self": {}, "foreign": {}}
            for name in self_names:
                data, valid = self_columns[name]
                out["self"][name] = (data[self_row],
                                     valid[self_row] & out_valid_row)
            for name in foreign_names:
                data, valid = foreign_columns[name]
                out["foreign"][name] = (
                    data[foreign_row],
                    valid[foreign_row] & out_valid_row & matched)
            return out, self_row, foreign_row

        # lo/counts/f_order are phase1 outputs owned by execute_join
        # and phase2 is their only consumer — donate them so XLA reuses
        # the three chunk-sized planes for phase2's gather outputs
        # (ISSUE 19; inert on CPU).  Donation mode bakes at build time;
        # programs are cached, so a mid-process config flip keeps the
        # built mode (donation never changes results, only residency).
        from ytsaurus_tpu.config import compile_config
        donate = (6, 7, 8) if compile_config().donate_buffers else ()
        fn = jax.jit(phase2, donate_argnums=donate)
        phase2_cache[out_cap] = fn
        return fn

    return jax.jit(phase1), make_phase2


def _gather_host(col: Column, idx: np.ndarray, out_cap: int):
    if col.host_values is None:
        return None
    vals = [col.host_values[int(i)] if int(i) < len(col.host_values) else None
            for i in idx[:out_cap]]
    return vals
