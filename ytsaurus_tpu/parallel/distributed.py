"""SPMD distributed query execution over a device mesh.

The host-coordinated path (query/coordinator.py) loops over shards; this
module is the TPU-native fast path: every shard (tablet analog) lives on its
own device, the bottom query runs as ONE shard_map program, and the front
merge happens on-device via all_gather over ICI — no host round-trip, no bus.

Ref mapping (SURVEY.md §2.8 parallelism table):
  partition-parallel scan  → shard_map over the 'shard' mesh axis
  two-phase aggregation    → per-shard partial states + all_gather + re-group
  (psum applies when group keys are static; the general re-group handles
  arbitrary key sets)
"""

from __future__ import annotations

import warnings as _warnings
from dataclasses import dataclass
from typing import Optional, Sequence

import jax

# Buffer donation (ISSUE 19) is inert on CPU backends but warns per
# call; keep the armed SPMD path quiet on the CPU test floor.
_warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ytsaurus_tpu.chunks.columnar import (
    Column,
    ColumnarChunk,
    unify_dictionaries,
)
from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.parallel.mesh import SHARD_AXIS
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.coordinator import split_plan
from ytsaurus_tpu.query.parameterize import plan_fingerprint
from ytsaurus_tpu.query.engine.lowering import prepare
from ytsaurus_tpu.schema import EValueType, TableSchema
from ytsaurus_tpu.utils import failpoints
from ytsaurus_tpu.utils.logging import get_logger

_ladder_log = get_logger("Distributed")


def _exchange_error(site: str) -> YtError:
    return YtError(f"injected collective failure at {site}",
                   code=EErrorCode.QueryExecutionError,
                   attributes={"failpoint": site})


# Shuffle-boundary fault sites: all_to_all guards the co-partition
# exchange, gather the all_gather merge.  coordinate_distributed's
# degradation ladder steps down a rung when one of them fails.
_FP_ALL_TO_ALL = failpoints.register_site("parallel.all_to_all",
                                          error=_exchange_error)
_FP_GATHER = failpoints.register_site("parallel.gather",
                                      error=_exchange_error)

# Mid-plan host-sync accounting (ISSUE 12): every blocking device→host
# read a distributed query performs notes here — the stitched rungs pay
# one per exchange-quota decision plus the final count; the whole-plan
# path pays exactly one (the final stacked transfer).  A plain counter
# (not a sensor): tests/test_whole_plan.py and test_multiway_join.py
# assert on its deltas.
_host_syncs_n = 0


def _note_host_sync() -> None:
    global _host_syncs_n
    _host_syncs_n += 1


def host_sync_count() -> int:
    return _host_syncs_n


@dataclass
class _RepColumn:
    """Vocabulary/type carrier used to bind plans without device planes."""
    type: EValueType
    dictionary: Optional[np.ndarray]


@dataclass
class _RepChunk:
    capacity: int
    columns: dict


class ShardedTable:
    """A table partitioned across a device mesh.

    All shards share one schema, one per-shard capacity and ONE unified
    string vocabulary per column (so dictionary codes agree across devices —
    the HBM-staging analog of the reference's in_memory_manager keeping
    chunks resident in a common format, tablet_node/in_memory_manager.h).

    Planes are global arrays of shape (n_shards * capacity,) sharded along
    the mesh axis; each device holds its (capacity,) slice.
    """

    def __init__(self, schema: TableSchema, mesh: Mesh, capacity: int,
                 columns: dict[str, Column], row_counts: list[int],
                 row_valid: jax.Array):
        self.schema = schema
        self.mesh = mesh
        self.capacity = capacity            # per shard
        self.columns = columns              # global sharded planes
        self.row_counts = row_counts
        self.row_valid = row_valid

    @property
    def n_shards(self) -> int:
        return len(self.row_counts)

    @property
    def total_rows(self) -> int:
        return sum(self.row_counts)

    @staticmethod
    def from_chunks(mesh: Mesh, chunks: Sequence[ColumnarChunk]
                    ) -> "ShardedTable":
        n = mesh.devices.size
        if len(chunks) != n:
            raise YtError(f"Need exactly {n} shards for this mesh, "
                          f"got {len(chunks)}",
                          code=EErrorCode.QueryExecutionError)
        schema = chunks[0].schema
        for c in chunks[1:]:
            if c.schema != schema:
                raise YtError("Shard schema mismatch",
                              code=EErrorCode.QueryExecutionError)
        cap = max(c.capacity for c in chunks)
        chunks = [c.with_capacity(cap) for c in chunks]
        shard_sharding = NamedSharding(mesh, P(SHARD_AXIS))
        columns: dict[str, Column] = {}
        for col_schema in schema:
            cols = [c.column(col_schema.name) for c in chunks]
            vocab = None
            if col_schema.type is EValueType.string:
                cols, vocab = unify_dictionaries(cols)
            data = jnp.concatenate([col.data for col in cols])
            valid = jnp.concatenate([col.valid for col in cols])
            data = jax.device_put(data, shard_sharding)
            valid = jax.device_put(valid, shard_sharding)
            columns[col_schema.name] = Column(
                type=col_schema.type, data=data, valid=valid, dictionary=vocab)
        row_valid = jnp.concatenate(
            [jnp.arange(cap) < c.row_count for c in chunks])
        row_valid = jax.device_put(row_valid, shard_sharding)
        return ShardedTable(schema=schema, mesh=mesh, capacity=cap,
                            columns=columns,
                            row_counts=[c.row_count for c in chunks],
                            row_valid=row_valid)

    def rep_chunk(self) -> _RepChunk:
        return _RepChunk(
            capacity=self.capacity,
            columns={name: _RepColumn(type=col.type, dictionary=col.dictionary)
                     for name, col in self.columns.items()})


def _assemble_chunk(prepared_output, out_planes, out_count) -> ColumnarChunk:
    """Materialize prepared-query output planes into a ColumnarChunk."""
    out_columns: dict[str, Column] = {}
    out_schema_cols = []
    for out_col, (data, valid) in zip(prepared_output, out_planes):
        out_schema_cols.append((out_col.name, out_col.type.value))
        out_columns[out_col.name] = Column(
            type=out_col.type, data=data, valid=valid,
            dictionary=out_col.vocab)
    return ColumnarChunk(schema=TableSchema.make(out_schema_cols),
                         row_count=int(out_count), columns=out_columns)


def _canonical_hash_plane(data: jax.Array) -> jax.Array:
    """Canonicalize values before hashing for routing: -0.0 and +0.0
    compare equal but differ by bit pattern, so without this two rows
    that MATCH under the join/group comparison could land on different
    devices and never meet."""
    if jnp.issubdtype(data.dtype, jnp.floating):
        return jnp.where(data == 0, jnp.zeros_like(data), data)
    return data


def _vocab_remap_slots(self_bound, f_bound, bindings: list):
    """String join keys: both sides' dictionary codes are remapped onto a
    MERGED vocabulary so equality compares one code space (the SPMD
    analog of execute_join's host remap).  Returns per-key binding slots
    (None for non-string keys); tables are appended to `bindings`."""
    import numpy as np

    from ytsaurus_tpu.query.engine.expr import (
        _merge_vocabs, _pad_np, _remap_table, _vocab_bucket,
    )

    self_slots: list = []
    foreign_slots: list = []
    for sb, fb in zip(self_bound, f_bound):
        if sb.vocab is None and fb.vocab is None:
            self_slots.append(None)
            foreign_slots.append(None)
            continue
        s_vocab = sb.vocab if sb.vocab is not None \
            else np.array([], dtype=object)
        f_vocab = fb.vocab if fb.vocab is not None \
            else np.array([], dtype=object)
        merged = _merge_vocabs(s_vocab, f_vocab)
        for vocab in (s_vocab, f_vocab):
            table = _remap_table(vocab, merged)
            bindings.append(jnp.asarray(
                _pad_np(table, _vocab_bucket(len(table)), 0)))
        self_slots.append(len(bindings) - 2)
        foreign_slots.append(len(bindings) - 1)
    return self_slots, foreign_slots


@dataclass
class _JoinSetup:
    """Device-resident broadcast-join plan: replicated sorted foreign
    planes + a traceable per-shard augment step."""
    apply: callable          # (columns, mask, bindings, args) -> (cols, mask)
    bindings: tuple          # host-bound remap/constant slots
    args: tuple              # replicated device planes (P() specs)
    rep_columns: dict        # joined-namespace _RepColumns for prepare()
    fingerprint: tuple


def _chunk_memo(cache: dict, key: tuple, chunk, build):
    """id()-keyed per-chunk memo with a weakref liveness guard and
    finalizer eviction (the stats_for_chunk discipline): a recycled
    object id can never serve a DEAD chunk's staged planes, and a dead
    chunk's device buffers do not outlive it in the cache."""
    import weakref
    entry = cache.get(key)
    if entry is not None and entry[0]() is chunk:
        return entry[1]
    value = build()
    cache[key] = (weakref.ref(chunk), value)
    weakref.finalize(chunk, cache.pop, key, None)
    return value


def _foreign_host_order(cache: dict, join: ir.JoinClause, foreign,
                        self_bound, f_bound, foreign_slots, bindings):
    """Host phase shared by the stitched broadcast join and the fused
    whole-plan join: encode + sort the foreign keys once, verify
    uniqueness, memoize per (join shape, foreign chunk identity, vocab
    identities).  Returns (f_order, f_sorted, unique)."""
    from ytsaurus_tpu.query.engine.expr import EmitContext
    from ytsaurus_tpu.query.engine.joins import (
        _emit_encoded_keys, sort_foreign_keys,
    )

    f_ctx = EmitContext(columns={
        name: (foreign.columns[name].data, foreign.columns[name].valid)
        for name in foreign.schema.column_names},
        bindings=tuple(bindings), capacity=foreign.capacity)
    f_keys = _emit_encoded_keys(f_bound, foreign_slots, f_ctx)
    n_foreign = foreign.row_count
    # Deliberately the VALUE-CARRYING fingerprint (not the parameterized
    # one): this cache holds computed key planes, not a program, so
    # equation literals must distinguish.  Remapped codes depend on BOTH
    # sides' vocabularies (the merged space): key on their identities.
    host_key = ("join-host", ir.fingerprint(ir.Query(
        schema=join.foreign_schema, source=join.foreign_table,
        joins=(join,))), id(foreign), foreign.capacity, n_foreign,
        tuple(id(b.vocab) if b.vocab is not None else None
              for b in list(self_bound) + list(f_bound)))

    def build():
        f_order, f_sorted = sort_foreign_keys(f_keys, foreign.row_valid)
        # Unique-key check over adjacent sorted pairs.  Null-keyed rows
        # match nothing, so duplicates among them are fine.
        live = jnp.arange(foreign.capacity) < (n_foreign - 1)
        same = jnp.ones(foreign.capacity, dtype=bool)
        non_null = jnp.ones(foreign.capacity, dtype=bool)
        for v, d in f_sorted:
            same = same & (v == jnp.roll(v, -1)) & \
                (d == jnp.roll(d, -1))
            non_null = non_null & (v > 0)
        unique = not bool(jnp.any(same & live & non_null))
        return f_order, f_sorted, unique

    return _chunk_memo(cache, host_key, foreign, build)


def _stitched_mesh_block(stats, plan: ir.Query, key, n: int, in_rows,
                         out_rows, exchanges, stages=None) -> None:
    """Stitched-rung mesh telemetry (ISSUE 20 parity): assemble the SAME
    block shape the fused program returns — from host values the
    stitched rungs ALREADY read for their quota/capacity decisions, so
    this costs zero additional device→host transfers — and fan it out
    to the same surfaces (whole_plan._publish_mesh).  Blocks carry
    path="stitched", so /mesh and `yt mesh top` show which lowering
    measured what."""
    from ytsaurus_tpu.parallel.whole_plan import (
        _mesh_armed, _mesh_block, _publish_mesh)
    if not _mesh_armed():
        return
    block = _mesh_block(n, in_rows, out_rows, exchanges, stages=stages,
                        path="stitched")
    _publish_mesh(stats, plan_fingerprint(plan), key, block)


class DistributedEvaluator:
    """Compiles and caches SPMD (join ∘ bottom ∘ all_gather ∘ front)
    programs."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._cache: dict = {}
        # Settled exchange quotas per whole-plan shape (parallel/
        # whole_plan.py): the data-dependent decision the stitched path
        # host-syncs for, memoized instead of measured per query.
        self._quota_memo: dict = {}
        # Per-process compile split for the restart acceptance leg: a
        # warm-started daemon serves SPMD plans with fresh_compiles == 0.
        self.fresh_compiles = 0
        self.disk_hits = 0

    def _dispatch_spmd(self, key: tuple, build, args, donate: tuple = ()):
        """Run one SPMD program through the compile-once ladder (ISSUE
        10, extended to the distributed plane): memory cache → AOT disk
        tier (`aot_cache.py` — serialize_executable products of
        `lower().compile()`, so a rolling restart or a mesh resize is a
        cache fill) → fresh compile.  `build()` returns the un-jitted
        program; `args` are the concrete call arguments AOT lowering
        pins shapes from."""
        from ytsaurus_tpu.config import compile_config
        if not compile_config().donate_buffers:
            donate = ()
        fn = self._cache.get(key)
        if fn is None:
            fn = self._compile_spmd(key, build, args, donate)
        try:
            return fn(*args)
        except Exception:
            if hasattr(fn, "lower"):
                raise             # plain jitted fn: a genuine error
            # AOT-compiled executable rejects an aval drift the cache
            # key did not capture: rebuild through the tolerant jit
            # wrapper (a genuine execution error re-raises identically).
            # This IS a fresh compile — count it, or a rotten disk tier
            # could report a perfect warm start while recompiling
            # everything.  (Aval rejection happens before execution, so
            # donated inputs are still alive for the retry.)
            fn = jax.jit(build(), donate_argnums=donate)
            self.fresh_compiles += 1
            self._cache[key] = fn
            return fn(*args)

    def _compile_spmd(self, key: tuple, build, args, donate: tuple = ()):
        import time as _time

        from ytsaurus_tpu.query.engine.aot_cache import get_disk_cache
        disk = get_disk_cache()
        fn = disk.load(key) if disk is not None else None
        if fn is not None:
            self.disk_hits += 1
            self._observe_compiled(key, fn)
        else:
            jitted = jax.jit(build(), donate_argnums=donate)
            t0 = _time.perf_counter()
            lowered = None
            try:
                lowered = jitted.lower(*args)
                fn = lowered.compile()
            except Exception:   # noqa: BLE001 — AOT is an optimization;
                # anything it cannot lower OR compile falls back to the
                # jit wrapper (first call compiles fused); lowered must
                # reset or the store below would serialize the wrapper.
                fn = jitted
                lowered = None
            self.fresh_compiles += 1
            seconds = _time.perf_counter() - t0
            if disk is not None and lowered is not None:
                disk.store(key, fn, str(key[0]), seconds)
            if lowered is not None:
                self._observe_compiled(key, fn, lowered, seconds)
        self._cache[key] = fn
        return fn

    @staticmethod
    def _observe_compiled(key: tuple, fn, lowered=None,
                          seconds: float = 0.0) -> None:
        """Compile-time capture for one SPMD executable (ISSUE 20):
        memory_analysis()/cost_analysis() land in the mesh observatory
        (keyed by the program cache key the dispatch site holds — the
        runtime telemetry block joins them at decode time), and — behind
        `WorkloadConfig.capture_artifacts` — the HLO + FLOPs/bytes land
        in the compile observatory's artifact ring, so fused/stitched
        SPMD programs show up in `yt compile-cache top` instead of
        blanks.  Never observe_hit/observe_miss here: those counters
        must reconcile with the /query/compile_cache pool sensors,
        which only count the local evaluator's dispatches."""
        from ytsaurus_tpu.parallel.mesh_observatory import (
            get_mesh_observatory, memory_analysis_dict)
        from ytsaurus_tpu.query.engine.evaluator import (
            _cost_analysis, get_compile_observatory)
        try:
            cost = _cost_analysis(fn)
            get_mesh_observatory().record_compile(
                key, memory_analysis_dict(fn), cost)
            from ytsaurus_tpu.config import workload_config
            if workload_config().capture_artifacts and lowered is not None:
                get_compile_observatory().capture_artifact(
                    f"spmd/{key[0]}", key, lowered.as_text(), cost,
                    seconds)
        except Exception:   # noqa: BLE001 — observability capture is a
            # debugging aid, never an execution hazard.
            pass

    def run(self, plan: ir.Query, table: ShardedTable,
            foreign_chunks: Optional[dict] = None,
            shuffle: Optional[bool] = None, stats=None) -> ColumnarChunk:
        """Execute a plan SPMD.  `shuffle=True` uses the all_to_all
        repartition path for GROUP BY (ref CoordinateAndExecuteWithShuffle,
        engine_api/coordinator.h:92): rows move to hash(key)-owned devices
        and each device computes its COMPLETE groups — right when group
        cardinality is high (the all_gather merge would replicate heavy
        front work).  Default: gather-merge.

        Joined plans run one of two ways:
        - broadcast join (unique foreign keys, the lookup shape, e.g.
          TPC-H Q3): each foreign table is key-sorted once, replicated to
          every device, and probed per shard with a vectorized
          lexicographic binary search (the batch reshaping of
          MultiJoinOpHelper's foreign lookups, cg_routines/
          registry.cpp:599);
        - partitioned hash join (non-unique keys / fact-to-fact, or
          under shuffle=True): BOTH sides are routed by join-key hash
          over one all_to_all so equal keys co-locate, then each device
          joins locally with match expansion — the shuffle-aware join of
          engine_api/coordinator.h:92-97.
        String keys work on both paths via merged vocabularies."""
        join_setup = None
        if plan.joins:
            # Cost-based execution order (query/planner.py): the same
            # decisions the fused rung makes, so a query degrading off
            # the whole-plan rung runs the SAME join order — and the
            # reordered plan's fingerprint keys every stitched program
            # cache (a stats-driven order flip never reuses stale).
            from ytsaurus_tpu.query import planner
            plan, _jplan = planner.reorder_for_chunks(
                plan, table.total_rows, foreign_chunks or {})
            join_setup = None if shuffle else self._prepare_joins(
                plan, table, foreign_chunks or {})
            if join_setup is None:
                return self._run_partitioned(plan, table,
                                             foreign_chunks or {},
                                             bool(shuffle), stats=stats)
        if plan.window is not None and plan.window.partition_items and \
                shuffle is not False and join_setup is None:
            # Window functions co-partition by the PARTITION BY key over
            # one all_to_all (default path): each device then owns
            # COMPLETE partitions and computes exact windows locally;
            # only order/project/offset/limit merge at the front.
            # shuffle=False forces the gather-merge fallback (the front
            # recomputes the window over the full gathered rowset).
            return self._finish_shuffled(
                plan, {name: (col.data, col.valid)
                       for name, col in table.columns.items()},
                table.row_valid,
                {name: _RepColumn(type=col.type, dictionary=col.dictionary)
                 for name, col in table.columns.items()},
                table.capacity, stats=stats,
                in_rows=list(table.row_counts))
        if shuffle and plan.group is not None and not plan.group.totals:
            return self._run_shuffled(plan, table, stats=stats)
        columns_global = {name: (col.data, col.valid)
                          for name, col in table.columns.items()}
        if join_setup is None:
            rep_columns = {
                name: _RepColumn(type=col.type, dictionary=col.dictionary)
                for name, col in table.columns.items()}
        else:
            rep_columns = join_setup.rep_columns
        return self._finish_gather(plan, columns_global, table.row_valid,
                                   rep_columns, table.capacity,
                                   join_setup=join_setup, stats=stats,
                                   in_rows=list(table.row_counts))

    def _finish_gather(self, plan: ir.Query, columns_global: dict,
                       row_valid, rep_columns: dict, cap: int,
                       join_setup: "Optional[_JoinSetup]" = None,
                       stats=None, in_rows=None) -> ColumnarChunk:
        """Bottom-per-shard + all_gather front merge over bare sharded
        planes — run()'s tail for both the no-join and broadcast-join
        shapes, reusable after a partitioned join has replaced the table
        planes.  With join_setup, the broadcast probe runs as a traced
        step ahead of the bottom query inside the same program."""
        _FP_GATHER.hit()
        n = self.mesh.devices.size
        bottom, front = split_plan(plan)
        rep = _RepChunk(capacity=cap, columns=dict(rep_columns))
        prepared_b = prepare(bottom, rep)
        inter_rep = _RepChunk(
            capacity=n * prepared_b.out_capacity,
            columns={c.name: _RepColumn(type=c.type, dictionary=c.vocab)
                     for c in prepared_b.output})
        prepared_f = prepare(front, inter_rep)
        # Compiled-program caches key on the PARAMETERIZED shape
        # fingerprint (ISSUE 10): the emit paths are literal-value-
        # independent (values ride the bindings tuple, passed as args
        # per dispatch), so one SPMD program serves every constant.
        key = ("finish", plan_fingerprint(bottom), plan_fingerprint(front), n,
               cap, prepared_b.binding_shapes(),
               prepared_f.binding_shapes(),
               join_setup.fingerprint if join_setup else None)
        columns = {c.name: columns_global[c.name]
                   for c in bottom.schema if c.name in columns_global}
        extra = (join_setup.args, tuple(join_setup.bindings)) \
            if join_setup else ()
        out_planes, out_count = self._dispatch_spmd(
            key, lambda: self._build(prepared_b, prepared_f, cap,
                                     join_setup),
            (columns, row_valid, tuple(prepared_b.bindings),
             tuple(prepared_f.bindings), *extra))
        _note_host_sync()
        if in_rows is not None:
            # The gather rung's only host-known per-shard cardinality is
            # the input spread (the front count is a merged global): its
            # skew IS the per-shard work on this rung, so it doubles as
            # the output spread in the parity block.
            _stitched_mesh_block(stats, plan, key, n, in_rows, in_rows,
                                 [])
        return _assemble_chunk(prepared_f.output, out_planes, out_count)

    def _run_partitioned(self, plan: ir.Query, table: ShardedTable,
                         foreign_chunks: dict, shuffle: bool, stats=None
                         ) -> ColumnarChunk:
        """Partitioned hash join: route BOTH sides of each join by
        join-key hash over one all_to_all so equal keys co-locate, then
        join locally per device with match expansion — the general
        fact-to-fact shape (non-unique foreign keys), composing with the
        shuffled GROUP BY.  Ref: shuffle-aware join coordination,
        engine_api/coordinator.h:92-97 + executor.cpp join routing.

        Static-shape discipline (per join): a count pass sizes the
        exchange quotas; a route+probe program moves rows and computes
        per-self-row match ranges (outputs stay device-resident); the
        host reads only the per-device totals to pick the expansion
        capacity; an expand program materializes the joined planes."""
        from dataclasses import replace as dc_replace

        from ytsaurus_tpu.chunks.columnar import pad_capacity
        from ytsaurus_tpu.parallel.shuffle import route_rows, transfer_counts
        from ytsaurus_tpu.query.engine.expr import (
            BindContext, ColumnBinding, EmitContext, ExprBinder,
            _combine_u64, _mix_u64,
        )
        from ytsaurus_tpu.query.engine.joins import (
            _bind_keys, _emit_encoded_keys, _lex_searchsorted,
            null_key_mask, sort_foreign_keys,
        )

        mesh = self.mesh
        n = table.n_shards
        shard_sharding = NamedSharding(mesh, P(SHARD_AXIS))

        cur_cap = table.capacity
        columns_global = {name: (col.data, col.valid)
                          for name, col in table.columns.items()}
        # Only planes the plan actually reads ride the exchange — a wide
        # table joined on one key must not pay all_to_all bandwidth for
        # dead columns.
        needed = ir.referenced_columns(plan)
        if needed is not None:
            columns_global = {name: planes
                              for name, planes in columns_global.items()
                              if name in needed}
        row_valid = table.row_valid
        namespace = {name: ColumnBinding(type=col.type, vocab=col.dictionary)
                     for name, col in table.columns.items()}
        rep_columns = {
            name: _RepColumn(type=col.type, dictionary=col.dictionary)
            for name, col in table.columns.items()}
        # Mesh parity telemetry (ISSUE 20): the quota/capacity host
        # reads this path already pays carry enough to assemble the
        # fused block's shape — exchange demand vs granted per side,
        # per-shard joined-output totals.  Transfer MATRICES stay on
        # device here (only their maxes cross), so entries carry
        # matrix=None.
        mesh_exchanges: list = []
        mesh_stages: list = []
        mesh_out_rows = list(table.row_counts)

        for join_index, join in enumerate(plan.joins):
            foreign = foreign_chunks.get(join.foreign_table)
            if foreign is None:
                raise YtError(
                    f"No data provided for join table "
                    f"{join.foreign_table!r}",
                    code=EErrorCode.QueryExecutionError)
            bindings: list = []
            bind_structure: list = []
            bind_ctx = BindContext(columns=dict(namespace),
                                   bindings=bindings,
                                   structure=bind_structure)
            binder = ExprBinder(bind_ctx)
            self_bound = [binder.bind(e) for e in join.self_equations]
            f_bound = _bind_keys(foreign, join.foreign_schema,
                                 join.foreign_equations, bindings,
                                 structure=bind_structure)
            self_slots, foreign_slots = _vocab_remap_slots(
                self_bound, f_bound, bindings)
            bnd = tuple(bindings)
            is_left = join.is_left
            s_cap = cur_cap

            flat_names = [
                (f"{join.alias}.{f}" if join.alias else f, f)
                for f in join.foreign_columns]
            if needed is not None:
                flat_names = [(flat, f) for flat, f in flat_names
                              if flat in needed]
            # Shard the foreign table across the mesh (1/n per device);
            # route only the planes the join reads (key-expression
            # sources + pulled columns that survive pruning).
            f_count = foreign.row_count
            f_slice = pad_capacity(max((f_count + n - 1) // n, 1))
            f_total = n * f_slice
            f_key_refs: set = set()
            for eq in join.foreign_equations:
                f_key_refs.update(ir.expr_references(eq))
            f_names = sorted(f_key_refs | {f for _, f in flat_names})
            f_global = {}
            for fname in f_names:
                fcol = foreign.columns[fname]
                pad = f_total - f_count
                data = jnp.concatenate(
                    [fcol.data[:f_count],
                     jnp.zeros(pad, dtype=fcol.data.dtype)])
                valid = jnp.concatenate(
                    [fcol.valid[:f_count], jnp.zeros(pad, dtype=bool)])
                f_global[fname] = (jax.device_put(data, shard_sharding),
                                   jax.device_put(valid, shard_sharding))
            f_row_valid = jax.device_put(
                jnp.arange(f_total) < f_count, shard_sharding)

            def make_pid(keys, mask, keep_null_local: bool):
                """Destination device by key hash; null-keyed live rows
                stay local for LEFT joins (they must still emit an
                unmatched output row) and are discarded otherwise."""
                acc = jnp.full(mask.shape, np.uint64(0x9E3779B97F4A7C15),
                               dtype=jnp.uint64)
                for v, d in keys:
                    h = _mix_u64(_canonical_hash_plane(d))
                    h = jnp.where(v > 0, h, jnp.zeros_like(h))
                    acc = _combine_u64(acc, h)
                pid = (acc % np.uint64(n)).astype(jnp.int32)
                null = null_key_mask(keys)
                if keep_null_local:
                    me = jax.lax.axis_index(SHARD_AXIS).astype(jnp.int32)
                    pid = jnp.where(null, me, pid)
                else:
                    pid = jnp.where(null, n, pid)
                return jnp.where(mask, pid, n)

            def emit_self(cols, capacity, bnd_t):
                ctx = EmitContext(columns=cols, bindings=bnd_t,
                                  capacity=capacity)
                return _emit_encoded_keys(self_bound, self_slots, ctx)

            def emit_foreign(cols, capacity, bnd_t):
                ctx = EmitContext(columns=cols, bindings=bnd_t,
                                  capacity=capacity)
                return _emit_encoded_keys(f_bound, foreign_slots, ctx)

            def count_pass(cols, mask, fcols, fmask, bnd_t):
                pid_s = make_pid(emit_self(cols, s_cap, bnd_t), mask,
                                 is_left)
                pid_f = make_pid(emit_foreign(fcols, f_slice, bnd_t),
                                 fmask, False)
                return (transfer_counts(pid_s, pid_s < n, n),
                        transfer_counts(pid_f, pid_f < n, n))

            key_base = ("pjoin", plan_fingerprint(plan), join_index, n,
                        s_cap, f_slice, f_count > 0,
                        # Bind-phase structure notebook: baked host
                        # constants (concat widths) binding shapes
                        # alone cannot distinguish (ISSUE 10).
                        tuple(bind_structure),
                        tuple((tuple(b.shape), str(b.dtype))
                              for b in bindings))
            counts_s, counts_f = self._dispatch_spmd(
                key_base + ("count",),
                lambda: shard_map(
                    count_pass, mesh=mesh,
                    in_specs=(P(SHARD_AXIS),) * 4 + (P(),),
                    out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                    check_vma=False),
                (columns_global, row_valid, f_global, f_row_valid, bnd))
            _note_host_sync()
            # One stacked device→host transfer for both quotas (the
            # `yt analyze` jax pass flagged the original pair of
            # np.asarray reads — the self and foreign counts each
            # blocked the dispatch queue separately).
            # analyze: allow(host-sync): routing quotas are a host decision; one stacked transfer
            quotas = np.asarray(jnp.stack([counts_s.max(),
                                           counts_f.max()]))
            # analyze: allow(host-sync): quotas is host numpy (the one stacked transfer above)
            demand_s, demand_f = (int(q) for q in quotas)
            quota_s = pad_capacity(max(demand_s, 1))
            quota_f = pad_capacity(max(demand_f, 1))
            S, F = n * quota_s, n * quota_f
            from ytsaurus_tpu.parallel.whole_plan import (
                _mesh_exchange_entry, _row_bytes)
            mesh_exchanges.append(_mesh_exchange_entry(
                f"join[{join_index}]/self", None, demand_s,
                quota_s, _row_bytes({name: rep_columns[name]
                                     for name in columns_global
                                     if name in rep_columns})))
            mesh_exchanges.append(_mesh_exchange_entry(
                f"join[{join_index}]/foreign", None, demand_f,
                quota_f, _row_bytes({
                    f: _RepColumn(type=foreign.columns[f].type,
                                  dictionary=foreign.columns[f].dictionary)
                    for f in f_names})))

            def route_probe(cols, mask, fcols, fmask, bnd_t):
                pid_s = make_pid(emit_self(cols, s_cap, bnd_t), mask,
                                 is_left)
                recv_s, mask_s = route_rows(cols, pid_s, n, quota_s, s_cap)
                pid_f = make_pid(emit_foreign(fcols, f_slice, bnd_t),
                                 fmask, False)
                recv_f, mask_f = route_rows(fcols, pid_f, n, quota_f,
                                            f_slice)
                s_keys = emit_self(recv_s, S, bnd_t)
                f_keys = emit_foreign(recv_f, F, bnd_t)
                f_order, f_sorted = sort_foreign_keys(f_keys, mask_f)
                n_f = mask_f.sum()
                lo = _lex_searchsorted(f_sorted, n_f, F, s_keys, "left")
                hi = _lex_searchsorted(f_sorted, n_f, F, s_keys, "right")
                s_null = null_key_mask(s_keys)
                counts = jnp.where(mask_s & ~s_null, hi - lo, 0)
                per_row = jnp.where(mask_s, jnp.maximum(counts, 1), 0) \
                    if is_left else counts
                return (recv_s, mask_s, recv_f, f_order, lo, counts,
                        per_row.sum()[None])

            (recv_s, mask_s, recv_f, f_order, lo, counts,
             totals) = self._dispatch_spmd(
                key_base + ("probe", quota_s, quota_f),
                lambda: shard_map(
                    route_probe, mesh=mesh,
                    in_specs=(P(SHARD_AXIS),) * 4 + (P(),),
                    out_specs=(P(SHARD_AXIS),) * 7, check_vma=False),
                (columns_global, row_valid, f_global, f_row_valid, bnd))
            _note_host_sync()
            # analyze: allow(host-sync): join output capacity is a host decision — one totals transfer
            totals_np = np.asarray(totals)
            out_cap = pad_capacity(max(int(totals_np.max()), 1))
            mesh_out_rows = [int(t) for t in totals_np.reshape(-1)]
            mesh_stages.append({
                "stage": join_index, "table": join.foreign_table,
                "strategy": "partition", "est_rows": 0,
                "actual_rows": int(totals_np.sum()), "drift": 0.0})
            self_names = sorted(columns_global)

            def expand(recv_s, mask_s, recv_f, f_order, lo, counts):
                per_row = jnp.where(mask_s, jnp.maximum(counts, 1), 0) \
                    if is_left else counts
                offsets = jnp.cumsum(per_row)
                total = offsets[-1]
                starts = jnp.concatenate(
                    [jnp.zeros(1, dtype=offsets.dtype), offsets[:-1]])
                out_idx = jnp.arange(out_cap)
                self_row = jnp.clip(
                    jnp.searchsorted(offsets, out_idx, side="right"),
                    0, S - 1)
                within = out_idx - starts[self_row]
                matched = counts[self_row] > 0
                f_pos = jnp.clip(lo[self_row] + within, 0, F - 1)
                f_row = f_order[f_pos]
                live = out_idx < total
                out = {}
                for name in self_names:
                    d, v = recv_s[name]
                    out[name] = (d[self_row], v[self_row] & live)
                for flat, fname in flat_names:
                    d, v = recv_f[fname]
                    out[flat] = (d[f_row], v[f_row] & live & matched)
                return out, live

            # Every input of `expand` is a route_probe output this
            # loop iteration owns, consumed exactly once here — donate
            # all six so the routed planes' buffers are reused for the
            # expanded output (ISSUE 19; inert on CPU).
            columns_global, row_valid = self._dispatch_spmd(
                key_base + ("expand", quota_s, quota_f, out_cap),
                lambda: shard_map(
                    expand, mesh=mesh,
                    in_specs=(P(SHARD_AXIS),) * 6,
                    out_specs=P(SHARD_AXIS), check_vma=False),
                (recv_s, mask_s, recv_f, f_order, lo, counts),
                donate=(0, 1, 2, 3, 4, 5))
            cur_cap = out_cap
            for flat, fname in flat_names:
                fcol = foreign.columns[fname]
                namespace[flat] = ColumnBinding(type=fcol.type,
                                                vocab=fcol.dictionary)
                rep_columns[flat] = _RepColumn(type=fcol.type,
                                               dictionary=fcol.dictionary)

        _stitched_mesh_block(stats, plan, None, n,
                             list(table.row_counts), mesh_out_rows,
                             mesh_exchanges, stages=mesh_stages)

        plan_nojoin = dc_replace(plan, joins=())
        if needed is not None:
            # The finish stages bind every schema column; drop the ones
            # pruned out of the exchange so the namespaces agree.
            plan_nojoin = dc_replace(plan_nojoin, schema=TableSchema(
                columns=tuple(c for c in plan.schema
                              if c.name in needed)))
        if plan_nojoin.window is not None and \
                plan_nojoin.window.partition_items and shuffle:
            return self._finish_shuffled(
                plan_nojoin, columns_global, row_valid, rep_columns,
                cur_cap, stats=stats, in_rows=mesh_out_rows)
        if shuffle and plan.group is not None and not plan.group.totals:
            return self._finish_shuffled(plan_nojoin, columns_global,
                                         row_valid, rep_columns, cur_cap,
                                         stats=stats,
                                         in_rows=mesh_out_rows)
        return self._finish_gather(plan_nojoin, columns_global, row_valid,
                                   rep_columns, cur_cap, stats=stats,
                                   in_rows=mesh_out_rows)

    def _run_shuffled(self, plan: ir.Query, table: ShardedTable,
                      stats=None) -> ColumnarChunk:
        columns_global = {name: (col.data, col.valid)
                          for name, col in table.columns.items()}
        rep_columns = {
            name: _RepColumn(type=col.type, dictionary=col.dictionary)
            for name, col in table.columns.items()}
        return self._finish_shuffled(plan, columns_global, table.row_valid,
                                     rep_columns, table.capacity,
                                     stats=stats,
                                     in_rows=list(table.row_counts))

    def _finish_shuffled(self, plan: ir.Query, columns_global: dict,
                         row_valid, rep_columns: dict, cap: int,
                         stats=None, in_rows=None) -> ColumnarChunk:
        """Key-hash all_to_all finish, shared by two stage shapes:

        - GROUP BY (route by group key): every device owns complete
          groups, so group+having run fully local;
        - window stage (route by PARTITION BY key): every device owns
          complete partitions, so the segmented-scan window stage is
          exact per device.

        Only order/project/offset/limit merge at the front.  Operates on
        bare sharded planes so it also finishes partitioned-join
        outputs."""
        _FP_ALL_TO_ALL.hit()
        from dataclasses import replace as dc_replace

        import numpy as np

        from ytsaurus_tpu.parallel.shuffle import route_rows, transfer_counts
        from ytsaurus_tpu.chunks.columnar import pad_capacity
        from ytsaurus_tpu.query.engine.expr import (
            BindContext, ColumnBinding, EmitContext, ExprBinder, _mix_u64,
            _combine_u64,
        )

        mesh = self.mesh
        n = mesh.devices.size

        # Bind where + routing-key expressions (PARTITION BY keys for a
        # window stage, group keys otherwise) against the (shared) vocab.
        key_items = plan.window.partition_items if plan.window is not None \
            else plan.group.group_items

        def bind_keys():
            bind_ctx = BindContext(columns={
                name: ColumnBinding(type=rc.type, vocab=rc.dictionary)
                for name, rc in rep_columns.items()})
            binder = ExprBinder(bind_ctx)
            where_b = binder.bind(plan.where) if plan.where is not None else None
            key_b = [binder.bind(item.expr) for item in key_items]
            return bind_ctx, where_b, key_b

        bind_ctx, where_b, key_b = bind_keys()
        bindings = tuple(bind_ctx.bindings)
        names = [c.name for c in plan.schema if c.name in columns_global]
        columns_global = {name: columns_global[name] for name in names}

        def dest_ids(columns, row_valid, bnd):
            ctx = EmitContext(columns=columns, bindings=bnd, capacity=cap)
            mask = row_valid
            if where_b is not None:
                d, v = where_b.emit(ctx)
                mask = mask & v & d.astype(bool)
            acc = jnp.full(cap, np.uint64(0x9E3779B97F4A7C15), dtype=jnp.uint64)
            for kb in key_b:
                data, valid = kb.emit(ctx)
                if data.dtype == jnp.bool_:
                    data = data.astype(jnp.int8)
                h = _mix_u64(_canonical_hash_plane(data))
                h = jnp.where(valid, h, jnp.zeros_like(h))
                acc = _combine_u64(acc, h)
            pid = (acc % np.uint64(n)).astype(jnp.int32)
            return jnp.where(mask, pid, n), mask

        # Pass 1: transfer matrix → exact quota.  Cached + AOT-tiered
        # like every SPMD program (a fresh closure per query used to
        # defeat jax.jit's identity cache — the count pass silently
        # recompiled on every shuffled query).
        def count_pass(columns, row_valid, bnd):
            pid, mask = dest_ids(columns, row_valid, bnd)
            return transfer_counts(pid, mask, n)

        count_key = ("shuffled-count", plan_fingerprint(plan), n, cap,
                     tuple(bind_ctx.structure),
                     tuple((tuple(b.shape), str(b.dtype))
                           for b in bindings))
        counts = self._dispatch_spmd(
            count_key,
            lambda: shard_map(
                count_pass, mesh=mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
                out_specs=P(SHARD_AXIS), check_vma=False),
            (columns_global, row_valid, bindings))
        _note_host_sync()
        # analyze: allow(host-sync): all_to_all quota is a host decision — one transfer-matrix read
        counts_np = np.asarray(counts)
        quota = pad_capacity(max(int(counts_np.max()), 1))
        recv_cap = quota * n

        # Local plan: complete groups (group + having) or complete
        # partitions (where + window, identity projection carrying the
        # slots) per device; then the front (order/project/offset/limit)
        # runs ON THE MESH over the all_gathered rows — no host
        # round-trip (the round-1 host-merge contradiction of this
        # module's framing).
        local_plan = dc_replace(plan, order=None, project=None, offset=0,
                                limit=None)
        local_rep = _RepChunk(
            capacity=recv_cap,
            columns={name: _RepColumn(type=rc.type,
                                      dictionary=rc.dictionary)
                     for name, rc in rep_columns.items()})
        prepared_local = prepare(local_plan, local_rep)
        front = ir.FrontQuery(
            schema=local_plan.output_schema(), order=plan.order,
            project=plan.project, offset=plan.offset, limit=plan.limit)
        out_cap = prepared_local.out_capacity
        front_rep = _RepChunk(
            capacity=n * out_cap,
            columns={c.name: _RepColumn(type=c.type, dictionary=c.vocab)
                     for c in prepared_local.output})
        prepared_front = prepare(front, front_rep)

        def exchange_group_front(columns, row_valid, bnd, local_bnd,
                                 front_bnd):
            pid, mask = dest_ids(columns, row_valid, bnd)
            recv, recv_mask = route_rows(columns, pid, n, quota, cap)
            planes, count = prepared_local.run(recv, recv_mask, local_bnd)
            shard_mask = jnp.arange(out_cap) < count
            gathered = {}
            for out_col, (d, v) in zip(prepared_local.output, planes):
                gathered[out_col.name] = (
                    jax.lax.all_gather(d, SHARD_AXIS)
                    .reshape((-1,) + d.shape[1:]),
                    jax.lax.all_gather(v, SHARD_AXIS).reshape(-1))
            g_mask = jax.lax.all_gather(shard_mask, SHARD_AXIS).reshape(-1)
            return prepared_front.run(gathered, g_mask, front_bnd)

        key = ("shuffled", plan_fingerprint(plan), n, cap, quota,
               # dest_ids' where/key binds can bake host constants
               # (concat widths) — fold their structure notebook in.
               tuple(bind_ctx.structure),
               prepared_local.binding_shapes(),
               prepared_front.binding_shapes())
        out_planes, out_count = self._dispatch_spmd(
            key,
            lambda: shard_map(
                exchange_group_front, mesh=mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P(), P()),
                out_specs=P(), check_vma=False),
            (columns_global, row_valid, bindings,
             tuple(prepared_local.bindings),
             tuple(prepared_front.bindings)))
        _note_host_sync()
        # Mesh parity telemetry (ISSUE 20): the quota decision above
        # already transferred the FULL n x n transfer matrix to the
        # host, so this rung reports the same exchange detail as the
        # fused block — per-shard received rows (column sums) give the
        # post-exchange skew — at zero additional transfers.
        from ytsaurus_tpu.parallel.whole_plan import (
            _mesh_exchange_entry, _row_bytes)
        entry = _mesh_exchange_entry(
            "shuffle/stitched", counts_np.reshape(-1),
            int(counts_np.max()), quota, _row_bytes(rep_columns))
        recv_rows = [int(r) for r in counts_np.sum(axis=0)]
        _stitched_mesh_block(
            stats, plan, key, n,
            in_rows if in_rows is not None else
            [int(r) for r in counts_np.sum(axis=1)],
            recv_rows, [entry])
        return _assemble_chunk(prepared_front.output, out_planes,
                               out_count)

    def _prepare_joins(self, plan: ir.Query, table: ShardedTable,
                       foreign_chunks: dict) -> "Optional[_JoinSetup]":
        """Bind every join as a replicated lookup: sort the foreign side
        once on the host device, verify key uniqueness, and return a
        traceable per-shard probe step.  String keys ride merged
        vocabularies (self codes remapped at probe time via a binding
        table, foreign codes remapped host-side before the sort).
        Returns None when any join's foreign keys are NOT unique — the
        caller falls back to the partitioned-exchange path."""
        from ytsaurus_tpu.query.engine.expr import (
            BindContext, ColumnBinding, EmitContext, ExprBinder,
        )
        from ytsaurus_tpu.query.engine.joins import (
            _bind_keys, _emit_encoded_keys, probe_replicated,
        )

        cap = table.capacity
        bindings: list = []
        namespace: dict[str, ColumnBinding] = {
            name: ColumnBinding(type=col.type, vocab=col.dictionary)
            for name, col in table.columns.items()}
        rep_columns: dict = {
            name: _RepColumn(type=col.type, dictionary=col.dictionary)
            for name, col in table.columns.items()}
        steps = []          # (self_bound, n_keys, is_left, flat_names, arg_slice)
        args: list = []
        fingerprint_parts = []

        for join in plan.joins:
            foreign = foreign_chunks.get(join.foreign_table)
            if foreign is None:
                raise YtError(
                    f"No data provided for join table "
                    f"{join.foreign_table!r}",
                    code=EErrorCode.QueryExecutionError)
            # Bind self keys against the namespace accumulated so far.
            bind_structure: list = []
            bind_ctx = BindContext(columns=dict(namespace),
                                   bindings=bindings,
                                   structure=bind_structure)
            binder = ExprBinder(bind_ctx)
            self_bound = [binder.bind(e) for e in join.self_equations]
            f_bound = _bind_keys(foreign, join.foreign_schema,
                                 join.foreign_equations, bindings,
                                 structure=bind_structure)
            self_slots, foreign_slots = _vocab_remap_slots(
                self_bound, f_bound, bindings)
            # Host phase cached per (join shape, foreign chunk identity):
            # repeated queries against an unchanged dimension table must
            # not re-sort it or pay the uniqueness-check device sync.
            f_order, f_sorted, unique = _foreign_host_order(
                self._cache, join, foreign, self_bound, f_bound,
                foreign_slots, bindings)
            n_foreign = foreign.row_count
            if not unique:
                return None     # fact-to-fact: partitioned exchange path
            # Replicated args: sorted key planes + gathered foreign columns.
            arg_start = len(args)
            for v, d in f_sorted:
                args.append(v)
                args.append(d)
            flat_names = []
            for fname in join.foreign_columns:
                fcol = foreign.columns[fname]
                flat = f"{join.alias}.{fname}" if join.alias else fname
                flat_names.append(flat)
                args.append(fcol.data[f_order])
                args.append(fcol.valid[f_order])
                namespace[flat] = ColumnBinding(type=fcol.type,
                                                vocab=fcol.dictionary)
                rep_columns[flat] = _RepColumn(type=fcol.type,
                                               dictionary=fcol.dictionary)
            args.append(jnp.asarray(n_foreign, dtype=jnp.int64))
            steps.append((self_bound, self_slots, len(f_bound),
                          join.is_left, flat_names, (arg_start, len(args)),
                          foreign.capacity))
            fingerprint_parts.append(
                (plan_fingerprint(ir.Query(schema=join.foreign_schema,
                                           source=join.foreign_table,
                                           joins=(join,))),
                 foreign.capacity, n_foreign > 0,
                 # Exact vocab lens + the bind-phase structure notebook
                 # (baked concat widths etc., ISSUE 10).
                 tuple(bind_structure),
                 tuple(len(b.vocab) if b.vocab is not None else -1
                       for b in list(self_bound) + list(f_bound))))

        join_bindings = tuple(bindings)

        def apply(columns, mask, bnd, join_args):
            for (self_bound, self_slots, n_keys, is_left, flat_names,
                 (a0, a1), f_cap) in steps:
                ctx = EmitContext(columns=columns, bindings=bnd,
                                  capacity=cap)
                self_keys = _emit_encoded_keys(
                    self_bound, self_slots, ctx)
                pulled, mask = probe_replicated(
                    join_args[a0:a1], n_keys, f_cap, self_keys, mask,
                    is_left)
                columns = dict(columns)
                for flat, plane in zip(flat_names, pulled):
                    columns[flat] = plane
            return columns, mask

        return _JoinSetup(apply=apply, bindings=join_bindings,
                          args=tuple(args), rep_columns=rep_columns,
                          fingerprint=tuple(fingerprint_parts))

    def _build(self, prepared_b, prepared_f, cap: int, join_setup=None):
        mesh = self.mesh
        join_apply = join_setup.apply if join_setup is not None else None

        def spmd(columns, row_valid, b_bindings, f_bindings,
                 join_args=(), join_bindings=()):
            if join_apply is not None:
                columns, row_valid = join_apply(columns, row_valid,
                                                join_bindings, join_args)
            planes, count = prepared_b.run(columns, row_valid, b_bindings)
            shard_mask = jnp.arange(prepared_b.out_capacity) < count
            gathered = {}
            for out_col, (d, v) in zip(prepared_b.output, planes):
                gd = jax.lax.all_gather(d, SHARD_AXIS) \
                    .reshape((-1,) + d.shape[1:])
                gv = jax.lax.all_gather(v, SHARD_AXIS).reshape(-1)
                gathered[out_col.name] = (gd, gv)
            g_mask = jax.lax.all_gather(shard_mask, SHARD_AXIS).reshape(-1)
            return prepared_f.run(gathered, g_mask, f_bindings)

        # check_vma=False: outputs ARE replicated (every device computes the
        # same front merge over the all_gathered states), but the checker
        # can't infer that through the gather+sort pipeline.
        n_extra = 2 if join_apply is not None else 0
        return shard_map(
            spmd, mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P())
            + (P(),) * n_extra,
            out_specs=P(), check_vma=False)


def coordinate_distributed(plan: ir.Query, mesh: Mesh,
                           chunks: Sequence[ColumnarChunk],
                           foreign_chunks: Optional[dict] = None,
                           evaluator: Optional[DistributedEvaluator] = None,
                           host_evaluator=None,
                           prefer_shuffle: bool = True,
                           stats=None) -> ColumnarChunk:
    """Distributed execution with a graceful-degradation ladder (ISSUE 2,
    extended by ISSUE 12's whole-plan rung):

        whole-plan fused SPMD  →  all_to_all co-partition  →
        gather-merge SPMD  →  host coordinator

    Each rung trades throughput for fewer moving parts: the whole-plan
    rung fuses every stage (and its exchange) into ONE program with one
    final host sync (parallel/whole_plan.py — gated per plan by
    `can_fuse` and `CompileConfig.whole_plan`), the stitched shuffle
    path needs every device link healthy, gather-merge only the
    all_gather collective, and the host coordinator nothing but
    per-shard programs (which carry their own per-shard retry —
    query/coordinator.py).  A YtError on one rung degrades to the next
    instead of failing the query; the final error (if every rung fails)
    aggregates the rungs' errors.  Ref: the coordinator falling back
    from CoordinateAndExecuteWithShuffle to plain CoordinateAndExecute
    when a tablet cell cannot serve the shuffle
    (engine_api/coordinator.h:92).
    """
    import logging as _logging

    from ytsaurus_tpu.query.coordinator import coordinate_and_execute
    from ytsaurus_tpu.utils.logging import log_event
    from ytsaurus_tpu.utils.tracing import child_span

    errors: "list[YtError]" = []
    de = evaluator if evaluator is not None else DistributedEvaluator(mesh)
    table = None
    if len(chunks) == mesh.devices.size and \
            all(not callable(c) for c in chunks):
        try:
            table = ShardedTable.from_chunks(mesh, list(chunks))
        except YtError:
            table = None        # ragged shards: host path handles them
    if table is not None:
        from ytsaurus_tpu.config import compile_config
        from ytsaurus_tpu.parallel.whole_plan import can_fuse, \
            run_whole_plan
        if compile_config().whole_plan and can_fuse(plan) is None:
            try:
                # One span per degradation rung, tagged with its rung
                # index — a query served off-rung shows WHERE it fell.
                with child_span("distributed.whole_plan", rung=0,
                                shards=len(chunks)):
                    return run_whole_plan(de, plan, table, stats=stats,
                                          foreign_chunks=foreign_chunks)
            except Exception as err:   # noqa: BLE001 — the fused rung
                # degrades on ANY fault (whole_plan.py's contract): a
                # plan shape whose fused lowering trips an XLA/dtype
                # error must still be served by the stitched rungs, not
                # fail a query that worked before this rung existed.
                if not isinstance(err, YtError):
                    err = YtError(f"whole-plan lowering failed: {err!r}",
                                  code=EErrorCode.QueryExecutionError)
                errors.append(err)
                log_event(_ladder_log, _logging.WARNING,
                          "degrade_to_stitched", error=str(err))
        shuffled_shape = (plan.group is not None and not plan.group.totals) \
            or (plan.window is not None and plan.window.partition_items)
        if prefer_shuffle and shuffled_shape and not plan.joins:
            try:
                with child_span("distributed.shuffle", rung=1,
                                shards=len(chunks)):
                    return de.run(plan, table, foreign_chunks,
                                  shuffle=True, stats=stats)
            except YtError as err:
                errors.append(err)
                log_event(_ladder_log, _logging.WARNING,
                          "degrade_to_gather", error=str(err))
        try:
            with child_span("distributed.gather_merge", rung=2,
                            shards=len(chunks)):
                return de.run(plan, table, foreign_chunks, shuffle=False,
                              stats=stats)
        except YtError as err:
            errors.append(err)
            log_event(_ladder_log, _logging.WARNING,
                      "degrade_to_host", error=str(err))
    try:
        with child_span("distributed.host_coordinate", rung=3,
                        shards=len(chunks)):
            return coordinate_and_execute(plan, list(chunks),
                                          foreign_chunks,
                                          evaluator=host_evaluator,
                                          stats=stats)
    except YtError as err:
        if not errors:
            raise
        raise YtError(
            "distributed query failed on every rung of the degradation "
            "ladder", code=EErrorCode.QueryExecutionError,
            inner_errors=[*errors, err]) from err
