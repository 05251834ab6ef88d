"""Whole-plan SPMD execution: the entire distributed query as ONE program.

The stitched rungs of `coordinate_distributed` re-enter Python between
phases — `_finish_shuffled` runs a count program, blocks on a host read
to size the exchange quota, then runs the exchange program; the host
coordinator stitches N per-shard programs with Python glue.  Flare
(arxiv 1703.08219) and the JIT-in-databases survey (arxiv 2311.04692)
both locate the payoff of native compilation in the WHOLE-QUERY unit:
collapsing the interpretive glue between stages, not the operators.
This module is that collapse for the mesh: scan→filter→[partial
aggregate]→shuffle→aggregate/window→order/topk/project lowers as ONE
`jit(shard_map(...))` program over the `'shard'` axis, with
`with_sharding_constraint` pinning the inputs to the partition-rule
registry's placement and in-program collectives (all_to_all routing,
all_gather merge) replacing the Python-stitched exchanges.

Stage placement is driven by a partition-rule registry (the
`match_partition_rules` idiom of SNIPPETS.md [2]: stage-name regex →
PartitionSpec): `scan/<column>`, `filter`, `bottom/*`, `shuffle/*` and
`local/*` stages map onto `P('shard')`; `front`, `order`, `topk`,
`project`, `limit` are replicated (they run over the all_gathered
rowset on every device).  The registry digest folds into the program
cache key, so a placement change can never serve a stale executable.

The data-dependent decision the stitched path syncs for — the exchange
quota — moves from a per-query host read to a CACHED decision: the
fused program runs with a static pow2 quota, computes the true
transfer-matrix maximum on device, and returns it (with an overflow
flag) stacked WITH the result count — one final device→host transfer,
the only host sync in the whole plan.  On overflow the query re-runs
at the demanded quota (a fresh pow2 rung of the same compile-once
ladder) and the settled quota is memoized per plan shape, so steady
serving never syncs mid-plan and never overflows.  Unfusable plans
(joins, WITH TOTALS) and any in-program fault degrade to the stitched
ladder in `coordinate_distributed`.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import replace as dc_replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ytsaurus_tpu.chunks.columnar import pad_capacity
from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.parallel.mesh import SHARD_AXIS
from ytsaurus_tpu.parallel.shuffle import route_rows, transfer_counts
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.coordinator import split_plan
from ytsaurus_tpu.query.engine.lowering import prepare
from ytsaurus_tpu.query.parameterize import plan_fingerprint

# -- partition-rule registry ---------------------------------------------------

# Stage-name regex → PartitionSpec (the match_partition_rules idiom,
# SNIPPETS.md [2]).  Sharded stages run inside the shard_map body on the
# per-device slice; replicated stages run after the in-program
# all_gather (every device computes the same merge).  Rules are matched
# first-hit, so a custom registry can pin one stage or column family
# ("scan/l_.*") ahead of the defaults.
DEFAULT_PARTITION_RULES: "tuple[tuple[str, P], ...]" = (
    (r"^(scan|filter|bottom|shuffle|local|join)(/|$)", P(SHARD_AXIS)),
    (r"^(front|merge|order|topk|project|limit)(/|$)", P()),
)


def match_partition_rules(rules, name: str) -> P:
    """First rule whose regex matches `name` wins; no match is an error
    (an unplaceable stage must fail loudly, not silently replicate)."""
    for pattern, spec in rules:
        if re.search(pattern, name) is not None:
            return spec
    raise YtError(f"No partition rule matches stage {name!r}",
                  code=EErrorCode.QueryExecutionError)


def rules_fingerprint(rules) -> str:
    """Stable digest of a rule set — a cache-key axis, so editing the
    registry can never serve a program compiled under the old placement."""
    text = repr([(pattern, tuple(spec)) for pattern, spec in rules])
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _validate_stages(rules, stages: "list[tuple[str, bool]]") -> None:
    """Check the registry places every stage where the fused program can
    execute it: (name, wants_sharded) pairs."""
    for name, want_sharded in stages:
        spec = match_partition_rules(rules, name)
        sharded = tuple(spec) == (SHARD_AXIS,)
        if sharded != want_sharded:
            where = "on the shard axis" if want_sharded else "replicated"
            raise YtError(
                f"partition rules place stage {name!r} as {tuple(spec)!r} "
                f"but the fused program runs it {where}",
                code=EErrorCode.QueryExecutionError)


# -- fusion gate ---------------------------------------------------------------


def can_fuse(plan: ir.Query) -> Optional[str]:
    """None when the whole plan lowers as one SPMD program; otherwise
    the reason it stays on the stitched ladder.  Multiway equi-join
    plans fuse since ISSUE 14 (planner-ordered broadcast/partition
    joins ride inside the one program — `_run_join`); WITH TOTALS stays
    stitched (it concatenates two materialized rowsets)."""
    if plan.group is not None and plan.group.totals:
        return "WITH TOTALS concatenates two materialized rowsets"
    return None


def _shape_of(plan: ir.Query) -> str:
    """Which fused shape serves this plan:

    exchange-states  GROUP BY without cardinality: partial aggregate
                     states per shard, then the states (not the rows)
                     ride the all_to_all — the in-program combiner.
    exchange-rows    cardinality GROUP BY / windowed plans: complete
                     groups (partitions) need the raw rows co-located.
    gather           everything else: bottom per shard, all_gather,
                     replicated front.
    """
    if plan.group is not None and not plan.group.totals:
        if any(a.function == "cardinality"
               for a in plan.group.aggregate_items):
            return "exchange-rows"
        return "exchange-states"
    if plan.window is not None and plan.window.partition_items:
        return "exchange-rows"
    return "gather"


# -- entry ---------------------------------------------------------------------


def run_whole_plan(evaluator, plan: ir.Query, table, stats=None,
                   rules=None, foreign_chunks=None):
    """Execute `plan` over a ShardedTable as ONE fused SPMD program.

    `evaluator` is the DistributedEvaluator owning the compile ladder
    (memory cache → AOT disk tier → fresh compile) and the quota memo.
    `foreign_chunks` maps join table path → replicated ColumnarChunk
    (multiway join plans fuse through `_run_join`).  Raises YtError for
    unfusable plans or in-program faults — the caller's degradation
    ladder steps down to the stitched rungs.
    """
    reason = can_fuse(plan)
    if reason is not None:
        raise YtError(f"plan is not whole-plan fusable: {reason}",
                      code=EErrorCode.QueryUnsupported)
    rules = DEFAULT_PARTITION_RULES if rules is None else tuple(rules)
    if plan.joins:
        chunk = _run_join(evaluator, plan, table, rules, stats,
                          foreign_chunks or {})
    else:
        shape = _shape_of(plan)
        if shape == "gather":
            chunk = _run_gather(evaluator, plan, table, rules, stats)
        else:
            chunk = _run_exchange(evaluator, plan, table, rules, shape,
                                  stats)
    if stats is not None:
        stats.whole_plan = 1
    return chunk


def _read_counts(final) -> np.ndarray:
    """THE whole-plan host sync: ONE stacked device→host transfer.
    Every fused shape funnels its single blocking read through here —
    gather programs return a bare count, exchange programs a (count,
    overflow, max-cell) triple, fused-join programs the count plus the
    per-join quota-demand/actual telemetry block.  Returns a 1-D int64
    vector; callers index their layout."""
    from ytsaurus_tpu.utils import sanitizers
    sanitizers.note_host_sync("whole_plan._read_counts")
    vals = np.asarray(final)
    if vals.ndim == 0:
        return np.array([int(vals)], dtype=np.int64)
    return vals.astype(np.int64).reshape(-1)


# -- mesh telemetry (ISSUE 20) -------------------------------------------------

# Layout version of the telemetry lanes appended to the stacked final
# transfer.  Rides as the first appended lane so a decoder can never
# misread a layout change as data.
MESH_TELEMETRY_VERSION = 1


def _mesh_armed() -> bool:
    """Whether the in-program mesh telemetry block is stacked onto the
    final transfer (TelemetryConfig.mesh_telemetry).  Folds into every
    whole-plan cache key — arming or disarming compiles a fresh program,
    it never reinterprets an old one's layout."""
    from ytsaurus_tpu.config import telemetry_config
    return bool(telemetry_config().mesh_telemetry)


def _mesh_lanes(row_valid, shard_out):
    """Device-side shape-independent lanes: [version] + per-shard live
    input rows + per-shard output rows.  Each is replicated via
    all_gather (legal under out_specs=P()), so they concatenate onto the
    existing stacked final — same single transfer, zero extra syncs."""
    version = jnp.full((1,), MESH_TELEMETRY_VERSION, dtype=jnp.int64)
    in_rows = jax.lax.all_gather(
        row_valid.sum().astype(jnp.int64), SHARD_AXIS).reshape(-1)
    out_rows = jax.lax.all_gather(
        shard_out.astype(jnp.int64), SHARD_AXIS).reshape(-1)
    return [version, in_rows, out_rows]


def _mesh_slices(vals, base: int, n: int):
    """Decode the shape-independent lanes appended at index `base` of
    the host-read final vector: (in_rows, out_rows, next_offset)."""
    version = int(vals[base])
    if version != MESH_TELEMETRY_VERSION:
        raise YtError(
            f"mesh telemetry version mismatch: program returned "
            f"{version}, host decodes {MESH_TELEMETRY_VERSION}",
            code=EErrorCode.QueryExecutionError)
    in_rows = vals[base + 1: base + 1 + n]
    out_rows = vals[base + 1 + n: base + 1 + 2 * n]
    return in_rows, out_rows, base + 1 + 2 * n


def _row_bytes(rep_columns) -> int:
    """Host-side bytes-per-row estimate of a routed rowset: encoded
    plane itemsize per EValueType (+1 for the validity plane) summed
    over columns.  An estimate for exchange-byte ACCOUNTING (string
    columns ride int32 dict codes on device), never a capacity."""
    from ytsaurus_tpu.schema import EValueType
    sizes = {EValueType.boolean: 1, EValueType.string: 4}
    total = 0
    for rc in rep_columns.values():
        total += sizes.get(rc.type, 8) + 1
    return total


def _mesh_exchange_entry(stage: str, matrix, demand: int, quota: int,
                         row_bytes: int) -> dict:
    """One all_to_all exchange's decoded telemetry: the flattened
    shard-major n*n transfer-count matrix, total rows/bytes moved, and
    quota demand vs granted (headroom = demand/quota utilization)."""
    cells = [int(x) for x in matrix] if matrix is not None else None
    rows = sum(cells) if cells else 0
    return {"stage": stage, "matrix": cells, "rows": rows,
            "bytes": rows * int(row_bytes), "demand": int(demand),
            "quota": int(quota),
            "headroom": round(float(demand) / float(quota), 4)
            if quota else 0.0}


def _mesh_block(n: int, in_rows, out_rows, exchanges, stages=None,
                path: str = "fused") -> dict:
    """The versioned per-program telemetry block every surface consumes
    (QueryStatistics, EXPLAIN ANALYZE, /mesh, `yt mesh top`).  The
    stitched rungs assemble the SAME shape from host values they
    already read (distributed._stitched_mesh_block)."""
    out = [int(x) for x in out_rows]
    total = sum(out)
    mean = total / float(n) if n else 0.0
    skew = (max(out) / mean) if mean > 0 else 1.0
    block = {"version": MESH_TELEMETRY_VERSION, "path": path,
             "shards": int(n),
             "in_rows": [int(x) for x in in_rows],
             "out_rows": out,
             "skew": round(float(skew), 4),
             "exchange_bytes": int(sum(e["bytes"] for e in exchanges)),
             "exchanges": list(exchanges)}
    if stages:
        block["stages"] = list(stages)
    return block


def _publish_mesh(stats, fingerprint: str, key, block: dict) -> None:
    """Fan one decoded telemetry block out to every surface: the query's
    statistics (EXPLAIN ANALYZE), the mesh observatory roll-up +
    /query/mesh sensors, and the ambient trace span (`yt trace` answers
    "which shard was hot").  Pure host bookkeeping over the vector the
    one sanctioned sync already transferred — zero extra syncs."""
    from ytsaurus_tpu.parallel.mesh_observatory import get_mesh_observatory
    from ytsaurus_tpu.utils import tracing
    obs = get_mesh_observatory()
    mem = obs.memory_for(key)
    if mem is not None:
        block["memory_watermark_bytes"] = mem
    if stats is not None:
        stats.note_mesh_block(block)
    obs.record_execution(fingerprint, block)
    span = tracing.current_trace()
    if span is not None and span.sampled:
        out_rows = block.get("out_rows") or []
        span.add_tag("mesh_skew", block.get("skew"))
        span.add_tag("mesh_exchange_bytes",
                     block.get("exchange_bytes", 0))
        if out_rows:
            hot = int(max(range(len(out_rows)),
                          key=out_rows.__getitem__))
            span.add_tag("mesh_hot_shard", hot)
            span.add_tag("mesh_hot_shard_rows", int(out_rows[hot]))
        if block.get("memory_watermark_bytes"):
            span.add_tag("mesh_memory_watermark_bytes",
                         block["memory_watermark_bytes"])


def _scan_shardings(rules, mesh, names: "list[str]"):
    """NamedShardings for the input planes per the registry ("scan/<col>"
    rules must keep scan columns on the shard axis — the planes ARE
    sharded)."""
    shardings = {}
    stages = []
    for name in names:
        stage = f"scan/{name}"
        stages.append((stage, True))
        shardings[name] = NamedSharding(mesh,
                                        match_partition_rules(rules, stage))
    _validate_stages(rules, stages)
    return shardings


def _constrain_inputs(mesh, shardings, columns: dict, row_valid):
    """`with_sharding_constraint` at the jit boundary: pins the scan
    planes to the registry's placement before the shard_map body (the
    GSPMD spelling of "this stage lives on the shard axis")."""
    out = {}
    for name, (data, valid) in columns.items():
        sh = shardings[name]
        out[name] = (jax.lax.with_sharding_constraint(data, sh),
                     jax.lax.with_sharding_constraint(valid, sh))
    rv = jax.lax.with_sharding_constraint(
        row_valid, NamedSharding(mesh, P(SHARD_AXIS)))
    return out, rv


def _gathered(planes_with_cols, shard_mask, out_cap: int):
    """In-program all_gather of a stage's output planes + mask."""
    gathered = {}
    for out_col, (d, v) in planes_with_cols:
        # Collapse only the (shards, rows) leading axes: trailing dims
        # (vector planes are (rows, dim)) ride through the gather.
        gathered[out_col.name] = (
            jax.lax.all_gather(d, SHARD_AXIS).reshape((-1,) + d.shape[1:]),
            jax.lax.all_gather(v, SHARD_AXIS).reshape(-1))
    g_mask = jax.lax.all_gather(shard_mask, SHARD_AXIS).reshape(-1)
    return gathered, g_mask


# -- gather shape --------------------------------------------------------------


def _run_gather(evaluator, plan: ir.Query, table, rules, stats=None):
    """bottom per shard → all_gather → replicated front, fused.  The
    same dataflow as the stitched gather rung, but compiled through the
    whole-plan ladder (AOT-serializable, registry-placed)."""
    from ytsaurus_tpu.parallel import distributed as dist
    dist._FP_GATHER.hit()
    mesh = table.mesh
    n = mesh.devices.size
    cap = table.capacity
    armed = _mesh_armed()
    bottom, front = split_plan(plan)
    prepared_b = prepare(bottom, table.rep_chunk())
    inter_rep = dist._RepChunk(
        capacity=n * prepared_b.out_capacity,
        columns={c.name: dist._RepColumn(type=c.type, dictionary=c.vocab)
                 for c in prepared_b.output})
    prepared_f = prepare(front, inter_rep)
    names = [c.name for c in bottom.schema if c.name in table.columns]
    shardings = _scan_shardings(rules, mesh, names)
    stages = [("bottom", True), ("front", False)]
    if plan.order is not None:
        stages.append(("order", False))
    if plan.project is not None:
        stages.append(("project", False))
    _validate_stages(rules, stages)
    out_cap = prepared_b.out_capacity

    def build():
        def fused(columns, row_valid, b_bnd, f_bnd):
            planes, count = prepared_b.run(columns, row_valid, b_bnd)
            shard_mask = jnp.arange(out_cap) < count
            gathered, g_mask = _gathered(
                list(zip(prepared_b.output, planes)), shard_mask, out_cap)
            out_planes, out_count = prepared_f.run(gathered, g_mask,
                                                   f_bnd)
            if not armed:
                return out_planes, out_count
            final = jnp.concatenate(
                [out_count.astype(jnp.int64).reshape(1)]
                + _mesh_lanes(row_valid, count))
            return out_planes, final

        mapped = shard_map(
            fused, mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P()),
            out_specs=P(), check_vma=False)

        def program(columns, row_valid, b_bnd, f_bnd):
            columns, row_valid = _constrain_inputs(mesh, shardings,
                                                   columns, row_valid)
            return mapped(columns, row_valid, b_bnd, f_bnd)

        return program

    key = ("whole", "gather", plan_fingerprint(bottom),
           plan_fingerprint(front), n, cap,
           prepared_b.binding_shapes(), prepared_f.binding_shapes(),
           rules_fingerprint(rules), armed)
    columns = {name: (table.columns[name].data, table.columns[name].valid)
               for name in names}
    out_planes, out_count = evaluator._dispatch_spmd(
        key, build, (columns, table.row_valid,
                     tuple(prepared_b.bindings),
                     tuple(prepared_f.bindings)))
    dist._note_host_sync()            # the final count read
    vals = _read_counts(out_count)
    count = int(vals[0])
    if armed:
        in_rows, out_rows, _off = _mesh_slices(vals, 1, n)
        _publish_mesh(stats, plan_fingerprint(plan), key,
                      _mesh_block(n, in_rows, out_rows, exchanges=[]))
    return dist._assemble_chunk(prepared_f.output, out_planes, count)


# -- exchange shapes -----------------------------------------------------------


def _bind_route_keys(rep_columns, key_refs, where_expr):
    """Bind routing-key expressions (+ optional WHERE) against a
    namespace of _RepColumn-like carriers.  Returns (bind_ctx, where_b,
    key_b)."""
    from ytsaurus_tpu.query.engine.expr import BindContext, ColumnBinding, \
        ExprBinder
    bind_ctx = BindContext(columns={
        name: ColumnBinding(type=rc.type, vocab=rc.dictionary)
        for name, rc in rep_columns.items()})
    binder = ExprBinder(bind_ctx)
    where_b = binder.bind(where_expr) if where_expr is not None else None
    key_b = [binder.bind(expr) for expr in key_refs]
    return bind_ctx, where_b, key_b


def _dest_hash(key_b, ctx, mask, cap: int, n: int):
    """Destination device by canonical key hash (mirrors the stitched
    shuffle's routing so both paths co-locate identical key sets)."""
    from ytsaurus_tpu.query.engine.expr import _combine_u64, _mix_u64
    from ytsaurus_tpu.parallel.distributed import _canonical_hash_plane
    acc = jnp.full(cap, np.uint64(0x9E3779B97F4A7C15), dtype=jnp.uint64)
    for kb in key_b:
        data, valid = kb.emit(ctx)
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int8)
        h = _mix_u64(_canonical_hash_plane(data))
        h = jnp.where(valid, h, jnp.zeros_like(h))
        acc = _combine_u64(acc, h)
    pid = (acc % np.uint64(n)).astype(jnp.int32)
    return jnp.where(mask, pid, n)


def _initial_quota(memo: dict, memo_key, bound_cap: int, n: int,
                   headroom: float) -> "tuple[int, int]":
    """(starting quota, hard bound).  The bound is the per-source live
    capacity — a source cannot send more rows than it holds to one
    destination, so a program at the bound can never overflow."""
    bound = pad_capacity(bound_cap)
    start = memo.get(memo_key)
    if start is None:
        start = min(bound,
                    pad_capacity(max(64, int(bound_cap * headroom) // n)))
    return start, bound


def _settle_quota(memo: dict, memo_key, demand: int,
                  bound: int) -> None:
    """Memoize the demand-sized quota for the next query of this shape.
    pow2 rounding of the MEASURED demand is the steady-state slack
    (multiplying by the configured headroom first would double most
    capacities for nothing — headroom belongs to the overflow
    escalation, where the estimate has proven short).  Hysteresis: only
    shrink past a 4x gap, and upward moves always apply, so per-query
    demand jitter cannot thrash the compile cache with alternating
    quota rungs."""
    settled = min(bound, pad_capacity(max(int(demand), 64)))
    prev = memo.get(memo_key)
    if prev is None or settled > prev or settled * 4 <= prev:
        memo[memo_key] = settled


def _run_exchange(evaluator, plan: ir.Query, table, rules, shape: str,
                  stats):
    """The co-partitioned shapes, fused end to end:

    exchange-states  scan→filter→partial group (per shard) → all_to_all
                     of the GROUP STATES by key hash → merge group +
                     having (complete groups per device) → all_gather →
                     order/project/offset/limit.  The exchange moves
                     aggregate states, not rows — the in-program
                     combiner.
    exchange-rows    scan→filter → all_to_all of the surviving ROWS by
                     group/PARTITION BY hash → full local stage
                     (complete groups: cardinality; complete partitions:
                     window) → all_gather → front.

    One static pow2 quota sizes the exchange; the program returns the
    true transfer max + overflow flag WITH the count (one stacked final
    transfer).  Overflow re-runs at the demanded quota and memoizes it.
    """
    from ytsaurus_tpu.config import compile_config
    from ytsaurus_tpu.parallel import distributed as dist
    from ytsaurus_tpu.query.engine.expr import EmitContext

    dist._FP_ALL_TO_ALL.hit()
    mesh = table.mesh
    n = mesh.devices.size
    cap = table.capacity
    headroom = compile_config().whole_plan_headroom
    armed = _mesh_armed()

    if shape == "exchange-states":
        bottom, front = split_plan(plan)
        prepared_s1 = prepare(bottom, table.rep_chunk())
        bound_cap = prepared_s1.out_capacity
        route_rep = {c.name: dist._RepColumn(type=c.type, dictionary=c.vocab)
                     for c in prepared_s1.output}
        route_names = [c.name for c in prepared_s1.output]
        # Routing keys: the group-key slots of the state rowset (bare
        # references — the bottom already evaluated the expressions).
        key_refs = [ir.TReference(type=item.expr.type, name=item.name)
                    for item in bottom.group.group_items]
        where_expr = None                 # consumed by the bottom
        local_plan = ir.FrontQuery(schema=front.schema, group=front.group,
                                   having=front.having)
        front_final = ir.FrontQuery(
            schema=local_plan.output_schema(), order=front.order,
            project=front.project, offset=front.offset, limit=front.limit)
        stage_names = [("bottom/group", True), ("shuffle/group", True),
                       ("local/group", True), ("front", False)]
    else:
        bottom = None
        prepared_s1 = None
        bound_cap = cap
        route_rep = {name: dist._RepColumn(type=col.type,
                                           dictionary=col.dictionary)
                     for name, col in table.columns.items()}
        route_names = [c.name for c in plan.schema
                       if c.name in table.columns]
        route_rep = {name: route_rep[name] for name in route_names}
        key_items = plan.window.partition_items \
            if plan.window is not None else plan.group.group_items
        key_refs = [item.expr for item in key_items]
        where_expr = plan.where
        local_plan = dc_replace(plan, order=None, project=None, offset=0,
                                limit=None)
        front_final = None                # built per quota below
        kind = "window" if plan.window is not None else "group"
        stage_names = [(f"shuffle/{kind}", True), (f"local/{kind}", True),
                       ("front", False)]
    if plan.order is not None:
        stage_names.append(("order", False))
    if plan.project is not None:
        stage_names.append(("project", False))
    _validate_stages(rules, stage_names)

    key_ctx, where_b, key_b = _bind_route_keys(route_rep, key_refs,
                                               where_expr)
    key_bindings = tuple(key_ctx.bindings)
    if shape == "exchange-states":
        columns = {name: (table.columns[name].data,
                          table.columns[name].valid)
                   for name in [c.name for c in bottom.schema
                                if c.name in table.columns]}
        scan_names = sorted(columns)
    else:
        columns = {name: (table.columns[name].data,
                          table.columns[name].valid)
                   for name in route_names}
        scan_names = route_names
    shardings = _scan_shardings(rules, mesh, scan_names)

    memo_key = (shape, plan_fingerprint(plan), n, bound_cap)
    quota, bound = _initial_quota(evaluator._quota_memo, memo_key,
                                  bound_cap, n, headroom)

    while True:
        recv_cap = n * quota
        local_rep = dist._RepChunk(
            capacity=recv_cap, columns=dict(route_rep))
        prepared_local = prepare(local_plan, local_rep)
        out_cap = prepared_local.out_capacity
        if shape == "exchange-states":
            final_plan = front_final
        else:
            final_plan = ir.FrontQuery(
                schema=local_plan.output_schema(), order=plan.order,
                project=plan.project, offset=plan.offset,
                limit=plan.limit)
        front_rep = dist._RepChunk(
            capacity=n * out_cap,
            columns={c.name: dist._RepColumn(type=c.type,
                                             dictionary=c.vocab)
                     for c in prepared_local.output})
        prepared_front = prepare(final_plan, front_rep)

        def build(quota=quota, prepared_local=prepared_local,
                  prepared_front=prepared_front, out_cap=out_cap):
            def fused(columns, row_valid, s1_bnd, key_bnd, l_bnd, f_bnd):
                if prepared_s1 is not None:
                    planes, cnt = prepared_s1.run(columns, row_valid,
                                                  s1_bnd)
                    routed = {c.name: plane for c, plane in
                              zip(prepared_s1.output, planes)}
                    mask = jnp.arange(bound_cap) < cnt
                else:
                    routed = {name: columns[name] for name in route_names}
                    mask = row_valid
                ctx = EmitContext(columns=routed, bindings=key_bnd,
                                  capacity=bound_cap)
                if where_b is not None:
                    d, v = where_b.emit(ctx)
                    mask = mask & v & d.astype(bool)
                pid = _dest_hash(key_b, ctx, mask, bound_cap, n)
                cell_counts = transfer_counts(pid, mask, n)
                recv, recv_mask = route_rows(routed, pid, n, quota,
                                             bound_cap)
                planes2, cnt2 = prepared_local.run(recv, recv_mask,
                                                   l_bnd)
                shard_mask = jnp.arange(out_cap) < cnt2
                gathered, g_mask = _gathered(
                    list(zip(prepared_local.output, planes2)),
                    shard_mask, out_cap)
                out_planes, out_count = prepared_front.run(gathered,
                                                           g_mask, f_bnd)
                # Replicated exchange telemetry riding the result: the
                # true transfer-matrix max (quota demand) + overflow.
                all_cells = jax.lax.all_gather(
                    cell_counts, SHARD_AXIS).reshape(-1)
                max_cell = all_cells.max().astype(jnp.int64)
                over = (max_cell > quota).astype(jnp.int64)
                final = jnp.stack(
                    [out_count.astype(jnp.int64), over, max_cell])
                if armed:
                    # Mesh telemetry lanes (ISSUE 20) append AFTER the
                    # existing layout — same stacked transfer.
                    final = jnp.concatenate(
                        [final] + _mesh_lanes(row_valid, cnt2)
                        + [all_cells.astype(jnp.int64)])
                return out_planes, final

            mapped = shard_map(
                fused, mesh=mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P(), P(),
                          P()),
                out_specs=P(), check_vma=False)

            def program(columns, row_valid, s1_bnd, key_bnd, l_bnd,
                        f_bnd):
                columns, row_valid = _constrain_inputs(
                    mesh, shardings, columns, row_valid)
                return mapped(columns, row_valid, s1_bnd, key_bnd,
                              l_bnd, f_bnd)

            return program

        key = ("whole", shape, plan_fingerprint(plan), n, cap, quota,
               bound_cap,
               prepared_s1.binding_shapes() if prepared_s1 is not None
               else None,
               tuple(key_ctx.structure),
               tuple((tuple(b.shape), str(b.dtype))
                     for b in key_bindings),
               prepared_local.binding_shapes(),
               prepared_front.binding_shapes(),
               rules_fingerprint(rules), armed)
        args = (columns, table.row_valid,
                tuple(prepared_s1.bindings) if prepared_s1 is not None
                else (),
                key_bindings, tuple(prepared_local.bindings),
                tuple(prepared_front.bindings))
        out_planes, final = evaluator._dispatch_spmd(key, build, args)
        # Noted PER read: an overflow retry performs a real second
        # stacked transfer and the counter must say so (steady state
        # stays at exactly one).
        dist._note_host_sync()
        vals = _read_counts(final)
        count, over, demand = int(vals[0]), int(vals[1]), int(vals[2])
        if not over:
            break
        if quota >= bound:
            raise YtError(
                "whole-plan exchange overflowed at the maximal quota "
                f"(quota={quota}, demand={demand})",
                code=EErrorCode.QueryExecutionError)
        if stats is not None:
            stats.whole_plan_retries += 1
        quota = min(bound,
                    max(pad_capacity(max(int(demand * headroom), 1)),
                        quota * 2))
    _settle_quota(evaluator._quota_memo, memo_key, demand, bound)
    if armed:
        in_rows, out_rows, off = _mesh_slices(vals, 3, n)
        entry = _mesh_exchange_entry(
            f"shuffle/{shape}", vals[off: off + n * n], demand, quota,
            _row_bytes(route_rep))
        _publish_mesh(stats, plan_fingerprint(plan), key,
                      _mesh_block(n, in_rows, out_rows, [entry]))
    return dist._assemble_chunk(prepared_front.output, out_planes, count)


# -- fused multiway join (ISSUE 14) --------------------------------------------


_OUT_CAP_UNBOUNDED = 1 << 40      # join expansion has no per-source bound


def _join_flat_names(join: ir.Query, needed) -> "list[tuple[str, str]]":
    """(flat output name, foreign column) pairs this join pulls, pruned
    to what the plan reads."""
    pairs = [(f"{join.alias}.{f}" if join.alias else f, f)
             for f in join.foreign_columns]
    if needed is not None:
        pairs = [(flat, f) for flat, f in pairs if flat in needed]
    return pairs


def _gate_fusable_join(join, foreign) -> None:
    """Foreign sides with host-resident payloads (`any` columns) cannot
    ride a device program — degrade to the stitched/host rungs."""
    from ytsaurus_tpu.schema import EValueType
    for fname in join.foreign_columns:
        fcol = foreign.columns.get(fname)
        if fcol is None:
            raise YtError(f"Join table {join.foreign_table!r} has no "
                          f"column {fname!r}",
                          code=EErrorCode.QueryExecutionError)
        if fcol.type is EValueType.any or fcol.host_values is not None:
            raise YtError(
                f"join column {fname!r} carries host payloads — "
                "not whole-plan fusable",
                code=EErrorCode.QueryUnsupported)


def _fallback_decisions(plan_x: ir.Query, foreign_chunks) -> tuple:
    """Planner-off decisions: declared order, broadcast only for small
    sides (same threshold), no pushdown."""
    from ytsaurus_tpu.config import compile_config
    from ytsaurus_tpu.query.planner import JoinDecision
    cap = compile_config().broadcast_join_rows
    out = []
    for i, join in enumerate(plan_x.joins):
        foreign = foreign_chunks.get(join.foreign_table)
        f_rows = foreign.row_count if foreign is not None else 0
        out.append(JoinDecision(
            index=i, strategy="broadcast" if 0 < f_rows <= cap
            else "partition", est_in=0, est_out=0, foreign_rows=f_rows))
    return tuple(out)


class _BroadcastSetup:
    """Replicated probe: sorted foreign key planes + pulled columns ride
    as P() args; per-shard lexicographic search, no exchange."""

    def __init__(self, join, self_bound, self_slots, n_keys,
                 arg_slice, f_cap, flat_names):
        self.join = join
        self.self_bound = self_bound
        self.self_slots = self_slots
        self.n_keys = n_keys
        self.arg_slice = arg_slice
        self.f_cap = f_cap
        self.flat_names = flat_names
        self.strategy = "broadcast"


class _PartitionSetup:
    """Co-partition exchange: both sides route by key hash over the
    in-program all_to_all, then probe + expand per device."""

    def __init__(self, join, self_bound, self_slots, f_bound,
                 foreign_slots, f_shard_index, f_slice, f_count,
                 flat_names):
        self.join = join
        self.self_bound = self_bound
        self.self_slots = self_slots
        self.f_bound = f_bound
        self.foreign_slots = foreign_slots
        self.f_shard_index = f_shard_index
        self.f_slice = f_slice
        self.f_count = f_count
        self.flat_names = flat_names
        self.strategy = "partition"


def _stage_foreign_shards(evaluator, foreign, f_names, n, mesh):
    """Shard a foreign chunk 1/n per device (the partition-join staging
    of the stitched path), memoized per (chunk identity, mesh shape):
    repeated queries against an unchanged dimension table must not
    re-transfer it."""
    from ytsaurus_tpu.chunks.columnar import pad_capacity as _pad
    from ytsaurus_tpu.parallel import distributed as dist
    f_count = foreign.row_count
    f_slice = _pad(max((f_count + n - 1) // n, 1))
    key = ("join-fshard", id(foreign), n, f_slice, tuple(f_names))

    def build():
        shard_sharding = NamedSharding(mesh, P(SHARD_AXIS))
        f_total = n * f_slice
        pad = f_total - f_count
        f_global = {}
        for fname in f_names:
            fcol = foreign.columns[fname]
            data = jnp.concatenate(
                [fcol.data[:f_count],
                 jnp.zeros(pad, dtype=fcol.data.dtype)])
            valid = jnp.concatenate(
                [fcol.valid[:f_count], jnp.zeros(pad, dtype=bool)])
            f_global[fname] = (jax.device_put(data, shard_sharding),
                               jax.device_put(valid, shard_sharding))
        f_row_valid = jax.device_put(jnp.arange(f_total) < f_count,
                                     shard_sharding)
        return f_global, f_row_valid, f_slice

    return dist._chunk_memo(evaluator._cache, key, foreign, build)


def _broadcast_args(evaluator, join, foreign, f_order, f_sorted,
                    flat_names):
    """Replicated probe args for one broadcast join (sorted key planes,
    f_order-gathered pulled columns, live count), memoized with the
    host-order phase's identity discipline."""
    from ytsaurus_tpu.parallel import distributed as dist
    key = ("join-bargs", id(foreign), id(f_order),
           tuple(f for _flat, f in flat_names))

    def build():
        args: list = []
        for v, d in f_sorted:
            args.append(v)
            args.append(d)
        for _flat, fname in flat_names:
            fcol = foreign.columns[fname]
            args.append(fcol.data[f_order])
            args.append(fcol.valid[f_order])
        args.append(jnp.asarray(foreign.row_count, dtype=jnp.int64))
        return tuple(args)

    return dist._chunk_memo(evaluator._cache, key, foreign, build)


def _join_pid(keys, mask, n: int, keep_null_local: bool):
    """Destination device by encoded-key hash (the partitioned-join
    routing of distributed.py): null-keyed live rows stay local for
    LEFT joins (they still emit an unmatched row) and are discarded
    otherwise."""
    from ytsaurus_tpu.query.engine.expr import _combine_u64, _mix_u64
    from ytsaurus_tpu.parallel.distributed import _canonical_hash_plane
    from ytsaurus_tpu.query.engine.joins import null_key_mask
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


def _run_join(evaluator, plan: ir.Query, table, rules, stats,
              foreign_chunks: dict):
    """Multiway equi-join plans as ONE fused SPMD program (the ISSUE 14
    tentpole): the cost-based planner (query/planner.py) orders the
    joins and picks broadcast-vs-partition per side off chunk-stats
    cardinalities; broadcast sides replicate their sorted key planes
    (the joins.py lexicographic-search backbone probes them per shard),
    partition sides co-partition BOTH inputs by join-key hash through
    the same in-program all_to_all the GROUP BY shapes use; the joined
    rowset then runs bottom → all_gather → front without leaving the
    program.  The PR 10 memoized quota/overflow protocol covers every
    data-dependent capacity (two exchange quotas + the match-expansion
    output capacity per partition join): static pow2 sizes, true
    demands computed on device and returned stacked WITH the final
    count — one host sync — and an overflow re-runs at the demanded
    rung then memoizes it.  Planner decisions (order, strategies,
    pushdown columns) fold into the program cache key, so a stats-
    driven plan change can never serve a stale program."""
    from dataclasses import replace as dc_replace

    from ytsaurus_tpu.config import compile_config
    from ytsaurus_tpu.parallel import distributed as dist
    from ytsaurus_tpu.query import planner
    from ytsaurus_tpu.query.engine.expr import (
        BindContext, ColumnBinding, EmitContext, ExprBinder,
    )
    from ytsaurus_tpu.query.engine.joins import (
        _bind_keys, _emit_encoded_keys, _lex_searchsorted, null_key_mask,
        probe_replicated, sort_foreign_keys,
    )
    from ytsaurus_tpu.schema import EValueType, TableSchema

    mesh = table.mesh
    n = mesh.devices.size
    cap = table.capacity
    headroom = compile_config().whole_plan_headroom
    armed = _mesh_armed()

    # -- plan: order + strategies + pushdown off the chunk stats -------
    jplan = planner.plan_for_chunks(plan, table.total_rows,
                                    foreign_chunks)
    plan_x = planner.apply_order(plan, jplan)
    decisions = jplan.decisions if jplan is not None else \
        _fallback_decisions(plan_x, foreign_chunks)
    needed = ir.referenced_columns(plan_x)
    scan_names = sorted(name for name in table.columns
                        if needed is None or name in needed)

    # -- host phase: bind every join against the widening namespace ----
    bindings: list = []
    bind_structure: list = []
    namespace: dict = {
        name: ColumnBinding(type=col.type, vocab=col.dictionary)
        for name, col in table.columns.items()}
    rep_columns: dict = {
        name: dist._RepColumn(type=col.type, dictionary=col.dictionary)
        for name, col in table.columns.items()}
    setups: list = []
    rep_args: list = []             # replicated broadcast-probe args
    f_shards: list = []             # per-partition-join sharded planes
    fingerprint_parts: list = []
    # Host-side rowset-width tracking for exchange-byte accounting
    # (ISSUE 20): the self-side routed width at each partition stage is
    # the scan columns + every flat a PRIOR join pulled.
    cur_rep = {name: rep_columns[name] for name in scan_names}
    stage_row_bytes: list = []      # (self, foreign) bytes/row, or None
    for join, decision in zip(plan_x.joins, decisions):
        foreign = foreign_chunks.get(join.foreign_table)
        if foreign is None:
            raise YtError(
                f"No data provided for join table {join.foreign_table!r}",
                code=EErrorCode.QueryExecutionError)
        _gate_fusable_join(join, foreign)
        bind_ctx = BindContext(columns=dict(namespace),
                               bindings=bindings,
                               structure=bind_structure)
        binder = ExprBinder(bind_ctx)
        self_bound = [binder.bind(e) for e in join.self_equations]
        f_bound = _bind_keys(foreign, join.foreign_schema,
                             join.foreign_equations, bindings,
                             structure=bind_structure)
        self_slots, foreign_slots = dist._vocab_remap_slots(
            self_bound, f_bound, bindings)
        flat_names = _join_flat_names(join, needed)
        strategy = decision.strategy
        if strategy == "broadcast":
            # Broadcast needs provably unique foreign keys (the probe
            # gathers a single match row); the host-order phase verifies
            # and memoizes per chunk — non-unique sides fall back to the
            # partition exchange, and the RESOLVED strategy keys caches.
            f_order, f_sorted, unique = dist._foreign_host_order(
                evaluator._cache, join, foreign, self_bound, f_bound,
                foreign_slots, bindings)
            if not unique:
                strategy = "partition"
        if strategy == "broadcast":
            a0 = len(rep_args)
            rep_args.extend(_broadcast_args(evaluator, join, foreign,
                                            f_order, f_sorted,
                                            flat_names))
            setups.append(_BroadcastSetup(
                join, self_bound, self_slots, len(f_bound),
                (a0, len(rep_args)), foreign.capacity, flat_names))
            stage_row_bytes.append(None)
            fingerprint_parts.append(
                ("broadcast", foreign.capacity, foreign.row_count > 0))
        else:
            f_key_refs: set = set()
            for eq in join.foreign_equations:
                f_key_refs.update(ir.expr_references(eq))
            f_names = sorted(f_key_refs | {f for _flat, f in flat_names})
            f_global, f_row_valid, f_slice = _stage_foreign_shards(
                evaluator, foreign, f_names, n, mesh)
            f_shards.append((f_global, f_row_valid))
            setups.append(_PartitionSetup(
                join, self_bound, self_slots, f_bound, foreign_slots,
                len(f_shards) - 1, f_slice, foreign.row_count,
                flat_names))
            stage_row_bytes.append((
                _row_bytes(cur_rep),
                _row_bytes({f: dist._RepColumn(
                    type=foreign.columns[f].type,
                    dictionary=foreign.columns[f].dictionary)
                    for f in f_names})))
            fingerprint_parts.append(
                ("partition", f_slice, foreign.row_count > 0))
        for flat, fname in flat_names:
            fcol = foreign.columns[fname]
            namespace[flat] = ColumnBinding(type=fcol.type,
                                            vocab=fcol.dictionary)
            rep_columns[flat] = dist._RepColumn(type=fcol.type,
                                                dictionary=fcol.dictionary)
            cur_rep[flat] = rep_columns[flat]
        fingerprint_parts.append(tuple(
            len(b.vocab) if b.vocab is not None else -1
            for b in list(self_bound) + list(f_bound)))

    # Semi-join pushdown: selective INNER sides' key ranges mask self
    # rows BEFORE the first exchange (values ride 0-d bindings so stats
    # drift that moves a bound recompiles nothing; the pushed COLUMN set
    # is a planner decision and folds into the key via the token).
    push_slots: list = []
    if jplan is not None:
        pushable = {EValueType.int64, EValueType.uint64, EValueType.double}
        for name, lo, hi in jplan.pushdown_ranges():
            col = table.columns.get(name)
            if col is None or col.type not in pushable:
                continue
            dt = col.data.dtype
            lo_slot = len(bindings)
            bindings.append(jnp.asarray(lo, dtype=dt))
            hi_slot = len(bindings)
            bindings.append(jnp.asarray(hi, dtype=dt))
            push_slots.append((name, lo_slot, hi_slot))
    join_bindings = tuple(bindings)

    # Shuffle-boundary fault sites (the chaos-soak contract): the fused
    # join program ends in an all_gather, and partition joins ride the
    # in-program all_to_all — an injected collective fault knocks this
    # rung out and the ladder serves the query stitched.
    dist._FP_GATHER.hit()
    if any(s.strategy == "partition" for s in setups):
        dist._FP_ALL_TO_ALL.hit()

    columns = {name: (table.columns[name].data, table.columns[name].valid)
               for name in scan_names}
    shardings = _scan_shardings(rules, mesh, scan_names)
    stage_names = [(f"join/{i}", True) for i in range(len(setups))]
    stage_names += [(f"shuffle/join/{i}", True)
                    for i, s in enumerate(setups)
                    if s.strategy == "partition"]
    stage_names += [("bottom", True), ("front", False)]
    _validate_stages(rules, stage_names)

    # -- the post-join plan (bottom per device, all_gather, front) -----
    plan_nojoin = dc_replace(plan_x, joins=())
    if needed is not None:
        plan_nojoin = dc_replace(plan_nojoin, schema=TableSchema(
            columns=tuple(c for c in plan_x.schema if c.name in needed)))
    bottom, front = split_plan(plan_nojoin)

    token = tuple((d.index, s.strategy) for d, s in zip(decisions, setups)) \
        + (tuple(name for name, _lo, _hi in push_slots),)
    memo_base = ("join", plan_fingerprint(plan_x), token, n, cap)

    def initial(kind: str, j: int, est: int, bound: int) -> int:
        memo_key = memo_base + (j, kind)
        start = evaluator._quota_memo.get(memo_key)
        if start is None:
            # pow2 rounding IS the first-guess headroom (1-2x slack):
            # multiplying an accurate estimate by the configured
            # headroom BEFORE rounding doubles every capacity — and the
            # out capacity sizes all post-join stages.  A rare slight
            # under-estimate costs one overflow retry (which applies
            # the headroom) and memoizes; an accurate one runs tight.
            start = min(bound, pad_capacity(max(64, est)))
        return min(start, bound)

    quotas: dict = {}
    for j, (setup, decision) in enumerate(zip(setups, decisions)):
        if setup.strategy != "partition":
            continue
        est_in = max(decision.est_in, 1)
        est_out = max(decision.est_out, 1)
        quotas[j] = {
            # Expected max transfer cell ~ rows-per-device / n under
            # uniform hashing; the overflow protocol absorbs skew.
            "qs": initial("qs", j, est_in // (n * n), cap),
            "qf": initial("qf", j, max(setup.f_count, 1) // (n * n),
                          setup.f_slice),
            "out": initial("out", j, max(est_out // n, 128),
                           _OUT_CAP_UNBOUNDED),
        }

    while True:
        # Per-iteration static capacities: each partition join's input
        # capacity is the previous expansion's output capacity.
        caps: list = []
        cur_cap = cap
        for j, setup in enumerate(setups):
            caps.append(cur_cap)
            if setup.strategy == "partition":
                cur_cap = quotas[j]["out"]
        final_cap = cur_cap

        local_rep = dist._RepChunk(
            capacity=final_cap,
            columns={c.name: rep_columns[c.name]
                     for c in bottom.schema})
        prepared_b = prepare(bottom, local_rep)
        inter_rep = dist._RepChunk(
            capacity=n * prepared_b.out_capacity,
            columns={c.name: dist._RepColumn(type=c.type,
                                             dictionary=c.vocab)
                     for c in prepared_b.output})
        prepared_f = prepare(front, inter_rep)
        out_cap_b = prepared_b.out_capacity

        quota_state = tuple(
            (j, quotas[j]["qs"], quotas[j]["qf"], quotas[j]["out"])
            for j in sorted(quotas))

        def build(quota_state=quota_state, caps=tuple(caps),
                  prepared_b=prepared_b, prepared_f=prepared_f,
                  out_cap_b=out_cap_b):
            q = {j: (qs, qf, oc) for j, qs, qf, oc in quota_state}

            def fused(columns, row_valid, jbnd, rep_args_t, f_shards_t,
                      b_bnd, f_bnd):
                cur = dict(columns)
                mask = row_valid
                for _name, lo_slot, hi_slot in push_slots:
                    d, v = cur[_name]
                    mask = mask & v & (d >= jbnd[lo_slot]) & \
                        (d <= jbnd[hi_slot])
                telemetry = []
                mesh_mats = []          # armed: n*n matrices per exchange
                for j, setup in enumerate(setups):
                    cur_cap_j = caps[j]
                    ctx = EmitContext(columns=cur, bindings=jbnd,
                                      capacity=cur_cap_j)
                    self_keys = _emit_encoded_keys(
                        setup.self_bound, setup.self_slots, ctx)
                    zero = jnp.zeros((), dtype=jnp.int64)
                    if setup.strategy == "broadcast":
                        a0, a1 = setup.arg_slice
                        pulled, mask = probe_replicated(
                            rep_args_t[a0:a1], setup.n_keys, setup.f_cap,
                            self_keys, mask, setup.join.is_left)
                        for (flat, _f), plane in zip(setup.flat_names,
                                                     pulled):
                            cur[flat] = plane
                        actual = jax.lax.psum(
                            mask.sum().astype(jnp.int64), SHARD_AXIS)
                        telemetry.extend([zero, zero, zero, actual])
                        continue
                    # -- partition join ------------------------------
                    qs, qf, oc = q[j]
                    S, F = n * qs, n * qf
                    is_left = setup.join.is_left
                    fcols, fvalid = f_shards_t[setup.f_shard_index]
                    fctx = EmitContext(columns=fcols, bindings=jbnd,
                                       capacity=setup.f_slice)
                    f_keys = _emit_encoded_keys(
                        setup.f_bound, setup.foreign_slots, fctx)
                    pid_s = _join_pid(self_keys, mask, n, is_left)
                    pid_f = _join_pid(f_keys, fvalid, n, False)
                    cells_s = transfer_counts(pid_s, pid_s < n, n)
                    cells_f = transfer_counts(pid_f, pid_f < n, n)
                    if armed:
                        mesh_mats.append(jax.lax.all_gather(
                            cells_s,
                            SHARD_AXIS).reshape(-1).astype(jnp.int64))
                        mesh_mats.append(jax.lax.all_gather(
                            cells_f,
                            SHARD_AXIS).reshape(-1).astype(jnp.int64))
                    recv_s, mask_s = route_rows(cur, pid_s, n, qs,
                                                cur_cap_j)
                    recv_f, mask_f = route_rows(fcols, pid_f, n, qf,
                                                setup.f_slice)
                    sctx = EmitContext(columns=recv_s, bindings=jbnd,
                                       capacity=S)
                    s_keys = _emit_encoded_keys(
                        setup.self_bound, setup.self_slots, sctx)
                    rctx = EmitContext(columns=recv_f, bindings=jbnd,
                                       capacity=F)
                    r_keys = _emit_encoded_keys(
                        setup.f_bound, setup.foreign_slots, rctx)
                    f_order, f_sorted = sort_foreign_keys(r_keys, mask_f)
                    n_f = mask_f.sum()
                    lo = _lex_searchsorted(f_sorted, n_f, F, s_keys,
                                           "left")
                    hi = _lex_searchsorted(f_sorted, n_f, F, s_keys,
                                           "right")
                    s_null = null_key_mask(s_keys)
                    counts = jnp.where(mask_s & ~s_null, hi - lo, 0)
                    per_row = jnp.where(mask_s, jnp.maximum(counts, 1),
                                        0) if is_left else counts
                    offsets = jnp.cumsum(per_row)
                    total = offsets[-1]
                    starts = jnp.concatenate(
                        [jnp.zeros(1, dtype=offsets.dtype),
                         offsets[:-1]])
                    out_idx = jnp.arange(oc)
                    self_row = jnp.clip(
                        jnp.searchsorted(offsets, out_idx, side="right"),
                        0, S - 1)
                    within = out_idx - starts[self_row]
                    matched = counts[self_row] > 0
                    f_pos = jnp.clip(lo[self_row] + within, 0, F - 1)
                    f_row = f_order[f_pos]
                    live = out_idx < total
                    nxt = {}
                    for name in sorted(cur):
                        d, v = recv_s[name]
                        nxt[name] = (d[self_row],
                                     v[self_row] & live)
                    for flat, fname in setup.flat_names:
                        d, v = recv_f[fname]
                        nxt[flat] = (d[f_row],
                                     v[f_row] & live & matched)
                    cur = nxt
                    mask = live
                    # Demands (replicated via collectives): true max
                    # transfer cells + max per-device expansion.
                    ds = jax.lax.pmax(
                        cells_s.max().astype(jnp.int64), SHARD_AXIS)
                    df = jax.lax.pmax(
                        cells_f.max().astype(jnp.int64), SHARD_AXIS)
                    dout = jax.lax.pmax(total.astype(jnp.int64),
                                        SHARD_AXIS)
                    actual = jax.lax.psum(
                        live.sum().astype(jnp.int64), SHARD_AXIS)
                    telemetry.extend([ds, df, dout, actual])
                # -- bottom per device, all_gather, replicated front --
                planes, cnt = prepared_b.run(cur, mask, b_bnd)
                shard_mask = jnp.arange(out_cap_b) < cnt
                gathered, g_mask = _gathered(
                    list(zip(prepared_b.output, planes)), shard_mask,
                    out_cap_b)
                out_planes, out_count = prepared_f.run(gathered, g_mask,
                                                       f_bnd)
                over = jnp.zeros((), dtype=jnp.int64)
                for j, (_j, qs, qf, oc) in enumerate(quota_state):
                    base = 4 * _j
                    over = jnp.maximum(
                        over, (telemetry[base] > qs).astype(jnp.int64))
                    over = jnp.maximum(
                        over,
                        (telemetry[base + 1] > qf).astype(jnp.int64))
                    over = jnp.maximum(
                        over,
                        (telemetry[base + 2] > oc).astype(jnp.int64))
                final = jnp.stack(
                    [out_count.astype(jnp.int64), over] + telemetry)
                if armed:
                    # Mesh telemetry lanes (ISSUE 20) append AFTER the
                    # existing layout — same stacked transfer.
                    final = jnp.concatenate(
                        [final] + _mesh_lanes(row_valid, cnt)
                        + mesh_mats)
                return out_planes, final

            mapped = shard_map(
                fused, mesh=mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P(),
                          P(SHARD_AXIS), P(), P()),
                out_specs=P(), check_vma=False)

            def program(columns, row_valid, jbnd, rep_args_t, f_shards_t,
                        b_bnd, f_bnd):
                columns, row_valid = _constrain_inputs(
                    mesh, shardings, columns, row_valid)
                return mapped(columns, row_valid, jbnd, rep_args_t,
                              f_shards_t, b_bnd, f_bnd)

            return program

        key = ("whole", "join", plan_fingerprint(plan_x), n, cap, token,
               quota_state, tuple(fingerprint_parts),
               tuple(bind_structure),
               tuple((tuple(b.shape), str(b.dtype))
                     for b in join_bindings),
               prepared_b.binding_shapes(), prepared_f.binding_shapes(),
               rules_fingerprint(rules), armed)
        args = (columns, table.row_valid, join_bindings, tuple(rep_args),
                tuple(f_shards), tuple(prepared_b.bindings),
                tuple(prepared_f.bindings))
        out_planes, final = evaluator._dispatch_spmd(key, build, args)
        # Noted PER read: an overflow retry performs a real second
        # stacked transfer and the counter must say so.
        dist._note_host_sync()
        vals = _read_counts(final)
        count, over = int(vals[0]), int(vals[1])
        if not over:
            break
        if stats is not None:
            stats.whole_plan_retries += 1
        escalated = False
        for j, setup in enumerate(setups):
            if setup.strategy != "partition":
                continue
            dem_s, dem_f, dem_o = (int(vals[2 + 4 * j]),
                                   int(vals[3 + 4 * j]),
                                   int(vals[4 + 4 * j]))
            q = quotas[j]
            if dem_s > q["qs"]:
                bound = caps[j]
                if q["qs"] >= bound:
                    raise YtError(
                        "fused join exchange overflowed at the maximal "
                        f"quota (join {j}, quota={q['qs']}, "
                        f"demand={dem_s})",
                        code=EErrorCode.QueryExecutionError)
                q["qs"] = min(bound,
                              max(pad_capacity(
                                  max(int(dem_s * headroom), 1)),
                                  q["qs"] * 2))
                escalated = True
            if dem_f > q["qf"]:
                bound = setup.f_slice
                if q["qf"] >= bound:
                    raise YtError(
                        "fused join exchange overflowed at the maximal "
                        f"quota (join {j}, quota={q['qf']}, "
                        f"demand={dem_f})",
                        code=EErrorCode.QueryExecutionError)
                q["qf"] = min(bound,
                              max(pad_capacity(
                                  max(int(dem_f * headroom), 1)),
                                  q["qf"] * 2))
                escalated = True
            if dem_o > q["out"]:
                q["out"] = max(pad_capacity(
                    max(int(dem_o * headroom), 1)), q["out"] * 2)
                escalated = True
        if not escalated:
            raise YtError("fused join overflow without a demand above "
                          "quota — telemetry inconsistent",
                          code=EErrorCode.QueryExecutionError)

    # Settle quotas (hysteresis via _settle_quota) + EXPLAIN telemetry.
    for j, setup in enumerate(setups):
        if setup.strategy == "partition":
            dem_s, dem_f, dem_o = (int(vals[2 + 4 * j]),
                                   int(vals[3 + 4 * j]),
                                   int(vals[4 + 4 * j]))
            _settle_quota(evaluator._quota_memo, memo_base + (j, "qs"),
                          dem_s, caps[j])
            _settle_quota(evaluator._quota_memo, memo_base + (j, "qf"),
                          dem_f, setup.f_slice)
            _settle_quota(evaluator._quota_memo, memo_base + (j, "out"),
                          dem_o, _OUT_CAP_UNBOUNDED)
    if stats is not None:
        for j, (setup, decision) in enumerate(zip(setups, decisions)):
            stats.note_join_stage(
                j, setup.join.foreign_table, setup.strategy,
                est_rows=decision.est_out,
                actual_rows=int(vals[5 + 4 * j]))
    if armed:
        base = 2 + 4 * len(setups)
        in_rows, out_rows, off = _mesh_slices(vals, base, n)
        exchanges: list = []
        stages_meta: list = []
        for j, (setup, decision) in enumerate(zip(setups, decisions)):
            actual = int(vals[5 + 4 * j])
            stages_meta.append({
                "stage": j, "table": setup.join.foreign_table,
                "strategy": setup.strategy,
                "est_rows": int(decision.est_out),
                "actual_rows": actual,
                "drift": planner.est_drift(decision.est_out, actual)})
            if setup.strategy != "partition":
                continue
            q = quotas[j]
            self_bytes, f_bytes = stage_row_bytes[j]
            exchanges.append(_mesh_exchange_entry(
                f"join[{j}]/self", vals[off: off + n * n],
                int(vals[2 + 4 * j]), q["qs"], self_bytes))
            off += n * n
            exchanges.append(_mesh_exchange_entry(
                f"join[{j}]/foreign", vals[off: off + n * n],
                int(vals[3 + 4 * j]), q["qf"], f_bytes))
            off += n * n
        _publish_mesh(stats, plan_fingerprint(plan_x), key,
                      _mesh_block(n, in_rows, out_rows, exchanges,
                                  stages=stages_meta))
    return dist._assemble_chunk(prepared_f.output, out_planes, count)
