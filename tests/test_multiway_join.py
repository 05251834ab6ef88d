"""Multiway join fusion + the cost-based planner (ISSUE 14).

Quick tier-1 coverage: the fused-join dual-check over one
representative per strategy/stage shape (CORPUS_QUICK — the 4-way
broadcast+partition+string plan, LEFT broadcast, window-after-join,
cardinality front) against the local evaluator with exactly one steady
host sync; planner units (selectivity order, dependency + LEFT
barriers, broadcast threshold, semi-join pushdown); the stats-drift
recompile; the join degradation ladder; the NDV sketch (accuracy,
merge, bounded payload, decode backfill); EXPLAIN ANALYZE join-plan
rendering; and client-side shard pruning through pushed-down join key
ranges.  The FULL corpus sweep, skew-driven quota overflow escalation,
and the cross-process AOT restart leg run under `slow` so the quick
pass fits the tier-1 870s budget (sibling quick coverage: whole-plan
quota memo + disk-tier tests in test_whole_plan.py).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.chunks import ColumnarChunk
from ytsaurus_tpu.chunks.columnar import concat_chunks
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.engine.evaluator import Evaluator
from ytsaurus_tpu.query.statistics import QueryStatistics
from ytsaurus_tpu.schema import TableSchema

FACT = TableSchema.make([
    ("k", "int64", "ascending"), ("ok", "int64"), ("sk", "int64"),
    ("s", "string"), ("v", "int64")])
DIM = TableSchema.make([("d_ok", "int64"), ("d_w", "int64")])
DUP = TableSchema.make([("u_sk", "int64"), ("u_t", "string")])
SDIM = TableSchema.make([("m_s", "string"), ("m_w", "int64")])
SCHEMAS = {"//l": FACT, "//d": DIM, "//u": DUP, "//m": SDIM}

# The dual-check corpus: every strategy mix across the Q5/Q7/Q8-class
# shapes — broadcast (unique int dim), partition (duplicated keys),
# string-key broadcast, LEFT variants, and post-join group/window/
# order/cardinality stages.
CORPUS = [
    # broadcast + group (Q3-class tail)
    "d_w, sum(v) AS sv, count(*) AS c FROM [//l] JOIN [//d] ON ok = d_ok "
    "GROUP BY d_w ORDER BY d_w LIMIT 500",
    # partition (non-unique foreign keys) + group
    "u_t, sum(v) AS sv FROM [//l] JOIN [//u] ON sk = u_sk "
    "GROUP BY u_t ORDER BY u_t LIMIT 500",
    # 3-way mixed broadcast + partition, string group key (Q5-class)
    "m_w, count(*) AS c, sum(v) AS sv FROM [//l] "
    "JOIN [//u] ON sk = u_sk JOIN [//m] ON s = m_s "
    "GROUP BY m_w ORDER BY m_w LIMIT 100",
    # 4-way: broadcast + partition + string broadcast (Q8-class)
    "d_w, m_w, sum(v) AS sv FROM [//l] JOIN [//d] ON ok = d_ok "
    "JOIN [//u] ON sk = u_sk JOIN [//m] ON s = m_s "
    "GROUP BY d_w, m_w ORDER BY d_w, m_w LIMIT 500",
    # LEFT broadcast (string key), bare select
    "k, m_w, v FROM [//l] LEFT JOIN [//m] ON s = m_s WHERE v > 50",
    # LEFT partition join
    "k, u_t FROM [//l] LEFT JOIN [//u] ON sk = u_sk WHERE v > 90",
    # window after join
    "k, d_w, sum(v) OVER (PARTITION BY d_w ORDER BY k) AS rs "
    "FROM [//l] JOIN [//d] ON ok = d_ok ORDER BY k LIMIT 300",
    # cardinality after join (exchange-rows front)
    "d_w, cardinality(s) AS cd FROM [//l] JOIN [//d] ON ok = d_ok "
    "GROUP BY d_w ORDER BY d_w LIMIT 100",
]

# Quick-tier subset: one representative per strategy/stage shape — the
# 4-way plan exercises broadcast + partition + string-broadcast edges
# in one program, plus LEFT broadcast, window-after-join, and the
# cardinality exchange-rows front.  Each corpus query costs a full
# 8-device shard_map compile (~6s on CPU); the 2/3-way and LEFT
# partition variants those subsume run in the `slow` full sweep
# (test_multiway_dual_check_corpus_full).
CORPUS_QUICK = [CORPUS[3], CORPUS[4], CORPUS[6], CORPUS[7]]


@pytest.fixture(autouse=True)
def _fresh_compile_config():
    yield
    yt_config.set_compile_config(None)


@pytest.fixture(scope="module")
def mw_tables(request):
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import ShardedTable
    rng = np.random.default_rng(37)
    words = [f"w{i:02d}" for i in range(13)]
    chunks = []
    for sh in range(8):
        n = 120 + sh * 9
        rows = []
        for i in range(n):
            rows.append((
                sh * 10_000 + i,
                # ~10% null join keys: they must match nothing (and
                # still surface under LEFT joins).
                int(rng.integers(0, 50)) if rng.uniform() > 0.1 else None,
                int(rng.integers(0, 40)),
                words[int(rng.integers(0, 13))],
                int(rng.integers(0, 100))))
        chunks.append(ColumnarChunk.from_rows(FACT, rows))
    table = ShardedTable.from_chunks(mesh, chunks)
    dim = ColumnarChunk.from_arrays(DIM, {
        "d_ok": np.arange(50), "d_w": np.arange(50) * 3 % 7})
    dup_rows = [(key, f"t{key % 5}")
                for key in range(40) for _ in range(int(rng.integers(0, 4)))]
    dup = ColumnarChunk.from_rows(DUP, dup_rows)
    sdim = ColumnarChunk.from_rows(
        SDIM, [(w, i * 10) for i, w in enumerate(words[:9])])
    foreign = {"//d": dim, "//u": dup, "//m": sdim}
    return mesh, chunks, table, concat_chunks(chunks), foreign


def _canon(rows):
    def norm(v):
        if v is None:
            return (0, 0)
        return (1, round(v, 9) if isinstance(v, float) else v)

    return sorted(tuple((k, norm(v)) for k, v in sorted(r.items()))
                  for r in rows)


def _dual_check(mw_tables, corpus):
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        host_sync_count,
    )
    from ytsaurus_tpu.parallel.whole_plan import can_fuse, run_whole_plan
    mesh, _chunks, table, merged, foreign = mw_tables
    de = DistributedEvaluator(mesh)
    local = Evaluator()
    for query in corpus:
        plan = build_query(query, SCHEMAS)
        assert can_fuse(plan) is None, query
        stats = QueryStatistics()
        got = run_whole_plan(de, plan, table, stats=stats,
                             foreign_chunks=foreign)
        assert stats.whole_plan == 1
        want = local.run_plan(plan, merged, foreign)
        assert _canon(got.to_rows()) == _canon(want.to_rows()), query
        # Steady state (quotas settled): exactly one stacked transfer.
        s0 = host_sync_count()
        got2 = run_whole_plan(de, plan, table, foreign_chunks=foreign)
        assert host_sync_count() - s0 == 1, query
        assert _canon(got2.to_rows()) == _canon(want.to_rows()), query


def test_stitched_cascade_pays_syncs_per_join(mw_tables):
    """What the fused multiway join is compared with: the stitched
    binary cascade (`DistributedEvaluator.run`, shuffle) runs count /
    probe / expand programs per join with host syncs between them —
    three a query or more over a 3-join plan, against the fused rung's
    one — and answers the same rows."""
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        host_sync_count,
    )
    mesh, _chunks, table, merged, foreign = mw_tables
    plan = build_query(CORPUS[3], SCHEMAS)
    yt_config.set_compile_config(yt_config.CompileConfig(whole_plan=False))
    de = DistributedEvaluator(mesh)
    de.run(plan, table, foreign, shuffle=True)               # warm-up
    s0 = host_sync_count()
    got = de.run(plan, table, foreign, shuffle=True)
    assert host_sync_count() - s0 >= 3
    want = Evaluator().run_plan(plan, merged, foreign)
    assert _canon(got.to_rows()) == _canon(want.to_rows())


def test_multiway_dual_check_corpus(mw_tables):
    """Fused multiway joins vs the local evaluator over the quick
    shape-representative corpus, with exactly ONE steady-state host
    sync per fused query."""
    _dual_check(mw_tables, CORPUS_QUICK)


@pytest.mark.slow
def test_multiway_dual_check_corpus_full(mw_tables):
    """The full strategy-mix corpus — minutes-long variant of
    test_multiway_dual_check_corpus."""
    _dual_check(mw_tables, CORPUS)


def test_join_ladder_serves_fused_and_degrades(mw_tables):
    """coordinate_distributed serves join plans off the fused rung; an
    injected all_to_all fault knocks a partition-join plan down the
    ladder bit-identically (a broadcast-only fused join genuinely does
    not touch all_to_all and survives)."""
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        coordinate_distributed,
    )
    from ytsaurus_tpu.utils import failpoints
    mesh, chunks, _table, merged, foreign = mw_tables
    de = DistributedEvaluator(mesh)
    local = Evaluator()
    plan = build_query(CORPUS[1], SCHEMAS)       # partition strategy
    stats = QueryStatistics()
    got = coordinate_distributed(plan, mesh, chunks, foreign,
                                 evaluator=de, stats=stats)
    base = _canon(got.to_rows())
    assert base == _canon(local.run_plan(plan, merged, foreign).to_rows())
    assert stats.whole_plan == 1
    stats = QueryStatistics()
    with failpoints.active("parallel.all_to_all=error:times=1", seed=5):
        got = coordinate_distributed(plan, mesh, chunks, foreign,
                                     evaluator=de, stats=stats)
    assert _canon(got.to_rows()) == base
    assert stats.whole_plan == 0                 # served off-rung
    # Every collective dead → the host coordinator still answers.
    with failpoints.active("parallel.all_to_all=error:times=4;"
                           "parallel.gather=error:times=4", seed=6):
        got = coordinate_distributed(plan, mesh, chunks, foreign,
                                     evaluator=de)
    assert _canon(got.to_rows()) == base


def test_planner_order_dependencies_and_barriers():
    """Greedy selectivity order respects column dependencies and LEFT
    joins pin their position."""
    from ytsaurus_tpu.query import planner
    fact = TableSchema.make([("ok", "int64"), ("sk", "int64"),
                             ("v", "int64")])
    orders = TableSchema.make([("o_ok", "int64"), ("o_ck", "int64")])
    cust = TableSchema.make([("c_ck", "int64"), ("c_n", "int64")])
    supp = TableSchema.make([("s_sk", "int64"), ("s_n", "int64")])
    schemas = {"//l": fact, "//o": orders, "//c": cust, "//s": supp}
    plan = build_query(
        "c_n, s_n, sum(v) AS sv FROM [//l] JOIN [//o] ON ok = o_ok "
        "JOIN [//c] ON o_ck = c_ck JOIN [//s] ON sk = s_sk "
        "GROUP BY c_n, s_n", schemas)
    o_chunk = ColumnarChunk.from_arrays(orders, {
        "o_ok": np.arange(10_000), "o_ck": np.arange(10_000) % 500})
    c_chunk = ColumnarChunk.from_arrays(cust, {
        "c_ck": np.arange(500), "c_n": np.arange(500) % 7})
    s_chunk = ColumnarChunk.from_arrays(supp, {
        "s_sk": np.arange(40), "s_n": np.arange(40) % 7})
    jp = planner.plan_for_chunks(plan, 100_000, {
        "//o": o_chunk, "//c": c_chunk, "//s": s_chunk})
    order = jp.order
    # Most selective available join first: tiny supplier beats orders.
    assert order[0] == 2
    # Dependency: customer (needs o_ck from orders) must follow orders.
    assert order.index(1) > order.index(0)
    # LEFT joins are barriers: nothing reorders across them.
    plan_left = build_query(
        "c_n, s_n, v FROM [//l] JOIN [//o] ON ok = o_ok "
        "LEFT JOIN [//c] ON o_ck = c_ck JOIN [//s] ON sk = s_sk",
        schemas)
    jp2 = planner.plan_for_chunks(plan_left, 100_000, {
        "//o": o_chunk, "//c": c_chunk, "//s": s_chunk})
    assert jp2.order == (0, 1, 2)
    # Planner off: no plan (declared order everywhere).
    yt_config.set_compile_config(
        yt_config.CompileConfig(cost_join_planner=False))
    assert planner.plan_for_chunks(plan, 100_000, {
        "//o": o_chunk, "//c": c_chunk, "//s": s_chunk}) is None


def test_planner_broadcast_threshold_and_pushdown():
    from ytsaurus_tpu.query import planner
    fact = TableSchema.make([("ok", "int64"), ("v", "int64")])
    dim = TableSchema.make([("d_ok", "int64"), ("d_w", "int64")])
    schemas = {"//l": fact, "//d": dim}
    plan = build_query("d_w, sum(v) AS sv FROM [//l] "
                       "JOIN [//d] ON ok = d_ok GROUP BY d_w", schemas)
    chunk = ColumnarChunk.from_arrays(dim, {
        "d_ok": np.arange(100, 200), "d_w": np.arange(100)})
    jp = planner.plan_for_chunks(plan, 10_000, {"//d": chunk})
    d = jp.decisions[0]
    assert d.strategy == "broadcast"
    # The INNER side's key range pushes into the scan stage.
    assert d.pushdown == (("ok", 100, 199),)
    iv = planner.pushdown_intervals(
        plan, {"//d": planner.stats_for_chunk(chunk)})
    assert iv["ok"].lo == 100 and iv["ok"].hi == 199
    # A LEFT join must not push (unmatched rows survive).
    plan_l = build_query("d_w, v FROM [//l] LEFT JOIN [//d] "
                         "ON ok = d_ok", schemas)
    assert planner.pushdown_intervals(
        plan_l, {"//d": planner.stats_for_chunk(chunk)}) == {}
    # Over the broadcast row threshold → partition.
    yt_config.set_compile_config(
        yt_config.CompileConfig(broadcast_join_rows=50))
    jp = planner.plan_for_chunks(plan, 10_000, {"//d": chunk})
    assert jp.decisions[0].strategy == "partition"


@pytest.mark.slow
def test_quota_overflow_escalation_and_memo(request):
    """Skewed join keys overflow the optimistic quotas: the query
    re-runs at the demanded rung (correct results) and the settled
    quotas memoize so the next query runs clean."""
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        ShardedTable,
    )
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    fact = TableSchema.make([("k", "int64", "ascending"),
                             ("ok", "int64"), ("v", "int64")])
    dup = TableSchema.make([("d_ok", "int64"), ("d_t", "int64")])
    rng = np.random.default_rng(11)
    per = 256
    chunks = []
    for sh in range(8):
        # ~90% of rows share ONE join key: the hot (src, dst) cell and
        # the hot device's expansion both overflow the uniform estimate.
        ok = np.where(rng.uniform(size=per) < 0.9, 7,
                      rng.integers(0, 64, per))
        chunks.append(ColumnarChunk.from_arrays(fact, {
            "k": np.arange(per) + sh * per, "ok": ok,
            "v": rng.integers(0, 100, per)}))
    table = ShardedTable.from_chunks(mesh, chunks)
    merged = concat_chunks(chunks)
    dup_chunk = ColumnarChunk.from_rows(
        dup, [(k, k * 10 + r) for k in range(64) for r in range(3)])
    foreign = {"//d": dup_chunk}
    plan = build_query(
        "d_t, count(*) AS c FROM [//l] JOIN [//d] ON ok = d_ok "
        "GROUP BY d_t ORDER BY d_t LIMIT 500",
        {"//l": fact, "//d": dup})
    de = DistributedEvaluator(mesh)
    stats = QueryStatistics()
    got = run_whole_plan(de, plan, table, stats=stats,
                         foreign_chunks=foreign)
    want = Evaluator().run_plan(plan, merged, foreign)
    assert got.to_rows() == want.to_rows()
    assert stats.whole_plan_retries >= 1
    stats2 = QueryStatistics()
    got2 = run_whole_plan(de, plan, table, stats=stats2,
                          foreign_chunks=foreign)
    assert stats2.whole_plan_retries == 0
    assert got2.to_rows() == want.to_rows()


def test_stats_drift_flips_strategy_new_program(request):
    """A foreign table growing past the broadcast threshold flips the
    planner's strategy: the fused program recompiles under a NEW key
    (never serves the stale broadcast program) and results stay right."""
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        ShardedTable,
    )
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    fact = TableSchema.make([("k", "int64", "ascending"),
                             ("ok", "int64"), ("v", "int64")])
    dim = TableSchema.make([("d_ok", "int64"), ("d_w", "int64")])
    rng = np.random.default_rng(23)
    per = 128
    chunks = [ColumnarChunk.from_arrays(fact, {
        "k": np.arange(per) + s * per, "ok": rng.integers(0, 64, per),
        "v": rng.integers(0, 100, per)}) for s in range(8)]
    table = ShardedTable.from_chunks(mesh, chunks)
    merged = concat_chunks(chunks)
    plan = build_query("d_w, sum(v) AS sv FROM [//l] JOIN [//d] "
                       "ON ok = d_ok GROUP BY d_w ORDER BY d_w LIMIT 500",
                       {"//l": fact, "//d": dim})
    yt_config.set_compile_config(
        yt_config.CompileConfig(broadcast_join_rows=100))
    de = DistributedEvaluator(mesh)
    local = Evaluator()
    small = ColumnarChunk.from_arrays(dim, {
        "d_ok": np.arange(64), "d_w": np.arange(64)})
    stats = QueryStatistics()
    got = run_whole_plan(de, plan, table, stats=stats,
                         foreign_chunks={"//d": small})
    assert stats.join_plan[0]["strategy"] == "broadcast"
    assert _canon(got.to_rows()) == _canon(
        local.run_plan(plan, merged, {"//d": small}).to_rows())
    # Stable stats: pure cache hit, zero fresh compiles.
    fc = de.fresh_compiles
    run_whole_plan(de, plan, table, foreign_chunks={"//d": small})
    assert de.fresh_compiles == fc
    # The table grows past the threshold: partition strategy, NEW
    # program (fresh compile), still bit-identical to local.
    grown = ColumnarChunk.from_arrays(dim, {
        "d_ok": np.arange(64).repeat(4),
        "d_w": np.arange(256) % 64})
    stats = QueryStatistics()
    got = run_whole_plan(de, plan, table, stats=stats,
                         foreign_chunks={"//d": grown})
    assert stats.join_plan[0]["strategy"] == "partition"
    assert de.fresh_compiles > fc
    assert _canon(got.to_rows()) == _canon(
        local.run_plan(plan, merged, {"//d": grown}).to_rows())


@pytest.mark.slow
def test_fused_join_cross_process_aot_restart(mw_tables, tmp_path):
    """ISSUE 14 acceptance: compile the fused multiway-join program in
    THIS process; a SECOND process over the same artifact dir serves
    the same plan with 0 fresh SPMD compiles (disk hits only)."""
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    mesh, _chunks, table, _merged, foreign = mw_tables
    yt_config.set_compile_config(
        yt_config.CompileConfig(disk_cache_dir=str(tmp_path)))
    plan = build_query(CORPUS[2], SCHEMAS)       # mixed strategies
    de = DistributedEvaluator(mesh)
    want = run_whole_plan(de, plan, table, foreign_chunks=foreign)
    assert de.fresh_compiles >= 1
    script = f"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"
import numpy as np
from ytsaurus_tpu import config as yt_config
yt_config.set_compile_config(yt_config.CompileConfig(
    disk_cache_dir={str(tmp_path)!r}))
from ytsaurus_tpu.chunks import ColumnarChunk
from ytsaurus_tpu.parallel.distributed import DistributedEvaluator, \
    ShardedTable
from ytsaurus_tpu.parallel.mesh import make_mesh
from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.schema import TableSchema

FACT = TableSchema.make([
    ("k", "int64", "ascending"), ("ok", "int64"), ("sk", "int64"),
    ("s", "string"), ("v", "int64")])
DIM = TableSchema.make([("d_ok", "int64"), ("d_w", "int64")])
DUP = TableSchema.make([("u_sk", "int64"), ("u_t", "string")])
SDIM = TableSchema.make([("m_s", "string"), ("m_w", "int64")])
rng = np.random.default_rng(37)
words = [f"w{{i:02d}}" for i in range(13)]
chunks = []
for sh in range(8):
    n = 120 + sh * 9
    rows = []
    for i in range(n):
        rows.append((
            sh * 10_000 + i,
            int(rng.integers(0, 50)) if rng.uniform() > 0.1 else None,
            int(rng.integers(0, 40)),
            words[int(rng.integers(0, 13))],
            int(rng.integers(0, 100))))
    chunks.append(ColumnarChunk.from_rows(FACT, rows))
mesh = make_mesh(8)
table = ShardedTable.from_chunks(mesh, chunks)
dim = ColumnarChunk.from_arrays(DIM, {{
    "d_ok": np.arange(50), "d_w": np.arange(50) * 3 % 7}})
dup_rows = [(key, f"t{{key % 5}}")
            for key in range(40) for _ in range(int(rng.integers(0, 4)))]
dup = ColumnarChunk.from_rows(DUP, dup_rows)
sdim = ColumnarChunk.from_rows(
    SDIM, [(w, i * 10) for i, w in enumerate(words[:9])])
foreign = {{"//d": dim, "//u": dup, "//m": sdim}}
plan = build_query({CORPUS[2]!r},
                   {{"//l": FACT, "//d": DIM, "//u": DUP, "//m": SDIM}})
de = DistributedEvaluator(mesh)
out = run_whole_plan(de, plan, table, foreign_chunks=foreign)
print("CHILD", out.row_count, de.fresh_compiles, de.disk_hits)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    child = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CHILD")][0].split()
    rows, fresh, disk = int(child[1]), int(child[2]), int(child[3])
    assert rows == want.row_count
    assert fresh == 0, \
        "restart leg must serve the fused join plan from disk"
    assert disk >= 1


# --- NDV sketch -----------------------------------------------------------


def test_ndv_sketch_estimate_merge_and_bounds():
    from ytsaurus_tpu.chunks.columnar import (
        chunk_column_stats,
        merge_column_stats,
        ndv_estimate,
    )
    schema = TableSchema.make([("k", "int64"), ("s", "string"),
                               ("d", "double")])
    rng = np.random.default_rng(0)
    rows = [(int(rng.integers(0, 1000)),
             f"w{int(rng.integers(0, 50)):03d}",
             float(rng.uniform())) for _ in range(5000)]
    chunk = ColumnarChunk.from_rows(schema, rows)
    stats = chunk_column_stats(chunk)
    exact_k = len({r[0] for r in rows})
    est_k = ndv_estimate(stats["k"]["ndv_sketch"])
    # HLL with 64 registers: ~13% standard error; allow 3 sigma.
    assert abs(est_k - exact_k) / exact_k < 0.4
    assert abs(ndv_estimate(stats["s"]["ndv_sketch"]) - 50) <= 15
    # Merge of two halves == whole (register max is exact for unions).
    a = chunk.slice_rows(0, 2500)
    b = chunk.slice_rows(2500, 5000)
    merged = merge_column_stats(
        [chunk_column_stats(a), chunk_column_stats(b)])
    assert merged["k"]["ndv_sketch"] == stats["k"]["ndv_sketch"]
    assert merged["$row_count"] == 5000
    assert merged["k"]["min"] == stats["k"]["min"]
    # Payload stays fixed-size no matter the data.
    assert len(stats["k"]["ndv_sketch"]) == 64


def test_stats_payload_stays_bounded_with_huge_strings():
    """The PR 5 hunk-externalization regression must not recur: sealing
    stats (now including sketches) into meta never re-inlines data-
    sized payloads — meta stays small for a chunk of multi-KB strings."""
    from ytsaurus_tpu.chunks.encoding import serialize_chunk
    from ytsaurus_tpu.utils.varint import read_varint_u
    schema = TableSchema.make([("k", "int64"), ("blob", "string")])
    rows = [(i, bytes([65 + i % 26]) * 4096) for i in range(64)]
    chunk = ColumnarChunk.from_rows(schema, rows)
    blob = serialize_chunk(chunk)
    meta_len, _pos = read_varint_u(blob, 4)
    # 64 x 4KB values ≈ 256KB of data; the meta header (schema + stats
    # incl. two 64-byte sketches + capped string bounds) stays tiny.
    assert meta_len < 8192, meta_len


def test_read_stats_backfills_missing_sketch(tmp_path):
    """Chunks sealed BEFORE the sketch existed decode once and
    recompute the full payload (the PR 4 read_stats memo discipline)."""
    from ytsaurus_tpu import yson
    from ytsaurus_tpu.chunks.encoding import (
        MAGIC,
        read_chunk_meta,
        serialize_chunk,
    )
    from ytsaurus_tpu.chunks.store import FsChunkStore
    from ytsaurus_tpu.utils.varint import encode_varint_u
    store = FsChunkStore(str(tmp_path))
    schema = TableSchema.make([("k", "int64")])
    chunk = ColumnarChunk.from_rows(schema, [{"k": 5}, {"k": 9}])
    blob = serialize_chunk(chunk)
    meta = read_chunk_meta(blob)
    data_start = meta.pop("_data_start")
    for entry in meta["column_stats"].values():
        if isinstance(entry, dict):
            entry.pop("ndv_sketch", None)       # pre-sketch format
    meta_blob = yson.dumps(meta, binary=True)
    legacy = b"".join([MAGIC, encode_varint_u(len(meta_blob)), meta_blob,
                       blob[data_start:]])
    cid = store.put_blob("ab" + "0" * 30, legacy)
    assert "ndv_sketch" not in \
        store.read_meta(cid)["column_stats"]["k"]
    # Default read: metadata-only consumers ($timestamp, bounds
    # pruning) get the sealed stats with NO chunk decode.
    sealed = store.read_stats(cid)
    assert sealed["k"]["min"] == 5
    assert "ndv_sketch" not in sealed["k"]
    # Planner-fold opt-in: decode-backfill computes the full payload.
    stats = store.read_stats(cid, backfill_sketch=True)
    assert stats["k"].get("ndv_sketch") is not None
    from ytsaurus_tpu.chunks.columnar import ndv_estimate
    assert ndv_estimate(stats["k"]["ndv_sketch"]) >= 1
    # Memoized and upgraded in place: every later reader serves the
    # backfilled payload, the decode happened once.
    assert store.read_stats(cid) is stats
    assert store.read_stats(cid, backfill_sketch=True) is stats


# --- EXPLAIN ANALYZE + client pushdown ------------------------------------


def test_explain_analyze_renders_join_plan():
    from ytsaurus_tpu.query.profile import format_profile_dict
    stats = QueryStatistics(whole_plan=1)
    stats.note_join_stage(0, "//dim", "broadcast", est_rows=1000,
                          actual_rows=950)
    stats.note_join_stage(1, "//orders", "partition", est_rows=5000,
                          actual_rows=7100)
    text = format_profile_dict({"statistics": stats.to_dict()})
    assert "join plan:" in text
    assert "1. //dim [broadcast] est rows 1000 -> actual 950" in text
    assert "2. //orders [partition] est rows 5000 -> actual 7100" in text
    cold = format_profile_dict(
        {"statistics": QueryStatistics().to_dict()})
    assert "join plan" not in cold


def test_client_prunes_shards_via_join_pushdown(tmp_path):
    """End to end through the client: a selective dimension's key range
    (off sealed chunk-stats metadata) prunes source shards whose key
    range cannot join anything — before staging."""
    from ytsaurus_tpu.client import YtClient, YtCluster
    client = YtClient(YtCluster(str(tmp_path / "cluster")))
    fact_schema = TableSchema.make([("ok", "int64"), ("v", "int64")])
    dim_schema = TableSchema.make([("d_ok", "int64"), ("d_w", "int64")])
    # Three fact shards with DISJOINT key ranges; the dim only joins
    # the middle range.
    for lo in (0, 1000, 2000):
        client.write_table("//fact", [
            {"ok": lo + i, "v": i} for i in range(100)],
            schema=fact_schema,
            append=lo > 0)
    client.write_table("//dim", [
        {"d_ok": 1000 + i, "d_w": i} for i in range(100)],
        schema=dim_schema)
    stats_attr = client.get("//fact/@chunk_stats")
    assert len(stats_attr) == 3
    rows = client.select_rows(
        "d_w, sum(v) AS sv FROM [//fact] JOIN [//dim] ON ok = d_ok "
        "GROUP BY d_w ORDER BY d_w LIMIT 500")
    want = {(i, i) for i in range(100)}
    assert {(r["d_w"], r["sv"]) for r in rows} == want
    stats = client.last_query_statistics
    # Two of three fact shards pruned off the pushed-down key range.
    assert stats.shards_pruned == 2
    # A legacy placeholder in the dim's @chunk_stats ({} — sealed
    # before stats existed) makes its key range UNKNOWN: pushdown must
    # stand down entirely (pruning off the remaining chunks' bounds
    # would drop rows joining the legacy chunk).
    dim_stats = client.get("//dim/@chunk_stats")
    client.set("//dim/@chunk_stats", [{}] + list(dim_stats)[1:])
    rows = client.select_rows(
        "d_w, sum(v) AS sv FROM [//fact] JOIN [//dim] ON ok = d_ok "
        "GROUP BY d_w ORDER BY d_w LIMIT 500")
    assert {(r["d_w"], r["sv"]) for r in rows} == want
    assert client.last_query_statistics.shards_pruned == 0
    client.set("//dim/@chunk_stats", dim_stats)
    # Pushdown off → no pruning, same rows.
    yt_config.set_compile_config(
        yt_config.CompileConfig(cost_join_planner=False))
    rows = client.select_rows(
        "d_w, sum(v) AS sv FROM [//fact] JOIN [//dim] ON ok = d_ok "
        "GROUP BY d_w ORDER BY d_w LIMIT 500")
    assert {(r["d_w"], r["sv"]) for r in rows} == want
    assert client.last_query_statistics.shards_pruned == 0
