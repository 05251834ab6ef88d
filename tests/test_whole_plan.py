"""Whole-plan fused SPMD execution (ISSUE 12): the entire distributed
query as ONE jit(shard_map) program on the virtual 8-device mesh.

Quick tier-1 coverage: dual-check over one representative per fused
SHAPE (CORPUS_QUICK), the single-host-sync contract, the fusion gate +
unfusable-plan ladder fallback, exchange-quota overflow escalation +
memoization, the partition-rule registry, and the in-process SPMD AOT
disk tier.  The full post-stage/alias/key corpus (over 3 random
tables), the failpoint-injected collective-fault ladder, mesh resize,
and the cross-process restart leg live behind `slow` in this module
(test_dual_check_randomized_sweep et al.) so the quick pass fits the
tier-1 870s budget.
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
from ytsaurus_tpu.utils import failpoints

SCHEMA = TableSchema.make([
    ("k", "int64", "ascending"), ("g", "int64"), ("s", "string"),
    ("v", "int64"), ("d", "double")])
T = "//t"

# The dual-check plan corpus: every fused shape (exchange-states,
# exchange-rows, gather) across the q1/groupby/window/topk classes.
CORPUS = [
    # q1 class: multi-aggregate GROUP BY over few groups.
    "g, sum(v) AS sv, count(*) AS c, avg(d) AS a, min(v) AS mn, "
    "max(v) AS mx FROM [//t] GROUP BY g",
    # groupby class: WHERE + HAVING + ORDER + LIMIT on top.
    "g, sum(v) AS sv FROM [//t] WHERE v > 100 GROUP BY g "
    "HAVING count(*) > 2 ORDER BY g LIMIT 500",
    # string group keys ride the unified vocabulary.
    "s, sum(v) AS sv, count(*) AS c FROM [//t] GROUP BY s "
    "ORDER BY s LIMIT 100",
    # argmin/argmax decompose into mergeable states.
    "g, argmax(k, d) AS am, argmin(k, d) AS an FROM [//t] GROUP BY g "
    "ORDER BY g LIMIT 500",
    # ORDER BY avg(): the front substitutes the avg alias into its
    # sum/count state columns — the merge must agree with local.
    "g, avg(d) AS a FROM [//t] GROUP BY g ORDER BY avg(d) DESC LIMIT 5",
    # Expression group keys route by the EVALUATED key slot.
    "g + 1 AS gg, sum(v * 2) AS sv FROM [//t] WHERE d < 8.0 "
    "GROUP BY g + 1 ORDER BY g + 1 LIMIT 100",
    # cardinality cannot merge from states → exchange-rows shape.
    "g, cardinality(s) AS cd, count(*) AS c FROM [//t] GROUP BY g "
    "ORDER BY g LIMIT 500",
    # window class: co-partitioned exact windows → exchange-rows shape.
    "k, v, sum(v) OVER (PARTITION BY g ORDER BY k) AS rs, "
    "rank() OVER (PARTITION BY g ORDER BY k) AS rk FROM [//t] "
    "ORDER BY k LIMIT 200",
    # topk class: gather shape with the per-shard top-k bottom.
    "k, d FROM [//t] ORDER BY d DESC LIMIT 9",
    # plain filter scan: gather shape.
    "k, v FROM [//t] WHERE v > 900",
]

# Quick-tier subset: one representative per fused SHAPE (exchange-states
# multi-agg, cardinality exchange-rows, window exchange-rows, top-k
# gather, filter gather).  Each corpus query costs a full 8-device
# shard_map compile (~6s on CPU); the remaining post-stage/alias/key
# variants of the exchange-states shape run under `slow` in
# test_dual_check_randomized_sweep, which sweeps the FULL corpus over
# 3 random tables.
CORPUS_QUICK = [CORPUS[0], CORPUS[6], CORPUS[7], CORPUS[8], CORPUS[9]]


@pytest.fixture(autouse=True)
def _fresh_compile_config():
    yield
    yt_config.set_compile_config(None)


@pytest.fixture(scope="module")
def table8(request):
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import ShardedTable
    rng = np.random.default_rng(21)
    words = [f"w{i:02d}" for i in range(13)]
    chunks = []
    for sh in range(8):
        n = 150 + sh * 11
        rows = [(sh * 10_000 + i, int(rng.integers(0, 40)),
                 words[int(rng.integers(0, 13))],
                 int(rng.integers(0, 1000)), float(rng.uniform(0, 10)))
                for i in range(n)]
        chunks.append(ColumnarChunk.from_rows(SCHEMA, rows))
    table = ShardedTable.from_chunks(mesh, chunks)
    return mesh, chunks, table, concat_chunks(chunks)


def _canon(rows):
    """Order-insensitive row canon: ints/strings bit-exact, floats to
    1e-9 (partial-state merges sum in a different order than the local
    single pass — same discipline as test_distributed).  NULLs encode
    as a sortable rank so null-keyed rows canonicalize too."""
    def norm(v):
        if v is None:
            return (0, 0)
        return (1, round(v, 9) if isinstance(v, float) else v)

    out = []
    for r in rows:
        out.append(tuple((k, norm(v)) for k, v in sorted(r.items())))
    return sorted(out)


def _canon_ordered(rows):
    """Position-sensitive canon for totally-ordered outputs."""
    def norm(v):
        if v is None:
            return (0, 0)
        return (1, round(v, 9) if isinstance(v, float) else v)

    return [tuple((k, norm(v)) for k, v in sorted(r.items()))
            for r in rows]


def test_dual_check_corpus(table8):
    """Fused whole-plan vs the local evaluator over the full corpus,
    with exactly ONE host sync per fused query."""
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        host_sync_count,
    )
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    mesh, _chunks, table, merged = table8
    de = DistributedEvaluator(mesh)
    local = Evaluator()
    for query in CORPUS_QUICK:
        plan = build_query(query, {T: SCHEMA})
        stats = QueryStatistics()
        s0 = host_sync_count()
        got = run_whole_plan(de, plan, table, stats=stats)
        assert host_sync_count() - s0 == 1, query
        assert stats.whole_plan == 1
        want = local.run_plan(plan, merged)
        if plan.order is not None:
            # Every ordered corpus query sorts by a key that is UNIQUE
            # in its output (group keys post-group, unique k, random
            # doubles), so positions must match exactly — compare the
            # canon WITHOUT the order-insensitive final sort.
            assert _canon_ordered(got.to_rows()) == \
                _canon_ordered(want.to_rows()), query
        assert _canon(got.to_rows()) == _canon(want.to_rows()), query


def test_stitched_shuffle_rung_pays_more_than_one_sync(table8):
    """What the fused rung is compared with: the same GROUP BY with
    `whole_plan` off goes down the stitched shuffle rung, which reads
    counts back between its programs — two host syncs a query or more,
    against the fused rung's one — and answers the same rows."""
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        coordinate_distributed,
        host_sync_count,
    )
    mesh, chunks, _table, merged = table8
    plan = build_query(CORPUS[0], {T: SCHEMA})
    yt_config.set_compile_config(yt_config.CompileConfig(whole_plan=False))
    de = DistributedEvaluator(mesh)
    stats = QueryStatistics()
    coordinate_distributed(plan, mesh, chunks, evaluator=de,
                           prefer_shuffle=True, stats=stats)   # warm-up
    assert stats.whole_plan == 0
    s0 = host_sync_count()
    got = coordinate_distributed(plan, mesh, chunks, evaluator=de,
                                 prefer_shuffle=True)
    assert host_sync_count() - s0 >= 2
    want = Evaluator().run_plan(plan, merged)
    assert _canon(got.to_rows()) == _canon(want.to_rows())


def test_repeat_query_compiles_nothing(table8):
    """Steady state: a repeated fused query is a pure cache hit — zero
    fresh compiles, zero overflow retries (the quota memo settled)."""
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    mesh, _chunks, table, merged = table8
    de = DistributedEvaluator(mesh)
    plan = build_query(CORPUS[0], {T: SCHEMA})
    run_whole_plan(de, plan, table)
    fc = de.fresh_compiles
    stats = QueryStatistics()
    got = run_whole_plan(de, plan, table, stats=stats)
    assert de.fresh_compiles == fc
    assert stats.whole_plan_retries == 0
    assert _canon(got.to_rows()) == \
        _canon(Evaluator().run_plan(plan, merged).to_rows())


def test_unfusable_plans_fall_to_stitched_ladder(table8):
    """WITH TOTALS stays on the stitched rungs; join plans fuse since
    ISSUE 14 — but one with NO foreign data still degrades cleanly, and
    the fused join result matches the local evaluator."""
    from dataclasses import replace as dc_replace

    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        coordinate_distributed,
    )
    from ytsaurus_tpu.parallel.whole_plan import can_fuse, run_whole_plan
    from ytsaurus_tpu.errors import YtError
    mesh, chunks, table, merged = table8
    dim_schema = TableSchema.make([("dk", "int64", "ascending"),
                                   ("name", "int64")])
    dim = ColumnarChunk.from_arrays(dim_schema, {
        "dk": np.arange(0, 80, 2), "name": np.arange(40) * 10})
    plan = build_query("g, name, sum(v) AS sv FROM [//t] "
                       "JOIN [//d] ON g = dk GROUP BY g, name",
                       {T: SCHEMA, "//d": dim_schema})
    # Joins fuse now (ISSUE 14) — missing foreign data raises, and the
    # ladder serves the query off-rung.
    assert can_fuse(plan) is None
    de = DistributedEvaluator(mesh)
    with pytest.raises(YtError):
        run_whole_plan(de, plan, table)         # no foreign chunks
    stats = QueryStatistics()
    got = coordinate_distributed(plan, mesh, chunks, {"//d": dim},
                                 evaluator=de, stats=stats)
    want = Evaluator().run_plan(plan, merged, {"//d": dim})
    assert _canon(got.to_rows()) == _canon(want.to_rows())
    assert stats.whole_plan == 1               # fused join rung served it
    # WITH TOTALS: gated (eager two-rowset concat), reason names it.
    gplan = build_query("g, sum(v) AS sv FROM [//t] GROUP BY g",
                        {T: SCHEMA})
    totals_plan = dc_replace(
        gplan, group=dc_replace(gplan.group, totals=True))
    assert "TOTALS" in can_fuse(totals_plan)


@pytest.mark.slow
def test_failpoint_fault_lands_on_stitched_ladder(table8):
    """A failpoint-injected `parallel.all_to_all` fault knocks the fused
    rung (and the stitched shuffle) out; the ladder still serves the
    query bit-identically — and with every collective dead, the host
    coordinator answers."""
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        coordinate_distributed,
    )
    mesh, chunks, table, merged = table8
    de = DistributedEvaluator(mesh)
    plan = build_query(CORPUS[0], {T: SCHEMA})
    baseline = _canon(coordinate_distributed(
        plan, mesh, chunks, evaluator=de).to_rows())
    assert baseline == _canon(Evaluator().run_plan(plan, merged).to_rows())
    stats = QueryStatistics()
    with failpoints.active("parallel.all_to_all=error:times=1", seed=3):
        got = coordinate_distributed(plan, mesh, chunks, evaluator=de,
                                     stats=stats)
    assert _canon(got.to_rows()) == baseline
    assert stats.whole_plan == 0       # served off-rung
    with failpoints.active("parallel.all_to_all=error:times=4;"
                           "parallel.gather=error:times=4", seed=4):
        got = coordinate_distributed(plan, mesh, chunks, evaluator=de)
    assert _canon(got.to_rows()) == baseline


def test_overflow_escalation_and_quota_memo(request):
    """Skewed routing keys overflow the optimistic static quota: the
    query re-runs at the demanded pow2 rung (correct results), and the
    settled quota memoizes so the NEXT query runs clean."""
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    schema = TableSchema.make([("k", "int64", "ascending"),
                               ("g", "int64"), ("v", "int64")])
    rng = np.random.default_rng(5)
    chunks = []
    for sh in range(8):
        n = 256
        # ~90% of rows share one partition key → one (src, dst) cell
        # holds most of a shard.
        g = np.where(rng.uniform(size=n) < 0.9, 7,
                     rng.integers(0, 32, n))
        chunks.append(ColumnarChunk.from_arrays(schema, {
            "k": np.arange(n) + sh * n, "g": g,
            "v": rng.integers(0, 100, n)}))
    from ytsaurus_tpu.parallel.distributed import ShardedTable
    table = ShardedTable.from_chunks(mesh, chunks)
    merged = concat_chunks(chunks)
    de = DistributedEvaluator(mesh)
    plan = build_query(
        "k, sum(v) OVER (PARTITION BY g) AS s FROM [//t] "
        "ORDER BY k LIMIT 100", {T: schema})
    stats = QueryStatistics()
    got = run_whole_plan(de, plan, table, stats=stats)
    want = Evaluator().run_plan(plan, merged)
    assert got.to_rows() == want.to_rows()
    assert stats.whole_plan_retries >= 1
    assert de._quota_memo, "settled quota must memoize"
    stats2 = QueryStatistics()
    got2 = run_whole_plan(de, plan, table, stats=stats2)
    assert stats2.whole_plan_retries == 0
    assert got2.to_rows() == want.to_rows()


def test_partition_rule_registry(table8):
    """The registry is consulted for real: stage names resolve through
    match_partition_rules, a registry that misplaces a stage fails
    loudly, and the rules digest is a cache-key axis."""
    from jax.sharding import PartitionSpec as P

    from ytsaurus_tpu.errors import YtError
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    from ytsaurus_tpu.parallel.mesh import SHARD_AXIS
    from ytsaurus_tpu.parallel.whole_plan import (
        DEFAULT_PARTITION_RULES,
        match_partition_rules,
        rules_fingerprint,
        run_whole_plan,
    )
    mesh, _chunks, table, _merged = table8
    assert match_partition_rules(DEFAULT_PARTITION_RULES, "scan/k") == \
        P(SHARD_AXIS)
    assert match_partition_rules(DEFAULT_PARTITION_RULES,
                                 "shuffle/group") == P(SHARD_AXIS)
    assert match_partition_rules(DEFAULT_PARTITION_RULES, "front") == P()
    with pytest.raises(YtError):
        match_partition_rules(DEFAULT_PARTITION_RULES, "nonsense-stage")
    # A first-hit override ahead of the defaults changes placement —
    # and misplaces the front merge, which must fail loudly (the
    # coordinate_distributed ladder would then degrade to stitched).
    bad = ((r"^front$", P(SHARD_AXIS)),) + DEFAULT_PARTITION_RULES
    plan = build_query(CORPUS[0], {T: SCHEMA})
    de = DistributedEvaluator(mesh)
    with pytest.raises(YtError, match="partition rules place stage"):
        run_whole_plan(de, plan, table, rules=bad)
    assert rules_fingerprint(bad) != \
        rules_fingerprint(DEFAULT_PARTITION_RULES)


@pytest.mark.slow
def test_mesh_resize_is_a_cache_fill(request, tmp_path):
    """Elastic fleet: the mesh shape is a cache-key axis, so resizing
    8 → 4 devices compiles fresh rungs once and a restarted evaluator
    on the SAME disk tier serves the resized mesh with zero fresh
    compiles."""
    request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        ShardedTable,
    )
    from ytsaurus_tpu.parallel.mesh import make_mesh
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    yt_config.set_compile_config(
        yt_config.CompileConfig(disk_cache_dir=str(tmp_path)))
    schema = TableSchema.make([("k", "int64", "ascending"),
                               ("g", "int64"), ("v", "int64")])
    plan = build_query("g, sum(v) AS sv, count(*) AS c FROM [//t] "
                       "GROUP BY g", {T: schema})

    def shards(n):
        return [ColumnarChunk.from_arrays(schema, {
            "k": np.arange(64) + sh * 64,
            "g": (np.arange(64) + sh) % 7,
            "v": np.arange(64) * 3}) for sh in range(n)]

    want = _canon(Evaluator().run_plan(
        plan, concat_chunks(shards(8))).to_rows())
    for n in (8, 4):
        mesh = make_mesh(n)
        table = ShardedTable.from_chunks(mesh, shards(n))
        de = DistributedEvaluator(mesh)
        got = run_whole_plan(de, plan, table)
        assert de.fresh_compiles >= 1      # a new mesh shape = new rung
        if n == 8:
            assert _canon(got.to_rows()) == want
        # Restarted evaluator, same mesh shape, same disk dir: pure
        # cache fill — 0 fresh compiles.
        de2 = DistributedEvaluator(mesh)
        got2 = run_whole_plan(de2, plan, table)
        assert de2.fresh_compiles == 0 and de2.disk_hits >= 1
        assert _canon(got2.to_rows()) == _canon(got.to_rows())


def test_stitched_spmd_caches_ride_the_disk_tier(table8, tmp_path):
    """ISSUE 12 satellite: the surviving stitched-path program caches
    (finish / shuffled / shuffled-count) serialize too — a fresh
    evaluator over the same artifact dir re-runs both rungs with zero
    fresh SPMD compiles."""
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    mesh, _chunks, table, merged = table8
    yt_config.set_compile_config(
        yt_config.CompileConfig(disk_cache_dir=str(tmp_path)))
    plan = build_query("g, sum(v) AS sv, count(*) AS c FROM [//t] "
                       "GROUP BY g", {T: SCHEMA})
    de = DistributedEvaluator(mesh)
    a = de.run(plan, table, shuffle=True)
    b = de.run(plan, table, shuffle=False)
    assert de.fresh_compiles >= 3          # count + exchange + finish
    de2 = DistributedEvaluator(mesh)
    a2 = de2.run(plan, table, shuffle=True)
    b2 = de2.run(plan, table, shuffle=False)
    assert de2.fresh_compiles == 0, \
        "restart must serve every stitched SPMD program from disk"
    assert de2.disk_hits >= 3
    assert _canon(a2.to_rows()) == _canon(a.to_rows())
    assert _canon(b2.to_rows()) == _canon(b.to_rows())
    want = _canon(Evaluator().run_plan(plan, merged).to_rows())
    assert _canon(a.to_rows()) == want and _canon(b.to_rows()) == want


@pytest.mark.slow
def test_cross_process_spmd_restart(table8, tmp_path):
    """ISSUE 12 acceptance: compile the fused whole-plan program in THIS
    process, then a SECOND process over the same artifact dir serves the
    same plan with 0 fresh SPMD compiles (disk hits only)."""
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    mesh, _chunks, table, merged = table8
    yt_config.set_compile_config(
        yt_config.CompileConfig(disk_cache_dir=str(tmp_path)))
    plan = build_query(CORPUS[0], {T: SCHEMA})
    de = DistributedEvaluator(mesh)
    want = run_whole_plan(de, plan, table)
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

SCHEMA = TableSchema.make([
    ("k", "int64", "ascending"), ("g", "int64"), ("s", "string"),
    ("v", "int64"), ("d", "double")])
rng = np.random.default_rng(21)
words = [f"w{{i:02d}}" for i in range(13)]
chunks = []
for sh in range(8):
    n = 150 + sh * 11
    rows = [(sh * 10_000 + i, int(rng.integers(0, 40)),
             words[int(rng.integers(0, 13))],
             int(rng.integers(0, 1000)), float(rng.uniform(0, 10)))
            for i in range(n)]
    chunks.append(ColumnarChunk.from_rows(SCHEMA, rows))
mesh = make_mesh(8)
table = ShardedTable.from_chunks(mesh, chunks)
plan = build_query({CORPUS[0]!r}, {{"//t": SCHEMA}})
de = DistributedEvaluator(mesh)
out = run_whole_plan(de, plan, table)
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
    assert fresh == 0, "restart leg must serve the fused plan from disk"
    assert disk >= 1


@pytest.mark.slow
def test_dual_check_randomized_sweep(request):
    """Deeper corpus: 3 random tables (fresh vocabularies, null keys,
    negative values) × the full plan corpus, fused vs local — the
    minutes-long variant of test_dual_check_corpus."""
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        ShardedTable,
    )
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    local = Evaluator()
    for seed in (101, 202, 303):
        rng = np.random.default_rng(seed)
        words = [f"t{i:03d}" for i in range(int(rng.integers(3, 50)))]
        chunks = []
        for sh in range(8):
            n = int(rng.integers(40, 400))
            rows = []
            for i in range(n):
                rows.append((
                    sh * 100_000 + i,
                    int(rng.integers(-50, 50))
                    if rng.uniform() > 0.05 else None,
                    words[int(rng.integers(0, len(words)))],
                    int(rng.integers(-1000, 1000)),
                    float(rng.uniform(-5, 5))))
            chunks.append(ColumnarChunk.from_rows(SCHEMA, rows))
        table = ShardedTable.from_chunks(mesh, chunks)
        merged = concat_chunks(chunks)
        de = DistributedEvaluator(mesh)
        for query in CORPUS:
            plan = build_query(query, {T: SCHEMA})
            got = run_whole_plan(de, plan, table)
            want = local.run_plan(plan, merged)
            assert _canon(got.to_rows()) == _canon(want.to_rows()), \
                (seed, query)


def test_explain_analyze_renders_whole_plan_flag():
    from ytsaurus_tpu.query.profile import format_profile_dict
    stats = QueryStatistics(whole_plan=1, whole_plan_retries=1)
    text = format_profile_dict({"statistics": stats.to_dict()})
    assert "whole-plan fused SPMD" in text
    assert "overflow retries 1" in text
    cold = format_profile_dict(
        {"statistics": QueryStatistics().to_dict()})
    assert "whole-plan" not in cold


# -- mesh telemetry (ISSUE 20) -------------------------------------------------


def _oracle_pids(values, n: int):
    """Destination shard per row via the SAME canonical-hash helpers the
    fused program routes with (`whole_plan._dest_hash`), applied OUTSIDE
    shard_map on the raw numpy column — an independent recomputation in
    the dual-check discipline."""
    import jax.numpy as jnp

    from ytsaurus_tpu.parallel.distributed import _canonical_hash_plane
    from ytsaurus_tpu.query.engine.expr import _combine_u64, _mix_u64
    acc = jnp.full(len(values), np.uint64(0x9E3779B97F4A7C15),
                   dtype=jnp.uint64)
    h = _mix_u64(_canonical_hash_plane(
        jnp.asarray(values, dtype=jnp.int64)))
    acc = _combine_u64(acc, h)
    return np.asarray(acc % np.uint64(n)).astype(int)


def test_mesh_telemetry_block_matches_numpy_oracle(request):
    """ISSUE 20 acceptance: the telemetry block decoded from the ONE
    stacked final transfer is bit-identical to a host-side oracle — live
    input rows per shard, per-shard output rows, and the full all_to_all
    transfer-count matrix recomputed with numpy + the canonical hash
    outside shard_map — and arming telemetry still costs exactly one
    host sync per query on the 8-device mesh."""
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        ShardedTable,
        host_sync_count,
    )
    from ytsaurus_tpu.parallel.whole_plan import (
        MESH_TELEMETRY_VERSION,
        run_whole_plan,
    )
    schema = TableSchema.make([("k", "int64", "ascending"),
                               ("g", "int64"), ("v", "int64")])
    rng = np.random.default_rng(11)
    sizes = [40 + 9 * sh for sh in range(8)]
    g_cols, v_cols, chunks = [], [], []
    for sh, rows in enumerate(sizes):
        g = rng.integers(0, 12, rows)
        v = rng.integers(0, 1000, rows)
        g_cols.append(g)
        v_cols.append(v)
        chunks.append(ColumnarChunk.from_arrays(schema, {
            "k": np.arange(rows) + sh * 10_000, "g": g, "v": v}))
    table = ShardedTable.from_chunks(mesh, chunks)
    merged = concat_chunks(chunks)
    de = DistributedEvaluator(mesh)

    # Gather shape: in_rows = live rows, out_rows = per-shard filter
    # survivors, no exchanges.
    plan = build_query("k, v FROM [//t] WHERE v > 500", {T: schema})
    stats = QueryStatistics()
    s0 = host_sync_count()
    got = run_whole_plan(de, plan, table, stats=stats)
    assert host_sync_count() - s0 == 1
    [block] = stats.mesh_blocks
    want_out = [int((v > 500).sum()) for v in v_cols]
    assert block["version"] == MESH_TELEMETRY_VERSION
    assert block["path"] == "fused" and block["shards"] == 8
    assert block["in_rows"] == sizes
    assert block["out_rows"] == want_out
    assert block["skew"] == round(max(want_out) / (sum(want_out) / 8), 4)
    assert block["exchanges"] == [] and block["exchange_bytes"] == 0
    assert got.row_count == sum(want_out)
    assert stats.mesh_skew_max == block["skew"]

    # Exchange-rows shape (window): the routed transfer-count matrix is
    # the canonical key hash of the PARTITION BY column, shard-major.
    planw = build_query(
        "k, v, sum(v) OVER (PARTITION BY g ORDER BY k) AS rs "
        "FROM [//t] ORDER BY k LIMIT 64", {T: schema})
    statsw = QueryStatistics()
    s0 = host_sync_count()
    goww = run_whole_plan(de, planw, table, stats=statsw)
    assert statsw.whole_plan_retries == 0
    assert host_sync_count() - s0 == 1
    [blockw] = statsw.mesh_blocks
    matrix = np.zeros((8, 8), dtype=int)
    for sh in range(8):
        matrix[sh] = np.bincount(_oracle_pids(g_cols[sh], 8),
                                 minlength=8)
    [entry] = blockw["exchanges"]
    assert entry["stage"] == "shuffle/exchange-rows"
    assert entry["matrix"] == matrix.reshape(-1).tolist()
    assert entry["rows"] == int(matrix.sum())
    assert entry["demand"] == int(matrix.max())
    assert entry["quota"] >= entry["demand"]
    assert entry["headroom"] == round(matrix.max() / entry["quota"], 4)
    # Routed rowset = the k/g/v int64 planes: (8 data + 1 validity) × 3.
    assert entry["bytes"] == int(matrix.sum()) * 27
    assert blockw["exchange_bytes"] == entry["bytes"]
    assert blockw["in_rows"] == sizes
    # The window local stage emits one row per received row, so the
    # per-destination output spread IS the matrix column sums.
    assert blockw["out_rows"] == matrix.sum(axis=0).tolist()
    assert _canon_ordered(goww.to_rows()) == _canon_ordered(
        Evaluator().run_plan(planw, merged).to_rows())


def test_mesh_telemetry_disarm_is_free_and_bit_identical(table8):
    """Disarming mesh telemetry compiles a fresh program (the armed bit
    is a cache-key axis), still costs exactly one host sync, publishes
    nothing — and the query result is bit-identical either way."""
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        host_sync_count,
    )
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    mesh, _chunks, table, merged = table8
    de = DistributedEvaluator(mesh)
    plan = build_query(CORPUS[0], {T: SCHEMA})
    try:
        stats_on = QueryStatistics()
        s0 = host_sync_count()
        armed_out = run_whole_plan(de, plan, table, stats=stats_on)
        assert host_sync_count() - s0 == 1
        assert len(stats_on.mesh_blocks) == 1
        assert stats_on.mesh_skew_max >= 1.0
        assert stats_on.mesh_exchange_bytes > 0
        yt_config.set_telemetry_config(
            yt_config.TelemetryConfig(mesh_telemetry=False))
        stats_off = QueryStatistics()
        s0 = host_sync_count()
        plain_out = run_whole_plan(de, plan, table, stats=stats_off)
        assert host_sync_count() - s0 == 1
        assert stats_off.mesh_blocks == []
        assert stats_off.mesh_skew_max == 0.0
    finally:
        yt_config.set_telemetry_config(None)
    want = _canon(Evaluator().run_plan(plan, merged).to_rows())
    assert _canon(armed_out.to_rows()) == want
    assert _canon(plain_out.to_rows()) == want


def test_stitched_rungs_report_the_same_block_shape(table8):
    """The stitched shuffle path assembles the SAME versioned block from
    host values it already read (path="stitched"), with the transfer
    matrix agreeing with the canonical-hash oracle — zero additional
    device reads."""
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    from ytsaurus_tpu.parallel.whole_plan import MESH_TELEMETRY_VERSION
    mesh, chunks, table, _merged = table8
    de = DistributedEvaluator(mesh)
    plan = build_query("g, sum(v) AS sv FROM [//t] GROUP BY g",
                       {T: SCHEMA})
    stats = QueryStatistics()
    de.run(plan, table, shuffle=True, stats=stats)
    assert stats.mesh_blocks, "stitched shuffle must publish a block"
    block = stats.mesh_blocks[0]
    assert block["version"] == MESH_TELEMETRY_VERSION
    assert block["path"] == "stitched" and block["shards"] == 8
    assert block["in_rows"] == [c.row_count for c in chunks]
    [entry] = block["exchanges"]
    assert entry["stage"] == "shuffle/stitched"
    assert sum(entry["matrix"]) == entry["rows"] > 0
    assert entry["quota"] >= entry["demand"] == max(entry["matrix"])


def test_explain_analyze_renders_mesh_telemetry():
    from ytsaurus_tpu.query.profile import format_profile_dict
    stats = QueryStatistics(whole_plan=1)
    stats.note_mesh_block({
        "version": 1, "path": "fused", "shards": 4,
        "in_rows": [10, 10, 10, 10], "out_rows": [2, 3, 4, 11],
        "skew": 2.2, "exchange_bytes": 540,
        "exchanges": [{"stage": "shuffle/group", "rows": 20,
                       "bytes": 540, "demand": 11, "quota": 16,
                       "headroom": 0.6875}],
        "memory_watermark_bytes": 4096})
    text = format_profile_dict({"statistics": stats.to_dict()})
    assert "mesh telemetry:" in text
    assert "rows/shard min 2 / median 4 / max 11  skew 2.2" in text
    assert "exchange shuffle/group: 20 rows / 540 bytes" in text
    assert "quota 16 granted / 11 demanded (headroom 0.6875)" in text
    assert "memory watermark 4096 bytes" in text
    cold = format_profile_dict(
        {"statistics": QueryStatistics().to_dict()})
    assert "mesh telemetry" not in cold
