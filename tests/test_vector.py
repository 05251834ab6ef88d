"""Vector similarity search (ISSUE 16): the `vector<float, N>` column
type end to end — schema/storage/wire/arrow round-trips with loud
write-path rejection, per-chunk centroid+norm stats (seal, merge,
backfill), NEAREST recall=1.0 against a numpy brute-force oracle
(dot/cosine/l2 × filtered/unfiltered × ties × k>matching-rows),
bit-identical local vs 8-device whole-plan SPMD at exactly one host
sync, the `?` placeholder/params surface, and the serving-plane
NearestBatcher (co-admitted cohort → ONE batched distance matmul).
"""

import threading
import time

import numpy as np
import pytest

from ytsaurus_tpu.chunks.columnar import (
    ColumnarChunk,
    chunk_column_stats,
    concat_chunks,
    merge_column_stats,
)
from ytsaurus_tpu.errors import YtError
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.engine.evaluator import Evaluator
from ytsaurus_tpu.schema import TableSchema, VectorType, parse_type

DIM = 8
SCHEMA = TableSchema.make([
    ("k", "int64", "ascending"), ("g", "int64"),
    ("emb", f"vector<float, {DIM}>"), ("v", "int64")])
T = "//t"


def _corpus(n=96, seed=0, null_every=0):
    """Integer-component vectors: f32 distance arithmetic on them is
    exact, so oracle comparisons are == not approx."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        emb = None if (null_every and i % null_every == 0) else \
            [float(x) for x in rng.integers(-6, 7, DIM)]
        rows.append({"k": i, "g": i % 5, "emb": emb,
                     "v": int(rng.integers(0, 100))})
    return rows


def _oracle(rows, q, metric, k, pred=lambda r: True):
    """Brute-force numpy ranking (the acceptance oracle): returns the
    kth measure so ties accept ANY row at the cut, plus the expected
    row count min(k, matching)."""
    q = np.asarray(q, dtype=np.float32)
    measures = {}
    for r in rows:
        if r["emb"] is None or not pred(r):
            continue
        e = np.asarray(r["emb"], dtype=np.float32)
        if metric == "dot":
            m = float(e @ q)
        elif metric == "cosine":
            denom = float(np.linalg.norm(e) * np.linalg.norm(q))
            m = 1.0 - float(e @ q) / denom if denom > 0 else 1.0
        else:
            m = float(np.sqrt(((e - q) ** 2).sum()))
        measures[r["k"]] = m
    reverse = metric == "dot"
    ranked = sorted(measures, key=lambda kk: (-measures[kk] if reverse
                                              else measures[kk], kk))
    take = min(k, len(ranked))
    if take == 0:
        return set(), None, 0, measures
    cut = measures[ranked[take - 1]]
    return set(ranked[:take]), cut, take, measures


def _assert_recall(got_ks, rows, q, metric, k, pred=lambda r: True):
    """recall == 1.0 with ties admitted: exactly min(k, matching) rows,
    every one at-or-better than the oracle's kth measure."""
    _top, cut, take, measures = _oracle(rows, q, metric, k, pred)
    assert len(got_ks) == take, (metric, k, got_ks)
    assert len(set(got_ks)) == take, "duplicate rows in top-k"
    for kk in got_ks:
        assert kk in measures, f"row {kk} fails the predicate"
        if metric == "dot":
            assert measures[kk] >= cut
        else:
            assert measures[kk] <= cut


# -- schema + type -------------------------------------------------------------

def test_vector_type_parses_and_interns():
    t1 = parse_type("vector<float, 16>")
    t2 = parse_type("vector<float,16>")
    t3 = parse_type("vector<float, 32>")
    assert isinstance(t1, VectorType) and t1.dim == 16
    assert t1 is t2, "same dim must intern to one object"
    assert t1 is not t3 and t1 != t3
    assert t1.value == "vector<float,16>"
    assert not t1.is_numeric and not t1.is_comparable


def test_vector_schema_survives_rebuild():
    rebuilt = TableSchema.make(
        [(c.name, c.type.value) for c in SCHEMA],
        strict=SCHEMA.strict)
    assert isinstance(rebuilt.get("emb").type, VectorType)
    assert rebuilt.get("emb").type.dim == DIM


def test_vector_key_column_rejected():
    with pytest.raises(YtError, match="key column"):
        TableSchema.make([("emb", "vector<float, 4>", "ascending"),
                          ("v", "int64")])


# -- write-path hardening (satellite 1) ----------------------------------------

@pytest.mark.parametrize("bad,msg", [
    ([1.0, 2.0], "dim mismatch"),                      # wrong dim
    ([[1.0, 2.0], [3.0, 4.0]], "Ragged"),              # nested/ragged
    ([1.0] * (DIM - 1) + [float("nan")], "Non-finite"),
    ([1.0] * (DIM - 1) + [float("inf")], "Non-finite"),
    (["a"] * DIM, "Bad vector value"),
])
def test_write_path_rejects_loudly(bad, msg):
    rows = _corpus(4)
    rows[2]["emb"] = bad
    with pytest.raises(YtError, match=msg):
        ColumnarChunk.from_rows(SCHEMA, rows)


def test_storage_round_trip_with_nulls():
    rows = _corpus(32, seed=1, null_every=7)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    assert chunk.columns["emb"].data.shape == (chunk.capacity, DIM)
    back = chunk.to_rows()
    for want, got in zip(rows, back):
        assert got["emb"] == want["emb"], want["k"]


def test_wire_round_trip_and_non_finite_decode_guard(tmp_path):
    from ytsaurus_tpu.chunks.store import FsChunkStore
    rows = _corpus(48, seed=2, null_every=9)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    store = FsChunkStore(str(tmp_path))
    cid = store.write_chunk(chunk)
    back = store.read_chunk(cid)
    assert back.to_rows() == chunk.to_rows()
    assert np.array_equal(np.asarray(back.columns["emb"].data),
                          np.asarray(chunk.columns["emb"].data))


def test_arrow_round_trip():
    from ytsaurus_tpu.arrow import (
        arrow_ipc_to_rows,
        arrow_schema_to_table_schema,
        chunk_to_arrow,
        chunks_to_arrow_ipc,
    )
    rows = _corpus(24, seed=3, null_every=5)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    table = chunk_to_arrow(chunk)
    assert str(table.schema.field("emb").type).startswith(
        "fixed_size_list")
    back = arrow_ipc_to_rows(chunks_to_arrow_ipc([chunk]))
    for want, got in zip(rows, back):
        assert got["emb"] == want["emb"]
    ts = arrow_schema_to_table_schema(table.schema)
    emb = next(c for c in ts if c.name == "emb")
    assert isinstance(emb.type, VectorType) and emb.type.dim == DIM


# -- per-chunk stats: seal, merge, backfill (satellite 3) ----------------------

def test_vector_stats_sealed_and_exact():
    rows = _corpus(40, seed=4, null_every=11)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    stats = chunk_column_stats(chunk)
    entry = stats["emb"]
    planes = np.array([r["emb"] for r in rows if r["emb"] is not None],
                      dtype=np.float64)
    norms = np.sqrt((planes * planes).sum(axis=1))
    assert entry["vector_dim"] == DIM
    assert entry["count"] == len(planes)
    assert entry["has_null"] is True
    np.testing.assert_allclose(entry["centroid_sum"],
                               planes.sum(axis=0), rtol=1e-6)
    assert entry["norm_min"] == pytest.approx(float(norms.min()))
    assert entry["norm_max"] == pytest.approx(float(norms.max()))


def test_vector_stats_merge_is_exact_fold():
    """Centroid sums ADD across chunks (the reason the stat is a sum,
    not a mean): merged == whole-table stats exactly."""
    rows = _corpus(60, seed=5, null_every=13)
    parts = [ColumnarChunk.from_rows(SCHEMA, rows[i::3])
             for i in range(3)]
    merged = merge_column_stats([chunk_column_stats(c) for c in parts])
    whole = chunk_column_stats(
        ColumnarChunk.from_rows(SCHEMA, rows))["emb"]
    got = merged["emb"]
    assert got["count"] == whole["count"]
    assert got["vector_dim"] == DIM
    np.testing.assert_allclose(got["centroid_sum"],
                               whole["centroid_sum"], rtol=1e-9)
    assert got["norm_min"] == pytest.approx(whole["norm_min"])
    assert got["norm_max"] == pytest.approx(whole["norm_max"])


def test_vector_stats_backfill_via_read_stats(tmp_path):
    """A chunk sealed without stats decode-backfills vector stats
    through ChunkStore.read_stats like every other column."""
    from ytsaurus_tpu.chunks.store import FsChunkStore
    chunk = ColumnarChunk.from_rows(SCHEMA, _corpus(16, seed=6))
    store = FsChunkStore(str(tmp_path))
    cid = store.write_chunk(chunk)
    stats = store.read_stats(cid)
    assert stats["emb"]["vector_dim"] == DIM
    assert stats["emb"]["count"] == 16


# -- NEAREST recall oracle (local evaluator) -----------------------------------

QUERY_VECTORS = [
    [1.0, -2.0, 3.0, 0.0, 5.0, -1.0, 2.0, 4.0],
    [0.0] * DIM,
    [-3.0, -3.0, -3.0, -3.0, 3.0, 3.0, 3.0, 3.0],
]


@pytest.mark.parametrize("metric", ["l2", "cosine", "dot"])
@pytest.mark.parametrize("k", [1, 7, 16])
def test_nearest_recall_unfiltered(metric, k):
    rows = _corpus(96, seed=7, null_every=10)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    ev = Evaluator()
    for q in QUERY_VECTORS:
        plan = build_query(
            f"SELECT k FROM [{T}] NEAREST(emb, ?, {k}, '{metric}')",
            {T: SCHEMA}, params=[q])
        got = [r["k"] for r in ev.run_plan(plan, chunk).to_rows()]
        _assert_recall(got, rows, q, metric, k)


@pytest.mark.parametrize("metric", ["l2", "dot"])
def test_nearest_recall_filtered(metric):
    """The predicate fuses BEFORE the distance pass: filtered-out rows
    can never displace matching rows from the top-k."""
    rows = _corpus(96, seed=8, null_every=10)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    ev = Evaluator()
    q = QUERY_VECTORS[0]
    plan = build_query(
        f"SELECT k FROM [{T}] WHERE g = 2 AND v < 70 "
        f"NEAREST(emb, ?, 8, '{metric}')",
        {T: SCHEMA}, params=[q])
    got = [r["k"] for r in ev.run_plan(plan, chunk).to_rows()]
    _assert_recall(got, rows, q, metric, 8,
                   pred=lambda r: r["g"] == 2 and r["v"] < 70)


def test_nearest_k_exceeds_matching_rows():
    rows = _corpus(64, seed=9)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    plan = build_query(
        f"SELECT k FROM [{T}] WHERE g = 3 NEAREST(emb, ?, 50)",
        {T: SCHEMA}, params=[QUERY_VECTORS[0]])
    got = [r["k"] for r in Evaluator().run_plan(plan, chunk).to_rows()]
    matching = [r for r in rows if r["g"] == 3]
    assert len(got) == len(matching)
    _assert_recall(got, rows, QUERY_VECTORS[0], "l2", 50,
                   pred=lambda r: r["g"] == 3)


def test_nearest_ties_admit_any_tied_row():
    """Duplicate vectors at the k cut: every returned row must be
    at-or-under the cut distance (set equality is NOT required)."""
    rows = []
    for i in range(12):
        rows.append({"k": i, "g": 0,
                     "emb": [float(i % 3)] * DIM, "v": 0})
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    q = [0.0] * DIM
    plan = build_query(f"SELECT k FROM [{T}] NEAREST(emb, ?, 5)",
                       {T: SCHEMA}, params=[q])
    got = [r["k"] for r in Evaluator().run_plan(plan, chunk).to_rows()]
    _assert_recall(got, rows, q, "l2", 5)


def test_nearest_order_by_distance_equivalent():
    """The sugared and unsugared spellings produce identical rows."""
    rows = _corpus(48, seed=10)
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    q = QUERY_VECTORS[2]
    ev = Evaluator()
    a = ev.run_plan(build_query(
        f"SELECT k FROM [{T}] NEAREST(emb, ?, 6)",
        {T: SCHEMA}, params=[q]), chunk).to_rows()
    b = ev.run_plan(build_query(
        f"SELECT k FROM [{T}] ORDER BY l2_distance(emb, ?) LIMIT 6",
        {T: SCHEMA}, params=[q]), chunk).to_rows()
    assert a == b


# -- params surface ------------------------------------------------------------

def test_params_arity_mismatch_is_loud():
    with pytest.raises(YtError, match="[Pp]laceholder"):
        build_query(f"SELECT k FROM [{T}] NEAREST(emb, ?, 4)",
                    {T: SCHEMA}, params=[])
    with pytest.raises(YtError, match="[Pp]laceholder|param"):
        build_query(f"SELECT k FROM [{T}] NEAREST(emb, ?, 4)",
                    {T: SCHEMA}, params=[[1.0] * DIM, [2.0] * DIM])
    with pytest.raises(YtError, match="[Uu]nbound"):
        build_query(f"SELECT k FROM [{T}] NEAREST(emb, ?, 4)",
                    {T: SCHEMA})


def test_nearest_surface_validation():
    with pytest.raises(YtError, match="dim"):
        build_query(f"SELECT k FROM [{T}] NEAREST(emb, ?, 4)",
                    {T: SCHEMA}, params=[[1.0, 2.0]])
    with pytest.raises(YtError, match="metric"):
        build_query(f"SELECT k FROM [{T}] NEAREST(emb, ?, 4, 'bogus')",
                    {T: SCHEMA}, params=[[1.0] * DIM])
    with pytest.raises(YtError):
        build_query(f"SELECT k FROM [{T}] NEAREST(emb, ?, 0)",
                    {T: SCHEMA}, params=[[1.0] * DIM])
    with pytest.raises(YtError, match="ORDER BY|LIMIT"):
        build_query(
            f"SELECT k FROM [{T}] NEAREST(emb, ?, 4) ORDER BY k",
            {T: SCHEMA}, params=[[1.0] * DIM])


def test_vector_column_guards():
    """Raw vectors have no total order / equality surface: comparisons,
    GROUP BY and ORDER BY on them are loud type errors."""
    for q in [f"SELECT k FROM [{T}] WHERE emb = emb",
              f"SELECT k, count(*) AS c FROM [{T}] GROUP BY emb",
              f"SELECT k FROM [{T}] ORDER BY emb LIMIT 3"]:
        with pytest.raises(YtError):
            build_query(q, {T: SCHEMA})


# -- distributed: whole-plan SPMD, one host sync (tentpole acceptance) ---------

@pytest.fixture(scope="module")
def vtable8(request):
    mesh = request.getfixturevalue("mesh8")
    from ytsaurus_tpu.parallel.distributed import ShardedTable
    chunks, all_rows = [], []
    for sh in range(8):
        rows = _corpus(40 + sh * 7, seed=20 + sh,
                       null_every=13 if sh % 2 else 0)
        for r in rows:
            r["k"] += sh * 10_000
        all_rows.extend(rows)
        chunks.append(ColumnarChunk.from_rows(SCHEMA, rows))
    return mesh, ShardedTable.from_chunks(mesh, chunks), \
        concat_chunks(chunks), all_rows


@pytest.mark.parametrize("metric", ["l2", "cosine", "dot"])
def test_nearest_spmd_bit_identical_one_sync(vtable8, metric):
    from ytsaurus_tpu.parallel.distributed import (
        DistributedEvaluator,
        host_sync_count,
    )
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    from ytsaurus_tpu.query.statistics import QueryStatistics
    mesh, table, merged, all_rows = vtable8
    de = DistributedEvaluator(mesh)
    local = Evaluator()
    q = QUERY_VECTORS[0]
    plan = build_query(
        f"SELECT k FROM [{T}] NEAREST(emb, ?, 9, '{metric}')",
        {T: SCHEMA}, params=[q])
    stats = QueryStatistics()
    s0 = host_sync_count()
    got = run_whole_plan(de, plan, table, stats=stats)
    assert host_sync_count() - s0 == 1, \
        "fused NEAREST must cost exactly one host sync"
    assert stats.whole_plan == 1
    want = local.run_plan(plan, merged)
    assert got.to_rows() == want.to_rows(), \
        "distributed top-k must be bit-identical to local"
    _assert_recall([r["k"] for r in got.to_rows()],
                   all_rows, q, metric, 9)


def test_nearest_spmd_filtered(vtable8):
    from ytsaurus_tpu.parallel.distributed import DistributedEvaluator
    from ytsaurus_tpu.parallel.whole_plan import run_whole_plan
    mesh, table, merged, all_rows = vtable8
    q = QUERY_VECTORS[2]
    plan = build_query(
        f"SELECT k, g FROM [{T}] WHERE g != 1 NEAREST(emb, ?, 12)",
        {T: SCHEMA}, params=[q])
    got = run_whole_plan(DistributedEvaluator(mesh), plan, table)
    want = Evaluator().run_plan(plan, merged)
    assert got.to_rows() == want.to_rows()
    _assert_recall([r["k"] for r in got.to_rows()], all_rows, q, "l2",
                   12, pred=lambda r: r["g"] != 1)


# -- serving: co-admitted cohort = ONE batched matmul (tentpole) ---------------

@pytest.fixture
def vclient(tmp_path):
    from ytsaurus_tpu.client import YtClient, YtCluster
    client = YtClient(YtCluster(str(tmp_path / "cluster")))
    client.create("map_node", "//home", recursive=True,
                  ignore_existing=True)
    client.create("table", "//home/vec", attributes={
        "schema": [
            {"name": "k", "type": "int64", "sort_order": "ascending"},
            {"name": "g", "type": "int64"},
            {"name": "emb", "type": f"vector<float, {DIM}>"},
            {"name": "v", "type": "int64"},
        ],
        "dynamic": True})
    client.mount_table("//home/vec")
    rows = _corpus(80, seed=30)
    client.insert_rows("//home/vec", rows)
    return client, rows


def test_nearest_rows_client_api(vclient):
    client, rows = vclient
    q = QUERY_VECTORS[0]
    out = client.nearest_rows("//home/vec", "emb", q, 5, metric="l2")
    _assert_recall([r["k"] for r in out], rows, q, "l2", 5)
    # $distance rides each row, ascending for l2.
    ds = [r["$distance"] for r in out]
    assert ds == sorted(ds)
    # dot returns similarity, descending.
    out = client.nearest_rows("//home/vec", "emb", q, 5, metric="dot")
    ds = [r["$distance"] for r in out]
    assert ds == sorted(ds, reverse=True)
    _assert_recall([r["k"] for r in out], rows, q, "dot", 5)


def _hold_flush_until_pending(monkeypatch, batcher, n):
    """The flusher's accumulate step, for this test: wait until the
    batcher SHOWS `n` requests pending, in place of the serving poll
    (flush once 0.2 ms pass with no arrival, inside `flush_window_ms`),
    which eight threads on a busy host do not reliably beat.  The
    cohort's membership then follows from what the test observes, not
    from the wall clock."""
    def accumulate():
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with batcher._cond:
                pending = sum(len(b.queries)
                              for b in batcher._batches.values())
            if pending >= n:
                return
            time.sleep(0.001)
    monkeypatch.setattr(batcher, "_accumulate", accumulate)


def _nearest_from_threads(client, calls):
    """One `nearest_rows(//home/vec, emb, vector, k)` per (vector, k) of
    `calls`, each from its own thread; the results in call order."""
    results = [None] * len(calls)

    def work(i):
        vector, k = calls[i]
        results[i] = client.nearest_rows("//home/vec", "emb", vector, k)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(len(calls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not any(t.is_alive() for t in threads)
    return results


def test_cohort_shares_one_batched_matmul(vclient, monkeypatch):
    """THE serving acceptance: N co-admitted NEAREST queries on one
    (table, column, metric) execute as ONE batched flush — the batcher
    counts one batch, and the jitted kernel does not re-trace for the
    co-batched queries (they ride the batch dimension of one matmul)."""
    from ytsaurus_tpu.query import vector as vmod
    client, rows = vclient
    batcher = client.cluster.gateway.nearest_batcher
    # Warm one flush so the kernel for this (capacity, batch-bucket,
    # k-bucket) is already traced, then assert the cohort run adds
    # exactly one batch and zero fresh traces for its members.
    client.nearest_rows("//home/vec", "emb", QUERY_VECTORS[1], 3)
    rng = np.random.default_rng(31)
    queries = [[float(x) for x in rng.integers(-6, 7, DIM)]
               for _ in range(8)]
    _hold_flush_until_pending(monkeypatch, batcher, len(queries))
    b0 = batcher.batches_n
    t0 = vmod.nearest_trace_count()
    results = _nearest_from_threads(client, [(q, 3) for q in queries])
    assert batcher.batches_n - b0 == 1, \
        "co-admitted cohort must flush as ONE batch"
    assert vmod.nearest_trace_count() - t0 <= 1, \
        "cohort members must share one compiled kernel"
    for i, q in enumerate(queries):
        _assert_recall([r["k"] for r in results[i]], rows, q, "l2", 3)


def test_mixed_k_cohort_each_member_gets_its_k(vclient, monkeypatch):
    client, rows = vclient
    batcher = client.cluster.gateway.nearest_batcher
    ks = [1, 3, 7, 2]
    _hold_flush_until_pending(monkeypatch, batcher, len(ks))
    b0 = batcher.batches_n
    results = _nearest_from_threads(
        client, [(QUERY_VECTORS[0], k) for k in ks])
    assert batcher.batches_n - b0 == 1      # one cohort, mixed k
    for i, k in enumerate(ks):
        _assert_recall([r["k"] for r in results[i]], rows,
                       QUERY_VECTORS[0], "l2", k)


def test_nearest_accounting_folds(vclient):
    from ytsaurus_tpu.query.accounting import get_accountant
    client, _rows = vclient
    before = get_accountant().totals()
    client.nearest_rows("//home/vec", "emb", QUERY_VECTORS[0], 4)
    after = get_accountant().totals()
    assert after["nearest_queries"] - before["nearest_queries"] == 1
    assert after["nearest_batches"] - before["nearest_batches"] == 1
    assert after["nearest_rows_scanned"] > \
        before["nearest_rows_scanned"]


def test_nearest_rejects_bad_inputs(vclient):
    client, _rows = vclient
    with pytest.raises(YtError, match="metric"):
        client.nearest_rows("//home/vec", "emb", QUERY_VECTORS[0], 3,
                            metric="manhattan")
    with pytest.raises(YtError, match="k >= 1"):
        client.nearest_rows("//home/vec", "emb", QUERY_VECTORS[0], 0)
    with pytest.raises(YtError, match="shape"):
        client.nearest_rows("//home/vec", "emb", [1.0, 2.0], 3)
    with pytest.raises(YtError, match="Non-finite"):
        client.nearest_rows("//home/vec", "emb",
                            [float("nan")] * DIM, 3)
    with pytest.raises(YtError, match="not a vector"):
        client.nearest_rows("//home/vec", "v", QUERY_VECTORS[0], 3)


def test_select_rows_params_through_client(vclient):
    client, rows = vclient
    q = QUERY_VECTORS[0]
    out = client.select_rows(
        "SELECT k FROM [//home/vec] NEAREST(emb, ?, 6)", params=[q])
    _assert_recall([r["k"] for r in out], rows, q, "l2", 6)
