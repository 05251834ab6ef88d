"""Persistent AOT compile-artifact cache (ISSUE 10 tentpole, piece c):
cross-process warm start (compile in one process, disk-hit in a fresh
one), loud-but-safe fallback on corrupted artifacts, versioned-header
refusal on jax/schema mismatch, the size-capped mtime-LRU disk tier,
and the observability surfaces (sensors, /compile snapshot, EXPLAIN
ANALYZE's cause=disk_hit arm).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.schema import TableSchema


@pytest.fixture(autouse=True)
def _fresh_configs():
    yield
    yt_config.set_compile_config(None)
    yt_config.set_workload_config(None)
    from ytsaurus_tpu.query.engine.evaluator import (
        get_compile_observatory,
    )
    get_compile_observatory().reset()


def _inputs(n=64):
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    schema = TableSchema.make([("k", "int64"), ("v", "int64")])
    chunk = ColumnarChunk.from_arrays(schema, {
        "k": np.arange(n, dtype=np.int64),
        "v": np.arange(n, dtype=np.int64) * 2})
    return schema, chunk


def _plan(q, schema):
    from ytsaurus_tpu.query.builder import build_query
    return build_query(q, {"//t": schema})


def _use_disk(tmp_path, **kwargs):
    cfg = yt_config.CompileConfig(disk_cache_dir=str(tmp_path),
                                  **kwargs)
    yt_config.set_compile_config(cfg)
    return cfg


def test_warm_start_across_evaluators(tmp_path):
    """In-process restart analog: a FRESH evaluator over the same cache
    dir serves the shape from disk — zero fresh compiles."""
    from ytsaurus_tpu.query.engine.aot_cache import get_disk_cache
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.query.statistics import QueryStatistics
    _use_disk(tmp_path)
    schema, chunk = _inputs()
    s1 = QueryStatistics()
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 10", schema),
                         chunk, stats=s1)
    assert s1.compile_count == 1 and s1.compile_disk_hit == 0
    assert get_disk_cache().snapshot()["files"] == 1
    # "Restart": fresh evaluator, fresh memory cache, same disk dir —
    # and a DIFFERENT constant of the same shape still disk-hits.
    s2 = QueryStatistics()
    out = Evaluator().run_plan(
        _plan("k FROM [//t] WHERE v < 6", schema), chunk, stats=s2)
    assert [r["k"] for r in out.to_rows()] == [0, 1, 2]
    assert s2.compile_disk_hit == 1
    assert s2.compile_count - s2.compile_disk_hit == 0, \
        "warm start must not fresh-compile"
    snap = get_disk_cache().snapshot()
    assert snap["hits"] == 1 and snap["errors"] == 0


def test_cluster_store_serves_a_joining_evaluator_without_a_fresh_compile(
        tmp_path):
    """The cluster rung (memory -> disk -> CLUSTER -> compile): what one
    evaluator compiled and published, a fresh one (empty memory, no disk
    tier — a replica that joins hot) fetches: every program it loads is
    a cluster hit, none a fresh compile."""
    from ytsaurus_tpu.chunks.store import FsChunkStore
    from ytsaurus_tpu.query.engine import aot_cache
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.query.statistics import QueryStatistics
    store = aot_cache.ClusterArtifactStore(
        FsChunkStore(str(tmp_path / "artifacts")))
    aot_cache.set_cluster_store(store)
    try:
        schema, chunk = _inputs()
        queries = ["k FROM [//t] WHERE v < 10",
                   "k, sum(v) AS s FROM [//t] GROUP BY k"]
        first = QueryStatistics()
        for q in queries:
            Evaluator().run_plan(_plan(q, schema), chunk, stats=first)
        assert first.compile_count == 2 and first.compile_cluster_hit == 0
        assert store.snapshot()["publishes"] == 2
        joined = QueryStatistics()
        joiner = Evaluator()
        outs = [joiner.run_plan(_plan(q, schema), chunk, stats=joined)
                for q in queries]
        assert [r["k"] for r in outs[0].to_rows()] == [0, 1, 2, 3, 4]
        assert joined.compile_count == 2
        assert joined.compile_count - joined.compile_cluster_hit == 0, \
            "a joining evaluator must fetch, not compile"
        assert store.snapshot()["hits"] == 2
    finally:
        aot_cache.set_cluster_store(None)


def test_cross_process_persistence(tmp_path):
    """ISSUE 10 acceptance: compile in ONE process, start a fresh
    evaluator in ANOTHER on the same cache dir, assert disk hits and
    zero fresh compiles."""
    script = f"""
import numpy as np
from ytsaurus_tpu import config as yt_config
yt_config.set_compile_config(yt_config.CompileConfig(
    disk_cache_dir={str(tmp_path)!r}))
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.engine.evaluator import Evaluator
from ytsaurus_tpu.query.statistics import QueryStatistics
from ytsaurus_tpu.chunks.columnar import ColumnarChunk
from ytsaurus_tpu.schema import TableSchema
schema = TableSchema.make([("k", "int64"), ("v", "int64")])
chunk = ColumnarChunk.from_arrays(schema, {{
    "k": np.arange(64, dtype=np.int64),
    "v": np.arange(64, dtype=np.int64) * 2}})
stats = QueryStatistics()
rows = Evaluator().run_plan(
    build_query("k FROM [//t] WHERE v < 8", {{"//t": schema}}),
    chunk, stats=stats).to_rows()
print("CHILD", len(rows), stats.compile_count, stats.compile_disk_hit)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    child = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CHILD")][0].split()
    assert child[1:] == ["4", "1", "0"], child    # compiled fresh there
    # THIS process: fresh evaluator on the artifact the child wrote.
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.query.statistics import QueryStatistics
    _use_disk(tmp_path)
    schema, chunk = _inputs()
    stats = QueryStatistics()
    out = Evaluator().run_plan(
        _plan("k FROM [//t] WHERE v < 12", schema), chunk, stats=stats)
    assert [r["k"] for r in out.to_rows()] == [0, 1, 2, 3, 4, 5]
    assert stats.compile_disk_hit == 1
    assert stats.compile_count - stats.compile_disk_hit == 0


def test_corrupted_artifact_falls_back_and_counts_error(tmp_path):
    """Truncated artifact → fresh compile + disk_errors, never a query
    failure."""
    from ytsaurus_tpu.query.engine.aot_cache import get_disk_cache
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.query.statistics import QueryStatistics
    _use_disk(tmp_path)
    schema, chunk = _inputs()
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 10", schema),
                         chunk)
    [artifact] = [p for p in os.listdir(tmp_path)
                  if p.endswith(".aot")]
    path = os.path.join(tmp_path, artifact)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 3])      # truncate mid-pickle
    stats = QueryStatistics()
    out = Evaluator().run_plan(
        _plan("k FROM [//t] WHERE v < 10", schema), chunk, stats=stats)
    assert [r["k"] for r in out.to_rows()] == [0, 1, 2, 3, 4]
    assert stats.compile_disk_hit == 0
    assert stats.compile_count == 1          # fresh compile
    assert get_disk_cache().snapshot()["errors"] == 1


def test_version_mismatch_refused_loudly(tmp_path):
    """The versioned-header discipline: a jax-version (or schema)
    mismatch is REFUSED — counted as an error, fallback compiles."""
    from ytsaurus_tpu.query.engine.aot_cache import get_disk_cache
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    from ytsaurus_tpu.query.statistics import QueryStatistics
    _use_disk(tmp_path)
    schema, chunk = _inputs()
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 10", schema),
                         chunk)
    [artifact] = [p for p in os.listdir(tmp_path)
                  if p.endswith(".aot")]
    path = os.path.join(tmp_path, artifact)
    with open(path, "rb") as f:
        header = json.loads(f.readline())
        rest = f.read()
    header["jax"] = "0.0.1-other"
    with open(path, "wb") as f:
        f.write(json.dumps(header).encode() + b"\n")
        f.write(rest)
    stats = QueryStatistics()
    out = Evaluator().run_plan(
        _plan("k FROM [//t] WHERE v < 10", schema), chunk, stats=stats)
    assert [r["k"] for r in out.to_rows()] == [0, 1, 2, 3, 4]
    assert stats.compile_count == 1 and stats.compile_disk_hit == 0
    assert get_disk_cache().snapshot()["errors"] == 1


def test_disk_tier_is_size_capped_with_mtime_lru(tmp_path):
    """Bounded disk tier: a byte cap evicts oldest-mtime artifacts."""
    from ytsaurus_tpu.query.engine.aot_cache import get_disk_cache
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    _use_disk(tmp_path)
    schema, chunk = _inputs()
    ev = Evaluator()
    ev.run_plan(_plan("k FROM [//t] WHERE v < 10", schema), chunk)
    one_size = get_disk_cache().snapshot()["bytes"]
    assert one_size > 0
    # Re-point at the same dir with a cap that holds ~1.5 artifacts.
    _use_disk(tmp_path, disk_cache_capacity_bytes=int(one_size * 1.5))
    for i, shape in enumerate(("v > %d", "v = %d", "v != %d")):
        ev.run_plan(_plan("k FROM [//t] WHERE " + shape % i, schema),
                    chunk)
    snap = get_disk_cache().snapshot()
    assert snap["evictions"] >= 2
    assert snap["bytes"] <= int(one_size * 1.5)
    assert snap["files"] >= 1


def test_min_compile_seconds_gates_persistence(tmp_path):
    from ytsaurus_tpu.query.engine.evaluator import Evaluator
    _use_disk(tmp_path, disk_cache_min_compile_seconds=3600.0)
    schema, chunk = _inputs()
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 10", schema),
                         chunk)
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".aot")]


def test_disk_sensors_and_compile_snapshot(tmp_path):
    """/compile carries the disk tier; the catalog sensors move."""
    from ytsaurus_tpu.query.engine.evaluator import (
        Evaluator,
        get_compile_observatory,
    )
    from ytsaurus_tpu.utils.profiling import get_registry
    obs = get_compile_observatory()
    obs.reset()
    _use_disk(tmp_path)
    schema, chunk = _inputs()
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 10", schema),
                         chunk)
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 4", schema),
                         chunk)
    snap = obs.snapshot()
    assert snap["disk"]["hits"] == 1
    assert snap["disk"]["files"] == 1
    assert snap["totals"]["disk_hits"] == 1
    [row] = snap["fingerprints"]
    assert row["disk_hits"] == 1 and row["compiles"] == 1
    registry = get_registry()
    with registry._lock:
        sensors = {name: s.get() for (name, _tags), s
                   in registry._sensors.items()
                   if name.startswith("/query/compile_cache/disk_")}
    assert sensors["/query/compile_cache/disk_hits"] >= 1
    assert sensors["/query/compile_cache/disk_bytes"] > 0
    assert sensors["/query/compile_cache/disk_files"] >= 1
    # EXPLAIN ANALYZE's cause arm (profile renderer).
    from ytsaurus_tpu.query.profile import format_profile_dict
    from ytsaurus_tpu.query.statistics import QueryStatistics
    stats = QueryStatistics()
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 2", schema),
                         chunk, stats=stats)
    text = format_profile_dict({"statistics": stats.to_dict()})
    assert "disk_hit 1" in text


def test_compile_cache_top_renders_disk_tier(tmp_path, capsys):
    from ytsaurus_tpu.cli import _format_compile_top
    from ytsaurus_tpu.query.engine.evaluator import (
        Evaluator,
        get_compile_observatory,
    )
    obs = get_compile_observatory()
    obs.reset()
    _use_disk(tmp_path)
    schema, chunk = _inputs()
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 10", schema),
                         chunk)
    Evaluator().run_plan(_plan("k FROM [//t] WHERE v < 4", schema),
                         chunk)
    out = _format_compile_top(obs.snapshot(), "compile_seconds", 10)
    assert "disk tier: 1 hits" in out
    assert "disk_hits" in out
