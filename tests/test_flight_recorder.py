"""Query flight recorder (ISSUE 5): end-to-end tracing, EXPLAIN ANALYZE
profiles, the /traces plane, and the cross-process propagation fixes.

Covers the satellite checklist:
  - multi-hop trace driver → gateway → coordinator → tablet with
    parent/child linkage + tag correctness,
  - RPC server context restoration on executor threads (leaked contexts
    must not poison later requests) and RetryingChannel same-trace/
    fresh-span-per-attempt retries,
  - slow-query log capture + eviction,
  - /traces endpoint round-trip,
  - span ring-buffer bounded memory.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.utils.tracing import (
    NULL_SPAN,
    SpanCollector,
    SpanRecord,
    TraceContext,
    child_span,
    current_trace,
    get_collector,
    is_instrument,
    span_tree,
    start_query_span,
    trace_summaries,
)


@pytest.fixture
def tracing_defaults():
    """Restore process-wide tracing config + flight recorder after a test
    that installs a custom TracingConfig."""
    yield yt_config.set_tracing_config
    yt_config.set_tracing_config(None)
    from ytsaurus_tpu.query.profile import get_flight_recorder
    get_flight_recorder().clear()


def _walk(nodes):
    for node in nodes:
        yield node
        yield from _walk(node.get("children") or [])


def _find(nodes, name):
    return [n for n in _walk(nodes) if n["name"] == name]


# -- span primitives ----------------------------------------------------------

def test_child_span_is_null_without_ambient_trace():
    assert current_trace() is None
    span = child_span("orphan", key=1)
    assert span is NULL_SPAN
    with span as s:
        s.add_tag("ignored", True)       # all no-ops
        assert current_trace() is None   # activation touches nothing


def test_child_span_null_under_unsampled_parent():
    with TraceContext("quiet", sampled=False):
        assert child_span("inner") is NULL_SPAN


def test_start_query_span_sampling_and_force(tracing_defaults):
    set_config = tracing_defaults
    set_config(yt_config.TracingConfig(enabled=True, sample_rate=0.0))
    assert start_query_span("q") is NULL_SPAN
    forced = start_query_span("q", force=True)
    assert forced is not NULL_SPAN
    set_config(yt_config.TracingConfig(enabled=False))
    assert start_query_span("q") is NULL_SPAN
    # force overrides even a disabled config (explain_analyze contract).
    assert start_query_span("q", force=True) is not NULL_SPAN


def test_start_query_span_pins_trace_id():
    span = start_query_span("q", force=True, trace_id="feedface" * 4)
    assert span.trace_id == "feedface" * 4
    with span:
        pass
    assert get_collector().find("feedface" * 4)


def test_exception_tagged_on_span():
    ctx = TraceContext("boom")
    with pytest.raises(ValueError):
        with ctx:
            raise ValueError("payload")
    (rec,) = [s for s in get_collector().find(ctx.trace_id)
              if not is_instrument(s.name)]
    assert "ValueError" in rec.tags["error"]


# -- ring buffer --------------------------------------------------------------

def _record(name="s", trace_id=None):
    ctx = TraceContext(name, trace_id=trace_id)
    ctx.start_time = time.time()
    return SpanRecord(ctx, 0.001)


def test_collector_ring_is_bounded():
    col = SpanCollector(capacity=8)
    for i in range(50):
        col.add(_record(name=f"s{i}"))
    snap = col.snapshot()
    assert len(snap) == 8
    assert [s.name for s in snap] == [f"s{i}" for i in range(42, 50)]
    col.set_capacity(3)                   # shrink drops the oldest
    assert [s.name for s in col.snapshot()] == ["s47", "s48", "s49"]


def test_collector_drain_cursor_preserves_views():
    col = SpanCollector(capacity=16)
    col.add(_record("a"))
    col.add(_record("b"))
    assert [s.name for s in col.drain()] == ["a", "b"]
    # Drained spans are still VISIBLE to the flight-recorder views; only
    # the export cursor advanced.
    assert [s.name for s in col.snapshot()] == ["a", "b"]
    assert col.drain() == []
    col.add(_record("c"))
    assert [s.name for s in col.drain()] == ["c"]


def test_span_tree_structure_and_summaries():
    with TraceContext("root") as root:
        with root.create_child("mid") as mid:
            with mid.create_child("leaf"):
                pass
        with root.create_child("sibling"):
            pass
    tree = span_tree(root.trace_id)
    assert len(tree) == 1 and tree[0]["name"] == "root"
    # a collection or a stall may land in the trace (gc.*, host.*)
    children = [n for n in tree[0]["children"]
                if not is_instrument(n["name"])]
    assert [n["name"] for n in children] == ["mid", "sibling"]  # by start
    assert [n["name"] for n in children[0]["children"]
            if not is_instrument(n["name"])] == ["leaf"]
    (row,) = [r for r in trace_summaries()
              if r["trace_id"] == root.trace_id]
    spans = get_collector().find(root.trace_id)
    assert row["root"] == "root" and row["spans"] == len(spans)
    assert sum(not is_instrument(s.name) for s in spans) == 4
    assert span_tree("no-such-trace") == []


# -- RPC propagation regressions (satellite 1) --------------------------------

def test_rpc_server_restores_context_and_isolates_leaks():
    """Handlers run on pooled executor threads: the dispatcher must (a)
    restore the caller's wire context and (b) isolate each request in a
    fresh contextvars copy, so a handler that LEAKS an active context
    cannot poison the next request on the same thread."""
    from ytsaurus_tpu.rpc import Channel, RpcServer
    from ytsaurus_tpu.rpc.server import Service, rpc_method

    seen = []

    class Leaky(Service):
        name = "leaky"

        @rpc_method()
        def leak(self, body, attachments):
            # Enter WITHOUT exiting: the worst-behaved handler.
            TraceContext("leaked").__enter__()
            return {"ok": True}

        @rpc_method()
        def probe(self, body, attachments):
            ctx = current_trace()
            seen.append(ctx.trace_id if ctx is not None else None)
            return {"ok": True}

    # ONE worker thread: every request shares it, maximizing exposure.
    server = RpcServer([Leaky()], max_workers=1)
    server.start()
    channel = Channel(server.address, timeout=10)
    try:
        channel.call("leaky", "leak", {})
        channel.call("leaky", "probe", {})
        assert seen == [None]             # the leak did not escape
        with TraceContext("caller") as root:
            channel.call("leaky", "probe", {})
        assert seen[1] == root.trace_id   # wire context restored
        channel.call("leaky", "probe", {})
        assert seen[2] is None            # and not sticky afterwards
    finally:
        channel.close()
        server.stop()


def test_rpc_server_does_not_root_traces_for_untraced_requests():
    from ytsaurus_tpu.rpc import Channel, RpcServer
    from ytsaurus_tpu.rpc.server import Service, rpc_method

    class Echo(Service):
        name = "echo2"

        @rpc_method()
        def ping(self, body, attachments):
            return {"ok": True}

    server = RpcServer([Echo()])
    server.start()
    channel = Channel(server.address, timeout=10)
    try:
        before = len(get_collector().snapshot())
        channel.call("echo2", "ping", {})
        after = [s for s in get_collector().snapshot()[before:]
                 if s.name == "echo2.ping"]
        assert after == []       # sampling belongs to the entry points
    finally:
        channel.close()
        server.stop()


def test_retrying_channel_fresh_span_per_attempt():
    from ytsaurus_tpu.rpc.channel import RetryingChannel

    calls = []

    class FlakyChannel:
        def call(self, service, method, body, attachments=(),
                 timeout=None):
            ctx = current_trace()
            calls.append((ctx.trace_id, ctx.span_id))
            if len(calls) < 3:
                raise YtError("transport down",
                              code=EErrorCode.TransportError)
            return {"ok": True}, ()

    retrying = RetryingChannel(FlakyChannel(), attempts=4, backoff=0.001)
    with TraceContext("client_root") as root:
        body, _ = retrying.call("svc", "m", {})
    assert body == {"ok": True} and len(calls) == 3
    # Same trace id on every attempt...
    assert {tid for tid, _ in calls} == {root.trace_id}
    # ...but a FRESH span per attempt (no aliasing of server work).
    assert len({sid for _, sid in calls}) == 3
    attempts = sorted(
        s.tags["attempt"] for s in get_collector().find(root.trace_id)
        if s.name == "rpc.call")
    assert attempts == [0, 1, 2]
    # Attempt spans are siblings under the root, not nested chains.
    assert all(s.parent_span_id == root.span_id
               for s in get_collector().find(root.trace_id)
               if s.name == "rpc.call")


# -- execution profiles + flight recorder -------------------------------------

def _profile(wall, query="q", trace_id=None):
    from ytsaurus_tpu.query.profile import ExecutionProfile
    return ExecutionProfile(
        query=query, trace_id=trace_id, pool="default",
        started_at=time.time(), wall_time=wall, admission_wait=0.0,
        compile_time=0.0, execute_time=wall, statistics={})


def test_slow_query_log_capture_and_eviction(tracing_defaults):
    from ytsaurus_tpu.query.profile import get_flight_recorder
    set_config = tracing_defaults
    set_config(yt_config.TracingConfig(
        slow_query_threshold=0.1, slow_log_capacity=3,
        recent_log_capacity=2, sample_rate=0.0))
    rec = get_flight_recorder()
    rec.clear()
    for i in range(6):
        rec.observe(_profile(wall=0.2 + i, query=f"slow{i}"))
    rec.observe(_profile(wall=0.01, query="fast"))
    slow = [p.query for p in rec.slow_queries()]
    assert slow == ["slow3", "slow4", "slow5"]   # bounded, oldest evicted
    assert rec.recent() == []       # sample_rate=0: fast queries dropped
    set_config(yt_config.TracingConfig(
        slow_query_threshold=0.1, slow_log_capacity=3,
        recent_log_capacity=2, sample_rate=1.0))
    for i in range(4):
        rec.observe(_profile(wall=0.01, query=f"fast{i}"))
    assert [p.query for p in rec.recent()] == ["fast2", "fast3"]
    set_config(yt_config.TracingConfig(enabled=False))
    rec.observe(_profile(wall=9.0, query="while_disabled"))
    assert "while_disabled" not in [p.query for p in rec.slow_queries()]


def test_execution_profile_format_and_dict():
    with TraceContext("query.select") as root:
        with root.create_child("serving.admission") as adm:
            adm.add_tag("pool", "default")
    p = _profile(wall=0.5, query="SELECT 1", trace_id=root.trace_id)
    text = p.format()
    assert "SELECT 1" in text and root.trace_id in text
    assert "compile" in text and "execute" in text
    d = p.to_dict(include_rows=False)
    assert d["trace_id"] == root.trace_id
    assert _find(d["span_tree"], "serving.admission")
    assert "rows" not in d


# -- /traces plane ------------------------------------------------------------

def test_traces_endpoint_round_trip(tracing_defaults):
    from ytsaurus_tpu.query.profile import get_flight_recorder
    from ytsaurus_tpu.server.monitoring import MonitoringServer
    from ytsaurus_tpu.server.orchid import OrchidTree
    from ytsaurus_tpu.utils.profiling import ProfilerRegistry

    set_config = tracing_defaults
    set_config(yt_config.TracingConfig(slow_query_threshold=0.1))
    with TraceContext("query.select") as root:
        with root.create_child("coordinator.shard") as shard:
            shard.add_tag("shard", 0)
    get_flight_recorder().clear()
    get_flight_recorder().observe(
        _profile(wall=0.5, query="SELECT slow", trace_id=root.trace_id))

    server = MonitoringServer(OrchidTree(), ProfilerRegistry())
    server.start()
    try:
        base = f"http://{server.address}"
        listing = json.loads(
            urllib.request.urlopen(f"{base}/traces").read())
        assert root.trace_id in [r["trace_id"]
                                 for r in listing["recent_traces"]]
        (slow,) = listing["slow_queries"]
        assert slow["query"] == "SELECT slow"
        assert slow["trace_id"] == root.trace_id
        detail = json.loads(urllib.request.urlopen(
            f"{base}/traces/{root.trace_id}").read())
        assert detail["trace_id"] == root.trace_id
        (tree_root,) = detail["spans"]
        assert tree_root["name"] == "query.select"
        (shard,) = [n for n in tree_root["children"]
                    if not is_instrument(n["name"])]
        assert shard["name"] == "coordinator.shard"
        assert shard["tags"] == {"shard": 0}
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{base}/traces/deadbeef")
        assert err.value.code == 404
    finally:
        server.stop()


def test_orchid_flight_recorder_views(tracing_defaults):
    from ytsaurus_tpu.query.profile import get_flight_recorder
    from ytsaurus_tpu.server.orchid import default_orchid

    set_config = tracing_defaults
    set_config(yt_config.TracingConfig(slow_query_threshold=0.1))
    with TraceContext("query.orchid_view") as root:
        pass
    get_flight_recorder().clear()
    get_flight_recorder().observe(
        _profile(wall=1.0, query="Q", trace_id=root.trace_id))
    tree = default_orchid()
    traces = tree.get("/tracing/traces")
    assert root.trace_id in traces
    assert traces[root.trace_id][0]["name"] == "query.orchid_view"
    (slow,) = tree.get("/tracing/slow_queries")
    assert slow["query"] == "Q"


# -- EXPLAIN ANALYZE end-to-end (acceptance criterion) ------------------------

@pytest.fixture(scope="module")
def flight_cluster(tmp_path_factory):
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.schema import TableSchema

    client = connect(str(tmp_path_factory.mktemp("flight")))
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("g", "int64"), ("v", "int64")],
        unique_keys=True)
    client.create("table", "//fr/t",
                  attributes={"schema": schema, "dynamic": True,
                              "pivot_keys": [[100], [200]]},
                  recursive=True)
    client.mount_table("//fr/t")
    client.insert_rows("//fr/t", [{"k": i, "g": i % 5, "v": i}
                                  for i in range(300)])
    return client


def test_explain_analyze_distributed_select(flight_cluster):
    client = flight_cluster
    profile = client.select_rows(
        "g, sum(v) AS s FROM [//fr/t] GROUP BY g", explain_analyze=True)
    assert [r["g"] for r in sorted(profile.rows,
                                   key=lambda r: r["g"])] == list(range(5))
    # Compile and execute reported SEPARATELY, both real.
    assert profile.compile_time >= 0.0
    assert profile.execute_time > 0.0
    assert profile.wall_time >= profile.execute_time
    assert profile.trace_id is not None
    tree = profile.span_tree()
    (root,) = tree
    assert root["name"] == "query.select"
    by_name = {}
    for node in _walk(tree):
        by_name.setdefault(node["name"], []).append(node)
    # Admission → coordinator shards → evaluator → tablet reads all
    # covered, in ONE trace.
    assert "serving.admission" in by_name
    shards = by_name["coordinator.shard"]
    assert shards       # ≥1 shard program (coalescing may merge tablets)
    assert all(isinstance(n["tags"]["shard"], int) for n in shards)
    assert all(n["tags"]["attempt"] == 0 for n in shards)
    evals = by_name["evaluator.run_plan"]
    assert all("fingerprint" in n["tags"] for n in evals)
    reads = by_name["tablet.read_snapshot"]
    assert all(n["tags"]["snapshot_cache"] in ("hit", "miss", "bypass")
               for n in reads)
    # Parent/child linkage: every non-root span's parent is in the trace.
    ids = {n["span_id"] for n in _walk(tree)}
    for node in _walk(tree):
        if node is not root:
            assert node["parent_span_id"] in ids
        assert node["trace_id"] == profile.trace_id
    # The same trace is retrievable by id from the /traces plane.
    from ytsaurus_tpu.server.monitoring import MonitoringServer
    from ytsaurus_tpu.server.orchid import OrchidTree
    from ytsaurus_tpu.utils.profiling import ProfilerRegistry
    server = MonitoringServer(OrchidTree(), ProfilerRegistry())
    server.start()
    try:
        detail = json.loads(urllib.request.urlopen(
            f"http://{server.address}/traces/{profile.trace_id}").read())
        assert detail["spans"][0]["name"] == "query.select"
    finally:
        server.stop()


def test_explain_analyze_compile_vs_cache_hit(flight_cluster):
    client = flight_cluster
    query = "g, count(*) AS c FROM [//fr/t] WHERE v < 250 GROUP BY g"
    first = client.select_rows(query, explain_analyze=True)
    again = client.select_rows(query, explain_analyze=True)
    # Warm plan cache: the second run compiles nothing new.
    assert again.statistics["compile_count"] == 0
    assert again.statistics["cache_hits"] >= 1
    assert again.compile_time == 0.0
    assert not _find(again.span_tree(), "evaluator.compile")
    assert first.statistics["compile_count"] >= 1 or \
        first.statistics["cache_hits"] >= 1


def test_unsampled_select_has_no_trace(flight_cluster, tracing_defaults):
    client = flight_cluster
    set_config = tracing_defaults
    set_config(yt_config.TracingConfig(sample_rate=0.0))
    before = len(get_collector().snapshot())
    rows = client.select_rows("k, v FROM [//fr/t] WHERE k < 3")
    assert len(rows) == 3
    new = get_collector().snapshot()[before:]
    assert [s for s in new if s.name == "query.select"] == []
    # explain_analyze still forces a full trace.
    profile = client.select_rows("k, v FROM [//fr/t] WHERE k < 3",
                                 explain_analyze=True)
    assert profile.trace_id is not None
    assert _find(profile.span_tree(), "query.select")


def test_traced_lookup_batches_link_into_caller_trace(flight_cluster):
    client = flight_cluster
    with TraceContext("test.lookup_root") as root:
        rows = client.lookup_rows("//fr/t", [(7,), (8,)])
    assert [r["k"] for r in rows] == [7, 8]
    spans = get_collector().find(root.trace_id)
    names = {s.name for s in spans}
    assert "query.lookup" in names
    assert "serving.batch_flush" in names
    # The flush span (flusher thread) parents into THIS caller's trace.
    flush = next(s for s in spans if s.name == "serving.batch_flush")
    assert flush.trace_id == root.trace_id
    assert "tablet.lookup" in names


# -- multi-hop: remote client → driver service → gateway → tablet -------------

def test_multihop_remote_driver_trace(flight_cluster):
    from ytsaurus_tpu.remote_client import connect_remote
    from ytsaurus_tpu.rpc import RpcServer
    from ytsaurus_tpu.server.services import DriverService

    client = flight_cluster
    server = RpcServer([DriverService(client)])
    server.start()
    remote = connect_remote(server.address)
    try:
        with TraceContext("cli.request") as root:
            result = remote.select_rows(
                "g, sum(v) AS s FROM [//fr/t] GROUP BY g",
                explain_analyze=True)
        def _text(v):
            return v.decode() if isinstance(v, bytes) else v
        result = {_text(k): v for k, v in dict(result).items()}
        trace_id = _text(result["trace_id"])
        # The whole hop chain shares the CLIENT's trace id.
        assert trace_id == root.trace_id
        spans = get_collector().find(root.trace_id)
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        # Client side: the RetryingChannel's per-attempt rpc.call span
        # sits between the root and the server-side handler span.
        (rpc_span,) = [s for s in by_name["rpc.call"]
                       if s.tags.get("method") == "execute"]
        assert rpc_span.parent_span_id == root.span_id
        assert rpc_span.tags["attempt"] == 0
        (server_span,) = by_name["driver.execute"]
        assert server_span.parent_span_id == rpc_span.span_id
        assert server_span.tags["service"] == "driver"
        (select_span,) = by_name["query.select"]
        assert select_span.parent_span_id == server_span.span_id
        shard_parents = {s.parent_span_id
                         for s in by_name["coordinator.shard"]}
        assert shard_parents == {select_span.span_id}
        assert "evaluator.run_plan" in by_name
        assert "tablet.read_snapshot" in by_name
        # The wire profile carries the compile/execute split too.
        assert float(result["execute_time"]) > 0.0
    finally:
        remote.close()
        server.stop()


# -- CLI ----------------------------------------------------------------------

def test_cli_explain_analyze_and_trace(flight_cluster, capsys):
    import re

    from ytsaurus_tpu import cli

    rc = cli.run(["select-rows", "--explain-analyze",
                  "g, count(*) AS c FROM [//fr/t] GROUP BY g"],
                 client=flight_cluster)
    out = capsys.readouterr().out
    assert rc == 0
    assert "compile" in out and "execute" in out and "spans:" in out
    assert "query.select" in out
    trace_id = re.search(r"trace_id: ([0-9a-f]{32})", out).group(1)

    rc = cli.run(["trace", trace_id], client=flight_cluster)
    out = capsys.readouterr().out
    assert rc == 0
    assert out.startswith(f"trace {trace_id}")
    assert "query.select" in out and "serving.admission" in out

    rc = cli.run(["trace", trace_id, "--json"], client=flight_cluster)
    tree = json.loads(capsys.readouterr().out)
    assert tree[0]["name"] == "query.select"

    rc = cli.run(["trace", "no-such-trace"], client=flight_cluster)
    assert rc == 1
    assert "no such trace" in capsys.readouterr().err


# -- threaded executors keep linkage ------------------------------------------

def test_scheduler_operation_spans(flight_cluster):
    """Operations plane: operation → phase → job spans link across the
    JobManager's worker threads (explicit contextvars capture)."""
    client = flight_cluster
    client.write_table("//fr/in", [{"a": i} for i in range(10)])
    collector = get_collector()
    before = len(collector.snapshot())
    op = client.run_map(
        lambda rows: [{"b": r["a"] * 2} for r in rows],
        "//fr/in", "//fr/out", rows_per_job=4)
    assert op.state == "completed"
    assert sorted(r["b"] for r in client.read_table("//fr/out")) == \
        [i * 2 for i in range(10)]
    new = collector.snapshot()[before:]
    ops = [s for s in new if s.name == "operation.run"]
    assert ops, "operation root span missing"
    op = ops[-1]
    phases = [s for s in new if s.name == "operation.phase"
              and s.trace_id == op.trace_id]
    assert phases
    jobs = [s for s in new if s.name == "operation.job"
            and s.trace_id == op.trace_id]
    assert jobs
    phase_ids = {s.span_id for s in phases}
    assert all(j.parent_span_id in phase_ids for j in jobs)
    assert {j.tags["index"] for j in jobs} <= set(range(len(jobs) + 16))
