"""Spans on the select host path (ISSUE 27): the substrate's self time,
monotonic start, drop count and profiler annotation, and the span set one
`select_rows` leaves in its trace.
"""

import sys
import time

import pytest

from ytsaurus_tpu.utils import tracing
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
)


def _by_name(trace_id):
    out = {}
    for span in get_collector().find(trace_id):
        out.setdefault(span.name, []).append(span)
    return out


def _covered(trace_id, span_id):
    """What a span's children covered: a collection may land among them
    (a `gc.collect` child); the `host.*` overlays cover nothing."""
    return sum(s.duration for s in get_collector().find(trace_id)
               if s.parent_span_id == span_id and
               not s.name.startswith("host."))


# -- self time, start_mono ----------------------------------------------------

def test_self_time_of_nested_and_sibling_spans():
    with TraceContext("root") as root:
        with child_span("a"):
            time.sleep(0.004)
            with child_span("a.inner"):
                time.sleep(0.006)
        with child_span("b"):
            time.sleep(0.003)
        time.sleep(0.002)
    spans = {name: s[0] for name, s in _by_name(root.trace_id).items()}
    a, inner, b, top = (spans[n] for n in ("a", "a.inner", "b", "root"))
    # a leaf, unless a collection landed in it
    assert inner.self_time == pytest.approx(
        inner.duration - _covered(root.trace_id, inner.span_id))
    assert _covered(root.trace_id, a.span_id) >= inner.duration
    assert a.self_time == pytest.approx(
        a.duration - _covered(root.trace_id, a.span_id))
    assert a.self_time >= 0.004
    # siblings both count against the parent; the grandchild only once
    assert _covered(root.trace_id, root.span_id) >= a.duration + b.duration
    assert top.self_time == pytest.approx(
        top.duration - _covered(root.trace_id, root.span_id))
    assert top.self_time >= 0.002
    # self times partition the root
    assert sum(s.self_time for s in get_collector().find(root.trace_id)) \
        == pytest.approx(top.duration)


def test_self_time_clamped_when_children_overlap():
    """Children that run side by side (prefetch threads) may cover more
    than the parent lasted: self time stops at 0."""
    parent = TraceContext("p")
    with parent:
        pass
    parent._covered = 10.0
    assert SpanRecord(parent, 0.5).self_time == 0.0


def test_start_mono_is_on_perf_counter_and_ordered():
    before = time.perf_counter()
    with TraceContext("root") as root:
        with child_span("first"):
            pass
        with child_span("second"):
            pass
    after = time.perf_counter()
    spans = {name: s[0] for name, s in _by_name(root.trace_id).items()}
    assert before <= spans["root"].start_mono <= spans["first"].start_mono \
        <= spans["second"].start_mono <= after
    assert "start_mono" in spans["root"].to_dict()
    assert "self_time" in spans["root"].to_dict()


def test_span_tree_and_renderer_show_self_time():
    from ytsaurus_tpu.query.profile import format_span_tree
    with TraceContext("root") as root:
        with child_span("leaf"):
            time.sleep(0.001)
    (node,) = span_tree(root.trace_id)
    assert node["self_time"] == pytest.approx(
        node["duration"] - _covered(root.trace_id, root.span_id))
    lines = format_span_tree([node])
    assert "(self " in lines[0]           # a span with children
    assert "(self " not in lines[1]       # a leaf: self is its duration


# -- the ring's drop count ----------------------------------------------------

def _record(name):
    ctx = TraceContext(name)
    ctx.start_time = time.time()
    return SpanRecord(ctx, 0.001)


def test_dropped_counts_spans_that_left_the_ring():
    col = SpanCollector(capacity=4)
    for i in range(4):
        col.add(_record(f"s{i}"))
    assert col.dropped == 0
    for i in range(4, 10):
        col.add(_record(f"s{i}"))
    assert col.dropped == 6
    assert [s.name for s in col.snapshot()] == ["s6", "s7", "s8", "s9"]
    col.set_capacity(3)                    # a shrink drops the oldest
    assert col.dropped == 7
    col.set_capacity(8)                    # growing drops nothing
    assert col.dropped == 7
    assert [s.name for s in col.drain()] == ["s7", "s8", "s9"]


def test_default_ring_holds_a_benchmark_window():
    from ytsaurus_tpu import config as yt_config
    assert yt_config.TracingConfig().ring_capacity == 65536
    assert SpanCollector().capacity == 65536


# -- the profiler annotation --------------------------------------------------

class _FakeAnnotation:
    opened = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.opened.append(("enter", self.name))

    def __exit__(self, *exc):
        _FakeAnnotation.opened.append(("exit", self.name))


def test_sampled_span_opens_a_profiler_annotation(monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.opened = []
    with TraceContext("query.select"):
        with child_span("query.plan"):
            pass
    assert _FakeAnnotation.opened == [
        ("enter", "yt.query.select"), ("enter", "yt.query.plan"),
        ("exit", "yt.query.plan"), ("exit", "yt.query.select")]


def test_null_and_unsampled_spans_open_no_annotation(monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.opened = []
    assert current_trace() is None
    span = child_span("orphan")
    assert span is NULL_SPAN      # the untraced fast path: a singleton
    with span:
        pass
    with TraceContext("quiet", sampled=False):
        with child_span("inner"):
            pass
    assert _FakeAnnotation.opened == []


def test_tracing_never_imports_jax(monkeypatch):
    """Where jax is not loaded, spans record, nothing is annotated and
    tracing does not load it (today the package's own `__init__` imports
    jax; the substrate must not be one more reason)."""
    monkeypatch.delitem(sys.modules, "jax")
    with TraceContext("root") as root:
        assert root._annotation is None
        with child_span("child") as child:
            assert child is not NULL_SPAN and child._annotation is None
    assert child_span("orphan") is NULL_SPAN
    assert [s.name for s in get_collector().find(root.trace_id)
            if not is_instrument(s.name)] == ["child", "root"]
    assert "jax" not in sys.modules, "tracing imported jax"
    with open(tracing.__file__) as f:
        source = f.read()
    assert "import jax" not in source and "from jax" not in source


# -- one select_rows ----------------------------------------------------------

SELECT_SPANS = {
    "query.select": 1, "serving.admission": 1, "query.plan": 1,
    "query.stage": 1, "coordinator.shard": 1, "evaluator.run_plan": 1,
    "evaluator.prepare": 1, "evaluator.launch": 1, "evaluator.sync": 1,
    "query.decode": 1, "query.record": 2,
}


@pytest.fixture(scope="module")
def static_client(tmp_path_factory):
    from ytsaurus_tpu.client import connect
    client = connect(str(tmp_path_factory.mktemp("spans")))
    client.create("table", "//s/t", recursive=True, attributes={
        "schema": [{"name": "k", "type": "int64"},
                   {"name": "v", "type": "double"}]})
    client.write_table("//s/t", [{"k": i % 3, "v": float(i)}
                                 for i in range(100)])
    return client


QUERY = "k, sum(v) AS s FROM [//s/t] GROUP BY k"


def _inside(inner, outer):
    return outer.start_mono <= inner.start_mono and \
        inner.start_mono + inner.duration <= \
        outer.start_mono + outer.duration + 1e-9


def test_select_rows_leaves_the_span_table(static_client):
    static_client.select_rows(QUERY)              # compile
    profile = static_client.select_rows(QUERY, explain_analyze=True)
    assert len(profile.rows) == 3
    spans = _by_name(profile.trace_id)
    assert {n: len(s) for n, s in spans.items()
            if not is_instrument(n)} == SELECT_SPANS
    # a collection or a stall may land in any trace: a few at most
    assert sum(len(s) for n, s in spans.items() if is_instrument(n)) <= 4
    by_id = {s.span_id: s for group in spans.values() for s in group}
    root = spans["query.select"][0]
    assert root.parent_span_id is None
    assert root.tags["rows"] == 3

    def parent(span):
        return by_id[span.parent_span_id].name

    # the epilogue and the decode are inside the root, in time and in tree
    for name in ("query.record", "query.decode", "query.plan",
                 "query.stage", "serving.admission", "coordinator.shard"):
        for span in spans[name]:
            assert parent(span) == "query.select"
            assert _inside(span, root)
    first, second = sorted(spans["query.record"],
                           key=lambda s: s.start_mono)
    decode = spans["query.decode"][0]
    assert first.start_mono < decode.start_mono < second.start_mono
    assert decode.tags == {"rows": 3, "columns": 2, "fetch": "whole",
                           "bytes": 2 * 128 * (8 + 1)}
    # the evaluator's three parts, the sync included, under run_plan
    run_plan = spans["evaluator.run_plan"][0]
    assert parent(run_plan) == "coordinator.shard"
    for name in ("evaluator.prepare", "evaluator.launch", "evaluator.sync"):
        assert parent(spans[name][0]) == "evaluator.run_plan"
        assert _inside(spans[name][0], run_plan)
    assert spans["evaluator.prepare"][0].tags["cache"] == "hit"
    assert spans["evaluator.prepare"][0].tags["fingerprint"] == \
        run_plan.tags["fingerprint"]
    assert spans["evaluator.sync"][0].tags["pendings"] == 1
    stage = spans["query.stage"][0]
    assert stage.tags["chunks"] == 1 and stage.tags["bytes"] > 0
    assert stage.tags["cache_hits"] >= 1
    # self times partition the call
    assert sum(s.self_time for s in by_id.values()) == \
        pytest.approx(root.duration, rel=1e-6)
    # the execution is no shorter in the tree than in the statistics
    assert run_plan.duration >= profile.execute_time


def test_decode_span_says_what_crossed(static_client):
    """`query.decode` carries `fetch` and `bytes` (ISSUE 28): a small
    result crosses whole, every output plane in it, and EXPLAIN ANALYZE's
    tree shows both."""
    profile = static_client.select_rows(
        "k, v FROM [//s/t] WHERE k = 1 LIMIT 40", explain_analyze=True)
    assert len(profile.rows) == 33
    (decode,) = _by_name(profile.trace_id)["query.decode"]
    assert decode.tags["rows"] == 33 and decode.tags["columns"] == 2
    assert decode.tags["fetch"] == "whole"
    # two columns, a data and a validity plane each, of one capacity
    assert decode.tags["bytes"] % (2 * (8 + 1)) == 0
    assert decode.tags["bytes"] >= 33 * 2 * (8 + 1)
    from ytsaurus_tpu.query.profile import format_span_tree
    (line,) = [l for l in format_span_tree(span_tree(profile.trace_id))
               if "query.decode" in l]
    assert "fetch=whole" in line and f"bytes={decode.tags['bytes']}" in line


def test_default_ring_keeps_2600_selects(static_client):
    """A Q1 window of the benchmark at half the call time: 2,600 selects
    of 12 spans each, and the collections and stalls that land among
    them, stay in a ring of the default capacity, none dropped (at
    16,384 the window's first 1,235 selects would be)."""
    static_client.select_rows(QUERY)              # compile
    ring = SpanCollector()
    real = tracing._collector
    tracing._collector = ring
    try:
        for _ in range(2600):
            static_client.select_rows(QUERY)
    finally:
        tracing._collector = real
    spans = ring.snapshot()
    assert ring.dropped == 0 and len(spans) <= ring.capacity
    path = [s for s in spans if not is_instrument(s.name)]
    assert len(path) == 2600 * sum(SELECT_SPANS.values())
    # ~0.07 a select with the CPU backend (gc.collect, generation 0)
    assert len(spans) - len(path) <= 2600
    assert len({s.trace_id for s in path}) == 2600
    assert sum(s.name == "query.decode" and s.tags["fetch"] == "whole"
               for s in spans) == 2600


def test_cold_select_nests_the_compile(static_client):
    profile = static_client.select_rows(
        "k, min(v) AS lo FROM [//s/t] GROUP BY k", explain_analyze=True)
    spans = _by_name(profile.trace_id)
    assert spans["evaluator.prepare"][0].tags["cache"] == "miss"
    (compile_span,) = spans["evaluator.compile"]
    by_id = {s.span_id: s for group in spans.values() for s in group}
    assert by_id[compile_span.parent_span_id].name == "evaluator.run_plan"
    assert compile_span.tags["cause"] in ("new_fingerprint", "new_shape")


def test_failed_select_closes_the_root_with_the_error(static_client):
    from ytsaurus_tpu.errors import YtError
    before = tracing.get_collector()._seq
    with pytest.raises(YtError):
        static_client.select_rows("nope FROM [//s/t]")
    fresh = [s for s in get_collector().snapshot() if s.seq > before]
    (root,) = [s for s in fresh if s.name == "query.select"]
    assert "error" in root.tags and "rows" not in root.tags
    assert not [s for s in fresh if s.name == "query.decode"]


def test_fanout_sync_is_one_span_for_the_batch(tmp_path):
    """On the coordinator's fan-out path `run_plan_async` spans end at
    the launch and `finish_all` syncs the batch under one
    `evaluator.sync`."""
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.schema import TableSchema
    client = connect(str(tmp_path))
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("v", "int64")], unique_keys=True)
    client.create("table", "//s/dyn", recursive=True, attributes={
        "schema": schema, "dynamic": True, "pivot_keys": [[100], [200]]})
    client.mount_table("//s/dyn")
    client.insert_rows("//s/dyn", [{"k": i, "v": i} for i in range(300)])
    import ytsaurus_tpu.client as client_module
    profile = None
    # three tablets stay three shards only below the coalescing threshold
    real = client_module.coordinate_and_execute

    def uncoalesced(*args, **kwargs):
        kwargs["merge_shards_below"] = 0
        return real(*args, **kwargs)

    client_module.coordinate_and_execute = uncoalesced
    try:
        profile = client.select_rows(
            "sum(v) AS s FROM [//s/dyn] GROUP BY 1", explain_analyze=True)
    finally:
        client_module.coordinate_and_execute = real
    assert profile.rows == [{"s": sum(range(300))}]
    spans = _by_name(profile.trace_id)
    by_id = {s.span_id: s for group in spans.values() for s in group}
    batch = [s for s in spans["evaluator.sync"] if s.tags["pendings"] == 3]
    assert len(batch) == 1
    assert by_id[batch[0].parent_span_id].name == "query.select"
    assert len(spans["tablet.read_snapshot"]) == 3
    for snap in spans["tablet.read_snapshot"]:
        assert by_id[snap.parent_span_id].name == "query.stage"
        assert snap.tags["lock_wait_s"] >= 0.0


# -- tablet side --------------------------------------------------------------

def test_tablet_lookup_tags_lock_wait(tmp_path):
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.schema import TableSchema
    client = connect(str(tmp_path))
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("v", "int64")], unique_keys=True)
    client.create("table", "//s/l", recursive=True,
                  attributes={"schema": schema, "dynamic": True})
    client.mount_table("//s/l")
    client.insert_rows("//s/l", [{"k": i, "v": i} for i in range(10)])
    before = tracing.get_collector()._seq
    assert client.lookup_rows("//s/l", [(3,)]) == [{"k": 3, "v": 3}]
    lookups = [s for s in get_collector().snapshot()
               if s.seq > before and s.name == "tablet.lookup"]
    assert lookups and all(s.tags["lock_wait_s"] >= 0.0 for s in lookups)


def test_mvcc_first_call_is_timed_as_a_compile(tmp_path):
    """The MVCC merge program is jitted outside the evaluator: its first
    call lands in a `tablet.mvcc_compile` span and in the query's
    compile counters; the second read compiles nothing."""
    from ytsaurus_tpu import config as yt_config
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.schema import TableSchema
    from ytsaurus_tpu.tablet import mvcc
    client = connect(str(tmp_path))
    # a schema of this test's own: no other test has compiled its program
    schema = TableSchema.make(
        [("mvcc_key", "int64", "ascending"), ("mvcc_value", "double")],
        unique_keys=True)
    client.create("table", "//s/m", recursive=True,
                  attributes={"schema": schema, "dynamic": True})
    client.mount_table("//s/m")
    yt_config.set_tablet_config(
        yt_config.TabletConfig(vectorized_scan_min_rows=0))
    try:
        client.insert_rows("//s/m", [{"mvcc_key": i, "mvcc_value": 1.0 * i}
                                     for i in range(50)])
        programs = len(mvcc._PROGRAMS)
        cold = client.select_rows("sum(mvcc_value) AS s FROM [//s/m] "
                                  "GROUP BY 1", explain_analyze=True)
        assert len(mvcc._PROGRAMS) == programs + 1
        spans = _by_name(cold.trace_id)
        (compiled,) = spans["tablet.mvcc_compile"]
        assert compiled.tags["kind"] == "visible"
        by_id = {s.span_id: s for g in spans.values() for s in g}
        assert by_id[compiled.parent_span_id].name == "tablet.mvcc_merge"
        stats = cold.statistics
        assert stats["compile_time"] >= compiled.duration * 0.99
        evaluator_compiles = len(spans.get("evaluator.compile", ()))
        assert stats["compile_count"] == evaluator_compiles + 1
        client.insert_rows("//s/m", [{"mvcc_key": 99, "mvcc_value": 1.0}])
        warm = client.select_rows("sum(mvcc_value) AS s FROM [//s/m] "
                                  "GROUP BY 1", explain_analyze=True)
        assert "tablet.mvcc_compile" not in _by_name(warm.trace_id)
        assert "tablet.mvcc_merge" in _by_name(warm.trace_id)
    finally:
        yt_config.set_tablet_config(None)
