"""The host-stall instruments of `utils/tracing.py`: collections
(`gc.collect`), JAX compiles anywhere in the process (`jax.*`), the stall
watch (`host.stall`, `host.overrun`), and the fan-in's two spans
(`chunk.concat.unify`, `chunk.concat.planes`).

Timings hold a margin of 4x or more: the suite runs under six workers.
"""

import gc
import sys
import threading
import time
from collections import deque

import numpy as np
import pytest

from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.utils import tracing
from ytsaurus_tpu.utils.tracing import (
    SpanCollector,
    TraceContext,
    child_span,
    get_collector,
)


@pytest.fixture
def tracing_config():
    """Installs a TracingConfig; the defaults come back afterwards."""
    yield yt_config.set_tracing_config
    yt_config.set_tracing_config(None)


@pytest.fixture
def stall_threshold(tracing_config, monkeypatch):
    """Sets the stall watch's threshold and re-arms (the watch restarts at
    it); the default comes back afterwards."""
    def set_threshold(seconds):
        monkeypatch.setattr(tracing, "_STALL_THRESHOLD", seconds)
        tracing_config(None)
    yield set_threshold
    monkeypatch.undo()


def _spans(trace_id, name=None):
    return [s for s in get_collector().find(trace_id)
            if name is None or s.name == name]


def _children(trace_id, parent_id):
    return [s for s in get_collector().find(trace_id)
            if s.parent_span_id == parent_id]


def _covered(trace_id, parent_id):
    return sum(s.duration for s in _children(trace_id, parent_id)
               if not s.name.startswith("host."))


def _watch_threads():
    return [t for t in threading.enumerate() if t.name == "yt-stall-watch"]


# -- gc.collect ---------------------------------------------------------------

def test_a_collection_under_a_trace_is_a_span_under_the_innermost(
        tracing_config):
    tracing_config(None)
    with TraceContext("root") as root:
        with child_span("inner") as inner:
            gc.collect()
    (full,) = [s for s in _spans(root.trace_id, "gc.collect")
               if s.tags["generation"] == 2]
    assert full.parent_span_id == inner.span_id
    assert set(full.tags) == {"generation", "collected", "uncollectable"}
    assert full.self_time == pytest.approx(
        full.duration - _covered(root.trace_id, full.span_id))
    (inner_rec,) = _spans(root.trace_id, "inner")
    assert inner_rec.self_time == pytest.approx(
        inner_rec.duration - _covered(root.trace_id, inner.span_id))
    assert _covered(root.trace_id, inner.span_id) >= full.duration

    before = get_collector()._seq
    gc.collect()                        # no live trace: nothing recorded
    assert not [s for s in get_collector().snapshot()
                if s.seq > before and s.name == "gc.collect"]


class _CollectingDeque(deque):
    """A ring that collects while it is being copied: under the
    collector's lock."""

    def __iter__(self):
        gc.collect()
        return super().__iter__()


def test_a_collection_inside_snapshot_neither_deadlocks_nor_loses_the_span(
        tracing_config, monkeypatch):
    tracing_config(None)
    ring = SpanCollector()
    ring._spans = _CollectingDeque(maxlen=ring.capacity)
    monkeypatch.setattr(tracing, "_collector", ring)
    seen = {}

    def select():
        with TraceContext("root") as root:
            seen["root"] = root
            seen["inside"] = ring.snapshot()   # collects under the lock

    worker = threading.Thread(target=select, daemon=True)
    worker.start()
    worker.join(timeout=30)
    assert not worker.is_alive(), "a collection under the lock deadlocked"
    root = seen["root"]
    kept = [s for s in list(deque.__iter__(ring._spans))
            if s.trace_id == root.trace_id]
    (full,) = [s for s in kept if s.name == "gc.collect"
               and s.tags["generation"] == 2]
    assert full.parent_span_id == root.span_id
    assert [s.name for s in kept if s.name == "root"] == ["root"]


class _CollectingVar:
    """The span contextvar, made to collect inside the `set` that
    activates the span named `at`: before the variable changes, as an
    allocation there does."""

    def __init__(self, var, at):
        self.var, self.at = var, at

    def get(self):
        return self.var.get()

    def set(self, span):
        if span.name == self.at:
            gc.collect()
        return self.var.set(span)

    def reset(self, token):
        self.var.reset(token)


def test_a_collection_while_a_child_is_entered_is_booked_once(
        tracing_config, monkeypatch):
    tracing_config(None)
    monkeypatch.setattr(tracing, "_current",
                        _CollectingVar(tracing._current, "child"))
    with TraceContext("root") as root:
        with child_span("child") as child:
            pass
    (full,) = [s for s in _spans(root.trace_id, "gc.collect")
               if s.tags["generation"] == 2]
    (child_rec,) = _spans(root.trace_id, "child")
    (root_rec,) = _spans(root.trace_id, "root")
    # parented to the root and ended before the child's clock started, so
    # the root's self time counts the collection once
    assert full.parent_span_id == root.span_id
    assert full.start_mono + full.duration <= child_rec.start_mono
    assert root_rec.self_time == pytest.approx(
        root_rec.duration - full.duration - child_rec.duration)
    assert child_rec.self_time == pytest.approx(child_rec.duration)
    assert child.span_id == child_rec.span_id


# -- jax.* ---------------------------------------------------------------------

def test_a_fresh_jit_under_a_trace_nests_jax_spans(tracing_config):
    import jax
    import jax.numpy as jnp
    tracing_config(None)
    token = np.random.default_rng().integers(1 << 30)

    def fresh(x):
        return x * 3 + int(token)

    with TraceContext("root") as root:
        with child_span("site") as site:
            jax.jit(fresh)(jnp.arange(5)).block_until_ready()
    under = _children(root.trace_id, site.span_id)
    # `jnp.arange`'s own program may compile here too
    assert {"jax.trace", "jax.lower", "jax.compile"} <= {
        s.name for s in under if "fresh" in s.tags.get("fun_name", "")}
    (site_rec,) = _spans(root.trace_id, "site")
    jax_time = sum(s.duration for s in under if s.name.startswith("jax."))
    assert jax_time > 0
    assert site_rec.self_time == pytest.approx(
        site_rec.duration - _covered(root.trace_id, site.span_id))
    assert site_rec.self_time <= site_rec.duration - jax_time + 1e-9


# -- the switch ----------------------------------------------------------------

def test_stall_threshold_zero_starts_no_thread(stall_threshold):
    stall_threshold(0.0)
    with TraceContext("root"):
        pass
    assert tracing._watch is None and not _watch_threads()
    assert tracing._on_gc in gc.callbacks
    stall_threshold(0.05)
    assert tracing._watch is not None
    assert len(_watch_threads()) == 1


def test_disabled_tracing_has_no_hook_no_watch_and_idle_listeners(
        tracing_config):
    import jax
    import jax.numpy as jnp
    tracing_config(None)
    with TraceContext("root"):             # arms, listeners included
        pass
    watch = tracing._watch
    assert watch is not None and watch.is_alive()
    tracing_config(yt_config.TracingConfig(enabled=False))
    assert tracing._on_gc not in gc.callbacks
    assert tracing._watch is None and not watch.is_alive()
    assert not _watch_threads()
    token = np.random.default_rng().integers(1 << 30)
    with TraceContext("root") as root:     # a forced root still records
        tracing._on_jax_start("/jax/core/compile/backend_compile_duration",
                              0.0, fun_name="f")
        jax.jit(lambda x: x - int(token))(jnp.arange(3)).block_until_ready()
        gc.collect()
    assert not tracing._jax_open.get(threading.get_ident())
    assert [s.name for s in _spans(root.trace_id)] == ["root"]


def test_the_watch_sleeps_on_an_event_and_stops(tracing_config):
    tracing_config(None)
    with TraceContext("root"):
        pass
    watch = tracing._watch
    time.sleep(0.2)
    frame = sys._current_frames()[watch.ident]
    functions = []
    while frame is not None:
        functions.append(frame.f_code.co_name)
        frame = frame.f_back
    assert "wait" in functions and "run" in functions
    tracing_config(yt_config.TracingConfig(enabled=False))
    assert not watch.is_alive()


# -- host.stall, host.overrun --------------------------------------------------

def _hold_the_gil(seconds):
    """One C call that never lets the GIL go for `seconds` or more (the
    call is made longer until one lasts that long)."""
    t0 = time.perf_counter()
    sum(range(1_000_000))
    n = int(2 * seconds / (time.perf_counter() - t0) * 1_000_000)
    for _ in range(4):
        t0 = time.perf_counter()
        sum(range(n))
        held = time.perf_counter() - t0
        if held >= seconds:
            break
        n *= 2
    return held


def test_a_gil_holding_call_is_a_host_stall_that_covers_nothing(
        stall_threshold):
    stall_threshold(0.05)
    with TraceContext("root") as root:
        time.sleep(0.05)                   # the watch is up and waiting
        held = _hold_the_gil(0.4)
        time.sleep(0.05)                   # it wakes and books the stall
    assert held >= 0.4
    stalls = _spans(root.trace_id, "host.stall")
    longest = max(stalls, key=lambda s: s.duration)
    assert longest.duration >= 0.2
    assert longest.self_time == 0.0
    assert longest.parent_span_id == root.span_id
    assert set(longest.tags) == {"cpu_ms", "nivcsw", "majflt", "gc",
                                 "compile", "stacks"}
    assert longest.tags["cpu_ms"] >= 100
    assert longest.tags["stacks"][threading.current_thread().name]
    (root_rec,) = _spans(root.trace_id, "root")
    # an overlay: the root's self time is what its live children leave
    assert root_rec.self_time == pytest.approx(
        root_rec.duration - _covered(root.trace_id, root.span_id))
    assert root_rec.self_time >= longest.duration


def test_a_root_blocked_with_the_gil_released_is_a_host_overrun(
        stall_threshold):
    stall_threshold(0.05)
    query = f"blocked {np.random.default_rng().integers(1 << 30)}"
    for _ in range(10):
        with tracing.start_query_span("query.select", query=query):
            pass
    with tracing.start_query_span("query.select", query=query) as root:
        time.sleep(0.6)
    (overrun,) = _spans(root.trace_id, "host.overrun")
    stalled = sum(s.duration for s in _spans(root.trace_id, "host.stall"))
    assert overrun.duration + stalled >= 0.2
    assert overrun.self_time == 0.0
    assert overrun.parent_span_id == root.span_id
    assert overrun.tags["samples"] >= 4
    top = overrun.tags["stacks"][0]
    assert top["count"] >= 1
    assert any("test_host_stall_spans.py" in frame
               for stack in overrun.tags["stacks"]
               for frame in stack["frames"])


# -- the fan-in's two spans ----------------------------------------------------

def _parts():
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.schema import TableSchema
    schema = TableSchema.make([("k", "int64"), ("s", "string"),
                               ("v", "double"), ("t", "string")])
    rng = np.random.default_rng(5)
    parts = []
    for i, rows in enumerate((5, 9, 3)):
        words = [f"w{i}-{j}".encode() for j in range(4)] + [b"shared"]
        parts.append(ColumnarChunk.from_rows(schema, [
            {"k": int(rng.integers(100)),
             "s": None if j % 4 == 0 else words[j % len(words)],
             "v": float(rng.random()),
             "t": b"same"} for j in range(rows)]))
    return parts


def _concat_column_by_column(chunks):
    """The concatenation as one loop over the columns, each string
    column unified where its planes are dispatched."""
    from ytsaurus_tpu.chunks import columnar
    from ytsaurus_tpu.schema import EValueType
    total = sum(c.row_count for c in chunks)
    cap = columnar.pad_capacity(max(total, 1))
    offsets = np.cumsum([0] + [c.row_count for c in chunks[:-1]],
                        dtype=np.int32)
    out = {}
    for col_schema in chunks[0].schema:
        cols = [c.column(col_schema.name) for c in chunks]
        vocab = None
        if col_schema.type is EValueType.string:
            cols, vocab = columnar.unify_dictionaries(cols)
        data, valid = columnar._concat_planes(
            tuple(c.data for c in cols), tuple(c.valid for c in cols),
            offsets, np.int32(total), capacity=cap,
            dtype=columnar._plane_dtype(col_schema.type))
        out[col_schema.name] = (np.asarray(data), np.asarray(valid), vocab)
    return total, out


def test_concat_chunks_is_the_column_by_column_chunk_in_two_spans(
        tracing_config):
    from ytsaurus_tpu.chunks.columnar import concat_chunks
    tracing_config(None)
    parts = _parts()
    with TraceContext("root") as root:
        merged = concat_chunks(parts)
    total, want = _concat_column_by_column(parts)
    assert merged.row_count == total
    for name, (data, valid, vocab) in want.items():
        column = merged.column(name)
        assert np.array_equal(np.asarray(column.data), data)
        assert np.asarray(column.data).dtype == data.dtype
        assert np.array_equal(np.asarray(column.valid), valid)
        if vocab is None:
            assert column.dictionary is None
        else:
            assert list(column.dictionary) == list(vocab)
    assert merged.to_rows() == [row for p in parts for row in p.to_rows()]
    spans = [s for s in _children(root.trace_id, root.span_id)
             if s.name.startswith("chunk.")]
    assert [s.name for s in spans] == ["chunk.concat.unify",
                                       "chunk.concat.planes"]
    unify, planes = spans
    assert unify.tags == {"columns": 2, "vocab_entries": len(
        want["s"][2]) + len(want["t"][2])}
    assert planes.tags == {"columns": 4, "capacity": merged.capacity}
    assert unify.start_mono + unify.duration <= planes.start_mono


def test_roots_on_many_threads_under_a_busy_watch_leave_nothing_open(
        stall_threshold):
    """More threads than cores open and close roots while the watch wakes
    every millisecond and samples them: every root is recorded, none is
    left registered as open, and the watch survives."""
    stall_threshold(0.004)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    query = f"stress {np.random.default_rng().integers(1 << 30)}"
    traces, errors = [], []

    def worker():
        try:
            for i in range(200):
                with tracing.start_query_span("query.select",
                                              query=query) as root:
                    with child_span("inner"):
                        if i % 50 == 49:
                            time.sleep(0.01)   # an overrun now and then
                traces.append(root.trace_id)
        except Exception as err:               # reported below
            errors.append(err)

    try:
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(16)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=60)
        assert not any(thread.is_alive() for thread in threads)
    finally:
        sys.setswitchinterval(switch)
    assert errors == []
    assert len(traces) == 16 * 200
    idents = {thread.ident for thread in threads}
    assert not idents & set(tracing._open_roots)
    assert tracing._watch.is_alive()
    wanted = set(traces)
    names = [s.name for s in get_collector().snapshot()
             if s.trace_id in wanted and not tracing.is_instrument(s.name)]
    assert len(names) == 2 * 16 * 200
