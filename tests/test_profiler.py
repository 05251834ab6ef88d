"""Continuous CPU profiler + trace export.

Ref model: library/ytprof/cpu_profiler.h (timer-driven stack sampling
into pprof) and library/tracing/jaeger/tracer.h (batched span flush to
an agent).
"""

import json
import threading
import time

import pytest

from ytsaurus_tpu.utils.profiler import (
    SamplingProfiler,
    TraceExporter,
    jsonl_sink,
)
from ytsaurus_tpu.utils.tracing import (
    TraceContext,
    get_collector,
    is_instrument,
)


def _busy_function_alpha(stop):
    while not stop.is_set():
        sum(i * i for i in range(500))


def test_sampler_finds_the_hot_function():
    stop = threading.Event()
    worker = threading.Thread(target=_busy_function_alpha, args=(stop,),
                              daemon=True)
    worker.start()
    profiler = SamplingProfiler(interval=0.005).start()
    time.sleep(0.8)
    profiler.stop()
    stop.set()
    worker.join(timeout=5)
    state = profiler.state()
    assert state["total_samples"] > 20
    flat = "\n".join(profiler.collapsed())
    assert "_busy_function_alpha" in flat
    hotspots = profiler.hotspots()
    assert hotspots and abs(sum(h["share"] for h in hotspots)) <= 1.01
    assert any("_busy_function_alpha" in h["frame"] or
               "<genexpr>" in h["frame"] for h in hotspots)


def test_sampler_reset_and_bounds():
    profiler = SamplingProfiler(interval=0.005, max_entries=3)
    for _ in range(10):
        profiler.sample_once()
    assert profiler.state()["distinct_stacks"] <= 3
    profiler.reset()
    assert profiler.state()["total_samples"] == 0


def test_trace_exporter_flushes_batches(tmp_path):
    collector = get_collector()
    collector.drain()                       # isolate from other tests
    path = str(tmp_path / "traces.jsonl")
    exporter = TraceExporter(jsonl_sink(path), flush_interval=60,
                             collector=collector)
    with TraceContext("op.parent") as parent:
        with parent.create_child("op.child"):
            time.sleep(0.01)
    n = exporter.flush_once()
    lines = [json.loads(line) for line in open(path)]
    assert n == len(lines) >= 2
    # a collection or a stall may land among them (gc.*, host.*)
    lines = [line for line in lines if not is_instrument(line["name"])]
    names = {line["name"] for line in lines}
    assert names == {"op.parent", "op.child"}
    traces = {line["trace_id"] for line in lines}
    assert len(traces) == 1 and len(lines) == 2   # one trace, two spans
    assert exporter.stats == {"batches": 1, "spans": n}
    # Nothing new → no batch.
    assert exporter.flush_once() == 0


def test_trace_exporter_background_loop(tmp_path):
    collector = get_collector()
    collector.drain()
    path = str(tmp_path / "bg.jsonl")
    exporter = TraceExporter(jsonl_sink(path), flush_interval=0.1,
                             collector=collector)
    exporter.start()
    with TraceContext("bg.span"):
        pass
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and exporter.stats["spans"] < 1:
        time.sleep(0.05)
    exporter.stop()
    assert exporter.stats["spans"] >= 1
    assert any("bg.span" in line for line in open(path))
