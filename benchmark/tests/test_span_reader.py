"""The `span` reader over rehearsals (CPU, rehearsal sizes) of every cell
that lists span entries: each of the cell's entries that reads spans gets
a number; trace by trace the named readings and the remainder add up to
the root's duration, and the remainder is the self time of the spans no
reading names; a ring that dropped spans of the window, or a program
without self times, reads as nothing; a traced run's ring holds the
window; set-up's garbage is freed before the window.

    python3 -m pytest benchmark/tests/test_span_reader.py -q
"""

import json
import os
import sys
import time
import types
import weakref

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from readers import span as span_reader  # noqa: E402

CELL = "tpch_q1_sf1"
DYN = "tpch_q1_dyn_8tablets"


def load_bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def span_entries(bench, cell):
    return [e["name"] for e in bench["per_layer"]
            if e["source"] == "program_span" and cell in e["workloads"]]


SPAN_CELLS = sorted({cell for e in load_bench()["per_layer"]
                     if e["source"] == "program_span"
                     for cell in e["workloads"]})


@pytest.fixture(scope="module")
def bench():
    return load_bench()


def rehearse(bench, cell, seed=2147483777, seconds=2):
    """One rehearsed window of `cell`, as `run_cell` drives it, with the
    context kept: load, warm, window (the configuration's
    `rehearse_sizes`)."""
    jax = run.start_jax(rehearse=True)
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse"])
    ctx = run.make_context(bench, args, jax)
    from ytsaurus_tpu.client import connect
    ctx.driver.prepare()
    state = run.state_dir()
    try:
        root = os.path.join(state, "cluster")
        ctx.driver.load(connect(root))
        yt = connect(root, fresh=True)
        ctx.driver.warm(yt)
        ctx.record.setup_s = 0.0
        ctx.driver.window(yt, args.seconds, ctx.record)
    finally:
        import shutil
        shutil.rmtree(state, ignore_errors=True)
    # the window's spans, read now: the next rehearsal's share the ring
    from ytsaurus_tpu.utils import tracing
    ctx.spans = tracing.get_collector().snapshot()
    ctx.metrics = run.compute_metrics(ctx, "per_layer")
    ctx.traces = span_reader.window_traces("query.select", ctx)
    return ctx


_REHEARSED = {}


def rehearsed(bench, cell):
    """Each cell rehearsed once for this module."""
    if cell not in _REHEARSED:
        _REHEARSED[cell] = rehearse(bench, cell)
    return _REHEARSED[cell]


@pytest.fixture(params=SPAN_CELLS)
def cell_rehearsal(bench, request):
    return rehearsed(bench, request.param)


@pytest.fixture
def rehearsal(bench):
    """Q1's window, for the tests of what the reader refuses."""
    return rehearsed(bench, CELL)


def test_q1_and_the_dynamic_cell_list_span_entries():
    assert {CELL, DYN} <= set(SPAN_CELLS)


def test_every_span_entry_reads_a_number(bench, cell_rehearsal):
    cell = cell_rehearsal.cell["name"]
    names = span_entries(bench, cell)
    assert names
    metrics = cell_rehearsal.metrics
    for name in names:
        assert name in metrics, (name, sorted(metrics))
        assert metrics[name]["unit"] == "ms"
        assert metrics[name]["value"] >= 0.0
    # the readings the cell had are still there
    suffix = names[0].split(".", 1)[1]
    for stem in ("host_ms_per_select", "execute_ms_per_select",
                 "window_compiles"):
        assert f"{stem}.{suffix}" in metrics


def unnamed_self_time(trace, duration_names, self_names):
    """What no reading names: the self time of every span that is neither
    read by its self time nor inside a span read by its duration."""
    spans = [s for group in trace.values() for s in group]
    by_id = {s.span_id: s for s in spans}
    total = 0.0
    for span in {s.span_id: s for s in spans}.values():
        if span.name in self_names:
            continue
        node, inside = span, False
        while node is not None:
            if node.name in duration_names:
                inside = True
                break
            node = by_id.get(node.parent_span_id)
        if not inside:
            total += span.self_time
    return total


def test_named_spans_and_remainder_add_up_to_the_root(bench, cell_rehearsal):
    cell = cell_rehearsal.cell["name"]
    traces = cell_rehearsal.traces
    assert len(traces) == len(cell_rehearsal.record.requests) > 10
    remainders = [run.metric_definition(name)
                  for name in span_entries(bench, cell)
                  if run.metric_definition(name)["stat"] == "remainder"]
    assert remainders or cell not in (CELL, DYN)
    for remainder in remainders:
        named = [run.metric_definition(stem) for stem in remainder["minus"]]
        by_stat = {stat: {name for d in named if d["stat"] == stat
                          for name in d["spans"]}
                   for stat in ("duration", "self_time")}
        for trace in traces:
            root = trace[""][0]
            values = [span_reader.trace_value(d, trace) for d in named]
            rest = span_reader.trace_value(remainder, trace)
            assert all(v > 0.0 for v in values), values
            assert sum(values) + rest == pytest.approx(root.duration,
                                                       abs=1e-12)
            # and what no span names is what the unnamed spans kept to
            # themselves
            unnamed = unnamed_self_time(trace, by_stat["duration"],
                                        by_stat["self_time"])
            assert rest == pytest.approx(unnamed, abs=1e-9)
    if cell not in (CELL, DYN):
        return
    # the execution as the spans see it is the execution as the counter
    # does (a select without a join: prepare, launch and sync are all of it)
    metrics = cell_rehearsal.metrics
    suffix = span_entries(bench, cell)[0].split(".", 1)[1]
    three = sum(metrics[f"{n}.{suffix}"]["value"] for n in (
        "prepare_ms_per_select", "launch_ms_per_select",
        "sync_ms_per_select"))
    assert three == pytest.approx(
        metrics[f"execute_ms_per_select.{suffix}"]["value"], rel=0.15)


def test_coalesce_span_agrees_with_its_counter(bench):
    """The fan-in as its span sees it and as QueryStatistics.coalesce_time
    counts it: request by request (one client, closed loop: the n-th trace
    is the n-th request) the counter encloses the span, and what it holds
    beyond is the span's own bookkeeping (its context, annotation and ring
    entry), tens of microseconds on a CPU: under 0.1 ms in the
    median, which is under 2% of the fan-in's ~5.7 ms on the chip."""
    dyn = rehearsed(bench, DYN)
    definition = run.metric_definition("coalesce_span_ms_per_select")
    spans = [span_reader.trace_value(definition, trace)
             for trace in dyn.traces]
    counted = [line["coalesce_s"] for line in dyn.record.requests]
    assert len(spans) == len(counted) and all(s > 0.0 for s in spans)
    beyond = sorted(c - s for c, s in zip(counted, spans))
    assert beyond[0] >= 0.0
    assert beyond[len(beyond) // 2] < 1e-4
    assert span_reader.read(definition, dyn) == pytest.approx(
        dyn.metrics["coalesce_ms_per_select.dyn"]["value"], abs=0.1)


def test_window_starts_with_its_ring_and_without_setup_garbage(
        bench, monkeypatch):
    """A `--trace 1` run widens the program's span ring before its window;
    an untraced run leaves the program's own.  Either way, garbage that
    set-up left in reference cycles is freed before the window starts."""
    from ytsaurus_tpu.utils import tracing
    collector = tracing.get_collector()
    own = collector.capacity
    jax = run.start_jax(rehearse=True)
    seen = []
    real_module = run.Context.module

    class Cycle:
        pass

    def module(self, package, name):
        loaded = real_module(self, package, name)
        if package == "drivers":
            driver_cls = loaded.Driver

            class Watched(driver_cls):
                def warm(self, yt):
                    super().warm(yt)
                    left = Cycle()
                    left.me = left
                    self.left = weakref.ref(left)

                def window(self, yt, seconds, record):
                    seen.append((collector.capacity, self.left() is None))
                    return super().window(yt, seconds, record)
            return types.SimpleNamespace(Driver=Watched)
        return loaded

    monkeypatch.setattr(run.Context, "module", module)
    try:
        for trace in (0, 1):
            args = run.parse_args(["--workload", CELL, "--seed", "2147483779",
                                   "--seconds", "0.5", "--trace", str(trace),
                                   "--rehearse"])
            result, _ = run.run_cell(bench, args, jax, time.perf_counter())
            assert result["correct"]
    finally:
        collector.set_capacity(own)
    assert seen == [(own, True), (run.TRACED_RING_SPANS, True)]


class _Ring:
    def __init__(self, spans, dropped):
        self.spans, self.dropped = spans, dropped

    def snapshot(self):
        return list(self.spans)


def fake_context(record):
    return types.SimpleNamespace(record=record)


def test_dropped_spans_of_the_window_read_as_nothing(rehearsal, monkeypatch,
                                                     capsys):
    from ytsaurus_tpu.utils import tracing
    definition = run.metric_definition("plan_ms_per_select.tpch")
    record = rehearsal.record
    spans = rehearsal.spans
    in_window = [s for s in spans if s.start_mono >= record.window_start]
    assert len(in_window) < len(spans)         # set-up's spans came first

    # the whole ring, spans dropped before the window only: a reading
    monkeypatch.setattr(tracing, "get_collector", lambda: _Ring(spans, 5))
    assert span_reader.read(definition, fake_context(record)) > 0.0

    # a ring that begins inside the window: spans of the window are gone
    monkeypatch.setattr(tracing, "get_collector",
                        lambda: _Ring(in_window[40:], 40))
    capsys.readouterr()
    assert span_reader.read(definition, fake_context(record)) is None
    assert "dropped spans of the window" in capsys.readouterr().err

    # nothing dropped, but a trace of the window is not whole
    first = in_window[0].trace_id
    holed = [s for s in spans if not (s.trace_id == first and
                                      s.name == "query.select")]
    monkeypatch.setattr(tracing, "get_collector", lambda: _Ring(holed, 0))
    assert span_reader.read(definition, fake_context(record)) is None
    assert "completed requests" in capsys.readouterr().err


def test_a_program_without_self_times_reads_as_nothing(rehearsal,
                                                       monkeypatch, capsys):
    """The parent commit's collector: no `dropped`, no `self_time`."""
    from ytsaurus_tpu.utils import tracing

    class OldRing:
        def snapshot(self):
            return [types.SimpleNamespace(
                trace_id="t", span_id="s", parent_span_id=None,
                name="query.select", start=time.time(), duration=0.04,
                tags={})]

    monkeypatch.setattr(tracing, "get_collector", OldRing)
    for stem in ("plan_ms_per_select", "select_unspanned_ms"):
        definition = run.metric_definition(stem + ".tpch")
        assert span_reader.read(
            definition, fake_context(rehearsal.record)) is None
    assert "no self time" in capsys.readouterr().err
