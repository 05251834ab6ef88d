"""The `span` reader over a rehearsal of Q1 (CPU, rehearsal sizes): every
entry of BENCHMARK.json that reads spans gets a number; trace by trace the
eight named readings and the remainder add up to the root's duration; a
ring that dropped spans of the window, or a program without self times,
reads as nothing.

    python3 -m pytest benchmark/tests/test_span_reader.py -q
"""

import json
import os
import sys
import time
import types

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from readers import span as span_reader  # noqa: E402

CELL = "tpch_q1_sf1"


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal(bench):
    """One rehearsed window of Q1, as `run_cell` drives it, with the
    context kept: load, warm, window."""
    jax = run.start_jax(rehearse=True)
    args = run.parse_args(["--workload", CELL, "--seed", "2147483777",
                           "--seconds", "2", "--rehearse"])
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
        yield ctx
    finally:
        import shutil
        shutil.rmtree(state, ignore_errors=True)


def span_entries(bench):
    return [e["name"] for e in bench["per_layer"]
            if e["source"] == "program_span"]


def test_every_span_entry_reads_a_number(bench, rehearsal):
    names = span_entries(bench)
    assert len(names) == 9
    metrics = run.compute_metrics(rehearsal, "per_layer")
    for name in names:
        assert name in metrics, (name, sorted(metrics))
        assert metrics[name]["unit"] == "ms"
        assert metrics[name]["value"] >= 0.0
    # the readings the cell had are still there
    for name in ("host_ms_per_select.tpch", "execute_ms_per_select.tpch",
                 "window_compiles.tpch"):
        assert name in metrics


def test_named_spans_and_remainder_add_up_to_the_root(bench, rehearsal):
    traces = span_reader.window_traces("query.select", rehearsal)
    assert len(traces) == len(rehearsal.record.requests) > 10
    remainder = run.metric_definition("select_unspanned_ms.tpch")
    assert len(remainder["minus"]) == 8
    named = [run.metric_definition(stem) for stem in remainder["minus"]]
    for trace in traces:
        root = trace[""][0]
        parts = [span_reader.trace_value(d, trace) for d in named]
        rest = span_reader.trace_value(remainder, trace)
        assert all(p > 0.0 for p in parts), parts
        assert sum(parts) + rest == pytest.approx(root.duration, abs=1e-12)
        # and what no span names is what the unnamed spans kept to
        # themselves
        unnamed = sum(s.self_time for name in (
            "query.select", "coordinator.shard", "evaluator.run_plan")
            for s in trace[name])
        assert rest == pytest.approx(unnamed, abs=1e-9)
    # the execution as the spans see it is the execution as the counter does
    metrics = run.compute_metrics(rehearsal, "per_layer")
    three = sum(metrics[n + ".tpch"]["value"] for n in (
        "prepare_ms_per_select", "launch_ms_per_select",
        "sync_ms_per_select"))
    assert three == pytest.approx(
        metrics["execute_ms_per_select.tpch"]["value"], rel=0.15)


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
    spans = tracing.get_collector().snapshot()
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
