"""The `span_window` reader and the entries of the host-stall instruments
and the fan-in's split, over CPU rehearsals of Q1 and the dynamic-table
cell: every entry reads a number; an injected stall moves its reading by
its duration over the window's traces; a span nested in another of the
same reading counts once; a program without the instruments reads 0.0;
the fan-in's two spans stay inside `coordinator.coalesce`, trace by trace.
The dynamic cell's readings are read from their definitions: its metrics
are named one by one in `test_q1_dyn_correct.py`, so `BENCHMARK.json`
lists them there only once that set admits them.

    python3 -m pytest benchmark/tests/test_span_window.py -q
"""

import types

import numpy as np
import pytest

# test_span_reader puts the benchmark's directory on the path
from test_span_reader import CELL, DYN, load_bench, rehearsed
import run  # noqa: E402
from readers import span_window  # noqa: E402

INSTRUMENTS = ["stall_ms_per_select", "gc_ms_per_select",
               "jax_compile_ms_per_select"]
FAN_IN = ["coalesce_unify_ms_per_select", "coalesce_concat_ms_per_select"]
READINGS = {CELL: INSTRUMENTS, DYN: INSTRUMENTS + FAN_IN}
ENTRIES = [stem + ".tpch" for stem in INSTRUMENTS]


@pytest.fixture(scope="module")
def bench():
    return load_bench()


def reading(ctx, stem):
    """What the definition `stem` reads in the rehearsal `ctx`."""
    definition = run.metric_definition(stem)
    return ctx.module("readers", definition["kind"]).read(definition, ctx)


def test_the_entries_stand_in_the_benchmark(bench):
    entries = {e["name"]: e for e in bench["per_layer"]}
    for name in ENTRIES:
        entry = entries[name]
        assert entry["source"] == "program_span" and entry["unit"] == "ms"
        (cell,) = entry["workloads"]
        moved = next(m for m in bench["end_to_end"]
                     if m["name"] == entry["moves"])
        assert cell in moved["workloads"]
        assert run.metric_definition(name)["kind"] in ("span", "span_window")


@pytest.mark.parametrize("cell", [CELL, DYN])
def test_every_new_entry_reads_a_number(bench, cell):
    ctx = rehearsed(bench, cell)
    for stem in READINGS[cell]:
        assert reading(ctx, stem) >= 0.0, stem
    if cell != CELL:
        return
    for name in ENTRIES:
        assert name in ctx.metrics, (name, sorted(ctx.metrics))
        assert ctx.metrics[name]["unit"] == "ms"
        assert ctx.metrics[name]["value"] == reading(ctx, name)


def copied_context(ctx):
    """A context whose window traces may be altered: the rehearsal's
    others read the originals."""
    traces = [{name: list(spans) for name, spans in trace.items()}
              for trace in ctx.traces]
    fake = types.SimpleNamespace(record=ctx.record)
    fake._span_windows = {"query.select": traces}
    return fake, traces


def span(name, parent, duration, span_id):
    return types.SimpleNamespace(name=name, parent_span_id=parent.span_id,
                                 span_id=span_id, duration=duration,
                                 self_time=0.0, tags={})


@pytest.mark.parametrize("cell", [CELL, DYN])
def test_an_injected_stall_moves_the_reading_by_its_share(bench, cell):
    ctx = rehearsed(bench, cell)
    definition = run.metric_definition("stall_ms_per_select")
    fake, traces = copied_context(ctx)
    before = span_window.read(definition, fake)
    root = traces[len(traces) // 2][""][0]
    traces[len(traces) // 2].setdefault("host.stall", []).append(
        span("host.stall", root, 0.25, "injected"))
    after = span_window.read(definition, fake)
    assert after - before == pytest.approx(250.0 / len(traces), rel=1e-9)


def test_a_span_inside_another_of_the_reading_counts_once(bench):
    ctx = rehearsed(bench, DYN)
    definition = run.metric_definition("jax_compile_ms_per_select")
    fake, traces = copied_context(ctx)
    before = span_window.read(definition, fake)
    trace = traces[0]
    root = trace[""][0]
    compile_ = span("jax.compile", root, 0.5, "compile")
    read = span("jax.cache_read", compile_, 0.2, "read")
    trace.setdefault("jax.compile", []).append(compile_)
    trace.setdefault("jax.cache_read", []).append(read)
    after = span_window.read(definition, fake)
    assert after - before == pytest.approx(500.0 / len(traces), rel=1e-9)


def test_a_program_without_the_instruments_reads_zero(bench):
    ctx = rehearsed(bench, CELL)
    fake, traces = copied_context(ctx)
    for trace in traces:
        for name in [n for n in trace if n.startswith(("gc.", "jax.",
                                                       "host."))]:
            del trace[name]
    for stem in ("stall_ms_per_select", "gc_ms_per_select",
                 "jax_compile_ms_per_select"):
        assert span_window.read(run.metric_definition(stem), fake) == 0.0


def test_the_fan_in_split_stays_inside_the_coalesce(bench):
    ctx = rehearsed(bench, DYN)
    unify, planes, rest = [], [], []
    for trace in ctx.traces:
        (coalesce,) = trace["coordinator.coalesce"]
        (u,) = trace["chunk.concat.unify"]
        (p,) = trace["chunk.concat.planes"]
        assert u.parent_span_id == p.parent_span_id == coalesce.span_id
        assert u.duration + p.duration <= coalesce.duration
        assert u.start_mono + u.duration <= p.start_mono
        unify.append(u.duration)
        planes.append(p.duration)
        rest.append(coalesce.duration - u.duration - p.duration)
    unify_ms, planes_ms = (reading(ctx, stem) for stem in FAN_IN)
    assert unify_ms == pytest.approx(np.median(unify) * 1e3)
    assert planes_ms == pytest.approx(np.median(planes) * 1e3)
    whole = float(np.median([t["coordinator.coalesce"][0].duration
                             for t in ctx.traces])) * 1e3
    assert unify_ms + planes_ms <= whole
    assert min(rest) >= 0.0
