"""`correct` has to be able to fail in the join cell too.  On the CPU, at
the rehearsal's sizes, in `test_correct.py`'s manner:

- the program comes out correct, and the cell's control (the reference
  with every line joined to the NEXT order's row, put in the program's
  place) does not;
- a run of the harness with one answer among many altered where it is
  produced (one count off by one) sees `correct` come out false;
- the two join counter definitions (`metrics/join_ms_per_select.json`,
  `metrics/join_sync_ms_per_select.json`) read a number from the cell's
  own window through the `counter` reader, and nothing (not 0) where the
  program's QueryStatistics has no such counter, as the parent's has not.

    python3 -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys
import time

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from drivers import select_join_stream  # noqa: E402
from readers import counter  # noqa: E402

CELL = "tpch_q12_join"


@pytest.fixture(scope="module")
def jax():
    return run.start_jax(rehearse=True)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(bench, jax, seed=2147483777, seconds=1.5, **kw):
    args = run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse"])
    return run.run_cell(bench, args, jax, time.perf_counter(), **kw)


def test_program_is_correct_and_control_is_not(bench, jax):
    result, control = run_cell(bench, jax, with_control=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["compared"]) == {"rows_mismatched",
                                       "requests_off_tier"}
    assert control["rows_mismatched"]["value"] > \
        control["rows_mismatched"]["limit"], control


def test_altered_answer_is_not_correct(bench, jax, monkeypatch):
    from ytsaurus_tpu.client import YtClient
    real = YtClient.select_rows
    calls = {"n": 0}

    def altered(self, query, *a, **kw):
        rows = real(self, query, *a, **kw)
        # timed calls carry no timeout= (warm-up calls all do); only the
        # third is altered: one wrong answer among many has to be enough
        calls["n"] += "timeout" not in kw
        if calls["n"] == 3 and "timeout" not in kw:
            rows = [dict(r) for r in rows]
            rows[-1]["low_line_count"] += 1
        return rows

    monkeypatch.setattr(YtClient, "select_rows", altered)
    result, _ = run_cell(bench, jax, seconds=4)
    assert calls["n"] >= 4, calls
    assert result["compared"]["rows_mismatched"]["value"] == 1
    assert not result["correct"], result["compared"]


def test_cell_reports_its_bytes_and_rows(bench, jax):
    """What the roofline share and the rate are computed from: the
    columns Q12 reads of both tables at the configuration's device
    widths, and both tables' rows per request."""
    args = run.parse_args(["--workload", CELL, "--seed", "1", "--seconds",
                           "1", "--rehearse"])
    ctx = run.make_context(bench, args, jax)
    sizes = ctx.config["rehearse_sizes"]
    assert ctx.driver.rows == sizes["rows"] + sizes["orders"]
    # l_orderkey + 3 dates (8 B) + l_shipmode (4 B); o_orderkey (8 B) +
    # o_orderpriority (4 B)
    assert ctx.driver.bytes_needed_per_request() == \
        36 * sizes["rows"] + 12 * sizes["orders"]


JOIN_COUNTERS = ("join_ms_per_select", "join_sync_ms_per_select")


def test_join_counter_definitions_read_the_window(bench, jax):
    """Every request line carries the seconds the program counted in the
    join; the count sync is inside them, and the join inside the
    evaluator's time."""
    args = run.parse_args(["--workload", CELL, "--seed", "2147483901",
                           "--seconds", "1.5", "--rehearse"])
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
        shutil.rmtree(state, ignore_errors=True)
    assert ctx.record.requests and not ctx.record.failures
    for line in ctx.record.requests:
        assert 0.0 < line["join_sync_s"] < line["join_s"] < \
            line["execute_s"] < line["wall_s"], line
    read = {stem: counter.read(run.metric_definition(stem), ctx)
            for stem in JOIN_COUNTERS + ("execute_ms_per_select",)}
    assert 0.0 < read["join_sync_ms_per_select"] < \
        read["join_ms_per_select"] < read["execute_ms_per_select"], read
    # the cell's own per-layer entries name these definitions
    mine = {entry["name"]: definition
            for entry, definition in ctx.metric_defs("per_layer")}
    assert set(mine) == {
        "join_ms_per_select.q12", "join_sync_ms_per_select.q12",
        "execute_ms_per_select.q12", "host_ms_per_select.q12",
        "window_compiles.q12", "device_idle.q12", "query_hbm_roofline.q12"}
    assert all(d["kind"] != "span" for d in mine.values())


def test_program_without_the_counters_gives_no_reading(bench, jax):
    """The parent's program under these files: its QueryStatistics has no
    `join_time`, so the lines carry None and the metric is left out of
    the result line (0.0 would read as a join that costs nothing)."""
    class Stats:
        execute_time, compile_count, execution_tier = 0.5, 0, "compiled"

    class Client:
        last_query_statistics = Stats()

    args = run.parse_args(["--workload", CELL, "--seed", "1", "--seconds",
                           "1", "--rehearse"])
    ctx = run.make_context(bench, args, jax)
    t0 = ctx.record.start()
    lines = select_join_stream.JoinLines(ctx.record, Client())
    lines.request("select", t0, t0 + 1.0, source_rows=10, execute_s=0.5,
                  compile_count=0, tier="compiled")
    (line,) = ctx.record.requests
    assert line["join_s"] is None and line["join_sync_s"] is None
    assert line["host_s"] == 0.5
    for stem in JOIN_COUNTERS:
        assert counter.read(run.metric_definition(stem), ctx) is None
    assert counter.read(run.metric_definition("execute_ms_per_select"),
                        ctx) == 500.0
