"""`correct` has to be able to fail in the Q3 cell too.  On the CPU, at the
rehearsal's sizes, in `test_correct.py`'s manner:

- the program comes out correct; the cell's control (the reference with
  every order joined to the NEXT customer's row: stage 2 broken, nothing
  else) fails `rows_mismatched`, and the reference held in float32 fails
  `rel_gap_max`, each by its own limit and no other;
- a run of the harness with the timed path broken underneath (one revenue
  altered by 1e-8; two rows swapped; a row's o_orderdate off by one), one
  answer among many, sees `correct` come out false;
- a `--rehearse` run of the cell ends and prints no metric;
- the two stage counters read a number from the cell's own window through
  the `counter` reader, and nothing (not 0) where the program's
  QueryStatistics has no `join_stage_seconds`, as the parent's has not.

    python3 -m pytest benchmark/tests -q
"""

import json
import os
import sys
import time

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from drivers import select_cascade_stream  # noqa: E402
from readers import counter  # noqa: E402

CELL = "tpch_q3_sf01"
Q3_METRICS = {
    "join_ms_per_select.q3", "join_sync_ms_per_select.q3",
    "execute_ms_per_select.q3", "host_ms_per_select.q3",
    "window_compiles.q3", "device_idle.q3", "query_hbm_roofline.q3",
    "join_stage1_ms_per_select.q3", "join_stage2_ms_per_select.q3"}


@pytest.fixture(scope="module")
def jax():
    return run.start_jax(rehearse=True)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_args(seed=2147483777, seconds=1.5):
    return run.parse_args(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--rehearse"])


def run_cell(bench, jax, **kw):
    return run.run_cell(bench, cell_args(**kw), jax, time.perf_counter())[0]


def broken(compared):
    return [name for name, pair in compared.items()
            if pair["value"] > pair["limit"]]


CONTROLS = {
    "stage_2_broken": (None, "rows_mismatched"),      # the traffic file's
    "float32": ({"kind": "precision", "dtype": "float32"}, "rel_gap_max"),
}


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_program_is_correct_and_control_is_not(bench, jax, name,
                                               monkeypatch):
    control, fails = CONTROLS[name]
    if control is not None:
        real = run.load_json

        def with_control(*parts):
            loaded = real(*parts)
            if parts == ("traffic", "q3_stream.json"):
                loaded["control"] = control
            return loaded
        monkeypatch.setattr(run, "load_json", with_control)
    result, read = run.run_cell(bench, cell_args(), jax,
                                time.perf_counter(), with_control=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["compared"]) == {"rows_mismatched", "rel_gap_max",
                                       "requests_off_tier"}
    assert broken(read) == [fails], read


def alter_revenue(rows):
    rows[0]["revenue"] *= 1 + 1e-8


def swap_rows(rows):
    rows[0], rows[1] = rows[1], rows[0]


def shift_date(rows):
    rows[-1]["o_orderdate"] += 1


@pytest.mark.parametrize("fault, fails", [
    (alter_revenue, "rel_gap_max"), (swap_rows, "rows_mismatched"),
    (shift_date, "rows_mismatched")],
    ids=["revenue_1e-8", "rows_swapped", "orderdate_off_by_one"])
def test_altered_answer_is_not_correct(bench, jax, monkeypatch, fault,
                                       fails):
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
            fault(rows)
        return rows

    monkeypatch.setattr(YtClient, "select_rows", altered)
    result = run_cell(bench, jax, seconds=4)
    assert calls["n"] >= 4, calls
    assert broken(result["compared"]) == [fails], result["compared"]
    assert not result["correct"]


def test_rehearsal_ends_and_prints_no_metric(capsys):
    assert run.main(["--workload", CELL, "--seed", "4294967397",
                     "--seconds", "1", "--trace", "0", "--rehearse"]) == 0
    captured = capsys.readouterr()
    result = json.loads(captured.out.strip().splitlines()[-1])
    assert result["correct"] and result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert "REHEARSAL on cpu" in captured.err


def test_cell_reports_its_bytes_rows_and_metrics(bench, jax):
    """What the roofline share and the rate are computed from: the columns
    Q3 reads of the three tables at the configuration's device widths,
    and the three tables' rows per request."""
    ctx = run.make_context(bench, cell_args(), jax)
    sizes = ctx.config["rehearse_sizes"]
    assert ctx.driver.rows == sizes["rows"] + sizes["orders"] + \
        sizes["customers"]
    # l_orderkey, l_extendedprice, l_discount, l_shipdate; o_orderkey,
    # o_custkey, o_orderdate, o_shippriority (8 B each); c_custkey (8 B) +
    # c_mktsegment (4 B)
    assert ctx.driver.bytes_needed_per_request() == \
        32 * sizes["rows"] + 32 * sizes["orders"] + 12 * sizes["customers"]
    full = ctx.config["sizes"]
    assert full["rows"] + full["orders"] + full["customers"] == 765572
    mine = {entry["name"]: definition
            for entry, definition in ctx.metric_defs("per_layer")}
    assert set(mine) == Q3_METRICS
    assert all(d["kind"] != "span" for d in mine.values())
    assert {entry["name"] for entry, _ in ctx.metric_defs("end_to_end")} \
        == {"scan_rows_per_s", "setup_s"}


STAGE_COUNTERS = ("join_stage1_ms_per_select", "join_stage2_ms_per_select")


def test_stage_counter_definitions_read_the_window(bench, jax):
    """Every request line carries the seconds the program counted in
    each join stage; together they are the cascade's seconds."""
    from ytsaurus_tpu.client import connect
    import shutil
    ctx = run.make_context(bench, cell_args(seed=2147483901), jax)
    ctx.driver.prepare()
    state = run.state_dir()
    try:
        root = os.path.join(state, "cluster")
        ctx.driver.load(connect(root))
        yt = connect(root, fresh=True)
        ctx.driver.warm(yt)
        ctx.record.setup_s = 0.0
        ctx.driver.window(yt, 1.5, ctx.record)
    finally:
        shutil.rmtree(state, ignore_errors=True)
    assert ctx.record.requests and not ctx.record.failures
    for line in ctx.record.requests:
        assert line["join_stage1_s"] > 0 and line["join_stage2_s"] > 0
        assert "join_stage3_s" not in line
        assert line["join_stage1_s"] + line["join_stage2_s"] == \
            pytest.approx(line["join_s"])
        assert line["join_sync_s"] < line["join_s"] < line["execute_s"] \
            < line["wall_s"]
    read = {stem: counter.read(run.metric_definition(stem), ctx)
            for stem in STAGE_COUNTERS + ("join_ms_per_select",)}
    assert all(value > 0 for value in read.values()), read
    assert max(read[stem] for stem in STAGE_COUNTERS) < \
        read["join_ms_per_select"]


def test_program_without_the_counter_gives_no_reading(bench, jax):
    """The parent's program under these files: its QueryStatistics has
    `join_time` and no `join_stage_seconds`, so the lines carry no stage
    field and the two metrics are left out of the result line."""
    class Stats:
        execute_time, compile_count, execution_tier = 0.5, 0, "compiled"
        join_time, join_sync_time = 0.25, 0.125

    class Client:
        last_query_statistics = Stats()

    ctx = run.make_context(bench, cell_args(), jax)
    t0 = ctx.record.start()
    lines = select_cascade_stream.CascadeLines(ctx.record, Client())
    lines.request("select", t0, t0 + 1.0, source_rows=10, execute_s=0.5,
                  compile_count=0, tier="compiled")
    (line,) = ctx.record.requests
    assert line["join_s"] == 0.25 and "join_stage1_s" not in line
    for stem in STAGE_COUNTERS:
        assert counter.read(run.metric_definition(stem), ctx) is None
    assert counter.read(run.metric_definition("join_ms_per_select"),
                        ctx) == 250.0
