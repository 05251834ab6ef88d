"""`correct` has to be able to fail in the dynamic-table Q1 cell too.  On
the CPU, at the rehearsal's sizes, in `test_q3_correct.py`'s manner:

- the program comes out correct; the cell's control (the reference with
  refresh pair 2 left out: an acknowledged write not read back) fails
  `rows_mismatched` by its `count_order` values, and `rel_gap_max` with it
  (every sum moves with the writes left out); the reference held in
  float32 fails `rel_gap_max` and no other;
- a run of the harness with the write path broken underneath (one RF2
  delete dropped; one RF1 line not inserted) sees `correct` come out false;
- a `--rehearse` run of the cell ends and prints no metric;
- the snapshot and fan-in counters read a number from the cell's own
  window through the `counter` reader, and nothing (not 0) where the
  program's QueryStatistics has no such counter;
- a program whose staging lowers new programs for new row counts is
  refused before the set-up starts.

    python3 -m pytest benchmark/tests -q
"""

import json
import os
import sys
import time

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from drivers import select_dynamic_stream  # noqa: E402
from readers import counter  # noqa: E402

CELL = "tpch_q1_dyn_8tablets"
SHIPDATE_CUT = 10471                      # Q1's filter keeps l_shipdate <= it
DYN_METRICS = {
    "snapshot_ms_per_select.dyn", "coalesce_ms_per_select.dyn",
    "snapshot_misses.dyn", "host_ms_per_select.dyn",
    "execute_ms_per_select.dyn", "window_compiles.dyn", "device_idle.dyn",
    "query_hbm_roofline.dyn", "idle_unspanned.dyn"}
DYN_SPANS = {stem + ".dyn" for stem in (
    "admission_ms_per_select", "plan_ms_per_select", "stage_ms_per_select",
    "prepare_ms_per_select", "launch_ms_per_select", "sync_ms_per_select",
    "decode_ms_per_select", "record_ms_per_select", "select_unspanned_ms")}
COUNTERS = ("snapshot_ms_per_select", "coalesce_ms_per_select",
            "snapshot_misses")


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


def broken(compared):
    return [name for name, pair in compared.items()
            if pair["value"] > pair["limit"]]


CONTROLS = {
    "pair_2_left_out": (None, ["rows_mismatched", "rel_gap_max"]),
    "float32": ({"kind": "precision", "dtype": "float32"}, ["rel_gap_max"]),
}


@pytest.mark.parametrize("name", sorted(CONTROLS))
def test_program_is_correct_and_control_is_not(bench, jax, name,
                                               monkeypatch):
    control, fails = CONTROLS[name]
    if control is not None:
        real = run.load_json

        def with_control(*parts):
            loaded = real(*parts)
            if parts == ("traffic", "q1_dyn_stream.json"):
                loaded["control"] = control
            return loaded
        monkeypatch.setattr(run, "load_json", with_control)
    result, read = run.run_cell(bench, cell_args(), jax,
                                time.perf_counter(), with_control=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["compared"]) == {"rows_mismatched", "rel_gap_max",
                                       "requests_off_tier"}
    assert sorted(broken(read)) == sorted(fails), read


def drop_one_delete(drivers, keys):
    """RF2's keys less one line that Q1 counts."""
    base = drivers[0].base
    for index, (order, line) in enumerate(keys):
        at = (base["l_orderkey"] == order) & (base["l_linenumber"] == line)
        if base["l_shipdate"][at][0] <= SHIPDATE_CUT:
            return keys[:index] + keys[index + 1:]
    raise AssertionError("no deleted line passes Q1's filter")


def lose_one_line(drivers, rows):
    """RF1's rows less one line that Q1 counts."""
    for index, row in enumerate(rows):
        if row["l_shipdate"] <= SHIPDATE_CUT:
            return rows[:index] + rows[index + 1:]
    raise AssertionError("no new line passes Q1's filter")


@pytest.mark.parametrize("fault", [drop_one_delete, lose_one_line],
                         ids=["rf2_delete_dropped", "rf1_line_not_inserted"])
def test_broken_write_path_is_not_correct(bench, jax, monkeypatch, fault):
    """The first refresh call of the kind the fault breaks loses one write
    that Q1 reads; the acknowledgement still comes back."""
    from ytsaurus_tpu.client import YtClient
    drivers, done = [], []
    real_prepare = select_dynamic_stream.Driver.prepare

    def prepare(self):
        real_prepare(self)
        drivers.append(self)

    method = "delete_rows" if fault is drop_one_delete else "insert_rows"
    real_write = getattr(YtClient, method)

    def write(self, path, batch, *a, **kw):
        batch = list(batch)
        # a refresh batch: RF2's keys, or RF1's new orders (bits 3-4 set)
        refresh = method == "delete_rows" or \
            (batch[0]["l_orderkey"] >> 3) & 3
        if refresh and not done:
            batch = fault(drivers, batch)
            done.append(path)
        return real_write(self, path, batch, *a, **kw)

    monkeypatch.setattr(select_dynamic_stream.Driver, "prepare", prepare)
    monkeypatch.setattr(YtClient, method, write)
    result = run.run_cell(bench, cell_args(seconds=2), jax,
                          time.perf_counter())[0]
    assert done
    assert "rows_mismatched" in broken(result["compared"])
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
    """What the roofline share and the rate are computed from: the 7
    columns Q1 reads at the configuration's device widths, and the rows
    visible after both refresh pairs."""
    ctx = run.make_context(bench, cell_args(), jax)
    ctx.driver.prepare()
    driver = ctx.driver
    sizes = ctx.config["rehearse_sizes"]
    pairs = driver.pairs
    assert driver.rows == sizes["rows"] + sum(
        len(p["insert"]["l_orderkey"]) - len(p["delete"]) for p in pairs)
    # two flags of 4 B, quantity, price, discount, tax, shipdate of 8 B
    assert driver.bytes_needed_per_request() == 48 * driver.rows
    full = ctx.config["sizes"]
    assert full["rows"] == 600572 and full["refresh_orders"] == \
        full["orders"] // 1000
    mine = {entry["name"]: definition
            for entry, definition in ctx.metric_defs("per_layer")}
    assert set(mine) == DYN_METRICS | DYN_SPANS
    assert {name for name, d in mine.items() if d["kind"] == "span"} == \
        DYN_SPANS
    assert {entry["name"] for entry, _ in ctx.metric_defs("end_to_end")} \
        == {"scan_rows_per_s", "setup_s"}


def test_counter_definitions_read_the_window(bench, jax):
    """Every request line carries the snapshot and fan-in counters; in a
    window that writes nothing no snapshot is merged anew."""
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
        assert line["shards_coalesced"] == 8
        assert line["snapshot_cache_misses"] == 0
        assert 0 < line["snapshot_s"] and 0 < line["coalesce_s"] \
            < line["host_s"] < line["wall_s"]
    read = {stem: counter.read(run.metric_definition(stem), ctx)
            for stem in COUNTERS}
    assert read["snapshot_misses"] == 0.0
    assert read["snapshot_ms_per_select"] > 0 and \
        read["coalesce_ms_per_select"] > 0


def test_program_without_the_counters_gives_no_reading(bench, jax):
    """The parent's program under these files: its QueryStatistics has
    none of the four counters, so the lines carry None there and the
    three metrics are left out of the result line."""
    class Stats:
        execute_time, compile_count, execution_tier = 0.5, 0, "compiled"

    class Client:
        last_query_statistics = Stats()

    ctx = run.make_context(bench, cell_args(), jax)
    t0 = ctx.record.start()
    lines = select_dynamic_stream.DynamicLines(ctx.record, Client())
    lines.request("select", t0, t0 + 1.0, source_rows=10, execute_s=0.5,
                  compile_count=0, tier="compiled")
    (line,) = ctx.record.requests
    assert line["snapshot_s"] is None and line["coalesce_s"] is None
    for stem in COUNTERS:
        assert counter.read(run.metric_definition(stem), ctx) is None
    assert counter.read(run.metric_definition("execute_ms_per_select"),
                        ctx) == 500.0


def test_staging_that_compiles_per_row_count_is_refused(bench, jax,
                                                        monkeypatch):
    """A concatenation that slices each part at its row count lowers new
    programs for every new row total: the driver refuses such a program
    before generating any data."""
    import jax.numpy as jnp
    from ytsaurus_tpu.chunks import columnar

    def per_row_count(datas, valids, offsets, total, capacity, dtype):
        ends = list(np.asarray(offsets)[1:]) + [int(total)]
        counts = np.diff([0] + ends)
        data = jnp.concatenate([d[:n].astype(dtype)
                                for d, n in zip(datas, counts)])
        valid = jnp.concatenate([v[:n] for v, n in zip(valids, counts)])
        pad = capacity - int(total)
        return (jnp.pad(data, [(0, pad)] + [(0, 0)] * (data.ndim - 1)),
                jnp.pad(valid, (0, pad)))

    monkeypatch.setattr(columnar, "_concat_planes", per_row_count)
    ctx = run.make_context(bench, cell_args(), jax)
    with pytest.raises(SystemExit, match="lowered"):
        ctx.driver.prepare()
    assert ctx.driver.pairs is None
