"""The two-table TPC-H deployment (ISSUE 30): ORDERS generated consistently
with LINEITEM, Q12's equi-join through `client.select_rows` against the
benchmark's plain numpy reference (exact), the spans and counters the join
adds, and the benchmark cell's control.  CPU, tiny sizes; the tables are
published by the benchmark's own driver.
"""

import json
import os
import sys
import time
from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from drivers import select_join_stream  # noqa: E402
from generators import tpch_dbgen, tpch_dbgen_orders  # noqa: E402
from reference import tpch_join_spec  # noqa: E402

from ytsaurus_tpu.utils.tracing import get_collector, span_tree  # noqa: E402

CELL = "tpch_q12_join"
SEEDS = [7, 2147483659, 4294967311]       # the driver's seeds pass 2**31
SIZES = {"rows": 20004, "orders": 5000, "parts": 700, "suppliers": 40,
         "customers": 600, "clerks": 5}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def make_driver(bench, seed, sizes=SIZES):
    """The cell's driver over tables of `sizes`, host arrays made."""
    ctx = run.Context(bench, CELL, seed, rehearse=True)
    ctx.config["rehearse_sizes"] = sizes
    driver = select_join_stream.Driver(ctx)
    driver.prepare()
    return driver


def connect(tmp_path_factory, name):
    from ytsaurus_tpu.client import connect
    return connect(str(tmp_path_factory.mktemp(name)))


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    """(client, driver) of the first seed: both tables as the driver
    publishes them."""
    driver = make_driver(bench, SEEDS[0])
    client = connect(tmp_path_factory, "q12")
    driver.load(client)
    return client, driver


def q12(driver):
    return driver.queries[0]


def mismatched(rows, spec, driver, tables=None):
    want = tpch_join_spec.evaluate(spec, tables or driver.host,
                                   driver.vocabs)
    assert want, "an empty answer compares nothing"
    return tpch_join_spec.compare(rows, want)


# -- the ORDERS generator -----------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_orders_are_consistent_with_the_lines(bench, seed):
    driver = make_driver(bench, seed)
    lines, orders = driver.host["lineitem"], driver.host["orders"]
    n = SIZES["orders"]
    assert set(orders) == {c["name"] for c in
                           driver.tables["orders"]["columns"]}
    assert all(len(column) == n for column in orders.values())
    # the same sparse keys, stored sorted; every line has its order
    keys = orders["o_orderkey"]
    assert np.array_equal(keys, tpch_dbgen.sparse_order_keys(n))
    assert np.all(np.diff(keys) > 0)
    assert np.array_equal(np.unique(lines["l_orderkey"]), keys)
    order_of_line = np.searchsorted(keys, lines["l_orderkey"])
    # o_orderdate is the date the lines' dates hang on
    date = orders["o_orderdate"][order_of_line]
    assert np.all((lines["l_shipdate"] - date >= 1) &
                  (lines["l_shipdate"] - date <= 121))
    assert np.all((lines["l_commitdate"] - date >= 30) &
                  (lines["l_commitdate"] - date <= 90))
    assert tpch_dbgen.START_DATE <= date.min() and \
        date.max() <= tpch_dbgen.END_DATE - 151
    # o_orderstatus from the lines' statuses
    open_lines = np.bincount(order_of_line, lines["l_linestatus"], n)
    all_lines = np.bincount(order_of_line, minlength=n)
    want = np.where(open_lines == 0, "F",
                    np.where(open_lines == all_lines, "O", "P"))
    status = np.array(driver.vocabs["o_orderstatus"])[orders["o_orderstatus"]]
    assert np.array_equal(status, want)
    # o_totalprice = sum of price x (1 + tax) x (1 - discount), to the cent
    total = np.bincount(order_of_line, lines["l_extendedprice"] *
                        (1 + lines["l_tax"]) * (1 - lines["l_discount"]), n)
    assert np.abs(orders["o_totalprice"] - total).max() < 0.00501
    assert np.allclose(orders["o_totalprice"] * 100,
                       np.rint(orders["o_totalprice"] * 100), atol=1e-6)
    # the columns of ORDERS' own
    custkey = orders["o_custkey"]
    assert custkey.min() >= 1 and custkey.max() <= SIZES["customers"]
    assert np.all(custkey % 3 != 0)
    assert driver.vocabs["o_orderpriority"] == [
        "1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
    assert set(orders["o_orderpriority"]) == set(range(5))
    assert driver.vocabs["o_clerk"][0] == "Clerk#000000001" and \
        driver.vocabs["o_clerk"][-1] == f"Clerk#{SIZES['clerks']:09d}"
    assert set(orders["o_clerk"]) == set(range(SIZES["clerks"]))
    assert not orders["o_shippriority"].any()
    lengths = np.char.str_len(orders["o_comment"])
    assert lengths.min() >= 19 and lengths.max() <= 78
    # the lines of a seed are the same with or without this table
    alone, _ = tpch_dbgen.generate(driver.config, seed, SIZES)
    assert all(np.array_equal(alone[name], lines[name]) for name in alone)


def test_orders_refuse_lines_of_another_seed(bench):
    driver = make_driver(bench, 11)
    with pytest.raises(ValueError, match="not made from this seed"):
        tpch_dbgen_orders.generate(driver.config, 12, SIZES,
                                   driver.host["lineitem"])


# -- the plain reference ------------------------------------------------------

def test_reference_join_is_many_to_many_inner_and_left():
    probe = np.array([5, 1, 9, 5, 7])
    build = np.array([5, 9, 5, 2, 9, 9])
    pairs = lambda *a, **kw: [  # noqa: E731
        (int(p), int(b) if m else None)
        for p, b, m in zip(*tpch_join_spec.equi_join(*a, **kw))]
    brute = [(i, j) for i, key in enumerate(probe)
             for j, other in enumerate(build) if key == other]
    assert pairs(probe, build) == brute
    left = []
    for i in range(len(probe)):
        left.extend([p for p in brute if p[0] == i] or [(i, None)])
    assert pairs(probe, build, kind="left") == left
    assert pairs(probe, build, shift=1) == [(i, (j + 1) % 6)
                                            for i, j in brute]
    assert pairs(probe, np.array([], dtype=np.int64)) == []


def test_reference_expressions_carry_null_as_sql_does():
    class Columns(dict):
        def __len__(self):
            return 4
    ok = np.ones(4, dtype=bool)
    columns = Columns(a=(np.array([0, 1, 2, 3]), ok),
                      s=(np.array([0, 1, 0, 1]),
                         np.array([True, True, False, False])))
    vocabs = {"s": ["x", "y"]}

    def value(text):
        values, valid = tpch_join_spec.evaluate_expr(text, columns, vocabs)
        return [v if known else None
                for v, known in zip(values.tolist(), valid.tolist())]

    assert value("s == 'y'") == [False, True, None, None]
    assert value("s != 'y'") == [True, False, None, None]
    assert value("s == 'absent'") == [False, False, None, None]
    assert value("s in ('x', 'y')") == [True, True, None, None]
    assert value("s == 'y' or a >= 3") == [False, True, None, True]
    assert value("s == 'y' and a < 3") == [False, True, None, False]
    assert value("if_(s == 'x', 1, 0)") == [1, 0, None, None]
    assert value("is_null(s)") == [False, False, True, True]
    with pytest.raises(ValueError, match="string literal"):
        value("s < 'y'")


# -- Q12 through select_rows --------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_q12_equals_the_reference(bench, tmp_path_factory, deployment, seed):
    client, driver = deployment
    if seed != SEEDS[0]:
        driver = make_driver(bench, seed)
        client = connect(tmp_path_factory, f"q12-{seed}")
        driver.load(client)
    rows = client.select_rows(q12(driver)["ql"])
    assert [r["l_shipmode"] for r in rows] == [b"MAIL", b"SHIP"]
    assert all(r["high_line_count"] > 0 and r["low_line_count"] > 0
               for r in rows)
    assert mismatched(rows, q12(driver)["reference"], driver) == 0
    stats = client.last_query_statistics
    assert stats.execution_tier == "compiled"
    assert stats.joins_executed == 1 and stats.join_host_syncs == 1
    # every line has exactly one order
    assert stats.join_rows_out == SIZES["rows"]
    assert 0.0 < stats.join_sync_time < stats.join_time < stats.execute_time


LEFT_QL = (
    "l_shipmode, sum(1) AS lines, "
    "sum(if(is_null(o_orderpriority), 1, 0)) AS orphans, "
    "sum(if(o_orderpriority = '1-URGENT' OR o_orderpriority = '2-HIGH', "
    "1, 0)) AS high_line_count FROM [{lineitem}] LEFT JOIN [{orders}] "
    "ON l_orderkey = o_orderkey WHERE l_shipmode IN ('MAIL', 'SHIP') "
    "GROUP BY l_shipmode ORDER BY l_shipmode LIMIT 10")
LEFT_SPEC = {
    "from": "lineitem",
    "join": {"table": "orders", "kind": "left",
             "on": ["l_orderkey", "o_orderkey"]},
    "filter": "l_shipmode in ('MAIL', 'SHIP')",
    "group_by": ["l_shipmode"],
    "aggregates": [
        {"name": "lines", "fn": "sum", "expr": "1"},
        {"name": "orphans", "fn": "sum",
         "expr": "if_(is_null(o_orderpriority), 1, 0)"},
        {"name": "high_line_count", "fn": "sum",
         "expr": "if_(o_orderpriority == '1-URGENT' or "
                 "o_orderpriority == '2-HIGH', 1, 0)"}],
    "order_by": [["l_shipmode", "asc"]], "limit": 10}


@pytest.fixture(scope="module")
def withheld(bench, tmp_path_factory):
    """The deployment with every tenth order withheld: its lines are
    orphans."""
    driver = make_driver(bench, SEEDS[1])
    keep = np.arange(SIZES["orders"]) % 10 != 3
    driver.host["orders"] = {name: column[keep] for name, column
                             in driver.host["orders"].items()}
    client = connect(tmp_path_factory, "withheld")
    driver.load(client)
    return client, driver


def test_inner_join_drops_the_orphan_lines(withheld):
    client, driver = withheld
    rows = client.select_rows(q12(driver)["ql"])
    assert mismatched(rows, q12(driver)["reference"], driver) == 0
    assert client.last_query_statistics.join_rows_out < SIZES["rows"]
    # and the answer is not the whole deployment's
    whole = make_driver_like(driver)
    assert mismatched(rows, q12(driver)["reference"], whole) > 0


def make_driver_like(driver):
    """A driver of the same seed with no order withheld."""
    ctx = driver.ctx
    whole = select_join_stream.Driver(ctx)
    whole.prepare()
    return whole


def test_left_join_keeps_the_orphan_lines(withheld):
    client, driver = withheld
    paths = {name: t["path"] for name, t in driver.tables.items()}
    rows = client.select_rows(LEFT_QL.format(**paths))
    assert mismatched(rows, LEFT_SPEC, driver) == 0
    assert all(0 < r["orphans"] < r["lines"] for r in rows)
    assert client.last_query_statistics.join_rows_out == SIZES["rows"]
    # as INNER the reference counts no orphan: the two differ
    inner = dict(LEFT_SPEC, join=dict(LEFT_SPEC["join"], kind="inner"))
    assert mismatched(rows, inner, driver) > 0


def test_duplicate_foreign_keys_join_many_to_many(deployment):
    client, driver = deployment
    rng = np.random.default_rng(5)
    keys = driver.host["orders"]["o_orderkey"]
    # every order 0 to 3 times, shuffled: the foreign side is not sorted
    dims = rng.permutation(np.repeat(keys, rng.integers(0, 4, len(keys))))
    host = {"d_orderkey": dims, "d_weight": rng.integers(1, 100, len(dims))}
    select_join_stream.publish(
        client, "//tpch/dims",
        [{"name": "d_orderkey", "type": "int64"},
         {"name": "d_weight", "type": "int64"}], host, {})
    rows = client.select_rows(
        "l_shipmode, sum(d_weight) AS weight, sum(1) AS pairs "
        "FROM [//tpch/lineitem] JOIN [//tpch/dims] ON l_orderkey = "
        "d_orderkey GROUP BY l_shipmode ORDER BY l_shipmode LIMIT 10")
    spec = {"from": "lineitem",
            "join": {"table": "dims", "kind": "inner",
                     "on": ["l_orderkey", "d_orderkey"]},
            "group_by": ["l_shipmode"],
            "aggregates": [{"name": "weight", "fn": "sum",
                            "expr": "d_weight"},
                           {"name": "pairs", "fn": "sum", "expr": "1"}],
            "order_by": [["l_shipmode", "asc"]], "limit": 10}
    tables = {"lineitem": driver.host["lineitem"], "dims": host}
    assert len(rows) == 7
    assert mismatched(rows, spec, driver, tables) == 0
    pairs = client.last_query_statistics.join_rows_out
    assert pairs == sum(r["pairs"] for r in rows) != SIZES["rows"]


def test_two_key_on_joins_on_both(deployment):
    client, driver = deployment
    rng = np.random.default_rng(6)
    lines = driver.host["lineitem"]
    # per line 0 to 2 rows keyed by (order, line number), shuffled
    reps = rng.integers(0, 3, len(lines["l_orderkey"]))
    perm = rng.permutation(int(reps.sum()))
    host = {"p_orderkey": np.repeat(lines["l_orderkey"], reps)[perm],
            "p_line": np.repeat(lines["l_linenumber"], reps)[perm],
            "p_weight": rng.integers(1, 100, len(perm))}
    select_join_stream.publish(
        client, "//tpch/pairs",
        [{"name": "p_orderkey", "type": "int64"},
         {"name": "p_line", "type": "int64"},
         {"name": "p_weight", "type": "int64"}], host, {})
    rows = client.select_rows(
        "l_shipmode, sum(p_weight) AS weight, sum(1) AS pairs "
        "FROM [//tpch/lineitem] JOIN [//tpch/pairs] ON l_orderkey = "
        "p_orderkey AND l_linenumber = p_line GROUP BY l_shipmode "
        "ORDER BY l_shipmode LIMIT 10")
    # the reference joins on one key: (order, line) folded into one whole
    # number on the host (line numbers are 1..7)
    spec = {"from": "lineitem",
            "join": {"table": "pairs", "kind": "inner",
                     "on": ["l_pairkey", "p_pairkey"]},
            "group_by": ["l_shipmode"],
            "aggregates": [{"name": "weight", "fn": "sum",
                            "expr": "p_weight"},
                           {"name": "pairs", "fn": "sum", "expr": "1"}],
            "order_by": [["l_shipmode", "asc"]], "limit": 10}
    tables = {
        "lineitem": dict(lines, l_pairkey=lines["l_orderkey"] * 8
                         + lines["l_linenumber"]),
        "pairs": dict(host, p_pairkey=host["p_orderkey"] * 8
                      + host["p_line"])}
    assert len(rows) == 7
    assert mismatched(rows, spec, driver, tables) == 0
    assert client.last_query_statistics.join_rows_out == len(perm)
    # joined on the order alone the answer would hold more pairs
    on_order = dict(spec, join=dict(spec["join"],
                                    on=["l_orderkey", "p_orderkey"]))
    assert mismatched(rows, on_order, driver, tables) > 0


# -- the foreign side's sort ----------------------------------------------------

@pytest.mark.parametrize("dtype", ["int64", "int32", "float64", "int8"])
@pytest.mark.parametrize("n_keys", [1, 2])
def test_foreign_sort_order(dtype, n_keys):
    """What the probe's search counts on, whatever engine sorts (today
    one variadic `lax.sort`; ROADMAP's join queue would swap it): the
    stable permutation by (masked rows last, then per key NULL first,
    then value), first key most significant."""
    import jax.numpy as jnp

    from ytsaurus_tpu.query.engine.joins import sort_foreign_keys
    rng = np.random.default_rng(n_keys)
    n = 4096
    keys = []
    for _ in range(n_keys):
        valid = rng.random(n) < 0.9
        data = rng.integers(-40, 40, n).astype(dtype)
        if dtype == "int64":
            data = data * (1 << 40) + rng.integers(0, 3, n)
        keys.append((valid.astype(np.int8),
                     np.where(valid, data, 0).astype(dtype)))
    row_valid = rng.random(n) < 0.8
    planes = []
    for v, d in reversed(keys):
        planes.extend([d, v])
    planes.append(~row_valid)
    want = np.lexsort(planes)
    order, ordered = sort_foreign_keys(
        [(jnp.asarray(v), jnp.asarray(d)) for v, d in keys],
        jnp.asarray(row_valid))
    assert np.array_equal(np.asarray(order), want)
    for (v, d), (sv, sd) in zip(keys, ordered):
        assert np.array_equal(np.asarray(sv), v[want])
        assert np.array_equal(np.asarray(sd), d[want])


# -- spans and counters -------------------------------------------------------

def _by_name(trace_id):
    out = {}
    for span in get_collector().find(trace_id):
        out.setdefault(span.name, []).append(span)
    return out


def test_join_select_opens_the_join_spans(deployment):
    client, driver = deployment
    client.select_rows(q12(driver)["ql"])               # compile
    profile = client.select_rows(q12(driver)["ql"], explain_analyze=True)
    spans = _by_name(profile.trace_id)
    (join,), (sync,) = spans["evaluator.join"], spans["join.count_sync"]
    by_id = {s.span_id: s for group in spans.values() for s in group}
    assert by_id[join.parent_span_id].name == "evaluator.run_plan"
    assert by_id[sync.parent_span_id] is join
    capacity = 1 << int(np.ceil(np.log2(SIZES["rows"])))
    assert join.tags == {
        "table": "//tpch/orders", "stage": 0, "self_rows": SIZES["rows"],
        "foreign_rows": SIZES["orders"], "out_rows": SIZES["rows"],
        "out_capacity": capacity, "cache": "hit",
        # Q12 reads 5 of the two tables' 25 columns after its join
        "columns_out": 5, "columns_pruned": 20}
    assert join.duration >= sync.duration > 0
    # the join runs before the main program is looked up
    assert join.start_mono + join.duration <= \
        spans["evaluator.prepare"][0].start_mono
    # both tables staged under the one stage span
    assert spans["query.stage"][0].tags["chunks"] == 2
    from ytsaurus_tpu.query.profile import format_span_tree
    tree = "\n".join(format_span_tree(span_tree(profile.trace_id)))
    assert "evaluator.join" in tree and "join.count_sync" in tree
    assert "out_rows=%d" % SIZES["rows"] in tree
    text = profile.format()
    assert "ms in the cascade" in text
    assert "in 1 host syncs between phases" in text
    assert f"{SIZES['rows']} rows materialized" in text
    # the counters are the spans' seconds, on the same clock
    stats = client.last_query_statistics
    assert stats.join_time == pytest.approx(join.duration, abs=2e-3)
    assert stats.join_sync_time == pytest.approx(sync.duration, abs=2e-3)


def test_first_join_select_says_cache_miss(bench, tmp_path_factory):
    driver = make_driver(bench, 3, dict(SIZES, rows=9000, orders=2100))
    client = connect(tmp_path_factory, "miss")
    driver.load(client)
    profile = client.select_rows(q12(driver)["ql"], explain_analyze=True)
    (join,) = _by_name(profile.trace_id)["evaluator.join"]
    assert join.tags["cache"] == "miss"


def test_q1_opens_exactly_the_spans_it_opened_before(deployment):
    from test_select_spans import SELECT_SPANS
    client, _ = deployment
    q1 = run.load_json("traffic", "q1_stream.json")["queries"][0]["ql"]
    q1 = q1.format(table="//tpch/lineitem")
    client.select_rows(q1)                              # compile
    profile = client.select_rows(q1, explain_analyze=True)
    assert len(profile.rows) == 4
    spans = _by_name(profile.trace_id)
    assert {name: len(s) for name, s in spans.items()} == SELECT_SPANS
    stats = client.last_query_statistics
    assert stats.joins_executed == stats.join_host_syncs == \
        stats.join_rows_out == 0
    assert stats.join_time == stats.join_sync_time == 0.0
    assert "join plan" not in profile.format()


def join_phase_programs(stage, chunk, foreign):
    """(phase1, args1, phase2, args2): the two jitted programs of one
    join stage (an `ir.JoinStage`: the join and the namespace it
    materializes, both cut to the live columns) and what `execute_join`
    calls them with, caught on the way through one real call with an
    empty program cache."""
    from ytsaurus_tpu.query.engine import joins
    caught = []
    build = joins._build_join_programs

    def catching(*a, **kw):
        phase1, make_phase2 = build(*a, **kw)

        def catch(program):
            def call(*args):
                caught.extend([program, args])
                return program(*args)
            return call
        return catch(phase1), lambda cap: catch(make_phase2(cap))

    with mock.patch.object(joins, "_build_join_programs", catching):
        joins.execute_join(chunk, stage.schema, stage.join, foreign, {})
    return tuple(caught)


def cascade_intermediate(plan, chunk, foreign_chunks, stages):
    """(the plan's `ir.JoinCascade`, its intermediate after `stages`
    joins), made as `evaluator._dispatch_traced` makes it: the FROM
    chunk projected to `from_schema`, then one `execute_join` a stage."""
    from ytsaurus_tpu.query import ir
    from ytsaurus_tpu.chunks.columnar import project_chunk
    from ytsaurus_tpu.query.engine import joins
    cascade = ir.join_cascade(plan)
    current = project_chunk(chunk, cascade.from_schema)
    for stage in cascade.stages[:stages]:
        current = joins.execute_join(
            current, stage.schema, stage.join,
            foreign_chunks[stage.join.foreign_table], {})
    return cascade, current


def q12_join_inputs(client, driver):
    """(join stage, probe chunk, foreign chunk) of the cell's query over
    the tables `client` holds."""
    from ytsaurus_tpu.client import _SchemaResolver
    from ytsaurus_tpu.query.builder import build_query
    plan = build_query(q12(driver)["ql"], _SchemaResolver(client))
    lines = client._query_shards("//tpch/lineitem", 2 ** 62)[0]
    orders = client._query_shards("//tpch/orders", 2 ** 62)[0]
    cascade, probe = cascade_intermediate(plan, lines, {}, stages=0)
    return cascade.stages[0], probe, orders


def test_join_scopes_name_the_phase_programs(deployment):
    """`ql.join.sort`, `.probe` and `.expand` reach the programs' op
    names, where a device trace finds them."""
    phase1, args1, phase2, args2 = join_phase_programs(
        *q12_join_inputs(*deployment))
    text = phase1.lower(*args1).as_text(debug_info=True)
    assert "ql.join.sort" in text and "ql.join.probe" in text
    assert "ql.join.expand" not in text
    text = phase2.lower(*args2).as_text(debug_info=True)
    assert "ql.join.expand" in text


# -- the cell's control -------------------------------------------------------

def test_cell_is_correct_and_its_control_is_not(bench):
    """The tier-1 twin of benchmark/tests/test_join_correct.py: the
    harness drives the cell at the rehearsal's sizes; the program's
    answers are exact, the reference with every line joined to the next
    order's row is not."""
    jax = run.start_jax(rehearse=True)
    args = run.parse_args(["--workload", CELL, "--seed", "2147483700",
                           "--seconds", "1", "--rehearse"])
    result, control = run.run_cell(bench, args, jax, time.perf_counter(),
                                   with_control=True)
    assert result["correct"] and result["failed"] == 0, result
    assert result["compared"]["rows_mismatched"] == \
        {"value": 0, "limit": 0}
    assert result["compared"]["requests_off_tier"] == \
        {"value": 0, "limit": 0}
    assert control["rows_mismatched"]["value"] > 0
