"""The three-table TPC-H deployment (ISSUE 34): CUSTOMER generated beside
ORDERS and LINEITEM, Q3's two-stage join cascade through
`client.select_rows` against the benchmark's plain numpy reference
(`tpch_cascade_spec`: keys exact, revenue by its relative gap), the counters
and tags the cascade adds, and the benchmark cell's controls.  CPU, tiny
sizes; the tables are published by the benchmark's own driver.
"""

import copy
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from drivers import select_cascade_stream, select_join_stream  # noqa: E402
from generators import tpch_dbgen_customer  # noqa: E402
from reference import tpch_cascade_spec  # noqa: E402

from ytsaurus_tpu import config as yt_config  # noqa: E402
from ytsaurus_tpu.utils.tracing import get_collector  # noqa: E402

CELL = "tpch_q3_sf01"
SEEDS = [7, 2147483659, 4294967311]       # the driver's seeds pass 2**31
SIZES = {"rows": 20004, "orders": 5000, "parts": 700, "suppliers": 40,
         "customers": 600, "clerks": 5}
GAP_LIMIT = 1e-10                          # the cell's `rel_gap_max` limit
DATE = 9204                                # 1995-03-15


@pytest.fixture(autouse=True)
def _fresh_compile_config():
    yield
    yt_config.set_compile_config(None)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def make_driver(bench, seed, sizes=SIZES):
    """The cell's driver over tables of `sizes`, host arrays made."""
    ctx = run.Context(bench, CELL, seed, rehearse=True)
    ctx.config["rehearse_sizes"] = sizes
    driver = select_cascade_stream.Driver(ctx)
    driver.prepare()
    return driver


def connect(tmp_path_factory, name):
    from ytsaurus_tpu.client import connect
    return connect(str(tmp_path_factory.mktemp(name)))


def load(tmp_path_factory, name, driver):
    client = connect(tmp_path_factory, name)
    driver.load(client)
    return client


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    """(client, driver) of the first seed: the three tables as the driver
    publishes them."""
    driver = make_driver(bench, SEEDS[0])
    return load(tmp_path_factory, "q3", driver), driver


def q3(driver):
    return driver.queries[0]


def paths(driver):
    return {name: table["path"] for name, table in driver.tables.items()}


def agrees(rows, spec, driver):
    """Whether `rows` hold the cell's limits against the reference."""
    want = tpch_cascade_spec.evaluate(spec, driver.host, driver.vocabs)
    assert want, "an empty answer compares nothing"
    mismatched, gap = tpch_cascade_spec.compare(spec, rows, want)
    return mismatched == 0 and gap < GAP_LIMIT


def _by_name(trace_id):
    out = {}
    for span in get_collector().find(trace_id):
        out.setdefault(span.name, []).append(span)
    return out


# -- the configuration and the CUSTOMER generator -----------------------------

def test_configuration_states_what_it_is(bench):
    entry = next(c for c in bench["configs"]
                 if c["name"] == "tpch-customer-orders-lineitem")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"] and "s2.4.3 Q3" in \
        config["source"]
    assert config["reduced"] == entry["reduced"] == ["scale_factor"]
    assert set(config["reduced_why"]) == {"scale_factor"}
    assert config["sizes"] == {"rows": 600572, "orders": 150000,
                               "parts": 20000, "suppliers": 1000,
                               "customers": 15000, "clerks": 100}
    assert [len(t["columns"]) for t in config["tables"].values()] == \
        [16, 9, 8]
    assert "l_orderkey -> o_orderkey" in config["key_relationship"] and \
        "o_custkey -> c_custkey" in config["key_relationship"]
    assert config["assumed"] and len(config["guarantees"]) == 4
    # the sibling's two tables, column for column
    sibling = run.load_json("configs", "tpch-orders-lineitem.json")
    for name in ("lineitem", "orders"):
        assert config["tables"][name] == sibling["tables"][name]
    assert config["sizes"] == sibling["sizes"] and \
        config["env"] == sibling["env"]


def test_lines_and_orders_are_the_join_deployments(bench):
    """LINEITEM and ORDERS are `tpch-orders-lineitem`'s to the last draw:
    the same seed and sizes give bit-equal host arrays."""
    mine = make_driver(bench, SEEDS[1])
    ctx = run.Context(bench, "tpch_q12_join", SEEDS[1], rehearse=True)
    ctx.config["rehearse_sizes"] = SIZES
    theirs = select_join_stream.Driver(ctx)
    theirs.prepare()
    for table in ("lineitem", "orders"):
        assert set(mine.host[table]) == set(theirs.host[table])
        for name, column in theirs.host[table].items():
            assert mine.host[table][name].dtype == column.dtype
            assert np.array_equal(mine.host[table][name], column), name
    assert all(mine.vocabs[name] == vocab
               for name, vocab in theirs.vocabs.items())


@pytest.mark.parametrize("seed", SEEDS)
def test_customers_are_as_the_specification_populates_them(bench, seed):
    driver = make_driver(bench, seed)
    customer, orders = driver.host["customer"], driver.host["orders"]
    n = SIZES["customers"]
    assert list(customer) == [c["name"] for c in
                              driver.tables["customer"]["columns"]]
    assert all(len(column) == n for column in customer.values())
    # 1..n unique, stored sorted; every order's customer is here, and a
    # third of the customers (the multiples of 3) has no order
    assert np.array_equal(customer["c_custkey"], np.arange(1, n + 1))
    assert np.isin(orders["o_custkey"], customer["c_custkey"]).all()
    without = np.setdiff1d(customer["c_custkey"], orders["o_custkey"])
    assert np.isin(np.arange(3, n + 1, 3), without).all()
    assert customer["c_name"][0] == b"Customer#000000001" and \
        customer["c_name"][-1] == b"Customer#%09d" % n
    lengths = np.char.str_len(customer["c_address"])
    assert lengths.min() >= 10 and lengths.max() <= 40
    assert customer["c_nationkey"].min() == 0 and \
        customer["c_nationkey"].max() == 24
    phone = customer["c_phone"].astype("U15")
    assert all(len(p) == 15 and p[2] == p[6] == p[10] == "-"
               for p in phone)
    assert np.array_equal(np.array([int(p[:2]) for p in phone]),
                          customer["c_nationkey"] + 10)
    assert all(100 <= int(p[3:6]) and 100 <= int(p[7:10])
               and 1000 <= int(p[11:]) for p in phone)
    cents = customer["c_acctbal"] * 100
    assert np.allclose(cents, np.rint(cents), atol=1e-6)
    assert -999.99 <= customer["c_acctbal"].min() and \
        customer["c_acctbal"].max() <= 9999.99
    assert driver.vocabs["c_mktsegment"] == sorted(
        ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"])
    counts = np.bincount(customer["c_mktsegment"], minlength=5)
    assert len(counts) == 5 and counts.min() > n / 5 * 0.6
    lengths = np.char.str_len(customer["c_comment"])
    assert lengths.min() >= 29 and lengths.max() <= 116
    # the orders of a seed are the same with or without this table
    again, _ = tpch_dbgen_customer.generate(driver.config, seed, SIZES)
    assert all(np.array_equal(again[name], customer[name])
               for name in customer)


def test_customers_refuse_orders_of_another_size(bench):
    driver = make_driver(bench, 11)
    with pytest.raises(ValueError, match="outside this table"):
        tpch_dbgen_customer.generate(
            driver.config, 11, dict(SIZES, customers=100),
            orders=driver.host["orders"])


# -- the plain reference ------------------------------------------------------

TINY = {
    "a": {"a_key": np.array([1, 2, 3, 4]),
          "a_price": np.array([1.5, 2.5, 4.0, 8.0])},
    "b": {"b_key": np.array([2, 2, 3, 9]), "b_next": np.array([7, 8, 7, 7])},
    "c": {"c_key": np.array([7, 7, 5]), "c_code": np.array([0, 1, 1])},
}


def tiny_spec(kind_b, kind_c, **more):
    return dict({
        "from": "a",
        "joins": [{"table": "b", "kind": kind_b, "on": ["a_key", "b_key"]},
                  {"table": "c", "kind": kind_c, "on": ["b_next", "c_key"]}],
        "group_by": ["a_key"],
        "aggregates": [{"name": "total", "fn": "sum", "expr": "a_price"},
                       {"name": "pairs", "fn": "sum", "expr": "1"},
                       {"name": "coded", "fn": "sum",
                        "expr": "if_(is_null(c_code), 0, 1)"}],
        "order_by": [["total", "desc"], ["a_key", "asc"]], "limit": 10},
        **more)


@pytest.mark.parametrize("kinds, want", [
    # a2 -> b0 (7: two c rows), b1 (8: none); a3 -> b2 (7: two c rows)
    (("inner", "inner"), [(3, 8.0, 2, 2), (2, 5.0, 2, 2)]),
    (("inner", "left"), [(3, 8.0, 2, 2), (2, 7.5, 3, 2)]),
    # LEFT on stage 1 keeps a1 and a4 with a NULL key for stage 2
    (("left", "left"), [(3, 8.0, 2, 2), (4, 8.0, 1, 0), (2, 7.5, 3, 2),
                        (1, 1.5, 1, 0)]),
    (("left", "inner"), [(3, 8.0, 2, 2), (2, 5.0, 2, 2)]),
], ids=lambda v: "-".join(v) if isinstance(v[0], str) else "")
def test_reference_cascade_by_hand(kinds, want):
    rows = tpch_cascade_spec.evaluate(tiny_spec(*kinds), TINY, {"c_code":
                                                                ["x", "y"]})
    assert [(r["a_key"], r["total"], r["pairs"], r["coded"])
            for r in rows] == want


def test_reference_filter_shift_and_precision():
    vocabs = {"c_code": ["x", "y"]}
    spec = tiny_spec("inner", "inner", filter="c_code == 'y' and a_key < 9")
    rows = tpch_cascade_spec.evaluate(spec, TINY, vocabs)
    assert [(r["a_key"], r["pairs"]) for r in rows] == [(3, 1), (2, 1)]
    # the control's fault on one named stage: every pair of c's join takes
    # the next c row, so key 7 joins rows 1 and 2 (codes y, y)
    shifted = tpch_cascade_spec.evaluate(spec, TINY, vocabs,
                                         shift=("c", 1))
    assert [(r["a_key"], r["pairs"]) for r in shifted] == [(3, 2), (2, 2)]
    assert tpch_cascade_spec.evaluate(spec, TINY, vocabs,
                                      shift=("b", 0)) == rows
    # `dtype` holds every double in it: the sum is a float32's
    third = {"a": dict(TINY["a"], a_price=np.full(4, 1 / 3)),
             "b": TINY["b"], "c": TINY["c"]}
    exact = tpch_cascade_spec.evaluate(tiny_spec("inner", "inner"), third,
                                       vocabs)
    single = tpch_cascade_spec.evaluate(tiny_spec("inner", "inner"), third,
                                        vocabs, dtype=np.float32)
    assert exact[0]["total"] == 2 / 3
    assert single[0]["total"] == float(np.float32(1 / 3) * 2) != 2 / 3
    mismatched, gap = tpch_cascade_spec.compare(
        tiny_spec("inner", "inner"), single, exact)
    assert mismatched == 0 and 1e-9 < gap < 1e-6


# -- Q3 through select_rows ---------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_q3_equals_the_reference(bench, tmp_path_factory, deployment, seed):
    client, driver = deployment
    if seed != SEEDS[0]:
        driver = make_driver(bench, seed)
        client = load(tmp_path_factory, f"q3-{seed}", driver)
    rows = client.select_rows(q3(driver)["ql"])
    assert len(rows) == 10
    assert list(rows[0]) == ["l_orderkey", "revenue", "o_orderdate",
                             "o_shippriority"]
    revenue = [r["revenue"] for r in rows]
    assert revenue == sorted(revenue, reverse=True) and revenue[-1] > 0
    assert all(r["o_orderdate"] < DATE for r in rows)
    assert agrees(rows, q3(driver)["reference"], driver)
    stats = client.last_query_statistics
    assert stats.execution_tier == "compiled"
    # every line has one order, every order one customer
    assert stats.join_rows_out == 2 * SIZES["rows"]


SELECT = ("l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue, "
          "o_orderdate, o_shippriority ")
WHERE = ("WHERE c_mktsegment = 'BUILDING' AND o_orderdate < 9204 AND "
         "l_shipdate > 9204 ")
TAIL = ("GROUP BY l_orderkey, o_orderdate, o_shippriority ORDER BY "
        "sum(l_extendedprice * (1 - l_discount)) DESC, o_orderdate LIMIT 10")
LINES = "JOIN [{lineitem}] ON o_orderkey = l_orderkey "
CUSTOMER = "JOIN [{customer}] ON o_custkey = c_custkey "
# ORDERS as the FROM table: both joins hang on its columns, so the two
# declared orders are both valid and the planner has a choice to make.
DECLARED = {
    "lines_first": "FROM [{orders}] " + LINES + CUSTOMER,
    "customer_first": "FROM [{orders}] " + CUSTOMER + LINES,
}
ORDERS_FIRST_SPEC = {
    "from": "orders",
    "joins": [{"table": "lineitem", "kind": "inner",
               "on": ["o_orderkey", "l_orderkey"]},
              {"table": "customer", "kind": "inner",
               "on": ["o_custkey", "c_custkey"]}]}


@pytest.mark.parametrize("planner", [True, False], ids=["planned", "declared"])
@pytest.mark.parametrize("declared", ["lines_first", "customer_first",
                                      "the_cells"])
def test_join_orders_give_one_answer(deployment, declared, planner):
    """Both declared orders, with the cost-based planner on and off, and
    the cell's own spelling (LINEITEM first): one answer; the spans say
    which order ran and whether the planner chose it."""
    client, driver = deployment
    yt_config.set_compile_config(
        yt_config.CompileConfig(cost_join_planner=planner))
    if declared == "the_cells":
        ql = q3(driver)["ql"]
        spec = q3(driver)["reference"]
        # stage 2's key comes from stage 1: one admissible order
        order, reordered = ["orders", "customer"], False
    else:
        ql = (SELECT + DECLARED[declared] + WHERE + TAIL).format(
            **paths(driver))
        spec = dict(q3(driver)["reference"], **ORDERS_FIRST_SPEC)
        # 5,000 orders x 1 customer each before x 4 lines each
        first = "customer" if planner or declared == "customer_first" \
            else "lineitem"
        order = [first, "lineitem" if first == "customer" else "customer"]
        reordered = planner and declared == "lines_first"
    profile = client.select_rows(ql, explain_analyze=True)
    assert agrees(profile.rows, spec, driver)
    assert agrees(profile.rows, q3(driver)["reference"], driver)
    spans = _by_name(profile.trace_id)
    want = [paths(driver)[name] for name in order]
    assert [s.tags["table"] for s in spans["evaluator.join"]] == want
    assert [s.tags["stage"] for s in spans["evaluator.join"]] == [0, 1]
    planned = [s for s in spans["query.plan"] if "join_order" in s.tags]
    assert len(planned) == 1 and len(spans["query.plan"]) == 2
    assert planned[0].tags["join_order"] == want
    assert planned[0].tags["join_reordered"] is reordered


PREDICATES = {
    "segment": ("c_mktsegment = 'BUILDING'", "c_mktsegment == 'BUILDING'"),
    "orderdate": ("o_orderdate < 9204", "o_orderdate < 9204"),
    "shipdate": ("l_shipdate > 9204", "l_shipdate > 9204"),
}


@pytest.mark.parametrize("predicate", sorted(PREDICATES))
def test_each_predicate_alone(deployment, predicate):
    client, driver = deployment
    ql, expr = PREDICATES[predicate]
    query = q3(driver)["ql"].replace(WHERE, f"WHERE {ql} ")
    assert query != q3(driver)["ql"]
    spec = dict(q3(driver)["reference"], filter=expr)
    rows = client.select_rows(query)
    assert len(rows) == 10 and agrees(rows, spec, driver)
    # and it is not Q3's answer: the other two predicates matter
    assert not agrees(rows, q3(driver)["reference"], driver)


def variant(bench, tmp_path_factory, name, seed, change):
    """(client, driver) of `seed` with the host arrays changed by
    `change(driver.host)` before they are published."""
    driver = make_driver(bench, seed)
    change(driver.host)
    return load(tmp_path_factory, name, driver), driver


def top_order(driver):
    """Row of ORDERS whose order leads Q3's answer."""
    want = tpch_cascade_spec.evaluate(q3(driver)["reference"], driver.host,
                                      driver.vocabs)
    return int(np.searchsorted(driver.host["orders"]["o_orderkey"],
                               want[0]["l_orderkey"]))


@pytest.mark.parametrize("case", ["customers_without_orders",
                                  "an_order_without_a_customer"])
def test_unmatched_rows_contribute_nothing(bench, tmp_path_factory, case):
    whole = make_driver(bench, SEEDS[1])
    lead = top_order(whole)

    def change(host):
        if case == "an_order_without_a_customer":
            # planted: the order that leads the answer names a customer
            # the table does not hold
            host["orders"]["o_custkey"][lead] = SIZES["customers"] + 7
        else:
            # every order of the leading order's customer goes: one
            # BUILDING customer more with no order
            gone = host["orders"]["o_custkey"] == \
                host["orders"]["o_custkey"][lead]
            keep_line = ~np.isin(host["lineitem"]["l_orderkey"],
                                 host["orders"]["o_orderkey"][gone])
            host["orders"] = {n: c[~gone] for n, c in host["orders"].items()}
            host["lineitem"] = {n: c[keep_line]
                                for n, c in host["lineitem"].items()}
    client, driver = variant(bench, tmp_path_factory, case, SEEDS[1], change)
    rows = client.select_rows(q3(driver)["ql"])
    assert agrees(rows, q3(driver)["reference"], driver)
    key = int(whole.host["orders"]["o_orderkey"][lead])
    assert key not in [r["l_orderkey"] for r in rows]
    # and the answer is not the whole deployment's
    assert not agrees(rows, q3(whole)["reference"], whole)
    stats = client.last_query_statistics
    if case == "an_order_without_a_customer":
        # stage 1 keeps the order's lines, stage 2 drops them
        lines = int((whole.host["lineitem"]["l_orderkey"] == key).sum())
        assert stats.join_rows_out == 2 * SIZES["rows"] - lines


def test_duplicate_foreign_keys_join_many_to_many_on_stage_2(deployment):
    client, driver = deployment
    rng = np.random.default_rng(5)
    keys = driver.host["customer"]["c_custkey"]
    # every customer 0 to 3 times, shuffled: the foreign side of stage 2
    # has duplicates and is not sorted
    dims = rng.permutation(np.repeat(keys, rng.integers(0, 4, len(keys))))
    host = {"d_custkey": dims, "d_weight": rng.integers(1, 100, len(dims)),
            "d_segment": driver.host["customer"]["c_mktsegment"][dims - 1]}
    select_join_stream.publish(
        client, "//tpch/dims",
        [{"name": "d_custkey", "type": "int64"},
         {"name": "d_weight", "type": "int64"},
         {"name": "d_segment", "type": "string"}], host,
        {"d_segment": driver.vocabs["c_mktsegment"]})
    rows = client.select_rows(
        "l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue, "
        "sum(d_weight) AS weight, sum(1) AS pairs, o_orderdate "
        "FROM [//tpch/lineitem] JOIN [//tpch/orders] ON l_orderkey = "
        "o_orderkey JOIN [//tpch/dims] ON o_custkey = d_custkey "
        "WHERE d_segment = 'BUILDING' AND o_orderdate < 9204 AND "
        "l_shipdate > 9204 GROUP BY l_orderkey, o_orderdate ORDER BY "
        "sum(l_extendedprice * (1 - l_discount)) DESC, o_orderdate LIMIT 10")
    spec = {
        "from": "lineitem",
        "joins": [{"table": "orders", "kind": "inner",
                   "on": ["l_orderkey", "o_orderkey"]},
                  {"table": "dims", "kind": "inner",
                   "on": ["o_custkey", "d_custkey"]}],
        "filter": "d_segment == 'BUILDING' and o_orderdate < 9204 and "
                  "l_shipdate > 9204",
        "group_by": ["l_orderkey", "o_orderdate"],
        "aggregates": [{"name": "revenue", "fn": "sum",
                        "expr": "l_extendedprice * (1 - l_discount)"},
                       {"name": "weight", "fn": "sum", "expr": "d_weight"},
                       {"name": "pairs", "fn": "sum", "expr": "1"}],
        "order_by": [["revenue", "desc"], ["o_orderdate", "asc"]],
        "limit": 10}
    tables = dict(driver.host, dims=host)
    vocabs = dict(driver.vocabs, d_segment=driver.vocabs["c_mktsegment"])
    want = tpch_cascade_spec.evaluate(spec, tables, vocabs)
    assert tpch_cascade_spec.compare(spec, rows, want)[0] == 0
    assert [(r["weight"], r["pairs"]) for r in rows] == \
        [(w["weight"], w["pairs"]) for w in want]
    assert max(r["pairs"] for r in rows) > 1
    stats = client.last_query_statistics
    pairs_of = np.bincount(dims, minlength=len(keys) + 1)
    lines_of_order = np.bincount(np.searchsorted(
        driver.host["orders"]["o_orderkey"],
        driver.host["lineitem"]["l_orderkey"]))
    assert stats.join_rows_out == SIZES["rows"] + int(
        (pairs_of[driver.host["orders"]["o_custkey"]] * lines_of_order).sum())


LEFT_QL = (
    "o_orderpriority, sum(l_extendedprice * (1 - l_discount)) AS revenue, "
    "sum(1) AS lines, sum(if(is_null(c_mktsegment), 1, 0)) AS orphans "
    "FROM [{lineitem}] JOIN [{orders}] ON l_orderkey = o_orderkey "
    "LEFT JOIN [{customer}] ON o_custkey = c_custkey WHERE l_shipdate > 9204 "
    "GROUP BY o_orderpriority ORDER BY o_orderpriority LIMIT 10")
LEFT_SPEC = {
    "from": "lineitem",
    "joins": [{"table": "orders", "kind": "inner",
               "on": ["l_orderkey", "o_orderkey"]},
              {"table": "customer", "kind": "left",
               "on": ["o_custkey", "c_custkey"]}],
    "filter": "l_shipdate > 9204",
    "group_by": ["o_orderpriority"],
    "aggregates": [
        {"name": "revenue", "fn": "sum",
         "expr": "l_extendedprice * (1 - l_discount)"},
        {"name": "lines", "fn": "sum", "expr": "1"},
        {"name": "orphans", "fn": "sum",
         "expr": "if_(is_null(c_mktsegment), 1, 0)"}],
    "order_by": [["o_orderpriority", "asc"]], "limit": 10}


def test_left_join_on_stage_2_keeps_the_orphan_orders(bench,
                                                      tmp_path_factory):
    def change(host):
        # every tenth customer withheld: its orders' lines are orphans
        keep = np.arange(SIZES["customers"]) % 10 != 3
        host["customer"] = {n: c[keep] for n, c in host["customer"].items()}
    client, driver = variant(bench, tmp_path_factory, "withheld", SEEDS[2],
                             change)
    rows = client.select_rows(LEFT_QL.format(**paths(driver)))
    want = tpch_cascade_spec.evaluate(LEFT_SPEC, driver.host, driver.vocabs)
    mismatched, gap = tpch_cascade_spec.compare(LEFT_SPEC, rows, want)
    assert mismatched == 0 and gap < GAP_LIMIT and len(rows) == 5
    assert [(r["lines"], r["orphans"]) for r in rows] == \
        [(w["lines"], w["orphans"]) for w in want]
    assert all(0 < r["orphans"] < r["lines"] for r in rows)
    assert client.last_query_statistics.join_rows_out == 2 * SIZES["rows"]
    # as INNER the reference counts no orphan and fewer lines
    inner = copy.deepcopy(LEFT_SPEC)
    inner["joins"][1]["kind"] = "inner"
    fewer = tpch_cascade_spec.evaluate(inner, driver.host, driver.vocabs)
    assert all(f["lines"] < r["lines"] and f["orphans"] == 0
               for f, r in zip(fewer, rows))
    # Q3 itself, INNER on both stages, drops them
    rows = client.select_rows(q3(driver)["ql"])
    assert agrees(rows, q3(driver)["reference"], driver)
    assert client.last_query_statistics.join_rows_out < 2 * SIZES["rows"]


@pytest.mark.parametrize("earlier", ["first_order", "second_order"])
def test_ties_in_revenue_are_decided_by_the_order_date(bench,
                                                       tmp_path_factory,
                                                       earlier):
    """Two orders with the same revenue, to the bit, lead the answer: the
    one with the earlier o_orderdate comes first, whichever it is."""
    whole = make_driver(bench, SEEDS[0])
    groups = tpch_cascade_spec.evaluate(
        dict(q3(whole)["reference"], limit=None), whole.host, whole.vocabs)
    first, second = [g for g in groups
                     if g["o_orderdate"] != groups[0]["o_orderdate"]
                     or g is groups[0]][:2]
    dates = sorted((first["o_orderdate"], second["o_orderdate"]))
    if earlier == "second_order":
        dates.reverse()

    def change(host):
        lines, orders = host["lineitem"], host["orders"]
        for group, date in zip((first, second), dates):
            mine = lines["l_orderkey"] == group["l_orderkey"]
            lines["l_extendedprice"][mine] = 0.0
            lines["l_discount"][mine] = 0.0
            # one line that passes the filter carries all the revenue
            passing = np.flatnonzero(mine & (lines["l_shipdate"] > DATE))
            lines["l_extendedprice"][passing[0]] = 1e7
            orders["o_orderdate"][np.searchsorted(
                orders["o_orderkey"], group["l_orderkey"])] = date
    client, driver = variant(bench, tmp_path_factory, f"ties-{earlier}",
                             SEEDS[0], change)
    rows = client.select_rows(q3(driver)["ql"])
    assert agrees(rows, q3(driver)["reference"], driver)
    assert rows[0]["revenue"] == rows[1]["revenue"] == 1e7
    keys = [first["l_orderkey"], second["l_orderkey"]]
    if earlier == "second_order":
        keys.reverse()
    assert [r["l_orderkey"] for r in rows[:2]] == keys
    assert rows[0]["o_orderdate"] < rows[1]["o_orderdate"]


# -- counters, tags, EXPLAIN ANALYZE ------------------------------------------

@pytest.mark.parametrize("query", ["q3", "q12", "no_join"])
def test_join_stage_seconds_count_the_stages(deployment, query):
    client, driver = deployment
    stages = {"q3": 2, "q12": 1, "no_join": 0}[query]
    ql = {
        "q3": q3(driver)["ql"],
        "q12": "l_shipmode, sum(o_shippriority) AS s FROM [{lineitem}] JOIN "
               "[{orders}] ON l_orderkey = o_orderkey GROUP BY l_shipmode "
               "ORDER BY l_shipmode LIMIT 10".format(**paths(driver)),
        "no_join": "l_shipmode, sum(l_quantity) AS q FROM [{lineitem}] "
                   "GROUP BY l_shipmode ORDER BY l_shipmode LIMIT 10".format(
                       **paths(driver))}[query]
    client.select_rows(ql)                              # compile
    profile = client.select_rows(ql, explain_analyze=True)
    stats = client.last_query_statistics
    assert stats.joins_executed == stats.join_host_syncs == stages
    assert len(stats.join_stage_seconds) == stages
    assert all(seconds > 0 for seconds in stats.join_stage_seconds)
    assert sum(stats.join_stage_seconds) == pytest.approx(stats.join_time)
    assert stats.to_dict()["join_stage_seconds"] == stats.join_stage_seconds
    spans = _by_name(profile.trace_id)
    joins = spans.get("evaluator.join", [])
    assert [s.tags["stage"] for s in joins] == list(range(stages))
    # each entry is its stage's span, on the same clock
    for seconds, span in zip(stats.join_stage_seconds, joins):
        assert seconds == pytest.approx(span.duration, abs=2e-3)
    # one join gives the planner nothing to order: no second query.plan
    assert len(spans["query.plan"]) == (2 if stages > 1 else 1)
    text = profile.format()
    assert ("join plan" in text) == bool(stages)
    for position in range(stages):
        assert f"  {position + 1}. " in text
    assert text.count(" ms)\n") == stages       # each stage's seconds


def test_explain_analyze_shows_both_stages(deployment):
    client, driver = deployment
    client.select_rows(q3(driver)["ql"])                # compile
    profile = client.select_rows(q3(driver)["ql"], explain_analyze=True)
    text = profile.format()
    assert "in 2 host syncs between phases" in text
    assert f"{2 * SIZES['rows']} rows materialized" in text
    lines = [line for line in text.splitlines()
             if line.startswith(("  1. ", "  2. "))]
    assert [line.split()[1] for line in lines] == \
        ["//tpch/orders", "//tpch/customer"]
    stats = client.last_query_statistics
    for line, seconds in zip(lines, stats.join_stage_seconds):
        assert f"-> actual {SIZES['rows']} " in line and "est rows" in line
        assert line.endswith(f", {seconds * 1e3:.3f} ms)")
    from ytsaurus_tpu.query.profile import format_span_tree
    from ytsaurus_tpu.utils.tracing import span_tree
    tree = "\n".join(format_span_tree(span_tree(profile.trace_id)))
    assert tree.count("evaluator.join") == 2
    assert "stage=0" in tree and "stage=1" in tree
    assert "join_reordered=False" in tree


# -- the cell's controls ------------------------------------------------------

@pytest.mark.parametrize("control, fails", [
    ({"kind": "join", "table": "customer", "shift": 1}, "rows_mismatched"),
    ({"kind": "precision", "dtype": "float32"}, "rel_gap_max"),
], ids=["stage_2_broken", "float32"])
def test_cell_is_correct_and_its_controls_are_not(bench, control, fails):
    """The tier-1 twin of benchmark/tests/test_q3_correct.py: the harness
    drives the cell at the rehearsal's sizes; the program's answers hold
    every limit, each control breaks its own and no other."""
    jax = run.start_jax(rehearse=True)
    if control["kind"] == "join":
        assert control.items() <= run.load_json(
            "traffic", "q3_stream.json")["control"].items()
    args = run.parse_args(["--workload", CELL, "--seed", "2147483700",
                           "--seconds", "1", "--rehearse"])
    with pytest.MonkeyPatch.context() as patch:
        real = run.load_json

        def with_control(*parts):
            loaded = real(*parts)
            if parts == ("traffic", "q3_stream.json"):
                loaded["control"] = control
            return loaded
        patch.setattr(run, "load_json", with_control)
        result, read = run.run_cell(bench, args, jax, time.perf_counter(),
                                    with_control=True)
    assert result["correct"] and result["failed"] == 0, result
    assert set(result["compared"]) == {"rows_mismatched", "rel_gap_max",
                                       "requests_off_tier"}
    broken = [name for name, pair in read.items()
              if pair["value"] > pair["limit"]]
    assert broken == [fails], read
