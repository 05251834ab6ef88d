"""Column liveness through the join cascade (ISSUE 35): `ir.join_cascade`
cuts every stage to the columns that are read after it, so
`joins.execute_join`'s phase 2 expands those and no others.  Held here: the
intermediates carry exactly the live columns, the answers are a plain
nested-loop reference's (Python over the rows, nothing of the program), and
`evaluator.join`, `QueryStatistics` and EXPLAIN ANALYZE say how many columns a
stage materialized and how many it left behind.  CPU, tiny tables through
`client.select_rows`; Q12 and Q3 over the benchmark's three-table deployment
against its numpy reference.
"""

from unittest import mock

import pytest

import test_tpch_q3_deployment as q3_deployment
from test_tpch_q3_deployment import bench  # noqa: F401  (a fixture)

import run  # noqa: E402  (benchmark/, on the path since the import above)
from reference import tpch_join_spec  # noqa: E402

from ytsaurus_tpu import config as yt_config
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.engine import evaluator
from ytsaurus_tpu.schema import TableSchema
from ytsaurus_tpu.utils.tracing import get_collector

SCHEMAS = {
    "//f": TableSchema.make([
        ("f_id", "int64"), ("f_a", "int64"), ("f_v", "int64"),
        ("f_w", "double"), ("f_s", "string"), ("f_x", "int64")]),
    "//a": TableSchema.make([
        ("a_id", "int64"), ("a_b", "int64"), ("a_name", "string"),
        ("a_n", "int64"), ("a_x", "double")]),
    "//b": TableSchema.make([
        ("b_id", "int64"), ("b_tag", "string"), ("b_m", "int64")]),
    "//u": TableSchema.make([("f_a", "int64"), ("u_w", "int64")]),
}
NAMES = [b"alder", b"alnus", b"birch", b"cedar"]
ROWS = {
    # every seventh fact has no key, keys 9..11 name no row of //a
    "//f": [{"f_id": i, "f_a": None if i % 7 == 3 else i % 12,
             "f_v": (i * 5) % 13, "f_w": i / 4, "f_s": b"s%d" % (i % 3),
             "f_x": -i} for i in range(60)],
    # key 4 twice: the join is many-to-many; a_b 5 names no row of //b
    "//a": [{"a_id": k, "a_b": k % 6, "a_name": NAMES[k % 4],
             "a_n": None if k == 2 else k * 10, "a_x": k / 2}
            for k in [0, 1, 2, 3, 4, 4, 5, 6, 7, 8]],
    "//b": [{"b_id": k, "b_tag": b"t%d" % (k % 2), "b_m": 100 + k}
            for k in range(5)],
    "//u": [{"f_a": k, "u_w": k * k} for k in range(0, 12, 2)],
}


@pytest.fixture(autouse=True)
def _fresh_compile_config():
    yield
    yt_config.set_compile_config(None)


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    from ytsaurus_tpu.client import connect
    client = connect(str(tmp_path_factory.mktemp("liveness")))
    for path, schema in SCHEMAS.items():
        client.create("table", path, attributes={"schema": schema})
        client.write_table(path, ROWS[path])
    return client


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):  # noqa: F811
    """(client, driver) of the three-table TPC-H deployment."""
    driver = q3_deployment.make_driver(bench, q3_deployment.SEEDS[0])
    return q3_deployment.load(tmp_path_factory, "live-q3", driver), driver


# -- the plain reference ------------------------------------------------------

def nested_loop(rows, *joins):
    """`rows` joined to each (table, self key, foreign key, left) in turn:
    a NULL key matches nothing, LEFT keeps the row with the table's columns
    NULL, duplicates multiply."""
    for table, self_key, foreign_key, left in joins:
        out = []
        for row in rows:
            matches = [other for other in ROWS[table]
                       if row[self_key] is not None
                       and other[foreign_key] == row[self_key]]
            if not matches and left:
                matches = [dict.fromkeys(ROWS[table][0])]
            out += [{**other, **row} for other in matches]
        rows = out
    return rows


def grouped(rows, key, **sums):
    """One row a distinct `key`, in key order; `sums`: name -> column or a
    function of the row; NULLs are skipped and a group of NULLs sums NULL."""
    out = {}
    for row in rows:
        group = out.setdefault(row[key], {key: row[key],
                                          **dict.fromkeys(sums)})
        for name, of in sums.items():
            value = of(row) if callable(of) else row[of]
            if value is not None:
                group[name] = (group[name] or 0) + value
    return [out[k] for k in sorted(out)]


def nulls_first(*names):
    return lambda row: tuple((row[n] is not None, row[n]) for n in names)


F_A = ("//a", "f_a", "a_id")
A_B = ("//b", "a_b", "b_id")


def want_totals():
    groups = grouped(nested_loop(ROWS["//f"], F_A + (False,)), "a_name",
                     s="f_v")
    return groups + [{"a_name": None, "s": sum(g["s"] for g in groups)}]


# ql; per stage (columns out, columns pruned, the columns it materializes);
# the answer.
CASES = {
    "q12_shape": (
        "a_name, sum(f_v) AS s FROM [//f] JOIN [//a] ON f_a = a_id "
        "WHERE f_w < 12 GROUP BY a_name ORDER BY a_name LIMIT 10",
        [(3, 8, {"f_v", "f_w", "a_name"})],
        lambda: grouped([r for r in nested_loop(ROWS["//f"], F_A + (False,))
                         if r["f_w"] < 12], "a_name", s="f_v")),
    "bare_join_keeps_every_column": (
        "* FROM [//f] JOIN [//a] ON f_a = a_id",
        [(11, 0, set(SCHEMAS["//f"].column_names)
          | set(SCHEMAS["//a"].column_names))],
        lambda: sorted(nested_loop(ROWS["//f"], F_A + (False,)),
                       key=lambda r: (r["f_id"], r["a_x"]))),
    "left_pulled_column_only_in_where": (
        "f_id FROM [//f] LEFT JOIN [//a] ON f_a = a_id "
        "WHERE is_null(a_n) OR a_n > 30",
        [(2, 9, {"f_id", "a_n"})],
        lambda: sorted(({"f_id": r["f_id"]} for r in nested_loop(
            ROWS["//f"], F_A + (True,))
            if r["a_n"] is None or r["a_n"] > 30),
            key=lambda r: r["f_id"])),
    "left_pulled_column_only_in_having": (
        "f_s, sum(f_v) AS s FROM [//f] LEFT JOIN [//a] ON f_a = a_id "
        "GROUP BY f_s HAVING sum(a_n) > 500 ORDER BY f_s LIMIT 10",
        [(3, 8, {"f_s", "f_v", "a_n"})],
        lambda: [{"f_s": g["f_s"], "s": g["s"]} for g in grouped(
            nested_loop(ROWS["//f"], F_A + (True,)), "f_s", s="f_v",
            n="a_n") if g["n"] > 500]),
    "left_pulled_column_only_in_order_by": (
        "f_id, f_v FROM [//f] LEFT JOIN [//a] ON f_a = a_id "
        "ORDER BY a_n, f_id LIMIT 25",
        [(3, 8, {"f_id", "f_v", "a_n"})],
        lambda: [{"f_id": r["f_id"], "f_v": r["f_v"]} for r in sorted(
            nested_loop(ROWS["//f"], F_A + (True,)),
            key=nulls_first("a_n", "f_id"))[:25]]),
    "a_later_stages_key_goes_after_that_stage": (
        "f_id, b_tag FROM [//f] JOIN [//a] ON f_a = a_id "
        "JOIN [//b] ON a_b = b_id",
        [(2, 9, {"f_id", "a_b"}), (2, 12, {"f_id", "b_tag"})],
        lambda: sorted(({"f_id": r["f_id"], "b_tag": r["b_tag"]}
                        for r in nested_loop(ROWS["//f"], F_A + (False,),
                                             A_B + (False,))),
                       key=lambda r: r["f_id"])),
    "a_stages_own_key_stays_where_the_projection_reads_it": (
        "f_a, a_n FROM [//f] JOIN [//a] ON f_a = a_id",
        [(2, 9, {"f_a", "a_n"})],
        lambda: sorted(({"f_a": r["f_a"], "a_n": r["a_n"]}
                        for r in nested_loop(ROWS["//f"], F_A + (False,))),
                       key=nulls_first("f_a", "a_n"))),
    "nothing_read_after_the_joins": (
        "sum(1) AS n FROM [//f] JOIN [//a] ON f_a = a_id "
        "JOIN [//b] ON a_b = b_id GROUP BY 1 AS one",
        [(1, 10, {"a_b"}), (0, 14, set())],
        lambda: [{"n": len(nested_loop(ROWS["//f"], F_A + (False,),
                                       A_B + (False,)))}]),
    "an_aliased_table_joined_on_an_expression": (
        "f_id, x.a_n AS n FROM [//f] JOIN [//a] AS x ON f_a + 1 = x.a_id + 1 "
        "WHERE x.a_x < 3",
        [(3, 8, {"f_id", "x.a_n", "x.a_x"})],
        lambda: sorted(({"f_id": r["f_id"], "n": r["a_n"]}
                        for r in nested_loop(ROWS["//f"], F_A + (False,))
                        if r["a_x"] < 3), key=nulls_first("f_id", "n"))),
    "using": (
        "f_id, u_w FROM [//f] JOIN [//u] USING f_a",
        [(2, 5, {"f_id", "u_w"})],
        lambda: sorted(({"f_id": r["f_id"], "u_w": r["u_w"]}
                        for r in nested_loop(
                            ROWS["//f"], ("//u", "f_a", "f_a", False))),
                       key=lambda r: r["f_id"])),
    "with_totals": (
        "a_name, sum(f_v) AS s FROM [//f] JOIN [//a] ON f_a = a_id "
        "GROUP BY a_name WITH TOTALS",
        [(2, 9, {"f_v", "a_name"})],
        want_totals),
    "string_predicate_on_a_pulled_dictionary_column": (
        "f_id, a_name FROM [//f] JOIN [//a] ON f_a = a_id "
        "WHERE a_name LIKE 'al%' AND f_s = 's1'",
        [(3, 8, {"f_id", "f_s", "a_name"})],
        lambda: sorted(({"f_id": r["f_id"], "a_name": r["a_name"]}
                        for r in nested_loop(ROWS["//f"], F_A + (False,))
                        if r["a_name"].startswith(b"al")
                        and r["f_s"] == b"s1"),
                       key=lambda r: r["f_id"])),
}
UNORDERED = {"bare_join_keeps_every_column", "with_totals"}


def _spans(trace_id, name):
    return [s for s in get_collector().find(trace_id) if s.name == name]


def select_watching_the_stages(client, ql):
    """(profile, the column names of every intermediate the cascade made)."""
    made = []
    real = evaluator.execute_join

    def watching(*args, **kwargs):
        out = real(*args, **kwargs)
        assert set(out.columns) == set(out.schema.column_names)
        made.append(set(out.columns))
        return out

    with mock.patch.object(evaluator, "execute_join", watching):
        profile = client.select_rows(ql, explain_analyze=True)
    return profile, made


def check_counters(client, profile, made, stages):
    """The spans, the statistics and EXPLAIN ANALYZE's lines say what
    `stages` = [(columns out, columns pruned, names)] says."""
    assert made == [names for _, _, names in stages]
    spans = _spans(profile.trace_id, "evaluator.join")
    assert [(s.tags["columns_out"], s.tags["columns_pruned"])
            for s in spans] == [(out, pruned) for out, pruned, _ in stages]
    stats = client.last_query_statistics
    assert stats.join_columns_out == sum(out for out, _, _ in stages)
    assert stats.join_columns_pruned == sum(p for _, p, _ in stages)
    assert stats.to_dict()["join_columns_pruned"] == \
        stats.join_columns_pruned
    text = profile.format()
    for out, pruned, _ in stages:
        assert f", {out} columns out / {pruned} pruned, " in text


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_cascade_carries_the_live_columns(tables, case):
    ql, stages, want = CASES[case]
    profile, made = select_watching_the_stages(tables, ql)
    rows = profile.rows
    if case in UNORDERED:
        rows = sorted(rows, key=nulls_first(*rows[0]))
        want_rows = sorted(want(), key=nulls_first(*rows[0]))
    else:
        want_rows = want()
        if "ORDER BY" not in ql:
            rows = sorted(rows, key=nulls_first(*rows[0]))
    assert want_rows and rows == want_rows
    check_counters(tables, profile, made, stages)


Q12_COLUMNS = {"l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate",
               "o_orderpriority"}
Q3_READS = {"l_orderkey", "l_extendedprice", "l_discount", "l_shipdate",
            "o_orderdate", "o_shippriority"}


def q12_agrees(client, driver):
    """The benchmark's Q12 over the deployment's LINEITEM and ORDERS (the
    two-table deployment's to the last draw) against its own reference."""
    query = run.load_json("traffic", "q12_stream.json")["queries"][0]
    profile, made = select_watching_the_stages(
        client, query["ql"].format(**q3_deployment.paths(driver)))
    want = tpch_join_spec.evaluate(query["reference"], driver.host,
                                   driver.vocabs)
    assert len(want) == 2
    return profile, made, tpch_join_spec.compare(profile.rows, want) == 0


@pytest.mark.parametrize("query", ["q12", "q3", "q3_orders_first"])
def test_tpch_cascades_carry_what_they_read(deployment, query):
    """Q12 expands 5 of its 25 columns; Q3 7 of 25, then 7 of 33, in the
    cell's spelling and with ORDERS as the FROM table, where the planner
    moves CUSTOMER before LINEITEM: the same columns by another route."""
    client, driver = deployment
    if query == "q12":
        profile, made, agrees = q12_agrees(client, driver)
        assert agrees
        check_counters(client, profile, made, [(5, 20, Q12_COLUMNS)])
        return
    if query == "q3":
        ql, spec = q3_deployment.q3(driver)["ql"], \
            q3_deployment.q3(driver)["reference"]
        stages = [(7, 18, Q3_READS | {"o_custkey"}),
                  (7, 26, Q3_READS | {"c_mktsegment"})]
    else:
        ql = (q3_deployment.SELECT + q3_deployment.DECLARED["lines_first"]
              + q3_deployment.WHERE + q3_deployment.TAIL).format(
                  **q3_deployment.paths(driver))
        spec = dict(q3_deployment.q3(driver)["reference"],
                    **q3_deployment.ORDERS_FIRST_SPEC)
        # ORDERS (9) + CUSTOMER (8), then + LINEITEM (16): o_orderkey is
        # stage 2's key and goes after it, l_orderkey comes with the lines
        orders = {"o_orderdate", "o_shippriority", "c_mktsegment"}
        stages = [(4, 13, orders | {"o_orderkey"}),
                  (7, 26, Q3_READS | {"c_mktsegment"})]
    profile, made = select_watching_the_stages(client, ql)
    assert q3_deployment.agrees(profile.rows, spec, driver)
    check_counters(client, profile, made, stages)
    if query == "q3_orders_first":
        (planned,) = [s for s in _spans(profile.trace_id, "query.plan")
                      if "join_order" in s.tags]
        assert planned.tags["join_reordered"] is True


def test_a_select_without_a_join_counts_no_columns(tables):
    profile = tables.select_rows(
        "f_s, sum(f_v) AS s FROM [//f] GROUP BY f_s", explain_analyze=True)
    assert len(profile.rows) == 3
    assert not _spans(profile.trace_id, "evaluator.join")
    stats = tables.last_query_statistics
    assert stats.join_columns_out == stats.join_columns_pruned == 0
    assert "columns out" not in profile.format()


# -- the liveness function alone ----------------------------------------------

def cascade_of(ql):
    return ir.join_cascade(build_query(ql, SCHEMAS))


def test_join_cascade_cuts_the_from_chunk_the_joins_and_the_plan():
    cascade = cascade_of(CASES["a_later_stages_key_goes_after_that_stage"][0])
    # the FROM chunk keeps what is read and stage 1's key, no more
    assert cascade.from_schema.column_names == ["f_id", "f_a"]
    first, second = cascade.stages
    assert first.join.foreign_columns == ("a_b",)
    assert second.join.foreign_columns == ("b_tag",)
    assert first.schema.column_names == ["f_id", "a_b"]
    assert second.schema.column_names == ["f_id", "b_tag"]
    # what is not pulled is still the foreign table's to be keyed by
    assert first.join.foreign_schema == SCHEMAS["//a"]
    assert cascade.query.schema == second.schema
    assert cascade.query.joins == (first.join, second.join)
    assert [stage.columns_pruned for stage in cascade.stages] == [9, 12]


def test_join_cascade_follows_the_order_the_joins_stand_in():
    """The same three tables, `//b` hung on a column of `//f`: declared
    either way round, each stage carries the keys of the stages after it
    and nothing of a table that has not been joined yet."""
    head = "f_id, a_n, b_m FROM [//f] "
    a, b = "JOIN [//a] ON f_a = a_id ", "JOIN [//b] ON f_v = b_id "
    a_first, b_first = cascade_of(head + a + b), cascade_of(head + b + a)
    assert a_first.from_schema == b_first.from_schema
    assert [s.schema.column_names for s in a_first.stages] == \
        [["f_id", "f_v", "a_n"], ["f_id", "a_n", "b_m"]]
    assert [s.schema.column_names for s in b_first.stages] == \
        [["f_id", "f_a", "b_m"], ["f_id", "b_m", "a_n"]]
    # of 6 + 5 (+ 3) columns, and of 6 + 3 (+ 5)
    assert [s.columns_pruned for s in a_first.stages] == [8, 11]
    assert [s.columns_pruned for s in b_first.stages] == [6, 11]
