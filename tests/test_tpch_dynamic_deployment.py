"""The dynamic-table TPC-H deployment: LINEITEM as a sorted dynamic table
over 8 tablets, loaded by `insert_rows` / `freeze_table`, written by two
TPC-H refresh pairs (RF1 by `insert_rows`, RF2 by `delete_rows`) and read by
Q1 at read-latest through `client.select_rows`, against the benchmark's
plain numpy reference (`tpch_refresh_spec`).  Also the counters and span the
fan-in adds, and the concatenation it stages with, compiled per capacity.
CPU, tiny sizes; the table is written by the benchmark's own driver.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from drivers import select_dynamic_stream  # noqa: E402
from generators import tpch_dbgen, tpch_refresh  # noqa: E402
from reference import ql_spec, tpch_refresh_spec  # noqa: E402

from ytsaurus_tpu.utils.tracing import get_collector  # noqa: E402

CELL = "tpch_q1_dyn_8tablets"
SEED = 2147483659                          # the driver's seeds pass 2**31
# 1,500 rows a tablet: every tablet's flush and snapshot take the columnar
# MVCC programs (TabletConfig.vectorized_scan_min_rows is 1,024)
SIZES = {"rows": 12004, "orders": 3000, "parts": 400, "suppliers": 20,
         "refresh_orders": 24}
GAP_LIMIT = 1e-10                          # the cell's `rel_gap_max` limit


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def make_driver(bench, seed=SEED, **config):
    """The cell's driver at `SIZES`, host arrays and refresh pairs made;
    `config` overrides keys of the configuration (`tablets`, ...)."""
    ctx = run.Context(bench, CELL, seed, rehearse=True)
    ctx.config["rehearse_sizes"] = dict(SIZES)
    ctx.config.update(config)
    driver = select_dynamic_stream.Driver(ctx)
    driver.prepare()
    return driver


def deploy(driver, root, flush_pair_2=False):
    """The table as the harness leaves it when the window opens: loaded,
    pair 1 flushed, the cluster reopened from its files, pair 2 written
    (and flushed where asked)."""
    from ytsaurus_tpu.client import connect
    driver.load(connect(root))
    client = connect(root, fresh=True)
    driver.refresh(client, driver.pairs[1])
    if flush_pair_2:
        client.freeze_table(driver.table)
    return client


@pytest.fixture(scope="module")
def deployment(bench, tmp_path_factory):
    driver = make_driver(bench)
    return deploy(driver, str(tmp_path_factory.mktemp("dyn"))), driver


def q1(driver):
    return driver.queries[0]


def agrees(client, driver):
    want = tpch_refresh_spec.evaluate(q1(driver)["reference"], driver.base,
                                      driver.vocabs, driver.pairs)
    assert want, "an empty answer compares nothing"
    got = client.select_rows(q1(driver)["ql"])
    mismatched, gap = ql_spec.compare(q1(driver)["reference"], got, want)
    return mismatched == 0 and gap < GAP_LIMIT


def tablets(client, driver):
    return client._mounted_tablets(driver.table)


def keys_of(rows):
    return {(r["l_orderkey"], r["l_linenumber"]) for r in rows}


def visible_keys(driver):
    return set(zip(driver.host["l_orderkey"].tolist(),
                   driver.host["l_linenumber"].tolist()))


# -- the refresh pairs and their reference ------------------------------------

def test_refresh_pairs_are_the_specifications():
    config = run.load_json("configs", "tpch-lineitem-dynamic-8t.json")
    host, _ = tpch_dbgen.generate(config, SEED, SIZES)
    pairs = tpch_refresh.generate(config, SEED, SIZES, host)
    assert len(pairs) == 2
    loaded = set(host["l_orderkey"].tolist())
    for number, pair in enumerate(pairs, start=1):
        new = pair["insert"]["l_orderkey"]
        # RF1: SF x 1,500 new orders, keys the load leaves unused, bits 3-4
        # set to the pair's number; 1..7 lines each, numbered from 1
        orders, counts = np.unique(new, return_counts=True)
        assert len(orders) == SIZES["refresh_orders"]
        assert not loaded & set(orders.tolist())
        assert ((orders >> 3) & 3 == number).all()
        assert counts.min() >= 1 and counts.max() <= 7
        for order, count in zip(orders, counts):
            lines = pair["insert"]["l_linenumber"][new == order]
            assert sorted(lines.tolist()) == list(range(1, count + 1))
        # RF2: every line of SF x 1,500 loaded orders
        gone = pair["delete"]
        assert len(np.unique(gone[:, 0])) == SIZES["refresh_orders"]
        assert np.isin(host["l_orderkey"], gone[:, 0]).sum() == len(gone)
    # the pairs touch other orders
    assert not set(pairs[0]["delete"][:, 0].tolist()) & \
        set(pairs[1]["delete"][:, 0].tolist())
    again = tpch_refresh.generate(config, SEED, SIZES, host)
    assert all(np.array_equal(a["delete"], b["delete"])
               for a, b in zip(pairs, again))


def test_reference_applies_the_pairs_in_commit_order():
    host = {"l_orderkey": np.array([1, 1, 2, 3]),
            "l_linenumber": np.array([1, 2, 1, 1]),
            "v": np.array([1.0, 2.0, 4.0, 8.0])}
    pairs = [
        {"insert": {"l_orderkey": np.array([9]),
                    "l_linenumber": np.array([1]), "v": np.array([16.0])},
         "delete": np.array([[1, 1], [1, 2]])},
        {"insert": {"l_orderkey": np.array([10, 10]),
                    "l_linenumber": np.array([1, 2]),
                    "v": np.array([32.0, 64.0])},
         "delete": np.array([[9, 1]])},
    ]
    out = tpch_refresh_spec.visible(host, pairs)
    assert out["v"].tolist() == [4.0, 8.0, 32.0, 64.0]
    assert tpch_refresh_spec.visible(host, pairs[:1])["v"].tolist() == \
        [4.0, 8.0, 16.0]
    with pytest.raises(ValueError, match="1..7"):
        tpch_refresh_spec.visible(host, [dict(pairs[0], delete=np.array(
            [[1, 8]]))])


# -- the deployment against the reference -------------------------------------

def case_8_tablets(bench, tmp_path_factory, deployment):
    client, driver = deployment
    assert len(tablets(client, driver)) == 8
    # pair 2 is in the dynamic stores: its RF1 lines and RF2 tombstones
    pair = driver.pairs[1]
    assert sum(t.active_store.store_row_count
               for t in tablets(client, driver)) == \
        len(pair["insert"]["l_orderkey"]) + len(pair["delete"])
    assert all(len(t.chunk_ids) == 2 for t in tablets(client, driver))
    assert agrees(client, driver)


def case_1_tablet(bench, tmp_path_factory, deployment):
    driver = make_driver(bench, tablets=1)
    client = deploy(driver, str(tmp_path_factory.mktemp("dyn1")))
    assert len(tablets(client, driver)) == 1
    assert agrees(client, driver)


def case_pair_2_flushed(bench, tmp_path_factory, deployment):
    driver = make_driver(bench)
    client = deploy(driver, str(tmp_path_factory.mktemp("dynf")),
                    flush_pair_2=True)
    assert all(t.active_store.store_row_count == 0 and len(t.chunk_ids) == 3
               for t in tablets(client, driver))
    assert agrees(client, driver)


def case_deleted_keys_invisible(bench, tmp_path_factory, deployment):
    client, driver = deployment
    rows = client.select_rows(
        f"l_orderkey, l_linenumber FROM [{driver.table}]")
    got = keys_of(rows)
    assert len(got) == len(rows) == driver.rows
    assert got == visible_keys(driver)
    for pair in driver.pairs:
        assert not got & set(map(tuple, pair["delete"].tolist()))
        assert set(zip(pair["insert"]["l_orderkey"].tolist(),
                       pair["insert"]["l_linenumber"].tolist())) <= got


def case_tablet_keys_inside_pivots(bench, tmp_path_factory, deployment):
    client, driver = deployment
    held = tablets(client, driver)
    pivots = [t.pivot_key for t in held[1:]]
    assert pivots == select_dynamic_stream.pivot_keys(
        driver.base["l_orderkey"], 8)
    total = 0
    for index, tablet in enumerate(held):
        keys = sorted(keys_of(tablet.read_snapshot().to_rows()))
        assert keys, f"tablet {index} is empty"
        total += len(keys)
        # (order, null) sorts before every line of the order
        if index:
            assert keys[0][0] >= pivots[index - 1][0]
        if index + 1 < len(held):
            assert keys[-1][0] < pivots[index][0]
    assert total == driver.rows


def case_steady_state(bench, tmp_path_factory, deployment):
    client, driver = deployment
    client.select_rows(q1(driver)["ql"])            # snapshots cached
    client.select_rows(q1(driver)["ql"])
    stats = client.last_query_statistics
    assert stats.snapshot_cache_misses == 0
    assert stats.shards_coalesced == 8
    assert stats.snapshot_time > 0 and stats.coalesce_time > 0
    assert stats.compile_count == 0


CASES = {name[len("case_"):]: fn for name, fn in globals().items()
         if name.startswith("case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_deployment(bench, tmp_path_factory, deployment, case):
    CASES[case](bench, tmp_path_factory, deployment)


# -- counters, the span, EXPLAIN ANALYZE --------------------------------------

def test_fan_in_counters_span_and_explain_line(deployment):
    client, driver = deployment
    client.select_rows(q1(driver)["ql"])
    profile = client.select_rows(q1(driver)["ql"], explain_analyze=True)
    stats = client.last_query_statistics
    assert {"snapshot_time", "snapshot_cache_misses", "coalesce_time",
            "shards_coalesced", "coalesce_columns",
            "coalesce_columns_pruned"} <= set(stats.to_dict())
    (span,) = [s for s in get_collector().find(profile.trace_id)
               if s.name == "coordinator.coalesce"]
    assert span.tags["shards_in"] == 8 and span.tags["groups_out"] == 1
    assert span.tags["rows"] == driver.rows
    # Q1 reads 7 of the 16 columns: l_returnflag and l_linestatus are its
    # strings, l_comment and the rest stay out of the concatenation
    assert span.tags["columns"] == 7 and span.tags["string_columns"] == 2
    assert span.tags["columns_pruned"] == 9
    assert stats.coalesce_columns == 7
    assert stats.coalesce_columns_pruned == 9
    assert span.tags["vocab_entries"] <= 8 * (3 + 2)   # A/N/R, F/O a tablet
    assert span.tags["vocab_entries"] < driver.rows // 100
    assert span.duration == pytest.approx(stats.coalesce_time, abs=2e-3)
    reads = [s for s in get_collector().find(profile.trace_id)
             if s.name == "tablet.read_snapshot"]
    assert len(reads) == 8
    assert all(s.tags["snapshot_cache"] == "hit" for s in reads)
    (line,) = [line for line in profile.format().splitlines()
               if line.startswith("fan-in: ")]
    assert line.startswith("fan-in: 8 shards coalesced in ")
    assert " (7 of 16 columns); tablet snapshots " in line
    assert line.endswith("(0 merged anew)")


def test_static_table_counts_no_fan_in(tmp_path):
    from ytsaurus_tpu.client import connect
    client = connect(str(tmp_path))
    client.write_table("//t", [{"k": i, "v": i * 2} for i in range(10)])
    client.select_rows("sum(v) AS s FROM [//t] GROUP BY k % 2 AS g")
    stats = client.last_query_statistics
    assert stats.snapshot_time == stats.coalesce_time == 0
    assert stats.snapshot_cache_misses == stats.shards_coalesced == 0


# -- the fan-in's column cut ---------------------------------------------------

CUT_COLUMNS = ["k", "g", "s", "c", "x", "y"]
# 4 tablets of 40 rows; `g` has other values in every tablet, `s` and `c`
# one a row, so every string column's dictionaries differ shard to shard
CUT_ROWS = [{"k": k, "g": b"g%d.%d" % (k // 40, k % 3),
             "s": None if k % 11 == 5 else b"s%03d" % k,
             "c": b"comment %d" % (k * 7919 % 1000), "x": k / 8, "y": k % 5}
            for k in range(160)]
# (query, the FROM columns it reads, coalescing threshold: the client's
# unless given, where the 4 tablets make one group)
CUT_CASES = {
    "star": ("* FROM [{t}]", CUT_COLUMNS, None),
    "projection": ("s, x FROM [{t}]", ["s", "x"], None),
    "group": ("g, sum(x) AS sx, count(*) AS n FROM [{t}] GROUP BY g",
              ["g", "x"], None),
    "where_not_projected": ("s FROM [{t}] WHERE y = 2", ["s", "y"], None),
    # a LIMIT without a group stages lazily; with one it is eager
    "group_order_limit": ("g, sum(y) AS sy FROM [{t}] GROUP BY g "
                          "ORDER BY sum(y) DESC, g LIMIT 3", ["g", "y"], None),
    # the join key is kept, and `s`, read only above the join
    "join": ("s, name FROM [{t}] JOIN [//cut/dim] ON y = gk WHERE x > 3",
             ["s", "x", "y"], None),
    # two groups: the bottom query split off a distinct count drops the
    # grouping and the projection, and still reads only what was kept
    "join_two_groups": ("name, cardinality(s) AS n FROM [{t}] "
                        "JOIN [//cut/dim] ON y = gk WHERE x > 3 GROUP BY name",
                        ["s", "x", "y"], 50),
}


@pytest.fixture(scope="module")
def cut_tables(tmp_path_factory):
    """`//cut/dyn`, a sorted dynamic table over 4 tablets, its static
    one-chunk copy `//cut/static`, and `//cut/dim` to join to."""
    from ytsaurus_tpu.client import connect
    from ytsaurus_tpu.schema import TableSchema
    client = connect(str(tmp_path_factory.mktemp("cut")))
    types = dict(k="int64", g="string", s="string", c="string", x="double",
                 y="int64")
    client.create("table", "//cut/dyn", recursive=True, attributes={
        "schema": TableSchema.make(
            [("k", "int64", "ascending")] +
            [(name, types[name]) for name in CUT_COLUMNS[1:]],
            unique_keys=True),
        "dynamic": True, "pivot_keys": [[40], [80], [120]]})
    client.mount_table("//cut/dyn")
    client.insert_rows("//cut/dyn", CUT_ROWS)
    client.create("table", "//cut/static", attributes={
        "schema": TableSchema.make([(n, types[n]) for n in CUT_COLUMNS])})
    client.write_table("//cut/static", CUT_ROWS)
    client.create("table", "//cut/dim", attributes={
        "schema": TableSchema.make([("gk", "int64"), ("name", "string")])})
    client.write_table("//cut/dim",
                       [{"gk": k, "name": b"n%d" % k} for k in range(4)])
    assert len(client._mounted_tablets("//cut/dyn")) == 4
    return client


@pytest.mark.parametrize("case", sorted(CUT_CASES))
def test_fan_in_concatenates_only_the_columns_the_plan_reads(
        cut_tables, monkeypatch, case):
    import ytsaurus_tpu.client as client_module
    client = cut_tables
    ql, kept, threshold = CUT_CASES[case]
    want = client.select_rows(ql.format(t="//cut/static"))
    assert want, "an empty answer compares nothing"
    assert client.last_query_statistics.shards_coalesced == 0
    if threshold is not None:
        real = client_module.coordinate_and_execute

        def coalescing_below(*args, **kwargs):
            return real(*args, **dict(kwargs, merge_shards_below=threshold))
        monkeypatch.setattr(client_module, "coordinate_and_execute",
                            coalescing_below)
    profile = client.select_rows(ql.format(t="//cut/dyn"),
                                 explain_analyze=True)
    if "ORDER BY" in ql:
        assert profile.rows == want
    else:
        assert sorted(profile.rows, key=repr) == sorted(want, key=repr)
    stats = client.last_query_statistics
    assert stats.shards_coalesced == 4
    assert stats.coalesce_columns == len(kept)
    assert stats.coalesce_columns_pruned == len(CUT_COLUMNS) - len(kept)
    (span,) = [s for s in get_collector().find(profile.trace_id)
               if s.name == "coordinator.coalesce"]
    assert span.tags["groups_out"] == (1 if threshold is None else 2)
    assert span.tags["columns"] == len(kept)
    assert span.tags["columns_pruned"] == len(CUT_COLUMNS) - len(kept)
    assert span.tags["string_columns"] == len(set(kept) & {"g", "s", "c"})
    (line,) = [line for line in profile.format().splitlines()
               if line.startswith("fan-in: ")]
    assert f" ({len(kept)} of {len(CUT_COLUMNS)} columns); " in line


# -- the concatenation the fan-in stages with ----------------------------------

def chunk_of(rows, start, vocab, dim_offset=0.0):
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.schema import TableSchema
    schema = TableSchema.make([("k", "int64"), ("x", "double"),
                               ("s", "string"), ("b", "boolean"),
                               ("e", "vector<float, 3>")])
    keys = np.arange(start, start + rows)
    return ColumnarChunk.from_rows(schema, [
        {"k": int(k) if k % 5 else None, "x": k / 4.0,
         "s": vocab[k % len(vocab)], "b": bool(k % 2),
         "e": [float(k), dim_offset, 1.0] if k % 3 else None}
        for k in keys])


@pytest.mark.parametrize("rows", [(100, 200), (0, 7, 130), (129, 0),
                                  (1, 1, 1, 300)],
                         ids=["two", "empty_first", "empty_last", "four"])
def test_concat_chunks_keeps_every_row(rows):
    from ytsaurus_tpu.chunks.columnar import concat_chunks
    vocabs = ([b"a", b"b"], [b"b", b"c", b"z"], [b"q"], [b"a", b"q", b"r"])
    parts, start = [], 0
    for index, count in enumerate(rows):
        parts.append(chunk_of(count, start, vocabs[index]))
        start += count
    out = concat_chunks(parts)
    assert out.row_count == sum(rows)
    want = [row for part in parts for row in part.to_rows()]
    assert out.to_rows() == want
    # beyond the rows: zero and invalid, as the program's padding is
    for column in out.columns.values():
        assert not np.asarray(column.valid)[out.row_count:].any()
        assert not np.asarray(column.data)[out.row_count:].any()


def test_concat_chunks_compiles_per_capacity_not_per_row_count():
    """Chunks of new row counts at capacities already seen lower nothing:
    the probe the dynamic cell's driver runs before its set-up."""
    from ytsaurus_tpu.chunks.columnar import concat_chunks
    concat_chunks([chunk_of(100, 0, [b"a"]), chunk_of(200, 100, [b"b"])])
    assert select_dynamic_stream.staging_compiles_per_row_count() == 0
