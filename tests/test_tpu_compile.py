"""Chip compiles without the chip: main-path programs lowered and compiled
for a DESCRIBED TPU v5e (the installed libtpu compiles for a device that is
not attached).  Nothing runs — a pass says only that the chip's compiler
takes the program; `chip_smoke.py` is what runs them.

The topology is described inside a module-scoped fixture (never at import:
only one process may hold libtpu, and every xdist worker imports this
file), compiles happen in the test's own process, and JAX's persistent
compile cache is off around them (an entry written for a described device
cannot be read back without one).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture
def as_on_chip(monkeypatch):
    """Sorts ride the radix engine, as the benchmark's configs and
    chip_smoke.py run them (the network engine `auto` picks below 8M
    rows costs ~40 s of chip compile PER KEY WORD).  Nothing else
    differs: ops/ asks no backend, the programs traced here are the
    ones every test executes."""
    monkeypatch.setenv("YT_TPU_SORT_ENGINE", "radix")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_for(fn, args, sharding):
    """jit(fn) lowered on `args`' shapes placed on the described chip."""
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape if hasattr(a, "shape") else (),
            a.dtype if hasattr(a, "dtype") else jnp.result_type(a),
            sharding=sharding), args)
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled, time.perf_counter() - t0


def compile_query(query, schemas, chunk, sharding):
    """The program select_rows dispatches for `query` over `chunk`
    (evaluator: jit(prepare(plan, chunk).run).lower(...).compile())."""
    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.query.engine.lowering import prepare
    plan = build_query(query, schemas)
    prepared = prepare(plan, chunk)
    columns = {c.name: (chunk.columns[c.name].data,
                        chunk.columns[c.name].valid) for c in plan.schema}
    args = (columns, chunk.row_valid, tuple(prepared.bindings))
    return compile_for(prepared.run, args, sharding)


def lineitem(rows):
    from ytsaurus_tpu.models import tpch
    return tpch.generate_lineitem(rows), \
        {"//tpch/lineitem": tpch.LINEITEM_SCHEMA}


def dyn_merged_chunk(rows):
    """A flushed dynamic store's versioned chunk (k, v, $timestamp, ...)
    as Tablet.flush / the scan path hand it to the MVCC programs."""
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.schema import EValueType, TableSchema
    from ytsaurus_tpu.tablet.tablet import versioned_schema
    schema = TableSchema.make(
        [("k", "int64", "ascending"), ("v", "int64")], unique_keys=True)
    vschema = versioned_schema(schema)
    arrays = {}
    for c in vschema:
        if c.name == "k":
            arrays[c.name] = np.arange(rows)
        elif c.type is EValueType.boolean:
            arrays[c.name] = np.full(rows, c.name != "$tombstone")
        else:
            arrays[c.name] = np.arange(rows) * 3
    return schema, ColumnarChunk.from_arrays(vschema, arrays)


def compile_mvcc_visible(rows, sharding):
    """tablet/mvcc.py's read-snapshot merge over a `rows`-row versioned
    chunk."""
    from ytsaurus_tpu.tablet import mvcc
    schema, merged = dyn_merged_chunk(rows)
    key_names = tuple(schema.key_column_names)
    value_names = tuple(c.name for c in schema if c.sort_order is None)
    builder = mvcc._build_visible(key_names, value_names, merged.capacity)
    args = (mvcc._planes(merged), np.int64(merged.row_count),
            np.int64(1 << 60))
    return compile_for(builder, args, sharding)


def compile_sort_chunk(rows, sharding):
    """sort_chunk's device work for the smoke's sort table (k, v int64)."""
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.operations.sort_op import sort_chunk
    from ytsaurus_tpu.schema import TableSchema
    schema = TableSchema.make([("k", "int64"), ("v", "int64")])
    chunk = ColumnarChunk.from_arrays(
        schema, {"k": np.arange(rows)[::-1], "v": np.arange(rows)})

    def run(planes):
        import dataclasses
        cols = {n: dataclasses.replace(chunk.columns[n], data=d, valid=v)
                for n, (d, v) in planes.items()}
        out = sort_chunk(dataclasses.replace(chunk, columns=cols), ["k"])
        return {n: (c.data, c.valid) for n, c in out.columns.items()}

    planes = {n: (c.data, c.valid) for n, c in chunk.columns.items()}
    return compile_for(run, (planes,), sharding)


HIGH_CARD = (
    "l_orderkey, sum(l_quantity) AS q FROM [//tpch/lineitem] "
    "GROUP BY l_orderkey ORDER BY sum(l_quantity) DESC, l_orderkey LIMIT 10")


def test_q1_at_sf1_capacity(one_chip, as_on_chip):
    from ytsaurus_tpu.models import tpch
    chunk, schemas = lineitem(6_001_215)
    assert chunk.capacity == 8_388_608
    compiled, _ = compile_query(tpch.Q1, schemas, chunk, one_chip)
    mem = compiled.memory_analysis()
    # Arguments + temporaries must fit one v5e chip's 16 GB beside the
    # resident table.
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


def test_double_order_by_key(one_chip, as_on_chip):
    """ORDER BY on a `double` expression: the packed-key encoding of an
    f64 plane (ops/segments.f64_bits_u32) must compile for the chip."""
    chunk, schemas = lineitem(131_072)
    compile_query(
        "l_orderkey, l_extendedprice FROM [//tpch/lineitem] "
        "ORDER BY l_extendedprice * (1 - l_discount) DESC, l_orderkey "
        "LIMIT 10", schemas, chunk, one_chip)


def test_high_cardinality_group_order(one_chip, as_on_chip):
    chunk, schemas = lineitem(131_072)
    compile_query(HIGH_CARD, schemas, chunk, one_chip)


def test_double_hash_mix(one_chip, as_on_chip):
    """farm_hash over a double column (expr._mix_u64's f64 bit pattern)."""
    chunk, schemas = lineitem(131_072)
    compile_query("farm_hash(l_quantity) AS h FROM [//tpch/lineitem]",
                  schemas, chunk, one_chip)


def test_radix_argsort_above_threshold(one_chip, as_on_chip):
    """What `auto` picks one row above LSD_SORT_THRESHOLD: the tiled
    radix engine, two u32 words at 8,388,608 rows."""
    from ytsaurus_tpu.ops.segments import stable_argsort_u32
    words = [jax.ShapeDtypeStruct((8_388_608,), jnp.uint32)] * 2
    compile_for(lambda a, b: stable_argsort_u32([a, b]), tuple(words),
                one_chip)


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.float64],
                         ids=["int64", "double"])
def test_sorted_segment_reduce_at_cell_capacity(one_chip, dtype):
    """The top-k cell's reduce (1,048,576 rows, as many segments): what
    the chip's compiler makes of it holds one scatter and no loop (a
    search would be a `while` over row-sized planes)."""
    from ytsaurus_tpu.ops.segments import _sorted_segment_reduce
    n = 1 << 20
    args = (jax.ShapeDtypeStruct((n,), dtype),
            jax.ShapeDtypeStruct((n,), jnp.int32))
    compiled, _ = compile_for(
        lambda d, s: _sorted_segment_reduce("sum", d, s, n), args, one_chip)
    ops = compiled.as_text().splitlines()
    assert not [line for line in ops if " while(" in line]
    assert sum(" scatter(" in line for line in ops) == 1


def test_sort_chunk_program(one_chip, as_on_chip):
    compile_sort_chunk(200_000, one_chip)


def test_dynamic_table_mvcc_program(one_chip, as_on_chip):
    """The dynamic table's device program at the smoke's 1,000,000 rows:
    the MVCC visibility merge every scan runs — the flush program is its
    first stage, the (key, -ts) version sort.  (Point lookups probe host
    planes — tablet.lookup_rows — and compile nothing.)"""
    compile_mvcc_visible(1_000_000, one_chip)


def dynamic_lineitem_schema():
    """The benchmark's dynamic LINEITEM (all 16 columns, keyed on
    l_orderkey, l_linenumber) as its configuration states it."""
    import json
    import os

    from ytsaurus_tpu.schema import TableSchema
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "tpch-lineitem-dynamic-8t.json")
    with open(path) as f:
        columns = json.load(f)["columns"]
    return TableSchema.make(
        [(c["name"], c["type"], c["sort_order"]) if c.get("sort_order")
         else (c["name"], c["type"]) for c in columns], unique_keys=True)


def test_dynamic_lineitem_mvcc_program(one_chip, as_on_chip):
    """The dynamic LINEITEM cell's snapshot merge at one tablet's
    capacity, 131,072 versions (two versioned chunks and a store, ~75,000
    versions): two key columns, fourteen value columns and their written
    flags."""
    from ytsaurus_tpu.chunks.columnar import _plane_dtype
    from ytsaurus_tpu.tablet import mvcc
    from ytsaurus_tpu.tablet.tablet import versioned_schema
    schema = dynamic_lineitem_schema()
    capacity = 131_072
    planes = {c.name: (np.zeros(capacity, _plane_dtype(c.type)),
                       np.zeros(capacity, bool))
              for c in versioned_schema(schema)}
    builder = mvcc._build_visible(
        tuple(schema.key_column_names),
        tuple(c.name for c in schema if c.sort_order is None), capacity)
    compile_for(builder, (planes, np.int64(capacity), np.int64(1 << 60)),
                one_chip)


def test_q1_over_coalesced_tablets(one_chip, as_on_chip):
    """Q1 over what the coordinator's fan-in hands it in the dynamic
    cell: the 8 tablet snapshots concatenated, 600,572 rows of all 16
    columns in a capacity of 1,048,576."""
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.models import tpch
    from ytsaurus_tpu.schema import EValueType
    schema = dynamic_lineitem_schema().to_unsorted()
    rows = 600_572
    vocabs = {"l_returnflag": [b"A", b"N", b"R"], "l_linestatus": [b"F", b"O"]}
    arrays, dictionaries = {}, {}
    for c in schema:
        if c.type is EValueType.string:
            vocab = vocabs.get(c.name, [b"x"])
            arrays[c.name] = np.arange(rows) % len(vocab)
            dictionaries[c.name] = np.array(vocab, dtype=object)
        elif c.type is EValueType.double:
            arrays[c.name] = np.ones(rows)
        else:
            arrays[c.name] = np.arange(rows)
    chunk = ColumnarChunk.from_arrays(schema, arrays,
                                      dictionaries=dictionaries)
    assert chunk.capacity == 1_048_576
    compiled, _ = compile_query(tpch.Q1, {"//tpch/lineitem": schema}, chunk,
                                one_chip)
    assert "ql.group" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.int64, jnp.int32, jnp.bool_],
                         ids=["int64", "codes", "flags"])
def test_fan_in_concat_at_cell_capacity(one_chip, dtype):
    """The fan-in's concatenation of one column in the dynamic cell: 8
    snapshots of 131,072 slots into 1,048,576, one program whatever the
    tablets' row counts (chunks/columnar._concat_planes)."""
    from ytsaurus_tpu.chunks.columnar import _concat_planes
    parts = tuple(jax.ShapeDtypeStruct((131_072,), dtype) for _ in range(8))
    flags = tuple(jax.ShapeDtypeStruct((131_072,), jnp.bool_)
                  for _ in range(8))
    compile_for(
        lambda d, v, o, t: _concat_planes(d, v, o, t, capacity=1_048_576,
                                          dtype=np.dtype(dtype)),
        (parts, flags, jax.ShapeDtypeStruct((8,), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32)), one_chip)


def test_join_phase_programs(one_chip, as_on_chip):
    """`execute_join`'s two device programs (phase 1: network sort of the
    foreign keys, two searches, count; phase 2: expand) at 16,384 lines /
    4,096 orders.  At the benchmark cell's capacities (1,048,576 /
    262,144) the network sort's compile alone is minutes: PERF.md holds
    those seconds, no test does."""
    from test_tpch_join_deployment import (
        cascade_intermediate, join_phase_programs)
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.schema import TableSchema
    l_schema = TableSchema.make([("l_orderkey", "int64"),
                                 ("l_quantity", "int64")])
    o_schema = TableSchema.make([("o_orderkey", "int64", "ascending"),
                                 ("o_shippriority", "int64")])
    lines, orders = np.arange(16_384), np.arange(4_096)
    probe = ColumnarChunk.from_arrays(
        l_schema, {"l_orderkey": lines % 4_096, "l_quantity": lines})
    foreign = ColumnarChunk.from_arrays(
        o_schema, {"o_orderkey": orders, "o_shippriority": orders})
    plan = build_query(
        "sum(l_quantity + o_shippriority) AS s FROM [//l] JOIN [//o] "
        "ON l_orderkey = o_orderkey GROUP BY 1",
        {"//l": l_schema, "//o": o_schema})
    cascade, probe = cascade_intermediate(plan, probe, {}, stages=0)
    phase1, args1, phase2, args2 = join_phase_programs(
        cascade.stages[0], probe, foreign)
    compile_for(phase1, args1, one_chip)
    compile_for(phase2, args2, one_chip)


Q3_SCHEMAS = {
    "//l": [("l_orderkey", "int64"), ("l_extendedprice", "double"),
            ("l_discount", "double"), ("l_shipdate", "int64")],
    "//o": [("o_orderkey", "int64", "ascending"), ("o_custkey", "int64"),
            ("o_orderdate", "int64"), ("o_shippriority", "int64")],
    "//c": [("c_custkey", "int64", "ascending"), ("c_mktsegment", "string")],
}
Q3 = (
    "l_orderkey, sum(l_extendedprice * (1 - l_discount)) AS revenue, "
    "o_orderdate, o_shippriority FROM [//l] JOIN [//o] ON l_orderkey = "
    "o_orderkey JOIN [//c] ON o_custkey = c_custkey WHERE c_mktsegment = "
    "'BUILDING' AND o_orderdate < 9204 AND l_shipdate > 9204 GROUP BY "
    "l_orderkey, o_orderdate, o_shippriority ORDER BY "
    "sum(l_extendedprice * (1 - l_discount)) DESC, o_orderdate LIMIT 10")


def q3_tables(lines, orders, customers):
    """TPC-H Q3's plan and its three tables (the columns it reads), every
    line with one order and every order with one customer."""
    from ytsaurus_tpu.chunks.columnar import ColumnarChunk
    from ytsaurus_tpu.query.builder import build_query
    from ytsaurus_tpu.schema import TableSchema
    schemas = {path: TableSchema.make(columns)
               for path, columns in Q3_SCHEMAS.items()}
    row, order, customer = (np.arange(n) for n in (lines, orders, customers))
    chunks = {
        "//l": ColumnarChunk.from_arrays(schemas["//l"], {
            "l_orderkey": row % orders, "l_extendedprice": row * 1.5,
            "l_discount": (row % 11) / 100.0, "l_shipdate": 9000 + row % 400}),
        "//o": ColumnarChunk.from_arrays(schemas["//o"], {
            "o_orderkey": order, "o_custkey": order % customers,
            "o_orderdate": 9000 + order % 400,
            "o_shippriority": np.zeros(orders, dtype=np.int64)}),
        "//c": ColumnarChunk.from_arrays(
            schemas["//c"],
            {"c_custkey": customer, "c_mktsegment": customer % 2},
            dictionaries={"c_mktsegment": np.array(
                [b"AUTOMOBILE", b"BUILDING"], dtype=object)}),
    }
    return build_query(Q3, schemas), chunks


def test_join_stage_2_phase_programs(one_chip, as_on_chip):
    """The second stage of a cascade probes with a JOINED INTERMEDIATE
    (16,384 slots, the first stage's output: the columns of both tables
    that are live after it), not a staged table: its two programs compile
    for the chip."""
    from test_tpch_join_deployment import (
        cascade_intermediate, join_phase_programs)
    plan, chunks = q3_tables(16_384, 4_096, 512)
    cascade, intermediate = cascade_intermediate(
        plan, chunks["//l"], chunks, stages=1)
    assert intermediate.row_count == 16_384
    assert "o_custkey" in intermediate.schema.column_names
    phase1, args1, phase2, args2 = join_phase_programs(
        cascade.stages[1], intermediate, chunks["//c"])
    compile_for(phase1, args1, one_chip)
    compile_for(phase2, args2, one_chip)


def test_three_key_group_two_key_order(one_chip, as_on_chip):
    """TPC-H Q3's main program over the twice-joined rows: the filter on
    all three tables' columns, a group by three int64 keys above the dense
    limit (sort + sorted segment reduce), then ORDER BY a double DESC and
    a date, LIMIT 10."""
    from test_tpch_join_deployment import cascade_intermediate
    from ytsaurus_tpu.query.engine.lowering import prepare
    plan, chunks = q3_tables(131_072, 32_768, 4_096)
    cascade, joined = cascade_intermediate(
        plan, chunks["//l"], chunks, stages=2)
    assert joined.row_count == 131_072 and joined.capacity == 131_072
    prepared = prepare(cascade.query, joined)
    columns = {name: (column.data, column.valid)
               for name, column in joined.columns.items()}
    args = (columns, joined.row_valid, tuple(prepared.bindings))
    compiled, _ = compile_for(prepared.run, args, one_chip)
    text = compiled.as_text()
    assert "ql.group" in text and "ql.order" in text


def _segment_end_reference(starts):
    n = len(starts)
    out = np.empty(n, dtype=np.int64)
    end = n - 1
    for i in range(n - 1, -1, -1):
        out[i] = end
        if starts[i]:
            end = i - 1
    return out


@pytest.mark.parametrize("n", [1, 2, 7, 300])
@pytest.mark.parametrize("function", ["sum", "min", "max"])
def test_shifted_prefix_scan_matches_associative_scan(function, n):
    """prefix_scan's one form (shifted, log-step; what every backend
    runs) against `lax.associative_scan` as its reference."""
    from ytsaurus_tpu.ops import segments
    rng = np.random.default_rng(n)
    data = jnp.asarray(rng.integers(-50, 50, n))
    starts = jnp.asarray(rng.random(n) < 0.1).at[0].set(True)
    got = segments.segment_scan(function, data, starts)
    combine = segments._scan_combine(
        {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[function])
    want, _ = jax.lax.associative_scan(combine, (data, starts))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(segments.segment_end_index(starts)),
        _segment_end_reference(np.asarray(starts)))
