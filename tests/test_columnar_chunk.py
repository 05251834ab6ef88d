"""Columnar chunk tests (ref model: ytlib/columnar_chunk_format)."""

import numpy as np
import pytest

from ytsaurus_tpu import EValueType, TableSchema
from ytsaurus_tpu.chunks import ColumnarChunk, concat_chunks, pad_capacity


def test_pad_capacity_buckets():
    assert pad_capacity(1) == 128
    assert pad_capacity(128) == 128
    assert pad_capacity(129) == 256
    assert pad_capacity(1000) == 1024


SCHEMA = TableSchema.make([
    ("k", "int64", "ascending"),
    ("v", "double"),
    ("s", "string"),
    ("b", "boolean"),
])


def test_from_rows_roundtrip():
    rows = [
        {"k": 1, "v": 1.5, "s": "foo", "b": True},
        {"k": 2, "v": None, "s": "bar", "b": False},
        {"k": 3, "v": -2.25, "s": None, "b": None},
    ]
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    assert chunk.row_count == 3
    assert chunk.capacity == 128
    out = chunk.to_rows()
    assert out[0] == {"k": 1, "v": 1.5, "s": b"foo", "b": True}
    assert out[1]["v"] is None and out[1]["s"] == b"bar"
    assert out[2]["s"] is None and out[2]["b"] is None


def test_string_dictionary_order_preserving():
    rows = [{"k": i, "v": None, "s": s, "b": None}
            for i, s in enumerate(["zeta", "alpha", "midway", "alpha"])]
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    col = chunk.column("s")
    codes = np.asarray(col.data[:4])
    # alpha < midway < zeta; equal strings share a code
    assert codes[1] == codes[3]
    assert codes[1] < codes[2] < codes[0]
    assert list(col.dictionary) == [b"alpha", b"midway", b"zeta"]


def test_tuple_rows_and_uint64():
    schema = TableSchema.make([("u", "uint64"), ("i", "int64")])
    big = 2**63 + 5
    chunk = ColumnarChunk.from_rows(schema, [(big, -7), (0, None)])
    rows = chunk.to_rows()
    assert rows[0]["u"] == big
    assert rows[0]["i"] == -7
    assert rows[1]["i"] is None


def test_concat_chunks_unifies_dictionaries():
    a = ColumnarChunk.from_rows(SCHEMA, [
        {"k": 1, "v": 1.0, "s": "bb", "b": True}])
    b = ColumnarChunk.from_rows(SCHEMA, [
        {"k": 2, "v": 2.0, "s": "aa", "b": False},
        {"k": 3, "v": 3.0, "s": "bb", "b": True}])
    merged = concat_chunks([a, b])
    assert merged.row_count == 3
    rows = merged.to_rows()
    assert [r["s"] for r in rows] == [b"bb", b"aa", b"bb"]
    col = merged.column("s")
    codes = np.asarray(col.data[:3])
    assert codes[0] == codes[2] and codes[1] < codes[0]


def test_slice_rows():
    rows = [{"k": i, "v": float(i), "s": str(i), "b": i % 2 == 0}
            for i in range(10)]
    chunk = ColumnarChunk.from_rows(SCHEMA, rows)
    part = chunk.slice_rows(3, 7)
    assert part.row_count == 4
    assert [r["k"] for r in part.to_rows()] == [3, 4, 5, 6]


def test_from_arrays_fast_path():
    schema = TableSchema.make([("x", "int64"), ("y", "double")])
    n = 1000
    chunk = ColumnarChunk.from_arrays(
        schema,
        {"x": np.arange(n), "y": np.linspace(0, 1, n)})
    assert chunk.row_count == n
    assert chunk.capacity == 1024
    assert np.asarray(chunk.column("x").data[:5]).tolist() == [0, 1, 2, 3, 4]


def test_any_column_roundtrip():
    schema = TableSchema.make([("k", "int64"), ("a", "any")])
    rows = [{"k": 1, "a": {"x": 1}}, {"k": 2, "a": [1, 2, 3]}, {"k": 3, "a": None}]
    chunk = ColumnarChunk.from_rows(schema, rows)
    out = chunk.to_rows()
    assert out[0]["a"] == {"x": 1}
    assert out[1]["a"] == [1, 2, 3]
    assert out[2]["a"] is None
    merged = concat_chunks([chunk, ColumnarChunk.from_rows(schema, [{"k": 4, "a": "s"}])])
    assert merged.to_rows()[3]["a"] == "s"


def test_concat_schema_mismatch_rejected():
    import pytest
    from ytsaurus_tpu import YtError
    a = ColumnarChunk.from_rows(TableSchema.make([("k", "int64")]), [(1,)])
    b = ColumnarChunk.from_rows(TableSchema.make([("k", "double")]), [(1.5,)])
    with pytest.raises(YtError):
        concat_chunks([a, b])


def test_strict_schema_rejects_unknown_columns():
    import pytest
    from ytsaurus_tpu import YtError
    schema = TableSchema.make([("k", "int64")])
    with pytest.raises(YtError):
        ColumnarChunk.from_rows(schema, [{"k": 1, "junk": 2}])
    loose = TableSchema.make([("k", "int64")], strict=False)
    chunk = ColumnarChunk.from_rows(loose, [{"k": 1, "junk": 2}])
    assert chunk.to_rows() == [{"k": 1}]


# -- result decode: one device-to-host fetch (ISSUE 28) -----------------------

ALL_TYPES = TableSchema.make([
    ("i", "int64"), ("u", "uint64"), ("d", "double"), ("b", "boolean"),
    ("s", "string"), ("a", "any"), ("e", "vector<float, 4>"), ("n", "null"),
])


def _all_types_rows(n):
    """Every type with nulls mixed in: row r leaves column r % 9 null (a
    row in nine is whole)."""
    rows = []
    for r in range(n):
        row = {"i": r - 3, "u": 2**63 + r, "d": r / 4 - 1.5,
               "b": r % 3 == 0, "s": f"s{r % 7}",
               "a": {"r": r} if r % 2 else [r, "x"],
               "e": [float(r), 0.5, -1.0, r / 8], "n": None}
        hole = r % 9
        if hole < 7:
            row[ALL_TYPES.column_names[hole]] = None
        rows.append(row)
    return rows


def _expected(rows, columns=len(ALL_TYPES.column_names)):
    """What `to_rows` gives for the first `columns` columns of `rows`."""
    names = ALL_TYPES.column_names[:columns]
    return [{name: row[name].encode() if isinstance(row[name], str)
             else row[name] for name in names} for row in rows]


def _first_columns(columns, capacity, n=9):
    """A chunk of `n` rows over the first `columns` columns."""
    schema = TableSchema.make(
        [(c.name, c.type) for c in list(ALL_TYPES)[:columns]])
    rows = _all_types_rows(n)
    chunk = ColumnarChunk.from_rows(
        schema, [{name: row[name] for name in schema.column_names}
                 for row in rows], capacity=capacity)
    return chunk, _expected(rows, columns)


def _reference_rows(chunk):
    """The decode as it was before the batched fetch: a slice and a read
    of its own for every plane, the same per-type conversion."""
    from ytsaurus_tpu.schema import VectorType
    n = chunk.row_count
    out = [{} for _ in range(n)]
    for name in chunk.schema.column_names:
        col = chunk.columns[name]
        data = np.asarray(col.data[:n])
        valid = np.asarray(col.valid[:n])
        for i in range(n):
            if not valid[i] or col.type is EValueType.null:
                v = None
            elif isinstance(col.type, VectorType):
                v = [float(x) for x in data[i]]
            elif col.type is EValueType.string:
                v = bytes(col.dictionary[int(data[i])])
            elif col.type is EValueType.any:
                v = col.host_values[i]
            elif col.type is EValueType.boolean:
                v = bool(data[i])
            elif col.type is EValueType.double:
                v = float(data[i])
            else:
                v = int(data[i])
            out[i][name] = v
    return out


def _fetched_bytes(chunk, slots):
    return sum(plane[:slots].nbytes for col in chunk.columns.values()
               for plane in col.fetched_planes())


# capacity 256: 11 KB of planes, under the constant; 65,536: 2.8 MB, over
FETCH_CASES = [(capacity, rows)
               for capacity in (256, 65536)
               for rows in (0, 1, 5, 200, capacity)]


@pytest.mark.parametrize("capacity,row_count", FETCH_CASES)
def test_to_rows_and_to_tuples_on_both_fetch_paths(capacity, row_count):
    from ytsaurus_tpu.chunks import columnar
    rows = _all_types_rows(row_count)
    chunk = ColumnarChunk.from_rows(ALL_TYPES, rows, capacity=capacity)
    assert chunk.capacity == capacity
    over = _fetched_bytes(chunk, capacity) > columnar.WHOLE_FETCH_BYTES
    assert over == (capacity == 65536)
    tags = {}
    got = chunk.to_rows(tag=tags.__setitem__)
    assert got == _reference_rows(chunk) == _expected(rows)
    assert chunk.to_tuples() == [
        tuple(row[name] for name in ALL_TYPES.column_names) for row in got]
    # what engaged: the live prefix is cut on the device only where the
    # planes are large AND its bucket is shorter than they are
    bucket = pad_capacity(row_count)
    prefix = over and bucket < capacity
    assert tags == {"fetch": "prefix" if prefix else "whole",
                    "bytes": _fetched_bytes(
                        chunk, bucket if prefix else capacity)}


@pytest.mark.parametrize("capacity", [256, 1 << 18])
def test_column_decode_alone_matches_the_chunk(capacity):
    """A column decoded on its own (`operations/reduce_op.decode_keys`)
    fetches its own planes the same way: whole at 256 slots, the 8-byte
    ones by the prefix path at 262,144."""
    from ytsaurus_tpu.chunks import columnar
    chunk = ColumnarChunk.from_rows(ALL_TYPES, _all_types_rows(12),
                                    capacity=capacity)
    rows = chunk.to_rows()
    for name, col in chunk.columns.items():
        assert col.decode(chunk.row_count) == [row[name] for row in rows]
        assert col.decode(3) == [row[name] for row in rows[:3]]
        assert col.decode(0) == []
    fetch = columnar.fetch_prefix(chunk.columns["i"].fetched_planes(), 12)[1]
    assert fetch == ("whole" if capacity == 256 else "prefix")


class _Counting:
    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self.fn(*args, **kwargs)


@pytest.fixture
def fetch_counters(monkeypatch):
    """The one fetch site (`jax.device_get`, as `fetch_prefix` calls it),
    the one program of the decode (`_cut_planes`), and every program jax
    compiles meanwhile: a fresh shape that dispatched any would compile."""
    import jax
    from ytsaurus_tpu.chunks import columnar
    gets = _Counting(jax.device_get)
    cuts = _Counting(columnar._cut_planes)
    monkeypatch.setattr(jax, "device_get", gets)
    monkeypatch.setattr(columnar, "_cut_planes", cuts)
    compiles = []

    def listener(event, duration, **kwargs):
        if "compile" in event:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield gets, cuts, compiles
    jax.monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize("columns", [1, 3, 8])
def test_one_fetch_per_to_rows_and_no_program_on_the_whole_path(
        fetch_counters, columns):
    import jax
    gets, cuts, compiles = fetch_counters
    # capacities no other test uses: a program over them would compile
    chunk, expected = _first_columns(columns, capacity=640 + columns)
    jax.block_until_ready([col.data for col in chunk.columns.values()])
    del compiles[:]
    assert chunk.to_rows() == expected
    assert (gets.calls, cuts.calls, compiles) == (1, 0, [])
    assert chunk.to_tuples() == [tuple(r.values()) for r in chunk.to_rows()]
    assert (gets.calls, cuts.calls, compiles) == (3, 0, [])


@pytest.mark.parametrize("columns", [1, 3, 8])
def test_one_fetch_and_one_program_per_to_rows_on_the_prefix_path(
        fetch_counters, columns):
    from ytsaurus_tpu.chunks import columnar
    gets, cuts, _ = fetch_counters
    chunk, expected = _first_columns(columns, capacity=1 << 18)
    assert chunk.nbytes > columnar.WHOLE_FETCH_BYTES
    tags = {}
    assert chunk.to_rows(tag=tags.__setitem__) == expected
    assert (gets.calls, cuts.calls) == (1, 1)
    assert tags == {"fetch": "prefix", "bytes": _fetched_bytes(chunk, 128)}

