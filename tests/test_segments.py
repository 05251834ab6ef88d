"""ops/segments.py's segment reduce against numpy, on the program every
backend runs: the dense broadcast at or below 256 segments, above it one
sort by segment id + `_sorted_segment_reduce` (segmented scan, then each
segment's last row placed by one unique-index scatter: no search).
Boundary sizes on both sides of the limit, empty segments, nulls, and
masked rows parked at `num_segments`.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ytsaurus_tpu.ops import segments
from ytsaurus_tpu.schema import EValueType

SEGMENTS = [1, 16, 17, 256, 257, 10_000]
DTYPES = {"int64": (np.int64, EValueType.int64),
          "double": (np.float64, EValueType.double)}


def _rows(num_segments, dtype, in_order, seed):
    """(data, valid, seg_ids): about a fifth of the segments empty, a
    tenth of the rows masked (id == num_segments), a sixth null."""
    rng = np.random.default_rng(seed)
    n = max(40, 3 * num_segments)
    live = np.flatnonzero(rng.random(num_segments) < 0.8)
    if live.size == 0:
        live = np.array([0])
    ids = rng.choice(live, n)
    ids[rng.random(n) < 0.1] = num_segments
    if in_order:
        ids = np.sort(ids, kind="stable")
    valid = rng.random(n) > 0.15
    if dtype is np.int64:
        data = rng.integers(-1000, 1000, n, dtype=np.int64)
    else:
        data = rng.normal(0.0, 1e3, n)
    return data, valid, ids.astype(np.int32)


def _reference(function, data, valid, ids, num_segments):
    """(out, out_valid): per segment over its valid rows, in row order;
    a slot's value means something where `out_valid` is set."""
    out = np.zeros(num_segments, dtype=np.int64 if function == "count"
                   else data.dtype)
    any_valid = np.zeros(num_segments, dtype=bool)
    for s in range(num_segments):
        rows = data[(ids == s) & valid]
        any_valid[s] = rows.size > 0
        if function == "count":
            out[s] = rows.size
        elif rows.size:
            out[s] = {"sum": np.sum, "min": np.min, "max": np.max,
                      "first": lambda r: r[0]}[function](rows)
    if function == "count":
        return out, np.ones(num_segments, dtype=bool)
    return out, any_valid


@pytest.mark.parametrize("in_order", [True, False],
                         ids=["sorted", "unsorted"])
@pytest.mark.parametrize("num_segments", SEGMENTS)
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("function", ["sum", "min", "max", "count", "first"])
def test_segment_aggregate_matches_numpy(function, dtype, num_segments,
                                         in_order):
    np_dtype, value_type = DTYPES[dtype]
    data, valid, ids = _rows(num_segments, np_dtype, in_order,
                             seed=num_segments + in_order)
    run = jax.jit(lambda d, v, s: segments.segment_aggregate(
        function, d, v, s, num_segments, value_type,
        assume_sorted=in_order))
    out, out_valid = run(jnp.asarray(data), jnp.asarray(valid),
                         jnp.asarray(ids))
    want, want_valid = _reference(function, data, valid, ids, num_segments)
    out, out_valid = np.asarray(out), np.asarray(out_valid)
    assert out.shape == (num_segments,) and out.dtype == want.dtype
    np.testing.assert_array_equal(out_valid, want_valid)
    if function == "sum" and dtype == "double":
        # the scan adds a segment's rows in another order than numpy:
        # float64 rounding over <= a few hundred values of ~1e3
        np.testing.assert_allclose(out[want_valid], want[want_valid],
                                   rtol=1e-12, atol=1e-9)
    else:
        np.testing.assert_array_equal(out[want_valid], want[want_valid])


@pytest.mark.parametrize("num_segments", [256, 257])
@pytest.mark.parametrize("function", ["sum", "min", "max"])
def test_segment_reduce_empty_segments_read_neutral(function, num_segments):
    """`_segment_reduce` itself, on both sides of the dense limit: a
    segment no row names reads the function's neutral, ids at or past
    `num_segments` drop."""
    rng = np.random.default_rng(num_segments)
    n = 2000
    ids = rng.choice(np.arange(0, num_segments, 2), n)     # odd ones empty
    ids[::7] = num_segments
    ids[::11] = num_segments + 5
    data = rng.integers(-99, 99, n, dtype=np.int64)
    out = np.asarray(jax.jit(lambda d, s: segments._segment_reduce(
        function, d, s, num_segments))(jnp.asarray(data),
                                       jnp.asarray(ids.astype(np.int32))))
    info = np.iinfo(np.int64)
    neutral = {"sum": 0, "min": info.max, "max": info.min}[function]
    fold = {"sum": np.sum, "min": np.min, "max": np.max}[function]
    want = np.array([fold(data[ids == s]) if (ids == s).any() else neutral
                     for s in range(num_segments)])
    np.testing.assert_array_equal(out, want)


def _sorted_plane_cases():
    """(name, ids, num_segments): nondecreasing id planes at the edges of
    "row i closes its segment where row i + 1 opens one"."""
    rng = np.random.default_rng(33)
    return [
        ("all_masked", np.full(700, 300), 300),
        ("all_masked_mixed", np.sort(rng.choice([300, 305, 999], 700)), 300),
        ("one_segment_every_row", np.full(700, 41), 300),
        ("one_segment_last_id", np.full(700, 299), 300),
        ("final_row_alone", np.r_[np.repeat(np.arange(233), 3), [299]], 300),
        ("final_row_alone_before_nothing", np.r_[np.zeros(699, int), [7]],
         300),
        ("first_row_alone", np.r_[[0], np.full(699, 5)], 300),
        ("final_row_masked_alone", np.r_[np.repeat(np.arange(233), 3),
                                         [300]], 300),
        ("more_segments_than_rows", np.sort(rng.choice(5000, 700)), 5000),
        ("more_segments_one_row", np.array([4321]), 5000),
        ("every_row_its_own", np.arange(700), 700),
        ("every_row_its_own_then_masked", np.r_[np.arange(400),
                                                np.full(300, 700)], 700),
    ]


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("function", ["sum", "min", "max"])
@pytest.mark.parametrize("case", _sorted_plane_cases(),
                         ids=lambda c: c[0])
def test_sorted_segment_reduce_edge_planes(case, function, dtype):
    """`_sorted_segment_reduce` itself on the planes the marks form could
    get wrong: every row masked, one segment holding every row, the final
    row alone in its segment, more segments than rows."""
    _, ids, num_segments = case
    assert num_segments > segments._DENSE_SEGMENT_LIMIT
    assert (np.diff(ids) >= 0).all()
    rng = np.random.default_rng(len(ids) + num_segments)
    if dtype == "int64":
        data = rng.integers(-1000, 1000, len(ids), dtype=np.int64)
        info = np.iinfo(np.int64)
        neutral = {"sum": 0, "min": info.max, "max": info.min}[function]
    else:
        data = rng.normal(0.0, 1e3, len(ids))
        neutral = {"sum": 0.0, "min": np.inf, "max": -np.inf}[function]
    out = np.asarray(jax.jit(lambda d, s: segments._sorted_segment_reduce(
        function, d, s, num_segments))(
            jnp.asarray(data), jnp.asarray(ids.astype(np.int32))))
    fold = {"sum": np.sum, "min": np.min, "max": np.max}[function]
    want = np.full(num_segments, neutral, dtype=data.dtype)
    for s in np.unique(ids[ids < num_segments]):
        want[s] = fold(data[ids == s])
    assert out.shape == want.shape and out.dtype == want.dtype
    if function == "sum" and dtype == "double":
        np.testing.assert_allclose(out, want, rtol=1e-12, atol=1e-9)
    else:
        np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("function", ["sum", "min", "max"])
def test_sorted_segment_reduce_has_no_search(function, dtype):
    """Each segment's last row comes from the start marks: traced at
    1,048,576 rows and 1,048,576 segments (nothing executes), the reduce
    carries no loop over row-sized state (a search is one, with two
    row-sized gathers a step) and at most two row-sized gather-or-scatter
    ops, the placement's; the one scatter promises distinct indices."""
    from test_radix import row_sized_loops_and_moves
    n = 1 << 20
    jaxpr = jax.make_jaxpr(
        lambda d, s: segments._sorted_segment_reduce(function, d, s, n))(
            jax.ShapeDtypeStruct((n,), DTYPES[dtype][0]),
            jax.ShapeDtypeStruct((n,), jnp.int32))
    row_loops, row_moves = row_sized_loops_and_moves(jaxpr, n)
    assert not row_loops
    assert 1 <= len(row_moves) <= 2
    for eqn in row_moves:
        if eqn.primitive.name.startswith("scatter"):
            assert eqn.primitive.name == "scatter"      # a set, never an add
            assert eqn.params["unique_indices"]


GROUPS = 1000
GROUP_QUERIES = {
    # integer reference key: ids from the key's min/max (arithmetic), one
    # presort shared by the aggregates
    "key_range": "g, sum(v) AS s, min(x) AS lo, max(x) AS hi, "
                 "count(v) AS c FROM [//seg/t] GROUP BY g",
    # expression key: exact group order + segment boundaries
    "general": "k % 1000 AS g, sum(v) AS s, min(x) AS lo, max(x) AS hi, "
               "count(v) AS c FROM [//seg/t] GROUP BY k % 1000",
}


@pytest.fixture(scope="module")
def grouped_client(tmp_path_factory):
    from ytsaurus_tpu.client import connect
    client = connect(str(tmp_path_factory.mktemp("segments")))
    client.create("table", "//seg/t", recursive=True, attributes={
        "schema": [{"name": "k", "type": "int64"},
                   {"name": "g", "type": "int64"},
                   {"name": "v", "type": "int64"},
                   {"name": "x", "type": "double"}]})
    rng = np.random.default_rng(32)
    n = 6000
    k = rng.integers(0, 1 << 40, n)
    v = rng.integers(-500, 500, n)
    x = rng.normal(0.0, 10.0, n)
    rows = [{"k": int(k[i]), "g": int(k[i] % GROUPS),
             "v": None if i % 9 == 0 else int(v[i]), "x": float(x[i])}
            for i in range(n)]
    client.write_table("//seg/t", rows)
    return client, rows


@pytest.mark.parametrize("shape", sorted(GROUP_QUERIES))
def test_group_by_above_dense_limit_runs_sorted_reduce(
        grouped_client, monkeypatch, shape):
    """A GROUP BY of 1,000 groups through `select_rows` on this backend
    traces `_sorted_segment_reduce` (the chip's path) and answers as
    numpy does."""
    client, rows = grouped_client
    entered = []
    inner = segments._sorted_segment_reduce

    def counting(function, data, seg_ids, num_segments):
        entered.append((function, num_segments))
        return inner(function, data, seg_ids, num_segments)

    monkeypatch.setattr(segments, "_sorted_segment_reduce", counting)
    got = client.select_rows(GROUP_QUERIES[shape])
    assert client.last_query_statistics.execution_tier == "compiled"
    assert entered and all(n > segments._DENSE_SEGMENT_LIMIT
                           for _, n in entered)
    assert {f for f, _ in entered} == {"sum", "min", "max"}

    want = {}
    for row in rows:
        s = want.setdefault(row["g"], {"g": row["g"], "s": None,
                                       "lo": np.inf, "hi": -np.inf, "c": 0})
        if row["v"] is not None:
            s["s"] = (s["s"] or 0) + row["v"]
            s["c"] += 1
        s["lo"] = min(s["lo"], row["x"])
        s["hi"] = max(s["hi"], row["x"])
    assert len(want) > segments._DENSE_SEGMENT_LIMIT
    assert sorted(got, key=lambda r: r["g"]) == \
        [want[g] for g in sorted(want)]


def test_group_topk_above_dense_limit_matches_numpy(tmp_path):
    """The top-k cell's shape in small: `GROUP BY k ORDER BY sum DESC, k
    LIMIT 10` over ~5,000 groups, a WHERE that masks rows (and whole
    groups) out and null values, through `select_rows` on the compiled
    tier, against numpy."""
    from ytsaurus_tpu.client import connect
    client = connect(str(tmp_path))
    client.create("table", "//seg/topk", recursive=True, attributes={
        "schema": [{"name": "k", "type": "int64"},
                   {"name": "f", "type": "int64"},
                   {"name": "price", "type": "double"},
                   {"name": "disc", "type": "double"}]})
    rng = np.random.default_rng(33)
    n, groups = 20_000, 5_000
    k = rng.integers(0, groups, n) * 7 + 3          # sparse keys, 1..~12 rows
    f = rng.integers(0, 10, n)
    price = np.round(rng.uniform(900.0, 105_000.0, n), 2)
    disc = rng.integers(0, 11, n) / 100.0
    null = rng.random(n) < 0.12
    client.write_table("//seg/topk", [
        {"k": int(k[i]), "f": int(f[i]),
         "price": None if null[i] else float(price[i]),
         "disc": float(disc[i])} for i in range(n)])
    got = client.select_rows(
        "k, sum(price * (1 - disc)) AS revenue, count(price) AS c "
        "FROM [//seg/topk] WHERE f < 7 GROUP BY k "
        "ORDER BY sum(price * (1 - disc)) DESC, k LIMIT 10")
    assert client.last_query_statistics.execution_tier == "compiled"

    keep = f < 7
    keys = np.unique(k[keep])
    assert len(keys) > 4_000 and len(keys) < len(np.unique(k))
    revenue, count = {}, {}
    for i in np.flatnonzero(keep):                  # row order, as the scan
        count.setdefault(k[i], 0)
        if not null[i]:
            revenue[k[i]] = revenue.get(k[i], 0.0) + \
                price[i] * (1 - disc[i])
            count[k[i]] += 1
    assert len(revenue) < len(keys)                 # some groups all null
    # a group whose values are all null sums to null, which DESC puts last
    ranked = sorted(revenue, key=lambda g: (-revenue[g], g))[:10]
    assert [r["k"] for r in got] == [int(g) for g in ranked]
    assert [r["c"] for r in got] == [count[g] for g in ranked]
    np.testing.assert_allclose([r["revenue"] for r in got],
                               [revenue[g] for g in ranked], rtol=1e-12)


@pytest.mark.parametrize("engine", ["lsd32", "radix_scatter", "bogus"])
def test_retired_and_unknown_sort_engines_raise(monkeypatch, engine):
    """A name the dispatch does not know (the two retired engines
    included) must not silently run the network."""
    monkeypatch.setenv("YT_TPU_SORT_ENGINE", engine)
    with pytest.raises(ValueError, match="YT_TPU_SORT_ENGINE"):
        segments.stable_argsort_u32([jnp.arange(8, dtype=jnp.uint32)])
