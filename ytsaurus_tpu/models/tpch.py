"""TPC-H-shaped workloads: the framework's flagship "models".

Data generators (seeded, numpy) + query text for the BASELINE.md configs:
  Q1  — scan + filter + 8-aggregate GROUP BY over lineitem
  Q3  — two-table join + GROUP BY (customer/orders condensed into dims)
These drive bench.py and the graft entry; the chip-measured join deployment
is benchmark/configs/tpch-orders-lineitem.json (dbgen-shaped ORDERS +
LINEITEM, TPC-H Q12; cell tpch_q12_join).
"""

from __future__ import annotations

import numpy as np

from ytsaurus_tpu.chunks.columnar import ColumnarChunk
from ytsaurus_tpu.schema import TableSchema

LINEITEM_SCHEMA = TableSchema.make([
    ("l_orderkey", "int64"),
    ("l_quantity", "double"),
    ("l_extendedprice", "double"),
    ("l_discount", "double"),
    ("l_tax", "double"),
    ("l_returnflag", "string"),
    ("l_linestatus", "string"),
    ("l_shipdate", "int64"),          # days since epoch
])

ORDERS_SCHEMA = TableSchema.make([
    ("o_orderkey", "int64", "ascending"),
    ("o_custkey", "int64"),
    ("o_orderdate", "int64"),
    ("o_shippriority", "int64"),
])

# TPC-H date constants expressed as days since 1970-01-01.
_DATE_1998_09_02 = 10471
_DATE_1995_03_15 = 9204

Q1 = (
    "l_returnflag, l_linestatus, "
    "sum(l_quantity) AS sum_qty, "
    "sum(l_extendedprice) AS sum_base_price, "
    "sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
    "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge, "
    "avg(l_quantity) AS avg_qty, "
    "avg(l_extendedprice) AS avg_price, "
    "avg(l_discount) AS avg_disc, "
    "count(*) AS count_order "
    f"FROM [//tpch/lineitem] WHERE l_shipdate <= {_DATE_1998_09_02} "
    "GROUP BY l_returnflag, l_linestatus"
)

Q3 = (
    "l_orderkey, "
    "sum(l_extendedprice * (1 - l_discount)) AS revenue "
    "FROM [//tpch/lineitem] "
    "JOIN [//tpch/orders] ON l_orderkey = o_orderkey "
    f"WHERE o_orderdate < {_DATE_1995_03_15} "
    "GROUP BY l_orderkey "
    "ORDER BY sum(l_extendedprice * (1 - l_discount)) DESC, l_orderkey "
    "LIMIT 10"
)


def device_planes(specs: dict, n_rows: int, seed: int = 0) -> dict:
    """Generate column planes ON DEVICE with jax.random — nothing crosses
    the host↔device link, so set-up time does not grow with a host copy
    of the table (the in-memory-mode analog).

    specs: name → ("arange",) | ("randint", lo, hi) | ("uniform", lo, hi)
                 | ("randint_f64", lo, hi)
    Planes come back zero-padded to pad_capacity(n_rows) with values only
    in [0, n_rows).
    """
    import jax
    import jax.numpy as jnp
    from jax import random

    from ytsaurus_tpu.chunks.columnar import pad_capacity

    cap = pad_capacity(max(n_rows, 1))
    names = sorted(specs)

    def gen(key):
        valid = jnp.arange(cap) < n_rows
        out = {}
        for i, name in enumerate(names):
            spec = specs[name]
            k = random.fold_in(key, i)
            kind = spec[0]
            if kind == "arange":
                plane = jnp.arange(cap, dtype=jnp.int64)
            elif kind == "randint":
                plane = random.randint(k, (cap,), spec[1], spec[2],
                                       dtype=jnp.int64)
            elif kind == "randint_f64":
                plane = random.randint(k, (cap,), spec[1], spec[2],
                                       dtype=jnp.int64).astype(jnp.float64)
            elif kind == "uniform":
                plane = random.uniform(k, (cap,), dtype=jnp.float64,
                                       minval=spec[1], maxval=spec[2])
            else:
                raise ValueError(f"Unknown spec {spec!r}")
            zero = jnp.zeros((), dtype=plane.dtype)
            out[name] = jnp.where(valid, plane, zero)
        return out

    return jax.jit(gen)(random.PRNGKey(seed))


def device_chunk(schema: TableSchema, planes: dict, n_rows: int,
                 dictionaries: dict | None = None) -> ColumnarChunk:
    """Wrap device-resident planes into a ColumnarChunk (no host copy)."""
    import jax.numpy as jnp

    from ytsaurus_tpu.chunks.columnar import Column, pad_capacity
    from ytsaurus_tpu.schema import device_dtype

    cap = pad_capacity(max(n_rows, 1))
    valid = jnp.arange(cap) < n_rows
    columns = {}
    for col in schema:
        data = planes[col.name].astype(device_dtype(col.type))
        vocab = None
        if dictionaries is not None and col.name in dictionaries:
            vocab = np.asarray(dictionaries[col.name], dtype=object)
        columns[col.name] = Column(type=col.type, data=data, valid=valid,
                                   dictionary=vocab)
    return ColumnarChunk(schema=schema, row_count=n_rows, columns=columns)


def generate_lineitem_device(n_rows: int, seed: int = 0,
                             n_orders: int | None = None) -> ColumnarChunk:
    """lineitem generated entirely in HBM (same schema/distributions as
    generate_lineitem; dictionary codes for the two flag columns)."""
    n_orders = n_orders or max(n_rows // 4, 1)
    planes = device_planes({
        "l_orderkey": ("randint", 0, n_orders),
        "l_quantity": ("randint_f64", 1, 51),
        "l_extendedprice": ("uniform", 900.0, 105000.0),
        "l_discount": ("uniform", 0.0, 0.10),
        "l_tax": ("uniform", 0.0, 0.08),
        "l_returnflag": ("randint", 0, 3),
        "l_linestatus": ("randint", 0, 2),
        "l_shipdate": ("randint", 8000, 10600),
    }, n_rows, seed)
    flags = np.array([b"A", b"N", b"R"], dtype=object)
    status = np.array([b"F", b"O"], dtype=object)
    return device_chunk(LINEITEM_SCHEMA, planes, n_rows,
                        dictionaries={"l_returnflag": flags,
                                      "l_linestatus": status})


def generate_orders_device(n_orders: int, seed: int = 1) -> ColumnarChunk:
    planes = device_planes({
        "o_orderkey": ("arange",),
        "o_custkey": ("randint", 0, max(n_orders // 10, 1)),
        "o_orderdate": ("randint", 8000, 10600),
        "o_shippriority": ("randint", 0, 2),
    }, n_orders, seed)
    return device_chunk(ORDERS_SCHEMA, planes, n_orders)


def generate_lineitem(n_rows: int, seed: int = 0,
                      n_orders: int | None = None) -> ColumnarChunk:
    rng = np.random.default_rng(seed)
    n_orders = n_orders or max(n_rows // 4, 1)
    flags = np.array([b"A", b"N", b"R"], dtype=object)
    status = np.array([b"F", b"O"], dtype=object)
    return ColumnarChunk.from_arrays(
        LINEITEM_SCHEMA,
        {
            "l_orderkey": rng.integers(0, n_orders, n_rows),
            "l_quantity": rng.integers(1, 51, n_rows).astype(np.float64),
            "l_extendedprice": rng.uniform(900.0, 105000.0, n_rows),
            "l_discount": rng.uniform(0.0, 0.10, n_rows),
            "l_tax": rng.uniform(0.0, 0.08, n_rows),
            "l_returnflag": rng.integers(0, 3, n_rows),
            "l_linestatus": rng.integers(0, 2, n_rows),
            "l_shipdate": rng.integers(8000, 10600, n_rows),
        },
        dictionaries={"l_returnflag": flags, "l_linestatus": status})


def generate_orders(n_orders: int, seed: int = 1) -> ColumnarChunk:
    rng = np.random.default_rng(seed)
    return ColumnarChunk.from_arrays(
        ORDERS_SCHEMA,
        {
            "o_orderkey": np.arange(n_orders),
            "o_custkey": rng.integers(0, max(n_orders // 10, 1), n_orders),
            "o_orderdate": rng.integers(8000, 10600, n_orders),
            "o_shippriority": rng.integers(0, 2, n_orders),
        })


def q1_reference_numpy(chunk: ColumnarChunk) -> dict:
    """Numpy oracle for Q1 (returns {(flag, status): (sum_qty, count)})."""
    n = chunk.row_count
    ship = np.asarray(chunk.column("l_shipdate").data[:n])
    rf = np.asarray(chunk.column("l_returnflag").data[:n])
    ls = np.asarray(chunk.column("l_linestatus").data[:n])
    qty = np.asarray(chunk.column("l_quantity").data[:n])
    mask = ship <= _DATE_1998_09_02
    out = {}
    for f in range(3):
        for s in range(2):
            sel = mask & (rf == f) & (ls == s)
            out[(f, s)] = (float(qty[sel].sum()), int(sel.sum()))
    return out
