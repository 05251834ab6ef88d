"""TPC-H-shaped workloads: the framework's flagship "models".

Data generators (seeded, numpy) + query text for the BASELINE.md configs:
  Q1  — scan + filter + 8-aggregate GROUP BY over lineitem
  Q3  — two-table join + GROUP BY (customer/orders condensed into dims)
These drive chip_smoke.py, the graft entry and tests/test_tpu_compile.py;
the chip-measured deployments are benchmark/configs/ (dbgen-shaped
LINEITEM and ORDERS; cells tpch_q1_sf1, tpch_group_topk_sf01,
tpch_q12_join).
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
