"""models/tpch.py's Q1 and Q3 over its own generators, against numpy:
the group counts and the LIMIT the retired harness asserted while it
timed them, and the rows besides.  Q3 is a join, then a GROUP BY of
about a thousand keys (the sorted segment reduce), then a top-10.
"""

import numpy as np

from ytsaurus_tpu.models import tpch
from ytsaurus_tpu.query.builder import build_query
from ytsaurus_tpu.query.engine.evaluator import Evaluator

SCHEMAS = {"//tpch/lineitem": tpch.LINEITEM_SCHEMA,
           "//tpch/orders": tpch.ORDERS_SCHEMA}


def _host(chunk, name):
    return np.asarray(chunk.column(name).data[:chunk.row_count])


def test_q1_groups_match_numpy():
    chunk = tpch.generate_lineitem(4096)
    out = Evaluator().run_plan(build_query(tpch.Q1, SCHEMAS), chunk)
    rows = out.to_rows()
    want = {key: value for key, value in
            tpch.q1_reference_numpy(chunk).items() if value[1]}
    assert 1 <= len(rows) <= 6 and len(rows) == len(want)
    flags, status = [b"A", b"N", b"R"], [b"F", b"O"]
    for row in rows:
        sum_qty, count = want[(flags.index(row["l_returnflag"]),
                               status.index(row["l_linestatus"]))]
        assert row["count_order"] == count
        assert row["sum_qty"] == sum_qty      # whole quantities: exact


def test_q3_top10_matches_numpy():
    n_orders = 1200
    lines = tpch.generate_lineitem(4096, n_orders=n_orders)
    orders = tpch.generate_orders(n_orders)
    out = Evaluator().run_plan(build_query(tpch.Q3, SCHEMAS), lines,
                               {"//tpch/orders": orders})
    rows = out.to_rows()

    date = _host(orders, "o_orderdate")          # o_orderkey == position
    key = _host(lines, "l_orderkey")
    kept = date[key] < tpch._DATE_1995_03_15
    revenue = np.zeros(n_orders)
    np.add.at(revenue, key[kept], (_host(lines, "l_extendedprice")
                                   * (1 - _host(lines, "l_discount")))[kept])
    present = np.flatnonzero(np.bincount(key[kept], minlength=n_orders))
    assert present.size > 256                    # above the dense limit
    top = sorted(present, key=lambda k: (-revenue[k], k))[:10]
    assert len(rows) == 10
    assert [r["l_orderkey"] for r in rows] == [int(k) for k in top]
    np.testing.assert_allclose([r["revenue"] for r in rows], revenue[top],
                               rtol=1e-12)
