"""Host arrays of TPC-H ORDERS as the specification's clause 4.2.3 builds
it, consistent with the LINEITEM that `tpch_dbgen.generate` makes from the
same seed and sizes: the same sparse order keys, `o_orderdate` the date the
lines' ship / commit dates hang on (the lineitem generator's first two draws,
replayed here from a generator of the same seed), `o_orderstatus` from the
lines' statuses, `o_totalprice` summed over the order's lines.  The columns
ORDERS has of its own (customer, priority, clerk, comment) come from a second
stream, so the lines of a seed are what they are without this table.
"""

import numpy as np

from generators import tpch_dbgen

ORDER_STATUS = ["F", "O", "P"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
COMMENT_MIN, COMMENT_MAX = 19, 78          # varchar(79), average 49


def order_dates(seed, sizes):
    """(lines per order, order date per order): `tpch_dbgen.generate`'s
    first two draws, from a generator in the state it starts in."""
    rng = np.random.default_rng(seed)
    counts = tpch_dbgen.lines_per_order(rng, sizes["orders"], sizes["rows"])
    dates = rng.integers(tpch_dbgen.START_DATE,
                         tpch_dbgen.END_DATE - 151 + 1, sizes["orders"])
    return counts, dates


def customer_keys(rng, orders, customers):
    """1..customers, never a multiple of 3 (a third of the customers place
    no order): uniform over the keys that are left."""
    allowed = customers - customers // 3
    rank = rng.integers(0, allowed, orders)      # rank among allowed keys
    return rank + rank // 2 + 1                  # 1, 2, 4, 5, 7, 8, ...


def comments(rng, rows):
    """`rows` substrings of the text pool, 19 to 78 bytes, as an S78
    array (as `tpch_dbgen.comments`, at ORDERS' lengths)."""
    pool = tpch_dbgen.text_pool(rng)
    offset = rng.integers(0, len(pool) - COMMENT_MAX, rows)
    length = rng.integers(COMMENT_MIN, COMMENT_MAX + 1, rows)
    out = np.empty(rows, dtype=f"S{COMMENT_MAX}")
    chars = out.view(np.uint8).reshape(rows, COMMENT_MAX)
    column = np.arange(COMMENT_MAX, dtype=np.int64)
    block = 1 << 18
    for lo in range(0, rows, block):
        hi = min(lo + block, rows)
        taken = pool[offset[lo:hi, None] + column]
        taken[column >= length[lo:hi, None]] = 0
        chars[lo:hi] = taken
    return out


def generate(config, seed, sizes, lineitem):
    """(host, vocabs) of ORDERS for the LINEITEM host arrays `lineitem`
    that `tpch_dbgen.generate` made from the same `seed` and `sizes`."""
    orders = sizes["orders"]
    counts, dates = order_dates(seed, sizes)
    starts = np.cumsum(counts) - counts
    keys = tpch_dbgen.sparse_order_keys(orders)
    if not np.array_equal(lineitem["l_orderkey"][starts], keys) or \
            not np.array_equal(lineitem["l_linenumber"][starts + counts - 1],
                               counts):
        raise ValueError("the lines were not made from this seed and sizes")

    # F where every line is F, O where every line is O, else P.
    open_lines = np.add.reduceat(lineitem["l_linestatus"], starts)
    status = np.where(open_lines == 0, ORDER_STATUS.index("F"),
                      np.where(open_lines == counts, ORDER_STATUS.index("O"),
                               ORDER_STATUS.index("P")))
    # Whole hundredths throughout: price in cents x (100 + tax %) x
    # (100 - discount %), summed exactly, rounded to the cent once.
    cents = np.rint(lineitem["l_extendedprice"] * 100).astype(np.int64)
    tax = np.rint(lineitem["l_tax"] * 100).astype(np.int64)
    discount = np.rint(lineitem["l_discount"] * 100).astype(np.int64)
    total = np.add.reduceat(cents * (100 + tax) * (100 - discount), starts)

    rng = np.random.default_rng([seed, 1])       # ORDERS' own stream
    host = {
        "o_orderkey": keys,
        "o_custkey": customer_keys(rng, orders, sizes["customers"]),
        "o_orderstatus": status,
        "o_totalprice": np.rint(total / 10000.0) / 100.0,
        "o_orderdate": dates,
        "o_orderpriority": rng.integers(0, len(PRIORITIES), orders),
        "o_clerk": rng.integers(0, sizes["clerks"], orders),
        "o_shippriority": np.zeros(orders, dtype=np.int64),
        "o_comment": comments(rng, orders),
    }
    vocabs = {"o_orderstatus": ORDER_STATUS, "o_orderpriority": PRIORITIES,
              "o_clerk": [f"Clerk#{n:09d}"
                          for n in range(1, sizes["clerks"] + 1)]}
    return host, vocabs
