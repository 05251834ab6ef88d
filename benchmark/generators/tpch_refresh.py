"""The TPC-H refresh functions' data for LINEITEM (clauses 2.6 and 2.7),
drawn from the seed: for each refresh pair, RF1's new lines and RF2's
deleted keys.

- RF1 ("new sales") inserts `refresh_orders` new orders (SF x 1,500) with 1
  to 7 lines each.  Their keys come from the part of clause 4.2.3's sparse
  key space the load leaves unused: the load keeps bits 3 and 4 of every
  key at 0 (`tpch_dbgen.sparse_order_keys`), pair p sets them to p, as
  dbgen's update sets do.  The lines themselves are `tpch_dbgen`'s,
  drawn with a seed of their own.
- RF2 ("old sales") deletes every line of `refresh_orders` orders of the
  load, drawn without replacement; the pairs delete different orders.

`generate` returns one dict per pair, in commit order:
{"insert": name -> array (the generator's host form), "delete": int64
array (m, 2) of (l_orderkey, l_linenumber)}.
"""

import numpy as np

from generators import tpch_dbgen

PAIRS = 2


def update_keys(numbers, pair):
    """Sparse keys of order numbers with the update set's bits set."""
    numbers = np.asarray(numbers, dtype=np.int64)
    return ((numbers >> 3) << 5) | (pair << 3) | (numbers & 7)


def new_lines(config, rng, sizes, keys):
    """`tpch_dbgen`'s lines for len(keys) orders, each order's lines given
    its key from `keys` (ascending)."""
    orders = len(keys)
    lines = int(rng.integers(1, 8, orders).sum())
    host, _ = tpch_dbgen.generate(
        config, int(rng.integers(0, 2**62)),
        {"orders": orders, "rows": lines, "parts": sizes["parts"],
         "suppliers": sizes["suppliers"]})
    position = np.searchsorted(tpch_dbgen.sparse_order_keys(orders),
                               host["l_orderkey"])
    host["l_orderkey"] = keys[position]
    return host


def generate(config, seed, sizes, host):
    """The refresh pairs for the load `host` (the generator's arrays)."""
    rng = np.random.default_rng([seed, 0x7E1])
    count, orders = sizes["refresh_orders"], sizes["orders"]
    # RF1's order numbers, and RF2's loaded orders, distinct across pairs
    inserted = rng.choice(orders, (PAIRS, count), replace=False) + 1
    deleted = rng.choice(orders, (PAIRS, count), replace=False) + 1
    pairs = []
    for index in range(PAIRS):
        keys = np.sort(update_keys(inserted[index], index + 1))
        gone = np.isin(host["l_orderkey"],
                       tpch_dbgen.sparse_order_keys(orders)[deleted[index] - 1])
        pairs.append({
            "insert": new_lines(config, rng, sizes, keys),
            "delete": np.stack([host["l_orderkey"][gone],
                                host["l_linenumber"][gone]], axis=1)})
    return pairs
