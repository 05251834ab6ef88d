"""Host arrays of TPC-H CUSTOMER as the specification's clause 4.2.3 builds
it: all 8 columns, one row per customer key 1..`customers`, stored sorted.
It covers every `o_custkey` that `tpch_dbgen_orders.generate` draws from the
same sizes (the keys that are no multiple of 3); the customers that place no
order stay in the table, as the specification has them.  The draws come from
a stream of CUSTOMER's own, so a seed's lines and orders are what they are
with or without this table.
"""

import numpy as np

from generators import tpch_dbgen

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
NATIONS = 25
ADDRESS_MIN, ADDRESS_MAX = 10, 40          # varchar(40), average 25
COMMENT_MIN, COMMENT_MAX = 29, 116         # varchar(117), average 73
# Clause 4.2.2.7's v-string alphabet, shortened to what a comma-separated
# file keeps apart: digits, letters, space and the comma's neighbours.
_ADDRESS_CHARS = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyz"
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZ ,.", dtype=np.uint8)


def cut_strings(source, offset, length, width):
    """`len(offset)` byte strings as an S`width` array: `source[o:o + l]`
    for each (o, l), NUL-padded (numpy drops the padding)."""
    rows = len(offset)
    out = np.empty(rows, dtype=f"S{width}")
    chars = out.view(np.uint8).reshape(rows, width)
    column = np.arange(width, dtype=np.int64)
    taken = source[offset[:, None] + column]
    taken[column >= length[:, None]] = 0
    chars[:] = taken
    return out


def addresses(rng, rows):
    """Random strings of 10 to 40 characters (clause 4.2.2.7)."""
    length = rng.integers(ADDRESS_MIN, ADDRESS_MAX + 1, rows)
    letters = _ADDRESS_CHARS[rng.integers(0, len(_ADDRESS_CHARS),
                                          rows * ADDRESS_MAX)]
    return cut_strings(letters, np.arange(rows) * ADDRESS_MAX, length,
                       ADDRESS_MAX)


def phones(rng, nationkey):
    """Clause 4.2.2.9: country code (nation key + 10), then a local
    number of 3-3-4 digits drawn from 100..999, 100..999, 1000..9999."""
    rows = len(nationkey)
    parts = (nationkey + 10, rng.integers(100, 1000, rows),
             rng.integers(100, 1000, rows), rng.integers(1000, 10000, rows))
    return np.array(["%02d-%03d-%03d-%04d" % numbers
                     for numbers in zip(*(p.tolist() for p in parts))],
                    dtype="S15")


def comments(rng, rows):
    """`rows` substrings of the text pool the LINEITEM generator has, 29
    to 116 bytes, as an S116 array."""
    pool = tpch_dbgen.text_pool(rng)
    offset = rng.integers(0, len(pool) - COMMENT_MAX, rows)
    length = rng.integers(COMMENT_MIN, COMMENT_MAX + 1, rows)
    return cut_strings(pool, offset, length, COMMENT_MAX)


def generate(config, seed, sizes, orders=None):
    """(host, vocabs) of CUSTOMER.  `orders`, where given, are the ORDERS
    host arrays made from the same sizes: every customer key they hold has
    to be in this table."""
    customers = sizes["customers"]
    keys = np.arange(1, customers + 1, dtype=np.int64)
    if orders is not None and len(orders["o_custkey"]) and not (
            1 <= orders["o_custkey"].min()
            and orders["o_custkey"].max() <= customers):
        raise ValueError("an order's customer key is outside this table")
    rng = np.random.default_rng([seed, 2])       # CUSTOMER's own stream
    nationkey = rng.integers(0, NATIONS, customers)
    host = {
        "c_custkey": keys,
        "c_name": np.array([b"Customer#%09d" % key for key in keys.tolist()],
                           dtype="S18"),
        "c_address": addresses(rng, customers),
        "c_nationkey": nationkey,
        "c_phone": phones(rng, nationkey),
        "c_acctbal": rng.integers(-99999, 999999 + 1, customers) / 100.0,
        "c_mktsegment": rng.integers(0, len(SEGMENTS), customers),
        "c_comment": comments(rng, customers),
    }
    return host, {"c_mktsegment": SEGMENTS}
