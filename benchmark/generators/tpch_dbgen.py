"""Host arrays of TPC-H LINEITEM as the specification's clause 4.2.3 builds
it: all 16 columns, 1 to 7 lines to an order, sparse order keys (the first 8
of every 32), part and supplier keys tied together, the extended price worked
out from the part's retail price, ship / commit / receipt dates hung on the
order's date, and the return flag and line status derived from those dates.
Decimals are whole hundredths.  The draws come from one
`numpy.random.default_rng(seed)` and not from dbgen's own per-column streams,
and the comment text from a small pool (see the configuration's `assumed`).
"""

import datetime

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)


def _days(y, m, d):
    return (datetime.date(y, m, d) - _EPOCH).days


START_DATE = _days(1992, 1, 1)
END_DATE = _days(1998, 12, 31)
CURRENT_DATE = _days(1995, 6, 17)

# Vocabularies in sorted order: the program's string codes keep order.
RETURN_FLAGS = ["A", "N", "R"]
LINE_STATUS = ["F", "O"]
SHIP_INSTRUCT = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                 "TAKE BACK RETURN"]
SHIP_MODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]

# Word lists of the spec's text grammar (clause 4.2.2.13), shortened.
_NOUNS = ("packages requests accounts deposits foxes ideas theodolites "
          "pinto beans instructions dependencies excuses platelets asymptotes "
          "courts dolphins multipliers sauternes warthogs frets dinos "
          "attainments somas braids hockey players frays warhorses dugouts "
          "notornis epitaphs pearls tithes waters orbits gifts sheaves "
          "depths sentiments decoys realms pains grouches escapades").split()
_VERBS = ("sleep wake are cajole haggle nag use boost affix detect integrate "
          "maintain nod was lose sublate solve thrash promise engage hinder "
          "print x-ray breach eat grow impress mold poach serve run dazzle "
          "snooze doze unwind kindle play hang believe doubt").split()
_ADJECTIVES = ("furious sly careful blithe quick fluffy slow quiet ruthless "
               "thin close dogged daring brave stealthy permanent enticing "
               "idle busy regular final ironic even bold silent").split()
_ADVERBS = ("sometimes always never furiously slyly carefully blithely "
            "quickly fluffily slowly quietly ruthlessly thinly closely "
            "doggedly daringly bravely stealthily permanently enticingly "
            "idly busily regularly finally ironically evenly boldly "
            "silently").split()
_PREPOSITIONS = ("about above across after against along among around at "
                 "atop before behind beneath beside besides between beyond "
                 "by despite during except for from inside into near of on "
                 "outside over past since through throughout to toward "
                 "under until up upon without with within").split()
_TERMINATORS = [".", ";", ":", "?", "!", "--"]
COMMENT_MIN, COMMENT_MAX = 10, 43          # varchar(44), average 27
_POOL_SENTENCES = 200_000                  # about 9 MB of text


def sparse_order_keys(orders):
    """dbgen's mk_sparse: the low 3 bits of the order's number stay, the
    rest moves up by 2 bits, so 8 keys of every 32 are used."""
    number = np.arange(1, orders + 1, dtype=np.int64)
    return ((number >> 3) << 5) | (number & 7)


def lines_per_order(rng, orders, rows):
    """1..7 lines to an order, uniform; then single lines added to or taken
    from orders drawn at random until the table has exactly `rows`."""
    if not orders <= rows <= 7 * orders:
        raise ValueError(f"{rows} rows do not fit {orders} orders of 1..7")
    counts = rng.integers(1, 8, orders)
    while (diff := rows - int(counts.sum())):
        step = 1 if diff > 0 else -1
        able = np.flatnonzero(counts < 7 if step > 0 else counts > 1)
        counts[rng.choice(able, min(abs(diff), len(able)), replace=False)] \
            += step
    return counts


def text_pool(rng):
    """Sentences of the grammar's commonest shape (adverb? adjective noun
    verb preposition the adjective noun terminator), as one byte string."""
    n = _POOL_SENTENCES
    def pick(words):
        return np.array(words, dtype=object)[rng.integers(0, len(words), n)]
    with_adverb = rng.random(n) < 0.5
    parts = zip(with_adverb, pick(_ADVERBS), pick(_ADJECTIVES), pick(_NOUNS),
                pick(_VERBS), pick(_PREPOSITIONS), pick(_ADJECTIVES),
                pick(_NOUNS), pick(_TERMINATORS))
    text = " ".join(
        f"{adv + ' ' if lead else ''}{a1} {n1} {verb} {prep} the {a2} {n2}"
        f"{end}" for lead, adv, a1, n1, verb, prep, a2, n2, end in parts)
    return np.frombuffer(text.encode(), dtype=np.uint8)


def comments(rng, rows):
    """`rows` substrings of the pool, each 10 to 43 bytes, as an S43 array
    (numpy drops the padding NULs)."""
    pool = text_pool(rng)
    offset = rng.integers(0, len(pool) - COMMENT_MAX, rows)
    length = rng.integers(COMMENT_MIN, COMMENT_MAX + 1, rows)
    out = np.empty(rows, dtype=f"S{COMMENT_MAX}")
    chars = out.view(np.uint8).reshape(rows, COMMENT_MAX)
    column = np.arange(COMMENT_MAX, dtype=np.int64)
    block = 1 << 19
    for lo in range(0, rows, block):
        hi = min(lo + block, rows)
        taken = pool[offset[lo:hi, None] + column]
        taken[column >= length[lo:hi, None]] = 0
        chars[lo:hi] = taken
    return out


def generate(config, seed, sizes):
    """(host, vocabs): name -> array of `rows` values (low-cardinality
    string columns as integer codes, `l_comment` as raw bytes), and name ->
    sorted vocabulary for the coded columns."""
    rng = np.random.default_rng(seed)
    orders, rows = sizes["orders"], sizes["rows"]
    parts, suppliers = sizes["parts"], sizes["suppliers"]

    counts = lines_per_order(rng, orders, rows)
    order = np.repeat(np.arange(orders), counts)
    first_line = np.cumsum(counts) - counts
    order_date = rng.integers(START_DATE, END_DATE - 151 + 1, orders)[order]

    partkey = rng.integers(1, parts + 1, rows)
    supplier_step = rng.integers(0, 4, rows)
    suppkey = (partkey + supplier_step * (
        suppliers // 4 + (partkey - 1) // suppliers)) % suppliers + 1
    quantity = rng.integers(1, 51, rows)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    shipdate = order_date + rng.integers(1, 122, rows)
    commitdate = order_date + rng.integers(30, 91, rows)
    receiptdate = shipdate + rng.integers(1, 31, rows)
    returned = np.where(rng.integers(0, 2, rows) == 0,
                        RETURN_FLAGS.index("R"), RETURN_FLAGS.index("A"))

    host = {
        "l_orderkey": sparse_order_keys(orders)[order],
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": np.arange(rows) - first_line[order] + 1,
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": (quantity * retail_cents) / 100.0,
        "l_discount": rng.integers(0, 11, rows) / 100.0,
        "l_tax": rng.integers(0, 9, rows) / 100.0,
        "l_returnflag": np.where(receiptdate <= CURRENT_DATE, returned,
                                 RETURN_FLAGS.index("N")),
        "l_linestatus": (shipdate > CURRENT_DATE).astype(np.int64),
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, len(SHIP_INSTRUCT), rows),
        "l_shipmode": rng.integers(0, len(SHIP_MODES), rows),
        "l_comment": comments(rng, rows),
    }
    vocabs = {"l_returnflag": RETURN_FLAGS, "l_linestatus": LINE_STATUS,
              "l_shipinstruct": SHIP_INSTRUCT, "l_shipmode": SHIP_MODES}
    return host, vocabs
