"""Plain numpy reference of a LINEITEM that TPC-H refresh pairs have
written: RF1's lines appended and RF2's keys removed, pair by pair in commit
order, then the single-table spec evaluated over what is left
(`ql_spec.evaluate`).

It imports nothing of the program.  A key is (l_orderkey, l_linenumber),
with 1 to 7 lines an order (clause 4.2.3).
"""

import numpy as np

from reference import ql_spec


def _keys(orderkey, linenumber):
    linenumber = np.asarray(linenumber, dtype=np.int64)
    if len(linenumber) and not (1 <= linenumber.min() and
                                linenumber.max() <= 7):
        raise ValueError("l_linenumber outside 1..7")
    return np.asarray(orderkey, dtype=np.int64) * 8 + linenumber - 1


def visible(host, pairs):
    """The host arrays after every pair of `pairs`, in commit order."""
    out = dict(host)
    for pair in pairs:
        out = {name: np.concatenate([column, pair["insert"][name]])
               for name, column in out.items()}
        gone = np.isin(_keys(out["l_orderkey"], out["l_linenumber"]),
                       _keys(pair["delete"][:, 0], pair["delete"][:, 1]))
        out = {name: column[~gone] for name, column in out.items()}
    return out


def evaluate(spec, host, vocabs, pairs, dtype=np.float64):
    """Rows the spec selects from the load `host` once `pairs` are
    applied."""
    return ql_spec.evaluate(spec, visible(host, pairs), vocabs, dtype=dtype)
