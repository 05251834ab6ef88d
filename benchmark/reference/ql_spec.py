"""Plain numpy evaluator of a single-table query spec (filter, group keys,
aggregates, order keys, limit) over the generator's host arrays.

It imports nothing of the program.  A traffic file gives the spec beside
the QL text; this is what the program's answers are compared with.  `dtype`
is the precision every `double` column, expression and accumulation is held
in: float64 is the reference, float32 the precision control.
"""

import ast
import operator

import numpy as np

_BINOPS = {ast.Add: operator.add, ast.Sub: operator.sub,
           ast.Mult: operator.mul, ast.Div: operator.truediv}
_CMPOPS = {ast.LtE: operator.le, ast.Lt: operator.lt, ast.GtE: operator.ge,
           ast.Gt: operator.gt, ast.Eq: operator.eq, ast.NotEq: operator.ne}
# Above this many groups a per-group boolean mask costs too much; sums go
# over segments of the rows sorted by group instead.
_MASK_GROUPS = 64


def evaluate_expr(text, columns, dtype):
    """Arithmetic / comparison expression over column names and number
    literals; float literals take `dtype`, so nothing widens silently."""
    def walk(node):
        if isinstance(node, ast.BinOp) and type(node.op) in _BINOPS:
            return _BINOPS[type(node.op)](walk(node.left), walk(node.right))
        if isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and type(node.ops[0]) in _CMPOPS:
            return _CMPOPS[type(node.ops[0])](walk(node.left),
                                              walk(node.comparators[0]))
        if isinstance(node, ast.BoolOp) and isinstance(node.op, ast.And):
            out = walk(node.values[0])
            for value in node.values[1:]:
                out = out & walk(value)
            return out
        if isinstance(node, ast.Name):
            return columns[node.id]
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, (int, float)):
            if isinstance(node.value, float):
                return dtype(node.value)
            return node.value
        raise ValueError(f"unsupported expression node in {text!r}: "
                         f"{ast.dump(node)}")
    return walk(ast.parse(text, mode="eval").body)


class _Columns:
    """The host arrays as the expressions see them: float columns held in
    `dtype`, the filter's rows kept; each column prepared when first read
    (a query reads a few of the table's columns)."""

    def __init__(self, host, dtype):
        self.host, self.dtype, self.keep, self.ready = host, dtype, None, {}

    def __getitem__(self, name):
        if name not in self.ready:
            arr = self.host[name]
            if arr.dtype.kind == "f":
                arr = arr.astype(self.dtype)
            self.ready[name] = arr if self.keep is None else arr[self.keep]
        return self.ready[name]


def evaluate(spec, host, vocabs=None, dtype=np.float64):
    """Rows (list of dicts) the spec selects from `host` (name -> array).
    Coded string columns are integers in `host`; `vocabs` (name -> list)
    decodes them in the answer."""
    vocabs = vocabs or {}
    cols = _Columns(host, dtype)
    n = len(next(iter(host.values())))
    if spec.get("filter"):
        keep = evaluate_expr(spec["filter"], cols, dtype)
        cols = _Columns(host, dtype)
        cols.keep = keep
        n = int(keep.sum())

    group_by = spec.get("group_by") or []
    if not group_by:
        raise ValueError("only grouped specs are supported")
    # Group keys are integers (ids, dates, dictionary codes): one mixed-
    # radix int64 per row, so a 1-D unique does the grouping.
    composite = np.zeros(n, dtype=np.int64)
    radix = []
    for g in group_by:
        col = cols[g]
        if col.dtype.kind not in "iu":
            raise ValueError(f"group key {g!r} is not an integer column")
        lo = int(col.min()) if n else 0
        span = (int(col.max()) - lo + 1) if n else 1
        composite = composite * span + (col - lo)
        radix.append((lo, span))
    uniq, inverse, counts = np.unique(composite, return_inverse=True,
                                      return_counts=True)
    n_groups = len(uniq)
    out_cols = {}
    rest = uniq.copy()
    for g, (lo, span) in reversed(list(zip(group_by, radix))):
        out_cols[g] = rest % span + lo
        rest //= span
    out_cols = {g: out_cols[g] for g in group_by}

    if n_groups <= _MASK_GROUPS:
        members = [np.flatnonzero(inverse == g) for g in range(n_groups)]
    else:
        order = np.argsort(inverse, kind="stable")
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))

    def group_sum(values):
        values = np.asarray(values)
        if values.dtype.kind == "f":
            values = values.astype(dtype)
        if n_groups <= _MASK_GROUPS:
            # numpy's pairwise sum in `dtype`, one group at a time
            return np.array([values[m].sum(dtype=values.dtype)
                             for m in members])
        return np.add.reduceat(values[order], starts, dtype=values.dtype)

    for agg in spec["aggregates"]:
        fn = agg["fn"]
        if fn == "count":
            out_cols[agg["name"]] = counts
            continue
        total = group_sum(evaluate_expr(agg["expr"], cols, dtype))
        if fn == "sum":
            out_cols[agg["name"]] = total
        elif fn == "avg":
            out_cols[agg["name"]] = total / counts.astype(total.dtype)
        else:
            raise ValueError(f"unsupported aggregate {fn!r}")

    index = np.arange(n_groups)
    if spec.get("order_by"):
        sort_keys = []
        for name, direction in reversed(spec["order_by"]):
            col = out_cols[name]
            sort_keys.append(-col if direction == "desc" else col)
        index = np.lexsort(sort_keys)
    if spec.get("limit") is not None:
        index = index[:spec["limit"]]

    rows = []
    for i in index:
        row = {}
        for name, col in out_cols.items():
            value = col[i]
            if name in vocabs:
                row[name] = vocabs[name][int(value)]
            elif np.asarray(value).dtype.kind == "f":
                row[name] = float(value)
            else:
                row[name] = int(value)
        rows.append(row)
    return rows


def _text(value):
    return value.decode() if isinstance(value, (bytes, bytearray)) else value


def compare(spec, got_rows, want_rows):
    """(rows_mismatched, rel_gap_max) of one answer against the reference.
    Ordered specs compare by position, grouped ones by group key; integer
    and string values must be equal, doubles give their relative gap."""
    group_by = spec["group_by"]
    float_names = [a["name"] for a in spec["aggregates"]
                   if a["fn"] in ("sum", "avg")]
    exact_names = [a["name"] for a in spec["aggregates"]
                   if a["fn"] == "count"]

    def key(row):
        return tuple(_text(row.get(g)) for g in group_by)

    mismatched = 0
    if spec.get("order_by"):
        pairs = list(zip(got_rows, want_rows))
        mismatched += abs(len(got_rows) - len(want_rows))
        mismatched += sum(1 for g, w in pairs if key(g) != key(w))
        pairs = [(g, w) for g, w in pairs if key(g) == key(w)]
    else:
        got = {key(r): r for r in got_rows}
        want = {key(r): r for r in want_rows}
        mismatched += len(set(got) ^ set(want)) + \
            (len(got_rows) - len(got))
        pairs = [(got[k], want[k]) for k in want if k in got]
    gap = 0.0
    for g, w in pairs:
        mismatched += sum(1 for name in exact_names if g[name] != w[name])
        for name in float_names:
            denom = abs(w[name])
            diff = abs(float(g[name]) - w[name])
            if not np.isfinite(diff):
                return mismatched + 1, float("inf")
            gap = max(gap, diff / denom if denom else diff)
    return mismatched, gap
