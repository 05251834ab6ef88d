"""Plain numpy evaluator of a query spec over several tables: a list of
equi-joins applied in order to the generators' host arrays (each one
`tpch_join_spec.equi_join`: many-to-many, INNER or LEFT; a later join may
probe with a column an earlier one brought), then the filter over the joined
rows, a group by several integer keys, `sum` aggregates, an order by several
keys and a limit.

It imports nothing of the program.  Values carry a validity mask the way SQL
carries NULL: a LEFT join's unmatched rows have no values of the table they
missed, a NULL key matches nothing, a comparison or sum with NULL is NULL,
AND / OR are three-valued, a filter keeps the rows that are true, `sum`
skips NULLs.  Expressions are written in Python syntax over column names,
whole numbers and string literals (`a == 'x'`, `if_(c, a, b)`,
`is_null(a)`); string columns are integer codes into sorted vocabularies
and a string literal stands for its code.  `dtype` is the precision every
`double` column, expression and accumulation is held in: float64 is the
reference, float32 the precision control.  `shift` = (table, places) is the
join control's fault: every pair of that table's join takes the build row
`places` on.  Grouping, ordering, the limit, the decoding of codes and the
comparison of two answers are `ql_spec`'s, over columns made here.
"""

import ast

import numpy as np

from reference import ql_spec
from reference.tpch_join_spec import equi_join


class _Joined:
    """The joined rows: for every table joined so far the row each joined
    row takes from it (`rows`) and whether it has one (`matched`)."""

    def __init__(self, tables, spec, dtype, shift):
        self.tables, self.dtype = tables, dtype
        first = spec["from"]
        n = len(next(iter(tables[first].values())))
        self.rows = {first: np.arange(n)}
        self.matched = {first: np.ones(n, dtype=bool)}
        self.ready = {}
        for join in spec["joins"]:
            places = shift[1] if shift and shift[0] == join["table"] else 0
            self._join(join, places)

    def _join(self, join, shift):
        probe_column, build_column = join["on"]
        keys, valid = self[probe_column]
        build_keys = self.tables[join["table"]][build_column]
        # A NULL probe key matches nothing: only the rows with a key go
        # through the join; LEFT keeps the others, unmatched, in place.
        with_key = np.flatnonzero(valid)
        probe, build, matched = equi_join(keys[with_key], build_keys,
                                          join["kind"], shift)
        probe = with_key[probe]
        if join["kind"] == "left" and len(with_key) < len(valid):
            without = np.flatnonzero(~valid)
            probe = np.concatenate((probe, without))
            build = np.concatenate((build, np.zeros_like(without)))
            matched = np.concatenate((matched,
                                      np.zeros(len(without), dtype=bool)))
            back = np.argsort(probe, kind="stable")
            probe, build, matched = probe[back], build[back], matched[back]
        self.rows = {t: rows[probe] for t, rows in self.rows.items()}
        self.matched = {t: m[probe] for t, m in self.matched.items()}
        self.rows[join["table"]] = build
        self.matched[join["table"]] = matched
        self.ready = {}

    def cut(self, keep):
        """Only the filter's rows from here on."""
        self.rows = {t: rows[keep] for t, rows in self.rows.items()}
        self.matched = {t: m[keep] for t, m in self.matched.items()}
        self.ready = {}

    def __len__(self):
        return len(next(iter(self.rows.values())))

    def __getitem__(self, name):
        """(values, valid) of one column of the joined rows; a `double`
        column is held in `dtype`."""
        if name not in self.ready:
            table = next((t for t in self.rows if name in self.tables[t]),
                         None)
            if table is None:
                raise KeyError(f"no joined table has a column {name!r}")
            values = self.tables[table][name][self.rows[table]]
            if values.dtype.kind == "f":
                values = values.astype(self.dtype)
            self.ready[name] = values, self.matched[table]
        return self.ready[name]


def evaluate_expr(text, columns, vocabs):
    """(values, valid) of one expression over the joined rows.  Whole
    numbers stay Python numbers, so nothing widens a `dtype` column."""
    n = len(columns)
    always = np.ones(n, dtype=bool)

    def operand(node, other, op):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if not isinstance(other, ast.Name) or \
                    op not in (ast.Eq, ast.NotEq):
                raise ValueError(f"string literal in {text!r} is not "
                                 f"compared for equality with a column")
            vocab = vocabs[other.id]
            # -1 is equal to no code: a literal the vocabulary lacks
            code = vocab.index(node.value) if node.value in vocab else -1
            return code, always
        return walk(node)

    def walk(node):
        if isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and type(node.ops[0]) in ql_spec._CMPOPS:
            op, right = type(node.ops[0]), node.comparators[0]
            (a, a_ok), (b, b_ok) = operand(node.left, right, op), \
                operand(right, node.left, op)
            return ql_spec._CMPOPS[op](a, b), a_ok & b_ok
        if isinstance(node, ast.BoolOp):
            # three-valued: a false decides AND, a true decides OR, whatever
            # else is NULL; undecided, a NULL makes the result NULL
            decides = isinstance(node.op, ast.Or)
            decided = np.zeros(n, dtype=bool)
            all_ok = always
            for values, valid in map(walk, node.values):
                decided = decided | (valid & (values == decides))
                all_ok = all_ok & valid
            return np.where(decided, decides, not decides), decided | all_ok
        if isinstance(node, ast.BinOp) and type(node.op) in ql_spec._BINOPS:
            (a, a_ok), (b, b_ok) = walk(node.left), walk(node.right)
            return ql_spec._BINOPS[type(node.op)](a, b), a_ok & b_ok
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            args = [walk(arg) for arg in node.args]
            if node.func.id == "is_null" and len(args) == 1:
                return ~args[0][1], always
            if node.func.id == "if_" and len(args) == 3:
                (cond, cond_ok), (a, a_ok), (b, b_ok) = args
                return np.where(cond, a, b), \
                    cond_ok & np.where(cond, a_ok, b_ok)
        if isinstance(node, ast.Name):
            return columns[node.id]
        if isinstance(node, ast.Constant) and type(node.value) is int:
            return node.value, always
        raise ValueError(f"unsupported expression node in {text!r}: "
                         f"{ast.dump(node)}")
    values, valid = walk(ast.parse(text, mode="eval").body)
    return np.broadcast_to(values, (n,)), valid


def evaluate(spec, tables, vocabs, dtype=np.float64, shift=None):
    """Rows (list of dicts) the spec selects from `tables` (table name ->
    column name -> array).  `vocabs` (column name -> sorted list) holds the
    coded string columns of every table."""
    joined = _Joined(tables, spec, dtype, shift)
    if spec.get("filter"):
        values, valid = evaluate_expr(spec["filter"], joined, vocabs)
        joined.cut(values & valid)
    columns = {}
    for name in spec["group_by"]:
        columns[name], valid = joined[name]
        if not valid.all():
            raise ValueError(f"group key {name!r} has NULLs")
    for aggregate in spec["aggregates"]:
        if aggregate["fn"] != "sum":
            raise ValueError(f"unsupported aggregate {aggregate['fn']!r}")
        values, valid = evaluate_expr(aggregate["expr"], joined, vocabs)
        columns[aggregate["name"]] = np.where(valid, values,
                                              values.dtype.type(0))
    grouped = dict(spec, filter=None, aggregates=[
        {"name": a["name"], "fn": "sum", "expr": a["name"]}
        for a in spec["aggregates"]])
    return ql_spec.evaluate(grouped, columns, vocabs, dtype=dtype)


def compare(spec, got_rows, want_rows):
    """(rows_mismatched, rel_gap_max) of one answer against the
    reference: positions of the ordered answer whose group keys differ,
    plus missing or extra rows; and the widest relative gap of a sum."""
    return ql_spec.compare(spec, got_rows, want_rows)
