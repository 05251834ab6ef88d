"""Plain numpy evaluator of a two-table query spec: an equi-join of the
generators' host arrays (sort the foreign key, search every probe key's run
of matches, expand the pairs; many-to-many, INNER or LEFT), then filter,
group, aggregate, order and limit over the joined rows.

It imports nothing of the program.  Values carry a validity mask the way SQL
carries NULL: a LEFT join's unmatched rows have no foreign values, a
comparison with NULL is NULL, `if_` of a NULL condition is NULL, AND / OR are
three-valued, a filter keeps the rows that are true, `sum` skips NULLs.
Expressions are written in Python syntax over column names, whole numbers
and string literals (`a = 'x'` is `a == 'x'`, IN is `in (..)`, `if_(c, a,
b)`, `is_null(a)`); string columns are integer codes into sorted
vocabularies, and a string literal stands for its code.  Grouping, ordering,
the limit and the decoding of codes are `ql_spec`'s, over columns made here.
"""

import ast

import numpy as np

from reference import ql_spec


def equi_join(probe_keys, build_keys, kind="inner", shift=0):
    """Row pairs of `probe_keys[i] == build_keys[j]`: (probe_row,
    build_row, matched), in probe order and, within a probe row, in the
    build side's order.  LEFT keeps a probe row without a match once, with
    `matched` false (its build_row is 0 and means nothing).  `shift` is the
    control's fault: every pair takes the build row `shift` places on."""
    order = np.argsort(build_keys, kind="stable")
    ordered = build_keys[order]
    lo = np.searchsorted(ordered, probe_keys, side="left")
    hi = np.searchsorted(ordered, probe_keys, side="right")
    counts = hi - lo
    per_row = np.maximum(counts, 1) if kind == "left" else counts
    probe_row = np.repeat(np.arange(len(probe_keys)), per_row)
    starts = np.cumsum(per_row) - per_row
    within = np.arange(len(probe_row)) - starts[probe_row]
    matched = counts[probe_row] > 0
    position = np.where(matched, lo[probe_row] + within, 0)
    build_row = order[position] if len(order) else position
    if shift and len(order):
        build_row = (build_row + shift) % len(order)
    return probe_row, build_row, matched


class _Joined:
    """The joined rows as the expressions see them: name -> (values,
    valid); `keep` cuts them to the filter's rows."""

    def __init__(self, tables, spec, shift):
        probe, build = tables[spec["from"]], tables[spec["join"]["table"]]
        probe_key, build_key = spec["join"]["on"]
        self.probe_row, self.build_row, self.matched = equi_join(
            probe[probe_key], build[build_key], spec["join"]["kind"], shift)
        self.probe, self.build = probe, build
        self.keep, self.ready = None, {}

    def cut(self, keep):
        """Only the filter's rows from here on."""
        self.keep, self.ready = keep, {}

    def __len__(self):
        return len(self.probe_row) if self.keep is None \
            else int(self.keep.sum())

    def __getitem__(self, name):
        if name not in self.ready:
            if name in self.probe:
                values = self.probe[name][self.probe_row]
                valid = np.ones(len(values), dtype=bool)
            else:
                values = self.build[name][self.build_row]
                valid = self.matched
            if self.keep is not None:
                values, valid = values[self.keep], valid[self.keep]
            self.ready[name] = values, valid
        return self.ready[name]


def evaluate_expr(text, columns, vocabs):
    """(values, valid) of one expression over the joined rows."""
    n = len(columns)

    def constant(value):
        return np.full(n, value), np.ones(n, dtype=bool)

    def code(name, literal):
        """The code a string literal has in the column's vocabulary; -1
        (equal to no code) where the vocabulary lacks it."""
        vocab = vocabs[name]
        return vocab.index(literal) if literal in vocab else -1

    def compare_nodes(op, left, right):
        def operand(node, other):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                if not isinstance(other, ast.Name) or \
                        op not in (ast.Eq, ast.NotEq):
                    raise ValueError(f"string literal in {text!r} is not "
                                     f"compared for equality with a column")
                return constant(code(other.id, node.value))
            return walk(node)
        (a, a_ok), (b, b_ok) = operand(left, right), operand(right, left)
        return ql_spec._CMPOPS[op](a, b), a_ok & b_ok

    def walk(node):
        if isinstance(node, ast.Compare) and len(node.ops) == 1:
            op, right = type(node.ops[0]), node.comparators[0]
            if op is ast.In:
                values, valid = compare_nodes(ast.Eq, node.left, right.elts[0])
                for element in right.elts[1:]:
                    more, more_ok = compare_nodes(ast.Eq, node.left, element)
                    values, valid = values | more, valid & more_ok
                return values, valid
            if op in ql_spec._CMPOPS:
                return compare_nodes(op, node.left, right)
        if isinstance(node, ast.BoolOp):
            # three-valued: a false decides AND, a true decides OR, whatever
            # else is NULL; undecided, a NULL makes the result NULL
            decides = isinstance(node.op, ast.Or)
            parts = [walk(value) for value in node.values]
            decided = np.zeros(n, dtype=bool)
            all_ok = np.ones(n, dtype=bool)
            for values, valid in parts:
                decided |= valid & (values == decides)
                all_ok &= valid
            return np.where(decided, decides, not decides), decided | all_ok
        if isinstance(node, ast.BinOp) and type(node.op) in ql_spec._BINOPS:
            (a, a_ok), (b, b_ok) = walk(node.left), walk(node.right)
            return ql_spec._BINOPS[type(node.op)](a, b), a_ok & b_ok
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
            args = [walk(arg) for arg in node.args]
            if node.func.id == "is_null" and len(args) == 1:
                return ~args[0][1], np.ones(n, dtype=bool)
            if node.func.id == "if_" and len(args) == 3:
                (cond, cond_ok), (a, a_ok), (b, b_ok) = args
                return np.where(cond, a, b), \
                    cond_ok & np.where(cond, a_ok, b_ok)
        if isinstance(node, ast.Name):
            return columns[node.id]
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return constant(node.value)
        raise ValueError(f"unsupported expression node in {text!r}: "
                         f"{ast.dump(node)}")
    return walk(ast.parse(text, mode="eval").body)


def evaluate(spec, tables, vocabs, shift=0):
    """Rows (list of dicts) the spec selects from `tables` (table name ->
    column name -> array).  `vocabs` (column name -> sorted list) holds the
    coded string columns of both tables."""
    joined = _Joined(tables, spec, shift)
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
        if values.dtype.kind not in "iub":
            raise ValueError(f"{aggregate['name']!r} is no whole number")
        columns[aggregate["name"]] = np.where(valid, values, 0).astype(
            np.int64)
    grouped = dict(spec, filter=None, aggregates=[
        {"name": a["name"], "fn": "sum", "expr": a["name"]}
        for a in spec["aggregates"]])
    return ql_spec.evaluate(grouped, columns, vocabs)


def compare(got_rows, want_rows):
    """rows_mismatched of one ordered answer: positions whose rows differ
    in any column (keys, strings and whole-number aggregates alike, all
    exact), plus missing or extra rows."""
    def plain(row):
        return {name: ql_spec._text(value) for name, value in row.items()}
    return abs(len(got_rows) - len(want_rows)) + sum(
        1 for got, want in zip(got_rows, want_rows)
        if plain(got) != plain(want))
