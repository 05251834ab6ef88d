"""The no-compile interpreter tier: PreparedQuery plans over numpy planes.

The adaptive-tiering gap (arxiv 2311.04692; Flare, arxiv 1703.08219): the
FIRST execution of a genuinely new plan shape pays its 200-400 ms XLA
compile inline.  This module is the tier below the compiled path — a
vectorized numpy interpreter that executes the SAME staged pipeline
lowering.py traces (filter → group → order → project → compact/offset/
limit) over the SAME ColumnarChunk planes, with zero compilation.  The
evaluator serves a cold shape from here immediately while the background
compiler (evaluator.BackgroundCompiler) builds the XLA program off-thread.

Bit-identity contract: every stage mirrors lowering.py / expr.py /
ops/segments.py formula-for-formula — including garbage values under
invalid lanes, the flags-word-major group ordering of the sort-group
path, the dense-slot ordering of the fast-group path (identical
`_column_min_max` probe, so the fast/sort decision can never diverge),
and the clamped offset/limit finale.  The only sanctioned divergence is
float SUM accumulation order (XLA tree-reduce vs numpy sequential);
everything else is decode-identical, test-enforced by
tests/test_tiering.py's dual-check corpus.

Coverage is DECLARED, never guessed: `covers()` walks the plan against an
explicit allow-list (scan/filter/project/group/order/limit, the full
aggregate set, and the expression subset below).  Joins, windows, NEAREST
(vector types), and the host-table string builtins fall through to the
compiled path.  ORDER BY ... LIMIT takes a full stable lexsort instead of
the device's top-k candidate pruning — provably identical over the
visible [offset, offset+limit) window (lax.top_k breaks ties by lowest
index, the candidate set is a superset of the window, and the compacted
candidate count clamps to the same value).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.engine.expr import (
    _EMPTY_VOCAB,
    _merge_vocabs,
    _pad_np,
    _range_code,
    _remap_table,
    _string_matcher,
    _vocab_bucket,
    _vocab_code,
)
from ytsaurus_tpu.schema import EValueType, device_dtype
from ytsaurus_tpu.utils import sanitizers


class InterpUnsupported(Exception):
    """Plan/expression outside the declared interpreter coverage: the
    caller falls through to the compiled path (never an error)."""


# --- declared coverage --------------------------------------------------------

COVERED_FUNCTIONS = frozenset({
    "if", "is_null", "if_null",
    "int64", "uint64", "double", "boolean",
    "abs", "floor", "ceil", "sqrt",
    "min_of", "max_of",
    "length", "lower", "upper", "concat",
    "is_finite", "is_nan",
    "timestamp_floor_hour", "timestamp_floor_day", "timestamp_floor_week",
    "timestamp_floor_month", "timestamp_floor_year",
})

COVERED_AGGREGATES = frozenset({
    "sum", "min", "max", "avg", "count", "first",
    "argmin", "argmax", "cardinality",
})


def _check_expr(node: ir.TExpr) -> None:
    """Raise InterpUnsupported for any node outside the allow-list."""
    if isinstance(node, ir.TLiteral):
        if not isinstance(node.type, EValueType):
            raise InterpUnsupported("vector literal")   # NEAREST vectors
        return
    if isinstance(node, ir.TReference):
        if not isinstance(node.type, EValueType):
            raise InterpUnsupported("vector column")
        return
    if isinstance(node, ir.TUnary):
        _check_expr(node.operand)
        return
    if isinstance(node, ir.TBinary):
        _check_expr(node.lhs)
        _check_expr(node.rhs)
        return
    if isinstance(node, ir.TFunction):
        if node.name not in COVERED_FUNCTIONS:
            raise InterpUnsupported(f"function {node.name}")
        for arg in node.args:
            _check_expr(arg)
        return
    if isinstance(node, (ir.TIn, ir.TBetween)):
        for operand in node.operands:
            _check_expr(operand)
        return
    if isinstance(node, ir.TStringPredicate):
        _check_expr(node.operand)
        return
    raise InterpUnsupported(type(node).__name__)


def covers(plan) -> bool:
    """The declared-coverage predicate: True iff every clause and
    expression of `plan` is inside the interpreter's allow-list."""
    if not isinstance(plan, (ir.Query, ir.FrontQuery)):
        return False
    if getattr(plan, "joins", ()):
        return False
    if plan.window is not None:
        return False
    try:
        for col in plan.schema:
            if not isinstance(col.type, EValueType) or \
                    col.type is EValueType.any:
                raise InterpUnsupported(f"column type {col.type!r}")
        where = getattr(plan, "where", None)
        if where is not None:
            _check_expr(where)
        if plan.group is not None:
            if len(plan.group.group_items) > 31:
                raise InterpUnsupported("too many group keys")
            for item in plan.group.group_items:
                _check_expr(item.expr)
            for agg in plan.group.aggregate_items:
                if agg.function not in COVERED_AGGREGATES:
                    raise InterpUnsupported(f"aggregate {agg.function}")
                if agg.argument is None:
                    raise InterpUnsupported("argument-less aggregate")
                _check_expr(agg.argument)
                if agg.by_argument is not None:
                    _check_expr(agg.by_argument)
        if plan.having is not None:
            _check_expr(plan.having)
        if plan.order is not None:
            for item in plan.order.items:
                _check_expr(item.expr)
        if plan.project is not None:
            for item in plan.project.items:
                _check_expr(item.expr)
    except InterpUnsupported:
        return False
    return True


# --- numpy mirrors of device primitives ---------------------------------------

_SIGN64 = np.uint64(1 << 63)


def _np_monotone_u64(data: np.ndarray) -> np.ndarray:
    """Order-preserving uint64 encoding — the (hi << 32 | lo) collapse of
    segments.monotone_u32_words, identical order and tie classes."""
    if data.dtype == np.bool_:
        return data.astype(np.uint64)
    if np.issubdtype(data.dtype, np.floating):
        x = data.astype(np.float64)
        # Same canonical classes as segments.f64_bits_u32: one quiet
        # +NaN, subnormals flushed to a zero of their sign.
        x = np.where(np.isnan(x), np.nan,
                     np.where(np.abs(x) < 2.0 ** -1022,
                              np.copysign(0.0, x), x))
        bits = np.ascontiguousarray(x).view(np.uint64)
        sign = (bits >> np.uint64(63)).astype(bool)
        return np.where(sign, ~bits, bits | _SIGN64)
    if np.issubdtype(data.dtype, np.unsignedinteger):
        return data.astype(np.uint64)
    return data.astype(np.int64).astype(np.uint64) ^ _SIGN64


def _np_equality_u64(data: np.ndarray) -> np.ndarray:
    """Equality-class uint64 encoding (bit view; order irrelevant)."""
    if data.dtype == np.bool_:
        return data.astype(np.uint64)
    if np.issubdtype(data.dtype, np.floating):
        return np.ascontiguousarray(
            data.astype(np.float64)).view(np.uint64)
    return data.astype(np.int64).astype(np.uint64) \
        if np.issubdtype(data.dtype, np.signedinteger) \
        else data.astype(np.uint64)


def _np_compare(op: str, lhs: np.ndarray, rhs: np.ndarray) -> np.ndarray:
    if op == "=":
        return lhs == rhs
    if op == "!=":
        return lhs != rhs
    if op == "<":
        return lhs < rhs
    if op == "<=":
        return lhs <= rhs
    if op == ">":
        return lhs > rhs
    if op == ">=":
        return lhs >= rhs
    raise AssertionError(op)


def _np_promote_pair(a: np.ndarray,
                     b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    if a.dtype == b.dtype:
        return a, b
    target = np.promote_types(a.dtype, b.dtype)
    return a.astype(target), b.astype(target)


def _np_trunc_div(ld: np.ndarray, rd: np.ndarray) -> np.ndarray:
    """C++ truncating integer division (jax.lax.div semantics)."""
    if np.issubdtype(ld.dtype, np.unsignedinteger):
        return ld // rd
    q = np.floor_divide(ld, rd)
    r = ld - q * rd
    return q + ((r != 0) & ((ld < 0) != (rd < 0)))


def _np_days_to_civil(days: np.ndarray):
    z = days + 719468
    era = np.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = np.floor_divide(
        doe - doe // 1460 + doe // 36524 - doe // 146096, 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = np.floor_divide(5 * doy + 2, 153)
    d = doy - np.floor_divide(153 * mp + 2, 5) + 1
    m = np.where(mp < 10, mp + 3, mp - 9)
    y = y + (m <= 2)
    return y, m, d


def _np_civil_to_days(y, m, d) -> np.ndarray:
    y = y - (m <= 2)
    era = np.floor_divide(y, 400)
    yoe = y - era * 400
    mp = np.mod(m + 9, 12)
    doy = np.floor_divide(153 * mp + 2, 5) + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _np_timestamp_floor(ts: np.ndarray, unit: str) -> np.ndarray:
    if unit == "hour":
        return ts - np.mod(ts, 3600)
    if unit == "day":
        return ts - np.mod(ts, 86400)
    days = np.floor_divide(ts, 86400)
    if unit == "week":
        dow = np.mod(days + 3, 7)
        return (days - dow) * 86400
    y, m, _ = _np_days_to_civil(days)
    if unit == "month":
        return _np_civil_to_days(y, m, np.ones_like(m)) * 86400
    if unit == "year":
        one = np.ones_like(y)
        return _np_civil_to_days(y, one, one) * 86400
    raise InterpUnsupported(f"timestamp unit {unit}")


def _reduce_neutral(dtype, function: str):
    if np.issubdtype(dtype, np.floating):
        return np.array(np.inf if function == "min" else -np.inf,
                        dtype=dtype)
    info = np.iinfo(dtype)
    return np.array(info.max if function == "min" else info.min,
                    dtype=dtype)


def _seg_reduce(function: str, data: np.ndarray, seg: np.ndarray,
                nseg: int) -> np.ndarray:
    """Per-segment sum/min/max; rows with seg outside [0, nseg) are
    dropped (the device's `seg == s` compare never matches them)."""
    keep = (seg >= 0) & (seg < nseg)
    if not keep.all():
        data = data[keep]
        seg = seg[keep]
    if function == "sum":
        out = np.zeros(nseg, dtype=data.dtype)
        np.add.at(out, seg, data)
        return out
    neutral = _reduce_neutral(data.dtype, function)
    out = np.full(nseg, neutral, dtype=data.dtype)
    (np.minimum if function == "min" else np.maximum).at(out, seg, data)
    return out


def _seg_first_index(eligible: np.ndarray, seg: np.ndarray,
                     nseg: int) -> np.ndarray:
    cap = eligible.shape[0]
    idx = np.where(eligible, np.arange(cap, dtype=np.int64),
                   np.int64(cap - 1))
    first = _seg_reduce("min", idx, seg, nseg)
    return np.clip(first, 0, cap - 1)


def _np_segment_aggregate(function: str, data: np.ndarray,
                          valid: np.ndarray, seg: np.ndarray, nseg: int,
                          value_type) -> tuple[np.ndarray, np.ndarray]:
    contributes = valid
    count = _seg_reduce("sum", contributes.astype(np.int64), seg, nseg)
    any_valid = count > 0
    if function == "count":
        return count, np.ones_like(any_valid)
    if function == "sum":
        masked = np.where(contributes, data, np.zeros_like(data))
        return _seg_reduce("sum", masked, seg, nseg), any_valid
    if function in ("min", "max"):
        if data.dtype == np.bool_:
            data = data.astype(np.int8)
        neutral = _reduce_neutral(data.dtype, function)
        masked = np.where(contributes, data, neutral)
        out = _seg_reduce(function, masked, seg, nseg)
        if value_type is EValueType.boolean:
            out = out.astype(np.bool_)
        return out, any_valid
    if function == "first":
        first_idx = _seg_first_index(contributes, seg, nseg)
        return data[first_idx], any_valid
    raise InterpUnsupported(f"segment aggregate {function}")


def _np_segment_arg_by(value_data, value_valid, by_data, by_valid,
                       seg, nseg, take_max: bool):
    if by_data.dtype == np.bool_:
        by_data = by_data.astype(np.int8)
    competes = by_valid
    if np.issubdtype(by_data.dtype, np.floating):
        competes = competes & ~np.isnan(by_data)
    fn = "max" if take_max else "min"
    neutral = _reduce_neutral(by_data.dtype, fn)
    masked_by = np.where(competes, by_data, neutral)
    extreme = _seg_reduce(fn, masked_by, seg, nseg)
    safe_seg = np.clip(seg, 0, nseg - 1)
    winner = competes & (masked_by == extreme[safe_seg]) & (seg < nseg)
    first_idx = _seg_first_index(winner, seg, nseg)
    any_competes = _seg_reduce(
        "sum", competes.astype(np.int64), seg, nseg) > 0
    return value_data[first_idx], value_valid[first_idx] & any_competes


def _np_segment_distinct_count(data, valid, seg, nseg):
    value = np.where(valid, data, np.zeros_like(data))
    nan_flag = np.zeros(value.shape[0], dtype=np.int8)
    if np.issubdtype(value.dtype, np.floating):
        is_nan = np.isnan(value)
        nan_flag = is_nan.astype(np.int8)
        value = np.where(is_nan, np.full_like(value, np.inf),
                         value + 0.0)
    flags = (valid.astype(np.uint32) << np.uint32(1)) | \
        nan_flag.astype(np.uint32)
    enc = _np_equality_u64(value)
    order = np.lexsort((enc, flags, seg))
    seg_s = seg[order]
    enc_s = enc[order]
    valid_s = valid[order]
    flags_s = flags[order]
    new = (seg_s != np.roll(seg_s, 1)) | (enc_s != np.roll(enc_s, 1)) | \
        (flags_s != np.roll(flags_s, 1))
    if len(new):
        new[0] = True
    counts = _seg_reduce("sum", (new & valid_s).astype(np.int64),
                         seg_s, nseg)
    return counts.astype(np.uint64), np.ones(nseg, dtype=bool)


# --- expression interpretation ------------------------------------------------


@dataclass
class _Ctx:
    """Stage state: numpy (data, valid) planes per column name."""
    columns: dict[str, tuple[np.ndarray, np.ndarray]]
    capacity: int


@dataclass
class _NBound:
    """One bound expression: type + result vocab + numpy evaluator."""
    type: EValueType
    vocab: Optional[np.ndarray]
    emit: Callable[[_Ctx], tuple[np.ndarray, np.ndarray]]


_CMP_OPS = ("=", "!=", "<", "<=", ">", ">=")


def _gather_table(table: np.ndarray):
    """Mirror of expr._gather_binding: pad-bucketed table + clip gather,
    so garbage codes under invalid lanes map to the SAME garbage."""
    def gather(codes: np.ndarray) -> np.ndarray:
        return table[np.clip(codes, 0, table.shape[0] - 1)]
    return gather


class NumpyBinder:
    """ExprBinder's numpy twin: binds one plan's expressions against one
    chunk's vocabularies, producing closures that evaluate eagerly.  The
    bind-phase host computations (vocab merges, remap/predicate tables,
    literal codes) are shared with expr.py helper-for-helper, so codes
    and vocabularies can never diverge from the compiled path."""

    def __init__(self, columns: dict):
        # name -> (EValueType, vocab) — same view ColumnBinding carries.
        self.columns = columns

    def bind(self, node: ir.TExpr) -> _NBound:
        method = getattr(self, f"_bind_{type(node).__name__}", None)
        if method is None:
            raise InterpUnsupported(type(node).__name__)
        return method(node)

    # -- leaves ---------------------------------------------------------------

    def _bind_TLiteral(self, node: ir.TLiteral) -> _NBound:
        ty = node.type
        if not isinstance(ty, EValueType):
            raise InterpUnsupported("vector literal")
        if ty is EValueType.null:
            def emit_null(ctx: _Ctx):
                return (np.zeros(ctx.capacity, dtype=np.int8),
                        np.zeros(ctx.capacity, dtype=bool))
            return _NBound(type=ty, vocab=None, emit=emit_null)
        if ty is EValueType.string:
            vocab = np.array([node.value], dtype=object)

            def emit_str(ctx: _Ctx):
                return (np.zeros(ctx.capacity, dtype=np.int32),
                        np.ones(ctx.capacity, dtype=bool))
            return _NBound(type=ty, vocab=vocab, emit=emit_str)
        value = node.value
        dt = device_dtype(ty)
        if ty is EValueType.boolean:
            def emit_bool(ctx: _Ctx):
                return (np.full(ctx.capacity, bool(value), dtype=dt),
                        np.ones(ctx.capacity, dtype=bool))
            return _NBound(type=ty, vocab=None, emit=emit_bool)
        # analyze: allow(host-sync): `value` is a host python literal, not a device plane
        const = np.asarray(value, dtype=dt)

        def emit(ctx: _Ctx):
            return (np.broadcast_to(const, (ctx.capacity,)),
                    np.ones(ctx.capacity, dtype=bool))
        return _NBound(type=ty, vocab=None, emit=emit)

    def _bind_TReference(self, node: ir.TReference) -> _NBound:
        binding = self.columns.get(node.name)
        if binding is None:
            raise InterpUnsupported(f"unbound column {node.name}")
        if not isinstance(node.type, EValueType):
            raise InterpUnsupported("vector column")
        name = node.name

        def emit(ctx: _Ctx):
            return ctx.columns[name]
        return _NBound(type=node.type, vocab=binding[1], emit=emit)

    # -- operators ------------------------------------------------------------

    def _bind_TUnary(self, node: ir.TUnary) -> _NBound:
        operand = self.bind(node.operand)
        op = node.op

        def emit(ctx: _Ctx):
            data, valid = operand.emit(ctx)
            if op == "not":
                return ~data.astype(bool), valid
            if op == "-":
                return -data, valid
            if op == "~":
                return ~data, valid
            raise InterpUnsupported(op)
        return _NBound(type=node.type, vocab=None, emit=emit)

    def _bind_TBinary(self, node: ir.TBinary) -> _NBound:
        op = node.op
        lhs_b = self.bind(node.lhs)
        rhs_b = self.bind(node.rhs)

        if op in ("and", "or"):
            def emit_logical(ctx: _Ctx):
                ld, lv = lhs_b.emit(ctx)
                rd, rv = rhs_b.emit(ctx)
                ld, rd = ld.astype(bool), rd.astype(bool)
                if op == "and":
                    known_false = (lv & ~ld) | (rv & ~rd)
                    valid = (lv & rv) | known_false
                    data = np.where(lv, ld, True) & np.where(rv, rd, True)
                else:
                    known_true = (lv & ld) | (rv & rd)
                    valid = (lv & rv) | known_true
                    data = np.where(lv, ld, False) | np.where(rv, rd,
                                                              False)
                return data & valid if op == "and" else data, valid
            return _NBound(type=EValueType.boolean, vocab=None,
                           emit=emit_logical)

        if EValueType.string in (lhs_b.type, rhs_b.type) and \
                lhs_b.type is not EValueType.null and \
                rhs_b.type is not EValueType.null:
            encoded = self._bind_string_literal_cmp(node, op, lhs_b, rhs_b)
            if encoded is not None:
                return encoded
            merged = _merge_vocabs(lhs_b.vocab, rhs_b.vocab)
            l_vocab = lhs_b.vocab if lhs_b.vocab is not None \
                else _EMPTY_VOCAB
            r_vocab = rhs_b.vocab if rhs_b.vocab is not None \
                else _EMPTY_VOCAB
            l_gather = _gather_table(_pad_np(
                _remap_table(l_vocab, merged),
                _vocab_bucket(max(len(l_vocab), 1)), 0))
            r_gather = _gather_table(_pad_np(
                _remap_table(r_vocab, merged),
                _vocab_bucket(max(len(r_vocab), 1)), 0))

            def emit_strcmp(ctx: _Ctx):
                ld, lv = lhs_b.emit(ctx)
                rd, rv = rhs_b.emit(ctx)
                data = _np_compare(op, l_gather(ld), r_gather(rd))
                return data, lv & rv
            return _NBound(type=EValueType.boolean, vocab=None,
                           emit=emit_strcmp)

        target = node.type if op not in _CMP_OPS else None

        def emit(ctx: _Ctx):
            ld, lv = lhs_b.emit(ctx)
            rd, rv = rhs_b.emit(ctx)
            valid = lv & rv
            if op in _CMP_OPS:
                ld, rd = _np_promote_pair(ld, rd)
                return _np_compare(op, ld, rd), valid
            dt = device_dtype(target)
            ld = ld.astype(dt)
            rd = rd.astype(dt)
            if op == "+":
                data = ld + rd
            elif op == "-":
                data = ld - rd
            elif op == "*":
                data = ld * rd
            elif op == "/":
                if np.issubdtype(dt, np.integer):
                    safe = np.where(rd == 0, np.ones_like(rd), rd)
                    data = _np_trunc_div(ld, safe)
                    valid = valid & (rd != 0)
                else:
                    data = ld / rd
            elif op == "%":
                if np.issubdtype(dt, np.integer):
                    safe = np.where(rd == 0, np.ones_like(rd), rd)
                    data = np.fmod(ld, safe)
                    valid = valid & (rd != 0)
                else:
                    data = np.fmod(ld, rd)
            elif op == "|":
                data = ld | rd
            elif op == "&":
                data = ld & rd
            elif op == "^":
                data = ld ^ rd
            elif op == "<<":
                data = np.left_shift(ld, rd)
            elif op == ">>":
                data = np.right_shift(ld, rd)
            else:
                raise InterpUnsupported(op)
            return data, valid
        return _NBound(type=node.type, vocab=None, emit=emit)

    def _bind_string_literal_cmp(self, node: ir.TBinary, op: str,
                                 lhs_b: _NBound,
                                 rhs_b: _NBound) -> Optional[_NBound]:
        """Numpy twin of ExprBinder._bind_string_literal_cmp — the SAME
        decision (config gate, literal side, vocab presence) and the SAME
        code formulas (_vocab_code for =/!=, doubled-space _range_code
        for range ops), or tier bit-identity breaks."""
        from ytsaurus_tpu.config import compile_config
        if op not in _CMP_OPS or not compile_config().encoded_predicates:
            return None
        if not (lhs_b.type is EValueType.string
                and rhs_b.type is EValueType.string):
            return None
        if isinstance(node.rhs, ir.TLiteral) and lhs_b.vocab is not None:
            col_b, lit, lit_on_right = lhs_b, node.rhs.value, True
        elif isinstance(node.lhs, ir.TLiteral) and rhs_b.vocab is not None:
            col_b, lit, lit_on_right = rhs_b, node.lhs.value, False
        else:
            return None
        if lit is None:
            return None
        vocab = col_b.vocab
        if op in ("=", "!="):
            code = np.int32(_vocab_code(vocab, lit))

            def emit_eq(ctx: _Ctx):
                data, valid = col_b.emit(ctx)
                out = (data == code) if op == "=" else (data != code)
                return out, valid
            return _NBound(type=EValueType.boolean, vocab=None,
                           emit=emit_eq)
        code = np.int32(_range_code(vocab, lit))

        def emit_rng(ctx: _Ctx):
            data, valid = col_b.emit(ctx)
            doubled = data.astype(np.int32) * 2 + 1
            out = _np_compare(op, doubled, code) if lit_on_right \
                else _np_compare(op, code, doubled)
            return out, valid
        return _NBound(type=EValueType.boolean, vocab=None, emit=emit_rng)

    # -- functions ------------------------------------------------------------

    def _bind_TFunction(self, node: ir.TFunction) -> _NBound:
        name = node.name
        if name not in COVERED_FUNCTIONS:
            raise InterpUnsupported(f"function {name}")
        args = [self.bind(a) for a in node.args]

        if name == "if":
            return self._bind_if(node, args)
        if name == "is_null":
            a = args[0]

            def emit_is_null(ctx):
                _, valid = a.emit(ctx)
                return ~valid, np.ones_like(valid)
            return _NBound(type=EValueType.boolean, vocab=None,
                           emit=emit_is_null)
        if name == "if_null":
            return self._bind_merge_select(
                node, [args[0], args[1]],
                lambda ctx, planes: (
                    np.where(planes[0][1], planes[0][0], planes[1][0]),
                    planes[0][1] | planes[1][1]))
        if name in ("int64", "uint64", "double", "boolean"):
            a = args[0]
            dt = device_dtype(node.type)

            def emit_cast(ctx):
                data, valid = a.emit(ctx)
                if data.dtype == np.bool_ or \
                        node.type is EValueType.boolean:
                    return (data.astype(dt)
                            if node.type is not EValueType.boolean
                            else (data != 0)), valid
                return data.astype(dt), valid
            return _NBound(type=node.type, vocab=None, emit=emit_cast)
        if name == "abs":
            a = args[0]

            def emit_abs(ctx):
                data, valid = a.emit(ctx)
                if np.issubdtype(data.dtype, np.unsignedinteger):
                    return data, valid
                return np.abs(data), valid
            return _NBound(type=node.type, vocab=None, emit=emit_abs)
        if name in ("floor", "ceil", "sqrt"):
            a = args[0]
            fn = {"floor": np.floor, "ceil": np.ceil,
                  "sqrt": np.sqrt}[name]

            def emit_math(ctx):
                data, valid = a.emit(ctx)
                return fn(data.astype(np.float64)), valid
            return _NBound(type=node.type, vocab=None, emit=emit_math)
        if name in ("lower", "upper"):
            return self._bind_string_map(
                args[0], (lambda v: v.lower()) if name == "lower" else
                (lambda v: v.upper()))
        if name == "concat":
            return self._bind_concat(args[0], args[1])
        if name.startswith("timestamp_floor_"):
            unit = name[len("timestamp_floor_"):]
            a = args[0]

            def emit_ts_floor(ctx):
                data, valid = a.emit(ctx)
                return _np_timestamp_floor(data.astype(np.int64),
                                           unit), valid
            return _NBound(type=EValueType.int64, vocab=None,
                           emit=emit_ts_floor)
        if name in ("is_finite", "is_nan"):
            a = args[0]
            fn = np.isfinite if name == "is_finite" else np.isnan

            def emit_fpred(ctx):
                data, valid = a.emit(ctx)
                return fn(data.astype(np.float64)), valid
            return _NBound(type=EValueType.boolean, vocab=None,
                           emit=emit_fpred)
        if name == "length":
            return self._bind_vocab_table(args[0], EValueType.int64,
                                          np.int64, len)
        if name in ("min_of", "max_of"):
            pick_min = name == "min_of"

            def emit_minmax(ctx):
                planes = [a.emit(ctx) for a in args]
                data, valid = planes[0]
                for d, v in planes[1:]:
                    d, data2 = _np_promote_pair(d, data)
                    better = (d < data2) if pick_min else (d > data2)
                    take = v & (~valid | better)
                    data = np.where(take, d, data2)
                    valid = valid | v
                return data, valid
            return _NBound(type=node.type, vocab=None, emit=emit_minmax)
        raise InterpUnsupported(f"function {name}")

    def _bind_if(self, node, args):
        cond, then_b, else_b = args

        def select(ctx, planes):
            cd, cv = planes[0]
            td, tv = planes[1]
            ed, ev = planes[2]
            take_then = cv & cd.astype(bool)
            take_else = cv & ~cd.astype(bool)
            td2, ed2 = _np_promote_pair(td, ed)
            data = np.where(take_then, td2, ed2)
            valid = np.where(take_then, tv, take_else & ev)
            return data, valid
        return self._bind_merge_select(node, [cond, then_b, else_b],
                                       select, string_operands=(1, 2))

    def _bind_merge_select(self, node, args, select,
                           string_operands=(0, 1)):
        if node.type is EValueType.string:
            value_args = [args[i] for i in string_operands]
            merged = _merge_vocabs(*[a.vocab for a in value_args])
            remap_gathers = {}
            for i in string_operands:
                a = args[i]
                vocab = a.vocab if a.vocab is not None else _EMPTY_VOCAB
                remap_gathers[i] = _gather_table(_pad_np(
                    _remap_table(vocab, merged),
                    _vocab_bucket(max(len(vocab), 1)), 0))

            def emit_str(ctx):
                planes = []
                for i, a in enumerate(args):
                    d, v = a.emit(ctx)
                    if i in remap_gathers and a.type is EValueType.string:
                        d = remap_gathers[i](d)
                    planes.append((d, v))
                return select(ctx, planes)
            return _NBound(type=node.type, vocab=merged, emit=emit_str)

        def emit(ctx):
            planes = [a.emit(ctx) for a in args]
            return select(ctx, planes)
        return _NBound(type=node.type, vocab=None, emit=emit)

    def _bind_concat(self, a: _NBound, b: _NBound) -> _NBound:
        va = a.vocab if a.vocab is not None else _EMPTY_VOCAB
        vb = b.vocab if b.vocab is not None else _EMPTY_VOCAB
        na, nb = max(len(va), 1), max(len(vb), 1)
        if na * nb > 1 << 16:
            raise YtError(
                f"concat() vocabulary cross product too large "
                f"({len(va)}x{len(vb)}); reduce distinct values",
                code=EErrorCode.QueryUnsupported)
        pairs = [bytes(x) + bytes(y)
                 for x in (va if len(va) else [b""])
                 for y in (vb if len(vb) else [b""])]
        merged = np.array(sorted(set(pairs)), dtype=object)
        lookup = {v: i for i, v in enumerate(merged)}
        table = np.array([lookup[p] for p in pairs], dtype=np.int32)
        gather = _gather_table(_pad_np(table,
                                       _vocab_bucket(len(table)), 0))
        nb_const = nb

        def emit(ctx):
            da, valid_a = a.emit(ctx)
            db, valid_b = b.emit(ctx)
            pair = da.astype(np.int32) * nb_const + db.astype(np.int32)
            return gather(pair), valid_a & valid_b
        return _NBound(type=EValueType.string, vocab=merged, emit=emit)

    def _bind_vocab_table(self, a: _NBound, result_type, np_dtype,
                          fn) -> _NBound:
        vocab = a.vocab if a.vocab is not None else _EMPTY_VOCAB
        table = np.array([fn(v) for v in vocab] or [np_dtype()],
                         dtype=np_dtype)
        gather = _gather_table(_pad_np(table,
                                       _vocab_bucket(len(table)), 0))

        def emit(ctx):
            data, valid = a.emit(ctx)
            return gather(data), valid
        return _NBound(type=result_type, vocab=None, emit=emit)

    def _bind_string_map(self, a: _NBound, fn) -> _NBound:
        vocab = a.vocab if a.vocab is not None else _EMPTY_VOCAB
        new_values = [fn(v) for v in vocab]
        new_vocab = np.array(sorted(set(new_values)), dtype=object)
        lookup = {v: i for i, v in enumerate(new_vocab)}
        table = np.array([lookup[v] for v in new_values], dtype=np.int32)
        if len(table) == 0:
            table = np.zeros(1, dtype=np.int32)
        gather = _gather_table(_pad_np(table,
                                       _vocab_bucket(len(table)), 0))

        def emit(ctx):
            data, valid = a.emit(ctx)
            return gather(data), valid
        return _NBound(type=EValueType.string, vocab=new_vocab,
                       emit=emit)

    # -- membership / ranges / predicates --------------------------------------

    def _value_tuples(self, operands, values, range_encode=False,
                      pad_to=None):
        """Mirror of expr._bind_value_tuples returning host arrays."""
        cols = []
        oks = []
        for oi, operand in enumerate(operands):
            col = [tup[oi] if oi < len(tup) else None for tup in values]
            if operand.type is EValueType.string:
                vocab = operand.vocab if operand.vocab is not None \
                    else _EMPTY_VOCAB
                if range_encode:
                    arr = np.array(
                        [_range_code(vocab, v) if v is not None else 0
                         for v in col], dtype=np.int32)
                else:
                    arr = np.array(
                        [_vocab_code(vocab, v) if v is not None else -2
                         for v in col], dtype=np.int32)
            else:
                dt = device_dtype(operand.type) \
                    if operand.type is not EValueType.null else np.int64
                arr = np.array([v if v is not None else 0 for v in col],
                               dtype=dt)
            ok = np.array([v is not None for v in col], dtype=bool)
            if len(arr) == 0:
                arr = np.zeros(1, dtype=arr.dtype)
                ok = np.zeros(1, dtype=bool)
            if pad_to is not None and len(arr) < pad_to:
                arr = _pad_np(arr, pad_to, 0)
                ok = _pad_np(ok, pad_to, False)
            cols.append(arr)
            oks.append(ok)
        return cols, oks

    def _bind_TIn(self, node: ir.TIn) -> _NBound:
        from ytsaurus_tpu.chunks.columnar import next_pow2
        operands = [self.bind(o) for o in node.operands]
        n_bucket = next_pow2(len(node.values))
        value_cols, value_oks = self._value_tuples(
            operands, node.values, pad_to=n_bucket)
        present = np.zeros(n_bucket, dtype=bool)
        present[: len(node.values)] = True

        def emit(ctx):
            op_planes = [o.emit(ctx) for o in operands]
            match_any = np.zeros(ctx.capacity, dtype=bool)
            for vi in range(n_bucket):
                row_match = np.ones(ctx.capacity, dtype=bool)
                for oi, (data, valid) in enumerate(op_planes):
                    const = value_cols[oi][vi]
                    cvalid = value_oks[oi][vi]
                    row_match = row_match & np.where(
                        cvalid, valid & (data == const), ~valid)
                match_any = match_any | (row_match & present[vi])
            return match_any, np.ones(ctx.capacity, dtype=bool)
        return _NBound(type=EValueType.boolean, vocab=None, emit=emit)

    def _bind_TBetween(self, node: ir.TBetween) -> _NBound:
        operands = [self.bind(o) for o in node.operands]
        string_ops = [o.type is EValueType.string for o in operands]
        bound_ranges = []
        for lower, upper in node.ranges:
            lo = self._value_tuples(operands[: len(lower)], [lower],
                                    range_encode=True)
            up = self._value_tuples(operands[: len(upper)], [upper],
                                    range_encode=True)
            bound_ranges.append((len(lower), lo, len(upper), up))

        def _lex_compare(cap, op_planes, tables, op):
            value_cols, value_oks = tables
            result = np.full(cap, op in ("<=", ">="), dtype=bool)
            for oi in range(len(op_planes) - 1, -1, -1):
                data, valid = op_planes[oi]
                const = value_cols[oi][0]
                cvalid = value_oks[oi][0]
                eq = np.where(cvalid, valid & (data == const), ~valid)
                if op in ("<=", "<"):
                    lt = np.where(cvalid, (~valid) | (data < const),
                                  np.zeros(cap, dtype=bool))
                    result = lt | (eq & result)
                else:
                    gt = np.where(cvalid, valid & (data > const), valid)
                    result = gt | (eq & result)
            return result

        def emit(ctx):
            op_planes = []
            for operand, is_str in zip(operands, string_ops):
                data, valid = operand.emit(ctx)
                if is_str:
                    data = data.astype(np.int32) * 2 + 1
                op_planes.append((data, valid))
            in_any = np.zeros(ctx.capacity, dtype=bool)
            for lo_len, lo_t, up_len, up_t in bound_ranges:
                ge = _lex_compare(ctx.capacity, op_planes[:lo_len],
                                  lo_t, ">=")
                le = _lex_compare(ctx.capacity, op_planes[:up_len],
                                  up_t, "<=")
                in_any = in_any | (ge & le)
            result = ~in_any if node.negated else in_any
            return result, np.ones(ctx.capacity, dtype=bool)
        return _NBound(type=EValueType.boolean, vocab=None, emit=emit)

    def _bind_TStringPredicate(self, node) -> _NBound:
        operand = self.bind(node.operand)
        vocab = operand.vocab if operand.vocab is not None \
            else _EMPTY_VOCAB
        matcher = _string_matcher(node)
        table = np.array([matcher(v) for v in vocab], dtype=bool)
        if len(table) == 0:
            table = np.zeros(1, dtype=bool)
        if node.negated:
            table = ~table
        gather = _gather_table(_pad_np(
            table, _vocab_bucket(len(table)), False))

        def emit(ctx):
            data, valid = operand.emit(ctx)
            return gather(data), valid
        return _NBound(type=EValueType.boolean, vocab=None, emit=emit)


# --- the plan pipeline --------------------------------------------------------


def materialize_planes(chunk, schema) -> tuple[dict, np.ndarray]:
    """The interpreter tier's ONE sanctioned device→host sync: pull the
    chunk's column planes and row mask to numpy in a single place (the
    `yt analyze` jax pass knows this function by name)."""
    sanitizers.note_host_sync("interp.materialize_planes")
    columns = {}
    for col_schema in schema:
        col = chunk.columns.get(col_schema.name)
        if col is None:
            raise YtError(f"Chunk is missing column {col_schema.name!r}",
                          code=EErrorCode.QueryExecutionError)
        columns[col_schema.name] = (np.asarray(col.data),
                                    np.asarray(col.valid))
    return columns, np.asarray(chunk.row_valid)


@dataclass
class InterpretedQuery:
    """Host-bound interpreted plan for one chunk shape: `execute(chunk)`
    returns (planes, count) exactly like PreparedQuery.run, with numpy
    planes and a python-int count."""
    run: Callable
    output: list

    def execute(self, chunk):
        return self.run(chunk)


def try_prepare(plan, chunk) -> Optional[InterpretedQuery]:
    """Bind `plan` for interpretation, or None when any part of it falls
    outside the declared coverage (the caller compiles inline instead)."""
    if not covers(plan):
        return None
    try:
        return _prepare(plan, chunk)
    except InterpUnsupported:
        return None


def _prepare(plan, chunk) -> InterpretedQuery:
    from ytsaurus_tpu.query.engine.lowering import (
        OutputColumn,
        _column_min_max,
    )
    from ytsaurus_tpu.chunks.columnar import next_pow2, pad_capacity
    from ytsaurus_tpu.config import compile_config

    capacity = chunk.capacity
    columns_meta = {}
    for col_schema in plan.schema:
        col = chunk.columns.get(col_schema.name)
        if col is None:
            raise YtError(f"Chunk is missing column {col_schema.name!r}",
                          code=EErrorCode.QueryExecutionError)
        columns_meta[col_schema.name] = (col_schema.type, col.dictionary)
    binder = NumpyBinder(columns_meta)

    where_b = None
    where = getattr(plan, "where", None)
    if where is not None:
        where_b = binder.bind(where)

    group = plan.group
    group_key_b = []
    agg_arg_b = []
    having_b = None
    post_binder = None
    if group is not None:
        for item in group.group_items:
            group_key_b.append((item.name, binder.bind(item.expr)))
        for agg in group.aggregate_items:
            if agg.argument is None:
                raise InterpUnsupported("argument-less aggregate")
            arg = binder.bind(agg.argument)
            by_arg = binder.bind(agg.by_argument) \
                if agg.by_argument is not None else None
            agg_arg_b.append((agg, arg, by_arg))
        post_columns = {}
        for (name, bound), item in zip(group_key_b, group.group_items):
            post_columns[name] = (bound.type, bound.vocab)
        for agg, arg, _ in agg_arg_b:
            vocab = arg.vocab if (arg is not None and
                                  agg.type is EValueType.string) else None
            post_columns[agg.name] = (agg.type, vocab)
        post_binder = NumpyBinder(post_columns)
        if plan.having is not None:
            having_b = post_binder.bind(plan.having)
    final_binder = post_binder if post_binder is not None else binder

    order_b = []
    if plan.order is not None:
        for item in plan.order.items:
            order_b.append((final_binder.bind(item.expr),
                            item.descending))

    project_b = []
    if plan.project is not None:
        for item in plan.project.items:
            project_b.append((item.name, final_binder.bind(item.expr)))
    else:
        if group is not None:
            for (name, bound) in group_key_b:
                project_b.append((name, _post_ref(name, bound.type,
                                                  bound.vocab)))
            for agg, arg, _ in agg_arg_b:
                vocab = arg.vocab if (arg is not None and
                                      agg.type is EValueType.string) \
                    else None
                project_b.append((agg.name, _post_ref(agg.name, agg.type,
                                                      vocab)))
        else:
            for col_schema in plan.schema:
                project_b.append(
                    (col_schema.name,
                     final_binder.bind(ir.TReference(
                         type=col_schema.type, name=col_schema.name))))

    output = [OutputColumn(name=name, type=b.type, vocab=b.vocab)
              for name, b in project_b]
    offset = plan.offset
    limit = plan.limit
    parameterized = compile_config().parameterize

    # Fast-group decision: IDENTICAL probe to lowering's (same memoized
    # _column_min_max, same domain caps) — a divergent decision would
    # change the group output ORDER (dense slots put nulls last; the
    # sorted path puts them first).
    fast_group = None
    if group is not None:
        sizes_offsets = []
        for item, (_, bound) in zip(group.group_items, group_key_b):
            if bound.type is EValueType.string and \
                    bound.vocab is not None:
                sizes_offsets.append((len(bound.vocab), 0))
            elif bound.type is EValueType.boolean:
                sizes_offsets.append((2, 0))
            elif bound.type in (EValueType.int64, EValueType.uint64) and \
                    isinstance(item.expr, ir.TReference):
                col = chunk.columns.get(item.expr.name) \
                    if hasattr(chunk, "columns") else None
                data = getattr(col, "data", None)
                if data is None:
                    sizes_offsets = None
                    break
                lo, hi = _column_min_max(col, bound.type)
                if hi - lo + 1 > 65536:
                    sizes_offsets = None
                    break
                sizes_offsets.append((hi - lo + 1, lo))
            else:
                sizes_offsets = None
                break
        if sizes_offsets is not None:
            dims = 1
            for s, _ in sizes_offsets:
                dims *= s + 1
            if 0 < dims <= 65536:
                strides = []
                acc = 1
                for s, _ in reversed(sizes_offsets):
                    strides.append(acc)
                    acc *= s + 1
                strides.reverse()
                fast_group = (tuple(sizes_offsets), tuple(strides), dims,
                              pad_capacity(dims + 1))

    def run(chunk):
        with np.errstate(over="ignore", invalid="ignore",
                         divide="ignore"):
            return _execute(chunk)

    def _execute(chunk):
        columns, row_valid = materialize_planes(chunk, plan.schema)
        ctx = _Ctx(columns=columns, capacity=capacity)
        stage_cap = capacity
        mask = row_valid
        if where_b is not None:
            d, v = where_b.emit(ctx)
            mask = mask & v & d.astype(bool)

        if group is not None and fast_group is not None:
            sizes_offsets, strides, dims, seg_cap = fast_group
            nseg = dims + 1

            def _pad(plane):
                out = np.zeros(seg_cap, dtype=plane.dtype)
                out[:nseg] = plane
                return out

            key_planes = [b.emit(ctx) for _, b in group_key_b]
            seg = np.zeros(capacity, dtype=np.int32)
            for (data, valid), (size, key_offset), stride in zip(
                    key_planes, sizes_offsets, strides):
                if np.issubdtype(data.dtype, np.integer):
                    off = np.uint64(key_offset % (1 << 64))
                    shifted = (data.astype(np.uint64)
                               - off).astype(np.int32)
                else:
                    shifted = (data.astype(np.int64)
                               - key_offset).astype(np.int32)
                code = np.where(valid, shifted, size)
                seg = seg + code * stride
            seg = np.where(mask, seg, dims).astype(np.int64)
            present_counts, _ = _np_segment_aggregate(
                "count", mask, mask, seg, nseg, EValueType.int64)
            present = _pad((np.arange(nseg) < dims) &
                           (present_counts > 0))
            new_columns = {}
            slot = np.arange(seg_cap)
            for (name, bound), (size, key_offset), stride in zip(
                    group_key_b, sizes_offsets, strides):
                code = (slot // stride) % (size + 1)
                key_valid = code < size
                data = np.clip(code, 0, max(size - 1, 0))
                if bound.type is EValueType.boolean:
                    data = data.astype(np.bool_)
                elif bound.type in (EValueType.int64, EValueType.uint64):
                    dt = device_dtype(bound.type)
                    data = data.astype(dt) + np.array(key_offset,
                                                      dtype=dt)
                else:
                    data = data.astype(np.int32)
                new_columns[name] = (data, key_valid)
            _aggregate_into(new_columns, agg_arg_b, ctx, mask, seg, nseg,
                            pad=_pad)
            mask = present
            stage_cap = seg_cap
            ctx = _Ctx(columns=new_columns, capacity=seg_cap)
            if having_b is not None:
                d, v = having_b.emit(ctx)
                mask = mask & v & d.astype(bool)
        elif group is not None:
            key_planes = [b.emit(ctx) for _, b in group_key_b]
            order_idx = _hash_group_order(key_planes, mask)
            sorted_mask = mask[order_idx]
            sorted_keys = [(d[order_idx], v[order_idx])
                           for d, v in key_planes]
            seg_ids, num_groups = _segment_boundaries(sorted_keys,
                                                      sorted_mask)
            new_columns = {}
            for (name, _), (data, valid) in zip(group_key_b,
                                                sorted_keys):
                out_d, _ = _np_segment_aggregate(
                    "first", data, sorted_mask, seg_ids, capacity,
                    EValueType.null)
                out_v, _ = _np_segment_aggregate(
                    "first", valid.astype(np.int8), sorted_mask,
                    seg_ids, capacity, EValueType.null)
                new_columns[name] = (out_d, out_v.astype(bool))
            _aggregate_into(new_columns, agg_arg_b, ctx, sorted_mask,
                            seg_ids, capacity, reorder=order_idx)
            mask = np.arange(capacity) < num_groups
            ctx = _Ctx(columns=new_columns, capacity=capacity)
            if having_b is not None:
                d, v = having_b.emit(ctx)
                mask = mask & v & d.astype(bool)

        if order_b:
            # Full stable sort (no top-k candidate stage): identical over
            # the visible window, see the module docstring.
            keys = [(~mask).astype(np.uint8)]
            for bound, descending in order_b:
                data, valid = bound.emit(ctx)
                null_plane = ((~valid) if descending
                              else valid).astype(np.uint8)
                enc = _np_monotone_u64(data)
                if descending:
                    enc = ~enc
                enc = np.where(valid, enc, np.uint64(0))
                keys.append(null_plane)
                keys.append(enc)
            order_idx = np.lexsort(tuple(reversed(keys)))
            ctx = _Ctx(columns={name: (d[order_idx], v[order_idx])
                                for name, (d, v) in ctx.columns.items()},
                       capacity=stage_cap)
            mask = mask[order_idx]

        planes = [b.emit(ctx) for _, b in project_b]

        comp_idx = np.argsort((~mask).astype(np.uint8), kind="stable")
        total = int(mask.sum())
        off = min(offset, stage_cap) if parameterized else offset
        count = total - off
        if limit is not None:
            lim = min(limit, stage_cap) if parameterized else limit
            count = min(count, lim)
        count = max(count, 0)
        out_planes = []
        shift = np.clip(np.arange(stage_cap) + off, 0, stage_cap - 1)
        in_count = np.arange(stage_cap) < count
        for d, v in planes:
            d = d[comp_idx][shift]
            v = v[comp_idx][shift] & in_count
            out_planes.append((d, v))
        return out_planes, count

    return InterpretedQuery(run=run, output=output)


def _post_ref(name: str, ty, vocab) -> _NBound:
    def emit(ctx: _Ctx):
        return ctx.columns[name]
    return _NBound(type=ty, vocab=vocab, emit=emit)


def _aggregate_into(new_columns, agg_arg_b, ctx, gmask, seg, nseg,
                    pad=None, reorder=None):
    """Shared aggregate loop for both group paths, mirroring lowering's
    per-function dispatch.  `reorder` re-sorts argument planes into the
    grouped row order (the sorted path); `pad` widens fast-group outputs
    to the padded slot capacity."""
    def _r(plane):
        return plane if reorder is None else plane[reorder]

    def _out(plane):
        return plane if pad is None else pad(plane)

    for agg, arg, by_arg in agg_arg_b:
        if agg.function == "avg":
            data, valid = arg.emit(ctx)
            data = _r(data).astype(np.float64)
            valid = _r(valid) & gmask
            s, sv = _np_segment_aggregate("sum", data, valid, seg, nseg,
                                          EValueType.double)
            c, _ = _np_segment_aggregate("count", data, valid, seg,
                                         nseg, EValueType.int64)
            new_columns[agg.name] = (_out(s / np.maximum(c, 1)),
                                     _out(sv))
        elif agg.function == "cardinality":
            data, valid = arg.emit(ctx)
            d, dv = _np_segment_distinct_count(
                _r(data), _r(valid) & gmask, seg, nseg)
            new_columns[agg.name] = (_out(d), _out(dv))
        elif agg.function in ("argmin", "argmax"):
            vd, vv = arg.emit(ctx)
            bd, bv = by_arg.emit(ctx)
            out_d, out_v = _np_segment_arg_by(
                _r(vd), _r(vv), _r(bd), _r(bv) & gmask, seg, nseg,
                take_max=(agg.function == "argmax"))
            new_columns[agg.name] = (_out(out_d), _out(out_v))
        else:
            data, valid = arg.emit(ctx)
            valid = _r(valid) & gmask
            out, out_v = _np_segment_aggregate(
                agg.function, _r(data), valid, seg, nseg, agg.type)
            new_columns[agg.name] = (_out(out), _out(out_v))


def _hash_group_order(key_planes, mask) -> np.ndarray:
    """Mirror of segments.hash_group_order: stable ascending sort by
    [flags word (masked bit | per-key validity bits), then each key's
    monotone encoding with invalid values zeroed]."""
    flags = (~mask).astype(np.uint64)
    for data, valid in key_planes:
        flags = (flags << np.uint64(1)) | valid.astype(np.uint64)
    keys = [flags]
    for data, valid in key_planes:
        keys.append(np.where(valid, _np_monotone_u64(data),
                             np.uint64(0)))
    return np.lexsort(tuple(reversed(keys)))


def _segment_boundaries(sorted_keys, in_mask):
    """Mirror of segments.segment_boundaries — including the raw-plane
    compare (garbage under invalid splits exactly like the device)."""
    cap = in_mask.shape[0]
    change = np.zeros(cap, dtype=bool)
    for data, valid in sorted_keys:
        differs = (data != np.roll(data, 1)) | \
            (valid != np.roll(valid, 1))
        change = change | differs
    if cap:
        change[0] = False
    boundary = change & in_mask
    seg = np.cumsum(boundary.astype(np.int64))
    num_segments = int(seg[-1] + 1) if in_mask.any() else 0
    seg = np.where(in_mask, seg, num_segments)
    return seg, num_segments
