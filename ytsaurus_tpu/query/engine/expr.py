"""Expression compilation: typed IR → XLA, with host-side vocabulary binding.

Architecture (TPU-first redesign of the reference's LLVM expression codegen,
library/query/engine/cg_fragment_compiler.cpp):

  * Device planes are (data, valid) pairs; all null logic is three-valued and
    vectorized (the reference branches per row; we mask).
  * String work is split: per-row compute stays on device over int32
    dictionary codes; anything that inspects string BYTES (LIKE, lower,
    comparisons against literals, cross-vocabulary equality) is evaluated
    host-side over the chunk vocabulary — O(|vocab|), usually ≪ O(rows) —
    and shipped to the device as small bound arrays consumed by gathers.

  Two phases walk the IR in IDENTICAL order:
    - bind phase (per chunk, host): resolves vocabularies, computes remap /
      predicate tables and literal codes, appending them to a bindings list.
    - emit phase (once per compile-cache entry, at jit trace time): builds the
      jnp computation, pulling bound values positionally from the traced
      bindings tuple.
  Emit control flow depends only on IR structure and binding SHAPES, never on
  binding VALUES, so one traced program serves every chunk whose bindings
  have the same shapes.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.schema import EValueType, device_dtype

_EMPTY_VOCAB = np.array([], dtype=object)


def _dtype_for(ty: EValueType):
    return device_dtype(ty)


# --- bind-phase context -------------------------------------------------------


@dataclass
class ColumnBinding:
    """Host view of one input column at bind time."""
    type: EValueType
    vocab: Optional[np.ndarray]  # for string columns


@dataclass
class BindContext:
    """Per-chunk bind state: column vocabs in, bound host arrays out.

    `structure` is the bind-phase STRUCTURE NOTEBOOK: any host constant
    a bind method bakes into the traced program (concat's pair-table
    width, order-key bit widths, ...) must be noted here — it folds into
    PreparedQuery.structure_key and hence the compile-cache key, so two
    plans that share a (parameterized) fingerprint and binding shapes
    but differ in a baked constant can never share a program."""
    columns: dict[str, ColumnBinding]
    bindings: list = field(default_factory=list)
    structure: list = field(default_factory=list)

    def add(self, value) -> int:
        self.bindings.append(value)
        return len(self.bindings) - 1

    def note(self, *entry) -> None:
        self.structure.append(entry)


@dataclass
class EmitContext:
    """Trace-time state: column planes + the traced bindings tuple."""
    columns: dict[str, tuple[jax.Array, jax.Array]]
    bindings: tuple
    capacity: int


@dataclass
class BoundExpr:
    """Result of binding one IR node for one chunk."""
    type: EValueType
    vocab: Optional[np.ndarray]          # result vocabulary if string-typed
    emit: Callable[[EmitContext], tuple[jax.Array, jax.Array]]


def _vocab_bucket(n: int) -> int:
    """Pad vocab-indexed bound arrays to power-of-two buckets ≥ 8 so binding
    shapes (and hence compiled programs) are reused across chunks."""
    from ytsaurus_tpu.chunks.columnar import next_pow2
    return next_pow2(n, floor=8)


def _pad_np(arr: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full(size, fill, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _vocab_code(vocab: np.ndarray, value: bytes) -> int:
    """Code of `value` in sorted vocab, or -1 if absent."""
    idx = np.searchsorted(vocab, value) if len(vocab) else 0
    if idx < len(vocab) and vocab[idx] == value:
        return int(idx)
    return -1


def _range_code(vocab: np.ndarray, value: bytes) -> int:
    """Order-preserving encoding of `value` against a sorted vocab in the
    doubled space where row code c sits at 2c+1: a present value lands
    exactly on its row encoding, an absent one on the even insertion
    point between its neighbors (comparable, never equal)."""
    idx = int(np.searchsorted(vocab, value)) if len(vocab) else 0
    if idx < len(vocab) and vocab[idx] == value:
        return 2 * idx + 1
    return 2 * idx


def _remap_table(old_vocab: np.ndarray, new_vocab: np.ndarray) -> np.ndarray:
    lookup = {v: i for i, v in enumerate(new_vocab)}
    table = np.array([lookup[v] for v in old_vocab], dtype=np.int32)
    if len(table) == 0:
        table = np.zeros(1, dtype=np.int32)
    return table


def _merge_vocabs(*vocabs: Optional[np.ndarray]) -> np.ndarray:
    values = set()
    for v in vocabs:
        if v is not None:
            values.update(v)
    return np.array(sorted(values), dtype=object)


def _gather_binding(slot: int):
    """Emit helper: codes -> bound table lookup (clipped; -1-safe callers
    must mask validity themselves)."""
    def gather(ctx: EmitContext, codes: jax.Array) -> jax.Array:
        table = ctx.bindings[slot]
        return table[jnp.clip(codes, 0, table.shape[0] - 1)]
    return gather


class ExprBinder:
    """Binds a typed IR expression for one chunk (host phase)."""

    def __init__(self, bind_ctx: BindContext):
        self.ctx = bind_ctx

    def bind(self, node: ir.TExpr) -> BoundExpr:
        method = getattr(self, f"_bind_{type(node).__name__}", None)
        if method is None:
            raise YtError(f"Cannot lower {type(node).__name__}",
                          code=EErrorCode.QueryUnsupported)
        return method(node)

    # -- leaves ---------------------------------------------------------------

    def _bind_TLiteral(self, node: ir.TLiteral) -> BoundExpr:
        ty = node.type
        if ty is EValueType.null:
            def emit_null(ctx: EmitContext):
                zeros = jnp.zeros(ctx.capacity, dtype=jnp.int8)
                return zeros, jnp.zeros(ctx.capacity, dtype=bool)
            return BoundExpr(type=ty, vocab=None, emit=emit_null)
        if ty is EValueType.string:
            # Value-independent on device already: the literal is code 0
            # of its own one-entry vocabulary; every consumer reads the
            # actual bytes through bound remap/predicate tables.
            vocab = np.array([node.value], dtype=object)

            def emit_str(ctx: EmitContext):
                return (jnp.zeros(ctx.capacity, dtype=jnp.int32),
                        jnp.ones(ctx.capacity, dtype=bool))
            return BoundExpr(type=ty, vocab=vocab, emit=emit_str)
        if not isinstance(ty, EValueType):
            # Vector literal (the NEAREST query vector): a (dim,) float32
            # runtime BINDING.  The binding SHAPE keys the compile cache
            # per dim; the component values never enter the traced
            # program, so one program serves every query vector.
            # analyze: allow(host-sync): node.value is a host python tuple (bind phase), not a device plane
            slot = self.ctx.add(jnp.asarray(np.asarray(node.value,
                                                       dtype=np.float32)))

            def emit_vec(ctx: EmitContext):
                return (ctx.bindings[slot].astype(jnp.float32),
                        jnp.ones(ctx.capacity, dtype=bool))
            return BoundExpr(type=ty, vocab=None, emit=emit_vec)
        value = node.value
        dt = _dtype_for(ty)
        if ty is EValueType.boolean:
            # Static residue: true/false are keywords to the lexer and
            # stay in the (parameterized) fingerprint, so baking the
            # value cannot grow a shape spectrum.
            def emit_bool(ctx: EmitContext):
                return (jnp.full(ctx.capacity, bool(value), dtype=dt),
                        jnp.ones(ctx.capacity, dtype=bool))
            return BoundExpr(type=ty, vocab=None, emit=emit_bool)
        # Numeric literals ride as a 0-d BINDING, not a trace constant:
        # the compiled program is literal-value-independent, which is
        # what lets the parameterized fingerprint (ir.fingerprint with
        # omit_values=True) key one program for every constant.
        # analyze: allow(host-sync): `value` is a host python scalar (bind phase), not a device plane
        slot = self.ctx.add(jnp.asarray(np.asarray(value, dtype=dt)))

        def emit(ctx: EmitContext):
            return (jnp.broadcast_to(ctx.bindings[slot].astype(dt),
                                     (ctx.capacity,)),
                    jnp.ones(ctx.capacity, dtype=bool))
        return BoundExpr(type=ty, vocab=None, emit=emit)

    def _bind_TReference(self, node: ir.TReference) -> BoundExpr:
        binding = self.ctx.columns.get(node.name)
        if binding is None:
            raise YtError(f"Unbound column {node.name!r}",
                          code=EErrorCode.QueryExecutionError)
        name = node.name

        def emit(ctx: EmitContext):
            return ctx.columns[name]
        return BoundExpr(type=node.type, vocab=binding.vocab, emit=emit)

    # -- operators ------------------------------------------------------------

    def _bind_TUnary(self, node: ir.TUnary) -> BoundExpr:
        operand = self.bind(node.operand)
        op = node.op

        def emit(ctx: EmitContext):
            data, valid = operand.emit(ctx)
            if op == "not":
                return ~data.astype(bool), valid
            if op == "-":
                return -data, valid
            if op == "~":
                return ~data, valid
            raise AssertionError(op)
        return BoundExpr(type=node.type, vocab=None, emit=emit)

    def _bind_TBinary(self, node: ir.TBinary) -> BoundExpr:
        op = node.op
        lhs_b = self.bind(node.lhs)
        rhs_b = self.bind(node.rhs)

        if op in ("and", "or"):
            def emit_logical(ctx: EmitContext):
                ld, lv = lhs_b.emit(ctx)
                rd, rv = rhs_b.emit(ctx)
                ld, rd = ld.astype(bool), rd.astype(bool)
                if op == "and":
                    known_false = (lv & ~ld) | (rv & ~rd)
                    valid = (lv & rv) | known_false
                    data = jnp.where(lv, ld, True) & jnp.where(rv, rd, True)
                else:
                    known_true = (lv & ld) | (rv & rd)
                    valid = (lv & rv) | known_true
                    data = jnp.where(lv, ld, False) | jnp.where(rv, rd, False)
                return data & valid if op == "and" else data, valid
            return BoundExpr(type=EValueType.boolean, vocab=None,
                             emit=emit_logical)

        # String comparison: encoded-plane fast path first (ISSUE 19) —
        # a literal against a dict-encoded side compares CODES against
        # one host-bound code, skipping the merged-vocab remap tables
        # and their two per-row gathers entirely.
        if EValueType.string in (lhs_b.type, rhs_b.type) and \
                lhs_b.type is not EValueType.null and rhs_b.type is not EValueType.null:
            encoded = self._bind_string_literal_cmp(node, op, lhs_b, rhs_b)
            if encoded is not None:
                return encoded
            # Decoded fallback: the remap-table path.  Note it in the
            # structure notebook so the dispatcher can book the
            # /query/kernels/decoded_fallbacks sensor and EXPLAIN
            # ANALYZE can say which execution mode actually ran.
            self.ctx.note("str-decoded", op)
            merged = _merge_vocabs(lhs_b.vocab, rhs_b.vocab)
            l_vocab = lhs_b.vocab if lhs_b.vocab is not None else _EMPTY_VOCAB
            r_vocab = rhs_b.vocab if rhs_b.vocab is not None else _EMPTY_VOCAB
            l_slot = self.ctx.add(jnp.asarray(_pad_np(
                _remap_table(l_vocab, merged),
                _vocab_bucket(max(len(l_vocab), 1)), 0)))
            r_slot = self.ctx.add(jnp.asarray(_pad_np(
                _remap_table(r_vocab, merged),
                _vocab_bucket(max(len(r_vocab), 1)), 0)))
            l_gather = _gather_binding(l_slot)
            r_gather = _gather_binding(r_slot)

            def emit_strcmp(ctx: EmitContext):
                ld, lv = lhs_b.emit(ctx)
                rd, rv = rhs_b.emit(ctx)
                lm = l_gather(ctx, ld)
                rm = r_gather(ctx, rd)
                data = _compare(op, lm, rm)
                return data, lv & rv
            return BoundExpr(type=EValueType.boolean, vocab=None,
                             emit=emit_strcmp)

        target = node.type if op not in _CMP_OPS else None

        def emit(ctx: EmitContext):
            ld, lv = lhs_b.emit(ctx)
            rd, rv = rhs_b.emit(ctx)
            valid = lv & rv
            if op in _CMP_OPS:
                ld, rd = _promote_pair(ld, rd)
                return _compare(op, ld, rd), valid
            dt = _dtype_for(target)
            ld = ld.astype(dt)
            rd = rd.astype(dt)
            if op == "+":
                data = ld + rd
            elif op == "-":
                data = ld - rd
            elif op == "*":
                data = ld * rd
            elif op == "/":
                if jnp.issubdtype(dt, jnp.integer):
                    safe = jnp.where(rd == 0, jnp.ones_like(rd), rd)
                    data = jax.lax.div(ld, safe)   # C++ trunc semantics
                    valid = valid & (rd != 0)
                else:
                    data = ld / rd
            elif op == "%":
                if jnp.issubdtype(dt, jnp.integer):
                    safe = jnp.where(rd == 0, jnp.ones_like(rd), rd)
                    data = jax.lax.rem(ld, safe)
                    valid = valid & (rd != 0)
                else:
                    data = jnp.fmod(ld, rd)
            elif op == "|":
                data = ld | rd
            elif op == "&":
                data = ld & rd
            elif op == "^":
                data = ld ^ rd
            elif op == "<<":
                data = jnp.left_shift(ld, rd)
            elif op == ">>":
                data = jnp.right_shift(ld, rd)
            else:
                raise AssertionError(op)
            return data, valid
        return BoundExpr(type=node.type, vocab=None, emit=emit)

    def _bind_string_literal_cmp(self, node: ir.TBinary, op: str,
                                 lhs_b: BoundExpr,
                                 rhs_b: BoundExpr) -> Optional[BoundExpr]:
        """Encoded-plane string comparison (ISSUE 19): literal vs a
        dict-encoded expression compares CODES, not remapped vocabs.

        The binding carries the literal's position in the COLUMN side's
        own sorted vocabulary: =/!= bind the exact code (-1 when absent —
        equal to no row code), range ops bind in the doubled space where
        row code c sits at 2c+1 and an absent literal lands on its even
        insertion point (strictly between neighboring codes, equal to
        none — see _range_code).  Order preservation of the encode makes
        the integer compare the byte compare.  Bit-identical to the
        merged remap-table path on valid lanes; that path remains the
        decoded oracle (compile_config().encoded_predicates=False).

        NOTE: interp.NumpyBinder mirrors this decision AND these
        formulas — change both or tier bit-identity breaks."""
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
        from ytsaurus_tpu.chunks.columnar import vocab_digest
        vocab = col_b.vocab
        # The bound code is only meaningful against THIS vocab
        # generation: fold its content digest into the structure notebook
        # (-> structure_key -> compile cache key) so a chunk re-encode
        # after compaction can never pair a stale code binding with a
        # cached program, even if a future layer memoizes bind output.
        self.ctx.note("strlit", op, vocab_digest(vocab))
        if op in ("=", "!="):
            slot = self.ctx.add(jnp.asarray(
                np.int32(_vocab_code(vocab, lit))))

            def emit_eq(ctx: EmitContext):
                data, valid = col_b.emit(ctx)
                code = ctx.bindings[slot]
                out = (data == code) if op == "=" else (data != code)
                return out, valid
            return BoundExpr(type=EValueType.boolean, vocab=None,
                             emit=emit_eq)
        slot = self.ctx.add(jnp.asarray(np.int32(_range_code(vocab, lit))))

        def emit_rng(ctx: EmitContext):
            data, valid = col_b.emit(ctx)
            doubled = data.astype(jnp.int32) * 2 + 1
            code = ctx.bindings[slot]
            out = _compare(op, doubled, code) if lit_on_right \
                else _compare(op, code, doubled)
            return out, valid
        return BoundExpr(type=EValueType.boolean, vocab=None,
                         emit=emit_rng)

    # -- functions ------------------------------------------------------------

    def _bind_TFunction(self, node: ir.TFunction) -> BoundExpr:
        name = node.name
        args = [self.bind(a) for a in node.args]

        if name == "if":
            return self._bind_if(node, args)
        if name in ("l2_distance", "distance", "cosine_distance",
                    "dot_product"):
            a, b = args[0], args[1]
            metric = name

            def emit_dist(ctx: EmitContext):
                da, va = a.emit(ctx)
                db, vb = b.emit(ctx)
                da = da.astype(jnp.float32)
                db = db.astype(jnp.float32)
                if da.ndim == 1 and db.ndim == 2:
                    da, db = db, da
                    va, vb = vb, va
                if da.ndim == 2 and db.ndim == 1:
                    # THE tiled distance pass: (capacity, dim) @ (dim,)
                    # — one MXU matmul over the contiguous plane.
                    dot = da @ db
                elif da.ndim == 2:
                    dot = (da * db).sum(axis=1)   # row-wise col vs col
                else:
                    dot = da @ db                 # two literals: scalar
                na2 = (da * da).sum(axis=-1)
                nb2 = (db * db).sum(axis=-1)
                if metric == "dot_product":
                    out = dot
                elif metric == "cosine_distance":
                    denom = jnp.sqrt(na2) * jnp.sqrt(nb2)
                    out = jnp.where(denom > 0.0, 1.0 - dot / denom, 1.0)
                else:
                    # L2 via the norm trick off the shared dot pass.
                    out = jnp.sqrt(jnp.maximum(na2 - 2.0 * dot + nb2, 0.0))
                out = jnp.broadcast_to(out, (ctx.capacity,))
                return out.astype(jnp.float64), va & vb
            return BoundExpr(type=EValueType.double, vocab=None,
                             emit=emit_dist)
        if name == "is_null":
            a = args[0]

            def emit_is_null(ctx):
                _, valid = a.emit(ctx)
                return ~valid, jnp.ones_like(valid)
            return BoundExpr(type=EValueType.boolean, vocab=None,
                             emit=emit_is_null)
        if name == "if_null":
            return self._bind_merge_select(
                node, [args[0], args[1]],
                lambda ctx, planes: (
                    jnp.where(planes[0][1], planes[0][0], planes[1][0]),
                    planes[0][1] | planes[1][1]))
        if name in ("int64", "uint64", "double", "boolean"):
            a = args[0]
            dt = _dtype_for(node.type)

            def emit_cast(ctx):
                data, valid = a.emit(ctx)
                if data.dtype == jnp.bool_ or node.type is EValueType.boolean:
                    return data.astype(dt) if node.type is not EValueType.boolean \
                        else (data != 0), valid
                return data.astype(dt), valid
            return BoundExpr(type=node.type, vocab=None, emit=emit_cast)
        if name == "abs":
            a = args[0]

            def emit_abs(ctx):
                data, valid = a.emit(ctx)
                if jnp.issubdtype(data.dtype, jnp.unsignedinteger):
                    return data, valid
                return jnp.abs(data), valid
            return BoundExpr(type=node.type, vocab=None, emit=emit_abs)
        if name in ("floor", "ceil", "sqrt"):
            a = args[0]
            fn = {"floor": jnp.floor, "ceil": jnp.ceil, "sqrt": jnp.sqrt}[name]

            def emit_math(ctx):
                data, valid = a.emit(ctx)
                return fn(data.astype(jnp.float64)), valid
            return BoundExpr(type=node.type, vocab=None, emit=emit_math)
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
                return _timestamp_floor(data.astype(jnp.int64), unit), valid
            return BoundExpr(type=EValueType.int64, vocab=None,
                             emit=emit_ts_floor)
        if name in ("is_finite", "is_nan"):
            a = args[0]
            fn = jnp.isfinite if name == "is_finite" else jnp.isnan

            def emit_fpred(ctx):
                data, valid = a.emit(ctx)
                return fn(data.astype(jnp.float64)), valid
            return BoundExpr(type=EValueType.boolean, vocab=None,
                             emit=emit_fpred)
        if name == "length":
            return self._bind_vocab_table(args[0], EValueType.int64,
                                          np.int64, len)
        if name in ("is_prefix", "is_substr"):
            # Non-literal pattern path comes through here; only literal
            # patterns (TStringPredicate) are supported for now.
            raise YtError(f"{name} requires a literal pattern",
                          code=EErrorCode.QueryUnsupported)
        if name == "farm_hash":
            return self._bind_hash(args)
        if name in ("regex_full_match", "regex_partial_match"):
            # Pattern compiles at PLAN time against the vocabulary (ref
            # regex_* builtins run RE2 per row; here the match set is a
            # host-computed table consumed by one device gather).
            rx = _compile_regex(_literal_bytes(node.args[0], name), name)
            return self._bind_vocab_table(
                args[1], EValueType.boolean, np.bool_,
                (lambda v: rx.fullmatch(v) is not None)
                if name == "regex_full_match"
                else (lambda v: rx.search(v) is not None))
        if name in ("regex_replace_first", "regex_replace_all"):
            rx = _compile_regex(_literal_bytes(node.args[0], name), name)
            rewrite = _literal_bytes(node.args[2], name)
            count = 1 if name == "regex_replace_first" else 0
            try:
                return self._bind_string_map(
                    args[1], lambda v: rx.sub(rewrite, v, count=count))
            except re.error as exc:
                raise YtError(f"{name}: invalid rewrite "
                              f"{rewrite!r}: {exc}",
                              code=EErrorCode.QueryParseError)
        if name == "regex_escape":
            return self._bind_string_map(args[0], re.escape)
        if name == "sha256":
            return self._bind_string_map(
                args[0], lambda v: hashlib.sha256(v).digest())
        if name == "bigb_hash":
            # A farm_hash-class string hash with its own mix (ref
            # bigb_hash over uids) — domain-separated from farm_hash.
            return self._bind_vocab_table(
                args[0], EValueType.uint64, np.uint64,
                lambda v: _bytes_hash(b"bigb:" + v))
        if name == "parse_int64":
            s = args[0]
            vocab = s.vocab if s.vocab is not None else _EMPTY_VOCAB

            def _try_parse(v: bytes):
                # Reference FromString semantics: optional sign + digits
                # only (Python int() would also take '1_2'), and the
                # value must FIT int64 (overflow → null, not a bind-time
                # OverflowError from np.int64).
                try:
                    text = v.strip()
                except AttributeError:
                    return 0, False
                if not re.fullmatch(rb"[+-]?[0-9]+", text):
                    return 0, False
                value = int(text)
                if not (-(1 << 63) <= value < (1 << 63)):
                    return 0, False
                return value, True
            parsed = [_try_parse(v) for v in vocab]
            val_t = np.array([p[0] for p in parsed] or [0],
                             dtype=np.int64)
            ok_t = np.array([p[1] for p in parsed] or [False],
                            dtype=np.bool_)
            val_slot = self.ctx.add(jnp.asarray(
                _pad_np(val_t, _vocab_bucket(len(val_t)), 0)))
            ok_slot = self.ctx.add(jnp.asarray(
                _pad_np(ok_t, _vocab_bucket(len(ok_t)), 0)))
            g_val = _gather_binding(val_slot)
            g_ok = _gather_binding(ok_slot)

            def emit_parse(ctx):
                data, valid = s.emit(ctx)
                # Unparseable strings yield null (ref parse_int64
                # error→null semantics for the non-throwing variant).
                return g_val(ctx, data), valid & g_ok(ctx, data)
            return BoundExpr(type=EValueType.int64, vocab=None,
                             emit=emit_parse)
        if name == "substr":
            start = int(_literal_int(node.args[1], name))
            length = int(_literal_int(node.args[2], name)) \
                if len(node.args) > 2 else None
            if start < 0 or (length is not None and length < 0):
                raise YtError("substr: start/length must be >= 0",
                              code=EErrorCode.QueryTypeError)
            end = None if length is None else start + length
            return self._bind_string_map(
                args[0], lambda v: v[start:end])
        if name in ("min_of", "max_of"):
            pick_min = name == "min_of"

            def emit_minmax(ctx):
                planes = [a.emit(ctx) for a in args]
                data, valid = planes[0]
                for d, v in planes[1:]:
                    d, data2 = _promote_pair(d, data)
                    better = (d < data2) if pick_min else (d > data2)
                    take = v & (~valid | better)
                    data = jnp.where(take, d, data2)
                    valid = valid | v
                return data, valid
            return BoundExpr(type=node.type, vocab=None, emit=emit_minmax)
        raise YtError(f"Function {name!r} has no lowering",
                      code=EErrorCode.QueryUnsupported)

    def _bind_if(self, node: ir.TFunction, args: list[BoundExpr]) -> BoundExpr:
        cond, then_b, else_b = args

        def select(ctx, planes):
            cd, cv = planes[0]
            td, tv = planes[1]
            ed, ev = planes[2]
            take_then = cv & cd.astype(bool)
            take_else = cv & ~cd.astype(bool)
            td2, ed2 = _promote_pair(td, ed)
            data = jnp.where(take_then, td2, ed2)
            valid = jnp.where(take_then, tv, take_else & ev)
            return data, valid
        return self._bind_merge_select(node, [cond, then_b, else_b], select,
                                       string_operands=(1, 2))

    def _bind_merge_select(self, node, args: list[BoundExpr], select,
                           string_operands: tuple[int, ...] = (0, 1)) -> BoundExpr:
        """Shared lowering for if/if_null: merges string vocabs of the
        value-producing operands when the result is string-typed."""
        if node.type is EValueType.string:
            value_args = [args[i] for i in string_operands]
            merged = _merge_vocabs(*[a.vocab for a in value_args])
            remap_gathers = {}
            for i in string_operands:
                a = args[i]
                vocab = a.vocab if a.vocab is not None else _EMPTY_VOCAB
                slot = self.ctx.add(jnp.asarray(_pad_np(
                    _remap_table(vocab, merged),
                    _vocab_bucket(max(len(vocab), 1)), 0)))
                remap_gathers[i] = _gather_binding(slot)

            def emit_str(ctx):
                planes = []
                for i, a in enumerate(args):
                    d, v = a.emit(ctx)
                    if i in remap_gathers and a.type is EValueType.string:
                        d = remap_gathers[i](ctx, d)
                    planes.append((d, v))
                return select(ctx, planes)
            return BoundExpr(type=node.type, vocab=merged, emit=emit_str)

        def emit(ctx):
            planes = [a.emit(ctx) for a in args]
            return select(ctx, planes)
        return BoundExpr(type=node.type, vocab=None, emit=emit)

    def _bind_concat(self, a: BoundExpr, b: BoundExpr) -> BoundExpr:
        """String concatenation at the vocabulary level: the result vocab is
        the (sorted, deduped) cross product of operand vocabs; the device
        computes pair index c_a * |v_b| + c_b and gathers through a bound
        remap.  Guarded by a cross-product cap."""
        va = a.vocab if a.vocab is not None else _EMPTY_VOCAB
        vb = b.vocab if b.vocab is not None else _EMPTY_VOCAB
        na, nb = max(len(va), 1), max(len(vb), 1)
        # nb bakes into the pair-index arithmetic below (a trace
        # constant the padded table shape alone cannot distinguish).
        self.ctx.note("concat", na, nb)
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
        slot = self.ctx.add(jnp.asarray(
            _pad_np(table, _vocab_bucket(len(table)), 0)))
        gather = _gather_binding(slot)
        nb_const = nb

        def emit(ctx):
            da, valid_a = a.emit(ctx)
            db, valid_b = b.emit(ctx)
            pair = da.astype(jnp.int32) * nb_const + db.astype(jnp.int32)
            return gather(ctx, pair), valid_a & valid_b
        return BoundExpr(type=EValueType.string, vocab=merged, emit=emit)

    def _bind_vocab_table(self, a: BoundExpr, result_type: EValueType,
                          np_dtype, fn) -> BoundExpr:
        """String → scalar via a host-computed per-vocabulary table and
        one device gather (the length/regex/hash shape)."""
        vocab = a.vocab if a.vocab is not None else _EMPTY_VOCAB
        table = np.array([fn(v) for v in vocab] or [np_dtype()],
                         dtype=np_dtype)
        slot = self.ctx.add(jnp.asarray(
            _pad_np(table, _vocab_bucket(len(table)), 0)))
        gather = _gather_binding(slot)

        def emit(ctx):
            data, valid = a.emit(ctx)
            return gather(ctx, data), valid
        return BoundExpr(type=result_type, vocab=None, emit=emit)

    def _bind_string_map(self, a: BoundExpr, fn) -> BoundExpr:
        """Vocabulary-level string→string transform (lower/upper/…)."""
        vocab = a.vocab if a.vocab is not None else _EMPTY_VOCAB
        new_values = [fn(v) for v in vocab]
        new_vocab = np.array(sorted(set(new_values)), dtype=object)
        lookup = {v: i for i, v in enumerate(new_vocab)}
        table = np.array([lookup[v] for v in new_values], dtype=np.int32)
        if len(table) == 0:
            table = np.zeros(1, dtype=np.int32)
        slot = self.ctx.add(jnp.asarray(
            _pad_np(table, _vocab_bucket(len(table)), 0)))
        gather = _gather_binding(slot)

        def emit(ctx):
            data, valid = a.emit(ctx)
            return gather(ctx, data), valid
        return BoundExpr(type=EValueType.string, vocab=new_vocab, emit=emit)

    def _bind_hash(self, args: list[BoundExpr]) -> BoundExpr:
        hashed_args = []
        for a in args:
            if a.type is EValueType.string:
                vocab = a.vocab if a.vocab is not None else _EMPTY_VOCAB
                table = np.array(
                    [_bytes_hash(v) for v in vocab], dtype=np.uint64)
                if len(table) == 0:
                    table = np.zeros(1, dtype=np.uint64)
                slot = self.ctx.add(jnp.asarray(
                    _pad_np(table, _vocab_bucket(len(table)), 0)))
                hashed_args.append((a, _gather_binding(slot)))
            else:
                hashed_args.append((a, None))

        def emit(ctx):
            # Hash of a null value is defined (contributes 0), so the result
            # is always valid.
            acc = jnp.full(ctx.capacity, np.uint64(0x9E3779B97F4A7C15),
                           dtype=jnp.uint64)
            for a, gather in hashed_args:
                data, valid = a.emit(ctx)
                if gather is not None:
                    h = gather(ctx, data)
                else:
                    h = _mix_u64(data)
                h = jnp.where(valid, h, jnp.zeros_like(h))
                acc = _combine_u64(acc, h)
            return acc, jnp.ones(ctx.capacity, dtype=bool)
        return BoundExpr(type=EValueType.uint64, vocab=None, emit=emit)

    # -- membership / ranges / transform --------------------------------------

    def _bind_TIn(self, node: ir.TIn) -> BoundExpr:
        from ytsaurus_tpu.chunks.columnar import next_pow2
        operands = [self.bind(o) for o in node.operands]
        # IN lists trace a membership loop per tuple, so the list LENGTH
        # bakes into the program.  Bucket it pow2 (same discipline as
        # chunk capacities / lookup needles): padded slots carry
        # present=False so they match nothing, and `user_id IN (...)`
        # traffic with drifting list sizes compiles O(log max) programs
        # instead of one per length.
        n_bucket = next_pow2(len(node.values))
        self.ctx.note("in", n_bucket)
        value_planes, value_valids = self._bind_value_tuples(
            operands, node.values, pad_to=n_bucket)
        present_np = np.zeros(n_bucket, dtype=bool)
        present_np[: len(node.values)] = True
        present_slot = self.ctx.add(jnp.asarray(present_np))

        def emit(ctx):
            op_planes = [o.emit(ctx) for o in operands]
            match_any = jnp.zeros(ctx.capacity, dtype=bool)
            present = ctx.bindings[present_slot]
            for vi in range(n_bucket):
                row_match = jnp.ones(ctx.capacity, dtype=bool)
                for oi, (data, valid) in enumerate(op_planes):
                    const = ctx.bindings[value_planes[oi]][vi]
                    cvalid = ctx.bindings[value_valids[oi]][vi]
                    # null element matches null rows; non-null matches equal
                    # valid rows (null == null per CompareRowValues).
                    row_match = row_match & jnp.where(
                        cvalid, valid & (data == const), ~valid)
                match_any = match_any | (row_match & present[vi])
            return match_any, jnp.ones(ctx.capacity, dtype=bool)
        return BoundExpr(type=EValueType.boolean, vocab=None, emit=emit)

    def _bind_TBetween(self, node: ir.TBetween) -> BoundExpr:
        operands = [self.bind(o) for o in node.operands]
        string_ops = [o.type is EValueType.string for o in operands]
        bound_ranges = []
        for lower, upper in node.ranges:
            lo = self._bind_value_tuples(operands[: len(lower)], [lower],
                                         range_encode=True)
            up = self._bind_value_tuples(operands[: len(upper)], [upper],
                                         range_encode=True)
            bound_ranges.append((len(lower), lo, len(upper), up))

        def emit(ctx):
            op_planes = []
            for operand, is_str in zip(operands, string_ops):
                data, valid = operand.emit(ctx)
                if is_str:
                    # Doubled space: see _range_code.
                    data = data.astype(jnp.int32) * 2 + 1
                op_planes.append((data, valid))
            in_any = jnp.zeros(ctx.capacity, dtype=bool)
            for lo_len, lo_slots, up_len, up_slots in bound_ranges:
                ge = _lex_compare(ctx, op_planes[:lo_len], lo_slots, 0, ">=")
                le = _lex_compare(ctx, op_planes[:up_len], up_slots, 0, "<=")
                in_any = in_any | (ge & le)
            result = in_any
            if node.negated:
                result = ~result
            return result, jnp.ones(ctx.capacity, dtype=bool)
        return BoundExpr(type=EValueType.boolean, vocab=None, emit=emit)

    def _bind_TTransform(self, node: ir.TTransform) -> BoundExpr:
        operands = [self.bind(o) for o in node.operands]
        from_slots, from_valids = self._bind_value_tuples(
            operands, node.from_values)
        default = self.bind(node.default) if node.default is not None else None

        # Output values (may be strings → need an output vocab).
        out_vocab = None
        if node.type is EValueType.string:
            out_vocab = _merge_vocabs(
                np.array([v for v in node.to_values if v is not None],
                         dtype=object),
                default.vocab if default is not None else None)
            to_codes = np.array(
                [_vocab_code(out_vocab, v) if v is not None else 0
                 for v in node.to_values], dtype=np.int32)
            to_valid = np.array([v is not None for v in node.to_values])
            to_slot = self.ctx.add(jnp.asarray(to_codes if len(to_codes) else
                                               np.zeros(1, dtype=np.int32)))
            default_gather = None
            if default is not None and default.type is EValueType.string:
                vocab = default.vocab if default.vocab is not None else _EMPTY_VOCAB
                slot = self.ctx.add(jnp.asarray(_pad_np(
                    _remap_table(vocab, out_vocab),
                    _vocab_bucket(max(len(vocab), 1)), 0)))
                default_gather = _gather_binding(slot)
        else:
            dt = _dtype_for(node.type)
            to_np = np.array(
                [v if v is not None else 0 for v in node.to_values], dtype=dt)
            to_valid = np.array([v is not None for v in node.to_values])
            to_slot = self.ctx.add(jnp.asarray(to_np if len(to_np) else
                                               np.zeros(1, dtype=dt)))
            default_gather = None
        to_valid_slot = self.ctx.add(jnp.asarray(
            to_valid if len(to_valid) else np.zeros(1, dtype=bool)))

        def emit(ctx):
            op_planes = [o.emit(ctx) for o in operands]
            n_values = len(node.from_values)
            # Find first matching from-tuple per row.
            match_idx = jnp.full(ctx.capacity, n_values, dtype=jnp.int32)
            for vi in range(n_values - 1, -1, -1):
                row_match = jnp.ones(ctx.capacity, dtype=bool)
                for oi, (data, valid) in enumerate(op_planes):
                    const = ctx.bindings[from_slots[oi]][vi]
                    cvalid = ctx.bindings[from_valids[oi]][vi]
                    row_match = row_match & jnp.where(
                        cvalid, valid & (data == const), ~valid)
                match_idx = jnp.where(row_match, vi, match_idx)
            matched = match_idx < n_values
            safe_idx = jnp.clip(match_idx, 0, max(n_values - 1, 0))
            to_table = ctx.bindings[to_slot]
            to_valid_tab = ctx.bindings[to_valid_slot]
            data = to_table[safe_idx]
            valid = matched & to_valid_tab[safe_idx]
            if default is not None:
                dd, dv = default.emit(ctx)
                if default_gather is not None:
                    dd = default_gather(ctx, dd)
                dd = dd.astype(data.dtype)
                data = jnp.where(matched, data, dd)
                valid = jnp.where(matched, valid, dv)
            return data, valid
        return BoundExpr(type=node.type, vocab=out_vocab, emit=emit)

    def _bind_value_tuples(self, operands: list[BoundExpr],
                           values, range_encode: bool = False,
                           pad_to: Optional[int] = None
                           ) -> tuple[list[int], list[int]]:
        """Bind literal tuples column-wise; returns (value_slots, valid_slots)
        — one binding slot per operand holding the per-tuple constants
        (strings → codes) plus one holding the per-tuple element validity
        (False where the literal is null), so null tuple elements match null
        rows and nothing else (CompareRowValues semantics: null == null).

        range_encode=True (BETWEEN bounds): string literals ABSENT from
        the column's vocabulary must still order correctly against row
        codes, not collapse to -1 (which made `s BETWEEN 'a' AND 'b'`
        empty whenever the bounds were not column values).  Rows compare
        in a DOUBLED space (code*2+1, see _bind_TBetween); a present
        literal binds exactly (idx*2+1, equality preserved) and an
        absent one binds at its even insertion point (idx*2), which
        orders strictly between the neighboring codes and can equal no
        row — exactly the semantics of a value missing from the sorted
        vocabulary."""
        slots = []
        valid_slots = []
        for oi, operand in enumerate(operands):
            col = [tup[oi] if oi < len(tup) else None for tup in values]
            if operand.type is EValueType.string:
                vocab = operand.vocab if operand.vocab is not None else _EMPTY_VOCAB
                if range_encode:
                    arr = np.array(
                        [_range_code(vocab, v) if v is not None else 0
                         for v in col], dtype=np.int32)
                else:
                    arr = np.array(
                        [_vocab_code(vocab, v) if v is not None else -2
                         for v in col], dtype=np.int32)
            else:
                dt = _dtype_for(operand.type) if operand.type is not EValueType.null \
                    else np.int64
                arr = np.array([v if v is not None else 0 for v in col],
                               dtype=dt)
            ok = np.array([v is not None for v in col], dtype=bool)
            if len(arr) == 0:
                arr = np.zeros(1, dtype=arr.dtype)
                ok = np.zeros(1, dtype=bool)
            if pad_to is not None and len(arr) < pad_to:
                # pow2-bucketed value list (TIn): padded slots are
                # masked off by the caller's `present` binding.
                arr = _pad_np(arr, pad_to, 0)
                ok = _pad_np(ok, pad_to, False)
            slots.append(self.ctx.add(jnp.asarray(arr)))
            valid_slots.append(self.ctx.add(jnp.asarray(ok)))
        return slots, valid_slots

    # -- string predicates -----------------------------------------------------

    def _bind_TStringPredicate(self, node: ir.TStringPredicate) -> BoundExpr:
        operand = self.bind(node.operand)
        vocab = operand.vocab if operand.vocab is not None else _EMPTY_VOCAB
        matcher = _string_matcher(node)
        table = np.array([matcher(v) for v in vocab], dtype=bool)
        if len(table) == 0:
            table = np.zeros(1, dtype=bool)
        if node.negated:
            table = ~table
        slot = self.ctx.add(jnp.asarray(
            _pad_np(table, _vocab_bucket(len(table)), False)))
        gather = _gather_binding(slot)

        def emit(ctx):
            data, valid = operand.emit(ctx)
            return gather(ctx, data), valid
        return BoundExpr(type=EValueType.boolean, vocab=None, emit=emit)


_CMP_OPS = ("=", "!=", "<", "<=", ">", ">=")


def _compare(op: str, lhs: jax.Array, rhs: jax.Array) -> jax.Array:
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


def _promote_pair(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Promote two numeric planes to a common dtype for comparison/select."""
    if a.dtype == b.dtype:
        return a, b
    target = jnp.promote_types(a.dtype, b.dtype)
    return a.astype(target), b.astype(target)


def _lex_compare(ctx: EmitContext, op_planes, slots, vi: int,
                 op: str) -> jax.Array:
    """Lexicographic tuple comparison against bound constants (tuple index vi).
    Null-aware: null sorts before every value and equals null (the
    CompareRowValues total order)."""
    value_slots, valid_slots = slots
    cap = ctx.capacity
    result = jnp.full(cap, op in ("<=", ">="), dtype=bool)
    # Build from least-significant operand backwards:
    for oi in range(len(op_planes) - 1, -1, -1):
        data, valid = op_planes[oi]
        const = ctx.bindings[value_slots[oi]][vi]
        cvalid = ctx.bindings[valid_slots[oi]][vi]
        eq = jnp.where(cvalid, valid & (data == const), ~valid)
        if op in ("<=", "<"):
            lt = jnp.where(cvalid, (~valid) | (data < const),
                           jnp.zeros(cap, dtype=bool))
            result = lt | (eq & result)
        else:
            gt = jnp.where(cvalid, valid & (data > const), valid)
            result = gt | (eq & result)
    return result


def _string_matcher(node: ir.TStringPredicate):
    pattern = node.pattern
    if node.kind == "prefix":
        return lambda v: v.startswith(pattern)
    if node.kind == "substr":
        return lambda v: pattern in v
    if node.kind == "regex":
        rx = _compile_regex(pattern, "regex predicate")
        return lambda v: rx.fullmatch(v) is not None
    if node.kind == "like":
        rx = _like_to_regex(pattern, node.case_insensitive)
        return lambda v: rx.fullmatch(v) is not None
    raise YtError(f"Unknown string predicate {node.kind!r}")


def _like_to_regex(pattern: bytes, case_insensitive: bool):
    """SQL LIKE → regex: % and _ wildcard; backslash escapes the next
    character (\\% and \\_ match literally, \\\\ is a backslash — the
    standard ESCAPE '\\' semantics the reference's LIKE applies)."""
    out = []
    chars = pattern.decode("utf-8", errors="surrogateescape")
    i = 0
    while i < len(chars):
        ch = chars[i]
        if ch == "\\":
            # Standard ESCAPE: only %, _, and \ may follow; anything
            # else (incl. a trailing lone backslash) is a pattern error,
            # not a silent guess.
            if i + 1 >= len(chars) or chars[i + 1] not in "%_\\":
                raise YtError(
                    f"LIKE: invalid escape in pattern {pattern!r} "
                    f"(backslash must precede %, _ or \\)",
                    code=EErrorCode.QueryParseError)
            out.append(re.escape(chars[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    flags = re.DOTALL | (re.IGNORECASE if case_insensitive else 0)
    return re.compile("".join(out).encode("utf-8", errors="surrogateescape"),
                      flags)


def _days_to_civil(days: jax.Array):
    """Vectorized days-since-epoch → (year, month, day), proleptic Gregorian
    (the civil-from-days algorithm as pure integer device ops)."""
    z = days + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = jnp.floor_divide(
        doe - doe // 1460 + doe // 36524 - doe // 146096, 365)
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = jnp.floor_divide(5 * doy + 2, 153)
    d = doy - jnp.floor_divide(153 * mp + 2, 5) + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = y + (m <= 2)
    return y, m, d


def _civil_to_days(y: jax.Array, m: jax.Array, d: jax.Array) -> jax.Array:
    y = y - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    mp = jnp.mod(m + 9, 12)
    doy = jnp.floor_divide(153 * mp + 2, 5) + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _timestamp_floor(ts: jax.Array, unit: str) -> jax.Array:
    """Floor unix seconds to a calendar boundary (weeks start Monday)."""
    if unit == "hour":
        return ts - jnp.mod(ts, 3600)
    if unit == "day":
        return ts - jnp.mod(ts, 86400)
    days = jnp.floor_divide(ts, 86400)
    if unit == "week":
        dow = jnp.mod(days + 3, 7)       # epoch day was a Thursday
        return (days - dow) * 86400
    y, m, _ = _days_to_civil(days)
    if unit == "month":
        return _civil_to_days(y, m, jnp.ones_like(m)) * 86400
    if unit == "year":
        one = jnp.ones_like(y)
        return _civil_to_days(y, one, one) * 86400
    raise YtError(f"Unknown timestamp unit {unit!r}",
                  code=EErrorCode.QueryUnsupported)


def _compile_regex(pattern: bytes, what: str):
    try:
        return re.compile(pattern)
    except re.error as exc:
        raise YtError(f"{what}: invalid regex {pattern!r}: {exc}",
                      code=EErrorCode.QueryParseError)


def _literal_bytes(arg, what: str) -> bytes:
    """Plan-time literal string (patterns/rewrites compile against the
    vocabulary at bind time; a computed pattern has no vocabulary-sized
    table)."""
    if not isinstance(arg, ir.TLiteral) or not isinstance(arg.value,
                                                          (bytes, str)):
        raise YtError(f"{what} requires a literal string argument",
                      code=EErrorCode.QueryUnsupported)
    value = arg.value
    return value.encode() if isinstance(value, str) else value


def _literal_int(arg, what: str) -> int:
    if not isinstance(arg, ir.TLiteral) or not isinstance(arg.value, int):
        raise YtError(f"{what} requires a literal integer argument",
                      code=EErrorCode.QueryUnsupported)
    return arg.value


def _bytes_hash(v: bytes) -> np.uint64:
    """Deterministic 64-bit FNV-1a (stands in for FarmHash; stable across
    runs, which is all sharding/sampling needs)."""
    h = np.uint64(0xCBF29CE484222325)
    for b in v:
        h = np.uint64((int(h) ^ b) * 0x100000001B3 % (1 << 64))
    return h


def _mix_u64(data: jax.Array) -> jax.Array:
    if data.dtype == jnp.float64:
        # No 64-bit float bitcast compiles for the TPU: take the bit
        # pattern arithmetically (ops/segments.f64_bits_u32).
        from ytsaurus_tpu.ops.segments import f64_bits_u32
        hi, lo = f64_bits_u32(data)
        x = (hi.astype(jnp.uint64) << np.uint64(32)) | lo.astype(jnp.uint64)
    else:
        x = data.astype(jnp.uint64)
    x = x ^ (x >> np.uint64(33))
    x = x * np.uint64(0xFF51AFD7ED558CCD)
    x = x ^ (x >> np.uint64(33))
    return x


def _combine_u64(a: jax.Array, b: jax.Array) -> jax.Array:
    return (a ^ b) * np.uint64(0x9E3779B97F4A7C15) + (a << np.uint64(6))
