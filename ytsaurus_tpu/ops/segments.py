"""Segmented reductions and sort-key helpers used by GROUP BY / ORDER BY.

These are the XLA analogs of the reference's cg_routines hot loops
(library/query/engine/cg_routines/registry.cpp: GroupOpHelper, OrderOpHelper):
instead of a per-row JIT'd hash-table loop, grouping is lex-sort + segment
reduction over static-capacity planes — batch-friendly for the VPU/MXU.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.schema import EValueType


def sort_key_planes(data: jax.Array, valid: jax.Array,
                    descending: bool = False) -> list[jax.Array]:
    """Produce ascending-order integer/float planes encoding (null, value).

    YT comparison semantics: null sorts before any value.  For descending
    order the value plane is complemented so a single ascending lexsort works.
    Returns [value_plane, null_plane] ordered minor→major for jnp.lexsort.
    """
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    if descending:
        if jnp.issubdtype(data.dtype, jnp.integer):
            value = ~data   # order-reversing for signed and unsigned alike
        else:
            value = -data
        # Nulls sort before any value; descending reverses that → nulls last:
        # key 0 for valid rows, 1 for nulls.
        null_key = (~valid).astype(jnp.int8)
    else:
        value = data
        # Ascending: nulls first → key 0 for null, 1 for valid.
        null_key = valid.astype(jnp.int8)
    value = jnp.where(valid, value, jnp.zeros_like(value))
    return [value, null_key]


def lexsort_indices(key_planes: list[jax.Array]) -> jax.Array:
    """Stable ascending argsort over multiple key planes (major key LAST).

    (jnp.lexsort already lowers to ONE variadic lax.sort with a composite
    comparator in current JAX — do not hand-roll it.)"""
    return jnp.lexsort(key_planes)


def prefix_scan(combine, elems):
    """Inclusive prefix scan of 1-D planes (a pytree) under an associative
    `combine` — the one scan primitive every segmented scan here rides.

    The log-step shifted form (Hillis-Steele: step d combines each row
    with the row d before it): n log n work, but every step is one fused
    elementwise pass over whole planes.  libtpu takes MINUTES to compile
    `lax.associative_scan`'s strided slice/interleave ladder (compiled
    for a described v5e, libtpu 0.0.34: one 1M-row segmented sum 161 s
    and 41 MB of code, `jnp.cumsum` 96 s; the shifted form 6 s), and a
    served query carries several.  One form on every backend, so the
    tests run what the chip runs."""
    n = jax.tree_util.tree_leaves(elems)[0].shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    d = 1
    while d < n:
        shifted = jax.tree_util.tree_map(
            lambda x: jnp.roll(x, d), elems)
        combined = combine(shifted, elems)
        keep = iota >= d
        elems = jax.tree_util.tree_map(
            lambda c, x: jnp.where(keep, c, x),
            combined, elems)
        d *= 2
    return elems


def segment_boundaries(sorted_keys: list[tuple[jax.Array, jax.Array]],
                       in_mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Given key (data, valid) planes already in sorted order plus the row
    mask (also sorted so that masked-out rows are at the end), return
    (segment_ids, num_segments).  Masked-out rows get segment id
    == num_real_segments (they land in trailing garbage segments)."""
    cap = in_mask.shape[0]
    change = jnp.zeros(cap, dtype=bool)
    for data, valid in sorted_keys:
        prev_data = jnp.roll(data, 1)
        prev_valid = jnp.roll(valid, 1)
        differs = (data != prev_data) | (valid != prev_valid)
        change = change | differs
    change = change.at[0].set(False)
    # New segment whenever keys change, restricted to in-mask rows.
    boundary = change & in_mask
    seg = prefix_scan(jnp.add, boundary.astype(jnp.int64))
    num_segments = jnp.where(jnp.any(in_mask), seg[-1] + 1, 0)
    # Rows outside the mask go to a trailing segment.
    seg = jnp.where(in_mask, seg, num_segments)
    return seg, num_segments


# At or below this many segments a reduce is a masked broadcast-reduction:
# XLA fuses the (S, N) compare+select into the reduce (bandwidth-bound VPU
# work, its cost linear in S).  Above it: one sort by segment id, then
# _sorted_segment_reduce.  Never a scatter with DUPLICATE indices (a
# scatter-add of rows into their segments): that is what serializes on a
# TPU.  The sorted reduce's one scatter has pairwise distinct indices and
# says so (unique_indices=True), as ops/radix.py's does.
_DENSE_SEGMENT_LIMIT = 256


def _dense_segment_reduce(function: str, data: jax.Array, seg_ids: jax.Array,
                          num_segments: int):
    sids = jnp.arange(num_segments, dtype=seg_ids.dtype)

    if function == "sum":
        def one(s):
            return jnp.where(seg_ids == s, data, jnp.zeros_like(data)).sum()
    elif function == "min":
        neutral = _reduce_neutral(data.dtype, "min")
        def one(s):
            return jnp.where(seg_ids == s, data, neutral).min()
    elif function == "max":
        neutral = _reduce_neutral(data.dtype, "max")
        def one(s):
            return jnp.where(seg_ids == s, data, neutral).max()
    else:
        raise ValueError(function)
    return jax.vmap(one)(sids)


def _sorted_segment_reduce(function: str, data: jax.Array,
                           seg_ids: jax.Array, num_segments: int):
    """Segment reduce for NONDECREASING, non-negative seg_ids: a segmented
    prefix scan (the combine resets at segment starts, so float sums keep
    per-segment precision) leaves each segment's value at its LAST row,
    and the start marks the scan already has say where that is: row i
    closes its segment where row i + 1 opens one.  One unique-index
    scatter places those rows' indices by segment id and one gather reads
    the scanned plane there.  No search: a search for every segment's
    rows is two row-sized gathers per step, 2 x 21 steps at 1,048,576
    rows (PERF.md section 6, PR 33).  Ids at or past num_segments (masked
    rows parked there) are dropped; a segment no row names reads the
    function's neutral."""
    cap = data.shape[0]
    starts = jnp.concatenate([
        jnp.ones(1, dtype=bool), seg_ids[1:] != seg_ids[:-1]])
    if function == "sum":
        combine_val = lambda a, b: a + b
    elif function == "min":
        combine_val = jnp.minimum
    elif function == "max":
        combine_val = jnp.maximum
    else:
        raise ValueError(function)

    def combine(x, y):
        xv, xf = x
        yv, yf = y
        return jnp.where(yf, yv, combine_val(xv, yv)), xf | yf

    scanned, _ = prefix_scan(combine, (data, starts))
    # Every plane is held to int32 (the package enables x64).  A row that
    # closes no kept segment goes to an out-of-range slot of its own, so
    # the indices are pairwise distinct and mode="drop" discards it.  This
    # depends on seg_ids and num_segments alone: the aggregates of one
    # group stage share it.
    iota = jnp.arange(cap, dtype=jnp.int32)
    ids = seg_ids.astype(jnp.int32)
    closes = jnp.concatenate([starts[1:], jnp.ones(1, dtype=bool)]) & \
        (ids < num_segments)
    last_row = jnp.full(num_segments, -1, dtype=jnp.int32).at[
        jnp.where(closes, ids, np.int32(num_segments) + iota)].set(
            iota, unique_indices=True, mode="drop")
    out = scanned[jnp.clip(last_row, 0, cap - 1)]
    if function == "sum":
        neutral = jnp.zeros((), dtype=data.dtype)
    else:
        neutral = _reduce_neutral(data.dtype, function)
    return jnp.where(last_row >= 0, out, neutral)


@jax.named_scope("segments.reduce")  # the name its ops carry in a trace
def _segment_reduce(function: str, data: jax.Array, seg_ids: jax.Array,
                    num_segments: int, assume_sorted: bool = False):
    if num_segments <= _DENSE_SEGMENT_LIMIT:
        return _dense_segment_reduce(function, data, seg_ids, num_segments)
    if assume_sorted:
        return _sorted_segment_reduce(function, data, seg_ids, num_segments)
    # Unsorted mid/high cardinality: one u32 sort by segment id, then the
    # segmented scan.  Hot paths pre-sort ONCE for all aggregates
    # (lowering's group stage) and take assume_sorted instead.
    order = stable_argsort_u32([seg_ids.astype(jnp.uint32)])
    return _sorted_segment_reduce(function, data[order], seg_ids[order],
                                  num_segments)


def presort_segments(seg_ids: jax.Array,
                     num_segments: int) -> "jax.Array | None":
    """Shared presort policy for multi-aggregate group stages: returns the
    row order to apply once (then pass assume_sorted=True for every
    aggregate), or None when the reduce needs no ordering — the dense
    broadcast path.  Keeping the dispatch HERE keeps it in lockstep with
    _segment_reduce's threshold."""
    if num_segments <= _DENSE_SEGMENT_LIMIT:
        return None
    return stable_argsort_u32([seg_ids.astype(jnp.uint32)])


def segment_aggregate(function: str, data: jax.Array, valid: jax.Array,
                      seg_ids: jax.Array, num_segments: int,
                      value_type: EValueType,
                      assume_sorted: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """Aggregate `data` per segment, skipping nulls. Returns (out, out_valid)
    planes of length num_segments (static capacity).  assume_sorted=True
    (nondecreasing seg_ids — the presorted and hash-grouped paths) spares
    the reduce its own sort above the dense limit."""
    contributes = valid
    count = _segment_reduce(
        "sum", contributes.astype(jnp.int64), seg_ids, num_segments,
        assume_sorted)
    any_valid = count > 0
    if function == "count":
        return count, jnp.ones_like(any_valid)
    if function == "sum":
        masked = jnp.where(contributes, data, jnp.zeros_like(data))
        out = _segment_reduce("sum", masked, seg_ids, num_segments,
                              assume_sorted)
        return out, any_valid
    if function == "min" or function == "max":
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int8)
        neutral = _reduce_neutral(data.dtype, function)
        masked = jnp.where(contributes, data, neutral)
        out = _segment_reduce(function, masked, seg_ids, num_segments,
                              assume_sorted)
        if value_type is EValueType.boolean:
            out = out.astype(jnp.bool_)
        return out, any_valid
    if function == "first":
        first_idx = _segment_first_index(contributes, seg_ids, num_segments,
                                         assume_sorted)
        return data[first_idx], any_valid
    raise ValueError(f"Unknown segment aggregate {function!r}")


def _reduce_neutral(dtype, function: str):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(np.inf if function == "min" else -np.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if function == "min" else info.min, dtype=dtype)


def _segment_first_index(eligible: jax.Array, seg_ids: jax.Array,
                         num_segments: int,
                         assume_sorted: bool = False) -> jax.Array:
    """First row index per segment among `eligible` rows (clipped sentinel
    when a segment has none — callers must mask validity separately)."""
    cap = eligible.shape[0]
    idx = jnp.where(eligible, jnp.arange(cap), cap - 1)
    first = _segment_reduce("min", idx, seg_ids, num_segments,
                            assume_sorted)
    return jnp.clip(first, 0, cap - 1)


def segment_arg_by(value_data: jax.Array, value_valid: jax.Array,
                   by_data: jax.Array, by_valid: jax.Array,
                   seg_ids: jax.Array, num_segments: int,
                   take_max: bool,
                   assume_sorted: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """Per segment: the value at the row whose `by` key is smallest/largest
    (argmin/argmax; rows with null or NaN `by` don't compete; ties take the
    first row)."""
    if by_data.dtype == jnp.bool_:
        by_data = by_data.astype(jnp.int8)
    competes = by_valid
    if jnp.issubdtype(by_data.dtype, jnp.floating):
        # NaN poisons the reduce AND never equals the extreme, which would
        # select an arbitrary row flagged valid.
        competes = competes & ~jnp.isnan(by_data)
    fn = "max" if take_max else "min"
    neutral = _reduce_neutral(by_data.dtype, fn)
    masked_by = jnp.where(competes, by_data, neutral)
    extreme = _segment_reduce(fn, masked_by, seg_ids, num_segments,
                              assume_sorted)
    winner = competes & (masked_by == extreme[seg_ids])
    first_idx = _segment_first_index(winner, seg_ids, num_segments,
                                     assume_sorted)
    any_competes = _segment_reduce(
        "sum", competes.astype(jnp.int64), seg_ids, num_segments,
        assume_sorted) > 0
    return value_data[first_idx], value_valid[first_idx] & any_competes


def segment_distinct_count(data: jax.Array, valid: jax.Array,
                           seg_ids: jax.Array, num_segments: int
                           ) -> tuple[jax.Array, jax.Array]:
    """Exact per-segment distinct count of `data` (nulls don't count).

    One extra lexsort by (segment, value): a row is "new" when its (segment,
    value) differs from the previous row's.  The reference's `cardinality`
    is an HLL approximation (library/query engine UDF); exact is affordable
    here because the sort is one fused device pass.
    """
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    value = jnp.where(valid, data, jnp.zeros_like(data))
    nan_flag = jnp.zeros(value.shape[0], dtype=jnp.int8)
    if jnp.issubdtype(value.dtype, jnp.floating):
        # Float equality pitfalls: NaN != NaN (every NaN would count) and
        # -0.0 == +0.0 bit-wise distinct.  Canonicalize: -0.0 → +0.0 via
        # `+ 0.0`; NaNs → +inf with a side flag so NaN stays distinct from a
        # real +inf.  (No bitcast: f64→i64 bitcasts don't lower on TPU X64.)
        is_nan = jnp.isnan(value)
        nan_flag = is_nan.astype(jnp.int8)
        value = jnp.where(is_nan, jnp.full_like(value, jnp.inf),
                          value + 0.0)
    flags_word = (valid.astype(jnp.uint32) << np.uint32(1)) | \
        nan_flag.astype(jnp.uint32)
    order = stable_argsort_u32(
        [seg_ids.astype(jnp.uint32), flags_word,
         *monotone_u32_words(value, jnp.ones_like(valid))])
    seg_s = seg_ids[order]
    val_s = value[order]
    valid_s = valid[order]
    nan_s = nan_flag[order]
    prev_seg = jnp.roll(seg_s, 1)
    prev_val = jnp.roll(val_s, 1)
    prev_valid = jnp.roll(valid_s, 1)
    prev_nan = jnp.roll(nan_s, 1)
    new_value = (seg_s != prev_seg) | (val_s != prev_val) | \
        (valid_s != prev_valid) | (nan_s != prev_nan)
    new_value = new_value.at[0].set(True)
    flags = (new_value & valid_s).astype(jnp.int64)
    # seg_s is the major sort key above, so it is nondecreasing.
    counts = _segment_reduce("sum", flags, seg_s, num_segments,
                             assume_sorted=True)
    return counts.astype(jnp.uint64), jnp.ones(num_segments, dtype=bool)


# --- segmented prefix scans (window-function backbone) ------------------------
#
# Window functions (query/engine/window.py) lower to these: ranking is a
# segmented position/peer scan, running aggregates are segmented inclusive
# scans, ROWS frames are scan differences (sum/count) or doubling-table
# range queries (min/max).  All operate on SEGMENT-SORTED planes (equal
# partition keys adjacent); `starts[i]` marks row i as the first of its
# segment (starts[0] must be True for a non-empty plane).


def _scan_combine(combine_val):
    """Segmented-scan monoid over (value, start_flag) pairs: the combine
    resets at segment starts (associative — the standard construction)."""
    def combine(x, y):
        xv, xf = x
        yv, yf = y
        return jnp.where(yf, yv, combine_val(xv, yv)), xf | yf
    return combine


def segment_scan(function: str, data: jax.Array,
                 starts: jax.Array) -> jax.Array:
    """Segmented INCLUSIVE prefix scan (sum/min/max), log-depth via
    prefix_scan — no scatters, the window primitive."""
    if function == "sum":
        combine_val = lambda a, b: a + b
    elif function == "min":
        combine_val = jnp.minimum
    elif function == "max":
        combine_val = jnp.maximum
    else:
        raise ValueError(f"Unknown scan function {function!r}")
    scanned, _ = prefix_scan(_scan_combine(combine_val), (data, starts))
    return scanned


def segment_suffix_scan(function: str, data: jax.Array,
                        starts: jax.Array) -> jax.Array:
    """Segmented inclusive SUFFIX scan (combine toward segment ends):
    reverse the plane, rebuild start flags from the forward ends, scan,
    reverse back."""
    n = data.shape[0]
    ends = jnp.concatenate([starts[1:], jnp.ones(1, dtype=bool)])
    return segment_scan(function, data[::-1], ends[::-1])[::-1]


def segment_start_index(starts: jax.Array) -> jax.Array:
    """Per row: index of its segment's FIRST row.  Running max of
    (starts ? i : 0) — segment starts arrive in increasing index order,
    so no reset is needed."""
    iota = jnp.arange(starts.shape[0], dtype=jnp.int32)
    return prefix_scan(
        jnp.maximum, jnp.where(starts, iota, jnp.zeros_like(iota)))


def segment_end_index(starts: jax.Array) -> jax.Array:
    """Per row: index of its segment's LAST row (reverse of
    segment_start_index over the mirrored plane)."""
    n = starts.shape[0]
    ends = jnp.concatenate([starts[1:], jnp.ones(1, dtype=bool)])
    iota = jnp.arange(n, dtype=jnp.int32)
    rev_start = prefix_scan(
        jnp.maximum, jnp.where(ends[::-1], iota, jnp.zeros_like(iota)))
    return (n - 1) - rev_start[::-1]


def segment_position(starts: jax.Array) -> jax.Array:
    """0-based row position within its segment (row_number() - 1)."""
    iota = jnp.arange(starts.shape[0], dtype=jnp.int32)
    return iota - segment_start_index(starts)


def segment_shift(data: jax.Array, valid: jax.Array, starts: jax.Array,
                  shift: int, seg_lo: "jax.Array | None" = None,
                  seg_hi: "jax.Array | None" = None
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Within-segment shifted gather: row i reads row i-shift (shift>0 =
    lag, shift<0 = lead).  Returns (data, valid, in_segment) — rows whose
    source falls outside their own segment get in_segment=False and the
    caller substitutes the default.  Callers that already hold the
    per-row segment bounds pass seg_lo/seg_hi to skip recomputing the
    two index scans."""
    n = data.shape[0]
    src = jnp.arange(n, dtype=jnp.int32) - shift
    if seg_lo is None:
        seg_lo = segment_start_index(starts)
    if seg_hi is None:
        seg_hi = segment_end_index(starts)
    in_seg = (src >= seg_lo) & (src <= seg_hi)
    src = jnp.clip(src, 0, n - 1)
    return data[src], valid[src], in_seg


def segment_range_extreme(function: str, data: jax.Array, valid: jax.Array,
                          lo: jax.Array, hi: jax.Array,
                          max_width: int) -> jax.Array:
    """Per-row min/max over rows [lo_i, hi_i] (a ROWS frame already
    clipped inside the row's segment; lo_i <= hi_i, hi_i - lo_i + 1 <=
    max_width).  Sparse-table range query: level p holds the reduce of
    the 2^p rows starting at each index (O(n log w) build, two gathers
    per query) — the log-depth sliding-window reduction bounded frames
    need where a prefix-scan difference only works for sums."""
    n = data.shape[0]
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    neutral = _reduce_neutral(data.dtype, function)
    combine = jnp.minimum if function == "min" else jnp.maximum
    base = jnp.where(valid, data, neutral)
    n_levels = max(int(max_width).bit_length() - 1, 1)   # floor(log2(w))
    levels = [base]
    for p in range(1, n_levels + 1):
        half = 1 << (p - 1)
        prev = levels[-1]
        shifted = jnp.concatenate(
            [prev[half:], jnp.full(half, neutral, dtype=prev.dtype)])
        levels.append(combine(prev, shifted))
    table = jnp.stack(levels)                    # (n_levels+1, n)
    length = (hi - lo + 1).astype(jnp.int32)
    # p = floor(log2(length)) via static comparisons (exact, no floats).
    p = jnp.zeros(n, dtype=jnp.int32)
    for k in range(1, n_levels + 1):
        p = p + (length >= (1 << k)).astype(jnp.int32)
    pow_p = (jnp.ones(n, dtype=jnp.int32) << p)
    flat = table.reshape(-1)
    left = flat[p * n + jnp.clip(lo, 0, n - 1)]
    right = flat[p * n + jnp.clip(hi - pow_p + 1, 0, n - 1)]
    return combine(left, right)


def compact_mask(mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Indices that move in-mask rows to the front (stable); plus count."""
    order = stable_argsort_u32([(~mask).astype(jnp.uint32)])
    return order, jnp.sum(mask.astype(jnp.int64))


# --- packed sort keys ---------------------------------------------------------
#
# lax.sort moves EVERY operand plane through the whole sort network, so the
# cost of a lexsort grows with plane count x plane width — and on TPU each
# 64-bit operand's comparator is EMULATED as u32 limb pairs inside every
# stage of the O(n log^2 n) network.  The planes from sort_key_planes
# (value + null per key, plus the row mask) are collapsed here into as few
# u32 words as possible via order-preserving bit packing: a two-dict-key
# ORDER BY + mask becomes ONE u32 operand; an i64 key becomes two native
# u32 words.  (The reference's row comparers JIT a composite comparator —
# row_comparer_api; on TPU the composite packed KEY is the idiomatic
# equivalent.)

_SIGN64 = np.uint64(1 << 63)
_SIGN32 = np.uint32(1 << 31)


_POW2_STEPS = tuple(1 << i for i in range(9, -1, -1))      # 512 .. 1


def f64_bits_u32(data: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(hi, lo) u32 words of an f64 plane's IEEE-754 bit pattern, by
    ARITHMETIC — no bitcast.  The TPU compiler stores f64 as 32-bit
    pairs and refuses every 64-bit float bitcast (f64→u64 and the
    f64→u32[N,2] split alike: "UNIMPLEMENTED: While rewriting computation
    to not contain X64 element types", libtpu 0.0.34, v5e), so the
    exponent is found by exact power-of-two normalization (20 fused
    compare/select/multiply steps) and the mantissa by one exact
    float→int conversion.  Same form on every backend.

    Two value classes are canonicalized, matching how the engine's own
    float arithmetic sees them: every NaN becomes the one quiet +NaN
    (sorts after +inf), and subnormals become a zero of their sign (XLA
    flushes them in arithmetic)."""
    x = data.astype(jnp.float64)
    a = jnp.abs(x)
    normal = (a >= 2.0 ** -1022) & (a < jnp.inf)
    neg = (x < 0) | ((x == 0) & (1.0 / x < 0))
    m = jnp.where(normal, a, 1.0)
    e = jnp.full(x.shape, 1023, dtype=jnp.int32)
    for k in _POW2_STEPS:
        big = m >= 2.0 ** k
        m = jnp.where(big, m * 2.0 ** -k, m)
        e = e + jnp.where(big, k, 0)
    for k in _POW2_STEPS:
        small = m < 2.0 ** (1 - k)
        m = jnp.where(small, m * 2.0 ** k, m)
        e = e - jnp.where(small, k, 0)
    # m is now in [1, 2): (m - 1) * 2^52 is the exact 52-bit mantissa.
    bits = (e.astype(jnp.int64) << 52) | \
        ((m - 1.0) * 2.0 ** 52).astype(jnp.int64)
    special = jnp.where(a == jnp.inf, 0x7FF0 << 48,
                        jnp.where(a != a, 0x7FF8 << 48, 0))
    bits = jnp.where(normal, bits, special)
    hi = (bits >> 32).astype(jnp.uint32) | \
        (neg.astype(jnp.uint32) << np.uint32(31))
    return hi, bits.astype(jnp.uint32)


def monotone_u32_words(data: jax.Array,
                       valid: jax.Array) -> list[jax.Array]:
    """Order-preserving encoding as u32 WORDS, major first.

    The device sort's comparator cost is per-operand-word; TPU compares
    u32 natively but emulates u64 as limb pairs INSIDE every comparator
    of the O(n log^2 n) sort network.  Encoding once into u32 words moves
    the limb split out of the network: 64-bit types cost one elementwise
    decomposition pass, then every comparator is native."""
    if data.dtype == jnp.bool_:
        words = [data.astype(jnp.uint32)]
    elif data.dtype == jnp.float32:
        bits = jax.lax.bitcast_convert_type(data, jnp.uint32)
        sign = (bits >> np.uint32(31)).astype(bool)
        words = [jnp.where(sign, ~bits, bits | _SIGN32)]
    elif jnp.issubdtype(data.dtype, jnp.floating):
        hi, lo = f64_bits_u32(data)
        sign = (hi >> np.uint32(31)).astype(bool)
        words = [jnp.where(sign, ~hi, hi | _SIGN32),
                 jnp.where(sign, ~lo, lo)]
    elif data.dtype in (jnp.int32, jnp.int16, jnp.int8):
        words = [data.astype(jnp.int32).astype(jnp.uint32) ^ _SIGN32]
    elif data.dtype in (jnp.uint32, jnp.uint16, jnp.uint8):
        words = [data.astype(jnp.uint32)]
    elif jnp.issubdtype(data.dtype, jnp.unsignedinteger):
        x = data.astype(jnp.uint64)
        words = [(x >> np.uint64(32)).astype(jnp.uint32),
                 x.astype(jnp.uint32)]
    else:
        x = data.astype(jnp.int64).astype(jnp.uint64) ^ _SIGN64
        words = [(x >> np.uint64(32)).astype(jnp.uint32),
                 x.astype(jnp.uint32)]
    zero = jnp.zeros((), jnp.uint32)
    return [jnp.where(valid, w, zero) for w in words]


def pack_key_planes_bits(items) -> tuple[list[jax.Array], list[int]]:
    """items: (data, valid, descending, value_bits) MAJOR key first.

    value_bits <= 31 asserts the encoded value fits [0, 2^bits) AND
    leaves room for its null bit in one u32 word (dictionary codes,
    booleans, small ints); anything wider goes full-width via
    monotone_u32_words.  Each field carries a null bit above its value
    (ascending: null sorts first; descending: null sorts last — YT
    comparator semantics).  Returns (u32 planes major-first, significant
    LOW bits per plane): the last word is shifted down so its unused bits
    sit HIGH and zero, letting the radix engine skip whole byte passes
    (a 12-bit packed key costs 2 passes, not 4).  TPU compares u32
    natively, so no sort path ever touches an emulated 64-bit
    comparator."""
    words: list[jax.Array] = []
    bits_left = 0

    def push(plane: jax.Array, width: int) -> None:
        nonlocal bits_left
        if width > bits_left:
            words.append(jnp.zeros_like(plane))
            bits_left = 32
        bits_left -= width
        words[-1] = words[-1] | (plane << np.uint32(bits_left))

    for data, valid, descending, value_bits in items:
        null_plane = ((~valid) if descending else valid).astype(jnp.uint32)
        if value_bits > 31:        # 32-bit value + null bit exceed one word
            value_words = monotone_u32_words(data, valid)
            if descending:
                value_words = [jnp.where(valid, ~w, jnp.zeros_like(w))
                               for w in value_words]
            push(null_plane, 1)
            for w in value_words:      # full words, less significant
                push(w, 32)
        else:
            enc = data.astype(jnp.uint32) & np.uint32(
                (1 << value_bits) - 1)
            if descending:
                enc = np.uint32((1 << value_bits) - 1) - enc
            enc = jnp.where(valid, enc, jnp.zeros_like(enc))
            push((null_plane << np.uint32(value_bits)) | enc,
                 value_bits + 1)
    sig = [32] * len(words)
    if words and bits_left:
        # Unused bits of the final word move from LOW to HIGH (zeros):
        # relative order is unchanged, and byte passes above the
        # significant width can be skipped.
        words[-1] = words[-1] >> np.uint32(bits_left)
        sig[-1] = 32 - bits_left
    return words, sig


# Above this row count, sorts leave the single-pass network (which
# re-evaluates the composite comparator inside every compare-exchange of
# an O(n log^2 n) network whose depth grows with the FULL row count) for
# the tiled radix engine.
LSD_SORT_THRESHOLD = 8 * 1024 * 1024


def stable_argsort_u32(words: list[jax.Array],
                       word_bits: "list[int] | None" = None) -> jax.Array:
    """Stable ascending argsort over u32 key words (major first); the
    payload rides as a u32 iota so no 64-bit plane enters the sort.

    word_bits[k] (optional) bounds the significant LOW bits of word k —
    the radix engine skips byte passes above the bound.

    Engine dispatch (YT_TPU_SORT_ENGINE = auto | network | radix, read
    at trace time):
      network — one variadic lax.sort; `auto` below the ~8M network cliff.
      radix   — tiled 8-bit LSD counting sort (ops/radix.py): per-TILE
                sort networks + histogram rank movement; depth never
                grows with n.  `auto` past LSD_SORT_THRESHOLD.

    Unknown engine names raise (a typo must not silently run the
    one-pass network into the very cliff the radix engine exists to
    avoid).
    """
    n = words[0].shape[0]
    engine = os.environ.get("YT_TPU_SORT_ENGINE", "auto")
    if engine == "auto":
        # The network's comparator cost grows with operand count too
        # (round-1 observation: full multi-plane lexsorts collapse past
        # ~4M rows), so the cliff threshold scales down with word count.
        effective = min(LSD_SORT_THRESHOLD,
                        2 * LSD_SORT_THRESHOLD // max(len(words), 1))
        engine = "network" if n <= effective else "radix"
    if engine == "radix":
        from ytsaurus_tpu.ops.radix import radix_argsort_u32
        return radix_argsort_u32(words, word_bits)
    if engine != "network":
        raise ValueError(f"unknown YT_TPU_SORT_ENGINE {engine!r}")
    iota = jnp.arange(n, dtype=jnp.uint32)
    out = jax.lax.sort((*words, iota), num_keys=len(words),
                       is_stable=True)
    return out[-1]


def packed_sort_indices(items) -> jax.Array:
    """Stable ascending argsort over packed key fields (major first)."""
    words, bits = pack_key_planes_bits(items)
    return stable_argsort_u32(words, word_bits=bits)


# --- exact grouping order -----------------------------------------------------

def hash_group_order(key_planes, mask) -> jax.Array:
    """Row ordering that makes equal group keys adjacent, masked rows
    last, using the EXACT order-preserving key encoding.

    History: rounds 1-2 ordered rows by a 128-bit hash of the key planes
    (cheap fixed operand count, but a full double-word collision could
    silently merge or fragment a group).  The tiled radix engine makes
    the exact encoding the cheaper path as well for typical key shapes —
    one int64 key is 9 byte passes versus the hash's 16 — so group
    identity no longer rides on any hash bits at all: the analog of
    TGroupByClosure's exact hash table semantics
    (yt/yt/library/query/engine/cg_routines/registry.cpp:1230), reached
    by counting-sort adjacency instead of open addressing.

    Encoding: word0 packs [masked-out bit (most significant) | one
    validity bit per key], then each key contributes its full monotone
    u32 words.  Invalid values are zeroed by monotone_u32_words, so the
    validity bit alone distinguishes NULL from literal zero."""
    n = mask.shape[0]
    words: list[jax.Array] = []
    bits: list[int] = []
    flags = (~mask).astype(jnp.uint32)
    nflag = 1
    for data, valid in key_planes:
        if nflag == 32:            # >31 keys: overflow into another word
            words.append(flags)
            bits.append(nflag)
            flags = jnp.zeros(n, dtype=jnp.uint32)
            nflag = 0
        flags = (flags << np.uint32(1)) | valid.astype(jnp.uint32)
        nflag += 1
    words.append(flags)
    bits.append(nflag)
    for data, valid in key_planes:
        vw = monotone_u32_words(data, valid)
        words.extend(vw)
        bits.extend([32] * len(vw))
    return stable_argsort_u32(words, word_bits=bits)
