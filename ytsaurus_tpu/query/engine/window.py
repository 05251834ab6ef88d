"""Window-function execution: segmented prefix scans over partition-sorted
planes.

The reference query engine has no window functions; databases that JIT
them stream each partition through a stateful per-row loop.  The TPU lowering instead turns the whole stage
into the backbone's strongest primitive — ONE u32 packed sort bringing
equal PARTITION BY keys adjacent (ordered by the ORDER BY spec inside
each partition), then every window item is a segmented prefix scan,
shifted gather, or scan-difference over the sorted planes:

  row_number        position scan (iota - segment start index)
  rank              peer-boundary running max
  dense_rank        segmented cumsum of peer boundaries
  lag / lead        within-segment shifted gather
  first/last_value  gather at the frame boundary row
  sum/count/avg     inclusive segmented cumsum, ROWS frame = P[hi] - P[lo-1]
  min / max         prefix/suffix scans, or a doubling-table range query
                    for two-sided bounded frames

Results scatter back to the original row order through the inverse
permutation, so the stage ADDS columns without moving rows — filter,
ORDER BY and projection downstream see the input rowset unchanged.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.errors import EErrorCode, YtError
from ytsaurus_tpu.ops.segments import (
    packed_sort_indices,
    prefix_scan,
    segment_end_index,
    segment_position,
    segment_range_extreme,
    segment_scan,
    segment_shift,
    segment_start_index,
    segment_suffix_scan,
)
from ytsaurus_tpu.query import ir
from ytsaurus_tpu.query.engine.expr import (
    ColumnBinding,
    EmitContext,
    ExprBinder,
    _gather_binding,
    _merge_vocabs,
    _pad_np,
    _remap_table,
    _vocab_bucket,
)
from ytsaurus_tpu.query.engine.lowering import _order_key_bits
from ytsaurus_tpu.schema import EValueType, device_dtype


class WindowStage:
    """Host-bound window stage for one chunk: binds partition/order/item
    expressions (appending vocabulary tables to the shared bindings
    list), exposes the slot column bindings for downstream reference
    resolution, and emits the traced computation."""

    def __init__(self, window: ir.WindowClause, binder: ExprBinder):
        self.window = window
        self.partition_b = [binder.bind(item.expr)
                            for item in window.partition_items]
        self.order_b = [(binder.bind(oi.expr), oi.descending)
                        for oi in window.order_items]
        self.items_b = []
        for item in window.items:
            arg = binder.bind(item.argument) \
                if item.argument is not None else None
            dflt = binder.bind(item.default) \
                if item.default is not None else None
            # String lag/lead with a string default: both planes must
            # land in ONE code space — merge vocabularies host-side and
            # remap through bound tables (the if/if_null pattern).
            vocab = None
            arg_gather = dflt_gather = None
            if item.type is EValueType.string:
                vocab = arg.vocab
                if dflt is not None and dflt.type is EValueType.string:
                    vocab = _merge_vocabs(arg.vocab, dflt.vocab)
                    for side in (arg, dflt):
                        side_vocab = side.vocab if side.vocab is not None \
                            else np.array([], dtype=object)
                        table = _remap_table(side_vocab, vocab)
                        slot = binder.ctx.add(jnp.asarray(_pad_np(
                            table, _vocab_bucket(max(len(side_vocab), 1)),
                            0)))
                        if side is arg:
                            arg_gather = _gather_binding(slot)
                        else:
                            dflt_gather = _gather_binding(slot)
            self.items_b.append((item, arg, dflt, vocab,
                                 arg_gather, dflt_gather))

    def slot_bindings(self) -> dict[str, ColumnBinding]:
        return {item.name: ColumnBinding(type=item.type, vocab=vocab)
                for item, _, _, vocab, _, _ in self.items_b}

    # -- trace-time ------------------------------------------------------------

    def emit(self, ctx: EmitContext, mask: jax.Array
             ) -> dict[str, tuple[jax.Array, jax.Array]]:
        """Compute every window column; returns slot planes in the
        ORIGINAL row order (validity already restricted to `mask`)."""
        n = ctx.capacity
        iota = jnp.arange(n, dtype=jnp.int32)

        # One packed sort: masked-last, then partition keys (ascending,
        # groups only need adjacency), then the ORDER BY spec.
        sort_items = [((~mask), jnp.ones_like(mask), False, 1)]
        part_planes = [b.emit(ctx) for b in self.partition_b]
        for b, (d, v) in zip(self.partition_b, part_planes):
            sort_items.append((d, v, False, _order_key_bits(b)))
        order_planes = [b.emit(ctx) for b, _ in self.order_b]
        for (b, descending), (d, v) in zip(self.order_b, order_planes):
            sort_items.append((d, v, descending, _order_key_bits(b)))
        order_idx = packed_sort_indices(sort_items)
        inv = jnp.zeros(n, dtype=jnp.int32).at[order_idx].set(iota)

        s_mask = mask[order_idx]
        # Segment starts: row 0, any partition-key change, and the
        # unmasked→masked transition (so the trailing masked rows never
        # extend a real partition's frame range).
        starts = jnp.zeros(n, dtype=bool).at[0].set(True)
        starts = starts | (s_mask != jnp.roll(s_mask, 1))
        for d, v in part_planes:
            sd, sv = d[order_idx], v[order_idx]
            starts = starts | (sd != jnp.roll(sd, 1)) | \
                (sv != jnp.roll(sv, 1))
        starts = starts.at[0].set(True)
        # Peer boundaries: a new segment or any ORDER BY key change.
        peers = starts
        for (b, _), (d, v) in zip(self.order_b, order_planes):
            sd, sv = d[order_idx], v[order_idx]
            peers = peers | (sd != jnp.roll(sd, 1)) | \
                (sv != jnp.roll(sv, 1))
        peers = peers.at[0].set(True)

        seg_lo = segment_start_index(starts)
        seg_hi = segment_end_index(starts)
        # Last row of each ORDER-BY peer group (peers is itself a starts
        # plane over the peer segmentation, and partition starts always
        # open a peer group, so peer ends never cross partitions).  Used
        # by the standard default frame (RANGE-peers end).
        peer_end = None
        if any(item.frame[2] == "peer" for item, *_ in self.items_b):
            peer_end = segment_end_index(peers)

        out: dict[str, tuple[jax.Array, jax.Array]] = {}
        for item, arg, dflt, vocab, arg_gather, dflt_gather in self.items_b:
            data, valid = self._emit_item(
                ctx, item, arg, dflt, arg_gather, dflt_gather,
                order_idx, s_mask, starts, peers, seg_lo, seg_hi,
                peer_end, iota)
            out[item.name] = (data[inv], valid[inv] & mask)
        return out

    def _frame_range(self, item: ir.WindowItem, seg_lo, seg_hi, peer_end,
                     iota):
        lo_kind, lo_off, hi_kind, hi_off = item.frame
        lo = seg_lo if lo_kind == "unbounded" else \
            jnp.maximum(seg_lo, iota + lo_off)
        if hi_kind == "unbounded":
            hi = seg_hi
        elif hi_kind == "peer":
            hi = peer_end
        else:
            hi = jnp.minimum(seg_hi, iota + hi_off)
        return lo, hi, lo > hi

    def _emit_item(self, ctx, item, arg, dflt, arg_gather, dflt_gather,
                   order_idx, s_mask, starts, peers, seg_lo, seg_hi,
                   peer_end, iota):
        fn = item.function
        n = s_mask.shape[0]

        if fn == "row_number":
            pos = segment_position(starts)
            return (pos + 1).astype(jnp.int64), jnp.ones(n, dtype=bool)
        if fn == "rank":
            peer_start = prefix_scan(
                jnp.maximum, jnp.where(peers, iota, jnp.zeros_like(iota)))
            return (peer_start - seg_lo + 1).astype(jnp.int64), \
                jnp.ones(n, dtype=bool)
        if fn == "dense_rank":
            dr = segment_scan("sum", peers.astype(jnp.int64), starts)
            return dr, jnp.ones(n, dtype=bool)

        a_data, a_valid = arg.emit(ctx)
        a_data = a_data[order_idx]
        a_valid = a_valid[order_idx] & s_mask
        if arg_gather is not None:
            a_data = arg_gather(ctx, a_data)

        if fn in ("lag", "lead"):
            shift = item.offset if fn == "lag" else -item.offset
            sh_d, sh_v, in_seg = segment_shift(a_data, a_valid, starts,
                                               shift, seg_lo=seg_lo,
                                               seg_hi=seg_hi)
            if dflt is not None:
                d_data, d_valid = dflt.emit(ctx)
                d_data = d_data[order_idx]
                d_valid = d_valid[order_idx]
                if dflt_gather is not None:
                    d_data = dflt_gather(ctx, d_data)
                sh_d, d_data = _promote_window_pair(sh_d, d_data)
                data = jnp.where(in_seg, sh_d, d_data)
                valid = jnp.where(in_seg, sh_v, d_valid)
            else:
                data = sh_d
                valid = sh_v & in_seg
            return data, valid

        lo, hi, empty = self._frame_range(item, seg_lo, seg_hi, peer_end,
                                          iota)
        lo_c = jnp.clip(lo, 0, n - 1)
        hi_c = jnp.clip(hi, 0, n - 1)

        if fn == "first_value":
            return a_data[lo_c], a_valid[lo_c] & ~empty
        if fn == "last_value":
            return a_data[hi_c], a_valid[hi_c] & ~empty

        # Framed aggregates: count of contributing rows first (validity
        # for every other aggregate, the result for count itself).
        cnt_scan = segment_scan("sum", a_valid.astype(jnp.int64), starts)
        cnt = cnt_scan[hi_c] - jnp.where(
            lo > seg_lo, cnt_scan[jnp.clip(lo - 1, 0, n - 1)],
            jnp.zeros_like(cnt_scan))
        cnt = jnp.where(empty, jnp.zeros_like(cnt), cnt)
        if fn == "count":
            return cnt, jnp.ones(n, dtype=bool)

        if fn in ("sum", "avg"):
            acc_dtype = jnp.float64 if fn == "avg" else \
                device_dtype(item.type)
            contrib = jnp.where(a_valid, a_data.astype(acc_dtype),
                                jnp.zeros(n, dtype=acc_dtype))
            p = segment_scan("sum", contrib, starts)
            total = p[hi_c] - jnp.where(
                lo > seg_lo, p[jnp.clip(lo - 1, 0, n - 1)],
                jnp.zeros_like(p))
            if fn == "avg":
                total = total / jnp.maximum(cnt, 1)
            return total, cnt > 0

        if fn in ("min", "max"):
            lo_kind, _, hi_kind, _ = item.frame
            if lo_kind == "unbounded" and hi_kind == "unbounded":
                scan = segment_scan(fn, _neutralized(a_data, a_valid, fn),
                                    starts)
                data = scan[seg_hi]
            elif lo_kind == "unbounded":
                scan = segment_scan(fn, _neutralized(a_data, a_valid, fn),
                                    starts)
                data = scan[hi_c]
            elif hi_kind == "unbounded":
                scan = segment_suffix_scan(
                    fn, _neutralized(a_data, a_valid, fn), starts)
                data = scan[lo_c]
            else:
                _, lo_off, _, hi_off = item.frame
                data = segment_range_extreme(
                    fn, a_data, a_valid, lo_c, jnp.maximum(hi_c, lo_c),
                    max_width=hi_off - lo_off + 1)
            if item.type is EValueType.boolean:
                data = data.astype(jnp.bool_)
            return data, cnt > 0

        raise YtError(f"Window function {fn!r} has no lowering",
                      code=EErrorCode.QueryUnsupported)


def _neutralized(data: jax.Array, valid: jax.Array, fn: str) -> jax.Array:
    from ytsaurus_tpu.ops.segments import _reduce_neutral
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    return jnp.where(valid, data, _reduce_neutral(data.dtype, fn))


def _promote_window_pair(a: jax.Array, b: jax.Array):
    if a.dtype == b.dtype:
        return a, b
    target = jnp.promote_types(a.dtype, b.dtype)
    return a.astype(target), b.astype(target)
