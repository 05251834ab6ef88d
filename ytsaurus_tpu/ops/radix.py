"""Tiled stable LSD radix sort for large rowsets.

Why: one-pass variadic sort (jax.lax.sort over all key words at once)
drags every operand through an O(n log^2 n) compare-exchange network whose
depth grows with the FULL row count — past ~8M rows on v5e the warm-up
never completes (the round-2 "sort cliff").  The TPU-shaped replacement
keeps every sort network TILE-sized and does the global movement with
histogram arithmetic:

  per 8-bit digit pass:
    1. batched per-tile stable sort by (digit, position) — ONE u32
       composite key, network depth log^2(TILE) not log^2(n), vectorized
       across tiles on the VPU;
    2. per-tile bin offsets via batched searchsorted over the sorted
       digits (a (tiles, 256) table — tiny);
    3. global stable rank for every output slot from exclusive cumsums of
       that table, inverted with a vectorized binary search (log(tiles)
       gather sweeps over the cumulative table);
    4. one contiguous-run gather moves the payload planes.

No data-dependent shapes, no giant network, no scatter (TPU scatters with
duplicate indices serialize; the one permutation scatter variant is kept
behind engine="scatter" for measurement, using unique_indices=True).

Reference analog: the Sort operation's partition tree + k-way heap merge
(yt/yt/server/controller_agent/controllers/sort_controller.cpp:459,
yt/yt/ytlib/table_client/partition_sort_reader.h:20) — re-expressed as
counting-rank movement instead of comparison merges, which is what a
batch-synchronous vector machine wants.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

# Tile size for the per-tile sort networks: the composite key is
# (digit << LOG_TILE) | position, so RADIX_BITS + LOG_TILE must be <= 32.
RADIX_TILE = int(os.environ.get("YT_TPU_RADIX_TILE", 2048))
RADIX_BITS = 8
_B = 1 << RADIX_BITS


def _exclusive(x, axis):
    return jnp.cumsum(x, axis=axis) - x


@jax.named_scope("radix.pass")       # the name its ops carry in a trace
def radix_pass(digit: jax.Array, payloads: list[jax.Array],
               engine: str = "gather") -> list[jax.Array]:
    """One stable ascending partition by `digit` (u32 values < 256).

    digit and each payload are (N,) with N % RADIX_TILE == 0; returns the
    payloads reordered by a stable counting sort on digit."""
    n = digit.shape[0]
    if n == 0:
        return list(payloads)
    tile = min(RADIX_TILE, n)
    nt = n // tile
    log_tile = tile.bit_length() - 1
    assert tile == 1 << log_tile and n == nt * tile
    assert RADIX_BITS + log_tile <= 32

    d2 = digit.reshape(nt, tile).astype(jnp.uint32)
    pos = jnp.arange(tile, dtype=jnp.uint32)
    composite = (d2 << np.uint32(log_tile)) | pos[None, :]
    operands = (composite,) + tuple(p.reshape(nt, tile) for p in payloads)
    # The composite key is unique within a tile, so a non-stable sort is
    # stable by construction (and cheaper).
    sorted_ops = jax.lax.sort(operands, dimension=1, num_keys=1,
                              is_stable=False)
    d_sorted = (sorted_ops[0] >> np.uint32(log_tile)).astype(jnp.int32)
    pay_sorted = [p.reshape(n) for p in sorted_ops[1:]]

    # local_start[t, b] = first position of digit b inside tile t.
    bins = jnp.arange(_B, dtype=jnp.int32)
    local_start = jax.vmap(
        lambda row: jnp.searchsorted(row, bins, side="left"))(d_sorted)
    local_start = local_start.astype(jnp.int32)                 # (nt, B)
    ends = jnp.concatenate(
        [local_start[:, 1:], jnp.full((nt, 1), tile, jnp.int32)], axis=1)
    counts = ends - local_start                                 # (nt, B)

    per_bin = counts.sum(axis=0)                                # (B,)
    bin_start = _exclusive(per_bin, 0)                          # (B,)
    tile_excl = _exclusive(counts, 0)                           # (nt, B)

    if engine == "scatter":
        # dest of tile t's bin-b run = bin_start[b] + rows of b in earlier
        # tiles; every element's destination is unique (a permutation).
        run_start = bin_start[None, :] + tile_excl              # (nt, B)
        rs = jnp.take_along_axis(run_start, d_sorted, axis=1)
        ls = jnp.take_along_axis(local_start, d_sorted, axis=1)
        dest = (rs + (pos[None, :].astype(jnp.int32) - ls)).reshape(n)
        return [jnp.zeros(n, p.dtype).at[dest].set(
                    p, unique_indices=True, mode="drop")
                for p in pay_sorted]

    # engine == "gather": invert the permutation by rank arithmetic.
    # For output slot j: which bin, which tile, which local row?
    j = jnp.arange(n, dtype=jnp.int32)
    b = jnp.clip(jnp.searchsorted(bin_start, j, side="right") - 1, 0,
                 _B - 1).astype(jnp.int32)
    k = j - bin_start[b]                       # rank of j within its bin
    # Vectorized binary search over the per-bin inclusive tile cumsums:
    # t(j) = first tile whose inclusive count exceeds k.
    ccounts = (tile_excl + counts).T.reshape(-1)     # (B*nt,) row-major b
    lo = jnp.zeros(n, jnp.int32)
    hi = jnp.full(n, nt, jnp.int32)
    for _ in range(max(nt.bit_length(), 1)):
        mid = (lo + hi) >> 1
        go_right = ccounts[b * nt + jnp.minimum(mid, nt - 1)] <= k
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    t = jnp.clip(lo, 0, nt - 1)
    prev = jnp.where(t > 0, ccounts[b * nt + jnp.maximum(t - 1, 0)], 0)
    r = k - prev                               # rank within tile t's run
    src = t * tile + local_start.reshape(-1)[t * _B + b] + r
    return [p[src] for p in pay_sorted]


def _pad_to_tile(x: jax.Array, n_pad: int, fill) -> jax.Array:
    if n_pad == 0:
        return x
    return jnp.concatenate([x, jnp.full(n_pad, fill, x.dtype)])


def radix_argsort_u32(words: list[jax.Array],
                      word_bits: "list[int] | None" = None,
                      engine: str = "gather") -> jax.Array:
    """Stable ascending argsort over u32 key words (major word first) via
    LSD radix passes.  `word_bits[k]` bounds the significant LOW bits of
    word k (higher bits must be zero) — digit passes above the bound are
    skipped, so a packed 12-bit key costs 2 byte passes, not 4.

    engine: "gather" | "scatter" (ops above).

    Pad rows (to the tile multiple) carry all-ones keys and sort last;
    ties against real all-ones rows resolve to the real rows first by
    stability (pad payload indices are appended after)."""
    n = words[0].shape[0]
    if n == 0:
        # A forced engine must not die on an empty rowset (tile math
        # degenerates); the identity permutation is the sorted order.
        return jnp.arange(0, dtype=jnp.uint32)
    if word_bits is None:
        word_bits = [32] * len(words)
    pass_bits = RADIX_BITS
    tile = min(RADIX_TILE, 1 << max(n - 1, 1).bit_length())
    pass_fn = lambda d, p: radix_pass(d, p, engine=engine)  # noqa: E731
    padded = ((n + tile - 1) // tile) * tile
    n_pad = padded - n
    perm = jnp.arange(padded, dtype=jnp.uint32)
    mask = np.uint32((1 << pass_bits) - 1)
    for word, bits in zip(reversed(words), reversed(word_bits)):
        if bits <= 0:
            continue
        # Pad keys sort last: all-ones is the maximum in every pass.
        fill = np.uint32((1 << min(bits, 32)) - 1)
        wpad = _pad_to_tile(word.astype(jnp.uint32), n_pad, fill)
        for shift in range(0, min(bits, 32), pass_bits):
            digit = (jnp.take(wpad, perm) >> np.uint32(shift)) & mask
            (perm,) = pass_fn(digit, [perm])
    return perm[:n]
