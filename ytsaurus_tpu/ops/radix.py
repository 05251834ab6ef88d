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
    2. per-tile digit counts as a small matrix product per tile (a
       (tiles, 256) table — tiny), and from its exclusive sums where
       every (tile, bin) run of the tile-sorted rows starts and where in
       the output it goes;
    3. the tile-sorted rows are those tiles x 256 runs laid end to end,
       and inside a run (output slot - row) is one constant: each run's
       first row is marked with the change of that constant (one
       scatter-add of tiles x 256 sorted indices, an eighth of n at the
       default tile) and a prefix sum of the marks gives every row its
       output slot;
    4. one unique-index scatter per payload plane moves the rows (a
       permutation: no duplicate index, which is what serializes a TPU
       scatter; on the v5e it costs half a row-sized gather).

No data-dependent shapes, no giant network, and no per-row search: a
search costs a row-sized gather per step, and row-sized gathers were
what a pass was made of (PERF.md section 6, PR 31).

Reference analog: the Sort operation's partition tree + k-way heap merge
(yt/yt/server/controller_agent/controllers/sort_controller.cpp:459,
yt/yt/ytlib/table_client/partition_sort_reader.h:20) — re-expressed as
counting-rank movement instead of comparison merges, which is what a
batch-synchronous vector machine wants.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ytsaurus_tpu.ops.segments import prefix_scan

# Tile size for the per-tile sort networks: the composite key is
# (digit << LOG_TILE) | position, so RADIX_BITS + LOG_TILE must be <= 32.
RADIX_TILE = 2048
RADIX_BITS = 8
_B = 1 << RADIX_BITS


def _exclusive(x, axis):
    return jnp.cumsum(x, axis=axis, dtype=jnp.int32) - x


def _tile_counts(d_sorted):
    """counts[t, b] = rows of digit b in tile t, (nt, 256) int32, as one
    small matrix product per tile: with b = 16 * hi + lo it is
    onehot(hi)^T . onehot(lo), (16 x tile) . (tile x 16).  The 0/1 factors
    are exact in bf16 and the sums in f32 (a tile holds < 2**24 rows).  No
    search and no (nt, tile, 256) plane: a search over the sorted digits
    costs a table-sized gather per step (16 ms of a 26-ms pass at 512
    tiles, PERF.md section 6), and the plain compare-and-sum against all
    256 bins is left unfused by the CPU backend."""
    nibble = jnp.arange(16, dtype=jnp.int32)[None, :, None]
    hi = ((d_sorted >> 4)[:, None, :] == nibble).astype(jnp.bfloat16)
    lo = ((d_sorted & 15)[:, None, :] == nibble).astype(jnp.bfloat16)
    counts = jnp.einsum("tha,tla->thl", hi, lo,
                        preferred_element_type=jnp.float32)
    return counts.astype(jnp.int32).reshape(d_sorted.shape[0], _B)


@jax.named_scope("radix.pass")       # the name its ops carry in a trace
def radix_pass(digit: jax.Array,
               payloads: list[jax.Array]) -> list[jax.Array]:
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

    counts = _tile_counts(d_sorted)                             # (nt, B)
    # local_start[t, b] = first position of digit b inside tile t.
    local_start = _exclusive(counts, 1)                         # (nt, B)
    per_bin = counts.sum(axis=0, dtype=jnp.int32)               # (B,)
    bin_start = _exclusive(per_bin, 0)                          # (B,)
    tile_excl = _exclusive(counts, 0)                           # (nt, B)
    # dest of tile t's bin-b run = bin_start[b] + rows of b in earlier
    # tiles; every element's destination is unique (a permutation).
    run_start = bin_start[None, :] + tile_excl                  # (nt, B)

    # The tile-sorted rows are the nt x 256 runs in (tile, bin) order; run
    # (t, b) starts at row src0 and goes to slots run_start[t, b] onward,
    # so inside a run dest - row is the constant run_start - src0.  Mark
    # each run's first row with the CHANGE of that constant and prefix-sum
    # the marks: an empty run shares its row with the run after it and
    # their changes add up, so the sum at a row telescopes to the constant
    # of the run that holds it.  No per-row search, no row-sized gather.
    # Every plane is held to int32 (the package enables x64); wrap-around
    # is harmless.
    src0 = (jnp.arange(nt, dtype=jnp.int32)[:, None] * np.int32(tile)
            + local_start)                                      # (nt, B)
    jump = jnp.diff((run_start - src0).reshape(-1), prepend=0)
    # Runs that start past the last row (src0 == n) are empty: dropped.
    marks = jnp.zeros(n, jnp.int32).at[src0.reshape(-1)].add(
        jump, indices_are_sorted=True, mode="drop")
    dest = jnp.arange(n, dtype=jnp.int32) + prefix_scan(jnp.add, marks)
    return [jnp.zeros(n, p.dtype).at[dest].set(
                p, unique_indices=True, mode="drop")
            for p in pay_sorted]


def _pad_to_tile(x: jax.Array, n_pad: int, fill) -> jax.Array:
    if n_pad == 0:
        return x
    return jnp.concatenate([x, jnp.full(n_pad, fill, x.dtype)])


def radix_argsort_u32(words: list[jax.Array],
                      word_bits: "list[int] | None" = None) -> jax.Array:
    """Stable ascending argsort over u32 key words (major word first) via
    LSD radix passes.  `word_bits[k]` bounds the significant LOW bits of
    word k (higher bits must be zero) — digit passes above the bound are
    skipped, so a packed 12-bit key costs 2 byte passes, not 4.

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
            (perm,) = radix_pass(digit, [perm])
    return perm[:n]
