"""Tiled radix sort engine (ops/radix.py) + exact group ordering.

Correctness oracle: numpy stable sorts.  The radix engine must match the
variadic-network engine bit-for-bit (same stable order) for every key
shape, because stable_argsort_u32 dispatches between them by size.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ytsaurus_tpu.ops.radix import radix_argsort_u32, radix_pass
from ytsaurus_tpu.ops.segments import (
    hash_group_order,
    pack_key_planes_bits,
    packed_sort_indices,
    segment_boundaries,
    stable_argsort_u32,
)


def _np_stable_argsort(words):
    # np.lexsort takes minor key FIRST; words are major-first.
    return np.lexsort(tuple(np.asarray(w) for w in reversed(words)))


_DIGIT_KINDS = ("uniform", "one_value", "ends", "sorted", "sparse",
                "one_bin_heavy")


def _digits(kind, n, rng):
    if kind == "uniform":
        d = rng.integers(0, 256, n)
    elif kind == "one_value":
        d = np.full(n, 77)
    elif kind == "ends":                     # only 0 and 255
        d = rng.choice([0, 255], n)
    elif kind == "sorted":
        d = np.sort(rng.integers(0, 256, n))
    elif kind == "sparse":                   # 200 of the 256 bins empty
        d = rng.choice(rng.permutation(256)[:56], n)
    else:       # one bin holds every row of all tiles but the last one
        d = np.full(n, 5)
        tail = min(n, 2048)
        d[n - tail:] = rng.integers(0, 256, tail)
    return d.astype(np.uint32)


def _pass_cases():
    cases = []
    for n in (8, 2048, 4096):                # nt = 1, 1, 2
        for i, kind in enumerate(_DIGIT_KINDS):
            cases.append((n, kind, 1 + 2 * (i % 2)))
    for kind in ("uniform", "sparse", "one_bin_heavy"):
        cases.append((262_144, kind, 3))
    for i, kind in enumerate(_DIGIT_KINDS):  # the other plane count
        cases.append((4096, kind, 3 - 2 * (i % 2)))
    cases += [(8, "ends", 3), (2048, "sparse", 3), (262_144, "uniform", 1)]
    assert len(set(cases)) == len(cases)
    return cases


@pytest.mark.parametrize("n,kind,planes", _pass_cases())
def test_radix_pass_matches_numpy_stable(n, kind, planes):
    """One byte pass is a stable ascending partition by digit: every
    payload plane comes back in np.argsort(kind="stable") order."""
    rng = np.random.default_rng(n + planes)
    digit = _digits(kind, n, rng)
    payloads = [np.arange(n, dtype=np.uint32),
                rng.integers(-1 << 62, 1 << 62, n, dtype=np.int64),
                rng.random(n)][:planes]
    got = jax.jit(radix_pass)(
        jnp.asarray(digit), [jnp.asarray(p) for p in payloads])
    order = np.argsort(digit, kind="stable")
    assert len(got) == planes
    for plane, out in zip(payloads, got):
        assert out.dtype == plane.dtype
        np.testing.assert_array_equal(np.asarray(out), plane[order])


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def row_sized_loops_and_moves(jaxpr, n):
    """(shapes of the loop-carried values of `n` elements or more, the
    gather / scatter equations over `n` indices or more) of a traced
    program: what a per-row search leaves behind."""
    row_loops, row_moves = [], []
    for eqn in _eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in ("while", "scan"):        # fori_loop is either
            row_loops += [v.aval.shape for v in eqn.outvars
                          if v.aval.size >= n]
        elif name == "gather" or name.startswith("scatter"):
            indices = eqn.invars[1].aval
            if int(np.prod(indices.shape[:-1])) >= n:
                row_moves.append(eqn)
    return row_loops, row_moves


def test_radix_pass_has_no_per_slot_search():
    """The pass finds every row's place from run marks and a prefix sum:
    traced at 1,048,576 rows (nothing executes), it carries no loop over
    row-sized state and touches row-sized indices ONCE per payload plane,
    to move it (a search would bring back a row-sized gather per step)."""
    n = 1 << 20
    plane = jax.ShapeDtypeStruct((n,), jnp.uint32)
    jaxpr = jax.make_jaxpr(lambda d, p: radix_pass(d, [p]))(plane, plane)
    row_loops, row_moves = row_sized_loops_and_moves(jaxpr, n)
    assert not row_loops
    assert len(row_moves) == 1


@pytest.mark.parametrize("n", [1, 2, 5, 100, 2048, 2049, 5000, 100_000])
def test_radix_single_word(n):
    rng = np.random.default_rng(n)
    word = jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
    got = np.asarray(radix_argsort_u32([word]))
    expect = _np_stable_argsort([word])
    np.testing.assert_array_equal(got, expect)


def test_radix_multi_word():
    rng = np.random.default_rng(7)
    n = 10_000
    keys = rng.integers(0, 1 << 63, n, dtype=np.uint64)
    hi = jnp.asarray((keys >> 32).astype(np.uint32))
    lo = jnp.asarray(keys.astype(np.uint32))
    got = np.asarray(radix_argsort_u32([hi, lo]))
    expect = _np_stable_argsort([hi, lo])
    np.testing.assert_array_equal(got, expect)


def test_radix_empty_input():
    """ADVICE r3: a forced engine must return an empty permutation for
    n=0, not crash on degenerate tile math."""
    empty = jnp.zeros((0,), jnp.uint32)
    got = np.asarray(radix_argsort_u32([empty]))
    assert got.shape == (0,)
    assert got.dtype == np.uint32


def test_radix_stability_with_duplicates():
    rng = np.random.default_rng(3)
    n = 50_000
    word = jnp.asarray(rng.integers(0, 7, n, dtype=np.uint32))
    got = np.asarray(radix_argsort_u32([word]))
    expect = _np_stable_argsort([word])
    np.testing.assert_array_equal(got, expect)      # ties keep input order


def test_radix_word_bits_skips_high_bytes():
    rng = np.random.default_rng(11)
    n = 30_000
    word = jnp.asarray(rng.integers(0, 1 << 12, n, dtype=np.uint32))
    got = np.asarray(radix_argsort_u32([word], word_bits=[12]))
    expect = _np_stable_argsort([word])
    np.testing.assert_array_equal(got, expect)


def test_radix_all_equal_and_extremes():
    n = 4096
    ones = jnp.full(n, 0xFFFFFFFF, dtype=jnp.uint32)
    np.testing.assert_array_equal(np.asarray(radix_argsort_u32([ones])),
                                  np.arange(n))
    zeros = jnp.zeros(n, dtype=jnp.uint32)
    np.testing.assert_array_equal(np.asarray(radix_argsort_u32([zeros])),
                                  np.arange(n))


def test_engine_dispatch_matches_network(monkeypatch):
    rng = np.random.default_rng(5)
    n = 20_000
    w1 = jnp.asarray(rng.integers(0, 50, n, dtype=np.uint32))
    w2 = jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint32))
    monkeypatch.setenv("YT_TPU_SORT_ENGINE", "network")
    a = np.asarray(stable_argsort_u32([w1, w2]))
    monkeypatch.setenv("YT_TPU_SORT_ENGINE", "radix")
    b = np.asarray(stable_argsort_u32([w1, w2]))
    np.testing.assert_array_equal(a, b)


def test_packed_sort_small_fields_radix(monkeypatch):
    """Packed small fields (null bit + value bits in one word) sort the
    same under the radix engine, including the shifted tail word."""
    rng = np.random.default_rng(9)
    n = 10_000
    data = jnp.asarray(rng.integers(0, 30, n, dtype=np.int64))
    valid = jnp.asarray(rng.random(n) > 0.1)
    items = [(data, valid, False, 5),
             (jnp.asarray(rng.integers(0, 4, n, dtype=np.int64)),
              jnp.ones(n, dtype=bool), True, 2)]
    monkeypatch.setenv("YT_TPU_SORT_ENGINE", "network")
    a = np.asarray(packed_sort_indices(items))
    monkeypatch.setenv("YT_TPU_SORT_ENGINE", "radix")
    b = np.asarray(packed_sort_indices(items))
    np.testing.assert_array_equal(a, b)
    words, bits = pack_key_planes_bits(items)
    assert len(words) == 1 and bits == [9]       # 1+5 + 1+2 bits packed


@pytest.mark.parametrize("engine", ["network", "radix"])
def test_group_order_exact_null_vs_zero(monkeypatch, engine):
    """NULL and literal 0 are distinct groups; masked rows sort last;
    group identity is exact (no hash involved)."""
    monkeypatch.setenv("YT_TPU_SORT_ENGINE", engine)
    data = jnp.asarray([0, 5, 0, 5, 0, 7], dtype=jnp.int64)
    valid = jnp.asarray([True, True, False, True, True, True])
    mask = jnp.asarray([True, True, True, True, True, False])
    order = np.asarray(hash_group_order([(data, valid)], mask))
    # Masked row (index 5) last.
    assert order[-1] == 5
    sorted_keys = [(data[order], valid[order])]
    seg, nseg = segment_boundaries(sorted_keys, mask[order])
    # Groups: NULL, 0, 5 -> 3 groups (7 is masked out).
    assert int(nseg) == 3
    # The NULL row (2) must not group with the zero rows (0, 4).
    seg = np.asarray(seg)
    pos = {int(r): seg[i] for i, r in enumerate(order)}
    assert pos[0] == pos[4]
    assert pos[2] != pos[0]
    assert pos[1] == pos[3]


@pytest.mark.slow   # ~13s property sweep; tier-1 keeps radix/group-order
# coverage via the single/multi-word, stability, and null-vs-zero tests.
def test_group_order_multi_key_adjacency():
    rng = np.random.default_rng(17)
    n = 30_000
    k1 = jnp.asarray(rng.integers(-50, 50, n, dtype=np.int64))
    v1 = jnp.asarray(rng.random(n) > 0.05)
    k2 = jnp.asarray(rng.random(n).astype(np.float64) * 4 // 1)
    v2 = jnp.asarray(rng.random(n) > 0.05)
    mask = jnp.asarray(rng.random(n) > 0.1)
    order = np.asarray(hash_group_order([(k1, v1), (k2, v2)], mask))
    # Every (key-tuple) group must be CONTIGUOUS among unmasked rows.
    mask_np = np.asarray(mask)
    rows = [(bool(mask_np[i]),
             (None if not v1[i] else int(k1[i]),
              None if not v2[i] else float(k2[i])))
            for i in np.asarray(order)]
    unmasked = [key for m, key in rows if m]
    assert all(not m for m, _ in rows[len(unmasked):])   # masked tail
    seen = set()
    prev = object()
    for key in unmasked:
        if key != prev:
            assert key not in seen, f"group {key} fragmented"
            seen.add(key)
            prev = key
