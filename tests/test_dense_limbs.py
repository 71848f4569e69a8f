"""The dense aggregate's byte-limb contraction against Python integers.

``K.dense_segment_reduce`` sums int64 lanes and counts flags per dense id
through int8 contractions of the values' own bytes (exec/kernels.py,
docs/fusion.md). Exactness is the contract: the 128-bit sums are the true
signed sums, their low words are Java's long wrap, counts are exact, and a
row whose id is outside the domain is in nothing. CPU only; the shapes are
small, the values are the extremes.
"""

import numpy as np
import pytest

import jax

import spark_rapids_tpu  # noqa: F401  (x64 on)
from spark_rapids_tpu.exec import kernels as K

N = 3000
MASK64 = (1 << 64) - 1


def _values(kind, R, n=N, seed=11):
    rng = np.random.default_rng(seed)
    if kind == "random":
        return rng.integers(-2**63, 2**63 - 1, size=(R, n), dtype=np.int64,
                            endpoint=True)
    fill = {"min": -2**63, "neg1": -1, "max": 2**63 - 1}[kind]
    return np.full((R, n), fill, np.int64)


def _ids(G, n=N, seed=5, outside=False):
    rng = np.random.default_rng(seed)
    lo, hi = (-3, G + 3) if outside else (0, G)
    return rng.integers(lo, hi, size=n).astype(np.int32)


def _want(x, ids, G):
    """Python-integer sums: (R, G) lists."""
    return [[sum(int(v) for v in row[ids == g]) for g in range(G)]
            for row in x]


def _as_int128(hi, lo):
    hi, lo = np.asarray(hi), np.asarray(lo)
    return [[(int(h) << 64) + (int(v) & MASK64) for h, v in zip(hr, lr)]
            for hr, lr in zip(hi, lo)]


def _wrap64(v):
    v &= MASK64
    return v - (1 << 64) if v >> 63 else v


KINDS = ["random", "min", "neg1", "max"]


@pytest.mark.parametrize("G", [1, 16])
@pytest.mark.parametrize("R", [1, 9])
@pytest.mark.parametrize("kind", KINDS)
def test_sums_int128_exact(kind, R, G):
    x, ids = _values(kind, R), _ids(G)
    hi, lo = jax.jit(
        lambda x, i: K.dense_segment_reduce(list(x), (), i, G)[:2])(x, ids)
    assert _as_int128(hi, lo) == _want(x, ids, G)


@pytest.mark.parametrize("G", [1, 16])
@pytest.mark.parametrize("R", [1, 9])
@pytest.mark.parametrize("kind", KINDS)
def test_sums_int_wrap_like_java_long(kind, R, G):
    x, ids = _values(kind, R), _ids(G)
    got = np.asarray(jax.jit(
        lambda x, i: K.dense_segment_reduce(list(x), (), i, G)[1])(x, ids))
    assert got.dtype == np.int64
    want = [[_wrap64(v) for v in row] for row in _want(x, ids, G)]
    assert got.tolist() == want


@pytest.mark.parametrize("G", [1, 16])
@pytest.mark.parametrize("k", [1, 7, 8, 15])
def test_counts_flags_alone(k, G):
    """No int lane at all; 7 flags fill a word, 8 and 15 spill into more."""
    rng = np.random.default_rng(k)
    flags = rng.random((k, N)) < 0.4
    ids = _ids(G, outside=True)
    got = np.asarray(jax.jit(lambda f, i: K.dense_segment_reduce(
        (), list(f), i, G)[2])(flags, ids))
    want = [[int((f & (ids == g)).sum()) for g in range(G)] for f in flags]
    assert got.tolist() == want


@pytest.mark.parametrize("G", [1, 16])
@pytest.mark.parametrize("kind", KINDS)
def test_ids_outside_the_domain_are_in_nothing(kind, G):
    """Masking by id, as the first pass does it: such rows keep their
    values and still count nowhere."""
    x, ids = _values(kind, 2), _ids(G, outside=True)
    flags = np.ones((1, N), np.bool_)
    hi, lo, counts, n_rows = jax.jit(
        lambda x, f, i: K.dense_segment_reduce(list(x), list(f), i, G))(
            x, flags, ids)
    assert _as_int128(hi, lo) == _want(x, ids, G)
    per_id = [int((ids == g).sum()) for g in range(G)]
    assert np.asarray(n_rows).tolist() == per_id
    assert np.asarray(counts).tolist() == [per_id]


@pytest.mark.parametrize("kind", KINDS)
def test_masked_rows_carry_zero(kind):
    """Masking by value, the lanes' other contract: a masked row holds 0,
    keeps a valid id, and moves no sum (it does count as a row)."""
    G = 16
    x, ids = _values(kind, 3), _ids(G)
    live = np.random.default_rng(2).random(N) < 0.5
    xm = np.where(live, x, 0)
    hi, lo = jax.jit(
        lambda x, i: K.dense_segment_reduce(list(x), (), i, G)[:2])(xm, ids)
    assert _as_int128(hi, lo) == _want(x[:, live], ids[live], G)


@pytest.mark.parametrize("n", [4096, 5000])
@pytest.mark.parametrize("kind", KINDS)
def test_batch_longer_than_one_contraction_block(kind, n):
    """Blocks of 2^10 rows (2^23 in use): 4 whole blocks, and 5 with the
    last one padded; block sums add up in 64 bits."""
    G, R = 16, 3
    x, ids = _values(kind, R, n), _ids(G, n, outside=True)
    flags = np.random.default_rng(3).random((9, n)) < 0.5
    hi, lo, counts, n_rows = jax.jit(
        lambda x, f, i: K.dense_segment_reduce(list(x), list(f), i, G,
                                               block_rows=1 << 10))(
            x, flags, ids)
    assert _as_int128(hi, lo) == _want(x, ids, G)
    assert np.asarray(counts).tolist() == [
        [int((f & (ids == g)).sum()) for g in range(G)] for f in flags]
    assert np.asarray(n_rows).tolist() == [
        int((ids == g).sum()) for g in range(G)]


def test_one_block_holds_int32():
    """The bound behind the block size: |limb| <= 128 over 2^23 rows is
    2^30, inside int32; 2^24 rows of -128 would be -2^31, the edge."""
    assert 128 * K._LIMB_BLOCK_ROWS < 2**31 - 1
