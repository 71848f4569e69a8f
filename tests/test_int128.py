"""int128 (hi, lo) device arithmetic vs Python-int oracle."""

import numpy as np
import pytest

from spark_rapids_tpu.exec import int128 as I


M128 = 1 << 128


def rnd_vals(rng, n, bits=120):
    out = []
    for _ in range(n):
        b = int(rng.integers(0, bits))
        v = int(rng.integers(0, 1 << 62)) << max(b - 62, 0)
        if rng.random() < 0.5:
            v = -v
        out.append(v)
    out.extend([0, 1, -1, (1 << 127) - 1, -(1 << 127), 10**38, -(10**38)])
    return out


def to_dev(vals):
    import jax

    hi, lo = I.from_py_ints(vals)
    return jax.device_put(hi), jax.device_put(lo)


def back(h, l):
    vals = I.to_py_ints(np.asarray(h), np.asarray(l))
    # normalize to signed 128-bit
    return [v - M128 if v >= (1 << 127) else v for v in vals]


def signed128(v):
    v %= M128
    return v - M128 if v >= (1 << 127) else v


def test_roundtrip():
    rng = np.random.default_rng(0)
    vals = rnd_vals(rng, 50)
    h, l = to_dev(vals)
    assert back(h, l) == [signed128(v) for v in vals]


def test_add_sub_neg():
    rng = np.random.default_rng(1)
    a = rnd_vals(rng, 40)
    b = rnd_vals(rng, 40)[: len(a)]
    b = b + [0] * (len(a) - len(b))
    ah, al = to_dev(a)
    bh, bl = to_dev(b)
    assert back(*I.add(ah, al, bh, bl)) == [signed128(x + y)
                                            for x, y in zip(a, b)]
    assert back(*I.sub(ah, al, bh, bl)) == [signed128(x - y)
                                            for x, y in zip(a, b)]
    assert back(*I.neg(ah, al)) == [signed128(-x) for x in a]


def test_cmp():
    rng = np.random.default_rng(2)
    a = rnd_vals(rng, 40)
    b = list(reversed(a))
    ah, al = to_dev(a)
    bh, bl = to_dev(b)
    lt = np.asarray(I.cmp_lt(ah, al, bh, bl))
    eq = np.asarray(I.cmp_eq(ah, al, bh, bl))
    assert lt.tolist() == [signed128(x) < signed128(y) for x, y in zip(a, b)]
    assert eq.tolist() == [signed128(x) == signed128(y) for x, y in zip(a, b)]


def test_mul_64x64():
    import jax

    rng = np.random.default_rng(3)
    a = [int(rng.integers(-(1 << 62), 1 << 62)) for _ in range(60)] + \
        [0, 1, -1, (1 << 62) - 1, -(1 << 62)]
    b = list(reversed(a))
    ad = jax.device_put(np.array(a, np.int64))
    bd = jax.device_put(np.array(b, np.int64))
    assert back(*I.mul_64x64(ad, bd)) == [signed128(x * y)
                                          for x, y in zip(a, b)]


def test_mul_small_rescale():
    rng = np.random.default_rng(4)
    a = rnd_vals(rng, 40, bits=90)
    ah, al = to_dev(a)
    assert back(*I.mul_small(ah, al, 10**9)) == [signed128(x * 10**9)
                                                 for x in a]
    assert back(*I.rescale10(ah, al, 20)) == [signed128(x * 10**20)
                                              for x in a]


def test_div_small_half_up():
    import jax

    rng = np.random.default_rng(5)
    a = rnd_vals(rng, 60, bits=110)
    d = [int(rng.integers(1, 1 << 30)) for _ in a]
    ah, al = to_dev(a)
    dd = jax.device_put(np.array(d, np.int64))

    def half_up(x, y):
        q, r = divmod(abs(x), y)
        if 2 * r >= y:
            q += 1
        return q if x >= 0 else -q
    got = back(*I.div_small_half_up(ah, al, dd))
    # skip the int128-min edge (abs overflow; Spark overflow-nulls there)
    want = [half_up(signed128(x), y) for x, y in zip(a, d)]
    for g, w, x in zip(got, want, a):
        if signed128(x) == -(1 << 127):
            continue
        assert g == w, (x, g, w)


def test_overflow_mask():
    import jax

    vals = [10**38 - 1, 10**38, -(10**38) + 1, -(10**38), 0, 10**20]
    h, l = to_dev(vals)
    got = np.asarray(I.overflow_mask(h, l, 38)).tolist()
    assert got == [False, True, False, True, False, False]


def test_sortable_keys():
    rng = np.random.default_rng(6)
    a = rnd_vals(rng, 60)
    sa = sorted(range(len(a)), key=lambda i: signed128(a[i]))
    h, l = to_dev(a)
    kh, kl = I.sortable_keys(h, l)
    order = np.lexsort((np.asarray(kl), np.asarray(kh)))
    assert [signed128(a[i]) for i in order] == \
        [signed128(a[i]) for i in sa]


INT64_MIN, INT64_MAX = -(1 << 63), (1 << 63) - 1


def _wide_narrow_cases(family, precision):
    """(wide, narrow) Python-int operand pairs of one family."""
    rng = np.random.default_rng(7)
    if family == "random":  # every width of either operand, both signs
        pairs = []
        for _ in range(400):
            a = int.from_bytes(rng.bytes(16), "little") >> int(
                rng.integers(1, 128))
            b = int.from_bytes(rng.bytes(8), "little") >> int(
                rng.integers(1, 64))
            pairs.append((a if rng.random() < .5 else -a,
                          b if rng.random() < .5 else -b))
        return pairs
    if family == "extremes":
        wide = [0, 1, -1, 10**38 - 1, -(10**38 - 1), (1 << 127) - 1,
                -(1 << 127), (1 << 64) - 1, 1 << 64, -(1 << 64),
                1 << 63, -(1 << 63), (1 << 63) | 5, -((1 << 63) | 5),
                (7 << 64) | (1 << 63), 10**19, 10**37]  # lo's top bit set
        narrow = [0, 1, -1, INT64_MAX, INT64_MIN, INT64_MIN + 1,
                  INT64_MAX - 1, 10**18, -(10**18), 1 << 32, -(1 << 32),
                  (1 << 32) - 1, 3]
        return [(a, b) for a in wide for b in narrow]
    if family == "bound":  # |a*b| just under, at and just over 10^p
        pairs = []
        for b in (1, -1, 3, -7, 10**9 + 7, -(10**17 + 3), (1 << 62) + 1,
                  INT64_MIN):
            q = min(10**precision, (1 << 127) - 1) // abs(b)
            pairs += [(s * min(q + d, (1 << 127) - 1), b)
                      for d in (-2, -1, 0, 1, 2) for s in (1, -1)]
        return pairs
    assert family == "over128"  # |a*b| in [2^127 - eps, 2^191)
    pairs = []
    for b in (2, -2, 1 << 40, INT64_MAX, INT64_MIN, -(10**18)):
        for bits in (127, 128, 129, 160, 190):
            q = (1 << bits) // abs(b)
            pairs += [(s * min(q + d, (1 << 127) - 1), b)
                      for d in (-1, 0, 1) for s in (1, -1)]
    return pairs + [(-(1 << 127), 1), (-(1 << 127), -1), (1 << 126, 2),
                    (-(1 << 126), 2), (1 << 126, -2), (1 << 64, INT64_MIN)]


@pytest.mark.parametrize("precision", [38, 24, 39])
@pytest.mark.parametrize("family", ["random", "extremes", "bound", "over128"])
def test_mul_128x64(family, precision):
    """The wide x narrow multiply against Python integers, and against
    mul_128_exact of the sign-extended narrow operand: (hi, lo, overflow)
    equal on every row, overflow rows included (precision 39: no decimal
    bound, the 127-bit one alone)."""
    import jax

    pairs = _wide_narrow_cases(family, precision)
    a = [x for x, _ in pairs]
    b = np.array([y for _, y in pairs], np.int64)
    ah, al = to_dev(a)
    bd = jax.device_put(b)
    h, l, ovf = jax.jit(I.mul_128x64, static_argnums=3)(ah, al, bd, precision)
    want = [x * int(y) for x, y in pairs]
    want_ovf = [abs(v) >= 1 << 127 or (precision <= 38
                                       and abs(v) >= 10**precision)
                for v in want]
    assert np.asarray(ovf).tolist() == want_ovf
    assert any(want_ovf) or family == "random"
    assert not all(want_ovf) or family == "over128"
    got = back(h, l)
    assert [g for g, o in zip(got, want_ovf) if not o] == \
        [v for v, o in zip(want, want_ovf) if not o]
    bh, bl = I.from_i64(bd)
    eh, el, eovf = jax.jit(I.mul_128_exact, static_argnums=4)(
        ah, al, bh, bl, precision)
    assert np.array_equal(np.asarray(ovf), np.asarray(eovf))
    assert np.array_equal(np.asarray(h), np.asarray(eh))
    assert np.array_equal(np.asarray(l), np.asarray(el))
