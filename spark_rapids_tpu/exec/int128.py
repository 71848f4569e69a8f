"""128-bit integer arithmetic on TPU as (hi, lo) int64 limb pairs.

The device representation of DECIMAL128 (precision > 18) values: scaled
unscaled-value v = hi * 2^64 + (lo interpreted unsigned), two's complement.
All ops are exact mod 2^128.  This replaces the reference's cuDF
decimal128 columns + spark-rapids-jni DecimalUtils (SURVEY §2.11.2) with a
pure-XLA formulation: int64 adds/compares are native-ish on TPU, 64x64
multiplies split into 32-bit halves, divides by small ints run as 4-digit
schoolbook long division — everything vectorizes, nothing scatters.

Unsigned comparison of int64 lo limbs uses the sign-flip trick
(x ^ 2^63 preserves unsigned order in signed compares).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

I64 = jnp.int64
U64 = jnp.uint64
_SIGN = np.int64(np.uint64(1) << np.uint64(63))
_MASK32 = np.uint64(0xFFFFFFFF)


def from_i64(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sign-extend an int64 into (hi, lo)."""
    x = x.astype(I64)
    return jnp.where(x < 0, I64(-1), I64(0)), x


def _ult(a: jax.Array, b: jax.Array) -> jax.Array:
    """Unsigned < on int64 bit patterns."""
    return (a ^ _SIGN) < (b ^ _SIGN)


def add(ah, al, bh, bl) -> Tuple[jax.Array, jax.Array]:
    lo = al + bl  # wraps
    carry = _ult(lo, al)
    hi = ah + bh + carry.astype(I64)
    return hi, lo


def neg(h, l) -> Tuple[jax.Array, jax.Array]:
    lo = -l  # two's complement: ~l + 1 wraps correctly
    borrow = (l != 0).astype(I64)
    hi = -h - borrow
    return hi, lo


def sub(ah, al, bh, bl) -> Tuple[jax.Array, jax.Array]:
    nh, nl = neg(bh, bl)
    return add(ah, al, nh, nl)


def is_neg(h, l) -> jax.Array:
    return h < 0


def abs_(h, l) -> Tuple[jax.Array, jax.Array]:
    nh, nl = neg(h, l)
    m = is_neg(h, l)
    return jnp.where(m, nh, h), jnp.where(m, nl, l)


def cmp_lt(ah, al, bh, bl) -> jax.Array:
    return (ah < bh) | ((ah == bh) & _ult(al, bl))


def cmp_eq(ah, al, bh, bl) -> jax.Array:
    return (ah == bh) & (al == bl)


def mul_64x64(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Full signed 64x64 -> 128 product via 32-bit half words."""
    au = a.astype(U64)
    bu = b.astype(U64)
    a0 = au & _MASK32
    a1 = au >> 32
    b0 = bu & _MASK32
    b1 = bu >> 32
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> 32) + (p01 & _MASK32) + (p10 & _MASK32)
    lo = (p00 & _MASK32) | (mid << 32)
    hi_u = p11 + (p01 >> 32) + (p10 >> 32) + (mid >> 32)
    # unsigned -> signed correction: subtract b<<64 if a<0, a<<64 if b<0
    hi = hi_u.astype(I64)
    hi = hi - jnp.where(a < 0, b, I64(0)) - jnp.where(b < 0, a, I64(0))
    return hi, lo.astype(I64)


def mul_small(h, l, m: int) -> Tuple[jax.Array, jax.Array]:
    """(hi, lo) * m for a small positive python int m (< 2^31)."""
    ph, pl = mul_64x64(l, jnp.full_like(l, m))
    # for negative l the mul_64x64 sign correction already applied; but we
    # want (h*2^64 + lo_u) * m: treat l as UNSIGNED here -> add back m where
    # l < 0 (the correction subtracted m*2^64 once)
    ph = ph + jnp.where(l < 0, I64(m), I64(0))
    return ph + h * I64(m), pl


def rescale10(h, l, k: int) -> Tuple[jax.Array, jax.Array]:
    """(hi, lo) * 10^k, k >= 0, exact mod 2^128."""
    while k > 0:
        step = min(k, 9)  # 10^9 < 2^31
        h, l = mul_small(h, l, 10 ** step)
        k -= step
    return h, l


def rescale10_checked(h, l, k: int, precision: int):
    """(hi, lo) * 10^k with Spark overflow detection BEFORE multiplying —
    a wrapped product mod 2^128 could masquerade as in-range, so rows whose
    magnitude >= 10^(precision-k) are flagged (and will be nulled by the
    caller) rather than multiplied blind. Returns (hi, lo, overflow)."""
    if k <= 0:
        return h, l, overflow_mask(h, l, precision)
    if precision - k >= 1:
        ovf = overflow_mask(h, l, precision - k)
    else:
        ovf = ~cmp_eq(h, l, jnp.zeros_like(h), jnp.zeros_like(l))
    zh = jnp.where(ovf, jnp.zeros_like(h), h)
    zl = jnp.where(ovf, jnp.zeros_like(l), l)
    rh, rl = rescale10(zh, zl, k)
    return rh, rl, ovf


def _udivmod_small(h, l, d: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Unsigned (hi, lo) // d and remainder, for divisor 0 < d < 2^31.

    Schoolbook long division over four 32-bit digits; remainders stay
    below 2^31 so every partial value fits non-negative int64.
    """
    hu = h.astype(U64)
    lu = l.astype(U64)
    digits = [(hu >> 32).astype(I64), (hu & _MASK32).astype(I64),
              (lu >> 32).astype(I64), (lu & _MASK32).astype(I64)]
    d = d.astype(I64)
    r = jnp.zeros_like(d)
    qd = []
    for dig in digits:
        cur = (r << 32) | dig
        q = cur // d
        r = cur - q * d
        qd.append(q)
    q_hi = (qd[0].astype(U64) << 32) | qd[1].astype(U64)
    q_lo = (qd[2].astype(U64) << 32) | qd[3].astype(U64)
    return q_hi.astype(I64), q_lo.astype(I64), r


def div_small_half_up(h, l, d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Signed (hi, lo) / d with ROUND_HALF_UP (away from zero); d > 0."""
    ah, al = abs_(h, l)
    qh, ql, r = _udivmod_small(ah, al, d)
    round_up = (2 * r >= d).astype(I64)
    qh, ql = add(qh, ql, jnp.zeros_like(qh), round_up)
    nqh, nql = neg(qh, ql)
    m = is_neg(h, l)
    return jnp.where(m, nqh, qh), jnp.where(m, nql, ql)


_POW10_HI_LO = {}


def pow10_128(k: int) -> Tuple[int, int]:
    """(hi, lo) python ints of 10^k (two's complement limbs)."""
    v = 10 ** k
    lo = v & ((1 << 64) - 1)
    hi = v >> 64
    if lo >= 1 << 63:
        lo -= 1 << 64
    if hi >= 1 << 63:
        hi -= 1 << 64
    return hi, lo


def overflow_mask(h, l, precision: int) -> jax.Array:
    """True where |value| >= 10^precision (Spark non-ANSI -> NULL)."""
    if precision >= 39:
        return jnp.zeros_like(h, dtype=jnp.bool_)
    bh, bl = pow10_128(precision)
    ah, al = abs_(h, l)
    # abs of -2^127 stays negative; treat as overflow
    neg_abs = ah < 0
    bound_h = jnp.full_like(h, bh)
    bound_l = jnp.full_like(l, bl)
    ge = ~cmp_lt(ah, al, bound_h, bound_l)
    return ge | neg_abs


def to_py_ints(h_np: np.ndarray, l_np: np.ndarray):
    """Host-side exact reconstruction: value = hi*2^64 + lo_unsigned."""
    out = []
    for hi, lo in zip(h_np.tolist(), l_np.tolist()):
        out.append((hi << 64) + (lo & ((1 << 64) - 1)))
    return out


def from_py_ints(vals) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side split of python ints into (hi, lo) int64 limb arrays."""
    n = len(vals)
    hi = np.empty(n, np.int64)
    lo = np.empty(n, np.int64)
    m64 = (1 << 64) - 1
    for i, v in enumerate(vals):
        u = v & ((1 << 128) - 1)
        lou = u & m64
        hiu = (u >> 64) & m64
        lo[i] = lou - (1 << 64) if lou >= (1 << 63) else lou
        hi[i] = hiu - (1 << 64) if hiu >= (1 << 63) else hiu
    return hi, lo


def sortable_keys(h, l):
    """Order-preserving (primary, secondary) int64 keys for lexsort."""
    return h, (l ^ _SIGN)


# ---------------------------------------------------------------------------
# 16-bit-limb bignum engine (round 4): exact 128x128 multiply and 256/128
# divide, fully vectorized.  The device replacement for the reference's jni
# DecimalUtils multiply128/divide128 (SURVEY §2.11.2): limbs live on a
# trailing axis of shape (..., L), every step is an elementwise int64 op or
# a take_along_axis, and the Knuth-D loop is a STATIC 9-iteration unroll —
# no data-dependent control flow, so XLA fuses the whole division.
# ---------------------------------------------------------------------------

_B16 = 1 << 16


def _limbs8(h, l) -> jax.Array:
    """Unsigned (hi, lo) -> (..., 8) int64 limbs, little-endian 16-bit."""
    hu = h.astype(U64)
    lu = l.astype(U64)
    parts = []
    for word in (lu, hu):
        for k in range(4):
            parts.append(((word >> U64(16 * k)) & U64(0xFFFF)).astype(I64))
    return jnp.stack(parts, axis=-1)


def _from_limbs8(limbs: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(..., >=8) limbs -> unsigned (hi, lo); limbs above 8 ignored."""
    lo = jnp.zeros(limbs.shape[:-1], U64)
    hi = jnp.zeros(limbs.shape[:-1], U64)
    for k in range(4):
        lo = lo | (limbs[..., k].astype(U64) << U64(16 * k))
        hi = hi | (limbs[..., 4 + k].astype(U64) << U64(16 * k))
    return hi.astype(I64), lo.astype(I64)


def _mul_limbs(a: jax.Array, b: jax.Array, out_n: int) -> jax.Array:
    """Schoolbook product of limb arrays (each limb < 2^16) -> out_n limbs.

    Partial sums stay below 2^36 (<= 16 terms of < 2^32), so carries fit
    int64 comfortably."""
    cols = []
    na, nb = a.shape[-1], b.shape[-1]
    for k in range(out_n):
        acc = None
        for i in range(max(0, k - nb + 1), min(na, k + 1)):
            t = a[..., i] * b[..., k - i]
            acc = t if acc is None else acc + t
        cols.append(acc if acc is not None
                    else jnp.zeros(a.shape[:-1], I64))
    prod = jnp.stack(cols, axis=-1)
    # carry propagation
    out = []
    carry = jnp.zeros(a.shape[:-1], I64)
    for k in range(out_n):
        v = prod[..., k] + carry
        out.append(v & (_B16 - 1))
        carry = v >> 16
    return jnp.stack(out, axis=-1)


def mul_128_exact(ah, al, bh, bl, precision: int):
    """Signed 128x128 multiply with Spark overflow-to-NULL semantics.

    Returns (hi, lo, overflow): overflow is True when |a*b| needs more
    than 127 bits or is >= 10^precision. One int64 operand: mul_128x64."""
    sa = is_neg(ah, al)
    sb = is_neg(bh, bl)
    aah, aal = abs_(ah, al)
    abh, abl = abs_(bh, bl)
    prod = _mul_limbs(_limbs8(aah, aal), _limbs8(abh, abl), 16)
    high_any = jnp.zeros(prod.shape[:-1], jnp.bool_)
    for k in range(8, 16):
        high_any = high_any | (prod[..., k] != 0)
    h, l = _from_limbs8(prod)
    neg_out = sa != sb
    nh, nl = neg(h, l)
    oh = jnp.where(neg_out, nh, h)
    ol = jnp.where(neg_out, nl, l)
    ovf = high_any | overflow_mask(oh, ol, precision) | is_neg(h, l)
    return oh, ol, ovf


def mul_128x64(ah, al, b, precision: int):
    """Signed 128x64 multiply: ``mul_128_exact``'s contract and its very
    (hi, lo, overflow) for an int64 ``b``, at a quarter of its device time
    (PERF.md, PR 34; why it is exact: docs/fusion.md). With a = ah*2^64 +
    al_u, a*b = al_u*b + (ah*b << 64): two signed 64x64 products summed
    word by word; no limb axis, no abs/neg."""
    p0h, p0l = mul_64x64(al, b)
    p0h = p0h + jnp.where(al < 0, b, I64(0))  # al is unsigned (mul_small)
    p1h, p1l = mul_64x64(ah, b)
    # words of the 192-bit sum: lo = p0l, hi = p0h + p1l, and above them
    # sext(p0h) + p1h + carry (|p1h| <= 2^62: no wrap), which is the sign
    # extension of hi exactly where the product fits 128 bits
    h = p0h + p1l
    top = (p0h >> 63) + p1h + _ult(h, p1l).astype(I64)
    ovf = (top != (h >> 63)) | overflow_mask(h, p0l, precision)
    if precision >= 39:  # mask off; -2^127 fits 128 bits but not 127
        ovf = ovf | ((h == _SIGN) & (p0l == 0))
    return h, p0l, ovf


def _clz16_limbs(v: jax.Array) -> jax.Array:
    """Per-row count of leading ZERO LIMBS + bit normalization shift so the
    top significant limb lands in position L-1 with its high bit set.
    Returns total left-shift in bits (0 when v == 0)."""
    L = v.shape[-1]
    # index of highest nonzero limb
    idx = jnp.full(v.shape[:-1], -1, jnp.int32)
    for k in range(L):
        idx = jnp.where(v[..., k] != 0, jnp.int32(k), idx)
    top = jnp.take_along_axis(
        v, jnp.clip(idx, 0, L - 1)[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    # bits needed to bring top limb's msb to bit 15
    tb = jnp.zeros(v.shape[:-1], jnp.int32)
    cur = top
    for b in (8, 4, 2, 1):
        fits = cur < (1 << (16 - b))
        tb = tb + jnp.where(fits, b, 0)
        cur = jnp.where(fits, cur << b, cur)
    return jnp.where(idx < 0, 0, (L - 1 - idx) * 16 + tb)


def _shl_limbs(v: jax.Array, s: jax.Array, out_n: int) -> jax.Array:
    """Left-shift limb array (..., L) by per-row s bits into out_n limbs."""
    assert v.ndim >= 2
    L = v.shape[-1]
    sl = (s // 16).astype(jnp.int32)
    sb = (s % 16).astype(jnp.int64)
    k = jnp.arange(out_n, dtype=jnp.int32)
    src = k[None, :] - sl[..., None]
    padded = jnp.concatenate(
        [v, jnp.zeros(v.shape[:-1] + (max(out_n - L, 1),), I64)], axis=-1)
    src_c = jnp.clip(src, 0, padded.shape[-1] - 1)
    base = jnp.where((src >= 0) & (src < L),
                     jnp.take_along_axis(padded, src_c, axis=-1), 0)
    src_m1 = jnp.clip(src - 1, 0, padded.shape[-1] - 1)
    below = jnp.where((src - 1 >= 0) & (src - 1 < L),
                      jnp.take_along_axis(padded, src_m1, axis=-1), 0)
    sbx = sb[..., None]
    # sb == 0 -> (below >> 16) == 0 contribution (jnp shift by 16 is ok)
    out = ((base << sbx) | (below >> (16 - sbx))) & (_B16 - 1)
    return out


def udivmod_256_by_128(u: jax.Array, v: jax.Array):
    """Knuth algorithm D, vectorized: u (..., 16) limbs / v (..., 8) limbs.

    Returns (q (..., 9) limbs, r (..., 8) limbs). v must be nonzero
    (caller masks div-by-zero rows). Static 9x8 unrolled loop."""
    s = _clz16_limbs(v)
    vn = _shl_limbs(v, s, 8)
    un = _shl_limbs(u, s, 17)
    B = _B16
    v_top = vn[..., 7]
    v_next = vn[..., 6]
    q_limbs = []
    for j in reversed(range(9)):  # 16 - 8 + 1 quotient positions
        top2 = un[..., j + 8] * B + un[..., j + 7]
        qhat = jnp.minimum(top2 // jnp.maximum(v_top, 1), B - 1)
        rhat = top2 - qhat * jnp.maximum(v_top, 1)
        # at most two corrections (Knuth Thm B)
        for _ in range(2):
            over = (qhat * v_next > rhat * B + un[..., j + 6]) & (rhat < B)
            qhat = jnp.where(over, qhat - 1, qhat)
            rhat = jnp.where(over, rhat + v_top, rhat)
        # multiply-subtract qhat * vn from un[j .. j+8]
        borrow = jnp.zeros_like(qhat)
        new_u = []
        for i in range(8):
            t = un[..., j + i] - qhat * vn[..., i] - borrow
            lim = t & (B - 1)
            new_u.append(lim)
            borrow = (lim - t) >> 16  # non-negative multiple of 2^16 / 2^16
        t = un[..., j + 8] - borrow
        neg_row = t < 0
        new_u.append(t & (B - 1))
        # add back one v when we overshot
        qhat = jnp.where(neg_row, qhat - 1, qhat)
        carry = jnp.zeros_like(qhat)
        fixed = []
        for i in range(8):
            t2 = new_u[i] + jnp.where(neg_row, vn[..., i], 0) + carry
            fixed.append(t2 & (B - 1))
            carry = t2 >> 16
        fixed.append((new_u[8] + carry) & (B - 1))
        cols = [un[..., i] for i in range(un.shape[-1])]
        for i in range(9):
            cols[j + i] = fixed[i]
        un = jnp.stack(cols, axis=-1)
        q_limbs.append(qhat)
    q = jnp.stack(list(reversed(q_limbs)), axis=-1)
    # remainder = un[0:8] >> s  (denormalize)
    r = _shr_limbs(un[..., :8], s)
    return q, r


def _shr_limbs(v: jax.Array, s: jax.Array) -> jax.Array:
    L = v.shape[-1]
    sl = (s // 16).astype(jnp.int32)
    sb = (s % 16).astype(jnp.int64)
    k = jnp.arange(L, dtype=jnp.int32)
    src = k[None, :] + sl[..., None] if v.ndim == 2 else k + sl
    src_c = jnp.clip(src, 0, L - 1)
    base = jnp.where(src < L, jnp.take_along_axis(v, src_c, axis=-1), 0)
    src_p1 = jnp.clip(src + 1, 0, L - 1)
    above = jnp.where(src + 1 < L,
                      jnp.take_along_axis(v, src_p1, axis=-1), 0)
    sbx = sb[..., None]
    return ((base >> sbx) | (above << (16 - sbx))) & (_B16 - 1)


def decimal_divide_128(ah, al, bh, bl, shift_k: int, precision: int):
    """q = ROUND_HALF_UP(a * 10^shift_k / b) over signed 128-bit operands.

    The Spark decimal divide kernel (DecimalUtils.divide128 analog):
    returns (hi, lo, overflow_or_div0). shift_k in [0, 38]."""
    assert 0 <= shift_k <= 76, shift_k
    sa = is_neg(ah, al)
    sb = is_neg(bh, bl)
    aah, aal = abs_(ah, al)
    abh, abl = abs_(bh, bl)

    def pw_limbs(k):
        ph, pl = pow10_128(k)
        ph_s = int(np.int64(np.uint64(ph & ((1 << 64) - 1))))
        pl_s = int(np.int64(np.uint64(pl & ((1 << 64) - 1))))
        return _limbs8(jnp.full_like(ah, ph_s), jnp.full_like(al, pl_s))

    k1 = min(shift_k, 38)
    u = _mul_limbs(_limbs8(aah, aal), pw_limbs(k1), 16)
    big_ovf = jnp.zeros(ah.shape, jnp.bool_)
    if shift_k > 38:
        # second stage: u * 10^(k-38) into 24 limbs; spill past 256 bits
        # means |q| > 2^129 > 10^38 -> overflow regardless of b
        u24 = _mul_limbs(u, pw_limbs(shift_k - 38), 24)
        for k in range(16, 24):
            big_ovf = big_ovf | (u24[..., k] != 0)
        u = u24[..., :16]
    v = _limbs8(abh, abl)
    div0 = ~jnp.any(v != 0, axis=-1)
    v_safe = v.at[..., 0].set(jnp.where(div0, 1, v[..., 0]))
    q, r = udivmod_256_by_128(u, v_safe)
    # HALF_UP: 2*r >= |b|  (compare limbwise: 2r as 9 limbs vs v 8 limbs)
    two_r = _mul_limbs(r, jnp.ones(r.shape[:-1] + (1,), I64) * 2, 9)
    # lexicographic unsigned compare two_r >= v
    ge = jnp.zeros(ah.shape, jnp.bool_)
    decided = jnp.zeros(ah.shape, jnp.bool_)
    for k in reversed(range(9)):
        tv = two_r[..., k]
        vv = v[..., k] if k < 8 else jnp.zeros_like(tv)
        gt = ~decided & (tv > vv)
        lt = ~decided & (tv < vv)
        ge = ge | gt
        decided = decided | gt | lt
    ge = ge | ~decided  # equal -> round up (HALF_UP)
    qh, ql = _from_limbs8(q)
    rp = ge.astype(I64)
    qh, ql = add(qh, ql, jnp.zeros_like(qh), rp)
    q_high = q[..., 8] != 0
    # UNSIGNED magnitude bound before the sign is applied: quotients in
    # [10^precision, 2^128) would otherwise wrap the signed pair and slip
    # past overflow_mask
    bph, bpl = pow10_128(min(precision, 38))
    bph_u = np.uint64(bph & ((1 << 64) - 1))
    bpl_u = np.uint64(bpl & ((1 << 64) - 1))
    qh_u = qh.astype(U64)
    ql_u = ql.astype(U64)
    mag_lt = (qh_u < bph_u) | ((qh_u == bph_u) & (ql_u < bpl_u))
    neg_out = sa != sb
    nh, nl = neg(qh, ql)
    oh = jnp.where(neg_out, nh, qh)
    ol = jnp.where(neg_out, nl, ql)
    ovf = q_high | ~mag_lt | div0 | big_ovf
    return oh, ol, ovf


def decimal_avg_128(sh, sl, cnt, d: int, out_precision: int):
    """avg = HALF_UP(sum / cnt) rescaled by 10^d into the result scale
    (the window/aggregate decimal-average kernel; divide FIRST so the
    rescale of the small remainder cannot wrap 2^127)."""
    den = jnp.maximum(cnt, 1).astype(I64)
    ah, al = abs_(sh, sl)
    q1h, q1l, r = _udivmod_small(ah, al, den)
    pre_ovf = overflow_mask(q1h, q1l, max(out_precision - d, 1))
    S = 10 ** d
    frac = r * I64(S)
    f_q = frac // den
    f_r = frac - f_q * den
    f_q = f_q + (2 * f_r >= den).astype(I64)
    qh, ql = mul_small(q1h, q1l, S)
    qh, ql = add(qh, ql, jnp.zeros_like(f_q), f_q)
    nh, nl = neg(qh, ql)
    neg_in = is_neg(sh, sl)
    oh = jnp.where(neg_in, nh, qh)
    ol = jnp.where(neg_in, nl, ql)
    ovf = pre_ovf | overflow_mask(oh, ol, out_precision)
    return oh, ol, ovf
