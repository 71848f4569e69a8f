"""``K.join_rows_table``'s ``twin``: whether two build rows with a valid key
share a 128-bit hash pair, found by comparing the slots of each bucket of
the finished table with each other (no probe of the table by its build).
``_prepare_rows`` refuses a table with a twin, and the partition goes to
the general path: a missed twin is a dropped or doubled join row."""

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401  (x64 on)
from spark_rapids_tpu.columnar.batch import batch_from_arrow
from spark_rapids_tpu.exec import kernels as K

LG_B = 4  # sixteen buckets: the top four bits of h1 name one


def _placed(h1, h2, valid, lg_b=LG_B):
    """``join_row_slots``'s output for hash pairs given by hand: a row's
    bucket, and its rank among its bucket's keyed rows in row order."""
    h1 = np.asarray(h1, np.uint64)
    valid = np.asarray(valid, bool)
    bucket = np.where(valid, (h1 >> np.uint64(64 - lg_b)).astype(np.int32),
                      1 << lg_b).astype(np.int32)
    rank = np.zeros(len(h1), np.int32)
    seen = {}
    for i, b in enumerate(bucket):
        rank[i] = seen.get(int(b), 0)
        seen[int(b)] = rank[i] + 1
    return (jnp.asarray(h1), jnp.asarray(np.asarray(h2, np.uint64)),
            jnp.asarray(valid), jnp.asarray(bucket), jnp.asarray(rank))


def _h1(bucket, low):
    return (bucket << (64 - LG_B)) | low


def _slots_for(largest):
    """The table's row width as ``_prepare_rows`` sizes it: the power of
    two that holds the largest bucket."""
    slots = 1
    while slots < largest:
        slots *= 2
    return slots


def _twin(h1, h2, valid, slots):
    rows, twin = K.join_rows_table(_placed(h1, h2, valid), slots, LG_B)
    assert rows.shape == (1 << LG_B, K.ROW_WORDS * slots)
    return bool(twin)


def _bucket_of(slots, twins_at):
    """One bucket filled to ``slots`` rows of distinct pairs, but that the
    rows at the two ranks of ``twins_at`` carry one pair; a few rows in
    other buckets around them."""
    h1 = [_h1(5, 100 + s) for s in range(slots)]
    h2 = [7000 + s for s in range(slots)]
    if twins_at is not None:
        a, b = twins_at
        h1[b], h2[b] = h1[a], h2[a]
    h1 = [_h1(4, 1), _h1(6, 1)] + h1 + [_h1(15, 9)]
    h2 = [1, 1] + h2 + [1]
    return h1, h2, [True] * len(h1)


BY_HAND = {
    # two keyed rows given one (h1, h2)
    "same_pair": ([_h1(3, 11), _h1(9, 2), _h1(3, 11)], [5, 6, 5],
                  [True, True, True], 4, True),
    # one h1, another h2: neighbours in a bucket, no twins
    "same_h1_other_h2": ([_h1(3, 11), _h1(9, 2), _h1(3, 11)], [5, 6, 8],
                         [True, True, True], 4, False),
    # one h2 and one bucket, another h1
    "same_h2_other_h1": ([_h1(3, 11), _h1(9, 2), _h1(3, 12)], [5, 6, 5],
                         [True, True, True], 4, False),
    # a row without a key never counts, whatever pair it carries
    "invalid_copy": ([_h1(3, 11), _h1(9, 2), _h1(3, 11)], [5, 6, 5],
                     [True, True, False], 4, False),
    "both_invalid": ([_h1(3, 11), _h1(9, 2), _h1(3, 11)], [5, 6, 5],
                     [False, True, False], 4, False),
    # the all-zero pair is what an empty slot holds: two keyed rows with it
    # are twins, one is not the twin of an empty slot
    "zero_pair_twice": ([0, 0, _h1(1, 1)], [0, 0, 0], [True] * 3, 4, True),
    "zero_pair_once": ([0, _h1(1, 1)], [0, 0], [True] * 2, 4, False),
    "no_rows": ([0, 0], [0, 0], [False, False], 1, False),
}
for _slots in (1, 4, 8, 16):
    BY_HAND[f"slots{_slots}_full_bucket"] = _bucket_of(_slots, None) + (
        _slots, False)
    if _slots > 1:
        BY_HAND[f"slots{_slots}_first_two"] = _bucket_of(_slots, (0, 1)) + (
            _slots, True)
        BY_HAND[f"slots{_slots}_last_two"] = _bucket_of(
            _slots, (_slots - 2, _slots - 1)) + (_slots, True)
        BY_HAND[f"slots{_slots}_first_and_last"] = _bucket_of(
            _slots, (0, _slots - 1)) + (_slots, True)
# the last OCCUPIED slots of a bucket that is shorter than the table's rows
BY_HAND["short_bucket_last_two"] = _bucket_of(5, (3, 4)) + (16, True)
BY_HAND["short_bucket_unique"] = _bucket_of(5, None) + (16, False)


@pytest.mark.parametrize("case", sorted(BY_HAND))
def test_twin_by_hand(case):
    h1, h2, valid, slots, want = BY_HAND[case]
    assert _twin(h1, h2, valid, slots) is want


def _batch(keys, nulls=()):
    mask = np.zeros(len(keys), bool)
    mask[list(nulls)] = True
    return batch_from_arrow(pa.table({
        "k": pa.array(np.asarray(keys, np.int64), mask=mask)}))


@pytest.mark.parametrize("keys,nulls,want", [
    (np.arange(700) * 7919 + (1 << 40), (), False),
    (np.r_[np.arange(700) * 7919, 13 * 7919], (), True),
    (np.r_[5, np.arange(700) * 7919 + 6, 5], (), True),
    # SQL: null keys never match, so two of them are no twins
    (np.r_[np.arange(700), 0, 0], (700, 701), False),
], ids=["unique", "one_key_twice", "first_and_last_row", "two_nulls"])
def test_twin_from_keys(keys, nulls, want):
    """Through ``join_row_slots``, as ``_prepare_rows`` calls the pair:
    the table sized by the largest bucket the first half reports."""
    build = _batch(keys, nulls)
    placed, largest = K.join_row_slots(build, (0,))
    slots = _slots_for(int(largest))
    lg_b = K.join_rows_lg_b(build.capacity)
    rows, twin = K.join_rows_table(placed, slots, lg_b)
    assert bool(twin) is want
    if not want:  # and the table is the one the probe reads
        h1, h2, valid = placed[:3]
        bi, hit = K.probe_join_rows(rows, lg_b, h1, h2, valid)
        live = np.asarray(valid)
        assert np.array_equal(np.asarray(hit), live)
        assert np.array_equal(np.asarray(bi)[live], np.flatnonzero(live))


@pytest.mark.parametrize("seed", [3, 2147483659, 20261004])
@pytest.mark.parametrize("planted", [0, 1, 3])
def test_twin_is_numpy_count_of_repeated_pairs(seed, planted):
    """Crowded buckets (400 rows over 16), some rows without a key, and
    ``planted`` rows that copy another row's pair: the flag is whether a
    plain count finds a pair more than once among the keyed rows."""
    rng = np.random.default_rng(seed)
    n = 400
    h1 = rng.integers(0, 1 << 63, n, dtype=np.uint64) << np.uint64(1)
    h2 = rng.integers(0, 1 << 16, n, dtype=np.uint64)
    valid = rng.random(n) < 0.8
    for _ in range(planted):
        a, b = rng.choice(n, 2, replace=False)
        h1[b], h2[b] = h1[a], h2[a]
    # rows of one h1 and another h2, and the other way round
    h1[7], h2[7] = h1[8], h2[8] ^ np.uint64(1)
    h2[9] = h2[10]
    pairs = list(zip(h1[valid].tolist(), h2[valid].tolist()))
    repeated = len(pairs) - len(set(pairs))
    p = _placed(h1, h2, valid)
    slots = _slots_for(int(np.asarray(p[4])[valid].max()) + 1)
    assert slots > 16  # every width of the compare's loop and then some
    rows, twin = K.join_rows_table(p, slots, LG_B)
    assert bool(twin) is (repeated > 0)
