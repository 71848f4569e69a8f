"""The runs rung of the fused probe: an inner join whose build keys repeat,
probed inside its fused stage (exec/join.py ``_prepare_runs``,
``_FusedJoinProbe._runs_fn``; exec/kernels.py ``join_runs``,
``join_runs_table``, ``expand_runs``). The ladder is dense -> unique ->
runs -> the unfused ``ht``/sorted path, chosen by the data alone.

What is held: the kernels against numpy (every key's rows in one run, the
heads placed one a slot of their bucket, the pairs a run expands into);
through the planner the same multiset of rows as the unfused operator
(the general hash table) on a skewed build; pairs that outgrow what the
stage learned run again unshrunk, exact and without a fallback; a build
whose keys are unique keeps the unique rung."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.config.conf import RapidsConf
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exprs.expr import GreaterThan, Sum, col, lit
from spark_rapids_tpu.obs import gauges
from spark_rapids_tpu.plan import from_arrow

import jax.numpy as jnp

BATCH = 8192
PATHS = ("dense", "unique", "runs", "ht", "sorted")


def _paths(before, after):
    return {p: after[f"join_build_path_{p}_total"]
            - before[f"join_build_path_{p}_total"] for p in PATHS}


def _stage(df):
    from spark_rapids_tpu.exec.fused import TpuFusedStageExec

    def find(node):
        if isinstance(node, TpuFusedStageExec):
            return node
        return next((s for s in map(find, node.children) if s), None)
    return find(df.physical_plan())


@pytest.mark.parametrize("ncols", [1, 2], ids=["by-value", "by-hash"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_join_runs_puts_every_key_in_one_run(seed, ncols):
    """``K.join_runs`` over int64 keys that repeat (up to 40 times), with
    nulls and padding, as one key (sorted by its value) or two (sorted by
    their hash): each head starts a run of ``count`` rows of one key
    (``order`` maps them to the build's rows), every keyed row is in
    exactly one run, and the heads take distinct (bucket, rank) slots with
    ranks under the largest bucket it reports."""
    rng = np.random.default_rng(seed)
    n, cap = 3000, 4096
    keys = rng.integers(0, 400, n) * 7919
    keys[:40] = 5  # one long run
    valid = rng.random(n) > 0.05
    data = np.zeros(cap, np.int64)
    data[:n] = keys
    vmask = np.zeros(cap, bool)
    vmask[:n] = valid
    col = DeviceColumn(T.LONG, jnp.asarray(data), jnp.asarray(vmask))
    batch = ColumnarBatch([col] * ncols, jnp.int32(n))
    (order, count), placed, stats = K.join_runs(batch, tuple(range(ncols)))
    order, count = np.asarray(order), np.asarray(count)
    largest, longest, rows, mixed = (int(x) for x in stats)
    live = keys[valid]
    _, mult = np.unique(live, return_counts=True)
    assert rows == valid.sum() and longest == mult.max() and not mixed
    heads = np.flatnonzero(count)
    covered = []
    for h in heads:
        run = order[h:h + count[h]]
        assert len(set(data[run].tolist())) == 1 and vmask[run].all()
        covered.extend(run.tolist())
    assert sorted(covered) == sorted(np.flatnonzero(vmask).tolist())
    assert len(heads) == len(mult)
    _, _, head, bucket, rank = (np.asarray(a) for a in placed)
    assert np.array_equal(np.flatnonzero(head), heads)
    slots = set(zip(bucket[heads].tolist(), rank[heads].tolist()))
    assert len(slots) == len(heads) and rank[heads].max() + 1 == largest


def test_expand_runs_repeats_each_row_its_run_s_length():
    """``K.expand_runs``: row r with ``cnt[r]`` build rows from sorted
    position ``start[r]`` fills that many consecutive slots; the total is
    reported whole when the slots cannot hold it."""
    rng = np.random.default_rng(9)
    cnt = rng.integers(0, 5, 300).astype(np.int32)
    cnt[:7] = 0
    start = rng.integers(0, 10_000, 300).astype(np.int32)
    want_row = np.repeat(np.arange(300), cnt)
    want_pos = np.concatenate([np.arange(s, s + c) for s, c
                               in zip(start, cnt)])
    for out_cap in (1024, 256):
        row, pos, total = K.expand_runs(jnp.asarray(start), jnp.asarray(cnt),
                                        out_cap)
        k = min(out_cap, len(want_row))
        assert int(total) == cnt.sum()
        assert np.array_equal(np.asarray(row)[:k], want_row[:k])
        assert np.array_equal(np.asarray(pos)[:k], want_pos[:k])


def _skewed_build():
    """One key 5,000 times, the rest unique; each probe batch of 8,192
    rows meets the long run once, so its pairs fit the batch."""
    rk = np.concatenate([np.full(5000, 7), np.arange(900, 1900)])
    right = pa.table({"rk": pa.array(rk, pa.int64()),
                      "w": pa.array(np.arange(len(rk)), pa.int64())})
    k = np.arange(2 * BATCH, dtype=np.int64) % 4000 + 1000
    k[[5, BATCH + 5]] = 7
    left = pa.table({"k": pa.array(k),
                     "v": pa.array(np.arange(2 * BATCH), pa.int64())})
    return left, right


def _skewed_df(left, right, conf=None):
    return (from_arrow(left, conf, batch_rows=BATCH, partitions=1)
            .filter(GreaterThan(col("v"), lit(-1)))
            .join(from_arrow(right, conf, partitions=1),
                  left_on="k", right_on="rk"))


def test_a_skewed_build_gives_the_rows_of_the_unfused_path():
    """The same multiset of (k, v, rk, w) rows through the runs rung as
    through the unfused operator, which takes the general hash table; the
    fused stage builds once, by runs, and never falls back."""
    left, right = _skewed_build()
    before = gauges.snapshot()
    fused = _skewed_df(left, right).to_arrow()
    after = gauges.snapshot()
    assert _paths(before, after) == {"dense": 0, "unique": 0, "runs": 1,
                                     "ht": 0, "sorted": 0}
    assert after["fused_fallback_total"] == before["fused_fallback_total"]
    keys, mult = np.unique(right["rk"].to_numpy(), return_counts=True)
    at = np.searchsorted(keys, left["k"].to_numpy())
    pairs = int(np.where(keys[np.minimum(at, len(keys) - 1)]
                         == left["k"].to_numpy(),
                         mult[np.minimum(at, len(keys) - 1)], 0).sum())
    assert after["join_run_pairs_total"] - before[
        "join_run_pairs_total"] == fused.num_rows == pairs
    off = RapidsConf({"spark.rapids.tpu.sql.fusion.enabled": False})
    before = gauges.snapshot()
    unfused = _skewed_df(left, right, off).to_arrow()
    assert _paths(before, gauges.snapshot())["ht"] == 1

    def rows(t):
        return sorted(zip(*(t[c].to_pylist() for c in ("k", "v", "rk",
                                                         "w"))))
    assert rows(fused) == rows(unfused)


@pytest.mark.parametrize("agg", [False, True], ids=["plain", "aggregate"])
def test_pairs_that_outgrow_the_learned_capacity_run_again_unshrunk(agg):
    """Every build key twice: the first probe batch makes 20 pairs, and the
    stage learns 1,024; the second makes 6,000, which its own 8,192 slots
    hold. What was cut runs again unshrunk: the same rows, no fallback to
    the unfused chain, and the stage stops shrinking that partition."""
    k = np.arange(2 * BATCH, dtype=np.int64) % 500 + 10_000  # misses...
    k[:10] = np.arange(10)                                    # ...but ten
    k[BATCH:BATCH + 3000] = np.arange(3000) % 500             # and 3,000
    left = pa.table({"k": pa.array(k),
                     "v": pa.array(np.ones(2 * BATCH, np.int64))})
    rk = np.repeat(np.arange(500, dtype=np.int64), 2)
    right = pa.table({"rk": pa.array(rk), "w": pa.array(rk)})
    df = (from_arrow(left, batch_rows=BATCH, partitions=1)
          .filter(GreaterThan(col("v"), lit(0)))
          .join(from_arrow(right, partitions=1), left_on="k", right_on="rk"))
    if agg:
        df = df.group_by("k").agg(Sum(col("w")).alias("s"))
    hits = np.concatenate([k[:10], k[BATCH:BATCH + 3000]])
    before = gauges.snapshot()
    out = df.to_arrow()
    after = gauges.snapshot()
    assert _paths(before, after)["runs"] == 1
    assert after["fused_fallback_total"] == before["fused_fallback_total"]
    assert _stage(df)._learned == {0: None}
    if agg:
        got = dict(zip(out["k"].to_pylist(), out["s"].to_pylist()))
        keys, counts = np.unique(hits, return_counts=True)
        assert got == {int(x): 2 * int(x) * int(c)
                       for x, c in zip(keys, counts)}
    else:
        assert sorted(out["k"].to_pylist()) == sorted(
            np.repeat(hits, 2).tolist())
        assert out["w"].to_pylist() == out["k"].to_pylist()


def test_unique_builds_keep_the_unique_rung():
    """Keys past ``join.denseKey.maxDomain`` that do not repeat (Q3's
    `orders` build at SF10): the unique table, not the runs."""
    far = (1 << 26) + np.arange(3000, dtype=np.int64) * 3
    right = pa.table({"rk": pa.array(far), "w": pa.array(far)})
    left = pa.table({"k": pa.array(np.concatenate([far[::2], far[:5] + 1])),
                     "v": pa.array(np.ones(1505, np.int64))})
    df = (from_arrow(left, batch_rows=1024, partitions=1)
          .filter(GreaterThan(col("v"), lit(0)))
          .join(from_arrow(right, partitions=1), left_on="k", right_on="rk"))
    before = gauges.snapshot()
    out = df.to_arrow()
    assert _paths(before, gauges.snapshot()) == {
        "dense": 0, "unique": 1, "runs": 0, "ht": 0, "sorted": 0}
    assert sorted(out["k"].to_pylist()) == far[::2].tolist()
