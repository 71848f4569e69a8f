"""The runs rung of the fused probe: an inner join whose build keys repeat,
probed inside its fused stage (exec/join.py ``_prepare_runs``,
``_FusedJoinProbe._runs_fn``; exec/kernels.py ``join_runs``,
``join_runs_table``, ``join_runs_ranged``, ``join_run_heads``,
``merge_run_heads``, ``expand_runs``). The ladder is dense -> unique ->
runs -> the unfused ``ht``/sorted path, chosen by the data alone; the runs
rung probes by merging u32 offsets over one ranged integer key whose heads
fit a probe batch, and through the heads' table otherwise.

What is held: the kernels against numpy (every key's rows in one run, the
heads placed one a slot of their bucket, the u32 build's runs those of the
u64 sort, the merge's rows and heads, the pairs a run expands into);
through the planner the same multiset of rows as the unfused operator
(the general hash table) on a skewed build, and over ranged keys the merge
form's rows as the table form's and numpy's; pairs that outgrow what the
stage learned run again unshrunk, exact and without a fallback; heads
wider than a probe batch keep the table form; a build whose keys are
unique keeps the unique rung."""

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


def _int_col(values, valid):
    return DeviceColumn(T.LONG, jnp.asarray(values), jnp.asarray(valid))


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_the_u32_build_gives_the_runs_of_the_u64_sort(seed):
    """``K.join_runs_ranged`` + ``K.join_run_heads`` over one ranged key
    (negative keys, nulls, padding): the same ``order`` as ``K.join_runs``'
    sort by value, each head's run length where that one writes it, the
    heads in ascending offset and nothing past them."""
    rng = np.random.default_rng(seed)
    n, cap = 3000, 4096
    keys = rng.integers(-300, 300, n) * 11
    data = np.zeros(cap, np.int64)
    data[:n] = keys
    vmask = np.zeros(cap, bool)
    vmask[:n] = rng.random(n) > 0.05
    batch = ColumnarBatch([_int_col(data, vmask)], jnp.int32(n))
    (order, count), _, stats = K.join_runs(batch, (0,))
    kmin = int(keys[vmask[:n]].min())
    (offsets, order32), (heads, longest, rows) = K.join_runs_ranged(
        batch, 0, jnp.int64(kmin))
    assert np.array_equal(np.asarray(order32), np.asarray(order))
    assert (int(longest), int(rows)) == (int(stats[1]), int(stats[2]))
    live = np.asarray(offsets) != K.NO_OFFSET
    assert np.array_equal(np.asarray(offsets)[live],
                          data[np.asarray(order)][live] - kmin)
    hcap = 2048
    hoff, hpos, hcnt = (np.asarray(a) for a in
                        K.join_run_heads(offsets, hcap))
    nh = int(heads)
    assert nh == len(np.unique(keys[vmask[:n]]))
    want = np.zeros(cap, np.int32)
    want[hpos[:nh]] = hcnt[:nh]
    assert np.array_equal(want, np.asarray(count))
    assert np.all(np.diff(hoff[:nh].astype(np.int64)) > 0)
    assert np.all(hoff[nh:] == K.NO_OFFSET) and not hcnt[nh:].any()


@pytest.mark.parametrize("hcap", [512, 64], ids=["fits", "cut"])
def test_merge_finds_each_probe_row_s_head_in_row_order(hcap):
    """``K.merge_run_heads``: the probe rows whose offset is a head's, in
    row order, each with its head's index; rows with no key never match,
    and a count above ``hcap`` is reported whole."""
    rng = np.random.default_rng(3)
    hoff = np.full(256, K.NO_OFFSET, np.uint32)
    heads = np.sort(rng.choice(1000, 200, replace=False)).astype(np.uint32)
    hoff[:200] = heads
    off = rng.integers(0, 1100, 2048).astype(np.uint32)
    off[rng.random(2048) < 0.1] = K.NO_OFFSET
    rows, at, n = K.merge_run_heads(jnp.asarray(off), jnp.asarray(hoff),
                                    hcap)
    where = {int(h): i for i, h in enumerate(heads)}
    want = [r for r in range(2048) if int(off[r]) in where]
    assert int(n) == len(want)
    k = min(hcap, len(want))
    assert np.asarray(rows)[:k].tolist() == want[:k]
    assert np.asarray(at)[:k].tolist() == [where[int(off[r])]
                                           for r in want[:k]]
    assert not np.asarray(rows)[k:].any() and not np.asarray(at)[k:].any()


def _ranged_case(case):
    """(probe, build, filter threshold) for one case of the ranged runs
    rung; the build's keys repeat 1-7 times over a range that reads."""
    rng = np.random.default_rng(len(case))
    keys = np.arange(-600, 2400, 10, dtype=np.int64)
    rk = np.repeat(keys, rng.integers(1, 8, len(keys)))
    rng.shuffle(rk)
    rvalid = np.ones(len(rk), bool)
    n = 3 * BATCH
    k = rng.choice(keys, n) + rng.integers(0, 10, n)  # one in ten hits
    kvalid = np.ones(n, bool)
    v = np.arange(n, dtype=np.int64)
    low = -1
    if case == "nulls":
        rvalid = rng.random(len(rk)) > 0.2
        kvalid = rng.random(n) > 0.2
    elif case == "live-mask":
        v = rng.integers(-5, 5, n)
        low = 0
    elif case == "out-of-range":
        far = rng.integers(0, 4, n)
        k = np.where(far == 0, k, np.select(
            [far == 1, far == 2], [k - 10_000, k + 10_000], -k - 1))
    elif case == "no-hits":
        k = k * 0 + 2401
    elif case == "short":
        # the first batch meets 20 runs, the last 1,500 rows of them
        k[:] = 2401
        k[:20] = keys[:20]
        k[2 * BATCH:2 * BATCH + 1500] = keys[rng.integers(0, 50, 1500)]
    right = pa.table({"rk": pa.array(rk, mask=~rvalid),
                      "w": pa.array(np.arange(len(rk), dtype=np.int64))})
    left = pa.table({"k": pa.array(k, mask=~kvalid), "v": pa.array(v)})
    return left, right, low


def _numpy_join(left, right, low):
    """(k, v, rk, w) rows of the inner join, by a dict of the build."""
    at = {}
    for key, w in zip(right["rk"].to_pylist(), right["w"].to_pylist()):
        if key is not None:
            at.setdefault(key, []).append(w)
    return sorted((key, v, key, w) for key, v in zip(
        left["k"].to_pylist(), left["v"].to_pylist())
        if key is not None and v > low for w in at.get(key, ()))


CASES = ("repeats", "nulls", "live-mask", "out-of-range", "no-hits", "short")


@pytest.mark.parametrize("case", CASES)
def test_the_ranged_form_joins_as_the_table_form_and_numpy(case,
                                                           monkeypatch):
    """Over one ranged integer key the runs rung probes by merging
    offsets; the same rows as the table form (the hash form, taken where
    the stage gives no probe capacity) and as a plain join in numpy:
    keys repeated 1-7 times, null keys on both sides, a filter's mask on
    the probe, probe keys under the least key, over the greatest and
    negative, no hits, and pairs that outgrow what the stage learned
    (run again unshrunk, no fallback)."""
    from spark_rapids_tpu.exec import fused
    left, right, low = _ranged_case(case)
    want = _numpy_join(left, right, low)

    def run():
        df = (from_arrow(left, batch_rows=BATCH, partitions=1)
              .filter(GreaterThan(col("v"), lit(low)))
              .join(from_arrow(right, partitions=1), left_on="k",
                    right_on="rk"))
        before = gauges.snapshot()
        out = df.to_arrow()
        after = gauges.snapshot()
        assert after["fused_fallback_total"] == before["fused_fallback_total"]
        assert _paths(before, after)["runs"] == 1
        if case == "short":
            assert _stage(df)._learned == {0: None}
        got = sorted(zip(*(out[c].to_pylist() for c in ("k", "v", "rk",
                                                        "w"))))
        return got, {f: after[f"join_run_probe_{f}_total"]
                     - before[f"join_run_probe_{f}_total"]
                     for f in ("merge", "table")}

    merged, forms = run()
    assert forms == {"merge": 1, "table": 0}
    assert merged == want
    monkeypatch.setattr(fused, "_cap_after", lambda cap, segs: None)
    hashed, forms = run()
    assert forms == {"merge": 0, "table": 1}
    assert hashed == want


def test_heads_wider_than_the_probe_batch_keep_the_table_form():
    """3,000 keys twice over (a bucket of 4,096 heads) probed in batches
    of 1,024: the merge would sort more heads than rows, so the build
    keeps the presence and heads' tables, says so on its span, and joins
    the same rows."""
    from spark_rapids_tpu.obs import span
    from spark_rapids_tpu.utils import tracing
    rk = np.repeat(np.arange(3000, dtype=np.int64) * 5, 2)
    right = pa.table({"rk": pa.array(rk), "w": pa.array(rk + 1)})
    k = np.arange(4096, dtype=np.int64) * 4
    left = pa.table({"k": pa.array(k), "v": pa.array(np.ones(4096,
                                                             np.int64))})
    df = (from_arrow(left, batch_rows=1024, partitions=1)
          .filter(GreaterThan(col("v"), lit(0)))
          .join(from_arrow(right, partitions=1), left_on="k", right_on="rk"))
    before = gauges.snapshot()
    tracing.set_capture(True, clear=True)
    try:
        with span.activate(span.new_trace()):
            out = df.to_arrow()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
    after = gauges.snapshot()
    assert {f: after[f"join_run_probe_{f}_total"]
            - before[f"join_run_probe_{f}_total"]
            for f in ("merge", "table")} == {"merge": 0, "table": 1}
    builds = [e["args"] for e in events if e["name"] == "exec:join-build"]
    assert [(b["path"], b["probe"]) for b in builds] == [("runs", "table")]
    hits = k[(k % 5 == 0) & (k < 15000)]
    assert sorted(out["k"].to_pylist()) == sorted(np.repeat(hits, 2).tolist())
    assert out["w"].to_pylist() == [x + 1 for x in out["k"].to_pylist()]


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
