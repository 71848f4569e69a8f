"""The exists build's marks (exec/join.py ``HashJoinExec._fused_exists``,
``_run_heads``, ``_exists_mark_caps``, ``_exists_mark``): each build batch
marks the first live row of each run of equal adjacent keys, packed by one
sort into C, the bucket of the most heads a batch of its capacity holds, at
most the capacity.

What is held, for each case, semi and anti alike: the presence map the
build leaves holds exactly the live build keys; the answer equals the plain
reference (a probe row is kept by semi where its key is among the live
build keys, by anti where it is not or is null); the heads the device
counts and marks equal a row-by-row loop's; and the counters
(``join_exists_mark_compact_total``, ``_indices_total``) and the
``exec:join-build`` span's ``mark_cap`` read the C each batch took. Cases: a
run whose first row is masked and a later one live; a run live, masked,
live; a run split by a batch boundary; a batch with no live row; null keys;
heads exactly at C; a short tail batch whose C is its whole capacity beside
full batches cut below theirs; a shuffled build and distinct keys, whose C
is the whole batch."""

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import bucket_capacity
from spark_rapids_tpu.exec import join as J
from spark_rapids_tpu.exec.fused import TpuFusedStageExec
from spark_rapids_tpu.exprs.expr import GreaterThan, col, lit
from spark_rapids_tpu.obs import gauges, span
from spark_rapids_tpu.plan import from_arrow
from spark_rapids_tpu.utils import tracing

BATCH = 4096
ROWS = 3 * BATCH
JOINS = ("left_semi", "left_anti")


def _runs(lengths, start=1000, step=3):
    """Ascending keys ``start``, ``start + step``, ... repeated ``lengths``
    times each, cut to ROWS."""
    keys = start + step * np.arange(len(lengths))
    return np.repeat(keys, lengths)[:ROWS].astype(np.int64)


def _first_row_masked():
    """Runs of three whose first row is masked and whose later rows are
    live; every fifth run wholly masked."""
    keys = _runs(np.full(ROWS // 3, 3))
    pos = np.arange(ROWS) % 3
    run = np.arange(ROWS) // 3
    return keys, (pos > 0) & (run % 5 != 0), None, (True,) * 3


def _live_masked_live():
    """Runs of three: live, masked, live. One head a run, where a rule
    that restarts after a masked row counts two (and would need 4,096)."""
    keys = _runs(np.full(ROWS // 3, 3))
    return keys, np.arange(ROWS) % 3 != 1, None, (True,) * 3


def _split_by_a_batch():
    """Runs of five, offset so that a run spans each batch boundary; the
    run over the first boundary is masked before it and live after it."""
    keys = _runs(np.concatenate([[4], np.full(ROWS // 5 + 1, 5)]))
    keep = np.ones(ROWS, np.bool_)
    keep[BATCH - 2:BATCH] = False
    assert keys[BATCH - 2] == keys[BATCH + 2] != keys[BATCH - 3]
    return keys, keep, None, (True,) * 3


def _no_live_batch():
    """The second batch keeps no row: it marks nothing."""
    keys = _runs(np.full(ROWS // 4, 4))
    keep = np.ones(ROWS, np.bool_)
    keep[BATCH:2 * BATCH] = False
    return keys, keep, None, (True,) * 3


def _null_keys():
    """A null key first in every seventh run and a few more nulls; nulls
    are never live, and one inside a run splits it."""
    keys = _runs(np.full(ROWS // 4, 4))
    valid = np.random.default_rng(21).random(ROWS) > 0.05
    valid[np.arange(0, ROWS, 28)] = False
    return keys, np.ones(ROWS, np.bool_), valid, (True,) * 3


def _heads_at_c():
    """Each batch holds 1,024 keys four times, all live: heads equal C."""
    return _runs(np.full(ROWS // 4, 4)), np.ones(ROWS, np.bool_), None, \
        (True,) * 3


def _tail_batch():
    """Two full batches and a tail of 1,000 rows (capacity 1,024) whose
    333 heads take a bucket of 1,024: the tail scatters its capacity."""
    keys = _runs(np.full(ROWS // 3, 3))[:2 * BATCH + 1000]
    return keys, np.ones(len(keys), np.bool_), None, (True, True, False)


def _shuffled():
    """Repeated keys in random order, 63% live: a head about a live row."""
    rng = np.random.default_rng(22)
    keys = rng.permutation(_runs(rng.integers(1, 8, ROWS)))
    return keys, rng.random(ROWS) < 0.632, None, (False,) * 3


def _distinct():
    """Distinct ascending keys, all live, as an anti join's build may be."""
    return _runs(np.ones(ROWS, np.int64)), np.ones(ROWS, np.bool_), None, \
        (False,) * 3


CASES = {"first-row-masked": _first_row_masked,
         "live-masked-live": _live_masked_live,
         "split-by-a-batch": _split_by_a_batch,
         "no-live-batch": _no_live_batch, "null-keys": _null_keys,
         "heads-at-c": _heads_at_c, "tail-batch": _tail_batch,
         "shuffled": _shuffled, "distinct": _distinct}


def _heads_by_loop(keys, live) -> int:
    """The run-head rule row by row: a live row is a head where no earlier
    row of its run of equal adjacent keys was live. A null (None) is in no
    key's run: the device holds no key in its data."""
    heads, seen = 0, False
    for i, (k, ok) in enumerate(zip(keys, live)):
        if i == 0 or k is None or k != keys[i - 1]:
            seen = False
        if ok and not seen:
            heads, seen = heads + 1, True
    return heads


def _batches(keys, live):
    """(capacity, keys, live) of each build batch, as the scan cuts them."""
    return [(bucket_capacity(len(keys[i:i + BATCH])), keys[i:i + BATCH],
             live[i:i + BATCH]) for i in range(0, len(keys), BATCH)]


def _expected_caps(keys, live):
    """(capacity, C) a batch, from the loop's heads."""
    parts = _batches(keys, live)
    most = {}
    for cap, k, ok in parts:
        most[cap] = max(most.get(cap, 0), _heads_by_loop(k, ok))
    return [(cap, min(bucket_capacity(most[cap]), cap))
            for cap, _, _ in parts]


def _data(case):
    keys, keep, valid, cut = CASES[case]()
    live = keep if valid is None else keep & valid
    seen = keys.tolist() if valid is None else [
        k if v else None for k, v in zip(keys.tolist(), valid)]
    rng = np.random.default_rng(23)
    lo, hi = int(keys.min()) - 50, int(keys.max()) + 50
    pk = rng.integers(lo, hi, 2 * BATCH)
    pvalid = rng.random(2 * BATCH) > 0.05
    build = pa.table({"rk": pa.array(keys, pa.int64(),
                                     mask=None if valid is None else ~valid),
                      "f": pa.array(keep.astype(np.int64))})
    probe = pa.table({"k": pa.array(pk, pa.int64(), mask=~pvalid),
                      "v": pa.array(np.arange(len(pk)), pa.int64())})
    return keys, seen, live, cut, build, probe


def _df(build, probe, how):
    """The probe (a fused stage) semi- or anti-joined on k = rk to the
    build rows whose flag is set (a fused stage of its own, read through
    its masks)."""
    lhs = from_arrow(probe, batch_rows=BATCH, partitions=1).filter(
        GreaterThan(col("v"), lit(-1)))
    rhs = from_arrow(build, batch_rows=BATCH, partitions=1).filter(
        GreaterThan(col("f"), lit(0))).select("rk")
    return lhs.join(rhs, how=how, left_on="k", right_on="rk")


def _join_of(df) -> J.HashJoinExec:
    stage = df.physical_plan()
    while not isinstance(stage, TpuFusedStageExec):
        stage = stage.children[0]
    join, = [op for op in stage.segments if isinstance(op, J.HashJoinExec)]
    return join


@pytest.mark.parametrize("how", JOINS)
@pytest.mark.parametrize("case", CASES)
def test_the_marks_build_the_plain_map_and_the_reference_answer(case, how):
    keys, seen, live, cut, build, probe = _data(case)
    df = _df(build, probe, how)
    before = gauges.snapshot()
    tracing.set_capture(True, clear=True)
    try:
        with span.activate(span.new_trace()):
            out = df.to_arrow()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
    after = gauges.snapshot()
    # the answer: the plain reference
    want_keys = set(keys[live].tolist())
    pk, pv = probe["k"].to_pylist(), probe["v"].to_pylist()
    hit = [k is not None and k in want_keys for k in pk]
    want = sorted(((k, v) for k, v, h in zip(pk, pv, hit)
                   if h == (how == "left_semi")), key=repr)
    got = sorted(zip(out["k"].to_pylist(), out["v"].to_pylist()),
                 key=repr)
    assert got == want
    # the marks: each batch's C, counted and on the span
    caps = _expected_caps(seen, live)
    assert tuple(c < capacity for capacity, c in caps) == cut
    delta = {k: after[k] - before[k] for k in after
             if k.startswith("join_exists_mark_")}
    assert delta == {"join_exists_mark_compact_total": len(caps),
                     "join_exists_mark_indices_total": sum(c for _, c in caps)}
    builds = [e["args"] for e in events if e["name"] == "exec:join-build"]
    assert [(b["path"], b["mark_cap"]) for b in builds] == [
        ("exists", max(c for _, c in caps))]
    # the map: the live build keys' offsets from the least, and no other
    present, first = _join_of(df)._fused_exists(0).tbl
    plain = np.zeros(present.shape[0], np.bool_)
    plain[np.unique(keys[live]) - int(first)] = True
    assert np.array_equal(np.asarray(present), plain)


@pytest.mark.parametrize("case", CASES)
def test_the_device_counts_the_heads_the_loop_counts(case):
    """``_exists_stats``'s fourth number and its heads mask, batch by
    batch, against the row-by-row rule."""
    _, seen, live, _, build, probe = _data(case)
    join = _join_of(_df(build, probe, "left_semi"))
    got = [J._exists_stats(b, join._rkeys[0], m)
           for b, m in join._exists_batches(0)]
    want = [_heads_by_loop(k, ok) for _, k, ok in _batches(seen, live)]
    assert [int(n[3]) for n, _ in got] == want
    assert [int(np.asarray(h).sum()) for _, h in got] == want
