"""The fused streaming aggregate over a partition shaped like TPC-H SF10's
``lineitem`` (benchmark cell ``sf10_q1_agg1``): many full batches and a last
one short enough to fall into a smaller capacity bucket (SF10: 57 batches of
2**20 rows and one of 231,165 rows, capacity 2**18; here 2,048-row batches
and a last one of 231 rows, capacity 1,024).

What is held: Q1 equals the benchmark's plain reference digit for digit
through the planner and over the wire, for the default window and others;
the step programs a partition binds do not grow with its batch count, and
capacities that interleave are bounded in dispatches and in programs; a
shorter last window changes no digit; an overflow that only the tail sees
still falls back; the window dispatches are spans and the table's upload
is timed.
"""

import os
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

import conftest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import datagen  # noqa: E402  (benchmark/datagen.py: numpy and pyarrow only)

from spark_rapids_tpu.config import conf as C  # noqa: E402
from spark_rapids_tpu.exec import fused  # noqa: E402
from spark_rapids_tpu.obs import gauges, span  # noqa: E402
from spark_rapids_tpu.plan import from_arrow  # noqa: E402
from spark_rapids_tpu.utils import tracing  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _release_programs():
    """Once XLA:CPU has loaded it, a Q1 step program of 7 unrolled bodies
    is thousands of memory mappings, and a process may hold 65,530
    (``vm.max_map_count``): 17,522 after this file's first case in a fresh
    process, 45,895 after its last (``/proc/self/maps``). So these cases
    start from none, whatever the worker held (conftest.py gives them back
    again when the next module starts)."""
    conftest.drop_programs()


BATCH = 2048
SHORT = 231  # rows of the last batch: capacity 1,024, a bucket below BATCH
SEEDS = (11, 2147483693, 314159265)


def _q1():
    import harness
    return harness.load_by_path("queries", "q1")


def _lineitem(seed: int, batches: int):
    """(raw columns, Arrow table) of ``batches`` batches: all full but the
    last, which holds SHORT rows. Cut from datagen's SF0.02 ``lineitem``."""
    rows = (batches - 1) * BATCH + SHORT
    raw = datagen.make(["lineitem"], 0.02, seed)["lineitem"]
    cut = {}
    for name, v in raw.items():
        if isinstance(v, datagen.Text):
            cut[name] = datagen.Text(v.offsets[:rows + 1],
                                     v.data[:v.offsets[rows]])
        else:
            cut[name] = v[:rows]
    table = datagen.arrow(cut)
    assert table.num_rows == rows
    return {"lineitem": cut}, table


def _assert_equals_reference(table: pa.Table, raw: dict):
    """The benchmark's own comparison (benchmark/compare.py): columns, row
    count, keys, counts, row order and every DECIMAL digit."""
    import compare
    q1 = _q1()
    r = compare.answer_readings(table, q1.reference(raw), q1)
    assert (r["wrong"], r["units_off"]) == (0, 0), r["why"]
    assert table.num_rows == 4


def _conf(window=None) -> C.RapidsConf:
    values = {}
    if window is not None:
        values[C.FUSION_AGG_WINDOW.key] = window
    return C.RapidsConf(values)


def _step_programs() -> list:
    """[(capacity, window length)] of the step programs bound since the
    last clear: each holds that many unrolled chain -> first-pass bodies."""
    out = []
    for key in fused._STEP_KEYS:
        i = key.index("step")
        out.append((key[i + 3], key[i + 4]))
    return sorted(out)


@pytest.mark.parametrize("path,window", [("apply", None), ("apply", 3),
                                         ("submit", None)])
@pytest.mark.parametrize("seed", SEEDS)
def test_q1_at_58_batches_equals_the_reference(seed, path, window):
    """58 batches, the shape of SF10's partition: through Overrides.apply
    (DataFrame.to_arrow) with the default window of 7 and with one of 3
    (19 full windows), and through NetClient.submit() with the default.
    The window is the conf's: the stage no longer explores others."""
    raw, table = _lineitem(seed, 58)
    conf = _conf(window)
    if path == "apply":
        df = from_arrow(table, conf=conf, batch_rows=BATCH, partitions=1)
        out = _q1().build({"lineitem": df}).to_arrow()
    else:
        from spark_rapids_tpu.net import NetClient, QueryFrontend
        from spark_rapids_tpu.serve import QueryServer
        srv = QueryServer(conf)
        fe = QueryFrontend(srv, tables={"lineitem": table},
                           host="127.0.0.1", port=0)
        try:
            cl = NetClient(fe.host, fe.port, conf=conf, timeout_s=600)
            try:
                df = cl.table("lineitem", batch_rows=BATCH, partitions=1)
                out = cl.submit(_q1().build({"lineitem": df}), name="q1",
                                timeout_s=600)
            finally:
                cl.close()
        finally:
            fe.close()
            srv.close()
    _assert_equals_reference(out, raw)


@pytest.mark.parametrize("batches,want", [
    # the seed takes the first batch; the rest run in windows of 7
    (8, [(1024, 1), (2048, 6)]),    # 6 full batches: one window, 6 bodies
    (23, [(1024, 1), (2048, 7)]),   # 3 full windows of 7
    (58, [(1024, 1), (2048, 7)]),   # 8 full windows of 7
    (26, [(1024, 1), (2048, 3), (2048, 7)]),  # 3 full windows, a tail of 3
])
def test_step_programs_do_not_grow_with_the_batch_count(batches, want):
    """A partition of N batches with a short last one binds at most three
    step programs whatever N: the window's (7 bodies; fewer only where
    the whole partition is shorter than a window), one for a tail of full
    batches where N leaves one (N = 26: 3 bodies), and the short batch's
    own (1 body). Before, every window explored and every tail was a
    program (6 at 58 batches, 41 bodies)."""
    raw, table = _lineitem(SEEDS[0], batches)
    fused._STEP_KEYS.clear()
    df = from_arrow(table, conf=_conf(), batch_rows=BATCH, partitions=1)
    out = _q1().build({"lineitem": df}).to_arrow()
    _assert_equals_reference(out, raw)
    assert _step_programs() == want
    assert gauges.snapshot()["fused_step_programs_total"] == len(want) <= 3


def _step_spans(events) -> dict:
    """{trace id: [(batches, rows) of each exec:agg-step, in order]}."""
    steps = {}
    for e in events:
        if e["name"] == "exec:agg-step":
            a = e["args"]
            assert "padded" not in a  # no window runs a longer one's program
            steps.setdefault(a["trace_id"], []).append(
                (a["batches"], a["rows"]))
    return steps


A, B = 1024, 2048  # two batch capacities (their row counts fill them)


@pytest.mark.parametrize("caps,dispatches,programs", [
    # after the seed the capacities alternate: a window of one batch each
    ([A] + [B, A] * 7 + [B], [(1, A)] + [(1, B), (1, A)] * 7 + [(1, B)],
     [(A, 1), (B, 1)]),
    # runs of equal capacity: 9 x A is a window of 7 and one of 2
    ([A] + [A] * 9 + [B] * 3 + [A] * 2 + [B] * 3,
     [(1, A), (7, 7 * A), (2, 2 * A), (3, 3 * B), (2, 2 * A), (3, 3 * B)],
     [(A, 2), (A, 7), (B, 3)]),
], ids=["alternating", "runs"])
def test_interleaved_capacities_are_bounded(caps, dispatches, programs):
    """A partition whose batch capacities interleave (shuffle reads, row
    groups, post-join batches): a window never mixes capacities, so it
    pays one dispatch per run of equal capacity (and per full window
    inside a run), and binds one program per (capacity, window length)
    met, never one per arrangement of capacities; no digit changes."""
    from test_fusion import canon, rows
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import batch_from_arrow
    from spark_rapids_tpu.exec import HashAggregateExec, fuse_exec
    from spark_rapids_tpu.exec.base import BatchSourceExec
    from spark_rapids_tpu.exprs.expr import Count, Sum, col
    n = sum(caps)
    rng = np.random.default_rng(5)
    t = pa.table({"k": pa.array(rng.integers(0, 29, n), pa.int64()),
                  "v": pa.array(rng.integers(-10**6, 10**6, n), pa.int64())})
    starts = np.concatenate([[0], np.cumsum(caps)])

    def build():
        batches = [batch_from_arrow(t.slice(int(o), c))
                   for o, c in zip(starts, caps)]
        assert [b.capacity for b in batches] == caps
        return HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s"), Count().alias("n")],
            BatchSourceExec([batches], T.Schema.from_arrow(t.schema)))
    expect = canon(rows(build()))
    stage = fuse_exec(build())
    fused._STEP_KEYS.clear()
    tracing.set_capture(True, clear=True)
    try:
        with span.span("query:execute"):  # steps record under a trace only
            assert canon(rows(stage)) == expect
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
    assert stage.metrics["numFallbacks"].value == 0
    (got,) = _step_spans(events).values()
    assert got == dispatches
    runs = 1 + sum(a != b for a, b in zip(caps[1:], caps[2:]))
    assert len(got) - 1 <= runs + (len(caps) - 1) // C.FUSION_AGG_WINDOW.default
    assert _step_programs() == programs


def test_overflow_seen_only_by_the_tail_falls_back():
    """27 batches of 128 rows: the seed, 3 full windows over 10 groups,
    and a tail of 5 whose rows are each a group of their own: the tail's
    overflow flag alone sends the partition back to the unfused chain,
    and no truncated buffer escapes."""
    from test_fusion import canon, rows, source
    from spark_rapids_tpu.exec import HashAggregateExec, fuse_exec
    from spark_rapids_tpu.exprs.expr import Sum, col
    n = 27 * 128
    k = np.arange(n, dtype=np.int64) % 10
    k[22 * 128:] = 1000 + np.arange(n - 22 * 128)
    t = pa.table({"k": pa.array(k), "v": pa.array(np.ones(n, np.int64))})

    def build():
        return HashAggregateExec([col("k")], [Sum(col("v")).alias("s")],
                                 source(t, batch_rows=128))
    expect = canon(rows(build()))
    stage = fuse_exec(build())
    assert canon(rows(stage)) == expect
    assert stage.metrics["numFallbacks"].value == 1


def test_window_dispatches_are_spans_and_the_upload_is_timed():
    """A served Q1 over 26 batches: per request the seed's exec:agg-step,
    3 full windows, the tail of 3 and the short batch alone, no digit
    changed; the table's first scan adds its upload to
    ingest_upload_ns_total once, from a thread beside the plan."""
    from spark_rapids_tpu.net import NetClient, QueryFrontend
    from spark_rapids_tpu.serve import QueryServer
    raw, table = _lineitem(SEEDS[1], 26)
    conf = _conf()
    before = gauges.snapshot()
    srv = QueryServer(conf)
    fe = QueryFrontend(srv, tables={"lineitem": table}, host="127.0.0.1",
                       port=0)
    tracing.set_capture(True, clear=True)
    try:
        cl = NetClient(fe.host, fe.port, conf=conf, timeout_s=600)
        try:
            df = cl.table("lineitem", batch_rows=BATCH, partitions=1)
            q = _q1().build({"lineitem": df})
            outs = [cl.submit(q, name=f"r{i}", timeout_s=600)
                    for i in range(2)]
        finally:
            cl.close()
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False, clear=True)
        fe.close()
        srv.close()
    for out in outs:
        _assert_equals_reference(out, raw)
    deadline = time.monotonic() + 30  # the timer thread's last step
    while (gauges.snapshot()["ingest_upload_ns_total"]
           == before["ingest_upload_ns_total"]
           and time.monotonic() < deadline):
        time.sleep(0.01)
    first = gauges.snapshot()["ingest_upload_ns_total"]
    assert first > before["ingest_upload_ns_total"]
    steps = _step_spans(events)
    assert len(steps) == 2
    for got in steps.values():
        assert got == [(1, BATCH)] + [(7, 7 * BATCH)] * 3 + [
            (3, 3 * BATCH), (1, 1024)]
    time.sleep(0.2)  # the second request found the device copy: no upload
    assert gauges.snapshot()["ingest_upload_ns_total"] == first


def _agg_cases():
    """(name, fused stage) over a few buffer layouts: Q1's dense path
    (dictionary-coded string keys, 128-bit sums), int keys with a double
    sum, plain string keys with min/max/count."""
    from test_fusion import source
    from spark_rapids_tpu.exec import HashAggregateExec, fuse_exec
    from spark_rapids_tpu.exprs.expr import Count, Max, Min, Sum, col
    rng = np.random.default_rng(3)
    n = 1024
    ints = pa.table({"k": pa.array(rng.integers(0, 37, n), pa.int64()),
                     "v": pa.array(rng.normal(size=n))})
    strs = pa.table({"s": pa.array([f"key-{i % 23:03d}" * (1 + i % 3)
                                    for i in range(n)]),
                     "w": pa.array(rng.integers(0, 100, n), pa.int64())})
    _, li = _lineitem(SEEDS[0], 4)
    df = from_arrow(li, conf=_conf(), batch_rows=BATCH, partitions=1)
    return [
        ("q1", _q1().build({"lineitem": df}).physical_plan().children[0]),
        ("int-keys", fuse_exec(HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s")],
            source(ints, batch_rows=256)))),
        ("string-keys", fuse_exec(HashAggregateExec(
            [col("s")], [Min(col("w")).alias("lo"), Max(col("w")).alias("hi"),
                         Count().alias("n")],
            source(strs, batch_rows=256)))),
    ]


@pytest.mark.parametrize("case", [0, 1, 2], ids=["q1", "int-keys",
                                                 "string-keys"])
def test_seed_and_step_return_one_carry_signature(case):
    """The step program takes the carry from the seed once and from a step
    ever after; the two must be one pytree of one set of shapes and types,
    or every window's program exists twice (until PR 31 the first window
    after the seed compiled and loaded a second program of the same
    bodies, and bound through the export store it fell back to a fresh
    trace at the second window)."""
    import jax
    name, stage = _agg_cases()[case]
    assert isinstance(stage, fused.TpuFusedStageExec), name
    agg = stage.agg
    agg._prepare()
    segs = stage._runtime_segments(0)
    consts = tuple(seg.consts for seg in segs)
    batches = list(stage.child.execute(0))[:3]
    fns = stage._chain_fns(segs, batches[0].capacity)
    seeded = jax.eval_shape(fused._make_seed(fns, agg), batches[0],
                            consts)[0]
    step = fused._make_step(fns, agg, *fused._carry_shape(seeded))
    stepped = jax.eval_shape(step, seeded, tuple(batches[1:]), consts)[0]
    assert jax.tree.structure(seeded) == jax.tree.structure(stepped)
    assert jax.tree.leaves(seeded) == jax.tree.leaves(stepped)


@pytest.mark.parametrize("value,error", [
    ("agg.boundedStepPrograms", None),
    (" agg.boundedStepPrograms, ", None),
    ("", None),
    ("agg.boundedStepPrograms,agg.rolledWindow", "this build lacks"),
])
def test_a_configuration_names_what_it_depends_on(value, error):
    """``spark.rapids.tpu.requires`` is how the configuration of
    ``sf10_q1_agg1`` says that it needs the bound this file holds: a build
    that has the capability takes the conf and changes nothing for it, one
    that lacks a name raises where the conf is made (and one from before
    the key raises on the key: RapidsConf's typo guard)."""
    if error:
        with pytest.raises(ValueError, match=error):
            C.RapidsConf({C.REQUIRES.key: value})
        return
    conf = C.RapidsConf({C.REQUIRES.key: value})
    assert conf[C.REQUIRES] == value
    assert "agg.boundedStepPrograms" in C.CAPABILITIES
