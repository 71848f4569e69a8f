"""Differential tests for the device-native sort & window kernels (PR 18).

Oracles are deliberately foreign to the code under test: a pure-Python
stable multi-pass sort for SortExec (dtypes x nulls x NaN x direction),
a NumPy loop for the segmented scans, and the CPU engine for window
frames. The radix / merge-path / rmq dispatch alternatives are forced
via the autotune seam and must be BIT-IDENTICAL to the default paths —
they are order-equivalent rewrites, never approximations. Pallas
kernels run under ``interpret=True`` on this lane (reference: the
hash-table probe suite in test_hash_table.py).
"""

import functools
import math

import numpy as np
import pyarrow as pa
import pytest
import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exec import BatchSourceExec, SortExec, SortOrder
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exec import sort as sort_mod
from spark_rapids_tpu.exprs.expr import col, Count, Max, Min, Sum
from spark_rapids_tpu.exprs.window import WindowFrame, over, window_spec
from spark_rapids_tpu.plan import autotune as AT
from spark_rapids_tpu.plan import from_arrow
from spark_rapids_tpu.config.conf import RapidsConf


def source(table: pa.Table, batch_rows=None, min_bucket=16):
    schema = T.Schema.from_arrow(table.schema)
    if batch_rows is None:
        batches = [batch_from_arrow(table, min_bucket)]
    else:
        batches = [batch_from_arrow(table.slice(i, batch_rows), min_bucket)
                   for i in range(0, max(table.num_rows, 1), batch_rows)]
    return BatchSourceExec([batches], schema)


def rows(node):
    out = []
    for b in node.execute_all():
        out.extend(batch_to_arrow(b, node.output_schema).to_pylist())
    return out


# ---------------------------------------------------------------------------
# python sort oracle: stable multi-pass lexicographic sort with Spark null
# and NaN semantics (nulls per nulls_first, NaN greater than every number)
# ---------------------------------------------------------------------------


def _oracle_sort(pyrows, specs):
    """specs: [(name, ascending, nulls_first)] — primary key first."""
    out = list(pyrows)
    for name, asc, nf in reversed(specs):
        def key(r, name=name, asc=asc, nf=nf):
            v = r[name]
            if v is None:
                # under reverse=True larger sorts first, so flip the rank
                null_rank = (0 if nf else 2) if asc else (2 if nf else 0)
                return (null_rank, False, 0)
            nan = isinstance(v, float) and math.isnan(v)
            return (1, nan, 0 if nan else v)
        out.sort(key=key, reverse=not asc)  # python sorts are stable
    return out


def _keys_for(dtype, rng, n):
    if dtype == "int32":
        return pa.array([None if x % 7 == 0 else int(x)
                         for x in rng.integers(-50, 50, n)], pa.int32())
    if dtype == "int64":
        return pa.array([None if x % 9 == 0 else int(x) << 33
                         for x in rng.integers(-40, 40, n)], pa.int64())
    if dtype == "float64":
        vals = rng.normal(size=n).tolist()
        for i in range(0, n, 11):
            vals[i] = None
        for i in range(1, n, 13):
            vals[i] = float("nan")
        for i in range(2, n, 17):
            vals[i] = -0.0 if i % 2 else 0.0
        return pa.array(vals, pa.float64())
    if dtype == "string":
        pool = ["", "a", "aa", "ab", "zebra", "Zebra", "\x00x",
                "longer-string-key-beyond-the-16-byte-prefix"]
        return pa.array([None if x % 6 == 0 else pool[x % len(pool)]
                         for x in rng.integers(0, 60, n)], pa.string())
    if dtype == "date32":
        return pa.array([None if x % 8 == 0 else int(x)
                         for x in rng.integers(0, 20000, n)], pa.date32())
    raise AssertionError(dtype)


@pytest.mark.parametrize("dtype",
                         ["int32", "int64", "float64", "string", "date32"])
@pytest.mark.parametrize("asc,nf", [(True, True), (False, False),
                                    (True, False)])
def test_sort_single_key_matches_oracle(rng, dtype, asc, nf):
    n = 160
    t = pa.table({"k": _keys_for(dtype, rng, n),
                  "idx": pa.array(np.arange(n, dtype=np.int64))})
    got = rows(SortExec([SortOrder(col("k"), ascending=asc, nulls_first=nf)],
                        source(t, batch_rows=37)))
    want = _oracle_sort(t.to_pylist(), [("k", asc, nf)])

    def norm(r):
        v = r["k"]
        if isinstance(v, float):
            v = "nan" if math.isnan(v) else v + 0.0  # -0.0 == 0.0
        return (v, r["idx"])
    # ties resolved identically: device lexsort and the oracle are stable
    assert [norm(r) for r in got] == [norm(r) for r in want]


def test_sort_multi_key_matches_oracle(rng):
    n = 200
    t = pa.table({
        "a": _keys_for("int32", rng, n),
        "s": _keys_for("string", rng, n),
        "idx": pa.array(np.arange(n, dtype=np.int64)),
    })
    specs = [("a", True, True), ("s", False, False)]
    got = rows(SortExec([SortOrder(col("a"), ascending=True,
                                   nulls_first=True),
                         SortOrder(col("s"), ascending=False,
                                   nulls_first=False)],
                        source(t, batch_rows=41)))
    want = _oracle_sort(t.to_pylist(), specs)
    assert [(r["a"], r["s"], r["idx"]) for r in got] \
        == [(r["a"], r["s"], r["idx"]) for r in want]


# ---------------------------------------------------------------------------
# radix pack: same total order as the lexsort chain, bit-identical perm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arrow_t,width", [
    (pa.int16(), 16), (pa.int32(), 32), (pa.bool_(), 2),
    (pa.float32(), 32), (pa.int64(), 64), (pa.date32(), 32)])
@pytest.mark.parametrize("asc,nf", [(True, True), (False, True)])
def test_radix_sort_indices_match_lexsort(rng, arrow_t, width, asc, nf):
    n = 120
    raw = rng.integers(-30, 30, n)
    if arrow_t == pa.bool_():
        vals = [None if x % 5 == 0 else bool(x % 2) for x in raw]
    elif arrow_t == pa.float32():
        vals = [None if x % 5 == 0 else float(x) / 3.0 for x in raw]
    elif arrow_t == pa.date32():
        vals = [None if x % 5 == 0 else int(abs(x)) for x in raw]
    else:
        vals = [None if x % 5 == 0 else int(x) for x in raw]
    b = batch_from_arrow(pa.table({"k": pa.array(vals, arrow_t)}), 16)
    specs = (K.SortSpec(0, asc, nf),)
    lex = K.sort_indices(b, specs, "lex")
    radix = K.sort_indices(b, specs, "radix")
    np.testing.assert_array_equal(jax.device_get(lex),
                                  jax.device_get(radix))


def test_radix_plan_rejects_unpackable():
    b = batch_from_arrow(pa.table({
        "d": pa.array([1.0, 2.0], pa.float64()),
        "s": pa.array(["a", "b"], pa.string())}), 16)
    dts = (b.columns[0].dtype, b.columns[1].dtype)
    assert K.radix_plan(dts, (K.SortSpec(0),)) is None
    assert K.radix_plan(dts, (K.SortSpec(1),)) is None
    assert K.merge_key_bits(b.columns[0].dtype) is None  # 64-bit key


# ---------------------------------------------------------------------------
# out-of-core merge path vs resort: forced via the autotune seam
# ---------------------------------------------------------------------------


def _force_path(monkeypatch, table):
    def choose(op, shape, static_path, candidates):
        want = table.get(op)
        if want is not None and want in candidates:
            return want, "measured"
        return static_path, "default"
    monkeypatch.setattr(AT, "choose", choose)


@pytest.mark.parametrize("asc,nf", [(True, True), (True, False),
                                    (False, True), (False, False)])
def test_ooc_merge_path_bit_identical_to_resort(rng, monkeypatch, asc, nf):
    n = 400
    t = pa.table({
        "k": pa.array([None if x % 10 == 0 else int(x)
                       for x in rng.integers(-99, 99, n)], pa.int32()),
        "pay": pa.array([f"row{i:04d}" for i in range(n)], pa.string()),
    })
    orders = [SortOrder(col("k"), ascending=asc, nulls_first=nf)]

    def ooc():
        return SortExec(orders, source(t, 48), out_of_core=True,
                        target_rows=96)
    base = rows(SortExec(orders, source(t, 48)))
    _force_path(monkeypatch, {"sort:ooc": "resort"})
    assert rows(ooc()) == base
    before = K.counters()["sort_merge_total"]
    _force_path(monkeypatch, {"sort:ooc": "merge"})
    assert rows(ooc()) == base
    assert K.counters()["sort_merge_total"] > before


def test_ooc_merge_run_counter_and_cap(rng):
    n = 600
    t = pa.table({"k": pa.array(rng.integers(0, 1000, n), pa.int64())})
    orders = [SortOrder(col("k"))]
    exp = sorted(int(x) for x in t.column("k").to_pylist())
    before = K.counters()["sort_runs_total"]
    got = rows(SortExec(orders, source(t, 32), out_of_core=True,
                        target_rows=64))
    assert [r["k"] for r in got] == exp
    assert K.counters()["sort_runs_total"] > before
    # cap the merge set: runs beyond the cap are pre-merged, result equal
    old = C.get_active()
    C.set_active(C.RapidsConf(
        {"spark.rapids.tpu.sql.sort.outOfCore.maxMergeRuns": 4}))
    try:
        got = rows(SortExec(orders, source(t, 32), out_of_core=True,
                            target_rows=64))
    finally:
        C.set_active(old)
    assert [r["k"] for r in got] == exp


def test_merge_gather_matches_concat_resort(rng):
    """Kernel-level: merge-path gather over sorted pieces == stable
    concat+sort, including null placement and padding rows."""
    pieces_vals = [sorted([int(x) for x in rng.integers(-20, 20, m)])
                   for m in (13, 7, 21)]
    batches = [batch_from_arrow(
        pa.table({"k": pa.array(v, pa.int32())}), 16) for v in pieces_vals]
    from spark_rapids_tpu.exec.aggregate import concat_jit
    merged = concat_jit(batches)
    got = sort_mod._merge_gather(merged, tuple(batches), 0, True, True)
    want = sort_mod._sort_run(merged, (K.SortSpec(0, True, True),), "lex")
    schema = T.Schema.of(("k", T.INT))
    assert batch_to_arrow(got, schema).equals(batch_to_arrow(want, schema))


# ---------------------------------------------------------------------------
# segmented scans: NumPy oracle, then Pallas interpret == XLA
# ---------------------------------------------------------------------------


def _np_segscan(vals, starts, op):
    out = np.empty_like(vals)
    for i in range(len(vals)):
        if i == 0 or starts[i]:
            out[i] = vals[i]
        else:
            out[i] = op(out[i - 1], vals[i])
    return out


@pytest.mark.parametrize("name,op", [("add", np.add),
                                     ("min", np.minimum),
                                     ("max", np.maximum)])
@pytest.mark.parametrize("dt", [np.int32, np.float32])
def test_segmented_scan_xla_matches_numpy(rng, name, op, dt):
    n = 257  # off the power-of-two grid
    vals = rng.integers(-9, 9, n).astype(dt)
    starts = (rng.random(n) < 0.2)
    starts[0] = bool(rng.random() < 0.5)  # both first-row conventions
    got = K.segmented_scan_xla(jnp.asarray(vals), jnp.asarray(starts), name)
    np.testing.assert_array_equal(jax.device_get(got),
                                  _np_segscan(vals, starts, op))


@pytest.mark.parametrize("n", [512, 40000])  # one tile; two, with a carry
@pytest.mark.parametrize("name", ["add", "min", "max"])
def test_segmented_scan_pallas_interpret_matches_xla(rng, name, n):
    # int32 for add: float running sums associate differently between the
    # blocked kernel and the XLA tree scan (last-ulp), ints are exact
    if name == "add":
        vals = rng.integers(-9, 9, n).astype(np.int32)
    else:
        vals = rng.normal(size=n).astype(np.float32)
    # sparse heads in the long case, so segments cross rows and the tile
    starts = (rng.random(n) < (0.15 if n == 512 else 0.0005))
    ref = K.segmented_scan_xla(jnp.asarray(vals), jnp.asarray(starts), name)
    got = K.segmented_scan_pallas(jnp.asarray(vals), jnp.asarray(starts),
                                  name, interpret=True)
    np.testing.assert_array_equal(jax.device_get(got), jax.device_get(ref))


# ---------------------------------------------------------------------------
# window frames: fuzz vs the CPU engine; rmq vs scan; pallasMode contract
# ---------------------------------------------------------------------------


def _win_table(rng, n=240):
    return pa.table({
        "p": pa.array(rng.integers(0, 5, n).astype(np.int64)),
        "o": pa.array(np.arange(n, dtype=np.int64)),
        "v": pa.array([None if i % 11 == 0 else float(x) for i, x in
                       enumerate(rng.normal(size=n))], pa.float64()),
        "iv": pa.array(rng.integers(-50, 50, n).astype(np.int64)),
    })


def _win_rows(t, frame, extra_conf=None, enabled=True):
    conf = {"spark.rapids.tpu.sql.enabled": enabled}
    conf.update(extra_conf or {})
    df = from_arrow(t, RapidsConf(conf))
    spec = window_spec(partition_by=[col("p")],
                       order_by=[SortOrder(col("o"))], frame=frame)
    out = df.with_window(
        over(Min(col("v")), spec).alias("mn"),
        over(Max(col("iv")), spec).alias("mx"),
        over(Sum(col("iv")), spec).alias("s"),
        over(Count(col("v")), spec).alias("c"),
    ).collect()

    def norm(r):
        # round like test_window_frames: the pallas<->xla sum scans may
        # associate differently at last-ulp on the TPU lane
        return tuple(
            (k, "NaN" if isinstance(v, float) and math.isnan(v)
             else str(round(v, 9)) if isinstance(v, float) else str(v))
            for k, v in sorted(r.items()))
    return sorted(map(norm, out))


def test_window_frame_fuzz_vs_cpu_engine(rng):
    t = _win_table(rng)
    bounds = sorted(rng.integers(-6, 6, 2).tolist())
    frames = [WindowFrame("rows", int(lo), int(hi))
              for lo, hi in [tuple(bounds), (-4, 0), (1, 3), (-2, -1)]]
    frames += [
        WindowFrame("rows", None, None),   # unbounded both
        WindowFrame("rows", None, 0),      # running
        WindowFrame("rows", 0, None),      # reverse-running
        WindowFrame("range", None, 0),     # running RANGE (peers included)
        WindowFrame("range", -5, 5),       # bounded RANGE (CPU-tagged path)
    ]
    for frame in frames:
        assert _win_rows(t, frame, enabled=True) \
            == _win_rows(t, frame, enabled=False), repr(frame)


def test_window_null_order_keys_vs_cpu(rng):
    """Nullable ORDER BY / PARTITION BY keys: deterministic only for
    tie-insensitive frames (unbounded; running RANGE includes peers)."""
    n = 180
    t = pa.table({
        "p": pa.array([None if i % 13 == 0 else int(x) for i, x in
                       enumerate(rng.integers(0, 4, n))], pa.int64()),
        "o": pa.array([None if i % 7 == 0 else int(x) for i, x in
                       enumerate(rng.integers(0, 40, n))], pa.int64()),
        "v": pa.array(rng.normal(size=n), pa.float64()),
        "iv": pa.array(rng.integers(-50, 50, n).astype(np.int64)),
    })
    for frame in (WindowFrame("rows", None, None),
                  WindowFrame("range", None, 0)):
        assert _win_rows(t, frame, enabled=True) \
            == _win_rows(t, frame, enabled=False), repr(frame)


def test_window_rmq_path_bit_identical(rng, monkeypatch):
    t = _win_table(rng)
    frame = WindowFrame("rows", -3, 2)
    base = _win_rows(t, frame)
    before = K.counters()["window_loop_total"]
    _force_path(monkeypatch, {"window:minmax": "rmq"})
    assert _win_rows(t, frame) == base
    assert K.counters()["window_loop_total"] > before


@pytest.mark.parametrize("mode", ["off", "on"])
def test_window_pallas_mode_results_stable(rng, mode):
    """pallasMode=on on the CPU lane: the eager probe fails, latches the
    sticky fallback, and the XLA path produces identical results —
    pallasMode never changes answers (docs/kernels.md contract)."""
    t = _win_table(rng, n=180)
    frame = WindowFrame("rows", -5, 0)
    K.reset_sortwin_pallas_fallback()
    key = "spark.rapids.tpu.sql.kernel.sortWindow.pallasMode"
    got = _win_rows(t, frame, extra_conf={key: mode})
    assert got == _win_rows(t, frame)
    if mode == "on" and jax.default_backend() != "tpu":
        assert K.counters()["sortwin_pallas_fallback_total"] > 0
    K.reset_sortwin_pallas_fallback()


@pytest.mark.parametrize("dt", [np.int32, np.float32])
@pytest.mark.parametrize("name", ["add", "min", "max"])
def test_segmented_scan_pallas_on_the_chip_matches_xla(rng, name, dt):
    """The compiled kernel against the XLA scan on real hardware (1, 2 and 4
    tiles). Needs the chip: `chiprun -- env SRTPU_TPU_LANE=1 python -m
    pytest tests/test_sortwin_kernels.py -k on_the_chip`; skips elsewhere."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU: interpret mode is covered above")
    for n in (512, 40000, 100000):
        # integer-valued, so float32 sums are exact in any association
        vals = rng.integers(-1000, 1000, n).astype(dt)
        starts = rng.random(n) < (0.15 if n == 512 else 0.0005)
        ref = K.segmented_scan_xla(jnp.asarray(vals), jnp.asarray(starts),
                                   name)
        got = K.segmented_scan_pallas(jnp.asarray(vals), jnp.asarray(starts),
                                      name)
        np.testing.assert_array_equal(jax.device_get(got),
                                      jax.device_get(ref))


@pytest.mark.parametrize("kernel_is_right", [True, False])
def test_segscan_probe_holds_the_kernel_to_the_xla_answer(monkeypatch,
                                                          kernel_is_right):
    """The eager probe latches the XLA fallback not only when the kernel
    fails to lower but when it lowers and answers wrongly (what interpret
    mode cannot show about the hardware)."""
    interp = functools.partial(K.segmented_scan_pallas, interpret=True)
    wrong = lambda v, s, op: interp(v, s, op) + 1  # noqa: E731
    monkeypatch.setattr(K, "segmented_scan_pallas",
                        interp if kernel_is_right else wrong)
    K.reset_sortwin_pallas_fallback()
    before = K.counters()["sortwin_pallas_fallback_total"]
    # asked from inside a trace, as window programs ask: it still runs now
    seen = []
    jax.jit(lambda x: seen.append(K._segscan_pallas_ok()) or x)(jnp.int32(0))
    assert seen == [kernel_is_right]
    assert (K.counters()["sortwin_pallas_fallback_total"] - before
            == (0 if kernel_is_right else 1))
    K.reset_sortwin_pallas_fallback()


def test_window_scan_counter_increments(rng):
    before = K.counters()["window_scan_total"]
    _win_rows(_win_table(rng, n=64), WindowFrame("rows", -1, 1))
    assert K.counters()["window_scan_total"] > before


# ---------------------------------------------------------------------------
# lint pass: clean on this repo, catches a broken synthetic tree
# ---------------------------------------------------------------------------


def test_pallas_fallback_lint_clean_and_catches(tmp_path):
    from tools.lint import pallas_fallback as P
    import textwrap

    repo_root = C.__file__.rsplit("/spark_rapids_tpu/", 1)[0]
    assert P.run_pass(repo_root) == []

    ex = tmp_path / "spark_rapids_tpu" / "exec"
    ex.mkdir(parents=True)
    (ex / "kernels.py").write_text(textwrap.dedent("""
        import jax.experimental.pallas as pl
        def rogue(x):
            return pl.pallas_call(lambda r: r)(x)
        def probe_pallas(x):
            return pl.pallas_call(lambda r: r)(x)
    """))
    (ex / "sort.py").write_text(textwrap.dedent("""
        import jax
        @jax.jit
        def _sort_run(batch, specs, path):
            return batch
    """))
    msgs = "\n".join(P.run_pass(str(tmp_path)))
    assert "must live in a *_pallas wrapper" in msgs
    assert "must take interpret=" in msgs
    assert "sticky *_broken latch" in msgs
    assert "static jit args" in msgs
    assert "_merge_gather() not found" in msgs
