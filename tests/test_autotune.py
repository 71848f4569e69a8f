"""Measurement-driven dispatch (plan/autotune.py): store resilience,
choose() precedence, selectivity feedback, CBO measured costs, footer
memoization, and the Pallas sticky-fallback latch (default lane; the
cross-process warm start + tracker differential is slow-lane,
tests/test_autotune_warm.py)."""

import json
import os

import pyarrow as pa
import pytest

from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.obs import gauges as G
from spark_rapids_tpu.plan import autotune as AT
from spark_rapids_tpu.plan.dataframe import from_arrow


@pytest.fixture
def at_dir(tmp_path):
    """Point the autotune store at a fresh tmpdir and restore after."""
    active0 = C.get_active()
    conf = C.RapidsConf({"spark.rapids.tpu.autotune.dir": str(tmp_path)})
    C.set_active(conf)
    AT.reset_for_tests()
    AT.configure(conf)
    yield tmp_path
    C.set_active(active0)
    AT.reset_for_tests()


def _feed(op, shape, path, ns_per_row, n=2):
    for _ in range(n):
        AT.observe(op, shape, path, ns_per_row * 1000.0, 1000.0)
    AT.flush()


# -- shape classes ------------------------------------------------------


def test_shape_class_log2_buckets():
    assert AT.shape_class(1024, 2, "int") == "r10/w2/int"
    assert AT.shape_class(1025, 2, "int") == "r10/w2/int"
    assert AT.shape_class(2048, 2, "int") == "r11/w2/int"
    # degenerate rows clamp to bucket 0, never raise
    assert AT.shape_class(0).startswith("r0/")
    assert AT.shape_class(-5).startswith("r0/")


def test_family_of_collapses_types():
    assert AT.family_of(["int64", "int32"]) == "int"
    assert AT.family_of(["string", "int64"]) == "int+str"
    assert AT.family_of(["double"]) == "flt"
    assert AT.family_of(["decimal(10,2)"]) == "dec"
    assert AT.family_of([]) == "na"


def test_plan_fingerprint_stable_across_equal_exprs():
    a = E.col("x") > E.lit(5)
    b = E.col("x") > E.lit(5)
    assert AT.plan_fingerprint(a) == AT.plan_fingerprint(b)
    assert AT.plan_fingerprint(a) != AT.plan_fingerprint(E.col("y") > E.lit(5))


# -- choose() precedence ------------------------------------------------


def test_choose_empty_store_returns_static(at_dir):
    c0 = AT.counters()
    path, source = AT.choose("join:inner", "r8/w1/int", "ht",
                             ("ht", "sorted"))
    assert (path, source) == ("ht", "default")
    assert AT.counters()["autotune_miss_total"] == \
        c0["autotune_miss_total"] + 1


def test_choose_explores_then_ranks(at_dir):
    shape = "r8/w1/int"
    _feed("join:inner", shape, "ht", 50.0)
    # static measured, alternate not: deterministic exploration
    path, source = AT.choose("join:inner", shape, "ht", ("ht", "sorted"))
    assert (path, source) == ("sorted", "measured")
    # alternate measured faster: measured ranking overrides the static
    _feed("join:inner", shape, "sorted", 10.0)
    c0 = AT.counters()
    path, source = AT.choose("join:inner", shape, "ht", ("ht", "sorted"))
    assert (path, source) == ("sorted", "measured")
    c1 = AT.counters()
    assert c1["autotune_hit_total"] == c0["autotune_hit_total"] + 1
    assert c1["autotune_override_total"] == c0["autotune_override_total"] + 1
    # static faster: measured ranking agrees with the static choice
    _feed("join:inner", shape, "sorted", 90.0, n=8)
    path, source = AT.choose("join:inner", shape, "ht", ("ht", "sorted"))
    assert (path, source) == ("ht", "measured")


def test_choose_needs_min_samples(at_dir):
    shape = "r4/w1/int"
    AT.observe("join:inner", shape, "ht", 100.0, 10.0)  # one sample < min 2
    AT.flush()
    path, source = AT.choose("join:inner", shape, "ht", ("ht", "sorted"))
    assert (path, source) == ("ht", "default")


# -- persistence + resilience -------------------------------------------


def test_store_roundtrip_across_reset(at_dir):
    _feed("join:inner", "r8/w1/int", "ht", 50.0)
    _feed("join:inner", "r8/w1/int", "sorted", 10.0)
    p = AT.store_path()
    assert p is not None and os.path.exists(p)
    data = json.loads(open(p).read())
    assert data["salt"] == AT._environment_salt()
    # fresh-process shape: drop in-memory state, re-load from disk
    AT.reset_for_tests()
    AT.configure(C.get_active())
    path, source = AT.choose("join:inner", "r8/w1/int", "ht",
                             ("ht", "sorted"))
    assert (path, source) == ("sorted", "measured")


@pytest.mark.parametrize("garbage", [
    b"definitely not json",
    b'{"version": 1, "salt": "x", "entries"',          # truncated write
    b'{"version": 1, "entries": {"a": {"p": [1e400]}}}',  # non-finite
    b'[1, 2, 3]',                                      # wrong root type
])
def test_corrupt_store_unlinked_and_static(at_dir, garbage):
    _feed("join:inner", "r8/w1/int", "sorted", 10.0)
    _feed("join:inner", "r8/w1/int", "ht", 50.0)
    p = AT.store_path()
    with open(p, "wb") as f:
        f.write(garbage)
    AT.reset_for_tests()
    AT.configure(C.get_active())
    path, source = AT.choose("join:inner", "r8/w1/int", "ht",
                             ("ht", "sorted"))
    assert (path, source) == ("ht", "default"), \
        "corrupt store must degrade to the static choice"
    assert not os.path.exists(p), "corrupt store must be unlinked"


def test_salt_drift_under_same_digest_unlinked(at_dir):
    _feed("join:inner", "r8/w1/int", "sorted", 10.0)
    _feed("join:inner", "r8/w1/int", "ht", 50.0)
    p = AT.store_path()
    data = json.loads(open(p).read())
    data["salt"] = "jax-0.0.1|tpu|other-host"  # drifted env, same filename
    with open(p, "w") as f:
        json.dump(data, f)
    AT.reset_for_tests()
    AT.configure(C.get_active())
    path, source = AT.choose("join:inner", "r8/w1/int", "ht",
                             ("ht", "sorted"))
    assert (path, source) == ("ht", "default")
    assert not os.path.exists(p)


def test_disabled_is_inert(at_dir):
    conf = C.RapidsConf({"spark.rapids.tpu.autotune.enabled": False,
                         "spark.rapids.tpu.autotune.dir": str(at_dir)})
    C.set_active(conf)
    AT.reset_for_tests()
    AT.configure(conf)
    AT.observe("join:inner", "r8/w1/int", "ht", 100.0, 10.0)
    assert AT.flush() == 0
    assert AT.store_path() is None
    assert os.listdir(at_dir) == []
    path, source = AT.choose("join:inner", "r8/w1/int", "ht",
                             ("ht", "sorted"))
    assert (path, source) == ("ht", "default")


def test_sample_cap_bounds_file(at_dir):
    for i in range(100):
        AT.observe("join:inner", "r8/w1/int", "ht", float(i + 1), 1.0)
    AT.flush()
    samples = AT._ENTRIES["join:inner|r8/w1/int"]["ht"]
    assert len(samples) == AT._MAX_SAMPLES
    assert samples[-1] == 100.0  # newest kept, oldest aged out


# -- selectivity ratio channel ------------------------------------------


def test_ratio_clamped_and_gated(at_dir):
    fp = AT.plan_fingerprint(E.col("a") > E.lit(1))
    AT.observe_ratio("filter", fp, 30.0, 100.0)
    AT.flush()
    assert AT.ratio("filter", fp) is None  # below minSamples
    AT.observe_ratio("filter", fp, 30.0, 100.0)
    AT.flush()
    assert AT.ratio("filter", fp) == pytest.approx(0.3)
    # out > in clamps to 1.0 (never inflates estimates)
    fp2 = "deadbeefdeadbeef"
    AT.observe_ratio("agg", fp2, 500.0, 100.0)
    AT.observe_ratio("agg", fp2, 500.0, 100.0)
    AT.flush()
    assert AT.ratio("agg", fp2) == 1.0


def test_rejects_degenerate_samples(at_dir):
    AT.observe("x", "s", "p", -1.0, 10.0)   # negative time
    AT.observe("x", "s", "p", 10.0, 0.0)    # zero rows
    AT.observe("x", "s", "p", float("nan"), 10.0)
    AT.observe("x", "s", "p", float("inf"), 10.0)
    assert AT.flush() == 0


# -- end-to-end: feedback populates the store, dispatch is visible ------


# the semi join's dispatch that autotune ranks (ht <-> sorted) is the
# unfused operator's: fused, a semi join on one integer key takes the
# exists form (exec/join.py), which has no other path to rank
UNFUSED = {"spark.rapids.tpu.sql.fusion.enabled": False}


def _join_agg_query(conf):
    t1 = pa.table({"k": pa.array([i % 200 for i in range(2000)], pa.int64()),
                   "v": pa.array([i % 7 for i in range(2000)], pa.int64())})
    t2 = pa.table({"k": pa.array([i % 150 for i in range(300)], pa.int64())})
    df1 = from_arrow(t1, conf=conf, batch_rows=256, partitions=2)
    df2 = from_arrow(t2, conf=conf, batch_rows=256, partitions=2)
    return (df1.join(df2, on="k", how="left_semi")
            .group_by("k").agg(E.Sum(E.col("v"))))


def test_feedback_populates_store_and_explain(at_dir):
    conf = C.RapidsConf({"spark.rapids.tpu.autotune.dir": str(at_dir),
                         "spark.rapids.tpu.profile.enabled": True,
                         **UNFUSED})
    q = _join_agg_query(conf)
    q.to_arrow()
    p = AT.store_path()
    assert p is not None and os.path.exists(p)
    entries = json.loads(open(p).read())["entries"]
    assert any(k.startswith("join:left_semi|") for k in entries)
    assert "cbo|global" in entries
    ea = q.explain_analyze()
    assert "path=" in ea and "source=default" in ea
    prof = q.last_profile()
    dp = prof.dispatch_paths()
    assert any(k.startswith("join:left_semi:") for k in dp)
    assert dp == prof.to_dict()["dispatch_paths"]


def test_warm_dispatch_measured_and_differential(at_dir):
    conf_on = C.RapidsConf({"spark.rapids.tpu.autotune.dir": str(at_dir),
                            "spark.rapids.tpu.profile.enabled": True,
                            **UNFUSED})
    base = _join_agg_query(conf_on).to_arrow()
    # second run: the semi-join + agg-window candidates explore/rank from
    # the persisted measurements
    q2 = _join_agg_query(conf_on)
    warm = q2.to_arrow()
    assert "source=measured" in q2.explain_analyze()
    assert G.snapshot()["autotune_hit_total"] > 0
    conf_off = C.RapidsConf({
        "spark.rapids.tpu.autotune.enabled": False,
        "spark.rapids.tpu.profile.enabled": True, **UNFUSED})
    off = _join_agg_query(conf_off).to_arrow()
    # measurements re-rank among order-equivalent paths only: results are
    # bit-identical to the static dispatch, in the same order
    assert warm.equals(off) and base.equals(off)


def test_gauges_exported_in_catalog():
    names = {n for n, _, _ in G.CATALOG}
    for n in ("autotune_hit_total", "autotune_miss_total",
              "autotune_store_total", "autotune_override_total",
              "hashtbl_pallas_fallback_total"):
        assert n in names
    snap = G.snapshot()
    for n in ("autotune_hit_total", "hashtbl_pallas_fallback_total"):
        assert n in snap


# -- CBO consumes measurements ------------------------------------------


def test_cbo_costs_measured_and_clamped(at_dir):
    from spark_rapids_tpu.plan import cbo

    opt = cbo.CostBasedOptimizer(C.get_active())
    assert opt.cost_source == "default"
    _feed("cbo", "global", "dev", 10.0)
    _feed("cbo", "global", "cpu", 40.0)
    _feed("cbo", "global", "xfer", 20.0)
    opt = cbo.CostBasedOptimizer(C.get_active())
    assert opt.cost_source == "measured"
    assert opt.cpu_cost == pytest.approx(opt.dev_cost * 4.0)
    assert opt.xfer_cost == pytest.approx(opt.dev_cost * 2.0)
    # pathological samples stay clamped so the DP never degenerates
    AT.reset_for_tests()
    AT.configure(C.get_active())
    _feed("cbo", "global", "dev", 1.0)
    _feed("cbo", "global", "cpu", 1e9)
    opt = cbo.CostBasedOptimizer(C.get_active())
    assert opt.cpu_cost == pytest.approx(opt.dev_cost * 1e3)


def test_cbo_selectivity_uses_observed_ratio(at_dir):
    from spark_rapids_tpu.plan import cbo, logical as L

    t = pa.table({"a": list(range(100))})
    cond = E.col("a") > E.lit(90)
    scan = L.InMemoryScan(t, 1 << 20, 1)
    filt = L.Filter(cond, scan)
    assert cbo.estimate_rows(filt) == pytest.approx(50.0)  # static 0.5
    fp = AT.plan_fingerprint(cond)
    AT.observe_ratio("filter", fp, 9.0, 100.0)
    AT.observe_ratio("filter", fp, 9.0, 100.0)
    AT.flush()
    assert cbo.estimate_rows(filt) == pytest.approx(9.0)


# -- parquet footer memoization through the scan pool -------------------


def test_estimate_rows_footer_memoized(at_dir, tmp_path, monkeypatch):
    import pyarrow.parquet as pq

    from spark_rapids_tpu.plan import cbo, logical as L

    paths = []
    for i in range(3):
        p = str(tmp_path / f"part-{i}.parquet")
        pq.write_table(pa.table({"a": list(range(100 * (i + 1)))}), p)
        paths.append(p)
    cbo._FOOTER_ROWS.clear()
    reads = []
    real = cbo._read_footer_rows
    monkeypatch.setattr(cbo, "_read_footer_rows",
                        lambda p: (reads.append(p), real(p))[1])
    scan = L.ParquetScan(paths, None, None)
    assert cbo.estimate_rows(scan) == pytest.approx(600.0)
    assert len(reads) == 3
    # across passes: a fresh estimate re-reads nothing
    assert cbo.estimate_rows(scan) == pytest.approx(600.0)
    assert len(reads) == 3
    # a rewritten file (new mtime/size) invalidates just its key
    pq.write_table(pa.table({"a": list(range(7))}), paths[0])
    assert cbo.estimate_rows(scan) == pytest.approx(507.0)
    assert len(reads) == 4


# -- Pallas sticky fallback latch ---------------------------------------


def test_pallas_fallback_counter_journal_and_reset(monkeypatch):
    from spark_rapids_tpu.exec import kernels as K
    from spark_rapids_tpu.obs import events

    active0 = C.get_active()
    calls = []

    def _boom(*a, **kw):
        calls.append("pallas")
        raise RuntimeError("lowering not supported on this backend")

    monkeypatch.setattr(K, "probe_hash_table_pallas", _boom)
    monkeypatch.setattr(K, "probe_hash_table",
                        lambda *a, **kw: ("xla", "xla"))
    monkeypatch.setattr(K, "_pallas_broken", False)
    monkeypatch.setattr(K, "_pallas_mode_last", None)
    try:
        C.set_active(C.RapidsConf(
            {"spark.rapids.tpu.sql.kernel.hashTable.pallasMode": "on"}))
        c0 = K.counters()["hashtbl_pallas_fallback_total"]
        out = K.probe_hash_table_dispatch(None, None, None, 16, 0, 8)
        assert out == ("xla", "xla")
        assert K.counters()["hashtbl_pallas_fallback_total"] == c0 + 1
        evs = events.recent(kind="pallas-fallback", limit=1)
        assert evs and "RuntimeError" in evs[-1]["error"]
        # sticky: the next probe does NOT re-attempt pallas
        K.probe_hash_table_dispatch(None, None, None, 16, 0, 8)
        assert len(calls) == 1
        # conf flip off -> on: operator asked for a re-attempt
        C.set_active(C.RapidsConf(
            {"spark.rapids.tpu.sql.kernel.hashTable.pallasMode": "off"}))
        K.probe_hash_table_dispatch(None, None, None, 16, 0, 8)
        assert len(calls) == 1
        C.set_active(C.RapidsConf(
            {"spark.rapids.tpu.sql.kernel.hashTable.pallasMode": "on"}))
        K.probe_hash_table_dispatch(None, None, None, 16, 0, 8)
        assert len(calls) == 2, "pallasMode=on after a conf change must " \
            "clear the sticky latch and re-attempt"
    finally:
        C.set_active(active0)
