"""Plan-rewrite memo, small-query fast path, and persistent-program-cache
recovery (the cross-process warm start and tracker-wide on/off
differential live in tests/test_warmstart.py)."""

import threading

import pyarrow as pa

from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.obs import gauges as G
from spark_rapids_tpu.plan import plan_cache
from spark_rapids_tpu.plan.dataframe import from_arrow


def _table(n=500, seed=0):
    # fresh table object per call: plan-memo keys pin table identity, so
    # each test starts from a guaranteed-cold entry
    return pa.table({"a": [(i * 7 + seed) % 97 for i in range(n)],
                     "b": [float(i + seed) for i in range(n)]})


def _agg_query(table, conf, out_name="s"):
    df = from_arrow(table, conf=conf)
    return (df.filter(E.col("a") > E.lit(10))
            .group_by(E.col("a"))
            .agg(E.Alias(E.Sum(E.col("b")), out_name)))


def _counters():
    return plan_cache.counters()


def test_warm_repeat_hits_and_skips_compile():
    t = _table()
    conf = C.RapidsConf()
    c0 = _counters()
    first = _agg_query(t, conf).to_arrow()
    c1 = _counters()
    assert c1["plan_cache_miss_total"] == c0["plan_cache_miss_total"] + 1
    second = _agg_query(t, conf).to_arrow()
    c2 = _counters()
    assert c2["plan_cache_hit_total"] == c1["plan_cache_hit_total"] + 1
    assert second.equals(first)
    from spark_rapids_tpu.obs.profile import last_profile
    prof = last_profile()
    assert prof.plan_explain.startswith("[plan-cache hit]")
    # warm repeat re-dispatches already-traced programs: compile phase 0
    assert prof.phases["compile"] == 0.0
    assert "plan-cache" in prof.phases


def test_conf_change_misses():
    t = _table(seed=1)
    base = C.RapidsConf()
    _agg_query(t, base).to_arrow()
    c0 = _counters()
    for override in ({"spark.rapids.tpu.sql.fusion.enabled": False},
                     {"spark.rapids.tpu.sql.agg.repartition.targetBytes":
                      123456}):
        _agg_query(t, base.with_overrides(**override)).to_arrow()
        c1 = _counters()
        assert c1["plan_cache_hit_total"] == c0["plan_cache_hit_total"], \
            f"conf change {override} was served from the plan memo"
        assert c1["plan_cache_miss_total"] > c0["plan_cache_miss_total"]
        c0 = c1


def test_literal_change_misses_rename_hits():
    t = _table(seed=2)
    conf = C.RapidsConf()

    def q(mid, cutoff):
        df = from_arrow(t, conf=conf)
        return (df.select(E.Alias(E.col("a"), mid),
                          E.Alias(E.col("b"), "bb"))
                .filter(E.col(mid) > E.lit(cutoff))
                .select(E.Alias(E.col(mid), "out"), E.col("bb")))

    first = q("x", 5).to_arrow()
    c0 = _counters()
    # pure intermediate rename: same semantics, must hit
    renamed = q("y", 5).to_arrow()
    c1 = _counters()
    assert c1["plan_cache_hit_total"] == c0["plan_cache_hit_total"] + 1
    assert renamed.equals(first)
    # literal change: different semantics, must miss
    q("x", 6).to_arrow()
    c2 = _counters()
    assert c2["plan_cache_hit_total"] == c1["plan_cache_hit_total"]
    assert c2["plan_cache_miss_total"] == c1["plan_cache_miss_total"] + 1


def test_output_rename_misses():
    t = _table(seed=3)
    conf = C.RapidsConf()
    _agg_query(t, conf, out_name="s").to_arrow()
    c0 = _counters()
    out = _agg_query(t, conf, out_name="renamed").to_arrow()
    c1 = _counters()
    assert c1["plan_cache_hit_total"] == c0["plan_cache_hit_total"]
    assert "renamed" in out.column_names


def test_disabled_never_caches():
    t = _table(seed=4)
    conf = C.RapidsConf({"spark.rapids.tpu.plan.cache.enabled": False})
    c0 = _counters()
    _agg_query(t, conf).to_arrow()
    _agg_query(t, conf).to_arrow()
    c1 = _counters()
    assert c1["plan_cache_hit_total"] == c0["plan_cache_hit_total"]
    assert c1["plan_cache_miss_total"] == c0["plan_cache_miss_total"]


def test_lru_eviction():
    conf = C.RapidsConf({"spark.rapids.tpu.plan.cache.maxEntries": 2})
    plan_cache.clear()
    tables = [_table(seed=10 + i) for i in range(3)]
    c0 = _counters()
    for t in tables:
        _agg_query(t, conf).to_arrow()
    c1 = _counters()
    assert c1["plan_cache_evict_total"] == c0["plan_cache_evict_total"] + 1
    assert c1["plan_cache_size"] <= 2


def test_epoch_bump_invalidates():
    t = _table(seed=5)
    conf = C.RapidsConf()
    _agg_query(t, conf).to_arrow()
    plan_cache.bump_epoch()
    c0 = _counters()
    _agg_query(t, conf).to_arrow()
    c1 = _counters()
    assert c1["plan_cache_hit_total"] == c0["plan_cache_hit_total"]
    assert c1["plan_cache_miss_total"] == c0["plan_cache_miss_total"] + 1


def test_dead_table_entry_invalidated():
    """A memo entry whose pinned table died must never be served: id reuse
    after gc could otherwise alias a brand-new table onto a stale plan."""
    conf = C.RapidsConf()
    t = _table(seed=6)
    df = _agg_query(t, conf)
    df.to_arrow()
    pinned = []
    key = plan_cache.build_key(df.plan, conf, df.shuffle_partitions, pinned)
    assert key is not None and plan_cache.lookup(key) is not None
    del df, t, pinned
    import gc
    gc.collect()
    assert plan_cache.lookup(key) is None


# ---------------------------------------------------------------------------
# small-query fast path
# ---------------------------------------------------------------------------


def test_fastpath_no_prefetch_threads_bit_identical():
    t = _table(n=200, seed=7)
    on = C.RapidsConf()
    off = C.RapidsConf({"spark.rapids.tpu.fastpath.enabled": False})

    before = {th.name for th in threading.enumerate()}
    s0 = G.snapshot()
    df = _agg_query(t, on)
    node = df.physical_plan()
    assert getattr(node, "_fastpath", False) is True
    df._pplan = ((df.conf, df.shuffle_partitions), node)
    fast = df.to_arrow()
    s1 = G.snapshot()
    new = [n for n in
           {th.name for th in threading.enumerate()} - before
           if n.startswith("srtpu-prefetch")]
    assert new == [], f"fast path spawned prefetch threads: {new}"
    # and no semaphore round-trips
    assert s1["semaphore_acquire_total"] == s0["semaphore_acquire_total"]

    slow_df = _agg_query(t, off)
    slow_node = slow_df.physical_plan()
    assert getattr(slow_node, "_fastpath", False) is False
    slow_df._pplan = ((slow_df.conf, slow_df.shuffle_partitions), slow_node)
    assert fast.equals(slow_df.to_arrow())


def test_fastpath_threshold_disqualifies_large_input():
    big = pa.table({"a": list(range(200_000)),
                    "b": [0.0] * 200_000})
    df = from_arrow(big, conf=C.RapidsConf())
    node = df.filter(E.col("a") > E.lit(1)).physical_plan()
    assert getattr(node, "_fastpath", False) is False


def test_offpath_takes_semaphore():
    big = pa.table({"a": list(range(200_000)),
                    "b": [0.0] * 200_000})
    s0 = G.snapshot()
    from_arrow(big, conf=C.RapidsConf()).filter(
        E.col("a") > E.lit(1)).to_arrow()
    s1 = G.snapshot()
    assert s1["semaphore_acquire_total"] > s0["semaphore_acquire_total"]


# ---------------------------------------------------------------------------
# persistent program cache: corruption recovery (same-process shape; the
# cross-process warm start is tests/test_warmstart.py)
# ---------------------------------------------------------------------------


def test_corrupted_persist_entry_recovers(tmp_path):
    import os

    from spark_rapids_tpu.config import conf as _conf
    from spark_rapids_tpu.exec import jit_cache, jit_persist

    active0 = _conf.get_active()
    _conf.set_active(_conf.RapidsConf(
        {"spark.rapids.tpu.jit.persist.dir": str(tmp_path)}))
    try:
        key = ("test_plan_cache", "corrupt-recovery")
        fn = jit_cache.shared_jit(key, lambda: (lambda x: x * 2))
        import jax.numpy as jnp
        import numpy as np
        expect = np.asarray(fn(jnp.arange(16)))
        files = os.listdir(tmp_path)
        assert len(files) == 1, "program was not persisted"
        with open(tmp_path / files[0], "wb") as f:
            f.write(b"definitely not a serialized program")
        # fresh-process shape: drop the in-memory entry, reload from disk
        with jit_cache._LOCK:
            jit_cache._CACHE.pop(key)
        c0 = jit_persist.counters()
        fn2 = jit_cache.shared_jit(key, lambda: (lambda x: x * 2))
        out = np.asarray(fn2(jnp.arange(16)))
        c1 = jit_persist.counters()
        assert (out == expect).all()
        assert c1["jit_persist_error_total"] == \
            c0["jit_persist_error_total"] + 1
        assert c1["jit_persist_store_total"] == \
            c0["jit_persist_store_total"] + 1, \
            "corrupt entry was not replaced by a recompiled one"
    finally:
        _conf.set_active(active0)


def test_persist_disabled_stays_off(tmp_path):
    import os

    from spark_rapids_tpu.config import conf as _conf
    from spark_rapids_tpu.exec import jit_cache

    active0 = _conf.get_active()
    _conf.set_active(_conf.RapidsConf(
        {"spark.rapids.tpu.jit.persist.enabled": False,
         "spark.rapids.tpu.jit.persist.dir": str(tmp_path)}))
    try:
        import jax.numpy as jnp
        fn = jit_cache.shared_jit(("test_plan_cache", "disabled"),
                                  lambda: (lambda x: x + 3))
        fn(jnp.arange(4))
        assert os.listdir(tmp_path) == []
    finally:
        _conf.set_active(active0)
