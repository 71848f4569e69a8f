"""Whole-stage fusion (exec/fused.py): structure, correctness, fallbacks,
metric attribution, and the jit-cache key regression from VERDICT r5.

The full tracker differential (every TPC-H/TPC-DS planner query, fusion
on vs off) lives in test_fusion_diff.py; this module keeps to hand-built
chains plus one small planner query.
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.exec import (
    BatchSourceExec,
    FilterExec,
    HashAggregateExec,
    ProjectExec,
    SortExec,
    SortOrder,
    TpuFusedStageExec,
    fuse_exec,
)
from spark_rapids_tpu.exec import jit_cache
from spark_rapids_tpu.exprs.expr import Like, Sum, col


def source(table: pa.Table, batch_rows=None, min_bucket=16):
    schema = T.Schema.from_arrow(table.schema)
    if batch_rows is None:
        batches = [batch_from_arrow(table, min_bucket)]
    else:
        batches = [
            batch_from_arrow(table.slice(i, batch_rows), min_bucket)
            for i in range(0, max(table.num_rows, 1), batch_rows)
        ]
    return BatchSourceExec([batches], schema)


def rows(node):
    out = []
    for b in node.execute_all():
        out.extend(batch_to_arrow(b, node.output_schema).to_pylist())
    return out


def canon(rs):
    return sorted((tuple(sorted(r.items())) for r in rs))


def _table(n=1000, seed=0):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, 37, n), pa.int64()),
        "v": pa.array(rng.normal(size=n)),
        "w": pa.array(rng.integers(0, 100, n), pa.int64()),
    })


# ---------------------------------------------------------------------------
# plan rewrite structure
# ---------------------------------------------------------------------------


def test_fuse_collapses_chain_under_barrier():
    t = _table()
    chain = ProjectExec([(col("k") + col("w")).alias("kw"),
                         col("v").alias("v")],
                        FilterExec(col("w") > 10, source(t)))
    top = SortExec([SortOrder(col("kw"))], chain)
    fused = fuse_exec(top)
    # sort is a barrier: stays, its child becomes one fused stage
    assert isinstance(fused, SortExec)
    stage = fused.children[0]
    assert isinstance(stage, TpuFusedStageExec)
    assert [type(op).__name__ for op in stage.fused_ops] == [
        "FilterExec", "ProjectExec"]
    assert "TpuFusedStage" in fused.explain()


def test_min_operators_respected():
    t = _table()
    lone = FilterExec(col("w") > 10, source(t))
    assert not isinstance(fuse_exec(lone, min_ops=2), TpuFusedStageExec)
    # an absorbed aggregate counts as two dispatch sites (windowed
    # streaming alone beats per-batch dispatch), so agg-only chains fuse
    agg = HashAggregateExec([col("k")], [Sum(col("v")).alias("s")],
                            source(_table()))
    assert isinstance(fuse_exec(agg, min_ops=2), TpuFusedStageExec)


# ---------------------------------------------------------------------------
# correctness: fused == classic
# ---------------------------------------------------------------------------


def test_plain_stage_matches_classic():
    t = _table(2000, seed=1)
    def build():
        return ProjectExec([(col("k") * col("w")).alias("kw")],
                           FilterExec(col("w") > 50,
                                      source(t, batch_rows=256)))
    expect = canon(rows(build()))
    stage = fuse_exec(build())
    assert isinstance(stage, TpuFusedStageExec)
    assert canon(rows(stage)) == expect
    assert stage.metrics["numFusedBatches"].value > 0
    assert stage.metrics["numFallbacks"].value == 0


def test_streaming_agg_stage_matches_classic():
    t = _table(3000, seed=2)
    def build():
        return HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s")],
            FilterExec(col("w") > 20, source(t, batch_rows=256)))
    expect = canon(rows(build()))
    stage = fuse_exec(build())
    assert isinstance(stage, TpuFusedStageExec)
    got = canon(rows(stage))
    assert [g[0] for g in got] == [e[0] for e in expect]
    for g, e in zip(got, expect):
        assert g[1][1] == pytest.approx(e[1][1], rel=1e-12)
    assert stage.metrics["numFallbacks"].value == 0


def test_carry_overflow_falls_back_correctly():
    # first batch defines the carry capacity; a later flood of fresh group
    # keys must trip the on-device overflow flag and re-run the partition
    # unfused — never emit truncated buffers
    n = 4096
    k = np.arange(n, dtype=np.int64)  # every row its own group
    t = pa.table({"k": pa.array(k), "v": pa.array(np.ones(n))})
    def build():
        return HashAggregateExec([col("k")],
                                 [Sum(col("v")).alias("s")],
                                 source(t, batch_rows=128))
    expect = canon(rows(build()))
    stage = fuse_exec(build())
    assert isinstance(stage, TpuFusedStageExec)
    assert canon(rows(stage)) == expect
    assert stage.metrics["numFallbacks"].value >= 1


def test_string_group_keys_roundtrip():
    rng = np.random.default_rng(5)
    n = 1500
    keys = [f"key_{i % 53:03d}" for i in rng.integers(0, 53, n)]
    t = pa.table({"k": pa.array(keys), "v": pa.array(rng.normal(size=n))})
    def build():
        return HashAggregateExec([col("k")], [Sum(col("v")).alias("s")],
                                 source(t, batch_rows=256))
    expect = canon(rows(build()))
    stage = fuse_exec(build())
    got = canon(rows(stage))
    assert [g[0] for g in got] == [e[0] for e in expect]
    for g, e in zip(got, expect):
        assert g[1][1] == pytest.approx(e[1][1], rel=1e-12)


def test_fusion_conf_gates_rewrite():
    from spark_rapids_tpu.config.conf import RapidsConf
    from spark_rapids_tpu.plan import from_arrow

    t = _table(500, seed=3)
    def plan(enabled):
        conf = RapidsConf(
            {"spark.rapids.tpu.sql.fusion.enabled": enabled})
        df = from_arrow(t, conf).filter(col("w") > 10) \
            .group_by("k").agg(Sum(col("v")).alias("s"))
        return df.physical_plan()

    def has_stage(node):
        if isinstance(node, TpuFusedStageExec):
            return True
        return any(has_stage(c) for c in node.children)

    assert has_stage(plan(True))
    assert not has_stage(plan(False))


# ---------------------------------------------------------------------------
# metric attribution survives fusion
# ---------------------------------------------------------------------------


def test_attribution_survives_fusion():
    from spark_rapids_tpu.obs.profile import QueryProfile

    t = _table(2000, seed=4)
    stage = fuse_exec(ProjectExec(
        [(col("k") + col("w")).alias("kw")],
        FilterExec(col("w") > 50, source(t, batch_rows=256))))
    assert isinstance(stage, TpuFusedStageExec)
    prof = QueryProfile("fusion-test")
    list(stage.execute_all())
    prof.finish(stage)
    nodes = prof.to_dict()["nodes"]
    fused_rows = [nd for nd in nodes if "fused" in nd]
    # every constituent reports under the stage with its own rows
    assert {nd["name"] for nd in fused_rows} == {"FilterExec", "ProjectExec"}
    filt = next(nd for nd in fused_rows if nd["name"] == "FilterExec")
    assert filt["metrics"]["numOutputRows"] > 0
    assert filt["metrics"]["numOutputBatches"] > 0
    txt = prof.explain_analyze()
    assert "fused=#" in txt


# ---------------------------------------------------------------------------
# jit-cache: key regression (VERDICT r5) + counters
# ---------------------------------------------------------------------------


def test_like_patterns_get_distinct_programs():
    # two filters identical except for the LIKE pattern literal: repr-based
    # keys collided here (VERDICT r5) and silently shared one compiled
    # program; cache_key must include Expression._params
    t = pa.table({"s": pa.array(["apple", "banana", "avocado", "berry"])})
    before = jit_cache.cache_stats()["jit_cache_size"]
    fa = FilterExec(Like(col("s"), "a%"), source(t))
    fb = FilterExec(Like(col("s"), "b%"), source(t))
    ka, kb = fa.batch_fn_key(), fb.batch_fn_key()
    assert ka != kb
    ra = [r["s"] for r in rows(fa)]
    rb = [r["s"] for r in rows(fb)]
    after = jit_cache.cache_stats()["jit_cache_size"]
    assert after >= before + 2  # one compiled program per pattern
    assert sorted(ra) == ["apple", "avocado"]
    assert sorted(rb) == ["banana", "berry"]


def test_jit_cache_counters_in_gauges():
    from spark_rapids_tpu.obs import gauges

    t = pa.table({"s": pa.array(["x", "yy"])})
    list(FilterExec(Like(col("s"), "x%"), source(t)).execute_all())
    snap = gauges.snapshot()
    assert snap["jit_cache_size"] >= 1
    assert snap["jit_cache_miss_total"] >= 1
    assert snap["jit_cache_hit_total"] >= 0
    from spark_rapids_tpu.obs.expose import render_prometheus

    text = render_prometheus(snap)
    assert "srtpu_jit_cache_size" in text
    assert "srtpu_jit_cache_miss_total" in text
