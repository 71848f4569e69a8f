"""Oversized-aggregation repartition tests (docs/oversized_state.md): when
merge state exceeds the target (or the pool denies it), the aggregate
recursively hash-repartitions its partials into buckets and aggregates each
bucket independently — split-retry stays the last resort, and results are
bit-identical to the unpressured plan."""

import numpy as np
import pyarrow as pa
import pytest

import conftest

from spark_rapids_tpu import faults
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exec import BatchSourceExec, HashAggregateExec
from spark_rapids_tpu.exec import aggregate as AGG
from spark_rapids_tpu.exprs.expr import Count, Sum, col
from spark_rapids_tpu.mem.pool import HbmPool, set_pool


@pytest.fixture(autouse=True)
def _clean_conf_and_pool():
    yield
    C.set_active(None)
    set_pool(None)
    faults.install("")


def _table(n=20_000, n_keys=5000, seed=7):
    rng = np.random.default_rng(seed)
    return pa.table({
        "k": pa.array(rng.integers(0, n_keys, n), pa.int64()),
        "s": pa.array([f"g{x:04d}" for x in rng.integers(0, 3000, n)]),
        "v": pa.array(rng.integers(-100, 100, n), pa.int64()),
    })


def _source(table, batch_rows):
    schema = T.Schema.from_arrow(table.schema)
    batches = [batch_from_arrow(table.slice(i, batch_rows), 16)
               for i in range(0, table.num_rows, batch_rows)]
    return BatchSourceExec([batches], schema)


def _agg(table, batch_rows=1024):
    return HashAggregateExec([col("k"), col("s")],
                             [Sum(col("v")).alias("sv"),
                              Count(col("v")).alias("cv")],
                             _source(table, batch_rows))


def _run(node):
    out = []
    for b in node.execute_all():
        out.extend(batch_to_arrow(b, node.output_schema).to_pylist())
    return sorted((r["k"], r["s"], r["sv"], r["cv"]) for r in out)


def test_capped_pool_completes_via_repartition_bit_identical(monkeypatch):
    """More merge state than the pool target: the agg must finish through
    the repartition door (NOT split-retry) with bit-identical rows."""
    t = _table()
    C.set_active(C.RapidsConf(
        {"spark.rapids.tpu.sql.agg.repartition.enabled": False}))
    base = _run(_agg(t))

    # capped pool; targetBytes=0 derives target = limit // 4, so the
    # ~20k-group merge state (hundreds of KB over 20 partials) exceeds it
    set_pool(HbmPool(1 << 21))
    C.set_active(C.RapidsConf())  # defaults: repartition enabled
    monkeypatch.setattr(
        HashAggregateExec, "_merge_last_resort",
        lambda self, hs, fw: pytest.fail(
            "split-retry last resort reached; repartition should complete"))
    s0 = AGG.repartition_snapshot()
    node = _agg(t)
    got = _run(node)
    s1 = AGG.repartition_snapshot()

    assert got == base
    assert s1["total"] > s0["total"]
    assert node.metrics["numRepartitions"].value > 0


def test_repartition_recurses_and_spills_buckets(monkeypatch):
    """A tiny target forces recursion past level 0; bucket sub-batches are
    registered spillable and shed through the framework under pressure."""
    from spark_rapids_tpu.mem.spill import get_framework

    # The recursion binds a program per bucket shape and depth: 60,325
    # mappings when the capped run holds them all at once, of the 65,530 a
    # process may have (ROADMAP D1). So it starts from none, and gives
    # them back between two levels whenever half the limit is in use.
    recurse = HashAggregateExec._repartition_merge

    def recurse_with_room(self, *args, **kwargs):
        if conftest.mappings() > 32_000:
            conftest.drop_programs()
        yield from recurse(self, *args, **kwargs)

    monkeypatch.setattr(HashAggregateExec, "_repartition_merge",
                        recurse_with_room)
    conftest.drop_programs()
    t = _table()
    C.set_active(C.RapidsConf(
        {"spark.rapids.tpu.sql.agg.repartition.enabled": False}))
    base = _run(_agg(t))

    conftest.drop_programs()
    set_pool(HbmPool(1 << 21))
    C.set_active(C.RapidsConf({
        "spark.rapids.tpu.sql.agg.repartition.targetBytes": 1,
        "spark.rapids.tpu.sql.agg.repartition.numBuckets": 4,
        "spark.rapids.tpu.sql.agg.repartition.maxDepth": 3,
    }))
    s0 = AGG.repartition_snapshot()
    got = _run(_agg(t))
    s1 = AGG.repartition_snapshot()
    fw = get_framework()

    assert got == base
    assert s1["max_depth"] >= 2
    # the capped pool could not hold every bucket: some spilled, in chunks
    assert fw.spilled_to_host_count > 0
    assert fw.chunks_written_count > 0


def test_repartition_site_fault_recovers():
    """An injected RetryOOM at agg.repartition is retried with backoff and
    recorded as recovered; rows stay bit-identical."""
    t = _table(4000, n_keys=2000)
    C.set_active(C.RapidsConf(
        {"spark.rapids.tpu.sql.agg.repartition.enabled": False}))
    base = _run(_agg(t))

    C.set_active(C.RapidsConf(
        {"spark.rapids.tpu.sql.agg.repartition.targetBytes": 1}))
    faults.install("agg.repartition:retry@count=1")
    c0 = faults.counters()
    got = _run(_agg(t))
    c1 = faults.counters()

    assert got == base
    assert c1["fault_injected_total"] > c0["fault_injected_total"]
    assert c1["fault_recovered_total"] > c0["fault_recovered_total"]


def test_single_partial_skips_repartition():
    """One partial batch means nothing to repartition: the plain merge
    runs even with an absurdly low target."""
    t = _table(500, n_keys=100)
    C.set_active(C.RapidsConf(
        {"spark.rapids.tpu.sql.agg.repartition.targetBytes": 1}))
    s0 = AGG.repartition_snapshot()
    got = _run(_agg(t, batch_rows=1024 * 1024))
    s1 = AGG.repartition_snapshot()
    assert s1["total"] == s0["total"]
    assert len(got) == len({(r[0], r[1]) for r in got})
