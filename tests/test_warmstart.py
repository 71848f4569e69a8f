"""Cross-process warm start and cache/fastpath differential.

Two halves:

1. A subprocess primes the persistent program cache (jit_persist) into a
   tmp directory, then a second subprocess runs the same queries and must
   serve its programs from disk: ``jit_persist_hit_total > 0`` and a
   compile phase well below the cold process's.

2. Every TPC-H and TPC-DS query the planner can build runs with the whole
   interactive fast path on (plan memo + persistent programs + small-query
   bypass, each query executed twice so the second run is a memo hit) and
   with all three disabled; results must be byte-identical.
"""

import json
import os
import subprocess
import sys

import pytest

from spark_rapids_tpu.bench import tpcds, tpch
from spark_rapids_tpu.config.conf import RapidsConf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
from spark_rapids_tpu.bench import tpch
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exec import jit_cache, jit_persist
from spark_rapids_tpu.obs.profile import last_profile

cache_dir = sys.argv[1]
conf = C.RapidsConf({"spark.rapids.tpu.jit.persist.dir": cache_dir})
C.set_active(conf)
tables = tpch.tables_for(0.01, seed=3)
d = tpch.df_tables(tables, conf, shuffle_partitions=2, partitions=2,
                   batch_rows=512)
rows = []
for q in ("q1", "q6"):
    out = tpch.DF_QUERIES[q](d).to_arrow()
    rows.append(out.num_rows)
prof = last_profile()
print(json.dumps({
    "rows": rows,
    "compile_ms": jit_cache.compile_ns_total() / 1e6,
    **jit_persist.counters(),
}))
"""


def _run_child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(cache_dir)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, f"child failed:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cross_process_warm_start(tmp_path):
    cold = _run_child(tmp_path)
    assert cold["jit_persist_store_total"] > 0, \
        f"cold process persisted nothing: {cold}"
    warm = _run_child(tmp_path)
    assert warm["rows"] == cold["rows"]
    assert warm["jit_persist_hit_total"] > 0, \
        f"warm process compiled from scratch: {warm}"
    assert warm["jit_persist_error_total"] == 0
    # The warm process deserializes programs instead of tracing them. On a
    # pristine XLA disk cache that saves trace time only (~20%: the
    # deserialized HLO still compiles once); once XLA's own cache has seen
    # the exported programs the saving is several-fold. Gate on the floor.
    assert warm["compile_ms"] < cold["compile_ms"] * 0.9, \
        (f"warm start did not cut compile time: cold "
         f"{cold['compile_ms']:.0f}ms -> warm {warm['compile_ms']:.0f}ms")


# ---------------------------------------------------------------------------
# cached / fastpath on-off differential over the tracker set
# ---------------------------------------------------------------------------

_ON = {}
_OFF = {"spark.rapids.tpu.plan.cache.enabled": False,
        "spark.rapids.tpu.jit.persist.enabled": False,
        "spark.rapids.tpu.fastpath.enabled": False}


@pytest.fixture(scope="module")
def tpch_tables():
    return tpch.tables_for(0.005, seed=3)


@pytest.fixture(scope="module")
def tpcds_tables():
    return tpcds.tables_for(0.002, seed=42)


@pytest.mark.parametrize("q", sorted(tpch.DF_QUERIES))
def test_tpch_cache_differential(tpch_tables, q):
    def run(settings):
        conf = RapidsConf(settings)
        d = tpch.df_tables(tpch_tables, conf, shuffle_partitions=2,
                           partitions=2, batch_rows=512)
        return tpch.DF_QUERIES[q](d).to_arrow()

    first = run(_ON)      # cold: populates the plan memo
    second = run(_ON)     # warm: served from the memo
    off = run(_OFF)
    assert second.equals(first), f"tpch {q}: memo hit changed results"
    assert first.equals(off), f"tpch {q}: caches/fastpath changed results"


@pytest.mark.parametrize("q", sorted(tpcds.QUERIES))
def test_tpcds_cache_differential(tpcds_tables, q):
    def run(settings):
        conf = RapidsConf(settings)
        return tpcds.build_query(q, tpcds_tables, conf,
                                 shuffle_partitions=2).to_arrow()

    first = run(_ON)
    second = run(_ON)
    off = run(_OFF)
    assert second.equals(first), f"tpcds {q}: memo hit changed results"
    assert first.equals(off), f"tpcds {q}: caches/fastpath changed results"
