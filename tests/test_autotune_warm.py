"""Cross-process self-tuning dispatch + tracker differential (slow lane).

Two halves, mirroring tests/test_warmstart.py:

1. A subprocess runs a dispatch-heavy workload (semi-join + fused agg +
   the TPC-H tracker queries) with the autotune store pointed at a tmp
   directory; a second subprocess must load the persisted timings and
   dispatch at least one join/agg from measurements
   (``source=measured``, ``autotune_hit_total > 0``) with zero
   re-calibration — and produce byte-identical results. A third
   subprocess with autotune disabled must match too (measurements only
   re-rank order-equivalent paths, never change results).

2. Every TPC-H and TPC-DS tracker query runs twice with autotune on (the
   second pass dispatches from the store the first populated) and once
   with it off; results must be identical.
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
import hashlib, json, sys
import pyarrow as pa
from spark_rapids_tpu.bench import tpch
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.plan import autotune as AT
from spark_rapids_tpu.plan.dataframe import from_arrow

store_dir, mode = sys.argv[1], sys.argv[2]
conf_kv = {"spark.rapids.tpu.autotune.dir": store_dir,
           "spark.rapids.tpu.autotune.enabled": mode == "on",
           "spark.rapids.tpu.profile.enabled": True}
C.set_active(C.RapidsConf(conf_kv))

rows, digests, measured = [], [], 0

def note(q, out):
    global measured
    rows.append(out.num_rows)
    digests.append(hashlib.sha256(
        repr(out.to_pydict()).encode()).hexdigest())
    prof = q.last_profile()
    if prof is not None:
        for k, n in prof.dispatch_paths().items():
            if k.endswith(":measured") and (
                    k.startswith("join:")):
                measured += n

conf = C.RapidsConf(conf_kv)
# dispatch-heavy synthetic: a semi-join (order-equivalent ht<->sorted
# candidates) feeding a fused int-sum agg (tunable batch window)
t1 = pa.table({"k": pa.array([i % 200 for i in range(2000)], pa.int64()),
               "v": pa.array([i % 7 for i in range(2000)], pa.int64())})
t2 = pa.table({"k": pa.array([i % 150 for i in range(300)], pa.int64())})
df1 = from_arrow(t1, conf=conf, batch_rows=256, partitions=2)
df2 = from_arrow(t2, conf=conf, batch_rows=256, partitions=2)
q = (df1.join(df2, on="k", how="left_semi")
     .group_by("k").agg(E.Sum(E.col("v"))))
note(q, q.to_arrow())

tables = tpch.tables_for(0.005, seed=3)
d = tpch.df_tables(tables, conf, shuffle_partitions=2, partitions=2,
                   batch_rows=512)
for name in sorted(tpch.DF_QUERIES):
    q = tpch.DF_QUERIES[name](d)
    note(q, q.to_arrow())

print(json.dumps({"rows": rows, "digests": digests,
                  "measured": measured, **AT.counters()}))
"""


def _run_child(store_dir, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    # the conftest-pinned hermetic dir must not leak into children: the
    # store location under test is the conf-passed one
    env.pop("SRTPU_AUTOTUNE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(store_dir), mode],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, f"child failed:\n{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cross_process_self_tuning(tmp_path):
    cold = _run_child(tmp_path, "on")
    assert cold["autotune_store_total"] > 0, \
        f"cold process persisted no timings: {cold}"
    assert len(os.listdir(tmp_path)) == 1, "one store file per environment"
    warm = _run_child(tmp_path, "on")
    assert warm["rows"] == cold["rows"]
    assert warm["digests"] == cold["digests"], \
        "measured dispatch changed query results"
    assert warm["autotune_hit_total"] > 0, \
        f"warm process never dispatched from the store: {warm}"
    assert warm["measured"] > 0, \
        f"warm process made no measured join/agg dispatch: {warm}"
    off = _run_child(tmp_path, "off")
    assert off["digests"] == cold["digests"], \
        "autotune-off results differ: measurements changed results"
    assert off["autotune_hit_total"] == 0
    assert off["autotune_store_total"] == 0


# ---------------------------------------------------------------------------
# autotune on/off differential over the tracker set
# ---------------------------------------------------------------------------

_OFF = {"spark.rapids.tpu.autotune.enabled": False,
        "spark.rapids.tpu.profile.enabled": True}
_ON = {"spark.rapids.tpu.profile.enabled": True}


@pytest.fixture(scope="module")
def tpch_tables():
    return tpch.tables_for(0.005, seed=3)


@pytest.fixture(scope="module")
def tpcds_tables():
    return tpcds.tables_for(0.002, seed=42)


@pytest.mark.parametrize("q", sorted(tpch.DF_QUERIES))
def test_tpch_autotune_differential(tpch_tables, q):
    def run(settings):
        conf = RapidsConf(settings)
        d = tpch.df_tables(tpch_tables, conf, shuffle_partitions=2,
                           partitions=2, batch_rows=512)
        return tpch.DF_QUERIES[q](d).to_arrow()

    first = run(_ON)     # populates the store (profile feedback)
    second = run(_ON)    # may dispatch from measurements
    off = run(_OFF)
    assert second.equals(first), f"tpch {q}: measured dispatch changed results"
    assert first.equals(off), f"tpch {q}: autotune changed results"


@pytest.mark.parametrize("q", sorted(tpcds.QUERIES))
def test_tpcds_autotune_differential(tpcds_tables, q):
    def run(settings):
        conf = RapidsConf(settings)
        return tpcds.build_query(q, tpcds_tables, conf,
                                 shuffle_partitions=2).to_arrow()

    first = run(_ON)
    second = run(_ON)
    off = run(_OFF)
    assert second.equals(first), f"tpcds {q}: measured dispatch changed results"
    assert first.equals(off), f"tpcds {q}: autotune changed results"
