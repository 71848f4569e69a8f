"""Tracker-wide computation-reuse differential.

Every TPC-H and TPC-DS query the planner can build runs twice — exchange
reuse on and off — through the full DataFrame/Overrides/shuffle pipeline;
results must be byte-identical. This is the acceptance net for
plan/reuse.py + exec/reuse.py: collapsing repeated exchange/broadcast/
subquery subtrees into shared materializations may change dispatch
structure and bytes moved, never results.
"""

import pytest

from spark_rapids_tpu.bench import tpcds, tpch
from spark_rapids_tpu.config.conf import RapidsConf

REUSE_KEY = "spark.rapids.tpu.sql.exchange.reuse.enabled"


@pytest.fixture(scope="module")
def tpch_tables():
    return tpch.tables_for(0.005, seed=3)


@pytest.fixture(scope="module")
def tpcds_tables():
    return tpcds.tables_for(0.002, seed=42)


@pytest.mark.parametrize("q", sorted(tpch.DF_QUERIES))
def test_tpch_reuse_differential(tpch_tables, q):
    def run(enabled):
        conf = RapidsConf({REUSE_KEY: enabled})
        d = tpch.df_tables(tpch_tables, conf, shuffle_partitions=2,
                           partitions=2, batch_rows=512)
        return tpch.DF_QUERIES[q](d).to_arrow()

    on, off = run(True), run(False)
    assert on.equals(off), f"tpch {q}: reuse changed results"


@pytest.mark.parametrize("q", sorted(tpcds.QUERIES))
def test_tpcds_reuse_differential(tpcds_tables, q):
    def run(enabled):
        conf = RapidsConf({REUSE_KEY: enabled})
        return tpcds.build_query(q, tpcds_tables, conf,
                                 shuffle_partitions=2).to_arrow()

    on, off = run(True), run(False)
    assert on.equals(off), f"tpcds {q}: reuse changed results"
