"""Semi and anti joins inside their fused stage: the exists form
(exec/join.py ``HashJoinExec.fuses_as_exists``, ``_fused_exists``,
``_FusedExistsProbe``; exec/fused.py ``execute_live``). A semi or anti
join with no residual condition on one integer key a side is absorbed into
its probe side's stage; its build, batch by batch, marks its keys in a
presence map, and the probe ANDs a byte a row into the batch's live mask.

What is held: for each case, the fused stage's rows equal the unfused
``HashJoinExec``'s and the CPU engine's (``CpuJoinExec``) as multisets,
for semi and anti alike: a build whose keys repeat (built by a fused stage
of its own, read through its masks), a build with no live row, probe keys
below and above the build's range, null probe keys (dropped by semi, kept
by anti), and a broadcast build read whole by every probe partition. Each builds once, by exists, with no fallback, and the
probe counters read the rows tested and kept. The refusals give the same
rows unfused: a residual condition, two key columns and a string key are
not absorbed (the plan shows them); a build whose keys span more than
``PRESENCE_MAX_DOMAIN`` is seen only when it runs, and falls back once as
``join-refused``."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config.conf import RapidsConf
from spark_rapids_tpu.exec.join import HashJoinExec
from spark_rapids_tpu.exprs.expr import GreaterThan, LessThan, col, lit
from spark_rapids_tpu.obs import gauges
from spark_rapids_tpu.plan import from_arrow

BATCH = 2048
JOINS = ("left_semi", "left_anti")
UNFUSED = {"spark.rapids.tpu.sql.fusion.enabled": False}
CPU = {"spark.rapids.tpu.sql.enabled": False}


def _probe_table(keys, valid=None):
    n = len(keys)
    return pa.table({"k": pa.array(keys, pa.int64(),
                                   mask=None if valid is None else ~valid),
                     "s": pa.array([f"r{i}" for i in range(n)]),
                     "v": pa.array(np.arange(n), pa.int64())})


def _build_table(keys):
    keys = np.asarray(keys, np.int64)
    return pa.table({"rk": pa.array(keys),
                     "rs": pa.array([f"b{k % 97}" for k in keys]),
                     "w": pa.array(np.arange(len(keys)), pa.int64())})


def _repeated_keys():
    """Every third key of 0..4,500 up to five times, over three batches of
    the build; probe keys over the same range."""
    rng = np.random.default_rng(11)
    rk = np.repeat(np.arange(0, 4500, 3), rng.integers(1, 6, 1500))
    return (_probe_table(rng.integers(0, 4500, 3 * BATCH)),
            _build_table(rk), {})


def _empty_build():
    """The build's filter keeps no row: semi keeps nothing, anti all."""
    rng = np.random.default_rng(12)
    rk = rng.integers(0, 500, 2 * BATCH)
    return (_probe_table(rng.integers(0, 500, 2 * BATCH)),
            _build_table(rk), {"floor": len(rk)})


def _outside_range():
    """Negative build keys; half the probe keys fall below the build's
    least key or above its greatest, past the map's end."""
    rng = np.random.default_rng(13)
    rk = rng.integers(-5000, 5000, BATCH)
    pk = np.concatenate([rng.integers(-1 << 40, -5001, BATCH),
                         rng.integers(-5000, 5000, BATCH),
                         rng.integers(5000, 1 << 40, BATCH)])
    return _probe_table(pk), _build_table(rk), {}


def _null_probe_keys():
    """A tenth of the probe keys null; the build holds every key."""
    rng = np.random.default_rng(14)
    pk = rng.integers(0, 300, 2 * BATCH)
    valid = rng.random(2 * BATCH) > 0.1
    return _probe_table(pk, valid), _build_table(np.arange(300)), {}


def _broadcast_build():
    """Both sides in two partitions, the build small: a broadcast join,
    whose exists build reads every partition of the build, never the
    probe partition's slice; each key's rows lie in one partition."""
    rng = np.random.default_rng(16)
    rk = np.sort(rng.integers(0, 3000, 2 * BATCH))
    return (_probe_table(rng.integers(0, 3000, 2 * BATCH)),
            _build_table(rk), {"parts": 2})


CASES = {"repeated-keys": _repeated_keys, "empty-build": _empty_build,
         "outside-range": _outside_range, "null-probe-keys": _null_probe_keys,
         "broadcast-build": _broadcast_build}


def _df(left, right, how, conf=None, floor=0, parts=1, **join):
    """The probe filtered (a fused stage), joined to the build rows whose
    ``w`` passes ``floor`` (a filter and a projection: a fused stage of its
    own) on k = rk; each side in ``parts`` partitions."""
    lhs = from_arrow(left, conf, batch_rows=BATCH, partitions=parts).filter(
        GreaterThan(col("v"), lit(-1)))
    rhs = (from_arrow(right, conf, batch_rows=BATCH, partitions=parts)
           .filter(GreaterThan(col("w"), lit(floor)))
           .select("rk", "rs", "w"))
    join.setdefault("left_on", "k")
    join.setdefault("right_on", "rk")
    return lhs.join(rhs, how=how, **join)


def _rows(table):
    return sorted(zip(*(table[c].to_pylist() for c in ("k", "s", "v"))),
                  key=repr)


def _run(left, right, how, **kw):
    """(fused rows, counter deltas, plan) and the unfused and CPU rows."""
    df = _df(left, right, how, **kw)
    before = gauges.snapshot()
    fused = df.to_arrow()
    after = gauges.snapshot()
    delta = {k: after[k] - before[k] for k in after
             if k.startswith(("join_", "fused_fallback_"))}
    unfused = _df(left, right, how, RapidsConf(UNFUSED), **kw)
    cpu = _df(left, right, how, RapidsConf(CPU), **kw)
    assert "CpuJoin" in cpu.physical_plan().explain()
    return (fused, delta, df.physical_plan(),
            _rows(unfused.to_arrow()), _rows(cpu.to_arrow()))


def _absorbed(plan) -> list:
    """The hash joins the plan's fused stages absorbed."""
    out = [op for op in getattr(plan, "segments", ())
           if isinstance(op, HashJoinExec)]
    for c in plan.children:
        out += _absorbed(c)
    return out


@pytest.mark.parametrize("how", JOINS)
@pytest.mark.parametrize("case", CASES)
def test_the_fused_exists_probe_gives_the_rows_of_the_unfused_joins(
        case, how):
    left, right, kw = CASES[case]()
    fused, delta, plan, unfused, cpu = _run(left, right, how, **kw)
    assert [j.join_type for j in _absorbed(plan)] == [how]
    assert _rows(fused) == unfused == cpu
    assert delta["join_build_path_exists_total"] == 1
    assert delta["join_build_path_total"] == 1
    assert delta["fused_fallback_total"] == 0
    assert delta["join_semi_probe_rows_total"] == len(left)
    assert delta["join_semi_probe_hits_total"] == fused.num_rows
    if case == "null-probe-keys":
        nulls = left["k"].null_count
        kept = sum(r[0] is None for r in _rows(fused))
        assert kept == (nulls if how == "left_anti" else 0) and nulls > 0


def _residual():
    left, right, _ = _repeated_keys()
    return left, right, {"condition": LessThan(col("v"), col("w"))}


def _two_keys():
    left, right, _ = _repeated_keys()
    return left, right, {"left_on": ["k", "v"], "right_on": ["rk", "w"]}


def _string_key():
    left, right, _ = _repeated_keys()
    return left, right, {"left_on": "s", "right_on": "rs"}


def _wide_span():
    rng = np.random.default_rng(15)
    far = HashJoinExec.PRESENCE_MAX_DOMAIN
    rk = np.concatenate([[0, far], rng.integers(0, 1000, BATCH)])
    pk = np.concatenate([[far, far - 1], rng.integers(0, 2000, BATCH)])
    return _probe_table(pk), _build_table(rk), {}


REFUSALS = {"residual-condition": (_residual, False),
            "two-keys": (_two_keys, False),
            "string-key": (_string_key, False),
            "span-past-the-map": (_wide_span, True)}


@pytest.mark.parametrize("how", JOINS)
@pytest.mark.parametrize("case", REFUSALS)
def test_a_refused_semi_join_runs_unfused_with_the_same_rows(case, how):
    """What the plan shows is not absorbed; what only the data show falls
    back before any output, counted once as join-refused."""
    make, seen_at_run = REFUSALS[case]
    left, right, join = make()
    fused, delta, plan, unfused, cpu = _run(left, right, how, **join)
    assert _rows(fused) == unfused == cpu
    assert delta["join_build_path_exists_total"] == 0
    assert delta["join_semi_probe_rows_total"] == 0
    if seen_at_run:
        assert [j.join_type for j in _absorbed(plan)] == [how]
        assert delta["fused_fallback_join_refused_total"] == 1
        assert delta["fused_fallback_total"] == 1
    else:
        assert _absorbed(plan) == []
        assert delta["fused_fallback_total"] == 0
