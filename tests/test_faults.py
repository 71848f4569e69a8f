"""Fault-injection & resilience suite (docs/fault_injection.md).

Fast-lane sections: schedule grammar + determinism + thread safety of the
registry (faults/registry.py), the legacy OomInjector race fix, the shuffle
integrity trailer + refetch path, blacklist classification and CPU
degradation, retry backoff/recovery accounting, and the cache-key static
guard (tools/lint/cache_keys.py).

Chaos lane (``SRTPU_CHAOS_LANE=1``, tests/run_chaos_lane.sh): every tracker
TPC-H/TPC-DS query runs under a seeded fault schedule (injected OOMs,
corrupted shuffle blocks, slow serializes) and must be bit-identical to the
fault-free run with ``srtpu_fault_recovered_total`` > 0 — the acceptance
net for the hardened retry/refetch/degradation paths.
"""

import os
import threading
import time

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import faults
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.config.conf import RapidsConf
from spark_rapids_tpu.faults import blacklist as bl
from spark_rapids_tpu.faults.registry import (
    FaultInjectedError, FaultRegistry, parse_spec,
)
from spark_rapids_tpu.shuffle import integrity

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHAOS_LANE = os.environ.get("SRTPU_CHAOS_LANE") == "1"
FAULTS_SEED = int(os.environ.get("SRTPU_FAULTS_SEED", "42"))

chaos = pytest.mark.skipif(
    not CHAOS_LANE, reason="chaos lane; run tests/run_chaos_lane.sh")


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with no schedule installed and no
    blacklist history (counters are process totals and persist; tests
    assert deltas)."""
    faults.reset()
    bl.clear()
    yield
    faults.reset()
    bl.clear()
    C.set_active(None)


def _delta(before, after, key):
    return after[key] - before[key]


# -- grammar ----------------------------------------------------------------

def test_parse_spec_issue_example():
    rules = parse_spec("mem.alloc:retry@skip=3;shuffle.fetch:drop@p=0.1,"
                       "seed=42;io.decode:error@file=*.parquet;"
                       "executor:kill@id=1")
    assert [(r.site, r.action) for r in rules] == [
        ("mem.alloc", "retry"), ("shuffle.fetch", "drop"),
        ("io.decode", "error"), ("executor", "kill")]
    assert rules[0]._skip == 3
    assert rules[1].p == 0.1 and rules[1]._count is None  # p => unbounded
    assert rules[2].file_glob == "*.parquet" and rules[2]._count == 1
    assert rules[3].worker_id == 1


@pytest.mark.parametrize("bad", [
    "mem.free:retry",               # unknown site
    "mem.alloc:explode",            # unknown action
    "mem.alloc:retry@wat=1",        # unknown param
    "mem.alloc:retry@skip",         # param without '='
])
def test_parse_spec_rejects(bad):
    with pytest.raises(ValueError):
        parse_spec(bad)


def test_skip_count_schedule_deterministic():
    reg = FaultRegistry("io.decode:error@skip=2,count=1")
    fired = []
    for _ in range(5):
        try:
            reg.check("io.decode", {})
            fired.append(False)
        except FaultInjectedError:
            fired.append(True)
    assert fired == [False, False, True, False, False]


def test_seeded_probability_deterministic():
    spec = "shuffle.fetch:drop@p=0.3,seed=7"

    def pattern():
        reg = FaultRegistry(spec)
        out = []
        for _ in range(200):
            try:
                reg.check("shuffle.fetch", {})
                out.append(0)
            except TimeoutError:
                out.append(1)
        return out

    a, b = pattern(), pattern()
    assert a == b                      # same seed -> same schedule
    assert 20 < sum(a) < 120           # and it actually fires ~30%


def test_context_matching():
    reg = FaultRegistry("io.decode:error@file=*.parquet,count=10;"
                        "executor:error@id=1,count=10")
    reg.check("io.decode", {"file": "/data/t.csv"})        # glob mismatch
    with pytest.raises(FaultInjectedError):
        reg.check("io.decode", {"file": "/data/t.parquet"})
    reg.check("executor", {"id": 0})                       # id mismatch
    reg.check("executor", {})                              # no id in ctx
    with pytest.raises(FaultInjectedError):
        reg.check("executor", {"id": 1})


# -- thread safety (satellite: the OomInjector.on_alloc race class) ---------

def test_rule_draw_thread_safe():
    reg = FaultRegistry("mem.alloc:error@count=100")
    hits = []
    lock = threading.Lock()

    def worker():
        for _ in range(50):
            try:
                reg.check("mem.alloc", {})
            except FaultInjectedError:
                with lock:
                    hits.append(1)

    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(hits) == 100  # exactly count fires, no lost/double decrements


def test_oom_injector_on_alloc_thread_safe():
    from spark_rapids_tpu.mem.pool import OomInjector, RetryOOM

    inj = OomInjector(kind="RETRY", skip=5, count=3)
    hits = []
    lock = threading.Lock()

    def worker():
        for _ in range(20):
            try:
                inj.on_alloc()
            except RetryOOM:
                with lock:
                    hits.append(1)

    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(hits) == 3


# -- configuration ----------------------------------------------------------

def test_configure_installs_the_conf_schedule():
    conf = RapidsConf({
        "spark.rapids.tpu.test.faults": "mem.alloc:retry@skip=2",
    })
    faults.configure(conf)
    reg = faults.get_registry()
    assert reg is not None and "mem.alloc:retry@skip=2" in reg.spec


def test_install_reuses_registry_while_spec_unchanged():
    faults.install("mem.alloc:retry@skip=1")
    first = faults.get_registry()
    faults.install("mem.alloc:retry@skip=1")
    assert faults.get_registry() is first  # seeded streams keep advancing
    faults.install("mem.alloc:retry@skip=2")
    assert faults.get_registry() is not first
    faults.install("")
    assert faults.get_registry() is None
    faults.check("mem.alloc")  # no registry: pure no-op


# -- shuffle integrity trailer ----------------------------------------------

def test_integrity_roundtrip():
    blob = b"kudo frame bytes" * 9
    sealed = integrity.seal(blob)
    assert len(sealed) == len(blob) + integrity.TRAILER_BYTES
    assert integrity.is_sealed(sealed)
    assert not integrity.is_sealed(blob)
    assert integrity.unseal(sealed) == blob


@pytest.mark.parametrize("pos", [0, 7, -5])
def test_integrity_detects_flip(pos):
    sealed = bytearray(integrity.seal(b"payload" * 23))
    sealed[pos] ^= 0xFF
    with pytest.raises(integrity.BlockCorruption):
        integrity.unseal(bytes(sealed))


def test_integrity_rejects_unsealed():
    with pytest.raises(integrity.BlockCorruption):
        integrity.unseal(b"no trailer here")
    with pytest.raises(integrity.BlockCorruption):
        integrity.unseal(b"x")  # shorter than the trailer


def test_corrupt_hook_flips_one_byte():
    faults.install("shuffle.block:corrupt@count=1,seed=3")
    blob = bytes(range(64))
    out = faults.corrupt("shuffle.block", blob)
    assert out != blob
    assert sum(a != b for a, b in zip(out, blob)) == 1
    assert faults.corrupt("shuffle.block", blob) == blob  # count exhausted


# -- refetch-then-recompute on corrupt blocks -------------------------------

def _write_one_partition(mgr):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import batch_from_arrow
    from spark_rapids_tpu.shuffle.partition import SinglePartitioner

    t = pa.table({"k": pa.array(range(100), pa.int64()),
                  "v": pa.array([i * 0.5 for i in range(100)], pa.float64())})
    schema = T.Schema.from_arrow(t.schema)
    reg = mgr.register(schema, n_reduce=1)
    mgr.write_map_output(reg, SinglePartitioner(), [batch_from_arrow(t)])
    return reg, t


def test_manager_refetch_recovers_corrupt_block():
    from spark_rapids_tpu.shuffle.manager import ShuffleManager

    mgr = ShuffleManager(cache_only=True, integrity=True)
    reg, t = _write_one_partition(mgr)
    before = faults.counters()
    # first read draws the corruption; the refetch re-reads the pristine
    # cached source and the trailer verifies clean
    faults.install("shuffle.block:corrupt@count=1,seed=11")
    out = mgr.read_partition(reg, 0)
    assert out.to_pylist() == t.to_pylist()
    after = faults.counters()
    assert _delta(before, after, "fault_injected_total") == 1
    assert _delta(before, after, "fault_recovered_total") == 1


def test_manager_persistent_corruption_raises():
    from spark_rapids_tpu.shuffle.manager import ShuffleManager

    mgr = ShuffleManager(cache_only=True, integrity=True)
    reg, _ = _write_one_partition(mgr)
    faults.install("shuffle.block:corrupt@p=1.0,seed=11")
    with pytest.raises(integrity.BlockCorruption, match="persistent"):
        mgr.read_partition(reg, 0)


def test_integrity_off_passes_corruption_through():
    from spark_rapids_tpu.shuffle.manager import ShuffleManager

    mgr = ShuffleManager(cache_only=True, integrity=False)
    reg, t = _write_one_partition(mgr)
    out = mgr.read_partition(reg, 0)  # no trailer, plain read still works
    assert out.to_pylist() == t.to_pylist()


# -- with_retry recovery accounting + OOM backoff ---------------------------

def test_with_retry_notes_recovery():
    from spark_rapids_tpu.mem.pool import HbmPool, OomInjector
    from spark_rapids_tpu.mem.retry import with_retry
    from spark_rapids_tpu.mem.spill import SpillableBatch, SpillFramework
    from spark_rapids_tpu.columnar.batch import batch_from_arrow

    t = pa.table({"k": pa.array(range(32), pa.int64())})
    pool = HbmPool(1 << 30)
    fw = SpillFramework(pool, host_limit_bytes=1 << 20, spill_dir="/tmp/x")
    h = SpillableBatch(batch_from_arrow(t), fw)
    pool.set_injector(OomInjector(kind="RETRY", skip=0, count=2))
    before = faults.counters()

    def fn(b):
        pool.allocate(128)
        pool.release(128)
        return int(b.num_rows)

    [got] = list(with_retry([h], fn, framework=fw))
    assert got == 32
    after = faults.counters()
    assert _delta(before, after, "fault_injected_total") == 2
    assert _delta(before, after, "fault_recovered_total") == 1


def test_oom_backoff_paces_retries():
    from spark_rapids_tpu.mem.retry import _oom_backoff

    C.set_active(RapidsConf(
        {"spark.rapids.tpu.memory.retry.backoffMs": 40.0}))
    t0 = time.monotonic()
    _oom_backoff(1)  # scale 1, jitter in [0.5, 1.5) -> sleeps >= 20ms
    assert time.monotonic() - t0 >= 0.015
    C.set_active(RapidsConf())
    t0 = time.monotonic()
    _oom_backoff(1)  # default 0: immediate
    assert time.monotonic() - t0 < 0.015


# -- blacklist classification / CPU degradation -----------------------------

def test_blacklist_classification_sequence():
    from spark_rapids_tpu.mem.pool import RetryOOM

    conf = RapidsConf()  # threshold 3
    dev = FaultInjectedError("io.decode", "injected")
    assert bl.classify("plan-a", dev, conf) == bl.RETRY
    assert bl.classify("plan-a", dev, conf) == bl.RETRY
    assert bl.classify("plan-a", dev, conf) == bl.DEGRADE
    assert bl.is_listed("plan-a", conf)
    assert not bl.is_listed("plan-b", conf)

    # OOMs: bounded retry, never degrade
    oom = RetryOOM("pressure")
    assert bl.classify("plan-b", oom, conf) == bl.RETRY
    assert bl.classify("plan-b", oom, conf) == bl.RETRY
    assert bl.classify("plan-b", oom, conf) == bl.RAISE
    assert not bl.is_listed("plan-b", conf)

    # corruption: transient (a re-run regenerates the data), never degrade
    assert bl.classify("plan-c", integrity.BlockCorruption("crc"),
                       conf) == bl.RETRY

    # anything else is not ours
    assert bl.classify("plan-d", ValueError("nope"), conf) == bl.RAISE

    bl.clear()
    assert not bl.is_listed("plan-a", conf)


def test_blacklist_disabled_always_raises():
    conf = RapidsConf(
        {"spark.rapids.tpu.fault.deviceBlacklist.enabled": False})
    dev = FaultInjectedError("io.decode", "injected")
    for _ in range(5):
        assert bl.classify("plan-x", dev, conf) == bl.RAISE
    assert not bl.is_listed("plan-x", conf)


def test_query_degrades_to_cpu_after_repeated_device_faults(tmp_path):
    from spark_rapids_tpu.plan import read_parquet

    t = pa.table({"k": pa.array([1, 2, 1, 3] * 25, pa.int64()),
                  "v": pa.array(range(100), pa.int64())})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    expected = read_parquet(path).to_arrow()

    conf = RapidsConf({"spark.rapids.tpu.test.faults":
                       "io.decode:error@file=*.parquet,count=100"})
    before = faults.counters()
    out = read_parquet(path, conf=conf).to_arrow()
    assert out.equals(expected)  # completed on the CPU engine
    after = faults.counters()
    assert _delta(before, after, "fault_degraded_total") == 1
    assert _delta(before, after, "fault_injected_total") >= 3  # threshold


def test_query_recovers_from_escaped_device_fault(tmp_path):
    """One injected decode error: the whole-query retry absorbs it (no
    degradation) and the recovered counter ticks."""
    from spark_rapids_tpu.plan import read_parquet

    t = pa.table({"v": pa.array(range(50), pa.int64())})
    path = str(tmp_path / "u.parquet")
    pq.write_table(t, path)
    expected = read_parquet(path).to_arrow()

    conf = RapidsConf({"spark.rapids.tpu.test.faults":
                       "io.decode:error@file=*.parquet,count=1"})
    before = faults.counters()
    out = read_parquet(path, conf=conf).to_arrow()
    assert out.equals(expected)
    after = faults.counters()
    assert _delta(before, after, "fault_recovered_total") >= 1
    assert _delta(before, after, "fault_degraded_total") == 0


# -- counters surface through obs -------------------------------------------

def test_journal_records_fault_lifecycle(tmp_path):
    """Every fault counter tick has a matching journal event: injection,
    recovery, and CPU degradation all leave an auditable trail."""
    from spark_rapids_tpu.obs import events as journal
    from spark_rapids_tpu.plan import read_parquet

    t = pa.table({"v": pa.array(range(60), pa.int64())})
    path = str(tmp_path / "j.parquet")
    pq.write_table(t, path)

    # persistent decode faults -> blacklist -> CPU degradation
    journal.clear()
    conf = RapidsConf({"spark.rapids.tpu.test.faults":
                       "io.decode:error@file=*.parquet,count=100"})
    before = faults.counters()
    read_parquet(path, conf=conf).to_arrow()
    after = faults.counters()
    inj = journal.recent("fault-injected")
    assert len(inj) == _delta(before, after, "fault_injected_total")
    assert all(e["site"] == "io.decode" for e in inj)
    deg = journal.recent("degraded")
    assert len(deg) == _delta(before, after, "fault_degraded_total") == 1
    assert journal.recent("query-retry"), "retry attempts journaled"

    # single transient fault -> whole-query retry absorbs it (forget the
    # first phase's blacklist entry so this plan runs on the device)
    from spark_rapids_tpu.faults import blacklist
    blacklist.clear()
    t2 = pa.table({"w": pa.array(range(40), pa.int64())})
    path2 = str(tmp_path / "j2.parquet")
    pq.write_table(t2, path2)
    journal.clear()
    conf = RapidsConf({"spark.rapids.tpu.test.faults":
                       "io.decode:error@file=*.parquet,count=1"})
    before = faults.counters()
    read_parquet(path2, conf=conf).to_arrow()
    after = faults.counters()
    rec = journal.recent("fault-recovered")
    assert len(rec) == _delta(before, after, "fault_recovered_total") >= 1
    assert all("site" in e for e in rec)
    assert journal.recent("degraded") == []
    journal.clear()


def test_gauges_surface_fault_counters():
    from spark_rapids_tpu.obs import gauges

    faults.install("mem.alloc:error@count=1")
    try:
        faults.check("mem.alloc")
    except FaultInjectedError:
        pass
    snap = gauges.snapshot()
    for k in ("fault_injected_total", "fault_recovered_total",
              "fault_degraded_total"):
        assert k in snap
    assert snap["fault_injected_total"] >= 1


# -- satellite: cache-key static guard --------------------------------------

def test_cache_key_guard_passes_on_tree():
    from tools.lint import cache_keys

    assert cache_keys.run_pass(REPO) == []


def test_cache_key_guard_flags_violation(tmp_path):
    from tools.lint import cache_keys

    bad = tmp_path / "bad_expr.py"
    bad.write_text(
        "class Broken:\n"
        "    def __init__(self):\n"
        "        self._params = (1,)\n"
        "    def cache_key(self):\n"
        "        return (type(self).__name__,)\n")
    violations = []
    cache_keys.check_file(str(bad), violations, REPO)
    assert len(violations) == 1 and "Broken" in violations[0]

    ok = tmp_path / "ok_expr.py"
    ok.write_text(
        "class Fine:\n"
        "    def __init__(self):\n"
        "        self._params = (1,)\n"
        "    def cache_key(self):\n"
        "        return super().cache_key() + self._params\n")
    violations = []
    cache_keys.check_file(str(ok), violations, REPO)
    assert violations == []


# -- chaos lane: tracker differential under a seeded fault schedule ---------

def _chaos_spec():
    s = FAULTS_SEED
    # mem.spill retry fires on the write path (recoverable: state untouched)
    # and agg.repartition retries with backoff; corrupt on mem.spill reads
    # is deliberately NOT here — a corrupted spilled chunk is unrecoverable
    # by design and lives in its dedicated error-path test
    return (f"mem.alloc:retry@p=0.02,seed={s};"
            f"shuffle.block:corrupt@p=0.2,seed={s + 1};"
            f"shuffle.serialize:slow@p=0.05,ms=1,seed={s + 2};"
            f"shuffle.fetch:drop@p=0.1,seed={s + 3};"
            f"mem.spill:retry@op=write,p=0.05,seed={s + 4};"
            f"agg.repartition:retry@p=0.1,seed={s + 5}")


@pytest.fixture(scope="module")
def tpch_tables():
    from spark_rapids_tpu.bench import tpch
    return tpch.tables_for(0.005, seed=3)


@pytest.fixture(scope="module")
def tpcds_tables():
    from spark_rapids_tpu.bench import tpcds
    return tpcds.tables_for(0.002, seed=42)


@chaos
def test_tpch_chaos_differential(tpch_tables):
    from spark_rapids_tpu.bench import tpch

    for q in sorted(tpch.DF_QUERIES):
        def run(spec):
            conf = RapidsConf({"spark.rapids.tpu.test.faults": spec})
            d = tpch.df_tables(tpch_tables, conf, shuffle_partitions=2,
                               partitions=2, batch_rows=512)
            return tpch.DF_QUERIES[q](d).to_arrow()

        on, off = run(_chaos_spec()), run("")
        assert on.equals(off), f"tpch {q}: faults changed results"


@chaos
def test_tpcds_chaos_differential(tpcds_tables):
    from spark_rapids_tpu.bench import tpcds

    for q in sorted(tpcds.QUERIES):
        def run(spec):
            conf = RapidsConf({"spark.rapids.tpu.test.faults": spec})
            return tpcds.build_query(q, tpcds_tables, conf,
                                     shuffle_partitions=2).to_arrow()

        on, off = run(_chaos_spec()), run("")
        assert on.equals(off), f"tpcds {q}: faults changed results"


@chaos
def test_chaos_exercised_and_recovered():
    """Runs after the differentials (pytest preserves definition order):
    the schedule must have actually fired, and at least one hardened path
    must have absorbed an injected fault (the acceptance criterion)."""
    ctr = faults.counters()
    assert ctr["fault_injected_total"] > 0
    assert ctr["fault_recovered_total"] > 0


@chaos
def test_chaos_journal_matches_fault_counters():
    """Chaos acceptance for the journal: a seeded corrupt-block injection
    absorbed by the refetch path leaves matching fault-injected and
    fault-recovered journal events — the counters never tick silently."""
    from spark_rapids_tpu.obs import events as journal
    from spark_rapids_tpu.shuffle.manager import ShuffleManager

    mgr = ShuffleManager(cache_only=True, integrity=True)
    reg, t = _write_one_partition(mgr)
    journal.clear()
    before = faults.counters()
    faults.install(f"shuffle.block:corrupt@count=1,seed={FAULTS_SEED}")
    out = mgr.read_partition(reg, 0)
    faults.install("")
    assert out.to_pylist() == t.to_pylist()
    after = faults.counters()
    inj = journal.recent("fault-injected")
    rec = journal.recent("fault-recovered")
    assert len(inj) == _delta(before, after, "fault_injected_total") == 1
    assert len(rec) == _delta(before, after, "fault_recovered_total") == 1
    assert inj[0]["site"] == "shuffle.block"
    assert rec[0]["site"]
    journal.clear()
