"""Memory & resilience tests: pool accounting, spill cascade, OOM
retry/split-retry with deterministic injection, semaphore.

Mirrors the reference's retry suites (WithRetrySuite,
HashAggregateRetrySuite — which use RmmSpark.forceRetryOOM/
forceSplitAndRetryOOM; SURVEY.md §4 item 1)."""

import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.mem import (
    HbmPool,
    RetryOOM,
    SpillableBatch,
    SpillFramework,
    TaskSemaphore,
    with_retry,
)
from spark_rapids_tpu.mem.pool import OomInjector, SplitAndRetryOOM
from spark_rapids_tpu.mem.retry import split_batch_half


def make_batch(n=100, seed=0, with_strings=True):
    rng = np.random.default_rng(seed)
    cols = {"a": pa.array(rng.integers(0, 1000, n), pa.int64())}
    if with_strings:
        cols["s"] = pa.array([f"row{i}" if i % 7 else None for i in range(n)],
                             pa.string())
    t = pa.table(cols)
    return batch_from_arrow(t, min_bucket=16), T.Schema.from_arrow(t.schema)


def rows_of(batch, schema):
    return batch_to_arrow(batch, schema).to_pylist()


def test_pool_accounting_and_oom():
    pool = HbmPool(1000)
    pool.allocate(600)
    pool.allocate(300)
    assert pool.used == 900
    with pytest.raises(RetryOOM):
        pool.allocate(200)
    pool.release(300)
    pool.allocate(200)
    assert pool.used == 800
    assert pool.max_used == 900
    assert pool.oom_count == 1


def test_spill_cascade_device_host_disk(tmp_path):
    batch, schema = make_batch(200, seed=1)
    nb = batch.nbytes() + 4
    pool = HbmPool(nb * 2 + 64)
    fw = SpillFramework(pool, host_limit_bytes=nb + 16,
                        spill_dir=str(tmp_path))
    h1 = SpillableBatch(batch, fw)
    expected = rows_of(batch, schema)
    b2, _ = make_batch(200, seed=2)
    h2 = SpillableBatch(b2, fw)
    # third registration exceeds device budget -> h1 spills to host
    b3, _ = make_batch(200, seed=3)
    h3 = SpillableBatch(b3, fw)
    assert h1.state == "HOST"
    assert fw.spilled_to_host_count == 1
    # fourth -> h2 spills to host, host budget overflows -> h1 -> disk
    b4, _ = make_batch(200, seed=4)
    h4 = SpillableBatch(b4, fw)
    assert h2.state == "HOST"
    assert h1.state == "DISK"
    assert fw.spilled_to_disk_count == 1
    # materializing h1 spills something else and restores content exactly
    with h1 as back:
        assert rows_of(back, schema) == expected
    assert h1.state == "DEVICE"
    for h in (h1, h2, h3, h4):
        h.close()
    assert pool.used == 0
    assert fw.host_used == 0


def test_retry_oom_injection():
    batch, schema = make_batch(50, seed=5)
    pool = HbmPool(1 << 30)
    fw = SpillFramework(pool, host_limit_bytes=1 << 20, spill_dir="/tmp/x")
    h = SpillableBatch(batch, fw)
    expected = rows_of(batch, schema)

    calls = {"n": 0}

    def fn(b):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RetryOOM("transient")
        return rows_of(b, schema)

    [got] = list(with_retry([h], fn, framework=fw))
    assert got == expected
    assert calls["n"] == 3


def test_split_and_retry():
    batch, schema = make_batch(64, seed=6, with_strings=False)
    pool = HbmPool(1 << 30)
    fw = SpillFramework(pool, host_limit_bytes=1 << 20, spill_dir="/tmp/x")
    h = SpillableBatch(batch, fw)
    expected = rows_of(batch, schema)

    seen = {"first": True}

    def fn(b):
        if seen["first"]:
            seen["first"] = False
            raise SplitAndRetryOOM("too big")
        return rows_of(b, schema)

    got = [r for rs in with_retry([h], fn, framework=fw) for r in rs]
    assert got == expected  # order preserved across the split


def test_split_preserves_strings():
    batch, schema = make_batch(31, seed=7)
    expected = rows_of(batch, schema)
    a, b = split_batch_half(batch)
    assert rows_of(a, schema) + rows_of(b, schema) == expected


def test_pool_injector_drives_retry():
    """End-to-end: injected pool OOM on allocation inside fn, recovered by
    the retry loop (the @inject_oom test pattern, spark_session.py:64)."""
    batch, schema = make_batch(40, seed=8, with_strings=False)
    pool = HbmPool(1 << 30)
    fw = SpillFramework(pool, host_limit_bytes=1 << 20, spill_dir="/tmp/x")
    h = SpillableBatch(batch, fw)
    pool.set_injector(OomInjector(kind="RETRY", skip=1, count=2))
    expected = rows_of(batch, schema)

    def fn(b):
        pool.allocate(128)  # may hit the injector
        pool.release(128)
        return rows_of(b, schema)

    [got] = list(with_retry([h], fn, framework=fw))
    assert got == expected


def test_semaphore_limits_and_priority():
    sem = TaskSemaphore(permits=2)
    order = []
    lock = threading.Lock()

    def task(tid, hold_s):
        with sem.held(tid):
            with lock:
                order.append(tid)
            import time
            time.sleep(hold_s)

    import time
    # two permits: holders that let go together would hand their permits to
    # the next two waiters at once, and those race to `order`; uneven holds
    # keep every grant 20 ms or more from the next
    threads = [threading.Thread(target=task, args=(i, 0.05 + 0.03 * (i % 2)))
               for i in range(6)]
    for i, t in enumerate(threads):
        t.start()
        # the next task arrives only once this one is in the queue (or in):
        # a fixed 10 ms stagger let two arrivals swap on a loaded machine
        give_up = time.monotonic() + 30
        while sem.acquire_count <= i and time.monotonic() < give_up:
            time.sleep(0.001)
    for t in threads:
        t.join()
    assert sorted(order) == list(range(6))
    # arrival order preserved (longest-waiting first)
    assert order == sorted(order)
    assert sem.max_waiters >= 1


def test_spill_roundtrip_wide_decimal(tmp_path):
    """DECIMAL128 (hi, lo) columns survive device->host->disk->device
    spill with both limbs intact."""
    import decimal
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.mem.pool import HbmPool
    from spark_rapids_tpu.mem.spill import SpillFramework, SpillableBatch

    D = decimal.Decimal
    vals = [D("12345678901234567890.123456789012345678"),
            D("-99999999999999999999.999999999999999999"), None]
    t = pa.table({"w": pa.array(vals, pa.decimal128(38, 18)),
                  "i": pa.array([1, 2, 3], pa.int64())})
    b = batch_from_arrow(t)
    nb = b.nbytes()
    # device budget fits ~1.5 batches, host budget ~0 -> registering two
    # more batches pushes the first through HOST to DISK
    fw = SpillFramework(HbmPool(nb + nb // 2), host_limit_bytes=16,
                        spill_dir=str(tmp_path))
    h = SpillableBatch(b, fw)
    extra = [SpillableBatch(batch_from_arrow(t), fw) for _ in range(2)]
    assert h.state == "DISK", h.state
    with h as back:
        schema = T.Schema.from_arrow(t.schema)
        got = batch_to_arrow(back, schema).to_pylist()
        assert [r["w"] for r in got] == vals
    for x in [h] + extra:
        x.close()


def test_memory_cleaner_sweep():
    """MemoryCleaner analog (reference: Plugin.scala:575-590): leaked pool
    bytes, unclosed spill handles and uncleaned shuffles are all reported;
    releasing them clears the report."""
    import pyarrow as pa

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import batch_from_arrow
    from spark_rapids_tpu.mem import cleaner
    from spark_rapids_tpu.mem.pool import HbmPool
    from spark_rapids_tpu.mem.spill import SpillFramework
    from spark_rapids_tpu.shuffle.manager import ShuffleManager
    from spark_rapids_tpu.shuffle.partition import HashPartitioner

    base = cleaner.sweep()

    pool = HbmPool(1 << 20)
    pool.allocate(4096)
    fw = SpillFramework(pool)
    b = batch_from_arrow(pa.table({"x": pa.array([1, 2, 3], pa.int64())}), 16)
    h = fw.track(b) if hasattr(fw, "track") else None
    mgr = ShuffleManager(local_dir="/tmp/srtpu_cleaner_test")
    schema = T.Schema.from_arrow(pa.schema([("x", pa.int64())]))
    reg = mgr.register(schema, 2)
    mgr.write_map_output(reg, HashPartitioner([0], 2), [b])

    leaks = [l for l in cleaner.sweep() if l not in base]
    assert any("HbmPool" in l for l in leaks), leaks
    assert any("ShuffleManager" in l for l in leaks), leaks

    pool.release(4096)
    if h is not None:
        h.close()
    mgr.cleanup(reg)
    leaks2 = [l for l in cleaner.sweep() if l not in base]
    assert not any("srtpu_cleaner_test" in l for l in leaks2)
    assert not any("HbmPool: 4096" in l for l in leaks2), leaks2


@pytest.mark.parametrize("platform,stats,want", [
    ("tpu", {"bytes_limit": 123 << 20}, (123 << 20, "memory_stats.bytes_limit")),
    ("cpu", None, (16 << 30, "default-16GiB(cpu)")),
    ("tpu", None, TypeError),   # a TPU that gives no size: raise, never guess
    ("tpu", {}, KeyError),
])
def test_pool_size_asks_the_tpu_or_raises(monkeypatch, platform, stats, want):
    """On a TPU the pool is sized from what the device reports, or not at
    all; the 16 GiB stand-in is for backends that report no limit."""
    import types

    import jax

    from spark_rapids_tpu.mem import pool as P

    dev = types.SimpleNamespace(platform=platform,
                                memory_stats=lambda: stats)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    if isinstance(want, tuple):
        assert P._detect_hbm_bytes() == want
    else:
        with pytest.raises(want):
            P._detect_hbm_bytes()
