"""Concurrent-query serving runtime suite (docs/serving.md).

Fast-lane sections: concurrent-vs-serial bit-identity through the
QueryServer (mixed same/distinct queries), single-flight dedup, typed
cancellation/deadline unwind with pool-balance and no poisoning of
subsequent queries, admission shedding (queue depth, memory reservations,
injected faults), per-query pool budgets (QueryBudgetExceeded), the
reworked TaskSemaphore (timeout/cancel-aware acquire, waiter removal,
priority + anti-starvation ordering), the get_task_semaphore conf re-read
regression, and concurrency-correct memtrack attribution/audit scoping.

Chaos lane (``SRTPU_CHAOS_LANE=1``, tests/run_chaos_lane.sh): N client
threads submit mixed queries through the server under a seeded fault
schedule that includes the new ``serve.admit``/``serve.cancel`` sites;
shed submissions are retried and every result must be bit-identical to
the fault-free serial run.
"""

import os
import threading
import time

import pyarrow as pa
import pytest

from spark_rapids_tpu import faults
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.faults import blacklist as bl
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.mem import semaphore as sem_mod
from spark_rapids_tpu.mem.pool import (
    HbmPool, QueryBudgetExceeded, RetryOOM, get_pool,
)
from spark_rapids_tpu.mem.semaphore import TaskSemaphore, get_task_semaphore
from spark_rapids_tpu.obs import memtrack as mt
from spark_rapids_tpu.plan.dataframe import from_arrow
from spark_rapids_tpu.serve import (
    AdmissionController, AdmissionRejected, QueryCancelled, QueryContext,
    QueryDeadlineExceeded, QueryServer,
)

CHAOS_LANE = os.environ.get("SRTPU_CHAOS_LANE") == "1"
FAULTS_SEED = int(os.environ.get("SRTPU_FAULTS_SEED", "42"))

chaos = pytest.mark.skipif(
    not CHAOS_LANE, reason="chaos lane; run tests/run_chaos_lane.sh")


@pytest.fixture(autouse=True)
def _clean_serve():
    faults.reset()
    bl.clear()
    mt.reset()
    yield
    faults.reset()
    bl.clear()
    mt.reset()
    C.set_active(None)


def _table(n=2000, seed=0):
    return pa.table({"a": [(i * 7 + seed) % 911 for i in range(n)],
                     "b": [float((i + seed) % 97) for i in range(n)]})


def _queries(conf, n=4):
    """Distinct small tracker queries over one in-memory table."""
    t = _table()
    out = []
    for k in range(n):
        out.append(from_arrow(t, conf, partitions=2)
                   .filter(E.col("a") > E.lit(k * 3))
                   .group_by("b")
                   .agg(E.Alias(E.Sum(E.col("a")), "s"))
                   .sort("b"))
    return out


# -- concurrent differential ------------------------------------------------


def test_concurrent_mixed_queries_bit_identical():
    """N submissions of mixed same/distinct queries through the server
    return exactly the serial engine's bytes."""
    conf = C.RapidsConf()
    dfs = _queries(conf)
    expected = [d.to_arrow() for d in dfs]
    srv = QueryServer(conf)
    try:
        tickets = [srv.submit(dfs[i % len(dfs)], name=f"mix{i}")
                   for i in range(12)]
        for i, tk in enumerate(tickets):
            assert tk.result(timeout_s=120).equals(expected[i % len(dfs)])
    finally:
        srv.close()
    assert get_pool().used == 0


def test_singleflight_dedup_shares_one_execution():
    """An identical submission while the primary is still in flight gets a
    follower ticket resolved from the primary's result."""
    conf = C.RapidsConf()
    blocker, q, *_ = _queries(conf)
    expected = q.to_arrow()
    # the blocker's first cancellation poll sleeps, pinning the single
    # worker while the two identical submissions land
    faults.install("serve.cancel:slow@op=blocker,ms=400,count=1")
    srv = QueryServer(conf, max_concurrent=1)
    try:
        b0 = srv.snapshot()["counters"]["sched_singleflight_hit_total"]
        tk_b = srv.submit(blocker, name="blocker")
        t1 = srv.submit(q, name="dup")
        t2 = srv.submit(q, name="dup")
        assert t1.result(120).equals(expected)
        assert t2.result(120).equals(expected)
        tk_b.result(120)
        hits = (srv.snapshot()["counters"]["sched_singleflight_hit_total"]
                - b0)
        assert hits >= 1
    finally:
        srv.close()


def test_singleflight_disabled_by_conf():
    conf = C.RapidsConf({C.SERVE_SINGLEFLIGHT.key: False})
    srv = QueryServer(conf)
    try:
        assert srv._singleflight is False
        [df] = _queries(conf, n=1)
        tk = srv.submit(df)
        assert tk.key is None
        tk.result(timeout_s=120)
    finally:
        srv.close()


# -- cancellation / deadline ------------------------------------------------


def test_cancel_queued_query_is_typed_and_does_not_poison():
    conf = C.RapidsConf()
    blocker, q, q2, *_ = _queries(conf)
    faults.install("serve.cancel:slow@op=blocker,ms=400,count=1")
    srv = QueryServer(conf, max_concurrent=1)
    try:
        srv.submit(blocker, name="blocker")
        tk = srv.submit(q, name="victim")
        tk.cancel()
        with pytest.raises(QueryCancelled):
            tk.result(timeout_s=120)
        # a subsequent query on the same server is unaffected
        assert srv.submit(q2, name="after").result(120).equals(q2.to_arrow())
        assert srv.snapshot()["counters"]["sched_cancelled_total"] >= 1
    finally:
        srv.close()
    assert get_pool().used == 0


def test_deadline_is_typed_bounded_and_releases_pool():
    conf = C.RapidsConf()
    _, q, q2, *_ = _queries(conf)
    srv = QueryServer(conf)
    try:
        t0 = time.monotonic()
        tk = srv.submit(q, deadline_ms=0.01, name="deadline")
        with pytest.raises(QueryDeadlineExceeded):
            tk.result(timeout_s=120)
        assert time.monotonic() - t0 < 30  # bounded grace, not a hang
        assert get_pool().used == 0
        # next query unpoisoned
        assert srv.submit(q2, name="after").result(120).equals(q2.to_arrow())
    finally:
        srv.close()


def test_close_cancels_pending_typed():
    conf = C.RapidsConf()
    blocker, q, *_ = _queries(conf)
    faults.install("serve.cancel:slow@op=blocker,ms=400,count=1")
    srv = QueryServer(conf, max_concurrent=1)
    srv.submit(blocker, name="blocker")
    tk = srv.submit(q, name="pending")
    srv.close(cancel_pending=True)
    with pytest.raises(QueryCancelled):
        tk.result(timeout_s=30)
    with pytest.raises(AdmissionRejected) as ei:
        srv.submit(q)
    assert ei.value.reason == "shutdown"


# -- Ticket.add_done_callback ------------------------------------------------


def _bare_ticket():
    from spark_rapids_tpu.serve.server import Ticket
    return Ticket(None, QueryContext(name="bare"), None)


@pytest.mark.parametrize("how", ["fulfill", "fail"])
def test_done_callback_runs_once_after_done_ns_is_set(how):
    """The callback sees a ticket that is done, stamped and readable, and
    runs exactly once however often the ticket is looked at later."""
    tk = _bare_ticket()
    seen = []
    tk.add_done_callback(
        lambda: seen.append((tk.done(), tk.done_ns, time.perf_counter_ns())))
    assert seen == [] and tk.done_ns is None
    if how == "fulfill":
        tk._fulfill(pa.table({"x": [1]}))
        assert tk.result(1).num_rows == 1
    else:
        tk._fail(QueryCancelled("bare cancelled"))
        with pytest.raises(QueryCancelled):
            tk.result(1)
    assert len(seen) == 1
    done, done_ns, at_ns = seen[0]
    assert done and done_ns is not None and done_ns <= at_ns
    assert done_ns == tk.done_ns


def test_done_callback_on_a_done_ticket_runs_at_once():
    tk = _bare_ticket()
    tk._fulfill(pa.table({"x": [1]}))
    seen = []
    tk.add_done_callback(lambda: seen.append(threading.get_ident()))
    assert seen == [threading.get_ident()]


def test_done_callback_that_raises_does_not_lose_the_result(caplog):
    tk = _bare_ticket()
    after = []

    def broken():
        raise RuntimeError("waiter's bug")

    tk.add_done_callback(broken)
    tk.add_done_callback(lambda: after.append(1))
    tk._fulfill(pa.table({"x": [1, 2]}))
    assert tk.result(1).num_rows == 2
    assert after == [1], "a broken callback starved the one behind it"
    assert "waiter's bug" in caplog.text
    tk.add_done_callback(broken)  # at once on a done ticket: still contained
    assert tk.result(1).num_rows == 2


def test_done_callback_racing_the_resolution_runs_exactly_once():
    """add_done_callback against _fulfill from another thread, many times
    under a short switch interval: never lost, never twice."""
    import sys
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        deadline = time.monotonic() + 20
        for i in range(400):
            assert time.monotonic() < deadline
            tk = _bare_ticket()
            hits = []
            go = threading.Barrier(3)

            def resolve():
                go.wait(5)
                tk._fulfill(i)

            def add():
                go.wait(5)
                tk.add_done_callback(lambda: hits.append(1))

            threads = [threading.Thread(target=resolve),
                       threading.Thread(target=add)]
            for th in threads:
                th.start()
            go.wait(5)
            for th in threads:
                th.join(5)
                assert not th.is_alive()
            assert hits == [1], f"round {i}: callback ran {len(hits)} times"
    finally:
        sys.setswitchinterval(old)


def test_follower_ticket_forwards_its_callback_to_the_primary():
    from spark_rapids_tpu.serve.server import _FollowerTicket
    primary = _bare_ticket()
    follower = _FollowerTicket(primary, QueryContext(name="follower"))
    seen = []
    follower.add_done_callback(lambda: seen.append(follower.done()))
    assert seen == []
    primary._fulfill(pa.table({"x": [1]}))
    assert seen == [True]
    assert follower.result(1).num_rows == 1


def test_ticket_resolves_after_the_reservation_is_released():
    """A waiter woken by the ticket submits its next query at once: by then
    the finished query's reservation and active slot are gone, so a budget
    that fits the server only once is never shed against itself."""
    conf = C.RapidsConf({C.SERVE_SINGLEFLIGHT.key: False})
    srv = QueryServer(conf, max_concurrent=1)
    try:
        budget = srv.admission.reservable_bytes * 3 // 4
        seen = []
        for i in range(20):
            tk = srv.submit(_RecordingDF(i, []), name=f"loop-{i}",
                            memory_budget=budget)
            tk.add_done_callback(lambda: seen.append(
                srv.admission.snapshot()["reserved_bytes"]))
            tk.result(30)  # returns the moment the event is set
        # callbacks run on the resolving thread before it moves on; the
        # last one may still be running when result() returns
        deadline = time.monotonic() + 5
        while len(seen) < 20 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert seen == [0] * 20
    finally:
        srv.close()


# -- admission --------------------------------------------------------------


def test_queue_full_sheds_typed():
    conf = C.RapidsConf()
    dfs = _queries(conf)
    faults.install("serve.cancel:slow@op=blocker,ms=500,count=1")
    srv = QueryServer(conf, max_concurrent=1, max_queue=1)
    try:
        srv.submit(dfs[0], name="blocker")
        time.sleep(0.1)  # let the worker dequeue the blocker
        srv.submit(dfs[1], name="queued")
        with pytest.raises(AdmissionRejected) as ei:
            srv.submit(dfs[2], name="overflow")
        assert ei.value.reason == "queue-full"
    finally:
        srv.close()


def test_memory_reservation_sheds_typed():
    adm = AdmissionController(max_queue=10, reservable_bytes=1000)
    c1 = QueryContext(name="a", memory_budget=600)
    adm.admit(c1)
    with pytest.raises(AdmissionRejected) as ei:
        adm.admit(QueryContext(name="b", memory_budget=600))
    assert ei.value.reason == "memory"
    # release frees the reservation
    adm.release(c1, still_queued=True)
    adm.admit(QueryContext(name="c", memory_budget=600))


def test_admit_fault_site_sheds_typed():
    conf = C.RapidsConf()
    [df] = _queries(conf, n=1)
    faults.install("serve.admit:error@count=1")
    srv = QueryServer(conf)
    try:
        with pytest.raises(AdmissionRejected) as ei:
            srv.submit(df)
        assert ei.value.reason == "fault-injected"
        # the schedule is exhausted: next submission admits and completes
        assert srv.submit(df).result(120).equals(df.to_arrow())
    finally:
        srv.close()


def test_query_budget_exceeded_is_typed_not_retryable():
    """An over-budget allocation raises QueryBudgetExceeded (attributed,
    NOT a RetryOOM — spilling cannot shrink the query's own footprint)."""
    pool = HbmPool(1 << 20)
    pool.set_query_budget(77, 1000)
    mt.begin_query(77)
    try:
        tag = pool.allocate(800)
        with pytest.raises(QueryBudgetExceeded) as ei:
            pool.allocate(800)
        assert not isinstance(ei.value, RetryOOM)
        assert "77" in str(ei.value)
        # under budget still fine; other queries are uncapped
        tag2 = pool.allocate(100)
        pool.release(800, tag=tag)
        pool.release(100, tag=tag2)
    finally:
        mt.end_query(77)
        pool.clear_query_budget(77)


# -- TaskSemaphore rework ---------------------------------------------------


def test_semaphore_timeout_removes_waiter():
    sem = TaskSemaphore(permits=1)
    assert sem.acquire("holder")
    t0 = time.monotonic()
    assert sem.acquire("late", timeout_ms=80) is False
    assert time.monotonic() - t0 < 10
    snap = sem.snapshot()
    assert snap["timeout_count"] == 1
    assert snap["waiters"] == {}          # abandoned waiter removed
    assert "late" not in snap["holders"]
    sem.release("holder")
    # a timed-out task can come back and acquire normally
    assert sem.acquire("late", timeout_ms=80) is True
    sem.release("late")


def test_semaphore_cancel_check_raises_and_removes_waiter():
    sem = TaskSemaphore(permits=1)
    assert sem.acquire("holder")

    def boom():
        raise QueryCancelled("cancelled mid-wait")

    with pytest.raises(QueryCancelled):
        sem.acquire("victim", cancel_check=boom)
    snap = sem.snapshot()
    assert snap["cancel_count"] == 1
    assert snap["waiters"] == {}
    sem.release("holder")


def test_semaphore_priority_order_with_fifo_tiebreak():
    sem = TaskSemaphore(permits=1)
    assert sem.acquire("holder")
    order = []
    started = threading.Barrier(3)

    def waiter(tid, prio):
        started.wait()
        # stagger so "low" registers first (FIFO would pick it)
        if prio:
            time.sleep(0.1)
        sem.acquire(tid, priority=prio)
        order.append(tid)
        time.sleep(0.05)
        sem.release(tid)

    ts = [threading.Thread(target=waiter, args=("low", 0)),
          threading.Thread(target=waiter, args=("high", 5))]
    for t in ts:
        t.start()
    started.wait()
    time.sleep(0.3)  # both registered as waiters
    assert len(sem.snapshot()["waiters"]) == 2
    sem.release("holder")
    for t in ts:
        t.join()
    assert order == ["high", "low"]


def test_semaphore_starvation_aging_beats_priority():
    sem = TaskSemaphore(permits=1, starvation_ns=50_000_000)  # 50ms
    assert sem.acquire("holder")
    order = []

    def waiter(tid, prio, delay):
        time.sleep(delay)
        sem.acquire(tid, priority=prio)
        order.append(tid)
        time.sleep(0.02)
        sem.release(tid)

    ts = [threading.Thread(target=waiter, args=("old-low", 0, 0.0)),
          threading.Thread(target=waiter, args=("new-high", 9, 0.1))]
    for t in ts:
        t.start()
    time.sleep(0.3)  # old-low has aged past starvation_ns
    sem.release("holder")
    for t in ts:
        t.join()
    assert order[0] == "old-low"


def test_get_task_semaphore_rereads_conf(monkeypatch):
    """Regression: the process semaphore used to freeze its permit count
    at first use; it must now follow concurrentTpuTasks on conf change."""
    monkeypatch.setattr(sem_mod, "_process_sem", None)
    C.set_active(C.RapidsConf({C.CONCURRENT_TASKS.key: 2}))
    s1 = get_task_semaphore()
    assert s1.snapshot()["permits"] == 2
    C.set_active(C.RapidsConf({C.CONCURRENT_TASKS.key: 5}))
    s2 = get_task_semaphore()
    assert s2 is s1                       # resized in place, not replaced
    assert s2.snapshot()["permits"] == 5


# -- concurrency-correct attribution ---------------------------------------


def test_memtrack_thread_scoped_attribution_and_audit():
    """Two queries on two threads attribute to their own ids, and the
    strict leak audit for the finishing query ignores the other query's
    still-live allocations."""
    pool = HbmPool(1 << 20)
    errs = []
    a_allocated = threading.Event()
    b_done = threading.Event()

    def qa():
        try:
            mt.begin_query(101)
            try:
                tag = pool.allocate(4096)
                assert tag[0] == 101, tag
                a_allocated.set()
                # hold the allocation live across B's whole lifecycle
                assert b_done.wait(30)
                pool.release(4096, tag=tag)
                mt.audit_query(101, strict=True)  # clean after release
            finally:
                mt.end_query(101)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)
            a_allocated.set()

    def qb():
        try:
            assert a_allocated.wait(30)
            mt.begin_query(202)
            try:
                tag = pool.allocate(1024)
                assert tag[0] == 202, tag
                pool.release(1024, tag=tag)
                # strict audit of B must NOT trip over A's live 4096 bytes
                report = mt.audit_query(202, strict=True)
                assert report["leaked_bytes"] == 0
            finally:
                mt.end_query(202)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)
        finally:
            b_done.set()

    ta, tb = threading.Thread(target=qa), threading.Thread(target=qb)
    ta.start(); tb.start()
    ta.join(); tb.join()
    assert not errs, errs
    assert pool.used == 0


def test_memtrack_single_query_fallback_for_worker_threads():
    """With exactly one active query, a worker thread with no thread-local
    id still inherits it (the pre-serving behavior PrefetchIterator's
    consumer-built tags rely on)."""
    mt.begin_query(55)
    got = {}

    def worker():
        got["qid"] = mt.current_query()

    t = threading.Thread(target=worker)
    t.start(); t.join()
    assert got["qid"] == 55
    mt.end_query(55)
    assert mt.current_query() is None


# -- chaos lane -------------------------------------------------------------


@chaos
def test_chaos_concurrent_serving_bit_identical():
    """Seeded faults at serve.admit/serve.cancel plus mem.alloc while N
    threads submit mixed queries: sheds are retried, slow polls ride
    through, and every result is bit-identical to the fault-free run."""
    conf = C.RapidsConf()
    dfs = _queries(conf)
    expected = [d.to_arrow() for d in dfs]
    faults.install(
        f"serve.admit:error@p=0.2,seed={FAULTS_SEED};"
        f"serve.cancel:slow@p=0.05,seed={FAULTS_SEED + 1},ms=10;"
        f"mem.alloc:retry@p=0.02,seed={FAULTS_SEED + 2}")
    srv = QueryServer(conf)
    errs = []

    def client(ci):
        try:
            for i in range(4):
                k = (ci + i) % len(dfs)
                for _attempt in range(8):
                    try:
                        tk = srv.submit(dfs[k], name=f"c{ci}#{i}")
                    except AdmissionRejected:
                        time.sleep(0.01)
                        continue
                    assert tk.result(timeout_s=180).equals(expected[k])
                    break
                else:
                    raise AssertionError("shed 8 times in a row")
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    try:
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        srv.close()
    assert not errs, errs
    assert get_pool().used == 0


# -- per-tenant SLO metrics + submit tracing --------------------------------


def test_tenant_slo_histograms_and_outcomes():
    """Completed queries land queue-wait/deadline-slack observations and
    outcome counts keyed by (tenant, priority); tenant_slos() merges them
    into one percentile view."""
    from spark_rapids_tpu.obs import histo
    from spark_rapids_tpu.serve import metrics as sm

    histo.reset_all()
    sm.reset_tenants()
    conf = C.RapidsConf()
    dfs = _queries(conf, n=3)
    srv = QueryServer(conf)
    try:
        tks = [srv.submit(dfs[i % 2], name=f"slo{i}", tenant="acme",
                          priority=1, deadline_ms=600_000)
               for i in range(2)]
        # a DISTINCT query (identical ones would singleflight-dedup onto
        # the in-flight acme submission and never reach "completed")
        tk_def = srv.submit(dfs[2], name="slo-default")
        for tk in tks + [tk_def]:
            tk.result(timeout_s=120)
    finally:
        srv.close()
    outcomes = sm.tenant_outcomes()
    assert outcomes[("acme", 1)]["admitted"] == 2
    assert outcomes[("acme", 1)]["completed"] == 2
    # a tenant-less submit folds into the "default" tenant
    assert outcomes[(sm.DEFAULT_TENANT, 0)]["completed"] >= 1
    slos = sm.tenant_slos()
    acme = slos[("acme", 1)]
    qw = acme["queue_wait_ms"]
    assert qw["count"] == 2
    assert 0 <= qw["p50"] <= qw["p95"] <= qw["p99"]
    # deadline was set: slack histogram observed for both completions
    assert acme["deadline_slack_ms"]["count"] == 2
    histo.reset_all()
    sm.reset_tenants()


def test_tenant_slo_rejection_outcomes_and_overflow_fold():
    from spark_rapids_tpu.obs import histo
    from spark_rapids_tpu.serve import metrics as sm

    histo.reset_all()
    sm.reset_tenants()
    sm.configure_slo(True, max_tenants=2)
    try:
        for t in ("t0", "t1", "t2", "t3"):
            sm.note_outcome(t, 0, "admitted")
        oc = sm.tenant_outcomes()
        assert oc[("t0", 0)]["admitted"] == 1
        assert oc[("t1", 0)]["admitted"] == 1
        # past the cap, unknown tenants fold into the overflow bucket
        # instead of growing the label space unbounded
        assert oc[(sm.OVERFLOW_TENANT, 0)]["admitted"] == 2
        assert ("t2", 0) not in oc and ("t3", 0) not in oc
    finally:
        sm.configure_slo(True, max_tenants=64)
        sm.reset_tenants()

    # a real queue-full shed is counted as a typed rejection outcome
    conf = C.RapidsConf()
    blocker, q, q2, *_ = _queries(conf)
    faults.install("serve.cancel:slow@op=blk,ms=300,count=1")
    srv = QueryServer(conf, max_concurrent=1, max_queue=1)
    try:
        tk_b = srv.submit(blocker, name="blk", tenant="shed-t")
        # wait for the worker to move the blocker from the queue into the
        # running slot, else q1 (not q2) eats the queue-full rejection
        deadline = time.monotonic() + 30
        while srv.admission._queued and time.monotonic() < deadline:
            time.sleep(0.005)
        tk_q = srv.submit(q, name="q1", tenant="shed-t")
        with pytest.raises(AdmissionRejected):
            srv.submit(q2, name="q2", tenant="shed-t")
        tk_b.result(120)
        tk_q.result(120)
    finally:
        srv.close()
    oc = sm.tenant_outcomes()[("shed-t", 0)]
    assert oc["rejected:queue-full"] == 1
    assert oc["admitted"] == 2
    sm.reset_tenants()
    histo.reset_all()


def test_tenant_slo_disabled_by_conf():
    from spark_rapids_tpu.obs import histo
    from spark_rapids_tpu.serve import metrics as sm

    histo.reset_all()
    sm.reset_tenants()
    conf = C.RapidsConf({C.SERVE_SLO_ENABLED.key: False})
    srv = QueryServer(conf)
    try:
        [df] = _queries(conf, n=1)
        srv.submit(df, tenant="ghost").result(timeout_s=120)
    finally:
        srv.close()
        # restore the default for later servers in this process
        sm.configure_slo(True, 64)
    assert ("ghost", 0) not in sm.tenant_outcomes()
    sm.reset_tenants()
    histo.reset_all()


def test_submit_records_query_lifecycle_spans():
    """One submission produces submit/admit/queue-wait/execute spans that
    share the Ticket's trace id — the serving half of the distributed
    timeline."""
    from spark_rapids_tpu.obs import span as sp
    from spark_rapids_tpu.utils import tracing

    conf = C.RapidsConf()
    [df] = _queries(conf, n=1)
    srv = QueryServer(conf)
    tracing.set_capture(True, clear=True)
    try:
        tk = srv.submit(df, name="traced", tenant="acme")
        tk.result(timeout_s=120)
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
        srv.close()
    traces = sp.assemble_traces({"driver": events})
    assert traces, "no span events captured"
    # find the trace that carries the submit span for THIS query
    mine = [spans for spans in traces.values()
            if any(s["name"] == "query:submit"
                   and s["attrs"].get("query") == "traced" for s in spans)]
    assert len(mine) == 1
    names = {s["name"] for s in mine[0]}
    assert {"query:submit", "query:admit", "query:queue-wait",
            "query:execute"} <= names
    execute = [s for s in mine[0] if s["name"] == "query:execute"][0]
    assert execute["attrs"]["tenant"] == "acme"


def _traced_submit(srv, df, name):
    """Submit with capture on; (the query's spans, the raw events)."""
    from spark_rapids_tpu.obs import span as sp
    from spark_rapids_tpu.utils import tracing

    tracing.set_capture(True, clear=True)
    try:
        srv.submit(df, name=name).result(timeout_s=120)
        events = tracing.trace_events(clear=True)
    finally:
        tracing.set_capture(False)
        tracing.trace_events(clear=True)
    traces = sp.assemble_traces({"driver": events})
    mine = [spans for spans in traces.values()
            if any(s["name"] == "query:submit"
                   and s["attrs"].get("query") == name for s in spans)]
    assert len(mine) == 1
    return mine[0], events


def _inside(child, parent):
    return (child["start_ns"] >= parent["start_ns"]
            and child["start_ns"] + child["dur_ns"]
            <= parent["start_ns"] + parent["dur_ns"])


def test_query_plan_is_a_real_interval_before_the_first_operator():
    """query:plan is opened around physical_plan() on the executor thread:
    it starts and ends inside query:execute, carries the request's name and
    whether the plan memo answered, and is over before any operator runs."""
    conf = C.RapidsConf()
    t = _table()
    df = (from_arrow(t, conf, partitions=2)
          .filter(E.col("a") > E.lit(4243)).group_by("b")
          .agg(E.Alias(E.Sum(E.col("a")), "s")).sort("b"))
    srv = QueryServer(conf)
    try:
        first, _ = _traced_submit(srv, df, "plan-cold")
        spans, events = _traced_submit(srv, df, "plan-warm")
    finally:
        srv.close()
    [plan] = [s for s in spans if s["name"] == "query:plan"]
    [execute] = [s for s in spans if s["name"] == "query:execute"]
    assert _inside(plan, execute) and plan["dur_ns"] > 0
    assert plan["parent_id"] == execute["span_id"]
    assert plan["attrs"] == {"query": "plan-warm", "cache_hit": True}
    [cold] = [s for s in first if s["name"] == "query:plan"]
    assert cold["attrs"]["cache_hit"] is False
    ops = [e for e in events if e["name"].endswith("Exec")
           and e["start_ns"] >= execute["start_ns"]]
    assert ops, "no operator event captured"
    assert plan["start_ns"] + plan["dur_ns"] <= min(
        e["start_ns"] for e in ops)


def test_query_compile_span_only_where_a_program_was_first_called():
    """The first run of a plan with a literal nothing has bound before
    records query:compile spans, each a real interval inside query:execute
    naming its program; the second run compiles nothing and records none."""
    conf = C.RapidsConf()
    t = _table()
    df = (from_arrow(t, conf, partitions=2)
          .filter(E.col("a") > E.lit(77123)).group_by("b")
          .agg(E.Alias(E.Sum(E.col("a")), "s")).sort("b"))
    srv = QueryServer(conf)
    try:
        cold, _ = _traced_submit(srv, df, "compile-cold")
        warm, _ = _traced_submit(srv, df, "compile-warm")
    finally:
        srv.close()
    [execute] = [s for s in cold if s["name"] == "query:execute"]
    compiles = [s for s in cold if s["name"] == "query:compile"]
    assert compiles, "a new program was bound but no query:compile recorded"
    for c in compiles:
        assert _inside(c, execute) and c["dur_ns"] > 0
        assert c["attrs"]["program"]
    assert [s for s in warm if s["name"] == "query:compile"] == []


def test_readback_and_finish_tile_the_tail_of_execute():
    """After the last operator event: query:readback (with its
    exec:host-sync child) per result batch, then query:finish, both inside
    query:execute and in that order."""
    conf = C.RapidsConf()
    [df] = _queries(conf, n=1)
    srv = QueryServer(conf)
    try:
        _traced_submit(srv, df, "tail-warm")
        spans, _ = _traced_submit(srv, df, "tail")
    finally:
        srv.close()
    [execute] = [s for s in spans if s["name"] == "query:execute"]
    [finish] = [s for s in spans if s["name"] == "query:finish"]
    reads = [s for s in spans if s["name"] == "query:readback"]
    assert reads and all(_inside(r, execute) for r in reads)
    assert _inside(finish, execute)
    assert max(r["start_ns"] + r["dur_ns"] for r in reads) \
        <= finish["start_ns"]
    syncs = [s for s in spans if s["name"] == "exec:host-sync"
             and s["attrs"]["site"] == "batch_to_arrow"]
    assert len(syncs) == len(reads)
    assert {s["parent_id"] for s in syncs} == {r["span_id"] for r in reads}
    assert sum(r["attrs"]["rows"] for r in reads) > 0

# -- deadline-aware (EDF) scheduling + fair-share admission -----------------


class _RecordingDF:
    """Minimal df stand-in: records its label when executed. Only usable
    with single-flight disabled (no plan to fingerprint)."""

    def __init__(self, label, order, gate=None):
        self.label = label
        self._order = order
        self._gate = gate
        self.conf = None
        self.shuffle_partitions = 1

    def to_arrow(self):
        if self._gate is not None:
            self._gate.wait(30)
        self._order.append(self.label)
        return pa.table({"x": [1]})


def _edf_server(conf_items):
    conf = C.RapidsConf(dict({C.SERVE_SINGLEFLIGHT.key: False}, **conf_items))
    return QueryServer(conf, max_concurrent=1)


def _run_ordered(srv, specs):
    """Hold the one worker with a gated blocker, enqueue ``specs`` =
    [(label, deadline_ms)], release, return execution order."""
    order = []
    gate = threading.Event()
    blocker = srv.submit(_RecordingDF("blocker", order, gate), name="blk")
    deadline = time.monotonic() + 30
    while srv.admission._queued and time.monotonic() < deadline:
        time.sleep(0.005)
    tickets = [srv.submit(_RecordingDF(label, order), name=label,
                          deadline_ms=dl)
               for label, dl in specs]
    gate.set()
    blocker.result(timeout_s=60)
    for tk in tickets:
        tk.result(timeout_s=60)
    return order


def test_edf_orders_by_deadline_within_priority():
    """With EDF on (default), queued same-priority queries run earliest-
    deadline first; no-deadline queries run after every dated one."""
    srv = _edf_server({})
    try:
        order = _run_ordered(srv, [("nodl", None), ("late", 120_000),
                                   ("soon", 20_000)])
    finally:
        srv.close()
    assert order == ["blocker", "soon", "late", "nodl"]


def test_edf_disabled_falls_back_to_fifo():
    srv = _edf_server({C.SERVE_EDF_ENABLED.key: False})
    try:
        order = _run_ordered(srv, [("late", 120_000), ("soon", 20_000),
                                   ("nodl", None)])
    finally:
        srv.close()
    # pure submission order: deadlines are ignored for ordering
    assert order == ["blocker", "late", "soon", "nodl"]


def test_priority_still_dominates_deadline():
    """EDF only breaks ties WITHIN a priority band: a high-priority query
    with a far deadline still beats a low-priority one due sooner."""
    srv = _edf_server({})
    try:
        order = []
        gate = threading.Event()
        blocker = srv.submit(_RecordingDF("blocker", order, gate))
        deadline = time.monotonic() + 30
        while srv.admission._queued and time.monotonic() < deadline:
            time.sleep(0.005)
        t1 = srv.submit(_RecordingDF("lo-soon", order), priority=0,
                        deadline_ms=20_000)
        t2 = srv.submit(_RecordingDF("hi-late", order), priority=5,
                        deadline_ms=120_000)
        gate.set()
        for tk in (blocker, t1, t2):
            tk.result(timeout_s=60)
    finally:
        srv.close()
    assert order == ["blocker", "hi-late", "lo-soon"]


def test_fairshare_quota_parse_and_math():
    from spark_rapids_tpu.serve.admission import parse_weights

    assert parse_weights("") == {}
    assert parse_weights("a=2, b=1") == {"a": 2.0, "b": 1.0}
    with pytest.raises(ValueError):
        parse_weights("a")
    with pytest.raises(ValueError):
        parse_weights("a=0")  # non-positive weight

    ac = AdmissionController(max_queue=8, reservable_bytes=1 << 30)
    ac.configure_fairshare(True, {"a": 3.0, "b": 1.0}, default_weight=1.0)
    assert ac.tenant_quota("a") == 6  # 8 * 3/4
    assert ac.tenant_quota("b") == 2
    # unknown tenant: defaultWeight joins the denominator
    assert ac.tenant_quota("ghost") == 1  # max(1, int(8 * 1/5))


def test_fairshare_quota_sheds_typed_and_frees_on_dequeue():
    """Tenant a (weight 1 of 2, max_queue 4 -> quota 2) sheds its third
    QUEUED query with reason 'quota' while tenant b still admits; slots
    free as queries move from queued to running."""
    from spark_rapids_tpu.serve import metrics as sm

    conf = C.RapidsConf({
        C.SERVE_SINGLEFLIGHT.key: False,
        C.SERVE_FAIRSHARE_ENABLED.key: True,
        C.SERVE_FAIRSHARE_WEIGHTS.key: "a=1,b=1",
    })
    quota_before = sm.counters()["admission_quota_rejected_total"]
    srv = QueryServer(conf, max_concurrent=1, max_queue=4)
    try:
        order = []
        gate = threading.Event()
        blocker = srv.submit(_RecordingDF("blocker", order, gate),
                             tenant="b")
        deadline = time.monotonic() + 30
        while srv.admission._queued and time.monotonic() < deadline:
            time.sleep(0.005)
        t1 = srv.submit(_RecordingDF("a1", order), tenant="a")
        t2 = srv.submit(_RecordingDF("a2", order), tenant="a")
        with pytest.raises(AdmissionRejected) as ei:
            srv.submit(_RecordingDF("a3", order), tenant="a")
        assert ei.value.reason == "quota"
        assert (sm.counters()["admission_quota_rejected_total"]
                == quota_before + 1)
        # the OTHER tenant's share is untouched by a's shed
        tb = srv.submit(_RecordingDF("b1", order), tenant="b")
        gate.set()
        for tk in (blocker, t1, t2, tb):
            tk.result(timeout_s=60)
        # queue drained -> a's slots freed; it admits again
        srv.submit(_RecordingDF("a4", order), tenant="a").result(timeout_s=60)
    finally:
        srv.close()
    snap = srv.admission.snapshot()
    assert snap["fairshare"] and snap["tenant_queued"] == {}
