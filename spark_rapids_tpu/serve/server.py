"""QueryServer: the concurrent query-lifecycle runtime.

Accepts N concurrent queries and makes concurrency safe before fast:

- **Admission** (serve/admission.py): bounded queue + HBM budget
  reservations; overload sheds with a typed ``AdmissionRejected``.
- **Scheduling**: a priority queue (higher ``priority`` first; within a
  priority band, earliest absolute deadline first when
  ``serve.edf.enabled``, submit order breaking ties and deadline-less
  queries sorting last) drained by ``serve.maxConcurrentQueries``
  executor threads;
  device-side fairness is the reworked TaskSemaphore (mem/semaphore.py),
  which the execution path enters with the query's priority, deadline
  budget, and cancellation hook.
- **Lifecycle**: every query carries a QueryContext (serve/context.py);
  cancel/deadline unwind at the runtime's poll points and release every
  pool allocation (verified by the per-query leak audit, obs/memtrack.py).
- **Single-flight dedup**: identical in-flight queries (same semantic plan
  key + same session conf + same partitioning) share one execution — the
  followers get tickets that resolve from the primary's result. Combined
  with the plan memo and the materialization cache (PR-5/PR-9), two
  clients running the same dashboard query cost one execution.

Lifecycle states (docs/serving.md): created -> queued -> running ->
{completed | cancelled | deadline | failed}, or rejected at admission.
``serve.admit`` is a fault site: an injected failure there surfaces as
AdmissionRejected(reason="fault-injected") — shedding, never corruption.
"""

from __future__ import annotations

import heapq
import itertools
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from spark_rapids_tpu.serve import admission as _adm
from spark_rapids_tpu.serve import context as _ctx
from spark_rapids_tpu.serve import metrics as _m
from spark_rapids_tpu.serve.admission import AdmissionController, AdmissionRejected
from spark_rapids_tpu.serve.context import (
    QueryCancelled,
    QueryContext,
    QueryDeadlineExceeded,
)

_seq = itertools.count()


class Ticket:
    """Handle for one submitted query: a one-shot future plus its
    QueryContext. ``result()`` returns the pa.Table or re-raises the
    query's typed failure."""

    def __init__(self, df, ctx: QueryContext, key):
        self.df = df
        self.ctx = ctx
        self.key = key
        self._done = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        self.enqueued_ns = time.perf_counter_ns()
        self._done_ns: Optional[int] = None
        # guards the hand-over of _callbacks at resolution, so a callback
        # added while the ticket resolves runs exactly once
        self._cb_lock = threading.Lock()
        self._callbacks: List[Callable[[], None]] = []

    def done(self) -> bool:
        return self._done.is_set()

    @property
    def done_ns(self) -> Optional[int]:
        """perf_counter_ns at which the query resolved (either way), or
        None while it has not: the start of the front-end's net:wake-lag."""
        return self._done_ns

    def cancel(self, reason: str = "cancelled") -> None:
        self.ctx.cancel(reason)

    def result(self, timeout_s: Optional[float] = None):
        if not self._done.wait(timeout_s):
            raise TimeoutError(f"{self.ctx.name} still running after "
                               f"{timeout_s}s")
        if self._error is not None:
            raise self._error
        return self._result

    def add_done_callback(self, fn: Callable[[], None]) -> None:
        """Call ``fn()`` once when the query resolves (either way), on the
        resolving thread, after ``done_ns`` is stamped and ``done()`` is
        true; at once, on this thread, when it already has. What lets a
        waiter that cannot block on the event (the wire front-end, which
        must keep reading its socket) be woken by the resolution."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        _run_done_callback(fn)

    def _fulfill(self, table) -> None:
        self._result = table
        self._resolve()

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._resolve()

    def _resolve(self) -> None:
        self._done_ns = time.perf_counter_ns()
        with self._cb_lock:
            self._done.set()
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            _run_done_callback(fn)


def _run_done_callback(fn: Callable[[], None]) -> None:
    # the outcome is already stored: a waiter's broken callback must not
    # reach the executor thread, which still owes the query its release
    try:
        fn()
    except Exception:  # noqa: BLE001 — the callback is the waiter's code
        logging.getLogger(__name__).exception(
            "ticket done-callback %r raised", fn)


class _FollowerTicket(Ticket):
    """Single-flight follower: resolves from the primary's outcome but has
    its own context — cancelling a follower detaches only that caller,
    never the shared execution."""

    def __init__(self, primary: Ticket, ctx: QueryContext):
        super().__init__(primary.df, ctx, primary.key)
        self._primary = primary

    def done(self) -> bool:
        return self.ctx.cancelled() or self._primary.done()

    @property
    def done_ns(self) -> Optional[int]:
        return self._primary.done_ns

    def add_done_callback(self, fn: Callable[[], None]) -> None:
        # the primary's resolution is the one event a follower's waiter
        # cannot see for itself; the follower's own cancellation is made
        # by that waiter, which re-checks done() after it
        self._primary.add_done_callback(fn)

    def result(self, timeout_s: Optional[float] = None):
        deadline = (None if timeout_s is None
                    else time.monotonic() + timeout_s)
        while not self._primary._done.wait(0.05):
            if self.ctx.cancelled():
                raise QueryCancelled(
                    f"{self.ctx.name} cancelled: {self.ctx.cancel_reason}")
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"{self.ctx.name} still running after "
                                   f"{timeout_s}s")
        if self.ctx.cancelled():
            raise QueryCancelled(
                f"{self.ctx.name} cancelled: {self.ctx.cancel_reason}")
        if self._primary._error is not None:
            raise self._primary._error
        return self._primary._result


class QueryServer:
    """N-concurrent-query runtime over the single-query engine."""

    def __init__(self, conf=None, max_concurrent: Optional[int] = None,
                 max_queue: Optional[int] = None):
        from spark_rapids_tpu.config import conf as C
        self.conf = conf if conf is not None else C.RapidsConf()
        self.max_concurrent = int(
            max_concurrent if max_concurrent is not None
            else C.SERVE_MAX_CONCURRENT.get(self.conf))
        mq = (max_queue if max_queue is not None
              else C.SERVE_QUEUE_DEPTH.get(self.conf))
        self.admission = AdmissionController(
            mq, _adm.reservable_bytes(self.conf))
        self.admission.configure_fairshare(
            C.SERVE_FAIRSHARE_ENABLED.get(self.conf),
            _adm.parse_weights(C.SERVE_FAIRSHARE_WEIGHTS.get(self.conf)),
            C.SERVE_FAIRSHARE_DEFAULT_WEIGHT.get(self.conf))
        self._edf = bool(C.SERVE_EDF_ENABLED.get(self.conf))
        self.grace_ms = float(C.SERVE_GRACE_MS.get(self.conf))
        self._singleflight = bool(C.SERVE_SINGLEFLIGHT.get(self.conf))
        self._default_budget = int(C.SERVE_DEFAULT_BUDGET.get(self.conf))
        self._default_deadline = float(
            C.SERVE_DEFAULT_DEADLINE_MS.get(self.conf))
        # process-wide observability knobs: last server constructed wins,
        # which matches how gauges/journal toggles behave already
        _m.configure_slo(C.SERVE_SLO_ENABLED.get(self.conf),
                         C.SERVE_SLO_MAX_TENANTS.get(self.conf))
        from spark_rapids_tpu.obs import span as _span
        _span.set_enabled(C.METRICS_SPANS_ENABLED.get(self.conf))
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # (-prio, deadline-key, seq, ticket): EDF within a priority band
        self._pq: List[Tuple[int, float, int, Ticket]] = []
        self._inflight: Dict[object, Ticket] = {}  # single-flight registry
        self._stopping = False
        self._workers = [
            threading.Thread(target=self._run_loop,
                             name=f"srtpu-serve-{i}", daemon=True)
            for i in range(self.max_concurrent)]
        for w in self._workers:
            w.start()

    # -- submission --------------------------------------------------------
    def _plan_fingerprint(self, df):
        """Single-flight identity: semantic plan text + the full session
        conf + the shuffle partitioning (the same inputs the plan memo
        keys on — a false negative costs a duplicate execution, never a
        wrong share)."""
        from spark_rapids_tpu.plan import plan_cache as _pc
        conf = df.conf if df.conf is not None else self.conf
        return (df._plan_key(), _pc._conf_key(conf), df.shuffle_partitions)

    def submit(self, df, priority: int = 0,
               deadline_ms: Optional[float] = None,
               memory_budget: Optional[int] = None,
               name: Optional[str] = None,
               tenant: Optional[str] = None,
               trace=None) -> Ticket:
        """Admit one query; returns its Ticket or raises AdmissionRejected.
        Defaults for deadline/budget come from the serve.* conf knobs.
        ``tenant`` keys the per-tenant SLO histograms/outcome counters
        (None folds into the "default" tenant). ``trace`` lets a caller
        that already opened a trace (the network front-end propagating a
        client's TraceContext) keep the query's spans under it; None
        starts a fresh trace."""
        from spark_rapids_tpu.obs import span as _span

        trace = trace if trace is not None else _span.new_trace()
        # under the caller's span when it belongs to this trace (the
        # front-end's net:accept), else directly under the trace's root
        cur = _span.current()
        parent = (cur if cur is not None and cur.trace_id == trace.trace_id
                  else trace)
        with _span.span("query:submit", ctx=parent,
                        attrs={"query": name,
                               "tenant": tenant or _m.DEFAULT_TENANT,
                               "priority": priority}) as sp:
            ticket = self._submit(df, priority, deadline_ms, memory_budget,
                                  name, tenant, trace)
            if sp is not None:
                sp.attrs["query"] = ticket.ctx.name  # the default, if unnamed
            return ticket

    def _submit(self, df, priority, deadline_ms, memory_budget, name,
                tenant, trace) -> Ticket:
        from spark_rapids_tpu import faults
        from spark_rapids_tpu.obs import events as _ev
        from spark_rapids_tpu.obs import span as _span

        _m.bump("admission_submitted_total")
        try:
            faults.check("serve.admit", op=name or "query")
        except Exception as e:  # injected: shed typed, never corrupt
            _m.bump("admission_rejected_total")
            _m.note_outcome(tenant, priority, "rejected:fault-injected")
            raise AdmissionRejected(
                "fault-injected", f"injected admission fault: {e}") from e
        if deadline_ms is None and self._default_deadline > 0:
            deadline_ms = self._default_deadline
        if memory_budget is None:
            memory_budget = self._default_budget
        ctx = QueryContext(name=name, priority=priority,
                           deadline_ms=deadline_ms,
                           memory_budget=memory_budget, tenant=tenant)
        ctx.trace = trace
        with self._lock:
            if self._stopping:
                _m.bump("admission_rejected_total")
                _m.note_outcome(tenant, priority, "rejected:shutdown")
                raise AdmissionRejected("shutdown", "server is shutting down")
            key = self._plan_fingerprint(df) if self._singleflight else None
            if key is not None:
                primary = self._inflight.get(key)
                if primary is not None and not primary.done():
                    _m.bump("sched_singleflight_hit_total")
                    _m.note_outcome(tenant, priority, "deduped")
                    _ev.emit("serve-singleflight", query_id=ctx.ctx_id,
                             primary=primary.ctx.ctx_id)
                    ctx.state = "deduped"
                    return _FollowerTicket(primary, ctx)
            # admission gates raise AdmissionRejected (counted inside)
            with _span.span("query:admit", attrs={"query": ctx.name}):
                try:
                    self.admission.admit(ctx)
                except AdmissionRejected as e:
                    _m.note_outcome(tenant, priority, f"rejected:{e.reason}")
                    raise
            ticket = Ticket(df, ctx, key)
            if key is not None:
                self._inflight[key] = ticket
            ctx.state = "queued"
            # EDF key: absolute deadline (monotonic s) within the band;
            # deadline-less queries sort after every deadlined one. With
            # EDF off the key is constant, restoring pure FIFO-by-seq.
            deadline_key = (ctx.deadline
                            if self._edf and ctx.deadline is not None
                            else (float("inf") if self._edf else 0.0))
            heapq.heappush(self._pq, (-ctx.priority, deadline_key,
                                      next(_seq), ticket))
            self._cv.notify()
        _m.note_outcome(tenant, priority, "admitted")
        _ev.emit("serve-admit", query_id=ctx.ctx_id, name=ctx.name,
                 priority=ctx.priority, budget=ctx.memory_budget,
                 deadline_ms=deadline_ms, tenant=tenant)
        return ticket

    # -- executors ---------------------------------------------------------
    def _run_loop(self) -> None:
        while True:
            with self._cv:
                while not self._pq and not self._stopping:
                    self._cv.wait(0.1)
                if not self._pq:
                    if self._stopping:
                        return
                    continue
                _, _, _, ticket = heapq.heappop(self._pq)
            self.admission.dequeued(ticket.ctx)
            self._execute(ticket)

    def _execute(self, ticket: Ticket) -> None:
        from spark_rapids_tpu.obs import events as _ev
        from spark_rapids_tpu.obs import span as _span
        ctx = ticket.ctx
        wait_ns = time.perf_counter_ns() - ticket.enqueued_ns
        _m.bump("sched_queue_wait_ns_total", wait_ns)
        _m.observe_queue_wait(ctx.tenant, ctx.priority, wait_ns)
        _span.record_span("query:queue-wait", ticket.enqueued_ns, wait_ns,
                          ctx=ctx.trace, attrs={"query": ctx.name})
        _m.bump("sched_active_queries")
        ctx.state = "running"
        out = error = None
        try:
            ctx.check()  # cancelled/deadlined while queued: never start
            with _ctx.activate(ctx), _span.activate(ctx.trace):
                with _span.span("query:execute",
                                attrs={"query": ctx.name,
                                       "tenant": ctx.tenant
                                       or _m.DEFAULT_TENANT}):
                    out = ticket.df.to_arrow()
            ctx.state = "completed"
            _m.bump("sched_completed_total")
            _m.note_outcome(ctx.tenant, ctx.priority, "completed")
            slack_ms = ctx.remaining_ms()
            if slack_ms is not None:
                _m.observe_deadline_slack(ctx.tenant, ctx.priority,
                                          int(slack_ms * 1e6))
        except QueryDeadlineExceeded as e:
            ctx.state = "deadline"
            _m.bump("sched_deadline_exceeded_total")
            _m.note_outcome(ctx.tenant, ctx.priority, "deadline")
            _m.observe_deadline_slack(ctx.tenant, ctx.priority, 0)
            error = e
        except QueryCancelled as e:
            ctx.state = "cancelled"
            _m.bump("sched_cancelled_total")
            _m.note_outcome(ctx.tenant, ctx.priority, "cancelled")
            error = e
        except BaseException as e:  # noqa: BLE001 — must reach the caller
            ctx.state = "failed"
            _m.bump("sched_failed_total")
            _m.note_outcome(ctx.tenant, ctx.priority, "failed")
            error = e
        finally:
            _m.bump("sched_active_queries", -1)
            self.admission.release(ctx)
            # resolved only now: a waiter woken by the ticket may submit
            # its next query at once, and that one must not be shed for
            # memory against this one's reservation
            if error is not None:
                ticket._fail(error)
            else:
                ticket._fulfill(out)
            if ticket.key is not None:
                with self._lock:
                    if self._inflight.get(ticket.key) is ticket:
                        del self._inflight[ticket.key]
            _ev.emit("serve-finish", query_id=ctx.ctx_id, state=ctx.state,
                     name=ctx.name)

    # -- shutdown ----------------------------------------------------------
    def close(self, cancel_pending: bool = True) -> None:
        """Stop accepting work and join the executors. Pending queries are
        cancelled (typed) unless ``cancel_pending=False``, in which case
        they drain first. Join is bounded by serve.cancelGraceMs per
        worker beyond any in-flight deadline."""
        with self._lock:
            self._stopping = True
            pending = ([t for _, _, _, t in self._pq]
                       if cancel_pending else [])
            if cancel_pending:
                self._pq.clear()
            self._cv.notify_all()
        for t in pending:
            t.ctx.cancel("server shutdown")
            self.admission.release(t.ctx, still_queued=True)
            t._fail(QueryCancelled(f"{t.ctx.name} cancelled: server "
                                   f"shutdown"))
        for w in self._workers:
            w.join(timeout=self.grace_ms / 1e3)

    def snapshot(self) -> Dict:
        with self._lock:
            queued = len(self._pq)
            inflight = len(self._inflight)
        return {"queued": queued, "inflight_keys": inflight,
                "admission": self.admission.snapshot(),
                "counters": _m.counters()}
