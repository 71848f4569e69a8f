"""Distributed query tracing: spans + a serializable TraceContext.

The reference plugin attributes time with NVTX ranges and a
driver-coordinated profiler; both stop at the process boundary. This
module is the standalone analog for the serving + mesh/cluster path: a
``Span`` names one timed region, carries ``trace_id``/``span_id``/
``parent_id``, and records into the *existing* observability machinery:
a live span is one ``utils/tracing.TraceRange``, i.e. an event in the
in-process log (``tracing.record_event``, so spans land in per-process
Chrome traces and survive the multi-worker merge in obs/trace_export.py)
and a ``jax.profiler.TraceAnnotation`` of the same name for the span's
life (so a jax profiler trace carries the program's spans on the device
trace's own clock). Nothing else is written: the lifecycle journal
(obs/events.py) holds lifecycle events, not spans.

Cost with capture off and no profiler running: no lock, no
``os.urandom``. ``record_event`` reads the capture flag before its lock,
the annotation is the native TraceMe's flag check, and ids are a
per-process random prefix (drawn once, again in a forked child) plus a
counter, so they are unique across the processes of a trace without a
system call per span.

Cross-process propagation uses ``TraceContext``: a two-field value
(``trace_id``, ``span_id`` of the would-be parent) whose ``to_wire()``
tuple rides the cluster ctrl pipe (shuffle/cluster.py), is installed on
executor threads via ``activate()``, and parents every span a worker
records — cluster map/reduce tasks, shuffle block fetches, mesh
dispatches. ``assemble()`` reverses the trip: given per-process event
lists (e.g. from ``TcpShuffleCluster.collect_traces``) it regroups span
events by trace_id so one query's submit→admit→queue-wait→plan→compile→
shuffle-fetch→execute timeline reads as a single tree even though its
spans were recorded in three processes.

Span *names* are a declared catalog (``CATALOG`` below), mirroring
obs/gauges.CATALOG: opening a span with an undeclared name raises, and
tools/lint/span_catalog.py flags undeclared string constants statically
so the default lane catches them without running the code. Dynamic
detail (shuffle id, node type, tenant) goes in ``attrs``, never in the
name.
"""

from __future__ import annotations

import itertools
import os
import threading
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu.utils import tracing

# name -> help; the closed set of span names. Parsed statically by
# tools/lint/span_catalog.py (keep this a literal list of 2-tuples).
# Dynamic identifiers (shuffle id, query name, node type) belong in
# attrs so traces aggregate by phase, not by instance.
CATALOG: "List[Tuple[str, str]]" = [
    ("query:submit", "QueryServer.submit window (validate + admit + enqueue)"),
    ("query:admit", "Admission-control decision inside submit"),
    ("query:queue-wait", "Admitted-to-scheduled wait on the priority queue; "
     "stamped afterwards (its start is in the past), so it has no "
     "profiler annotation"),
    ("query:plan", "DataFrame.to_arrow's physical_plan(): plan-cache "
     "lookup or the full Overrides.apply (attrs: query, cache_hit)"),
    ("query:compile", "First call of a newly bound jitted program: trace + "
     "compile or cache load (attrs: program); absent when nothing compiled"),
    ("query:execute", "Execute window on the serving executor thread"),
    ("query:readback", "One batch_to_arrow of the result: device->host copy "
     "(its exec:host-sync child) plus host-side Arrow assembly (attrs: rows)"),
    ("query:finish", "Query close-out on the executor thread: profile "
     "finish, autotune feedback and its file write, cleanup walk, leak audit"),
    ("exec:host-sync", "One blocking device->host read on the query path, "
     "through utils/sync.host_get (attrs: site)"),
    ("exec:agg-step", "One dispatch of a fused stage's streaming aggregate: "
     "the seed (first batch), then one per window: chain + first pass per "
     "batch, one carry merge (attrs: batches, rows = batches x their "
     "capacity)"),
    ("exec:join-build", "One hash join's build side: executing it and "
     "constructing the probe structure, the construction's host syncs "
     "(join.dense_key_stats, join.dense_dup, join.table_stats, join.table_dup) as children; "
     "the unfused operator's also holds the peek of its first probe batch "
     "(attrs: path = dense | unique | ht | sorted, rows = live build rows "
     "where a sync already read them, capacity)"),
    ("exec:topn", "One dispatch of a top-N (exec/sort.py TopNExec): a "
     "batch's k best rows by selection, or the partials' (attrs: k, rows = "
     "slots scanned, the batch's capacity, capacity = the output's)"),
    ("exec:fused-fallback", "A partition re-run through the unfused "
     "operator chain after its fused stage gave up, until the chain is "
     "drained (attrs: cause = join-refused | carry-overflow | empty)"),
    ("cluster:map", "Map task executed by a cluster executor process"),
    ("cluster:reduce", "Reduce task executed by a cluster executor process"),
    ("shuffle:fetch", "One shuffle block fetch round-trip (client side)"),
    ("shuffle:write", "Map-output partition/serialize/spill on the write path"),
    ("mesh:dispatch", "One SPMD dispatch by the mesh executor"),
    ("net:request", "NetClient.submit, whole call: the root span of a "
     "served request, whose id the server's spans parent on (attrs: query)"),
    ("net:client-send", "Client side of SUBMIT: strip tables, pickle the "
     "plan, send the frame"),
    ("net:client-recv", "Client side of the result: first result frame "
     "received through the decoded pa.Table (attrs: rows)"),
    ("net:accept", "Wire SUBMIT intake: decode + table resolve + lowering "
     "gate + QueryServer.submit"),
    ("net:wake-lag", "Ticket resolved -> the front-end's _await_result, "
     "woken through the connection's wake channel, returned: the hand-off "
     "between two threads; stamped afterwards (its start is in the past), "
     "so it has no profiler annotation"),
    ("net:stream", "Result streaming window: Arrow IPC batches over the "
     "wire, RESULT_START through RESULT_END"),
]

_NAMES = frozenset(name for name, _ in CATALOG)

_enabled = True


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


_id_prefix = ""
_id_counter = itertools.count(1)


def _reseed_ids() -> None:
    global _id_prefix, _id_counter
    _id_prefix = os.urandom(4).hex()
    _id_counter = itertools.count(1)


_reseed_ids()
os.register_at_fork(after_in_child=_reseed_ids)


def _new_id() -> str:
    """This process's random prefix + a counter (``next`` on
    ``itertools.count`` is atomic under the GIL: no lock)."""
    return f"{_id_prefix}{next(_id_counter):010x}"


class TraceContext:
    """Serializable (trace_id, parent span_id) pair — the propagation unit.

    ``to_wire()``/``from_wire()`` round-trip through the cluster ctrl
    pipe as a plain tuple so the pickled payload stays version-tolerant.
    """

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def to_wire(self) -> Tuple[str, str]:
        return (self.trace_id, self.span_id)

    @classmethod
    def from_wire(cls, wire) -> "Optional[TraceContext]":
        if wire is None:
            return None
        trace_id, span_id = wire
        return cls(trace_id, span_id)

    def __repr__(self) -> str:
        return f"TraceContext({self.trace_id}, {self.span_id})"


def new_trace() -> TraceContext:
    """Fresh root context: trace_id plus a synthetic root span id."""
    return TraceContext(_new_id(), _new_id())


_TLS = threading.local()


def current() -> Optional[TraceContext]:
    """The TraceContext installed on this thread, or None."""
    return getattr(_TLS, "ctx", None)


@contextmanager
def activate(ctx: Optional[TraceContext]):
    """Install ``ctx`` as this thread's current trace context."""
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = ctx
    try:
        yield ctx
    finally:
        _TLS.ctx = prev


class Span:
    """One timed, named region of a trace, live from construction to
    ``finish()``: a ``tracing.TraceRange`` (profiler annotation now, event
    in the in-process log at finish, args carrying the ids + attrs).
    ``attrs`` may be filled in until ``finish()``. Parentage comes from the
    explicit ``ctx`` or the thread's current context; with neither, the
    span is the root of a new trace. Open and finish on one thread."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs",
                 "_range")

    def __init__(self, name: str, ctx: Optional[TraceContext] = None,
                 attrs: Optional[Dict] = None):
        if name not in _NAMES:
            raise KeyError(f"span name {name!r} is not declared in "
                           "obs/span.CATALOG")
        ctx = ctx if ctx is not None else current()
        if ctx is None:
            self.trace_id, self.parent_id = _new_id(), None
        else:
            self.trace_id, self.parent_id = ctx.trace_id, ctx.span_id
        self.span_id = _new_id()
        self.name = name
        self.attrs = dict(attrs) if attrs else {}
        self._range = tracing.TraceRange(name).open()

    @property
    def start_ns(self) -> int:
        return self._range.start_ns

    def adopt(self, ctx: Optional[TraceContext]) -> None:
        """Move this span under ``ctx``: for a server whose span has to
        open before the payload that names its trace is decoded. Call it
        before ``context()`` is handed to anyone."""
        if ctx is not None:
            self.trace_id, self.parent_id = ctx.trace_id, ctx.span_id

    def context(self) -> TraceContext:
        """Child context: propagate this to parent sub-spans on me."""
        return TraceContext(self.trace_id, self.span_id)

    def finish(self, end_ns: Optional[int] = None) -> None:
        rng, self._range = self._range, None
        if rng is None:
            return
        args = (_args(self.trace_id, self.span_id, self.parent_id,
                      self.attrs) if tracing.capturing() else None)
        rng.close(args=args, end_ns=end_ns, record=args is not None)


def _args(trace_id: str, span_id: str, parent_id: Optional[str],
          attrs: Optional[Dict]) -> Dict:
    args = {"trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id}
    if attrs:
        args.update(attrs)
    return args


def record_span(name: str, start_ns: int, dur_ns: int,
                ctx: Optional[TraceContext] = None,
                attrs: Optional[Dict] = None) -> Optional[str]:
    """Record an already-timed region as a completed span.

    For sites whose region began before anything could open it (a wait
    measured from a stamp another thread left: query:queue-wait,
    net:wake-lag) or that timed a window themselves (shuffle fetch retry
    loop). Such a span has no profiler annotation. Returns the new
    span_id, or None when tracing is disabled, no capture window is open,
    or no context is active and ``ctx`` was not given.
    """
    if name not in _NAMES:
        raise KeyError(f"span name {name!r} is not declared in "
                       "obs/span.CATALOG")
    if not _enabled or not tracing.capturing():
        return None
    ctx = ctx if ctx is not None else current()
    if ctx is None:
        return None
    span_id = _new_id()
    tracing.record_event(name, start_ns, max(0, int(dur_ns)),
                         args=_args(ctx.trace_id, span_id, ctx.span_id,
                                    attrs))
    return span_id


@contextmanager
def span(name: str, ctx: Optional[TraceContext] = None,
         attrs: Optional[Dict] = None):
    """Open a span, install its child context on this thread, finish it
    on exit. The workhorse API:

        with span("query:execute", attrs={"tenant": t}) as sp:
            ...                      # sub-spans parent on sp.context()
    """
    if not _enabled:
        yield None
        return
    s = Span(name, ctx=ctx, attrs=attrs)
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = s.context()
    try:
        yield s
    finally:
        _TLS.ctx = prev
        s.finish()


@contextmanager
def task_span(name: str, ctx: Optional[TraceContext] = None,
              attrs: Optional[Dict] = None):
    """Like ``span()`` but a no-op when no trace context is active or
    supplied — for worker-side sites (cluster tasks, shuffle, mesh) that
    should only record when a trace was actually propagated to them,
    instead of fabricating orphan single-span traces."""
    ctx = ctx if ctx is not None else current()
    if not _enabled or ctx is None:
        yield None
        return
    with span(name, ctx=ctx, attrs=attrs) as s:
        yield s


# -- trace reassembly --------------------------------------------------------

def span_events(events: List[Dict]) -> List[Dict]:
    """Filter a raw tracing.trace_events() list down to span events."""
    out = []
    for e in events:
        args = e.get("args") or {}
        if "trace_id" in args and "span_id" in args:
            out.append(e)
    return out


def assemble_traces(per_process: Dict[str, List[Dict]]) -> Dict[str, List[Dict]]:
    """Regroup per-process event lists into per-trace span timelines.

    ``per_process`` maps a process label (e.g. "driver", "worker-0") to
    its raw trace-event list — the same shape
    ``TcpShuffleCluster.collect_traces`` / ``tracing.trace_events``
    produce. Returns ``{trace_id: [span dicts sorted by start_ns]}``
    where each span dict carries name/span_id/parent_id/process/
    start_ns/dur_ns/attrs. A query's distributed timeline is one entry.
    """
    traces: Dict[str, List[Dict]] = {}
    for process, events in per_process.items():
        for e in span_events(events):
            args = dict(e.get("args") or {})
            trace_id = args.pop("trace_id")
            rec = {
                "name": e.get("name"),
                "span_id": args.pop("span_id"),
                "parent_id": args.pop("parent_id", None),
                "process": process,
                "start_ns": e["start_ns"] if "start_ns" in e else 0,
                "dur_ns": e["dur_ns"] if "dur_ns" in e else 0,
                "attrs": args,
            }
            traces.setdefault(trace_id, []).append(rec)
    for spans in traces.values():
        spans.sort(key=lambda s: s["start_ns"])
    return traces
