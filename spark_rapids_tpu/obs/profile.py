"""Per-query profile: one structured view of a query's full cost.

The reference spreads a query's observability across leveled GpuMetrics on
every operator (GpuExec.scala:41-178), GpuTaskMetrics accumulators, NVTX
timelines, and "explain with metrics" in the Spark UI. This module is the
standalone unification: a ``QueryProfile`` is installed per planned query
(plan/overrides.py), snapshots every process gauge at start, and at finish
walks the executed operator tree to capture per-node metrics, gauge deltas,
task-metric aggregates, and the trace-event window.

Products:
- ``to_dict()``      the structured breakdown (bench dumps one per query)
- ``explain_analyze()``  plan tree with rows/batches/opTime inline (the
  AdaptiveSparkPlan "explain with metrics" analog)
- ``chrome_trace()``     Perfetto/chrome://tracing-loadable trace_event JSON
"""

from __future__ import annotations

import collections
import json
import threading
import time
from typing import Dict, List, Optional

from spark_rapids_tpu.obs import events as _events
from spark_rapids_tpu.obs import gauges as G
from spark_rapids_tpu.obs import histo as _histo
from spark_rapids_tpu.utils import task_metrics as TM
from spark_rapids_tpu.utils import tracing

# Registry of recent profiles (bounded; profiles hold only plain dicts, not
# exec trees or device buffers, so retention is cheap).
MAX_PROFILES = 64
_lock = threading.Lock()
_next_id = 1
_profiles: "collections.OrderedDict[int, QueryProfile]" = \
    collections.OrderedDict()


def _ns_ms(ns: int) -> float:
    return round(ns / 1e6, 3)


class QueryProfile:
    """Lifecycle: ``start()`` at plan time -> query executes -> ``finish(root)``
    once output is consumed (plan/dataframe.py wires both ends)."""

    def __init__(self, description: str = "", conf=None,
                 capture_trace: bool = False):
        global _next_id
        with _lock:
            self.query_id = _next_id
            _next_id += 1
            _profiles[self.query_id] = self
            while len(_profiles) > MAX_PROFILES:
                _profiles.popitem(last=False)
        self.description = description
        self.conf = conf
        self.capture_trace = capture_trace
        self.plan_explain = ""
        self.started = False
        self.finished = False
        self.wall_ns = 0
        self.phases: Dict[str, float] = {}  # phase name -> ms
        self.nodes: List[Dict] = []
        self.metrics: Dict[str, int] = {}
        self.gauges: Dict[str, Dict] = {}
        self.task_metrics: Dict[str, int] = {}
        self.memory: Dict = {}
        self.events: List[Dict] = []
        self.tenant: Optional[str] = None  # serving attribution, set at
        self.priority = 0                  # finish() from the QueryContext
        self._t0 = 0
        self._gauges0: Dict[str, int] = {}
        self._tasks0: Dict[str, int] = {}
        self._compile0 = 0
        self._owned_capture = False
        _events.emit("submit", query_id=self.query_id,
                     description=description[:160])

    # -- lifecycle ---------------------------------------------------------
    def note_phase(self, name: str, dur_ns: int) -> None:
        """Attribute a planning-side phase (plan-rewrite/reuse/fusion);
        journaled as it happens so the lifecycle timeline reads in order."""
        self.phases[name] = self.phases.get(name, 0.0) + _ns_ms(dur_ns)
        _events.emit("phase", query_id=self.query_id, phase=name,
                     dur_ms=_ns_ms(dur_ns))

    def start(self) -> "QueryProfile":
        self._t0 = time.perf_counter_ns()
        self._gauges0 = G.snapshot()
        self._tasks0 = TM.aggregate_snapshot()
        from spark_rapids_tpu.exec import jit_cache as _jc
        self._compile0 = _jc.compile_ns_total()
        if self.capture_trace and not tracing.capturing():
            # open our own event window; a user-managed Profiler window
            # stays untouched (we'd otherwise clear their events)
            tracing.set_capture(True, clear=True)
            self._owned_capture = True
        self.started = True
        return self

    def attach(self, root) -> "QueryProfile":
        """Pin this profile on an exec tree root (read back by
        ``profile_for`` / DataFrame.to_arrow)."""
        root._query_profile = self
        return self

    def finish(self, root=None) -> "QueryProfile":
        """Snapshot everything; idempotent (re-finish refreshes)."""
        first = not self.finished
        self.wall_ns = time.perf_counter_ns() - self._t0
        # Attribute the execute window: ns spent tracing+compiling new
        # jitted programs (exec/jit_cache.py first-call timer) vs the rest.
        from spark_rapids_tpu.exec import jit_cache as _jc
        compile_ns = max(0, _jc.compile_ns_total() - self._compile0)
        self.phases["compile"] = _ns_ms(compile_ns)
        self.phases["execute"] = _ns_ms(max(0, self.wall_ns - compile_ns))
        end = G.snapshot()
        self.gauges = G.diff(self._gauges0, end)
        tasks1 = TM.aggregate_snapshot()
        self.task_metrics = {
            f: (max(0, tasks1[f] - self._tasks0.get(f, 0))
                if not f.startswith("max_") else tasks1[f])
            for f in tasks1
        }
        if self._owned_capture:
            tracing.set_capture(False)
            self._owned_capture = False
        self.events = tracing.trace_events()
        # per-query HBM attribution (obs/memtrack.py): peaks and per-site/
        # per-op aggregates of allocations tagged to this query. Updated in
        # place so a later leak_audit entry (plan/dataframe.py) survives a
        # re-finish.
        from spark_rapids_tpu.obs import memtrack as _mt
        if _mt.enabled():
            self.memory.update(_mt.query_summary(self.query_id))
        if root is not None:
            self.nodes = collect_node_stats(root)
            self.metrics = root.collect_metrics()
            if first:
                # close the measurement loop: operator timings, dispatch
                # decisions, and output ratios feed the persistent
                # autotune store (plan/autotune.py; never raises, and
                # collect_node_stats above already copied the decisions
                # this drains)
                from spark_rapids_tpu.plan import autotune as _at
                _at.feedback(root)
        if first:
            _histo.record("query_wall_ns", self.wall_ns)
            # per-phase distributions (bench --latency reads these through
            # snapshot/diff windows, so cold and warm tails separate)
            plan_ms = sum(v for k, v in self.phases.items()
                          if k not in ("compile", "execute"))
            _histo.record("plan_phase_ns", int(plan_ms * 1e6))
            _histo.record("compile_phase_ns", compile_ns)
            _histo.record("execute_phase_ns",
                          max(0, self.wall_ns - compile_ns))
            # (the query:plan and query:compile spans are real intervals,
            # opened where planning and compilation happen: plan/
            # dataframe.py and exec/jit_cache.py)
            # serving attribution for the explain_analyze tenant-slo line
            from spark_rapids_tpu.serve import context as _qc
            qc = _qc.current()
            if qc is not None:
                self.tenant = qc.tenant or "default"
                self.priority = qc.priority
            _events.emit("finish", query_id=self.query_id,
                         wall_ms=_ns_ms(self.wall_ns),
                         compile_ms=self.phases["compile"])
        self.finished = True
        return self

    # -- products ----------------------------------------------------------
    def dispatch_paths(self) -> Dict[str, int]:
        """Dispatch decisions across the plan, counted by
        ``op:path:source`` — which join/agg paths served the query and
        whether each choice was measured or the static default
        (plan/autotune.py)."""
        out: Dict[str, int] = {}
        for node in self.nodes:
            for d in node.get("dispatch", ()):
                key = f"{d['op']}:{d['path']}:{d['source']}"
                out[key] = out.get(key, 0) + 1
        return out

    def to_dict(self) -> Dict:
        return {
            "query_id": self.query_id,
            "description": self.description,
            "wall_ms": _ns_ms(self.wall_ns),
            "phases": dict(self.phases),
            "dispatch_paths": self.dispatch_paths(),
            "latency": {  # process-wide log-bucket estimates (obs/histo.py)
                "query_wall": _histo.percentiles("query_wall_ns"),
                "batch_op": _histo.percentiles("batch_op_ns"),
            },
            "nodes": self.nodes,
            "metrics": self.metrics,
            "gauges": self.gauges,
            "task_metrics": self.task_metrics,
            "memory": self.memory,
            "num_trace_events": len(self.events),
            "plan_explain": self.plan_explain,
        }

    def dump(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, default=str)
        return path

    def explain_analyze(self) -> str:
        """Plan tree with per-node metric rows inline."""
        lines = [f"== Query Profile #{self.query_id} "
                 f"(wall {_ns_ms(self.wall_ns)} ms) =="]
        if self.phases:
            order = ("plan-rewrite", "reuse", "fusion", "prefetch",
                     "compile", "execute")
            cells = [f"{p}={self.phases[p]}ms" for p in order
                     if p in self.phases]
            cells += [f"{p}={v}ms" for p, v in sorted(self.phases.items())
                      if p not in order]
            lines.append(f"phases: {' '.join(cells)}")
        if self.memory.get("tracked_peak_bytes"):
            audit = self.memory.get("leak_audit", {})
            mem_cells = [f"peak={self.memory['tracked_peak_bytes']}B"]
            if audit:
                mem_cells.append(f"leaked={audit.get('leaked_bytes', 0)}B")
                if audit.get("retained_bytes"):
                    mem_cells.append(f"retained={audit['retained_bytes']}B")
            lines.append(f"memory: {' '.join(mem_cells)}")
        if self.tenant is not None:
            # per-tenant SLO tails for the tenant this query ran under
            from spark_rapids_tpu.serve import metrics as _sm
            slo = _sm.tenant_slos().get((self.tenant, self.priority))
            if slo:
                cells = []
                for field in ("queue_wait_ms", "semaphore_wait_ms",
                              "deadline_slack_ms"):
                    pc = slo.get(field)
                    if pc:
                        cells.append(
                            f"{field.removesuffix('_ms')}="
                            f"{pc['p50']}/{pc['p95']}/{pc['p99']}ms")
                for outcome, n in sorted(slo.get("outcomes", {}).items()):
                    cells.append(f"{outcome}={n}")
                lines.append(f"tenant-slo[{self.tenant}/p{self.priority}] "
                             f"(p50/p95/p99): {' '.join(cells)}")
        mem_ops = self.memory.get("ops", {})
        for node in self.nodes:
            pad = "  " * node["depth"]
            prefix = "+- " if node["depth"] else ""
            m = node["metrics"]
            cells = []
            if "numOutputRows" in m:
                cells.append(f"rows={m['numOutputRows']}")
            if "numOutputBatches" in m:
                cells.append(f"batches={m['numOutputBatches']}")
            if "opTime" in m:
                cells.append(f"opTime={_ns_ms(m['opTime'])}ms")
            for k, v in sorted(m.items()):
                if k in ("numOutputRows", "numOutputBatches", "opTime"):
                    continue
                cells.append(f"{k.removesuffix('Ns')}={_ns_ms(v)}ms"
                             if k.endswith("Ns") else f"{k}={v}")
            if "fused" in node:
                cells.append(f"fused=#{node['fused']}")
            dseen: List[str] = []
            for d in node.get("dispatch", ()):
                cell = f"path={d['path']} source={d['source']}"
                if cell not in dseen:
                    dseen.append(cell)
            cells.extend(dseen)
            lines.append(f"{pad}{prefix}{node['description']}  "
                         f"[{' '.join(cells)}]" if cells else
                         f"{pad}{prefix}{node['description']}")
            # per-operator HBM line, only for operators that actually
            # touched the pool — most demo queries never allocate, so the
            # tree shape (and line-offset expectations) stays unchanged
            ms = mem_ops.get(node["name"])
            if ms and (ms.get("peak") or ms.get("allocd")):
                lines.append(f"{pad}   mem: peak={ms['peak']}B "
                             f"alloc={ms['allocd']}B "
                             f"spilled={ms['spilled']}B")
        return "\n".join(lines)

    def chrome_trace(self) -> Dict:
        from spark_rapids_tpu.obs import trace_export
        return trace_export.to_chrome_trace(
            self.events, self.nodes,
            process_name=f"spark_rapids_tpu query {self.query_id}")

    def dump_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


def collect_node_stats(root) -> List[Dict]:
    """Pre-order walk of an exec tree -> plain per-node dicts (node id,
    depth, parent, description, enabled metric values).

    Fused-stage constituents (exec/fused.py) are not structural children
    but still carry attributed row/batch metrics; they are emitted as
    extra rows right under their stage, tagged ``fused=<stage id>``, with
    the stage's opTime split evenly across them so per-operator cost
    stays visible in explain_analyze and the Chrome trace."""
    out: List[Dict] = []

    def walk(node, depth: int, parent: Optional[int]):
        nid = len(out)
        snap = node.metrics_snapshot()
        row = {
            "id": nid,
            "parent": parent,
            "depth": depth,
            "name": type(node).__name__,
            "description": node.node_description(),
            "metrics": snap,
        }
        disp = getattr(node, "_dispatch", None)
        if disp:
            row["dispatch"] = [dict(d) for d in disp]
        out.append(row)
        fused = list(getattr(node, "fused_ops", ()))
        if fused:
            share = snap.get("opTime", 0) // len(fused)
            for op in reversed(fused):  # top-down like the plan tree
                m = op.metrics_snapshot()
                m["opTime"] = m.get("opTime", 0) + share
                fid = len(out)
                frow = {
                    "id": fid,
                    "parent": nid,
                    "depth": depth + 1,
                    "name": type(op).__name__,
                    "description": op.node_description(),
                    "metrics": m,
                    "fused": nid,
                }
                fdisp = getattr(op, "_dispatch", None)
                if fdisp:
                    frow["dispatch"] = [dict(d) for d in fdisp]
                out.append(frow)
                if len(op.children) == 2:
                    # absorbed join: its build subtree executed for real
                    walk(op.children[1], depth + 2, fid)
        for c in node.children:
            walk(c, depth + 1, nid)

    walk(root, 0, None)
    return out


def profile_for(root) -> Optional[QueryProfile]:
    """The profile installed on an exec tree root (or None)."""
    return getattr(root, "_query_profile", None)


def get_profile(query_id: int) -> Optional[QueryProfile]:
    with _lock:
        return _profiles.get(query_id)


def recent_profiles() -> List[QueryProfile]:
    """Registry contents, oldest first."""
    with _lock:
        return list(_profiles.values())


def last_profile() -> Optional[QueryProfile]:
    with _lock:
        return next(reversed(_profiles.values()), None)
