"""Serving-runtime counters (srtpu_admission_* / srtpu_sched_* gauges)
plus the per-tenant SLO surface.

Every name here is declared in obs/gauges.CATALOG (guarded by
tools/lint/gauge_catalog.py); ``counters()`` feeds gauges.snapshot() the
same way pipeline.STATS and faults.counters() do. Counters are process
totals; gauges (queue depth, reserved bytes, active queries) are levels.

Per-tenant SLOs (ROADMAP item 2's quota/fair-share substrate): queue
wait, semaphore wait, and deadline slack are recorded as labeled
children of the declared obs/histo.py families, keyed by
(tenant, priority); admission outcomes are counted per key. Tenant
cardinality is bounded (``spark.rapids.tpu.serve.slo.maxTenants``):
past the cap, new tenants collapse into the ``"overflow"`` bucket so a
tenant-id flood cannot grow the registry without bound. The whole layer
can be switched off (``spark.rapids.tpu.serve.slo.enabled``).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

_LOCK = threading.Lock()
_COUNTERS: Dict[str, int] = {
    "admission_submitted_total": 0,
    "admission_rejected_total": 0,
    "admission_quota_rejected_total": 0,
    "admission_unsupported_plan_total": 0,
    "admission_budget_exceeded_total": 0,
    "admission_queue_depth": 0,
    "admission_reserved_bytes": 0,
    "sched_completed_total": 0,
    "sched_failed_total": 0,
    "sched_cancelled_total": 0,
    "sched_deadline_exceeded_total": 0,
    "sched_singleflight_hit_total": 0,
    "sched_active_queries": 0,
    "sched_queue_wait_ns_total": 0,
}


def bump(name: str, delta: int = 1) -> None:
    with _LOCK:
        _COUNTERS[name] += delta


def set_level(name: str, value: int) -> None:
    """Set a gauge-kind entry to an absolute level."""
    with _LOCK:
        _COUNTERS[name] = value


def counters() -> Dict[str, int]:
    with _LOCK:
        return dict(_COUNTERS)


# -- per-tenant SLOs ---------------------------------------------------------

DEFAULT_TENANT = "default"
OVERFLOW_TENANT = "overflow"

_slo_enabled = True
_slo_max_tenants = 64
_tenant_lock = threading.Lock()
# (tenant, priority) -> outcome -> count. Outcomes are short verbs
# ("admitted", "completed", "failed", "rejected:queue-full", ...), not
# *_total metric names; Prometheus rendering adds the suffix.
_TENANT_OUTCOMES: "Dict[Tuple[str, int], Dict[str, int]]" = {}


def configure_slo(enabled: bool, max_tenants: int) -> None:
    """Apply the serve.slo.* conf (QueryServer does this at startup)."""
    global _slo_enabled, _slo_max_tenants
    _slo_enabled = bool(enabled)
    _slo_max_tenants = max(1, int(max_tenants))


def slo_enabled() -> bool:
    return _slo_enabled


def _tenant_key(tenant: Optional[str], priority: int) -> Tuple[str, int]:
    t = tenant or DEFAULT_TENANT
    with _tenant_lock:
        known = {k[0] for k in _TENANT_OUTCOMES}
        if t not in known and len(known) >= _slo_max_tenants:
            t = OVERFLOW_TENANT
    return (t, int(priority))


def note_outcome(tenant: Optional[str], priority: int, outcome: str) -> None:
    """Count one admission/terminal outcome for (tenant, priority)."""
    if not _slo_enabled:
        return
    key = _tenant_key(tenant, priority)
    with _tenant_lock:
        per = _TENANT_OUTCOMES.setdefault(key, {})
        per[outcome] = per.get(outcome, 0) + 1


def observe_queue_wait(tenant: Optional[str], priority: int,
                       wait_ns: int) -> None:
    if not _slo_enabled:
        return
    from spark_rapids_tpu.obs import histo
    t, p = _tenant_key(tenant, priority)
    histo.record_labeled("serve_queue_wait_ns", wait_ns,
                         tenant=t, priority=p)


def observe_deadline_slack(tenant: Optional[str], priority: int,
                           slack_ns: int) -> None:
    if not _slo_enabled:
        return
    from spark_rapids_tpu.obs import histo
    t, p = _tenant_key(tenant, priority)
    histo.record_labeled("serve_deadline_slack_ns", max(0, slack_ns),
                         tenant=t, priority=p)


def observe_semaphore_wait(wait_ns: int) -> None:
    """Attribute a task-semaphore wait to the serving tenant on this
    thread (mem/semaphore.py calls this; no-op outside a serve context)."""
    if not _slo_enabled:
        return
    from spark_rapids_tpu.serve import context as _ctx
    qc = _ctx.current()
    if qc is None:
        return
    from spark_rapids_tpu.obs import histo
    t, p = _tenant_key(getattr(qc, "tenant", None), qc.priority)
    histo.record_labeled("serve_semaphore_wait_ns", wait_ns,
                         tenant=t, priority=p)


def tenant_outcomes() -> "Dict[Tuple[str, int], Dict[str, int]]":
    with _tenant_lock:
        return {k: dict(v) for k, v in _TENANT_OUTCOMES.items()}


def tenant_slos() -> "Dict[Tuple[str, int], Dict]":
    """Merged per-(tenant, priority) view: outcome counts plus
    p50/p95/p99 (ms) for each SLO histogram family — the block
    explain_analyze / bench --clients / obs_report render."""
    from spark_rapids_tpu.obs import histo

    out: "Dict[Tuple[str, int], Dict]" = {}
    for key, per in tenant_outcomes().items():
        out[key] = {"outcomes": per}
    for hname, field in (("serve_queue_wait_ns", "queue_wait_ms"),
                         ("serve_semaphore_wait_ns", "semaphore_wait_ms"),
                         ("serve_deadline_slack_ns", "deadline_slack_ms")):
        for lkey, h in histo.family(hname).items():
            labels = dict(lkey)
            key = (labels.get("tenant", DEFAULT_TENANT),
                   int(labels.get("priority", 0)))
            snap = h.snapshot()
            if snap["count"] == 0:
                continue
            entry = out.setdefault(key, {"outcomes": {}})
            entry[field] = dict(h.percentiles_ms(snap), count=snap["count"])
    return out


def reset_tenants() -> None:
    with _tenant_lock:
        _TENANT_OUTCOMES.clear()
