"""Cross-layer fault injection & resilience (docs/fault_injection.md).

Public surface:

- ``check(site, **ctx)`` — one-line injection hook threaded through the
  runtime (mem/pool, io decode, shuffle serialize/fetch/block, the ICI
  exchange, executor task loops). A single ``None`` test when no schedule
  is installed, so production paths pay nothing.
- ``corrupt(site, data, **ctx)`` — like ``check`` but for byte streams:
  ``corrupt`` rules flip a seeded byte (caught downstream by the shuffle
  integrity trailer, shuffle/integrity.py).
- ``configure(conf)`` — install the registry from
  ``spark.rapids.tpu.test.faults`` (called by Overrides.apply and the
  cluster worker task loop). The registry is reused while the spec is
  unchanged so seeded schedules advance across plans — retries draw NEW
  events instead of deterministically replaying the same fault.
- ``note_recovered(site)`` / ``note_degraded(site)`` — recovery-path
  bookkeeping; totals surface as ``srtpu_fault_{injected,recovered,
  degraded}_total`` through obs/gauges.py.

Reference: RmmSpark.forceRetryOOM / RapidsConf OomInjectionConf generalized
to every layer (see faults/registry.py).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from spark_rapids_tpu.faults.registry import (  # noqa: F401
    FaultInjectedError,
    FaultRegistry,
    parse_spec,
)

_REGISTRY: Optional[FaultRegistry] = None
_REG_LOCK = threading.Lock()

_CTR_LOCK = threading.Lock()
_COUNTERS = {
    "fault_injected_total": 0,
    "fault_recovered_total": 0,
    "fault_degraded_total": 0,
}


# -- hooks (hot path: one attribute read + None test when unconfigured) -----

def check(site: str, **ctx) -> None:
    r = _REGISTRY
    if r is None:
        return
    r.check(site, ctx)


def corrupt(site: str, data: bytes, **ctx) -> bytes:
    r = _REGISTRY
    if r is None:
        return data
    return r.corrupt(site, data, ctx)


# -- configuration ----------------------------------------------------------

def configure(conf=None) -> None:
    """Install (or clear) the registry from the active conf's
    ``spark.rapids.tpu.test.faults`` spec."""
    from spark_rapids_tpu.config import conf as _C

    if conf is None:
        conf = _C.get_active()
    install(_C.TEST_FAULTS.get(conf))


def install(spec: str) -> None:
    """Install a schedule directly (tests). Empty spec clears. A registry
    whose spec is unchanged is kept, so its seeded streams keep advancing."""
    global _REGISTRY
    with _REG_LOCK:
        if not spec:
            _REGISTRY = None
            return
        if _REGISTRY is not None and _REGISTRY.spec == spec:
            return
        _REGISTRY = FaultRegistry(spec)


def reset() -> None:
    """Drop the installed schedule (counters persist — they are process
    totals, like every other srtpu counter)."""
    install("")


def get_registry() -> Optional[FaultRegistry]:
    return _REGISTRY


# -- counters ---------------------------------------------------------------
# Each note_* also journals the event (obs/events.py) so the fault registry
# and the lifecycle journal tell one story end-to-end: chaos-lane tests
# assert every counted recovery/degrade has a matching journal event.

def _journal(kind: str, site: str) -> None:
    from spark_rapids_tpu.obs import events as _ev
    _ev.emit(kind, site=site)


def note_injected(site: str) -> None:
    with _CTR_LOCK:
        _COUNTERS["fault_injected_total"] += 1
    _journal("fault-injected", site)


def note_recovered(site: str) -> None:
    """A hardened path absorbed a failure (injected or real): OOM retry
    succeeded, a corrupt block re-fetched clean, a fetch retry connected,
    a lost map output recomputed, a failed query re-ran clean."""
    with _CTR_LOCK:
        _COUNTERS["fault_recovered_total"] += 1
    _journal("fault-recovered", site)


def note_degraded(site: str) -> None:
    """A stage/query gave up on the device and completed on the CPU engine
    (graceful degradation, plan/cpu.py)."""
    with _CTR_LOCK:
        _COUNTERS["fault_degraded_total"] += 1
    _journal("degraded", site)


def counters() -> Dict[str, int]:
    with _CTR_LOCK:
        return dict(_COUNTERS)
