"""Log2-bucketed latency histograms with cheap lock-guarded recording.

Latency *distributions* — not just totals — are what ROADMAP items 1/2
gate on (p50/p95/p99 for serving, sub-second small-query tails). Each
histogram is a fixed array of power-of-two buckets: ``record(ns)`` is
one ``bit_length`` plus two adds under a lock, no allocation, which is
what lets the per-batch opTime site in exec/base.py stay always on.

Bucket ``i`` counts values with ``int(v).bit_length() == i`` — i.e.
``[2**(i-1), 2**i)`` ns for ``i >= 1``; bucket 0 holds zeros. 64 buckets
cover everything a ns clock can produce. Quantiles interpolate linearly
inside the winning bucket, so they are estimates with at most 2x
resolution error — plenty for dashboards and regression gates.

The registry is a declared catalog (mirroring obs/gauges.CATALOG):
recording to an undeclared name raises, so Prometheus exposition
(obs/expose.py renders ``_bucket``/``_sum``/``_count`` families) can
never silently miss a series.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

N_BUCKETS = 64  # bit_length of a ns duration; 2**63 ns ≈ 292 years

# name -> help; names end in _ns (recorded in nanoseconds) and are
# exposed to Prometheus as <name minus _ns>_seconds histogram families.
CATALOG: "List[Tuple[str, str]]" = [
    ("query_wall_ns", "End-to-end query wall time (submit to finish)"),
    ("batch_op_ns", "Per-operator per-batch device compute time"),
    ("shuffle_fetch_ns", "Shuffle block fetch round-trip time"),
    ("retry_backoff_ns", "Time slept in OOM/fetch retry backoff"),
    ("plan_phase_ns",
     "Per-query planning time (rewrite/reuse/fusion/prefetch, or the "
     "plan-cache lookup on a memo hit)"),
    ("compile_phase_ns",
     "Per-query trace+compile time attributed by the jit first-call timer"),
    ("execute_phase_ns",
     "Per-query execute-window time (wall minus compile attribution)"),
    ("shuffle_write_ns",
     "Map-output write time (partition + serialize + spill, the PR-3 "
     "writeThreads path)"),
    ("serve_queue_wait_ns",
     "Serving queue wait: admission to executor pickup (per-tenant "
     "labeled family rides on this)"),
    ("serve_semaphore_wait_ns",
     "Serving task-semaphore wait before execution slots free up"),
    ("serve_deadline_slack_ns",
     "Deadline slack at completion (deadline minus finish; 0 when the "
     "deadline was already blown)"),
    ("net_stream_ns",
     "Result-stream window on the wire: RESULT_START through RESULT_END "
     "(per-tenant labeled family rides on this)"),
]

_enabled = True


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


class Histogram:
    """One log2-bucketed distribution; thread-safe."""

    __slots__ = ("name", "help", "_lock", "_counts", "_sum", "_count")

    def __init__(self, name: str, help_text: str = ""):
        self.name = name
        self.help = help_text
        self._lock = threading.Lock()
        self._counts = [0] * N_BUCKETS
        self._sum = 0
        self._count = 0

    def record(self, value_ns: int) -> None:
        v = int(value_ns)
        if v < 0:
            v = 0
        idx = min(v.bit_length(), N_BUCKETS - 1)
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> Dict:
        with self._lock:
            return {"counts": list(self._counts), "sum": self._sum,
                    "count": self._count}

    def reset(self) -> None:
        with self._lock:
            self._counts = [0] * N_BUCKETS
            self._sum = 0
            self._count = 0

    def percentile(self, q: float, snap: Optional[Dict] = None) -> float:
        """Estimated q-quantile in ns (linear within the winning bucket)."""
        s = snap or self.snapshot()
        total = s["count"]
        if total == 0:
            return 0.0
        rank = q * total
        seen = 0
        for i, c in enumerate(s["counts"]):
            if c == 0:
                continue
            if seen + c >= rank:
                lo = 0 if i == 0 else (1 << (i - 1))
                hi = 1 if i == 0 else (1 << i)
                frac = (rank - seen) / c
                return lo + frac * (hi - lo)
            seen += c
        return float(1 << (N_BUCKETS - 1))

    def percentiles_ms(self, snap: Optional[Dict] = None) -> Dict[str, float]:
        """p50/p95/p99 in milliseconds (the profile/bench surface)."""
        s = snap or self.snapshot()
        return {p: round(self.percentile(v, s) / 1e6, 3)
                for p, v in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))}


HISTOGRAMS: Dict[str, Histogram] = {
    name: Histogram(name, help_text) for name, help_text in CATALOG
}


def get(name: str) -> Histogram:
    try:
        return HISTOGRAMS[name]
    except KeyError:
        raise KeyError(f"histogram {name!r} is not declared in "
                       "obs/histo.CATALOG") from None


def record(name: str, value_ns: int) -> None:
    """Record into a declared histogram; no-op when histograms are off."""
    if _enabled:
        get(name).record(value_ns)


def snapshot_all() -> Dict[str, Dict]:
    return {name: h.snapshot() for name, h in HISTOGRAMS.items()}


def diff(start: Dict, end: Dict) -> Dict:
    """Window view: the distribution recorded between two snapshots (pass
    to ``Histogram.percentile``/``percentiles_ms`` for per-window tails)."""
    return {"counts": [e - s for s, e in zip(start["counts"], end["counts"])],
            "sum": end["sum"] - start["sum"],
            "count": end["count"] - start["count"]}


def percentiles(name: str) -> Dict[str, float]:
    return get(name).percentiles_ms()


# -- labeled families --------------------------------------------------------
#
# A labeled family is a declared base histogram plus per-label-set child
# histograms created on first record (the per-tenant SLO surface:
# serve_queue_wait_ns{tenant=...,priority=...}). Children share the base
# name — only declared names grow families — and every labeled record
# also lands in the base aggregate so unlabeled dashboards keep working.
# Cardinality is the caller's problem (serve/metrics.py caps tenants).

_family_lock = threading.Lock()
_FAMILIES: "Dict[str, Dict[Tuple[Tuple[str, str], ...], Histogram]]" = {}


def _label_key(labels: Dict[str, str]) -> "Tuple[Tuple[str, str], ...]":
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def record_labeled(name: str, value_ns: int, **labels) -> None:
    """Record into the base histogram AND its per-label child."""
    if not _enabled:
        return
    base = get(name)  # raises on undeclared names, same as record()
    base.record(value_ns)
    if not labels:
        return
    key = _label_key(labels)
    with _family_lock:
        fam = _FAMILIES.setdefault(name, {})
        child = fam.get(key)
        if child is None:
            child = fam[key] = Histogram(name, base.help)
    child.record(value_ns)


def family(name: str) -> "Dict[Tuple[Tuple[str, str], ...], Histogram]":
    """Live child histograms of a declared family (label-key -> Histogram)."""
    get(name)
    with _family_lock:
        return dict(_FAMILIES.get(name, {}))


def family_snapshot(name: str) -> "Dict[Tuple[Tuple[str, str], ...], Dict]":
    return {key: h.snapshot() for key, h in family(name).items()}


def reset_all() -> None:
    for h in HISTOGRAMS.values():
        h.reset()
    with _family_lock:
        _FAMILIES.clear()
