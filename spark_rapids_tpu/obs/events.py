"""Structured, bounded, thread-safe query-lifecycle event journal.

The reference plugin surfaces lifecycle state through the Spark UI and
driver logs; standalone we keep a process-wide ring of structured events
(submit -> plan-rewrite -> reuse -> fusion -> compile -> execute ->
finish, plus spill / retry / fault-recovered / degraded / worker-stale)
that tests, ``tools/obs_report.py``, and humans can query or dump as
JSONL. The journal is always on: emission is one dict build plus a
deque append under a lock (bounded, oldest evicted) — per-event work is
per *query phase*, never per batch or per row.

Event shape: ``{"ts": epoch_s, "kind": str, ...fields}``; ``query_id``
and ``dur_ms`` are conventional fields, everything else is free-form
JSON-serializable context supplied by the emitter.
"""

from __future__ import annotations

import collections
import json
import threading
from time import time as _now
from typing import Deque, Dict, List, Optional

DEFAULT_CAPACITY = 4096

_lock = threading.Lock()
_events: "Deque[Dict]" = collections.deque(maxlen=DEFAULT_CAPACITY)
_enabled = True
_emitted = 0  # lifetime emissions (journal_events_total)
_evicted = 0  # bounded-ring drops (journal_evicted_total)


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


def set_capacity(n: int) -> None:
    """Rebound the ring (keeps the newest ``n`` events)."""
    global _events
    n = max(1, int(n))
    with _lock:
        if _events.maxlen != n:
            _events = collections.deque(_events, maxlen=n)


def capacity() -> int:
    return _events.maxlen or DEFAULT_CAPACITY


def emit(kind: str, query_id: Optional[int] = None, **fields) -> Optional[Dict]:
    """Append one event; returns it (or None when the journal is off)."""
    global _emitted, _evicted
    if not _enabled:
        return None
    ev: Dict = {"ts": _now(), "kind": kind}
    if query_id is not None:
        ev["query_id"] = query_id
    if fields:
        ev.update(fields)
    with _lock:
        _emitted += 1
        if len(_events) == _events.maxlen:
            _evicted += 1
        _events.append(ev)
    return ev


def recent(kind: Optional[str] = None, query_id: Optional[int] = None,
           limit: Optional[int] = None) -> List[Dict]:
    """Newest-last view, optionally filtered by kind and/or query."""
    with _lock:
        evs = list(_events)
    if kind is not None:
        evs = [e for e in evs if e["kind"] == kind]
    if query_id is not None:
        evs = [e for e in evs if e.get("query_id") == query_id]
    if limit is not None:
        evs = evs[-limit:]
    return evs


def clear() -> None:
    global _emitted, _evicted
    with _lock:
        _events.clear()
        _emitted = 0
        _evicted = 0


def counters() -> Dict[str, int]:
    """Lifetime counters for obs/gauges.py."""
    with _lock:
        return {"journal_events_total": _emitted,
                "journal_evicted_total": _evicted}


def dump_jsonl(path: str) -> str:
    """Write the current ring as one JSON object per line."""
    evs = recent()
    with open(path, "w") as f:
        for ev in evs:
            f.write(json.dumps(ev, default=str) + "\n")
    return path
