"""Tracing and profiling.

Reference (SURVEY.md §5): NVTX ranges everywhere (NvtxRange /
NvtxWithMetrics, docs/dev/nvtx_profiling.md) feeding nsys timelines, plus a
driver-coordinated async profiler (profiler.scala) writing traces to a
directory. TPU-native mapping: jax.profiler — TraceAnnotation is the NVTX
range analog (shows up on the XPlane/TensorBoard timeline), start_trace/
stop_trace the capture window. A lightweight in-process event log rides
along so tests and metrics can observe ranges without a trace viewer; the
obs/ layer exports it as a Chrome trace_event file (obs/trace_export.py).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax

# One lock guards BOTH the event list and the capture flag: a range that
# observes the flag appends under the same critical section, so a capture
# window can never tear (flag off, event still appended) and back-to-back
# windows cannot interleave stale events. Reentrant: a garbage collection
# runs its callbacks between two bytecodes of whatever thread it stopped,
# so obs/span's host:gc event can be recorded inside this critical section.
_events_lock = threading.RLock()
_events: List[Dict] = []
_capture_events = False
# Worker/process identity stamped onto every recorded event ("exec-0",
# "mesh", ...). None in the driver: the merged-trace exporter labels the
# driver's own pid, so only subordinate processes pay the extra field.
_process_label: Optional[str] = None


def set_process_label(label: Optional[str]) -> None:
    global _process_label
    _process_label = label


def process_label() -> Optional[str]:
    return _process_label


def trace_events(clear: bool = False) -> List[Dict]:
    """Recorded {name, start_ns, dur_ns, thread} events (when capturing)."""
    with _events_lock:
        out = list(_events)
        if clear:
            _events.clear()
        return out


def capturing() -> bool:
    """Whether a capture window is open. A plain read of the flag: callers
    use it to skip building what ``record_event`` would drop, and
    ``record_event`` itself looks again under the lock."""
    return _capture_events


def set_capture(enabled: bool, clear: bool = False) -> None:
    """Turn the in-process event log on/off; ``clear`` drops any events left
    over from a previous window so windows never mix."""
    global _capture_events
    with _events_lock:
        if clear:
            _events.clear()
        _capture_events = bool(enabled)


def record_event(name: str, start_ns: int, dur_ns: int,
                 args: Optional[Dict] = None) -> None:
    """Append one event if a capture window is open (span-shaped; the
    Chrome exporter renders it as a 'ph: X' complete event). The flag is
    read before the lock is taken, so with capture off this costs one
    global read and no lock; it is read again under the lock, so a window
    still never tears."""
    if not _capture_events:
        return
    with _events_lock:
        if not _capture_events:
            return
        ev = {
            "name": name,
            "start_ns": start_ns,
            "dur_ns": dur_ns,
            "thread": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        if _process_label is not None:
            a = ev.get("args")
            ev["args"] = dict(a) if a else {}
            ev["args"].setdefault("worker", _process_label)
        _events.append(ev)


def record_counter(name: str, values: Dict,
                   ts_ns: Optional[int] = None) -> None:
    """Append one counter sample if a capture window is open (the Chrome
    exporter renders it as a 'ph: C' counter track — obs/memtrack.py uses
    this for memory watermark timelines)."""
    if not _capture_events:
        return
    with _events_lock:
        if not _capture_events:
            return
        ev = {
            "name": name,
            "start_ns": ts_ns if ts_ns is not None
            else time.perf_counter_ns(),
            "dur_ns": 0,
            "thread": threading.get_ident(),
            "counter": True,
            "args": {k: v for k, v in values.items()},
        }
        if _process_label is not None:
            ev["args"].setdefault("worker", _process_label)
        _events.append(ev)


class TraceRange:
    """NvtxRange analog and the one primitive of "event + annotation": a
    ``jax.profiler.TraceAnnotation`` of the same name for the range's life
    (so a running jax profiler writes it to the ``.xplane.pb`` host plane,
    on the device trace's own clock) and, at close, one event in the
    in-process log when a capture window is open. ``obs/span.Span`` (which
    adds the thread's CPU time under capture) and the per-batch operator
    event of ``exec/base.py`` are both built on it.
    With no profiler running and capture off a range costs the native
    TraceMe's flag check and two clock reads: no lock."""

    __slots__ = ("name", "start_ns", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.start_ns = 0
        self._ann = None

    def open(self) -> "TraceRange":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def close(self, args: Optional[Dict] = None,
              end_ns: Optional[int] = None, record: bool = True) -> None:
        """End the annotation and (unless ``record`` is False) log the
        event; ``args`` may be built late, from what the range learned."""
        end = end_ns if end_ns is not None else time.perf_counter_ns()
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        if record:
            record_event(self.name, self.start_ns,
                         max(0, end - self.start_ns), args=args)

    def __enter__(self):
        return self.open()

    def __exit__(self, *exc):
        self.close()
        return False


class Profiler:
    """Capture-window profiler (profiler.scala analog): start/stop writes a
    jax profiler trace (XPlane, TensorBoard-viewable) to ``out_dir`` and
    turns on the in-process event log for the window. Each window starts
    from an EMPTY event log, so consecutive windows observe only their own
    ranges."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self._active = False

    def start(self):
        if self._active:
            return
        try:
            jax.profiler.start_trace(self.out_dir)
        except Exception:
            pass  # tracing unavailable in some environments; events still on
        set_capture(True, clear=True)
        self._active = True

    def stop(self):
        if not self._active:
            return
        set_capture(False)
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._active = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
