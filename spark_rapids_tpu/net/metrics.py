"""Network front-end counters (srtpu_net_* gauges).

Every name here is declared in obs/gauges.CATALOG (guarded by the
gauge-catalog lint pass); ``counters()`` feeds gauges.snapshot() the same
way serve/metrics.py and faults.counters() do. Counters are process
totals; ``net_connections_active`` / ``net_sessions_active`` are levels.
"""

from __future__ import annotations

import threading
from typing import Dict

_LOCK = threading.Lock()
_COUNTERS: Dict[str, int] = {
    "net_connections_total": 0,
    "net_connections_active": 0,
    "net_sessions_active": 0,
    "net_sessions_reaped_total": 0,
    "net_auth_fail_total": 0,
    "net_frames_rx_total": 0,
    "net_frames_tx_total": 0,
    "net_bytes_rx_total": 0,
    "net_bytes_tx_total": 0,
    "net_submit_total": 0,
    "net_submit_rejected_total": 0,
    "net_cancel_total": 0,
    "net_await_wake_ticket_total": 0,
    "net_await_wake_frame_total": 0,
    "net_await_wake_timeout_total": 0,
    "net_stream_batches_total": 0,
    "net_protocol_error_total": 0,
    "net_disconnect_cancel_total": 0,
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


def reset() -> None:
    with _LOCK:
        for k in _COUNTERS:
            _COUNTERS[k] = 0
