"""Device execution fence, and the one door for device->host reads.

Every honest wall-clock measurement (op-time metrics, chip_smoke.py's
walls) ends with a device->host readback of a value that depends on the
computation. The fence was written for a platform that no longer exists,
whose ``block_until_ready`` returned before execution finished; whether
``block_until_ready`` alone would do on the current machine (one TPU v5e
through ``chiprun``) is not measured, and a readback is a fence on any
backend.

``fence`` reads back ONE element per array — a few bytes of transfer, fully
ordered behind the producing computation, so the readback cannot complete
until the array's producer has executed. This is the engine's analog of the
reference's stream synchronize (Cuda.deviceSynchronize / stream sync points
that GpuMetric op-time semantics rely on, reference GpuExec.scala:41-178).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from spark_rapids_tpu.utils import tracing

_lock = threading.Lock()
_syncs = 0
_sync_ns = 0


def host_get(x: Any, site: str) -> Any:
    """``jax.device_get(x)``, seen: THE door for a blocking device->host
    read on the query path. The host thread waits here for everything the
    value depends on, then for the copy; while it waits it dispatches
    nothing, so each call is a candidate cut in the dispatch stream
    (ROADMAP S3). Every call

    - is an ``exec:host-sync`` span with ``site`` in its attrs when a trace
      context is active on this thread, and a plain ``TraceRange`` of that
      name otherwise (either way a profiler annotation, so an idle gap in
      the device trace can be laid against the sync that ended it);
    - bumps the always-on pair ``exec_host_sync_total`` /
      ``exec_host_sync_ns_total`` (obs/gauges.py): with tracing off, syncs
      per query is its rate over ``sched_completed_total``.

    ``site`` is a short constant naming the caller (``"shrink_to_live"``);
    ``tools/lint/span_catalog.py`` flags a bare ``jax.device_get`` under
    ``exec/`` that goes round this door."""
    global _syncs, _sync_ns
    from spark_rapids_tpu.obs import span as _span
    traced = _span.enabled() and _span.current() is not None
    sp = (_span.Span("exec:host-sync", attrs={"site": site}) if traced
          else tracing.TraceRange("exec:host-sync").open())
    t0 = sp.start_ns
    try:
        return jax.device_get(x)
    finally:
        # one reading, so span and counter agree. Host-only: device_get of
        # a tracer raises, so this can never be baked into a program
        end = time.perf_counter_ns()  # jit-purity: ok
        if traced:
            sp.finish(end_ns=end)
        else:
            sp.close(args={"site": site}, end_ns=end)
        with _lock:
            _syncs += 1
            _sync_ns += end - t0


def counters() -> Dict[str, int]:
    """For obs/gauges.snapshot()."""
    with _lock:
        return {"exec_host_sync_total": _syncs,
                "exec_host_sync_ns_total": _sync_ns}


def fence(*values: Any) -> None:
    """Force execution of every jax array in the given pytrees.

    Dispatches a 1-element slice of each array, then pulls ALL slices in a
    single ``jax.device_get`` — one round trip total, where per-array
    readbacks would pay one each (the cost of a readback is not measured
    on the current machine).
    """
    tiny = []
    for leaf in jax.tree_util.tree_leaves(values):
        if isinstance(leaf, jax.Array) and leaf.size:
            tiny.append(jnp.ravel(leaf)[:1])
    if tiny:
        host_get(tiny, "fence")
