"""Counters the harness reads beside the program: what JAX reports of
compilation, and the program's own evidence that an answer did not come
from the chip. Copied from chip_smoke.py (PR 26), which stays the smoke."""

import time

# a device failure, or a kernel the compiler refused, that the engine
# absorbed: any of these means the answer may not have come from the chip
FORBIDDEN_COUNTERS = ("fault_degraded_total", "hashtbl_pallas_fallback_total",
                      "sortwin_pallas_fallback_total",
                      "jit_persist_error_total")
FORBIDDEN_EVENTS = ("query-retry", "degraded-to-cpu", "degraded",
                    "pallas-fallback")


class CompileMeter:
    """Counts what JAX itself reports through jax.monitoring: programs
    handed to the backend compiler with the seconds that took and the time
    each ended, and persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiled_at = []  # perf_counter at the end of each compile
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled_at.append(time.perf_counter())
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def programs_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in list(self.compiled_at) if t0 <= t <= t1)

    def read(self) -> dict:
        return {"programs": len(self.compiled_at), "compile_s": self.seconds,
                "xla_cache_hits": self.cache_hits,
                "xla_cache_misses": self.cache_misses}


def hidden_device_evidence() -> dict:
    """Non-zero counters and journal events that say the engine left the
    device, or a kernel, behind; and an evicted journal, which could have
    hidden such an event."""
    from spark_rapids_tpu.obs import events, gauges
    snap = gauges.snapshot()
    bad = {k: snap[k] for k in FORBIDDEN_COUNTERS if snap[k]}
    for kind in FORBIDDEN_EVENTS:
        n = len(events.recent(kind=kind))
        if n:
            bad[f"event:{kind}"] = n
    if snap["journal_evicted_total"]:
        bad["journal_evicted_total"] = snap["journal_evicted_total"]
    return bad


def store_counters() -> dict:
    """The program's own counters of its two stores and of single-flight
    sharing, for the run's notes: what the set-up found there."""
    from spark_rapids_tpu.obs import gauges
    snap = gauges.snapshot()
    return {k: v for k, v in snap.items()
            if k.startswith(("jit_persist_", "autotune_", "jit_cache_",
                             "sched_singleflight_"))}
