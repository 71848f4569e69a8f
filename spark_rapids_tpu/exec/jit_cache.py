"""Process-wide jit sharing across operator instances.

Operators bind per-instance ``@jax.jit`` closures; two instances of the
same operator with an IDENTICAL bound program (common: the TPC-DS tracker
re-plans every query, CTE reuse, both engines of a differential test)
would each re-trace and re-load the compiled executable from the
persistent cache, which dominates small-scale queries (the per-kernel
cost is not measured on the current machine).

``shared_jit(key, make)`` returns ONE jit per semantic key per process:
the key must capture everything that changes the traced program. Bound
expressions are keyed by ``Expression.cache_key()`` — NOT ``repr``, which
omits non-child literals (LIKE patterns, round scales, JSON paths) and
silently shared one program across distinct plans (VERDICT r5).

Hit/miss/size counters are exported as ``srtpu_jit_cache_*`` gauges
(obs/gauges.py) so fusion's compile amplification — more distinct stage
programs — is visible in the metrics endpoint.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict

_CACHE: Dict[tuple, Callable] = {}
_LOCK = threading.Lock()
_HITS = 0
_MISSES = 0
_COMPILE_NS = 0


def _timed_first_call(jfn: Callable, program: str) -> Callable:
    """jax.jit is lazy: trace+compile happens on the first invocation, not
    at jit() time. Time that first call and bank it as compile cost so
    QueryProfile can attribute compile-vs-execute (the first call also
    runs the first batch, so this is an upper bound — dominated by
    compilation for anything the disk cache misses). Under a trace it is
    also the ``query:compile`` span, a real interval; a query that binds
    no new program records none. Later calls pay one flag check."""
    state = {"first": True}

    def wrapper(*args, **kwargs):
        global _COMPILE_NS
        if state["first"]:
            from spark_rapids_tpu.obs import span as _span
            t0 = time.perf_counter_ns()
            with _span.task_span("query:compile",
                                 attrs={"program": program}):
                out = jfn(*args, **kwargs)
            dt = time.perf_counter_ns() - t0
            state["first"] = False
            with _LOCK:
                _COMPILE_NS += dt
            return out
        return jfn(*args, **kwargs)

    return wrapper


def shared_jit(key: tuple, make: Callable[[], Callable]) -> Callable:
    global _HITS, _MISSES
    fn = _CACHE.get(key)
    if fn is None:
        with _LOCK:
            fn = _CACHE.get(key)
            if fn is None:
                _MISSES += 1
                # jit_persist may serve the program from the on-disk
                # cross-process cache instead of tracing it; either way the
                # first call is timed as compile cost (a persisted load is
                # just a much cheaper "compile").
                from spark_rapids_tpu.exec import jit_persist
                fn = _CACHE[key] = _timed_first_call(
                    jit_persist.bind(key, make), str(key[0]))
                return fn
    _HITS += 1
    return fn


def compile_ns_total() -> int:
    """Lifetime ns spent in first calls of newly-traced programs."""
    return _COMPILE_NS


def cache_stats() -> Dict[str, int]:
    """Counters for obs/gauges.py: lifetime hits/misses and current size."""
    return {"jit_cache_hit_total": _HITS,
            "jit_cache_miss_total": _MISSES,
            "jit_compile_ns_total": _COMPILE_NS,
            "jit_cache_size": len(_CACHE)}


def reset_stats() -> None:
    """Zero the hit/miss counters (tests); compiled entries are kept."""
    global _HITS, _MISSES
    with _LOCK:
        _HITS = 0
        _MISSES = 0
