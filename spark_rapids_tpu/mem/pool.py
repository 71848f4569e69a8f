"""HBM accounting pool with retryable OOM and test fault injection.

Reference: GpuDeviceManager.scala (RMM pool init, :152-501),
DeviceMemoryEventHandler.scala:37 (alloc-failure -> spill -> retry
escalation), RmmSpark OOM injection (jni; used by tests via
forceRetryOOM/forceSplitAndRetryOOM and RapidsConf.scala:2753 OomInjectionConf).

TPU design: XLA owns physical HBM; this pool tracks the *framework's logical
footprint* (live accounted batches). `allocate` is called by batch-holding
code (SpillableBatch registration, operator scratch reservations). On budget
exhaustion it first asks the spill framework to free accounted bytes
(device->host->disk cascade), then throws `RetryOOM` — recoverable by design
via mem.retry, exactly like the reference's GpuRetryOOM path.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Tuple

from spark_rapids_tpu import faults


class RetryOOM(RuntimeError):
    """Allocation failed but may succeed after spilling/rolling back and
    retrying the same inputs (reference: GpuRetryOOM)."""


class SplitAndRetryOOM(RuntimeError):
    """Allocation failed and the input must be split before retrying
    (reference: GpuSplitAndRetryOOM)."""


class CpuRetryOOM(RetryOOM):
    """Host-memory flavor (reference: CpuRetryOOM)."""


class QueryBudgetExceeded(RuntimeError):
    """An allocation would push a query past its admitted memory budget
    (spark.rapids.tpu.serve.*). Deliberately NOT a RetryOOM: spilling and
    retrying cannot shrink the query's own live footprint, so the typed
    error propagates to the submitter instead of spinning the retry loop
    (faults/blacklist.py classifies unknown errors as RAISE)."""

    def __init__(self, query_id, nbytes: int, live: int, budget: int):
        super().__init__(
            f"query {query_id} over its memory budget: allocating {nbytes} "
            f"with {live} live attributed bytes against a budget of "
            f"{budget}")
        self.query_id = query_id


class OomInjector:
    """Deterministic OOM injection for tests (RmmSpark.forceRetryOOM analog):
    after `skip` allocations, throw `count` OOMs of the given kind.

    Kept for API back-compat; new code should install the general schedule
    via ``spark.rapids.tpu.test.faults`` (mem.alloc site, faults/registry.py).
    Schedule state is lock-guarded: the parallel shuffle map writers drive
    concurrent allocations, and unlocked skip/count decrements could fire
    the injection zero or multiple times.
    """

    def __init__(self, kind: str = "RETRY", skip: int = 0, count: int = 1):
        assert kind in ("RETRY", "SPLIT")
        self.kind = kind
        self.skip = skip
        self.count = count
        self._lock = threading.Lock()

    def on_alloc(self) -> None:
        with self._lock:
            if self.skip > 0:
                self.skip -= 1
                return
            if self.count <= 0:
                return
            self.count -= 1
            kind = self.kind
        faults.note_injected("mem.alloc")
        if kind == "RETRY":
            raise RetryOOM("injected retry OOM")
        raise SplitAndRetryOOM("injected split-and-retry OOM")


class HbmPool:
    """Thread-safe logical HBM accounting.

    ``spill_fn(bytes_needed) -> bytes_freed`` is installed by the
    SpillFramework; the pool escalates: spill -> synchronize -> RetryOOM
    (mirroring OOMRetryState escalation in DeviceMemoryEventHandler:53-105).
    """

    def __init__(self, limit_bytes: int, limit_source: str = "caller"):
        from spark_rapids_tpu.mem import cleaner
        cleaner.register_pool(self)
        self.limit = int(limit_bytes)
        self.limit_source = limit_source  # who chose the limit (reports)
        self._used = 0
        self._lock = threading.Lock()
        self._spill_fn: Optional[Callable[[int], int]] = None
        self._injector: Optional[OomInjector] = None
        # watermarks (GpuTaskMetrics maxDeviceMemoryBytes analog)
        self.max_used = 0
        self.alloc_count = 0
        self.oom_count = 0
        self.spill_request_count = 0
        # query_id -> admitted budget in bytes (serve/admission.py promises,
        # this map enforces; empty when no serving runtime is active)
        self._query_budgets: Dict[object, int] = {}

    # -- wiring ------------------------------------------------------------
    def set_spill_fn(self, fn: Optional[Callable[[int], int]]) -> None:
        self._spill_fn = fn

    def set_injector(self, injector: Optional[OomInjector]) -> None:
        self._injector = injector

    def set_query_budget(self, query_id, nbytes: int) -> None:
        """Cap ``query_id``'s live attributed bytes (0/None clears). Set by
        plan/dataframe.py when the active QueryContext carries a budget."""
        with self._lock:
            if nbytes:
                self._query_budgets[query_id] = int(nbytes)
            else:
                self._query_budgets.pop(query_id, None)

    def clear_query_budget(self, query_id) -> None:
        with self._lock:
            self._query_budgets.pop(query_id, None)

    # -- accounting --------------------------------------------------------
    @property
    def used(self) -> int:
        return self._used

    @property
    def free(self) -> int:
        return self.limit - self._used

    def allocate(self, nbytes: int, tag=None):
        """Account nbytes; spill then raise RetryOOM if over budget.

        Returns the attribution tag memtrack resolved for this allocation
        (None when tracking is off) — holders of long-lived accounted state
        (SpillableBatch, prefetch queue entries) store it and hand it back
        to ``release`` so frees attribute to the allocating operator even
        when they happen on another thread.
        """
        # injection site, outside the pool lock so slow/stall rules cannot
        # serialize unrelated allocators
        from spark_rapids_tpu.obs import memtrack as _mt
        faults.check("mem.alloc", nbytes=nbytes)
        if self._query_budgets:  # serving runtime active: per-query caps
            qid = tag[0] if isinstance(tag, tuple) else _mt.current_query()
            budget = self._query_budgets.get(qid)
            if budget:
                live = _mt.query_live(qid)
                if live + nbytes > budget:
                    from spark_rapids_tpu.serve import metrics as _sm
                    _sm.bump("admission_budget_exceeded_total")
                    raise QueryBudgetExceeded(qid, nbytes, live, budget)
        with self._lock:
            self.alloc_count += 1
            if self._injector is not None:
                self._injector.on_alloc()
            fits = self._used + nbytes <= self.limit
            if fits:
                self._used += nbytes
                self.max_used = max(self.max_used, self._used)
            else:
                needed = self._used + nbytes - self.limit
        if fits:  # attribution outside the pool lock (memtrack has its own)
            return _mt.on_alloc(nbytes, tag)
        # spill outside the lock (spill does host/disk I/O)
        freed = 0
        if self._spill_fn is not None:
            self.spill_request_count += 1
            freed = self._spill_fn(needed)
        with self._lock:
            fits = self._used + nbytes <= self.limit
            if fits:
                self._used += nbytes
                self.max_used = max(self.max_used, self._used)
            else:
                self.oom_count += 1
                from spark_rapids_tpu.utils import task_metrics as TM
                TM.add("oom_count", 1)
        if fits:
            return _mt.on_alloc(nbytes, tag)
        # ranked post-mortem snapshot, rate-limited to one per query (the
        # RetryOOM below is recoverable by design — mem/retry.py)
        _mt.on_pool_denied(nbytes, pool=self, freed=freed)
        raise RetryOOM(
            f"HBM pool exhausted: need {nbytes}, used {self._used}, "
            f"limit {self.limit}, spill freed {freed}")

    def release(self, nbytes: int, tag=None) -> None:
        with self._lock:
            self._used -= nbytes
            assert self._used >= 0, "pool accounting underflow"
        from spark_rapids_tpu.obs import memtrack as _mt
        _mt.on_free(nbytes, tag)


_default_pool: Optional[HbmPool] = None
_pool_lock = threading.Lock()


def _detect_hbm_bytes() -> Tuple[int, str]:
    """(per-chip HBM bytes, where the figure came from). On a TPU the
    device is asked and the answer is required: a size assumed for a chip
    that did not give one would mis-size every admission and spill
    decision. Other backends (the CPU test lane reports no limit) keep a
    16 GiB stand-in."""
    import jax

    d = jax.devices()[0]
    if d.platform == "tpu":
        return (int(d.memory_stats()["bytes_limit"]),
                "memory_stats.bytes_limit")
    return 16 << 30, f"default-16GiB({d.platform})"


def get_pool(conf=None) -> HbmPool:
    """Process-wide pool; sized from ``conf`` on first call (startup-only,
    like spark.rapids.memory.gpu.allocFraction in the reference)."""
    global _default_pool
    with _pool_lock:
        if _default_pool is None:
            from spark_rapids_tpu.config import conf as C

            if conf is None:
                conf = C.RapidsConf()
            max_bytes = C.HBM_POOL_BYTES.get(conf)
            if max_bytes:
                limit, source = int(max_bytes), C.HBM_POOL_BYTES.key
            else:
                hbm, source = _detect_hbm_bytes()
                limit = int(hbm * C.HBM_POOL_FRACTION.get(conf))
            _default_pool = HbmPool(limit, source)
        return _default_pool


def set_pool(pool: Optional[HbmPool]) -> None:
    global _default_pool
    with _pool_lock:
        _default_pool = pool
