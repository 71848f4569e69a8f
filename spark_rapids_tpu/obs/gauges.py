"""Process-level gauge catalog: one place that knows how to read every
counter the runtime maintains.

Sources (the fragments the obs layer unifies):
- ``mem/pool.py``   HbmPool accounting (used/peak/allocs/OOMs/spill requests)
- ``mem/spill.py``  SpillFramework tiers (host bytes, spill/unspill counts)
- ``mem/semaphore.py`` TaskSemaphore wait totals
- ``shuffle/manager.py`` ShuffleManager bytes/blocks written
- ``io/filecache.py``   FileCache hit/miss counters

Instances are discovered through the same registries the leak sweeper uses
(mem/cleaner.py weaksets) plus the filecache/semaphore instance sets, and
summed across instances — the process view a scraper wants. ``snapshot()``
is also the QueryProfile's start/end capture, diffed per query.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

# name -> (kind, help); kind is "counter" (monotonic) or "gauge" (level).
# Counters diff meaningfully across a query window; gauges are reported as
# start/end levels.
CATALOG: "List[Tuple[str, str, str]]" = [
    ("pool_limit_bytes", "gauge", "HBM accounting pool budget"),
    ("pool_used_bytes", "gauge", "Accounted live device bytes"),
    ("pool_max_used_bytes", "gauge", "High-water mark of accounted bytes"),
    ("pool_alloc_total", "counter", "Pool allocation calls"),
    ("pool_oom_total", "counter", "Retryable OOMs thrown by the pool"),
    ("pool_spill_request_total", "counter",
     "Times the pool asked the spill framework for bytes"),
    ("spill_host_used_bytes", "gauge", "Host-tier bytes holding spilled batches"),
    ("spill_to_host_total", "counter", "Device->host spill events"),
    ("spill_to_disk_total", "counter", "Host->disk spill events"),
    ("spill_unspill_total", "counter", "Rematerializations of spilled batches"),
    ("spill_chunks_total", "counter",
     "Fixed-size spill chunks written (host or disk tier, docs/memory.md)"),
    ("spill_chunk_bytes_total", "counter",
     "Payload bytes written into spill chunks (post-codec)"),
    ("agg_repartition_total", "counter",
     "Oversized agg-state hash-repartition passes (docs/oversized_state.md)"),
    ("hashtbl_build_total", "counter",
     "Open-addressing device hash tables built (docs/kernels.md)"),
    ("hashtbl_probe_total", "counter",
     "Probe passes against a device hash table"),
    ("hashtbl_rehash_total", "counter",
     "Table builds that overflowed and retried with a new seed/capacity"),
    ("hashtbl_chunk_total", "counter",
     "Bounded gather chunks emitted by the chunked join gatherer"),
    ("hashtbl_pallas_fallback_total", "counter",
     "Pallas probe-kernel lowering failures that engaged the sticky "
     "XLA fallback (exec/kernels.py; reset by switching "
     "kernel.hashTable.pallasMode to 'on')"),
    ("sort_runs_total", "counter",
     "Sorted runs produced by the out-of-core sort (exec/sort.py)"),
    ("sort_merge_total", "counter",
     "Out-of-core merge sets combined by the merge-path device merge "
     "(searchsorted ranks, no re-sort — docs/kernels.md)"),
    ("sort_radix_total", "counter",
     "Sorts executed on the packed key-normalized (radix) encoding "
     "instead of the flat lexsort word chain"),
    ("window_scan_total", "counter",
     "Window batches computed by the segmented-scan engine "
     "(exec/window.py)"),
    ("window_loop_total", "counter",
     "Window batches that queried a sparse-table/RMQ path (per-row "
     "log-range gathers: First/Last, value-bounded or autotuned-rmq "
     "min/max frames)"),
    ("sortwin_pallas_fallback_total", "counter",
     "Pallas segmented-scan lowering failures that engaged the sticky "
     "XLA fallback (exec/kernels.py; reset by switching "
     "kernel.sortWindow.pallasMode to 'on')"),
    ("autotune_hit_total", "counter",
     "Dispatch decisions served from measured timings "
     "(plan/autotune.py, docs/adaptive_dispatch.md)"),
    ("autotune_miss_total", "counter",
     "Dispatch lookups that fell back to the static default path "
     "(no sample at the op's shape-class)"),
    ("autotune_store_total", "counter",
     "Timing samples merged into the persistent autotune store"),
    ("autotune_override_total", "counter",
     "Measured dispatch decisions that differed from the static "
     "default path (exploration or re-ranking)"),
    ("semaphore_wait_ns_total", "counter",
     "Nanoseconds tasks waited to enter the device"),
    ("semaphore_acquire_total", "counter", "Semaphore acquire calls"),
    ("semaphore_max_waiters", "gauge", "Peak simultaneous semaphore waiters"),
    ("shuffle_bytes_written_total", "counter", "Serialized shuffle bytes written"),
    ("shuffle_blocks_written_total", "counter", "Shuffle blocks written"),
    ("filecache_hit_total", "counter", "Filecache range hits"),
    ("filecache_miss_total", "counter", "Filecache range misses"),
    ("filecache_hit_bytes_total", "counter", "Bytes served from the filecache"),
    ("filecache_miss_bytes_total", "counter",
     "Bytes read through on filecache misses"),
    ("filecache_cached_bytes", "gauge", "Bytes currently held by filecaches"),
    ("jit_cache_hit_total", "counter", "shared_jit lookups served from cache"),
    ("jit_cache_miss_total", "counter",
     "shared_jit entries traced+compiled (distinct programs)"),
    ("jit_compile_ns_total", "counter",
     "Nanoseconds spent in first calls of newly-traced programs "
     "(compile-cost attribution for QueryProfile phases)"),
    ("jit_cache_size", "gauge", "Distinct jitted programs currently cached"),
    ("exec_host_sync_total", "counter",
     "Blocking device->host reads on the query path "
     "(utils/sync.host_get); syncs per query = its rate over "
     "sched_completed_total"),
    ("exec_host_sync_ns_total", "counter",
     "Nanoseconds host threads spent blocked in those reads "
     "(waiting for the device, then copying; not dispatching)"),
    ("fused_step_programs_total", "counter",
     "Distinct streaming-aggregate step programs bound by fused stages in "
     "this process (exec/fused.py: one per batch capacity and window "
     "length; it must not grow with a partition's batch count)"),
    ("fused_fallback_total", "counter",
     "Partitions a fused stage re-ran through the unfused operator chain "
     "(exec/fused.py _fall_back; one exec:fused-fallback span each); the "
     "three counters below split it by cause"),
    ("fused_fallback_join_refused_total", "counter",
     "Fallbacks because an absorbed join's build took a path whose probe "
     "is not traceable (duplicate keys, an empty build, an outer join)"),
    ("fused_fallback_carry_overflow_total", "counter",
     "Fallbacks because the streaming aggregate's groups (or their key "
     "bytes) outgrew the carry's capacity, the first batch's bucket"),
    ("fused_fallback_empty_total", "counter",
     "Fallbacks of partitions without a batch (classic empty-input "
     "semantics)"),
    ("join_build_path_total", "counter",
     "Hash-join build sides constructed (exec/join.py build_span; one "
     "exec:join-build span each); the four counters below split it by the "
     "probe structure the build ended in"),
    ("join_build_path_dense_total", "counter",
     "Build sides probed through the direct-address table (integer key "
     "domain under join.denseKey.maxDomain, unique keys)"),
    ("join_build_path_unique_total", "counter",
     "Build sides probed through the bucketed unique-key table"),
    ("join_build_path_ht_total", "counter",
     "Build sides probed through the open-addressing hash table"),
    ("join_build_path_sorted_total", "counter",
     "Build sides probed through the sorted-hash general path"),
    ("ingest_upload_ns_total", "counter",
     "Nanoseconds from the start of an in-memory table's first scan until "
     "its last batch was on the device: dictionary encoding and the "
     "host->device copies (plan/overrides._device_source_parts; waited for "
     "beside the plan, not in it; later scans reuse the device copy)"),
    ("jit_persist_hit_total", "counter",
     "Jitted programs reloaded from the on-disk cross-process cache "
     "(exec/jit_persist.py) instead of being re-traced"),
    ("jit_persist_miss_total", "counter",
     "Persistent-cache lookups that found no usable entry"),
    ("jit_persist_store_total", "counter",
     "Programs exported and written to the persistent cache"),
    ("jit_persist_bytes_total", "counter",
     "Serialized bytes written to the persistent cache"),
    ("jit_persist_error_total", "counter",
     "Corrupt/mismatched/unexportable entries handled by falling back to "
     "a fresh trace (never an error surfaced to the query)"),
    ("jit_persist_load_ns_total", "counter",
     "Nanoseconds spent deserializing persisted programs"),
    ("plan_cache_hit_total", "counter",
     "Queries whose whole rewrite pipeline was served by the plan memo "
     "(plan/plan_cache.py)"),
    ("plan_cache_miss_total", "counter",
     "Memoizable plans that ran the full rewrite pipeline and were stored"),
    ("plan_cache_evict_total", "counter",
     "Plan-memo entries evicted by the LRU cap"),
    ("plan_cache_uncacheable_total", "counter",
     "Plans refused by the memo (unfingerprintable node or expression)"),
    ("plan_cache_size", "gauge", "Memoized physical plans currently held"),
    ("prefetch_depth", "gauge",
     "Batches currently held ready in prefetch queues"),
    ("prefetch_stalls", "counter",
     "Consumer arrivals that found a prefetch queue empty"),
    ("prefetch_sheds", "counter",
     "Prefetch queues degraded to synchronous execution on RetryOOM"),
    ("fault_injected_total", "counter",
     "Faults fired by the injection registry (docs/fault_injection.md)"),
    ("fault_recovered_total", "counter",
     "Failures absorbed by a hardened path: OOM retry succeeded, corrupt "
     "block refetched clean, fetch retry connected, lost output recomputed"),
    ("fault_degraded_total", "counter",
     "Queries that gave up on the device and completed on the CPU engine"),
    ("reuse_exchanges_total", "counter",
     "Repeated shuffle-exchange subtrees collapsed to ReusedExchange"),
    ("reuse_broadcasts_total", "counter",
     "Repeated broadcast builds collapsed to ReusedBroadcast"),
    ("reuse_subqueries_total", "counter",
     "DPP/subquery filters deduped or repointed at a shared build"),
    ("reuse_bytes_saved_total", "counter",
     "Bytes a consumer replayed from a shared materialization instead of "
     "recomputing (docs/exchange_reuse.md)"),
    ("journal_events_total", "counter",
     "Lifecycle events emitted to the bounded journal (obs/events.py)"),
    ("journal_evicted_total", "counter",
     "Journal events evicted by the bounded ring"),
    ("worker_stale_total", "counter",
     "Workers flagged stalled by the health registry (no task progress)"),
    ("worker_lost_total", "counter",
     "Workers removed from the health registry as dead/lost"),
    ("mem_tracked_live_bytes", "gauge",
     "Attributed live pool bytes (obs/memtrack.py tags)"),
    ("mem_tracked_peak_bytes", "gauge",
     "High-water mark of attributed pool bytes"),
    ("mem_site_scan_upload_peak_bytes", "gauge",
     "Peak attributed bytes at the scan-upload site"),
    ("mem_site_shuffle_peak_bytes", "gauge",
     "Peak attributed bytes at the shuffle site"),
    ("mem_site_agg_state_peak_bytes", "gauge",
     "Peak attributed bytes at the agg-state site"),
    ("mem_site_broadcast_peak_bytes", "gauge",
     "Peak attributed bytes at the broadcast site"),
    ("mem_site_materialization_cache_peak_bytes", "gauge",
     "Peak attributed bytes held by the materialization cache"),
    ("mem_site_sort_spill_peak_bytes", "gauge",
     "Peak attributed bytes at the out-of-core sort site"),
    ("mem_site_other_peak_bytes", "gauge",
     "Peak attributed bytes with no declared site"),
    ("oom_postmortem_total", "counter",
     "OOM post-mortem snapshots written (docs/memory.md)"),
    ("mem_leaked_bytes_total", "counter",
     "Bytes still attributed to a query at its leak audit"),
    ("semaphore_timeout_total", "counter",
     "Semaphore waits abandoned at their timeout (deadline budget spent)"),
    ("semaphore_cancel_total", "counter",
     "Semaphore waits abandoned by the cancellation hook"),
    ("admission_submitted_total", "counter",
     "Queries submitted to the serving runtime (serve/server.py)"),
    ("admission_rejected_total", "counter",
     "Submissions shed with a typed AdmissionRejected"),
    ("admission_budget_exceeded_total", "counter",
     "Allocations refused for exceeding the query's admitted memory "
     "budget (mem/pool.py QueryBudgetExceeded)"),
    ("admission_queue_depth", "gauge",
     "Queries currently waiting to run in the serving queue"),
    ("admission_reserved_bytes", "gauge",
     "HBM bytes promised to admitted queries' memory budgets"),
    ("sched_completed_total", "counter",
     "Served queries that completed successfully"),
    ("sched_failed_total", "counter",
     "Served queries that failed with a non-lifecycle error"),
    ("sched_cancelled_total", "counter",
     "Served queries cancelled before completion"),
    ("sched_deadline_exceeded_total", "counter",
     "Served queries that ran past their deadline"),
    ("sched_singleflight_hit_total", "counter",
     "Submissions deduped onto an identical in-flight query"),
    ("sched_active_queries", "gauge",
     "Served queries currently executing"),
    ("sched_queue_wait_ns_total", "counter",
     "Total time served queries spent waiting in the admission queue"),
    ("admission_quota_rejected_total", "counter",
     "Submissions shed because the tenant hit its fair-share queue quota "
     "(serve.fairshare.*)"),
    ("admission_unsupported_plan_total", "counter",
     "Wire submissions shed at the lowering gate: the plan memo + type "
     "support matrix proved the plan will not lower (serve/lowering.py)"),
    ("net_connections_total", "counter",
     "TCP connections accepted by the network front-end (net/frontend.py)"),
    ("net_connections_active", "gauge",
     "Front-end connections currently open"),
    ("net_sessions_active", "gauge",
     "Authenticated tenant sessions currently live"),
    ("net_sessions_reaped_total", "counter",
     "Sessions closed by the idle reaper (net.session.idleTimeoutS)"),
    ("net_auth_fail_total", "counter",
     "AUTH frames rejected for an unknown token"),
    ("net_frames_rx_total", "counter",
     "Protocol frames received by the front-end"),
    ("net_frames_tx_total", "counter",
     "Protocol frames sent by the front-end"),
    ("net_bytes_rx_total", "counter",
     "Wire bytes received by the front-end (headers + payloads)"),
    ("net_bytes_tx_total", "counter",
     "Wire bytes sent by the front-end (headers + payloads)"),
    ("net_submit_total", "counter",
     "SUBMIT frames received (pre-gate, pre-admission)"),
    ("net_submit_rejected_total", "counter",
     "Wire submissions answered with a typed ERROR before execution"),
    ("net_cancel_total", "counter",
     "CANCEL frames honored by the front-end"),
    ("net_await_wake_ticket_total", "counter",
     "Waits of _await_result ended by a ticket's resolution (the wake "
     "channel): in a healthy run, one per submission that was admitted"),
    ("net_await_wake_frame_total", "counter",
     "Waits of _await_result ended by the client's socket: a CANCEL "
     "frame or a disconnect while the query was in flight"),
    ("net_await_wake_timeout_total", "counter",
     "Waits of _await_result ended by the backstop timeout; rising with "
     "the request count means the wake was lost and the poll is back"),
    ("net_stream_batches_total", "counter",
     "Arrow IPC record batches streamed to clients"),
    ("net_protocol_error_total", "counter",
     "Connections dropped for malformed/oversized/unexpected frames"),
    ("net_disconnect_cancel_total", "counter",
     "Queries cancelled because their client vanished mid-flight"),
    ("reuse_evict_total", "counter",
     "Materialization-cache entries evicted by the retention scorer "
     "(exec/reuse.py)"),
    ("reuse_evict_bytes_total", "counter",
     "Bytes freed by materialization-cache eviction"),
    ("reuse_evict_skipped_active_total", "counter",
     "Eviction candidates skipped because a reader was replaying them"),
]


def snapshot() -> Dict[str, int]:
    """Current value of every catalog gauge, summed over live instances
    (max for high-water marks)."""
    from spark_rapids_tpu.io import filecache as _fc
    from spark_rapids_tpu.mem import cleaner as _cleaner
    from spark_rapids_tpu.mem import semaphore as _sem

    out = {name: 0 for name, _, _ in CATALOG}
    with _cleaner._lock:
        pools = list(_cleaner._pools)
        fws = list(_cleaner._frameworks)
        managers = list(_cleaner._managers)
    for p in pools:
        out["pool_limit_bytes"] += p.limit
        out["pool_used_bytes"] += p.used
        out["pool_max_used_bytes"] = max(out["pool_max_used_bytes"],
                                         p.max_used)
        out["pool_alloc_total"] += p.alloc_count
        out["pool_oom_total"] += p.oom_count
        out["pool_spill_request_total"] += p.spill_request_count
    for fw in fws:
        out["spill_host_used_bytes"] += fw.host_used
        out["spill_to_host_total"] += fw.spilled_to_host_count
        out["spill_to_disk_total"] += fw.spilled_to_disk_count
        out["spill_unspill_total"] += fw.unspilled_count
        out["spill_chunks_total"] += fw.chunks_written_count
        out["spill_chunk_bytes_total"] += fw.chunk_bytes_written
    for sem in _sem.instances():
        out["semaphore_wait_ns_total"] += sem.total_wait_ns
        out["semaphore_acquire_total"] += sem.acquire_count
        out["semaphore_max_waiters"] = max(out["semaphore_max_waiters"],
                                           sem.max_waiters)
        out["semaphore_timeout_total"] += sem.timeout_count
        out["semaphore_cancel_total"] += sem.cancel_count
    for m in managers:
        out["shuffle_bytes_written_total"] += m.bytes_written
        out["shuffle_blocks_written_total"] += m.blocks_written
    for fc in _fc.instances():
        out["filecache_hit_total"] += fc.hits
        out["filecache_miss_total"] += fc.misses
        out["filecache_hit_bytes_total"] += fc.hit_bytes
        out["filecache_miss_bytes_total"] += fc.miss_bytes
        out["filecache_cached_bytes"] += fc.cached_bytes
    from spark_rapids_tpu.exec import jit_cache as _jc
    out.update(_jc.cache_stats())
    from spark_rapids_tpu.utils import sync as _sync
    out.update(_sync.counters())
    from spark_rapids_tpu.exec import jit_persist as _jp
    out.update(_jp.counters())
    from spark_rapids_tpu.plan import plan_cache as _pc
    out.update(_pc.counters())
    from spark_rapids_tpu.exec import pipeline as _pl
    out.update(_pl.STATS.snapshot())
    from spark_rapids_tpu import faults as _faults
    out.update(_faults.counters())
    from spark_rapids_tpu.exec import reuse as _reuse
    out.update(_reuse.counters())
    from spark_rapids_tpu.obs import events as _ev
    out.update(_ev.counters())
    from spark_rapids_tpu.obs import health as _health
    out.update(_health.counters())
    from spark_rapids_tpu.obs import memtrack as _mt
    out.update(_mt.counters())
    from spark_rapids_tpu.exec import aggregate as _agg
    out.update(_agg.counters())
    from spark_rapids_tpu.exec import fused as _fused
    out.update(_fused.counters())
    from spark_rapids_tpu.exec import join as _join
    out.update(_join.counters())
    from spark_rapids_tpu.plan import overrides as _ov
    out.update(_ov.upload_counters())
    from spark_rapids_tpu.exec import kernels as _k
    out.update(_k.counters())
    from spark_rapids_tpu.serve import metrics as _serve_m
    out.update(_serve_m.counters())
    from spark_rapids_tpu.plan import autotune as _at
    out.update(_at.counters())
    from spark_rapids_tpu.net import metrics as _net_m
    out.update(_net_m.counters())
    return out


def diff(start: Dict[str, int], end: Dict[str, int]) -> Dict[str, Dict]:
    """Per-query window view: counters as deltas, gauges as start/end."""
    out: Dict[str, Dict] = {}
    for name, kind, _ in CATALOG:
        s, e = start.get(name, 0), end.get(name, 0)
        if kind == "counter":
            out[name] = {"delta": e - s}
        else:
            out[name] = {"start": s, "end": e}
    return out
