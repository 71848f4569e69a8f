"""Typed configuration system with self-documenting registry.

Re-designs the reference's ``RapidsConf`` typed-builder DSL (reference:
sql-plugin/.../RapidsConf.scala:122-328, 3419 LoC, 251 entries) for the TPU
framework: every knob is a declared, typed ``ConfEntry`` with a doc string;
``generate_docs()`` renders docs/configs.md the same way RapidsConf.scala:2548
generates the reference's configs.md.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional


_REGISTRY: "Dict[str, ConfEntry]" = {}
_REG_LOCK = threading.Lock()


@dataclasses.dataclass(frozen=True)
class ConfEntry:
    key: str
    default: Any
    doc: str
    conv: Callable[[str], Any]
    internal: bool = False
    startup_only: bool = False
    check: Optional[Callable[[Any], Optional[str]]] = None

    def get(self, conf: "RapidsConf"):
        return conf.get(self.key)


def _to_bool(s):
    if isinstance(s, bool):
        return s
    return str(s).strip().lower() in ("true", "1", "yes")


def _register(entry: ConfEntry) -> ConfEntry:
    with _REG_LOCK:
        if entry.key in _REGISTRY:
            raise ValueError(f"duplicate conf key {entry.key}")
        _REGISTRY[entry.key] = entry
    return entry


def conf(key: str, *, default, doc: str, internal: bool = False,
         startup_only: bool = False, check=None) -> ConfEntry:
    """Declare a config entry. Type is inferred from the default."""
    if isinstance(default, bool):
        conv: Callable[[str], Any] = _to_bool
    elif isinstance(default, int):
        conv = int
    elif isinstance(default, float):
        conv = float
    else:
        conv = str
    return _register(ConfEntry(key, default, doc, conv, internal, startup_only, check))


# ---------------------------------------------------------------------------
# Entries (mirroring the major spark.rapids.* groups; RapidsConf.scala:320+)
# ---------------------------------------------------------------------------

SQL_ENABLED = conf(
    "spark.rapids.tpu.sql.enabled", default=True,
    doc="Enable plan rewrite onto TPU operators. When false all operators run "
        "on the CPU fallback engine.")

EXPLAIN = conf(
    "spark.rapids.tpu.sql.explain", default="NONE",
    doc="Explain why parts of a plan did or did not run on TPU: NONE, "
        "NOT_ON_TPU, ALL. (reference: spark.rapids.sql.explain)")

CONCURRENT_TASKS = conf(
    "spark.rapids.tpu.sql.concurrentTpuTasks", default=2,
    doc="Number of tasks that may hold the TPU concurrently "
        "(reference: spark.rapids.sql.concurrentGpuTasks / GpuSemaphore).")

HBM_POOL_FRACTION = conf(
    "spark.rapids.tpu.memory.pool.fraction", default=0.85,
    doc="Fraction of per-chip HBM the framework pool may account for before "
        "allocations start throwing retryable OOM "
        "(reference: spark.rapids.memory.gpu.allocFraction).")

HBM_POOL_BYTES = conf(
    "spark.rapids.tpu.memory.pool.maxBytes", default=0,
    doc="Absolute cap in bytes for the HBM accounting pool; 0 = derive from "
        "fraction * detected HBM.", startup_only=True)

HOST_SPILL_LIMIT = conf(
    "spark.rapids.tpu.memory.host.spillStorageSize", default=8 << 30,
    doc="Bytes of host memory to use for spilled device buffers before "
        "cascading to disk (reference: spark.rapids.memory.host.spillStorageSize).")

SPILL_DIR = conf(
    "spark.rapids.tpu.memory.spillDir", default="/tmp/srtpu_spill",
    doc="Directory for disk-tier spill files.")

SPILL_CHUNK_BYTES = conf(
    "spark.rapids.tpu.memory.spill.chunkBytes", default=8 << 20,
    doc="Fixed chunk size for spilled batches. A batch is serialized into "
        "CRC-guarded chunks of this size so host/disk tiers move bounded "
        "pieces through a small reusable bounce buffer instead of "
        "whole-batch copies, and unspill can stream one chunk at a time "
        "(reference: GpuDeviceManager bounce buffer pools).",
    check=lambda v: None if v >= 4096 else "must be >= 4096")

SPILL_CODEC = conf(
    "spark.rapids.tpu.memory.spill.codec", default="none",
    doc="Compression codec applied per spill chunk: none, zlib, lz4, zstd. "
        "lz4/zstd need their python modules importable; selecting a missing "
        "codec fails fast at spill-framework construction "
        "(reference: spark.rapids.shuffle.compression.codec).",
    check=lambda v: None if v in ("none", "zlib", "lz4", "zstd")
    else "must be one of none, zlib, lz4, zstd")

AGG_REPARTITION_ENABLED = conf(
    "spark.rapids.tpu.sql.agg.repartition.enabled", default=True,
    doc="When hash-aggregate merge state outgrows the target (or a "
        "retryable OOM fires mid-merge), recursively hash-repartition the "
        "partial buffers into buckets and aggregate each bucket "
        "independently instead of split-retrying the input "
        "(reference: GpuAggregateExec repartition-based fallback).")

AGG_REPARTITION_TARGET_BYTES = conf(
    "spark.rapids.tpu.sql.agg.repartition.targetBytes", default=0,
    doc="Merge-state byte threshold that triggers the aggregate "
        "repartition fallback; 0 derives a quarter of the HBM pool budget.",
    check=lambda v: None if v >= 0 else "must be >= 0")

AGG_REPARTITION_NUM_BUCKETS = conf(
    "spark.rapids.tpu.sql.agg.repartition.numBuckets", default=16,
    doc="Hash buckets per repartition level; each level re-seeds the bucket "
        "hash so a skewed bucket re-splits on a different boundary "
        "(reference: GpuAggregateExec.scala hashSeed + 7).",
    check=lambda v: None if v >= 2 else "must be >= 2")

AGG_REPARTITION_MAX_DEPTH = conf(
    "spark.rapids.tpu.sql.agg.repartition.maxDepth", default=3,
    doc="Maximum recursion depth for aggregate hash-repartition; past it "
        "the engine falls back to split-retry as the last resort.",
    check=lambda v: None if v >= 1 else "must be >= 1")

# -- fault injection & resilience (docs/fault_injection.md) -----------------

TEST_FAULTS = conf(
    "spark.rapids.tpu.test.faults", default="",
    doc="Fault-injection schedule: 'site:action@k=v,...;site:action@...' "
        "(e.g. 'mem.alloc:retry@skip=3;shuffle.fetch:drop@p=0.1,seed=42'). "
        "Sites: mem.alloc, mem.spill, io.decode, shuffle.serialize, "
        "shuffle.fetch, shuffle.block, parallel.exchange, executor, "
        "agg.repartition, serve.admit, serve.cancel. Actions: retry, split, "
        "drop, error, corrupt, slow, stall, kill. Empty = injection off, "
        "zero overhead. Generalizes the reference's OomInjectionConf "
        "(RapidsConf.scala:2753) to every layer; see docs/fault_injection.md.",
    internal=True)

SHUFFLE_INTEGRITY = conf(
    "spark.rapids.tpu.shuffle.integrity.enabled", default=True,
    doc="Append a per-block CRC trailer (CRC32C when available, else CRC-32) "
        "to serialized shuffle blocks and verify it on read. A mismatch "
        "triggers refetch from the source, then recompute of the map output "
        "if the source itself is corrupt.")

SHUFFLE_FETCH_MAX_ATTEMPTS = conf(
    "spark.rapids.tpu.shuffle.fetch.maxAttempts", default=4,
    doc="Attempts per remote shuffle fetch before the failure propagates "
        "(first try + retries). Retried on timeout/connection errors with "
        "exponential backoff and jitter.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SHUFFLE_FETCH_BACKOFF_MS = conf(
    "spark.rapids.tpu.shuffle.fetch.retryBackoffMs", default=50.0,
    doc="Base backoff between shuffle fetch retries; doubles per attempt "
        "with +/-50% jitter to avoid thundering-herd refetch.")

SHUFFLE_FETCH_DEADLINE_S = conf(
    "spark.rapids.tpu.shuffle.fetch.deadlineSeconds", default=120.0,
    doc="Overall wall-clock deadline across all attempts of one shuffle "
        "fetch, bounding worst-case stall regardless of maxAttempts.")

RETRY_BACKOFF_MS = conf(
    "spark.rapids.tpu.memory.retry.backoffMs", default=0.0,
    doc="Optional base backoff between OOM retry attempts in with_retry "
        "(exponential, jittered, capped at 32x base). 0 = retry immediately "
        "(reference behavior: RmmRapidsRetryIterator blocks on the state "
        "machine instead).")

FAULT_BLACKLIST_ENABLED = conf(
    "spark.rapids.tpu.fault.deviceBlacklist.enabled", default=True,
    doc="After repeated device failures of the same plan, blacklist it and "
        "degrade execution to the CPU engine (graceful degradation; the "
        "reference instead hard-exits the executor, Plugin.scala:560).")

FAULT_BLACKLIST_THRESHOLD = conf(
    "spark.rapids.tpu.fault.deviceBlacklist.threshold", default=3,
    doc="Device failures of one plan tolerated before it is blacklisted to "
        "the CPU engine. Escaped retryable OOMs get the same number of "
        "whole-query retries but never degrade.",
    check=lambda v: None if v >= 1 else "must be >= 1")

METRICS_LEVEL = conf(
    "spark.rapids.tpu.sql.metrics.level", default="MODERATE",
    doc="Operator metrics verbosity: ESSENTIAL, MODERATE, DEBUG "
        "(reference: GpuExec.scala:41 metrics levels). Metrics above the "
        "level are not collected (docs/observability.md metric catalog).")

METRICS_SYNC = conf(
    "spark.rapids.tpu.sql.metrics.sync", default=False,
    doc="Fence device execution at every operator batch boundary so opTime "
        "metrics measure real execution instead of async dispatch. Adds one "
        "tiny device->host readback per batch per operator; enable for "
        "profiling, not throughput runs. (The real-TPU platform's "
        "block_until_ready returns at dispatch; only a dependent host "
        "readback drains compute — utils/sync.py.) See "
        "docs/observability.md.")

PROFILE_ENABLED = conf(
    "spark.rapids.tpu.profile.enabled", default=True,
    doc="Install a QueryProfile per planned query: operator metrics, task "
        "metrics, and memory/shuffle/filecache gauge deltas aggregated into "
        "one breakdown readable via DataFrame.explain_analyze() / "
        "QueryProfile.to_dict() (docs/observability.md).")

PROFILE_TRACE = conf(
    "spark.rapids.tpu.profile.traceCapture", default=False,
    doc="Also capture in-process trace events for the query window so "
        "QueryProfile.chrome_trace() carries real per-operator batch spans "
        "(small per-batch overhead; docs/observability.md).")

METRICS_JOURNAL_ENABLED = conf(
    "spark.rapids.tpu.metrics.journal.enabled", default=True,
    doc="Record query lifecycle phases (submit/plan-rewrite/reuse/fusion/"
        "compile/execute/finish) plus spill/retry/fault/worker events in "
        "the bounded in-process journal (obs/events.py; "
        "docs/observability.md). Per-event cost is one dict append under "
        "a lock.")

METRICS_JOURNAL_CAPACITY = conf(
    "spark.rapids.tpu.metrics.journal.capacity", default=4096,
    doc="Bounded journal ring size; oldest events are evicted "
        "(srtpu_journal_evicted_total counts drops).")

METRICS_HISTOGRAM_ENABLED = conf(
    "spark.rapids.tpu.metrics.histogram.enabled", default=True,
    doc="Record log2-bucketed latency histograms (query wall, per-batch "
        "opTime, shuffle fetch/write, retry backoff, serving SLO waits) "
        "exposed as Prometheus _bucket/_sum/_count families with "
        "p50/p95/p99 in profiles (obs/histo.py).")

METRICS_SPANS_ENABLED = conf(
    "spark.rapids.tpu.metrics.spans.enabled", default=True,
    doc="Record distributed-tracing spans (obs/span.py): named regions "
        "carrying trace_id/span_id/parent_id through the serving runtime, "
        "the cluster ctrl pipe, shuffle fetches/writes, and mesh dispatch, "
        "so one query's cross-process timeline reassembles into a single "
        "merged trace. Span events ride the existing trace-capture window "
        "(profile.traceCapture) and a jax.profiler annotation; with capture "
        "off and no profiler running a span takes no lock "
        "(docs/observability.md).")

MEM_TRACK_ENABLED = conf(
    "spark.rapids.tpu.memory.track.enabled", default=True,
    doc="Attribute every HBM-pool allocation to a (query, operator, site) "
        "tag (obs/memtrack.py): per-site watermark gauges, memory sections "
        "in query profiles, OOM post-mortem ranking, and the query-end "
        "leak audit all read this. Disabled, the pool hooks are one flag "
        "read per allocation (docs/memory.md).")

MEM_POSTMORTEM_ENABLED = conf(
    "spark.rapids.tpu.memory.oomPostmortem.enabled", default=True,
    doc="On an unrecoverable allocation failure (pool denied after "
        "spilling, or with_retry exhausted), write a ranked snapshot of "
        "live allocations, spill/semaphore state, and recent retry "
        "history to oom_postmortem_*.json (docs/memory.md).")

MEM_POSTMORTEM_DIR = conf(
    "spark.rapids.tpu.memory.oomPostmortem.dir", default="artifacts",
    doc="Directory OOM post-mortem JSON files are written to (created on "
        "first dump).")

MEM_LEAK_AUDIT_ENABLED = conf(
    "spark.rapids.tpu.memory.leakAudit.enabled", default=True,
    doc="At query end, check that every allocation tagged to the query "
        "was freed (MemoryCleaner analog; materialization-cache entries "
        "are exempt while cached). Leaks feed srtpu_mem_leaked_bytes_total "
        "and a leak-audit journal event (docs/memory.md).")

MEM_LEAK_AUDIT_STRICT = conf(
    "spark.rapids.tpu.memory.leakAudit.strict", default=False,
    internal=True,
    doc="Test-lane flag: raise MemoryLeakError when the query-end leak "
        "audit finds leaked bytes on an otherwise-successful query.")

HEALTH_PROGRESS_TIMEOUT_S = conf(
    "spark.rapids.tpu.metrics.health.progressTimeoutSeconds", default=60.0,
    doc="A worker that keeps heartbeating but reports no task progress "
        "for this long is flagged stalled in the health registry and "
        "raises a worker-stale journal event (obs/health.py).")

CPU_FALLBACK_ENABLED = conf(
    "spark.rapids.tpu.sql.fallback.enabled", default=True,
    doc="Allow per-operator CPU fallback. When false an unsupported operator "
        "raises instead.")

AQE_ENABLED = conf(
    "spark.rapids.tpu.sql.adaptive.enabled", default=True,
    doc="Adaptive query execution: after a shuffle stage materializes, plan "
        "the downstream read from actual partition sizes — coalescing small "
        "partitions and splitting skewed join partitions (reference: "
        "GpuCustomShuffleReaderExec.scala:37, docs/dev/adaptive-query.md).")

AQE_TARGET_PARTITION_BYTES = conf(
    "spark.rapids.tpu.sql.adaptive.advisoryPartitionSizeBytes",
    default=64 << 20,
    doc="Advisory serialized size per post-shuffle partition; adjacent "
        "partitions below it are coalesced into one reader task "
        "(Spark spark.sql.adaptive.advisoryPartitionSizeInBytes).")

AQE_SKEW_ENABLED = conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.enabled", default=True,
    doc="Split skewed shuffle-join partitions into per-map-range chunks "
        "(Spark spark.sql.adaptive.skewJoin.enabled).")

AQE_SKEW_FACTOR = conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor",
    default=5.0,
    doc="A join partition is skewed when its size exceeds this multiple of "
        "the median partition size (and the threshold below).")

PATHS_TO_REPLACE = conf(
    "spark.rapids.tpu.alluxio.pathsToReplace", default="",
    doc="Comma-separated 'src->dst' prefix rules applied to scan paths "
        "before reading, e.g. 's3://bucket->/mnt/cache/bucket' "
        "(reference: spark.rapids.alluxio.pathsToReplace, AlluxioUtils).")

CBO_ENABLED = conf(
    "spark.rapids.tpu.sql.optimizer.enabled", default=False,
    doc="Cost-based optimizer: compare estimated device vs host cost "
        "including host<->device transfer at placement boundaries, and keep "
        "sections on CPU when acceleration doesn't pay (reference: "
        "spark.rapids.sql.optimizer.enabled, CostBasedOptimizer.scala:36).")

CBO_DEVICE_OP_COST = conf(
    "spark.rapids.tpu.sql.optimizer.deviceOperatorCost", default=0.2,
    doc="Relative per-row cost of an operator on device (reference: "
        "spark.rapids.sql.optimizer.gpu.exec.default).", internal=True)

CBO_CPU_OP_COST = conf(
    "spark.rapids.tpu.sql.optimizer.cpuOperatorCost", default=1.0,
    doc="Relative per-row cost of an operator on the CPU fallback engine.",
    internal=True)

CBO_TRANSFER_COST = conf(
    "spark.rapids.tpu.sql.optimizer.transferCost", default=2.0,
    doc="Relative per-row cost of crossing the host<->device boundary "
        "(row<->columnar transition analog).", internal=True)

JOIN_BROADCAST_ROWS = conf(
    "spark.rapids.tpu.sql.join.broadcastRowThreshold", default=500_000,
    doc="Estimated build-side row count at or below which a multi-partition "
        "hash join uses a broadcast build instead of co-partitioning both "
        "sides (reference: spark.sql.autoBroadcastJoinThreshold consumed by "
        "GpuBroadcastHashJoinExecBase; size-based strategy per "
        "GpuShuffledSizedHashJoinExec.scala:768).")

JOIN_MAX_OUTPUT_ROWS = conf(
    "spark.rapids.tpu.sql.join.maxCandidateRowsPerBatch",
    default=1 << 27,
    doc="Hard cap on candidate join pairs produced by ONE probe batch. A "
        "plan whose join explodes past this raises a clear error instead "
        "of hanging/OOMing (JoinGatherer chunking analog; the round-2 q72 "
        "semi-cartesian hang motivates the guard).")

DPP_ENABLED = conf(
    "spark.rapids.tpu.sql.dynamicPartitionPruning.enabled", default=True,
    doc="Dynamic partition pruning: collect a join's build-side key values "
        "and prune the probe scan's parquet row groups whose statistics "
        "prove no key can match (reference: GpuDynamicPruningExpression / "
        "GpuSubqueryBroadcastExec; docs/dev/adaptive-query.md DPP).")

DPP_MAX_KEYS = conf(
    "spark.rapids.tpu.sql.dynamicPartitionPruning.maxKeys", default=1 << 16,
    doc="Disable dynamic pruning when the build side has more distinct keys "
        "than this (broadcast-threshold analog).", internal=True)

AQE_SKEW_THRESHOLD_BYTES = conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionThresholdBytes",
    default=256 << 20,
    doc="Minimum size for a join partition to be considered skewed.")


# ---------------------------------------------------------------------------
# Round-5 perf/feature knobs (VERDICT r4 item 10: the knobs perf sweeps need)
# ---------------------------------------------------------------------------

FUSION_ENABLED = conf(
    "spark.rapids.tpu.sql.fusion.enabled", default=True,
    doc="Collapse maximal chains of narrow per-batch operators (project/"
        "filter/expand), inner-join probes, and a terminal partial/complete "
        "aggregate into one jitted program per pipeline stage, paying the "
        "per-dispatch floor once per stage instead of once per operator "
        "(exec/fused.py; WholeStageCodegenExec analog). Data-dependent "
        "runtime conditions (a join build no fused rung takes, aggregate "
        "carry overflow) fall back to the unfused operator chain per "
        "partition.")

FUSION_MIN_OPERATORS = conf(
    "spark.rapids.tpu.sql.fusion.minOperators", default=2,
    doc="Minimum number of absorbed per-batch dispatch sites for a fused "
        "stage to be built; below this the extra compiled program isn't "
        "worth it. Narrow ops and join probes count one each; a terminal "
        "aggregate counts two (its windowed streaming absorption alone "
        "replaces aggBatchWindow dispatches with one).")

FUSION_AGG_WINDOW = conf(
    "spark.rapids.tpu.sql.fusion.aggBatchWindow", default=7,
    doc="Number of input batches one fused streaming-aggregate dispatch "
        "consumes (chain+first-pass per batch, then a single carry+firsts "
        "concat/merge). 7 keeps the merge concat 8-wide, matching the "
        "classic operator's tuned cascade width. A window holds batches "
        "of one capacity and is a program of that many unrolled bodies: a "
        "partition of equal batches binds one such program, plus one for "
        "a shorter last window and one for a last batch in a smaller "
        "capacity bucket. Capacities that interleave cost a dispatch per "
        "run of equal capacity.")

SHRINK_TO_LIVE_ENABLED = conf(
    "spark.rapids.tpu.sql.batch.shrinkToLive.enabled", default=True,
    doc="Re-bucket filter/join/aggregate outputs down to the live row "
        "count's power-of-two capacity so downstream kernels run at the "
        "smaller static shape (device cost scales with capacity).")

SHRINK_TO_LIVE_MIN_CAPACITY = conf(
    "spark.rapids.tpu.sql.batch.shrinkToLive.minCapacity", default=1 << 20,
    doc="Smallest batch capacity the shrink pass considers; below this the "
        "host sync costs more than the shrink saves.")

WINDOW_STREAMING_ENABLED = conf(
    "spark.rapids.tpu.sql.window.streaming.enabled", default=True,
    doc="Stream window groups across batches (running-state carry / "
        "bounded neighbor context) instead of coalescing each partition "
        "into one batch (reference: GpuRunningWindowExec / "
        "GpuBatchedBoundedWindowExec).")

WINDOW_MAX_BOUNDED_CONTEXT = conf(
    "spark.rapids.tpu.sql.window.streaming.maxContextRows", default=1024,
    doc="Largest bounded-frame extent / lead-lag offset handled by the "
        "batch-streaming window path; larger frames coalesce to one batch.")

SORT_OOC_TARGET_ROWS = conf(
    "spark.rapids.tpu.sql.sort.outOfCore.targetRows", default=1 << 17,
    doc="Output batch row target for the out-of-core sort merge "
        "(reference: GpuSortExec targetSize).")

SORT_OOC_MAX_MERGE_RUNS = conf(
    "spark.rapids.tpu.sql.sort.outOfCore.maxMergeRuns", default=16,
    doc="Cap on the number of sorted runs the out-of-core sort merges per "
        "output batch. Above the cap, runs are pre-merged pairwise-grouped "
        "into combined runs that shed through the spill framework, so the "
        "bounded merge set (and its device concat) never grows with input "
        "batch count.",
    check=lambda v: None if int(v) >= 2 else "must be >= 2")

SORT_MERGE_PATH_ENABLED = conf(
    "spark.rapids.tpu.sql.sort.outOfCore.mergePath", default=True,
    doc="Use the merge-path partitioned device merge for out-of-core "
        "sorted runs when the sort key packs into one word (single-column "
        "boolean/int/date/float32/short/byte keys): ranks presorted "
        "pieces by binary search instead of re-sorting the concatenated "
        "merge set. Bit-identical to the re-sort; plan/autotune.py picks "
        "between the two from measured ns/row.")

SORT_RADIX_ENABLED = conf(
    "spark.rapids.tpu.sql.sort.radixPack", default=True,
    doc="Allow the packed key-normalized ('radix') sort path: key words "
        "are normalized to bit-width-bounded unsigned fields and packed "
        "into fewer u32 sort operands. Bit-identical to the lexsort path; "
        "plan/autotune.py picks between them from measured ns/row.")

LEXSORT_VARIADIC_MAX = conf(
    "spark.rapids.tpu.sql.sort.variadicMaxOperands", default=6,
    doc="Max sort-key words for the single fused variadic device sort; "
        "beyond this the LSD carry-chain (one fixed-size compile per key) "
        "is used. Compile time grows superlinearly with operand count.")

JOIN_DENSE_MAX_DOMAIN = conf(
    "spark.rapids.tpu.sql.join.denseKey.maxDomain", default=1 << 25,
    doc="Largest integer key domain for the dense direct-address join "
        "table (one int32 slot per possible key).")

JOIN_UNIQUE_MAX_SLOTS = conf(
    "spark.rapids.tpu.sql.join.uniqueTable.maxSlots", default=16,
    doc="Bucket-scan width cap for the bucketed unique-key join table; "
        "build sides needing more slots use the general hash-table join.")

JOIN_HASHTBL_ENABLED = conf(
    "spark.rapids.tpu.sql.join.hashTable.enabled", default=True,
    doc="Use the open-addressing device hash table (kernels.HashTable) for "
        "duplicate-key / wide-domain build sides, with bounded chunked "
        "gather output; disabled falls back to the round-2 sorted-hash "
        "join with its candidate-explosion guard (docs/kernels.md).")

JOIN_CHUNK_TARGET_ROWS = conf(
    "spark.rapids.tpu.sql.join.gatherChunkTargetRows", default=1 << 22,
    doc="Candidate-pair budget per emitted output chunk of the general "
        "hash-table join. One probe batch whose candidates exceed this is "
        "emitted as multiple bounded chunks through the spillable "
        "framework (GpuSubPartitionHashJoin gatherer-chunking analog) "
        "instead of materializing at once.",
    check=lambda v: None if v >= 1024 else "must be >= 1024")

AGG_HASHTBL_ENABLED = conf(
    "spark.rapids.tpu.sql.agg.hashTable.enabled", default=True,
    internal=True,
    doc="Cluster 128-bit-hashed group keys through the open-addressing "
        "table (one int32 slot sort) instead of the 128-bit lexsort. "
        "Read at trace time; same treat-as-exact grouping bar.")

HASHTBL_PALLAS_MODE = conf(
    "spark.rapids.tpu.sql.kernel.hashTable.pallasMode", default="auto",
    internal=True,
    doc="Hash-table probe kernel dispatch: 'auto' uses the Pallas kernel "
        "only on a backend whose compiler is shown to accept it "
        "(tests/test_tpu_compile.py) - none today, the TPU v5e compiler "
        "refuses it - and pure XLA elsewhere; 'on'/'off' force a side. "
        "A Pallas lowering failure under 'on' falls back to XLA "
        "permanently.",
    check=lambda v: None if v in ("auto", "on", "off")
    else "must be auto|on|off")

SORTWIN_PALLAS_MODE = conf(
    "spark.rapids.tpu.sql.kernel.sortWindow.pallasMode", default="auto",
    internal=True,
    doc="Segmented-scan kernel dispatch for sort/window primitives: "
        "'auto' uses the Pallas kernel only on a backend whose compiler "
        "is shown to accept it (tests/test_tpu_compile.py) - the TPU, for "
        "float32/int32 lanes - and pure XLA elsewhere; "
        "'on'/'off' force a side. The kernel is probed with an "
        "eager lowering test before any traced program commits to it; any "
        "failure falls back to XLA permanently (reset by switching this "
        "conf to 'on').",
    check=lambda v: None if v in ("auto", "on", "off")
    else "must be auto|on|off")

STRING_SORT_MAX_WORDS = conf(
    "spark.rapids.tpu.sql.sort.stringKeyMaxWords", default=16,
    doc="Widest static string sort key in uint64 words (8 bytes each). "
        "Sorts widen keys to the observed max row length bucketed to a "
        "power of two; rows longer than 8*words bytes tie past the cap.",
    check=lambda v: None if v >= 2 else "must be >= 2")

SCAN_ROW_GROUP_PRUNING = conf(
    "spark.rapids.tpu.sql.parquet.rowGroupPruning.enabled", default=True,
    doc="Prune parquet row groups with min/max statistics against pushed "
        "predicates (reference: GpuParquetScan predicate pushdown).")

SCAN_COMBINE_WINDOW = conf(
    "spark.rapids.tpu.sql.parquet.reader.combineWindow", default=4,
    doc="Files decoded per threadpool window in the multithreaded parquet "
        "reader before device upload (reference: MULTITHREADED reader "
        "combine settings).")

SCAN_METADATA_THREADS = conf(
    "spark.rapids.tpu.sql.scan.metadataThreads", default=4,
    doc="Threads reading parquet footers + row-group metadata ahead of the "
        "decode pool; large scans are otherwise serialized on per-file "
        "metadata I/O (reference: MULTITHREADED reader footer threads).",
    check=lambda v: None if v >= 1 else "must be >= 1")

WRITER_ASYNC_MAX_IN_FLIGHT = conf(
    "spark.rapids.tpu.sql.write.async.maxInFlightBytes", default=256 << 20,
    doc="Host bytes allowed in flight for async writes before producers "
        "block (reference: HostMemoryThrottle).")

SHUFFLE_TARGET_BATCH_ROWS = conf(
    "spark.rapids.tpu.shuffle.targetBatchRows", default=1 << 20,
    doc="Post-shuffle coalesce row target for merged device uploads "
        "(reference: GpuShuffleCoalesceExec target size).")

CLUSTER_HEARTBEAT_TIMEOUT_S = conf(
    "spark.rapids.tpu.cluster.heartbeat.timeoutSeconds", default=10.0,
    doc="Missed-heartbeat window after which an executor is declared dead "
        "and its tasks are rescheduled on survivors.")

CLUSTER_TASK_RETRIES = conf(
    "spark.rapids.tpu.cluster.task.maxRetries", default=2,
    doc="Times a failed/orphaned cluster task is re-run on another "
        "executor before the query fails (Spark task-retry analog).")

REGEX_MAX_STATES = conf(
    "spark.rapids.tpu.sql.regex.maxDfaStates", default=96,
    doc="DFA state budget for device regex compilation; patterns "
        "exceeding it fall back to CPU (reference: "
        "RegexComplexityEstimator). The default matches the device "
        "kernel's transition-table size.")

TZ_DB_ENABLED = conf(
    "spark.rapids.tpu.sql.timezone.db.enabled", default=True,
    doc="Device timezone-transition table for non-UTC timestamp "
        "expressions (reference: GpuTimeZoneDB).")

FILECACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.filecache.maxBytes", default=8 << 30,
    doc="Local disk budget for the file range cache.")

BLOOM_JOIN_BITS = conf(
    "spark.rapids.tpu.sql.join.bloomFilter.bits", default=1 << 23,
    doc="Bloom filter size in bits for runtime join filters "
        "(resolved via exec/bloom.default_bits() outside jit).")

GATHER_FUSION_ENABLED = conf(
    "spark.rapids.tpu.sql.kernel.fusedGather.enabled", default=True,
    internal=True,
    doc="Pack fixed-width lanes into one matrix per gather op (the r5 "
        "packed-matrix gather); disable only to debug kernel issues.")


# ---------------------------------------------------------------------------
# Round-7 async pipeline knobs (exec/pipeline.py; docs/async_pipeline.md)
# ---------------------------------------------------------------------------

PREFETCH_ENABLED = conf(
    "spark.rapids.tpu.sql.prefetch.enabled", default=True,
    doc="Run batch iterators ahead of their consumer at pipeline-breaking "
        "boundaries (scan, shuffle read, CPU->TPU transitions): a background "
        "worker drives the producer into a bounded queue so host decode, "
        "device upload, and compute overlap instead of running in lockstep "
        "(exec/pipeline.py; the MultiFileCloudParquetPartitionReader "
        "read-ahead analog). Queued device batches are accounted with the "
        "HBM pool; under memory pressure the queue sheds and execution "
        "degrades to synchronous.")

PREFETCH_DEPTH = conf(
    "spark.rapids.tpu.sql.prefetch.depth", default=2,
    doc="Batches a prefetch boundary may hold ready ahead of its consumer. "
        "Each queued batch is pool-accounted, so deeper queues trade HBM "
        "headroom for overlap.",
    check=lambda v: None if v >= 1 else "must be >= 1")

SHUFFLE_WRITE_THREADS = conf(
    "spark.rapids.tpu.shuffle.writeThreads", default=4,
    doc="Map partitions a shuffle exchange materializes concurrently. "
        "Partition 0 always runs on the calling thread first (it primes "
        "lazy operator state the remaining map tasks share read-only); the "
        "rest are partitioned/serialized on a threadpool of this size. "
        "1 restores the fully serial write.",
    check=lambda v: None if v >= 1 else "must be >= 1")

REUSE_ENABLED = conf(
    "spark.rapids.tpu.sql.exchange.reuse.enabled", default=True,
    doc="Collapse semantically-equal exchange/broadcast/DPP-subquery "
        "subtrees of a physical plan into ReusedExchange/ReusedBroadcast "
        "aliases of one surviving materialization (Spark's "
        "ReuseExchangeAndSubquery analog, plan/reuse.py). Runs before "
        "fusion so fused stages see the rewritten plan.")

REUSE_CACHE_MAX_BYTES = conf(
    "spark.rapids.tpu.sql.exchange.reuse.cache.maxBytes", default=2 << 30,
    doc="Byte cap on reduce-side batches the reuse materialization cache "
        "may pin as SpillableBatches across all shared exchanges. An entry "
        "denied admission falls back to re-reading the shuffle manager "
        "(still one map-side materialization) — the cap bounds memory, "
        "never correctness.",
    check=lambda v: None if v >= 0 else "must be >= 0")

REUSE_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.tpu.sql.exchange.reuse.cache.maxEntries", default=64,
    doc="Cap on distinct shared-exchange entries admitted to the reuse "
        "materialization cache at once.",
    check=lambda v: None if v >= 1 else "must be >= 1")

REUSE_EVICT_ENABLED = conf(
    "spark.rapids.tpu.sql.exchange.reuse.eviction.enabled", default=True,
    doc="When the materialization cache is full, evict idle cached "
        "entries (no active reader) by ascending retention score instead "
        "of refusing the new entry outright. The score combines rebuild "
        "cost (cached bytes as the proxy), recency of last access, and "
        "the owning tenant's fair-share weight, so a hot tenant cannot "
        "starve the cache (exec/reuse.py; docs/net.md). Disabled, a full "
        "cache denies admission exactly as before.")

REUSE_EVICT_COST_WEIGHT = conf(
    "spark.rapids.tpu.sql.exchange.reuse.eviction.costWeight", default=1.0,
    doc="Weight of the rebuild-cost term (log2 of cached bytes) in the "
        "eviction retention score. 0 removes size from the decision.",
    check=lambda v: None if v >= 0 else "must be >= 0")

REUSE_EVICT_RECENCY_HALFLIFE_S = conf(
    "spark.rapids.tpu.sql.exchange.reuse.eviction.recencyHalfLifeS",
    default=300.0,
    doc="Half-life in seconds of the recency term in the eviction "
        "retention score: an entry's recency value halves every interval "
        "of this length since its last access, so stale entries decay "
        "toward eviction.",
    check=lambda v: None if v > 0 else "must be > 0")

REUSE_EVICT_TENANT_WEIGHT = conf(
    "spark.rapids.tpu.sql.exchange.reuse.eviction.tenantWeight",
    default=1.0,
    doc="Strength of the tenant term in the eviction retention score: "
        "entries cached on behalf of tenants with a higher "
        "serve.fairshare.weights share survive longer under pressure. 0 "
        "makes eviction tenant-blind.",
    check=lambda v: None if v >= 0 else "must be >= 0")


# ---------------------------------------------------------------------------
# Round-9 interactive-latency knobs (plan/plan_cache.py, exec/jit_persist.py,
# the small-query fast path; docs/latency.md)
# ---------------------------------------------------------------------------

PLAN_CACHE_ENABLED = conf(
    "spark.rapids.tpu.plan.cache.enabled", default=True,
    doc="Memoize the full Overrides.apply rewrite pipeline (rewrite -> "
        "reuse -> fusion -> prefetch insertion) keyed by a canonical "
        "logical-plan fingerprint plus the session configuration. A repeat "
        "arrival of a rename-equal query reuses the already-built physical "
        "plan instead of re-running every rule; any conf change or "
        "plan_cache.bump_epoch() invalidates (plan/plan_cache.py; the "
        "plan-rewrite analog of the reference plugin's kernel amortization, "
        "docs/latency.md).")

PLAN_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.tpu.plan.cache.maxEntries", default=128,
    doc="Cap on memoized physical plans held by the plan-rewrite cache; "
        "least-recently-used entries are evicted past the cap.",
    check=lambda v: None if v >= 1 else "must be >= 1")

JIT_PERSIST_ENABLED = conf(
    "spark.rapids.tpu.jit.persist.enabled", default=True,
    doc="Persist jitted programs (per-expression and fused-stage batch "
        "functions) to an on-disk cache via jax.export so a fresh process "
        "reloads serialized executables instead of re-tracing and "
        "re-compiling them. Entries are keyed by the semantic shared_jit "
        "key plus jax version, backend, and the host CPU-feature "
        "fingerprint; a corrupt or mismatched entry is discarded and the "
        "program recompiled (exec/jit_persist.py, docs/latency.md).")

JIT_PERSIST_DIR = conf(
    "spark.rapids.tpu.jit.persist.dir", default="",
    doc="Directory for the persistent jitted-program cache. Empty (the "
        "default) selects a temp-dir path keyed by the CPU-feature "
        "fingerprint, the same scheme the XLA:CPU kernel cache uses "
        "(_xla_cpu_cache.py), so feature-set changes land in a fresh cache.")

AUTOTUNE_ENABLED = conf(
    "spark.rapids.tpu.autotune.enabled", default=True,
    doc="Measurement-driven dispatch: persist per-(op, shape-class) "
        "operator timings harvested from query profiles and consult them "
        "when picking join paths (dense/bucketed/ht/sorted) "
        "and CBO cost constants. Never a correctness "
        "dependency — with no sample the static defaults apply, and "
        "candidate paths are restricted to bit-identical alternatives "
        "(plan/autotune.py, docs/adaptive_dispatch.md).")

AUTOTUNE_DIR = conf(
    "spark.rapids.tpu.autotune.dir", default="",
    doc="Directory for the persistent autotune timing store. Empty (the "
        "default) selects the SRTPU_AUTOTUNE_DIR environment variable when "
        "set, else a temp-dir path keyed by the CPU-feature fingerprint. "
        "The store file name folds the jax version, backend, and host "
        "CPU-feature salt (the jit_persist digest contract), and the salt "
        "is re-verified on load; drifted or corrupt stores are unlinked.")

AUTOTUNE_MIN_SAMPLES = conf(
    "spark.rapids.tpu.autotune.minSamples", default=2,
    doc="Samples required per (op, shape-class, path) before its median "
        "participates in measured dispatch; below this the static default "
        "path is used.",
    check=lambda v: None if v >= 1 else "must be >= 1")

FASTPATH_ENABLED = conf(
    "spark.rapids.tpu.fastpath.enabled", default=True,
    doc="Execute small queries on an interactive fast path: when every "
        "leaf's estimated rows and bytes sit below the fastpath.maxRows/"
        "maxBytes thresholds, plan a single partition (no shuffle "
        "machinery), skip prefetch-thread insertion, and bypass the task "
        "semaphore — the per-query fixed costs dominate such queries, not "
        "the data (docs/latency.md).")

FASTPATH_MAX_ROWS = conf(
    "spark.rapids.tpu.fastpath.maxRows", default=100_000,
    doc="Estimated-row ceiling (summed over scan leaves) below which a "
        "query qualifies for the small-query fast path.",
    check=lambda v: None if v >= 0 else "must be >= 0")

FASTPATH_MAX_BYTES = conf(
    "spark.rapids.tpu.fastpath.maxBytes", default=32 << 20,
    doc="Estimated-byte ceiling (summed over scan leaves) below which a "
        "query qualifies for the small-query fast path.",
    check=lambda v: None if v >= 0 else "must be >= 0")

# ---------------------------------------------------------------------------
# Round-10 concurrent-serving knobs (spark_rapids_tpu/serve/;
# docs/serving.md)
# ---------------------------------------------------------------------------

SERVE_MAX_CONCURRENT = conf(
    "spark.rapids.tpu.serve.maxConcurrentQueries", default=4,
    doc="Executor threads in the QueryServer: how many admitted queries "
        "run simultaneously. Device-side concurrency within and across "
        "queries is still governed by sql.concurrentTpuTasks via the task "
        "semaphore — this knob bounds whole-query parallelism, that one "
        "bounds partitions on the chip (docs/serving.md).",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVE_QUEUE_DEPTH = conf(
    "spark.rapids.tpu.serve.queue.maxDepth", default=16,
    doc="Bound on queries waiting to run in the QueryServer. A submission "
        "past this depth is shed with a typed AdmissionRejected instead of "
        "queueing unboundedly (serve/admission.py).",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVE_ADMIT_FRACTION = conf(
    "spark.rapids.tpu.serve.admission.memoryFraction", default=0.9,
    doc="Fraction of the HBM pool limit admission control may promise out "
        "as per-query memory-budget reservations. A submission whose "
        "declared budget does not fit the remaining headroom is shed with "
        "AdmissionRejected(reason='memory') — overload becomes a typed "
        "refusal at the front door, never an unattributed OOM mid-query.",
    check=lambda v: None if 0.0 < v <= 1.0 else "must be in (0, 1]")

SERVE_DEFAULT_BUDGET = conf(
    "spark.rapids.tpu.serve.defaultMemoryBudgetBytes", default=0,
    doc="Memory budget applied to submissions that do not declare one. "
        "While the query runs, the pool rejects allocations that would "
        "push its live attributed bytes past the budget with a typed "
        "QueryBudgetExceeded (mem/pool.py). 0 = uncapped.",
    check=lambda v: None if v >= 0 else "must be >= 0")

SERVE_DEFAULT_DEADLINE_MS = conf(
    "spark.rapids.tpu.serve.defaultDeadlineMs", default=0.0,
    doc="Deadline applied to submissions that do not declare one, in "
        "milliseconds of wall time from submission. Past it, the query "
        "unwinds with QueryDeadlineExceeded at its next cancellation poll "
        "point and releases every pool allocation. 0 = no deadline.",
    check=lambda v: None if v >= 0 else "must be >= 0")

SERVE_GRACE_MS = conf(
    "spark.rapids.tpu.serve.cancelGraceMs", default=5000.0,
    doc="Bound on how long QueryServer.close() waits for each executor "
        "thread to observe cancellation and unwind. Poll points sit at "
        "partition boundaries, retry attempts, prefetch pulls, and "
        "semaphore wait slices, so unwind latency is one batch of work.",
    check=lambda v: None if v >= 0 else "must be >= 0")

SERVE_SINGLEFLIGHT = conf(
    "spark.rapids.tpu.serve.singleflight.enabled", default=True,
    doc="Deduplicate identical in-flight queries: a submission whose "
        "semantic plan fingerprint (plan key + session conf + shuffle "
        "partitioning) matches a query already queued or running shares "
        "that execution's result instead of running again "
        "(serve/server.py; the cross-query complement of the plan memo "
        "and materialization cache, docs/latency.md).")

SERVE_SLO_ENABLED = conf(
    "spark.rapids.tpu.serve.slo.enabled", default=True,
    doc="Per-tenant SLO metrics (serve/metrics.py): queue-wait, semaphore-"
        "wait, and deadline-slack histograms plus admission-outcome "
        "counters keyed by (tenant, priority), surfaced in Prometheus "
        "exposition and explain_analyze (docs/observability.md).")

SERVE_SLO_MAX_TENANTS = conf(
    "spark.rapids.tpu.serve.slo.maxTenants", default=64,
    doc="Cardinality bound on the per-tenant SLO registry. Submissions "
        "from tenants past the cap are folded into the 'overflow' tenant "
        "so an unbounded tenant-id stream cannot grow label cardinality "
        "without bound (serve/metrics.py).",
    check=lambda v: None if v >= 1 else "must be >= 1")

SERVE_EDF_ENABLED = conf(
    "spark.rapids.tpu.serve.edf.enabled", default=True,
    doc="Deadline-aware ordering within a priority band: among queued "
        "queries of equal priority the one with the earliest absolute "
        "deadline runs first (EDF); queries without a deadline sort after "
        "every deadlined one and stay FIFO among themselves. Disabled, "
        "order within a band is pure FIFO (serve/server.py; "
        "docs/serving.md).")

SERVE_FAIRSHARE_ENABLED = conf(
    "spark.rapids.tpu.serve.fairshare.enabled", default=False,
    doc="Per-tenant weighted fair-share admission: each tenant's queued "
        "submissions are capped at its quota — its share of "
        "serve.queue.maxDepth under serve.fairshare.weights — and a "
        "submission past quota is shed with "
        "AdmissionRejected(reason='quota') while other tenants' slots "
        "stay available (serve/admission.py; docs/net.md).")

SERVE_FAIRSHARE_WEIGHTS = conf(
    "spark.rapids.tpu.serve.fairshare.weights", default="",
    doc="Comma-separated 'tenant=weight' relative shares for fair-share "
        "admission and tenant-weighted cache eviction, e.g. "
        "'dashboards=3,adhoc=1'. A tenant not listed gets "
        "serve.fairshare.defaultWeight. Each tenant's queue quota is "
        "max(1, floor(maxDepth * weight / total declared weight)).")

SERVE_FAIRSHARE_DEFAULT_WEIGHT = conf(
    "spark.rapids.tpu.serve.fairshare.defaultWeight", default=1.0,
    doc="Relative share assigned to tenants absent from "
        "serve.fairshare.weights (and to the None tenant).",
    check=lambda v: None if v > 0 else "must be > 0")


# ---------------------------------------------------------------------------
# Round-19 network front-end knobs (spark_rapids_tpu/net/; docs/net.md)
# ---------------------------------------------------------------------------

NET_HOST = conf(
    "spark.rapids.tpu.net.host", default="127.0.0.1",
    doc="Interface the network front-end (net/frontend.py) binds its "
        "listening socket to.")

NET_PORT = conf(
    "spark.rapids.tpu.net.port", default=0,
    doc="TCP port for the network front-end; 0 picks an ephemeral port "
        "(read the bound address from QueryFrontend.address).",
    check=lambda v: None if 0 <= v <= 65535 else "must be in [0, 65535]")

NET_MAX_FRAME_BYTES = conf(
    "spark.rapids.tpu.net.maxFrameBytes", default=64 << 20,
    doc="Upper bound on one wire frame's payload. A frame header "
        "declaring more is rejected with a typed protocol error and the "
        "connection is closed without reading the payload, so an "
        "adversarial length cannot balloon server memory "
        "(net/protocol.py).",
    check=lambda v: None if v >= 1024 else "must be >= 1024")

NET_AUTH_TOKENS = conf(
    "spark.rapids.tpu.net.auth.tokens", default="",
    doc="Comma-separated 'token=tenant' shared-secret credentials for "
        "the front-end, e.g. 's3cret=dashboards,t0ken=adhoc'. A client "
        "must AUTH with a listed token before SUBMIT is accepted; its "
        "session is pinned to the mapped tenant id. Empty (the default) "
        "runs the front-end in open mode: any token authenticates as the "
        "'default' tenant — for tests and single-tenant benches only "
        "(net/session.py; docs/net.md).")

NET_SESSION_IDLE_TIMEOUT_S = conf(
    "spark.rapids.tpu.net.session.idleTimeoutS", default=300.0,
    doc="Idle bound on an authenticated session: a connection with no "
        "frame activity for this long is reaped — its socket closed and "
        "any in-flight query cancelled (net/session.py).",
    check=lambda v: None if v > 0 else "must be > 0")

NET_SUBMIT_GATE_ENABLED = conf(
    "spark.rapids.tpu.net.submitGate.enabled", default=True,
    doc="Admission-time lowering gate at the wire: SUBMIT consults the "
        "plan tagger (the PR-9 plan memo keeps repeats cheap) and the "
        "type_support matrix, and a plan with any CPU-fallback node is "
        "rejected with AdmissionRejected(reason='unsupported-plan') "
        "carrying the offending (operator, type) cells — instead of "
        "accepting work that degrades mid-execution "
        "(serve/lowering.py; docs/net.md).")

NET_STREAM_BATCH_ROWS = conf(
    "spark.rapids.tpu.net.streamBatchRows", default=65536,
    doc="Row cap per Arrow IPC record batch on the result stream. "
        "Smaller batches give the client earlier first bytes and the "
        "server finer-grained backpressure (each batch frame is one "
        "blocking send); larger batches amortize framing overhead.",
    check=lambda v: None if v >= 1 else "must be >= 1")


# ---------------------------------------------------------------------------
# What a deployment's configuration may depend on (a guard, not a knob)
# ---------------------------------------------------------------------------

#: properties of this build that a configuration can name in
#: spark.rapids.tpu.requires; each is always on and changes nothing when named
CAPABILITIES = {
    "agg.boundedStepPrograms":
        "a streaming aggregate binds a step program per (batch capacity, "
        "window length), at most three for a partition of equal batches "
        "whatever their number, each compiled once (exec/fused.py, "
        "exec/jit_persist.py; docs/fusion.md)",
    "sort.boundedTopN":
        "a sort with a limit is planned as a top-N (exec/sort.py TopNExec): "
        "k rounds of selection per batch, whose program does not grow with "
        "the batch as a variadic sort's does, so ORDER BY ... LIMIT over a "
        "100k-group aggregate compiles in seconds (docs/fusion.md)",
    "join.fusedDuplicateKeys":
        "an inner join whose build keys repeat is probed inside its fused "
        "stage through the build's runs, one a key (exec/join.py "
        "_prepare_runs): no host sync a probe batch, and the stage's output "
        "capacity counts pairs (docs/fusion.md)",
    "join.fusedSemi":
        "a semi or anti join with no residual condition, on one integer key "
        "whose build range spans at most 2^28, is a filter inside its fused "
        "stage: the build marks its keys in a presence map of a byte a "
        "possible key in one sync, and a probe batch gathers a byte a row "
        "into its live mask, with no host sync and no pairs (exec/join.py "
        "_fused_exists; docs/fusion.md)",
}


def _check_requires(v: str) -> Optional[str]:
    lacking = [c for c in (s.strip() for s in v.split(",")) if c
               and c not in CAPABILITIES]
    return f"this build lacks {lacking}" if lacking else None


REQUIRES = conf(
    "spark.rapids.tpu.requires", default="",
    doc="Comma-separated capabilities the deployment depends on; a build "
        "that lacks one refuses the configuration (RapidsConf raises) "
        "instead of running it without. Naming a capability changes no "
        "behaviour. Known: agg.boundedStepPrograms (step programs of a "
        "streaming aggregate are bounded per partition and compile once, "
        "so a large resident table's first start fits a deadline); "
        "sort.boundedTopN (a sort with a limit is a top-N by selection, "
        "whose compile cost does not grow with the batch); "
        "join.fusedDuplicateKeys (an inner join whose build keys repeat "
        "stays in its fused stage, with no host sync a probe batch); "
        "join.fusedSemi (a semi or anti join on one ranged integer key is "
        "a filter inside its fused stage, built in one sync into a "
        "presence map).",
    check=_check_requires)


_ACTIVE: "Optional[RapidsConf]" = None


def set_active(conf_obj: "RapidsConf") -> None:
    """Install the process-wide active conf (called by Overrides.apply so
    exec-layer code without a threaded conf — shrink pass, kernel caps —
    sees session settings; the reference similarly re-reads RapidsConf per
    plan, GpuOverrides.scala:4748)."""
    global _ACTIVE
    _ACTIVE = conf_obj


def get_active() -> "RapidsConf":
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = RapidsConf()
    return _ACTIVE


class RapidsConf:
    """Immutable snapshot of configuration values.

    Construct from a plain dict of string/typed values; unknown keys under the
    spark.rapids.tpu namespace raise (typo guard).
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._values: Dict[str, Any] = {}
        settings = settings or {}
        for k, v in settings.items():
            if k.startswith("spark.rapids.tpu.") and k not in _REGISTRY:
                raise KeyError(f"unknown config {k}")
            if k in _REGISTRY:
                e = _REGISTRY[k]
                val = e.conv(v) if isinstance(v, str) else v
                if e.check is not None:
                    err = e.check(val)
                    if err:
                        raise ValueError(f"{k}: {err}")
                self._values[k] = val
            else:
                self._values[k] = v

    def get(self, key: str):
        if key in self._values:
            return self._values[key]
        if key in _REGISTRY:
            return _REGISTRY[key].default
        raise KeyError(key)

    def __getitem__(self, entry: ConfEntry):
        return self.get(entry.key)

    def with_overrides(self, **kv) -> "RapidsConf":
        merged = dict(self._values)
        merged.update(kv)
        return RapidsConf(merged)


def all_entries() -> List[ConfEntry]:
    return sorted(_REGISTRY.values(), key=lambda e: e.key)


def generate_docs() -> str:
    """Render configs.md (reference: RapidsConf.scala:2548-2589)."""
    lines = [
        "# spark_rapids_tpu configuration",
        "",
        "Generated by `spark_rapids_tpu.config.conf.generate_docs()`; do not edit.",
        "",
        "| Name | Default | Description |",
        "|---|---|---|",
    ]
    for e in all_entries():
        if e.internal:
            continue
        lines.append(f"| {e.key} | {e.default} | {e.doc} |")
    return "\n".join(lines) + "\n"
