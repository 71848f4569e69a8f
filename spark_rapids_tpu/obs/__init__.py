"""Unified observability layer (SURVEY.md §5 as one subsystem).

The reference treats observability as first-class: leveled GpuMetrics on
every operator, GpuTaskMetrics per task, NVTX ranges feeding nsys, a
driver-coordinated profiler, and "explain with metrics" in the UI. This
package is the standalone unification of the repo's fragments:

- ``profile``       QueryProfile registry: per-query snapshot/aggregate of
                    operator metrics, task metrics, memory/shuffle/filecache
                    gauges, trace events, and phase attribution;
                    ``explain_analyze`` rendering
- ``events``        bounded thread-safe lifecycle event journal (JSONL)
- ``histo``         log-bucketed latency histograms (p50/p95/p99)
- ``memtrack``      per-query HBM attribution: site/operator watermarks,
                    OOM post-mortems, query-end leak audit (docs/memory.md)
- ``health``        worker heartbeat + health registry (merged driver view)
- ``trace_export``  Chrome trace_event JSON for chrome://tracing / Perfetto,
                    incl. multi-worker merge with per-process tracks
- ``expose``        Prometheus text exposition of process gauges + histograms
- ``gauges``        the gauge catalog the above read
- ``span``          distributed tracing: Span/TraceContext propagated across
                    the serving runtime, cluster ctrl pipe, and mesh dispatch

See docs/observability.md for the metric catalog and workflows.
"""

from spark_rapids_tpu.obs import memtrack  # noqa: F401
from spark_rapids_tpu.obs.gauges import snapshot as gauge_snapshot  # noqa: F401
from spark_rapids_tpu.obs.profile import (  # noqa: F401
    QueryProfile,
    collect_node_stats,
    get_profile,
    last_profile,
    profile_for,
    recent_profiles,
)
from spark_rapids_tpu.obs.trace_export import (  # noqa: F401
    merge_process_traces,
    to_chrome_trace,
)
from spark_rapids_tpu.obs.expose import (  # noqa: F401
    render_histograms,
    render_prometheus,
)
from spark_rapids_tpu.obs import events as journal  # noqa: F401
from spark_rapids_tpu.obs import health  # noqa: F401
from spark_rapids_tpu.obs import histo  # noqa: F401
from spark_rapids_tpu.obs import span as tracespan  # noqa: F401
from spark_rapids_tpu.obs.span import (  # noqa: F401
    Span,
    TraceContext,
    assemble_traces,
)
from spark_rapids_tpu.obs.health import REGISTRY as health_registry  # noqa: F401
