"""Prometheus-style text exposition of process-level gauges.

Renders the gauge catalog (obs/gauges.py) in the Prometheus text exposition
format (version 0.0.4): ``# HELP`` / ``# TYPE`` pairs followed by one
sample line per metric, all under the ``srtpu_`` namespace. Serve the
string from any HTTP endpoint (or write it for the node_exporter textfile
collector) to scrape pool, spill, semaphore, shuffle, and filecache state.
"""

from __future__ import annotations

from typing import Dict, Optional

from spark_rapids_tpu.obs import gauges as G
from spark_rapids_tpu.obs import histo as H

NAMESPACE = "srtpu"


def render_histograms(snapshots: Optional[Dict[str, Dict]] = None) -> str:
    """Latency histograms (obs/histo.py) as ``_bucket``/``_sum``/``_count``
    families. Internal unit is ns; exposed as Prometheus-conventional
    seconds under ``<name minus _ns>_seconds``. Empty buckets past the
    largest populated one are elided (``+Inf`` always closes the family).
    """
    snaps = snapshots if snapshots is not None else H.snapshot_all()
    lines = []
    for name, help_text in H.CATALOG:
        s = snaps.get(name)
        if s is None:
            continue
        base = name[:-3] if name.endswith("_ns") else name
        full = f"{NAMESPACE}_{base}_seconds"
        lines.append(f"# HELP {full} {help_text}")
        lines.append(f"# TYPE {full} histogram")
        counts = s["counts"]
        top = max((i for i, c in enumerate(counts) if c), default=-1)
        cum = 0
        for i in range(top + 1):
            cum += counts[i]
            le = (1 << i) / 1e9  # bucket i upper bound: 2**i ns
            lines.append(f'{full}_bucket{{le="{le:g}"}} {cum}')
        lines.append(f'{full}_bucket{{le="+Inf"}} {s["count"]}')
        lines.append(f"{full}_sum {s['sum'] / 1e9:g}")
        lines.append(f"{full}_count {s['count']}")
    return "\n".join(lines) + "\n"


def _label_str(label_key) -> str:
    """histo family label-key tuple -> Prometheus label body (sorted)."""
    return ",".join(f'{k}="{v}"' for k, v in label_key)


def render_tenant_slos() -> str:
    """Per-tenant serving SLOs: labeled histogram families
    (``{tenant=...,priority=...}``) for queue wait / semaphore wait /
    deadline slack, plus per-(tenant, priority, outcome) admission
    counters from serve/metrics.py. Empty when serving never ran."""
    lines = []
    for name, help_text in H.CATALOG:
        fam = H.family(name)
        if not fam:
            continue
        base = name[:-3] if name.endswith("_ns") else name
        full = f"{NAMESPACE}_{base}_seconds"
        lines.append(f"# HELP {full} {help_text} (labeled family)")
        lines.append(f"# TYPE {full} histogram")
        for label_key in sorted(fam):
            s = fam[label_key].snapshot()
            lbl = _label_str(label_key)
            counts = s["counts"]
            top = max((i for i, c in enumerate(counts) if c), default=-1)
            cum = 0
            for i in range(top + 1):
                cum += counts[i]
                le = (1 << i) / 1e9
                lines.append(f'{full}_bucket{{{lbl},le="{le:g}"}} {cum}')
            lines.append(f'{full}_bucket{{{lbl},le="+Inf"}} {s["count"]}')
            lines.append(f"{full}_sum{{{lbl}}} {s['sum'] / 1e9:g}")
            lines.append(f"{full}_count{{{lbl}}} {s['count']}")
    from spark_rapids_tpu.serve import metrics as _sm
    outcomes = _sm.tenant_outcomes()
    if outcomes:
        full = f"{NAMESPACE}_serve_tenant_outcome_total"
        lines.append(f"# HELP {full} Admission/terminal outcomes per "
                     f"(tenant, priority)")
        lines.append(f"# TYPE {full} counter")
        for (tenant, priority) in sorted(outcomes):
            for outcome, n in sorted(outcomes[(tenant, priority)].items()):
                lines.append(
                    f'{full}{{tenant="{tenant}",priority="{priority}",'
                    f'outcome="{outcome}"}} {n}')
    return "\n".join(lines) + ("\n" if lines else "")


def render_prometheus(snapshot: Optional[Dict[str, int]] = None) -> str:
    """The current (or given) gauge snapshot as exposition text, followed
    by the latency histogram families and the per-tenant SLO series."""
    snap = snapshot if snapshot is not None else G.snapshot()
    lines = []
    for name, kind, help_text in G.CATALOG:
        full = f"{NAMESPACE}_{name}"
        lines.append(f"# HELP {full} {help_text}")
        lines.append(f"# TYPE {full} {kind}")
        lines.append(f"{full} {snap.get(name, 0)}")
    return ("\n".join(lines) + "\n" + render_histograms()
            + render_tenant_slos())
