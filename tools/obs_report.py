#!/usr/bin/env python3
"""Diagnostics bundle: one directory with everything needed to debug a run.

The reference ships a driver-coordinated profiler whose output (metrics,
traces, env) support engineers ask for as a single attachment. This is the
standalone analog: ``build_bundle(out_dir)`` collects, from the live
process,

- ``profiles.json``    recent QueryProfile breakdowns (``to_dict`` each)
- ``explain.txt``      ``explain_analyze`` rendering of those profiles
- ``journal.jsonl``    the bounded lifecycle event journal
- ``metrics.prom``     Prometheus exposition (gauges + latency histograms)
- ``health.json``      merged worker health view (heartbeat registry)
- ``trace.json``       Chrome trace; merged across workers when a
                       ``TcpShuffleCluster`` is passed, else driver-only
- ``memory.json``      HBM attribution summary + watermark timeline
                       (obs/memtrack.py)
- ``memory.txt``       human top-consumers table + timeline chart
                       (tools/mem_report.py renderers)
- ``oom_postmortem_*.json``  copies of post-mortems this process wrote
- ``config.json``      resolved active configuration (every registered key)
- ``MANIFEST.json``    what was written, with sizes

CLI: ``python tools/obs_report.py --out DIR [--demo]``. ``--demo`` runs a
tiny in-memory query with profiling + trace capture on first — plus one
synthetic OOM post-mortem — so the bundle is non-empty; tests/test_obs.py
exercises it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _resolved_config() -> dict:
    from spark_rapids_tpu.config import conf as C
    active = C.get_active()
    return {e.key: active.get(e.key) for e in C.all_entries()}


def build_bundle(out_dir: str, cluster=None) -> dict:
    """Write the bundle into ``out_dir`` (created if missing); returns the
    manifest dict. ``cluster`` may be a TcpShuffleCluster for a merged
    multi-worker trace + fresh heartbeat health view."""
    from spark_rapids_tpu import obs
    from spark_rapids_tpu.obs import events as journal
    from spark_rapids_tpu.utils import tracing

    os.makedirs(out_dir, exist_ok=True)
    files = {}

    def write(name: str, text: str) -> None:
        path = os.path.join(out_dir, name)
        with open(path, "w") as f:
            f.write(text)
        files[name] = os.path.getsize(path)

    profiles = obs.recent_profiles()
    write("profiles.json",
          json.dumps([p.to_dict() for p in profiles], indent=1, default=str))
    write("explain.txt",
          "\n\n".join(p.explain_analyze() for p in profiles if p.finished))
    journal.dump_jsonl(os.path.join(out_dir, "journal.jsonl"))
    files["journal.jsonl"] = os.path.getsize(
        os.path.join(out_dir, "journal.jsonl"))
    write("metrics.prom", obs.render_prometheus())

    if cluster is not None:
        health = cluster.collect_health()
        trace = cluster.merged_chrome_trace()
    else:
        health = obs.health_registry.view()
        trace = obs.merge_process_traces({"driver": tracing.trace_events()})
    write("health.json", json.dumps(health, indent=1, default=str))
    write("trace.json", json.dumps(trace))

    # memory attribution section (obs/memtrack.py + tools/mem_report.py)
    from spark_rapids_tpu.obs import memtrack as _mt
    from tools import mem_report as _mr
    write("memory.json", json.dumps({
        **_mt.process_summary(),
        "timeline": _mt.timeline(),
        "postmortems": _mt.postmortem_paths(),
    }, indent=1, default=str))
    write("memory.txt",
          _mr.top_consumers(_mt.live_by_tag()) + "\n\n"
          + _mr.render_timeline(_mt.timeline()))
    for pm_path in _mt.postmortem_paths():
        if not os.path.exists(pm_path):
            continue
        name = os.path.basename(pm_path)
        with open(pm_path) as f:
            write(name, f.read())

    write("config.json", json.dumps(_resolved_config(), indent=1, default=str))

    manifest = {
        "files": files,
        "num_profiles": len(profiles),
        "journal_events": len(journal.recent()),
        "workers": [w["worker_id"] for w in health.get("workers", [])],
    }
    write("MANIFEST.json", json.dumps(manifest, indent=1))
    return manifest


def _run_demo_query() -> None:
    """A tiny grouped aggregation with profiling + trace capture on, so the
    bundle carries a real profile, journal lifecycle, and trace spans."""
    import pyarrow as pa

    from spark_rapids_tpu.config import conf as C
    from spark_rapids_tpu.exprs.expr import Count, Sum, col
    from spark_rapids_tpu.plan import from_arrow

    conf = C.RapidsConf({
        C.PROFILE_ENABLED.key: True,
        C.PROFILE_TRACE.key: True,
    })
    table = pa.table({
        "k": pa.array([i % 4 for i in range(512)], pa.int64()),
        "v": pa.array([float(i) for i in range(512)], pa.float64()),
    })
    df = (from_arrow(table, conf)
          .group_by("k")
          .agg(Sum(col("v")).alias("total"), Count().alias("n")))
    rows = df.collect()
    assert len(rows) == 4, rows

    # one synthetic OOM post-mortem so the bundle's memory section carries
    # a ranked snapshot (tools/mem_report.py renders the same file)
    from tools import mem_report as _mr
    _mr._run_demo()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="artifacts/obs_report",
                    help="bundle output directory")
    ap.add_argument("--demo", action="store_true",
                    help="run a tiny query first so the bundle is non-empty")
    args = ap.parse_args(argv)
    if args.demo:
        _run_demo_query()
    manifest = build_bundle(args.out)
    print(f"obs report bundle: {args.out}")
    for name, size in sorted(manifest["files"].items()):
        print(f"  {name:14s} {size:>8d} bytes")
    print(f"  ({manifest['num_profiles']} profiles, "
          f"{manifest['journal_events']} journal events, "
          f"workers={manifest['workers']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
