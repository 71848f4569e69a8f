#!/usr/bin/env python3
"""One clock? A traced run of one benchmark cell, then two checks of the
program's spans against the run's own ``.xplane.pb``:

1. every live span (obs/span.py) and operator range the program logged
   inside the traced window is found there as a host annotation of the same
   name, and the difference

       annotation start - (span start_ns + the harness's one offset)

   is reported per name and overall (median, largest). The offset is the one
   ``benchmark/harness._reduce_trace`` takes at the traced window's start
   to lay the program's spans over the device trace; a large difference is
   a limit of the idle-gap labels built that way.
2. per request of the whole window, the wire's tiles (net:client-send,
   net:accept, net:wake-lag, net:stream with net:client-recv, and
   net:request's self time) summed, beside ``net.overhead_ms``, which times
   the same thing from outside; and the host syncs by site (how many and
   how long per request, and under which span).
3. per request of the whole window, the host's own time: the CPU of each
   thread's top-level spans (client net:request, front-end net:accept +
   net:stream, executor query:execute), the executor's off-CPU time and
   involuntary context switches, the syncs' own CPU, the garbage-collection
   pauses by generation, and the five slowest requests with what each
   spent: tiles, syncs, CPU, off-CPU, switches, pauses.

    python3 tools/span_clock.py --workload sf1_q1_agg1 --seed 7 --seconds 30

The last line of standard output is one JSON object: ``clock``, ``tiles``,
``sync_sites``, ``host``, ``await_wakes`` (the front-end's submit counters
and, on a program that has them, its counters of what ended each wait for a
result, over the whole run), ``operators`` (the joins' build paths, the
runs rung's probe forms and pairs, the exists builds' marked batches and
the indices they scattered, the semi probes' rows tested and kept, and the
fused stages' fallbacks and learned output capacities by their counters,
and the exec:* spans per request) and
the run's ordinary ``result``. Needs the chip, as benchmark/run.py
does (``--rehearse-sf`` runs every step on the CPU and prints no device
number). It wraps one private function of the harness to see the offset
and the spans, and edits nothing there."""

import time
T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# stamped afterwards (record_span, the gc hook): no annotation
STAMPED = ("query:queue-wait", "net:wake-lag", "exec:join-pairs", "host:gc")
SLOWEST = 5


def match(annotations: dict, events: list, shift: int) -> dict:
    """``annotations``: {name: sorted [start_ns]} on the trace's clock;
    ``events``: the program's log ({name, start_ns}); ``shift``: host clock
    -> trace clock. Per name, the differences (ns) between each event's
    shifted start and the nearest annotation of its name."""
    out = {}
    for e in events:
        starts = annotations.get(e["name"])
        if not starts:
            out.setdefault(e["name"], None)
            continue
        want = e["start_ns"] + shift
        i = bisect.bisect_left(starts, want)
        near = min(starts[max(0, i - 1):i + 1], key=lambda s: abs(s - want))
        out.setdefault(e["name"], [])
        out[e["name"]].append(near - want)
    return out


def summarize(diffs: dict) -> dict:
    names, every = {}, []
    for name, d in sorted(diffs.items()):
        if d is None:
            continue
        every += d
        names[name] = {"n": len(d), "median_ns": statistics.median(d),
                       "max_abs_ns": max(abs(x) for x in d)}
    return {"names": names,
            "missing": sorted(n for n, d in diffs.items()
                              if d is None and n not in STAMPED),
            "stamped_absent": sorted(n for n, d in diffs.items()
                                     if d is None and n in STAMPED),
            "n": len(every),
            "median_ns": statistics.median(every) if every else None,
            "max_abs_ns": max((abs(x) for x in every), default=None)}


def tile_row(tree: list):
    """One request's wire tiles (ms), or None where it has no net:request."""
    import spantree
    from tracered import union
    roots = spantree.named(tree, "net:request")
    if not roots:
        return None
    root = roots[0]
    tail = union([(s["start"], s["end"]) for s in tree
                  if s["name"] in ("net:stream", "net:client-recv")])
    row = {n: spantree.total_ms(tree, n) for n in
           ("net:client-send", "net:accept", "query:submit",
            "net:wake-lag", "query:queue-wait", "query:execute")}
    row["net:stream+client-recv"] = sum(e - s for s, e in tail) / 1e6
    row["net:request self"] = spantree.self_ms(
        root, [s for s in tree if s is not root])
    row["net:request"] = (root["end"] - root["start"]) / 1e6
    row["tiles_sum"] = (row["net:client-send"] + row["net:accept"]
                        + row["net:wake-lag"]
                        + row["net:stream+client-recv"]
                        + row["net:request self"])
    row["request_minus_wait_and_execute"] = (
        row["net:request"] - row["query:queue-wait"]
        - row["query:execute"])
    return row


def tiles(spans: list, requests: list) -> dict:
    """Medians over the window's requests of each tile of the wire, their
    sum, and the same quantity taken as net.overhead_ms takes it."""
    import spantree
    rows = [r for r in map(tile_row, spantree.by_request(
        spans, requests).values()) if r is not None]
    if not rows:
        return {}
    return dict({k: statistics.median(r[k] for r in rows) for k in rows[0]},
                requests=len(rows))


def host_time(spans: list, requests: list, profiler=None) -> dict:
    """Means over the window's requests of each thread's CPU (ms) in its
    top-level spans, the executor's off-CPU time (as the benchmark's
    host.execute_offcpu_ms_per_query takes it) and involuntary switches,
    the syncs' wall and own CPU, and the live spans (those that read the
    CPU clock) of a request; the window's garbage-collection pauses
    per request by generation; and the SLOWEST requests by net:request,
    each with its tiles, what it spent, its start (s after the window's
    first request) and the wall and CPU ms of query:execute's children by
    name. ``profiler``, (start, end) ns of the profiler's trace through
    its stop_trace, is reported on the same scale. Empty on a program
    whose spans carry no cpu_ns."""
    import harness
    import spantree
    from tracered import union
    offcpu = harness.load_by_path("layer_metrics",
                                  "host.execute_offcpu_ms_per_query")
    pauses = [e for e in spans if e["name"] == "host:gc"]

    def cpu_ms(tree, *names):
        return sum(s["attrs"].get("cpu_ns", 0) for n in names
                   for s in spantree.named(tree, n)) / 1e6

    rows = []
    for name, tree in spantree.by_request(spans, requests).items():
        roots = spantree.named(tree, "net:request")
        execs = spantree.named(tree, "query:execute")
        if not roots or not execs or "cpu_ns" not in execs[0]["attrs"]:
            continue
        root, ex = roots[0], execs[0]
        below = offcpu._below(tree, ex)
        syncs = [s for s in below if s["name"] == "exec:host-sync"]
        lo, hi = root["start"], root["end"]
        gc_ms = sum(min(e["start_ns"] + e["dur_ns"], hi)
                    - max(e["start_ns"], lo) for e in pauses
                    if e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo)
        children = {}
        for c in tree:
            if c["parent"] == ex["id"]:
                w = children.setdefault(c["name"], [0.0, 0.0])
                w[0] += (c["end"] - c["start"]) / 1e6
                w[1] += c["attrs"].get("cpu_ns", 0) / 1e6
        rows.append({
            "request": name, "start_ns": lo, "wall_ms": (hi - lo) / 1e6,
            "client_cpu_ms": cpu_ms(tree, "net:request"),
            "frontend_cpu_ms": cpu_ms(tree, "net:accept", "net:stream"),
            "execute_wall_ms": (ex["end"] - ex["start"]) / 1e6,
            "execute_cpu_ms": ex["attrs"]["cpu_ns"] / 1e6,
            "execute_offcpu_ms": offcpu._offcpu(tree),
            "nivcsw": ex["attrs"].get("nivcsw"),
            "sync_ms": sum(e - s for s, e in union(
                [(s["start"], s["end"]) for s in syncs])) / 1e6,
            "sync_cpu_ms": sum(s["attrs"].get("cpu_ns", 0)
                               for s in syncs) / 1e6,
            "gc_ms": gc_ms / 1e6,
            "live_spans": sum(1 for s in tree if "cpu_ns" in s["attrs"]),
            "tiles": tile_row(tree), "children": children})
    if not rows:
        return {}
    t0 = min(r["start_ns"] for r in rows)
    for r in rows:
        r["at_s"] = (r.pop("start_ns") - t0) / 1e9
    keys = [k for k in rows[0]
            if k not in ("request", "tiles", "children", "at_s")]
    mean = {k: statistics.fmean(r[k] for r in rows if r[k] is not None)
            for k in keys if any(r[k] is not None for r in rows)}
    by_gen = {}
    for e in pauses:
        g = by_gen.setdefault(str(e["args"]["generation"]), [0, 0.0, 0.0])
        g[0] += 1
        g[1] += e["dur_ns"] / 1e6
        g[2] = max(g[2], e["dur_ns"] / 1e6)
    return {"requests": len(rows), "mean": mean,
            "profiler_s": profiler and [(t - t0) / 1e9 for t in profiler],
            "gc_by_generation": {
                gen: {"per_request": c / len(rows),
                      "ms_per_request": ms / len(rows), "longest_ms": top}
                for gen, (c, ms, top) in sorted(by_gen.items())},
            "slowest": sorted(rows, key=lambda r: -r["wall_ms"])[:SLOWEST]}


def sync_sites(spans: list, requests: list) -> dict:
    """{site: [syncs per request, blocked ms per request]} over the
    window's requests, and under which span each site's syncs sit: the
    ranking ROADMAP S3 cuts by."""
    import spantree
    trees = list(spantree.by_request(spans, requests).values())
    out = {}
    for tree in trees:
        by_id = {s["id"]: s["name"] for s in tree}
        for s in spantree.named(tree, "exec:host-sync"):
            row = out.setdefault(s["attrs"].get("site"), [0, 0.0, set()])
            row[0] += 1
            row[1] += (s["end"] - s["start"]) / 1e6
            row[2].add(by_id.get(s["parent"]))
    n = max(len(trees), 1)
    return {site: {"per_request": c / n, "ms_per_request": ms / n,
                   "under": sorted(str(p) for p in parents)}
            for site, (c, ms, parents) in sorted(
                out.items(), key=lambda kv: -kv[1][1])}


def operator_spans(spans: list, requests: list) -> dict:
    """{span name [path or cause]: [per request, ms per request]} of the
    exec:* spans other than host syncs over the window's requests."""
    import spantree
    trees = list(spantree.by_request(spans, requests).values())
    out = {}
    for tree in trees:
        for s in tree:
            if not s["name"].startswith("exec:") or (
                    s["name"] == "exec:host-sync"):
                continue
            tag = s["attrs"].get("path") or s["attrs"].get("cause")
            row = out.setdefault(s["name"] + (f" {tag}" if tag else ""),
                                 [0, 0.0])
            row[0] += 1
            row[1] += (s["end"] - s["start"]) / 1e6
    n = max(len(trees), 1)
    return {k: {"per_request": c / n, "ms_per_request": ms / n}
            for k, (c, ms) in sorted(out.items())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse-sf", type=float, default=None)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    sys.path.insert(1, ROOT)
    import harness
    import run as bench_run
    marker = harness.primed_marker(args.workload, args.rehearse_sf)
    if not os.path.exists(marker):  # before this process touches JAX
        rc = bench_run.prime(args, marker)
        if rc:
            return rc
    keep = args.keep_trace or tempfile.mkdtemp(prefix="span_clock_")
    os.makedirs(keep, exist_ok=True)
    xplane = os.path.join(keep, "span_clock.xplane.pb")
    seen = {}
    reduce_trace = harness._reduce_trace

    def spy(info, spans, requests, log, keep_dir=None):
        seen.update(info=info, spans=spans, requests=requests)
        # our own copy first: the harness keeps one only after a reduction
        # that found a device timeline, and then removes the directory
        [found] = glob.glob(os.path.join(harness.TRACE_DIR, "**",
                                         "*.xplane.pb"), recursive=True)
        shutil.copy(found, xplane)
        return reduce_trace(info, spans, requests, log, keep_dir)
    harness._reduce_trace = spy
    trace_window = harness._trace_window

    def timed_window(*a):  # when the profiler's stop_trace returned
        info = trace_window(*a)
        seen["stopped_ns"] = time.perf_counter_ns()
        return info
    harness._trace_window = timed_window
    rehearsal = args.rehearse_sf is not None
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                               t_process=T_PROCESS, scale=args.rehearse_sf,
                               require_chip=not rehearsal)
    except harness.NoChip as e:
        print(f"[span_clock] {e}", file=sys.stderr)
        return 3
    import tracered
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane)
    annotations = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    annotations.setdefault(e.name, []).append(e.start_ns)
    for starts in annotations.values():
        starts.sort()
    info = seen["info"]
    shift = annotations[tracered.WINDOW][0] - info["enter_ns"]
    inside = [e for e in seen["spans"] if not e.get("counter")
              and e["start_ns"] >= info["enter_ns"]
              and e["start_ns"] + e["dur_ns"] <= info["exit_ns"]]
    report = {"clock": summarize(match(annotations, inside, shift)),
              "tiles": tiles(seen["spans"], seen["requests"]),
              "sync_sites": sync_sites(seen["spans"], seen["requests"]),
              "host": host_time(seen["spans"], seen["requests"],
                                (info["enter_ns"], seen["stopped_ns"]))}
    from spark_rapids_tpu.net import metrics as net_metrics
    report["await_wakes"] = {
        k: v for k, v in net_metrics.counters().items()
        if k.startswith(("net_await_wake_", "net_submit_"))}
    # over the whole run (set-up and warm rounds too): which probe
    # structure the joins' build sides ended in, the pairs the runs rung
    # emitted, every fallback of a fused stage by cause, and the partitions
    # of stages without a join packed to a learned capacity (or that
    # outgrew it); per request of the window: each operator span's count
    # and milliseconds
    from spark_rapids_tpu.obs import gauges
    snap = gauges.snapshot()
    report["host"]["gc_counters"] = {k: v for k, v in snap.items()
                                     if k.startswith("host_gc_")}
    report["operators"] = {
        "counters": {k: v for k, v in snap.items()
                     if k.startswith(("join_build_path_", "join_run_pairs_",
                                      "join_run_probe_", "join_semi_probe_",
                                      "join_exists_mark_",
                                      "fused_fallback_", "fused_plain_",
                                      "fused_step_programs_"))},
        "spans": operator_spans(seen["spans"], seen["requests"])}
    if rehearsal:  # no device number from a rehearsal
        out = {"rehearsal": True, "correct": out["correct"],
               "metrics_read": sorted(out["metrics"])}
    report["result"] = out
    print(json.dumps(report), flush=True)
    return 4 if rehearsal else 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # as benchmark/run.py: daemon threads hold no state
