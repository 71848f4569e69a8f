"""Perf probe: dissect the operator-level slowdown (VERDICT r4 weak #2).

Facts to explain: warm jitted FilterExec on a 16M-row 9-col batch = 8s
while its primitives total ~1s, and the full Q1 chain runs 1.1-1.4s on
fresh inputs.

Each experiment times a warm jitted computation with the honest fence
(device_get of a 1-element slice per output) and varies ONE axis:
  - output buffer COUNT (same total bytes)
  - output buffer BYTES (same count)
  - chained consumption (big intermediates consumed by tiny reducer)
  - the real FilterExec on a lineitem-shaped batch
"""
from __future__ import annotations

import json
import os
import sys
import time

# ---------------------------------------------------------------------------
# `python tools/perf_probe.py dispatch` — count jitted dispatches per warm
# iteration with whole-stage fusion on vs off. The wrapper must be installed
# BEFORE any spark_rapids_tpu import: operator modules capture jax.jit at
# import time (``@partial(jax.jit, ...)`` decorators), so patching later
# would miss every per-operator program.
# ---------------------------------------------------------------------------
_DISPATCH_MODE = "dispatch" in sys.argv[1:]
_dispatches = {"n": 0}

if _DISPATCH_MODE:
    import functools

    import jax as _jax_early

    _orig_jit = _jax_early.jit

    def _counting_jit(fun=None, **kw):
        if fun is None:
            return lambda f: _counting_jit(f, **kw)
        jitted = _orig_jit(fun, **kw)

        @functools.wraps(fun)
        def wrapper(*a, **k):
            _dispatches["n"] += 1
            return jitted(*a, **k)

        return wrapper

    _jax_early.jit = _counting_jit

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.utils import tracing

N = 1 << 24  # 16M


def timeit(name, fn, *args, reps=3):
    # warm
    out = fn(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    _fence(out)
    ts = []
    for i in range(reps):
        t0 = time.perf_counter()
        with tracing.TraceRange(f"{name} #{i}"):
            out = fn(*args)
            _fence(out)
        ts.append(time.perf_counter() - t0)
    print(f"{name:55s} min={min(ts):7.3f}s  all={[round(t,3) for t in ts]}")
    return min(ts)


def _fence(out):
    tiny = [jnp.ravel(x)[:1] for x in jax.tree_util.tree_leaves(out)
            if isinstance(x, jax.Array) and x.size]
    jax.device_get(tiny)


def main():
    print("devices:", jax.devices())
    # every timeit rep below lands in this window; dumped as a Chrome
    # trace at the end so experiments can be compared on one timeline
    tracing.set_capture(True, clear=True)
    key = np.random.default_rng(0)
    xs = [jnp.asarray(key.standard_normal(N).astype(np.float32))
          for _ in range(10)]
    for x in xs:
        x.block_until_ready()

    # 1. one big output, elementwise (bandwidth bound): 64MB out
    @jax.jit
    def one_out(a):
        return a * 1.0001 + 3.0

    timeit("1 output  x 64MB elementwise", one_out, xs[0])

    # 2. ten big outputs (640MB out total)
    @jax.jit
    def ten_out(*a):
        return [v * 1.0001 + 3.0 for v in a]

    timeit("10 outputs x 64MB elementwise", ten_out, *xs)

    # 3. twenty outputs from ten inputs (each input produces 2)
    @jax.jit
    def twenty_out(*a):
        out = []
        for v in a:
            out.append(v * 1.0001)
            out.append(v + 1.0)
        return out

    timeit("20 outputs x 64MB elementwise", twenty_out, *xs)

    # 4. ten tiny outputs from ten big inputs (reduction)
    @jax.jit
    def ten_tiny(*a):
        return [jnp.sum(v) for v in a]

    timeit("10 outputs x 4B (sums)", ten_tiny, *xs)

    # 5. gather-shaped: one permutation applied to 10 cols (10 big outputs)
    perm = jnp.asarray(key.permutation(N).astype(np.int32))
    perm.block_until_ready()

    @jax.jit
    def gather10(idx, *a):
        return [v[idx] for v in a]

    timeit("10 outputs x 64MB gather", gather10, perm, *xs)

    # 6. chain: big-output producer fn then tiny-output consumer fn
    @jax.jit
    def consumer(cols):
        return [jnp.sum(v) for v in cols]

    def chain(idx, *a):
        mids = gather10(idx, *a)
        return consumer(mids)

    timeit("chain gather10 -> sums (2 dispatches)", chain, perm, *xs)

    # 7. the real FilterExec on a lineitem-shaped batch
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.bench.tpch import _source
    from spark_rapids_tpu.exec.project import FilterExec
    from spark_rapids_tpu.exprs import expr as E

    li = tpch.gen_lineitem(2.0, seed=7)
    src = _source(li, batch_rows=1 << 24)
    for c in src._parts[0][0].columns:
        c.data.block_until_ready()
    cut = (np.datetime64("1998-09-03") - np.datetime64("1970-01-01")).astype(int)
    f = FilterExec(E.Lt(E.Col("l_shipdate"), E.Lit(int(cut), "date")), src)
    f._bind()
    batch = src._parts[0][0]

    def run_filter(b):
        return f._run(b)

    timeit("FilterExec 16M x 9col (1 dispatch)", run_filter, batch)

    # 8. filter_indices only (no gather)
    from spark_rapids_tpu.exec import kernels as K
    from spark_rapids_tpu.exprs import eval as EV

    cond = E.resolve(E.Lt(E.Col("l_shipdate"), E.Lit(int(cut), "date")),
                     src.output_schema)

    @jax.jit
    def just_indices(b):
        ctx = EV.EvalContext(b, False)
        pred = EV.eval_expr(cond, ctx)
        keep = pred.data & pred.validity
        return K.filter_indices(keep, b.active_mask())

    timeit("filter_indices only (2 outputs)", just_indices, batch)

    # 9. filter + gather but summing outputs on-device (tiny outputs)
    @jax.jit
    def filter_sum(b):
        ctx = EV.EvalContext(b, False)
        pred = EV.eval_expr(cond, ctx)
        keep = pred.data & pred.validity
        idx, n = K.filter_indices(keep, b.active_mask())
        out = K.gather_batch(b, idx, n)
        return [jnp.sum(c.data) for c in out.columns] + [n]

    timeit("filter+gather+sum fused (tiny outputs)", filter_sum, batch)

    # 10. filter exec then consume via sums (2 dispatches, big intermediates)
    @jax.jit
    def consume_batch(ob):
        return [jnp.sum(c.data) for c in ob.columns]

    def filter_then_sum(b):
        ob = f._run(b)
        return consume_batch(ob)

    timeit("FilterExec -> sums (2 dispatches)", filter_then_sum, batch)

    tracing.set_capture(False)
    from spark_rapids_tpu.obs import to_chrome_trace

    events = tracing.trace_events(clear=True)
    out_path = os.environ.get("PROBE_TRACE",
                              os.path.join("artifacts",
                                           "trace_perf_probe.json"))
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(to_chrome_trace(events, process_name="perf_probe"), f)
    print(f"chrome trace ({len(events)} spans):", out_path)


def dispatch_count(queries=("q1", "q3"), sf=0.005):
    """Dispatches per warm iteration, fusion on vs off (docs/fusion.md).

    Counts every call into a jitted callable during one full warm
    execution of a planner-built query. Warming and counting use two
    SEPARATE plan instances of the same query: compiled programs are
    process-wide (shared_jit + module-level jax.jit), so the second
    instance runs warm, but its shuffle exchanges have not materialized
    yet — re-executing the SAME node would skip the whole pre-shuffle
    pipeline (ShuffleExchangeExec writes map outputs once) and count
    nothing. The whole-stage fusion claim is that this count drops by
    >= 2x: one program per stage per batch (windowed for aggregates)
    instead of one per operator per batch.
    """
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.config.conf import RapidsConf

    tables = tpch.tables_for(sf, seed=3)
    results = {}
    for qn in queries:
        per = {}
        for fused in (False, True):
            conf = RapidsConf(
                {"spark.rapids.tpu.sql.fusion.enabled": fused})

            def fresh_plan():
                d = tpch.df_tables(tables, conf, shuffle_partitions=2,
                                   partitions=2, batch_rows=512)
                return tpch.DF_QUERIES[qn](d).physical_plan()

            def run_once(node):
                for p in range(node.num_partitions()):
                    for _ in node.execute(p):
                        pass

            run_once(fresh_plan())  # warm: trace + compile
            node = fresh_plan()
            _dispatches["n"] = 0
            run_once(node)
            per["fused" if fused else "classic"] = _dispatches["n"]
        per["ratio"] = round(per["classic"] / max(per["fused"], 1), 2)
        results[qn] = per
        print(f"{qn}: classic={per['classic']} fused={per['fused']} "
              f"ratio={per['ratio']}x", file=sys.stderr, flush=True)
    print(json.dumps({"dispatch_counts_per_iteration": results,
                      "sf": sf, "batch_rows": 512, "partitions": 2}))
    return results


def _lane_of(name: str) -> str:
    """Trace-span -> pipeline-lane mapping for the overlap report."""
    if name == "scan:decode":
        return "decode"
    if name == "scan:upload":
        return "upload"
    if name.startswith("prefetch:"):
        return "prefetch-worker"
    if name == "PrefetchExec":
        return "prefetch-wait"
    if name.startswith("shuffle:"):
        return "shuffle"
    if name.endswith("ScanExec"):
        return "scan-iter"
    return "compute"


def _merge_intervals(spans):
    """[(start, end)] -> disjoint sorted union."""
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _intersect_s(a, b):
    """Total seconds the two disjoint interval lists overlap."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if lo < hi:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total / 1e9


def overlap(sf=None, n_files=None, reps=2):
    """``python tools/perf_probe.py overlap`` — the async-pipeline proof
    (docs/async_pipeline.md): a scan-bound Q6 over a multi-file parquet
    lineitem, prefetch on vs off. Reports wall time both ways, the scan
    throughput ratio, per-lane busy time from the captured trace, and how
    long each host lane ran CONCURRENTLY with device compute. The
    prefetch-on trace is exported for Perfetto (lanes land on distinct
    tracks because the exporter assigns one tid per producing thread)."""
    import shutil
    import tempfile

    import pyarrow.parquet as pq

    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.config.conf import RapidsConf
    from spark_rapids_tpu.obs import to_chrome_trace
    from spark_rapids_tpu.plan import read_parquet

    sf = float(os.environ.get("OVERLAP_SF", sf or 0.3))
    n_files = int(os.environ.get("OVERLAP_FILES", n_files or 8))
    li = tpch.gen_lineitem(sf, seed=7)
    tmp = tempfile.mkdtemp(prefix="srtpu_overlap_")
    paths = []
    step = (li.num_rows + n_files - 1) // n_files
    for i in range(n_files):
        p = os.path.join(tmp, f"lineitem_{i:02d}.parquet")
        pq.write_table(li.slice(i * step, step), p)
        paths.append(p)

    def run(enabled, capture):
        conf = RapidsConf(
            {"spark.rapids.tpu.sql.prefetch.enabled": enabled})
        d = {"lineitem": read_parquet(paths, conf=conf)}
        q = tpch.DF_QUERIES["q6"](d)
        best, events = None, []
        for _ in range(reps):
            if capture:
                tracing.set_capture(True, clear=True)
            t0 = time.perf_counter()
            out = q.to_arrow()
            dt = time.perf_counter() - t0
            if capture:
                tracing.set_capture(False)
            if best is None or dt < best[0]:
                best = (dt, out)
                if capture:
                    events = tracing.trace_events(clear=True)
        return best[0], best[1], events

    try:
        on_s, on_out, events = run(True, capture=True)
        off_s, off_out, _ = run(False, capture=False)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    assert on_out.equals(off_out), "prefetch changed q6 results"

    lanes = {}
    for ev in events:
        lanes.setdefault(_lane_of(ev["name"]), []).append(ev)
    merged = {ln: _merge_intervals(
                  [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in evs])
              for ln, evs in lanes.items()}
    busy = {ln: round(sum(e - s for s, e in iv) / 1e9, 4)
            for ln, iv in merged.items()}
    threads = {ln: len({e["thread"] for e in evs})
               for ln, evs in lanes.items()}
    compute = merged.get("compute", [])
    conc = {ln: round(_intersect_s(iv, compute), 4)
            for ln, iv in merged.items() if ln != "compute"}

    trace_path = os.environ.get("PROBE_TRACE",
                                os.path.join("artifacts",
                                             "trace_overlap.json"))
    os.makedirs(os.path.dirname(trace_path) or ".", exist_ok=True)
    with open(trace_path, "w") as f:
        json.dump(to_chrome_trace(events, process_name="overlap"), f)

    print(json.dumps({
        "mode": "overlap",
        # overlap can only beat serial execution when the host has cores to
        # run lanes on (or the device is a real accelerator): on a 1-core
        # host the ratio is ~1.0 by construction and the lane-concurrency
        # numbers below are the meaningful output
        "host_cores": os.cpu_count(),
        "sf": sf, "files": n_files, "rows": li.num_rows,
        "prefetch_on_s": round(on_s, 4),
        "prefetch_off_s": round(off_s, 4),
        "scan_throughput_ratio": round(off_s / on_s, 3),
        "lane_busy_s": busy,
        "lane_threads": threads,
        "lane_concurrent_with_compute_s": conc,
        "trace": trace_path,
    }))


def roofline(sizes=(1 << 24, 1 << 26, 1 << 28), reps=3):
    """``python tools/perf_probe.py roofline`` — the delivered-bandwidth
    ceiling bench.py's per-query ``roofline_util`` divides by, swept over
    buffer sizes so the fixed dispatch cost is visible (small
    buffers under-report the ceiling; the largest size is the anchor).

    Two kernels per size: a pipelined f32 reduce (read-only traffic, the
    same shape bench.py measures) and an elementwise copy-scale (read +
    write, counts both directions). Prints one JSON object; the driver
    ceiling is ``roofline_GBps`` = the reduce bandwidth at the largest
    size, matching bench.py."""
    sizes = tuple(int(s) for s in os.environ.get(
        "ROOFLINE_SIZES", ",".join(map(str, sizes))).split(","))

    @jax.jit
    def red(v, s):
        return jnp.sum(v * (1.0 + s))

    @jax.jit
    def ewise(v, s):
        return v * (1.0001 + s) + 3.0

    points = []
    for n in sizes:
        x = jnp.ones(n, jnp.float32)
        x.block_until_ready()
        per = {"elems": n, "buffer_MB": round(4 * n / 1e6, 1)}
        for name, fn, bytes_per_elem in (("reduce", red, 4),
                                         ("copy_scale", ewise, 8)):
            fn(x, 0.0).block_until_ready()
            best = 0.0
            for r in range(reps):
                t0 = time.perf_counter()
                outs = [fn(x, 1e-9 * (r * 4 + i)) for i in range(4)]
                for o in outs:
                    o.block_until_ready()
                dt = (time.perf_counter() - t0) / 4
                best = max(best, bytes_per_elem * n / dt)
            per[f"{name}_GBps"] = round(best / 1e9, 3)
        points.append(per)
        print(f"n={n:>10d} reduce={per['reduce_GBps']:8.3f} GB/s "
              f"copy_scale={per['copy_scale_GBps']:8.3f} GB/s",
              file=sys.stderr, flush=True)
    print(json.dumps({
        "mode": "roofline",
        "devices": [str(d) for d in jax.devices()],
        "points": points,
        "roofline_GBps": points[-1]["reduce_GBps"],
    }))
    return points


def reuse_report(queries=("q1", "q2", "q59"), sf=0.002):
    """``python tools/perf_probe.py reuse`` — per-query duplicate-subtree
    counts and reuse hits (docs/exchange_reuse.md).

    For each CTE-shaped tracker TPC-DS query: how many repeated reusable
    subtrees the fingerprint pass finds (with the rewrite disabled, so the
    raw duplicates are visible), then the reuse counters + bytes saved from
    actually executing with the rewrite on, plus a bit-identical check
    against the rewrite off."""
    from spark_rapids_tpu.bench import tpcds_queries as Q
    from spark_rapids_tpu.bench.tpcds_schema import tables_for
    from spark_rapids_tpu.config.conf import RapidsConf
    from spark_rapids_tpu.exec import reuse as R
    from spark_rapids_tpu.plan import from_arrow
    from spark_rapids_tpu.plan.reuse import duplicate_groups

    tables = tables_for(sf, seed=42)

    def build(name, reuse_on, fusion=True):
        conf = RapidsConf({"spark.rapids.tpu.sql.exchange.reuse.enabled":
                           reuse_on,
                           "spark.rapids.tpu.sql.fusion.enabled": fusion})
        d = {}
        for k, v in tables.items():
            df = from_arrow(v, conf)
            df.shuffle_partitions = 2
            d[k] = df
        return Q.QUERIES[name](d)

    results = {}
    for qn in queries:
        # duplicate probe on the pre-fusion shape: fused stages fingerprint
        # opaque, which is exactly why the rewrite runs before fusion
        raw_plan = build(qn, False, fusion=False).physical_plan()
        dups = duplicate_groups(raw_plan)
        off = build(qn, False).to_arrow()
        R.reset_counters()
        on = build(qn, True).to_arrow()
        c = R.counters()
        results[qn] = {
            "duplicate_groups": dups,
            "reused_exchanges": c["reuse_exchanges_total"],
            "reused_broadcasts": c["reuse_broadcasts_total"],
            "reused_subqueries": c["reuse_subqueries_total"],
            "bytes_saved": c["reuse_bytes_saved_total"],
            "bit_identical": on.equals(off),
        }
        print(f"{qn}: dups={len(dups)} "
              f"exchanges={c['reuse_exchanges_total']} "
              f"bytes_saved={c['reuse_bytes_saved_total']} "
              f"identical={on.equals(off)}", file=sys.stderr, flush=True)
    print(json.dumps({"reuse": results, "sf": sf}))
    return results


if __name__ == "__main__":
    if _DISPATCH_MODE:
        dispatch_count()
    elif "overlap" in sys.argv[1:]:
        overlap()
    elif "reuse" in sys.argv[1:]:
        reuse_report()
    elif "roofline" in sys.argv[1:]:
        roofline()
    else:
        main()
