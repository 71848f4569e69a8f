"""Benchmark driver: TPC-H Q1/Q3/Q5/Q6 (SF2) + a TPC-DS subset (SF1)
through the PLANNER (Overrides.apply — never hand-assembled exec trees,
matching the reference where every plan comes from the rewrite,
GpuOverrides.scala:4541) on the TPU engine vs host-CPU baselines.

Prints JSON lines; the LAST is the driver metric
{"metric", "value", "unit", "vs_baseline", "utilization", ...}.

Methodology: built for a platform that no longer exists (its depth
pipelining and rotated input copies answered that platform's fixed
round-trip cost and result memoization), not used for chip measurement on
the current machine, and to be replaced by ROADMAP S1; `chip_smoke.py` is
what runs on the chip today.

- Correctness gates: copy 0 of every query is checked row-for-row
  against an independent baseline before timing (TPC-H: hand-vectorized
  pandas; TPC-DS: this framework's CPU fallback engine, which shares no
  device code with the TPU path).

``vs_baseline`` is the speedup over the same queries on the host CPU:
TPC-H against the hand-written pandas/numpy implementations below (the
in-environment stand-in for CPU Spark; the reference repo publishes no
absolute numbers, BASELINE.md), TPC-DS against the framework's CPU
engine (vectorized numpy/pandas operators, plan/cpu.py).

``utilization`` anchors the headline to the roofline: bytes the TPC-H
queries touch per second divided by the MEASURED device reduce-bandwidth
ceiling.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

# env overrides are for smoke tests only; driver runs use the defaults
SF_H = float(os.environ.get("BENCH_SF_H", 2.0))    # TPC-H: 12M lineitem rows
SF_DS = float(os.environ.get("BENCH_SF_DS", 1.0))  # TPC-DS: 2.88M store_sales
COPIES_H = 3     # pre-staged permuted input copies (TPC-H)
COPIES_DS = 2
RUNS = int(os.environ.get("BENCH_RUNS", 3))
DEPTH = int(os.environ.get("BENCH_DEPTH", 3))  # pipelined iters per timed run
TPCDS_QUERIES = ["q3", "q7", "q42", "q52", "q96"]


# ---------------------------------------------------------------------------
# CPU baselines (hand-vectorized pandas/numpy) — TPC-H
# ---------------------------------------------------------------------------

def _cpu_tpch(li, orders, cust, supp, nation, region):
    import pandas as pd

    df = li.to_pandas()
    odf = orders.to_pandas()
    cdf = cust.to_pandas()
    sdf = supp.to_pandas()
    ndf = nation.to_pandas()
    rdf = region.to_pandas()
    ship = df.l_shipdate.to_numpy().astype("datetime64[D]").astype(np.int64)
    lo = (np.datetime64("1994-01-01") - np.datetime64("1970-01-01")).astype(int)
    hi = (np.datetime64("1995-01-01") - np.datetime64("1970-01-01")).astype(int)
    cut = (np.datetime64("1998-09-03") - np.datetime64("1970-01-01")).astype(int)

    def q6():
        m = ((ship >= lo) & (ship < hi)
             & (df.l_discount.to_numpy() >= 0.05 - 1e-9)
             & (df.l_discount.to_numpy() < 0.07 + 1e-9)
             & (df.l_quantity.to_numpy() < 24))
        return float((df.l_extendedprice.to_numpy()[m]
                      * df.l_discount.to_numpy()[m]).sum())

    def q1():
        f = df[ship < cut].copy()
        f["disc_price"] = f.l_extendedprice * (1 - f.l_discount)
        f["charge"] = f.disc_price * (1 + f.l_tax)
        return (f.groupby(["l_returnflag", "l_linestatus"], sort=True)
                .agg(sum_qty=("l_quantity", "sum"),
                     sum_base=("l_extendedprice", "sum"),
                     sum_disc=("disc_price", "sum"),
                     sum_charge=("charge", "sum"),
                     avg_qty=("l_quantity", "mean"),
                     avg_price=("l_extendedprice", "mean"),
                     avg_disc=("l_discount", "mean"),
                     n=("l_quantity", "size")))

    def q3():
        c = cdf[cdf.c_mktsegment == "BUILDING"]
        o = odf[odf.o_orderdate.to_numpy().astype("datetime64[D]")
                < np.datetime64("1995-03-15")]
        ll = df[df.l_shipdate.to_numpy().astype("datetime64[D]")
                >= np.datetime64("1995-03-16")]
        oc = o.merge(c, left_on="o_custkey", right_on="c_custkey")
        j = ll.merge(oc, left_on="l_orderkey", right_on="o_orderkey")
        j["rev"] = j.l_extendedprice * (1 - j.l_discount)
        return (j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
                .agg(revenue=("rev", "sum")).reset_index()
                .sort_values(["revenue", "o_orderdate"],
                             ascending=[False, True]).head(10))

    def q5():
        r = rdf[rdf.r_name == "ASIA"]
        n = ndf.merge(r, left_on="n_regionkey", right_on="r_regionkey")
        s = sdf.merge(n, left_on="s_nationkey", right_on="n_nationkey")
        od = odf.o_orderdate.to_numpy().astype("datetime64[D]")
        o = odf[(od >= np.datetime64("1994-01-01"))
                & (od < np.datetime64("1995-01-01"))]
        co = o.merge(cdf, left_on="o_custkey", right_on="c_custkey")
        lco = df.merge(co, left_on="l_orderkey", right_on="o_orderkey")
        ls = lco.merge(s, left_on=["l_suppkey", "c_nationkey"],
                       right_on=["s_suppkey", "s_nationkey"])
        ls["rev"] = ls.l_extendedprice * (1 - ls.l_discount)
        return (ls.groupby("n_name").agg(revenue=("rev", "sum"))
                .reset_index().sort_values("revenue", ascending=False))

    return {"q1": q1, "q3": q3, "q5": q5, "q6": q6}


def _measure_roofline(n=1 << 28, reps=3):
    """Delivered device reduce bandwidth: bytes/s of a
    pipelined f32 sum (1GB at the default ``n``). ``n``/``reps`` shrink
    under a tight --budget — a cheap measurement is still a valid ceiling
    estimate, and per-query roofline_util lines must never go missing."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones(n, jnp.float32)
    x.block_until_ready()

    @jax.jit
    def red(v, s):
        return jnp.sum(v * (1.0 + s))

    red(x, 0.0).block_until_ready()
    best = 0.0
    for r in range(reps):
        t0 = time.perf_counter()
        outs = [red(x, 1e-9 * (r * 4 + i)) for i in range(4)]
        for o in outs:
            o.block_until_ready()
        dt = (time.perf_counter() - t0) / 4
        best = max(best, 4 * n / dt)
    return best


def _permute(table, seed):
    rng = np.random.default_rng(seed)
    return table.take(rng.permutation(table.num_rows))


def _canon(rows):
    def key(v):
        if v is None:
            return (0, "")
        if isinstance(v, float):
            return (1, round(v, 6))
        if isinstance(v, int):
            return (1, float(v))
        return (2, str(v))

    return sorted((tuple(r.values()) for r in rows),
                  key=lambda t: tuple(key(v) for v in t))


def _rows_match(a, b, rel=1e-6):
    """Canonically sorted row-set equality with float tolerance (the TPU
    backend's f64 is a double-double with ~1e-14 relative noise)."""
    ca, cb = _canon(a), _canon(b)
    if len(ca) != len(cb):
        return False
    for ra, rb in zip(ca, cb):
        if len(ra) != len(rb):
            return False
        for va, vb in zip(ra, rb):
            if isinstance(va, float) or isinstance(vb, float):
                if va is None or vb is None:
                    return False
                if abs(va - vb) > rel * max(1.0, abs(va), abs(vb)):
                    return False
            elif va != vb:
                return False
    return True


def _mark(msg):
    print(f"[bench] {time.strftime('%H:%M:%S')} {msg}", file=sys.stderr,
          flush=True)


class _Budget:
    """Soft wall-clock budget (--budget SECONDS).

    Phases deduct their measured wall time; downstream phases consult
    ``remaining()`` and shrink the knobs that only affect statistical
    quality (COPIES / RUNS / DEPTH, the reused-buffer comparison runs,
    roofline reps, profile dumps). Correctness gates are NEVER skipped or
    shrunk, and the final driver-metric line is always emitted — a budget
    run degrades to fewer/noisier samples, not to rc=124 with no metric.
    """

    def __init__(self, total):
        self.total = total
        self.t0 = time.perf_counter()

    @property
    def enabled(self):
        return self.total is not None

    def remaining(self):
        if self.total is None:
            return float("inf")
        return self.total - (time.perf_counter() - self.t0)


def _faults_guard(faults_spec, environ, pool_cap=None):
    """Chaos and capped-pool runs must never shrink correctness coverage:
    with a fault schedule or a --pool-cap active, refuse the BENCH_* env
    overrides that scale down the inputs/runs the differential gates
    compare. (The --budget shrinkage of statistical knobs is already
    gate-safe by construction; the envs are not — they change WHAT is
    checked, not how often.)"""
    if not faults_spec and not pool_cap:
        return
    flag = "--faults" if faults_spec else "--pool-cap"
    banned = [k for k in ("BENCH_SF_H", "BENCH_SF_DS", "BENCH_RUNS",
                          "BENCH_DEPTH") if k in environ]
    if banned:
        raise SystemExit(
            f"{flag} is set: refusing to run with correctness-gate "
            f"overrides {banned} (chaos/memory-pressure runs must execute "
            f"the full differential check)")


def main(budget_s=None, faults=None, pool_cap=None):
    import jax
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.bench import tpcds_queries as DSQ
    from spark_rapids_tpu.bench.tpcds_schema import tables_for as ds_tables
    from spark_rapids_tpu.config.conf import RapidsConf
    from spark_rapids_tpu.plan import from_arrow
    from spark_rapids_tpu.utils.sync import fence

    _faults_guard(faults, os.environ, pool_cap=pool_cap)
    # An external timeout (timeout -k N) delivers SIGTERM before SIGKILL;
    # convert it to SystemExit so the finally block below still flushes the
    # final driver-metric line (rc stays non-zero — the run is degraded,
    # not silently healthy).
    import signal

    def _on_term(signum, frame):
        raise SystemExit(124)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass  # non-main thread (tests drive main() directly)
    if pool_cap:
        # memory-pressure run: replace the process pool with a capped one so
        # every device allocation contends for the reduced budget — spill,
        # retry, and agg repartition all fire for real (the correctness
        # gates below then prove results are unchanged under pressure)
        from spark_rapids_tpu.mem.pool import HbmPool, set_pool
        set_pool(HbmPool(int(pool_cap)))
        _mark(f"pool capped at {int(pool_cap)} bytes")
    dev_conf = RapidsConf(
        {"spark.rapids.tpu.test.faults": faults} if faults else {})
    cpu_conf = RapidsConf({"spark.rapids.tpu.sql.enabled": False})
    bud = _Budget(budget_s)

    # ---- TPC-H sources + permuted copies --------------------------------
    t_gen = time.perf_counter()
    base_h = {
        "lineitem": tpch.gen_lineitem(SF_H, seed=7),
        "orders": tpch.gen_orders(SF_H, seed=8),
        "customer": tpch.gen_customer(SF_H, seed=9),
        "supplier": tpch.gen_supplier(SF_H, seed=10),
        "nation": tpch.gen_nation(),
        "region": tpch.gen_region(),
    }
    t_gen = time.perf_counter() - t_gen
    copies_h_n = COPIES_H
    if bud.enabled:
        # each extra copy re-pays roughly a base generation (permute) plus
        # its uploads/compiles downstream; cap copy cost at ~20% of what's
        # left so the mandatory gates + timed runs always fit
        while copies_h_n > 1 and (copies_h_n - 1) * t_gen > 0.2 * bud.remaining():
            copies_h_n -= 1
        _mark(f"budget: COPIES_H={copies_h_n} (of {COPIES_H}), "
              f"{bud.remaining():.0f}s left")
    copies_h = [base_h] + [
        {k: _permute(v, 100 + 7 * c + i) for i, (k, v) in
         enumerate(base_h.items())}
        for c in range(1, copies_h_n)
    ]
    h_names = ["q1", "q3", "q5", "q6"]

    def build_plans(tables, conf, builders, names, batch_rows):
        plans = {}
        for qn in names:
            d = {k: from_arrow(v, conf, batch_rows=batch_rows)
                 for k, v in tables.items()}
            plans[qn] = builders[qn](d).physical_plan()
        return plans

    _mark("tpch plans+uploads")
    h_plans = [build_plans(tabs, dev_conf, tpch.DF_QUERIES, h_names, 1 << 24)
               for tabs in copies_h]

    def run_plan(node):
        out = []
        for p in range(node.num_partitions()):
            out.extend(node.execute(p))
        return node, out

    # ---- correctness gates (copy 0, row-for-row) ------------------------
    from spark_rapids_tpu.columnar.batch import batch_to_arrow

    _mark("tpch correctness gates")
    cpu_h = _cpu_tpch(*[base_h[k] for k in
                        ("lineitem", "orders", "customer", "supplier",
                         "nation", "region")])
    q6_exp = cpu_h["q6"]()
    node, bs = run_plan(h_plans[0]["q6"])
    got = [r for b in bs for r in batch_to_arrow(b, node.output_schema).to_pylist()]
    assert abs(got[0]["revenue"] - q6_exp) <= 1e-6 * abs(q6_exp)
    q1_exp = cpu_h["q1"]()
    node, bs = run_plan(h_plans[0]["q1"])
    got = [r for b in bs for r in batch_to_arrow(b, node.output_schema).to_pylist()]
    assert len(got) == len(q1_exp)
    for row, (_, e) in zip(got, q1_exp.reset_index().iterrows()):
        assert row["l_returnflag"] == e.l_returnflag
        assert row["count_order"] == e.n
        assert abs(row["sum_disc_price"] - e.sum_disc) <= 1e-9 * abs(e.sum_disc)
    q3_exp = cpu_h["q3"]().reset_index(drop=True)
    node, bs = run_plan(h_plans[0]["q3"])
    got = [r for b in bs for r in batch_to_arrow(b, node.output_schema).to_pylist()]
    assert len(got) == len(q3_exp)
    for row, (_, e) in zip(got, q3_exp.iterrows()):
        assert row["l_orderkey"] == e.l_orderkey, (row, dict(e))
        assert abs(row["revenue"] - e.revenue) <= 1e-6 * abs(e.revenue)
    q5_exp = cpu_h["q5"]().reset_index(drop=True)
    node, bs = run_plan(h_plans[0]["q5"])
    got = [r for b in bs for r in batch_to_arrow(b, node.output_schema).to_pylist()]
    assert len(got) == len(q5_exp)
    for row, (_, e) in zip(got, q5_exp.iterrows()):
        assert row["n_name"] == e.n_name
        assert abs(row["revenue"] - e.revenue) <= 1e-6 * abs(e.revenue)

    _mark("tpch cpu baseline")
    # CPU baseline timing (TPC-H)
    cpu_times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for qn in h_names:
            cpu_h[qn]()
        cpu_times.append(time.perf_counter() - t0)
    cpu_h_s = min(cpu_times)

    # ---- timed-run machinery (shared by both suites) --------------------
    def timed(plan_copies, names, runs, depth, rotate):
        times = []
        it = 0
        for _ in range(runs):
            t0 = time.perf_counter()
            outs = []
            for _ in range(depth):
                plans = plan_copies[it % len(plan_copies) if rotate else 0]
                it += 1
                for qn in names:
                    outs.append(run_plan(plans[qn])[1])
            fence(outs)
            times.append((time.perf_counter() - t0) / depth)
        return min(times), sorted(times)[len(times) // 2]

    def warm_and_time(plan_copies, names, frac):
        """Warm every copy (compile + first run), size RUNS/DEPTH to the
        budget share ``frac`` of what's left, then run the fresh-input and
        reused-buffer timings. Returns (fresh, reused, t_iter); reused is
        (None, None) when the budget cannot afford the comparison pass."""
        t_iter = time.perf_counter()
        for qn in names:
            fence([run_plan(plan_copies[0][qn])[1]])
        t_iter = time.perf_counter() - t_iter
        for plans in plan_copies[1:]:
            for qn in names:
                fence([run_plan(plans[qn])[1]])
        runs, depth = RUNS, DEPTH
        do_reused = True
        if bud.enabled:
            # fresh blocks cost ~runs*depth iterations; reused doubles that
            avail = max(frac * bud.remaining(), t_iter)
            while runs * depth * t_iter * 2 > avail and (runs > 1 or depth > 1):
                if depth > 1:
                    depth -= 1
                else:
                    runs -= 1
            do_reused = runs * depth * t_iter * 2 * 2 <= avail
            _mark(f"budget: RUNS={runs} DEPTH={depth} reused={do_reused} "
                  f"(iter~{t_iter:.1f}s, {bud.remaining():.0f}s left)")
        fresh = timed(plan_copies, names, runs, depth, rotate=True)
        reused = (timed(plan_copies, names, runs, depth, rotate=False)
                  if do_reused else (None, None))
        return fresh, reused, t_iter

    def _r(v, nd):
        return round(v, nd) if v is not None else None

    def _mem_window_start():
        """Memory baseline for a suite's timed window: spill byte counters
        (delta across the window) and the tracked-peak watermark."""
        from spark_rapids_tpu.utils import task_metrics as TM
        return TM.aggregate_snapshot()

    def _mem_window_end(tm0):
        from spark_rapids_tpu.obs import gauges as G
        from spark_rapids_tpu.utils import task_metrics as TM
        tm1 = TM.aggregate_snapshot()
        spill = sum(max(0, tm1.get(f, 0) - tm0.get(f, 0))
                    for f in ("spill_to_host_bytes", "spill_to_disk_bytes"))
        return {"peak_hbm_bytes": G.snapshot()["mem_tracked_peak_bytes"],
                "spill_bytes": spill}

    def suite_line(suite, fresh, reused, cpu_s, rows, mem=None):
        """Per-suite metric line, flushed the moment the suite is measured —
        a run killed during a later suite's setup still reports this one."""
        print(json.dumps({
            "suite": suite,
            "s_per_iter": {"fresh_min": round(fresh[0], 4),
                           "fresh_median": round(fresh[1], 4),
                           "reused_min": _r(reused[0], 4),
                           "reused_median": _r(reused[1], 4)},
            "cpu_s": round(cpu_s, 3),
            "rows_per_sec": round(rows / fresh[0], 1),
            **(mem or {}),
        }), flush=True)

    # ---- TPC-H timed runs (metric line lands BEFORE TPC-DS setup) ------
    _mark("tpch warmup + timed runs")
    # TPC-DS is still ahead: spend at most half the remaining budget here
    tm0_h = _mem_window_start()
    h_fresh, h_reused, t_iter_h = warm_and_time(h_plans, h_names, 0.5)
    mem_h = _mem_window_end(tm0_h)
    li, orders, cust = base_h["lineitem"], base_h["orders"], base_h["customer"]
    rows_h = (2 * li.num_rows                       # q1 + q6
              + li.num_rows + orders.num_rows + cust.num_rows   # q3
              + li.num_rows + orders.num_rows + cust.num_rows)  # q5
    suite_line("tpch", h_fresh, h_reused, cpu_h_s, rows_h, mem=mem_h)

    def q_bytes(table, cols):
        return sum(table.column(c).nbytes for c in cols)

    bytes_h = (
        q_bytes(li, ["l_shipdate", "l_discount", "l_quantity",
                     "l_extendedprice"])
        + q_bytes(li, ["l_shipdate", "l_quantity", "l_extendedprice",
                       "l_discount", "l_tax", "l_returnflag", "l_linestatus"])
        + q_bytes(li, ["l_shipdate", "l_orderkey", "l_extendedprice",
                       "l_discount"])
        + q_bytes(orders, ["o_orderkey", "o_custkey", "o_orderdate",
                           "o_shippriority"])
        + q_bytes(cust, ["c_custkey", "c_mktsegment"])
        + q_bytes(li, ["l_orderkey", "l_suppkey", "l_extendedprice",
                       "l_discount"])
        + q_bytes(orders, ["o_orderkey", "o_custkey", "o_orderdate"])
        + q_bytes(cust, ["c_custkey", "c_nationkey"])
    )

    # Everything below fills this state; the finally block flushes the
    # final driver-metric lines from whatever completed. A budgeted or
    # externally-timed-out run degrades to null fields, never to a dead
    # process with no parseable metric line.
    ds_fresh = ds_reused = (None, None)
    cpu_ds_s = 0.0
    rows_ds = 0
    t_iter_ds = 0.0
    ds_ran = False
    roofline = None
    profile_files, trace_files = [], []
    prom_path = None
    try:
        # ---- TPC-DS sources + plans ---------------------------------
        run_ds = not (bud.enabled
                      and bud.remaining() < max(60.0, 8 * t_iter_h))
        if not run_ds:
            _mark(f"budget: skipping tpcds suite "
                  f"({bud.remaining():.0f}s left)")
        if run_ds:
            _mark("tpcds gen+plans")
            t_gen_ds = time.perf_counter()
            base_ds = ds_tables(SF_DS)
            t_gen_ds = time.perf_counter() - t_gen_ds
            copies_ds_n = COPIES_DS
            if bud.enabled:
                while copies_ds_n > 1 and (copies_ds_n - 1) * t_gen_ds > 0.2 * bud.remaining():
                    copies_ds_n -= 1
                _mark(f"budget: COPIES_DS={copies_ds_n} (of {COPIES_DS}), "
                      f"{bud.remaining():.0f}s left")
            copies_ds = [base_ds] + [
                {k: _permute(v, 500 + 11 * c + i) for i, (k, v) in
                 enumerate(base_ds.items())}
                for c in range(1, copies_ds_n)
            ]
            ds_plans = [build_plans(tabs, dev_conf, DSQ.QUERIES,
                                    TPCDS_QUERIES, 1 << 22)
                        for tabs in copies_ds]
            if bud.enabled and bud.remaining() < max(30.0, 6 * t_iter_h):
                _mark(f"budget: skipping tpcds correctness+timed "
                      f"({bud.remaining():.0f}s left)")
                run_ds = False
        if run_ds:
            # TPC-DS correctness vs the CPU engine + CPU baseline timing
            _mark("tpcds correctness + cpu baseline")
            for qn in TPCDS_QUERIES:
                d = {k: from_arrow(v, cpu_conf) for k, v in base_ds.items()}
                cdf = DSQ.QUERIES[qn](d)
                t0 = time.perf_counter()
                cpu_rows = cdf.collect()
                cpu_ds_s += time.perf_counter() - t0
                node, bs = run_plan(ds_plans[0][qn])
                dev_rows = [
                    r for b in bs
                    for r in batch_to_arrow(b, node.output_schema).to_pylist()]
                assert _rows_match(dev_rows, cpu_rows), f"tpcds {qn} mismatch"

            # ---- TPC-DS timed runs ----------------------------------
            _mark("tpcds warmup + timed runs")
            tm0_ds = _mem_window_start()
            ds_fresh, ds_reused, t_iter_ds = warm_and_time(
                ds_plans, TPCDS_QUERIES, 0.75)
            mem_ds = _mem_window_end(tm0_ds)
            rows_ds = sum(base_ds["store_sales"].num_rows
                          for _ in TPCDS_QUERIES)
            suite_line("tpcds", ds_fresh, ds_reused, cpu_ds_s, rows_ds,
                       mem=mem_ds)
            ds_ran = True
        t_iter = t_iter_h + t_iter_ds

        if not bud.enabled or bud.remaining() > 20:
            _mark("roofline")
            roofline = _measure_roofline()
        else:
            # tight budget: a 1-rep 64MB sweep costs well under a second
            # and keeps roofline_util on every per-query line
            _mark("budget: cheap roofline")
            roofline = _measure_roofline(n=1 << 24, reps=1)

        # ---- per-query profile artifacts (docs/observability.md) --------
        # Untimed pass on freshly planned copies so per-node metrics reflect
        # exactly one execution (the timed plans have accumulated RUNS*DEPTH
        # iterations); traceCapture gives each dump a Perfetto-loadable
        # trace.
        do_profiles = not bud.enabled or bud.remaining() > 2 * t_iter + 15
        if not do_profiles:
            _mark("budget: skipping profile dumps")
        _mark("profile dumps")
        from spark_rapids_tpu.obs import profile_for

        prof_conf = RapidsConf(
            {"spark.rapids.tpu.profile.traceCapture": True})
        prof_dir = os.environ.get("BENCH_PROFILE_DIR", "artifacts")
        os.makedirs(prof_dir, exist_ok=True)
        specs = []
        if do_profiles:
            specs = [("tpch", qn, base_h, tpch.DF_QUERIES, 1 << 24)
                     for qn in h_names]
            if ds_ran:
                specs += [("tpcds", qn, base_ds, DSQ.QUERIES, 1 << 22)
                          for qn in TPCDS_QUERIES]
        from spark_rapids_tpu.obs import histo as _histo
        batch_histo = _histo.get("batch_op_ns")
        from spark_rapids_tpu.obs import memtrack as _mt
        for suite, qn, tabs, builders, batch_rows in specs:
            if bud.enabled and bud.remaining() < 1.5 * t_iter + 10:
                _mark(f"budget: stopping profile dumps at {suite}_{qn} "
                      f"({bud.remaining():.0f}s left)")
                break
            # record which tables the query builder touches — their arrow
            # bytes anchor the bytes-touched estimate below (intermediate
            # HBM attribution only sees pooled/spillable allocations)
            accessed = set()

            class _Rec(dict):
                def __getitem__(self, k, _a=accessed):
                    _a.add(k)
                    return dict.__getitem__(self, k)

            d = _Rec({k: from_arrow(v, prof_conf, batch_rows=batch_rows)
                      for k, v in tabs.items()})
            node = builders[qn](d).physical_plan()
            prof = profile_for(node)
            b0 = batch_histo.snapshot()
            # run_plan drives the exec tree directly (no DataFrame), so open
            # the attribution window the dataframe layer would normally own
            if prof is not None:
                _mt.begin_query(prof.query_id)
            try:
                fence([run_plan(node)[1]])
            finally:
                if prof is not None:
                    _mt.end_query(prof.query_id)
            if prof is None:
                continue
            prof.finish(node)
            # per-query metric line: wall, plan/compile/execute attribution,
            # and batch-op tail percentiles over exactly this query's window
            win = _histo.diff(b0, batch_histo.snapshot())
            ph = prof.phases
            # bytes the query touched: arrow bytes of every input table the
            # builder referenced (each is read at least once), plus tracked
            # pooled-HBM allocations (written once each) and spill round
            # trips. Utilization divides by execute-phase time — this
            # untimed pass pays full compile, which is not bandwidth.
            input_bytes = sum(tabs[k].nbytes for k in accessed)
            mem_ops = prof.memory.get("ops", {})
            alloc_bytes = sum(int(g.get("allocd", 0))
                              for g in mem_ops.values())
            spill_rw = sum(prof.task_metrics.get(f, 0) for f in
                           ("spill_to_host_bytes", "spill_to_disk_bytes",
                            "read_spill_bytes"))
            bytes_touched = input_bytes + alloc_bytes + spill_rw
            ex_s = (ph.get("execute") or prof.wall_ns / 1e6) / 1e3
            print(json.dumps({
                "query": f"{suite}_{qn}",
                "wall_ms": round(prof.wall_ns / 1e6, 3),
                "phases_ms": {
                    "plan": round(sum(ph.get(p, 0.0) for p in
                                      ("plan-rewrite", "reuse", "fusion",
                                       "prefetch")), 3),
                    "compile": ph.get("compile", 0.0),
                    "execute": ph.get("execute", 0.0),
                },
                "batch_op_ms": batch_histo.percentiles_ms(win),
                # per-query HBM attribution (obs/memtrack.py via profile)
                "peak_hbm_bytes": prof.memory.get("tracked_peak_bytes", 0),
                "spill_bytes": sum(prof.task_metrics.get(f, 0) for f in
                                   ("spill_to_host_bytes",
                                    "spill_to_disk_bytes")),
                "bytes_touched": int(bytes_touched),
                "roofline_util": (round(bytes_touched / ex_s / roofline, 6)
                                  if roofline and ex_s > 0 else None),
                # oversized-agg evidence (docs/oversized_state.md): passes
                # this query triggered and the deepest level reached
                "repartitions": prof.task_metrics.get(
                    "agg_repartition_count", 0),
                "repartition_depth": prof.task_metrics.get(
                    "max_agg_repartition_depth", 0),
                # which join/agg paths served the query and whether each
                # was measured or static (plan/autotune.py); bench_diff
                # tolerates rounds without the field
                "dispatch_paths": prof.dispatch_paths(),
            }), flush=True)
            ppath = os.path.join(prof_dir, f"profile_{suite}_{qn}.json")
            with open(ppath, "w") as f:
                json.dump({**prof.to_dict(),
                           "explain_analyze": prof.explain_analyze()},
                          f, indent=1, default=str)
            profile_files.append(ppath)
            trace_files.append(prof.dump_chrome_trace(
                os.path.join(prof_dir, f"trace_{suite}_{qn}.json")))
        from spark_rapids_tpu.obs import write_textfile
        prom_path = write_textfile(
            os.path.join(prof_dir, "metrics_bench.prom"))
        from tools.trace_viewer_check import check_file
        bad_traces = {p: errs for p in trace_files
                      if (errs := check_file(p))}
        assert not bad_traces, f"invalid chrome traces: {bad_traces}"
    finally:
        # flushed even when a suite was skipped for budget or the run died
        # mid-phase (an exception or the SIGTERM handler above) — partial
        # fields go out as null instead of the whole line going missing
        total_fresh = h_fresh[0] + (ds_fresh[0] or 0.0)
        total_med = h_fresh[1] + (ds_fresh[1] or 0.0)
        cpu_total = cpu_h_s + cpu_ds_s
        util = ((bytes_h / h_fresh[0]) / roofline
                if roofline is not None else None)

        print(json.dumps({
            "tpch_s_per_iter": {"fresh_min": round(h_fresh[0], 4),
                                "fresh_median": round(h_fresh[1], 4),
                                "reused_min": _r(h_reused[0], 4),
                                "reused_median": _r(h_reused[1], 4)},
            "tpcds_s_per_iter": {"fresh_min": _r(ds_fresh[0], 4),
                                 "fresh_median": _r(ds_fresh[1], 4),
                                 "reused_min": _r(ds_reused[0], 4),
                                 "reused_median": _r(ds_reused[1], 4)},
            "cpu_s": {"tpch_pandas": round(cpu_h_s, 3),
                      "tpcds_cpu_engine": round(cpu_ds_s, 3)},
            "roofline_GBps": _r(
                roofline / 1e9 if roofline is not None else None, 2),
            "tpch_bytes_per_iter_GB": round(bytes_h / 1e9, 3),
            "queries": {"tpch": h_names,
                        "tpcds": TPCDS_QUERIES if ds_ran else [],
                        "sf": {"tpch": SF_H, "tpcds": SF_DS}},
            "pool_cap": int(pool_cap) if pool_cap else None,
            "profiles": profile_files,
            "traces": trace_files,
            "prometheus": prom_path,
        }), flush=True)
        print(json.dumps({
            "metric": "tpch4_sf2_plus_tpcds5_sf1_rows_per_sec",
            "value": round((rows_h + rows_ds) / total_fresh, 1),
            "unit": "rows/s",
            "vs_baseline": round(cpu_total / total_fresh, 3),
            "utilization": _r(util, 4),
            "value_median": round((rows_h + rows_ds) / total_med, 1),
        }), flush=True)


def _latency_guard(environ):
    """--latency is a regression gate (warm must beat cold); refuse the
    BENCH_* env overrides that would change what the gate compares — the
    same refuse-to-shrink contract as --faults/--pool-cap. LAT_* knobs
    (scale, iteration counts) stay overridable: cold and warm always run
    at the same scale, so they tune noise, not the comparison."""
    banned = [k for k in ("BENCH_SF_H", "BENCH_SF_DS", "BENCH_RUNS",
                          "BENCH_DEPTH") if k in environ]
    if banned:
        raise SystemExit(
            f"--latency is set: refusing to run with correctness-gate "
            f"overrides {banned} (the latency lane gates warm-vs-cold "
            f"regressions and must control its own inputs)")


def _pctiles_ms(samples_s):
    """Exact nearest-rank p50/p95/p99 of wall-clock samples, in ms."""
    s = sorted(samples_s)
    if not s:
        return {"p50": None, "p95": None, "p99": None}

    def pct(q):
        return s[min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))]

    return {p: round(pct(q) * 1e3, 3)
            for p, q in (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))}


def latency_main(budget_s=None, out_path="artifacts/latency.json"):
    """Interactive-latency lane: N cold + N warm iterations of q1/q6/q3 at
    a small scale factor, reporting wall p50/p95/p99 plus per-phase
    (plan/compile/execute) percentiles read through the obs/histo.py
    snapshot/diff windows. Cold iterations clear the in-process plan memo
    and jit cache (a fresh process with the persistent program cache still
    primed); warm iterations repeat the query so the plan memo and shared
    jits serve it. Writes an artifact and gates warm-vs-cold regressions;
    the final driver-metric line is emitted even when the budget truncates
    iterations (partial samples still summarize)."""
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.config.conf import RapidsConf
    from spark_rapids_tpu.exec import jit_cache
    from spark_rapids_tpu.obs import gauges as G
    from spark_rapids_tpu.obs import histo as _histo
    from spark_rapids_tpu.plan import from_arrow
    from spark_rapids_tpu.plan import plan_cache

    _latency_guard(os.environ)
    sf = float(os.environ.get("LAT_SF", 0.1))
    cold_n = int(os.environ.get("LAT_COLD_ITERS", 4))
    warm_n = int(os.environ.get("LAT_WARM_ITERS", 12))
    names = ["q1", "q6", "q3"]
    bud = _Budget(budget_s)
    conf = RapidsConf()

    _mark(f"latency lane: sf={sf} cold={cold_n} warm={warm_n}")
    tables = {
        "lineitem": tpch.gen_lineitem(sf, seed=7),
        "orders": tpch.gen_orders(sf, seed=8),
        "customer": tpch.gen_customer(sf, seed=9),
        "supplier": tpch.gen_supplier(sf, seed=10),
        "nation": tpch.gen_nation(),
        "region": tpch.gen_region(),
    }

    def run_once(qn):
        """Build the DataFrame fresh (the interactive arrival shape) and
        execute; returns end-to-end seconds including planning."""
        d = {k: from_arrow(v, conf) for k, v in tables.items()}
        t0 = time.perf_counter()
        tpch.DF_QUERIES[qn](d).to_arrow()
        return time.perf_counter() - t0

    phase_names = ("plan_phase_ns", "compile_phase_ns", "execute_phase_ns")

    def phase_window(snap0):
        snap1 = _histo.snapshot_all()
        out = {}
        for n in phase_names:
            d = _histo.diff(snap0[n], snap1[n])
            out[n.removesuffix("_phase_ns")] = \
                _histo.get(n).percentiles_ms(d)
        return out

    g0 = G.snapshot()
    results = {}
    gates = {}
    try:
        for qn in names:
            cold_walls, warm_walls = [], []
            snap = _histo.snapshot_all()
            for i in range(cold_n):
                # cold = fresh-process shape: no plan memo, no in-process
                # jits (the persistent program cache still serves, which
                # is exactly the warm-start story being measured)
                plan_cache.clear()
                jit_cache._CACHE.clear()
                cold_walls.append(run_once(qn))
                if bud.enabled and bud.remaining() < 0.25 * bud.total:
                    break
            cold_phases = phase_window(snap)
            snap = _histo.snapshot_all()
            for i in range(warm_n):
                warm_walls.append(run_once(qn))
                if bud.enabled and bud.remaining() < 0.15 * bud.total:
                    break
            warm_phases = phase_window(snap)
            results[qn] = {
                "cold": {"iters": len(cold_walls),
                         "wall_ms": _pctiles_ms(cold_walls),
                         "phases_ms": cold_phases},
                "warm": {"iters": len(warm_walls),
                         "wall_ms": _pctiles_ms(warm_walls),
                         "phases_ms": warm_phases},
            }
            _mark(f"{qn}: cold p50 "
                  f"{results[qn]['cold']['wall_ms']['p50']}ms, warm p50 "
                  f"{results[qn]['warm']['wall_ms']['p50']}ms")
    finally:
        g1 = G.snapshot()
        counters = {k: g1[k] - g0.get(k, 0) for k in
                    ("plan_cache_hit_total", "plan_cache_miss_total",
                     "jit_persist_hit_total", "jit_persist_store_total",
                     "jit_cache_miss_total")}
        # regression gates: a warm repeat must actually be served by the
        # caches (hits observed) and must not be slower than cold
        for qn, r in results.items():
            cold50 = r["cold"]["wall_ms"]["p50"]
            warm50 = r["warm"]["wall_ms"]["p50"]
            ok = (cold50 is not None and warm50 is not None
                  and warm50 <= cold50 * 1.10)  # 10% noise allowance
            gates[f"{qn}_warm_not_slower"] = bool(ok)
        gates["plan_cache_served"] = counters["plan_cache_hit_total"] > 0
        artifact = {
            "sf": sf, "queries": names,
            "results": results, "counters": counters, "gates": gates,
        }
        out_dir = os.path.dirname(out_path)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        warm50s = [r["warm"]["wall_ms"]["p50"] for r in results.values()
                   if r["warm"]["wall_ms"]["p50"] is not None]
        print(json.dumps({"latency": results, "counters": counters,
                          "gates": gates, "artifact": out_path}))
        print(json.dumps({
            "metric": "latency_warm_wall_p50_ms",
            "value": (round(sum(warm50s) / len(warm50s), 3)
                      if warm50s else None),
            "unit": "ms",
            "queries": names,
            "gates_passed": all(gates.values()) if gates else False,
        }))
    if gates and not all(gates.values()):
        raise SystemExit(f"latency gates failed: "
                         f"{[k for k, v in gates.items() if not v]}")


def _clients_guard(environ):
    """--clients is a correctness gate (concurrent results must be
    bit-identical to serial); refuse the BENCH_* overrides that would
    change what the gate compares — the same refuse-to-shrink contract as
    --faults/--pool-cap/--latency. CL_* knobs (scale, per-client
    iteration count) stay overridable: serial baseline and concurrent runs
    always use the same inputs, so they tune load, not the comparison."""
    banned = [k for k in ("BENCH_SF_H", "BENCH_SF_DS", "BENCH_RUNS",
                          "BENCH_DEPTH") if k in environ]
    if banned:
        raise SystemExit(
            f"--clients is set: refusing to run with correctness-gate "
            f"overrides {banned} (the concurrency lane gates concurrent-"
            f"vs-serial bit-identity and must control its own inputs)")


def clients_main(budget_s=None, clients=8, faults_spec=None,
                 out_path="artifacts/serve_clients.json"):
    """Concurrency lane: N client threads submit TPC-H q1/q6/q3 through the
    QueryServer (serve/) while a serial pass provides the expected tables.
    Gates: every concurrent result bit-identical to serial, every submitted
    query accounted for (completed / shed / timed out — nothing lost), and
    the HBM pool balanced afterward. Reports wall p50/p95/p99 across all
    client-observed latencies, aggregate queries/s, and shed/timeout
    counts; the final driver-metric line is emitted even when the budget
    truncates iterations (docs/serving.md)."""
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.config import conf as C
    from spark_rapids_tpu.mem.pool import get_pool
    from spark_rapids_tpu.obs import gauges as G
    from spark_rapids_tpu.plan import from_arrow
    from spark_rapids_tpu.serve import AdmissionRejected, QueryServer

    _clients_guard(os.environ)
    sf = float(os.environ.get("CL_SF", 0.05))
    iters = int(os.environ.get("CL_ITERS", 6))
    names = ["q1", "q6", "q3"]
    bud = _Budget(budget_s)
    conf = C.RapidsConf()
    if faults_spec:
        conf = conf.with_overrides(**{C.TEST_FAULTS.key: faults_spec})

    _mark(f"clients lane: sf={sf} clients={clients} iters={iters}"
          + (f" faults={faults_spec}" if faults_spec else ""))
    tables = {
        "lineitem": tpch.gen_lineitem(sf, seed=7),
        "orders": tpch.gen_orders(sf, seed=8),
        "customer": tpch.gen_customer(sf, seed=9),
        "supplier": tpch.gen_supplier(sf, seed=10),
        "nation": tpch.gen_nation(),
        "region": tpch.gen_region(),
    }

    def build(qn):
        d = {k: from_arrow(v, conf) for k, v in tables.items()}
        return tpch.DF_QUERIES[qn](d)

    # serial baseline with injection off: the expected bits
    base = C.RapidsConf()
    expected = {}
    for qn in names:
        d = {k: from_arrow(v, base) for k, v in tables.items()}
        expected[qn] = tpch.DF_QUERIES[qn](d).to_arrow()
    _mark(f"serial baseline done ({bud.remaining():.0f}s left)"
          if bud.enabled else "serial baseline done")

    g0 = G.snapshot()
    srv = QueryServer(conf)
    walls = []
    walls_lock = threading.Lock()
    stats = {"completed": 0, "shed": 0, "timeout": 0, "mismatch": 0,
             "error": 0}

    def client(ci):
        # tenants/priorities cycle over clients so the per-tenant SLO block
        # below has multiple keys; the generous deadline populates the
        # deadline-slack family without ever firing
        tenant = f"tenant-{ci % 3}"
        prio = ci % 2
        for i in range(iters):
            if bud.enabled and bud.remaining() < 0.25 * bud.total:
                return
            qn = names[(ci + i) % len(names)]
            t0 = time.perf_counter()
            try:
                tk = srv.submit(build(qn), name=f"c{ci}-{qn}#{i}",
                                tenant=tenant, priority=prio,
                                deadline_ms=600_000)
            except AdmissionRejected:
                with walls_lock:
                    stats["shed"] += 1
                time.sleep(0.02)
                continue
            try:
                out = tk.result(timeout_s=300)
            except TimeoutError:
                tk.cancel("bench timeout")
                with walls_lock:
                    stats["timeout"] += 1
                continue
            except Exception:
                with walls_lock:
                    stats["error"] += 1
                continue
            wall = time.perf_counter() - t0
            with walls_lock:
                walls.append(wall)
                stats["completed"] += 1
                if not out.equals(expected[qn]):
                    stats["mismatch"] += 1

    gates = {}
    t_lane0 = time.perf_counter()
    try:
        threads = [threading.Thread(target=client, args=(ci,),
                                    name=f"bench-client-{ci}")
                   for ci in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        lane_s = time.perf_counter() - t_lane0
        srv.close()
        g1 = G.snapshot()
        counters = {k: g1[k] - g0.get(k, 0) for k in
                    ("admission_submitted_total", "admission_rejected_total",
                     "sched_completed_total", "sched_singleflight_hit_total",
                     "semaphore_timeout_total", "semaphore_cancel_total")}
        pcts = _pctiles_ms(walls)
        gates["bit_identical"] = (stats["mismatch"] == 0
                                  and stats["completed"] > 0)
        gates["no_unexplained_failures"] = stats["error"] == 0
        gates["pool_balanced"] = get_pool().used == 0
        # per-tenant SLO percentile block (serve/metrics.py): queue-wait /
        # semaphore-wait / deadline-slack p50/p95/p99 + outcome counts,
        # keyed "tenant/priority"
        from spark_rapids_tpu.serve import metrics as _slo
        tenant_slos = {f"{t}/p{p}": v
                       for (t, p), v in sorted(_slo.tenant_slos().items())}
        artifact = {
            "sf": sf, "clients": clients, "iters": iters,
            "queries": names, "faults": faults_spec,
            "wall_ms": pcts, "lane_s": round(lane_s, 3),
            "stats": stats, "counters": counters, "gates": gates,
            "tenant_slos": tenant_slos,
        }
        out_dir = os.path.dirname(out_path)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(json.dumps({"serve_clients": artifact}))
        print(json.dumps({"serve_tenant_slos": tenant_slos}))
        print(json.dumps({
            "metric": "serve_clients_wall_p50_ms",
            "value": pcts["p50"],
            "unit": "ms",
            "p95_ms": pcts["p95"],
            "p99_ms": pcts["p99"],
            "queries_per_s": (round(stats["completed"] / lane_s, 3)
                              if lane_s > 0 else None),
            "shed_total": stats["shed"],
            "timeout_total": stats["timeout"],
            "clients": clients,
            "tenants": len(tenant_slos),
            "gates_passed": all(gates.values()) if gates else False,
        }))
    if gates and not all(gates.values()):
        raise SystemExit(f"clients gates failed: "
                         f"{[k for k, v in gates.items() if not v]} "
                         f"(stats={stats})")


def _serve_open_guard(environ):
    """--serve-open gates remote-vs-in-process bit-identity; refuse the
    BENCH_* overrides that would change what the gate compares. SO_*
    knobs (scale, lambda steps, window) tune load, not the comparison."""
    banned = [k for k in ("BENCH_SF_H", "BENCH_SF_DS", "BENCH_RUNS",
                          "BENCH_DEPTH") if k in environ]
    if banned:
        raise SystemExit(
            f"--serve-open is set: refusing to run with correctness-gate "
            f"overrides {banned} (the open-workload lane gates remote-vs-"
            f"in-process bit-identity and must control its own inputs)")


def serve_open_main(budget_s=None, out_path="artifacts/serve_open.json"):
    """Open-workload overload lane: Poisson arrivals submit TPC-H q1/q6
    OVER THE WIRE (net/ front-end, two authenticated tenants) at stepped
    offered loads; the server runs deliberately small (max_concurrent /
    max_queue) so the top step overloads it for real. Measures the
    goodput-vs-offered-load curve and the per-tenant shed curve under
    weighted fair-share admission. Gates: every completed remote result
    bit-identical to in-process ``to_arrow()``, every non-completion a
    TYPED shed (admission reason / deadline / local thread-cap — never an
    unexplained error), shedding actually observed at the overload step,
    and the HBM pool balanced after teardown. The final driver-metric
    line is emitted even when the budget truncates steps (docs/net.md)."""
    import random

    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.config import conf as C
    from spark_rapids_tpu.mem.pool import get_pool
    from spark_rapids_tpu.net import NetClient, QueryFrontend
    from spark_rapids_tpu.net import metrics as netm
    from spark_rapids_tpu.plan import from_arrow
    from spark_rapids_tpu.serve import (AdmissionRejected,
                                        QueryDeadlineExceeded, QueryServer)
    from spark_rapids_tpu.serve import metrics as slo

    _serve_open_guard(os.environ)
    sf = float(os.environ.get("SO_SF", 0.02))
    lambdas = [float(x) for x in
               os.environ.get("SO_LAMBDAS", "4,16,48").split(",")]
    window_s = float(os.environ.get("SO_WINDOW_S", 4.0))
    seed = int(os.environ.get("SO_SEED", 42))
    max_inflight = int(os.environ.get("SO_MAX_INFLIGHT", 256))
    max_concurrent = int(os.environ.get("SO_MAX_CONCURRENT", 2))
    max_queue = int(os.environ.get("SO_MAX_QUEUE", 8))
    bud = _Budget(budget_s)
    names = ["q1", "q6"]
    tenants = [("gold", "tok-gold", 1), ("bronze", "tok-bronze", 0)]

    _mark(f"serve-open lane: sf={sf} lambdas={lambdas} window={window_s}s "
          f"server={max_concurrent}x/{max_queue}q")
    tables = {"lineitem": tpch.gen_lineitem(sf, seed=7)}
    expected = {}
    for qn in names:
        d = {k: from_arrow(v) for k, v in tables.items()}
        expected[qn] = tpch.DF_QUERIES[qn](d).to_arrow()
    _mark("in-process baseline done")

    # single-flight off: the lane measures scheduling under load, and the
    # repeated query mix would otherwise dedupe the queue empty
    conf = C.RapidsConf({
        C.SERVE_SINGLEFLIGHT.key: False,
        C.SERVE_FAIRSHARE_ENABLED.key: True,
        C.SERVE_FAIRSHARE_WEIGHTS.key: "gold=3,bronze=1",
        C.NET_AUTH_TOKENS.key: "tok-gold=gold,tok-bronze=bronze",
    })
    srv = QueryServer(conf, max_concurrent=max_concurrent,
                      max_queue=max_queue)
    fe = QueryFrontend(srv, tables=tables)

    points = []
    totals = {"arrivals": 0, "completed": 0, "mismatch": 0, "untyped": 0}
    shed_curve = {}  # tenant -> reason -> count (lane total)
    gates = {}
    try:
        for lam in lambdas:
            if bud.enabled and bud.remaining() < window_s + 2:
                _mark(f"budget: skipping lambda={lam:g} and beyond")
                break
            rng = random.Random(seed + int(lam * 1000))
            cap = threading.BoundedSemaphore(max_inflight)
            lock = threading.Lock()
            stats = {"arrivals": 0, "completed": 0, "mismatch": 0,
                     "untyped": 0, "local-cap": 0}
            sheds = {}  # tenant -> reason -> count (this step)
            walls = []
            threads = []

            def shed(tenant, reason):
                with lock:
                    sheds.setdefault(tenant, {})
                    sheds[tenant][reason] = sheds[tenant].get(reason, 0) + 1

            def one_arrival(i, lam=lam, rng_pick=None):
                qn = names[i % len(names)]
                tenant, token, prio = tenants[rng_pick]
                t0 = time.perf_counter()
                try:
                    with NetClient(fe.host, fe.port, token=token) as cl:
                        d = {k: cl.table(k, partitions=2) for k in tables}
                        out = cl.submit(tpch.DF_QUERIES[qn](d), priority=prio,
                                        deadline_ms=60_000,
                                        name=f"so-{lam:g}-{i}", timeout_s=120)
                except AdmissionRejected as e:
                    shed(tenant, e.reason)
                    return
                except QueryDeadlineExceeded:
                    shed(tenant, "deadline")
                    return
                except Exception as e:  # noqa: BLE001 — gate counts these
                    with lock:
                        stats["untyped"] += 1
                    _mark(f"UNTYPED failure: {type(e).__name__}: {e}")
                    return
                finally:
                    cap.release()
                with lock:
                    walls.append(time.perf_counter() - t0)
                    stats["completed"] += 1
                    if not out.equals(expected[qn]):
                        stats["mismatch"] += 1

            t_start = time.perf_counter()
            t_end = t_start + window_s
            next_at = t_start
            i = 0
            while time.perf_counter() < t_end:
                now = time.perf_counter()
                if now < next_at:
                    time.sleep(min(next_at - now, 0.05))
                    continue
                next_at += rng.expovariate(lam)
                stats["arrivals"] += 1
                # typed local shed: the driver itself refuses to hold more
                # than max_inflight submission threads open
                if not cap.acquire(blocking=False):
                    stats["local-cap"] += 1
                    tenant = tenants[rng.randrange(len(tenants))][0]
                    shed(tenant, "local-cap")
                    i += 1
                    continue
                th = threading.Thread(
                    target=one_arrival, args=(i,),
                    kwargs={"rng_pick": rng.randrange(len(tenants))},
                    name=f"so-arrival-{i}", daemon=True)
                th.start()
                threads.append(th)
                i += 1
            for th in threads:
                th.join(timeout=180)
            step_s = time.perf_counter() - t_start
            shed_total = sum(n for per in sheds.values()
                             for n in per.values())
            point = {
                "lambda": lam,
                "offered_per_s": round(stats["arrivals"] / step_s, 3),
                "goodput_per_s": round(stats["completed"] / step_s, 3),
                "shed_per_s": round(shed_total / step_s, 3),
                "wall_ms": _pctiles_ms(walls),
                "arrivals": stats["arrivals"],
                "completed": stats["completed"],
                "sheds": {t: dict(per) for t, per in sorted(sheds.items())},
                "untyped": stats["untyped"],
            }
            points.append(point)
            for t, per in sheds.items():
                agg = shed_curve.setdefault(t, {})
                for r, n in per.items():
                    agg[r] = agg.get(r, 0) + n
            for k in ("arrivals", "completed", "mismatch", "untyped"):
                totals[k] += stats[k]
            _mark(f"lambda={lam:g}: offered={point['offered_per_s']}/s "
                  f"goodput={point['goodput_per_s']}/s "
                  f"shed={point['shed_per_s']}/s untyped={stats['untyped']}")
    finally:
        fe.close()
        srv.close()
        gates["bit_identical"] = (totals["mismatch"] == 0
                                  and totals["completed"] > 0)
        gates["typed_sheds_only"] = totals["untyped"] == 0
        # the top offered-load step must actually overload the small
        # server: at least one typed shed observed there
        gates["sheds_at_overload"] = bool(points) and (
            sum(n for per in points[-1]["sheds"].values()
                for n in per.values()) > 0)
        gates["pool_balanced"] = get_pool().used == 0
        goodput = max((p["goodput_per_s"] for p in points), default=0.0)
        tenant_slos = {f"{t}/p{p}": v
                       for (t, p), v in sorted(slo.tenant_slos().items())}
        artifact = {
            "sf": sf, "window_s": window_s, "seed": seed,
            "max_inflight": max_inflight,
            "server": {"max_concurrent": max_concurrent,
                       "max_queue": max_queue,
                       "fairshare_weights": "gold=3,bronze=1"},
            "queries": names, "points": points, "totals": totals,
            "shed_curve": {t: dict(per)
                           for t, per in sorted(shed_curve.items())},
            "net": netm.counters(), "tenant_slos": tenant_slos,
            "gates": gates,
        }
        out_dir = os.path.dirname(out_path)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(json.dumps({"serve_open": artifact}))
        for p in points:
            print(json.dumps({
                "metric": f"serve_open:lam{p['lambda']:g}:queries_per_s",
                "value": p["goodput_per_s"],
                "unit": "queries/s",
                "offered_per_s": p["offered_per_s"],
                "shed_per_s": p["shed_per_s"],
            }))
        print(json.dumps({
            "metric": "serve_open_goodput_queries_per_s",
            "value": goodput,
            "unit": "queries/s",
            "points": len(points),
            "arrivals": totals["arrivals"],
            "completed": totals["completed"],
            "shed_curve": {t: dict(per)
                           for t, per in sorted(shed_curve.items())},
            "gates_passed": all(gates.values()) if gates else False,
        }))
    if gates and not all(gates.values()):
        raise SystemExit(f"serve-open gates failed: "
                         f"{[k for k, v in gates.items() if not v]} "
                         f"(totals={totals})")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", type=float, default=None, metavar="SECONDS",
                    help="soft wall-clock budget: phases deduct measured "
                         "time; COPIES/RUNS/DEPTH shrink and optional "
                         "phases (reused-buffer runs, roofline, profile "
                         "dumps) are skipped to fit. Correctness gates "
                         "always run; the final driver-metric line is "
                         "always emitted.")
    ap.add_argument("--faults", type=str, default=None, metavar="SPEC",
                    help="fault-injection schedule (spark.rapids.tpu.test."
                         "faults grammar) applied to the device runs; "
                         "refuses BENCH_* correctness-gate overrides so "
                         "chaos runs always execute the full differential "
                         "check (docs/fault_injection.md)")
    ap.add_argument("--pool-cap", type=int, default=None, metavar="BYTES",
                    help="cap the HBM accounting pool at BYTES for the "
                         "whole run (memory-pressure gauntlet: spill, "
                         "retry, and agg repartition fire for real while "
                         "the correctness gates still compare full "
                         "results; refuses BENCH_* overrides like "
                         "--faults, docs/oversized_state.md)")
    ap.add_argument("--latency", action="store_true",
                    help="run the interactive-latency lane instead of the "
                         "throughput sweep: N cold + N warm iterations of "
                         "q1/q6/q3, cold/warm p50/p95/p99 wall and "
                         "per-phase (plan/compile/execute) percentiles, "
                         "an artifact, and warm-vs-cold regression gates "
                         "(docs/latency.md)")
    ap.add_argument("--latency-out", type=str,
                    default="artifacts/latency.json", metavar="PATH",
                    help="artifact path for --latency results")
    ap.add_argument("--clients", type=int, default=None, metavar="N",
                    help="run the concurrency lane instead of the "
                         "throughput sweep: N client threads submit "
                         "q1/q6/q3 through the QueryServer; gates "
                         "concurrent-vs-serial bit-identity and pool "
                         "balance; reports wall p50/p95/p99, queries/s, "
                         "and shed/timeout counts (docs/serving.md). "
                         "Combine with --faults for the seeded chaos "
                         "variant")
    ap.add_argument("--clients-out", type=str,
                    default="artifacts/serve_clients.json", metavar="PATH",
                    help="artifact path for --clients results")
    ap.add_argument("--serve-open", action="store_true",
                    help="run the open-workload overload lane instead of "
                         "the throughput sweep: Poisson arrivals submit "
                         "q1/q6 over the network front-end (two "
                         "authenticated tenants, weighted fair-share) at "
                         "stepped offered loads against a deliberately "
                         "small server; gates remote-vs-in-process bit-"
                         "identity, typed-sheds-only, shedding at the "
                         "overload step, and pool balance; reports the "
                         "goodput-vs-offered-load curve and per-tenant "
                         "shed curve (docs/net.md). SO_* env knobs tune "
                         "lambda steps/window/scale")
    ap.add_argument("--serve-open-out", type=str,
                    default="artifacts/serve_open.json", metavar="PATH",
                    help="artifact path for --serve-open results")
    _args = ap.parse_args()
    if _args.budget is None and not sys.stdout.isatty():
        # non-interactive bare run (CI/harness): a full unbudgeted sweep can
        # outlive the caller's timeout and lose the final metric line —
        # default to a conservative budget instead
        _args.budget = float(os.environ.get("SRTPU_BENCH_BUDGET_S", "600"))
    if _args.latency:
        latency_main(budget_s=_args.budget, out_path=_args.latency_out)
    elif _args.serve_open:
        serve_open_main(budget_s=_args.budget,
                        out_path=_args.serve_open_out)
    elif _args.clients is not None:
        clients_main(budget_s=_args.budget, clients=_args.clients,
                     faults_spec=_args.faults, out_path=_args.clients_out)
    else:
        main(budget_s=_args.budget, faults=_args.faults,
             pool_cap=_args.pool_cap)
