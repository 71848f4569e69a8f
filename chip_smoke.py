#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the engine still starts on the chip.

One process, one TPU chip. TPC-H q6/q1/q3 go through the planner
(``DataFrame.to_arrow()``) and through the wire (``NetClient.submit()`` to a
``QueryFrontend`` + ``QueryServer`` on loopback, all threads of this process),
are compared with the repo's CPU engine, and the engine's own counters are
read afterwards so that no quiet way back to the CPU can make a broken chip
run look green. Run it as ``chiprun -- python chip_smoke.py``.

Scale: ``--sf`` defaults to DEFAULT_SF, not to TPC-H's smallest official
scale (SF1), because a run that starts with an empty compile cache must end
within 1,200 s and the v5e compiler's bill for these three plans, measured in
the sandbox for a described v5e, is 1,065 s at SF1 against 406 s at SF0.25
(PERF.md, PR 26). SF0.25 still splits ``lineitem`` into more than one batch,
so the multi-batch ``step`` programs run as they do at SF1. ``--sf 1`` is the
full size, for a call that has the time or a warm cache.

Every phase and query prints one JSON line, also appended to
``chiprun_out/chip_smoke.jsonl``. The last line of stdout is
``{"ok": ..., "device": {...}}``; ``ok`` is true only on a TPU. There is no
option that lets a CPU run pass: ``JAX_PLATFORMS=cpu python chip_smoke.py
--sf 0.01`` rehearses every phase and then fails on the device check.
"""

import argparse
import glob
import importlib.metadata
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_PATH = os.path.join(ROOT, "chiprun_out", "chip_smoke.jsonl")

# The one tolerance of the comparison with the CPU engine: float64 sums and
# averages agree to this relative bound; everything else (row counts, keys,
# ints, dates, strings) is exact.
FLOAT_RTOL = 1e-6

DEFAULT_SF = 0.25
QUERIES = ("q6", "q1", "q3")
SERVED = ("q6", "q1", "q3", "q6", "q1")
TENANTS = (("gold", "tok-gold", 1), ("bronze", "tok-bronze", 0))
# from_arrow's and NetClient.table's defaults, stated so that the in-process
# and the served plans are the same plans
BATCH_ROWS = 1 << 20
PARTITIONS = 1
SHUFFLE_PARTITIONS = 4
# a CPU rehearsal above this scale has nothing to show that a small one does
# not, so it fails before it generates any data
REHEARSAL_MAX_SF = 0.1

# a device failure, or a kernel the compiler refused, that the engine
# absorbed: any of these means the answer may not have come from the chip
FORBIDDEN_COUNTERS = ("fault_degraded_total", "hashtbl_pallas_fallback_total",
                      "sortwin_pallas_fallback_total",
                      "jit_persist_error_total")
FORBIDDEN_EVENTS = ("query-retry", "degraded-to-cpu", "degraded",
                    "pallas-fallback")


def emit(record: dict) -> None:
    line = json.dumps(record, sort_keys=True, default=str)
    print(line, flush=True)
    with open(OUT_PATH, "a") as f:
        f.write(line + "\n")


class CompileMeter:
    """Counts what JAX itself reports: programs handed to the backend
    compiler, the seconds that took, and persistent-cache hits/misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def read(self) -> dict:
        return {"programs": self.programs, "compile_s": self.seconds,
                "xla_cache_hits": self.cache_hits,
                "xla_cache_misses": self.cache_misses}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def compare(name: str, got, want) -> None:
    """``got`` (device) against ``want`` (CPU engine); raises on mismatch."""
    import pyarrow as pa
    if got.schema.names != want.schema.names:
        raise AssertionError(f"{name}: columns {got.schema.names} != "
                             f"{want.schema.names}")
    if got.num_rows != want.num_rows:
        raise AssertionError(f"{name}: {got.num_rows} rows != "
                             f"{want.num_rows}")
    for col in got.schema.names:
        g, w = got[col].to_pylist(), want[col].to_pylist()
        if pa.types.is_floating(got.schema.field(col).type):
            for i, (a, b) in enumerate(zip(g, w)):
                if (a is None) != (b is None) or (
                        a is not None
                        and abs(a - b) > FLOAT_RTOL * max(abs(b), 1e-300)):
                    raise AssertionError(
                        f"{name}.{col}[{i}]: {a!r} vs {b!r} beyond "
                        f"rtol {FLOAT_RTOL}")
        elif g != w:
            raise AssertionError(f"{name}.{col}: {g[:5]} != {w[:5]}")


def hidden_device_evidence(snapshot: dict) -> dict:
    """What step 6 fails on: non-zero counters and journal events that say
    the engine left the device, or a kernel, behind."""
    from spark_rapids_tpu.obs import events
    bad = {k: snapshot[k] for k in FORBIDDEN_COUNTERS if snapshot[k]}
    for kind in FORBIDDEN_EVENTS:
        n = len(events.recent(kind=kind))
        if n:
            bad[f"event:{kind}"] = n
    if snapshot["journal_evicted_total"]:
        bad["journal_evicted_total"] = snapshot["journal_evicted_total"]
    return bad


def build(q: str, tables: dict, conf):
    """The DataFrame of query ``q`` over ``tables``, as a user builds it."""
    from spark_rapids_tpu.bench import tpch
    return tpch.DF_QUERIES[q](tpch.df_tables(
        tables, conf, SHUFFLE_PARTITIONS, PARTITIONS, BATCH_ROWS))


def run_oracle(tables: dict, cpu_conf) -> dict:
    """The same plans on the CPU engine, outside any timing."""
    oracle = {}
    for q in QUERIES:
        t0 = time.perf_counter()
        df = build(q, tables, cpu_conf)
        if df.device_plan_stats()["device"]:
            raise AssertionError(f"oracle plan for {q} has device nodes")
        oracle[q] = df.to_arrow()
        emit({"phase": "oracle", "query": q,
              "rows_out": oracle[q].num_rows,
              "seconds": time.perf_counter() - t0})
    return oracle


def run_planner(q: str, tables: dict, conf, want, meter, dev, pool):
    """``q`` through DataFrame.to_arrow(), cold (with compile) then warm;
    each run compared with the oracle and checked for hidden fallbacks."""
    from spark_rapids_tpu import native
    from spark_rapids_tpu.obs import gauges
    walls, compiles = [], []
    for run in ("cold", "warm"):
        df = build(q, tables, conf)
        stats = df.device_plan_stats()
        if stats["cpu_nodes"]:
            raise AssertionError(
                f"{q}: CPU nodes in the plan: {stats['cpu_nodes']}")
        c0 = meter.read()
        g0 = gauges.snapshot()
        t0 = time.perf_counter()
        out = df.to_arrow()  # a host Arrow table: the readback is inside
        walls.append(time.perf_counter() - t0)
        g1 = gauges.snapshot()
        comp = CompileMeter.delta(c0, meter.read())
        comp["jit_compile_s"] = (g1["jit_compile_ns_total"]
                                 - g0["jit_compile_ns_total"]) / 1e9
        comp["jit_cache_miss"] = (g1["jit_cache_miss_total"]
                                  - g0["jit_cache_miss_total"])
        compiles.append(comp)
        compare(f"{q}/{run}", out, want)
        bad = hidden_device_evidence(g1)
        if bad:
            raise AssertionError(f"{q}/{run}: the device was hidden: {bad}")
    emit({"phase": "planner", "query": q, "device_kind": dev.device_kind,
          "rows_in": {k: tables[k].num_rows
                      for k in ("lineitem", "orders", "customer")},
          "rows_out": out.num_rows,
          "cold_wall_s": walls[0], "warm_wall_s": walls[1],
          "cold_compile": compiles[0], "warm_compile": compiles[1],
          "device_plan_stats": stats, "match": True,
          "pool_limit_bytes": pool.limit,
          "pool_limit_source": pool.limit_source,
          "pool_max_used_bytes": pool.max_used,
          "peak_bytes_in_use":
              (dev.memory_stats() or {}).get("peak_bytes_in_use"),
          "native_lib": native.available()})
    return out


def run_served(tables: dict, conf, serve_conf, results: dict, meter, dev):
    """The same plans over the wire: server, front-end and client are
    threads of this process; five requests from two tenants, each result
    identical to the planner path's."""
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.net import NetClient, QueryFrontend
    from spark_rapids_tpu.serve import QueryServer
    c0 = meter.read()
    requests = []
    srv = QueryServer(serve_conf)
    try:
        fe = QueryFrontend(srv, tables=tables, host="127.0.0.1", port=0)
        try:
            for i, q in enumerate(SERVED):
                tenant, token, prio = TENANTS[i % len(TENANTS)]
                t0 = time.perf_counter()
                with NetClient(fe.host, fe.port, token=token, conf=conf,
                               shuffle_partitions=SHUFFLE_PARTITIONS,
                               timeout_s=600) as cl:
                    d = {k: cl.table(k, batch_rows=BATCH_ROWS,
                                     partitions=PARTITIONS) for k in tables}
                    out = cl.submit(tpch.DF_QUERIES[q](d), priority=prio,
                                    name=f"smoke-{i}-{q}", timeout_s=600)
                requests.append({"query": q, "tenant": tenant,
                                 "wall_s": time.perf_counter() - t0})
                if not out.equals(results[q]):
                    raise AssertionError(
                        f"served {q} (request {i}) differs from the "
                        f"in-process result")
        finally:
            fe.close()
    finally:
        srv.close()
    emit({"phase": "served", "device_kind": dev.device_kind,
          "requests": requests, "identical_to_planner_path": True,
          "compile": CompileMeter.delta(c0, meter.read())})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=DEFAULT_SF,
                    help="TPC-H scale factor (1 for the full size, 0.01 "
                         "for the CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()

    os.makedirs(os.path.dirname(OUT_PATH), exist_ok=True)
    # A native library built on another host's CPU must not be loaded here:
    # it is rebuilt on this machine from the committed sources, or is absent.
    shutil.rmtree(os.path.join(ROOT, "spark_rapids_tpu", "native", "_build"),
                  ignore_errors=True)

    import jax
    import jaxlib
    import spark_rapids_tpu  # noqa: F401  (x64 on, compile cache placed)
    from spark_rapids_tpu import native
    from spark_rapids_tpu.bench import tpch
    from spark_rapids_tpu.config import conf as C
    from spark_rapids_tpu.mem.pool import get_pool
    from spark_rapids_tpu.obs import events, gauges

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    try:
        libtpu_version = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu_version = None
    cache_dir = jax.config.jax_compilation_cache_dir
    conf_set = {
        # the first device error surfaces with its traceback instead of
        # three recompiles and the CPU engine's answer (which IS the oracle)
        C.FAULT_BLACKLIST_ENABLED.key: False,
    }
    serve_conf_set = {
        **conf_set,
        C.SERVE_FAIRSHARE_ENABLED.key: True,
        C.SERVE_FAIRSHARE_WEIGHTS.key: "gold=3,bronze=1",
        C.NET_AUTH_TOKENS.key: "tok-gold=gold,tok-bronze=bronze",
    }
    emit({"phase": "config", "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu_version,
          "device": device, "device_kind": dev.device_kind,
          "JAX_COMPILATION_CACHE_DIR":
              os.environ.get("JAX_COMPILATION_CACHE_DIR"),
          "xla_cache_dir": cache_dir,
          "xla_cache_entries_at_start":
              len(glob.glob(os.path.join(cache_dir, "*-cache"))),
          "sf": args.sf, "seed": args.seed, "batch_rows": BATCH_ROWS,
          "partitions": PARTITIONS, "shuffle_partitions": SHUFFLE_PARTITIONS,
          "float_rtol": FLOAT_RTOL, "conf": conf_set,
          "served_conf": serve_conf_set,
          "native_lib_built_here": native.available(),
          "child_processes": "none: server, front-end and client are "
                             "threads; shuffle/cluster.py executors and the "
                             "udf/arrow_eval.py worker are not on this path"})
    if not on_tpu and args.sf > REHEARSAL_MAX_SF:
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1

    meter = CompileMeter()
    conf = C.RapidsConf(conf_set)
    pool = get_pool(conf)

    t0 = time.perf_counter()
    tables = tpch.tables_for(args.sf, args.seed)
    emit({"phase": "data", "device_kind": dev.device_kind,
          "seconds": time.perf_counter() - t0,
          "rows": {k: v.num_rows for k, v in tables.items()},
          "arrow_bytes": {k: v.nbytes for k, v in tables.items()}})

    oracle = run_oracle(
        tables, conf.with_overrides(**{C.SQL_ENABLED.key: False}))
    results = {q: run_planner(q, tables, conf, oracle[q], meter, dev, pool)
               for q in QUERIES}
    run_served(tables, conf, C.RapidsConf(serve_conf_set), results, meter,
               dev)

    # -- nothing hid the device ---------------------------------------------
    snap = gauges.snapshot()
    emit({"phase": "counters", "device_kind": dev.device_kind,
          "forbidden": {k: snap[k] for k in FORBIDDEN_COUNTERS},
          "forbidden_events": {k: len(events.recent(kind=k))
                               for k in FORBIDDEN_EVENTS},
          "fault_recovered_total": snap["fault_recovered_total"],
          "jit_persist": {k: snap[k] for k in
                          ("jit_persist_hit_total", "jit_persist_miss_total",
                           "jit_persist_store_total")},
          "autotune": {k: snap[k] for k in
                       ("autotune_hit_total", "autotune_miss_total")},
          "compile_total": meter.read(),
          "xla_cache_entries_at_end":
              len(glob.glob(os.path.join(cache_dir, "*-cache"))),
          "peak_bytes_in_use":
              (dev.memory_stats() or {}).get("peak_bytes_in_use"),
          "pool_max_used_bytes": pool.max_used})
    bad = hidden_device_evidence(snap)
    if bad:
        raise AssertionError(f"the device was hidden: {bad}")

    print(json.dumps({"ok": on_tpu, "device": device}), flush=True)
    return 0 if on_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
