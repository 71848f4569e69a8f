"""TPC-DS 99-query differential tracker.

Runs every registered query twice — once on the device engine, once on the
CPU fallback engine (spark.rapids.tpu.sql.enabled=false) — and compares
results (sorted canonical form; floats to 1e-9 relative). Per-query status:

  ok        device == cpu oracle
  wrong     both ran, results differ
  dev_fail  device run raised (oracle ran)
  cpu_fail  oracle raised (device ran)
  both_fail neither engine ran the query
  missing   query not implemented yet

Writes docs/tpcds_status.md + docs/tpcds_status.json. This is the
standalone analog of the reference's assert_gpu_and_cpu_are_equal_collect
suite over NDS (reference: integration_tests/.../asserts.py:479-617).

Usage: python tools/tpcds_tracker.py [--sf 0.01] [--queries q1,q2]
       [--cpu-mesh] [--out docs/]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def canon(rows, float_tol=1e-9):
    """Canonical sortable form of a result set."""
    def key(v):
        if v is None:
            return (0, "")
        if isinstance(v, float):
            return (1, round(v, 6))
        if isinstance(v, (int,)):
            return (1, float(v))
        return (2, str(v))

    return sorted((tuple(r.values()) for r in rows),
                  key=lambda t: tuple(key(v) for v in t))


def rows_equal(a, b, float_tol=1e-9):
    if len(a) != len(b):
        return False, f"row count {len(a)} vs {len(b)}"
    for i, (ra, rb) in enumerate(zip(canon(a), canon(b))):
        if len(ra) != len(rb):
            return False, f"row {i}: arity {len(ra)} vs {len(rb)}"
        for va, vb in zip(ra, rb):
            if va is None and vb is None:
                continue
            if isinstance(va, float) or isinstance(vb, float):
                if va is None or vb is None:
                    return False, f"row {i}: {va!r} vs {vb!r}"
                if math.isnan(va) and math.isnan(vb):
                    continue
                if abs(va - vb) > float_tol * max(1.0, abs(va), abs(vb)):
                    return False, f"row {i}: {va!r} vs {vb!r}"
            elif va != vb:
                return False, f"row {i}: {va!r} vs {vb!r}"
    return True, ""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--queries", type=str, default="")
    ap.add_argument("--cpu-mesh", action="store_true",
                    help="force the virtual CPU mesh platform (CI)")
    ap.add_argument("--out", type=str, default="docs")
    args = ap.parse_args()

    if args.cpu_mesh:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
        # isolated per-run compile cache: the shared persistent cache can
        # serve CPU AOT kernels compiled under other host-feature flags and
        # segfault hours into a run (_xla_cpu_cache.py)
        import tempfile
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              tempfile.mkdtemp(prefix="srtpu_xla_run_"))
        import jax
        jax.config.update("jax_platforms", "cpu")

    import spark_rapids_tpu  # noqa: F401
    from spark_rapids_tpu.bench import tpcds_queries as Q
    from spark_rapids_tpu.bench.tpcds_schema import tables_for
    from spark_rapids_tpu.config.conf import RapidsConf
    from spark_rapids_tpu.plan import from_arrow

    tables = tables_for(args.sf)
    names = (args.queries.split(",") if args.queries
             else [f"q{i}" for i in range(1, 100)])

    def dfs_for(conf):
        out = {}
        for k, v in tables.items():
            df = from_arrow(v, conf)
            df.shuffle_partitions = 2
            out[k] = df
        return out

    dev_conf = RapidsConf({})
    cpu_conf = RapidsConf({"spark.rapids.tpu.sql.enabled": False})

    results = {}
    for name in names:
        fn = Q.QUERIES.get(name)
        if fn is None:
            results[name] = {"status": "missing"}
            print(f"{name:5s} missing", flush=True)
            continue
        entry = {}
        t0 = time.perf_counter()
        dev_rows = cpu_rows = None
        dev_err = cpu_err = None
        try:
            cpu_rows = fn(dfs_for(cpu_conf)).collect()
        except Exception as e:
            cpu_err = f"{type(e).__name__}: {e}"
            entry["cpu_trace"] = traceback.format_exc(limit=8)
        try:
            dev_df = fn(dfs_for(dev_conf))
            stats = dev_df.device_plan_stats()
            entry["device_fraction"] = stats["device_fraction"]
            if stats["cpu_nodes"]:
                entry["cpu_nodes"] = stats["cpu_nodes"]
            dev_rows = dev_df.collect()
        except Exception as e:
            dev_err = f"{type(e).__name__}: {e}"
            entry["dev_trace"] = traceback.format_exc(limit=8)
        entry["seconds"] = round(time.perf_counter() - t0, 2)
        if dev_rows is not None and cpu_rows is not None:
            same, why = rows_equal(dev_rows, cpu_rows)
            entry["status"] = "ok" if same else "wrong"
            entry["rows"] = len(dev_rows)
            if not same:
                entry["diff"] = why
        elif dev_rows is None and cpu_rows is None:
            entry["status"] = "both_fail"
            entry["dev_err"] = dev_err
            entry["cpu_err"] = cpu_err
        elif dev_rows is None:
            entry["status"] = "dev_fail"
            entry["dev_err"] = dev_err
        else:
            entry["status"] = "cpu_fail"
            entry["cpu_err"] = cpu_err
        results[name] = entry
        print(f"{name:5s} {entry['status']:9s} "
              f"{entry.get('rows', '')} rows {entry['seconds']}s "
              f"{entry.get('dev_err', '') or entry.get('cpu_err', '') or entry.get('diff', '')}"[:140],
              flush=True)
        # crash-safe: persist progress after every query
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "tpcds_status.partial.json"),
                  "w") as f:
            json.dump({"sf": args.sf, "results": results}, f, indent=1,
                      default=str)

    counts = {}
    for e in results.values():
        counts[e["status"]] = counts.get(e["status"], 0) + 1
    summary = {"sf": args.sf, "counts": counts, "results": results}

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "tpcds_status.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    with open(os.path.join(args.out, "tpcds_status.md"), "w") as f:
        f.write("# TPC-DS 99-query differential status\n\n")
        f.write(f"Scale factor {args.sf}; device engine vs CPU-fallback "
                "oracle (same plans, disjoint execution paths).\n\n")
        f.write("| status | count |\n|---|---|\n")
        for k in sorted(counts):
            f.write(f"| {k} | {counts[k]} |\n")
        f.write("\n| query | status | rows | seconds | device% | note |\n"
                "|---|---|---|---|---|---|\n")
        for name in names:
            e = results.get(name, {})
            note = (e.get("dev_err") or e.get("cpu_err")
                    or e.get("diff") or "")
            if e.get("cpu_nodes"):
                note = f"cpu: {','.join(e['cpu_nodes'])} {note}"
            frac = e.get("device_fraction")
            f.write(f"| {name} | {e.get('status')} | {e.get('rows', '')} | "
                    f"{e.get('seconds', '')} | "
                    f"{'' if frac is None else frac} | {str(note)[:90]} |\n")
    print("summary:", counts, flush=True)


if __name__ == "__main__":
    main()
