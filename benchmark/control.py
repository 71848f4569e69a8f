#!/usr/bin/env python3
"""The readings a limit of ``correct`` is set from, and its controls.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process (set-up is most of a run): the program's
readings are ``answers_wrong`` and ``decimal_units_off_max`` of a short
window of the cell at its own size and load, as run.py would judge it. The
controls are the query's own (``CONTROLS`` in queries/<q>.py: a precision
below DECIMAL, a probe match dropped or repeated, rows misplaced, ...):
each is the plain reference degraded in one way and put in the program's
place, one answer per query instance of the cell, judged by the run's own
comparison, and each has to read ``wrong`` 1 in every instance. A query's
``PASSES_BY_DESIGN`` are run and reported with their reason, and not held
to that. One JSON line per seed on standard output; the exit code is 1
where the program was not correct or any control was. The benchmark's own
runs never run this.
"""

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def control_readings(probe: dict) -> dict:
    """``{"controls": {"<query>.<name>": reading}, "passes_by_design": ...}``
    over the cell's query instances; a reading sums ``answers_wrong`` and
    takes the widest ``units_off``, with each instance's own beside."""
    import compare
    out = {"controls": {}, "passes_by_design": {}}
    for key, (q, p) in probe["instances"].items():
        mod = probe["queries"][q]
        declared = [("controls", name, fn, None)
                    for name, fn in mod.CONTROLS.items()]
        declared += [("passes_by_design", name, fn, why) for name, (fn, why)
                     in getattr(mod, "PASSES_BY_DESIGN", {}).items()]
        for kind, name, fn, why in declared:
            low = fn(probe["raw"], p or mod.PARAMS)
            r = compare.answer_readings(compare.control_table(low, mod),
                                        probe["references"][key], mod)
            total = out[kind].setdefault(f"{q}.{name}", {
                "answers_wrong": 0, "decimal_units_off_max": 0,
                "per_instance": {}})
            total["answers_wrong"] += r["wrong"]
            total["decimal_units_off_max"] = max(
                total["decimal_units_off_max"], r["units_off"])
            total["per_instance"][key] = {"wrong": r["wrong"],
                                          "units_off": r["units_off"],
                                          "why": r["why"]}
            if why:
                total["reason"] = why
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse-sf", type=float, default=None)
    args = ap.parse_args()
    import harness
    rc = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        probe = {}
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_process=time.perf_counter(),
                               scale=args.rehearse_sf,
                               require_chip=args.rehearse_sf is None,
                               probe=probe)
        t = time.perf_counter()
        ctl = control_readings(probe)
        passed = sorted(name for name, r in ctl["controls"].items()
                        if not all(i["wrong"] for i in
                                   r["per_instance"].values()))
        if passed or not out["correct"]:
            rc = 1
        for name in passed:
            print(f"[control] FINDING: control {name} reads correct in "
                  f"{args.workload} on seed {seed}: nothing separates it "
                  f"from the program", file=sys.stderr, flush=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "device": out["device"],
            "program_correct": out["correct"],
            "program_units_off": out["compared"]["decimal_units_off_max"][
                "value"],
            "program_answers_wrong": out["compared"]["answers_wrong"]["value"],
            "limit": 0, "controls": ctl["controls"],
            "controls_that_read_correct": passed,
            "passes_by_design": ctl["passes_by_design"],
            "controls_s": time.perf_counter() - t,
            "answers": out["compared"]["answers_compared"]["value"],
            "programs_this_seed": out["notes"]["compile"]["programs"],
            "bytes_in_use": out["notes"]["bytes_in_use"],
            "setup_stages_s": out["notes"]["stages_s"]}), flush=True)
        # the plan memo pins the last seed's tables (and their device copy)
        del probe, out
        from spark_rapids_tpu.plan import plan_cache
        plan_cache.clear()
        gc.collect()
    return rc


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
