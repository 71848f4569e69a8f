#!/usr/bin/env python3
"""The readings a limit of ``correct`` is set from, and its control.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process (set-up is most of a run): the program's
readings are ``answers_wrong`` and ``decimal_units_off_max`` of a short
window of the cell at its own size and load, as run.py would judge it; the
control's readings are the same numbers for the plain reference computed
with money as float64 dollars (the step below DECIMAL that would tempt a
later PR; predicates stay exact, which is the kindest float engine) and put
in the program's place, one answer per query and set of parameters. One
JSON line per seed on standard output. The benchmark's own runs never run
this.
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


def control_reading(probe: dict) -> dict:
    """The float64-money reference judged against the exact reference by the
    run's own comparison, over the cell's queries and parameter sets."""
    import compare
    wrong, worst, per = 0, 0, {}
    for key, (q, p) in probe["instances"].items():
        mod = probe["queries"][q]
        low = (mod.reference(probe["raw"], p, money=float) if p
               else mod.reference(probe["raw"], money=float))
        r = compare.answer_readings(compare.control_table(low, mod),
                                    probe["references"][key], mod)
        wrong += r["wrong"]
        worst = max(worst, r["units_off"])
        per[key] = r["units_off"]
    return {"answers_wrong": wrong, "decimal_units_off_max": worst,
            "per_instance": per}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse-sf", type=float, default=None)
    args = ap.parse_args()
    import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        probe = {}
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_process=time.perf_counter(),
                               scale=args.rehearse_sf,
                               require_chip=args.rehearse_sf is None,
                               probe=probe)
        ctl = control_reading(probe)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "device": out["device"],
            "program_correct": out["correct"],
            "program_units_off": out["compared"]["decimal_units_off_max"][
                "value"],
            "program_answers_wrong": out["compared"]["answers_wrong"]["value"],
            "control": ctl, "limit": 0,
            "control_correct": ctl["answers_wrong"] == 0,
            "answers": out["compared"]["answers_compared"]["value"],
            "programs_this_seed": out["notes"]["compile"]["programs"],
            "bytes_in_use": out["notes"]["bytes_in_use"],
            "setup_stages_s": out["notes"]["stages_s"]}), flush=True)
        # the plan memo pins the last seed's tables (and their device copy)
        del probe, out
        from spark_rapids_tpu.plan import plan_cache
        plan_cache.clear()
        gc.collect()
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
