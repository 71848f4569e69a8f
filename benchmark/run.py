#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on: TPC-H
queries through ``NetClient.submit()`` over loopback into the query server,
one process, one TPU chip. The last line of standard output is the result
object; everything else goes to standard error. A run that finds no TPU (or
fewer chips than the cell asks for, or a device kind that peaks.json does not
know) exits with code 3 and prints no result.

``--rehearse-sf <scale>`` is for the sandbox: it runs every phase at that
scale on whatever backend JAX has, prints what it did to standard error,
prints no result line and exits with code 4. No option lets a run without a
chip print a result.

On a cold checkout (no ``primed.<cell>`` marker under .bench_cache/) the
run first repeats the cell's set-up in child processes, one after another
and before this process touches JAX, until one of them neither compiles
nor exports anything:
the program's export store (jit_persist, on by default) makes a program it
loads compile once more in the process after the one that traced it, and
every later run has to find every program in the caches. A child runs in
a process group of its own, and the group is killed when this process is
told to end (SIGTERM, SIGINT), when the child passes its time limit and on
any other exit: a run cut from outside leaves nothing on the chip.

``--self-check`` reduces the recorded trace under testdata/ and compares the
reduction with the numbers kept beside it; it needs no chip.
"""

import time
T_PROCESS = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import atexit  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def self_check() -> int:
    import tracered
    base = os.path.join(HERE, "testdata", "recorded")
    got = tracered.reduce_file(base + ".xplane.pb.gz")
    with open(base + ".expected.json") as f:
        want = json.load(f)
    bad = []
    for key in ("window_s", "busy_s", "launches", "chips_busy"):
        if abs(got[key] - want[key]) > 1e-9 * max(1.0, abs(want[key])):
            bad.append(f"{key}: {got[key]!r} != {want[key]!r}")
    for key in ("device_ops", "idle_gaps"):
        if [k for k, _ in got[key]] != [k for k, _ in want[key]]:
            bad.append(f"{key}: ranking differs")
    print(json.dumps({"self_check": "trace reduction", "ok": not bad,
                      "differences": bad}))
    return 1 if bad else 0


PRIME_CHILDREN = 3  # traced programs, loaded ones, a set-up that is quiet
PRIME_TIMEOUT_S = 1000


def run_child(cmd: list, timeout: float) -> tuple:
    """(exit code, standard output) of ``cmd``, run in a process group of
    its own that does not outlive this process: the group is killed on
    SIGTERM and SIGINT (this process then exits with 128 + the signal), at
    the time-out (code 124) and on every way out of this function. The
    child asks the kernel to kill it when its parent dies (main()), which
    covers a SIGKILL of this process too."""
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                         start_new_session=True)
    print(f"[bench] priming child pid {p.pid}", file=sys.stderr, flush=True)

    def kill_group():
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass  # the group has gone already

    def on_signal(signum, _frame):
        kill_group()
        try:  # not p.wait(): communicate() below may hold Popen's lock
            os.waitpid(p.pid, 0)
        except ChildProcessError:
            pass
        print(f"[bench] signal {signum}: priming child {p.pid} killed",
              file=sys.stderr, flush=True)
        os._exit(128 + signum)
    before = {s: signal.signal(s, on_signal)
              for s in (signal.SIGTERM, signal.SIGINT)}
    atexit.register(kill_group)
    try:
        out, _ = p.communicate(timeout=timeout)
        return p.returncode, out
    except subprocess.TimeoutExpired:
        print(f"[bench] priming child {p.pid} passed {timeout:.0f} s",
              file=sys.stderr, flush=True)
        return 124, ""
    finally:
        kill_group()  # the child, and whatever it left in its group
        p.wait()
        atexit.unregister(kill_group)
        for s, handler in before.items():
            signal.signal(s, handler)


def die_with_parent():
    """Linux: the kernel sends this process SIGKILL when its parent dies,
    however that came about."""
    import ctypes
    PR_SET_PDEATHSIG = 1
    ctypes.CDLL(None).prctl(PR_SET_PDEATHSIG, signal.SIGKILL)


def prime(args, marker: str) -> int:
    """Fills the checkout's caches for the cell; 0, or the child's code."""
    cmd = [sys.executable, os.path.abspath(__file__), "--workload",
           args.workload, "--seed", str(args.seed), "--prime-child"]
    if args.rehearse_sf is not None:
        cmd += ["--rehearse-sf", str(args.rehearse_sf)]
    for i in range(PRIME_CHILDREN):
        rc, out = run_child(cmd, PRIME_TIMEOUT_S)
        if rc != 0:
            return rc
        told = json.loads(out.strip().splitlines()[-1])
        print(f"[bench] priming {i + 1}: {json.dumps(told)}",
              file=sys.stderr, flush=True)
        if told["compiled"] == 0 and told["exported"] == 0:
            break  # it loaded every program and had to compile none
    os.makedirs(os.path.dirname(marker), exist_ok=True)
    with open(marker, "w") as f:
        f.write(json.dumps(told) + "\n")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-sf", type=float, default=None)
    ap.add_argument("--self-check", action="store_true")
    ap.add_argument("--prime-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the run's .xplane.pb into")
    args = ap.parse_args()
    if args.self_check:
        return self_check()
    if not args.workload:
        ap.error("--workload is required")

    import harness
    rehearsal = args.rehearse_sf is not None
    if args.prime_child:
        die_with_parent()
    else:
        marker = harness.primed_marker(args.workload, args.rehearse_sf)
        if not os.path.exists(marker):
            rc = prime(args, marker)
            if rc:
                return rc
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               scale=args.rehearse_sf,
                               require_chip=not rehearsal,
                               keep_trace=args.keep_trace,
                               prime=args.prime_child)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    if args.prime_child:
        print(json.dumps(out), flush=True)
        return 0
    if rehearsal:
        # never a device metric from a rehearsal: counts and checks only
        out["metrics_read"] = sorted(out.pop("metrics"))  # names only
        out["notes"].pop("end_to_end_seen")
        out["notes"].pop("latency_ms")
        print("[bench] rehearsal (no result line): "
              + json.dumps(out, default=str), file=sys.stderr)
        return 4
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # daemon threads of server and front-end hold no state
