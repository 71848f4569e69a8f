"""The pieces around a run: no chip means no result; the trace reduction
agrees with the recorded trace, names an op by its program and charges no
nanosecond twice; traffic is the same work for every seed; a cold checkout
is primed by children that do not outlive a run cut from outside; the conf
is made before the data."""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

import harness
import tracered
import trafficgen as traffic

RUN = os.path.join(harness.HERE, "run.py")


def test_no_chip_no_result():
    p = subprocess.run([sys.executable, RUN, "--workload", "sf1_q1_agg1",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, cwd=harness.ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 3 and p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.peaks_for("TPU v9 imaginary")
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_recorded_trace_self_check():
    p = subprocess.run([sys.executable, RUN, "--self-check"],
                       capture_output=True, text=True, cwd=harness.ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout + p.stderr
    assert json.loads(p.stdout.strip().splitlines()[-1])["ok"] is True


def test_union_and_labels():
    assert tracered.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tracered.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tracered.op_label(
        '%custom-call.1 = f32[1048576]{0:T(1024)S(1)} custom-call(f64[1048576]'
        '{0:T(1024)} %b), custom_call_target="X64SplitLow"'
    ) == "custom-call.1 X64SplitLow"
    assert tracered.op_label("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), "
                             "kind=kLoop") == "fusion.3"
    assert tracered.op_label("%batch_0.1 = f32[8]{0} copy(f32[8]{0} %p)"
                             ) == "batch_0.1 copy"
    mods = [("jit_step(6844749427986326659)", 0, 1),
            ("jit_step(6844749427986326659)", 2, 3),
            ("jit_call(5716514634917873765)", 4, 5),
            ("jit_call(1234567890123456789)", 6, 7)]
    # programs loaded from the export store are all called jit_call
    assert tracered.program_labels(mods) == {
        "jit_step(6844749427986326659)": "jit_step",
        "jit_call(5716514634917873765)": "jit_call.571651",
        "jit_call(1234567890123456789)": "jit_call.123456"}


def test_an_op_is_named_by_its_program_and_charged_once():
    """Two programs each have a ``fusion.1``; a ``while``'s body ops lie
    inside its interval on the same line and take their own time, the
    ``while`` keeps the rest; what overlaps in part goes to the later op."""
    mods = [("jit_a(1)", 0, 100), ("jit_b(2)", 200, 400)]
    ops = [("%fusion.1 = f32[] fusion()", 10, 40),
           ("%while.4 = (s32[]) while((s32[]) %t), body=%b", 50, 100),
           ("%fusion.10 = f32[] fusion()", 55, 75),   # the while's body
           ("%fusion.10 = f32[] fusion()", 80, 95),
           ("%fusion.1 = f32[] fusion()", 200, 300),
           ("%copy.2 = f32[] copy()", 290, 330),      # overlaps in part
           ("%fusion.7 = f32[] fusion()", 500, 520)]  # in no program
    got = tracered.self_times(ops, mods, 0, 1000)
    assert got == {"jit_a/fusion.1": 30, "jit_a/while.4": 15,
                   "jit_a/fusion.10": 35, "jit_b/fusion.1": 90,
                   "jit_b/copy.2": 40, "?/fusion.7": 20}
    busy = sum(e - s for s, e in tracered.union([(s, e) for _, s, e in ops]))
    assert sum(got.values()) == busy
    # clipped to the window
    assert tracered.self_times(ops, mods, 60, 90) == {
        "jit_a/fusion.10": 25, "jit_a/while.4": 5}


def test_the_recorded_trace_s_ops_sum_to_its_busy_time():
    planes = tracered.read_planes(os.path.join(
        harness.HERE, "testdata", "recorded.xplane.pb.gz"))
    lo, hi = [h for h in planes["host"] if h[0] == tracered.WINDOW][0][1:]
    [lines] = planes["devices"].values()
    charged = tracered.self_times(lines["XLA Ops"], lines["XLA Modules"],
                                  lo, hi)
    reduced = tracered.reduce_planes(planes)
    assert abs(sum(charged.values()) / 1e9 - reduced["busy_s"]) < 1e-9
    assert all("/" in k and not k.startswith("?/") for k in charged)
    assert reduced["device_ops"][0][0].startswith("jit_")
    with open(os.path.join(harness.HERE, "testdata",
                           "recorded.expected.json")) as f:
        assert json.load(f)["device_ops"] == reduced["device_ops"]


def test_reduction_of_a_made_up_trace():
    planes = {"host": [("bench:window", 100, 1100),
                       ("bench:submit:q6:r1", 50, 600)],
              "devices": {"/device:TPU:0": {
                  "XLA Modules": [("jit_a(1)", 90, 300), ("jit_b(2)", 400, 700),
                                  ("jit_c(3)", 1200, 1300)],
                  "XLA Ops": [("%a = f32[] add()", 90, 200),
                              ("%b = f32[] fusion()", 150, 300),
                              ("%c = f32[] fusion()", 400, 700)]}}}
    r = tracered.reduce_planes(planes, [("query:execute", 690, 1000)])
    assert r["window_s"] == 1000e-9
    assert r["busy_s"] == pytest.approx((200 + 300) * 1e-9)
    assert r["launches"] == 1  # only jit_b begins inside the window
    # %a began in jit_a, before the window; %b began last where they overlap
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_a/a add": 50e-9, "jit_a/b fusion": 150e-9,
         "jit_b/c fusion": 300e-9})
    gaps = dict(r["idle_gaps"])
    assert gaps["query:execute"] == pytest.approx(400e-9)
    assert gaps["request in flight, outside the program's spans"] == \
        pytest.approx(100e-9)


@pytest.mark.parametrize("name", ["streams2_mix", "q1_agg1"])
def test_traffic_is_the_same_work_for_every_seed(name):
    mix = traffic.load(traffic.traffic_path(harness.ROOT, name))
    for i, stream in enumerate(mix["streams"]):
        n = len(stream["queries"])
        for seed in (1, 2147483999):
            cyc = traffic.stream_cycle(mix, seed, i)
            got = [next(cyc) for _ in range(2 * n)]
            assert sorted(got[:n]) == sorted(stream["queries"])
            assert got[:n] == got[n:]  # the stream's order, from a seeded place
    keys = {traffic.instance_key(q, s["params"].get(q))
            for s in mix["streams"] for q in s["queries"]}
    assert len(keys) == sum(len(set(s["queries"])) for s in mix["streams"])


def test_cold_checkout_is_primed_before_jax_is_touched(tmp_path, monkeypatch):
    """prime() runs children until one compiles nothing, then leaves the
    marker that lets later runs skip it."""
    import types
    sys.path.insert(0, harness.HERE)
    import run
    told = iter(['{"compiled": 0, "exported": 5}',  # a warm XLA cache
                 '{"compiled": 3, "exported": 0}',
                 '{"compiled": 0, "exported": 0}'])
    calls = []

    def fake(cmd, timeout):
        calls.append(cmd)
        assert timeout == run.PRIME_TIMEOUT_S
        return 0, next(told) + "\n"
    monkeypatch.setattr(run, "run_child", fake)
    marker = tmp_path / "cfg" / "primed.cell"
    args = types.SimpleNamespace(workload="cell", seed=5, rehearse_sf=None)
    assert run.prime(args, str(marker)) == 0
    assert len(calls) == 3 and "--prime-child" in calls[0]
    assert json.loads(marker.read_text()) == {"compiled": 0, "exported": 0}
    # a child that fails, or passes its time limit, leaves no marker
    marker.unlink()
    monkeypatch.setattr(run, "run_child", lambda cmd, timeout: (124, ""))
    assert run.prime(args, str(marker)) == 124 and not marker.exists()


def _alive(pid: int) -> bool:
    """A process that runs (a zombie has ended)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except FileNotFoundError:
        return False


def test_a_child_s_group_dies_with_the_time_limit_and_on_the_way_out():
    sys.path.insert(0, harness.HERE)
    import run
    # the child starts a grandchild in its group and hangs: time limit
    code = ("import subprocess, sys, time; p = subprocess.Popen([sys."
            "executable, '-c', 'import time; time.sleep(60)']); "
            "print(p.pid, flush=True); time.sleep(60)")
    t = time.perf_counter()
    rc, out = run.run_child([sys.executable, "-c", code], 1.5)
    assert (rc, out) == (124, "") and time.perf_counter() - t < 20
    # the child ends and leaves a grandchild behind: killed on the way out
    code = ("import subprocess, sys; p = subprocess.Popen([sys.executable, "
            "'-c', 'import time; time.sleep(60)'], stdout=subprocess.DEVNULL"
            "); print(p.pid, flush=True)")
    rc, out = run.run_child([sys.executable, "-c", code], 30)
    assert rc == 0
    deadline = time.perf_counter() + 10
    while _alive(int(out)) and time.perf_counter() < deadline:
        time.sleep(0.05)
    assert not _alive(int(out))
    assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL


def test_a_run_cut_during_priming_leaves_no_child(tmp_path):
    """run.py on a checkout without the cell's marker (a scale no other
    test uses), told to end while its priming child is in set-up: the child
    is gone when run.py is, and nothing was primed."""
    sf = "0.0137"
    marker = harness.primed_marker("sf1_q1_agg1", float(sf))
    assert not os.path.exists(marker)
    err = tmp_path / "stderr"
    with open(err, "w") as f:
        p = subprocess.Popen(
            [sys.executable, RUN, "--workload", "sf1_q1_agg1", "--seed", "9",
             "--seconds", "1", "--rehearse-sf", sf], stdout=subprocess.PIPE,
            stderr=f, text=True, cwd=harness.ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        child, deadline = None, time.perf_counter() + 120
        while child is None and time.perf_counter() < deadline:
            # the child's own first line: it has imported the harness
            text = err.read_text()
            if "[bench] sf1_q1_agg1 seed=9" in text:
                child = int(text.split("priming child pid ")[1].split()[0])
            time.sleep(0.1)
        assert child and _alive(child), err.read_text()[-2000:]
        p.send_signal(signal.SIGTERM)
        assert p.wait(timeout=30) == 128 + signal.SIGTERM
        assert p.stdout.read().strip() == ""
        assert not _alive(child)
        assert not os.path.exists(marker)
    finally:
        p.kill()
        p.wait()


def test_the_conf_is_made_before_the_data(monkeypatch):
    """A build that lacks a capability the configuration requires refuses
    before datagen.make: in seconds, not after SF10's minute of data."""
    from spark_rapids_tpu.config import conf as C
    made = []
    monkeypatch.setattr(harness.datagen, "make",
                        lambda *a, **kw: made.append(a) or {})
    monkeypatch.setattr(C, "CAPABILITIES", {})
    with pytest.raises(ValueError, match="this build lacks"):
        harness.run_cell("sf10_q1_agg1", 1, 1.0, False,
                         t_process=time.perf_counter(), scale=0.01,
                         require_chip=False, log=lambda *a: None)
    assert made == []
