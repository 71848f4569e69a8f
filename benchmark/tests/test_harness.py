"""The pieces around a run: no chip means no result; the trace reduction
agrees with the recorded trace; traffic is the same work for every seed."""
import json
import os
import subprocess
import sys

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
                             "kind=kLoop") == "fusion.3 fusion"


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

    def fake(cmd, **kw):
        calls.append(cmd)
        return types.SimpleNamespace(returncode=0, stdout=next(told) + "\n")
    monkeypatch.setattr(run.subprocess, "run", fake)
    marker = tmp_path / "cfg" / "primed.cell"
    args = types.SimpleNamespace(workload="cell", seed=5, rehearse_sf=None)
    assert run.prime(args, str(marker)) == 0
    assert len(calls) == 3 and "--prime-child" in calls[0]
    assert json.loads(marker.read_text()) == {"compiled": 0, "exported": 0}
