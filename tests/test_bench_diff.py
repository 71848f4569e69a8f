"""Perf-trajectory sentinel suite (tools/bench_diff.py).

The sentinel runs CLEAN over a bench history with every kind of drift a
real one has had — degraded rc=124 / rc=1 rounds tolerated, a MULTICHIP
tail without metric lines tolerated, a metric rename between rounds 1 and
2 starting a fresh history — AND exits nonzero when a regression round is
injected. The history is synthetic and built in ``tmp_path``: the rounds
once checked in at the root were measured on a platform that no longer
exists and were deleted (CHANGES.md, PR 26); what remains at the root
(``MULTICHIP_r02``-``r05``, the on/off tracker rounds) must still gate
clean.
"""

import json
import os
import pathlib
import sys

import pytest

REPO = str(pathlib.Path(__file__).resolve().parents[1])
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import bench_diff  # noqa: E402


def _write(dirpath, name, doc):
    with open(os.path.join(dirpath, name), "w") as f:
        json.dump(doc, f)


#: a synthetic history with the drift of a real one; the numbers mean nothing
_HISTORY = {
    "BENCH_r01.json": {"rc": 0, "tail": "", "parsed": {
        "metric": "tpch_q1_q6_sf0.2_rows_per_sec", "value": 2000.0}},
    # workload rename r01 -> r02: a lower value under a NEW name is no drop
    "BENCH_r02.json": {"rc": 0, "tail": "", "parsed": {
        "metric": "tpch_q1_q6_sf2.0_rows_per_sec", "value": 1000.0}},
    "BENCH_r03.json": {"rc": 0, "tail": "", "parsed": {
        "metric": "tpch_q1_q6_sf2.0_rows_per_sec", "value": 1200.0}},
    "BENCH_r04.json": {"rc": 0, "tail": "", "parsed": {
        "metric": "tpch_q1_q3_q6_sf2.0_rows_per_sec", "value": 800.0,
        "utilization": 0.01}},
    # a round cut by its time limit: nothing parsed
    "BENCH_r05.json": {"rc": 124, "tail": "[bench] plans+uploads\n",
                       "parsed": None},
    # a multichip round that failed, then one that passed; neither tail
    # carries a metric line
    "MULTICHIP_r01.json": {"n_devices": 8, "rc": 1, "ok": False,
                           "skipped": False, "tail": "Traceback ...\n"},
    "MULTICHIP_r02.json": {"n_devices": 8, "rc": 0, "ok": True,
                           "skipped": False, "tail": "dryrun ok\n"},
}


@pytest.fixture()
def bench_dir(tmp_path):
    """A bench history the tests can extend."""
    d = tmp_path / "rounds"
    d.mkdir()
    for name, doc in _HISTORY.items():
        _write(str(d), name, doc)
    return str(d)


def test_clean_over_history(bench_dir, capsys):
    """The history — including the degraded r05/multichip-r01 rounds and
    the r01→r02 workload rename — gates clean, and so does what is
    checked in at the root."""
    assert bench_diff.main(["--dir", bench_dir]) == 0
    out = capsys.readouterr().out
    assert "rounds clean" in out
    assert "DEGRADED (rc=124)" in out          # BENCH_r05 tolerated
    assert "DEGRADED (rc=1)" in out            # MULTICHIP_r01 tolerated
    assert bench_diff.main(["--dir", REPO]) == 0


def test_injected_regression_exits_nonzero(bench_dir, capsys):
    """A new round whose tracked metric drops >threshold below the best
    prior round under the SAME name fails the gate."""
    prior = json.load(open(os.path.join(bench_dir, "BENCH_r04.json")))
    metric = prior["parsed"]["metric"]
    _write(bench_dir, "BENCH_r06.json", {
        "rc": 0, "tail": "",
        "parsed": {"metric": metric,
                   "value": prior["parsed"]["value"] * 0.5},
    })
    assert bench_diff.main(["--dir", bench_dir]) == 1
    err = capsys.readouterr().err
    assert "REGRESSION" in err and metric in err
    assert "BENCH_r06.json" in err


def test_within_threshold_drop_is_noise(bench_dir):
    prior = json.load(open(os.path.join(bench_dir, "BENCH_r04.json")))
    _write(bench_dir, "BENCH_r06.json", {
        "rc": 0, "tail": "",
        "parsed": {"metric": prior["parsed"]["metric"],
                   "value": prior["parsed"]["value"] * 0.9},
    })
    # 10% drop < default 15% threshold: noise, not a regression ...
    assert bench_diff.main(["--dir", bench_dir]) == 0
    # ... but a tighter threshold flags the same round
    assert bench_diff.main(["--dir", bench_dir, "--threshold", "0.05"]) == 1


def test_degraded_round_never_fails_alone(bench_dir):
    """rc!=0 / parsed-null rounds are reported and contribute no
    baselines — even with absurd numbers in their tail."""
    _write(bench_dir, "BENCH_r06.json", {
        "rc": 17, "parsed": None,
        "tail": '{"metric": "tpch_q1_q3_q6_sf2.0_rows_per_sec", '
                '"value": 1.0}\n',
    })
    assert bench_diff.main(["--dir", bench_dir]) == 0
    # and the degraded round's tail numbers did not become a baseline:
    # a later healthy round at the old level is still clean
    prior = json.load(open(os.path.join(bench_dir, "BENCH_r04.json")))
    _write(bench_dir, "BENCH_r07.json", {
        "rc": 0, "tail": "",
        "parsed": dict(prior["parsed"]),
    })
    assert bench_diff.main(["--dir", bench_dir]) == 0


def test_renamed_metric_starts_fresh_history(bench_dir):
    """Schema/workload drift: a new metric NAME is a fresh history even
    when its value is far below an unrelated prior metric's."""
    _write(bench_dir, "BENCH_r06.json", {
        "rc": 0, "tail": "",
        "parsed": {"metric": "tpch_q9_sf2.0_rows_per_sec", "value": 3.0},
    })
    assert bench_diff.main(["--dir", bench_dir]) == 0


def test_extract_metrics_tail_and_parsed_precedence():
    doc = {
        "tail": "\n".join([
            "noise line",
            '{"suite": "tpch", "rows_per_sec": 100.0}',
            '{"query": "q1", "roofline_util": 0.5}',
            '{"metric": "m_rows_per_sec", "value": 7.0, '
            '"utilization": 0.1}',
            '{"metric": "bool_guard", "value": true}',
            "{not json}",
        ]),
        "parsed": {"metric": "m_rows_per_sec", "value": 9.0},
    }
    m = bench_diff.extract_metrics(doc)
    assert m["suite:tpch:rows_per_sec"] == 100.0
    assert m["query:q1:roofline_util"] == 0.5
    # the parsed summary is authoritative over its stale tail duplicate
    assert m["m_rows_per_sec"] == 9.0
    assert m["m_rows_per_sec:utilization"] == 0.1
    assert "bool_guard" not in m      # bools are not metric values


def test_lower_is_better_metrics_ignored(bench_dir):
    """Latency-style metrics never participate in the higher-is-better
    gate, whatever direction they move."""
    for i, v in ((6, 10.0), (7, 500.0)):
        _write(bench_dir, f"BENCH_r0{i}.json", {
            "rc": 0, "tail": "",
            "parsed": {"metric": "warm_wall_p50_ms", "value": v},
        })
    assert bench_diff.main(["--dir", bench_dir]) == 0


def test_usage_errors_exit_two(tmp_path):
    assert bench_diff.main(["--dir", str(tmp_path / "nope")]) == 2
    assert bench_diff.main(["--dir", str(tmp_path), "--threshold",
                            "1.5"]) == 2
    # an empty directory is clean, not an error (first round ever)
    assert bench_diff.main(["--dir", str(tmp_path)]) == 0


def test_unreadable_round_is_degraded_not_fatal(bench_dir):
    with open(os.path.join(bench_dir, "BENCH_r06.json"), "w") as f:
        f.write("{truncated")
    assert bench_diff.main(["--dir", bench_dir]) == 0


def test_json_report_shape(bench_dir, capsys):
    assert bench_diff.main(["--dir", bench_dir, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["regressions"] == []
    kinds = {r["kind"] for r in doc["rounds"]}
    assert kinds == {"bench", "multichip"}
    assert doc["threshold"] == 0.15
