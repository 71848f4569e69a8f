"""The span readers this benchmark gained with the program's tiled request
(layer_metrics/net.wake_lag_ms ... plan.finish_ms_per_query, spantree.py) on
made-up span lists: a request its spans tile, one with a hole, and a
program that records none of the new spans (the parent of that PR)."""
import pytest

import harness
import spantree

MS = 1_000_000


def _ev(name, start_ms, dur_ms, trace, sid, parent=None, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "dur_ns": int(dur_ms * MS), "thread": 1,
            "args": dict({"trace_id": trace, "span_id": sid,
                          "parent_id": parent}, **attrs)}


def _request(trace, name, t0, hole_ms=0.0, finish_ms=3.0):
    """A 55 ms request: send 1, accept 2 (submit inside), queue-wait 1,
    execute 30 (plan 2, three syncs, readback 4 with a 1.5 ms sync inside,
    finish), wake-lag 15, stream 4, recv 5 (overlapping the stream)."""
    r = trace + ":root"
    ev = [
        _ev("net:request", t0, 55, trace, r, query=name),
        _ev("net:client-send", t0, 1, trace, trace + ":cs", r),
        _ev("net:accept", t0 + 1, 2, trace, trace + ":ac", r, query=name),
        _ev("query:submit", t0 + 2, 0.5, trace, trace + ":su",
            trace + ":ac", query=name),
        _ev("query:queue-wait", t0 + 3, 1, trace, trace + ":qw", r,
            query=name),
        _ev("query:execute", t0 + 4, 30 - hole_ms, trace, trace + ":ex", r,
            query=name),
        _ev("query:plan", t0 + 4, 2, trace, trace + ":pl", trace + ":ex",
            query=name),
        _ev("exec:host-sync", t0 + 10, 6, trace, trace + ":s1",
            trace + ":ex", site="fused.overflow_flags"),
        _ev("exec:host-sync", t0 + 17, 2, trace, trace + ":s2",
            trace + ":ex", site="shrink_to_live"),
        _ev("query:readback", t0 + 20, 4, trace, trace + ":rb",
            trace + ":ex", rows=4),
        _ev("exec:host-sync", t0 + 20, 1.5, trace, trace + ":s3",
            trace + ":rb", site="batch_to_arrow"),
        _ev("query:finish", t0 + 24, finish_ms, trace, trace + ":fi",
            trace + ":ex"),
        _ev("net:wake-lag", t0 + 34, 15, trace, trace + ":wl", r,
            query=name),
        _ev("net:stream", t0 + 49, 4, trace, trace + ":st", r, query=name),
        _ev("net:client-recv", t0 + 50, 5, trace, trace + ":cr", r, rows=4),
        # an operator event: no trace id, so it is nobody's span
        {"name": "SortExec", "start_ns": int((t0 + 8) * MS),
         "dur_ns": 9 * MS, "thread": 2, "args": {"partition": 0}},
    ]
    return ev


def _ctx(spans, names):
    return {"spans": spans, "requests": [{"name": n} for n in names]}


def _read(metric, ctx):
    return harness.load_by_path("layer_metrics", metric).read(ctx)


def test_tiled_requests_read_zero_self_time_and_their_parts():
    spans = _request("a", "r1", 100) + _request("b", "r2", 200) \
        + _request("c", "warm-up", 0)  # not a request of the window
    ctx = _ctx(spans, ["r1", "r2"])
    assert set(spantree.by_request(spans, ctx["requests"])) == {"r1", "r2"}
    assert _read("net.request_self_ms", ctx) == 0.0
    assert _read("net.wake_lag_ms", ctx) == 15.0
    assert _read("exec.host_syncs_per_query", ctx) == 3.0
    assert _read("exec.host_sync_ms_per_query", ctx) == pytest.approx(9.5)
    # the readback's 4 ms less the 1.5 ms copy inside it
    assert _read("exec.readback_ms_per_query", ctx) == pytest.approx(2.5)
    assert _read("plan.finish_ms_per_query", ctx) == pytest.approx(3.0)


def test_a_hole_shows_as_self_time_and_the_median_sees_the_typical_one():
    # execute ends 6 ms early and nothing else covers 28-34 ms: a hole
    holed = _request("h", "r1", 100, hole_ms=6.0)
    assert _read("net.request_self_ms", _ctx(holed, ["r1"])) == \
        pytest.approx(6.0)
    three = holed + _request("t1", "r2", 200) + _request("t2", "r3", 300)
    assert _read("net.request_self_ms",
                 _ctx(three, ["r1", "r2", "r3"])) == 0.0
    assert _read("plan.finish_ms_per_query", _ctx(
        _request("x", "r1", 0, finish_ms=1.0)
        + _request("y", "r2", 100, finish_ms=5.0), ["r1", "r2"])) == \
        pytest.approx(3.0)


def test_self_time_clips_children_to_the_parent_and_counts_overlap_once():
    parent = {"start": 0, "end": 100}
    others = [{"start": -20, "end": 30}, {"start": 20, "end": 50},
              {"start": 90, "end": 140}, {"start": 200, "end": 300}]
    assert spantree.self_ms(parent, others) == pytest.approx(40 / 1e6)
    assert spantree.self_ms(parent, []) == pytest.approx(100 / 1e6)


@pytest.mark.parametrize("metric", [
    "net.wake_lag_ms", "net.request_self_ms", "exec.host_syncs_per_query",
    "exec.host_sync_ms_per_query", "exec.readback_ms_per_query",
    "plan.finish_ms_per_query"])
def test_a_program_without_the_span_reads_nothing_and_does_not_raise(metric):
    """What the parent commit records for a request: no client span, no
    syncs, no readback or finish; and a window with no spans at all."""
    old = [_ev("net:accept", 1, 2, "a", "ac", "unrecorded", query="r1"),
           _ev("query:submit", 2, 0.5, "a", "su", "unrecorded", query="r1"),
           _ev("query:queue-wait", 3, 1, "a", "qw", "unrecorded",
               query="r1"),
           _ev("query:execute", 4, 30, "a", "ex", "unrecorded", query="r1"),
           _ev("query:plan", 4, 2, "a", "pl", "ex", profile=7),
           _ev("net:stream", 49, 4, "a", "st", "unrecorded", query="r1")]
    assert _read(metric, _ctx(old, ["r1"])) is None
    assert _read(metric, _ctx([], ["r1"])) is None


def test_every_new_metric_is_declared_and_has_its_reader():
    spec = harness.load_cell("sf1_q1_agg1")
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name, layer in [("net.wake_lag_ms", "wire (net/)"),
                        ("net.request_self_ms", "wire (net/)"),
                        ("exec.host_syncs_per_query", "operators (exec/)"),
                        ("exec.host_sync_ms_per_query", "operators (exec/)"),
                        ("exec.readback_ms_per_query", "operators (exec/)"),
                        ("plan.finish_ms_per_query", "planner (plan/)")]:
        m = declared[name]
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            layer, "query_rate", "program_span", "lower")
        assert "workloads" not in m
        assert callable(harness.load_by_path("layer_metrics", name).read)
