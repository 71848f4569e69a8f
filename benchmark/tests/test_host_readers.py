"""The host (process) readers of layer_metrics/ (host.*) on made-up span
lists: requests whose spans carry the host attrs (cpu_ns, nivcsw,
proc_cpu_ns) with host:gc events beside them, and a program whose spans
carry none of them (the parent of the change that added them)."""
import pytest

import harness

MS = 1_000_000
READERS = ("host.execute_cpu_ms_per_query", "host.execute_offcpu_ms_per_query",
           "host.client_cpu_ms_per_query", "host.gc_ms_per_query",
           "host.process_cpu_ms_per_query")


def _ev(name, start_ms, dur_ms, trace, sid, parent=None, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "dur_ns": int(dur_ms * MS), "thread": 1,
            "args": dict({"trace_id": trace, "span_id": sid,
                          "parent_id": parent}, **attrs)}


def _request(trace, name, t0, host=True, sync_cpu_ms=0.0, proc_ms=0.0):
    """A 40 ms request: client 2 ms of CPU; execute 30 ms wall with 12 of
    CPU; under it syncs of 6 and 2 ms and a 1.5 ms one inside the readback
    (one level deeper); a sync of another request's tree never counts."""
    r, ex = trace + ":root", trace + ":ex"

    def h(**kw):
        return kw if host else {}
    sync = h(cpu_ns=int(sync_cpu_ms * MS))
    return [
        _ev("net:request", t0, 40, trace, r, query=name, **h(cpu_ns=2 * MS)),
        _ev("net:accept", t0 + 1, 2, trace, trace + ":ac", r, query=name,
            **h(cpu_ns=MS)),
        _ev("query:execute", t0 + 4, 30, trace, ex, r, query=name,
            **h(cpu_ns=12 * MS, nivcsw=3, proc_cpu_ns=int(proc_ms * MS))),
        _ev("query:plan", t0 + 4, 2, trace, trace + ":pl", ex,
            **h(cpu_ns=2 * MS)),
        _ev("exec:host-sync", t0 + 10, 6, trace, trace + ":s1", ex,
            site="fused.overflow_flags", **sync),
        _ev("exec:host-sync", t0 + 17, 2, trace, trace + ":s2", ex,
            site="shrink_to_live", **sync),
        _ev("query:readback", t0 + 20, 4, trace, trace + ":rb", ex,
            **h(cpu_ns=2 * MS)),
        _ev("exec:host-sync", t0 + 20, 1.5, trace, trace + ":s3",
            trace + ":rb", site="batch_to_arrow", **sync),
        _ev("net:stream", t0 + 35, 3, trace, trace + ":st", r, query=name,
            **h(cpu_ns=MS)),
    ]


def _gc(start_ms, dur_ms, generation):
    return {"name": "host:gc", "start_ns": int(start_ms * MS),
            "dur_ns": int(dur_ms * MS), "thread": 2,
            "args": {"generation": generation, "collected": 10}}


def _ctx(spans, names):
    return {"spans": spans, "requests": [{"name": n} for n in names]}


def _read(name, ctx):
    return harness.load_by_path("layer_metrics", name).read(ctx)


def _window(host=True, sync_cpu_ms=0.0):
    spans = (_request("t1", "r1", 0, host, sync_cpu_ms, proc_ms=100)
             + _request("t2", "r2", 50, host, sync_cpu_ms, proc_ms=250)
             + _request("t3", "r3", 100, host, sync_cpu_ms, proc_ms=400))
    if host:
        spans += [_gc(12, 0.5, 0), _gc(60, 4.0, 2), _gc(200, 1.5, 1)]
    return _ctx(spans, ["r1", "r2", "r3"])


def test_execute_and_client_cpu_are_the_spans_cpu_ns():
    ctx = _window()
    assert _read("host.execute_cpu_ms_per_query", ctx) == pytest.approx(12.0)
    assert _read("host.client_cpu_ms_per_query", ctx) == pytest.approx(2.0)


def test_offcpu_is_wall_less_cpu_less_the_syncs_below_execute():
    """30 ms of wall, 12 of CPU, syncs of 6 + 2 + 1.5 at two depths: 8.5 ms
    off the CPU; the same three syncs cost 0.25 ms of CPU each, which lies
    in both cpu_ns and their wall, and is given back once."""
    ctx = _window()
    assert _read("host.execute_offcpu_ms_per_query", ctx) == \
        pytest.approx(30 - 12 - (6 + 2 + 1.5))
    ctx = _window(sync_cpu_ms=0.25)
    assert _read("host.execute_offcpu_ms_per_query", ctx) == \
        pytest.approx(30 - 12 - (6 + 2 + 1.5) + 3 * 0.25)


def test_offcpu_counts_overlapping_syncs_once():
    ctx = _window()
    ctx["spans"].append(_ev("exec:host-sync", 11, 4, "t1", "t1:s4", "t1:ex",
                            site="fence", cpu_ns=0))  # inside s1's 10-16
    got = [_read("host.execute_offcpu_ms_per_query", ctx)]
    assert got == [pytest.approx(8.5)]


def test_process_cpu_divides_by_the_requests_closed_between_samples():
    """Samples at the closes of three requests, 100, 250 and 400 ms: two
    requests closed after the first sample, 150 ms each."""
    ctx = _window()
    assert _read("host.process_cpu_ms_per_query", ctx) == pytest.approx(150)
    # a request outside the window's answered ones is not a sample
    ctx["spans"] += _request("t9", "r9", 150, proc_ms=900)
    assert _read("host.process_cpu_ms_per_query", ctx) == pytest.approx(150)
    one = _ctx(_request("t1", "r1", 0, proc_ms=100), ["r1"])
    assert _read("host.process_cpu_ms_per_query", one) is None


def test_gc_sums_the_window_s_pauses_over_its_requests():
    ctx = _window()
    assert _read("host.gc_ms_per_query", ctx) == \
        pytest.approx((0.5 + 4.0 + 1.5) / 3)
    quiet = _ctx([e for e in ctx["spans"] if e["name"] != "host:gc"],
                 ["r1", "r2", "r3"])
    assert _read("host.gc_ms_per_query", quiet) == 0


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_host_attrs_reads_nothing(name):
    assert _read(name, _window(host=False)) is None
    assert _read(name, _ctx([], [])) is None
