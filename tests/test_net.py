"""Network front-end suite (docs/net.md).

Covers the wire end to end: frame-codec round-trips under arbitrary
chunk splits, malformed/truncated/oversized-frame rejection without
wedging the accept loop, token auth and idle session reaping, concurrent
multi-tenant sessions bit-identical to in-process ``submit()``, the
SUBMIT-time lowering gate (typed ``unsupported-plan`` with the offending
(op, reason) cell), single reassembled traces across client/wire/
executor spans, and the ``net.*`` chaos sites — a connection killed
mid-flight cancels its query, releases its admission reservation, and
leaves the next query unpoisoned.
"""

import random
import socket
import struct
import threading
import time

import pyarrow as pa
import pytest

from spark_rapids_tpu import faults
from spark_rapids_tpu.config import conf as C
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.faults import blacklist as bl
from spark_rapids_tpu.mem.pool import get_pool
from spark_rapids_tpu.net import NetClient, NetError, QueryFrontend
from spark_rapids_tpu.net import metrics as nm
from spark_rapids_tpu.net import protocol as P
from spark_rapids_tpu.net.session import SessionManager, parse_tokens
from spark_rapids_tpu.obs import memtrack as mt
from spark_rapids_tpu.plan.dataframe import from_arrow
from spark_rapids_tpu.serve import AdmissionRejected, QueryServer
from spark_rapids_tpu.serve import metrics as sm


@pytest.fixture(autouse=True)
def _clean_net():
    faults.reset()
    bl.clear()
    mt.reset()
    nm.reset()
    yield
    faults.reset()
    bl.clear()
    mt.reset()
    C.set_active(None)


def _table(n=600, seed=0):
    return pa.table({"k": [(i * 5 + seed) % 37 for i in range(n)],
                     "v": [float((i + seed) % 101) for i in range(n)]})


def _query(df):
    return (df.filter(E.col("k") > E.lit(3))
            .group_by("k")
            .agg(E.Alias(E.Sum(E.col("v")), "s"))
            .sort("k"))


class _Serving:
    """One QueryServer + QueryFrontend over a registered table set."""

    def __init__(self, tables, conf=None, **server_kw):
        self.conf = conf if conf is not None else C.RapidsConf()
        self.server = QueryServer(self.conf, **server_kw)
        self.frontend = QueryFrontend(self.server, tables=tables)

    def client(self, token="", conf=None):
        return NetClient(self.frontend.host, self.frontend.port,
                         token=token, conf=conf)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.frontend.close()
        self.server.close()
        return False


# -- frame codec -------------------------------------------------------------


def test_frame_roundtrip_survives_any_chunking():
    """Property test: a frame sequence reassembles identically no matter
    how the byte stream is split."""
    rng = random.Random(42)
    frames = [(P.HELLO, b""), (P.SUBMIT, b"x"),
              (P.RESULT_BATCH, bytes(rng.getrandbits(8)
                                     for _ in range(3000))),
              (P.ERROR, P.error_payload("failed", "boom")),
              (P.RESULT_END, b"\x00" * 257)]
    wire = b"".join(P.encode_frame(t, p) for t, p in frames)
    for split in (1, 2, 3, 7, 13, len(wire)):
        buf = P.FrameBuffer(1 << 20)
        got = []
        for i in range(0, len(wire), split):
            got.extend(buf.feed(wire[i:i + split]))
        assert got == frames, f"split={split}"
        assert buf.pending() == 0


def test_frame_header_rejections():
    hdr = struct.Struct("!4sBBHI")
    with pytest.raises(P.ProtocolError, match="bad magic"):
        P.decode_header(hdr.pack(b"XXXX", 1, P.HELLO, 0, 0), 1 << 20)
    with pytest.raises(P.ProtocolError, match="version"):
        P.decode_header(hdr.pack(b"SRTP", 9, P.HELLO, 0, 0), 1 << 20)
    with pytest.raises(P.ProtocolError, match="frame type"):
        P.decode_header(hdr.pack(b"SRTP", 1, 250, 0, 0), 1 << 20)
    # oversized length is refused from the HEADER, before any payload read
    with pytest.raises(P.ProtocolError, match="exceeds"):
        P.decode_header(hdr.pack(b"SRTP", 1, P.SUBMIT, 0, 1 << 30), 1 << 20)
    with pytest.raises(P.ProtocolError, match="short header"):
        P.decode_header(b"SRTP", 1 << 20)


def test_tableref_strip_and_resolve():
    t = _table()
    df = _query(from_arrow(t, partitions=2))
    refs = {id(t): ("t", 1 << 20, 2)}
    stripped = P.strip_tables(df.plan, refs)
    # no pa.Table left anywhere in the stripped tree
    def walk(p):
        assert not hasattr(p, "table") or isinstance(p, P.TableRef)
        for c in p.children:
            walk(c)
    walk(stripped)
    resolved = P.resolve_tables(stripped, {"t": t})
    from spark_rapids_tpu.plan.dataframe import DataFrame
    assert DataFrame(resolved, None, 2).to_arrow().equals(df.to_arrow())
    with pytest.raises(NetError) as ei:
        P.resolve_tables(stripped, {"other": t})
    assert ei.value.code == "protocol"


def test_parse_tokens_validation():
    assert parse_tokens("") == {}
    assert parse_tokens("s3cret=acme, tok2=beta") == {
        "s3cret": "acme", "tok2": "beta"}
    with pytest.raises(ValueError):
        parse_tokens("missing-separator")
    with pytest.raises(ValueError):
        parse_tokens("=tenant")


def test_session_idle_reaping():
    mgr = SessionManager({"tok": "acme"}, idle_timeout_s=0.05)
    s = mgr.authenticate("tok")
    assert s.tenant == "acme" and not s.closed
    assert mgr.reap_idle() == []
    time.sleep(0.12)
    reaped = mgr.reap_idle()
    assert reaped == [s] and s.closed and mgr.active() == []


# -- live front-end ----------------------------------------------------------


def test_remote_query_bit_identical_to_in_process():
    t = _table()
    expected = _query(from_arrow(t, partitions=2)).to_arrow()
    with _Serving({"t": t}) as srv:
        with srv.client() as cl:
            out = cl.submit(_query(cl.table("t", partitions=2)), name="q")
        assert out.equals(expected)  # byte-identical: schema + data
    assert get_pool().used == 0


def test_malformed_frames_do_not_wedge_accept_loop():
    t = _table()
    expected = _query(from_arrow(t, partitions=2)).to_arrow()
    hdr = struct.Struct("!4sBBHI")
    with _Serving({"t": t}) as srv:
        addr = (srv.frontend.host, srv.frontend.port)
        before = nm.counters()["net_protocol_error_total"]
        # garbage bytes, an oversized declared frame, and a truncated
        # frame (header promising more payload than ever arrives)
        for payload in (b"NOPE" * 8,
                        hdr.pack(b"SRTP", 1, P.HELLO, 0, 1 << 29),
                        hdr.pack(b"SRTP", 1, P.HELLO, 0, 500) + b"short"):
            s = socket.create_connection(addr)
            s.sendall(payload)
            time.sleep(0.05)
            s.close()
        deadline = time.monotonic() + 2
        while (nm.counters()["net_protocol_error_total"] < before + 2
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert nm.counters()["net_protocol_error_total"] >= before + 2
        # the accept loop survived all three: a real query still runs
        with srv.client() as cl:
            out = cl.submit(_query(cl.table("t", partitions=2)))
        assert out.equals(expected)


def test_bad_token_rejected_good_token_maps_tenant():
    t = _table()
    conf = C.RapidsConf({
        "spark.rapids.tpu.net.auth.tokens": "s3cret=acme,tok-b=beta"})
    before = nm.counters()["net_auth_fail_total"]
    with _Serving({"t": t}, conf=conf) as srv:
        with pytest.raises(NetError) as ei:
            srv.client(token="wrong")
        assert ei.value.code == "auth"
        assert nm.counters()["net_auth_fail_total"] == before + 1
        with srv.client(token="s3cret") as cl:
            assert cl.tenant == "acme"
            out = cl.submit(_query(cl.table("t", partitions=2)))
            assert out.num_rows > 0


def test_concurrent_multi_tenant_sessions_bit_identical():
    t = _table()
    conf = C.RapidsConf({
        "spark.rapids.tpu.net.auth.tokens": "ta=acme,tb=beta"})
    expected = _query(from_arrow(t, partitions=2)).to_arrow()
    with _Serving({"t": t}, conf=conf) as srv:
        results, errors = {}, []

        def worker(token, wid):
            try:
                with srv.client(token=token) as cl:
                    df = _query(cl.table("t", partitions=2))
                    for i in range(3):
                        results[(wid, i)] = cl.submit(df, name=f"w{wid}-{i}")
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(tok, i))
                   for i, tok in enumerate(["ta", "tb", "ta", "tb"])]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not errors, errors
        assert len(results) == 12
        for out in results.values():
            assert out.equals(expected)
        outcomes = sm.tenant_outcomes()

        def done(tenant):
            return sum(n for (t_, _p), per in outcomes.items() if t_ == tenant
                       for oc, n in per.items()
                       if oc in ("completed", "deduped"))

        assert done("acme") >= 1 and done("beta") >= 1
    assert get_pool().used == 0


def test_remote_query_reassembles_into_one_trace():
    from spark_rapids_tpu.obs import span as sp
    from spark_rapids_tpu.utils import tracing

    t = _table()
    with _Serving({"t": t}) as srv:
        tracing.set_capture(True, clear=True)
        try:
            with srv.client() as cl:
                cl.submit(_query(cl.table("t", partitions=2)), name="traced")
            events = tracing.trace_events(clear=True)
        finally:
            tracing.set_capture(False)
            tracing.trace_events(clear=True)
    traces = sp.assemble_traces({"driver": events})
    mine = [spans for spans in traces.values()
            if any(s["name"] == "net:stream"
                   and s["attrs"].get("query") == "traced" for s in spans)]
    assert len(mine) == 1, "wire spans did not land in exactly one trace"
    names = {s["name"] for s in mine[0]}
    # client trace context flowed through SUBMIT into the executor spans:
    # wire intake, scheduling, and execution are ONE timeline
    assert {"net:accept", "net:stream", "query:submit",
            "query:execute"} <= names


def _captured_request(name, partitions=2):
    """One request over the wire with capture on: its assembled trace (the
    one whose net:request carries ``name``) and the raw events."""
    from spark_rapids_tpu.obs import span as sp
    from spark_rapids_tpu.utils import tracing

    with _Serving({"t": _table()}) as srv:
        with srv.client() as cl:
            df = _query(cl.table("t", partitions=partitions))
            cl.submit(df, name="warm-" + name)  # compile outside the capture
            tracing.set_capture(True, clear=True)
            try:
                cl.submit(df, name=name)
                events = tracing.trace_events(clear=True)
            finally:
                tracing.set_capture(False)
                tracing.trace_events(clear=True)
    traces = sp.assemble_traces({"driver": events})
    mine = [spans for spans in traces.values()
            if any(s["name"] == "net:request"
                   and s["attrs"].get("query") == name for s in spans)]
    assert len(mine) == 1, "the request did not land in exactly one trace"
    return mine[0], events


def test_served_request_is_one_tree_under_a_recorded_net_request():
    """The client's net:request is recorded and owns the id the server's
    spans parent on: every span of the trace descends from it, and the
    request is tiled from the client's send to the decoded table."""
    spans, _ = _captured_request("tiled")
    by_id = {s["span_id"]: s for s in spans}
    roots = [s for s in spans if s["parent_id"] is None]
    assert [r["name"] for r in roots] == ["net:request"]
    for s in spans:
        hops, cur = 0, s
        while cur["parent_id"] is not None:
            assert cur["parent_id"] in by_id, (
                f"{cur['name']} parents on an id nothing recorded")
            cur = by_id[cur["parent_id"]]
            hops += 1
            assert hops < 16
        assert cur is roots[0]
    names = {s["name"] for s in spans}
    assert {"net:request", "net:client-send", "net:accept", "query:submit",
            "query:admit", "query:queue-wait", "query:execute", "query:plan",
            "query:readback", "query:finish", "exec:host-sync",
            "net:wake-lag", "net:stream", "net:client-recv"} <= names
    parent_of = {s["name"]: by_id[s["parent_id"]]["name"]
                 for s in spans if s["parent_id"]}
    assert parent_of["query:submit"] == "net:accept"
    assert parent_of["query:admit"] == "query:submit"
    assert parent_of["query:plan"] == "query:execute"
    assert parent_of["query:finish"] == "query:execute"
    recv = [s for s in spans if s["name"] == "net:client-recv"][0]
    assert recv["attrs"]["rows"] > 0


def test_every_span_lies_inside_its_parent():
    """Real intervals: a child starts and ends within its parent. Spans of
    one thread nest exactly; a child stamped by another thread (the
    server's, under the client's net:request) may close a moment after
    the parent's thread moved on, so it gets a scheduling allowance."""
    spans, events = _captured_request("nested")
    by_id = {s["span_id"]: s for s in spans}
    thread_of = {e["args"]["span_id"]: e["thread"] for e in events
                 if "span_id" in (e.get("args") or {})}
    checked = 0
    for s in spans:
        if s["parent_id"] is None:
            continue
        p = by_id[s["parent_id"]]
        same = thread_of[s["span_id"]] == thread_of[p["span_id"]]
        slack = 0 if same else 5_000_000
        assert s["start_ns"] >= p["start_ns"] - slack, (s["name"], p["name"])
        assert (s["start_ns"] + s["dur_ns"]
                <= p["start_ns"] + p["dur_ns"] + slack), (s["name"],
                                                           p["name"])
        checked += 1
    assert checked >= 12


def test_wake_lag_is_measured_from_the_ticket_to_await_result(monkeypatch):
    """net:wake-lag starts where the ticket stamped done_ns and ends when
    _await_result returns: never negative, never past the stream that
    follows it, and never as long as the backstop timeout."""
    from spark_rapids_tpu.net import frontend as fe_mod

    returned = []
    orig = fe_mod.QueryFrontend._await_result

    def spy(self, conn, wake, ticket):
        try:
            return orig(self, conn, wake, ticket)
        finally:
            returned.append((ticket.done_ns, time.perf_counter_ns()))
    monkeypatch.setattr(fe_mod.QueryFrontend, "_await_result", spy)
    spans, _ = _captured_request("lagged")
    [lag] = [s for s in spans if s["name"] == "net:wake-lag"]
    [stream] = [s for s in spans if s["name"] == "net:stream"]
    [execute] = [s for s in spans if s["name"] == "query:execute"]
    done_ns, back_ns = returned[-1]
    assert lag["start_ns"] == done_ns
    assert lag["dur_ns"] >= 0
    assert lag["start_ns"] >= execute["start_ns"] + execute["dur_ns"]
    end = lag["start_ns"] + lag["dur_ns"]
    assert end <= back_ns and end <= stream["start_ns"]
    assert lag["dur_ns"] < fe_mod._AWAIT_BACKSTOP_S * 1e9
    assert lag["attrs"]["query"] == "lagged"


# -- the wake channel: _await_result wakes on the ticket ----------------------


class _CountedSelect:
    """Stands in for the front-end's ``select`` module: counts the waits of
    _await_result (the only select over two descriptors)."""

    def __init__(self):
        self.waits = 0

    def select(self, rlist, wlist, xlist, timeout=None):
        import select
        if len(rlist) == 2:
            self.waits += 1
        return select.select(rlist, wlist, xlist, timeout)


def _serve_20_traced(monkeypatch):
    """20 requests over one connection with capture on, the backstop far
    beyond the test's patience: their net:wake-lag durations, the net
    counters' movement, and the number of waits."""
    from spark_rapids_tpu.net import frontend as fe_mod
    from spark_rapids_tpu.utils import tracing

    counted = _CountedSelect()
    monkeypatch.setattr(fe_mod, "select", counted)
    monkeypatch.setattr(fe_mod, "_AWAIT_BACKSTOP_S", 60.0)
    with _Serving({"t": _table()}) as srv:
        with srv.client() as cl:
            df = _query(cl.table("t", partitions=2))
            cl.submit(df, name="warm", timeout_s=30)
            before, waits0 = nm.counters(), counted.waits
            tracing.set_capture(True, clear=True)
            try:
                for i in range(20):
                    cl.submit(df, name=f"wake-{i}", timeout_s=30)
                events = tracing.trace_events(clear=True)
            finally:
                tracing.set_capture(False)
                tracing.trace_events(clear=True)
            after = nm.counters()
    lags = [e["dur_ns"] for e in events if e["name"] == "net:wake-lag"]
    moved = {k: after[k] - before[k] for k in after}
    return lags, moved, counted.waits - waits0


def test_wake_lag_median_is_a_hand_off_not_a_poll(monkeypatch):
    """(a) Over 20 served requests the ticket's resolution wakes the
    connection's thread: the median lag is under 5 ms (the 50 ms poll read
    ~25 ms on average) and no request waited out the backstop."""
    import statistics
    lags_ns, moved, _ = _serve_20_traced(monkeypatch)
    assert len(lags_ns) == 20
    assert statistics.median(lags_ns) < 5e6, sorted(lags_ns)
    assert max(lags_ns) < 1e9, sorted(lags_ns)
    assert moved["net_await_wake_timeout_total"] == 0


def test_await_wake_counters_add_up_to_the_waits(monkeypatch):
    """(g) Each wait of _await_result is ended by the ticket, a frame or
    the backstop, and counted once; over healthy requests none by the
    backstop and none by a frame."""
    _, moved, waits = _serve_20_traced(monkeypatch)
    assert moved["net_submit_total"] == 20
    assert waits >= 1
    assert (moved["net_await_wake_ticket_total"]
            + moved["net_await_wake_frame_total"]
            + moved["net_await_wake_timeout_total"]) == waits
    assert moved["net_await_wake_timeout_total"] == 0
    assert moved["net_await_wake_frame_total"] == 0
    # one byte per request; a request whose ticket was done before its
    # wait began leaves its byte to the next wait, which then turns twice
    assert 1 <= moved["net_await_wake_ticket_total"] <= 20


def test_ticket_resolved_before_the_wait_returns_at_once(monkeypatch):
    """(b) The callback is registered after _fulfill ran: it runs at once,
    nothing selects, and the result is there; the next wait on the same
    channel is not fooled by the byte this one left."""
    from spark_rapids_tpu.net import frontend as fe_mod
    from spark_rapids_tpu.serve import QueryContext
    from spark_rapids_tpu.serve.server import Ticket

    monkeypatch.setattr(fe_mod, "_AWAIT_BACKSTOP_S", 60.0)
    with _Serving({}) as srv:
        ours, theirs = socket.socketpair()
        wake = fe_mod._WakeChannel()
        try:
            done = Ticket(None, QueryContext(name="early"), None)
            done._fulfill(pa.table({"x": [7]}))
            t0 = time.monotonic()
            out = srv.frontend._await_result(ours, wake, done)
            assert time.monotonic() - t0 < 5
            assert out.column("x").to_pylist() == [7]
            assert nm.counters()["net_await_wake_timeout_total"] == 0

            late = Ticket(None, QueryContext(name="late"), None)
            threading.Timer(0.2, late._fulfill,
                            args=(pa.table({"x": [8]}),)).start()
            out = srv.frontend._await_result(ours, wake, late)
            assert out.column("x").to_pylist() == [8]
            assert late.done_ns is not None
            assert nm.counters()["net_await_wake_timeout_total"] == 0
        finally:
            wake.close()
            ours.close()
            theirs.close()


def test_backstop_cancels_the_query_of_a_frontend_that_is_closing(
        monkeypatch):
    """(e) What the timeout is kept for: with the socket silent and the
    ticket unresolved, a closing front-end still cancels the query within
    one backstop, and that wait is counted as a timeout."""
    from spark_rapids_tpu.net import frontend as fe_mod
    from spark_rapids_tpu.serve import QueryCancelled, QueryContext
    from spark_rapids_tpu.serve.server import Ticket

    monkeypatch.setattr(fe_mod, "_AWAIT_BACKSTOP_S", 0.1)
    with _Serving({}) as srv:
        ours, theirs = socket.socketpair()
        wake = fe_mod._WakeChannel()
        tk = Ticket(None, QueryContext(name="orphan"), None)

        def executor():  # unwinds at its next poll point, as a real one
            while not tk.ctx.cancelled():
                time.sleep(0.01)
            tk._fail(QueryCancelled(
                f"orphan cancelled: {tk.ctx.cancel_reason}"))

        th = threading.Thread(target=executor)
        th.start()
        try:
            threading.Timer(0.15, setattr,
                            args=(srv.frontend, "_closing", True)).start()
            t0 = time.monotonic()
            with pytest.raises(QueryCancelled, match="frontend shutdown"):
                srv.frontend._await_result(ours, wake, tk)
            assert time.monotonic() - t0 < 5
            assert nm.counters()["net_await_wake_timeout_total"] >= 1
            assert nm.counters()["net_await_wake_ticket_total"] == 1
        finally:
            tk.cancel("test over")
            th.join(5)
            wake.close()
            ours.close()
            theirs.close()
        assert not th.is_alive()


def _slow_cancel_conf(op, ms=600):
    # rides the CLIENT conf (installed when the doomed plan is applied):
    # the query's next cancellation poll sleeps, holding it in flight. The
    # small-query fast path has no such poll, so it is off
    return C.RapidsConf({
        "spark.rapids.tpu.test.faults":
            f"serve.cancel:slow@op={op},ms={ms},count=1",
        C.FASTPATH_ENABLED.key: False})


def _wait_for(cond, seconds=10):
    deadline = time.monotonic() + seconds
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.02)
    return cond()


@pytest.mark.parametrize("how", ["cancel-frame", "disconnect",
                                 "frontend-close"])
def test_in_flight_cancellation_still_cancels_and_does_not_poison(
        how, monkeypatch):
    """(c) CANCEL, (d) the client's disconnect, (e) the front-end's close,
    each while the query runs: the ticket is cancelled as before, through
    the socket or the backstop and not the ticket's wake; the reservation
    is released and the next query is bit-identical."""
    from spark_rapids_tpu.net import frontend as fe_mod
    from spark_rapids_tpu.serve import QueryCancelled

    # only the front-end's close leans on the backstop: short there, and
    # out of reach where a frame has to end the wait
    monkeypatch.setattr(fe_mod, "_AWAIT_BACKSTOP_S",
                        0.1 if how == "frontend-close" else 60.0)
    t = _table()
    expected = _query(from_arrow(t, partitions=2)).to_arrow()
    with _Serving({"t": t}, max_concurrent=1) as srv:
        cancelled0 = sm.counters()["sched_cancelled_total"]
        injected0 = faults.counters()["fault_injected_total"]
        cl = srv.client(conf=_slow_cancel_conf("doomed"))
        df = _query(cl.table("t", partitions=2))
        seen = []

        def run():
            try:
                seen.append(cl.submit(df, name="doomed", timeout_s=20))
            except Exception as e:  # noqa: BLE001 — expected path
                seen.append(e)

        th = threading.Thread(target=run)
        th.start()
        assert _wait_for(lambda: faults.counters()["fault_injected_total"]
                         > injected0)  # inside the slowed cancellation poll
        if how == "cancel-frame":
            cl.cancel()
        elif how == "disconnect":
            # a close() alone waits for the thread blocked in recv
            cl._sock.shutdown(socket.SHUT_RDWR)
            cl.close()
        else:
            srv.frontend.close()
        th.join(timeout=30)
        assert not th.is_alive()
        assert seen and isinstance(seen[0], Exception), seen
        if how == "cancel-frame":
            assert isinstance(seen[0], QueryCancelled)
            assert nm.counters()["net_cancel_total"] == 1
            assert nm.counters()["net_await_wake_frame_total"] >= 1
        elif how == "disconnect":
            assert _wait_for(lambda: nm.counters()[
                "net_disconnect_cancel_total"] == 1)
        assert _wait_for(lambda: sm.counters()["sched_cancelled_total"]
                         > cancelled0)
        assert _wait_for(lambda: srv.server.admission.snapshot()[
            "reserved_bytes"] == 0)
        if how != "frontend-close":
            assert nm.counters()["net_await_wake_timeout_total"] == 0
        cl.close()
        # the next query, over a fresh connection (and a fresh front-end
        # where that one was closed), is unpoisoned
        frontend = (QueryFrontend(srv.server, tables={"t": t})
                    if how == "frontend-close" else srv.frontend)
        try:
            with NetClient(frontend.host, frontend.port) as cl2:
                out = cl2.submit(_query(cl2.table("t", partitions=2)),
                                 timeout_s=30)
            assert out.equals(expected)
        finally:
            frontend.close()
    assert get_pool().used == 0


def test_singleflight_follower_wakes_on_its_primarys_resolution(monkeypatch):
    """(f) A second connection submitting the identical query is deduped
    onto the first one's execution; its thread waits on its own channel
    and is woken by the PRIMARY's resolution, not by a timeout."""
    from spark_rapids_tpu.net import frontend as fe_mod

    monkeypatch.setattr(fe_mod, "_AWAIT_BACKSTOP_S", 60.0)
    t = _table()
    expected = _query(from_arrow(t, partitions=2)).to_arrow()
    conf = _slow_cancel_conf("dup", ms=800)
    with _Serving({"t": t}, max_concurrent=1) as srv:
        hits0 = sm.counters()["sched_singleflight_hit_total"]
        injected0 = faults.counters()["fault_injected_total"]
        results = {}

        def run(who):
            with srv.client(conf=conf) as cl:
                results[who] = cl.submit(
                    _query(cl.table("t", partitions=2)), name="dup",
                    timeout_s=20)

        first = threading.Thread(target=run, args=("primary",))
        first.start()
        assert _wait_for(lambda: faults.counters()["fault_injected_total"]
                         > injected0)  # the primary is held in flight
        second = threading.Thread(target=run, args=("follower",))
        second.start()
        for th in (first, second):
            th.join(timeout=30)
            assert not th.is_alive()
        assert sm.counters()["sched_singleflight_hit_total"] == hits0 + 1
        assert results["primary"].equals(expected)
        assert results["follower"].equals(expected)
        assert nm.counters()["net_await_wake_timeout_total"] == 0
        assert nm.counters()["net_await_wake_ticket_total"] >= 2


def _open_fds():
    """fd -> what it is open on (sockets by inode), as /proc has it."""
    import os
    out = {}
    for fd in os.listdir("/proc/self/fd"):
        try:
            out[fd] = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            pass  # the listing's own descriptor
    return out


def test_a_connection_opens_one_wake_channel_and_closes_it():
    """(h) 100 requests over one connection: the connection holds its
    socket and the two ends of its wake channel from the first request to
    the last, and gives all of them back."""
    with _Serving({"t": _table()}) as srv:
        with srv.client() as warm:
            warm.submit(_query(warm.table("t", partitions=2)), timeout_s=30)
        assert _wait_for(
            lambda: nm.counters()["net_connections_active"] == 0)
        idle = _open_fds()

        def opened_since_idle():
            # by what it is open on, so that a descriptor another test's
            # thread gives back meanwhile is no difference
            return {fd: what for fd, what in _open_fds().items()
                    if idle.get(fd) != what}

        cl = srv.client()
        try:
            df = _query(cl.table("t", partitions=2))
            cl.submit(df, timeout_s=30)
            # the client's socket, the server's, and the channel's two ends
            held = opened_since_idle()
            assert len(held) == 4, held
            assert all(w.startswith("socket:") for w in held.values())
            for _ in range(99):
                cl.submit(df, timeout_s=30)
                assert opened_since_idle() == held
        finally:
            cl.close()
        assert _wait_for(
            lambda: nm.counters()["net_connections_active"] == 0)
        assert _wait_for(lambda: not opened_since_idle()), (
            opened_since_idle())


def test_unsupported_plan_rejected_at_the_wire():
    t = pa.table({"s": ["a", "b", "c"], "v": [1.0, 2.0, 3.0]})
    with _Serving({"t": t}) as srv:
        executed_before = sm.counters()["sched_completed_total"]
        with srv.client() as cl:
            bad = (cl.table("t").group_by("v")
                   .agg(E.Alias(E.Sum(E.col("s")), "bad")))
            with pytest.raises(AdmissionRejected) as ei:
                cl.submit(bad, name="no-lower")
            assert ei.value.reason == "unsupported-plan"
            # the typed error carries the offending (op, reason) cell
            cells = ei.value.detail
            assert any(op == "Aggregate" and "Sum" in reason
                       for op, reason in cells)
            # shed at the wire: the executors never saw it
            assert (sm.counters()["sched_completed_total"]
                    == executed_before)
            # the session is not poisoned: a good plan still runs
            good = (cl.table("t").group_by("s")
                    .agg(E.Alias(E.Sum(E.col("v")), "sv")).sort("s"))
            assert cl.submit(good).num_rows == 3


# -- chaos: net.* fault sites ------------------------------------------------


def test_disconnect_mid_stream_cancels_and_next_query_unpoisoned():
    """net.stream stall + a killed connection: the front-end cancels the
    query, admission drops every reservation, and the next query over a
    fresh connection is bit-identical — an abandoned client costs the
    server nothing durable."""
    t = _table(n=3000)
    # the fault spec rides the CLIENT conf: faults install from the conf
    # of the plan being applied, so the stall arms exactly for the doomed
    # query. Small stream batches make the post-stall sends reliably hit
    # the dead socket.
    fault_conf = C.RapidsConf({
        "spark.rapids.tpu.test.faults": "net.stream:stall@ms=1500,count=1"})
    srv_conf = C.RapidsConf({"spark.rapids.tpu.net.streamBatchRows": 256})
    expected = _query(from_arrow(t, partitions=2)).to_arrow()
    with _Serving({"t": t}, conf=srv_conf, max_concurrent=1) as srv:
        before = nm.counters()["net_disconnect_cancel_total"]
        cl = srv.client(conf=fault_conf)
        df = _query(cl.table("t", partitions=2))
        seen = []

        def run():
            try:
                seen.append(cl.submit(df, name="doomed", timeout_s=0.7))
            except Exception as e:  # noqa: BLE001 — expected path
                seen.append(e)

        th = threading.Thread(target=run)
        th.start()
        time.sleep(0.5)  # server is stalled inside the stream window
        cl.close()       # kill the connection mid-stream
        th.join(timeout=30)
        assert seen and isinstance(seen[0], Exception)
        deadline = time.monotonic() + 10
        while (nm.counters()["net_disconnect_cancel_total"] == before
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert nm.counters()["net_disconnect_cancel_total"] > before
        # reservation released once the handler unwound
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            snap = srv.server.admission.snapshot()
            if snap["reserved_bytes"] == 0 and snap["queued"] == 0:
                break
            time.sleep(0.05)
        assert snap["reserved_bytes"] == 0 and snap["queued"] == 0
        # next query (fault count exhausted) is unpoisoned
        with srv.client() as cl2:
            out = cl2.submit(_query(cl2.table("t", partitions=2)))
        assert out.equals(expected)
    assert get_pool().used == 0


def test_disconnect_while_queued_cancels_the_ticket():
    """A client that vanishes while its query is still waiting behind the
    only executor gets its queued query cancelled (typed), not run."""
    t = _table()
    conf = C.RapidsConf({
        "spark.rapids.tpu.serve.singleflight.enabled": False})
    with _Serving({"t": t}, conf=conf, max_concurrent=1) as srv:
        gate = threading.Event()
        order = []

        class _Blocker:
            conf = None
            shuffle_partitions = 1

            def to_arrow(self):
                gate.wait(10)
                order.append("blocker")
                return pa.table({"x": [1]})

        blocker = srv.server.submit(_Blocker(), name="blocker")
        cancelled_before = sm.counters()["sched_cancelled_total"]
        cl = srv.client()
        df = _query(cl.table("t", partitions=2))

        def run():
            try:
                cl.submit(df, name="abandoned", timeout_s=0.5)
            except Exception:  # noqa: BLE001 — expected disconnect path
                pass

        th = threading.Thread(target=run)
        th.start()
        time.sleep(0.4)  # query is QUEUED behind the blocker
        cl.close()
        th.join(timeout=10)
        # frontend notices EOF and cancels the ticket before release
        deadline = time.monotonic() + 5
        while (nm.counters()["net_disconnect_cancel_total"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.05)
        gate.set()
        blocker.result(timeout_s=30)
        deadline = time.monotonic() + 10
        while (sm.counters()["sched_cancelled_total"] == cancelled_before
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert sm.counters()["sched_cancelled_total"] > cancelled_before
    assert get_pool().used == 0


def test_net_frame_fault_drops_connection_not_listener():
    t = _table()
    with _Serving({"t": t}) as srv:
        # install() is safe here: the drop fires on the first HELLO frame,
        # before any plan apply can re-install from a conf spec
        faults.install("net.frame:drop@count=1")
        with pytest.raises((NetError, OSError)):
            srv.client()
        # the listener survived; the next connection works end to end
        with srv.client() as cl:
            assert cl.submit(_query(cl.table("t", partitions=2))).num_rows > 0
