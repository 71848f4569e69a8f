"""QueryFrontend: the TCP serving loop in front of QueryServer.

Topology: one listener socket, one accept thread, one handler thread per
connection. The handler speaks the protocol.py framing — HELLO exchange
(the server's banner carries the table catalog as serialized Arrow
schemas), AUTH (token -> tenant via session.py), then SUBMIT/CANCEL
until either side hangs up. Results stream back as Arrow IPC record
batches; backpressure is the TCP window — ``sendall`` blocks when the
client stops draining, which stalls only that query's handler thread,
never the executors (the query already completed by the time streaming
starts; PR-10's Ticket is a one-shot future, not an iterator).

Failure containment, in order of blast radius:

- a malformed/oversized/truncated frame, a fault at ``net.frame``, or
  any per-connection exception kills that CONNECTION (typed ERROR frame
  when the socket still writes), never the accept loop;
- a client disconnect (or ``net.stream`` fault) while its query is
  queued or streaming cancels the query via ``ticket.cancel`` — the
  executor unwinds at its poll points and admission releases the
  reservation, so an abandoned query cannot hold queue slots or HBM
  promises (chaos-tested in tests/test_net.py);
- ``net.accept`` faults drop the incoming connection pre-handshake.

Waiting for a result: the handler selects on the client's socket (for a
CANCEL frame or a disconnect) and on the connection's wake channel, which
the ticket's resolution pokes (``Ticket.add_done_callback``). The select's
timeout is a backstop for a front-end closed under a running query; no
request waits on it. ``net_await_wake_*_total`` count what ended each wait.

Tracing: the client ships its ``TraceContext`` wire tuple in SUBMIT, the
front-end passes it to ``QueryServer.submit(trace=...)`` and records its
own ``net:accept`` / ``net:stream`` spans under the same trace — a remote
query reassembles into ONE trace spanning client, wire, and executors.
"""

from __future__ import annotations

import select
import socket
import threading
import time
from typing import Dict, Optional

from spark_rapids_tpu.net import metrics as _m
from spark_rapids_tpu.net import protocol as P
from spark_rapids_tpu.net.session import Session, SessionManager, parse_tokens

_POLL_S = 0.05
# _await_result's select returns on the ticket's wake or on a frame; this
# only bounds how long a frontend closed under a running query takes to
# cancel it
_AWAIT_BACKSTOP_S = 1.0


class _WakeChannel:
    """A connection's selectable wake-up: ``poke()`` from any thread makes
    ``select`` on the channel return in the connection's thread. A socket
    pair and not an ``os.eventfd``: a poke that arrives after ``close()``
    (a cancelled follower's primary resolving later) finds a closed socket
    object, never a descriptor number that something else now owns."""

    def __init__(self):
        self._r, self._w = socket.socketpair()
        self._r.setblocking(False)
        self._w.setblocking(False)
        self._lock = threading.Lock()  # poke against close

    def fileno(self) -> int:
        return self._r.fileno()

    def poke(self) -> None:
        with self._lock:
            try:
                self._w.send(b"\0")
            except OSError:
                pass  # closed, or full of wake-ups nobody drained yet

    def drain(self) -> None:
        try:
            self._r.recv(4096)
        except BlockingIOError:
            pass

    def close(self) -> None:
        with self._lock:
            self._r.close()
            self._w.close()


class QueryFrontend:
    """Serve one QueryServer over TCP. ``tables`` is the named catalog
    remote plans reference through TableRef leaves."""

    def __init__(self, server, tables: Optional[Dict[str, object]] = None,
                 host: Optional[str] = None, port: Optional[int] = None,
                 conf=None):
        from spark_rapids_tpu.config import conf as C
        self.server = server
        self.conf = conf if conf is not None else server.conf
        self.max_frame_bytes = int(C.NET_MAX_FRAME_BYTES.get(self.conf))
        self.stream_batch_rows = int(C.NET_STREAM_BATCH_ROWS.get(self.conf))
        self._gate = bool(C.NET_SUBMIT_GATE_ENABLED.get(self.conf))
        self.sessions = SessionManager(
            parse_tokens(C.NET_AUTH_TOKENS.get(self.conf)),
            float(C.NET_SESSION_IDLE_TIMEOUT_S.get(self.conf)))
        self._catalog: Dict[str, object] = dict(tables or {})
        self._lock = threading.Lock()
        self._closing = False
        self._conns: Dict[int, socket.socket] = {}
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((
            host if host is not None else C.NET_HOST.get(self.conf),
            int(port if port is not None else C.NET_PORT.get(self.conf))))
        self._listener.listen(64)
        self.host, self.port = self._listener.getsockname()[:2]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="srtpu-net-accept", daemon=True)
        self._accept_thread.start()

    @property
    def address(self):
        return (self.host, self.port)

    def register_table(self, name: str, table) -> None:
        with self._lock:
            self._catalog[name] = table

    # -- accept loop -------------------------------------------------------
    def _accept_loop(self) -> None:
        from spark_rapids_tpu import faults
        while not self._closing:
            try:
                ready, _, _ = select.select([self._listener], [], [],
                                            _POLL_S)
            except OSError:
                return  # listener closed under us
            self.sessions.reap_idle()
            if not ready:
                continue
            try:
                conn, peer = self._listener.accept()
            except OSError:
                return
            _m.bump("net_connections_total")
            try:
                # an injected accept fault drops the CONNECTION — the
                # loop itself must survive every action the grammar has
                faults.check("net.accept", op="accept", file=str(peer[0]))
            except Exception:
                conn.close()
                continue
            with self._lock:
                if self._closing:
                    conn.close()
                    return
                self._conns[conn.fileno()] = conn
                _m.set_level("net_connections_active", len(self._conns))
            threading.Thread(target=self._serve_conn, args=(conn,),
                             name=f"srtpu-net-conn-{peer[1]}",
                             daemon=True).start()

    # -- per-connection handler -------------------------------------------
    def _serve_conn(self, conn: socket.socket) -> None:
        fileno = conn.fileno()
        session: Optional[Session] = None
        wake: Optional[_WakeChannel] = None
        try:
            # one per connection, not per request: a request pays no socket()
            wake = _WakeChannel()
            session = self._handshake(conn)
            if session is not None:
                self._serve_session(conn, wake, session)
        except (P.ConnectionClosed, BrokenPipeError, ConnectionError,
                OSError):
            pass  # peer gone; nothing left to tell it
        except P.ProtocolError as e:
            _m.bump("net_protocol_error_total")
            self._try_error(conn, "protocol", str(e))
        except Exception as e:  # noqa: BLE001 — connection-scoped
            self._try_error(conn, "failed", f"{type(e).__name__}: {e}")
        finally:
            if session is not None:
                self.sessions.drop(session)
            if wake is not None:
                wake.close()
            try:
                conn.close()
            except OSError:
                pass
            with self._lock:
                self._conns.pop(fileno, None)
                _m.set_level("net_connections_active", len(self._conns))

    def _recv(self, conn):
        from spark_rapids_tpu import faults
        ftype, payload = P.recv_frame(conn, self.max_frame_bytes)
        faults.check("net.frame", op=P.TYPE_NAMES.get(ftype, "?"))
        payload = faults.corrupt("net.frame", payload,
                                 op=P.TYPE_NAMES.get(ftype, "?"))
        _m.bump("net_frames_rx_total")
        _m.bump("net_bytes_rx_total", P.HEADER_BYTES + len(payload))
        return ftype, payload

    def _send(self, conn, ftype: int, payload: bytes = b"") -> None:
        n = P.send_frame(conn, ftype, payload)
        _m.bump("net_frames_tx_total")
        _m.bump("net_bytes_tx_total", n)

    def _try_error(self, conn, code: str, message: str, detail=None) -> None:
        try:
            self._send(conn, P.ERROR, P.error_payload(code, message, detail))
        except (BrokenPipeError, ConnectionError, OSError):
            pass

    def _handshake(self, conn) -> Optional[Session]:
        """HELLO exchange then AUTH; returns the session or None after an
        auth rejection (typed ERROR already sent)."""
        from spark_rapids_tpu.net.session import AuthError
        ftype, _payload = self._recv(conn)  # pre-auth: payload NOT unpickled
        if ftype != P.HELLO:
            raise P.ProtocolError(
                f"expected HELLO, got {P.TYPE_NAMES.get(ftype, ftype)}")
        with self._lock:
            catalog = {name: P.encode_schema(t.schema)
                       for name, t in self._catalog.items()}
        self._send(conn, P.HELLO, P.dump_obj({
            "server": "spark-rapids-tpu", "version": P.VERSION,
            "open_mode": self.sessions.open_mode, "tables": catalog,
            "max_frame_bytes": self.max_frame_bytes}))
        ftype, payload = self._recv(conn)
        if ftype != P.AUTH:
            raise P.ProtocolError(
                f"expected AUTH, got {P.TYPE_NAMES.get(ftype, ftype)}")
        token = payload.decode("utf-8", "replace")  # raw bytes, no pickle
        try:
            session = self.sessions.authenticate(token)
        except AuthError:
            self._try_error(conn, "auth", "authentication failed")
            return None
        self._send(conn, P.OK, P.dump_obj({
            "session_id": session.session_id, "tenant": session.tenant}))
        return session

    def _serve_session(self, conn, wake: _WakeChannel,
                       session: Session) -> None:
        while not self._closing and not session.closed:
            ready, _, _ = select.select([conn], [], [], _POLL_S)
            if session.closed or self._closing:
                return
            if not ready:
                continue
            ftype, payload = self._recv(conn)
            session.touch()
            if ftype == P.SUBMIT:
                self._handle_submit(conn, wake, session, payload)
            elif ftype == P.CANCEL:
                # no query in flight at this point; ack idempotently
                _m.bump("net_cancel_total")
                self._send(conn, P.OK, P.dump_obj({"cancelled": False}))
            else:
                raise P.ProtocolError(
                    f"unexpected {P.TYPE_NAMES.get(ftype, ftype)} frame")

    # -- submit + result streaming ----------------------------------------
    def _handle_submit(self, conn, wake: _WakeChannel, session: Session,
                       payload: bytes) -> None:
        from spark_rapids_tpu.obs import span as _span
        from spark_rapids_tpu.serve import AdmissionRejected

        _m.bump("net_submit_total")
        # net:accept opens before the payload is unpickled and joins the
        # client's trace once the payload has named it
        sp = (_span.Span("net:accept", attrs={"tenant": session.tenant})
              if _span.enabled() else None)
        try:
            doc = P.load_obj(payload)  # post-auth only
            # a client that sent no context still gets one trace
            trace = (_span.TraceContext.from_wire(doc.get("trace"))
                     or _span.new_trace())
            name = doc.get("name")
            if sp is not None:
                sp.adopt(trace)
                if name:
                    sp.attrs["query"] = name
            with _span.activate(sp.context() if sp is not None else None):
                ticket = self._admit(doc, session, name, trace)
        except AdmissionRejected as e:
            _m.bump("net_submit_rejected_total")
            self._try_error(conn, e.reason, str(e))
            return
        except P.NetError as e:
            _m.bump("net_submit_rejected_total")
            self._try_error(conn, e.code, str(e), e.detail)
            return
        finally:
            if sp is not None:
                sp.finish()
        session.queries += 1
        self._await_and_stream(conn, wake, session, ticket)

    def _admit(self, doc, session: Session, name, trace):
        """Resolve the plan against the catalog, gate it, hand it to the
        query server; returns the Ticket or raises the typed rejection."""
        from spark_rapids_tpu.config.conf import RapidsConf
        from spark_rapids_tpu.plan.dataframe import DataFrame
        from spark_rapids_tpu.serve import lowering as _low
        from spark_rapids_tpu.serve import metrics as _sm

        with self._lock:
            catalog = dict(self._catalog)
        plan = P.resolve_tables(doc["plan"], catalog)
        conf = (RapidsConf(doc["conf_items"])
                if doc.get("conf_items") is not None else None)
        df = DataFrame(plan, conf, int(doc.get("shuffle_partitions", 4)))
        if self._gate:
            cells = _low.unsupported_cells(
                df, conf if conf is not None else self.conf)
            if cells:
                _sm.bump("admission_unsupported_plan_total")
                _sm.note_outcome(session.tenant, doc.get("priority", 0),
                                 "rejected:unsupported-plan")
                raise P.NetError(
                    "unsupported-plan",
                    f"plan will not lower: {cells[0][0]}: "
                    f"{cells[0][1]}", detail=cells)
        return self.server.submit(
            df, priority=int(doc.get("priority", 0)),
            deadline_ms=doc.get("deadline_ms"),
            memory_budget=doc.get("memory_budget"),
            name=name, tenant=session.tenant, trace=trace)

    def _await_result(self, conn, wake: _WakeChannel, ticket):
        """Block until the ticket resolves, servicing CANCEL frames and
        cancelling on client disconnect. Returns the result table or
        raises the query's typed failure. The ticket's resolution wakes
        the select through ``wake``; a byte left there by an earlier
        request only costs one more turn of the loop."""
        from spark_rapids_tpu.obs import span as _span
        ticket.add_done_callback(wake.poke)
        try:
            while not ticket.done():
                ready, _, _ = select.select([conn, wake], [], [],
                                            _AWAIT_BACKSTOP_S)
                if self._closing:
                    ticket.cancel("frontend shutdown")
                if wake in ready:
                    _m.bump("net_await_wake_ticket_total")
                    wake.drain()
                    continue
                if not ready:
                    _m.bump("net_await_wake_timeout_total")
                    continue
                _m.bump("net_await_wake_frame_total")
                try:
                    ftype, _payload = self._recv(conn)
                except (P.ConnectionClosed, ConnectionError, OSError):
                    _m.bump("net_disconnect_cancel_total")
                    ticket.cancel("client-disconnect")
                    raise
                if ftype == P.CANCEL:
                    _m.bump("net_cancel_total")
                    ticket.cancel("client-cancel")
                else:
                    raise P.ProtocolError(
                        f"unexpected {P.TYPE_NAMES.get(ftype, ftype)} "
                        f"frame while a query is in flight")
        finally:
            # how long the resolved ticket sat before this loop saw it: the
            # hand-off's share of the request, measured and not inferred
            done_ns = ticket.done_ns
            if done_ns is not None:
                _span.record_span(
                    "net:wake-lag", done_ns,
                    time.perf_counter_ns() - done_ns, ctx=ticket.ctx.trace,
                    attrs={"query": ticket.ctx.name})
        return ticket.result()

    def _await_and_stream(self, conn, wake: _WakeChannel, session: Session,
                          ticket) -> None:
        from spark_rapids_tpu.obs import histo as _h
        from spark_rapids_tpu.obs import span as _span
        from spark_rapids_tpu.serve import (QueryCancelled,
                                            QueryDeadlineExceeded)
        try:
            table = self._await_result(conn, wake, ticket)
        except QueryDeadlineExceeded as e:
            self._try_error(conn, "deadline", str(e))
            return
        except QueryCancelled as e:
            self._try_error(conn, "cancelled", str(e))
            return
        except (P.ConnectionClosed, P.ProtocolError):
            raise
        except (ConnectionError, OSError):
            raise
        except Exception as e:  # noqa: BLE001 — typed to the client
            self._try_error(conn, "failed", f"{type(e).__name__}: {e}")
            return

        with _span.span("net:stream", ctx=ticket.ctx.trace,
                        attrs={"query": ticket.ctx.name,
                               "tenant": session.tenant}):
            stream_t0 = time.perf_counter_ns()
            try:
                self._stream(conn, ticket, table)
            finally:
                _h.record_labeled("net_stream_ns",
                                  time.perf_counter_ns() - stream_t0,
                                  tenant=session.tenant,
                                  priority=ticket.ctx.priority)

    def _stream(self, conn, ticket, table) -> None:
        from spark_rapids_tpu import faults
        batches = table.combine_chunks().to_batches(
            max_chunksize=self.stream_batch_rows)
        try:
            self._send(conn, P.RESULT_START, P.dump_obj({
                "schema": P.encode_schema(table.schema),
                "rows": table.num_rows, "batches": len(batches)}))
            sent = 0
            for batch in batches:
                # a fault here models a wire failure mid-stream: the
                # chaos test proves it cancels cleanly, releases the
                # reservation, and the next query is unpoisoned
                faults.check("net.stream", op=ticket.ctx.name or "query")
                data = faults.corrupt("net.stream", P.encode_batch(batch),
                                      op=ticket.ctx.name or "query")
                self._send(conn, P.RESULT_BATCH, data)
                sent += 1
                _m.bump("net_stream_batches_total")
            self._send(conn, P.RESULT_END, P.dump_obj({
                "rows": table.num_rows, "batches": sent}))
        except (BrokenPipeError, ConnectionError, OSError):
            _m.bump("net_disconnect_cancel_total")
            raise P.ConnectionClosed("client vanished mid-stream")

    # -- shutdown ----------------------------------------------------------
    def close(self) -> None:
        with self._lock:
            self._closing = True
            conns = list(self._conns.values())
        try:
            self._listener.close()
        except OSError:
            pass
        for conn in conns:
            try:
                conn.close()
            except OSError:
                pass
        self._accept_thread.join(timeout=2.0)
        for session in self.sessions.active():
            self.sessions.drop(session)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
