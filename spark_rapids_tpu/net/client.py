"""NetClient: blocking client for the QueryFrontend wire protocol.

Used by tests, ``chip_smoke.py`` and the benchmark's harness. One client =
one connection = one authenticated session; thread-safe for sequential use
per instance (hold one client per worker thread, the same discipline as
a DB-API connection).

``table(name)`` materializes a client-side DataFrame handle over the
server's registered table: a normal DataFrame over that table's EMPTY
schema-bearing table, remembered so ``submit`` swaps the placeholder
leaf for a ``TableRef`` before pickling — the plan ships without data
and the server resolves it against its one catalog table, keeping the
plan memo and single-flight dedup keyed identically across clients.

``submit`` re-raises server failures as the SAME typed exceptions the
in-process API uses (AdmissionRejected, QueryCancelled,
QueryDeadlineExceeded), so callers port between in-process and remote
submission without changing their error handling.
"""

from __future__ import annotations

import socket
import threading
from typing import Dict, Optional, Tuple

from spark_rapids_tpu.net import protocol as P


class NetClient:
    def __init__(self, host: str, port: int, token: str = "",
                 conf=None, shuffle_partitions: int = 4,
                 timeout_s: Optional[float] = 30.0,
                 max_frame_bytes: int = 64 << 20):
        self.conf = conf
        self.shuffle_partitions = int(shuffle_partitions)
        self.max_frame_bytes = int(max_frame_bytes)
        self._lock = threading.Lock()
        self._refs: Dict[int, Tuple[str, int, int]] = {}
        self._pins = []  # placeholder tables whose id() keys _refs
        self._sock = socket.create_connection((host, port),
                                              timeout=timeout_s)
        try:
            self._send(P.HELLO)
            ftype, payload = self._recv()
            if ftype == P.ERROR:
                P.raise_typed(P.load_obj(payload))
            if ftype != P.HELLO:
                raise P.ProtocolError(
                    f"expected HELLO, got {P.TYPE_NAMES.get(ftype, ftype)}")
            hello = P.load_obj(payload)
            self.server_tables: Dict[str, object] = {
                name: P.decode_schema(raw)
                for name, raw in hello.get("tables", {}).items()}
            self.open_mode = bool(hello.get("open_mode"))
            self._send(P.AUTH, token.encode("utf-8"))
            ftype, payload = self._recv()
            if ftype == P.ERROR:
                P.raise_typed(P.load_obj(payload))
            if ftype != P.OK:
                raise P.ProtocolError(
                    f"expected OK, got {P.TYPE_NAMES.get(ftype, ftype)}")
            ack = P.load_obj(payload)
            self.session_id = ack["session_id"]
            self.tenant = ack["tenant"]
        except BaseException:
            self._sock.close()
            raise

    # -- wire helpers ------------------------------------------------------
    def _send(self, ftype: int, payload: bytes = b"") -> None:
        P.send_frame(self._sock, ftype, payload)

    def _recv(self) -> Tuple[int, bytes]:
        return P.recv_frame(self._sock, self.max_frame_bytes)

    # -- tables ------------------------------------------------------------
    def table(self, name: str, batch_rows: int = 1 << 20,
              partitions: int = 1):
        """DataFrame handle over the server-registered table ``name``.
        Build any plan on it with the normal DataFrame API; ``submit``
        ships the plan with a TableRef leaf instead of the data."""
        from spark_rapids_tpu.plan import from_arrow
        schema = self.server_tables.get(name)
        if schema is None:
            raise KeyError(f"server has no table {name!r} "
                           f"(registered: {sorted(self.server_tables)})")
        empty = schema.empty_table()
        df = from_arrow(empty, conf=self.conf, batch_rows=batch_rows,
                        partitions=partitions)
        with self._lock:
            self._refs[id(empty)] = (name, batch_rows, partitions)
            # pin the placeholder: its id() must stay valid client-lifetime
            self._pins.append(empty)
        return df

    # -- query -------------------------------------------------------------
    def submit(self, df, priority: int = 0,
               deadline_ms: Optional[float] = None,
               memory_budget: Optional[int] = None,
               name: Optional[str] = None,
               timeout_s: Optional[float] = None):
        """Run ``df`` remotely; returns a pa.Table byte-identical to the
        in-process ``df.to_arrow()``. Raises the same typed exceptions as
        ``QueryServer.submit``/``Ticket.result``."""
        from spark_rapids_tpu.obs import span as _span

        # the request's root span: its id is what the wire hands the
        # server, so every server span parents on a span that is recorded
        with _span.span("net:request",
                        attrs={"query": name} if name else None) as root:
            with _span.span("net:client-send"):
                with self._lock:
                    refs = dict(self._refs)
                plan = P.strip_tables(df.plan, refs)
                conf = df.conf if df.conf is not None else self.conf
                conf_items = (dict(conf._values) if conf is not None
                              else None)
                payload = P.dump_obj({
                    "plan": plan,
                    "conf_items": conf_items,
                    "shuffle_partitions": df.shuffle_partitions,
                    "priority": priority,
                    "deadline_ms": deadline_ms,
                    "memory_budget": memory_budget,
                    "name": name,
                    "trace": (root.context().to_wire()
                              if root is not None else None),
                })
                if timeout_s is not None:
                    self._sock.settimeout(timeout_s)
                self._send(P.SUBMIT, payload)
            return self._recv_result()

    def _recv_result(self):
        """The result stream as one pa.Table. ``net:client-recv`` opens
        when the first frame has arrived (the wait before it is the
        server's) and closes on the decoded table."""
        import pyarrow as pa
        from spark_rapids_tpu.obs import span as _span

        schema = None
        batches = []
        expected = None
        recv = None
        try:
            while True:
                ftype, data = self._recv()
                if recv is None and _span.enabled():
                    recv = _span.Span("net:client-recv")
                if ftype == P.ERROR:
                    P.raise_typed(P.load_obj(data))
                elif ftype == P.RESULT_START:
                    start = P.load_obj(data)
                    schema = P.decode_schema(start["schema"])
                    expected = start.get("batches")
                elif ftype == P.RESULT_BATCH:
                    if schema is None:
                        raise P.ProtocolError(
                            "RESULT_BATCH before RESULT_START")
                    batches.append(P.decode_batch(data, schema))
                elif ftype == P.RESULT_END:
                    end = P.load_obj(data)
                    if expected is not None and end.get("batches") not in (
                            None, len(batches)):
                        raise P.ProtocolError(
                            f"stream truncated: {len(batches)} of "
                            f"{end.get('batches')} batches")
                    table = pa.Table.from_batches(batches, schema=schema)
                    if recv is not None:
                        recv.attrs["rows"] = table.num_rows
                    return table
                else:
                    raise P.ProtocolError(
                        f"unexpected {P.TYPE_NAMES.get(ftype, ftype)} frame "
                        f"in result stream")
        finally:
            if recv is not None:
                recv.finish()

    def cancel(self) -> None:
        """Best-effort cancel of the in-flight query (sent async; the
        server acks by failing the stream with a typed 'cancelled')."""
        self._send(P.CANCEL)

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
