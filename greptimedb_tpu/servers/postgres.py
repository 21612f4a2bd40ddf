"""PostgreSQL wire-protocol (v3) server.

Reference behavior: src/servers/src/postgres/ — pgwire-based startup/auth
handling (auth_handler.rs:250) and simple + extended query support
(handler.rs:648). Implemented directly on the v3 message format: startup /
SSLRequest negotiation, cleartext-password auth against the shared
`UserProvider`, simple query ('Q'), and the extended Parse/Bind/Describe/
Execute/Sync flow with text-format parameters. Every SQL string funnels
into the same frontend `do_query` as the other protocols.
"""

from __future__ import annotations

import logging
import socket
import socketserver
import ssl as ssl_mod
import struct
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..errors import GreptimeError
from ..session import Channel, QueryContext
from .columnar import (POSTGRES_TEXT, RouteRows, SlabWriter, TextColumn,
                       cell_lengths, text_chunks)
from .render import render

logger = logging.getLogger(__name__)

PROTOCOL_V3 = 196608
SSL_REQUEST = 80877103
CANCEL_REQUEST = 80877102

OID_BOOL, OID_INT8, OID_TEXT, OID_FLOAT8, OID_TIMESTAMP = 16, 20, 25, 701, 1114


def _pg_oid(dtype) -> int:
    if dtype.is_timestamp:
        return OID_TIMESTAMP
    if dtype.is_string:
        return OID_TEXT
    if dtype.is_float:
        return OID_FLOAT8
    if dtype.is_boolean:
        return OID_BOOL
    return OID_INT8


#: microseconds between the PG epoch (2000-01-01) and the Unix epoch
_PG_EPOCH_US = 946_684_800_000_000


def _decode_binary_param(raw: bytes, oid: int) -> str:
    """Binary-format Bind parameter → the text form the $N substitution
    consumes (reference pgwire accepts both formats, handler.rs:648).
    Decoding keys off the Parse-declared OID; length disambiguates when
    the driver declared none."""
    n = len(raw)
    if oid in (21, 23, 20):                                    # int2/4/8
        return str(int.from_bytes(raw, "big", signed=True))
    if oid == 700 and n == 4:                                  # float4
        return repr(struct.unpack("!f", raw)[0])
    if oid == 701 and n == 8:                                  # float8
        return repr(struct.unpack("!d", raw)[0])
    if oid == OID_BOOL and n == 1:
        return "true" if raw[0] else "false"
    if oid in (1114, 1184) and n == 8:       # timestamp[tz]: µs since 2000
        us = int.from_bytes(raw, "big", signed=True) + _PG_EPOCH_US
        import datetime as _dt
        # integer µs math: float-seconds rounds the last digit at
        # current-epoch magnitudes (float64 resolution ~0.24µs there)
        sec, us_rem = divmod(us, 1_000_000)
        dt = _dt.datetime.fromtimestamp(sec, _dt.timezone.utc) \
            + _dt.timedelta(microseconds=us_rem)
        return dt.strftime("%Y-%m-%d %H:%M:%S.%f")
    if oid == 1082 and n == 4:               # date: days since 2000-01-01
        days = int.from_bytes(raw, "big", signed=True)
        import datetime as _dt
        return str(_dt.date(2000, 1, 1) + _dt.timedelta(days=days))
    # text/varchar/unknown: binary representation is the utf8 bytes
    return raw.decode("utf-8", errors="replace")


class _MessageIO(SlabWriter):
    """Tagged, length-prefixed v3 messages over a socket. Without a
    socket `send` frames, counts and drops (servers/render.py encodes an
    EXPLAIN ANALYZE'd result that way)."""

    def _read_n(self, n: int) -> Optional[bytes]:
        chunks = []
        while n > 0:
            chunk = self.sock.recv(n)
            if not chunk:
                return None
            chunks.append(chunk)
            n -= len(chunk)
        return b"".join(chunks)

    def read_startup(self) -> Optional[Tuple[int, bytes]]:
        head = self._read_n(4)
        if head is None:
            return None
        length = struct.unpack("!I", head)[0]
        body = self._read_n(length - 4)
        if body is None or len(body) < 4:
            return None
        code = struct.unpack_from("!I", body, 0)[0]
        return code, body[4:]

    def read_message(self) -> Optional[Tuple[int, bytes]]:
        head = self._read_n(5)
        if head is None:
            return None
        tag = head[0]
        length = struct.unpack_from("!I", head, 1)[0]
        body = self._read_n(length - 4)
        return tag, body if body is not None else b""

    def send(self, tag: bytes, body: bytes = b"") -> None:
        self.write(tag + struct.pack("!I", len(body) + 4) + body)

    def send_data_rows(self, columns: List[TextColumn], nrows: int) -> None:
        """One DataRow a row, framed together: int16 columns, then an
        int32 length (-1 for NULL) and the text of every cell."""
        lengths = np.full(nrows, 6 + 4 * len(columns), dtype=np.int64)
        parts: List[List[bytes]] = []
        for cells, nulls in columns:
            lens = cell_lengths(cells)
            lengths += lens
            if nulls is not None:
                lens[nulls] = -1
            parts.append(lens.astype(">i4").view("V4").tolist())
            parts.append(cells)
        heads = np.empty(nrows, dtype=[("tag", "S1"), ("length", ">u4"),
                                       ("columns", ">u2")])
        heads["tag"], heads["length"], heads["columns"] = \
            b"D", lengths, len(columns)
        self.write_rows(heads.view("V7").tolist(), *parts)

    def send_raw(self, data: bytes) -> None:
        self.sock.sendall(data)


_PG_ROW_RETURNING = {"select", "show", "describe", "desc", "tql", "explain",
                     "with", "values", "table"}


def _sqlstate(e: GreptimeError) -> str:
    """SQLSTATE for a taxonomy error: admission rejections map to
    53300 (too_many_connections — the class clients retry with
    backoff); everything else stays the generic internal_error."""
    from ..errors import OverloadedError
    return "53300" if isinstance(e, OverloadedError) else "XX000"


def _returns_rows(sql: str) -> bool:
    word = sql.lstrip().split(None, 1)
    return bool(word) and word[0].lower() in _PG_ROW_RETURNING


class _PgPortal:
    __slots__ = ("sql", "result", "described")

    def __init__(self, sql: str):
        self.sql = sql
        self.result = None     # Output cached by Describe, reused by Execute
        self.described = False  # Describe sent RowDescription already


class _PgConnection:
    def __init__(self, server: "PostgresServer", sock: socket.socket,
                 conn_id: int):
        self.server = server
        self.sock = sock
        self.io = _MessageIO(sock)
        self.conn_id = conn_id
        self.ctx = QueryContext(channel=Channel.POSTGRES)
        self.stmts: Dict[str, str] = {}       # name -> sql with $N params
        self.stmt_param_oids: Dict[str, List[int]] = {}
        self.portals: Dict[str, _PgPortal] = {}
        # v3 protocol: after an error in the extended protocol, discard
        # messages until Sync (a pipelined Execute after a failed Bind must
        # not run a stale portal)
        self._in_error = False

    # ---- message helpers ----
    def send_error(self, message: str, code: str = "XX000",
                   severity: str = "ERROR") -> None:
        fields = (b"S" + severity.encode() + b"\x00"
                  + b"C" + code.encode() + b"\x00"
                  + b"M" + message.encode() + b"\x00" + b"\x00")
        self.io.send(b"E", fields)

    def send_ready(self) -> None:
        self.io.send(b"Z", b"I")

    def ext_error(self, message: str, code: str = "XX000") -> None:
        """ErrorResponse inside the extended protocol: enter the
        skip-until-Sync state the v3 protocol requires."""
        self.send_error(message, code)
        self._in_error = True

    def send_row_description(self, schema,
                             io: Optional[_MessageIO] = None) -> None:
        body = struct.pack("!H", len(schema.column_schemas))
        for col in schema.column_schemas:
            body += (col.name.encode() + b"\x00"
                     + struct.pack("!IHIhih", 0, 0, _pg_oid(col.dtype),
                                   -1, -1, 0))
        (io or self.io).send(b"T", body)

    def send_rows(self, batches, io: Optional[_MessageIO] = None
                  ) -> RouteRows:
        """The DataRows of a result -> the rows each route rendered."""
        io = io or self.io
        routes = RouteRows()
        for nrows, columns, route in text_chunks(batches, POSTGRES_TEXT):
            io.send_data_rows(columns, nrows)
            routes[route] += nrows
        return routes

    def send_result(self, sql: str, out, described: bool = False) -> None:
        """One result on the wire, under the `render` span:
        RowDescription (unless a Describe sent it already), the DataRows,
        CommandComplete."""
        def encode(outs, discard: bool):
            io = _MessageIO(None) if discard else self.io
            sent = io.bytes_out
            result = outs[-1]
            routes = RouteRows()
            with io.slab():
                if result.is_batches:
                    if result.batches:
                        if not described:
                            self.send_row_description(
                                result.batches[0].schema, io)
                        routes = self.send_rows(result.batches, io)
                    elif not described:
                        io.send(b"T", struct.pack("!H", 0))
                self.send_complete(sql, result, io)
            return None, io.bytes_out - sent, routes

        render("postgres", [out], encode)

    def send_complete(self, sql: str, output,
                      io: Optional[_MessageIO] = None) -> None:
        word = sql.lstrip().split(None, 1)
        word = word[0].upper() if word else ""
        if output.is_batches:
            tag = f"SELECT {output.num_rows}"
        elif word == "INSERT":
            tag = f"INSERT 0 {output.affected_rows or 0}"
        elif word == "DELETE":
            tag = f"DELETE {output.affected_rows or 0}"
        else:
            tag = word or "OK"
        (io or self.io).send(b"C", tag.encode() + b"\x00")

    # ---- startup/auth ----
    def startup(self) -> bool:
        while True:
            msg = self.io.read_startup()
            if msg is None:
                return False
            code, body = msg
            if code == SSL_REQUEST:
                if self.server.ssl_context is not None:
                    self.io.send_raw(b"S")
                    self.sock = self.server.ssl_context.wrap_socket(
                        self.sock, server_side=True)
                    self.io.sock = self.sock
                else:
                    self.io.send_raw(b"N")
                continue
            if code == CANCEL_REQUEST:
                return False
            if code != PROTOCOL_V3:
                self.send_error(f"unsupported protocol {code}", "0A000",
                                "FATAL")
                return False
            break
        params: Dict[str, str] = {}
        parts = body.split(b"\x00")
        for k, v in zip(parts[::2], parts[1::2]):
            if k:
                params[k.decode()] = v.decode()
        user = params.get("user", "greptime")
        if params.get("database"):
            self.ctx.set_current_schema(params["database"])

        provider = self.server.user_provider
        if provider is not None and provider.requires_password:
            if self.server.auth_method == "md5":
                # md5(md5(password + user) + salt), "md5"-prefixed hex
                # (reference: pgwire md5 flow, auth_handler.rs)
                import hashlib
                import os as _os
                salt = _os.urandom(4)
                self.io.send(b"R", struct.pack("!I", 5) + salt)
                msg = self.io.read_message()
                if msg is None or msg[0] != ord("p"):
                    return False
                got = msg[1].rstrip(b"\x00").decode()
                expected_pwd = provider.plain_password(user)
                ok = False
                if expected_pwd is not None:
                    inner = hashlib.md5(
                        (expected_pwd + user).encode()).hexdigest()
                    want = "md5" + hashlib.md5(
                        inner.encode() + salt).hexdigest()
                    ok = got == want
                if not ok:
                    self.send_error(f'password authentication failed for '
                                    f'user "{user}"', "28P01", "FATAL")
                    return False
            else:
                self.io.send(b"R", struct.pack("!I", 3))  # cleartext
                msg = self.io.read_message()
                if msg is None or msg[0] != ord("p"):
                    return False
                password = msg[1].rstrip(b"\x00").decode()
                if not provider.authenticate(user, password):
                    self.send_error(f'password authentication failed for '
                                    f'user "{user}"', "28P01", "FATAL")
                    return False
        self.ctx.username = user
        self.io.send(b"R", struct.pack("!I", 0))       # AuthenticationOk
        for k, v in (("server_version", "16.0"),
                     ("server_encoding", "UTF8"),
                     ("client_encoding", "UTF8"),
                     ("DateStyle", "ISO, MDY"),
                     ("TimeZone", "UTC"),
                     ("integer_datetimes", "on")):
            self.io.send(b"S", k.encode() + b"\x00" + v.encode() + b"\x00")
        self.io.send(b"K", struct.pack("!II", self.conn_id, 0))
        self.send_ready()
        return True

    # ---- query execution ----
    def _execute_sql(self, sql: str, *, describe_only: bool = False):
        outputs = self.server.instance.do_query(sql, self.ctx)
        return outputs[-1]

    def handle_simple_query(self, sql: str) -> None:
        sql = sql.rstrip("\x00")
        if not sql.strip():
            self.io.send(b"I")
            self.send_ready()
            return
        try:
            self.send_result(sql, self._execute_sql(sql))
        except GreptimeError as e:
            self.send_error(str(e), _sqlstate(e))
        except Exception as e:  # noqa: BLE001
            logger.exception("postgres query failed: %s", sql)
            self.send_error(str(e))
        self.send_ready()

    # ---- extended protocol ----
    def handle_parse(self, body: bytes) -> None:
        end = body.index(b"\x00")
        name = body[:end].decode()
        end2 = body.index(b"\x00", end + 1)
        sql = body[end + 1:end2].decode()
        # optional parameter-type OIDs: binary Bind values decode by them
        # (reference pgwire accepts both formats, handler.rs:648)
        pos = end2 + 1
        oids: List[int] = []
        if pos + 2 <= len(body):
            (noids,) = struct.unpack_from("!H", body, pos)
            pos += 2
            for _ in range(noids):
                if pos + 4 > len(body):
                    break
                oids.append(struct.unpack_from("!I", body, pos)[0])
                pos += 4
        self.stmts[name] = sql
        self.stmt_param_oids[name] = oids
        self.io.send(b"1")                              # ParseComplete

    def handle_bind(self, body: bytes) -> None:
        pos = body.index(b"\x00")
        portal = body[:pos].decode()
        end = body.index(b"\x00", pos + 1)
        stmt_name = body[pos + 1:end].decode()
        pos = end + 1
        nfmt = struct.unpack_from("!H", body, pos)[0]
        pos += 2
        fmts = list(struct.unpack_from(f"!{nfmt}H", body, pos)) \
            if nfmt else []
        pos += 2 * nfmt
        nparams = struct.unpack_from("!H", body, pos)[0]
        pos += 2
        sql = self.stmts.get(stmt_name)
        if sql is None:
            self.ext_error(
                f"prepared statement {stmt_name!r} does not exist", "26000")
            return
        oids = self.stmt_param_oids.get(stmt_name, [])
        params: List[Optional[str]] = []
        for i in range(nparams):
            plen = struct.unpack_from("!i", body, pos)[0]
            pos += 4
            if plen == -1:
                params.append(None)
                continue
            raw = body[pos:pos + plen]
            pos += plen
            # per-protocol: 0 codes = all text, 1 code = applies to all
            fmt = fmts[i] if i < len(fmts) else (fmts[0] if fmts else 0)
            if fmt == 1:
                oid = oids[i] if i < len(oids) else 0
                params.append(_decode_binary_param(raw, oid))
            else:
                params.append(raw.decode())
        self.portals[portal] = _PgPortal(_substitute_pg_params(sql, params))
        self.io.send(b"2")                              # BindComplete

    def handle_describe(self, body: bytes) -> None:
        """Describe must return the RowDescription for row-returning
        statements/portals (v3 protocol; the reference's pgwire plans at
        Describe, src/servers/src/postgres/handler.rs:648). JDBC and
        psycopg3 extended mode plan on this. Portals execute here and cache
        the result for Execute; parametrized statement Describe probes the
        schema with NULL-substituted params."""
        import re
        kind = chr(body[0])
        name = body[1:].rstrip(b"\x00").decode()
        if kind == "S":
            sql = self.stmts.get(name)
            if sql is None:
                self.ext_error(
                    f"prepared statement {name!r} does not exist", "26000")
                return
            nparams = len(set(re.findall(r"\$(\d+)", sql)))
            # all parameters described as text; values coerce at parse time
            self.io.send(b"t", struct.pack("!H", nparams)
                         + struct.pack("!I", OID_TEXT) * nparams)
            if _returns_rows(sql):
                probe = _substitute_pg_params(sql, [None] * nparams) \
                    if nparams else sql
                # prefer a LIMIT 0 probe: schema without scanning any rows
                # (Execute re-runs the statement through its portal anyway)
                word = probe.lstrip().split(None, 1)[0].lower()
                candidates = []
                if word in ("select", "with", "values", "table"):
                    # LIMIT 0 probe first (schema without scanning rows);
                    # the full probe is the fallback for statements the
                    # suffix breaks (e.g. an existing LIMIT clause)
                    candidates.append(probe.rstrip().rstrip(";") + " LIMIT 0")
                    candidates.append(probe)
                if word in ("show", "describe", "desc"):
                    candidates.append(probe)  # metadata queries are cheap
                # expensive non-LIMITable statements (TQL, EXPLAIN) fall
                # through to NoData rather than executing twice
                for cand in candidates:
                    try:
                        out = self._execute_sql(cand)
                    except Exception:  # noqa: BLE001 — try next / NoData
                        logger.debug("describe probe failed: %s", cand,
                                     exc_info=True)
                        continue
                    if out.is_batches and out.batches:
                        self.send_row_description(out.batches[0].schema)
                        return
            self.io.send(b"n")                          # NoData
            return
        portal = self.portals.get(name)
        if portal is None:
            self.ext_error(f"portal {name!r} does not exist", "34000")
            return
        if _returns_rows(portal.sql):
            try:
                portal.result = self._execute_sql(portal.sql)
            except GreptimeError as e:
                self.ext_error(str(e), _sqlstate(e))
                return
            except Exception as e:  # noqa: BLE001
                logger.exception("postgres describe failed: %s", portal.sql)
                self.ext_error(str(e))
                return
            if portal.result.is_batches and portal.result.batches:
                self.send_row_description(portal.result.batches[0].schema)
                portal.described = True
                return
        self.io.send(b"n")                              # NoData

    def handle_execute(self, body: bytes) -> None:
        name = body[:body.index(b"\x00")].decode()
        portal = self.portals.get(name)
        if portal is None:
            self.ext_error(f"portal {name!r} does not exist", "34000")
            return
        sql = portal.sql
        try:
            # reuse the result a preceding Describe already computed
            out, portal.result = portal.result, None
            described, portal.described = portal.described, False
            if out is None:
                out = self._execute_sql(sql)
            # `described`: the Describe already sent the 'T'
            self.send_result(sql, out, described)
        except GreptimeError as e:
            self.ext_error(str(e), _sqlstate(e))
        except Exception as e:  # noqa: BLE001
            logger.exception("postgres execute failed: %s", sql)
            self.ext_error(str(e))

    def handle_close(self, body: bytes) -> None:
        kind = chr(body[0])
        name = body[1:].rstrip(b"\x00").decode()
        if kind == "S":
            self.stmts.pop(name, None)
        else:
            self.portals.pop(name, None)
        self.io.send(b"3")                              # CloseComplete

    # ---- main loop ----
    def run(self) -> None:
        try:
            if not self.startup():
                return
            while True:
                msg = self.io.read_message()
                if msg is None:
                    return
                tag, body = msg
                ch = chr(tag)
                if ch == "X":                           # Terminate
                    return
                if ch == "S":                           # Sync
                    self._in_error = False              # error state ends
                    # Describe-cached results live only within one pipeline
                    # batch: replaying them in a later cycle would miss
                    # intervening writes, and an un-Executed portal would
                    # pin its whole result set for the connection lifetime
                    for p in self.portals.values():
                        p.result = None
                    self.send_ready()
                elif ch == "Q":
                    self._in_error = False
                    self.handle_simple_query(body.decode())
                elif self._in_error and ch in "PBDECH":
                    pass  # v3: discard until Sync after an error
                elif ch == "P":
                    self.handle_parse(body)
                elif ch == "B":
                    self.handle_bind(body)
                elif ch == "D":
                    self.handle_describe(body)
                elif ch == "E":
                    self.handle_execute(body)
                elif ch == "C":
                    self.handle_close(body)
                elif ch == "H":                         # Flush
                    pass
                else:
                    self.send_error(f"unsupported message {ch!r}", "0A000")
                    self.send_ready()
        except (ConnectionError, OSError):
            pass
        except Exception:  # noqa: BLE001
            logger.exception("postgres connection %d crashed", self.conn_id)
        finally:
            try:
                self.sock.close()
            except OSError:
                pass


def _substitute_pg_params(sql: str, params: List[Optional[str]]) -> str:
    """Text-format $N substitution (reference pgwire handles typed params;
    values arrive as text and our parser coerces by column type)."""
    out = []
    i = 0
    in_str = False
    while i < len(sql):
        ch = sql[i]
        if ch == "'":
            in_str = not in_str
            out.append(ch)
            i += 1
        elif ch == "$" and not in_str and i + 1 < len(sql) \
                and sql[i + 1].isdigit():
            j = i + 1
            while j < len(sql) and sql[j].isdigit():
                j += 1
            idx = int(sql[i + 1:j]) - 1
            if 0 <= idx < len(params):
                v = params[idx]
                if v is None:
                    out.append("NULL")
                elif _is_number(v):
                    out.append(v)
                else:
                    out.append("'" + v.replace("'", "''") + "'")
                i = j
            else:
                out.append(ch)
                i += 1
        else:
            out.append(ch)
            i += 1
    return "".join(out)


def _is_number(s: str) -> bool:
    try:
        float(s)
        return True
    except ValueError:
        return False


class PostgresServer:
    """Threaded PostgreSQL protocol listener over a frontend instance."""

    def __init__(self, instance, host: str = "127.0.0.1", port: int = 0,
                 user_provider=None,
                 ssl_context: Optional[ssl_mod.SSLContext] = None,
                 auth_method: str = "md5"):
        self.instance = instance
        self.user_provider = user_provider
        self.ssl_context = ssl_context
        self.auth_method = auth_method
        self._next_conn_id = 1
        self._lock = threading.Lock()
        server_self = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                with server_self._lock:
                    conn_id = server_self._next_conn_id
                    server_self._next_conn_id += 1
                _PgConnection(server_self, self.request, conn_id).run()

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._tcp = Server((host, port), Handler)
        self.port = self._tcp.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def serve_in_background(self) -> threading.Thread:
        from ..common.runtime import new_thread
        self._thread = new_thread(self._tcp.serve_forever, daemon=True,
                                  name="postgres-server",
                                  propagate_context=False)
        self._thread.start()
        return self._thread

    # CLI lifecycle alias (cmd/main.py starts all servers uniformly)
    start = serve_in_background

    @property
    def host(self) -> str:
        return self._tcp.server_address[0]

    def shutdown(self) -> None:
        self._tcp.shutdown()
        self._tcp.server_close()
