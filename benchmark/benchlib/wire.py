"""Wire clients: sockets only, so the benchmark's parent never imports jax
and depends on the served protocols, not on the program's Python client.

`Http` and `MiniMysql` are copied from `chip_smoke.py` (proven on the chip,
PR 21); the Flight put and the line-protocol post are the two write paths
the cells use.
"""

from __future__ import annotations

import http.client
import json
import socket
import struct
import urllib.error
import urllib.parse
import urllib.request


class WireError(RuntimeError):
    """The server answered with an error, or not at all."""


class Http:
    def __init__(self, port: int):
        self.port = port
        self.base = f"http://127.0.0.1:{port}"

    def _open(self, path, params=None, timeout=300):
        data = urllib.parse.urlencode(params).encode() if params else None
        try:
            with urllib.request.urlopen(self.base + path, data=data,
                                        timeout=timeout) as r:
                return r.read()
        except urllib.error.HTTPError as e:
            raise WireError(
                f"{path}: HTTP {e.code}: {e.read()[:2000]!r}") from None

    def status(self) -> dict:
        return json.loads(self._open("/status", timeout=30))

    def metrics(self) -> dict:
        """Prometheus text of /metrics -> {sample name with labels: value}."""
        out = {}
        for line in self._open("/metrics", timeout=30).decode().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                try:
                    out[name] = float(value)
                except ValueError:
                    pass
        return out

    def sql_raw(self, sql: str) -> bytes:
        """The response body as sent; `decode_sql` reads it later, so a
        timed loop pays no JSON parse."""
        return self._open("/v1/sql", {"sql": sql})

    @staticmethod
    def decode_sql(raw: bytes, sql: str = ""):
        """-> (column names, rows) or affected-row count; the BODY's code
        decides, not the HTTP status."""
        body = json.loads(raw)
        if body.get("code") != 0:
            raise WireError(f"/v1/sql code={body.get('code')}: "
                            f"{str(body)[:2000]} for {sql[:200]}")
        out = body["output"][-1]
        if "affectedrows" in out:
            return out["affectedrows"]
        rec = out["records"]
        return ([c["name"] for c in rec["schema"]["column_schemas"]],
                rec["rows"])

    def sql(self, sql: str):
        return self.decode_sql(self.sql_raw(sql), sql)


class MiniMysql:
    """Just enough of the MySQL client protocol: protocol-41 handshake
    with an empty mysql_native_password, COM_QUERY, text result sets."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=300)
        # buffered: a 48,000-row result is 48,000 packets, and two
        # recv() calls a packet would be the client's own cost
        self.rfile = self.sock.makefile("rb", buffering=1 << 20)
        self.seq = 0
        greeting = self._read()
        if greeting[0] != 10:
            raise WireError("mysql: expected a protocol-10 greeting")
        caps = 0x0200 | 0x8000 | 0x80000   # PROTOCOL_41|SECURE|PLUGIN_AUTH
        self._write(struct.pack("<IIB", caps, 1 << 24, 45) + b"\x00" * 23
                    + b"greptime\x00" + b"\x00"
                    + b"mysql_native_password\x00")
        resp = self._read()
        if resp[0] != 0x00:
            raise WireError(f"mysql: login refused: {resp[9:]!r}")

    def close(self):
        self.rfile.close()
        self.sock.close()

    def _recv(self, n: int) -> bytes:
        buf = self.rfile.read(n)
        if len(buf) < n:
            raise WireError("mysql: connection closed")
        return buf

    def _read(self) -> bytes:
        payload = b""
        while True:
            head = self._recv(4)
            n = head[0] | head[1] << 8 | head[2] << 16
            self.seq = (head[3] + 1) & 0xFF
            payload += self._recv(n)
            if n < 0xFFFFFF:
                return payload

    def _write(self, payload: bytes) -> None:
        if len(payload) >= 0xFFFFFF:
            raise WireError("mysql: statement too long for one packet")
        self.sock.sendall(struct.pack("<I", len(payload))[:3]
                          + bytes([self.seq]) + payload)
        self.seq = (self.seq + 1) & 0xFF

    @staticmethod
    def _lenenc(p: bytes, pos: int):
        b = p[pos]
        if b < 0xFB:
            return b, pos + 1
        width = {0xFC: 2, 0xFD: 3, 0xFE: 8}[b]
        return (int.from_bytes(p[pos + 1:pos + 1 + width], "little"),
                pos + 1 + width)

    def query_raw(self, sql: str):
        """-> affected-row count, or (column names, row packets as sent);
        `decode_rows` reads them later, outside a timed loop."""
        self.seq = 0
        self._write(b"\x03" + sql.encode())
        head = self._read()
        if head[0] == 0xFF:
            raise WireError(f"mysql: {head[9:]!r} for {sql[:200]}")
        if head[0] == 0x00:
            return self._lenenc(head, 1)[0]
        ncols = self._lenenc(head, 0)[0]
        names = []
        for _ in range(ncols):
            col, pos = self._read(), 0
            for _ in range(5):          # catalog, schema, table, org, name
                n, pos = self._lenenc(col, pos)
                name, pos = col[pos:pos + n], pos + n
            names.append(name.decode())
        if self._read()[0] != 0xFE:
            raise WireError("mysql: expected EOF after the columns")
        packets = []
        while True:
            p = self._read()
            if p[0] == 0xFE and len(p) < 9:
                return names, packets
            packets.append(p)

    @classmethod
    def decode_rows(cls, raw):
        if isinstance(raw, int):
            return raw
        names, packets = raw
        rows = []
        for p in packets:
            row, pos = [], 0
            for _ in names:
                if p[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    n, pos = cls._lenenc(p, pos)
                    row.append(p[pos:pos + n].decode())
                    pos += n
            rows.append(row)
        return names, rows

    def query(self, sql: str):
        """-> (column names, rows of str/None) or affected-row count."""
        return self.decode_rows(self.query_raw(sql))


def flight_bulk_load(port: int, table: str, arrow_table, tag_columns,
                     timestamp_column: str) -> int:
    """One Arrow Flight do_put of the WAL-less bulk path (the command is
    `servers/flight.py`'s `bulk_load`); -> acknowledged rows."""
    from pyarrow import flight
    conn = flight.connect(f"grpc://127.0.0.1:{port}")
    try:
        descriptor = flight.FlightDescriptor.for_command(json.dumps({
            "type": "bulk_load", "table": table,
            "tag_columns": list(tag_columns),
            "timestamp_column": timestamp_column}).encode())
        writer, reader = conn.do_put(descriptor, arrow_table.schema)
        with writer:
            writer.write_table(arrow_table)
            writer.done_writing()
            buf = reader.read()
    finally:
        conn.close()
    meta = json.loads(buf.to_pybytes()) if buf is not None else {}
    return int(meta.get("affected_rows", 0))


class InfluxWriter:
    """One keep-alive connection posting line-protocol bodies; 204 is the
    acknowledgement (after WAL append and fsync, `handle_row_insert`)."""

    def __init__(self, port: int, precision: str = "ms"):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=300)
        self.path = f"/v1/influxdb/write?precision={precision}"

    def post(self, body: bytes) -> None:
        self.conn.request("POST", self.path, body=body)
        resp = self.conn.getresponse()
        payload = resp.read()
        if resp.status != 204:
            raise WireError(f"influx write: HTTP {resp.status}: "
                            f"{payload[:500]!r}")

    def close(self):
        self.conn.close()
