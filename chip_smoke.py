#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

Starts ONE `standalone start` server (the only process that touches
JAX), loads TSBS devops `cpu-only` at 4000 hosts x 12 h = 17,280,000
rows over Arrow Flight, writes the next tick through the WAL path, sends
the TSBS aggregate families over HTTP, the MySQL wire and the Prometheus
API, and checks every answer against a float64 numpy evaluation of the
same statements over the same generated rows. It fails unless every
statement EXECUTED `device-resident` on its first run and on its repeat
(the `dispatch` row of EXPLAIN ANALYZE, not the plan text), the table's
f32/int32 mirrors are in HBM, every acknowledged WAL-path row survives
SIGKILL + restart, and the restarted server compiles nothing anew.

The last stdout line is the result, with exactly these keys:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}
The line before it, `summary: {...}`, carries what the run showed (rows,
statements, dispatches, errors, HBM, WAL rows, compile cache); the full
report goes to chiprun_out/chip_smoke_report.json. Any failed phase
raises: there is no result line and the exit code is not 0. Without a
TPU it stops before loading anything.

Source of the deployment: timescale/tsbs, cmd/tsbs_generate_data
--use-case=cpu-only --scale=4000 --log-interval=10s, and the query
families of cmd/tsbs_generate_queries for it. Written from memory (no
network), so ASSUMED lists what was recalled rather than copied, and
REDUCED what was cut and why.
"""

from __future__ import annotations

import argparse
import calendar
import json
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pyarrow as pa

# the parent talks to the server over sockets only; the Flight client is
# the package's own and imports (and runs) without jax
from greptimedb_tpu.client.flight import Database
from greptimedb_tpu.common.jax_cache import DEFAULT_CACHE_DIR

HERE = os.path.dirname(os.path.abspath(__file__))

TAGS = ["hostname", "region", "datacenter", "rack", "os", "arch", "team",
        "service", "service_version", "service_environment"]
FIELDS = ["usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice"]
REGIONS = ["us-east-1", "us-west-1", "us-west-2", "eu-west-1",
           "eu-central-1", "ap-southeast-1", "ap-southeast-2",
           "ap-northeast-1", "sa-east-1"]
T0_MS = 1_451_606_400_000          # 2016-01-01T00:00:00Z, TSBS's default
TICK_MS = 10_000
TICKS_PER_HOUR = 3_600_000 // TICK_MS
WAL_HOSTS = 300                    # rows of the next tick via SQL INSERT
LOAD_CHUNK_TICKS = 135             # x 4000 hosts = 540,000 rows per put

ASSUMED = [
    "tag value sets (9 regions, datacenter = region + a/b/c, rack 0-99, "
    "3 os, 2 arch, 4 teams, service 0-19, version 0-1, 3 environments) "
    "and their uniform draw per host",
    "fields are clamped random walks in [0, 100]: uniform start, "
    "N(0, 1) step per 10 s tick. TSBS emits the walk truncated to an "
    "integer (as recalled); the fraction is kept here, since integers "
    "<= 100 are exact in f32 and in bf16 and would not test precision",
    "start 2016-01-01T00:00:00Z; query windows drawn uniformly from the "
    "loaded span, aligned to the minute",
    "PromQL range selectors are left-open, (t - 5m, t] (Prometheus 3)",
]
REDUCED = [
    "12 h of TSBS's 3 days (17.28M of 103.68M rows): the largest whole "
    "half-day the default configuration keeps device-resident in one "
    "region (17.28M x 102 B estimated = 1.76 GB < 2 GiB admission)",
    "lastpoint as `last(usage_user) GROUP BY hostname` (the row-returning "
    "TSBS form leaves the device plan today)",
]

# float64 reference vs f32 device mirrors. One f32 rounding of a value in
# [0, 100] is <= 100 * 2^-24 = 6e-6; bf16 would be off by up to 0.25.
TOL = {
    "max": dict(rtol=0.0, atol=1e-5),
    "last": dict(rtol=0.0, atol=1e-5),
    # f32 accumulation of <= 4320 values: measured ~1e-7 relative on the
    # CPU backend; bf16 (4e-3) and a plain f32 running prefix over 17M
    # rows (9e-3, the defect this PR repaired) both fail this
    "avg": dict(rtol=1e-5, atol=0.0),
    # per-series f32 rate, summed over ~444 hosts per region
    "rate": dict(rtol=1e-4, atol=0.0),
}


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T_START:7.1f}s] {msg}",
          flush=True)


_T_START = time.monotonic()


# ---------------------------------------------------------------------------
# data: TSBS devops cpu-only, from --seed
# ---------------------------------------------------------------------------

def generate(seed: int, hosts: int, ticks: int):
    """-> (tag_values {tag: [str per host]}, data float64 [ticks + 1,
    hosts, 10]); tick `ticks` is the next tick, written via the WAL
    path for the first WAL_HOSTS hosts only."""
    rng = np.random.default_rng(seed)
    reg = rng.integers(0, len(REGIONS), hosts)
    tags = {
        "hostname": [f"host_{i}" for i in range(hosts)],
        "region": [REGIONS[r] for r in reg],
        "datacenter": [REGIONS[r] + "abc"[z] for r, z in
                       zip(reg, rng.integers(0, 3, hosts))],
        "rack": [str(v) for v in rng.integers(0, 100, hosts)],
        "os": [("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")[v]
               for v in rng.integers(0, 3, hosts)],
        "arch": [("x64", "x86")[v] for v in rng.integers(0, 2, hosts)],
        "team": [("SF", "NYC", "LON", "CHI")[v]
                 for v in rng.integers(0, 4, hosts)],
        "service": [str(v) for v in rng.integers(0, 20, hosts)],
        "service_version": [str(v) for v in rng.integers(0, 2, hosts)],
        "service_environment": [("production", "staging", "test")[v]
                                for v in rng.integers(0, 3, hosts)],
    }
    data = np.empty((ticks + 1, hosts, len(FIELDS)), dtype=np.float64)
    x = rng.uniform(0.0, 100.0, (hosts, len(FIELDS)))
    data[0] = x
    t = 1
    while t <= ticks:
        steps = rng.standard_normal(
            (min(512, ticks + 1 - t), hosts, len(FIELDS)))
        for s in steps:
            x = np.clip(x + s, 0.0, 100.0)
            data[t] = x
            t += 1
    return tags, data


# ---------------------------------------------------------------------------
# wire clients (sockets only; the parent never imports jax)
# ---------------------------------------------------------------------------

class Http:
    def __init__(self, port: int):
        self.base = f"http://127.0.0.1:{port}"

    def _open(self, path, params=None, timeout=300):
        data = urllib.parse.urlencode(params).encode() if params else None
        try:
            with urllib.request.urlopen(self.base + path, data=data,
                                        timeout=timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            raise RuntimeError(
                f"{path}: HTTP {e.code}: {e.read()[:2000]!r}") from None

    def status(self) -> dict:
        return self._open("/status", timeout=30)

    def sql(self, sql: str):
        """-> (column names, rows) or affected-row count; the BODY's
        code decides, not the HTTP status."""
        body = self._open("/v1/sql", {"sql": sql})
        if body.get("code") != 0:
            raise RuntimeError(f"/v1/sql code={body.get('code')}: "
                               f"{str(body)[:2000]} for {sql[:200]}")
        out = body["output"][-1]
        if "affectedrows" in out:
            return out["affectedrows"]
        rec = out["records"]
        return ([c["name"] for c in rec["schema"]["column_schemas"]],
                rec["rows"])

    def query_range(self, query: str, start_ms: int, end_ms: int,
                    step_s: int) -> list:
        body = self._open("/api/v1/query_range", {
            "query": query, "start": start_ms / 1000.0,
            "end": end_ms / 1000.0, "step": step_s})
        if body.get("status") != "success":
            raise RuntimeError(f"query_range: {str(body)[:2000]}")
        return body["data"]["result"]


class MiniMysql:
    """Just enough of the MySQL client protocol: protocol-41 handshake
    with an empty mysql_native_password, COM_QUERY, text result sets."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=300)
        self.seq = 0
        greeting = self._read()
        if greeting[0] != 10:
            raise RuntimeError("mysql: expected a protocol-10 greeting")
        caps = 0x0200 | 0x8000 | 0x80000   # PROTOCOL_41|SECURE|PLUGIN_AUTH
        self._write(struct.pack("<IIB", caps, 1 << 24, 45) + b"\x00" * 23
                    + b"greptime\x00" + b"\x00"
                    + b"mysql_native_password\x00")
        resp = self._read()
        if resp[0] != 0x00:
            raise RuntimeError(f"mysql: login refused: {resp[9:]!r}")

    def close(self):
        self.sock.close()

    def _recv(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(min(1 << 20, n - len(buf)))
            if not chunk:
                raise RuntimeError("mysql: connection closed")
            buf += chunk
        return bytes(buf)

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
            raise RuntimeError("mysql: statement too long for one packet")
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

    def query(self, sql: str):
        """-> (column names, rows of str/None) or affected-row count."""
        self.seq = 0
        self._write(b"\x03" + sql.encode())
        head = self._read()
        if head[0] == 0xFF:
            raise RuntimeError(f"mysql: {head[9:]!r} for {sql[:200]}")
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
            raise RuntimeError("mysql: expected EOF after the columns")
        rows = []
        while True:
            p = self._read()
            if p[0] == 0xFE and len(p) < 9:
                return names, rows
            row, pos = [], 0
            for _ in range(ncols):
                if p[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    n, pos = self._lenenc(p, pos)
                    row.append(p[pos:pos + n].decode())
                    pos += n
            rows.append(row)


# ---------------------------------------------------------------------------
# the server: the one process that owns the chip
# ---------------------------------------------------------------------------

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, data_home: str, log_path: str):
        self.data_home = data_home
        self.log_path = log_path
        self.proc = None
        self.ports = {}

    def start(self) -> None:
        self.ports = {k: free_port()
                      for k in ("http", "mysql", "postgres", "grpc")}
        cmd = [sys.executable, "-m", "greptimedb_tpu.cmd.main",
               "standalone", "start", "--data-home", self.data_home]
        for k, port in self.ports.items():
            cmd += [f"--{k}-addr", f"127.0.0.1:{port}"]
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(cmd, cwd=HERE, stdout=self._log,
                                     stderr=subprocess.STDOUT,
                                     start_new_session=True)
        http = Http(self.ports["http"])
        deadline = time.monotonic() + 180
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with {self.proc.returncode} at start:\n"
                    + self.log_tail())
            try:
                http.status()
                return
            except (OSError, RuntimeError):
                if time.monotonic() > deadline:
                    raise RuntimeError("server not ready after 180 s:\n"
                                       + self.log_tail()) from None
                time.sleep(0.5)

    def kill(self) -> None:
        """SIGKILL the server's process group (the crash the durability
        phase wants, and the way out on every other path)."""
        if self.proc is None:
            return
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        self._log.close()
        self.proc = None

    def log_tail(self, nbytes: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - nbytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"<no server log: {e}>"


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def compare(name: str, got: dict, want: dict, tol: dict,
            slack: dict = None) -> dict:
    """got/want: {key: [floats]}. Fails on a key-set or value mismatch;
    returns the worst errors seen. `slack` widens the bound per element
    (same shape as want) where the statement itself is ill-conditioned
    at f32."""
    if set(got) != set(want):
        missing = sorted(set(want) - set(got))[:5]
        extra = sorted(set(got) - set(want))[:5]
        raise AssertionError(
            f"{name}: result keys differ: {len(got)} rows vs {len(want)} "
            f"expected; missing {missing}, unexpected {extra}")
    keys = sorted(want)
    g = np.array([got[k] for k in keys], dtype=np.float64)
    w = np.array([want[k] for k in keys], dtype=np.float64)
    if g.shape != w.shape:
        raise AssertionError(f"{name}: shape {g.shape} vs {w.shape}")
    err = np.abs(g - w)
    bound = tol["atol"] + tol["rtol"] * np.abs(w)
    if slack is not None:
        bound = bound + np.array([slack[k] for k in keys], dtype=np.float64)
    if not np.isfinite(g).all() or (err > bound).any():
        i = int(np.argmax(err - bound)) // max(g.shape[1], 1)
        raise AssertionError(
            f"{name}: off beyond {tol} at {keys[i]}: got {g[i]}, "
            f"want {w[i]}")
    # worst errors over the well-conditioned elements (all, without slack)
    firm = np.ones(w.shape, dtype=bool) if slack is None else \
        np.array([slack[k] for k in keys]) == 0
    rel = err / np.maximum(np.abs(w), 1e-300)
    return {"rows": len(keys),
            "max_abs_err": float(err.max(where=firm, initial=0.0)),
            "max_rel_err": float(rel.max(where=firm, initial=0.0)),
            "ill_conditioned_values": int((~firm).sum())}


def stages_of(rows) -> dict:
    """EXPLAIN ANALYZE rows -> {stage: detail / elapsed}."""
    out = {}
    for stage, nrows, _files, ms, detail in rows:
        out[str(stage)] = {"rows": int(nrows), "elapsed_ms": float(ms),
                           "detail": detail or ""}
    return out


def executed_dispatch(name: str, run: str, rows) -> dict:
    st = stages_of(rows)
    dispatch = st.get("dispatch", {}).get("detail", "<no dispatch row>")
    if dispatch != "device-resident (scan cache)":
        raise AssertionError(
            f"{name} ({run}): executed dispatch is {dispatch!r}, not "
            "device-resident")
    return st


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def cache_entries(cache_dir: str) -> set:
    try:
        return {n for n in os.listdir(cache_dir) if not n.endswith("-atime")}
    except FileNotFoundError:
        return set()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--hosts", type=int, default=4000)
    ap.add_argument("--hours", type=int, default=12)
    ap.add_argument(
        "--debug-platform", default="tpu",
        help="run every phase against this platform for debugging (cpu at "
             "a tiny size); anything but tpu prints no result line and "
             "exits 3")
    args = ap.parse_args()

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_CACHE_DIR
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    server = Server(os.path.join(work, "data"),
                    os.path.join(work, "server.log"))
    report = {"seed": args.seed, "hosts": args.hosts, "hours": args.hours,
              "assumed": ASSUMED, "reduced": REDUCED, "statements": {}}

    def on_alarm(signum, frame):
        raise TimeoutError("chip_smoke exceeded its 1150 s budget")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(1150)
    try:
        summary = run(args, server, report, cache_dir)
    except BaseException:
        print("---- server log tail ----\n" + server.log_tail(),
              file=sys.stderr, flush=True)
        raise
    finally:
        signal.alarm(0)
        server.kill()
        save_report(report, server)
        shutil.rmtree(work, ignore_errors=True)
    if "jax" in sys.modules:
        raise AssertionError("the parent process imported jax")
    if args.debug_platform != "tpu":
        log(f"debug run on {args.debug_platform}: every phase passed; "
            "no result line")
        return 3
    print("summary: " + json.dumps(summary), flush=True)
    print(result_line(report["device"]), flush=True)
    return 0


def result_line(dev: dict) -> str:
    """The last stdout line: these keys and no others (the driver's check
    parses it strictly). `dev` is /status.device, which the server fills
    from jax.devices()."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(dev["platform"]),
                   "kind": str(dev["device_kind"]),
                   "count": int(dev["device_count"])}})


def save_report(report: dict, server: Server) -> None:
    """Everything too long for the tail of stdout goes to chiprun_out/."""
    out_dir = os.path.join(HERE, "chiprun_out")
    try:
        os.makedirs(out_dir, exist_ok=True)
        report["server_log_tail"] = server.log_tail(20000)
        with open(os.path.join(out_dir, "chip_smoke_report.json"), "w") as f:
            json.dump(report, f, indent=1)
    except OSError as e:
        log(f"report not written: {e}")


def run(args, server: Server, report: dict, cache_dir: str) -> dict:
    hosts, ticks = args.hosts, args.hours * TICKS_PER_HOUR
    end_ms = T0_MS + ticks * TICK_MS
    if hosts < WAL_HOSTS or args.hours < 2:
        raise SystemExit("need --hosts >= 300 and --hours >= 2")

    # ---- start, and name the device before anything is loaded ----------
    server.start()
    http = Http(server.ports["http"])
    status = http.status()
    dev = status["device"]
    log(f"server up: device={dev} wal_backend={status['wal_backend']} "
        f"compile_cache={cache_dir}")
    if dev["platform"] != args.debug_platform:
        raise SystemExit(
            f"chip_smoke: the server runs on platform "
            f"{dev['platform']!r} ({dev['device_kind']}, "
            f"{dev['device_count']} device(s)), not {args.debug_platform!r}"
            "; nothing was loaded")
    report["device"] = dev
    report["wal_backend"] = status["wal_backend"]

    # ---- data ----------------------------------------------------------
    t = time.monotonic()
    tags, data = generate(args.seed, hosts, ticks)
    rows_loaded = hosts * ticks
    log(f"generated {rows_loaded:,} rows x {len(FIELDS)} fields "
        f"(seed {args.seed}) in {time.monotonic() - t:.1f} s")

    # ---- load over the wire: DDL first, then Flight bulk_load ----------
    cols = ", ".join(f"{c} STRING" for c in TAGS) + \
        ", ts TIMESTAMP TIME INDEX, " + \
        ", ".join(f"{c} DOUBLE" for c in FIELDS)
    http.sql(f"CREATE TABLE cpu ({cols}, PRIMARY KEY({', '.join(TAGS)}))")
    db = Database(f"grpc://127.0.0.1:{server.ports['grpc']}")
    dictionaries = {}
    codes = {}
    for tag in TAGS:
        uniq, inv = np.unique(np.array(tags[tag], dtype=object),
                              return_inverse=True)
        dictionaries[tag] = pa.array(list(uniq), type=pa.string())
        codes[tag] = inv.astype(np.int32)
    t = time.monotonic()
    acked = 0
    for a in range(0, ticks, LOAD_CHUNK_TICKS):
        b = min(a + LOAD_CHUNK_TICKS, ticks)
        n = b - a
        # host-major within the chunk: long per-series runs
        block = data[a:b].transpose(1, 0, 2).reshape(hosts * n, len(FIELDS))
        columns = {tag: pa.DictionaryArray.from_arrays(
            pa.array(np.repeat(codes[tag], n)), dictionaries[tag])
            for tag in TAGS}
        columns["ts"] = np.tile(T0_MS + np.arange(a, b, dtype=np.int64)
                                * TICK_MS, hosts)
        for i, f in enumerate(FIELDS):
            columns[f] = np.ascontiguousarray(block[:, i])
        acked += db.bulk_load("cpu", columns, tag_columns=TAGS,
                              timestamp_column="ts")
    db.close()
    load_s = time.monotonic() - t
    if acked != rows_loaded:
        raise AssertionError(f"bulk_load acknowledged {acked} of "
                             f"{rows_loaded} rows")
    log(f"loaded {acked:,} rows over Flight in {load_s:.1f} s")
    report["rows_loaded"] = acked
    report["load_s"] = load_s

    # ---- the next tick through the WAL path, each INSERT acknowledged --
    col_list = ", ".join(TAGS + ["ts"] + FIELDS)
    for a in range(0, WAL_HOSTS, 100):
        values = ", ".join(
            "(" + ", ".join(f"'{tags[tag][h]}'" for tag in TAGS)
            + f", {end_ms}, "
            + ", ".join(repr(float(v)) for v in data[ticks, h]) + ")"
            for h in range(a, a + 100))
        n = http.sql(f"INSERT INTO cpu ({col_list}) VALUES {values}")
        if n != 100:
            raise AssertionError(f"INSERT acknowledged {n} of 100 rows")
    log(f"wrote {WAL_HOSTS} rows of tick {ticks} through the WAL path")
    report["wal_rows"] = WAL_HOSTS

    # ---- statements ----------------------------------------------------
    rng = np.random.default_rng(args.seed + 1)
    mysql = MiniMysql(server.ports["mysql"])
    statements = build_statements(rng, tags, data, hosts, ticks,
                                  dev["platform"])
    for st in statements:
        run_statement(st, http, mysql, report)
    mysql.close()

    # ---- the table lives on the device ---------------------------------
    status = http.status()
    in_use = status["device"].get("bytes_in_use")
    total_rows = rows_loaded + WAL_HOSTS
    # int32 ts + one f32 mirror per field the statements touched (all ten)
    mirrors = total_rows * 4 * (1 + len(FIELDS))
    log(f"HBM in use {in_use} B (peak "
        f"{status['device'].get('peak_bytes_in_use')}); mirrors of the "
        f"touched columns {mirrors} B; scan cache "
        f"{status['scan_cache_resident_bytes']} B")
    report["hbm_bytes_in_use"] = in_use
    report["hbm_peak_bytes_in_use"] = status["device"].get(
        "peak_bytes_in_use")
    report["mirror_bytes"] = mirrors
    if args.debug_platform == "tpu" and (in_use is None or in_use < mirrors):
        raise AssertionError(
            f"HBM in use {in_use} < {mirrors} B of column mirrors: the "
            "table is not on the device")

    # ---- durability: SIGKILL, restart on the same data_home ------------
    server.kill()
    before = cache_entries(cache_dir)
    server.start()
    http = Http(server.ports["http"])
    rows = http.sql(
        f"SELECT hostname, {', '.join(FIELDS)} FROM cpu "
        f"WHERE ts = {end_ms} ORDER BY hostname")[1]
    got = {r[0]: r[1:] for r in rows}
    want = {tags["hostname"][h]: list(data[ticks, h])
            for h in range(WAL_HOSTS)}
    if got != want:
        raise AssertionError(
            f"after SIGKILL + restart {len(got)} of {WAL_HOSTS} "
            "acknowledged WAL-path rows read back, or their values differ")
    log(f"restart: all {WAL_HOSTS} acknowledged WAL-path rows read back "
        "exactly")
    repeated = next(s for s in statements if s["name"] == "double-groupby-all")
    repeated = dict(repeated, name="double-groupby-all@restart", via="http")
    run_statement(repeated, http, None, report)
    new = cache_entries(cache_dir) - before
    report["compile_cache"] = {"dir": cache_dir, "entries": len(before),
                               "new_after_restart": sorted(new)}
    if new:
        raise AssertionError(
            f"the restarted server wrote {len(new)} new compile-cache "
            f"entries for a repeated statement: {sorted(new)[:4]}")
    log(f"restart: {len(before)} compile-cache entries in {cache_dir}, "
        "none new")

    return {
        "rows_loaded": acked, "wal_rows_read_back": WAL_HOSTS,
        "wal_backend": report["wal_backend"],
        "statements": {
            name: {"via": s["via"], "dispatch": s["dispatch"],
                   "rows": s["check"]["rows"],
                   "max_abs_err": s["check"]["max_abs_err"],
                   "max_rel_err": s["check"]["max_rel_err"]}
            for name, s in report["statements"].items()},
        "hbm_bytes_in_use": in_use, "mirror_bytes": mirrors,
        "compile_cache_new_after_restart": 0,
        "seed": args.seed, "reduced": REDUCED,
    }


def run_statement(st: dict, http: Http, mysql, report: dict) -> None:
    """EXPLAIN ANALYZE (first run: compiles), the statement itself
    (answer checked), EXPLAIN ANALYZE again (the repeat)."""
    name, via = st["name"], st["via"]
    send = mysql.query if via == "mysql" else http.sql
    timings = []

    def timed(fn, *a):
        t = time.monotonic()
        out = fn(*a)
        timings.append(round(time.monotonic() - t, 4))
        return out

    if via == "prom":
        analyze = (f"TQL ANALYZE ({st['start_ms'] / 1000.0}, "
                   f"{st['end_ms'] / 1000.0}, '{st['step_s']}s') "
                   f"{st['query']}")
        first = timed(http.sql, analyze)[1]
        series = timed(http.query_range, st["query"], st["start_ms"],
                       st["end_ms"], st["step_s"])
        got = st["parse"](series)
        second = timed(http.sql, analyze)[1]
        # the sliding window does not lower to the scan kernels: its
        # executed dispatch names where the window kernel's result lived
        wanted = f"promql-row-path (window kernel on {st['platform']})"
        dispatch = []
        for run_name, rows in (("first run", first), ("repeat", second)):
            text = "\n".join(str(r[1]) for r in rows)
            if f", {wanted}" not in text:
                raise AssertionError(
                    f"{name} ({run_name}): TQL ANALYZE shows no "
                    f"{wanted!r}:\n{text}")
            dispatch.append(wanted)
        stages = {"first": [list(r) for r in first],
                  "repeat": [list(r) for r in second]}
    else:
        first = executed_dispatch(
            name, "first run", timed(send, "EXPLAIN ANALYZE " + st["sql"])[1])
        rows = timed(send, st["sql"])[1]
        got = st["parse"](rows)
        second = executed_dispatch(
            name, "repeat", timed(send, "EXPLAIN ANALYZE " + st["sql"])[1])
        dispatch = [first["dispatch"]["detail"], second["dispatch"]["detail"]]
        stages = {"first": first, "repeat": second}
    check = compare(name, got, st["want"], TOL[st["agg"]], st.get("slack"))
    report["statements"][name] = {
        "via": via, "sql": st.get("sql") or st.get("query"),
        "dispatch": dispatch, "check": check, "tolerance": TOL[st["agg"]],
        "wall_s_first_answer_repeat": timings, "stages": stages}
    log(f"{name} via {via}: {check['rows']} rows correct "
        f"(max abs err {check['max_abs_err']:.3g}, rel "
        f"{check['max_rel_err']:.3g}); dispatch {dispatch}; wall "
        f"{timings} s")


def build_statements(rng, tags, data, hosts: int, ticks: int,
                     platform: str) -> list:
    """The statements with their float64 references. data[t, h, f]."""
    hostnames = tags["hostname"]
    hours = ticks // TICKS_PER_HOUR
    end_ms = T0_MS + ticks * TICK_MS
    full = data[:ticks]

    def window(hours_long: int):
        """A minute-aligned [lo, hi) of that length inside the load."""
        minutes = rng.integers(0, (hours - hours_long) * 60 + 1)
        lo = int(minutes) * 6
        return lo, lo + hours_long * TICKS_PER_HOUR

    def ms(tick: int) -> int:
        return T0_MS + tick * TICK_MS

    def in_list(hs) -> str:
        return ", ".join(f"'{hostnames[h]}'" for h in hs)

    def floats(row):
        return [float(v) for v in row]

    def to_ms(v) -> int:
        """HTTP returns epoch ms, MySQL 'YYYY-MM-DD HH:MM:SS.mmm' (UTC)."""
        if isinstance(v, (int, float)):
            return int(v)
        whole = calendar.timegm(time.strptime(v[:19], "%Y-%m-%d %H:%M:%S"))
        return whole * 1000 + int(v[20:23] or 0)

    def by_time(rows):
        return {to_ms(r[0]): floats(r[1:]) for r in rows}

    def by_host_time(rows):
        return {(r[0], to_ms(r[1])): floats(r[2:]) for r in rows}

    def by_host(rows):
        return {r[0]: floats(r[1:]) for r in rows}

    out = []

    # non-TSBS: 4000 groups keeps the LOW-cardinality kernels (RMQ sparse
    # table for max, block partials for sum) on the served path; it is
    # also the first statement, so it pays the scan-cache build, and the
    # point predicates below find the region resident (a cold one would
    # answer them through the SST index, off the device)
    out.append({
        "name": "host-summary", "via": "http", "agg": "avg",
        "sql": "SELECT hostname, max(usage_user), avg(usage_system) "
               f"FROM cpu WHERE ts >= {T0_MS} AND ts < {end_ms} "
               "GROUP BY hostname ORDER BY hostname",
        "parse": by_host,
        "want": {hostnames[h]: [full[:, h, 0].max(), full[:, h, 1].mean()]
                 for h in range(hosts)}})

    hourly = full.reshape(hours, TICKS_PER_HOUR, hosts, len(FIELDS))
    for name, via, fn, agg in (
            ("double-groupby-all", "mysql", "avg", hourly.mean(axis=1)),
            ("double-groupby-all-max", "http", "max", hourly.max(axis=1))):
        out.append({
            "name": name, "via": via, "agg": fn,
            "sql": "SELECT hostname, date_bin(INTERVAL '1 hour', ts) AS "
                   "hour, " + ", ".join(f"{fn}({f})" for f in FIELDS)
                   + f" FROM cpu WHERE ts >= {T0_MS} AND ts < {end_ms} "
                   "GROUP BY hostname, hour ORDER BY hostname, hour",
            "parse": by_host_time,
            "want": {(hostnames[h], ms(k * TICKS_PER_HOUR)): agg[k, h]
                     for k in range(hours) for h in range(hosts)}})

    lo, hi = window(1)
    h1 = [int(rng.integers(0, hosts))]
    out.append({
        "name": "single-groupby-1-1-1", "via": "http", "agg": "max",
        "sql": "SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               "max(usage_user) FROM cpu WHERE hostname = "
               f"'{hostnames[h1[0]]}' AND ts >= {ms(lo)} AND ts < {ms(hi)} "
               "GROUP BY minute ORDER BY minute",
        "parse": by_time,
        "want": {ms(lo + 6 * k): [data[lo + 6 * k:lo + 6 * k + 6, h1[0],
                                       0].max()] for k in range(60)}})

    lo, hi = window(1)
    h8 = [int(h) for h in rng.choice(hosts, 8, replace=False)]
    out.append({
        "name": "single-groupby-5-8-1", "via": "mysql", "agg": "max",
        "sql": "SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
               + ", ".join(f"max({f})" for f in FIELDS[:5])
               + f" FROM cpu WHERE hostname IN ({in_list(h8)}) AND "
               f"ts >= {ms(lo)} AND ts < {ms(hi)} "
               "GROUP BY minute ORDER BY minute",
        "parse": by_time,
        "want": {ms(lo + 6 * k):
                 data[lo + 6 * k:lo + 6 * k + 6][:, h8, :5].max(axis=(0, 1))
                 for k in range(60)}})

    span = min(8, hours)
    lo, hi = window(span)
    lo -= lo % TICKS_PER_HOUR          # hour buckets: whole hours
    hi = lo + span * TICKS_PER_HOUR
    h8 = [int(h) for h in rng.choice(hosts, 8, replace=False)]
    out.append({
        "name": "cpu-max-all-8", "via": "http", "agg": "max",
        "sql": "SELECT date_bin(INTERVAL '1 hour', ts) AS hour, "
               + ", ".join(f"max({f})" for f in FIELDS)
               + f" FROM cpu WHERE hostname IN ({in_list(h8)}) AND "
               f"ts >= {ms(lo)} AND ts < {ms(hi)} "
               "GROUP BY hour ORDER BY hour",
        "parse": by_time,
        "want": {ms(lo + TICKS_PER_HOUR * k):
                 data[lo + TICKS_PER_HOUR * k:
                      lo + TICKS_PER_HOUR * (k + 1)][:, h8].max(axis=(0, 1))
                 for k in range(span)}})

    # no time predicate: the WAL-path rows of tick `ticks` are the last
    # point of the first WAL_HOSTS hosts
    out.append({
        "name": "lastpoint-agg", "via": "mysql", "agg": "last",
        "sql": "SELECT hostname, last(usage_user) FROM cpu "
               "GROUP BY hostname ORDER BY hostname",
        "parse": by_host,
        "want": {hostnames[h]: [data[ticks if h < WAL_HOSTS else ticks - 1,
                                     h, 0]] for h in range(hosts)}})

    # one sliding PromQL window over the Prometheus API
    lo, hi = window(1)
    lo = max(lo, 30)                   # a full 5m of samples before start
    hi = lo + TICKS_PER_HOUR
    want, slack = {}, {}
    region_of = np.array([REGIONS.index(r) for r in tags["region"]])
    for k in range(61):
        t_idx = lo + 6 * k
        v = data[t_idx - 29:t_idx + 1, :, 0]           # (t - 5m, t]
        reset = v[1:] < v[:-1]
        raw = v[-1] - v[0] + np.where(reset, v[:-1], 0.0).sum(axis=0)
        # rate() treats a drop as a counter reset and adds the previous
        # value back. Two samples closer than one f32 ulp compare either
        # way on the f32 mirrors (expected a handful of times in 1.6M
        # gauge samples), so such a pair's contribution is slack, not error
        step = np.abs(v[1:] - v[:-1])        # 0 at a clamp: decided
        near = (step > 0) & (step <= np.spacing(
            np.maximum(v[1:], v[:-1]).astype(np.float32)))
        undecided = np.where(near, v[:-1], 0.0).sum(axis=0)
        sampled, avg_dur = 290.0, 10.0
        to_start = np.full(hosts, 10.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            to_zero = np.where((raw > 0) & (v[0] >= 0),
                               sampled * (v[0] / raw), np.inf)
        to_start = np.minimum(to_start, to_zero)
        ext = sampled + np.where(to_start < avg_dur * 1.1, to_start,
                                 avg_dur / 2) + 0.0
        rate = raw * (ext / sampled) / 300.0
        # ... plus what the changed increase does to the extrapolation
        # factor, which lies in [1, 300/290]
        loose = (undecided * 1.07 + np.where(undecided > 0, raw, 0.0)
                 * 0.035) / 300.0
        for r, name in enumerate(REGIONS):
            want.setdefault(name, []).append(rate[region_of == r].sum())
            slack.setdefault(name, []).append(loose[region_of == r].sum())

    def parse_prom(series):
        got = {}
        for s in series:
            vals = s["values"]
            if [int(round(float(t) * 1000)) for t, _ in vals] != \
                    [ms(lo + 6 * k) for k in range(61)]:
                raise AssertionError(
                    f"prom series {s['metric']}: steps differ")
            got[s["metric"]["region"]] = [float(v) for _, v in vals]
        return got

    out.append({
        "name": "promql-rate-5m-by-region", "via": "prom", "agg": "rate",
        "query": 'sum by (region) (rate(cpu{__field__="usage_user"}[5m]))',
        "start_ms": ms(lo), "end_ms": ms(hi), "step_s": 60,
        "platform": platform, "parse": parse_prom,
        "want": {k: v for k, v in want.items()
                 if (region_of == REGIONS.index(k)).any()},
        "slack": slack})
    return out


if __name__ == "__main__":
    sys.exit(main())
