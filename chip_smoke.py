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
families of cmd/tsbs_generate_queries for it: the benchmark's
configuration `benchmark/configs/tsbs-cpu-4000.json`, whose `assumed` lists
what was recalled rather than copied and `reduced` what was cut and why.
The generator, the Flight loader, the wire clients, the server's start and
SIGKILL and the comparison are `benchmark/benchlib`'s; what is the smoke's
own is the statements with their references, the Prometheus API, the HBM
check and the compile cache across a restart.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# the benchmark's client library: the deployment's generator and Flight
# loader, the wire clients, the server's start and SIGKILL, the comparison.
# Sockets only: no module of it imports jax, and neither does this parent
sys.path.insert(0, os.path.join(HERE, "benchmark"))
from benchlib.check import (                             # noqa: E402
    compare, executed_dispatch, stages_of)
from benchlib.data import REGIONS, Dataset               # noqa: E402
from benchlib.harness import cache_entries               # noqa: E402
from benchlib.server import Server                       # noqa: E402
from benchlib.spec import load_json                      # noqa: E402
from benchlib.tsbs import (                              # noqa: E402
    DEVICE_RESIDENT, TOL as TSBS_TOL, by_host, by_host_time, by_time)
from benchlib.wire import Http, MiniMysql                # noqa: E402

# the deployment: the benchmark's own file (table, tags, fields, start,
# tick, Flight chunk, `assumed`, `reduced`); the statements below call the
# time index ts, as that file says they do
CONFIG = dict(load_json(HERE, "benchmark", "configs", "tsbs-cpu-4000.json"),
              time_index="ts")
TICKS_PER_HOUR = 3600 // CONFIG["log_interval_s"]
WAL_HOSTS = 300                    # rows of the next tick via SQL INSERT

# float64 reference vs f32 device mirrors: the families' tolerances, and
# for the PromQL statement a per-series f32 rate summed over ~444 hosts
TOL = dict(TSBS_TOL, rate=dict(rtol=1e-4, atol=0.0))


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T_START:7.1f}s] {msg}",
          flush=True)


_T_START = time.monotonic()


class PromHttp(Http):
    """benchlib's client plus the Prometheus API, which no cell calls."""

    def query_range(self, query: str, start_ms: int, end_ms: int,
                    step_s: int) -> list:
        body = json.loads(self._open("/api/v1/query_range", {
            "query": query, "start": start_ms / 1000.0,
            "end": end_ms / 1000.0, "step": step_s}))
        if body.get("status") != "success":
            raise RuntimeError(f"query_range: {str(body)[:2000]}")
        return body["data"]["result"]


def check_answer(name: str, got: dict, want: dict, tol: dict,
                 slack: dict = None) -> dict:
    """benchlib's `compare`, raising on a wrong answer. What differs:
    `slack` (same shape as want) widens the bound per element where the
    statement itself is ill-conditioned at f32, which no family of the
    benchmark needs; it is taken off the error before the comparison."""
    if slack is not None and set(got) == set(want):
        def within(k):
            g, w = np.asarray(got[k], float), np.asarray(want[k], float)
            return w + np.sign(g - w) * np.maximum(
                np.abs(g - w) - np.asarray(slack[k]), 0.0)
        got = {k: within(k) for k in want}
    result = compare(got, want, tol)
    if not result["ok"]:
        raise AssertionError(f"{name}: {result['why']}")
    return result


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

    # where benchlib's Server puts the cache when nothing else says
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(HERE, ".jax_cache")
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    # the real run leaves the platform as this process found it (on the
    # chip's machine nothing is set, and the server refuses a CPU nobody
    # asked for); a debug run names it
    server = Server(work, CONFIG["server_options"],
                    os.environ.get("JAX_PLATFORMS")
                    if args.debug_platform == "tpu" else args.debug_platform)
    report = {"seed": args.seed, "hosts": args.hosts, "hours": args.hours,
              "assumed": CONFIG["assumed"], "reduced": CONFIG["reduced"],
              "statements": {}}

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
    if hosts < WAL_HOSTS or args.hours < 2:
        raise SystemExit("need --hosts >= 300 and --hours >= 2")

    # ---- start, and name the device before anything is loaded ----------
    server.start()
    status = server.wait_ready()
    http = PromHttp(server.ports["http"])
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
    # data[ticks] is the next tick, written via the WAL path for the
    # first WAL_HOSTS hosts only
    t = time.monotonic()
    ds = Dataset(CONFIG, args.seed, extra_ticks=1, scale=hosts, ticks=ticks)
    tags, data, fields = ds.tags, ds.data, ds.field_names
    rows_loaded, end_ms = ds.rows, ds.end_ms
    log(f"generated {rows_loaded:,} rows x {len(fields)} fields "
        f"(seed {args.seed}) in {time.monotonic() - t:.1f} s")

    # ---- load over the wire: DDL first, then Flight bulk_load ----------
    http.sql(ds.create_table_sql())
    t = time.monotonic()
    acked = ds.load(server.ports["grpc"], CONFIG["load_chunk_ticks"])
    load_s = time.monotonic() - t
    if acked != rows_loaded:
        raise AssertionError(f"bulk_load acknowledged {acked} of "
                             f"{rows_loaded} rows")
    log(f"loaded {acked:,} rows over Flight in {load_s:.1f} s")
    report["rows_loaded"] = acked
    report["load_s"] = load_s

    # ---- the next tick through the WAL path, each INSERT acknowledged --
    col_list = ", ".join(ds.tag_names + ["ts"] + fields)
    for a in range(0, WAL_HOSTS, 100):
        values = ", ".join(
            "(" + ", ".join(f"'{tags[tag][h]}'" for tag in ds.tag_names)
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
    statements = build_statements(rng, ds, dev["platform"])
    for st in statements:
        run_statement(st, http, mysql, report)
    mysql.close()

    # ---- the table lives on the device ---------------------------------
    status = http.status()
    in_use = status["device"].get("bytes_in_use")
    total_rows = rows_loaded + WAL_HOSTS
    # int32 ts + one f32 mirror per field the statements touched (all ten)
    mirrors = total_rows * 4 * (1 + len(fields))
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
    server.wait_ready()
    http = PromHttp(server.ports["http"])
    rows = http.sql(
        f"SELECT hostname, {', '.join(fields)} FROM cpu "
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
        "seed": args.seed, "reduced": CONFIG["reduced"],
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
        first = stages_of(timed(send, "EXPLAIN ANALYZE " + st["sql"])[1])
        rows = timed(send, st["sql"])[1]
        got = st["parse"](rows)
        second = stages_of(timed(send, "EXPLAIN ANALYZE " + st["sql"])[1])
        dispatch = [executed_dispatch(first), executed_dispatch(second)]
        for run_name, executed in zip(("first run", "repeat"), dispatch):
            if executed != DEVICE_RESIDENT:
                raise AssertionError(
                    f"{name} ({run_name}): executed dispatch is "
                    f"{executed!r}, not device-resident")
        stages = {"first": first, "repeat": second}
    check = check_answer(name, got, st["want"], TOL[st["agg"]],
                         st.get("slack"))
    report["statements"][name] = {
        "via": via, "sql": st.get("sql") or st.get("query"),
        "dispatch": dispatch, "check": check, "tolerance": TOL[st["agg"]],
        "wall_s_first_answer_repeat": timings, "stages": stages}
    log(f"{name} via {via}: {check['rows']} rows correct "
        f"(max abs err {check['max_abs_err']:.3g}, rel "
        f"{check['max_rel_err']:.3g}); dispatch {dispatch}; wall "
        f"{timings} s")


def build_statements(rng, ds: Dataset, platform: str) -> list:
    """The statements with their float64 references. data[t, h, f]."""
    tags, data, hosts, ticks = ds.tags, ds.data, ds.hosts, ds.ticks
    FIELDS, ms = ds.field_names, ds.ms
    T0_MS, end_ms = ds.t0_ms, ds.end_ms
    hostnames = ds.hostnames
    hours = ticks // TICKS_PER_HOUR
    full = data[:ticks]

    def window(hours_long: int):
        """A minute-aligned [lo, hi) of that length inside the load."""
        minutes = rng.integers(0, (hours - hours_long) * 60 + 1)
        lo = int(minutes) * 6
        return lo, lo + hours_long * TICKS_PER_HOUR

    def in_list(hs) -> str:
        return ", ".join(f"'{hostnames[h]}'" for h in hs)

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
