"""Protocol server tests: HTTP API, ingest protocols, snappy, auth.

Mirrors the reference integration matrix (tests-integration/tests/http.rs)
against a live server on an ephemeral port.
"""

import json
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.datanode import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend import FrontendInstance
from greptimedb_tpu.servers.auth import StaticUserProvider
from greptimedb_tpu.servers.http import HttpServer
from greptimedb_tpu.servers import prometheus as prom
from greptimedb_tpu.utils import snappy


@pytest.fixture()
def server(tmp_path):
    dn = DatanodeInstance(DatanodeOptions(data_home=str(tmp_path)))
    fe = FrontendInstance(dn)
    fe.start()
    srv = HttpServer(fe, addr="127.0.0.1:0")
    srv.start()
    yield srv
    srv.shutdown()
    fe.shutdown()


def req(server, path, method="GET", body=None, headers=None, params=None,
        raise_on_error=True):
    url = f"http://127.0.0.1:{server.port}{path}"
    if params:
        url += "?" + urllib.parse.urlencode(params, doseq=True)
    r = urllib.request.Request(url, data=body, method=method,
                               headers=headers or {})
    try:
        with urllib.request.urlopen(r, timeout=10) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        if raise_on_error and e.code == 401:
            raise
        return e.code, e.read()


def sql(server, stmt):
    status, body = req(server, "/v1/sql", "POST",
                       urllib.parse.urlencode({"sql": stmt}).encode(),
                       {"Content-Type": "application/x-www-form-urlencoded"})
    assert status == 200, body
    return json.loads(body)


class TestSnappy:
    def test_round_trip(self):
        for payload in (b"", b"a", b"hello world " * 100,
                        bytes(range(256)) * 50):
            assert snappy.decompress(snappy.compress(payload)) == payload

    def test_backreference_decode(self):
        # handcrafted: literal 'abcd' + copy(offset=4, len=4) → 'abcdabcd'
        data = bytes([8]) + bytes([(4 - 1) << 2]) + b"abcd" + \
            bytes([0x01 | ((4 - 4) << 2)]) + bytes([4])
        assert snappy.decompress(data) == b"abcdabcd"


class TestHttpSql:
    def test_sql_round_trip(self, server):
        out = sql(server, "CREATE TABLE m (host STRING, ts TIMESTAMP TIME "
                          "INDEX, cpu DOUBLE, PRIMARY KEY(host))")
        assert out["code"] == 0
        out = sql(server, "INSERT INTO m VALUES ('a', 1000, 0.5)")
        assert out["output"][0]["affectedrows"] == 1
        out = sql(server, "SELECT * FROM m")
        rec = out["output"][0]["records"]
        assert [c["name"] for c in rec["schema"]["column_schemas"]] == \
            ["host", "ts", "cpu"]
        assert rec["rows"] == [["a", 1000, 0.5]]

    def test_sql_error(self, server):
        status, body = req(
            server, "/v1/sql", "POST",
            urllib.parse.urlencode({"sql": "SELECT * FROM missing"}).encode(),
            {"Content-Type": "application/x-www-form-urlencoded"})
        assert status == 400
        assert "not found" in json.loads(body)["error"]

    def test_get_with_query_param(self, server):
        status, body = req(server, "/v1/sql", params={"sql": "SELECT 1"})
        assert status == 200
        assert json.loads(body)["output"][0]["records"]["rows"] == [[1]]

    def test_health_status_metrics(self, server):
        assert req(server, "/health")[0] == 200
        status, body = req(server, "/status")
        assert json.loads(body)["version"]
        status, body = req(server, "/metrics")
        assert status == 200

    def test_status_shape(self, server):
        """/status reports uptime, region count, cache health and the
        latest ingest/scan profile summaries (ISSUE 2 satellite)."""
        sql(server, "CREATE TABLE st (host STRING, ts TIMESTAMP TIME "
                    "INDEX, v DOUBLE, PRIMARY KEY(host))")
        sql(server, "INSERT INTO st VALUES ('a', 1000, 1.0)")
        t = server.frontend.catalog.table("greptime", "public", "st")
        region = next(iter(t.regions.values()))
        region.bulk_ingest({"host": np.array(["b"], dtype=object),
                            "ts": np.array([2000], dtype=np.int64),
                            "v": np.array([2.0])})
        status, body = req(server, "/status")
        assert status == 200
        data = json.loads(body)
        # the node names the device it runs on and the WAL it writes
        # (memory stats ride along where the backend keeps them: not CPU)
        import jax
        assert data["device"] == {
            "platform": "cpu", "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices())}
        assert data["wal_backend"] in ("native", "python")
        for key in ("version", "uptime_s", "region_count",
                    "read_cache_hit_ratio", "scan_cache_resident_bytes",
                    "last_ingest_profile", "last_scan_profile"):
            assert key in data, f"/status missing {key}"
        assert data["uptime_s"] >= 0
        assert data["region_count"] >= 1
        # the bulk ingest above left a stage profile behind
        assert "rows" in data["last_ingest_profile"]
        # a scan leaves the scan twin behind
        t.flush()
        from greptimedb_tpu.query import stream_exec, tpu_exec
        old = stream_exec.stream_threshold_rows()
        old_floor = tpu_exec.TPU_DISPATCH_MIN_ROWS
        old_dt = tpu_exec._observed_min_dt[0]
        stream_exec.configure_streaming(threshold_rows=1)
        tpu_exec.TPU_DISPATCH_MIN_ROWS = 1
        tpu_exec._observed_min_dt[0] = None
        try:
            sql(server, "SELECT host, avg(v) FROM st GROUP BY host")
        finally:
            stream_exec.configure_streaming(threshold_rows=old)
            tpu_exec.TPU_DISPATCH_MIN_ROWS = old_floor
            tpu_exec._observed_min_dt[0] = old_dt
        status, body = req(server, "/status")
        data = json.loads(body)
        assert data["last_scan_profile"] is not None
        assert data["last_scan_profile"].startswith("streamed:")

    def test_runtime_metrics_matches_metrics_endpoint(self, server):
        """SELECT over information_schema.runtime_metrics returns the
        same counters /metrics exports, with the same values (ISSUE 2
        acceptance)."""
        sql(server, "CREATE TABLE rmm (host STRING, ts TIMESTAMP TIME "
                    "INDEX, v DOUBLE, PRIMARY KEY(host))")
        sql(server, "INSERT INTO rmm VALUES ('a', 1000, 1.0)")
        out = sql(server, "SELECT metric_name, value FROM "
                          "information_schema.runtime_metrics")
        table_vals = {}
        for name, value in out["output"][0]["records"]["rows"]:
            table_vals[name] = value
        assert "greptime_region_write_rows_total" in table_vals
        status, body = req(server, "/metrics")
        exported = {}
        for line in body.decode().splitlines():
            if line.startswith("#") or " " not in line:
                continue
            name, _, value = line.rpartition(" ")
            if "{" in name:
                name = name[:name.index("{")]
            try:
                exported.setdefault(name, float(value))
            except ValueError:
                continue
        # every label-free counter the endpoint exports is queryable
        # over SQL; values may drift between the two reads only for
        # metrics the comparison itself bumps, so check a quiet one
        assert "greptime_region_write_rows_total" in exported
        # the SELECT ran before /metrics: the write counter is stable
        # between the two reads (no writes in between)
        assert table_vals["greptime_region_write_rows_total"] == \
            exported["greptime_region_write_rows_total"]
        # and the table is a superset modulo the engine gauges
        missing = [n for n in exported
                   if n.startswith("greptime_") and n not in table_vals]
        assert not missing, f"runtime_metrics missing {missing[:5]}"

    def test_db_param(self, server):
        sql(server, "CREATE DATABASE db9")
        status, _ = req(
            server, "/v1/sql", "POST",
            urllib.parse.urlencode({
                "sql": "CREATE TABLE t (ts TIMESTAMP TIME INDEX, v DOUBLE)",
            }).encode(),
            {"Content-Type": "application/x-www-form-urlencoded"},
            params={"db": "db9"})
        assert status == 200
        out = sql(server, "SHOW TABLES FROM db9")
        names = [r[0] for r in out["output"][0]["records"]["rows"]]
        assert "t" in names


class TestLatencyHistograms:
    """ISSUE 6: log-bucketed latency histograms on /metrics (proper
    Prometheus histogram text format) and their p50/p95/p99 summaries in
    information_schema.runtime_metrics."""

    def _histogram_series(self, text, family):
        """{labelkey: [(le, count)...]}, plus _sum/_count presence."""
        import re
        buckets = {}
        saw_sum = saw_count = False
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            if line.startswith(f"{family}_sum"):
                saw_sum = True
            if line.startswith(f"{family}_count"):
                saw_count = True
            m = re.match(rf"{family}_bucket\{{(.*)\}} (\S+)", line)
            if not m:
                continue
            labels, value = m.group(1), float(m.group(2))
            le = re.search(r'le="([^"]+)"', labels).group(1)
            key = re.sub(r'le="[^"]+",?', "", labels).strip(",")
            buckets.setdefault(key, []).append((float(le), value))
        return buckets, saw_sum, saw_count

    def test_prometheus_text_format_compliance(self, server):
        """_bucket/_sum/_count with le labels; cumulative buckets are
        monotone non-decreasing and end at le=+Inf == _count."""
        sql(server, "SELECT 1")       # at least one stmt observation
        status, body = req(server, "/metrics")
        assert status == 200
        text = body.decode()
        family = "greptime_stmt_latency_seconds"
        assert f"# TYPE {family} histogram" in text
        buckets, saw_sum, saw_count = self._histogram_series(text, family)
        assert saw_sum and saw_count and buckets
        import re
        counts_by_labels = {}
        for line in text.splitlines():
            m = re.match(rf"{family}_count\{{(.*)\}} (\S+)", line)
            if m:
                counts_by_labels[m.group(1)] = float(m.group(2))
        for key, series in buckets.items():
            les = [le for le, _ in series]
            assert les == sorted(les)
            assert les[-1] == float("inf"), "le=+Inf bucket required"
            values = [v for _, v in series]
            assert values == sorted(values), \
                f"buckets must be cumulative monotone: {series}"
            assert values[-1] == counts_by_labels[key], \
                "+Inf bucket must equal _count"

    def test_log_bucket_layout(self, server):
        """The primitive is log-bucketed: consecutive finite bounds keep
        a constant ratio (×2), not the prometheus linear default."""
        sql(server, "SELECT 1")
        _, body = req(server, "/metrics")
        buckets, _, _ = self._histogram_series(
            body.decode(), "greptime_stmt_latency_seconds")
        series = next(iter(buckets.values()))
        finite = [le for le, _ in series if le != float("inf")]
        ratios = {round(b / a, 6) for a, b in zip(finite, finite[1:])}
        assert ratios == {2.0}, finite

    def test_runtime_metrics_serves_quantiles(self, server):
        sql(server, "SELECT 1")
        out = sql(server,
                  "SELECT metric_name, value, kind FROM "
                  "information_schema.runtime_metrics WHERE metric_name "
                  "LIKE 'greptime_stmt_latency_seconds_p%'")
        rows = out["output"][0]["records"]["rows"]
        names = {r[0] for r in rows}
        assert {"greptime_stmt_latency_seconds_p50",
                "greptime_stmt_latency_seconds_p95",
                "greptime_stmt_latency_seconds_p99"} <= names
        for name, value, kind in rows:
            assert kind == "summary"
            assert 0.0 <= value < 60.0

    def test_http_route_latency_recorded(self, server):
        sql(server, "SELECT 1")
        _, body = req(server, "/metrics")
        text = body.decode()
        assert "greptime_http_request_seconds_bucket" in text
        assert 'route="/v1/sql"' in text


def samples(server):
    """/metrics -> {sample name with labels: value}."""
    out = {}
    for line in req(server, "/metrics")[1].decode().splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


class TestRequestPhases:
    """ISSUE 39: what a request does outside its statement's rows. The
    HTTP server's hand-offs as `greptime_http_phase_seconds{route,
    phase}`, the event loop's lag, a timer's thread CPU seconds."""

    PHASES = ("read", "queue", "resume", "write")

    @staticmethod
    def phase(route, phase, what="count"):
        return (f'greptime_http_phase_seconds_{what}'
                f'{{phase="{phase}",route="{route}"}}')

    @staticmethod
    def send(server, route):
        if route == "/v1/sql":
            sql(server, "SELECT 1")
        else:
            assert req(server, route, "POST", b"phases,host=a v=1 1000",
                       params={"precision": "ms"})[0] == 204

    @pytest.mark.parametrize("phase", PHASES)
    @pytest.mark.parametrize("route", ["/v1/sql", "/v1/influxdb/write"])
    def test_every_request_is_observed_in_each_phase(self, server, route,
                                                     phase):
        self.send(server, route)            # the series exist from here
        before = samples(server)
        for _ in range(3):
            self.send(server, route)
        after = samples(server)
        name = self.phase(route, phase)
        assert after[name] - before[name] == 3
        total = self.phase(route, phase, "sum")
        assert 0.0 < after[total] - before[total] < 30.0
        assert self.phase(route, phase, 'bucket').replace(
            '{', '{le="+Inf",') in after

    @pytest.mark.parametrize("route", ["/v1/sql", "/v1/influxdb/write"])
    def test_the_request_histogram_counts_as_before(self, server, route):
        """One observation a request, from the middleware's entry to the
        handler's return; the phases lie inside it but for `write`."""
        self.send(server, route)
        before = samples(server)
        for _ in range(3):
            self.send(server, route)
        after = samples(server)

        def delta(name):
            return after[name] - before[name]
        count = f'greptime_http_request_seconds_count{{route="{route}"}}'
        total = f'greptime_http_request_seconds_sum{{route="{route}"}}'
        assert delta(count) == 3
        inside = sum(delta(self.phase(route, p, "sum"))
                     for p in ("read", "queue", "resume"))
        assert inside < delta(total)

    def test_a_handler_that_stays_on_the_loop_has_no_handoff(self, server):
        req(server, "/health")
        before = samples(server)
        req(server, "/health")
        after = samples(server)
        for phase in ("read", "write"):
            name = self.phase("/health", phase)
            assert after[name] - before[name] == 1
        assert self.phase("/health", "queue") not in after
        assert self.phase("/health", "resume") not in after

    def test_an_error_response_is_written_under_the_write_phase(self,
                                                                 server):
        def bad():
            status, body = req(server, "/v1/sql", "POST",
                               b"sql=SELECT+*+FROM+no_such_table",
                               {"Content-Type":
                                "application/x-www-form-urlencoded"})
            assert status == 400 and json.loads(body)["code"] != 0
        bad()
        before = samples(server)
        bad()
        after = samples(server)
        for phase in self.PHASES:
            name = self.phase("/v1/sql", phase)
            assert after[name] - before[name] == 1, phase

    def test_an_unrouted_path_is_no_series(self, server):
        assert req(server, "/no/such/path")[0] == 404
        assert not [n for n in samples(server) if "/no/such/path" in n]

    def test_the_event_loops_lag_is_read_ten_times_a_second(self, server):
        import time
        before = samples(server).get(
            "greptime_event_loop_lag_seconds_count", 0.0)
        time.sleep(0.55)
        after = samples(server)
        ticks = after["greptime_event_loop_lag_seconds_count"] - before
        # other servers of this process tick into the same series
        assert ticks >= 3
        assert after["greptime_event_loop_lag_seconds_sum"] >= 0.0

    def test_a_timer_counts_its_threads_cpu_seconds(self, server):
        self.send(server, "/v1/influxdb/write")
        got = samples(server)
        for timer in ("region_write", "ingest_parse", "wal_append"):
            cpu = got[f"greptime_{timer}_cpu_seconds_total"]
            wall = got[f"greptime_{timer}_seconds_sum"]
            assert 0.0 <= cpu <= wall + 0.005 * got[
                f"greptime_{timer}_seconds_count"], timer

    def test_the_cpu_counter_tells_work_from_waiting(self):
        import time
        from prometheus_client import REGISTRY
        from greptimedb_tpu.common.telemetry import timer

        def cpu(name):
            return REGISTRY.get_sample_value(
                f"greptime_{name}_cpu_seconds_total") or 0.0
        with timer("phases_test_sleep"):
            time.sleep(0.05)
        with timer("phases_test_burn"):
            end = time.perf_counter() + 0.05
            while time.perf_counter() < end:
                pass
        assert cpu("phases_test_sleep") < 0.01
        assert 0.015 <= cpu("phases_test_burn") <= 0.06


class TestTraceparentHeader:
    def test_sql_joins_external_trace(self, server, caplog):
        """A client-supplied W3C traceparent header threads through the
        executor: the slow-query log reports the client's trace id."""
        import logging
        from greptimedb_tpu.common.telemetry import (
            set_slow_query_threshold_ms)
        trace = "beadfeedbeadfeedbeadfeedbeadfeed"
        set_slow_query_threshold_ms(1)
        try:
            with caplog.at_level(logging.WARNING,
                                 logger="greptimedb_tpu.slow_query"):
                status, _ = req(
                    server, "/v1/sql", "POST",
                    urllib.parse.urlencode(
                        {"sql": "SELECT count(*) AS c FROM numbers a "
                                "CROSS JOIN numbers b"}).encode(),
                    {"Content-Type": "application/x-www-form-urlencoded",
                     "traceparent":
                         f"00-{trace}-00f067aa0ba902b7-01"})
        finally:
            set_slow_query_threshold_ms(None)
        assert status == 200
        slow = [r.getMessage() for r in caplog.records
                if "slow query" in r.getMessage()]
        assert slow and f"trace={trace}" in slow[-1]

    def test_malformed_traceparent_ignored(self, server):
        status, _ = req(
            server, "/v1/sql", "POST",
            urllib.parse.urlencode({"sql": "SELECT 1"}).encode(),
            {"Content-Type": "application/x-www-form-urlencoded",
             "traceparent": "garbage-header"})
        assert status == 200


class TestInfluxIngest:
    def test_line_protocol_write(self, server):
        body = (b"weather,location=us-midwest temperature=82.5 "
                b"1465839830100400200\n"
                b"weather,location=us-east temperature=75,humidity=32i "
                b"1465839830100400200")
        status, _ = req(server, "/v1/influxdb/write", "POST", body)
        assert status == 204
        out = sql(server, "SELECT location, temperature, humidity FROM "
                          "weather ORDER BY location")
        rows = out["output"][0]["records"]["rows"]
        assert rows == [["us-east", 75.0, 32], ["us-midwest", 82.5, None]]

    def test_precision(self, server):
        status, _ = req(server, "/v1/influxdb/write", "POST",
                        b"m1 v=1 1700000000", params={"precision": "s"})
        assert status == 204
        out = sql(server, "SELECT greptime_timestamp FROM m1")
        assert out["output"][0]["records"]["rows"][0][0] == 1700000000000


class TestOpenTsdb:
    def test_http_put(self, server):
        body = json.dumps([
            {"metric": "sys.cpu", "timestamp": 1700000000, "value": 18.0,
             "tags": {"host": "web01"}},
            {"metric": "sys.cpu", "timestamp": 1700000001, "value": 19.5,
             "tags": {"host": "web02"}},
        ]).encode()
        status, _ = req(server, "/v1/opentsdb/api/put", "POST", body,
                        {"Content-Type": "application/json"})
        assert status == 200
        out = sql(server, 'SELECT host, greptime_value FROM "sys.cpu" '
                          "ORDER BY host")
        assert out["output"][0]["records"]["rows"] == [
            ["web01", 18.0], ["web02", 19.5]]


class TestOpenTsdbTelnet:
    def test_telnet_put_over_raw_tcp(self, server):
        """The reference serves telnet `put` on its own TCP port
        (src/servers/src/opentsdb.rs:60-120); datapoints land in the
        metric's table, errors answer as text lines."""
        import socket

        from greptimedb_tpu.servers.opentsdb import OpentsdbServer
        tsdb = OpentsdbServer(server.frontend, host="127.0.0.1", port=0)
        tsdb.start()
        try:
            with socket.create_connection(("127.0.0.1", tsdb.port),
                                          timeout=10) as s:
                f = s.makefile("rwb")
                f.write(b"put tsd.cpu 1700000000 41.5 host=web01 dc=east\n"
                        b"put tsd.cpu 1700000001 43.0 host=web02 dc=west\n")
                f.flush()
                # version answers a line; also proves the puts were read
                f.write(b"version\n")
                f.flush()
                assert b"net.opentsdb" in f.readline()
                # a bad line answers an error line
                f.write(b"put tsd.cpu not_a_ts 1.0 host=a\n")
                f.flush()
                assert f.readline().startswith(b"error:")
                f.write(b"exit\n")
                f.flush()
            # telnet puts are synchronous per line: rows are queryable
            out = sql(server, 'SELECT host, dc, greptime_value FROM '
                              '"tsd.cpu" ORDER BY host')
            assert out["output"][0]["records"]["rows"] == [
                ["web01", "east", 41.5], ["web02", "west", 43.0]]
        finally:
            tsdb.shutdown()


class TestPrometheusRemote:
    def test_write_then_read(self, server):
        series = [
            prom.TimeSeries(
                labels={"__name__": "up", "job": "api", "instance": "i1"},
                samples=[(1.0, 1000), (0.0, 2000)]),
            prom.TimeSeries(
                labels={"__name__": "up", "job": "api", "instance": "i2"},
                samples=[(1.0, 1500)]),
        ]
        body = prom.encode_write_request(series)
        status, _ = req(server, "/v1/prometheus/write", "POST", body)
        assert status == 204
        out = sql(server, "SELECT instance, job, greptime_value FROM up "
                          "ORDER BY greptime_timestamp")
        assert out["output"][0]["records"]["rows"] == [
            ["i1", "api", 1.0], ["i2", "api", 1.0], ["i1", "api", 0.0]]

        # remote read round trip
        read_q = (prom.pw.field_bytes(1, (
            prom.pw.field_varint(1, 0) + prom.pw.field_varint(2, 5000) +
            prom.pw.field_bytes(3, (
                prom.pw.field_varint(1, prom.MATCH_EQ) +
                prom.pw.field_bytes(2, b"__name__") +
                prom.pw.field_bytes(3, b"up"))))))
        status, body = req(server, "/v1/prometheus/read", "POST",
                           snappy.compress(bytes(read_q)))
        assert status == 200
        decoded = snappy.decompress(body)
        text = decoded.decode("latin1")
        assert "job" in text and "api" in text and "instance" in text

    def test_prom_metadata_endpoints(self, server):
        series = [prom.TimeSeries(
            labels={"__name__": "cpu_seconds", "host": "a"},
            samples=[(0.5, 1000)])]
        req(server, "/v1/prometheus/write", "POST",
            prom.encode_write_request(series))
        status, body = req(server, "/api/v1/labels")
        data = json.loads(body)["data"]
        assert "host" in data and "__name__" in data
        status, body = req(server, "/api/v1/label/host/values")
        assert json.loads(body)["data"] == ["a"]
        status, body = req(server, "/api/v1/series",
                           params={"match[]": "cpu_seconds"})
        assert json.loads(body)["data"] == [
            {"__name__": "cpu_seconds", "host": "a"}]


class TestAuth:
    def test_basic_auth_required(self, tmp_path):
        dn = DatanodeInstance(DatanodeOptions(data_home=str(tmp_path)))
        fe = FrontendInstance(dn)
        fe.start()
        provider = StaticUserProvider({"admin": "pwd123"})
        srv = HttpServer(fe, provider, addr="127.0.0.1:0")
        srv.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as err:
                req(srv, "/v1/sql", params={"sql": "SELECT 1"})
            assert err.value.code == 401
            import base64
            token = base64.b64encode(b"admin:pwd123").decode()
            status, body = req(srv, "/v1/sql", params={"sql": "SELECT 1"},
                               headers={"Authorization": f"Basic {token}"})
            assert status == 200
            with pytest.raises(urllib.error.HTTPError) as err:
                bad = base64.b64encode(b"admin:nope").decode()
                req(srv, "/v1/sql", params={"sql": "SELECT 1"},
                    headers={"Authorization": f"Basic {bad}"})
            assert err.value.code == 401
        finally:
            srv.shutdown()
            fe.shutdown()


class TestCli:
    def test_load_options_from_toml_and_flags(self, tmp_path):
        from greptimedb_tpu.cmd.main import load_options
        cfg = tmp_path / "config.toml"
        cfg.write_text("""
[storage]
data_home = "/tmp/x"
[http]
addr = "0.0.0.0:9999"
[mysql]
enable = false
""")
        import argparse
        args = argparse.Namespace(config_file=str(cfg),
                                  data_home=None, http_addr=None,
                                  mysql_addr="127.0.0.1:1234",
                                  postgres_addr=None, grpc_addr=None,
                                  user_provider=None)
        opts = load_options(args)
        assert opts.data_home == "/tmp/x"
        assert opts.http_addr == "0.0.0.0:9999"
        assert opts.mysql_addr == "127.0.0.1:1234"
        assert opts.enable_mysql is False


class TestPromApiQuery:
    """/api/v1/query{,_range} + /v1/promql end-to-end (reference:
    src/servers/src/prom.rs:70-95 — the round-1 gap where routes crashed)."""

    def _seed(self, server):
        sql(server, "CREATE TABLE qcpu (host STRING, ts TIMESTAMP TIME "
                    "INDEX, val DOUBLE, PRIMARY KEY(host))")
        rows = ",".join(
            f"('h{j}', {i * 10_000}, {float(i * (j + 1))})"
            for i in range(30) for j in range(2))
        sql(server, f"INSERT INTO qcpu VALUES {rows}")

    def test_query_range(self, server):
        self._seed(server)
        status, body = req(server, "/api/v1/query_range", params={
            "query": "rate(qcpu[1m])", "start": "120", "end": "240",
            "step": "60"})
        assert status == 200, body
        data = json.loads(body)
        assert data["status"] == "success"
        res = data["data"]
        assert res["resultType"] == "matrix"
        by_host = {r["metric"]["host"]: r for r in res["result"]}
        for _, v in by_host["h0"]["values"]:
            assert abs(float(v) - 0.1) < 1e-9
        for _, v in by_host["h1"]["values"]:
            assert abs(float(v) - 0.2) < 1e-9

    def test_instant_query(self, server):
        self._seed(server)
        status, body = req(server, "/api/v1/query", params={
            "query": "sum(qcpu)", "time": "100"})
        assert status == 200, body
        data = json.loads(body)
        res = data["data"]
        assert res["resultType"] == "vector"
        assert float(res["result"][0]["value"][1]) == 30.0

    def test_query_error_shape(self, server):
        status, body = req(server, "/api/v1/query", params={
            "query": "rate(", "time": "100"}, raise_on_error=False)
        assert status == 422
        data = json.loads(body)
        assert data["status"] == "error"

    def test_v1_promql(self, server):
        self._seed(server)
        status, body = req(server, "/v1/promql", params={
            "query": "qcpu", "start": "100", "end": "100", "step": "10s"})
        assert status == 200, body

    def test_series_endpoint_still_works(self, server):
        self._seed(server)
        status, body = req(server, "/api/v1/series",
                           params={"match[]": "qcpu"})
        assert status == 200
        data = json.loads(body)
        hosts = {e.get("host") for e in data["data"]}
        assert hosts == {"h0", "h1"}

    def test_query_range_explain_param(self, server):
        """?explain=1 returns the plan/dispatch lines instead of data —
        the HTTP twin of TQL EXPLAIN (ISSUE 16)."""
        self._seed(server)
        status, body = req(server, "/api/v1/query_range", params={
            "query": "sum by (host) (rate(qcpu[1m]))", "start": "0",
            "end": "240", "step": "60", "explain": "1"})
        assert status == 200, body
        data = json.loads(body)
        assert data["status"] == "success"
        assert data["data"]["resultType"] == "explain"
        joined = "\n".join(data["data"]["result"])
        assert "PromSeriesScan: qcpu" in joined
        assert "Dispatch:" in joined


class TestAdminCompact:
    def test_flush_then_compact_endpoint(self, server):
        sql(server, "CREATE TABLE ac (host STRING, ts TIMESTAMP TIME INDEX,"
                    " cpu DOUBLE, PRIMARY KEY(host))")
        for gen in range(2):
            sql(server, f"INSERT INTO ac VALUES ('a', 1, {gen}.0)")
            req(server, "/v1/admin/flush?table=ac", "POST", b"")
        status, body = req(server, "/v1/admin/compact?table=ac", "POST", b"")
        assert status == 200
        t = server.frontend.catalog.table("greptime", "public", "ac")
        region = next(iter(t.regions.values()))
        assert len(region.version_control.current.ssts.levels[1]) == 1
        out = sql(server, "SELECT cpu FROM ac")
        assert out["output"][0]["records"]["rows"] == [[1.0]]


class TestAdminDownsample:
    def test_downsample_endpoint(self, server):
        sql(server, "CREATE TABLE ds_raw (host STRING, ts TIMESTAMP TIME"
                    " INDEX, v DOUBLE, PRIMARY KEY(host))")
        sql(server, "CREATE TABLE ds_agg (host STRING, ts TIMESTAMP TIME"
                    " INDEX, v DOUBLE, PRIMARY KEY(host))")
        rows = ",".join(f"('h{i % 2}', {i * 1000}, {float(i)})"
                        for i in range(240))
        sql(server, f"INSERT INTO ds_raw VALUES {rows}")
        status, body = req(server,
                           "/v1/admin/downsample?src=ds_raw&dst=ds_agg"
                           "&stride=60s&agg=avg", "POST", b"")
        assert status == 200, body
        data = json.loads(body)
        assert data["code"] == 0
        assert data["rows_written"] == 8      # 2 hosts x 4 minutes
        out = sql(server, "SELECT count(*) FROM ds_agg")
        assert out["output"][0]["records"]["rows"][0][0] == 8
        out = sql(server, "SELECT v FROM ds_agg WHERE host = 'h0'"
                          " ORDER BY ts LIMIT 1")
        # first minute of h0: even i in [0, 60) -> mean 29
        assert out["output"][0]["records"]["rows"][0][0] == 29.0

    def test_downsample_bad_args(self, server):
        status, body = req(server,
                           "/v1/admin/downsample?src=nope&dst=nope"
                           "&stride=60s", "POST", b"")
        assert status == 404
        status, body = req(server, "/v1/admin/downsample?src=a",
                           "POST", b"")
        assert status == 400
