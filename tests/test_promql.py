"""PromQL parser + engine tests.

Mirrors the reference's test strategy: parser shape tests (the promql-parser
crate's grammar), extrapolated rate/increase golden semantics
(src/promql/src/functions/extrapolate_rate.rs tests), planner behaviors
(src/promql/src/planner.rs:1229-1953 golden plans — here asserted on
results), and Prometheus JSON shaping (src/servers/src/prom.rs:150-400).
"""

import json
import math

import numpy as np
import pytest

from greptimedb_tpu.datanode import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend import FrontendInstance
from greptimedb_tpu.promql import PromqlEngine, PromqlParseError, parse_promql
from greptimedb_tpu.promql.ast import (
    Aggregate, Binary, Call, NumberLiteral, SubqueryExpr, VectorSelector)
from greptimedb_tpu.promql.parser import parse_duration_ms
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.sql import parse_sql
from greptimedb_tpu.sql.ast import Tql


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

class TestParser:
    def test_durations(self):
        assert parse_duration_ms("5m") == 300_000
        assert parse_duration_ms("1h30m") == 5_400_000
        assert parse_duration_ms("1.5h") == 5_400_000
        assert parse_duration_ms("10ms") == 10
        assert parse_duration_ms("1y") == 31_536_000_000
        with pytest.raises(PromqlParseError):
            parse_duration_ms("5")
        with pytest.raises(PromqlParseError):
            parse_duration_ms("m")

    def test_selector(self):
        e = parse_promql('cpu{host="a", region=~"us-.*", az!~"z", x!="y"}')
        assert isinstance(e, VectorSelector)
        assert e.metric == "cpu"
        assert [(m.name, m.op, m.value) for m in e.matchers] == [
            ("host", "=", "a"), ("region", "=~", "us-.*"),
            ("az", "!~", "z"), ("x", "!=", "y")]

    def test_matrix_selector_offset(self):
        e = parse_promql("cpu[5m] offset 1m")
        assert e.range_ms == 300_000 and e.offset_ms == 60_000
        e = parse_promql("cpu offset -30s")
        assert e.offset_ms == -30_000

    def test_at_modifier(self):
        e = parse_promql("cpu @ 1609746180")
        assert e.at_ms == 1_609_746_180_000
        assert parse_promql("cpu @ start()").at_ms == "start"
        assert parse_promql("cpu @ end()").at_ms == "end"

    def test_name_matcher_selector(self):
        e = parse_promql('{__name__="cpu", host="a"}')
        assert e.metric == "cpu"

    def test_precedence(self):
        e = parse_promql("1 + 2 * 3")
        assert isinstance(e, Binary) and e.op == "+"
        assert isinstance(e.rhs, Binary) and e.rhs.op == "*"
        # ^ is right-associative and binds tighter than unary minus
        e = parse_promql("2 ^ 3 ^ 2")
        assert e.op == "^" and isinstance(e.rhs, Binary)
        e = parse_promql("a + b or c")
        assert e.op == "or" and e.lhs.op == "+"

    def test_aggregate_forms(self):
        for q in ["sum by (host) (cpu)", "sum(cpu) by (host)"]:
            e = parse_promql(q)
            assert isinstance(e, Aggregate) and e.by == ["host"]
        e = parse_promql("sum without (host, az) (cpu)")
        assert e.without == ["host", "az"]
        e = parse_promql("topk(5, cpu)")
        assert isinstance(e.param, NumberLiteral) and e.param.value == 5
        e = parse_promql("quantile(0.9, cpu)")
        assert e.param.value == 0.9

    def test_binary_modifiers(self):
        e = parse_promql("a / on(host) group_left(extra) b")
        assert e.matching.on == ["host"] and e.matching.group_left
        assert e.matching.include == ["extra"]
        e = parse_promql("a > bool b")
        assert e.return_bool
        e = parse_promql("a and ignoring(x) b")
        assert e.matching.ignoring == ["x"]

    def test_subquery(self):
        e = parse_promql("rate(cpu[5m])[30m:1m]")
        assert isinstance(e, SubqueryExpr)
        assert e.range_ms == 1_800_000 and e.step_ms == 60_000

    def test_literals(self):
        assert parse_promql("0x1F").value == 31.0
        assert parse_promql("1e3").value == 1000.0
        assert parse_promql("-2.5").value == -2.5
        assert math.isinf(parse_promql("Inf").value)
        assert math.isnan(parse_promql("NaN").value)

    def test_errors(self):
        for q in ["", "cpu{", "rate(cpu[5m)", "sum by host (cpu)",
                  "cpu[5]", "1 +", "{}"]:
            with pytest.raises(PromqlParseError):
                parse_promql(q)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@pytest.fixture()
def fe(tmp_path):
    inst = FrontendInstance(
        DatanodeInstance(DatanodeOptions(data_home=str(tmp_path))))
    inst.start()
    yield inst
    inst.shutdown()


def _mk_cpu(fe, counter=True):
    fe.do_query("CREATE TABLE cpu (host STRING, ts TIMESTAMP TIME INDEX, "
                "val DOUBLE, PRIMARY KEY(host))")
    rows = []
    for i in range(60):                 # samples every 10s for 10 min
        rows.append(f"('a', {i * 10_000}, {i * 2.0})")
        rows.append(f"('b', {i * 10_000}, {i * 5.0})")
    fe.do_query("INSERT INTO cpu VALUES " + ",".join(rows))


def _q(fe, promql, start, end, step, instant=False):
    eng = fe.promql_engine()
    return eng.query_to_prom_json(promql, start, end, step, QueryContext(),
                                  instant=instant)


def _series(result, **labels):
    for r in result["result"]:
        if all(r["metric"].get(k) == v for k, v in labels.items()):
            return r
    raise AssertionError(f"series {labels} not in {result['result']}")


class TestEngine:
    def test_instant_vector_lookback(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "cpu", 100_000, 100_000, 1000, instant=True)
        assert out["resultType"] == "vector"
        a = _series(out, host="a")
        assert a["metric"]["__name__"] == "cpu"
        assert a["value"] == [100.0, "20"]
        # beyond the 5m lookback: empty
        out = _q(fe, "cpu", 1_000_000, 1_000_000, 1000, instant=True)
        assert out["result"] == []

    def test_rate_counter(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "rate(cpu[1m])", 300_000, 480_000, 60_000)
        a = _series(out, host="a")
        assert "__name__" not in a["metric"]
        for _, v in a["values"]:
            assert abs(float(v) - 0.2) < 1e-9
        b = _series(out, host="b")
        for _, v in b["values"]:
            assert abs(float(v) - 0.5) < 1e-9

    def test_increase_with_reset(self, fe):
        fe.do_query("CREATE TABLE c2 (ts TIMESTAMP TIME INDEX, val DOUBLE)")
        # counter resets at t=40s: 0,10,20,30,5,15,25 (10s apart)
        vals = [0, 10, 20, 30, 5, 15, 25]
        rows = ",".join(f"({i * 10_000}, {v})" for i, v in enumerate(vals))
        fe.do_query(f"INSERT INTO c2 VALUES {rows}")
        out = _q(fe, "increase(c2[1m])", 60_000, 60_000, 1000, instant=True)
        # window (0,60] holds samples 10..60s (6 samples), reset-adjusted
        # values 10,20,30,35,45,55: raw delta 45 over 50s sampled;
        # extrapolation adds dur_to_start=10s (within the 11s threshold,
        # not zero-capped: dur_to_zero = 50*10/45 = 11.1s) and
        # dur_to_end=0 → 45 * (50+10+0)/50 = 54
        v = float(out["result"][0]["value"][1])
        assert abs(v - 54.0) < 1e-6

    def test_avg_over_time(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "avg_over_time(cpu[1m])", 60_000, 60_000, 1000,
                 instant=True)
        # window (0,60]: host a samples at 10..60s → values 2,4,..,12 avg=7
        a = _series(out, host="a")
        assert abs(float(a["value"][1]) - 7.0) < 1e-9

    def test_min_max_quantile_over_time(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "max_over_time(cpu[1m])", 60_000, 60_000, 1000,
                 instant=True)
        assert float(_series(out, host="b")["value"][1]) == 30.0
        out = _q(fe, "min_over_time(cpu[1m])", 60_000, 60_000, 1000,
                 instant=True)
        assert float(_series(out, host="b")["value"][1]) == 5.0
        out = _q(fe, "quantile_over_time(0.5, cpu[1m])", 60_000, 60_000,
                 1000, instant=True)
        assert float(_series(out, host="a")["value"][1]) == 7.0

    def test_sum_aggregate(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "sum(rate(cpu[1m]))", 300_000, 300_000, 1000,
                 instant=True)
        assert len(out["result"]) == 1
        assert out["result"][0]["metric"] == {}
        assert abs(float(out["result"][0]["value"][1]) - 0.7) < 1e-9

    def test_aggregate_by(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "sum by (host) (cpu)", 100_000, 100_000, 1000,
                 instant=True)
        assert len(out["result"]) == 2
        assert float(_series(out, host="a")["value"][1]) == 20.0

    def test_topk(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "topk(1, cpu)", 100_000, 100_000, 1000, instant=True)
        assert len(out["result"]) == 1
        assert out["result"][0]["metric"]["host"] == "b"

    def test_vector_scalar(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "cpu * 2", 100_000, 100_000, 1000, instant=True)
        assert float(_series(out, host="a")["value"][1]) == 40.0
        # filter comparison
        out = _q(fe, "cpu > 30", 100_000, 100_000, 1000, instant=True)
        assert len(out["result"]) == 1
        assert out["result"][0]["metric"]["host"] == "b"
        # bool comparison
        out = _q(fe, "cpu > bool 30", 100_000, 100_000, 1000, instant=True)
        vals = {r["metric"]["host"]: r["value"][1] for r in out["result"]}
        assert vals == {"a": "0", "b": "1"}

    def test_vector_vector_matching(self, fe):
        _mk_cpu(fe)
        fe.do_query("CREATE TABLE lim (host STRING, ts TIMESTAMP TIME INDEX,"
                    " val DOUBLE, PRIMARY KEY(host))")
        fe.do_query("INSERT INTO lim VALUES ('a', 0, 10.0), ('b', 0, 100.0)")
        out = _q(fe, "cpu / lim", 100_000, 100_000, 1000, instant=True)
        vals = {r["metric"]["host"]: float(r["value"][1])
                for r in out["result"]}
        assert vals == {"a": 2.0, "b": 0.5}

    def test_set_ops(self, fe):
        _mk_cpu(fe)
        out = _q(fe, 'cpu and cpu{host="a"}', 100_000, 100_000, 1000,
                 instant=True)
        assert len(out["result"]) == 1
        out = _q(fe, 'cpu unless cpu{host="a"}', 100_000, 100_000, 1000,
                 instant=True)
        assert out["result"][0]["metric"]["host"] == "b"
        out = _q(fe, 'cpu{host="a"} or cpu', 100_000, 100_000, 1000,
                 instant=True)
        assert len(out["result"]) == 2

    def test_scalar_and_functions(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "42", 100_000, 100_000, 1000, instant=True)
        assert out["resultType"] == "scalar" and out["result"][1] == "42"
        out = _q(fe, "3 * scalar(cpu{host=\"a\"})", 100_000, 100_000,
                 1000, instant=True)
        assert out["result"][1] == "60"
        out = _q(fe, "abs(0 - cpu)", 100_000, 100_000, 1000, instant=True)
        assert float(_series(out, host="a")["value"][1]) == 20.0
        out = _q(fe, "clamp_max(cpu, 25)", 100_000, 100_000, 1000,
                 instant=True)
        assert float(_series(out, host="b")["value"][1]) == 25.0

    def test_absent(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "absent(nosuch)", 100_000, 100_000, 1000, instant=True)
        assert out["result"][0]["value"][1] == "1"
        out = _q(fe, "absent(cpu)", 100_000, 100_000, 1000, instant=True)
        assert out["result"] == []

    def test_histogram_quantile(self, fe):
        fe.do_query("CREATE TABLE hist (le STRING, ts TIMESTAMP TIME INDEX,"
                    " val DOUBLE, PRIMARY KEY(le))")
        # cumulative buckets: 0.1→10, 0.5→60, +Inf→100
        fe.do_query("INSERT INTO hist VALUES ('0.1', 0, 10), "
                    "('0.5', 0, 60), ('+Inf', 0, 100)")
        out = _q(fe, "histogram_quantile(0.5, hist)", 1000, 1000, 1000,
                 instant=True)
        v = float(out["result"][0]["value"][1])
        # rank 50 lands in (0.1, 0.5]: 0.1 + 0.4*(50-10)/(60-10) = 0.42
        assert abs(v - 0.42) < 1e-9

    def test_label_replace(self, fe):
        _mk_cpu(fe)
        out = _q(fe, 'label_replace(cpu, "h2", "$1-x", "host", "(.*)")',
                 100_000, 100_000, 1000, instant=True)
        assert _series(out, host="a")["metric"]["h2"] == "a-x"

    def test_offset(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "cpu offset 1m", 160_000, 160_000, 1000, instant=True)
        # value at 100s (160 - 60)
        assert float(_series(out, host="a")["value"][1]) == 20.0

    def test_range_query_json_shape(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "cpu", 0, 120_000, 60_000)
        assert out["resultType"] == "matrix"
        a = _series(out, host="a")
        assert a["values"][0][0] == 0.0
        assert len(a["values"]) == 3

    def test_raw_matrix_instant(self, fe):
        _mk_cpu(fe)
        out = _q(fe, "cpu[30s]", 60_000, 60_000, 1000, instant=True)
        assert out["resultType"] == "matrix"
        a = _series(out, host="a")
        assert [v for _, v in a["values"]] == ["8", "10", "12"]


class TestExtrapolationGolden:
    """Extrapolated-rate semantics (reference:
    src/promql/src/functions/extrapolate_rate.rs, prometheus
    extrapolatedRate). The reference's unit tests feed hand-built 2-sample
    windows straight into the UDF; through a real aligned-grid query the
    same counter (value t at ts=t ms, 1..9) gives these hand-derived
    goldens for increase(g[5ms]) at steps 2..9:

    - t=2: window (-3,2] = samples {1,2}: raw=1, sampled=1, avg_dur=1,
      threshold=1.1; dur_to_start=4 but zero-capped to sampled*first/raw=1
      (<1.1 → take it), dur_to_end=0 → factor (1+1+0)/1 = 2 → 2.0
    - t=3: samples {1..3}: raw=2, sampled=2, zero-cap 2*1/2=1 → factor
      (2+1)/2 = 1.5 → 3.0; t=4 → 4/3 factor → 4.0; t=5 → 5/4 → 5.0
    - t≥6: 5-sample windows with dur_to_start=1 (<1.1): factor 5/4 → 5.0
    """

    def test_increase_normal_input(self, fe):
        fe.do_query("CREATE TABLE g (ts TIMESTAMP TIME INDEX, val DOUBLE)")
        rows = ",".join(f"({t}, {float(t)})" for t in range(1, 10))
        fe.do_query(f"INSERT INTO g VALUES {rows}")
        eng = fe.promql_engine()
        val, steps = eng.query_range("increase(g[5ms])", 2, 9, 1,
                                     QueryContext())
        got = [round(float(v), 6) for v in val.values[0]]
        assert list(steps) == list(range(2, 10))
        assert got == [2.0, 3.0, 4.0, 5.0, 5.0, 5.0, 5.0, 5.0]

    def test_increase_counter_reset(self, fe):
        fe.do_query("CREATE TABLE g2 (ts TIMESTAMP TIME INDEX, val DOUBLE)")
        # reference increase_counter_reset: this series must behave exactly
        # like the uninterrupted 1..9 counter after reset adjustment
        vals = [1.0, 2.0, 3.0, 4.0, 1.0, 2.0, 3.0, 4.0, 5.0]
        rows = ",".join(f"({t + 1}, {v})" for t, v in enumerate(vals))
        fe.do_query(f"INSERT INTO g2 VALUES {rows}")
        eng = fe.promql_engine()
        val, _ = eng.query_range("increase(g2[5ms])", 2, 9, 1,
                                 QueryContext())
        got = [round(float(v), 6) for v in val.values[0]]
        assert got == [2.0, 3.0, 4.0, 5.0, 5.0, 5.0, 5.0, 5.0]

    def test_rate_is_increase_per_second(self, fe):
        fe.do_query("CREATE TABLE g3 (ts TIMESTAMP TIME INDEX, val DOUBLE)")
        rows = ",".join(f"({t * 1000}, {float(t)})" for t in range(10))
        fe.do_query(f"INSERT INTO g3 VALUES {rows}")
        eng = fe.promql_engine()
        inc, _ = eng.query_range("increase(g3[5s])", 9000, 9000, 1000,
                                 QueryContext())
        rate, _ = eng.query_range("rate(g3[5s])", 9000, 9000, 1000,
                                  QueryContext())
        assert abs(float(inc.values[0][0]) -
                   5.0 * float(rate.values[0][0])) < 1e-9

    def test_delta_gauge(self, fe):
        fe.do_query("CREATE TABLE g4 (ts TIMESTAMP TIME INDEX, val DOUBLE)")
        # gauge going down — delta must not apply counter correction
        rows = ",".join(f"({t * 1000}, {10.0 - t})" for t in range(6))
        fe.do_query(f"INSERT INTO g4 VALUES {rows}")
        eng = fe.promql_engine()
        val, _ = eng.query_range("delta(g4[5s])", 5000, 5000, 1000,
                                 QueryContext())
        assert float(val.values[0][0]) == -5.0


class TestTql:
    def test_tql_eval_via_sql(self, fe):
        _mk_cpu(fe)
        out = fe.do_query(
            "TQL EVAL (300, 480, '60s') rate(cpu[1m])")[-1]
        rows = out.batches[0].to_pylist()
        assert len(rows) == 8            # 2 hosts × 4 steps
        hosts = {r["host"] for r in rows}
        assert hosts == {"a", "b"}
        assert all(abs(r["value"] - (0.2 if r["host"] == "a" else 0.5))
                   < 1e-9 for r in rows)

    def test_tql_parse_roundtrip(self):
        stmt = parse_sql("TQL EVAL (0, 100, '15s') sum(rate(x[5m]))")
        assert isinstance(stmt, Tql)
        assert stmt.query.strip().startswith("sum")


class TestMultiRegion:
    def test_promql_over_partitioned_table(self, fe):
        fe.do_query("""
            CREATE TABLE pm (host STRING, ts TIMESTAMP TIME INDEX,
                             val DOUBLE, PRIMARY KEY(host))
            PARTITION BY RANGE COLUMNS (host) (
              PARTITION r0 VALUES LESS THAN ('m'),
              PARTITION r1 VALUES LESS THAN (MAXVALUE))""")
        rows = []
        for i in range(30):
            rows.append(f"('alpha', {i * 10_000}, {i * 1.0})")
            rows.append(f"('zulu', {i * 10_000}, {i * 3.0})")
        fe.do_query("INSERT INTO pm VALUES " + ",".join(rows))
        out = _q(fe, "rate(pm[1m])", 120_000, 240_000, 60_000)
        a = _series(out, host="alpha")
        z = _series(out, host="zulu")
        for _, v in a["values"]:
            assert abs(float(v) - 0.1) < 1e-9
        for _, v in z["values"]:
            assert abs(float(v) - 0.3) < 1e-9


class TestReviewRegressions:
    """Round-2 inline review findings."""

    def test_unary_minus_binds_looser_than_pow(self):
        e = parse_promql("-1^2")
        # -(1^2) = -1, not (-1)^2
        from greptimedb_tpu.promql.ast import Unary
        assert isinstance(e, Unary) or (
            isinstance(e, NumberLiteral) and e.value == -1)
        e = parse_promql("-2*3")
        assert isinstance(e, Binary) and e.op == "*"
        assert e.lhs.value == -2.0

    def test_irate_and_timestamp_at_realistic_epoch(self, fe):
        base = 1_700_000_000_000          # Nov 2023, epoch ms
        fe.do_query("CREATE TABLE ep (ts TIMESTAMP TIME INDEX, val DOUBLE)")
        rows = ",".join(f"({base + i * 15_000}, {i * 3.0})"
                        for i in range(20))
        fe.do_query(f"INSERT INTO ep VALUES {rows}")
        eng = fe.promql_engine()
        t = (base + 19 * 15_000) // 1000
        out = eng.query_to_prom_json("irate(ep[1m])", t * 1000, t * 1000,
                                     1000, QueryContext(), instant=True)
        # 3 per 15s = 0.2/s; float32 epoch seconds would return empty/0
        assert out["result"], "irate returned empty at realistic epoch"
        assert abs(float(out["result"][0]["value"][1]) - 0.2) < 1e-3
        out = eng.query_to_prom_json("timestamp(ep)", t * 1000, t * 1000,
                                     1000, QueryContext(), instant=True)
        got = float(out["result"][0]["value"][1])
        assert abs(got - t) < 1.0         # was off by up to ~128s

    def test_absent_selector_labels(self, fe):
        _mk_cpu(fe)
        out = _q(fe, 'absent(nosuch{job="api", host=~"h.*"})',
                 100_000, 100_000, 1000, instant=True)
        assert out["result"][0]["metric"] == {"job": "api"}


class TestReviewRegressions2:
    def test_irate_counter_reset(self, fe):
        fe.do_query("CREATE TABLE ir (ts TIMESTAMP TIME INDEX, val DOUBLE)")
        # counter resets between the last two samples: prometheus uses the
        # last value alone (0.5/s), not a huge negative rate
        fe.do_query("INSERT INTO ir VALUES (0, 100000), (10000, 100005), "
                    "(20000, 5)")
        out = _q(fe, "irate(ir[1m])", 20_000, 20_000, 1000, instant=True)
        v = float(out["result"][0]["value"][1])
        assert abs(v - 0.5) < 1e-6

    def test_invalid_regex_is_query_error(self, fe):
        _mk_cpu(fe)
        with pytest.raises(PromqlParseError):
            _q(fe, 'cpu{host=~"["}', 0, 0, 1000, instant=True)

    def test_invalid_duration_is_greptime_error(self):
        from greptimedb_tpu.common.time import parse_prom_duration
        from greptimedb_tpu.errors import GreptimeError
        with pytest.raises(GreptimeError):
            parse_prom_duration("abc")


class TestTqlExplain:
    def test_explain_plan_tree(self, fe):
        _mk_cpu(fe)
        out = fe.do_query("TQL EXPLAIN (0, 60, '1m')"
                          " sum by (host) (rate(cpu[1m]))")[-1]
        plan = out.batches[0].to_pydict()["plan"][0]
        assert "PromAggregate: sum by (host)" in plan
        assert "PromCall: rate" in plan
        assert "PromSeriesScan: cpu[60000ms]" in plan

    def test_analyze_reports_stats(self, fe):
        _mk_cpu(fe)
        out = fe.do_query("TQL ANALYZE (0, 100, '10s') cpu")[-1]
        doc = out.batches[0].to_pydict()
        assert doc["plan_type"] == ["logical_plan", "analyze"]
        assert "elapsed" in doc["plan"][1] and "series: 2" in doc["plan"][1]


class TestStreamedColdSelect:
    """Satellite (ISSUE 3): PromQL range selectors take the streamed cold
    path — a window-bounded host read that never enters the scan cache —
    when the region exceeds the stream threshold, with identical answers
    to the resident path."""

    def test_streamed_matches_resident(self, fe):
        from greptimedb_tpu.query import stream_exec, tpu_exec
        from greptimedb_tpu.storage import scan_cache
        _mk_cpu(fe)
        table = fe.catalog.table("greptime", "public", "cpu")
        region = next(iter(table.regions.values()))
        saved = stream_exec.stream_threshold_rows()
        try:
            # resident baseline (threshold far above the 120 rows)
            stream_exec.configure_streaming(threshold_rows=10_000_000)
            assert not tpu_exec.region_streams_cold(region)
            resident = _q(fe, "rate(cpu[1m])", 300_000, 480_000, 60_000)
            inst_res = _q(fe, "cpu", 100_000, 100_000, 1000, instant=True)
            # force the cold path and evict any residency
            stream_exec.configure_streaming(threshold_rows=1)
            scan_cache.SCAN_CACHE._entries.clear()
            assert tpu_exec.region_streams_cold(region)
            streamed = _q(fe, "rate(cpu[1m])", 300_000, 480_000, 60_000)
            inst_str = _q(fe, "cpu", 100_000, 100_000, 1000, instant=True)
            assert streamed == resident
            assert inst_str == inst_res
            # the cold read must not have populated the scan cache
            assert scan_cache.SCAN_CACHE.resident_bytes() == 0
        finally:
            stream_exec.configure_streaming(threshold_rows=saved)

    def test_streamed_reads_only_window(self, fe):
        from greptimedb_tpu.query import stream_exec
        from greptimedb_tpu.storage import scan_cache
        from greptimedb_tpu.session import QueryContext
        from greptimedb_tpu.promql.parser import parse_promql
        _mk_cpu(fe)                      # 60 samples / host, 10s apart
        saved = stream_exec.stream_threshold_rows()
        try:
            stream_exec.configure_streaming(threshold_rows=1)
            scan_cache.SCAN_CACHE._entries.clear()
            eng = fe.promql_engine()
            sel = parse_promql("cpu[1m]")
            selection = eng.select(sel, 100_000, 160_000, QueryContext())
            # window-bounded: 7 samples/host in [100s, 160s], not 60
            total = int(np.sum(selection.matrix.lengths))
            assert total == 2 * 7
        finally:
            stream_exec.configure_streaming(threshold_rows=saved)
