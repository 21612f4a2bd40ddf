"""The per-layer readers added with the program's spans (ISSUE 24), each
fed a synthetic run record: rows / timers present -> the number, absent
(the parent program) -> None, so the metric is left out of the line."""

import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
T0 = 1_790_000_000_000_000_000


def stage(ms, start_ms=None, detail=""):
    if start_ms is not None:
        detail = (detail + ", " if detail else "") + \
            f"t0_ns={T0 + int(start_ms * 1e6)}"
    return {"rows": 0, "elapsed_ms": float(ms), "detail": detail}


def statement(family, scale, spans=True):
    """One statement sent at T0: parse 1 ms, then a 100 ms `total` from
    +2 ms (plan 4, scan_prep 1, reduce 60 with its parts, finalize 10,
    project 20; 5 ms under no row), render 30 ms after it; all times
    `scale`d. Without `spans` the rows are what the parent reports."""
    def at(ms):
        return ms * scale if spans else None

    def s(ms, start, detail=""):
        return stage(ms * scale, at(start), detail)

    stages = {
        "plan": s(4, 2, "TpuAggregateExec: x\n  TableScan: cpu")
        if spans else stage(0.0, None, "TpuAggregateExec: x"),
        "dispatch": stage(0.0, None, "device-resident (scan cache)"),
        "scan_prep": s(1, 6, "cache=hit"),
        "reduce": s(60, 7),
        "finalize": s(10, 67, "partial_frames=1"),
        "project": s(20, 77),
        "total": stage(100 * scale, None, "trace_id=ab" if spans else ""),
    }
    if spans:
        stages.update({
            "parse": s(1, 0),
            "reduce.runs": s(5, 7), "reduce.mask": s(10, 12),
            "reduce.upload": s(3, 22), "reduce.launch": s(2, 25),
            "reduce.fetch": s(30, 27), "reduce.collect": s(10, 57),
            "project.sort": s(8, 80), "project.to_batches": s(9, 88),
            "render": s(30, 102, "protocol=http, bytes=99"),
        })
    return {"family": family, "in_window": True, "ok": True,
            "client_ms": 140.0 * scale, "t_send_ns": T0,
            "t_done_ns": T0 + int(140e6 * scale), "stages": stages}


class FakeTrace:
    """The device is busy for the first half of every interval asked."""
    planes = {"/device:TPU:0": [[0, 1]]}

    @staticmethod
    def busy_ns_between(lo, hi):
        return (hi - lo) / 2


def query_run(spans=True):
    return {"statements": [statement("a", 1, spans), statement("a", 1, spans),
                           statement("b", 3, spans)],
            "trace": FakeTrace()}


def ingest_run(timers=True):
    route = '{route="/v1/influxdb/write"}'
    before = {"greptime_http_request_seconds_count" + route: 10.0,
              "greptime_http_request_seconds_sum" + route: 20.0,
              "greptime_region_write_seconds_sum": 5.0,
              "greptime_wal_fsync_seconds_sum": 1.0}
    after = {"greptime_http_request_seconds_count" + route: 110.0,
             "greptime_http_request_seconds_sum" + route: 220.0,
             "greptime_region_write_seconds_sum": 105.0,
             "greptime_wal_fsync_seconds_sum": 3.0}
    if timers:
        before["greptime_ingest_parse_seconds_sum"] = 2.0
        after["greptime_ingest_parse_seconds_sum"] = 52.0
        after["greptime_ingest_coalesce_wait_seconds_sum"] = 40.0
    return {"batches": [{"in_window": True, "ok": True}],
            "counters": {"before": before, "after": after}}


@pytest.fixture(scope="module")
def reader():
    sys.path.insert(0, BENCH)
    try:
        from benchlib.spec import load_layer_reader
        yield load_layer_reader
    finally:
        sys.path.remove(BENCH)


# mean over families of family means: family a at scale 1, b at scale 3
QUERY_READERS = {
    "parse_ms": 2 * 1,
    "plan_ms": 2 * 4,
    "untimed_ms": 2 * 5,
    "mask_ms": 2 * (5 + 10 + 3),
    "fetch_ms": 2 * 30,
    "collect_ms": 2 * 10,
    "project_ms.point": 2 * 20,
    "render_ms": 2 * 30,
    # busy half of the statement's 140 ms less half of reduce's 60 ms
    "device_outside_reduce_ms.point": 2 * (70 - 30),
}
INGEST_READERS = {
    "ingest_server_ms": 2000.0,
    "ingest_parse_ms": 500.0,
    "ingest_wait_ms": 400.0,
    "region_write_ms": 1000.0,
    "wal_fsync_ms": 20.0,
}


@pytest.mark.parametrize("metric", sorted(QUERY_READERS))
def test_query_reader_reads_its_rows(reader, metric):
    assert reader(metric)(query_run()) == pytest.approx(
        QUERY_READERS[metric])
    assert reader(metric)(ingest_run()) is None


@pytest.mark.parametrize("metric", sorted(QUERY_READERS))
def test_query_reader_without_spans_reads_nothing(reader, metric):
    assert reader(metric)(query_run(spans=False)) is None
    assert reader(metric)({"statements": []}) is None


@pytest.mark.parametrize("metric", sorted(INGEST_READERS))
def test_ingest_reader_reads_its_timer(reader, metric):
    assert reader(metric)(ingest_run()) == pytest.approx(
        INGEST_READERS[metric])
    assert reader(metric)(query_run()) is None


@pytest.mark.parametrize("metric", ["ingest_parse_ms", "ingest_wait_ms"])
def test_ingest_reader_without_the_timer_reads_nothing(reader, metric):
    assert reader(metric)(ingest_run(timers=False)) is None


@pytest.mark.parametrize("metric, timer", [
    ("ingest_wait_ms", "ingest_coalesce_wait"), ("wal_fsync_ms", "wal_fsync")])
def test_a_timer_never_observed_reads_zero(reader, metric, timer):
    """No follower in the window; a deployment that acknowledges without
    waiting for an fsync."""
    run = ingest_run()
    for when in run["counters"].values():
        when.pop(f"greptime_{timer}_seconds_sum", None)
    assert reader(metric)(run) == 0.0


def test_every_new_metric_has_its_reader_and_entry():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    for metric in list(QUERY_READERS) + list(INGEST_READERS):
        base = metric.split(".", 1)[0]
        assert os.path.isfile(os.path.join(BENCH, "layers", base + ".py"))
        assert base in names
        if metric in QUERY_READERS:
            assert base + ".point" in names


def test_idle_time_falls_under_the_innermost_stage_row():
    """benchmark/stage_idle.py: one statement of the synthetic run, the
    device busy from +30 to +50 ms of a 200 ms window."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_stage_idle", os.path.join(BENCH, "stage_idle.py"))
    stage_idle = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stage_idle)

    class Trace:
        offset = 0
        lo, hi = T0, T0 + 200_000_000
        planes = {"/device:TPU:0": [[T0 + 30_000_000, T0 + 50_000_000]]}

    run = {"statements": [statement("a", 1)], "trace": Trace()}
    idle = {k: round(v * 1e3, 6)
            for k, v in stage_idle.idle_by_stage_row(run)}
    assert idle == {
        "parse": 1, "plan": 4, "scan_prep": 1, "reduce.runs": 5,
        "reduce.mask": 10, "reduce.upload": 3, "reduce.launch": 2,
        "reduce.fetch": 3 + 7, "reduce.collect": 10, "finalize": 10,
        "project": 3, "project.sort": 8, "project.to_batches": 9,
        "render": 30, "statement_outside_rows": 1 + 5 + 8,
        "between_statements": 60}
    assert sum(idle.values()) == 180
    means = stage_idle.family_stage_ms(run)
    assert means["a"]["render"] == 30 and means["a"]["client_ms"] == 140
