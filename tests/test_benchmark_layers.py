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


# ---------------------------------------------------------------------------
# the readers of a scan-cache refresh and the cell that reports them
# (ISSUE 37: `tsbs4k-read-while-ingest`)
# ---------------------------------------------------------------------------

CELL = "tsbs4k-read-while-ingest"


def refresh_run(refreshing=True, counters=True):
    """`query_run` in which family a's statements refreshed the scan
    cache (scan_prep 1 ms = delta 0.5 + apply 0.3 + upload 0.2 at scale
    1) and b's found it current; 2 refreshes, 5,000 rows, 42 MB."""
    run = query_run()
    if refreshing:
        for rec in run["statements"][:2]:
            rec["stages"].update({
                "scan_prep.delta": stage(0.5, 6), "scan_prep.apply":
                stage(0.3, 6.5), "scan_prep.upload": stage(0.2, 6.8)})
    before = {"greptime_scan_cache_incremental_total": 3.0,
              "greptime_scan_cache_miss_total": 1.0,
              "greptime_scan_cache_hit_total": 7.0}
    after = {"greptime_scan_cache_incremental_total": 5.0 if refreshing
             else 3.0, "greptime_scan_cache_miss_total": 1.0,
             "greptime_scan_cache_hit_total": 9.0}
    if counters:
        before.update({"greptime_scan_cache_delta_rows_total": 1000.0,
                       "greptime_scan_cache_upload_bytes_total": 1e6})
        after.update({"greptime_scan_cache_delta_rows_total": 6000.0
                      if refreshing else 1000.0,
                      "greptime_scan_cache_upload_bytes_total": 43e6
                      if refreshing else 1e6})
    run["counters"] = {"before": before, "after": after}
    return run


REFRESH_READERS = {
    "refresh_apply_ms": 0.8,            # family a alone refreshed
    "refresh_upload_ms": 0.2,
    "refresh_delta_rows": 2500.0,
    "refresh_upload_bytes": 21e6,
    "tail_merges": 0.0,                 # counts rows, merged nothing
    "cache_refreshes": 2.0,
    "launch_ms": 2 * 2,
}


@pytest.mark.parametrize("metric", sorted(REFRESH_READERS))
def test_refresh_reader_reads_its_rows_and_counters(reader, metric):
    assert reader(metric)(refresh_run()) == pytest.approx(
        REFRESH_READERS[metric])


@pytest.mark.parametrize("metric", [
    "refresh_apply_ms", "refresh_upload_ms", "refresh_delta_rows",
    "refresh_upload_bytes"])
def test_a_window_without_a_refresh_reads_nothing(reader, metric):
    """As each reader's docstring says: None, and the line leaves the
    metric out; `tail_merges` and `cache_refreshes` are counts and read
    0."""
    run = refresh_run(refreshing=False)
    assert reader(metric)(run) is None
    assert reader("tail_merges")(run) == 0.0
    assert reader("cache_refreshes")(run) == 0.0


@pytest.mark.parametrize("metric", [
    "refresh_apply_ms", "refresh_upload_ms", "refresh_delta_rows",
    "refresh_upload_bytes", "tail_merges"])
def test_refresh_reader_on_the_parent_program_reads_nothing(reader, metric):
    """No `scan_prep.*` row and no such counter: None, never a raise."""
    parent = refresh_run(refreshing=False, counters=False)
    assert reader(metric)(parent) is None
    assert reader(metric)(ingest_run()) is None
    assert reader(metric)({}) is None


def test_a_merge_in_the_window_is_counted(reader):
    run = refresh_run()
    run["counters"]["after"]["greptime_scan_cache_merges_total"] = 1.0
    assert reader("tail_merges")(run) == 1.0


def test_the_wait_for_the_parse_turn_is_read_beside_the_parse(reader):
    """`ingest_parse_wait_ms`: the timer over the window's acknowledged
    batches, as `ingest_parse_ms` beside it; a program without the timer
    (the parent: bodies parsed side by side) and a window without writes
    read None."""
    read = reader("ingest_parse_wait_ms")
    run = ingest_run()
    assert read(run) is None
    run["counters"]["before"]["greptime_ingest_parse_wait_seconds_sum"] = 1.0
    run["counters"]["after"]["greptime_ingest_parse_wait_seconds_sum"] = 181.0
    assert read(run) == pytest.approx(1800.0)       # 180 s, 100 batches
    assert reader("ingest_parse_ms")(run) == pytest.approx(500.0)
    assert read(query_run()) is None and read({}) is None
    entry = next(m for m in _benchmark()["per_layer"]
                 if m["name"] == "ingest_parse_wait_ms")
    assert entry["workloads"] == line_protocol_cells()
    assert (entry["moves"], entry["layer"]) == ("ingest_rows_per_s",
                                                "write path")


def test_full_collections_in_the_window_are_read_in_ms(reader):
    read = reader("gc_full_ms")
    run = refresh_run()
    assert read(run) is None and read({}) is None       # no such timer
    name = "greptime_gc_full_collection_seconds_sum"
    run["counters"]["before"][name] = 0.25
    run["counters"]["after"][name] = 0.25
    assert read(run) == 0.0
    run["counters"]["after"][name] = 4.5
    assert read(run) == pytest.approx(4250.0)
    entry = next(m for m in _benchmark()["per_layer"]
                 if m["name"] == "gc_full_ms")
    assert entry["workloads"] == read_while_write_cells()


def _benchmark():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def reporting(metric: str) -> list:
    """The cells that report an end-to-end metric: what the `workloads`
    list of a per-layer metric that moves it holds where every such cell
    has something for its reader (the accepted cells, then those later
    PRs added)."""
    entry = next(m for m in _benchmark()["end_to_end"]
                 if m["name"] == metric)
    assert CELL in entry["workloads"]
    return entry["workloads"]


def line_protocol_cells() -> list:
    """The cells whose writers post InfluxDB line protocol: the readers
    of that handler's timers and phases divide by its route's requests
    (`spanlib.WRITE_ROUTE`) and find nothing in a cell that writes over
    Prometheus remote write, which has readers of its own
    (`layers/prom_write_*.py`, ISSUE 44)."""
    return [c for c in reporting("ingest_rows_per_s")
            if c != "prom1k-remote-write-while-read"]


def read_while_write_cells() -> list:
    """The cells whose window holds statements and batches: the readers
    of a scan-cache refresh and of the collector's pauses find something
    there and nowhere else."""
    cells = [c for c in reporting("stmt_geomean_ms")
             if c in reporting("ingest_rows_per_s")]
    assert cells[0] == CELL and "tsbs4k-backfill-while-read" in cells
    return cells


def test_the_cell_reports_what_its_mix_says():
    import json
    bench = _benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("tsbs-cpu-4000-durable", "read-while-ingest", 1)
    with open(os.path.join(BENCH, "traffic", "read-while-ingest.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "mixed" and mix["families"] == \
        ["double-groupby-1", "lastpoint-live"]
    with open(os.path.join(BENCH, "traffic", "read-under-ingest.json")) as f:
        queued = json.load(f)
    differs = {k for k in set(mix) | set(queued)
               if mix.get(k) != queued.get(k)}
    assert differs == {"about", "assumed", "reports", "extra_ticks"}
    assert (mix["extra_ticks"], mix["debug_extra_ticks"], mix["workers"],
            mix["batch_rows"], mix["prefill_batches"],
            mix["warm_statements"], mix["max_statements"]) == \
        (250, 1200, 6, 2500, 12, 3, 4000)
    reported = {m["name"] for m in bench["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == set(mix["reports"].values()) | {"setup_s"}
    assert "p90_ms" not in mix["reports"]
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert [bounds[n] for n in ("stmt_geomean_ms", "stmt_per_s",
                                "ingest_rows_per_s", "setup_s")] == \
        [0.07, 0.06, 0.10, 0.25]
    layers = {m["name"]: m for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(REFRESH_READERS) | {
        "compiled_in_window", "visible_lag_ms", "wal_fsync_ms",
        "scan_prep_ms", "kernel_ms", "scan_kernels_roofline",
        "batch_ack_ms", "region_write_ms"} <= set(layers)
    for name, m in layers.items():
        assert os.path.isfile(os.path.join(
            BENCH, "layers", name.split(".", 1)[0] + ".py")), name
        assert m["moves"] in reported, name
    for name in REFRESH_READERS:
        assert layers[name]["workloads"] == read_while_write_cells()
        assert layers[name]["moves"] == "stmt_geomean_ms"


def test_the_durable_configuration_is_tsbs_cpu_4000_plus_the_fsync():
    import json
    bench = _benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "tsbs-cpu-4000-durable")
    with open(os.path.join(os.path.dirname(BENCH), entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "configs", "tsbs-cpu-4000.json")) as f:
        plain = json.load(f)
    assert len(entry["source"]) < 200 and entry["source"] == config["source"]
    assert "sync_write = true" in entry["source"]
    assert entry["reduced"] == ["duration_s"] == list(config["reduced"])
    assert config["server_options"] == ["--wal-sync-on-write"]
    assert plain["server_options"] == []
    same = ("use_case", "scale", "log_interval_s", "duration_s", "start",
            "table", "time_index", "tags", "fields", "primary_key",
            "load_chunk_ticks", "reduced", "debug")
    assert {k: config[k] for k in same} == {k: plain[k] for k in same}
    assert set(config["guarantees"]) == {"durability", "consistency",
                                         "answers"}
    assert config["guarantees"]["answers"] == plain["guarantees"]["answers"]
    assert "fsync" in config["guarantees"]["durability"]
    assert config["assumed"][:len(plain["assumed"])] == plain["assumed"]
    # the flag is one `standalone start` takes
    from greptimedb_tpu.cmd.main import build_parser
    args = build_parser().parse_args(
        ["standalone", "start", *config["server_options"]])
    assert args.wal_sync_on_write is True


# ---------------------------------------------------------------------------
# the readers of a request's frame, of the threads' CPU time and of the
# event loop's lag (ISSUE 39)
# ---------------------------------------------------------------------------

def with_cpu(row, share):
    """A span row as the program reports it since ISSUE 39: `cpu_ms=`
    (`share` of its elapsed time) ahead of `t0_ns=`."""
    lead, sep, start = row["detail"].rpartition("t0_ns=")
    assert sep, row
    cpu = f"cpu_ms={row['elapsed_ms'] * share:.3f}, "
    return dict(row, detail=f"{lead}{cpu}t0_ns={start}")


def framed_run(cpu=True, frame=True):
    """`query_run` as the program of ISSUE 39 reports it. Every span
    with its thread's CPU time: 3/4 of its elapsed time, `reduce.fetch`
    (asleep for the device) a tenth, `reduce.launch` half, `total` 60%,
    `render` 90%. Family a went over HTTP and has the request's rows
    (read 0.25 ms, queue 0.5, resume 1.5), b over MySQL and has none.
    /metrics: 30 `/v1/sql` responses written in 1.2 s, 500 ticks of the
    loop 0.3 s late in all."""
    run = query_run()
    for rec in run["statements"]:
        stages, scale = rec["stages"], rec["client_ms"] / 140.0
        if cpu:
            shares = {"reduce.fetch": 0.1, "reduce.launch": 0.5,
                      "render": 0.9}
            for name, row in stages.items():
                if "t0_ns=" in row["detail"]:
                    stages[name] = with_cpu(row, shares.get(name, 0.75))
            stages["total"]["detail"] += f", cpu_ms={60.0 * scale:.3f}"
        if frame and rec["family"] == "a":
            rec["stages"] = {
                "request.read": stage(0.25, -0.75),
                "request.queue": stage(0.5, -0.5), **stages,
                "request.resume": stage(1.5, 102),
                "render": stages["render"]}
    write = 'greptime_http_phase_seconds_{}{{phase="write",route="/v1/sql"}}'
    lag = "greptime_event_loop_lag_seconds_{}"
    run["counters"] = {"before": {}, "after": {}}
    if frame:
        run["counters"] = {
            "before": {write.format("sum"): 0.2, write.format("count"): 10.0,
                       lag.format("sum"): 0.5, lag.format("count"): 100.0},
            "after": {write.format("sum"): 1.4, write.format("count"): 40.0,
                      lag.format("sum"): 0.8, lag.format("count"): 600.0}}
    return run


def phased_ingest_run():
    """`ingest_run` with the write route's phases (3 s of body reads, 1 s
    queued, 0.5 s resuming over the 100 batches) and the timers' CPU
    seconds (the parser computes for 45 of its 50 s, `Region.write` for
    20 of its 100)."""
    run = ingest_run()
    before, after = run["counters"]["before"], run["counters"]["after"]
    for phase, seconds in (("read", 3.0), ("queue", 1.0), ("resume", 0.5)):
        name = ('greptime_http_phase_seconds_sum{phase="%s",'
                'route="/v1/influxdb/write"}' % phase)
        before[name], after[name] = 1.0, 1.0 + seconds
    for timer, seconds in (("ingest_parse", 45.0), ("region_write", 20.0)):
        name = f"greptime_{timer}_cpu_seconds_total"
        before[name], after[name] = 1.0, 1.0 + seconds
    return run


FRAME_READERS = {
    "request_read_ms": 0.25,        # family a alone went over HTTP
    "request_queue_ms.point": 0.5,
    "request_resume_ms": 1.5,
    "request_write_ms": 40.0,
    "loop_lag_ms.point": 0.6,
    # total 100 - 60, render 30 - 27, less fetch 30 - 3 and launch 2 - 1
    "host_off_cpu_ms": 2 * (40 + 3 - 27 - 1),
}
PHASED_INGEST_READERS = {
    "ingest_body_read_ms": 30.0,
    "ingest_queue_ms": 10.0,
    "ingest_resume_ms": 5.0,
    "ingest_parse_off_cpu_ms": 500.0 - 450.0,
    "region_write_off_cpu_ms": 1000.0 - 200.0,
    "loop_lag_ms.ingest": None,     # that run's /metrics has no such series
}


@pytest.mark.parametrize("metric", sorted(FRAME_READERS))
def test_frame_reader_reads_its_rows_and_series(reader, metric):
    assert reader(metric)(framed_run()) == pytest.approx(
        FRAME_READERS[metric])


@pytest.mark.parametrize("metric", sorted(FRAME_READERS))
def test_frame_reader_on_the_parent_program_reads_nothing(reader, metric):
    """No `request.*` row, no `cpu_ms=`, no such series: None, never a
    raise, and the line leaves the metric out."""
    read = reader(metric)
    assert read(framed_run(cpu=False, frame=False)) is None
    assert read(query_run(spans=False)) is None
    assert read(ingest_run()) is None
    assert read({"statements": []}) is None and read({}) is None


@pytest.mark.parametrize("metric", sorted(PHASED_INGEST_READERS))
def test_write_phase_reader_reads_its_series(reader, metric):
    want = PHASED_INGEST_READERS[metric]
    got = reader(metric)(phased_ingest_run())
    assert got is None if want is None else got == pytest.approx(want)
    if want is not None:
        assert reader(metric)(ingest_run()) is None     # the parent
        assert reader(metric)(query_run()) is None
        assert reader(metric)({}) is None


def test_the_loops_lag_is_read_in_a_write_window_too(reader):
    run = phased_ingest_run()
    run["counters"]["before"]["greptime_event_loop_lag_seconds_sum"] = 1.0
    run["counters"]["before"]["greptime_event_loop_lag_seconds_count"] = 10.0
    run["counters"]["after"]["greptime_event_loop_lag_seconds_sum"] = 3.0
    run["counters"]["after"]["greptime_event_loop_lag_seconds_count"] = 510.0
    assert reader("loop_lag_ms.ingest")(run) == pytest.approx(4.0)
    run["counters"]["after"]["greptime_event_loop_lag_seconds_count"] = 10.0
    assert reader("loop_lag_ms.ingest")(run) is None    # no tick, no mean


def test_a_family_over_mysql_is_left_out_of_the_requests_means(reader):
    """Both families over HTTP: the mean is over both; neither: None."""
    run = framed_run()
    rec = run["statements"][2]                  # family b, scale 3
    rec["stages"]["request.queue"] = stage(2.5, -1.0)
    assert reader("request_queue_ms")(run) == pytest.approx((0.5 + 2.5) / 2)
    assert reader("request_read_ms")(run) == pytest.approx(0.25)
    for rec in run["statements"]:
        rec["stages"].pop("request.read", None)
    assert reader("request_read_ms")(run) is None


def test_a_statement_without_cpu_on_a_wait_row_reads_no_off_cpu(reader):
    run = framed_run()
    assert reader("host_off_cpu_ms")(run) is not None
    for rec in run["statements"]:
        rec["stages"]["reduce.fetch"] = stage(30, 27)     # a span, no cpu
    assert reader("host_off_cpu_ms")(run) is None
    # a PromQL statement's waits are its window rows
    run = framed_run()
    for rec in run["statements"]:
        rec["stages"]["window.fetch"] = with_cpu(stage(8, 40), 0.25)
    assert reader("host_off_cpu_ms")(run) == pytest.approx(
        FRAME_READERS["host_off_cpu_ms"] - 6)


@pytest.mark.parametrize("metric", ["untimed_ms", "wire_ms", "parse_ms",
                                    "render_ms", "mask_ms"])
def test_the_new_rows_move_no_older_reader(reader, metric):
    """Every new row's name has a dot: none is taken for a part of
    `total`, and the rows' details still end with `t0_ns=`."""
    assert reader(metric)(framed_run()) == pytest.approx(
        reader(metric)(query_run()))


def test_idle_inside_a_statement_under_no_row_shrinks_with_the_frame(reader):
    """`idle_unattributed_s` is `stage_idle.py`'s `statement_outside_rows`:
    the statement of `test_idle_time_falls_under_the_innermost_stage_row`
    (14 ms under no row: 1 before `total`, 5 inside it, 8 after `render`)
    with 0.75 ms of request rows after `parse` and 3 after `render`."""
    class Trace:
        offset = 0
        lo, hi = T0, T0 + 200_000_000
        planes = {"/device:TPU:0": [[T0 + 30_000_000, T0 + 50_000_000]]}

    read = reader("idle_unattributed_s")
    rec = statement("a", 1)
    assert read({"statements": [rec], "trace": Trace()}) == \
        pytest.approx(0.014)
    rec["stages"].update({"request.read": stage(0.25, 1.0),
                          "request.queue": stage(0.5, 1.25),
                          "request.resume": stage(3, 132)})
    assert read({"statements": [rec], "trace": Trace()}) == \
        pytest.approx(0.014 - 0.00075 - 0.003)
    assert read({"statements": [rec]}) is None          # no trace
    assert read(ingest_run()) is None and read({}) is None

    class Busy(Trace):
        planes = {"/device:TPU:0": [[T0, T0 + 200_000_000]]}
    assert read({"statements": [rec], "trace": Busy()}) is None


def test_every_metric_of_issue_39_has_its_reader_and_entry():
    per_layer = {m["name"]: m for m in _benchmark()["per_layer"]}
    statement_cells = reporting("stmt_geomean_ms")
    assert statement_cells[:5] == [
        "tsbs4k-scan", "tsbs100k-groupby", "prom1k-dashboard",
        "prom1k-longrange", CELL]
    layer_of = {"host_off_cpu_ms": "process start, compile cache"}
    for name in ("request_read_ms", "request_queue_ms", "request_resume_ms",
                 "request_write_ms", "host_off_cpu_ms", "loop_lag_ms",
                 "idle_unattributed_s"):
        assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
        m, point = per_layer[name], per_layer[name + ".point"]
        assert m["workloads"] == statement_cells
        assert point["workloads"] == ["tsbs4k-point"]
        assert (m["moves"], point["moves"]) == ("stmt_geomean_ms",
                                                "point_geomean_ms")
        assert m["layer"] == point["layer"] == layer_of.get(
            name, "protocol servers")
    lag = per_layer["loop_lag_ms.ingest"]
    assert (lag["workloads"], lag["moves"]) == (["tsbs4k-ingest"],
                                                "ingest_rows_per_s")
    for name in (n for n in PHASED_INGEST_READERS if "." not in n):
        assert os.path.isfile(os.path.join(BENCH, "layers", name + ".py"))
        m = per_layer[name]
        assert m["workloads"] == line_protocol_cells()
        assert (m["moves"], m["layer"], m["source"]) == (
            "ingest_rows_per_s", "write path", "program_counter")
