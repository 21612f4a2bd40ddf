"""One run of one cell: start the server, make and load the data, warm,
measure, check, stop, and build the result line."""

from __future__ import annotations

import json
import os
import shutil
import statistics
import time

from .loops import Context, log
from .server import ROOT, Server
from .spec import (Cell, load_generator, load_json, load_layer_reader,
                   load_loop)
from .trace import DeviceTrace
from .wire import Http

WORK_ROOT = os.path.join(ROOT, ".bench_work")


class NoChip(SystemExit):
    """The server did not come up on what the cell asks for."""


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def cache_entries(cache_dir: str) -> set:
    try:
        return {n for n in os.listdir(cache_dir) if not n.endswith("-atime")}
    except FileNotFoundError:
        return set()


def end_to_end(cell: Cell, run: dict) -> dict:
    """The cell's end-to-end metrics from the window's own records: every
    statement (or batch) that completed inside the window, all of the
    window's time (`window_s`: for statements, --seconds plus the rest
    of the round in flight)."""
    seconds = run["window_s"]
    out = {"setup_s": run["setup_s"]}
    # a mix may report its quantities under names of its own (`reports`),
    # so that a noisier mix has bounds of its own
    names = run["mix"].get("reports", {})
    done = [r for r in run.get("statements", ())
            if r["in_window"] and r["ok"]]
    if done:        # a run whose every statement failed has no latency
        by_family = {}
        for r in done:
            by_family.setdefault(r["family"], []).append(r["client_ms"])
        # the mean, not the median: a family's latencies can sit in two
        # modes (double-groupby-1 alternates 265 / 335 ms on the chip), and
        # the median of such a family jumps by a fifth with the parity of
        # the rounds in the window
        means = [statistics.fmean(v) for v in by_family.values()]
        out[names.get("geomean_ms", "stmt_geomean_ms")] = \
            statistics.geometric_mean(means)
        out[names.get("p90_ms", "stmt_p90_ms")] = percentile(
            [r["client_ms"] for r in done], 90)
        out[names.get("per_s", "stmt_per_s")] = len(done) / seconds
        run["window_statements"] = len(done)
        run["family_mean_ms"] = {f: statistics.fmean(v)
                                 for f, v in sorted(by_family.items())}
        run["family_median_ms"] = {f: statistics.median(v)
                                   for f, v in sorted(by_family.items())}
    if "batches" in run:
        rows = sum(r["rows"] for r in run["batches"]
                   if r["in_window"] and r["ok"])
        out[names.get("rows_per_s", "ingest_rows_per_s")] = rows / seconds
        run["window_rows"] = rows
    return out


def compared_numbers(run: dict) -> dict:
    """Every number the check compared, beside its limit, under short
    plain names: per family the largest error of its answers and the
    count of wrong ones; per read-back the count and sum errors. None
    where there was nothing to subtract (the result's keys differed)."""
    out = {}
    for family, c in run.get("compared", {}).items():
        out[f"{family}.{c['number']}"] = {"value": c["largest"],
                                          "limit": c["limit"]}
        out[f"{family}.wrong_answers"] = {"value": c["wrong"], "limit": 0}
    for when, rb in run.get("read_back", {}).items():
        out[f"read_back.{when}.count_max_abs_err"] = {
            "value": rb["count"]["max_abs_err"], "limit": 0.0}
        out[f"read_back.{when}.sum_max_rel_err"] = {
            "value": rb["sums"]["max_rel_err"],
            "limit": run["mix"]["sum_tolerance"]["rtol"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             debug_platform: str = None, perturb: str = None) -> dict:
    """-> the result line as a dict. Raises NoChip before anything is
    loaded when the server is not on the platform and chip count the cell
    asks for. `perturb` breaks the timed path's output on the benchmark's
    side (control.py and selftest.py; run.py cannot set it)."""
    cell = Cell(workload)
    config, mix = cell.config, cell.mix
    debug = debug_platform is not None
    size = config["debug"] if debug else config
    work = os.path.join(WORK_ROOT, f"{workload}-seed{seed}-trace{int(traced)}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    run = {"workload": workload, "seed": seed, "seconds": seconds,
           "traced": traced, "config": config, "mix": mix}
    server = Server(work, config.get("server_options", ()), debug_platform)
    t_setup = time.monotonic()
    try:
        server.start()
        # the data is made while the server reaches the chip
        extra = int(mix.get("extra_ticks", 0))
        if debug:
            extra = int(mix.get("debug_extra_ticks", extra))
        ds = load_generator(config)(
            config, seed, extra_ticks=extra, scale=size["scale"],
            ticks=size["duration_s"] // config["log_interval_s"])
        run["generate_s"] = time.monotonic() - t_setup
        try:
            status = server.wait_ready()
        except RuntimeError as e:
            if "no TPU" in str(e):      # common/device.py's refusal
                raise NoChip("benchmark: the server found no TPU and did "
                             f"not start. Nothing was loaded.\n{e}") from None
            raise
        dev = status["device"]
        wanted = debug_platform or "tpu"
        if dev["platform"] != wanted or dev["device_count"] < cell.chips:
            raise NoChip(
                f"benchmark: the server runs on platform {dev['platform']!r}"
                f" ({dev['device_kind']}, {dev['device_count']} device(s));"
                f" {workload} needs {cell.chips} x {wanted}. Nothing was "
                "loaded.")
        log(f"server up on {dev}, wal {status['wal_backend']}; generated "
            f"{ds.rows:,} rows in {run['generate_s']:.1f} s")
        http = Http(server.ports["http"])
        http.sql(ds.create_table_sql())
        t = time.monotonic()
        acked = ds.load(server.ports["grpc"],
                        int(size["load_chunk_ticks"]))
        run["load_s"] = time.monotonic() - t
        run["rows_loaded"] = acked
        if acked != ds.rows:
            raise RuntimeError(f"the load acknowledged {acked} of "
                               f"{ds.rows} rows")
        log(f"loaded {acked:,} rows over Flight in {run['load_s']:.1f} s")
        ctx = Context(cell, ds, server, run, seed, traced, debug, perturb)
        loop = load_loop(mix["loop"])(ctx)
        loop.prepare()
        run["setup_s"] = time.monotonic() - t_setup

        # ---- the measured window (and the traced one: the same) --------
        entries = cache_entries(cache_dir)
        counters = {"before": http.metrics()}
        status = http.status()
        run["status_before_window"] = {
            k: status.get(k) for k in ("scan_cache_resident_bytes",
                                       "last_scan_profile", "region_count")}
        if traced:
            mark = server.trace_start(os.path.join(work, "trace"))
        t0_ns = time.time_ns()
        loop.window(seconds)
        t1_ns = time.time_ns()
        counters["after"] = http.metrics()
        run["counters"] = counters
        run["compiled_in_window"] = len(cache_entries(cache_dir) - entries)
        if run["compiled_in_window"]:
            log(f"WARNING: {run['compiled_in_window']} programs were "
                "compiled inside the window: a shape was not warmed")
        loop.after_window()
        t2_ns = time.time_ns()
        if traced:
            run["trace_marks"] = [mark, server.trace_stop()]
            hi_ns = t2_ns if mix.get("trace_through_check") else t1_ns
            run["trace"] = DeviceTrace(
                load_json(work, "trace", "events.json"),
                mark["anchor_wall_ns"], (t0_ns, hi_ns), loop.spans())
        peak = http.status()["device"].get("peak_bytes_in_use")

        # ---- outside the window: the answers, then the server goes -----
        verdict = loop.check()
        status = Http(server.ports["http"]).status()   # new after a restart
        peaks = [p for p in (peak, status["device"].get(
            "peak_bytes_in_use")) if p is not None]
        run["device"] = status["device"]
    except BaseException:
        print("---- server log tail ----\n" + server.log_tail(), flush=True)
        raise
    finally:
        server.kill()
        shutil.rmtree(os.path.join(work, "data"), ignore_errors=True)
        shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)

    if traced:
        metrics = {}
        for m in cell.metrics("per_layer"):
            value = load_layer_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        end_to_end(cell, run)
    else:
        values = end_to_end(cell, run)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end") if m["name"] in values}
    device = {"platform": str(dev["platform"]),
              "kind": str(dev["device_kind"]),
              "count": int(dev["device_count"]),
              "memory_peak_bytes": max(peaks) if peaks else None}
    result = {"correct": bool(verdict["correct"]),
              "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": device}
    if traced:
        trace = run["trace"]
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.device_ops(),
                               "idle_gaps": trace.idle_gaps()}
    result["compared"] = compared_numbers(run)      # the line's last key
    save_record(work, run, result)
    return result


def save_record(work: str, run: dict, result: dict) -> None:
    """What the run showed, beside the result: every statement's or
    batch's send and answer time, the warm-up, the counters."""
    keep = {k: v for k, v in run.items()
            if k not in ("trace", "config", "mix", "counters")}
    flush = ("greptime_flush_files_total",
             "greptime_region_write_stalls_total",
             "greptime_region_write_rows_total",
             "greptime_ingest_sst_files_total",
             "greptime_compaction_runs_total",
             "greptime_compaction_files_in_total",
             "greptime_compaction_files_out_total")
    keep["counters"] = {when: {k: v.get(k) for k in flush}
                        for when, v in run["counters"].items()}
    keep["result"] = result
    with open(os.path.join(work, "record.json"), "w") as f:
        json.dump(keep, f, default=lambda o: o.tolist()
                  if hasattr(o, "tolist") else str(o))
