"""Flagship benchmark: TSBS-style scan+aggregate throughput on TPU.

Models the north-star config (BASELINE.json): TSBS cpu-only
`single-groupby-5-8-1`-shape query — group by (host, 1-minute bucket) over
one hour, per-minute MAX of 5 metric columns — on rows resident in HBM in
the engine's post-merge layout (sorted by group key, which is what region
scans produce after the device merge/dedup pass). Uses the scatter-free
sorted-segment kernel (ops/kernels.py:sorted_grouped_aggregate); measured
~44x faster than XLA scatter segment_sum on v5e for this shape.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
`vs_baseline` is the speedup vs a same-machine CPU columnar baseline
(pandas groupby over the identical arrays — the stand-in denominator for
"CPU DataFusion", since the reference publishes no numbers).

Timing notes: each timed iteration fetches a scalar result to host, so the
clock stops only when the device has finished; iterations use distinct
shifted inputs so no result can be reused.
"""

import json
import os
import time

import numpy as np

HOSTS, BUCKETS = 8, 60
NUM_GROUPS = HOSTS * BUCKETS
OPS = ("max",) * 5  # TSBS single-groupby computes per-minute max


def gen_data(n_rows: int, seed: int = 42):
    rng = np.random.default_rng(seed)
    # post-merge region-scan layout: rows sorted by (host, minute bucket)
    gids = np.sort(rng.integers(0, NUM_GROUPS, n_rows)).astype(np.int32)
    ts = ((gids % BUCKETS) * 60_000 +
          rng.integers(0, 60_000, n_rows)).astype(np.int32)
    metrics = tuple(rng.random(n_rows, dtype=np.float32) * 100
                    for _ in range(5))
    return gids, ts, metrics


def bench_tpu(gids, ts, metrics, iters=8):
    import jax
    from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate

    import jax.numpy as jnp

    n = len(gids)
    mask = np.ones(n, bool)
    d_gids = jax.device_put(gids)
    d_ts = jax.device_put(ts)
    d_mask = jax.device_put(mask)
    d_ms = tuple(jax.device_put(m) for m in metrics)

    # Data arrays are jit *arguments* (not closure constants) so the compiled
    # program is code-only — closure capture bakes 16.7M-row arrays into the
    # HLO as constants, which blows remote-compile payload limits.
    @jax.jit
    def step(gids_a, mask_a, ts_a, ms_a, shift):
        # distinct shift per iteration → distinct numerics, so the runtime
        # cannot reuse a previous result
        ms_a = (ms_a[0] + shift,) + ms_a[1:]
        return sorted_grouped_aggregate(gids_a, mask_a, ts_a, ms_a,
                                        num_groups=NUM_GROUPS, ops=OPS)

    def step_i(shift):
        return step(d_gids, d_mask, d_ts, d_ms, shift)

    out = step_i(jnp.float32(0))
    float(np.asarray(out[1])[0])     # compile + warmup, forced to completion
    t0 = time.perf_counter()
    for i in range(iters):
        out = step_i(jnp.float32(i + 1))
    float(np.asarray(out[1])[0])     # stream order ⇒ all iters completed
    dt = (time.perf_counter() - t0) / iters
    return n / dt, out


def bench_cpu(gids, ts, metrics):
    """CPU columnar baseline: pandas groupby-max over identical data."""
    import pandas as pd
    df = pd.DataFrame({"g": gids})
    for i, m in enumerate(metrics):
        df[f"m{i}"] = m
    t0 = time.perf_counter()
    df.groupby("g").agg({f"m{i}": "max" for i in range(5)})
    dt = time.perf_counter() - t0
    return len(gids) / dt


def bench_cold_e2e(n_rows: int):
    """Second driver metric: cold single-groupby Mrows/s over a small
    REGION PERSISTED THROUGH THE REAL WRITE PATH — parquet decode →
    lean slice reduce → fold, via frontend.do_query with the scan cache
    cleared. The flagship kernel number above has been flat for rounds
    while the actual work moved to this path; carrying both makes a
    regression in either visible (ISSUE 1 satellite)."""
    import shutil
    import tempfile

    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    from greptimedb_tpu.query import stream_exec, tpu_exec
    from greptimedb_tpu.session import QueryContext

    tmpdir = tempfile.mkdtemp(prefix="bench-cold-")
    fe = None
    saved_threshold = stream_exec.stream_threshold_rows()
    try:
        dn = DatanodeInstance(DatanodeOptions(
            data_home=tmpdir, register_numbers_table=False))
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        ctx = QueryContext()
        fe.do_query("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP "
                    "TIME INDEX, usage_user DOUBLE, "
                    "PRIMARY KEY(hostname))")
        table = fe.catalog.table("greptime", "public", "cpu")
        rng = np.random.default_rng(7)
        hosts = 500
        per = n_rows // hosts
        ts = np.tile(np.arange(per, dtype=np.int64) * 10_000, hosts)
        host = np.repeat(
            np.array([f"host_{i}" for i in range(hosts)]),
            per).astype(object)
        table.bulk_load({"hostname": host, "ts": ts,
                         "usage_user": rng.random(len(ts)) * 100})
        n = hosts * per
        sql = "SELECT hostname, avg(usage_user) FROM cpu GROUP BY hostname"
        stream_exec.configure_streaming(threshold_rows=1)
        fe.do_query(sql, ctx)              # absorb one-time costs
        dt = float("inf")
        for _ in range(2):                 # best of 2: noisy shared hosts
            tpu_exec.SCAN_CACHE._entries.clear()
            t0 = time.perf_counter()
            fe.do_query(sql, ctx)
            dt = min(dt, time.perf_counter() - t0)
        # stage breakdown of the final run: the scan profiler +
        # ExecStats collector (so BENCH rounds capture where the time
        # went, not just the headline rate — ISSUE 2 satellite)
        region = next(iter(table.regions.values()))
        sp = region.last_scan_profile
        st = fe.query_engine.last_exec_stats
        profile = {
            "scan_profile": None if sp is None else {
                "path": sp.path, "rows": sp.rows,
                "total_s": round(sp.total_s, 4),
                "stages": {k: round(v, 4)
                           for k, v in sp.stages.items()},
                "counters": sp.counters,
            },
            "exec_stats": None if st is None else {
                "dispatch": st.dispatch,
                "stages": {s.stage: {"rows": s.rows, "files": s.files,
                                     "ms": round(s.elapsed_s * 1e3, 2)}
                           for s in st.stages.values()},
            },
        }
        return n / dt, profile             # rows/sec + stage breakdown
    finally:
        # the streaming threshold is process-global: restore it so any
        # metric added after this one measures the normal dispatch, and
        # stop the engine's background workers before deleting their dir
        stream_exec.configure_streaming(threshold_rows=saved_threshold)
        if fe is not None:
            fe.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)


def bench_rollup_e2e(n_rows: int):
    """Third driver metric: rollup-served double-groupby throughput
    (ISSUE 3). A 1s→1m flow folds the region once; the timed query is
    the same GROUP BY (host, 5m bucket) aggregate served cold through
    the `rollup-rewrite` dispatch — the scan cache is cleared every
    iteration, so the win measured is "aggregate table vs raw SSTs",
    not cache warmth. Value is EFFECTIVE raw-row throughput: raw rows
    the answer covers / elapsed. `vs_raw_scan` is the speedup against
    the identical query with the rewrite disabled (cold raw scan)."""
    import shutil
    import tempfile

    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    from greptimedb_tpu.query import tpu_exec
    from greptimedb_tpu.session import QueryContext

    tmpdir = tempfile.mkdtemp(prefix="bench-rollup-")
    fe = None
    try:
        dn = DatanodeInstance(DatanodeOptions(
            data_home=tmpdir, register_numbers_table=False))
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        ctx = QueryContext()
        fe.do_query("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP "
                    "TIME INDEX, usage_user DOUBLE, "
                    "PRIMARY KEY(hostname))")
        table = fe.catalog.table("greptime", "public", "cpu")
        rng = np.random.default_rng(7)
        hosts = 500
        per = n_rows // hosts
        ts = np.tile(np.arange(per, dtype=np.int64) * 1_000, hosts)
        host = np.repeat(
            np.array([f"host_{i}" for i in range(hosts)]),
            per).astype(object)
        table.bulk_load({"hostname": host, "ts": ts,
                         "usage_user": rng.random(len(ts)) * 100})
        n = hosts * per
        fe.do_query(
            "CREATE FLOW cpu_1m AS SELECT hostname, "
            "date_bin(INTERVAL '1 minute', ts) AS b, "
            "sum(usage_user) AS u_sum, count(usage_user) AS u_cnt "
            "FROM cpu GROUP BY hostname, b", ctx)
        dn.flow_manager.tick()             # fold once, off the clock
        sql = ("SELECT hostname, date_bin(INTERVAL '5 minutes', ts) AS b, "
               "avg(usage_user) FROM cpu GROUP BY hostname, b")
        fe.do_query(sql, ctx)              # absorb one-time costs

        def timed(q):
            dt = float("inf")
            for _ in range(2):             # best of 2: noisy shared hosts
                tpu_exec.SCAN_CACHE._entries.clear()
                t0 = time.perf_counter()
                fe.do_query(q, ctx)
                dt = min(dt, time.perf_counter() - t0)
            return dt

        dt_roll = timed(sql)
        assert "rollup-rewrite" in fe.query_engine.last_exec_stats.dispatch
        fe.do_query("SET rollup_rewrite = 0", ctx)
        dt_raw = timed(sql)
        fe.do_query("SET rollup_rewrite = 1", ctx)
        return n / dt_roll, dt_raw / dt_roll
    finally:
        if fe is not None:
            fe.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)


def bench_ingest_failpoint_overhead(n_rows: int):
    """Fourth driver metric (ISSUE 4): bulk-ingest throughput with the
    failpoint layer compiled in but INACTIVE, differentialed against the
    same ingest with every failpoint call stubbed out entirely. The
    instrumented sites are one module-bool branch each, so the ratio must
    sit inside run-to-run noise — the assert here keeps future
    instrumentation honest."""
    import shutil
    import tempfile
    import timeit

    from greptimedb_tpu.common import failpoint as fp
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)

    assert fp.active_count() == 0
    # (a) raw cost of one inactive fail_point() evaluation
    per_call_ns = timeit.timeit(
        lambda: fp.fail_point("wal_append"), number=1_000_000) * 1e3

    # (b) end-to-end bulk ingest, instrumented vs stubbed
    rng = np.random.default_rng(11)
    hosts = 200
    per = n_rows // hosts
    host = np.repeat(np.array([f"host_{i}" for i in range(hosts)]),
                     per).astype(object)
    ts = np.tile(np.arange(per, dtype=np.int64) * 1000, hosts)
    vals = rng.random(hosts * per)

    def ingest_once() -> float:
        tmpdir = tempfile.mkdtemp(prefix="bench-fp-")
        try:
            dn = DatanodeInstance(DatanodeOptions(
                data_home=tmpdir, register_numbers_table=False))
            dn.start()
            from greptimedb_tpu.frontend.instance import FrontendInstance
            fe = FrontendInstance(dn)
            fe.start()
            fe.do_query("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP "
                        "TIME INDEX, usage_user DOUBLE, "
                        "PRIMARY KEY(hostname))")
            table = fe.catalog.table("greptime", "public", "cpu")
            t0 = time.perf_counter()
            table.bulk_load({"hostname": host, "ts": ts,
                             "usage_user": vals})
            dt = time.perf_counter() - t0
            fe.shutdown()
            return dt
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)

    ingest_once()                         # absorb one-time costs
    # interleave the two configurations (best of 2 each) so slow-drift
    # on a shared box lands on both sides of the differential
    saved = (fp.fail_point, fp.fires)
    dt_instrumented = dt_stubbed = float("inf")
    try:
        for _ in range(2):
            fp.fail_point, fp.fires = saved
            dt_instrumented = min(dt_instrumented, ingest_once())
            fp.fail_point = lambda name: None   # the layer compiled "out"
            fp.fires = lambda name: False
            dt_stubbed = min(dt_stubbed, ingest_once())
    finally:
        fp.fail_point, fp.fires = saved
    ratio = dt_stubbed / dt_instrumented  # 1.0 = zero overhead
    # instrumented must stay within noise of stubbed-out: on a 2-vCPU
    # shared box run-to-run jitter is ~±10%; a 30% wall-clock regression
    # would mean someone put a failpoint in a per-row loop
    assert ratio >= 0.7, (
        f"inactive failpoint layer cost {1/ratio:.2f}x on bulk ingest")
    return len(ts) / dt_instrumented, ratio, per_call_ns


def bench_self_monitoring_overhead(n_rows: int):
    """Seventh driver metric (ISSUE 8): bulk-ingest throughput with the
    self-monitoring scraper ticking aggressively in the background
    (0.5s cadence — 60x the production default) vs with it off, same
    interleaved best-of-2 differential as the failpoint assertion. The
    scraper writes its registry snapshot through the normal ingest path
    under telemetry.suppress_metrics, so the only cost the user ingest
    can see is the scrape writes' share of the box — the target is <3%
    at the PRODUCTION cadence, which the 60x-tightened loop bounds from
    far above."""
    import shutil
    import tempfile

    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)

    rng = np.random.default_rng(13)
    hosts = 200
    per = n_rows // hosts
    host = np.repeat(np.array([f"host_{i}" for i in range(hosts)]),
                     per).astype(object)
    ts = np.tile(np.arange(per, dtype=np.int64) * 1000, hosts)
    vals = rng.random(hosts * per)

    def ingest_once(monitor: bool) -> "tuple[float, int]":
        tmpdir = tempfile.mkdtemp(prefix="bench-mon-")
        try:
            dn = DatanodeInstance(DatanodeOptions(
                data_home=tmpdir, register_numbers_table=False,
                self_monitor_interval_s=0))   # cadence driven explicitly
            dn.start()
            from greptimedb_tpu.frontend.instance import FrontendInstance
            fe = FrontendInstance(dn)
            fe.start()
            fe.do_query("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP "
                        "TIME INDEX, usage_user DOUBLE, "
                        "PRIMARY KEY(hostname))")
            if monitor:
                fe.self_monitor.tick()         # tables exist up front
                fe.self_monitor.start_background(0.5)
            table = fe.catalog.table("greptime", "public", "cpu")
            t0 = time.perf_counter()
            table.bulk_load({"hostname": host, "ts": ts,
                             "usage_user": vals})
            dt = time.perf_counter() - t0
            ticks = int(fe.self_monitor.stats["ticks"]) if monitor else 0
            fe.shutdown()
            return dt, ticks
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)

    ingest_once(False)                        # absorb one-time costs
    dt_on = dt_off = float("inf")
    ticks_seen = 0
    for _ in range(2):
        dt, ticks = ingest_once(True)
        dt_on = min(dt_on, dt)
        ticks_seen = max(ticks_seen, ticks)
        dt, _ = ingest_once(False)
        dt_off = min(dt_off, dt)
    overhead = dt_on / dt_off - 1.0           # 0.0 = free
    return len(ts) / dt_on, overhead, ticks_seen


def bench_trace_store_overhead(n_rows: int):
    """Tenth driver metric (ISSUE 15): bulk-ingest + mixed small-query
    throughput with the durable trace store's sink at sample ratio 1.0
    (worst case: EVERY trace retained, buffered and written) and at the
    production default 0.01, against the sink uninstalled. The <3% bar
    binds at the default ratio — the PR 8 self-monitoring precedent."""
    import shutil
    import tempfile

    from greptimedb_tpu.common import trace_store
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)

    rng = np.random.default_rng(17)
    hosts = 200
    per = n_rows // hosts
    host = np.repeat(np.array([f"host_{i}" for i in range(hosts)]),
                     per).astype(object)
    ts = np.tile(np.arange(per, dtype=np.int64) * 1000, hosts)
    vals = rng.random(hosts * per)
    n_queries = 300

    def run_once(ratio) -> "tuple[float, float]":
        """(bulk_ingest_s, mixed_query_s + trace_flush_s) for one
        configuration; ratio=None uninstalls the sink entirely. The
        flush that writes retained spans into trace_spans is TIMED —
        at ratio 1.0 it IS the dominant bill, and excluding it would
        let a write-path regression pass the <3% assert."""
        tmpdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            dn = DatanodeInstance(DatanodeOptions(
                data_home=tmpdir, register_numbers_table=False,
                self_monitor_interval_s=0))
            dn.start()
            from greptimedb_tpu.frontend.instance import FrontendInstance
            fe = FrontendInstance(dn)
            fe.start()
            if ratio is None:
                trace_store.install(None)
            else:
                trace_store.configure(sample_ratio=ratio)
            fe.do_query("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP "
                        "TIME INDEX, usage_user DOUBLE, "
                        "PRIMARY KEY(hostname))")
            table = fe.catalog.table("greptime", "public", "cpu")
            t0 = time.perf_counter()
            table.bulk_load({"hostname": host, "ts": ts,
                             "usage_user": vals})
            ingest_dt = time.perf_counter() - t0
            t0 = time.perf_counter()
            for i in range(n_queries):
                fe.do_query(f"SELECT usage_user FROM cpu WHERE "
                            f"hostname = 'host_{i % hosts}' LIMIT 5")
            if ratio is not None:
                s = trace_store.sink()
                if s is not None:
                    s.flush()
            query_dt = time.perf_counter() - t0
            fe.shutdown()
            return ingest_dt, query_dt
        finally:
            trace_store.install(None)
            trace_store.configure(sample_ratio=0.01)
            shutil.rmtree(tmpdir, ignore_errors=True)

    run_once(None)                               # absorb one-time costs
    best = {}
    for _ in range(2):                           # interleaved best-of-2
        for key, ratio in (("off", None), ("full", 1.0),
                           ("default", 0.01)):
            ing, q = run_once(ratio)
            b = best.get(key, (float("inf"), float("inf")))
            best[key] = (min(b[0], ing), min(b[1], q))
    ing_off, q_off = best["off"]
    ing_full, q_full = best["full"]
    ing_def, q_def = best["default"]
    overhead_default = (ing_def + q_def) / (ing_off + q_off) - 1.0
    overhead_full = (ing_full + q_full) / (ing_off + q_off) - 1.0
    return (len(ts) / ing_def, overhead_default, overhead_full,
            n_queries / q_def)


def bench_profiler_overhead(n_rows: int):
    """Eleventh driver metric (ISSUE 17): mixed bulk-ingest + small-query
    throughput with the continuous profiler sampling at the default
    19 Hz, against the sampler disabled. The sampler holds the GIL for
    each sys._current_frames() walk, so the bill is real but bounded by
    the rate — the <3% bar binds at the default."""
    import shutil
    import tempfile

    from greptimedb_tpu.common import profiler

    rng = np.random.default_rng(23)
    hosts = 200
    per = n_rows // hosts
    host = np.repeat(np.array([f"host_{i}" for i in range(hosts)]),
                     per).astype(object)
    ts = np.tile(np.arange(per, dtype=np.int64) * 1000, hosts)
    vals = rng.random(hosts * per)
    n_queries = 300

    def run_once(enabled: bool) -> float:
        """Wall seconds for one ingest + query pass, profiler on/off.
        The flush that persists the sampled window is TIMED — it is
        part of the feature's bill exactly like the trace store's."""
        from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                      DatanodeOptions)
        from greptimedb_tpu.frontend.instance import FrontendInstance
        tmpdir = tempfile.mkdtemp(prefix="bench-prof-")
        try:
            dn = DatanodeInstance(DatanodeOptions(
                data_home=tmpdir, register_numbers_table=False,
                self_monitor_interval_s=0))
            dn.start()
            fe = FrontendInstance(dn)
            fe.start()
            profiler.configure(enabled=enabled, hz=19.0)
            fe.do_query("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP "
                        "TIME INDEX, usage_user DOUBLE, "
                        "PRIMARY KEY(hostname))")
            table = fe.catalog.table("greptime", "public", "cpu")
            t0 = time.perf_counter()
            table.bulk_load({"hostname": host, "ts": ts,
                             "usage_user": vals})
            for i in range(n_queries):
                fe.do_query(f"SELECT usage_user FROM cpu WHERE "
                            f"hostname = 'host_{i % hosts}' LIMIT 5")
            if enabled:
                fe.profiler.flush()
            dt = time.perf_counter() - t0
            fe.shutdown()
            return dt
        finally:
            profiler.configure(enabled=False)
            profiler.install(None)
            shutil.rmtree(tmpdir, ignore_errors=True)

    run_once(False)                              # absorb one-time costs
    best = {"off": float("inf"), "on": float("inf")}
    for _ in range(2):                           # interleaved best-of-2
        best["off"] = min(best["off"], run_once(False))
        best["on"] = min(best["on"], run_once(True))
    overhead = best["on"] / best["off"] - 1.0
    return overhead, len(ts) / best["on"], n_queries / best["on"]


def emit_profiler_overhead():
    rows = int(os.environ.get("GREPTIME_BENCH_PROF_ROWS", 2_000_000))
    overhead, rps, qps = bench_profiler_overhead(rows)
    assert overhead < 0.03, (
        f"continuous profiler costs {overhead:.1%} at the default "
        f"19 Hz — the bar is <3%")
    print(json.dumps({
        "metric": "profiler_overhead",
        "value": round(overhead * 100, 2),
        "unit": "percent",
        "sample_hz": 19.0,
        "ingest_mrows_s_sampling": round(rps / 1e6, 2),
        "point_qps_sampling": round(qps, 1),
        "rows": rows,
    }))


def emit_trace_store_overhead():
    rows = int(os.environ.get("GREPTIME_BENCH_TRACE_ROWS", 2_000_000))
    rps, overhead_default, overhead_full, qps = \
        bench_trace_store_overhead(rows)
    assert overhead_default < 0.03, (
        f"trace store costs {overhead_default:.1%} at the default "
        f"0.01 sample ratio — the bar is <3%")
    print(json.dumps({
        "metric": "trace_store_overhead",
        "value": round(overhead_default * 100, 2),
        "unit": "percent",
        "overhead_at_ratio_1_pct": round(overhead_full * 100, 2),
        "ingest_mrows_s_at_default": round(rps / 1e6, 2),
        "point_qps_at_default": round(qps, 1),
        "rows": rows,
    }))


def bench_concurrent_qps(n_clients: int = 1000):
    """Eighth driver metric (ISSUE 12): the missing dimension — sustained
    QPS × tail latency under a 1000-logical-client MIXED workload (small
    point scans + remote-write bursts through the ingest coalescer)
    against a persisted region, plus the WAL group-commit on/off
    differential on fsync-enabled concurrent ingest.

    The differential is published twice: `raw` on this box's fsync (a
    VM write cache makes fsync ~0.15 ms, cheaper than the Python write
    path, so raw barely moves), and `fsync2ms` with a modeled 2 ms
    device sync via the existing wal_fsync delay failpoint — the
    hardware-independent number (same technique as the dist-scatter
    metric's modeled 10 ms RPC hop). The assert keeps the modeled
    differential honest."""
    import shutil
    import tempfile
    import threading
    import timeit
    from queue import Queue

    from greptimedb_tpu.common import failpoint as fp
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    from greptimedb_tpu.servers.coalesce import COALESCER
    from greptimedb_tpu.session import QueryContext
    from greptimedb_tpu.storage.wal import Wal, configure_group_commit
    from greptimedb_tpu.storage.write_batch import WriteBatch

    # ---- (a) raw Wal.append cost (the hoisted-import satellite) ----
    wal_dir = tempfile.mkdtemp(prefix="bench-qps-wal-")
    w = Wal(wal_dir, sync_on_write=False)
    seq_box = [0]

    def one_append():
        seq_box[0] += 1
        w.append(seq_box[0], b"x" * 64)

    append_ns = timeit.timeit(one_append, number=50_000) / 50_000 * 1e9
    w.close()
    shutil.rmtree(wal_dir, ignore_errors=True)

    # ---- (b) group-commit differential on fsync-enabled ingest ----
    from greptimedb_tpu.datatypes import Schema
    from greptimedb_tpu.datatypes.data_type import (
        FLOAT64, STRING, TIMESTAMP_MILLISECOND)
    from greptimedb_tpu.datatypes.schema import ColumnSchema, SemanticType
    from greptimedb_tpu.storage.object_store import FsObjectStore
    from greptimedb_tpu.storage.region import Region, RegionDescriptor

    schema = Schema([
        ColumnSchema("host", STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("v", FLOAT64),
    ])
    n_threads, per, rows_per = 16, 8, 20

    def sync_ingest_once(group_on: bool, delay_ms: int) -> float:
        configure_group_commit(enabled=group_on)
        home = tempfile.mkdtemp(prefix="bench-qps-gc-")
        try:
            region = Region.create(
                RegionDescriptor("gc", schema, "gc",
                                 os.path.join(home, "wal")),
                FsObjectStore(os.path.join(home, "data")),
                wal=Wal(os.path.join(home, "wal"), sync_on_write=True))
            errs = []

            def writer(i):
                try:
                    for j in range(per):
                        wb = WriteBatch(region.schema)
                        base = (i * per + j) * rows_per
                        wb.put({"host": [f"h{i}"] * rows_per,
                                "ts": list(range(base, base + rows_per)),
                                "v": [1.0] * rows_per})
                        region.write(wb)
                except Exception as e:  # noqa: BLE001 — assert below
                    errs.append(e)

            threads = [threading.Thread(target=writer, args=(i,))
                       for i in range(n_threads)]
            import contextlib
            ctx = fp.cfg("wal_fsync", f"delay({delay_ms})") if delay_ms \
                else contextlib.nullcontext()
            with ctx:
                t0 = time.perf_counter()
                [t.start() for t in threads]
                [t.join() for t in threads]
                dt = time.perf_counter() - t0
            assert not errs, errs
            got = region.snapshot().read_merged().num_rows
            assert got == n_threads * per * rows_per, got
            region.close()
            return dt
        finally:
            shutil.rmtree(home, ignore_errors=True)

    sync_ingest_once(True, 0)                 # absorb one-time costs
    ratios = {}
    for label, delay in (("raw", 0), ("fsync2ms", 2)):
        dt_on = min(sync_ingest_once(True, delay) for _ in range(2))
        dt_off = min(sync_ingest_once(False, delay) for _ in range(2))
        ratios[label] = dt_off / dt_on
    configure_group_commit(enabled=True)
    assert ratios["fsync2ms"] > 1.5, (
        f"group commit only {ratios['fsync2ms']:.2f}x on modeled-fsync "
        f"concurrent ingest — the shared fsync is not being shared")

    # ---- (c) 1000-logical-client mixed workload over a persisted
    # region: sustained QPS and p50/p95/p99 ----
    home = tempfile.mkdtemp(prefix="bench-qps-")
    try:
        dn = DatanodeInstance(DatanodeOptions(
            data_home=home, register_numbers_table=False))
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        fe.do_query("CREATE TABLE qps (host STRING, ts TIMESTAMP "
                    "TIME INDEX, v DOUBLE, PRIMARY KEY(host))")
        table = fe.catalog.table("greptime", "public", "qps")
        hosts = 64
        per_host = 512
        host_col = np.repeat(
            np.array([f"h{i}" for i in range(hosts)]), per_host
        ).astype(object)
        ts_col = np.tile(
            np.arange(per_host, dtype=np.int64) * 1000, hosts)
        table.bulk_load({"host": host_col, "ts": ts_col,
                         "v": np.random.default_rng(7).random(
                             hosts * per_host)})
        table.flush()                          # persisted region

        ops_per_client = 4                     # 3 point scans + 1 burst
        latencies = []
        lat_lock = threading.Lock()
        work: "Queue[int]" = Queue()
        for c in range(n_clients):
            work.put(c)
        errs = []

        def client_ops(c: int):
            ctx = QueryContext()
            local = []
            for k in range(ops_per_client):
                t0 = time.perf_counter()
                if k < 3:
                    fe.do_query(
                        f"SELECT v FROM qps WHERE host = "
                        f"'h{(c * 7 + k) % hosts}' LIMIT 5")
                else:
                    COALESCER.ingest(
                        fe, "qps_rw",
                        {"ts": [int(time.time() * 1000) + c],
                         "host": [f"h{c % hosts}"],
                         "v": [float(c)]},
                        tag_columns=("host",), timestamp_column="ts",
                        ctx=ctx)
                local.append(time.perf_counter() - t0)
            with lat_lock:
                latencies.extend(local)

        def worker():
            while True:
                try:
                    c = work.get_nowait()
                except Exception:  # noqa: BLE001 — queue drained
                    return
                try:
                    client_ops(c)
                except Exception as e:  # noqa: BLE001 — assert below
                    errs.append(e)

        n_workers = 32
        threads = [threading.Thread(target=worker)
                   for _ in range(n_workers)]
        t0 = time.perf_counter()
        [t.start() for t in threads]
        [t.join() for t in threads]
        wall = time.perf_counter() - t0
        assert not errs, errs[:3]
        assert len(latencies) == n_clients * ops_per_client
        lat_ms = np.sort(np.array(latencies)) * 1e3
        qps = len(latencies) / wall
        p50, p95, p99 = (float(np.percentile(lat_ms, p))
                         for p in (50, 95, 99))
        fe.shutdown()
    finally:
        shutil.rmtree(home, ignore_errors=True)
    return qps, p50, p95, p99, ratios, append_ns


def bench_lock_overhead():
    """Sixth driver metric (ISSUE 7): the lock-order detector's
    inactive-mode cost, same methodology as the failpoint ~190ns/call
    assertion. The TrackedLock factory must hand back a PLAIN
    threading.Lock when the detector is off — production acquires pay
    literally zero extra — so the differential against threading.Lock
    is asserted structurally (identical type) AND by wall clock."""
    import threading
    import timeit

    from greptimedb_tpu.common import locks

    # bench.py never imports pytest, so auto-detection leaves the
    # detector off unless the operator forced it via env
    assert not locks.enabled(), (
        "detector unexpectedly ON in bench (GREPTIME_LOCK_CHECK set, or "
        "pytest leaked into the process) — inactive-mode numbers would "
        "be meaningless")
    tracked = locks.TrackedLock("bench.lock")
    raw = threading.Lock()
    assert type(tracked) is type(raw), (
        "inactive TrackedLock must BE threading.Lock, not a wrapper")

    n = 1_000_000

    def cycle(lk):
        def run():
            lk.acquire()
            lk.release()
        return run

    # interleave best-of-3 so shared-box drift lands on both sides
    t_tracked = t_raw = float("inf")
    for _ in range(3):
        t_tracked = min(t_tracked, timeit.timeit(cycle(tracked), number=n))
        t_raw = min(t_raw, timeit.timeit(cycle(raw), number=n))
    ns_tracked = t_tracked / n * 1e9
    ns_raw = t_raw / n * 1e9
    ratio = t_raw / t_tracked            # 1.0 = zero overhead
    # same objects, same type: anything past noise means the factory
    # started wrapping inactive locks
    assert ratio >= 0.7, (
        f"inactive TrackedLock cost {1/ratio:.2f}x a raw threading.Lock "
        f"({ns_tracked:.1f}ns vs {ns_raw:.1f}ns per acquire/release)")

    # active-mode cost, for the record (what tests pay, never production)
    forced = locks.TrackedLock("bench.lock_active", force=True)
    t_active = timeit.timeit(cycle(forced), number=n // 10)
    ns_active = t_active / (n // 10) * 1e9
    return ns_tracked, ns_raw, ratio, ns_active


def bench_greptsan_inactive_overhead():
    """ISSUE 10: greptsan's off-mode cost, held to the same bar as
    tracked_lock_inactive_overhead. tracked_state() is a FACTORY that
    returns its argument unchanged when the race detector is off, so
    the wrapped dict IS a plain dict — the identity assert below is the
    real regression detector (any wrapping in off mode fails it first),
    while the timed get/set/contains cycle on a region-map-shaped dict
    (same object on both sides, by construction) publishes the noise
    floor the <1.1x acceptance bar is read against — the
    bench_lock_overhead methodology exactly."""
    import timeit

    from greptimedb_tpu.devtools import greptsan

    assert not greptsan.enabled(), (
        "race detector unexpectedly ON in bench (GREPTIME_RACE_CHECK "
        "set, or pytest leaked in) — inactive numbers would be "
        "meaningless")
    raw = {f"region_{i}": i for i in range(64)}
    wrapped = greptsan.tracked_state(raw, "bench.regions")
    assert wrapped is raw and type(wrapped) is dict, (
        "inactive tracked_state must return its argument unchanged")

    n = 1_000_000

    def cycle(d):
        def run():
            d["region_7"] = 7
            d.get("region_9")
            "region_11" in d
        return run

    t_wrapped = t_raw = float("inf")
    for _ in range(3):       # interleave best-of-3: drift lands on both
        t_wrapped = min(t_wrapped, timeit.timeit(cycle(wrapped),
                                                 number=n))
        t_raw = min(t_raw, timeit.timeit(cycle(raw), number=n))
    ns_wrapped = t_wrapped / n * 1e9
    ns_raw = t_raw / n * 1e9
    ratio = t_wrapped / t_raw            # 1.0 = zero overhead
    # same noise tolerance as bench_lock_overhead's inactive ratio
    # (its >=0.7 bar): the identity assert above already catches any
    # real off-mode wrapping, so the timing bound only needs to reject
    # gross regressions, not flake on shared-box drift. The published
    # inactive_ratio is what the <1.1x acceptance reading uses.
    assert ratio <= 1 / 0.7, (
        f"inactive tracked_state cost {ratio:.2f}x a raw dict "
        f"({ns_wrapped:.1f}ns vs {ns_raw:.1f}ns per cycle) — beyond "
        f"even shared-box noise for what must be the SAME object")

    # active-mode cost for the record (tests only): per-access record +
    # vector-clock race check on a tracked dict
    import subprocess
    import sys
    code = (
        "import timeit\n"
        "from greptimedb_tpu.devtools import greptsan\n"
        "assert greptsan.enabled()\n"
        "d = greptsan.tracked_state({'k': 1}, 'bench.active')\n"
        "t = timeit.timeit(lambda: d.get('k'), number=100000)\n"
        "print(t / 100000 * 1e9)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, GREPTIME_RACE_CHECK="1",
                              JAX_PLATFORMS="cpu"))
    ns_active = float(proc.stdout.strip()) if proc.returncode == 0 \
        else float("nan")
    return ns_wrapped, ns_raw, ratio, ns_active


def bench_dist_scatter(n_rows: int):
    """Fifth driver metric (ISSUE 5): multi-datanode group-by through the
    distributed frontend. 4 in-process datanodes host an 8-region
    hash-partitioned table; the timed query is a full-table GROUP BY
    (hostname) avg, cold (scan cache cleared per iteration, so each
    datanode pays SST decode + merge + reduce). Two differentials
    against SET dist_fanout = 1 (the pre-PR serial fan-out):

    - ``vs_serial`` — cold, same-process, compute-bound run. On a box
      with fewer cores than datanodes this approaches 1.0 (the serial
      path already saturates the cores through XLA/numpy intra-op
      threads); it expresses the parallel win only when
      cores >= datanodes.
    - ``vs_serial_warm_10ms_rpc`` — the warm dashboard shape: scan
      caches hot, and each datanode RPC carries a modeled 10ms
      network+queueing latency (dist_rpc failpoint, action delay(10) —
      what every real multi-host hop pays). Serial sums the four hops,
      the scatter overlaps them; this is the hardware-independent
      measure of the fan-out mechanism itself.

    Also probes the acceptance criterion: a tag-point query must report
    `regions pruned 7/8` in its dispatch."""
    import shutil
    import tempfile

    from greptimedb_tpu.common import failpoint

    from greptimedb_tpu.client import LocalDatanodeClient
    from greptimedb_tpu.common.runtime import (configure_dist_fanout,
                                               dist_fanout)
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.distributed import DistInstance
    from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
    from greptimedb_tpu.query import tpu_exec
    from greptimedb_tpu.session import QueryContext

    tmpdir = tempfile.mkdtemp(prefix="bench-dist-")
    datanodes = {}
    saved_fanout = dist_fanout()
    try:
        srv = MetaSrv(MemKv())
        meta = MetaClient(srv)
        clients = {}
        for i in range(1, 5):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=f"{tmpdir}/dn{i}", node_id=i,
                register_numbers_table=False))
            dn.start()
            datanodes[i] = dn
            clients[i] = LocalDatanodeClient(dn)
            srv.register_datanode(Peer(i, f"dn{i}"))
            srv.handle_heartbeat(i)
        fe = DistInstance(meta, clients)
        ctx = QueryContext()
        fe.do_query(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP TIME INDEX, "
            "usage_user DOUBLE, PRIMARY KEY(hostname)) "
            "PARTITION BY HASH (hostname) PARTITIONS 8", ctx)
        table = fe.catalog.table("greptime", "public", "cpu")
        rng = np.random.default_rng(7)
        hosts = 256
        per = n_rows // hosts
        ts = np.tile(np.arange(per, dtype=np.int64) * 10_000, hosts)
        host = np.repeat(
            np.array([f"host_{i}" for i in range(hosts)]),
            per).astype(object)
        table.bulk_load({"hostname": host, "ts": ts,
                         "usage_user": rng.random(len(ts)) * 100})
        table.flush()
        n = hosts * per
        sql = ("SELECT hostname, avg(usage_user) FROM cpu "
               "GROUP BY hostname")
        fe.do_query(sql, ctx)              # absorb one-time costs

        def timed(cold: bool, iters: int = 2, node_ms_out: dict = None):
            dt = float("inf")
            for _ in range(iters):         # best of N: noisy shared hosts
                if cold:
                    tpu_exec.SCAN_CACHE._entries.clear()
                t0 = time.perf_counter()
                fe.do_query(sql, ctx)
                it = time.perf_counter() - t0
                if it < dt and node_ms_out is not None:
                    # snapshot the vector of the BEST iteration, so the
                    # emitted per-node breakdown profiles the same run
                    # as the throughput published next to it
                    node_ms_out.clear()
                    node_ms_out.update(table.last_scatter_node_ms)
                dt = min(dt, it)
            return dt

        configure_dist_fanout(8)
        # per-node latency vector of the winning parallel scatter (ISSUE
        # 6: the per-node timings the old slowest_node_ms max discarded)
        from greptimedb_tpu.common.exec_stats import node_sort_key
        best_node_ms: dict = {}
        dt_parallel = timed(cold=True, node_ms_out=best_node_ms)
        node_ms = {k: round(best_node_ms[k], 2)
                   for k in sorted(best_node_ms, key=node_sort_key)}
        configure_dist_fanout(1)           # the pre-PR serial scatter
        dt_serial = timed(cold=True)

        # warm + modeled per-RPC network latency: the hop cost every
        # real multi-host hop pays, which the scatter exists to overlap
        fe.do_query(sql, ctx)              # heat every region's cache
        failpoint.configure("dist_rpc", "delay(10)")
        try:
            configure_dist_fanout(8)
            dt_par_net = timed(cold=False, iters=3)
            configure_dist_fanout(1)
            dt_ser_net = timed(cold=False, iters=3)
        finally:
            failpoint.configure("dist_rpc", None)
        configure_dist_fanout(8)

        fe.do_query("SELECT hostname, avg(usage_user) FROM cpu "
                    "WHERE hostname = 'host_7' GROUP BY hostname", ctx)
        dispatch = fe.query_engine.last_exec_stats.dispatch
        assert "regions pruned 7/8" in dispatch, dispatch
        return (n / dt_parallel, dt_serial / dt_parallel,
                dt_ser_net / dt_par_net, node_ms)
    finally:
        configure_dist_fanout(saved_fanout)
        for dn in datanodes.values():
            dn.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)


def _record_batches_bytes(batches):
    """Bytes a raw-row scatter ships: column buffers (+ validity), with
    object/string columns measured by their encoded text lengths."""
    total = 0
    for b in batches:
        for v in b.columns:
            data = getattr(v, "data", None)
            if data is None:
                continue
            if getattr(data, "dtype", None) is not None and \
                    data.dtype == object:
                total += int(sum(len(str(x)) for x in data
                                 if x is not None))
            else:
                total += int(getattr(data, "nbytes", 0) or 0)
            validity = getattr(v, "validity", None)
            if validity is not None:
                total += int(getattr(validity, "nbytes", 0) or 0)
    return total


def bench_dist_partial_agg(n_rows: int):
    """Seventh driver metric (ISSUE 14): distributed GROUP BY through
    the sketch partial pushdown. 4 in-process datanodes host an
    8-region hash table; the timed query is the TSBS-ish wide shape —
    GROUP BY tag with count / count(DISTINCT) / approx_percentile(95)
    — which before this PR fell back to pulling RAW ROWS from every
    region. Differential: `SET dist_partial_agg = 0` (the raw-row
    fallback). Published: rows/s through the pushdown, the speedup vs
    raw, and the wire-byte comparison — partial frames actually folded
    (ExecStats partial_bytes) vs the bytes a raw scatter ships
    (projected scan batches) — asserted >= 3x smaller."""
    import shutil
    import tempfile

    from greptimedb_tpu.client import LocalDatanodeClient
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.distributed import DistInstance
    from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
    from greptimedb_tpu.session import QueryContext

    tmpdir = tempfile.mkdtemp(prefix="bench-distagg-")
    datanodes = {}
    try:
        srv = MetaSrv(MemKv())
        meta = MetaClient(srv)
        clients = {}
        for i in range(1, 5):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=f"{tmpdir}/dn{i}", node_id=i,
                register_numbers_table=False))
            dn.start()
            datanodes[i] = dn
            clients[i] = LocalDatanodeClient(dn)
            srv.register_datanode(Peer(i, f"dn{i}"))
            srv.handle_heartbeat(i)
        fe = DistInstance(meta, clients)
        ctx = QueryContext()
        fe.do_query(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP TIME INDEX, "
            "usage_user DOUBLE, uid BIGINT, PRIMARY KEY(hostname)) "
            "PARTITION BY HASH (hostname) PARTITIONS 8", ctx)
        table = fe.catalog.table("greptime", "public", "cpu")
        rng = np.random.default_rng(11)
        hosts = 256
        per = n_rows // hosts
        ts = np.tile(np.arange(per, dtype=np.int64) * 10_000, hosts)
        host = np.repeat(
            np.array([f"host_{i}" for i in range(hosts)]),
            per).astype(object)
        # uid: ~2000 revisiting users — the classic "distinct users per
        # host" cardinality shape count(DISTINCT) exists for
        table.bulk_load({"hostname": host, "ts": ts,
                         "usage_user": rng.random(len(ts)) * 100,
                         "uid": rng.integers(0, 2000, len(ts))})
        table.flush()
        n = hosts * per
        sql = ("SELECT hostname, count(usage_user) AS c, "
               "count(DISTINCT uid) AS cd, "
               "approx_percentile(usage_user, 95) AS p95 "
               "FROM cpu GROUP BY hostname")

        def timed(iters=2):
            dt = float("inf")
            for _ in range(iters):
                t0 = time.perf_counter()
                fe.do_query(sql, ctx)
                dt = min(dt, time.perf_counter() - t0)
            return dt

        fe.do_query(sql, ctx)              # warm caches + compiles
        dt_partial = timed()
        stats = fe.query_engine.last_exec_stats
        assert "aggregate-pushdown" in (stats.dispatch or ""), \
            stats.dispatch
        partial_bytes = stats.totals()["partial_bytes"]
        assert partial_bytes > 0

        # the raw-row differential: what the pre-PR fallback shipped
        raw_bytes = _record_batches_bytes(table.scan_batches(
            projection=["hostname", "ts", "usage_user", "uid"]))
        fe.do_query("SET dist_partial_agg = 0", ctx)
        try:
            fe.do_query(sql, ctx)
            dt_raw = timed()
        finally:
            fe.do_query("SET dist_partial_agg = 1", ctx)
        reduction = raw_bytes / max(partial_bytes, 1)
        assert reduction >= 3.0, (raw_bytes, partial_bytes, reduction)
        return (n / dt_partial, dt_raw / dt_partial, partial_bytes,
                raw_bytes, reduction)
    finally:
        for dn in datanodes.values():
            dn.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)


def emit_dist_partial_agg():
    n_rows = int(os.environ.get("GREPTIME_BENCH_DISTAGG_ROWS", 2_000_000))
    rps, vs_raw, partial_b, raw_b, reduction = \
        bench_dist_partial_agg(n_rows)
    print(json.dumps({
        "metric": "dist_partial_agg_throughput",
        "value": round(rps / 1e6, 2),
        "unit": "Mrows/s",
        "vs_raw_pull": round(vs_raw, 2),
        "partial_wire_bytes": int(partial_b),
        "raw_wire_bytes": int(raw_b),
        "wire_byte_reduction": round(reduction, 1),
        "rows": n_rows,
        "datanodes": 4,
    }))


def bench_promql_dist_range(n_rows: int):
    """Eleventh driver metric (ISSUE 16): a distributed PromQL range
    query through the plan-IR pushdown. 4 in-process datanodes host an
    8-region hash table; the timed query is the canonical dashboard
    shape — `sum by (hostname) (rate(cpu[1m]))` over the whole span —
    which before this PR pulled RAW SAMPLES from every region to the
    frontend row path. Now it lowers onto the same TpuPlan SQL ships:
    datanodes fold regions into per-(series, bucket) moment frames,
    only frames cross the wire, the frontend reconstructs rate and
    folds by hostname. Differential: `SET dist_partial_agg = 0` (the
    raw-pull row path). Published: rows/s through the IR, the speedup
    vs raw-pull (>= 3x asserted), and the wire-byte comparison —
    moment frames folded (ExecStats partial_bytes) vs the bytes a raw
    scatter ships."""
    import shutil
    import tempfile

    from greptimedb_tpu.client import LocalDatanodeClient
    from greptimedb_tpu.common import exec_stats
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.distributed import DistInstance
    from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
    from greptimedb_tpu.session import QueryContext

    tmpdir = tempfile.mkdtemp(prefix="bench-promql-")
    datanodes = {}
    try:
        srv = MetaSrv(MemKv())
        meta = MetaClient(srv)
        clients = {}
        for i in range(1, 5):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=f"{tmpdir}/dn{i}", node_id=i,
                register_numbers_table=False))
            dn.start()
            datanodes[i] = dn
            clients[i] = LocalDatanodeClient(dn)
            srv.register_datanode(Peer(i, f"dn{i}"))
            srv.handle_heartbeat(i)
        fe = DistInstance(meta, clients)
        ctx = QueryContext()
        fe.do_query(
            "CREATE TABLE cpu (hostname STRING, ts TIMESTAMP TIME INDEX, "
            "usage_user DOUBLE, PRIMARY KEY(hostname)) "
            "PARTITION BY HASH (hostname) PARTITIONS 8", ctx)
        table = fe.catalog.table("greptime", "public", "cpu")
        rng = np.random.default_rng(11)
        hosts = 256
        per = n_rows // hosts
        ts = np.tile(np.arange(per, dtype=np.int64) * 10_000, hosts)
        host = np.repeat(
            np.array([f"host_{i}" for i in range(hosts)]),
            per).astype(object)
        # a counter: monotone per series, the shape rate() exists for
        vals = np.tile(np.cumsum(rng.random(per) * 5.0), hosts)
        table.bulk_load({"hostname": host, "ts": ts, "usage_user": vals})
        table.flush()
        n = hosts * per
        end_s = (per - 1) * 10
        tql = (f"TQL EVAL (0, {end_s}, '60s') "
               "sum by (hostname) (rate(cpu[1m]))")

        def timed(iters=2):
            dt = float("inf")
            for _ in range(iters):
                t0 = time.perf_counter()
                fe.do_query(tql, ctx)
                dt = min(dt, time.perf_counter() - t0)
            return dt

        fe.do_query(tql, ctx)              # warm caches + compiles
        stats = exec_stats.ExecStats()
        with exec_stats.collect(stats):
            fe.do_query(tql, ctx)
        partial_bytes = stats.totals()["partial_bytes"]
        assert partial_bytes > 0, "PromQL did not ride the IR pushdown"
        dt_ir = timed()

        # the raw-pull differential: what the pre-PR row path shipped
        raw_bytes = _record_batches_bytes(table.scan_batches(
            projection=["hostname", "ts", "usage_user"]))
        fe.do_query("SET dist_partial_agg = 0", ctx)
        try:
            fe.do_query(tql, ctx)
            dt_raw = timed()
        finally:
            fe.do_query("SET dist_partial_agg = 1", ctx)
        speedup = dt_raw / dt_ir
        assert speedup >= 3.0, (dt_ir, dt_raw, speedup)
        wire_reduction = raw_bytes / max(partial_bytes, 1)
        return (n / dt_ir, speedup, partial_bytes, raw_bytes,
                wire_reduction)
    finally:
        for dn in datanodes.values():
            dn.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)


def emit_promql_dist_range():
    n_rows = int(os.environ.get("GREPTIME_BENCH_PROMQL_ROWS", 2_000_000))
    rps, vs_raw, partial_b, raw_b, reduction = \
        bench_promql_dist_range(n_rows)
    print(json.dumps({
        "metric": "promql_dist_range_query_throughput",
        "value": round(rps / 1e6, 2),
        "unit": "Mrows/s",
        "vs_raw_pull": round(vs_raw, 2),
        "partial_wire_bytes": int(partial_b),
        "raw_wire_bytes": int(raw_b),
        "wire_byte_reduction": round(reduction, 1),
        "rows": n_rows,
        "datanodes": 4,
    }))


def bench_region_migration_availability(n_rows: int):
    """Sixth driver metric (ISSUE 9): migrate a region between datanodes
    UNDER sustained single-row ingest and measure availability:

    - ``handoff_window_ms`` — the fenced window (WAL-tail capture →
      route commit, from the op doc's state timestamps): the ONLY span
      in which writes to the migrating region stall.
    - ``max_write_stall_ms`` — the worst user-visible insert latency
      during the whole migration (the stale-route retry riding over the
      fence; every other insert proceeds at normal speed).
    - ``lost_rows`` / ``dup_rows`` — acked-write continuity: every row
      the ingest thread got an ack for is readable EXACTLY once after
      the handoff (asserted zero/zero, then published).

    2 in-process datanodes over one SHARED object store (the elastic
    deployment shape); the balancer + heartbeats run in a background
    pump thread at production-like cadence while the foreground ingests.
    """
    import shutil
    import tempfile
    import threading

    from greptimedb_tpu.client import LocalDatanodeClient
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.distributed import DistInstance
    from greptimedb_tpu.meta import MemKv, MetaClient, MetaSrv, Peer
    from greptimedb_tpu.session import QueryContext
    from greptimedb_tpu.storage.object_store import FsObjectStore

    tmpdir = tempfile.mkdtemp(prefix="bench-migrate-")
    datanodes = {}
    try:
        shared = FsObjectStore(f"{tmpdir}/shared")
        srv = MetaSrv(MemKv())
        srv.balancer.resend_interval_s = 0.05
        meta = MetaClient(srv)
        clients = {}
        for i in (1, 2):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=f"{tmpdir}/dn{i}", node_id=i,
                register_numbers_table=False), store=shared)
            dn.start()
            dn.attach_meta(meta)
            datanodes[i] = dn
            clients[i] = LocalDatanodeClient(dn)
            srv.register_datanode(Peer(i, f"dn{i}"))
            srv.handle_heartbeat(i)
        fe = DistInstance(meta, clients)
        ctx = QueryContext()
        fe.do_query(
            "CREATE TABLE mig (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host)) "
            "PARTITION BY RANGE COLUMNS (host) ("
            "  PARTITION r0 VALUES LESS THAN ('m'),"
            "  PARTITION r1 VALUES LESS THAN (MAXVALUE))", ctx)
        table = fe.catalog.table("greptime", "public", "mig")
        # preload the region that will move (host 'a' < 'm' → region 0)
        ts0 = np.arange(n_rows, dtype=np.int64) * 1000
        table.bulk_load({
            "host": np.array(["a"] * n_rows, dtype=object), "ts": ts0,
            "v": np.random.default_rng(3).random(n_rows)})
        table.flush()
        route = srv.table_route("greptime.public.mig")
        src = next(rr.leader.id for rr in route.region_routes
                   if rr.region_number == 0)
        dst = 2 if src == 1 else 1

        stop = threading.Event()

        def pump():
            while not stop.is_set():
                srv.balancer.tick()
                for i, dn in datanodes.items():
                    resp = srv.handle_heartbeat(i)
                    for msg in resp.mailbox:
                        dn._handle_mailbox(msg)
                time.sleep(0.02)

        acked = []
        stalls = []
        ingest_stop = threading.Event()

        def ingest():
            n = 0
            while not ingest_stop.is_set():
                n += 1
                key_ts = 10_000_000 + n
                t0 = time.perf_counter()
                try:
                    fe.do_query(
                        f"INSERT INTO mig VALUES ('a', {key_ts}, 1.0)",
                        ctx)
                except Exception:  # noqa: BLE001 — an unacked write
                    continue       # during the fault is legal
                stalls.append((time.perf_counter() - t0) * 1e3)
                acked.append(key_ts)

        pump_t = threading.Thread(target=pump, daemon=True)
        ingest_t = threading.Thread(target=ingest, daemon=True)
        pump_t.start()
        ingest_t.start()
        time.sleep(0.3)                       # steady-state ingest
        fe.do_query(f"ADMIN MIGRATE REGION mig 0 TO {dst}", ctx)
        t0 = time.time()
        while srv.balancer.ops() and time.time() - t0 < 120:
            time.sleep(0.05)
        time.sleep(0.3)                       # post-handoff ingest
        ingest_stop.set()
        ingest_t.join(timeout=60)
        stop.set()
        pump_t.join(timeout=10)

        done = srv.balancer.done_ops()[-1]
        assert done["state"] == "done", done
        times = done.get("times", {})
        handoff_ms = max(0, times.get("release", 0) -
                         times.get("open", 0))
        # continuity: every acked row readable exactly once
        out = fe.do_query(
            "SELECT ts FROM mig WHERE ts >= 10000000", ctx)[-1]
        got = [r[0] for b in out.batches for r in b.rows()]
        lost = len(set(acked) - set(got))
        dup = len(got) - len(set(got))
        assert lost == 0, f"lost {lost} acked rows"
        assert dup == 0, f"{dup} duplicated rows"
        new_owner = next(
            rr.leader.id for rr in
            srv.table_route("greptime.public.mig").region_routes
            if rr.region_number == 0)
        assert new_owner == dst
        return (handoff_ms, max(stalls) if stalls else 0.0, len(acked),
                lost, dup)
    finally:
        for dn in datanodes.values():
            dn.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)


def bench_replicated_read_qps(n_rows: int = 100_000):
    """Eighth driver metric (ISSUE 19): read-QPS scaling across region
    read replicas, plus failover quality numbers:

    - ``qps_{1,2,3}_replicas`` — SET read_replica = 'follower' point
      reads against the same region served by 1 (leader only), 2 and 3
      replicas; the rotating least-assigned pool spreads the load.
    - ``promotion_handoff_ms`` — kill -9 twin of the leader under
      sustained fsync-acked ingest → time until a write acks through
      the promoted follower (lease loss + salvage + route commit).
    - ``acked_lost_rows`` / ``dup_rows`` — every row acked before or
      after the fault is readable exactly once (asserted zero/zero,
      then published).

    3 in-process datanodes over one SHARED object store AND one shared
    data_home (node-scoped WAL dirs) — the deployment shape where
    promotion can salvage the dead leader's fsynced WAL tail.
    """
    import shutil
    import tempfile
    import threading

    from greptimedb_tpu.client import LocalDatanodeClient
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.distributed import (DistInstance,
                                                     configure_read_replica)
    from greptimedb_tpu.meta import (DatanodeStat, MemKv, MetaClient,
                                     MetaSrv, Peer)
    from greptimedb_tpu.query.stream_exec import region_stat_entries
    from greptimedb_tpu.session import QueryContext
    from greptimedb_tpu.storage.object_store import FsObjectStore

    tmpdir = tempfile.mkdtemp(prefix="bench-replica-")
    datanodes = {}
    stop = threading.Event()
    pump_t = None
    try:
        shared = FsObjectStore(f"{tmpdir}/shared")
        srv = MetaSrv(MemKv(), datanode_lease_secs=3600.0)
        srv.balancer.resend_interval_s = 0.05
        meta = MetaClient(srv)
        clients = {}
        for i in (1, 2, 3):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=f"{tmpdir}/home", node_id=i,
                wal_sync_on_write=True,
                register_numbers_table=False), store=shared)
            dn.start()
            dn.attach_meta(meta)
            datanodes[i] = dn
            clients[i] = LocalDatanodeClient(dn)
            srv.register_datanode(Peer(i, f"dn{i}"))
            srv.handle_heartbeat(i)
        fe = DistInstance(meta, clients)
        ctx = QueryContext()
        fe.do_query(
            "CREATE TABLE rr (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))", ctx)
        table = fe.catalog.table("greptime", "public", "rr")
        table.bulk_load({
            "host": np.array([f"h{i % 64}" for i in range(n_rows)],
                             dtype=object),
            "ts": np.arange(n_rows, dtype=np.int64) * 1000,
            "v": np.random.default_rng(7).random(n_rows)})
        table.flush()
        route = srv.table_route("greptime.public.rr")
        leader = next(rr.leader.id for rr in route.region_routes
                      if rr.region_number == 0)
        followers = [i for i in (1, 2, 3) if i != leader]

        dead = set()

        def pump():
            # production cadence stand-in: balancer ticks + full
            # stat-bearing heartbeats (they carry replicated_seq, the
            # lag gate behind replica read eligibility) + failover scan
            while not stop.is_set():
                try:
                    srv.balancer.tick()
                    srv.failover_check()
                    for i, dn in list(datanodes.items()):
                        if i in dead:
                            continue       # kill -9 twin: silence
                        regions = dn.storage.list_regions()
                        entries, rows_, nb = region_stat_entries(
                            regions.values())
                        resp = srv.handle_heartbeat(i, DatanodeStat(
                            region_count=len(regions),
                            approximate_rows=rows_,
                            approximate_bytes=nb,
                            region_stats=entries))
                        for msg in resp.mailbox:
                            dn._handle_mailbox(msg)
                except Exception:  # noqa: BLE001 — a mid-fault pump
                    pass           # round retries on the next tick
                time.sleep(0.02)

        pump_t = threading.Thread(target=pump, daemon=True)
        pump_t.start()

        def wait_replica(target):
            deadline = time.time() + 60
            while time.time() < deadline:
                caught = any(
                    r.get("table_name") == "greptime.public.rr" and
                    r.get("peer_id") == target and
                    r.get("is_leader") == "No" and
                    r.get("status") == "ALIVE" and
                    r.get("lag_ms") is not None
                    for r in srv.region_peers())
                if caught and not srv.balancer.ops():
                    return
                time.sleep(0.02)
            raise AssertionError(f"replica on dn{target} never caught up")

        configure_read_replica(mode="follower", max_lag_ms=60_000)

        def measure_qps(seconds=1.2, threads=4):
            counts = [0] * threads
            t_end = time.perf_counter() + seconds

            def worker(k):
                rng = np.random.default_rng(k)
                while time.perf_counter() < t_end:
                    h = int(rng.integers(0, 64))
                    fe.do_query(
                        f"SELECT count(*) FROM rr WHERE host = 'h{h}'",
                        ctx)
                    counts[k] += 1

            ws = [threading.Thread(target=worker, args=(k,))
                  for k in range(threads)]
            for w in ws:
                w.start()
            for w in ws:
                w.join()
            return sum(counts) / seconds

        qps = {1: measure_qps()}                  # leader only
        fe.do_query(f"ADMIN ADD REPLICA rr 0 TO {followers[0]}", ctx)
        wait_replica(followers[0])
        qps[2] = measure_qps()
        fe.do_query(f"ADMIN ADD REPLICA rr 0 TO {followers[1]}", ctx)
        wait_replica(followers[1])
        qps[3] = measure_qps()

        # --- promotion handoff under sustained fsync-acked ingest ---
        acked = []
        ingest_stop = threading.Event()

        def ingest():
            n = 0
            while not ingest_stop.is_set():
                n += 1
                key_ts = 10_000_000 + n
                try:
                    fe.do_query(
                        f"INSERT INTO rr VALUES ('w', {key_ts}, 1.0)",
                        ctx)
                except Exception:  # noqa: BLE001 — an unacked write
                    continue       # during the fault is legal
                acked.append((key_ts, time.perf_counter()))

        ingest_t = threading.Thread(target=ingest, daemon=True)
        ingest_t.start()
        time.sleep(0.3)                           # steady-state ingest
        t_kill = time.perf_counter()
        dn = datanodes[leader]
        for region in dn.storage.list_regions().values():
            with region._writer_lock:              # kill -9 twin: stop
                region.closed = True               # answering mid-state
                region.wal.close()
        dead.add(leader)
        srv._last_seen[leader] = 0.0               # lease lost
        t0 = time.time()
        while time.time() - t0 < 60:
            rt = srv.table_route("greptime.public.rr")
            lid = next(r.leader.id for r in rt.region_routes
                       if r.region_number == 0)
            if lid != leader:
                break
            time.sleep(0.005)
        else:
            raise AssertionError("promotion never committed")
        t_flip = time.perf_counter()
        # first ack THROUGH the promoted follower bounds the handoff
        # (acks before the route flip were in-flight writes the kill
        # loop let drain under the writer lock — not handoff evidence)
        t0 = time.time()
        while time.time() - t0 < 60:
            if any(t > t_flip for _, t in acked):
                break
            time.sleep(0.005)
        first_ack = min(t for _, t in acked if t > t_flip)
        handoff_ms = (first_ack - t_kill) * 1e3
        time.sleep(0.3)                           # post-handoff ingest
        ingest_stop.set()
        ingest_t.join(timeout=60)

        # continuity: every acked row readable exactly once
        configure_read_replica(mode="leader")
        out = fe.do_query(
            "SELECT ts FROM rr WHERE ts >= 10000000", ctx)[-1]
        got = [r[0] for b in out.batches for r in b.rows()]
        lost = len({k for k, _ in acked} - set(got))
        dup = len(got) - len(set(got))
        assert lost == 0, f"lost {lost} acked rows"
        assert dup == 0, f"{dup} duplicated rows"
        return (qps[1], qps[2], qps[3], handoff_ms, len(acked), lost,
                dup)
    finally:
        stop.set()
        if pump_t is not None:
            pump_t.join(timeout=10)
        configure_read_replica(mode="leader", max_lag_ms=5000)
        for dn in datanodes.values():
            try:
                dn.shutdown()
            except Exception:  # noqa: BLE001 — the killed twin's WAL is
                pass           # already closed
        shutil.rmtree(tmpdir, ignore_errors=True)


def emit_replicated_read_qps():
    q1, q2, q3, handoff_ms, acked_n, lost, dup = \
        bench_replicated_read_qps()
    print(json.dumps({
        "metric": "replicated_read_qps",
        "value": round(q3, 1),
        "unit": "qps_at_3_replicas",
        "qps_1_replica": round(q1, 1),
        "qps_2_replicas": round(q2, 1),
        "qps_3_replicas": round(q3, 1),
        "promotion_handoff_ms": round(handoff_ms, 1),
        "acked_writes_during_failover": acked_n,
        "acked_lost_rows": lost,
        "dup_rows": dup,
    }))


def bench_index_point_query(n_series: int = 100_000, files: int = 16):
    """Seventh driver metric (ISSUE 13): high-cardinality point-query
    throughput against a persisted many-SST region, with the per-SST
    secondary index on vs off (`SET sst_index = 0`).

    Layout is the shape the index exists for: the series dictionary is
    primed once (so sids are host-ordered), then each of `files` bulk
    batches carries a SCATTERED 1/files-th of the series — every SST's
    coarse sid_range spans nearly the whole keyspace (stats-only file
    pruning keeps everything) while its bloom holds only its own sids
    (index pruning drops ~(files-1)/files of the files). Point + IN(8)
    queries alternate; the scan cache is cleared per query on both sides
    so the differential measures the cold read path, not cache warmth.

    Asserts: answers identical on/off (zero drift), differential >= 3x,
    and `files pruned by index` visible in the EXPLAIN ANALYZE profile
    (index_files_pruned / index_files_checked on the prune stage)."""
    import shutil
    import tempfile

    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    from greptimedb_tpu.query import tpu_exec
    from greptimedb_tpu.session import QueryContext

    tmpdir = tempfile.mkdtemp(prefix="bench-index-")
    fe = None
    rows_per = 16
    try:
        dn = DatanodeInstance(DatanodeOptions(
            data_home=tmpdir, register_numbers_table=False))
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        ctx = QueryContext()
        fe.do_query("CREATE TABLE idx (host STRING, ts TIMESTAMP "
                    "TIME INDEX, v DOUBLE, PRIMARY KEY(host))")
        table = fe.catalog.table("greptime", "public", "idx")
        for region in table.regions.values():
            # keep the scattered L0 layout: auto-compaction would merge
            # the batches into per-window files that genuinely contain
            # every series (nothing left for any index to prune)
            region.max_l0_files = 1 << 30
        rng = np.random.default_rng(17)
        hosts_all = np.array([f"h{i:06d}" for i in range(n_series)],
                             dtype=object)
        # values are dyadic rationals (multiples of 1/8, < 512): exactly
        # representable in BOTH float64 and the index-off resident
        # path's f32 device mirrors, so the zero-drift assertion below
        # compares semantics, not float rounding regimes
        def vals(n: int) -> np.ndarray:
            return rng.integers(0, 4096, n).astype(np.float64) / 8.0

        # prime the dictionary in host order: one row per series
        table.bulk_load({"host": hosts_all,
                         "ts": np.zeros(n_series, dtype=np.int64),
                         "v": vals(n_series)})
        total = n_series
        for k in range(files):
            sel = hosts_all[k::files]
            host_col = np.repeat(sel, rows_per)
            ts_col = np.tile(
                (np.arange(rows_per, dtype=np.int64) + 1) * 1000 + k,
                len(sel))
            table.bulk_load({"host": host_col, "ts": ts_col,
                             "v": vals(len(host_col))})
            total += len(host_col)
        n_ssts = sum(len(r.version_control.current.ssts.all_files())
                     for r in table.regions.values())
        assert n_ssts >= files, f"expected >= {files} SSTs, got {n_ssts}"
        fe.do_query("SET tpu_dispatch_min_rows = 131072", ctx)

        def point_sql(i: int) -> str:
            return (f"SELECT host, max(v), count(v) FROM idx WHERE "
                    f"host = '{hosts_all[i % n_series]}' GROUP BY host")

        def in8_sql(i: int) -> str:
            picks = ", ".join(
                f"'{hosts_all[(i * 131 + j * 977) % n_series]}'"
                for j in range(8))
            return (f"SELECT host, avg(v) FROM idx WHERE host IN "
                    f"({picks}) GROUP BY host ORDER BY host")

        def run(sql: str):
            out = fe.do_query(sql, ctx)[-1]
            return sorted(tuple(r) for b in out.batches
                          for r in b.rows())

        def timed(iters: int) -> float:
            t0 = time.perf_counter()
            for i in range(iters):
                tpu_exec.SCAN_CACHE._entries.clear()
                run(point_sql(i * 7919))
                tpu_exec.SCAN_CACHE._entries.clear()
                run(in8_sql(i))
            return (time.perf_counter() - t0) / (2 * iters)

        # zero answer drift on vs off, for both shapes
        for sql in (point_sql(42), in8_sql(3)):
            tpu_exec.SCAN_CACHE._entries.clear()
            on_rows = run(sql)
            fe.do_query("SET sst_index = 0", ctx)
            tpu_exec.SCAN_CACHE._entries.clear()
            off_rows = run(sql)
            fe.do_query("SET sst_index = 1", ctx)
            assert on_rows == off_rows, sql

        timed(1)                               # absorb one-time costs
        dt_on = timed(6)
        fe.do_query("SET sst_index = 0", ctx)
        dt_off = timed(2)
        fe.do_query("SET sst_index = 1", ctx)

        # EXPLAIN ANALYZE profile: files pruned by index must be visible
        tpu_exec.SCAN_CACHE._entries.clear()
        run(point_sql(123))
        st = fe.query_engine.last_exec_stats
        prune = st.stages["prune"].detail
        pruned = int(prune.get("index_files_pruned", 0))
        checked = int(prune.get("index_files_checked", 0))
        assert pruned >= files - 2, (pruned, checked)
        speedup = dt_off / dt_on
        assert speedup >= 3.0, (
            f"index differential only {speedup:.2f}x on the many-SST "
            f"region (on={dt_on * 1e3:.1f}ms off={dt_off * 1e3:.1f}ms)")
        return (1.0 / dt_on, speedup, total, n_ssts,
                {"dispatch": st.dispatch,
                 "files_pruned_by_index": f"{pruned}/{checked}",
                 "query_ms_index_on": round(dt_on * 1e3, 2),
                 "query_ms_index_off": round(dt_off * 1e3, 2)})
    finally:
        if fe is not None:
            fe.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)


def emit_index_point_query():
    """The ISSUE 13 metric, runnable alone via `make bench-index`
    (GREPTIME_BENCH_ONLY=index)."""
    n_series = int(os.environ.get("GREPTIME_BENCH_INDEX_SERIES",
                                  100_000))
    n_files = int(os.environ.get("GREPTIME_BENCH_INDEX_FILES", 16))
    qps, speedup, rows, n_ssts, profile = \
        bench_index_point_query(n_series, n_files)
    print(json.dumps({
        "metric": "high_cardinality_point_query_throughput",
        "value": round(qps, 1),
        "unit": "queries/s",
        "series": n_series,
        "rows": rows,
        "sst_files": n_ssts,
        "vs_index_off": round(speedup, 2),
        "profile": profile,
    }))


def emit_concurrent_qps():
    """The ISSUE 12 metric, runnable alone via `make bench-qps`
    (GREPTIME_BENCH_ONLY=concurrent_qps)."""
    n_clients = int(os.environ.get("GREPTIME_BENCH_QPS_CLIENTS", 1000))
    qps, p50, p95, p99, ratios, append_ns = \
        bench_concurrent_qps(n_clients)
    print(json.dumps({
        "metric": "concurrent_qps_p99",
        "value": round(qps, 0),
        "unit": "qps",
        "clients": n_clients,
        "p50_ms": round(p50, 2),
        "p95_ms": round(p95, 2),
        "p99_ms": round(p99, 2),
        "group_commit_speedup_fsync2ms": round(ratios["fsync2ms"], 2),
        "group_commit_speedup_raw": round(ratios["raw"], 2),
        "wal_append_ns": round(append_ns, 0),
    }))


def main():
    if os.environ.get("GREPTIME_BENCH_ONLY") == "concurrent_qps":
        emit_concurrent_qps()
        return
    if os.environ.get("GREPTIME_BENCH_ONLY") == "index":
        emit_index_point_query()
        return
    if os.environ.get("GREPTIME_BENCH_ONLY") == "distagg":
        emit_dist_partial_agg()
        return
    if os.environ.get("GREPTIME_BENCH_ONLY") == "promql":
        emit_promql_dist_range()
        return
    if os.environ.get("GREPTIME_BENCH_ONLY") == "replica":
        emit_replicated_read_qps()
        return
    if os.environ.get("GREPTIME_BENCH_ONLY") == "trace":
        emit_trace_store_overhead()
        return
    if os.environ.get("GREPTIME_BENCH_ONLY") == "prof":
        emit_profiler_overhead()
        return
    n_rows = int(os.environ.get("GREPTIME_BENCH_ROWS", 1 << 24))
    gids, ts, metrics = gen_data(n_rows)

    tpu_rps, out = bench_tpu(gids, ts, metrics)

    # sanity: TPU result must agree with a numpy oracle on one group
    # (last iteration shifted metric 0 by +iters)
    g0 = gids == 0
    if g0.any():
        got = float(np.asarray(out[0][0])[0])
        assert abs(got - float(metrics[0][g0].max()) - 8.0) < 1e-2, got

    cpu_rps = bench_cpu(gids, ts, metrics)

    print(json.dumps({
        "metric": "tsbs_single_groupby_scan_agg_throughput",
        "value": round(tpu_rps / 1e6, 2),
        "unit": "Mrows/s",
        "vs_baseline": round(tpu_rps / cpu_rps, 2),
    }))

    cold_rows = int(os.environ.get("GREPTIME_BENCH_COLD_ROWS", 4_000_000))
    cold_rps, cold_profile = bench_cold_e2e(cold_rows)
    print(json.dumps({
        "metric": "cold_single_groupby_e2e_throughput",
        "value": round(cold_rps / 1e6, 2),
        "unit": "Mrows/s",
        "rows": cold_rows,
    }))
    print(json.dumps({
        "metric": "cold_scan_stage_profile",
        "unit": "json",
        **cold_profile,
    }))

    roll_rows = int(os.environ.get("GREPTIME_BENCH_ROLLUP_ROWS",
                                   4_000_000))
    roll_rps, vs_raw = bench_rollup_e2e(roll_rows)
    print(json.dumps({
        "metric": "rollup_groupby_e2e_throughput",
        "value": round(roll_rps / 1e6, 2),
        "unit": "Mrows/s",
        "vs_raw_scan": round(vs_raw, 2),
        "rows": roll_rows,
    }))

    dist_rows = int(os.environ.get("GREPTIME_BENCH_DIST_ROWS", 2_000_000))
    dist_rps, vs_serial, vs_serial_net, node_ms = \
        bench_dist_scatter(dist_rows)
    print(json.dumps({
        "metric": "dist_scatter_gather_throughput",
        "value": round(dist_rps / 1e6, 2),
        "unit": "Mrows/s",
        "vs_serial": round(vs_serial, 2),
        "vs_serial_warm_10ms_rpc": round(vs_serial_net, 2),
        "rows": dist_rows,
        "datanodes": 4,
        "scatter_node_ms": node_ms,
    }))

    emit_dist_partial_agg()

    emit_promql_dist_range()

    mig_rows = int(os.environ.get("GREPTIME_BENCH_MIGRATE_ROWS",
                                  1_000_000))
    handoff_ms, max_stall_ms, acked_n, lost, dup = \
        bench_region_migration_availability(mig_rows)
    print(json.dumps({
        "metric": "region_migration_availability",
        "value": round(handoff_ms, 1),
        "unit": "ms_handoff_window",
        "max_write_stall_ms": round(max_stall_ms, 1),
        "migrated_rows": mig_rows,
        "acked_writes_during_migration": acked_n,
        "lost_rows": lost,
        "dup_rows": dup,
    }))

    emit_replicated_read_qps()

    fp_rows = int(os.environ.get("GREPTIME_BENCH_FAILPOINT_ROWS",
                                 2_000_000))
    ingest_rps, fp_ratio, fp_ns = bench_ingest_failpoint_overhead(fp_rows)
    print(json.dumps({
        "metric": "bulk_ingest_e2e_throughput",
        "value": round(ingest_rps / 1e6, 2),
        "unit": "Mrows/s",
        "rows": fp_rows,
        "failpoint_inactive_ratio": round(fp_ratio, 3),
        "failpoint_inactive_ns_per_call": round(fp_ns, 1),
    }))

    emit_index_point_query()

    mon_rows = int(os.environ.get("GREPTIME_BENCH_MONITOR_ROWS",
                                  2_000_000))
    mon_rps, mon_overhead, mon_ticks = \
        bench_self_monitoring_overhead(mon_rows)
    print(json.dumps({
        "metric": "self_monitoring_overhead",
        "value": round(mon_overhead * 100, 2),
        "unit": "percent",
        "ingest_mrows_s_with_scraper": round(mon_rps / 1e6, 2),
        "rows": mon_rows,
        "scrape_interval_s": 0.5,
        "ticks_during_ingest": mon_ticks,
    }))

    lk_ns, lk_raw_ns, lk_ratio, lk_active_ns = bench_lock_overhead()
    print(json.dumps({
        "metric": "tracked_lock_inactive_overhead",
        "value": round(lk_ns, 1),
        "unit": "ns/acquire-release",
        "raw_lock_ns": round(lk_raw_ns, 1),
        "inactive_ratio": round(lk_ratio, 3),
        "active_mode_ns": round(lk_active_ns, 1),
    }))

    san_ns, san_raw_ns, san_ratio, san_active_ns = \
        bench_greptsan_inactive_overhead()
    print(json.dumps({
        "metric": "greptsan_inactive_overhead",
        "value": round(san_ns, 1),
        "unit": "ns/dict-cycle",
        "raw_dict_ns": round(san_raw_ns, 1),
        "inactive_ratio": round(san_ratio, 3),
        "active_mode_ns_per_get": round(san_active_ns, 1),
    }))

    emit_trace_store_overhead()

    emit_profiler_overhead()

    emit_concurrent_qps()


if __name__ == "__main__":
    main()
