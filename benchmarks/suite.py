"""BASELINE.json measurement suite: configs 1-5 on real hardware.

Run on the TPU host:  python benchmarks/suite.py [--rows-scale 1.0]
Prints one JSON line per config.

Config map (BASELINE.json):
  1 README monitor smoke — end-to-end standalone SQL latency
  2 TSBS single-groupby-1-1-1 @ scaled rows — device scan+agg
  3 TSBS double-groupby-5 + high-cardinality hosts — device scan+agg
  4 PromQL rate(cpu[5m]) + avg_over_time over 10k series / 24h
  5 compaction + 1s→1m downsample over a multi-SST region

CPU denominators are same-machine pandas columnar equivalents (the
reference publishes no numbers).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _p(name, value, unit, extra=None):
    doc = {"config": name, "value": round(value, 2), "unit": unit}
    if extra:
        doc.update(extra)
    print(json.dumps(doc), flush=True)


# ---------------------------------------------------------------------------
def config1_monitor(tmpdir):
    from greptimedb_tpu.datanode.instance import (
        DatanodeInstance, DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    dn = DatanodeInstance(DatanodeOptions(
        data_home=f"{tmpdir}/monitor", register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    fe.do_query("CREATE TABLE monitor (host STRING, ts TIMESTAMP TIME"
                " INDEX, cpu DOUBLE, memory DOUBLE, PRIMARY KEY(host))")
    rng = np.random.default_rng(1)
    t_ins = time.perf_counter()
    for chunk in range(10):
        rows = ", ".join(
            f"('host{int(h)}', {1000 + chunk * 1000 + i}, "
            f"{float(c):.2f}, {float(m):.1f})"
            for i, (h, c, m) in enumerate(zip(
                rng.integers(0, 8, 1000), rng.random(1000) * 100,
                rng.random(1000) * 4096)))
        fe.do_query(f"INSERT INTO monitor VALUES {rows}")
    ins_dt = time.perf_counter() - t_ins
    q = "SELECT host, avg(cpu) FROM monitor GROUP BY host ORDER BY host"
    fe.do_query(q)                                   # warm / compile
    t0 = time.perf_counter()
    iters = 20
    for _ in range(iters):
        out = fe.do_query(q)[-1]
    dt = (time.perf_counter() - t0) / iters
    assert out.batches[0].num_rows == 8
    _p("1_monitor_smoke", dt * 1e3, "ms/query",
       {"insert_rows_per_s": round(10_000 / ins_dt)})
    fe.shutdown()


# ---------------------------------------------------------------------------
def _device_groupby(n_rows, num_groups, n_metrics, ops, iters=6):
    import jax
    import jax.numpy as jnp
    from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate

    rng = np.random.default_rng(7)
    gids = np.sort(rng.integers(0, num_groups, n_rows)).astype(np.int32)
    ts = rng.integers(0, 3_600_000, n_rows).astype(np.int32)
    metrics = tuple(rng.random(n_rows, dtype=np.float32) * 100
                    for _ in range(n_metrics))
    mask = np.ones(n_rows, bool)
    d = (jax.device_put(gids), jax.device_put(mask), jax.device_put(ts),
         tuple(jax.device_put(m) for m in metrics))

    @jax.jit
    def step(gids_a, mask_a, ts_a, ms_a, shift):
        ms_a = (ms_a[0] + shift,) + ms_a[1:]
        return sorted_grouped_aggregate(gids_a, mask_a, ts_a, ms_a,
                                        num_groups=num_groups, ops=ops)

    out = step(*d, jnp.float32(0))
    float(np.asarray(out[1])[0])
    t0 = time.perf_counter()
    for i in range(iters):
        out = step(*d, jnp.float32(i + 1))
    float(np.asarray(out[1])[0])
    dt = (time.perf_counter() - t0) / iters

    import pandas as pd
    df = pd.DataFrame({"g": gids})
    for i, m in enumerate(metrics):
        df[f"m{i}"] = m
    t0 = time.perf_counter()
    df.groupby("g").agg({f"m{i}": ("mean" if op == "avg" else op)
                         for i, op in enumerate(ops)})
    cpu_dt = time.perf_counter() - t0
    return n_rows / dt, n_rows / cpu_dt


def config2_tsbs_single(scale):
    n = int(100e6 * scale)
    tpu, cpu = _device_groupby(n, 8 * 60, 1, ("max",))
    _p("2_tsbs_single_groupby_1_1_1", tpu / 1e6, "Mrows/s",
       {"rows": n, "cpu_mrows_s": round(cpu / 1e6, 2),
        "vs_cpu": round(tpu / cpu, 1)})


def config3_tsbs_double_highcard(scale):
    n = int(100e6 * scale)
    groups = 10_000 * 12                 # 10k hosts × 12 5-min buckets
    tpu, cpu = _device_groupby(n, groups, 5, ("avg",) * 5)
    _p("3_tsbs_double_groupby_5_highcard", tpu / 1e6, "Mrows/s",
       {"rows": n, "groups": groups,
        "cpu_mrows_s": round(cpu / 1e6, 2),
        "vs_cpu": round(tpu / cpu, 1)})


# ---------------------------------------------------------------------------
def config4_promql(scale):
    import jax
    import jax.numpy as jnp
    from greptimedb_tpu.ops.window import AlignedWindowEval, SeriesMatrix

    num_series = int(10_000 * max(scale, 0.1))
    pts = 5760                            # 24h at 15s scrape
    n = num_series * pts
    rng = np.random.default_rng(11)
    sids = np.repeat(np.arange(num_series, dtype=np.int32), pts)
    ts = np.tile(np.arange(pts, dtype=np.int64) * 15_000, num_series)
    vals = np.cumsum(rng.random(n, dtype=np.float32), dtype=np.float32)
    matrix = SeriesMatrix.build(sids, ts, vals, num_series)
    d_ts, d_vals, d_lens, base = matrix.device_arrays()
    d_ts = jax.device_put(d_ts)
    d_vals = jax.device_put(d_vals)
    d_lens = jax.device_put(d_lens)
    nsteps = 1440                         # 24h at 1m step
    add = jax.jit(lambda v, s: v + s)

    def eval_once(i):
        """Engine-style evaluation: AlignedWindowEval shares the bounds
        pass, cumsums, and the one stacked gather between rate and
        avg_over_time — the same path PromqlEngine takes."""
        v2 = add(d_vals, jnp.float32(i))
        awe = AlignedWindowEval(d_ts, v2, d_lens, 300_000 - base, 60_000,
                                300_000, nsteps)
        r, ok = awe.eval("rate")
        a, ok2 = awe.eval("avg_over_time")
        return r, a, jnp.logical_and(ok, ok2)

    out = eval_once(0)
    float(np.asarray(out[0])[0, 0])
    iters = 4
    t0 = time.perf_counter()
    for i in range(iters):
        out = eval_once(i)
    float(np.asarray(out[0])[0, 0])
    dt = (time.perf_counter() - t0) / iters
    _p("4_promql_rate_avg_24h", dt * 1e3, "ms/eval",
       {"series": num_series, "points": n, "steps": nsteps,
        "points_per_s_m": round(n / dt / 1e6, 1),
        "outputs_per_s_m": round(2 * num_series * nsteps / dt / 1e6, 1)})


# ---------------------------------------------------------------------------
def config5_downsample(tmpdir, scale):
    from greptimedb_tpu.datanode.instance import (
        DatanodeInstance, DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance

    n_rows = int(8e6 * max(scale, 0.1))
    per_sst = n_rows // 4
    dn = DatanodeInstance(DatanodeOptions(
        data_home=f"{tmpdir}/ds", register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    fe.do_query("CREATE TABLE raw (host STRING, ts TIMESTAMP TIME INDEX,"
                " v DOUBLE, PRIMARY KEY(host))")
    fe.do_query("CREATE TABLE agg (host STRING, ts TIMESTAMP TIME INDEX,"
                " v DOUBLE, PRIMARY KEY(host))")
    raw = fe.catalog.table("greptime", "public", "raw")
    rng = np.random.default_rng(3)
    n_hosts = 100
    secs_per_sst = per_sst // n_hosts     # every host emits 1 point/sec
    t_load = time.perf_counter()
    for s in range(4):
        base_ts = s * secs_per_sst * 1000
        ts = np.tile(np.arange(secs_per_sst, dtype=np.int64) * 1000
                     + base_ts, n_hosts)
        host = np.repeat([f"h{i}" for i in range(n_hosts)], secs_per_sst)
        cols = {"host": host, "ts": ts, "v": rng.random(len(ts))}
        raw.insert(cols)
        raw.flush()
    n_rows = 4 * secs_per_sst * n_hosts
    load_dt = time.perf_counter() - t_load

    from greptimedb_tpu.storage.downsample import downsample_region
    fe.do_query("CREATE TABLE agg_warm (host STRING, ts TIMESTAMP TIME "
                "INDEX, v DOUBLE, PRIMARY KEY(host))")
    agg = fe.catalog.table("greptime", "public", "agg")
    src_region = next(iter(raw.regions.values()))
    dst_region = next(iter(agg.regions.values()))
    warm_region = next(iter(fe.catalog.table(
        "greptime", "public", "agg_warm").regions.values()))
    # cold pass pays XLA compile + scan-cache build (once per process /
    # region); the timed pass is the steady state a periodic maintenance
    # job runs in — kernels compiled, source region device-resident (the
    # same warm-then-time protocol as config 4)
    t0 = time.perf_counter()
    downsample_region(src_region, warm_region, stride_ms=60_000,
                      aggs={"v": "avg"})
    cold_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    downsample_region(src_region, dst_region, stride_ms=60_000,
                      aggs={"v": "avg"})
    dt = time.perf_counter() - t0
    out_rows = sum(b.num_rows for b in agg.scan_batches())
    _p("5_downsample_1s_to_1m", n_rows / dt / 1e6, "Mrows/s",
       {"rows_in": n_rows, "rows_out": out_rows,
        "load_rows_per_s": round(n_rows / load_dt),
        "downsample_s": round(dt, 2), "cold_s": round(cold_dt, 2)})
    fe.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-scale", type=float, default=1.0,
                    help="scale factor on row counts (1.0 = full size)")
    ap.add_argument("--configs", default="1,2,3,4,5")
    ap.add_argument("--block-rows", type=int, default=50_000_000)
    args = ap.parse_args()
    import tempfile
    want = set(args.configs.split(","))
    with tempfile.TemporaryDirectory() as tmpdir:
        if "1" in want:
            config1_monitor(tmpdir)
        if "2" in want:
            config2_tsbs_single(args.rows_scale)
        if "3" in want:
            config3_tsbs_double_highcard(args.rows_scale)
        if "3b" in want:
            config3_blocked_1b(block_rows=args.block_rows)
        if "4" in want:
            config4_promql(args.rows_scale)
        if "5" in want:
            config5_downsample(tmpdir, args.rows_scale)




def config3_blocked_1b(total_rows: int = 1_000_000_000,
                       block_rows: int = 50_000_000):
    """BASELINE config 3 at its true scale: 1B rows streamed through
    HBM-sized time blocks, per-block device aggregation, device-side
    moment merge (sum/count add; min/max reduce) — the time-axis
    blocking design from SURVEY §5/§7. Data is generated on device per
    block (same methodology as bench.py: measures the scan+aggregate
    path, not host→device transfer of synthetic data)."""
    import jax
    import jax.numpy as jnp
    from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate

    groups = 10_000 * 12
    # exact sorted-uniform ids without int32-overflowing products
    # (x64 is off on TPU): block = groups * reps rows
    reps = max(1, block_rows // groups)
    block_rows = groups * reps

    @jax.jit
    def block_moments(key):
        kv = key
        # sorted-by-construction group ids (region scans arrive sorted
        # from the device merge; sorting here would be a datagen artifact)
        gids = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), reps)
        ts = jnp.zeros((block_rows,), jnp.int32)
        mask = jnp.ones((block_rows,), bool)
        vals = tuple(
            jax.random.uniform(jax.random.fold_in(kv, i),
                               (block_rows,), jnp.float32) * 100
            for i in range(5))
        # per-block moments: sums + counts (avg folds at the end)
        (s0, s1, s2, s3, s4), counts = sorted_grouped_aggregate(
            gids, mask, ts, vals, num_groups=groups, ops=("sum",) * 5)
        return jnp.stack([s0, s1, s2, s3, s4]), counts

    @jax.jit
    def merge(acc_s, acc_c, s, c):
        return acc_s + s, acc_c + c

    n_blocks = total_rows // block_rows
    key = jax.random.PRNGKey(0)
    s, c = block_moments(key)
    jax.block_until_ready(c)
    t0 = time.perf_counter()
    acc_s, acc_c = s, c
    for i in range(1, n_blocks):
        s, c = block_moments(jax.random.fold_in(key, i))
        acc_s, acc_c = merge(acc_s, acc_c, s, c)
    final_avg = acc_s / jnp.maximum(acc_c, 1)[None, :]
    float(np.asarray(final_avg)[0, 0])            # force completion
    dt = time.perf_counter() - t0
    rows_done = (n_blocks - 1) * block_rows       # first block was warmup
    _p("3b_tsbs_double_groupby_1B_blocked", rows_done / dt / 1e6,
       "Mrows/s", {"rows": rows_done + block_rows, "blocks": n_blocks,
                   "groups": groups, "block_rows": block_rows,
                   "wall_s": round(dt, 1)})


if __name__ == "__main__":
    main()
