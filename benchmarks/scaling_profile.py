"""Kernel-scaling bench: sorted_grouped_aggregate across group counts.

Measures the kernel-scaling table (25M rows, 5 metrics) in the
pipeline-realistic staging: gids/values device-resident (the scan cache
keeps them in HBM across queries) and segment ends precomputed (the LSM
scan path has run boundaries on the host already — tpu_exec ships them
with the query).

Usage: PYTHONPATH=. python benchmarks/scaling_profile.py
"""

import argparse
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp


def timeit(fn, *args, reps=3):
    """Time device compute: reduce outputs to one scalar ON DEVICE so the
    D2H transfer of the full result doesn't pollute the measurement."""
    @jax.jit
    def reduced(*a):
        leaves = jax.tree_util.tree_leaves(fn(*a))
        return sum(jnp.sum(jnp.nan_to_num(jnp.asarray(x, jnp.float32)))
                   for x in leaves)

    s = reduced(*args)
    np.asarray(s)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(reduced(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=25_000_000)
    ap.add_argument("--groups", default="480,12000,120000,1200000")
    ap.add_argument("--op-sets", default="avg,minmax,firstlast")
    args = ap.parse_args()
    from greptimedb_tpu.ops.kernels import _sorted_grouped_aggregate_pre

    OP_SETS = {
        "avg": ("avg",) * 5,
        "minmax": ("min", "max", "min", "max", "min"),
        "firstlast": ("first", "last"),
    }
    n = args.rows
    rng = np.random.default_rng(0)
    vals = jax.device_put(rng.random(n, dtype=np.float32))
    mask = jnp.ones(n, bool)
    ts = jax.device_put(np.arange(n, dtype=np.int32))
    for G in [int(g) for g in args.groups.split(",")]:
        gids_np = np.sort(rng.integers(0, G, n)).astype(np.int32)
        ends_np = np.cumsum(np.bincount(gids_np, minlength=G),
                            dtype=np.int64).astype(np.int32)
        # static longest-segment bucket, as the scan pipeline stages it
        # (enables the shift-doubling min/max + first/last kernels)
        from greptimedb_tpu.ops.kernels import seg_len_bucket
        seg_k = seg_len_bucket(
            int(np.diff(ends_np, prepend=np.int32(0)).max()))
        gids = jax.device_put(gids_np)
        ends = jax.device_put(ends_np)
        line = [f"G={G:>8}:"]
        for name in args.op_sets.split(","):
            ops = OP_SETS[name]
            f = functools.partial(_sorted_grouped_aggregate_pre,
                                  num_groups=G, ops=ops,
                                  has_col_masks=False, seg_len_k=seg_k)
            t = timeit(f, gids, mask, ts, tuple(vals for _ in ops), (),
                       ends)
            line.append(f"{name}[{len(ops)}c] {t*1e3:7.0f}ms"
                        f" {n/t/1e6:7.1f} Mrows/s")
        print("  ".join(line), flush=True)


if __name__ == "__main__":
    main()
