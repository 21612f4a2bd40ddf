"""Kernel tests against NumPy oracles.

Mirrors the reference's memtable/merge/dedup semantics tests
(src/storage/src/memtable/tests.rs, src/storage/src/read/merge.rs) and the
PromQL function tests (src/promql/src/functions/*)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from greptimedb_tpu.ops import Dictionary
from greptimedb_tpu.ops.kernels import (
    OP_DELETE, OP_PUT, combine_group_ids, grouped_aggregate,
    merge_dedup_numpy, pad_axis0, shape_bucket, sort_merge_dedup,
    time_bucket_ids,
)
from greptimedb_tpu.ops.window import (
    SeriesMatrix, instant_select, range_aggregate_cumsum,
    range_aggregate_gather,
)


class TestDictionary:
    def test_roundtrip(self):
        d = Dictionary()
        ids = d.encode(["a", "b", "a", "c"])
        assert ids.tolist() == [0, 1, 0, 2]
        assert d.decode(np.array([2, 0])) == ["c", "a"]
        assert d.encode_existing(["b", "zzz"]).tolist() == [1, -1]
        d2 = Dictionary.from_list(d.to_list())
        assert d2.encode_existing(["c"]).tolist() == [2]


class TestShapeBucket:
    def test_bucket(self):
        assert shape_bucket(1) == 1024
        assert shape_bucket(1025) == 2048
        assert shape_bucket(4096) == 4096

    def test_pad(self):
        a = np.arange(3)
        p = pad_axis0(a, 8, fill=-1)
        assert p.tolist() == [0, 1, 2, -1, -1, -1, -1, -1]


class TestGroupedAggregate:
    def _data(self, seed=0, n=1000, groups=7):
        rng = np.random.default_rng(seed)
        gids = rng.integers(0, groups, n).astype(np.int32)
        vals = rng.normal(size=n)
        mask = rng.random(n) > 0.3
        ts = rng.integers(0, 10_000, n).astype(np.int64)
        return gids, mask, ts, vals, groups

    def test_sum_count_avg_min_max(self):
        gids, mask, ts, vals, G = self._data()
        (s, c, a, mn, mx), counts = grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            (jnp.asarray(vals),) * 5,
            num_groups=G, ops=("sum", "count", "avg", "min", "max"))
        for g in range(G):
            sel = (gids == g) & mask
            if sel.any():
                # f32 accumulation in the production (x64-off) regime
                np.testing.assert_allclose(s[g], vals[sel].sum(), rtol=1e-4,
                                           atol=1e-4)
                assert int(c[g]) == sel.sum()
                np.testing.assert_allclose(a[g], vals[sel].mean(), rtol=1e-4,
                                           atol=1e-4)
                np.testing.assert_allclose(mn[g], vals[sel].min())
                np.testing.assert_allclose(mx[g], vals[sel].max())
            assert int(counts[g]) == sel.sum()

    def test_first_last(self):
        gids = np.array([0, 0, 1, 1, 0], dtype=np.int32)
        ts = np.array([5, 1, 9, 2, 3], dtype=np.int64)
        vals = np.array([50.0, 10.0, 90.0, 20.0, 30.0])
        mask = np.ones(5, dtype=bool)
        (fst, lst), _ = grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            (jnp.asarray(vals), jnp.asarray(vals)),
            num_groups=2, ops=("first", "last"))
        assert fst[0] == 10.0 and lst[0] == 50.0
        assert fst[1] == 20.0 and lst[1] == 90.0

    def test_empty_group(self):
        gids = np.array([0], dtype=np.int32)
        mask = np.ones(1, dtype=bool)
        ts = np.zeros(1, dtype=np.int64)
        vals = np.array([1.0])
        (a,), counts = grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            (jnp.asarray(vals),), num_groups=3, ops=("avg",))
        assert counts[1] == 0 and counts[2] == 0
        assert np.isnan(a[1])

    def test_variance_large_tight_values(self):
        """Shifted-moment regression: int columns must not wrap on
        squaring, and f32 cancellation must not floor the variance of
        large, tight distributions (review r4)."""
        from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate
        gids = np.zeros(3, np.int32)
        mask = np.ones(3, bool)
        ts = np.arange(3, dtype=np.int32)
        for vals in (np.array([100000, 100000, 100001], np.int32),
                     np.array([100000.0, 100000.0, 100001.0], np.float32)):
            (v1,), _ = grouped_aggregate(
                jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
                (jnp.asarray(vals),), num_groups=1, ops=("variance",))
            (v2,), _ = sorted_grouped_aggregate(
                gids, mask, ts, (jnp.asarray(vals),), num_groups=1,
                ops=("variance",))
            np.testing.assert_allclose(float(v1[0]), 1 / 3, rtol=1e-3)
            np.testing.assert_allclose(float(v2[0]), 1 / 3, rtol=1e-3)

    def test_stddev(self):
        gids, mask, ts, vals, G = self._data(seed=3)
        (sd,), counts = grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            (jnp.asarray(vals),), num_groups=G, ops=("stddev",))
        for g in range(G):
            sel = (gids == g) & mask
            if sel.sum() > 1:
                np.testing.assert_allclose(sd[g], vals[sel].std(ddof=1),
                                           rtol=1e-6)

    def test_time_bucket_combine(self):
        ts = jnp.array([0, 999, 1000, 2500], dtype=jnp.int32)
        b = time_bucket_ids(ts, 0, 1000, 4)
        assert b.tolist() == [0, 0, 1, 2]
        gid = combine_group_ids(jnp.array([1, 0, 1, 0]), b, 4)
        assert gid.tolist() == [4, 0, 5, 2]


class TestMergeDedup:
    def test_basic_dedup(self):
        # two runs: memtable overwrites an SST row at (s=0, ts=10)
        series = np.array([0, 0, 1, 0], dtype=np.int32)
        ts = np.array([10, 20, 10, 10], dtype=np.int64)
        seq = np.array([1, 2, 3, 7], dtype=np.int64)
        op = np.array([OP_PUT] * 4, dtype=np.int8)
        kept = merge_dedup_numpy(series, ts, seq, op)
        # rows sorted by (series, ts): winner at (0,10) is seq=7 → index 3
        assert kept.tolist() == [3, 1, 2]

    def test_delete_hides_row(self):
        series = np.array([0, 0], dtype=np.int32)
        ts = np.array([10, 10], dtype=np.int64)
        seq = np.array([1, 2], dtype=np.int64)
        op = np.array([OP_PUT, OP_DELETE], dtype=np.int8)
        kept = merge_dedup_numpy(series, ts, seq, op)
        assert kept.tolist() == []

    def test_device_matches_numpy(self):
        rng = np.random.default_rng(42)
        n = 500
        series = rng.integers(0, 20, n).astype(np.int32)
        ts = rng.integers(0, 50, n).astype(np.int64)
        seq = np.arange(n, dtype=np.int64)
        op = rng.choice([OP_PUT, OP_PUT, OP_PUT, OP_DELETE], n).astype(np.int8)
        valid = np.ones(n, dtype=bool)
        order, keep = sort_merge_dedup(
            jnp.asarray(series), jnp.asarray(ts), jnp.asarray(seq),
            jnp.asarray(op), jnp.asarray(valid))
        device_kept = np.asarray(order)[np.asarray(keep)]
        oracle = merge_dedup_numpy(series, ts, seq, op)
        assert device_kept.tolist() == oracle.tolist()

    def test_padding_rows_dropped(self):
        series = np.array([0, 0, 0], dtype=np.int32)
        ts = np.array([1, 2, 3], dtype=np.int64)
        seq = np.array([1, 2, 3], dtype=np.int64)
        op = np.zeros(3, dtype=np.int8)
        valid = np.array([True, True, False])
        order, keep = sort_merge_dedup(
            jnp.asarray(series), jnp.asarray(ts), jnp.asarray(seq),
            jnp.asarray(op), jnp.asarray(valid))
        kept = np.asarray(order)[np.asarray(keep)]
        assert 2 not in kept.tolist() and len(kept) == 2


def make_matrix():
    # 3 series; series 0: samples every 10s; series 1: sparse; series 2: empty
    s0_ts = np.arange(0, 300_000, 10_000, dtype=np.int64)
    s0_v = np.arange(len(s0_ts), dtype=np.float64)  # counter 0,1,2...
    s1_ts = np.array([50_000, 250_000], dtype=np.int64)
    s1_v = np.array([5.0, 2.0])
    series = np.concatenate([np.zeros(len(s0_ts)), np.ones(len(s1_ts))]).astype(np.int32)
    ts = np.concatenate([s0_ts, s1_ts])
    vals = np.concatenate([s0_v, s1_v])
    return SeriesMatrix.build(series, ts, vals, 3)


class TestWindow:
    def test_build(self):
        m = make_matrix()
        assert m.num_series == 3
        assert m.lengths.tolist() == [30, 2, 0]

    def test_avg_sum_count(self):
        m = make_matrix()
        # steps at 60s, 120s; range 60s → window (t-60s, t]
        out, ok = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            60_000, 60_000, 60_000, op="avg_over_time", nsteps=2)
        # series 0 window (0,60s]: samples at 10..60s → values 1..6 → avg 3.5
        np.testing.assert_allclose(out[0, 0], 3.5)
        # window (60s,120s]: values 7..12 → avg 9.5
        np.testing.assert_allclose(out[0, 1], 9.5)
        assert not bool(ok[2, 0])  # empty series
        out, _ = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            60_000, 60_000, 60_000, op="count_over_time", nsteps=2)
        assert out[0, 0] == 6

    def test_min_max_gather(self):
        m = make_matrix()
        out, ok = range_aggregate_gather(
            m.ts, m.values,
            60_000, 60_000, 60_000, op="max_over_time", nsteps=2, maxw=32)
        np.testing.assert_allclose(out[0, 0], 6.0)
        np.testing.assert_allclose(out[0, 1], 12.0)
        out, _ = range_aggregate_gather(
            m.ts, m.values,
            60_000, 60_000, 60_000, op="min_over_time", nsteps=2, maxw=32)
        np.testing.assert_allclose(out[0, 0], 1.0)

    def test_rate_steady_counter(self):
        m = make_matrix()
        # series 0 increases by 1 every 10s → rate = 0.1/s
        out, ok = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            100_000, 100_000, 100_000, op="rate", nsteps=2)
        assert bool(ok[0, 0])
        np.testing.assert_allclose(out[0, 0], 0.1, rtol=1e-6)

    def test_increase_with_reset(self):
        ts = np.arange(0, 50_000, 10_000, dtype=np.int64)
        vals = np.array([0.0, 10.0, 20.0, 5.0, 15.0])  # reset at i=3
        m = SeriesMatrix.build(np.zeros(5, np.int32), ts, vals, 1)
        out, ok = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            40_000, 40_000, 40_000, op="increase", nsteps=1)
        # within (0, 40000]: samples v=10,20,5,15 → adjusted 10,20,25,35
        # raw = 25; extrapolation factor: sampled=30000, durToStart/End=10000/0,
        # avg_dur=10000, threshold=11000 → ext=10000+0 → factor=40/30
        np.testing.assert_allclose(out[0, 0], 25 * (40000 / 30000), rtol=1e-6)

    def test_delta_gauge(self):
        ts = np.arange(0, 50_000, 10_000, dtype=np.int64)
        vals = np.array([10.0, 8.0, 6.0, 4.0, 2.0])
        m = SeriesMatrix.build(np.zeros(5, np.int32), ts, vals, 1)
        out, ok = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            40_000, 40_000, 40_000, op="delta", nsteps=1)
        np.testing.assert_allclose(out[0, 0], (2.0 - 8.0) * (40000 / 30000), rtol=1e-6)

    def test_changes_resets(self):
        ts = np.arange(0, 60_000, 10_000, dtype=np.int64)
        vals = np.array([1.0, 1.0, 2.0, 1.0, 1.0, 3.0])
        m = SeriesMatrix.build(np.zeros(6, np.int32), ts, vals, 1)
        out, _ = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            50_000, 50_000, 50_001, op="changes", nsteps=1)
        assert out[0, 0] == 3  # 1→2, 2→1, 1→3
        out, _ = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            50_000, 50_000, 50_001, op="resets", nsteps=1)
        assert out[0, 0] == 1

    def test_quantile(self):
        ts = np.arange(0, 40_000, 10_000, dtype=np.int64)
        vals = np.array([1.0, 2.0, 3.0, 4.0])
        m = SeriesMatrix.build(np.zeros(4, np.int32), ts, vals, 1)
        out, _ = range_aggregate_gather(
            m.ts, m.values,
            30_000, 30_000, 30_001, op="quantile_over_time", nsteps=1,
            maxw=8, param=0.5)
        np.testing.assert_allclose(out[0, 0], 2.5)

    def test_deriv(self):
        ts = np.arange(0, 50_000, 10_000, dtype=np.int64)
        vals = 2.0 * np.arange(5) + 3.0  # slope 2 per 10s = 0.2/s
        m = SeriesMatrix.build(np.zeros(5, np.int32), ts, vals, 1)
        out, ok = range_aggregate_gather(
            m.ts, m.values,
            40_000, 40_000, 40_001, op="deriv", nsteps=1, maxw=8)
        np.testing.assert_allclose(out[0, 0], 0.2, rtol=1e-5)

    def test_instant_select_lookback(self):
        m = make_matrix()
        vals, ok = instant_select(
            m.ts, m.values,
            55_000, 100_000, 300_000, nsteps=1)
        # series 1 latest sample at 50s (value 5.0) within 5m lookback
        assert bool(ok[1, 0]) and vals[1, 0] == 5.0
        # short lookback (1s) → no point
        vals, ok = instant_select(
            m.ts, m.values,
            55_000, 100_000, 1_000, nsteps=1)
        assert not bool(ok[1, 0])

    def test_idelta_first_last(self):
        ts = np.arange(0, 40_000, 10_000, dtype=np.int64)
        vals = np.array([1.0, 5.0, 2.0, 9.0])
        m = SeriesMatrix.build(np.zeros(4, np.int32), ts, vals, 1)
        args = (m.ts, m.values, m.lengths, 30_000, 30_000, 30_001)
        out, _ = range_aggregate_cumsum(*args, op="idelta", nsteps=1)
        np.testing.assert_allclose(out[0, 0], 7.0)
        out, _ = range_aggregate_cumsum(*args, op="last_over_time", nsteps=1)
        assert out[0, 0] == 9.0
        out, _ = range_aggregate_cumsum(*args, op="first_over_time", nsteps=1)
        assert out[0, 0] == 1.0


class TestReviewRegressions:
    """Regression tests for code-review findings."""

    def test_timestamp_eq_hash_cross_unit(self):
        from greptimedb_tpu.common.time import Timestamp, TimeUnit
        a = Timestamp(1, TimeUnit.SECOND)
        b = Timestamp(1000, TimeUnit.MILLISECOND)
        assert a == b and hash(a) == hash(b)
        assert len({a, b}) == 1

    def test_timestamp_ns_precision(self):
        from greptimedb_tpu.common.time import Timestamp, TimeUnit
        t = Timestamp.from_str("2023-01-02 03:04:05.123456", TimeUnit.NANOSECOND)
        assert t.value % 1_000_000_000 == 123_456_000

    def test_series_matrix_max_len_too_small(self):
        with pytest.raises(ValueError, match="max_len"):
            SeriesMatrix.build(np.zeros(10, np.int32),
                               np.arange(10, dtype=np.int64),
                               np.zeros(10), 1, max_len=4)

    def test_device_arrays_int32_rebase(self):
        base_ts = 1_700_000_000_000
        ts = base_ts + np.arange(0, 50_000, 10_000, dtype=np.int64)
        m = SeriesMatrix.build(np.zeros(5, np.int32), ts, np.arange(5.0), 2)
        rel, vals, lengths, base = m.device_arrays()
        assert rel.dtype == np.int32 and base == base_ts
        assert rel[0, 0] == 0 and rel[0, 4] == 40_000
        # padding sentinel survives as int32 max (still sorts last)
        assert rel[1, 0] == np.iinfo(np.int32).max
        # kernels accept the rebased arrays with rebased query times
        out, ok = range_aggregate_cumsum(
            jnp.asarray(rel), jnp.asarray(vals), jnp.asarray(lengths),
            40_000, 40_000, 40_001, op="sum_over_time", nsteps=1)
        np.testing.assert_allclose(out[0, 0], 10.0)

    def test_first_last_preserve_int_dtype(self):
        import jax
        gids = np.array([0], np.int32)
        mask = np.ones(1, bool)
        ts = np.array([5], np.int64)
        big = np.array([2**60 + 7], np.int64)
        if jax.config.jax_enable_x64:
            (fst,), _ = grouped_aggregate(gids, mask, ts, (big,),
                                          num_groups=2, ops=("first",))
            assert fst.dtype == jnp.int64
            assert int(fst[0]) == 2**60 + 7
        else:
            # production regime: values beyond int32 cannot ride the device
            # silently — the host guard must refuse, not truncate
            with pytest.raises(ValueError, match="rebase"):
                grouped_aggregate(gids, mask, ts, (big,),
                                  num_groups=2, ops=("first",))
        # in-range int values keep an integer dtype end to end
        small = np.array([123456], np.int64)
        (fst,), _ = grouped_aggregate(gids, mask, ts, (small,),
                                      num_groups=2, ops=("first",))
        assert jnp.issubdtype(fst.dtype, jnp.integer)
        assert int(fst[0]) == 123456

    def test_holt_winters(self):
        ts = np.arange(0, 60_000, 10_000, dtype=np.int64)
        vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
        m = SeriesMatrix.build(np.zeros(6, np.int32), ts, vals, 1)
        out, ok = range_aggregate_gather(
            m.ts, m.values,
            50_000, 50_000, 50_001, op="holt_winters", nsteps=1, maxw=8,
            param=0.5, param2=0.5)
        assert bool(ok[0, 0])
        # perfectly linear data → smoothed value equals the last sample
        np.testing.assert_allclose(out[0, 0], 6.0, rtol=1e-5)

    def test_rate_negative_first_sample_no_zero_cap(self):
        ts = np.arange(0, 30_000, 10_000, dtype=np.int64)
        vals = np.array([-5.0, 5.0, 10.0])
        m = SeriesMatrix.build(np.zeros(3, np.int32), ts, vals, 1)
        out, ok = range_aggregate_cumsum(
            m.ts, m.values, m.lengths,
            30_000, 30_000, 30_001, op="increase", nsteps=1)
        assert bool(ok[0, 0])
        assert float(out[0, 0]) > 0  # not sign-flipped by a negative cap


# ---------------------------------------------------------------------------
# sorted_grouped_aggregate (the scatter-free LSM fast path)
# ---------------------------------------------------------------------------

def block_edge_lens():
    """Segment lengths, laid end to end from row 0, that meet the prefix
    form's blocks of `_SUM_BLOCK` rows every way."""
    from greptimedb_tpu.ops.kernels import _SUM_BLOCK as B
    return [B - 1,          # shorter than a block
            1,              # ends on the block's edge
            B,              # exactly a block, edge to edge
            0,              # empty, on an edge
            B + 1,          # a block and a row of the next
            3 * B + 5,      # over three blocks, both ends inside one
            5,              # inside one block
            4 * B - 11,     # over three whole blocks, ends on an edge
            7]


class TestSortedGroupedAggregate:
    def _mk(self, n=50_000, groups=97, skew=False, seed=3):
        rng = np.random.default_rng(seed)
        if skew:
            raw = rng.zipf(1.5, n) % groups
        else:
            raw = rng.integers(0, groups, n)
        gids = np.sort(raw).astype(np.int32)
        mask = rng.random(n) > 0.15
        ts = np.arange(n, dtype=np.int32)  # sorted within groups by position
        vals = (rng.normal(size=n) * 50).astype(np.float32)
        return gids, mask, ts, vals

    @pytest.mark.parametrize("ops", [
        ("sum", "count", "avg", "min", "max"),
        ("stddev", "variance", "first", "last"),
    ])
    @pytest.mark.parametrize("skew", [False, True])
    def test_matches_scatter_kernel(self, ops, skew):
        from greptimedb_tpu.ops.kernels import (
            grouped_aggregate, sorted_grouped_aggregate)
        groups = 97
        gids, mask, ts, vals = self._mk(groups=groups, skew=skew)
        values = tuple(vals for _ in ops)
        got, counts = sorted_grouped_aggregate(
            gids, mask, ts, values, num_groups=groups, ops=ops)
        want, want_counts = grouped_aggregate(
            gids, mask, ts, values, num_groups=groups, ops=ops)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        for op, g, w in zip(ops, got, want):
            # both kernels accumulate in f32; differing association orders
            # legitimately diverge ~1e-3 on cancellation-heavy skewed sums
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(w, np.float64),
                rtol=2e-3, atol=2e-3, err_msg=f"{op} skew={skew}")

    @pytest.mark.parametrize("ops", [
        ("min", "max"),
        ("first", "last"),
        ("min", "max", "first", "last", "avg"),
    ])
    def test_doubling_kernels_high_cardinality(self, ops):
        """The shift-doubling min/max + argext kernels (seg_len_k set,
        G > the high-card threshold) match the scatter oracle, including
        masked rows, empty groups, and skewed segment lengths."""
        from greptimedb_tpu.ops.kernels import (
            grouped_aggregate, sorted_grouped_aggregate)
        rng = np.random.default_rng(11)
        G = 9000                      # > _SEG_HIGH_CARD_THRESHOLD
        n = 120_000
        raw = np.concatenate([
            rng.integers(0, G, n - 5000),
            np.full(5000, 1234)])     # one fat segment (skew)
        gids = np.sort(raw).astype(np.int32)
        mask = rng.random(n) > 0.2
        ts = rng.integers(0, 1 << 20, n).astype(np.int32)
        vals = (rng.normal(size=n) * 50).astype(np.float32)
        ends = np.cumsum(np.bincount(gids, minlength=G),
                         dtype=np.int64).astype(np.int32)
        from greptimedb_tpu.ops.kernels import seg_len_bucket
        seg_k = seg_len_bucket(
            int(np.diff(ends, prepend=np.int32(0)).max()))
        values = tuple(vals for _ in ops)
        got, counts = sorted_grouped_aggregate(
            gids, mask, ts, values, num_groups=G, ops=ops, ends=ends,
            seg_len_k=seg_k)
        want, want_counts = grouped_aggregate(
            gids, mask, ts, values, num_groups=G, ops=ops)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        for op, g, w in zip(ops, got, want):
            np.testing.assert_allclose(
                np.asarray(g, np.float64), np.asarray(w, np.float64),
                rtol=2e-3, atol=2e-3, err_msg=op)

    def test_small_and_empty_groups(self):
        from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate
        # groups 0,2 used; 1,3 empty; single-row group
        gids = np.array([0, 0, 0, 2], np.int32)
        mask = np.array([True, True, False, True])
        ts = np.arange(4, dtype=np.int32)
        vals = np.array([1.0, 5.0, 100.0, -3.0], np.float32)
        (s, mn, mx, fst), counts = sorted_grouped_aggregate(
            gids, mask, ts, (vals,) * 4, num_groups=4,
            ops=("sum", "min", "max", "first"))
        np.testing.assert_array_equal(np.asarray(counts), [2, 0, 1, 0])
        np.testing.assert_allclose(np.asarray(s), [6.0, 0.0, -3.0, 0.0])
        assert np.asarray(mn)[0] == 1.0 and np.asarray(mx)[0] == 5.0
        assert np.asarray(mn)[2] == -3.0
        assert np.asarray(fst)[0] == 1.0 and np.asarray(fst)[2] == -3.0
        assert np.isnan(np.asarray(fst)[1])

    def test_col_masks_null_semantics(self):
        from greptimedb_tpu.ops.kernels import (
            grouped_aggregate, sorted_grouped_aggregate)
        rng = np.random.default_rng(5)
        n, groups = 4096, 7
        gids = np.sort(rng.integers(0, groups, n)).astype(np.int32)
        mask = np.ones(n, bool)
        cm = rng.random(n) > 0.5
        ts = np.arange(n, dtype=np.int32)
        vals = rng.normal(size=n).astype(np.float32)
        got, _ = sorted_grouped_aggregate(
            gids, mask, ts, (vals, vals), (cm, np.ones(n, bool)),
            num_groups=groups, ops=("avg", "count"), has_col_masks=True)
        want, _ = grouped_aggregate(
            gids, mask, ts, (vals, vals), (cm, np.ones(n, bool)),
            num_groups=groups, ops=("avg", "count"), has_col_masks=True)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    def test_first_last_unsorted_ts_within_segment(self):
        # several series collapse into one GROUP BY key → ts NOT sorted
        # within the segment; first/last must still pick by extreme ts
        from greptimedb_tpu.ops.kernels import (
            grouped_aggregate, sorted_grouped_aggregate)
        rng = np.random.default_rng(11)
        n, groups = 5000, 5
        gids = np.sort(rng.integers(0, groups, n)).astype(np.int32)
        ts = rng.permutation(n).astype(np.int32)  # unique → no ties
        mask = rng.random(n) > 0.2
        vals = rng.normal(size=n).astype(np.float32)
        got, _ = sorted_grouped_aggregate(
            gids, mask, ts, (vals, vals), num_groups=groups,
            ops=("first", "last"))
        want, _ = grouped_aggregate(
            gids, mask, ts, (vals, vals), num_groups=groups,
            ops=("first", "last"))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]))

    def test_block_boundary_segments(self):
        # segments straddling exactly the 1024-block boundaries
        from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate
        B = 1024
        sizes = [B - 1, 1, B, 2 * B - 2, 3, 2 * B + 5]
        gids = np.concatenate([np.full(s, i, np.int32)
                               for i, s in enumerate(sizes)])
        n = len(gids)
        vals = np.random.default_rng(0).normal(size=n).astype(np.float32)
        mask = np.ones(n, bool)
        ts = np.arange(n, dtype=np.int32)
        (s, mn, mx, lst), counts = sorted_grouped_aggregate(
            gids, mask, ts, (vals,) * 4, num_groups=len(sizes),
            ops=("sum", "min", "max", "last"))
        off = 0
        for i, sz in enumerate(sizes):
            seg = vals[off:off + sz]
            np.testing.assert_allclose(np.asarray(s)[i], seg.sum(), rtol=1e-4,
                                       atol=1e-4)
            assert np.asarray(mn)[i] == seg.min()
            assert np.asarray(mx)[i] == seg.max()
            assert np.asarray(lst)[i] == seg[-1]
            off += sz


class TestHighCardinalityPaths:
    """Force num_groups above _SEG_HIGH_CARD_THRESHOLD so the prefix-sum
    and in-block sparse-table paths (not the edge-window path) execute,
    cross-checked against the numpy oracle."""

    def _data(self, n=200_000, groups=20_000, seed=0):
        rng = np.random.default_rng(seed)
        gids = np.sort(rng.integers(0, groups, n)).astype(np.int32)
        ts = rng.integers(0, 1 << 30, n).astype(np.int64)
        vals = (rng.random(n, dtype=np.float32) * 100) - 50
        mask = rng.random(n) > 0.1
        return gids, mask, ts, vals, groups

    def test_sum_min_max_avg_vs_oracle(self):
        from greptimedb_tpu.ops.kernels import (
            _SEG_HIGH_CARD_THRESHOLD, sorted_grouped_aggregate)
        gids, mask, ts, vals, groups = self._data()
        assert groups > _SEG_HIGH_CARD_THRESHOLD
        ops = ("sum", "min", "max", "avg", "count")
        (s, mn, mx, av, ct), counts = sorted_grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            tuple(jnp.asarray(vals) for _ in ops),
            num_groups=groups, ops=ops)
        import pandas as pd
        df = pd.DataFrame({"g": gids[mask], "v": vals[mask]})
        want = df.groupby("g")["v"].agg(["sum", "min", "max", "mean",
                                         "count"])
        got_s, got_mn = np.asarray(s), np.asarray(mn)
        got_mx, got_av = np.asarray(mx), np.asarray(av)
        got_ct = np.asarray(ct)
        for g in want.index[:4000]:
            np.testing.assert_allclose(got_s[g], want.loc[g, "sum"],
                                       rtol=2e-4, atol=1e-3)
            assert got_mn[g] == np.float32(want.loc[g, "min"])
            assert got_mx[g] == np.float32(want.loc[g, "max"])
            np.testing.assert_allclose(got_av[g], want.loc[g, "mean"],
                                       rtol=2e-4, atol=1e-3)
            assert got_ct[g] == want.loc[g, "count"]
        # empty groups: count 0 and min/max at the +/-inf identities
        empty = np.setdiff1d(np.arange(groups), gids[mask])[:50]
        assert (got_ct[empty] == 0).all()
        if len(empty):
            assert np.isposinf(got_mn[empty]).all()
            assert np.isneginf(got_mx[empty]).all()

    def test_segments_spanning_blocks(self):
        """Shapes that hit every decomposition branch: empty, single-row,
        single-block, two-block-no-inner, many-inner-blocks."""
        from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate
        lens = [0, 1, 5, 31, 32, 33, 63, 64, 65, 200, 1024]
        groups = 9000                     # above the threshold
        seg = []
        for g, ln in enumerate(lens):
            seg += [g] * ln
        # the rest of the groups get 0-2 rows
        rng = np.random.default_rng(1)
        extra = np.sort(rng.integers(len(lens), groups, 5000))
        gids = np.concatenate([np.array(seg, np.int32),
                               extra.astype(np.int32)])
        n = len(gids)
        vals = (rng.random(n, dtype=np.float32) * 10) - 5
        mask = np.ones(n, bool)
        ts = np.arange(n, dtype=np.int64)
        (mn, mx), _counts = sorted_grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            (jnp.asarray(vals), jnp.asarray(vals)),
            num_groups=groups, ops=("min", "max"))
        mn, mx = np.asarray(mn), np.asarray(mx)
        for g in range(len(lens)):
            rows = vals[gids == g]
            if len(rows):
                assert mn[g] == rows.min(), f"min len={lens[g]}"
                assert mx[g] == rows.max(), f"max len={lens[g]}"
        for g in np.unique(extra)[:200]:
            rows = vals[gids == g]
            assert mn[g] == rows.min() and mx[g] == rows.max()

    def test_precomputed_ends_match_device_bounds(self):
        """The host-ends fast path (LSM callers ship run boundaries) must
        agree exactly with the on-device searchsorted bounds."""
        from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate
        rng = np.random.default_rng(9)
        n, groups = 100_000, 11_000
        gids = np.sort(rng.integers(0, groups, n)).astype(np.int32)
        mask = rng.random(n) > 0.2
        ts = np.arange(n, dtype=np.int32)
        vals = rng.normal(size=n).astype(np.float32)
        ends = np.cumsum(np.bincount(gids, minlength=groups),
                         dtype=np.int64).astype(np.int32)
        ops = ("sum", "avg", "min", "max", "count", "first", "last")
        values = tuple(vals for _ in ops)
        got, counts = sorted_grouped_aggregate(
            gids, mask, ts, values, num_groups=groups, ops=ops, ends=ends)
        want, want_counts = sorted_grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            tuple(jnp.asarray(v) for v in values),
            num_groups=groups, ops=ops)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        for op, g, w in zip(ops, got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-5, err_msg=op,
                                       equal_nan=True)

    def test_first_last_high_cardinality(self):
        """first/last above the threshold (two-pass argext path) vs a
        pandas oracle, with unsorted ts inside segments and ties."""
        from greptimedb_tpu.ops.kernels import (
            _SEG_HIGH_CARD_THRESHOLD, sorted_grouped_aggregate)
        rng = np.random.default_rng(5)
        n, groups = 120_000, 20_000
        assert groups > _SEG_HIGH_CARD_THRESHOLD
        gids = np.sort(rng.integers(0, groups, n)).astype(np.int32)
        ts = rng.integers(0, 50, n).astype(np.int64)   # many ties
        vals = rng.random(n, dtype=np.float32)
        mask = rng.random(n) > 0.15
        (first, last), _c = sorted_grouped_aggregate(
            jnp.asarray(gids), jnp.asarray(mask), jnp.asarray(ts),
            (jnp.asarray(vals), jnp.asarray(vals)),
            num_groups=groups, ops=("first", "last"))
        first, last = np.asarray(first), np.asarray(last)
        import pandas as pd
        df = pd.DataFrame({"g": gids, "t": ts, "v": vals,
                           "i": np.arange(n)})[mask]
        # oracle: smallest (t, i) / largest (t, i) per group
        fo = df.sort_values(["g", "t", "i"]).groupby("g").first()["v"]
        lo = df.sort_values(["g", "t", "i"]).groupby("g").last()["v"]
        for g in fo.index[:3000]:
            assert first[g] == np.float32(fo.loc[g]), g
            assert last[g] == np.float32(lo.loc[g]), g


class TestSegmentsPickedOutOfALayout:
    """`sorted_grouped_aggregate` with explicit `starts` beside `ends`
    (ISSUE 36): the segments asked for are some of a dense layout's (a
    scan's live runs out of the table's), `gids` stays the dense layout's
    run ids, and every op answers what the dense launch answers at those
    segments."""

    #: (id, the dense layout's groups, segments picked; None: all of them,
    #: with `starts` the shift of `ends`)
    LAYOUTS = [
        ("low", 300, 60),                 # both under the threshold
        ("high", 40_000, 12_000),         # both above it
        ("straddle", 20_000, 2_000),      # the layout above, the picked under
        ("low-shift", 300, None),
        ("high-shift", 40_000, None),
        # segments that meet the prefix form's blocks every way
        # (`block_edge_lens`), picked and dense against `starts=`
        ("high-blocks", 40_000, 12_000),
        ("high-blocks-shift", 40_000, None),
        ("low-blocks-shift", 300, None),      # the counts' prefix form
    ]

    @pytest.mark.parametrize("with_k", [True, False],
                             ids=["seg_len_k", "no-seg_len_k"])
    @pytest.mark.parametrize("layout", LAYOUTS, ids=[c[0] for c in LAYOUTS])
    def test_equals_the_dense_launch_at_those_segments(self, layout, with_k):
        from greptimedb_tpu.ops.kernels import (
            _SEG_HIGH_CARD_THRESHOLD, seg_len_bucket,
            sorted_grouped_aggregate)
        name, groups, picked = layout
        rng = np.random.default_rng(groups + bool(with_k))
        longest = 70                  # past two 32-row blocks
        lens = rng.integers(0, 9, groups)
        lens[rng.integers(0, groups, 12)] = rng.integers(30, longest + 1, 12)
        if "blocks" in name:
            edge = block_edge_lens()
            lens[:len(edge)], longest = edge, max(edge)
        n = int(lens.sum())
        dense_b = shape_bucket(groups, minimum=256)
        dense_ends = np.full(dense_b, n, dtype=np.int32)
        dense_ends[:groups] = np.cumsum(lens)
        dense_starts = np.concatenate([[0], dense_ends[:-1]]).astype(np.int32)
        gids = np.repeat(np.arange(groups, dtype=np.int32), lens)
        ts = rng.integers(0, 40, n).astype(np.int32)        # ties
        vals = (rng.random(n, dtype=np.float32) * 100) - 50
        mask = rng.random(n) > 0.15
        valid = rng.random(n) > 0.1
        ops = ("count", "sum", "avg", "min", "max", "first", "last",
               "min", "max") + (("growth",) if with_k else ())
        values = tuple(ts if i in (7, 8) else vals for i in range(len(ops)))
        k = seg_len_bucket(longest) if with_k else None

        if picked is None:
            live = np.arange(dense_b)
            live_b, starts, ends = dense_b, dense_starts, dense_ends
        else:
            live = rng.choice(groups, picked, replace=False)
            if "blocks" in name:      # the segments at the blocks' edges
                live = np.union1d(live[len(edge):], np.arange(len(edge)))
            live, picked = np.sort(live), len(live)
            # one picked segment with rows and none under the mask
            emptied = live[np.nonzero(lens[live] > 2)[0][
                -3 if "blocks" in name else 3]]
            mask[dense_starts[emptied]:dense_ends[emptied]] = False
            live_b = shape_bucket(picked, minimum=256)
            assert live_b > picked            # padding groups past the live
            starts = np.full(live_b, n, dtype=np.int32)
            ends = np.full(live_b, n, dtype=np.int32)
            starts[:picked] = dense_starts[live]
            ends[:picked] = dense_ends[live]
        assert (dense_b > _SEG_HIGH_CARD_THRESHOLD) == (not name.startswith(
            "low"))
        assert (live_b > _SEG_HIGH_CARD_THRESHOLD) == name.startswith("high")

        def run(num_groups, **segments):
            res, counts = sorted_grouped_aggregate(
                gids, mask, ts, values, tuple(valid for _ in ops),
                num_groups=num_groups, ops=ops, has_col_masks=True,
                seg_len_k=k, **segments)
            return [np.asarray(r) for r in res], np.asarray(counts)

        want, want_counts = run(dense_b, ends=dense_ends)
        got, got_counts = run(live_b, ends=ends, starts=starts)
        m = len(live)
        assert np.array_equal(got_counts[:m], want_counts[live])
        assert (got_counts[m:] == 0).all()
        if picked is not None:
            at = int(np.searchsorted(live, emptied))
            assert got_counts[at] == 0 and np.isnan(got[5][at])
        for i, (op, g, w) in enumerate(zip(ops, got, want)):
            g, w = g[:m], w[live]
            if op in ("sum", "avg") and name == "straddle":
                # edge windows against prefix differences: f32 rounding
                np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-3,
                                           err_msg=op, equal_nan=True)
            else:
                assert np.array_equal(g, w, equal_nan=True), (op, i)


class TestThePrefixFormsBlocks:
    """ISSUE 45: past `_SEG_HIGH_CARD_THRESHOLD` groups (a count: at any
    cardinality) a sum reads its rows in blocks of `_SUM_BLOCK`: a segment
    inside one block is that block's row summed between its bounds, a
    longer one the rest of its first block, the whole blocks between and
    the head of its last. Every way a segment can meet the blocks, against
    a float64 reference at this file's tolerance for such sums, counts
    exact, and `starts=` (all of the layout, and every other segment of
    it) against the dense launch bit for bit."""

    #: id -> the segments' lengths, laid end to end from row 0 (B: the
    #: rows of a block); the groups past them are padding (starts == ends)
    CASES = {
        "shorter-than-a-block": lambda B: [B - 1, 3, B // 2, 1, B - 2],
        "exactly-a-block": lambda B: [B, B, B],
        "three-and-more-blocks": lambda B: [3 * B + 5, 4 * B, 7 * B + 1, 2],
        "an-end-on-a-blocks-edge": lambda B: [B - 1, 1, 2 * B, B // 2,
                                              B // 2, 3 * B, 7],
        "n-no-multiple-of-the-block": lambda B: [B, B + 3, 2 * B + 17],
        "n-under-one-block": lambda B: [5, 0, 7, B // 4],
        "empty-segments": lambda B: [0, B, 0, 0, 5, 0, 2 * B, 0, 0, 9],
        "one-segment-of-forty-blocks": lambda B: [40 * B + 3, 1],
        # a gauge at 1e10 (node_memory_*) beside one at 1, in one block
        # and across a block's edge
        "1e10-beside-1": lambda B: [B // 2, B // 4, B // 2, B // 4, B // 8,
                                    2 * B, B // 2, 3],
    }
    GROUPS = 16_384

    @pytest.mark.parametrize("case", list(CASES))
    def test_against_float64_and_dense_against_starts(self, case):
        from greptimedb_tpu.ops.kernels import (
            _SEG_HIGH_CARD_THRESHOLD, _SUM_BLOCK, sorted_grouped_aggregate)
        assert self.GROUPS > _SEG_HIGH_CARD_THRESHOLD
        lens = np.array(self.CASES[case](_SUM_BLOCK))
        rng = np.random.default_rng(len(case))
        n, m = int(lens.sum()), len(lens)
        ends = np.full(self.GROUPS, n, dtype=np.int32)
        ends[:m] = np.cumsum(lens)
        starts = np.concatenate([[0], ends[:-1]]).astype(np.int32)
        gids = np.repeat(np.arange(m, dtype=np.int32), lens)
        ts = np.arange(n, dtype=np.int32)
        if case == "1e10-beside-1":
            vals = np.where(gids % 2 == 0, 1e10, 1.0).astype(np.float32)
            vals *= rng.integers(1, 4, n)
            mask = np.ones(n, bool)
        else:
            vals = (rng.random(n, dtype=np.float32) * 100) - 50
            mask = rng.random(n) > 0.2
        valid = rng.random(n) > 0.1
        ops = ("sum", "count", "avg")

        def run(**segments):
            res, counts = sorted_grouped_aggregate(
                gids, mask, ts, (vals,) * 3, (valid,) * 3,
                num_groups=self.GROUPS, ops=ops, has_col_masks=True,
                **segments)
            return [np.asarray(r) for r in res], np.asarray(counts)

        (sm, ct, av), rows = run(ends=ends)
        # counts: exact; float sums: float64 at the file's tolerance
        keep = mask & valid
        v64 = np.where(keep, vals.astype(np.float64), 0)
        at = np.concatenate([[0], np.cumsum(v64)])
        n_at = np.concatenate([[0], np.cumsum(keep)])
        rows_at = np.concatenate([[0], np.cumsum(mask)])
        want_ct = n_at[ends] - n_at[starts]
        assert np.array_equal(rows, rows_at[ends] - rows_at[starts])
        assert np.array_equal(ct, want_ct)
        assert sm.dtype == np.float32 and ct.dtype == np.int32
        want = np.array([v64[s:e].sum() for s, e in zip(starts[:m],
                                                        ends[:m])])
        np.testing.assert_allclose(sm[:m], want, rtol=2e-4, atol=1e-3)
        assert (sm[m:] == 0).all() and (ct[m:] == 0).all()
        some = want_ct[:m] > 0
        np.testing.assert_allclose(av[:m][some], (want / np.maximum(
            want_ct[:m], 1))[some], rtol=2e-4, atol=1e-3)
        assert np.isnan(av[:m][~some]).all() and np.isnan(av[m:]).all()
        # `starts=` over the same layout, and over every other segment
        for pick in (np.arange(self.GROUPS), np.arange(0, m, 2)):
            s = np.full(self.GROUPS, n, dtype=np.int32)
            e = np.full(self.GROUPS, n, dtype=np.int32)
            s[:len(pick)], e[:len(pick)] = starts[pick], ends[pick]
            (sm2, ct2, av2), rows2 = run(ends=e, starts=s)
            for got, dense in ((sm2, sm), (ct2, ct), (av2, av),
                               (rows2, rows)):
                assert np.array_equal(got[:len(pick)], dense[pick],
                                      equal_nan=True)


def test_past_a_million_groups_a_bound_is_read_as_scalars():
    """A read-back that groups by row has as many groups as rows: past
    `_SUM_ROW_READS_MAX_GROUPS` a gathered [G, 128] would be 512 B a
    group (the chip's compiler refused 16 GB at 33.5M groups), and the
    sums read three scalars a bound, dense and `starts=` alike."""
    from greptimedb_tpu.ops.kernels import (
        _SUM_ROW_READS_MAX_GROUPS, sorted_grouped_aggregate)
    groups = 2 * _SUM_ROW_READS_MAX_GROUPS
    rng = np.random.default_rng(45)
    lens = rng.integers(0, 3, 3_000)
    lens[7], lens[90] = 300, 129
    n, m = int(lens.sum()), len(lens)
    ends = np.full(groups, n, dtype=np.int32)
    ends[:m] = np.cumsum(lens)
    starts = np.concatenate([[0], ends[:-1]]).astype(np.int32)
    gids = np.repeat(np.arange(m, dtype=np.int32), lens)
    vals = (rng.random(n, dtype=np.float32) * 100) - 50
    mask = rng.random(n) > 0.2
    want = np.array([vals[s:e][mask[s:e]].astype(np.float64).sum()
                     for s, e in zip(starts[:m], ends[:m])])
    want_n = np.array([mask[s:e].sum() for s, e in zip(starts[:m],
                                                      ends[:m])])
    for segments in ({"ends": ends}, {"ends": ends, "starts": starts}):
        (sm, ct), rows = sorted_grouped_aggregate(
            gids, mask, np.arange(n, dtype=np.int32), (vals, vals),
            num_groups=groups, ops=("sum", "count"), **segments)
        sm, ct = np.asarray(sm), np.asarray(ct)
        assert np.array_equal(ct[:m], want_n) and not ct[m:].any()
        assert np.array_equal(np.asarray(rows), ct)
        np.testing.assert_allclose(sm[:m], want, rtol=2e-4, atol=1e-2)
        assert not sm[m:].any()


class TestMomentsShareTheirPasses:
    """ISSUE 41: a launch computes once what its moments share (a count a
    distinct validity, the row count where a column has no NULL, one
    arg-extreme the `first`s / `last`s and time extremes of a validity).
    The shared program's results equal, bit for bit, those of the same
    moments each handed copies of its arrays, which share nothing."""

    #: (id, groups, segments picked out of the layout or None, seg_len_k?)
    LAYOUTS = [
        ("low", 300, None, False),
        ("high", 9_000, None, False),        # above _SEG_HIGH_CARD_THRESHOLD
        ("doubling", 9_000, None, True),     # the shift-doubling kernels
        ("live-runs", 12_000, 9_000, True),      # dense=False with `starts`
        # segments that meet the prefix form's blocks every way
        ("high-blocks", 9_000, None, False),
        ("live-runs-blocks", 12_000, 9_000, True),
    ]
    #: the validity each column reads: two columns with no NULL, one with
    #: NULLs of its own, two under one NULL-holding validity, which leaves
    #: one run with rows and no valid one
    READS = (None, None, "a", "b", "b")

    @pytest.mark.parametrize("layout", LAYOUTS, ids=[c[0] for c in LAYOUTS])
    def test_equals_a_pass_a_moment_bit_for_bit(self, layout):
        from greptimedb_tpu.ops.kernels import (
            _SEG_HIGH_CARD_THRESHOLD, _max_ident, _min_ident,
            distinct_arrays, moment_sharing, seg_len_bucket,
            sorted_grouped_aggregate)
        name, groups, picked, with_k = layout
        reads = self.READS
        rng = np.random.default_rng(groups)
        longest = 70                  # past two 32-row blocks
        lens = rng.integers(1, 9, groups)
        lens[rng.integers(0, groups, 12)] = rng.integers(30, longest + 1, 12)
        if "blocks" in name:
            edge = [ln for ln in block_edge_lens() if ln]
            lens[:len(edge)], longest = edge, max(edge)
        n = int(lens.sum())
        nb = shape_bucket(groups, minimum=256)
        ends = np.full(nb, n, dtype=np.int32)
        ends[:groups] = np.cumsum(lens)
        starts = np.concatenate([[0], ends[:-1]]).astype(np.int32)
        gids = np.repeat(np.arange(groups, dtype=np.int32), lens)
        ts = rng.integers(0, 40, n).astype(np.int32)        # ties
        mask = rng.random(n) > 0.15
        emptied = int(np.nonzero(lens > 2)[0][-3 if "blocks" in name else 3])
        valid = {"a": rng.random(n) > 0.2, "b": rng.random(n) > 0.2}
        valid["b"][starts[emptied]:ends[emptied]] = False
        mask[starts[emptied]:ends[emptied]] = True
        cols = [(rng.random(n, dtype=np.float32) * 100) - 50
                for _ in reads]
        segments = {"ends": ends}
        if picked is not None:
            live = np.append(rng.choice(
                np.delete(np.arange(groups), emptied), picked - 1,
                replace=False), emptied)
            if "blocks" in name:      # the segments at the blocks' edges
                live = np.union1d(live[len(edge):], np.arange(len(edge)))
            live = np.sort(live)
            picked = len(live)
            emptied = int(np.searchsorted(live, emptied))
            nb = shape_bucket(picked, minimum=256)
            segments = {"starts": np.full(nb, n, dtype=np.int32),
                        "ends": np.full(nb, n, dtype=np.int32)}
            segments["starts"][:picked] = starts[live]
            segments["ends"][:picked] = ends[live]
        assert (nb > _SEG_HIGH_CARD_THRESHOLD) == (name != "low")

        # a column each: avg beside a count of its own, every extreme,
        # first / last and the time extremes beside them (`standard_final`)
        per_column = ("sum", "count", "avg", "min", "max", "stddev",
                      "first", "last", "min", "max") + \
            (("growth",) if with_k else ())
        reads_ts = (8, 9)
        float_sums = ("sum", "avg", "stddev")
        ops, shared_v, shared_m, own_v, own_m = [], [], [], [], []
        for col, which in zip(cols, reads):
            for i, op in enumerate(per_column):
                ops.append(op)
                shared_v.append(ts if i in reads_ts else col)
                shared_m.append(None if which is None else valid[which])
                own_v.append((ts if i in reads_ts else col).copy())
                own_m.append(np.ones(n, dtype=bool) if which is None
                             else valid[which].copy())

        def run(values, col_masks, only=None):
            keep = [i for i, op in enumerate(ops)
                    if only is None or op in only]
            res, counts = sorted_grouped_aggregate(
                gids, mask, ts, tuple(values[i] for i in keep),
                tuple(col_masks[i] for i in keep), num_groups=nb,
                ops=tuple(ops[i] for i in keep), has_col_masks=True,
                seg_len_k=seg_len_bucket(longest) if with_k else None,
                **segments)
            return dict(zip(keep, map(np.asarray, res))), np.asarray(counts)

        want, want_counts = run(own_v, own_m)
        got, got_counts = run(shared_v, shared_m)
        assert np.array_equal(got_counts, want_counts)
        for i, op in enumerate(ops):
            g, w = got[i], want[i]
            assert g.dtype == w.dtype, (i, op)
            if op in float_sums:
                # the same numbers added; XLA:CPU picks the order inside a
                # block by what it fuses around the sum, so two compiled
                # programs may differ in a last bit (seen at low
                # cardinality): exact below, primitive by primitive
                np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-4,
                                           err_msg=f"{i} {op}")
            else:
                assert np.array_equal(g, w, equal_nan=True), (i, op)
        with jax.disable_jit():
            want_sums, _ = run(own_v, own_m, only=float_sums)
            got_sums, _ = run(shared_v, shared_m, only=float_sums)
        for i, g in got_sums.items():
            assert np.array_equal(g, want_sums[i], equal_nan=True), \
                (i, ops[i])

        def sharing(values, col_masks):
            return moment_sharing(tuple(ops),
                                  distinct_arrays(values, ts)[1],
                                  distinct_arrays(col_masks, None)[1])

        m = len(per_column)
        own_passes = 1 + len(cols) * (m + 3)     # avg 2, stddev 3
        assert sharing(own_v, own_m)[1:] == (own_passes, 0)
        # what a column keeps to itself: sum, min, max, the two centred
        # sums (and growth); beside them a count and two arg-extremes a
        # distinct validity (none: the row count)
        out_ix, run_passes, shared_passes = sharing(shared_v, shared_m)
        assert run_passes == 1 + len(cols) * (m - 5) + 2 + 2 * 3
        assert run_passes + shared_passes == own_passes

        count_of = [got[c * m + 1] for c in range(len(cols))]
        for which, count, ix in zip(reads, count_of, out_ix[1::m]):
            if which is None:   # no NULL: its count is the row count
                assert ix == -1 and np.array_equal(count, got_counts)
            else:               # NULLs: a count of that validity's own
                assert (count <= got_counts).all() and \
                    (count < got_counts).any()
        assert out_ix[3 * m + 1] == out_ix[4 * m + 1] != out_ix[2 * m + 1]
        assert not np.array_equal(count_of[2], count_of[3])
        for c in (3, 4):        # rows, and none valid under "b"
            assert got_counts[emptied] > 0 and count_of[c][emptied] == 0
            assert np.isnan(got[c * m + 6][emptied]) and \
                np.isnan(got[c * m + 7][emptied])
            assert got[c * m + 8][emptied] == _max_ident(jnp.int32) and \
                got[c * m + 9][emptied] == _min_ident(jnp.int32)


@pytest.mark.parametrize("moments, want", [
    # avg of ten all-valid columns: the row count and ten sums
    ([("sum", c, -1) for c in range(10)] +
     [("count", c, -1) for c in range(10)], (11, 10)),
    # one of them holds NULLs: a count of its own
    ([("sum", c, 0 if c == 3 else -1) for c in range(10)] +
     [("count", c, 0 if c == 3 else -1) for c in range(10)], (12, 9)),
    # lastpoint: last(c) + max_ts(c)
    ([("last", 0, -1), ("max", -1, -1)], (2, 1)),
    # a time extreme with no first / last beside it keeps its pass
    ([("sum", 0, -1), ("max", -1, -1)], (3, 0)),
    # ten lasts and their time extremes under no validity
    ([("last", c, -1) for c in range(10)] + [("max", -1, -1)] * 10,
     (2, 19)),
], ids=["avg-10", "avg-10-one-null", "lastpoint", "max_ts-alone",
        "last-10"])
def test_moment_sharing_counts_passes(moments, want):
    from greptimedb_tpu.ops.kernels import moment_sharing
    ops, value_ix, mask_ix = zip(*moments)
    out_ix, run, shared = moment_sharing(ops, value_ix, mask_ix)
    assert (run, shared) == want
    # moments of one result are handed one array; a count under no
    # validity is the row counts (-1)
    assert len(out_ix) == len(moments)
    assert all((i == -1) == (m[0] == "count" and m[2] < 0)
               for i, m in zip(out_ix, moments))
