"""Window kernels without gathers (ISSUE 29): on the step grid a window's
samples are read by compare, select and reduce over the row
(`ops/window.py`: `_rag_body`, `_read_at_dense`), and the gather
form stays for long rows and for the ops that need the samples side by
side. Dense against gather case by case, the lowered programs, the
choice by shape, and the counter and span detail that say which ran.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from greptimedb_tpu.common.telemetry import registry_snapshot
from greptimedb_tpu.datanode.instance import (
    DatanodeInstance, DatanodeOptions)
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.ops import window as W

I32_MAX = np.iinfo(np.int32).max
STEP, RANGE, T = 100, 300, 16          # windows of up to 4 samples at 100 ms
T0 = 100


def force(monkeypatch, form: str) -> None:
    """Every shape takes `form` ("dense" / "gather"), whatever its size."""
    big = 1 << 30
    monkeypatch.setattr(W, "_DENSE_WINDOW_MAX_RATIO",
                        big if form == "dense" else 0)
    monkeypatch.setattr(W, "_DENSE_POINT_MAX_LEN",
                        big if form == "dense" else 0)


def rows(S: int, L: int, lengths, seed: int = 0):
    """[S, L] int32 timestamps (sorted, TS_PAD last), float32 values (a
    walk of a few hundred), int32 lengths."""
    rng = np.random.default_rng(seed)
    lengths = np.broadcast_to(np.asarray(lengths), (S,)).astype(np.int32)
    ts = np.full((S, L), I32_MAX, np.int32)
    val = np.zeros((S, L), np.float32)
    for s, n in enumerate(lengths):
        if n:
            ts[s, :n] = np.sort(rng.choice(np.arange(0, 2000, 10), n,
                                           replace=False))
            val[s, :n] = np.cumsum(rng.normal(size=n)) * 100
    return ts, val, lengths


def case_random():
    rng = np.random.default_rng(1)
    return rows(37, 64, rng.integers(0, 65, 37), seed=1)


def case_empty_windows():
    """Every sample lies before the grid's first window or in its last
    step: all windows but the last are empty."""
    ts, val, lengths = rows(5, 16, 6)
    ts[:, :5] = np.arange(-900, -400, 100)[None, :]
    ts[:, 5] = T0 + (T - 1) * STEP
    return ts, val, lengths


def case_first_and_last_sample():
    """A window that holds only the row's first sample, one that holds
    only its last, on a full row (no padding)."""
    L = 8
    ts = (T0 + STEP * np.arange(L, dtype=np.int32) * 2)[None, :].repeat(3, 0)
    val = np.arange(3 * L, dtype=np.float32).reshape(3, L) ** 2
    return ts, val, np.full(3, L, np.int32)


def case_padded():
    """Short rows in a wide matrix, an empty row, and a series count that
    is no multiple of any block."""
    return rows(13, 128, [0, 1, 2, 3, 5, 8, 13, 21, 34, 55, 89, 127, 128])


def case_row_of_length_one():
    ts, val, lengths = rows(4, 1, [1, 0, 1, 1])
    ts[ts != I32_MAX] = [T0, T0 + 250, T0 + (T - 1) * STEP]
    return ts, val, lengths


def case_inf_and_nan():
    """`inf` and `NaN` inside a window, and beside one (the step before
    and the step after): a sample outside the window is none of its
    business, -0.0 stays -0.0."""
    ts, val, lengths = rows(8, 32, 24, seed=3)
    ts[:, :24] = (np.arange(24, dtype=np.int32) * 70)[None, :]
    val[0, 3] = np.inf
    val[1, 5] = -np.inf
    val[2, 7] = np.nan
    val[3, 0] = np.inf           # the row's first sample
    val[4, 23] = np.nan          # its last
    val[5, 10] = -0.0
    val[6, 2], val[6, 20] = np.inf, np.nan
    return ts, val, lengths


CASES = {
    "random": case_random,
    "empty-windows": case_empty_windows,
    "first-and-last-sample": case_first_and_last_sample,
    "padded-rows-and-series": case_padded,
    "row-of-length-1": case_row_of_length_one,
    "inf-and-nan": case_inf_and_nan,
}
REDUCE_OPS = sorted(W.REDUCE_OPS)
STACKS = ["_stack_rate", "_stack_prefix", "_stack_counter", "_stack_sq"]
POINT_OPS = ["rate", "increase", "delta", "sum_over_time", "avg_over_time",
             "stddev_over_time", "first_over_time", "last_over_time",
             "idelta", "irate_num", "changes", "resets"]


def reduce_both(monkeypatch, op, ts, val):
    """(dense, gather) results of a window reduction, bounds given, each
    traced afresh (the un-jitted body: a jit would cache the other)."""
    lo, hi = W.compute_window_bounds(ts, np.int32(T0), step=STEP,
                                     range_ms=RANGE, nsteps=T)
    out = []
    for form in ("dense", "gather"):
        force(monkeypatch, form)
        assert W.window_read_path(op, ts.shape[1], 2) == form
        v, ok = W._rag_body(
            jnp.asarray(ts), jnp.asarray(val), np.int32(T0), STEP, RANGE,
            lo, hi, op=op, nsteps=T, maxw=max(ts.shape[1], 2), param=60.0)
        out.append((np.asarray(v), np.asarray(ok)))
    return out


def assert_reductions_agree(dense, gather, val):
    (dv, dok), (gv, gok) = dense, gather
    assert np.array_equal(dok, gok)
    assert dv.shape == gv.shape
    finite = val[np.isfinite(val)]
    scale = float(np.abs(finite).max()) if finite.size else 1.0
    # 1e-6 relative; a slope or a mean near 0 is held to the values' size
    np.testing.assert_allclose(dv[dok], gv[gok], rtol=1e-6,
                               atol=1e-6 * scale, equal_nan=True)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("op", REDUCE_OPS)
def test_dense_reduction_is_the_gathers(monkeypatch, op, case):
    ts, val, _ = CASES[case]()
    dense, gather = reduce_both(monkeypatch, op, ts, val)
    assert_reductions_agree(dense, gather, val)


@pytest.mark.parametrize("op", REDUCE_OPS)
def test_dense_reduction_in_blocks_of_series(monkeypatch, op):
    """A selection over the block budget goes block by block (`lax.map`),
    its series padded to a multiple of the block."""
    ts, val, _ = case_padded()
    monkeypatch.setattr(W, "_DENSE_BLOCK_CELLS", 8 * T * ts.shape[1])
    dense, gather = reduce_both(monkeypatch, op, ts, val)   # 13 = 8 + 5
    assert_reductions_agree(dense, gather, val)


def test_an_inf_beside_a_window_is_not_its_business(monkeypatch):
    """The least squares selects a window's samples (it multiplied by a
    0/1 mask once: `inf * 0` made every window after an `inf` NaN)."""
    ts, val, _ = case_inf_and_nan()
    for form in ("dense", "gather"):
        force(monkeypatch, form)
        v, ok = W._rag_body(jnp.asarray(ts), jnp.asarray(val), np.int32(T0),
                            STEP, RANGE, None, None, op="deriv", nsteps=T,
                            maxw=32)
        v, ok = np.asarray(v), np.asarray(ok)
        # row 0's inf is sample 3 (t = 210): in the windows that end at
        # 300, 400, 500 and in no other
        assert not np.isfinite(v[0, 2:5]).any()
        assert np.isfinite(v[0, 5:][ok[0, 5:]]).all() and ok[0, 5:].any()


def bits(a) -> np.ndarray:
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def stack_args(reader, ts, val, lengths):
    ext = W._ext_counts(jnp.asarray(ts), np.int32(T0), step=STEP,
                        range_ms=RANGE, nsteps=T)
    if reader == "_stack_rate":
        return jnp.asarray(ts), jnp.asarray(val), jnp.asarray(val * 2), ext
    return jnp.asarray(ts), jnp.asarray(val), jnp.asarray(lengths), ext


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("reader", STACKS)
def test_dense_point_reads_are_the_gathers_bit_for_bit(monkeypatch, reader,
                                                       case):
    ts, val, lengths = CASES[case]()
    args = stack_args(reader, ts, val, lengths)
    got = []
    for form in ("dense", "gather"):
        force(monkeypatch, form)
        got.append(getattr(W, reader).__wrapped__(*args))
    assert got[0].shape == got[1].shape == \
        (ts.shape[0], T + RANGE // STEP, got[0].shape[2])
    assert np.array_equal(bits(got[0]), bits(got[1]))


@pytest.mark.parametrize("case", ["random", "inf-and-nan",
                                  "row-of-length-1"])
@pytest.mark.parametrize("op", POINT_OPS)
def test_dense_point_reads_in_the_per_op_kernel(monkeypatch, op, case):
    """`_rac_body` (a window that is no multiple of the step, `changes`,
    `resets`, `irate` over counter arrays) reads through the same form."""
    ts, val, lengths = CASES[case]()
    lo, hi = W.compute_window_bounds(ts, np.int32(T0), step=STEP,
                                     range_ms=RANGE - 50, nsteps=T)
    ends = np.int32(T0) + jnp.arange(T, dtype=jnp.int32) * STEP
    got = []
    for form in ("dense", "gather"):
        force(monkeypatch, form)
        got.append(W._rac_body(
            jnp.asarray(ts), jnp.asarray(val), jnp.asarray(lengths), lo, hi,
            ends, RANGE - 50, op=op, nsteps=T))
    (dv, dok), (gv, gok) = got
    assert np.array_equal(np.asarray(dok), np.asarray(gok))
    assert np.array_equal(bits(dv), bits(gv))


# ---------------------------------------------------------------------------
# the programs: what the dense form lowers to, and which shapes take it
# ---------------------------------------------------------------------------

def has_gather(lowered_text: str) -> bool:
    """A gather op in a lowered (StableHLO) program; the program's own
    name may well hold the word."""
    return "stablehlo.gather" in lowered_text


def shapes(S, L, nsteps=T):
    i32, f32 = jnp.int32, jnp.float32
    sd = jax.ShapeDtypeStruct
    return dict(ts=sd((S, L), i32), val=sd((S, L), f32),
                lengths=sd((S,), i32), lo=sd((S, nsteps), i32),
                hi=sd((S, nsteps), i32),
                ext=sd((S, nsteps + RANGE // STEP), i32))


def lowered_reduce(op, S, L, maxw):
    a = shapes(S, L)
    return W._range_aggregate_gather_pre.lower(
        a["ts"], a["val"], np.int32(T0), STEP, RANGE, a["lo"], a["hi"],
        op=op, nsteps=T, maxw=maxw).as_text()


def lowered_stack(reader, S, L):
    a = shapes(S, L)
    third = a["val"] if reader == "_stack_rate" else a["lengths"]
    return getattr(W, reader).lower(a["ts"], a["val"], third,
                                    a["ext"]).as_text()


def lowered_point_op(op, S, L):
    a = shapes(S, L)
    return W._range_aggregate_cumsum_pre.lower(
        a["ts"], a["val"], a["lengths"], np.int32(T0), STEP, RANGE - 50,
        a["lo"], a["hi"], op=op, nsteps=T).as_text()


@pytest.mark.parametrize("op", REDUCE_OPS)
def test_a_dense_reduction_lowers_to_no_gather(op):
    assert not has_gather(lowered_reduce(op, 48, 256, 256))
    # the control: the same op where the row is far wider than the window
    wide = W._DENSE_WINDOW_MAX_RATIO * 2 * 2
    assert has_gather(lowered_reduce(op, 8, wide, 2))


@pytest.mark.parametrize("reader", STACKS)
def test_a_dense_stack_lowers_to_no_gather(reader):
    assert not has_gather(lowered_stack(reader, 48, 128))
    assert has_gather(lowered_stack(reader, 8, 2 * W._DENSE_POINT_MAX_LEN))


@pytest.mark.parametrize("op", POINT_OPS)
def test_a_dense_point_op_lowers_to_no_gather(op):
    assert not has_gather(lowered_point_op(op, 48, 128))
    assert has_gather(lowered_point_op(op, 8, 2 * W._DENSE_POINT_MAX_LEN))


@pytest.mark.parametrize("op", sorted(W.GATHER_OPS - W.REDUCE_OPS))
def test_ops_that_need_the_samples_side_by_side_stay_gathers(op):
    assert W.window_read_path(op, 128, 128) == "gather"
    assert has_gather(lowered_reduce(op, 8, 128, 128))


def test_the_choice_follows_the_rows_length_across_the_crossover():
    ratio, point = W._DENSE_WINDOW_MAX_RATIO, W._DENSE_POINT_MAX_LEN
    for op in REDUCE_OPS:
        for maxw in (2, 64):
            assert W.window_read_path(op, ratio * maxw, maxw) == "dense"
            assert W.window_read_path(op, ratio * maxw * 2, maxw) == "gather"
        # the engine hands the whole row as the window: dense at any length
        for L in (1, 128, 256, 32768, 1 << 20):
            assert W.window_read_path(op, L, max(L, 2)) == "dense"
    for op in POINT_OPS:
        assert W.window_read_path(op, point) == "dense"
        assert W.window_read_path(op, point * 2) == "gather"
    for op in ("count_over_time", "present_over_time"):
        assert W.window_read_path(op, 128) is None      # bounds alone
    # a dashboard's rows take the dense form in both families
    for L in (128, 256, 512, 1024):
        assert W.window_read_path("predict_linear", L, L) == "dense"
        assert W.window_read_path("rate", L) == "dense"


# ---------------------------------------------------------------------------
# the counter and the span detail that say which form ran
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gauge(tmp_path_factory):
    fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
        data_home=str(tmp_path_factory.mktemp("dense")),
        register_numbers_table=False)))
    fe.start()
    fe.do_query("CREATE TABLE g (name STRING, greptime_timestamp TIMESTAMP "
                "TIME INDEX, greptime_value DOUBLE, PRIMARY KEY(name))")
    fe.do_query("INSERT INTO g VALUES " + ", ".join(
        f"('{name}', {k * 10_000}, {slope * k + 0.5!r})"
        for name, slope in (("a", 2.0), ("b", -3.0)) for k in range(180)))
    yield fe
    fe.shutdown()


def window_reads() -> dict:
    return {labels: value for name, labels, value, _ in registry_snapshot()
            if name == "greptime_promql_window_reads_total"}


@pytest.mark.parametrize("query, path, reads", [
    ("predict_linear(g[5m], 60)", "dense", 1),
    ("max_over_time(g[5m])", "dense", 1),
    ("rate(g[5m])", "dense", 1),
    ("sum_over_time(g[5m])", "dense", 1),       # its count reads no sample
    ("quantile_over_time(0.5, g[5m])", "gather", 1),
    ("count_over_time(g[5m])", None, 0),
])
def test_counter_and_span_say_which_form_ran(gauge, query, path, reads):
    before = window_reads()
    out = gauge.do_query(
        f"EXPLAIN ANALYZE TQL EVAL (600, 1500, '15s') {query}")[-1]
    stages = {r[0]: r[4] or "" for b in out.batches for r in b.rows()}
    after = window_reads()
    moved = {k: after[k] - before.get(k, 0.0) for k in after
             if after[k] != before.get(k, 0.0)}
    if path is None:
        assert moved == {} and "path=" not in stages["window.launch"]
        return
    assert moved == {f'{{path="{path}"}}': float(reads)}
    detail = stages["window.launch"]
    assert re.search(rf"path={path}, cpu_ms=[0-9.]+, t0_ns=", detail), detail


def test_predict_linear_on_the_dense_form_is_the_line(gauge):
    rows_ = [r for b in gauge.do_query(
        "TQL EVAL (600, 1500, '15s') predict_linear(g[5m], 60)")[-1].batches
        for r in b.rows()]
    assert len(rows_) == 2 * 61
    for r in rows_:
        slope = {"a": 2.0, "b": -3.0}[r[-3]]
        # the line through (k, slope * k + 0.5) a minute (6 samples) on
        t_s = r[-2].timestamp() if hasattr(r[-2], "timestamp") \
            else float(r[-2]) / 1000.0
        assert float(r[-1]) == pytest.approx(
            slope * (t_s + 60) / 10 + 0.5, rel=1e-5)


# ---------------------------------------------------------------------------
# the dashboard's shapes compiled for a described v5e (no chip needed): the
# gain rests on XLA fusing compare, select and reduce, so that nothing of
# [S, T, L] is ever written
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # whatever keeps the compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def on(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def assert_fused(compiled, cells: int):
    text = compiled.as_text()
    assert " gather(" not in text and " while(" not in text
    # far under one f32 array of the compared cells
    assert compiled.memory_analysis().temp_size_in_bytes < cells * 4 // 16


@pytest.mark.parametrize("op", ["predict_linear", "min_over_time"])
def test_the_dashboards_window_reduction_is_fused_on_a_v5e(one_chip, op):
    S, L, steps = 3072, 256, 64         # prom-fs-predict: 3,060 series
    i32, f32 = jnp.int32, jnp.float32
    compiled = W._range_aggregate_gather_pre.lower(
        on(one_chip, (S, L), i32), on(one_chip, (S, L), f32), np.int32(0),
        15_000, 600_000, on(one_chip, (S, steps), i32),
        on(one_chip, (S, steps), i32), op=op, nsteps=steps, maxw=L,
        param=3600.0).compile()
    assert_fused(compiled, S * steps * L)


def test_the_dashboards_stack_is_fused_on_a_v5e(one_chip):
    S, L, ext = 65536, 128, 84          # prom-cpu-by-mode-all: 65,280 series
    i32, f32 = jnp.int32, jnp.float32
    compiled = W._stack_rate.lower(
        on(one_chip, (S, L), i32), on(one_chip, (S, L), f32),
        on(one_chip, (S, L), f32), on(one_chip, (S, ext), i32)).compile()
    assert_fused(compiled, S * (L + 1) * ext)


def test_a_46m_row_tables_run_labels_are_one_pass_on_a_v5e(one_chip):
    """`scan_narrow.run_labels` at `prom-node-1k-2h`'s 46.08M rows: what a
    statement off every laid-out grid launches ahead of the scan kernel
    (`scan_full._selection_layout`). One fused pass: nothing gathered,
    nothing kept but the labels."""
    from greptimedb_tpu.query import scan_narrow
    n, i32 = 46_080_000, jnp.int32
    compiled = scan_narrow.run_labels.lower(
        on(one_chip, (n,), i32), on(one_chip, (n,), i32),
        tuple(np.asarray(x, np.int32) for x in (-13_000, 60_000, 122))
    ).compile()
    text = compiled.as_text()
    assert " gather(" not in text and " while(" not in text
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == n * 4
    assert mem.temp_size_in_bytes < n * 4 // 16


def test_lastpoints_program_reads_rows_of_128_lanes_on_a_v5e(one_chip):
    """`tsbs4k-scan`'s `lastpoint` (ISSUE 46): `last` of one column over
    17.28M rows in 4,096 groups, launched as the scan path launches it
    (`ts` stands in for the run ids, no `seg_len_k`). The rows are read
    as [135000, 128]; nothing is shaped by blocks of 32 ([540000, 32],
    the 21-level pair table over 540,000 blocks, the [4096, 64] edge
    windows), and the program writes the key once and nothing else of the
    table's length. (Kept in this file: one worker describes the chip.)"""
    from greptimedb_tpu.ops import kernels as K
    n, groups = 17_280_000, 4_096
    i32 = jnp.int32
    assert K.extreme_form(groups, None) == "rows"
    compiled = K._sorted_grouped_aggregate_pre.lower(
        on(one_chip, (n,), i32), on(one_chip, (n,), jnp.bool_),
        on(one_chip, (n,), i32), (on(one_chip, (), i32),) * 2,
        (on(one_chip, (n,), jnp.float32),), (), on(one_chip, (groups,), i32),
        None, num_groups=groups, ops=("last",), value_ix=(0,),
        mask_ix=(-1,)).compile()
    text = compiled.as_text()
    assert "[135000,128]" in text
    for gone in ("[540000,32]", "[540000]", "[21,540000]", "[262144]",
                 "[4096,64]"):
        assert gone not in text, gone
    assert compiled.memory_analysis().temp_size_in_bytes < n * 4 * 3 // 2
