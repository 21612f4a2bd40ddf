"""Bring-up on the local chip (ISSUE 21): nothing hides the device.

CPU-side checks of what the chip smoke (`chip_smoke.py`) relies on: the
compile cache is placed from outside or at one fixed path, a server
refuses a CPU nobody asked for, the smoke refuses to load without a TPU,
a repeated statement keeps its device dispatch, native libraries load
only when built from the present source, and segment sums stay accurate
at a resident scan's size.
"""

import hashlib
import inspect
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pandas as pd
import pytest

from greptimedb_tpu.common import jax_cache
from greptimedb_tpu.datanode.instance import (
    DatanodeInstance, DatanodeOptions)
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.query import moment_fold, tpu_exec
from greptimedb_tpu.session import QueryContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _restore_jax_config(self):
        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        old = {n: getattr(jax.config, n) for n in names}
        yield
        for n, v in old.items():
            jax.config.update(n, v)

    def test_env_places_the_cache_and_code_stays_out(self, monkeypatch,
                                                     tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        jax.config.update("jax_compilation_cache_dir", "left-alone")
        assert jax_cache.enable_compile_cache() == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == "left-alone"
        assert os.path.isdir(tmp_path / "c")

    def test_default_is_the_fixed_checkout_path(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert jax_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        # nothing a caller passes (a data_home, a temp dir) can move it
        assert not inspect.signature(
            jax_cache.enable_compile_cache).parameters

    def test_one_guarded_call_site(self):
        hits = []
        for root in ("greptimedb_tpu", "benchmark"):
            for dirpath, _, files in os.walk(os.path.join(REPO, root)):
                hits += [os.path.join(dirpath, f) for f in files
                         if f.endswith(".py")]
        hits += [os.path.join(REPO, f) for f in
                 ("chip_smoke.py", "__graft_entry__.py")]
        sites = []
        for path in hits:
            with open(path, encoding="utf-8") as f:
                sites += [path for line in f if re.search(
                    r"update\(\s*[\"']jax_compilation_cache_dir", line)]
        assert sites == [os.path.join(REPO, "greptimedb_tpu", "common",
                                      "jax_cache.py")]


def _env_without_platform():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = REPO
    return env


def test_standalone_refuses_a_cpu_nobody_asked_for(tmp_path):
    """JAX_PLATFORMS unset + no TPU: JAX falls back to the CPU; the
    server must exit and say how to run on the CPU on purpose."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "greptimedb_tpu.cmd.main", "standalone",
         "start", "--data-home", str(tmp_path / "d"),
         "--http-addr", "127.0.0.1:0", "--mysql-addr", "127.0.0.1:0",
         "--postgres-addr", "127.0.0.1:0", "--grpc-addr", "127.0.0.1:0"],
        cwd=REPO, env=_env_without_platform(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        out, _ = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        pytest.skip("the server stayed up: this box has a TPU")
    assert proc.returncode != 0
    assert "no TPU" in out and "JAX_PLATFORMS=cpu" in out
    assert not os.path.exists(tmp_path / "d")      # before any state


def test_flight_client_runs_without_jax():
    """A parent that starts a chip-owning child talks to it through this
    client; importing jax there is how a parent ends up holding the
    chip. (The request-tracing hook used to pull in storage/ → kernels.)"""
    code = ("import sys; from greptimedb_tpu.client import flight; "
            "flight._traced({'type': 'sql'}); "
            "flight._absorb_wire_spans([{}]); "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=120)


def test_chip_smoke_without_a_tpu_fails_before_loading():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert "nothing was loaded" in proc.stderr
    assert '"ok"' not in proc.stdout               # no result line
    assert "generated" not in proc.stdout


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any key besides ok / device
    {platform, kind, count}; what the run showed goes on the `summary:`
    line before it."""
    code = (
        "import json, sys; sys.path.insert(0, '.'); import chip_smoke; "
        "print(chip_smoke.result_line({'platform': 'tpu', 'device_kind': "
        "'TPU v5 lite', 'device_count': 1, 'bytes_in_use': 7})); "
        "assert 'jax' not in sys.modules, 'jax imported'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                          capture_output=True, text=True, timeout=120)
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}


def test_repeat_keeps_the_device_dispatch(tmp_path, monkeypatch):
    """A table over the static floor (131,072 rows) answered
    device-resident is answered device-resident again — and again, once
    the adaptive floor has its first steady-state observation. No SET.
    (The first launch compiles; fed into the floor it used to push every
    table under 7.5M rows onto the CPU path for the life of the process.)"""
    monkeypatch.setattr(tpu_exec, "_observed_min_dt", [None])
    # the host path's assumed speed, lowered 15x: the table then stays on
    # the device while a repeat takes under 134 ms, where at 15M rows/s
    # one repeat descheduled past 8.96 ms (6 test workers on 8 cores:
    # 9.5-11.2 ms on this tree and on its parent alike) sent the next
    # statement to the CPU; a compiling first launch (0.3 s and more)
    # fed into the floor would still do that
    monkeypatch.setattr(tpu_exec, "_CPU_ROWS_PER_SEC", 1e6)
    dn = DatanodeInstance(DatanodeOptions(
        data_home=str(tmp_path / "d"), register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    try:
        ctx = QueryContext()
        fe.do_query("CREATE TABLE m (host STRING, ts TIMESTAMP TIME "
                    "INDEX, cpu DOUBLE, PRIMARY KEY(host))")
        hosts, per = 64, 2100                       # 134,400 rows
        table = fe.catalog.table("greptime", "public", "m")
        table.bulk_load({
            "host": np.repeat(np.array([f"h{i}" for i in range(hosts)]),
                              per).astype(object),
            "ts": np.tile(np.arange(per, dtype=np.int64) * 1000, hosts),
            "cpu": np.random.default_rng(5).random(hosts * per)})
        seen = []
        for _ in range(4):
            out = fe.do_query("EXPLAIN ANALYZE SELECT host, max(cpu) "
                              "FROM m GROUP BY host", ctx)[0]
            seen += [detail for b in out.batches
                     for stage, _, _, _, detail in b.rows()
                     if stage == "dispatch"]
        assert seen == ["device-resident (scan cache)"] * 4
        # the repeats were observed, the compiling first launch was not
        assert tpu_exec._observed_min_dt[0] is not None
        assert tpu_exec._observed_min_dt[0] < 0.5
    finally:
        fe.shutdown()


def test_frames_nbytes_sizes_strings_by_value_under_either_dtype():
    """pandas 3 infers `str` for string columns where pandas 2 kept
    object; partial_bytes must not change with it."""
    as_object = pd.DataFrame({"h": pd.Series(["h4", "host_12"],
                                             dtype=object), "v": [1.0, 2.0]})
    inferred = pd.DataFrame({"h": ["h4", "host_12"], "v": [1.0, 2.0]})
    assert moment_fold.frames_nbytes([as_object]) == 9 + 16
    assert moment_fold.frames_nbytes([inferred]) == 9 + 16


def test_native_library_is_named_by_its_source():
    from greptimedb_tpu.utils.native_build import (
        NATIVE_DIR, build_native_library)
    try:
        path = build_native_library("snappy")
    except (OSError, subprocess.SubprocessError) as e:
        pytest.skip(f"no toolchain: {e}")
    with open(os.path.join(NATIVE_DIR, "snappy.cpp"), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(path) == f"libgdbsnappy-{digest}.so"
    # a library of another source (any other name) is never picked up
    assert [n for n in os.listdir(NATIVE_DIR)
            if n.startswith("libgdbsnappy") and n.endswith(".so")] == \
        [os.path.basename(path)]


@pytest.mark.parametrize("seg", [360, 4320])
def test_segment_sum_is_accurate_at_resident_scan_size(seg):
    """avg() must not degrade with the size of the scan it runs over:
    4.3M rows near 50 put a float32 running prefix at 2e8 (spacing 16),
    which made hourly sums wrong in the fourth digit (at the chip smoke's
    17M rows, the third). Both cardinality regimes: 12,000 and 1,000
    segments."""
    from greptimedb_tpu.ops.kernels import sorted_grouped_aggregate
    n = 4_320_000
    groups = n // seg
    rng = np.random.default_rng(0)
    x64 = rng.uniform(0.0, 100.0, n)
    idx = np.arange(n, dtype=np.int32)
    mask = np.ones(n, dtype=bool)
    nb = 1 << (groups - 1).bit_length()
    ends = np.full(nb, n, dtype=np.int32)
    ends[:groups] = (np.arange(1, groups + 1) * seg)
    (sums,), counts = sorted_grouped_aggregate(
        idx, mask, idx, (x64.astype(np.float32),), (mask,), num_groups=nb,
        ops=("sum",), has_col_masks=True, ends=ends)
    want = x64.reshape(groups, seg).sum(axis=1)
    assert (np.asarray(counts)[:groups] == seg).all()
    np.testing.assert_allclose(np.asarray(sums)[:groups], want, rtol=2e-6)
