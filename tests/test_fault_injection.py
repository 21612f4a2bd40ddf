"""Fault-injection layer tests (ISSUE 4).

- failpoint registry semantics: action grammar, NxM one-in-N firing,
  delay, env / SET / HTTP activation, information_schema.failpoints;
- RetryingObjectStore: backoff, give-up, transient classification,
  greptime_objstore_retry_* counters;
- S3 error taxonomy: 5xx/429 and socket errors are S3TransientError,
  4xx stays terminal S3Error;
- graceful degradation: read-cache corruption and scan-cache corruption
  both fall back to a cold read with identical answers;
- WAL torn-tail repair: truncate + WARN instead of raising, CRC catches
  corrupt-but-complete records;
- the crash-recovery torture matrix (tests/torture.py) as parametrized
  tier-1 cases plus a slow-marked extended sweep;
- the acceptance shape: ingest+flush+scan completes through 1-in-3
  injected transient object-store faults with retries visible in
  runtime_metrics.
"""

import json
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from greptimedb_tpu.common import failpoint as fp

from torture import CRASH_POINTS, TortureRig, make_batch, run_crash_case


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.clear_all()
    yield
    fp.clear_all()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_parse_actions(self):
        assert fp.parse_action("err") == ("err", None, 1, 1)
        assert fp.parse_action("err(transient)") == ("err", "transient", 1, 1)
        assert fp.parse_action("crash") == ("crash", None, 1, 1)
        assert fp.parse_action("delay(25)") == ("delay", "25", 1, 1)
        assert fp.parse_action("1x3*err") == ("err", None, 1, 3)
        assert fp.parse_action("2x5*crash") == ("crash", None, 2, 5)
        for bad in ("nope", "err(", "0x3*err", "4x3*err", "delay",
                    "delay(ms)", "1x0*err"):
            with pytest.raises(ValueError):
                fp.parse_action(bad)

    def test_inactive_is_noop_and_zero_cost_guard(self):
        fp.register("fi_test_point")
        assert not fp._ACTIVE
        fp.fail_point("fi_test_point")    # must not raise or count
        assert not fp.fires("fi_test_point")
        rec = [p for p in fp.list_points() if p["name"] == "fi_test_point"]
        assert rec and rec[0]["hits"] == 0 and rec[0]["action"] is None

    def test_err_and_off(self):
        fp.configure("fi_test_err", "err")
        with pytest.raises(fp.FailpointError):
            fp.fail_point("fi_test_err")
        fp.configure("fi_test_err", "off")
        fp.fail_point("fi_test_err")      # disarmed: no-op

    def test_transient_flag(self):
        with fp.cfg("fi_test_tr", "err(transient)"):
            with pytest.raises(fp.FailpointError) as ei:
                fp.fail_point("fi_test_tr")
            assert ei.value.transient
        with fp.cfg("fi_test_tr", "err"):
            with pytest.raises(fp.FailpointError) as ei:
                fp.fail_point("fi_test_tr")
            assert not ei.value.transient

    def test_crash_is_base_exception(self):
        with fp.cfg("fi_test_crash", "crash"):
            with pytest.raises(fp.SimulatedCrash):
                try:
                    fp.fail_point("fi_test_crash")
                except Exception:  # noqa: BLE001
                    pytest.fail("SimulatedCrash caught by except Exception")

    def test_one_in_n_firing(self):
        with fp.cfg("fi_test_nxm", "1x3*err"):
            fired = 0
            for _ in range(9):
                try:
                    fp.fail_point("fi_test_nxm")
                except fp.FailpointError:
                    fired += 1
            assert fired == 3             # exactly one per window of 3
        rec = [p for p in fp.list_points() if p["name"] == "fi_test_nxm"][0]
        assert rec["hits"] == 9 and rec["fires"] == 3

    def test_delay(self):
        with fp.cfg("fi_test_delay", "delay(40)"):
            t0 = time.perf_counter()
            fp.fail_point("fi_test_delay")
            assert time.perf_counter() - t0 >= 0.03

    def test_env_activation(self, monkeypatch):
        monkeypatch.setenv("GREPTIME_FAILPOINTS",
                           "fi_env_a=err;fi_env_b=1x2*delay(1)")
        fp.refresh_from_env()
        points = {p["name"]: p for p in fp.list_points()}
        assert points["fi_env_a"]["action"] == "err"
        assert points["fi_env_b"]["action"] == "1x2*delay(1)"

    def test_bad_names_rejected(self):
        with pytest.raises(ValueError):
            fp.configure("Bad Name!", "err")
        with pytest.raises(ValueError):
            fp.configure("x", "nonsense-action")


# ---------------------------------------------------------------------------
# RetryingObjectStore
# ---------------------------------------------------------------------------

class _FlakyStore:
    """Object-store stub failing the first `fail_n` calls per op."""

    def __init__(self, fail_n, exc_factory):
        self.fail_n = fail_n
        self.exc_factory = exc_factory
        self.calls = 0
        self.data = {}

    def _maybe_fail(self):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise self.exc_factory()

    def read(self, key):
        self._maybe_fail()
        return self.data[key]

    def write(self, key, data):
        self._maybe_fail()
        self.data[key] = data

    def delete(self, key):
        self._maybe_fail()
        self.data.pop(key, None)

    def exists(self, key):
        self._maybe_fail()
        return key in self.data

    def list(self, prefix):
        self._maybe_fail()
        return sorted(k for k in self.data if k.startswith(prefix))


class TestRetryingObjectStore:
    def _counter_value(self, name):
        from prometheus_client import REGISTRY
        v = REGISTRY.get_sample_value(name)
        return v or 0.0

    def test_retries_transient_then_succeeds(self):
        from greptimedb_tpu.storage.retry import (RetryingObjectStore,
                                                  configure_retry)
        configure_retry(max_retries=3, base_ms=1)
        inner = _FlakyStore(2, ConnectionResetError)
        store = RetryingObjectStore(inner)
        before = self._counter_value("greptime_objstore_retry_total")
        store.write("k", b"v")
        assert inner.data["k"] == b"v"
        assert inner.calls == 3
        assert self._counter_value(
            "greptime_objstore_retry_total") == before + 2

    def test_gives_up_after_budget(self):
        from greptimedb_tpu.storage.retry import (RetryingObjectStore,
                                                  configure_retry)
        configure_retry(max_retries=2, base_ms=1)
        inner = _FlakyStore(10, ConnectionResetError)
        store = RetryingObjectStore(inner)
        before = self._counter_value("greptime_objstore_retry_giveup_total")
        with pytest.raises(ConnectionResetError):
            store.read("k")
        assert inner.calls == 3           # 1 try + 2 retries
        assert self._counter_value(
            "greptime_objstore_retry_giveup_total") == before + 1

    def test_terminal_errors_surface_immediately(self):
        from greptimedb_tpu.storage.retry import (RetryingObjectStore,
                                                  configure_retry)
        configure_retry(max_retries=3, base_ms=1)
        inner = _FlakyStore(10, lambda: FileNotFoundError("k"))
        store = RetryingObjectStore(inner)
        with pytest.raises(FileNotFoundError):
            store.read("k")
        assert inner.calls == 1           # no retry on a logical 404

    def test_backoff_grows(self, monkeypatch):
        from greptimedb_tpu.storage import retry as retry_mod
        retry_mod.configure_retry(max_retries=3, base_ms=8)
        sleeps = []
        monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
        inner = _FlakyStore(3, ConnectionResetError)
        store = retry_mod.RetryingObjectStore(inner)
        store.read.__func__  # noqa: B018 — touch to keep linters quiet
        inner.data["k"] = b"v"
        assert store.read("k") == b"v"
        assert len(sleeps) == 3
        # exponential with ±50% jitter: each window is [0.5, 1.5]×base·2ⁱ
        for i, s in enumerate(sleeps):
            base = 0.008 * (2 ** i)
            assert 0.5 * base <= s <= 1.5 * base

    def test_transient_classification(self):
        from greptimedb_tpu.storage.retry import is_transient
        from greptimedb_tpu.storage.s3 import S3Error, S3TransientError
        assert is_transient(S3TransientError("x"))
        assert not is_transient(S3Error("x"))
        assert is_transient(ConnectionResetError())
        assert is_transient(TimeoutError())
        assert not is_transient(FileNotFoundError("k"))
        assert not is_transient(ValueError("x"))
        assert is_transient(fp.FailpointError("x", transient=True))
        assert not is_transient(fp.FailpointError("x"))

    def test_set_knobs_apply_live(self, tmp_path):
        from greptimedb_tpu.storage import retry as retry_mod
        old = retry_mod.retry_settings()
        try:
            retry_mod.configure_retry(max_retries=7, base_ms=13)
            assert retry_mod.retry_settings() == {"max_retries": 7,
                                                 "base_ms": 13}
        finally:
            retry_mod.configure_retry(**old)


# ---------------------------------------------------------------------------
# S3 error taxonomy (satellite 1)
# ---------------------------------------------------------------------------

class TestS3Taxonomy:
    def test_status_classification(self):
        from greptimedb_tpu.storage.s3 import (S3Error, S3TransientError,
                                               _status_error)
        for st in (429, 500, 502, 503, 504):
            assert isinstance(_status_error("GET", "k", st),
                              S3TransientError)
        for st in (400, 403, 409, 412):
            e = _status_error("GET", "k", st)
            assert isinstance(e, S3Error)
            assert not isinstance(e, S3TransientError)

    def test_socket_error_is_transient(self):
        from greptimedb_tpu.storage.s3 import (S3Config, S3ObjectStore,
                                               S3TransientError)
        # nothing listens on this port: connection refused before any
        # status line → must classify transient, not raise raw OSError
        store = S3ObjectStore(S3Config(
            bucket="b", endpoint="http://127.0.0.1:1"))
        with pytest.raises(S3TransientError):
            store.read("k")


# ---------------------------------------------------------------------------
# graceful degradation (cache corruption → cold read)
# ---------------------------------------------------------------------------

class TestCacheDegradation:
    def test_read_cache_corruption_falls_back_cold(self, tmp_path):
        from greptimedb_tpu.storage.cache import LruCacheLayer
        from greptimedb_tpu.storage.object_store import FsObjectStore
        inner = FsObjectStore(str(tmp_path / "data"))
        cache = LruCacheLayer(inner, str(tmp_path / "cache"))
        inner.write("a/k", b"payload-bytes")
        assert cache.read("a/k") == b"payload-bytes"   # admit
        # corrupt the cached blob on disk (truncate)
        blob = cache._cache_path("a/k")
        with open(blob, "wb") as f:
            f.write(b"junk")
        # differential: the corrupted cache entry must not surface
        hits_before = cache.hits
        assert cache.read("a/k") == inner.read("a/k")
        # the corrupt read counts as a miss, NOT a hit-plus-miss
        assert cache.hits == hits_before
        # and the cache re-admitted a good copy
        assert cache.read("a/k") == b"payload-bytes"
        assert cache.hits == hits_before + 1

    def test_read_cache_io_error_falls_back_cold(self, tmp_path):
        from greptimedb_tpu.storage.cache import LruCacheLayer
        from greptimedb_tpu.storage.object_store import FsObjectStore
        inner = FsObjectStore(str(tmp_path / "data"))
        cache = LruCacheLayer(inner, str(tmp_path / "cache"))
        inner.write("a/k", b"v1")
        cache.read("a/k")
        with fp.cfg("cache_read", "err"):
            assert cache.read("a/k") == b"v1"          # injected IO error

    def test_scan_cache_corruption_falls_back_cold(self, tmp_path):
        """Differential: a poisoned incremental scan-cache refresh must
        rebuild cold and produce the same answer."""
        from greptimedb_tpu.storage.scan_cache import SCAN_CACHE
        rig = TortureRig(str(tmp_path))
        rig.create()
        rows = make_batch(0)
        rig.write(rows)
        SCAN_CACHE.get(rig.region)                    # prime the entry
        rows2 = make_batch(1)
        rig.write(rows2)                              # forces incremental
        with fp.cfg("scan_cache_incremental", "err"):
            scan = SCAN_CACHE.get(rig.region)
        assert SCAN_CACHE.last_outcome() == "full"
        got = {(rig.region.series_dict.decode_tag_column(
                    scan.series_ids, 0)[i], int(scan.ts[i]))
               for i in range(len(scan.ts))}
        assert got == set(rows) | set(rows2)
        rig.region.close()


# ---------------------------------------------------------------------------
# WAL torn tail (satellite 2)
# ---------------------------------------------------------------------------

class TestWalTornTail:
    def _wal(self, tmp_path, **kw):
        from greptimedb_tpu.storage.wal import Wal
        return Wal(str(tmp_path / "wal"), **kw)

    def test_torn_tail_truncates_and_warns(self, tmp_path, caplog):
        import logging as _logging
        w = self._wal(tmp_path)
        for seq in range(1, 4):
            w.append(seq, f"payload-{seq}".encode() * 10)
        w.close()
        seg = next(iter(sorted((tmp_path / "wal").glob("*.wal"))))
        good_size = seg.stat().st_size
        with open(seg, "ab") as f:        # simulate a half-written record
            f.write(b"\x50\x00\x00\x00torngarbage")
        w2 = self._wal(tmp_path)
        with caplog.at_level(_logging.WARNING):
            recs = list(w2.read_from(1))
        assert [r[0] for r in recs] == [1, 2, 3]
        assert any("truncating" in r.message for r in caplog.records)
        assert seg.stat().st_size == good_size         # physically repaired
        # appends after repair land cleanly and replay end-to-end
        w2.append(4, b"after-recovery")
        w2.close()
        w3 = self._wal(tmp_path)
        assert [r[0] for r in w3.read_from(1)] == [1, 2, 3, 4]
        w3.close()

    def test_torn_injection_on_live_wal_self_heals(self, tmp_path):
        """If the process SURVIVES an injected torn write (live server,
        not the torture rig), the next append must cut the garbage off —
        otherwise later acked records sit behind bytes replay cannot
        cross and are silently lost at the next restart."""
        from greptimedb_tpu.storage.wal import Wal
        w = Wal(str(tmp_path / "wal"))
        w.append(1, b"first-record")
        with fp.cfg("wal_append_torn", "crash"):
            with pytest.raises(fp.SimulatedCrash):
                w.append(2, b"torn-record")
        w.append(3, b"acked-after-tear")   # same live Wal object
        w.close()
        recs = list(Wal(str(tmp_path / "wal")).read_from(1))
        assert [r[0] for r in recs] == [1, 3]

    def test_crc_catches_corrupt_complete_record(self, tmp_path):
        w = self._wal(tmp_path)
        w.append(1, b"aaaa-bbbb-cccc")
        w.append(2, b"dddd-eeee-ffff")
        w.close()
        seg = next(iter(sorted((tmp_path / "wal").glob("*.wal"))))
        data = bytearray(seg.read_bytes())
        data[-3] ^= 0xFF                  # flip a payload byte of record 2
        seg.write_bytes(bytes(data))
        w2 = self._wal(tmp_path)
        recs = list(w2.read_from(1))
        assert [r[0] for r in recs] == [1]             # not silently replayed
        w2.close()

    def test_mid_log_corruption_still_raises(self, tmp_path):
        from greptimedb_tpu.errors import StorageError
        w = self._wal(tmp_path, segment_bytes=64)      # force tiny segments
        for seq in range(1, 5):
            w.append(seq, f"record-{seq}".encode() * 8)
        w.close()
        segs = sorted((tmp_path / "wal").glob("*.wal"))
        assert len(segs) >= 2
        first = segs[0]
        data = bytearray(first.read_bytes())
        data[-1] ^= 0xFF                  # corrupt an EARLIER segment
        first.write_bytes(bytes(data))
        w2 = self._wal(tmp_path, segment_bytes=64)
        with pytest.raises(StorageError):
            list(w2.read_from(1))
        w2.close()


# ---------------------------------------------------------------------------
# crash-recovery torture matrix (the tentpole invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("point", sorted(CRASH_POINTS))
def test_torture_matrix(tmp_path, point):
    run_crash_case(str(tmp_path), point)


@pytest.mark.slow
@pytest.mark.parametrize("sync_wal", [False, True])
@pytest.mark.parametrize("point", sorted(CRASH_POINTS))
def test_torture_matrix_extended(tmp_path, point, sync_wal):
    """The extended sweep: both WAL fsync modes, deeper baselines."""
    run_crash_case(str(tmp_path), point, sync_wal=sync_wal,
                   baseline_batches=6)


def test_failed_wal_append_burns_its_sequence(tmp_path):
    """A WAL append that fails AFTER the record may be durable (fsync
    fault) must consume the sequence: reusing it would put two different
    batches at one seq and make the replay winner undefined."""
    from greptimedb_tpu.storage.write_batch import WriteBatch
    rig = TortureRig(str(tmp_path), sync_wal=True)
    rig.create()
    region = rig.region
    vc = region.version_control
    rig.write(make_batch(0))
    seq_before = vc.committed_sequence
    with fp.cfg("wal_fsync", "err"):
        wb = WriteBatch(region.schema)
        wb.put({"host": ["x"], "ts": [999_000], "v": [9.0]})
        with pytest.raises(fp.FailpointError):
            region.write(wb)
    # the failed write's sequence is consumed, not handed to the next one
    assert vc.committed_sequence == seq_before + 1
    rig.write(make_batch(1))
    assert vc.committed_sequence == seq_before + 2
    # reopen: the failed batch is durable in the WAL at its own seq and
    # replays exactly once alongside the acked batches — no collision
    rig2 = TortureRig(str(tmp_path), sync_wal=True)
    rig2.open()
    got = rig2.region.snapshot().read_merged()
    keys = list(zip(got.series_ids.tolist(), got.ts.tolist()))
    assert len(keys) == len(set(keys))
    assert 999_000 in got.ts
    rig2.region.close()


def test_sync_flush_reports_coalesced_background_failure(tmp_path):
    """flush() coalescing onto an already-queued background flush whose
    failure is swallowed for retry must still raise — /v1/admin/flush
    and bulk_ingest rely on success meaning 'the memtables are on disk'."""
    import threading
    from greptimedb_tpu.errors import StorageError
    from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
    from greptimedb_tpu.storage.write_batch import WriteBatch
    from torture import make_schema
    eng = StorageEngine(EngineConfig(data_home=str(tmp_path),
                                     bg_workers=1))
    region = eng.create_region("r", make_schema())
    release = threading.Event()
    eng.scheduler.submit("blocker", release.wait)   # pin the only worker
    region.flush_size_bytes = 1
    wb = WriteBatch(region.schema)
    wb.put({"host": ["a"], "ts": [1000], "v": [1.0]})
    region.write(wb)               # queues the background flush (held)
    result = {}

    def do_flush():
        try:
            result["files"] = region.flush()
        except StorageError as e:
            result["err"] = e

    with fp.cfg("flush_commit", "err"):
        th = threading.Thread(target=do_flush)
        th.start()
        time.sleep(0.2)            # let flush() coalesce onto the bg job
        release.set()
        th.join(timeout=30)
        assert not th.is_alive()
        assert "err" in result, \
            "sync flush reported success while its memtables stayed dirty"
    # fault cleared: the background retry ladder finishes the flush
    deadline = time.time() + 20
    while time.time() < deadline and \
            not region.version_control.current.ssts.all_files():
        time.sleep(0.05)
    assert region.version_control.current.ssts.all_files()
    eng.close()


def test_background_flush_failure_retries_with_backoff(tmp_path):
    """A failing background flush must not wedge the region: it records
    the failure (surfaced via /status), backs off, retries, and the
    retry succeeds once the fault clears."""
    from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
    from torture import make_schema
    from greptimedb_tpu.storage.write_batch import WriteBatch
    eng = StorageEngine(EngineConfig(data_home=str(tmp_path),
                                     flush_size_bytes=1))
    region = eng.create_region("r", make_schema())
    # first flush-commit attempt fails, the backoff retry succeeds
    with fp.cfg("flush_commit", "1x2*err"):
        wb = WriteBatch(region.schema)
        wb.put({"host": ["a"], "ts": [1000], "v": [1.0]})
        region.write(wb)                  # triggers the background flush
        deadline = time.time() + 20
        while time.time() < deadline:
            if region.version_control.current.ssts.all_files():
                break
            time.sleep(0.02)
    files = region.version_control.current.ssts.all_files()
    assert files, "background flush never recovered from the fault"
    assert region.bg_errors["flush"]["count"] == 1
    assert "FailpointError" in region.bg_errors["flush"]["last_error"]
    eng.close()


def test_flush_retry_after_drop_writes_nothing(tmp_path, monkeypatch):
    """A delayed background-flush retry firing after DROP must not
    resurrect SSTs under the destroyed region dir (nothing would ever
    collect them — a dropped region never reopens)."""
    from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
    from greptimedb_tpu.storage.write_batch import WriteBatch
    from torture import make_schema
    eng = StorageEngine(EngineConfig(data_home=str(tmp_path)))
    region = eng.create_region("r", make_schema())
    region.flush_size_bytes = 1
    region_dir = region.descriptor.region_dir
    # the retry's backoff is held here and let go once the region is
    # dropped: whether DROP beats a 50 ms timer is the host's clock (under
    # six workers it did not, and the retry in flight left its index file
    # behind the delete), not what this test claims
    delayed = []
    monkeypatch.setattr(eng.scheduler, "submit_later",
                        lambda key, fn, delay_s: delayed.append((key, fn)))
    with fp.cfg("flush_commit", "err"):
        wb = WriteBatch(region.schema)
        wb.put({"host": ["a"], "ts": [1000], "v": [1.0]})
        region.write(wb)               # bg flush fails, retry queued
        deadline = time.time() + 10
        while time.time() < deadline and not delayed:
            time.sleep(0.02)
        assert region.bg_errors.get("flush") and len(delayed) == 1
        eng.drop_region("r")           # destroys the region dir
    for key, fn in delayed:            # the retry fires, runs and returns
        eng.scheduler.submit(key, fn).wait(timeout=10)
    eng.scheduler.wait_idle(timeout=10)
    leaked = [k for k in eng.store.list(region_dir)]
    assert not leaked, f"flush retry resurrected files: {leaked}"
    eng.close()


def test_meta_kv_crash_preserves_previous_value(tmp_path):
    from greptimedb_tpu.meta.kv import FileKv
    path = str(tmp_path / "meta" / "kv.json")
    kv = FileKv(path)
    kv.put("route/a", b"v1")
    with fp.cfg("meta_kv_put", "crash"):
        with pytest.raises(fp.SimulatedCrash):
            kv.put("route/a", b"v2")
    kv2 = FileKv(path)                    # reopen from disk
    assert kv2.get("route/a") == b"v1"    # atomic: never half-written
    kv2.put("route/a", b"v3")
    assert FileKv(path).get("route/a") == b"v3"


# ---------------------------------------------------------------------------
# end-to-end surfaces + acceptance shape
# ---------------------------------------------------------------------------

@pytest.fixture()
def frontend(tmp_path):
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    dn = DatanodeInstance(DatanodeOptions(
        data_home=str(tmp_path), register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    yield fe
    fe.shutdown()


def _rows(out):
    return [tuple(r) for b in out.batches for r in b.rows()]


class TestSurfaces:
    def test_set_and_information_schema(self, frontend):
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        frontend.do_query("SET failpoint_wal_append = '1x4*err'", ctx)
        out = frontend.do_query(
            "SELECT name, action FROM information_schema.failpoints "
            "WHERE name = 'wal_append'", ctx)[-1]
        assert _rows(out) == [("wal_append", "1x4*err")]
        frontend.do_query("SET failpoint_wal_append = 'off'", ctx)
        out = frontend.do_query(
            "SELECT action FROM information_schema.failpoints "
            "WHERE name = 'wal_append'", ctx)[-1]
        assert _rows(out) == [(None,)]
        with pytest.raises(Exception):
            frontend.do_query("SET failpoint_wal_append = 'bogus'", ctx)

    def test_objstore_retry_knobs_via_set(self, frontend):
        from greptimedb_tpu.session import QueryContext
        from greptimedb_tpu.storage import retry as retry_mod
        ctx = QueryContext()
        old = retry_mod.retry_settings()
        try:
            frontend.do_query("SET objstore_max_retries = 9", ctx)
            frontend.do_query("SET objstore_retry_base_ms = 21", ctx)
            assert retry_mod.retry_settings() == {"max_retries": 9,
                                                  "base_ms": 21}
        finally:
            retry_mod.configure_retry(**old)

    def test_http_failpoint_admin(self, frontend):
        from greptimedb_tpu.servers.http import HttpServer
        srv = HttpServer(frontend, addr="127.0.0.1:0")
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}/v1/admin/failpoints"
            q = urllib.parse.urlencode(
                {"name": "flush_commit", "action": "err"})
            with urllib.request.urlopen(
                    urllib.request.Request(f"{base}?{q}", method="POST"),
                    timeout=10) as resp:
                assert json.loads(resp.read())["code"] == 0
            with urllib.request.urlopen(base, timeout=10) as resp:
                doc = json.loads(resp.read())
            armed = {p["name"]: p["action"] for p in doc["failpoints"]}
            assert armed["flush_commit"] == "err"
            # a POST without 'action' must 400, NOT silently disarm
            q2 = urllib.parse.urlencode({"name": "flush_commit"})
            try:
                urllib.request.urlopen(
                    urllib.request.Request(f"{base}?{q2}", method="POST"),
                    timeout=10)
                pytest.fail("action-less POST accepted")
            except urllib.error.HTTPError as e:
                assert e.code == 400
            assert fp.active_count() == 1
            # /status surfaces the armed count
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/status",
                    timeout=10) as resp:
                status = json.loads(resp.read())
            assert status["failpoints_active"] >= 1
            with urllib.request.urlopen(
                    urllib.request.Request(base, method="DELETE"),
                    timeout=10) as resp:
                assert json.loads(resp.read())["code"] == 0
            assert fp.active_count() == 0
        finally:
            srv.shutdown()

    def test_ingest_flush_scan_through_one_in_three_faults(self, frontend):
        """Acceptance: 1-in-3 transient object-store faults on write AND
        read; bulk ingest + flush + cold scan all succeed through retry,
        and the retry counter is visible in runtime_metrics."""
        from greptimedb_tpu.query import stream_exec
        from greptimedb_tpu.session import QueryContext
        from greptimedb_tpu.storage.retry import configure_retry, \
            retry_settings
        ctx = QueryContext()
        frontend.do_query(
            "CREATE TABLE fi (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))", ctx)
        table = frontend.catalog.table("greptime", "public", "fi")
        n = 4000
        old = retry_settings()
        saved_threshold = stream_exec.stream_threshold_rows()
        configure_retry(base_ms=1)
        try:
            with fp.cfg("objstore_write", "1x3*err(transient)"):
                table.bulk_load({
                    "host": np.repeat(
                        np.array(["a", "b"], dtype=object), n // 2),
                    "ts": np.arange(n, dtype=np.int64) * 1000,
                    "v": np.ones(n)})
                table.flush()
            # cold scan (streamed path) with injected read faults
            stream_exec.configure_streaming(threshold_rows=1)
            from greptimedb_tpu.storage.scan_cache import SCAN_CACHE
            SCAN_CACHE._entries.clear()
            with fp.cfg("objstore_read", "1x3*err(transient)"):
                out = frontend.do_query(
                    "SELECT count(*), sum(v) FROM fi", ctx)[-1]
            assert _rows(out) == [(n, float(n))]
            out = frontend.do_query(
                "SELECT value FROM information_schema.runtime_metrics "
                "WHERE metric_name = 'greptime_objstore_retry_total'",
                ctx)[-1]
            rows = _rows(out)
            assert rows and rows[0][0] > 0
        finally:
            configure_retry(**old)
            stream_exec.configure_streaming(threshold_rows=saved_threshold)

    def test_flow_fold_commit_crash_never_double_folds(self, frontend):
        """Crash between the sink fold write and the watermark persist;
        after recovery the re-fold must be idempotent (sink == raw)."""
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        frontend.do_query(
            "CREATE TABLE src (host STRING, ts TIMESTAMP TIME INDEX, "
            "v DOUBLE, PRIMARY KEY(host))", ctx)
        frontend.do_query(
            "CREATE FLOW f1 AS SELECT host, "
            "date_bin(INTERVAL '1 minute', ts) AS b, sum(v) AS s, "
            "count(v) AS c FROM src GROUP BY host, b", ctx)
        frontend.do_query(
            "INSERT INTO src VALUES ('a', 1000, 1.0), ('a', 2000, 2.0), "
            "('b', 61000, 3.0)", ctx)
        fm = frontend.datanode.flow_manager
        with fp.cfg("flow_fold_commit", "crash"):
            with pytest.raises(fp.SimulatedCrash):
                fm.tick()
        # simulated restart of the flow layer: reload specs + watermarks
        # from the durable store (the pre-crash watermark was never
        # persisted, so the window re-folds)
        fm._flows.clear()
        fm.recover()
        frontend.do_query(
            "INSERT INTO src VALUES ('b', 62000, 4.0)", ctx)
        fm.tick()
        sink = frontend.do_query(
            "SELECT host, s, c FROM f1 ORDER BY host", ctx)[-1]
        assert sorted(_rows(sink)) == [("a", 3.0, 2), ("b", 7.0, 2)]
