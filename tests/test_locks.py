"""Lock-order race detector (common/locks.py): the runtime half of
greptlint. The ABBA tests MUST fail if the detector's raise is removed —
they are the proof the detector detects — and the storage concurrency
scenario proves it stays quiet on the real flush+scan+compaction
interleavings (no false positives on code we ship).
"""

import concurrent.futures
import subprocess
import sys
import threading

import pytest

from greptimedb_tpu.common import locks
from greptimedb_tpu.common.locks import (IoUnderLockError, LockOrderError,
                                         TrackedLock, TrackedRLock)


@pytest.fixture(autouse=True)
def _fresh_graph():
    """Lock-order edges are global by design (cross-test accumulation is
    how real inversions surface); these tests seed their own unique lock
    classes, so isolate them from each other."""
    locks.reset_graph()
    yield
    locks.reset_graph()


class TestAbbaDetection:
    def test_abba_cycle_raises_instead_of_deadlocking(self):
        a = TrackedLock("t.abba_a", force=True)
        b = TrackedLock("t.abba_b", force=True)

        def leg_one():                  # establishes the order a -> b
            with a:
                with b:
                    pass

        t = threading.Thread(target=leg_one)
        t.start()
        t.join()
        assert "t.abba_b" in locks.order_edges().get("t.abba_a", set())

        with pytest.raises(LockOrderError, match="cycle"):
            with b:
                with a:                 # inverse order: ABBA
                    pass

    def test_error_names_both_sides_and_prior_stack(self):
        a = TrackedLock("t.named_a", force=True)
        b = TrackedLock("t.named_b", force=True)
        with a:
            with b:
                pass
        with pytest.raises(LockOrderError) as ei:
            with b:
                with a:
                    pass
        msg = str(ei.value)
        assert "t.named_a" in msg and "t.named_b" in msg
        assert "first seen at" in msg   # the acquisition that set the order

    def test_transitive_cycle_through_third_lock(self):
        a = TrackedLock("t.tri_a", force=True)
        b = TrackedLock("t.tri_b", force=True)
        c = TrackedLock("t.tri_c", force=True)
        with a:
            with b:
                pass
        with b:
            with c:
                pass
        with pytest.raises(LockOrderError):   # c -> a closes a->b->c->a
            with c:
                with a:
                    pass

    def test_two_instances_of_same_class_nested_raises(self):
        r1 = TrackedLock("t.same_class", force=True)
        r2 = TrackedLock("t.same_class", force=True)
        with pytest.raises(LockOrderError, match="same"):
            with r1:
                with r2:
                    pass

    def test_consistent_order_never_raises(self):
        a = TrackedLock("t.ok_a", force=True)
        b = TrackedLock("t.ok_b", force=True)
        for _ in range(3):
            with a:
                with b:
                    pass


class TestLockProtocol:
    def test_rlock_reentry_is_fine(self):
        r = TrackedRLock("t.rlock", force=True)
        with r:
            with r:
                assert locks.held_locks().count("t.rlock") == 2

    def test_nonreentrant_self_reacquire_raises_not_deadlocks(self):
        lk = TrackedLock("t.self_dead", force=True)
        with lk:
            with pytest.raises(LockOrderError, match="re-acquired"):
                lk.acquire()

    def test_try_acquire_records_no_order_edge(self):
        """Non-blocking acquisition cannot deadlock, so it must not
        poison the order graph."""
        a = TrackedLock("t.try_a", force=True)
        b = TrackedLock("t.try_b", force=True)
        with a:
            assert b.acquire(blocking=False)
            b.release()
        assert "t.try_b" not in locks.order_edges().get("t.try_a", set())
        with b:                          # inverse order is still legal
            with a:
                pass

    def test_release_supports_non_lifo(self):
        a = TrackedLock("t.lifo_a", force=True)
        b = TrackedLock("t.lifo_b", force=True)
        a.acquire()
        b.acquire()
        a.release()                      # out of order
        assert locks.held_locks() == ["t.lifo_b"]
        b.release()
        assert locks.held_locks() == []


class TestIoUnderLock:
    def test_io_failpoint_site_under_memory_lock_raises(self):
        from greptimedb_tpu.common import failpoint as fp
        lk = TrackedLock("t.mem_only", io_ok=False, force=True)
        with lk:
            with pytest.raises(IoUnderLockError, match="objstore_read"):
                fp.fires("objstore_read")

    def test_io_ok_lock_permits_io_sites(self):
        from greptimedb_tpu.common import failpoint as fp
        lk = TrackedLock("t.io_fine", io_ok=True, force=True)
        with lk:
            fp.fires("objstore_read")    # no raise

    def test_non_io_site_is_ignored(self):
        from greptimedb_tpu.common import failpoint as fp
        lk = TrackedLock("t.mem_only2", io_ok=False, force=True)
        with lk:
            fp.fires("manifest_commit")  # metadata site, not blocking I/O


class TestInactiveMode:
    def test_disabled_factory_returns_raw_lock(self):
        """GREPTIME_LOCK_CHECK=0 ⇒ plain threading primitives, nothing
        wrapped — production pays zero per-acquire cost."""
        code = (
            "from greptimedb_tpu.common.locks import TrackedLock, "
            "TrackedRLock, enabled\n"
            "import threading\n"
            "assert not enabled()\n"
            "assert type(TrackedLock('x')) is type(threading.Lock())\n"
            "assert type(TrackedRLock('x')) is type(threading.RLock())\n"
            "print('RAW_OK')\n")
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, env={"GREPTIME_LOCK_CHECK": "0", "PATH": "/usr/bin",
                              "JAX_PLATFORMS": "cpu"})
        assert "RAW_OK" in proc.stdout, proc.stderr

    def test_enabled_under_pytest(self):
        assert locks.enabled()           # auto-on: pytest in sys.modules


class TestNoFalsePositivesOnStorage:
    """The detector wraps ~10 real storage locks; the flush+scan+
    compaction interleaving from tests/test_concurrency.py must run
    clean — a detector that cries wolf gets turned off."""

    def test_flush_scan_compact_interleaving_is_clean(self, tmp_path):
        from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                      DatanodeOptions)
        from greptimedb_tpu.frontend.instance import FrontendInstance

        assert locks.enabled()
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / "d"), register_numbers_table=False,
            flush_size_bytes=64 * 1024))   # tiny: flushes trigger mid-test
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        try:
            fe.do_query("CREATE TABLE lk (host STRING, ts TIMESTAMP TIME"
                        " INDEX, v DOUBLE, PRIMARY KEY(host))")
            stop = threading.Event()
            errors = []

            def writer():
                try:
                    for i in range(200):
                        fe.do_query(f"INSERT INTO lk VALUES"
                                    f" ('h{i % 4}', {i}, {float(i)})")
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            def reader():
                try:
                    while not stop.is_set():
                        fe.do_query("SELECT count(*) FROM lk")
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            def flusher():
                t = fe.catalog.table("greptime", "public", "lk")
                try:
                    while not stop.is_set():
                        t.flush()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            with concurrent.futures.ThreadPoolExecutor(3) as pool:
                w = pool.submit(writer)
                pool.submit(reader)
                pool.submit(flusher)
                w.result(timeout=120)
                stop.set()
            bad = [e for e in errors if isinstance(e, LockOrderError)]
            assert not bad, f"false positive on real storage path: {bad}"
            assert not errors, errors
            out = fe.do_query("SELECT count(*) FROM lk")[-1]
            assert next(out.batches[0].rows())[0] == 200
        finally:
            fe.shutdown()

    def test_storage_locks_are_tracked_under_pytest(self, tmp_path):
        """The swap-in is live: a freshly built engine's locks are
        _Tracked instances, named, and the writer lock is reentrant."""
        from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine

        eng = StorageEngine(EngineConfig(data_home=str(tmp_path / "s")))
        assert isinstance(eng._lock, locks._Tracked)
        assert eng._lock.name == "storage.engine"


class TestConditionProtocol:
    """Regression: LocalScheduler builds threading.Condition over its
    (now tracked) lock; without _is_owned/_release_save/_acquire_restore
    on _Tracked, Condition's acquire(False) fallback misreads the owner
    probing its own non-reentrant lock as a self-deadlock — every
    background worker died at _wake.wait()."""

    def test_condition_wait_notify_over_tracked_lock(self):
        lk = TrackedLock("t.cond", io_ok=False, force=True)
        cond = threading.Condition(lk)
        ready = []

        def consumer():
            with cond:
                while not ready:
                    cond.wait(timeout=10)
                ready.append("consumed")

        t = threading.Thread(target=consumer)
        t.start()
        import time
        time.sleep(0.05)                 # let the consumer park in wait()
        with cond:
            ready.append("produced")
            cond.notify()
        t.join(timeout=10)
        assert not t.is_alive()
        assert ready == ["produced", "consumed"]

    def test_wait_releases_held_bookkeeping(self):
        """While parked in cond.wait() the thread must not count as
        holding the lock (the IO check and order graph read that list)."""
        lk = TrackedLock("t.cond_held", force=True)
        cond = threading.Condition(lk)
        observed = []

        def waiter():
            with cond:
                cond.wait(timeout=5)
                observed.append(list(locks.held_locks()))

        t = threading.Thread(target=waiter)
        t.start()
        import time
        time.sleep(0.05)
        with cond:                       # acquirable ⇒ waiter released it
            cond.notify()
        t.join(timeout=10)
        assert observed == [["t.cond_held"]]   # reacquired after wait
        assert locks.held_locks() == []

    def test_condition_over_tracked_rlock(self):
        lk = TrackedRLock("t.cond_r", force=True)
        cond = threading.Condition(lk)
        with cond:
            with lk:                     # re-entry while conditioned
                pass
            assert not cond.wait(timeout=0.01)  # times out, then restores
            assert locks.held_locks() == ["t.cond_r"]
        assert locks.held_locks() == []

    def test_scheduler_background_jobs_run_under_detector(self):
        """End to end: the real LocalScheduler (Condition over a tracked
        lock) still runs jobs with the detector on."""
        from greptimedb_tpu.storage.scheduler import LocalScheduler
        assert locks.enabled()
        s = LocalScheduler(max_inflight=2, name="lk-test")
        try:
            hs = [s.submit(f"j{i}", lambda i=i: i * i) for i in range(4)]
            assert [h.wait(10) for h in hs] == [0, 1, 4, 9]
        finally:
            s.stop()
