"""Tier-1 gate for greptsan (devtools/greptsan), the happens-before
race detector: the selftest (every seeded concurrency bug fires), the
no-false-positive proof over the real flush+scan+compact path, the
multi-thread hammer (concurrent ingest+flush+compact+scatter+balancer
tick+self-monitor scrape must report ZERO races — the burn-down
regression surface), and the suppression-baseline policy (zero entries,
only ever shrinks).

The session-wide gate lives in tests/conftest.py: any unsuppressed race
recorded by ANY test fails the whole run at sessionfinish.
"""

import json
import subprocess
import sys
import threading
import time

import pytest

from greptimedb_tpu.devtools import greptsan
from greptimedb_tpu.devtools.greptsan import detector, selftest as seeded
from greptimedb_tpu.common.locks import TrackedLock

REPO = __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__)))
BASELINE = __import__("os").path.join(REPO, ".greptsan-baseline.json")


@pytest.fixture(autouse=True)
def _isolated():
    """Seeded fixtures deliberately race; drain them so the session
    gate only ever sees races from production code paths."""
    detector.reset()
    yield
    detector.reset()


def _race_states(reports):
    return {r.state for r in reports}


class TestSeededBugsFire:
    def test_unlocked_dict_mutation_across_threads(self):
        name = seeded.unlocked_dict_mutation()
        reports = detector.drain_races()
        assert name in _race_states(reports), (
            f"seeded unlocked-dict race did not fire; got "
            f"{_race_states(reports)}")

    def test_notify_without_lock(self):
        name = seeded.notify_without_lock()
        reports = detector.drain_races()
        assert name in _race_states(reports), (
            f"seeded notify-before-publish race did not fire; got "
            f"{_race_states(reports)}")

    def test_pool_result_read_before_join_edge(self):
        name = seeded.pool_result_before_join()
        reports = detector.drain_races()
        assert name in _race_states(reports), (
            f"seeded done()-polling race did not fire; got "
            f"{_race_states(reports)}")

    def test_report_names_both_stacks_and_missing_edge(self):
        seeded.unlocked_dict_mutation()
        [report] = [r for r in detector.drain_races()
                    if r.state == "greptsan.selftest.unlocked_dict"][:1]
        text = report.render()
        assert "DATA RACE" in text
        assert "prior" in text and "current" in text
        # both stacks must carry the RACING frames (the fixture's bump
        # workers), not just detector/threading internals — regression
        # for the substring frame filter that ate selftest frames
        assert text.count("in bump") >= 2
        assert "missing edge" in text
        assert report.suppression_key().startswith(
            "greptsan.selftest.unlocked_dict:")


class TestHappensBeforeEdgesSuppressRaces:
    """The dual of the seeded tests: each sanctioned synchronization
    idiom must NOT report (a detector that cries wolf gets turned off)."""

    def test_same_tracked_lock_orders_access(self):
        lk = TrackedLock("t.san_edge_lock", force=True)
        d = greptsan.tracked_state({}, "t.san_locked")

        def bump():
            for _ in range(20):
                with lk:
                    d["n"] = d.get("n", 0) + 1

        ts = [threading.Thread(target=bump) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not detector.drain_races()
        assert d["n"] == 60

    def test_thread_join_edge(self):
        d = greptsan.tracked_state({}, "t.san_join")

        def child():
            d["x"] = 1

        t = threading.Thread(target=child)
        t.start()
        t.join()
        d["x"] = 2                         # ordered by join()
        assert not detector.drain_races()

    def test_pool_submit_and_result_edges(self):
        from concurrent.futures import ThreadPoolExecutor
        d = greptsan.tracked_state({}, "t.san_pool_ok")
        d["x"] = 0                         # submit edge orders this
        with ThreadPoolExecutor(2) as p:
            f = p.submit(lambda: d.__setitem__("x", d["x"] + 1))
            f.result()                     # result edge orders the next
            d["x"] = 9
        assert not detector.drain_races()

    def test_event_set_wait_edge(self):
        d = greptsan.tracked_state({}, "t.san_event")
        ev = threading.Event()

        def producer():
            d["x"] = 1
            ev.set()

        t = threading.Thread(target=producer)
        t.start()
        assert ev.wait(10)
        d["x"] = 2                         # ordered by set->wait
        t.join()
        assert not detector.drain_races()

    def test_condition_handoff_over_tracked_lock(self):
        lk = TrackedLock("t.san_cond", force=True)
        cond = threading.Condition(lk)
        d = greptsan.tracked_state({}, "t.san_cond_state")

        def producer():
            with cond:
                d["ready"] = 1             # published BEFORE the notify
                cond.notify()

        t = threading.Thread(target=producer)
        with cond:
            t.start()
            while not d.get("ready"):
                cond.wait(timeout=10)
        t.join()
        assert not detector.drain_races()


class TestNoFalsePositivesOnStorage:
    def test_flush_scan_compact_is_clean(self, tmp_path):
        """The real storage interleaving (the lock-order detector's
        no-FP scenario, now replayed against the race detector): tracked
        region maps, caches and scheduler queues see concurrent ingest,
        reads, flushes and compactions — zero reports."""
        from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                      DatanodeOptions)
        from greptimedb_tpu.frontend.instance import FrontendInstance

        assert greptsan.enabled()
        dn = DatanodeInstance(DatanodeOptions(
            data_home=str(tmp_path / "d"), register_numbers_table=False,
            flush_size_bytes=64 * 1024))
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        try:
            fe.do_query("CREATE TABLE sanfp (host STRING, ts TIMESTAMP "
                        "TIME INDEX, v DOUBLE, PRIMARY KEY(host))")
            detector.drain_races()         # isolate this workload
            stop = threading.Event()
            errors = []

            def writer():
                try:
                    for i in range(150):
                        fe.do_query(f"INSERT INTO sanfp VALUES"
                                    f" ('h{i % 4}', {i}, {float(i)})")
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            def reader():
                try:
                    while not stop.is_set():
                        fe.do_query("SELECT count(*) FROM sanfp")
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            def flusher():
                t = fe.catalog.table("greptime", "public", "sanfp")
                try:
                    while not stop.is_set():
                        t.flush()
                        for region in dn.storage.list_regions().values():
                            region.schedule_compaction()
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            ts = [threading.Thread(target=f)
                  for f in (writer, reader, flusher)]
            for t in ts:
                t.start()
            ts[0].join(timeout=120)
            stop.set()
            for t in ts:
                t.join(timeout=30)
            assert not errors, errors
            reports = detector.drain_races()
            assert not reports, "false positive(s) on storage path:\n" + \
                "\n".join(r.render() for r in reports)
        finally:
            fe.shutdown()


class TestHammer:
    def test_concurrent_everything_reports_zero_races(self, tmp_path):
        """The burn-down surface: concurrent ingest + flush + compact +
        distributed scatter + balancer tick + self-monitor scrape over
        an in-process 2-datanode cluster. Every race this hammer ever
        finds gets FIXED (plus a regression test), never suppressed —
        the suppression baseline stays at zero entries."""
        from test_balancer import Cluster

        assert greptsan.enabled()
        c = Cluster(tmp_path, nodes=(1, 2))
        try:
            c.fe.do_query(
                "CREATE TABLE hammer (host STRING, ts TIMESTAMP TIME "
                "INDEX, v DOUBLE, PRIMARY KEY(host)) "
                "PARTITION BY HASH (host) PARTITIONS 4")
            detector.drain_races()
            stop = threading.Event()
            errors = []

            def guard(fn):
                def run():
                    try:
                        fn()
                    except Exception as e:  # noqa: BLE001
                        errors.append(e)
                return run

            def ingest():
                i = 0
                while not stop.is_set():
                    vals = ", ".join(
                        f"('h{j % 8}', {i * 50 + j}, {float(j)})"
                        for j in range(50))
                    c.fe.do_query(f"INSERT INTO hammer VALUES {vals}")
                    i += 1

            def scatter():
                while not stop.is_set():
                    c.fe.do_query("SELECT host, count(*), max(v) FROM "
                                  "hammer GROUP BY host")
                    c.fe.do_query("SELECT count(*) FROM hammer "
                                  "WHERE host = 'h3'")

            def flush_compact():
                while not stop.is_set():
                    for dn in list(c.datanodes.values()):
                        for region in \
                                dn.storage.list_regions().values():
                            region.flush()
                            region.schedule_compaction()
                    time.sleep(0.01)

            def balancer_pump():
                while not stop.is_set():
                    c.srv.balancer.tick()
                    for i in list(c.datanodes):
                        resp = c.srv.handle_heartbeat(i)
                        for msg in resp.mailbox:
                            c.datanodes[i]._handle_mailbox(msg)
                    c.srv.cluster_info()
                    c.srv.region_heat()
                    time.sleep(0.005)

            def monitor():
                while not stop.is_set():
                    c.fe.self_monitor.tick()
                    time.sleep(0.02)

            ts = [threading.Thread(target=guard(f), name=f"hammer-{i}")
                  for i, f in enumerate((ingest, scatter, flush_compact,
                                         balancer_pump, monitor))]
            for t in ts:
                t.start()
            time.sleep(6.0)
            stop.set()
            for t in ts:
                t.join(timeout=60)
            assert not errors, errors
            reports = detector.drain_races()
            assert not reports, (
                "hammer found data race(s) — fix them (never suppress):"
                "\n" + "\n".join(r.render() for r in reports))
        finally:
            c.shutdown()


class TestSuppressionPolicy:
    def test_baseline_exists_version_1_and_zero_entries(self):
        """ISSUE 10 acceptance: the baseline is burned to zero in this
        PR and — like greptlint's — only ever shrinks. With a floor of
        zero, 'only shrinks' means it stays empty forever."""
        with open(BASELINE, encoding="utf-8") as f:
            doc = json.load(f)
        assert doc.get("version") == 1
        assert doc.get("suppressions") == {}, (
            "greptsan suppressions must stay at ZERO entries: fix the "
            "race instead (ISSUE 10 burn-down policy)")

    def test_loader_and_filter_roundtrip(self, tmp_path):
        seeded.unlocked_dict_mutation()
        reports = detector.drain_races()
        assert reports
        key = reports[0].suppression_key()
        bl = tmp_path / "bl.json"
        bl.write_text(json.dumps({
            "version": 1,
            "suppressions": {key: "seeded fixture, test-only"}}))
        left = detector.unsuppressed(reports[:1], path=str(bl))
        assert left == []
        # and an unrelated key still passes through
        left = detector.unsuppressed(reports[:1],
                                     path=str(tmp_path / "missing.json"))
        assert left == reports[:1]

    def test_suppression_key_is_stable_across_runs(self):
        seeded.pool_result_before_join()
        k1 = {r.suppression_key() for r in detector.drain_races()}
        seeded.pool_result_before_join()
        k2 = {r.suppression_key() for r in detector.drain_races()}
        assert k1 & k2, "same seeded bug must produce a stable key"


class TestProxyFidelity:
    def test_tracked_ordereddict_copy_returns_plain(self):
        """Regression: OrderedDict.copy() builds self.__class__(self),
        whose first positional on the proxy is the tracker NAME — the
        inherited copy raised TypeError only under the detector (the
        cache/scheduler structures are TrackedOrderedDicts in tests)."""
        from collections import OrderedDict
        d = greptsan.tracked_state(OrderedDict([("a", 1), ("b", 2)]),
                                   "t.od_copy")
        c = d.copy()
        assert type(c) is OrderedDict and c == OrderedDict(
            [("a", 1), ("b", 2)])
        d2 = greptsan.tracked_state({"a": 1}, "t.d_copy")
        assert type(d2.copy()) is dict and d2.copy() == {"a": 1}
        detector.drain_races()


class TestInactiveMode:
    def test_tracked_state_is_identity_when_off(self):
        """GREPTIME_RACE_CHECK=0 ⇒ tracked_state returns its argument
        unchanged (same object, plain type) — production pays nothing."""
        code = (
            "from greptimedb_tpu.devtools.greptsan import tracked_state,"
            " enabled\n"
            "assert not enabled()\n"
            "d = {}\n"
            "assert tracked_state(d, 'x') is d\n"
            "assert type(tracked_state(d, 'x')) is dict\n"
            "import threading\n"
            "from greptimedb_tpu.common.locks import TrackedLock\n"
            "assert type(TrackedLock('x')) is type(threading.Lock())\n"
            "assert threading.Thread.start.__qualname__ == "
            "'Thread.start'\n"       # stdlib unpatched when off
            "print('OFF_OK')\n")
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
            env={"GREPTIME_RACE_CHECK": "0", "GREPTIME_LOCK_CHECK": "0",
                 "PATH": "/usr/bin", "JAX_PLATFORMS": "cpu"})
        assert "OFF_OK" in proc.stdout, proc.stderr

    def test_race_check_env_forces_lock_tracking_on(self):
        """GREPTIME_RACE_CHECK=1 outside pytest must switch the lock
        detector on too — greptsan's lock edges ride its hooks."""
        code = (
            "from greptimedb_tpu.common import locks\n"
            "from greptimedb_tpu.devtools.greptsan import detector\n"
            "assert locks.enabled() and detector.enabled()\n"
            "assert locks._RACE_HOOKS is not None\n"
            "print('FORCED_ON')\n")
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
            env={"GREPTIME_RACE_CHECK": "1", "PATH": "/usr/bin",
                 "JAX_PLATFORMS": "cpu"})
        assert "FORCED_ON" in proc.stdout, proc.stderr


class TestGenerationHygiene:
    def test_new_generation_clears_vars_but_keeps_races(self):
        seeded.unlocked_dict_mutation()
        n = len(detector.races())
        assert n >= 1
        detector.new_generation()
        assert len(detector.races()) == n      # races survive
        with detector._san_lock:
            assert not detector._vars          # metadata does not
