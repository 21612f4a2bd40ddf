"""Block-streamed cold scan (query/stream_exec.py).

The streamed path must produce byte-identical aggregate answers to the
cached device path and the CPU fallback oracle — including MVCC
overwrites, delete tombstones, NULLs, memtable+SST mixes, time filters,
field filters, and first/last — because a (series, ts) key lives in
exactly one time slice. Mirrors the reference's chunk-reader tests
(src/storage/src/chunk.rs) at the query level.
"""

import numpy as np
import pytest

from greptimedb_tpu import DEFAULT_CATALOG_NAME as CAT, \
    DEFAULT_SCHEMA_NAME as SCH
from greptimedb_tpu.catalog import MemoryCatalogManager
from greptimedb_tpu.datatypes import data_type as dt
from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema, SemanticType
from greptimedb_tpu.mito import MitoEngine
from greptimedb_tpu.query import QueryEngine
from greptimedb_tpu.query import stream_exec, tpu_exec
from greptimedb_tpu.storage import scan_cache
from greptimedb_tpu.session import QueryContext
from greptimedb_tpu.sql import parse_sql
from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
from greptimedb_tpu.storage.write_batch import WriteBatch
from greptimedb_tpu.table import CreateTableRequest


@pytest.fixture(autouse=True)
def _force_device_dispatch(monkeypatch):
    monkeypatch.setattr(tpu_exec, "TPU_DISPATCH_MIN_ROWS", 0)
    # the latency-adaptive floor would route these small test tables to
    # the CPU path; pin it so the device (and streaming) paths execute
    monkeypatch.setattr(tpu_exec, "_dispatch_min_rows", lambda: 0)


def make_world(tmp_path, *, n=6000, seed=3, flushes=4):
    """A region whose rows span several SSTs + a live memtable, with
    overwrites, deletes, and NULLs."""
    rng = np.random.default_rng(seed)
    schema = Schema([
        ColumnSchema("host", dt.STRING, nullable=False,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("cpu", dt.FLOAT64),
        ColumnSchema("mem", dt.FLOAT64),
    ])
    storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
    mito = MitoEngine(storage)
    cm = MemoryCatalogManager()
    table = mito.create_table(CreateTableRequest(
        "m", schema, primary_key_indices=[0]))
    cm.register_table(CAT, SCH, "m", table)
    region = next(iter(table.regions.values()))

    chunk = n // (flushes + 1)
    for part in range(flushes + 1):
        hosts = [f"h{int(h)}" for h in rng.integers(0, 7, chunk)]
        # overlapping time ranges across flushes → overlapping SSTs,
        # repeated (host, ts) keys → MVCC overwrites across files
        ts = rng.integers(0, n * 40, chunk).astype(np.int64)
        cpu = rng.random(chunk).round(4)
        mem = [None if i % 13 == 0 else float(i % 50)
               for i in range(chunk)]
        wb = WriteBatch(schema)
        wb.put({"host": hosts, "ts": ts.tolist(), "cpu": cpu.tolist(),
                "mem": mem})
        region.write(wb)
        if part % 2 == 1:
            mdel = int(rng.integers(1, 40))
            wb = WriteBatch(schema)
            wb.delete({"host": [f"h{int(h)}"
                                for h in rng.integers(0, 7, mdel)],
                       "ts": rng.integers(0, n * 40, mdel).tolist()})
            region.write(wb)
        if part < flushes:
            region.flush()
    return storage, QueryEngine(cm), table, region


QUERIES = [
    "SELECT host, count(*), sum(cpu), avg(cpu) FROM m GROUP BY host "
    "ORDER BY host",
    "SELECT host, min(cpu), max(cpu), stddev(cpu) FROM m GROUP BY host "
    "ORDER BY host",
    "SELECT host, count(mem), avg(mem) FROM m GROUP BY host ORDER BY host",
    "SELECT host, first(cpu), last(cpu) FROM m GROUP BY host ORDER BY host",
    "SELECT host, date_bin(INTERVAL '30 seconds', ts) AS b, avg(cpu) "
    "FROM m GROUP BY host, b ORDER BY host, b LIMIT 50",
    "SELECT count(*), avg(cpu) FROM m",
    "SELECT host, avg(cpu) FROM m WHERE ts >= 40000 AND ts < 180000 "
    "GROUP BY host ORDER BY host",
    "SELECT host, count(*) FROM m WHERE cpu > 0.5 GROUP BY host "
    "ORDER BY host",
    "SELECT host, avg(cpu) FROM m WHERE host != 'h3' GROUP BY host "
    "ORDER BY host",
]


def rows_of(engine, sql):
    out = engine.execute(parse_sql(sql), QueryContext())
    return out.batches[0].to_pylist() if out.batches else []


def approx_equal(a, b):
    assert len(a) == len(b), f"{len(a)} vs {len(b)} rows"
    for ra, rb in zip(a, b):
        assert list(ra) == list(rb)
        for k in ra:
            va, vb = ra[k], rb[k]
            if isinstance(va, float) and isinstance(vb, float):
                if np.isnan(va) and np.isnan(vb):
                    continue
                np.testing.assert_allclose(va, vb, rtol=1e-4, atol=1e-5)
            else:
                assert va == vb, f"{k}: {va} != {vb}"


class TestStreamedMatchesCached:
    @pytest.mark.parametrize("sql", QUERIES)
    def test_query(self, tmp_path, monkeypatch, sql):
        storage, engine, table, region = make_world(tmp_path)
        try:
            want = rows_of(engine, sql)          # cached device path
            monkeypatch.setattr(stream_exec, "_STREAM_THRESHOLD_ROWS", [0])
            monkeypatch.setattr(stream_exec, "_SLICE_ROWS", [700])
            monkeypatch.setattr(stream_exec, "_ROW_BUCKET_MIN", 256)
            got = rows_of(engine, sql)           # streamed path
            approx_equal(got, want)
        finally:
            storage.close()

    def test_lean_path_engages_on_clean_bulk_region(self, tmp_path,
                                                    monkeypatch):
        """A bulk-loaded region (dup-free, delete-free, key-disjoint
        files, no memtable rows) must take the zero-copy chunk-frame
        fast path — and produce the same answers as the general merge
        path with the lean proof disabled."""
        rng = np.random.default_rng(11)
        schema = Schema([
            ColumnSchema("host", dt.STRING, nullable=False,
                         semantic_type=SemanticType.TAG),
            ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                         semantic_type=SemanticType.TIMESTAMP),
            ColumnSchema("cpu", dt.FLOAT64),
        ])
        storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
        mito = MitoEngine(storage)
        cm = MemoryCatalogManager()
        table = mito.create_table(CreateTableRequest(
            "m", schema, primary_key_indices=[0]))
        cm.register_table(CAT, SCH, "m", table)
        engine = QueryEngine(cm)
        try:
            hosts = 5
            per = 400
            for batch_no in range(3):           # 3 time-disjoint files
                ts = np.tile(np.arange(per, dtype=np.int64) * 100
                             + batch_no * per * 100, hosts)
                host = np.repeat(np.array(
                    [f"h{i}" for i in range(hosts)]), per).astype(object)
                table.bulk_load({"host": host, "ts": ts,
                                 "cpu": rng.random(len(ts)).round(4)})
            monkeypatch.setattr(stream_exec, "_STREAM_THRESHOLD_ROWS", [0])
            monkeypatch.setattr(stream_exec, "_SLICE_ROWS", [per * hosts])
            monkeypatch.setattr(stream_exec, "_ROW_BUCKET_MIN", 256)
            lean_calls = []
            orig = stream_exec._lean_chunk_frames

            def spy(*a, **k):
                r = orig(*a, **k)
                lean_calls.append(r is not None)
                return r
            monkeypatch.setattr(stream_exec, "_lean_chunk_frames", spy)
            sqls = [
                "SELECT host, count(*), avg(cpu) FROM m GROUP BY host "
                "ORDER BY host",
                "SELECT host, date_bin(INTERVAL '30 seconds', ts) AS b, "
                "min(cpu), max(cpu) FROM m GROUP BY host, b "
                "ORDER BY host, b LIMIT 40",
                "SELECT host, avg(cpu) FROM m WHERE ts >= 5000 AND "
                "ts < 100000 GROUP BY host ORDER BY host",
            ]
            got = [rows_of(engine, s) for s in sqls]
            assert lean_calls and all(lean_calls), \
                "clean bulk region must take the lean chunk-frame path"
            # same answers with the lean proof disabled (general path)
            monkeypatch.setattr(stream_exec, "_slice_lean_proof",
                                lambda *a, **k: (False, False, []))
            want = [rows_of(engine, s) for s in sqls]
            for g, w in zip(got, want):
                approx_equal(g, w)
        finally:
            storage.close()

    def test_first_last_across_key_disjoint_boundary_sid(self, tmp_path,
                                                         monkeypatch):
        """Two key-disjoint files sharing a boundary series with
        non-monotonic time across the concat: the dedup-skip proof holds
        (no key has two versions), but positional first/last must NOT
        trust concat order — regression for the round-6 review find."""
        schema = Schema([
            ColumnSchema("host", dt.STRING, nullable=False,
                         semantic_type=SemanticType.TAG),
            ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                         semantic_type=SemanticType.TIMESTAMP),
            ColumnSchema("cpu", dt.FLOAT64),
        ])
        storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
        mito = MitoEngine(storage)
        cm = MemoryCatalogManager()
        table = mito.create_table(CreateTableRequest(
            "m", schema, primary_key_indices=[0]))
        cm.register_table(CAT, SCH, "m", table)
        engine = QueryEngine(cm)
        try:
            # file A: sids for h00..h10, LATE times; h10 written here
            # first (larger ts)
            hosts_a = [f"h{i:02d}" for i in range(11) for _ in range(4)]
            ts_a = [5000 + 100 * j for _ in range(11) for j in range(4)]
            table.bulk_load({"host": np.array(hosts_a, dtype=object),
                             "ts": np.array(ts_a, dtype=np.int64),
                             "cpu": np.array(
                                 [float(t) for t in ts_a])})
            # file B: sids h10..h20, EARLY times (disjoint from A's
            # window, so the key rectangles stay disjoint)
            hosts_b = [f"h{i:02d}" for i in range(10, 21)
                       for _ in range(4)]
            ts_b = [100 * j for _ in range(11) for j in range(4)]
            table.bulk_load({"host": np.array(hosts_b, dtype=object),
                             "ts": np.array(ts_b, dtype=np.int64),
                             "cpu": np.array(
                                 [float(t) for t in ts_b])})
            monkeypatch.setattr(stream_exec, "_STREAM_THRESHOLD_ROWS", [0])
            # one big slice spanning both files → concat path, and
            # disable the chunk-frame reader to force the general path
            monkeypatch.setattr(stream_exec, "_SLICE_ROWS", [100000])
            monkeypatch.setattr(stream_exec, "_ROW_BUCKET_MIN", 256)
            monkeypatch.setattr(stream_exec, "_lean_chunk_frames",
                                lambda *a, **k: None)
            rows = rows_of(engine,
                           "SELECT host, first(cpu), last(cpu) FROM m "
                           "WHERE host = 'h10' GROUP BY host")
            assert len(rows) == 1
            r = rows[0]
            # h10's earliest row is ts=0 (file B), latest ts=5300 (file A)
            assert r["first(cpu)"] == 0.0, r
            assert r["last(cpu)"] == 5300.0, r
        finally:
            storage.close()

    def test_streaming_actually_streams(self, tmp_path, monkeypatch):
        storage, engine, table, region = make_world(tmp_path)
        try:
            monkeypatch.setattr(stream_exec, "_STREAM_THRESHOLD_ROWS", [0])
            monkeypatch.setattr(stream_exec, "_SLICE_ROWS", [700])
            monkeypatch.setattr(stream_exec, "_ROW_BUCKET_MIN", 256)
            calls = []
            orig = stream_exec._load_slice

            def spy(*a, **k):
                calls.append(1)
                return orig(*a, **k)
            monkeypatch.setattr(stream_exec, "_load_slice", spy)
            rows_of(engine, "SELECT host, avg(cpu) FROM m GROUP BY host")
            assert len(calls) > 3, "expected multiple slices"
            # the huge region never entered the scan cache
            assert region.uid not in scan_cache.SCAN_CACHE._entries
        finally:
            storage.close()

    def test_wide_region_streams_on_byte_budget(self, tmp_path,
                                                monkeypatch):
        """A region under the ROW threshold still streams when its
        estimated decoded bytes exceed half the scan-cache budget (one
        fat region must not blow residency — the cache never evicts its
        newest entry)."""
        storage, engine, table, region = make_world(tmp_path)
        try:
            # row threshold far above the region; byte budget tiny
            monkeypatch.setattr(stream_exec, "_STREAM_THRESHOLD_ROWS",
                                [1 << 62])
            est = stream_exec.region_estimated_bytes(region)
            assert est > 0
            monkeypatch.setattr(scan_cache.SCAN_CACHE, "budget_bytes", est)
            called = []
            orig = stream_exec.stream_region_moment_frames

            def spy(*a, **k):
                called.append(1)
                return orig(*a, **k)
            monkeypatch.setattr(stream_exec,
                                "stream_region_moment_frames", spy)
            rows_of(engine, "SELECT host, avg(cpu) FROM m GROUP BY host")
            assert called, "wide region must stream, not cache"
            assert region.uid not in scan_cache.SCAN_CACHE._entries
        finally:
            storage.close()

    def test_memtable_only_region(self, tmp_path, monkeypatch):
        storage, engine, table, region = make_world(
            tmp_path, n=900, flushes=0)
        try:
            want = rows_of(engine, "SELECT host, avg(cpu) FROM m "
                                   "GROUP BY host ORDER BY host")
            monkeypatch.setattr(stream_exec, "_STREAM_THRESHOLD_ROWS", [0])
            monkeypatch.setattr(stream_exec, "_SLICE_ROWS", [200])
            monkeypatch.setattr(stream_exec, "_ROW_BUCKET_MIN", 64)
            got = rows_of(engine, "SELECT host, avg(cpu) FROM m "
                                  "GROUP BY host ORDER BY host")
            approx_equal(got, want)
        finally:
            storage.close()


class TestScanCacheBudget:
    def test_lru_byte_eviction_and_rebuild(self, tmp_path):
        """N regions whose combined scans exceed the budget: LRU scans
        evict whole, steady residency stays under budget, and an evicted
        region rebuilds correctly on the next query."""
        from greptimedb_tpu.storage.engine import EngineConfig, StorageEngine
        schema = Schema([
            ColumnSchema("host", dt.STRING, nullable=False,
                         semantic_type=SemanticType.TAG),
            ColumnSchema("ts", dt.TIMESTAMP_MILLISECOND, nullable=False,
                         semantic_type=SemanticType.TIMESTAMP),
            ColumnSchema("cpu", dt.FLOAT64),
        ])
        storage = StorageEngine(EngineConfig(data_home=str(tmp_path)))
        regions = []
        n = 4000                                 # ~100KB+ per scan
        for i in range(6):
            r = storage.create_region(f"r{i}", schema)
            wb = WriteBatch(schema)
            wb.put({"host": [f"h{j % 4}" for j in range(n)],
                    "ts": (np.arange(n) * 100 + i).tolist(),
                    "cpu": np.full(n, float(i)).tolist()})
            r.write(wb)
            regions.append(r)
        cache = scan_cache._ScanCache(capacity=100)
        one = cache.get(regions[0]).nbytes
        cache.configure(budget_bytes=int(one * 2.5))
        for r in regions:
            cache.get(r)
        assert cache.resident_bytes() <= int(one * 2.5)
        assert len(cache._entries) <= 2
        # most-recent survives; evicted region rebuilds with right data
        assert regions[5].uid in cache._entries
        scan0 = cache.get(regions[0])
        assert scan0.num_rows == n
        assert float(scan0.fields["cpu"][0][0]) == 0.0
        # LRU order: touching r0 made it most-recent; r5 still cached
        assert list(cache._entries)[-1] == regions[0].uid
        storage.close()


class TestSlicePlanning:
    def test_single_slice_under_budget(self):
        assert stream_exec._plan_slices([(0, 99, 50)], 100, None, None) == \
            [(0, 100)]

    def test_cuts_on_chunk_edges(self):
        stats = [(0, 9, 40), (10, 19, 40), (20, 29, 40)]
        slices = stream_exec._plan_slices(stats, 60, None, None)
        assert slices[0][0] == 0 and slices[-1][1] == 30
        # contiguous, non-overlapping cover
        for (a, b), (c, d) in zip(slices, slices[1:]):
            assert b == c and a < b
        assert len(slices) >= 2

    def test_clip_bounds(self):
        stats = [(0, 99, 100)]
        assert stream_exec._plan_slices(stats, 1000, 40, 60) == [(40, 60)]
        assert stream_exec._plan_slices(stats, 1000, 200, None) == []
        assert stream_exec._plan_slices([], 1000, None, None) == []

    def test_overlapping_chunks(self):
        stats = [(0, 50, 30), (25, 75, 30), (50, 99, 30)]
        slices = stream_exec._plan_slices(stats, 45, None, None)
        assert slices[0][0] == 0 and slices[-1][1] == 100
        for (a, b), (c, d) in zip(slices, slices[1:]):
            assert b == c
