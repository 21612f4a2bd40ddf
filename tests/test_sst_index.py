"""Per-SST secondary index (ISSUE 13): differential + degrade sweep.

The contract under test: index-on and index-off answers are IDENTICAL
across predicate shapes (the sid-set is a pruning superset, never a
filter), bloom false positives are harmless, pre-upgrade files (no
sidecar) stay scannable, and a corrupt or unreadable sidecar degrades
to stats-only pruning with `greptime_sst_index_degrade_total` counting
it — never a failed query.
"""

import os

import numpy as np
import pytest

from greptimedb_tpu.common import failpoint as fp
from greptimedb_tpu.datatypes import Schema
from greptimedb_tpu.datatypes.data_type import (FLOAT64, STRING,
                                                TIMESTAMP_MILLISECOND)
from greptimedb_tpu.datatypes.schema import ColumnSchema, SemanticType
from greptimedb_tpu.storage import index as sst_index
from greptimedb_tpu.storage.index import (SstIndex, SstIndexCorrupt,
                                          configure_sst_index,
                                          index_file_name,
                                          sst_index_enabled)
from greptimedb_tpu.storage.object_store import FsObjectStore
from greptimedb_tpu.storage.region import Region, RegionDescriptor
from greptimedb_tpu.storage.write_batch import WriteBatch


def _counter_value(name: str) -> float:
    from prometheus_client import REGISTRY
    return REGISTRY.get_sample_value(name) or 0.0


@pytest.fixture(autouse=True)
def _index_on():
    """Every test starts (and leaves the process) with the index tier
    enabled — the default production state."""
    configure_sst_index(enabled=True)
    yield
    configure_sst_index(enabled=True)
    fp.clear_all()


# ---------------------------------------------------------------------------
# unit: bloom + row-group summary + codec
# ---------------------------------------------------------------------------

class TestSstIndexUnit:
    def test_membership_and_fp_rate(self):
        rng = np.random.default_rng(3)
        members = np.unique(rng.integers(0, 1 << 30, 4000))
        idx = SstIndex.build(np.sort(members), row_group_size=1 << 20)
        assert idx.may_contain(members).all()
        probes = np.setdiff1d(rng.integers(0, 1 << 30, 20000), members)
        fp_rate = idx.may_contain(probes).mean()
        assert fp_rate < 0.05, f"bloom fp rate {fp_rate:.3f}"

    def test_row_group_summary_exact(self):
        # rows sorted by sid; groups of 4: [1,1,3,3] [3,7,7,7] [9,9]
        sids = np.array([1, 1, 3, 3, 3, 7, 7, 7, 9, 9])
        idx = SstIndex.build(sids, row_group_size=4)
        assert list(idx.row_groups_for(np.array([3]))) == [True, True,
                                                           False]
        assert list(idx.row_groups_for(np.array([9]))) == [False, False,
                                                           True]
        # sid 5 is inside group bounds [3,7] but absent: the exact
        # per-group sid set (not just [lo, hi]) prunes it
        assert list(idx.row_groups_for(np.array([5]))) == [False, False,
                                                           False]
        assert not idx.row_groups_for(np.zeros(0, np.int64)).any()

    def test_codec_roundtrip(self):
        sids = np.repeat(np.arange(0, 50, 7), 5)
        idx = SstIndex.build(sids, row_group_size=8)
        idx2 = SstIndex.from_bytes(idx.to_bytes())
        assert idx2.num_rows == idx.num_rows
        assert (idx2.words == idx.words).all()
        assert (idx2.rg_lo == idx.rg_lo).all()
        assert idx2.may_contain_any(np.array([7]))
        assert not idx2.may_contain_any(np.array([6]))

    def test_codec_rejects_corruption(self):
        data = SstIndex.build(np.arange(100), 16).to_bytes()
        with pytest.raises(SstIndexCorrupt):
            SstIndex.from_bytes(b"junk" + data)
        with pytest.raises(SstIndexCorrupt):
            SstIndex.from_bytes(data[:-3])          # truncated payload
        flipped = bytearray(data)
        flipped[-1] ^= 0xFF
        with pytest.raises(SstIndexCorrupt):        # crc catches bitrot
            SstIndex.from_bytes(bytes(flipped))

    def test_false_positive_is_harmless(self, tmp_path, monkeypatch):
        """A bloom that answers 'maybe' for everything only loses the
        pruning — answers stay exact (the scan re-masks rows)."""
        region = _make_region(str(tmp_path))
        _ingest_overlapping_batches(region)
        monkeypatch.setattr(SstIndex, "may_contain_any",
                            lambda self, s: True)
        sd = region.series_dict
        got = _rows_for(region, sd.sids_for_tag_values(0, ["h2"]))
        assert got == _full_rows(region, {"h2"})


# ---------------------------------------------------------------------------
# storage-level differential
# ---------------------------------------------------------------------------

def _make_schema(tag_nullable: bool = False) -> Schema:
    return Schema([
        ColumnSchema("host", STRING, nullable=tag_nullable,
                     semantic_type=SemanticType.TAG),
        ColumnSchema("ts", TIMESTAMP_MILLISECOND, nullable=False,
                     semantic_type=SemanticType.TIMESTAMP),
        ColumnSchema("v", FLOAT64),
    ])


def _make_region(home: str, tag_nullable: bool = False) -> Region:
    return Region.create(
        RegionDescriptor("idx", _make_schema(tag_nullable), "idx",
                         os.path.join(home, "wal")),
        FsObjectStore(os.path.join(home, "data")))


def _ingest_overlapping_batches(region: Region) -> None:
    """Three flushed SSTs with overlapping sid RANGES but distinct sid
    sets (h4 rides every batch), plus an overwrite and a delete so the
    kept files still exercise MVCC dedup."""
    ts = 0
    for batch in (("h1", "h4"), ("h2", "h4"), ("h3", "h4")):
        wb = WriteBatch(region.schema)
        hosts = list(batch) * 3
        wb.put({"host": hosts, "ts": list(range(ts, ts + len(hosts))),
                "v": [float(ts + i) for i in range(len(hosts))]})
        region.write(wb)
        region.flush()
        ts += len(hosts)
    # overwrite one h2 key and delete one h4 key in a fourth file
    wb = WriteBatch(region.schema)
    wb.put({"host": ["h2"], "ts": [6], "v": [99.5]})
    region.write(wb)
    wb = WriteBatch(region.schema)
    wb.delete({"host": ["h4"], "ts": [1]})
    region.write(wb)
    region.flush()


def _rows_for(region: Region, sid_set) -> set:
    data = region.snapshot().read_merged(sid_set=sid_set)
    hosts = region.series_dict.decode_tag_column(data.series_ids, 0)
    return {(h, int(t), float(v)) for h, t, v in
            zip(hosts, data.ts, data.fields["v"][0])}


def _full_rows(region: Region, keep_hosts) -> set:
    data = region.snapshot().read_merged()
    hosts = region.series_dict.decode_tag_column(data.series_ids, 0)
    return {(h, int(t), float(v)) for h, t, v in
            zip(hosts, data.ts, data.fields["v"][0])
            if h in keep_hosts}


class TestScanSidSet:
    def test_point_scan_matches_full_scan(self, tmp_path):
        region = _make_region(str(tmp_path))
        _ingest_overlapping_batches(region)
        sd = region.series_dict
        for hosts in (["h1"], ["h2"], ["h4"], ["h1", "h3"],
                      ["h2", "h4"], ["nope"]):
            sids = sd.sids_for_tag_values(0, hosts)
            assert _rows_for(region, sids) == \
                _full_rows(region, set(hosts)), hosts

    def test_files_pruned_before_footer(self, tmp_path):
        region = _make_region(str(tmp_path))
        _ingest_overlapping_batches(region)
        sd = region.series_dict
        from greptimedb_tpu.common import exec_stats
        with exec_stats.collect() as st:
            _rows_for(region, sd.sids_for_tag_values(0, ["h2"]))
        prune = st.stages["prune"].detail
        # 4 files: file 1 range-pruned, file 3 bloom-pruned, files 2+4
        # (h2 lives in both) kept
        assert prune["index_files_checked"] == 4
        assert prune["index_files_pruned"] == 2

    def test_null_tags_excluded(self, tmp_path):
        """Rows whose tag is NULL form their own series; a point sid
        set never includes them (= is UNKNOWN on NULL), matching the
        engine's fillna(False) WHERE semantics."""
        region = _make_region(str(tmp_path), tag_nullable=True)
        wb = WriteBatch(region.schema)
        wb.put({"host": ["a", None, "a", None], "ts": [1, 2, 3, 4],
                "v": [1.0, 2.0, 3.0, 4.0]})
        region.write(wb)
        # memtable-only: parquet cannot encode a null dictionary value
        # (pre-existing writer limitation), but the sid-set path must
        # exclude NULL-tag series wherever the rows live
        sids = region.series_dict.sids_for_tag_values(0, ["a"])
        got = _rows_for(region, sids)
        assert got == {("a", 1, 1.0), ("a", 3, 3.0)}

    def test_pre_upgrade_files_stats_only(self, tmp_path):
        """Files written with the index disabled (= pre-upgrade files
        recovered from an old manifest) carry no sidecar and stay fully
        scannable through the stats-only path."""
        configure_sst_index(enabled=False)
        region = _make_region(str(tmp_path))
        _ingest_overlapping_batches(region)
        assert all(f.index_file is None for f in
                   region.version_control.current.ssts.all_files())
        configure_sst_index(enabled=True)
        sd = region.series_dict
        assert _rows_for(region, sd.sids_for_tag_values(0, ["h3"])) == \
            _full_rows(region, {"h3"})

    def test_mixed_upgrade_files(self, tmp_path):
        """Half the files indexed, half pre-upgrade: the planner prunes
        what it can and keeps the rest — answers identical."""
        configure_sst_index(enabled=False)
        region = _make_region(str(tmp_path))
        wb = WriteBatch(region.schema)
        wb.put({"host": ["h1", "h4"], "ts": [0, 1], "v": [0.0, 1.0]})
        region.write(wb)
        region.flush()
        configure_sst_index(enabled=True)
        wb = WriteBatch(region.schema)
        wb.put({"host": ["h2", "h4"], "ts": [2, 3], "v": [2.0, 3.0]})
        region.write(wb)
        region.flush()
        metas = region.version_control.current.ssts.all_files()
        assert sorted(m.index_file is not None for m in metas) == \
            [False, True]
        sd = region.series_dict
        for hosts in (["h1"], ["h2"], ["h4"]):
            assert _rows_for(region, sd.sids_for_tag_values(0, hosts)) \
                == _full_rows(region, set(hosts))

    def test_corrupt_sidecar_degrades(self, tmp_path):
        region = _make_region(str(tmp_path))
        _ingest_overlapping_batches(region)
        for f in region.version_control.current.ssts.all_files():
            assert f.index_file is not None
            region.store.write(f"idx/sst/{f.index_file}", b"garbage!")
        region.access_layer._sst_index.clear()   # drop parsed copies
        before = _counter_value("greptime_sst_index_degrade_total")
        sd = region.series_dict
        assert _rows_for(region, sd.sids_for_tag_values(0, ["h2"])) == \
            _full_rows(region, {"h2"})
        assert _counter_value("greptime_sst_index_degrade_total") > before

    def test_read_failpoint_degrades(self, tmp_path):
        region = _make_region(str(tmp_path))
        _ingest_overlapping_batches(region)
        region.access_layer._sst_index.clear()
        before = _counter_value("greptime_sst_index_degrade_total")
        sd = region.series_dict
        with fp.cfg("sst_index_read", "err"):
            assert _rows_for(region, sd.sids_for_tag_values(0, ["h1"])) \
                == _full_rows(region, {"h1"})
        assert _counter_value("greptime_sst_index_degrade_total") > before

    def test_write_failpoint_degrades_to_stats_only(self, tmp_path):
        """An err (not crash) on the sidecar write must not fail the
        flush: the file commits stats-only."""
        region = _make_region(str(tmp_path))
        wb = WriteBatch(region.schema)
        wb.put({"host": ["h1"], "ts": [0], "v": [1.0]})
        region.write(wb)
        with fp.cfg("sst_index_write", "err"):
            region.flush()
        metas = region.version_control.current.ssts.all_files()
        assert len(metas) == 1 and metas[0].index_file is None
        assert _rows_for(region, region.series_dict.sids_for_tag_values(
            0, ["h1"])) == _full_rows(region, {"h1"})

    def test_sidecar_swept_with_orphan_sst(self, tmp_path):
        """Crash between sidecar publish and manifest commit: BOTH the
        data file and its sidecar are unreferenced orphans the reopen
        sweep collects (the full matrix cell lives in torture.py)."""
        region = _make_region(str(tmp_path))
        wb = WriteBatch(region.schema)
        wb.put({"host": ["h1"], "ts": [0], "v": [1.0]})
        region.write(wb)
        with fp.cfg("flush_commit", "crash"):
            with pytest.raises(fp.SimulatedCrash):
                region.flush()
        reopened = Region.open(
            RegionDescriptor("idx", None, "idx",
                             os.path.join(str(tmp_path), "wal")),
            FsObjectStore(os.path.join(str(tmp_path), "data")))
        on_disk = reopened.store.list("idx/sst/")
        assert on_disk == [], on_disk
        assert _rows_for(reopened, reopened.series_dict.
                         sids_for_tag_values(0, ["h1"])) == \
            _full_rows(reopened, {"h1"})

    def test_compaction_outputs_carry_indexes(self, tmp_path):
        region = _make_region(str(tmp_path))
        _ingest_overlapping_batches(region)
        region.compact()
        metas = region.version_control.current.ssts.all_files()
        assert metas and all(f.index_file is not None for f in metas)
        # sidecars of compacted-away inputs are deleted with their SSTs
        names = {f.index_file for f in metas} | \
            {f.file_name for f in metas}
        region.purger.sweep() if region.purger else None
        sd = region.series_dict
        assert _rows_for(region, sd.sids_for_tag_values(0, ["h2"])) == \
            _full_rows(region, {"h2"})
        assert names  # compaction preserved index coverage


# ---------------------------------------------------------------------------
# SQL-level differential: index-on == index-off across predicate shapes
# ---------------------------------------------------------------------------

@pytest.fixture()
def frontend(tmp_path):
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    dn = DatanodeInstance(DatanodeOptions(data_home=str(tmp_path),
                                          register_numbers_table=False))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    yield fe
    fe.shutdown()


def _rows(out) -> list:
    return sorted(tuple(r) for b in out.batches for r in b.rows())


class TestSqlDifferential:
    QUERIES = [
        # point
        "SELECT host, max(v) FROM d WHERE host = 'h2' GROUP BY host",
        # IN
        "SELECT host, count(v) FROM d WHERE host IN ('h1', 'h3') "
        "GROUP BY host",
        # != is EXCLUDED from sid derivation (near-total set) but must
        # answer identically
        "SELECT host, sum(v) FROM d WHERE host != 'h2' GROUP BY host",
        # mixed tag + time
        "SELECT host, avg(v) FROM d WHERE host = 'h4' AND ts >= 3000 "
        "AND ts < 9000 GROUP BY host",
        # point + IN + range conjuncts together (sid sets intersect)
        "SELECT host, min(v) FROM d WHERE host IN ('h2', 'h4') "
        "AND host = 'h2' AND v >= 0 GROUP BY host",
        # never-seen value: provably empty
        "SELECT host, max(v) FROM d WHERE host = 'zzz' GROUP BY host",
        # raw row SELECT through the fallback path
        "SELECT host, ts, v FROM d WHERE host = 'h3' ORDER BY ts",
    ]

    def _setup(self, fe, ctx):
        fe.do_query("CREATE TABLE d (host STRING, ts TIMESTAMP "
                    "TIME INDEX, v DOUBLE, PRIMARY KEY(host))", ctx)
        ts = 0
        for batch in (("h1", "h4"), ("h2", "h4"), ("h3", "h4")):
            vals = []
            for i in range(6):
                h = batch[i % 2]
                vals.append(f"('{h}', {(ts + i) * 1000}, {ts + i}.5)")
            fe.do_query(f"INSERT INTO d VALUES {', '.join(vals)}", ctx)
            fe.do_query("ADMIN FLUSH TABLE d", ctx)
            ts += 6
        # an overwrite in a fourth file so kept files need dedup
        fe.do_query("INSERT INTO d VALUES ('h2', 7000, 123.5)", ctx)
        fe.do_query("ADMIN FLUSH TABLE d", ctx)

    def test_on_off_answers_identical(self, frontend):
        from greptimedb_tpu.storage import scan_cache
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        self._setup(frontend, ctx)
        frontend.do_query("SET tpu_dispatch_min_rows = 1", ctx)
        try:
            for q in self.QUERIES:
                answers = {}
                for on in (1, 0):
                    frontend.do_query(f"SET sst_index = {on}", ctx)
                    scan_cache.SCAN_CACHE._entries.clear()
                    answers[on] = _rows(frontend.do_query(q, ctx)[-1])
                assert answers[1] == answers[0], q
        finally:
            frontend.do_query("SET sst_index = 1", ctx)
            frontend.do_query("SET tpu_dispatch_min_rows = 131072", ctx)

    def test_streamed_cold_differential(self, frontend, monkeypatch):
        """The streamed cold path threads the sid set through every
        slice (and the lean chunk reader): answers must match index-off
        with the same threshold. region_point_sids is pinned to None so
        the stream path itself (not the indexed-point route that would
        otherwise win) consumes the sid set."""
        from greptimedb_tpu.query import stream_exec, tpu_exec
        from greptimedb_tpu.storage import scan_cache
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        self._setup(frontend, ctx)
        frontend.do_query("SET tpu_dispatch_min_rows = 1", ctx)
        saved = stream_exec.stream_threshold_rows()
        stream_exec.configure_streaming(threshold_rows=1)
        monkeypatch.setattr(tpu_exec, "region_point_sids",
                            lambda region, plan: None)
        try:
            for q in self.QUERIES[:5]:
                answers = {}
                for on in (1, 0):
                    frontend.do_query(f"SET sst_index = {on}", ctx)
                    scan_cache.SCAN_CACHE._entries.clear()
                    answers[on] = _rows(frontend.do_query(q, ctx)[-1])
                assert answers[1] == answers[0], q
        finally:
            stream_exec.configure_streaming(threshold_rows=saved)
            frontend.do_query("SET sst_index = 1", ctx)
            frontend.do_query("SET tpu_dispatch_min_rows = 131072", ctx)

    def test_explain_analyze_reports_index_prune(self, frontend):
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        self._setup(frontend, ctx)
        frontend.do_query("SET tpu_dispatch_min_rows = 1", ctx)
        try:
            out = frontend.do_query(
                "EXPLAIN ANALYZE SELECT host, max(v) FROM d "
                "WHERE host = 'h2' GROUP BY host", ctx)[-1]
            text = "\n".join(str(r) for b in out.batches
                             for r in b.rows())
            assert "index_files_pruned" in text
            assert "indexed-point" in text
        finally:
            frontend.do_query("SET tpu_dispatch_min_rows = 131072", ctx)

    def test_promql_selector_differential(self, frontend):
        """The PromQL cold selector path resolves equality matchers to
        sid sets; answers must match the index-off run."""
        from greptimedb_tpu.query import stream_exec
        from greptimedb_tpu.storage import scan_cache
        from greptimedb_tpu.session import QueryContext
        ctx = QueryContext()
        self._setup(frontend, ctx)
        saved = stream_exec.stream_threshold_rows()
        stream_exec.configure_streaming(threshold_rows=1)  # force cold
        try:
            answers = {}
            for on in (1, 0):
                frontend.do_query(f"SET sst_index = {on}", ctx)
                scan_cache.SCAN_CACHE._entries.clear()
                out = frontend.do_query(
                    "TQL EVAL (0, 30, '5s') d{host=\"h2\"}", ctx)[-1]
                answers[on] = _rows(out)
            assert answers[1] == answers[0]
            assert answers[1], "selector returned nothing"
        finally:
            stream_exec.configure_streaming(threshold_rows=saved)
            frontend.do_query("SET sst_index = 1", ctx)
