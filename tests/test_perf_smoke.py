"""Perf-smoke: the bulk_ingest stage profiler end to end on ~1M rows.

Slow-marked so tier-1 stays inside its timeout; speeds are the
benchmark's (`benchmark/run.py`) — this test only asserts the
profiling machinery the ingest stage breakdown is built from keeps working
(stages present, times positive, rows counted, merge() accumulates).
"""

import shutil
import tempfile

import numpy as np
import pytest

pytestmark = pytest.mark.slow


@pytest.mark.slow
def test_bulk_ingest_stage_profile_end_to_end():
    from greptimedb_tpu.datanode.instance import (DatanodeInstance,
                                                  DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    from greptimedb_tpu.storage.region import IngestProfile

    tmpdir = tempfile.mkdtemp(prefix="perfsmoke-")
    fe = None
    try:
        dn = DatanodeInstance(DatanodeOptions(
            data_home=tmpdir, register_numbers_table=False))
        dn.start()
        fe = FrontendInstance(dn)
        fe.start()
        fe.do_query("CREATE TABLE cpu (hostname STRING, ts TIMESTAMP "
                    "TIME INDEX, usage_user DOUBLE, "
                    "PRIMARY KEY(hostname))")
        table = fe.catalog.table("greptime", "public", "cpu")
        region = next(iter(table.regions.values()))
        assert region.last_ingest_profile is None

        rng = np.random.default_rng(0)
        hosts = 200
        per = 1_000_000 // hosts
        total = IngestProfile()
        for batch_no in range(2):
            ts = np.tile(np.arange(per, dtype=np.int64) * 1_000
                         + batch_no * per * 1_000, hosts)
            host = np.repeat(
                np.array([f"host_{i}" for i in range(hosts)]),
                per).astype(object)
            n = table.bulk_load({
                "hostname": host, "ts": ts,
                "usage_user": rng.random(len(ts)) * 100})
            assert n == hosts * per
            prof = region.last_ingest_profile
            assert prof is not None
            assert prof.rows == hosts * per
            assert prof.total_s > 0
            assert prof.mrows_per_s() > 0
            # the stages the BASELINE breakdown publishes
            for stage in ("coerce", "series_encode", "sort_check",
                          "field_prep", "chunk_plan", "sst_write",
                          "manifest"):
                assert stage in prof.stages, stage
                assert prof.stages[stage] >= 0
            # stage times must account for (almost all of) the wall:
            # a profiler that loses a stage under-reports forever
            assert sum(prof.stages.values()) >= prof.total_s * 0.8
            total.merge(prof)

        assert total.rows == 2 * hosts * per
        assert total.total_s > 0
        desc = total.describe()
        assert "sst_write" in desc and "Mrows/s" in desc

        # the profiled load must be queryable (the profiler must not
        # perturb the write path)
        out = fe.do_query("SELECT count(*) FROM cpu")
        if isinstance(out, list):
            out = out[0]
        batch = out.batches[0] if out.batches else None
        assert batch is not None
        assert batch.column(0).data[0] == 2 * hosts * per
    finally:
        if fe is not None:
            fe.shutdown()
        shutil.rmtree(tmpdir, ignore_errors=True)
