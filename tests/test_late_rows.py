"""Rows that arrive late or twice (ISSUE 42): a relay's queue drains behind
the live ticks, a proxy re-sends a body whose acknowledgement was lost.
The scan cache (`scan_cache._ScanCache`) puts a late row into the tail
wherever its time lies and drops a re-sent one, at the cost of the delta
and the tail; a statement after such a write answers as a from-scratch
reference does, and meets no merge and no compile.

The reference shares nothing with the cache: every row ever acknowledged,
in the order it was written, `drop_duplicates(keep="last")` on (series,
time), then the statement's aggregate in float64 pandas. The table is TSBS
`cpu-only`'s shape at a small size (hostname + region in the key, three
DOUBLE fields, 10 s ticks, three loaded hours) with an hour missing for
eight hosts inside the load, and the statements are the families of
`tsbs4k-backfill-while-read` (`double-groupby-1`, `lastpoint`,
`cpu-max-all-8`) and `single-groupby-1-1-1`.
"""

import numpy as np
import pandas as pd
import pytest

from greptimedb_tpu.common import telemetry
from greptimedb_tpu.datanode.instance import DatanodeInstance, DatanodeOptions
from greptimedb_tpu.frontend.instance import FrontendInstance
from greptimedb_tpu.ops.kernels import _sorted_grouped_aggregate_pre
from greptimedb_tpu.query import scan_narrow, tpu_exec
from greptimedb_tpu.storage import scan_cache

TICK_MS, HOUR = 10_000, 3_600_000
T0 = 472_223 * HOUR                         # a whole hour
TICKS, EXTRA = 1080, 60                     # three loaded hours, then live
LATE = [3, 7, 11, 19, 23, 29, 31, 40]       # the hosts behind the relay
GAP = range(540, 900)                       # their hour, missing in the load
FIELDS = ("usage_user", "usage_system", "usage_idle")
RTOL = 1e-5                                 # f32 mirrors (PERF.md section 6)
RESIDENT = "device-resident (scan cache)"


def metric(name: str, **labels) -> float:
    counter = telemetry._counters.get(name)
    if counter is None:
        return 0.0
    child = counter.labels(**labels) if labels else counter
    return child._value.get()


def ts(k: int) -> int:
    return T0 + int(k) * TICK_MS


def hostname(h: int) -> str:
    return f"host_{h:03d}"


class Fleet:
    """One frontend over one data_home, the load, and the log of every
    write in the order it was acknowledged."""

    def __init__(self, data_home: str, hosts: int, seed: int = 42):
        self.data_home, self.hosts = data_home, hosts
        rng = np.random.default_rng(seed)
        #: data[tick, host, field], as the benchmark's generators have it
        self.data = np.round(rng.uniform(0.0, 100.0, (
            TICKS + EXTRA, hosts, len(FIELDS))), 4)
        self.log = []           # (host, tick, values or None for a delete)
        self.open()
        self.fe.do_query(
            "CREATE TABLE cpu (hostname STRING, region STRING, "
            "ts TIMESTAMP TIME INDEX, " + ", ".join(
                f"{f} DOUBLE" for f in FIELDS)
            + ", PRIMARY KEY(hostname, region))")
        for h in range(hosts):
            self.put([(h, k) for k in range(TICKS)
                      if h not in LATE or k not in GAP])

    def open(self):
        self.fe = FrontendInstance(DatanodeInstance(DatanodeOptions(
            data_home=self.data_home, register_numbers_table=False)))
        self.fe.start()

    def close(self):
        self.fe.do_query("SET tpu_dispatch_min_rows = 131072")
        self.fe.shutdown()

    def restart(self):
        """Down without a flush and up again: the WAL is replayed."""
        self.fe.shutdown()
        self.open()

    @property
    def table(self):
        return self.fe.catalog.table("greptime", "public", "cpu")

    def region(self):
        return next(iter(self.table.regions.values()))

    # ---- writes, each logged ---------------------------------------------
    def put(self, cells, changed: float = 0.0):
        """One write of the rows (host, tick); `changed` is added to every
        value (an overwrite that is no retry)."""
        cells = list(cells)
        values = np.array([self.data[k, h] for h, k in cells]) + changed
        self.log += [(h, k, tuple(v)) for (h, k), v in zip(cells, values)]
        columns = {"hostname": [hostname(h) for h, _ in cells],
                   "region": [f"r{h % 3}" for h, _ in cells],
                   "ts": [ts(k) for _, k in cells]}
        for j, f in enumerate(FIELDS):
            columns[f] = values[:, j].tolist()
        self.table.insert(columns)

    def delete(self, cells):
        for h, k in cells:
            self.log.append((h, k, None))
            self.fe.do_query(
                f"DELETE FROM cpu WHERE hostname = '{hostname(h)}' AND "
                f"region = 'r{h % 3}' AND ts = {ts(k)}")

    def flush(self):
        self.fe.do_query("ADMIN FLUSH TABLE cpu")

    # ---- the plain reference -----------------------------------------------
    def rows(self) -> pd.DataFrame:
        """Every row ever acknowledged in sequence order, the newest of a
        (series, time) kept, a tombstone dropped."""
        log = pd.DataFrame({"h": [e[0] for e in self.log],
                            "k": [e[1] for e in self.log],
                            "v": [e[2] for e in self.log]})
        log = log.drop_duplicates(["h", "k"], keep="last")
        log = log[log.v.notna()].sort_values(["h", "k"])
        out = pd.DataFrame({"h": log.h.to_numpy(), "k": log.k.to_numpy(),
                            "hostname": [hostname(h) for h in log.h],
                            "ts": [ts(k) for k in log.k]})
        for j, f in enumerate(FIELDS):
            out[f] = [v[j] for v in log.v]
        return out

    # ---- statements ---------------------------------------------------------
    floor = 1

    def sql(self, sql: str) -> pd.DataFrame:
        self.fe.do_query(f"SET tpu_dispatch_min_rows = {self.floor}")
        out = self.fe.do_query(sql)
        out = out[-1] if isinstance(out, list) else out
        frames = [pd.DataFrame(b.to_pydict()) for b in out.batches]
        return pd.concat(frames, ignore_index=True) if frames else \
            pd.DataFrame()

    def stages(self, sql: str) -> dict:
        rows = self.sql("EXPLAIN ANALYZE " + sql)
        return {r.stage: r.detail or "" for r in rows.itertuples()}


# ---------------------------------------------------------------------------
# the families, each with its float64 reference over `Fleet.rows`
# ---------------------------------------------------------------------------

def _ms(col: pd.Series) -> pd.Series:
    return col.astype("int64") // (1 if col.dtype.kind in "iu" else 10**6)


def in_list(hosts) -> str:
    return ", ".join(f"'{hostname(h)}'" for h in hosts)


class DoubleGroupby1:
    """avg of one metric by hostname and hour over the loaded hours, the
    gap included: the full launch over base and tail."""
    name = "double-groupby-1"
    sql = (f"SELECT hostname, date_bin(INTERVAL '1 hour', ts) AS hour, "
           f"avg(usage_user) AS a FROM cpu WHERE ts >= {ts(0)} AND "
           f"ts < {ts(TICKS)} GROUP BY hostname, hour")

    @staticmethod
    def want(rows):
        rows = rows[rows.k < TICKS]
        return rows.assign(hour=rows.ts // HOUR * HOUR).groupby(
            ["hostname", "hour"]).usage_user.mean().to_dict()

    @staticmethod
    def got(out):
        return dict(zip(zip(out.hostname, _ms(out.hour)), out.a))


class LastPoint:
    """last(usage_user) of every host: a late row is never it."""
    name = "lastpoint"
    sql = "SELECT hostname, last(usage_user) AS l FROM cpu GROUP BY hostname"

    @staticmethod
    def want(rows):
        return rows.sort_values("ts").groupby(
            "hostname").usage_user.last().to_dict()

    @staticmethod
    def got(out):
        return dict(zip(out.hostname, out.l))


class FirstPoint:
    """first(usage_user) of every host: a late row is it where it is the
    earliest."""
    name = "firstpoint"
    sql = "SELECT hostname, first(usage_user) AS f FROM cpu GROUP BY hostname"

    @staticmethod
    def want(rows):
        return rows.sort_values("ts").groupby(
            "hostname").usage_user.first().to_dict()

    @staticmethod
    def got(out):
        return dict(zip(out.hostname, out.f))


class CpuMaxAll8:
    """max of every metric by hour for the eight late hosts: the narrowed
    launch over base ranges with a hole and tail ranges that fill it."""
    name = "cpu-max-all-8"
    sql = ("SELECT date_bin(INTERVAL '1 hour', ts) AS hour, " + ", ".join(
        f"max({f}) AS m{j}" for j, f in enumerate(FIELDS))
        + f" FROM cpu WHERE hostname IN ({in_list(LATE)}) AND ts >= {ts(0)}"
        f" AND ts < {ts(TICKS)} GROUP BY hour")

    @staticmethod
    def want(rows):
        rows = rows[rows.h.isin(LATE) & (rows.k < TICKS)]
        g = rows.assign(hour=rows.ts // HOUR * HOUR).groupby("hour")
        return {hour: tuple(r) for hour, r in
                g[list(FIELDS)].max().iterrows()}

    @staticmethod
    def got(out):
        return {hour: tuple(r) for hour, r in zip(
            _ms(out.hour), out[["m0", "m1", "m2"]].itertuples(index=False))}


class SingleGroupby111:
    """max of one metric of one late host by minute over the gap's hour."""
    name = "single-groupby-1-1-1"
    sql = (f"SELECT date_bin(INTERVAL '1 minute', ts) AS minute, "
           f"max(usage_user) AS m FROM cpu WHERE hostname IN "
           f"({in_list(LATE[:1])}) AND ts >= {ts(GAP[0])} AND "
           f"ts < {ts(GAP[-1] + 1)} GROUP BY minute")

    @staticmethod
    def want(rows):
        rows = rows[(rows.h == LATE[0]) & rows.k.isin(GAP)]
        return rows.assign(minute=rows.ts // 60_000 * 60_000).groupby(
            "minute").usage_user.max().to_dict()

    @staticmethod
    def got(out):
        return dict(zip(_ms(out.minute), out.m)) if len(out) else {}


FAMILIES = (DoubleGroupby1, LastPoint, FirstPoint, CpuMaxAll8,
            SingleGroupby111)


def check(fleet: Fleet, families=FAMILIES):
    rows = fleet.rows()
    for fam in families:
        want, got = fam.want(rows), fam.got(fleet.sql(fam.sql))
        assert set(got) == set(want), (fam.name, len(got), len(want))
        keys = sorted(want)
        np.testing.assert_allclose(
            np.array([got[k] for k in keys], dtype=float),
            np.array([want[k] for k in keys], dtype=float),
            rtol=RTOL, atol=1e-6, err_msg=fam.name)


@pytest.fixture
def fleet(tmp_path):
    f = Fleet(str(tmp_path), hosts=48)
    yield f
    f.close()


def appended(f: Fleet, ticks=range(TICKS, TICKS + 3)):
    f.put([(h, k) for k in ticks for h in range(f.hosts)])


def backlog(f: Fleet, ticks):
    """The relay's queue, drained tick by tick."""
    f.put([(h, k) for k in ticks for h in LATE])


# ---------------------------------------------------------------------------
# a seeded series of deltas, every family after each
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [7, 2147483659])
def test_every_family_answers_the_reference_after_each_delta(fleet, seed):
    """Appends, late rows before a series' last base row, equal and
    changed overwrites of base and of tail rows, in a seeded order and in
    bodies that mix them: the served path against the plain reference
    after each, and only a changed base row merges."""
    rng = np.random.default_rng(seed)
    check(fleet)
    base = scan_cache.SCAN_CACHE.get_parts(fleet.region())[0]
    live, queue = TICKS, list(GAP)
    steps = ["append", "late", "resend-base", "resend-tail", "mixed",
             "change-tail", "late", "mixed", "append", "resend-tail"]
    order = rng.permutation(len(steps)).tolist()
    written_late = []
    for step in ["append", "late"] + [steps[i] for i in order]:
        merges = metric("scan_cache_merges")
        if step == "append":
            appended(fleet, range(live, live + 2))
            live += 2
        elif step == "late":
            n = int(rng.integers(3, 40))
            backlog(fleet, queue[:n])
            written_late += queue[:n]
            queue = queue[n:]
        elif step == "resend-base":
            # the tick before the gap and a few loaded rows, as they are
            fleet.put([(h, GAP[0] - 1) for h in LATE]
                      + [(int(h), int(k)) for h, k in zip(
                          rng.integers(0, fleet.hosts, 20),
                          rng.integers(0, GAP[0], 20))])
        elif step == "resend-tail":
            fleet.put([(h, k) for k in written_late[-5:] for h in LATE[:4]]
                      + [(h, live - 1) for h in range(0, fleet.hosts, 3)])
        elif step == "change-tail":
            fleet.put([(LATE[2], written_late[0]), (5, live - 1)],
                      changed=1.5)
        elif step == "mixed":
            # one body: live rows, queue rows, a re-sent row of each kind
            n = int(rng.integers(2, 10))
            fleet.put([(h, live) for h in range(fleet.hosts)]
                      + [(h, k) for k in queue[:n] for h in LATE]
                      + [(LATE[0], GAP[0] - 1), (LATE[1], written_late[0])])
            live += 1
            written_late += queue[:n]
            queue = queue[n:]
        check(fleet)
        now, tail = scan_cache.SCAN_CACHE.get_parts(fleet.region())
        assert now is base and tail is not None, step
        assert metric("scan_cache_merges") == merges, step
    # a row that changes a value the base holds: the one merge
    merges = metric("scan_cache_merges")
    fleet.put([(9, 100), (LATE[3], GAP[0] - 1)], changed=-2.25)
    check(fleet)
    now, tail = scan_cache.SCAN_CACHE.get_parts(fleet.region())
    assert now is not base and tail is None
    assert metric("scan_cache_merges") == merges + 1


def test_a_late_row_and_a_retry_are_counted_and_said(fleet):
    check(fleet)
    late, equal, changed = (metric("scan_cache_late_rows"),
                            metric("scan_cache_overwrites", kind="equal"),
                            metric("scan_cache_overwrites", kind="changed"))
    # one body: a tick of the queue, the tick before the gap re-sent
    fleet.put([(h, GAP[0]) for h in LATE] + [(h, GAP[0] - 1) for h in LATE])
    stages = fleet.stages(DoubleGroupby1.sql)
    assert stages["dispatch"] == RESIDENT
    assert "cache=incremental" in stages["scan_prep"]
    apply = stages["scan_prep.apply"]
    assert "late=8" in apply and "equal_dropped=8" in apply \
        and "changed=0" in apply, apply
    assert "merged" not in apply
    assert "tail_rows=8" in stages["reduce"], stages["reduce"]
    assert "tail_span=history" in stages["reduce"]
    assert metric("scan_cache_late_rows") == late + 8
    assert metric("scan_cache_overwrites", kind="equal") == equal + 8
    # a retry alone writes nothing: the tail stays the object it was
    tail = scan_cache.SCAN_CACHE.get_parts(fleet.region())[1]
    fleet.put([(h, GAP[0]) for h in LATE[:3]])
    apply = fleet.stages(DoubleGroupby1.sql)["scan_prep.apply"]
    assert "late=0" in apply and "equal_dropped=3" in apply, apply
    assert scan_cache.SCAN_CACHE.get_parts(fleet.region())[1] is tail
    # appended rows leave the tail's span where it was said to reach
    appended(fleet)
    reduce = fleet.stages(LastPoint.sql)["reduce"]
    assert "tail_span=history" in reduce and "tail_rows=" in reduce
    assert metric("scan_cache_overwrites", kind="changed") == changed
    check(fleet)


def test_last_is_never_a_late_row_and_first_is_where_it_is_earlier(tmp_path):
    """A host whose first loaded hour is missing: its late rows are the
    earliest it has, so `first` is one of them, and `last` stays with the
    newest live row whatever arrives."""
    f = Fleet(str(tmp_path), hosts=12)
    try:
        h = 5
        first = f.sql(FirstPoint.sql).set_index("hostname").f
        last = f.sql(LastPoint.sql).set_index("hostname").l
        appended(f)
        f.put([(LATE[0], k) for k in (GAP[0] + 4, GAP[0])])
        got_last = f.sql(LastPoint.sql).set_index("hostname").l
        assert got_last[hostname(LATE[0])] == pytest.approx(
            f.data[TICKS + 2, LATE[0], 0], rel=RTOL)
        assert f.sql(FirstPoint.sql).set_index("hostname").f.equals(first)
        # rows before every loaded row of a host: ticks -3..-1
        early = np.round(np.random.default_rng(1).uniform(0, 100, (
            3, len(FIELDS))), 4)
        f.log += [(h, -3 + j, tuple(early[j])) for j in range(3)]
        f.table.insert(dict(
            {"hostname": [hostname(h)] * 3, "region": [f"r{h % 3}"] * 3,
             "ts": [ts(-3 + j) for j in range(3)]},
            **{name: early[:, j].tolist() for j, name in enumerate(FIELDS)}))
        got_first = f.sql(FirstPoint.sql).set_index("hostname").f
        assert got_first[hostname(h)] == pytest.approx(early[0, 0], rel=RTOL)
        assert got_first.drop(hostname(h)).equals(first.drop(hostname(h)))
        assert f.sql(LastPoint.sql).set_index("hostname").l.equals(got_last)
        assert not got_last.equals(last)
        check(f, (LastPoint, FirstPoint, CpuMaxAll8))
    finally:
        f.close()


# ---------------------------------------------------------------------------
# no merge and no compile: a table past the dispatch floor
# ---------------------------------------------------------------------------

def compiled() -> tuple:
    return (_sorted_grouped_aggregate_pre._cache_size(),
            scan_narrow._narrow_reduce._cache_size())


def test_late_and_resent_rows_meet_no_merge_and_no_compile(tmp_path):
    """The warm statements come before any write and compile, beside
    their own programs, those of the base's tail (a closed range's too:
    its stand-in lies inside the range). After them a tick of every host,
    late rows before their series' last base row, a retry of a base row
    and one of a tail row launch what was compiled."""
    f = Fleet(str(tmp_path), hosts=128)
    f.floor = 131072            # the floor as it is: 138,240 - gap rows
    try:
        assert f.region() is not None
        families = (DoubleGroupby1, LastPoint, CpuMaxAll8, SingleGroupby111)
        for _ in range(2):
            check(f, families)
        base = scan_cache.SCAN_CACHE.get_parts(f.region())[0]
        assert base.num_rows >= tpu_exec.TPU_DISPATCH_MIN_ROWS
        assert len(base.tail_programs) == len(families)
        programs, merges = compiled(), metric("scan_cache_merges")
        misses = metric("scan_cache_miss")
        writes = [
            ("a tick of every host", lambda: appended(f, [TICKS])),
            ("late rows", lambda: backlog(f, GAP[:6])),
            ("a retry of base rows",
             lambda: f.put([(h, GAP[0] - 1) for h in LATE])),
            ("a retry of tail rows",
             lambda: f.put([(h, GAP[2]) for h in LATE]
                           + [(h, TICKS) for h in range(40)])),
            ("a body of all of them", lambda: f.put(
                [(h, TICKS + 1) for h in range(f.hosts)]
                + [(h, k) for k in GAP[6:9] for h in LATE]
                + [(LATE[0], GAP[0] - 1), (LATE[1], GAP[1])])),
            ("a changed tail row",
             lambda: f.put([(LATE[1], GAP[1])], changed=3.0)),
        ]
        for what, write in writes:
            write()
            check(f, families)
            assert compiled() == programs, f"{what} met a new program"
            assert metric("scan_cache_merges") == merges, what
            assert metric("scan_cache_miss") == misses, what
        now, tail = scan_cache.SCAN_CACHE.get_parts(f.region())
        assert now is base
        assert tail.valid_rows == 2 * f.hosts + 9 * len(LATE)
    finally:
        f.close()


# ---------------------------------------------------------------------------
# what still merges, and the other roads a late row takes
# ---------------------------------------------------------------------------

def test_a_changed_overwrite_is_right_whichever_way_it_goes(fleet):
    check(fleet)
    appended(fleet)
    backlog(fleet, GAP[:4])
    check(fleet)
    base = scan_cache.SCAN_CACHE.get_parts(fleet.region())[0]
    merges = metric("scan_cache_merges")
    changed = metric("scan_cache_overwrites", kind="changed")
    # of tail rows (a live one, a late one): replaced in the tail
    fleet.put([(2, TICKS + 1), (LATE[0], GAP[1])], changed=7.0)
    check(fleet)
    assert scan_cache.SCAN_CACHE.get_parts(fleet.region())[0] is base
    assert metric("scan_cache_merges") == merges
    assert metric("scan_cache_overwrites", kind="changed") == changed + 2
    # of a base row, in a body that also carries late and re-sent rows
    fleet.put([(4, 17)], changed=-1.0)
    backlog(fleet, GAP[4:6])
    fleet.put([(LATE[0], GAP[1])], changed=7.0)         # now a retry
    apply = fleet.stages(LastPoint.sql)["scan_prep.apply"]
    assert "changed=1" in apply and "merged=1" in apply, apply
    now, tail = scan_cache.SCAN_CACHE.get_parts(fleet.region())
    assert now is not base and tail is None
    assert metric("scan_cache_merges") == merges + 1
    check(fleet)


def test_a_delete_still_merges_and_is_right(fleet):
    check(fleet)
    backlog(fleet, GAP[:5])
    check(fleet)
    merges = metric("scan_cache_merges")
    fleet.delete([(LATE[0], GAP[2]), (6, 3), (LATE[1], GAP[0] - 1)])
    backlog(fleet, GAP[5:7])
    check(fleet)
    assert metric("scan_cache_merges") > merges
    assert scan_cache.SCAN_CACHE.get_parts(fleet.region())[1] is None
    # and the deleted late row written again is a row again
    fleet.put([(LATE[0], GAP[2])])
    check(fleet)


def test_a_plan_that_wants_one_scan_merges_the_late_rows(tmp_path):
    """A lowered `rate` (a window's growth) reduces over one sorted scan:
    tail and base merge, counted, and the late samples are in their
    windows."""
    import test_read_while_ingest as rwi
    db = rwi.Db(str(tmp_path))
    try:
        rwi.warm(db)
        rwi.appended(db)
        rwi.older_rows(db)          # ticks 4..9 of h05, before its first
        rwi.check_sql(db, rwi.FULL)
        merges = metric("scan_cache_merges")
        rwi.check_promql(db, rwi.H05_FIRST + 62)   # windows the rows lie in
        assert metric("scan_cache_merges") > merges
        rwi.check_all(db, rwi.TICKS + 4)
    finally:
        db.close()


def test_late_rows_met_in_a_flushed_sst(fleet):
    """The delta read from a file that overlaps older files in time
    (`_ScanCache._delta`'s second branch)."""
    fleet.flush()               # the load leaves the memtables
    check(fleet)
    base = scan_cache.SCAN_CACHE.get_parts(fleet.region())[0]
    merges = metric("scan_cache_merges")
    appended(fleet)
    backlog(fleet, GAP[:7])
    fleet.put([(h, GAP[0] - 1) for h in LATE])          # re-sent
    fleet.put([(h, GAP[3]) for h in LATE])              # re-sent, late
    fleet.flush()
    assert not any(mt.num_rows for mt in
                   fleet.region().snapshot()._version
                   .memtables.all_memtables())
    stages = fleet.stages(DoubleGroupby1.sql)
    assert "late=56" in stages["scan_prep.apply"], stages["scan_prep.apply"]
    assert "equal_dropped=8" in stages["scan_prep.apply"]
    check(fleet)
    assert scan_cache.SCAN_CACHE.get_parts(fleet.region())[0] is base
    assert metric("scan_cache_merges") == merges
    # and across a second flush, met half in a file and half in a memtable
    backlog(fleet, GAP[7:9])
    fleet.flush()
    backlog(fleet, GAP[9:12])
    check(fleet)
    assert metric("scan_cache_merges") == merges


def test_late_and_resent_rows_after_a_restart(fleet):
    """Written, acknowledged, never flushed: the WAL's replay brings the
    late rows and the retries back in their order, and they count once."""
    appended(fleet)
    backlog(fleet, GAP[:10])
    fleet.put([(h, GAP[0] - 1) for h in LATE])
    fleet.put([(h, GAP[4]) for h in LATE])
    fleet.put([(LATE[2], GAP[5])], changed=2.0)
    check(fleet)
    per_tick = (f"SELECT date_bin(INTERVAL '10 second', ts) AS tick, "
                f"count(*) AS n FROM cpu WHERE ts >= {ts(GAP[0] - 1)} AND "
                f"ts < {ts(GAP[12])} GROUP BY tick")
    before = fleet.sql(per_tick).sort_values("tick").n.tolist()
    assert before == [fleet.hosts] * 11 + [fleet.hosts - len(LATE)] * 2
    fleet.restart()
    check(fleet)
    assert fleet.sql(per_tick).sort_values("tick").n.tolist() == before
    backlog(fleet, GAP[10:12])
    check(fleet)


def test_closed_history_the_late_rows_do_not_reach_is_still_a_hit(fleet):
    """A statement whose range ends before the gap: every unmerged row
    lies at or after it, so the entry answers as it stands."""
    closed = (f"SELECT hostname, avg(usage_user) AS a FROM cpu WHERE "
              f"ts >= {ts(0)} AND ts < {ts(GAP[0] - 1)} GROUP BY hostname")

    def check_closed():
        rows = fleet.rows()
        want = rows[rows.k < GAP[0] - 1].groupby(
            "hostname").usage_user.mean()
        got = fleet.sql(closed).set_index("hostname").a
        np.testing.assert_allclose(got[want.index].to_numpy(),
                                   want.to_numpy(), rtol=RTOL)

    fleet.flush()
    check(fleet)
    appended(fleet)
    backlog(fleet, GAP[:5])
    fleet.put([(h, GAP[0] - 1) for h in LATE])
    refreshes = metric("scan_cache_incremental")
    stages = fleet.stages(closed)
    assert "cache=hit" in stages["scan_prep"], stages["scan_prep"]
    assert "tail" not in stages["reduce"]
    check_closed()
    assert metric("scan_cache_incremental") == refreshes
    # the statement that reaches them refreshes and reads the tail
    stages = fleet.stages(DoubleGroupby1.sql)
    assert "cache=incremental" in stages["scan_prep"]
    assert "tail_span=history" in stages["reduce"]
    # now the tail reaches into the closed range's table, not its range
    stages = fleet.stages(closed)
    assert "tail=skipped" in stages["reduce"], stages["reduce"]
    check_closed()
    check(fleet)
