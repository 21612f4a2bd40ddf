"""Unit tests of `tsbs4k-backfill-while-read`'s own pieces, no server and
no chip (seconds), and its whole-run controls on the CPU backend at the
debug size (a minute each; `selftest.py`'s `PERTURBATIONS` is keyed by
loop kind and does not know `backfill`, so they run from here).

    python3 -m pytest benchmark/test_backfill.py -q

What the mix sends (the families, what a body carries, the order in which
the relay's queue is drained, which bodies go out twice), what the load
leaves out, and the bounds of a backlog answer on synthetic records: an
answer that lacks an acknowledged row, shows one that no body carried yet,
half a body or the hosts at different states, or counts a row twice is not
correct; one that shows the bodies in flight in either order is.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from benchlib import check as chk  # noqa: E402
from benchlib.spec import (Cell, load_family, load_generator,  # noqa: E402
                           load_loop)

CELL = "tsbs4k-backfill-while-read"
LOOP = load_loop("backfill")            # one load: one module's globals
BACKFILL = LOOP.prepare.__globals__
SEED = 2147483777
EXTRA = 200        # live ticks: enough bodies to drain the debug queue


@pytest.fixture(scope="module")
def cell():
    return Cell(CELL)


@pytest.fixture(scope="module")
def ds(cell):
    """The debug size with 200 live ticks: 400 hosts, 2 h loaded, 40
    late hosts whose hour ends half an hour before the load's end."""
    size = cell.config["debug"]
    return load_generator(cell.config)(
        cell.config, SEED, extra_ticks=EXTRA, scale=size["scale"],
        ticks=size["duration_s"] // cell.config["log_interval_s"])


# ---------------------------------------------------------------------------
# the deployment and what the mix sends
# ---------------------------------------------------------------------------

def test_the_configuration_is_the_durable_one_with_an_outage(cell):
    durable = Cell("tsbs4k-read-while-ingest").config
    config = cell.config
    for key in ("scale", "log_interval_s", "duration_s", "start", "table",
                "time_index", "tags", "fields", "primary_key", "deployment",
                "server_options", "load_chunk_ticks", "reduced"):
        assert config[key] == durable[key], key
    for key, text in durable["guarantees"].items():
        assert config["guarantees"][key] == text
    assert "late or twice is one row" in \
        config["guarantees"]["late_and_repeated_rows"]
    assert config["generator"] == "tsbs-cpu-outage"
    assert config["outage"] == {
        "late_hosts": 400, "gap_s": 3600, "gap_ends_before_load_end_s": 1800,
        "late_rows_per_body": 500, "resend_every": 25}
    assert config["assumed"][:len(durable["assumed"])] == durable["assumed"]
    entry = next(c for c in cell.benchmark["configs"]
                 if c["name"] == "tsbs-cpu-4000-outage")
    assert entry["reduced"] == ["duration_s"] == list(config["reduced"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200


def test_the_mix_is_read_while_ingest_with_a_third_statement(cell):
    rwi = Cell("tsbs4k-read-while-ingest").mix
    mix = cell.mix
    assert mix["loop"] == "backfill" and cell.chips == 1
    assert mix["families"] == ["double-groupby-1-backfill", "lastpoint-live",
                               "cpu-max-all-8-backfill"]
    for key in ("clients", "warm_statements", "precision", "batch_rows",
                "workers", "prefill_batches", "extra_ticks",
                "max_body_bytes", "sum_tolerance", "dispatch", "reports"):
        assert mix[key] == rwi[key], key
    assert mix["late_statement_limit_s"] == 10.0
    reports = {m["name"] for m in cell.metrics("end_to_end")}
    assert reports == {"stmt_geomean_ms", "stmt_per_s", "ingest_rows_per_s",
                       "setup_s"}
    layers = {m["name"] for m in cell.metrics("per_layer")}
    theirs = {m["name"] for m in
              Cell("tsbs4k-read-while-ingest").metrics("per_layer")}
    assert layers == theirs | {"late_rows_in_window",
                               "equal_overwrites_dropped"}


def test_the_data_is_tsbs_cpu_onlys_and_the_load_lacks_the_gap(cell, ds):
    from benchlib.data import Dataset as Plain
    plain = Plain(cell.config, SEED, extra_ticks=EXTRA, scale=ds.hosts,
                  ticks=ds.ticks)
    assert len(ds.late) == 40 == len(set(ds.late.tolist()))
    assert (ds.gap_lo, ds.gap_hi, ds.ticks) == (180, 540, 720)
    # the same walk; the queue's ticks keep 4 decimals for the late hosts
    differs = np.argwhere((ds.data != plain.data).any(axis=2))
    assert set(differs[:, 1].tolist()) <= set(ds.late.tolist())
    assert differs[:, 0].min() == ds.gap_lo - 1
    assert differs[:, 0].max() == ds.gap_hi - 1
    assert np.abs(ds.data - plain.data).max() <= 5e-5
    assert ds.rows == ds.hosts * ds.ticks - 40 * 360
    # another seed, other hosts behind the relay
    other = load_generator(cell.config)(cell.config, SEED + 1, scale=400,
                                        ticks=720)
    assert other.late.tolist() != ds.late.tolist()


def test_what_a_body_carries(cell, ds):
    batches = ds.line_protocol_batches(int(cell.mix["batch_rows"]))
    bodies = ds.bodies
    assert len(batches) == len(bodies)
    assert ds.queue_rows == 40 * 361
    live = queue = 0
    for (body, first, rows), b in zip(batches, bodies):
        lines = body.decode().split("\n")
        assert len(lines) == rows == b["live_rows"] + b["queue_rows"]
        assert first == b["live_first"] == live
        assert b["queue_first"] == queue
        # a fifth of a body is the queue's while it lasts
        assert b["queue_rows"] == min(500, ds.queue_rows - queue)
        assert rows == 2500 or b is bodies[-1]
        live, queue = live + b["live_rows"], queue + b["queue_rows"]
    assert (live, queue) == (EXTRA * ds.hosts, ds.queue_rows)

    def key(line):
        head, _fields, stamp = line.rsplit(" ", 2)
        return head.split(",")[1], int(stamp)

    # live rows in TSBS file order, then the queue in the order it filled:
    # tick by tick from the tick BEFORE the gap, a tick's hosts in file order
    lines = batches[0][0].decode().split("\n")
    assert [key(x) for x in lines[:3]] == [
        (f"hostname=host_{h}", ds.ms(ds.ticks)) for h in range(3)]
    queued = [key(x) for b, c in zip(batches, bodies)
              for x in b[0].decode().split("\n")[c["live_rows"]:]]
    want = [(f"hostname=host_{h}", ds.ms(t))
            for t in range(ds.gap_lo - 1, ds.gap_hi) for h in ds.late]
    assert queued == want
    # the first 40 are overwrites of loaded rows with equal values
    first = batches[0][0].decode().split("\n")[bodies[0]["live_rows"]]
    values = [float(x.split("=")[1]) for x in
              first.rsplit(" ", 2)[1].split(",")]
    assert values == ds.data[ds.gap_lo - 1, ds.late[0]].tolist()


class FakeServer:
    ports = {"http": 1, "mysql": 2}


def backfill_loop(cell, ds, records, perturb=None):
    from benchlib.loops import Context
    ctx = Context(cell, ds, FakeServer(), {}, SEED, False, True, perturb)
    loop = LOOP(ctx)
    loop.writers.records = records
    return loop


def batch(i, t_send, t_ack, ds, error=None, resent=False):
    b = ds.bodies[i]
    return {"i": i, "first_row": b["live_first"],
            "rows": b["live_rows"] + b["queue_rows"], "resent": resent,
            "t_send_ns": t_send, "t_ack_ns": t_ack, "error": error}


def test_every_25th_body_goes_out_twice(cell, ds, monkeypatch):
    """The workers post through a writer that records: each body once, in
    order a worker, and bodies 24, 49, ... a second time by the same
    worker right after the acknowledgement."""
    import threading

    from benchlib import loops
    ds.line_protocol_batches(2500)
    posted, lock = [], threading.Lock()

    class Writer:
        def __init__(self, *a):
            pass

        def post(self, body):
            with lock:
                posted.append((threading.get_ident(), body))

        def close(self):
            pass

    monkeypatch.setitem(BACKFILL, "InfluxWriter", Writer)
    loop = backfill_loop(cell, ds, [])
    writers = loop.writers
    writers.batches = ds.line_protocol_batches(2500)
    writers._start_workers()
    for th in writers._threads:
        th.join()
    assert isinstance(writers, loops.IngestLoop)
    index = {b[0]: i for i, b in enumerate(writers.batches)}
    sent = [index[body] for _w, body in posted]
    twice = [i for i in range(len(index)) if i % 25 == 24]
    assert sorted(sent) == sorted(list(range(len(index))) + twice)
    for i in twice:
        a, b = [k for k, x in enumerate(sent) if x == i]
        assert posted[a][0] == posted[b][0]         # the same worker
    assert sum(r["resent"] for r in writers.records) == len(twice)
    assert sum(r["rows"] for r in writers.records) == sum(
        b[2] for b in writers.batches) + sum(
            writers.batches[i][2] for i in twice)


def test_newest_tick_reads_a_bodys_live_rows_only(cell, ds):
    ds.line_protocol_batches(2500)
    loop = backfill_loop(cell, ds, [])
    assert (loop._newest_tick([]) == ds.ticks - 1).all()
    # body 0: 2,000 live rows = ticks 720..724 of the 400 hosts; its 500
    # queue rows (ticks 179..) are never a host's newest
    got = loop._newest_tick([batch(0, 0, 1, ds)])
    assert (got == ds.ticks + 4).all()
    got = loop._newest_tick([batch(2, 0, 1, ds), batch(1, 0, 1, ds)])
    assert (got == ds.ticks + 14).all()


def test_a_record_keeps_what_the_scan_cache_counted_in_the_window(cell, ds):
    loop = backfill_loop(cell, ds, [])
    equal = 'greptime_scan_cache_overwrites_total{kind="equal"}'
    loop.ctx.run["counters"] = {
        "before": {"greptime_scan_cache_delta_rows_total": 30000.0,
                   equal: 400.0},
        "after": {"greptime_scan_cache_delta_rows_total": 270000.0,
                  equal: 10400.0,
                  "greptime_scan_cache_late_rows_total": 48000.0}}
    loop.after_window()
    got = loop.ctx.run["scan_cache_in_window"]
    # a counter that never moved is absent from /metrics and reads 0
    assert got["tail_merges"] == got["changed_overwrites"] == 0.0
    assert (got["refresh_delta_rows"], got["late_rows"],
            got["equal_overwrites_dropped"]) == (240000.0, 48000.0, 10000.0)
    # a program without the counters: nothing is kept
    loop.ctx.run.pop("scan_cache_in_window")
    loop.ctx.run["counters"] = {"before": {}, "after": {}}
    loop.after_window()
    assert "scan_cache_in_window" not in loop.ctx.run


# ---------------------------------------------------------------------------
# the bounds of a backlog answer
# ---------------------------------------------------------------------------

def state(ds, ticks_of: dict) -> np.ndarray:
    """-> bool [late, gap]: late host j holds the gap ticks ticks_of[j]."""
    out = np.zeros((len(ds.late), ds.gap_ticks), dtype=bool)
    for j, ticks in ticks_of.items():
        out[j, list(ticks)] = True
    return out


def served(fam, ds, params, present):
    """What a server that holds exactly `present` answers."""
    if hasattr(fam, "present"):
        packed = np.packbits(present.reshape(-1)).tobytes()
        return fam.reference(dict(params, present=packed), ds)
    return fam._over(params, ds, present)


def ticks_of_all(ds, ticks) -> np.ndarray:
    return state(ds, {j: ticks for j in range(len(ds.late))})


def some_hosts(ds, base, ticks_of: dict) -> np.ndarray:
    out = base.copy()
    for j, ticks in ticks_of.items():
        out[j] = False
        out[j, list(ticks)] = True
    return out


#: (what, the gap ticks every late host shows, those of single hosts that
#: differ, correct?, the bodies in flight shown out of the queue's order?)
#: Acknowledged: ticks 0-4. In flight: three bodies, ticks 5, 6, 7.
AVG_CASES = [
    ("what was acknowledged", range(5), {}, True, 0),
    ("and a prefix of the bodies in flight", range(7), {}, True, 0),
    ("bodies in flight in the other order", [0, 1, 2, 3, 4, 7], {}, True, 1),
    ("an acknowledged row missing", range(5), {0: range(4)}, False, 0),
    ("a row no body carried yet", range(5), {0: [0, 1, 2, 3, 4, 8]}, False, 0),
    ("half a body", range(5), {j: range(6) for j in range(20)}, False, 0),
    ("hosts at different prefixes", range(6), {3: range(7)}, False, 0),
]


@pytest.mark.parametrize("what,shown,single,ok,unordered", AVG_CASES,
                         ids=[c[0] for c in AVG_CASES])
def test_an_average_is_that_of_the_acked_rows_and_whole_bodies_in_flight(
        ds, what, shown, single, ok, unordered):
    fam = load_family("double-groupby-1-backfill")
    params = fam.draw(np.random.default_rng(1), ds)
    assert (params["lo"], params["hi"]) == (0, ds.ticks)
    must = ticks_of_all(ds, range(5))
    flights = [ticks_of_all(ds, [g]) for g in (5, 6, 7)]
    got = served(fam, ds, params,
                 some_hosts(ds, ticks_of_all(ds, shown), single))
    settled = fam.settle(got, ds, params, must, flights)
    res = chk.compare(got, fam.reference(settled, ds), fam.tolerance)
    assert res["ok"] is ok, (what, res["why"])
    assert fam.unordered == unordered


def test_a_missing_acknowledged_row_is_not_hidden_by_many_rows_in_flight(ds):
    """Ten bodies in flight, a row a host each: an answer that lacks one
    acknowledged row of one host and shows five of the ten is no state of
    the table, however many averages the rows in flight could make."""
    fam = load_family("double-groupby-1-backfill")
    params = fam.draw(np.random.default_rng(1), ds)
    must = ticks_of_all(ds, range(5))
    flights = [ticks_of_all(ds, [g]) for g in range(5, 15)]
    whole = ticks_of_all(ds, range(10))
    for lacks in ({0: [0, 1, 2, 3, 5, 6, 7, 8, 9]},
                  {j: [0, 1, 2, 3, 5, 6, 7, 8, 9]
                   for j in range(len(ds.late))}):
        got = served(fam, ds, params, some_hosts(ds, whole, lacks))
        settled = fam.settle(got, ds, params, must, flights)
        res = chk.compare(got, fam.reference(settled, ds), fam.tolerance)
        assert not res["ok"]
    got = served(fam, ds, params, whole)
    settled = fam.settle(got, ds, params, must, flights)
    assert chk.compare(got, fam.reference(settled, ds), fam.tolerance)["ok"]


def test_a_row_counted_twice_is_not_an_average_of_the_table(ds):
    fam = load_family("double-groupby-1-backfill")
    params = fam.draw(np.random.default_rng(1), ds)
    must = ticks_of_all(ds, range(40))
    got = served(fam, ds, params, must)
    h = int(ds.late[3])
    tph = ds.ticks_per_hour
    hour = (ds.gap_lo // tph) * tph
    key = (ds.hostnames[h], ds.ms(hour))
    n = (tph - (hour + tph - ds.gap_lo)) + 40       # loaded + backlog rows
    twice = (got[key][0] * n + ds.data[ds.gap_lo, h, 0]) / (n + 1)
    got[key] = np.array([twice])
    for flights in ((), [ticks_of_all(ds, [40])]):
        settled = fam.settle(got, ds, params, must, flights)
        res = chk.compare(got, fam.reference(settled, ds), fam.tolerance)
        assert not res["ok"]


#: Acknowledged: gap ticks 0-99. In flight: ticks 100-149 and 150-199.
MAX_CASES = [
    ("what was acknowledged", range(100), True),
    ("every body in flight", range(200), True),
    ("the second body and not the first",
     list(range(100)) + list(range(150, 200)), True),
    ("below what was acknowledged", [], False),
    ("above what had been sent", range(360), False),
]


@pytest.mark.parametrize("what,shown,ok", MAX_CASES,
                         ids=[c[0] for c in MAX_CASES])
def test_a_maximum_is_that_of_the_acked_rows_and_whole_bodies_in_flight(
        ds, what, shown, ok):
    fam = load_family("cpu-max-all-8-backfill")
    params = fam.draw(np.random.default_rng(3), ds)
    assert set(params["hosts"]) <= set(ds.late.tolist())
    assert (params["lo"], params["hi"]) == (0, ds.ticks)    # 2 h at debug
    must = ticks_of_all(ds, range(100))
    flights = [ticks_of_all(ds, range(100, 150)),
               ticks_of_all(ds, range(150, 200))]
    got = served(fam, ds, params, ticks_of_all(ds, shown))
    lo = served(fam, ds, params, must)
    hi = served(fam, ds, params, ticks_of_all(ds, range(200)))
    if not shown:
        assert any((got[k] < lo[k]).any() for k in lo)
    if len(shown) == 360:
        assert any((got[k] > hi[k]).any() for k in hi)
    settled = fam.settle(got, ds, params, must, flights)
    res = chk.compare(got, fam.reference(settled, ds), fam.tolerance)
    assert res["ok"] is ok, (what, res["why"])


def test_a_maximum_between_the_bounds_that_no_row_gives_is_wrong(ds):
    fam = load_family("cpu-max-all-8-backfill")
    params = fam.draw(np.random.default_rng(3), ds)
    must = ticks_of_all(ds, range(20))
    flights = [ticks_of_all(ds, range(20, 200))]
    lo = served(fam, ds, params, must)
    hi = served(fam, ds, params, ticks_of_all(ds, range(200)))
    stamp, m = next((k, int(np.argmax(hi[k] - lo[k]))) for k in sorted(lo)
                    if (hi[k] > lo[k]).any())
    got = {k: v.copy() for k, v in lo.items()}
    got[stamp][m] = (lo[stamp][m] + hi[stamp][m]) / 2
    settled = fam.settle(got, ds, params, must, flights)
    res = chk.compare(got, fam.reference(settled, ds), fam.tolerance)
    assert not res["ok"]


def test_the_loop_takes_the_bounds_from_the_batch_records(cell, ds):
    """Body i carries queue rows [500 i, 500 (i + 1)): 40 overwrites, then
    gap ticks. A statement sent at 1,000 and answered at 2,000: bodies
    acknowledged before 1,000 must show; a body sent before 2,000 and not
    acknowledged before 1,000 is in flight, one that errored too (its rows
    may have been written) and a re-sent one once; in the queue's order."""
    ds.line_protocol_batches(2500)
    queue_rows = BACKFILL["queue_rows"]
    records = [batch(0, 0, 100, ds), batch(1, 50, 900, ds),
               batch(3, 1900, 2500, ds), batch(2, 500, 1500, ds),
               batch(2, 1600, 2400, ds, resent=True),
               batch(4, 2100, 2600, ds),
               batch(5, 100, 200, ds, error="reset"),
               batch(1, 950, 1700, ds, resent=True)]
    loop = backfill_loop(cell, ds, records)
    must, flights = loop._must_and_flights(
        {"t_send_ns": 1000, "t_done_ns": 2000})
    assert must.shape == (40, 360)
    # bodies 0, 1: queue rows 0..999 = the overwrites + 24 gap ticks
    assert must.sum() == 1000 - 40 and must[:, :24].all()
    # bodies 2, 3 and the errored 5, in the queue's order; body 1's retry
    # adds nothing to what its first acknowledgement vouches for
    assert [int(f.sum()) for f in flights] == [500, 500, 500]
    for f, i in zip(flights, (2, 3, 5)):
        assert (f == queue_rows(ds, [batch(i, 0, 0, ds)])).all()
        assert not (f & must).any()
    assert not np.logical_or.reduce(flights)[:, 49:61].any()   # body 4


# ---------------------------------------------------------------------------
# the whole-run controls, on the CPU backend at the debug size
# ---------------------------------------------------------------------------

def run_control(*args):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "control.py"), "--workload", CELL,
         "--seed", str(SEED), "--debug", *args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=900, cwd=os.path.dirname(HERE))
    lines = [x for x in proc.stdout.splitlines() if x.startswith("{")]
    return proc.returncode, json.loads(lines[-1]), proc.stdout


def test_the_bf16_control_fails_every_family():
    rc, out, text = run_control("--draws", "2")
    assert rc == 0, text[-2000:]
    assert set(out["control"]) == {"double-groupby-1-backfill",
                                   "lastpoint-live", "cpu-max-all-8-backfill"}
    assert all(v["fails"] for v in out["control"].values())


def backlog_control(ds, draws: int = 2) -> dict:
    """The bf16 control with the whole backlog in: `control.py`'s draws
    carry no bounds, so its references hold the loaded rows alone; here
    every gap row is in both, the float64 reference and the one over bf16
    mirrors, which is what the gap's path has to tell apart.
    -> family: (the smallest number of the draws, its limit)."""
    import copy
    mirror = copy.copy(ds)
    mirror.data = chk.bf16_round(ds.data.reshape(-1)).reshape(ds.data.shape)
    whole = np.ones((len(ds.late), ds.gap_ticks), dtype=bool)
    out = {}
    for name in ("double-groupby-1-backfill", "cpu-max-all-8-backfill"):
        fam = load_family(name)
        rng = np.random.default_rng(5)
        smallest = None
        for _ in range(draws):
            params = fam.draw(rng, ds)
            res = chk.compare(served(fam, mirror, params, whole),
                              served(fam, ds, params, whole), fam.tolerance)
            _number, value, limit = chk.compared_number(res, fam.tolerance)
            smallest = value if smallest is None else min(smallest, value)
        out[name] = (smallest, limit)
    return out


def test_the_bf16_control_fails_with_the_backlog_in(ds):
    for name, (value, limit) in backlog_control(ds).items():
        assert value > limit, name


def test_a_stale_answer_and_a_lost_body_fail_the_run():
    """`stale-lastpoint`: every late host's average one backlog row short
    of what was acknowledged (and `lastpoint-live` a tick); `lost-batch`:
    an acknowledgement booked for a body, backlog included, that the
    server never got. Each part has to be off in its own numbers."""
    rc, out, text = run_control("--seconds", "3", "--perturb",
                                "stale-lastpoint+lost-batch")
    assert rc == 0, text[-3000:]
    assert out["result"]["correct"] is False
    assert out["parts_off"] == ["answers", "read_back"]
    compared = out["result"]["compared"]
    assert compared["double-groupby-1-backfill.wrong_answers"]["value"] > 0
    assert compared["lastpoint-live.wrong_answers"]["value"] > 0
    assert compared["read_back.before_crash.count_max_abs_err"]["value"] != 0
