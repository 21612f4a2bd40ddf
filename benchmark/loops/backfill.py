"""Loop kind `backfill`: `mixed` (one statement client beside the writers)
over a dataset whose bodies carry, beside the ticks after the load, the
rows of a relay's queue that belong inside the loaded history, and of
which some are posted twice (`generators/tsbs-cpu-outage.py`: `bodies`,
`late`, `gap_lo`, `gap_hi`, `resend_every`). `mixed`'s row arithmetic
(`rows % hosts`, `ti >= end_ms`) describes live rows only, so this kind
says again what a body carries wherever `mixed` and `ingest` ask.

The writers: `ingest`'s workers, and a worker posts every
`resend_every`th body a second time as soon as it is acknowledged (a retry
after a lost acknowledgement: the same keys, the same values). A re-sent
body's rows count as acknowledged rows; they are no new rows of the table.

Set-up ends with one statement a family after the unmeasured batches, each
of which carries queue rows. One that takes longer than the mix's
`late_statement_limit_s` stops the run with an error: a program that
copies the table and compiles anew at every late row would answer a
handful of statements in an hour, and gives no result instead.

`correct`: as `mixed`, and
- a `backfill` family (`families/*-backfill.py`) within the bounds this
  loop takes from the batch records: per late host the gap ticks
  acknowledged before the statement's send (`must`) and, a body each, those
  whose body had been sent before its answer and not acknowledged before
  its send (`flights`); a body becomes visible whole, so the family's
  `settle` holds the answer to `must` and some of the bodies in flight,
  the same ones for every host;
- `lastpoint-live` as in `mixed`, over the live rows of a body: a late
  row is never a host's `last`;
- the read-back before and after SIGKILL + restart from the tick before
  the gap on: `count(*)` and two sums a tick, which count every loaded
  and every acknowledged key once however often it was sent.

The controls (`control.py --perturb`): `stale-lastpoint` also books, for
every late host, one more backlog row as acknowledged before the send than
the answer shows; `lost-batch`'s phantom is the next body, which carries
backlog while the queue lasts, so the gap's `count(*)` is off too.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchlib import check as chk
from benchlib.loops import IngestLoop, Sender, log
from benchlib.spec import load_loop
from benchlib.wire import InfluxWriter, WireError

MixedLoop = load_loop("mixed")


def body_of(ds, rec: dict) -> dict:
    """What the body of a batch record carries (`Dataset.bodies`); the
    lost-batch control's phantom has no index, only its first live row."""
    if rec["i"] >= 0:
        return ds.bodies[rec["i"]]
    return next(b for b in ds.bodies if b["live_first"] == rec["first_row"])


def queue_rows(ds, records) -> np.ndarray:
    """-> bool [late hosts, gap ticks]: the backlog rows these batch
    records carry (the queue's first tick, the overwrites of the tick
    before the gap, left out)."""
    carried = np.zeros(ds.queue_rows, dtype=bool)
    for rec in records:
        b = body_of(ds, rec)
        carried[b["queue_first"]:b["queue_first"] + b["queue_rows"]] = True
    return carried.reshape(len(ds.queue_ticks), len(ds.late))[1:].T


class BackfillWriters(IngestLoop):
    """`ingest`'s workers with the retries, and a read-back that also
    counts the gap."""

    def _start_workers(self) -> None:
        ctx = self.ctx
        every = int(ctx.ds.resend_every)

        def post(writer, i: int, w: int, resent: bool) -> None:
            body, first, rows = self.batches[i]
            rec = {"i": i, "worker": w, "first_row": first, "rows": rows,
                   "resent": resent, "t_send_ns": time.time_ns(),
                   "error": None}
            t = time.perf_counter()
            try:
                writer.post(body)
            except (WireError, OSError) as e:
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec["ack_ms"] = (time.perf_counter() - t) * 1e3
            rec["t_ack_ns"] = time.time_ns()
            with self.lock:
                self.records.append(rec)

        def worker(w: int) -> None:
            writer = InfluxWriter(ctx.server.ports["http"],
                                  ctx.mix["precision"])
            try:
                while not self._stop.is_set():
                    i = self._take()
                    if i is None:
                        return
                    post(writer, i, w, False)
                    if i % every == every - 1 and not self._stop.is_set():
                        post(writer, i, w, True)
            except BaseException as e:  # noqa: BLE001 - re-raised later
                self._errors.append(e)
            finally:
                writer.close()

        self._stop, self._errors = threading.Event(), []
        self._threads = [threading.Thread(target=worker, args=(w,))
                         for w in range(int(ctx.mix["workers"]))]
        for th in self._threads:
            th.start()

    def stop(self) -> None:
        self._stop.set()
        for th in self._threads:
            th.join()

    def _read_back(self, http, when: str) -> dict:
        """count(*) and sum() of two fields per tick from the tick before
        the gap on, a device statement, against the float64 reference of
        the load and the acknowledged batches, every key once however
        often it was sent: a gap tick holds the hosts that never lacked
        it and the late hosts whose row was acknowledged. One statement:
        after a restart a second one would meet the dispatch floor that
        the first, over a table of a new length, has just raised."""
        ctx, ds = self.ctx, self.ctx.ds
        ti, f0, f1 = ds.time_index, ds.field_names[0], ds.field_names[1]
        sql = (f"SELECT date_bin(INTERVAL '{ds.tick_ms // 1000} second', "
               f"{ti}) AS tick, count(*), sum({f0}), sum({f1}) FROM "
               f"{ds.table} WHERE {ti} >= {ds.ms(ds.gap_lo - 1)} "
               "GROUP BY tick ORDER BY tick")
        acked = [r for r in self.records if r["error"] is None]
        first = ds.gap_lo - 1
        there = np.zeros((ds.ticks + ds.extra_ticks - first, ds.hosts),
                         dtype=bool)
        there[:ds.ticks - first] = True                 # the load
        there[np.ix_(np.arange(1, 1 + ds.gap_ticks), ds.late)] = \
            queue_rows(ds, acked).T
        live = there[ds.ticks - first:].reshape(-1)
        for rec in acked:
            b = body_of(ds, rec)
            live[b["live_first"]:b["live_first"] + b["live_rows"]] = True
        want_count, want_sums = {}, {}
        for k in np.flatnonzero(there.any(axis=1)):
            stamp = ds.ms(first + k)
            want_count[stamp] = [int(there[k].sum())]
            want_sums[stamp] = ds.data[first + k][there[k], :2].sum(axis=0)
        ctx.before_statement(http)
        t = time.monotonic()
        stages = chk.stages_of(http.sql("EXPLAIN ANALYZE " + sql)[1])
        explain_s = time.monotonic() - t
        ctx.before_statement(http)
        rows = http.sql(sql)[1]
        got_count = {int(r[0]): [int(r[1])] for r in rows}
        got_sums = {int(r[0]): [float(r[2]), float(r[3])] for r in rows}
        counts = chk.compare(got_count, want_count, dict(rtol=0.0, atol=0.0))
        sums = chk.compare(got_sums, want_sums, ctx.mix["sum_tolerance"])
        dispatch = chk.executed_dispatch(stages)
        ok = counts["ok"] and sums["ok"] and dispatch == ctx.mix["dispatch"]
        backlog = int(there[1:1 + ds.gap_ticks][:, ds.late].sum())
        log(f"check read-back {when}: {int(live.sum())} live and {backlog} "
            f"backlog rows acknowledged, {len(want_count)} ticks from the "
            f"tick before the gap; count max_abs_err "
            f"{counts['max_abs_err']} (limit 0), sum max_rel_err "
            f"{sums['max_rel_err']} (limit "
            f"{ctx.mix['sum_tolerance']['rtol']:g}); dispatch {dispatch!r} "
            f"(wanted {ctx.mix['dispatch']!r}); first statement "
            f"{explain_s:.1f} s -> {'ok' if ok else 'FAILED'} "
            f"{counts['why']} {sums['why']}")
        return {"ok": ok, "rows": int(live.sum()) + backlog,
                "dispatch": dispatch, "count": counts, "sums": sums,
                "stages": stages, "explain_s": explain_s}


class BackfillLoop(MixedLoop):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.writers = BackfillWriters(ctx)

    def prepare(self) -> None:
        super().prepare()
        try:
            self._statements_after_a_late_body()
        except BaseException:
            self.writers.stop()
            raise

    #: what the scan cache counted inside the window, kept in `record.json`
    #: of every run, traced or not (the per-layer readers report the same
    #: counters in a traced run's line)
    SCAN_CACHE_COUNTERS = {
        "tail_merges": "greptime_scan_cache_merges_total",
        "cache_refreshes": "greptime_scan_cache_incremental_total",
        "refresh_delta_rows": "greptime_scan_cache_delta_rows_total",
        "refresh_upload_bytes": "greptime_scan_cache_upload_bytes_total",
        "late_rows": "greptime_scan_cache_late_rows_total",
        "equal_overwrites_dropped":
            'greptime_scan_cache_overwrites_total{kind="equal"}',
        "changed_overwrites":
            'greptime_scan_cache_overwrites_total{kind="changed"}'}

    def after_window(self) -> None:
        super().after_window()
        counters = self.ctx.run.get("counters")
        if counters and self.SCAN_CACHE_COUNTERS["refresh_delta_rows"] \
                in counters["after"]:
            # a counter that never moved is absent from /metrics: 0
            before, after = counters["before"], counters["after"]
            self.ctx.run["scan_cache_in_window"] = {
                name: after.get(key, 0.0) - before.get(key, 0.0)
                for name, key in self.SCAN_CACHE_COUNTERS.items()}

    def _statements_after_a_late_body(self) -> None:
        """Every unmeasured batch carried queue rows: the table now holds
        rows inside its loaded history. One statement a family, timed."""
        ctx, ds = self.ctx, self.ctx.ds
        limit = float(ctx.mix["late_statement_limit_s"])
        sender, took = Sender(ctx), {}
        try:
            for fam in self.reader.families:
                sql = fam.sql(fam.draw(np.random.default_rng(0), ds), ds)
                ctx.before_statement(sender.http)
                t = time.monotonic()
                sender.send(fam.via, sql)
                took[fam.name] = time.monotonic() - t
                if took[fam.name] > limit:
                    raise RuntimeError(
                        f"benchmark: {fam.name}, sent after the first "
                        f"bodies that carry late rows, took "
                        f"{took[fam.name]:.1f} s (limit {limit:g} s): this "
                        "program pays for the table at every late row, and "
                        "the cell's window would hold a handful of "
                        "statements. No result.")
        finally:
            sender.close()
        ctx.run["after_late_body_s"] = took
        log("statements after the first late bodies: " + ", ".join(
            f"{k} {v:.2f} s" for k, v in took.items()))

    # ---- what a body carries, where `mixed` asks ---------------------------

    def _newest_tick(self, records) -> np.ndarray:
        """Per host the newest tick among the *live* rows of these batch
        records; the last loaded tick where they hold none."""
        ds = self.ctx.ds
        newest = np.full(ds.hosts, ds.ticks - 1, dtype=np.int64)
        for rec in records:
            b = body_of(ds, rec)
            rows = np.arange(b["live_first"],
                             b["live_first"] + b["live_rows"])
            np.maximum.at(newest, rows % ds.hosts, ds.ticks + rows // ds.hosts)
        return newest

    def _lag(self, rec: dict, ticks: np.ndarray) -> None:
        """`visible_lag_ms` as `mixed` has it: the statement's send time
        minus the first send time of the body that carried the oldest of
        the live rows it shows."""
        ds = self.ctx.ds
        sent = {}
        for r in self.writers.records:
            if r["i"] >= 0:
                sent[r["i"]] = min(r["t_send_ns"],
                                   sent.get(r["i"], r["t_send_ns"]))
        written = ticks >= ds.ticks
        rows = (ticks[written] - ds.ticks) * ds.hosts \
            + np.flatnonzero(written)
        firsts = np.array([b["live_first"] for b in ds.bodies])
        found = [sent[i] for i in np.unique(
            np.searchsorted(firsts, rows, side="right") - 1).tolist()
            if i in sent]
        if found:
            rec["visible_lag_ms"] = (rec["t_send_ns"] - min(found)) / 1e6

    def _lag_from_stage_rows(self) -> None:
        """A traced statement returns stage rows: `scan_prep` counts the
        rows its scan held, so the written rows it saw are that many less
        the load, taken as the bodies first acknowledged; a retry and the
        overwrites of the tick before the gap add no row."""
        ds = self.ctx.ds
        acked, seen_bodies = [], set()
        for r in sorted((r for r in self.writers.records
                         if r["error"] is None and r["i"] >= 0),
                        key=lambda r: r["t_ack_ns"]):
            if r["i"] in seen_bodies:
                continue
            seen_bodies.add(r["i"])
            b = ds.bodies[r["i"]]
            new = b["live_rows"] + b["queue_rows"] - max(0, min(
                len(ds.late) - b["queue_first"], b["queue_rows"]))
            acked.append((r, new))
        for rec in self.reader.records:
            fam = self.reader.plan[rec["i"]][0]
            seen = rec.get("stages", {}).get("scan_prep", {}).get("rows")
            if not getattr(fam, "live", False) or not seen:
                continue
            visible, rows = [], seen - ds.rows
            for r, new in acked:
                if new > rows:
                    break
                visible.append(r)
                rows -= new
            self._lag(rec, self._newest_tick(visible))

    # ---- what a backfill answer may be -------------------------------------

    def _bound_live_answers(self) -> None:
        super()._bound_live_answers()
        ctx, ds, reader = self.ctx, self.ctx.ds, self.reader
        batches = self.writers.records
        final = queue_rows(ds, (r for r in batches if r["error"] is None))
        unordered = 0
        for rec in reader.records:
            fam, params, sql = reader.plan[rec["i"]]
            if not getattr(fam, "backfill", False):
                continue
            if rec["error"] is not None or ctx.traced:
                # a traced window holds stage rows: the family's one plain
                # statement goes out after the writers have stopped and
                # must show every acknowledged row
                reader.plan[rec["i"]] = (
                    fam, fam.settle({}, ds, params, final), sql)
                continue
            must, flights = self._must_and_flights(rec)
            got = fam.parse(Sender.decode(fam.via, rec["raw"], sql)[1], ds)
            if "stale-lastpoint" in self._perturbed() and \
                    hasattr(fam, "present"):
                # one more backlog row of every late host booked as
                # acknowledged before the send than the answer shows
                shown = fam.present(
                    fam.settle(got, ds, params, must, flights), ds)
                nxt = np.argmin(shown, axis=1)
                must = shown.copy()
                must[np.arange(len(nxt)), nxt] = True
            settled = fam.settle(got, ds, params, must, flights)
            unordered += getattr(fam, "unordered", 0)
            rec["backfill"] = {
                "acknowledged_before_send": int(must.sum()),
                "bodies_in_flight": len(flights),
                "sent_before_answer": int(
                    np.logical_or.reduce([must, *flights]).sum())}
            reader.plan[rec["i"]] = (fam, settled, sql)
        ctx.run["backfill_answers_out_of_queue_order"] = unordered
        if unordered:
            log(f"backfill: {unordered} answers showed the bodies in flight "
                "in another order than the queue's")

    def _must_and_flights(self, rec: dict):
        """-> (must, flights) of a statement record, both in the client's
        clock: `must` [late hosts, gap ticks], the backlog rows whose body
        was acknowledged before the statement's send; `flights`, one such
        array a body that was sent before the statement's answer and not
        acknowledged before its send (an errored one too: its rows may have
        been written), in the queue's order. A body is one write of the
        region and becomes visible whole, so the answer is that of `must`
        and some of the `flights`, the same for every host."""
        ds = self.ctx.ds

        def acked(r) -> bool:
            return r["error"] is None and r["t_ack_ns"] < rec["t_send_ns"]

        batches = self.writers.records
        must = queue_rows(ds, (r for r in batches if acked(r)))
        flying = {body_of(ds, r)["queue_first"]: r for r in batches
                  if r["t_send_ns"] < rec["t_done_ns"] and not acked(r)}
        flights = [rows for _first, r in sorted(flying.items())
                   for rows in [queue_rows(ds, [r]) & ~must] if rows.any()]
        return must, flights


LOOP = BackfillLoop
