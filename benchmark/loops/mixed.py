"""Loop kind `mixed`: one statement client reads while the writers post,
composed from the two kinds of `benchlib/loops.py`. The reader is a
`StatementLoop` (warm statements first, whole rounds, no round started
after --seconds), the writers an `IngestLoop` (bodies encoded in set-up,
workers started in `prepare`, set-up ends when `prefill_batches` are
acknowledged). The window opens when both are running and closes at the
end of the round in flight; the writers post until then. A statement
counts when it was sent and answered inside the window, a row when its
acknowledgement arrived inside it: one `window_s` for both, and the
record keeps `statements` and `batches`, so the readers of both sides
work.

`correct`: every answer of the window as `StatementLoop.check` compares
it (a family over closed history against its float64 reference: rows
written later carry later timestamps), a `live` family (`families/
lastpoint-live.py`) within the bounds this loop takes from the batch
records, every family executed as its `dispatch` says, and
`IngestLoop`'s read-back of exactly the acknowledged rows before and
after SIGKILL + restart. The check's own statements go out after the
writers have stopped, so they all meet one final table length.
"""

from __future__ import annotations

import numpy as np

from benchlib.loops import IngestLoop, Sender, StatementLoop, log


class MixedLoop:
    def __init__(self, ctx):
        self.ctx = ctx
        self.reader = StatementLoop(ctx)
        self.writers = IngestLoop(ctx)

    def prepare(self) -> None:
        self.reader.prepare()       # warm statements, before any write
        self.writers.prepare()      # encode, start the workers, prefill

    def window(self, seconds: float) -> None:
        try:
            self.reader.window(seconds)
        finally:
            self.writers._stop.set()
        ctx, reader, writers = self.ctx, self.reader, self.writers
        run, window_s = ctx.run, ctx.run["window_s"]
        # IngestLoop's own close, with no time left to wait: it joins the
        # workers, raises what they raised, refuses a window that outran
        # the encoded bodies and books the lost-batch control's phantom
        # (control.py may ask for both of this loop's controls in one run)
        perturb = ctx.perturb
        if "lost-batch" in self._perturbed():
            ctx.perturb = "lost-batch"
        try:
            writers.window(0.0)
        finally:
            ctx.perturb = perturb
        writers.t_start_ns = reader.t_start_ns
        writers.t_end_ns = max(r["t_done_ns"] for r in reader.records)
        for rec in writers.records:
            rec["in_window"] = (writers.t_start_ns < rec["t_ack_ns"]
                                <= writers.t_end_ns)
        run["window_s"] = window_s

    def _perturbed(self) -> list:
        return (self.ctx.perturb or "").split("+")

    def spans(self) -> list:
        return self.reader.spans()

    def after_window(self) -> None:
        """The read-backs are the check's: the profiler's window is the
        statements' window."""

    # ---- what a live answer may be -----------------------------------

    def _newest_tick(self, records) -> np.ndarray:
        """Per host the newest tick among the rows of these batch
        records; the last loaded tick where they hold none."""
        ds = self.ctx.ds
        newest = np.full(ds.hosts, ds.ticks - 1, dtype=np.int64)
        for rec in records:
            rows = np.arange(rec["first_row"], rec["first_row"] + rec["rows"])
            np.maximum.at(newest, rows % ds.hosts, ds.ticks + rows // ds.hosts)
        return newest

    def _lag(self, rec: dict, ticks: np.ndarray) -> None:
        """`visible_lag_ms` of one statement: its send time minus the
        send time of the oldest of the rows it shows (one tick per
        host), which is the send time of the batch that carried that
        row. Nothing where every row it shows was loaded in set-up."""
        ds = self.ctx.ds
        sent = {r["i"]: r["t_send_ns"] for r in self.writers.records}
        written = ticks >= ds.ticks
        rows = (ticks[written] - ds.ticks) * ds.hosts \
            + np.flatnonzero(written)
        found = [sent[i] for i in np.unique(
            rows // int(self.ctx.mix["batch_rows"])).tolist() if i in sent]
        if found:
            rec["visible_lag_ms"] = (rec["t_send_ns"] - min(found)) / 1e6

    def _bound_live_answers(self) -> None:
        """For every statement of a `live` family: the ticks its answer
        may show, the tick per host that it does show, and with that the
        statement's reference. A traced window holds stage rows and no
        answers: its one plain statement a family goes out after the
        writers have stopped and must show the newest acknowledged tick."""
        ctx, ds, reader = self.ctx, self.ctx.ds, self.reader
        batches = self.writers.records
        final = self._newest_tick(r for r in batches if r["error"] is None)
        for rec in reader.records:
            fam, _params, sql = reader.plan[rec["i"]]
            if not getattr(fam, "live", False):
                continue
            ticks = final
            if rec["error"] is None and not ctx.traced:
                k_lo = self._newest_tick(
                    r for r in batches if r["error"] is None
                    and r["t_ack_ns"] < rec["t_send_ns"])
                k_hi = np.maximum(k_lo, self._newest_tick(
                    r for r in batches if r["t_send_ns"] < rec["t_done_ns"]))
                got = fam.parse(
                    Sender.decode(fam.via, rec["raw"], sql)[1], ds)
                ticks = fam.match(got, ds, k_lo, k_hi)
                self._lag(rec, ticks)
                if "stale-lastpoint" in self._perturbed():
                    # one more tick booked as acknowledged before the
                    # send than the answer shows: to the comparison the
                    # answer is one tick older than k_lo
                    k_lo = np.minimum(ticks + 1,
                                      ds.ticks + ds.extra_ticks - 1)
                    ticks = fam.match(got, ds, k_lo, np.maximum(k_lo, k_hi))
                rec["live"] = {
                    "k_lo": [int(k_lo.min()), int(k_lo.max())],
                    "k_hi": [int(k_hi.min()), int(k_hi.max())],
                    "answered": [int(ticks.min()), int(ticks.max())]}
                log(f"live {fam.name} #{rec['i']}: acknowledged before the "
                    f"send up to tick {rec['live']['k_lo']}, sent before "
                    f"the answer up to {rec['live']['k_hi']}, answered "
                    f"{rec['live']['answered']} (loaded: {ds.ticks - 1}); "
                    f"visible lag {rec.get('visible_lag_ms')} ms")
            reader.plan[rec["i"]] = (fam, {"ticks": tuple(ticks.tolist())},
                                     sql)

    def _lag_from_stage_rows(self) -> None:
        """A traced statement returns stage rows, not rows: `scan_prep`
        counts the rows its scan held, so the written rows it saw are
        that many less the load, taken as the batches first
        acknowledged."""
        ds = self.ctx.ds
        acked = sorted((r for r in self.writers.records
                        if r["error"] is None and r["i"] >= 0),
                       key=lambda r: r["t_ack_ns"])
        for rec in self.reader.records:
            fam = self.reader.plan[rec["i"]][0]
            seen = rec.get("stages", {}).get("scan_prep", {}).get("rows")
            if not getattr(fam, "live", False) or not seen:
                continue
            visible, rows = [], seen - ds.rows
            for b in acked:
                if b["rows"] > rows:
                    break
                visible.append(b)
                rows -= b["rows"]
            self._lag(rec, self._newest_tick(visible))

    def check(self) -> dict:
        self._bound_live_answers()
        statements = self.reader.check()
        if self.ctx.traced:
            self._lag_from_stage_rows()
        self.writers.after_window()     # the read-back before the crash
        batches = self.writers.check()  # SIGKILL, restart, read-back
        return {"correct": statements["correct"] and batches["correct"],
                "attempted": statements["attempted"] + batches["attempted"],
                "failed": statements["failed"] + batches["failed"]}


LOOP = MixedLoop
