"""Loop kind `remote-write`: `mixed` (one statement client beside the
writers) where the writers are an agent's remote-write queues and the
reader's "now" is what they have been acknowledged
(`generators/node-exporter-live.py`: `blocks`, `block_samples`).

The writers: `ingest`'s workers, each posting its next block (one second
of the scrape schedule: a snappy prompb `WriteRequest`, all seven tables)
to `/v1/prometheus/write` when the last is acknowledged; 204 is the
acknowledgement, after the WAL append and fsync of every table of the
block. Blocks are encoded in set-up and taken in the schedule's order.

The reader: `statements`' client in rounds of the mix's families, but a
statement is made when it is sent: its range ends at the **acknowledged
frontier**, the largest whole second below the start of the oldest block
not yet acknowledged (a family may align it further: `Live.frontier`), so
every sample at or before its end was acknowledged before the send and
the answer is one answer: the float64 reference over the generator's
samples up to that end, whatever is in flight.

Set-up ends with one statement a family after the unmeasured blocks. One
that takes longer than the mix's `live_statement_limit_s` stops the run
with an error: a program that merges a written table into a new base
(46.08M rows copied, mirrors uploaded, programs compiled again) for every
PromQL statement would answer a handful in an hour, and gives no result
instead.

`correct`: every statement of the window against its reference, every
family executed as its `dispatch` says (a lowered statement whose
`scan_prep` row says `seam=merged` took the merged table and fails it),
and the read-back of exactly the acknowledged samples, a table at a time:
the samples from the end of the load on over Prometheus remote read,
counted and summed in float64 a scrape timestamp on this side, before and
after SIGKILL + restart.

The controls (`control.py --perturb`, names as `mixed` has them):
`stale-lastpoint` holds every answer to the table as it was one block
behind the statement's end (the newest block whose samples all lie at or
before it is left out of the reference: what a reader that missed an
acknowledged block would show); `lost-batch` books an acknowledgement for
a block the server never got; `seam-left-out` (not among `control.py`'s
names: `benchmark/test_remote_write.py` runs it through `run_cell`) holds
the fleet panel's growth to the sum of what the load and the written rows
give apart, without the growth between a series' last loaded sample and
its first written one.
"""

from __future__ import annotations

import http.client
import threading
import time

import numpy as np

from benchlib import check as chk
from benchlib.loops import (IngestLoop, Sender, StatementLoop, family_rng,
                            log)
from benchlib.spec import load_loop
from benchlib.wire import WireError
from benchlib.writelib import field, varint

MixedLoop = load_loop("mixed")


class PromWriter:
    """One keep-alive connection posting remote-write bodies; 204 is the
    acknowledgement."""

    def __init__(self, port: int):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=300)

    def post(self, body: bytes) -> None:
        self.conn.request("POST", "/v1/prometheus/write", body=body, headers={
            "Content-Encoding": "snappy",
            "Content-Type": "application/x-protobuf"})
        resp = self.conn.getresponse()
        payload = resp.read()
        if resp.status != 204:
            raise WireError(f"remote write: HTTP {resp.status}: "
                            f"{payload[:500]!r}")

    def close(self):
        self.conn.close()


def _read_varint(buf, pos: int):
    n = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(number, wire type, value or (start, end)) of a protobuf message's
    fields: varints and length-delimited ones, which is all prompb's
    `ReadResponse` holds down to a series' samples."""
    while pos < end:
        key, pos = _read_varint(buf, pos)
        if key & 7 == 0:
            value, pos = _read_varint(buf, pos)
            yield key >> 3, 0, value
        elif key & 7 == 2:
            size, pos = _read_varint(buf, pos)
            yield key >> 3, 2, (pos, pos + size)
            pos += size
        else:
            raise WireError(f"remote read: wire type {key & 7}")


class RemoteReader:
    """Prometheus remote read of one metric's samples in a time range:
    a snappy prompb `ReadRequest` with one query (an equality matcher on
    `__name__`), the `ReadResponse` taken apart here."""

    #: a sample as the program encodes it: field 2 of its series, 16
    #: bytes: the value (fixed64) and the timestamp (a six-byte varint)
    SAMPLE = 2 + 1 + 8 + 1 + 6

    def __init__(self, port: int):
        import pyarrow as pa
        self.codec = pa.Codec("snappy")
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=600)

    def samples(self, metric: str, lo_ms: int, hi_ms: int):
        """-> (timestamps int64 [n], values float64 [n]) of every series
        of `metric` in [lo_ms, hi_ms]."""
        matcher = b"\x08\x00" + field(2, b"__name__") \
            + field(3, metric.encode())
        query = b"\x08" + varint(lo_ms) + b"\x10" + varint(hi_ms) \
            + field(3, matcher)
        self.conn.request("POST", "/v1/prometheus/read",
                          body=self.codec.compress(field(1, query),
                                                   asbytes=True))
        resp = self.conn.getresponse()
        payload = resp.read()
        if resp.status != 200:
            raise WireError(f"remote read: HTTP {resp.status}: "
                            f"{payload[:500]!r}")
        size, _ = _read_varint(payload, 0)   # snappy's own preamble
        raw = self.codec.decompress(payload, decompressed_size=size,
                                    asbytes=True)
        buf = np.frombuffer(raw, dtype=np.uint8)
        starts = []
        for _n, _w, (a, b) in _fields(raw, 0, len(raw)):        # results
            for _n, _w, (sa, sb) in _fields(raw, a, b):         # series
                for number, _w, (fa, fb) in _fields(raw, sa, sb):
                    if number == 2:     # its samples lie end to end
                        if (sb - fa + 2) % self.SAMPLE:
                            raise WireError("remote read: a sample of "
                                            "another length")
                        starts.append(np.arange(fa - 2, sb, self.SAMPLE))
                        break
        if not starts:
            return np.zeros(0, np.int64), np.zeros(0, np.float64)
        at = np.concatenate(starts)
        values = buf[at[:, None] + 3 + np.arange(8)].copy().view("<f8")[:, 0]
        septets = buf[at[:, None] + 12 + np.arange(6)].astype(np.int64) & 0x7F
        stamps = (septets << (np.arange(6) * 7)).sum(axis=1)
        return stamps, values

    def close(self):
        self.conn.close()


class BlockWriters(IngestLoop):
    """`ingest`'s workers over remote-write blocks, the frontier they
    have reached, and a read-back a table."""

    def prepare(self) -> None:
        ctx, run = self.ctx, self.ctx.run
        t = time.monotonic()
        self.batches = ctx.ds.blocks()
        self.acked = np.zeros(len(self.batches), dtype=bool)
        biggest = max(len(b[0]) for b in self.batches)
        run["encode_s"] = time.monotonic() - t
        log(f"encoded {len(self.batches)} blocks of "
            f"{self.batches[0][2]} samples (largest {biggest} B "
            f"compressed) in {run['encode_s']:.1f} s")
        t = time.monotonic()
        self._start_workers()
        while self._acked() < int(ctx.mix["prefill_batches"]):
            if self._errors or not any(t.is_alive() for t in self._threads):
                self.stop()
                raise self._errors[0] if self._errors else RuntimeError(
                    "the workers ended during the unmeasured blocks")
            time.sleep(0.01)
        run["prefill_s"] = time.monotonic() - t

    def _start_workers(self) -> None:
        ctx = self.ctx

        def worker(w: int) -> None:
            writer = PromWriter(ctx.server.ports["http"])
            try:
                while not self._stop.is_set():
                    i = self._take()
                    if i is None:
                        return
                    body, first, rows = self.batches[i]
                    rec = {"i": i, "worker": w, "first_row": first,
                           "rows": rows, "t_send_ns": time.time_ns(),
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
                        self.acked[i] = rec["error"] is None
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

    def frontier_s(self) -> int:
        """Seconds from the data's start to the largest whole second
        below the start of the oldest block not yet acknowledged."""
        ds = self.ctx.ds
        with self.lock:
            behind = np.flatnonzero(~self.acked)
        oldest = int(behind[0]) if len(behind) else len(self.acked)
        return (ds.block_start_ms(oldest) - ds.t0_ms) // 1000 - 1

    def _block_of(self, rec: dict) -> int:
        """The lost-batch control's phantom has no index, only its first
        row."""
        if rec["i"] >= 0:
            return rec["i"]
        return next(i for i, b in enumerate(self.batches)
                    if b[1] == rec["first_row"])

    def _read_back(self, http, when: str) -> dict:
        """Every sample from the end of the load on, a table at a time,
        over Prometheus remote read (`POST /v1/prometheus/read`: the
        region's rows of that time range as float64, from the memtables
        and the files the range keeps; no scan cache, no program of
        46.08M rows compiled at a new length after a restart, and not the
        SQL fallback's frame of the whole table); counted and summed in
        float64 a scrape timestamp here, against the acknowledged
        blocks' samples."""
        ctx, ds = self.ctx, self.ctx.ds
        want = {name: ([], []) for name in ds.tables}
        acked = sorted({self._block_of(r) for r in self.records
                        if r["error"] is None})
        for b in acked:
            for name, (stamps, values) in ds.block_samples(b).items():
                want[name][0].append(stamps)
                want[name][1].append(values)
        got_count, got_sums, want_count, want_sums = {}, {}, {}, {}

        def by_stamp(stamps, values, counts: dict, sums: dict, name: str):
            uniq, inv = np.unique(stamps, return_inverse=True)
            n = np.bincount(inv, minlength=len(uniq))
            total = np.bincount(inv, weights=values, minlength=len(uniq))
            for t, c, s in zip(uniq.tolist(), n.tolist(), total.tolist()):
                counts[(name, t)] = [c]
                sums[(name, t)] = [s]

        t = time.monotonic()
        reader = RemoteReader(ctx.server.ports["http"])
        try:
            for name in ds.tables:
                stamps, values = reader.samples(
                    name, ds.end_ms, ds.ms(ds.total_ticks + 1))
                by_stamp(stamps, values, got_count, got_sums, name)
                if want[name][0]:
                    by_stamp(np.concatenate(want[name][0]),
                             np.concatenate(want[name][1]),
                             want_count, want_sums, name)
        finally:
            reader.close()
        took = time.monotonic() - t
        counts = chk.compare(got_count, want_count, dict(rtol=0.0, atol=0.0))
        sums = chk.compare(got_sums, want_sums, ctx.mix["sum_tolerance"])
        ok = counts["ok"] and sums["ok"]
        samples = sum(self.batches[b][2] for b in acked)
        log(f"check read-back {when}: {len(acked)} acknowledged blocks, "
            f"{samples} samples at {len(want_count)} (table, scrape "
            f"timestamp) in {len(ds.tables)} tables; count max_abs_err "
            f"{counts['max_abs_err']} (limit 0), sum max_rel_err "
            f"{sums['max_rel_err']} (limit "
            f"{ctx.mix['sum_tolerance']['rtol']:g}); {took:.1f} s -> "
            f"{'ok' if ok else 'FAILED'} {counts['why']} {sums['why']}")
        return {"ok": ok, "rows": samples, "count": counts, "sums": sums,
                "read_s": took}


class LiveReader(StatementLoop):
    """`statements`' client whose statements are made at the send: each
    family's parameters as drawn from the seed, its range's end the
    writers' frontier at that moment."""

    def __init__(self, ctx, writers: BlockWriters):
        super().__init__(ctx)
        self.writers = writers

    def make(self, fam, rng):
        """-> (family, parameters, text) of a statement sent now."""
        ds = self.ctx.ds
        params = dict(fam.draw(rng, ds),
                      **fam.frontier(self.writers.frontier_s(), ds))
        return fam, params, fam.sql(params, ds)

    def prepare(self) -> None:
        super().prepare()       # the warm statements, before any write
        self.plan = []          # filled as the window sends

    def window(self, seconds: float) -> None:
        """One client, whole rounds, no round started once `seconds`
        have passed: as `StatementLoop.window`, the statement made at
        its send."""
        ctx = self.ctx
        prefix = "EXPLAIN ANALYZE " if ctx.traced else ""
        rngs = {f.name: family_rng(ctx.seed, f.name, "window")
                for f in self.families}
        limit = int(ctx.mix["max_statements"])
        sender = Sender(ctx)
        self.t_start_ns = time.time_ns()
        t_end = time.monotonic() + seconds
        try:
            while time.monotonic() < t_end:
                if len(self.plan) + len(self.families) > limit:
                    raise RuntimeError(
                        f"the window outran max_statements={limit}")
                for fam in self.families:
                    made = self.make(fam, rngs[fam.name])
                    ctx.before_statement(sender.http)
                    rec = {"i": len(self.plan), "family": fam.name,
                           "via": fam.via, "raw": None, "error": None,
                           "in_window": True, "end_s": made[1]["end_s"]}
                    self.plan.append(made)
                    rec["t_send_ns"] = time.time_ns()
                    t = time.perf_counter()
                    try:
                        rec["raw"] = sender.send_raw(fam.via,
                                                     prefix + made[2])
                    except (WireError, OSError) as e:
                        rec["error"] = f"{type(e).__name__}: {e}"[:300]
                    rec["client_ms"] = (time.perf_counter() - t) * 1e3
                    rec["t_done_ns"] = time.time_ns()
                    self.records.append(rec)
        finally:
            sender.close()
        ctx.run["window_s"] = (
            max(r["t_done_ns"] for r in self.records)
            - self.t_start_ns) / 1e9


class RemoteWriteLoop(MixedLoop):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.writers = BlockWriters(ctx)
        self.reader = LiveReader(ctx, self.writers)

    def prepare(self) -> None:
        super().prepare()
        try:
            self._statements_after_the_first_blocks()
        except BaseException:
            self.writers.stop()
            raise

    def _statements_after_the_first_blocks(self) -> None:
        """The unmeasured blocks are acknowledged: every table holds rows
        written since its scan cache was built. One statement a family,
        timed."""
        ctx = self.ctx
        limit = float(ctx.mix["live_statement_limit_s"])
        sender, took = Sender(ctx), {}
        try:
            for fam in self.reader.families:
                _fam, _params, sql = self.reader.make(
                    fam, family_rng(ctx.seed, fam.name, "guard"))
                ctx.before_statement(sender.http)
                t = time.monotonic()
                sender.send(fam.via, sql)
                took[fam.name] = time.monotonic() - t
                if took[fam.name] > limit:
                    raise RuntimeError(
                        f"benchmark: {fam.name}, sent after the first "
                        f"remote-write blocks, took {took[fam.name]:.1f} s "
                        f"(limit {limit:g} s): this program pays for the "
                        "table at every written block, and the cell's "
                        "window would hold a handful of statements. No "
                        "result.")
        finally:
            sender.close()
        ctx.run["after_first_blocks_s"] = took
        log("statements after the first blocks: " + ", ".join(
            f"{k} {v:.2f} s" for k, v in took.items()))

    #: what the scan cache counted inside the window, kept in
    #: `record.json` of every run, traced or not (and the interpreter's
    #: full collections, for which every thread stands still: an
    #: untraced run's stalls are read against them)
    SCAN_CACHE_COUNTERS = {
        "gc_full_s": "greptime_gc_full_collection_seconds_sum",
        "gc_full_collections": "greptime_gc_full_collection_seconds_count",
        "tail_merges": "greptime_scan_cache_merges_total",
        "cache_refreshes": "greptime_scan_cache_incremental_total",
        "refresh_delta_rows": "greptime_scan_cache_delta_rows_total",
        "refresh_upload_bytes": "greptime_scan_cache_upload_bytes_total",
        "seam_pairs": "greptime_scan_seam_pairs_total",
        "selects_with_a_tail":
            'greptime_promql_select_parts_total{tail="yes"}',
        "selects_without_a_tail":
            'greptime_promql_select_parts_total{tail="no"}'}

    def after_window(self) -> None:
        super().after_window()
        counters = self.ctx.run.get("counters")
        if counters:        # a counter that never moved is absent: 0
            before, after = counters["before"], counters["after"]
            self.ctx.run["scan_cache_in_window"] = {
                name: after.get(key, 0.0) - before.get(key, 0.0)
                for name, key in self.SCAN_CACHE_COUNTERS.items()}

    def check(self) -> dict:
        ctx, reader = self.ctx, self.reader
        if "stale-lastpoint" in self._perturbed():
            # every answer held to the table one block behind its end
            for i, (fam, params, sql) in enumerate(reader.plan):
                reader.plan[i] = (fam, dict(params, without_newest_block=1),
                                  sql)
        if "seam-left-out" in self._perturbed():
            # every growth held to the sum of the two scans' alone
            for i, (fam, params, sql) in enumerate(reader.plan):
                reader.plan[i] = (fam, dict(params, without_seam=1), sql)
        verdict = super().check()
        merged = [r["i"] for r in reader.records
                  if "seam=merged" in (r.get("stages") or {}).get(
                      "scan_prep", {}).get("detail", "")]
        ctx.run["statements_through_a_merged_table"] = len(merged)
        # none through a merged table: the window's count of merges,
        # beside its limit among the compared numbers
        merges = int(ctx.run.get("scan_cache_in_window", {}).get(
            "tail_merges", 0))
        ctx.run["compared"]["scan_cache"] = {
            "number": "tail_merges", "limit": 0, "largest": merges,
            "answers": len(reader.records), "wrong": len(merged), "why": ""}
        if merged or merges:
            log(f"check: {merges} merges of a written table in the window, "
                f"{len(merged)} of its statements say seam=merged -> FAILED")
            verdict["correct"] = False
        return verdict


LOOP = RemoteWriteLoop
