"""The general traffic generator: one loop kind per `loop` value of a
traffic file, each with the same three phases. `prepare` is set-up (warm
every shape the window uses), `window` is the measured time, `check` runs
after it and decides `correct`. A mix is parameters only; a new mix that
needs no new loop kind is a new JSON file.

What a loop leaves in `run` (a plain dict) is what the per-layer readers
under `benchmark/layers/` read.
"""

from __future__ import annotations

import hashlib
import threading
import time

import numpy as np

from . import check as chk
from .spec import load_family
from .wire import Http, InfluxWriter, MiniMysql, WireError


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


class Context:
    """What a loop works with: the cell, the dataset, the running server,
    the run record, and the switches of this run."""

    def __init__(self, cell, ds, server, run, seed, traced, debug,
                 perturb=None):
        self.cell, self.ds, self.server, self.run = cell, ds, server, run
        self.seed, self.traced, self.debug = seed, traced, debug
        self.mix = cell.mix
        # the selftest's negative controls break the timed path's output
        # on the benchmark's side; the command line cannot set this
        self.perturb = perturb

    def http(self) -> Http:
        return Http(self.server.ports["http"])

    def mysql(self) -> MiniMysql:
        return MiniMysql(self.server.ports["mysql"])

    def before_statement(self, http: Http) -> None:
        """A debug size sits under the latency-adaptive dispatch floor, so
        a debug configuration may name a statement that pins it; never
        sent on the chip."""
        sql = self.debug and self.cell.config["debug"].get(
            "before_each_statement")
        if sql:
            http.sql(sql)


def family_rng(seed: int, family: str, stream: str):
    digest = hashlib.sha256(f"{family}/{stream}".encode()).digest()
    return np.random.default_rng([seed, int.from_bytes(digest[:8], "big")])


class Sender:
    """One client's connections: a statement goes out on its family's
    wire and comes back undecoded."""

    def __init__(self, ctx: Context):
        self.http, self.mysql = ctx.http(), ctx.mysql()

    def send_raw(self, via: str, sql: str):
        if via == "mysql":
            return self.mysql.query_raw(sql)
        return self.http.sql_raw(sql)

    @staticmethod
    def decode(via: str, raw, sql: str = ""):
        if via == "mysql":
            return MiniMysql.decode_rows(raw)
        return Http.decode_sql(raw, sql)

    def send(self, via: str, sql: str):
        return self.decode(via, self.send_raw(via, sql), sql)

    def close(self):
        self.mysql.close()


# ---------------------------------------------------------------------------
# loop kind "statements": closed loop, round robin over statement families
# ---------------------------------------------------------------------------

class StatementLoop:
    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.families = [load_family(n) for n in ctx.mix["families"]]
        self.plan = []          # (family, params, sql) in sending order
        self.records = []       # one dict per statement sent in the window

    def prepare(self) -> None:
        ctx, ds, run = self.ctx, self.ctx.ds, self.ctx.run
        sender = Sender(ctx)
        warm, t_warm = [], time.monotonic()
        prime = ctx.mix.get("prime")
        order = ([load_family(prime)] if prime else []) + self.families
        for n, fam in enumerate(order):
            rng = family_rng(ctx.seed, fam.name, f"warm{n}")
            for k in range(int(ctx.mix.get("warm_statements", 3))):
                sql = fam.sql(fam.draw(rng, ds), ds)
                ctx.before_statement(sender.http)
                t = time.monotonic()
                # the first goes as EXPLAIN ANALYZE: its stage rows show
                # the scan-cache build; a traced window sends only those
                explain = k == 0 or (ctx.traced and k == 1)
                rows = sender.send(
                    fam.via, ("EXPLAIN ANALYZE " if explain else "") + sql)
                warm.append({"family": fam.name, "explain": explain,
                             "wall_s": time.monotonic() - t,
                             "stages": chk.stages_of(rows[1])
                             if explain else None})
        run["warm"] = warm
        run["warm_s"] = time.monotonic() - t_warm
        sender.close()
        # the window's statements, drawn from the seed: a seed replays the
        # same sequence, and a family's draws do not depend on its mix
        rngs = {f.name: family_rng(ctx.seed, f.name, "window")
                for f in self.families}
        rounds = int(ctx.mix["max_statements"]) // len(self.families)
        for i in range(rounds * len(self.families)):
            fam = self.families[i % len(self.families)]
            params = fam.draw(rngs[fam.name], ds)
            self.plan.append((fam, params, fam.sql(params, ds)))
        log(f"warmed {len(warm)} statements in {run['warm_s']:.1f} s")

    def window(self, seconds: float) -> None:
        """Closed loop: each client sends whole rounds (one statement of
        every family, in the mix's order) and starts no round once
        `seconds` have passed, so the window closes at the end of the
        round in flight. Every statement sent completes inside it, every
        family has the same count, and a rate over it does not jump by a
        statement when the speed changes by a hair."""
        ctx = self.ctx
        clients = int(ctx.mix.get("clients", 1))
        per_round = len(self.families)
        prefix = "EXPLAIN ANALYZE " if ctx.traced else ""
        self.t_start_ns = time.time_ns()
        t_end = time.monotonic() + seconds
        results = [[] for _ in range(clients)]
        errors = []

        def client(c: int) -> None:
            sender = Sender(ctx)
            try:
                for first in range(c * per_round, len(self.plan),
                                   clients * per_round):
                    if time.monotonic() >= t_end:
                        return
                    for i in range(first, first + per_round):
                        fam, _params, sql = self.plan[i]
                        ctx.before_statement(sender.http)
                        rec = {"i": i, "family": fam.name, "via": fam.via,
                               "raw": None, "error": None,
                               "in_window": True}
                        rec["t_send_ns"] = time.time_ns()
                        t = time.perf_counter()
                        try:
                            rec["raw"] = sender.send_raw(fam.via,
                                                         prefix + sql)
                        except (WireError, OSError) as e:
                            rec["error"] = f"{type(e).__name__}: {e}"[:300]
                        rec["client_ms"] = (time.perf_counter() - t) * 1e3
                        rec["t_done_ns"] = time.time_ns()
                        results[c].append(rec)
                raise RuntimeError(
                    f"the window outran max_statements={len(self.plan)}")
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errors.append(e)
            finally:
                sender.close()

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]
        self.records = sorted((r for rs in results for r in rs),
                              key=lambda r: r["t_send_ns"])
        self.ctx.run["window_s"] = (
            max(r["t_done_ns"] for r in self.records)
            - self.t_start_ns) / 1e9

    def spans(self) -> list:
        return [(r["family"], r["t_send_ns"], r["t_done_ns"])
                for r in self.records]

    def after_window(self) -> None:
        """Nothing of this loop needs the profiler after the window."""

    def check(self) -> dict:
        """Every answer of the window against the float64 reference (an
        answer identical in bytes to one already compared is not parsed
        again), and the executed dispatch of every family."""
        ctx, ds, run = self.ctx, self.ctx.ds, self.ctx.run
        sender = Sender(ctx)
        references, verdicts, compared = {}, {}, {}
        failed = 0

        def want(fam, params):
            key = (fam.name, repr(sorted(params.items())))
            if key not in references:
                references[key] = fam.reference(params, ds)
            return key, references[key]

        def judge(fam, params, raw, sql):
            key, ref = want(fam, params)
            packets = raw[1] if isinstance(raw, tuple) else [raw]
            digest = (key, hashlib.sha256(b"".join(packets)).digest())
            if digest not in verdicts:
                rows = sender.decode(fam.via, raw, sql)[1]
                got = fam.parse(rows, ds)
                if ctx.perturb == "bf16-answers":
                    got = {k: chk.bf16_round(v) for k, v in got.items()}
                verdicts[digest] = chk.compare(got, ref, fam.tolerance)
            res = verdicts[digest]
            name, value, limit = chk.compared_number(res, fam.tolerance)
            worst = compared.setdefault(
                fam.name, {"number": name, "limit": limit, "largest": 0.0,
                           "answers": 0, "wrong": 0, "why": ""})
            worst["answers"] += 1
            if not res["ok"]:
                worst["wrong"] += 1
                worst["why"] = worst["why"] or res["why"]
            if value is not None:
                worst["largest"] = max(worst["largest"], value)
            return res["ok"]

        dispatches = {}
        for rec in self.records:
            fam, params, sql = self.plan[rec["i"]]
            rec["ok"] = rec["error"] is None
            if rec["ok"] and ctx.traced:
                rec["stages"] = chk.stages_of(
                    sender.decode(fam.via, rec["raw"], sql)[1])
                dispatches.setdefault(fam.name, set()).add(
                    chk.executed_dispatch(rec["stages"]))
            elif rec["ok"]:
                rec["ok"] = judge(fam, params, rec["raw"], sql)
            rec["raw"] = None
            failed += not rec["ok"]
        # after the window: one EXPLAIN ANALYZE per family for the executed
        # dispatch, and in a traced run (whose window answers are stage
        # rows) one plain statement per family for its answer
        last = {}
        for rec in self.records:
            last[rec["family"]] = rec["i"]
        for fam in self.families:
            if fam.name not in last:
                raise RuntimeError(f"the window sent no {fam.name}")
            _fam, params, sql = self.plan[last[fam.name]]
            ctx.before_statement(sender.http)
            if ctx.traced:
                raw = sender.send_raw(fam.via, sql)
                if not judge(fam, params, raw, sql):
                    failed += 1
            else:
                rows = sender.send(fam.via, "EXPLAIN ANALYZE " + sql)[1]
                dispatches.setdefault(fam.name, set()).add(
                    chk.executed_dispatch(chk.stages_of(rows)))
        sender.close()
        correct = failed == 0
        for fam in self.families:
            c = compared.get(fam.name)
            if c is None:       # every statement of the family errored
                correct = False
                log(f"check {fam.name}: no answer to compare -> FAILED")
                continue
            ok = c["wrong"] == 0 and c["largest"] <= c["limit"]
            seen = sorted(str(d) for d in dispatches.get(fam.name, ()))
            dispatched = seen == [fam.dispatch]
            correct = correct and ok and dispatched
            log(f"check {fam.name}: {c['number']} {c['largest']:.4g} "
                f"(limit {c['limit']:g}) over {c['answers']} answers, "
                f"{c['wrong']} wrong; dispatch {seen} (wanted "
                f"{fam.dispatch!r}) -> {'ok' if ok and dispatched else 'FAILED'}"
                + (f": {c['why']}" if c["why"] else ""))
        run["statements"] = self.records
        run["compared"] = compared
        run["dispatches"] = {k: sorted(map(str, v))
                             for k, v in dispatches.items()}
        return {"correct": correct, "attempted": len(self.records),
                "failed": failed}


# ---------------------------------------------------------------------------
# loop kind "ingest": closed loop of workers posting pre-encoded batches
# ---------------------------------------------------------------------------

class IngestLoop:
    """The rows of the ticks after the load, in TSBS file order, as
    line-protocol bodies encoded during set-up: the window only posts
    bytes. Every batch is acknowledged (HTTP 204) after WAL append and
    fsync. The check reads back exactly the acknowledged rows, before and
    after SIGKILL + restart."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.batches = []       # (body, first row, rows)
        self.records = []       # one dict per batch posted
        self.next = 0
        self.lock = threading.Lock()

    def _take(self):
        with self.lock:
            if self.next >= len(self.batches):
                return None
            i = self.next
            self.next += 1
            return i

    def _start_workers(self) -> None:
        """`workers` threads post batches in order, each its next as soon
        as the last is acknowledged, until told to stop (checked before
        each batch) or the batches run out."""
        ctx = self.ctx

        def worker(w: int) -> None:
            writer = InfluxWriter(ctx.server.ports["http"],
                                  ctx.mix["precision"])
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
            except BaseException as e:  # noqa: BLE001 - re-raised later
                self._errors.append(e)
            finally:
                writer.close()

        self._stop, self._errors = threading.Event(), []
        self._threads = [threading.Thread(target=worker, args=(w,))
                         for w in range(int(ctx.mix["workers"]))]
        for th in self._threads:
            th.start()

    def _acked(self) -> int:
        with self.lock:
            return len(self.records)

    def prepare(self) -> None:
        ctx, run = self.ctx, self.ctx.run
        t = time.monotonic()
        self.batches = ctx.ds.line_protocol_batches(
            int(ctx.mix["batch_rows"]))
        biggest = max(len(b[0]) for b in self.batches)
        if biggest > int(ctx.mix["max_body_bytes"]):
            raise RuntimeError(f"a body of {biggest} B is over the "
                               f"server's {ctx.mix['max_body_bytes']} B")
        run["encode_s"] = time.monotonic() - t
        log(f"encoded {len(self.batches)} bodies (largest {biggest} B) in "
            f"{run['encode_s']:.1f} s")
        # the workers start here and post without a pause through the
        # window; set-up ends once the unmeasured batches are acknowledged
        t = time.monotonic()
        self._start_workers()
        while self._acked() < int(ctx.mix["prefill_batches"]):
            if self._errors or not any(t.is_alive() for t in self._threads):
                self._stop.set()
                raise self._errors[0] if self._errors else RuntimeError(
                    "the workers ended during the unmeasured batches")
            time.sleep(0.01)
        run["prefill_s"] = time.monotonic() - t

    def window(self, seconds: float) -> None:
        """The workers are already posting: the window is `seconds` of
        their steady flow. A row counts when its acknowledgement arrived
        inside it, so the batches in flight when it opens count and those
        in flight when it closes do not."""
        self.t_start_ns = time.time_ns()
        self.t_end_ns = self.t_start_ns + int(seconds * 1e9)
        time.sleep(seconds)
        self._stop.set()
        for th in self._threads:
            th.join()
        if self._errors:
            raise self._errors[0]
        if self.next >= len(self.batches):
            raise RuntimeError(
                f"the window outran the {len(self.batches)} encoded "
                "batches: a new mix needs more extra_ticks")
        for rec in self.records:
            rec["in_window"] = (self.t_start_ns < rec["t_ack_ns"]
                                <= self.t_end_ns)
        self.ctx.run["window_s"] = seconds
        if self.ctx.perturb == "lost-batch":
            # an acknowledgement for rows the server never got
            _body, first, rows = self.batches[self._take()]
            self.records.append({
                "i": -1, "worker": -1, "first_row": first, "rows": rows,
                "t_send_ns": self.t_end_ns, "error": None, "ack_ms": 0.0,
                "t_ack_ns": self.t_end_ns, "in_window": False})

    def spans(self) -> list:
        return [("ingest_window", self.t_start_ns, self.t_end_ns),
                ("read_back", self.t_end_ns, self.t_read_back_ns)]

    def _read_back(self, http: Http, when: str) -> dict:
        """count(*) and sum() of two fields per written tick, a device
        statement, against the float64 reference of the acknowledged
        batches."""
        ctx, ds = self.ctx, self.ctx.ds
        ti, f0, f1 = ds.time_index, ds.field_names[0], ds.field_names[1]
        sql = (f"SELECT date_bin(INTERVAL '{ds.tick_ms // 1000} second', "
               f"{ti}) AS tick, count(*), sum({f0}), sum({f1}) FROM "
               f"{ds.table} WHERE {ti} >= {ds.end_ms} "
               "GROUP BY tick ORDER BY tick")
        acked = np.zeros(ds.extra_ticks * ds.hosts, dtype=bool)
        for rec in self.records:
            if rec["error"] is None:
                acked[rec["first_row"]:rec["first_row"] + rec["rows"]] = True
        acked = acked.reshape(ds.extra_ticks, ds.hosts)
        want_count, want_sums = {}, {}
        for k in range(ds.extra_ticks):
            n = int(acked[k].sum())
            if n:
                stamp = ds.ms(ds.ticks + k)
                want_count[stamp] = [n]
                want_sums[stamp] = ds.data[ds.ticks + k][acked[k], :2].sum(
                    axis=0)
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
        log(f"check read-back {when}: {int(acked.sum())} acknowledged rows "
            f"in {len(want_count)} ticks; count max_abs_err "
            f"{counts['max_abs_err']} (limit 0), sum max_rel_err "
            f"{sums['max_rel_err']} (limit "
            f"{ctx.mix['sum_tolerance']['rtol']:g}); dispatch {dispatch!r} "
            f"(wanted {ctx.mix['dispatch']!r}); first statement "
            f"{explain_s:.1f} s -> {'ok' if ok else 'FAILED'} "
            f"{counts['why']} {sums['why']}")
        return {"ok": ok, "rows": int(acked.sum()), "dispatch": dispatch,
                "count": counts, "sums": sums, "stages": stages,
                "explain_s": explain_s}

    def after_window(self) -> None:
        """The first read-back is the only device work of the cell, so a
        traced run keeps the profiler on through it."""
        self.first = self._read_back(self.ctx.http(), "before the crash")
        self.t_read_back_ns = time.time_ns()

    def check(self) -> dict:
        ctx, run = self.ctx, self.ctx.run
        t = time.monotonic()
        ctx.server.kill()
        ctx.server.start()
        ctx.server.wait_ready()
        run["restart_s"] = time.monotonic() - t
        second = self._read_back(ctx.http(), "after SIGKILL + restart")
        run["restart_to_answer_s"] = time.monotonic() - t
        failed = sum(r["error"] is not None for r in self.records)
        for rec in self.records:
            rec["ok"] = rec["error"] is None
        run["batches"] = self.records
        run["read_back"] = {"before_crash": self.first,
                            "after_restart": second}
        window = [r for r in self.records if r["in_window"]]
        return {"correct": bool(self.first["ok"] and second["ok"]
                                and failed == 0),
                "attempted": len(window),
                "failed": sum(not r["ok"] for r in window)}


LOOPS = {"statements": StatementLoop, "ingest": IngestLoop}
