"""TSBS devops `cpu-only` with a relay that lost its uplink: the data is
`benchlib/data.py`'s from --seed (the same `data[tick, host, field]`),
and what differs is what is loaded and in what order the rest is posted.

`late_hosts` hosts (drawn from the seed: the hosts behind one relay) lack
`gap_s` of their history inside the loaded span, ending
`gap_ends_before_load_end_s` before its end: the uplink is back, they tick
like every other host again, and the missing ticks lie *before* each of
these series' last loaded row. They sit in the relay's queue, which is
drained behind the live ticks in the order it was filled: tick by tick, a
tick's late hosts in file order. The queue's first tick is the one
*before* the gap, which the load holds (the relay never saw that body's
acknowledgement): overwrites of loaded rows with equal values.

`line_protocol_batches` makes the bodies the writers post: the ticks after
the load in TSBS file order and, while the queue lasts, `late_rows_per_body`
rows of it in every body. `bodies[i]` says what body i carries: its live
rows [live_first, + live_rows) numbered as `benchlib/data.py` numbers them
(tick-major from the first tick after the load) and its queue rows
[queue_first, + queue_rows), row q being queue tick q // late_hosts of
late host q % late_hosts. The values of the queue's ticks keep 4 decimals
for the late hosts, as every written tick does (a body stays under the
server's 1 MiB), and the load carries those same values.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa

from benchlib.data import Dataset as TsbsCpu
from benchlib.wire import flight_bulk_load


class Dataset(TsbsCpu):
    def __init__(self, config: dict, seed: int, extra_ticks: int = 0,
                 scale: int = None, ticks: int = None):
        super().__init__(config, seed, extra_ticks, scale, ticks)
        outage = dict(config["outage"])
        if scale is not None and scale != config["scale"]:
            outage.update(config["debug"].get("outage", {}))
        per_s = self.tick_ms // 1000
        self.gap_hi = self.ticks - int(
            outage["gap_ends_before_load_end_s"]) // per_s
        self.gap_lo = self.gap_hi - int(outage["gap_s"]) // per_s
        if not 0 < self.gap_lo < self.gap_hi < self.ticks:
            raise ValueError("the gap does not lie inside the loaded span")
        self.late_rows_per_body = int(outage["late_rows_per_body"])
        self.resend_every = int(outage["resend_every"])
        # a stream of its own: the walk's draws stay `benchlib/data.py`'s
        rng = np.random.default_rng([int(seed), 0x0074A6E])
        self.late = np.sort(rng.choice(
            self.hosts, int(outage["late_hosts"]), replace=False))
        #: queue tick j is this tick: the one before the gap, then the gap
        self.queue_ticks = np.arange(self.gap_lo - 1, self.gap_hi)
        self.queue_rows = len(self.queue_ticks) * len(self.late)
        sel = np.ix_(self.queue_ticks, self.late)
        self.data[sel] = np.round(self.data[sel], 4)
        self.bodies = []

    @property
    def gap_ticks(self) -> int:
        return self.gap_hi - self.gap_lo

    @property
    def rows(self) -> int:
        """The loaded rows: the span less the late hosts' gap."""
        return self.hosts * self.ticks - len(self.late) * self.gap_ticks

    def load(self, grpc_port: int, chunk_ticks: int) -> int:
        """As `benchlib/data.py` loads (host-major within a chunk), less
        the late hosts' rows inside the gap; -> acknowledged rows."""
        dictionaries, codes = {}, {}
        for tag in self.tag_names:
            uniq, inv = np.unique(np.array(self.tags[tag], dtype=object),
                                  return_inverse=True)
            dictionaries[tag] = pa.array(list(uniq), type=pa.string())
            codes[tag] = inv.astype(np.int32)
        nf = len(self.field_names)
        is_late = np.zeros(self.hosts, dtype=bool)
        is_late[self.late] = True

        def chunk(a: int):
            b = min(a + chunk_ticks, self.ticks)
            n = b - a
            ticks = np.arange(a, b)
            in_gap = (ticks >= self.gap_lo) & (ticks < self.gap_hi)
            keep = ~(is_late[:, None] & in_gap[None, :]).reshape(-1)
            block = self.data[a:b].transpose(1, 0, 2).reshape(
                self.hosts * n, nf)[keep]
            columns = {tag: pa.DictionaryArray.from_arrays(
                pa.array(np.repeat(codes[tag], n)[keep]), dictionaries[tag])
                for tag in self.tag_names}
            columns[self.time_index] = np.tile(
                self.t0_ms + ticks.astype(np.int64) * self.tick_ms,
                self.hosts)[keep]
            for i, f in enumerate(self.field_names):
                columns[f] = np.ascontiguousarray(block[:, i])
            return pa.table(columns)

        acked = 0
        starts = list(range(0, self.ticks, chunk_ticks))
        with ThreadPoolExecutor(max_workers=1) as pool:
            nxt = pool.submit(chunk, starts[0])
            for k in range(len(starts)):
                table = nxt.result()
                if k + 1 < len(starts):
                    nxt = pool.submit(chunk, starts[k + 1])
                acked += flight_bulk_load(
                    grpc_port, self.table, table, self.tag_names,
                    self.time_index)
        return acked

    def _lines(self, ticks, hosts) -> list:
        """One line-protocol line a (tick, host), tick-major."""
        heads = {int(h): self.table + "," + ",".join(
            f"{tag}={self.tags[tag][h]}" for tag in self.tag_names) + " "
            for h in hosts}
        names = [f + "=" for f in self.field_names]
        lines = []
        for t in ticks:
            stamp = f" {self.ms(t)}"
            values = self.data[t].tolist()
            for h in hosts:
                lines.append(heads[int(h)] + ",".join(
                    [n + repr(v) for n, v in zip(names, values[h])])
                    + stamp)
        return lines

    def line_protocol_batches(self, batch_rows: int) -> list:
        """-> [(body bytes, first live row, rows in the body)]; what each
        body carries is in `bodies`. Field values are `repr(float)` of
        4-decimal values, which round-trips them exactly, so a queue row of
        the tick before the gap equals the loaded row bit for bit."""
        live = self._lines(range(self.ticks, self.ticks + self.extra_ticks),
                           range(self.hosts))
        queue = self._lines(self.queue_ticks.tolist(), self.late.tolist())
        out, self.bodies = [], []
        a = q = 0
        while a < len(live):
            nq = min(self.late_rows_per_body, len(queue) - q)
            nl = min(batch_rows - nq, len(live) - a)
            body = "\n".join(live[a:a + nl] + queue[q:q + nq]).encode()
            out.append((body, a, nl + nq))
            self.bodies.append({"live_first": a, "live_rows": nl,
                                "queue_first": q, "queue_rows": nq})
            a, q = a + nl, q + nq
        return out
