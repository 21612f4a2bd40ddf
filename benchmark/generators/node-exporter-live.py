"""The node_exporter fleet of `generators/node-exporter.py` as an agent
scrapes and remote-writes it: the same targets, tables, labels and value
processes from --seed, and what differs is when a target is scraped and
that the scrapes go on after the load.

**An offset a target.** An agent spreads its scrapes over the interval:
target i is scraped at `tick * interval + offset[i]`, through the loaded
history and after it. The offsets are a seeded permutation of `hosts`
slots of `interval / hosts` each (10 ms at 1,000 targets), taken at the
slot's middle, so no sample lies on a whole second (a statement's steps
do, and the far edge of a lookback is closed in the program and open in
Prometheus 3); a replaced target's successor takes its slot. Every one
second slice of the schedule therefore holds exactly `hosts / 10`
targets.

**The scrapes after the load.** `extra_ticks` more scrape rounds are made
with the loaded ones, in one pass: every walk and every counter goes on
(a value process is not restarted at the seam), the churn event due at the
first live round is in (its new targets' series exist only in what remote
write brings), and a reboot may fall on either side. `rows`, `load` and
`arrow_chunks` are the loaded `ticks`; `samples(name)` holds every round
(`first` / `last` over all of them, `offset` a series): a reference that
evaluates at steps up to a statement's `end` reads no sample after it.

**Blocks.** `blocks()` cuts the live rounds into consecutive one-second
slices of the schedule: block b holds the round `b // 10`'s scrapes of the
targets whose slot lies in slice `b % 10`, one sample a series, all seven
tables: a snappy prompb `WriteRequest` as vmagent posts it (labels sorted
by name, `__name__` first). A series' label bytes are the same in every
block, so a slice's body is a template made once and a block fills in its
values and timestamps. No late sample, no re-sent block.
"""

from __future__ import annotations

import numpy as np

from benchlib.spec import _load_module
from benchlib.writelib import field as _field, varint as _varint

_base = _load_module("generators", "node-exporter")
Samples = _base.Samples


class LiveSamples(Samples):
    """`Samples` on a grid every series holds shifted by its target's
    `offset` (ms): series s's sample k lies at `times[k] + offset[s]`."""

    def __init__(self, name, times, values, first, last, labels, offset):
        super().__init__(name, times, values, first, last, labels)
        self.offset = offset


#: a prompb.Sample: field 1 the value (fixed64), field 2 the timestamp
#: (varint; six bytes for a time in ms from 2004 to 2109)
_SAMPLE_LEN = 1 + 8 + 1 + 6


class Dataset(_base.Dataset):
    def __init__(self, config: dict, seed: int, extra_ticks: int = 0,
                 scale: int = None, ticks: int = None):
        loaded = int(ticks if ticks is not None
                     else config["duration_s"] // config["log_interval_s"])
        # one pass over the loaded and the live rounds: the parent makes
        # `self.ticks` rounds
        self.total_ticks = loaded + int(extra_ticks)
        super().__init__(config, seed, 0, scale, self.total_ticks)
        self.ticks, self.extra_ticks = loaded, int(extra_ticks)
        if self.hosts % 10 or self.tick_ms % self.hosts:
            raise ValueError("the targets do not fill ten slices of slots")
        self._offsets(seed)

    # ---- when a target is scraped --------------------------------------
    def _offsets(self, seed: int) -> None:
        """`slot[i]` / `offset_ms[i]` of every target that ever lives: a
        permutation for the first `hosts`, then a successor its
        predecessor's (paired in index order at each event)."""
        # a stream of its own: the fleet's draws stay the parent's
        rng = np.random.default_rng([int(seed), 0x11FE5C4A])
        n = len(self.instances)
        slot = np.full(n, -1, dtype=np.int64)
        slot[:self.hosts] = rng.permutation(self.hosts)
        for tick in np.unique(self.first[self.first > 0]):
            gone = np.flatnonzero(self.last == tick)
            new = np.flatnonzero(self.first == tick)
            slot[new] = slot[gone]
        width = self.tick_ms // self.hosts
        self.slot = slot
        self.offset_ms = slot * width + width // 2

    # ---- the parent's surface, over the loaded rounds ---------------------
    def _block(self, table):
        return self.data[table.offset:table.offset + table.series
                         * self.total_ticks].reshape(table.series,
                                                     self.total_ticks)

    @property
    def rows(self) -> int:
        alive = np.minimum(self.last, self.ticks) \
            - np.minimum(self.first, self.ticks)
        return int(sum(alive[t.instance_of].sum()
                       for t in self.tables.values()))

    def samples(self, name: str) -> LiveSamples:
        t = self.tables[name]
        times = self.t0_ms + np.arange(self.total_ticks, dtype=np.int64) \
            * self.tick_ms
        return LiveSamples(name, times, self._block(t),
                           self.first[t.instance_of],
                           self.last[t.instance_of], t.labels,
                           self.offset_ms[t.instance_of])

    def arrow_chunks(self, chunk_ticks: int):
        """The parent's chunks of the loaded rounds, every sample at its
        target's offset."""
        import pyarrow as pa
        for t in self.tables.values():
            block = self._block(t)
            first = np.minimum(self.first[t.instance_of], self.ticks)
            last = np.minimum(self.last[t.instance_of], self.ticks)
            offset = self.offset_ms[t.instance_of]
            dictionaries, codes = {}, {}
            for tag in t.label_names:
                uniq, inv = np.unique(t.labels[tag], return_inverse=True)
                dictionaries[tag] = pa.array(list(uniq), type=pa.string())
                codes[tag] = inv.astype(np.int32)
            for a in range(0, self.ticks, chunk_ticks):
                b = min(a + chunk_ticks, self.ticks)
                lo, hi = np.maximum(first, a), np.minimum(last, b)
                counts = np.maximum(hi - lo, 0)
                n = int(counts.sum())
                if n == 0:
                    continue
                series = np.repeat(np.arange(t.series), counts)
                starts = np.cumsum(counts) - counts
                tick = np.arange(n) - starts[series] + lo[series]
                columns = {tag: pa.DictionaryArray.from_arrays(
                    pa.array(codes[tag][series]), dictionaries[tag])
                    for tag in t.label_names}
                columns[self.time_index] = self.t0_ms + tick.astype(
                    np.int64) * self.tick_ms + offset[series]
                columns[self.value_field] = block[series, tick]
                yield t.name, t.label_names, pa.table(columns)

    # ---- the live rounds as remote-write blocks ----------------------------
    @property
    def blocks_per_round(self) -> int:
        return self.tick_ms // 1000

    def block_start_ms(self, b: int) -> int:
        """Where block b's one-second slice of the schedule begins."""
        return self.end_ms + b * 1000

    def block_series(self, b: int) -> dict:
        """-> {table name: (series [k], their round's tick)} of block b:
        the series of the targets that live in the block's round and are
        scraped in its slice."""
        tick = self.ticks + b // self.blocks_per_round
        per = self.hosts // self.blocks_per_round
        j = b % self.blocks_per_round
        targets = (self.first <= tick) & (tick < self.last) \
            & (self.slot >= j * per) & (self.slot < (j + 1) * per)
        return {name: (np.flatnonzero(targets[t.instance_of]), tick)
                for name, t in self.tables.items()}

    def _template(self, series_of: dict):
        """A slice's `WriteRequest` with nothing filled in, and where the
        values (8 bytes a series) and the timestamps (6) go. A series'
        message: its labels, sorted by name, then one sample."""
        parts, at, pos = [], [], 0
        for name, (series, _tick) in series_of.items():
            t = self.tables[name]
            names = sorted(t.label_names)
            for s in series.tolist():
                labels = _field(1, _field(1, b"__name__")
                                + _field(2, name.encode()))
                for label in names:
                    labels += _field(1, _field(1, label.encode()) + _field(
                        2, str(t.labels[label][s]).encode()))
                body = labels + _varint(2 << 3 | 2) + _varint(_SAMPLE_LEN)
                head = _varint(1 << 3 | 2) + _varint(
                    len(body) + _SAMPLE_LEN) + body + b"\x09"
                parts += [head, bytes(8), b"\x10", bytes(6)]
                at.append(pos + len(head))
                pos += len(head) + _SAMPLE_LEN - 1
        raw = np.frombuffer(b"".join(parts), dtype=np.uint8).copy()
        at = np.asarray(at, dtype=np.int64)
        return raw, at[:, None] + np.arange(8), at[:, None] + 9 + np.arange(6)

    def blocks(self) -> list:
        """-> [(body, first row, rows)] of the live rounds, in the
        schedule's order; `first row` numbers the samples through the
        blocks. A template a slice and alive set (the fleet changes at a
        churn event only)."""
        import pyarrow as pa
        codec = pa.Codec("snappy")
        out, first_row, templates = [], 0, {}
        for b in range(self.extra_ticks * self.blocks_per_round):
            series_of = self.block_series(b)
            tick = self.ticks + b // self.blocks_per_round
            key = (b % self.blocks_per_round,
                   int(((self.first > 0) & (self.first <= tick)).sum()))
            if key not in templates:
                templates[key] = self._template(series_of)
            raw, value_at, time_at = templates[key]
            raw = raw.copy()
            values, stamps = [], []
            for name, (series, tick) in series_of.items():
                t = self.tables[name]
                values.append(self._block(t)[series, tick])
                stamps.append(self.ms(tick)
                              + self.offset_ms[t.instance_of[series]])
            values = np.concatenate(values)
            stamps = np.concatenate(stamps).astype(np.uint64)
            raw[value_at] = values.astype("<f8").view(np.uint8).reshape(-1, 8)
            shifts = np.arange(6, dtype=np.uint64) * np.uint64(7)
            septets = (stamps[:, None] >> shifts) & np.uint64(0x7F)
            septets[:, :5] |= np.uint64(0x80)
            raw[time_at] = septets.astype(np.uint8)
            out.append((codec.compress(raw.tobytes(), asbytes=True),
                        first_row, len(values)))
            first_row += len(values)
        return out

    def newest_block_at(self, end_ms: int):
        """The newest block whose samples all lie at or before `end_ms`;
        None where no live block does."""
        b = (int(end_ms) - self.end_ms) // 1000 - 1
        return b if 0 <= b < self.extra_ticks * self.blocks_per_round \
            else None

    def without_block(self, b):
        """This fleet as a reader that missed block b would see it up to
        the block's second: the block's targets end before its round (a
        copy; `b` None: the fleet itself)."""
        if b is None:
            return self
        import copy
        tick = self.ticks + b // self.blocks_per_round
        per = self.hosts // self.blocks_per_round
        j = b % self.blocks_per_round
        out = copy.copy(self)
        out.last = np.where((self.slot >= j * per)
                            & (self.slot < (j + 1) * per),
                            np.minimum(self.last, tick), self.last)
        return out

    def block_samples(self, b: int) -> dict:
        """-> {table name: (timestamps [k], values [k])} of block b, for
        the read-back's reference."""
        out = {}
        for name, (series, tick) in self.block_series(b).items():
            t = self.tables[name]
            out[name] = (self.ms(tick)
                         + self.offset_ms[t.instance_of[series]],
                         self._block(t)[series, tick])
        return out

