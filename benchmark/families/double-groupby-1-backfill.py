"""`double-groupby-1-backfill`: TSBS cpu-only `double-groupby-1` (avg of one
metric by hostname and hour) over the 12 loaded hours of a fleet of which
some hosts have an hour missing that arrives late (`generators/
tsbs-cpu-outage.py`): the gap lies inside the statement's range, so what
a late host's two hours average depends on how much of its backlog the
table held when the statement ran. The loop kind that sends it
(`loops/backfill.py`) bounds every answer: `must[j, g]`, gap tick g of
late host j was acknowledged before the send, and `flights`, the rows of
each body that had been sent before the answer came and was not
acknowledged before the send, in the order the queue is drained. A body
is one write and becomes visible whole, and a statement reads one state of
the table, so `settle` looks for the bodies in flight that the answer
shows, the same ones for every late host: a prefix of them in the queue's
order, else any of them (two bodies may commit in the other order:
counted, `unordered`). The reference is then the average over the loaded
rows, `must` and exactly those bodies' rows, and the tolerance
`double-groupby-1`'s, so an answer that lacks an acknowledged row, counts
one twice, shows one never sent or half a body is off by a row's share of
an hour. Without bounds (set-up's warm statements, `control.py`) it is the
loaded rows alone."""

import itertools

import numpy as np

from benchlib.tsbs import DoubleGroupby


def pack(present) -> bytes:
    return np.packbits(present.reshape(-1)).tobytes()


class DoubleGroupbyBackfill(DoubleGroupby):
    backfill = True     # the loop has to bound each answer (`settle`)
    #: 1 where the last `settle` found no prefix of the bodies in flight
    #: that fits the answer, only others of them
    unordered = 0

    @staticmethod
    def present(p, ds):
        """params -> bool [late hosts, gap ticks]: the backlog rows the
        reference holds (none without bounds)."""
        shape = (len(ds.late), ds.gap_ticks)
        if "present" not in p:
            return np.zeros(shape, dtype=bool)
        bits = np.unpackbits(np.frombuffer(p["present"], dtype=np.uint8))
        return bits[:shape[0] * shape[1]].astype(bool).reshape(shape)

    def draw(self, rng, ds):
        return {"lo": 0, "hi": ds.ticks}

    def _late_hours(self, ds):
        """-> (hours the gap touches, per gap tick its index among them,
        sums [late, hours, metrics] and counts [hours] of the late hosts'
        loaded rows in those hours)."""
        tph = ds.ticks_per_hour
        hour_of = np.arange(ds.gap_lo, ds.gap_hi) // tph
        hours = np.unique(hour_of)
        sums = np.zeros((len(ds.late), len(hours), self.metrics))
        counts = np.zeros(len(hours))
        for u, hour in enumerate(hours):
            ticks = np.arange(hour * tph, min((hour + 1) * tph, ds.ticks))
            ticks = ticks[(ticks < ds.gap_lo) | (ticks >= ds.gap_hi)]
            sums[:, u] = ds.data[ticks][:, ds.late, :self.metrics].sum(0)
            counts[u] = len(ticks)
        return hours, hour_of - hours[0], sums, counts

    def _averages(self, ds, present, late_hours, hosts=slice(None)):
        """-> [late hosts (those `hosts` names), hours, metrics]: the
        averages with these rows in."""
        hours, which, sums, counts = late_hours
        x = ds.data[ds.gap_lo:ds.gap_hi][:, ds.late[hosts], :self.metrics]
        x = x.transpose(1, 0, 2)                        # [late, gap, m]
        sums, present = sums[hosts], present[hosts]
        out = np.empty_like(sums)
        for u in range(len(hours)):
            w = present & (which == u)[None, :]
            n = counts[u] + w.sum(axis=1)
            out[:, u] = (sums[:, u] + (x * w[:, :, None]).sum(axis=1)) \
                / np.maximum(n, 1)[:, None]
        return out

    def reference(self, p, ds):
        tph = ds.ticks_per_hour
        out = {}
        late_hours = self._late_hours(ds)
        hours = late_hours[0]
        first = p["lo"] - p["lo"] % tph
        for lo in range(first, p["hi"], tph):
            a, b = max(lo, p["lo"]), min(lo + tph, p["hi"])
            mean = ds.data[a:b, :, :self.metrics].mean(axis=0)
            if lo // tph in hours:
                if (a, b) != (lo, min(lo + tph, ds.ticks)):
                    raise ValueError("the range cuts an hour of the gap")
                mean[ds.late] = self._averages(
                    ds, self.present(p, ds), late_hours)[
                        :, int(lo // tph - hours[0])]
            stamp = ds.ms(lo)
            for h, name in enumerate(ds.hostnames):
                out[(name, stamp)] = mean[h]
        return out

    #: more bodies in flight than this and only their prefixes are tried
    MAX_FLIGHTS = 10

    def settle(self, got, ds, p, must, flights=()):
        """-> the params whose reference this answer is held to: the one
        over `must` and the bodies in flight that fits the answer best."""
        late_hours = self._late_hours(ds)
        hours = late_hours[0]
        tph = ds.ticks_per_hour
        nan = [np.nan] * self.metrics
        answer = np.array([[got.get((ds.hostnames[h], ds.ms(int(hour) * tph)),
                                    nan) for hour in hours]
                           for h in ds.late], dtype=np.float64)

        def off(present, hosts=slice(None)):
            """The largest relative error over these late hosts' hours."""
            e = np.abs(self._averages(ds, present, late_hours, hosts)
                       - answer[hosts]) \
                / np.maximum(np.abs(answer[hosts]), 1e-300)
            return float(np.where(np.isnan(e), np.inf, e).max(initial=0.0))

        def with_bodies(some):
            return np.logical_or.reduce([must, *(flights[i] for i in some)])

        k = len(flights)
        prefixes = [tuple(range(r)) for r in range(k + 1)]
        tried = [(off(with_bodies(some)), some) for some in prefixes]
        err, best = min(tried)
        self.unordered = 0
        if err > self.tolerance["rtol"] and 1 < k <= self.MAX_FLIGHTS:
            # no prefix fits: one host's hours rule out most of the other
            # subsets, and only what is left is held to every host
            probe = slice(0, 1)
            for r in range(1, k + 1):
                for some in itertools.combinations(range(k), r):
                    if some in prefixes or off(
                            with_bodies(some), probe) > self.tolerance["rtol"]:
                        continue
                    e = off(with_bodies(some))
                    if e < err:
                        err, best, self.unordered = e, some, 1
        return dict(p, present=pack(with_bodies(best)))


FAMILY = DoubleGroupbyBackfill("double-groupby-1-backfill", 1, "http")
