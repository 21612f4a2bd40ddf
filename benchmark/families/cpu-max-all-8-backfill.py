"""`cpu-max-all-8-backfill`: TSBS cpu-only `cpu-max-all-8` (max of all 10
metrics by hour over 8 whole hours, 8 hosts) for 8 of the hosts whose
missing hour arrives late (`generators/tsbs-cpu-outage.py`), over the last
8 loaded hours: the returning hosts' panel. The narrowed launch reads base
ranges with a hole in them and the ranges of a tail that fills it. The
loop kind that sends it (`loops/backfill.py`) bounds every answer: the
backlog rows acknowledged before the send (`must`) and, a body each, the
rows sent before the answer came (`flights`). A body becomes visible
whole, so the answer has to be the maxima over the loaded rows, `must` and
some of the bodies in flight: `settle` holds it to the nearest of those,
and the tolerance is `cpu-max-all-8`'s; a value between the bounds that no
such state of the table gives is as wrong as one outside them. Without
bounds (set-up's warm statements, `control.py`) it is the loaded rows
alone."""

import itertools

import numpy as np

from benchlib.tsbs import CpuMaxAll


class CpuMaxAllBackfill(CpuMaxAll):
    backfill = True     # the loop has to bound each answer (`settle`)

    def draw(self, rng, ds):
        tph = ds.ticks_per_hour
        span = min(8 * tph, ds.ticks - ds.ticks % tph or ds.ticks)
        lo = ds.ticks - ds.ticks % tph - span
        hosts = [int(h) for h in rng.choice(ds.late, self.nhosts,
                                            replace=False)]
        return {"lo": lo, "hi": lo + span, "hosts": hosts}

    def _over(self, p, ds, present):
        """The maxima over the loaded rows and the backlog rows `present`
        [late hosts, gap ticks] names."""
        tph = ds.ticks_per_hour
        j = np.searchsorted(ds.late, p["hosts"])
        there = np.ones((ds.ticks, len(j)), dtype=bool)
        there[ds.gap_lo:ds.gap_hi] = present[j].T
        out = {}
        for lo in range(p["lo"], p["hi"], tph):
            hi = min(lo + tph, p["hi"])
            block = np.where(there[lo:hi, :, None],
                             ds.data[lo:hi][:, p["hosts"]], -np.inf)
            out[ds.ms(lo)] = block.max(axis=(0, 1))
        return out

    def reference(self, p, ds):
        if "expect" in p:
            return {k: np.array(v) for k, v in p["expect"]}
        return self._over(p, ds, np.zeros((len(ds.late), ds.gap_ticks),
                                          dtype=bool))

    #: more bodies in flight than this and only their prefixes are tried
    MAX_FLIGHTS = 10

    def settle(self, got, ds, p, must, flights=()):
        """-> the params whose reference this answer is held to. A maximum
        over a union is the largest of the parts' maxima, so the bodies
        are read once each."""
        base = self._over(p, ds, must)
        stamps = sorted(base)

        def table(maxima):
            return np.array([maxima[stamp] for stamp in stamps])

        parts = [table(self._over(p, ds, must | rows)) for rows in flights]
        k = len(parts)
        subsets = [tuple(range(r)) for r in range(k + 1)]
        if k <= self.MAX_FLIGHTS:
            subsets += [some for r in range(1, k + 1)
                        for some in itertools.combinations(range(k), r)
                        if some not in subsets]
        want = [np.maximum.reduce([table(base), *(parts[i] for i in some)])
                for some in subsets]
        best = 0
        if set(got) == set(stamps):
            answer = table(got)
            best = int(np.argmin([np.abs(w - answer).max() for w in want]))
        return dict(p, expect=tuple(
            (stamp, tuple(v.tolist()))
            for stamp, v in zip(stamps, want[best])))


FAMILY = CpuMaxAllBackfill("cpu-max-all-8-backfill", 8, "http")
