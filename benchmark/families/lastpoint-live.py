"""`lastpoint-live`: TSBS cpu-only `lastpoint` (the last usage_user of every
host, as last() GROUP BY hostname, over MySQL) while the next ticks are
being written. What a host's answer may be depends on what had been
acknowledged when the statement was sent, so the loop kind that sends it
(`loops/mixed.py`) bounds every answer: the newest tick whose row of that
host was acknowledged before the send (`k_lo`), and the newest whose body
had been sent before the answer came (`k_hi`). `match` finds, per host,
the tick in that range whose written value is nearest the answer; the
reference is then that tick's value and the tolerance `lastpoint`'s, so an
answer older than `k_lo` (or one that was never written) is off by a step
of the walk. Without bounds (set-up's warm statements, `control.py`) it is
`lastpoint` over the loaded ticks."""

import numpy as np

from benchlib.tsbs import LastPoint


class LastPointLive(LastPoint):
    live = True         # the loop has to bound each answer (`match`)

    def match(self, got: dict, ds, k_lo, k_hi):
        """got {hostname: [value]}; k_lo, k_hi int arrays of ticks per
        host -> the tick per host in [k_lo, k_hi] whose written value is
        nearest the answer (k_lo for a host the answer leaves out)."""
        hosts = np.arange(ds.hosts)
        answer = np.array([got.get(name, [np.nan])[0]
                           for name in ds.hostnames], dtype=np.float64)
        best, err = k_lo.copy(), np.full(ds.hosts, np.inf)
        for d in range(int((k_hi - k_lo).max(initial=0)) + 1):
            k = np.minimum(k_lo + d, k_hi)
            e = np.abs(ds.data[k, hosts, 0] - answer)
            closer = e < err
            best[closer], err[closer] = k[closer], e[closer]
        return best

    def reference(self, p, ds):
        ticks = p.get("ticks")
        if ticks is None:
            return super().reference(p, ds)
        return {name: ds.data[ticks[h], h, :1]
                for h, name in enumerate(ds.hostnames)}


FAMILY = LastPointLive("lastpoint-live", "mysql")
