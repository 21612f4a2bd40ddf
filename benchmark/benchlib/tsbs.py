"""The TSBS devops `cpu-only` statement families (timescale/tsbs,
cmd/tsbs_generate_queries, as recalled) as builders: a file under
`benchmark/families/` is one call of one of these. Each family draws its
parameters from a numpy Generator, renders the statement, evaluates the
same statement in float64 numpy over the generated rows (the plain
reference: it imports nothing of the program), and parses an answer into
the same {key: [floats]} form.

Tolerances are `chip_smoke.py`'s TOL (PR 21): one f32 rounding of a value
in [0, 100] is <= 100 * 2^-25 = 3e-6, and bf16 is off by up to 0.25.
"""

from __future__ import annotations

import calendar
import time

DEVICE_RESIDENT = "device-resident (scan cache)"

TOL = {
    "max": dict(rtol=0.0, atol=1e-5),
    "last": dict(rtol=0.0, atol=1e-5),
    # f32 accumulation of <= 4320 values: ~3e-7 relative measured on the
    # chip; bf16 mirrors (4e-3) fail
    "avg": dict(rtol=1e-5, atol=0.0),
}


def to_ms(v) -> int:
    """HTTP returns epoch ms, MySQL 'YYYY-MM-DD HH:MM:SS.mmm' (UTC)."""
    if isinstance(v, (int, float)):
        return int(v)
    whole = calendar.timegm(time.strptime(v[:19], "%Y-%m-%d %H:%M:%S"))
    return whole * 1000 + int(v[20:23] or 0)


def floats(row):
    return [float(v) for v in row]


def by_time(rows):
    return {to_ms(r[0]): floats(r[1:]) for r in rows}


def by_host_time(rows):
    return {(r[0], to_ms(r[1])): floats(r[2:]) for r in rows}


def by_host(rows):
    return {r[0]: floats(r[1:]) for r in rows}


class Family:
    """What the harness needs of a family. `via` is the wire it is sent
    on, `agg` names its tolerance in TOL, `dispatch` the executed
    dispatch its EXPLAIN ANALYZE must show."""

    name = ""
    via = "http"
    agg = "max"
    dispatch = DEVICE_RESIDENT

    @property
    def tolerance(self) -> dict:
        return TOL[self.agg]

    def draw(self, rng, ds) -> dict:
        return {}

    def sql(self, p: dict, ds) -> str:
        raise NotImplementedError

    def reference(self, p: dict, ds) -> dict:
        raise NotImplementedError

    def parse(self, rows, ds) -> dict:
        raise NotImplementedError


def _window(rng, ds, ticks_long: int):
    """A minute-aligned [lo, hi) of that many ticks inside the load."""
    per_minute = 60_000 // ds.tick_ms
    span = min(ticks_long, ds.ticks)
    minutes = int(rng.integers(0, (ds.ticks - span) // per_minute + 1))
    lo = minutes * per_minute
    return lo, lo + span


def _in_list(ds, hosts) -> str:
    return ", ".join(f"'{ds.hostnames[h]}'" for h in hosts)


class SingleGroupby(Family):
    """single-groupby-M-H-T: max of the first M metrics of H random hosts
    per minute over a random T hours."""
    agg = "max"

    def __init__(self, name, metrics, hosts, hours, via):
        self.name, self.metrics, self.nhosts = name, metrics, hosts
        self.hours, self.via = hours, via

    def draw(self, rng, ds):
        lo, hi = _window(rng, ds, self.hours * ds.ticks_per_hour)
        hosts = [int(h) for h in rng.choice(ds.hosts, self.nhosts,
                                            replace=False)]
        return {"lo": lo, "hi": hi, "hosts": hosts}

    def sql(self, p, ds):
        ti = ds.time_index
        return (f"SELECT date_bin(INTERVAL '1 minute', {ti}) AS minute, "
                + ", ".join(f"max({f})"
                            for f in ds.field_names[:self.metrics])
                + f" FROM {ds.table} WHERE hostname IN "
                f"({_in_list(ds, p['hosts'])}) AND {ti} >= {ds.ms(p['lo'])}"
                f" AND {ti} < {ds.ms(p['hi'])} "
                "GROUP BY minute ORDER BY minute")

    def reference(self, p, ds):
        per = 60_000 // ds.tick_ms
        block = ds.data[p["lo"]:p["hi"]][:, p["hosts"], :self.metrics]
        best = block.reshape(-1, per, len(p["hosts"]),
                             self.metrics).max(axis=(1, 2))
        return {ds.ms(p["lo"] + per * k): best[k] for k in range(len(best))}

    def parse(self, rows, ds):
        return by_time(rows)


class CpuMaxAll(Family):
    """cpu-max-all-H: max of all metrics of H random hosts per hour over
    a random 8 whole hours."""
    agg = "max"

    def __init__(self, name, hosts, via):
        self.name, self.nhosts, self.via = name, hosts, via

    def draw(self, rng, ds):
        tph = ds.ticks_per_hour
        span = min(8 * tph, ds.ticks - ds.ticks % tph or ds.ticks)
        lo, _ = _window(rng, ds, span)
        lo -= lo % tph                       # hour buckets: whole hours
        hosts = [int(h) for h in rng.choice(ds.hosts, self.nhosts,
                                            replace=False)]
        return {"lo": lo, "hi": lo + span, "hosts": hosts}

    def sql(self, p, ds):
        ti = ds.time_index
        return (f"SELECT date_bin(INTERVAL '1 hour', {ti}) AS hour, "
                + ", ".join(f"max({f})" for f in ds.field_names)
                + f" FROM {ds.table} WHERE hostname IN "
                f"({_in_list(ds, p['hosts'])}) AND {ti} >= {ds.ms(p['lo'])}"
                f" AND {ti} < {ds.ms(p['hi'])} GROUP BY hour ORDER BY hour")

    def reference(self, p, ds):
        tph = ds.ticks_per_hour
        out = {}
        for lo in range(p["lo"], p["hi"], tph):
            hi = min(lo + tph, p["hi"])
            out[ds.ms(lo)] = ds.data[lo:hi][:, p["hosts"]].max(axis=(0, 1))
        return out

    def parse(self, rows, ds):
        return by_time(rows)


class DoubleGroupby(Family):
    """double-groupby-M: avg of the first M metrics GROUP BY hostname and
    hour over 12 h (cut to the loaded span where that is shorter)."""
    agg = "avg"

    def __init__(self, name, metrics, via):
        self.name, self.metrics, self.via = name, metrics, via
        self.full_scan_fields = metrics     # every row, this many fields

    def draw(self, rng, ds):
        lo, hi = _window(rng, ds, 12 * ds.ticks_per_hour)
        return {"lo": lo, "hi": hi}

    def sql(self, p, ds):
        ti = ds.time_index
        return (f"SELECT hostname, date_bin(INTERVAL '1 hour', {ti}) AS "
                "hour, " + ", ".join(f"avg({f})"
                                     for f in ds.field_names[:self.metrics])
                + f" FROM {ds.table} WHERE {ti} >= {ds.ms(p['lo'])} AND "
                f"{ti} < {ds.ms(p['hi'])} "
                "GROUP BY hostname, hour ORDER BY hostname, hour")

    def reference(self, p, ds):
        tph = ds.ticks_per_hour
        out = {}
        first = p["lo"] - p["lo"] % tph
        for lo in range(first, p["hi"], tph):
            a, b = max(lo, p["lo"]), min(lo + tph, p["hi"])
            mean = ds.data[a:b, :, :self.metrics].mean(axis=0)
            stamp = ds.ms(lo)
            for h, name in enumerate(ds.hostnames):
                out[(name, stamp)] = mean[h]
        return out

    def parse(self, rows, ds):
        return by_host_time(rows)


class LastPoint(Family):
    """lastpoint as `last(usage_user) GROUP BY hostname` (the
    row-returning TSBS form leaves the device plan today: `reduced`)."""
    agg = "last"

    full_scan_fields = 1                    # every row of one field

    def __init__(self, name, via):
        self.name, self.via = name, via

    def sql(self, p, ds):
        return (f"SELECT hostname, last({ds.field_names[0]}) FROM "
                f"{ds.table} GROUP BY hostname ORDER BY hostname")

    def reference(self, p, ds):
        return {name: ds.data[ds.ticks - 1, h, :1]
                for h, name in enumerate(ds.hostnames)}

    def parse(self, rows, ds):
        return by_host(rows)
