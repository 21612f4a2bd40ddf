"""A fleet of node_exporter targets from --seed, written the way upstream
GreptimeDB documents Prometheus remote write: one table per metric name,
every label a tag column of the primary key (in sorted order, as
`servers/prometheus.py:series_to_inserts` makes them), the time index
`greptime_timestamp`, one DOUBLE field `greptime_value`. Everything a
configuration fixes is read from its file (`configs/prom-node-1k.json`).

What a statements-only mix needs of the `Dataset` surface the README
lists, and this class has: `rows`, `ticks`, `extra_ticks`, `hosts`,
`data`, `ms(tick)`, `end_ms`, `time_index`, `tick_ms`,
`create_table_sql()` and `load(grpc_port, chunk_ticks)` (which first
makes sure the program can run the deployment's cells at all:
`require_analyzed_tql`). It has no
`table`, `tag_names`, `field_names`, `hostnames` or `ticks_per_hour` (one
table's, which the TSBS families read) and no `line_protocol_batches`
(only a writing loop calls it). Its families read a table through
`samples(name)`.

`data` is ONE float64 array of every sample value of every table, dense
over [series, tick] per table (a series that has ended or not begun has
values there too; `first` / `last` say which ticks exist and are loaded).
`control.py` makes its bf16 mirror by rounding `data` and calling each
family's `reference()` again, so nothing here keeps a second copy of a
value: `samples()` cuts its view from `data` on every call.
"""

from __future__ import annotations

import calendar
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

DAY_S = 86_400.0


def parse_utc_ms(stamp: str) -> int:
    return calendar.timegm(time.strptime(stamp, "%Y-%m-%dT%H:%M:%SZ")) * 1000


class Samples:
    """One metric's series on the shared scrape ticks: `values[s, k]` is
    the sample of series `s` at `times[k]` where `first[s] <= k <
    last[s]`, `labels[name][s]` its label values."""

    def __init__(self, name, times, values, first, last, labels):
        self.name, self.times, self.values = name, times, values
        self.first, self.last, self.labels = first, last, labels


class Table:
    """One metric: its label names (sorted, the primary key), per series
    the label values and the instance, and where its values lie in
    `Dataset.data`."""

    def __init__(self, name: str, kind: str, instance_of, labels: dict):
        self.name, self.kind = name, kind
        self.instance_of = np.asarray(instance_of, dtype=np.int64)
        self.labels = {k: np.asarray(v, dtype=object)
                       for k, v in sorted(labels.items())}
        self.label_names = list(self.labels)
        self.series = len(self.instance_of)
        self.offset = 0


class Dataset:
    def __init__(self, config: dict, seed: int, extra_ticks: int = 0,
                 scale: int = None, ticks: int = None):
        self.config = config
        self.time_index = config["time_index"]
        self.value_field = config["value_field"]
        self.t0_ms = parse_utc_ms(config["start"])
        self.tick_ms = int(config["log_interval_s"]) * 1000
        self.hosts = int(scale if scale is not None else config["scale"])
        self.ticks = int(ticks if ticks is not None
                         else config["duration_s"]
                         // config["log_interval_s"])
        self.extra_ticks = int(extra_ticks)     # nothing here writes
        #: the configuration's debug size runs on another platform: the
        #: families state their dispatch for the chip and swap its name
        self.debug = self.hosts == int(config["debug"]["scale"])
        rng = np.random.default_rng(seed)
        self._instances(rng)
        self._tables()
        total = 0
        for t in self.tables.values():
            t.offset = total
            total += t.series * self.ticks
        self.data = np.empty(total, dtype=np.float64)
        self._values(rng)

    # ---- the fleet ----------------------------------------------------
    def _instances(self, rng) -> None:
        """`hosts` targets live at any moment; at every churn interval
        `percent` of them are replaced: their series end, as many new
        `instance` values start. `first` / `last` are ticks."""
        churn = self.config["churn"]
        every = int(churn["interval_s"]) * 1000 // self.tick_ms
        events = [k for k in range(every, self.ticks, every)]
        per_event = max(1, round(self.hosts * churn["percent"] / 100))
        n = self.hosts + per_event * len(events)
        first = np.zeros(n, dtype=np.int64)
        last = np.full(n, self.ticks, dtype=np.int64)
        alive = list(range(self.hosts))
        for e, tick in enumerate(events):
            # a tick that ends a series lies one before the event, and
            # the events lie on whole minutes: no last sample is a
            # multiple of 30 s, so no step of a 15 s grid meets a
            # lookback's far edge (closed in the program, left-open in
            # Prometheus 3) on a sample
            gone = rng.choice(len(alive), per_event, replace=False)
            for g in sorted(gone, reverse=True):
                last[alive.pop(int(g))] = tick
            new = self.hosts + e * per_event + np.arange(per_event)
            first[new] = tick
            alive += [int(i) for i in new]
        self.instances = [f"host_{i}:9100" for i in range(n)]
        self.first, self.last = first, last
        self.uptime_s = rng.uniform(
            0.0, float(self.config["uptime_days"]) * DAY_S, n)
        # a reboot: every counter of the target restarts at 0
        reboots = max(1, round(self.hosts * self.config["reboot_share"]))
        self.reboot_tick = np.full(n, -1, dtype=np.int64)
        whole = np.nonzero((first == 0) & (last == self.ticks))[0]
        for i in rng.choice(whole, reboots, replace=False):
            self.reboot_tick[i] = int(rng.integers(self.ticks // 6,
                                                   self.ticks * 5 // 6))

    def _tables(self) -> None:
        cfg, n = self.config, len(self.instances)
        inst = np.array(self.instances, dtype=object)
        job = cfg["job"]

        def table(name, kind, per_target: list):
            """`per_target`: the label sets every target exports."""
            k = len(per_target)
            instance_of = np.repeat(np.arange(n), k)
            labels = {"instance": inst[instance_of],
                      "job": np.full(n * k, job, dtype=object)}
            for key in per_target[0]:
                labels[key] = np.tile(
                    np.array([p[key] for p in per_target], dtype=object), n)
            return Table(name, kind, instance_of, labels)

        cpu_sets = [{"cpu": str(c), "mode": m}
                    for c in range(int(cfg["cpus"])) for m in cfg["modes"]]
        fs_sets = [dict(f) for f in cfg["filesystems"]]
        net_sets = [{"device": d} for d in cfg["net_devices"]]
        made = {
            "node_cpu_seconds_total": table(
                "node_cpu_seconds_total", "cpu", cpu_sets),
            "node_memory_MemAvailable_bytes": table(
                "node_memory_MemAvailable_bytes", "mem_available", [{}]),
            "node_memory_MemTotal_bytes": table(
                "node_memory_MemTotal_bytes", "mem_total", [{}]),
            "node_load1": table("node_load1", "load", [{}]),
            "node_network_receive_bytes_total": table(
                "node_network_receive_bytes_total", "net", net_sets),
            "node_filesystem_avail_bytes": table(
                "node_filesystem_avail_bytes", "fs_avail", fs_sets),
            "node_filesystem_size_bytes": table(
                "node_filesystem_size_bytes", "fs_size", fs_sets),
        }
        self.tables = {name: made[name] for name in cfg["tables"]}

    # ---- the values ---------------------------------------------------
    def _block(self, table: Table) -> np.ndarray:
        return self.data[table.offset:table.offset + table.series
                         * self.ticks].reshape(table.series, self.ticks)

    def _counter(self, out, increments, start, instance_of) -> None:
        """out[s, k] = start[s] + increments[s, :k + 1].sum(), restarted
        at 0 where the series' target reboots."""
        np.cumsum(increments, axis=1, out=out)
        reboot = self.reboot_tick[instance_of]
        for s in np.nonzero(reboot >= 0)[0]:
            r = int(reboot[s])
            out[s, r:] -= out[s, r - 1] + start[s]
        out += start[:, None]

    def _walk(self, rng, out, start, step, lo, hi) -> None:
        """Clamped random walks in float64: N(0, step) a tick."""
        x = np.array(start, dtype=np.float64)
        out[:, 0] = x
        for k in range(1, self.ticks):
            x = np.clip(x + rng.standard_normal(len(x)) * step, lo, hi)
            out[:, k] = x

    def _values(self, rng) -> None:
        cfg, n, T = self.config, len(self.instances), self.ticks
        tick_s = self.tick_ms / 1000.0
        modes = list(cfg["modes"])
        cpus = int(cfg["cpus"])
        # what two tables share is drawn once, whichever comes first:
        # MemTotal (what the kernel reserves makes it no power of two)
        # and a filesystem's size
        gib = rng.choice([8, 16, 32, 64, 128], n).astype(np.float64)
        mem_total = np.floor(gib * 2**30 * rng.uniform(0.96, 0.99, n))
        nfs = n * len(cfg["filesystems"])
        fs_size = np.floor(rng.choice([20, 50, 100, 500, 1000, 2000], nfs)
                           * 1e9 * rng.uniform(0.9, 1.0, nfs))
        for table in self.tables.values():
            out = self._block(table)
            up = self.uptime_s[table.instance_of]
            if table.kind == "cpu":
                # a CPU's eight modes sum to 1 s/s at every tick: the busy
                # share walks, the seven busy modes split it by fixed
                # weights drawn per CPU
                busy = np.empty((n * cpus, T))
                self._walk(rng, busy, rng.uniform(0.05, 0.85, n * cpus),
                           0.01, 0.02, 0.9)
                weights = rng.dirichlet(np.ones(len(modes) - 1), n * cpus)
                share = np.empty((n * cpus, len(modes), T))
                w = 0
                for m, mode in enumerate(modes):
                    if mode == "idle":
                        share[:, m] = 1.0 - busy
                    else:
                        share[:, m] = busy * weights[:, w:w + 1]
                        w += 1
                inc = share.reshape(table.series, T) * tick_s
                rate = inc.mean(axis=1) / tick_s
                self._counter(out, inc, rate * up, table.instance_of)
            elif table.kind == "net":
                rate = self._receive_rates(rng, n)
                per = np.array([0.01 if d == "lo" else 1.0
                                for d in cfg["net_devices"]])
                rate = (rate[:, None] * per[None, :]).reshape(-1)
                inc = rate[:, None] * tick_s * rng.uniform(
                    0.9, 1.1, (table.series, T))
                self._counter(out, inc, rate * up, table.instance_of)
            elif table.kind == "mem_total":
                out[:] = mem_total[:, None]
            elif table.kind == "mem_available":
                self._walk(rng, out, mem_total * rng.uniform(0.1, 0.9, n),
                           mem_total * 0.002, mem_total * 0.05,
                           mem_total * 0.95)
            elif table.kind == "load":
                self._walk(rng, out, rng.uniform(0.0, 8.0, n), 0.05,
                           0.0, 16.0)
            elif table.kind in ("fs_size", "fs_avail"):
                tmpfs = table.labels["fstype"] == "tmpfs"
                size = np.where(tmpfs, np.floor(fs_size / 100), fs_size)
                if table.kind == "fs_size":
                    out[:] = size[:, None]
                else:
                    # a walk with a drift of its own: disks fill
                    drift = -rng.uniform(0.0, 2e5, table.series) * tick_s
                    x = size * rng.uniform(0.2, 0.9, table.series)
                    out[:, 0] = x
                    for k in range(1, T):
                        x = np.clip(x + drift + rng.standard_normal(
                            table.series) * 1e5, 0.0, size)
                        out[:, k] = x
            else:
                raise ValueError(f"no values for table kind {table.kind}")

    def _receive_rates(self, rng, n: int) -> np.ndarray:
        """Bytes/s of a target's `eth0`, log-uniform between the
        configuration's bounds. The eight largest are at least 10% apart
        (a window's rate wanders by about 1%): which five `topk` picks
        does not hang on the last digits of f32."""
        lo, hi = self.config["receive_bytes_per_s"]
        rate = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
        order = np.argsort(-rate)
        for a, b in zip(order[:7], order[1:8]):
            rate[b] = min(rate[b], rate[a] / 1.1)
        return rate

    # ---- what the harness and the families use --------------------------
    @property
    def rows(self) -> int:
        alive = self.last - self.first
        return int(sum(alive[t.instance_of].sum()
                       for t in self.tables.values()))

    @property
    def end_ms(self) -> int:
        return self.ms(self.ticks)

    def ms(self, tick: int) -> int:
        return self.t0_ms + int(tick) * self.tick_ms

    def samples(self, name: str) -> Samples:
        t = self.tables[name]
        times = self.t0_ms + np.arange(self.ticks, dtype=np.int64) \
            * self.tick_ms
        return Samples(name, times, self._block(t),
                       self.first[t.instance_of], self.last[t.instance_of],
                       t.labels)

    def create_table_sql(self) -> str:
        """One CREATE TABLE per metric, in one string (`do_query` runs
        them in turn): what `/v1/prometheus/write` creates on demand."""
        out = []
        for t in self.tables.values():
            cols = ", ".join(f"{c} STRING" for c in t.label_names)
            out.append(
                f"CREATE TABLE {t.name} ({cols}, {self.time_index} "
                f"TIMESTAMP TIME INDEX, {self.value_field} DOUBLE, "
                f"PRIMARY KEY({', '.join(t.label_names)}))")
        return "; ".join(out)

    def arrow_chunks(self, chunk_ticks: int):
        """-> (table name, its tag names, a pyarrow Table of the samples
        that exist in `chunk_ticks` ticks), series-major within a chunk
        (long per-series runs), a table at a time."""
        import pyarrow as pa
        for t in self.tables.values():
            block = self._block(t)
            first, last = self.first[t.instance_of], self.last[t.instance_of]
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
                    np.int64) * self.tick_ms
                columns[self.value_field] = block[series, tick]
                yield t.name, t.label_names, pa.table(columns)

    @staticmethod
    def require_analyzed_tql(grpc_port: int) -> None:
        """A program whose EXPLAIN ANALYZE does not execute a TQL
        statement (every program before PR 28: it answers `parse`,
        `plan`, `dispatch` n/a, `total`) cannot run this deployment's
        cells: the statement loop reads the executed dispatch and its
        traced window from those rows, and its answers would be judged
        as if only the families that happen to pass existed. Found out
        before the load, so that such a program fails in set-up and
        prints no result. The probe names no table: a selector on a
        still empty table would leave an empty scan-cache entry behind,
        and its refresh after the load walks 13.86M new rows series by
        series (the first warm statement then never answers)."""
        import json

        from pyarrow import flight
        conn = flight.connect(f"grpc://127.0.0.1:{grpc_port}")
        try:
            stages = conn.do_get(flight.Ticket(json.dumps({
                "type": "sql",
                "sql": "EXPLAIN ANALYZE TQL EVAL (0, 0, '15s') vector(1)"
            }).encode())).read_all().column(0).to_pylist()
        finally:
            conn.close()
        if "outer" not in stages:
            raise RuntimeError(
                "this program answers EXPLAIN ANALYZE of a TQL statement "
                f"without running it (rows {stages}): the PromQL cells "
                "need its stage rows")

    def load(self, grpc_port: int, chunk_ticks: int) -> int:
        """Every sample that exists over Arrow Flight; -> acknowledged
        rows. The next chunk's table is built while the server takes
        this one."""
        from benchlib.wire import flight_bulk_load
        self.require_analyzed_tql(grpc_port)
        acked = 0
        chunks = self.arrow_chunks(chunk_ticks)
        with ThreadPoolExecutor(max_workers=1) as pool:
            nxt = pool.submit(next, chunks, None)
            while True:
                chunk = nxt.result()
                if chunk is None:
                    return acked
                nxt = pool.submit(next, chunks, None)
                name, tags, table = chunk
                acked += flight_bulk_load(grpc_port, name, table, tags,
                                          self.time_index)
