"""TSBS devops `cpu-only` from --seed: the generator of `chip_smoke.py`
(proven on the chip, PR 21), the Flight loader, and the line-protocol
encoder. Everything a configuration fixes is read from its file."""

from __future__ import annotations

import calendar
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa

from .wire import flight_bulk_load

REGIONS = ["us-east-1", "us-west-1", "us-west-2", "eu-west-1",
           "eu-central-1", "ap-southeast-1", "ap-southeast-2",
           "ap-northeast-1", "sa-east-1"]


def parse_utc_ms(stamp: str) -> int:
    return calendar.timegm(time.strptime(stamp, "%Y-%m-%dT%H:%M:%SZ")) * 1000


class Dataset:
    """One deployment's rows. `data[t, h, f]` float64 holds the loaded
    ticks [0, ticks) and `extra_ticks` more that a write mix sends."""

    def __init__(self, config: dict, seed: int, extra_ticks: int = 0,
                 scale: int = None, ticks: int = None):
        self.config = config
        self.table = config["table"]
        self.time_index = config["time_index"]
        self.tag_names = list(config["tags"])
        self.field_names = list(config["fields"])
        self.t0_ms = parse_utc_ms(config["start"])
        self.tick_ms = int(config["log_interval_s"]) * 1000
        self.hosts = int(scale if scale is not None else config["scale"])
        self.ticks = int(ticks if ticks is not None
                         else config["duration_s"]
                         // config["log_interval_s"])
        self.extra_ticks = int(extra_ticks)
        self.ticks_per_hour = 3_600_000 // self.tick_ms
        self.tags, self.data = generate(
            seed, self.hosts, self.ticks + self.extra_ticks,
            len(self.field_names))
        # the written ticks keep 4 decimals: a 3000-row line-protocol
        # body then stays under the server's 1 MiB request limit
        self.data[self.ticks:] = np.round(self.data[self.ticks:], 4)
        self.hostnames = self.tags["hostname"]

    @property
    def rows(self) -> int:
        return self.hosts * self.ticks

    @property
    def end_ms(self) -> int:
        return self.ms(self.ticks)

    def ms(self, tick: int) -> int:
        return self.t0_ms + int(tick) * self.tick_ms

    def create_table_sql(self) -> str:
        cols = ", ".join(f"{c} STRING" for c in self.tag_names) + \
            f", {self.time_index} TIMESTAMP TIME INDEX, " + \
            ", ".join(f"{c} DOUBLE" for c in self.field_names)
        return (f"CREATE TABLE {self.table} ({cols}, "
                f"PRIMARY KEY({', '.join(self.tag_names)}))")

    def load(self, grpc_port: int, chunk_ticks: int) -> int:
        """The loaded ticks over Arrow Flight, host-major within a chunk
        (long per-series runs); -> acknowledged rows."""
        dictionaries, codes = {}, {}
        for tag in self.tag_names:
            uniq, inv = np.unique(np.array(self.tags[tag], dtype=object),
                                  return_inverse=True)
            dictionaries[tag] = pa.array(list(uniq), type=pa.string())
            codes[tag] = inv.astype(np.int32)
        nf = len(self.field_names)

        def chunk(a: int):
            b = min(a + chunk_ticks, self.ticks)
            n = b - a
            block = self.data[a:b].transpose(1, 0, 2).reshape(
                self.hosts * n, nf)
            columns = {tag: pa.DictionaryArray.from_arrays(
                pa.array(np.repeat(codes[tag], n)), dictionaries[tag])
                for tag in self.tag_names}
            columns[self.time_index] = np.tile(
                self.t0_ms + np.arange(a, b, dtype=np.int64) * self.tick_ms,
                self.hosts)
            for i, f in enumerate(self.field_names):
                columns[f] = np.ascontiguousarray(block[:, i])
            return pa.table(columns)

        # the next chunk's table is built while the server takes this one
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

    def line_protocol_batches(self, batch_rows: int) -> list:
        """The extra ticks as line-protocol bodies in TSBS file order
        (time, then host), `batch_rows` lines each (the last may be
        short). -> [(body bytes, first row, row count)], rows numbered
        from 0 in that order. Field values are `repr(float)` of the
        4-decimal values, which round-trips them exactly; timestamps are
        epoch ms."""
        heads = [self.table + "," + ",".join(
            f"{tag}={self.tags[tag][h]}" for tag in self.tag_names) + " "
            for h in range(self.hosts)]
        names = [f + "=" for f in self.field_names]
        lines = []
        for t in range(self.ticks, self.ticks + self.extra_ticks):
            stamp = f" {self.ms(t)}"
            values = self.data[t].tolist()
            for h in range(self.hosts):
                lines.append(heads[h] + ",".join(
                    [n + repr(v) for n, v in zip(names, values[h])])
                    + stamp)
        return [("\n".join(lines[a:a + batch_rows]).encode(), a,
                 min(batch_rows, len(lines) - a))
                for a in range(0, len(lines), batch_rows)]


def generate(seed: int, hosts: int, ticks: int, nfields: int):
    """-> (tag_values {tag: [str per host]}, data float64 [ticks, hosts,
    nfields]): clamped random walks in [0, 100]."""
    rng = np.random.default_rng(seed)
    reg = rng.integers(0, len(REGIONS), hosts)
    tags = {
        "hostname": [f"host_{i}" for i in range(hosts)],
        "region": [REGIONS[r] for r in reg],
        "datacenter": [REGIONS[r] + "abc"[z] for r, z in
                       zip(reg, rng.integers(0, 3, hosts))],
        "rack": [str(v) for v in rng.integers(0, 100, hosts)],
        "os": [("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")[v]
               for v in rng.integers(0, 3, hosts)],
        "arch": [("x64", "x86")[v] for v in rng.integers(0, 2, hosts)],
        "team": [("SF", "NYC", "LON", "CHI")[v]
                 for v in rng.integers(0, 4, hosts)],
        "service": [str(v) for v in rng.integers(0, 20, hosts)],
        "service_version": [str(v) for v in rng.integers(0, 2, hosts)],
        "service_environment": [("production", "staging", "test")[v]
                                for v in rng.integers(0, 3, hosts)],
    }
    data = np.empty((ticks, hosts, nfields), dtype=np.float64)
    x = rng.uniform(0.0, 100.0, (hosts, nfields))
    data[0] = x
    t = 1
    chunk = max(1, min(512, 20_000_000 // (hosts * nfields)))
    while t < ticks:
        steps = rng.standard_normal((min(chunk, ticks - t), hosts, nfields))
        for s in steps:
            x = np.clip(x + s, 0.0, 100.0)
            data[t] = x
            t += 1
    return tags, data
