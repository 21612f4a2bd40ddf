"""Datanode client: the router↔worker data-plane interface.

Reference behavior: src/client — `Database` sends per-region inserts and
ships plans to datanodes, streaming results back over Arrow Flight
(database.rs:39,209-260). The same surface here has two implementations:

- `LocalDatanodeClient`: direct in-process calls (the reference's
  MockDistributedInstance topology, frontend/src/tests.rs:60) — also the
  fast path when router and worker share a host;
- a Flight/gRPC client implements the identical surface over sockets for
  multi-host (servers/flight.py).

Aggregate pushdown note: v0.2 of the reference pushes only scans
(projection/filter/limit) to datanodes and aggregates on the frontend
(frontend/src/table.rs:109-156). Here `region_moments` pushes the
*aggregation moments* down: each worker reduces its regions with the TPU
kernel and returns per-run moment frames that the frontend folds — a
strict upgrade the SURVEY (§3.4) calls for.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import pandas as pd

from ..table.requests import CreateTableRequest, DropTableRequest


class DatanodeClient:
    """Abstract data-plane client for one datanode."""

    def ddl_create_table(self, request: CreateTableRequest) -> None:
        raise NotImplementedError

    def ddl_drop_table(self, catalog: str, schema: str, name: str) -> bool:
        raise NotImplementedError

    def ddl_alter_table(self, request) -> None:
        raise NotImplementedError

    def write_region(self, catalog: str, schema: str, table: str,
                     region_number: int, columns: Dict[str, Sequence],
                     op: str = "put") -> int:
        raise NotImplementedError

    def region_moments(self, catalog: str, schema: str, table: str,
                       plan, regions: Optional[Sequence[int]] = None
                       ) -> List[pd.DataFrame]:
        """Run the TPU aggregate plan over this node's regions of the
        table (restricted to `regions` when the frontend pruned);
        returns per-region moment frames for the frontend fold."""
        raise NotImplementedError

    def scan_batches(self, catalog: str, schema: str, table: str,
                     projection: Optional[Sequence[str]] = None,
                     time_range=None, limit: Optional[int] = None,
                     filters: Optional[Sequence] = None,
                     regions: Optional[Sequence[int]] = None) -> list:
        raise NotImplementedError

    def flush_table(self, catalog: str, schema: str, table: str) -> None:
        raise NotImplementedError

    def describe_table(self, catalog: str, schema: str, name: str):
        """(TableInfo, partition_rule) of a hosted table, or None."""
        raise NotImplementedError


class LocalDatanodeClient(DatanodeClient):
    def __init__(self, datanode):
        self.datanode = datanode

    @property
    def node_id(self) -> int:
        return self.datanode.opts.node_id

    def _table(self, catalog: str, schema: str, name: str):
        from ..errors import TableNotFoundError
        t = self.datanode.catalog.table(catalog, schema, name)
        if t is None:
            raise TableNotFoundError(f"table {catalog}.{schema}.{name} "
                                     f"not on datanode {self.node_id}")
        return t

    def ddl_create_table(self, request: CreateTableRequest) -> None:
        table = self.datanode.mito.create_table(request)
        cat = self.datanode.catalog
        if cat.table(request.catalog_name, request.schema_name,
                     request.table_name) is None:
            cat.register_table(request.catalog_name, request.schema_name,
                               request.table_name, table)

    def ddl_drop_table(self, catalog: str, schema: str, name: str) -> bool:
        ok = self.datanode.mito.drop_table(
            DropTableRequest(name, catalog, schema))
        self.datanode.catalog.deregister_table(catalog, schema, name)
        return ok

    def ddl_alter_table(self, request) -> None:
        table = self.datanode.mito.alter_table(request)
        cat = self.datanode.catalog
        cat.deregister_table(request.catalog_name, request.schema_name,
                             request.table_name)
        cat.register_table(request.catalog_name, request.schema_name,
                           request.new_table_name or request.table_name,
                           table)

    def _node_ctx(self):
        # in-process cluster: datanode work runs on the frontend's own
        # threads, so the sampler needs the per-node label pushed here
        # (a no-op context while nothing samples)
        from ..common import profiler
        return profiler.node_context(f"dn{self.node_id}")

    def write_region(self, catalog: str, schema: str, table: str,
                     region_number: int, columns: Dict[str, Sequence],
                     op: str = "put") -> int:
        with self._node_ctx():
            return self._table(catalog, schema, table).write_region(
                region_number, columns, op)

    def region_moments(self, catalog: str, schema: str, table: str,
                       plan, regions: Optional[Sequence[int]] = None
                       ) -> List[pd.DataFrame]:
        from ..query import tpu_exec
        with self._node_ctx():
            return tpu_exec.region_moment_frames(
                self._table(catalog, schema, table), plan,
                regions=regions)

    def scan_batches(self, catalog: str, schema: str, table: str,
                     projection: Optional[Sequence[str]] = None,
                     time_range=None, limit: Optional[int] = None,
                     filters: Optional[Sequence] = None,
                     regions: Optional[Sequence[int]] = None) -> list:
        from ..common import exec_stats
        with self._node_ctx(), exec_stats.stage("scan"):
            batches = self._table(catalog, schema, table).scan_batches(
                projection=projection, time_range=time_range, limit=limit,
                filters=filters, regions=regions)
        # same stage name the Flight datanode server records, so the
        # per-node EXPLAIN ANALYZE tree is identical on both transports
        exec_stats.record("scan", rows=sum(b.num_rows for b in batches))
        return batches

    def flush_table(self, catalog: str, schema: str, table: str) -> None:
        with self._node_ctx():
            self._table(catalog, schema, table).flush()

    def describe_table(self, catalog: str, schema: str, name: str):
        t = self.datanode.catalog.table(catalog, schema, name)
        if t is None:
            return None
        return t.info, getattr(t, "partition_rule", None)

    def ping(self) -> int:
        return self.node_id

    def repl_apply(self, catalog: str, schema: str, table: str,
                   region_number: int, entries: list,
                   leader_flushed: int = 0) -> dict:
        """Apply shipped WAL records to this node's standby replica of
        the region (the continuous-replication consumer side)."""
        with self._node_ctx():
            return self.datanode.repl_apply(
                catalog, schema, table, region_number, entries,
                leader_flushed=leader_flushed)

    def background_jobs(self) -> list:
        """In-process twin of the Flight action. The registry is
        process-wide, so for an in-process cluster these rows duplicate
        the frontend's own — the view dedups by (node, job_id)."""
        from ..common import background_jobs
        return background_jobs.rows()

    def profile(self, *, seconds: Optional[float] = None,
                hz: Optional[float] = None, drain: bool = False) -> list:
        """In-process twin of the Flight `profile` action. The sampler
        is process-wide (the frontend's own), so draining or bursting
        here would double-count it — per-node attribution instead rides
        the `node_context` pushed around the data-plane calls above."""
        return []
