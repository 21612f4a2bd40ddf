"""Session state: QueryContext.

Reference behavior: src/session/src/context.rs:28 — current catalog/schema
plus the protocol channel the query arrived on.
"""

from __future__ import annotations

import enum
from typing import Optional

from .. import DEFAULT_CATALOG_NAME, DEFAULT_SCHEMA_NAME


class Channel(enum.Enum):
    HTTP = "http"
    MYSQL = "mysql"
    POSTGRES = "postgres"
    GRPC = "grpc"
    INFLUX = "influxdb"
    OPENTSDB = "opentsdb"
    PROMETHEUS = "prometheus"


class QueryContext:
    def __init__(self, current_catalog: str = DEFAULT_CATALOG_NAME,
                 current_schema: str = DEFAULT_SCHEMA_NAME,
                 channel: Channel = Channel.HTTP,
                 username: Optional[str] = None):
        self.current_catalog = current_catalog
        self.current_schema = current_schema
        self.channel = channel
        self.username = username
        self.time_zone = "UTC"
        #: how long parsing the statement text in flight took
        #: (exec_stats.Timed, set by do_query); the query engine reports
        #: it as the `parse` stage row
        self.parse_span = None
        #: the hand-offs of the HTTP request that carries the statement
        #: (servers/http.py:RequestPhases); its `read` and `queue` are
        #: the stage rows ahead of `parse`. None on the wires that run a
        #: statement on the connection's own thread
        self.request_phases = None

    def set_current_schema(self, schema: str) -> None:
        self.current_schema = schema

    def resolve(self, name) -> tuple:
        """Resolve a sql.ast.ObjectName to (catalog, schema, table)."""
        catalog = name.catalog or self.current_catalog
        schema = name.schema or self.current_schema
        return catalog, schema, name.table

    def __repr__(self):  # pragma: no cover
        return (f"QueryContext({self.current_catalog}."
                f"{self.current_schema}, {self.channel.value})")
