"""Query output container.

Reference behavior: src/common/query — `Output::{AffectedRows,
RecordBatches, Stream}`. Streams collapse to eager batch lists here; the
protocol servers chunk them on the way out.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from ..datatypes.record_batch import RecordBatch, pretty_print
from ..datatypes.schema import Schema


@dataclass
class Output:
    affected_rows: Optional[int] = None
    batches: Optional[List[RecordBatch]] = None
    schema: Optional[Schema] = None
    #: (trace_id, span_id) of the `execute_stmt` span that made this
    #: result: the protocol writer's `render` span hangs off it
    trace: Optional[Tuple[str, str]] = None
    #: EXPLAIN ANALYZE only: the analysed statement's own result. The
    #: protocol writer encodes it into a discarded buffer and reports
    #: that as the `render` row (servers/render.py)
    analyzed: Optional["Output"] = None

    @staticmethod
    def rows(n: int) -> "Output":
        return Output(affected_rows=n)

    @staticmethod
    def record_batches(batches: List[RecordBatch],
                       schema: Optional[Schema] = None) -> "Output":
        if schema is None and batches:
            schema = batches[0].schema
        return Output(batches=batches, schema=schema)

    @property
    def is_batches(self) -> bool:
        return self.batches is not None

    @property
    def num_rows(self) -> int:
        if self.batches is not None:
            return sum(b.num_rows for b in self.batches)
        return self.affected_rows or 0

    def pretty(self) -> str:
        if self.batches is not None:
            return pretty_print(self.batches)
        return f"Affected Rows: {self.affected_rows}"
