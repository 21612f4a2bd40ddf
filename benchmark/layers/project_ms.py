"""Layer: fold / finalize / wire. The `project` stage row: the SELECT
list, ORDER BY / LIMIT and the DataFrame -> RecordBatch conversion
(`query/engine.py:_project_and_finish`). EXPLAIN ANALYZE."""

from benchlib.spanlib import mean_span_ms


def read(run):
    return mean_span_ms(run, "project")
