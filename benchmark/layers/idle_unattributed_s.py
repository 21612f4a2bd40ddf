"""Layer: protocol servers. The device's idle time inside a statement
(between the client's send and its answer) under no stage row of the
program: `stage_idle.py:idle_by_stage_row`'s `statement_outside_rows`, in
seconds of the window. With the `request.*` rows it is what lies before
the middleware, in the response's write and on the client's side. 0 where
every idle interval inside a statement has a row; None without a device
trace. Device trace and EXPLAIN ANALYZE's wall-clock starts."""

from stage_idle import idle_by_stage_row


def read(run):
    idle = idle_by_stage_row(run)
    if not idle:
        return None
    return dict(idle).get("statement_outside_rows", 0.0)
