"""sqlness-style golden-file SQL harness.

Reference behavior: tests/runner/src/{main,env,util}.rs + tests/cases/ —
`.sql` files run against a started server; outputs are diffed against
committed `.result` files. This is the reference's primary end-to-end
regression rig (SURVEY §4); this port executes each case file against a
fresh in-process standalone frontend and renders results in the same
shape (`Affected Rows: N` / ASCII tables / `Error: ...`).

Usage:
    python tests/sqlness/runner.py            # run all cases, diff
    python tests/sqlness/runner.py --update   # (re)generate .result files
    python tests/sqlness/runner.py name ...   # filter by substring

Pytest integration lives in tests/test_sqlness.py.
"""

from __future__ import annotations

import argparse
import difflib
import sys
import tempfile
from pathlib import Path
from typing import List, Optional

CASES_DIR = Path(__file__).parent / "cases"


def split_statements(text: str) -> List[str]:
    """Split a .sql file into ';'-terminated statements, respecting
    single-quoted strings and line comments."""
    statements, buf = [], []
    in_str = False
    in_comment = False
    for ch in text:
        if in_comment:
            buf.append(ch)
            if ch == "\n":
                in_comment = False
            continue
        if ch == "'" :
            in_str = not in_str
            buf.append(ch)
            continue
        if not in_str and ch == "-" and buf and buf[-1] == "-":
            in_comment = True
            buf.append(ch)
            continue
        if ch == ";" and not in_str:
            stmt = "".join(buf).strip()
            if stmt:
                statements.append(stmt + ";")
            buf = []
            continue
        buf.append(ch)
    tail = "".join(buf).strip()
    if tail:
        statements.append(tail)
    return statements


def _strip_comment_lines(stmt: str) -> str:
    lines = [ln for ln in stmt.splitlines()
             if not ln.lstrip().startswith("--")]
    return "\n".join(lines).strip()


#: column name -> placeholder: wall-clock / wall-advancing columns whose
#: values cannot byte-compare across runs (elapsed_ms in EXPLAIN ANALYZE;
#: flow watermark timestamps in SHOW FLOWS / information_schema.flows;
#: last-seen heartbeat times and dialed addresses in cluster_info)
_VOLATILE_COLUMNS = {"elapsed_ms": "<elapsed>", "watermark": "<watermark>",
                     "last_seen_ms": "<last_seen>", "peer_addr": "<addr>",
                     "op_id": "<op_id>",
                     # trace-store waterfall / background_jobs timings
                     # and ids (ISSUE 15)
                     "duration_ms": "<ms>", "self_ms": "<ms>",
                     "start_offset_ms": "<ms>", "start_ms": "<ms>",
                     "trace_id": "<trace>", "span_id": "<span>",
                     "parent_span_id": "<span>",
                     # continuous-profiler sample counts / stack hashes
                     # (ISSUE 17): wall-clock sampling never byte-repeats
                     "self_samples": "<n>", "total_samples": "<n>",
                     "stack_id": "<stack>"}

#: wall-clock fragments inside EXPLAIN ANALYZE detail strings: the
#: scatter's slowest-node latency, the per-node latency vector, the
#: node rows' node-vs-network split, every timed row's thread CPU time
#: and wall-clock start, and the total row's trace id
import re as _re  # noqa: E402

_VOLATILE_DETAIL = [
    (_re.compile(r"slowest_node_ms=[0-9.]+"), "slowest_node_ms=<ms>"),
    (_re.compile(r"node_ms=[0-9A-Za-z:./#-]+"), "node_ms=<ms>"),
    (_re.compile(r"network_ms=[0-9.]+"), "network_ms=<ms>"),
    (_re.compile(r"cpu_ms=[0-9.]+"), "cpu_ms=<ms>"),
    (_re.compile(r"t0_ns=[0-9]+"), "t0_ns=<ns>"),
    (_re.compile(r"trace_id=[0-9a-f]+"), "trace_id=<trace>"),
]


def _scrub_detail(v: str) -> str:
    for pattern, repl in _VOLATILE_DETAIL:
        v = pattern.sub(repl, v)
    return v


def _normalize_timings(out):
    """Replace volatile columns with fixed placeholders so goldens
    byte-compare across runs — the runner's stand-in for reference
    sqlness' result REPLACE directives. Rebuilds the batch with the
    column retyped to STRING so the pretty table renders identical
    widths every run."""
    from greptimedb_tpu.datatypes import data_type as dt
    from greptimedb_tpu.datatypes.record_batch import RecordBatch
    from greptimedb_tpu.datatypes.schema import ColumnSchema, Schema
    from greptimedb_tpu.query.output import Output

    if not out.is_batches or not out.batches:
        return out
    if not any(set(b.schema.names()) & (set(_VOLATILE_COLUMNS) |
                                        {"detail"})
               for b in out.batches):
        return out
    batches = []
    for b in out.batches:
        data = b.to_pydict()
        cols = []
        for cs in b.schema.column_schemas:
            if cs.name in _VOLATILE_COLUMNS:
                data[cs.name] = [_VOLATILE_COLUMNS[cs.name]] * b.num_rows
                cols.append(ColumnSchema(cs.name, dt.STRING))
            else:
                if cs.name == "detail":
                    data[cs.name] = [
                        _scrub_detail(v) if isinstance(v, str) else v
                        for v in data[cs.name]]
                cols.append(cs)
        schema = Schema(cols)
        batches.append(RecordBatch.from_pydict(schema, data))
    return Output.record_batches(batches, batches[0].schema)


def render_output(out) -> str:
    from greptimedb_tpu.datatypes.record_batch import pretty_print
    out = _normalize_timings(out)
    if out.is_batches:
        if not out.batches or all(b.num_rows == 0 for b in out.batches):
            names = out.batches[0].schema.names() if out.batches else []
            if names:
                return pretty_print(out.batches)
            return "(empty)"
        return pretty_print(out.batches)
    return f"Affected Rows: {out.affected_rows or 0}"


def run_case(sql_text: str, frontend) -> str:
    """Execute a case file's statements; return the .result content."""
    from greptimedb_tpu.errors import GreptimeError
    from greptimedb_tpu.session import QueryContext

    ctx = QueryContext()
    blocks: List[str] = []
    for stmt in split_statements(sql_text):
        body = _strip_comment_lines(stmt)
        if not body:
            continue
        blocks.append(stmt)
        try:
            outputs = frontend.do_query(body, ctx)
            blocks.append(render_output(outputs[-1]))
        except GreptimeError as e:
            blocks.append(f"Error: {e}")
        except Exception as e:  # noqa: BLE001 — parser/planner crashes
            blocks.append(f"Error: {type(e).__name__}: {e}")
    return "\n\n".join(blocks) + "\n"


def make_frontend(data_home: str):
    from greptimedb_tpu.datanode.instance import (
        DatanodeInstance, DatanodeOptions)
    from greptimedb_tpu.frontend.instance import FrontendInstance
    dn = DatanodeInstance(DatanodeOptions(data_home=data_home,
                                          register_numbers_table=True))
    dn.start()
    fe = FrontendInstance(dn)
    fe.start()
    return fe


class _DistEnv:
    """2-datanode cluster frontend for cases/distributed/ (the reference
    runs the same golden cases against a distributed env,
    tests/runner/src/env.rs + tests/cases/distributed/)."""

    def __init__(self, data_home: str):
        from greptimedb_tpu.client import LocalDatanodeClient
        from greptimedb_tpu.datanode.instance import (
            DatanodeInstance, DatanodeOptions)
        from greptimedb_tpu.frontend.distributed import DistInstance
        from greptimedb_tpu.meta import MetaClient, Peer
        from greptimedb_tpu.meta.kv import MemKv
        from greptimedb_tpu.meta.service import MetaSrv
        from greptimedb_tpu.storage.object_store import FsObjectStore
        self.datanodes = []
        self.srv = MetaSrv(MemKv())
        meta = MetaClient(self.srv)
        clients = {}
        # ONE shared object store (the elastic-region deployment shape:
        # migrate/split hand regions between nodes through it); control
        # state + WAL stay node-scoped
        shared = FsObjectStore(f"{data_home}/shared")
        for i in (1, 2):
            dn = DatanodeInstance(DatanodeOptions(
                data_home=f"{data_home}/dn{i}", node_id=i,
                register_numbers_table=False), store=shared)
            dn.start()
            dn.attach_meta(meta)
            self.datanodes.append(dn)
            clients[i] = LocalDatanodeClient(dn)
            self.srv.register_datanode(Peer(i, f"dn{i}"))
            self.srv.handle_heartbeat(i)
        self.fe = DistInstance(meta, clients)

    def do_query(self, sql: str, ctx=None):
        outs = self.fe.do_query(sql, ctx)
        self._pump_balancer()
        return outs

    def _pump_balancer(self):
        """Drive any balancer ops the statement enqueued to completion
        (the cooperative stand-in for the background tick + heartbeat
        loops, so ADMIN goldens are deterministic)."""
        for _ in range(24):
            if not self.srv.balancer.ops():
                return
            self.srv.balancer.tick()
            for dn in self.datanodes:
                resp = self.srv.handle_heartbeat(dn.opts.node_id)
                for msg in resp.mailbox:
                    dn._handle_mailbox(msg)

    def shutdown(self):
        for dn in self.datanodes:
            dn.shutdown()


def case_files(filters: List[str]) -> List[Path]:
    files = sorted(CASES_DIR.rglob("*.sql"))
    if filters:
        files = [f for f in files
                 if any(flt in str(f) for flt in filters)]
    return files


def run_one(sql_path: Path, update: bool) -> Optional[str]:
    result_path = sql_path.with_suffix(".result")
    distributed = "distributed" in sql_path.relative_to(CASES_DIR).parts
    # failpoint state/counters are process-global; a case sees them as a
    # fresh server would (system/failpoints.sql pins exact hit counts).
    # The background-job registry and trace knobs are process-global
    # too (system/background_jobs.sql pins exact job rows)
    from greptimedb_tpu.common import background_jobs, failpoint
    from greptimedb_tpu.common import profiler, trace_store
    failpoint.reset()
    background_jobs.reset()
    trace_store.configure(sample_ratio=0.01)
    # profiler knobs are process-global too; a case that SET them must
    # not leak into the next (the frontend construct installs a fresh
    # sampler, but enabled/hz/retention live at module level)
    profiler.configure(enabled=False, hz=19.0,
                       retention_ms=24 * 3600 * 1000)
    with tempfile.TemporaryDirectory() as home:
        fe = _DistEnv(home) if distributed else make_frontend(home)
        try:
            got = run_case(sql_path.read_text(), fe)
        finally:
            fe.shutdown()
    if update:
        result_path.write_text(got)
        return None
    if not result_path.exists():
        return f"{sql_path}: missing .result (run with --update)"
    want = result_path.read_text()
    if got != want:
        diff = "\n".join(difflib.unified_diff(
            want.splitlines(), got.splitlines(),
            fromfile=str(result_path), tofile="actual", lineterm=""))
        return f"{sql_path}:\n{diff}"
    return None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description="sqlness golden harness")
    parser.add_argument("--update", action="store_true",
                        help="regenerate .result files")
    parser.add_argument("filters", nargs="*",
                        help="substring filters on case paths")
    args = parser.parse_args(argv)

    failures = []
    files = case_files(args.filters)
    if not files:
        print("no cases matched", file=sys.stderr)
        return 2
    for f in files:
        err = run_one(f, args.update)
        status = "UPDATED" if args.update else ("FAIL" if err else "PASS")
        print(f"[{status}] {f.relative_to(CASES_DIR)}")
        if err:
            failures.append(err)
    if failures:
        print("\n" + "\n\n".join(failures))
        return 1
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    import jax
    jax.config.update("jax_platforms", "cpu")
    # run goldens in the production numeric regime (x64 off, as on TPU)
    jax.config.update("jax_enable_x64", False)
    sys.exit(main())
