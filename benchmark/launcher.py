#!/usr/bin/env python3
"""The benchmark's child: `greptimedb_tpu.cmd.main` plus a profiler switch.

Only the process that holds the chip can trace it, and the program has no
profiler hook of its own, so the server is started through this wrapper. A
daemon thread reads one-word commands from stdin (the parent's pipe):

    trace_start <dir>   jax.profiler.start_trace(<dir>)
    trace_stop          stop_trace(), then reduce the .xplane.pb to
                        <dir>/events.json (device events only)

and answers each with one JSON line appended to the file named by
--marks, carrying the wall clock (`time.time_ns()`) around the call and,
for trace_start, around a `bench_anchor` TraceAnnotation, so that the
parent can put its own statement times and the device events on one
timeline. The program is not changed and not imported before its own
`main` runs.

usage: launcher.py --marks <file> -- <greptimedb_tpu.cmd.main arguments>
"""

from __future__ import annotations

import glob
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def short_op_name(name: str) -> str:
    """An XLA Ops event is named by its whole HLO line, `%fusion.3 =
    s32[65536]{0:T(1024)} fusion(...)`: keep the instruction and its
    result type, `fusion.3:s32[65536]`. Other names pass unchanged."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name
    result = rhs.lstrip("(").split("{", 1)[0].split(" ", 1)[0].rstrip(",")
    return lhs.lstrip("%") + ":" + result


def device_events(xplane_path: str) -> dict:
    """The device planes of a profiler trace as plain data:
    {"planes": {plane: {line: [[name, start_ns, duration_ns], ...]}},
     "anchor_ns": start of the `bench_anchor` host event or None}.
    Host planes are dropped (the parent has its own clock for the host)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    planes, anchor = {}, None
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = {}
        for line in plane.lines:
            events = []
            for ev in line.events:
                if device:
                    events.append([short_op_name(ev.name),
                                   int(ev.start_ns), int(ev.duration_ns)])
                elif ev.name == "bench_anchor" and anchor is None:
                    anchor = int(ev.start_ns)
            if events:
                lines[line.name] = events
        if lines:
            planes[plane.name] = lines
    return {"planes": planes, "anchor_ns": anchor}


class TraceSwitch(threading.Thread):
    def __init__(self, marks_path: str):
        super().__init__(name="bench-trace-switch", daemon=True)
        self.marks_path = marks_path
        self.trace_dir = None

    def mark(self, record: dict) -> None:
        with open(self.marks_path, "a") as f:
            f.write(json.dumps(record) + "\n")
            f.flush()

    def run(self) -> None:
        for line in sys.stdin:
            words = line.split()
            if not words:
                continue
            try:
                if words[0] == "trace_start":
                    self.mark(self.start_trace(words[1]))
                elif words[0] == "trace_stop":
                    self.mark(self.stop_trace())
                else:
                    self.mark({"cmd": words[0], "error": "unknown command"})
            except Exception as e:  # noqa: BLE001 - the parent reads it
                self.mark({"cmd": words[0],
                           "error": f"{type(e).__name__}: {e}"})

    def start_trace(self, trace_dir: str) -> dict:
        import jax
        self.trace_dir = trace_dir
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        t0 = time.time_ns()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t1 = time.time_ns()
        with jax.profiler.TraceAnnotation("bench_anchor"):
            anchor_wall = time.time_ns()
        return {"cmd": "trace_start", "call_ns": t0, "started_ns": t1,
                "anchor_wall_ns": anchor_wall}

    def stop_trace(self) -> dict:
        import jax
        t0 = time.time_ns()
        jax.profiler.stop_trace()
        t1 = time.time_ns()
        found = sorted(glob.glob(os.path.join(
            self.trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.trace_dir}")
        events = device_events(found[-1])
        with open(os.path.join(self.trace_dir, "events.json"), "w") as f:
            json.dump(events, f)
        return {"cmd": "trace_stop", "call_ns": t0, "stopped_ns": t1,
                "xplane": found[-1], "reduced_ns": time.time_ns()}


def main() -> int:
    argv = sys.argv[1:]
    if len(argv) < 3 or argv[0] != "--marks" or argv[2] != "--":
        print(__doc__, file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    TraceSwitch(argv[1]).start()
    from greptimedb_tpu.cmd.main import main as server_main
    return server_main(argv[3:])


if __name__ == "__main__":
    sys.exit(main())
