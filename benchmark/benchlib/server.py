"""The one process that owns the chip: `standalone start`, started through
`benchmark/launcher.py` so that the parent can switch its profiler.
Copied from `chip_smoke.py`'s `Server` (proven on the chip, PR 21)."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

from .wire import Http, WireError

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, work_dir: str, options=(), platform_env=None):
        """`options`: further arguments of `standalone start` from the
        configuration's file; `platform_env`: JAX_PLATFORMS for a debug
        run, None to leave the platform to JAX (the chip, or no start)."""
        self.data_home = os.path.join(work_dir, "data")
        self.log_path = os.path.join(work_dir, "server.log")
        self.marks_path = os.path.join(work_dir, "trace_marks.jsonl")
        self.options = list(options)
        self.platform_env = platform_env
        self.proc = None
        self.ports = {}
        self._marks_read = 0

    def start(self) -> None:
        self.ports = {k: free_port()
                      for k in ("http", "mysql", "postgres", "grpc")}
        cmd = [sys.executable, os.path.join(BENCH_DIR, "launcher.py"),
               "--marks", self.marks_path, "--",
               "standalone", "start", "--data-home", self.data_home]
        for k, port in self.ports.items():
            cmd += [f"--{k}-addr", f"127.0.0.1:{port}"]
        cmd += self.options
        env = dict(os.environ)
        # a fixed path inside the checkout: the path is part of the key
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(ROOT, ".jax_cache"))
        # one hash seed for every run: str hashes (so set and dict order)
        # otherwise differ from one server process to the next
        env.setdefault("PYTHONHASHSEED", "0")
        if self.platform_env is None:
            env.pop("JAX_PLATFORMS", None)
        else:
            env["JAX_PLATFORMS"] = self.platform_env
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def wait_ready(self, timeout_s: float = 180) -> dict:
        """-> /status once the server answers."""
        http = Http(self.ports["http"])
        deadline = time.monotonic() + timeout_s
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited with {self.proc.returncode} at start:\n"
                    + self.log_tail())
            try:
                return http.status()
            except (OSError, WireError):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"server not ready after {timeout_s} s:\n"
                        + self.log_tail()) from None
                time.sleep(0.2)

    def _command(self, line: str, timeout_s: float) -> dict:
        self.proc.stdin.write((line + "\n").encode())
        self.proc.stdin.flush()
        deadline = time.monotonic() + timeout_s
        while True:
            try:
                with open(self.marks_path) as f:
                    marks = f.read().splitlines()
            except FileNotFoundError:
                marks = []
            if len(marks) > self._marks_read:
                self._marks_read += 1
                mark = json.loads(marks[self._marks_read - 1])
                if "error" in mark:
                    raise RuntimeError(f"launcher: {line}: {mark['error']}")
                return mark
            if self.proc.poll() is not None:
                raise RuntimeError("server died during " + line + ":\n"
                                   + self.log_tail())
            if time.monotonic() > deadline:
                raise TimeoutError(f"launcher: no answer to {line!r}")
            time.sleep(0.01)

    def trace_start(self, trace_dir: str) -> dict:
        os.makedirs(trace_dir, exist_ok=True)
        return self._command(f"trace_start {trace_dir}", 120)

    def trace_stop(self) -> dict:
        return self._command("trace_stop", 300)

    def kill(self) -> None:
        """SIGKILL the server's process group (the crash the durability
        check wants, and the way out on every other path), and wait."""
        if self.proc is None:
            return
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait(timeout=60)
        self.proc.stdin.close()
        self._log.close()
        self.proc = None

    def log_tail(self, nbytes: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - nbytes))
                return f.read().decode(errors="replace")
        except OSError as e:
            return f"<no server log: {e}>"
