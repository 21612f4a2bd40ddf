"""`common/runtime.py:pin_allocator`: a server process fixes glibc malloc's
thresholds before its first thread, so that what a statement's arrays cost
does not hang on what the process happened to free before (PERF.md, PR 44).
The real call runs in a child: a test process keeps its allocator."""

import logging
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import ctypes, json
from greptimedb_tpu.common.runtime import pin_allocator

libc = ctypes.CDLL(None)


class Info(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


libc.mallinfo2.restype = Info
libc.malloc.restype = ctypes.c_void_p
libc.malloc.argtypes = [ctypes.c_size_t]
libc.free.argtypes = [ctypes.c_void_p]


def block(size):
    # -> (blocks mapped for it, bytes the heap keeps once it is freed)
    before = libc.mallinfo2()
    p = libc.malloc(size)
    mapped = libc.mallinfo2().hblks - before.hblks
    libc.free(p)
    return mapped, libc.mallinfo2().arena - before.arena


out = {"unpinned": block(20 << 20)}
out["pinned"] = pin_allocator()
out["again"] = pin_allocator()
out["under"] = block(24 << 20)
out["over"] = block(400 << 20)
print(json.dumps(out))
"""


def _child(env=None):
    import json
    e = {k: v for k, v in os.environ.items()
         if k != "GLIBC_TUNABLES" and not k.startswith("MALLOC_")}
    e.update(env or {}, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    done = subprocess.run([sys.executable, "-c", _CHILD], env=e, cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def pinned_child():
    import ctypes
    try:
        ctypes.CDLL(None).mallinfo2
    except (OSError, AttributeError):
        pytest.skip("not glibc 2.33 or later")
    return _child()


def test_pin_allocator_says_what_it_set_and_repeats(pinned_child):
    want = {"mmap_threshold": 32 << 20, "trim_threshold": 1 << 30,
            "top_pad": 64 << 20}
    assert pinned_child["pinned"] == want
    assert pinned_child["again"] == want


def test_a_block_under_the_threshold_comes_from_memory_the_process_keeps(
        pinned_child):
    # a new process maps a 20 MiB block and hands it back when freed
    assert pinned_child["unpinned"] == [1, 0]
    # pinned: 24 MiB is cut from the heap, which keeps it when freed
    mapped, kept = pinned_child["under"]
    assert mapped == 0 and kept >= 24 << 20


def test_a_block_over_what_the_heap_keeps_is_mapped_and_returned_as_before(
        pinned_child):
    # 400 MiB: over the threshold and over the room the heap has
    assert pinned_child["over"] == [1, 0]


@pytest.mark.parametrize("env", [
    {"MALLOC_TRIM_THRESHOLD_": "268435456"},
    {"GLIBC_TUNABLES": "glibc.malloc.mmap_threshold=1048576"}])
def test_an_operator_who_set_malloc_in_the_environment_keeps_it(env):
    out = _child(env)
    assert out["pinned"] is None and out["again"] is None


@pytest.mark.parametrize("pinned,said", [
    ({"mmap_threshold": 32 << 20, "top_pad": 64 << 20},
     "allocator: mmap_threshold=32 MiB, top_pad=64 MiB"),
    (None, "allocator: as the environment has it")])
def test_the_server_start_logs_the_allocator(monkeypatch, caplog, pinned,
                                             said):
    import importlib
    main = importlib.import_module("greptimedb_tpu.cmd.main")
    from greptimedb_tpu.common import runtime
    monkeypatch.setattr(runtime, "pin_allocator", lambda: pinned)
    with caplog.at_level(logging.INFO):
        main._pin_allocator()
    assert said in caplog.text


def test_the_roles_that_hold_tables_pin_before_they_claim_the_device():
    import importlib
    import inspect
    main = importlib.import_module("greptimedb_tpu.cmd.main")
    for role in (main.standalone_start, main.datanode_start):
        src = inspect.getsource(role)
        assert 0 < src.index("_pin_allocator()") < src.index(
            "_claim_device()")
