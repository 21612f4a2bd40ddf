"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective tests run
against `--xla_force_host_platform_device_count=8` on CPU, which exercises the
same SPMD partitioner XLA uses on real meshes.

The tier-1 command sets JAX_PLATFORMS=cpu; the jax.config update below keeps
a bare `pytest` on the CPU too (backend initialization is lazy, so it still
wins after the import).
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Production TPU never enables x64 — run the suite in the same numeric
# regime so int64→int32 narrowing bugs surface here, not in the driver's
# multichip gate (they escaped in rounds 1 and 2 because this was True).
jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


@pytest.fixture()
def tmp_data_dir(tmp_path):
    return str(tmp_path)


# ---------------------------------------------------------------------
# greptsan (devtools/greptsan): the happens-before race detector runs
# for the whole session (auto-on under pytest, like the lock-order
# detector); races are recorded, not raised, and THIS gate fails the
# run if any survived the suppression baseline. Importing the package
# is what installs the thread/pool/lock hooks.
# ---------------------------------------------------------------------

from greptimedb_tpu.devtools import greptsan  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_GREPTSAN_BASELINE = os.path.join(_REPO, ".greptsan-baseline.json")


@pytest.fixture(autouse=True)
def _greptsan_generation():
    """Between-test hygiene: drop per-variable access metadata and let
    thread clocks reset lazily (bounds clock size to one test's thread
    count instead of the whole session's). Recorded races persist — the
    session gate below reads them."""
    yield
    if greptsan.enabled():
        greptsan.detector.new_generation()


def pytest_sessionfinish(session, exitstatus):
    if not greptsan.enabled():
        return
    fresh = greptsan.unsuppressed(greptsan.races(),
                                  path=_GREPTSAN_BASELINE)
    if fresh:
        print("\n" + "=" * 70, file=sys.stderr)
        print(f"greptsan: {len(fresh)} unsuppressed data race(s) "
              f"detected during this session:", file=sys.stderr)
        for r in fresh:
            print("\n" + r.render(), file=sys.stderr)
        print("=" * 70, file=sys.stderr)
        session.exitstatus = 1
