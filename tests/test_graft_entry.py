"""The driver's gate, run in-suite.

Rounds 1 and 2 failed the driver's multichip dryrun while 490 tests passed,
because the suite ran with x64 on and the dryrun runs with it off. This test
executes the driver entry points verbatim in the suite's (now x64-off)
regime so that divergence is structurally impossible.
"""

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    avg_cpu, max_mem, cnt, counts = out
    assert avg_cpu.shape == (graft.NUM_GROUPS,)
    assert int(np.asarray(counts).sum()) == len(args[0])


def test_driver_dryrun_multichip_verbatim():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the conftest 8-device virtual CPU mesh")
    assert not jax.config.jax_enable_x64  # the regime the driver uses
    graft._dryrun_impl(8)


def test_dryrun_multichip_refuses_more_devices_than_exist():
    """No quiet re-run on a virtual CPU mesh: asked for more devices than
    the machine has, the dry run raises and names what it found."""
    n = len(jax.devices())
    with pytest.raises(RuntimeError, match=rf"has {n} device"):
        graft.dryrun_multichip(n + 1)
