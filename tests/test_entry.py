"""Driver-contract tests: __graft_entry__ must keep working.

Round-1 lesson (VERDICT #1): the driver's multi-chip dryrun failed on device
pinning while the suite stayed green, because nothing tested the driver-facing
entry points. These tests exercise exactly what the driver runs: ``entry()``
traceability and ``dryrun_multichip(8)`` end-to-end on the CPU mesh.
"""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


@pytest.mark.slow  # ResNet-50 trace+lower is minutes-scale on 1 core
def test_entry_traces():
    fn, args = graft.entry()
    # The driver compile-checks single-chip; tracing catches API breakage
    # without paying a full ResNet-50 CPU compile in the suite.
    lowered = jax.jit(fn).lower(*args)
    assert lowered is not None


def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


def test_dryrun_multichip_raises_on_too_few_devices():
    with pytest.raises(RuntimeError, match="found"):
        graft.dryrun_multichip(len(jax.devices()) + 1)
