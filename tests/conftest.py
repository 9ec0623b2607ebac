"""Shared test fixtures.  NOTE: never set xla_force_host_platform_device_count
here -- the perf benches want 1 device and multi-device tests run in
subprocesses (tests/helpers.py) with their own device count.  In-process
factored-mesh tests (tests/test_hier.py) skip unless the *environment*
provides >= 8 devices; CI sets XLA_FLAGS=--xla_force_host_platform_device_count=8
on the tier-1 step so they execute there."""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _verify_plans():
    """Statically verify every concrete plan produced by balancer.solve.

    Enables the opt-in plan-verification hook (repro.analysis.plan_check)
    for all tests: any plan-producing test that solves outside jit gets its
    conservation / placement / tier invariants checked for free.  Traced
    solves are skipped by the hook itself.
    """
    from repro.analysis import plan_check

    with plan_check.plan_verification():
        yield


@pytest.fixture
def tpu_interpret():
    """Run Pallas kernels in Pallas's TPU interpreter.

    The kernels always lower for the TPU; a CPU test that calls one asks
    for the interpreter explicitly through this fixture.
    """
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        yield
