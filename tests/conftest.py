"""Shared pytest configuration.

``REPRO_REQUIRE_HYPOTHESIS=1`` turns the hypothesis shim into a hard
collection failure: several property suites (test_fault_residue,
test_kernels_assign) degrade gracefully to a seeded-parametrize sweep
when hypothesis is not installed, which is the right behavior for the
minimal container — but silently wrong for the CI *full* lane, whose
whole point is to run the property suites as property tests. The full
lane sets the variable (after installing requirements-dev.txt), so a
broken dev-install fails loudly at collection time instead of quietly
downgrading coverage.

The suite runs on the CPU, so the Pallas kernels run in the interpreter:
``REPRO_PALLAS_INTERPRET=1`` asks ``repro.kernels.ops`` for it (without it the
kernel wrappers raise off-TPU).
"""
from __future__ import annotations

import os

import pytest

os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")


def pytest_configure(config: pytest.Config) -> None:
    if os.environ.get("REPRO_REQUIRE_HYPOTHESIS") != "1":
        return
    try:
        import hypothesis  # noqa: F401
    except ImportError:
        raise pytest.UsageError(
            "REPRO_REQUIRE_HYPOTHESIS=1 but hypothesis is not importable: "
            "the property suites would silently fall back to the "
            "seeded-parametrize shim. Install requirements-dev.txt (the CI "
            "full lane does) or unset the variable.")
