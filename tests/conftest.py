"""Test environment: force JAX onto a virtual 8-device CPU mesh so the
multi-chip sharding paths compile and run without TPU hardware.
JAX_PLATFORMS=cpu is the one way to choose the CPU; the chip itself is
exercised by chip_smoke.py, and tests/test_tpu_compile.py compiles the
kernels for a described (not attached) v5e.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# authoritative even if a plugin imported jax before this file ran
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# hand-written stand-ins for the reference's example apps
# (tests/data/reference/application: simple, open_local, more_pods)
REFERENCE_EXAMPLES = os.path.join(os.path.dirname(__file__), "data", "reference")


@pytest.fixture(autouse=True)
def _deterministic_names():
    from open_simulator_tpu.models.workloads import reset_name_counter

    reset_name_counter()
    yield


@pytest.fixture(autouse=True)
def _fresh_io_state():
    # per-endpoint circuit breakers are process-global (runtime/retry);
    # one test's deliberately dead endpoint must not fail-fast another's
    from open_simulator_tpu.runtime.retry import reset_io_state

    reset_io_state()
    yield


# ---- chaos-matrix artifact (CI uploads it per PR) -------------------
# SIMON_CHAOS_MATRIX_OUT=<path> collects per-cell outcomes from the
# chaos suites into one machine-readable JSON artifact.

_CHAOS_FILES = (
    "tests/test_chaos_matrix.py",
    "tests/test_inject.py",
    "tests/test_torn_tail.py",
    "tests/test_serve_hardening.py",
)
_chaos_outcomes = []


def pytest_runtest_logreport(report):
    if report.when != "call":
        return
    if any(report.nodeid.startswith(f) for f in _CHAOS_FILES):
        _chaos_outcomes.append(
            {
                "cell": report.nodeid,
                "outcome": report.outcome,
                "seconds": round(report.duration, 3),
            }
        )


def pytest_sessionfinish(session):
    out = os.environ.get("SIMON_CHAOS_MATRIX_OUT")
    if not out or not _chaos_outcomes:
        return
    import json

    with open(out, "w") as f:
        json.dump(
            {
                "cells": _chaos_outcomes,
                "total": len(_chaos_outcomes),
                "passed": sum(
                    1 for c in _chaos_outcomes if c["outcome"] == "passed"
                ),
                "failed": sum(
                    1 for c in _chaos_outcomes if c["outcome"] == "failed"
                ),
            },
            f,
            indent=2,
        )


@pytest.fixture(autouse=True)
def _inject_disarmed():
    # the chaos injector is process-global (runtime/inject); a test
    # that died with a spec armed must not fault every later test
    from open_simulator_tpu.runtime.inject import INJECT
    from open_simulator_tpu.serve.admission import reset_tenant_registry

    INJECT.clear()
    reset_tenant_registry()
    yield
    INJECT.clear()
