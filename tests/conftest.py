"""Test configuration: force a virtual 8-device CPU platform for JAX.

Multi-chip sharding is validated on a virtual CPU mesh
(xla_force_host_platform_device_count), matching how the driver dry-runs the
multi-chip path; the run on real chips is chip_smoke.py.  The override
logic is shared with __graft_entry__.dryrun_multichip via
volcano_tpu.virtualcpu.
"""

import os

import pytest

from volcano_tpu.virtualcpu import force_virtual_cpu_platform

force_virtual_cpu_platform(8)

# Fast-path exceptions must FAIL tests, not silently fall back to the
# object session (a fastpath bug could otherwise hide behind green
# tests that pass via the fallback).  Tests that exercise the fallback
# behavior itself override this with monkeypatch.setenv(..., "auto").
os.environ.setdefault("VOLCANO_TPU_FALLBACK", "never")

# The legacy preempt/reclaim suites (test_preempt_reclaim,
# test_evict_oracle, test_reclaim_multiqueue, ...) assert the reference
# host-walk semantics bind-for-bind against the object path; the
# device-native plan-prove-commit lane (ISSUE 11, volcano_tpu/whatif.py)
# is new semantics and its suites opt in explicitly with
# monkeypatch.setenv("VOLCANO_TPU_EVICT_DEVICE", "1").  Outside tests
# the device lane is the default.
os.environ.setdefault("VOLCANO_TPU_EVICT_DEVICE", "0")


@pytest.fixture(scope="module", autouse=True)
def _drop_jit_caches_between_modules():
    """One process runs ~850 tests and compiles thousands of XLA:CPU
    executables; with all of them kept alive, jaxlib 0.9.0 segfaults
    inside backend_compile_and_load a little past half way (compiling
    ops/allocate.py's sequential solver in test_oracle_parity or
    test_parallel).  Dropping the jit caches after each module keeps the
    live set small, and the whole tier-1 line runs to the end."""
    yield
    import jax

    jax.clear_caches()
