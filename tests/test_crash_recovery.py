"""Recovery from device memory exhaustion mid-solve (the
hyperscale-affinity failure mode).

At 50k x 500k with inter-pod affinity the [E, D] count tensors can
exhaust a 16 GB chip.  The cycle must not be lost to it: the allocate
action catches the runtime's RESOURCE_EXHAUSTED error, halves the
affinity chunk budget, re-probes the device, and resumes the cycle with
the remaining pending work — completing degraded instead of failing.
These tests inject the error through a fake solver wrapper, in the form
a direct-attached v5e raised it (PR 21 chip run).
"""

import jax
import numpy as np
import pytest

import volcano_tpu.ops.wave as wave_mod
from volcano_tpu.fastpath import FastCycle
from volcano_tpu.scheduler import Scheduler
from volcano_tpu.synth import synthetic_cluster

OOM = ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
       "allocate 24.00G. That was not possible. There are 15.75G free.; "
       "(0x0x0_HBM0)")


def crashing_once(real_fn, crashes, error=None):
    """Wrap the solver: the first ``crashes`` calls raise ``error``
    (default: the runtime's out-of-memory error); later calls
    delegate."""
    state = {"left": crashes, "calls": 0}

    def fn(*args, **kw):
        state["calls"] += 1
        if state["left"] > 0:
            state["left"] -= 1
            raise error or jax.errors.JaxRuntimeError(OOM)
        return real_fn(*args, **kw)

    return fn, state


def affinity_store(seed=0):
    return synthetic_cluster(
        n_nodes=48, n_pods=192, gang_size=4, zones=4,
        affinity_fraction=0.2, anti_affinity_fraction=0.1,
        spread_fraction=0.1, seed=seed,
    )


def test_cycle_completes_after_injected_crash(monkeypatch):
    store = affinity_store()
    real = wave_mod.solve_wave
    fake, state = crashing_once(real, crashes=1)
    monkeypatch.setattr(wave_mod, "solve_wave", fake)
    Scheduler(store).run_once()
    assert state["calls"] >= 2  # crashed once, then resumed
    bound = [p for p in store.pods.values() if p.node_name]
    assert len(bound) == len(store.pods)  # cycle completed degraded
    # Budget degraded and the recovery is user-visible.
    assert store._aff_budget_scale == 0.5
    evs = store.events_for("Scheduler/device")
    assert any(e["reason"] == "DeviceCrashRecovered" for e in evs)


def test_repeated_crashes_eventually_propagate(monkeypatch):
    """More than 3 crashes in one cycle give up (health machinery takes
    over) instead of looping forever."""
    store = affinity_store()
    real = wave_mod.solve_wave
    fake, state = crashing_once(real, crashes=99)
    monkeypatch.setattr(wave_mod, "solve_wave", fake)
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        Scheduler(store).run_once()
    assert store._aff_budget_scale <= 0.25


def test_programming_errors_are_not_swallowed(monkeypatch):
    """Only the out-of-memory error triggers recovery; a genuine bug
    propagates immediately (no silent degradation)."""
    store = affinity_store()
    real = wave_mod.solve_wave
    fake, state = crashing_once(
        real, crashes=1, error=RuntimeError("name 'x' is not defined"))
    monkeypatch.setattr(wave_mod, "solve_wave", fake)
    monkeypatch.setenv("VOLCANO_TPU_FALLBACK", "never")
    with pytest.raises(RuntimeError, match="not defined"):
        Scheduler(store).run_once()
    assert getattr(store, "_aff_budget_scale", 1.0) == 1.0


def test_budget_scale_recovers_after_clean_cycles(monkeypatch):
    from volcano_tpu.api import GROUP_NAME_ANNOTATION, Pod, PodGroup

    store = affinity_store()
    real = wave_mod.solve_wave
    fake, state = crashing_once(real, crashes=1)
    monkeypatch.setattr(wave_mod, "solve_wave", fake)
    sched = Scheduler(store)
    sched.run_once()
    assert store._aff_budget_scale == 0.5
    # Fresh pending AFFINITY work each cycle: only affinity-bearing
    # solves count toward walking the degraded budget back up.
    for i in range(FastCycle._SCALE_RECOVER_AFTER):
        pg = PodGroup(name=f"late-{i}", min_member=1)
        store.add_pod_group(pg)
        store.add_pod(Pod(
            name=f"late-{i}-0",
            annotations={GROUP_NAME_ANNOTATION: pg.name},
            containers=[{"cpu": "1", "memory": "1Gi"}],
            topology_spread=[("zone", 10)],
        ))
        sched.run_once()
    # The degraded budget walked back up after the clean streak.
    assert store._aff_budget_scale == 1.0


def test_crash_classification_is_by_type_and_status_name():
    E = jax.errors.JaxRuntimeError
    assert FastCycle._is_device_crash(E(OOM))
    # Other runtime statuses are not known to be survivable.
    assert not FastCycle._is_device_crash(E("INTERNAL: core halted"))
    assert not FastCycle._is_device_crash(E("UNAVAILABLE: Socket closed"))
    # The status name in some other error's text is not a match.
    assert not FastCycle._is_device_crash(RuntimeError(OOM))
    assert not FastCycle._is_device_crash(
        E("INTERNAL: while handling RESOURCE_EXHAUSTED"))
    assert not FastCycle._is_device_crash(KeyboardInterrupt(OOM))
