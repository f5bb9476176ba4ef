"""chip_smoke.py on the CPU: its phases at a toy shape with the platform
check inverted, its refusals, and where the compile cache lands."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TOY = chip_smoke.Shape(n_nodes=64, n_pods=512, gang_size=8, zones=16,
                       sync_cycles=1, pipe_cycles=6, touch_nodes=2)


def test_all_phases_at_toy_shape(capsys):
    """Every phase, one chip then the 4-device mesh, on virtual CPU
    devices: parity, service, synchronous and pipelined north cycles,
    mesh bind parity."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    result = chip_smoke.run(4, seed=0, shape=TOY, platform="cpu")
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["phase"] for l in lines] == [
        "device", "parity", "service", "north_sync", "north_pipe",
        "north_sync", "north_pipe", "mesh_parity", "summary"]
    for line in lines:
        assert line["ok"] is True
        assert set(line["device"]) == {"platform", "kind", "count"}
        assert set(line["versions"]) == {"jax", "jaxlib", "libtpu"}
    pipe = lines[4]
    assert pipe["devsnap"]["delta"] >= 1
    assert ["warm", 1] in pipe["devincr_modes"]
    assert all("_scatter_rows" in n for n in pipe["compiled_in_window"])


def test_device_phase_refuses_a_cpu_backend():
    with pytest.raises(AssertionError, match="default backend is 'cpu'"):
        chip_smoke.phase_device(1)  # wants "tpu"


def test_device_phase_refuses_too_few_chips():
    with pytest.raises(AssertionError, match="need 64"):
        chip_smoke.phase_device(64, platform="cpu")


def test_device_phase_refuses_the_numpy_stand_in(monkeypatch):
    from volcano_tpu import native

    monkeypatch.setattr(native, "native_available", lambda: False)
    with pytest.raises(AssertionError, match="native bridge"):
        chip_smoke.phase_device(1, platform="cpu")


def test_entry_point_exits_nonzero_on_cpu():
    """The real entry point, as the driver runs it in a sandbox without
    a chip: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "default backend is 'cpu'" in proc.stderr


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory.
    Unset: one fixed, git-ignored directory inside the checkout."""
    import jax

    from volcano_tpu import scheduler

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(scheduler, "_compile_cache_enabled", False)
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        scheduler.enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == "sentinel"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.setattr(scheduler, "_compile_cache_enabled", False)
        scheduler.enable_compilation_cache()
        placed = Path(jax.config.jax_compilation_cache_dir)
        assert placed == scheduler.COMPILE_CACHE_DIR == ROOT / ".xla_cache"
        ignored = subprocess.run(
            ["git", "check-ignore", "-q", str(placed / "x")], cwd=ROOT)
        assert ignored.returncode == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_importing_the_package_takes_no_device():
    """No jnp call at module level: with a platform JAX cannot
    initialise, every entry module still imports.  (A process that must
    stay off the chip pins itself to the CPU in main(), after import.)"""
    env = dict(os.environ, JAX_PLATFORMS="no_such_platform")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import chip_smoke, volcano_tpu.service, "
         "volcano_tpu.solver_service, volcano_tpu.fastpath, "
         "volcano_tpu.whatif, volcano_tpu.parallel"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_no_silent_cpu_when_an_accelerator_was_asked_for(monkeypatch):
    """``tpu,cpu`` (what the chip machine sets) asks for the TPU: finding
    only the CPU behind it is refused.  ``cpu`` first is a CPU run asked
    for by name and passes."""
    import jax

    from volcano_tpu import device

    assert device.cpu_requested()
    device.require_accelerator("test")  # JAX_PLATFORMS=cpu: fine
    before = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", "tpu,cpu")
        assert not device.cpu_requested()
        with pytest.raises(RuntimeError, match="no accelerator"):
            device.require_accelerator("test")
    finally:
        jax.config.update("jax_platforms", before)
