"""Virtual CPU platform override, shared by tests/conftest.py and
``__graft_entry__.dryrun_multichip``.

Multi-chip sharding is validated on a virtual N-device CPU mesh
(``xla_force_host_platform_device_count``), matching how the driver
dry-runs the multi-chip path without N real chips.  ``JAX_PLATFORMS`` is
read into the jax config when jax is first imported, which may be before
this runs, so setting the env vars is not enough: the live jax config is
overridden too.

This module intentionally imports jax only inside the function, so callers
can set the env vars before jax's first import when they are early enough
(conftest is; a driver calling ``dryrun_multichip`` may not be — the
post-import config update covers that case, and the final device-count
check catches the one unrecoverable ordering: jax already *initialized*
with too few devices).
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"


def force_virtual_cpu_platform(n_devices: int = 8) -> None:
    """Pin JAX to the virtual-CPU platform with >= ``n_devices`` devices.

    Raises RuntimeError if jax was already initialized with fewer virtual
    CPU devices than requested (the override can then no longer take
    effect in this process).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(rf"--{_FLAG}=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + f" --{_FLAG}={n_devices}").strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = re.sub(
            rf"--{_FLAG}=\d+", f"--{_FLAG}={n_devices}", flags
        )
    os.environ.setdefault("JAX_ENABLE_X64", "0")

    import jax

    jax.config.update("jax_platforms", "cpu")
    try:
        cpus = jax.devices("cpu")
    except RuntimeError as e:
        # Backends already initialized TPU-only: jax raises its own
        # "Unknown backend cpu" with no hint at the real problem.
        raise RuntimeError(
            "jax backends were initialized before the virtual-CPU "
            "platform override could take effect — call "
            "force_virtual_cpu_platform (or dryrun_multichip) in a "
            f"fresh process (underlying error: {e})"
        ) from e
    if len(cpus) < n_devices:
        raise RuntimeError(
            f"virtual CPU platform has {len(cpus)} devices, need "
            f"{n_devices}; jax was initialized before the platform "
            "override could take effect — call force_virtual_cpu_platform "
            "(or dryrun_multichip) in a fresh process"
        )
