"""Which device this process computes on, and start-up checks on it.

JAX registers the TPU backend as fail-quietly: when libtpu cannot
initialise (no chip, or another process holds it), ``JAX_PLATFORMS`` is
unset and JAX has not recognised a TPU VM (where it sets
``jax_platforms`` to ``tpu,cpu`` itself and fails loudly), the process
carries on on the host CPU with an INFO log line.  For a scheduler whose
hot path was built for the chip that is a silent change of system, so
``vtpu-solver`` and ``hack/endurance.py`` call ``require_accelerator``
before they build anything, and the results ``chip_smoke.py`` and
``hack/endurance.py`` print carry ``device_info()``.

A chip belongs to one process.  Under ``vtpu-service --remote-solver``
the solver child owns it; the service process still runs its small
auxiliary kernels (``gang_block_fit``, ``frag_scores``, victim scoring,
the crash probe) through JAX, so it pins itself to the host CPU with
``keep_off_accelerator`` instead of racing the child for the chip.
"""

from __future__ import annotations

import logging
import os

log = logging.getLogger(__name__)


def cpu_requested() -> bool:
    """True when the operator named the CPU as the backend to compute on:
    it comes first in ``JAX_PLATFORMS`` / the live ``jax_platforms``
    config (the test suite and the virtual-mesh dry runs set both).
    ``tpu,cpu`` asks for the TPU, with the CPU only as host platform."""
    import jax

    asked = (jax.config.jax_platforms
             or os.environ.get("JAX_PLATFORMS", ""))
    return asked.split(",")[0].strip().lower() == "cpu"


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def versions() -> dict:
    """jax / jaxlib / libtpu versions (libtpu: None when not installed)."""
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def require_accelerator(who: str) -> None:
    """Fail at start when the default backend is the host CPU and
    nobody asked for it.  A CPU run asked for by name returns before any
    backend initialises (the caller may still want virtual devices)."""
    if cpu_requested():
        return
    if device_info()["platform"] == "cpu":
        raise RuntimeError(
            f"{who}: JAX found no accelerator and fell back to the host "
            "CPU (is the chip held by another process?).  Set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose."
        )


def keep_off_accelerator(who: str) -> None:
    """Pin this process to the host CPU, and say so.  Must run before
    anything initialises a JAX backend; raises when the process already
    holds an accelerator."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    info = device_info()
    if info["platform"] != "cpu":
        raise RuntimeError(
            f"{who}: a JAX backend was initialised before the process "
            f"could be pinned to the CPU; it holds {info}")
    log.warning(
        "%s: this process stays OFF the accelerator (jax_platforms=cpu); "
        "its auxiliary kernels run on the host CPU and the chip belongs "
        "to the solver process", who,
    )
