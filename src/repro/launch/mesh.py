"""Production mesh construction (dry-run contract, DESIGN.md §6).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; the dry-run sets the 512-device XLA flag before
any jax import and only then calls it.
"""
from __future__ import annotations

import os
import re

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for unit tests (requires xla_force_host_platform_device_count)."""
    return _make_mesh(shape, axes)


def make_data_mesh(num_shards: int):
    """1-D ``("data",)`` mesh over the first ``num_shards`` local devices —
    the mesh search loop's layout (``run_search_multi_sharded``).  Built
    from an explicit device subset so a search can use fewer shards than
    the host exposes (``jax.make_mesh`` insists on all of them)."""
    import numpy as np

    devices = jax.devices()
    if len(devices) < num_shards:
        raise ValueError(
            f"need {num_shards} devices for a {num_shards}-way data mesh, "
            f"have {len(devices)} (under JAX_PLATFORMS=cpu, set "
            "--xla_force_host_platform_device_count)"
        )
    return jax.sharding.Mesh(
        np.asarray(devices[:num_shards]).reshape(num_shards), ("data",)
    )


def virtual_devices_allowed() -> bool:
    """True iff the caller pinned JAX to the CPU (``JAX_PLATFORMS=cpu``) —
    the only case in which a mesh may be made of virtual host devices.
    Decided from the environment alone, without touching JAX, so a parent
    never takes an accelerator that a child would need."""
    return os.environ.get("JAX_PLATFORMS", "") == "cpu"


def _forced_device_count() -> int | None:
    m = re.search(
        r"--xla_force_host_platform_device_count=(\d+)",
        os.environ.get("XLA_FLAGS", ""),
    )
    return int(m.group(1)) if m else None


def virtual_device_env(num_devices: int) -> dict:
    """Environment for a child process that runs on ``num_devices`` virtual
    CPU devices.  Existing ``XLA_FLAGS`` are appended to, never clobbered.

    Raises ``RuntimeError`` unless the caller set ``JAX_PLATFORMS=cpu``:
    on any other platform a mesh too large for the host is an error, never
    a silent move to the CPU."""
    if not virtual_devices_allowed():
        raise RuntimeError(
            f"need {num_devices} devices; virtual CPU devices are only used "
            "when JAX_PLATFORMS=cpu is set"
        )
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags + " " if flags else "") + (
        f"--xla_force_host_platform_device_count={num_devices}"
    )
    return env


def ensure_host_devices(num_shards: int, *, argv=None) -> None:
    """Make sure this process can build a ``num_shards``-device mesh.

    Under ``JAX_PLATFORMS=cpu`` the device count is read from
    ``XLA_FLAGS`` without touching JAX; when it is short, this re-execs a
    child with ``--xla_force_host_platform_device_count`` (the flag must
    precede the child's first jax import) and exits with the child's
    code.  A device-count flag already present acts as the repeat guard.
    ``argv`` overrides the child command line (e.g. ``[sys.executable,
    "-m", "pkg.mod", ...]`` for ``-m`` entry points); default re-runs
    ``sys.argv`` as a script.

    On any other platform the accelerators are what there is: too few of
    them raises ``RuntimeError`` naming the count needed.  Returns
    normally iff enough devices are available in THIS process.
    """
    import subprocess
    import sys

    if virtual_devices_allowed():
        forced = _forced_device_count()
        if forced is None and num_shards > 1:
            sys.exit(subprocess.call(
                argv or [sys.executable] + sys.argv,
                env=virtual_device_env(num_shards),
            ))
        if (forced or 1) < num_shards:
            raise RuntimeError(
                f"need {num_shards} devices, XLA_FLAGS forces {forced}"
            )
        return
    have = len(jax.devices())
    if have < num_shards:
        raise RuntimeError(
            f"need {num_shards} devices for a {num_shards}-way mesh, this "
            f"host has {have} {jax.devices()[0].platform} device(s); set "
            "JAX_PLATFORMS=cpu to run on virtual CPU devices instead"
        )


def describe(mesh) -> str:
    return f"mesh{tuple(mesh.devices.shape)} axes={mesh.axis_names}"
