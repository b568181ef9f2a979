"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (jax locks the device count on first init).
"""
from __future__ import annotations

import os
import re
from typing import Dict, Optional

import jax

from repro.configs.base import MeshConfig

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def host_device_env(n_devices: int,
                    base_env: Optional[Dict[str, str]] = None
                    ) -> Dict[str, str]:
    """Env dict for a child process simulating an ``n_devices`` host mesh.

    Rewrites only the device-count flag inside ``XLA_FLAGS`` so any other
    flags already present (e.g. set by a CI matrix cell for the parent)
    survive into the child. The parent's own device count is untouched —
    jax locks it on first init, which is why multi-device measurement is
    subprocess-spawned at all (see bench/runner.run_with_devices).

    The child is pinned to the CPU (``JAX_PLATFORMS=cpu``): it is a
    sharding rehearsal on host devices, and on a machine with an
    accelerator it must not reach for a chip the parent may hold.
    """
    env = dict(os.environ if base_env is None else base_env)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith(_DEVICE_COUNT_FLAG)]
    flags.append(f"{_DEVICE_COUNT_FLAG}={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def simulated_device_count(env: Optional[Dict[str, str]] = None
                           ) -> Optional[int]:
    """The host-platform device count forced via ``XLA_FLAGS``, if any.
    Reads the env (not jax) so it works before jax initializes."""
    flags = (os.environ if env is None else env).get("XLA_FLAGS", "")
    m = re.search(re.escape(_DEVICE_COUNT_FLAG) + r"=(\d+)", flags)
    return int(m.group(1)) if m else None


def _mk(shape, axes, devices=None):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices)


def set_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    if multi_pod:
        return MeshConfig(shape=(2, 16, 16), axes=("pod", "data", "model"))
    return MeshConfig(shape=(16, 16), axes=("data", "model"))


def make_mesh(mesh_cfg: MeshConfig, devices=None):
    """Build a jax Mesh for an arbitrary MeshConfig (tests use small ones)
    over ``devices`` (default: all of them)."""
    return _mk(mesh_cfg.shape, mesh_cfg.axes, devices)
