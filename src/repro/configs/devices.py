"""Device setup: platform, host-device count, meshes and the compile cache.

Tests that need a *multi-device* mesh on a CPU-only host call
:func:`setup_devices` before anything touches the jax backend:

    from repro.configs import setup_devices
    setup_devices(platform="cpu", n_devices=8)

which forces XLA to expose ``n_devices`` host devices (the
``--xla_force_host_platform_device_count`` idiom), pins the platform and
optionally flips fp64 on — so a laptop and CI lower the exact same
sharded decode program as an 8-chip slice. Called without a platform it
pins nothing and returns whatever devices jax finds (a TPU where one is
attached). The call is idempotent for the same arguments and fails
loudly when the backend was already initialised with a different device
count (jax reads these knobs once).

:func:`make_mesh` is the one mesh constructor of the repository, and
:func:`enable_compile_cache` the one place the persistent compilation
cache is configured.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Sequence

_FORCE_FLAG = "--xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: fixed per-checkout cache path (the path is part of the cache key, so it
#: must not move between runs); listed in .gitignore
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_devices(platform: str | None = None, n_devices: int | None = None,
                  use_x64: bool = False) -> list:
    """Configure platform / device count / precision, returning the devices.

    Must run before the first jax computation. ``platform`` is pinned only
    when given. ``n_devices`` only has an effect on the host (CPU)
    platform, where XLA is told to expose that many independent devices —
    the standard recipe for exercising real GSPMD partitioning in unit
    tests.
    """
    if platform == "cpu" and n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        parts = [p for p in flags.split() if not p.startswith(_FORCE_FLAG)]
        parts.append(f"{_FORCE_FLAG}={int(n_devices)}")
        os.environ["XLA_FLAGS"] = " ".join(parts)

    import jax

    if platform is not None:
        jax.config.update("jax_platform_name", platform)
    jax.config.update("jax_enable_x64", bool(use_x64) or
                      bool(int(os.getenv("JAX_ENABLE_X64", "0") or 0)))

    devices = jax.devices()
    if n_devices is not None and len(devices) != int(n_devices):
        raise RuntimeError(
            f"requested {n_devices} {platform or 'default'} devices but the "
            f"backend exposes {len(devices)} — setup_devices() must be "
            f"called before jax initialises (import repro.configs first, or "
            f"set XLA_FLAGS={_FORCE_FLAG}={n_devices} in the environment)")
    return devices


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is left for jax to use.
    Otherwise the cache goes to ``<checkout>/.jax_cache``. Entry points
    that compile for the device call this (``chip_smoke.py``, the
    launchers, the examples, the daemon worker); tests do not.
    """
    path = os.environ.setdefault(_CACHE_ENV, str(DEFAULT_CACHE_DIR))
    jax = sys.modules.get("jax")
    if jax is not None:   # already imported: the env var was read at import
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_mesh(shape: Sequence[int], axis_names: Sequence[str], *,
              devices: Sequence | None = None):
    """Mesh with Auto axes, which ``models.common.shard``'s
    ``with_sharding_constraint`` requires (``jax.make_mesh`` defaults to
    Explicit axes)."""
    import jax
    from jax.sharding import AxisType

    names = tuple(axis_names)
    return jax.make_mesh(tuple(int(s) for s in shape), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_serving_mesh(data: int = 1, model: int = 1,
                      axis_names: Sequence[str] = ("data", "model")):
    """(data, model) mesh over the visible devices."""
    return make_mesh((data, model), axis_names)
