"""Production mesh construction and the process-wide compile cache.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run must set XLA_FLAGS
before the first jax call.

Every mesh is built with ``Auto`` axis types: the model code places
activations with ``with_sharding_constraint`` (``maybe_constrain``),
which only accepts Auto axes.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

# fixed, checkout-relative: the cache path is part of the cache key, so a
# directory that moves between runs never hits
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def _auto(n: int) -> Tuple[AxisType, ...]:
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape: Tuple[int, ...],
              axis_names: Optional[Tuple[str, ...]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Arbitrary mesh over the available devices (elastic re-mesh path).

    ``devices`` restricts the mesh to an explicit subset — the recovery
    path builds the post-failure mesh from the *surviving* devices, so
    the mesh can shrink without restarting the process.
    """
    if axis_names is None:
        axis_names = ("pod", "data", "model")[-len(shape):]
    if devices is not None:
        return Mesh(np.asarray(devices).reshape(shape), axis_names,
                    axis_types=_auto(len(axis_names)))
    return jax.make_mesh(shape, axis_names,
                         axis_types=_auto(len(axis_names)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins. Otherwise the cache lives at ``<checkout>/.jax_cache``. The
    minimum compile time is lowered so the step programs (seconds to
    compile, not minutes) are cached too.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir


def describe_platform() -> str:
    """``platform=<...> device_kind=<...>`` of the default backend."""
    d = jax.devices()[0]
    return f"platform={d.platform} device_kind={d.device_kind}"
