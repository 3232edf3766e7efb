"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Production target: TPU v5e, 16x16 = 256 chips
per pod; the multi-pod mesh adds a leading "pod" axis (2 pods = 512 chips).
Every axis is ``Auto``: the models place activations with
``with_sharding_constraint`` and leave the rest to the partitioner.

The serving stack uses *host-level* meshes: :func:`make_local_mesh` for
one tensor-parallel instance, :func:`make_slice_meshes` to carve the
local devices into disjoint same-size slices (data-parallel instances x
tensor-parallel shards — the production serving topology).  On CPU CI
the local "devices" are forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_parallel: int = 1,
                    devices: Optional[Sequence] = None):
    """("data", "model") mesh over the locally available devices.

    ``devices`` overrides the device set (sub-slice construction: a
    cluster carves ``jax.devices()`` into disjoint groups and builds one
    mesh per group).  ``model_parallel`` must be a positive factor of
    the device count — a non-factor used to silently floor-divide into
    a broken (0- or short-row) mesh; now it raises.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    n = len(devs)
    if model_parallel < 1:
        raise ValueError(
            f"model_parallel must be >= 1, got {model_parallel}")
    if n == 0 or n % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide the "
            f"{n} available device(s); pick a factor of the device count "
            f"(or pass an explicit `devices=` slice)")
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), devices=devs,
                         axis_types=(AxisType.Auto,) * 2)


def make_slice_meshes(n_slices: int, model_parallel: int = 1,
                      devices: Optional[Sequence] = None) -> List:
    """Disjoint ("data", "model") sub-meshes for data-parallel serving.

    Carves the device list into ``n_slices`` consecutive groups of
    ``model_parallel`` devices each — one tensor-parallel instance per
    slice, no device shared between slices.  Raises when the device
    count cannot supply ``n_slices * model_parallel`` devices.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if n_slices < 1:
        raise ValueError(f"n_slices must be >= 1, got {n_slices}")
    need = n_slices * model_parallel
    if need > len(devs):
        raise ValueError(
            f"{n_slices} slice(s) x {model_parallel}-way model parallel "
            f"needs {need} devices; only {len(devs)} available")
    return [make_local_mesh(model_parallel,
                            devices=devs[i * model_parallel:
                                         (i + 1) * model_parallel])
            for i in range(n_slices)]
